"""Every file the cells name exists and loads."""

import json
import os
import subprocess
import sys

import pytest

from harness import cells

BENCHMARK = cells.benchmark()
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("cfg", BENCHMARK["configs"],
                         ids=lambda c: c["name"])
def test_config_round_trips_through_sweepspec(cfg):
    from repro.experiments.spec import SweepSpec
    data = cells.config(cfg["name"])
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    spec = SweepSpec.from_dict(cells.spec_dict(data))
    assert SweepSpec.from_dict(spec.to_dict()) == spec
    assert json.loads(json.dumps(spec.to_dict())) == cells.spec_dict(data)
    assert data["source"] and isinstance(data["reduced"], list)


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_names_config_traffic_and_limits(name):
    w = cells.workload(name)
    assert w["chips"] in (1, 4)
    assert any(c["name"] == w["config"] for c in BENCHMARK["configs"])
    tfc = cells.traffic(w["traffic"])
    assert tfc["n_seeds"] >= 1
    assert tfc["mesh"] in (None, w["chips"])
    lim = cells.limits(name)
    spec = cells.spec_dict(cells.config(w["config"]))
    readouts = spec["epsilon"] is not None or any(
        j["predict"] for j in spec["jobs"])
    want = {"curve_gap", "characters_gap"} | (
        {"epsilon_gap", "decisions_differ"} if readouts else set())
    assert set(lim["limits"]) == want


@pytest.mark.parametrize("name", WORKLOADS)
def test_every_algorithm_has_a_count(name):
    spec = cells.spec_dict(cells.config(cells.workload(name)["config"]))
    for job in spec["jobs"]:
        assert callable(cells.algorithm_flops(job["algorithm"]).flops)


@pytest.mark.parametrize("m", BENCHMARK["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader(m):
    assert callable(cells.metric_reader(m["name"]).read)


def test_requests_draw_distinct_seeds_in_range():
    w = cells.workload(WORKLOADS[0])
    cfg, tfc = cells.config(w["config"]), cells.traffic(w["traffic"])
    seen = set()
    for k in range(4):
        spec = cells.request(cfg, tfc, 3 * 2 ** 31 + 5, k)
        seeds = tuple(d["seed"] for d in spec["datasets"].values())
        assert all(0 <= s < 2 ** 31 for s in seeds)
        seen.add(seeds + (spec["split_seed"],))
    assert len(seen) == 4
    assert cells.request(cfg, tfc, 7, 1) == cells.request(cfg, tfc, 7, 1)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(cells.BENCH / "run.py"), "--workload",
         WORKLOADS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
