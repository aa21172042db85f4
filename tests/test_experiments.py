"""repro.experiments: spec registry round-trip, vmapped-vs-sequential sweep
equivalence, artifact cache hit/miss behavior, and a CLI smoke run."""

import json

import jax
import numpy as np
import pytest

from repro.data import synth
from repro.experiments import (SPEC_IDS, DatasetSpec, EpsilonSpec, JobSpec,
                               SweepSpec, curves_by_m, fingerprint, get_spec,
                               run_sweep)
from repro.experiments import engine
from repro.experiments import run as cli
from repro.core.algorithms import (run_dadm, run_ecd_psgd, run_hogwild,
                                   run_minibatch)

KEY = jax.random.PRNGKey(0)


def tiny_spec(name="tiny", algorithms=("minibatch",), ms=(1, 2, 4),
              epsilon=None, iters=60):
    return SweepSpec(
        name=name, description="test spec", ms=ms, iters=iters, eval_every=20,
        datasets={"d0": DatasetSpec("higgs_like", {"n": 120, "d": 8})},
        jobs=tuple(JobSpec(a, "d0") for a in algorithms),
        epsilon=epsilon).validate()


# ---------------------------------------------------------------------------
# spec registry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", SPEC_IDS)
def test_registry_roundtrip(name):
    """Every registered spec survives dict/JSON round-trip bit-exactly."""
    spec = get_spec(name, quick=True)
    clone = SweepSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert clone == spec
    assert fingerprint(clone) == fingerprint(spec)


def test_fingerprint_tracks_content():
    assert fingerprint(get_spec("ls", quick=True)) != \
        fingerprint(get_spec("ls", quick=False))
    assert fingerprint(tiny_spec(iters=60)) != fingerprint(tiny_spec(iters=80))


def test_spec_validation_rejects_bad_specs():
    with pytest.raises(KeyError):
        get_spec("nope")
    with pytest.raises(ValueError):
        tiny_spec(ms=(1, 2, 2))
    with pytest.raises(KeyError):
        SweepSpec(name="x", ms=(1,), iters=40, eval_every=20,
                  datasets={}, jobs=(JobSpec("minibatch", "ghost"),)
                  ).validate()
    with pytest.raises(ValueError):   # epsilon probe_m must be on the grid
        tiny_spec(epsilon=EpsilonSpec(probe_m=3))
    with pytest.raises(ValueError):   # epsilon frac must be a proper fraction
        tiny_spec(epsilon=EpsilonSpec(probe_m=2, frac=1.0))


# ---------------------------------------------------------------------------
# engine: the vmapped grid is the sequential loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sweeper", [engine.sweep_minibatch,
                                     engine.sweep_ecd_psgd,
                                     engine.sweep_dadm,
                                     engine.sweep_hogwild])
def test_vmapped_equals_sequential(sweeper):
    ds = synth.make_higgs_like(KEY, n=160, d=10)
    tr, te = ds.split(key=KEY)
    kw = dict(iters=60, eval_every=20)
    v = sweeper(tr, te, [1, 2, 4], use_vmap=True, **kw)
    s = sweeper(tr, te, [1, 2, 4], use_vmap=False, **kw)
    assert v["ms"] == s["ms"] == [1, 2, 4]
    np.testing.assert_allclose(v["losses"], s["losses"],
                               rtol=2e-4, atol=2e-5)
    assert np.isfinite(v["losses"]).all()


def test_hogwild_sweep_matches_single_runs():
    """The vmapped one-trace Hogwild! grid reproduces the legacy per-m
    runner (the original staleness recurrence with m static) within 1e-5
    for every m of the default grid — the acceptance bar for folding
    Hogwild! into the vmapped engine."""
    ds = synth.make_higgs_like(KEY, n=160, d=10)
    tr, te = ds.split(key=KEY)
    ms = [1, 2, 4, 8]
    sw = engine.sweep_hogwild(tr, te, ms, iters=80, eval_every=20,
                              use_vmap=True)
    for m, curve in curves_by_m(sw).items():
        r = run_hogwild(tr, te, m=m, iters=80, eval_every=20)
        np.testing.assert_allclose(curve, r["losses"], rtol=1e-5)


def test_buckets_partition_properties():
    """_buckets covers every grid position once and bounds pad waste at
    MAX_PAD_RATIO x the smallest member of each bucket."""
    for ms in ([1, 2, 4, 8, 16, 32, 64], [1, 4, 16], [8, 1, 4, 2], [7],
               [3, 5, 6, 12, 13]):
        buckets = engine._buckets(ms)
        seen = sorted(i for pos, _ in buckets for i in pos)
        assert seen == list(range(len(ms)))
        for pos, m_pad in buckets:
            members = [ms[i] for i in pos]
            assert m_pad == max(members)
            assert max(members) <= engine.MAX_PAD_RATIO * min(members)


@pytest.mark.parametrize("sweeper", [engine.sweep_minibatch,
                                     engine.sweep_ecd_psgd,
                                     engine.sweep_dadm])
def test_bucketed_equals_flat(sweeper):
    """Bucketed padding must not change numerics: draws are made at the
    global m_top and sliced per bucket, so member m's computation is
    identical whichever bucket it lands in."""
    ds = synth.make_higgs_like(KEY, n=160, d=10)
    tr, te = ds.split(key=KEY)
    kw = dict(iters=60, eval_every=20)
    ms = [1, 2, 4, 8]                 # two buckets under MAX_PAD_RATIO=2
    b = sweeper(tr, te, ms, use_vmap=True, bucketed=True, **kw)
    f = sweeper(tr, te, ms, use_vmap=True, bucketed=False, **kw)
    np.testing.assert_allclose(b["losses"], f["losses"],
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("sweeper,legacy,kwname", [
    (engine.sweep_minibatch, run_minibatch, "batch_size"),
    (engine.sweep_ecd_psgd, run_ecd_psgd, "m"),
    (engine.sweep_dadm, run_dadm, "m"),
])
def test_engine_matches_legacy_at_full_m(sweeper, legacy, kwname):
    """At m == m_max the padded grid uses the same index draws as the legacy
    per-m runner (same key, same shapes, all-ones mask), so the sweep's last
    row must reproduce the original algorithm's curve almost exactly."""
    ds = synth.make_higgs_like(KEY, n=160, d=10)
    tr, te = ds.split(key=KEY)
    m_max = 4
    sw = sweeper(tr, te, [1, 2, m_max], iters=60, eval_every=20)
    r = legacy(tr, te, iters=60, eval_every=20, **{kwname: m_max})
    np.testing.assert_allclose(curves_by_m(sw)[m_max], r["losses"],
                               rtol=2e-4, atol=2e-5)


def test_engine_rejects_unknown_algorithm():
    ds = synth.make_higgs_like(KEY, n=64, d=4)
    tr, te = ds.split(key=KEY)
    with pytest.raises(KeyError):
        engine.run_algorithm_sweep("sgd9000", tr, te, [1],
                                   iters=20, eval_every=20)


# ---------------------------------------------------------------------------
# engine: the seed axis (ENGINE_VERSION 4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ["minibatch", "ecd_psgd", "dadm",
                                       "hogwild"])
def test_seeded_seed0_matches_single_seed_grid(algorithm):
    """Acceptance: an n_seeds=1 sweep is the ENGINE_VERSION-3 grid, and the
    seed-0 rows of a replicated sweep reproduce it at 1e-5."""
    ds = synth.make_higgs_like(KEY, n=160, d=10)
    tr, te = ds.split(key=KEY)
    kw = dict(iters=60, eval_every=20)
    single = engine.run_algorithm_sweep(algorithm, tr, te, [1, 2, 4], **kw)
    seeded = engine.run_algorithm_sweep(algorithm, tr, te, [1, 2, 4],
                                        n_seeds=3, **kw)
    assert single["n_seeds"] == 1 and "losses_seeds" not in single
    assert seeded["n_seeds"] == 3
    np.testing.assert_allclose(seeded["losses"], single["losses"],
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(
        [row[0] for row in seeded["losses_seeds"]], single["losses"],
        rtol=1e-5, atol=1e-7)


def test_seeded_replicates_match_independent_keyed_runs():
    """Seed s of the vmapped batch must equal a fresh single-seed sweep
    keyed with fold_in(key, s) — replicates are real independent draws,
    and growing n_seeds only appends."""
    ds = synth.make_higgs_like(KEY, n=160, d=10)
    tr, te = ds.split(key=KEY)
    kw = dict(iters=60, eval_every=20)
    seeded = engine.sweep("minibatch", tr, te, [1, 2, 4], n_seeds=3, **kw)
    for s in (1, 2):
        solo = engine.sweep("minibatch", tr, te, [1, 2, 4],
                            key=jax.random.fold_in(KEY, s), **kw)
        np.testing.assert_allclose(
            [row[s] for row in seeded["losses_seeds"]], solo["losses"],
            rtol=2e-4, atol=2e-5)


def test_seeded_grid_compiles_once_per_bucket(cold_programs):
    """Acceptance: n_seeds=8 runs as ONE vmapped trace — the jit count
    equals the bucket count, exactly as for a single seed."""
    ds = synth.make_higgs_like(KEY, n=120, d=8)
    tr, te = ds.split(key=KEY)
    kw = dict(iters=40, eval_every=20)
    ms = [1, 2, 4, 8]                     # 2 buckets under MAX_PAD_RATIO=2
    j0 = engine.JIT_CALLS
    engine.sweep("minibatch", tr, te, ms, n_seeds=1, **kw)
    single = engine.JIT_CALLS - j0
    j0 = engine.JIT_CALLS
    engine.sweep("minibatch", tr, te, ms, n_seeds=8, **kw)
    assert engine.JIT_CALLS - j0 == single == 2
    j0 = engine.JIT_CALLS
    engine.sweep("hogwild", tr, te, ms, n_seeds=8, **kw)   # force_flat
    assert engine.JIT_CALLS - j0 == 1


def test_seeded_sequential_equals_vmapped():
    ds = synth.make_higgs_like(KEY, n=120, d=8)
    tr, te = ds.split(key=KEY)
    kw = dict(iters=40, eval_every=20, n_seeds=3)
    v = engine.sweep("minibatch", tr, te, [1, 2, 4], use_vmap=True, **kw)
    s = engine.sweep("minibatch", tr, te, [1, 2, 4], use_vmap=False, **kw)
    np.testing.assert_allclose(v["losses_seeds"], s["losses_seeds"],
                               rtol=2e-4, atol=2e-5)


def test_spec_n_seeds_validation_and_fingerprint():
    base = tiny_spec()
    import dataclasses
    seeded = dataclasses.replace(base, n_seeds=4).validate()
    assert fingerprint(seeded) != fingerprint(base)   # cache key covers it
    with pytest.raises(ValueError):
        dataclasses.replace(base, n_seeds=0).validate()
    with pytest.raises(ValueError):
        engine.sweep("minibatch", None, None, [1], iters=20, eval_every=20,
                     n_seeds=0)
    # registry-level seeds override
    from repro.experiments import registry
    assert registry.get_spec("upper_bound", quick=True, seeds=5).n_seeds == 5
    # character_surface must measure §IV characters on EVERY row —
    # character_knob tiles duplicates after the unique head, so a capped
    # summary would misreport diversity to the m_max regression
    surf = registry.get_spec("character_surface", quick=True)
    assert surf.characters_rows == \
        surf.datasets[next(iter(surf.datasets))].kwargs["n"]


def test_runner_seeded_result_block(tmp_path):
    import dataclasses
    spec = dataclasses.replace(
        tiny_spec(name="tiny_seeded", algorithms=("minibatch", "hogwild"),
                  epsilon=EpsilonSpec(probe_m=2, frac=0.5)),
        n_seeds=3).validate()
    res = run_sweep(spec, cache_dir=str(tmp_path))
    for jr in res["jobs"].values():
        assert jr["n_seeds"] == 3
        block = np.asarray(jr["losses_seeds"])
        assert block.shape == (len(spec.ms), 3, 60 // 20)
        np.testing.assert_array_equal(block[:, 0], jr["losses"])
        # scalar readouts stay seed-0 / legacy-keyed
        assert jr["measured_m_max"] in spec.ms
    # the artifact round-trips the seed block through the cache
    hit = run_sweep(spec, cache_dir=str(tmp_path))
    assert hit["cache"]["hit"] is True
    assert hit["jobs"]["minibatch/d0"]["losses_seeds"] == \
        res["jobs"]["minibatch/d0"]["losses_seeds"]


# ---------------------------------------------------------------------------
# runner: epsilon/cost readout, predictions, caching
# ---------------------------------------------------------------------------

def test_epsilon_probe_clamps_to_last_eval():
    """Regression (ISSUE 2): frac == 1.0 used to index one past the end of
    the probe curve; the readout must clamp to the final eval instead."""
    from repro.experiments import runner
    job_result = {"ms": [2], "losses": [[0.9, 0.5, 0.3]]}
    eps = runner._epsilon_from_probe(job_result, EpsilonSpec(probe_m=2,
                                                             frac=1.0))
    assert eps == pytest.approx(0.3)
    # interior fractions are unchanged by the clamp
    eps = runner._epsilon_from_probe(job_result, EpsilonSpec(probe_m=2,
                                                             frac=0.5))
    assert eps == pytest.approx(0.5)


def test_runner_epsilon_cost_readout(tmp_path):
    spec = tiny_spec(algorithms=("minibatch", "hogwild"),
                     epsilon=EpsilonSpec(probe_m=2, frac=0.5))
    res = run_sweep(spec, cache_dir=str(tmp_path))
    for jr in res["jobs"].values():
        assert len(jr["costs"]) == len(spec.ms)
        assert len(jr["gain_growth"]) == len(spec.ms) - 1
        assert jr["measured_m_max"] in spec.ms
        assert np.isfinite(jr["epsilon"])


def test_runner_predictions():
    spec = SweepSpec(
        name="tiny_pred", ms=(1, 2), iters=40, eval_every=20,
        datasets={"d0": DatasetSpec("realsim_like",
                                    {"n": 100, "d": 40, "density": 0.1})},
        jobs=(JobSpec("hogwild", "d0", predict=True, predict_rows=80),)
    ).validate()
    res = run_sweep(spec, use_cache=False)
    pred = res["jobs"]["hogwild/d0"]["predicted"]
    assert pred["predicted_m_max"] >= 1
    assert res["cache"] == {"hit": False, "path": None}


def test_cache_hit_miss_and_force(tmp_path):
    spec = tiny_spec(name="tiny_cache")
    r1 = run_sweep(spec, cache_dir=str(tmp_path))
    assert r1["cache"]["hit"] is False
    r2 = run_sweep(spec, cache_dir=str(tmp_path))
    assert r2["cache"]["hit"] is True
    assert r2["jobs"]["minibatch/d0"]["losses"] == \
        r1["jobs"]["minibatch/d0"]["losses"]
    # content change -> different artifact -> miss
    r3 = run_sweep(tiny_spec(name="tiny_cache", iters=80),
                   cache_dir=str(tmp_path))
    assert r3["cache"]["hit"] is False
    # force recomputes even though the artifact exists
    r4 = run_sweep(spec, cache_dir=str(tmp_path), force=True)
    assert r4["cache"]["hit"] is False


def test_cache_artifact_is_json(tmp_path):
    spec = tiny_spec(name="tiny_json")
    r = run_sweep(spec, cache_dir=str(tmp_path))
    with open(r["cache"]["path"]) as f:
        payload = json.load(f)
    assert payload["fingerprint"] == fingerprint(spec)
    # JSON normalizes tuples to lists; the round-trip must still parse back
    assert SweepSpec.from_dict(payload["spec"]) == spec


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_list(capsys):
    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in SPEC_IDS:
        assert name in out


@pytest.mark.slow
def test_cli_smoke_quick(tmp_path, capsys):
    rc = cli.main(["--spec", "variance_sparsity", "--quick",
                   "--iters", "40", "--n", "120",
                   "--cache-dir", str(tmp_path),
                   "--json", str(tmp_path / "out.json")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "sweep variance_sparsity" in out
    assert "final loss" in out
    payload = json.loads((tmp_path / "out.json").read_text())
    assert set(payload["jobs"]) == {
        f"{a}/{d}" for d in ("higgs_like", "realsim_like")
        for a in ("minibatch", "ecd_psgd", "hogwild")}


# ---------------------------------------------------------------------------
# cache size cap (LRU) + single-flight dedup
# ---------------------------------------------------------------------------

def test_cache_cap_evicts_lru_and_warns_once(tmp_path):
    """The cap keeps the most-recently-USED artifacts (load bumps
    recency), evicts the rest, and warns exactly once per process."""
    import os
    import time
    import warnings
    from repro.experiments import cache as C

    cache_dir = str(tmp_path)
    for i in range(3):
        C.store(cache_dir, f"s{i}", f"fp{i:016d}", {"v": i})
        os.utime(C.artifact_path(cache_dir, f"s{i}", f"fp{i:016d}"),
                 (time.time() - 100 + i, time.time() - 100 + i))
    # touch s0: now s1 is the least recently used
    assert C.load(cache_dir, "s0", "fp" + "0" * 14 + "00") is not None

    C._EVICTION_WARNED = False
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        C.store(cache_dir, "s3", "fp" + "0" * 13 + "003", {"v": 3},
                max_artifacts=3)
        first = [x for x in w if issubclass(x.category, RuntimeWarning)]
        assert len(first) == 1 and "cap" in str(first[0].message)
    assert len(C.list_artifacts(cache_dir)) == 3
    assert C.load(cache_dir, "s1", "fp" + "0" * 14 + "01") is None   # evicted
    assert C.load(cache_dir, "s0", "fp" + "0" * 14 + "00") is not None

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        C.store(cache_dir, "s4", "fp" + "0" * 13 + "004", {"v": 4},
                max_artifacts=3)
        assert not [x for x in w if issubclass(x.category, RuntimeWarning)]


def test_evicted_artifact_recomputes_byte_identical(tmp_path):
    """An evicted sweep that gets requested again recomputes into the
    SAME bytes (content-addressed determinism), checksum verified."""
    spec = tiny_spec(name="lru-refetch", epsilon=EpsilonSpec(probe_m=2))
    run_sweep(spec, cache_dir=str(tmp_path))
    from repro.experiments import cache as C
    from repro.experiments.spec import fingerprint as fp_fn
    path = C.artifact_path(str(tmp_path), spec.name, fp_fn(spec))
    first = open(path, "rb").read()
    C.enforce_cap(str(tmp_path), 0)                # evict everything
    assert C.list_artifacts(str(tmp_path)) == []
    result = run_sweep(spec, cache_dir=str(tmp_path))
    assert result["cache"]["hit"] is False         # really recomputed
    assert open(path, "rb").read() == first        # byte-identical
    assert C.load(str(tmp_path), spec.name, fp_fn(spec)) is not None


def test_inflight_table_single_leader():
    import threading
    from repro.experiments.cache import InFlightTable

    table = InFlightTable()
    grants = []
    start = threading.Barrier(8)

    def race():
        start.wait()
        grants.append(table.lease("fp-x"))

    ts = [threading.Thread(target=race) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert sum(grants) == 1                        # exactly one leader
    assert table.n_inflight == 1
    table.release("fp-x")
    assert table.n_inflight == 0
    assert table.wait("fp-x", timeout=0.01)        # nothing in flight
    assert table.lease("fp-x")                     # leasable again
    table.release("fp-x")


def test_run_sweep_dedup_concurrent_single_compute(tmp_path):
    """N concurrent run_sweep(dedup=True) calls on one fingerprint:
    exactly one compute; every caller gets the same computational
    payload."""
    import threading
    from repro.experiments import cache as C
    from repro.experiments import runner as R

    spec = tiny_spec(name="dedup-conc", iters=40)
    results = []
    lock = threading.Lock()

    def go():
        r = run_sweep(spec, cache_dir=str(tmp_path), dedup=True)
        with lock:
            results.append(r)

    before = R.SWEEP_COMPUTES
    ts = [threading.Thread(target=go) for _ in range(5)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert R.SWEEP_COMPUTES - before == 1
    assert sorted(r["cache"]["hit"] for r in results) == \
        [False, True, True, True, True]
    payloads = set()
    for r in results:
        body = {k: v for k, v in r.items()
                if k not in C.VOLATILE_KEYS + ("fingerprint", "checksum")}
        payloads.add(json.dumps(body, sort_keys=True, default=float))
    assert len(payloads) == 1
