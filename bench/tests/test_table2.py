"""The Table II cells, ``table2.seq`` and ``table2.seeds8.mesh4``, on the
CPU at a shortened size: 60 iterations, ``sparse8`` cut to 600 rows (at
its configured 20,958 features and density) and ``dense`` to 20,000.

* a sound sweep of each cell, through `run_sweep` as the benchmark issues
  it (the mesh cell sharded over four devices), against the plain
  reference under the cell's own limits file: curves, characters,
  epsilon, costs and both m_max;
* on the mesh, one device's results altered after the sharded program ran
  and before the gather: ``correct`` reads every device's elements;
* the readers of ``predict_s_per_sweep`` and
  ``mesh_replicate_s_per_sweep`` on hand-made spans.

The mesh tests need four devices: run the benchmark's tests with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

import tempfile

import jax
import pytest

from harness import cells, check, reference

ITERS = 60
ROWS = 600
DENSE_ROWS = 20_000
CELLS = ["table2.seq", "table2.seeds8.mesh4"]
MESH_CELL = "table2.seeds8.mesh4"


def shortened_request(cell, seed=3 * 2 ** 31 + 11):
    w = cells.workload(cell)
    cfg = cells.config(w["config"])
    cfg["iters"], cfg["eval_every"] = ITERS, ITERS // 10
    cfg["datasets"]["sparse8"]["kwargs"]["n"] = ROWS
    cfg["datasets"]["dense"]["kwargs"]["n"] = DENSE_ROWS
    tfc = cells.traffic(w["traffic"])
    return cells.request(cfg, tfc, seed, 1), tfc


def sweep(cell):
    from repro.experiments import runner
    from repro.experiments.spec import SweepSpec
    spec, tfc = shortened_request(cell)
    with tempfile.TemporaryDirectory() as d:
        prog = runner.run_sweep(SweepSpec.from_dict(spec), cache_dir=d,
                                mesh=tfc["mesh"])
    return spec, prog


def need_devices(n):
    assert len(jax.devices()) >= n, (
        f"{n} devices needed: set XLA_FLAGS="
        f"--xla_force_host_platform_device_count={n}")


def test_configuration_is_real_sim_wide():
    cfg = cells.config("table2")
    assert cfg["datasets"]["sparse8"]["kwargs"] == {
        "n": 45193, "d": 20958, "density": 0.0025}
    assert cfg["datasets"]["dense"]["kwargs"] == {"n": 2_750_000, "d": 28}
    assert cfg["ms"] == [2, 4, 8, 16, 24]
    assert cfg["epsilon"] == {"probe_m": 2, "frac": 0.7}
    assert set(cfg["reduced"]) == {"sparse8.n", "dense.n"}
    assert set(cfg["reduced"]) <= set(cfg["assumed"])


def test_dense_size_keeps_the_reference_predictor_exact():
    """The reference sums the feature variance in float32 along the rows
    (NumPy), which drifts low as ``dense`` grows (-1.2 % at HIGGS's 11 M
    rows, enough to move mini-batch's predicted m_max); at the configured
    rows it must still give the float64 answer."""
    import numpy as np
    from repro.data import synth
    cfg = cells.config("table2")
    X = np.asarray(synth.make_higgs_like(
        jax.random.PRNGKey(7), **cfg["datasets"]["dense"]["kwargs"]).X)
    assert reference.predicted_mmax("minibatch", X) == \
        reference.predicted_mmax("minibatch", X.astype(np.float64))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_sweep_is_correct(cell):
    need_devices(cells.workload(cell)["chips"])
    spec, prog = sweep(cell)
    assert prog["datasets"]["sparse8"]["d"] == 20958
    assert prog["execution"]["sharded"] == (cells.traffic(
        cells.workload(cell)["traffic"])["mesh"] is not None)
    verdict = check.compare(prog, reference.sweep(spec), spec,
                            cells.limits(cell))
    assert verdict["ok"], verdict
    assert verdict["decisions"] > 0
    assert set(verdict["numbers"]) == {"curve_gap", "epsilon_gap",
                                       "decisions_differ", "characters_gap"}


def test_fault_on_one_device_fails_correct(monkeypatch):
    """Every loss the last device of the mesh computes is raised by 0.1 %
    on its way out of the sharded program; nothing else changes."""
    need_devices(4)
    from repro.telemetry import instrument
    real = instrument.dispatch
    last = jax.devices()[3]

    def faulty(jfn, *args, span_name="bucket", **attrs):
        out = real(jfn, *args, span_name=span_name, **attrs)
        if span_name != "mesh_bucket":
            return out
        shards = [s.data * 1.001 if s.device == last else s.data
                  for s in out.addressable_shards]
        return jax.make_array_from_single_device_arrays(
            out.shape, out.sharding, shards)
    monkeypatch.setattr(instrument, "dispatch", faulty)
    spec, prog = sweep(MESH_CELL)
    verdict = check.compare(prog, reference.sweep(spec), spec,
                            cells.limits(MESH_CELL))
    assert not verdict["ok"]
    assert verdict["numbers"]["curve_gap"]["value"] == pytest.approx(
        1e-3, rel=1e-2)


def span(name, ts, dur, depth=0, /, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur,
            "args": dict(args, depth=depth)}


def read(metric, spans, sweeps=1):
    return cells.metric_reader(metric).read({"spans": spans,
                                             "sweeps": sweeps})


SWEEP = [
    span("sweep", 0, 10e6),
    span("job", 1e6, 4e6, 1, key="dadm/sparse8"),
    span("grid", 1e6, 4e6, 2),
    span("shard_put", 1e6, 0.01e6, 3, devices=4, elements=40),
    span("replicate", 1.01e6, 0.3e6, 3, bytes=1_360_000_000, devices=4),
    span("mesh_bucket", 1.31e6, 0.1e6, 3),
    span("replicate", 2e6, 0.05e6, 3, bytes=6_400_000, devices=4),
    span("readout", 5e6, 2e6, 1, key="dadm/sparse8"),
    span("predict", 5.5e6, 1.25e6, 2, predictor="dadm", rows=600, d=20958),
    span("diversity", 5.5e6, 1.2e6, 3, rows=600, bytes=600 * 20958 * 4),
    span("readout", 7e6, 0.5e6, 1, key="hogwild/ub"),
    span("predict", 7e6, 0.25e6, 2, predictor="hogwild", rows=4000, d=400),
]


def test_predict_and_replicate_sum_their_spans():
    assert read("predict_s_per_sweep", SWEEP) == pytest.approx(1.5)
    assert read("predict_s_per_sweep", SWEEP, sweeps=2) == pytest.approx(
        0.75)
    assert read("mesh_replicate_s_per_sweep", SWEEP) == pytest.approx(0.35)


@pytest.mark.parametrize("metric", ["predict_s_per_sweep",
                                    "mesh_replicate_s_per_sweep"])
def test_nothing_to_read_gives_none(metric):
    # a sweep on one chip, or an older program, has no such spans
    assert read(metric, [span("sweep", 0, 1e6),
                         span("readout", 0, 5e5, 1)]) is None


def test_request_shards_eight_seeds_over_four_chips():
    spec, tfc = shortened_request(MESH_CELL)
    assert spec["n_seeds"] == 8 and tfc["mesh"] == 4
    assert cells.workload(MESH_CELL)["chips"] == 4
