"""repro.distributed: mesh resolution, the grid partitioner, the
execution-only fingerprint contract, and — in 8-virtual-device
subprocesses — the mesh-invariance + cross-mesh cache contract and the
racing Hogwild! parity against the sequential staleness oracle."""

import dataclasses
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synth
from repro.distributed import (element_plan, get_mesh, layout,
                               pad_to_multiple, resolve, run_grid_sharded)
from repro.distributed import mesh as mesh_mod
from repro.experiments import engine
from repro.experiments.spec import (DatasetSpec, JobSpec, SweepSpec,
                                    EXECUTION_ONLY_FIELDS, fingerprint)


# ---------------------------------------------------------------------------
# mesh resolution
# ---------------------------------------------------------------------------

def test_get_mesh_auto_and_overrides():
    auto = get_mesh()
    assert auto.n_devices == len(jax.devices())
    assert get_mesh("auto").n_devices == auto.n_devices
    one = get_mesh(1)
    assert one.n_devices == 1
    assert "fallback" in one.describe()
    assert resolve(None) is None                 # None = "not requested"
    assert resolve(one) is one                   # passthrough
    with pytest.raises(ValueError):
        get_mesh(0)
    # over-subscription clamps with a one-shot warning, never raises
    # (graceful degradation — results are mesh-invariant anyway)
    mesh_mod._CLAMP_WARNED = False
    with pytest.warns(RuntimeWarning, match="clamping"):
        clamped = get_mesh(len(jax.devices()) + 1)
    assert clamped.n_devices == len(jax.devices())
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # second ask: silent (one-shot)
        assert get_mesh(len(jax.devices()) + 5).n_devices == len(
            jax.devices())
    mesh_mod._CLAMP_WARNED = False


# ---------------------------------------------------------------------------
# partitioner
# ---------------------------------------------------------------------------

def test_pad_to_multiple():
    assert pad_to_multiple(5, 4) == 8
    assert pad_to_multiple(8, 4) == 8
    assert pad_to_multiple(1, 8) == 8


def test_element_plan_layout():
    # bucket positions (1, 3) of ms, 2 seeds, 4 devices: 4 real elements
    m_idx, s_idx, n_real = element_plan((1, 3), [1, 2, 4, 8], 2, 4)
    assert n_real == 4 and len(m_idx) == 4
    assert list(m_idx) == [2, 2, 8, 8] and list(s_idx) == [0, 1, 0, 1]
    # 3 members x 1 seed on 4 devices pads by repeating element 0
    m_idx, s_idx, n_real = element_plan((0, 1, 2), [1, 2, 4], 1, 4)
    assert n_real == 3 and len(m_idx) == 4
    assert list(m_idx) == [1, 2, 4, 1] and list(s_idx) == [0, 0, 0, 0]


def test_layout_rule():
    # seeds that divide evenly over the devices are split over them...
    for n_seeds, n_devices in ((8, 4), (4, 4), (8, 8), (16, 8), (6, 2),
                               (1, 1), (3, 1)):
        assert layout(n_seeds, n_devices) == "seed", (n_seeds, n_devices)
    # ...anything else flattens (m, seed) elements and pads
    for n_seeds, n_devices in ((1, 4), (2, 4), (3, 8), (6, 4), (2, 8),
                               (5, 4)):
        assert layout(n_seeds, n_devices) == "element", (n_seeds, n_devices)


@dataclasses.dataclass(frozen=True)
class _FourWide(mesh_mod.DeviceMesh):
    """A one-device mesh the partitioner plans for as four devices: the
    layout rule and the element padding see four, the arrays land on the
    one device there is."""

    @property
    def n_devices(self):
        return 4


def test_run_grid_sharded_matches_direct_eval():
    """The partitioner's layout choice, pad/reshape/scatter bookkeeping
    and seed axis, with an analytic sim (3 'evals' encoding m, the seed's
    draw and m_pad) planned for four devices: 1 and 3 seeds take the
    element plan and pad it, 4 and 8 split their seeds and pad nothing."""
    from repro.distributed import partition
    from repro.telemetry import trace

    ms = [1, 2, 3, 4, 6, 8]
    dmesh = _FourWide(get_mesh(1).mesh)
    counted = partition._MESH_BUCKETS

    for n_seeds in (1, 3, 4, 8):
        def make_sim(m_pad):
            def sim(m, data, sub):
                return jnp.stack([m.astype(jnp.float32), sub,
                                  jnp.float32(m_pad)])
            # seed s's draw is 10 s, so a row names its seed
            return sim, (), 10.0 * jnp.arange(n_seeds, dtype=jnp.float32)

        how = layout(n_seeds, 4)
        assert how == ("seed" if n_seeds in (4, 8) else "element")
        for buckets in (engine._buckets(ms),
                        [(tuple(range(len(ms))), max(ms))]):
            before = {k: c.value for k, c in counted.items()}
            tracer = trace.start()
            try:
                out = np.asarray(run_grid_sharded(
                    make_sim, ms, n_seeds, dmesh, buckets))
            finally:
                trace.stop()
            assert {k: c.value - before[k] for k, c in counted.items()} \
                == {k: len(buckets) * (k == how) for k in counted}
            spans = [e["args"] for e in tracer.events
                     if e["name"] == "mesh_bucket"]
            real = [len(pos) * n_seeds for pos, _ in buckets]
            assert [a["layout"] for a in spans] == [how] * len(buckets)
            assert [a["elements"] for a in spans] == (
                real if how == "seed"
                else [pad_to_multiple(r, 4) for r in real])
            pad_of = {i: m_pad for pos, m_pad in buckets for i in pos}
            if n_seeds == 1:
                assert out.shape == (len(ms), 3)
                for i, m in enumerate(ms):
                    assert list(out[i]) == [m, 0, pad_of[i]]
            else:
                assert out.shape == (len(ms), n_seeds, 3)
                for i, m in enumerate(ms):
                    for s in range(n_seeds):
                        assert list(out[i, s]) == [m, 10 * s, pad_of[i]]


# ---------------------------------------------------------------------------
# execution never enters result identity
# ---------------------------------------------------------------------------

def _tiny_spec(**over):
    base = dict(
        name="dist_tiny", description="distributed unit spec",
        ms=(1, 2), iters=40, eval_every=20,
        datasets={"d0": DatasetSpec("higgs_like", {"n": 120, "d": 8})},
        jobs=(JobSpec("minibatch", "d0"),))
    base.update(over)
    return SweepSpec(**base).validate()


def test_fingerprint_excludes_devices():
    assert "devices" in EXECUTION_ONLY_FIELDS
    fps = {fingerprint(_tiny_spec(devices=d))
           for d in (None, 1, 8, "auto")}
    assert len(fps) == 1
    # ...but a computational field still splits the key
    assert fingerprint(_tiny_spec(iters=80)) not in fps


def test_spec_devices_validation_and_roundtrip():
    with pytest.raises(ValueError, match="devices"):
        _tiny_spec(devices=0)
    with pytest.raises(ValueError, match="devices"):
        _tiny_spec(devices="all")
    spec = _tiny_spec(devices="auto")
    assert SweepSpec.from_dict(spec.to_dict()) == spec
    # pre-ENGINE_VERSION-5 artifact spec dicts (no devices key) still load
    d = spec.to_dict()
    del d["devices"]
    assert SweepSpec.from_dict(d).devices is None


def test_cache_hit_served_without_resolving_devices(tmp_path):
    """An artifact cached anywhere must serve on a host that cannot
    satisfy the spec's `devices` ask — the mesh resolves only on a miss,
    and the persisted spec dict drops execution-only fields."""
    import json

    from repro.experiments import runner

    spec = _tiny_spec()
    r = runner.run_sweep(spec, cache_dir=str(tmp_path))
    assert r["cache"]["hit"] is False
    assert "devices" not in r["spec"]                # execution-only
    persisted = json.load(open(r["cache"]["path"]))
    assert "devices" not in persisted["spec"]
    # same fingerprint, but an unsatisfiable mesh request: must NOT raise
    big = dataclasses.replace(spec, devices=len(jax.devices()) + 7)
    r2 = runner.run_sweep(big, cache_dir=str(tmp_path))
    assert r2["cache"]["hit"] is True
    # ...and a fresh compute with that request degrades gracefully: the
    # mesh clamps to the host (one-shot warning) instead of raising
    mesh_mod._CLAMP_WARNED = False
    with pytest.warns(RuntimeWarning, match="clamping"):
        r3 = runner.run_sweep(big, cache_dir=str(tmp_path), force=True)
    mesh_mod._CLAMP_WARNED = False
    assert r3["cache"]["hit"] is False
    assert r3["execution"]["devices"] == len(jax.devices())


def test_sweep_hogwild_sharded_any_grid():
    """The racing-mode sweep aligns each m's eval cadence to its round
    boundaries, so grids with m not dividing eval_every just work and
    every row has the same number of evals."""
    from repro.distributed import sweep_hogwild_sharded

    ds = synth.make_higgs_like(jax.random.PRNGKey(0), n=150, d=8)
    tr, te = ds.split(key=jax.random.PRNGKey(0))
    r = sweep_hogwild_sharded(tr, te, [1, 2, 3], iters=120, eval_every=40)
    assert np.asarray(r["losses"]).shape == (3, 3)
    assert np.isfinite(r["losses"]).all()


def test_engine_single_device_mesh_is_bitexact():
    ds = synth.make_higgs_like(jax.random.PRNGKey(0), n=200, d=10)
    tr, te = ds.split(key=jax.random.PRNGKey(0))
    kw = dict(iters=60, eval_every=20)
    for algo in ("minibatch", "hogwild"):
        r0 = engine.sweep(algo, tr, te, [1, 2, 4], **kw)
        r1 = engine.sweep(algo, tr, te, [1, 2, 4], mesh=1, **kw)
        assert np.array_equal(np.asarray(r0["losses"]),
                              np.asarray(r1["losses"]))


# ---------------------------------------------------------------------------
# the contract, for real: 8 virtual host devices in a subprocess
# ---------------------------------------------------------------------------

def _run_sub(body, timeout):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, numpy as np
        assert len(jax.devices()) == 8
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], cwd=".",
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    return r.stdout


MESH_INVARIANCE = """
    from repro.data import synth
    from repro.experiments import engine
    from repro.telemetry import metrics

    ds = synth.make_higgs_like(jax.random.PRNGKey(0), n=400, d=16)
    tr, te = ds.split(key=jax.random.PRNGKey(0))
    ms = [1, 2, 4, 8]
    laid = {how: metrics.counter("repro_mesh_buckets_total",
                                 labels={"layout": how})
            for how in ("seed", "element")}
    # deterministic-arithmetic algorithms: sweep scale, with seed axis;
    # 1, 2 and 3 seeds take the element plan on 8 devices, 8 seeds split
    for algo, n_seeds, iters in (("minibatch", 3, 400), ("hogwild", 3, 400),
                                 ("dadm", 1, 400), ("ecd_psgd", 2, 60),
                                 ("dadm", 8, 400), ("minibatch", 8, 400)):
        kw = dict(iters=iters, eval_every=iters // 4, n_seeds=n_seeds)
        r1 = engine.sweep(algo, tr, te, ms, **kw)
        j0 = engine.JIT_CALLS
        l0 = {how: c.value for how, c in laid.items()}
        r8 = engine.sweep(algo, tr, te, ms, mesh=8, **kw)
        compiles = engine.JIT_CALLS - j0
        a = np.asarray(r1.get("losses_seeds", r1["losses"]))
        b = np.asarray(r8.get("losses_seeds", r8["losses"]))
        d = float(np.abs(a - b).max())
        assert d <= 1e-5, (algo, d)
        # one compile per bucket per mesh, seed axis included
        n_buckets = len(engine._buckets(ms)) if (
            engine.alg_base.get_algorithm(algo).bucketed_default
            and not engine.alg_base.get_algorithm(algo).force_flat) else 1
        assert compiles == n_buckets, (algo, compiles, n_buckets)
        # every bucket dispatched once, in the layout the seeds choose
        how = "seed" if n_seeds == 8 else "element"
        counts = {k: c.value - l0[k] for k, c in laid.items()}
        assert counts == {k: n_buckets * (k == how) for k in laid}, (
            algo, n_seeds, counts)
        print(algo, n_seeds, "invariant", d, "compiles", compiles, how)
    print("MESH_INVARIANCE_OK")
"""


CACHE_CROSS_MESH = """
    import tempfile, json, glob
    from repro.experiments import registry, runner

    spec = registry.get_spec("variance_sparsity", quick=True, iters=60,
                             n=200)
    with tempfile.TemporaryDirectory() as cd:
        r1 = runner.run_sweep(spec, cache_dir=cd, mesh=1)
        assert r1["cache"]["hit"] is False
        assert r1["execution"] == {"devices": 1, "sharded": False,
                                   "backend": "cpu"}
        art1 = open(r1["cache"]["path"]).read()
        r8 = runner.run_sweep(spec, cache_dir=cd, mesh=8)
        assert r8["cache"]["hit"] is True          # 1-device sweep serves 8
        assert r8["execution"]["devices"] == 8
    with tempfile.TemporaryDirectory() as cd:
        r8 = runner.run_sweep(spec, cache_dir=cd, mesh=8)
        assert r8["cache"]["hit"] is False and r8["execution"]["sharded"]
        art8 = open(r8["cache"]["path"]).read()
        r1 = runner.run_sweep(spec, cache_dir=cd, mesh=1)
        assert r1["cache"]["hit"] is True          # ...and vice versa
    p1, p8 = json.loads(art1), json.loads(art8)
    assert p1["fingerprint"] == p8["fingerprint"]
    # volatile keys never persist: artifacts carry no mesh trace at all
    assert "cache" not in p1 and "execution" not in p1
    assert "cache" not in p8 and "execution" not in p8
    assert "devices" not in p1["spec"] and "devices" not in p8["spec"]
    print("CACHE_CROSS_MESH_OK")
"""


HOGWILD_RACE = """
    from repro.data import synth
    from repro.core.algorithms import run_hogwild
    from repro.distributed import run_hogwild_sharded

    ds = synth.make_higgs_like(jax.random.PRNGKey(0), n=400, d=16)
    tr, te = ds.split(key=jax.random.PRNGKey(0))
    kw = dict(m=8, iters=1600, gamma=0.05, eval_every=200)
    oracle = np.asarray(run_hogwild(tr, te, **kw)["losses"])

    # m == devices, sync_every=1: the race IS the staleness recurrence —
    # every round's gradients read the last round boundary, exactly the
    # oracle's tau=(j%m)+1 structure, so curves match to summation order
    race = run_hogwild_sharded(tr, te, mesh=8, **kw)
    assert race["devices"] == 8
    d = float(np.abs(np.asarray(race["losses"]) - oracle).max())
    assert d <= 1e-5, d
    print("parity", d)

    # widening the sync window makes the shards genuinely race ahead on
    # stale parameters: trajectories must now DIVERGE from the oracle
    # (that is the point of the mode) while still optimizing
    stale = run_hogwild_sharded(tr, te, mesh=8, sync_every=4, **kw)
    sd = float(np.abs(np.asarray(stale["losses"]) - oracle).max())
    assert sd > 1e-4, sd
    assert np.isfinite(stale["losses"]).all()
    assert stale["losses"][-1] < stale["losses"][0]
    print("stale divergence", sd)

    # any m on any mesh: padded worker slots are inert
    odd = run_hogwild_sharded(tr, te, m=6, iters=600, gamma=0.05,
                              eval_every=60, mesh=8)
    assert np.isfinite(odd["losses"]).all()
    print("HOGWILD_RACE_OK")
"""


@pytest.mark.slow
def test_mesh_invariance_subprocess():
    """1 vs 8 host devices: identical curves (<=1e-5), 1 compile/bucket."""
    out = _run_sub(MESH_INVARIANCE, timeout=420)
    assert "MESH_INVARIANCE_OK" in out


@pytest.mark.slow
def test_cache_cross_mesh_subprocess():
    """A sweep cached on 1 device is a hit on 8 (and vice versa); the
    persisted artifacts share the fingerprint and carry no mesh trace."""
    out = _run_sub(CACHE_CROSS_MESH, timeout=420)
    assert "CACHE_CROSS_MESH_OK" in out


@pytest.mark.slow
def test_hogwild_race_subprocess():
    """Racing Hogwild!: parity with the oracle at m==D/sync_every=1,
    genuine divergence at a wider sync window."""
    out = _run_sub(HOGWILD_RACE, timeout=420)
    assert "HOGWILD_RACE_OK" in out


TRACED_RACE = """
    from repro.data import synth
    from repro.distributed import hogwild_shards, run_hogwild_sharded
    from repro.telemetry import metrics, trace
    from repro.telemetry.recorder import RECORDER

    ds = synth.make_higgs_like(jax.random.PRNGKey(0), n=400, d=16)
    tr, te = ds.split(key=jax.random.PRNGKey(0))
    kw = dict(m=8, iters=800, gamma=0.05, eval_every=200, sync_every=2,
              mesh=8)

    base = run_hogwild_sharded(tr, te, **kw)

    c0 = metrics.REGISTRY.counter(
        "repro_distributed_psum_rounds_total").value
    RECORDER.clear()
    trace.start()
    traced = run_hogwild_sharded(tr, te, **kw)
    tracer = trace.stop()

    # the observational contract under shard_map + donated buffers:
    # the traced race runs the same jit call, so the curves are exactly
    # equal
    np.testing.assert_array_equal(np.asarray(traced["losses"]),
                                  np.asarray(base["losses"]))

    # the psum counter keeps its host-side accounting while traced
    delta = metrics.REGISTRY.counter(
        "repro_distributed_psum_rounds_total").value - c0
    assert delta == traced["psum_rounds"], (delta, traced["psum_rounds"])

    # the race span holds its program's compile phases, and the fetch
    # that waits for the device follows it
    evs = tracer.events
    races = [e for e in evs if e["name"] == "race"]
    assert len(races) == 1
    r = races[0]
    assert r["args"]["m"] == 8 and r["args"]["devices"] == 8
    assert r["args"]["sync_every"] == 2
    inside = [e["name"] for e in evs
              if e is not r and e["ts"] >= r["ts"] - 1e-6
              and e["ts"] + e["dur"] <= r["ts"] + r["dur"] + 1e-6
              and e["args"]["depth"] == r["args"]["depth"] + 1]
    for child in ("jaxpr_trace", "lower", "compile"):
        assert child in inside, (child, inside)
    assert "execute" not in inside
    assert any(e["name"] == "fetch" and e["ts"] >= r["ts"] + r["dur"]
               for e in evs)

    # and the recorder mirrored both the span and the race event
    snap = RECORDER.snapshot()
    assert any(s["name"] == "race" for s in snap["spans"])
    race_events = [e for e in snap["events"] if e["kind"] == "race"]
    assert race_events and \\
        race_events[-1]["psum_rounds"] == traced["psum_rounds"]
    print("TRACED_RACE_OK")
"""


@pytest.mark.slow
def test_traced_race_subprocess():
    """Tracing the racing path on 8 virtual devices: exactly-equal
    curves, the race span's compile-phase children and the fetch after
    it, and live psum accounting — telemetry survives shard_map +
    donation."""
    out = _run_sub(TRACED_RACE, timeout=420)
    assert "TRACED_RACE_OK" in out
