"""The engine's per-process program cache: a bucket program is a pure
function of its data and draws, built once per static signature and
reused by every later sweep of the same shapes."""

import sys
import threading

import jax
import numpy as np
import pytest

from repro.data import synth
from repro.experiments import engine, runner
from repro.experiments.spec import DatasetSpec, JobSpec, SweepSpec
from repro.telemetry import metrics, trace

KW = dict(iters=40, eval_every=20)
MS = [1, 2, 4, 8]                 # 2 buckets under MAX_PAD_RATIO = 2


def lookups():
    """(hits, misses) of the engine's program cache so far."""
    return tuple(metrics.REGISTRY.counter(
        "repro_engine_program_cache_total", labels={"outcome": o}).value
        for o in ("hit", "miss"))


def split(seed, n=120, d=8):
    ds = synth.make_higgs_like(jax.random.PRNGKey(seed), n=n, d=d)
    return ds.split(key=jax.random.PRNGKey(seed + 1))


def losses(r):
    return np.asarray(r.get("losses_seeds", r["losses"]))


@pytest.mark.parametrize("algorithm,n_buckets", [("minibatch", 2),
                                                 ("dadm", 1)])
def test_same_shapes_new_data_hit_and_match_cold(algorithm, n_buckets,
                                                 cold_programs):
    """A second sweep with new data and a new key builds nothing, hits
    once per bucket, and computes what a cold program computes."""
    engine.sweep(algorithm, *split(0), MS, **KW)
    tr, te = split(7)
    key = jax.random.PRNGKey(11)
    (h0, m0), j0 = lookups(), engine.JIT_CALLS
    warm = engine.sweep(algorithm, tr, te, MS, key=key, **KW)
    h1, m1 = lookups()
    assert (h1 - h0, m1 - m0, engine.JIT_CALLS - j0) == (n_buckets, 0, 0)
    engine.clear_programs()
    cold = engine.sweep(algorithm, tr, te, MS, key=key, **KW)
    assert lookups()[1] - m1 == n_buckets
    np.testing.assert_array_equal(losses(warm), losses(cold))


@pytest.mark.parametrize("change", [
    dict(gamma=0.05), dict(lam=3e-3), dict(iters=60), dict(n_seeds=2),
    dict(ms=[1, 2, 4, 16])], ids=["gamma", "lam", "iters", "n_seeds",
                                  "m_pad"])
def test_changed_configuration_misses_and_is_not_stale(change,
                                                       cold_programs):
    """A change of a hyperparameter, the horizon, the seed count or a
    bucket's pad width builds a new program, whose losses are those of a
    cold run of the new configuration."""
    tr, te = split(0)
    engine.sweep("minibatch", tr, te, MS, **KW)
    kw = dict(KW, **change)
    ms = kw.pop("ms", MS)
    m0 = lookups()[1]
    warm = engine.sweep("minibatch", tr, te, ms, **kw)
    assert lookups()[1] - m0 >= 1
    engine.clear_programs()
    cold = engine.sweep("minibatch", tr, te, ms, **kw)
    np.testing.assert_array_equal(losses(warm), losses(cold))


def test_replaced_code_keys_a_new_program(monkeypatch, cold_programs):
    """A method or a module function replaced at run time is not served
    from a program traced before the replacement."""
    from repro.core.algorithms import ecd_psgd, minibatch

    tr, te = split(0)
    for algorithm in ("minibatch", "ecd_psgd"):
        engine.sweep(algorithm, tr, te, MS, **KW)
    monkeypatch.setattr(minibatch.Minibatch, "step",
                        lambda self, prob, data, ctx, state, batch, t: state)
    m0 = lookups()[1]
    still = losses(engine.sweep("minibatch", tr, te, MS, **KW))
    assert lookups()[1] - m0 == 2
    assert (still == still[:, :1]).all()          # no step moved a model

    real = ecd_psgd.quantize_stochastic
    monkeypatch.setattr(ecd_psgd, "quantize_stochastic",
                        lambda z, key, *, bits=8: real(z, key, bits=4))
    m0 = lookups()[1]
    coarse = losses(engine.sweep("ecd_psgd", tr, te, MS, **KW))
    assert lookups()[1] - m0 == 2
    monkeypatch.undo()
    engine.clear_programs()
    fine = losses(engine.sweep("ecd_psgd", tr, te, MS, **KW))
    assert not np.array_equal(coarse, fine)


def test_same_shape_datasets_of_one_spec_share_programs(tmp_path,
                                                        cold_programs):
    """A cold run_sweep over two same-shape datasets builds each bucket
    program once: the second dataset's job hits, and its bucket spans say
    so."""
    spec = SweepSpec(
        name="pc_two_sets", ms=(1, 2, 4), iters=40, eval_every=20,
        datasets={"a": DatasetSpec("higgs_like", {"n": 96, "d": 8}),
                  "b": DatasetSpec("higgs_like", {"n": 96, "d": 8},
                                   seed=5)},
        jobs=(JobSpec("minibatch", "a"), JobSpec("minibatch", "b")),
    ).validate()
    (h0, m0), j0 = lookups(), engine.JIT_CALLS
    tracer = trace.start()
    runner.run_sweep(spec, cache_dir=str(tmp_path / "c"))
    trace.stop()
    h1, m1 = lookups()
    assert (m1 - m0, h1 - h0, engine.JIT_CALLS - j0) == (2, 2, 2)
    cached = [e["args"]["cached"] for e in tracer.events
              if e["name"] == "bucket"]
    assert cached == [False, False, True, True]


def test_threads_sweeping_same_shapes_count_exactly(cold_programs):
    """Two threads sweeping the same shapes at once get the serial
    losses; each bucket program is built exactly once, and hits plus
    misses equal the bucket dispatches."""
    inputs = [split(0), split(3)]
    serial = [losses(engine.sweep("minibatch", tr, te, MS, **KW))
              for tr, te in inputs]
    engine.clear_programs()
    (h0, m0), j0 = lookups(), engine.JIT_CALLS
    out = [None, None]
    barrier = threading.Barrier(2)

    def work(i):
        barrier.wait()
        out[i] = losses(engine.sweep("minibatch", *inputs[i], MS, **KW))

    threads = [threading.Thread(target=work, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    h1, m1 = lookups()
    assert (m1 - m0, h1 - h0, engine.JIT_CALLS - j0) == (2, 2, 2)
    for got, want in zip(out, serial):
        np.testing.assert_array_equal(got, want)


def test_program_lookups_exact_under_thread_stress(cold_programs):
    """Sixteen threads look up four signatures 200 times each, with a
    short switch interval: each signature is built once, every thread
    gets that one program, and hits plus misses equal the lookups."""
    keys = [("stress", k) for k in range(4)]
    got = {k: set() for k in keys}
    (h0, m0), j0 = lookups(), engine.JIT_CALLS

    def work():
        for r in range(200):
            key = keys[r % len(keys)]
            program, _ = engine._program(key, lambda: (lambda x: x))
            got[key].add(id(program))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    h1, m1 = lookups()
    assert (m1 - m0, engine.JIT_CALLS - j0) == (4, 4)
    assert (h1 - h0) + (m1 - m0) == 16 * 200
    assert all(len(ids) == 1 for ids in got.values())


def test_cache_is_bounded_least_recently_used(monkeypatch, cold_programs):
    """Past PROGRAM_CACHE_SIZE the least recently used program goes; a
    sweep that needs it again builds it again."""
    monkeypatch.setattr(engine, "PROGRAM_CACHE_SIZE", 1)
    tr, te = split(0)
    engine.sweep("hogwild", tr, te, MS, **KW)          # one flat program
    engine.sweep("dadm", tr, te, MS, **KW)             # evicts hogwild's
    m0 = lookups()[1]
    engine.sweep("dadm", tr, te, MS, **KW)
    assert lookups()[1] == m0
    engine.sweep("hogwild", tr, te, MS, **KW)
    assert lookups()[1] == m0 + 1


def test_program_cache_counter_is_exported():
    text = metrics.REGISTRY.render_prometheus(prefix="repro_engine")
    for outcome in ("hit", "miss"):
        assert (f'repro_engine_program_cache_total{{outcome="{outcome}"}}'
                in text)
