"""repro.resilience: deterministic fault injection (FaultSpec streams wired
into Hogwild!, local SGD, and the racing shards) and engine fault tolerance
(crash journal + resume, retry/status accounting, checksummed artifacts).

The determinism contract under test (docs/robustness.md):

* a zero-rate FaultSpec is BIT-exact with ``fault=None`` on every wired
  algorithm — the fault path costs nothing when clean;
* a fixed fault seed makes faulted sweeps bit-reproducible, and the fault
  schedule is shared across seed replicates (environment, not experiment
  randomness);
* fault kwargs are computational: they split the artifact fingerprint;
* a sweep killed mid-run resumes from its crash journal and produces a
  byte-identical artifact; corrupted artifacts quarantine, diverged and
  failed jobs carry a ``status`` and stay out of every readout.
"""

import dataclasses
import json
import os
import signal
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.analysis import fit
from repro.data import synth
from repro.experiments import cache as artifact_cache
from repro.experiments import engine, runner
from repro.experiments.spec import (DatasetSpec, EpsilonSpec, JobSpec,
                                    SweepSpec, fingerprint)
from repro.resilience import FaultSpec, faults, journal

KEY = jax.random.PRNGKey(0)

#: drop + sign-flip at rates strong enough to visibly move curves
FAULT = {"drop_rate": 0.2, "corrupt_rate": 0.1,
         "corrupt_kind": "sign_flip", "seed": 3}


def _data(n=160, d=10):
    ds = synth.make_higgs_like(KEY, n=n, d=d)
    return ds.split(key=KEY)


def _tiny_spec(name="res_tiny", jobs=None, **over):
    base = dict(
        name=name, description="resilience test spec",
        ms=(1, 2), iters=40, eval_every=20,
        datasets={"d0": DatasetSpec("higgs_like", {"n": 100, "d": 8})},
        jobs=jobs if jobs is not None else (JobSpec("minibatch", "d0"),))
    base.update(over)
    return SweepSpec(**base).validate()


# ---------------------------------------------------------------------------
# FaultSpec resolution and validation
# ---------------------------------------------------------------------------

def test_fault_spec_resolution():
    assert faults.resolve(None) is None
    spec = faults.resolve(FAULT)
    assert isinstance(spec, FaultSpec)
    assert spec.drop_rate == 0.2 and spec.seed == 3
    assert faults.resolve(spec) == spec           # passthrough, validated
    assert spec.to_dict()["corrupt_kind"] == "sign_flip"


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="drop_rate"):
        faults.resolve({"drop_rate": 1.5})
    with pytest.raises(ValueError, match="corrupt_rate"):
        faults.resolve(FaultSpec(corrupt_rate=-0.1))
    with pytest.raises(ValueError, match="corrupt_kind"):
        faults.resolve({"corrupt_rate": 0.1, "corrupt_kind": "bitrot"})
    with pytest.raises(ValueError, match="straggle_rounds"):
        faults.resolve(FaultSpec(straggle_rounds=0))
    with pytest.raises(ValueError):               # unknown field
        faults.resolve({"dropp_rate": 0.2})
    with pytest.raises(TypeError):
        faults.resolve("drop everything")


def test_fault_stream_is_seeded_and_shaped():
    spec = faults.resolve({"drop_rate": 0.5, "straggle_rate": 0.25,
                           "seed": 11})
    s1 = faults.make_stream(spec, (64, 4))
    s2 = faults.make_stream(spec, (64, 4))
    assert set(s1) == {"drop", "dup", "straggle", "corrupt"}
    for k in s1:
        assert s1[k].shape == (64, 4)
        np.testing.assert_array_equal(s1[k], s2[k])     # deterministic
    other = faults.make_stream(dataclasses.replace(spec, seed=12), (64, 4))
    assert not np.array_equal(s1["drop"], other["drop"])
    # zero-rate channels are exactly all-zero, rate channels roughly match
    assert float(np.asarray(s1["dup"]).sum()) == 0.0
    assert 0.3 < float(np.asarray(s1["drop"]).mean()) < 0.7


# ---------------------------------------------------------------------------
# zero-rate faults are bit-exact with fault=None (all three algorithms)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo,kw", [("hogwild", {"gamma": 0.05}),
                                     ("local_sgd", {"gamma": 0.1})])
def test_zero_rate_bit_exact_engine(algo, kw):
    tr, te = _data()
    clean = engine.sweep(algo, tr, te, [1, 2, 4], iters=60, eval_every=20,
                         **kw)
    zero = engine.sweep(algo, tr, te, [1, 2, 4], iters=60, eval_every=20,
                        fault={}, **kw)
    np.testing.assert_array_equal(np.asarray(clean["losses"]),
                                  np.asarray(zero["losses"]))


def test_zero_rate_bit_exact_race():
    from repro.distributed import run_hogwild_sharded

    tr, te = _data(n=200, d=8)
    kw = dict(m=4, iters=400, eval_every=100, gamma=0.05, mesh=1)
    clean = run_hogwild_sharded(tr, te, **kw)
    zero = run_hogwild_sharded(tr, te, fault={}, **kw)
    np.testing.assert_array_equal(np.asarray(clean["losses"]),
                                  np.asarray(zero["losses"]))
    assert "fault" not in clean
    # a provided spec is recorded (resolved) even when every rate is zero —
    # the record says "a fault spec was requested", not "faults happened"
    assert zero["fault"]["drop_rate"] == 0.0
    assert zero["fault"]["corrupt_rate"] == 0.0


# ---------------------------------------------------------------------------
# faulted runs: reproducible, different from clean, finite, seed-shared
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo,kw", [("hogwild", {"gamma": 0.05}),
                                     ("local_sgd", {"gamma": 0.1})])
def test_faulted_runs_reproducible_and_distinct(algo, kw):
    tr, te = _data()
    run = lambda f: engine.sweep(                     # noqa: E731
        algo, tr, te, [1, 2, 4], iters=60, eval_every=20, fault=f, **kw)
    a, b = run(FAULT), run(FAULT)
    np.testing.assert_array_equal(np.asarray(a["losses"]),
                                  np.asarray(b["losses"]))
    clean = run(None)
    assert not np.array_equal(np.asarray(a["losses"]),
                              np.asarray(clean["losses"]))
    assert np.isfinite(np.asarray(a["losses"])).all()
    reseeded = run({**FAULT, "seed": 99})
    assert not np.array_equal(np.asarray(a["losses"]),
                              np.asarray(reseeded["losses"]))


def test_fault_schedule_shared_across_seed_replicates():
    """Faults are environment, not experiment randomness: the engine's
    per-seed draw keys must not perturb the fault stream, so seed 0 of a
    multi-seed faulted run matches the single-seed faulted run (to the
    ~1-ulp fusion difference between the vmapped-over-seeds trace and the
    single trace) — while a different *fault* seed moves the curves by
    orders of magnitude more."""
    tr, te = _data()
    run = lambda **kw: engine.sweep(                  # noqa: E731
        "hogwild", tr, te, [1, 2], iters=60, eval_every=20, gamma=0.05, **kw)
    one = run(fault=FAULT)
    many = run(fault=FAULT, n_seeds=3)
    np.testing.assert_allclose(np.asarray(many["losses_seeds"])[:, 0],
                               np.asarray(one["losses"]), rtol=0, atol=1e-6)
    reseeded = run(fault={**FAULT, "seed": 99})
    assert np.abs(np.asarray(reseeded["losses"])
                  - np.asarray(one["losses"])).max() > 1e-4


def test_fingerprint_splits_on_fault_kwargs():
    def spec_with(fault):
        kw = {"gamma": 0.05}
        if fault is not None:
            kw["fault"] = fault
        return _tiny_spec(jobs=(JobSpec("hogwild", "d0", kw),))

    fps = [fingerprint(spec_with(f))
           for f in (None, FAULT, {**FAULT, "drop_rate": 0.3},
                     {**FAULT, "seed": 4})]
    assert len(set(fps)) == len(fps)              # all distinct
    assert fingerprint(spec_with(dict(FAULT))) == fps[1]   # equal spec, equal fp


# ---------------------------------------------------------------------------
# artifact checksums: quarantine on corruption, legacy artifacts still load
# ---------------------------------------------------------------------------

def test_cache_checksum_roundtrip_and_quarantine(tmp_path):
    spec = _tiny_spec(name="res_sum")
    res = runner.run_sweep(spec, cache_dir=str(tmp_path))
    path = res["cache"]["path"]
    fp = fingerprint(spec)
    payload = json.load(open(path))
    assert payload["checksum"] == artifact_cache._payload_checksum(payload)

    # hit serves normally while intact
    assert artifact_cache.load(str(tmp_path), spec.name, fp) is not None

    # hand-truncated artifact (torn write / bit rot): quarantined, miss
    raw = open(path).read()
    with open(path, "w") as f:
        f.write(raw[:len(raw) // 2])
    with pytest.warns(RuntimeWarning, match="quarantined"):
        assert artifact_cache.load(str(tmp_path), spec.name, fp) is None
    assert os.path.exists(path + ".corrupt") and not os.path.exists(path)

    # the sweep recomputes and restores a healthy artifact
    res2 = runner.run_sweep(spec, cache_dir=str(tmp_path))
    assert res2["cache"]["hit"] is False
    assert runner.run_sweep(spec, cache_dir=str(tmp_path))["cache"]["hit"]


def test_cache_checksum_detects_mutation(tmp_path):
    spec = _tiny_spec(name="res_mut")
    res = runner.run_sweep(spec, cache_dir=str(tmp_path))
    path = res["cache"]["path"]
    payload = json.load(open(path))
    job = next(iter(payload["jobs"].values()))
    job["losses"][0][0] += 1e-9                   # a single flipped value
    with open(path, "w") as f:
        json.dump(payload, f)                     # checksum left stale
    with pytest.warns(RuntimeWarning, match="checksum mismatch"):
        assert artifact_cache.load(str(tmp_path), spec.name,
                                   fingerprint(spec)) is None
    assert os.path.exists(path + ".corrupt")


def test_cache_legacy_artifact_without_checksum_loads(tmp_path):
    spec = _tiny_spec(name="res_leg")
    res = runner.run_sweep(spec, cache_dir=str(tmp_path))
    path = res["cache"]["path"]
    payload = json.load(open(path))
    payload.pop("checksum")                       # pre-checksum artifact
    with open(path, "w") as f:
        json.dump(payload, f)
    hit = runner.run_sweep(spec, cache_dir=str(tmp_path))
    assert hit["cache"]["hit"] is True


# ---------------------------------------------------------------------------
# crash journal: torn lines, resume, byte-identical artifacts
# ---------------------------------------------------------------------------

def test_journal_read_skips_torn_and_foreign_entries(tmp_path):
    path = journal.journal_path(str(tmp_path), "j", "f" * 64)
    journal.append_entry(path, "f" * 64, "good", {"x": 1.5})
    journal.append_entry(path, "0" * 64, "foreign", {"x": 2})
    with open(path, "a") as f:
        f.write('{"fingerprint": "' + "f" * 64 + '", "key": "torn')
    entries = journal.read_entries(path, "f" * 64)
    assert entries == {"good": {"x": 1.5}}
    assert journal.read_entries("/nonexistent/journal", "f" * 64) == {}
    journal.consume(path)
    assert not os.path.exists(path)
    journal.consume(path)                          # idempotent


def test_journal_resume_is_byte_identical(tmp_path, monkeypatch):
    """Crash after job 1 of 2 (simulated with a KeyboardInterrupt, which
    the retry loop must NOT swallow), then re-run: only job 2 computes,
    and the final artifact is byte-identical to an uninterrupted run's."""
    spec = _tiny_spec(
        name="res_resume",
        jobs=(JobSpec("minibatch", "d0"),
              JobSpec("hogwild", "d0", {"gamma": 0.05})),
        epsilon=EpsilonSpec(probe_m=1, frac=0.7))
    a, b = str(tmp_path / "a"), str(tmp_path / "b")

    uninterrupted = runner.run_sweep(spec, cache_dir=a)
    golden = open(uninterrupted["cache"]["path"], "rb").read()

    real = engine.sweep
    calls = []

    def crashing(*args, **kwargs):
        calls.append(args)
        if len(calls) == 2:
            raise KeyboardInterrupt("simulated SIGKILL")
        return real(*args, **kwargs)

    monkeypatch.setattr(engine, "sweep", crashing)
    with pytest.raises(KeyboardInterrupt):
        runner.run_sweep(spec, cache_dir=b)
    jpath = journal.journal_path(b, spec.name, fingerprint(spec))
    assert os.path.exists(jpath)                  # job 1 journaled
    assert len(journal.read_entries(jpath, fingerprint(spec))) == 1

    counting = []

    def counted(*args, **kwargs):
        counting.append(args)
        return real(*args, **kwargs)

    monkeypatch.setattr(engine, "sweep", counted)
    resumed = runner.run_sweep(spec, cache_dir=b)
    assert len(counting) == 1                     # only job 2 recomputed
    assert open(resumed["cache"]["path"], "rb").read() == golden
    assert not os.path.exists(jpath)              # consumed after store


def test_journal_disabled_or_uncached_writes_nothing(tmp_path):
    spec = _tiny_spec(name="res_noj")
    jpath = journal.journal_path(str(tmp_path), spec.name, fingerprint(spec))
    runner.run_sweep(spec, cache_dir=str(tmp_path), journal=False)
    runner.run_sweep(spec, use_cache=False, cache_dir=str(tmp_path))
    assert not os.path.exists(jpath)


# ---------------------------------------------------------------------------
# retry + status accounting, and unhealthy jobs staying out of readouts
# ---------------------------------------------------------------------------

def test_transient_failure_retries_to_ok(tmp_path, monkeypatch):
    spec = _tiny_spec(name="res_retry")
    real = engine.sweep
    calls = []

    def flaky(*args, **kwargs):
        calls.append(args)
        if len(calls) == 1:
            raise RuntimeError("transient device loss")
        return real(*args, **kwargs)

    monkeypatch.setattr(engine, "sweep", flaky)
    res = runner.run_sweep(spec, cache_dir=str(tmp_path),
                           retry_backoff_s=0.0)
    jr = res["jobs"]["minibatch/d0"]
    assert jr["status"] == "retried:1"
    assert runner.job_is_healthy(jr)
    assert np.isfinite(np.asarray(jr["losses"])).all()


def test_permanent_failure_becomes_structured_stub(tmp_path, monkeypatch):
    spec = _tiny_spec(name="res_fail", epsilon=EpsilonSpec(probe_m=1))

    def broken(*args, **kwargs):
        raise RuntimeError("device pool gone")

    monkeypatch.setattr(engine, "sweep", broken)
    with pytest.warns(RuntimeWarning, match="failed after 2 attempt"):
        res = runner.run_sweep(spec, cache_dir=str(tmp_path),
                               retry_backoff_s=0.0)
    jr = res["jobs"]["minibatch/d0"]
    assert jr["status"] == "failed"
    assert "device pool gone" in jr["error"]
    assert not runner.job_is_healthy(jr)
    assert "losses" not in jr and "measured_m_max" not in jr
    # the stub is cached (and served) like any result
    assert runner.run_sweep(spec, cache_dir=str(tmp_path))["cache"]["hit"]


def test_cli_exits_nonzero_on_failed_job(monkeypatch, capsys):
    """A job whose every attempt raised (a chip compile error, say) makes
    the sweep CLI exit 1 instead of reporting success over a stub."""
    from repro.experiments import run as cli

    def broken(*args, **kwargs):
        raise RuntimeError("device pool gone")

    monkeypatch.setattr(engine, "sweep", broken)
    with pytest.warns(RuntimeWarning, match="failed after"):
        rc = cli.main(["--spec", "variance_sparsity", "--quick",
                       "--iters", "40", "--n", "120", "--no-cache"])
    assert rc == 1
    assert "FAILED jobs:" in capsys.readouterr().err


def test_diverged_job_excluded_from_readouts(tmp_path):
    """A diverged cell keeps its curves and a 'diverged' status but stays
    out of the epsilon/cost readout, the predictor, and the characters
    regression — its healthy neighbor's numbers are exactly what they are
    in a sweep without the bad job."""
    good = JobSpec("minibatch", "d0", predict=True)
    # ridge curvature on wide higgs-like features (d=28) blows up at this
    # step size — the same divergent cell test_protocols pins the warning on
    bad = JobSpec("minibatch", "wide", {"gamma": 0.1}, problem="ridge",
                  label="bad")
    eps = EpsilonSpec(probe_m=1, frac=0.7)
    mixed_spec = _tiny_spec(
        name="res_mixed", jobs=(good, bad), iters=120, epsilon=eps,
        datasets={"d0": DatasetSpec("higgs_like", {"n": 100, "d": 8}),
                  "wide": DatasetSpec("higgs_like", {"n": 120, "d": 28})})
    clean_spec = _tiny_spec(name="res_clean", jobs=(good,), iters=120,
                            epsilon=eps)

    with pytest.warns(RuntimeWarning, match="non-finite"):
        mixed = runner.run_sweep(mixed_spec, cache_dir=str(tmp_path),
                                 retry_backoff_s=0.0)
    clean = runner.run_sweep(clean_spec, cache_dir=str(tmp_path))

    jr_bad = mixed["jobs"]["minibatch[bad]+ridge/wide"]
    assert jr_bad["status"] == "diverged"
    assert "losses" in jr_bad                     # curves kept for forensics
    assert "epsilon" not in jr_bad and "measured_m_max" not in jr_bad
    assert "predicted" not in jr_bad

    jr_good, jr_ref = mixed["jobs"]["minibatch/d0"], clean["jobs"]["minibatch/d0"]
    assert jr_good["status"] == "ok"
    assert jr_good["measured_m_max"] == jr_ref["measured_m_max"]
    assert jr_good["epsilon"] == jr_ref["epsilon"]

    points = fit.collect_character_points([mixed])
    assert [p["job"] for p in points] == ["minibatch/d0"]


def test_legacy_artifacts_default_to_healthy():
    assert runner.job_is_healthy({"losses": [[0.1]]})       # no status key
    assert runner.job_is_healthy({"status": "retried:2"})
    assert not runner.job_is_healthy({"status": "diverged"})
    assert not runner.job_is_healthy({"status": "failed"})


# ---------------------------------------------------------------------------
# the fault_tolerance spec + report section
# ---------------------------------------------------------------------------

def test_fault_tolerance_spec_registered():
    from repro.experiments.registry import get_spec

    spec = get_spec("fault_tolerance", quick=True)
    assert {j.algorithm for j in spec.jobs} == {"hogwild", "local_sgd"}
    rates = {j.kwargs["fault"]["straggle_rate"] for j in spec.jobs}
    assert 0.0 in rates and max(rates) == 0.5
    assert all(j.kwargs["fault"]["seed"] == 7 for j in spec.jobs)
    assert spec.epsilon.probe_m == 1              # serial probe: see builder


@pytest.mark.slow
def test_fault_tolerance_report_trend(tmp_path):
    """Acceptance: the rendered fault-tolerance section shows m_max
    degrading faster on the hi-variance character setting than on the
    duplicated lo-variance one, for both wired algorithms."""
    from repro.analysis import report, stats
    from repro.experiments.registry import get_spec

    spec = get_spec("fault_tolerance", quick=True)
    res = runner.run_sweep(spec, cache_dir=str(tmp_path))
    text = "\n".join(report.render_fault_tolerance(res))
    assert "Fault tolerance" in text and "hogwild" in text

    kept = {}
    for (algo, ds) in [("hogwild", "lo_char"), ("hogwild", "hi_char"),
                       ("local_sgd", "lo_char"), ("local_sgd", "hi_char")]:
        boots = {}
        for job in spec.jobs:
            if job.algorithm != algo or job.dataset != ds:
                continue
            rate = job.kwargs["fault"]["straggle_rate"]
            boots[rate] = stats.mmax_bootstrap(
                res["jobs"][job.key], probe_m=1, frac=0.7)["m_max"]
        kept[(algo, ds)] = boots[max(boots)] / boots[0.0]
    for algo in ("hogwild", "local_sgd"):
        assert kept[(algo, "hi_char")] < kept[(algo, "lo_char")], kept


# ---------------------------------------------------------------------------
# subprocess contracts: SIGKILL crash/resume, 8-device faulted parity
# ---------------------------------------------------------------------------

_SUB_PRELUDE = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, numpy as np
"""


def _run_sub(body, timeout, check=True):
    script = textwrap.dedent(_SUB_PRELUDE) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], cwd=".",
                       capture_output=True, text=True, timeout=timeout)
    if check:
        assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    return r


KILL_RESUME_BODY = """
    import os, signal, sys
    from repro.experiments.spec import DatasetSpec, EpsilonSpec, JobSpec, SweepSpec
    from repro.experiments import engine, runner

    spec = SweepSpec(
        name="kr", ms=(1, 2), iters=40, eval_every=20,
        datasets={"d0": DatasetSpec("higgs_like", {"n": 100, "d": 8})},
        jobs=(JobSpec("minibatch", "d0"),
              JobSpec("hogwild", "d0", {"gamma": 0.05}),
              JobSpec("local_sgd", "d0", {"gamma": 0.1})),
        epsilon=EpsilonSpec(probe_m=1, frac=0.7)).validate()

    cache_dir, mode = sys.argv[1], sys.argv[2]
    real = engine.sweep
    calls = [0]
    def wrapper(*a, **k):
        calls[0] += 1
        if mode == "kill" and calls[0] == 2:
            os.kill(os.getpid(), signal.SIGKILL)   # job 1 journaled, die
        return real(*a, **k)
    engine.sweep = wrapper
    res = runner.run_sweep(spec, cache_dir=cache_dir)
    print("CALLS", calls[0])
    print("PATH", res["cache"]["path"])
"""


@pytest.mark.slow
def test_sigkill_resume_byte_identical(tmp_path):
    """Kill a sweep with SIGKILL mid-job-2, re-run: the journal replays
    job 1, only jobs 2-3 recompute, and the artifact is byte-identical to
    an uninterrupted run's."""
    script = textwrap.dedent(_SUB_PRELUDE) + textwrap.dedent(KILL_RESUME_BODY)
    crashed_dir, control_dir = str(tmp_path / "c"), str(tmp_path / "u")

    r = subprocess.run([sys.executable, "-c", script, crashed_dir, "kill"],
                       cwd=".", capture_output=True, text=True, timeout=420)
    assert r.returncode == -signal.SIGKILL, (r.stdout, r.stderr)
    journals = [f for f in os.listdir(crashed_dir)
                if f.endswith(".journal.jsonl")]
    assert len(journals) == 1                     # the crash left a journal

    r = subprocess.run([sys.executable, "-c", script, crashed_dir, "run"],
                       cwd=".", capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "CALLS 2" in r.stdout                  # jobs 2-3 only
    resumed_path = r.stdout.split("PATH ")[1].strip()
    assert not any(f.endswith(".journal.jsonl")
                   for f in os.listdir(crashed_dir))

    r = subprocess.run([sys.executable, "-c", script, control_dir, "run"],
                       cwd=".", capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "CALLS 3" in r.stdout                  # uninterrupted: all jobs
    control_path = r.stdout.split("PATH ")[1].strip()

    assert open(resumed_path, "rb").read() == open(control_path, "rb").read()


FAULTED_PARITY_BODY = """
    from repro.data import synth
    from repro.experiments import engine
    from repro.distributed import run_hogwild_sharded

    assert len(jax.devices()) == 8
    ds = synth.make_higgs_like(jax.random.PRNGKey(0), n=400, d=16)
    tr, te = ds.split(key=jax.random.PRNGKey(0))
    FAULT = {"drop_rate": 0.25, "corrupt_rate": 0.1,
             "corrupt_kind": "sign_flip", "seed": 3}

    # racing dropped-delta vs the sequential fault oracle at m == D,
    # sync_every=1 (threefry streams are flat-identical at equal counts)
    m, iters, ev = 8, 1600, 200
    oracle = engine.sweep(
        "hogwild", tr, te, [m], iters=iters, eval_every=ev,
        gamma=0.05, fault=FAULT)
    race = run_hogwild_sharded(tr, te, m=m, iters=iters, gamma=0.05,
                               eval_every=ev, mesh=8, fault=FAULT)
    d = float(np.abs(np.asarray(oracle["losses"][0])
                     - np.asarray(race["losses"])).max())
    print("parity", d)
    assert d <= 1e-5, d
    assert race["fault"]["drop_rate"] == 0.25     # spec recorded in result

    # faulted engine sweeps stay mesh-invariant
    ms = [1, 2, 4, 8]
    for algo, kw in (("hogwild", {"gamma": 0.05}),
                     ("local_sgd", {"gamma": 0.1})):
        r1 = engine.sweep(algo, tr, te, ms, iters=400, eval_every=100,
                          n_seeds=2, fault=FAULT, **kw)
        r8 = engine.sweep(algo, tr, te, ms, iters=400, eval_every=100,
                          n_seeds=2, fault=FAULT, mesh=8, **kw)
        d = float(np.abs(np.asarray(r1["losses_seeds"])
                         - np.asarray(r8["losses_seeds"])).max())
        print("invariance", algo, d)
        assert d <= 1e-5, (algo, d)
"""


@pytest.mark.slow
def test_faulted_race_parity_and_mesh_invariance_8dev():
    out = _run_sub(FAULTED_PARITY_BODY, timeout=420).stdout
    assert "parity" in out and out.count("invariance") == 2
