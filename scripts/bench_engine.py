#!/usr/bin/env python
"""Engine wall-clock benchmark — emits BENCH_10.json (perf-trajectory anchor).

PR 10 adds the live observability plane (`repro.service.http`,
docs/observability.md): an HTTP transport serving the advisor plus
``GET /metrics`` / ``/healthz`` / ``/flight`` / ``/trace``, and the
always-on flight recorder the sweep publishes per-job progress events
into.  The **observability** section measures its costs: the per-publish
flight-recorder cost in isolation (a lock + deque append — the only new
always-on work on the sweep path, a handful per sweep), the
``/metrics`` and ``/flight`` scrape latencies against a live server, and
the end-to-end tax of running the full engine_default sweep *while a
scraper polls both endpoints* vs unobserved (warm jit caches, fresh
cache dir per run, interleaved and min-reduced — same protocol as the
resilience/telemetry sections).  The claim: the plane is observational —
scraping reads registry/recorder state beside the sweep, so the
concurrent-scrape tax stays within noise, and artifact bytes are
identical either way (tests/test_http.py).  The **vs_bench9** block
embeds BENCH_9's engine_default wall-clock for the non-regression
comparison; `scripts/bench_check.py` additionally gates the whole
BENCH_2..10 trajectory (docs/bench_history.md).

PR 9 adds `repro.telemetry` (docs/observability.md): span tracing plus a
process metrics registry, instrumented through the engine, runner,
distributed, and service layers.  The contract is *zero overhead when
disabled*: with no tracer installed every `trace.span(...)` returns one
shared no-op object, and the engine dispatches the jitted grid exactly
as before (a tracer adds spans from JAX's compile events, on the same
jit calls).  The **telemetry** section measures that contract: the
full engine_default sweep through `run_sweep` with tracing off vs on
(warm jit caches, fresh cache dir per run, off/on *interleaved* and
min-reduced over repeats so a slow system phase hits both labels), plus
the per-span record cost isolated.  The claims: disabled overhead < 1%
(the acceptance gate — the off path must stay within noise of the
**vs_bench8** anchor below), and the enabled tax is bounded and
reported honestly (the traced path records a span per JAX compile
event and enters a profiler annotation per span).
The **vs_bench8** block embeds BENCH_8's engine_default wall-clock for
the non-regression comparison: telemetry is observational only —
artifact bytes are identical on/off (tests/test_telemetry.py), so the
original 4-algorithm sweep must stay within noise.

PR 8 adds the advisor service (`repro.service`, docs/service.md).  The
**service** section measures its three claims on this container: (a)
*batched vs looped probe latency* — N dataset-character probes through
the slot-batched front end (one masked-batch jitted call) against N
sequential `from_dataset` calls, warm; (b) *dedup hit behavior* — N
concurrent forced escalations sharing one SweepSpec fingerprint, with
the executed-sweep count read off `runner.SWEEP_COMPUTES` (the claim is
exactly 1); (c) the *analytic-tier answer fraction* on a mixed workload
(raw high-confidence probes + spec-carrying forced escalations) — the
early-exit rate that keeps heavy traffic off the sweep engine.  The
**vs_bench7** block embeds BENCH_7's engine_default wall-clock for the
non-regression comparison: the service is a new layer over the engine
(`run_sweep` gained dedup/cache-cap paths that are no-ops by default),
so the original 4-algorithm sweep must stay within noise.

PR 7 adds crash-safe sweep execution (`repro.resilience`): the runner
journals every completed job to an fsync'd sidecar so a killed sweep
resumes from the last finished job, plus bounded retries and per-job
health status.  The **resilience** section measures what that safety
costs on the hot path: the full engine_default sweep through `run_sweep`
with journaling on vs off (warm jit caches, fresh cache dir per run, min
over repeats), where the on-path pays one append+fsync per job plus one
journal probe and unlink per sweep.  The claim: overhead < 2% of the
sweep wall-clock.  The **vs_bench6** block embeds BENCH_6's
engine_default wall-clock for the non-regression comparison — the fault
path is dormant unless a job opts in (`fault=None` compiles the
unchanged pipelines), so the original 4-algorithm sweep must stay within
noise.

PR 6 registers three critical-parameter algorithms (momentum, local_sgd,
async_svrg) against the UNCHANGED ENGINE_VERSION-5 engine.  The
**new_algorithms** section times each of them through the same generic
sweep path the paper's four take (cold, fine worker grid) and records the
jit compile count — one compile per flat grid (or per bucket), exactly
like the incumbents, because nothing algorithm-specific leaks into the
engine.  The **vs_bench5** block embeds BENCH_5's engine_default
wall-clock for the non-regression comparison: the registration-only PR
must leave the original 4-algorithm sweep within noise.

ENGINE_VERSION 5 adds device-mesh sharded execution (`repro.distributed`):
each bucket's batched (m-grid x seed) simulation can be laid over every
available XLA device with mesh-invariant results.  The **distributed**
section measures the claims: the full engine_default sweep on 1 vs N
forced host devices (each count in its own subprocess — XLA locks the
device count at first init), the jit compile count per mesh size (must
stay 1 per bucket: sharding reuses the same jitted vmap, it never
re-traces per device), and the racing-mode sharded Hogwild!
(`repro.distributed.hogwild_shards`) against the sequential staleness
oracle at the same server-iteration budget.  Host-device CPU sharding
is real parallelism (one XLA executable slice per device) but only up
to the physical core count, and a single-device run already uses every
core through intra-op threads — so on this 2-core reference container
the expected sharded wall-clock is ~parity, and the stable measured
claims are the structural ones: compile count identical on every mesh
size, results mesh-invariant (the distributed config note records the
full reasoning).

ENGINE_VERSION 4's seed axis claims are retained: (a) the seed batch
costs no extra compiles — `engine.JIT_CALLS` stays at 1 per algorithm on
a flat grid whether n_seeds is 1 or 8 — and (b) the vmapped seed batch
beats re-running the sweep once per seed.  The **seed_axis** section
measures exactly that; the older sections are retained unchanged (the
single-seed single-device path is bit-identical, so they double as a
no-regression check against BENCH_4, embedded for comparison).

Three measurements, chosen to isolate what the ENGINE_VERSION-2 rewrite
changed relative to PR 1 (all still tracked):

1. **main** — the full 4-algorithm sweep over a *fine* worker grid
   (m = 1..32, the paper's m_max-detection regime) on the dense
   higgs-like dataset, in four engine configurations:

     pr1             the PR-1 engine: flat vmapped grids for the
                     synchronous algorithms + *sequential* legacy
                     Hogwild! — one jit compile per m, because m was a
                     `static_argname` there (S compiles total)
     sequential      the masked kernels run once per m in a Python loop
                     (the equivalence-test reference path)
     vmap_flat       everything vmapped (Hogwild! included, one compile
                     for the whole grid), flat padding to max(ms)
     engine_default  the shipped ENGINE_VERSION-2 defaults: vmapped
                     everything, bucketed padding for mini-batch and
                     ECD-PSGD, flat for DADM/Hogwild!

   The headline `speedup_vs_pr1` compares engine_default against pr1;
   the dominant term is Hogwild!'s compile count dropping from S to 1.

2. **characters** — the §IV dataset-characters pipeline: PR-1's
   Python-unrolled `csim_ref` + per-batch `ls_sync_ref` vs the fused
   `lax.scan` pipeline.

3. **bucketing_regime** — ECD-PSGD (the most m-scaled sweeper: its
   quantization work grows with the padded worker axis) on a *wide*
   sparse grid at runtime-dominated scale, flat vs bucketed padding —
   the regime bucketing exists for.  On compile-dominated toy runs
   bucketing loses (extra compiles per bucket); this entry tracks the
   crossover honestly.

jit caches are cleared between configurations so every timing includes
its own compiles, as a cold run would.  Results land in BENCH_10.json at
the repo root so the perf trajectory is tracked from this PR onward.

Usage:  PYTHONPATH=src python scripts/bench_engine.py [--quick]
        (--dist-worker N is internal: re-entered in a subprocess with N
        forced host devices for the distributed section)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import jax

from repro.core import metrics as MX
from repro.data import synth
from repro.experiments import engine
from repro.experiments import run_sweep
from repro.experiments.spec import (DatasetSpec, JobSpec, SweepSpec,
                                    ENGINE_VERSION)

ALGOS = ("minibatch", "ecd_psgd", "dadm", "hogwild")
# the PR-6 critical-parameter registrations — benchmarked separately so
# the `main` section stays comparable against every earlier BENCH anchor
NEW_ALGOS = ("momentum", "local_sgd", "async_svrg")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def time_configuration(tr, te, ms, iters, eval_every, *, use_vmap, bucketed,
                       hogwild_legacy):
    """Wall-clock one full 4-algorithm sweep, cold (fresh jit caches).
    Returns (seconds, jit compile count) — every jit the engine dispatches
    here is compiled exactly once, so JIT_CALLS is the compile count."""
    jax.clear_caches()
    jits0 = engine.JIT_CALLS
    t0 = time.perf_counter()
    for algo in ALGOS:
        uv = False if (algo == "hogwild" and hogwild_legacy) else use_vmap
        engine.run_algorithm_sweep(algo, tr, te, ms, iters=iters,
                                   eval_every=eval_every, use_vmap=uv,
                                   bucketed=bucketed)
    return time.perf_counter() - t0, engine.JIT_CALLS - jits0


def time_new_algorithms(tr, te, ms, iters, eval_every):
    """Each PR-6 registration through the engine's shipped defaults, cold.

    Per-algorithm (not one lump) so a future regression is attributable;
    sequential reruns the same sweep with use_vmap=False.  The stable
    claim is the compile pattern matching the incumbents — 1 jit per
    bucket (momentum/local_sgd bucket by default, async_svrg is
    force_flat like hogwild -> exactly 1) — not a vmap speedup: at this
    compile-dominated scale the bucketed grids with per-worker state
    (local_sgd's replicas) can lose to the sequential loop, same
    crossover the bucketing_regime section tracks."""
    out = {}
    for algo in NEW_ALGOS:
        entry = {}
        for label, use_vmap in (("vmapped", True), ("sequential", False)):
            jax.clear_caches()
            jits0 = engine.JIT_CALLS
            t0 = time.perf_counter()
            engine.run_algorithm_sweep(algo, tr, te, ms, iters=iters,
                                       eval_every=eval_every,
                                       use_vmap=use_vmap)
            entry[label + "_s"] = time.perf_counter() - t0
            entry["jit_compiles_" + label] = engine.JIT_CALLS - jits0
        entry["speedup"] = entry["sequential_s"] / max(entry["vmapped_s"],
                                                       1e-9)
        out[algo] = entry
    return out


def time_characters(X, rng, batch_size):
    """PR-1 characters implementations vs the fused pipeline."""
    jax.clear_caches()
    t0 = time.perf_counter()
    MX.csim_ref(X, rng)
    MX.ls_sync_ref(X, batch_size)
    ref = time.perf_counter() - t0
    jax.clear_caches()
    t0 = time.perf_counter()
    MX.csim(X, rng)
    MX.ls_sync(X, batch_size)
    fused = time.perf_counter() - t0
    return ref, fused


def time_bucketing_regime(ms, iters, eval_every, n, d):
    """ECD-PSGD flat vs bucketed on a wide sparse grid (runtime regime)."""
    ds = synth.make_realsim_like(jax.random.PRNGKey(1), n=n, d=d,
                                 density=0.05)
    tr, te = ds.split(key=jax.random.PRNGKey(1))
    out = {}
    for label, bucketed in (("flat", False), ("bucketed", True)):
        jax.clear_caches()
        t0 = time.perf_counter()
        engine.run_algorithm_sweep("ecd_psgd", tr, te, ms, iters=iters,
                                   eval_every=eval_every, bucketed=bucketed)
        out[label] = time.perf_counter() - t0
    return out


def time_seed_axis(tr, te, ms, iters, eval_every, n_seeds):
    """seeds x m grid: one vmapped trace vs a Python loop over seeds.

    Both paths produce the same replicate curves (looped seed s uses
    fold_in(key, s), the vmapped batch's exact per-seed keys); the
    vmapped path pays ONE compile per algorithm (flat grids) regardless
    of n_seeds, while the loop re-enters the engine per seed — each entry
    builds a fresh jit wrapper, so it pays the trace + compile + dispatch
    chain every time, exactly what a pre-seed-axis caller replicating by
    hand would pay.
    """
    out = {}
    for algo in ("minibatch", "hogwild"):
        jax.clear_caches()
        jits0 = engine.JIT_CALLS
        t0 = time.perf_counter()
        engine.run_algorithm_sweep(algo, tr, te, ms, iters=iters,
                                   eval_every=eval_every, bucketed=False,
                                   n_seeds=n_seeds)
        vmapped = time.perf_counter() - t0
        vmapped_jits = engine.JIT_CALLS - jits0
        jax.clear_caches()
        jits0 = engine.JIT_CALLS
        t0 = time.perf_counter()
        key = jax.random.PRNGKey(0)
        for s in range(n_seeds):
            engine.run_algorithm_sweep(
                algo, tr, te, ms, iters=iters, eval_every=eval_every,
                bucketed=False,
                key=key if s == 0 else jax.random.fold_in(key, s))
        looped = time.perf_counter() - t0
        out[algo] = {"vmapped_s": vmapped, "looped_s": looped,
                     "speedup": looped / max(vmapped, 1e-9),
                     "jit_compiles_vmapped": vmapped_jits,
                     "jit_compiles_looped": engine.JIT_CALLS - jits0}
    return out


def dist_worker(args) -> int:
    """Subprocess body for the distributed section: time the full
    engine_default sweep and the racing Hogwild! under THIS process's
    forced device count, print one JSON line.  Runs after the parent set
    XLA_FLAGS, so jax sees exactly --dist-worker devices."""
    from repro.core.algorithms import run_hogwild
    from repro.distributed import (get_mesh, hogwild_shards,
                                   run_hogwild_sharded)

    dmesh = get_mesh()
    assert dmesh.n_devices == args.dist_worker, (
        f"XLA gave {dmesh.n_devices} devices, wanted {args.dist_worker}")
    ds = synth.make_higgs_like(jax.random.PRNGKey(0), n=args.n, d=args.d)
    tr, te = ds.split(key=jax.random.PRNGKey(0))
    ms = list(range(1, args.m_max + 1))

    jax.clear_caches()
    jits0 = engine.JIT_CALLS
    t0 = time.perf_counter()
    for algo in ALGOS:
        engine.run_algorithm_sweep(algo, tr, te, ms, iters=args.iters,
                                   eval_every=args.eval_every, mesh=dmesh)
    sweep_s = time.perf_counter() - t0
    compiles = engine.JIT_CALLS - jits0

    # the compute-dominated regime: wide features (d=400) make per-step
    # FLOPs dominate the scan's fixed per-iteration overhead, which is
    # what sharding can actually divide — the fine d=28 grid above is
    # overhead-bound (same 4000-step scan on every device) and is
    # expected NOT to speed up; this one is
    wide_iters = max(300, args.iters // 2)
    wide = synth.make_realsim_like(jax.random.PRNGKey(1), n=800, d=400,
                                   density=0.05)
    trw, tew = wide.split(key=jax.random.PRNGKey(1))
    jax.clear_caches()
    jits0 = engine.JIT_CALLS
    t0 = time.perf_counter()
    for algo in ALGOS:
        engine.run_algorithm_sweep(algo, trw, tew, ms, iters=wide_iters,
                                   eval_every=wide_iters // 5, mesh=dmesh)
    wide_s = time.perf_counter() - t0
    wide_compiles = engine.JIT_CALLS - jits0

    # racing Hogwild! throughput: m workers over the mesh vs the
    # sequential staleness oracle at the same server-iteration budget
    m = min(8, args.m_max)
    ev = m * max(1, args.eval_every // m)
    race_kw = dict(m=m, iters=args.iters, gamma=0.05, eval_every=ev)
    jax.clear_caches()
    race_jits0 = hogwild_shards.JIT_CALLS
    t0 = time.perf_counter()
    run_hogwild_sharded(tr, te, mesh=dmesh, **race_kw)
    race_s = time.perf_counter() - t0
    race_compiles = hogwild_shards.JIT_CALLS - race_jits0
    jax.clear_caches()
    t0 = time.perf_counter()
    run_hogwild(tr, te, **race_kw)
    oracle_s = time.perf_counter() - t0

    print(json.dumps({
        "devices": dmesh.n_devices,
        "engine_default_s": sweep_s,
        "jit_compiles": compiles,
        "wide_compute": {"n": 800, "d": 400, "iters": wide_iters,
                         "wall_clock_s": wide_s,
                         "jit_compiles": wide_compiles},
        "hogwild_race": {"m": m, "iters": args.iters, "race_s": race_s,
                         "jit_compiles": race_compiles,
                         "sequential_oracle_s": oracle_s,
                         "throughput_vs_oracle":
                             oracle_s / max(race_s, 1e-9)},
    }))
    return 0


def time_distributed(args, device_counts=(1, 8), repeats=2):
    """Spawn one subprocess per mesh size (the device count is locked at
    first jax init, so 1-vs-N cannot share a process).  Each mesh size
    runs ``repeats`` times and keeps the per-metric minimum — shared
    containers show large run-to-run noise, and the minimum is the least
    contaminated estimate of what the configuration can do."""
    out = {}
    for ndev in device_counts:
        # JAX_PLATFORMS=cpu: the forced host devices are the point, and
        # a child must never contend for an accelerator this process holds
        env = {**os.environ,
               "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": f"--xla_force_host_platform_device_count={ndev}",
               "PYTHONPATH": "src" + (
                   os.pathsep + os.environ["PYTHONPATH"]
                   if os.environ.get("PYTHONPATH") else "")}
        cmd = [sys.executable, os.path.abspath(__file__),
               "--dist-worker", str(ndev),
               "--n", str(args.n), "--d", str(args.d),
               "--iters", str(args.iters),
               "--eval-every", str(args.eval_every),
               "--m-max", str(args.m_max)]
        best = None
        for _ in range(repeats):
            r = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                               text=True, timeout=1800)
            if r.returncode != 0:
                raise RuntimeError(f"dist worker ({ndev} devices) failed:\n"
                                   f"{r.stderr[-2000:]}")
            j = json.loads(r.stdout.strip().splitlines()[-1])
            if best is None:
                best = j
            else:
                best["engine_default_s"] = min(best["engine_default_s"],
                                               j["engine_default_s"])
                best["wide_compute"]["wall_clock_s"] = min(
                    best["wide_compute"]["wall_clock_s"],
                    j["wide_compute"]["wall_clock_s"])
                hb, hj = best["hogwild_race"], j["hogwild_race"]
                hb["race_s"] = min(hb["race_s"], hj["race_s"])
                hb["sequential_oracle_s"] = min(hb["sequential_oracle_s"],
                                                hj["sequential_oracle_s"])
                hb["throughput_vs_oracle"] = (
                    hb["sequential_oracle_s"] / max(hb["race_s"], 1e-9))
        best["repeats"] = repeats
        out[f"devices_{ndev}"] = best
    return out


def time_resilience(ms, iters, eval_every, n, d, repeats=5):
    """run_sweep with journaling on vs off: the crash-safety tax.

    Warm jit caches (one untimed warm-up run first), a fresh cache dir
    per timed run so every run is a real compute that stores its
    artifact.  The journal's whole on-path is ~4 fsync'd appends — sub-ms
    each, measured directly below as ``append_fsync_ms`` — so the
    end-to-end delta sits far below run-to-run dispatch noise on a
    shared container; the off/on runs are *interleaved* and min-reduced
    over ``repeats`` so a slow system phase hits both labels instead of
    biasing whichever ran second."""
    from repro.resilience import journal as journal_mod

    spec = SweepSpec(
        name="bench_resilience", description="journal overhead probe",
        ms=tuple(ms), iters=iters, eval_every=eval_every,
        datasets={"d0": DatasetSpec("higgs_like", {"n": n, "d": d})},
        jobs=tuple(JobSpec(a, "d0") for a in ALGOS)).validate()
    out = {"journal_off_s": float("inf"), "journal_on_s": float("inf")}
    with tempfile.TemporaryDirectory() as root:
        run_sweep(spec, cache_dir=os.path.join(root, "warm"), journal=False)
        for r in range(repeats):
            for label, journal in (("journal_off", False),
                                   ("journal_on", True)):
                t0 = time.perf_counter()
                run_sweep(spec, cache_dir=os.path.join(root,
                                                       f"{label}{r}"),
                          journal=journal)
                out[label + "_s"] = min(out[label + "_s"],
                                        time.perf_counter() - t0)
        # the journal's actual disk cost, isolated: one durable append of
        # a representative per-job entry on this filesystem
        jpath = os.path.join(root, "probe.jsonl")
        t0 = time.perf_counter()
        for i in range(50):
            journal_mod.append_entry(jpath, "f" * 64, f"k{i}",
                                     {"losses": [[0.5] * 10] * len(ms)})
        out["append_fsync_ms"] = (time.perf_counter() - t0) / 50 * 1000
    out["overhead_frac"] = (out["journal_on_s"]
                            / max(out["journal_off_s"], 1e-9) - 1.0)
    return out


def time_telemetry(ms, iters, eval_every, n, d, repeats=5):
    """run_sweep with tracing off vs on: the observability tax.

    Same protocol as the resilience section: warm jit caches (one
    untimed warm-up), a fresh cache dir per timed run so every run is a
    real compute, off/on interleaved and min-reduced over ``repeats``.
    The *off* label is the acceptance gate (disabled overhead < 1% — the
    no-op span path plus always-on counters must be free at sweep
    granularity); the *on* label reports the enabled tax honestly: the
    traced path re-lowers each bucket once to separate compile from
    execute, so it pays ~one extra trace per bucket plus per-span
    recording, measured in isolation as ``span_record_us``."""
    from repro.telemetry import trace

    spec = SweepSpec(
        name="bench_telemetry", description="telemetry overhead probe",
        ms=tuple(ms), iters=iters, eval_every=eval_every,
        datasets={"d0": DatasetSpec("higgs_like", {"n": n, "d": d})},
        jobs=tuple(JobSpec(a, "d0") for a in ALGOS)).validate()
    out = {"trace_off_s": float("inf"), "trace_on_s": float("inf")}
    with tempfile.TemporaryDirectory() as root:
        run_sweep(spec, cache_dir=os.path.join(root, "warm"))
        for r in range(repeats):
            for label, traced in (("trace_off", False), ("trace_on", True)):
                if traced:
                    trace.start()
                try:
                    t0 = time.perf_counter()
                    run_sweep(spec,
                              cache_dir=os.path.join(root, f"{label}{r}"))
                    out[label + "_s"] = min(out[label + "_s"],
                                            time.perf_counter() - t0)
                finally:
                    if traced:
                        tracer = trace.stop()
        out["spans_per_traced_sweep"] = len(tracer.events)
    # per-span record cost, isolated: enter/exit of an attributed span
    trace.start()
    t0 = time.perf_counter()
    for i in range(10000):
        with trace.span("probe", i=i):
            pass
    out["span_record_us"] = (time.perf_counter() - t0) / 10000 * 1e6
    trace.stop()
    t0 = time.perf_counter()
    for i in range(10000):
        with trace.span("probe", i=i):
            pass
    out["noop_span_us"] = (time.perf_counter() - t0) / 10000 * 1e6
    # disabled-vs-baseline lands in vs_bench8 (this whole section already
    # runs with telemetry "off" unless trace.start() is live); on-vs-off
    # is the honest enabled tax
    out["enabled_overhead_frac"] = (out["trace_on_s"]
                                    / max(out["trace_off_s"], 1e-9) - 1.0)
    return out


def time_observability(ms, iters, eval_every, n, d, repeats=3):
    """PR-10 live observability plane: publish cost, scrape latencies,
    and the concurrent-scrape tax on a real sweep.

    Three numbers: (a) the per-event flight-recorder publish cost in
    isolation — the only new always-on work on the sweep path (a lock +
    deque append, a handful per sweep); (b) ``GET /metrics`` and
    ``GET /flight`` latency against a live `ServiceServer` (warm, min
    over 50 requests — what one scrape costs an operator); (c) the full
    engine_default sweep through `run_sweep` unobserved vs with a
    scraper thread polling both endpoints every 50 ms, interleaved and
    min-reduced over ``repeats`` — the observational claim at sweep
    granularity (scrapes read registry/recorder state beside the sweep,
    never in it)."""
    import threading
    import urllib.request

    from repro.service.http import ServiceServer
    from repro.telemetry.recorder import FlightRecorder

    out = {}
    rec = FlightRecorder()
    t0 = time.perf_counter()
    for i in range(10000):
        rec.publish("bench", i=i, job="probe")
    out["publish_us"] = (time.perf_counter() - t0) / 10000 * 1e6

    spec = SweepSpec(
        name="bench_observability", description="scrape tax probe",
        ms=tuple(ms), iters=iters, eval_every=eval_every,
        datasets={"d0": DatasetSpec("higgs_like", {"n": n, "d": d})},
        jobs=tuple(JobSpec(a, "d0") for a in ALGOS)).validate()

    with ServiceServer(None) as server:
        for path in ("/metrics", "/flight"):
            url = server.url + path
            urllib.request.urlopen(url).read()      # warm
            best = float("inf")
            for _ in range(50):
                t0 = time.perf_counter()
                urllib.request.urlopen(url).read()
                best = min(best, time.perf_counter() - t0)
            out[path.strip("/") + "_scrape_ms"] = best * 1000

        with tempfile.TemporaryDirectory() as root:
            run_sweep(spec, cache_dir=os.path.join(root, "warm"))
            out["sweep_unobserved_s"] = float("inf")
            out["sweep_scraped_s"] = float("inf")
            for r in range(repeats):
                for label, scraped in (("sweep_unobserved", False),
                                       ("sweep_scraped", True)):
                    stop = threading.Event()

                    def _scraper():
                        # a real poller: full /metrics per scrape (how
                        # Prometheus reads it), /flight tailed by cursor
                        # (how --watch reads it)
                        since = 0
                        while not stop.wait(0.05):
                            urllib.request.urlopen(
                                server.url + "/metrics").read()
                            snap = json.load(urllib.request.urlopen(
                                f"{server.url}/flight?since={since}"))
                            since = snap.get("seq", since)

                    t = threading.Thread(target=_scraper, daemon=True)
                    if scraped:
                        t.start()
                    try:
                        t0 = time.perf_counter()
                        run_sweep(spec, cache_dir=os.path.join(
                            root, f"{label}{r}"))
                        out[label + "_s"] = min(out[label + "_s"],
                                                time.perf_counter() - t0)
                    finally:
                        stop.set()
                        if scraped:
                            t.join()
    out["scrape_overhead_frac"] = (out["sweep_scraped_s"]
                                   / max(out["sweep_unobserved_s"], 1e-9)
                                   - 1.0)
    return out


def time_cache_roundtrip(ms, iters, eval_every, n, d):
    """Fresh vs cached `run_sweep` through the artifact cache."""
    spec = SweepSpec(
        name="bench_engine", description="BENCH_2 cache round-trip",
        ms=tuple(ms), iters=iters, eval_every=eval_every,
        datasets={"d0": DatasetSpec("higgs_like", {"n": n, "d": d})},
        jobs=tuple(JobSpec(a, "d0") for a in ALGOS)).validate()
    with tempfile.TemporaryDirectory() as cache_dir:
        t0 = time.perf_counter()
        r1 = run_sweep(spec, cache_dir=cache_dir)
        fresh = time.perf_counter() - t0
        t0 = time.perf_counter()
        r2 = run_sweep(spec, cache_dir=cache_dir)
        cached = time.perf_counter() - t0
    assert r1["cache"]["hit"] is False and r2["cache"]["hit"] is True
    return fresh, cached


def time_service(n_probes, n, d, sweep_iters, sweep_eval_every):
    """PR-8 advisor service: batched vs looped probe latency, single-flight
    escalation dedup, and the analytic-tier early-exit fraction.

    Latency: N dataset-character probes through the slot-batched front
    end (one masked-batch jitted call for all resident slots) vs N
    sequential `ScalabilityAdvisor.from_dataset` calls, both warm (one
    untimed warm-up each) — the claim is the batched path amortizing
    per-probe dispatch, not a FLOP win.  Dedup: N threads force-escalate
    the same SweepSpec fingerprint concurrently; `runner.SWEEP_COMPUTES`
    must rise by exactly 1 (single-flight) and every waiter must get the
    one stored artifact.  Mixed workload: raw high-confidence probes
    answer at the analytic tier while spec-carrying forced escalations
    go to the measured tier — the recorded fraction is the traffic the
    service keeps off the sweep engine entirely."""
    import threading

    import numpy as np

    from repro.core.advisor import ScalabilityAdvisor
    from repro.experiments import runner as runner_mod
    from repro.service.api import AdvisorService, ProbeRequest

    keys = jax.random.split(jax.random.PRNGKey(42), n_probes)
    Xs = [np.asarray(synth.make_higgs_like(k, n=n, d=d).X) for k in keys]
    out = {"config": {"n_probes": n_probes, "n": n, "d": d,
                      "sweep_ms": [1, 2, 4], "sweep_iters": sweep_iters}}
    with tempfile.TemporaryDirectory() as cache_dir:
        svc = AdvisorService(cache_dir=cache_dir, sweep_ms=(1, 2, 4),
                             sweep_iters=sweep_iters,
                             sweep_eval_every=sweep_eval_every)
        adv = ScalabilityAdvisor()
        svc.probe(ProbeRequest(X=Xs[0]))     # warm the batched envelope
        adv.from_dataset(Xs[0])              # warm the scalar path
        t0 = time.perf_counter()
        batched_resp = svc.probe_batch([ProbeRequest(X=X) for X in Xs])
        batched = time.perf_counter() - t0
        assert all(r.status == "ok" for r in batched_resp)
        t0 = time.perf_counter()
        for X in Xs:
            adv.from_dataset(X)
        looped = time.perf_counter() - t0
        out["probe_latency"] = {
            "batched_s": batched, "looped_s": looped,
            "speedup": looped / max(batched, 1e-9)}

        # N concurrent forced escalations of one fingerprint -> ONE sweep
        before = runner_mod.SWEEP_COMPUTES
        responses = [None] * n_probes

        def _escalated(i):
            responses[i] = svc.probe(ProbeRequest(
                dataset=DatasetSpec("higgs_like", {"n": n, "d": d}),
                escalate=True))

        threads = [threading.Thread(target=_escalated, args=(i,))
                   for i in range(n_probes)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dedup_s = time.perf_counter() - t0
        computes = runner_mod.SWEEP_COMPUTES - before
        paths = {r.escalation["artifact_path"] for r in responses}
        assert computes == 1, f"dedup leak: {computes} sweeps for 1 fp"
        assert len(paths) == 1, f"waiters got {len(paths)} artifacts"
        assert all(r.status == "ok" and r.tier == "measured"
                   for r in responses)
        t0 = time.perf_counter()
        svc.probe(ProbeRequest(
            dataset=DatasetSpec("higgs_like", {"n": n, "d": d}),
            escalate=True))
        cached_probe = time.perf_counter() - t0
        out["dedup"] = {
            "concurrent_requests": n_probes, "sweep_computes": computes,
            "wall_clock_s": dedup_s,
            "per_request_s": dedup_s / max(n_probes, 1),
            "cached_probe_s": cached_probe}

        # mixed workload: raw probes exit at the analytic tier, the two
        # spec-carrying forced escalations share one fresh fingerprint
        # (first computes, second is a cache hit inside the same batch)
        reqs = [ProbeRequest(X=X) for X in Xs]
        reqs += [ProbeRequest(
            dataset=DatasetSpec("realsim_like",
                                {"n": n, "d": d, "density": 0.05}),
            escalate=True) for _ in range(2)]
        before = runner_mod.SWEEP_COMPUTES
        t0 = time.perf_counter()
        mixed_resp = svc.probe_batch(reqs)
        mixed_s = time.perf_counter() - t0
        analytic = sum(r.tier == "analytic" for r in mixed_resp)
        out["mixed_workload"] = {
            "requests": len(reqs),
            "analytic_tier_answers": analytic,
            "analytic_fraction": analytic / len(reqs),
            "sweep_computes": runner_mod.SWEEP_COMPUTES - before,
            "wall_clock_s": mixed_s}
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--n", type=int, default=1500)
    p.add_argument("--d", type=int, default=28)
    p.add_argument("--iters", type=int, default=4000)
    p.add_argument("--eval-every", type=int, default=400)
    p.add_argument("--m-max", type=int, default=32,
                   help="main grid is every integer 1..m_max")
    p.add_argument("--quick", action="store_true",
                   help="small sizes for a fast smoke of the bench itself")
    p.add_argument("--seeds", type=int, default=8,
                   help="seed replicates for the seed_axis section")
    p.add_argument("--dist-worker", type=int, default=None,
                   help="internal: run the distributed-section worker "
                        "under this forced host device count and exit")
    p.add_argument("--out", default=None,
                   help="output path (default: BENCH_10.json at the repo "
                        "root; quick mode defaults elsewhere so a smoke "
                        "never overwrites the committed perf anchor)")
    args = p.parse_args(argv)
    if args.dist_worker is not None:
        return dist_worker(args)
    if args.quick:
        args.n, args.d, args.iters, args.eval_every = 300, 12, 400, 100
        args.m_max = 8
        args.seeds = min(args.seeds, 4)
    if args.out is None:
        args.out = (os.path.join(tempfile.gettempdir(), "BENCH_10.quick.json")
                    if args.quick else os.path.join(ROOT, "BENCH_10.json"))
    ms = list(range(1, args.m_max + 1))

    ds = synth.make_higgs_like(jax.random.PRNGKey(0), n=args.n, d=args.d)
    tr, te = ds.split(key=jax.random.PRNGKey(0))
    kw = dict(ms=ms, iters=args.iters, eval_every=args.eval_every)

    configs = {
        "pr1": dict(use_vmap=True, bucketed=False, hogwild_legacy=True),
        "sequential": dict(use_vmap=False, bucketed=False,
                           hogwild_legacy=True),
        "vmap_flat": dict(use_vmap=True, bucketed=False,
                          hogwild_legacy=False),
        # bucketed=None -> per-sweeper defaults (the shipped config)
        "engine_default": dict(use_vmap=True, bucketed=None,
                               hogwild_legacy=False),
    }
    timings, jit_counts = {}, {}
    for name, cfg in configs.items():
        timings[name], jit_counts[name] = time_configuration(
            tr, te, **kw, **cfg)
        print(f"{name:>15}: {timings[name]:7.2f} s "
              f"({jit_counts[name]} compiles)")

    new_algos = time_new_algorithms(tr, te, ms, args.iters, args.eval_every)
    for algo, r in new_algos.items():
        print(f"{algo:>15}: vmapped {r['vmapped_s']:6.2f} s "
              f"({r['jit_compiles_vmapped']} compiles)  sequential "
              f"{r['sequential_s']:6.2f} s  {r['speedup']:.2f}x")

    chars_ref, chars_fused = time_characters(
        ds.X[:min(400, args.n)], rng=args.m_max, batch_size=args.m_max)
    print(f"{'chars ref':>15}: {chars_ref:7.2f} s")
    print(f"{'chars fused':>15}: {chars_fused:7.2f} s")

    seed_axis = time_seed_axis(tr, te, ms, args.iters, args.eval_every,
                               args.seeds)
    for algo, r in seed_axis.items():
        print(f"{algo + ' seeds':>15}: vmapped {r['vmapped_s']:6.2f} s "
              f"({r['jit_compiles_vmapped']} compiles)  looped "
              f"{r['looped_s']:6.2f} s ({r['jit_compiles_looped']} "
              f"compiles)  {r['speedup']:.2f}x")

    if args.quick:
        bucket_cfg = dict(ms=[1, 2, 4, 8], iters=300, eval_every=100,
                          n=200, d=40)
    else:
        bucket_cfg = dict(ms=[1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64],
                          iters=5000, eval_every=500, n=800, d=400)
    regime = time_bucketing_regime(**bucket_cfg)
    print(f"{'ecd flat':>15}: {regime['flat']:7.2f} s")
    print(f"{'ecd bucketed':>15}: {regime['bucketed']:7.2f} s")

    fresh, cached = time_cache_roundtrip(ms, args.iters, args.eval_every,
                                         args.n, args.d)
    print(f"{'cache fresh':>15}: {fresh:7.2f} s")
    print(f"{'cache hit':>15}: {cached:7.2f} s")

    resil = time_resilience(ms, args.iters, args.eval_every,
                            args.n, args.d)
    print(f"{'journal off':>15}: {resil['journal_off_s']:7.2f} s")
    print(f"{'journal on':>15}: {resil['journal_on_s']:7.2f} s "
          f"({resil['overhead_frac'] * 100:+.2f}% overhead)")

    tel = time_telemetry(ms, args.iters, args.eval_every, args.n, args.d)
    print(f"{'trace off':>15}: {tel['trace_off_s']:7.2f} s")
    print(f"{'trace on':>15}: {tel['trace_on_s']:7.2f} s "
          f"({tel['enabled_overhead_frac'] * 100:+.2f}% enabled tax, "
          f"{tel['spans_per_traced_sweep']} spans, "
          f"{tel['span_record_us']:.1f} us/span recorded, "
          f"{tel['noop_span_us']:.2f} us/span disabled)")

    obs = time_observability(ms, args.iters, args.eval_every,
                             args.n, args.d)
    print(f"{'obs publish':>15}: {obs['publish_us']:7.2f} us/event")
    print(f"{'obs scrape':>15}: /metrics {obs['metrics_scrape_ms']:.2f} ms "
          f"/flight {obs['flight_scrape_ms']:.2f} ms")
    print(f"{'obs sweep':>15}: unobserved {obs['sweep_unobserved_s']:.2f} s "
          f"scraped {obs['sweep_scraped_s']:.2f} s "
          f"({obs['scrape_overhead_frac'] * 100:+.2f}% tax)")

    if args.quick:
        svc_cfg = dict(n_probes=6, n=192, d=12, sweep_iters=120,
                       sweep_eval_every=20)
    else:
        svc_cfg = dict(n_probes=8, n=384, d=16, sweep_iters=400,
                       sweep_eval_every=40)
    service = time_service(**svc_cfg)
    lat, dd, mx = (service["probe_latency"], service["dedup"],
                   service["mixed_workload"])
    print(f"{'svc batched':>15}: {lat['batched_s']:7.3f} s  looped "
          f"{lat['looped_s']:7.3f} s  {lat['speedup']:.2f}x")
    print(f"{'svc dedup':>15}: {dd['concurrent_requests']} concurrent "
          f"escalations -> {dd['sweep_computes']} sweep in "
          f"{dd['wall_clock_s']:.2f} s (cached refetch "
          f"{dd['cached_probe_s'] * 1000:.0f} ms)")
    print(f"{'svc mixed':>15}: {mx['analytic_tier_answers']}/"
          f"{mx['requests']} answered analytically "
          f"({mx['sweep_computes']} sweeps)")

    # mesh sizes: 1, the physical core count (the only mesh that can win
    # on CPU — intra-op parallelism can't cross scan iterations, device
    # sharding of the element axis can), and 8 (CI's forced-device size;
    # oversubscribed when cores < 8, measuring the invariance-tool regime)
    counts = ((1, 8) if args.quick
              else tuple(sorted({1, os.cpu_count() or 1, 8})))
    dist = time_distributed(args, device_counts=counts)
    d1 = dist["devices_1"]
    for key in sorted(dist):
        e = dist[key]
        print(f"{key:>15}: fine {e['engine_default_s']:6.2f} s  wide "
              f"{e['wide_compute']['wall_clock_s']:6.2f} s "
              f"({e['jit_compiles']} compiles)  hogwild race "
              f"{e['hogwild_race']['race_s']:6.2f} s "
              f"({e['hogwild_race']['throughput_vs_oracle']:.2f}x oracle)")
    dist_summary = {
        key: {"speedup_fine_vs_1dev": d1["engine_default_s"]
              / max(dist[key]["engine_default_s"], 1e-9),
              "speedup_wide_vs_1dev":
                  d1["wide_compute"]["wall_clock_s"]
                  / max(dist[key]["wide_compute"]["wall_clock_s"], 1e-9),
              "jit_compiles": dist[key]["jit_compiles"]}
        for key in dist}

    speedup = (timings["pr1"] + chars_ref) / (timings["engine_default"]
                                              + chars_fused)
    # embed the PR-4 anchor for the within-noise comparison, if present
    # (the single-seed single-device path is bit-identical)
    vs_bench4 = None
    b4_path = os.path.join(ROOT, "BENCH_4.json")
    if not args.quick and os.path.exists(b4_path):
        with open(b4_path) as f:
            b4 = json.load(f)["main"]["wall_clock_s"]
        vs_bench4 = {
            "bench4_wall_clock_s": b4,
            "ratio_engine_default": timings["engine_default"]
            / max(b4["engine_default"], 1e-9),
        }
    # PR-6 non-regression: registration-only PR, the original 4-algorithm
    # engine_default sweep must stay within noise of the PR-5 anchor
    vs_bench5 = None
    b5_path = os.path.join(ROOT, "BENCH_5.json")
    if not args.quick and os.path.exists(b5_path):
        with open(b5_path) as f:
            b5 = json.load(f)["main"]["wall_clock_s"]
        vs_bench5 = {
            "bench5_wall_clock_s": b5,
            "ratio_engine_default": timings["engine_default"]
            / max(b5["engine_default"], 1e-9),
        }
    # PR-7 non-regression: the fault path is dormant unless a job opts
    # in, so the original sweep must stay within noise of the PR-6 anchor
    vs_bench6 = None
    b6_path = os.path.join(ROOT, "BENCH_6.json")
    if not args.quick and os.path.exists(b6_path):
        with open(b6_path) as f:
            b6 = json.load(f)["main"]["wall_clock_s"]
        vs_bench6 = {
            "bench6_wall_clock_s": b6,
            "ratio_engine_default": timings["engine_default"]
            / max(b6["engine_default"], 1e-9),
        }
    # PR-8 non-regression: the service is a new layer over the engine
    # (run_sweep's dedup/cache-cap paths are no-ops by default), so the
    # original sweep must stay within noise of the PR-7 anchor
    vs_bench7 = None
    b7_path = os.path.join(ROOT, "BENCH_7.json")
    if not args.quick and os.path.exists(b7_path):
        with open(b7_path) as f:
            b7 = json.load(f)["main"]["wall_clock_s"]
        vs_bench7 = {
            "bench7_wall_clock_s": b7,
            "ratio_engine_default": timings["engine_default"]
            / max(b7["engine_default"], 1e-9),
        }
    # PR-9 non-regression: telemetry disabled must be free — the no-op
    # span path and registry counters may not move the original sweep
    # out of noise vs the PR-8 anchor (acceptance: < 1% regression)
    vs_bench8 = None
    b8_path = os.path.join(ROOT, "BENCH_8.json")
    if not args.quick and os.path.exists(b8_path):
        with open(b8_path) as f:
            b8 = json.load(f)["main"]["wall_clock_s"]
        vs_bench8 = {
            "bench8_wall_clock_s": b8,
            "ratio_engine_default": timings["engine_default"]
            / max(b8["engine_default"], 1e-9),
        }
    # PR-10 non-regression: the observability plane is read-side only —
    # the sweep gained a handful of flight-recorder publishes (measured
    # in isolation as publish_us), so the original sweep must stay
    # within noise of the PR-9 anchor; bench_check.py additionally
    # gates the whole BENCH_2..10 trajectory
    vs_bench9 = None
    b9_path = os.path.join(ROOT, "BENCH_9.json")
    if not args.quick and os.path.exists(b9_path):
        with open(b9_path) as f:
            b9 = json.load(f)["main"]["wall_clock_s"]
        vs_bench9 = {
            "bench9_wall_clock_s": b9,
            "ratio_engine_default": timings["engine_default"]
            / max(b9["engine_default"], 1e-9),
        }

    payload = {
        "bench": "engine_sweep",
        "engine_version": ENGINE_VERSION,
        "backend": jax.default_backend(),
        "quick": args.quick,
        "speedup_vs_pr1": speedup,
        "main": {
            "config": {"dataset": "higgs_like", "n": args.n, "d": args.d,
                       "iters": args.iters, "eval_every": args.eval_every,
                       "ms": f"1..{args.m_max}"},
            "wall_clock_s": timings,
            "jit_compiles": jit_counts,
            "hogwild_compiles": {"pr1": len(ms), "vmap": 1},
        },
        "new_algorithms": {
            "config": {"dataset": "higgs_like", "n": args.n, "d": args.d,
                       "iters": args.iters, "eval_every": args.eval_every,
                       "ms": f"1..{args.m_max}",
                       "note": "PR-6 registrations on the unchanged "
                               "ENGINE_VERSION-5 engine, shipped defaults "
                               "(momentum/local_sgd bucketed, async_svrg "
                               "force_flat), cold per algorithm"},
            "results": new_algos,
        },
        "characters": {
            "config": {"rows": min(400, args.n), "rng": args.m_max,
                       "batch_size": args.m_max},
            "ref_s": chars_ref, "fused_s": chars_fused,
            "speedup": chars_ref / max(chars_fused, 1e-9),
        },
        "bucketing_regime": {
            "config": bucket_cfg,
            "wall_clock_s": regime,
            "speedup": regime["flat"] / max(regime["bucketed"], 1e-9),
            "buckets": [{"ms": [bucket_cfg["ms"][i] for i in pos],
                         "m_pad": m_pad}
                        for pos, m_pad in engine._buckets(bucket_cfg["ms"])],
        },
        "seed_axis": {
            "config": {"ms": f"1..{args.m_max}", "n_seeds": args.seeds,
                       "iters": args.iters, "bucketed": False},
            "results": seed_axis,
        },
        "distributed": {
            "config": {"dataset": "higgs_like", "n": args.n, "d": args.d,
                       "iters": args.iters, "ms": f"1..{args.m_max}",
                       "host_cores": os.cpu_count(),
                       "note": "forced host CPU devices, cold subprocess "
                               "per mesh size, min over repeats. "
                               "engine_default = fine d=28 grid, "
                               "wide_compute = d=400 grid (per-step "
                               "FLOPs dominate). Sharding divides the "
                               "element axis that intra-op threads "
                               "cannot (whole per-element scans run "
                               "concurrently), so speedup needs devices "
                               "<= physical cores AND compute-dominated "
                               "elements; this container has 2 shared "
                               "cores, where a 1-device run already "
                               "saturates memory bandwidth + both cores "
                               "via intra-op threads, so measured "
                               "sharding speedups are ~parity and noisy "
                               "(the mesh's value here is the "
                               "invariance contract + CI correctness; "
                               "real multi-chip meshes hit the same "
                               "code path).  Compile counts must stay "
                               "equal across mesh sizes: 1 jit per "
                               "bucket per mesh, sharded or not."},
            "per_mesh": dist,
            "summary": dist_summary,
        },
        "cache_roundtrip_s": {"fresh": fresh, "cached": cached,
                              "speedup": fresh / max(cached, 1e-9)},
        "resilience": {
            "config": {"dataset": "higgs_like", "n": args.n, "d": args.d,
                       "iters": args.iters, "ms": f"1..{args.m_max}",
                       "note": "run_sweep journal on vs off, warm jit "
                               "caches, fresh cache dir per run, "
                               "off/on interleaved and min-reduced "
                               "over 5 repeats; on-path cost = one "
                               "fsync'd append per job (measured "
                               "directly: append_fsync_ms) + one "
                               "journal probe and unlink per sweep "
                               "(target overhead < 2%)"},
            "results": resil,
        },
        "service": {
            "note": "advisor service (docs/service.md): batched front "
                    "end vs per-probe from_dataset loop (warm), N "
                    "concurrent same-fingerprint forced escalations "
                    "(single-flight: sweep_computes must be 1, every "
                    "waiter served the one stored artifact), and the "
                    "analytic-tier early-exit fraction on a mixed "
                    "raw+escalated workload",
            **service,
        },
        "telemetry": {
            "config": {"dataset": "higgs_like", "n": args.n, "d": args.d,
                       "iters": args.iters, "ms": f"1..{args.m_max}",
                       "note": "run_sweep traced on vs off, warm jit "
                               "caches, fresh cache dir per run, off/on "
                               "interleaved and min-reduced over 5 "
                               "repeats; the off label is the disabled "
                               "contract (no-op spans + counters, "
                               "gated < 1% vs_bench8), the on label is "
                               "the enabled tax (per-bucket AOT "
                               "re-lower for the compile/execute split "
                               "+ span recording, isolated as "
                               "span_record_us / noop_span_us)"},
            "results": tel,
        },
        "observability": {
            "config": {"dataset": "higgs_like", "n": args.n, "d": args.d,
                       "iters": args.iters, "ms": f"1..{args.m_max}",
                       "note": "PR-10 live observability plane: flight-"
                               "recorder publish cost isolated "
                               "(publish_us — the only new always-on "
                               "sweep-path work, a handful per sweep), "
                               "GET /metrics and /flight scrape latency "
                               "against a live ServiceServer (min over "
                               "50 warm requests), and the full "
                               "engine_default sweep unobserved vs with "
                               "a 50 ms scraper thread polling both "
                               "endpoints (warm jit caches, fresh cache "
                               "dir per run, interleaved, min over 3 "
                               "repeats) — the observational claim at "
                               "sweep granularity"},
            "results": obs,
        },
        "vs_bench4": vs_bench4,
        "vs_bench5": vs_bench5,
        "vs_bench6": vs_bench6,
        "vs_bench7": vs_bench7,
        "vs_bench8": vs_bench8,
        "vs_bench9": vs_bench9,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"speedup vs PR-1 engine: {speedup:.2f}x -> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
