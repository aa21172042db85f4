"""Sweep runner: SweepSpec -> datasets -> generic engine -> scalability.

`run_sweep` is the one entry point the benchmark, the examples, and the
CLI share.  For each job it

  1. materializes the job's dataset (`spec.build_dataset`) and splits it
     70/20 per the spec's shuffle policy,
  2. runs the worker-count grid through `engine.sweep`, which dispatches
     through the Algorithm x Problem registries (any registered pair runs
     with zero edits here),
  3. if the spec declares an epsilon readout, derives epsilon from the
     probe-m curve, converts curves to per-worker costs (§V.A.1; whether
     costs divide by m is the Algorithm class's `asynchronous` flag), and
     computes gain growth + the measured upper bound m_max (§V.B),
  4. if the job requests it, runs the theory-side predictor selected by
     the Algorithm class's `predictor` kind on the raw dataset characters,
     yielding the measured-vs-predicted m_max comparison the paper is
     about.

Every dataset self-reports its measured §IV characters (variance,
sparsity, diversity, LS) into ``result["datasets"][name]["characters"]``
— capped at `DEFAULT_CHARACTERS_ROWS` rows unless the spec asks for more
via ``characters_rows``.

Specs with ``n_seeds > 1`` replicate every curve over a vmapped seed
batch (see `engine.sweep`); the scalar epsilon/cost/m_max readouts here
stay seed-0 (every legacy key is unchanged) and the full per-seed block
lands in ``job["losses_seeds"]`` — `repro.analysis.stats` turns it into
mean/CI curves, seed-replicated costs, and bootstrap m_max
distributions.

Results are plain JSON-serializable dicts (curves as a row-per-m list of
lists; use `curves_by_m` for {m: curve} access) and are stored in the
content-hashed artifact cache — re-running an unchanged spec is a disk
read.  The fresh/cached distinction is reported in ``result["cache"]``
and the resolved device mesh in ``result["execution"]``; both are
attached after loading and never persisted (`cache.VOLATILE_KEYS`), so
artifacts are byte-identical whichever mesh computed them.

Fault tolerance (docs/robustness.md): every finished job is appended to a
crash journal (`repro.resilience.journal`) next to the artifact, so a
sweep killed mid-run resumes from the completed jobs and still produces a
byte-identical artifact; jobs that raise or diverge are retried with
backoff (``max_retries``) and carry a structured ``status`` field
("ok" / "retried:N" / "diverged" / "failed") instead of poisoning the
epsilon/cost/predictor readouts — unhealthy jobs keep their curves (or a
structured error stub) but are excluded from every derived quantity (see
`job_is_healthy`).
"""

from __future__ import annotations

import inspect
import math
import time
import warnings
from typing import Dict, List, Optional

import jax
import numpy as np

from repro import runtime
from repro.analysis import fit as fit_mod
from repro.core import metrics as MX
from repro.core import scalability as SC
from repro.core.algorithms import base as alg_base
from repro.distributed import mesh as dist_mesh
from repro.experiments import cache as artifact_cache
from repro.experiments import engine
from repro.experiments import spec as spec_mod
from repro.experiments.spec import SweepSpec
from repro.resilience import journal as journal_mod
from repro.telemetry import metrics, trace
from repro.telemetry.recorder import publish as _flight

#: theory-side m_max predictor per Algorithm.predictor kind — the
#: vectorized `repro.analysis.fit` scans (the scalar while-loops in
#: `core.scalability` remain the parity oracles)
_PREDICTORS = {
    "hogwild": fit_mod.predict_hogwild_mmax,
    "sync": fit_mod.predict_sync_mmax,
    "dadm": fit_mod.predict_dadm_mmax,
    "momentum": fit_mod.predict_momentum_mmax,
    "local_sgd": fit_mod.predict_local_sgd_mmax,
    "svrg": fit_mod.predict_svrg_mmax,
}


def _predict(predictor: str, X, job_kwargs: Dict) -> Dict:
    """Run the theory-side predictor, forwarding exactly the job
    hyperparameters its signature accepts (momentum's beta, local SGD's
    sync_every, async-SVRG's anchor_every) — the critical-parameter specs
    sweep those knobs, and the prediction must move with them."""
    fn = _PREDICTORS[predictor]
    accepted = inspect.signature(fn).parameters
    hints = {k: v for k, v in job_kwargs.items() if k in accepted}
    return fn(X, **hints)

#: row cap for the always-on dataset-characters report (the §IV indices are
#: O(rows^2)-ish through the LS scans; specs override via characters_rows)
DEFAULT_CHARACTERS_ROWS = 512

#: process-wide count of sweeps actually *computed* (cache hits and
#: dedup-follower waits don't increment) — tests and the service bench
#: read it to prove single-flight dedup executes exactly one sweep.
#: Registry-backed (PR 9): increments are locked, so exact deltas hold
#: under the service's concurrent probes; the module-level
#: ``SWEEP_COMPUTES`` read stays source-compatible via ``__getattr__``.
_SWEEP_COMPUTES = metrics.counter(
    "repro_sweep_computes_total",
    help="sweeps actually computed (cache hits / dedup waits excluded)")
_DEDUP_LEADER = metrics.counter(
    "repro_sweep_dedup_leader_total",
    help="single-flight leases won (this caller computed for the group)")
_DEDUP_WAITER = metrics.counter(
    "repro_sweep_dedup_waiter_total",
    help="single-flight waits (this caller blocked on a leader's compute)")
_JOB_RETRIES = metrics.counter(
    "repro_sweep_job_retries_total",
    help="job attempts beyond the first (raised or non-finite curves)")
_JOURNAL_APPENDS = metrics.counter(
    "repro_journal_appends_total",
    help="finished jobs appended to a crash journal")
_JOURNAL_REPLAYS = metrics.counter(
    "repro_journal_replays_total",
    help="jobs replayed from a crash journal instead of recomputed")


def __getattr__(name):
    # PEP 562 read alias for the legacy module global (see engine.py)
    if name == "SWEEP_COMPUTES":
        return _SWEEP_COMPUTES.value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


#: process-wide single-flight table for `run_sweep(dedup=True)` callers
_INFLIGHT = artifact_cache.InFlightTable()


def curves_by_m(job_result: Dict) -> Dict[int, List[float]]:
    """{worker count: convergence curve} view of a job result."""
    return {int(m): list(row) for m, row in
            zip(job_result["ms"], job_result["losses"])}


def _epsilon_from_probe(job_result: Dict, eps_spec) -> float:
    """Paper Table II policy: epsilon is the loss the probe_m-worker run
    reaches after `frac` of its eval budget — reachable by every setting,
    discriminative between them."""
    curve = curves_by_m(job_result)[eps_spec.probe_m]
    # frac == 1.0 would index one past the end; clamp to the last eval
    idx = min(int(len(curve) * eps_spec.frac), len(curve) - 1)
    return float(curve[idx])


def job_is_healthy(job_result: Dict) -> bool:
    """True when the job's curves are trustworthy inputs for readouts,
    fits, and reports.  "ok" and "retried:N" (succeeded after transient
    failure) are healthy; "diverged" and "failed" are not.  Artifacts
    from before the status field default to healthy."""
    status = str(job_result.get("status", "ok"))
    return status == "ok" or status.startswith("retried")


def _finite(job_result: Dict) -> bool:
    return bool(np.isfinite(
        job_result.get("losses_seeds", job_result["losses"])).all())


def _run_job_with_retries(spec: SweepSpec, job, tr, te, dmesh, use_vmap: bool,
                          max_retries: int, retry_backoff_s: float,
                          verbose: bool):
    """Run one job with bounded retry-with-backoff; returns
    ``(job_result, status)``.  The engine is deterministic, so retries
    target transient infrastructure failures (OOM, interrupted device
    pools), not numerics — a curve that diverges twice is reported as
    "diverged" with its curves intact, and a job whose every attempt
    raised becomes a structured "failed" stub instead of killing the
    sweep."""
    last_exc: Optional[BaseException] = None
    jr: Optional[Dict] = None
    for attempt in range(max_retries + 1):
        if attempt:
            _JOB_RETRIES.inc()
            _flight("job_retried", sweep=spec.name, job=job.key,
                    attempt=attempt + 1)
            if retry_backoff_s > 0:
                time.sleep(retry_backoff_s * (2 ** (attempt - 1)))
        try:
            jr = engine.sweep(
                job.algorithm, tr, te, spec.ms, iters=spec.iters,
                eval_every=spec.eval_every, use_vmap=use_vmap,
                problem=job.problem, n_seeds=spec.n_seeds, mesh=dmesh,
                **job.kwargs)
        except Exception as exc:  # noqa: BLE001 — one job must not kill the sweep
            last_exc = exc
            if verbose:
                print(f"[{spec.name}] {job.key}: attempt {attempt + 1} "
                      f"raised {type(exc).__name__}: {exc}")
            continue
        if _finite(jr):
            return jr, ("ok" if attempt == 0 else f"retried:{attempt}")
        if verbose:
            print(f"[{spec.name}] {job.key}: attempt {attempt + 1} "
                  f"produced non-finite curves")
    if jr is not None:
        return jr, "diverged"
    return ({"algorithm": job.algorithm, "problem": job.problem,
             "error": f"{type(last_exc).__name__}: {last_exc}"}, "failed")


def _cost_readout(job_result: Dict, epsilon: float, asynchronous: bool):
    iters = job_result["iters"]
    costs = []
    for m, losses in zip(job_result["ms"], job_result["losses"]):
        c = SC.cost_per_worker(
            {"losses": losses, "eval_every": job_result["eval_every"],
             "m": m}, epsilon, asynchronous=asynchronous)
        costs.append(float(c) if math.isfinite(c) else float(iters))
    gg = SC.gain_growth_from_costs(costs)
    bound = SC.measured_upper_bound(job_result["ms"][:-1], gg)
    return costs, gg, bound


def run_sweep(spec: SweepSpec, *, use_cache: bool = True, force: bool = False,
              cache_dir: Optional[str] = None, use_vmap: bool = True,
              verbose: bool = False, mesh: "dist_mesh.MeshLike" = None,
              journal: bool = True, max_retries: int = 1,
              retry_backoff_s: float = 0.25, dedup: bool = False,
              cache_cap: Optional[int] = None) -> Dict:
    """Execute (or fetch) the full sweep a spec describes.

    ``mesh`` (or, when absent, the spec's execution-only ``devices``
    field) shards every job's batched grid over a device mesh via
    `repro.distributed` — results and cache keys are mesh-invariant, so
    the mesh only changes where the arithmetic runs.  The resolved mesh
    is reported in ``result["execution"]`` (attached after load/store,
    never persisted — see `cache.VOLATILE_KEYS`).

    ``journal=True`` (with ``use_cache``) appends every finished job to a
    crash journal beside the artifact and, on a re-run after a crash,
    replays journaled jobs instead of recomputing them — the resumed
    artifact is byte-identical to an uninterrupted run's.  ``max_retries``
    bounds the retry-with-backoff loop for jobs that raise or produce
    non-finite curves (see `_run_job_with_retries`).

    ``dedup=True`` (with ``use_cache``) routes the call through a
    process-wide single-flight table: concurrent callers sharing this
    spec's fingerprint elect one *leader* that computes and stores the
    artifact while the rest block, then load the leader's bytes from the
    cache — N identical concurrent requests execute exactly one sweep
    (`SWEEP_COMPUTES` counts real executions; `repro.service` sets this
    for every escalation).  ``cache_cap`` forwards to
    `cache.store(max_artifacts=...)` for LRU-bounded artifact dirs.
    """
    spec.validate()
    cache_dir = cache_dir or artifact_cache.DEFAULT_CACHE_DIR
    fp = spec_mod.fingerprint(spec)

    leased = False
    while use_cache and not force:
        hit = artifact_cache.load(cache_dir, spec.name, fp)
        if hit is not None:
            if leased:
                _INFLIGHT.release(fp)
            hit["cache"] = {"hit": True,
                            "path": artifact_cache.artifact_path(
                                cache_dir, spec.name, fp)}
            # a hit executes nothing, so the mesh request is never
            # resolved — an artifact cached elsewhere must serve even on
            # a host that cannot satisfy the spec's `devices` ask
            hit["execution"] = {"devices": len(jax.devices()),
                                "sharded": False,
                                "backend": runtime.default_platform()}
            return hit
        if not dedup or leased:
            break
        if _INFLIGHT.lease(fp):
            # leader: re-check the cache once (a prior leader may have
            # stored between our miss and the lease), then compute
            leased = True
            _DEDUP_LEADER.inc()
            continue
        # follower: block until the leader releases, then re-check the
        # cache — on leader success that's a hit; on leader failure the
        # loop retries the lease (one follower takes over)
        _DEDUP_WAITER.inc()
        with trace.span("dedup_wait", fingerprint=fp[:12]):
            _INFLIGHT.wait(fp)

    try:
        return _compute_sweep(
            spec, fp, cache_dir, use_cache=use_cache, force=force,
            use_vmap=use_vmap, verbose=verbose, mesh=mesh, journal=journal,
            max_retries=max_retries, retry_backoff_s=retry_backoff_s,
            cache_cap=cache_cap)
    finally:
        if leased:
            # success or failure, wake every dedup waiter: on success
            # they hit the stored artifact; on failure one takes over
            _INFLIGHT.release(fp)


def _compute_sweep(spec: SweepSpec, fp: str, cache_dir: str, *,
                   use_cache: bool, force: bool, use_vmap: bool,
                   verbose: bool, mesh, journal: bool, max_retries: int,
                   retry_backoff_s: float,
                   cache_cap: Optional[int]) -> Dict:
    """The cache-miss path of `run_sweep`: journal replay, job execution,
    readouts, artifact store.  Split out so the dedup lease in
    `run_sweep` wraps exactly one compute in try/finally.  The whole
    compute runs under a root ``sweep`` span — its children (datasets,
    per-job grids, journal/cache IO) are the phase breakdown the report
    and ``--trace`` surface.

    Sweeps are specified in float32, so the compute runs at matmul
    precision ``"highest"``: a TPU otherwise runs f32 matmuls as one bf16
    pass, which moved curves by ~1e-3 on a v5e and broke the
    mesh-invariance contract (docs/distributed.md).  The CPU computes f32
    matmuls either way."""
    with trace.span("sweep", spec=spec.name, fingerprint=fp[:12],
                    jobs=len(spec.jobs)), \
            jax.default_matmul_precision("highest"):
        return _compute_sweep_inner(
            spec, fp, cache_dir, use_cache=use_cache, force=force,
            use_vmap=use_vmap, verbose=verbose, mesh=mesh, journal=journal,
            max_retries=max_retries, retry_backoff_s=retry_backoff_s,
            cache_cap=cache_cap)


def _compute_sweep_inner(spec: SweepSpec, fp: str, cache_dir: str, *,
                         use_cache: bool, force: bool, use_vmap: bool,
                         verbose: bool, mesh, journal: bool,
                         max_retries: int, retry_backoff_s: float,
                         cache_cap: Optional[int]) -> Dict:
    _SWEEP_COMPUTES.inc()

    jpath = journal_mod.journal_path(cache_dir, spec.name, fp)
    journaled: Dict[str, Dict] = {}
    if use_cache and journal and not force:
        with trace.span("journal_read"):
            journaled = journal_mod.read_entries(jpath, fp)
        if verbose and journaled:
            print(f"[{spec.name}] resuming: {len(journaled)} job(s) "
                  f"replayed from crash journal {jpath}")

    # flight-recorder progress events (docs/observability.md): in-memory
    # only, so a mid-sweep GET /flight shows per-job progress without
    # touching the computation or the artifact bytes
    _flight("sweep_started", sweep=spec.name, fingerprint=fp[:12],
            jobs=len(spec.jobs), replayed=len(journaled))

    dmesh = dist_mesh.resolve(mesh if mesh is not None else spec.devices)
    execution = {
        "devices": dmesh.n_devices if dmesh is not None else 1,
        "sharded": engine.runs_sharded(dmesh, use_vmap),
        "backend": runtime.default_platform(),
    }

    # perf_counter is monotonic — wall-clock (time.time) steps under NTP
    # corrections and corrupted elapsed_s; the value is volatile
    # (cache.VOLATILE_KEYS) so the switch cannot change artifact bytes
    t0 = time.perf_counter()
    # the persisted spec dict is exactly the fingerprinted one: two
    # requests differing only in execution fields share a fingerprint,
    # so the artifact they race to write must be byte-identical too
    result: Dict = {"name": spec.name,
                    "spec": spec_mod.computational_dict(spec),
                    "datasets": {}, "jobs": {}}

    datasets, splits = {}, {}
    with trace.span("datasets", count=len(spec.datasets)):
        # every build is dispatched before the first split (a split can
        # wait on its dataset), so the host compiles each dataset's
        # program while the device runs the last: a dataset has one
        # dataset_build span for its build and one for its split
        for name, ds in spec.datasets.items():
            with trace.span("dataset_build", name=name, part="build") as sp:
                data = datasets[name] = spec_mod.build_dataset(ds)
                sp.set(n=data.X.shape[0], d=data.X.shape[1])
        for name, data in datasets.items():
            with trace.span("dataset_build", name=name, part="split",
                            n=data.X.shape[0], d=data.X.shape[1]):
                splits[name] = spec_mod.split_dataset(
                    spec.datasets[name], data, spec.split_seed)

        for name, data in datasets.items():
            info: Dict = {"n": int(data.X.shape[0]),
                          "d": int(data.X.shape[1])}
            with trace.span("characters", name=name):
                if spec.measure_csim > 0:
                    info["csim"] = MX.csim(data.X[:spec.csim_rows],
                                           spec.measure_csim)
                # every dataset self-reports its §IV characters
                rows = spec.characters_rows or DEFAULT_CHARACTERS_ROWS
                info["characters"] = MX.summarize(data.X[:rows])
            result["datasets"][name] = info

    for job in spec.jobs:
        if job.key in journaled:
            # crash-journal replay: the entry already carries readouts,
            # predictions, and status — a JSON round-trip of exactly what
            # an uninterrupted run would have put here
            if verbose:
                print(f"[{spec.name}] {job.key}: resumed from journal")
            _JOURNAL_REPLAYS.inc()
            _flight("job_replayed", sweep=spec.name, job=job.key)
            result["jobs"][job.key] = journaled[job.key]
            continue
        if verbose:
            print(f"[{spec.name}] sweep {job.key} over m={list(spec.ms)}")
        alg_cls = alg_base.get_algorithm(job.algorithm)
        tr, te = splits[job.dataset]
        _flight("job_started", sweep=spec.name, job=job.key,
                algorithm=job.algorithm, dataset=job.dataset)
        with trace.span("job", key=job.key, algorithm=job.algorithm,
                        dataset=job.dataset):
            jr, status = _run_job_with_retries(
                spec, job, tr, te, dmesh, use_vmap,
                max_retries, retry_backoff_s, verbose)
        jr["dataset"] = job.dataset
        jr["status"] = status
        if status != "ok":
            # "retried:N" -> job_retried already fired per attempt; the
            # terminal unhealthy states get their own event kinds
            if status in ("diverged", "failed"):
                _flight(f"job_{status}", sweep=spec.name, job=job.key)
        if status == "diverged":
            # usually a step size tuned for another objective's curvature
            # (e.g. logistic gamma on ridge); surface it loudly — the
            # curves are kept but every readout below skips this job
            warnings.warn(
                f"job {job.key!r}: non-finite loss curve — the step size "
                f"is likely unstable for problem {job.problem!r} on this "
                f"dataset; tune the job kwargs (see the problem_generality "
                f"spec for per-problem gammas)", RuntimeWarning,
                stacklevel=2)
        elif status == "failed":
            warnings.warn(
                f"job {job.key!r}: failed after {max_retries + 1} "
                f"attempt(s) — {jr['error']}; a structured stub is cached "
                f"in its place", RuntimeWarning, stacklevel=2)
        healthy = job_is_healthy(jr)

        with trace.span("readout", key=job.key):
            if spec.epsilon is not None and healthy:
                eps = _epsilon_from_probe(jr, spec.epsilon)
                costs, gg, bound = _cost_readout(
                    jr, eps, asynchronous=alg_cls.asynchronous)
                jr.update(epsilon=eps, costs=costs, gain_growth=gg,
                          measured_m_max=int(bound))

            if job.predict and healthy:
                X = datasets[job.dataset].X
                if job.predict_rows > 0:
                    X = X[:job.predict_rows]
                with trace.span("predict", predictor=alg_cls.predictor,
                                rows=int(X.shape[0]), d=int(X.shape[1])):
                    jr["predicted"] = _predict(alg_cls.predictor, X,
                                               job.kwargs)

        result["jobs"][job.key] = jr
        _flight("job_stored", sweep=spec.name, job=job.key, status=status,
                healthy=healthy)
        if use_cache and journal:
            with trace.span("journal_append", key=job.key):
                journal_mod.append_entry(jpath, fp, job.key, jr)
            _JOURNAL_APPENDS.inc()

    result["elapsed_s"] = time.perf_counter() - t0
    path = None
    if use_cache:
        with trace.span("store"):
            path = artifact_cache.store(cache_dir, spec.name, fp, result,
                                        max_artifacts=cache_cap)
            if journal:
                # the artifact now supersedes the journal
                journal_mod.consume(jpath)
    result["cache"] = {"hit": False, "path": path}
    result["execution"] = execution
    _flight("sweep_stored", sweep=spec.name, fingerprint=fp[:12],
            elapsed_s=round(result["elapsed_s"], 3), path=path)
    return result
