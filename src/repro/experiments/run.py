"""CLI for the sweep engine — reproduce any paper figure from a spec name.

  PYTHONPATH=src python -m repro.experiments.run --list
  PYTHONPATH=src python -m repro.experiments.run --spec upper_bound --quick
  PYTHONPATH=src python -m repro.experiments.run --spec variance_sparsity \\
      --quick --iters 100 --n 300          # smoke-scale override
  PYTHONPATH=src python -m repro.experiments.run --spec diversity \\
      --quick --problem hinge              # same grid, hinge objective

``--list`` enumerates the registered specs AND the live Algorithm /
Problem / dataset-generator registries — anything listed is addressable
from a spec with no engine edits.  ``--problem`` re-points every job of
the chosen spec at another registered objective — but keeps each job's
kwargs, so a step size tuned for the spec's original objective may not
suit the new one's curvature (ridge on wide-range features wants a much
smaller gamma than Eq. 4 — see the ``problem_generality`` spec); the
runner warns if a curve goes non-finite.

``--devices`` (default ``auto``) shards every job's batched grid over a
device mesh (`repro.distributed`); the resolved mesh is reported at
startup.  Execution-only: curves and cache keys are identical on any
mesh size, so a sweep computed on 1 device is a cache hit on 8.

Repeated runs of an unchanged spec are served from the artifact cache
(--force recomputes, --no-cache bypasses it).  --json writes the full
result payload; the stdout report ends with the measured-vs-predicted
m_max comparison whenever the spec produces both sides.  The exit status
is 1 when any job ends ``failed`` (every attempt raised).

``--trace out.json`` records the run as nested spans (sweep -> job ->
grid -> bucket -> jaxpr_trace/lower/compile, fetch, journal and cache
IO) and writes
Chrome-trace / Perfetto JSON — load it at https://ui.perfetto.dev or
summarize with ``python -m repro.telemetry --summarize out.json``.
``--metrics`` dumps the process metrics registry (Prometheus text) after
the run.  ``--serve PORT`` additionally exposes the run's telemetry over
HTTP *while it executes* — ``GET /metrics`` (Prometheus text),
``/healthz``, ``/flight`` (the flight recorder's per-job progress
events; tail it with ``python -m repro.telemetry --watch URL``), and
``/trace`` (live span JSON when ``--trace`` is also on).  All three are
observational: the sweep executes the same code and the artifact bytes
are identical with or without them (docs/observability.md).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

from repro import runtime
from repro.core import problems as problems_mod
from repro.core.algorithms import base as alg_base
from repro.data import synth
from repro.distributed import get_mesh
from repro.experiments import registry, runner
from repro.telemetry import metrics as metrics_mod
from repro.telemetry import trace


def _print_report(result: dict) -> None:
    spec = result["spec"]
    print("=" * 72)
    print(f"sweep {result['name']}: {spec['description']}")
    line = (f"  m grid={list(spec['ms'])}  iters={spec['iters']}  "
            f"eval_every={spec['eval_every']}")
    if spec.get("n_seeds", 1) > 1:
        line += (f"  seeds={spec['n_seeds']} (stats: "
                 f"python -m repro.analysis.report)")
    print(line)
    print("=" * 72)

    for name, info in result["datasets"].items():
        line = f"dataset {name:18s} n={info['n']} d={info['d']}"
        if "csim" in info:
            line += f"  C_sim={info['csim']:.2f}"
        if "characters" in info:
            c = info["characters"]
            line += (f"  var={c['mean_feature_variance']:.3f} "
                     f"sparsity={c['sparsity']:.3f} "
                     f"div={c['diversity_ratio']:.2f}")
        print(line)

    print()
    comparisons = []
    for key, jr in result["jobs"].items():
        if jr.get("status") == "failed":
            print(f"{key:28s} FAILED: {jr['error']}")
            continue
        curves = runner.curves_by_m(jr)
        finals = "  ".join(f"m{m}={c[-1]:.4f}" for m, c in curves.items())
        print(f"{key:28s} final loss: {finals}")
        if "costs" in jr:
            costs = "  ".join(f"m{m}={c:.0f}"
                              for m, c in zip(jr["ms"], jr["costs"]))
            print(f"{'':28s} cost/worker (eps={jr['epsilon']:.4f}): {costs}")
            print(f"{'':28s} measured m_max = {jr['measured_m_max']}")
        if "predicted" in jr:
            pm = jr["predicted"]["predicted_m_max"]
            print(f"{'':28s} predicted m_max = {pm}")
        if "measured_m_max" in jr and "predicted" in jr:
            comparisons.append((key, jr["measured_m_max"],
                                jr["predicted"]["predicted_m_max"]))

    if comparisons:
        print()
        print("measured vs predicted scalability upper bound (core claim):")
        for key, meas, pred in comparisons:
            print(f"  {key:28s} measured={meas:<6d} predicted={pred}")

    cache = result.get("cache", {})
    exe = result.get("execution", {})
    if cache.get("hit"):
        src = "cache hit"
    else:
        src = f"computed in {result.get('elapsed_s', 0.0):.1f}s"
        if exe.get("sharded"):
            src += f" sharded over {exe['devices']} devices"
    print(f"\n[{src}] artifact: {cache.get('path')}")


def _print_registries() -> None:
    print(get_mesh().describe())
    print("\nregistered sweep specs:")
    for name in registry.SPEC_IDS:
        spec = registry.get_spec(name, quick=True)
        print(f"  {name:20s} {spec.description}")
    print("\nregistered algorithms (core.algorithms):")
    for name in sorted(alg_base.ALGORITHMS):
        cls = alg_base.ALGORITHMS[name]
        flags = []
        if cls.asynchronous:
            flags.append("async")
        flags.append("flat" if cls.force_flat
                     else ("bucketed" if cls.bucketed_default else "flat-default"))
        print(f"  {name:20s} predictor={cls.predictor:8s} "
              f"[{', '.join(flags)}]")
    print("\nregistered problems (core.problems):")
    for name in sorted(problems_mod.PROBLEMS):
        doc = (problems_mod.PROBLEMS[name].__doc__ or "").split("\n")[0]
        print(f"  {name:20s} {doc}")
    print("\nregistered dataset generators (data.synth):")
    for name in sorted(synth.GENERATORS):
        doc = (synth.GENERATORS[name].__doc__ or "").split("\n")[0]
        print(f"  {name:20s} {doc}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.experiments.run",
        description="run a registered scalability sweep")
    ap.add_argument("--spec", help=f"spec name; one of {registry.SPEC_IDS}")
    ap.add_argument("--list", action="store_true",
                    help="list registered specs, algorithms, problems, and "
                         "dataset generators, then exit")
    ap.add_argument("--problem",
                    help="re-point every job of the spec at this registered "
                         "problem (e.g. ridge, hinge); job kwargs are kept, "
                         "so curvature-mismatched step sizes may need "
                         "retuning (the runner warns on non-finite curves)")
    ap.add_argument("--quick", action="store_true",
                    help="CI-scale iteration counts")
    ap.add_argument("--iters", type=int, help="override iteration budget")
    ap.add_argument("--n", type=int, help="override dataset size")
    ap.add_argument("--seeds", type=int,
                    help="override the spec's n_seeds (seed replicates per "
                         "job, vmapped in one trace; see repro.analysis)")
    ap.add_argument("--force", action="store_true",
                    help="recompute even on a cache hit")
    ap.add_argument("--no-cache", action="store_true",
                    help="neither read nor write the artifact cache")
    ap.add_argument("--cache-dir", help="artifact cache directory")
    ap.add_argument("--devices", default="auto",
                    help="device mesh for sharded execution: 'auto' (all "
                         "available XLA devices, the default) or an int; "
                         "execution-only — results and cache keys are "
                         "mesh-invariant (see docs/distributed.md).  On "
                         "CPU, create virtual devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--seq", action="store_true",
                    help="sequential per-m loop instead of the vmapped grid "
                         "(never sharded)")
    ap.add_argument("--json", help="also write the full result to this path")
    ap.add_argument("--trace", metavar="TRACE_JSON",
                    help="record the run as spans and write Chrome-trace / "
                         "Perfetto JSON here (observational only — "
                         "artifact bytes are unchanged)")
    ap.add_argument("--metrics", action="store_true",
                    help="dump the process metrics registry (Prometheus "
                         "text) after the run")
    ap.add_argument("--serve", metavar="PORT", type=int, default=None,
                    help="expose /metrics /healthz /flight /trace over HTTP "
                         "on this port while the sweep runs (0 = ephemeral; "
                         "observational only — artifact bytes are "
                         "unchanged)")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    if args.list:
        _print_registries()
        return 0
    runtime.enable_compile_cache()
    if not args.spec:
        ap.error("--spec is required (or --list)")

    spec = registry.get_spec(args.spec, quick=args.quick,
                             iters=args.iters, n=args.n, seeds=args.seeds)
    if args.problem:
        problems_mod.get_problem(args.problem)    # fail fast if unknown
        spec = dataclasses.replace(spec, jobs=tuple(
            dataclasses.replace(j, problem=args.problem)
            for j in spec.jobs)).validate()
    devices = args.devices
    if devices != "auto":
        try:
            devices = int(devices)
        except ValueError:
            ap.error(f"--devices must be an int or 'auto', got {devices!r}")
    # startup mesh report — best-effort: an over-subscribed request (e.g.
    # --devices 8 on a 1-device host) clamps with a warning, and an
    # otherwise-invalid one (--devices 0) must still serve cached
    # artifacts, so the runner resolves the mesh only on a miss
    try:
        print(get_mesh(devices).describe())
    except ValueError as e:
        print(f"mesh: not resolvable here ({e}); cached artifacts still "
              f"serve, a fresh compute will fail")
    # the tracer brackets run_sweep tightly, so the root "sweep" span
    # attributes ~all of the traced wall-clock (the >=95% coverage gate
    # in CI's traced smoke); a cache hit traces only the lookup
    if args.trace:
        trace.start()
    server = None
    if args.serve is not None:
        # metrics-only observability plane: no advisor behind it, so
        # probe endpoints answer 503; /metrics /flight /trace watch THIS
        # process's sweep (import here keeps the plain CLI http-free)
        from repro.service.http import ServiceServer
        server = ServiceServer(None, port=args.serve).start()
        print(f"observability plane at {server.url} (GET /metrics "
              f"/healthz /flight /trace; watch: python -m repro.telemetry "
              f"--watch {server.url})", flush=True)
    try:
        result = runner.run_sweep(spec, use_cache=not args.no_cache,
                                  force=args.force, cache_dir=args.cache_dir,
                                  use_vmap=not args.seq, verbose=args.verbose,
                                  mesh=devices)
    finally:
        if server is not None:
            server.stop()
        if args.trace:
            trace.stop()
            trace.export(args.trace)
            print(f"wrote trace {args.trace} (load at "
                  f"https://ui.perfetto.dev, or: python -m repro.telemetry "
                  f"--summarize {args.trace})")
    _print_report(result)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1, default=float)
        print(f"wrote {args.json}")
    if args.metrics:
        print()
        print(metrics_mod.REGISTRY.render_prometheus(prefix="repro_"),
              end="")
    failed = [k for k, jr in result["jobs"].items()
              if jr.get("status") == "failed"]
    if failed:
        print(f"FAILED jobs: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
