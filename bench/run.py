#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (BENCHMARK.json) names a configuration (bench/configs: a paper
sweep as a `SweepSpec` dict) and a traffic (bench/traffic).  A run

  1. turns on the program's compile cache and fails unless JAX finds a TPU
     with the cell's chips (it never falls back to the CPU);
  2. set-up: one warm-up sweep, as every later one, through
     `repro.experiments.runner.run_sweep`; ``setup_s`` ends with it;
  3. ``--trace 0``: issues sweeps back to back, each with new dataset and
     split seeds drawn from ``--seed`` and its index, until ``--seconds``
     have passed (the last one runs to its end); ``sweep_s`` is the
     window over the sweeps issued.  ``--trace 1``: times one sweep
     untraced, then profiles one more, and reports the per-layer metrics;
  4. checks one sweep of the window, drawn from the seed, against the
     plain reference (harness/reference.py), number by number;
  5. prints, as the last line of standard output, one JSON object:
     ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
     (with ``--trace 1`` also ``breakdown``), and ``check`` last.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

from harness import cells, check, reference, trace_reduce  # noqa: E402

#: the platform a run must find (a rehearsal off the chip changes it)
PLATFORM = "tpu"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SYNC = "bench_sync"


class CompileClock:
    """XLA backend compiles seen by `jax.monitoring` while installed."""

    def __init__(self, jax):
        self._jax = jax
        self.count, self.seconds = 0, 0.0

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        self._jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        self._jax.monitoring.unregister_event_duration_listener(self._on)
        return False


class Cell:
    """One cell's files, and the requests of one run of it."""

    def __init__(self, name: str, seed: int):
        self.w = cells.workload(name)
        self.cfg = cells.config(self.w["config"])
        self.tfc = cells.traffic(self.w["traffic"])
        self.lim = cells.limits(name)
        self.seed = seed

    def spec(self, k: int):
        return cells.request(self.cfg, self.tfc, self.seed, k)


def issue(runner, SweepSpec, cell: Cell, k: int, cache_dir: str):
    """Run the cell's k-th sweep; (spec, result or None, failed)."""
    spec = cell.spec(k)
    try:
        with contextlib.redirect_stdout(sys.stderr):
            res = runner.run_sweep(SweepSpec.from_dict(spec),
                                   cache_dir=cache_dir,
                                   mesh=cell.tfc["mesh"])
    except Exception:  # noqa: BLE001 — a failed request is counted
        traceback.print_exc()
        return spec, None, True
    bad = [k for k, j in res["jobs"].items()
           if j.get("status") in ("failed", "diverged")]
    if bad:
        print(f"sweep {k}: jobs {bad} failed or diverged", file=sys.stderr)
    return spec, res, bool(bad)


def memory_peak(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def traced(jax, runner, SweepSpec, cell, cache_dir, work, chips, peaks):
    """Time one sweep untraced, then profile one; (sweeps, metrics, device
    extras, breakdown)."""
    from repro.telemetry import trace
    t0 = time.perf_counter_ns()
    sweeps = [issue(runner, SweepSpec, cell, 1, cache_dir)]
    timed_s = (time.perf_counter_ns() - t0) / 1e9
    pdir = os.path.join(work, "profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # annotations, no Python call tree
    opts.host_tracer_level = 1
    tracer = trace.start()
    jax.profiler.start_trace(pdir, profiler_options=opts)
    try:
        sync_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(SYNC):
            pass
        with CompileClock(jax) as clock:
            t0 = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation("bench_sweep"):
                sweeps.append(issue(runner, SweepSpec, cell, 2, cache_dir))
            t1 = time.perf_counter_ns()
    finally:
        jax.profiler.stop_trace()
        trace.stop()
    path = glob.glob(os.path.join(pdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = trace_reduce.load(path)
    devices = {p: evs for p, evs in data["devices"].items()
               if int(p.rsplit(":", 1)[1]) < chips}
    offset = trace_reduce.clock_offset(data["host"], SYNC, sync_ns)
    spans = [trace_reduce.Span(
        e["name"], tracer.t0_ns + e["ts"] * 1e3 + offset,
        tracer.t0_ns + (e["ts"] + e["dur"]) * 1e3 + offset,
        e["args"]["depth"]) for e in tracer.events]
    modules = {p: evs for p, evs in data["modules"].items() if p in devices}
    programs = {k: cells.kernel_counts(k).NAME for k in cells.kernel_names()}
    red = trace_reduce.reduce(devices, t0 + offset, t1 + offset, spans,
                              modules, programs)
    print(f"trace: {os.path.getsize(path)} bytes, {red['devices']} device "
          f"planes, program runs {red['program_runs']}, program seconds "
          f"{red['program_s']}", file=sys.stderr)
    ctx = {"sweeps": 1, "specs": [sweeps[1][0]],
           "window_s": (t1 - t0) / 1e9, "timed_spec": sweeps[0][0],
           "timed_s": timed_s, "compile_s": clock.seconds,
           "compiles": clock.count, "spans": tracer.events, "trace": red,
           "chips": chips, "peaks": peaks}
    metrics = {}
    for m in cells.benchmark()["per_layer"]:
        value = cells.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
    breakdown = {"device_ops": red["device_ops"],
                 "idle_gaps": red["idle_gaps"]}
    return sweeps, metrics, extra, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload, args.seed)
    chips = cell.w["chips"]

    import jax
    from repro import runtime
    cache = runtime.enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", file=sys.stderr,
          flush=True)
    if dev.platform != PLATFORM:
        print(f"no {PLATFORM.upper()}: JAX's default platform is "
              f"{dev.platform!r}; the benchmark never falls back",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"{args.workload} needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    used = devices[:chips]
    peaks = cells.peaks(dev.device_kind)

    from repro.experiments import engine, runner
    from repro.experiments.spec import SweepSpec

    work = tempfile.mkdtemp(prefix="bench-run-")
    cache_dir = os.path.join(work, "sweeps")
    try:
        _, _, warm_failed = issue(runner, SweepSpec, cell, 0, cache_dir)
        setup_s = time.perf_counter() - T_START
        print(f"setup_s={setup_s!r} warm-up failed={warm_failed}",
              file=sys.stderr, flush=True)

        extra, breakdown = {}, None
        if args.trace:
            sweeps, metrics, extra, breakdown = traced(
                jax, runner, SweepSpec, cell, cache_dir, work, chips, peaks)
        else:
            jit0 = engine.JIT_CALLS
            sweeps = []
            with CompileClock(jax) as clock:
                t0 = time.perf_counter()
                while True:
                    sweeps.append(issue(runner, SweepSpec, cell,
                                        len(sweeps) + 1, cache_dir))
                    if time.perf_counter() - t0 >= args.seconds:
                        break
                t1 = time.perf_counter()
            print(f"window: {len(sweeps)} sweeps in {t1 - t0!r} s; XLA "
                  f"compiles {clock.count} ({clock.seconds!r} s); the "
                  f"program's repro_engine_jit_compiles_total "
                  f"+{engine.JIT_CALLS - jit0}", file=sys.stderr)
            metrics = {"setup_s": {"value": setup_s, "unit": "s"},
                       "sweep_s": {"value": (t1 - t0) / len(sweeps),
                                   "unit": "s"}}
        peak = memory_peak(used)

        # the check: one sweep of the window, drawn from the seed
        spec, res, _ = random.Random(args.seed).choice(sweeps)
        if res is None:
            verdict = {"ok": False, "numbers": {}, "missing": ["all"]}
        else:
            verdict = check.compare(res, reference.sweep(spec), spec,
                                    cell.lim)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    numbers = verdict["numbers"]
    for name, v in numbers.items():
        print(f"check {name}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(f"check compared: {verdict.get('curve_entries', 0)} losses, "
          f"{verdict.get('decisions', 0)} integer readouts; missing: "
          f"{verdict['missing']}; correct: {verdict['ok']}", file=sys.stderr,
          flush=True)
    line = {"correct": verdict["ok"], "attempted": len(sweeps),
            "failed": sum(f for _, _, f in sweeps), "metrics": metrics,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devices), "memory_peak_bytes": peak,
                       **extra}}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["check"] = numbers
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
