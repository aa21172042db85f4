"""repro.telemetry — spans, metrics, and profiling for the sweep stack.

Three pieces (docs/observability.md):

  * `repro.telemetry.trace` — a context-var span tracer.  Off by
    default; `trace.start()` installs it, instrumented hot paths then
    emit nested spans (sweep -> job -> grid -> bucket ->
    jaxpr_trace/lower/compile, fetch, journal/cache IO, service tiers),
    and `trace.export(path)` writes
    Chrome-trace / Perfetto JSON.  With tracing off every `span()` is a
    shared no-op — the observational contract: the sweep path executes
    the same code and produces byte-identical artifacts either way.
  * `repro.telemetry.metrics` — an always-on, thread-safe registry of
    named counters / gauges / histograms with JSON and Prometheus text
    exposition.  It absorbs the legacy racy module globals:
    ``engine.JIT_CALLS`` and ``runner.SWEEP_COMPUTES`` are now
    registry-backed read aliases (existing reads stay source-
    compatible; increments are locked).
  * `repro.telemetry.instrument` — jax-aware helpers: JAX's compile
    events as spans (while tracing) and as always-on counters, the
    profiler annotation of every live span, named bucket programs.

CLI: ``python -m repro.telemetry`` dumps the process registry;
``--summarize trace.json`` validates + phase-breaks a saved trace.

This package deliberately has **no repro-internal imports** (and jax
only inside `instrument`), so any module — core, experiments,
distributed, service — can instrument itself without cycles.
"""

from repro.telemetry import recorder, trace
from repro.telemetry.metrics import (REGISTRY, Counter, Gauge, Histogram,
                                     MetricsRegistry, counter, gauge,
                                     histogram)
from repro.telemetry.recorder import RECORDER
from repro.telemetry.trace import span

# the flight recorder mirrors completed spans whenever a tracer runs, so
# recent span history is scrapeable (GET /flight) without draining the
# tracer itself
trace.add_span_sink(RECORDER.record_span)

__all__ = [
    "trace", "span",
    "REGISTRY", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "counter", "gauge", "histogram",
    "recorder", "RECORDER",
]
