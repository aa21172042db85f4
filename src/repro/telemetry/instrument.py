"""jax-aware tracing helpers: compile phases from JAX's own events, named
bucket programs, and the dispatch span.

A jitted grid function called through ``jfn(args)`` pays jaxpr tracing,
StableHLO lowering and XLA compilation on its first call, then enqueues
the device work and returns.  JAX reports each of the three phases as a
``jax.monitoring`` event; this module turns them into telemetry on the
same plain jit path that untraced code runs:

  * **Spans, while a tracer runs.**  ``trace.start()`` installs the
    listeners below and ``trace.stop()`` removes them.  Each of
    :data:`PHASES` becomes a span (``jaxpr_trace``, ``lower``,
    ``compile``) in the current context, below the innermost open span
    and below any JAX phase still open around it (nested jaxpr traces
    nest).  It starts at the tracer's ``perf_counter_ns`` when JAX opens
    the phase (the end less JAX's duration where the opening was not
    seen), ends when JAX closes it, and carries JAX's ``fun_name``.
  * **Counters, always.**  One listener, installed when this module is
    imported, counts backend compiles (``repro_jax_compiles_total``), the
    seconds of each phase (``repro_jax_compile_seconds_total{phase}``)
    and the persistent compilation cache's requests and hits: one locked
    increment per event.

:func:`dispatch` is one span around ``jfn(*args)``; it never blocks, so
the host waits for the device where the caller reads the result (the
engine's ``fetch`` span).  Importing this module also makes every live
span enter a ``jax.profiler.TraceAnnotation`` (``trace.set_annotation``),
so a profile taken while tracing shows the program's spans on the
profiler's own clock.

This is the one telemetry module that imports jax; `trace` and
`metrics` stay stdlib-only so the dump CLI works anywhere.
"""

from __future__ import annotations

import threading
import time

import jax

from repro.telemetry import metrics, trace

#: JAX's compile-phase events and the span names they become
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_COMPILES = metrics.counter(
    "repro_jax_compiles_total",
    help="XLA backend compiles (JAX backend_compile_duration events)")
_PHASE_SECONDS = {
    phase: metrics.counter(
        "repro_jax_compile_seconds_total", labels={"phase": phase},
        help="seconds JAX spent per compile phase")
    for phase in PHASES.values()}
_CACHE_REQUESTS = metrics.counter(
    "repro_jax_compile_cache_requests_total",
    help="compiles that consulted the persistent compilation cache")
_CACHE_HITS = metrics.counter(
    "repro_jax_compile_cache_hits_total",
    help="compiles answered by the persistent compilation cache")


def _count_phase(event, duration_secs, **_):
    phase = PHASES.get(event)
    if phase is None:
        return
    _PHASE_SECONDS[phase].inc(duration_secs)
    if phase == "compile":
        _COMPILES.inc()


def _count_cache(event, **_):
    if event == CACHE_REQUEST_EVENT:
        _CACHE_REQUESTS.inc()
    elif event == CACHE_HIT_EVENT:
        _CACHE_HITS.inc()


jax.monitoring.register_event_duration_secs_listener(_count_phase)
jax.monitoring.register_event_listener(_count_cache)


# ---------------------------------------------------------------------------
# compile-phase spans (installed only while a tracer runs)
# ---------------------------------------------------------------------------

#: per thread: (tracer, [perf_counter_ns at which each open phase began])
_OPEN = threading.local()


def _open_phases(tracer) -> list:
    held = getattr(_OPEN, "held", None)
    if held is None or held[0] is not tracer:
        held = _OPEN.held = (tracer, [])
    return held[1]


def _phase_opened(event, value, **_):
    # JAX records each phase's start as a scalar event when it opens it
    tracer = trace.active()
    if tracer is not None and event in PHASES:
        _open_phases(tracer).append(time.perf_counter_ns())


def _phase_closed(event, start_time, end_time, fun_name="", **_):
    phase = PHASES.get(event)
    tracer = trace.active()
    if phase is None or tracer is None:
        return
    end = time.perf_counter_ns()
    opened = _open_phases(tracer)
    start = (opened.pop() if opened
             else end - int((end_time - start_time) * 1e9))
    tracer.record(phase, start, end - start, trace.depth() + len(opened),
                  {"fun_name": fun_name})


#: whether the span listeners are registered (changed only under the
#: tracer's install lock, which runs the two hooks below)
_LISTENING = False


def _listen(tracer) -> None:
    global _LISTENING
    if not _LISTENING:
        jax.monitoring.register_scalar_listener(_phase_opened)
        jax.monitoring.register_event_time_span_listener(_phase_closed)
        _LISTENING = True


def _unlisten(tracer) -> None:
    global _LISTENING
    if _LISTENING:
        jax.monitoring.unregister_scalar_listener(_phase_opened)
        jax.monitoring.unregister_event_time_span_listener(_phase_closed)
        _LISTENING = False


trace.add_tracer_hook(_listen, _unlisten)
trace.set_annotation(jax.profiler.TraceAnnotation)


# ---------------------------------------------------------------------------
# dispatch and program names
# ---------------------------------------------------------------------------

def dispatch(jfn, *args, span_name: str = "bucket", **attrs):
    """Call jitted ``jfn(*args)`` inside a ``span_name`` span carrying
    ``attrs``; the compile phases it triggers nest inside as spans.  It
    returns as the plain call does, before the device finishes."""
    with trace.span(span_name, **attrs):
        return jfn(*args)


def named(fn, name: str):
    """``fn`` renamed, so ``jax.jit(fn)`` lowers to the module
    ``jit_<name>`` that the profiler's XLA Modules line shows."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def nbytes(*trees) -> int:
    """Bytes of the arrays in ``trees``, from their shapes (no device
    read)."""
    return sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(trees))
