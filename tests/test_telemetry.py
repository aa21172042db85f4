"""repro.telemetry: span schema/nesting, Perfetto export, registry
thread-safety, legacy-counter parity, the flight recorder, strict
Prometheus-text conformance, and the observational contract (artifact
bytes identical with telemetry on or off)."""

import json
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

import jax

from repro.core import metrics as MX
from repro.data import synth
from repro.experiments import cache as artifact_cache
from repro.experiments import engine
from repro.experiments import runner
from repro.experiments import run as run_cli
from repro.experiments import spec as spec_mod
from repro.experiments.spec import (DatasetSpec, EpsilonSpec, JobSpec,
                                    SweepSpec)
from repro.service.api import AdvisorService, ProbeRequest
from repro.service.queue import AdmissionQueue
from repro.telemetry import RECORDER, MetricsRegistry, metrics, trace
from repro.telemetry import __main__ as telemetry_cli
from repro.telemetry.metrics import parse_prometheus_text
from repro.telemetry.recorder import FlightRecorder

KEY = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test starts and ends with tracing disabled — a leaked active
    tracer would silently put every later test on the traced path."""
    trace.stop()
    yield
    trace.stop()


def tiny_spec(name, **kw):
    kw.setdefault("ms", (1, 2))
    kw.setdefault("iters", 40)
    kw.setdefault("eval_every", 20)
    kw.setdefault("datasets",
                  {"d0": DatasetSpec("higgs_like", {"n": 96, "d": 8})})
    kw.setdefault("jobs", (JobSpec("minibatch", "d0"),))
    return SweepSpec(name=name, **kw).validate()


# ---------------------------------------------------------------------------
# span tracer: schema, nesting, export
# ---------------------------------------------------------------------------

def test_span_schema_nesting_and_export(tmp_path):
    """Spans export as Chrome-trace "X" events with the required keys;
    children are contained in their parent's interval and carry depth."""
    trace.start()
    with trace.span("sweep", spec="demo"):
        with trace.span("bucket", m_pad=4):
            with trace.span("compile"):
                pass
            with trace.span("execute"):
                pass
        with trace.span("store"):
            pass
    trace.stop()
    path = trace.export(str(tmp_path / "t.json"))
    payload = json.load(open(path))          # Perfetto-loadable JSON object
    evs = payload["traceEvents"]
    assert len(evs) == 5
    for e in evs:
        for k in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert k in e, (k, e)
        assert e["ph"] == "X"
    by = {e["name"]: e for e in evs}
    assert by["sweep"]["args"]["depth"] == 0
    assert by["bucket"]["args"]["depth"] == 1
    assert by["compile"]["args"]["depth"] == 2
    assert by["bucket"]["args"]["m_pad"] == 4
    # containment: child interval inside parent interval
    for child, parent in (("bucket", "sweep"), ("compile", "bucket"),
                          ("execute", "bucket"), ("store", "sweep")):
        c, p = by[child], by[parent]
        assert c["ts"] >= p["ts"] - 1e-6
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-6
    # the CLI validator accepts it and scopes to the sweep root
    s = telemetry_cli.summarize(path, root="sweep")
    assert s["n_events"] == 5
    assert s["last_sweep"]["root"] == "sweep"
    assert set(s["last_sweep"]["phases"]) == {"bucket", "compile",
                                             "execute", "store"}


def test_disabled_spans_are_shared_noops():
    """With no tracer installed, span() returns one shared no-op object:
    nothing is allocated or recorded on the disabled hot path."""
    assert trace.active() is None and not trace.enabled()
    s1, s2 = trace.span("a", x=1), trace.span("b")
    assert s1 is s2
    with s1 as s:
        s.set(anything=True)


def test_spans_nest_per_thread():
    """Concurrent threads carry independent span stacks (contextvars):
    each thread's spans sit at depth 0/1 on its own tid."""
    trace.start()

    def work(i):
        with trace.span("outer", thread=i):
            with trace.span("inner", thread=i):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tracer = trace.stop()
    evs = tracer.events
    assert len(evs) == 8
    for i in range(4):
        mine = [e for e in evs if e["args"]["thread"] == i]
        assert sorted(e["args"]["depth"] for e in mine) == [0, 1]
        assert len({e["tid"] for e in mine}) == 1


_FORK_PID_SCRIPT = """
import json, os, sys
from repro.telemetry import trace
trace.start()
r, w = os.pipe()
child = os.fork()
if child == 0:
    with trace.span("child"):
        pass
    ev = [e for e in trace.active().events if e["name"] == "child"][0]
    os.write(w, json.dumps([os.getpid(), ev["pid"]]).encode())
    os._exit(0)
os.close(w)
got = json.loads(os.read(r, 256))
os.waitpid(child, 0)
with trace.span("parent"):
    pass
ev = [e for e in trace.stop().events if e["name"] == "parent"][0]
print(json.dumps({"child": got, "parent": [os.getpid(), ev["pid"]]}))
"""


def test_span_pid_is_the_recording_process():
    """Spans carry the id of the process that records them, in the parent
    and in a child forked while a tracer runs (the id is read once per
    process, not per span).  Run in a fresh interpreter without jax, which
    must not fork once its threads are up."""
    import os
    import pathlib
    import subprocess
    import sys
    src = str(pathlib.Path(trace.__file__).parents[2])
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", _FORK_PID_SCRIPT], env=env,
                         capture_output=True, text=True, check=True,
                         timeout=60)
    got = json.loads(out.stdout)
    for real, recorded in got.values():
        assert recorded == real
    assert got["child"][0] != got["parent"][0]


def test_phase_breakdown_coverage_math():
    """Union coverage merges overlaps; the root's own span is excluded
    from the phase table."""
    mk = lambda name, ts, dur, depth: {
        "name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1, "tid": 1,
        "args": {"depth": depth}}
    evs = [mk("sweep", 0.0, 100.0, 0),
           mk("job", 0.0, 60.0, 1), mk("job", 50.0, 40.0, 1)]
    bd = trace.phase_breakdown(evs, root="sweep")
    assert bd["root"] == "sweep"
    assert bd["coverage"] == pytest.approx(0.9)      # [0,60)+[50,90) = 90
    assert set(bd["phases"]) == {"job"}
    assert bd["phases"]["job"]["count"] == 2
    # without a root: depth-0 coverage over the trace wall
    bd0 = trace.phase_breakdown(evs)
    assert bd0["coverage"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_exact_under_threads():
    """6 threads x 2000 increments land exactly 12000 — the locked
    registry fixes the legacy racy `+= 1` module globals."""
    reg = MetricsRegistry()
    c = reg.counter("race_total")

    def hammer():
        for _ in range(2000):
            c.inc()

    threads = [threading.Thread(target=hammer) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 12000


def test_registry_kinds_labels_and_exposition():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", help="requests")
    c.inc(3)
    assert reg.counter("reqs_total") is c            # get-or-create
    with pytest.raises(TypeError):                   # kind clash
        reg.gauge("reqs_total")
    with pytest.raises(ValueError):                  # counters are monotone
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(5); g.set_max(3)
    assert g.value == 5
    g.set_max(9)
    assert g.value == 9
    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0),
                      labels={"tier": "analytic"})
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == {"0.01": 1, "0.1": 2, "1.0": 3}  # cumulative
    assert snap["+inf"] == 4 and snap["count"] == 4
    txt = reg.render_prometheus()
    assert "# TYPE reqs_total counter" in txt
    assert "reqs_total 3" in txt
    assert 'lat_seconds_bucket{le="0.1",tier="analytic"} 2' in txt
    assert 'lat_seconds_count{tier="analytic"} 4' in txt
    d = reg.to_dict(prefix="reqs")
    assert d == {"reqs_total": 3}


# ---------------------------------------------------------------------------
# Prometheus text-format conformance (the strict parser is the oracle)
# ---------------------------------------------------------------------------

def test_render_prometheus_roundtrips_through_strict_parser():
    """What render_prometheus emits, a conformant scraper can read back:
    TYPE/HELP headers per family, escaped label values round-trip, and
    histogram families satisfy the cumulative/+Inf/_sum/_count
    invariants."""
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", help='finished jobs ("stored")',
                    labels={"status": 'we"ird\\path\nx'})
    c.inc(7)
    reg.gauge("depth_now", help="current depth").set(2.5)
    h = reg.histogram("lat_seconds", help="latency",
                      buckets=(0.01, 0.1, 1.0), labels={"tier": "a"})
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render_prometheus()
    fams = parse_prometheus_text(text)
    assert fams["jobs_total"]["type"] == "counter"
    assert fams["jobs_total"]["help"].startswith("finished jobs")
    name, labels, value = fams["jobs_total"]["samples"][0]
    assert labels == {"status": 'we"ird\\path\nx'}    # escaping round-trips
    assert value == 7
    assert fams["depth_now"]["samples"][0][2] == 2.5
    hist = fams["lat_seconds"]
    assert hist["type"] == "histogram"
    by_name = {}
    for n, ls, v in hist["samples"]:
        by_name.setdefault(n, []).append((ls, v))
    assert [v for ls, v in by_name["lat_seconds_bucket"]] == [1, 2, 3, 4]
    assert by_name["lat_seconds_bucket"][-1][0]["le"] == "+Inf"
    assert by_name["lat_seconds_count"][0][1] == 4
    assert by_name["lat_seconds_sum"][0][1] == pytest.approx(5.555)


def test_metric_and_label_name_validation():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        reg.counter("2starts_with_digit")
    with pytest.raises(ValueError):
        reg.counter("ok_total", labels={"bad-label": "v"})
    reg.counter("rule:recorded_total")          # colons are legal in names


@pytest.mark.parametrize("bad, msg", [
    ("x_total 3", "newline"),                              # no trailing \n
    ("orphan_metric 1\n", "no preceding # TYPE"),
    ("# TYPE a counter\na 1\n# TYPE a counter\n", "duplicate TYPE"),
    ("# TYPE a counter\na -2\n", "negative"),
    ("# TYPE a wat\n", "unknown type"),
    ("# TYPE a counter\na{l=\"v\" 1\n", "malformed"),
    # histogram invariants
    ("# TYPE h histogram\n"
     'h_bucket{le="1.0"} 2\nh_bucket{le="+Inf"} 3\nh_sum 1\n',
     "missing _sum or _count"),
    ("# TYPE h histogram\n"
     'h_bucket{le="1.0"} 5\nh_bucket{le="+Inf"} 3\nh_sum 1\nh_count 3\n',
     "not cumulative"),
    ("# TYPE h histogram\n"
     'h_bucket{le="1.0"} 2\nh_sum 1\nh_count 2\n', r"\+Inf"),
    ("# TYPE h histogram\n"
     'h_bucket{le="1.0"} 2\nh_bucket{le="+Inf"} 3\nh_sum 1\nh_count 9\n',
     "!= _count"),
])
def test_parser_rejects_nonconformant_text(bad, msg):
    with pytest.raises(ValueError, match=msg):
        parse_prometheus_text(bad)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_seq_and_cursor():
    rec = FlightRecorder(max_events=4, max_spans=2)
    for i in range(7):
        rec.publish("probe", i=i)
    snap = rec.snapshot()
    assert snap["seq"] == 7 and snap["published"] == 7
    # bounded ring: only the newest 4 events are held, oldest first
    assert [e["i"] for e in snap["events"]] == [3, 4, 5, 6]
    # cursor: only events strictly newer than `since`
    tail = rec.snapshot(since=5)
    assert [e["i"] for e in tail["events"]] == [5, 6]
    # limit keeps the newest
    lim = rec.snapshot(limit=2)
    assert [e["i"] for e in lim["events"]] == [5, 6]
    rec.clear()
    assert rec.snapshot()["events"] == []
    rec.publish("after_clear")
    assert rec.snapshot()["seq"] == 8        # seq never replays


def test_recorder_mirrors_spans_only_while_tracing():
    """The span sink feeds RECORDER only while a tracer is installed —
    with tracing off the span ring stays untouched."""
    seq0 = RECORDER.snapshot()["seq"]
    with trace.span("untraced"):
        pass
    assert RECORDER.snapshot(since=seq0)["spans"] == []
    trace.start()
    with trace.span("traced_probe", x=1):
        pass
    trace.stop()
    spans = RECORDER.snapshot(since=seq0)["spans"]
    assert [s["name"] for s in spans] == ["traced_probe"]
    assert spans[0]["args"]["x"] == 1


def test_run_sweep_publishes_flight_events(tmp_path):
    """A computed sweep leaves its progress trail in the recorder:
    sweep_started -> job_started -> job_stored (per job) -> sweep_stored,
    plus the engine's grid pad-waste event; a cache hit publishes
    nothing."""
    spec = tiny_spec("tel_flight", jobs=(JobSpec("minibatch", "d0"),
                                         JobSpec("hogwild", "d0")))
    seq0 = RECORDER.snapshot()["seq"]
    runner.run_sweep(spec, cache_dir=str(tmp_path / "c"))
    evs = RECORDER.snapshot(since=seq0)["events"]
    kinds = [e["kind"] for e in evs]
    assert kinds[0] == "sweep_started"
    assert kinds[-1] == "sweep_stored"
    assert kinds.count("job_started") == 2
    assert kinds.count("job_stored") == 2
    assert "grid" in kinds
    started = next(e for e in evs if e["kind"] == "sweep_started")
    assert started["sweep"] == "tel_flight" and started["jobs"] == 2
    stored = [e for e in evs if e["kind"] == "job_stored"]
    assert {e["job"] for e in stored} == \
        {"minibatch:d0", "hogwild:d0"} or all("job" in e for e in stored)
    assert all(e["status"] == "ok" and e["healthy"] for e in stored)
    # cache hit: nothing executes, nothing is published
    seq1 = RECORDER.snapshot()["seq"]
    runner.run_sweep(spec, cache_dir=str(tmp_path / "c"))
    assert RECORDER.snapshot(since=seq1)["events"] == []


def test_race_publishes_psum_event():
    from repro.distributed import hogwild_shards

    ds = synth.make_higgs_like(KEY, n=96, d=8)
    tr, te = ds.split(key=KEY)
    seq0 = RECORDER.snapshot()["seq"]
    r = hogwild_shards.run_hogwild_sharded(tr, te, m=4, iters=80,
                                           gamma=0.05, eval_every=40)
    races = [e for e in RECORDER.snapshot(since=seq0)["events"]
             if e["kind"] == "race"]
    assert len(races) == 1
    assert races[0]["psum_rounds"] == r["psum_rounds"]
    assert races[0]["m"] == 4 and races[0]["faulted"] is False


# ---------------------------------------------------------------------------
# legacy counter parity (engine.JIT_CALLS / runner.SWEEP_COMPUTES aliases)
# ---------------------------------------------------------------------------

def test_jit_calls_alias_counts_cold_vs_cached(tmp_path, cold_programs):
    """The registry-backed engine.JIT_CALLS counts the programs the engine
    builds: one per bucket on a cold sweep, zero on an artifact-cache hit,
    and zero again when a later sweep of the same shapes finds every
    bucket program kept — traced or not."""
    spec = tiny_spec("tel_parity", ms=(1, 2, 4, 8))   # 2 buckets @ ratio 2
    cd = str(tmp_path / "cache")

    j0, s0 = engine.JIT_CALLS, runner.SWEEP_COMPUTES
    runner.run_sweep(spec, cache_dir=cd)
    assert engine.JIT_CALLS - j0 == 2
    assert runner.SWEEP_COMPUTES - s0 == 1

    # cache hit: nothing executes, neither counter moves
    j0, s0 = engine.JIT_CALLS, runner.SWEEP_COMPUTES
    runner.run_sweep(spec, cache_dir=cd)
    assert engine.JIT_CALLS - j0 == 0
    assert runner.SWEEP_COMPUTES - s0 == 0

    # a fresh artifact cache recomputes the sweep, traced: both bucket
    # programs are kept, so it builds none and hits twice
    trace.start()
    j0, h0 = engine.JIT_CALLS, _program_hits()
    runner.run_sweep(spec, cache_dir=str(tmp_path / "cache2"))
    trace.stop()
    assert engine.JIT_CALLS - j0 == 0
    assert _program_hits() - h0 == 2


def _program_hits():
    return metrics.REGISTRY.counter("repro_engine_program_cache_total",
                                    labels={"outcome": "hit"}).value


def test_module_getattr_raises_for_unknown():
    with pytest.raises(AttributeError):
        engine.NO_SUCH_COUNTER
    with pytest.raises(AttributeError):
        runner.NO_SUCH_COUNTER


# ---------------------------------------------------------------------------
# the observational contract
# ---------------------------------------------------------------------------

def test_artifact_bytes_identical_with_tracing(tmp_path):
    """Acceptance: artifacts are byte-identical with telemetry on vs off
    — a traced sweep runs the same jit calls, and no telemetry state
    enters the payload."""
    spec = tiny_spec("tel_bytes", ms=(1, 2, 4),
                     epsilon=EpsilonSpec(probe_m=2))
    fp = spec_mod.fingerprint(spec)

    runner.run_sweep(spec, cache_dir=str(tmp_path / "off"))
    trace.start()
    runner.run_sweep(spec, cache_dir=str(tmp_path / "on"))
    trace.stop()

    raw_off = open(artifact_cache.artifact_path(
        str(tmp_path / "off"), spec.name, fp), "rb").read()
    raw_on = open(artifact_cache.artifact_path(
        str(tmp_path / "on"), spec.name, fp), "rb").read()
    assert raw_on == raw_off


def test_trace_covers_sweep_with_bucket_split(tmp_path, cold_programs):
    """Acceptance: a traced sweep's root span attributes >=95% of the
    traced wall-clock; each bucket span holds the lower and compile of its
    program, and each grid holds the fetch that waits for the device."""
    spec = tiny_spec("tel_cov", ms=(1, 2, 4))
    trace.start()
    runner.run_sweep(spec, cache_dir=str(tmp_path / "c"))
    trace.stop()
    path = trace.export(str(tmp_path / "trace.json"))
    evs = json.load(open(path))["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"sweep", "datasets", "dataset_build", "characters", "job",
            "grid", "bucket", "jaxpr_trace", "lower", "compile", "fetch",
            "store"} <= names
    assert "execute" not in names
    overall = trace.phase_breakdown(evs)
    assert overall["coverage"] >= 0.95
    scoped = trace.phase_breakdown(evs, root="sweep")
    assert scoped["root"] == "sweep"

    def children(parent):
        return [e["name"] for e in evs
                if e["ts"] >= parent["ts"] - 1e-6
                and e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-6
                and e["args"]["depth"] == parent["args"]["depth"] + 1]

    buckets = [e for e in evs if e["name"] == "bucket"]
    assert buckets
    for b in buckets:
        inside = children(b)
        assert "compile" in inside and "lower" in inside
    for g in (e for e in evs if e["name"] == "grid"):
        assert "fetch" in children(g)


def test_compile_phase_spans_nest_in_their_bucket_with_fun_name(
        tmp_path, cold_programs):
    """JAX's compile events become spans one level below the bucket that
    triggered them, named for the bucket's program."""
    spec = tiny_spec("tel_phases", ms=(1, 2, 4))
    tracer = trace.start()
    runner.run_sweep(spec, cache_dir=str(tmp_path / "c"))
    trace.stop()
    evs = tracer.events
    buckets = [e for e in evs if e["name"] == "bucket"]
    assert [(b["args"]["algorithm"], b["args"]["m_pad"]) for b in buckets] \
        == [("minibatch", 2), ("minibatch", 4)]
    for b in buckets:
        program = f"bucket_minibatch_m{b['args']['m_pad']}"
        lo, hi = b["ts"], b["ts"] + b["dur"]
        mine = {e["name"]: e for e in evs
                if e["args"].get("fun_name") in (program, f"jit({program})")}
        assert set(mine) == {"jaxpr_trace", "lower", "compile"}
        for e in mine.values():
            assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi
        assert mine["compile"]["args"]["depth"] == b["args"]["depth"] + 1
        assert mine["lower"]["args"]["depth"] == b["args"]["depth"] + 1
    # arg_bytes: the train and test arrays, plus draws that widen with
    # the bucket's m_pad, counted from shapes; both programs were built
    assert [b["args"]["cached"] for b in buckets] == [False, False]
    ds = spec.datasets["d0"]
    tr, te = spec_mod.split_dataset(ds, spec_mod.build_dataset(ds),
                                    spec.split_seed)
    data = sum(a.nbytes for a in (tr.X, tr.y, te.X, te.y))
    small, wide = (b["args"]["arg_bytes"] for b in buckets)
    assert data < small < wide
    # a jaxpr trace nested in another sits one level below it
    top = next(e for e in evs if e["name"] == "jaxpr_trace"
               and e["args"]["fun_name"] == "bucket_minibatch_m2")
    inner = [e for e in evs if e["name"] == "jaxpr_trace"
             and top["ts"] <= e["ts"] and e is not top
             and e["ts"] + e["dur"] <= top["ts"] + top["dur"]]
    assert inner
    assert all(e["args"]["depth"] > top["args"]["depth"] for e in inner)


@pytest.mark.parametrize("traced", [False, True], ids=["off", "on"])
def test_jax_compile_counter_matches_backend_events(traced, tmp_path,
                                                   cold_programs):
    """repro_jax_compiles_total rises by exactly the number of JAX
    backend-compile events, whether or not a tracer runs; the phase
    seconds rise with them."""
    seen = []

    def on(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    compiles = metrics.REGISTRY.counter("repro_jax_compiles_total")
    seconds = metrics.REGISTRY.counter("repro_jax_compile_seconds_total",
                                       labels={"phase": "compile"})
    c0, s0 = compiles.value, seconds.value
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        if traced:
            trace.start()
        runner.run_sweep(tiny_spec(f"tel_count_{traced}", ms=(1, 3)),
                         cache_dir=str(tmp_path / "c"))
    finally:
        trace.stop()
        jax.monitoring.unregister_event_duration_listener(on)
    assert seen
    assert compiles.value - c0 == len(seen)
    assert seconds.value - s0 == pytest.approx(sum(seen))


def test_tracing_off_makes_no_span_or_annotation(tmp_path, monkeypatch):
    """With no tracer, spans are the shared no-op, no annotation is
    entered and no compile event becomes a span; trace.start() enters
    one anchor annotation and labels each span's annotation."""
    labels = []

    class Recorded:
        def __init__(self, label):
            labels.append(label)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "_ANNOTATION", Recorded)
    seq0 = RECORDER.snapshot()["seq"]
    assert trace.span("bucket", algorithm="minibatch", m_pad=8) \
        is trace.span("other")
    runner.run_sweep(tiny_spec("tel_quiet"), cache_dir=str(tmp_path / "c"))
    assert labels == []
    assert RECORDER.snapshot(since=seq0)["spans"] == []

    tracer = trace.start()
    with trace.span("bucket", algorithm="minibatch", m_pad=8):
        pass
    trace.stop()
    assert labels == [trace.ANCHOR, "bucket:minibatch/m8"]
    other = tracer.payload()["otherData"]
    assert other["anchor"] == trace.ANCHOR
    assert other["t0_ns"] <= other["anchor_ns"]


def test_bucket_programs_are_named(tmp_path, cold_programs):
    """The engine jits each bucket as bucket_<algorithm>_m<m_pad>, so the
    profiler's modules line reads jit_bucket_<algorithm>_m<m_pad>."""
    names = []

    def on(event, duration, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        runner.run_sweep(
            tiny_spec("tel_names", ms=(1, 2, 4),
                      jobs=(JobSpec("minibatch", "d0"),
                            JobSpec("hogwild", "d0"))),
            cache_dir=str(tmp_path / "c"))
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    buckets = sorted(n for n in names if n.startswith("jit(bucket_"))
    assert buckets == ["jit(bucket_hogwild_m4)", "jit(bucket_minibatch_m2)",
                       "jit(bucket_minibatch_m4)"]


def test_sequential_path_identical_traced(tmp_path, cold_programs):
    """use_vmap=False (repeated jit calls) takes the plain-span path —
    same losses traced or not, and no per-call recompiles: one program
    serves every m, and the traced rerun reuses it."""
    ds = synth.make_higgs_like(KEY, n=96, d=8)
    tr, te = ds.split(key=KEY)
    kw = dict(iters=40, eval_every=20, use_vmap=False)
    j0 = engine.JIT_CALLS
    r_off = engine.sweep("minibatch", tr, te, [1, 2, 4], **kw)
    assert engine.JIT_CALLS - j0 == 1      # one jit serves every m
    trace.start()
    j0, h0 = engine.JIT_CALLS, _program_hits()
    r_on = engine.sweep("minibatch", tr, te, [1, 2, 4], **kw)
    tracer = trace.stop()
    assert engine.JIT_CALLS - j0 == 0
    assert _program_hits() - h0 == 1
    np.testing.assert_array_equal(np.asarray(r_off["losses"]),
                                  np.asarray(r_on["losses"]))
    assert sum(e["name"] == "grid_member" for e in tracer.events) == 3


# ---------------------------------------------------------------------------
# readouts and the mesh's data placement (Table II at real-sim's width)
# ---------------------------------------------------------------------------

def _inside(child, parent):
    return (parent["ts"] <= child["ts"] and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"]
            and child["args"]["depth"] > parent["args"]["depth"])


def test_predict_span_per_predicted_job(tmp_path):
    """Each job that asks for a prediction has one predict span inside
    its readout, naming the predictor and the rows and width it read."""
    spec = tiny_spec(
        "tel_predict", ms=(1, 2),
        datasets={"d0": DatasetSpec("higgs_like", {"n": 96, "d": 8}),
                  "s0": DatasetSpec("realsim_like",
                                    {"n": 80, "d": 40, "density": 0.1})},
        jobs=(JobSpec("minibatch", "d0", predict=True),
              JobSpec("ecd_psgd", "d0"),
              JobSpec("dadm", "s0", predict=True, predict_rows=50)))
    tracer = trace.start()
    runner.run_sweep(spec, cache_dir=str(tmp_path / "c"))
    trace.stop()
    evs = tracer.events
    predicts = [e for e in evs if e["name"] == "predict"]
    assert [(p["args"]["predictor"], p["args"]["rows"], p["args"]["d"])
            for p in predicts] == [("sync", 96, 8), ("dadm", 50, 40)]
    readouts = [e for e in evs if e["name"] == "readout"]
    for p, key in zip(predicts, ("minibatch/d0", "dadm/s0")):
        assert any(r["args"]["key"] == key and _inside(p, r)
                   for r in readouts)
    # DADM's predictor counts diversity over its rows, inside its span
    assert any(e["name"] == "diversity" and e["args"]["rows"] == 50
               and _inside(e, predicts[1]) for e in evs)


@pytest.mark.parametrize("path", ["device", "host", "fallback"])
def test_diversity_span_and_counter_count_the_host_pull(path, monkeypatch):
    """A device array is counted on the device and pulls nothing; a NumPy
    array, or a device count that falls back (here every row hashes
    alike), pulls its n*d*4 bytes.  The span names the path and the
    bytes, and each count lands in its path's counter."""
    fetched = metrics.counter("repro_host_fetch_bytes_total",
                              labels={"site": "diversity"})
    counted = metrics.counter("repro_diversity_counts_total",
                              labels={"path": path})
    X = synth.make_realsim_like(KEY, n=64, d=300, density=0.05).X
    pulled = 0 if path == "device" else 64 * 300 * 4
    if path == "host":
        X = np.asarray(X)
    if path == "fallback":
        monkeypatch.setattr(MX, "_hash_weights",
                            lambda d: jax.numpy.zeros((2, d), np.uint32))
    MX._row_kinds.clear_cache()
    before, n0 = fetched.value, counted.value
    try:
        tracer = trace.start()
        kinds = MX.diversity(X)
        trace.stop()
    finally:
        MX._row_kinds.clear_cache()
    (span,) = [e for e in tracer.events if e["name"] == "diversity"]
    assert (span["args"]["rows"], span["args"]["d"]) == (64, 300)
    assert span["args"]["path"] == path
    assert span["args"]["bytes"] == pulled
    assert fetched.value - before == pulled
    assert counted.value - n0 == 1
    assert kinds == int(np.unique(np.round(np.asarray(X), 6),
                                  axis=0).shape[0])


MESH_PLACEMENT = """
    import tempfile
    from repro.experiments import cache as artifact_cache
    from repro.experiments import runner, spec as spec_mod
    from repro.experiments.spec import (DatasetSpec, EpsilonSpec, JobSpec,
                                        SweepSpec)
    from repro.telemetry import instrument, metrics, trace

    spec = SweepSpec(
        name="tel_mesh", ms=(1, 2, 4), iters=40, eval_every=20, n_seeds=2,
        datasets={"d0": DatasetSpec("higgs_like", {"n": 96, "d": 8}),
                  "s0": DatasetSpec("realsim_like",
                                    {"n": 80, "d": 40, "density": 0.1})},
        jobs=(JobSpec("minibatch", "d0", predict=True),
              JobSpec("dadm", "s0", predict=True, predict_rows=50)),
        epsilon=EpsilonSpec(probe_m=2)).validate()
    fp = spec_mod.fingerprint(spec)
    replicated = metrics.counter("repro_mesh_replicated_bytes_total")
    with tempfile.TemporaryDirectory() as off, \\
            tempfile.TemporaryDirectory() as on:
        runner.run_sweep(spec, cache_dir=off, mesh=4)
        before = replicated.value
        tracer = trace.start()
        res = runner.run_sweep(spec, cache_dir=on, mesh=4)
        trace.stop()
        assert res["execution"]["sharded"]
        raw = [open(artifact_cache.artifact_path(d, spec.name, fp),
                    "rb").read() for d in (off, on)]
    assert raw[0] == raw[1]
    evs = tracer.events
    reps = [e for e in evs if e["name"] == "replicate"]
    buckets = [e for e in evs if e["name"] == "mesh_bucket"]
    # one replicate per bucket, just before its dispatch: minibatch's
    # buckets [1, 2] and [4], DADM's one flat bucket
    assert [b["args"]["m_pad"] for b in buckets] == [2, 4, 4]
    assert len(reps) == len(buckets)
    data = {}
    for name, ds in spec.datasets.items():
        tr, te = spec_mod.split_dataset(ds, spec_mod.build_dataset(ds),
                                        spec.split_seed)
        data[name] = instrument.nbytes((tr.X, tr.y, te.X, te.y))
    # each bucket's arguments: its dataset, and draws of int32 indices
    # (seeds x iters x m_pad; DADM's x its local batch of 8)
    want = [data["d0"] + 2 * 40 * 2 * 4, data["d0"] + 2 * 40 * 4 * 4,
            data["s0"] + 2 * 40 * 4 * 8 * 4]
    for r, b, w in zip(reps, buckets, want):
        assert r["args"]["devices"] == 4
        assert r["args"]["bytes"] == b["args"]["arg_bytes"] == w
        assert r["ts"] + r["dur"] <= b["ts"]
    assert replicated.value - before == 4 * sum(want)
    print("MESH_PLACEMENT_OK")
"""


@pytest.mark.slow
def test_replicate_span_and_counter_on_four_devices():
    """On 4 virtual devices, a sharded sweep replicates each bucket's
    arguments under one replicate span (bytes of one copy, from their
    shapes), the counter rises by bytes x 4, and the artifact's bytes are
    the same traced and untraced."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import jax
        assert len(jax.devices()) == 4
    """) + textwrap.dedent(MESH_PLACEMENT)
    r = subprocess.run([sys.executable, "-c", script], cwd=".",
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "MESH_PLACEMENT_OK" in r.stdout


# ---------------------------------------------------------------------------
# instrumented subsystems
# ---------------------------------------------------------------------------

def test_queue_high_water_and_shed():
    q = AdmissionQueue(depth=3)
    assert q.try_admit() and q.try_admit()
    assert q.stats()["high_water"] == 2
    q.release()
    assert q.try_admit()                    # back to 2 in service
    assert q.stats()["high_water"] == 2     # high water holds the max
    assert q.try_admit()                    # 3/3
    assert not q.try_admit()                # shed
    st = q.stats()
    assert st == {"depth": 3, "in_service": 3, "admitted": 4, "shed": 1,
                  "high_water": 3}
    for _ in range(3):
        q.release()
    assert q.stats()["in_service"] == 0
    assert q.stats()["high_water"] == 3


def test_queue_wait_histogram_and_stats_reset():
    """try_admit() returns the admission stamp; handing it back through
    release(admitted_at=...) observes repro_service_queue_wait_seconds,
    and stats(reset=True) re-arms high_water to current occupancy so
    scrapers see per-window peaks instead of lifetime ones."""
    h = metrics.REGISTRY.histogram("repro_service_queue_wait_seconds")
    n0 = h.count
    q = AdmissionQueue(depth=2)
    stamp = q.try_admit()
    assert isinstance(stamp, float)
    q.release(admitted_at=stamp)
    assert h.count - n0 == 1
    # release without a stamp (legacy callers) must not observe
    assert q.try_admit()
    q.release()
    assert h.count - n0 == 1

    # windowed high-water: two in service, one released -> lifetime peak 2
    s1 = q.try_admit()
    s2 = q.try_admit()
    q.release(admitted_at=s2)
    st = q.stats(reset=True)
    assert st["high_water"] == 2            # pre-reset view is returned
    assert q.stats()["high_water"] == 1     # re-armed to current occupancy
    q.release(admitted_at=s1)
    assert h.count - n0 == 3


def test_psum_round_accounting():
    """Racing-mode comm accounting: psum_rounds = scheduled syncs
    (R_total // sync_every) + one forced reconcile per eval block."""
    from repro.distributed import hogwild_shards

    ds = synth.make_higgs_like(KEY, n=96, d=8)
    tr, te = ds.split(key=KEY)
    kw = dict(m=4, iters=240, gamma=0.05, eval_every=40)
    # n_evals=6, rounds_per_eval=10, R_total=60
    c0 = metrics.REGISTRY.counter(
        "repro_distributed_psum_rounds_total").value
    r1 = hogwild_shards.run_hogwild_sharded(tr, te, sync_every=1, **kw)
    assert r1["psum_rounds"] == 60 + 6
    r4 = hogwild_shards.run_hogwild_sharded(tr, te, sync_every=4, **kw)
    assert r4["psum_rounds"] == 15 + 6
    delta = metrics.REGISTRY.counter(
        "repro_distributed_psum_rounds_total").value - c0
    assert delta == 66 + 21
    # the compile-counter alias works here too
    assert isinstance(hogwild_shards.JIT_CALLS, int)


def test_service_stats_telemetry_block(tmp_path):
    """AdvisorService.stats() carries the registry-backed telemetry
    block: queue gauges/counters and the tier latency + confidence
    histograms observed by probe_batch."""
    svc = AdvisorService(cache_dir=str(tmp_path / "cache"), sweep_iters=50,
                         sweep_eval_every=10, n_slots=4)
    lat = metrics.REGISTRY.histogram("repro_service_tier_latency_seconds",
                                     labels={"tier": "analytic"})
    n0 = lat.count
    resp = svc.probe(ProbeRequest(X=np.random.default_rng(0)
                                  .normal(size=(40, 6)),
                                  escalate=False))
    assert resp.tier == "analytic"
    assert lat.count - n0 == 1
    st = svc.stats()
    assert st["queue"]["high_water"] >= 1
    tel = st["telemetry"]
    assert any(k.startswith("repro_service_tier_latency_seconds")
               for k in tel)
    assert tel["repro_service_queue_high_water"] >= 1
    conf = metrics.REGISTRY.histogram(
        "repro_service_confidence",
        buckets=(0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0))
    assert conf.count >= 1


# ---------------------------------------------------------------------------
# CLI surfacing
# ---------------------------------------------------------------------------

def test_run_cli_trace_flag(tmp_path, capsys):
    """--trace writes a validating Chrome-trace JSON whose root sweep
    span clears the CI coverage gate; --metrics dumps Prometheus text."""
    out = str(tmp_path / "cli_trace.json")
    rc = run_cli.main(["--spec", "upper_bound", "--quick", "--iters", "40",
                       "--n", "96", "--cache-dir",
                       str(tmp_path / "cache"), "--trace", out,
                       "--metrics"])
    assert rc == 0
    stdout = capsys.readouterr().out
    assert "repro_sweep_computes_total" in stdout
    assert telemetry_cli.main(
        ["--summarize", out, "--min-coverage", "0.95"]) == 0
    # re-validate the payload shape end to end
    s = telemetry_cli.summarize(out, root="sweep")
    assert s["last_sweep"]["root"] == "sweep"
    assert s["overall"]["coverage"] >= 0.95


def test_telemetry_cli_rejects_bad_trace(tmp_path, capsys):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"name": "x", "ph": "X"}]}))
    assert telemetry_cli.main(["--summarize", str(bad)]) == 2
    assert "missing required keys" in capsys.readouterr().err
