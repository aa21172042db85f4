"""HTTP transport + live observability plane (PR-10 acceptance).

The server is a thin codec over the in-process `AdvisorService` the rest
of the suite pins — these tests check the wire contract (shapes, status
codes, strict Prometheus exposition, flight-recorder tailing) and the
observational contract (artifact bytes identical with the transport and
recorder active vs absent).
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.experiments import cache as artifact_cache
from repro.experiments import runner
from repro.experiments import spec as spec_mod
from repro.experiments.spec import DatasetSpec, JobSpec, SweepSpec
from repro.service.api import AdvisorService
from repro.service.http import ServiceServer
from repro.telemetry import trace
from repro.telemetry.metrics import parse_prometheus_text
from repro.telemetry.recorder import RECORDER

RNG = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _clean_slate(monkeypatch):
    trace.stop()
    # /trace falls back to the last tracer; one left by an earlier test
    # file in this worker process must not leak into these payloads
    monkeypatch.setattr(trace, "_LAST", None)
    RECORDER.clear()
    yield
    trace.stop()


def make_service(tmp_path, **kw):
    kw.setdefault("cache_dir", str(tmp_path / "cache"))
    kw.setdefault("sweep_iters", 50)
    kw.setdefault("sweep_eval_every", 10)
    kw.setdefault("n_slots", 4)
    return AdvisorService(**kw)


def http_get(url):
    """(status, headers, body-bytes) — HTTPError is a response here."""
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def http_post_json(url, payload):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# ---------------------------------------------------------------------------
# lifecycle + routing
# ---------------------------------------------------------------------------

def test_server_lifecycle_ephemeral_port(tmp_path):
    """port=0 binds an ephemeral port; the context manager serves while
    open and releases the socket on exit."""
    svc = make_service(tmp_path)
    with ServiceServer(svc) as srv:
        assert srv.port > 0
        assert srv.url == f"http://127.0.0.1:{srv.port}"
        status, _, _ = http_get(srv.url + "/healthz")
        assert status == 200
        # second start() is a no-op, not a second thread
        assert srv.start() is srv
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(srv.url + "/healthz", timeout=2)


def test_unknown_route_404_lists_routes(tmp_path):
    with ServiceServer(make_service(tmp_path)) as srv:
        status, _, body = http_get(srv.url + "/nope")
        assert status == 404
        err = json.loads(body)["error"]
        assert "/probe" in err and "/metrics" in err
        status, resp = http_post_json(srv.url + "/metrics", {})
        assert status == 404                      # GET-only route


def test_bad_json_and_unknown_fields_400(tmp_path):
    with ServiceServer(make_service(tmp_path)) as srv:
        req = urllib.request.Request(
            srv.url + "/probe", data=b"{not json",
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400
        assert "invalid JSON" in json.loads(ei.value.read())["error"]

        status, resp = http_post_json(srv.url + "/probe",
                                      {"X": [[1.0]], "bogus": 1})
        assert status == 400 and "bogus" in resp["error"]
        status, resp = http_post_json(
            srv.url + "/probe",
            {"dataset": {"generator": "higgs_like", "surprise": True}})
        assert status == 400 and "surprise" in resp["error"]
        status, resp = http_post_json(
            srv.url + "/probe", {"dataset": {"generator": "no_such_gen",
                                             "kwargs": {"n": 8, "d": 2}}})
        assert status == 400 and "invalid dataset spec" in resp["error"]
        status, resp = http_post_json(srv.url + "/probe_batch",
                                      {"oops": []})
        assert status == 400
        status, resp = http_post_json(srv.url + "/flight?since=xyz", {})
        assert status == 404                      # POST to a GET route
        status, _, body = http_get(srv.url + "/flight?since=xyz")
        assert status == 400


def test_metrics_only_plane_answers_503():
    """run.py --serve mode: no advisor behind the transport — probes get
    a structured 503, the observability endpoints still serve."""
    with ServiceServer(None) as srv:
        status, resp = http_post_json(srv.url + "/probe", {"X": [[1.0]]})
        assert status == 503 and "metrics-only" in resp["error"]
        status, resp = http_post_json(srv.url + "/probe_batch",
                                      {"requests": []})
        assert status == 503
        status, _, body = http_get(srv.url + "/healthz")
        health = json.loads(body)
        assert status == 200
        assert health["service"] is False and health["queue"] is None
        assert health["status"] == "ok"
        status, _, _ = http_get(srv.url + "/metrics")
        assert status == 200


# ---------------------------------------------------------------------------
# probe round-trips
# ---------------------------------------------------------------------------

def test_probe_roundtrip_analytic(tmp_path):
    svc = make_service(tmp_path)
    with ServiceServer(svc) as srv:
        X = RNG.normal(size=(40, 6)).tolist()
        status, resp = http_post_json(
            srv.url + "/probe",
            {"X": X, "algorithm": "hogwild", "request_id": "wire-1"})
        assert status == 200
        assert resp["status"] == "ok" and resp["tier"] == "analytic"
        assert resp["request_id"] == "wire-1"
        # the transport must not perturb the answer: same probe
        # in-process gives the identical integer m_max per strategy
        from repro.service.api import ProbeRequest
        direct = svc.probe(ProbeRequest(X=np.asarray(X))).to_dict()
        for strat, block in direct["report"].items():
            if isinstance(block, dict) and "predicted_m_max" in block:
                assert resp["report"][strat]["predicted_m_max"] == \
                    block["predicted_m_max"], strat


def test_probe_batch_roundtrip(tmp_path):
    with ServiceServer(make_service(tmp_path)) as srv:
        reqs = [{"X": RNG.normal(size=(30 + 5 * i, 5)).tolist(),
                 "request_id": f"b{i}"} for i in range(3)]
        status, resp = http_post_json(srv.url + "/probe_batch",
                                      {"requests": reqs})
        assert status == 200
        assert [r["request_id"] for r in resp["responses"]] == \
            ["b0", "b1", "b2"]
        assert all(r["status"] == "ok" for r in resp["responses"])


@pytest.mark.slow
def test_escalated_probe_strips_artifact_unless_full(tmp_path):
    """A measured-tier response carries the escalation readout but not
    the bulky artifact — unless the caller opts in with ?full=1."""
    svc = make_service(tmp_path, confidence_threshold=0.9)
    ds = {"generator": "higgs_like", "kwargs": {"n": 64, "d": 8}}
    with ServiceServer(svc) as srv:
        status, resp = http_post_json(srv.url + "/probe", {"dataset": ds})
        assert status == 200 and resp["tier"] == "measured"
        assert "artifact" not in resp["escalation"]
        status, full = http_post_json(srv.url + "/probe?full=1",
                                      {"dataset": ds})
        assert status == 200
        assert "artifact" in full["escalation"]     # cached second sweep


# ---------------------------------------------------------------------------
# the observability plane
# ---------------------------------------------------------------------------

def test_metrics_endpoint_is_strictly_conformant(tmp_path):
    """GET /metrics parses under the strict v0.0.4 parser, advertises
    the exposition content type, and ?prefix= filters families."""
    svc = make_service(tmp_path)
    with ServiceServer(svc) as srv:
        from repro.service.api import ProbeRequest
        svc.probe(ProbeRequest(X=RNG.normal(size=(32, 4))))
        status, headers, body = http_get(srv.url + "/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith(
            "text/plain; version=0.0.4")
        families = parse_prometheus_text(body.decode())
        assert "repro_service_admitted_total" in families
        assert "repro_http_requests_total" in families
        assert families["repro_http_request_seconds"]["type"] == \
            "histogram"
        status, _, body = http_get(srv.url + "/metrics?prefix=repro_http")
        sub = parse_prometheus_text(body.decode())
        assert sub and all(f.startswith("repro_http") for f in sub)


@pytest.mark.slow
def test_metrics_scrape_during_inflight_escalated_sweep(tmp_path):
    """Acceptance: GET /metrics *while* an escalated sweep runs returns
    strictly parseable text carrying engine, cache, queue, and
    psum-round families."""
    import repro.distributed  # noqa: F401 — registers the psum counter

    svc = make_service(tmp_path, confidence_threshold=0.9)
    with ServiceServer(svc) as srv:
        done = threading.Event()

        def escalate():
            http_post_json(srv.url + "/probe", {
                "dataset": {"generator": "higgs_like",
                            "kwargs": {"n": 64, "d": 8}}})
            done.set()

        t = threading.Thread(target=escalate)
        t.start()
        mid_flight = []
        while not done.is_set():
            _, _, body = http_get(srv.url + "/metrics")
            mid_flight.append(parse_prometheus_text(body.decode()))
        t.join(timeout=120)
        assert mid_flight
        last = mid_flight[-1]
        for family in ("repro_engine_jit_compiles_total",
                       "repro_cache_misses_total",
                       "repro_sweep_computes_total",
                       "repro_service_queue_depth",
                       "repro_service_escalations_total",
                       "repro_distributed_psum_rounds_total"):
            assert family in last, family


def test_healthz_reports_queue_and_recorder(tmp_path):
    svc = make_service(tmp_path, n_slots=2)
    with ServiceServer(svc) as srv:
        status, _, body = http_get(srv.url + "/healthz")
        h = json.loads(body)
        assert status == 200 and h["status"] == "ok"
        assert h["service"] is True and h["uptime_s"] >= 0
        assert h["queue"]["depth"] == 32          # service default queue
        assert set(h["recorder"]) == {"seq", "published", "events_held",
                                      "spans_held", "max_events",
                                      "max_spans"}
        assert h["tracing"] is False


def test_flight_endpoint_tails_a_live_sweep(tmp_path):
    """GET /flight?since=N tails a sweep running in another thread: the
    poller sees sweep_started, per-job progress, and sweep_stored, in
    order, without rereading old events."""
    spec = SweepSpec(
        name="http_flight", ms=(1, 2), iters=40, eval_every=20,
        datasets={"d0": DatasetSpec("higgs_like", {"n": 96, "d": 8})},
        jobs=(JobSpec("minibatch", "d0"),
              JobSpec("hogwild", "d0"))).validate()
    with ServiceServer(None) as srv:
        t = threading.Thread(
            target=runner.run_sweep, args=(spec,),
            kwargs={"cache_dir": str(tmp_path / "c")})
        t.start()
        seen, since = [], 0
        while t.is_alive() or not any(
                e["kind"] == "sweep_stored" for e in seen):
            _, _, body = http_get(srv.url + f"/flight?since={since}")
            snap = json.loads(body)
            seen += snap["events"]
            since = snap["seq"]
            if any(e["kind"] == "sweep_stored" for e in seen):
                break
        t.join(timeout=60)
        kinds = [e["kind"] for e in seen
                 if e.get("sweep") == "http_flight" or
                 e["kind"] in ("grid", "race")]
        assert kinds[0] == "sweep_started"
        assert kinds[-1] == "sweep_stored"
        assert kinds.count("job_started") == 2
        assert kinds.count("job_stored") == 2
        # cursor semantics: no event delivered twice
        seqs = [e["seq"] for e in seen]
        assert seqs == sorted(set(seqs))


def test_trace_endpoint_payload_and_drain(tmp_path):
    with ServiceServer(None) as srv:
        status, _, body = http_get(srv.url + "/trace")
        empty = json.loads(body)
        assert status == 200 and empty["traceEvents"] == []
        trace.start()
        with trace.span("sweep", spec="wire"):
            with trace.span("bucket"):
                pass
        status, _, body = http_get(srv.url + "/trace")
        names = [e["name"] for e in json.loads(body)["traceEvents"]]
        assert "sweep" in names and "bucket" in names
        # drain pops: the second drain starts empty
        status, _, body = http_get(srv.url + "/trace?drain=1")
        drained = json.loads(body)
        assert drained["otherData"]["drained"] is True
        assert len(drained["traceEvents"]) == 2
        status, _, body = http_get(srv.url + "/trace?drain=1")
        assert json.loads(body)["traceEvents"] == []
        trace.stop()


# ---------------------------------------------------------------------------
# the observational contract, extended to the transport
# ---------------------------------------------------------------------------

def test_artifact_bytes_identical_under_scraping(tmp_path):
    """PR-9's contract extended: a sweep run while the HTTP plane is up
    and actively scraped (metrics + flight + trace) produces artifacts
    byte-identical to a bare run."""
    spec = SweepSpec(
        name="http_bytes", ms=(1, 2), iters=40, eval_every=20,
        datasets={"d0": DatasetSpec("higgs_like", {"n": 96, "d": 8})},
        jobs=(JobSpec("minibatch", "d0"),)).validate()
    fp = spec_mod.fingerprint(spec)

    runner.run_sweep(spec, cache_dir=str(tmp_path / "off"))

    stop = threading.Event()

    def scrape(url):
        while not stop.is_set():
            http_get(url + "/metrics")
            http_get(url + "/flight")
            http_get(url + "/trace")

    trace.start()
    with ServiceServer(None) as srv:
        t = threading.Thread(target=scrape, args=(srv.url,))
        t.start()
        runner.run_sweep(spec, cache_dir=str(tmp_path / "on"))
        stop.set()
        t.join(timeout=10)
    trace.stop()

    raw_off = open(artifact_cache.artifact_path(
        str(tmp_path / "off"), spec.name, fp), "rb").read()
    raw_on = open(artifact_cache.artifact_path(
        str(tmp_path / "on"), spec.name, fp), "rb").read()
    assert raw_on == raw_off
