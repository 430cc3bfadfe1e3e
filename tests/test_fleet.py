"""Fleet observatory (ISSUE 20): per-process telemetry spooling, the
cross-process aggregator (merged snapshot / Prometheus / chrome-trace),
distributed request tracing over HTTP, and the /admin fleet surface."""
import http.client
import json
import os
import re
import threading
import time
from urllib.parse import urlparse

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs, serving
from paddle_tpu.core import obs_hook
from paddle_tpu.observability import export as obs_export
from paddle_tpu.observability import fleet
from paddle_tpu.testing.chaos import make_dyadic_lm
from paddle_tpu.utils import monitor

# the PR-9 text exposition grammar gate (tools/obs_smoke.py keeps the
# same regex): proc-labelled fleet samples must still parse under it
PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+\-naif]+$")


@pytest.fixture
def spool(tmp_path):
    """Exporter flags on, pointing at a tmp spool; everything restored
    (and the exporter gone) on the way out."""
    old = paddle.get_flags(["obs_spool_dir", "obs_role",
                            "obs_export_interval_s"])
    d = str(tmp_path / "spool")
    paddle.set_flags({"obs_spool_dir": d, "obs_role": "t",
                      "obs_export_interval_s": 60.0})
    yield d
    obs_export.uninstall_exporter()
    paddle.set_flags(old)
    obs.disable()


@pytest.fixture(autouse=True)
def _clean_tracer():
    yield
    obs_export.uninstall_exporter()
    obs.disable()


# ------------------------------------------------- checksummed spool --
def test_checksum_roundtrip_and_corruption():
    body = {"role": "r", "pid": 1, "nested": {"a": [1, 2]}}
    data = obs_export.checksum_wrap(body)
    assert obs_export.checksum_unwrap(data) == body
    doc = json.loads(data)
    doc["body"]["pid"] = 2          # bit-flip after the digest
    with pytest.raises(ValueError, match="checksum mismatch"):
        obs_export.checksum_unwrap(json.dumps(doc).encode())
    with pytest.raises(ValueError):
        obs_export.checksum_unwrap(b'{"no": "digest"}')


def test_exporter_spools_and_read_spool_roundtrip(spool):
    exp = obs_export.install_exporter()
    assert exp is obs_hook._export and exp.role == "t"
    trc = obs_hook._tracer      # install enables one if none was live
    tid = "trace0001"
    trc.set_trace(tid)
    sid = trc.begin_span("unit.work", trace=tid)
    monitor.stat_add("fleet_test.requests", 5)
    trc.end_span(sid)
    trc.clear_trace()
    assert exp.flush()
    procs = fleet.read_spool(spool)
    assert [p["label"] for p in procs] == [f"t-{os.getpid()}"]
    p = procs[0]
    assert p["role"] == "t" and p["pid"] == os.getpid()
    assert p["corrupt"] == 0 and p["segments"] >= 1
    assert p["meta"]["build"]["jax"]
    assert p["metrics"]["stats"]["fleet_test.requests"] >= 5
    spans = [e for e in p["events"] if e.get("name") == "unit.work"]
    assert spans and spans[0]["trace"] == tid
    # wall-clock stamped so lanes align across monotonic epochs
    assert spans[0]["time"] == pytest.approx(time.time(), abs=120)


def test_read_spool_flags_corrupt_documents_without_raising(spool):
    exp = obs_export.install_exporter()
    obs_hook._tracer.emit("unit", "e1")
    assert exp.flush()
    [p] = fleet.read_spool(spool)
    seg = next(f for f in os.listdir(p["dir"]) if f.startswith("trace-"))
    path = os.path.join(p["dir"], seg)
    raw = json.loads(open(path).read())
    raw["body"]["events"] = []      # tamper: digest no longer matches
    open(path, "w").write(json.dumps(raw))
    [p2] = fleet.read_spool(spool)
    assert p2["corrupt"] == 1 and not p2["events"]


def test_fleet_snapshot_and_prometheus_proc_labels(spool):
    exp = obs_export.install_exporter()
    monitor.stat_add("fleet_test.gauge", 2)
    exp.flush()
    snap = fleet.fleet_snapshot(spool, include_self=False)
    assert set(snap["procs"]) == {f"t-{os.getpid()}"}
    assert snap["build_skew"] == []     # one build -> no skew
    text = fleet.fleet_prometheus_text(spool, include_self=False)
    lines = [ln for ln in text.splitlines()
             if ln and not ln.startswith("#")]
    assert lines
    bad = [ln for ln in lines if not PROM_LINE.match(ln)]
    assert not bad, bad[:3]
    unlabelled = [ln for ln in lines if 'proc="' not in ln]
    assert not unlabelled, unlabelled[:3]
    assert f'proc="t-{os.getpid()}"' in text


def test_merged_chrome_trace_names_one_lane_per_process(spool):
    # two "processes": two exporters with distinct roles sharing the
    # spool (read_spool keys by directory, not by live pid)
    exp_a = obs_export.install_exporter(role="a")
    obs_hook._tracer.emit("unit", "from_a")
    exp_a.flush()
    exp_b = obs_export.install_exporter(role="b")
    obs_hook._tracer.emit("unit", "from_b")
    exp_b.flush()
    merged = fleet.merged_chrome_trace(spool, include_self=False)
    evs = merged["traceEvents"]
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    pid = os.getpid()
    assert {f"a-{pid}", f"b-{pid}"} <= names
    assert all(e["ts"] >= 0 for e in evs if e["ph"] != "M")


# --------------------------------------- distributed request tracing --
@pytest.fixture(scope="module")
def gen_server():
    paddle.seed(3)
    model = make_dyadic_lm()
    eng = serving.GenerationEngine(model, num_slots=2, page_size=4,
                                   max_context=32)
    srv = serving.ServingServer(None, port=0, generation=eng).start()
    yield srv
    srv.close()
    eng.close()


def _raw_generate(srv, headers):
    u = urlparse(srv.url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=30)
    try:
        body = json.dumps({"prompt": [1, 2], "max_new_tokens": 2})
        conn.request("POST", "/generate", body=body, headers=dict(
            {"Content-Type": "application/json"}, **headers))
        r = conn.getresponse()
        raw = r.read().decode()
        last = json.loads(raw.strip().splitlines()[-1]) if raw else {}
        return r.status, dict(r.getheaders()), last
    finally:
        conn.close()


def test_server_adopts_wellformed_trace_id(gen_server):
    tracer = obs.enable(capacity=4096)
    try:
        status, hdrs, _ = _raw_generate(
            gen_server, {"X-Trace-Id": "req-abc.1", "X-Parent-Span": "7"})
        assert status == 200
        assert hdrs.get("X-Trace-Id") == "req-abc.1"
        # the handler's root span lands right after the last chunk is
        # written — a beat after the client sees the stream end
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            evs = [e for e in tracer.events()
                   if e.get("trace") == "req-abc.1"]
            if "http.generate" in {e["name"] for e in evs}:
                break
            time.sleep(0.02)
        names = {e["name"] for e in evs}
        assert "http.generate" in names     # the handler span adopted it
        # this process's root names the caller's span id
        assert any(e.get("remote_parent") == "7" for e in evs)
    finally:
        obs.disable()


@pytest.mark.parametrize("bad", [
    "spaces are bad", "bang!", "", "x" * 65, "-leadingdash",
    "unicodeé", '"quoted"'])
def test_malformed_trace_id_gets_fresh_id_never_500(gen_server, bad):
    status, hdrs, _ = _raw_generate(gen_server, {"X-Trace-Id": bad})
    assert status == 200
    echoed = hdrs.get("X-Trace-Id")
    assert echoed and echoed != bad     # minted, not adopted
    assert re.fullmatch(r"[0-9a-f]{32}", echoed)


def test_oversized_parent_span_ignored_not_500(gen_server):
    status, hdrs, _ = _raw_generate(
        gen_server, {"X-Trace-Id": "ok-id", "X-Parent-Span": "not-int"})
    assert status == 200 and hdrs.get("X-Trace-Id") == "ok-id"


def test_client_stamps_and_reports_trace_ids(gen_server):
    client = serving.Client(gen_server.url)
    assert client.last_trace_id is None
    client.generate([1, 2], max_new_tokens=2)
    first = client.last_trace_id
    assert first and re.fullmatch(r"[0-9a-f]{32}", first)
    client.generate([1, 2], max_new_tokens=2)
    assert client.last_trace_id != first    # minted per request
    pinned = serving.Client(gen_server.url, trace_id="pin-1")
    pinned.generate([1, 2], max_new_tokens=2)
    pinned.generate([1, 3], max_new_tokens=2)
    assert pinned.last_trace_id == "pin-1"


def test_trace_context_survives_reconnect_retry():
    """The retry loop must replay the SAME X-Trace-Id: headers are
    stamped once before _request, reconnect attempts reuse them."""
    tracer = obs.enable(capacity=4096)
    paddle.seed(3)
    model = make_dyadic_lm()
    eng = serving.GenerationEngine(model, num_slots=2, page_size=4,
                                   max_context=32)
    srv = serving.ServingServer(None, port=0, generation=eng).start()
    port = srv.port
    srv.close()                         # replica goes down
    client = serving.Client(f"http://127.0.0.1:{port}",
                            trace_id="retry-trace")
    client.reconnect_backoff_s = 1.0
    box = {}

    def restart():
        time.sleep(0.1)
        box["srv"] = serving.ServingServer(
            None, port=port, generation=eng).start()

    t = threading.Thread(target=restart)
    t.start()
    try:
        out = client.generate([1, 2], max_new_tokens=2)
        assert isinstance(out, list) and client.reconnects >= 1
        assert client.last_trace_id == "retry-trace"
        # the server closes its http.generate span after the response
        # has left: under load the client gets here first
        want = {"client.generate", "http.generate"}
        deadline = time.monotonic() + 5.0
        while True:
            names = {e["name"] for e in tracer.events()
                     if e.get("trace") == "retry-trace"}
            if want <= names or time.monotonic() > deadline:
                break
            time.sleep(0.02)
        assert want <= names
    finally:
        t.join()
        box["srv"].close()
        eng.close()
        obs.disable()


def test_assemble_trace_connects_client_and_server_spans(spool):
    exp = obs_export.install_exporter()
    model = make_dyadic_lm()
    eng = serving.GenerationEngine(model, num_slots=2, page_size=4,
                                   max_context=32)
    srv = serving.ServingServer(None, port=0, generation=eng).start()
    try:
        client = serving.Client(srv.url, trace_id="asm-1")
        client.generate([1, 2], max_new_tokens=2)
        # the server closes its http span after it has written the
        # response, so the client can be back before the span is: wait
        for _ in range(100):
            exp.flush()
            asm = fleet.assemble_trace(fleet.read_spool(spool), "asm-1")
            if "http.generate" in asm["names"]:
                break
            time.sleep(0.02)
        assert asm["connected"] and asm["components"] == 1
        assert asm["events"] >= 3       # client + http + engine spans
        assert "client.generate" in asm["names"]
        assert "http.generate" in asm["names"]
    finally:
        srv.close()
        eng.close()


# ------------------------------------------------------ admin surface --
def test_admin_fleet_aggregates_two_replicas(gen_server):
    paddle.seed(11)
    model = make_dyadic_lm()
    eng_b = serving.GenerationEngine(model, num_slots=2, page_size=4,
                                     max_context=32)
    srv_b = serving.ServingServer(None, port=0,
                                  generation=eng_b).start()
    fv = fleet.FleetView(timeout_s=5.0)
    fv.register("lm", urls=[gen_server.url, srv_b.url])
    gen_server.attach_fleet(fv)
    try:
        client = serving.Client(gen_server.url)
        snap = client._get_json("/admin/fleet")
        lm = snap["fleet"]["lm"]
        assert lm["count"] == 2 and lm["ready"] == 2
        assert all(r["reachable"] for r in lm["replicas"])
    finally:
        gen_server.attach_fleet(None)
        srv_b.close()
        eng_b.close()


def test_admin_trace_returns_merged_chrome_trace(gen_server, spool):
    exp = obs_export.install_exporter()
    client = serving.Client(gen_server.url, trace_id="admin-t")
    client.generate([1, 2], max_new_tokens=2)
    exp.flush()
    raw = client._post("/admin/trace?secs=0", b"",
                       {"Content-Type": "application/json"})
    trace = json.loads(raw)
    assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
    # bad secs is a 400, not a 500
    u = urlparse(gen_server.url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=10)
    try:
        conn.request("POST", "/admin/trace?secs=nope")
        assert conn.getresponse().status == 400
    finally:
        conn.close()
