"""Observability layer: exposition conformance, native stat parity,
span tracer, and the metric-inventory lint.

The exposition tests pin the Prometheus text-format 0.0.4 contract
(HELP/TYPE ordering, label escaping, the histogram ``_bucket``/``_sum``/
``_count`` invariants) against private registries; the parity test runs a
counted ``ed_fanout_send_udp`` burst and checks ``ed_get_stats()`` agrees
with what the receiver socket saw; the lint test runs
``tools/metrics_lint.py`` against the real process-wide inventory.
"""

import importlib.util
import json
import pathlib
import re
import socket
import threading
import time

import numpy as np
import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.obs import (Counter, EventLog, FlightRecorder, Gauge,
                                Histogram, Registry, SpanTracer)
from easydarwin_tpu.obs import events as events_mod


# ------------------------------------------------------------- exposition
def test_counter_gauge_exposition_format():
    reg = Registry()
    c = reg.counter("reqs_total", "requests served")
    g = reg.gauge("depth_bytes", "queue depth", labels=("queue",))
    c.inc(3)
    g.set(17, queue="a")
    g.set(4.5, queue="b")
    text = reg.expose()
    lines = text.splitlines()
    # per family: # HELP, then # TYPE, then samples; families sorted
    assert lines[0] == "# HELP depth_bytes queue depth"
    assert lines[1] == "# TYPE depth_bytes gauge"
    assert lines[2] == 'depth_bytes{queue="a"} 17'
    assert lines[3] == 'depth_bytes{queue="b"} 4.5'
    assert lines[4] == "# HELP reqs_total requests served"
    assert lines[5] == "# TYPE reqs_total counter"
    assert lines[6] == "reqs_total 3"
    assert text.endswith("\n")


def test_label_value_escaping():
    reg = Registry()
    c = reg.counter("odd_total", "odd labels", labels=("name",))
    c.inc(name='he said "hi"\\\n')
    line = [ln for ln in reg.expose().splitlines()
            if ln.startswith("odd_total{")][0]
    assert line == 'odd_total{name="he said \\"hi\\"\\\\\\n"} 1'


def test_histogram_bucket_invariants():
    reg = Registry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    lines = [ln for ln in reg.expose().splitlines()
             if ln.startswith("lat_seconds")]
    bucket_vals = [int(ln.rsplit(" ", 1)[1]) for ln in lines
                   if "_bucket" in ln]
    # cumulative and non-decreasing, +Inf equals _count
    assert bucket_vals == [2, 3, 4, 5]
    assert 'le="+Inf"' in lines[3]
    assert float(lines[4].split()[1]) == pytest.approx(5.56)
    assert lines[4].startswith("lat_seconds_sum ")
    assert lines[5] == "lat_seconds_count 5"
    # exact-boundary values land in their own bucket (le is inclusive)
    h2 = reg.histogram("edge_seconds", "edge", buckets=(1.0, 2.0))
    h2.observe(1.0)
    cum = [ln for ln in reg.expose().splitlines()
           if ln.startswith("edge_seconds_bucket")]
    assert cum[0] == 'edge_seconds_bucket{le="1"} 1'


def test_observe_many_matches_scalar_observe():
    reg = Registry()
    h1 = reg.histogram("a_seconds", "scalar path")
    h2 = reg.histogram("b_seconds", "vector path")
    rng = np.random.default_rng(7)
    vals = rng.uniform(0.00005, 70.0, size=500)
    for v in vals:
        h1.observe(float(v))
    h2.observe_many(vals)
    s1 = h1._states[()]
    s2 = h2._states[()]
    assert s1.counts == s2.counts
    assert s1.count == s2.count == 500
    assert s1.sum == pytest.approx(s2.sum)


def test_registry_validation():
    reg = Registry()
    reg.counter("x_total", "x")
    with pytest.raises(ValueError, match="duplicate"):
        reg.counter("x_total", "again")
    with pytest.raises(ValueError, match="bad metric name"):
        reg.counter("Bad-Name", "nope")
    with pytest.raises(ValueError, match="help"):
        reg.counter("y_total", "")
    lab = reg.counter("z_total", "z", labels=("kind",))
    with pytest.raises(ValueError, match="labels"):
        lab.inc(other="v")


def test_histogram_quantile_estimate():
    reg = Registry()
    h = reg.histogram("q_seconds", "q", buckets=(0.1, 1.0, 10.0))
    for _ in range(99):
        h.observe(0.5)
    h.observe(5.0)
    assert 0.1 <= h.quantile(0.5) <= 1.0
    assert h.quantile(0.99) <= 10.0
    assert Registry().histogram("e_seconds", "e").quantile(0.5) == 0.0


def test_counter_set_to_bridge_and_tree_view():
    reg = Registry()
    c = reg.counter("mirror_total", "externally maintained")
    c.set_to(42)
    seen = []
    reg.add_collector(lambda: seen.append(1))
    reg.add_collector(lambda: 1 / 0)     # a broken collector must not raise
    tree = reg.as_tree()
    assert tree["mirror_total"] == 42 and seen == [1]


def test_gauge_remove_drops_child():
    reg = Registry()
    g = reg.gauge("qos_x_ratio", "per-stream", labels=("path",))
    g.set(0.5, path="/a")
    g.remove(path="/a")
    g.remove(path="/never-set")          # idempotent
    assert "qos_x_ratio{" not in reg.expose()


# ------------------------------------------------------------------ lint
def _load_lint():
    p = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "metrics_lint.py"
    spec = importlib.util.spec_from_file_location("metrics_lint", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_metrics_lint_inventory_clean():
    lint = _load_lint().lint
    assert lint(obs.REGISTRY) == []


def test_metrics_lint_catches_violations():
    lint = _load_lint().lint
    reg = Registry()
    reg.counter("bad_counter", "counts things")        # no _total
    reg.gauge("depth", "no unit suffix")
    reg.histogram("h_total", "histogram named like a counter")
    errs = lint(reg)
    assert len(errs) >= 3
    assert any("_total" in e for e in errs)


def test_obs_lint_event_schema_clean():
    """The real event vocabulary and every emit call site pass the lint
    (the obs-lint half of the inventory contract)."""
    mod = _load_lint()
    assert mod.lint_events(events_mod.SCHEMA) == []
    pkg = pathlib.Path(__file__).resolve().parents[1] / "easydarwin_tpu"
    assert mod.lint_emit_sites(pkg, events_mod.SCHEMA) == []


def test_obs_lint_catches_event_violations(tmp_path):
    mod = _load_lint()
    bad = {
        "NotDotted": ("x",),                    # no layer dot, not lower
        "rtsp.ok": ("Bad-Field", "ts"),         # bad name + envelope shadow
    }
    errs = mod.lint_events(bad, reserved=events_mod.RESERVED_KEYS)
    assert len(errs) == 3
    (tmp_path / "m.py").write_text('EVENTS.emit("un.declared", x=1)\n')
    errs = mod.lint_emit_sites(tmp_path, events_mod.SCHEMA)
    assert len(errs) == 1 and "un.declared" in errs[0]


# -------------------------------------------------------- native parity
def test_native_stats_parity_counted_send():
    if not native.available():
        pytest.skip("native core unavailable")
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    rx.setblocking(False)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        native.reset_stats()
        n_slots, slot = 8, 256
        ring = np.zeros((n_slots, slot), np.uint8)
        lens = np.zeros(n_slots, np.int32)
        rng = np.random.default_rng(3)
        for i in range(n_slots):
            ln = int(rng.integers(60, slot))
            ring[i, :ln] = rng.integers(0, 256, ln, dtype=np.uint8)
            ring[i, 0] = 0x80            # valid RTP v2 byte
            lens[i] = ln
        dests = native.make_dests([rx.getsockname()])
        ops = native.make_ops([(i, 0) for i in range(n_slots)])
        seq = np.array([1000], np.uint32)
        ts = np.array([0], np.uint32)
        sc = np.array([0xABC], np.uint32)
        r = native.fanout_send_udp(tx.fileno(), ring, lens, seq, ts, sc,
                                   dests, ops, n_slots)
        assert r == n_slots
        s = native.get_stats()
        assert s["sendmmsg_calls"] >= 1
        assert s["send_packets"] == n_slots
        assert s["bytes_to_wire"] == int(lens.sum())
        assert s["sendto_calls"] == 0 and s["hard_errors"] == 0
        # the kernel delivered exactly what the stats claim
        got = 0
        import time
        deadline = time.monotonic() + 2
        while got < int(lens.sum()) and time.monotonic() < deadline:
            try:
                got += len(rx.recv(65536))
            except BlockingIOError:
                time.sleep(0.01)
        assert got == int(lens.sum())
        # the obs collector mirrors the same snapshot into the families
        obs.REGISTRY.collect()
        assert obs.EGRESS_PACKETS.value() == n_slots
        assert obs.EGRESS_BYTES.value() == int(lens.sum())
        assert "egress_sendmmsg_calls_total 1" in obs.REGISTRY.expose() \
            or obs.EGRESS_SENDMMSG_CALLS.value() >= 1
    finally:
        rx.close()
        tx.close()


def test_native_stats_count_scalar_baseline():
    if not native.available():
        pytest.skip("native core unavailable")
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        native.reset_stats()
        ring = np.zeros((2, 64), np.uint8)
        ring[:, 0] = 0x80
        lens = np.full(2, 40, np.int32)
        dests = native.make_dests([rx.getsockname()])
        ops = native.make_ops([(0, 0), (1, 0)])
        one = np.array([0], np.uint32)
        r = native.scalar_baseline_send(tx.fileno(), ring, lens, one, one,
                                        one, dests, ops, 2)
        assert r == 2
        s = native.get_stats()
        assert s["sendto_calls"] == 2 and s["sendmmsg_calls"] == 0
        assert s["send_packets"] == 2 and s["bytes_to_wire"] == 80
    finally:
        rx.close()
        tx.close()


# ------------------------------------------------------------------ trace
def test_tracer_records_and_dumps_chrome_format():
    tr = SpanTracer(capacity=16)
    tr.close(tr.open("pass", cat="tpu", n=3))
    tr.add("egress", time.perf_counter_ns(), cat="native")
    doc = json.loads(json.dumps(tr.dump()))   # must be JSON-serializable
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == ["pass", "egress"]
    for e in evs:
        assert e["ph"] == "X" and e["dur"] >= 0 and "ts" in e
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    assert evs[0]["args"] == {"n": 3}


def test_tracer_ring_is_bounded():
    tr = SpanTracer(capacity=8)
    for i in range(50):
        tr.add(f"s{i}", 0, 10)
    assert len(tr) == 8
    assert tr.dropped_hint == 42
    names = {e["name"] for e in tr.dump()["traceEvents"]}
    assert names == {f"s{i}" for i in range(42, 50)}
    # clear() resets the drop counter too (ISSUE 2 satellite)
    tr.clear()
    assert len(tr) == 0 and tr.dropped_hint == 0


def test_tracer_span_on_the_exception_path():
    tr = SpanTracer(capacity=8)
    with pytest.raises(ValueError):
        tok = tr.open("lost", cat="test")
        raise ValueError("nope")
    # a span an exception unwound past never reaches the ring ...
    assert tr.dump()["traceEvents"] == []
    tok = tr.open("boom", cat="test", n=1)
    try:
        raise ValueError("nope")
    except ValueError as e:
        # ... unless its owner closes it, tagged for trace queries
        tr.close(tok, error=type(e).__name__)
    evs = tr.dump()["traceEvents"]
    assert len(evs) == 1 and evs[0]["name"] == "boom"
    assert evs[0]["args"] == {"n": 1, "error": "ValueError"}


def test_tracer_concurrent_writers_dump_stable():
    """Hammer the ring from several threads while dump()/clear() run:
    no exceptions, exact drop accounting, every dump JSON-renderable."""
    tr = SpanTracer(capacity=64)
    n_threads, per_thread = 8, 2000
    errs = []

    def writer(k):
        try:
            for i in range(per_thread):
                tr.add(f"t{k}", 0, i, cat="load", i=i)
        except Exception as e:          # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for _ in range(50):                 # concurrent readers
        json.dumps(tr.dump())
    for t in threads:
        t.join()
    assert not errs
    assert len(tr) == 64
    # the lock makes drop accounting exact: every append past capacity
    assert tr.dropped_hint == n_threads * per_thread - 64


@pytest.mark.asyncio
async def test_metrics_exposition_content_type_header():
    """GET /metrics answers the Prometheus 0.0.4 content type through
    the real REST route (no server sockets needed)."""
    from easydarwin_tpu.server.config import ServerConfig
    from easydarwin_tpu.server.rest import RestApi
    api = RestApi(ServerConfig(), None)
    status, body, ctype = await api.route("GET", "/metrics", {}, b"")
    assert status == 200
    assert ctype == "text/plain; version=0.0.4; charset=utf-8"
    assert body.startswith("# HELP ") and body.endswith("\n")


# ------------------------------------------------------------------ events
def test_event_log_emit_ring_and_json_lines():
    log = EventLog(capacity=4)
    log.emit("session.create", stream="/live/a", trace_id="t1",
             path="/live/a", streams=2)
    rec = log.tail()[-1]
    assert rec["event"] == "session.create" and rec["trace"] == "t1"
    assert rec["stream"] == "/live/a" and "invalid" not in rec
    line = json.loads(log.dump_lines()[-1])
    assert line == rec
    for i in range(10):                 # bounded: oldest evicted, counted
        log.emit("session.remove", path=f"/p{i}")
    assert len(log) == 4 and log.dropped == 7
    assert [r["path"] for r in log.tail(2)] == ["/p8", "/p9"]
    assert log.tail(0) == []            # not recs[-0:] == everything
    log.clear()
    assert len(log) == 0 and log.dropped == 0


def test_event_log_schema_validation_flags_invalid():
    log = EventLog()
    before = obs.EVENTS_INVALID.value()
    log.emit("no.such.event", foo=1)
    assert log.tail()[-1]["invalid"] is True
    log.emit("session.create")          # missing required path/streams
    assert log.tail()[-1]["invalid"] is True
    log.emit("session.create", path="/x", streams=1, level="bogus")
    assert log.tail()[-1]["invalid"] is True
    assert obs.EVENTS_INVALID.value() == before + 3
    # envelope keys can never be shadowed by free-form fields
    log.emit("session.remove", path="/x", ts="spoofed")
    assert isinstance(log.tail()[-1]["ts"], float)


def test_event_log_broken_sink_counted_not_fatal_not_dropped():
    log = EventLog()
    seen = []
    before = obs.EVENTS_SINK_FAILURES.value()
    log.add_sink(lambda rec: 1 / 0)
    log.add_sink(seen.append)
    log.emit("session.remove", path="/a")
    log.emit("session.remove", path="/b")
    # healthy sinks keep receiving; the broken one is counted every
    # time, never silently unwired (a transient failure must not
    # permanently disable the flight recorder)
    assert [r["path"] for r in seen] == ["/a", "/b"]
    assert obs.EVENTS_SINK_FAILURES.value() == before + 2
    assert len(log._sinks) == 2


# ------------------------------------------------------------------ flight
def test_flight_recorder_ring_dump_and_lookup(tmp_path):
    fr = FlightRecorder(dump_dir=str(tmp_path))
    fr.register("sess1", trace_id="tr1", client_ip="1.2.3.4",
                path="/live/a")
    for i in range(300):                # ring keeps the newest ~256
        fr.on_event({"session": "sess1", "event": "rtsp.play", "i": i})
    fr.on_event({"session": "other", "event": "rtsp.play"})  # not ours
    live = fr.lookup("sess1")
    assert live["live"] is True and len(live["events"]) == 256
    assert live["events"][-1]["i"] == 299
    before = obs.FLIGHT_DUMPS.value()
    doc = fr.dump("sess1", reason="timeout: idle")
    assert obs.FLIGHT_DUMPS.value() == before + 1
    assert doc["reason"] == "timeout: idle" and doc["trace"] == "tr1"
    assert doc["meta"]["client_ip"] == "1.2.3.4"
    # written to disk as loadable JSON, and retrievable post-mortem
    on_disk = json.load(open(doc["file"]))
    assert on_disk["session"] == "sess1"
    assert fr.lookup("sess1")["reason"] == "timeout: idle"
    assert fr.lookup("nope") is None
    assert fr.dump("sess1", reason="again") is None   # already dumped
    # clean teardown leaves nothing behind
    fr.register("sess2")
    fr.discard("sess2")
    assert fr.lookup("sess2") is None and obs.FLIGHT_DUMPS.value() \
        == before + 1


def test_flight_dump_correlates_spans_by_trace_id(tmp_path):
    from easydarwin_tpu.obs import TRACER
    fr = FlightRecorder(dump_dir=str(tmp_path))
    fr.register("s9", trace_id="deadbeef")
    TRACER.close(TRACER.open("engine.step", "tpu", trace_id="deadbeef"),
                 sent=3)
    TRACER.close(TRACER.open("engine.step", "tpu",
                             trace_id="someone-else"))
    doc = fr.dump("s9", reason="exception: Boom")
    assert [s["name"] for s in doc["spans"]] == ["engine.step"]
    assert doc["spans"][0]["args"] == {"sent": 3}


# ------------------------------------------------------- cluster traceparent
def test_protocol_envelope_carries_trace_id():
    from easydarwin_tpu.cluster import protocol as ep
    m = ep.Message(ep.MSG_CS_GET_STREAM_REQ, 7, body={"Serial": "d1"},
                   trace_id="abc123")
    doc = json.loads(m.to_json())
    assert doc["EasyDarwin"]["Header"]["TraceId"] == "abc123"
    rt = ep.Message.parse(m.to_json())
    assert rt.trace_id == "abc123" and rt.cseq == 7
    # absent field parses to None and is omitted on the wire (stock
    # EasyDarwin tooling compatibility)
    plain = ep.Message(ep.MSG_CS_GET_STREAM_REQ)
    assert "TraceId" not in json.loads(plain.to_json())["EasyDarwin"]["Header"]
    assert ep.Message.parse(plain.to_json()).trace_id is None
    assert "TraceId" in ep.ack(ep.MSG_SC_GET_STREAM_ACK, trace_id="x")


def test_global_exposition_contains_required_families():
    """The acceptance-criteria families all exist at boot, value 0+."""
    text = obs.REGISTRY.expose()
    for fam in ("relay_ingest_to_wire_seconds", "egress_sendmmsg_calls_total",
                "egress_bytes_total", "tpu_pass_seconds",
                "tpu_h2d_bytes_total", "qos_fraction_lost_ratio",
                "log_lines_total", "log_rolls_total"):
        assert f"# TYPE {fam} " in text, fam
    # every HELP precedes its TYPE which precedes its samples
    kinds = dict(re.findall(r"# TYPE (\S+) (\S+)", text))
    helps = re.findall(r"# HELP (\S+) ", text)
    assert sorted(helps) == sorted(kinds) == helps
