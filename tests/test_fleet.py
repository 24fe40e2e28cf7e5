"""Fleet observability (ISSUE 15): cross-node trace propagation, metric
federation, freshness chains, the events NDJSON cursor, gzip scrape
compression, flight-dump node attribution + migration dedupe, and the
trace-lineage-across-migration e2e.
"""

import asyncio
import gzip
import json
import socket
import struct
import time
import urllib.request

import pytest

from easydarwin_tpu import obs
from easydarwin_tpu.obs import events as ev_mod
from easydarwin_tpu.obs import fleet
from easydarwin_tpu.obs.events import EventLog
from easydarwin_tpu.obs.flight import FlightRecorder
from easydarwin_tpu.relay.session import SessionRegistry
from easydarwin_tpu.resilience.checkpoint import (CKPT_VERSION,
                                                  restore_registry,
                                                  snapshot_session)
from easydarwin_tpu.server import ServerConfig, StreamingServer
from easydarwin_tpu.utils.client import RtspClient

SDP = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=fl\r\nt=0 0\r\n"
       "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
       "a=control:trackID=1\r\n")


def _pkt(seq: int) -> bytes:
    return (struct.pack("!BBHII", 0x80, 96, seq & 0xFFFF, seq * 90, 0xFE)
            + bytes([0x65]) + bytes(60))


@pytest.fixture
def node_identity():
    """Save/restore the process-wide node identity around a test."""
    saved = dict(ev_mod.NODE)
    yield
    ev_mod.NODE.update(saved)


# ------------------------------------------------------ events seq cursor
def test_event_seq_cursor_and_since():
    log = EventLog(capacity=8)
    for i in range(5):
        log.emit("pull.start", stream=f"/s{i}", url="u")
    recs = log.tail()
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == 5
    # since= slices strictly after the cursor
    cut = seqs[2]
    assert [r["seq"] for r in log.tail(since=cut)] == seqs[3:]
    assert log.tail(since=seqs[-1]) == []
    # ring eviction: the seq numbers keep counting, so a scraper paging
    # with since= can COUNT the gap instead of silently missing drops
    for i in range(10):
        log.emit("pull.eof", stream=f"/e{i}", url="u")
    newest = log.tail()
    assert newest[0]["seq"] > seqs[-1]
    assert log.dropped > 0
    # dump_lines round-trips the cursor filter
    lines = log.dump_lines(4, since=newest[-3]["seq"])
    assert len(lines) == 2
    assert all(json.loads(ln)["seq"] > newest[-3]["seq"] for ln in lines)
    # with a cursor the page is the OLDEST n matches: a scraper far
    # behind advances through the ring instead of skipping to the
    # newest page and miscounting the middle as drops
    page = log.tail(3, since=newest[0]["seq"])
    assert [r["seq"] for r in page] == \
        [r["seq"] for r in newest[1:4]]


def test_event_node_stamp(node_identity):
    log = EventLog(capacity=8)
    ev_mod.NODE["id"] = None
    rec = log.emit("pull.start", stream="/a", url="u")
    assert "node_id" not in rec
    ev_mod.set_node("nx", 7)
    rec = log.emit("pull.start", stream="/a", url="u")
    assert rec["node_id"] == "nx"
    # free-form fields can never shadow the cursor/attribution envelope
    rec = log.emit("pull.start", stream="/a", url="u", seq=999,
                   node_id="spoof")
    assert rec["node_id"] == "nx" and rec["seq"] != 999
    assert rec.get("invalid") is True


# ------------------------------------------- flight dump node + dedupe
def test_flight_dump_node_fence_and_dedupe(tmp_path, node_identity):
    fr = FlightRecorder(dump_dir=str(tmp_path))
    ev_mod.set_node("node-a", 5)
    fr.register("s1", trace_id="ab" * 4, path="/live/x")
    doc = fr.dump("s1", reason="timeout")
    assert doc["node_id"] == "node-a" and doc["fence"] == 5
    assert "node-a" in doc["file"]
    # the migration race: the same session flagged on another node under
    # an OLDER fence must not shadow the authoritative dump
    deduped = obs.FLIGHT_DUMPS_DEDUPED.value()
    ev_mod.set_node("node-b", 4)
    fr.register("s1", trace_id="ab" * 4, path="/live/x")
    doc2 = fr.dump("s1", reason="timeout")
    assert doc2 is doc or doc2.get("node_id") == "node-a"
    assert obs.FLIGHT_DUMPS_DEDUPED.value() == deduped + 1
    # a NEWER fence on the other node wins normally (fresh dump)
    ev_mod.set_node("node-b", 9)
    fr.register("s1", trace_id="ab" * 4, path="/live/x")
    doc3 = fr.dump("s1", reason="timeout")
    assert doc3["node_id"] == "node-b" and doc3["fence"] == 9


# ------------------------------------------------------ freshness chains
def test_freshness_chain_hops():
    from easydarwin_tpu.relay.output import CollectingOutput
    reg = SessionRegistry()
    sess = reg.find_or_create("/live/f", SDP)
    sess.add_output(1, CollectingOutput())
    sess.push(1, _pkt(0))
    chain = fleet.freshness_chain(sess, "n0")
    assert len(chain) == 1 and chain[0]["node"] == "n0"
    assert abs(chain[0]["ingest"] - time.time()) < 2.0

    class FakePull:
        upstream_chain = [{"node": "origin", "ingest": time.time() - 0.5}]

    sess.owner = FakePull()
    chain = fleet.freshness_chain(sess, "edge")
    assert [h["node"] for h in chain] == ["origin", "edge"]
    # the observation keys hops on the chain length
    before = obs.RELAY_E2E_FRESHNESS.count(hops="2")

    class App:
        config = ServerConfig(server_id="edge")
        registry = reg
    App.registry = reg
    fleet.observe_freshness(App)
    assert obs.RELAY_E2E_FRESHNESS.count(hops="2") == before + 1


# ------------------------------------------------- rollup + local fleet
def test_rollup_and_local_snapshot(tmp_path):
    cfg = ServerConfig(log_folder=str(tmp_path), access_log_enabled=False,
                       server_id="solo-1")
    app = StreamingServer(cfg)
    sess = app.registry.find_or_create("/live/r", SDP)
    sess.push(1, _pkt(0))
    roll = fleet.build_rollup(app)
    assert roll["node"] == "solo-1"
    assert roll["tiers"]["live"] == 1
    assert "/live/r" in roll["streams"]
    assert roll["streams"]["/live/r"]["tier"] == "live"
    assert set(roll["mismatches"]) == {"megabatch_wire", "fec_oracle",
                                       "requant_reassembly"}
    doc = fleet.fleet_snapshot(app)
    assert doc["source"] == "local" and doc["nodes_live"] == 1
    assert doc["nodes"]["solo-1"]["live"] is True
    # gauges re-derived from the aggregate
    assert obs.FLEET_NODES_LIVE.value() == 1
    assert obs.FLEET_STREAMS.value(tier="live") >= 1


# --------------------------------------- checkpoint trace lineage unit
def test_checkpoint_trace_lineage_roundtrip():
    reg = SessionRegistry()
    sess = reg.find_or_create("/live/ln", SDP)
    trace = sess.trace_id
    doc = snapshot_session(reg, "/live/ln", node_id="node-a")
    assert doc["trace"] == trace and doc["trace_nodes"] == ["node-a"]
    reg2 = SessionRegistry()
    restore_registry(reg2, {"version": CKPT_VERSION,
                            "saved_wall": time.time(),
                            "sessions": [doc]})
    sess2 = reg2.find("/live/ln")
    assert sess2.trace_id == trace
    assert sess2.trace_nodes == ["node-a"]
    # a re-snapshot on the adopter extends, not duplicates, the lineage
    doc2 = snapshot_session(reg2, "/live/ln", node_id="node-b")
    assert doc2["trace_nodes"] == ["node-a", "node-b"]
    doc3 = snapshot_session(reg2, "/live/ln", node_id="node-b")
    assert doc3["trace_nodes"] == ["node-a", "node-b"]


# ----------------------------------------------- REST surfaces (one app)
def _http(port: int, path: str, headers: dict | None = None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=5) as r:
        return r.status, r.read(), dict(r.headers)


async def test_rest_fleet_events_gzip_trace(tmp_path):
    cfg = ServerConfig(
        rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
        reflect_interval_ms=10, bucket_delay_ms=0,
        access_log_enabled=False, log_folder=str(tmp_path),
        server_id="rest-node")
    app = StreamingServer(cfg)
    await app.start()
    try:
        push = RtspClient()
        await push.connect("127.0.0.1", app.rtsp.port)
        await push.push_start(
            f"rtsp://127.0.0.1:{app.rtsp.port}/live/rf", SDP)
        player = RtspClient()
        await player.connect("127.0.0.1", app.rtsp.port)
        await player.play_start(
            f"rtsp://127.0.0.1:{app.rtsp.port}/live/rf")
        sid = player.session_id
        for seq in range(10):
            push.push_packet(0, _pkt(seq))
            await asyncio.sleep(0.005)
        port = app.rest.port

        # --- /api/v1/fleet: the single-node fleet document
        st, body, _h = await asyncio.to_thread(_http, port, "/api/v1/fleet")
        doc = json.loads(body)
        assert st == 200 and doc["nodes_live"] == 1
        roll = doc["nodes"]["rest-node"]
        assert roll["tiers"]["live"] >= 1 and roll["live"] is True

        # --- admin command=fleet serves the same aggregate
        st, body, _h = await asyncio.to_thread(
            _http, port, "/api/v1/admin?command=fleet")
        assert st == 200 and "rest-node" in json.loads(body)["nodes"]

        # --- /api/v1/events: NDJSON with the monotonic seq cursor
        st, body, hdrs = await asyncio.to_thread(
            _http, port, "/api/v1/events?n=64")
        assert st == 200
        assert hdrs.get("Content-Type") == "application/x-ndjson"
        recs = [json.loads(ln) for ln in body.decode().splitlines()]
        assert recs and all("seq" in r for r in recs)
        cut = recs[-2]["seq"]
        st, body, _h = await asyncio.to_thread(
            _http, port, f"/api/v1/events?since={cut}")
        after = [json.loads(ln) for ln in body.decode().splitlines()]
        assert after and all(r["seq"] > cut for r in after)

        # --- scrape-cost: a LOADED registry's /metrics compresses hard
        for i in range(512):
            obs.RELAY_INGEST_TO_WIRE.observe((i % 37) * 1e-4,
                                             engine="scalar")
        # the pump keeps mutating pump_*/relay_* families between two
        # scrapes of a LIVE server, so a plain/gzip pair taken 10 ms
        # apart can legitimately differ — retry until a stable pair
        # proves the encoding itself changes nothing.  The pump's own
        # pump_* lines move at every sleep and every wake: left out
        def settled(body: bytes) -> list:
            return [ln for ln in body.splitlines()
                    if not ln.startswith(b"pump_")]

        for _ in range(5):
            st, plain, hdrs = await asyncio.to_thread(
                _http, port, "/metrics")
            assert st == 200 and hdrs.get("Content-Encoding") is None
            st, packed, hdrs = await asyncio.to_thread(
                _http, port, "/metrics", {"Accept-Encoding": "gzip"})
            assert st == 200 and hdrs.get("Content-Encoding") == "gzip"
            assert hdrs.get("Vary") == "Accept-Encoding"
            unpacked = gzip.decompress(packed)
            if settled(unpacked) == settled(plain):
                break
        assert settled(unpacked) == settled(plain)  # content identical
        assert len(plain) > 4096            # genuinely loaded exposition
        assert len(packed) < len(plain) * 0.5, \
            f"scrape compression too weak: {len(packed)}/{len(plain)}"
        # NDJSON endpoints honor it too
        st, packed, hdrs = await asyncio.to_thread(
            _http, port, "/api/v1/events?n=256",
            {"Accept-Encoding": "gzip"})
        assert hdrs.get("Content-Encoding") == "gzip"
        assert gzip.decompress(packed).startswith(b"{")
        # HLS/HTML surfaces stay identity (the zero-copy egress path)
        st, body, hdrs = await asyncio.to_thread(
            _http, port, "/stats", {"Accept-Encoding": "gzip"})
        assert hdrs.get("Content-Encoding") is None

        # --- the session trace endpoint stitches (single hop here)
        st, body, _h = await asyncio.to_thread(
            _http, port, f"/api/v1/sessions/{sid}/trace")
        doc = json.loads(body)
        assert st == 200
        hops = doc["hops"]
        assert len(hops) == 1 and hops[0]["node"] == "rest-node"
        assert doc["stream_trace"] == hops[0]["trace"]
        assert doc["trace_stitched"] is True
        assert hops[0]["freshness"][0]["node"] == "rest-node"
        await player.close()
        await push.close()
    finally:
        await app.stop()


# ------------------------------ trace lineage across a live migration
def _cluster_cfg(tmp_path, node: str) -> ServerConfig:
    return ServerConfig(
        rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
        wan_ip="127.0.0.1", reflect_interval_ms=10, bucket_delay_ms=0,
        log_folder=str(tmp_path / node), access_log_enabled=False,
        server_id=node, cluster_enabled=True,
        cluster_lease_ttl_sec=1.0, cluster_heartbeat_sec=0.2,
        cluster_pull_connect_timeout_sec=3.0,
        cluster_pull_read_timeout_sec=1.0,
        cluster_pull_backoff_ms=100.0)


async def test_trace_lineage_across_migration_e2e(tmp_path):
    """Satellite: kill the owner mid-relay; the adopted session keeps
    the SAME trace_id with both nodes in its lineage, and the stitched
    trace on the survivor carries spans/events under that one id."""
    from easydarwin_tpu.cluster.redis_client import InMemoryRedis
    redis = InMemoryRedis()
    app_a = StreamingServer(_cluster_cfg(tmp_path, "tl-a"),
                            redis_client=redis)
    app_b = StreamingServer(_cluster_cfg(tmp_path, "tl-b"),
                            redis_client=redis)
    await app_a.start()
    await app_b.start()
    rtp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rtp.bind(("127.0.0.1", 0))
    rtp.setblocking(False)
    rtcp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rtcp.bind(("127.0.0.1", 0))
    rtcp.setblocking(False)
    push2 = None
    try:
        push = RtspClient()
        await push.connect("127.0.0.1", app_a.rtsp.port)
        await push.push_start(
            f"rtsp://127.0.0.1:{app_a.rtsp.port}/live/tl", SDP)
        player = RtspClient()
        await player.connect("127.0.0.1", app_a.rtsp.port)
        await player.play_start(
            f"rtsp://127.0.0.1:{app_a.rtsp.port}/live/tl", tcp=False,
            client_ports=[(rtp.getsockname()[1], rtcp.getsockname()[1])])
        for seq in range(20):
            push.push_packet(0, _pkt(seq))
            await asyncio.sleep(0.005)
        await asyncio.sleep(0.5)        # claim + checkpoint published
        trace = app_a.registry.find("/live/tl").trace_id
        assert trace

        app_a.cluster.crash()
        app_a.cluster = None
        t_kill = time.monotonic()
        await app_a.stop()
        while time.monotonic() - t_kill < 10.0:
            if app_b.registry.find("/live/tl") is not None:
                break
            await asyncio.sleep(0.05)
        sess_b = app_b.registry.find("/live/tl")
        assert sess_b is not None, "no migration within 10 s"
        # the ONE trace id survives the adoption, lineage spans both
        assert sess_b.trace_id == trace
        assert sess_b.trace_nodes == ["tl-a", "tl-b"]

        # the re-attaching pusher ADOPTS the stream trace (its spans
        # keep correlating under the preserved id)
        push2 = RtspClient()
        await push2.connect("127.0.0.1", app_b.rtsp.port)
        await push2.push_start(
            f"rtsp://127.0.0.1:{app_b.rtsp.port}/live/tl", SDP)
        for seq in range(20, 30):
            push2.push_packet(0, _pkt(seq))
            await asyncio.sleep(0.005)
        assert sess_b.trace_id == trace     # adoption did NOT re-mint

        # a post-migration subscriber's stitched trace: one trace id,
        # both nodes in the lineage, spans recorded under it
        player2 = RtspClient()
        await player2.connect("127.0.0.1", app_b.rtsp.port)
        await player2.play_start(
            f"rtsp://127.0.0.1:{app_b.rtsp.port}/live/tl")
        st, body, _h = await asyncio.to_thread(
            _http, app_b.rest.port,
            f"/api/v1/sessions/{player2.session_id}/trace")
        doc = json.loads(body)
        assert st == 200
        assert doc["stream_trace"] == trace
        assert doc["lineage"] == ["tl-a", "tl-b"]
        hops = doc["hops"]
        assert hops[0]["node"] == "tl-b"
        assert hops[0]["trace"] == trace
        assert hops[0]["spans"], "no spans stitched under the trace"
        assert any(e.get("trace") == trace for e in hops[0]["events"])
        await player2.close()
        await player.close()
        await push.close()
    finally:
        if push2 is not None:
            await push2.close()
        await app_b.stop()
        rtp.close()
        rtcp.close()


# -------------------------------------------------- contract surfaces
def test_lint_fleet_contract():
    import sys
    sys.path.insert(0, ".")
    from tools.metrics_lint import lint_fleet
    assert lint_fleet(obs.REGISTRY, ev_mod.SCHEMA) == []
    # a registry without the families fails loudly
    from easydarwin_tpu.obs.metrics import Registry
    errs = lint_fleet(Registry(), ev_mod.SCHEMA)
    assert any("fleet_streams_total" in e for e in errs)
    # an out-of-vocabulary tier child is rejected
    priv = Registry()
    priv.gauge("fleet_nodes_live", "h")
    g = priv.gauge("fleet_streams_total", "h", labels=("tier",))
    priv.counter("fleet_publishes_total", "h")
    priv.histogram("relay_e2e_freshness_seconds", "h", labels=("hops",))
    priv.counter("flight_dumps_deduped_total", "h")
    g.set(1, tier="bogus")
    errs = lint_fleet(priv, ev_mod.SCHEMA)
    assert any("bogus" in e for e in errs)


def test_bench_gate_accepts_composed_section():
    import sys
    sys.path.insert(0, ".")
    from tools.bench_gate import check_trajectory

    def traj(composed):
        return [{"file": "BENCH_rX.json", "rc": 0, "parsed": {
            "metric": "relay_packets_to_wire_per_sec", "value": 1000.0,
            "unit": "packets/s", "vs_baseline": 2.0,
            "extra": {"composed": composed}}}]

    good = {"nodes": 2,
            "tier_rates": {"live": 100.0, "hls": 5000.0, "vod": 30.0,
                           "dvr": 25.0, "tcp": 40.0},
            "scaling_efficiency": 0.6, "migration_gap_packets": 0,
            "mixed_p99_ms": 42.0, "e2e_freshness_p99_s": 0.4,
            "unresolved_traces": 0, "wire_mismatches": 0}
    assert check_trajectory(traj(good)) == []
    bad = dict(good, migration_gap_packets=3)
    assert any("migration_gap_packets" in e
               for e in check_trajectory(traj(bad)))
    bad = dict(good, tier_rates={"live": 0.0})
    assert any("tier_rates" in e for e in check_trajectory(traj(bad)))
    bad = dict(good, unresolved_traces=2)
    assert any("stitch" in e for e in check_trajectory(traj(bad)))
    bad = dict(good, scaling_efficiency=float("nan"))
    assert any("scaling_efficiency" in e
               for e in check_trajectory(traj(bad)))
    # rounds without the section stay valid
    assert check_trajectory(traj(None)) == []
