"""End-to-end loopback: push (ANNOUNCE/RECORD) → relay → play (PLAY).

The network-level equivalent of BASELINE config 1: an EasyPusher-style
client pushes H.264/AAC over interleaved TCP, PLAY clients receive the
relayed stream; assertions check SDP service, payload bit-equality,
keyframe fast-start, REST visibility, and teardown.
"""

import asyncio

import pytest

from easydarwin_tpu.protocol import nalu, rtp, sdp
from easydarwin_tpu.server import ServerConfig, StreamingServer
from easydarwin_tpu.utils.client import RtspClient

PUSH_SDP = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=pushtest\r\n"
            "c=IN IP4 0.0.0.0\r\nt=0 0\r\na=control:*\r\n"
            "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
            "a=control:trackID=1\r\n")


def vid_pkt(seq, ts, nal_type=1, marker=False):
    payload = bytes(((3 << 5) | nal_type,)) + bytes((seq + i) & 0xFF
                                                    for i in range(40))
    return rtp.RtpPacket(payload_type=96, seq=seq & 0xFFFF, timestamp=ts,
                         ssrc=0xDEAD, marker=marker, payload=payload
                         ).to_bytes()


@pytest.fixture
def cfg():
    return ServerConfig(rtsp_port=0, service_port=0, reflect_interval_ms=5,
                        bind_ip="127.0.0.1")


async def _start(cfg):
    app = StreamingServer(cfg)
    await app.start()
    return app


@pytest.mark.asyncio
async def test_push_play_roundtrip_interleaved(cfg):
    app = await _start(cfg)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/cam1.sdp"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)

        sent = []
        for i in range(5):
            p = vid_pkt(100 + i, i * 3000, nal_type=5 if i == 0 else 1)
            sent.append(p)
            pusher.push_packet(0, p)

        player = RtspClient()
        await player.connect("127.0.0.1", app.rtsp.port)
        sd = await player.play_start(uri)
        assert sd.streams and sd.streams[0].codec == "H264"

        got = [await player.recv_interleaved(0) for _ in range(5)]
        # payloads bit-identical; headers rewritten (new ssrc, rebased seq)
        for s, g in zip(sent, got):
            ps, pg = rtp.RtpPacket.parse(s), rtp.RtpPacket.parse(g)
            assert pg.payload == ps.payload
            assert pg.ssrc != ps.ssrc
        seqs = [rtp.RtpPacket.parse(g).seq for g in got]
        assert seqs == [(seqs[0] + i) & 0xFFFF for i in range(5)]

        # live packets flow too
        p = vid_pkt(105, 90_000, marker=True)
        pusher.push_packet(0, p)
        g = await player.recv_interleaved(0)
        assert rtp.RtpPacket.parse(g).payload == rtp.RtpPacket.parse(p).payload
        assert player.stats.packets == 6 and player.stats.lost == 0

        await player.teardown(uri)
        await pusher.close()
        await player.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_late_joiner_gets_keyframe_fast_start(cfg):
    app = await _start(cfg)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/cam2"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)
        # a GOP: IDR at seq 10, P-frames after
        for i in range(8):
            pusher.push_packet(0, vid_pkt(10 + i, 0, nal_type=5 if i == 0 else 1))
        await asyncio.sleep(0.05)

        late = RtspClient()
        await late.connect("127.0.0.1", app.rtsp.port)
        await late.play_start(uri)
        first = await late.recv_interleaved(0)
        # fast-start: the first delivered packet is the IDR, not the tail
        assert nalu.is_keyframe_first_packet(first)
        await late.close()
        await pusher.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_play_unknown_path_404(cfg):
    app = await _start(cfg)
    try:
        c = RtspClient()
        await c.connect("127.0.0.1", app.rtsp.port)
        r = await c.request("DESCRIBE", f"rtsp://127.0.0.1:{app.rtsp.port}/nope")
        assert r.status == 404
        r = await c.request("OPTIONS", "*")
        assert r.status == 200 and "PLAY" in r.headers.get("public", "")
        await c.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_udp_play_transport(cfg):
    app = await _start(cfg)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/cam3"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)
        pusher.push_packet(0, vid_pkt(1, 0, nal_type=5))

        # bind our own UDP pair as the "client"
        loop = asyncio.get_running_loop()
        got: asyncio.Queue = asyncio.Queue()

        class Sink(asyncio.DatagramProtocol):
            def datagram_received(self, data, addr):
                got.put_nowait(data)

        rtp_t, _ = await loop.create_datagram_endpoint(
            Sink, local_addr=("127.0.0.1", 0))
        rtp_port = rtp_t.get_extra_info("sockname")[1]
        rtcp_t, _ = await loop.create_datagram_endpoint(
            Sink, local_addr=("127.0.0.1", 0))
        rtcp_port = rtcp_t.get_extra_info("sockname")[1]

        player = RtspClient()
        await player.connect("127.0.0.1", app.rtsp.port)
        await player.play_start(uri, tcp=False,
                                client_ports=[(rtp_port, rtcp_port)])
        t = player.transports[0]
        assert t.server_port is not None

        pusher.push_packet(0, vid_pkt(2, 3000))
        data = await asyncio.wait_for(got.get(), 5.0)
        assert rtp.RtpPacket.parse(data).payload == \
            rtp.RtpPacket.parse(vid_pkt(1, 0, nal_type=5)).payload
        rtp_t.close()
        rtcp_t.close()
        await player.close()
        await pusher.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_pusher_teardown_removes_session(cfg):
    app = await _start(cfg)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/cam4"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)
        assert app.registry.find("/live/cam4") is not None
        await pusher.teardown(uri)
        await asyncio.sleep(0.05)
        assert app.registry.find("/live/cam4") is None
        await pusher.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_rest_api_endpoints(cfg):
    app = await _start(cfg)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/cam5"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)

        import json
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       app.rest.port)

        async def get(path, body=b"", method="GET"):
            writer.write(
                f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
                f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
            head = await reader.readuntil(b"\r\n\r\n")
            status = int(head.split(b" ")[1])
            clen = int([ln for ln in head.split(b"\r\n")
                        if ln.lower().startswith(b"content-length")][0]
                       .split(b":")[1])
            return status, json.loads(await reader.readexactly(clen))

        st, doc = await get("/api/v1/getserverinfo")
        assert st == 200
        body = doc["EasyDarwin"]["Body"]
        assert body["PushSessions"] == "1"

        st, doc = await get("/api/v1/getrtsplivesessions")
        sess = doc["EasyDarwin"]["Body"]["Sessions"]
        assert len(sess) == 1 and sess[0]["Path"] == "/live/cam5"

        st, doc = await get("/api/v1/getbaseconfig")
        assert doc["EasyDarwin"]["Body"]["Config"]["rtsp_port"] == 0

        st, doc = await get(
            "/api/v1/setbaseconfig",
            json.dumps({"Config": {"bucket_delay_ms": 50}}).encode(), "POST")
        assert st == 200 and app.config.bucket_delay_ms == 50

        st, doc = await get("/api/v1/bogus")
        assert st == 404
        writer.close()
        await pusher.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_config2_fanout_16_players_no_loss(cfg):
    """BASELINE config-2 shape (scaled to CI): one push source, 16
    concurrent interleaved players, every player receives every payload
    exactly once, keyframe fast-start for late joiners."""
    app = await _start(cfg)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/fan"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)
        pusher.push_packet(0, vid_pkt(0, 0, nal_type=5))

        players = []
        for _ in range(16):
            p = RtspClient()
            await p.connect("127.0.0.1", app.rtsp.port)
            await p.play_start(uri)
            players.append(p)

        n_pkts = 40
        for i in range(1, n_pkts + 1):
            pusher.push_packet(0, vid_pkt(i, i * 3000,
                                          nal_type=5 if i % 10 == 0 else 1))
            if i % 8 == 0:
                await asyncio.sleep(0.01)

        for p in players:
            got = []
            # players joined after the first packet: fast-start replays
            # from the newest keyframe, then the live tail
            for _ in range(n_pkts + 1):
                try:
                    got.append(await asyncio.wait_for(
                        p.recv_interleaved(0), 5.0))
                except asyncio.TimeoutError:
                    break
            assert len(got) >= n_pkts, len(got)
            assert p.stats.lost == 0 and p.stats.duplicates == 0
        for p in players:
            await p.close()
        await pusher.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_x_rtp_meta_info_negotiation_and_wrapping(cfg):
    """DSS QT-client extension: SETUP with x-RTP-Meta-Info gets assigned
    ids back and meta-info-framed packets whose md is the exact RTP
    payload (strip_to_rtp reconstructs the plain packet)."""
    from easydarwin_tpu.protocol import rtp_meta

    app = await _start(cfg)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/meta"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)
        pusher.push_packet(0, vid_pkt(5, 0, nal_type=5))

        player = RtspClient()
        await player.connect("127.0.0.1", app.rtsp.port)
        r = await player.request("DESCRIBE", uri,
                                 {"accept": "application/sdp"})
        sd = sdp.parse(r.body)
        r = await player.request(
            "SETUP", f"{uri}/trackID={sd.streams[0].track_id}",
            {"transport": "RTP/AVP/TCP;unicast;interleaved=0-1",
             "x-rtp-meta-info": "tt;sq;md;pp"})
        assert r.status == 200
        hdr = r.headers.get("x-rtp-meta-info", "")
        ids = rtp_meta.parse_header(hdr)
        assert set(ids) == {"tt", "sq", "md"}      # pp unsupported
        r = await player.request("PLAY", uri)
        assert r.status == 200

        pusher.push_packet(0, vid_pkt(6, 3000))
        seen = 0
        for _ in range(2):
            data = await asyncio.wait_for(player.recv_interleaved(0), 5.0)
            info = rtp_meta.parse_packet(data, ids)
            assert info is not None and info.media is not None
            assert info.transmit_time and info.seq is not None
            plain = rtp_meta.strip_to_rtp(data, ids)
            p = rtp.RtpPacket.parse(plain)
            assert p.payload[0] in (0x65, 0x61)    # our NAL bytes intact
            assert p.seq == info.seq
            seen += 1
        assert seen == 2
        await player.close()
        await pusher.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_tpu_fanout_engine_serves_players_end_to_end():
    """The device batch engine (tpu_fanout=1, min_outputs=1) must deliver
    byte-identical streams through the real server to real players."""
    cfg = ServerConfig(rtsp_port=0, service_port=0, reflect_interval_ms=5,
                       bind_ip="127.0.0.1", access_log_enabled=False,
                       tpu_fanout=True, tpu_min_outputs=1)
    app = await _start(cfg)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/tpu"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)
        sent = [vid_pkt(30 + i, i * 3000, nal_type=5 if i == 0 else 1)
                for i in range(6)]
        for p in sent:
            pusher.push_packet(0, p)

        players = []
        for _ in range(3):
            p = RtspClient()
            await p.connect("127.0.0.1", app.rtsp.port)
            await p.play_start(uri)
            players.append(p)
        live = [vid_pkt(36 + i, (6 + i) * 3000) for i in range(4)]
        for p in live:
            pusher.push_packet(0, p)
        payloads = {rtp.RtpPacket.parse(x).payload for x in sent + live}
        for pl in players:
            got = [await asyncio.wait_for(pl.recv_interleaved(0), 5.0)
                   for _ in range(10)]
            for g in got:
                assert rtp.RtpPacket.parse(g).payload in payloads
            assert pl.stats.lost == 0 and pl.stats.duplicates == 0
        # the engine actually ran (device batch, not the scalar loop)
        assert app.pump.engines, "TpuFanoutEngine was never instantiated"
        for pl in players:
            await pl.close()
        await pusher.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_glass_to_glass_latency_under_budget(cfg):
    """BASELINE budget: <200 ms added latency.  Through the full server
    (ingest → ring → fan-out → interleaved egress) the push→receive
    delta for live packets must stay well inside it on the CPU path."""
    import time
    app = await _start(cfg)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/lat"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)
        pusher.push_packet(0, vid_pkt(0, 0, nal_type=5))
        player = RtspClient()
        await player.connect("127.0.0.1", app.rtsp.port)
        await player.play_start(uri)
        await asyncio.wait_for(player.recv_interleaved(0), 5.0)

        lat_ms = []
        for i in range(1, 21):
            t0 = time.monotonic()
            pusher.push_packet(0, vid_pkt(i, i * 3000))
            await asyncio.wait_for(player.recv_interleaved(0), 5.0)
            lat_ms.append((time.monotonic() - t0) * 1000)
        lat_ms.sort()
        p50, p95 = lat_ms[len(lat_ms) // 2], lat_ms[-2]
        # reflect_interval_ms=5 in cfg: p50 should sit near one pump tick
        assert p50 < 60, f"p50 {p50:.1f} ms"
        assert p95 < 200, f"p95 {p95:.1f} ms (BASELINE budget)"
        await player.close()
        await pusher.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_rtcp_refreshes_udp_player_timeout():
    """A UDP player's RTSP TCP connection is legitimately silent during
    playback; its RTCP (RRs/acks) must refresh the idle clock or the
    sweep kills an actively-watching player at rtsp_timeout (found by
    the 300 s soak; reference: RTPStream::ProcessIncomingRTCPPacket →
    RefreshTimeout).  A player sending NO RTCP must still be swept."""
    import struct as _struct
    import time as _time

    cfg = ServerConfig(rtsp_port=0, service_port=0, reflect_interval_ms=5,
                       bind_ip="127.0.0.1", rtsp_timeout_sec=1)
    app = await _start(cfg)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/camto"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)
        pusher.push_packet(0, vid_pkt(1, 0, nal_type=5))

        loop = asyncio.get_running_loop()

        async def make_player():
            class Sink(asyncio.DatagramProtocol):
                def datagram_received(self, data, addr):
                    pass
            rtp_t, _ = await loop.create_datagram_endpoint(
                Sink, local_addr=("127.0.0.1", 0))
            rtcp_t, _ = await loop.create_datagram_endpoint(
                Sink, local_addr=("127.0.0.1", 0))
            pl = RtspClient()
            await pl.connect("127.0.0.1", app.rtsp.port)
            await pl.play_start(uri, tcp=False, client_ports=[
                (rtp_t.get_extra_info("sockname")[1],
                 rtcp_t.get_extra_info("sockname")[1])])
            return pl, rtp_t, rtcp_t

        alive, a_rtp, a_rtcp = await make_player()
        dead, d_rtp, d_rtcp = await make_player()
        try:
            assert len(app.rtsp.connections) == 3    # pusher + 2 players

            srv_rtcp = alive.transports[0].server_port[1]
            rr = _struct.pack("!BBH I", 0x80, 201, 1, 0xCAFE)  # empty RR
            t0 = _time.monotonic()
            seq = 2
            while _time.monotonic() - t0 < 3.2:
                a_rtcp.sendto(rr, ("127.0.0.1", srv_rtcp))
                pusher.push_packet(0, vid_pkt(seq, seq * 3000))
                seq += 1
                app.rtsp.sweep_timeouts()
                await asyncio.sleep(0.25)
            await asyncio.sleep(0.1)
            conns = list(app.rtsp.connections)
            # the silent player died; the RTCP-sending one survived 3x
            # the timeout while its TCP connection stayed idle
            assert any(c.player_tracks for c in conns), "alive swept"
            assert len(conns) == 2, [c.is_pusher for c in conns]
        finally:
            for tr in (a_rtp, a_rtcp, d_rtp, d_rtcp):
                tr.close()
            await alive.close()
            await dead.close()
            await pusher.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_metrics_endpoint_admin_tree_and_trace():
    """ISSUE 1 acceptance: after a real relay pass, GET /metrics returns
    valid Prometheus text with a nonzero in-server ingest→wire histogram
    and per-pass TPU families; the same values read through the admin
    AttrStore tree; command=trace returns loadable Chrome-trace JSON
    with engine-pass spans."""
    import json
    import re

    cfg = ServerConfig(rtsp_port=0, service_port=0, reflect_interval_ms=5,
                       bind_ip="127.0.0.1", access_log_enabled=False,
                       tpu_fanout=True, tpu_min_outputs=1)
    app = await _start(cfg)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/obs"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)
        pusher.push_packet(0, vid_pkt(0, 0, nal_type=5))
        player = RtspClient()
        await player.connect("127.0.0.1", app.rtsp.port)
        await player.play_start(uri)
        for i in range(1, 9):
            pusher.push_packet(0, vid_pkt(i, i * 3000))
        for _ in range(9):
            await asyncio.wait_for(player.recv_interleaved(0), 5.0)

        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       app.rest.port)

        async def get(path):
            writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
            head = await reader.readuntil(b"\r\n\r\n")
            status = int(head.split(b" ")[1])
            ctype = [ln for ln in head.split(b"\r\n")
                     if ln.lower().startswith(b"content-type")][0]
            clen = int([ln for ln in head.split(b"\r\n")
                        if ln.lower().startswith(b"content-length")][0]
                       .split(b":")[1])
            return status, ctype.decode(), await reader.readexactly(clen)

        # --- /metrics scrape: exposition + the acceptance families
        st, ctype, body = await get("/metrics")
        assert st == 200 and "text/plain" in ctype and "0.0.4" in ctype
        text = body.decode()
        assert "# TYPE relay_ingest_to_wire_seconds histogram" in text
        counts = {m[0]: float(m[1]) for m in re.findall(
            r'relay_ingest_to_wire_seconds_count\{engine="(\w+)"\} (\S+)',
            text)}
        assert sum(counts.values()) > 0, "in-server latency histogram empty"
        assert re.search(r"^tpu_passes_total [1-9]", text, re.M)
        assert re.search(r"^tpu_h2d_bytes_total [1-9]", text, re.M)
        assert re.search(r'tpu_pass_seconds_count\{stage="engine_step"\} '
                         r"[1-9]", text)
        for fam in ("egress_sendmmsg_calls_total", "egress_bytes_total",
                    "egress_eagain_total", "ingest_recvmmsg_calls_total"):
            assert re.search(rf"^{fam} \d", text, re.M), fam

        # --- the same values through the reflective admin tree
        st, _, body = await get("/api/v1/admin?path=server/metrics/"
                                "relay_ingest_to_wire_seconds")
        assert st == 200
        val = json.loads(body)["EasyDarwin"]["Body"]["Value"]
        assert sum(v["count"] for v in val.values()) >= sum(counts.values())
        st, _, body = await get("/api/v1/admin?path=server/metrics/*")
        assert st == 200
        fams = json.loads(body)["EasyDarwin"]["Body"]["Value"]
        assert fams["tpu_passes_total"] >= 1
        # get-by-id: @<id> resolves through the AttrStore like any attr
        mstore = app.metrics_store
        aid = mstore.spec("tpu_passes_total").attr_id
        st, _, body = await get(f"/api/v1/admin?path=server/metrics/@{aid}")
        assert st == 200
        # >= : the engine keeps passing between the two queries
        assert json.loads(body)["EasyDarwin"]["Body"]["Value"] \
            >= fams["tpu_passes_total"]

        # --- command=trace: loadable Chrome trace with engine spans
        st, ctype, body = await get("/api/v1/admin?command=trace")
        assert st == 200 and "application/json" in ctype
        doc = json.loads(body)
        names = {e["name"] for e in doc["traceEvents"]}
        assert "engine.step" in names
        for e in doc["traceEvents"]:
            assert e["ph"] == "X" and e["dur"] >= 0

        # --- getserverinfo rides the same snapshot (PacketsOut live)
        st, _, body = await get("/api/v1/getserverinfo")
        info = json.loads(body)["EasyDarwin"]["Body"]
        assert int(info["PacketsOut"]) >= 9
        assert "IngestToWireP99Ms" in info

        writer.close()
        await player.close()
        await pusher.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_trace_correlation_and_flight_recorder_e2e():
    """ISSUE 2 acceptance: one session's trace_id appears on spans at all
    three hops (RTSP handler, engine pass, native egress), and an
    abnormal teardown produces a flight dump retrievable via BOTH the
    admin command and the per-session REST endpoint."""
    import json
    import socket as _socket

    from easydarwin_tpu import native, obs

    cfg = ServerConfig(rtsp_port=0, service_port=0, reflect_interval_ms=5,
                       bind_ip="127.0.0.1", access_log_enabled=False,
                       tpu_fanout=True, tpu_min_outputs=1)
    app = await _start(cfg)
    udp_rtp = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
    udp_rtcp = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/flight"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)
        pusher.push_packet(0, vid_pkt(0, 0, nal_type=5))

        # UDP player on the shared egress → the engine's NATIVE fast path
        for s in (udp_rtp, udp_rtcp):
            s.bind(("127.0.0.1", 0))
            s.setblocking(False)
        player = RtspClient()
        await player.connect("127.0.0.1", app.rtsp.port)
        await player.play_start(uri, tcp=False, client_ports=[
            (udp_rtp.getsockname()[1], udp_rtcp.getsockname()[1])])
        for i in range(1, 12):
            pusher.push_packet(0, vid_pkt(i, i * 3000))
        await asyncio.sleep(0.3)        # several engine passes

        conns = {c.is_pusher: c for c in app.rtsp.connections}
        push_conn, play_conn = conns[True], conns[False]
        tid = push_conn.trace_id
        assert app.registry.find("/live/flight").trace_id == tid

        # --- hop correlation: the pusher session's trace_id on spans at
        # the RTSP handler, the engine pass, and the native egress
        by_hop = {}
        for ev in obs.TRACER.dump()["traceEvents"]:
            if (ev.get("args") or {}).get("trace_id") == tid:
                by_hop.setdefault(ev["name"].split(".")[0], set()
                                  ).add(ev["name"])
        assert "rtsp.announce" in by_hop.get("rtsp", set())
        assert "rtsp.setup" in by_hop["rtsp"]
        assert "engine.step" in by_hop.get("engine", set())
        if native.available():
            assert "native.egress" in by_hop.get("native", set())

        # the player's session events carry ITS trace end-to-end too
        play_sid = play_conn.session_id
        assert play_sid is not None

        # --- abnormal teardown: the sweep reaps the idle player and the
        # flight recorder freezes its black box
        dumps_before = obs.FLIGHT_DUMPS.value()
        play_conn.last_activity -= 10_000
        assert app.rtsp.sweep_timeouts() >= 1
        await asyncio.sleep(0.1)
        assert obs.FLIGHT_DUMPS.value() == dumps_before + 1

        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       app.rest.port)

        async def get(path):
            writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
            head = await reader.readuntil(b"\r\n\r\n")
            status = int(head.split(b" ")[1])
            clen = int([ln for ln in head.split(b"\r\n")
                        if ln.lower().startswith(b"content-length")][0]
                       .split(b":")[1])
            return status, await reader.readexactly(clen)

        # --- retrieval 1: the admin command
        st, body = await get(f"/api/v1/admin?command=flight"
                             f"&session={play_sid}")
        assert st == 200
        doc = json.loads(body)
        assert doc["session"] == play_sid
        assert doc["reason"].startswith("timeout")
        assert doc["trace"] == play_conn.trace_id
        events = {e["event"] for e in doc["events"]}
        assert {"rtsp.setup", "rtsp.play", "rtsp.close"} <= events
        assert any(e["event"] == "rtsp.close"
                   and e["reason"].startswith("timeout")
                   for e in doc["events"])

        # --- retrieval 2: the per-session REST endpoint, same box
        st, body = await get(f"/api/v1/sessions/{play_sid}/trace")
        assert st == 200
        assert json.loads(body)["events"] == doc["events"]
        st, _b = await get("/api/v1/sessions/feedfeed/trace")
        assert st == 404

        # a LIVE session reads back its current ring, no dump minted
        push_sid = push_conn.session_id
        st, body = await get(f"/api/v1/sessions/{push_sid}/trace")
        assert st == 200 and json.loads(body)["live"] is True
        assert obs.FLIGHT_DUMPS.value() == dumps_before + 1

        # --- clean teardown leaves no black box behind
        await pusher.teardown(uri)
        await asyncio.sleep(0.05)
        st, _b = await get(f"/api/v1/sessions/{push_sid}/trace")
        assert st == 404
        assert obs.FLIGHT_DUMPS.value() == dumps_before + 1

        writer.close()
        await player.close()
        await pusher.close()
    finally:
        udp_rtp.close()
        udp_rtcp.close()
        await app.stop()
