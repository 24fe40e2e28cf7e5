"""Live-server native egress: the bench pipeline inside StreamingServer.

VERDICT r1 item 1: ≥64 real UDP PLAY clients on one source must be served
through the TPU-affine + native sendmmsg/GSO path bit-identically to the
scalar oracle.  Clients here are real RTSP connections doing UDP SETUP
against the shared egress pair; every datagram they receive is checked
against the relay's rewrite contract (payload bit-equal from byte 12,
bytes 0-1 verbatim, contiguous seq, rebased ts, per-client SSRC).
"""

import asyncio
import socket
import struct

import pytest

from easydarwin_tpu.server import ServerConfig, StreamingServer
from easydarwin_tpu.utils.client import RtspClient

H264_SDP = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=live\r\nt=0 0\r\n"
            "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
            "a=control:trackID=1\r\n")

N_PLAYERS = 64
N_PKTS = 24


def make_rtp(seq: int, ts: int, *, key: bool, ssrc: int = 0x11223344,
             size: int = 200) -> bytes:
    hdr = struct.pack("!BBHII", 0x80, 96 | 0x80, seq & 0xFFFF,
                      ts & 0xFFFFFFFF, ssrc)
    nal = 0x65 if key else 0x41         # IDR vs non-IDR slice
    body = bytes([nal]) + bytes((seq + i) & 0xFF for i in range(size - 13))
    return hdr + body


def drain_sock(s: socket.socket) -> list[bytes]:
    out = []
    while True:
        try:
            out.append(s.recv(65536))
        except BlockingIOError:
            return out


@pytest.mark.asyncio
async def test_native_egress_64_udp_players_bit_identical():
    from easydarwin_tpu import native
    if not native.available():
        pytest.skip("native core unavailable")
    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       reflect_interval_ms=5, bucket_delay_ms=1,
                       tpu_fanout=True, tpu_min_outputs=4,
                       access_log_enabled=False)
    app = StreamingServer(cfg)
    await app.start()
    try:
        egress = app.rtsp.shared_egress
        assert egress is not None and egress.active
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/native"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, H264_SDP)

        players = []
        socks = []
        for _ in range(N_PLAYERS):
            rtp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            rtp.bind(("127.0.0.1", 0))
            rtp.setblocking(False)
            rtp.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 20)
            rtcp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            rtcp.bind(("127.0.0.1", 0))
            rtcp.setblocking(False)
            c = RtspClient()
            await c.connect("127.0.0.1", app.rtsp.port)
            await c.play_start(uri, tcp=False, client_ports=[
                (rtp.getsockname()[1], rtcp.getsockname()[1])])
            # every UDP player must ride the shared egress pair
            assert c.transports[0].server_port == (egress.rtp_port,
                                                   egress.rtcp_port)
            players.append(c)
            socks.append((rtp, rtcp))

        src_pkts = [make_rtp(100 + i, 9000 + 3000 * i, key=(i == 0))
                    for i in range(N_PKTS)]
        for p in src_pkts:
            pusher.push_packet(0, p)

        per_player: list[list[bytes]] = [[] for _ in range(N_PLAYERS)]
        for _ in range(400):
            done = 0
            for i, (rtp, _rtcp) in enumerate(socks):
                got = drain_sock(rtp)
                per_player[i].extend(
                    g for g in got if len(g) >= 12
                    and g[1] & 0x7F == 96)      # RTP only, not relayed RTCP
                if len(per_player[i]) >= N_PKTS:
                    done += 1
            if done == N_PLAYERS:
                break
            await asyncio.sleep(0.02)

        ssrcs = set()
        for i, got in enumerate(per_player):
            assert len(got) >= N_PKTS, (i, len(got))
            got = got[:N_PKTS]
            seqs = [struct.unpack("!H", g[2:4])[0] for g in got]
            tss = [struct.unpack("!I", g[4:8])[0] for g in got]
            ssrc = {g[8:12] for g in got}
            assert len(ssrc) == 1               # constant per player
            ssrcs.add(ssrc.pop())
            for j, (g, src) in enumerate(zip(got, src_pkts)):
                assert g[12:] == src[12:], (i, j)       # payload bit-equal
                assert g[:2] == src[:2], (i, j)         # V/P/X/CC, M/PT
                assert seqs[j] == (seqs[0] + j) & 0xFFFF
                assert (tss[j] - tss[0]) & 0xFFFFFFFF == 3000 * j
        assert len(ssrcs) == N_PLAYERS          # unique SSRC per player

        # the packets actually went through the native scatter path
        engines = list(app.pump.engines.values())
        native_sent = sum(e.native_sent for e in engines)
        assert native_sent >= N_PLAYERS * N_PKTS, native_sent
        assert all(e.device_param_refreshes >= 1 for e in engines
                   if e.native_passes)

        for c in players:
            await c.close()
        for rtp, rtcp in socks:
            rtp.close()
            rtcp.close()
        await pusher.close()
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_rtcp_feedback_demuxes_on_shared_pair():
    """A receiver report sent to the shared RTCP port from the player's
    registered rtcp port reaches that player's output (UDPDemuxer role)."""
    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       reflect_interval_ms=5, access_log_enabled=False)
    app = StreamingServer(cfg)
    await app.start()
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/demux"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, H264_SDP)
        rtp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rtp.bind(("127.0.0.1", 0))
        rtcp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rtcp.bind(("127.0.0.1", 0))
        c = RtspClient()
        await c.connect("127.0.0.1", app.rtsp.port)
        await c.play_start(uri, tcp=False, client_ports=[
            (rtp.getsockname()[1], rtcp.getsockname()[1])])
        out = next(cn for cn in app.rtsp.connections
                   if cn.player_tracks).player_tracks[1].output
        # RR with 78% loss toward the output's SSRC, from the registered port
        rr = (struct.pack("!BBHI", 0x81, 201, 7, 1)
              + struct.pack("!I", out.rewrite.ssrc)
              + bytes([200]) + b"\x00\x00\x00"          # fl, cum_lost
              + struct.pack("!IIII", 0, 0, 0, 0))       # ehsn/jit/lsr/dlsr
        egress = app.rtsp.shared_egress
        assert egress is not None and egress.active
        rtcp.sendto(rr, ("127.0.0.1", egress.rtcp_port))
        for _ in range(100):
            if out.thinning.controller.level > 0:
                break
            await asyncio.sleep(0.02)
        assert out.thinning.controller.level >= 1
        assert egress.rtcp_in >= 1
        await c.close()
        await pusher.close()
        rtp.close()
        rtcp.close()
    finally:
        await app.stop()


def test_poisoned_destination_cannot_starve_other_outputs():
    """A hard-failing destination (port 0 → EINVAL from sendto) must be
    skipped past, oracle WriteResult.ERROR style — not retried in place
    forever, which would starve every output ordered after it."""
    from easydarwin_tpu import native
    if not native.available():
        pytest.skip("native core unavailable")
    from easydarwin_tpu.protocol import sdp
    from easydarwin_tpu.relay.fanout import TpuFanoutEngine
    from easydarwin_tpu.relay.output import CollectingOutput
    from easydarwin_tpu.relay.stream import RelayStream, StreamSettings

    sdp_txt = ("v=0\r\ns=x\r\nt=0 0\r\nm=video 0 RTP/AVP 96\r\n"
               "a=rtpmap:96 H264/90000\r\na=control:trackID=1\r\n")
    st = RelayStream(sdp.parse(sdp_txt).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    rx.setblocking(False)
    bad = CollectingOutput(ssrc=1, out_seq_start=10)
    bad.native_addr = ("127.0.0.1", 0)          # sendto(port 0) → EINVAL
    good = CollectingOutput(ssrc=2, out_seq_start=20)
    good.native_addr = rx.getsockname()
    st.add_output(bad)
    st.add_output(good)
    n = 6
    for i in range(n):
        st.push_rtp(struct.pack("!BBHII", 0x80, 96, 100 + i, 9000, 0xAB)
                    + bytes(40), 0)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    eng = TpuFanoutEngine(egress_fd=tx.fileno())
    sent = 0
    for _ in range(4):                          # a few passes may be needed
        sent += eng.step(st, 1000)
        if good.packets_sent >= n:
            break
    assert good.packets_sent == n               # good output fully served
    assert bad.bookmark == st.rtp_ring.head     # poisoned output skipped
    assert eng.send_errors >= 1
    got = drain_sock(rx)
    assert len(got) == n
    tx.close()
    rx.close()


@pytest.mark.asyncio
async def test_reannounce_adoption_survives_old_pusher_close():
    """Pusher A announces, pusher B re-announces (adopts) the same path;
    A's disconnect must not tear down B's live session."""
    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       reflect_interval_ms=5, access_log_enabled=False)
    app = StreamingServer(cfg)
    await app.start()
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/adopt"
        a = RtspClient()
        await a.connect("127.0.0.1", app.rtsp.port)
        await a.push_start(uri, H264_SDP)
        sess_a = app.registry.find("/live/adopt")
        assert sess_a is not None
        b = RtspClient()
        await b.connect("127.0.0.1", app.rtsp.port)
        await b.push_start(uri, H264_SDP)       # adopts the same session
        assert app.registry.find("/live/adopt") is sess_a
        await a.close()
        await asyncio.sleep(0.05)
        # B owns it now: the session must have survived A's close
        assert app.registry.find("/live/adopt") is sess_a
        await b.close()
        await asyncio.sleep(0.05)
        assert app.registry.find("/live/adopt") is None
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_udp_play_falls_back_without_shared_egress():
    """shared_udp_egress=False restores the per-client port-pair path."""
    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       reflect_interval_ms=5, shared_udp_egress=False,
                       access_log_enabled=False)
    app = StreamingServer(cfg)
    await app.start()
    try:
        assert app.rtsp.shared_egress is None
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/fb"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, H264_SDP)
        rtp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rtp.bind(("127.0.0.1", 0))
        rtp.setblocking(False)
        rtcp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rtcp.bind(("127.0.0.1", 0))
        c = RtspClient()
        await c.connect("127.0.0.1", app.rtsp.port)
        await c.play_start(uri, tcp=False, client_ports=[
            (rtp.getsockname()[1], rtcp.getsockname()[1])])
        pkt = make_rtp(7, 1234, key=True)
        pusher.push_packet(0, pkt)
        got = None
        for _ in range(200):
            try:
                got = rtp.recv(65536)
                break
            except BlockingIOError:
                await asyncio.sleep(0.02)
        assert got is not None and got[12:] == pkt[12:]
        await c.close()
        await pusher.close()
        rtp.close()
        rtcp.close()
    finally:
        await app.stop()
