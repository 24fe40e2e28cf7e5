"""First-class TCP/HTTP delivery (ISSUE 14).

Wire-byte identity of the engine's framed interleave path — vectorized
``$``-framing rendered in C from the SAME affine device pass that
rewrites UDP headers, written through writev batches — against the
per-session batch-header baseline, over REAL TCP loopback sockets.
Plus: flow control (short writes, deep-backlog whole-AU shedding),
megabatch staging of the framing channel column, checkpoint parity for
``kind=tcp`` subscribers (park / re-attach / orphan), the HLS
etag/zero-copy serving path, and the lint/gate contracts.
"""

import asyncio
import random
import socket
import struct

import numpy as np
import pytest

from easydarwin_tpu import obs
from easydarwin_tpu.protocol import rtp, sdp
from easydarwin_tpu.relay import RelayStream, StreamSettings
from easydarwin_tpu.relay.fanout import TpuFanoutEngine
from easydarwin_tpu.relay.output import RelayOutput, WriteResult

VIDEO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=control:trackID=1\r\n")


def _tcp_pair(*, tiny: bool = False):
    """Real TCP loopback pair; ``tiny`` clamps both socket buffers
    BEFORE connect (the only time Linux honors small values) so short
    writes and backpressure are reachable in-process."""
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    if tiny:
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1024)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    a = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    if tiny:
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1024)
    a.connect(srv.getsockname())
    b, _ = srv.accept()
    srv.close()
    a.setblocking(False)
    b.setblocking(False)
    a.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return a, b


class TcpSink(RelayOutput):
    """Interleaved-output stand-in over a real TCP socket, modelling the
    asyncio transport's contract: ``pending`` is the transport buffer —
    raw engine writes are only legal while it is empty, a torn packet's
    remainder queues into it, and the buffered (batch-header) path
    appends frames behind whatever is already queued."""

    def __init__(self, sock, chan: int, *, fast: bool = True, **kw):
        super().__init__(**kw)
        self.sock = sock
        self.rtp_channel = chan
        self.rtcp_channel = chan + 1
        self.stream_fd = sock.fileno() if fast else -1
        self.pending = bytearray()

    @property
    def interleave_chan(self) -> int:
        return self.rtp_channel

    def engine_writable(self) -> bool:
        return not self.pending

    def push_tail(self, data) -> bool:
        self.pending += data
        return True

    def flush_pending(self) -> None:
        while self.pending:
            try:
                n = self.sock.send(self.pending)
            except BlockingIOError:
                return
            del self.pending[:n]

    #: transport high-water mark (the real InterleavedOutput's contract:
    #: past this the buffered path reports WOULD_BLOCK)
    HIGH_WATER = 2048

    def send_bytes(self, data: bytes, *, is_rtcp: bool) -> WriteResult:
        if is_rtcp:
            return WriteResult.OK
        blob = (b"$" + bytes((self.rtp_channel,))
                + len(data).to_bytes(2, "big") + data)
        if self.pending:
            if len(self.pending) > self.HIGH_WATER:
                return WriteResult.WOULD_BLOCK
            self.pending += blob
            return WriteResult.OK
        try:
            n = self.sock.send(blob)
        except BlockingIOError:
            return WriteResult.WOULD_BLOCK
        if n < len(blob):
            self.pending += blob[n:]
        return WriteResult.OK


def _pkt(seq, ts, nal_type=1, marker=False, size=30):
    payload = bytes(((3 << 5) | nal_type,)) + bytes(
        (seq * 7 + i) & 0xFF for i in range(size))
    return rtp.RtpPacket(payload_type=96, seq=seq & 0xFFFF, timestamp=ts,
                         ssrc=0x11112222, marker=marker,
                         payload=payload).to_bytes()


def _build(fast: bool, *, seed=5, n=120, n_out=4, chans=None,
           ring_capacity=None, tiny=False, size=None):
    rng = random.Random(seed)
    settings = StreamSettings(bucket_size=8)
    if ring_capacity:
        settings.ring_capacity = ring_capacity
    st = RelayStream(sdp.parse(VIDEO_SDP).streams[0], settings)
    pairs = []
    for i in range(n_out):
        a, b = _tcp_pair(tiny=tiny)
        ch = chans[i] if chans else 2 * i
        o = TcpSink(a, ch, fast=fast, ssrc=rng.getrandbits(32),
                    out_seq_start=rng.getrandbits(16),
                    out_ts_start=rng.getrandbits(32))
        st.add_output(o)
        pairs.append((o, b))
    for i in range(n):
        nt = 5 if i % 30 == 0 else 1
        sz = size if size else 20 + (i % 50) * 7   # mixed sizes
        st.push_rtp(_pkt(3000 + i, 90_000 + i * 3000, nal_type=nt,
                         marker=(i % 3 == 2), size=sz), 1000 + i)
    return st, pairs


def _drain(sock) -> bytes:
    out = b""
    while True:
        try:
            chunk = sock.recv(1 << 20)
        except BlockingIOError:
            return out
        if not chunk:
            return out
        out += chunk


def _parse_frames(blob: bytes):
    """Split an interleaved byte stream into (channel, payload) frames —
    asserts the stream is never torn mid-frame."""
    frames = []
    off = 0
    while off < len(blob):
        assert blob[off] == 0x24, f"stream torn at {off}"
        assert off + 4 <= len(blob)
        ch = blob[off + 1]
        ln = int.from_bytes(blob[off + 2:off + 4], "big")
        assert off + 4 + ln <= len(blob), "truncated frame"
        frames.append((ch, blob[off + 4:off + 4 + ln]))
        off += 4 + ln
    return frames


def test_engine_framed_wire_identical_mixed_sizes():
    """Engine-framed interleave vs per-session batch-header framing:
    byte-identical over real TCP sockets across mixed packet sizes."""
    st_a, pa = _build(fast=True)
    st_b, pb = _build(fast=False)
    now = 1000 + 120 + 5000
    ea = TpuFanoutEngine()
    eb = TpuFanoutEngine()
    sent_a = ea.step(st_a, now)
    sent_b = eb.step(st_b, now)
    assert sent_a == sent_b > 0
    for (oa, ra), (ob, rb) in zip(pa, pb):
        da, db = _drain(ra), _drain(rb)
        assert len(da) > 0
        assert da == db
        frames = _parse_frames(da)
        assert all(ch == oa.rtp_channel for ch, _ in frames)
    # fast-path honesty: the engine run really used the stream rung
    fam = obs.TCP_EGRESS_PACKETS
    assert fam._values.get(("writev",), 0) > 0


def test_mid_stream_join_and_channel_reuse():
    """A subscriber joining mid-stream — on a CHANNEL NUMBER another
    connection already uses — sees the same bytes the baseline path
    would give it; pre-existing subscribers are undisturbed."""
    st_a, pa = _build(fast=True, n=60, n_out=2, chans=[0, 0])
    st_b, pb = _build(fast=False, n=60, n_out=2, chans=[0, 0])
    now = 1000 + 60 + 5000
    ea, eb = TpuFanoutEngine(), TpuFanoutEngine()
    ea.step(st_a, now)
    eb.step(st_b, now)
    # mid-stream join, reusing channel 0 on a THIRD connection
    joins = []
    for st in (st_a, st_b):
        a, b = _tcp_pair()
        o = TcpSink(a, 0, fast=st is st_a, ssrc=0x5151,
                    out_seq_start=77, out_ts_start=88)
        st.add_output(o)
        joins.append((o, b))
    for st in (st_a, st_b):
        for i in range(60, 100):
            nt = 5 if i % 30 == 0 else 1
            st.push_rtp(_pkt(3000 + i, 90_000 + i * 3000, nal_type=nt,
                             size=20 + (i % 40) * 3), 1000 + i)
    now2 = 1000 + 100 + 5000
    ea.step(st_a, now2)
    eb.step(st_b, now2)
    for (oa, ra), (ob, rb) in zip(pa + [joins[0]], pb + [joins[1]]):
        da, db = _drain(ra), _drain(rb)
        assert da == db
        assert len(da) > 0
    assert joins[0][0].packets_sent == joins[1][0].packets_sent > 0


def test_partial_write_flow_control_stream_intact():
    """A tiny send buffer forces short writes: the torn packet's
    remainder rides ``push_tail`` (the transport), later passes replay
    from the bookmark, and the reassembled byte stream is identical to
    the unconstrained baseline — no torn or duplicated frames."""
    st_a, pa = _build(fast=True, n=80, n_out=1, tiny=True, size=700)
    st_b, pb = _build(fast=False, n=80, n_out=1, size=700)
    (oa, ra) = pa[0]
    ea, eb = TpuFanoutEngine(), TpuFanoutEngine()
    now = 1000 + 80 + 5000
    eb.step(st_b, now)
    want = _drain(pb[0][1])
    got = b""
    for i in range(200):
        ea.step(st_a, now + i)
        got += _drain(ra)
        oa.flush_pending()
        if len(got) >= len(want):
            break
    got += _drain(ra)
    assert got == want
    _parse_frames(got)                 # framing survived the tears
    assert oa.stalls > 0               # flow control actually engaged


def test_deep_backlog_sheds_whole_aus():
    """A reader stalled past half the ring is shed forward to the
    newest keyframe (whole AUs, frame-rate degradation) instead of
    accumulating a doomed backlog — and the pump never blocks."""
    st, pairs = _build(fast=True, n=8, n_out=1, ring_capacity=64,
                       tiny=True, size=700)
    (o, r) = pairs[0]
    eng = TpuFanoutEngine()
    base = obs.TCP_EGRESS_BACKPRESSURE_SHEDS._values.get(("writev",), 0)
    now = 1000 + 8 + 5000
    eng.step(st, now)                  # latches bookmark, fills socket
    # stall the reader completely and push far past half the ring —
    # the bookmark holds (WOULD_BLOCK replay), the pump keeps turning
    for i in range(8, 70):
        nt = 5 if i % 30 == 0 else 1
        st.push_rtp(_pkt(3000 + i, 90_000 + i * 3000, nal_type=nt,
                         size=400), 1000 + i)
        eng.step(st, now + i)
    behind_before = st.rtp_ring.head - o.bookmark
    assert behind_before > 32          # a real backlog accumulated
    # the reader comes back: transport drains, fast path re-engages —
    # and the deep backlog is shed forward to the newest keyframe
    for _ in range(50):
        _drain(r)
        o.flush_pending()
        if not o.pending:
            break
    eng.step(st, now + 100)
    shed = obs.TCP_EGRESS_BACKPRESSURE_SHEDS._values.get(("writev",), 0)
    assert shed > base                 # whole-AU shed fired
    assert st.rtp_ring.head - o.bookmark < behind_before


@pytest.mark.parametrize("ready_set", [False, True],
                         ids=["server_free", "ready_set"])
def test_megabatch_stages_tcp_framing_params(ready_set):
    """The cross-stream scheduler stages interleave channel columns in
    the SAME stacked pass as the UDP affine params; every install rides
    the host-oracle check and the wire stays byte-identical — through
    the server-free wake and through ``Pump.wake`` with the ready set."""
    from easydarwin_tpu.relay import pump
    from easydarwin_tpu.relay.megabatch import MegabatchScheduler
    from test_pump_ready import ReadyPump
    streams_a, streams_b, taps_a, taps_b = [], [], [], []
    for s in range(3):
        st_a, pa = _build(fast=True, seed=10 + s, n=50, n_out=2)
        st_b, pb = _build(fast=False, seed=10 + s, n=50, n_out=2)
        streams_a.append(st_a)
        streams_b.append(st_b)
        taps_a.extend(pa)
        taps_b.extend(pb)
    now = 1000 + 50 + 5000
    sched = MegabatchScheduler()
    engines = [TpuFanoutEngine() for _ in streams_a]
    if ready_set:
        rp = ReadyPump(sched, now)
        rp.wake(list(zip(streams_a, engines)), now)
        assert len(rp.pump.stepped) == 3
        # nothing marked since: the next wake harvests and steps nobody
        rp.wake(list(zip(streams_a, engines)), now + 20)
        assert rp.pump.stepped == []
    else:
        pump.wake(list(zip(streams_a, engines)), sched, now)
    for st_b in streams_b:
        TpuFanoutEngine().step(st_b, now)
    assert sched.mismatches == 0
    assert sum(e.megabatch_installs for e in engines) >= 3
    for (oa, ra), (ob, rb) in zip(taps_a, taps_b):
        da, db = _drain(ra), _drain(rb)
        assert da == db and len(da) > 0
    sched.drain()


def test_checkpoint_tcp_record_roundtrip():
    """``kind=tcp`` outputs are RECORDED with channel + session ids and
    parked on restore for the re-attach path; stale records age out as
    counted orphans (the long-standing recorded-but-skipped gap)."""
    from easydarwin_tpu.relay.session import SessionRegistry
    from easydarwin_tpu.resilience.checkpoint import (restore_registry,
                                                      snapshot_registry)
    reg = SessionRegistry(StreamSettings(bucket_size=8))
    sess = reg.find_or_create("/live/t", VIDEO_SDP)
    st = sess.streams[1]
    a, _b = _tcp_pair()
    o = TcpSink(a, 4, ssrc=0xAA, out_seq_start=100, out_ts_start=200)
    o.rewrite.base_src_seq = 3000
    o.rewrite.base_src_ts = 90_000
    o.session_id = "deadbeef"
    o.packets_sent = 17
    st.add_output(o)
    doc = snapshot_registry(reg)
    recs = doc["sessions"][0]["streams"][0]["outputs"]
    assert len(recs) == 1
    assert recs[0]["kind"] == "tcp"
    assert recs[0]["channels"] == [4, 5]
    assert recs[0]["session_id"] == "deadbeef"
    assert recs[0]["rewrite"] == [0xAA, 3000, 90_000, 100, 200]

    parked = []
    reg2 = SessionRegistry(StreamSettings(bucket_size=8))
    n_sess, n_out = restore_registry(
        reg2, doc, tcp_sink=lambda p, t, r: parked.append((p, t, r)))
    assert n_sess == 1 and n_out == 0  # parked, not live-restored
    assert parked == [("/live/t", 1, recs[0])]

    # app-level park/claim/orphan machinery
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    app = StreamingServer(ServerConfig(rtsp_timeout_sec=0))
    app._park_tcp_record("/live/t", 1, recs[0])
    assert app.claim_tcp_restore("/live/t", 1, "nope") is None
    assert app.claim_tcp_restore("/live/t", 1, "deadbeef") == recs[0]
    assert app.claim_tcp_restore("/live/t", 1, "deadbeef") is None
    base = obs.RESILIENCE_CKPT_TCP_ORPHANS._values.get((), 0)
    app._park_tcp_record("/live/t", 1, recs[0])
    app._sweep_pending_tcp()           # timeout 0: immediate orphan
    assert obs.RESILIENCE_CKPT_TCP_ORPHANS._values.get((), 0) == base + 1
    assert not app._pending_tcp
    # a record with no session id can never match: orphaned immediately
    app._park_tcp_record("/live/t", 1, {"rewrite": [0, -1, -1, 0, 0]})
    assert obs.RESILIENCE_CKPT_TCP_ORPHANS._values.get((), 0) == base + 2


def test_hls_playlist_cache_identity_and_zero_copy():
    """Playlist text rebuilt only when the window changes (same str
    object across repeat GETs); segment bodies served by reference."""
    from easydarwin_tpu.hls.segmenter import HlsOutput, Segment
    out = HlsOutput()
    out.init_segment = b"init"
    out.segments = [Segment(0, 2.0, b"seg0data"), Segment(1, 2.0, b"x" * 64)]
    p1 = out.playlist()
    p2 = out.playlist()
    assert p1 is p2                    # zero per-request rebuild
    assert out.playlist_builds == 1
    assert out.get_segment(1) is out.get_segment(1)
    out.segments.append(Segment(2, 2.0, b"y"))
    p3 = out.playlist()
    assert p3 is not p1 and out.playlist_builds == 2


async def test_hls_rest_etag_304_short_circuit():
    """A conditional GET with the served ETag gets 304 and ZERO body
    bytes; the normal GET carries the ETag header."""
    from easydarwin_tpu.server import ServerConfig
    from easydarwin_tpu.server.rest import RestApi

    class _Hls:
        def serve(self, path):
            if path.endswith(".m4s"):
                return ("video/iso.segment", b"S" * 100, '"seg-0-100"')
            return ("application/vnd.apple.mpegurl", "#EXTM3U\n",
                    'W/"pl-0-1-0"')

    class _App:
        hls = _Hls()
        uring_egress = None

    api = RestApi(ServerConfig(), _App())
    res = await api.route("GET", "/hls/cam/seg0.m4s", {}, b"")
    assert res[0] == 200 and res[3] == {"ETag": '"seg-0-100"'}
    res2 = await api.route("GET", "/hls/cam/seg0.m4s",
                           {"if-none-match": '"seg-0-100"'}, b"")
    assert res2[0] == 304 and res2[1] == b""
    assert api.hls_not_modified == 1
    res3 = await api.route("GET", "/hls/cam/index.m3u8",
                           {"if-none-match": 'W/"pl-0-1-0"'}, b"")
    assert res3[0] == 304


def _cfg(tmp_path, **kw):
    from easydarwin_tpu.server import ServerConfig
    return ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                        reflect_interval_ms=10, bucket_delay_ms=0,
                        log_folder=str(tmp_path),
                        access_log_enabled=False,
                        tpu_fanout=True, tpu_min_outputs=1, **kw)


E2E_SDP = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=t\r\nt=0 0\r\n"
           "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
           "a=control:trackID=1\r\n")


def _push_pkt(seq: int) -> bytes:
    return (struct.pack("!BBHII", 0x80, 96, seq & 0xFFFF, seq * 90, 0xB)
            + bytes([0x65]) + bytes(60))


async def test_server_e2e_interleaved_engine_path(tmp_path):
    """A real server serves an interleaved player through the ENGINE
    framed path: packets arrive in order on the negotiated channel and
    the stream-rung counters move."""
    from easydarwin_tpu.server import StreamingServer
    from easydarwin_tpu.utils.client import RtspClient

    def batch_rung():
        # the engine's batch rung is writev OR io_uring depending on
        # what the kernel offers — either proves the framed fast path
        # served (vs the per-session "buffered" fallback)
        return sum(v for k, v in obs.TCP_EGRESS_PACKETS._values.items()
                   if k[0] in ("writev", "io_uring"))

    base = batch_rung()
    app = StreamingServer(_cfg(tmp_path))
    await app.start()
    try:
        push = RtspClient()
        await push.connect("127.0.0.1", app.rtsp.port)
        await push.push_start(
            f"rtsp://127.0.0.1:{app.rtsp.port}/live/t", E2E_SDP)
        player = RtspClient()
        await player.connect("127.0.0.1", app.rtsp.port)
        await player.play_start(
            f"rtsp://127.0.0.1:{app.rtsp.port}/live/t", tcp=True)
        for seq in range(40):
            push.push_packet(0, _push_pkt(seq))
            await asyncio.sleep(0.004)
        got = []
        try:
            while len(got) < 30:
                got.append(await player.recv_interleaved(0, timeout=2.0))
        except asyncio.TimeoutError:
            pass
        assert len(got) >= 30
        seqs = [struct.unpack("!H", p[2:4])[0] for p in got]
        deltas = {(b2 - a2) & 0xFFFF for a2, b2 in zip(seqs, seqs[1:])}
        assert deltas <= {1}, f"seq gap/dup: {sorted(deltas)}"
        ssrcs = {p[8:12] for p in got}
        assert len(ssrcs) == 1
        assert batch_rung() > base
        await player.teardown(f"rtsp://127.0.0.1:{app.rtsp.port}/live/t")
        await player.close()
        await push.close()
    finally:
        await app.stop()


async def test_server_restart_reattaches_interleaved_gapless(tmp_path):
    """Migration/restart parity for TCP sessions: the player reconnects
    after a server restart, presents its old Session id on the
    interleaved SETUP, and sees the SAME ssrc with CONTINUOUS framed
    seq numbering — the kind=tcp checkpoint record adopted instead of
    dropped."""
    from easydarwin_tpu.server import StreamingServer
    from easydarwin_tpu.utils.client import RtspClient
    cfg = _cfg(tmp_path, resilience_checkpoint_enabled=True,
               resilience_checkpoint_interval_sec=0.5)
    app_a = StreamingServer(cfg)
    await app_a.start()
    rx: list[bytes] = []
    try:
        push = RtspClient()
        await push.connect("127.0.0.1", app_a.rtsp.port)
        await push.push_start(
            f"rtsp://127.0.0.1:{app_a.rtsp.port}/live/m", E2E_SDP)
        player = RtspClient()
        await player.connect("127.0.0.1", app_a.rtsp.port)
        await player.play_start(
            f"rtsp://127.0.0.1:{app_a.rtsp.port}/live/m", tcp=True)
        old_sid = player.session_id
        for seq in range(20):
            push.push_packet(0, _push_pkt(seq))
            await asyncio.sleep(0.004)
        try:
            while len(rx) < 20:
                rx.append(await player.recv_interleaved(0, timeout=1.0))
        except asyncio.TimeoutError:
            pass
        assert len(rx) >= 10
        assert app_a.checkpoint.write(app_a.registry)
        await push.close()
        await player.close()
    finally:
        await app_a.stop()

    n_before = len(rx)
    app_b = StreamingServer(_cfg(tmp_path,
                                 resilience_checkpoint_enabled=True,
                                 resilience_checkpoint_interval_sec=0.5))
    await app_b.start()
    try:
        assert app_b.registry.find("/live/m") is not None
        assert app_b._pending_tcp      # the tcp record parked, not lost
        # the player re-attaches FIRST (old Session id on the SETUP)...
        player2 = RtspClient()
        await player2.connect("127.0.0.1", app_b.rtsp.port)
        player2.session_id = old_sid
        await player2.play_start(
            f"rtsp://127.0.0.1:{app_b.rtsp.port}/live/m", tcp=True)
        # ...then the pusher resumes its numbering
        push2 = RtspClient()
        await push2.connect("127.0.0.1", app_b.rtsp.port)
        await push2.push_start(
            f"rtsp://127.0.0.1:{app_b.rtsp.port}/live/m", E2E_SDP)
        for seq in range(20, 40):
            push2.push_packet(0, _push_pkt(seq))
            await asyncio.sleep(0.004)
        try:
            while len(rx) < 40:
                rx.append(await player2.recv_interleaved(0, timeout=1.0))
        except asyncio.TimeoutError:
            pass
        assert len(rx) > n_before
        ssrcs = {p[8:12] for p in rx}
        assert len(ssrcs) == 1         # same subscriber identity
        seqs = [struct.unpack("!H", p[2:4])[0] for p in rx]
        deltas = {(b2 - a2) & 0xFFFF for a2, b2 in zip(seqs, seqs[1:])}
        assert deltas <= {1}, f"seq discontinuity: {sorted(deltas)}"
        await player2.close()
        await push2.close()
    finally:
        await app_b.stop()


def test_lint_and_gate_contracts():
    from tools.bench_gate import check_trajectory
    from tools.metrics_lint import lint_tcp_delivery
    from easydarwin_tpu.obs import events as ev
    assert lint_tcp_delivery(obs.REGISTRY, ev.SCHEMA) == []

    def entry(td=None):
        extra = {} if td is None else {"tcp_delivery": td}
        return {"file": "BENCH_r99.json", "rc": 0,
                "parsed": {"metric": "m", "value": 1.0, "unit": "p/s",
                           "vs_baseline": 1.0, "extra": extra}}

    good = {"engine_pkts_per_sec": 3000.0, "baseline_pkts_per_sec": 900.0,
            "speedup": 3.3, "wire_mismatches": 0}
    assert check_trajectory([entry(good)]) == []
    assert check_trajectory([entry()]) == []     # old rounds stay valid
    bad = dict(good, wire_mismatches=2)
    assert any("wire mismatch" in e for e in check_trajectory([entry(bad)]))
    slow = dict(good, engine_pkts_per_sec=100.0)
    assert any("below the per-session baseline" in e
               for e in check_trajectory([entry(slow)]))
    missing = dict(good, baseline_pkts_per_sec=None)
    assert any("not a positive finite rate" in e
               for e in check_trajectory([entry(missing)]))


def _uring_caps() -> int:
    from easydarwin_tpu import native
    return native.uring_probe()


@pytest.mark.skipif(_uring_caps() < 0,
                    reason="no io_uring on this kernel (the writev leg "
                           "above is the validated one here)")
def test_uring_stream_send_matches_writev():
    """io_uring-capable kernels only: the ring's framed stream sender
    (one SEND SQE per arena chunk) is byte-identical to writev."""
    from easydarwin_tpu import native
    from easydarwin_tpu.relay.ring import SLOT_SIZE
    a1, b1 = _tcp_pair()
    a2, b2 = _tcp_pair()
    ring = np.zeros((8, SLOT_SIZE), np.uint8)
    lens = np.zeros(8, np.int32)
    for i in range(5):
        pkt = _pkt(400 + i, 1000 + i * 90, size=40 + i * 13)
        ring[i, :len(pkt)] = np.frombuffer(pkt, np.uint8)
        lens[i] = len(pkt)
    slots = np.arange(5, dtype=np.int32)
    ur = native.UringEgress(a1.fileno(), max_pkt=SLOT_SIZE)
    try:
        r1, p1 = ur.stream_send(a1.fileno(), ring, lens, 7, 500, 0xEE, 3,
                                slots)
        r2, p2 = native.stream_send(a2.fileno(), ring, lens, 7, 500, 0xEE,
                                    3, slots)
        assert (r1, p1) == (r2, p2) == (5, 0)
        assert _drain(b1) == _drain(b2)
    finally:
        ur.close()
