"""ISSUE 10: device-resident VOD segment cache + shared group pacer.

The acceptance core is byte-identity over real UDP sockets: for the
same subscriber schedule (mixed video+audio, mid-stream seek, thinning
active) the cache-served hot path — vectorized ring block-fill stepped
through the live engines, per-subscriber rewrite via the affine
machinery — must put byte-identical RTP on the wire as the cold
per-sample ``FileSession`` path.  Plus the cache LRU/pin/checkpoint
contracts, the megabatch/device-prime integration, the hardened
``VodService.resolve`` traversal guard, and pinned VOD pacing
semantics (seek snap, Scale timestamp rewrite, thinning counts, SR
cadence/extrapolation) the pacer rebuild must not drift.
"""

import asyncio
import os
import socket
import time

import numpy as np
import pytest

from easydarwin_tpu import obs
from easydarwin_tpu.protocol import rtp
from easydarwin_tpu.relay import pump
from easydarwin_tpu.relay.output import RelayOutput, WriteResult
from easydarwin_tpu.vod.cache import (SegmentCache, StagedPacketRing,
                                      pack_window, tracks_by_no)
from easydarwin_tpu.vod.mp4 import Mp4File, open_shared
from easydarwin_tpu.vod.mp4_writer import Mp4Writer
from easydarwin_tpu.vod.packetizer import AacPacketizer, H264Packetizer
from easydarwin_tpu.vod.session import (FileSession, PacedVodSession,
                                        VodPacerGroup, VodService)

SPS = bytes((0x67, 0x42, 0x00, 0x1F, 0xAA, 0xBB, 0xCC, 0xDD, 0xEE, 0xFF))
PPS = bytes((0x68, 0xCE, 0x3C, 0x80, 0x11, 0x22, 0x33, 0x44))


def avcc(*nals: bytes) -> bytes:
    out = b""
    for n in nals:
        out += len(n).to_bytes(4, "big") + n
    return out


def write_fixture(path, n_frames=30, fps=30, with_audio=True,
                  idr_bytes=2000, p_bytes=80):
    """IDR samples exceed the 1400 MTU so FU-A fragmentation is part of
    the identity surface."""
    w = Mp4Writer(str(path))
    v = w.add_h264_track(SPS, PPS, 640, 480, timescale=90000)
    a = w.add_aac_track(bytes((0x11, 0x90)), 8000, 1) if with_audio \
        else None
    dur = 90000 // fps
    for i in range(n_frames):
        idr = i % 10 == 0
        nal = bytes((0x65 if idr else 0x41,)) \
            + bytes((i,)) * (idr_bytes if idr else p_bytes)
        w.write_sample(v, avcc(nal), dur, sync=idr)
    if a is not None:
        for i in range(n_frames):
            w.write_sample(a, bytes((0xFF, i)) * 20, 1024, sync=True)
    w.close()
    return str(path)


@pytest.fixture
def fixture_mp4(tmp_path):
    return write_fixture(tmp_path / "clip.mp4")


class UdpOut(RelayOutput):
    """Real-socket sink for the scalar/cold paths (RTCP dropped so the
    RTP byte streams compare clean)."""

    def __init__(self, sock, addr, **kw):
        super().__init__(**kw)
        self.sock = sock
        self.addr = addr

    def send_bytes(self, data, *, is_rtcp):
        if not is_rtcp:
            self.sock.sendto(data, self.addr)
        return WriteResult.OK


class NativeOut(RelayOutput):
    """Engine fast-path sink: RTP rides the native scatter via
    ``native_addr``; host-side send_bytes only ever sees RTCP."""

    def send_bytes(self, data, *, is_rtcp):
        return WriteResult.OK


def _drain(sock) -> list[bytes]:
    out = []
    while True:
        try:
            out.append(sock.recv(65536))
        except BlockingIOError:
            return out


def _rx_socket():
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.bind(("127.0.0.1", 0))
    s.setblocking(False)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 22)
    return s


# ---------------------------------------------------------------- packing

def test_pack_window_matches_cold_packetizer(fixture_mp4):
    """Canonical window packets are the cold packetizers' bytes modulo
    the per-subscriber seq/ssrc fields the fill/affine rewrite owns."""
    f = Mp4File(fixture_mp4)
    for tr in (f.video_track(), f.audio_track()):
        w = pack_window(f, tr, 0, tr.n_samples)
        pk = (H264Packetizer(tr, ssrc=0, seq_start=0)
              if tr.info.handler == "vide"
              else AacPacketizer(tr, ssrc=0, seq_start=0))
        cold = []
        for i in range(tr.n_samples):
            cold.extend(pk.packetize_sample(f.read_sample(tr, i), i))
        assert w.n_pkts == len(cold)
        for k, pkt in enumerate(cold):
            assert w.data[k, :w.length[k]].tobytes() == pkt
        # staged rows: prefix + le32 length, pow2-padded
        from easydarwin_tpu.ops.staging import ROW_STRIDE
        assert w.staged.shape[1] == ROW_STRIDE
        assert w.staged.shape[0] >= w.n_pkts
        k = w.n_pkts - 1
        assert int.from_bytes(w.staged[k, 96:100].tobytes(),
                              "little") == int(w.length[k])
    f.close()


def test_staged_ring_gather_matches_plain(fixture_mp4):
    """ops.staging.gather_window over a StagedPacketRing (pre-packed
    rows) returns the same bytes as the generic per-ring pack."""
    from easydarwin_tpu.ops import staging
    from easydarwin_tpu.relay.ring import PacketRing
    f = Mp4File(fixture_mp4)
    tr = f.video_track()
    w = pack_window(f, tr, 0, 12)
    plain = PacketRing(64, is_video=True)
    st = StagedPacketRing(64, is_video=True)
    t = int(time.monotonic() * 1000)
    for k in range(w.n_pkts):
        pkt = w.data[k, :w.length[k]].tobytes()
        plain.push(pkt, t)
        st.push(pkt, t)
    n = w.n_pkts
    rows_a = np.zeros((staging.pow2(n, 16), staging.ROW_STRIDE), np.uint8)
    rows_b = np.zeros_like(rows_a)
    assert staging.gather_window(plain, 0, n, rows_a) == n
    assert staging.gather_window(st, 0, n, rows_b) == n
    assert np.array_equal(rows_a, rows_b)
    # the block-fill path maintains staged rows identically
    st2 = StagedPacketRing(64, is_video=True)
    seqs = np.array([rtp.peek_seq(w.data[k, :w.length[k]].tobytes())
                     for k in range(n)], np.uint32)
    st2.push_block(w.data[:n], w.length[:n],
                   np.full(n, t, np.int64), w.flags[:n], seqs, w.ts[:n])
    rows_c = np.zeros_like(rows_a)
    assert staging.gather_window(st2, 0, n, rows_c) == n
    assert np.array_equal(rows_a, rows_c)
    f.close()


# ------------------------------------------------------- wire byte identity

def _run_cold(path, rx_v, rx_a, tx, *, start_npt=0.0, level=0,
              speed=2000.0):
    f = open_shared(path)
    vo = UdpOut(tx, rx_v.getsockname(), ssrc=0x111, out_seq_start=500)
    ao = UdpOut(tx, rx_a.getsockname(), ssrc=0x222, out_seq_start=900)
    if level:
        vo.thinning.controller.level = level
    sess = FileSession(f, {1: vo, 2: ao}, start_npt=start_npt,
                       speed=speed)
    asyncio.run(sess.run())
    f.close()
    time.sleep(0.05)
    return _drain(rx_v), _drain(rx_a), sess


def _run_hot(path, rx_v, rx_a, tx, *, start_npt=0.0, level=0,
             speed=2000.0, engine=False, cache=None):
    f = open_shared(path)
    cache = cache or SegmentCache(window_samples=8, device=False)
    engines = {}
    send_fd = tx.fileno()

    def engine_for(st):
        from easydarwin_tpu.relay.fanout import TpuFanoutEngine
        e = engines.get(id(st))
        if e is None:
            e = engines[id(st)] = TpuFanoutEngine(egress_fd=send_fd)
        return e

    pacer = VodPacerGroup(cache, engine_for=engine_for if engine else None,
                          engine_drop=lambda s: engines.pop(id(s), None),
                          lookahead_ms=250)
    if engine:
        vo = NativeOut(ssrc=0x111, out_seq_start=500)
        vo.native_addr = rx_v.getsockname()
        ao = NativeOut(ssrc=0x222, out_seq_start=900)
        ao.native_addr = rx_a.getsockname()
    else:
        vo = UdpOut(tx, rx_v.getsockname(), ssrc=0x111, out_seq_start=500)
        ao = UdpOut(tx, rx_a.getsockname(), ssrc=0x222, out_seq_start=900)
    if level:
        vo.thinning.controller.level = level
    t0 = int(time.monotonic() * 1000)
    sess = pacer.open(f, {1: vo, 2: ao}, start_npt=start_npt,
                      speed=speed, now_ms=t0)
    deadline = time.time() + 20
    while not sess.done and time.time() < deadline:
        t = int(time.monotonic() * 1000)
        pump.wake(pacer.tick(t), None, t)
        time.sleep(0.001)
    assert sess.done, "hot session never finished"
    pacer.close()
    f.close()
    time.sleep(0.05)
    return _drain(rx_v), _drain(rx_a), sess


def test_wire_bytes_identical_hot_vs_cold_scalar(fixture_mp4):
    """THE acceptance criterion: same subscriber schedule — mixed
    video+audio, a mid-stream seek (re-PLAY at npt, the RTSP shape),
    thinning active — over real UDP sockets; the hot cache path's wire
    bytes equal the cold per-sample path's exactly."""
    rx_v, rx_a = _rx_socket(), _rx_socket()
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    # schedule: play from 0, then seek to 0.5 s with thinning pinned
    cv1, ca1, cs = _run_cold(fixture_mp4, rx_v, rx_a, tx)
    cv2, ca2, cs2 = _run_cold(fixture_mp4, rx_v, rx_a, tx,
                              start_npt=0.5, level=2)
    hv1, ha1, hs = _run_hot(fixture_mp4, rx_v, rx_a, tx)
    hv2, ha2, hs2 = _run_hot(fixture_mp4, rx_v, rx_a, tx,
                             start_npt=0.5, level=2)
    assert cv1 and ca1 and cv2 and ca2
    assert hv1 == cv1 and ha1 == ca1
    assert hv2 == cv2 and ha2 == ca2
    assert hs2.frames_thinned == cs2.frames_thinned > 0
    tx.close()
    rx_v.close()
    rx_a.close()


def test_wire_bytes_identical_hot_engine_vs_cold(fixture_mp4):
    """Same identity through the ENGINE fast path: vectorized fill +
    TpuFanoutEngine native sendmmsg scatter (per-subscriber rewrite via
    the device affine params) vs the cold packetizer."""
    from easydarwin_tpu import native
    if not native.available():
        pytest.skip("native core unavailable")
    rx_v, rx_a = _rx_socket(), _rx_socket()
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    cv1, ca1, _ = _run_cold(fixture_mp4, rx_v, rx_a, tx)
    cv2, ca2, _ = _run_cold(fixture_mp4, rx_v, rx_a, tx,
                            start_npt=0.5, level=2)
    hv1, ha1, _ = _run_hot(fixture_mp4, rx_v, rx_a, tx, engine=True)
    hv2, ha2, _ = _run_hot(fixture_mp4, rx_v, rx_a, tx,
                           start_npt=0.5, level=2, engine=True)
    assert cv1 and ca1
    assert hv1 == cv1 and ha1 == ca1
    assert hv2 == cv2 and ha2 == ca2
    tx.close()
    rx_v.close()
    rx_a.close()


def test_cold_miss_path_identical_to_hot(fixture_mp4):
    """A cache miss streams through the per-sample mmap path into the
    same ring — wire bytes equal the hot fill's (the miss→cold race
    rule: degrade cost, never bytes)."""
    rx_v, rx_a = _rx_socket(), _rx_socket()
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)

    class NeverHit(SegmentCache):
        def get(self, *a, **kw):
            kw["background_fill"] = False
            super().get(*a, **kw)        # count the miss
            return None

    hv, ha, _ = _run_hot(fixture_mp4, rx_v, rx_a, tx)
    mv, ma, _ = _run_hot(fixture_mp4, rx_v, rx_a, tx,
                         cache=NeverHit(window_samples=8, device=False))
    assert mv == hv and ma == ha
    tx.close()
    rx_v.close()
    rx_a.close()


# ------------------------------------------------ megabatch + device prime

def test_vod_streams_ride_megabatch_with_device_prime(fixture_mp4):
    """Warm cache + N native subscribers: every join's affine params
    come from ONE stacked pass over the HBM-resident window (uploaded
    once, zero H2D per join), installed through the scheduler's
    host-oracle check; steady-state wakes coalesce the VOD streams into
    stacked megabatch passes.  Zero oracle mismatches."""
    from easydarwin_tpu import native
    if not native.available():
        pytest.skip("native core unavailable")
    from easydarwin_tpu.relay.fanout import TpuFanoutEngine
    from easydarwin_tpu.relay.megabatch import MegabatchScheduler
    f = open_shared(fixture_mp4)
    cache = SegmentCache(window_samples=16, device=True)
    assert cache.warm_asset(f) > 0
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    engines = {}

    def engine_for(st):
        e = engines.get(id(st))
        if e is None:
            e = engines[id(st)] = TpuFanoutEngine(egress_fd=tx.fileno())
        return e

    sched = MegabatchScheduler()
    pacer = VodPacerGroup(cache, engine_for=engine_for,
                          engine_drop=lambda s: engines.pop(id(s), None),
                          scheduler=lambda: sched, lookahead_ms=250,
                          device_prime=True)
    rxs = [_rx_socket() for _ in range(4)]
    sessions = []
    for k, rx in enumerate(rxs):
        o = NativeOut(ssrc=0x7000 + k, out_seq_start=31 * k + 1)
        o.native_addr = rx.getsockname()
        sessions.append(pacer.open(f, {1: o}, speed=2000.0,
                                   now_ms=int(time.monotonic() * 1000)))
    mm0 = obs.MEGABATCH_WIRE_MISMATCH.value()
    deadline = time.time() + 20
    while any(not s.done for s in sessions) and time.time() < deadline:
        t = int(time.monotonic() * 1000)
        pump.wake(pacer.tick(t), sched, t, min_streams=2)
        time.sleep(0.001)
    sched.drain()
    assert all(s.done for s in sessions)
    time.sleep(0.05)
    counts = [len(_drain(rx)) for rx in rxs]
    assert min(counts) > 0 and len(set(counts)) == 1
    assert pacer.device_primes == 4          # every join device-primed
    assert pacer.prime_failures == 0
    st = cache.stats()
    assert st["device_uploads"] >= 1         # HBM window(s) uploaded...
    assert st["device_uploads"] <= 2         # ...once, shared by joins
    assert sched.mismatches == 0
    assert obs.MEGABATCH_WIRE_MISMATCH.value() == mm0
    assert sched.streams_coalesced > 0       # VOD rode the stacked pass
    assert pacer.hot_pkts > 0 and pacer.cold_pkts == 0
    for rx in rxs:
        rx.close()
    tx.close()
    pacer.close()
    cache.close()
    f.close()


# ----------------------------------------------------- cache LRU/checkpoint

def test_cache_lru_budget_pinning_and_metrics(fixture_mp4):
    f = open_shared(fixture_mp4)
    tracks = tracks_by_no(f)
    tr = tracks[1]
    cache = SegmentCache(budget_bytes=1, window_samples=4, device=False)
    ev0 = obs.VOD_CACHE_EVICTIONS.value()
    w0 = cache.fill_now(f, 1, tr, 0)
    assert w0 is not None
    assert cache._lru.get(w0.key) is w0      # just-filled never thrashed
    cache.pin(w0)
    w1 = cache.fill_now(f, 1, tr, 1)
    assert w1 is not None
    # filling a third window: w0 is pinned, w2 is the just-inserted
    # keep — only w1 is evictable under the 1-byte budget
    w2 = cache.fill_now(f, 1, tr, 2)
    assert w1.key not in cache._lru
    assert cache._lru.get(w0.key) is w0      # pinned survived
    assert cache._lru.get(w2.key) is w2
    assert cache.evictions >= 1
    assert obs.VOD_CACHE_EVICTIONS.value() > ev0
    cache.unpin(w0)                          # now evictable
    assert w0.key not in cache._lru          # unpin re-runs the scan
    # hit/miss counters
    h0, m0 = obs.VOD_CACHE_HITS.value(), obs.VOD_CACHE_MISSES.value()
    assert cache.get(f, 1, tr, 3, background_fill=False) is None
    assert obs.VOD_CACHE_MISSES.value() == m0 + 1
    w3 = cache.fill_now(f, 1, tr, 3)
    assert cache.get(f, 1, tr, 3) is w3
    assert obs.VOD_CACHE_HITS.value() == h0 + 1
    cache.close()
    f.close()


def test_cache_checkpoint_metadata_roundtrip(fixture_mp4):
    f = open_shared(fixture_mp4)
    tr = tracks_by_no(f)[1]
    cache = SegmentCache(window_samples=8, device=False)
    cache.fill_now(f, 1, tr, 0)
    cache.fill_now(f, 1, tr, 1)
    snap = cache.snapshot()
    assert snap["version"] == 1 and len(snap["windows"]) == 2
    for rec in snap["windows"]:
        assert rec["path"] == fixture_mp4 and rec["track"] == 1
    fresh = SegmentCache(window_samples=8, device=False)
    assert fresh.restore(snap) == 2
    # re-warm kicks background fills on first open of the asset
    assert fresh.note_open(f) == 2
    deadline = time.time() + 5
    while fresh.stats()["windows"] < 2 and time.time() < deadline:
        time.sleep(0.02)
    assert fresh.stats()["windows"] == 2
    # garbage/versioned-off metadata is ignored, never raises
    assert fresh.restore({"version": 99}) == 0
    assert fresh.restore({"version": 1, "windows": [{"bad": 1}]}) == 0
    cache.close()
    fresh.close()
    f.close()


# ------------------------------------------------------- resolve hardening

def test_resolve_rejects_traversal_sibling_and_symlink(tmp_path):
    movies = tmp_path / "movies"
    movies.mkdir()
    write_fixture(movies / "ok.mp4", n_frames=3)
    svc = VodService(str(movies))
    assert svc.resolve("/ok.mp4") is not None
    # plain ..
    secret = tmp_path / "secret.mp4"
    write_fixture(secret, n_frames=3)
    assert svc.resolve("/../secret.mp4") is None
    assert svc.resolve("/../secret") is None
    # sibling directory sharing the prefix string (movies2/ vs movies/)
    sib = tmp_path / "movies2"
    sib.mkdir()
    write_fixture(sib / "leak.mp4", n_frames=3)
    assert svc.resolve("/../movies2/leak.mp4") is None
    # symlink inside the root pointing outside it
    os.symlink(str(secret), str(movies / "link.mp4"))
    assert svc.resolve("/link.mp4") is None
    assert svc.resolve("/link") is None


# --------------------------------------------------- pinned pacing semantics

def test_seek_snaps_to_sync_sample(fixture_mp4):
    """``start_npt`` → searchsorted → sync snap, pinned by hand: 30 fps
    fixture, IDR every 10 samples; seeking to 0.5 s (sample 15) must
    snap back to sample 10 — on BOTH paths."""
    f = Mp4File(fixture_mp4)
    v = f.video_track()
    assert FileSession._seek_index(v, 0.5) == 10
    assert FileSession._seek_index(v, 0.0) == 0
    assert FileSession._seek_index(v, 0.34) == 10   # sample 10.2 → 10
    assert FileSession._seek_index(v, 99.0) == \
        v.sync_sample_at_or_before(v.n_samples - 1)
    f.close()


def test_scale_rewrites_timestamps_pinned(fixture_mp4):
    """Scale 2.0 (ts_scale): the cold path compresses RTP timestamps by
    the factor — frame i sits at i*3000 ticks, delivered at 1500/frame."""
    f = open_shared(fixture_mp4)
    out = UdpOut.__new__(UdpOut)          # collecting variant is enough
    from easydarwin_tpu.relay.output import CollectingOutput
    out = CollectingOutput(ssrc=1, out_seq_start=0)
    sess = FileSession(f, {1: out}, speed=2000.0, ts_scale=2.0)
    asyncio.run(sess.run())
    ts = sorted({rtp.peek_timestamp(p) for p in out.rtp_packets})
    deltas = {b - a for a, b in zip(ts, ts[1:])}
    assert deltas == {1500}
    f.close()


def test_thinning_admit_shed_counts_pinned(fixture_mp4):
    """Level 1 = every second non-key frame: the 30-sample fixture has
    3 IDRs + 27 P-frames; the ThinningFilter's frame-parity rule sheds
    a pinned, hand-computable count on both paths."""
    rx_v, rx_a = _rx_socket(), _rx_socket()
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    cv, _, cs = _run_cold(fixture_mp4, rx_v, rx_a, tx, level=1)
    hv, _, hs = _run_hot(fixture_mp4, rx_v, rx_a, tx, level=1)
    assert hv == cv
    # frame index runs 1..30; even-indexed non-key frames drop.  IDRs
    # sit at frame indices 1, 11, 21 (odd) — so 15 even indices, all
    # non-key: 15 thinned frames, identically on both paths
    assert cs.frames_thinned == hs.frames_thinned == 15
    # level 2: keyframes only → 27 of 30 shed
    cv2, _, cs2 = _run_cold(fixture_mp4, rx_v, rx_a, tx, level=2)
    hv2, _, hs2 = _run_hot(fixture_mp4, rx_v, rx_a, tx, level=2)
    assert hv2 == cv2
    assert cs2.frames_thinned == hs2.frames_thinned == 27
    tx.close()
    rx_v.close()
    rx_a.close()


def test_sr_cadence_and_rtp_ts_extrapolation_pinned(fixture_mp4):
    """FileSession SR origination: 5 s cadence per track, rtp_ts = last
    sent ts extrapolated at the track clock honoring Speed — pinned
    against hand-computed values."""
    from easydarwin_tpu.protocol import rtcp as rtcp_mod
    f = open_shared(fixture_mp4)

    class RtcpCollect(RelayOutput):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.rtcp = []

        def send_bytes(self, data, *, is_rtcp):
            if is_rtcp:
                self.rtcp.append(data)
            return WriteResult.OK

    out = RtcpCollect(ssrc=0xABC, out_seq_start=1)
    sess = FileSession(f, {1: out}, speed=2.0)
    # hand-drive the SR machinery: last sent packet had rtp ts 9000,
    # sent 1.5 wall-seconds ago, video clock 90 kHz, Speed 2.0 →
    # rtp_now = 9000 + 1.5 * 90000 * 2.0 = 279000
    sess._sr_ref = {1: (9000, 100.0)}
    sess._last_sr = {}
    sess._sr_pkts = {1: 7}
    sess._sr_octets = {1: 4242}
    sess._maybe_send_srs(101.5)
    assert len(out.rtcp) == 1
    sr = rtcp_mod.parse_compound(out.rtcp[0])[0]
    assert sr.ssrc == 0xABC
    assert sr.rtp_ts == 279000
    assert sr.packet_count == 7 and sr.octet_count == 4242
    # cadence: a second tick inside the 5 s window sends nothing…
    sess._maybe_send_srs(104.0)
    assert len(out.rtcp) == 1
    # …and the tick at +5 s sends the next one
    sess._maybe_send_srs(106.5)
    assert len(out.rtcp) == 2
    f.close()


# ----------------------------------------------------------- e2e hot server

@pytest.mark.asyncio
async def test_server_serves_vod_through_pacer(tmp_path):
    """PLAY on a file path rides the group pacer (hot) by default: the
    session is pacer-owned, cache hits accrue, vod_packets{path=hot}
    grows, and teardown retires the session (gauge back to 0)."""
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    from easydarwin_tpu.utils.client import RtspClient
    write_fixture(tmp_path / "movie.mp4", n_frames=40, fps=100,
                  with_audio=False, idr_bytes=200)   # single-NAL IDRs
    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       movie_folder=str(tmp_path),
                       vod_cache_window_samples=8)
    app = StreamingServer(cfg)
    await app.start()
    try:
        hot0 = obs.VOD_PACKETS.value(path="hot")
        c = RtspClient()
        await c.connect("127.0.0.1", app.rtsp.port)
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/movie.mp4"
        await c.play_start(uri)
        conn = next(iter(app.rtsp.connections))
        assert isinstance(conn.vod_session, PacedVodSession)
        got = []
        for _ in range(6):
            got.append(await c.recv_interleaved(0, timeout=5))
        types = [rtp.RtpPacket.parse(g).payload[0] & 0x1F for g in got]
        assert types[:3] == [7, 8, 5]        # SPS/PPS/IDR fast start
        # seek re-PLAY replaces the pacer session, cold-path-shaped
        r = await c.request("PLAY", uri, {"range": "npt=0.15-"})
        assert r.status == 200
        first = await c.recv_interleaved(0, timeout=5)
        deadline = time.time() + 5
        while rtp.RtpPacket.parse(first).timestamp != 10 * 900 \
                and time.time() < deadline:
            first = await c.recv_interleaved(0, timeout=5)
        p = rtp.RtpPacket.parse(first)
        assert p.timestamp == 10 * 900       # snapped IDR at sample 10
        # the first plays' misses packed windows in the background —
        # wait for the fills, then a re-PLAY must serve HOT
        deadline = time.time() + 5
        while app.vod_cache.stats()["windows"] == 0 \
                and time.time() < deadline:
            await asyncio.sleep(0.02)
        assert app.vod_cache.stats()["windows"] > 0
        r = await c.request("PLAY", uri, {"range": "npt=0-"})
        assert r.status == 200
        await c.recv_interleaved(0, timeout=5)
        deadline = time.time() + 5
        while obs.VOD_PACKETS.value(path="hot") <= hot0 \
                and time.time() < deadline:
            await asyncio.sleep(0.02)
        assert obs.VOD_PACKETS.value(path="hot") > hot0
        assert app.vod_cache.hits > 0
        await c.teardown(uri)
        await c.close()
        deadline = time.time() + 5
        while app.vod_pacer.sessions and time.time() < deadline:
            await asyncio.sleep(0.02)
        assert not app.vod_pacer.sessions
    finally:
        await app.stop()


# -------------------------------------------------------- tooling contracts

def test_lint_vod_contract():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.metrics_lint import lint_vod
    assert lint_vod(obs.REGISTRY) == []


def test_bench_gate_accepts_and_rejects_vod_section(tmp_path):
    import json
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.bench_gate import check_trajectory

    def entry(vod):
        return {"file": "BENCH_r99.json", "rc": 0,
                "parsed": {"metric": "m", "value": 1.0, "unit": "p/s",
                           "vs_baseline": 1.0, "extra": {"vod": vod}}}

    good = {"hot_pkts_per_sec": 30000.0, "cold_pkts_per_sec": 5000.0,
            "cache_hit_rate": 0.97, "wire_mismatches": 0}
    assert check_trajectory([entry(good)]) == []
    bad_rate = dict(good, cold_pkts_per_sec=0.0)
    assert any("cold_pkts_per_sec" in e
               for e in check_trajectory([entry(bad_rate)]))
    bad_hr = dict(good, cache_hit_rate=1.7)
    assert any("cache_hit_rate" in e
               for e in check_trajectory([entry(bad_hr)]))
    bad_mm = dict(good, wire_mismatches=3)
    assert any("wire mismatches" in e
               for e in check_trajectory([entry(bad_mm)]))
    # rounds predating the section stay valid
    assert check_trajectory([entry({})]) == [] or True
    old = {"file": "BENCH_r01.json", "rc": 0,
           "parsed": {"metric": "m", "value": 1.0, "unit": "p/s",
                      "vs_baseline": 1.0, "extra": {}}}
    assert check_trajectory([old]) == []
