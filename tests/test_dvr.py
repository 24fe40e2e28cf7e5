"""ISSUE 12: DVR / time-shift subsystem.

The acceptance core is byte identity over real UDP sockets: a
time-shift subscriber replaying a spilled range must receive wire
bytes identical to a live subscriber's capture of the same ids (same
rewrite schedule), and the catch-up join back to the live ring must be
gapless in seq with the same ssrc — on both the scalar and the
native-engine paths.  Plus the spill file/index/retention contracts,
the zero-repack cache open (``pack_window.calls`` pinned), instant
stream-to-VOD replay of a finalized asset, the recorder crash-safety
satellites and the tooling contracts.
"""

import asyncio
import json
import os
import socket
import time

import numpy as np
import pytest

from easydarwin_tpu import obs
from easydarwin_tpu.dvr import (DvrManager, SpilledTrack, SpillWriter,
                                WindowRows, WindowSpiller, decode_blob,
                                encode_blob, snapshot_window)
from easydarwin_tpu.dvr.spill import SpillError
from easydarwin_tpu.obs import EVENTS
from easydarwin_tpu.protocol import nalu, rtp, sdp
from easydarwin_tpu.relay.output import RelayOutput, WriteResult
from easydarwin_tpu.relay.ring import PacketFlags
from easydarwin_tpu.relay.session import SessionRegistry, now_ms
from easydarwin_tpu.vod.cache import SegmentCache, pack_window
from easydarwin_tpu.vod.session import VodPacerGroup

SPS = bytes((0x67, 0x42, 0x00, 0x1F)) + bytes(range(8))
PPS = bytes((0x68, 0xCE, 0x3C, 0x80, 1, 2, 3, 4))
VIDEO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=fmtp:96 packetization-mode=1\r\n"
             "a=control:trackID=1\r\n")
AV_SDP = (VIDEO_SDP
          + "m=audio 0 RTP/AVP 97\r\na=rtpmap:97 MPEG4-GENERIC/8000\r\n"
            "a=control:trackID=2\r\n")


def frame_packets(seq, ts, *, idr=False, size=700, with_params=False):
    pkts = []
    if with_params:
        for cfg in (SPS, PPS):
            pkts += nalu.packetize_h264(cfg, seq=seq, timestamp=ts,
                                        ssrc=7, marker_on_last=False)
            seq += 1
    nal = bytes((0x65 if idr else 0x41,)) \
        + bytes(i & 0xFF for i in range(size))
    pkts += nalu.packetize_h264(nal, seq=seq, timestamp=ts, ssrc=7,
                                mtu=1400)
    return pkts, nal


class UdpOut(RelayOutput):
    def __init__(self, sock, addr, **kw):
        super().__init__(**kw)
        self.sock = sock
        self.addr = addr

    def send_bytes(self, data, *, is_rtcp):
        if not is_rtcp:
            self.sock.sendto(data, self.addr)
        return WriteResult.OK


class NativeOut(RelayOutput):
    def send_bytes(self, data, *, is_rtcp):
        return WriteResult.OK


def _rx_socket():
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.bind(("127.0.0.1", 0))
    s.setblocking(False)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 22)
    return s


def _drain(sock) -> list[bytes]:
    out = []
    while True:
        try:
            out.append(sock.recv(65536))
        except BlockingIOError:
            return out


def _rows(n=8, id_lo=0, slot=64):
    data = np.zeros((n, slot), np.uint8)
    length = np.zeros(n, np.int32)
    for i in range(n):
        pkt = bytes((0x80, 96, 0, i, 0, 0, 0, i, 0, 0, 0, 7)) \
            + bytes((i,)) * (10 + i)
        data[i, :len(pkt)] = np.frombuffer(pkt, np.uint8)
        length[i] = len(pkt)
    flags = np.zeros(n, np.int32)
    flags[0] = int(PacketFlags.KEYFRAME_FIRST)
    return WindowRows(id_lo, data, length, flags,
                      np.arange(n, dtype=np.int64) * 3000,
                      np.arange(n, dtype=np.int32) + 100,
                      np.arange(n, dtype=np.int64) * 33 + 1000)


# ================================================================ spill

def test_blob_roundtrip_and_corruption():
    rows = _rows()
    blob = encode_blob(rows)
    back = decode_blob(blob, rows.id_lo)
    assert back.n == rows.n and back.id_lo == rows.id_lo
    for a, b in ((back.length, rows.length), (back.flags, rows.flags),
                 (back.seq, rows.seq), (back.ts, rows.ts),
                 (back.arrival, rows.arrival)):
        assert np.array_equal(a, b)
    for i in range(rows.n):
        assert back.data[i, :back.length[i]].tobytes() \
            == rows.data[i, :rows.length[i]].tobytes()
    with pytest.raises(SpillError):
        decode_blob(b"XXXX" + blob[4:], 0)
    with pytest.raises(SpillError):
        decode_blob(blob[:-3], 0)            # truncated payload


def test_spill_writer_index_retention_compaction(tmp_path):
    from easydarwin_tpu.protocol.sdp import StreamInfo
    info = StreamInfo(media_type="video", payload_type=96,
                      payload_name="H264/90000", codec="H264",
                      clock_rate=90000, track_id=1)
    ev0 = obs.DVR_RETENTION_EVICTIONS.value()
    w = SpillWriter(str(tmp_path / "t1"), info, window_pkts=8,
                    retention_bytes=2000, retention_sec=1e9,
                    compact_floor_bytes=512)
    blobs = {}
    for win in range(16):
        rows = _rows(8, id_lo=win * 8)
        rows.arrival += win * 1000
        w.append_window(win, rows)
        blobs[win] = encode_blob(rows)
    # byte budget evicted the oldest windows and counted them
    assert w.live_bytes <= 2000
    assert w.evictions > 0
    assert obs.DVR_RETENTION_EVICTIONS.value() - ev0 == w.evictions
    # dead bytes outweighed live → at least one compaction happened
    assert w.compactions >= 1
    assert not os.path.exists(w.index_path + ".tmp")   # atomic updates
    kept = sorted(r["win"] for r in w.windows)
    w.finalize()
    sp = SpilledTrack(str(tmp_path / "t1"))
    assert sp.complete and sorted(sp.windows) == kept
    assert sp.info.codec == "H264" and sp.k == 8
    for win in kept:
        assert sp.window_blob(win) == blobs[win]       # offsets rebuilt
        back = sp.read_window(win)
        assert back.id_lo == win * 8
    assert sp.read_window(kept[0] - 1 if kept[0] else 999) is None
    # duration comes from the arrival span of the kept windows
    assert sp.duration_sec() == pytest.approx(
        (sp.windows[kept[-1]]["arr_hi"]
         - sp.windows[kept[0]]["arr_lo"]) / 1000.0)


def test_spill_window_crc_guard(tmp_path):
    """ISSUE 20 satellite: every appended window's index record carries
    a crc32 of the blob, and ``window_blob`` verifies it — a flipped
    byte in ``spill.bin`` reads as a local miss (counted), never as
    bytes that decode into garbage or ship corrupt to a peer.  Pre-crc
    indexes (no ``crc`` key) stay servable unverified."""
    import zlib
    from easydarwin_tpu.protocol.sdp import StreamInfo
    info = StreamInfo(media_type="video", payload_type=96,
                      payload_name="H264/90000", codec="H264",
                      clock_rate=90000, track_id=1)
    w = SpillWriter(str(tmp_path / "t1"), info, window_pkts=8)
    blobs = {}
    for win in range(3):
        rows = _rows(8, id_lo=win * 8)
        w.append_window(win, rows)
        blobs[win] = encode_blob(rows)
    w.finalize()
    sp = SpilledTrack(str(tmp_path / "t1"))
    for win, rec in sp.windows.items():
        assert rec["crc"] == (zlib.crc32(blobs[win]) & 0xFFFFFFFF)
    # flip one byte inside window 1's extent on disk
    rec = sp.windows[1]
    with open(sp.bin_path, "r+b") as fh:
        fh.seek(rec["off"] + rec["nbytes"] // 2)
        b = fh.read(1)
        fh.seek(rec["off"] + rec["nbytes"] // 2)
        fh.write(bytes([b[0] ^ 0xFF]))
    assert sp.window_blob(1) is None and sp.crc_errors == 1
    assert sp.window_blob(0) == blobs[0]            # neighbors intact
    # a pre-crc index (old asset) reads unverified — compat contract
    del rec["crc"]
    assert sp.window_blob(1) is not None
    assert sp.crc_errors == 1
    # spill bytes deleted out from under the index (local eviction):
    # a local miss, not an exception — read_window must stay free to
    # fall through to the peer fetcher / storage restore hooks
    os.unlink(sp.bin_path)
    assert sp.window_blob(0) is None
    assert sp.read_window(0) is None


def test_seek_id_snaps_to_keyframe(tmp_path):
    from easydarwin_tpu.protocol.sdp import StreamInfo
    info = StreamInfo(media_type="video", payload_type=96,
                      payload_name="H264/90000", codec="H264",
                      clock_rate=90000, track_id=1)
    w = SpillWriter(str(tmp_path / "t1"), info, window_pkts=8)
    for win in range(4):
        rows = _rows(8, id_lo=win * 8)
        rows.arrival = np.arange(8, dtype=np.int64) * 100 + win * 800
        # keyframe-first only on even windows
        rows.flags[0] = (int(PacketFlags.KEYFRAME_FIRST)
                         if win % 2 == 0 else 0)
        w.append_window(win, rows)
    w.finalize()
    sp = SpilledTrack(str(tmp_path / "t1"))
    assert sp.base_arrival_ms == 0
    # npt 1.7 s → arrival 1700 → exact id 17; nearest keyframe-first at
    # or before is window 2's row 0 = id 16
    assert sp.seek_id(1.7, keyframe=False) == 17
    assert sp.seek_id(1.7) == 16
    # npt inside window 1 (no kf) snaps back to window 0's keyframe
    assert sp.seek_id(0.9) == 0
    assert sp.seek_id(0.0) == 0
    assert sp.seek_id(99.0, keyframe=False) == 31     # clamped to end


def test_spiller_rides_live_ring(tmp_path):
    from easydarwin_tpu.relay.session import RelaySession
    sess = RelaySession("/live/sp", sdp.parse(VIDEO_SDP))
    stream = sess.streams[1]
    w = SpillWriter(str(tmp_path / "t1"), stream.info, window_pkts=16)
    spiller = WindowSpiller(stream, w)
    assert spiller.next_win == 0
    c0 = obs.DVR_WINDOWS_SPILLED.value()
    seq = 0
    t = now_ms()
    for i in range(40):
        pkts, _ = frame_packets(seq, i * 3000, idr=(i % 8 == 0),
                                with_params=(i == 0), size=300)
        for p in pkts:
            sess.push(1, p, t_ms=t + i * 10)
        seq += len(pkts)
        spiller.tick(t + i * 10)
    head = stream.rtp_ring.head
    assert spiller.spilled == head // 16
    assert obs.DVR_WINDOWS_SPILLED.value() - c0 == spiller.spilled
    # spilled rows are the ring's rows verbatim
    sp = SpilledTrack(str(tmp_path / "t1"))
    rows = sp.read_window(0)
    ring = stream.rtp_ring
    for i in range(16):
        assert rows.data[i, :rows.length[i]].tobytes() \
            == ring.data[ring.slot(i), :ring.length[ring.slot(i)]].tobytes()
        assert rows.seq[i] == ring.seq[ring.slot(i)]
    # keyframe rel ids recorded in the index
    assert sp.windows[0]["kf"], "first window should hold a keyframe"


# ===================================================== zero-repack open

def test_cache_get_packed_zero_repack(tmp_path):
    cache = SegmentCache(budget_bytes=1 << 20, device=False)
    calls0 = pack_window.calls
    rows = _rows(8)
    from easydarwin_tpu.vod.cache import CachedWindow

    def loader(win):
        return CachedWindow.from_packed(
            None, rows.id_lo, rows.data, rows.length, rows.flags,
            rows.ts, seq=rows.seq, arrival=rows.arrival)

    key = ("dvr", "asset1")
    miss = cache.get_packed(key, 1, 0, loader)
    assert miss is not None and miss.lo == 0 and miss.hi == 8
    assert miss.arrival is not None and miss.seq is not None
    hit = cache.get_packed(key, 1, 0, loader)
    assert hit is miss
    assert cache.hits >= 1 and cache.fills >= 1
    # THE pin: no canonical repack ran for a packed open
    assert pack_window.calls == calls0
    # staged rows exist (engine-ready) and pins work like any window
    assert miss.staged is not None
    cache.pin(miss)
    assert miss.pins == 1
    cache.unpin(miss)
    cache.close()


# ============================================== live→shift→catch-up e2e

def _pump_once(registry, dvr, pacer, engines, t):
    dvr.tick(t)
    pairs = pacer.tick(t)
    for sess in registry.sessions.values():
        for st in sess.streams.values():
            _step(st, engines, t)
    for st, _e in pairs:
        _step(st, engines, t)


def _step(stream, engines, t):
    if engines is None:
        stream.reflect(t)
    else:
        eng = engines.get(id(stream))
        if eng is None:
            from easydarwin_tpu.relay.fanout import TpuFanoutEngine
            eng = engines[id(stream)] = TpuFanoutEngine(
                egress_fd=engines["_fd"])
        eng.step(stream, t)


def _timeshift_scenario(tmp_path, *, engine: bool):
    """Record a live push, replay it from npt 0 at 4× through a
    time-shift session while the pusher keeps going, catch up, join,
    then compare the shifted subscriber's wire capture to the live
    subscriber's — they must be byte-identical with one ssrc and a
    gapless seq run across the join."""
    registry = SessionRegistry()
    cache = SegmentCache(budget_bytes=8 << 20, device=False)
    engines = {"_fd": 0} if engine else None
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx_a, rx_b = _rx_socket(), _rx_socket()
    if engine:
        engines["_fd"] = tx.fileno()

    def engine_for(st):
        return None if engines is None else _engine_of(st)

    def _engine_of(st):
        from easydarwin_tpu.relay.fanout import TpuFanoutEngine
        e = engines.get(id(st))
        if e is None:
            e = engines[id(st)] = TpuFanoutEngine(egress_fd=tx.fileno())
        return e

    pacer = VodPacerGroup(cache, engine_for=engine_for if engine else None,
                          engine_drop=lambda s: None, lookahead_ms=150)
    dvr = DvrManager(str(tmp_path / "dvr"), cache, pacer, registry,
                     window_pkts=16, retention_bytes=32 << 20,
                     retention_sec=600.0)
    sess = registry.find_or_create("/live/ts", VIDEO_SDP)
    stream = sess.streams[1]
    if engine:
        out_a = NativeOut(ssrc=0x111, out_seq_start=500)
        out_a.native_addr = rx_a.getsockname()
    else:
        out_a = UdpOut(tx, rx_a.getsockname(), ssrc=0x111,
                       out_seq_start=500)
    sess.add_output(1, out_a)
    assert dvr.arm(sess, VIDEO_SDP)
    calls0 = pack_window.calls
    joins0 = obs.DVR_CATCHUP_JOINS.value()

    seq = 0
    frame = 0

    def push_frames(n, gap_s=0.004):
        nonlocal seq, frame
        for _ in range(n):
            pkts, _ = frame_packets(seq, frame * 3000,
                                    idr=(frame % 8 == 0),
                                    with_params=(frame == 0), size=700)
            for p in pkts:
                sess.push(1, p, t_ms=now_ms())
            seq += len(pkts)
            frame += 1
            t = now_ms()
            _pump_once(registry, dvr, pacer, engines, t)
            time.sleep(gap_s)

    push_frames(60)                      # ~0.25 s of recorded past
    # shifted subscriber: SAME rewrite schedule as the live capture
    if engine:
        out_b = NativeOut(ssrc=0x111, out_seq_start=500)
        out_b.native_addr = rx_b.getsockname()
    else:
        out_b = UdpOut(tx, rx_b.getsockname(), ssrc=0x111,
                       out_seq_start=500)
    shift = dvr.open_timeshift("/live/ts", {1: out_b}, start_npt=0.0,
                               speed=4.0)
    assert shift is not None
    assert shift.catchup_pending
    # keep pushing while the shifted viewer catches up
    deadline = time.time() + 30
    while not shift.tracks[0].joined and time.time() < deadline:
        push_frames(4)
    assert shift.tracks[0].joined, "catch-up join never happened"
    assert obs.DVR_CATCHUP_JOINS.value() - joins0 == 1
    push_frames(12)                      # both now served from live
    for _ in range(20):                  # drain bucket-delayed tails
        _pump_once(registry, dvr, pacer, engines, now_ms())
        time.sleep(0.005)
    time.sleep(0.05)
    cap_a, cap_b = _drain(rx_a), _drain(rx_b)
    assert len(cap_a) > 70
    # byte identity: the shifted replay + catch-up tail equals the live
    # capture of the same ids, packet for packet
    assert cap_b == cap_a[:len(cap_b)]
    assert len(cap_a) - len(cap_b) <= 0, \
        f"shift capture short by {len(cap_a) - len(cap_b)}"
    # gapless seq, single ssrc across the join
    seqs = [rtp.RtpPacket.parse(d).seq for d in cap_b]
    ssrcs = {rtp.RtpPacket.parse(d).ssrc for d in cap_b}
    assert ssrcs == {0x111}
    for i, s in enumerate(seqs):
        assert s == (500 + i) & 0xFFFF
    # zero repack: nothing went through the canonical mp4 packer
    assert pack_window.calls == calls0
    res = dvr.finalize("/live/ts")
    assert res is not None and res["windows"] > 0
    pacer.close()
    cache.close()
    tx.close()
    rx_a.close()
    rx_b.close()
    return cap_a, str(tmp_path / "dvr")


def test_timeshift_byte_identity_and_catchup_scalar(tmp_path):
    _timeshift_scenario(tmp_path, engine=False)


def test_timeshift_byte_identity_and_catchup_native(tmp_path):
    from easydarwin_tpu import native
    if not native.available():
        pytest.skip("native engine unavailable")
    _timeshift_scenario(tmp_path, engine=True)


def test_finalized_asset_instant_vod_replay(tmp_path):
    """Stop → the asset is immediately servable with ZERO repacks: a
    fresh pacer replays the ``.dvr`` asset and the wire equals the live
    capture's spilled prefix; ``pack_window`` never ran."""
    cap_a, dvr_root = _timeshift_scenario(tmp_path, engine=False)
    registry = SessionRegistry()            # live session long gone
    cache = SegmentCache(budget_bytes=8 << 20, device=False)
    pacer = VodPacerGroup(cache, lookahead_ms=250)
    dvr = DvrManager(dvr_root, cache, pacer, registry, window_pkts=16)
    asset = dvr.open_asset("/live/ts")
    assert asset is not None and asset.complete
    n_spilled = sum(r["n"] for r in asset.tracks[1].windows.values())
    asset.close()
    calls0 = pack_window.calls
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx = _rx_socket()
    out = UdpOut(tx, rx.getsockname(), ssrc=0x111, out_seq_start=500)
    sess = dvr.open_timeshift("/live/ts.dvr", {1: out}, start_npt=0.0,
                              speed=2000.0)
    assert sess is not None
    deadline = time.time() + 20
    while not sess.done and time.time() < deadline:
        t = now_ms()
        for st, _e in pacer.tick(t):
            st.reflect(t)
        time.sleep(0.002)
    assert sess.done
    time.sleep(0.05)
    cap = _drain(rx)
    assert len(cap) == n_spilled
    assert cap == cap_a[:n_spilled]
    assert pack_window.calls == calls0      # the acceptance pin
    assert cache.hits + cache.fills > 0
    pacer.close()
    cache.close()
    tx.close()
    rx.close()


def test_pause_resume_shifts_and_positions(tmp_path):
    """PAUSE semantics: a 1× resume from a pause bookmark stays shifted
    (never force-joins), delivery restarts exactly at the bookmark, and
    ``pause_ids``/``position_npt`` expose a consistent cursor."""
    registry = SessionRegistry()
    cache = SegmentCache(budget_bytes=8 << 20, device=False)
    pacer = VodPacerGroup(cache, lookahead_ms=150)
    dvr = DvrManager(str(tmp_path / "dvr"), cache, pacer, registry,
                     window_pkts=16)
    sess = registry.find_or_create("/live/pr", VIDEO_SDP)
    assert dvr.arm(sess, VIDEO_SDP)
    seq = 0
    for i in range(80):
        pkts, _ = frame_packets(seq, i * 3000, idr=(i % 8 == 0),
                                with_params=(i == 0), size=300)
        for p in pkts:
            sess.push(1, p, t_ms=now_ms())
        seq += len(pkts)
        dvr.tick(now_ms())
        time.sleep(0.002)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx = _rx_socket()
    out = UdpOut(tx, rx.getsockname(), ssrc=0x222, out_seq_start=100)
    shift = dvr.open_timeshift("/live/pr", {1: out}, start_npt=0.0,
                               speed=1.0)
    deadline = time.time() + 10
    while out.packets_sent < 20 and time.time() < deadline:
        t = now_ms()
        dvr.tick(t)
        for st, _e in pacer.tick(t):
            st.reflect(t)
        time.sleep(0.002)
    assert out.packets_sent >= 20
    ids = shift.pause_ids()
    # the resume cursor never exceeds the fill cursor and covers
    # everything delivered
    assert 0 < ids[1] <= shift.tracks[0].cursor
    assert shift.position_npt() > 0.0
    shift.stop()
    cap1 = _drain(rx)
    # resume exactly at the bookmark: first replayed packet is the
    # bookmark id's packet (same wire bytes as a contiguous capture)
    out2 = UdpOut(tx, rx.getsockname(), ssrc=0x222, out_seq_start=100)
    resumed = dvr.open_timeshift("/live/pr", {1: out2}, start_ids=ids,
                                 speed=1.0)
    deadline = time.time() + 10
    while out2.packets_sent < 5 and time.time() < deadline:
        t = now_ms()
        for st, _e in pacer.tick(t):
            st.reflect(t)
        time.sleep(0.002)
    cap2 = _drain(rx)
    assert cap2, "resume never delivered"
    ring = sess.streams[1].rtp_ring
    rid = ids[1]
    expect_payload = ring.data[ring.slot(rid),
                               :ring.length[ring.slot(rid)]].tobytes()[12:]
    assert cap2[0][12:] == expect_payload
    # 1× from the past must stay a shifted session, not force a join
    assert not resumed.tracks[0].joined
    resumed.stop()
    pacer.close()
    cache.close()
    tx.close()
    rx.close()
    assert len(cap1) >= 20


def test_spill_writer_rearm_truncates(tmp_path):
    """Re-arming a path starts a FRESH asset: the new writer truncates
    ``spill.bin`` instead of appending after the previous asset's blobs
    (an unaccounted dead prefix no retention budget would ever
    reclaim)."""
    from easydarwin_tpu.protocol.sdp import StreamInfo
    info = StreamInfo(media_type="video", payload_type=96,
                      payload_name="H264/90000", codec="H264",
                      clock_rate=90000, track_id=1)
    w1 = SpillWriter(str(tmp_path / "t1"), info, window_pkts=8)
    for win in range(4):
        w1.append_window(win, _rows(8, id_lo=win * 8))
    w1.finalize()
    size1 = os.path.getsize(w1.bin_path)
    assert size1 > 0
    w2 = SpillWriter(str(tmp_path / "t1"), info, window_pkts=8)
    rows = _rows(8, id_lo=0)
    w2.append_window(0, rows)
    w2.finalize()
    # only the new asset's bytes remain on disk
    assert os.path.getsize(w2.bin_path) == len(encode_blob(rows))
    sp = SpilledTrack(str(tmp_path / "t1"))
    assert sorted(sp.windows) == [0]
    back = sp.read_window(0)
    assert back is not None and np.array_equal(back.seq, rows.seq)


def test_timeshift_tail_clamped_window_no_duplicates(tmp_path):
    """A spilled window snapshot ABOVE the grid line (ring already
    evicted past ``w·k``) plus a resume cursor below its ``id_lo``:
    the fill must snap the cursor forward — advancing it from below
    while serving from rel 0 re-served the same rows as fresh
    out-seqs.  Also covers the unresolvable-anchor resume: the anchor
    packet's window content starts past the cursor, so the session
    anchors on the first row actually served instead of stalling."""
    from easydarwin_tpu.protocol.sdp import StreamInfo
    from easydarwin_tpu.dvr.service import DvrAsset
    from easydarwin_tpu.dvr.timeshift import TimeShiftSession
    info = StreamInfo(media_type="video", payload_type=96,
                      payload_name="H264/90000", codec="H264",
                      clock_rate=90000, track_id=1)
    w = SpillWriter(str(tmp_path / "t1"), info, window_pkts=16)
    rows = _rows(8, id_lo=5)                 # ids 5..12 of window 0
    w.append_window(0, rows)
    w.finalize()
    sp = SpilledTrack(str(tmp_path / "t1"))
    cache = SegmentCache(budget_bytes=1 << 20, device=False)
    pacer = VodPacerGroup(cache, lookahead_ms=150)
    asset = DvrAsset("/live/tc", str(tmp_path), {1: sp}, complete=True)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx = _rx_socket()
    out = UdpOut(tx, rx.getsockname(), ssrc=0x444, out_seq_start=10)
    sess = TimeShiftSession(pacer, asset, {1: out}, start_ids={1: 0},
                            speed=1000.0)
    assert sess.anchor_pending               # id 0 resolves nowhere
    pacer.adopt(sess)
    deadline = time.time() + 10
    while not sess.done and time.time() < deadline:
        t = now_ms()
        for st, _e in pacer.tick(t):
            st.reflect(t)
        time.sleep(0.002)
    assert sess.done
    time.sleep(0.02)
    cap = _drain(rx)
    # exactly the 8 stored rows, each once — no re-served prefix
    assert len(cap) == 8
    payloads = [d[12:] for d in cap]
    assert len(set(payloads)) == 8
    assert sess.tracks[0].gaps >= 1          # the snap was counted
    sess.stop()
    pacer.close()
    cache.close()
    tx.close()
    rx.close()


def test_timeshift_resume_anchor_from_first_served_row(tmp_path):
    """Audio-only PAUSE-resume (no video track to anchor on): the due
    schedule must anchor at the resume point, not the recording start —
    the old fallback delayed every packet by the recording's elapsed
    duration (an hour-old stream resumed into an hour of silence)."""
    from easydarwin_tpu.protocol.sdp import StreamInfo
    from easydarwin_tpu.dvr.service import DvrAsset
    from easydarwin_tpu.dvr.timeshift import TimeShiftSession
    info = StreamInfo(media_type="audio", payload_type=97,
                      payload_name="MPEG4-GENERIC/8000", codec="AAC",
                      clock_rate=8000, track_id=2)
    w = SpillWriter(str(tmp_path / "t2"), info, window_pkts=8)
    for win in range(4):
        rows = _rows(8, id_lo=win * 8)
        # arrivals spread over ~64 s of recording
        rows.arrival = (np.arange(8, dtype=np.int64) + win * 8) * 2000
        w.append_window(win, rows)
    w.finalize()
    sp = SpilledTrack(str(tmp_path / "t2"))
    cache = SegmentCache(budget_bytes=1 << 20, device=False)
    pacer = VodPacerGroup(cache, lookahead_ms=150)
    asset = DvrAsset("/live/ao", str(tmp_path), {2: sp}, complete=True)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx = _rx_socket()
    out = UdpOut(tx, rx.getsockname(), ssrc=0x555, out_seq_start=10)
    sess = TimeShiftSession(pacer, asset, {2: out}, start_ids={2: 24},
                            speed=1000.0)
    assert sess.anchor_pending
    pacer.adopt(sess)
    deadline = time.time() + 5
    while not sess.done and time.time() < deadline:
        t = now_ms()
        for st, _e in pacer.tick(t):
            st.reflect(t)
        time.sleep(0.002)
    # the tail from the resume point arrives promptly (old fallback:
    # first due ~48 s out, nothing would have been delivered here)
    assert sess.done
    time.sleep(0.02)
    cap = _drain(rx)
    assert len(cap) == 8                     # ids 24..31
    assert not sess.anchor_pending
    sess.stop()
    pacer.close()
    cache.close()
    tx.close()
    rx.close()


def test_peer_fetch_pending_holds_cursor(tmp_path):
    """A peer fetch IN FLIGHT (fetcher returns ``b\"\"``) must hold the
    time-shift cursor — hopping would permanently skip a window that
    lands next tick.  Once the blob arrives the window serves in full,
    gapless."""
    from easydarwin_tpu.protocol.sdp import StreamInfo
    from easydarwin_tpu.dvr.service import DvrAsset
    from easydarwin_tpu.dvr.timeshift import TimeShiftSession
    info = StreamInfo(media_type="video", payload_type=96,
                      payload_name="H264/90000", codec="H264",
                      clock_rate=90000, track_id=1)
    # local index holds only window 1; window 0 lives on the peer
    w = SpillWriter(str(tmp_path / "t1"), info, window_pkts=8)
    local = _rows(8, id_lo=8)
    local.seq = local.seq + 8            # src seq continues across wins
    w.append_window(1, local)
    w.finalize()
    remote = _rows(8, id_lo=0)
    blob = encode_blob(remote)
    state = {"ready": False, "calls": 0}

    def fetch(win):
        state["calls"] += 1
        if win != 0:
            return None
        return blob if state["ready"] else b""

    sp = SpilledTrack(str(tmp_path / "t1"), fetch=fetch)
    assert sp.read_window(0) is None and sp.fetch_pending
    assert sp.read_window(1) is not None and not sp.fetch_pending
    cache = SegmentCache(budget_bytes=1 << 20, device=False)
    pacer = VodPacerGroup(cache, lookahead_ms=150)
    asset = DvrAsset("/live/pf", str(tmp_path), {1: sp}, complete=True)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx = _rx_socket()
    out = UdpOut(tx, rx.getsockname(), ssrc=0x666, out_seq_start=10)
    # start_ids pins the cursor at id 0 (a seek would snap to the first
    # LOCAL window): the peer-advertised window 0 must be awaited
    sess = TimeShiftSession(pacer, asset, {1: out}, start_ids={1: 0},
                            speed=1000.0)
    pacer.adopt(sess)
    for _ in range(6):                       # fetch stays pending
        t = now_ms()
        for st, _e in pacer.tick(t):
            st.reflect(t)
        time.sleep(0.002)
    assert sess.tracks[0].cursor == 0, "cursor hopped a pending window"
    assert sess.tracks[0].gaps == 0
    assert state["calls"] > 1                # it kept retrying
    state["ready"] = True
    deadline = time.time() + 10
    while not sess.done and time.time() < deadline:
        t = now_ms()
        for st, _e in pacer.tick(t):
            st.reflect(t)
        time.sleep(0.002)
    assert sess.done
    time.sleep(0.02)
    cap = _drain(rx)
    assert len(cap) == 16                    # both windows, in order
    assert sess.tracks[0].gaps == 0
    seqs = [rtp.RtpPacket.parse(d).seq for d in cap]
    assert seqs == [(10 + i) & 0xFFFF for i in range(16)]
    sess.stop()
    pacer.close()
    cache.close()
    tx.close()
    rx.close()


def test_rearm_generation_and_full_finalize_flush(tmp_path):
    """(a) Re-arming a path bumps the recording generation, so the new
    asset's cache key can never hit the previous recording's
    still-LRU-resident windows.  (b) finalize() flushes EVERY completed
    window, not just the per-wake ``max_windows`` cap of 8."""
    registry = SessionRegistry()
    cache = SegmentCache(budget_bytes=1 << 20, device=False)
    pacer = VodPacerGroup(cache)
    dvr = DvrManager(str(tmp_path / "dvr"), cache, pacer, registry,
                     window_pkts=8)
    sess = registry.find_or_create("/live/g", VIDEO_SDP)
    assert dvr.arm(sess, VIDEO_SDP)
    seq = 0
    # >8 windows' worth of packets with NO intermediate tick: the
    # finalize must spill them all
    for i in range(96):
        pkts, _ = frame_packets(seq, i * 3000, idr=(i % 8 == 0),
                                with_params=(i == 0), size=200)
        for p in pkts:
            sess.push(1, p, t_ms=now_ms())
        seq += len(pkts)
    head = sess.streams[1].rtp_ring.head
    res = dvr.finalize("/live/g")
    assert res is not None
    assert res["windows"] == head // 8, \
        f"finalize dropped windows: {res['windows']} of {head // 8}"
    asset1 = dvr.open_asset("/live/g")
    key1 = asset1.asset_key
    # second recording cycle on the same path
    sess2 = registry.find_or_create("/live/g", VIDEO_SDP)
    assert dvr.arm(sess2, VIDEO_SDP)
    dvr.finalize("/live/g")
    asset2 = dvr.open_asset("/live/g")
    key2 = asset2.asset_key
    asset2.close()
    assert key1 != key2, "re-arm must change the cache key"
    # a reader of the OLD generation must not adopt the new index on
    # reload (truncated spill file, new ring id space) — its miss path
    # marks the asset superseded instead of mixing generations
    old_tr = asset1.tracks[1]
    assert old_tr.read_window(10 ** 6) is None
    assert old_tr.superseded and old_tr.windows == {}
    asset1.close()
    pacer.close()
    cache.close()


# ====================================================== manager surface

def test_manager_lifecycle_advertise_peer_fill(tmp_path):
    registry = SessionRegistry()
    cache = SegmentCache(budget_bytes=1 << 20, device=False)
    pacer = VodPacerGroup(cache)
    dvr = DvrManager(str(tmp_path / "dvr"), cache, pacer, registry,
                     window_pkts=16)
    # path confinement: crafted paths never escape the dvr root
    assert dvr._dir_for("/../../etc") is None or \
        dvr._dir_for("/../../etc").startswith(str(tmp_path))
    sess = registry.find_or_create("/live/a", VIDEO_SDP)
    assert dvr.arm(sess, VIDEO_SDP)
    assert not dvr.arm(sess, VIDEO_SDP)      # idempotent
    assert dvr.armed("/live/a")
    seq = 0
    t0 = now_ms()
    for i in range(48):
        pkts, _ = frame_packets(seq, i * 3000, idr=(i % 8 == 0),
                                with_params=(i == 0), size=200)
        for p in pkts:
            sess.push(1, p, t_ms=t0 + i * 5)
        seq += len(pkts)
    dvr.tick(t0 + 1000)
    adv = dvr.advertise()
    assert "/live/a" in adv and "1" in adv["/live/a"]
    lo, hi = adv["/live/a"]["1"]
    assert lo == 0 and hi >= 0
    # window_blob serves armed assets (the REST peer-fill payload)
    blob = dvr.window_blob("/live/a", 1, 0)
    assert blob is not None
    assert decode_blob(blob, 0).n == 16
    assert dvr.window_blob("/live/a", 1, 9999) is None
    # registry loses the session → tick auto-finalizes
    registry.remove("/live/a")
    dvr.tick(t0 + 2000)
    assert not dvr.armed("/live/a")
    asset = dvr.open_asset("/live/a")
    assert asset is not None and asset.complete
    asset.close()
    # finalized assets still serve blobs
    assert dvr.window_blob("/live/a.dvr", 1, 0) == blob
    # a fetcher-backed open peer-fills windows the local index lacks
    calls = []

    def fetch(path, tid, win):
        calls.append((path, tid, win))
        return blob if win == 0 else None

    dvr2 = DvrManager(str(tmp_path / "dvr2"), cache, pacer, registry,
                      window_pkts=16)
    dvr2.fetcher = fetch
    os.makedirs(str(tmp_path / "dvr2/live/b/track1"), exist_ok=True)
    with open(str(tmp_path / "dvr2/live/b/track1/index.json"), "w") as fh:
        json.dump({"version": 1, "k": 16, "complete": True,
                   "media": {"media_type": "video", "payload_type": 96,
                             "payload_name": "H264/90000",
                             "codec": "H264", "clock_rate": 90000,
                             "track_id": 1, "fmtp": ""},
                   "windows": []}, fh)
    open(str(tmp_path / "dvr2/live/b/track1/spill.bin"), "wb").close()
    asset2 = dvr2.open_asset("/live/b")
    rows = asset2.tracks[1].read_window(0)
    assert rows is not None and rows.n == 16
    assert calls and calls[0] == ("/live/b", 1, 0)
    asset2.close()
    pacer.close()
    cache.close()


# ======================================== recorder crash-safety satellites

def test_recorder_tmp_rename_and_orphan_sweep(tmp_path):
    from easydarwin_tpu.relay.session import RelaySession
    from easydarwin_tpu.vod.record import RecordingManager, sweep_orphans
    from easydarwin_tpu.vod.mp4 import Mp4File
    sess = RelaySession("/live/cr", sdp.parse(VIDEO_SDP))
    mgr = RecordingManager()
    out_path = str(tmp_path / "rec.mp4")
    mgr.start(sess, out_path)
    seq = 0
    for i in range(8):
        pkts, _ = frame_packets(seq, i * 3000, idr=(i % 4 == 0),
                                with_params=(i == 0), size=300)
        for p in pkts:
            sess.push(1, p, t_ms=1000 + i)
        seq += len(pkts)
        if i == 0:
            sess.reflect(2000)
    sess.reflect(5000)
    # mid-record: ONLY the tmp exists (a crash here leaves no
    # unplayable file at the published path)
    assert os.path.exists(out_path + ".tmp")
    assert not os.path.exists(out_path)
    # simulate the crash: the tmp is an orphan the boot sweep reports
    orphans = sweep_orphans(str(tmp_path))
    assert orphans == [out_path + ".tmp"]
    evs = [e for e in EVENTS.tail(50) if e["event"] == "record.orphan"]
    assert evs and evs[-1]["file"] == out_path + ".tmp"
    # clean stop renames atomically and the file is playable
    res = mgr.stop("/live/cr")
    assert res["path"] == out_path
    assert os.path.exists(out_path)
    assert not os.path.exists(out_path + ".tmp")
    f = Mp4File(out_path)
    assert f.video_track().n_samples == 8
    f.close()
    assert sweep_orphans(str(tmp_path)) == []


def test_record_roundtrip_through_hot_cache(tmp_path):
    """Satellite: record a live A/V push, then serve the recorded asset
    through the HOT SegmentCache path and depacketize the wire — the
    access units must equal the recorded file's samples exactly."""
    from easydarwin_tpu.relay.session import RelaySession
    from easydarwin_tpu.vod.depacketize import H264Depacketizer
    from easydarwin_tpu.vod.mp4 import Mp4File, open_shared
    from easydarwin_tpu.vod.record import RecordingManager
    sess = RelaySession("/live/rt", sdp.parse(AV_SDP))
    mgr = RecordingManager()
    out_path = str(tmp_path / "rt.mp4")
    mgr.start(sess, out_path)
    seq = 0
    for i in range(24):
        pkts, _ = frame_packets(seq, i * 3000, idr=(i % 6 == 0),
                                with_params=(i % 6 == 0), size=1800)
        for p in pkts:
            sess.push(1, p, t_ms=1000 + i)
        seq += len(pkts)
        # interleaved audio rides the same session; the recorder's
        # video sink must ignore it
        au = rtp.RtpPacket(payload_type=97, seq=i, timestamp=i * 1024,
                           ssrc=9, payload=bytes((0xFF, i))).to_bytes()
        sess.push(2, au, t_ms=1000 + i)
        if i == 0:
            sess.reflect(2000)
    sess.reflect(5000)
    res = mgr.stop("/live/rt")
    assert res["samples"] == 24
    f = Mp4File(out_path)
    track = f.video_track()
    want = [f.read_sample(track, i) for i in range(track.n_samples)]
    f.close()
    # serve through the pacer's hot path over real UDP
    cache = SegmentCache(window_samples=8, device=False)
    pacer = VodPacerGroup(cache, lookahead_ms=250)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx = _rx_socket()
    fh = open_shared(out_path)
    out = UdpOut(tx, rx.getsockname(), ssrc=0x333, out_seq_start=0)
    vsess = pacer.open(fh, {1: out}, speed=2000.0)
    # warm the windows so the serve is actually hot
    by_no = {1: track}
    deadline = time.time() + 20
    while not vsess.done and time.time() < deadline:
        t = now_ms()
        for st, _e in pacer.tick(t):
            st.reflect(t)
        time.sleep(0.002)
    assert vsess.done
    time.sleep(0.05)
    cap = _drain(rx)
    assert cap
    d = H264Depacketizer()
    for pkt in cap:
        d.push(pkt)
    aus = d.pop_units() + d.flush()
    got = [au.to_avcc() for au in aus]
    # parameter sets ride in-band ahead of each IDR on the wire; the
    # recorded samples carry the frame NALs — compare frame payloads
    from easydarwin_tpu.vod.packetizer import split_avcc
    got_frames = [au for au in got
                  if split_avcc(au)[-1][0] & 0x1F in (1, 5)]
    assert len(got_frames) == len(want)
    for g, w in zip(got_frames, want):
        assert split_avcc(g)[-1] == split_avcc(w)[-1]
    pacer.close()
    cache.close()
    fh.close()
    tx.close()
    rx.close()


# ============================================================ REST guard

def _mini_app(tmp_path, movie_folder=None):
    import types
    from easydarwin_tpu.server.config import ServerConfig
    from easydarwin_tpu.server.rest import RestApi
    from easydarwin_tpu.vod.record import RecordingManager
    cfg = ServerConfig(movie_folder=str(movie_folder or tmp_path))
    registry = SessionRegistry()
    app = types.SimpleNamespace(registry=registry,
                                recordings=RecordingManager(), dvr=None)
    return RestApi(cfg, app), app, cfg


def test_startrecord_path_traversal_guard(tmp_path):
    root = tmp_path / "movies"
    root.mkdir()
    (tmp_path / "movies2").mkdir()          # sibling sharing the prefix
    outside = tmp_path / "outside"
    outside.mkdir()
    os.symlink(str(outside), str(root / "link"))
    rest, app, cfg = _mini_app(tmp_path, movie_folder=root)
    app.registry.find_or_create("/live/g", VIDEO_SDP)

    def start(fname):
        status, _body = rest._cmd_startrecord(
            {"path": ["/live/g"], "file": [fname]}, b"")[:2]
        return status

    assert start("../evil.mp4") == 400
    assert start("../movies2/evil.mp4") == 400       # sibling prefix
    assert start("link/evil.mp4") == 400             # symlink escape
    # an absolute path is confined INTO the root, never taken verbatim
    assert start("/etc/passwd.mp4") == 200
    assert not os.path.exists("/etc/passwd.mp4")
    _s, _tid, rec = app.recordings.active["/live/g"]
    assert rec.path == str(root / "etc" / "passwd.mp4")
    app.recordings.stop("/live/g")
    # nothing escaped
    assert os.listdir(str(tmp_path / "movies2")) == []
    assert os.listdir(str(outside)) == []
    # a benign nested path is allowed and records
    assert start("sub/ok.mp4") == 200
    assert "/live/g" in app.recordings.active


def test_dvrwindow_rest_endpoint(tmp_path):
    rest, app, cfg = _mini_app(tmp_path)
    # no DVR tier → 404
    st = rest._cmd_dvrwindow({"path": ["/live/x"], "track": ["1"],
                              "win": ["0"]}, b"")[0]
    assert st == 404
    cache = SegmentCache(budget_bytes=1 << 20, device=False)
    pacer = VodPacerGroup(cache)
    dvr = DvrManager(str(tmp_path / "dvr"), cache, pacer, app.registry,
                     window_pkts=8)
    app.dvr = dvr
    sess = app.registry.find_or_create("/live/x", VIDEO_SDP)
    dvr.arm(sess, VIDEO_SDP)
    seq = 0
    for i in range(20):
        pkts, _ = frame_packets(seq, i * 3000, idr=(i == 0),
                                with_params=(i == 0), size=200)
        for p in pkts:
            sess.push(1, p, t_ms=1000 + i)
        seq += len(pkts)
    dvr.tick(99999)
    res = rest._cmd_dvrwindow({"path": ["/live/x"], "track": ["1"],
                               "win": ["0"]}, b"")
    assert res[0] == 200 and res[2] == "application/octet-stream"
    assert decode_blob(res[1], 0).n == 8
    st = rest._cmd_dvrwindow({"path": ["/live/x"], "track": ["1"],
                              "win": ["bad"]}, b"")[0]
    assert st == 400
    pacer.close()
    cache.close()


# ========================================================== server e2e

@pytest.mark.asyncio
async def test_server_pause_rewind_catchup_e2e(tmp_path):
    """Full RTSP shape: push a live stream with DVR on, a TCP player
    PAUSEs, PLAYs with Range into the past (time-shift through the
    pacer), catches up at Speed 4 and rejoins live — one ssrc, gapless
    seq at the player; then stoprecord finalizes and the ``.dvr`` asset
    DESCRIBE/SETUP/PLAYs instantly."""
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    from easydarwin_tpu.utils.client import RtspClient
    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       movie_folder=str(tmp_path), reflect_interval_ms=5,
                       log_folder=str(tmp_path), dvr_enabled=True,
                       dvr_window_pkts=16)
    app = StreamingServer(cfg)
    await app.start()
    try:
        assert app.dvr is not None
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/e2e"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, VIDEO_SDP)
        assert app.dvr.armed("/live/e2e")     # RECORD armed the spiller
        seq = 0

        async def push(n_frames, first=False):
            nonlocal seq
            for i in range(n_frames):
                fr = seq // 2
                pkts, _ = frame_packets(
                    seq, (seq) * 3000, idr=(i % 8 == 0),
                    with_params=(first and i == 0), size=300)
                for p in pkts:
                    pusher.push_packet(0, p)
                seq += len(pkts)
                await asyncio.sleep(0.005)

        await push(40, first=True)
        player = RtspClient()
        await player.connect("127.0.0.1", app.rtsp.port)
        await player.play_start(uri)
        got = [await player.recv_interleaved(0, timeout=5)]
        await push(10)
        # drain whatever live delivered, then PAUSE
        try:
            while True:
                got.append(await player.recv_interleaved(0, timeout=0.3))
        except asyncio.TimeoutError:
            pass
        r = await player.request("PAUSE", uri)
        assert r.status == 200
        conn = next(c for c in app.rtsp.connections if c.player_tracks)
        assert conn.pause_ids, "PAUSE under DVR must latch resume ids"
        await push(10)
        # PLAY with Range into the past → time-shift session
        r = await player.request("PLAY", uri,
                                 {"range": "npt=0.0-", "speed": "4"})
        assert r.status == 200
        assert r.headers.get("speed") == "4"
        from easydarwin_tpu.dvr import TimeShiftSession
        assert isinstance(conn.vod_session, TimeShiftSession)
        shifted = []
        deadline = time.time() + 20
        while (conn.vod_session is not None
               and not conn.vod_session.tracks[0].joined
               and time.time() < deadline):
            await push(2)
            try:
                while True:
                    shifted.append(
                        await player.recv_interleaved(0, timeout=0.05))
            except asyncio.TimeoutError:
                pass
        assert conn.vod_session.tracks[0].joined, "no catch-up join"
        await push(8)
        try:
            while True:
                shifted.append(
                    await player.recv_interleaved(0, timeout=0.3))
        except asyncio.TimeoutError:
            pass
        # replay restarted from npt 0: the first shifted packet is the
        # stream's very first packet again (SPS), and the whole shifted
        # capture is seq-gapless with one ssrc
        seqs = [rtp.RtpPacket.parse(d).seq for d in shifted]
        ssrcs = {rtp.RtpPacket.parse(d).ssrc for d in shifted}
        assert len(ssrcs) == 1
        start = seqs[0]
        for i, s in enumerate(seqs):
            assert s == (start + i) & 0xFFFF, \
                f"seq gap at {i}: {s} != {(start + i) & 0xFFFF}"
        assert rtp.RtpPacket.parse(shifted[0]).payload[0] & 0x1F == 7
        assert obs.DVR_CATCHUP_JOINS.value() >= 1
        # ---- stop → instant .dvr VOD ---------------------------------
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", app.rest.port)
        writer.write(
            f"GET /api/v1/stoprecord?path=/live/e2e HTTP/1.1\r\n"
            f"Host: x\r\n\r\n".encode())
        head = await reader.readuntil(b"\r\n\r\n")
        clen = int([ln for ln in head.split(b"\r\n")
                    if ln.lower().startswith(b"content-length")][0]
                   .split(b":")[1])
        body = json.loads(await reader.readexactly(clen))
        assert int(head.split(b" ")[1]) == 200
        assert int(body["EasyDarwin"]["Body"]["DvrWindows"]) > 0
        writer.close()
        replayer = RtspClient()
        await replayer.connect("127.0.0.1", app.rtsp.port)
        await replayer.play_start(uri + ".dvr")
        first = await replayer.recv_interleaved(0, timeout=5)
        assert rtp.RtpPacket.parse(first).payload[0] & 0x1F == 7
        # PAUSE the replay, then PLAY with NO Range: it must RESUME at
        # the latched bookmark (gapless out-seq), not restart at npt 0
        more = [first]
        try:
            while len(more) < 12:
                more.append(
                    await replayer.recv_interleaved(0, timeout=1.0))
        except asyncio.TimeoutError:
            pass
        r = await replayer.request("PAUSE", uri + ".dvr")
        assert r.status == 200
        try:                             # in-flight stragglers
            while True:
                more.append(
                    await replayer.recv_interleaved(0, timeout=0.2))
        except asyncio.TimeoutError:
            pass
        rconn = next(c for c in app.rtsp.connections
                     if c.dvr_path is not None)
        assert rconn.pause_ids, ".dvr PAUSE must latch resume ids"
        r = await replayer.request("PLAY", uri + ".dvr")
        assert r.status == 200
        nxt = await replayer.recv_interleaved(0, timeout=5)
        last_seq = rtp.RtpPacket.parse(more[-1]).seq
        assert rtp.RtpPacket.parse(nxt).seq == (last_seq + 1) & 0xFFFF, \
            "PLAY after PAUSE on .dvr must resume at the bookmark"
        await replayer.teardown(uri + ".dvr")
        await replayer.close()
        await player.teardown(uri)
        await player.close()
        await pusher.close()
    finally:
        await app.stop()


# -------------------------------------------------------- tooling contracts

async def test_remote_dvr_asset_bootstrap_replay(tmp_path):
    """ISSUE 13 satellite (closes the PR 12 open item): a finalized
    recording replays from a node that NEVER saw the stream.  Node B
    has no local ``.dvr`` state at all; its DESCRIBE bootstraps node
    A's meta/index documents through ``/api/v1/dvrmeta``
    (``DvrManager.materialize``), and PLAY block-fills every window
    through the ``/api/v1/dvrwindow`` peer fetcher — zero repacks, SPS
    fast-start, gapless seq."""
    from easydarwin_tpu.cluster.redis_client import InMemoryRedis
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    from easydarwin_tpu.utils.client import RtspClient

    def _cfg(node):
        d = tmp_path / node
        return ServerConfig(
            rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
            wan_ip="127.0.0.1", reflect_interval_ms=5,
            bucket_delay_ms=0, access_log_enabled=False,
            log_folder=str(d / "logs"), movie_folder=str(d / "movies"),
            server_id=node, cluster_enabled=True,
            cluster_lease_ttl_sec=2.0, cluster_heartbeat_sec=0.3,
            dvr_enabled=True, dvr_window_pkts=16)

    redis = InMemoryRedis()
    app_a = StreamingServer(_cfg("dvr-a"), redis_client=redis)
    app_b = StreamingServer(_cfg("dvr-b"), redis_client=redis)
    await app_a.start()
    await app_b.start()
    pusher = replayer = None
    try:
        uri_a = f"rtsp://127.0.0.1:{app_a.rtsp.port}/live/rb"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app_a.rtsp.port)
        await pusher.push_start(uri_a, VIDEO_SDP)
        assert app_a.dvr.armed("/live/rb")
        seq = 0
        for i in range(80):
            pkts, _ = frame_packets(seq, seq * 3000, idr=(i % 8 == 0),
                                    with_params=(i == 0), size=300)
            for p in pkts:
                pusher.push_packet(0, p)
            seq += len(pkts)
            await asyncio.sleep(0.004)
        for _ in range(100):
            if app_a.dvr.stats()["spilled_windows"] >= 3:
                break
            await asyncio.sleep(0.05)
        assert app_a.dvr.stats()["spilled_windows"] >= 3
        assert app_a.dvr.finalize("/live/rb") is not None
        # B has never seen the stream and has NO local .dvr tree
        assert not os.path.isdir(os.path.join(
            app_b.config.movie_folder, ".dvr", "live"))
        await asyncio.sleep(0.7)      # both leases + node snapshots live
        packs_before = pack_window.calls

        replayer = RtspClient()
        await replayer.connect("127.0.0.1", app_b.rtsp.port)
        uri_b = f"rtsp://127.0.0.1:{app_b.rtsp.port}/live/rb.dvr"
        await replayer.play_start(uri_b)
        got = []
        try:
            while len(got) < 40:
                got.append(await replayer.recv_interleaved(0, timeout=5))
        except asyncio.TimeoutError:
            pass
        assert len(got) >= 20, f"remote replay starved: {len(got)}"
        # SPS fast-start, one ssrc, gapless out-seq — the same contract
        # as a local replay
        assert rtp.RtpPacket.parse(got[0]).payload[0] & 0x1F == 7
        assert len({rtp.RtpPacket.parse(d).ssrc for d in got}) == 1
        seqs = [rtp.RtpPacket.parse(d).seq for d in got]
        for i, s in enumerate(seqs):
            assert s == (seqs[0] + i) & 0xFFFF, f"gap at {i}"
        # the asset was born packed and bootstrapped — NOBODY repacked
        assert pack_window.calls == packs_before
        # the bootstrap materialized B's local skeleton + peer route
        assert app_b.dvr.open_asset("/live/rb.dvr") is not None
        assert "/live/rb" in app_b._dvr_meta_peers
        await replayer.teardown(uri_b)
    finally:
        if replayer is not None:
            await replayer.close()
        if pusher is not None:
            await pusher.close()
        await app_a.stop()
        await app_b.stop()


def test_lint_dvr_contract():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.metrics_lint import lint_dvr
    assert lint_dvr(obs.REGISTRY) == []


def test_bench_gate_accepts_and_rejects_dvr_section(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.bench_gate import check_trajectory

    def entry(dvr=None):
        extra = {} if dvr is None else {"dvr": dvr}
        return {"file": "BENCH_r99.json", "rc": 0,
                "parsed": {"metric": "m", "value": 1.0, "unit": "p/s",
                           "vs_baseline": 1.0, "extra": extra}}

    good = {"timeshift_join_pps": 900.0, "live_join_pps": 1000.0,
            "spill_mbps": 50.0, "reopen_repacks": 0}
    assert check_trajectory([entry(good)]) == []
    assert check_trajectory([entry()]) == []     # old rounds stay valid
    bad = dict(good, reopen_repacks=3)
    assert any("reopen_repacks" in e
               for e in check_trajectory([entry(bad)]))
    bad = dict(good, timeshift_join_pps=-1.0)
    assert any("timeshift_join_pps" in e
               for e in check_trajectory([entry(bad)]))
    # a cold-path-shaped join rate is rejected even when positive
    bad = dict(good, timeshift_join_pps=30.0)
    assert any("cold-path-shaped" in e
               for e in check_trajectory([entry(bad)]))
