"""The per-stream engine steps cohorts from an epoch-keyed output plan
(ISSUE 27): what it caches, who moves the epoch, and that the wire does
not change.

(a) a seeded churn run against a twin stepped by the scalar oracle,
(b) the walked counter follows what is due, (c) every epoch writer,
(d) the op list row for row, (e) weighted histogram observes.
"""

import random
import socket
import types

import numpy as np
import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.obs.metrics import Registry, TIME_BUCKETS
from easydarwin_tpu.obs.profile import PhaseProfiler, observe_wire
from easydarwin_tpu.protocol import rtp, sdp
from easydarwin_tpu.relay.fanout import TpuFanoutEngine, params_key
from easydarwin_tpu.relay.output import (CollectingOutput, RelayOutput,
                                         WriteResult)
from easydarwin_tpu.relay.stream import RelayStream, StreamSettings

VIDEO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=control:trackID=1\r\n")
AUDIO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=control:trackID=1\r\nm=audio 0 RTP/AVP 97\r\n"
             "a=rtpmap:97 MPEG4-GENERIC/44100\r\na=control:trackID=2\r\n")

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native core unavailable")


def vid_pkt(seq: int, ts: int, key: bool = False, size: int = 60) -> bytes:
    payload = bytes(((3 << 5) | (5 if key else 1),)) + bytes(
        (seq * 7 + i) & 0xFF for i in range(size))
    return rtp.RtpPacket(payload_type=96, seq=seq & 0xFFFF, timestamp=ts,
                         ssrc=0x1234, payload=payload).to_bytes()


def _rx_socket(rcvbuf: int = 1 << 22) -> socket.socket:
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.bind(("127.0.0.1", 0))
    s.setblocking(False)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    return s


def _drain(sock) -> list[bytes]:
    got = []
    while True:
        try:
            got.append(sock.recv(65536))
        except BlockingIOError:
            return got


class SockOut(RelayOutput):
    """A subscriber on a real UDP socket, for both engines: the cohort
    step reaches it by ``native_addr``, the scalar oracle and the
    batch-header path through ``send_bytes`` (RTCP dropped, so the RTP
    byte streams compare clean).  Port 0 is the hard-failing
    destination: EINVAL on either path."""

    def __init__(self, tx, addr, **kw):
        super().__init__(**kw)
        self.tx = tx
        self.native_addr = addr

    def send_bytes(self, data, *, is_rtcp):
        if is_rtcp:
            return WriteResult.OK
        try:
            self.tx.sendto(data, self.native_addr)
        except OSError:
            return WriteResult.ERROR
        return WriteResult.OK


def _stream(delay_ms: int = 30, bucket_size: int = 16) -> RelayStream:
    return RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                       StreamSettings(bucket_delay_ms=delay_ms,
                                      bucket_size=bucket_size,
                                      max_age_ms=1500))


def _per_output_ops(eng, st, now_ms: int) -> np.ndarray:
    """The op list one span per OUTPUT builds on this state — the build
    the cohort step replaced, kept here as the reference."""
    ring = st.rtp_ring
    fast = eng.plan(st, now_ms).udp
    start = max(min(o.bookmark for o in fast), ring.tail)
    ids, lengths, _f = ring.window_meta(start, ring.head - start)
    arrivals = ring.arrival[ids % ring.capacity]
    b_of = {id(o): b for b, bucket in enumerate(st.buckets) for o in bucket}
    rows = []
    for s, out in enumerate(fast):
        lo = max(out.bookmark - start, 0)
        hi = int(np.searchsorted(
            arrivals,
            now_ms - b_of[id(out)] * st.settings.bucket_delay_ms,
            side="right"))
        for j in range(lo, hi):
            if lengths[j] >= 12:
                rows.append((int(ids[j] % ring.capacity), s))
    return np.asarray(rows, np.int32).reshape(-1, 2)


# ------------------------------------------------------------ (a) churn
class _Twin:
    """One stream + its receivers; the same scenario drives two."""

    def __init__(self, tx, seed: int):
        self.tx = tx
        self.rng = random.Random(seed)
        self.st = _stream()
        self.outs: list[SockOut] = []
        self.rx: dict[int, socket.socket] = {}
        self.got: dict[int, list[bytes]] = {}
        self.serial = 0

    def join(self, *, bad: bool = False, bucket=None) -> SockOut:
        sid = self.serial
        self.serial += 1
        rx = _rx_socket()
        addr = ("127.0.0.1", 0) if bad else rx.getsockname()
        o = SockOut(self.tx, addr, ssrc=self.rng.getrandbits(32),
                    out_seq_start=self.rng.getrandbits(16),
                    out_ts_start=self.rng.getrandbits(32))
        o.sid = sid
        self.rx[sid], self.got[sid] = rx, []
        self.outs.append(o)
        self.st.add_output(o, bucket=bucket)
        return o

    def leave(self, i: int) -> None:
        o = self.outs.pop(i)
        self.st.remove_output(o)

    def drain(self) -> None:
        for sid, rx in self.rx.items():
            self.got[sid].extend(_drain(rx))

    def state(self) -> list[tuple]:
        return [(o.sid, o.bookmark, o.packets_sent, o.bytes_sent,
                 o.payload_octets) for o in self.outs]

    def close(self) -> None:
        for rx in self.rx.values():
            rx.close()


def _wire_counts() -> tuple[int, int]:
    return (obs.RELAY_INGEST_TO_WIRE.total_count(),
            obs.RELAY_DUE_TO_WIRE.total_count())


@needs_native
def test_churn_cohort_engine_equals_scalar_oracle_every_wake():
    """2 streams x up to 64 outputs over 4 buckets (+ one for the bad
    destination), 300 wakes of joins, leaves, a thinning-level flip, a
    rebase latch per join, a ladder excursion through ``stream.reflect``,
    a fast-start bookmark re-point, a hard-failing destination and a
    send socket that answers EAGAIN (the csrc fault knob, on the
    per-datagram rung: the 37th datagram of a wake is refused, mid
    cohort; this kernel's loopback drops at a full receiver instead of
    pushing back, so a tiny SO_RCVBUF cannot produce one).  After every
    wake: received bytes per flow in order, every bookmark /
    packets_sent / bytes_sent / payload_octets and both wire
    histograms' counts equal the twin's, stepped by the scalar oracle;
    a plan built from scratch equals the cached one.  On a wake the wire
    refused (and the one after, which replays), the engine's flows are
    a prefix of the oracle's instead."""
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    tx.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)
    pairs = [(_Twin(tx, 100 + k), _Twin(tx, 100 + k)) for k in range(2)]
    engines = [TpuFanoutEngine(egress_fd=tx.fileno()) for _ in pairs]
    rng = random.Random(7)
    for a, b in pairs:
        for _ in range(56):
            a.join()
            b.join()
    t, seq = 1000, 0
    rebuilds0 = obs.ENGINE_PLAN_REBUILDS.value()
    exact = lagging = 0
    try:
        for wake in range(300):
            refused = 150 <= wake < 170 and wake % 2 == 0
            for k, (a, b) in enumerate(pairs):
                # -- the same churn on both twins ----------------------
                if wake % 7 == 3 and len(a.outs) < 63:
                    a.join()
                    b.join()
                if wake % 11 == 5 and len(a.outs) > 50:
                    i = rng.randrange(len(a.outs))
                    if a.outs[i] is not getattr(a, "bad_out", None):
                        a.leave(i)
                        b.leave(i)
                if wake in (60, 75):                    # thinning flip
                    for tw in (a, b):
                        tw.outs[5].thinning.controller.level = \
                            1 if wake == 60 else 0
                if wake == 130:                         # fast-start re-point
                    for tw in (a, b):
                        tw.outs[9].bookmark = max(
                            tw.st.rtp_ring.tail, tw.st.rtp_ring.head - 6)
                if wake == 200:                         # the bad destination,
                    for tw in (a, b):                   # last in op order
                        tw.bad_out = tw.join(bad=True, bucket=4)
                n_new = rng.choice((0, 2, 3, 5))
                for _ in range(n_new):
                    p = vid_pkt(seq, seq * 90, key=seq % 25 == 0)
                    a.st.push_rtp(p, t)
                    b.st.push_rtp(p, t)
                    seq += 1
                if wake % 10 == 9:
                    assert a.st.prune(t) == b.st.prune(t)
                # -- step: the engine, and the oracle -------------------
                c0 = _wire_counts()
                if 100 <= wake < 105:                   # ladder excursion
                    a.st.reflect(t)
                else:
                    if refused:         # per-datagram sends: stop at the
                        engines[k].egress_backend = "scalar"   # 37th, mid
                        native.fault_set(37, 0, 0, 0)          # cohort
                    try:
                        engines[k].step(a.st, t)
                    finally:
                        native.fault_clear()
                        engines[k].egress_backend = "auto"
                c1 = _wire_counts()
                b.st.reflect(t)
                c2 = _wire_counts()
                a.drain()
                b.drain()
                # -- compare ---------------------------------------------
                assert c1[0] - c0[0] == c1[1] - c0[1]   # the pair, always
                behind = a.state() != b.state()
                if behind:
                    # the wire refused: nothing wrong or reordered went
                    # out, and no output is ahead of the oracle's
                    assert 150 <= wake <= 170, (wake, k)
                    lagging += 1
                    for oa, ob in zip(a.outs, b.outs):
                        assert oa.bookmark <= ob.bookmark
                        assert oa.packets_sent <= ob.packets_sent
                        ga, gb = a.got[oa.sid], b.got[ob.sid]
                        assert ga == gb[:len(ga)]
                else:
                    exact += 1
                    assert (c1[0] - c0[0], c1[1] - c0[1]) == \
                        (c2[0] - c1[0], c2[1] - c1[1]) or 149 < wake < 172
                    for oa in a.outs:
                        assert a.got[oa.sid] == b.got[oa.sid], (wake, oa.sid)
                eng = engines[k]
                cached = eng.plan(a.st, t)
                fresh = eng._build_plan(a.st, t, eng._native_ok())
                assert fresh.tables() == cached.tables(), wake
                for co in cached.cohorts:       # the mark is a copy
                    for mark, cols in co.items():
                        assert all(cached.udp[c].bookmark == mark
                                   for c in cols.tolist())
            t += 20
        # a quiet tail: everything owed went out, on both twins alike
        for _ in range(8):
            t += 40
            for k, (a, b) in enumerate(pairs):
                engines[k].step(a.st, t)
                b.st.reflect(t)
                a.drain()
                b.drain()
        for a, b in pairs:
            assert a.state() == b.state()
            assert sum(len(g) for g in a.got.values()) > 5000
            for sid in a.got:
                assert a.got[sid] == b.got[sid], sid
            assert a.bad_out in a.outs and a.bad_out.packets_sent == 0
            assert a.bad_out.bookmark == b.bad_out.bookmark > 0
        assert lagging >= 5 and exact >= 500
        # the plan is rebuilt by churn, not by wakes
        assert obs.ENGINE_PLAN_REBUILDS.value() - rebuilds0 < 2 * 300
        assert all(e.send_errors > 0 for e in engines)
    finally:
        native.fault_clear()
        for a, b in pairs:
            a.close()
            b.close()
        tx.close()


# ------------------------------------------------- (b) walked follows due
@needs_native
def test_walked_counter_follows_what_is_due():
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx = _rx_socket()
    try:
        st = _stream(delay_ms=73)
        for i in range(64):
            st.add_output(SockOut(tx, rx.getsockname(), ssrc=i + 1,
                                  out_seq_start=i))
        eng = TpuFanoutEngine(egress_fd=tx.fileno())
        for i in range(4):
            st.push_rtp(vid_pkt(i, i * 90, key=i == 0), 1000)
        for t in (1000, 1073, 1146, 1219):  # latch and drain every bucket
            eng.step(st, t)
        assert all(o.bookmark == st.rtp_ring.head for o in st.outputs)
        walked = obs.ENGINE_OUTPUTS_WALKED.value
        due = obs.ENGINE_OUTPUTS_DUE.value
        steps = obs.TPU_PASS_SECONDS.count(stage="engine_step")
        rebuilds = obs.ENGINE_PLAN_REBUILDS.value()
        # nothing due, no new packet, same epoch: no output is touched,
        # and the step still files its span and its sample
        w0, d0 = walked(), due()
        assert eng.step(st, 1300) == 0
        assert (walked(), due()) == (w0, d0)
        assert obs.TPU_PASS_SECONDS.count(stage="engine_step") == steps + 1
        # a new packet: bucket 0 is due at once and only it is walked
        st.push_rtp(vid_pkt(4, 360), 1400)
        assert eng.step(st, 1400) == 16
        assert (walked() - w0, due() - d0) == (16, 16)
        # held for the others: nothing; then one more bucket, its size
        assert eng.step(st, 1450) == 0
        assert eng.step(st, 1473) == 16
        assert (walked() - w0, due() - d0) == (32, 32)
        assert eng.step(st, 1400 + 3 * 73) == 32
        assert (walked() - w0, due() - d0) == (64, 64)
        assert obs.ENGINE_PLAN_REBUILDS.value() == rebuilds
    finally:
        tx.close()
        rx.close()


# ------------------------------------------------- (c) the epoch's writers
def _attached(n: int = 3):
    st = _stream()
    outs = [CollectingOutput(ssrc=i + 1, out_seq_start=i) for i in range(n)]
    for o in outs:
        st.add_output(o)
    for i in range(4):
        st.push_rtp(vid_pkt(i, i * 90, key=i == 0), 1000)
    return st, outs


def _w_add(st, outs):
    st.add_output(CollectingOutput(ssrc=99))


def _w_remove(st, outs):
    assert st.remove_output(outs[1])


def _w_reflect(st, outs):
    st.reflect(1000)                        # the ladder's scalar rung


def _w_bookmark(st, outs):
    outs[0].bookmark = 2                    # dvr/timeshift, vod, tests


def _w_meta(st, outs):
    outs[0].meta_field_ids = {"sq": 1}


def _w_level(st, outs):
    outs[0].thinning.controller.level = 2   # hls/segmenter's direct write


def _w_level_rr(st, outs):
    outs[0].on_receiver_report(0.5)         # RTCP feedback -> _bump


def _w_kf_resync(st, outs):
    from easydarwin_tpu.relay.session import RelaySession
    sess = RelaySession("/x", sdp.parse(AUDIO_SDP))
    audio = sess.streams[2]
    audio.add_output(outs[0].__class__(ssrc=5))
    sess.push(2, vid_pkt(0, 0), t_ms=1000)
    e0 = audio.plan_epoch
    sess.push(1, vid_pkt(1, 90, key=True), t_ms=1001)   # -> _kf_resync
    assert audio.plan_epoch > e0
    st.touch_plan()                         # (this case checked its own)


def _w_checkpoint(st, outs):
    from easydarwin_tpu.resilience.checkpoint import _restore_stream
    outs[0].bookmark = 4
    e0 = st.plan_epoch
    _restore_stream(st, {"head": 2, "outputs": [
        {"kind": "udp", "rewrite": [9, 1, 2, 3, 4], "bucket": 0}]},
        lambda rec: CollectingOutput())
    assert outs[0].bookmark == 2 and st.plan_epoch >= e0 + 2


def _w_capacity(st, outs):
    from easydarwin_tpu.cluster import capacity
    made = []
    orig = RelayStream.__init__

    def init(self, *a, **kw):
        orig(self, *a, **kw)
        made.append(self)
    RelayStream.__init__ = init
    try:
        capacity.self_bench(seconds=0.02, cache=False)
    finally:
        RelayStream.__init__ = orig
    # 8 joins, then a rewind of every bookmark per pass
    assert made and made[0].plan_epoch >= 8 + 8
    st.touch_plan()


def _w_dvr(st, outs):
    from easydarwin_tpu.dvr.timeshift import _ShiftTrack
    spilled = types.SimpleNamespace(
        k=1, info=sdp.parse(VIDEO_SDP).streams[0], win_lo=None)
    sess = types.SimpleNamespace(path="/x", trace_id=None)
    out = CollectingOutput(ssrc=7)
    tr = _ShiftTrack(sess, 1, spilled, out, StreamSettings(), 2,
                     live_stream=st)
    assert tr.stream.plan_epoch == 1 and out.bookmark == 0
    tr._maybe_join(sess)                    # leaves the shift ring, joins st
    assert tr.stream.plan_epoch == 2 and out in st.outputs
    assert out.bookmark == 2


def _w_vod(st, outs):
    import tempfile
    from pathlib import Path

    from test_vod_cache import write_fixture
    from easydarwin_tpu.vod.cache import SegmentCache
    from easydarwin_tpu.vod.mp4 import open_shared
    from easydarwin_tpu.vod.session import VodPacerGroup
    with tempfile.TemporaryDirectory() as d:
        f = open_shared(write_fixture(Path(d) / "clip.mp4"))
        pacer = VodPacerGroup(SegmentCache(window_samples=16, device=False),
                              engine_for=None, engine_drop=lambda s: None,
                              lookahead_ms=250)
        out = CollectingOutput(ssrc=3)
        sess = pacer.open(f, {1: out}, speed=1.0, now_ms=1000)
        vst = sess.tracks[0].stream
        e0 = vst.plan_epoch
        assert e0 >= 1 and out.bookmark == 0
        pacer.tick(1100)                    # first fill anchors out_ts_start
        assert vst.plan_epoch > e0
    st.touch_plan()


_REWRITE_FIELDS = ("ssrc", "base_src_seq", "base_src_ts", "out_seq_start",
                   "out_ts_start")

_WRITERS = {
    "add_output": _w_add, "remove_output": _w_remove,
    "stream_reflect": _w_reflect, "bookmark": _w_bookmark,
    "meta_field_ids": _w_meta, "thinning_level": _w_level,
    "thinning_level_rr": _w_level_rr, "session_kf_resync": _w_kf_resync,
    "checkpoint_restore": _w_checkpoint, "capacity_self_bench": _w_capacity,
    "dvr_timeshift": _w_dvr, "vod_session": _w_vod,
    **{f"rewrite_{f}": (lambda st, outs, f=f:
                        setattr(outs[0].rewrite, f, 77))
       for f in _REWRITE_FIELDS},
}


@pytest.mark.parametrize("writer", sorted(_WRITERS))
def test_every_writer_of_what_the_plan_caches_moves_the_epoch(writer):
    st, outs = _attached()
    eng = TpuFanoutEngine()
    plan0 = eng.plan(st, 1000)
    assert eng.plan(st, 1000) is plan0      # steady: the cached tables
    e0 = st.plan_epoch
    _WRITERS[writer](st, outs)
    assert st.plan_epoch > e0
    plan1 = eng.plan(st, 1000)
    assert plan1 is not plan0               # rebuilt, and rebuilt right
    assert plan1.tables() == eng._build_plan(
        st, 1000, eng._native_ok()).tables()


@needs_native
def test_native_ok_change_rebuilds_the_plan():
    """``_native_ok()`` is no stream's state: the plan carries the answer
    it was built under and a different one rebuilds it."""
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        st, outs = _attached()
        for o in outs:
            o.native_addr = ("127.0.0.1", 9)
        eng = TpuFanoutEngine(egress_fd=tx.fileno())
        p0 = eng.plan(st, 1000)
        assert len(p0.udp) == 3 and not p0.other
        assert p0.key == params_key(outs)
        eng.egress_fd = None                # the server lost its egress pair
        p1 = eng.plan(st, 1000)
        assert p1 is not p0 and not p1.udp and len(p1.slow) == 3
        # an output a removed stream no longer owns moves nobody's epoch
        e0 = st.plan_epoch
        st.remove_output(outs[0])
        outs[0].bookmark = 0
        assert st.plan_epoch == e0 + 1
    finally:
        tx.close()


# ------------------------------------------------- (d) ops_np, row for row
@needs_native
def test_cohort_op_list_equals_the_per_output_build(monkeypatch):
    """Across whole cohorts, a straggler in the middle of a bucket, a
    split cohort after a partial send and their re-merge: the op rows
    the cohort step hands the native sender are the rows one span per
    output gives on the same state."""
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    tx.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)
    rxs = [_rx_socket() for _ in range(8)]
    seen: list[np.ndarray] = []
    real = native.ops_from_numpy

    def spy(ops_np):
        seen.append(np.array(ops_np))
        return real(ops_np)
    monkeypatch.setattr(native, "ops_from_numpy", spy)
    try:
        st = _stream(delay_ms=40)
        for i in range(40):                 # buckets of 16, 16, 8
            st.add_output(SockOut(tx, rxs[i % 8].getsockname(),
                                  ssrc=i + 1, out_seq_start=3 * i))
        eng = TpuFanoutEngine(egress_fd=tx.fileno())
        t, seq, checked, multi = 1000, 0, 0, 0
        for wake in range(40):
            for _ in range((3, 0, 5, 2)[wake % 4]):
                st.push_rtp(vid_pkt(seq, seq * 90, key=seq % 20 == 0), t)
                seq += 1
            if wake == 12:                  # stragglers inside bucket 0 and 1
                st.outputs[5].bookmark -= 4
                st.outputs[20].bookmark -= 2
            plan = eng.plan(st, t)
            want = _per_output_ops(eng, st, t)
            multi += any(len(co) > 1 for co in plan.cohorts)
            if wake in (20, 21, 28):        # the wire stops mid-list
                eng.egress_backend = "scalar"
                native.fault_set(11, 0, 0, 0)
            seen.clear()
            sent = eng.step(st, t)
            native.fault_clear()
            eng.egress_backend = "auto"
            if len(want):
                assert len(seen) >= 1
                assert np.array_equal(seen[0], want), wake
                checked += 1
            else:
                assert not seen and sent == 0
            t += 25
        assert checked >= 20 and multi >= 3
        assert sum(len(_drain(rx)) for rx in rxs) == sum(
            o.packets_sent for o in st.outputs) > 0
    finally:
        native.fault_clear()
        tx.close()
        for rx in rxs:
            rx.close()


# ------------------------------------------------- (e) weighted observes
def test_weighted_observes_equal_the_expanded_array():
    rng = np.random.default_rng(3)
    lat = rng.uniform(0.0005, 1.4, 96)          # 3 cohorts x 32 packets
    weights = np.repeat(np.array([16, 1, 15]), 32)
    runs = [(32, 0), (32, 1), (32, 3)]
    expanded = np.repeat(lat, weights)
    runs_x = [(32 * 16, 0), (32, 1), (32 * 15, 3)]

    reg = Registry()
    h_w = reg.histogram("w_seconds", "weighted", labels=("engine",),
                        buckets=TIME_BUCKETS)
    h_x = reg.histogram("x_seconds", "expanded", labels=("engine",),
                        buckets=TIME_BUCKETS)
    h_w.observe_many(lat, weights, engine="native")
    h_x.observe_many(expanded, engine="native")
    sw, sx = (h._state({"engine": "native"}) for h in (h_w, h_x))
    assert sw.counts == sx.counts and sw.count == sx.count == 32 * 32
    assert sw.sum == pytest.approx(sx.sum, rel=1e-12)

    # the pair observe_wire files: hold off in place, weights carried
    before = [(h.count(engine="native"), h.total_sum(), list(
        h._state({"engine": "native"}).counts))
        for h in (obs.RELAY_INGEST_TO_WIRE, obs.RELAY_DUE_TO_WIRE)]
    observe_wire("native", lat.copy(), runs, 73, weights)
    mid = [(h.count(engine="native"), h.total_sum(), list(
        h._state({"engine": "native"}).counts))
        for h in (obs.RELAY_INGEST_TO_WIRE, obs.RELAY_DUE_TO_WIRE)]
    observe_wire("native", expanded.copy(), runs_x, 73)
    after = [(h.count(engine="native"), h.total_sum(), list(
        h._state({"engine": "native"}).counts))
        for h in (obs.RELAY_INGEST_TO_WIRE, obs.RELAY_DUE_TO_WIRE)]
    for b, m, a in zip(before, mid, after):
        assert m[0] - b[0] == a[0] - m[0] == 32 * 32
        assert m[1] - b[1] == pytest.approx(a[1] - m[1], rel=1e-9)
        assert [y - x for x, y in zip(b[2], m[2])] == \
            [y - x for x, y in zip(m[2], a[2])]

    prof_w, prof_x = PhaseProfiler(), PhaseProfiler()
    prof_w.enabled = prof_x.enabled = True
    prof_w.account_latency("/p", lat, weights)
    prof_x.account_latency("/p", expanded)
    a, b = prof_w._sessions["/p"], prof_x._sessions["/p"]
    assert a.lat_counts.tolist() == b.lat_counts.tolist()
    assert a.lat_count == b.lat_count == 32 * 32
    assert a.lat_sum == pytest.approx(b.lat_sum, rel=1e-12)
