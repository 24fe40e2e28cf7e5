"""Megabatch scheduler correctness (ISSUE 4).

The load-bearing guarantee: wire output — headers + payload bytes, in
per-destination order — is byte-identical between megabatched and
per-stream stepping, across mixed shapes, mid-wake stream join/teardown
and the bucket-growth retrace path.  Everything rides real UDP sockets so
the comparison covers the native sendmmsg path end to end.
"""

import random
import socket
import time

import numpy as np
import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.protocol import sdp
from easydarwin_tpu.relay import pump
from easydarwin_tpu.relay.fanout import TpuFanoutEngine, params_key
from easydarwin_tpu.relay.megabatch import (MegabatchScheduler,
                                            _host_affine_params)
from easydarwin_tpu.relay.output import CollectingOutput
from easydarwin_tpu.relay.stream import RelayStream, StreamSettings
from test_pump_ready import ReadyPump

VIDEO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=control:trackID=1\r\n")

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native core unavailable")


def vid_pkt(seq: int, ts: int, nal_type: int = 1) -> bytes:
    payload = bytes(((3 << 5) | nal_type,)) + bytes(
        (seq * 7 + i) & 0xFF for i in range(80))
    from easydarwin_tpu.protocol import rtp
    return rtp.RtpPacket(payload_type=96, seq=seq & 0xFFFF, timestamp=ts,
                         ssrc=0x1234, payload=payload).to_bytes()


class _Wire:
    """N receiver sockets; each logical output gets a distinct one, so
    per-destination ordering is observable per socket."""

    def __init__(self, n: int):
        self.socks = []
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.bind(("127.0.0.1", 0))
            s.setblocking(False)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 22)
            self.socks.append(s)
        self.addrs = [s.getsockname() for s in self.socks]
        self.rx: list[list[bytes]] = [[] for _ in self.socks]

    def drain(self) -> None:
        for i, s in enumerate(self.socks):
            while True:
                try:
                    self.rx[i].append(s.recv(65536))
                except BlockingIOError:
                    break

    def close(self) -> None:
        for s in self.socks:
            s.close()


def _mk_stream(n_outputs: int, addrs, seed: int) -> RelayStream:
    rng = random.Random(seed)
    st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    for i in range(n_outputs):
        o = CollectingOutput(ssrc=rng.getrandbits(32),
                             out_seq_start=rng.getrandbits(16),
                             out_ts_start=rng.getrandbits(32))
        o.native_addr = addrs[i % len(addrs)]
        st.add_output(o)
    return st


def _run_scenario(use_megabatch: bool, wire: _Wire, send_fd: int,
                  ready_set: bool = False):
    """Deterministic multi-stream relay scenario.  Exercises: mixed
    window/subscriber shapes, a mid-run output join (rebase latch +
    params-key change), a mid-run stream teardown, and bucket growth
    (the eligible stream count crosses a pow2 boundary).  ``ready_set``:
    through the server's ``Pump.wake`` with a wheel, so each wake steps
    the streams that were marked."""
    shapes = [(5, 3, 0), (9, 4, 100), (17, 5, 200)]  # (S, burst, seed)
    streams = [_mk_stream(s, wire.addrs, seed) for s, _, seed in shapes]
    engines = [TpuFanoutEngine(egress_fd=send_fd) for _ in streams]
    sched = MegabatchScheduler() if use_megabatch else None
    live = [streams[0]]                    # bucket growth: 1 → 2 → 3
    t, seq = 1000, 0
    serve = (ReadyPump(sched, t).wake if ready_set
             else lambda pairs, t: pump.wake(pairs, sched, t))
    for wake in range(24):
        if wake == 4:
            live.append(streams[1])
        if wake == 8:
            live.append(streams[2])
        if wake == 12:                     # mid-run join on stream 0
            o = CollectingOutput(ssrc=0xABCD, out_seq_start=77)
            o.native_addr = wire.addrs[0]
            streams[0].add_output(o)
        if wake == 18:                     # mid-run teardown of stream 1
            live.remove(streams[1])
        pairs = [(s, engines[streams.index(s)]) for s in live]
        for s in live:
            _S, burst, _seed = shapes[streams.index(s)]
            for _ in range(burst):
                s.push_rtp(vid_pkt(seq, seq * 90,
                                   nal_type=5 if seq % 25 == 0 else 1), t)
                seq += 1
        serve(pairs, t)
        wire.drain()
        t += 20
    if sched is not None:
        sched.drain()
    wire.drain()
    return streams, engines, sched


@needs_native
@pytest.mark.parametrize("ready_set", [False, True],
                         ids=["server_free", "ready_set"])
def test_megabatch_wire_bytes_identical_to_per_stream(ready_set):
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire_a, wire_b = _Wire(6), _Wire(6)
    try:
        _run_scenario(False, wire_a, send.fileno())
        streams_b, engines_b, sched = _run_scenario(
            True, wire_b, send.fileno(), ready_set)
        # byte-identical per destination, in order — headers AND payloads
        assert [len(r) for r in wire_a.rx] == [len(r) for r in wire_b.rx]
        for ra, rb in zip(wire_a.rx, wire_b.rx):
            assert ra == rb
        assert sum(len(r) for r in wire_b.rx) > 0
        # the scheduler actually did the device work: stacked passes ran,
        # per-stream queries and per-wake ring appends stayed at zero,
        # and no device/host divergence was counted
        assert sched.passes > 0
        assert sched.mismatches == 0
        assert sum(e.device_param_refreshes for e in engines_b) == 0
        assert sum(e.dring_appends for e in engines_b) == 0
        assert sum(e.megabatch_installs for e in engines_b) >= 4
    finally:
        wire_a.close()
        wire_b.close()
        send.close()


@needs_native
@pytest.mark.parametrize("ready_set", [False, True],
                         ids=["server_free", "ready_set"])
def test_megabatch_collecting_outputs_identical_to_per_stream(ready_set):
    """The batch-header (slow) sub-path under a megabatch wake: streams
    whose outputs are not native-addressed still deliver byte-identical
    packets — the scheduler must never perturb the fallback path.  With
    the ready set, the second stream is pushed every other wake only and
    is stepped in those."""
    def run(use_megabatch, ready_set=False):
        streams = []
        for seed, n in ((1, 4), (2, 11)):
            st = _mk_stream(n, [None], seed)
            for o in st.outputs:
                o.native_addr = None       # force the batch-header path
            streams.append(st)
        engines = [TpuFanoutEngine() for _ in streams]
        sched = MegabatchScheduler() if use_megabatch else None
        t, seq = 1000, 0
        rp = ReadyPump(sched, t) if ready_set else None
        stepped = 0
        for wake in range(8):
            for st in streams[:1 + (wake % 2 == 0)]:
                for _ in range(6):
                    st.push_rtp(vid_pkt(seq, seq * 90), t)
                    seq += 1
            if rp is not None:
                rp.wake(list(zip(streams, engines)), t)
                stepped += len(rp.pump.stepped)
            else:
                pump.wake(list(zip(streams, engines)), sched, t)
            t += 20
        assert stepped in (0, 12)           # 8 + 4 of the 16 entries
        return [[o.rtp_packets for o in st.outputs] for st in streams]

    assert run(False) == run(True, ready_set)


@needs_native
def test_stage_gather_native_matches_numpy():
    """Batched window extraction: the csrc gather and the numpy fallback
    pack byte-identical fused rows (prefix | le32 length | zero pad)."""
    from easydarwin_tpu.ops import staging
    st = _mk_stream(1, [("127.0.0.1", 1)], 3)
    t = 1000
    for i in range(37):
        st.push_rtp(vid_pkt(i, i * 90, nal_type=5 if i % 10 == 0 else 1), t)
    ring = st.rtp_ring
    rows_native = np.ones((64, staging.ROW_STRIDE), np.uint8)
    rows_numpy = np.ones((64, staging.ROW_STRIDE), np.uint8)
    n1 = native.stage_gather(
        ring.data, ring.length,
        (np.arange(ring.tail, ring.head) % ring.capacity).astype(np.int32),
        96, rows_native)
    # force the numpy path by pretending the native core is absent
    import easydarwin_tpu.native as native_mod
    orig = native_mod.loaded
    native_mod.loaded = lambda: False
    try:
        n2 = staging.gather_window(ring, ring.tail, 64, rows_numpy)
    finally:
        native_mod.loaded = orig
    assert n1 == 37 and n2 == 37
    assert np.array_equal(rows_native, rows_numpy)


def test_scatter_affine_segments_roundtrip():
    """Segment scatter trims the pow2 padding and recovers the -1
    keyframe sentinel through the uint32 wire format."""
    from easydarwin_tpu.models.relay_pipeline import scatter_affine_segments
    s_pad = 8
    packed = np.zeros((2, 4 * s_pad + 1), np.uint32)
    packed[0, 0:3] = (10, 11, 12)              # seq_off
    packed[0, s_pad:s_pad + 3] = (20, 21, 22)  # ts_off
    packed[0, 2 * s_pad:2 * s_pad + 3] = (30, 31, 32)
    packed[0, 3 * s_pad:3 * s_pad + 3] = (0, 2, 0xFFFFFFFF)  # chan
    packed[0, 4 * s_pad] = np.uint32(0xFFFFFFFF)   # kf = -1
    packed[1, 4 * s_pad] = 5
    segs = scatter_affine_segments(packed, [3, 2])
    (sq, ts, sc, ch, kf), (_sq2, _ts2, _sc2, _ch2, kf2) = segs
    assert sq.shape == (1, 3) and sq.flags.c_contiguous
    assert list(sq[0]) == [10, 11, 12]
    assert list(ts[0]) == [20, 21, 22]
    assert list(sc[0]) == [30, 31, 32]
    assert list(ch[0]) == [0, 2, 0xFFFFFFFF]
    assert kf == -1 and kf2 == 5


def test_host_affine_oracle_matches_device_formula():
    """The harvest-time mismatch check's host oracle agrees with the
    device's affine_params over random rewrite states (incl. the
    unlatched base = -1 clamp)."""
    import jax.numpy as jnp

    from easydarwin_tpu.ops.fanout import affine_params, pack_output_state
    rng = random.Random(9)
    outs = []
    for i in range(13):
        o = CollectingOutput(ssrc=rng.getrandbits(32),
                             out_seq_start=rng.getrandbits(16),
                             out_ts_start=rng.getrandbits(32))
        if i % 3:
            o.rewrite.base_src_seq = rng.getrandbits(16)
            o.rewrite.base_src_ts = rng.getrandbits(32)
        outs.append(o)
    key = params_key(outs)
    host = _host_affine_params(key)
    dev = affine_params(jnp.asarray(pack_output_state(outs)))
    for h, d in zip(host, dev):
        assert np.array_equal(h, np.asarray(d))


@needs_native
def test_megabatch_phase_attribution_recorded():
    """Megabatch wakes file their phases under the megabatch engine
    label, inside the closed vocabulary."""
    from easydarwin_tpu.obs import PHASES, families
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire = _Wire(4)
    try:
        _run_scenario(True, wire, send.fileno())
    finally:
        wire.close()
        send.close()
    seen = {k for k in dict(families.RELAY_PHASE_SECONDS._states)
            if k[0] == "megabatch"}
    assert seen, "no megabatch phases recorded"
    assert all(ph in PHASES for _e, ph in seen)
    assert ("megabatch", "stage_gather") in seen
    assert ("megabatch", "h2d") in seen


@needs_native
def test_idle_wake_drains_inflight_after_mass_teardown():
    """Eligibility dropping below megabatch_min_streams must not pin
    torn-down streams/buffers inside in-flight records forever — the
    pump's idle_wake keeps harvesting and drops the cursors."""
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire = _Wire(3)
    try:
        streams = [_mk_stream(5, wire.addrs, i) for i in range(2)]
        engines = [TpuFanoutEngine(egress_fd=send.fileno())
                   for _ in streams]
        sched = MegabatchScheduler()
        pairs = list(zip(streams, engines))
        t, seq = 1000, 0
        for wake in range(3):
            for st in streams:
                for _ in range(4):
                    st.push_rtp(vid_pkt(seq, seq * 90), t)
                    seq += 1
            pump.wake(pairs, sched, t)
            t += 20
        # mass teardown: the pump now sees zero eligible streams and
        # calls idle_wake instead of begin/end_wake
        for _ in range(50):
            pump.wake([], sched, t)
            if not sched._inflight and not sched._tracked:
                break
            time.sleep(0.01)
        assert not sched._inflight
        assert not sched._tracked and not sched._state_cache
        assert sched.mismatches == 0
    finally:
        wire.close()
        send.close()


def test_server_reflect_all_wires_the_scheduler():
    """StreamingServer._reflect_all builds the scheduler once enough
    engine-eligible streams exist and survives wakes with none."""
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    cfg = ServerConfig(tpu_fanout=True, megabatch_enabled=True,
                       tpu_min_outputs=2, megabatch_min_streams=2,
                       access_log_enabled=False)
    app = StreamingServer(cfg)
    app._reflect_all()                     # no streams: scheduler stays off
    assert app.pump.megabatch is None
    for path, seed in (("/live/a", 1), ("/live/b", 2)):
        sess = app.registry.find_or_create(path, VIDEO_SDP)
        st = sess.streams[1]
        rng = random.Random(seed)
        for _ in range(3):
            o = CollectingOutput(ssrc=rng.getrandbits(32))
            st.add_output(o)
        st.push_rtp(vid_pkt(seed, seed * 90), 1000)
    app._reflect_all()
    assert app.pump.megabatch is not None
    assert app.pump.megabatch.wakes >= 1
    # packets actually moved through the engines under the scheduler
    assert all(o.rtp_packets
               for sess in app.registry.sessions.values()
               for s in sess.streams.values() for o in s.outputs)
