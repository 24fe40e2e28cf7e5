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
import types

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
from test_relay_pump import _cfg, _Ladder

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
        assert not sched._tracked and not sched._riders
        assert not sched._carry and not sched.engaged
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


# ------------------------------------------- the scheduler and the ready set
class _EveryPair(MegabatchScheduler):
    """The scheduler as a caller with no ready set drives it
    (``ready=None``): every owned pair's plan read in every wake."""

    def begin_wake(self, pairs, t, ready=None):
        super().begin_wake(pairs, t)

    def end_wake(self, pairs, t, ready=None):
        super().end_wake(pairs, t)


class _Withholding(MegabatchScheduler):
    """Drops ``self.withheld`` from what the pump names: a hand-over
    that went missing."""

    withheld = None

    def _named(self, ready):
        return [p for p in ready if p[0] is not self.withheld]

    def begin_wake(self, pairs, t, ready=None):
        super().begin_wake(pairs, t, self._named(ready))

    def end_wake(self, pairs, t, ready=None):
        super().end_wake(pairs, t, self._named(ready))


class _ServedWorld:
    """The server's ``Pump`` — wheel, ready set, ladder — over real
    streams, engines and one scheduler, with each wake followed by its
    deadlines pass, the audit and a forced harvest (so which wake a pass
    is harvested in does not hang on the host's speed)."""

    def __init__(self, sched, send_fd=-1, t0=1000, min_streams=2):
        self.cfg = _cfg(tpu_min_outputs=1, megabatch_min_streams=min_streams)
        self.ladder = _Ladder()
        self.pump = pump.Pump(
            self.cfg, on_device=lambda s: True, ladder=self.ladder,
            new_engine=lambda: TpuFanoutEngine(egress_fd=send_fd))
        self.pump.wheel = native.TimerWheel(t0)
        self.pump.megabatch = self.sched = sched
        self.sessions = {}
        self.streams = []               # in the order they were made
        self.t, self.seq = t0, 0

    def add(self, stream) -> str:
        path = f"/live/s{len(self.streams)}"
        stream.session_path = path
        self.sessions[path] = types.SimpleNamespace(path=path,
                                                    streams={1: stream})
        self.streams.append(stream)
        return path

    def push(self, stream, n: int) -> None:
        for _ in range(n):
            stream.push_rtp(vid_pkt(self.seq, self.seq * 90,
                                    5 if self.seq % 25 == 0 else 1), self.t)
            self.seq += 1

    def owned(self) -> list:
        return [(s, e) for _p, s, e, r in self.pump.live if r == pump.OWNED]

    def wake(self) -> None:
        self.pump.wake(self.sessions, [], self.t)
        self.pump.arm(self.sessions)
        assert self.pump.audit() == 0
        self.sched.drain()
        self.t += 20


def _check_closed_set(w: _ServedWorld, built_after_join) -> None:
    """The rider counts kept across wakes against a count over every
    owned pair's plan, and nothing built since the players joined."""
    sched = w.sched
    if not sched.engaged:
        return
    fresh: dict[int, int] = {}
    for s, e in w.owned():
        r = sched.rides(e.plan(s, w.t - 20))
        fresh[r] = fresh.get(r, 0) + 1
    assert sched._riders == fresh
    assert sorted(sched._tracked, key=id) == sorted(
        (s for s, _ in w.owned()), key=id)
    if built_after_join is not None:
        assert sched._built == built_after_join
        assert sched.programs(sched.riders()[0]) <= sched._built


def _scheduled_run(sched, wire: _Wire, send_fd: int, seed: int):
    """One seeded schedule of ingest, a join, a leave, a ladder move
    down and back, a wake deferred at ``MAX_INFLIGHT``, an injected
    ``megabatch.dispatch`` fault, a teardown with a new stream in its
    place in the same wake (the roster's length does not change and the
    allocator may hand the new stream the old one's ``id()``), and three
    wakes below ``megabatch_min_streams`` with the re-engagement."""
    import gc

    from easydarwin_tpu.resilience.inject import INJECTOR, FaultPlan
    rng = random.Random(seed)
    w = _ServedWorld(sched, send_fd)
    for k, n_out in enumerate((5, 3, 4, 6, 2, 3, 1, 4, 12, 2)):
        w.add(_mk_stream(n_out, wire.addrs, seed * 100 + k))
    fallback0 = obs.MEGABATCH_FALLBACK.value()
    built = None
    for n in range(56):
        if n == 6:
            # the players joined before any media (wakes 0 and 1) and the
            # one fat stream's pad loaded behind its first packets, one
            # member a wake: nothing is built from here on
            built = set(sched._built)
        if n == 8:                          # a join inside its pad
            o = CollectingOutput(ssrc=0xABCD, out_seq_start=77)
            o.native_addr = wire.addrs[0]
            w.streams[0].add_output(o)
        if n == 12:
            w.streams[3].remove_output(w.streams[3].outputs[1])
        if n in (16, 22):                   # down the ladder, and back
            w.ladder.modes["/live/s2"] = 1 if n == 16 else 0
        if n == 26:
            sched.MAX_INFLIGHT = 0          # this wake's stage is deferred
        if n == 30:
            real = sched._dispatch_bucket

            def faulted(entries, p_pad, s_pad):
                INJECTOR.arm(FaultPlan(seed=seed, device_error_every=1))
                try:
                    return real(entries, p_pad, s_pad)
                finally:
                    INJECTOR.disarm()
            sched._dispatch_bucket = faulted
        if n == 34:
            del w.sessions["/live/s5"]
            w.streams[5] = None
            gc.collect()
            w.add(_mk_stream(3, wire.addrs, seed * 100 + 50))
        if n in (40, 43):
            w.cfg.megabatch_min_streams = 99 if n == 40 else 2
        if n >= 2:
            for s in w.streams:
                if s is not None and (n == 2 or rng.random() < 0.3):
                    w.push(s, 1 + rng.randrange(5))
            if n == 36:
                w.push(w.streams[1], 150)   # fell behind: further rows
        w.wake()
        wire.drain()
        if n == 26:
            del sched.MAX_INFLIGHT
        if n == 30:
            del sched._dispatch_bucket
        _check_closed_set(w, built)
    wire.drain()
    order = {s: i for i, s in enumerate(w.streams) if s is not None}
    tracked = {order[s]: (rec.head, rec.rides, rec.epoch)
               for s, rec in sched._tracked.items()}
    assert len(w.ladder.sched_errors) == 1   # the injected fault, charged
    return (tracked, set(sched._built), sched.passes,
            obs.MEGABATCH_FALLBACK.value() - fallback0, sched.mismatches)


@needs_native
@pytest.mark.parametrize("seed", [1, 36, 3600000001])
def test_the_ready_pairs_schedule_what_every_pair_did(seed):
    """The scheduler handed the pump's ready set against the scheduler
    handed every pair, over one schedule of every event that moves its
    records: the same bytes on every socket in the same order, the same
    staged heads and rider counts, the same programs, as many passes and
    as many fallback queries — for fewer plans read."""
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire_a, wire_b = _Wire(6), _Wire(6)
    try:
        walked0 = obs.MEGABATCH_PAIRS.value(kind="walked")
        every = _scheduled_run(_EveryPair(), wire_a, send.fileno(), seed)
        walked1 = obs.MEGABATCH_PAIRS.value(kind="walked")
        ready = _scheduled_run(MegabatchScheduler(), wire_b, send.fileno(),
                               seed)
        walked2 = obs.MEGABATCH_PAIRS.value(kind="walked")
        assert [len(r) for r in wire_a.rx] == [len(r) for r in wire_b.rx]
        for ra, rb in zip(wire_a.rx, wire_b.rx):
            assert ra == rb
        assert sum(len(r) for r in wire_b.rx) > 2_000
        assert ready == every
        tracked, built, passes, _fallbacks, mismatches = ready
        assert len(tracked) == 10 and passes > 30 and mismatches == 0
        assert {s for _b, _p, s in built} == {8, 16}
        assert 0 < walked2 - walked1 < 0.6 * (walked1 - walked0)
    finally:
        wire_a.close()
        wire_b.close()
        send.close()


def test_four_ready_of_256_owned_four_plans_read(monkeypatch):
    """256 owned pairs, four with a new packet: the scheduler reads the
    plan of those four in each of its two phases and of nobody else, and
    says so in ``megabatch_pairs_total``."""
    if not native.available():
        pytest.skip("the timer wheel is native")
    w = _ServedWorld(MegabatchScheduler())
    for k in range(256):
        w.add(_mk_stream(4, [None], k))
    reads = []
    real_plan = TpuFanoutEngine.plan
    monkeypatch.setattr(
        TpuFanoutEngine, "plan",
        lambda eng, stream, t: (reads.append(stream),
                                real_plan(eng, stream, t))[1])

    marks = []                              # len(reads) around each phase
    sched = w.sched
    for name in ("begin_wake", "end_wake"):
        def phase(pairs, t, ready=None, real=getattr(sched, name)):
            marks.append(len(reads))
            real(pairs, t, ready=ready)
            marks.append(len(reads))
        monkeypatch.setattr(sched, name, phase)

    def a_wake():
        """(plans read by the scheduler, by anyone) in one wake."""
        del reads[:], marks[:]
        w.wake()
        return (reads[marks[0]:marks[1]] + reads[marks[2]:marks[3]],
                list(reads))

    mine, _all = a_wake()                   # first rostered: every pair
    assert len(mine) == 2 * 256
    a_wake()
    pairs0 = {k: obs.MEGABATCH_PAIRS.value(kind=k)
              for k in ("handed", "walked")}
    four = [w.streams[k] for k in (3, 77, 200, 255)]
    for s in four:
        w.push(s, 2)
    mine, everyone = a_wake()
    assert sorted(map(id, mine)) == sorted(map(id, four + four))
    assert set(map(id, everyone)) == set(map(id, four))
    assert obs.MEGABATCH_PAIRS.value(kind="handed") - pairs0["handed"] == 256
    assert obs.MEGABATCH_PAIRS.value(kind="walked") - pairs0["walked"] == 4
    assert (w.sched.handed, w.sched.walked) == (256, 4)
    # a wake deferred at MAX_INFLIGHT carries the four it walked ...
    for s in four[:2]:
        w.push(s, 1)
    w.sched.MAX_INFLIGHT = 0
    mine, _all = a_wake()
    assert len(mine) == 2 and set(w.sched._carry) == set(four[:2])
    del w.sched.MAX_INFLIGHT
    # ... into the next, beside the one that is ready then
    w.push(four[3], 1)
    mine, _all = a_wake()
    assert sorted(map(id, mine)) == sorted(
        map(id, [four[3], four[0], four[1]] * 2))
    assert not w.sched._carry and w.sched.walked == 3
    assert all(rec.head == s.rtp_ring.head
               for s, rec in w.sched._tracked.items())


@needs_native
def test_a_withheld_hand_over_is_found_by_the_audit_and_served_meanwhile():
    """A pair the pump steps but the scheduler is not told of: its step
    takes the per-stream query (``megabatch_fallback_total``) and its
    packets reach the wire; the next audit finds the scheduler's record
    behind, counts it in ``pump_ready_missed_total`` and marks the
    stream, and the wake after that stages it."""
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire = _Wire(4)
    try:
        w = _ServedWorld(_Withholding(), send.fileno())
        for k in range(3):
            w.add(_mk_stream(3, wire.addrs[:3], k))
        for _ in range(2):
            for s in w.streams:
                w.push(s, 2)
            w.wake()
        wire.drain()
        st = w.streams[1]
        got0 = [len(r) for r in wire.rx]
        missed0 = obs.PUMP_READY_MISSED.value()
        fallback0 = obs.MEGABATCH_FALLBACK.value()
        w.sched.withheld = st
        late = CollectingOutput(ssrc=0x77)          # its key moves
        late.native_addr = wire.addrs[3]
        st.add_output(late)
        w.push(st, 3)
        w.pump.wake(w.sessions, [], w.t)
        w.pump.arm(w.sessions)
        wire.drain()
        assert st in [e[1] for e in w.pump.stepped]
        assert obs.MEGABATCH_FALLBACK.value() == fallback0 + 1
        # (the late joiner starts from the keyframe: more than the three)
        assert [len(r) for r in wire.rx[:3]] == [n + 3 for n in got0[:3]]
        assert len(wire.rx[3]) >= 3
        assert w.sched.behind(st)
        assert not any(w.sched.behind(s) for s in w.streams if s is not st)
        assert w.pump.audit() == 1
        assert obs.PUMP_READY_MISSED.value() == missed0 + 1
        w.sched.withheld = None
        w.sched.drain()
        w.t += 20
        w.wake()                            # marked by the audit: stepped
        assert not w.sched.behind(st)
        assert w.sched._tracked[st].head == st.rtp_ring.head
        assert obs.PUMP_READY_MISSED.value() == missed0 + 1
        assert w.sched.mismatches == 0
    finally:
        wire.close()
        send.close()
