"""The wake's ready set (``relay/pump.py``: ``needs_step``, the marks,
``Pump.arm``, ``Pump.audit``): a wake steps the streams that have
something to do and nothing is delivered later for it.

Real ``RelayStream``s on the scalar route (``stream.reflect``: nothing
here touches JAX), the native timer wheel, a virtual clock.  Each world
is built twice: one pump has the wheel and so the ready set, its twin
has none and steps every stream every wake — what the pump did before
the rule.  Every output stamps what it is sent with the clock.
"""

import random
import types

import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.relay.fanout import _Pass
from easydarwin_tpu.relay.output import CollectingOutput, WriteResult
from easydarwin_tpu.relay.pump import OWNED, Pump, needs_step
from easydarwin_tpu.protocol.rtcp import parse_compound
from easydarwin_tpu.relay.reliable import ReliableUdpOutput, build_ack
from easydarwin_tpu.relay.session import SessionRegistry
from easydarwin_tpu.relay.stream import SR_INTERVAL_MS, StreamSettings

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="the timer wheel is native")

VIDEO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=control:trackID=1\r\n")
T0 = 10_000


def _rtp(seq: int, key: bool = False) -> bytes:
    return (bytes([0x80, 96, (seq >> 8) & 0xFF, seq & 0xFF])
            + (seq * 3000).to_bytes(4, "big") + b"\x00\x00\x00\x07"
            + bytes([(3 << 5) | (5 if key else 1)]) + bytes(20))


class ReadyPump:
    """``Pump.wake`` and its deadlines pass, ready set engaged, over
    hand-built ``(stream, engine)`` pairs — for the differential tests
    (``test_megabatch.py``, ``test_tcp_delivery.py``) where they call the
    server-free ``pump.wake``.  Every pair is OWNED with a ``sched``,
    DEVICE without, as there."""

    def __init__(self, sched, t0: int):
        self.pump = Pump(types.SimpleNamespace(
            tpu_fanout=True, tpu_min_outputs=1,
            megabatch_enabled=sched is not None, megabatch_min_streams=1),
            on_device=lambda s: True)
        self.pump.megabatch = sched
        self.pump.wheel = native.TimerWheel(t0)
        self.paths: dict = {}

    def wake(self, pairs, t: int) -> int:
        sessions = {}
        for stream, eng in pairs:
            path = self.paths.setdefault(id(stream), f"/s{len(self.paths)}")
            sessions[path] = types.SimpleNamespace(path=path,
                                                   streams={1: stream})
            self.pump.engines[stream] = eng
        sent = self.pump.wake(sessions, [], t)
        self.pump.arm(sessions)
        assert self.pump.audit() == 0
        return sent


class _Stamped(CollectingOutput):
    """Records (clock, bytes) of everything it is sent."""

    def __init__(self, clock, **kw):
        super().__init__(**kw)
        self.clock = clock
        self.log = []

    def send_bytes(self, data, *, is_rtcp):
        res = super().send_bytes(data, is_rtcp=is_rtcp)
        if res is WriteResult.OK:
            # an SR's NTP field is wall clock: its arrival is what counts
            self.log.append((self.clock.t, "rtcp" if is_rtcp else data))
        return res


class _World:
    """``n`` one-track sessions served by one pump on the scalar route."""

    def __init__(self, n: int, *, ready_set: bool, bucket_size=2,
                 delay=40):
        self.clock = types.SimpleNamespace(t=T0)
        self.reg = SessionRegistry()
        self.streams = []
        for k in range(n):
            st = self.reg.find_or_create(f"/live/s{k}", VIDEO_SDP).streams[1]
            st.settings = StreamSettings(bucket_size=bucket_size,
                                         bucket_delay_ms=delay)
            st._wall_base = 1_000.0
            self.streams.append(st)
        self.pump = Pump(types.SimpleNamespace(
            tpu_fanout=False, tpu_min_outputs=1, megabatch_enabled=False,
            megabatch_min_streams=2), on_device=lambda s: False)
        if ready_set:
            self.pump.wheel = native.TimerWheel(T0)
        self.outputs = []
        self.seq = [0] * n
        self.steps = 0
        for st in self.streams:
            st.reflect = self._counted(st.reflect)

    def _counted(self, reflect):
        def counted(t):
            self.steps += 1
            return reflect(t)
        return counted

    def join(self, k: int, *, bucket=None, reliable=False) -> _Stamped:
        out = _Stamped(self.clock, ssrc=len(self.outputs) + 1)
        held = out
        if reliable:
            held = ReliableUdpOutput(out, clock=lambda: self.clock.t)
        self.streams[k].add_output(held, bucket=bucket)
        self.outputs.append((k, held, out))
        return out

    def push(self, k: int, n: int = 1) -> None:
        for _ in range(n):
            s = self.seq[k]
            self.seq[k] += 1
            self.streams[k].push_rtp(_rtp(s, key=s % 30 == 0), self.clock.t)

    def wake(self):
        """One wake and its deadlines pass; returns the stepped streams."""
        t = self.clock.t
        owed = [s for s in self.streams if needs_step(s, t)]
        self.pump.wake(self.reg.sessions, [], t)
        stepped = [s for _p, s, _e, _r in self.pump.stepped]
        if self.pump.wheel is not None:
            self.pump.arm(self.reg.sessions)
            for s in owed:
                assert s in stepped, (s.session_path, t)
            assert self.pump.audit() == 0
        return stepped

    def sleep(self, interval=20) -> None:
        """What ``_pump_loop`` waits: the wheel's next deadline, a whole
        interval at most."""
        nd = -1
        w = self.pump.wheel
        if w is not None and w.pending:
            nd = w.next_deadline(self.clock.t)
        self.clock.t += interval if nd < 0 else min(interval, max(nd, 1))


def _apply(w: _World, op: str, k: int, arg: int) -> None:
    st = w.streams[k]
    mine = [(held, out) for kk, held, out in w.outputs
            if kk == k and held._plan_cell is not None]
    if op == "push":
        w.push(k, 1 + arg % 4)
    elif op == "rtcp":
        st.push_rtcp(b"\x80\xc9\x00\x01" + bytes(4), w.clock.t)
    elif op == "join":
        w.join(k, bucket=arg % 4, reliable=arg % 5 == 0)
    elif op == "leave" and mine:
        st.remove_output(mine[arg % len(mine)][0])
    elif op == "bookmark" and mine:
        held = mine[arg % len(mine)][0]
        if held.bookmark is not None:       # a seek from outside the engine
            held.bookmark = max(st.rtp_ring.tail, held.bookmark - 1)
    elif op == "stall" and mine:
        mine[arg % len(mine)][1].block_next = 1 + arg % 3
    elif op == "ack" and mine:
        for held, _out in mine:
            if isinstance(held, ReliableUdpOutput):
                for seq in list(held.resender.pending)[:1 + arg % 3]:
                    held.on_rtcp_app(parse_compound(build_ack(1, seq))[0],
                                     w.clock.t)


OPS = ["push"] * 6 + ["rtcp", "join", "leave", "bookmark", "stall", "ack"]
#: past nothing, a wake, a bucket hold (40 ms), an RTO (≈ 1 s with its
#: back-off) and SR_INTERVAL_MS
GAPS = [0, 1, 3, 20, 20, 41, 90, 700, 1_300, SR_INTERVAL_MS + 1]


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 3300000001])
def test_no_stream_that_needs_a_step_is_skipped_and_nothing_arrives_later(
        seed):
    """Seeded sequences of push RTP / push RTCP / join / leave / outside
    bookmark write / stall / ack / clock advance: before every wake each
    stream for which ``needs_step`` is true is among those it steps, the
    audit finds nothing, and every output of the ready-set world was
    sent the same bytes at the same clock as its twin's."""
    missed0 = obs.PUMP_READY_MISSED.value()
    worlds = [_World(5, ready_set=True), _World(5, ready_set=False)]
    for w in worlds:
        for k in range(5):
            for b in range(1 + k % 3):
                w.join(k, bucket=b)
    rng = random.Random(seed)
    for _ in range(400):
        op, k, arg = rng.choice(OPS), rng.randrange(5), rng.randrange(60)
        gap = rng.choice(GAPS)
        for w in worlds:
            _apply(w, op, k, arg)
            w.clock.t += gap
            w.wake()
    a, b = worlds
    assert len(a.outputs) == len(b.outputs) > 10
    for (_k, _h, oa), (_k2, _h2, ob) in zip(a.outputs, b.outputs):
        assert oa.log == ob.log
    assert sum(len(o.log) for _k, _h, o in a.outputs) > 1_000
    assert a.steps < 0.8 * b.steps              # and it skipped some
    assert obs.PUMP_READY_MISSED.value() == missed0


def test_a_silent_sources_latched_output_gets_its_sr_from_the_wheel():
    w = _World(1, ready_set=True)
    out = w.join(0, bucket=0)
    w.push(0, 3)
    w.wake()                                    # latches, first SR
    assert [x for _t, x in out.log].count("rtcp") == 1
    steps0 = w.steps
    last_sr = T0
    while w.clock.t < T0 + 3 * SR_INTERVAL_MS + 100:
        w.sleep()
        w.wake()
        for t, x in out.log:
            if x == "rtcp" and t > last_sr:
                # within SR_INTERVAL_MS and one wake (20 ms)
                assert t - last_sr <= SR_INTERVAL_MS + 20
                last_sr = t
    assert last_sr >= T0 + 3 * SR_INTERVAL_MS
    # ≈ 750 wakes, one step an SR (and one for each timer that ran out)
    assert w.steps - steps0 <= 8


def test_sixteen_held_cohorts_are_released_on_time_by_the_wheel_alone():
    w = _World(1, ready_set=True, bucket_size=1, delay=73)
    outs = [w.join(0, bucket=b) for b in range(16)]
    w.push(0, 1)
    while w.clock.t < T0 + 16 * 73:             # every output latches at
        w.wake()                                # its first release: until
        w.sleep()                               # then it is re-checked
    t1, steps1 = w.clock.t, w.steps             # every pass, as before
    w.push(0, 1)                                # and never again
    wakes = 0
    while w.clock.t < t1 + 16 * 73:
        w.wake()
        w.sleep()
        wakes += 1
    for b, out in enumerate(outs):
        got = [t for t, x in out.log if x != "rtcp"]
        assert len(got) == 2
        assert 0 <= got[1] - (t1 + b * 73) <= 2
    # one step a release (and one more where a due one is armed at 1 ms)
    assert wakes > 50 and w.steps - steps1 <= 2 * 16 + 2


def test_a_mark_that_was_missed_costs_a_second_and_is_counted():
    """A write that goes round the marks (here: a packet pushed into the
    ring itself) is found by the audit, stepped in the next wake and
    counted."""
    w = _World(2, ready_set=True)
    out = w.join(0, bucket=0)
    w.join(1, bucket=0)
    w.push(0)
    w.push(1)
    w.wake()
    missed0 = obs.PUMP_READY_MISSED.value()
    st = w.streams[0]
    st.rtp_ring.push(_rtp(99), w.clock.t)       # not through push_rtp
    w.clock.t += 20
    w.pump.wake(w.reg.sessions, [], w.clock.t)
    assert w.pump.stepped == []
    assert w.pump.audit() == 1
    assert obs.PUMP_READY_MISSED.value() == missed0 + 1
    w.clock.t += 20
    assert w.wake() == [st]
    assert len([x for _t, x in out.log if x != "rtcp"]) == 2


class _Engine:
    def __init__(self, log):
        self.megabatch_owned = False
        self.log = log

    open_pass = None

    def begin(self, stream, t):
        self.log.append(stream.session_path)
        return _Pass(stream, t)         # nothing with the sender: done

    def finish(self, ps):
        return 1


class _Sched:
    engaged = False

    def __init__(self):
        self.begun, self.ended, self.ready = [], [], []

    def begin_wake(self, pairs, t, ready=None):
        self.engaged = True
        self.begun.append(len(pairs))
        self.ready.append([s for s, _ in ready])

    def idle_wake(self):
        self.engaged = False

    def end_wake(self, pairs, t, ready=None):
        self.ended.append(len(pairs))
        assert [s for s, _ in ready] == self.ready[-1]


def test_three_of_256_pushed_three_steps_and_the_scheduler_sees_them_all():
    reg = SessionRegistry()
    streams = []
    for k in range(256):
        st = reg.find_or_create(f"/live/c{k}", VIDEO_SDP).streams[1]
        for i in range(4):
            st.add_output(CollectingOutput(ssrc=k * 4 + i))
        streams.append(st)
    log = []
    cfg = types.SimpleNamespace(tpu_fanout=True, tpu_min_outputs=1,
                                megabatch_enabled=True,
                                megabatch_min_streams=2)
    p = Pump(cfg, new_engine=lambda: _Engine(log),
             on_device=lambda s: s.num_outputs >= cfg.tpu_min_outputs)
    p.wheel = native.TimerWheel(T0)
    p.megabatch = _Sched()
    roster0 = obs.PUMP_ROSTER_STREAMS.value()
    stepped0 = obs.PUMP_STEPPED_STREAMS.value()
    p.wake(reg.sessions, [], T0)                # first rostered: all step
    assert len(log) == 256
    p.arm(reg.sessions)
    del log[:]
    for k in (3, 77, 200):
        streams[k].push_rtp(_rtp(0, key=True), T0 + 5)
    p.wake(reg.sessions, [], T0 + 20)
    assert log == ["/live/c3", "/live/c77", "/live/c200"]
    assert p.megabatch.begun == [256, 256] == p.megabatch.ended
    # ... and is named the three beside them (all 256 when first rostered)
    assert [len(r) for r in p.megabatch.ready] == [256, 3]
    assert p.megabatch.ready[1] == [streams[k] for k in (3, 77, 200)]
    assert all(r == OWNED for _p, _s, _e, r in p.live) and p.streams == 256
    assert [s for _p, s, _e, _r in p.stepped] == [streams[k]
                                                  for k in (3, 77, 200)]
    assert obs.PUMP_ROSTER_STREAMS.value() - roster0 == 512
    assert obs.PUMP_STEPPED_STREAMS.value() - stepped0 == 259
    # a stub engine latches no output, and a stream with one un-latched
    # is retried every wake (rule 4): the three, not the 253
    p.arm(reg.sessions)
    del log[:]
    p.wake(reg.sessions, [], T0 + 40)
    assert log == ["/live/c3", "/live/c77", "/live/c200"]
    assert p.megabatch.begun[-1] == 256
