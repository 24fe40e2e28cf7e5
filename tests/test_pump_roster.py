"""The pump's kept roster (``relay/pump.py``: ``Pump.wake``, ``_build``,
``_reroute``, ``_audit_roster``): a wake routes the streams it may step
and keeps the rest, and steps, routes and delivers what a pump that
routes every stream every wake would.

Each world is built twice over real streams, a real registry and a real
ladder on a virtual clock: one pump is handed the registry's map (it
keeps its roster while the map's ``generation`` has not moved), its twin
a fresh plain dict each wake (no generation: the roster is built anew,
every stream routed — what the pump did before).  Stub engines run the
scalar ``reflect`` so every route delivers the same bytes; a stub
scheduler records the pairs it is handed.  Nothing here touches JAX.
"""

import random
import types

import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.obs.events import EventLog
from easydarwin_tpu.obs.metrics import Counter, Gauge
from easydarwin_tpu.relay.fanout import _Pass
from easydarwin_tpu.relay.output import CollectingOutput
from easydarwin_tpu.relay.pump import DEVICE, OWNED, SCALAR, Pump
from easydarwin_tpu.relay.session import SessionRegistry
from easydarwin_tpu.relay.stream import StreamSettings
from easydarwin_tpu.resilience.ladder import DegradationLadder, LadderConfig

from test_pump_ready import (T0, VIDEO_SDP, _Engine, _rtp, _Sched,
                             _Stamped)

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="the timer wheel is native")

AV_SDP = VIDEO_SDP + ("m=audio 0 RTP/AVP 97\r\n"
                      "a=rtpmap:97 MPEG4-GENERIC/44100\r\n"
                      "a=control:trackID=2\r\n")


class _ReflectEngine:
    """An engine whose step is the scalar loop's: any route delivers the
    same bytes, so only which streams are stepped, and when, shows."""

    open_pass = None

    def __init__(self):
        self.megabatch_owned = False
        self.sent = 0

    def begin(self, stream, t):
        self.sent = stream.reflect(t)
        return _Pass(stream, t)         # nothing with the sender: done

    def finish(self, ps):
        return self.sent


class _Handed:
    """A scheduler that records what each wake hands it, by path."""

    engaged = False

    def __init__(self):
        self.log = []

    def begin_wake(self, pairs, t, ready=None):
        self.engaged = True
        self.log.append(([(s.session_path, s.info.track_id, e is not None)
                          for s, e in pairs],
                         [(s.session_path, s.info.track_id)
                          for s, _e in ready]))

    def idle_wake(self):
        self.engaged = False

    def end_wake(self, pairs, t, ready=None):
        pass

    def behind(self, stream):
        return False


class _Sched256(_Sched):
    def behind(self, stream):
        return False


class _World:
    def __init__(self, *, kept: bool):
        self.kept = kept
        self.clock = types.SimpleNamespace(t=T0)
        self.cfg = types.SimpleNamespace(
            tpu_fanout=True, tpu_min_outputs=2, megabatch_enabled=True,
            megabatch_min_streams=2)
        self.ladder = DegradationLadder(
            LadderConfig(recover_sec=0.3, max_retries=2, backoff_ms=30,
                         backoff_cap_ms=120),
            clock=lambda: self.clock.t / 1000, events=EventLog(),
            gauge=Gauge("roster_test_level", "t", labels=("stream",)),
            transitions=Counter("roster_test_trans", "t",
                                labels=("direction",)),
            retries=Counter("roster_test_retries", "t"))
        cfg = self.cfg
        self.pump = Pump(cfg, new_engine=_ReflectEngine, ladder=self.ladder,
                         on_device=lambda s: (cfg.tpu_fanout and s.num_outputs
                                              >= cfg.tpu_min_outputs))
        self.pump.wheel = native.TimerWheel(T0)
        self.pump.megabatch = self.sched = _Handed()
        self.routes = self.wake_routes = 0
        self.taken = set()              # the routes stepped entries took
        route = self.pump.route

        def counted(stream, path, **kw):
            self.routes += 1
            return route(stream, path, **kw)
        self.pump.route = counted
        self.reg = SessionRegistry()
        self.made = 0
        self.outputs = []               # (path, track, held output)
        self.seq: dict = {}

    def streams(self) -> list:
        return [st for sess in self.reg.sessions.values()
                for st in sess.streams.values()]

    def create(self, two_tracks: bool) -> None:
        path = f"/live/n{self.made}"
        self.made += 1
        sess = self.reg.find_or_create(path, AV_SDP if two_tracks
                                       else VIDEO_SDP)
        for st in sess.streams.values():
            st.settings = StreamSettings(bucket_size=2, bucket_delay_ms=40)
            st._wall_base = 1_000.0

    def apply(self, op: str, a: int, b: int) -> None:
        streams = self.streams()
        st = streams[a % len(streams)] if streams else None
        path = st.session_path if st is not None else None
        if op == "push" and st is not None:
            for _ in range(1 + b % 3):
                key = (path, st.info.track_id)
                s = self.seq[key] = self.seq.get(key, -1) + 1
                st.push_rtp(_rtp(s, key=s % 30 == 0), self.clock.t)
        elif op == "join" and st is not None:
            out = _Stamped(self.clock, ssrc=len(self.outputs) + 1)
            st.add_output(out, bucket=b % 3)
            self.outputs.append(out)
        elif op == "leave" and st is not None and st.num_outputs:
            outs = st.outputs
            st.remove_output(outs[b % len(outs)])
        elif op == "create":
            self.create(two_tracks=b % 3 == 0)
        elif op == "remove" and len(self.reg.sessions) > 2:
            self.reg.remove(path)
        elif op == "error" and path is not None:
            self.ladder.note_device_error(path)     # a window, then a rung
        elif op == "ok" and path is not None:
            self.ladder.note_device_ok(path)
        elif op == "tick":
            self.ladder.tick({p: 0 for p in self.reg.sessions})
        elif op == "edit":
            if b % 3 == 0:
                self.cfg.tpu_fanout = not self.cfg.tpu_fanout
            elif b % 3 == 1:
                self.cfg.tpu_min_outputs = 1 + b % 4
            else:
                self.cfg.megabatch_enabled = not self.cfg.megabatch_enabled
        elif op == "drop" and st is not None:
            self.pump.engine_drop(st)

    def wake(self) -> tuple:
        sessions = self.reg.sessions if self.kept else dict(self.reg.sessions)
        t = self.clock.t
        routes0 = self.routes
        self.pump.wake(sessions, [], t)
        self.wake_routes += self.routes - routes0
        self.pump.arm(sessions)
        p = self.pump
        for _p, s, e, r in p.live:      # every entry holds the live engine
            assert e is (p.engines.get(s) if r else None)
        seen = ([(pa, s.info.track_id, r, e is None)
                 for pa, s, e, r in p.stepped],
                [(pa, s.info.track_id, r) for pa, s, _e, r in p.live],
                self.sched.log[-1] if self.sched.log else None)
        self.taken.update(r for _p, _s, _e, r in p.stepped)
        assert p.audit() == 0
        return seen


OPS = (["push"] * 8 + ["join"] * 3 + ["leave"] * 2
       + ["create", "remove", "error", "error", "ok", "tick", "edit", "drop"])
#: past nothing, a wake, a backoff window (30–120 ms), a recovery
GAPS = [0, 1, 3, 20, 20, 41, 130, 400]


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 3300000040])
def test_the_kept_roster_steps_routes_and_delivers_what_a_rebuild_does(
        seed):
    """Seeded pushes, joins and leaves across ``tpu_min_outputs``,
    sessions made and removed, ladder retry windows, rung moves and
    recoveries, REST-style edits of the route's three keys and engine
    drops: every wake steps the same entries on the same routes with the
    live engine, hands the scheduler the same pairs, the audit finds
    nothing stale or unmarked, and every output is sent the same bytes
    at the same clock — while the kept pump routes a fraction of what
    its twin routes."""
    stale0 = obs.PUMP_ROSTER_STALE.value()
    worlds = [_World(kept=True), _World(kept=False)]
    for w in worlds:
        for k in range(12):
            w.create(two_tracks=k % 4 == 0)
        for k in range(30):
            w.apply("join", k, k)
    rng = random.Random(seed)
    moves = 0
    for _ in range(600):
        op, a, b = rng.choice(OPS), rng.randrange(64), rng.randrange(60)
        gap = rng.choice(GAPS)
        seen = []
        for w in worlds:
            w.apply(op, a, b)
            w.clock.t += gap
            seen.append(w.wake())
        assert seen[0] == seen[1], (op, a, b)
        moves += op in ("error", "tick", "edit", "drop", "create", "remove")
    a, b = worlds
    assert len(a.outputs) == len(b.outputs) > 40
    for oa, ob in zip(a.outputs, b.outputs):
        assert oa.log == ob.log
    assert sum(len(o.log) for o in a.outputs) > 1_000
    assert a.ladder.degrades == b.ladder.degrades > 0
    assert a.taken == b.taken == {SCALAR, DEVICE, OWNED}
    assert moves > 100 and a.wake_routes < 0.5 * b.wake_routes
    assert obs.PUMP_ROSTER_STALE.value() == stale0


def _wall(n: int):
    reg = SessionRegistry()
    streams = []
    for k in range(n):
        st = reg.find_or_create(f"/live/c{k}", VIDEO_SDP).streams[1]
        for i in range(4):
            st.add_output(CollectingOutput(ssrc=k * 4 + i))
        streams.append(st)
    log = []
    cfg = types.SimpleNamespace(tpu_fanout=True, tpu_min_outputs=1,
                                megabatch_enabled=True,
                                megabatch_min_streams=2)
    lad = DegradationLadder(clock=lambda: 0.0, events=EventLog())
    p = Pump(cfg, new_engine=lambda: _Engine(log), ladder=lad,
             on_device=lambda s: s.num_outputs >= cfg.tpu_min_outputs)
    p.wheel = native.TimerWheel(T0)
    p.megabatch = _Sched256()
    routed = []
    route = p.route
    p.route = lambda s, path, **kw: routed.append(path) or route(s, path,
                                                                  **kw)
    return reg, streams, p, log, routed


def test_three_of_256_pushed_three_routes():
    reg, streams, p, log, routed = _wall(256)
    p.wake(reg.sessions, [], T0)                # first rostered: all routed
    assert len(routed) == len(log) == 256
    p.arm(reg.sessions)
    del routed[:], log[:]
    counted0 = obs.PUMP_ROUTED_STREAMS.value()
    roster0 = obs.PUMP_ROSTER_STREAMS.value()
    for k in (3, 77, 200):
        streams[k].push_rtp(_rtp(0, key=True), T0 + 5)
    p.wake(reg.sessions, [], T0 + 20)
    assert routed == ["/live/c3", "/live/c77", "/live/c200"] == log
    assert obs.PUMP_ROUTED_STREAMS.value() - counted0 == 3
    assert obs.PUMP_ROSTER_STREAMS.value() - roster0 == 256
    # the scheduler is still handed the whole owned roster beside them
    assert p.megabatch.begun[-1] == 256 and len(p.megabatch.ready[-1]) == 3
    assert p.owned == [(s, e) for _p, s, e, r in p.live if r == OWNED]
    # a new session moves the generation: the next wake routes them all
    reg.find_or_create("/live/late", VIDEO_SDP)
    del routed[:]
    p.wake(reg.sessions, [], T0 + 40)
    assert len(routed) == 257 and p.streams == 257


def test_a_ladder_move_routes_that_paths_streams_only():
    reg, streams, p, log, routed = _wall(8)
    p.wake(reg.sessions, [], T0)
    p.arm(reg.sessions)
    del routed[:]
    p.ladder.note_device_error("/live/c5")      # a retry window opens
    p.wake(reg.sessions, [], T0 + 20)
    assert routed == ["/live/c5"]
    assert [(s, r) for _p, s, _e, r in p.stepped] == [(streams[5], SCALAR)]
    assert streams[5] not in [s for s, _e in p.owned]
    # inside the window it is routed every wake; the window passing
    # puts it back on its rung
    p.ladder._clock = lambda: 1.0
    del routed[:]
    p.wake(reg.sessions, [], T0 + 40)
    assert routed == ["/live/c5"] and p.stepped[0][3] == OWNED
    del routed[:]
    p.wake(reg.sessions, [], T0 + 60)
    assert routed == [] and p.ladder.retrying == set()


def test_an_invalidation_that_went_missing_costs_a_second_and_is_counted():
    """A rung written round the ladder's methods and a session put in
    the map round the registry's: the audit finds both, counts them, and
    the next wake routes them."""
    reg, streams, p, log, routed = _wall(4)
    p.wake(reg.sessions, [], T0)
    p.arm(reg.sessions)
    assert p.audit() == 0
    stale0 = obs.PUMP_ROSTER_STALE.value()
    p.ladder._h("/live/c2").level = 1           # not through _degrade
    p.wake(reg.sessions, [], T0 + 20)
    assert p.stepped == []                      # missed: nothing routed
    assert p.audit() == 1
    assert obs.PUMP_ROSTER_STALE.value() == stale0 + 1
    p.wake(reg.sessions, [], T0 + 40)
    assert [(s, r) for _p, s, _e, r in p.stepped] == [(streams[2], 1)]
    late = SessionRegistry().find_or_create("/live/late", VIDEO_SDP)
    reg.sessions["/live/late"] = late           # no generation bump
    p.wake(reg.sessions, [], T0 + 60)
    assert p.streams == 4
    assert p.audit() == 1
    assert obs.PUMP_ROSTER_STALE.value() == stale0 + 2
    p.wake(reg.sessions, [], T0 + 80)
    assert p.streams == 5 and p.stepped[-1][1] is late.streams[1]
