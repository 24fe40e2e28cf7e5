"""The relay wake (``relay/pump.py``): one route per stream per wake, one
walk of the registry, one writer of ``megabatch_owned``, engines that go
with their stream.

Stub engines and a stub scheduler record what the wake did to them; the
streams, the registry, the ladder and (for the deadlines pass) the
server are the real ones.  Every pump here has a wheel (a stub that
records), so the ready set is engaged: a stream is stepped in the wake
that first rosters it and after that when something marked it
(``tests/test_pump_ready.py`` holds the rule itself).  Nothing here
touches JAX.
"""

import gc
import itertools
import types
import weakref

import pytest

from easydarwin_tpu.relay import pump
from easydarwin_tpu.relay.fanout import _Pass
from easydarwin_tpu.relay.output import CollectingOutput
from easydarwin_tpu.relay.pump import DEVICE, OWNED, SCALAR, Pump
from easydarwin_tpu.relay.session import SessionRegistry
from easydarwin_tpu.resilience.ladder import (LEVEL_FULL, DegradationLadder,
                                              LadderConfig)

VIDEO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=control:trackID=1\r\n")


def _cfg(**kw):
    base = dict(tpu_fanout=True, tpu_min_outputs=2, megabatch_enabled=True,
                megabatch_min_streams=2)
    base.update(kw)
    return types.SimpleNamespace(**base)


class _Engine:
    """What the wake does to an engine, recorded."""

    def __init__(self, log, fail=False):
        self.megabatch_owned = False
        self.log = log
        self.fail = fail

    open_pass = None

    def begin(self, stream, t):
        self.log.append(("step", stream.session_path, self.megabatch_owned))
        if self.fail:
            raise RuntimeError("device fell over")
        return _Pass(stream, t)         # nothing with the sender: done

    def finish(self, ps):
        return 1


class _Sched:
    """The scheduler's three entry points, recorded; ``begin_wake`` sees
    whether the engines handed to it were already marked."""

    def __init__(self, log, harvest_raises=False):
        self.log = log
        self.harvest_raises = harvest_raises
        self.engaged = False
        self.ready = []                 # what each begin_wake was named

    def begin_wake(self, pairs, t, ready=None):
        self.engaged = True
        self.ready.append(None if ready is None else
                          sorted(s.session_path for s, _ in ready))
        self.log.append(("begin", sorted(s.session_path for s, _ in pairs),
                         all(e.megabatch_owned for _, e in pairs)))
        if self.harvest_raises:
            raise RuntimeError("harvest fell over")

    def idle_wake(self):
        self.engaged = False
        self.log.append(("idle",))

    def end_wake(self, pairs, t, ready=None):
        self.log.append(("end", sorted(s.session_path for s, _ in pairs)))


class _Ladder:
    """A ladder that answers ``mode`` for every path and counts."""

    def __init__(self, mode=0, modes=None):
        self.mode, self.modes = mode, modes or {}
        self.asked, self.ok, self.errors, self.sched_errors = [], [], [], []

    def engine_mode(self, path):
        self.asked.append(path)
        return self.modes.get(path, self.mode)

    def note_device_ok(self, path):
        self.ok.append(path)

    def note_device_error(self, path):
        self.errors.append(path)

    def note_scheduler_error(self, paths):
        self.sched_errors.append(sorted(paths))


class _Wheel:
    """``native.TimerWheel``'s surface, in Python, recording."""

    def __init__(self):
        self.now, self.timers, self.ids = 0, {}, itertools.count(1)

    @property
    def pending(self):
        return len(self.timers)

    def advance(self, t):
        self.now = t
        fired = [i for i, (due, _k) in self.timers.items() if due <= t]
        return [self.timers.pop(i)[1] for i in fired]

    def schedule(self, d, key):
        i = next(self.ids)
        self.timers[i] = (self.now + d, key)
        return i

    def cancel(self, i):
        return self.timers.pop(i, None) is not None


def _push(stream, t, seq=0):
    """One keyframe packet through the ingest choke point."""
    stream.push_rtp(bytes([0x80, 96, 0, seq]) + bytes(8)
                    + bytes([(3 << 5) | 5]) + bytes(20), t)


def _registry(n_streams, n_outputs=2):
    reg = SessionRegistry()
    for k in range(n_streams):
        st = reg.find_or_create(f"/live/s{k}", VIDEO_SDP).streams[1]
        for i in range(n_outputs):
            st.add_output(CollectingOutput(ssrc=k * 100 + i))
    return reg


def _pump(cfg, log, ladder=None, fail=()):
    made = []

    def new_engine():
        made.append(_Engine(log))
        return made[-1]

    p = Pump(cfg, new_engine=new_engine, ladder=ladder,
             on_device=lambda s: (cfg.tpu_fanout and s.num_outputs
                                  >= cfg.tpu_min_outputs))
    p.wheel = _Wheel()
    p.made = made
    return p


def _steps(log):
    return [e for e in log if e[0] == "step"]


# ------------------------------------------------------------ the route table
@pytest.mark.parametrize("case, cfg, n_out, vod, ladder, want", [
    ("tier_off", dict(tpu_fanout=False), 4, False, _Ladder(0), SCALAR),
    ("below_min_outputs", dict(tpu_min_outputs=8), 7, False, _Ladder(0),
     SCALAR),
    ("at_min_outputs", dict(tpu_min_outputs=8), 8, False, _Ladder(0), OWNED),
    ("vod_ignores_min_outputs", dict(tpu_min_outputs=8), 1, True,
     _Ladder(0), OWNED),
    ("vod_tier_off", dict(tpu_fanout=False), 1, True, _Ladder(0), SCALAR),
    ("vod_never_asks_the_ladder", {}, 1, True, _Ladder(3), OWNED),
    ("no_ladder", {}, 2, False, None, OWNED),
    ("mode_1_device_unowned", {}, 2, False, _Ladder(1), DEVICE),
    ("mode_2_scalar", {}, 2, False, _Ladder(2), SCALAR),
    ("mode_3_scalar", {}, 2, False, _Ladder(3), SCALAR),
    ("megabatch_disabled", dict(megabatch_enabled=False), 2, False,
     _Ladder(0), DEVICE),
])
def test_route_table(case, cfg, n_out, vod, ladder, want):
    st = _registry(1, n_out).find("/live/s0").streams[1]
    p = _pump(_cfg(**cfg), [], ladder)
    assert p.route(st, "/live/s0", vod=vod) == want
    if vod and ladder is not None:
        assert ladder.asked == []


def test_a_backoff_window_is_scalar_and_an_oracle_failure_moves_no_rung():
    """Inside a device-retry backoff the stream is served by the CPU
    oracle without a rung change; a failure THERE is one broken output,
    not device health."""
    clk = types.SimpleNamespace(t=0.0)
    lad = DegradationLadder(LadderConfig(), clock=lambda: clk.t)
    reg = _registry(1)
    st = reg.find("/live/s0").streams[1]
    log = []
    p = _pump(_cfg(megabatch_min_streams=1), log, lad)
    lad.note_device_error("/live/s0")           # retry 1: backoff opens
    assert lad.level("/live/s0") == LEVEL_FULL
    assert p.route(st, "/live/s0") == SCALAR
    retries = lad.status()["/live/s0"]["retries"]
    st.reflect = lambda t: 1 / 0                # the oracle path fails
    p.wake(reg.sessions, [], 1000)
    assert _steps(log) == [] and p.made == []   # no engine was even built
    assert lad.status()["/live/s0"]["retries"] == retries
    assert lad.level("/live/s0") == LEVEL_FULL
    clk.t = 60.0                                # the window closed
    assert p.route(st, "/live/s0") == OWNED


# --------------------------------------------------------- the wake's protocol
def test_owned_engines_are_marked_before_the_harvest_and_staged_after():
    log = []
    reg = _registry(3)
    lad = _Ladder(modes={"/live/s2": 1})        # s2: its own engine
    p = _pump(_cfg(), log, lad)
    p.megabatch = _Sched(log)
    assert p.wake(reg.sessions, [], 1000) == 3
    assert log == [
        ("begin", ["/live/s0", "/live/s1"], True),
        ("step", "/live/s0", True), ("step", "/live/s1", True),
        ("step", "/live/s2", False),
        ("end", ["/live/s0", "/live/s1"])]
    assert lad.ok == ["/live/s0", "/live/s1", "/live/s2"]
    assert [r for _p, _s, _e, r in p.live] == [OWNED, OWNED, DEVICE]


def test_fewer_than_min_streams_owned_all_unowned_and_idle_wake_runs():
    log = []
    reg = _registry(1)
    p = _pump(_cfg(megabatch_min_streams=2), log)
    p.megabatch = _Sched(log)                   # built by an earlier wake
    p.wake(reg.sessions, [], 1000)
    assert log == [("idle",), ("step", "/live/s0", False)]


def test_the_scheduler_is_built_on_the_first_wake_with_min_streams_owned(
        monkeypatch):
    from easydarwin_tpu.relay import megabatch as mb
    built = []
    monkeypatch.setattr(
        mb, "MegabatchScheduler",
        lambda mesh=None: built.append(mesh) or _Sched([]))
    reg = _registry(1)
    p = _pump(_cfg(megabatch_min_streams=2), [])
    p.mesh = "the-mesh"
    p.wake(reg.sessions, [], 1000)
    assert p.megabatch is None and built == []
    st = reg.find_or_create("/live/s1", VIDEO_SDP).streams[1]
    st.add_output(CollectingOutput(ssrc=1))     # 1 output: still scalar
    p.wake(reg.sessions, [], 1020)
    assert p.megabatch is None
    st.add_output(CollectingOutput(ssrc=2))
    p.wake(reg.sessions, [], 1040)
    assert p.megabatch is not None and built == ["the-mesh"]
    p.wake(reg.sessions, [], 1060)
    assert built == ["the-mesh"]                # once


def test_a_harvest_that_raises_charges_the_ladder_and_steps_per_stream():
    log = []
    reg = _registry(2)
    lad = _Ladder(0)
    p = _pump(_cfg(), log, lad)
    p.megabatch = _Sched(log, harvest_raises=True)
    assert p.wake(reg.sessions, [], 1000) == 2
    assert lad.sched_errors == [["/live/s0", "/live/s1"]]
    # the same wake: every stream stepped, none owned, nothing staged,
    # and the built scheduler keeps harvesting (idle)
    assert log == [("begin", ["/live/s0", "/live/s1"], True), ("idle",),
                   ("step", "/live/s0", False), ("step", "/live/s1", False)]
    assert not any(e.megabatch_owned for e in p.made)


def test_one_streams_step_raising_does_not_stop_the_next():
    log = []
    reg = _registry(3)
    lad = _Ladder(1)
    p = _pump(_cfg(), log, lad)
    p.engine_for(reg.find("/live/s1").streams[1]).fail = True
    assert p.wake(reg.sessions, [], 1000) == 2
    assert [s[1] for s in _steps(log)] == ["/live/s0", "/live/s1",
                                           "/live/s2"]
    assert lad.errors == ["/live/s1"]           # once
    assert lad.ok == ["/live/s0", "/live/s2"]


def test_the_ladder_is_asked_once_per_device_stream_per_wake():
    log = []
    reg = _registry(5)
    thin = reg.find_or_create("/live/thin", VIDEO_SDP).streams[1]
    thin.add_output(CollectingOutput(ssrc=9))   # under min_outputs
    lad = _Ladder(0)
    p = _pump(_cfg(), log, lad)
    p.megabatch = _Sched(log)
    for k in range(3):
        lad.asked.clear()
        p.wake(reg.sessions, [], 1000 + 20 * k)
        assert sorted(lad.asked) == [f"/live/s{i}" for i in range(5)]
    assert p.streams == 6


def test_vod_pairs_ride_the_same_wake_and_never_move_the_ladder():
    log = []
    reg = _registry(1)
    vreg = _registry(2, n_outputs=1)            # two 1-output VOD streams
    vod = [s for sess in vreg.sessions.values()
           for s in sess.streams.values()]
    for k, s in enumerate(vod):
        s.session_path = f"/vod/{k}"
    lad = _Ladder(0)
    p = _pump(_cfg(tpu_min_outputs=1, megabatch_min_streams=3), log, lad)
    p.megabatch = _Sched(log)
    bad = _Engine(log, fail=True)
    pairs = [(vod[0], bad), (vod[1], None)]     # None: the pacer has none
    reflected = []
    vod[1].reflect = lambda t: reflected.append(t) or 0
    p.wake(reg.sessions, pairs, 1000)
    # 2 owned (live + one VOD) < 3: unowned, and the raising VOD engine
    # is nobody's device error
    assert _steps(log) == [("step", "/live/s0", False),
                           ("step", "/vod/0", False)]
    assert reflected == [1000]
    assert lad.asked == ["/live/s0"] and lad.errors == []
    assert lad.ok == ["/live/s0"]
    assert p.streams == 3 and len(p.live) == 1
    p.config.megabatch_min_streams = 2
    del log[:]
    p.wake(reg.sessions, pairs, 1020)
    assert log[0] == ("begin", ["/live/s0", "/vod/0"], True)
    assert log[-1] == ("end", ["/live/s0", "/vod/0"])


def test_the_server_free_wake_owns_every_pair_or_none():
    log = []
    streams = [s for sess in _registry(2).sessions.values()
               for s in sess.streams.values()]
    engines = [_Engine(log), _Engine(log)]
    pairs = list(zip(streams, engines))
    assert pump.wake(pairs, None, 1000) == 2
    assert [s[2] for s in _steps(log)] == [False, False]
    del log[:]
    pump.wake(pairs, _Sched(log), 1020)
    assert [e[0] for e in log] == ["begin", "step", "step", "end"]
    assert [s[2] for s in _steps(log)] == [True, True]
    del log[:]
    pump.wake(pairs, _Sched(log), 1040, min_streams=3)
    assert [e[0] for e in log] == ["idle", "step", "step"]
    assert [s[2] for s in _steps(log)] == [False, False]
    reflected = []
    streams[0].reflect = lambda t: reflected.append(t) or 0
    pump.wake([(streams[0], None)], None, 1060)
    assert reflected == [1060]


# ------------------------------------------------------- engines and streams
def test_a_torn_down_streams_engine_goes_with_it():
    log = []
    reg = _registry(2)
    p = _pump(_cfg(megabatch_enabled=False), log)
    p.wake(reg.sessions, [], 1000)
    p.wake(reg.sessions, [], 1020)
    assert len(p.made) == 2 == len(p.engines)     # built once a stream
    old = weakref.ref(p.engine_for(reg.find("/live/s0").streams[1]))
    p.made.clear()
    reg.remove("/live/s0")
    p.wake(reg.sessions, [], 1040)              # the roster lets go of it
    gc.collect()
    assert old() is None and len(p.engines) == 1
    st = reg.find_or_create("/live/s0", VIDEO_SDP).streams[1]
    for i in range(2):
        st.add_output(CollectingOutput(ssrc=i))
    p.wake(reg.sessions, [], 1060)
    assert len(p.made) == 1                     # a NEW engine, not a dead
    assert p.engine_for(st) is p.made[0]        # stream's by recycled id
    p.engine_drop(st)                           # the VOD pacer's way out
    assert len(p.engines) == 1


def test_the_servers_engines_are_built_once_from_what_start_settled():
    import socket
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    app = StreamingServer(ServerConfig(access_log_enabled=False))
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        app.rtsp.shared_egress = tx
        app._egress_backend_choice = "gso"
        st = app.registry.find_or_create("/live/a", VIDEO_SDP).streams[1]
        eng = app.pump.engine_for(st)
        assert eng.egress_fd == tx.fileno() and eng.egress_backend == "gso"
        assert eng.uring is app.uring_egress
        assert app.pump.engine_for(st) is eng
        assert not hasattr(ServerConfig(), "tcp_engine_enabled")
        assert not hasattr(eng, "tcp_fast_enabled")
    finally:
        tx.close()


def test_the_deadlines_pass_arms_exactly_the_streams_the_wake_stepped():
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    app = StreamingServer(ServerConfig(access_log_enabled=False,
                                       bucket_delay_ms=40))
    asked = []
    streams = []
    for k in range(3):
        st = app.registry.find_or_create(f"/live/s{k}",
                                         VIDEO_SDP).streams[1]
        st.add_output(CollectingOutput(ssrc=k))
        st.next_deadline_ms = (lambda t, allow_due=True, _p=st.session_path:
                               asked.append(_p) or 7)
        streams.append(st)
    wheel = app.pump.wheel = _Wheel()
    walked = []
    sessions = app.registry.sessions

    class _Counting(dict):
        def values(self):
            walked.append(1)
            return dict.values(self)

    app.registry.sessions = _Counting(sessions)
    app._reflect_all()              # first rostered: all three stepped
    # a session that joins after the wake waits for the next one; one a
    # step removed is skipped
    app.registry.find_or_create("/live/late", VIDEO_SDP)
    app.registry.remove("/live/s1")
    app.pump.arm(app.registry.sessions)
    app._wake_close()
    assert asked == ["/live/s0", "/live/s2"]
    assert [p for p, *_ in app.pump.live] == ["/live/s0", "/live/s1",
                                              "/live/s2"]
    assert app.pump.stepped == app.pump.live
    assert wheel.pending == 2
    assert walked == [1]            # one walk of the registry a wake
    # the next wake: only what was marked is stepped and re-armed; the
    # stream nothing touched keeps the timer it had
    del asked[:]
    _push(streams[2], app.pump.t)
    app._reflect_all()
    app.pump.arm(app.registry.sessions)
    app._wake_close()
    assert [p for p, *_ in app.pump.stepped] == ["/live/s2", "/live/late"]
    assert asked == ["/live/s2"]    # (the late one has no stub: real, -1)
    assert wheel.pending == 2 and app.pump.streams == 3


def test_a_ladder_move_steps_a_stream_nothing_else_marked():
    log = []
    reg = _registry(2)
    lad = _Ladder(0)
    p = _pump(_cfg(megabatch_min_streams=1), log, lad)
    p.megabatch = _Sched(log)
    p.wake(reg.sessions, [], 1000)
    assert [s[1] for s in _steps(log)] == ["/live/s0", "/live/s1"]
    del log[:]
    p.wake(reg.sessions, [], 1020)              # nothing marked, no move
    assert _steps(log) == [] and log[0][0] == "begin"
    lad.modes["/live/s1"] = 1                   # OWNED -> DEVICE
    p.wake(reg.sessions, [], 1040)
    assert _steps(log) == [("step", "/live/s1", False)]
    assert [e[3] for e in p.stepped] == [DEVICE]


def test_a_timer_that_ran_out_readies_its_stream_and_no_other():
    log = []
    reg = _registry(3)
    p = _pump(_cfg(megabatch_enabled=False), log)
    streams = [reg.find(f"/live/s{k}").streams[1] for k in range(3)]
    p.wake(reg.sessions, [], 1000)
    streams[1].next_deadline_ms = lambda t, allow_due=True: 30
    streams[1].touch_plan()
    p.wake(reg.sessions, [], 1010)
    p.arm(reg.sessions)                         # s1: a timer due at 1040
    del log[:]
    p.wake(reg.sessions, [], 1039)
    assert _steps(log) == [] and p.wheel.pending == 1
    p.wake(reg.sessions, [], 1040)
    assert [s[1] for s in _steps(log)] == ["/live/s1"]
    assert p.wheel.pending == 0
