"""The relay wake (ARCHITECTURE.md §2b): the only place that knows the
order "harvest the scheduler's pass, route every stream, step each once,
stage the next pass" and the rule that picks a stream's path.

The server's ``_reflect_all`` calls ``Pump.wake``; a caller with no
server hands ``wake`` its ``(stream, engine)`` pairs.  Both run ``serve``
over a roster of ``(session path, stream, engine | None, route)``, the
engine ``None`` exactly when the route is ``SCALAR``.
"""

from __future__ import annotations

import time
import weakref

from .. import obs
from .fanout import TpuFanoutEngine

#: a stream's path through one wake: ``RelayStream.reflect``, its own
#: device engine, or its engine with the device work done by the
#: megabatch scheduler's stacked pass
SCALAR, DEVICE, OWNED = 0, 1, 2


def _step(entries, t: int, ladder, log, label: str, timed: bool):
    """Step every entry once; returns (packets sent, the slowest
    stream's trace id when ``timed``).  ``ladder`` hears how the DEVICE
    path fared; an oracle-path failure (one broken output) is logged
    only — it is not device health and must not move a rung."""
    sent = 0
    worst_ns, worst_trace = -1, None
    for path, stream, eng, _route in entries:
        s0 = time.perf_counter_ns() if timed else 0
        pre_stalls = stream.stats.stalls
        # per-stream guard: one bad output (broken socket, buggy
        # transcoder tap) must never halt fan-out for the rest
        try:
            if eng is not None:
                sent += eng.step(stream, t)
                if ladder is not None:
                    ladder.note_device_ok(path)
            else:
                sent += stream.reflect(t)
        except Exception as e:
            if eng is not None and ladder is not None:
                # bounded retry with backoff; a rung only past the budget
                ladder.note_device_error(path)
            if log:
                log.warning(f"{label}reflect error on {path}: {e!r}")
        try:
            for out in stream.tickable_outputs:
                sent += out.tick(t)     # reliable-UDP retransmit sweep
        except Exception as e:
            if log:                     # never a device error either
                log.warning(f"{label}tick error on {path}: {e!r}")
        # wheel hint: a due-but-held release on a NON-stalled stream may
        # be armed at once; a stalled one must not be (a time wake
        # cannot unblock a full socket)
        stream._last_pass_stalled = stream.stats.stalls > pre_stalls
        if timed:
            el = time.perf_counter_ns() - s0
            if el > worst_ns:
                worst_ns, worst_trace = el, stream.trace_id
    return sent, worst_trace


def serve(live, vod, sched, t: int, *, min_streams: int = 1, ladder=None,
          log=None) -> int:
    """One wake over a built roster: ``live`` entries inside the
    ``live_relay`` ledger unit, ``vod`` entries (they neither consult
    nor move the ladder) inside ``vod_fill``.  ``OWNED`` falls to
    ``DEVICE`` for the whole wake without a ``sched``, under
    ``min_streams`` owned entries, or when the harvest raises: a
    scheduler failure degrades to per-stream stepping, never to a halted
    pump.  The one writer of ``TpuFanoutEngine.megabatch_owned``."""
    LEDGER = obs.LEDGER             # (tests put a private one there)
    roster = live + vod if vod else live
    owned = [(s, eng) for _p, s, eng, r in roster if r == OWNED]
    if sched is None or len(owned) < min_streams:
        owned = []

    def mark(engaged: bool) -> None:
        for _p, _s, eng, r in roster:
            if eng is not None:
                eng.megabatch_owned = engaged and r == OWNED

    mark(bool(owned))               # before the harvest's prime pass
    if owned:
        _u = LEDGER.unit_start("megabatch", part="harvest")
        try:
            sched.begin_wake(owned, t)
        except Exception as e:
            if ladder is not None:
                ladder.note_scheduler_error(
                    [s.session_path for s, _ in owned])
            mark(False)
            owned = []
            if log:
                log.warning(f"megabatch harvest: {e!r}")
        LEDGER.unit_end(_u, items=max(len(owned), 1))
    if not owned and sched is not None:
        # built but not engaged (mass teardown, megabatch disabled): keep
        # harvesting, or in-flight passes pin torn-down streams
        _u = LEDGER.unit_start("megabatch", part="idle")
        try:
            sched.idle_wake()
        except Exception as e:
            if log:
                log.warning(f"megabatch idle: {e!r}")
        LEDGER.unit_end(_u)
    # the slowest stream's trace_id rides the unit's record (the
    # critical-path correlation a p99 sample decomposes by)
    _u = LEDGER.unit_start("live_relay")
    sent, worst = _step(live, t, ladder, log, "", LEDGER.enabled)
    LEDGER.unit_end(_u, items=max(len(live), 1), trace_id=worst)
    if vod:
        _u = LEDGER.unit_start("vod_fill")
        sent += _step(vod, t, None, log, "vod ", False)[0]
        LEDGER.unit_end(_u, items=len(vod))
    if owned:
        _u = LEDGER.unit_start("megabatch", part="stage")
        try:
            sched.end_wake(owned, t)
        except Exception as e:
            if ladder is not None:
                ladder.note_scheduler_error(
                    [s.session_path for s, _ in owned])
            if log:
                log.warning(f"megabatch stage: {e!r}")
        LEDGER.unit_end(_u, items=len(owned))
    return sent


def wake(pairs, sched, t: int, *, min_streams: int = 1) -> int:
    """The wake of a caller with no server: every ``(stream, engine)``
    pair owned by ``sched`` when there is one; an engine of ``None``
    takes the scalar loop."""
    return serve([(s.session_path, s, eng,
                   SCALAR if eng is None else OWNED) for s, eng in pairs],
                 [], sched, t, min_streams=min_streams)


class Pump:
    """The server's wake.  Owns the engines — weakly keyed by stream, so
    a torn-down stream's engine, HBM ring and strike counters go with it
    and a new stream never inherits them through a recycled ``id()`` —
    and the megabatch scheduler."""

    def __init__(self, config=None, *, on_device=None,
                 new_engine=TpuFanoutEngine, ladder=None, error_log=None):
        # (no config: nothing to route — a bench's engine table)
        self.config = config            # read every wake (REST edits it)
        self.on_device = on_device      # StreamingServer._on_device
        self.new_engine = new_engine    # () -> engine, once per stream
        self.ladder = ladder
        self.error_log = error_log
        self.engines = weakref.WeakKeyDictionary()
        self.megabatch = None
        #: the serving mesh ``start()`` built, None = one device
        self.mesh = None
        #: the last wake: its live entries (the deadlines pass reads
        #: them), the entries it served and the packets it sent
        self.live: list = []
        self.streams = self.sent = 0

    def engine_for(self, stream) -> TpuFanoutEngine:
        eng = self.engines.get(stream)
        if eng is None:
            eng = self.engines[stream] = self.new_engine()
        return eng

    def engine_drop(self, stream) -> None:
        self.engines.pop(stream, None)

    def route(self, stream, path, *, vod: bool = False) -> int:
        """Which path serves ``stream`` this wake, from what can be
        observed: the device path is open to it (``on_device``; a VOD
        stream is one output by construction and costs a bucket row, so
        for it the tier alone decides) and the ladder's rung, read once
        — 0 may be owned, 1 keeps its own engine, 2 and up or a retry
        backoff window is the CPU oracle, the mandatory fallback."""
        cfg = self.config
        if not (cfg.tpu_fanout if vod else self.on_device(stream)):
            return SCALAR
        mode = (0 if vod or self.ladder is None
                else self.ladder.engine_mode(path))
        if mode >= 2:
            return SCALAR
        return OWNED if mode == 0 and cfg.megabatch_enabled else DEVICE

    def wake(self, sessions, vod_pairs, t: int) -> int:
        """Serve every stream of ``sessions`` (the registry's map, walked
        once) and every ``(stream, engine | None)`` pair of the VOD
        pacer.  The scheduler is built on the first wake that has
        ``megabatch_min_streams`` owned entries."""
        live, vod = [], []
        n_owned = 0
        for sess in sessions.values():
            path = sess.path
            for stream in sess.streams.values():
                r = self.route(stream, path)
                n_owned += r == OWNED
                live.append((path, stream,
                             self.engine_for(stream) if r else None, r))
        for stream, eng in vod_pairs:
            path = stream.session_path
            r = SCALAR if eng is None else self.route(stream, path, vod=True)
            n_owned += r == OWNED
            vod.append((path, stream, eng if r else None, r))
        min_streams = self.config.megabatch_min_streams
        if self.megabatch is None and n_owned and n_owned >= min_streams:
            from .megabatch import MegabatchScheduler
            self.megabatch = MegabatchScheduler(mesh=self.mesh)
        self.live, self.streams = live, len(live) + len(vod)
        self.sent = serve(live, vod, self.megabatch, t,
                          min_streams=min_streams, ladder=self.ladder,
                          log=self.error_log)
        return self.sent
