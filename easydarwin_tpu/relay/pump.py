"""The relay wake (ARCHITECTURE.md §2b): the only place that knows the
order "route, harvest the scheduler's pass, step each stream once, stage
the next pass" and the rule that picks a stream's path.

The server's ``_reflect_all`` calls ``Pump.wake``; a caller with no
server hands ``wake`` its ``(stream, engine)`` pairs.  Both run ``serve``
over a roster of ``(session path, stream, engine | None, route)``, the
engine ``None`` exactly when the route is ``SCALAR``.

**Which streams a wake steps.**  ``_step`` is handed the live streams
that have something to do — ``needs_step`` is the definition, for every
route alike.  ``Pump`` does not evaluate it over the roster: it keeps a
ready set, marked where the state ``needs_step`` reads is written
(``PlanCell.mark`` / ``touch`` from ingest and every plan move, the
wheel's fired timers, ``_step``'s own carry-over), and audits the marks
against the rule once a second (``Pump.audit``).  The VOD roster and a
caller with no wheel step all they hand in.

**Which streams a wake routes.**  ``Pump`` keeps the roster across
wakes and routes again only the entries a wake may step, where nothing
it was built from has moved: the registry's ``generation``, the route's
configuration, the paths the ladder names (``Pump.wake``); ``audit``
holds the kept roster to a walk of its own once a second.

**Which pairs the scheduler looks at.**  ``serve`` hands it the owned
roster and, beside it, the owned pairs among the entries the wake steps
(``ready``; None — every pair — where the caller keeps no ready set).
The scheduler reads the plan of those and of what it carries over
itself, keeps its count of the closed shape set across wakes, and holds
its records to the roster when the roster changed
(``MegabatchScheduler._walk``); ``Pump.audit`` holds it to that rule
too.  ``megabatch_owned`` is written on the engines a wake steps, and on
all of them when the engagement flips: a step is its only reader.
"""

from __future__ import annotations

import collections
import itertools
import time
import weakref

from .. import native, obs
from .fanout import TpuFanoutEngine

#: a stream's path through one wake: ``RelayStream.reflect``, its own
#: device engine, or its engine with the device work done by the
#: megabatch scheduler's stacked pass
SCALAR, DEVICE, OWNED = 0, 1, 2


def needs_step(stream, t: int, route: int | None = None) -> bool:
    """Whether ``stream`` has something to do in a wake at ``t``, from
    its own state and the record its last step left in its cell:

    1. ingest — either ring's head moved (``push_rtp``, the native
       drain, the chaos injector's held packet, ``push_rtcp``);
    2. its plan epoch moved (a join, a leave, a bookmark or rewrite
       field written from outside the engine, a thinning level) or
       ``route`` is not the route it last took (a ladder move);

    and, for a stream with outputs and a ring that is not empty (with
    neither a step leaves by its first exit):

    3. the timer armed for it has run out — a held cohort's release, a
       reliable-UDP RTO, the next SR (``next_deadline_ms``);
    4. its last step stalled, raised or left an output un-latched: it
       is retried every wake;
    5. its reliability tier owes a pass (``StreamFec.owes``): parity
       programs still to load, a window backlog past the tick's budget,
       a cursor a receiver report left to park or place (the report
       marked the stream).  An FEC output alone is no reason: the
       tick's windows come from ingest and from the step that sends
       them."""
    c = stream._plan_cell
    ring = stream.rtp_ring
    if (ring.head != c.rtp_head or stream.rtcp_ring.head != c.rtcp_head
            or c.epoch != c.stepped_epoch
            or (route is not None and route != c.route)):
        return True
    if not len(ring) or not stream.num_outputs:
        return False
    return (c.retry or c.due <= t
            or (stream.fec is not None and stream.fec.owes() is not None))


def _step(entries, t: int, ladder, log, label: str, timed: bool):
    """Step every entry once; returns (packets sent, the slowest
    stream's trace id when ``timed``, and of the send jobs the native
    sender was handed: their number, their send ns and how many of those
    the loop thread did not spend waiting).

    **Begin every entry in roster order, finish them in the same
    order.**  An engine's ``begin`` plans the stream and submits its UDP
    send to the one native sender thread; ``finish`` settles it from the
    job's result and runs the rest of the stream's pass.  After each
    begin the loop finishes whatever earlier entries' jobs are already
    done, and goes on; at the end it waits for the rest.  So the sends
    of one wake go out back to back while this thread plans and settles,
    never two at once, each stream's own order of events as it was.
    **The call returns with no job in flight**: the ring slots, param
    rows and dest tables a job points into are written only between
    wakes.

    ``ladder`` hears how the DEVICE path fared; an oracle-path failure
    (one broken output) is logged only — it is not device health and
    must not move a rung.  Leaves in each stream's cell what
    ``needs_step`` compares the next wake's state with, and in its
    pump's ready set the mark of a stream that is to be stepped again
    whatever happens."""
    sent = jobs = send_ns = hidden_ns = 0
    worst_ns, worst_trace = -1, None
    #: begun, not finished: [entry, pass | None, stalls before, raised,
    #: ns spent on it so far]
    pending: collections.deque = collections.deque()

    def finish(rec) -> None:
        nonlocal sent, jobs, send_ns, hidden_ns, worst_ns, worst_trace
        (path, stream, eng, route), ps, pre_stalls, raised, el = rec
        s0 = time.perf_counter_ns() if timed else 0
        # per-stream guard: one bad output (broken socket, buggy
        # transcoder tap) must never halt fan-out for the rest
        if eng is not None and not raised:
            try:
                if ps is not None:
                    sent += eng.finish(ps)
                if ladder is not None:
                    ladder.note_device_ok(path)
            except Exception as e:
                raised = True
                if ladder is not None:
                    # bounded retry with backoff; a rung only past the budget
                    ladder.note_device_error(path)
                if log:
                    log.warning(f"{label}reflect error on {path}: {e!r}")
        if ps is not None:
            jobs += ps.jobs
            send_ns += ps.send_ns
            hidden_ns += ps.hidden_ns
        try:
            for out in stream.tickable_outputs:
                sent += out.tick(t)     # reliable-UDP retransmit sweep
        except Exception as e:
            if log:                     # never a device error either
                log.warning(f"{label}tick error on {path}: {e!r}")
        # wheel hint: a due-but-held release on a NON-stalled stream may
        # be armed at once; a stalled one must not be (a time wake
        # cannot unblock a full socket)
        stalled = stream._last_pass_stalled = (stream.stats.stalls
                                               > pre_stalls)
        # the record for needs_step.  What the step itself wrote (a
        # scalar pass's bookmarks, a latch) marked the stream: that mark
        # goes, and the carry-over (rules 4 and 5) is put in its place.
        # An SR due at t is an un-latched output's "re-check every pass"
        c = stream._plan_cell
        c.rtp_head = stream.rtp_ring.head
        c.rtcp_head = stream.rtcp_ring.head
        c.stepped_epoch = c.epoch
        c.route = route
        live = len(stream.rtp_ring) > 0 and stream.num_outputs > 0
        c.retry = live and (stalled or raised
                            or stream._next_sr_due_ms <= t)
        if c.ready is not None:
            owed = (live and not c.retry and stream.fec is not None
                    and stream.fec.owes_pass)
            if c.retry or owed:
                c.ready.add(c.key)
                if owed:
                    obs.FEC_TIER_MARKS.inc(reason=owed)
            else:
                c.ready.discard(c.key)
        if timed:
            el += time.perf_counter_ns() - s0
            if el > worst_ns:
                worst_ns, worst_trace = el, stream.trace_id

    try:
        for entry in entries:
            path, stream, eng, _route = entry
            s0 = time.perf_counter_ns() if timed else 0
            pre_stalls = stream.stats.stalls
            ps, raised = None, False
            try:
                if eng is None:
                    sent += stream.reflect(t)
                else:
                    if eng.open_pass is not None:
                        # an engine shared by two entries holds one
                        # pass's scratch: settle up to the first
                        while pending:
                            finish(pending.popleft())
                    ps = eng.begin(stream, t)
            except Exception as e:
                raised = True
                if eng is not None and ladder is not None:
                    ladder.note_device_error(path)
                if log:
                    log.warning(f"{label}reflect error on {path}: {e!r}")
            pending.append((entry, ps, pre_stalls, raised,
                            time.perf_counter_ns() - s0 if timed else 0))
            while pending and (pending[0][1] is None or pending[0][1].done):
                finish(pending.popleft())
        while pending:
            finish(pending.popleft())   # blocks on the sender
    finally:
        # the barrier, whatever the way out: a BaseException may have
        # left a popped entry's job with the sender (one uncontended
        # mutex when there is none)
        native.sender_drain()
    if jobs:
        obs.EGRESS_PIPELINE_JOBS.inc(jobs)
        obs.EGRESS_PIPELINE_SECONDS.inc(send_ns / 1e9, part="send")
        obs.EGRESS_PIPELINE_SECONDS.inc(hidden_ns / 1e9, part="hidden")
    return sent, worst_trace, (jobs, send_ns, hidden_ns)


def serve(live, vod, sched, t: int, *, min_streams: int = 1, ladder=None,
          log=None, stepped=None, owned=None) -> tuple[int, tuple]:
    """One wake over a built roster: ``live`` entries inside the
    ``live_relay`` ledger unit — ``stepped`` of them where the caller
    keeps a ready set, the scheduler handed the owned pairs among them
    beside every owned pair — and ``vod`` entries (they neither consult
    nor move the ladder) inside ``vod_fill``.  ``OWNED`` falls to
    ``DEVICE`` for the whole wake without a ``sched``, under
    ``min_streams`` owned entries, or when the harvest raises: a
    scheduler failure degrades to per-stream stepping, never to a halted
    pump.  ``owned`` is the roster's owned pairs where the caller keeps
    them (``Pump``), in roster order.  The one writer of
    ``TpuFanoutEngine.megabatch_owned``.  Returns (packets sent,
    ``_step``'s tally of the send jobs)."""
    LEDGER = obs.LEDGER             # (tests put a private one there)
    roster = live + vod if vod else live
    if owned is None:
        owned = [(s, eng) for _p, s, eng, r in roster if r == OWNED]
    if sched is None or len(owned) < min_streams:
        owned = []
    if stepped is None:             # no ready set: every entry, every pair
        stepped, served, ready = live, roster, None
    else:
        served = stepped + vod if vod else stepped
        ready = [(s, eng) for _p, s, eng, r in served if r == OWNED]

    def mark(entries, engaged: bool) -> None:
        for _p, _s, eng, r in entries:
            if eng is not None:
                eng.megabatch_owned = engaged and r == OWNED

    # before the harvest's prime pass.  An engine reads the flag inside
    # its step only: the engines this wake steps, and every engine when
    # the wake is the scheduler's and the last was not, or the reverse
    flips = bool(owned) != (sched is not None and sched.engaged)
    mark(roster if flips else served, bool(owned))
    if owned:
        _u = LEDGER.unit_start("megabatch", part="harvest")
        try:
            sched.begin_wake(owned, t, ready=ready)
        except Exception as e:
            if ladder is not None:
                ladder.note_scheduler_error(
                    [s.session_path for s, _ in owned])
            mark(roster, False)
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
    sent, worst, jobs = _step(stepped, t, ladder, log, "", LEDGER.enabled)
    LEDGER.unit_end(_u, items=max(len(stepped), 1), trace_id=worst)
    if vod:
        _u = LEDGER.unit_start("vod_fill")
        v_sent, _worst, v_jobs = _step(vod, t, None, log, "vod ", False)
        sent += v_sent
        jobs = tuple(a + b for a, b in zip(jobs, v_jobs))
        LEDGER.unit_end(_u, items=len(vod))
    if owned:
        _u = LEDGER.unit_start("megabatch", part="stage")
        try:
            sched.end_wake(owned, t, ready=ready)
        except Exception as e:
            if ladder is not None:
                ladder.note_scheduler_error(
                    [s.session_path for s, _ in owned])
            if log:
                log.warning(f"megabatch stage: {e!r}")
        LEDGER.unit_end(_u, items=len(owned))
    return sent, jobs


def wake(pairs, sched, t: int, *, min_streams: int = 1) -> int:
    """The wake of a caller with no server: every ``(stream, engine)``
    pair owned by ``sched`` when there is one; an engine of ``None``
    takes the scalar loop."""
    return serve([(s.session_path, s, eng,
                   SCALAR if eng is None else OWNED) for s, eng in pairs],
                 [], sched, t, min_streams=min_streams)[0]


class Pump:
    """The server's wake.  Owns the engines — weakly keyed by stream, so
    a torn-down stream's engine, HBM ring and strike counters go with it
    and a new stream never inherits them through a recycled ``id()`` —
    and the megabatch scheduler — and the ready set, with the wheel whose
    timers feed it (``_pump_loop`` builds the wheel and sleeps by it) —
    and the roster, kept across wakes and corrected where it changes."""

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
        #: the 1 ms native timer wheel, None = no timer source: every
        #: live stream is stepped every wake
        self.wheel = None
        #: keys (``PlanCell.key``) of the streams marked since the last
        #: wake took its streams; the wheel's timers carry the same keys
        self.ready: set[int] = set()
        self._keys = itertools.count(1)
        #: the last wake: its clock, its live roster, the entries of it
        #: that were stepped (the deadlines pass re-arms those), how many
        #: entries it served in all, the packets it sent and the send
        #: jobs it handed the native sender: (their number, their send
        #: ns, the ns of those the loop thread did not wait for)
        self.t = 0
        self.live: list = []
        self.stepped: list = []
        self.streams = self.sent = 0
        self.jobs = (0, 0, 0)
        #: the roster kept across wakes: ``live`` and its owned pairs in
        #: roster order, each entry's place by its cell's key and the
        #: keys of each path (what a ladder move names); what it was
        #: built from — the sessions map, its generation, the route's
        #: configuration — and whether it may be kept at all; the keys
        #: whose engine was dropped since the last wake
        self.owned: list = []
        self._pos: dict[int, int] = {}
        self._by_path: dict[str, list[int]] = {}
        self._sessions = self._gen = self._cfg = None
        self._kept = False
        self._dropped: set[int] = set()

    def engine_for(self, stream) -> TpuFanoutEngine:
        eng = self.engines.get(stream)
        if eng is None:
            eng = self.engines[stream] = self.new_engine()
        return eng

    def engine_drop(self, stream) -> None:
        self.engines.pop(stream, None)
        c = stream._plan_cell
        if c.ready is self.ready:
            self._dropped.add(c.key)    # its roster entry holds the engine

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

    def _route_inputs(self) -> tuple:
        """The configuration ``route`` reads, beside the stream and the
        ladder: a REST edit of one of them re-routes the whole roster."""
        cfg = self.config
        return (cfg.tpu_fanout, cfg.tpu_min_outputs, cfg.megabatch_enabled)

    def wake(self, sessions, vod_pairs, t: int) -> int:
        """Serve every stream of ``sessions`` (the registry's map) and
        every ``(stream, engine | None)`` pair of the VOD pacer.  The
        scheduler is built on the first wake that has
        ``megabatch_min_streams`` owned entries.

        **The roster is kept across wakes.**  Where nothing it was built
        from has moved — the same map at the same ``generation`` (the
        registry bumps it on every session it adds or removes), the same
        route configuration, a ladder that names the paths it moved
        (``take_moved``) — the wake routes again only the entries it may
        step: the ready set's, the moved paths', those whose engine was
        dropped (``_reroute``).  A join or leave that moves ``on_device``
        moved the plan epoch, which marked the stream.  Otherwise, or with
        no wheel, it walks the map and routes every stream (``_build``).
        ``audit`` holds the kept roster to a walk of its own."""
        ready, wheel = self.ready, self.wheel
        if wheel is not None:
            # before the wake picks its streams, against its own clock
            ready.update(wheel.advance(t))
        ladder = self.ladder
        if ladder is None:
            moved = ()
        else:
            take = getattr(ladder, "take_moved", None)
            moved = None if take is None else take()
        gen, cfg = getattr(sessions, "generation", None), self._route_inputs()
        if (self._kept and sessions is self._sessions and gen == self._gen
                and cfg == self._cfg and moved is not None):
            stepped, routed = self._reroute(ready, moved)
        else:
            stepped = self._build(sessions, ready, wheel, keep=(
                wheel is not None and gen is not None and moved is not None))
            routed = len(self.live)
            self._sessions, self._gen, self._cfg = sessions, gen, cfg
        self._dropped.clear()
        # marks made from here on are the next wake's (and a torn-down
        # stream's key goes with the rest)
        ready.clear()
        live, vod = self.live, []
        for stream, eng in vod_pairs:
            path = stream.session_path
            r = SCALAR if eng is None else self.route(stream, path, vod=True)
            vod.append((path, stream, eng if r else None, r))
        owned = self.owned
        if vod:
            owned = owned + [(s, eng) for _p, s, eng, r in vod if r == OWNED]
        min_streams = self.config.megabatch_min_streams
        if self.megabatch is None and owned and len(owned) >= min_streams:
            from .megabatch import MegabatchScheduler
            self.megabatch = MegabatchScheduler(mesh=self.mesh)
        self.t, self.stepped = t, stepped
        self.streams = len(live) + len(vod)
        obs.PUMP_ROSTER_STREAMS.inc(len(live))
        obs.PUMP_ROUTED_STREAMS.inc(routed)
        obs.PUMP_STEPPED_STREAMS.inc(len(stepped))
        self.sent, self.jobs = serve(
            live, vod, self.megabatch, t, min_streams=min_streams,
            ladder=self.ladder, log=self.error_log, stepped=stepped,
            owned=owned)
        return self.sent

    def _build(self, sessions, ready, wheel, *, keep: bool) -> list:
        """Walk ``sessions`` once and route every stream: the roster anew,
        its owned pairs and, to ``keep`` it, each entry's place and each
        path's keys.  Returns the entries to step: all of them
        with no wheel, else those marked ready (a stream first rostered
        here is) or whose route is not the one they last took."""
        live, stepped, pos, by_path = [], [], {}, {}
        for sess in sessions.values():
            path = sess.path
            for stream in sess.streams.values():
                r = self.route(stream, path)
                entry = (path, stream,
                         self.engine_for(stream) if r else None, r)
                c = stream._plan_cell
                if c.ready is not ready:        # first rostered here
                    c.install(ready, next(self._keys))
                if keep:
                    pos[c.key] = len(live)
                    by_path.setdefault(path, []).append(c.key)
                live.append(entry)
                if wheel is None or c.key in ready or r != c.route:
                    stepped.append(entry)
        self.live, self._pos, self._by_path = live, pos, by_path
        self.owned = [(s, eng) for _p, s, eng, r in live if r == OWNED]
        self._kept = keep
        return stepped

    def _reroute(self, ready, moved) -> tuple[list, int]:
        """The kept roster's wake: route again the entries of the ready
        set, of the ``moved`` paths and of the dropped engines, in roster
        order; replace an entry, and the owned pairs, where its route or
        engine changed; step those marked ready or whose route is not the
        one they last took — what ``_build`` would step, since every
        entry it did not route is as the last wake left it.  Returns (the
        entries to step, the routes made)."""
        pos, live, keys = self._pos, self.live, ready
        if moved or self._dropped:
            keys = ready | self._dropped
            for path in moved:
                keys.update(self._by_path.get(path, ()))
        stepped, owned_moved = [], False
        at = sorted(pos[k] for k in keys if k in pos)
        for i in at:
            entry = live[i]
            path, stream, eng, r0 = entry
            r = self.route(stream, path)
            e = self.engine_for(stream) if r else None
            if r != r0 or e is not eng:
                entry = live[i] = (path, stream, e, r)
                owned_moved = owned_moved or OWNED in (r, r0)
            c = stream._plan_cell
            if c.key in ready or r != c.route:
                stepped.append(entry)
        if owned_moved:
            self.owned = [(s, eng) for _p, s, eng, r in live if r == OWNED]
        return stepped, len(at)

    def arm(self, sessions) -> None:
        """The deadlines pass: one wheel timer a stepped stream, at the
        earliest of what ``next_deadline_ms`` reports, relative to the
        wake's clock — the time the wheel was advanced to (no ``await``
        lies between).  A stream that was not stepped has the bookmarks,
        the head and so the timer it had; one whose session a step
        removed is skipped."""
        wheel, t = self.wheel, self.t
        for path, stream, _eng, _route in self.stepped:
            if path not in sessions:
                continue
            c = stream._plan_cell
            if c.due <= t:                      # it fired into this wake
                c.due, c.timer = c.NEVER, 0
            d = stream.next_deadline_ms(
                t, allow_due=not stream._last_pass_stalled)
            if d < 0 or c.due <= t + d:
                continue                # an earlier-or-equal timer pends
            if c.timer:
                wheel.cancel(c.timer)
            c.timer, c.due = wheel.schedule(d, c.key), t + d

    def audit(self) -> int:
        """The guard, once a second: hold the kept roster against a walk
        of its own (``_audit_roster``), then the marks against the rule —
        a stream the last wake skipped for which ``needs_step`` is true
        at that wake's clock and which nothing has marked since is
        stepped next wake and counted in ``pump_ready_missed_total``: a
        missed mark costs the second between two audits, not a stream.
        The scheduler reads the pairs the ready set names, so it is held
        to the same marks: an owned stream its records lag
        (``MegabatchScheduler.behind``) is counted and stepped alike.
        Returns the entries found stale and the streams found unmarked."""
        if self.wheel is None:
            return 0
        stale = self._audit_roster()
        ready, t, sched = self.ready, self.t, self.megabatch
        stepped = {id(e[1]) for e in self.stepped}
        missed = 0
        for _path, stream, _eng, route in self.live:
            c = stream._plan_cell
            if c.key in ready:
                continue
            if ((id(stream) not in stepped and needs_step(stream, t, route))
                    or (route == OWNED and sched is not None
                        and sched.behind(stream))):
                ready.add(c.key)
                missed += 1
        if missed:
            obs.PUMP_READY_MISSED.inc(missed)
        return stale + missed

    def _audit_roster(self) -> int:
        """Route every kept entry from scratch.  An entry whose route or
        engine differs is marked ready — the next wake routes it again
        and replaces it — and a map whose streams are not the roster's
        has the next wake build it anew; both are counted in
        ``pump_roster_stale_total``.  A missed invalidation costs a
        second, never a stream, and shows.  What the next wake routes
        anyway is left out: a roster it builds anew, and the entries
        ready, with a dropped engine or on a path the ladder moved or
        holds in a retry window since."""
        sessions, live = self._sessions, self.live
        if (not self._kept
                or getattr(sessions, "generation", None) != self._gen
                or self._route_inputs() != self._cfg):
            return 0
        walked = [st for sess in sessions.values()
                  for st in sess.streams.values()]
        stale = abs(len(walked) - len(live)) + sum(
            st is not e[1] for st, e in zip(walked, live))
        if stale:
            self._kept = False
        else:
            ladder, ready, dropped = self.ladder, self.ready, self._dropped
            unsettled = (ladder.moved | ladder.retrying
                         if ladder is not None else ())
            for path, stream, eng, r0 in live:
                key = stream._plan_cell.key
                if key in ready or key in dropped or path in unsettled:
                    continue
                r = self.route(stream, path)
                if r != r0 or (self.engines.get(stream) if r else None) \
                        is not eng:
                    ready.add(key)
                    stale += 1
        if stale:
            obs.PUMP_ROSTER_STALE.inc(stale)
        return stale
