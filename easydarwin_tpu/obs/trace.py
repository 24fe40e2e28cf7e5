"""Low-overhead span tracer → Chrome trace-event JSON.

Records complete spans (``"ph": "X"``) into a bounded ring buffer;
``dump()`` renders the ring as a ``{"traceEvents": [...]}`` document
that chrome://tracing and Perfetto load directly.  The admin API serves
it at ``/api/v1/admin?command=trace``.

Recording one span costs two ``perf_counter_ns`` reads plus one locked
deque append of a tuple — cheap enough to leave permanently on around
the engine pass and the native egress call.  JSON rendering happens only
at dump time.

One bracket, two sinks (ISSUE 25): ``tok = TRACER.open(name, cat,
**args)`` / ``TRACER.close(tok, **args)`` stamps the ring as above AND,
whenever a ``jax.profiler`` session is live (the benchmark's SIGUSR1/2
bracket in ``benchmark/server_child.py``, or an operator's), runs a
``jax.profiler.TraceAnnotation`` of the same name over the same
interval, so the span lands on the ``/host:CPU`` plane of the
``.xplane.pb`` on the profiler's clock, beside the device ops.  With no
session live the second sink costs one flag test and allocates nothing;
with ``EDTPU_PROFILE=0`` ``open`` returns ``None`` after one attribute
check.  The served path uses only open/close, with names from the closed
``SPANS`` vocabulary below (``tools/metrics_lint.py`` pins it); the one
post-hoc form, ``add``, stays for call sites off the served path and
reaches the ring only.  Both stamp the wake in progress.

Correlation: callers thread a session's ``trace_id`` through span args
(``TRACER.open(..., trace_id=sid)``); the per-session flight recorder
(``obs.flight``) and Perfetto queries select one session's spans across
the RTSP handler → engine pass → native egress hops by that key.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque

#: default ring capacity (spans); a wake of 16 stream-steps records
#: ~200 spans (ISSUE 25), so 16384 holds the last ~80 wakes
DEFAULT_CAPACITY = 16384

#: the CLOSED span vocabulary of the served path (``open``/``close``
#: names; ``tools/metrics_lint.py: lint_spans`` holds every call site in
#: the package to it).  Nesting in time on the pump thread gives the
#: parent; every span inside a wake carries ``wake=<sequence number>``
#: and every span of one stream its ``trace_id``.  None per output, none
#: per packet.  ``pump.<work_class>`` spans are opened by the wake
#: ledger (one per ``obs.ledger.WORK_CLASSES`` entry).
SPANS = (
    "pump.sleep", "pump.wake", "pump.deadlines", "pump.maintenance",
    "pump.live_relay", "pump.megabatch", "pump.vod_fill", "pump.dvr_spill",
    "pump.hls_requant", "pump.fec_parity", "pump.checkpoint",
    "pump.cluster_tick",
    "engine.step", "engine.plan", "engine.prime", "engine.ring_sync",
    "engine.params",
    "engine.egress", "engine.account", "engine.rtcp", "engine.settle",
    "egress.wait",
    "megabatch.harvest", "megabatch.fetch", "megabatch.prime",
    "megabatch.dispatch", "megabatch.gather", "megabatch.h2d",
    "megabatch.shard_h2d", "megabatch.shard_wait", "megabatch.shard_fetch",
    "ingest.read",
    "native.egress", "native.stream_egress",
    "pipeline.step", "jax.build",
    # a process's life before it listens (``obs.boot``), in order
    "boot.interpreter", "boot.imports", "boot.native", "boot.backend",
    "boot.listen")
#: span families whose last part is data: ``rtsp.<method>``
SPAN_PREFIXES = ("rtsp.",)


def _annotation():
    """``(TraceAnnotation, is_enabled)`` of the installed JAX; where
    its profiler cannot be imported no session is ever live, and the
    ring still works."""
    try:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation, TraceAnnotation.is_enabled
    except Exception:
        return None, lambda: False


class Span:
    """An open span: what ``SpanTracer.open`` hands to ``close``."""

    __slots__ = ("name", "cat", "t0", "args", "tm")

    def __init__(self, name, cat, t0, args, tm):
        self.name = name
        self.cat = cat
        self.t0 = t0            # perf_counter_ns at open
        self.args = args
        self.tm = tm            # the live TraceAnnotation, or None


def t0_of(span: Span | None) -> int:
    """The instant ``span`` opened; now, where the bracket is off — for
    a caller whose own histogram shares the span's first clock read."""
    return span.t0 if span is not None else time.perf_counter_ns()


class SpanTracer:
    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._ring: deque = deque(maxlen=capacity)
        self._pid = os.getpid()
        #: ns origin so ts starts near 0 in the viewer
        self._epoch_ns = time.perf_counter_ns()
        self.dropped_hint = 0          # appends past capacity
        #: serializes the len-check/append/dropped_hint triple — the
        #: engine pump, asyncio handlers and native callers all record
        #: concurrently, and an unlocked += is a lost-update race
        self._lock = threading.Lock()
        #: EDTPU_PROFILE=0 turns the open/close bracket off with the
        #: profiler and the ledger (one switch for the three)
        self.enabled = os.environ.get("EDTPU_PROFILE", "1") != "0"
        #: sequence number of the pump wake in progress, stamped into
        #: every span opened inside it (None between wakes)
        self.wake: int | None = None
        self._annotate, self._session_live = None, None

    # -- recording ---------------------------------------------------
    def open(self, name: str, cat: str = "relay", **args) -> Span | None:
        """Start a span; ``close`` ends it.  Returns ``None`` when
        disabled (``close(None)`` is a no-op), so call sites need no
        branches of their own.  A span dropped unclosed (an exception
        unwound past it) never reaches the ring, and its annotation
        ends when the object is collected."""
        if not self.enabled:
            return None
        if self.wake is not None:
            args["wake"] = self.wake
        live = self._session_live
        if live is None and "jax" in sys.modules:
            # a process that never imported JAX has no profiler session
            self._annotate, live = _annotation()
            self._session_live = live
        tm = None
        if live is not None and live():
            tm = self._annotate(name, **args)
            tm.__enter__()
        return Span(name, cat, time.perf_counter_ns(), args, tm)

    def close(self, span: Span | None, **args) -> int:
        """End ``span`` with any late ``args``; returns the end instant
        (``perf_counter_ns``) so the caller's histogram and the span
        share one clock read."""
        now = time.perf_counter_ns()
        if span is None:
            return now
        if span.tm is not None:
            if args:
                span.tm.set_metadata(**args)
            span.tm.__exit__(None, None, None)
        if args:
            span.args.update(args)
        self._record(span.name, span.cat, span.t0, now - span.t0,
                     span.args)
        return now

    def _record(self, name: str, cat: str, t0_ns: int, dur_ns: int,
                args: dict | None) -> None:
        rec = (name, cat, t0_ns, dur_ns, threading.get_ident(),
               args or None)
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped_hint += 1
            self._ring.append(rec)

    def lap(self, span: Span | None, **args) -> int:
        """``close`` for a caller that wants the span's duration (ns; 0
        when the bracket is off, and so are its consumers)."""
        return self.close(span, **args) - span.t0 if span is not None else 0

    def add(self, name: str, t0_ns: int, dur_ns: int | None = None,
            cat: str = "relay", **args) -> None:
        """Record a span post hoc (ring only): ``dur_ns`` as the caller
        measured it, or [t0_ns, now] where it gives none.  Filed inside
        a wake it carries the wake's number, as an opened span does."""
        if dur_ns is None:
            dur_ns = time.perf_counter_ns() - t0_ns
        if self.wake is not None:
            args["wake"] = self.wake
        self._record(name, cat, t0_ns, dur_ns, args)

    # -- read side ---------------------------------------------------
    def __len__(self) -> int:
        return len(self._ring)

    def records(self) -> list[tuple]:
        """Raw (name, cat, t0_ns, dur_ns, tid, args) snapshot, oldest
        first — the flight recorder's span-correlation source."""
        with self._lock:
            return list(self._ring)

    def names(self) -> set:
        return {rec[0] for rec in self.records()}

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped_hint = 0

    def dump(self) -> dict:
        """Chrome trace-event format: ts/dur in MICROseconds."""
        events = []
        for name, cat, t0, dur, tid, args in self.records():
            ev = {"name": name, "cat": cat, "ph": "X",
                  "ts": (t0 - self._epoch_ns) / 1000.0,
                  "dur": dur / 1000.0, "pid": self._pid, "tid": tid}
            if args:
                ev["args"] = args
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}


#: process-wide tracer every instrumented layer records into
TRACER = SpanTracer()
