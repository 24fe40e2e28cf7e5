"""Fan-out engines: CPU oracle loop vs TPU batch path.

``RelayStream.reflect`` *is* the CPU oracle (faithful to
``ReflectorSender::ReflectPackets``).  ``TpuFanoutEngine`` is the replacement
north-star path (BASELINE config 4): one device computation per pass renders
every (subscriber, packet) header; the host then walks each output's bookmark
over the precomputed ``[S, P, 12]`` header block and scatters
``header ∥ payload[12:]`` — via vectored I/O in the native sender, or plain
concatenation for in-process sinks.  Packets' payload bytes are never copied
per-subscriber on the host and never cross to the device at all.

Differential guarantee (tested): for identical ring + output state, the bytes
delivered by ``TpuFanoutEngine.step`` equal those of ``RelayStream.reflect``.
"""

from __future__ import annotations

import errno as errno_mod
import time
import weakref

import numpy as np

from .. import obs
from ..device import listen_builds
from ..obs import PROFILER, TRACER, t0_of
from ..obs.profile import builds
from ..ops import device_ring
from ..ops import fanout as fanout_ops
from ..ops import parse as parse_ops
from ..resilience.inject import INJECTOR
from .output import RelayOutput, WriteResult
from .stream import RelayStream


def render_headers(b01: np.ndarray, seq: np.ndarray, ts: np.ndarray,
                   seq_off: np.ndarray, ts_off: np.ndarray,
                   ssrc: np.ndarray) -> np.ndarray:
    """Vectorized host render of the affine fan-out: [S,P,12] uint8 headers
    from O(P) packet fields + O(S) output offsets (see
    ``ops.fanout.relay_affine_step``).  Pure numpy, runs at memory
    bandwidth; byte-identical to the device's ``fanout_headers``."""
    S, P = seq_off.shape[0], seq.shape[0]
    out = np.empty((S, P, 12), dtype=np.uint8)
    out[:, :, 0:2] = b01[None, :, :]
    seq_sp = ((seq[None, :].astype(np.uint32) + seq_off[:, None]) & 0xFFFF
              ).astype(">u2")
    out[:, :, 2:4] = seq_sp.view(np.uint8).reshape(S, P, 2)
    ts_sp = (ts[None, :].astype(np.uint32) + ts_off[:, None]).astype(">u4")
    out[:, :, 4:8] = ts_sp.view(np.uint8).reshape(S, P, 4)
    ssrc_sp = np.broadcast_to(ssrc.astype(np.uint32)[:, None], (S, P)
                              ).astype(">u4")
    out[:, :, 8:12] = ssrc_sp.view(np.uint8).reshape(S, P, 4)
    return out


# the ONE bucket-shape rounding rule (ops/staging.py); re-exported under
# the historical name every megabatch consumer imports from here
from ..ops.staging import pow2 as _pow2  # noqa: E402

#: the egress backend ladder (ISSUE 8).  ``auto`` resolves to the best
#: rung the boot-time capability probe grants: io_uring where the kernel
#: has it, the GSO/sendmmsg pair otherwise; ``scalar`` forces the
#: per-datagram sendto baseline (bench denominators, worst-case drills).
EGRESS_BACKENDS = ("auto", "io_uring", "gso", "scalar")


def params_key(outputs) -> tuple:
    """The affine-params cache key: one 6-tuple of rewrite state per fast
    output, in fast-list order (the 6th element is the interleave
    channel byte, -1 for datagram outputs — set-once like the rest).
    The single definition shared by the per-stream engine and the
    megabatch scheduler — a scheduler-computed key that didn't match
    the engine's would silently force the slow path on every pass."""
    def _chan(o):
        ch = getattr(o, "interleave_chan", None)
        return -1 if ch is None else (ch & 0xFF)
    return tuple((o.rewrite.ssrc, o.rewrite.base_src_seq,
                  o.rewrite.base_src_ts, o.rewrite.out_seq_start,
                  o.rewrite.out_ts_start, _chan(o)) for o in outputs)


def _native_mod():
    from .. import native
    return native if native.available() else None


class _Plan:
    """One stream's output tables as one engine steps them, built once
    per membership epoch (``RelayStream.plan_epoch``) and not once per
    wake.  The unit of a step is the **cohort**: the native-fast UDP
    outputs of one bucket that share a bookmark.  ``out.bookmark`` stays
    the truth; a cohort's mark is a copy, written back to the outputs
    in the step that moves it."""

    __slots__ = ("epoch", "native_ok", "n_outputs", "udp", "udp_key",
                 "dests", "aud_rows", "b_arr", "cohorts",
                 "unprimed", "other", "fast", "key", "tcp", "slow")

    def __init__(self):
        self.epoch = -1
        self.native_ok = False
        self.n_outputs = 0
        #: native-fast UDP outputs in canonical (bucket-major) order: an
        #: output's index here is its column in ``params_key``, the dest
        #: table, the device state matrix and the op list
        self.udp: list[RelayOutput] = []
        self.udp_key: tuple = ()
        self.dests = None               # native dest table, built on use
        self.aud_rows = np.zeros(0, np.int64)   # audience row per column
        #: bucket index of each entry of ``cohorts``
        self.b_arr = np.zeros(0, np.int64)
        #: per bucket with a fast output: {bookmark: int32 columns,
        #: ascending}
        self.cohorts: list[dict[int, np.ndarray]] = []
        #: residue walked per output every wake, because what decides it
        #: changes without notice: outputs not yet bookmarked or latched
        #: (``_prime``'s work) ...
        self.unprimed: list[tuple[RelayOutput, int]] = []
        #: ... and every output that is not native-fast UDP (TCP, meta,
        #: thinning, no address): ``engine_writable()`` / ``passthrough()``
        self.other: list[tuple[RelayOutput, int]] = []
        # this wake's view (``TpuFanoutEngine.plan`` refreshes it from
        # ``other``): fast = udp + the TCP outputs writable now
        self.fast: list[RelayOutput] = []
        self.key: tuple = ()
        self.tcp: list[tuple[RelayOutput, int]] = []
        self.slow: list[tuple[RelayOutput, int]] = []

    def tables(self) -> tuple:
        """Everything the plan caches, in a form two plans compare by
        (tests: a plan built from scratch equals the cached one)."""
        return (self.native_ok, self.n_outputs,
                [id(o) for o in self.udp], self.udp_key,
                self.aud_rows.tolist(),
                [(b, sorted((m, c.tolist()) for m, c in co.items()))
                 for b, co in zip(self.b_arr.tolist(), self.cohorts)],
                [(id(o), b) for o, b in self.unprimed],
                [(id(o), b) for o, b in self.other])


def _join(cohorts: dict, mark: int, cols: np.ndarray) -> None:
    """Put ``cols`` under ``mark``, merging with the cohort already
    there (a straggler that caught up to its bucket's mark)."""
    have = cohorts.get(mark)
    cohorts[mark] = cols if have is None else np.sort(
        np.concatenate((have, cols)))


def _file(co: dict, fast: list, cols: np.ndarray, mark: int) -> None:
    """``cols`` now stand at ``mark`` with nothing else to account: write
    it back to the outputs (the truth) and file them as one cohort."""
    for c in cols.tolist():
        fast[c]._bookmark = mark
    _join(co, mark, cols)


def _column_runs(parts: list) -> list:
    """Several due cohorts of ONE bucket, as units in column order: a
    unit is a run of consecutive due columns of one cohort, so the op
    rows come out in fast-list order whatever the cohorts' shapes (one
    cohort a bucket, the common case, never comes here)."""
    cols = np.concatenate([p[0] for p in parts])
    owner = np.repeat(np.arange(len(parts)), [len(p[0]) for p in parts])
    order = np.argsort(cols, kind="stable")
    cols, owner = cols[order], owner[order]
    cuts = (np.flatnonzero(owner[1:] != owner[:-1]) + 1).tolist()
    return [(cols[lo:hi],) + parts[owner[lo]][1:]
            for lo, hi in zip([0] + cuts, cuts + [len(cols)])]


class _Scatter:
    """One step's UDP send between its two halves (``_udp_plan`` →
    ``_udp_settle``): the units and op list the plan half built, the
    rung it chose and the job it submitted."""

    __slots__ = ("due", "units", "total", "ops_np", "dests", "params",
                 "job", "res", "backend", "used_gso", "uring_failed",
                 "uring_err")


class _Inline:
    """The result of a send made on the loop thread (the io_uring rung),
    in the form a finished ``native.SendJob`` has."""

    __slots__ = ("result", "err", "start_ns", "done_ns")

    def __init__(self, result, err, start_ns, done_ns):
        self.result, self.err = result, err
        self.start_ns, self.done_ns = start_ns, done_ns


class _Pass:
    """One step between ``begin`` and ``finish``."""

    __slots__ = ("stream", "now_ms", "plan", "begin_ns", "udp", "tcp",
                 "jobs", "send_ns", "wait_ns", "hidden_ns")

    def __init__(self, stream, now_ms):
        self.stream, self.now_ms = stream, now_ms
        self.plan = None                # None: the step left by its first exit
        self.begin_ns = 0               # what the begin half took
        self.udp: _Scatter | None = None
        self.tcp: tuple | None = None   # ``_tcp_scatter``'s arguments
        #: the send jobs this step settled, their start → done seconds,
        #: what the loop thread spent blocked on them, and per job the
        #: send less that wait (floored at 0)
        self.jobs = self.send_ns = self.wait_ns = self.hidden_ns = 0

    @property
    def done(self) -> bool:
        """Nothing of this step is still with the sender: ``finish``
        will not block."""
        return self.udp is None or self.udp.job is None or self.udp.job.done


class TpuFanoutEngine:
    """Batched fan-out for one stream, built and stepped once a wake by
    the pump (``relay/pump.py``).  The relay state stays in the stream
    and its outputs; the engine keeps what it derives from them (the
    output plan, the affine params, the HBM-resident ring).

    Two egress paths per step:

    * **native fast path** — outputs that expose ``native_addr`` (the
      server's shared-UDP-pair sinks), carry no meta-info wrap and whose
      thinning filter is pass-through.  The affine rewrite params come
      from the device step (``ops.fanout.relay_affine_step_window`` —
      recomputed only when membership/rebase state changes, since the
      params are independent of packet content) and the wire writes go
      through ``native.fanout_send_multi`` (sendmmsg/UDP-GSO scatter,
      or the server's io_uring ring): no per-packet Python, no
      per-subscriber payload copies.  Writable interleaved-TCP outputs
      ride the same params (``_tcp_scatter``).
    * **batch-header path** — everything else (TCP-interleaved,
      meta-info, actively-thinned outputs): the [S, P, 12] device header
      block walked per output exactly as round 1 did.
    """

    def __init__(self, prefix_width: int = parse_ops.PARSE_PREFIX,
                 egress_fd: int | None = None,
                 uring=None, egress_backend: str = "auto"):
        self.prefix_width = prefix_width
        self.egress_fd = egress_fd
        #: native.UringEgress over the same fd (None = no io_uring);
        #: owned by the server (shared across engines), never closed here
        self.uring = uring
        #: requested backend (EGRESS_BACKENDS); ``effective_backend()``
        #: resolves it against what the probe granted and what runtime
        #: strikes have since disqualified
        self.egress_backend = egress_backend
        self.steps = 0
        self.packets_sent = 0
        self.native_sent = 0
        self.native_passes = 0
        self.device_param_refreshes = 0
        self.last_newest_keyframe = -1
        self.send_errors = 0                # hard per-datagram send errors
        # GSO is tried per pass until proven broken: single-segment supers
        # succeed even without kernel UDP_SEGMENT, so success alone must
        # never latch it on; two passes where GSO fails but plain sendmmsg
        # succeeds disable it (transient errors don't)
        self._gso_disabled = False
        self._gso_strikes = 0
        # io_uring is disqualified the same way GSO is: two passes where
        # the ring fails outright but the sendmmsg rung succeeds drop
        # this engine one rung down the ladder, with ONE structured
        # egress.backend_fallback event (the PR 4 GSO-probe fix shape)
        self._uring_disabled = False
        self._uring_strikes = 0
        # the STREAM-socket rung strikes independently: a TCP-side ring
        # failure must not demote healthy datagram sends (and vice versa)
        self._uring_stream_disabled = False
        self._uring_stream_strikes = 0
        self._params_key = None
        self._params = None           # ([1,S] seq_off, ts_off, ssrc, chan)
        self._dests_key = None
        self._dests = None
        #: stream -> its ``_Plan`` (weak: a torn-down stream's tables go
        #: with it); ``plan()`` is the one way in
        self._plans: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()
        #: outputs a rebuild walked since the last step filed its count
        self._rebuild_walked = 0
        # HBM-resident GOP ring (SURVEY §5 long-context analogue): the
        # classification window lives on the device; each pass APPENDS
        # only the new packets' prefixes (async dispatch, no sync), so
        # per-pass H2D is O(new packets) instead of O(window) — round 1
        # re-staged the whole prefix window on every params refresh.
        self._dring: device_ring.RingState | None = None
        self._dring_appended = 0            # host pid appended up to
        self._dring_base = 0                # host pid of device abs id 0
        self._dring_epoch = 0               # arrival-ms epoch (int32 room)
        self.dring_appends = 0              # device append dispatches
        self.h2d_appended_bytes = 0
        self.h2d_window_equiv_bytes = 0     # what per-pass restaging costs
        # -- megabatch scheduler hooks (relay/megabatch.py) --------------
        #: True while the cross-stream scheduler owns this stream's
        #: device work: step() skips the per-wake device-ring append (the
        #: scheduler's stacked staging replaces it) and the scheduler
        #: harvest installs params via ``megabatch_params``
        self.megabatch_owned = False
        #: (params_key, (seq_off, ts_off, ssrc)) installed by the last
        #: scheduler harvest — consumed by ``_device_params`` when the
        #: key still matches; a stale key falls back to the per-stream
        #: device query (the slow path)
        self.megabatch_params: tuple | None = None
        self.megabatch_installs = 0
        #: mesh shard index that computed the last installed override
        #: (-1 = single-device dispatch or synchronous prime) — the
        #: per-stream half of the scheduler's device-keyed scatter,
        #: surfaced so an operator chasing one stream's divergence can
        #: see which chip produced its params
        self.megabatch_shard = -1
        # per-pass phase attribution scratch (obs/profile.py), keyed
        # (engine, phase): sub-steps accumulate brackets here; step()
        # reports the merged dict once per engine
        self._pass_phases: dict[tuple[str, str], int] = {}
        self._pass_wire_bytes = 0
        #: outputs this pass walked / those of them with a packet past
        #: its hold (engine_outputs_*_total, added once per step)
        self._pass_walked = 0
        self._pass_due = 0
        self._profiled = False
        #: the pass ``begin`` returned and ``finish`` has not taken yet:
        #: the scratch above is that pass's (None between steps)
        self.open_pass: _Pass | None = None
        #: ``trace_id`` of the stream being stepped, on every child span
        self._span_args: dict = {}
        # a bracket that held an XLA build (a cold pass's compile, or a
        # re-trace when a session grows past a power-of-two pad) stays
        # out of the phase histograms — one 100 ms+ outlier would own
        # every phase mean/p99 forever.  jax_executables_built_total
        # (obs.profile.builds) says exactly when that happened
        listen_builds()

    # -- helpers -----------------------------------------------------------
    def _native_ok(self) -> bool:
        return (self.egress_fd is not None and self.egress_fd >= 0
                and _native_mod() is not None)

    def effective_backend(self) -> str:
        """The rung actually serving this engine's wire writes.  A
        forced ``io_uring`` on a kernel without it reads ``gso`` here —
        what /metrics' ``egress_backend_info`` reports and what
        ``tools/soak.py --egress-backend`` asserts against."""
        if self.egress_backend == "scalar":
            return "scalar"
        if (self.egress_backend in ("auto", "io_uring")
                and not self._uring_disabled
                and self.uring is not None
                and getattr(self.uring, "active", False)):
            return "io_uring"
        return "gso"

    def _note_uring_failure(self, err: int) -> None:
        """A whole-batch io_uring failure while sendmmsg still works:
        strike the backend; two strikes retire it for this engine with
        ONE structured fallback event — never a counted hard_error
        (probe-outcome semantics, the PR 4 GSO EINVAL fix shape)."""
        if self._uring_disabled:
            return
        self._uring_strikes += 1
        if self._uring_strikes < 2:
            return
        self._uring_disabled = True
        reason = (errno_mod.errorcode.get(err, str(err)) if err
                  else "unknown")
        obs.EGRESS_BACKEND_FALLBACKS.inc(backend="io_uring")
        obs.EVENTS.emit("egress.backend_fallback", level="warn",
                        backend="io_uring", fallback="gso", reason=reason)
        # the info gauge tracks the engine-observed truth so a scrape
        # never claims io_uring while the GSO rung serves the wire
        obs.EGRESS_BACKEND_INFO.set(0, backend="io_uring")
        obs.EGRESS_BACKEND_INFO.set(1, backend="gso")

    @staticmethod
    def _fast_eligible(out, native_ok: bool) -> bool:
        """Native fast-path predicate — the ONE definition step() and the
        megabatch scheduler share, so the scheduler stages params for
        exactly the output set the engine will send through sendmmsg."""
        return (native_ok and out._bookmark is not None
                and getattr(out, "native_addr", None) is not None
                and out._meta_field_ids is None
                and out.thinning.passthrough())

    def _tcp_eligible(self, out, native_ok: bool) -> bool:
        """Interleaved-TCP fast-path predicate (ISSUE 14): a framed
        stream-socket output whose connection is currently directly
        writable (no asyncio transport backlog — raw fd writes must
        never reorder around buffered RTSP/RTCP bytes).  A forced
        ``scalar`` backend keeps TCP on the per-send batch-header rung,
        the honest baseline the bench compares against.  Unlike the UDP
        predicate this needs no shared egress fd — the connection IS
        the transport — only the native library."""
        return (_native_mod() is not None
                and self.egress_backend != "scalar"
                and out._bookmark is not None
                and getattr(out, "interleave_chan", None) is not None
                and getattr(out, "stream_fd", -1) >= 0
                and out._meta_field_ids is None
                and out.thinning.passthrough()
                and out.engine_writable())

    # -- the output plan ---------------------------------------------------
    def plan(self, stream: RelayStream, now_ms: int) -> _Plan:
        """This stream's output plan, current as of this wake: the ONE
        place the engine and the megabatch scheduler get the fast list
        (every UDP-fast output, bucket-major, then every TCP-fast one)
        and ``params_key`` from, so a scheduler-staged pass lands on
        exactly the columns the engine will consume.

        Per call: ``_prime``'s placement and latch over the un-primed
        residue, one epoch comparison, and the TCP / meta / thinning
        residue's predicates.  A moved epoch (``RelayStream.plan_epoch``
        — membership, a bookmark written from outside the engine, a
        rewrite field, a thinning level, ``meta_field_ids``) or a change
        of ``_native_ok()`` rebuilds the tables."""
        p = self._plans.get(stream)
        ok = self._native_ok()
        if p is not None and p.native_ok == ok:
            if p.unprimed:
                tok = self._open("engine.prime")
                self._prime(stream, p.unprimed, now_ms)
                TRACER.close(tok)
            if (p.epoch == stream.plan_epoch
                    and self._split_residue(p, stream.rtp_ring, ok)):
                return p
        t0 = time.perf_counter_ns()
        p = self._plans[stream] = self._build_plan(stream, now_ms, ok)
        self._rebuild_walked += p.n_outputs
        obs.ENGINE_PLAN_REBUILDS.inc()
        if TRACER.enabled:              # ring only: rare, and post hoc
            TRACER.add("engine.plan", t0, cat="tpu", **self._span_args,
                       outputs=p.n_outputs, fast=len(p.udp))
        return p

    def _build_plan(self, stream: RelayStream, now_ms: int,
                    ok: bool) -> _Plan:
        """One walk over every output: prime, classify, group the UDP
        fast list's columns by (bucket, bookmark)."""
        flat = [(out, b_idx) for b_idx, bucket in enumerate(stream.buckets)
                for out in bucket]
        self._prime(stream, [pair for pair in flat
                             if pair[0]._bookmark is None
                             or pair[0].rewrite.base_src_seq < 0], now_ms)
        p = _Plan()
        p.native_ok = ok
        p.n_outputs = len(flat)
        cur_b = -1
        b_list: list[int] = []
        marks: dict[int, list[int]] = {}
        for pair in flat:
            out, b_idx = pair
            if out._bookmark is None or out.rewrite.base_src_seq < 0:
                p.unprimed.append(pair)
            if not self._fast_eligible(out, ok):
                p.other.append(pair)
                continue
            if b_idx != cur_b:
                cur_b = b_idx
                marks = {}
                b_list.append(b_idx)
                p.cohorts.append(marks)
            marks.setdefault(out._bookmark, []).append(len(p.udp))
            p.udp.append(out)
        for co in p.cohorts:
            for mark, cols in co.items():
                co[mark] = np.asarray(cols, np.int32)
        p.b_arr = np.asarray(b_list, np.int64)
        p.udp_key = params_key(p.udp)
        p.aud_rows = np.asarray(
            [getattr(o, "audience_row", -1) for o in p.udp], np.int64)
        # the epoch as the walk leaves it: _prime's own writes moved it
        p.epoch = stream.plan_epoch
        p.fast, p.key = p.udp, p.udp_key
        # cannot ask for another rebuild: nothing in ``other`` is fast
        self._split_residue(p, stream.rtp_ring, ok)
        return p

    def _split_residue(self, p: _Plan, ring, ok: bool) -> bool:
        """This wake's TCP-fast and batch-header lists out of the
        residue, and the fast list / key they make with the cached UDP
        part.  False where a residue output has turned UDP-fast (its
        thinning filter went back to pass-through): rebuild."""
        if not p.other:
            return True                 # fast / key are the UDP tables
        tail = ring.tail
        tcp, slow = [], []
        for pair in p.other:
            out = pair[0]
            bm = out._bookmark
            if bm is not None and bm < tail:
                out._bookmark = tail    # evicted from under a stalled output
            if self._fast_eligible(out, ok):
                return False
            (tcp if self._tcp_eligible(out, ok) else slow).append(pair)
        p.tcp, p.slow = tcp, slow
        if tcp:
            outs = [o for o, _ in tcp]
            p.fast, p.key = p.udp + outs, p.udp_key + params_key(outs)
        else:
            p.fast, p.key = p.udp, p.udp_key
        return True

    def _prime(self, stream: RelayStream, pairs, now_ms: int) -> None:
        """New-output placement + seq/ts rebase priming, over the
        outputs that still need either.

        The scalar oracle latches the rebase origin exactly once, inside the
        first ``write_rtp`` *attempt* (``RewriteState.base_src_seq < 0``
        check — even a WOULD_BLOCK'd attempt latches).  Mirror that: latch
        only if unlatched, from the first ring packet this output would
        attempt this pass (bookmark advanced past runts, and only if that
        packet is bucket-eligible now).  A placement or a latch moves the
        stream's plan epoch: the output changes lists, or its key."""
        ring = stream.rtp_ring
        delay = stream.settings.bucket_delay_ms
        moved = False
        for out, b_idx in pairs:
            if out._bookmark is None:
                out._bookmark = stream.first_packet_for_new_output(now_ms)
                moved = moved or out._bookmark is not None
            if out._bookmark is not None and out._bookmark < ring.tail:
                out._bookmark = ring.tail
                moved = True            # it may sit in a cohort
            if out.rewrite.base_src_seq >= 0 or out._bookmark is None:
                continue
            pid = out._bookmark
            while pid < ring.head and ring.length[ring.slot(pid)] < 12:
                pid += 1               # runts are skipped, never latched
            if pid >= ring.head:
                continue
            s = ring.slot(pid)
            if now_ms - int(ring.arrival[s]) >= b_idx * delay:
                out.rewrite.base_src_seq = int(ring.seq[s])
                out.rewrite.base_src_ts = int(ring.timestamp[s])
                moved = True
        if moved:
            stream.touch_plan()

    # -- the batch pass ----------------------------------------------------
    def _phase_add(self, phase: str, dur_ns: int,
                   engine: str = "native") -> None:
        """Accumulate one phase bracket into the current pass (sub-steps
        may hit a phase more than once per pass — GSO retry, params
        refresh); ``step()`` hands the merged dict to the profiler ONCE
        per pass, so histogram cost stays per-pass, never per-bracket.
        Keyed (engine, phase): a mixed pass (native-addressed AND
        TCP/meta outputs) must file each sub-path's brackets under its
        own engine label, not whichever path happened to run."""
        key = (engine, phase)
        self._pass_phases[key] = self._pass_phases.get(key, 0) + dur_ns

    def _open(self, name: str, **args):
        """Open one span of a step's two halves (``obs.trace``)."""
        return TRACER.open(name, "tpu", **self._span_args, **args)

    def _spans_of(self, stream: RelayStream) -> None:
        """Every span opened from here on carries ``stream``'s trace."""
        self._span_args = ({} if stream.trace_id is None
                           else {"trace_id": stream.trace_id})

    def _close(self, span, phase: str | None = None, engine: str = "native",
               built0: float | None = None, **args) -> int:
        """Close a child span and file its interval as ``phase``: the
        span and the phase histogram share the bracket's two clock
        reads.  ``built0``: ``builds()`` at open — a bracket that held a
        build files no phase.  Returns the end instant."""
        end = TRACER.close(span, **args)
        if (phase is not None and span is not None and self._profiled
                and (built0 is None or builds() == built0)):
            self._phase_add(phase, end - span.t0, engine)
        return end

    def step(self, stream: RelayStream, now_ms: int) -> int:
        """One pass over ``stream``, begun and finished in this call
        (every caller with no pump)."""
        return self.finish(self.begin(stream, now_ms))

    def begin(self, stream: RelayStream, now_ms: int) -> _Pass:
        """The first half of a step: the plan, the device params, the op
        list, and the UDP send handed to the native sender.  Returns at
        once; ``finish`` takes what it returns.  Between the two the
        ring's slots, the param rows and the dest table the job points
        into must not be written — the pump finishes every pass it began
        before the wake ends."""
        self._spans_of(stream)
        step_span = self._open("engine.step")
        t0 = t0_of(step_span)
        ps = _Pass(stream, now_ms)
        ring = stream.rtp_ring
        if not stream.num_outputs or len(ring) == 0:
            TRACER.close(step_span, outputs=stream.num_outputs)
            obs.ENGINE_STEPS.inc(result="idle")
            return ps
        self._profiled = PROFILER.enabled
        self._pass_phases = {}
        self._pass_wire_bytes = 0
        self._pass_walked = self._pass_due = 0
        plan = ps.plan = self.plan(stream, now_ms)
        if plan.udp or plan.tcp:
            self._native_begin(ps)
        ps.begin_ns = TRACER.close(step_span, outputs=plan.n_outputs,
                                   due_outputs=self._pass_due) - t0
        self.open_pass = ps
        return ps

    def finish(self, ps: _Pass) -> int:
        """The second half: settle the UDP job (blocking until the sender
        is through with it), then the stream's TCP and batch-header
        outputs and its RTCP, inline and in that order."""
        plan = ps.plan
        if plan is None:
            return 0
        self.open_pass = None
        stream, now_ms = ps.stream, ps.now_ms
        self._spans_of(stream)
        settle_span = self._open("engine.settle")
        t0 = t0_of(settle_span)
        profiled = self._profiled
        sent = 0
        if ps.udp is not None:
            try:
                sent += self._udp_settle(ps)
            except BaseException:
                stream.touch_plan()     # due cohorts may be half settled
                raise
        if ps.tcp is not None:
            sent += self._tcp_scatter(stream, plan.tcp, len(plan.udp),
                                      *ps.tcp, now_ms)
        slow = plan.slow
        if slow:
            sent += self._batch_header_step(stream, slow, now_ms)
        # RTCP relay + SR origination, identical to the scalar path
        tok = self._open("engine.rtcp")
        stream.relay_rtcp(now_ms)
        end = TRACER.close(tok)
        if profiled and tok is not None:
            dt = end - tok.t0
            # file one slice per engine actually exercised this pass,
            # splitting the bracket so a mixed pass neither hides the
            # batch path's share under "native" nor double-counts the
            # wall time in the session's phase_ns
            engines = [e for e, ran in (("native",
                                         bool(plan.udp) or bool(plan.tcp)),
                                        ("batch", bool(slow))) if ran]
            share = dt // len(engines)
            for i, e in enumerate(engines):
                # last slice takes the division remainder so the summed
                # slices equal the measured bracket exactly
                self._phase_add("rtcp_qos",
                                dt - share * (len(engines) - 1)
                                if i == len(engines) - 1 else share,
                                engine=e)
        stream.stats.packets_out += sent
        self.steps += 1
        self.packets_sent += sent
        # the loop thread's time in both halves, its waits for the
        # sender included
        dur = ps.begin_ns + TRACER.close(settle_span, sent=sent) - t0
        obs.TPU_PASS_SECONDS.observe(dur / 1e9, stage="engine_step")
        obs.TPU_PASSES.inc()
        # idle: no cohort was past its hold and nothing else was sent
        obs.ENGINE_STEPS.inc(
            result="worked" if sent or self._pass_due else "idle")
        # output objects this step touched: the due cohorts' members, the
        # residue it sent for, and a rebuild's walk where there was one
        obs.ENGINE_OUTPUTS_WALKED.inc(self._pass_walked
                                      + self._rebuild_walked)
        self._rebuild_walked = 0
        if self._pass_due:
            obs.ENGINE_OUTPUTS_DUE.inc(self._pass_due)
        if sent:
            obs.TPU_PACKETS_SENT.inc(sent)
        if profiled and self._pass_phases:
            by_engine: dict[str, dict[str, int]] = {}
            for (eng, ph), ns in self._pass_phases.items():
                by_engine.setdefault(eng, {})[ph] = ns
            first_slice = True      # session bytes/passes counted once
            for eng, phases in by_engine.items():
                PROFILER.account_pass(
                    eng, dur, phases, path=stream.session_path,
                    wire_bytes=self._pass_wire_bytes if first_slice else 0,
                    count_pass=first_slice)
                first_slice = False
        return sent

    # -- native fast path --------------------------------------------------
    def _dests_for(self, plan: _Plan):
        if plan.dests is None:
            from .. import native
            key = tuple(o.native_addr for o in plan.udp)
            if key != self._dests_key:
                self._dests = native.make_dests(list(key))
                self._dests_key = key
            plan.dests = self._dests
        return plan.dests

    def _ring_sync(self, ring, now_ms: int) -> None:
        """Append packets the device ring has not seen yet (O(new) H2D,
        async dispatch — nothing blocks until a params refresh fetches).
        Staging + the async append dispatch are the pass's host-side
        H2D cost (the device-side copy overlaps later phases): callers
        bracket it as ``engine.ring_sync`` / ``h2d``."""
        if self._dring is None:
            self._dring = device_ring.init_ring(ring.capacity)
            self._dring_appended = self._dring_base = max(
                ring.tail, ring.head - ring.capacity)
            self._dring_epoch = now_ms
        if ring.head - self._dring_appended > ring.capacity:
            # fell too far behind (burst > capacity): restart the window
            self._dring = device_ring.init_ring(ring.capacity)
            self._dring_appended = self._dring_base = \
                ring.head - ring.capacity
            self._dring_epoch = now_ms
        n_new = ring.head - self._dring_appended
        if n_new <= 0:
            return
        ids, lengths, _f = ring.window_meta(self._dring_appended, n_new)
        b_pad = _pow2(len(ids), 16)
        prefix = np.zeros((b_pad, self.prefix_width), np.uint8)
        # advanced index with a column slice: copies only the prefix bytes
        prefix[:len(ids)] = ring.data[ids % ring.capacity,
                                      :self.prefix_width]
        length = np.zeros(b_pad, np.int32)
        length[:len(ids)] = lengths
        arrival = np.zeros(b_pad, np.int32)
        arrival[:len(ids)] = (ring.arrival[ids % ring.capacity]
                              - self._dring_epoch).astype(np.int32)
        self._dring = device_ring.append(
            self._dring, prefix, length, arrival, np.int32(len(ids)))
        self._dring_appended = ring.head
        self.dring_appends += 1
        self.h2d_appended_bytes += b_pad * (self.prefix_width + 8)
        obs.TPU_H2D_BYTES.inc(b_pad * (self.prefix_width + 8))

    def _device_params(self, fast, key, ring, now_ms: int):
        """Affine egress params from the device step over the RESIDENT
        window (``ops.device_ring``) — no window re-staging.

        The params depend only on per-output rewrite state, not packet
        content, so they are recomputed ONLY when membership or rebase
        state changes (subscribe/unsubscribe/latch) — the common-case
        pass reuses the cached triples and spends nothing on the device.
        Shapes are padded to powers of two to bound jit specializations."""
        if INJECTOR.active:
            # chaos sites (resilience/inject.py): stale_params discards
            # the cached/installed affine params (forcing the refresh
            # path); device_dispatch raises a transient InjectedFault
            # BEFORE any send, so the pump's per-stream guard and the
            # ladder's retry-with-backoff see exactly what a real device
            # error produces
            if INJECTOR.stale_params():
                self._params_key = None
                self.megabatch_params = None
            INJECTOR.device_dispatch("fanout.device_params")
        if key == self._params_key:
            return self._params
        mb = self.megabatch_params
        if mb is not None and mb[0] == key:
            # the cross-stream scheduler already computed this key's
            # params in a stacked pass — install, no device round-trip
            self._params = mb[1]
            self._params_key = key
            self.megabatch_installs += 1
            return self._params
        if self.megabatch_owned:
            # owned stream whose override is missing/stale (fresh join,
            # rebase latch mid-wake): per-stream device query is the
            # fallback.  The resident ring was not synced this pass
            # (the scheduler owns staging), so catch it up lazily first.
            obs.MEGABATCH_FALLBACK.inc()
            tok = self._open("engine.ring_sync")
            built0 = builds()
            self._ring_sync(ring, now_ms)
            self._close(tok, "h2d", built0=built0)
        S = len(fast)
        tok = self._open("engine.params", outputs=S)
        t0 = t0_of(tok)
        built0 = builds()
        s_pad = _pow2(S, 8)
        state = np.zeros((s_pad, fanout_ops.STATE_COLS), np.uint32)
        state[:S] = np.asarray(fanout_ops.pack_output_state(fast))
        res = device_ring.query(self._dring, state,
                                np.int32(now_ms - self._dring_epoch))
        # phase split: dispatching the fused query is device_step; the
        # np.asarray fetches below BLOCK on the result crossing back —
        # that wait is d2h, and charging it to device_step (or letting it
        # leak into egress, as the pre-profiler timing did) is exactly
        # the attribution error the phase layer exists to kill
        t_dev = time.perf_counter_ns()
        seq_off = np.asarray(res["seq_off"])[None, :S]
        ts_off = np.asarray(res["ts_off"])[None, :S]
        ssrc = np.asarray(res["ssrc"])[None, :S]
        chan = np.asarray(res["chan"])[None, :S]
        kf_abs = int(res["newest_keyframe_abs"])
        t_d2h = TRACER.close(tok)
        if PROFILER.enabled and builds() == built0:
            self._phase_add("device_step", t_dev - t0)
            self._phase_add("d2h", t_d2h - t_dev)
        self.last_newest_keyframe = (self._dring_base + kf_abs
                                     if kf_abs >= 0 else -1)
        self._params = (np.ascontiguousarray(seq_off),
                        np.ascontiguousarray(ts_off),
                        np.ascontiguousarray(ssrc),
                        np.ascontiguousarray(chan))
        self._params_key = key
        self.device_param_refreshes += 1
        obs.TPU_PARAM_REFRESHES.inc()
        # the three [1,S] uint32 param rows + the keyframe scalar crossed
        # device→host to serve this refresh
        obs.TPU_D2H_BYTES.inc(sum(a.nbytes for a in self._params) + 8)
        obs.TPU_PASS_SECONDS.observe((time.perf_counter_ns() - t0) / 1e9,
                                     stage="device_params")
        return self._params

    def _native_begin(self, ps: _Pass) -> None:
        """Every eligible (packet, output) pair goes through the native
        senders — ONE sendmmsg/GSO scatter for the UDP set, one framed
        writev/io_uring batch per interleaved-TCP connection — all from
        ONE device param pass (the affine rewrite plus the interleave
        channel column ride the same query).  This is the begin half:
        the UDP scatter is planned and submitted (``ps.udp``), the TCP
        scatter's arguments are left for ``finish`` (``ps.tcp``).

        Due selection is bucket-major: one ``searchsorted`` over the
        buckets' deadlines, then one comparison per cohort.  A stream
        with nothing due returns here, before the device params and
        before any output object is touched."""
        stream, plan, now_ms = ps.stream, ps.plan, ps.now_ms
        ring = stream.rtp_ring
        tcp = plan.tcp
        # extracting the host window view is part of staging it: one
        # h2d bracket over the view and the device-ring append
        tok = self._open("engine.ring_sync")
        built0 = builds()
        head = ring.head
        start = head
        for co in plan.cohorts:
            for mark in co:
                if mark < start:
                    start = mark
        if start < ring.tail:
            start = self._clamp_cohorts(plan, ring.tail)
        for o, _ in tcp:
            if o._bookmark < start:
                start = o._bookmark
        if start >= head:                   # everyone has caught up
            TRACER.close(tok)
            return
        ids, lengths, _flags = ring.window_meta(start, head - start)
        if len(ids) == 0:
            TRACER.close(tok)
            return
        start = int(ids[0])                 # window_meta clamps to tail
        idx = (ids % ring.capacity).astype(np.int32)
        arrivals = ring.arrival[idx]        # nondecreasing (ingest clock)
        # (bucket entry, mark, window index its hold has released up to)
        due: list[tuple[int, int, int]] = []
        if plan.cohorts:
            his = np.searchsorted(
                arrivals,
                now_ms - plan.b_arr * stream.settings.bucket_delay_ms,
                side="right").tolist()
            for bi, co in enumerate(plan.cohorts):
                hi = his[bi]
                if hi:
                    for mark in co:
                        if start + hi > mark:
                            due.append((bi, mark, hi))
        if not due and not tcp:
            TRACER.close(tok)
            return
        valid = lengths >= 12
        if not self.megabatch_owned:
            # scheduler-owned streams skip the per-wake device append:
            # the megabatch's stacked staging replaces it (the resident
            # ring catches up lazily if a per-stream query is ever
            # needed again)
            self._ring_sync(ring, now_ms)
        self._close(tok, "h2d", built0=built0)
        # counterfactual H2D of a design that re-stages the device's full
        # classification window every pass (what keeping the window fresh
        # without a resident ring costs); h2d_appended_bytes is the O(new)
        # actual.  The ratio is the device-ring saving (VERDICT r2 item 6).
        live_window = ring.head - max(ring.tail, ring.head - ring.capacity)
        self.h2d_window_equiv_bytes += live_window * (self.prefix_width + 8)
        seq_off, ts_off, ssrc, chan = self._device_params(
            plan.fast, plan.key, ring, now_ms)
        if due:
            try:
                ps.udp = self._udp_plan(stream, plan, due, start, ids, idx,
                                        valid, lengths, seq_off, ts_off,
                                        ssrc)
            except BaseException:
                stream.touch_plan()     # a runt-only span was being filed
                raise
        if tcp:
            ps.tcp = (start, ids, idx, arrivals, valid, lengths, seq_off,
                      ts_off, ssrc, chan)
        self.native_passes += 1

    def _clamp_cohorts(self, plan: _Plan, tail: int) -> int:
        """The ring evicted past a stalled cohort: it resumes at the
        tail, as ``_prime`` has it for one output.  Returns the tail."""
        for co in plan.cohorts:
            for mark in [m for m in co if m < tail]:
                _file(co, plan.udp, co.pop(mark), tail)
        return tail

    def _submit(self, ring, sc: _Scatter, ops, n_ops: int, use_gso,
                trace_id):
        """One UDP send job to the native sender thread."""
        from .. import native
        seq_off, ts_off, ssrc = sc.params
        return native.fanout_send_multi(
            self.egress_fd, ring.data, ring.length, seq_off, ts_off, ssrc,
            sc.dests, ops, n_ops, use_gso=use_gso, trace_id=trace_id,
            submit=True)

    def _await(self, ps: _Pass, job):
        """Block until the sender is through with ``job`` (``egress.wait``
        is the loop thread blocked), file the send as ``native.egress``
        from the job's own stamps, and account it to the pass: its send
        seconds into the ``egress_native`` phase, and what of them the
        loop thread did not spend waiting as hidden."""
        waited = 0
        if not job.done:
            tok = self._open("egress.wait")
            t0 = t0_of(tok)
            job.wait()
            waited = TRACER.close(tok) - t0
        send_ns = job.done_ns - job.start_ns
        if TRACER.enabled:
            TRACER.add("native.egress", job.start_ns, send_ns,
                       cat="native", **self._span_args, ops=job.n_ops,
                       gso=job.use_gso, sent=job.result,
                       datagrams=max(job.result, 0), syscalls=job.syscalls,
                       queued_us=(job.start_ns - job.submit_ns) // 1000)
        if self._profiled:
            self._phase_add("egress_native", send_ns)
        ps.jobs += 1
        ps.send_ns += send_ns
        ps.wait_ns += waited
        ps.hidden_ns += max(send_ns - waited, 0)
        return job

    def _udp_plan(self, stream: RelayStream, plan: _Plan, due, start,
                  ids, idx, valid, lengths, seq_off, ts_off,
                  ssrc) -> _Scatter | None:
        """The plan half of the UDP scatter: the due cohorts' units, the
        op list, and the send — submitted to the native sender, not
        waited for.  None where every due span was runts."""
        from .. import native
        ring = stream.rtp_ring
        fast = plan.udp
        cohorts = plan.cohorts
        # egress_native starts HERE: everything from params-in-hand to
        # wire — span selection, the scatter op list, and the native
        # sendmmsg/GSO calls — is the egress stage (leaving the
        # op-list numpy unphased put Σ(phases) ~15% under the pass total)
        egress = self._open("engine.egress")
        # one unit per due cohort: (bucket entry, columns, bookmark once
        # sent, pids, slots, lens) — numpy slices of the window, shared
        # by every output of the cohort
        units: list[tuple] = []
        total = 0
        n_due = 0                           # outputs with a packet to send
        i = 0
        while i < len(due):
            bi = due[i][0]
            parts = []
            while i < len(due) and due[i][0] == bi:
                _bi, mark, hi = due[i]
                i += 1
                lo = max(mark - start, 0)
                sel = valid[lo:hi]
                parts.append((cohorts[bi][mark], start + hi,
                              ids[lo:hi][sel], idx[lo:hi][sel],
                              lengths[lo:hi][sel]))
            if len(parts) > 1:
                parts = _column_runs(parts)
            for part in parts:
                units.append((bi,) + part)
                n_due += len(part[0])
                total += len(part[0]) * len(part[2])
        self._pass_walked += n_due
        self._pass_due += n_due
        if total == 0:
            for bi, mark, _hi in due:
                del cohorts[bi][mark]
            for bi, cols, hi_abs, _p, _s, _l in units:
                _file(cohorts[bi], fast, cols, hi_abs)  # runt-only: skip
            TRACER.close(egress, outputs=len(fast), due_outputs=n_due,
                         sent=0)
            return None
        # the SAME op list, row for row, as one span per output would
        # build: columns ascend (fast order is bucket-major) and a
        # cohort's columns each carry its slots
        ops_np = np.empty((total, 2), np.int32)
        pos = 0
        for _bi, cols, _hi, pids, slots, _l in units:
            n = len(cols) * len(pids)
            if n:
                ops_np[pos:pos + n, 0] = np.tile(slots, len(cols))
                ops_np[pos:pos + n, 1] = np.repeat(cols, len(pids))
                pos += n
        sc = _Scatter()
        sc.due, sc.units, sc.total = due, units, total
        sc.ops_np = ops_np
        sc.dests = self._dests_for(plan)
        sc.params = (seq_off, ts_off, ssrc)
        sc.job = sc.res = None
        sc.used_gso = sc.uring_failed = False
        sc.uring_err = 0
        ops = native.ops_from_numpy(ops_np)
        trace_id = stream.trace_id
        backend = self.effective_backend()
        if backend == "io_uring":
            # one linked-SQE submission per chain instead of one
            # sendmmsg slot per run — EAGAIN/hard semantics identical,
            # so the bookmark accounting is backend-blind.  The ring
            # belongs to the loop thread: this rung sends here, inline
            t_send = time.perf_counter_ns()
            r = self.uring.send_multi(
                ring.data, ring.length, seq_off, ts_off, ssrc, sc.dests,
                ops, total, trace_id=trace_id)
            if r < 0:
                # whole-batch ring failure with nothing sent: serve this
                # pass from the GSO rung; strike io_uring only if a
                # lower rung proves the destinations are fine
                sc.uring_failed = True
                sc.uring_err = native.last_send_errno() or -r
                backend = "gso"
            else:
                sc.res = _Inline(r, native.last_send_errno(), t_send,
                                 time.perf_counter_ns())
        if backend == "scalar":
            # forced per-datagram sendto baseline (egress_backend=scalar)
            sc.job = self._submit(ring, sc, ops, total, 2, trace_id)
        elif backend == "gso":
            sc.used_gso = not self._gso_disabled
            sc.job = self._submit(ring, sc, ops, total, sc.used_gso,
                                  trace_id)
        sc.backend = backend
        # the Python-side bracket of the send: the op-list build and the
        # hand-over (an io_uring send too, made right here); the job's
        # own send seconds join it at settle.  Filed under the BACKEND's
        # phase so per-pass egress cost is comparable across rungs on
        # one dashboard
        self._close(egress, "egress_io_uring" if backend == "io_uring"
                    else "egress_native", outputs=len(fast),
                    due_outputs=n_due, ops=total)
        return sc

    def _udp_settle(self, ps: _Pass) -> int:
        """The settle half: the job's result in hand, the rungs'
        fallbacks (each a second job, submitted and awaited here), then
        the bookmark and stat accounting."""
        from .. import native
        stream, plan, sc = ps.stream, ps.plan, ps.udp
        ring = stream.rtp_ring
        delay = stream.settings.bucket_delay_ms
        fast = plan.udp
        cohorts = plan.cohorts
        due, units, total, ops_np = sc.due, sc.units, sc.total, sc.ops_np
        trace_id = stream.trace_id
        used_gso = sc.used_gso
        res = sc.res if sc.job is None else self._await(ps, sc.job)
        r = res.result
        if sc.backend == "gso":
            if used_gso and r < 0:          # GSO unsupported/failed
                used_gso = False
                res = self._await(ps, self._submit(
                    ring, sc, native.ops_from_numpy(ops_np), total, False,
                    trace_id))
                r = res.result
                if r >= 0 and not self._gso_disabled:
                    self._gso_strikes += 1  # GSO failed, plain path works
                    if self._gso_strikes >= 2:
                        self._gso_disabled = True
            elif used_gso and self._gso_strikes:
                self._gso_strikes = 0
            if sc.uring_failed and r >= 0:
                # io_uring failed outright but a lower rung delivered:
                # a backend strike, not a destination failure
                self._note_uring_failure(sc.uring_err)
        hard = False
        if r < 0:
            # hard error with nothing sent: fall through to accounting as
            # r=0/hard so the poisoned output is skipped, not retried
            # forever (the scalar oracle advances on WriteResult.ERROR too)
            hard = True
            r = 0
        elif r < total:
            # the JOB's errno: the sender thread's, carried in its result
            hard = res.err not in (
                0, errno_mod.EAGAIN, errno_mod.EWOULDBLOCK)
            if hard and used_gso:
                # A partial GSO pass stopped on a hard errno.  On a kernel
                # without UDP_SEGMENT a single-segment super succeeds while
                # a later multi-segment one fails EINVAL — that is a GSO
                # failure, not a poisoned destination (ADVICE r2 medium).
                # Retry the unsent remainder through plain sendmmsg before
                # condemning anyone; count the strike either way.
                self._gso_strikes += 1
                if self._gso_strikes >= 2:
                    self._gso_disabled = True
                rem = ops_np[r:]            # row slice stays C-contiguous
                res2 = self._await(ps, self._submit(
                    ring, sc, native.ops_from_numpy(rem), total - r, False,
                    trace_id))
                if res2.result >= 0:
                    res = res2
                    r += res2.result
                    hard = r < total and res2.err not in (
                        0, errno_mod.EAGAIN, errno_mod.EWOULDBLOCK)
        # the packets were ON THE WIRE at the job's done stamp: latency
        # stamps below use that instant, not this thread's clock now
        # (which would bill the other streams' plans and our own
        # bookkeeping to the network)
        wire_ns = res.done_ns
        # bookmark/stat accounting by cohort, exact under partial
        # (EAGAIN) sends: a partial send splits the cohort at its
        # boundary, and a straggler is a cohort of one
        account = self._open("engine.account")
        # one entry per piece — outputs of a cohort that got the same
        # packets: its delivered slots, and its size as their weight
        lat_slots: list[np.ndarray] = []    # → both wire histograms
        lat_w: list[int] = []
        hold_runs: list[tuple] = []         # (slots, bucket) of each piece
        # audience aggregates (obs/audience.py): assembled per piece,
        # applied as ONE vectorized column pass below; disabled = one
        # attribute check
        aud = obs.AUDIENCE
        ablk = stream.audience if aud.enabled else None
        a_parts: list[tuple] = []   # (rows, k, bytes, first, last, piece)

        def settle(co, b_idx, cols, k, mark, pids, slots, lens) -> None:
            """``cols`` each got the unit's first ``k`` packets and now
            stand at ``mark``: write that back to the outputs (the
            truth) and file them as one cohort."""
            if not k:
                return _file(co, fast, cols, mark)
            nbytes = int(lens[:k].sum())
            for c in cols.tolist():
                out = fast[c]
                out._bookmark = mark
                out.packets_sent += k
                out.bytes_sent += nbytes
                out.payload_octets += nbytes - 12 * k
            self._pass_wire_bytes += nbytes * len(cols)
            if ablk is not None:
                rows = plan.aud_rows[cols]
                rows = rows[rows >= 0]
                if rows.size:
                    a_parts.append((rows, k, nbytes, int(pids[0]),
                                    int(pids[k - 1]), len(lat_slots)))
            lat_slots.append(slots[:k])
            lat_w.append(len(cols))
            hold_runs.append((k, b_idx))
            _join(co, mark, cols)

        for bi, mark, _hi in due:           # every member gets a new mark
            del cohorts[bi][mark]
        taken = 0
        hard_consumed = False
        for bi, cols, hi_abs, pids, slots, lens in units:
            co, b_idx = cohorts[bi], int(plan.b_arr[bi])
            n, m = len(cols), len(pids)
            if m == 0:                      # runt-only span: skip past it
                settle(co, b_idx, cols, 0, hi_abs, pids, slots, lens)
                continue
            got = min(max(r - taken, 0), n * m)
            taken += n * m
            full, k = divmod(got, m)        # whole outputs; the next one's
            if full:
                settle(co, b_idx, cols[:full], m, hi_abs, pids, slots, lens)
            if full == n:
                continue
            edge = fast[int(cols[full])]    # where the send stopped
            if hard and not hard_consumed:
                # the datagram at the boundary failed hard (unroutable/
                # rejected destination): drop this output's remainder for
                # the pass so it cannot starve the outputs behind it
                hard_consumed = True
                mark = hi_abs
                self.send_errors += m - k
            else:
                mark = int(pids[k])         # first unsent packet
                edge.stalls += 1
                stream.stats.stalls += 1
            settle(co, b_idx, cols[full:full + 1], k, mark, pids, slots,
                   lens)
            rest = cols[full + 1:]
            if len(rest):                   # nothing of theirs went out
                for c in rest.tolist():
                    fast[c].stalls += 1
                stream.stats.stalls += len(rest)
                settle(co, b_idx, rest, 0, int(pids[0]), pids, slots, lens)
        if lat_slots:
            # one vectorized observe per pass: perf_counter stamp at
            # push_rtp minus the send-return instant, per delivered
            # (packet, piece) pair, weighted by the piece's outputs
            sizes = [len(x) for x in lat_slots]
            all_slots = (lat_slots[0] if len(lat_slots) == 1
                         else np.concatenate(lat_slots))
            lat_s = (wire_ns - ring.arrival_ns[all_slots]) / 1e9
            weights = np.repeat(np.asarray(lat_w, np.int64), sizes)
            if a_parts:
                offs = np.cumsum([0] + sizes).tolist()
                cnt = [p[0].size for p in a_parts]
                aud.note_pass(
                    ablk, np.concatenate([p[0] for p in a_parts]),
                    *(np.repeat(np.asarray([p[j] for p in a_parts],
                                           np.int64), cnt)
                      for j in (1, 2, 3, 4)),
                    np.concatenate([
                        np.tile(lat_s[offs[p[5]]:offs[p[5]] + p[1]],
                                p[0].size) for p in a_parts]),
                    wire_ns)
            if obs.LEDGER.enabled:
                obs.LEDGER.note_queue_age(float(lat_s.max()), int(r))
            # per-session attribution (top-by-p99 in command=top)
            PROFILER.account_latency(stream.session_path, lat_s, weights)
            # last: it takes the hold off lat_s in place
            obs.observe_wire("native", lat_s, hold_runs, delay, weights)
        self.native_sent += r
        TRACER.close(account)
        return int(r)

    # -- interleaved-TCP fast path (ISSUE 14) ------------------------------
    def stream_backend(self) -> str:
        """The rung serving this engine's STREAM-socket writes.  No GSO
        tier exists for TCP, so the ladder is io_uring → writev →
        buffered (the per-send batch-header rung a forced ``scalar``
        backend keeps)."""
        if self.egress_backend == "scalar":
            return "buffered"
        if (self.egress_backend in ("auto", "io_uring")
                and not self._uring_stream_disabled
                and self.uring is not None
                and getattr(self.uring, "active", False)):
            return "io_uring"
        return "writev"

    def _note_uring_stream_failure(self, err: int) -> None:
        """Same strike shape as the datagram rung: two whole-batch ring
        failures while writev still delivers retire io_uring for this
        engine's stream sends with ONE structured fallback event."""
        if self._uring_stream_disabled:
            return
        self._uring_stream_strikes += 1
        if self._uring_stream_strikes < 2:
            return
        self._uring_stream_disabled = True
        reason = (errno_mod.errorcode.get(err, str(err)) if err
                  else "unknown")
        obs.EGRESS_BACKEND_FALLBACKS.inc(backend="io_uring")
        obs.EVENTS.emit("egress.backend_fallback", level="warn",
                        backend="io_uring", fallback="writev",
                        reason=reason)

    def _render_framed(self, ring, slot: int, out, chan: int) -> bytes:
        """One framed interleaved packet rendered host-side (the partial-
        write completion path): ``$ chan len16 | rewritten RTP`` —
        byte-identical to the C renderer by the same affine formulas."""
        from ..protocol import rtp
        ln = int(ring.length[slot])
        pkt = ring.data[slot, :ln].tobytes()
        rw = out.rewrite
        body = rtp.rewrite_header(
            pkt, seq=rw.map_seq(rtp.peek_seq(pkt)),
            timestamp=rw.map_ts(rtp.peek_timestamp(pkt)), ssrc=rw.ssrc)
        return b"$" + bytes((chan & 0xFF,)) + ln.to_bytes(2, "big") + body

    def _tcp_scatter(self, stream: RelayStream, tcp, col0: int, start,
                     ids, idx, arrivals, valid, lengths, seq_off, ts_off,
                     ssrc, chan, now_ms: int) -> int:
        """Framed interleave egress: per connection, ONE native call
        renders ``$``-framing + rewritten RTP headers in C and writes
        the whole eligible span through writev (or one io_uring
        submission) — no per-packet Python, payload bytes never copied
        per-subscriber on the host.

        Flow control maps onto the ladder, never onto the pump: a short
        write's torn packet is completed through the asyncio transport
        (which then owns ordering for the stalled tail), EAGAIN holds
        the bookmark (replay next pass), and a reader stalled so far
        behind that the backlog crosses half the ring is shed WHOLE AUs
        forward to the newest keyframe — frame-rate degradation, not a
        blocked wake."""
        from .. import native
        ring = stream.rtp_ring
        delay = stream.settings.bucket_delay_ms
        egress = self._open("engine.egress")
        backend = self.stream_backend()
        sent = 0
        due = 0
        sent_slots: list[np.ndarray] = []
        hold_runs: list[tuple] = []         # (deliveries, bucket) of each
        # audience aggregates — same ONE-vectorized-pass discipline as
        # the UDP scatter (obs/audience.py)
        aud = obs.AUDIENCE
        ablk = stream.audience if aud.enabled else None
        a_rows: list[int] = []
        a_pkts: list[int] = []
        a_byts: list[int] = []
        a_first: list[int] = []
        a_last: list[int] = []
        a_slots: list[np.ndarray] = []
        for j, (out, b_idx) in enumerate(tcp):
            col = col0 + j
            # deep-backlog shed BEFORE building the span: a reader this
            # far behind gets whole AUs dropped (resume at the newest
            # keyframe) instead of a doomed mega-writev
            behind = ring.head - out._bookmark
            if behind > ring.capacity // 2:
                kf = stream.keyframe_id
                if kf is None or kf <= out._bookmark:
                    kf = ring.head - ring.capacity // 4
                shed = int(kf - out._bookmark)
                if shed > 0:
                    out._bookmark = int(kf)
                    out.stalls += 1
                    stream.stats.stalls += 1
                    obs.TCP_EGRESS_BACKPRESSURE_SHEDS.inc(
                        shed, backend=backend)
            lo = max(out._bookmark - start, 0)
            hi = int(np.searchsorted(arrivals, now_ms - b_idx * delay,
                                     side="right"))
            if hi <= lo:
                continue
            due += 1
            sel = valid[lo:hi]
            pids = ids[lo:hi][sel]
            slots = np.ascontiguousarray(idx[lo:hi][sel])
            lens = lengths[lo:hi][sel]
            if len(pids) == 0:
                out._bookmark = start + hi   # runt-only span: skip past it
                continue
            ch = int(chan[0, col]) & 0xFF
            args = (out.stream_fd, ring.data, ring.length,
                    int(seq_off[0, col]), int(ts_off[0, col]),
                    int(ssrc[0, col]), ch, slots)
            used = backend
            r, partial = -1, 0
            if backend == "io_uring":
                r, partial = self.uring.stream_send(*args)
                if r < 0 and native.last_send_errno() not in (
                        errno_mod.EAGAIN, errno_mod.EWOULDBLOCK):
                    uring_err = native.last_send_errno()
                    used = "writev"
                    r, partial = native.stream_send(*args)
                    if r >= 0:
                        self._note_uring_stream_failure(uring_err)
            else:
                r, partial = native.stream_send(*args)
            if r < 0:
                err = native.last_send_errno()
                if err in (errno_mod.EAGAIN, errno_mod.EWOULDBLOCK):
                    out.stalls += 1           # replay from bookmark
                    stream.stats.stalls += 1
                else:
                    # hard connection error: ERROR semantics — skip the
                    # span so a dead socket cannot starve the pass
                    out._bookmark = start + hi
                    self.send_errors += len(pids)
                continue
            k = int(r)
            nbytes = int(lens[:k].sum()) if k else 0
            dead = False
            if partial > 0 and k < len(pids):
                # the k-th packet is torn mid-frame on the wire: its
                # remainder MUST be the connection's next bytes.  Hand
                # it to the asyncio transport, which owns ordering for
                # everything queued after (RTSP replies, RTCP) until
                # the buffer drains and the fast path re-engages.
                framed = self._render_framed(ring, int(slots[k]), out, ch)
                if out.push_tail(framed[partial:]):
                    nbytes += int(lens[k])
                    k += 1
                else:
                    # transport died mid-pass: skip the span (ERROR
                    # semantics) — it must NOT also be rescheduled as a
                    # stall, or the torn packet would be re-sent in
                    # full on a socket that already carries its prefix
                    dead = True
                    out._bookmark = start + hi
                    self.send_errors += len(pids) - k
            if dead:
                pass                        # span skipped above
            elif k == len(pids):
                out._bookmark = start + hi
            else:
                out._bookmark = int(pids[k])  # first unsent packet
                out.stalls += 1
                stream.stats.stalls += 1
            if k:
                out.packets_sent += k
                out.bytes_sent += nbytes
                out.payload_octets += nbytes - 12 * k
                self._pass_wire_bytes += nbytes
                sent += k
                sent_slots.append(slots[:k])
                hold_runs.append((k, b_idx))
                obs.TCP_EGRESS_PACKETS.inc(k, backend=used)
                obs.TCP_EGRESS_BYTES.inc(nbytes + 4 * k, backend=used)
                if ablk is not None:
                    row = getattr(out, "audience_row", -1)
                    if row >= 0:
                        a_rows.append(row)
                        a_pkts.append(k)
                        a_byts.append(nbytes)
                        a_first.append(int(pids[0]))
                        a_last.append(int(pids[k - 1]))
                        a_slots.append(slots[:k])
        self._pass_walked += len(tcp)
        self._pass_due += due
        wire_ns = self._close(
            egress, "egress_io_uring" if backend == "io_uring"
            else "egress_native", outputs=len(tcp), due_outputs=due,
            sent=sent)
        account = self._open("engine.account")
        if a_rows:
            a_cat = (a_slots[0] if len(a_slots) == 1
                     else np.concatenate(a_slots))
            aud.note_pass(ablk, a_rows, a_pkts, a_byts, a_first, a_last,
                          (wire_ns - ring.arrival_ns[a_cat]) / 1e9,
                          wire_ns)
        if sent_slots:
            all_slots = (sent_slots[0] if len(sent_slots) == 1
                         else np.concatenate(sent_slots))
            lat_s = (wire_ns - ring.arrival_ns[all_slots]) / 1e9
            if obs.LEDGER.enabled:
                obs.LEDGER.note_queue_age(float(lat_s.max()), lat_s.size)
            PROFILER.account_latency(stream.session_path, lat_s)
            obs.observe_wire("native", lat_s, hold_runs, delay)
        self.native_sent += sent
        TRACER.close(account)
        return sent

    # -- batch-header path (TCP/meta/thinned outputs) ----------------------
    def _batch_header_step(self, stream: RelayStream, flat,
                           now_ms: int) -> int:
        ring = stream.rtp_ring
        starts = [o._bookmark for o, _ in flat if o._bookmark is not None]
        if not starts:
            return 0
        start = min(starts)
        ids, data, lengths, _flags = ring.window_arrays(start, ring.head - start)
        if len(ids) == 0:
            return 0
        stage = self._open("engine.ring_sync")
        idx = ids % ring.capacity
        n = len(ids)
        # pow2-pad the window axis (the ONE bucket-shape rounding rule):
        # relay_batch_step re-traces per input shape, and a raw window
        # length means every distinct backlog size pays a full
        # recompile — the VOD catch-up path surfaced this as a compile
        # storm (each ~0.7 s compile delayed the pump, which grew the
        # next window, which was a NEW shape...).  Padding rows carry
        # length 0, so the device marks them invalid and the per-output
        # walk below never reaches them (j < n by construction).
        p_pad = _pow2(n, 16)
        prefix = np.zeros((p_pad, self.prefix_width), np.uint8)
        prefix[:n] = data[:, :self.prefix_width]
        lens_p = np.zeros(p_pad, np.int32)
        lens_p[:n] = lengths
        age = np.zeros(p_pad, np.int32)
        age[:n] = (now_ms - ring.arrival[idx]).astype(np.int32)
        state = fanout_ops.pack_output_state([o for o, _ in flat])
        buckets = np.array([b for _, b in flat], dtype=np.int32)
        self._close(stage, "h2d", engine="batch")

        # relay_batch_step re-traces per (window, outputs) shape: a
        # bracket that held the build files no phase
        tok = self._open("engine.params", outputs=len(flat))
        built0 = builds()
        res = fanout_ops.relay_batch_step(
            prefix, lens_p, age, state, buckets,
            np.int32(stream.settings.bucket_delay_ms))
        t_d2h = time.perf_counter_ns()
        headers = np.asarray(res["headers"])     # blocks: the D2H wait
        end = TRACER.close(tok)
        if self._profiled and tok is not None and builds() == built0:
            self._phase_add("device_step", t_d2h - tok.t0, engine="batch")
            self._phase_add("d2h", end - t_d2h, engine="batch")
        # the whole PADDED window's prefixes+metadata crossed to the
        # device and the [S, P_pad, 12] header block crossed back; only
        # the n real rows count as rendered headers (padding rows are
        # never read by the walk below)
        obs.TPU_H2D_BYTES.inc(prefix.nbytes + lens_p.nbytes + age.nbytes
                              + np.asarray(state).nbytes)
        obs.TPU_D2H_BYTES.inc(headers.nbytes)
        obs.TPU_HEADERS_RENDERED.inc(headers.shape[0] * n)

        egress = self._open("engine.egress")
        sent = 0
        due = 0
        lat_ns: list[int] = []
        hold_runs: list[tuple] = []         # (deliveries, bucket) per output
        delay = stream.settings.bucket_delay_ms
        # audience aggregates — assembled in the existing walk, ONE
        # vectorized column pass at the bottom (obs/audience.py)
        aud = obs.AUDIENCE
        ablk = stream.audience if aud.enabled else None
        a_rows: list[int] = []
        a_pkts: list[int] = []
        a_byts: list[int] = []
        a_first: list[int] = []
        a_last: list[int] = []
        a_lat: list[int] = []
        for s, (out, b_idx) in enumerate(flat):
            pid = out._bookmark
            if pid is None:
                continue
            deadline = now_ms - b_idx * delay
            tcp_ok = tcp_bytes = 0      # buffered-rung interleave counts
            o_row = (getattr(out, "audience_row", -1)
                     if ablk is not None else -1)
            o_sent = o_byts = 0
            o_first = o_last = -1
            while pid < ring.head:
                j = pid - start
                if j < 0:
                    break
                slot = ring.slot(pid)
                # ordering mirrors the oracle exactly: eligibility first
                # (break holds the bookmark), runt-skip second (advance)
                if int(ring.arrival[slot]) > deadline:
                    break
                if pid == out._bookmark:
                    due += 1                # its first packet is past hold
                if ring.length[slot] < 12:
                    pid += 1
                    continue
                if not out.thinning.admit(int(ring.flags[slot])):
                    if out.rewrite.base_src_seq >= 0:
                        out.thinning.withhold(
                            out.rewrite.map_seq(int(ring.seq[slot])))
                    pid += 1
                    continue
                payload = ring.data[slot, 12:ring.length[slot]]
                wr = out.send_rewritten(headers[s, j].tobytes(),
                                        payload.tobytes())
                if wr is WriteResult.WOULD_BLOCK:
                    out.stalls += 1
                    stream.stats.stalls += 1
                    break
                pid += 1
                if wr is WriteResult.OK:
                    out.packets_sent += 1
                    out.bytes_sent += 12 + len(payload)
                    out.payload_octets += len(payload)
                    self._pass_wire_bytes += 12 + len(payload)
                    sent += 1
                    tcp_ok += 1
                    tcp_bytes += 16 + len(payload)
                    stamp = int(ring.arrival_ns[slot])
                    lat_ns.append(stamp)
                    if o_row >= 0:
                        o_sent += 1
                        o_byts += 12 + len(payload)
                        if o_first < 0:
                            o_first = pid - 1
                        o_last = pid - 1
                        a_lat.append(stamp)
            out._bookmark = pid
            if tcp_ok:
                hold_runs.append((tcp_ok, b_idx))
            if o_sent:
                a_rows.append(o_row)
                a_pkts.append(o_sent)
                a_byts.append(o_byts)
                a_first.append(o_first)
                a_last.append(o_last)
            if tcp_ok and getattr(out, "interleave_chan", None) is not None:
                # interleaved sends served from the per-session rung —
                # counted so the tcp_egress families are an honest total
                # across the whole ladder, engine rungs AND fallback
                obs.TCP_EGRESS_PACKETS.inc(tcp_ok, backend="buffered")
                obs.TCP_EGRESS_BYTES.inc(tcp_bytes, backend="buffered")
        self._pass_walked += len(flat)
        self._pass_due += due
        now_ns = TRACER.close(egress, outputs=len(flat), due_outputs=due,
                              sent=sent)
        if lat_ns:
            lat_s = (now_ns - np.asarray(lat_ns, dtype=np.int64)) / 1e9
            if a_rows:
                aud.note_pass(
                    ablk, a_rows, a_pkts, a_byts, a_first, a_last,
                    (now_ns - np.asarray(a_lat, np.int64)) / 1e9,
                    now_ns)
            if obs.LEDGER.enabled:
                obs.LEDGER.note_queue_age(float(lat_s.max()), lat_s.size)
            PROFILER.account_latency(stream.session_path, lat_s)
            obs.observe_wire("batch", lat_s, hold_runs, delay)
        return sent
