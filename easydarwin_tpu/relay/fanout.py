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


class TpuFanoutEngine:
    """Batched fan-out for one stream.  Stateless between steps apart from
    jit caches; all mutable relay state stays in the stream/outputs.

    Two egress paths per step:

    * **native fast path** — outputs that expose ``native_addr`` (the
      server's shared-UDP-pair sinks), carry no meta-info wrap and whose
      thinning filter is pass-through.  The affine rewrite params come
      from the device step (``ops.fanout.relay_affine_step_window`` —
      recomputed only when membership/rebase state changes, since the
      params are independent of packet content) and the wire writes go
      through ``native.fanout_send_multi`` (sendmmsg/UDP-GSO scatter):
      no per-packet Python, no per-subscriber payload copies.  This is
      the bench pipeline (``bench.py``) running inside the live server —
      VERDICT r1 item 1.
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
        #: config.tcp_engine_enabled — off keeps interleaved outputs on
        #: the per-session batch-header rung (the bench baseline)
        self.tcp_fast_enabled = True
        self._params_key = None
        self._params = None           # ([1,S] seq_off, ts_off, ssrc, chan)
        self._dests_key = None
        self._dests = None
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
        return (native_ok and out.bookmark is not None
                and getattr(out, "native_addr", None) is not None
                and out.meta_field_ids is None
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
        return (self.tcp_fast_enabled
                and _native_mod() is not None
                and self.egress_backend != "scalar"
                and out.bookmark is not None
                and getattr(out, "interleave_chan", None) is not None
                and getattr(out, "stream_fd", -1) >= 0
                and out.meta_field_ids is None
                and out.thinning.passthrough()
                and out.engine_writable())

    def fast_from_flat(self, flat) -> list:
        """Canonical fast-list order over one output scan: every
        UDP-fast output first, then every TCP-fast output.  BOTH the
        engine and the megabatch scheduler build ``params_key`` and the
        device state matrix in this order, so a scheduler-staged pass
        lands on exactly the columns the engine will consume."""
        ok = self._native_ok()
        udp = [o for o, _ in flat if self._fast_eligible(o, ok)]
        tcp = [o for o, _ in flat if self._tcp_eligible(o, ok)]
        return udp + tcp

    def fast_outputs(self, stream: RelayStream) -> list:
        """This stream's native-fast outputs in fast-list order (the
        order ``params_key`` and the dest table are built in)."""
        return self.fast_from_flat(self._flat_outputs(stream))

    def _flat_outputs(self, stream: RelayStream):
        flat: list[tuple[RelayOutput, int]] = []
        for b_idx, bucket in enumerate(stream.buckets):
            for out in bucket:
                flat.append((out, b_idx))
        return flat

    def _prime(self, stream: RelayStream, flat, now_ms: int) -> None:
        """New-output placement + seq/ts rebase priming.

        The scalar oracle latches the rebase origin exactly once, inside the
        first ``write_rtp`` *attempt* (``RewriteState.base_src_seq < 0``
        check — even a WOULD_BLOCK'd attempt latches).  Mirror that: latch
        only if unlatched, from the first ring packet this output would
        attempt this pass (bookmark advanced past runts, and only if that
        packet is bucket-eligible now)."""
        ring = stream.rtp_ring
        delay = stream.settings.bucket_delay_ms
        for out, b_idx in flat:
            if out.bookmark is None:
                out.bookmark = stream.first_packet_for_new_output(now_ms)
            if out.bookmark is not None and out.bookmark < ring.tail:
                out.bookmark = ring.tail
            if out.rewrite.base_src_seq >= 0 or out.bookmark is None:
                continue
            pid = out.bookmark
            while pid < ring.head and ring.length[ring.slot(pid)] < 12:
                pid += 1               # runts are skipped, never latched
            if pid >= ring.head:
                continue
            s = ring.slot(pid)
            if now_ms - int(ring.arrival[s]) >= b_idx * delay:
                out.rewrite.base_src_seq = int(ring.seq[s])
                out.rewrite.base_src_ts = int(ring.timestamp[s])

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
        """Open one child span of ``engine.step`` (``obs.trace``)."""
        return TRACER.open(name, "tpu", **self._span_args, **args)

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
        self._span_args = ({} if stream.trace_id is None
                           else {"trace_id": stream.trace_id})
        step_span = self._open("engine.step")
        t0 = t0_of(step_span)
        ring = stream.rtp_ring
        flat = self._flat_outputs(stream)
        if not flat or len(ring) == 0:
            TRACER.close(step_span, outputs=len(flat), sent=0)
            return 0
        profiled = self._profiled = PROFILER.enabled
        self._pass_phases = {}
        self._pass_wire_bytes = 0
        self._pass_walked = self._pass_due = 0
        tok = self._open("engine.prime")
        self._prime(stream, flat, now_ms)
        TRACER.close(tok)
        fast: list[tuple[RelayOutput, int]] = []
        tcp: list[tuple[RelayOutput, int]] = []
        slow: list[tuple[RelayOutput, int]] = []
        native_ok = self._native_ok()
        for out, b_idx in flat:
            if self._fast_eligible(out, native_ok):
                fast.append((out, b_idx))
            elif self._tcp_eligible(out, native_ok):
                tcp.append((out, b_idx))
            else:
                slow.append((out, b_idx))
        sent = 0
        if fast or tcp:
            sent += self._native_step(stream, fast, tcp, now_ms)
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
            engines = [e for e, ran in (("native", bool(fast) or bool(tcp)),
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
        dur = TRACER.close(step_span, sent=sent, outputs=len(flat),
                           due_outputs=self._pass_due) - t0
        obs.TPU_PASS_SECONDS.observe(dur / 1e9, stage="engine_step")
        obs.TPU_PASSES.inc()
        obs.ENGINE_OUTPUTS_WALKED.inc(self._pass_walked)
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
    def _dests_for(self, fast):
        from .. import native
        key = tuple(o.native_addr for o, _ in fast)
        if key != self._dests_key:
            self._dests = native.make_dests(list(key))
            self._dests_key = key
        return self._dests

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

    def _device_params(self, fast, ring, now_ms: int):
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
        key = params_key([o for o, _ in fast])
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
        state[:S] = np.asarray(
            fanout_ops.pack_output_state([o for o, _ in fast]))
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

    def _native_step(self, stream: RelayStream, fast, tcp,
                     now_ms: int) -> int:
        """Send every eligible (packet, output) pair through the native
        senders — ONE sendmmsg/GSO scatter for the UDP set, one framed
        writev/io_uring batch per interleaved-TCP connection — all from
        ONE device param pass (the affine rewrite plus the interleave
        channel column ride the same query)."""
        ring = stream.rtp_ring
        # extracting the host window view is part of staging it: one
        # h2d bracket over the view and the device-ring append
        tok = self._open("engine.ring_sync")
        built0 = builds()
        combined = fast + tcp
        start = min(o.bookmark for o, _ in combined)
        ids, lengths, _flags = ring.window_meta(start, ring.head - start)
        if len(ids) == 0:
            TRACER.close(tok)
            return 0
        start = int(ids[0])                 # window_meta clamps to tail
        idx = (ids % ring.capacity).astype(np.int32)
        arrivals = ring.arrival[idx]        # nondecreasing (ingest clock)
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
        seq_off, ts_off, ssrc, chan = self._device_params(combined, ring,
                                                          now_ms)
        sent = 0
        if fast:
            sent += self._udp_scatter(stream, fast, start, ids, idx,
                                      arrivals, valid, lengths,
                                      seq_off, ts_off, ssrc, now_ms)
        if tcp:
            sent += self._tcp_scatter(stream, tcp, len(fast), start, ids,
                                      idx, arrivals, valid, lengths,
                                      seq_off, ts_off, ssrc, chan, now_ms)
        self.native_passes += 1
        return sent

    def _udp_scatter(self, stream: RelayStream, fast, start, ids, idx,
                     arrivals, valid, lengths, seq_off, ts_off, ssrc,
                     now_ms: int) -> int:
        from .. import native
        ring = stream.rtp_ring
        delay = stream.settings.bucket_delay_ms
        # egress_native starts HERE: everything from params-in-hand to
        # wire — per-output span selection, the scatter op list, and the
        # native sendmmsg/GSO calls — is the egress stage (leaving the
        # op-list numpy unphased put Σ(phases) ~15% under the pass total)
        egress = self._open("engine.egress")
        # per-output eligible spans (numpy slices, no per-op Python)
        per_out = []                        # (out, hi, pids, slots, lens)
        total = 0
        due = 0                             # outputs with a packet to send
        for s, (out, b_idx) in enumerate(fast):
            lo = max(out.bookmark - start, 0)
            hi = int(np.searchsorted(arrivals, now_ms - b_idx * delay,
                                     side="right"))
            if hi <= lo:
                per_out.append((out, None, None, None, None))
                continue
            due += 1
            sel = valid[lo:hi]
            per_out.append((out, hi, ids[lo:hi][sel], idx[lo:hi][sel],
                            lengths[lo:hi][sel]))
            total += int(sel.sum())
        self._pass_walked += len(fast)
        self._pass_due += due
        if total == 0:
            for out, hi, _p, _s, _l in per_out:
                if hi is not None:          # runt-only span: skip past it
                    out.bookmark = start + hi
            TRACER.close(egress, outputs=len(fast), due_outputs=due,
                         sent=0)
            return 0
        ops_np = np.empty((total, 2), np.int32)
        pos = 0
        counts = []
        for s, (out, hi, pids, slots, lens) in enumerate(per_out):
            n = 0 if pids is None else len(pids)
            counts.append(n)
            if n:
                ops_np[pos:pos + n, 0] = slots
                ops_np[pos:pos + n, 1] = s
                pos += n
        dests = self._dests_for(fast)
        ops = native.ops_from_numpy(ops_np)
        trace_id = stream.trace_id
        backend = self.effective_backend()
        used_backend = backend
        used_gso = False
        uring_failed = False
        uring_err = 0
        r = -1
        if backend == "io_uring":
            # one linked-SQE submission per chain instead of one
            # sendmmsg slot per run — EAGAIN/hard semantics identical,
            # so the bookmark accounting below is backend-blind
            r = self.uring.send_multi(
                ring.data, ring.length, seq_off, ts_off, ssrc, dests,
                ops, total, trace_id=trace_id)
            if r < 0:
                # whole-batch ring failure with nothing sent: serve this
                # pass from the GSO rung; strike io_uring only if a
                # lower rung proves the destinations are fine
                uring_failed = True
                uring_err = native.last_send_errno() or -r
                backend = used_backend = "gso"
        if backend == "scalar":
            # forced per-datagram sendto baseline (egress_backend=scalar)
            r = native.fanout_send_multi(
                self.egress_fd, ring.data, ring.length, seq_off, ts_off,
                ssrc, dests, ops, total, use_gso=2, trace_id=trace_id)
        elif backend == "gso":
            used_gso = not self._gso_disabled
            r = -1
            if used_gso:
                r = native.fanout_send_multi(
                    self.egress_fd, ring.data, ring.length, seq_off,
                    ts_off, ssrc, dests, ops, total, use_gso=True,
                    trace_id=trace_id)
            if r < 0:                       # GSO off/unsupported/failed
                used_gso = False
                r = native.fanout_send_multi(
                    self.egress_fd, ring.data, ring.length, seq_off,
                    ts_off, ssrc, dests, ops, total, use_gso=False,
                    trace_id=trace_id)
                if r >= 0 and not self._gso_disabled:
                    self._gso_strikes += 1  # GSO failed, plain path works
                    if self._gso_strikes >= 2:
                        self._gso_disabled = True
            elif self._gso_strikes:
                self._gso_strikes = 0
            if uring_failed and r >= 0:
                # io_uring failed outright but a lower rung delivered:
                # a backend strike, not a destination failure
                self._note_uring_failure(uring_err)
        hard = False
        if r < 0:
            # hard error with nothing sent: fall through to accounting as
            # r=0/hard so the poisoned output is skipped, not retried
            # forever (the scalar oracle advances on WriteResult.ERROR too)
            hard = True
            r = 0
        elif r < total:
            hard = native.last_send_errno() not in (
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
                r2 = native.fanout_send_multi(
                    self.egress_fd, ring.data, ring.length, seq_off,
                    ts_off, ssrc, dests, native.ops_from_numpy(rem),
                    total - r, use_gso=False, trace_id=trace_id)
                if r2 >= 0:
                    r += r2
                    hard = r < total and native.last_send_errno() not in (
                        0, errno_mod.EAGAIN, errno_mod.EWOULDBLOCK)
        # the packets are ON THE WIRE here: latency stamps below use this
        # instant, not a fresh read after the accounting walk (which
        # would bill our own bookkeeping to the network)
        # every native send this pass (op-list build, backend try,
        # lower-rung fallback, GSO remainder retry) — the Python-side
        # bracket; csrc's ed_stats.send_ns carries the in-library
        # half.  Filed under the BACKEND's phase so per-pass egress
        # cost is comparable across rungs on one dashboard
        wire_ns = self._close(
            egress, "egress_io_uring" if used_backend == "io_uring"
            else "egress_native", outputs=len(fast), due_outputs=due,
            sent=int(r))
        # bookmark/stat accounting, exact under partial (EAGAIN) sends
        account = self._open("engine.account")
        taken = 0
        hard_consumed = False
        sent_slots: list[np.ndarray] = []   # → ingest→wire histogram
        hold_runs: list[tuple] = []         # (deliveries, bucket) of each
        # audience aggregates (obs/audience.py): assembled inside this
        # existing accounting walk, applied as ONE vectorized column
        # pass below; disabled = one attribute check
        aud = obs.AUDIENCE
        ablk = stream.audience if aud.enabled else None
        a_rows: list[int] = []
        a_pkts: list[int] = []
        a_byts: list[int] = []
        a_first: list[int] = []
        a_last: list[int] = []
        a_slots: list[np.ndarray] = []
        for (out, hi, pids, slots, lens), n, (_o, b_idx) in zip(
                per_out, counts, fast):
            k = min(max(r - taken, 0), n)
            taken += n
            if n == 0:
                if hi is not None:
                    out.bookmark = start + hi
                continue
            if k == n:
                out.bookmark = start + hi
            elif hard and not hard_consumed:
                # the datagram at the boundary failed hard (unroutable/
                # rejected destination): drop this output's remainder for
                # the pass so it cannot starve the outputs behind it
                hard_consumed = True
                out.bookmark = start + hi
                self.send_errors += n - k
            else:
                out.bookmark = int(pids[k])  # first unsent packet
                out.stalls += 1
                stream.stats.stalls += 1
            if k:
                out.packets_sent += k
                sent_bytes = int(lens[:k].sum())
                out.bytes_sent += sent_bytes
                out.payload_octets += sent_bytes - 12 * k
                self._pass_wire_bytes += sent_bytes
                sent_slots.append(slots[:k])
                hold_runs.append((k, b_idx))
                if ablk is not None:
                    row = getattr(out, "audience_row", -1)
                    if row >= 0:
                        a_rows.append(row)
                        a_pkts.append(k)
                        a_byts.append(sent_bytes)
                        a_first.append(int(pids[0]))
                        a_last.append(int(pids[k - 1]))
                        a_slots.append(slots[:k])
        if a_rows:
            a_cat = (a_slots[0] if len(a_slots) == 1
                     else np.concatenate(a_slots))
            aud.note_pass(ablk, a_rows, a_pkts, a_byts, a_first, a_last,
                          (wire_ns - ring.arrival_ns[a_cat]) / 1e9,
                          wire_ns)
        if sent_slots:
            # one vectorized observe per pass: perf_counter stamp at
            # push_rtp minus the send-return instant, per delivered
            # (packet, subscriber) pair
            all_slots = (sent_slots[0] if len(sent_slots) == 1
                         else np.concatenate(sent_slots))
            lat_s = (wire_ns - ring.arrival_ns[all_slots]) / 1e9
            if obs.LEDGER.enabled:
                obs.LEDGER.note_queue_age(float(lat_s.max()), lat_s.size)
            # per-session attribution (top-by-p99 in command=top)
            PROFILER.account_latency(stream.session_path, lat_s)
            # last: it takes the hold off lat_s in place
            obs.observe_wire("native", lat_s, hold_runs, delay)
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
            behind = ring.head - out.bookmark
            if behind > ring.capacity // 2:
                kf = stream.keyframe_id
                if kf is None or kf <= out.bookmark:
                    kf = ring.head - ring.capacity // 4
                shed = int(kf - out.bookmark)
                if shed > 0:
                    out.bookmark = int(kf)
                    out.stalls += 1
                    stream.stats.stalls += 1
                    obs.TCP_EGRESS_BACKPRESSURE_SHEDS.inc(
                        shed, backend=backend)
            lo = max(out.bookmark - start, 0)
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
                out.bookmark = start + hi   # runt-only span: skip past it
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
                    out.bookmark = start + hi
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
                    out.bookmark = start + hi
                    self.send_errors += len(pids) - k
            if dead:
                pass                        # span skipped above
            elif k == len(pids):
                out.bookmark = start + hi
            else:
                out.bookmark = int(pids[k])  # first unsent packet
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
        starts = [o.bookmark for o, _ in flat if o.bookmark is not None]
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
            pid = out.bookmark
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
                if pid == out.bookmark:
                    due += 1                # its first packet is past hold
                if ring.length[slot] < 12:
                    pid += 1
                    continue
                if not out.thinning.admit(int(ring.flags[slot])):
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
            out.bookmark = pid
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
