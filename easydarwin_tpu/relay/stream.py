"""Per-track relay stream: rings, keyframe index, bucketed fan-out.

``ReflectorStream`` + ``ReflectorSender`` re-designed around absolute-id
rings.  One ``RelayStream`` per SDP media section; each owns an RTP ring and
an RTCP ring (the reference binds a UDP socket *pair* per stream and runs two
senders, ``ReflectorStream.h:87-180``).

Fan-out follows ``ReflectorSender::ReflectPackets`` (``ReflectorStream.cpp:
1024-1135``): outputs live in buckets of ``bucket_size``; bucket *b*'s sends
are delayed ``b × bucket_delay_ms`` to smooth the egress burst; a packet is
eligible for bucket *b* at ``arrival + b·delay ≤ now``.  New outputs
fast-start from the newest keyframe bookmark when the stream is video
(``GetNewestKeyFrameFirstPacket``, cpp:1310-1397) and otherwise from the
newest packet inside the over-buffer window.  Eviction keeps everything any
output still needs (bookmark pinning) up to ``max_age_ms``.
"""

from __future__ import annotations

import random
import time
from dataclasses import dataclass, field

import numpy as np

from .. import obs
from ..protocol import rtcp as rtcp_mod
from ..protocol.sdp import StreamInfo
from ..resilience.inject import INJECTOR
from .output import PlanCell, RelayOutput, WriteResult
from .ring import DEFAULT_CAPACITY, PacketFlags, PacketRing

#: SR origination / upstream-RR cadence (``ReflectorStream.h:341``
#: kRRInterval = 5 s; ``RTPStream.cpp:1300`` SR gen rides the same clock)
SR_INTERVAL_MS = 5000


@dataclass
class StreamSettings:
    """Tunables with the reference's defaults (``ReflectorStream.cpp:56-68``,
    prefs table ``QTSServerPrefs.cpp``)."""

    bucket_size: int = 16             # sBucketSize
    bucket_delay_ms: int = 73         # sBucketDelayInMsec
    overbuffer_ms: int = 10_000       # sOverBufferInMsec
    max_age_ms: int = 20_000          # sMaxPacketAgeMSec
    ring_capacity: int = DEFAULT_CAPACITY
    first_timeout_ms: int = 2_000     # kFirstPacketOffsetMsec-style new-output slack


@dataclass
class StreamStats:
    packets_in: int = 0
    bytes_in: int = 0
    packets_out: int = 0
    stalls: int = 0
    keyframes: int = 0


class RelayStream:
    def __init__(self, info: StreamInfo,
                 settings: StreamSettings | None = None, *,
                 rtp_ring: PacketRing | None = None):
        self.info = info
        self.settings = settings or StreamSettings()
        is_video = info.media_type == "video"
        #: callers with a specialized ring (the VOD pacer's staged
        #: ring) inject it instead of paying for a discarded default
        self.rtp_ring = rtp_ring if rtp_ring is not None else PacketRing(
            self.settings.ring_capacity, is_video=is_video,
            codec=info.codec or None)
        self.rtcp_ring = PacketRing(min(256, self.settings.ring_capacity))
        #: absolute id of the newest keyframe *run head* (video only).
        #: The reference keeps the newest keyframe-first packet
        #: (fKeyFrameStartPacketElementPointer) — which, when a pusher sends
        #: SPS/PPS/IDR as separate packets, lands on the IDR and drops the
        #: parameter sets for late joiners.  We instead pin the first packet
        #: of a consecutive keyframe-classified run (the SPS), so fast-start
        #: always delivers the whole GOP head.
        self.keyframe_id: int | None = None
        self._kf_run_active = False
        self.has_keyframe_update = False     # SetHasVideoKeyFrameUpdate
        #: correlation envelope stamped by the owning RelaySession
        #: (set_trace): the engine reads these when recording spans/events
        self.trace_id: str | None = None
        self.session_path: str | None = None
        self.buckets: list[list[RelayOutput]] = []
        #: the plan epoch, in a cell every output of this stream shares
        #: (``RelayOutput.touch_plan``): the engine's per-stream output
        #: plan (``relay.fanout``) is valid while it has not moved.
        #: Moved by membership (below) and by every write of what the
        #: plan derives from outside the engine's own cohort step.  A
        #: move, and every ingest, also marks the stream in the ready
        #: set of the pump that serves it (``relay.pump``)
        self._plan_cell = PlanCell()
        #: this stream's audience column block (obs/audience.py) — set
        #: by AUDIENCE.register on the first subscriber; None keeps the
        #: egress hooks to one attribute check per pass
        self.audience = None
        #: tier label new subscribers register under (closed
        #: obs.audience.AUDIENCE_TIERS vocabulary); creators of pull/
        #: vod/dvr streams override it
        self.audience_tier = "live"
        #: outputs needing per-pass retransmit sweeps (reliable-UDP); kept
        #: separately so the pump pays nothing when none exist
        self.tickable_outputs: list[RelayOutput] = []
        #: native recvmmsg ingest counters (amortization evidence)
        self.native_ingest_batches = 0
        self.native_ingest_pkts = 0
        self.stats = StreamStats()
        #: upstream RTCP: where receiver reports to the pusher go
        #: (interleaved channel writer or UDP sendto closure); set by the
        #: ingest owner.  ``ReflectorStream.h:341`` kRRInterval behavior.
        self.upstream_rtcp = None
        #: who installed upstream_rtcp (connection identity) — a closed
        #: pusher clears only its own closure, never an adopter's
        self.upstream_rtcp_owner = None
        self.last_upstream_rr_ms = 0
        #: random per-stream reporter identity for upstream RRs — a fixed
        #: constant collides across tracks/sessions at the pusher and could
        #: collide with a media SSRC (ADVICE r2)
        self.reporter_ssrc = random.getrandbits(32)
        #: wall-clock anchor for RTCP NTP fields: latched on first use so
        #: SR timestamps advance on the relay's monotonic clock but sit at
        #: real absolute NTP time (the reference uses wall clock; a
        #: monotonic-only value lands near the 1970 epoch — ADVICE r2)
        self._wall_base: float | None = None
        #: earliest moment any output could need an originated SR — lets
        #: the per-step relay_rtcp call early-return without touching the
        #: output list (it is on the fan-out hot path)
        self._next_sr_due_ms = 0
        #: chaos reorder hold (resilience/inject.py): the one-slot
        #: buffer an armed ingest_reorder fault parks a packet in —
        #: owned by the stream so a held packet dies with it
        self._chaos_hold: list = []
        #: lossy-WAN reliability tier (relay/fec.py): built lazily when
        #: the first FEC-negotiated output lands; ticked from the
        #: engines' shared relay_rtcp tail so the scalar oracle and the
        #: TPU engine emit identical parity bytes
        self.fec = None
        #: reception accounting for those RRs (RFC 3550 A.3)
        self._rr_base_seq: int | None = None
        self._rr_max_seq = 0
        self._rr_cycles = 0
        self._rr_received = 0
        self._rr_prev_expected = 0
        self._rr_prev_received = 0

    # -- ingest ------------------------------------------------------------
    def _note_rtp_ingested(self, pid: int) -> None:
        """Per-packet ingest bookkeeping from ring state: RR reception
        accounting (RFC 3550 A.3) + keyframe-run bookmark.  Shared by the
        Python push path and the native recvmmsg drain."""
        ring = self.rtp_ring
        s = ring.slot(pid)
        n = int(ring.length[s])
        self._plan_cell.mark()
        self.stats.packets_in += 1
        self.stats.bytes_in += n
        if n >= 12:
            seq = int(ring.seq[s])
            if self._rr_base_seq is None:
                self._rr_base_seq = seq
                self._rr_max_seq = seq
            else:
                delta = (seq - self._rr_max_seq) & 0xFFFF
                if delta < 0x8000:              # in-order / small gap
                    if seq < self._rr_max_seq:
                        self._rr_cycles += 1    # wrapped
                    self._rr_max_seq = seq
            self._rr_received += 1
        if int(ring.flags[s]) & PacketFlags.KEYFRAME_FIRST:
            if not self._kf_run_active:
                self.keyframe_id = pid
                self.has_keyframe_update = True
                self.stats.keyframes += 1
                self._kf_run_active = True
        else:
            self._kf_run_active = False

    def push_rtp(self, packet: bytes, now_ms: int) -> int:
        if self._wall_base is None:
            # latch the RTCP wall anchor at first ingest so engines
            # stepping a copied stream state share the exact base
            self._wall_base = time.time() - now_ms / 1000.0
        if INJECTOR.active:
            # chaos gauntlet (resilience/inject.py): seeded drop /
            # adjacent-swap reorder / payload corruption — one attribute
            # check when no plan is armed
            pid = -1
            for pkt in INJECTOR.ingest(packet, self._chaos_hold):
                pid = self.rtp_ring.push(pkt, now_ms)
                if pid >= 0:
                    self._note_rtp_ingested(pid)
            return pid
        pid = self.rtp_ring.push(packet, now_ms)
        if pid >= 0:
            self._note_rtp_ingested(pid)
        return pid

    def drain_rtp_native(self, fd: int, now_ms: int,
                         max_pkts: int = 512) -> int:
        """Batch-drain a pusher's RTP socket straight into the ring
        (recvmmsg, no per-datagram Python callback), then run the same
        per-packet bookkeeping the push path does.  Returns packets
        admitted this call."""
        if self._wall_base is None:
            self._wall_base = time.time() - now_ms / 1000.0
        pre = self.rtp_ring.head
        n = self.rtp_ring.native_drain(fd, now_ms, max_pkts)
        if n > 0 and INJECTOR.active:
            # chaos gauntlet for the recvmmsg path: drops/corruption
            # mutate the just-landed slots in place (a dropped slot
            # becomes a runt nothing ever relays)
            INJECTOR.ingest_ring(self.rtp_ring, pre, self.rtp_ring.head)
        for pid in range(pre, self.rtp_ring.head):
            self._note_rtp_ingested(pid)
        if n > 0:
            self.native_ingest_batches += 1
            self.native_ingest_pkts += n
        return n

    def push_rtcp(self, packet: bytes, now_ms: int) -> int:
        self._plan_cell.mark()
        return self.rtcp_ring.push(packet, now_ms, is_rtcp=True)

    # -- output management -------------------------------------------------
    @property
    def plan_epoch(self) -> int:
        return self._plan_cell.epoch

    def touch_plan(self) -> None:
        self._plan_cell.touch()

    def add_output(self, output: RelayOutput, *,
                   bucket: int | None = None) -> None:
        """Place in the first bucket with a free slot, growing the bucket
        array as needed (``ReflectorStream::AddOutput`` cpp:280-322).
        ``bucket`` pins an explicit index instead (checkpoint restore:
        the delay-stagger tier a subscriber was in is part of its
        serving state, and first-fit would repack over the holes)."""
        self._next_sr_due_ms = 0        # new output: SR due immediately
        if hasattr(output, "tick"):     # reliable-UDP retransmit sweeps
            self.tickable_outputs.append(output)
        if getattr(output, "fec", None) is not None:
            if self.fec is None:
                from .fec import StreamFec
                self.fec = StreamFec(self, output.fec.cfg)
            self.fec.add_output(output)
        if bucket is not None:
            while len(self.buckets) <= bucket:
                self.buckets.append([])
            self.buckets[bucket].append(output)
        else:
            for b in self.buckets:
                if len(b) < self.settings.bucket_size:
                    b.append(output)
                    break
            else:
                self.buckets.append([output])
        output._plan_cell = self._plan_cell
        self.touch_plan()
        obs.AUDIENCE.register(self, output)
        obs.EVENTS.emit("stream.output_add", stream=self.session_path,
                        trace_id=self.trace_id,
                        session_id=getattr(output, "session_id", None),
                        track=self.info.track_id, outputs=self.num_outputs)

    def remove_output(self, output: RelayOutput) -> bool:
        if output in self.tickable_outputs:
            self.tickable_outputs.remove(output)
        if self.fec is not None:
            self.fec.remove_output(output)
        for bucket in self.buckets:
            if output in bucket:
                bucket.remove(output)
                output._plan_cell = None
                self.touch_plan()
                obs.AUDIENCE.unregister(output)
                obs.EVENTS.emit(
                    "stream.output_remove", stream=self.session_path,
                    trace_id=self.trace_id,
                    session_id=getattr(output, "session_id", None),
                    track=self.info.track_id, outputs=self.num_outputs)
                return True
        return False

    @property
    def outputs(self) -> list[RelayOutput]:
        return [o for b in self.buckets for o in b]

    @property
    def num_outputs(self) -> int:
        return sum(len(b) for b in self.buckets)

    # -- new-output placement ---------------------------------------------
    def first_packet_for_new_output(self, now_ms: int) -> int | None:
        """Fast-start resume point for a just-added output."""
        ring = self.rtp_ring
        if len(ring) == 0:
            return None
        if self.keyframe_id is not None and ring.valid(self.keyframe_id):
            # newest keyframe still within the over-buffer window?
            age = now_ms - ring.get_arrival(self.keyframe_id)
            if age <= self.settings.overbuffer_ms:
                return self.keyframe_id
        # else: oldest packet younger than the over-buffer window
        for pid in ring.ids():
            if now_ms - ring.get_arrival(pid) <= self.settings.overbuffer_ms:
                return pid
        return ring.head - 1

    # -- fan-out (CPU oracle) ---------------------------------------------
    def reflect(self, now_ms: int) -> int:
        """One fan-out pass; returns packets written.  Semantics mirror
        ``ReflectPackets``: per-bucket delay stagger, per-output bookmark,
        stop-on-WouldBlock (bookmark holds for replay next pass)."""
        ring = self.rtp_ring
        sent = 0
        bytes_out = 0
        lat_ns: list[int] = []          # ingest stamps of delivered packets
        hold_runs: list[tuple] = []     # (deliveries, bucket) per output
        # audience aggregates (obs/audience.py): per-OUTPUT figures
        # assembled inside the existing walk, applied as ONE vectorized
        # column pass below; disabled costs one attribute check
        aud = obs.AUDIENCE
        ablk = self.audience if aud.enabled else None
        a_rows: list[int] = []
        a_pkts: list[int] = []
        a_byts: list[int] = []
        a_first: list[int] = []
        a_last: list[int] = []
        a_lat: list[int] = []           # stamps, audience rows only
        for b_idx, bucket in enumerate(self.buckets):
            deadline = now_ms - b_idx * self.settings.bucket_delay_ms
            for out in bucket:
                if out.bookmark is None:
                    out.bookmark = self.first_packet_for_new_output(now_ms)
                    if out.bookmark is None:
                        continue
                if out.bookmark < ring.tail:   # evicted from under a stalled output
                    out.bookmark = ring.tail
                pid = out.bookmark
                o_row = (getattr(out, "audience_row", -1)
                         if ablk is not None else -1)
                o_sent = o_byts = 0
                o_first = o_last = -1
                sent0 = sent
                while pid < ring.head:
                    if ring.get_arrival(pid) > deadline:
                        break
                    data = ring.get(pid)
                    if len(data) < 12:      # runt: skip, never parse
                        pid += 1
                        continue
                    if not out.thinning.admit(ring.get_flags(pid)):
                        if out.rewrite.base_src_seq >= 0:
                            out.thinning.withhold(out.rewrite.map_seq(
                                int(ring.seq[ring.slot(pid)])))
                        pid += 1            # thinned: frame dropped for this
                        continue            # output only (quality level)
                    res = out.write_rtp(data)
                    if res is WriteResult.WOULD_BLOCK:
                        self.stats.stalls += 1
                        break
                    pid += 1
                    if res is WriteResult.OK:
                        sent += 1
                        bytes_out += len(data)
                        stamp = int(ring.arrival_ns[ring.slot(pid - 1)])
                        lat_ns.append(stamp)
                        if o_row >= 0:
                            o_sent += 1
                            o_byts += len(data)
                            if o_first < 0:
                                o_first = pid - 1
                            o_last = pid - 1
                            a_lat.append(stamp)
                out.bookmark = pid
                if sent > sent0:
                    hold_runs.append((sent - sent0, b_idx))
                if o_sent:
                    a_rows.append(o_row)
                    a_pkts.append(o_sent)
                    a_byts.append(o_byts)
                    a_first.append(o_first)
                    a_last.append(o_last)
        self.stats.packets_out += sent
        if lat_ns:
            wire_ns = time.perf_counter_ns()
            lat_s = (wire_ns
                     - np.asarray(lat_ns, dtype=np.int64)) / 1e9
            if a_rows:
                aud.note_pass(
                    ablk, a_rows, a_pkts, a_byts, a_first, a_last,
                    (wire_ns - np.asarray(a_lat, np.int64)) / 1e9,
                    wire_ns)
            if obs.LEDGER.enabled:
                obs.LEDGER.note_queue_age(float(lat_s.max()), lat_s.size)
            # per-session attribution (command=top) works on the scalar
            # oracle too — small fan-outs are still sessions operators ask
            # about, and the SLO watchdog's offender lookup reads this
            obs.PROFILER.account_latency(self.session_path, lat_s)
            # last: it takes the hold off lat_s in place
            obs.observe_wire("scalar", lat_s, hold_runs,
                             self.settings.bucket_delay_ms)
            if self.session_path is not None:
                obs.PROFILER.account_pass("scalar", 0, {},
                                          path=self.session_path,
                                          wire_bytes=bytes_out)
        self.relay_rtcp(now_ms)
        return sent

    # -- RTCP relay + SR origination --------------------------------------
    def src_ts_now(self, now_ms: int) -> int | None:
        """Source-timeline RTP timestamp corresponding to ``now_ms`` —
        newest packet's timestamp extrapolated by its age at the stream
        clock rate (the reference extrapolates from its base arrival the
        same way, ``RTPSessionOutput.cpp:436-446``)."""
        ring = self.rtp_ring
        if len(ring) == 0:
            return None
        s = ring.slot(ring.head - 1)
        age_ms = max(now_ms - int(ring.arrival[s]), 0)
        rate = self.info.clock_rate or 90000
        return (int(ring.timestamp[s]) + age_ms * rate // 1000) & 0xFFFFFFFF

    def relay_rtcp(self, now_ms: int) -> None:
        """Forward the newest pusher RTCP compound (rebased onto each
        output's timeline) and originate SRs for outputs that have not
        seen one for ``SR_INTERVAL_MS`` (``RTPStream.cpp:1300`` SR gen —
        without this, a pusher that sends no RTCP leaves every player
        with no NTP↔RTP mapping and therefore no A/V sync).

        SR NTP time = a wall-clock base latched once per stream plus the
        monotonic delta: intra-session deltas stay monotonic (cross-stream
        sync works) while absolute times are real NTP wall clock, matching
        the reference and this repo's VOD path.  Both engines share the
        stream object, so differential tests stay byte-identical."""
        if self.fec is not None:
            # the reliability tier's per-pass hook: window parity rides
            # the SAME tail both engines share, so megabatch/native/
            # scalar passes emit identical parity bytes by construction.
            # Ledger-bracketed (ISSUE 16): parity windows run nested in
            # the live-relay pass — charge fec_parity its own service so
            # live_relay's figure stays conserved.
            _tok = obs.LEDGER.unit_start("fec_parity")
            self.fec.tick(now_ms)
            obs.LEDGER.unit_end(_tok)
        rring = self.rtcp_ring
        if len(rring) == 0 and now_ms < self._next_sr_due_ms:
            return                  # hot path: nothing buffered, none due
        if self._wall_base is None:
            self._wall_base = time.time() - now_ms / 1000.0
        unix_time = self._wall_base + now_ms / 1000.0
        ts_now = self.src_ts_now(now_ms)
        outputs = self.outputs
        if len(rring):
            newest = rring.get(rring.head - 1)
            has_sr = rtcp_mod.compound_has_sr(newest)
            for out in outputs:
                if has_sr and out.rewrite.base_src_ts < 0:
                    # cannot rebase yet: forwarding the source-timeline
                    # ntp/rtp pair would poison the client's sync; the
                    # origination below covers it right after the latch
                    continue
                out.write_rtcp(newest, src_ts_now=ts_now,
                               unix_time=unix_time)
                if has_sr:
                    out.last_sr_ms = now_ms
            rring.tail = rring.head
        next_due = now_ms + SR_INTERVAL_MS
        for out in outputs:
            if out.rewrite.base_src_ts < 0:
                next_due = now_ms      # re-check every pass until latched
                continue
            if ts_now is not None and (
                    out.last_sr_ms == 0            # 0 = never: first SR
                    or now_ms - out.last_sr_ms >= SR_INTERVAL_MS):
                out.last_sr_ms = now_ms
                sr = rtcp_mod.build_server_compound(
                    out.rewrite.ssrc, "easydarwin-tpu",
                    unix_time=unix_time,
                    rtp_ts=out.rewrite.map_ts(ts_now),
                    packet_count=out.packets_sent,
                    octet_count=out.payload_octets)
                out.send_bytes(sr, is_rtcp=True)
            next_due = min(next_due, out.last_sr_ms + SR_INTERVAL_MS)
        self._next_sr_due_ms = next_due

    def send_upstream_rr(self, now_ms: int) -> bool:
        """Receiver report to the broadcaster every 5 s so pushers see
        liveness/quality (``ReflectorStream.h:341`` kRRInterval; round 1
        sent nothing upstream).  Returns True when one was sent."""
        if (self.upstream_rtcp is None or self._rr_base_seq is None
                or now_ms - self.last_upstream_rr_ms < SR_INTERVAL_MS):
            return False
        self.last_upstream_rr_ms = now_ms
        ext_max = (self._rr_cycles << 16) | self._rr_max_seq
        expected = ext_max - self._rr_base_seq + 1
        # RFC 3550 A.3: cumulative lost is SIGNED — a duplicate-heavy
        # push drives received past expected and the pusher should see
        # the negative value, not a zero-clamp (ReportBlock handles the
        # 24-bit clamp/sign round-trip)
        lost = expected - self._rr_received
        d_exp = expected - self._rr_prev_expected
        d_rcv = self._rr_received - self._rr_prev_received
        self._rr_prev_expected = expected
        self._rr_prev_received = self._rr_received
        frac = 0
        if d_exp > 0 and d_exp > d_rcv:
            frac = min(int(((d_exp - d_rcv) << 8) / d_exp), 255)
        src_ssrc = int(self.rtp_ring.ssrc[
            self.rtp_ring.slot(self.rtp_ring.head - 1)]) \
            if len(self.rtp_ring) else 0
        rr = rtcp_mod.ReceiverReport(
            self.reporter_ssrc,
            [rtcp_mod.ReportBlock(src_ssrc, frac, lost, ext_max,
                                  0, 0, 0)]).to_bytes()
        try:
            self.upstream_rtcp(rr)
        except Exception:
            self.upstream_rtcp = None       # dead transport: stop trying
            self.upstream_rtcp_owner = None
        return True

    def next_deadline_ms(self, now_ms: int, *, allow_due: bool = False
                         ) -> int:
        """ms until this stream next needs a pump pass without new ingest:
        the earliest bucket-delay release among held-back packets, the
        earliest future reliable-UDP RTO, or the next SR ``relay_rtcp``
        owes a latched output.  -1 = nothing scheduled.  Feeds the 1 ms
        timer wheel that paces the pump (vs the reference's 10 ms
        scheduler floor, ``Task.cpp:334``) and readies the stream
        (``relay.pump``: a stream nothing marked is not stepped).

        ``allow_due`` controls already-due bucket releases: a caller that
        knows the last pass did NOT stall may arm them at 1 ms (the
        release matured mid-pass and the next pass will send it); for a
        stalled stream they are suppressed — a time wake cannot make a
        blocked socket writable, and re-arming 0/1 ms timers would spin
        the pump until the client drains.  Future RTOs are always
        reported; due RTOs never are (the tick that just ran handled
        them).  An SR that is due now is not reported either: it is an
        un-latched output's "re-check every pass", which the pump
        carries over from the step and no timer paces."""
        best = -1
        ring = self.rtp_ring
        delay = self.settings.bucket_delay_ms
        if self.buckets and len(ring):
            d = self._next_sr_due_ms - now_ms
            if d > 0 and self.num_outputs:
                best = d
        for b_idx, bucket in enumerate(self.buckets):
            if b_idx == 0:
                continue               # bucket 0 has no stagger delay
            for out in bucket:
                bm = out._bookmark      # a read: past the property
                if bm is None or bm >= ring.head:
                    continue
                if bm < ring.tail:
                    bm = ring.tail
                d = int(ring.arrival[ring.slot(bm)]) + b_idx * delay - now_ms
                if d <= 0:
                    if not allow_due:
                        continue
                    d = 1
                if best < 0 or d < best:
                    best = d
        for out in self.tickable_outputs:
            d = out.resender.next_deadline_ms(now_ms)
            if d > 0 and (best < 0 or d < best):
                best = d
        return best

    # -- maintenance -------------------------------------------------------
    def prune(self, now_ms: int) -> int:
        """Age-based eviction with bookmark + keyframe pinning
        (``RemoveOldPackets`` cpp:1242-1291)."""
        pins = [o._bookmark for b in self.buckets for o in b
                if o._bookmark is not None]
        if self.keyframe_id is not None:
            pins.append(self.keyframe_id)
        pin = min(pins) if pins else None
        n = self.rtp_ring.evict_older_than(now_ms, self.settings.max_age_ms, pin)
        if (self.keyframe_id is not None
                and not self.rtp_ring.valid(self.keyframe_id)):
            self.keyframe_id = None
        return n
