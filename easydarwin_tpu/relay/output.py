"""Subscriber sinks — ``ReflectorOutput``/``RTPSessionOutput`` equivalents.

An output is one subscriber's view of one relayed track.  It owns:

* a **bookmark** — the absolute ring id of the next packet it needs.  The
  reference threads bookmark pointers through per-output element arrays
  (``ReflectorOutput.h`` ``fBookmarkedPacketsElemsArray``); with absolute ids
  a plain integer suffices, and WouldBlock replay is "don't advance".
* **rewrite state** — per-subscriber SSRC, sequence and timestamp rebase so a
  late joiner sees a gapless RTP stream starting near zero.  The reference
  scatters this across ``RTPSessionOutput::WritePacket``'s seq/ts bookkeeping
  (``RTPSessionOutput.cpp:464-562``); here it is three integers that the TPU
  fan-out consumes as a ``[n_outputs, 3]`` tensor.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

from ..protocol import rtcp, rtp
from ..resilience.inject import INJECTOR


class WriteResult(enum.Enum):
    OK = 0
    WOULD_BLOCK = 1
    ERROR = 2


class PlanCell:
    """What one stream shares with its outputs and with the pump that
    serves it: the plan epoch (``relay.fanout``: the engine's output
    plan is valid while it has not moved) and, once a pump has rostered
    the stream, that pump's ready set and the stream's key in it
    (``relay.pump``: the wake steps the streams that were marked).  An
    output holds the cell and no reference to the stream itself.

    The rest is the pump's own record of the stream's last step, which
    ``relay.pump.needs_step`` reads: the two rings' heads and the epoch
    as the step left them, the route it took, whether it has to be
    retried whatever happens, and the wheel timer armed for it."""

    __slots__ = ("epoch", "ready", "key", "rtp_head", "rtcp_head",
                 "stepped_epoch", "route", "retry", "due", "timer")

    #: ``due`` with no timer pending
    NEVER = 1 << 62

    def __init__(self):
        self.epoch = 0
        self.ready: set | None = None
        self.key = 0
        self.rtp_head = self.rtcp_head = self.stepped_epoch = -1
        self.route = -1
        self.retry = False
        self.due = self.NEVER
        self.timer = 0

    def install(self, ready: set, key: int) -> None:
        """A pump rosters the stream for the first time: its marks land
        in ``ready`` under ``key`` from here on, it has no step on
        record (``needs_step`` holds) and no timer."""
        self.ready, self.key = ready, key
        self.rtp_head = -1
        self.due, self.timer = self.NEVER, 0
        ready.add(key)

    def mark(self) -> None:
        """The stream has something to do in the next wake."""
        if self.ready is not None:
            self.ready.add(self.key)

    def touch(self) -> None:
        self.epoch += 1
        self.mark()


@dataclass
class RewriteState:
    """Per-output header-rewrite parameters (device-friendly: 3 ints)."""

    ssrc: int = 0
    #: first source seq seen by this output (rebase origin)
    base_src_seq: int = -1
    base_src_ts: int = -1
    #: output-side origins (what base_src maps to)
    out_seq_start: int = 0
    out_ts_start: int = 0
    #: the output these parameters rewrite for.  The engine caches
    #: ``params_key`` per stream (``relay.fanout``: the output plan), so
    #: a write to any field here moves the owner's stream's plan epoch
    owner: "RelayOutput | None" = field(default=None, repr=False,
                                        compare=False)

    def __setattr__(self, name, value):
        object.__setattr__(self, name, value)
        owner = self.__dict__.get("owner")
        if owner is not None:
            owner.touch_plan()

    def map_seq(self, src_seq: int) -> int:
        return (src_seq - self.base_src_seq + self.out_seq_start) & 0xFFFF

    def map_ts(self, src_ts: int) -> int:
        return (src_ts - self.base_src_ts + self.out_ts_start) & 0xFFFFFFFF


class RelayOutput:
    """One subscriber × one track. Subclasses implement ``send_bytes``."""

    #: the owning stream's ``PlanCell`` (``RelayStream.add_output`` sets
    #: it, ``remove_output`` clears it)
    _plan_cell: PlanCell | None = None
    _bookmark: int | None = None
    _meta_field_ids: dict | None = None

    def __init__(self, *, ssrc: int = 0, out_seq_start: int = 1,
                 out_ts_start: int = 0):
        from .quality import ThinningFilter
        self.rewrite = RewriteState(ssrc=ssrc, out_seq_start=out_seq_start,
                                    out_ts_start=out_ts_start, owner=self)
        self.thinning = ThinningFilter()
        self.thinning.controller.owner = self
        self.packets_sent = 0
        self.bytes_sent = 0
        #: RTP payload octets only (no 12-byte header, no meta-info wrap) —
        #: the RFC 3550 sender-octet-count definition the SRs report
        self.payload_octets = 0
        self.stalls = 0
        #: monotonic ms of the last SR this output received (relayed or
        #: originated) — drives the 5 s origination cadence
        self.last_sr_ms = 0

    # -- what the engine's output plan derives from -----------------------
    def touch_plan(self) -> None:
        """Move the owning stream's plan epoch (``relay.fanout``: the
        engine steps cohorts from tables cached per epoch).  Every
        write below, of a ``RewriteState`` field and of the thinning
        level lands here; the engine's own cohort step writes
        ``_bookmark`` directly and does not."""
        cell = self._plan_cell
        if cell is not None:
            cell.touch()

    @property
    def bookmark(self) -> int | None:
        """Next ring id this output needs; None = not primed.  The truth
        every reader sees: a cohort's mark is a copy of it."""
        return self._bookmark

    @bookmark.setter
    def bookmark(self, pid: int | None) -> None:
        self._bookmark = pid
        self.touch_plan()

    @property
    def meta_field_ids(self) -> dict[str, int] | None:
        """Negotiated x-RTP-Meta-Info {field: compressed id} (SETUP
        header; None = plain RTP).  Wrapping covers both the scalar
        write_rtp path and the TPU engine's send_rewritten path."""
        return self._meta_field_ids

    @meta_field_ids.setter
    def meta_field_ids(self, ids: dict[str, int] | None) -> None:
        self._meta_field_ids = ids
        self.touch_plan()

    def on_receiver_report(self, fraction_lost: float) -> int:
        """RTCP RR feedback → quality level (FlowControl role input)."""
        return self.thinning.controller.on_receiver_report(fraction_lost)

    def on_nadu(self, playout_delay_ms: int, free_buffer_64b: int) -> int:
        """3GPP NADU buffer feedback → quality level (the reference parses
        NADU but never adapts; ``RTCPAPPNADUPacket.cpp``)."""
        return self.thinning.controller.on_nadu(playout_delay_ms,
                                                free_buffer_64b)

    # -- transport ---------------------------------------------------------
    def send_bytes(self, data: bytes, *, is_rtcp: bool) -> WriteResult:
        raise NotImplementedError

    def send_rewritten(self, header: bytes, tail: bytes) -> WriteResult:
        """Send a device-rewritten packet: 12-byte header + original bytes
        from offset 12.  Default concatenates; socket-backed outputs override
        with vectored I/O so the shared payload is never copied."""
        if INJECTOR.active:
            if INJECTOR.slow_subscriber():
                # chaos site: slow-subscriber backpressure — the
                # engine's WOULD_BLOCK machinery (bookmark replay)
                # handles it, the same as a genuinely full socket
                return WriteResult.WOULD_BLOCK
            if INJECTOR.egress_drop():
                # receiver-side loss site (ISSUE 11): the send is
                # accounted OK but the wire "ate" the packet — only the
                # receiver's RR/NACK feedback can surface it, which is
                # exactly what the reliability tier must react to
                return WriteResult.OK
        if self.meta_field_ids is not None:
            return self.send_bytes(self.wrap_meta(header, tail),
                                   is_rtcp=False)
        return self.send_bytes(header + tail, is_rtcp=False)

    def wrap_meta(self, header: bytes, payload: bytes, *,
                  frame_type: int | None = None,
                  packet_number: int | None = None,
                  packet_position: int | None = None) -> bytes:
        """RTP → x-RTP-Meta-Info packet with the negotiated fields
        (reference: RTPStream's meta-info send path, RTPMetaInfoLib).

        ``sq`` carries the seq of the packet AS SENT — the reference does
        the same (QTHintTrack.cpp:1355 writes hdrData.rtpSequenceNumber,
        the sent packet's own number), so clients correlate md with the
        RTP header, not with source-side numbering."""
        import time

        from ..protocol import rtp_meta
        ids = self.meta_field_ids
        return rtp_meta.build_packet(
            header, media=payload, field_ids=ids,
            transmit_time=int(time.time() * 1000) if "tt" in ids else None,
            seq=rtp.peek_seq(header) if "sq" in ids else None,
            frame_type=frame_type if "ft" in ids else None,
            packet_number=packet_number if "pn" in ids else None,
            packet_position=packet_position if "pp" in ids else None)

    # -- relay-facing API --------------------------------------------------
    def write_rtp(self, packet: bytes) -> WriteResult:
        """Rewrite header per this output's state and send. The TPU engine
        produces identical bytes in batch (differential-tested)."""
        rw = self.rewrite
        if rw.base_src_seq < 0:
            rw.base_src_seq = rtp.peek_seq(packet)
            rw.base_src_ts = rtp.peek_timestamp(packet)
        if INJECTOR.active and INJECTOR.slow_subscriber():
            self.stalls += 1            # same accounting as a real block
            return WriteResult.WOULD_BLOCK
        out = rtp.rewrite_header(
            packet,
            seq=rw.map_seq(rtp.peek_seq(packet)),
            timestamp=rw.map_ts(rtp.peek_timestamp(packet)),
            ssrc=rw.ssrc)
        if self.meta_field_ids is not None:
            out = self.wrap_meta(out[:12], out[12:])
        if INJECTOR.active and INJECTOR.egress_drop():
            # receiver-side loss: sent-and-lost, so the OK accounting
            # runs EXACTLY as for a real send — on the WRAPPED bytes,
            # or the counters (and the SRs built from them) would
            # drift from an identical non-dropped schedule and make
            # the loss sender-visible
            self.packets_sent += 1
            self.bytes_sent += len(out)
            self.payload_octets += max(len(packet) - 12, 0)
            return WriteResult.OK
        res = self.send_bytes(out, is_rtcp=False)
        if res is WriteResult.OK:
            self.packets_sent += 1
            self.bytes_sent += len(out)
            self.payload_octets += max(len(packet) - 12, 0)
        elif res is WriteResult.WOULD_BLOCK:
            self.stalls += 1
        return res

    def write_rtcp(self, packet: bytes, *,
                   src_ts_now: int | None = None,
                   unix_time: float | None = None) -> WriteResult:
        """Relay an RTCP compound onto this output's timeline
        (``RTPSessionOutput.cpp:403-460``): SSRC swapped always; when the
        caller supplies the stream's source-timeline "RTP time of now"
        and the rebase is latched, contained SRs get NTP←now and
        RTP←map_ts(now) so the forwarded ntp/rtp pair is valid on the
        OUTPUT timeline (round 1 forwarded the source-timeline pair)."""
        rw = self.rewrite
        if src_ts_now is not None and rw.base_src_ts >= 0:
            out = rtcp.rebase_compound(
                packet, rw.ssrc,
                unix_time=unix_time if unix_time is not None else 0.0,
                rtp_ts_now=rw.map_ts(src_ts_now),
                packet_count=self.packets_sent,
                octet_count=self.payload_octets)
        else:
            out = rtcp.rewrite_compound_ssrc(packet, rw.ssrc)
        res = self.send_bytes(out, is_rtcp=True)
        # packets_sent/bytes_sent stay RTP-only: they feed the SR sender
        # stats, which RFC 3550 defines over RTP data packets
        if res is WriteResult.WOULD_BLOCK:
            self.stalls += 1
        return res


class CollectingOutput(RelayOutput):
    """Test/bench sink that records everything (optionally stalling)."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.rtp_packets: list[bytes] = []
        self.rtcp_packets: list[bytes] = []
        self.block_next = 0

    def send_bytes(self, data: bytes, *, is_rtcp: bool) -> WriteResult:
        if self.block_next > 0:
            self.block_next -= 1
            return WriteResult.WOULD_BLOCK
        (self.rtcp_packets if is_rtcp else self.rtp_packets).append(data)
        return WriteResult.OK
