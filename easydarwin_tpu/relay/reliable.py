"""Reliable UDP: resend window, RTT/cwnd tracking, overbuffer pacing.

Reference parity: the reliable-RTP kit behind ``RTPStream::ReliableRTPWrite``
(``RTPStream.cpp:825``) —

* ``RTPBandwidthTracker.cpp``: Karn-style smoothed RTT (SRTT/RTTVAR → RTO)
  and a byte congestion window with slow-start + congestion avoidance;
* ``RTPPacketResender.cpp``: per-stream window of unacked packets, resend on
  RTO expiry with backoff, give-up after max resends;
* ``RTPOverbufferWindow.cpp``: how far ahead of real-time the sender may run
  (client-side buffer budget), with the send-ahead window from prefs;
* ``RTCPAckPacket.cpp``: the 'qtak' APP ack — first seq + following bit
  mask of additional acks.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass, field

from ..protocol import rtp
from ..protocol.rtcp import App

ACK_NAME = "qtak"
LEGACY_ACK_NAME = "ack "


# ------------------------------------------------------------- RTT / cwnd
class BandwidthTracker:
    """SRTT/RTTVAR/RTO + byte congestion window (slow start → avoidance)."""

    MIN_RTO_MS = 250          # reference clamps retransmit timeout
    MAX_RTO_MS = 24_000
    MSS = 1466                # segment size used for window arithmetic

    def __init__(self, *, initial_window: int = 3 * 1466):
        self.srtt_ms: float | None = None
        self.rttvar_ms = 0.0
        self.cwnd = float(initial_window)
        self.ssthresh = 64 * 1024.0
        #: client-advertised ceiling (x-Retransmit window=KB); None = none
        self.max_cwnd: float | None = None
        self.bytes_in_flight = 0
        self.acks = 0
        self.losses = 0

    @property
    def rto_ms(self) -> float:
        if self.srtt_ms is None:
            return 1000.0
        return min(max(self.srtt_ms + 4 * self.rttvar_ms, self.MIN_RTO_MS),
                   self.MAX_RTO_MS)

    def can_send(self, nbytes: int) -> bool:
        return self.bytes_in_flight + nbytes <= self.cwnd

    def on_sent(self, nbytes: int) -> None:
        self.bytes_in_flight += nbytes

    def on_ack(self, nbytes: int, rtt_ms: float | None) -> None:
        self.bytes_in_flight = max(0, self.bytes_in_flight - nbytes)
        self.acks += 1
        if rtt_ms is not None:           # Karn: only unambiguous samples
            if self.srtt_ms is None:
                self.srtt_ms = rtt_ms
                self.rttvar_ms = rtt_ms / 2
            else:
                self.rttvar_ms += 0.25 * (abs(self.srtt_ms - rtt_ms)
                                          - self.rttvar_ms)
                self.srtt_ms += 0.125 * (rtt_ms - self.srtt_ms)
        if self.cwnd < self.ssthresh:
            self.cwnd += self.MSS                      # slow start
        else:
            self.cwnd += self.MSS * self.MSS / self.cwnd   # avoidance
        if self.max_cwnd is not None:
            self.cwnd = min(self.cwnd, self.max_cwnd)

    def on_loss(self, nbytes: int) -> None:
        self.bytes_in_flight = max(0, self.bytes_in_flight - nbytes)
        self.losses += 1
        self.ssthresh = max(self.cwnd / 2, 2 * self.MSS)
        self.cwnd = self.ssthresh

    def deflate(self, nbytes: int) -> None:
        """Remove expired bytes from flight WITHOUT a window backoff —
        the resender applies one multiplicative decrease per loss sweep
        (standard congestion response), not one per lost packet."""
        self.bytes_in_flight = max(0, self.bytes_in_flight - nbytes)


# --------------------------------------------------------------- resender
@dataclass
class _Pending:
    data: bytes
    first_sent_ms: int
    last_sent_ms: int
    resends: int = 0


class PacketResender:
    MAX_RESENDS = 4           # then give up (counted as loss)

    def __init__(self, tracker: BandwidthTracker):
        self.tracker = tracker
        self.pending: dict[int, _Pending] = {}
        self.resent = 0
        self.expired = 0

    def add(self, seq: int, data: bytes, now_ms: int) -> None:
        self.pending[seq & 0xFFFF] = _Pending(data, now_ms, now_ms)
        self.tracker.on_sent(len(data))

    def ack(self, seq: int, now_ms: int) -> bool:
        p = self.pending.pop(seq & 0xFFFF, None)
        if p is None:
            return False
        rtt = (now_ms - p.first_sent_ms) if p.resends == 0 else None
        self.tracker.on_ack(len(p.data), rtt)
        return True

    def due_for_resend(self, now_ms: int) -> list[tuple[int, bytes]]:
        """Packets past RTO: returns them for retransmission; drops ones
        past MAX_RESENDS (loss).  The whole sweep is ONE congestion event:
        a burst loss halves the window once, not once per packet (a
        per-packet decrease collapses a 64 KB window to the 2·MSS floor
        in a single pump tick)."""
        rto = self.tracker.rto_ms
        out: list[tuple[int, bytes]] = []
        congested = False
        for seq in list(self.pending):
            p = self.pending[seq]
            if now_ms - p.last_sent_ms < rto * (2 ** p.resends):
                continue
            congested = True
            if p.resends >= self.MAX_RESENDS:
                del self.pending[seq]
                self.expired += 1
                self.tracker.deflate(len(p.data))
                continue
            p.resends += 1
            p.last_sent_ms = now_ms
            self.resent += 1
            out.append((seq, p.data))
        if congested:
            self.tracker.on_loss(0)      # one backoff per sweep
        return out

    @property
    def in_flight(self) -> int:
        return len(self.pending)

    def next_deadline_ms(self, now_ms: int) -> int:
        """ms until the earliest pending packet's RTO fires (0 = due now,
        -1 = nothing pending) — feeds the server's timer-wheel pacing."""
        if not self.pending:
            return -1
        rto = self.tracker.rto_ms
        due = min(p.last_sent_ms + rto * (2 ** p.resends)
                  for p in self.pending.values())
        return max(int(due - now_ms), 0)


# -------------------------------------------------------- overbuffer window
class OverbufferWindow:
    """Send-ahead budget: may we transmit a packet whose play-out time is
    ``ahead_ms`` in the future?  (``RTPOverbufferWindow.cpp`` semantics:
    unlimited window pref = always yes; otherwise bounded by the window
    minus what's already been sent ahead.)"""

    def __init__(self, *, window_ms: int = 10_000,
                 max_send_ahead_ms: int = 25_000):
        self.window_ms = window_ms
        self.max_send_ahead_ms = max_send_ahead_ms

    def can_send(self, packet_playout_ms: int, now_ms: int) -> bool:
        ahead = packet_playout_ms - now_ms
        if ahead <= 0:
            return True                   # due or late: always sendable
        if self.window_ms <= 0:
            return True                   # unlimited overbuffering
        return ahead <= min(self.window_ms, self.max_send_ahead_ms)

    def suggested_wakeup(self, packet_playout_ms: int, now_ms: int) -> int:
        """When to retry a deferred packet (ms from now)."""
        return max(packet_playout_ms - self.window_ms - now_ms, 10)


# ------------------------------------------------------------ ack parsing
def build_ack(ssrc: int, first_seq: int, extra_mask: int = 0,
              mask_bytes: int = 4) -> bytes:
    """Build a 'qtak' APP ack: first seq + bit mask of following seqs."""
    payload = struct.pack(">HH", first_seq & 0xFFFF, 0)
    payload += extra_mask.to_bytes(mask_bytes, "big")
    if len(payload) % 4:
        payload += b"\x00" * (4 - len(payload) % 4)
    return App(ssrc, ACK_NAME, data=payload).to_bytes()


def parse_ack(app: App) -> list[int]:
    """'qtak'/'ack ' APP → acked sequence numbers (first + mask bits,
    bit i of the mask acking ``first_seq + 1 + i`` — RTCPAckPacket's
    layout)."""
    if app.name not in (ACK_NAME, LEGACY_ACK_NAME) or len(app.data) < 4:
        return []
    first_seq = struct.unpack_from(">H", app.data, 0)[0]
    seqs = [first_seq]
    mask = app.data[4:]
    for byte_i, b in enumerate(mask):
        for bit in range(8):
            if b & (0x80 >> bit):
                seqs.append((first_seq + 1 + byte_i * 8 + bit) & 0xFFFF)
    return seqs


# ------------------------------------------------------- output decorator
from .output import RelayOutput, WriteResult  # noqa: E402


class ReliableUdpOutput(RelayOutput):
    """PRODUCTION reliable-UDP output: decorates a transport output
    (shared-egress ``NativeUdpOutput`` or per-connection ``UdpOutput``)
    with the resend window — the ``RTPStream::ReliableRTPWrite`` path
    (``RTPStream.cpp:825``) as a ``RelayOutput``:

    * ``send_bytes`` gates data packets on the congestion window
      (WouldBlock ⇒ the relay keeps the bookmark and replays — exactly the
      reference's flow-control contract) and records every sent packet,
      keyed by its OUTPUT sequence number, for retransmission;
    * ``on_rtcp_app`` consumes client 'qtak'/'ack ' acks from the RTCP
      demux (``RTCPAckPacket.cpp`` format);
    * ``tick`` retransmits RTO-expired packets (called from the server
      pump each pass).

    Engines route it down the batch-header path (no ``native_addr``), so
    per-packet bookkeeping survives TPU batching.  The rewrite/thinning
    state is SHARED with the wrapped transport, keeping the device's
    affine-params view consistent."""

    def __init__(self, transport: RelayOutput, *,
                 window_kb: int | None = None, clock=None):
        super().__init__()
        self.transport = transport
        self.rewrite = transport.rewrite        # shared rebase state
        self.thinning = transport.thinning
        # this wrapper is the output a stream holds: writes to the shared
        # state move ITS stream's plan epoch
        self.rewrite.owner = self
        self.thinning.controller.owner = self
        self.meta_field_ids = transport.meta_field_ids
        self.tracker = BandwidthTracker()
        if window_kb is not None:
            # client-advertised buffer (x-Retransmit;window=N, in KB):
            # never grow the send window past what the client can hold
            # (window=0 clamps to the 2*MSS floor, not to "unlimited")
            cap = max(int(window_kb) * 1024, 2 * BandwidthTracker.MSS)
            self.tracker.max_cwnd = float(cap)
            self.tracker.ssthresh = min(self.tracker.ssthresh, float(cap))
        self.resender = PacketResender(self.tracker)
        import time as _time
        self._clock = clock or (lambda: int(_time.monotonic() * 1000))
        #: correlation envelope (stamped by the RTSP layer at SETUP)
        self.session_id: str | None = getattr(transport, "session_id", None)
        self.trace_id: str | None = getattr(transport, "trace_id", None)
        self._expired_reported = 0

    @property
    def rtcp_addr(self):
        return self.transport.rtcp_addr         # RTCP demux registration

    @property
    def rtp_addr(self):
        return self.transport.rtp_addr

    def send_bytes(self, data: bytes, *, is_rtcp: bool) -> WriteResult:
        if is_rtcp:
            return self.transport.send_bytes(data, is_rtcp=True)
        if not self.tracker.can_send(len(data)):
            return WriteResult.WOULD_BLOCK
        res = self.transport.send_bytes(data, is_rtcp=False)
        if res is WriteResult.OK:
            self.resender.add(rtp.peek_seq(data), data, self._clock())
        return res

    def on_rtcp_app(self, app: App, now_ms: int | None = None) -> int:
        now = now_ms if now_ms is not None else self._clock()
        n = 0
        for seq in parse_ack(app):
            if self.resender.ack(seq, now):
                n += 1
        if n and self._plan_cell is not None:
            # an ack moves the RTO estimate and opens the window: the
            # stream's armed timer no longer stands for its next RTO
            self._plan_cell.mark()
        return n

    def tick(self, now_ms: int | None = None) -> int:
        """Retransmit RTO-expired packets (ungated: retransmits must not
        starve behind fresh data, matching the reference resender)."""
        now = now_ms if now_ms is not None else self._clock()
        n = 0
        for _seq, data in self.resender.due_for_resend(now):
            if self.transport.send_bytes(data, is_rtcp=False) \
                    is WriteResult.OK:
                n += 1
        if self.resender.expired > self._expired_reported:
            # packets past MAX_RESENDS gave up this sweep: that is real
            # loss the session's black box must show (per-sweep, never
            # per packet — this path rides the pump)
            from ..obs import EVENTS
            self._expired_reported = self.resender.expired
            EVENTS.emit("reliable.expired", level="warn",
                        session_id=self.session_id, trace_id=self.trace_id,
                        expired=self.resender.expired,
                        resent=self.resender.resent)
        return n
