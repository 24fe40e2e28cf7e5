"""Quality adaptation: RTCP-feedback-driven thinning/thickening.

Reference parity: ``QTSSFlowControlModule.cpp:94-441`` (RTCP loss/buffer
feedback → thin/thick decisions with hysteresis; default tolerances from
its pref table: thin when loss > 30%% once or > 10%% repeatedly, thicken
after several clean reports) and ``RTPStream``'s quality levels
(``RTPStream.h:144-174``).

The reference thins hinted VOD media per-track; a relay only knows frame
boundaries and keyframes (the ingest classifier), so thinning here drops
*complete frames* per output:

====  =========================================
0     full stream
1     drop every second non-key frame
2     key frames (IDR/SPS/PPS GOP heads) only
3     video muted (audio continues)
====  =========================================

Decisions live per output (one slow client must not thin the others —
exactly why the reference keeps quality on the RTPStream).
"""

from __future__ import annotations

from dataclasses import dataclass, field

from .. import obs
from .ring import PacketFlags

MAX_LEVEL = 3

# hysteresis thresholds (QTSSFlowControlModule pref defaults)
LOSS_THIN_NOW = 0.30        # one report above this → thin immediately
LOSS_THIN_SLOW = 0.10       # this many...
NUM_LOSSES_TO_THIN = 3      # ...consecutive reports above SLOW → thin
LOSS_THICK_BELOW = 0.03     # reports below this...
NUM_CLEAN_TO_THICK = 6      # ...this many times → thicken one level

# 3GPP NADU (TS 26.234) buffer-state thresholds.  The reference parses
# NADU (RTPStream::ProcessNADUPacket) but never feeds it to flow control;
# here the receiver's buffer state drives the same hysteresis as loss:
NADU_DELAY_UNKNOWN = 0xFFFF
NADU_UNDERRUN_NOW_MS = 40    # playout delay below this → thin immediately
NADU_DELAY_LOW_MS = 150      # below this repeatedly → thin (underrun risk)
NADU_DELAY_COMFY_MS = 1000   # above this (with free space) → clean report
NADU_FREE_LOW_64B = 24       # < 1.5 KB free receiver buffer → back off


@dataclass
class QualityController:
    level: int = 0
    _lossy_reports: int = 0
    _clean_reports: int = 0
    thins: int = 0
    thickens: int = 0
    #: the RelayOutput this controller thins for: a change of ``level``
    #: decides whether the engine may batch it (``passthrough``), so it
    #: moves the owner's stream's plan epoch (``relay.fanout``)
    owner: object = field(default=None, repr=False, compare=False)

    def __setattr__(self, name, value):
        object.__setattr__(self, name, value)
        if name == "level":
            owner = self.__dict__.get("owner")
            if owner is not None:
                owner.touch_plan()

    def on_receiver_report(self, fraction_lost: float) -> int:
        """Feed one RR's loss fraction (0..1); returns the new level."""
        if fraction_lost >= LOSS_THIN_NOW:
            self._bump(+1)
            self._lossy_reports = self._clean_reports = 0
            return self.level
        if fraction_lost >= LOSS_THIN_SLOW:
            self._lossy_reports += 1
            self._clean_reports = 0
            if self._lossy_reports >= NUM_LOSSES_TO_THIN:
                self._bump(+1)
                self._lossy_reports = 0
        elif fraction_lost <= LOSS_THICK_BELOW:
            self._clean_reports += 1
            self._lossy_reports = 0
            if self._clean_reports >= NUM_CLEAN_TO_THICK:
                self._bump(-1)
                self._clean_reports = 0
        else:
            self._lossy_reports = self._clean_reports = 0
        return self.level

    def on_nadu(self, playout_delay_ms: int, free_buffer_64b: int) -> int:
        """Feed one 3GPP NADU block's buffer state; returns the new level.

        A receiver about to underrun (tiny playout delay) or to overflow
        (no free buffer space) gets the lossy-report treatment — one
        extreme report thins immediately, sustained low buffer thins via
        the same hysteresis counters as loss; a deep comfortable buffer
        counts as a clean report toward thickening.  (Delay 0xFFFF means
        "not known" and contributes nothing.)"""
        delay_known = playout_delay_ms != NADU_DELAY_UNKNOWN
        if (delay_known and playout_delay_ms <= NADU_UNDERRUN_NOW_MS) \
                or free_buffer_64b == 0:
            self._bump(+1)
            self._lossy_reports = self._clean_reports = 0
            return self.level
        if (delay_known and playout_delay_ms < NADU_DELAY_LOW_MS) \
                or free_buffer_64b < NADU_FREE_LOW_64B:
            self._lossy_reports += 1
            self._clean_reports = 0
            if self._lossy_reports >= NUM_LOSSES_TO_THIN:
                self._bump(+1)
                self._lossy_reports = 0
        elif delay_known and playout_delay_ms >= NADU_DELAY_COMFY_MS:
            self._clean_reports += 1
            self._lossy_reports = 0
            if self._clean_reports >= NUM_CLEAN_TO_THICK:
                self._bump(-1)
                self._clean_reports = 0
        return self.level

    def _bump(self, d: int) -> None:
        new = max(0, min(MAX_LEVEL, self.level + d))
        if new > self.level:
            self.thins += 1
            obs.QOS_THINS.inc()
        elif new < self.level:
            self.thickens += 1
            obs.QOS_THICKENS.inc()
        self.level = new


def record_rr_qos(path: str, track_id, fraction_lost: float,
                  jitter_units: int, clock_rate: int | None = None) -> None:
    """Fold one RTCP receiver report into the per-stream QoS gauges.

    ``jitter_units`` is the RFC 3550 interarrival jitter in RTP timestamp
    units; it is converted to seconds with the stream clock rate (90 kHz
    when unknown).  Called from the RTSP RTCP demux for every matched
    report block — gauges carry the MOST RECENT report, the counters
    (qos_thins/thickens) accumulate the adaptation decisions."""
    rate = clock_rate or 90000
    labels = {"path": path or "-", "track": str(track_id)}
    obs.QOS_FRACTION_LOST.set(round(float(fraction_lost), 6), **labels)
    obs.QOS_JITTER.set(round(jitter_units / rate, 6), **labels)


def drop_qos(path: str, track_id) -> None:
    """Remove a departed stream's QoS gauges from the exposition."""
    labels = {"path": path or "-", "track": str(track_id)}
    obs.QOS_FRACTION_LOST.remove(**labels)
    obs.QOS_JITTER.remove(**labels)


@dataclass
class ThinningFilter:
    """Per-output frame-granular packet filter driven by a quality level."""

    controller: QualityController = field(default_factory=QualityController)
    _frame_index: int = 0
    _dropping_frame: bool = False
    dropped: int = 0

    def passthrough(self) -> bool:
        """True while the filter cannot drop anything (level 0, not mid
        frame-drop) — the native batched egress bypasses ``admit`` for
        such outputs and must route through the scalar path otherwise."""
        return self.controller.level == 0 and not self._dropping_frame

    def admit(self, flags: int) -> bool:
        """Decide for one packet (classification flags from the ring)."""
        level = self.controller.level
        if not flags & PacketFlags.VIDEO:
            return True                      # audio always flows
        is_key = bool(flags & PacketFlags.KEYFRAME_FIRST)
        if flags & PacketFlags.FRAME_FIRST:
            self._frame_index += 1
            if level == 0:
                self._dropping_frame = False
            elif level == 1:
                self._dropping_frame = (not is_key
                                        and self._frame_index % 2 == 0)
            elif level == 2:
                self._dropping_frame = not is_key
            else:
                self._dropping_frame = True
        elif level >= 3:
            self._dropping_frame = True
        if self._dropping_frame:
            self.dropped += 1
            return False
        return True
