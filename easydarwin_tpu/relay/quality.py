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

A loss fraction is judged over enough packets: an RR's own 8-bit
``fraction_lost`` covers whatever the receiver saw since its previous
report — a handful of packets in a viewer's first report at a low frame
rate, where one burst reads 40 %.  :class:`ReportFold` adds successive
report blocks of an output by RFC 3550 A.3 arithmetic (Δ extended
highest seq expected, Δ cumulative lost) and hands the controllers a
fraction only once it covers :data:`MIN_JUDGED_PACKETS`.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field

from .. import obs
from .ring import PacketFlags

#: the fewest expected packets a judged loss fraction covers.  One lost
#: burst of three in a viewer's first report of five packets reads 60 %
#: and thinned at once; over 64 a fraction moves in steps of 1.6 %, so
#: LOSS_THIN_NOW (30 %) needs 20 losses.  64 is about one 5 s report
#: (RFC 3550's minimum interval) of a 1.1 frames/s stream of ≈ 13
#: packets a frame; a faster stream is judged on every report
MIN_JUDGED_PACKETS = 64
#: a Δ extended highest seq above this is no interval's growth (a
#: reordered report, a receiver that restarted): the fold rebases
MAX_REPORT_SPAN = 0x8000


@dataclass
class ReportFold:
    """One output's receiver reports, folded until they cover enough
    packets (RFC 3550 A.3): the expected count is the growth of the
    extended highest sequence number received (32-bit, wrap included),
    the lost count the growth of the cumulative number lost (signed: a
    duplicate-heavy receiver's goes down)."""

    _last: tuple[int, int] | None = None    # (highest_seq, cumulative)
    _expected: int = 0
    _lost: int = 0

    def add(self, highest_seq: int, cumulative_lost: int,
            base_seq: int) -> float | None:
        """Fold one report block; the loss fraction (0..1) once the fold
        covers :data:`MIN_JUDGED_PACKETS`, else None.  ``base_seq`` is
        the first sequence number the output sent (the PLAY answer's
        RTP-Info ``seq``), from which a first block counts."""
        last = self._last or (base_seq - 1, 0)
        self._last = (highest_seq, cumulative_lost)
        grew = (highest_seq - last[0]) & 0xFFFFFFFF
        if grew == 0 or grew > MAX_REPORT_SPAN:
            return None
        self._expected += grew
        self._lost += cumulative_lost - last[1]
        if self._expected < MIN_JUDGED_PACKETS:
            return None
        frac = min(max(self._lost / self._expected, 0.0), 1.0)
        self._expected = self._lost = 0
        return frac


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
    #: this output's receiver reports, folded until they cover enough
    #: packets to be judged
    fold: ReportFold = field(default_factory=ReportFold, repr=False,
                             compare=False)

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
        if new == self.level:
            # a write of ``level`` moves the stream's plan epoch: a clean
            # streak at level 0 must not rebuild the plan
            return
        if new > self.level:
            self.thins += 1
            obs.QOS_THINS.inc()
        else:
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


#: runs of withheld output seqs a filter remembers: a run is one dropped
#: frame, so 256 hold a minute of level-1 thinning at 2 frames/s, far
#: past any NACK of them
WITHHELD_RUNS = 256


@dataclass
class ThinningFilter:
    """Per-output frame-granular packet filter driven by a quality level.

    The packets it drops leave holes in the output's sequence space (the
    rewrite is affine), which a conformant receiver NACKs: the caller
    notes each one's output seq (``withhold``) so that an RTX replay can
    tell a deliberate hole from a loss (``withheld``)."""

    controller: QualityController = field(default_factory=QualityController)
    _frame_index: int = 0
    _dropping_frame: bool = False
    dropped: int = 0
    #: [first output seq, count] runs of withheld packets, newest last
    _runs: deque | None = field(default=None, repr=False)

    def withhold(self, out_seq: int) -> None:
        """Note that the packet of output seq ``out_seq`` was dropped."""
        runs = self._runs
        if runs is None:
            runs = self._runs = deque(maxlen=WITHHELD_RUNS)
        if runs and (runs[-1][0] + runs[-1][1]) & 0xFFFF == out_seq:
            runs[-1][1] += 1
        else:
            runs.append([out_seq & 0xFFFF, 1])

    def withheld(self, out_seq: int) -> bool:
        """True when ``out_seq`` is a hole this filter made."""
        return self._runs is not None and any(
            (out_seq - first) & 0xFFFF < n for first, n in self._runs)

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
