"""Lossy-WAN reliability tier: FEC parity + NACK/RTX replay (ISSUE 11).

Every delivery path before this assumed the kernel delivers or the
subscriber is shed; the reference's ``RTPPacketResender``/flow-control
heritage exists because UDP loss is the NORMAL case on last miles.  This
module makes loss a measured, recovered quantity:

* **FEC parity as a matmul.**  The fixed-slot ring is already a dense
  ``[window, slot]`` uint8 matrix, so per-window parity is one GF
  matmul: XOR parity is the GF(2) all-ones row, Reed-Solomon parity is
  a GF(256) Vandermonde row set evaluated through log/antilog tables
  (``models.relay_pipeline.fec_parity_window_step`` — table-gather +
  XOR-reduce, the same jnp idiom as the affine fan-out kernels).  The
  device computes parity over the RAW ring rows once per (stream,
  window); the per-subscriber pieces — the 12-byte rewritten-header
  combo and the 2-byte length combo — are O(window × 12) host numpy.
  Every device parity row is checked against :func:`gf_matmul`, the
  independent host GF oracle, through the megabatch
  ``_install_segment`` discipline: a mismatch counts
  ``fec_parity_oracle_mismatch_total`` and latches the stream onto
  host-computed parity — a kernel bug degrades one stream to host
  parity, never corrupts the wire.

* **Parity packets** are RED/ULPFEC-shaped: RTP header (own ``fec_pt``
  and its own seq space, the output's SSRC) + a 12-byte FEC header
  (``snbase`` = output seq of the first protected packet, a 48-bit
  mask of protected seq offsets — RFC 5109's shape — protected count,
  parity index, kind) + the parity payload covering
  ``len(2) ∥ header(12) ∥ payload`` of each protected wire packet,
  zero-padded to the window's longest.  They leave through the same
  scalar egress rung the batch-header path uses (``out.send_bytes``).

* **NACK/RTX.**  The ring IS the retransmission buffer: an RFC 4585
  generic NACK resolves each lost OUTPUT seq back through the inverse
  affine rewrite to a live ring bookmark, and the replay is an RFC
  4588-shaped retransmission — original header re-rewritten, PT
  swapped to ``rtx_pt``, fresh RTX seq, the Original Sequence Number
  riding as the first two payload bytes.  A per-output token-bucket
  budget bounds replay so a black-holed client can't amplify;
  give-ups count ``rtx_giveup_total`` and are heard by the output's
  own quality controller, never by its stream's degradation ladder.
  A hole the output's thinning made is never replayed.

* **Closed-loop control.**  :class:`FecRateController` drives the
  per-subscriber overhead ratio (0–30%, the ``OVERHEAD_LADDER``) from
  the RTCP RR loss stream — judged once folded reports cover enough
  packets (``quality.ReportFold``) — with the same hysteresis shape
  as ``quality.QualityController`` (one heavy report steps now,
  sustained moderate loss steps slowly, sustained clean decays) and
  the NACK-vs-FEC split from the 3GPP NADU buffer gauges: a receiver
  whose buffer is distressed gets LESS parity bitrate (loss recovery
  shifts to RTX), a comfortable one lets loss drive parity up.

:class:`FecReceiver` is the receiver model the tests/soak/bench drive:
it reconstructs dropped packets byte-exactly from parity (GF Gaussian
elimination over the Vandermonde system) and from RTX replays, and
counts ``fec_recovered_total`` so an in-process lossy player surfaces
recovery in /metrics.
"""

from __future__ import annotations

import math
import struct
import time
from dataclasses import dataclass

import numpy as np

from .. import obs
from .quality import LOSS_THIN_SLOW

# ------------------------------------------------------------ GF(256) tables
#: the RS-standard polynomial x^8+x^4+x^3+x^2+1 (0x11D), generator 2 —
#: the same field every ULPFEC/RAID6 implementation uses
_GF_POLY = 0x11D

GF_EXP = np.zeros(255, np.uint8)
_x = 1
for _i in range(255):
    GF_EXP[_i] = _x
    _x <<= 1
    if _x & 0x100:
        _x ^= _GF_POLY
GF_LOG = np.zeros(256, np.int32)
for _i in range(255):
    GF_LOG[int(GF_EXP[_i])] = _i
# log[0] stays 0 as a SENTINEL — every consumer masks zero operands
# explicitly (gf_mul(0, ·) = 0), the table never encodes it
#: antilog table doubled so ``log(a)+log(b)`` (max 508) indexes without
#: a modulo — the host matmul's hot lookup; padded to 512 for the
#: device gather's static shape
GF_EXP512 = np.concatenate([GF_EXP, GF_EXP, GF_EXP[:2]]).astype(np.int32)


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(GF_EXP[(int(GF_LOG[a]) + int(GF_LOG[b])) % 255])


def gf_pow(a: int, n: int) -> int:
    if a == 0:
        return 0
    return int(GF_EXP[(int(GF_LOG[a]) * n) % 255])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(256) inverse of 0")
    return int(GF_EXP[(255 - int(GF_LOG[a])) % 255])


def gf_matmul(coeff: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """GF(256) matrix product with XOR accumulation — the host oracle.

    ``coeff [R, K] × rows [K, B] → [R, B]`` uint8.  Vectorized through
    the log/antilog tables (one gather + XOR reduce per parity row);
    an INDEPENDENT implementation of the arithmetic the device kernel
    performs, so comparing the two catches a kernel bug rather than
    re-running it."""
    coeff = np.asarray(coeff, np.uint8)
    rows = np.asarray(rows, np.uint8)
    lc = GF_LOG[coeff]                        # [R, K]
    lr = GF_LOG[rows]                         # [K, B]
    rows_zero = rows == 0                     # [K, B]
    out = np.empty((coeff.shape[0], rows.shape[1]), np.uint8)
    for p in range(coeff.shape[0]):
        t = GF_EXP512[lc[p][:, None] + lr].astype(np.uint8)
        t[rows_zero] = 0
        t[coeff[p] == 0, :] = 0
        np.bitwise_xor.reduce(t, axis=0, out=out[p])
    return out


def coeff_rows(deltas, n_parity: int) -> np.ndarray:
    """The Vandermonde coefficient matrix ``C[p, i] = α^(d_i · p)``.

    ``deltas`` are the protected packets' seq offsets from ``snbase``
    (distinct, < :data:`MASK_BITS`) — using the OFFSET as the
    evaluation point means the receiver rebuilds the identical matrix
    from the FEC header's mask alone.  Row 0 is all-ones (the XOR
    row); distinct evaluation points make every square submatrix a
    Vandermonde determinant, so any ``m ≤ n_parity`` erasures solve."""
    d = np.asarray(list(deltas), np.int64)
    p = np.arange(n_parity, dtype=np.int64)
    return GF_EXP512[(np.outer(p, d)) % 255].astype(np.uint8)


def gf_solve(a: np.ndarray, b: np.ndarray, *,
             caller: str = "unlabeled") -> np.ndarray | None:
    """Solve ``A · x = b`` over GF(256) (A ``[m, m]``, b ``[m, B]``) by
    Gaussian elimination; None when singular (cannot happen for the
    consecutive-from-0 Vandermonde systems :func:`coeff_rows` produces,
    but an arbitrary parity-index subset CAN be).  Singular returns are
    no longer silent: each one counts ``fec_solve_singular_total`` under
    ``caller`` so a storage read that cannot solve fails loudly and a
    receiver waiting for more parity rows is distinguishable from one
    that never will get them."""
    a = np.array(a, np.uint8)
    b = np.array(b, np.uint8)
    m = a.shape[0]
    for col in range(m):
        piv = next((r for r in range(col, m) if a[r, col]), None)
        if piv is None:
            obs.FEC_SOLVE_SINGULAR.inc(caller=caller)
            return None
        if piv != col:
            a[[col, piv]] = a[[piv, col]]
            b[[col, piv]] = b[[piv, col]]
        inv = gf_inv(int(a[col, col]))
        a[col] = gf_matmul(np.array([[inv]], np.uint8), a[col][None, :])[0]
        b[col] = gf_matmul(np.array([[inv]], np.uint8), b[col][None, :])[0]
        for r in range(m):
            if r != col and a[r, col]:
                f = np.array([[a[r, col]]], np.uint8)
                a[r] ^= gf_matmul(f, a[col][None, :])[0]
                b[r] ^= gf_matmul(f, b[col][None, :])[0]
    return b


# ------------------------------------------------------------- wire format
#: FEC header: snbase u16 | mask 6B | count u8 | index u8 | kind u8 | rsvd
FEC_HDR_LEN = 12
#: offsets representable in the protected-seq mask (RFC 5109's 48-bit shape)
MASK_BITS = 48
KIND_XOR, KIND_RS = 0, 1
KIND_NAMES = {KIND_XOR: "xor", KIND_RS: "rs"}


def _mask_from_deltas(deltas) -> bytes:
    bits = 0
    for d in deltas:
        bits |= 1 << (MASK_BITS - 1 - d)
    return bits.to_bytes(6, "big")


def _deltas_from_mask(mask: bytes) -> list[int]:
    bits = int.from_bytes(mask, "big")
    return [d for d in range(MASK_BITS) if bits & (1 << (MASK_BITS - 1 - d))]


def build_parity_packet(*, fec_pt: int, fec_seq: int, ts: int, ssrc: int,
                        snbase: int, deltas, idx: int, kind: int,
                        payload: bytes) -> bytes:
    hdr = struct.pack("!BBHII", 0x80, fec_pt & 0x7F, fec_seq & 0xFFFF,
                      ts & 0xFFFFFFFF, ssrc & 0xFFFFFFFF)
    fec = struct.pack("!H", snbase & 0xFFFF) + _mask_from_deltas(deltas) \
        + bytes((len(list(deltas)) & 0xFF, idx & 0xFF, kind & 0xFF, 0))
    return hdr + fec + payload


def parse_parity_packet(data: bytes) -> dict | None:
    if len(data) < 12 + FEC_HDR_LEN:
        return None
    snbase = struct.unpack_from("!H", data, 12)[0]
    deltas = _deltas_from_mask(data[14:20])
    count, idx, kind = data[20], data[21], data[22]
    if len(deltas) != count or kind not in KIND_NAMES:
        return None
    return {"seq": struct.unpack_from("!H", data, 2)[0],
            "snbase": snbase, "deltas": deltas, "idx": idx,
            "kind": kind, "payload": data[12 + FEC_HDR_LEN:]}


def build_rtx_packet(orig_wire: bytes, *, rtx_pt: int, rtx_seq: int) -> bytes:
    """RFC 4588-shaped retransmission of one already-rewritten wire
    packet: header copied (marker preserved), PT swapped to the RTX
    payload type, fresh RTX seq, OSN = the original OUTPUT seq as the
    first two payload bytes."""
    hdr = bytearray(orig_wire[:12])
    osn = bytes(hdr[2:4])
    hdr[1] = (hdr[1] & 0x80) | (rtx_pt & 0x7F)
    struct.pack_into("!H", hdr, 2, rtx_seq & 0xFFFF)
    return bytes(hdr) + osn + orig_wire[12:]


def restore_rtx_packet(data: bytes, *, media_pt: int) -> tuple[int, bytes]:
    """(original seq, original wire bytes) from an RTX packet."""
    osn = struct.unpack_from("!H", data, 12)[0]
    hdr = bytearray(data[:12])
    hdr[1] = (hdr[1] & 0x80) | (media_pt & 0x7F)
    struct.pack_into("!H", hdr, 2, osn)
    return osn, bytes(hdr) + data[14:]


# --------------------------------------------------------------- rate control
#: the closed per-subscriber overhead ladder the controller walks
OVERHEAD_LADDER = (0.0, 0.05, 0.10, 0.20, 0.30)
LOSS_FEC_NOW = 0.20          # one report at/above → step up immediately
LOSS_FEC_SLOW = 0.02         # this many...
NUM_LOSSY_TO_STEP = 3        # ...consecutive reports above SLOW → step up
LOSS_FEC_CLEAN = 0.005       # reports below this...
NUM_CLEAN_TO_STEP = 6        # ...this many times → step down
#: NADU buffer distress thresholds (same gauges quality.py reads)
NADU_DELAY_UNKNOWN = 0xFFFF
NADU_DISTRESS_DELAY_MS = 150
NADU_DISTRESS_FREE_64B = 24


class FecRateController:
    """Per-subscriber closed-loop FEC overhead — the ``QualityController``
    hysteresis shape over the :data:`OVERHEAD_LADDER`.

    Loss pressure (RR ``fraction_lost``) walks overhead UP until the
    current rung covers the observed loss; clean reports decay it one
    rung at a time.  NADU buffer distress walks it DOWN instead —
    parity is bitrate, and a receiver that cannot buffer what it
    already gets recovers through RTX, not more FEC (the NACK-vs-FEC
    split)."""

    def __init__(self, max_overhead: float = OVERHEAD_LADDER[-1]):
        self.max_overhead = max(0.0, min(max_overhead,
                                         OVERHEAD_LADDER[-1]))
        self._idx = 0
        self._lossy = 0
        self._clean = 0
        self.steps_up = 0
        self.steps_down = 0
        self.last_fraction_lost = 0.0

    @property
    def overhead(self) -> float:
        return min(OVERHEAD_LADDER[self._idx], self.max_overhead)

    @property
    def at_ceiling(self) -> bool:
        """No higher rung is within ``max_overhead``: loss can no longer
        be answered with more parity."""
        nxt = self._idx + 1
        return (nxt >= len(OVERHEAD_LADDER)
                or OVERHEAD_LADDER[nxt] > self.max_overhead)

    def parity_rows(self, window: int, *, kind: int = KIND_RS) -> int:
        r = int(np.ceil(self.overhead * window))
        if kind == KIND_XOR:
            r = min(r, 1)
        return min(r, MAX_PARITY_ROWS)

    def on_receiver_report(self, fraction_lost: float) -> float:
        self.last_fraction_lost = float(fraction_lost)
        if fraction_lost >= LOSS_FEC_NOW:
            self._step(+1)
            self._lossy = self._clean = 0
            return self.overhead
        if fraction_lost >= LOSS_FEC_SLOW:
            self._lossy += 1
            self._clean = 0
            # climb only while the rung undershoots the observed loss —
            # the residual is RTX's job once parity covers the rate
            if self._lossy >= NUM_LOSSY_TO_STEP \
                    and fraction_lost > self.overhead:
                self._step(+1)
                self._lossy = 0
        elif fraction_lost <= LOSS_FEC_CLEAN:
            self._clean += 1
            self._lossy = 0
            if self._clean >= NUM_CLEAN_TO_STEP:
                self._step(-1)
                self._clean = 0
        else:
            self._lossy = self._clean = 0
        return self.overhead

    def on_nadu(self, playout_delay_ms: int, free_buffer_64b: int) -> float:
        """Buffer distress shifts the split toward RTX: one rung down
        per distressed report run (hysteresis via the clean counter)."""
        delay_known = playout_delay_ms != NADU_DELAY_UNKNOWN
        distressed = ((delay_known
                       and playout_delay_ms < NADU_DISTRESS_DELAY_MS)
                      or free_buffer_64b == 0
                      or 0 < free_buffer_64b < NADU_DISTRESS_FREE_64B)
        if distressed:
            self._lossy = 0
            self._clean += 1
            if self._clean >= NUM_LOSSY_TO_STEP:
                self._step(-1)
                self._clean = 0
        return self.overhead

    def _step(self, d: int) -> None:
        new = max(0, min(len(OVERHEAD_LADDER) - 1, self._idx + d))
        while new > 0 and OVERHEAD_LADDER[new] > self.max_overhead:
            new -= 1
        if new > self._idx:
            self.steps_up += 1
        elif new < self._idx:
            self.steps_down += 1
        self._idx = new


def residual_loss(fraction_lost: float, window: int, rows: int) -> float:
    """The share of a ``window``'s packets that ``rows`` parity rows
    leave lost at loss ``fraction_lost``, losses taken as independent: a
    window that loses more than ``rows`` packets keeps every one of them
    (the rows repair any ``rows`` erasures, and no more)."""
    p = min(max(float(fraction_lost), 0.0), 1.0)
    return sum(x * math.comb(window, x) * p ** x * (1 - p) ** (window - x)
               for x in range(rows + 1, window + 1)) / window


#: parity rows per window ceiling (8 of 48 mask slots; overhead ladder
#: tops out well below this for every supported window size)
MAX_PARITY_ROWS = 8
#: the byte pad of a window's rows: its longest packet, pow2 from this
PARITY_MIN_BYTES = 256
#: a NACK naming a seq replayed less than this ago is served by that
#: replay.  A player repeats its NACK every interval of its own (200 ms
#: is common) while the hole stays open; after the event loop stood
#: still, those repeats are read back to back, and replaying each drains
#: the output's token bucket into give-ups for a healthy player
RTX_REPEAT_MS = 100

#: the parity programs this process has traced and loaded, (K, B, R)
_BUILT: set[tuple[int, int, int]] = set()


def parity_programs(cfg: "FecConfig", slot_bytes: int) -> set:
    """Every ``fec_parity_window_step`` shape a stream's windows can
    take: rows ``[K, B]`` × coefficients ``[R, K]`` with K the window,
    B each byte pad up to the ring slot's (``_window_parity``'s rule),
    R each row pad up to the most rows the rate controller asks for."""
    from ..ops.staging import pow2
    k = cfg.window
    rows = min(int(np.ceil(cfg.max_overhead * k)), MAX_PARITY_ROWS)
    if cfg.kind_code == KIND_XOR:
        rows = min(rows, 1)
    b_pads, b = [], PARITY_MIN_BYTES
    while True:
        b_pads.append(b)
        if b >= slot_bytes:
            break
        b <<= 1
    return {(k, b, pow2(r, 1)) for b in b_pads for r in range(1, rows + 1)}


def load_parity_programs(programs) -> None:
    """Trace and load each program not built yet, by a zero pass:
    nothing is emitted and nothing is checked."""
    from ..models.relay_pipeline import fec_parity_window_step
    for k, b, r in sorted(programs):
        if (k, b, r) in _BUILT:
            continue
        np.asarray(fec_parity_window_step(np.zeros((k, b), np.uint8),
                                          np.zeros((r, k), np.uint8)))
        _BUILT.add((k, b, r))


@dataclass(frozen=True)
class FecConfig:
    """The reliability-tier tunables (server config ``fec_*`` keys)."""

    window: int = 16              # media packets per FEC window
    max_overhead: float = 0.30    # parity budget ceiling (ratio of window)
    kind: str = "rs"              # "rs" | "xor" (xor caps parity at 1 row)
    payload_type: int = 127       # parity packets' RTP PT
    rtx_payload_type: int = 126   # RTX replays' RTP PT
    rtx_budget_per_sec: float = 64.0   # token refill per output
    rtx_burst: int = 32                # token bucket depth
    use_device: bool = True       # device parity (host oracle checked)

    @property
    def kind_code(self) -> int:
        return KIND_XOR if self.kind == "xor" else KIND_RS

    def validate(self) -> "FecConfig":
        if not 2 <= self.window <= MASK_BITS:
            raise ValueError(f"fec_window must be 2..{MASK_BITS}, "
                             f"got {self.window}")
        if self.kind not in ("rs", "xor"):
            raise ValueError(f"fec_kind must be rs|xor, got {self.kind!r}")
        for name, pt in (("fec_payload_type", self.payload_type),
                         ("rtx_payload_type", self.rtx_payload_type)):
            if not 0 <= pt <= 127:
                raise ValueError(f"{name} must be 0..127, got {pt}")
        if self.payload_type == self.rtx_payload_type:
            # colliding PTs would make receivers parse parity as RTX
            # (or vice versa) — corruption, not degradation
            raise ValueError(
                f"fec_payload_type and rtx_payload_type must differ "
                f"(both {self.payload_type})")
        return self


class FecOutputState:
    """Per-subscriber reliability state riding on a ``RelayOutput`` as
    ``out.fec``: the closed-loop controller, the parity seq space, and
    the RTX token bucket.  Attached by the RTSP layer at SETUP;
    registered with the stream's :class:`StreamFec` at PLAY."""

    def __init__(self, cfg: FecConfig):
        self.cfg = cfg
        self.controller = FecRateController(cfg.max_overhead)
        self.fec_seq = 0
        self.rtx_seq = 0
        self.next_window: int | None = None    # set at stream registration
        self.parity_sent = 0
        self.rtx_sent = 0
        self.rtx_giveups = 0
        self._tokens = float(cfg.rtx_burst)
        self._last_refill_ms: int | None = None
        self._giveup_reported = False
        #: seqs given up since the output's last judged report
        self.giveups_unjudged = 0
        #: output seq -> when it was last replayed (``RTX_REPEAT_MS``)
        self._replayed: dict[int, int] = {}

    def unrepaired(self, fraction_lost: float) -> float:
        """What this output's quality controller hears of a judged loss
        fraction, once its rate controller has heard it.  While that
        controller can still climb, nothing: the tier's answer to loss
        is its next rung, and a viewer's rare long burst (20 packets,
        1.5 s of a 13 packets/s stream, read as 30 % of a 64-packet
        judgment) moves the rung, not the frame rate.
        At its ceiling, the share its parity leaves unrepaired
        (:func:`residual_loss`): from ≈ 27 % raw loss at 5 rows of 16
        that is ``LOSS_THIN_SLOW``, and at 40 % ``LOSS_THIN_NOW``.  No
        less than ``LOSS_THIN_SLOW`` where the RTX budget gave up since
        the last judgment: the tier failed in that interval.  One
        interval of give-ups (a burst of NACKs after a stall) thins
        nothing, and three in a row thin, as three lossy reports do."""
        c, k = self.controller, self.cfg.window
        lost = 0.0
        if c.at_ceiling:
            lost = residual_loss(fraction_lost, k, c.parity_rows(
                k, kind=self.cfg.kind_code))
        if self.giveups_unjudged:
            self.giveups_unjudged = 0
            lost = max(lost, LOSS_THIN_SLOW)
        return lost

    def repeats(self, seq: int, now_ms: int) -> bool:
        """True when ``seq`` was replayed less than ``RTX_REPEAT_MS``
        ago: that replay serves this NACK too."""
        t = self._replayed.get(seq)
        return t is not None and now_ms - t < RTX_REPEAT_MS

    def note_replay(self, seq: int, now_ms: int) -> None:
        replayed = self._replayed
        replayed[seq] = now_ms
        if len(replayed) > 64:
            self._replayed = {s: t for s, t in replayed.items()
                              if now_ms - t < RTX_REPEAT_MS}

    def on_receiver_report(self, out, fraction_lost: float) -> float:
        """The rate controller hears a judged report of ``out``'s
        (``FecRateController.on_receiver_report``); returns the overhead."""
        parked = not self.controller._idx
        ratio = self.controller.on_receiver_report(fraction_lost)
        self._mark_if_moved(out, parked)
        return ratio

    def on_nadu(self, out, playout_delay_ms: int,
                free_buffer_64b: int) -> float:
        parked = not self.controller._idx
        ratio = self.controller.on_nadu(playout_delay_ms, free_buffer_64b)
        self._mark_if_moved(out, parked)
        return ratio

    def _mark_if_moved(self, out, parked: bool) -> None:
        """A report that moved the controller onto or off rung 0 leaves
        a cursor for the tick to park or place (``StreamFec.owes``): the
        stream is marked for the next wake, as ingest marks it."""
        cell = out._plan_cell
        if cell is not None and parked != (not self.controller._idx):
            cell.mark()
            obs.FEC_TIER_MARKS.inc(reason="rung")

    def refill(self, now_ms: int) -> None:
        if self._last_refill_ms is None:
            self._last_refill_ms = now_ms
            return
        dt = max(now_ms - self._last_refill_ms, 0) / 1000.0
        self._last_refill_ms = now_ms
        self._tokens = min(self._tokens + dt * self.cfg.rtx_budget_per_sec,
                           float(self.cfg.rtx_burst))

    def take_rtx_token(self, now_ms: int) -> bool:
        self.refill(now_ms)
        if self._tokens >= 1.0:
            self._tokens -= 1.0
            return True
        return False


class StreamFec:
    """Per-stream FEC engine: window accounting, the ONE device parity
    pass per (window, stream) shared by every subscriber, the host GF
    oracle gate, and per-output parity emission.

    Windows are aligned to the absolute-id grid (window ``w`` covers
    ring ids ``[w·k, (w+1)·k)``), so every subscriber of a stream
    shares the same protected sets and the device work is paid once.
    ``tick`` rides the engines' shared ``relay_rtcp`` tail — both the
    scalar oracle and the TPU engine emit identical parity bytes by
    construction."""

    #: windows of cached parity kept — must cover tick()'s per-output
    #: catch-up budget (8), or a multi-subscriber backlog recomputes
    #: the device passes the shared cache exists to amortize
    CACHE_WINDOWS = 8

    def __init__(self, stream, cfg: FecConfig):
        self.stream = stream
        self.cfg = cfg.validate()
        self._states: list[tuple[object, FecOutputState]] = []
        #: window id → (deltas, snbase_src_seq, lens, max_len, parity,
        #: row_slots) or None for a skipped window
        self._cache: dict[int, tuple | None] = {}
        self._cached_rows: dict[int, int] = {}     # window → parity rows
        #: latched by the first device/oracle disagreement: this stream
        #: serves host-computed parity from then on (the wire is always
        #: oracle-true either way)
        self.host_fallback = False
        self.oracle_mismatches = 0
        self.windows_emitted = 0
        self.windows_skipped = 0
        self.device_passes = 0
        #: parity programs still to load ahead of the windows that need
        #: them (``parity_programs``), one a pass once media flows
        self._to_load: list = []
        #: what the last ``tick`` left for the next pass to do, which no
        #: ingest, plan move or wheel timer will bring a step for: the
        #: reason ``owes`` gives, or None (``relay.pump._step``'s carry)
        self.owes_pass: str | None = None

    # -- registration -------------------------------------------------
    def add_output(self, out) -> None:
        f = getattr(out, "fec", None)
        if f is None:
            return
        if self.stream.info.payload_type in (f.cfg.payload_type,
                                             f.cfg.rtx_payload_type):
            # this stream's MEDIA payload type collides with the
            # parity/RTX PT: emitting would make receivers parse parity
            # bytes as media — leave this stream unprotected instead of
            # corrupting it (config validation can't know per-SDP PTs)
            out.fec = None
            return
        ring = self.stream.rtp_ring
        if not self._states and self.cfg.use_device:
            # the parity shapes are a closed set, loaded with the
            # stream's first FEC output and not by the window that first
            # meets one (the megabatch scheduler's discipline: before
            # media all of them, with media flowing one a pass)
            self._to_load = sorted(
                parity_programs(self.cfg, ring.data.shape[1]) - _BUILT)
            if ring.head == 0:
                load_parity_programs(self._to_load)
                self._to_load = []
        if f.next_window is None:
            # first FULL window after this subscriber joined — parity
            # must only describe packets the output actually sent
            k = self.cfg.window
            f.next_window = (ring.head + k - 1) // k
        self._states.append((out, f))

    def remove_output(self, out) -> None:
        self._states = [(o, f) for o, f in self._states if o is not out]

    @property
    def outputs(self) -> list:
        return [o for o, _ in self._states]

    # -- the per-pass hook ---------------------------------------------
    def tick(self, now_ms: int) -> int:
        """Advance every subscriber's window cursor past fully-sent
        windows, emitting parity for each; returns parity packets sent.
        Bounded per call: a subscriber that fell behind emits at most
        a handful of windows per pass instead of stalling the pump.
        Leaves ``owes_pass``: programs still to load, or a backlog past
        the budget.  (It parks and places every cursor: none is owed.)"""
        self.owes_pass = None
        if not self._states:
            return 0
        if self._to_load:
            load_parity_programs(self._to_load[:1])
            del self._to_load[:1]
            if self._to_load:
                self.owes_pass = "load"
        ring = self.stream.rtp_ring
        k = self.cfg.window
        sent = 0
        max_ratio = 0.0
        low = None                      # the lowest cursor, for the prune
        for out, f in self._states:
            if not f.controller._idx:
                # rung 0 sends no parity: the cursor is parked, and
                # restarts at the live edge when the rung rises
                f.next_window = None
                continue
            max_ratio = max(max_ratio, f.controller.overhead)
            if f.next_window is None:
                f.next_window = (ring.head + k - 1) // k
            if low is None or f.next_window < low:
                low = f.next_window
            if out.bookmark is None:
                continue
            if not out.thinning.passthrough():
                # a thinned output deliberately dropped frames: parity
                # describing packets it never sent would make the
                # receiver "recover" them — hold the cursor at the live
                # edge until the filter is passthrough again
                f.next_window = max(f.next_window, ring.head // k)
                continue
            for _ in range(8):             # per-pass window budget
                w = f.next_window
                end = (w + 1) * k
                if end > ring.head or out.bookmark < end:
                    break
                if w * k >= ring.tail:
                    sent += self._emit_window(out, f, w, now_ms)
                f.next_window = w + 1
            else:                       # the budget ran out: a backlog?
                if self.owes_pass is None and self._window_owed(out, f):
                    self.owes_pass = "budget"
        for w in [w for w in self._cache
                  if low is not None and w < low - self.CACHE_WINDOWS]:
            self._cache.pop(w, None)
            self._cached_rows.pop(w, None)
        if self.stream.session_path is not None:
            # UNCONDITIONAL set (the qos-gauge recovery rule): a
            # departed connection's close() drops this child for the
            # whole path, and a change-latch would leave a surviving
            # FEC subscriber's gauge permanently absent — the set is a
            # dict store under a lock, cheap enough for the pass tail
            obs.FEC_OVERHEAD_RATIO.set(
                round(max_ratio, 4), path=self.stream.session_path,
                track=str(self.stream.info.track_id))
        return sent

    def _window_owed(self, out, f: FecOutputState) -> bool:
        """The output's next window is complete and sent: the tick
        emits it at once, within its budget."""
        end = (f.next_window + 1) * self.cfg.window
        return end <= self.stream.rtp_ring.head and out.bookmark >= end

    def owes(self) -> str | None:
        """``owes_pass`` from the state as it stands, for a reader that
        was not in the step (``relay.pump.needs_step``): ``load`` —
        programs still to load; ``cursor`` — a rung-0 output's cursor
        not parked or a higher rung's not placed (a report moved the
        rung); ``budget`` — a window the tick would emit now.  Only
        ingest (``end ≤ head``) and a step (the bookmark) make a window
        owed, and a step's tick emits all it may: what is left past its
        budget is the backlog.  None: a tick now would do nothing but
        set the overhead gauge."""
        if not self._states:
            return None
        if self._to_load:
            return "load"
        for out, f in self._states:
            if not f.controller._idx:
                if f.next_window is not None:
                    return "cursor"
            elif f.next_window is None:
                return "cursor"
            elif (out.bookmark is not None and out.thinning.passthrough()
                  and self._window_owed(out, f)):
                return "budget"
        return None

    # -- window parity --------------------------------------------------
    def _window_rows(self, w: int):
        """(row_slots, deltas, src_seqs, lens, max_len) of window ``w``'s
        protected packets, or None when the window is unprotectable
        (empty, seq deltas past the mask, duplicate seqs)."""
        ring = self.stream.rtp_ring
        k = self.cfg.window
        ids = np.arange(w * k, (w + 1) * k)
        ids = ids[(ids >= ring.tail) & (ids < ring.head)]
        if len(ids) == 0:
            return None
        slots = (ids % ring.capacity).astype(np.int64)
        lens = ring.length[slots]
        keep = lens >= 12
        if not keep.any():
            return None
        slots, lens = slots[keep], lens[keep]
        seqs = ring.seq[slots].astype(np.int64)
        deltas = (seqs - seqs[0]) & 0xFFFF
        if deltas.max() >= MASK_BITS or len(set(deltas.tolist())) != len(deltas):
            self.windows_skipped += 1
            return None
        return slots, deltas.tolist(), seqs, lens, int(lens.max())

    def _window_parity(self, w: int, n_parity: int):
        """Device-or-host GF parity over window ``w``'s ring rows, host
        oracle checked, cached per window (recomputed only when a
        subscriber needs MORE parity rows than cached)."""
        if w in self._cache and self._cached_rows.get(w, 0) >= n_parity:
            return self._cache[w]
        meta = self._window_rows(w)
        if meta is None:
            self._cache[w] = None
            self._cached_rows[w] = MAX_PARITY_ROWS
            while len(self._cache) > self.CACHE_WINDOWS:
                oldest = min(self._cache)
                self._cache.pop(oldest, None)
                self._cached_rows.pop(oldest, None)
            return None
        slots, deltas, seqs, lens, max_len = meta
        ring = self.stream.rtp_ring
        k = self.cfg.window
        # fixed-slot rows, byte axis pow2-padded so jit specializations
        # latch per shape family (the ONE rounding rule, ops.staging)
        from ..ops.staging import pow2
        b_pad = pow2(max_len, PARITY_MIN_BYTES)
        rows = np.zeros((k, b_pad), np.uint8)
        width = min(b_pad, ring.data.shape[1])
        rows[:len(slots), :width] = ring.data[slots, :width]
        # zero the slack past each packet's length: the native recvmmsg
        # drain can leave a previous occupant's bytes beyond length[s]
        rows[:len(slots)][np.arange(b_pad)[None, :]
                          >= np.asarray(lens)[:, None]] = 0
        r_pad = pow2(n_parity, 1)
        coeff = np.zeros((r_pad, k), np.uint8)
        coeff[:, :len(deltas)] = coeff_rows(deltas, r_pad)
        device = self.cfg.use_device and not self.host_fallback
        span = obs.TRACER.open(
            "fec.parity_pass", "fec", rows=len(slots), b_pad=b_pad,
            r_pad=r_pad, source="device" if device else "host",
            trace_id=self.stream.trace_id)
        t0 = t1 = obs.t0_of(span)
        if device:
            from ..models.relay_pipeline import fec_parity_window_step
            dev = np.asarray(fec_parity_window_step(rows, coeff))
            t1 = time.perf_counter_ns()
            obs.FEC_PARITY_PASS_SECONDS.observe((t1 - t0) / 1e9,
                                                part="device")
        # the host oracle, computed for every window: the device's rows
        # reach the wire only once they equal it
        host = gf_matmul(coeff, rows)
        obs.FEC_PARITY_PASS_SECONDS.observe(
            (obs.TRACER.close(span) - t1) / 1e9, part="oracle")
        parity = host
        if device:
            obs.TPU_H2D_BYTES.inc(rows.nbytes + coeff.nbytes)
            obs.TPU_D2H_BYTES.inc(dev.nbytes)
            self.device_passes += 1
            if not np.array_equal(dev, host):
                # the _install_segment discipline: count, discard the
                # device result, degrade THIS stream to host parity —
                # the wire never carries an unchecked row
                self.oracle_mismatches += 1
                obs.FEC_PARITY_ORACLE_MISMATCH.inc()
                if not self.host_fallback:
                    self.host_fallback = True
                    obs.EVENTS.emit(
                        "fec.host_fallback", level="warn",
                        stream=self.stream.session_path,
                        trace_id=self.stream.trace_id,
                        mismatches=self.oracle_mismatches)
            else:
                parity = dev
        entry = (slots, deltas, seqs, lens, max_len, parity)
        self._cache[w] = entry
        self._cached_rows[w] = r_pad
        # HARD size bound, oldest-first: the min(next_window) prune in
        # tick() cannot move while one subscriber is stalled on
        # WOULD_BLOCK, and a pinned threshold must not let the cache
        # grow one multi-KB entry per window for minutes until the
        # stalled connection is reaped (a later advance past an evicted
        # window simply recomputes it)
        while len(self._cache) > self.CACHE_WINDOWS:
            oldest = min(self._cache)
            self._cache.pop(oldest, None)
            self._cached_rows.pop(oldest, None)
        return entry

    def _emit_window(self, out, f: FecOutputState, w: int,
                     now_ms: int) -> int:
        kind = self.cfg.kind_code
        r = f.controller.parity_rows(self.cfg.window, kind=kind)
        if r <= 0:
            return 0
        entry = self._window_parity(w, r)
        if entry is None:
            return 0
        win_slots, deltas, seqs, lens, max_len, parity = entry
        m = len(deltas)
        coeff = coeff_rows(deltas, r)
        # per-subscriber pieces: the rewritten 12-byte headers and the
        # 2-byte wire-length fields (host numpy, O(window × 12))
        ring = self.stream.rtp_ring
        rw = out.rewrite
        if rw.base_src_seq < 0:
            return 0                       # rebase never latched: unsent
        hdrs = np.zeros((m, 12), np.uint8)
        src_rows = ring.data[win_slots, :12]
        hdrs[:, 0:2] = src_rows[:, 0:2]
        out_seqs = (seqs - rw.base_src_seq + rw.out_seq_start) & 0xFFFF
        hdrs[:, 2:4] = out_seqs.astype(">u2")[:, None].view(np.uint8)
        ts = ring.timestamp[win_slots].astype(np.int64)
        out_ts = (ts - rw.base_src_ts + rw.out_ts_start) & 0xFFFFFFFF
        hdrs[:, 4:8] = out_ts.astype(">u4")[:, None].view(np.uint8)
        hdrs[:, 8:12] = np.frombuffer(
            struct.pack("!I", rw.ssrc & 0xFFFFFFFF), np.uint8)
        len_rows = np.asarray(lens, np.uint16).astype(">u2")[:, None] \
            .view(np.uint8).reshape(m, 2)
        hdr_par = gf_matmul(coeff, hdrs)
        len_par = gf_matmul(coeff, len_rows)
        snbase = int(out_seqs[0])
        sent = 0
        from .output import WriteResult
        for p in range(r):
            payload = (len_par[p].tobytes() + hdr_par[p].tobytes()
                       + parity[p, 12:max_len].tobytes())
            pkt = build_parity_packet(
                fec_pt=self.cfg.payload_type, fec_seq=f.fec_seq,
                ts=int(out_ts[-1]), ssrc=rw.ssrc, snbase=snbase,
                deltas=deltas, idx=p, kind=kind, payload=payload)
            if out.send_bytes(pkt, is_rtcp=False) is WriteResult.OK:
                f.fec_seq = (f.fec_seq + 1) & 0xFFFF
                f.parity_sent += 1
                sent += 1
        if sent:
            obs.FEC_PARITY_PACKETS.inc(sent, kind=KIND_NAMES[kind])
            self.windows_emitted += 1
        return sent

    # -- NACK / RTX -------------------------------------------------------
    def replay_nacked(self, out, seqs, now_ms: int) -> int:
        """Resolve NACKed OUTPUT seqs back to live ring bookmarks
        through the inverse affine rewrite and replay them as RTX
        packets — the ring IS the retransmission buffer; returns the
        replays sent.  A hole the output's thinning made is never owed,
        and a seq replayed less than ``RTX_REPEAT_MS`` ago is served by
        that replay: neither takes a token or is a give-up.  The
        per-output token bucket bounds replay; exhausted budget counts
        ``rtx_giveup_total`` once per seq and is heard by the output's
        own quality controller with its next judged report
        (``FecOutputState.unrepaired``) — a viewer's link is not its
        stream's device health."""
        f = getattr(out, "fec", None)
        if f is None:
            return 0
        seqs = list(seqs)
        span = obs.TRACER.open("rtx.replay", "fec", seqs=len(seqs),
                               trace_id=self.stream.trace_id)
        ring = self.stream.rtp_ring
        rw = out.rewrite
        sent = giveups = withheld = evicted = repeated = 0
        thinning = out.thinning
        if not thinning.passthrough():
            # a thinned output's seq gaps are DELIBERATE frame drops
            # (map_seq is pure affine, so thinned frames leave output-
            # seq holes a conformant receiver will NACK): replaying
            # them would defeat thinning and drain the token bucket —
            # the same guard the parity cursor applies in tick()
            withheld, seqs = len(seqs), ()
        elif rw.base_src_seq < 0:
            evicted, seqs = len(seqs), ()
        from .output import WriteResult
        for s_out in seqs:
            if thinning.withheld(s_out):
                withheld += 1              # a hole thinning made
                continue
            src_seq = (int(s_out) - rw.out_seq_start
                       + rw.base_src_seq) & 0xFFFF
            pid = _find_ring_id(ring, src_seq)
            if pid is None:
                evicted += 1               # evicted / never ingested
                continue
            if f.repeats(s_out, now_ms):
                repeated += 1              # a replay just sent serves it
                continue
            if not f.take_rtx_token(now_ms):
                giveups += 1
                f.rtx_giveups += 1
                obs.RTX_GIVEUP.inc()
                if not f._giveup_reported:
                    f._giveup_reported = True
                    obs.EVENTS.emit(
                        "rtx.giveup", level="warn",
                        stream=self.stream.session_path,
                        trace_id=self.stream.trace_id,
                        giveups=f.rtx_giveups)
                continue
            slot = ring.slot(pid)
            wire = bytearray(ring.data[slot, :ring.length[slot]].tobytes())
            struct.pack_into("!H", wire, 2, s_out & 0xFFFF)
            struct.pack_into(
                "!I", wire, 4,
                (int(ring.timestamp[slot]) - rw.base_src_ts
                 + rw.out_ts_start) & 0xFFFFFFFF)
            struct.pack_into("!I", wire, 8, rw.ssrc & 0xFFFFFFFF)
            pkt = build_rtx_packet(bytes(wire),
                                   rtx_pt=self.cfg.rtx_payload_type,
                                   rtx_seq=f.rtx_seq)
            if out.send_bytes(pkt, is_rtcp=False) is WriteResult.OK:
                f.rtx_seq = (f.rtx_seq + 1) & 0xFFFF
                f.rtx_sent += 1
                f.note_replay(s_out, now_ms)
                sent += 1
                obs.RTX_SENT.inc()
        if sent:
            # credit the repairs to this subscriber's audience row
            # (one call per NACK batch — cold control path)
            obs.AUDIENCE.note_credit(out, rtx=sent)
        f.giveups_unjudged += giveups
        for result, n in (("sent", sent), ("giveup", giveups),
                          ("withheld", withheld), ("evicted", evicted),
                          ("repeat", repeated)):
            obs.RTX_NACKED_SEQS.inc(n, result=result)
        obs.TRACER.close(span, sent=sent, giveups=giveups,
                         withheld=withheld, repeats=repeated)
        return sent


def _find_ring_id(ring, src_seq: int) -> int | None:
    """Live absolute ring id whose packet carries RTP seq ``src_seq``
    (the NACK→bookmark resolution).  Slot-indexed seq array scan — one
    vectorized compare over the ring, no per-packet Python."""
    for s in np.flatnonzero(ring.seq == src_seq):
        s = int(s)
        if ring.head <= 0:
            return None
        pid = ring.head - 1 - ((ring.head - 1 - s) % ring.capacity)
        if ring.valid(pid) and ring.length[s] >= 12:
            return pid
    return None


def drop_overhead_gauge(path: str, track_id) -> None:
    """Remove a departed stream's FEC overhead gauge (the qos drop rule)."""
    obs.FEC_OVERHEAD_RATIO.remove(path=path or "-", track=str(track_id))


# ----------------------------------------------------------- receiver model
class FecReceiver:
    """Receiver-side model: byte-exact reconstruction from parity + RTX.

    The tests, the lossy soak player and the bench feed every received
    datagram through :meth:`on_packet`; media packets keyed by UNWRAPPED
    output seq, parity grouped per window, RTX replays restored to
    their original wire bytes.  ``fec_recovered_total`` counts every
    parity-recovered packet (in-process receivers share the server's
    registry, so recovery is a scrapeable quantity)."""

    def __init__(self, *, media_pt: int = 96, fec_pt: int = 127,
                 rtx_pt: int = 126, subscriber=None):
        self.media_pt = media_pt
        self.fec_pt = fec_pt
        self.rtx_pt = rtx_pt
        #: optional audience binding (an object carrying
        #: ``audience_block``/``audience_row`` — typically the server-
        #: side RelayOutput serving this receiver): parity recoveries
        #: are credited to that subscriber's ``fec`` column so QoE
        #: accounts repairs the viewer actually benefited from
        self.subscriber = subscriber
        self.media: dict[int, bytes] = {}      # ext seq → wire bytes
        self.recovered: dict[int, bytes] = {}  # via FEC solve
        self.rtx_restored: dict[int, bytes] = {}
        #: (snbase_ext, mask-deltas tuple) → {idx: payload}
        self._groups: dict[tuple, dict] = {}
        self._group_kind: dict[tuple, int] = {}
        self._ext_hi: int | None = None
        self.duplicates = 0
        self.junk = 0

    # -- seq unwrap ----------------------------------------------------
    def _unwrap(self, seq: int) -> int:
        if self._ext_hi is None:
            self._ext_hi = seq
            return seq
        base = self._ext_hi & 0xFFFF
        delta = (seq - base) & 0xFFFF
        if delta < 0x8000:
            ext = self._ext_hi + delta
            self._ext_hi = max(self._ext_hi, ext)
        else:
            ext = self._ext_hi - ((base - seq) & 0xFFFF)
        return ext

    # -- ingest ----------------------------------------------------------
    def on_packet(self, data: bytes) -> str:
        if len(data) < 12 or data[0] >> 6 != 2:
            self.junk += 1
            return "junk"
        pt = data[1] & 0x7F
        if pt == self.fec_pt:
            p = parse_parity_packet(data)
            if p is None:
                self.junk += 1
                return "junk"
            self._on_parity(p)
            return "fec"
        if pt == self.rtx_pt:
            if len(data) < 14:
                self.junk += 1
                return "junk"
            osn, wire = restore_rtx_packet(data, media_pt=self.media_pt)
            ext = self._unwrap(osn)
            if ext in self.media or ext in self.rtx_restored:
                self.duplicates += 1
                return "dup"
            self.rtx_restored[ext] = wire
            self._try_recover()
            return "rtx"
        if pt == self.media_pt:
            seq = struct.unpack_from("!H", data, 2)[0]
            ext = self._unwrap(seq)
            if ext in self.media:
                self.duplicates += 1
                return "dup"
            self.media[ext] = data
            self._try_recover()
            return "media"
        self.junk += 1
        return "junk"

    def _on_parity(self, p: dict) -> None:
        sn_ext = self._unwrap(p["snbase"])
        key = (sn_ext, tuple(p["deltas"]))
        self._groups.setdefault(key, {})[p["idx"]] = p["payload"]
        self._group_kind[key] = p["kind"]
        self._try_recover()

    # -- reconstruction --------------------------------------------------
    def have(self, ext_seq: int) -> bytes | None:
        return (self.media.get(ext_seq)
                or self.rtx_restored.get(ext_seq)
                or self.recovered.get(ext_seq))

    def missing(self, lo: int, hi: int) -> list[int]:
        """Ext seqs in [lo, hi] with no media/RTX/recovered bytes."""
        return [s for s in range(lo, hi + 1) if self.have(s) is None]

    def _try_recover(self) -> int:
        solved = 0
        for key in list(self._groups):
            sn_ext, deltas = key
            parities = self._groups[key]
            prot = [sn_ext + d for d in deltas]
            miss = [s for s in prot if self.have(s) is None]
            if not miss:
                self._groups.pop(key, None)
                self._group_kind.pop(key, None)
                continue
            if len(miss) > len(parities):
                continue                   # not solvable yet
            # prefer the LOWEST parity indices: consecutive-from-0 rows
            # form a true Vandermonde system (always solvable); an
            # arbitrary index subset can be singular over GF(2^8), which
            # gf_solve reports as None and we simply wait for more rows
            rows_len = len(next(iter(parities.values())))
            if any(len(v) != rows_len for v in parities.values()):
                continue                   # corrupt group
            idxs = sorted(parities)[:len(miss)]
            synd = np.array([np.frombuffer(parities[p], np.uint8)
                             for p in idxs])
            # subtract (XOR) every RECEIVED protected row's contribution
            known_d, known_rows = [], []
            for s, d in zip(prot, deltas):
                wire = self.have(s)
                if wire is None:
                    continue
                row = np.zeros(rows_len, np.uint8)
                row[0:2] = np.frombuffer(
                    struct.pack("!H", len(wire)), np.uint8)
                n = min(len(wire), rows_len - 2)
                row[2:2 + n] = np.frombuffer(wire[:n], np.uint8)
                known_d.append(d)
                known_rows.append(row)
            if known_rows:
                c = coeff_for_indices(known_d, idxs)
                synd ^= gf_matmul(c, np.stack(known_rows))
            miss_d = [d for s, d in zip(prot, deltas)
                      if self.have(s) is None]
            a = coeff_for_indices(miss_d, idxs)
            rows = gf_solve(a, synd, caller="fec_receiver")
            if rows is None:
                continue
            ok = True
            out = {}
            for s, row in zip(miss, rows):
                ln = int(row[0]) << 8 | int(row[1])
                if not 12 <= ln <= rows_len - 2:
                    ok = False
                    break
                out[s] = row[2:2 + ln].tobytes()
            if not ok:
                continue
            for s, wire in out.items():
                self.recovered[s] = wire
                solved += 1
                obs.FEC_RECOVERED.inc()
            self._groups.pop(key, None)
            self._group_kind.pop(key, None)
        if solved and self.subscriber is not None:
            # audience credit: one call per solve batch, never per row
            obs.AUDIENCE.note_credit(self.subscriber, fec=solved)
        return solved


def coeff_for_indices(deltas, parity_idxs) -> np.ndarray:
    """``C[j, i] = α^(d_i · p_j)`` for the receiver's chosen parity
    rows — the encoder matrix restricted to the rows actually used."""
    d = np.asarray(list(deltas), np.int64)
    p = np.asarray(list(parity_idxs), np.int64)
    return GF_EXP512[np.outer(p, d) % 255].astype(np.uint8)


__all__ = [
    "FecConfig", "FecOutputState", "FecRateController", "FecReceiver",
    "StreamFec", "build_parity_packet", "parse_parity_packet",
    "build_rtx_packet", "restore_rtx_packet", "coeff_rows", "gf_matmul",
    "gf_solve", "gf_mul", "gf_pow", "gf_inv", "drop_overhead_gauge",
    "OVERHEAD_LADDER", "KIND_XOR", "KIND_RS", "MASK_BITS",
    "MAX_PARITY_ROWS",
]
