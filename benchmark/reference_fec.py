"""The plain reference for the reliability tier's wire: what a parity
packet and a retransmission owed to one subscriber must be, and how a
receiver repairs its losses with them.  Written from the wire format the
relay documents and from the RFCs, and from nothing of the program:

* **Parity** (RFC 5109's shape).  An RTP header of its own (payload type
  ``fec_payload_type``, its own sequence space, the subscriber's SSRC),
  then a 12-byte FEC header — ``snbase`` (u16, the output sequence
  number of the first protected packet), a 48-bit mask whose bit
  ``47 - d`` marks the packet at offset ``d`` from ``snbase``, the count
  of protected packets, the parity row's index, the kind (0 XOR, 1
  Reed-Solomon), a reserved zero — then the parity payload.  Each
  protected packet contributes the row ``len (u16) ∥ wire packet``,
  zero-padded to the longest such row of the window; parity row ``p`` is
  ``Σ_i α^(d_i · p) · row_i`` over GF(256) with the polynomial
  x^8 + x^4 + x^3 + x^2 + 1 (0x11D) and α = 2 — a Vandermonde row set
  whose row 0 is plain XOR.  Any ``m`` losses of a window with ``m``
  parity rows solve.
* **Retransmission** (RFC 4588 §4): the packet's header with the payload
  type swapped for ``rtx_payload_type`` and a sequence number of the RTX
  stream's own; the original sequence number is the first two payload
  bytes, the original payload follows.

The field's multiplication table here is built from shift-and-add
products, not from log tables, so that it shares no arithmetic with what
it checks.
"""

from __future__ import annotations

import numpy as np

POLY = 0x11D
FEC_HDR_LEN = 12
MASK_BITS = 48
KIND_XOR, KIND_RS = 0, 1


def _gf_mul(a: int, b: int) -> int:
    """Carry-less product of ``a`` and ``b`` reduced by ``POLY``."""
    r = 0
    while b:
        if b & 1:
            r ^= a
        a <<= 1
        if a & 0x100:
            a ^= POLY
        b >>= 1
    return r


#: α^n for n in 0..254
POW = [1]
for _ in range(254):
    POW.append(_gf_mul(POW[-1], 2))
#: MUL[a, b] = a · b in GF(256): row ``a`` multiplies a byte array by ``a``
MUL = np.zeros((256, 256), np.uint8)
MUL[1] = np.arange(256)
for _n in range(1, 255):
    _a = POW[_n]
    # a = α · α^(n-1): one shift-and-reduce of the row before it
    _prev = MUL[POW[_n - 1]].astype(np.int32) << 1
    MUL[_a] = np.where(_prev & 0x100, _prev ^ POLY, _prev)
INV = np.zeros(256, np.uint8)
INV[np.nonzero(MUL == 1)[0]] = np.nonzero(MUL == 1)[1]


def coeff(d: int, p: int) -> int:
    """α^(d·p): the weight of the packet at offset ``d`` in parity row
    ``p``."""
    return POW[(d * p) % 255]


def row_of(wire: bytes, width: int) -> np.ndarray:
    """``len (u16) ∥ wire``, zero-padded to ``width`` bytes."""
    row = np.zeros(width, np.uint8)
    row[0], row[1] = len(wire) >> 8, len(wire) & 0xFF
    row[2:2 + len(wire)] = np.frombuffer(wire, np.uint8)
    return row


def parity_payload(wires: list[bytes], deltas: list[int], idx: int) -> bytes:
    """Parity row ``idx`` over the protected wire packets."""
    width = max(len(w) for w in wires) + 2
    acc = np.zeros(width, np.uint8)
    for w, d in zip(wires, deltas):
        acc ^= MUL[coeff(d, idx)][row_of(w, width)]
    return acc.tobytes()


# ------------------------------------------------------------- the wire
def mask_of(deltas: list[int]) -> bytes:
    bits = 0
    for d in deltas:
        bits |= 1 << (MASK_BITS - 1 - d)
    return bits.to_bytes(6, "big")


def parse_parity(data: bytes) -> dict | None:
    """The FEC header and payload of a parity datagram; None where the
    header does not hang together (a count other than the mask's)."""
    if len(data) < 12 + FEC_HDR_LEN + 2:
        return None
    bits = int.from_bytes(data[14:20], "big")
    deltas = [d for d in range(MASK_BITS)
              if bits >> (MASK_BITS - 1 - d) & 1]
    count, idx, kind = data[20], data[21], data[22]
    if count != len(deltas) or not deltas or kind not in (KIND_XOR, KIND_RS):
        return None
    return {"snbase": int.from_bytes(data[12:14], "big"), "deltas": deltas,
            "idx": idx, "kind": kind, "payload": data[12 + FEC_HDR_LEN:]}


def parity_packet(wires: list[bytes], deltas: list[int], idx: int, *,
                  snbase: int, pt: int, seq: int, ts: int, ssrc: int,
                  kind: int = KIND_RS) -> bytes:
    """A whole parity datagram: what the reference would put on the
    wire (the control puts it in the program's place)."""
    return (bytes((0x80, pt & 0x7F)) + (seq & 0xFFFF).to_bytes(2, "big")
            + (ts & 0xFFFFFFFF).to_bytes(4, "big")
            + (ssrc & 0xFFFFFFFF).to_bytes(4, "big")
            + (snbase & 0xFFFF).to_bytes(2, "big") + mask_of(deltas)
            + bytes((len(deltas), idx, kind, 0))
            + parity_payload(wires, deltas, idx))


def rtx_packet(wire: bytes, *, rtx_pt: int, rtx_seq: int) -> bytes:
    """RFC 4588 retransmission of one wire packet, marker kept."""
    return (wire[:1] + bytes(((wire[1] & 0x80) | (rtx_pt & 0x7F),))
            + (rtx_seq & 0xFFFF).to_bytes(2, "big") + wire[4:12]
            + wire[2:4] + wire[12:])


def restore_rtx(data: bytes, media_pt: int) -> tuple[int, bytes] | None:
    """(original sequence number, original wire packet) of an RTX
    datagram; None where it is too short to carry one."""
    if len(data) < 14:
        return None
    osn = data[12:14]
    return (int.from_bytes(osn, "big"),
            data[:1] + bytes(((data[1] & 0x80) | (media_pt & 0x7F),))
            + osn + data[4:12] + data[14:])


# ------------------------------------------------------------- recovery
def solve(known: dict[int, bytes], missing: list[int],
          rows: dict[int, bytes]) -> dict[int, bytes] | None:
    """The missing packets of one window, by offset, from its received
    packets (``known``: offset → wire) and its parity rows (``rows``:
    index → payload): Gauss-Jordan elimination over GF(256) on the
    Vandermonde rows of the lowest indices.  None where there are fewer
    rows than losses, the system is singular, or a solved length does
    not fit its row."""
    if len(missing) > len(rows) or not missing:
        return None
    width = len(next(iter(rows.values())))
    if any(len(v) != width for v in rows.values()):
        return None
    idxs = sorted(rows)[:len(missing)]
    rhs = np.array([np.frombuffer(rows[p], np.uint8) for p in idxs])
    for d, w in known.items():
        if len(w) + 2 > width:
            return None
        r = row_of(w, width)
        for j, p in enumerate(idxs):
            rhs[j] ^= MUL[coeff(d, p)][r]
    a = np.array([[coeff(d, p) for d in missing] for p in idxs], np.uint8)
    m = len(missing)
    for col in range(m):
        piv = next((r for r in range(col, m) if a[r, col]), None)
        if piv is None:
            return None
        a[[col, piv]], rhs[[col, piv]] = a[[piv, col]], rhs[[piv, col]]
        f = INV[a[col, col]]
        a[col], rhs[col] = MUL[f][a[col]], MUL[f][rhs[col]]
        for r in range(m):
            if r != col and a[r, col]:
                g = a[r, col]
                a[r] ^= MUL[g][a[col]]
                rhs[r] ^= MUL[g][rhs[col]]
    out = {}
    for d, row in zip(missing, rhs):
        n = int(row[0]) << 8 | int(row[1])
        if not 12 <= n <= width - 2:
            return None
        out[d] = row[2:2 + n].tobytes()
    return out


# ---------------------------------------------------------- the judges
def parity_altered(datagrams: list[bytes], expected_at, ssrc: int,
                   fec_pt: int) -> int:
    """Parity datagrams that are not the reference's parity over the
    packets they name.  ``expected_at(seq)`` is the wire packet the
    subscriber is owed under output sequence number ``seq``, or None
    where it is owed none."""
    bad = 0
    for g in datagrams:
        p = parse_parity(g)
        ok = (p is not None and g[0] == 0x80 and g[1] & 0x7F == fec_pt
              and int.from_bytes(g[8:12], "big") == ssrc & 0xFFFFFFFF
              and (p["kind"] == KIND_RS or p["idx"] == 0))
        if ok:
            wires = [expected_at((p["snbase"] + d) & 0xFFFF)
                     for d in p["deltas"]]
            ok = None not in wires and p["payload"] == parity_payload(
                wires, p["deltas"], p["idx"])
        bad += not ok
    return bad


def rtx_altered(datagrams: list[bytes], expected_at, media_pt: int) -> int:
    """RTX datagrams that do not restore to the packet they name."""
    bad = 0
    for g in datagrams:
        r = restore_rtx(g, media_pt)
        bad += r is None or expected_at(r[0]) != r[1]
    return bad


def break_one(datagrams: list[bytes], at: int) -> list[bytes]:
    """The control: one byte of one datagram's payload flipped."""
    out = list(datagrams)
    k = at % len(out)
    p = bytearray(out[k])
    p[-1 - at % (len(p) - 14)] ^= 0x01
    out[k] = bytes(p)
    return out
