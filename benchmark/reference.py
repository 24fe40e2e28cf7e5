"""The plain reference: what a relayed packet must be, written from the
guarantees in the configuration's file and from nothing of the program.

A relay owes each joined subscriber every pushed packet, in order, with
the payload untouched from byte 12 and the first two header bytes (V/P/X
/CC, marker and payload type) untouched, renumbered from the sequence
number its PLAY answer announced, under the SSRC its SETUP answer
announced, at a constant offset from the pusher's timestamps.

``expected_packet`` is that relay, one packet at a time.  ``judge_flow``
holds what a subscriber's socket received against it, packet by packet,
and returns counts; every count's limit is 0 (the comparison is exact).

``break_guarantee`` is the control: the reference's own output with one
stated guarantee broken once.  The comparison must fail it.
"""

from __future__ import annotations

GUARANTEES = ("every_packet", "in_order", "bit_equal", "header_rewritten")


def expected_packet(pushed: bytes, k: int, first_seq: int, ssrc: int,
                    ts_offset: int) -> bytes:
    """The k-th packet a subscriber is owed, made from the k-th packet
    pushed since it joined."""
    ts = (int.from_bytes(pushed[4:8], "big") + ts_offset) & 0xFFFFFFFF
    return (pushed[:2] + ((first_seq + k) & 0xFFFF).to_bytes(2, "big")
            + ts.to_bytes(4, "big") + (ssrc & 0xFFFFFFFF).to_bytes(4, "big")
            + pushed[12:])


def reference_flow(pushed: list[bytes], first_seq: int, ssrc: int,
                   ts_offset: int) -> list[bytes]:
    return [expected_packet(p, k, first_seq, ssrc, ts_offset)
            for k, p in enumerate(pushed)]


def judge_flow(got: list[bytes], pushed: list[bytes], first_seq: int | None,
               ssrc: int | None, repaired: list[bytes] = ()
               ) -> dict[str, int]:
    """Counts of what is wrong with one subscriber's received packets.

    ``missing``: pushed packets that never arrived.  ``out_of_order``:
    arrivals whose place in the pushed order is before an earlier
    arrival's.  ``altered``: arrivals that are no pushed packet's
    rewrite — payload, first two bytes, SSRC, sequence number or
    timestamp offset differ.  ``unannounced``: 1 if the session's
    answers did not carry the SSRC or first sequence number.

    ``repaired``: behind a lossy last mile, the packets the subscriber
    restored from parity or RTX.  They count as held, and as altered
    where they are no pushed packet's rewrite; they came when they were
    restored, so they are no part of the order."""
    out = {"missing": 0, "out_of_order": 0, "altered": 0, "unannounced": 0}
    if first_seq is None or ssrc is None:
        out["unannounced"] = 1
        out["missing"] = len(pushed)
        return out
    got = [g for g in got if len(g) >= 12]
    if not got and not repaired:
        out["missing"] = len(pushed)
        return out
    ts_offset = offset_of((got or repaired)[0], pushed, first_seq)
    seen = bytearray(len(pushed))
    last = -1
    for g in got:
        k = place_of(g, first_seq, len(pushed), last)
        if k >= len(pushed) or g != expected_packet(
                pushed[k], k, first_seq, ssrc, ts_offset):
            out["altered"] += 1
            continue
        if seen[k]:
            out["altered"] += 1         # a duplicate is no pushed packet's
            continue
        seen[k] = 1
        if k < last:
            out["out_of_order"] += 1
        last = max(last, k)
    for g in repaired:
        k = place_of(g, first_seq, len(pushed), last)
        if k >= len(pushed) or seen[k] or g != expected_packet(
                pushed[k], k, first_seq, ssrc, ts_offset):
            out["altered"] += 1
            continue
        seen[k] = 1
    out["missing"] = len(pushed) - sum(seen)
    return out


def offset_of(g: bytes, pushed: list[bytes], first_seq: int) -> int:
    """The session's timestamp offset, as packet ``g`` shows it: the
    offset is constant per session, and the first packet that is in its
    place fixes it."""
    k0 = (int.from_bytes(g[2:4], "big") - first_seq) & 0xFFFF
    if k0 >= len(pushed):
        return 0
    return (int.from_bytes(g[4:8], "big")
            - int.from_bytes(pushed[k0][4:8], "big")) & 0xFFFFFFFF


def place_of(g: bytes, first_seq: int, n_pushed: int, last: int) -> int:
    """Which pushed packet ``g`` is, by its sequence number.  Sequence
    numbers wrap every 65,536 packets: take the place nearest after the
    last one in order."""
    k = (int.from_bytes(g[2:4], "big") - first_seq) & 0xFFFF
    while k + 65536 < n_pushed and k < last - 32768:
        k += 65536
    return k


def break_guarantee(flow: list[bytes], guarantee: str, at: int,
                    source_ssrc: int) -> list[bytes]:
    """The control: the reference's output for one flow with one
    guarantee broken once, at packet ``at``."""
    out = list(flow)
    at = at % max(len(out) - 1, 1)
    if guarantee == "every_packet":
        del out[at]
    elif guarantee == "in_order":
        out[at], out[at + 1] = out[at + 1], out[at]
    elif guarantee == "bit_equal":
        p = bytearray(out[at])
        p[12 + (at % (len(p) - 12))] ^= 0x01
        out[at] = bytes(p)
    elif guarantee == "header_rewritten":
        p = out[at]
        out[at] = p[:8] + source_ssrc.to_bytes(4, "big") + p[12:]
    else:
        raise ValueError(f"unknown guarantee {guarantee!r}")
    return out
