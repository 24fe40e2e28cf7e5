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

A subscriber who joins while the stream runs is owed the same from the
head of the newest keyframe run pushed before its PLAY was written, or
of a later one (the relay's fast start, within its over-buffer window):
``judge_joiner``.

``break_guarantee`` is the control: the reference's own output with one
stated guarantee broken once; ``join_control`` the reference in a
joiner's place with its start, or one packet, wrong.  The comparison
must fail both.
"""

from __future__ import annotations

GUARANTEES = ("every_packet", "in_order", "bit_equal", "header_rewritten")
#: the controls of a joiner: a start inside a GOP, a start at the run
#: before the newest, one packet dropped after a right start
JOIN_CONTROLS = ("join_mid_gop", "join_stale", "join_every_packet")


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
               ssrc: int | None, repaired: list[bytes] = (), start: int = 0
               ) -> dict[str, int]:
    """Counts of what is wrong with one subscriber's received packets.
    It is owed ``pushed[start:]``: the first sequence number stands for
    ``pushed[start]`` (0 for a subscriber joined before media).

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
    pushed = pushed[start:]
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


def judge_joiner(got: list[bytes], pushed: list[bytes], key_heads: list[int],
                 key_head: int, first_seq: int | None, ssrc: int | None,
                 start: int | None) -> dict[str, int]:
    """``judge_flow`` for a subscriber that joined while the stream ran.

    ``key_heads``: the pushed packets that head a keyframe run (an SPS,
    PPS and IDR in a row; every frame's first packet in RTP/JPEG);
    ``key_head``: the newest of them pushed before its PLAY was written
    (of those the server surely held then: pushed on time, not in a
    stalled generator's catch-up burst);
    ``start``: the pushed packet its first sequence number stands for,
    as its first datagram shows (None where it read nothing, or nothing
    pushed).  ``mid_gop``: 1 if that start heads no keyframe run;
    ``stale``: 1 if it is older than ``key_head``.  Judged from the
    start on; where there is none, from ``key_head``."""
    out = {"mid_gop": 0, "stale": 0}
    if start is not None:
        out["mid_gop"] = int(start not in set(key_heads))
        out["stale"] = int(start < key_head)
    out.update(judge_flow(got, pushed, first_seq, ssrc,
                          start=key_head if start is None else max(start, 0)))
    return out


def start_of(seq: int, k: int, first_seq: int) -> int:
    """The pushed packet a session's first sequence number stands for,
    from one datagram with sequence number ``seq`` that carries pushed
    packet ``k``."""
    return k - ((seq - first_seq) & 0xFFFF)


def join_control(pushed: list[bytes], key_heads: list[int], key_head: int,
                 control: str, first_seq: int, ssrc: int, at: int
                 ) -> list[bytes]:
    """A joiner's control: the reference's own packets for a subscriber
    joined at ``key_head``, with the start moved inside the GOP (to a
    packet after the head that heads no run) or to the run before it, or
    with one packet after a right start dropped.  ``judge_joiner`` must
    count exactly one fault."""
    later = [k for k in key_heads if k > key_head]
    if control == "join_mid_gop":
        end = later[0] if later else len(pushed)
        start = key_head + 1 + at % max(end - key_head - 1, 1)
    elif control == "join_stale":
        start = max(k for k in key_heads if k < key_head)
    elif control == "join_every_packet":
        start = key_head
    else:
        raise ValueError(f"unknown control {control!r}")
    flow = reference_flow(pushed[start:], first_seq, ssrc, 0)
    if control == "join_every_packet":
        del flow[1 + at % max(len(flow) - 1, 1)]
    return flow


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
