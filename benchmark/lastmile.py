"""The lossy last mile, stated as data in a configuration's ``last_mile``
key and played by the generator's receivers:

    "last_mile": {"model": "gilbert", "mean_loss": 0.02,
                  "mean_burst_packets": 3, "rr_interval_s": 5,
                  "nack": "stamped", "nack_after_ms": 200}

* **Loss** is Gilbert's two-state chain, one chain a flow, seeded from
  ``--seed`` and the flow's index: a packet is lost in Bad and never in
  Good; Bad is left with r = 1 / ``mean_burst_packets`` and entered with
  p = π·r / (1 − π), π = ``mean_loss`` (RFC 3611 §4.7 reports the burst and
  gap metrics of such a chain).  It applies to media datagrams after the
  socket read: the server's wire is untouched, the loss is the viewer's.
* **Reports**: every ``rr_interval_s`` (RFC 3550 §6.2's minimum is 5 s),
  at a seeded phase, an RR whose ``fraction_lost`` and cumulative loss
  follow RFC 3550 §6.4.1 (appendix A.3) over what arrived before repair.
* **Repair** (stamped players only, ``nack: "stamped"``): parity and RTX
  recover losses through ``reference_fec``; a sequence number still
  missing once parity naming it has come, or ``nack_after_ms`` after the
  gap was seen, is asked for again in an RFC 4585 generic NACK (PT 205,
  FMT 1), and again every ``nack_after_ms`` until the drain ends.

A configuration without the key runs none of this.
"""

from __future__ import annotations

import random
import struct

from benchmark import reference_fec

MEDIA_PT = 96               # the pushed SDP's payload type
#: the server's defaults where the configuration's ``server`` block
#: does not state them
DEFAULT_FEC_PT, DEFAULT_RTX_PT = 127, 126
RTCP_RR, RTCP_RTPFB, FMT_GENERIC_NACK = 201, 205, 1
#: the most places one arrival may show missing at once
MAX_GAP = 4096


class LastMileError(ValueError):
    """The ``last_mile`` key names something this generator cannot play."""


class LastMile:
    """The parsed key, with the chain's transition probabilities."""

    def __init__(self, spec: dict, server: dict):
        if spec.get("model") != "gilbert" or spec.get("nack") != "stamped":
            raise LastMileError(f"last_mile {spec!r}: model must be "
                                f"'gilbert' and nack 'stamped'")
        self.mean_loss = float(spec["mean_loss"])
        self.burst = float(spec["mean_burst_packets"])
        self.rr_interval_ns = int(float(spec["rr_interval_s"]) * 1e9)
        self.nack_after_ns = int(float(spec["nack_after_ms"]) * 1e6)
        if not (0 < self.mean_loss < 1 and self.burst >= 1
                and self.rr_interval_ns > 0 and self.nack_after_ns > 0):
            raise LastMileError(f"last_mile {spec!r}: out of range")
        self.r = 1.0 / self.burst
        self.p = self.mean_loss * self.r / (1.0 - self.mean_loss)
        if self.p > 1:
            raise LastMileError(f"last_mile {spec!r}: p = {self.p:g} > 1")
        self.fec_pt = int(server.get("fec_payload_type", DEFAULT_FEC_PT))
        self.rtx_pt = int(server.get("rtx_payload_type", DEFAULT_RTX_PT))

    @classmethod
    def of(cls, cfg: dict) -> "LastMile | None":
        spec = cfg.get("last_mile")
        return None if spec is None else cls(spec, cfg.get("server", {}))

    def chain(self, seed: int, *key) -> "GilbertChain":
        return GilbertChain(self.p, self.r, rng(seed, "chain", *key))

    def next_report(self, due: int, now: int) -> int:
        """The first instant of the report grid through ``due`` that is
        after ``now``: a player that was not listening skips the reports
        it missed, not sends them at once."""
        return due + ((now - due) // self.rr_interval_ns + 1) \
            * self.rr_interval_ns

    def kind(self, data: bytes) -> str:
        pt = data[1] & 0x7F
        return ("parity" if pt == self.fec_pt else
                "rtx" if pt == self.rtx_pt else "media")


def rng(seed: int, *key) -> random.Random:
    """A generator of its own for ``key`` under ``seed`` (a string seed is
    hashed by SHA-512, the same in every process)."""
    return random.Random(":".join(map(str, (seed, *key))))


class GilbertChain:
    """Two states; ``step()`` moves once and says whether the packet is
    lost.  It starts in its stationary distribution."""

    def __init__(self, p: float, r: float, rand: random.Random):
        self.p, self.r, self.rand = p, r, rand.random
        self.bad = self.rand() < p / (p + r)

    def step(self) -> bool:
        self.bad = (self.rand() >= self.r) if self.bad else (
            self.rand() < self.p)
        return self.bad


# ---------------------------------------------------------------- RTCP
class ReceptionStats:
    """RFC 3550 appendix A.3 over one source's packets as they arrived.
    A block's jitter, LSR and DLSR are 0: the server's controllers read
    the loss alone."""

    def __init__(self):
        self.base = self.max_seq = None
        self.cycles = self.received = 0
        self.expected_prior = self.received_prior = 0

    def on_seq(self, seq: int) -> None:
        self.received += 1
        if self.base is None:
            self.base = self.max_seq = seq
        elif 0 < (seq - self.max_seq) & 0xFFFF < 0x8000:
            if seq < self.max_seq:
                self.cycles += 1 << 16
            self.max_seq = seq

    def block(self, ssrc: int) -> bytes:
        """One report block, and the interval restarted."""
        ext = self.cycles + self.max_seq
        expected = ext - self.base + 1
        lost = expected - self.received
        exp_i = expected - self.expected_prior
        lost_i = exp_i - (self.received - self.received_prior)
        self.expected_prior, self.received_prior = expected, self.received
        frac = (lost_i << 8) // exp_i if exp_i > 0 and lost_i > 0 else 0
        cum = max(-0x800000, min(lost, 0x7FFFFF)) & 0xFFFFFF
        return struct.pack("!IIIIII", ssrc & 0xFFFFFFFF,
                           (min(frac, 255) << 24) | cum, ext & 0xFFFFFFFF,
                           0, 0, 0)


def receiver_report(sender_ssrc: int, blocks: list[bytes]) -> bytes:
    body = struct.pack("!I", sender_ssrc) + b"".join(blocks)
    return struct.pack("!BBH", 0x80 | len(blocks), RTCP_RR,
                       len(body) // 4) + body


def generic_nack(sender_ssrc: int, media_ssrc: int, seqs) -> bytes:
    """RFC 4585 §6.2.1: (PID, BLP) pairs over the sorted sequence
    numbers."""
    pairs: list[list[int]] = []
    for s in sorted({s & 0xFFFF for s in seqs}):
        if pairs and 1 <= (s - pairs[-1][0]) & 0xFFFF <= 16:
            pairs[-1][1] |= 1 << (((s - pairs[-1][0]) & 0xFFFF) - 1)
        else:
            pairs.append([s, 0])
    body = struct.pack("!II", sender_ssrc & 0xFFFFFFFF,
                       media_ssrc & 0xFFFFFFFF) + b"".join(
        struct.pack("!HH", pid, blp) for pid, blp in pairs)
    return struct.pack("!BBH", 0x80 | FMT_GENERIC_NACK, RTCP_RTPFB,
                       len(body) // 4) + body


# ------------------------------------------------------- stamped player
class StampedReceiver:
    """One stamped player on a lossy link: what it holds (received media
    and what it repaired, each stamped when it was held), what it lost
    to its chain, every parity and RTX datagram it read (for the judge),
    and its reports and NACKs.  Driven by one reader thread."""

    def __init__(self, flow, lm: LastMile, seed: int, index: int):
        self.flow, self.lm = flow, lm
        self.chain = lm.chain(seed, "stamped", index)
        r = rng(seed, "rtcp", index)
        self.own_ssrc = r.randrange(1, 1 << 32)
        self.next_rr = None                 # set by ``start``
        self.rr_phase = int(r.random() * lm.rr_interval_ns)
        self.received: list[bytes] = []     # chain-surviving media, in order
        self.repaired: list[bytes] = []     # what parity or RTX restored
        self.parity: list[bytes] = []
        self.rtx: list[bytes] = []
        self.held: dict[int, bytes] = {}    # place → wire packet
        self.missing: dict[int, int] = {}   # place → when to NACK it
        self.groups: dict[tuple, dict[int, bytes]] = {}
        self.top = -1                       # highest place held or missed
        self.end = 0                        # places owed so far (pushed)
        self.stats = ReceptionStats()
        self.lost_by_chain = self.recovered_fec = self.recovered_rtx = 0
        self.nacks_sent = self.rrs_sent = 0
        self.server_rtcp = None             # learned from the server's SRs
        self.nacking = True

    def place(self, seq: int) -> int:
        """The place of output sequence number ``seq`` in the flow."""
        k = (seq - self.flow.first_seq) & 0xFFFF
        while k < self.top - 32768:
            k += 65536
        return k

    def seq(self, k: int) -> int:
        return (self.flow.first_seq + k) & 0xFFFF

    # -- datagrams ---------------------------------------------------------
    def on_rtp(self, data: bytes, now: int) -> None:
        if len(data) < 12 or self.flow.first_seq is None:
            return
        kind = self.lm.kind(data)
        if kind == "parity":
            self.parity.append(data)
            self._on_parity(data, now)
        elif kind == "rtx":
            self.rtx.append(data)
            r = reference_fec.restore_rtx(data, MEDIA_PT)
            if r is not None and self._hold(self.place(r[0]), r[1], now,
                                            repaired=True):
                self.recovered_rtx += 1
                self._try_groups(now)
        elif self.chain.step():
            self.lost_by_chain += 1
        else:
            seq = int.from_bytes(data[2:4], "big")
            self.stats.on_seq(seq)
            self.received.append(data)
            k = self.place(seq)
            self._gap_to(k, now)
            if self._hold(k, data, now) and self.groups:
                self._try_groups(now)

    def _hold(self, k: int, wire: bytes, now: int,
              repaired: bool = False) -> bool:
        if k in self.held or not 0 <= k < 1 << 24:
            return False
        self.held[k] = wire
        if repaired:
            self.repaired.append(wire)
        self.missing.pop(k, None)
        self.flow.stamps.append(now)
        self.flow.packets.append(wire)
        self.top = max(self.top, k)
        return True

    def _gap_to(self, k: int, now: int, most: int = MAX_GAP) -> None:
        """Places before ``k`` not held are missing from now on.  A jump
        of more than ``most`` places is no gap of this flow's and is left
        to the judge."""
        if k - self.top > most:
            return
        due = now + self.lm.nack_after_ns
        for j in range(self.top + 1, k):
            if j not in self.held:
                self.missing.setdefault(j, due)
        self.top = max(self.top, k - 1)

    def _on_parity(self, data: bytes, now: int) -> None:
        p = reference_fec.parse_parity(data)
        if p is None:
            return
        base = self.place(p["snbase"])
        key = (base, tuple(p["deltas"]))
        self.groups.setdefault(key, {})[p["idx"]] = p["payload"]
        self._gap_to(base + p["deltas"][-1] + 1, now)
        if not self._solve(key, now):
            # its parity has come and cannot repair it (yet): ask now
            for d in key[1]:
                if self.missing.get(key[0] + d, 0) > now:
                    self.missing[key[0] + d] = now

    def _try_groups(self, now: int) -> None:
        for key in list(self.groups):
            self._solve(key, now)

    def _solve(self, key: tuple, now: int) -> bool:
        """Repair what the group's parity can; True once nothing it names
        is missing (the group is then dropped)."""
        base, deltas = key
        lost = [d for d in deltas if base + d not in self.held]
        if lost:
            got = reference_fec.solve(
                {d: self.held[base + d] for d in deltas
                 if base + d in self.held}, lost, self.groups[key])
            if got is None:
                return False
            for d, wire in got.items():
                self.recovered_fec += self._hold(base + d, wire, now,
                                                 repaired=True)
        del self.groups[key]
        return True

    # -- timers (the reader's sweep) -----------------------------------------
    def start(self, now: int) -> None:
        self.next_rr = now + self.rr_phase

    def sweep(self, now: int) -> None:
        if self.server_rtcp is None or self.flow.ssrc is None:
            return
        if self.end > self.top + 1:
            self._gap_to(self.end, now, most=self.end + 1)
        if self.next_rr is not None and now >= self.next_rr:
            self.next_rr = self.lm.next_report(self.next_rr, now)
            if self.stats.base is not None:
                self._send(receiver_report(
                    self.own_ssrc, [self.stats.block(self.flow.ssrc)]))
                self.rrs_sent += 1
        due = [k for k, t in self.missing.items() if t <= now]
        if due and self.nacking:
            for k in due:
                self.missing[k] = now + self.lm.nack_after_ns
            for i in range(0, len(due), 64):   # 64 numbers a datagram at most
                self._send(generic_nack(self.own_ssrc, self.flow.ssrc,
                                        map(self.seq, due[i:i + 64])))
                self.nacks_sent += 1

    def _send(self, data: bytes) -> None:
        try:
            self.flow.rtcp.sendto(data, self.server_rtcp)
        except OSError:         # a full send buffer: the next sweep retries
            pass


# ---------------------------------------------------------- bulk player
class BulkSource:
    """One bulk session behind the last mile: its chain and its reports."""

    def __init__(self, chain: GilbertChain):
        self.chain = chain
        self.stats = ReceptionStats()

    def on_media(self, data: bytes) -> bool:
        """False where the chain lost the datagram."""
        if self.chain.step():
            return False
        self.stats.on_seq(int.from_bytes(data[2:4], "big"))
        return True
