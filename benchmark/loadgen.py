"""The load generator: seeded H.264 sources, RTSP push and PLAY sessions,
stamped and bulk UDP receivers.  Copied out of ``chip_smoke.py`` (PR 21)
and cut loose from the program: it imports nothing of ``easydarwin_tpu``
— its own FU-A packetizer, its own RTSP client, its own receivers — so
that what is measured is the server child and nothing else.

Everything is made from ``--seed``; the same seed gives the same bytes,
the same due times and the same sockets layout.
"""

from __future__ import annotations

import asyncio
import mmap
import os
import select
import signal
import socket
import threading
import time

import numpy as np

SDP = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=benchmark\r\nt=0 0\r\n"
       "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
       "a=control:trackID=1\r\n")
RCVBUF = 1 << 24
N_IP, N_PORT = 64, 4        # bulk destinations: 64 loopback IPs x 4 ports
#: a traffic file's ``frame_phase``: "spread" (and no key) puts source i's
#: frames i/n of a period after source 0's, cameras on their own clocks;
#: "locked" puts every source's frame f at one instant, sources genlocked
#: to one house clock.  GOP phases stay spread either way.
FRAME_PHASES = {"spread": 1, "locked": 0}


class LoadgenError(Exception):
    """The generator itself could not do its part."""


# ------------------------------------------------------------------ media
def rtp_header(seq: int, ts: int, ssrc: int, marker: bool, pt: int = 96
               ) -> bytes:
    return (bytes((0x80, pt | (0x80 if marker else 0)))
            + (seq & 0xFFFF).to_bytes(2, "big")
            + (ts & 0xFFFFFFFF).to_bytes(4, "big")
            + (ssrc & 0xFFFFFFFF).to_bytes(4, "big"))


def packetize_h264(nal: bytes, seq: int, ts: int, ssrc: int, mtu: int,
                   marker_on_last: bool) -> list[bytes]:
    """One NAL unit as RTP (RFC 6184): single-NAL if it fits, else FU-A
    fragments of ``mtu`` payload bytes."""
    if len(nal) <= mtu:
        return [rtp_header(seq, ts, ssrc, marker_on_last) + nal]
    indicator = (nal[0] & 0x60) | 28
    ntype = nal[0] & 0x1F
    body, out, first = nal[1:], [], True
    while body:
        chunk, body = body[:mtu - 2], body[mtu - 2:]
        fu = ntype | (0x80 if first else 0) | (0x40 if not body else 0)
        out.append(rtp_header(seq, ts, ssrc, marker_on_last and not body)
                   + bytes((indicator, fu)) + chunk)
        seq, first = (seq + 1) & 0xFFFF, False
    return out


class Source:
    """One pusher's seeded stream: every packet it will ever push.
    ``shapes`` is the configuration's ``stream`` group."""

    def __init__(self, idx: int, seed: int, n_frames: int, fps: float,
                 shapes: dict, start_frame: int = 0, first_gop_at: int = 0):
        """``start_frame``: the frame with which this source comes on
        line, an IDR (cameras do not all start in one frame period);
        earlier frames hold no packets.  ``first_gop_at``: the frame at
        which its GOP cycle starts after that opening IDR: cameras are
        not GOP-locked, so each source's IDRs fall at their own place
        in the GOP period."""
        rng = np.random.default_rng([seed, idx])
        self.idx = idx
        self.path = f"/live/cam{idx:02d}"
        self.packets: list[bytes] = []
        #: packet index of each frame's first packet (+ end sentinel)
        self.frame_start: list[int] = []
        seq = int(rng.integers(0, 1 << 16))
        ts = int(rng.integers(0, 1 << 32))
        ssrc = int(rng.integers(1, 1 << 32))
        mtu, gop = shapes["rtp_payload_bytes"], shapes["gop_frames"]
        idr_lo, idr_hi = shapes["idr_bytes"]
        p_lo, p_hi = shapes["p_frame_bytes"]
        for f in range(n_frames):
            self.frame_start.append(len(self.packets))
            if f < start_frame:
                continue
            if f == start_frame or (f >= first_gop_at
                          and (f - first_gop_at) % gop == 0):
                nals = [bytes((0x67,)) + rng.bytes(23),
                        bytes((0x68,)) + rng.bytes(7),
                        bytes((0x65,)) + rng.bytes(
                            int(rng.integers(idr_lo, idr_hi)))]
            else:
                nals = [bytes((0x41,)) + rng.bytes(
                    int(rng.integers(p_lo, p_hi)))]
            for k, nal in enumerate(nals):
                out = packetize_h264(nal, seq, ts, ssrc, mtu,
                                     k == len(nals) - 1)
                self.packets += out
                seq = (seq + len(out)) & 0xFFFF
            ts = (ts + round(90_000 / fps)) & 0xFFFFFFFF     # 90 kHz
        self.frame_start.append(len(self.packets))
        self.pushed = 0                 # packets written so far
        self.conn: RtspConn | None = None

    def frame_bytes(self, f: int) -> tuple[bytes, int]:
        lo, hi = self.frame_start[f], self.frame_start[f + 1]
        framed = b"".join(b"$\x00" + len(p).to_bytes(2, "big") + p
                          for p in self.packets[lo:hi])
        return framed, hi - lo


# ------------------------------------------------------------------- RTSP
class RtspConn:
    """A minimal RTSP/1.0 client connection: one request in flight,
    ``$``-framed data from the server skipped."""

    def __init__(self, timeout: float = 300.0):
        self.reader: asyncio.StreamReader | None = None
        self.writer: asyncio.StreamWriter | None = None
        self.cseq = 0
        self.session = ""
        self.timeout = timeout

    async def connect(self, host: str, port: int, local_ip: str | None = None
                      ) -> None:
        self.reader, self.writer = await asyncio.open_connection(
            host, port, local_addr=(local_ip, 0) if local_ip else None)

    async def request(self, method: str, uri: str, headers: dict | None = None,
                      body: bytes = b"") -> tuple[int, dict, bytes]:
        self.cseq += 1
        lines = [f"{method} {uri} RTSP/1.0", f"CSeq: {self.cseq}"]
        if self.session:
            lines.append(f"Session: {self.session}")
        for k, v in (headers or {}).items():
            lines.append(f"{k}: {v}")
        if body:
            lines.append(f"Content-Length: {len(body)}")
        self.writer.write(("\r\n".join(lines) + "\r\n\r\n").encode() + body)
        return await asyncio.wait_for(self._response(), self.timeout)

    async def _response(self) -> tuple[int, dict, bytes]:
        r = self.reader
        while True:
            first = await r.readexactly(1)
            if first == b"$":                   # interleaved RTCP: skip
                head = await r.readexactly(3)
                await r.readexactly(int.from_bytes(head[1:], "big"))
                continue
            text = (first + await r.readuntil(b"\r\n\r\n")).decode(
                "latin-1")
            status_line, *rest = text.split("\r\n")
            hdrs = {}
            for ln in rest:
                k, sep, v = ln.partition(":")
                if sep:
                    hdrs[k.strip().lower()] = v.strip()
            n = int(hdrs.get("content-length", "0") or 0)
            body = await r.readexactly(n) if n else b""
            status = int(status_line.split()[1])
            if "session" in hdrs:
                self.session = hdrs["session"].split(";")[0].strip()
            return status, hdrs, body

    async def expect(self, method: str, uri: str, headers=None,
                     body: bytes = b"") -> dict:
        status, hdrs, _ = await self.request(method, uri, headers, body)
        if status != 200:
            raise LoadgenError(f"{method} {uri}: status {status}")
        return hdrs

    async def push_start(self, uri: str) -> None:
        await self.expect("ANNOUNCE", uri,
                          {"Content-Type": "application/sdp"}, SDP.encode())
        await self.expect("SETUP", f"{uri}/trackID=1", {
            "Transport": "RTP/AVP/TCP;unicast;interleaved=0-1;mode=record"})
        await self.expect("RECORD", uri)

    async def play_start(self, uri: str, rtp_port: int
                         ) -> tuple[int | None, int | None]:
        """DESCRIBE / SETUP / PLAY over UDP; returns what the server
        announced for this session: (ssrc of SETUP's Transport, first
        sequence number of PLAY's RTP-Info)."""
        await self.expect("DESCRIBE", uri, {"Accept": "application/sdp"})
        hdrs = await self.expect("SETUP", f"{uri}/trackID=1", {
            "Transport": f"RTP/AVP;unicast;client_port={rtp_port}-"
                         f"{rtp_port + 1}"})
        ssrc = None
        for part in hdrs.get("transport", "").split(";"):
            k, _, v = part.strip().partition("=")
            if k.lower() == "ssrc":
                ssrc = int(v, 16)
        hdrs = await self.expect("PLAY", uri)
        info, seq = hdrs.get("rtp-info", ""), None
        if "seq=" in info:
            seq = int(info.split("seq=")[1].split(";")[0].split(",")[0])
        return ssrc, seq


# -------------------------------------------------------------- receivers
def udp_socket(ip: str, port: int = 0) -> socket.socket:
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.setblocking(False)
    try:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, RCVBUF)
    except OSError:
        pass
    s.bind((ip, port))
    return s


def has_successor(port: int) -> bool:
    """Whether ``port + 1`` exists, to carry the RTCP of an RTP ``port``.
    The kernel does hand out 65535 as an ephemeral port."""
    return port + 1 <= 65535


def udp_pair(ip: str) -> tuple[socket.socket, socket.socket]:
    """An (RTP, RTCP) socket pair on adjacent ports of ``ip``.  A drawn
    port whose successor is taken, or does not exist, is drawn again."""
    for _ in range(64):
        a = udp_socket(ip)
        port = a.getsockname()[1]
        if has_successor(port):
            try:
                return a, udp_socket(ip, port + 1)
            except (OSError, OverflowError):
                pass
        a.close()
    raise LoadgenError(f"no adjacent UDP port pair on {ip}")


class Flow:
    """One stamped player: its own socket pair, every datagram kept with
    the clock reading at ``recv``."""

    def __init__(self, src: Source, ip: str):
        self.src, self.ip = src, ip
        self.rtp, self.rtcp = udp_pair(ip)
        self.port = self.rtp.getsockname()[1]
        self.packets: list[bytes] = []
        self.stamps: list[int] = []         # perf_counter_ns at recv
        self.ssrc: int | None = None        # from the SETUP answer
        self.first_seq: int | None = None   # from the PLAY RTP-Info


class StampReader(threading.Thread):
    """Drains a fixed set of stamped flows, stamping each datagram as it
    is read.  Blocking epoll: it wakes when a datagram lands."""

    def __init__(self, flows: list[Flow], name: str):
        super().__init__(daemon=True, name=name)
        self.ep = select.epoll()
        self.by_fd: dict[int, Flow | None] = {}
        for f in flows:
            self.by_fd[f.rtp.fileno()] = f
            self.by_fd[f.rtcp.fileno()] = None
            self.ep.register(f.rtp.fileno(), select.EPOLLIN)
            self.ep.register(f.rtcp.fileno(), select.EPOLLIN)
        self.socks = {s.fileno(): s for f in flows for s in (f.rtp, f.rtcp)}
        self.stop_flag = False

    def run(self) -> None:
        now = time.perf_counter_ns
        while not self.stop_flag:
            for fd, _ in self.ep.poll(0.05):
                flow, sock = self.by_fd[fd], self.socks[fd]
                try:
                    while True:
                        data = sock.recv(4096)
                        if flow is not None:
                            flow.stamps.append(now())
                            flow.packets.append(data)
                except OSError:         # drained (BlockingIOError) or gone
                    pass


class BulkDrains:
    """Counts datagrams on the bulk (unstamped) flows' sockets in forked
    worker processes of their own, so that neither the pusher nor the
    stamped readers share an interpreter lock with them.  One socket per
    (loopback IP, port) slot: every slot has its own receive queue.

    Counters live in shared memory: ``counts()`` reads them at any time.
    """

    def __init__(self, n_procs: int):
        self.n_procs = n_procs
        self.rtp: list[socket.socket] = []
        self.rtcp: list[socket.socket] = []
        self.ports: list[int] = []
        for _ in range(N_PORT):
            self.ports.append(self._open_port_group())
        # shared: [stop, pad] then per process (rtp datagrams, rtp bytes,
        # rtcp datagrams)
        self._mm = mmap.mmap(-1, 8 * (2 + 3 * n_procs))
        self._shared = memoryview(self._mm).cast("q")
        self.pids: list[int] = []

    def _open_port_group(self) -> int:
        """One RTP port (and the next, RTCP) free on every bulk IP."""
        for _ in range(200):
            probe = udp_socket("127.0.0.1")
            port = probe.getsockname()[1]
            probe.close()
            if port % 2 or not has_successor(port):
                continue
            made: list[socket.socket] = []
            try:
                for i in range(N_IP):
                    made.append(udp_socket(f"127.0.0.{1 + i}", port))
                    made.append(udp_socket(f"127.0.0.{1 + i}", port + 1))
            except OSError:
                for s in made:
                    s.close()
                continue
            self.rtp += made[0::2]
            self.rtcp += made[1::2]
            return port
        raise LoadgenError("no UDP port free on all bulk loopback IPs")

    def slot(self, j: int) -> tuple[str, int]:
        """Bulk destination ``j`` of a source: (ip, rtp port)."""
        return (f"127.0.0.{1 + (j // N_PORT) % N_IP}",
                self.ports[j % N_PORT])

    def start(self) -> None:
        """Fork the workers.  Call before any thread or event loop
        exists in this process."""
        for k in range(self.n_procs):
            pid = os.fork()
            if pid == 0:
                try:
                    self._work(k)
                finally:
                    os._exit(0)
            self.pids.append(pid)

    def _work(self, k: int) -> None:
        signal.signal(signal.SIGINT, signal.SIG_IGN)
        shared, base = self._shared, 2 + 3 * k
        ep = select.epoll()
        mine: dict[int, tuple[socket.socket, int]] = {}
        for which, socks in ((0, self.rtp), (2, self.rtcp)):
            for s in socks[k::self.n_procs]:
                mine[s.fileno()] = (s, which)
                ep.register(s.fileno(), select.EPOLLIN)
        buf = bytearray(4096)
        tot = [0, 0, 0]
        ppid = os.getppid()
        while not shared[0]:
            events = ep.poll(0.05)
            if not events and os.getppid() != ppid:
                return                      # orphaned: the parent died
            for fd, _ in events:
                s, which = mine[fd]
                try:
                    while True:
                        n = s.recv_into(buf)
                        tot[which] += 1
                        if which == 0:
                            tot[1] += n
                except OSError:
                    pass
            shared[base], shared[base + 1], shared[base + 2] = tot

    def counts(self) -> tuple[int, int, int]:
        """(RTP datagrams, RTP bytes, RTCP datagrams) so far."""
        s = self._shared
        return tuple(sum(s[2 + 3 * k + c] for k in range(self.n_procs))
                     for c in range(3))

    def stop(self) -> None:
        self._shared[0] = 1
        for pid in self.pids:
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                done, _ = os.waitpid(pid, os.WNOHANG)
                if done:
                    break
                time.sleep(0.01)
            else:
                os.kill(pid, signal.SIGKILL)
                os.waitpid(pid, 0)
        self.pids = []


def udp_kernel_counters() -> dict[str, int]:
    """The kernel's UDP counters (``/proc/net/snmp``); empty where the
    kernel does not give them."""
    try:
        with open("/proc/net/snmp") as f:
            rows = [ln.split() for ln in f if ln.startswith("Udp:")]
        return {k: int(v) for k, v in zip(rows[0][1:], rows[1][1:])}
    except (OSError, IndexError, ValueError):
        return {}


# ----------------------------------------------------------------- driver
class Loadgen:
    """Sources, sessions and receivers of one run."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 n_sources: int, n_players: int):
        phase = traffic.get("frame_phase", "spread")
        if phase not in FRAME_PHASES:       # before a socket is opened
            raise LoadgenError(f"frame_phase {phase!r} is none of "
                               f"{sorted(FRAME_PHASES)}")
        #: how much of i/n of a period source i's frames trail source 0's
        self.phase_spread = FRAME_PHASES[phase]
        self.fps = float(traffic["fps_per_source"])
        self.warm_frames = int(traffic["warm_frames"])
        self.n_src, self.n_sub = n_sources, n_players
        self.wave = int(cfg["players"]["join_wave"])
        self.stamped_every = int(cfg["players"]["stamped_every"])
        n_win = int(np.ceil(seconds * self.fps)) + 1
        # GOP phases: the same evenly spread set for every seed, dealt to
        # the sources in the seed's order
        gop = cfg["stream"]["gop_frames"]
        deal = np.random.default_rng([seed, 1 << 20]).permutation(n_sources)
        W = self.warm_frames
        self.sources = [
            Source(i, seed, W + n_win, self.fps, cfg["stream"],
                   start_frame=i * W // (2 * n_sources),
                   first_gop_at=W + int(deal[i]) * gop // n_sources)
            for i in range(n_sources)]
        self.bulk = BulkDrains(int(traffic.get("bulk_drain_procs", 4)))
        self.flows: list[Flow] = []
        for src in self.sources:
            for j in range(n_players):
                if j % self.stamped_every == 0:
                    self.flows.append(Flow(
                        src, f"127.0.0.{1 + (src.idx + j) % N_IP}"))
        self.readers = [StampReader(self.flows[k::4], f"stamp-reader-{k}")
                        for k in range(4)]
        self.players: list[RtspConn] = []
        self.n_bulk_joined = 0
        self.lateness: list[float] = []
        #: (source idx, frame) -> perf_counter_ns at which it was due
        self.due_ns: dict[tuple[int, int], int] = {}

    def start_receivers(self) -> None:
        self.bulk.start()               # forks: before any thread
        for r in self.readers:
            r.start()

    def stop_receivers(self) -> None:
        for r in self.readers:
            r.stop_flag = True
        self.bulk.stop()

    def received(self) -> int:
        """RTP datagrams read from any player socket so far."""
        return self.bulk.counts()[0] + sum(len(f.stamps) for f in self.flows)

    # -- sessions ----------------------------------------------------------
    async def start_pushers(self, port: int) -> None:
        for s in self.sources:
            c = RtspConn()
            await c.connect("127.0.0.1", port)
            await c.push_start(f"rtsp://127.0.0.1:{port}{s.path}")
            s.conn = c

    async def _join(self, port: int, src: Source, ip: str, rtp_port: int,
                    flow: Flow | None) -> None:
        c = RtspConn()
        await c.connect("127.0.0.1", port, local_ip=ip)
        ssrc, seq = await c.play_start(
            f"rtsp://127.0.0.1:{port}{src.path}", rtp_port)
        self.players.append(c)
        if flow is not None:
            flow.ssrc, flow.first_seq = ssrc, seq
        else:
            self.n_bulk_joined += 1

    async def join_players(self, port: int) -> None:
        """Every source's players join in waves of ``join_wave``, a
        wave's PLAYs answered before the next starts, all sources' waves
        side by side.  The server fills output buckets first-fit, so
        wave w is bucket w, and the stamped members of each wave sample
        every bucket's hold in every run."""
        by_src = {s.idx: [f for f in self.flows if f.src is s]
                  for s in self.sources}

        async def one_source(src: Source) -> None:
            stamped = iter(by_src[src.idx])
            bulk_j = 0
            for w0 in range(0, self.n_sub, self.wave):
                jobs = []
                for j in range(w0, min(w0 + self.wave, self.n_sub)):
                    if j % self.stamped_every == 0:
                        flow = next(stamped)
                        jobs.append(self._join(port, src, flow.ip,
                                               flow.port, flow))
                    else:
                        ip, p = self.bulk.slot(bulk_j)
                        bulk_j += 1
                        jobs.append(self._join(port, src, ip, p, None))
                await asyncio.gather(*jobs)

        await asyncio.gather(*(one_source(s) for s in self.sources))

    # -- media -------------------------------------------------------------
    def frame_plan(self, lo: int, hi_of) -> list[tuple[float, int, int]]:
        """``(due_s, source idx, frame)`` of frames ``lo`` onward, in the
        order they are pushed.  Frame f of source i is due ``((f - lo) +
        spread * i/n) / fps`` after t0, ``spread`` 1 with every source on
        its OWN phase and 0 with all on one (``FRAME_PHASES``): frames due
        at one instant leave back to back in source order.  ``hi_of(due_s)``
        says whether a frame due then is still pushed."""
        n = self.n_src
        plan = []
        for s in self.sources:
            f = lo
            while f < len(s.frame_start) - 1:
                due = ((f - lo) + self.phase_spread * s.idx / n) / self.fps
                if not hi_of(due):
                    break
                if s.frame_start[f + 1] > s.frame_start[f]:
                    plan.append((due, s.idx, f))
                f += 1
        plan.sort()
        return plan

    async def push_frames(self, lo: int, hi_of, t0_ns: int) -> None:
        """Push frames ``lo`` onward of every source at the mix's pace,
        as ``frame_plan`` orders them.  How late each frame left is
        recorded."""
        for due, i, f in self.frame_plan(lo, hi_of):
            due_ns = t0_ns + int(due * 1e9)
            delay = (due_ns - time.perf_counter_ns()) / 1e9
            if delay > 0:
                await asyncio.sleep(delay)
            self.lateness.append(
                max(time.perf_counter_ns() - due_ns, 0) / 1e9)
            src = self.sources[i]
            self.due_ns[(i, f)] = due_ns
            data, k = src.frame_bytes(f)
            src.conn.writer.write(data)
            src.pushed += k
            await src.conn.writer.drain()

    async def pusher_keepalive(self) -> None:
        """While nothing is pushed (the drain after the window) every
        pusher sends an OPTIONS inside any 5 s: the server reaps a push
        session silent for ``push_timeout_sec``."""
        while True:
            await asyncio.sleep(5.0)
            await asyncio.gather(*(s.conn.request("OPTIONS", "*")
                                   for s in self.sources))

    def expected_deliveries(self) -> int:
        return sum(s.pushed for s in self.sources) * self.n_sub
