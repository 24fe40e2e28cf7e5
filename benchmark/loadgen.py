"""The load generator: seeded H.264 or RTP/JPEG sources, RTSP push and
PLAY sessions, players who join while the stream runs, stamped and bulk
UDP receivers.  Copied out of ``chip_smoke.py`` (PR 21)
and cut loose from the program: it imports nothing of ``easydarwin_tpu``
— its own FU-A packetizer, its own RTSP client, its own receivers — so
that what is measured is the server child and nothing else.

Everything is made from ``--seed``; the same seed gives the same bytes,
the same due times and the same sockets layout.
"""

from __future__ import annotations

import array
import asyncio
import bisect
import ctypes
import functools
import mmap
import os
import select
import signal
import socket
import struct
import threading
import time

import numpy as np

from benchmark import lastmile, reference

SDP = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=benchmark\r\nt=0 0\r\n"
       "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
       "a=control:trackID=1\r\n")
#: an RTP/JPEG source's ANNOUNCE: the static payload type 26 (RFC 3551)
SDP_JPEG = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=benchmark\r\nt=0 0\r\n"
            "m=video 0 RTP/AVP 26\r\na=rtpmap:26 JPEG/90000\r\n"
            "a=control:trackID=1\r\n")
JPEG_PT = 26
#: RFC 2435 main header fields of every frame: type 1 (4:2:0, no restart
#: markers) and a Q under 128, so no quantization tables ride in band
JPEG_TYPE, JPEG_Q = 1, 75
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


def is_mjpeg(shapes: dict) -> bool:
    """Whether a configuration's ``stream`` group names RTP/JPEG."""
    return "JPEG" in str(shapes.get("codec", "")).upper()


def packetize_jpeg(scan: bytes, seq: int, ts: int, ssrc: int, mtu: int,
                   width: int, height: int) -> list[bytes]:
    """One JPEG frame's scan as RTP/JPEG (RFC 2435): each packet opens
    with the 8-byte main header (type-specific 0, the fragment's offset
    into the scan, type, Q, width / 8, height / 8); the marker is on the
    frame's last packet."""
    out, off = [], 0
    while off < len(scan):
        chunk = scan[off:off + mtu - 8]
        last = off + len(chunk) >= len(scan)
        out.append(rtp_header(seq, ts, ssrc, last, JPEG_PT)
                   + bytes((0,)) + off.to_bytes(3, "big")
                   + bytes((JPEG_TYPE, JPEG_Q, width // 8, height // 8))
                   + chunk)
        seq, off = (seq + 1) & 0xFFFF, off + len(chunk)
    return out


class Source:
    """One pusher's seeded stream: every packet it will ever push.
    ``shapes`` is the configuration's ``stream`` group: H.264 in FU-A,
    or RTP/JPEG where its ``codec`` names JPEG."""

    def __init__(self, idx: int, seed: int, n_frames: int, fps: float,
                 shapes: dict, start_frame: int = 0, first_gop_at: int = 0,
                 warm_idrs: dict[int, int] | None = None):
        """``start_frame``: the frame with which this source comes on
        line, an IDR (cameras do not all start in one frame period);
        earlier frames hold no packets.  ``first_gop_at``: the frame at
        which its GOP cycle starts after that opening IDR: cameras are
        not GOP-locked, so each source's IDRs fall at their own place
        in the GOP period.  ``warm_idrs``: frame -> the size of an IDR
        that the warm-up carries there in place of a P frame (the mix's
        ``warm_idr_bytes``)."""
        warm_idrs = warm_idrs or {}
        rng = np.random.default_rng([seed, idx])
        self.idx = idx
        self.path = f"/live/cam{idx:02d}"
        self.packets: list[bytes] = []
        #: packet index of each frame's first packet (+ end sentinel)
        self.frame_start: list[int] = []
        #: the frames that a decoder can start from: those that open with
        #: a keyframe run (SPS, PPS, IDR), or every JPEG frame
        self.key_frames: list[int] = []
        seq = int(rng.integers(0, 1 << 16))
        ts = int(rng.integers(0, 1 << 32))
        ssrc = int(rng.integers(1, 1 << 32))
        self.mjpeg = is_mjpeg(shapes)
        self.sdp = SDP_JPEG if self.mjpeg else SDP
        mtu = shapes["rtp_payload_bytes"]
        if self.mjpeg:
            self._jpeg(rng, n_frames, fps, shapes, start_frame, seq, ts, ssrc)
            return
        gop = shapes["gop_frames"]
        idr_lo, idr_hi = shapes["idr_bytes"]
        p_lo, p_hi = shapes["p_frame_bytes"]
        for f in range(n_frames):
            self.frame_start.append(len(self.packets))
            if f < start_frame:
                continue
            if f in warm_idrs or f == start_frame or (
                    f >= first_gop_at and (f - first_gop_at) % gop == 0):
                nals = [bytes((0x67,)) + rng.bytes(23),
                        bytes((0x68,)) + rng.bytes(7)]
                size = (warm_idrs[f] if f in warm_idrs
                        else int(rng.integers(idr_lo, idr_hi)))
                nals.append(bytes((0x65,)) + rng.bytes(size))
                self.key_frames.append(f)
            else:
                nals = [bytes((0x41,)) + rng.bytes(
                    int(rng.integers(p_lo, p_hi)))]
            for k, nal in enumerate(nals):
                out = packetize_h264(nal, seq, ts, ssrc, mtu,
                                     k == len(nals) - 1)
                self.packets += out
                seq = (seq + len(out)) & 0xFFFF
            ts = (ts + round(90_000 / fps)) & 0xFFFFFFFF     # 90 kHz
        self._close()

    def _jpeg(self, rng, n_frames: int, fps: float, shapes: dict,
              start_frame: int, seq: int, ts: int, ssrc: int) -> None:
        """Every frame a JPEG scan of a size drawn from the stream's
        ``frame_bytes``, and every frame a keyframe."""
        lo, hi = shapes["frame_bytes"]
        for f in range(n_frames):
            self.frame_start.append(len(self.packets))
            if f < start_frame:
                continue
            out = packetize_jpeg(rng.bytes(int(rng.integers(lo, hi))), seq,
                                 ts, ssrc, shapes["rtp_payload_bytes"],
                                 shapes["width"], shapes["height"])
            self.packets += out
            self.key_frames.append(f)
            seq = (seq + len(out)) & 0xFFFF
            ts = (ts + round(90_000 / fps)) & 0xFFFFFFFF     # 90 kHz
        self._close()

    def _close(self) -> None:
        self.frame_start.append(len(self.packets))
        #: packet index of each keyframe's head, in order
        self.key_heads = [self.frame_start[f] for f in self.key_frames]
        self.pushed = 0                 # packets written so far
        #: the first packet of each frame that left late or was not
        #: handed whole to the kernel (``Loadgen.push_frames``)
        self.unsure: set[int] = set()
        self.conn: RtspConn | None = None
        self._payloads: dict[bytes, int] | None = None

    def newest_key_head(self) -> int | None:
        """The head of the newest keyframe run pushed so far that the
        server surely holds: one that left within a frame period of its
        due instant and was handed whole to the kernel.  A frame pushed
        late, in the burst with which the generator catches up after a
        stall of its own, or kept in the transport's buffer, may still
        be on its way when a PLAY written after it is answered."""
        k = bisect.bisect_right(self.key_heads, self.pushed - 1)
        return next((h for h in reversed(self.key_heads[:k])
                     if h not in self.unsure), None)

    def start_of(self, got: list[bytes], first_seq: int | None
                 ) -> int | None:
        """The pushed packet a session's first sequence number stands
        for, as the first datagram of ``got`` shows it; None where there
        is none, or it carries none of this source's packets."""
        g = next((g for g in got if len(g) >= 12), None)
        k = None if g is None else self.place_of_payload(g)
        if k is None or first_seq is None:
            return None
        return reference.start_of(int.from_bytes(g[2:4], "big"), k, first_seq)

    def place_of_payload(self, packet: bytes) -> int | None:
        """Which of this source's packets ``packet`` carries, by its
        bytes from 12 on (a relay leaves them as pushed); None where it
        carries none of them."""
        if self._payloads is None:
            self._payloads = {p[12:]: k for k, p in enumerate(self.packets)}
        return self._payloads.get(packet[12:])

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

    async def push_start(self, uri: str, sdp: str = SDP) -> None:
        await self.expect("ANNOUNCE", uri,
                          {"Content-Type": "application/sdp"}, sdp.encode())
        await self.expect("SETUP", f"{uri}/trackID=1", {
            "Transport": "RTP/AVP/TCP;unicast;interleaved=0-1;mode=record"})
        await self.expect("RECORD", uri)

    async def play_start(self, uri: str, rtp_port: int,
                         setup_extra: dict | None = None,
                         before_play=None
                         ) -> tuple[int | None, int | None]:
        """DESCRIBE / SETUP / PLAY over UDP; returns what the server
        announced for this session: (ssrc of SETUP's Transport, first
        sequence number of PLAY's RTP-Info).  ``setup_extra``: more
        SETUP headers (a lossy last mile asks for parity and RTX).
        ``before_play()``: called just before the PLAY is written, with
        nothing awaited in between."""
        await self.expect("DESCRIBE", uri, {"Accept": "application/sdp"})
        hdrs = await self.expect("SETUP", f"{uri}/trackID=1", {
            "Transport": f"RTP/AVP;unicast;client_port={rtp_port}-"
                         f"{rtp_port + 1}", **(setup_extra or {})})
        ssrc = None
        for part in hdrs.get("transport", "").split(";"):
            k, _, v = part.strip().partition("=")
            if k.lower() == "ssrc":
                ssrc = int(v, 16)
        if before_play is not None:
            before_play()
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
    the clock reading at ``recv``.

    A player of an H.264 source joined before media is owed every packet
    pushed.  Where the relay's fast start decides where a player begins —
    a joiner's (``Joiner``), or any player's of an RTP/JPEG source, whose
    every frame is a keyframe, so that the relay starts it at the newest
    frame when it first steps it — the player is owed every packet from
    the keyframe run its first datagram heads (``fast_start``)."""

    def __init__(self, src: Source, ip: str):
        self.src, self.ip = src, ip
        self.rtp, self.rtcp = udp_pair(ip)
        self.port = self.rtp.getsockname()[1]
        self.packets: list[bytes] = []
        self.stamps: list[int] = []         # perf_counter_ns at recv
        self.ssrc: int | None = None        # from the SETUP answer
        self.first_seq: int | None = None   # from the PLAY RTP-Info
        #: behind a lossy last mile, its ``lastmile.StampedReceiver``
        self.lm: lastmile.StampedReceiver | None = None
        self.fast_start = src.mjpeg
        #: the head of the newest keyframe run pushed before its PLAY
        #: (before media: the first packet)
        self.key_head: int | None = 0
        self._start: int | None = None
        #: while a reader in another process holds its datagrams: the
        #: place among its source's packets and the sequence number of
        #: the first (``StampReader.first``)
        self.peek = None

    def start(self) -> int | None:
        """The pushed packet the session's first sequence number stands
        for: 0, or under a fast start as the first datagram read and its
        distance from the PLAY answer's RTP-Info ``seq`` show it; None
        while nothing is read, or where the first datagram is no pushed
        packet's."""
        if not self.fast_start:
            return 0
        if self._start is None and self.packets:
            self._start = self.src.start_of(self.packets, self.first_seq)
        elif self._start is None and self.peek is not None:
            first = self.peek()
            if first and None not in (first[0], self.first_seq):
                self._start = reference.start_of(first[1], first[0],
                                                 self.first_seq)
        return self._start

    def owed(self) -> int:
        """Packets owed so far: every one pushed from its start on (from
        the newest keyframe at its PLAY while nothing is read yet)."""
        start = self.start()
        if start is None:
            start = self.key_head or 0
        return max(self.src.pushed - max(start, 0), 0)


class Joiner(Flow):
    """A stamped player that joins once the window is open: what it was
    owed is fixed by the instant its PLAY was written."""

    def __init__(self, src: Source, ip: str):
        super().__init__(src, ip)
        self.fast_start = True
        self.key_head = None
        self.play_ns: int | None = None     # perf_counter_ns, PLAY written
        self.answer_ns: int | None = None   # ... and its answer read

    def before_play(self) -> None:
        self.key_head = self.src.newest_key_head()
        self.play_ns = time.perf_counter_ns()

    def owed(self) -> int:
        return 0 if self.answer_ns is None else super().owed()


class _Iovec(ctypes.Structure):
    _fields_ = [("base", ctypes.c_void_p), ("len", ctypes.c_size_t)]


class _Msghdr(ctypes.Structure):
    _fields_ = [("name", ctypes.c_void_p), ("namelen", ctypes.c_uint32),
                ("iov", ctypes.POINTER(_Iovec)), ("iovlen", ctypes.c_size_t),
                ("control", ctypes.c_void_p), ("controllen", ctypes.c_size_t),
                ("flags", ctypes.c_int)]


class _Mmsghdr(ctypes.Structure):
    _fields_ = [("hdr", _Msghdr), ("len", ctypes.c_uint)]


_recvmmsg = ctypes.CDLL(None, use_errno=True).recvmmsg
_recvmmsg.argtypes = [ctypes.c_int, ctypes.POINTER(_Mmsghdr), ctypes.c_uint,
                      ctypes.c_int, ctypes.c_void_p]
_recvmmsg.restype = ctypes.c_int


class BatchRecv:
    """``recvmmsg(2)`` into buffers of its own: up to ``n`` queued
    datagrams of a socket in one call.  A ``recv`` a datagram costs a
    system call each, which on the chip's sandboxed host is most of what
    reading costs."""

    SLOT = 4096
    MSG_DONTWAIT = 0x40

    def __init__(self, n: int = 64):
        self.n = n
        self.buf = ctypes.create_string_buffer(n * self.SLOT)
        base = ctypes.addressof(self.buf)
        self.iov = (_Iovec * n)(*(_Iovec(base + i * self.SLOT, self.SLOT)
                                  for i in range(n)))
        self.msgs = (_Mmsghdr * n)()
        for i in range(n):
            self.msgs[i].hdr.iov = ctypes.pointer(self.iov[i])
            self.msgs[i].hdr.iovlen = 1
        self.base = base

    def recv(self, fd: int) -> list[bytes]:
        """What is queued on ``fd``, up to ``n`` datagrams; none where
        nothing is (or the socket is gone)."""
        got = _recvmmsg(fd, self.msgs, self.n, self.MSG_DONTWAIT, None)
        return [ctypes.string_at(self.base + i * self.SLOT, self.msgs[i].len)
                for i in range(max(got, 0))]


class StampReader:
    """Drains the stamped flows in forked worker processes of their own,
    stamping each datagram as it is read: the datagrams one ``BatchRecv``
    call reads share the stamp of the instant it returned.  Eighty
    stamped players at 30 frames a second are more than threads that
    share this process's interpreter lock with the pushers can read on
    time.

    While the workers run, shared memory holds each flow's count and the
    place of its first datagram among its source's packets (what a fast
    start is judged by, ``Flow.start``); ``stop()`` brings every flow's
    datagrams and stamps home, through a pipe from each worker."""

    #: per flow: datagrams read, the place of the first among its source's
    #: packets (UNREAD before one is read, -1 where it is none of them),
    #: its sequence number
    UNREAD = -2

    def __init__(self, flows: list[Flow], n_procs: int):
        self.flows, self.n_procs = flows, n_procs
        self._mm = mmap.mmap(-1, 8 * (1 + 3 * len(flows)))
        self._shared = memoryview(self._mm).cast("q")
        for i in range(len(flows)):
            self._shared[2 + 3 * i] = self.UNREAD
        self.pids: list[int] = []
        self.pipes: list[int] = []

    def start(self) -> None:
        """Fork the workers.  Call before any thread or event loop
        exists in this process."""
        for i, f in enumerate(self.flows):
            f.peek = functools.partial(self.first, i)
        for k in range(min(self.n_procs, len(self.flows))):
            r, w = os.pipe()
            pid = os.fork()
            if pid == 0:
                try:
                    os.close(r)
                    self._work(k, w)
                finally:
                    os._exit(0)
            os.close(w)
            self.pids.append(pid)
            self.pipes.append(r)

    def _work(self, k: int, w: int) -> None:
        signal.signal(signal.SIGINT, signal.SIG_IGN)
        shared, ep = self._shared, select.epoll()
        mine: dict[int, int | None] = {}
        for i in range(k, len(self.flows), self.n_procs):
            f = self.flows[i]
            mine[f.rtp.fileno()], mine[f.rtcp.fileno()] = i, None
            ep.register(f.rtp.fileno(), select.EPOLLIN)
            ep.register(f.rtcp.fileno(), select.EPOLLIN)
        stamps = {i: array.array("q") for i in mine.values() if i is not None}
        packets: dict[int, list[bytes]] = {i: [] for i in stamps}
        rx, now, ppid = BatchRecv(), time.perf_counter_ns, os.getppid()
        while not shared[0]:
            events = ep.poll(0.05)
            if not events and os.getppid() != ppid:
                return                      # orphaned: the parent died
            for fd, _ in events:
                i = mine[fd]
                got = rx.recv(fd)
                while got:
                    if i is not None:
                        if shared[2 + 3 * i] == self.UNREAD:
                            self._note_first(i, got)
                        stamps[i].extend([now()] * len(got))
                        packets[i] += got
                        shared[1 + 3 * i] += len(got)
                    got = rx.recv(fd) if len(got) == rx.n else []
        with os.fdopen(w, "wb") as out:
            for i in sorted(stamps):
                lens = array.array("I", map(len, packets[i]))
                blob = b"".join(packets[i])
                out.write(struct.pack("<qqq", i, len(lens), len(blob)))
                out.write(stamps[i].tobytes() + lens.tobytes())
                out.write(blob)

    def _note_first(self, i: int, got: list[bytes]) -> None:
        g = next((g for g in got if len(g) >= 12), None)
        if g is not None:
            k = self.flows[i].src.place_of_payload(g)
            self._shared[3 + 3 * i] = int.from_bytes(g[2:4], "big")
            self._shared[2 + 3 * i] = -1 if k is None else k

    def read(self) -> int:
        """Datagrams read so far, over every stamped flow."""
        return sum(self._shared[1 + 3 * i] for i in range(len(self.flows)))

    def first(self, i: int) -> tuple[int | None, int] | None:
        """Flow ``i``'s first datagram so far: (its place among its
        source's packets, None where it is none of them; its sequence
        number), or None before one is read."""
        k = self._shared[2 + 3 * i]
        if k == self.UNREAD:
            return None
        return (None if k < 0 else k), self._shared[3 + 3 * i]

    def stop(self) -> None:
        """Stop the workers and bring every flow's datagrams and stamps
        home."""
        self._shared[0] = 1
        for pid, r in zip(self.pids, self.pipes):
            with os.fdopen(r, "rb") as inp:
                while head := inp.read(24):
                    i, n, size = struct.unpack("<qqq", head)
                    stamps, lens = array.array("q"), array.array("I")
                    stamps.frombytes(inp.read(8 * n))
                    lens.frombytes(inp.read(4 * n))
                    blob, f, at = inp.read(size), self.flows[i], 0
                    f.stamps = stamps.tolist()
                    f.packets = []
                    for n_bytes in lens:
                        f.packets.append(blob[at:at + n_bytes])
                        at += n_bytes
            os.waitpid(pid, 0)
        self.pids, self.pipes = [], []


class LossyStampReader(threading.Thread):
    """Drains a fixed set of stamped flows that sit behind a lossy last
    mile, on a thread: each datagram goes to its flow's
    ``lastmile.StampedReceiver``, which holds (and stamps) media as it
    survives the chain or is repaired; the server's sender reports name
    the address RRs and NACKs go to; every ``SWEEP_NS`` the receivers'
    timers run.  Blocking epoll: it wakes when a datagram lands."""

    SWEEP_NS = 20_000_000

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
        self.flows = flows
        self.rtcp_of = {f.rtcp.fileno(): f for f in flows}

    def stop(self) -> None:
        self.stop_flag = True

    def run(self) -> None:
        now = time.perf_counter_ns
        for f in self.flows:
            f.lm.start(now())
        next_sweep = 0
        while not self.stop_flag:
            for fd, _ in self.ep.poll(0.02):
                flow, sock = self.by_fd[fd], self.socks[fd]
                try:
                    while True:
                        if flow is None:
                            _, addr = sock.recvfrom(4096)
                            self.rtcp_of[fd].lm.server_rtcp = addr
                        else:
                            flow.lm.on_rtp(sock.recv(4096), now())
                except OSError:         # drained (BlockingIOError) or gone
                    pass
            t = now()
            if t >= next_sweep:
                next_sweep = t + self.SWEEP_NS
                for f in self.flows:
                    f.lm.sweep(t)


class BulkDrains:
    """Counts datagrams on the bulk (unstamped) flows' sockets in forked
    worker processes of their own, so that neither the pusher nor the
    stamped readers share an interpreter lock with them.  One socket per
    (loopback IP, port) slot: every slot has its own receive queue.

    Counters live in shared memory: ``counts()`` reads them at any time.

    Behind a lossy last mile (``last_mile``) every bulk session has a
    slot of its own (``n_bulk`` of them), since the server routes RTCP
    by its source address: the workers count media, parity and RTX
    apart, run one chain a slot over the media, and send the slot's RR
    every ``rr_interval_s`` from its RTCP socket; ``counts()`` then
    reads media only.
    """

    #: per process: rtp datagrams, rtp bytes, rtcp datagrams; behind a
    #: lossy last mile also parity and RTX datagrams, media the chains
    #: lost, RRs sent
    LOSSY_COUNTS = ("parity", "rtx", "lost_by_chain", "rrs_sent")

    def __init__(self, n_procs: int, last_mile=None, seed: int = 0,
                 n_bulk: int = 0):
        self.n_procs = n_procs
        self.rtp: list[socket.socket] = []
        self.rtcp: list[socket.socket] = []
        self.ports: list[int] = []
        groups = N_PORT if last_mile is None else -(-n_bulk // N_IP)
        for _ in range(groups):
            self.ports.append(self._open_port_group())
        self.last_mile, self.seed = last_mile, seed
        self.stride = 3 + (len(self.LOSSY_COUNTS) if last_mile else 0)
        # shared: [stop, pad] then ``stride`` counters a process
        self._mm = mmap.mmap(-1, 8 * (2 + self.stride * n_procs))
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
        """Bulk destination ``j`` of a source: (ip, rtp port).  Behind a
        lossy last mile ``j`` is the session's place among all sources'
        bulk sessions, and its slot is ``self.rtp[j]``."""
        if self.last_mile:
            return f"127.0.0.{1 + j % N_IP}", self.ports[j // N_IP]
        return (f"127.0.0.{1 + (j // N_PORT) % N_IP}",
                self.ports[j % N_PORT])

    def start(self) -> None:
        """Fork the workers.  Call before any thread or event loop
        exists in this process."""
        for k in range(self.n_procs):
            pid = os.fork()
            if pid == 0:
                try:
                    (self._work_lossy if self.last_mile else self._work)(k)
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

    def _work_lossy(self, k: int) -> None:
        """``_work`` behind the last mile: media, parity and RTX counted
        apart, each slot's media through its chain, and its RR sent from
        its RTCP socket to the address the server's SRs come from."""
        signal.signal(signal.SIGINT, signal.SIG_IGN)
        lm, shared = self.last_mile, self._shared
        base = 2 + self.stride * k
        ep = select.epoll()
        mine: dict[int, tuple[socket.socket, int, bool]] = {}
        #: slot -> its chain and reports, next RR, own SSRC, the SSRC of
        #: the session it reports on
        slots: dict[int, list] = {}
        t0 = time.perf_counter_ns()
        for slot in range(k, len(self.rtp), self.n_procs):
            for s, is_rtp in ((self.rtp[slot], True),
                              (self.rtcp[slot], False)):
                mine[s.fileno()] = (s, slot, is_rtp)
                ep.register(s.fileno(), select.EPOLLIN)
            r = lastmile.rng(self.seed, "bulk-rtcp", slot)
            slots[slot] = [lastmile.BulkSource(lm.chain(self.seed, "bulk",
                                                        slot)),
                           t0 + int(r.random() * lm.rr_interval_ns),
                           r.randrange(1, 1 << 32), None]
        server = None
        buf = bytearray(4096)
        tot = [0] * self.stride
        ppid = os.getppid()
        while not shared[0]:
            events = ep.poll(0.05)
            if not events and os.getppid() != ppid:
                return                      # orphaned: the parent died
            for fd, _ in events:
                s, slot, is_rtp = mine[fd]
                try:
                    while True:
                        if not is_rtp:
                            _, server = s.recvfrom_into(buf)
                            tot[2] += 1
                            continue
                        n = s.recv_into(buf)
                        kind = lm.kind(buf) if n >= 12 else "media"
                        if kind != "media":
                            tot[3 if kind == "parity" else 4] += 1
                            continue
                        tot[0] += 1
                        tot[1] += n
                        if n >= 12:
                            st = slots[slot]
                            st[3] = int.from_bytes(buf[8:12], "big")
                            tot[5] += not st[0].on_media(buf)
                except OSError:
                    pass
            now = time.perf_counter_ns()
            for slot, st in slots.items():
                if now < st[1]:
                    continue
                st[1] = lm.next_report(st[1], now)
                if server is None or st[0].stats.base is None:
                    continue
                try:
                    self.rtcp[slot].sendto(lastmile.receiver_report(
                        st[2], [st[0].stats.block(st[3])]), server)
                    tot[6] += 1
                except OSError:
                    pass
            for c, v in enumerate(tot):
                shared[base + c] = v

    def counts(self) -> tuple[int, int, int]:
        """(RTP datagrams, RTP bytes, RTCP datagrams) so far; behind a
        lossy last mile the RTP ones count media only."""
        s, w = self._shared, self.stride
        return tuple(sum(s[2 + w * k + c] for k in range(self.n_procs))
                     for c in range(3))

    def lossy_counts(self) -> dict[str, int]:
        """``LOSSY_COUNTS`` over every process so far."""
        s, w = self._shared, self.stride
        return {name: sum(s[2 + w * k + 3 + c] for k in range(self.n_procs))
                for c, name in enumerate(self.LOSSY_COUNTS)}

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


# ------------------------------------------------------------------ joins
#: a joining burst's phase in its source's GOP: burst i (in time order)
#: lands at the fractional part of (i + 1/2) times this, of a GOP past a
#: keyframe, so that the phases are spread and owe nothing to the sizes
PHASE_STEP = (5 ** 0.5 - 1) / 2


def burst_sizes(joins: dict) -> list[int]:
    """The bursts' sizes, in the order they join: ``burst_size``'s range
    from its low end up, again and again, until ``count`` players are
    placed, the last burst cut to fit.  The same for every seed."""
    lo, hi = (int(x) for x in joins["burst_size"])
    sizes, left = [], int(joins["count"])
    while left > 0:
        for n in range(lo, hi + 1):
            if left > 0:
                sizes.append(min(n, left))
                left -= sizes[-1]
    return sizes


def join_schedule(joins: dict, seed: int, gop_s: float, gop_at_s: float
                  ) -> list[tuple[float, int]]:
    """A mix's ``joins`` as bursts: ``(seconds into the window, players
    that join then)``, in time order.  Every seed gets the same bursts
    (``burst_sizes``) at the same phases of the source's GOP
    (``PHASE_STEP``; ``gop_s`` its length, ``gop_at_s`` where one starts,
    both in seconds of the window), each in a GOP of its own: the seed
    draws which GOPs, uniformly among those that lie inside
    ``within_s``.  What a joiner waits for is then the same for every
    seed — how old the newest keyframe is at its PLAY, and which output
    bucket it lands in — and the seed moves it in time."""
    a, b = (float(x) for x in joins["within_s"])
    sizes = burst_sizes(joins)
    phase = [((i + 0.5) * PHASE_STEP) % 1.0 * gop_s
             for i in range(len(sizes))]
    first = int(np.ceil((a - gop_at_s) / gop_s))
    gops = list(range(first, int(np.floor((b - gop_at_s) / gop_s))))
    if len(gops) < len(sizes):
        raise LoadgenError(f"joins {joins!r}: {len(sizes)} bursts need as "
                           f"many GOPs of {gop_s:g} s inside within_s")
    rng = np.random.default_rng([seed, 2 << 20])
    drawn = sorted(int(g) for g in rng.choice(gops, len(sizes),
                                              replace=False))
    return [(gop_at_s + g * gop_s + p, n)
            for g, p, n in zip(drawn, phase, sizes)]


def check_joins(joins: dict, n_sources: int, seconds: float) -> None:
    """A ``joins`` group the generator can play, or ``LoadgenError``."""
    try:
        src, count = int(joins["source"]), int(joins["count"])
        lo, hi = (int(x) for x in joins["burst_size"])
        a, b = (float(x) for x in joins["within_s"])
    except (KeyError, TypeError, ValueError) as e:
        raise LoadgenError(f"joins {joins!r}: {e!r}") from e
    if not (0 <= src < n_sources and count >= 1 and 1 <= lo <= hi
            and 0 < a < b <= seconds):
        raise LoadgenError(
            f"joins {joins!r}: a source of the {n_sources}, a count of 1 or "
            f"more, burst sizes 1 <= lo <= hi, and instants inside the "
            f"{seconds:g} s window")


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
        try:
            self.last_mile = lastmile.LastMile.of(cfg)
        except (lastmile.LastMileError, KeyError, TypeError,
                ValueError) as e:
            raise LoadgenError(f"last_mile: {e}") from e
        self.joins = traffic.get("joins")
        if self.joins is not None:
            check_joins(self.joins, n_sources, seconds)
            if self.last_mile:
                raise LoadgenError("joins behind a lossy last mile: the "
                                   "joiners would need chains and reports")
        #: SETUP headers a lossy last mile's players add: they ask for
        #: parity and RTX (the server grants them only when asked)
        self.setup_extra = {"x-FEC": "parity"} if self.last_mile else None
        self.fps = float(traffic["fps_per_source"])
        self.warm_frames = int(traffic["warm_frames"])
        self.n_src, self.n_sub = n_sources, n_players
        self.wave = int(cfg["players"]["join_wave"])
        self.stamped_every = int(cfg["players"]["stamped_every"])
        n_win = int(np.ceil(seconds * self.fps)) + 1
        # GOP phases: the same evenly spread set for every seed, dealt to
        # the sources in the seed's order
        gop = cfg["stream"].get("gop_frames", 1)     # RTP/JPEG: none
        deal = np.random.default_rng([seed, 1 << 20]).permutation(n_sources)
        W = self.warm_frames
        warm_idrs = self._warm_idrs(traffic, cfg["stream"], n_sources)
        self.sources = [
            Source(i, seed, W + n_win, self.fps, cfg["stream"],
                   start_frame=i * W // (2 * n_sources),
                   first_gop_at=W + int(deal[i]) * gop // n_sources,
                   warm_idrs=warm_idrs)
            for i in range(n_sources)]
        #: bulk sessions a source
        self.n_bulk = n_players - len(range(0, n_players, self.stamped_every))
        if self.sources[0].mjpeg and (self.n_bulk or self.last_mile):
            # where the relay starts a player is read off its stamps
            raise LoadgenError("an RTP/JPEG source's players are all "
                               "stamped (stamped_every 1), with no last "
                               "mile: the relay starts each at the newest "
                               "frame when it first steps it")
        self.bulk = BulkDrains(int(traffic.get("bulk_drain_procs", 4)),
                               self.last_mile, seed, n_sources * self.n_bulk)
        self.flows: list[Flow] = []
        for src in self.sources:
            for j in range(n_players):
                if j % self.stamped_every == 0:
                    self.flows.append(Flow(
                        src, f"127.0.0.{1 + (src.idx + j) % N_IP}"))
        #: the players who join inside the window, all stamped, and when
        self.joiners: list[Joiner] = []
        self.join_plan: list[tuple[float, int]] = []
        if self.joins is not None:
            src = self.sources[int(self.joins["source"])]
            gop = 1 if src.mjpeg else gop
            # the window opens at frame W; the source's GOP cycle starts
            # at its ``first_gop_at``
            at = int(deal[src.idx]) * gop // n_sources % gop
            self.join_plan = join_schedule(self.joins, seed, gop / self.fps,
                                           at / self.fps)
            self.joiners = [Joiner(src, f"127.0.0.{1 + j % N_IP}")
                            for j in range(int(self.joins["count"]))]
        if self.last_mile:
            for n, f in enumerate(self.flows):
                f.lm = lastmile.StampedReceiver(f, self.last_mile, seed, n)
            self.readers = [LossyStampReader(self.flows[k::4],
                                             f"stamp-reader-{k}")
                            for k in range(4)]
        else:
            self.readers = [StampReader(self.flows + self.joiners, 4)]
        self.players: list[RtspConn] = []
        self.n_bulk_joined = 0
        self.lateness: list[float] = []
        #: (source idx, frame) -> perf_counter_ns at which it was due
        self.due_ns: dict[tuple[int, int], int] = {}

    #: frames from one of the warm-up's stated IDRs to the next
    WARM_IDR_EVERY = 6

    def _warm_idrs(self, traffic: dict, stream: dict, n_sources: int
                   ) -> dict[int, int]:
        """The mix's ``warm_idr_bytes``: the warm-up's last frames carry
        IDRs of these sizes, in this order, one every ``WARM_IDR_EVERY``
        frames, and ``warm_tail`` says how many frames that is.  The
        harness pushes those frames only once the server has sent all it
        owes for the earlier ones, so that each such IDR reaches a server
        no longer behind its first builds, in a read of its own: the
        packet counts the window's IDRs take on either side of a
        device-ring pad (an IDR is 30-34 packets, astride 32) are then
        each met before the window opens.  Each size lies in the
        stream's ``idr_bytes``; every source comes on line before the
        tail."""
        sizes = [int(b) for b in traffic.get("warm_idr_bytes", ())]
        lo, hi = stream["idr_bytes"] if sizes else (0, 0)
        W = self.warm_frames
        self.warm_tail = self.WARM_IDR_EVERY * len(sizes)
        last_start = (n_sources - 1) * W // (2 * n_sources)
        if any(not lo <= b <= hi for b in sizes) or (
                sizes and last_start >= W - self.warm_tail):
            raise LoadgenError(
                f"warm_idr_bytes {sizes}: each in idr_bytes [{lo}, {hi}], "
                f"and their {self.warm_tail} frames after every source's "
                f"opening IDR (frame {last_start} at the latest) in the "
                f"{W} warm-up frames")
        return {W - self.warm_tail + self.WARM_IDR_EVERY * j: b
                for j, b in enumerate(sizes)}

    def start_receivers(self) -> None:
        self.bulk.start()               # forks: before any thread
        for r in self.readers:
            r.start()

    def stop_receivers(self) -> None:
        for r in self.readers:
            r.stop()
        self.bulk.stop()

    def stamped_read(self) -> int:
        """Datagrams read from the stamped players' sockets so far."""
        if self.last_mile:
            return sum(len(f.stamps) for f in self.flows)
        return self.readers[0].read()

    def received(self) -> int:
        """RTP datagrams read from any player socket so far."""
        return self.bulk.counts()[0] + self.stamped_read()

    # -- sessions ----------------------------------------------------------
    async def start_pushers(self, port: int) -> None:
        for s in self.sources:
            c = RtspConn()
            await c.connect("127.0.0.1", port)
            await c.push_start(f"rtsp://127.0.0.1:{port}{s.path}", s.sdp)
            s.conn = c

    async def _join(self, port: int, src: Source, ip: str, rtp_port: int,
                    flow: Flow | None) -> None:
        c = RtspConn()
        await c.connect("127.0.0.1", port, local_ip=ip)
        ssrc, seq = await c.play_start(
            f"rtsp://127.0.0.1:{port}{src.path}", rtp_port, self.setup_extra,
            flow.before_play if isinstance(flow, Joiner) else None)
        self.players.append(c)
        if flow is not None:
            flow.ssrc, flow.first_seq = ssrc, seq
            if isinstance(flow, Joiner):
                flow.answer_ns = time.perf_counter_ns()
        else:
            self.n_bulk_joined += 1

    async def run_joins(self, port: int, t0_ns: int) -> None:
        """The joiners, burst by burst at ``join_plan``'s instants after
        ``t0_ns``; a burst's players connect and send their DESCRIBEs
        back to back, and a burst does not wait for the one before it.
        Returns once every joiner's PLAY is answered."""
        todo, bursts = iter(self.joiners), []
        for at, n in self.join_plan:
            delay = (t0_ns + int(at * 1e9) - time.perf_counter_ns()) / 1e9
            if delay > 0:
                await asyncio.sleep(delay)
            src = self.joiners[0].src
            bursts.append(asyncio.ensure_future(asyncio.gather(*(
                self._join(port, src, j.ip, j.port, j)
                for j in (next(todo) for _ in range(n))))))
        await asyncio.gather(*bursts)

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
                        ip, p = self.bulk.slot(
                            src.idx * self.n_bulk + bulk_j
                            if self.last_mile else bulk_j)
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
            late = max(time.perf_counter_ns() - due_ns, 0) / 1e9
            self.lateness.append(late)
            src = self.sources[i]
            self.due_ns[(i, f)] = due_ns
            data, k = src.frame_bytes(f)
            src.conn.writer.write(data)
            if late > 1 / self.fps or \
                    src.conn.writer.transport.get_write_buffer_size():
                src.unsure.add(src.frame_start[f])
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
        """Pushed x joined before media, less what a fast start leaves
        out (``Flow.owed``), and what each joiner is owed from its
        start."""
        return (sum(s.pushed for s in self.sources) * self.n_sub
                - sum(f.src.pushed - f.owed() for f in self.flows
                      if f.fast_start)
                + sum(j.owed() for j in self.joiners))

    # -- the lossy last mile -------------------------------------------------
    def mark_pushed(self) -> None:
        """Every stamped receiver learns how many packets it is owed so
        far, once the server has sent them all: a loss at the tail of
        what was pushed has no later packet to show it.  (Before that, a
        packet not read yet may be one the server has not sent: asking
        for it would make the server send it twice.)"""
        for f in self.flows:
            f.lm.end = f.src.pushed

    def quiesce(self) -> None:
        """No more NACKs: the drain is over, what is missing stays so."""
        for f in self.flows:
            f.lm.nacking = False

    def lossy_counts(self) -> dict[str, int]:
        """What the players read and repaired behind the last mile."""
        got = self.bulk.lossy_counts()
        lms = [f.lm for f in self.flows]
        got["parity"] += sum(len(m.parity) for m in lms)
        got["rtx"] += sum(len(m.rtx) for m in lms)
        got["lost_by_chain"] += sum(m.lost_by_chain for m in lms)
        got["rrs_sent"] += sum(m.rrs_sent for m in lms)
        for name in ("recovered_fec", "recovered_rtx", "nacks_sent"):
            got[name] = sum(getattr(m, name) for m in lms)
        return got
