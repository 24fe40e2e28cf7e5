#!/usr/bin/env python3
"""chip_smoke.py — the served relay path, once, on the chip.

Starts ONE child, ``python -m easydarwin_tpu -c <toml>`` with
``tpu_fanout = true`` and every other engine key at its default, and
drives it from outside through real sockets at BASELINE config 4's full
width: 16 RTSP pushers (ANNOUNCE / SETUP mode=record / RECORD, 1400-byte
H.264 FU-A RTP, an IDR every 30 frames) and 256 UDP players per source
(DESCRIBE / SETUP / PLAY, 4,096 sessions) on 256 distinct
``(127.0.0.x, port)`` destinations per source, plus one late joiner per
source.  The child is the only process that touches the chip: this
parent never imports JAX.

It exits 0, and prints ``{"ok": true, "device": {...}}`` as its last
line, only if the server says it runs on a TPU, every checked packet is
bit-equal to the pushed one, the server's own egress count shows no
shortfall, the megabatch scheduler ran and stacked streams, no delivery
left through the scalar host loop, the degradation ladder rests at rung
0 having moved for no device reason, no device error was retried or
swallowed, the native core was built from this tree, every other served
jitted step matched its host oracle on the device
(``/api/v1/devicecheck``), and the child exits 0 on SIGTERM.

    python chip_smoke.py                       # the chip run, full width
    JAX_PLATFORMS=cpu python chip_smoke.py --sources 2 --players 8
                                               # the same code, debug size

The second form is for debugging this script where there is no chip: it
says ``platform cpu`` and passes only because the CPU was asked for by
name AND a size was given.  A full-width run never accepts a CPU.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import resource
import selectors
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from easydarwin_tpu import native  # noqa: E402
from easydarwin_tpu.protocol import nalu, rtp  # noqa: E402
from easydarwin_tpu.utils.client import RtspClient  # noqa: E402

# default frames/s per source (see --fps): the cut of scale a one-chip
# machine forces.  At 4,096 subscribers its served path (sandboxed
# loopback, no UDP GSO: about 20 us per delivered datagram) saturates near
# 70,000 deliveries/s, i.e. ~1.4 frames/s/source of 12-packet frames; 0.4
# offers ~30 % of that, so a healthy server keeps up.  The RTP timestamps
# advance at the same rate: the stream is what it says it is.
FPS = 0.4
GOP = 30                    # an IDR every 30 frames
WARM_FRAMES = GOP           # one GOP before the timed window
TIMED_FRAMES = 45           # two IDRs; extended until >= TIMED_MIN_PKTS
TIMED_MIN_PKTS = 512        # two 256-packet windows per source
LATE_JOIN_FRAME = 32        # timed-window frame at which late joiners PLAY
# (two frames past the second IDR: the fast-start backlog they are sent at
# once then fits a 208 KiB receive queue, the cap on the chip machines)
MTU = 1400
N_IP, N_PORT = 64, 4        # bulk destinations: 64 loopback IPs x 4 ports
BUCKET_DRAIN_S = 16 * 0.073  # the last bucket's stagger (16 x 73 ms)
# megabatch_streams_total / megabatch_passes_total must exceed this at full
# width: some pass stacked more than one source.  (ISSUE 21 asked for 16
# per pass; sources that are not frame-locked, at a pace this host serves,
# give ~1.08 — CHANGES.md PR 21.)
MIN_STREAMS_PER_PASS = 1.0
LADDER_RECOVER_WAIT_S = 30.0  # resilience_recover_sec (10) + slack
SO_RCVBUFFORCE = 33
RCVBUF = 1 << 24

SDP = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=chip_smoke\r\nt=0 0\r\n"
       "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
       "a=control:trackID=1\r\n")


class SmokeFailure(Exception):
    """A phase failed in a way that makes the later phases pointless."""


def log(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


# ------------------------------------------------------------------ media
class Source:
    """One pusher's seeded stream: every packet it will ever push."""

    def __init__(self, idx: int, seed: int, n_frames: int, fps: float):
        rng = np.random.default_rng([seed, idx])
        self.idx = idx
        self.path = f"/live/cam{idx:02d}"
        self.packets: list[bytes] = []
        #: packet index of each frame's first packet (+ end sentinel)
        self.frame_start: list[int] = []
        #: packet indices that start an IDR access unit (its SPS)
        self.idr_starts: set[int] = set()
        seq = int(rng.integers(0, 1 << 16))
        ts = int(rng.integers(0, 1 << 32))
        ssrc = int(rng.integers(1, 1 << 32))
        for f in range(n_frames):
            self.frame_start.append(len(self.packets))
            if f % GOP == 0:
                self.idr_starts.add(len(self.packets))
                nals = [bytes((0x67,)) + rng.bytes(23),
                        bytes((0x68,)) + rng.bytes(7),
                        bytes((0x65,)) + rng.bytes(
                            int(rng.integers(38_000, 44_000)))]
            else:
                nals = [bytes((0x41,)) + rng.bytes(
                    int(rng.integers(13_000, 16_500)))]
            for k, nal in enumerate(nals):
                out = nalu.packetize_h264(
                    nal, seq=seq, timestamp=ts, ssrc=ssrc, mtu=MTU,
                    marker_on_last=k == len(nals) - 1)
                self.packets += out
                seq = (seq + len(out)) & 0xFFFF
            ts = (ts + round(90_000 / fps)) & 0xFFFFFFFF     # 90 kHz
        self.frame_start.append(len(self.packets))
        self.pushed = 0                 # packets written so far
        self.client: RtspClient | None = None

    def frame_bytes(self, f: int) -> tuple[bytes, int]:
        lo, hi = self.frame_start[f], self.frame_start[f + 1]
        framed = b"".join(b"$\x00" + len(p).to_bytes(2, "big") + p
                          for p in self.packets[lo:hi])
        return framed, hi - lo


# -------------------------------------------------------------- receivers
def _udp_socket(ip: str, port: int = 0) -> socket.socket:
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.setblocking(False)
    for opt in (SO_RCVBUFFORCE, socket.SO_RCVBUF):
        try:
            s.setsockopt(socket.SOL_SOCKET, opt, RCVBUF)
            break
        except OSError:
            continue
    s.bind((ip, port))
    return s


def udp_pair(ip: str) -> tuple[socket.socket, socket.socket]:
    """An (RTP, RTCP) socket pair on adjacent ports of ``ip``."""
    for _ in range(64):
        a = _udp_socket(ip)
        port = a.getsockname()[1]
        if port < 65535:            # the kernel does hand out 65535
            try:
                return a, _udp_socket(ip, port + 1)
            except OSError:
                pass
        a.close()
    raise SmokeFailure(f"no adjacent UDP port pair on {ip}")


class Flow:
    """One fully-checked player: its own socket pair, every datagram
    kept."""

    def __init__(self, src: Source, ip: str, label: str):
        self.src, self.ip, self.label = src, ip, label
        self.rtp, self.rtcp = udp_pair(ip)
        self.port = self.rtp.getsockname()[1]
        self.packets: list[bytes] = []
        self.ssrc: int | None = None        # from the SETUP answer
        self.first_seq: int | None = None   # from the PLAY RTP-Info


class CheckedReader(threading.Thread):
    """Drains every checked flow's sockets as datagrams arrive."""

    def __init__(self):
        super().__init__(daemon=True, name="checked-reader")
        self.sel = selectors.DefaultSelector()
        self.lock = threading.Lock()
        self.stop_flag = False

    def add(self, flow: Flow) -> None:
        with self.lock:
            self.sel.register(flow.rtp, selectors.EVENT_READ, flow)
            self.sel.register(flow.rtcp, selectors.EVENT_READ, None)

    def run(self) -> None:
        while not self.stop_flag:
            with self.lock:
                events = self.sel.select(timeout=0.02)
            for key, _ in events:
                flow = key.data
                try:
                    while True:
                        data = key.fileobj.recv(4096)
                        if flow is not None:
                            flow.packets.append(data)
                except OSError:         # drained (BlockingIOError) or gone
                    pass
            if not events:
                time.sleep(0.001)       # let add() take the lock


class BulkDrain(threading.Thread):
    """Counts datagrams on the unchecked flows' wildcard sockets
    (native recvmmsg discard-drain; no GRO, so messages = datagrams)."""

    def __init__(self, socks):
        super().__init__(daemon=True, name="bulk-drain")
        self.socks = socks      # held: a collected socket's fd number is
        # reused, and the drain would then eat another socket's packets
        self.fds = [s.fileno() for s in socks]
        self.count = 0
        self.stop_flag = False

    def run(self) -> None:
        while not self.stop_flag:
            n, _nbytes = native.udp_drain_ex(self.fds)
            self.count += n
            if n == 0:
                time.sleep(0.001)


# ------------------------------------------------------------------- REST
_OPENER = urllib.request.build_opener(urllib.request.ProxyHandler({}))


def http_get(port: int, path: str, timeout: float = 60.0) -> bytes:
    with _OPENER.open(f"http://127.0.0.1:{port}{path}",
                      timeout=timeout) as r:
        return r.read()


def parse_metrics(text: str) -> dict[str, float]:
    out: dict[str, float] = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        key, _, val = ln.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            pass
    return out


def fam(m: dict[str, float], name: str) -> float:
    """Sum of every child of metric family ``name`` (0 when absent)."""
    return sum(v for k, v in m.items()
               if k == name or k.startswith(name + "{"))


def _ranges(idx: list[int]) -> str:
    """[3, 4, 5, 9] -> "3-5,9" (first few runs)."""
    runs: list[list[int]] = []
    for i in idx:
        if runs and i == runs[-1][1] + 1:
            runs[-1][1] = i
        else:
            runs.append([i, i])
    txt = ",".join(f"{a}-{b}" if b > a else str(a) for a, b in runs[:8])
    return txt + (",..." if len(runs) > 8 else "")


# ------------------------------------------------------------- the server
class Server:
    def __init__(self, out_dir: str, devices: int | None):
        self.out_dir = out_dir
        self.log_dir = os.path.join(out_dir, "logs")
        # this run's logs only: the error-log check reads them whole
        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        os.makedirs(os.path.join(out_dir, "movies"), exist_ok=True)
        self.cfg_path = os.path.join(out_dir, "server.toml")
        lines = ['rtsp_port = 0', 'service_port = 0',
                 'bind_ip = "127.0.0.1"', 'wan_ip = "127.0.0.1"',
                 'tpu_fanout = true',
                 f'log_folder = "{self.log_dir}"',
                 f'movie_folder = "{os.path.join(out_dir, "movies")}"']
        if devices is not None:
            lines.append(f"megabatch_devices = {devices}")
        with open(self.cfg_path, "w") as f:
            f.write("\n".join(lines) + "\n")
        self.stdout_lines: list[str] = []
        self.proc: subprocess.Popen | None = None
        self.rtsp_port = self.rest_port = 0

    def start(self) -> None:
        err = open(os.path.join(self.out_dir, "server.stderr"), "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "easydarwin_tpu", "-c", self.cfg_path],
            cwd=HERE, stdout=subprocess.PIPE, stderr=err, text=True,
            env=dict(os.environ, PYTHONUNBUFFERED="1"))
        err.close()
        threading.Thread(target=self._pump_stdout, daemon=True).start()

    def _pump_stdout(self) -> None:
        with open(os.path.join(self.out_dir, "server.stdout"), "w") as f:
            for ln in self.proc.stdout:
                f.write(ln)
                f.flush()
                self.stdout_lines.append(ln.rstrip("\n"))

    def wait_boot(self, timeout: float = 300.0) -> str:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            for ln in self.stdout_lines:
                if "listening:" in ln:
                    self.rtsp_port = int(
                        ln.split("rtsp://")[1].split()[0].rsplit(":", 1)[1])
                    self.rest_port = int(
                        ln.split("http://")[1].split("/")[0]
                        .rsplit(":", 1)[1])
                    return ln
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited {self.proc.returncode} before it "
                    f"listened: {self.stderr_tail()}")
            time.sleep(0.1)
        raise SmokeFailure("server did not listen within "
                           f"{timeout:.0f}s: {self.stderr_tail()}")

    def stderr_tail(self, n: int = 1500) -> str:
        try:
            with open(os.path.join(self.out_dir, "server.stderr"),
                      errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def info(self) -> dict:
        doc = json.loads(http_get(self.rest_port, "/api/v1/getserverinfo"))
        return doc["EasyDarwin"]["Body"]

    def metrics(self) -> dict[str, float]:
        return parse_metrics(http_get(self.rest_port, "/metrics").decode())

    def events(self, since: int) -> list[dict]:
        """Every event-log record after cursor ``since``, oldest first."""
        out: list[dict] = []
        while True:
            page = http_get(self.rest_port,
                            f"/api/v1/events?n=1024&since={since}")
            recs = [json.loads(ln) for ln in page.decode().splitlines()]
            out += recs
            if len(recs) < 1024:
                return out
            since = recs[-1]["seq"]

    def terminate(self) -> int | None:
        """SIGTERM, wait; SIGKILL only if it will not go.  Returns the
        exit code of a clean SIGTERM shutdown, None if it had to be
        killed."""
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                return self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                return None
        return self.proc.returncode


# ----------------------------------------------------------------- driver
class Smoke:
    def __init__(self, args):
        self.args = args
        self.sized = args.sources is not None or args.players is not None
        self.n_src = args.sources or 16
        self.n_sub = args.players or 256
        self.failures: list[str] = []
        self.facts: dict = {}
        self.out_dir = os.path.abspath(args.out)
        os.makedirs(self.out_dir, exist_ok=True)
        self.server = Server(self.out_dir, args.devices)
        n_frames = WARM_FRAMES + TIMED_FRAMES
        while True:
            self.sources = [Source(i, args.seed, n_frames, args.fps)
                            for i in range(self.n_src)]
            if min(s.frame_start[-1] - s.frame_start[WARM_FRAMES]
                   for s in self.sources) >= TIMED_MIN_PKTS:
                break
            n_frames += 1
        self.n_frames = n_frames
        self.reader = CheckedReader()
        self.checked: list[Flow] = []
        self.late: list[Flow] = []
        self.players: list[RtspClient] = []
        self.bulk_socks: list[socket.socket] = []
        self.bulk_rtcp_socks: list[socket.socket] = []
        self.bulk_ports: list[int] = []
        self.lateness: list[float] = []
        self.event_seq = 0
        #: the server's ladder / SLO / swallowed-error events, in order
        self.events: list[dict] = []

    def fail(self, msg: str) -> None:
        log(f"FAIL: {msg}")
        self.failures.append(msg)

    # -- set-up ------------------------------------------------------------
    def open_receivers(self) -> None:
        # deep receive queues want rmem_max above its default; best
        # effort, as bench.py does (SO_RCVBUFFORCE is the fallback)
        subprocess.run(["sysctl", "-q", "-w",
                        f"net.core.rmem_max={RCVBUF * 2}"],
                       check=False, capture_output=True, timeout=5)
        for _ in range(N_PORT):
            a, b = udp_pair("0.0.0.0")
            self.bulk_socks.append(a)
            self.bulk_rtcp_socks.append(b)
            self.bulk_ports.append(a.getsockname()[1])
        # the RTCP partners get their own discard drain (SRs only)
        self.bulk = BulkDrain(self.bulk_socks)
        self.bulk_rtcp = BulkDrain(self.bulk_rtcp_socks)
        self.bulk.start()
        self.bulk_rtcp.start()
        self.reader.start()
        got = self.bulk_socks[0].getsockopt(socket.SOL_SOCKET,
                                            socket.SO_RCVBUF)
        log(f"receivers: {N_PORT} wildcard RTP ports {self.bulk_ports} "
            f"(SO_RCVBUF {got})")

    async def start_pushers(self) -> None:
        for s in self.sources:
            c = RtspClient()
            c.request_timeout = 120.0
            await c.connect("127.0.0.1", self.server.rtsp_port)
            await c.push_start(
                f"rtsp://127.0.0.1:{self.server.rtsp_port}{s.path}", SDP)
            s.client = c

    async def _join(self, src: Source, ip: str, rtp_port: int,
                    flow: Flow | None) -> None:
        c = RtspClient()
        c.request_timeout = 300.0
        await c.connect("127.0.0.1", self.server.rtsp_port,
                        local_addr=(ip, 0))
        await c.play_start(
            f"rtsp://127.0.0.1:{self.server.rtsp_port}{src.path}",
            tcp=False, client_ports=[(rtp_port, rtp_port + 1)])
        self.players.append(c)
        if flow is not None:
            flow.ssrc = c.transports[0].ssrc
            info = c.play_response.headers.get("rtp-info", "")
            if "seq=" in info:
                flow.first_seq = int(
                    info.split("seq=")[1].split(";")[0].split(",")[0])

    async def join_players(self) -> None:
        """Every source gets ``n_sub`` players on ``n_sub`` distinct
        destinations: ``n_sub - 1`` on the bulk layout (bench.py's 64
        IPs x 4 ports, in that order) and one fully-checked player on
        its own socket pair."""
        sem = asyncio.Semaphore(96)

        async def one(src, ip, port, flow):
            async with sem:
                await self._join(src, ip, port, flow)

        jobs = []
        for src in self.sources:
            for j in range(self.n_sub - 1):
                ip = f"127.0.0.{1 + (j // N_PORT) % N_IP}"
                jobs.append(one(src, ip, self.bulk_ports[j % N_PORT],
                                None))
            flow = Flow(src, f"127.0.0.{1 + src.idx % N_IP}", "checked")
            self.reader.add(flow)
            self.checked.append(flow)
            jobs.append(one(src, flow.ip, flow.port, flow))
        await asyncio.gather(*jobs)

    # -- media -------------------------------------------------------------
    async def push_frames(self, lo: int, hi: int, hooks=None) -> None:
        """Push frames [lo, hi) of every source at ``--fps`` frames/s
        each, every source on its OWN phase (cameras are not frame-
        locked), recording how late each frame left."""
        fps, n = self.args.fps, self.n_src
        plan = sorted((((f - lo) + s.idx / n) / fps, s.idx, f)
                      for s in self.sources for f in range(lo, hi))
        t0 = time.perf_counter()
        for due, i, f in plan:
            delay = t0 + due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            self.lateness.append(max(time.perf_counter() - t0 - due, 0.0))
            src = self.sources[i]
            data, k = src.frame_bytes(f)
            src.client.writer.write(data)
            src.pushed += k
            await src.client.writer.drain()
            if hooks and i == 0 and f - lo in hooks:
                hooks[f - lo]()
        # the window lasts a whole number of frame periods
        await asyncio.sleep(max(t0 + (hi - lo) / fps
                                - time.perf_counter(), 0))

    async def keepalive(self) -> None:
        """What idle clients do on their RTSP connection: an OPTIONS
        each, every player inside any 40 s (the server reaps an RTSP
        session silent for rtsp_timeout_sec = 120) and every pusher
        inside any 5 s (push_timeout_sec = 20 covers the gaps between
        pushes).  One slice a second, its requests in flight together:
        a busy server answers late, and waiting for each answer in turn
        would itself outlast the timeout."""
        async def ping(c: RtspClient) -> None:
            try:
                r = await c.request("OPTIONS", "*")
                if r.status != 200:
                    raise SmokeFailure(f"status {r.status}")
                self.facts["keepalives"] = self.facts.get(
                    "keepalives", 0) + 1
            except Exception as e:
                self.fail(f"keepalive OPTIONS: {e!r}")

        pending: set[asyncio.Task] = set()
        tick = 0
        try:
            while True:
                await asyncio.sleep(1.0)
                due = self.players[tick % 40::40]
                if tick % 5 == 0:
                    due = due + [s.client for s in self.sources]
                tick += 1
                for c in due:
                    t = asyncio.ensure_future(ping(c))
                    pending.add(t)
                    t.add_done_callback(pending.discard)
        finally:
            for t in pending:
                t.cancel()

    async def wait_egress(self, want: int, timeout: float) -> int:
        """Poll the server's own sent-packet count until it reaches
        ``want`` (or stops short for ``timeout`` seconds)."""
        t0 = time.monotonic()
        got = -1
        while time.monotonic() - t0 < timeout:
            m = await asyncio.to_thread(self.server.metrics)
            got = int(fam(m, "egress_packets_total"))
            if got >= want:
                break
            await asyncio.sleep(0.25)
        return got

    async def join_late(self) -> None:
        async def one(src):
            flow = Flow(src, f"127.0.0.{1 + src.idx % N_IP}", "late")
            self.reader.add(flow)
            self.late.append(flow)
            await self._join(src, flow.ip, flow.port, flow)
        await asyncio.gather(*(one(s) for s in self.sources))

    async def rest_under_load(self) -> None:
        try:
            info = await asyncio.to_thread(self.server.info)
            sess = json.loads(await asyncio.to_thread(
                http_get, self.server.rest_port,
                "/api/v1/getrtsplivesessions"))
            m = await asyncio.to_thread(self.server.metrics)
            n_sess = int(sess["EasyDarwin"]["Body"]["SessionCount"])
            if n_sess != self.n_src:
                self.fail(f"getrtsplivesessions under load: {n_sess} "
                          f"sessions, want {self.n_src}")
            if int(info["PushSessions"]) != self.n_src:
                self.fail(f"getserverinfo under load: PushSessions "
                          f"{info['PushSessions']}, want {self.n_src}")
            log(f"REST under load: getserverinfo PacketsOut="
                f"{info['PacketsOut']}, getrtsplivesessions {n_sess} "
                f"sessions, /metrics {len(m)} samples")
        except Exception as e:
            self.fail(f"REST under load: {e!r}")

    # -- checks ------------------------------------------------------------
    def check_flow(self, flow: Flow, first_idx: int) -> None:
        """Relayed packets vs pushed ones: payload bit-equal from byte
        12, header bytes 0-1 untouched, seq contiguous from the RTP-Info
        seq, SSRC the SETUP-answered one, timestamps a constant offset
        from the source's."""
        src, tag = flow.src, f"{flow.label} player on {flow.src.path}"
        got = [p for p in flow.packets if len(p) >= 12]
        want = src.packets[first_idx:src.pushed]
        if len(got) != len(want):
            have = {g[12:] for g in got}
            missing = [k for k, w in enumerate(want) if w[12:] not in have]
            self.fail(f"{tag}: received {len(got)} packets, pushed "
                      f"{len(want)} since its first; missing indices "
                      f"{_ranges(missing)}")
        if not got or not want:
            return
        ts_off = (rtp.peek_timestamp(got[0])
                  - rtp.peek_timestamp(want[0])) & 0xFFFFFFFF
        for k, (g, w) in enumerate(zip(got, want)):
            bad = None
            if g[12:] != w[12:] or g[:2] != w[:2]:
                bad = "payload differs from the pushed packet"
            elif rtp.peek_seq(g) != (flow.first_seq + k) & 0xFFFF:
                bad = (f"seq {rtp.peek_seq(g)} not contiguous from "
                       f"RTP-Info seq {flow.first_seq}")
            elif rtp.peek_ssrc(g) != flow.ssrc:
                bad = (f"ssrc {rtp.peek_ssrc(g):08x}, SETUP answered "
                       f"{flow.ssrc:08x}")
            elif (rtp.peek_timestamp(g) - rtp.peek_timestamp(w)
                  ) & 0xFFFFFFFF != ts_off:
                bad = "timestamp offset drifted"
            if bad:
                self.fail(f"{tag}: packet {k}: {bad}")
                return

    def late_first_index(self, flow: Flow) -> int | None:
        """Which pushed packet the late joiner's first one is — it must
        start an IDR access unit."""
        got = [p for p in flow.packets if len(p) >= 12]
        if not got:
            self.fail(f"late player on {flow.src.path}: nothing received")
            return None
        for idx in sorted(flow.src.idr_starts):
            if got[0][12:] == flow.src.packets[idx][12:]:
                return idx
        self.fail(f"late player on {flow.src.path}: first packet does "
                  f"not start an IDR")
        return None

    def check_metrics(self, m: dict[str, float], expected: int) -> None:
        sent = int(fam(m, "egress_packets_total"))
        if sent != expected:
            self.fail(f"egress_packets_total {sent} != {expected} "
                      f"(packets pushed x subscribers joined at the "
                      f"time): {'shortfall' if sent < expected else 'excess'}"
                      f" {abs(expected - sent)}")
        for name in ("egress_send_errors_total",
                     "megabatch_wire_mismatch_total",
                     "resilience_retries_total",
                     "fec_parity_oracle_mismatch_total",
                     "device_errors_swallowed_total"):
            if fam(m, name) != 0:
                self.fail(f"{name} = {fam(m, name):g}, want 0")
        self.check_ladder(m)
        # what the ladder's lower rungs would serve through: the scalar
        # host loop observes its deliveries under engine="scalar"
        scalar = fam(m, 'relay_ingest_to_wire_seconds_count'
                        '{engine="scalar"}')
        if scalar:
            self.fail(f"{scalar:.0f} deliveries left through the scalar "
                      f"host loop (RelayStream.reflect), want 0")
        for name in ("tpu_passes_total", "megabatch_passes_total"):
            if fam(m, name) <= 0:
                self.fail(f"{name} = 0: the engine never ran")
        passes = fam(m, "megabatch_passes_total")
        per_pass = fam(m, "megabatch_streams_total") / max(passes, 1)
        self.facts["megabatch_streams_per_pass"] = round(per_pass, 3)
        self.facts["megabatch_passes"] = int(passes)
        if self.sized:
            log(f"megabatch: {per_pass:.3f} streams per pass (not gated "
                f"at a debug size)")
        elif per_pass <= MIN_STREAMS_PER_PASS:
            self.fail(f"megabatch_streams_total / megabatch_passes_total "
                      f"= {per_pass:.3f}, want > {MIN_STREAMS_PER_PASS:g}:"
                      f" no pass ever stacked two sources")
        backend = [k.split('"')[1] for k, v in m.items()
                   if k.startswith("egress_backend_info{") and v == 1]
        self.facts["egress_backend"] = backend
        if backend not in (["io_uring"], ["gso"]):
            self.fail(f"egress_backend_info names {backend}, want one "
                      f"native rung (io_uring or gso)")
        hist = sorted(
            (float(k.split('le="')[1].split('"')[0]), v)
            for k, v in m.items()
            if k.startswith("relay_ingest_to_wire_seconds_bucket{")
            and 'engine="native"' in k and "+Inf" not in k)
        total = fam(m, "relay_ingest_to_wire_seconds_count")
        if total:
            cum = {le: v / total for le, v in hist
                   if le in (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1.0)}
            self.facts["ingest_to_wire_cdf"] = cum
            log("server-side ingest->wire latency (bucket b's b x 73 ms "
                f"hold included), share of {total:.0f} deliveries at or "
                "under (s): "
                + ", ".join(f"{le:g}: {v:.4f}" for le, v in cum.items()))
        steps = fam(m, 'tpu_pass_seconds_count{stage="engine_step"}')
        step_ms = 1e3 * fam(
            m, 'tpu_pass_seconds_sum{stage="engine_step"}') / max(steps, 1)
        self.facts["engine_step_mean_ms"] = round(step_ms, 3)
        egress_ms = 1e3 * sum(
            v for k, v in m.items()
            if k.startswith("relay_phase_seconds_sum{")
            and 'phase="egress_' in k) / max(steps, 1)
        self.facts["engine_step_egress_ms"] = round(egress_ms, 3)
        log(f"pump: {steps / self.n_src:.0f} wakes x {self.n_src} "
            f"engine steps, mean step {step_ms:.2f} ms (egress phase "
            f"{egress_ms:.2f} ms of it) -> {step_ms * self.n_src:.1f} ms "
            f"per wake")
        per_dev = {k.split('"')[1]: int(v) for k, v in sorted(m.items())
                   if k.startswith("megabatch_device_passes_total{")}
        if per_dev or self.args.devices:
            self.facts["megabatch_device_passes"] = per_dev
            log(f"mesh: megabatch_device_passes_total by device "
                f"{per_dev}")
        log(f"server counters: egress_packets_total {sent} (expected "
            f"{expected}), tpu_passes_total "
            f"{fam(m, 'tpu_passes_total'):.0f}, megabatch passes "
            f"{passes:.0f} x {per_pass:.2f} streams, fallback queries "
            f"{fam(m, 'megabatch_fallback_total'):.0f}, egress backend "
            f"{backend}, slo_violations_total "
            f"{fam(m, 'slo_violations_total'):.0f}, gso supers "
            f"{fam(m, 'egress_gso_supers_total'):.0f}, eagain "
            f"{fam(m, 'egress_eagain_total'):.0f}")

    def check_ladder(self, m: dict[str, float]) -> None:
        """The ladder is how a chip run could be served from the CPU
        without anyone noticing, so every move it made is accounted for.
        A device reason (``device_errors``, a scheduler fault), a rung
        below ``device``, a degrade that never recovered, or a stream
        off rung 0 at the end fails.  ``slo_burn`` moves are reported,
        not failed: at its default 50 ms objective the latency SLO
        counts the buckets' deliberate stagger as lateness, so it burns
        on any stream with more than 16 subscribers and the rising edge
        costs the worst stream one rung (megabatch -> per-stream device
        engine) until it recovers — the finding, left to ROADMAP A1."""
        down = fam(m, 'resilience_transitions_total{direction="down"}')
        up = fam(m, 'resilience_transitions_total{direction="up"}')
        degrades = [e for e in self.events if e["event"] == "ladder.degrade"]
        self.facts["ladder"] = {
            "down": int(down), "up": int(up),
            "degrades": [{k: e.get(k) for k in
                          ("stream", "from_rung", "rung", "reason")}
                         for e in degrades],
            "slo_violations": [{k: e.get(k) for k in
                                ("stream", "slo", "burn", "bad", "total")}
                               for e in self.events
                               if e["event"] == "slo.violation"]}
        log(f"ladder: {down:.0f} down / {up:.0f} up; "
            f"{self.facts['ladder']['degrades']}; slo.violation "
            f"{self.facts['ladder']['slo_violations']}")
        if len(degrades) != down:
            self.fail(f"resilience_transitions_total down = {down:.0f} but "
                      f"{len(degrades)} ladder.degrade events were read: "
                      f"cannot say why the ladder moved")
        for e in degrades:
            if e.get("reason") != "slo_burn" or e.get("rung") != "device":
                self.fail(f"ladder degraded {e.get('stream')} "
                          f"{e.get('from_rung')} -> {e.get('rung')} for "
                          f"{e.get('reason')!r}: only an slo_burn step to "
                          f"the device rung is tolerated")
        if up != down:
            self.fail(f"ladder: {down:.0f} degrade(s) but {up:.0f} "
                      f"recover(s)")
        rungs = {k: v for k, v in m.items()
                 if k.startswith("resilience_ladder_level{") and v != 0}
        if rungs:
            self.fail(f"degradation ladder not at rest: {rungs}")

    def record_profile(self, doc: dict) -> None:
        """From ``/api/v1/profile``: the per-pass phase costs (the pace
        was chosen against them) and the SLO's view."""
        ph = doc.get("phases", {})
        brief = {p: {e: (v["count"], v["mean_ms"], v["p99_ms"])
                     for e, v in engines.items()}
                 for p, engines in ph.items()
                 if p in ("egress_native", "egress_io_uring",
                          "wake_to_pass", "device_step", "d2h", "h2d")}
        self.facts["phases_count_mean_p99_ms"] = brief
        log(f"phases (count, mean ms, p99 ms): {brief}")
        log(f"SLO status: {doc.get('slo', {}).get('objectives')}")

    def check_error_log(self) -> None:
        path = os.path.join(self.server.log_dir, "error.log")
        try:
            with open(path, errors="replace") as f:
                bad = [ln.strip() for ln in f
                       if "[WARNING]" in ln or "[FATAL]" in ln]
        except OSError as e:
            self.fail(f"no server error log: {e}")
            return
        if bad:
            self.fail(f"{len(bad)} warning(s) in the server error log, "
                      f"first: {bad[0][:300]}")

    # -- the run -----------------------------------------------------------
    async def drive(self) -> None:
        srv = self.server
        t_run = time.monotonic()

        def compiles(m):
            return (int(fam(m, "jax_executables_built_total")),
                    round(fam(m, "jax_executable_build_seconds_total"), 3),
                    int(fam(m, "jax_persistent_cache_hits_total")))

        def pull_events():
            """Keep the ladder / SLO / swallowed-error records; called
            at every phase boundary so the server's 4,096-record ring
            cannot roll past one unread."""
            for e in srv.events(self.event_seq):
                self.event_seq = e["seq"]
                if e["event"].split(".")[0] in ("ladder", "slo", "device"):
                    self.events.append(e)

        # 1. what the server says it runs on
        info = srv.info()
        dev = {"platform": info.get("Platform", ""),
               "kind": info.get("DeviceKind", ""),
               "count": int(info.get("DeviceCount", "0") or 0)}
        self.facts["device"] = dev
        log(f"server reports platform {dev['platform']} device_kind "
            f"{dev['kind']!r} devices {dev['count']}; native core "
            f"src={info.get('NativeSourceDigest')} built_at_boot="
            f"{info.get('NativeBuiltAtBoot')} loaded="
            f"{info.get('NativeCore')}")
        cpu_by_name = os.environ.get("JAX_PLATFORMS", "").split(
            ",")[0].strip().lower() == "cpu"
        if dev["platform"] != "tpu" and not (
                dev["platform"] == "cpu" and cpu_by_name and self.sized):
            raise SmokeFailure(
                f"server runs on platform {dev['platform']!r}, not a TPU "
                f"(a CPU run needs JAX_PLATFORMS=cpu AND a --sources/"
                f"--players size)")
        mesh = {k: v for k, v in info.items() if k.startswith("Mesh")}
        if mesh or self.args.devices:
            self.facts["mesh"] = mesh
            log(f"mesh keys of getserverinfo: {mesh}")
        if info.get("NativeCore") != "1":
            raise SmokeFailure("server did not load the native core")
        if info.get("NativeSourceDigest") != native.source_digest():
            self.fail("server's native core was not built from this "
                      "tree's csrc/")

        # 2. sessions: pushers, then every player, before any media
        self.open_receivers()
        t0 = time.monotonic()
        await self.start_pushers()
        await self.join_players()
        self.facts["join_s"] = round(time.monotonic() - t0, 2)
        pull_events()
        m = srv.metrics()
        log(f"{self.n_src} pushers recording, "
            f"{len(self.players)} UDP PLAY sessions joined in "
            f"{self.facts['join_s']} s; compiles so far "
            f"{compiles(m)}")

        # 3. warm-up GOP: first media, first device passes, cold compiles
        keep = asyncio.ensure_future(self.keepalive())
        t0 = time.perf_counter()
        await self.push_frames(0, WARM_FRAMES)
        want = sum(s.pushed for s in self.sources) * self.n_sub
        got = await self.wait_egress(want, 120.0)
        pull_events()
        m_warm = srv.metrics()
        self.facts["compile_warmup"] = compiles(m_warm)
        log(f"warm-up: {WARM_FRAMES} frames/source in "
            f"{time.perf_counter() - t0:.1f} s, server sent {got} of "
            f"{want}; compiles (count, seconds, cache hits) "
            f"{compiles(m_warm)}")
        if got != want:
            self.fail(f"warm-up: egress_packets_total {got} != {want}")

        # 4. the timed window: >= 512 packets/source, two IDRs; REST
        #    calls and one late joiner per source while media flows
        tasks: list[asyncio.Task] = []

        async def scrape_after_late():
            self.facts["m_after_late"] = await asyncio.to_thread(
                srv.metrics)

        hooks = {
            TIMED_FRAMES // 3: lambda: tasks.append(
                asyncio.ensure_future(self.rest_under_load())),
            LATE_JOIN_FRAME: lambda: tasks.append(
                asyncio.ensure_future(self.join_late())),
            LATE_JOIN_FRAME + 4: lambda: tasks.append(
                asyncio.ensure_future(scrape_after_late())),
        }
        self.lateness.clear()
        pushed0 = sum(s.pushed for s in self.sources)
        t0 = time.perf_counter()
        await self.push_frames(WARM_FRAMES, self.n_frames, hooks)
        push_s = time.perf_counter() - t0
        await asyncio.gather(*tasks)
        timed = sum(s.pushed for s in self.sources) - pushed0
        late_ms = sorted(x * 1e3 for x in self.lateness)
        self.facts["pace"] = {
            "fps": self.args.fps,
            "pkts_per_s_per_source": round(timed / self.n_src / push_s, 1),
            "deliveries_per_s_offered":
                round(timed * self.n_sub / push_s),
            "pusher_late_ms_median": round(late_ms[len(late_ms) // 2], 3),
            "pusher_late_ms_max": round(late_ms[-1], 3)}
        log(f"timed window: {timed // self.n_src} packets/source "
            f"({self.n_frames - WARM_FRAMES} frames at "
            f"{self.args.fps:g} fps/source, sources un-synchronised) in "
            f"{push_s:.2f} s = {timed / self.n_src / push_s:.1f} "
            f"pkts/s/source, {timed * self.n_sub / push_s:.0f} "
            f"deliveries/s offered; pusher lateness median "
            f"{late_ms[len(late_ms) // 2]:.2f} ms max {late_ms[-1]:.2f} ms")

        # 5. let the last bucket's stagger drain — every checked flow has
        #    the source's LAST packet — then settle the books
        await asyncio.sleep(BUCKET_DRAIN_S)
        deadline = time.monotonic() + 180.0
        flows = self.checked + self.late
        while time.monotonic() < deadline and not all(
                f.packets and f.packets[-1][12:]
                == f.src.packets[f.src.pushed - 1][12:] for f in flows):
            await asyncio.sleep(0.25)
        expected = 0
        for s in self.sources:
            expected += s.pushed * self.n_sub
        for flow in self.checked:
            self.check_flow(flow, 0)
        for flow in self.late:
            idx = self.late_first_index(flow)
            if idx is not None:
                expected += flow.src.pushed - idx
                self.check_flow(flow, idx)
        await self.wait_egress(expected, 60.0)
        # a stream the SLO's rising edge cost a rung climbs back after
        # resilience_recover_sec clean seconds: give it that long
        deadline = time.monotonic() + LADDER_RECOVER_WAIT_S
        while True:
            m_end = srv.metrics()
            if time.monotonic() > deadline or not any(
                    v for k, v in m_end.items()
                    if k.startswith("resilience_ladder_level{")):
                break
            await asyncio.sleep(1.0)
        pull_events()
        self.check_metrics(m_end, expected)
        self.record_profile(json.loads(http_get(srv.rest_port,
                                               "/api/v1/profile")))
        unchecked = expected - sum(
            len([p for p in f.packets if len(p) >= 12]) for f in flows)
        log(f"unchecked flows: {self.bulk.count} datagrams drained of "
            f"{unchecked} the server counts as sent to them "
            f"(not asserted: receiver queues may drop)")

        # 6. compile work, apart from the served window
        c_w, c_e = self.facts["compile_warmup"], compiles(m_end)
        c_l = compiles(self.facts.pop("m_after_late", m_end))
        self.facts["compile_served"] = c_e
        self.facts["compiles_after_last_join"] = c_e[0] - c_l[0]
        log(f"compile work (executables, seconds, cache hits): warm-up "
            f"{c_w}; timed window added {c_e[0] - c_w[0]} / "
            f"{c_e[1] - c_w[1]:.3f} s / {c_e[2] - c_w[2]}; after the "
            f"last join settled: {c_e[0] - c_l[0]} (should be 0; "
            f"printed, not gated)")

        # 7. every OTHER served jitted step, on the device
        t0 = time.monotonic()
        dc = json.loads(await asyncio.to_thread(
            http_get, srv.rest_port, "/api/v1/devicecheck", 900.0))
        self.facts["devicecheck"] = dc
        for row in dc["steps"]:
            log(f"devicecheck {row['step']}: "
                + (f"ok shape {row['shape']} first {row['first_s']} s "
                   f"again {row['again_s']} s" if row["ok"] else
                   f"FAILED {row.get('error', 'oracle mismatch')}"))
            if not row["ok"]:
                self.fail(f"devicecheck {row['step']}: "
                          f"{row.get('error', 'oracle mismatch')[:300]}")
        if dc["device"] != dev:
            self.fail(f"devicecheck ran on {dc['device']}, server "
                      f"reported {dev}")
        m_dc = srv.metrics()
        self.facts["compile_total"] = compiles(m_dc)
        log(f"devicecheck took {time.monotonic() - t0:.1f} s; compiles "
            f"since boot (count, seconds, cache hits) {compiles(m_dc)}")
        for name in ("device_errors_swallowed_total",
                     "resilience_retries_total"):
            if fam(m_dc, name) != 0:
                self.fail(f"{name} = {fam(m_dc, name):g} after "
                          f"devicecheck, want 0")
        if (fam(m_dc, "resilience_transitions_total")
                != fam(m_end, "resilience_transitions_total")):
            self.fail("the ladder moved during devicecheck")
        keep.cancel()
        self.facts["run_s"] = round(time.monotonic() - t_run, 1)

    def run(self) -> int:
        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
        need = self.n_src * (self.n_sub + 2) * 2 + 256
        if hard < need:
            raise SmokeFailure(f"RLIMIT_NOFILE {hard} < {need} needed")
        # the parent builds/verifies the native core FIRST: the child
        # then finds a library tied to this tree and never races a make
        native.require()
        nb = native.build_info()
        log(f"native core: {nb['so']} src={nb['source_digest']} "
            f"cpu={nb['cpu_key']} built_here={nb['built_this_process']}")
        log(f"size: {self.n_src} sources x {self.n_sub} players "
            f"(+1 late joiner each), seed {self.args.seed}, output "
            f"{self.out_dir}")
        self.server.start()
        rc = None
        try:
            log("server boot: " + self.server.wait_boot())
            asyncio.run(self.drive())
        except SmokeFailure as e:
            self.fail(str(e))
        except Exception as e:
            self.fail(f"driver error: {e!r}")
        finally:
            self.reader.stop_flag = True
            for d in (getattr(self, "bulk", None),
                      getattr(self, "bulk_rtcp", None)):
                if d is not None:
                    d.stop_flag = True
            rc = self.server.terminate()
        if rc != 0:
            self.fail(f"server exit code on SIGTERM: {rc} "
                      f"({self.server.stderr_tail(400)!r})")
        self.check_error_log()
        self.facts["failures"] = self.failures
        with open(os.path.join(self.out_dir, "summary.json"), "w") as f:
            json.dump(self.facts, f, indent=1, default=str)
        if self.failures:
            log(f"{len(self.failures)} failure(s):")
            for msg in self.failures:
                log(f"  - {msg}")
            return 1
        log(f"passed in {self.facts.get('run_s')} s")
        print(json.dumps({"ok": True, "device": self.facts["device"]}),
              flush=True)
        return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sources", type=int,
                    help="pushers (default 16; giving a size marks a "
                         "debug run)")
    ap.add_argument("--players", type=int,
                    help="UDP players per source (default 256)")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--fps", type=float, default=FPS,
                    help="pushed frames/s per source (default "
                         f"{FPS:g}: see PERF.md, 'the pace')")
    ap.add_argument("--devices", type=int,
                    help="megabatch_devices for the server (the by-hand "
                         "four-chip run); default: not set")
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"))
    return Smoke(ap.parse_args()).run()


if __name__ == "__main__":
    sys.exit(main())
