"""Programmatic RTSP client: pusher + player flows for tests and load-gen.

Reference parity: ``RTSPClientLib/ClientSession.{h,cpp}`` (programmatic
DESCRIBE/SETUP/PLAY state machine used by the old StreamingLoadTool) and
``PlayerSimulator.h`` (client-side loss/late tracking) — rebuilt on asyncio
as a usable harness instead of the reference's bit-rotted copy.
"""

from __future__ import annotations

import asyncio
from dataclasses import dataclass, field

from ..protocol import rtp, rtsp, sdp


@dataclass
class ReceiverStats:
    """PlayerSimulator-style accounting."""

    packets: int = 0
    bytes: int = 0
    lost: int = 0
    duplicates: int = 0
    out_of_order: int = 0
    _last_seq: int | None = None
    _seen: set = field(default_factory=set)

    def on_packet(self, data: bytes) -> None:
        self.packets += 1
        self.bytes += len(data)
        try:
            seq = rtp.peek_seq(data)
        except Exception:
            return
        if seq in self._seen:
            self.duplicates += 1
            return
        self._seen.add(seq)
        if self._last_seq is not None:
            d = rtp.seq_delta(seq, self._last_seq)
            if d > 1:
                self.lost += d - 1
            elif d < 0:
                self.out_of_order += 1
        if self._last_seq is None or rtp.seq_delta(seq, self._last_seq) > 0:
            self._last_seq = seq


def hexish(s: str) -> bool:
    """A plausible trace id: 8-64 lowercase hex chars (token_hex shape).
    Anything else must not become a correlation key."""
    return 8 <= len(s) <= 64 and all(c in "0123456789abcdef" for c in s)


class RtspClient:
    def __init__(self):
        self.reader: asyncio.StreamReader | None = None
        self.writer: asyncio.StreamWriter | None = None
        self.wire = rtsp.RtspWireReader(parse_responses=True)
        self.cseq = 0
        #: seconds a request waits for its response unless the call
        #: says otherwise (a load generator raises it: a server busy
        #: compiling answers late, not never)
        self.request_timeout = 5.0
        self.session_id: str | None = None
        #: headers merged into EVERY request (overridable per call) —
        #: the pull-relay envelope sets the cluster-peer correlation
        #: pair here (X-Trace-Id / X-Cluster-Node, ISSUE 15)
        self.default_headers: dict = {}
        #: the last DESCRIBE response (play_start) — carries the
        #: upstream stream's X-Trace-Id for downstream trace adoption
        self.describe_response: rtsp.RtspResponse | None = None
        self._responses: asyncio.Queue = asyncio.Queue()
        #: interleaved channel → asyncio.Queue of payload bytes
        self.channels: dict[int, asyncio.Queue] = {}
        #: set by enable_any_queue(): single (channel, data) stream instead
        #: of per-channel queues (pull-relay forwarding wants arrival order)
        self.any_queue: asyncio.Queue | None = None
        self.stats = ReceiverStats()
        self._reader_task: asyncio.Task | None = None

    async def connect(self, host: str, port: int, *,
                      local_addr: tuple[str, int] | None = None) -> None:
        """``local_addr`` binds the client side first — the address a
        UDP player connects FROM is where the server sends its media."""
        self.reader, self.writer = await asyncio.open_connection(
            host, port, local_addr=local_addr)
        self._reader_task = asyncio.create_task(self._read_loop())

    async def close(self) -> None:
        if self._reader_task:
            self._reader_task.cancel()
            try:
                await self._reader_task
            except (asyncio.CancelledError, Exception):
                pass
        if self.writer:
            self.writer.close()

    async def _read_loop(self) -> None:
        try:
            await self._read_loop_inner()
        finally:
            if self.any_queue is not None:      # EOF sentinel for recv_any
                self.any_queue.put_nowait((-1, b""))

    async def _read_loop_inner(self) -> None:
        while True:
            data = await self.reader.read(16384)
            if not data:
                break
            self.wire.feed(data)
            for ev in self.wire.events():
                if isinstance(ev, rtsp.InterleavedPacket):
                    if ev.channel % 2 == 0:
                        self.stats.on_packet(ev.data)
                    if self.any_queue is not None:
                        self.any_queue.put_nowait((ev.channel, ev.data))
                    else:
                        q = self.channels.setdefault(ev.channel,
                                                     asyncio.Queue())
                        q.put_nowait(ev.data)
                else:
                    self._responses.put_nowait(ev)

    # ------------------------------------------------------------ requests
    async def request(self, method: str, uri: str, headers=None,
                      body: bytes = b"", timeout: float | None = None
                      ) -> rtsp.RtspResponse:
        if timeout is None:
            timeout = self.request_timeout
        self.cseq += 1
        want = self.cseq
        hdrs = {"cseq": str(want)}
        if self.session_id:
            hdrs["session"] = self.session_id
        hdrs.update(self.default_headers)
        hdrs.update(headers or {})
        req = rtsp.RtspRequest(method, uri, hdrs, body)
        self.writer.write(req.to_bytes())
        deadline = asyncio.get_running_loop().time() + timeout
        while True:
            left = deadline - asyncio.get_running_loop().time()
            resp = await asyncio.wait_for(self._responses.get(),
                                          max(left, 0.001))
            # CSeq matching: a previously timed-out request's late reply
            # must not pair with THIS request (the queue is FIFO; one
            # desync would shift every later pairing) — drop stale ones
            rc = resp.headers.get("cseq")
            try:
                if rc is not None and int(rc) < want:
                    continue
            except ValueError:
                pass
            break
        if sid := resp.headers.get("session"):
            self.session_id = sid.split(";")[0].strip()
        return resp

    def send_interleaved(self, channel: int, data: bytes) -> None:
        self.writer.write(rtsp.frame_interleaved(channel, data))

    async def recv_interleaved(self, channel: int,
                               timeout: float = 5.0) -> bytes:
        q = self.channels.setdefault(channel, asyncio.Queue())
        return await asyncio.wait_for(q.get(), timeout)

    def enable_any_queue(self) -> None:
        """Switch to arrival-order (channel, data) delivery via recv_any."""
        self.any_queue = asyncio.Queue()

    async def recv_any(self) -> tuple[int, bytes]:
        """Next (channel, data) in arrival order; (-1, b"") on EOF."""
        if self.any_queue is None:
            self.enable_any_queue()
        return await self.any_queue.get()

    # ---------------------------------------------------------- push flow
    async def push_start(self, uri: str, sdp_text: str,
                         tcp: bool = True) -> None:
        """ANNOUNCE + SETUP(record) each track + RECORD (EasyPusher flow)."""
        r = await self.request("ANNOUNCE", uri, {
            "content-type": "application/sdp"}, sdp_text.encode())
        assert r.status == 200, r.status
        sd = sdp.parse(sdp_text)
        self.push_transports = []
        for i, st in enumerate(sd.streams):
            t = (f"RTP/AVP/TCP;unicast;interleaved={2*i}-{2*i+1};mode=record"
                 if tcp else "RTP/AVP;unicast;client_port=0-1;mode=record")
            r = await self.request("SETUP", f"{uri}/trackID={st.track_id}",
                                   {"transport": t})
            assert r.status == 200, r.status
            self.push_transports.append(rtsp.TransportSpec.parse(
                r.headers.get("transport", "RTP/AVP")))
        r = await self.request("RECORD", uri)
        assert r.status == 200, r.status

    def push_packet(self, track_index: int, data: bytes,
                    is_rtcp: bool = False) -> None:
        self.send_interleaved(2 * track_index + (1 if is_rtcp else 0), data)

    # ---------------------------------------------------------- play flow
    async def play_start(self, uri: str, *, tcp: bool = True,
                         client_ports: list[tuple[int, int]] | None = None,
                         setup_headers: dict | None = None
                         ) -> sdp.SessionDescription:
        r = await self.request("DESCRIBE", uri, {"accept": "application/sdp"})
        assert r.status == 200, r.status
        self.describe_response = r
        up_trace = r.headers.get("x-trace-id", "").strip()
        if "x-trace-id" in self.default_headers and hexish(up_trace):
            # trace-propagating caller (the pull-relay envelope): adopt
            # the upstream STREAM's trace before the SETUPs go out, so
            # the serving connection upstream is tagged with the same id
            # this edge will serve under (ISSUE 15)
            self.default_headers["x-trace-id"] = up_trace
        sd = sdp.parse(r.body)
        self.transports = []
        self.setup_responses = []
        for i, st in enumerate(sd.streams):
            if tcp:
                t = f"RTP/AVP/TCP;unicast;interleaved={2*i}-{2*i+1}"
            else:
                cp = client_ports[i]
                t = f"RTP/AVP;unicast;client_port={cp[0]}-{cp[1]}"
            r = await self.request("SETUP", f"{uri}/trackID={st.track_id}",
                                   {"transport": t, **(setup_headers or {})})
            assert r.status == 200, r.status
            self.setup_responses.append(r)
            self.transports.append(rtsp.TransportSpec.parse(
                r.headers.get("transport", "RTP/AVP")))
        r = await self.request("PLAY", uri)
        assert r.status == 200, r.status
        #: the PLAY response — its RTP-Info carries each track's first seq
        self.play_response = r
        return sd

    async def teardown(self, uri: str) -> None:
        try:
            await self.request("TEARDOWN", uri, timeout=2.0)
        except (asyncio.TimeoutError, ConnectionError):
            pass
