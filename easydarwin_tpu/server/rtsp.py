"""The RTSP session layer: per-connection request pipeline + media wiring.

Reference parity: ``RTSPSession.cpp:216`` (state machine over parsed
requests), ``QTSSReflectorModule.cpp`` request handling (``DoAnnounce`` 898,
``DoDescribe`` 1176, ``DoSetup`` 1597, ``DoPlay`` 1867, teardown), and the
interleaved ingest path ``QTSS_RTSPIncomingData_Role`` → ``ProcessRTPData``
(``QTSSReflectorModule.cpp:604``).  One asyncio task per connection replaces
the Task-thread state machine; WouldBlock backpressure is carried by the
transport write-buffer (see ``transports``).

A connection can be a *player* (DESCRIBE/SETUP/PLAY of a live path or VOD
file), a *pusher* (ANNOUNCE/SETUP mode=record/RECORD — the EasyPusher flow),
or a plain control connection.
"""

from __future__ import annotations

import asyncio
import secrets
import time
from dataclasses import dataclass, field

from ..obs import (EVENTS, FLIGHT, INGEST_INTERLEAVED_PACKETS,
                   INGEST_INTERLEAVED_SECONDS, RTCP_REPORT_BLOCKS,
                   RTSP_REQUEST_SECONDS, RTSP_REQUESTS, TRACER, t0_of)
from ..protocol import rtsp, sdp
from ..relay.session import RelaySession, SessionRegistry, now_ms
from .config import ServerConfig
from .transports import (InterleavedOutput, UdpOutput, UdpPair, UdpPortPool)

SERVER_NAME = "easydarwin-tpu/0.1"
ALLOWED = ("OPTIONS, DESCRIBE, ANNOUNCE, SETUP, PLAY, PAUSE, RECORD, "
           "TEARDOWN, GET_PARAMETER, SET_PARAMETER")


def _extract_track(uri_path: str) -> tuple[str, int | None]:
    """Split '/live/cam1/trackID=2' → ('/live/cam1', 2).

    The track component must be EXACTLY ``track<id>``/``trackID=<id>``/
    ``streamid=<id>`` — a path like ``/live/track5cam`` is a stream
    named track5cam, not track 5 of /live (a parser must not guess;
    VERDICT r3 weak 7)."""
    low = uri_path.lower()
    for marker in ("trackid=", "streamid=", "track"):
        pos = low.rfind("/" + marker)
        if pos >= 0:
            tail = uri_path[pos + 1 + len(marker):]
            if tail.isdigit():
                return uri_path[:pos], int(tail)
    return uri_path, None


@dataclass
class _PlayerTrack:
    track_id: int
    output: object                      # RelayOutput
    udp_pair: UdpPair | None = None


@dataclass
class _PusherTrack:
    track_id: int
    udp_pair: UdpPair | None = None


class RtspConnection:
    """One RTSP TCP connection (player, pusher, or control)."""

    def __init__(self, server: "RtspServer", reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter):
        self.server = server
        self.reader = reader
        self.writer = writer
        self.wire = rtsp.RtspWireReader()
        self.uri = ""
        self.session_id: str | None = None
        self.path: str | None = None
        self.relay: RelaySession | None = None
        self.vod_file = None                 # Mp4File when playing VOD
        self.vod_session = None              # FileSession
        #: the ``<live>.dvr`` asset path when SETUP landed on a spilled
        #: DVR asset (pure replay through the time-shift tier)
        self.dvr_path: str | None = None
        #: per-track absolute resume cursors latched by a PAUSE under an
        #: armed spiller: the next PLAY re-enters the past exactly here
        #: (cleared by a successful resume or an explicit Range seek)
        self.pause_ids: dict[int, int] | None = None
        self.is_pusher = False
        self.playing = False
        self.player_tracks: dict[int, _PlayerTrack] = {}
        self.pusher_tracks: dict[int, _PusherTrack] = {}
        #: interleaved channel → (track_id, is_rtcp) for push ingest
        self.channel_map: dict[int, tuple[int, bool]] = {}
        self.last_activity = time.monotonic()
        self.closed = False
        self.auth_user: str | None = None
        self.user_agent = ""
        self.created_at = time.monotonic()
        peer = writer.get_extra_info("peername") or ("?", 0)
        self.client_ip = peer[0]
        #: ip:port — the admission redirect's edge-spread key: thousands
        #: of viewers behind one CGNAT ip must still fan across edges,
        #: so the spread hashes the full 5-tuple-ish identity, not the ip
        self.client_key = f"{peer[0]}:{peer[1]}"
        #: correlation id threaded through every span/event/flight record
        #: this connection produces (and stamped onto its relay session /
        #: outputs, so engine-pass and native-egress spans carry it too)
        self.trace_id = secrets.token_hex(8)
        #: why this connection died, when not a clean TEARDOWN/EOF —
        #: set by the timeout sweep or the uncaught-exception catch;
        #: non-None at close() triggers the flight-recorder dump
        self.abnormal_reason: str | None = None

    # ------------------------------------------------------------------ io
    async def run(self) -> None:
        try:
            first = await self.reader.read(16384)
            if not first:
                await self.close()
                return
            if first.startswith(b"GET ") or first.startswith(b"POST"):
                # HTTP on the RTSP port: RTSP-over-HTTP tunnel, icy MP3, or
                # the stats page (RTSPSession.cpp:1339-1459 tunnel states;
                # MP3StreamingModule; WebStatsModule RTSP-port GET)
                await self._run_http(first)
                return
            self._feed(first)
            await self._drain_events()
            while not self.closed:
                data = await self.reader.read(16384)
                if not data:
                    break
                self._feed(data)
                await self._drain_events()
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        except rtsp.RtspError as e:
            self._reply(rtsp.RtspResponse(e.status), cseq=0)
            self.abnormal_reason = f"protocol: {e.status}"
        except Exception as e:
            # crash flight recorder: an uncaught handler exception must
            # leave a black box — including the stack frames asyncio
            # would have printed, or the crash is undiagnosable
            import traceback
            self.abnormal_reason = (f"exception: {type(e).__name__}: "
                                    f"{e}"[:200])
            EVENTS.emit("rtsp.exception", level="error",
                        session_id=self.session_id, stream=self.path,
                        trace_id=self.trace_id,
                        error=f"{type(e).__name__}: {e}"[:200],
                        tb=traceback.format_exc(limit=12)[-2000:])
        finally:
            await self.close()

    def _feed(self, data: bytes) -> None:
        self.last_activity = time.monotonic()
        self.wire.feed(data)

    async def _drain_events(self) -> None:
        """One socket read's events.  The pushed packets among them sit
        in one ``ingest.read`` span (a request between two of them ends
        it: nothing awaits inside a span)."""
        span, t0, pushed = None, 0, 0       # t0 == 0: no bracket open
        for ev in self.wire.events():
            if isinstance(ev, rtsp.InterleavedPacket):
                if not t0 and self.relay is not None:
                    span = TRACER.open("ingest.read", "ingest")
                    t0 = t0_of(span)
                pushed += self._on_interleaved(ev)
                continue
            if t0:
                self._ingest_close(span, t0, pushed)
                t0 = pushed = 0
            await self._dispatch(ev)
        if t0:
            self._ingest_close(span, t0, pushed)

    @staticmethod
    def _ingest_close(span, t0: int, pushed: int) -> None:
        end = TRACER.close(span, packets=pushed)
        if pushed:
            INGEST_INTERLEAVED_PACKETS.inc(pushed)
            INGEST_INTERLEAVED_SECONDS.inc((end - t0) / 1e9)

    # ------------------------------------------------ HTTP on the RTSP port
    async def _run_http(self, first: bytes) -> None:
        buf = bytearray(first)
        while b"\r\n\r\n" not in buf:
            data = await self.reader.read(16384)
            if not data:
                return
            buf += data
        head_end = buf.index(b"\r\n\r\n")
        lines = bytes(buf[:head_end]).decode("latin-1").split("\r\n")
        rest = bytes(buf[head_end + 4:])
        try:
            method, target, _ver = lines[0].split(None, 2)
        except ValueError:
            return
        headers = {}
        for ln in lines[1:]:
            k, sep, v = ln.partition(":")
            if sep:
                headers[k.strip().lower()] = v.strip()
        cookie = headers.get("x-sessioncookie")
        if method == "GET" and cookie:
            await self._tunnel_get(cookie)
        elif method == "POST" and cookie:
            await self._tunnel_post(cookie, rest)
        elif method == "GET":
            await self.server.handle_http_get(self, target, headers)

    async def _tunnel_get(self, cookie: str) -> None:
        """The data half of an RTSP-over-HTTP tunnel: hold the connection,
        answer the tunnel preamble; all RTSP replies/media flow here."""
        self.writer.write(
            b"HTTP/1.0 200 OK\r\nServer: " + SERVER_NAME.encode() +
            b"\r\nConnection: close\r\nCache-Control: no-store\r\n"
            b"Pragma: no-cache\r\n"
            b"Content-Type: application/x-rtsp-tunnelled\r\n\r\n")
        self.server.tunnels[cookie] = self
        try:
            while not self.closed:        # hold open; client sends nothing
                data = await self.reader.read(4096)
                if not data:
                    break
        finally:
            self.server.tunnels.pop(cookie, None)

    async def _tunnel_post(self, cookie: str, initial: bytes) -> None:
        """The command half: base64-encoded RTSP arrives here; decode and
        execute against the GET-side connection (replies go to its writer)."""
        import base64
        target = self.server.tunnels.get(cookie)
        if target is None:
            self.writer.write(b"HTTP/1.0 404 Not Found\r\n\r\n")
            return
        b64 = bytearray()

        async def feed(raw: bytes) -> None:
            b64.extend(c for c in raw if c not in b" \r\n\t")
            n = len(b64) // 4 * 4
            if n:
                decoded = base64.b64decode(bytes(b64[:n]))
                del b64[:n]
                target.wire.feed(decoded)
                await target._drain_events()

        await feed(initial)
        while not self.closed and not target.closed:
            data = await self.reader.read(16384)
            if not data:
                break
            self.last_activity = time.monotonic()
            await feed(data)

    def _reply(self, resp: rtsp.RtspResponse, cseq: int | None = None) -> None:
        resp.headers.setdefault("CSeq", str(cseq) if cseq is not None else "0")
        resp.headers.setdefault("Server", SERVER_NAME)
        if self.session_id:
            resp.headers.setdefault("Session", self.session_id)
        self._last_response = resp
        self.writer.write(resp.to_bytes())

    # ----------------------------------------------------------- dispatch
    def _adopt_peer_trace(self, req: rtsp.RtspRequest) -> None:
        """Cross-node trace propagation (ISSUE 15): a cluster peer's
        pull carries the stream's trace id upstream as ``X-Trace-Id``;
        this connection adopts it so its spans/events/flight box stitch
        into the same multi-hop trace.  Accepted ONLY from cluster
        peers: the request must name a live-leased node in
        ``X-Cluster-Node`` AND arrive from that node's registered lease
        address (node ids are public, so the name alone would be
        forgeable — see app._peer_trace_gate)."""
        from ..utils.client import hexish
        tid = req.headers.get("x-trace-id", "").strip()
        if not tid or tid == self.trace_id:
            return
        gate = getattr(self.server, "peer_trace_gate", None)
        if gate is None or not gate(req.headers.get("x-cluster-node", ""),
                                    self.client_ip):
            return
        if not hexish(tid):
            return
        self.trace_id = tid

    async def _dispatch(self, req: rtsp.RtspRequest) -> None:
        self.server.stats["requests"] += 1
        self._adopt_peer_trace(req)
        handler = getattr(self, f"_do_{req.method.lower()}", None)
        if handler is None:
            self._reply(rtsp.RtspResponse(501), req.cseq)
            return
        if ua := req.headers.get("user-agent"):
            self.user_agent = ua
        if req.uri != "*":
            self.uri = req.uri
        mods = self.server.modules
        # Filter role: a module may answer the request outright
        filtered = mods.run_filter(self, req)
        if filtered is not None:
            self._reply(filtered, req.cseq)
            return
        mods.run_route(self, req)
        auth = self.server.auth
        if (auth is not None
                and req.method in ("DESCRIBE", "SETUP", "ANNOUNCE", "PLAY",
                                   "RECORD")):
            allowed, user = auth.authorize(
                req.path(), req.method, req.headers.get("authorization"))
            if not allowed:
                self._reply(rtsp.RtspResponse(401, {
                    "WWW-Authenticate": auth.challenge()}), req.cseq)
                return
            self.auth_user = user
        if not mods.run_authorize(self, req):
            self._reply(rtsp.RtspResponse(403), req.cseq)
            return
        self._last_response = None
        t0 = time.perf_counter_ns()
        errored = False
        try:
            await handler(req)
        except rtsp.RtspError as e:
            errored = True
            self._reply(rtsp.RtspResponse(e.status), req.cseq)
            EVENTS.emit("rtsp.error", level="warn",
                        session_id=self.session_id, stream=self.path,
                        trace_id=self.trace_id, method=req.method,
                        status=e.status)
        finally:
            # the span's two clock reads are also the method's seconds:
            # what the event-loop thread spent in the handler, whoever
            # waited for the answer
            method = req.method.lower()
            dur_ns = time.perf_counter_ns() - t0
            TRACER.add(f"rtsp.{method}", t0, dur_ns, cat="rtsp",
                       trace_id=self.trace_id)
            RTSP_REQUEST_SECONDS.inc(dur_ns / 1e9, method=method)
            RTSP_REQUESTS.inc(method=method)
        if (not errored and req.method in self._EVENT_METHODS
                and self._last_response is not None):
            EVENTS.emit(f"rtsp.{req.method.lower()}",
                        session_id=self.session_id, stream=self.path,
                        trace_id=self.trace_id,
                        status=self._last_response.status)
        if self._last_response is not None:
            mods.run_postprocess(self, req, self._last_response)

    #: media lifecycle methods that emit a generic status event from the
    #: dispatcher (SETUP emits its richer event inside _do_setup)
    _EVENT_METHODS = frozenset(("ANNOUNCE", "PLAY", "RECORD", "PAUSE",
                                "TEARDOWN"))

    async def _do_options(self, req: rtsp.RtspRequest) -> None:
        self._reply(rtsp.RtspResponse(200, {"Public": ALLOWED}), req.cseq)

    async def _do_get_parameter(self, req: rtsp.RtspRequest) -> None:
        body = (req.body or b"").decode("utf-8", "replace").lower()
        if "x-freshness" in body:
            # the freshness-chain hop transport (ISSUE 15): answer this
            # stream's chain (origin hop first) so a downstream relay-
            # tree edge can append its own stamp — no media-wire change
            import json as json_mod
            from ..protocol.sdp import _norm
            path = self.path or _norm(req.path())
            sess = self.server.registry.find(path)
            if sess is not None:
                from ..obs import fleet
                chain = fleet.freshness_chain(
                    sess, self.server.config.server_id)
                self._reply(rtsp.RtspResponse(
                    200, {"Content-Type": "application/json"},
                    json_mod.dumps(chain).encode()), req.cseq)
                return
        self._reply(rtsp.RtspResponse(200), req.cseq)

    async def _do_set_parameter(self, req: rtsp.RtspRequest) -> None:
        self._reply(rtsp.RtspResponse(200), req.cseq)

    async def _do_describe(self, req: rtsp.RtspRequest) -> None:
        path = req.path()
        text = await self.server.describe(path)
        if text is None:
            self._reply(rtsp.RtspResponse(404), req.cseq)
            return
        self.path = sdp._norm(path)
        extra = {}
        sess = self.server.registry.find(self.path)
        if sess is not None:
            # downstream trace propagation (ISSUE 15): the reply names
            # the stream's trace id so a pulling edge serves its local
            # replica under the SAME id — informational for everyone
            # else (an id grants nothing; acceptance upstream is gated)
            extra["X-Trace-Id"] = sess.trace_id
        self._reply(rtsp.RtspResponse(200, {
            "Content-Type": "application/sdp",
            "Content-Base": req.uri.rstrip("/") + "/",
            **extra,
        }, text.encode()), req.cseq)

    async def _do_announce(self, req: rtsp.RtspRequest) -> None:
        if not req.body:
            raise rtsp.RtspError(400, "ANNOUNCE without SDP")
        path = req.path()
        existing = self.server.registry.find(sdp._norm(path))
        self.relay = self.server.registry.find_or_create(
            path, req.body.decode("utf-8", "replace"))
        self.relay.owner = self         # ANNOUNCE takes ownership (adoption)
        if existing is self.relay:
            # adopting a live session (re-ANNOUNCE after a migration /
            # restart / pull supersede): the STREAM's trace id is minted
            # once and survives feeder changes — the connection adopts
            # it, so a stitched trace spans the handover instead of
            # breaking at it (ISSUE 15 lineage)
            self.trace_id = self.relay.trace_id
        else:
            # fresh session: ownership carries the trace — engine-pass /
            # native-egress spans for this broadcast correlate to THIS
            # pusher connection
            self.relay.set_trace(self.trace_id)
        self.path = self.relay.path
        self.is_pusher = True
        self.server.stats["pushers"] += 1
        self._reply(rtsp.RtspResponse(200), req.cseq)

    # -- SETUP -------------------------------------------------------------
    async def _do_setup(self, req: rtsp.RtspRequest) -> None:
        t = req.transport
        if t is None:
            raise rtsp.RtspError(461)
        base, track_id = _extract_track(req.path())
        if self.session_id is None:
            self.session_id = secrets.token_hex(8)
            FLIGHT.register(self.session_id, trace_id=self.trace_id,
                            client_ip=self.client_ip, path=base)
        mode = "record" if (t.mode == "RECORD" or self.is_pusher) else "play"
        if mode == "record":
            await self._setup_record(req, base, track_id, t)
        else:
            await self._setup_play(req, base, track_id, t)
        EVENTS.emit("rtsp.setup", session_id=self.session_id,
                    stream=self.path or base, trace_id=self.trace_id,
                    status=self._last_response.status
                    if self._last_response else 0,
                    track=track_id, mode=mode)

    async def _setup_record(self, req, base, track_id, t) -> None:
        if self.relay is None:
            raise rtsp.RtspError(455, "SETUP record before ANNOUNCE")
        if track_id is None or track_id not in self.relay.streams:
            raise rtsp.RtspError(404, f"unknown track {track_id}")
        resp_t = rtsp.TransportSpec(protocol=t.protocol, mode="RECORD",
                                    is_tcp=t.is_tcp)
        if t.is_tcp:
            ch = t.interleaved or (2 * (len(self.pusher_tracks)),
                                   2 * len(self.pusher_tracks) + 1)
            self.channel_map[ch[0]] = (track_id, False)
            self.channel_map[ch[1]] = (track_id, True)
            self.pusher_tracks[track_id] = _PusherTrack(track_id)
            resp_t.interleaved = ch
            # receiver reports ride back on the RTCP channel
            # (ReflectorStream.h:341 kRRInterval liveness to the pusher)
            st = self.relay.streams.get(track_id)
            if st is not None:
                st.upstream_rtcp = (
                    lambda d, c=ch[1]: self.send_interleaved(c, d))
                st.upstream_rtcp_owner = self
        else:
            tid = track_id
            from .. import native
            if self.server.config.native_ingest and native.available():
                # recvmmsg batch drain straight into the ring — no
                # per-datagram Python on the push ingest path
                pair = await self.server.udp_pool.allocate_native(
                    on_readable=lambda fd, tid=tid:
                        self._native_rtp_drain(tid, fd),
                    on_rtcp=lambda d, a, tid=tid: self._udp_ingest(
                        tid, d, True, addr=a),
                    uring=getattr(self.server, "uring_ingest_enabled",
                                  False))
            else:
                pair = await self.server.udp_pool.allocate(
                    on_rtp=lambda d, a, tid=tid: self._udp_ingest(
                        tid, d, False),
                    on_rtcp=lambda d, a, tid=tid: self._udp_ingest(
                        tid, d, True, addr=a))
            self.pusher_tracks[track_id] = _PusherTrack(track_id, pair)
            resp_t.server_port = (pair.rtp_port, pair.rtcp_port)
            resp_t.client_port = t.client_port
        self._reply(rtsp.RtspResponse(200, {"Transport": resp_t.to_header()}),
                    req.cseq)

    async def _setup_play(self, req, base, track_id, t) -> None:
        # overload admission (ISSUE 13): past the utilization high-water
        # mark a node sheds NEW subscribers before it burns — 305 to the
        # placement-resolved edge when one has headroom, 453 otherwise.
        # Only the session's FIRST track gates: a half-set-up player
        # must complete or tear down, never strand mid-session.  Plain
        # local-file VOD is exempt: no peer can serve this node's movie
        # folder (live relays migrate, .dvr assets bootstrap — files
        # don't), so a redirect would turn overload into a hard 404.
        adm = self.server.admission
        vod = self.server.vod
        is_dvr = (self.server.dvr is not None
                  and self.server.dvr.is_dvr_path(base))
        local_file = (not is_dvr and vod is not None
                      and vod.resolve(base) is not None)
        if adm is not None and not self.player_tracks and not local_file:
            verdict = adm(base, self.client_key)
            if verdict is not None:
                action, url = verdict
                if action == "redirect" and url:
                    self._reply(rtsp.RtspResponse(
                        305, {"Location": url}), req.cseq)
                else:
                    raise rtsp.RtspError(453)
                return
        dvr = self.server.dvr
        if (dvr is not None and dvr.is_dvr_path(base)
                and self.vod_file is None):
            await self._setup_play_dvr(req, base, track_id, t)
            return
        relay = await self.server.open_for_play(base)
        if relay is None:
            await self._setup_play_vod(req, base, track_id, t)
            return
        self.relay = relay
        self.path = relay.path
        if track_id is None:
            track_id = sorted(set(relay.streams) - set(self.player_tracks))[0] \
                if set(relay.streams) - set(self.player_tracks) else None
        if track_id is None or track_id not in relay.streams:
            raise rtsp.RtspError(404, f"unknown track {track_id}")
        out, resp_t, pair = await self._make_output(t)
        if t.is_tcp:
            self._maybe_readopt_tcp(req, relay.path, track_id, out, resp_t)
        extra = self._negotiate_meta_info(req, out)
        out, rel_extra = self._negotiate_retransmit(req, out, t)
        extra.update(rel_extra)
        extra.update(self._attach_fec(req, out, t))
        self._install_player_track(track_id, out, pair)
        self._reply(rtsp.RtspResponse(200, {
            "Transport": resp_t.to_header(), **extra}), req.cseq)

    def _maybe_readopt_tcp(self, req, path, track_id, out, resp_t) -> None:
        """Checkpoint/migration parity for interleaved TCP (ISSUE 14):
        a player re-connecting after a restart/migration presents its
        old ``Session`` id; if a ``kind=tcp`` checkpoint record matches
        (path, track, session), its set-once rewrite state is adopted —
        same ssrc, framed seq continuing exactly where the dead
        process's wire stopped.  No match = a fresh subscriber (stale
        records age out counted as ``ckpt.tcp_orphan``)."""
        hook = self.server.tcp_restore
        sid = (req.headers.get("session") or "").strip()
        if hook is None or not sid:
            return
        rec = hook(path, track_id, sid)
        if rec is None:
            return
        rw = rec.get("rewrite") or [0, -1, -1, 0, 0]
        out.rewrite.ssrc = int(rw[0])
        out.rewrite.base_src_seq = int(rw[1])
        out.rewrite.base_src_ts = int(rw[2])
        out.rewrite.out_seq_start = int(rw[3])
        out.rewrite.out_ts_start = int(rw[4])
        out.packets_sent = int(rec.get("packets_sent", 0))
        out.bytes_sent = int(rec.get("bytes_sent", 0))
        out.payload_octets = int(rec.get("payload_octets", 0))
        resp_t.ssrc = out.rewrite.ssrc      # Transport echoes the OLD ssrc
        EVENTS.emit("ckpt.tcp_reattach", session_id=self.session_id,
                    stream=path, trace_id=self.trace_id, track=track_id)

    def _negotiate_retransmit(self, req, out, t):
        """Reliable-UDP negotiation: a UDP SETUP carrying
        ``x-Retransmit: our-retransmit[;window=KB]`` gets its output
        wrapped in the resend window and the header echoed back
        (``RTSPRequest::ParseRetransmitHeader`` RTSPRequest.cpp:530-560;
        ``RTPStream::SendSetupResponse`` RTPStream.cpp:616 echo).  TCP
        transports never downgrade (reference: only UDP upgrades)."""
        hdr = req.headers.get("x-retransmit", "")
        if (t.is_tcp or not self.server.config.reliable_udp
                or "our-retransmit" not in hdr.lower()):
            return out, {}
        window_kb = None
        for part in hdr.split(";"):
            k, _, v = part.partition("=")
            if k.strip().lower() == "window":
                try:
                    window_kb = int(v.strip())
                except ValueError:
                    pass
        from ..relay.reliable import ReliableUdpOutput
        return (ReliableUdpOutput(out, window_kb=window_kb),
                {"x-Retransmit": hdr})

    def _attach_fec(self, req, out, t) -> dict:
        """Arm the lossy-WAN reliability tier for one plain-UDP output
        (ISSUE 11): a closed-loop FEC encoder (overhead 0 until the
        subscriber's RRs report loss) + the NACK→RTX replay budget.

        OPT-IN, negotiated like x-Retransmit: the SETUP must carry
        ``x-FEC: parity`` and the grant is echoed back with the parity/
        RTX payload types.  Parity and RTX packets ride the media SSRC
        with their OWN seq spaces, which a non-FEC-aware RFC 3550
        receiver would fold into one per-SSRC seq tracker — garbage
        fraction_lost feeding back into the thinning controller — so
        un-negotiated emission is never allowed.  TCP transports don't
        lose packets; the reliable-UDP wrap owns its subscriber's loss
        already; meta-info wrapping changes the wire format parity
        would have to describe."""
        hdr = req.headers.get("x-fec", "")
        if (not self.server.config.fec_enabled or t.is_tcp
                or "parity" not in hdr.lower()
                or hasattr(out, "resender")
                or out.meta_field_ids is not None):
            return {}
        from ..relay.fec import FecOutputState
        cfg = self.server.config.fec_config()
        out.fec = FecOutputState(cfg)
        return {"x-FEC": f"parity;pt={cfg.payload_type}"
                         f";rtx-pt={cfg.rtx_payload_type}"}

    def _install_player_track(self, track_id, out, pair) -> None:
        """Land a SETUP'd output, releasing any replaced track's transport
        and registering native outputs for RTCP demux only AFTER every
        fallible step succeeded (no leak on a failed SETUP)."""
        egress = self.server.shared_egress
        old = self.player_tracks.get(track_id)
        if old is not None:
            if old.udp_pair:
                old.udp_pair.close()
            elif egress is not None and hasattr(old.output, "rtcp_addr"):
                egress.unregister(old.output, self)
        # correlate this output's retransmit/QoS events back to the
        # player's session (reliable-UDP emits through these)
        out.trace_id = self.trace_id
        out.session_id = self.session_id
        self.player_tracks[track_id] = _PlayerTrack(track_id, out, pair)
        if egress is not None and pair is None and hasattr(out, "rtcp_addr"):
            egress.register(out, self)

    #: x-RTP-Meta-Info fields fillable on the LIVE relay path (tt
    #: transmit-time, sq sequence, md media); VOD adds ft/pn from its
    #: sample tables (META_SUPPORTED_VOD)
    META_SUPPORTED = ("tt", "sq", "md")
    META_SUPPORTED_VOD = ("pp", "tt", "ft", "pn", "sq", "md")

    def _negotiate_meta_info(self, req, out, supported=None) -> dict:
        """DSS QT-client extension: a SETUP carrying ``x-RTP-Meta-Info``
        lists wanted fields; the answer assigns compressed ids and the
        output wraps packets in the meta-info format
        (``RTPMetaInfoLib``; ``RTPStream`` send path)."""
        from ..protocol import rtp_meta
        want = req.headers.get("x-rtp-meta-info", "")
        if not want:
            return {}
        requested = rtp_meta.parse_header(want)
        supported = supported or self.META_SUPPORTED
        granted = {f: i for i, f in enumerate(
            f for f in supported if f in requested)}
        if "md" not in granted:
            return {}                   # md is mandatory for a media stream
        granted["md"] = rtp_meta.UNCOMPRESSED   # md is never compressed
        out.meta_field_ids = granted
        return {"x-RTP-Meta-Info": rtp_meta.build_header(granted)}

    async def _make_output(self, t: rtsp.TransportSpec):
        """Create the egress output for one SETUP'd track (shared between
        live-relay and VOD play paths)."""
        ssrc = secrets.randbits(32)
        seq0 = secrets.randbits(16)
        resp_t = rtsp.TransportSpec(protocol=t.protocol, is_tcp=t.is_tcp)
        resp_t.ssrc = ssrc
        pair = None
        if t.is_tcp:
            ch = t.interleaved or (2 * len(self.player_tracks),
                                   2 * len(self.player_tracks) + 1)
            out = InterleavedOutput(self.writer.transport, ch[0], ch[1],
                                    ssrc=ssrc, out_seq_start=seq0)
            resp_t.interleaved = ch
        else:
            if not t.client_port:
                raise rtsp.RtspError(461, "UDP SETUP without client_port")
            egress = self.server.shared_egress
            if egress is not None and egress.active:
                # shared-pair egress (RTPSocketPool shape): the native
                # batched fan-out path serves this output
                from .egress import NativeUdpOutput
                out = NativeUdpOutput(egress, self.client_ip,
                                      t.client_port[0], t.client_port[1],
                                      ssrc=ssrc, out_seq_start=seq0)
                resp_t.server_port = (egress.rtp_port, egress.rtcp_port)
            else:
                pair = await self.server.udp_pool.allocate(
                    on_rtcp=lambda d, a: self.server.on_client_rtcp(self, d, a))
                out = UdpOutput(pair.rtp_transport, pair.rtcp_transport,
                                self.client_ip, t.client_port[0],
                                t.client_port[1], ssrc=ssrc,
                                out_seq_start=seq0)
                resp_t.server_port = (pair.rtp_port, pair.rtcp_port)
            resp_t.client_port = t.client_port
        return out, resp_t, pair

    async def _setup_play_vod(self, req, base, track_id, t) -> None:
        """SETUP on a file path (QTSSFileModule DoSetup equivalent)."""
        if self.vod_file is None:
            vod = self.server.vod
            f = vod.open(base) if vod is not None else None
            if f is None:
                raise rtsp.RtspError(404)
            self.vod_file = f
            self.path = base
        n_tracks = sum(1 for tr in (self.vod_file.video_track(),
                                    self.vod_file.audio_track())
                       if tr is not None)
        if track_id is None:
            track_id = len(self.player_tracks) + 1
        if not 1 <= track_id <= n_tracks:
            raise rtsp.RtspError(404, f"unknown track {track_id}")
        out, resp_t, pair = await self._make_output(t)
        meta_extra = self._negotiate_meta_info(
            req, out, supported=self.META_SUPPORTED_VOD)
        out, rel_extra = self._negotiate_retransmit(req, out, t)
        # x-FEC is NOT offered on VOD: the NACK handler resolves through
        # conn.relay (None for file sessions) and the cold FileSession
        # never registers with a RelayStream — granting a capability the
        # server cannot honor would leave the client waiting on it
        # (reliable-UDP is the VOD loss story, as in the reference)
        self._install_player_track(track_id, out, pair)
        self._reply(rtsp.RtspResponse(200, {
            "Transport": resp_t.to_header(), **rel_extra, **meta_extra}),
            req.cseq)

    async def _setup_play_dvr(self, req, base, track_id, t) -> None:
        """SETUP on a ``<live>.dvr`` asset path: the spilled per-track
        indexes name the tracks; outputs are ordinary player outputs
        the time-shift session block-fills at PLAY.  x-RTP-Meta-Info
        and x-FEC are not offered here — ft/pn need mp4 sample tables
        and FEC needs a live RelayStream, neither of which a spilled
        asset has (reliable-UDP remains the replay loss story)."""
        dvr = self.server.dvr
        asset = dvr.open_asset(base)
        if asset is None:
            raise rtsp.RtspError(404)
        try:
            track_ids = sorted(asset.tracks)
        finally:
            asset.close()
        if track_id is None:
            avail = [i for i in track_ids if i not in self.player_tracks]
            track_id = avail[0] if avail else None
        if track_id is None or track_id not in track_ids:
            raise rtsp.RtspError(404, f"unknown track {track_id}")
        self.dvr_path = sdp._norm(base)
        self.path = self.dvr_path
        out, resp_t, pair = await self._make_output(t)
        out, rel_extra = self._negotiate_retransmit(req, out, t)
        self._install_player_track(track_id, out, pair)
        self._reply(rtsp.RtspResponse(200, {
            "Transport": resp_t.to_header(), **rel_extra}), req.cseq)

    async def _do_record(self, req: rtsp.RtspRequest) -> None:
        if not self.is_pusher or self.relay is None:
            raise rtsp.RtspError(455)
        self.relay.pusher_alive = True
        if self.server.dvr is not None:
            # dvr_enabled: every pushed broadcast records — completed
            # ring windows spill to the packed-window store from the
            # first full window on (idempotent re-arm on re-RECORD)
            self.server.dvr.arm(
                self.relay,
                self.server.registry.sdp_cache.get(self.relay.path) or "")
        self._reply(rtsp.RtspResponse(200), req.cseq)

    @staticmethod
    def _range_npt(req: rtsp.RtspRequest) -> float | None:
        """The numeric start of a ``Range: npt=…`` header, or None for
        a missing/``now`` range (``npt=now-`` means the live edge, RFC
        2326 §3.6 — only an explicit number asks for the past)."""
        rng = req.headers.get("range", "")
        if not rng.startswith("npt="):
            return None
        start = rng[4:].split("-")[0].strip()
        if not start or start == "now":
            return None
        try:
            return max(float(start), 0.0)
        except ValueError:
            return None

    @staticmethod
    def _parse_speed(req: rtsp.RtspRequest) -> tuple[float, dict]:
        """RFC 2326 §12.35 Speed on a time-shift PLAY: the catch-up
        accelerator (delivery-rate factor; >1 is how a shifted viewer
        reaches the live head and rejoins).  Out-of-range plays at 1×
        and the response says so."""
        v = req.headers.get("speed", "")
        if not v:
            return 1.0, {}
        try:
            f = float(v)
        except ValueError:
            f = None
        if f is None or not 0.01 <= f <= 8.0:
            return 1.0, {"Speed": "1"}
        return f, {"Speed": f"{f:g}"}

    async def _do_play(self, req: rtsp.RtspRequest) -> None:
        if self.vod_file is not None:
            await self._do_play_vod(req)
            return
        if self.dvr_path is not None:
            await self._do_play_dvr(req)
            return
        if self.relay is None or not self.player_tracks:
            raise rtsp.RtspError(455)
        # live path under an armed spiller: an explicit numeric Range
        # (rewind) or a latched PAUSE bookmark re-enters through the
        # time-shift tier; ``npt=now-`` / no Range joins the live edge
        dvr = self.server.dvr
        start_npt = self._range_npt(req)
        if (dvr is not None
                and (start_npt is not None or self.pause_ids)
                and self._play_timeshift(req, start_npt)):
            return
        infos = []
        for tid, pt in self.player_tracks.items():
            if pt.output not in self.relay.streams[tid].outputs:
                self.relay.add_output(tid, pt.output)
            infos.append(f"url={req.uri.rstrip('/')}/trackID={tid}"
                         f";seq={pt.output.rewrite.out_seq_start}")
        self.playing = True
        self.server.stats["players"] += 1
        self.server.wake_pump()
        self._reply(rtsp.RtspResponse(200, {
            "Range": "npt=now-", "RTP-Info": ",".join(infos)}), req.cseq)

    async def _do_play_vod(self, req: rtsp.RtspRequest) -> None:
        from ..vod.session import FileSession
        if not self.player_tracks:
            raise rtsp.RtspError(455)
        start_npt = 0.0
        rng = req.headers.get("range", "")
        if rng.startswith("npt="):
            try:
                start_npt = float(rng[4:].split("-")[0] or 0.0)
            except ValueError:
                start_npt = 0.0
        if self.vod_session is not None:
            self.vod_session.stop()
        # Speed (RFC 2326 §12.35): delivery-rate factor, timestamps
        # untouched.  Scale (§12.34): viewing-rate factor — delivery is
        # paced faster AND RTP timestamps are compressed by the factor so
        # a compliant client actually renders fast-forward.  Reverse play
        # (negative Scale) is unsupported and ignored, not silently
        # converted to forward.
        extra = {}
        speed = 1.0
        ts_scale = 1.0
        for hdr in ("scale", "speed"):
            v = req.headers.get(hdr, "")
            if not v:
                continue
            try:
                f = float(v)
            except ValueError:
                f = None
            if f is None or not 0.01 <= f <= 8.0:
                # RFC 2326 §12.34: the response carries the value actually
                # used — a rejected request plays at 1x and must say so
                extra[hdr.capitalize()] = "1"
                continue
            speed *= f
            if hdr == "scale":
                ts_scale = f
            extra[hdr.capitalize()] = f"{f:g}"
        outputs = {tid: pt.output for tid, pt in self.player_tracks.items()}
        # hot vs cold: the group pacer serves plain-RTP sessions through
        # the cache + live engine tier (ISSUE 10); Scale (timestamp
        # compression is not an affine offset) and x-RTP-Meta-Info
        # sessions (ft/pn/pp come from the sample tables mid-send) keep
        # the per-session FileSession
        pacer = getattr(self.server, "vod_pacer", None)
        hot = (pacer is not None and ts_scale == 1.0
               and all(o.meta_field_ids is None for o in outputs.values()))
        if hot:
            self.vod_session = pacer.open(
                self.vod_file, outputs, start_npt=start_npt,
                speed=speed, path=self.path or req.uri)
            self.server.wake_pump()
        else:
            self.vod_session = FileSession(self.vod_file, outputs,
                                           start_npt=start_npt,
                                           speed=speed,
                                           ts_scale=ts_scale)
            self.vod_session.start()
        self.playing = True
        self.server.stats["players"] += 1
        infos = ",".join(
            f"url={req.uri.rstrip('/')}/trackID={tid}"
            f";seq={pt.output.rewrite.out_seq_start}"
            for tid, pt in self.player_tracks.items())
        self._reply(rtsp.RtspResponse(200, {
            "Range": f"npt={start_npt:.3f}-", "RTP-Info": infos,
            **extra}), req.cseq)

    def _play_timeshift(self, req, start_npt: float | None) -> bool:
        """PLAY into the past on a LIVE subscription: detach from the
        live fan-out and hand the outputs (rewrite state intact — same
        ssrc, contiguous seq across the shift and the eventual catch-up
        join) to a pacer-driven TimeShiftSession over the spilled
        windows.  An explicit Range wins over a pause bookmark; returns
        False (caller joins live) when the asset has nothing yet."""
        speed, extra = self._parse_speed(req)
        outputs = {tid: pt.output
                   for tid, pt in self.player_tracks.items()}
        start_ids = None if start_npt is not None else self.pause_ids
        self._detach_outputs()
        if self.vod_session is not None:
            self.vod_session.stop()
            self.vod_session = None
        sess = self.server.dvr.open_timeshift(
            self.path, outputs, start_npt=start_npt,
            start_ids=start_ids, speed=speed)
        if sess is None:
            return False
        self.vod_session = sess
        self.pause_ids = None
        self.playing = True
        self.server.stats["players"] += 1
        self.server.wake_pump()
        infos = ",".join(
            f"url={req.uri.rstrip('/')}/trackID={tid}"
            f";seq={pt.output.rewrite.out_seq_start}"
            for tid, pt in self.player_tracks.items())
        self._reply(rtsp.RtspResponse(200, {
            "Range": f"npt={sess.position_npt() or sess.start_npt:.3f}-",
            "RTP-Info": infos, **extra}), req.cseq)
        return True

    async def _do_play_dvr(self, req: rtsp.RtspRequest) -> None:
        """PLAY a spilled ``.dvr`` asset: pure replay under the shared
        VOD pacer (instant stream-to-VOD — nothing was re-muxed; live
        pause/rewind uses ``_play_timeshift`` on the live path)."""
        if not self.player_tracks:
            raise rtsp.RtspError(455)
        start_npt = self._range_npt(req)
        # no explicit Range + a latched PAUSE bookmark = resume exactly
        # there (the same contract as the live _play_timeshift path);
        # an explicit Range always wins and discards the bookmark
        start_ids = None if start_npt is not None else self.pause_ids
        speed, extra = self._parse_speed(req)
        if self.vod_session is not None:
            self.vod_session.stop()
            self.vod_session = None
        outputs = {tid: pt.output
                   for tid, pt in self.player_tracks.items()}
        sess = self.server.dvr.open_timeshift(
            self.dvr_path, outputs, start_npt=start_npt,
            start_ids=start_ids, speed=speed)
        if sess is None:
            raise rtsp.RtspError(404)
        self.vod_session = sess
        self.pause_ids = None
        self.playing = True
        self.server.stats["players"] += 1
        self.server.wake_pump()
        infos = ",".join(
            f"url={req.uri.rstrip('/')}/trackID={tid}"
            f";seq={pt.output.rewrite.out_seq_start}"
            for tid, pt in self.player_tracks.items())
        self._reply(rtsp.RtspResponse(200, {
            "Range": f"npt={sess.position_npt() or sess.start_npt:.3f}-",
            "RTP-Info": infos, **extra}), req.cseq)

    async def _do_pause(self, req: rtsp.RtspRequest) -> None:
        sess = self.vod_session
        if sess is not None and hasattr(sess, "pause_ids"):
            # pausing a time-shift session: latch the exact resume
            # cursors (next id the PLAYER has not received)
            self.pause_ids = sess.pause_ids()
        elif (self.relay is not None and self.playing
                and self.server.dvr is not None
                and self.server.dvr.armed(self.path)):
            # live pause under an armed spiller: each output's ring
            # bookmark is the next unsent absolute id, and the spill
            # shares the ring's id space — the bookmark IS the resume
            # cursor (a resume before the first reflect just re-joins)
            ids = {tid: int(pt.output.bookmark)
                   for tid, pt in self.player_tracks.items()
                   if pt.output.bookmark is not None}
            self.pause_ids = ids or None
        if sess is not None:
            sess.stop()
            self.vod_session = None
        self._detach_outputs()
        self.playing = False
        self._reply(rtsp.RtspResponse(200), req.cseq)

    async def _do_teardown(self, req: rtsp.RtspRequest) -> None:
        self._reply(rtsp.RtspResponse(200), req.cseq)
        await self.close()

    # -------------------------------------------------------- media paths
    def _on_interleaved(self, pkt: rtsp.InterleavedPacket) -> int:
        """Pushed media (RECORD mode) or player RTCP feedback; 1 where
        the packet was pushed into the relay."""
        m = self.channel_map.get(pkt.channel)
        if m is not None and self.relay is not None:
            track_id, is_rtcp = m
            if not is_rtcp:
                self.server.modules.run_incoming_rtp(self.relay, track_id,
                                                     pkt.data)
            self.relay.push(track_id, pkt.data, is_rtcp=is_rtcp)
            self.server.stats["packets_in"] += 1
            self.server.wake_pump()
            return 1
        if self.player_tracks and pkt.channel % 2 == 1:
            self.server.on_client_rtcp(self, pkt.data)
        return 0

    def send_interleaved(self, channel: int, data: bytes) -> None:
        """Write one $-framed packet on this connection (server→client)."""
        if not self.writer.is_closing():
            self.writer.write(b"$" + bytes([channel])
                              + len(data).to_bytes(2, "big") + data)

    def _native_rtp_drain(self, track_id: int, fd: int) -> None:
        """Readiness-edge callback for a pusher's native-ingest RTP
        socket: one call drains the whole pending batch into the ring."""
        if self.relay is None:
            return
        try:
            n = self.relay.drain_native(track_id, fd)
        except OSError:
            # hard recv error (or a close race on the fd): stop the
            # readiness callback so a permanently-readable dead socket
            # cannot spin the loop; the timeout sweep reaps the track
            try:
                asyncio.get_event_loop().remove_reader(fd)
            except (OSError, ValueError):
                pass
            return
        # the drain may have disarmed a failing io_uring ring (native
        # fallback to recvmmsg): its now-closed ring fd must stop being
        # watched before another socket recycles the number
        pt = self.pusher_tracks.get(track_id)
        pair = pt.udp_pair if pt is not None else None
        if pair is not None and getattr(pair, "_uring_armed", False):
            pair.prune_ring_watch()
        if n:
            self.last_activity = time.monotonic()
            self.server.stats["packets_in"] += n
            self.server.wake_pump()

    def _udp_ingest(self, track_id: int, data: bytes, is_rtcp: bool,
                    addr=None) -> None:
        if self.relay is not None:
            self.relay.push(track_id, data, is_rtcp=is_rtcp)
            self.server.stats["packets_in"] += 1
            self.server.wake_pump()
            if is_rtcp and addr is not None:
                # learn the pusher's RTCP address once → upstream RRs
                st = self.relay.streams.get(track_id)
                pt = self.pusher_tracks.get(track_id)
                if (st is not None and st.upstream_rtcp is None
                        and pt is not None and pt.udp_pair is not None):
                    tr = pt.udp_pair.rtcp_transport
                    st.upstream_rtcp = (
                        lambda d, t=tr, a=addr: t.sendto(d, a))
                    st.upstream_rtcp_owner = self

    # ----------------------------------------------------------- teardown
    def _detach_outputs(self) -> None:
        if self.relay is None:
            return
        for tid, pt in self.player_tracks.items():
            st = self.relay.streams.get(tid)
            if st is not None:
                st.remove_output(pt.output)

    async def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        if self.session_id is not None:
            EVENTS.emit("rtsp.close", session_id=self.session_id,
                        stream=self.path, trace_id=self.trace_id,
                        level="warn" if self.abnormal_reason else "info",
                        reason=self.abnormal_reason or "eof")
            if self.abnormal_reason and (self.player_tracks
                                         or self.is_pusher):
                # abnormal media-session death → freeze the black box
                FLIGHT.dump(self.session_id, reason=self.abnormal_reason)
            else:
                FLIGHT.discard(self.session_id)
        self.server.modules.run_session_closing(self)
        self.server.on_session_closed(self)
        if self.vod_session is not None:
            self.vod_session.stop()
            self.vod_session = None
        if self.vod_file is not None:
            self.vod_file.close()
            self.vod_file = None
        self._detach_outputs()
        if self.player_tracks:
            # a departed player's QoS gauges must not linger in /metrics
            # (a surviving subscriber's next RR re-creates them)
            from ..relay import quality as quality_mod
            from ..relay import fec as fec_mod
            for tid in self.player_tracks:
                quality_mod.drop_qos(self.path, tid)
                fec_mod.drop_overhead_gauge(self.path, tid)
        egress = self.server.shared_egress
        for pt in self.player_tracks.values():
            if pt.udp_pair:
                pt.udp_pair.close()
            elif egress is not None and hasattr(pt.output, "rtcp_addr"):
                egress.unregister(pt.output, self)
        for pt in self.pusher_tracks.values():
            if pt.udp_pair:
                pt.udp_pair.close()
        if self.is_pusher and self.relay is not None:
            # our upstream-RR closures reference this (dying) connection —
            # clear them so an adopted session re-learns the new pusher's
            # RTCP path instead of writing into a closed transport forever
            for st in self.relay.streams.values():
                if st.upstream_rtcp_owner is self:
                    st.upstream_rtcp = None
                    st.upstream_rtcp_owner = None
            # pusher gone → tear down the relay session (the reference frees
            # the ReflectorSession when the broadcast stops) — but only if
            # still OURS: a re-ANNOUNCE adopts the session (owner re-stamped)
            # and that live broadcast must survive our disconnect
            if (self.server.registry.find(self.relay.path) is self.relay
                    and self.relay.owner is self):
                self.server.registry.remove(self.relay.path)
            self.relay = None
        if self in self.server.connections:
            self.server.connections.discard(self)
            self.server.on_ip_disconnect(self.client_ip)
        try:
            self.writer.close()
        except Exception:
            pass


class RtspServer:
    """Listener + connection registry (QTSServer::CreateListeners analog)."""

    def __init__(self, config: ServerConfig, registry: SessionRegistry,
                 *, describe_fallback=None, on_pump_wake=None, vod=None,
                 auth=None, access_log=None):
        self.config = config
        self.registry = registry
        self.vod = vod                       # VodService or None
        #: VodPacerGroup (ISSUE 10) — set by the app once the engine
        #: tier is probed; None = every PLAY gets the cold FileSession
        self.vod_pacer = None
        #: DvrManager (ISSUE 12) — set by the app when dvr_enabled; None
        #: = PAUSE detaches (classic), ``.dvr`` paths 404, RECORD never
        #: arms a spiller
        self.dvr = None
        self.auth = auth                     # AuthService or None
        self.access_log = access_log         # AccessLog or None
        #: overload admission hook (ISSUE 13) — set by the app under
        #: cluster mode: ``(path, client_key) -> None | (action, url)``;
        #: None = every SETUP admitted (standalone behavior)
        self.admission = None
        #: cross-node trace acceptance gate (ISSUE 15) — set by the app
        #: under cluster mode: ``(x_cluster_node_header) -> bool``;
        #: None = X-Trace-Id headers are never adopted (standalone)
        self.peer_trace_gate = None
        #: interleaved-TCP checkpoint re-attach hook (ISSUE 14) — set by
        #: the app when checkpointing is on: ``(path, track_id,
        #: session_id) -> record | None``.  A re-connecting player that
        #: presents its old Session id on an interleaved SETUP adopts
        #: the recorded rewrite state, so the framed seq space continues
        #: gapless across a restart/migration.
        self.tcp_restore = None
        from .modules import ModuleRegistry
        self.modules = ModuleRegistry()
        #: RTSP-over-HTTP tunnels: x-sessioncookie → GET-side connection
        self.tunnels: dict[str, RtspConnection] = {}
        #: hook for plain HTTP GET on the RTSP port (mp3/stats); set by app
        self.http_get_handler = None
        self.udp_pool = UdpPortPool(bind_ip="0.0.0.0")
        #: shared (RTP, RTCP) egress pair for UDP players — the reference's
        #: RTPSocketPool shared-pair + UDPDemuxer design; doorway to the
        #: native batched egress (server/egress.py). None until start().
        self.shared_egress = None
        #: set by the app's egress-backend probe: pusher RTP sockets get
        #: multishot io_uring ingest (transports.NativeIngestPair arms
        #: per pair; the recvmmsg drain stays the fallback)
        self.uring_ingest_enabled = False
        #: SdpFileRelaySource for .sdp-described UDP/multicast broadcasts
        self.relay_source = None
        self.connections: set[RtspConnection] = set()
        #: live connection count per client IP (O(1) SpamDefense check)
        self._per_ip: dict[str, int] = {}
        self.stats = {"requests": 0, "pushers": 0, "players": 0,
                      "packets_in": 0}
        self._server: asyncio.AbstractServer | None = None
        #: hook for VOD / other describe sources: async (path) -> sdp | None
        self.describe_fallback = describe_fallback
        self._on_pump_wake = on_pump_wake
        self.port: int | None = None

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._on_connection, self.config.bind_ip, self.config.rtsp_port)
        self.port = self._server.sockets[0].getsockname()[1]
        if self.config.shared_udp_egress:
            from .egress import SharedUdpEgress
            self.shared_egress = SharedUdpEgress(self.config.bind_ip)
            await self.shared_egress.start()
            self.shared_egress.on_rtcp = self.on_client_rtcp

    async def stop(self) -> None:
        for conn in list(self.connections):
            await conn.close()
        if self.shared_egress is not None:
            self.shared_egress.close()
            self.shared_egress = None
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()

    async def _on_connection(self, reader, writer) -> None:
        if len(self.connections) >= self.config.max_connections:
            writer.close()
            return
        # per-IP cap (QTSSSpamDefenseModule): refuse before spending a task
        per_ip = self.config.max_connections_per_ip
        peer = writer.get_extra_info("peername")
        ip = peer[0] if peer else "?"       # same fallback as client_ip
        if per_ip and self._per_ip.get(ip, 0) >= per_ip:
            writer.close()
            return
        conn = RtspConnection(self, reader, writer)
        self.connections.add(conn)
        self._per_ip[ip] = self._per_ip.get(ip, 0) + 1
        await conn.run()

    def on_ip_disconnect(self, ip: str) -> None:
        n = self._per_ip.get(ip, 0) - 1
        if n > 0:
            self._per_ip[ip] = n
        else:
            self._per_ip.pop(ip, None)

    # -- hooks -------------------------------------------------------------
    async def describe(self, path: str) -> str | None:
        # live sessions (pushed or already-opened broadcasts) win over
        # on-disk .sdp files, which win over VOD assets
        text = self.registry.sdp_cache.get(path)
        if text is None and self.relay_source is not None:
            text = await self.relay_source.describe(path)
        if text is None and self.vod is not None:
            text = await self.vod.describe(path)
        if text is None and self.dvr is not None:
            # <live path>.dvr: the spilled asset's stored push SDP
            text = await self.dvr.describe(path)
        if text is None and self.describe_fallback is not None:
            text = await self.describe_fallback(path)
        return text

    async def open_for_play(self, path: str) -> RelaySession | None:
        sess = self.registry.find(path)
        if sess is None and self.relay_source is not None:
            sess = await self.relay_source.open(path)
        return sess

    async def handle_http_get(self, conn: RtspConnection, target: str,
                              headers: dict) -> None:
        if self.http_get_handler is not None:
            handled = await self.http_get_handler(conn, target, headers)
            if handled:
                return
        conn.writer.write(b"HTTP/1.0 404 Not Found\r\n\r\n")

    def on_session_closed(self, conn: RtspConnection) -> None:
        """ClientSessionClosing → access-log record (AccessLogModule role)."""
        if self.access_log is None or (not conn.player_tracks
                                       and not conn.is_pusher):
            return
        from ..utils.logs import AccessRecord
        sent = sum(pt.output.packets_sent
                   for pt in conn.player_tracks.values())
        nbytes = sum(pt.output.bytes_sent
                     for pt in conn.player_tracks.values())
        any_udp = any(pt.udp_pair for pt in conn.player_tracks.values())
        self.access_log.record(AccessRecord(
            client_ip=conn.client_ip, uri=conn.uri or conn.path or "-",
            method="RECORD" if conn.is_pusher else "PLAY",
            duration_sec=time.monotonic() - conn.created_at,
            bytes_sent=nbytes, packets_sent=sent,
            user_agent=conn.user_agent,
            transport="UDP" if any_udp else "TCP"))

    def on_client_rtcp(self, conn: RtspConnection, data: bytes,
                       addr=None) -> None:
        """Receiver reports from players → per-output quality adaptation
        (the QTSS_RTCPProcess_Role → FlowControlModule pipeline), and
        'qtak' acks → the reliable-UDP resend window.

        Valid RTCP from a player proves the session is alive: refresh its
        idle clock, or the sweep kills an actively-watching UDP player at
        rtsp_timeout (its RTSP TCP connection is legitimately silent
        during playback).  The refresh requires PROOF of ownership — the
        datagram's source is a registered track's RTCP address, or the
        compound references an SSRC this connection's outputs own — so a
        forged-but-parseable empty RR cannot keep a dead session
        allocated forever.  Reference: ``RTPStream::
        ProcessIncomingRTCPPacket`` → ``RefreshTimeout`` via RTCPTask."""
        from ..protocol import rtcp as rtcp_mod
        self.stats.setdefault("rtcp_in", 0)
        self.stats["rtcp_in"] += 1
        try:
            pkts = rtcp_mod.parse_compound(data)
        except rtcp_mod.RtcpError:
            return
        outputs = {pt.output.rewrite.ssrc: pt.output
                   for pt in conn.player_tracks.values()}
        track_of = {pt.output.rewrite.ssrc: tid
                    for tid, pt in conn.player_tracks.items()}
        # the RTCP source address names the track (each SETUP registers its
        # own client rtcp port) — required for acks, whose 16-bit seq
        # spaces collide across tracks (a video ack must never pop an
        # audio packet from its resend window)
        addr_out = None
        if addr is not None:
            for pt in conn.player_tracks.values():
                if getattr(pt.output, "rtcp_addr", None) == tuple(addr):
                    addr_out = pt.output
                    break
        proven = addr_out is not None
        from ..resilience.inject import INJECTOR
        for p in pkts:
            if isinstance(p, rtcp_mod.ReceiverReport):
                t0 = time.perf_counter_ns()
                judged_n = 0
                for rb in p.reports:
                    out = outputs.get(rb.ssrc)
                    if out is not None:
                        proven = True
                        frac = rb.fraction_lost / 256.0
                        spoof = (INJECTOR.rr_loss_spoof()
                                 if INJECTOR.active else None)
                        if spoof is not None:
                            # chaos site (ISSUE 11): drive the loss-fed
                            # controllers without a lossy wire
                            judged = spoof
                        else:
                            # judged once the folded reports cover
                            # enough packets (relay/quality.py)
                            judged = out.thinning.controller.fold.add(
                                rb.highest_seq, rb.cumulative_lost,
                                out.rewrite.out_seq_start)
                        RTCP_REPORT_BLOCKS.inc(
                            result="folded" if judged is None else "judged")
                        if judged is not None:
                            judged_n += 1
                            fec = getattr(out, "fec", None)
                            if fec is not None:
                                # closed-loop FEC overhead rides the SAME
                                # RR stream; thinning hears only what the
                                # tier leaves unrepaired
                                fec.on_receiver_report(out, judged)
                                judged = fec.unrepaired(judged)
                            out.on_receiver_report(judged)
                        # fold loss/jitter into the scrapeable per-stream
                        # QoS gauges (obs registry)
                        from ..relay import quality as quality_mod
                        tid = track_of.get(rb.ssrc)
                        rate = None
                        if conn.relay is not None and tid in conn.relay.streams:
                            rate = conn.relay.streams[tid].info.clock_rate
                        quality_mod.record_rr_qos(
                            conn.path, tid, frac, rb.jitter, rate)
                # ring only, as the RTSP handlers' spans: 800 a second
                TRACER.add("rtcp.rr", t0, cat="rtcp",
                           blocks=len(p.reports), judged=judged_n)
            elif isinstance(p, rtcp_mod.Nadu):
                # 3GPP NADU buffer state → per-output rate adaptation;
                # each block names the media sender SSRC it reports on
                for blk in p.blocks:
                    out = outputs.get(blk.ssrc)
                    if out is not None:
                        proven = True
                        out.on_nadu(blk.playout_delay_ms,
                                    blk.free_buffer_64b)
                        fec = getattr(out, "fec", None)
                        if fec is not None:
                            # buffer distress shifts the NACK-vs-FEC
                            # split toward RTX (parity is bitrate)
                            fec.on_nadu(out, blk.playout_delay_ms,
                                        blk.free_buffer_64b)
            elif isinstance(p, rtcp_mod.GenericNack):
                # RFC 4585 generic NACK → ring-bookmark RTX replay
                # (relay/fec.py): the ring IS the retransmission buffer
                out = outputs.get(p.media_ssrc)
                if out is None and addr_out is not None \
                        and getattr(addr_out, "fec", None) is not None:
                    out = addr_out       # source-addr routed fallback
                if out is not None and self._handle_nack(conn, out, p):
                    proven = True
            elif isinstance(p, rtcp_mod.App):
                # RTCPAckPacket → RTPPacketResender::AckPacket path.
                # Route: exact track by RTCP source addr, else by the
                # App's SSRC, else (single reliable track only) fall back
                # to it — never broadcast across colliding seq spaces
                routed = addr_out is not None or p.ssrc in outputs
                if addr_out is not None:
                    targets = [addr_out]
                elif p.ssrc in outputs:
                    targets = [outputs[p.ssrc]]
                else:
                    targets = [o for o in outputs.values()
                               if hasattr(o, "on_rtcp_app")]
                    if len(targets) != 1:
                        continue
                for out in targets:
                    ack_fn = getattr(out, "on_rtcp_app", None)
                    if ack_fn is not None:
                        matched = ack_fn(p)
                        # Ownership proof: a source-addr/SSRC-routed
                        # track, or — in the single-track fallback,
                        # where neither matched — an ack seq that
                        # actually popped a packet from the resend
                        # window.  A forged-but-parseable App with an
                        # arbitrary SSRC proves nothing and must not
                        # refresh the idle clock.
                        if routed or matched:
                            proven = True
        if proven:
            conn.last_activity = time.monotonic()

    def _handle_nack(self, conn: RtspConnection, out, nack) -> bool:
        """Resolve one generic NACK's lost OUTPUT seqs to live ring
        bookmarks and replay them as RTX (ISSUE 11).  Returns True when
        the NACK matched a FEC-armed output (ownership proof — a
        forged NACK for an unknown SSRC proves nothing)."""
        if getattr(out, "fec", None) is None or conn.relay is None:
            return False
        tid = next((t for t, pt in conn.player_tracks.items()
                    if pt.output is out), None)
        stream = conn.relay.streams.get(tid) if tid is not None else None
        if stream is None or stream.fec is None:
            return False
        stream.fec.replay_nacked(out, nack.lost_seqs(), now_ms())
        return True

    def wake_pump(self) -> None:
        if self._on_pump_wake is not None:
            self._on_pump_wake()

    def sweep_timeouts(self) -> int:
        """Close idle connections (TimeoutTask 15 s sweep equivalent)."""
        now = time.monotonic()
        killed = 0
        for conn in list(self.connections):
            idle = now - conn.last_activity
            limit = (self.config.push_timeout_sec if conn.is_pusher
                     else self.config.rtsp_timeout_sec)
            if conn.is_pusher and self.relay_active(conn):
                limit = max(limit, self.config.push_timeout_sec)
            if idle > limit:
                conn.abnormal_reason = (conn.abnormal_reason
                                        or f"timeout: idle {idle:.1f}s "
                                           f"> {limit}s")
                asyncio.get_event_loop().create_task(conn.close())
                killed += 1
        return killed

    @staticmethod
    def relay_active(conn: RtspConnection) -> bool:
        return (conn.relay is not None
                and now_ms() - conn.relay.last_ingest_ms < 5_000)
