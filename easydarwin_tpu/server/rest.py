"""JSON REST management API on the service port.

Reference parity: ``HTTPSession.cpp:318-732`` — routes at 365-405:
``/api/v1/{login, logout, getserverinfo, getbaseconfig, setbaseconfig,
restart, getrtsplivesessions, getdevicestream, livedevicestream}``, answers
wrapped in the EasyProtocol envelope (``HTTPSession.cpp:655-732``).

A deliberately tiny HTTP/1.1 server (no framework): parse request line +
headers + optional body, route, answer JSON, close or keep-alive.
"""

from __future__ import annotations

import asyncio
import base64
import json
import re
import secrets
import time
from urllib.parse import parse_qs, urlparse

from ..cluster import protocol as ep
from .config import ServerConfig

SERVER_NAME = "easydarwin-tpu/0.1"

#: /api/v1/sessions/<rtsp-session-id>/trace (ids are token_hex, so the
#: route()-level lowercasing is lossless)
_SESSION_TRACE_RE = re.compile(r"^sessions/([0-9a-f]+)/trace$")


class RestApi:
    def __init__(self, config: ServerConfig, app):
        self.config = config
        self.app = app                      # StreamingServer
        self.tokens: set[str] = set()
        # per-process CSRF token for the /admin HTML set form: a
        # cross-site POST rides cached Basic credentials but cannot READ
        # the admin page to learn this value (same-origin policy)
        self._admin_csrf = secrets.token_urlsafe(16)
        self._server: asyncio.AbstractServer | None = None
        self.port: int | None = None
        self.started_at = time.time()
        #: HLS serving health (ISSUE 14): 304 revalidations served and
        #: body sends per egress rung — the regression tests pin the
        #: zero-per-request-copy hot path on these
        self.hls_not_modified = 0
        self.hls_rungs = {"io_uring": 0, "writev": 0, "buffered": 0}

    def _stream_body(self, writer: asyncio.StreamWriter, head: bytes,
                     data) -> str:
        """Write one HLS response through the stream-egress rung ladder
        (io_uring → writev → buffered).  The header rides the transport
        (tiny, flushes immediately); when the transport buffer is empty
        the body goes straight to the socket through the native sender —
        no per-request copy of the segment bytes, no per-chunk Python.
        Any shortfall (EAGAIN, no raw socket, buffered header) hands the
        REMAINDER to the transport, which owns ordering from then on."""
        from .. import native, obs
        tr = writer.transport
        writer.write(head)
        mv = data if isinstance(data, memoryview) else memoryview(data)
        rung = "buffered"
        sent = 0
        try:
            sock = tr.get_extra_info("socket")
        except Exception:
            sock = None
        if (sock is not None and not tr.is_closing()
                and tr.get_write_buffer_size() == 0
                and native.loaded()):
            fd = sock.fileno()
            uring = getattr(self.app, "uring_egress", None)
            if uring is not None and getattr(uring, "active", False):
                rung = "io_uring"
                sent = uring.stream_write(fd, mv)
            else:
                rung = "writev"
                sent = native.stream_write(fd, mv)
            if sent < 0:
                rung, sent = "buffered", 0
        if sent < len(mv):
            # memoryview slice: the transport queues a VIEW of the same
            # immutable bytes — still zero copies of the segment body
            tr.write(mv[sent:])
        self.hls_rungs[rung] = self.hls_rungs.get(rung, 0) + 1
        obs.HLS_SEGMENT_EGRESS_BYTES.inc(len(mv), rung=rung)
        return rung

    #: content types the scrape-compression satellite covers: the
    #: Prometheus exposition and the NDJSON event feeds (big, highly
    #: repetitive, fetched every few seconds by federating scrapers).
    #: HLS bodies must NOT be here (the zero-copy stream-egress path
    #: sends them verbatim) and the pprof endpoint is already gzipped.
    _GZIP_CTYPES = ("text/plain", "application/x-ndjson")
    #: below this a gzip header costs more than it saves
    _GZIP_MIN_BYTES = 256

    def _maybe_gzip(self, headers: dict, status: int, ctype: str,
                    data: bytes) -> tuple[bytes, dict | None]:
        """Compress a /metrics or NDJSON response body when the client
        asked for it (``Accept-Encoding: gzip``).  Returns the (possibly
        compressed) body + the extra response headers; identity when
        compression would not help or does not apply."""
        if (status != 200 or not data or len(data) < self._GZIP_MIN_BYTES
                or not (ctype or "").startswith(self._GZIP_CTYPES)):
            return data, None
        accept = headers.get("accept-encoding", "")
        if "gzip" not in accept.lower():
            return data, None
        import gzip
        # mtime=0: deterministic bytes, so scrape-cost tests can pin size
        packed = gzip.compress(data, 6, mtime=0)
        if len(packed) >= len(data):
            return data, None
        return packed, {"Content-Encoding": "gzip",
                        "Vary": "Accept-Encoding"}

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._on_connection, self.config.bind_ip,
            self.config.service_port)
        self.port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()

    async def _on_connection(self, reader: asyncio.StreamReader,
                             writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                head = await reader.readuntil(b"\r\n\r\n")
                lines = head.decode("latin-1").split("\r\n")
                try:
                    method, target, _version = lines[0].split(None, 2)
                except ValueError:
                    break
                headers = {}
                for ln in lines[1:]:
                    k, _, v = ln.partition(":")
                    if _:
                        headers[k.strip().lower()] = v.strip()
                body = b""
                clen = int(headers.get("content-length", "0") or 0)
                if clen:
                    body = await reader.readexactly(clen)
                res = await self.route(method, target, headers, body)
                status, payload = res[0], res[1]
                ctype = res[2] if len(res) > 2 else None
                extra = res[3] if len(res) > 3 else None
                data = payload.encode() if isinstance(payload, str) else payload
                if ctype is None:
                    ctype = ("text/html" if data[:2] in (b"<!", b"<h")
                             else "application/json")
                data, enc_hdrs = self._maybe_gzip(headers, status, ctype,
                                                  data)
                extra = {**(extra or {}), **enc_hdrs} if enc_hdrs else extra
                reason = {200: "OK", 304: "Not Modified"}.get(status,
                                                              "Error")
                head = (
                    f"HTTP/1.1 {status} {reason}\r\n"
                    f"Server: {SERVER_NAME}\r\n"
                    f"Content-Type: {ctype}\r\n"
                    f"Content-Length: {len(data)}\r\n"
                    + "".join(f"{k}: {v}\r\n"
                              for k, v in (extra or {}).items())
                    + "Connection: keep-alive\r\n\r\n").encode()
                if (status == 200 and data
                        and target.split("?")[0].lower()
                        .startswith("/hls/")):
                    # HLS bodies ride the stream-egress rung ladder
                    # (ISSUE 14): header + body written separately so
                    # the segment bytes are never concatenated into a
                    # per-request copy
                    self._stream_body(writer, head, data)
                else:
                    writer.write(head)
                    if data:
                        writer.write(data)
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError,
                asyncio.LimitOverrunError):
            pass
        finally:
            writer.close()

    # ---------------------------------------------------------------- auth
    def _authorized(self, headers: dict, params: dict) -> bool:
        if not self.config.auth_enabled:
            return True
        token = (params.get("token", [None])[0]
                 or headers.get("x-token"))
        if token in self.tokens:
            return True
        auth = headers.get("authorization", "")
        if auth.lower().startswith("basic "):
            try:
                user, _, pw = base64.b64decode(auth[6:]).decode().partition(":")
                return (user == self.config.rest_username
                        and pw == self.config.rest_password)
            except Exception:
                return False
        return False

    # --------------------------------------------------------------- route
    async def route(self, method: str, target: str, headers: dict,
                    body: bytes) -> tuple[int, str]:
        url = urlparse(target)
        path = url.path.rstrip("/").lower()
        params = parse_qs(url.query)
        if path == "/stats":
            return 200, self._webstats_html()
        if path == "/metrics":
            # Prometheus scrape: unauthenticated read-only exposition,
            # same trust level as /stats
            from .. import obs
            return (200, obs.REGISTRY.expose(),
                    "text/plain; version=0.0.4; charset=utf-8")
        if path == "/debug/profile":
            # span-ring flamegraph as a gzipped pprof Profile proto
            # (`go tool pprof http://host:port/debug/profile` /
            # speedscope); aggregation happens at request time, same
            # read-only trust level as /metrics
            from ..obs import build_pprof
            return 200, build_pprof(), "application/octet-stream"
        if path == "/admin":
            if not self._authorized(headers, params):
                return 401, "<h1>401</h1>"
            if method == "POST" and body:
                params = {**params, **parse_qs(body.decode("utf-8",
                                                           "replace"))}
            return self._admin_html(params, method, headers)
        if path.startswith("/hls/") and self.app.hls is not None:
            served = self.app.hls.serve(url.path)
            if served is None:
                return 404, json.dumps({"error": "not found"})
            ctype, data, etag = served
            if etag is not None:
                if headers.get("if-none-match") == etag:
                    # revalidation short-circuit: a player polling the
                    # playlist (or re-fetching an immutable segment)
                    # costs a header round-trip, zero body bytes
                    self.hls_not_modified += 1
                    return 304, b"", ctype, {"ETag": etag}
                return 200, data, ctype, {"ETag": etag}
            return 200, data, ctype
        if not path.startswith("/api/v1/"):
            return 404, json.dumps({"error": "not found"})
        cmd = path[len("/api/v1/"):]
        if "x-token" in headers and "token" not in params:
            params["token"] = [headers["x-token"]]
        if cmd == "login":
            return self._login(params, headers)
        if not self._authorized(headers, params):
            return 401, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_UNAUTHORIZED)
        # per-session trace retrieval: GET /api/v1/sessions/<id>/trace
        # (the flight recorder's REST face; raw JSON, not the envelope,
        # so operators can pipe it straight to jq / a file).  Under
        # cluster mode the document is STITCHED (ISSUE 15): the local
        # hop plus every upstream hop of the stream's relay tree,
        # fetched through the peers' /api/v1/streamtrace endpoints —
        # ``local=1`` skips the stitch (the inter-node fetch uses it).
        m = _SESSION_TRACE_RE.match(cmd)
        if m is not None:
            from . import admin
            status, doc = admin.flight_query(self.app, m.group(1))
            if status == 200 and params.get("local", ["0"])[0] \
                    not in ("1", "true"):
                from ..obs import fleet
                try:
                    doc = await fleet.stitch_trace(self.app, doc)
                except Exception:
                    pass            # the local document still answers
            return status, json.dumps(doc, default=str), "application/json"
        if self.config.auth_enabled and self._mutates(cmd, params) \
                and headers.get("x-token") not in self.tokens:
            # CSRF altitude guard on the STATE CHANGE itself, not just
            # the HTML form: cached Basic creds (or a leaked query-string
            # token) ride any cross-site GET/POST, but a custom header
            # cannot cross origins without a CORS preflight this server
            # never grants.  Mutating commands therefore demand a login
            # token sent via the X-Token HEADER.
            return 403, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_UNAUTHORIZED,
                               body={"Detail":
                                     "mutating API calls need the X-Token "
                                     "header (see /api/v1/login)"})
        fn = getattr(self, f"_cmd_{cmd}", None)
        if fn is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        return await fn(params, body) if asyncio.iscoroutinefunction(fn) \
            else fn(params, body)

    #: API commands that change server state (everything not a pure read)
    _MUTATING = frozenset((
        "setbaseconfig", "restart", "startrecord", "stoprecord",
        "startpullrelay", "stoppullrelay", "starttranscode",
        "stoptranscode", "starthls", "stophls", "logout"))

    def _mutates(self, cmd: str, params: dict) -> bool:
        if cmd in self._MUTATING:
            return True
        return (cmd == "admin"
                and params.get("command", ["get"])[0].lower() == "set")

    def _login(self, params: dict, headers: dict) -> tuple[int, str]:
        user = params.get("username", [""])[0]
        pw = params.get("password", [""])[0]
        if (self.config.auth_enabled
                and (user != self.config.rest_username
                     or pw != self.config.rest_password)):
            return 401, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_UNAUTHORIZED)
        token = secrets.token_hex(16)
        self.tokens.add(token)
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK,
                           body={"Token": token})

    def _cmd_logout(self, params: dict, body: bytes) -> tuple[int, str]:
        # route() folds an X-Token header into params["token"], so a
        # header-only logout (the convention the mutation guard demands)
        # revokes that token rather than silently discarding nothing
        token = params.get("token", [""])[0]
        self.tokens.discard(token)
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK)

    def _cmd_profile(self, params: dict, body: bytes) -> tuple[int, str, str]:
        """GET /api/v1/profile — the phase profiler's live snapshot
        (same document as admin command=top; raw JSON, not the
        envelope, so it pipes straight to jq)."""
        from . import admin
        return (200, json.dumps(admin.profile_snapshot(self.app),
                                default=str), "application/json")

    async def _cmd_devicecheck(self, params: dict,
                               body: bytes) -> tuple[int, str, str]:
        """GET /api/v1/devicecheck — compile and run every served jitted
        step on the device this process holds, each against its host
        oracle (server/devicecheck.py).  Runs on a helper thread: a cold
        compile takes seconds and the pump must keep relaying.  Raw
        JSON; 200 with ``"ok": false`` when a step diverged or was
        refused by the compiler."""
        from . import devicecheck
        try:
            seed = int(params.get("seed", ["0"])[0])
        except ValueError:
            seed = 0
        doc = await asyncio.to_thread(devicecheck.run, seed)
        return 200, json.dumps(doc), "application/json"

    def _cmd_ledger(self, params: dict,
                    body: bytes) -> tuple[int, str, str]:
        """GET /api/v1/ledger — the wake-loop ledger's live snapshot
        (ISSUE 16): per-work-class wait/service aggregates, deferred
        counts, worst-wait trace correlation, and the cluster tick's
        Redis roundtrip sub-accounting.  Raw JSON (same pipe-to-jq
        convention as /api/v1/profile); ``tools/blame_report.py`` and
        the soak post-mortems read exactly this document."""
        from . import admin
        return (200, json.dumps(admin.ledger_snapshot(self.app),
                                default=str), "application/json")

    def _cmd_audience(self, params: dict,
                      body: bytes) -> tuple[int, str, str]:
        """GET /api/v1/audience — the columnar per-subscriber QoE
        store's drill-down (ISSUE 18): per-stream rollup + worst-N
        subscribers (``?n=`` overrides the default 5).  Raw JSON for
        jq pipelines; the composed soak's viewer-experience gate and
        ``tools/blame_report.py`` read exactly this document."""
        from . import admin
        try:
            n = int(params.get("n", ["5"])[0])
        except ValueError:
            n = 5
        return (200, json.dumps(
            admin.audience_snapshot(self.app, worst_n=max(0, min(n, 100))),
            default=str), "application/json")

    def _cmd_fleet(self, params: dict,
                   body: bytes) -> tuple[int, str, str]:
        """GET /api/v1/fleet — the aggregated cluster topology (ISSUE
        15): every node's latest rollup with liveness/staleness
        verdicts, served from the cluster tick's cache (a read never
        waits on Redis).  Standalone servers answer a single-node
        fleet of the same shape.  Raw JSON for jq pipelines."""
        from ..obs import fleet
        return (200, json.dumps(fleet.fleet_snapshot(self.app),
                                default=str), "application/json")

    def _cmd_streamtrace(self, params: dict,
                         body: bytes) -> tuple[int, str, str] | tuple[int, str]:
        """GET /api/v1/streamtrace?path= — this node's single hop of a
        stream's stitched trace (trace id, lineage, freshness chain,
        trace-tagged spans/events, the upstream node when pulled).
        This is the inter-node stitching wire the sessions/<id>/trace
        endpoint follows hop by hop."""
        from ..obs import fleet
        path = params.get("path", [""])[0]
        if not path:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST)
        doc = fleet.local_hop_doc(self.app, path)
        status = 404 if doc.get("error") else 200
        return status, json.dumps(doc, default=str), "application/json"

    @staticmethod
    def _page_params(params: dict) -> tuple[int, int | None]:
        """The ONE parser for the event log's (n, since) paging query —
        /api/v1/events and admin command=events must never drift on
        cursor semantics."""
        try:
            n = int(params.get("n", ["256"])[0])
        except ValueError:
            n = 256
        since = None
        try:
            if "since" in params:
                since = int(params["since"][0])
        except ValueError:
            since = None
        return n, since

    def _cmd_events(self, params: dict,
                    body: bytes) -> tuple[int, str, str]:
        """GET /api/v1/events?n=&since= — the structured event log as
        NDJSON.  Every record carries a monotonic per-process ``seq``;
        a federating scraper pages with ``since=<last seq seen>``
        (oldest-first pages, so a scraper far behind catches up through
        the ring) and COUNTS gaps from the seq jumps (plus
        events_dropped_total) instead of silently missing ring
        evictions."""
        from ..obs import EVENTS
        n, since = self._page_params(params)
        lines = EVENTS.dump_lines(n, since)
        return (200, "\n".join(lines) + ("\n" if lines else ""),
                "application/x-ndjson")

    def _cmd_getserverinfo(self, params: dict, body: bytes) -> tuple[int, str]:
        st = self.app.server_info()
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body=st)

    def _cmd_getrtsplivesessions(self, params: dict,
                                 body: bytes) -> tuple[int, str]:
        sessions = self.app.live_sessions()
        return 200, ep.ack(ep.MSG_SC_RTSP_LIVE_SESSIONS_ACK, body={
            "SessionCount": str(len(sessions)), "Sessions": sessions})

    def _cmd_getbaseconfig(self, params: dict, body: bytes) -> tuple[int, str]:
        cfg = {k: v for k, v in self.config.to_dict().items()
               if k != "rest_password"}
        return 200, ep.ack(ep.MSG_SC_BASE_CONFIG_ACK, body={"Config": cfg})

    def _cmd_setbaseconfig(self, params: dict, body: bytes) -> tuple[int, str]:
        try:
            doc = json.loads(body or b"{}")
            changes = doc.get("Config", doc) if isinstance(doc, dict) else {}
            self.config.update(**changes)
        except (json.JSONDecodeError, KeyError, TypeError, ValueError) as e:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST,
                               body={"Detail": str(e)})
        return 200, ep.ack(ep.MSG_SC_BASE_CONFIG_ACK)

    def _cmd_restart(self, params: dict, body: bytes) -> tuple[int, str]:
        self.app.request_restart()
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={"Restarting": "1"})

    def _cmd_getdevicestream(self, params: dict,
                             body: bytes) -> tuple[int, str]:
        """Start/locate a device stream (cloud mode: asks CMS; standalone:
        answers the local RTSP url if the path is live)."""
        device = params.get("device", params.get("serial", [""]))[0]
        if not device:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST)
        url = self.app.device_stream_url(device)
        if url is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION,
                               error=ep.ERR_DEVICE_OFFLINE)
        return 200, ep.ack(ep.MSG_SC_GET_STREAM_ACK, body={"URL": url})

    _cmd_livedevicestream = _cmd_getdevicestream

    def _cmd_startrecord(self, params: dict, body: bytes) -> tuple[int, str]:
        """Attach an MP4 recorder to a live session (RtspRecordModule);
        with the DVR tier on, also arm the window spiller (ISSUE 12) so
        stop leaves BOTH an MP4 and an instantly-servable packed asset."""
        path = params.get("path", [""])[0]
        sess = self.app.registry.find(path) if path else None
        if sess is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        import os
        from ..utils.paths import confined_subpath
        fname = params.get("file", [""])[0] or (
            sess.path.strip("/").replace("/", "_")
            + time.strftime("_%Y%m%d%H%M%S") + ".mp4")
        root = self.config.movie_folder
        os.makedirs(root, exist_ok=True)
        # confinement is commonpath-over-realpaths (utils/paths), the
        # one test that rejects ALL the escape classes: `..` traversal,
        # a sibling folder sharing the prefix string, and a symlink
        # inside movie_folder pointing outside it
        full = confined_subpath(root, fname)
        if full is None:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST,
                               body={"Detail": "file escapes movie_folder"})
        os.makedirs(os.path.dirname(full), exist_ok=True)
        try:
            self.app.recordings.start(sess, full)
        except ValueError as e:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST,
                               body={"Detail": str(e)})
        dvr_armed = False
        if self.app.dvr is not None:
            sdp = self.app.registry.sdp_cache.get(sess.path) or ""
            dvr_armed = self.app.dvr.arm(sess, sdp) or \
                self.app.dvr.armed(sess.path)
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK,
                           body={"Recording": sess.path, "File": full,
                                 "Dvr": "1" if dvr_armed else "0"})

    def _cmd_stoprecord(self, params: dict, body: bytes) -> tuple[int, str]:
        path = params.get("path", [""])[0]
        dvr_res = (self.app.dvr.finalize(path)
                   if self.app.dvr is not None else None)
        try:
            res = self.app.recordings.stop(path)
        except KeyError:
            if dvr_res is None:
                return 404, ep.ack(ep.MSG_SC_EXCEPTION,
                                   error=ep.ERR_NOT_FOUND)
            # DVR-only recording (armed at RECORD time, no MP4 sink)
            return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={
                "DvrWindows": str(dvr_res["windows"])})
        extra = ({"DvrWindows": str(dvr_res["windows"])}
                 if dvr_res is not None else {})
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={
            "File": res["path"], "Samples": str(res["samples"]), **extra})

    def _cmd_dvrwindow(self, params: dict,
                       body: bytes) -> tuple[int, object, str] | tuple[int, str]:
        """GET /api/v1/dvrwindow?path=&track=&win= — one spilled window's
        raw blob bytes, exactly as the spill file stores them.  This is
        the cluster peer-fill wire: node B time-shifting a stream node A
        recorded block-fills from A's spill files through here instead
        of hitting origin (the fetch side is ``app._dvr_peer_fetch``)."""
        if self.app.dvr is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        path = params.get("path", [""])[0]
        try:
            track = int(params.get("track", [""])[0])
            win = int(params.get("win", [""])[0])
        except ValueError:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST)
        blob = self.app.dvr.window_blob(path, track, win)
        if blob is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        return 200, blob, "application/octet-stream"

    def _cmd_dvrmeta(self, params: dict,
                     body: bytes) -> tuple[int, str] | tuple[int, str, str]:
        """GET /api/v1/dvrmeta?path= — an asset's meta + per-track spill
        index documents (ISSUE 13 satellite).  This is the bootstrap
        half of cluster peer-fill: a node that never saw the stream
        materializes these documents locally (``DvrManager.materialize``)
        and then block-fills every window through ``/api/v1/dvrwindow``
        — a fully-remote ``.dvr`` asset replays anywhere the cluster
        routes a subscriber."""
        if self.app.dvr is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        path = params.get("path", [""])[0]
        doc = self.app.dvr.meta_doc(path) if path else None
        if doc is None and path \
                and getattr(self.app, "storage", None) is not None:
            # erasure-tier fallback (ISSUE 20): the recording node is
            # gone, but the asset's DVR documents ride every shard
            # manifest — ANY surviving shard holder answers the
            # bootstrap sweep, so a fully-remote asset replays even
            # with its owner dead
            doc = self.app.storage.meta_doc(path)
        if doc is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        return 200, json.dumps(doc, separators=(",", ":")), \
            "application/json"

    # -- erasure storage wire (ISSUE 20) -----------------------------------
    def _cmd_shard(self, params: dict,
                   body: bytes) -> tuple[int, object, str] | tuple[int, str]:
        """GET /api/v1/shard?path=&name= — one local erasure shard's
        payload (crc-verified against the manifest before it ships; a
        corrupt local copy 404s and self-queues repair)."""
        st = getattr(self.app, "storage", None)
        if st is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        path = params.get("path", [""])[0]
        name = params.get("name", [""])[0]
        if not path or not name:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST)
        payload = st.serve_shard(path, name)
        if payload is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        return 200, payload, "application/octet-stream"

    def _cmd_shardmeta(self, params: dict,
                       body: bytes) -> tuple[int, str] | tuple[int, str, str]:
        """GET /api/v1/shardmeta?path= — the asset's shard manifest
        (stripe geometry, per-shard crc32s, holder map, embedded DVR
        documents)."""
        st = getattr(self.app, "storage", None)
        if st is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        path = params.get("path", [""])[0]
        man = st.manifest(path) if path else None
        if man is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        return 200, json.dumps(man, separators=(",", ":")), \
            "application/json"

    def _cmd_shardpush(self, params: dict,
                       body: bytes) -> tuple[int, str]:
        """POST /api/v1/shardpush?path=&name= — a peer placing one shard
        here at store/repair time; the body is ``manifest-json\\n\\n``
        followed by the raw payload.  Not in _MUTATING: the push rides
        Basic auth like every peer call, and the payload is fenced by
        the manifest crc32 — a corrupt or cross-gen push is refused, so
        the CSRF login-token dance would only couple node bring-up
        order."""
        st = getattr(self.app, "storage", None)
        if st is None:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        path = params.get("path", [""])[0]
        name = params.get("name", [""])[0]
        sep = body.find(b"\n\n")
        if not path or not name or sep < 0:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST)
        try:
            man = json.loads(body[:sep]) if sep > 0 else None
        except ValueError:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST)
        if not st.receive_shard(path, name, body[sep + 2:], man):
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST,
                               body={"Detail": "shard refused (crc/gen)"})
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={
            "Shard": name})

    def _cmd_storagestats(self, params: dict,
                          body: bytes) -> tuple[int, str, str]:
        """GET /api/v1/storagestats — the storage tier's counters plus
        the zero-repack witness (``vod.cache.pack_window.calls``): the
        cluster soak reads this on every survivor after the holder
        kill to assert shards reconstructed with no repacketization
        and no scrub errors."""
        from ..vod.cache import pack_window
        st = getattr(self.app, "storage", None)
        doc: dict = {"enabled": st is not None,
                     "pack_window_calls": int(pack_window.calls)}
        if st is not None:
            doc.update(st.stats())
        return 200, json.dumps(doc, separators=(",", ":")), \
            "application/json"

    async def _cmd_startpullrelay(self, params: dict,
                                  body: bytes) -> tuple[int, str]:
        """Pull a remote rtsp:// stream into a local path (EasyRelaySession
        direction: server chains act as players toward upstreams)."""
        from ..relay.pull import PullError
        url = params.get("url", [""])[0]
        path = params.get("path", [""])[0]
        if not url or not path:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST,
                               body={"Detail": "need url= and path="})
        try:
            pull = await self.app.pulls.start_pull(path, url)
        except PullError as e:
            return 502, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST,
                               body={"Detail": str(e)})
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={
            "Pull": pull.local_path, "Url": pull.url})

    async def _cmd_stoppullrelay(self, params: dict,
                                 body: bytes) -> tuple[int, str]:
        path = params.get("path", [""])[0]
        try:
            st = await self.app.pulls.stop_pull(path)
        except KeyError:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={
            "Pull": st["path"], "Packets": str(st["packets"])})

    def _cmd_getpullrelays(self, params: dict, body: bytes) -> tuple[int, str]:
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={
            "Pulls": self.app.pulls.list_pulls()})

    def _cmd_starttranscode(self, params: dict,
                            body: bytes) -> tuple[int, str]:
        """Start an on-TPU MJPEG bitrate ladder on a live path; the rungs
        appear as {path}@q{Q} live streams."""
        path = params.get("path", [""])[0]
        rungs = tuple(q for q in
                      params.get("rungs", ["40,20"])[0].split(",") if q)
        try:
            out = self.app.transcodes.start(path, rungs)
        except KeyError:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        except ValueError as e:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST,
                               body={"Detail": str(e)})
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={
            "Transcode": out.source_path,
            "Rungs": [r.session.path for r in out.rungs]})

    def _cmd_stoptranscode(self, params: dict,
                           body: bytes) -> tuple[int, str]:
        path = params.get("path", [""])[0]
        try:
            st = self.app.transcodes.stop(path)
        except KeyError:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={
            "Transcode": st["path"], "FramesIn": str(st["frames_in"])})

    def _cmd_gettranscodes(self, params: dict,
                           body: bytes) -> tuple[int, str]:
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={
            "Transcodes": self.app.transcodes.list_ladders()})

    def _cmd_starthls(self, params: dict, body: bytes) -> tuple[int, str]:
        """Publish a live path over HLS with a temporal rendition ladder
        (config-5 mux): one call → multi-rendition master.m3u8."""
        from ..hls.segmenter import DEFAULT_RUNGS
        from ..protocol.sdp import _norm
        path = params.get("path", [""])[0]
        rungs_raw = params.get("rungs", [""])[0]
        try:
            rungs = (tuple(r if r.startswith("q") else int(r)
                           for r in rungs_raw.split(",") if r)
                     if rungs_raw else DEFAULT_RUNGS)
            self.app.hls.start(path, rungs)
        except KeyError:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        except ValueError as e:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST,
                               error_string=str(e))
        key = _norm(path)
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={
            "Master": f"/hls{key}/master.m3u8",
            "Renditions": ["index.m3u8"]
            + [(f"{r}/index.m3u8" if isinstance(r, str)
                else f"r{int(r)}/index.m3u8") for r in rungs]})

    def _cmd_stophls(self, params: dict, body: bytes) -> tuple[int, str]:
        from ..protocol.sdp import _norm
        path = params.get("path", [""])[0]
        key = _norm(path)
        if key not in self.app.hls.outputs:
            return 404, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND)
        self.app.hls.stop(path)
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={"Hls": key})

    def _cmd_gethlsstreams(self, params: dict,
                           body: bytes) -> tuple[int, str]:
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK, body={
            "Streams": self.app.hls.list_streams()})

    def _cmd_admin(self, params: dict, body: bytes) -> tuple[int, str]:
        """Dictionary-tree browse (QTSSAdminModule's /modules/admin API):
        ``?path=server/prefs/*&command=get[&recurse=1]`` or
        ``?path=server/prefs/<name>&command=set&value=...``."""
        from . import admin
        path = params.get("path", ["server/*"])[0]
        command = params.get("command", ["get"])[0].lower()
        if command == "trace":
            # span-ring dump: the raw Chrome trace-event document (NOT
            # envelope-wrapped) so chrome://tracing / Perfetto load the
            # response body directly
            from ..obs import TRACER
            return 200, json.dumps(TRACER.dump()), "application/json"
        if command == "flight":
            # per-session black box (live ring or stored dump) — raw
            # JSON for the same pipe-to-jq reason as command=trace
            status, doc = admin.flight_query(
                self.app, params.get("session", [""])[0])
            return status, json.dumps(doc, default=str), "application/json"
        if command == "events":
            # structured event log as JSON lines; since=<seq> pages
            # from a cursor exactly like /api/v1/events (one parser)
            from ..obs import EVENTS
            n, since = self._page_params(params)
            lines = EVENTS.dump_lines(n, since)
            return (200, "\n".join(lines) + ("\n" if lines else ""),
                    "application/x-ndjson")
        if command == "fleet":
            # aggregated cluster topology (ISSUE 15) — raw JSON for the
            # same pipe-to-jq reason as command=trace
            from ..obs import fleet
            return (200, json.dumps(fleet.fleet_snapshot(self.app),
                                    default=str), "application/json")
        if command == "top":
            # live phase/session attribution snapshot (raw JSON for the
            # same pipe-to-jq reason as command=trace)
            return (200, json.dumps(admin.profile_snapshot(self.app),
                                    default=str), "application/json")
        if command == "blame":
            # the wake ledger's "why is p99 high" decomposition (ISSUE
            # 16): per-class wait/service attribution ranked by blame,
            # with cross-node suspect flags — raw JSON for jq
            return (200, json.dumps(admin.blame_snapshot(self.app),
                                    default=str), "application/json")
        if command == "audience":
            # the audience observatory's per-subscriber QoE drill-down
            # (ISSUE 18) — raw JSON for the same pipe-to-jq reason;
            # honors the same ?n= worst-N clamp as /api/v1/audience
            return self._cmd_audience(params, b"")
        if command == "set":
            status, payload = admin.set_pref(
                self.app, path, params.get("value", [""])[0])
        elif command == "get":
            recurse = params.get("recurse", ["0"])[0] in ("1", "true")
            status, payload = admin.query(self.app, path, recurse=recurse)
        else:
            return 400, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_BAD_REQUEST,
                               body={"Detail": f"unknown command {command}"})
        if status != 200:
            return status, ep.ack(ep.MSG_SC_EXCEPTION, error=ep.ERR_NOT_FOUND
                                  if status == 404 else ep.ERR_BAD_REQUEST,
                                  body=payload)
        return 200, ep.ack(ep.MSG_SC_SERVER_INFO_ACK,
                           body={"Path": path, "Value": payload})

    def _admin_html(self, params: dict, method: str = "GET",
                    headers: dict | None = None) -> tuple[int, str, str]:
        """HTML front-end over the admin dictionary tree — the mongoose
        web-admin role (``QTSSAdminModule.cpp:365`` served HTML over the
        same get/set query API): navigable containers, leaf values, and
        an inline set form for ``server/prefs/*``."""
        import html as _html
        from urllib.parse import quote

        from . import admin
        path = params.get("path", ["server/*"])[0]
        msg = ""
        if params.get("command", [""])[0].lower() == "set":
            if method != "POST":
                # a state-changing set must not ride an idempotent GET
                # (link prefetchers, refresh, cross-site <img> CSRF)
                msg = "<p class=err>set requires POST</p>"
            elif (not secrets.compare_digest(
                        params.get("csrf", [""])[0].encode("utf-8"),
                        self._admin_csrf.encode("ascii"))
                    and (headers or {}).get("x-token") not in self.tokens):
                # bytes, not str: compare_digest raises on non-ASCII str
                # input, and the csrf field is attacker-supplied
                # cross-site form POSTs ride cached Basic creds; demand
                # proof the caller read this page (embedded token) or
                # holds an API token sent via a header a form can't set
                msg = "<p class=err>set requires the page CSRF token</p>"
            else:
                st, payload = admin.set_pref(self.app, path.rstrip("/*"),
                                             params.get("value", [""])[0])
                msg = ("<p class=ok>set ok</p>" if st == 200 else
                       f"<p class=err>{_html.escape(str(payload))}</p>")
            path = "server/prefs/*"
        status, payload = admin.query(self.app, path)
        crumbs = []
        acc = []
        for part in [p for p in path.strip("/").split("/") if p != "*"]:
            acc.append(part)
            href = quote("/".join(acc), safe="/") + "/*"
            crumbs.append(f'<a href="/admin?path={quote(href, safe="/*")}"'
                          f">{_html.escape(part)}</a>")
        rows = []
        if status != 200:
            rows.append(f"<tr><td colspan=2 class=err>"
                        f"{_html.escape(str(payload))}</td></tr>")
        elif isinstance(payload, dict):
            base = path.strip("/").rstrip("*").rstrip("/")
            for k in sorted(payload):
                v = payload[k]
                if isinstance(v, dict) or v == "*container*":
                    href = quote(f"{base}/{k}", safe="/") + "/*"
                    rows.append(
                        f'<tr><td><a href="/admin?path='
                        f'{quote(href, safe="/*")}">'
                        f"{_html.escape(str(k))}/</a></td><td></td></tr>")
                else:
                    cell = _html.escape(str(v))
                    if base == "server/prefs":
                        cell += (f'<form method=post action=/admin '
                                 f'style="display:inline">'
                                 f'<input type=hidden name=path value='
                                 f'"server/prefs/{_html.escape(str(k))}">'
                                 f'<input type=hidden name=command '
                                 f'value=set>'
                                 f'<input type=hidden name=csrf value='
                                 f'"{self._admin_csrf}">'
                                 f'<input name=value size=12> '
                                 f'<input type=submit value=set></form>')
                    rows.append(f"<tr><td>{_html.escape(str(k))}</td>"
                                f"<td>{cell}</td></tr>")
        else:
            rows.append(f"<tr><td>{_html.escape(path)}</td>"
                        f"<td>{_html.escape(str(payload))}</td></tr>")
        body = ("<!doctype html><html><head><title>easydarwin-tpu admin"
                "</title><style>body{font-family:monospace;margin:2em}"
                "table{border-collapse:collapse}td{border:1px solid #ccc;"
                "padding:2px 8px}.err{color:#b00}.ok{color:#080}"
                "</style></head><body>"
                f"<h2><a href=\"/admin?path=server/*\">admin</a> "
                f"{' / '.join(crumbs)}</h2>{msg}"
                f"<table>{''.join(rows)}</table>"
                "<p><a href=/stats>stats</a></p></body></html>")
        return 200, body, "text/html"

    def _webstats_html(self) -> str:
        """HTML stats page (QTSSWebStatsModule.cpp:86-992 equivalent,
        served from the service port instead of RTSP-port HTTP GET)."""
        info = self.app.server_info()
        sessions = self.app.live_sessions()
        rows = "".join(
            f"<tr><td>{s['Path']}</td><td>{s['Outputs']}</td>"
            f"<td>{s['AgeSec']}s</td><td><code>{s['Url']}</code></td></tr>"
            for s in sessions)
        infos = "".join(f"<tr><td>{k}</td><td>{v}</td></tr>"
                        for k, v in info.items())
        return (
            "<!doctype html><html><head><title>easydarwin-tpu stats"
            "</title><style>body{font-family:monospace;margin:2em}"
            "table{border-collapse:collapse;margin:1em 0}"
            "td,th{border:1px solid #999;padding:4px 10px}</style></head>"
            f"<body><h1>easydarwin-tpu</h1><h2>Server</h2>"
            f"<table>{infos}</table>"
            f"<h2>Live sessions ({len(sessions)})</h2>"
            f"<table><tr><th>Path</th><th>Outputs</th><th>Age</th>"
            f"<th>URL</th></tr>{rows}</table></body></html>")
