"""Relay session: the per-source-path unit (``ReflectorSession``).

Built from a pushed (ANNOUNCE) or file-backed SDP; owns one ``RelayStream``
per media section, keyed by track id.  The registry keyed by path replaces
``sSessionMap`` (``QTSSReflectorModule.cpp:1379 FindOrCreateSession``).

Audio/video fast-start coupling: when a video stream records a fresh
keyframe, audio outputs that have not yet started are re-aligned so a late
joiner's audio starts with the video GOP rather than up to ``overbuffer_ms``
earlier (reference: audio bookmark resync on keyframe flag,
``ReflectorStream.cpp:1915-1934``).
"""

from __future__ import annotations

import secrets
import time
from dataclasses import dataclass, field

from ..obs import EVENTS
from ..protocol import sdp as sdp_mod
from .output import RelayOutput
from .stream import RelayStream, StreamSettings


def now_ms() -> int:
    return int(time.monotonic() * 1000)


class RelaySession:
    def __init__(self, path: str, description: sdp_mod.SessionDescription,
                 settings: StreamSettings | None = None):
        self.path = path
        self.description = description
        self.settings = settings or StreamSettings()
        #: correlation id carried on every engine-pass / native-egress
        #: span and lifecycle event of this source.  A feeder that owns a
        #: trace (ANNOUNCE pusher, pull relay) re-stamps via set_trace().
        self.trace_id = secrets.token_hex(8)
        #: node ids this stream's trace has lived on (ISSUE 15): grown
        #: by checkpoint restore/migration so a stitched trace names
        #: every server that ever carried the stream under this id
        self.trace_nodes: list[str] = []
        self.streams: dict[int, RelayStream] = {}
        for info in description.streams:
            self.streams[info.track_id] = RelayStream(info, self.settings)
        self.set_trace(self.trace_id)
        self.created_ms = now_ms()
        self.last_ingest_ms = self.created_ms
        self.pusher_alive = True
        #: the object feeding this session (RTSP pusher connection,
        #: PullRelay, BroadcastSource, transcode service) — identity-based
        #: ownership so teardown paths never remove a session something
        #: else has since taken over.  An ANNOUNCE on an existing path
        #: ADOPTS the session (find_or_create returns the same object), so
        #: `registry.find(p) is session` alone cannot detect takeover.
        self.owner: object | None = None

    def set_trace(self, trace_id: str) -> None:
        """Adopt the feeder's trace id and propagate it to every stream
        (the engine reads it off the stream when recording spans)."""
        self.trace_id = trace_id
        for st in self.streams.values():
            st.trace_id = trace_id
            st.session_path = self.path

    # -- ingest ------------------------------------------------------------
    def push(self, track_id: int, packet: bytes, *, is_rtcp: bool = False,
             t_ms: int | None = None) -> None:
        st = self.streams.get(track_id)
        if st is None:
            return
        t = now_ms() if t_ms is None else t_ms
        self.last_ingest_ms = t
        if is_rtcp:
            st.push_rtcp(packet, t)
        else:
            st.push_rtp(packet, t)
            # audio ↔ video GOP alignment for not-yet-started outputs
            self._kf_resync(st)

    def _kf_resync(self, st) -> None:
        if not st.has_keyframe_update:
            return
        st.has_keyframe_update = False
        for other in self.streams.values():
            if other is st or other.info.media_type != "audio":
                continue
            for out in other.outputs:
                if out.bookmark is None and len(other.rtp_ring):
                    out.bookmark = other.rtp_ring.head - 1

    def drain_native(self, track_id: int, fd: int,
                     max_pkts: int = 512) -> int:
        """Batch-ingest a pusher's RTP socket via the native recvmmsg
        drain (one syscall per 64 datagrams) with the same housekeeping
        as per-packet ``push``.  Returns packets admitted."""
        st = self.streams.get(track_id)
        if st is None:
            return 0
        t = now_ms()
        n = st.drain_rtp_native(fd, t, max_pkts)
        if n:
            self.last_ingest_ms = t
            self._kf_resync(st)
        return n

    # -- outputs -----------------------------------------------------------
    def add_output(self, track_id: int, output: RelayOutput) -> None:
        st = self.streams.get(track_id)
        if st is None:
            raise KeyError(f"no track {track_id} in {self.path}")
        st.add_output(output)

    def remove_output(self, track_id: int, output: RelayOutput) -> bool:
        st = self.streams.get(track_id)
        return st.remove_output(output) if st else False

    @property
    def num_outputs(self) -> int:
        return sum(s.num_outputs for s in self.streams.values())

    # -- fan-out + maintenance --------------------------------------------
    def reflect(self, t_ms: int | None = None) -> int:
        t = now_ms() if t_ms is None else t_ms
        return sum(s.reflect(t) for s in self.streams.values())

    def prune(self, t_ms: int | None = None) -> int:
        t = now_ms() if t_ms is None else t_ms
        return sum(s.prune(t) for s in self.streams.values())

    def stats(self) -> dict:
        return {
            "path": self.path,
            "outputs": self.num_outputs,
            "streams": {
                tid: {
                    "media": s.info.media_type, "codec": s.info.codec,
                    "packets_in": s.stats.packets_in,
                    "bytes_in": s.stats.bytes_in,
                    "packets_out": s.stats.packets_out,
                    "keyframes": s.stats.keyframes,
                    "queue": len(s.rtp_ring),
                    "oversize_dropped": s.rtp_ring.total_oversize,
                } for tid, s in self.streams.items()
            },
        }


class SessionMap(dict):
    """The registry's path → session map, with the ``generation`` the
    registry bumps on every session it adds or removes: the pump keeps
    its roster while it has not moved (``relay.pump.Pump.wake``)."""

    generation = 0


class SessionRegistry:
    """Path → RelaySession map (``sSessionMap`` / ``OSRefTable`` stand-in).
    ``find_or_create`` and ``remove`` are the map's only writers."""

    def __init__(self, settings: StreamSettings | None = None):
        self.settings = settings or StreamSettings()
        self.sessions: SessionMap[str, RelaySession] = SessionMap()
        self.sdp_cache = sdp_mod.SdpCache()

    def find(self, path: str) -> RelaySession | None:
        return self.sessions.get(sdp_mod._norm(path))

    def find_or_create(self, path: str, sdp_text: str) -> RelaySession:
        key = sdp_mod._norm(path)
        sess = self.sessions.get(key)
        if sess is None:
            sess = RelaySession(key, sdp_mod.parse(sdp_text), self.settings)
            self.sessions[key] = sess
            self._moved()
            self.sdp_cache.set(key, sdp_text)
            EVENTS.emit("session.create", stream=key,
                        trace_id=sess.trace_id, path=key,
                        streams=len(sess.streams))
        return sess

    def remove(self, path: str) -> None:
        key = sdp_mod._norm(path)
        sess = self.sessions.pop(key, None)
        self.sdp_cache.pop(key)
        if sess is not None:
            self._moved()
            EVENTS.emit("session.remove", stream=key,
                        trace_id=sess.trace_id, path=key)

    def _moved(self) -> None:
        """A session came or went: the next wake builds its roster anew
        (a map put in place of the registry's has no generation, and is
        walked every wake)."""
        if isinstance(self.sessions, SessionMap):
            self.sessions.generation += 1

    def paths(self) -> list[str]:
        return sorted(self.sessions)
