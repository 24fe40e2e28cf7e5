"""Paced VOD sessions + the VOD service hook for the RTSP server.

Two serving paths:

* ``FileSession`` — the reference-shaped cold path: one asyncio task per
  playing client with ``QTSSFileModule``'s ``SendPackets`` pull-pace-
  sleep structure (``QTSSFileModule.cpp:1489``); WouldBlock from an
  output retries the same packet on the next wake (bookmark semantics).
  Still used for Scale (timestamp-compressed) and meta-info sessions.
* ``PacedVodSession`` + ``VodPacerGroup`` — the ISSUE 10 hot path: each
  subscriber-track is a first-class ``RelayStream`` whose ring the
  shared group pacer fills from the device-resident segment cache
  (``vod/cache.py``) in vectorized block copies, with per-packet due
  times stamped into the ring's ``arrival`` clock so the live engines'
  existing eligibility gate IS the pacer.  The pump steps these streams
  through the same TpuFanoutEngine / megabatch scheduler as live relay
  — per-subscriber seq/ts/ssrc rewrite rides the content-independent
  affine machinery, oracle-checked at install.  A cache miss streams
  through the cold per-sample mmap path into the same ring while a
  background fill packs the window.
"""

from __future__ import annotations

import asyncio
import os
import time
from collections import deque

import numpy as np

from .. import obs
from ..protocol import rtp
from ..protocol.rtp_meta import FRAME_KEY, FRAME_P
from ..protocol.sdp import StreamInfo
from ..relay.quality import PacketFlags, ThinningFilter
from ..relay.output import RelayOutput, WriteResult
from ..relay.stream import RelayStream, StreamSettings
from ..utils.paths import under_root
from . import cache as cache_mod
from .cache import SegmentCache, StagedPacketRing
from .mp4 import Mp4Error, Mp4File
from .packetizer import (RTP_CLOCK_VIDEO, AacPacketizer, H264Packetizer,
                         sdp_for_file)
from ..protocol import sdp as sdp_mod

#: per-subscriber-track ring depth on the hot path — sized for the fill
#: lookahead (hundreds of ms), not the live relay's 4096-slot burst
#: absorber; 1024 slots x 2060 B keeps per-subscriber memory ~2 MB
VOD_RING_CAPACITY = 1024


class FileSession:
    """One playing client of one file: per-track packetizers + pacing."""

    def __init__(self, file: Mp4File, outputs: dict[int, RelayOutput],
                 *, start_npt: float = 0.0, speed: float = 1.0,
                 ts_scale: float = 1.0):
        self.file = file
        self.outputs = outputs
        self.speed = max(speed, 0.01)
        #: Scale support: RTP timestamps are divided by this so the media
        #: clock advances `ts_scale`× per wall second (RFC 2326 §12.34)
        self.ts_scale = max(ts_scale, 0.01)
        self._cursors: dict[int, int] = {}        # track_id -> sample index
        self._packetizers: dict[int, object] = {}
        #: deques, not lists: the send loop pops from the FRONT once per
        #: packet, and list.pop(0) is O(P) — O(P²) per fragmented sample
        self._pending: dict[int, deque[bytes]] = {}
        self._task: asyncio.Task | None = None
        self.packets_sent = 0
        #: frames shed by quality adaptation (RTPStream thinning on the
        #: VOD path: RR loss / NADU feedback raises the output's level,
        #: the pacer consults it per sample — graceful frame-drop
        #: instead of tail-drop, VERDICT r3 item 6)
        self.frames_thinned = 0
        self.done = False
        track_no = 0
        v = file.video_track()
        if v is not None:
            track_no += 1
            if track_no in outputs:
                out = outputs[track_no]
                self._packetizers[track_no] = H264Packetizer(
                    v, ssrc=out.rewrite.ssrc,
                    seq_start=out.rewrite.out_seq_start)
                self._cursors[track_no] = self._seek_index(v, start_npt)
                self._pending[track_no] = deque()
        a = file.audio_track()
        if a is not None:
            track_no += 1
            if track_no in outputs:
                out = outputs[track_no]
                self._packetizers[track_no] = AacPacketizer(
                    a, ssrc=out.rewrite.ssrc,
                    seq_start=out.rewrite.out_seq_start)
                self._cursors[track_no] = self._seek_index(a, start_npt)
                self._pending[track_no] = deque()
        self.start_npt = start_npt

    @staticmethod
    def _seek_index(track, npt: float) -> int:
        if npt <= 0 or track.n_samples == 0:
            return 0
        target = int(npt * track.info.timescale)
        import numpy as np
        i = int(np.searchsorted(track.dts, target))
        i = min(i, track.n_samples - 1)
        return track.sync_sample_at_or_before(i)

    # -- pull-pace loop ----------------------------------------------------
    def _track_of(self, track_id: int):
        p = self._packetizers[track_id]
        return p.track

    def _next_due(self) -> tuple[int | None, float]:
        """(track_id, npt seconds) of the earliest unsent sample."""
        best, best_t = None, float("inf")
        for tid, cur in self._cursors.items():
            tr = self._track_of(tid)
            if self._pending[tid]:
                t = self._pending_npt.get(tid, 0.0)
                if t < best_t:
                    best, best_t = tid, t
                continue
            if cur >= tr.n_samples:
                continue
            t = tr.sample_time_sec(cur)
            if t < best_t:
                best, best_t = tid, t
        return best, best_t

    #: SR cadence (RTPStream.cpp:1300 SR gen per RR interval; round 1's
    #: VOD path sent no SRs at all → no client A/V sync)
    SR_INTERVAL_SEC = 5.0

    def _clock_rate(self, tid: int) -> int:
        from .packetizer import AacPacketizer, RTP_CLOCK_VIDEO
        p = self._packetizers[tid]
        if isinstance(p, AacPacketizer):
            tr = p.track
            return tr.info.sample_rate or tr.info.timescale or 90000
        return RTP_CLOCK_VIDEO

    def _maybe_send_srs(self, now: float) -> None:
        """Originate SR+SDES per track every 5 s: ntp=now, rtp=the media
        timestamp playing at now (last sent ts extrapolated at the track
        clock, honoring Speed/Scale)."""
        from ..protocol import rtcp
        for tid, (last_ts, last_wall) in list(self._sr_ref.items()):
            if now - self._last_sr.get(tid, 0.0) < self.SR_INTERVAL_SEC:
                continue
            self._last_sr[tid] = now
            out = self.outputs[tid]
            rate = self._clock_rate(tid)
            rtp_now = int(last_ts + (now - last_wall) * rate
                          * self.speed / self.ts_scale) & 0xFFFFFFFF
            out.send_bytes(rtcp.build_server_compound(
                out.rewrite.ssrc, "easydarwin-tpu", unix_time=time.time(),
                rtp_ts=rtp_now, packet_count=self._sr_pkts.get(tid, 0),
                octet_count=self._sr_octets.get(tid, 0)), is_rtcp=True)

    async def run(self) -> None:
        t0 = time.monotonic() - self.start_npt / self.speed
        self._pending_npt: dict[int, float] = {}
        #: x-RTP-Meta-Info context: per-track running packet number and
        #: the current sample's (frame type, file position) — the
        #: packetizer context DSS fills ft/pn/pp from (RTPMetaInfoLib;
        #: VERDICT r3 item 9)
        self._meta_pn: dict[int, int] = {}
        self._pending_meta: dict[int, tuple[int | None, int]] = {}
        #: per track: (rtp_ts of newest sent packet, wall time it was sent)
        self._sr_ref: dict[int, tuple[int, float]] = {}
        self._last_sr: dict[int, float] = {}
        self._sr_pkts: dict[int, int] = {}
        self._sr_octets: dict[int, int] = {}
        while True:
            self._maybe_send_srs(time.monotonic())
            for o in self.outputs.values():
                tick = getattr(o, "tick", None)
                if tick is not None:      # reliable-UDP retransmit sweep
                    tick()
            tid, npt = self._next_due()
            if tid is None:
                self.done = True
                return
            due = t0 + npt / self.speed
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(min(delay, 0.5))
                continue
            if not self._pending[tid]:
                tr = self._track_of(tid)
                cur = self._cursors[tid]
                out0 = self.outputs[tid]
                if tr.info.handler == "vide" \
                        and not out0.thinning.passthrough():
                    flags = (PacketFlags.VIDEO | PacketFlags.FRAME_FIRST
                             | (PacketFlags.KEYFRAME_FIRST
                                if bool(tr.sync[cur]) else 0))
                    if not out0.thinning.admit(flags):
                        self._cursors[tid] = cur + 1
                        self.frames_thinned += 1
                        continue
                data = self.file.read_sample(tr, cur)
                if tr.info.handler == "vide":
                    ftype = FRAME_KEY if bool(tr.sync[cur]) else FRAME_P
                else:
                    ftype = None
                self._pending_meta[tid] = (ftype, int(tr.offsets[cur]))
                pkts = self._packetizers[tid].packetize_sample(data, cur)
                if self.ts_scale != 1.0:
                    pkts = [rtp.rewrite_header(
                        p, timestamp=int(rtp.peek_timestamp(p)
                                         / self.ts_scale) & 0xFFFFFFFF)
                        for p in pkts]
                self._pending[tid] = deque(pkts)
                self._pending_npt[tid] = npt
                self._cursors[tid] = cur + 1
            out = self.outputs[tid]
            q = self._pending[tid]
            last_sent = None
            while q:
                wire = q[0]
                if out.meta_field_ids is not None:
                    ftype, fpos = self._pending_meta.get(tid, (None, 0))
                    wire = out.wrap_meta(
                        wire[:12], wire[12:], frame_type=ftype,
                        packet_number=self._meta_pn.get(tid, 0),
                        packet_position=fpos)
                res = out.send_bytes(wire, is_rtcp=False)
                if res is WriteResult.WOULD_BLOCK:
                    await asyncio.sleep(0.02)      # bookmark: retry same pkt
                    break
                pkt = q.popleft()
                if res is WriteResult.OK:
                    out.packets_sent += 1
                    self.packets_sent += 1
                    self._meta_pn[tid] = self._meta_pn.get(tid, 0) + 1
                    last_sent = pkt
                    self._sr_pkts[tid] = self._sr_pkts.get(tid, 0) + 1
                    self._sr_octets[tid] = (self._sr_octets.get(tid, 0)
                                            + max(len(pkt) - 12, 0))
                elif res is WriteResult.ERROR:
                    self.done = True
                    return
            if last_sent is not None:   # once per sample, not per packet
                self._sr_ref[tid] = (rtp.peek_timestamp(last_sent),
                                     time.monotonic())

    def start(self) -> None:
        self._task = asyncio.create_task(self.run(), name="vod-session")

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None


class VodService:
    """Path → file resolution + SDP generation (the FileModule's Route +
    Describe roles).  Paths map under ``movie_folder``; '.sdp' suffixes and
    URL dots are normalized like the reference's path translation."""

    def __init__(self, movie_folder: str):
        self.movie_folder = movie_folder
        self._cache: dict[str, Mp4File] = {}

    def resolve(self, path: str) -> str | None:
        rel = path.lstrip("/")
        if rel.endswith(".sdp"):
            rel = rel[:-4]
        cand = os.path.normpath(os.path.join(self.movie_folder, rel))
        for p in (cand, cand + ".mp4", cand + ".mov", cand + ".m4v"):
            # traversal guard: commonpath over realpaths — the old
            # normpath-prefix startswith accepted sibling directories
            # sharing the prefix string (movies2/ under a movies/ root)
            # and symlinks inside the root pointing outside it
            if os.path.isfile(p) and under_root(self.movie_folder, p):
                return p
        return None

    def open(self, path: str) -> Mp4File | None:
        fp = self.resolve(path)
        if fp is None:
            return None
        try:
            from .mp4 import open_shared
            return open_shared(fp)
        except (Mp4Error, OSError):
            return None

    async def describe(self, path: str) -> str | None:
        f = self.open(path)
        if f is None:
            return None
        try:
            sd = sdp_for_file(f, name=os.path.basename(path))
            return sdp_mod.build(sd)
        finally:
            f.close()


# ======================================================================
# Hot path (ISSUE 10): cache-fed relay streams under a shared group pacer
# ======================================================================

class _VodEngineThinning(ThinningFilter):
    """Engine-facing thinning view of a pacer-served output.

    Fill-time thinning already removed shed frames from the ring (the
    cold path's per-sample semantics, applied by the pacer), so the
    engine must treat the output as passthrough — the native sendmmsg
    fast path stays eligible even while the subscriber is thinned —
    and must never re-filter.  RTCP feedback keeps flowing: the shared
    ``controller`` is the same object the pacer's fill filter reads."""

    def passthrough(self) -> bool:
        return True

    def admit(self, flags: int) -> bool:
        return True


class VodStream(RelayStream):
    """A paced VOD subscriber-track as a first-class relay stream: same
    ring/bucket/RTCP/bookmark machinery as live, fed by the group pacer
    instead of a network ingest — the unification that lets the pump,
    the engines and the megabatch scheduler treat both workloads
    identically."""

    def __init__(self, info: StreamInfo, settings: StreamSettings,
                 ring: StagedPacketRing):
        super().__init__(info, settings, rtp_ring=ring)


class _PacedTrack:
    """Per-(session, track) pacer state: cursor, seq runner, thinning
    fill filter, the pinned current cache window and the cold-miss
    packetizer."""

    def __init__(self, sess: "PacedVodSession", track_no: int, track,
                 out: RelayOutput, settings: StreamSettings,
                 start_npt: float):
        self.track_no = track_no
        self.track = track
        self.out = out
        self.is_video = track.info.handler == "vide"
        if self.is_video:
            clock = RTP_CLOCK_VIDEO
            info = StreamInfo(media_type="video", payload_type=96,
                              payload_name="H264/90000", codec="H264",
                              clock_rate=clock, track_id=track_no)
            self.packetizer = H264Packetizer(track, ssrc=0, seq_start=0,
                                             mtu=cache_mod.VOD_MTU)
            self.cursor = FileSession._seek_index(track, start_npt)
        else:
            clock = (track.info.sample_rate or track.info.timescale
                     or 90000)
            info = StreamInfo(media_type="audio", payload_type=97,
                              payload_name=f"MPEG4-GENERIC/{clock}",
                              codec="MPEG4-GENERIC", clock_rate=clock,
                              track_id=track_no)
            self.packetizer = AacPacketizer(track, ssrc=0, seq_start=0)
            self.cursor = FileSession._seek_index(track, start_npt)
        ring = StagedPacketRing(settings.ring_capacity,
                                is_video=self.is_video,
                                codec="H264" if self.is_video else None)
        self.stream = VodStream(info, settings, ring)
        self.stream.session_path = sess.path
        self.stream.audience_tier = "vod"
        # thinning split: the engine sees passthrough, the pacer thins
        # at fill with the cold path's per-sample semantics; both views
        # share the output's quality controller (RR/NADU feedback)
        self.orig_thinning = out.thinning
        out.thinning = _VodEngineThinning(
            controller=self.orig_thinning.controller)
        self.fill_filter = ThinningFilter(
            controller=self.orig_thinning.controller)
        # fresh serving state: the seq/ts rebase re-latches from the
        # first packet this session pushes (a re-PLAY restarts at
        # out_seq_start, matching the cold path's fresh packetizer)
        out.bookmark = 0
        out.rewrite.base_src_seq = -1
        out.rewrite.base_src_ts = -1
        self.seq_next = out.rewrite.out_seq_start & 0xFFFF
        self.ts_anchored = False
        self.samples_done = track.n_samples == 0
        self.window = None               # pinned current CachedWindow
        self.window_idx = -1
        self.released = False
        self.stream.add_output(out)

    # ------------------------------------------------------------- windows
    def _window_for(self, sess: "PacedVodSession", win_idx: int):
        c = sess.pacer.cache
        if self.window is not None:
            if self.window_idx == win_idx:
                return self.window
            c.unpin(self.window)
            self.window = None
        w = c.get(sess.file, self.track_no, self.track, win_idx)
        if w is not None:
            self.window = c.pin(w)
            self.window_idx = win_idx
        return w

    def _sample_flags(self, i: int) -> int:
        return (PacketFlags.VIDEO | PacketFlags.FRAME_FIRST
                | (PacketFlags.KEYFRAME_FIRST
                   if bool(self.track.sync[i]) else 0))

    def _anchor_ts(self, ts: int) -> None:
        # identity timestamp map: the rebase origin the engine latches
        # from the first pushed packet maps to itself, so wire ts equal
        # the cold packetizer's raw media timestamps byte-for-byte
        if not self.ts_anchored:
            self.out.rewrite.out_ts_start = int(ts) & 0xFFFFFFFF
            self.ts_anchored = True

    def _room(self) -> int:
        ring = self.stream.rtp_ring
        bm = self.out.bookmark
        base = ring.tail if bm is None else max(min(bm, ring.head),
                                                ring.tail)
        return ring.capacity - (ring.head - base) - 8

    # ---------------------------------------------------------------- fill
    def fill(self, sess: "PacedVodSession", now_ms: int,
             horizon_ms: float) -> None:
        track = self.track
        missed: set[int] = set()         # one cache lookup per window
        while not self.samples_done:     # per tick, hit or miss
            if sess._due_ms(track.sample_time_sec(self.cursor)) \
                    > horizon_ms:
                return
            if self._room() < 96:
                return                   # wait for the player to drain
            win_idx = sess.pacer.cache.window_of(self.cursor)
            w = (self.window if self.window is not None
                 and self.window_idx == win_idx else None)
            if w is None and win_idx not in missed:
                w = self._window_for(sess, win_idx)
                if w is None:
                    missed.add(win_idx)
            if w is not None:
                progressed = self._fill_hot(sess, w, horizon_ms)
            else:
                progressed = self._fill_cold(sess, horizon_ms)
            if not progressed:
                return
            if self.cursor >= track.n_samples:
                self.samples_done = True

    def _fill_hot(self, sess, w, horizon_ms: float) -> bool:
        """Vectorized block fill from a packed window: one fancy-index
        copy for the whole due span (plus a per-sample python walk only
        while thinning is active)."""
        ring = self.stream.rtp_ring
        room = self._room()
        lo_rel = self.cursor - w.lo
        dues = sess.t0_ms + w.sample_npt * (1000.0 / sess.speed)
        hi_rel = int(np.searchsorted(dues, horizon_ms, side="right"))
        hi_rel = min(max(hi_rel, lo_rel + 1), w.hi - w.lo)
        thinning = (self.is_video
                    and not self.fill_filter.passthrough())
        sel: list[tuple[int, int]] = []
        n_total = 0
        thinned = 0
        end_rel = lo_rel
        for s in range(lo_rel, hi_rel):
            p0, p1 = int(w.pkt_base[s]), int(w.pkt_base[s + 1])
            if p1 - p0 > ring.capacity - 8:
                # a sample larger than the whole ring can never be
                # block-served: drop it rather than stall the session
                # forever (cold FileSession delivery has no ring bound)
                end_rel = s + 1
                continue
            if n_total + (p1 - p0) > room:
                break
            if thinning and not ThinningFilter.admit(
                    self.fill_filter, self._sample_flags(w.lo + s)):
                end_rel = s + 1
                thinned += 1
                continue
            end_rel = s + 1
            if p1 > p0:
                if sel and sel[-1][1] == p0:
                    sel[-1] = (sel[-1][0], p1)   # extend contiguous run
                else:
                    sel.append((p0, p1))
                n_total += p1 - p0
        if end_rel == lo_rel:
            return False                 # first due sample did not fit
        if n_total:
            if len(sel) == 1:
                idx = np.arange(sel[0][0], sel[0][1])
            else:
                idx = np.concatenate([np.arange(a, b) for a, b in sel])
            self._anchor_ts(int(w.ts[idx[0]]))
            seqs = (self.seq_next + np.arange(n_total)) & 0xFFFF
            due_ms = sess.t0_ms + w.npt[idx] * (1000.0 / sess.speed)
            arrivals = due_ms.astype(np.int64)
            # latency stamps at each packet's DUE instant (clamped to
            # now for already-due fills): the ingest->wire histogram
            # then measures pacing delay, never the lookahead itself
            now_ns = time.perf_counter_ns()
            now_mono_ms = time.monotonic() * 1000.0
            due_ns = (now_ns + np.maximum(due_ms - now_mono_ms, 0.0)
                      * 1e6).astype(np.int64)
            ring.push_block(w.data[idx], w.length[idx], arrivals,
                            w.flags[idx], seqs, w.ts[idx],
                            arrival_ns=due_ns)
            self.seq_next = int((self.seq_next + n_total) & 0xFFFF)
            obs.VOD_PACKETS.inc(n_total, path="hot")
            sess.pacer.hot_pkts += n_total
        sess.frames_thinned += thinned
        self.cursor = w.lo + end_rel
        return True

    def _fill_cold(self, sess, horizon_ms: float,
                   max_samples: int = 16) -> bool:
        """Cache-miss path: per-sample mmap read + packetize into the
        SAME ring — the subscriber keeps streaming with cold-path cost
        while the background fill packs the window."""
        track = self.track
        ring = self.stream.rtp_ring
        progressed = False
        for _ in range(max_samples):
            if self.cursor >= track.n_samples:
                break
            i = self.cursor
            due = sess._due_ms(track.sample_time_sec(i))
            if due > horizon_ms:
                break
            if self.is_video and not self.fill_filter.passthrough() \
                    and not ThinningFilter.admit(
                        self.fill_filter, self._sample_flags(i)):
                self.cursor += 1
                sess.frames_thinned += 1
                progressed = True
                continue
            data = sess.file.read_sample(track, i)
            self.packetizer.state.seq = self.seq_next & 0xFFFF
            pkts = self.packetizer.packetize_sample(data, i)
            if len(pkts) > ring.capacity - 8:
                self.cursor += 1         # ring-sized sample: drop, never
                continue                 # stall (see _fill_hot)
            if len(pkts) > self._room():
                break
            if pkts:
                self._anchor_ts(rtp.peek_timestamp(pkts[0]))
            # due-instant latency stamp, same rule as the hot fill
            due_ns = (time.perf_counter_ns()
                      + max(due - time.monotonic() * 1000.0, 0.0) * 1e6)
            for p in pkts:
                pid = ring.push(p, int(due))
                if pid >= 0:
                    ring.arrival_ns[ring.slot(pid)] = int(due_ns)
            self.seq_next = (self.seq_next + len(pkts)) & 0xFFFF
            self.cursor += 1
            if pkts:
                obs.VOD_PACKETS.inc(len(pkts), path="cold")
                sess.pacer.cold_pkts += len(pkts)
            progressed = True
        return progressed

    # ------------------------------------------------------------- retire
    def drained(self) -> bool:
        ring = self.stream.rtp_ring
        if ring.head == 0:
            return self.samples_done
        bm = self.out.bookmark
        return self.samples_done and bm is not None and bm >= ring.head

    def release(self, pacer: "VodPacerGroup") -> None:
        if self.released:
            return
        self.released = True
        pacer.cache.unpin(self.window)
        self.window = None
        self.out.thinning = self.orig_thinning
        self.stream.remove_output(self.out)
        pacer.engine_drop(self.stream)


class PacedVodSession:
    """One playing client under the group pacer — the hot counterpart
    of ``FileSession`` with the same control surface (``speed``,
    ``ts_scale``, ``stop``, ``done``, ``packets_sent``,
    ``frames_thinned``)."""

    ts_scale = 1.0                       # Scale sessions stay cold

    def __init__(self, pacer: "VodPacerGroup", file: Mp4File,
                 outputs: dict[int, RelayOutput], *,
                 start_npt: float = 0.0, speed: float = 1.0,
                 path: str = "", now_ms: int | None = None):
        from .mp4 import open_shared
        self.pacer = pacer
        self.file = open_shared(file.path)   # own ref for fill reads
        self.speed = max(speed, 0.01)
        self.start_npt = start_npt
        self.path = path or os.path.basename(file.path)
        self.done = False
        self.stopped = False
        self.frames_thinned = 0
        t = int(time.monotonic() * 1000) if now_ms is None else now_ms
        self.t0_ms = t - start_npt * 1000.0 / self.speed
        self._pkts_base = {id(o): o.packets_sent
                           for o in outputs.values()}
        self.tracks: list[_PacedTrack] = []
        by_no = cache_mod.tracks_by_no(self.file)
        for track_no, out in outputs.items():
            tr = by_no.get(track_no)
            if tr is None:
                continue
            self.tracks.append(_PacedTrack(self, track_no, tr, out,
                                           pacer.settings, start_npt))
        pacer.cache.note_open(self.file)

    def _due_ms(self, npt_sec: float) -> float:
        return self.t0_ms + npt_sec * 1000.0 / self.speed

    @property
    def packets_sent(self) -> int:
        return sum(tr.out.packets_sent
                   - self._pkts_base.get(id(tr.out), 0)
                   for tr in self.tracks)

    def tick(self, now_ms: int) -> None:
        if self.stopped or self.done:
            return
        horizon = now_ms + self.pacer.lookahead_ms
        done = True
        for tr in self.tracks:
            tr.fill(self, now_ms, horizon)
            if not tr.drained():
                done = False
        self.done = done

    def start(self) -> None:            # FileSession API parity: the
        pass                            # pacer drives, nothing to spawn

    def stop(self) -> None:
        self.pacer.retire(self)


class VodPacerGroup:
    """The shared group pacer: owns every hot VOD session, fills their
    rings once per pump wake and hands (stream, engine) pairs back to
    the pump so VOD subscribers ride the exact live serving path —
    including the cross-stream megabatch scheduler."""

    def __init__(self, cache: SegmentCache, *, engine_for=None,
                 engine_drop=None, scheduler=None,
                 settings: StreamSettings | None = None,
                 lookahead_ms: int = 500, device_prime: bool = True):
        import dataclasses
        st = settings or StreamSettings()
        if st.ring_capacity > VOD_RING_CAPACITY:
            st = dataclasses.replace(st,
                                     ring_capacity=VOD_RING_CAPACITY)
        self.cache = cache
        self.settings = st
        self.engine_for = engine_for
        self.engine_drop = engine_drop or (lambda _s: None)
        #: () -> MegabatchScheduler | None — the live scheduler whose
        #: ``_install_segment`` host-oracle check every device-primed
        #: param set goes through
        self.scheduler = scheduler or (lambda: None)
        self.lookahead_ms = lookahead_ms
        self.device_prime = device_prime
        self.sessions: list[PacedVodSession] = []
        self._unprimed: list[tuple[PacedVodSession, _PacedTrack]] = []
        self._last_prune_ms = 0
        self.hot_pkts = 0
        self.cold_pkts = 0
        self.device_primes = 0
        self.prime_failures = 0

    # ------------------------------------------------------------ sessions
    def open(self, file: Mp4File, outputs: dict[int, RelayOutput], *,
             start_npt: float = 0.0, speed: float = 1.0, path: str = "",
             now_ms: int | None = None) -> PacedVodSession:
        sess = PacedVodSession(self, file, outputs, start_npt=start_npt,
                               speed=speed, path=path, now_ms=now_ms)
        self.sessions.append(sess)
        self._unprimed.extend((sess, tr) for tr in sess.tracks)
        obs.VOD_SESSIONS.set(len(self.sessions))
        return sess

    def adopt(self, sess) -> object:
        """Register an externally-built paced session (the DVR tier's
        ``TimeShiftSession``, ``dvr/timeshift.py``) under this pacer's
        tick/step/retire lifecycle.  The duck-typed contract is what
        ``tick``/``retire`` already consume: ``tick(now_ms)``, ``done``,
        ``stopped``, ``tracks`` (each with ``.stream``/``.release``),
        ``file.close()`` and an optional ``on_retire`` hook."""
        self.sessions.append(sess)
        obs.VOD_SESSIONS.set(len(self.sessions))
        return sess

    def retire(self, sess: PacedVodSession) -> None:
        if sess in self.sessions:
            self.sessions.remove(sess)
        if self._unprimed:
            self._unprimed = [(s, t) for s, t in self._unprimed
                              if s is not sess]
        for tr in sess.tracks:
            tr.release(self)
        if not sess.stopped:
            sess.stopped = True
            sess.file.close()
            # inside the stopped guard: retire() runs again when the
            # connection later stop()s an auto-retired session, and a
            # second on_retire would double-decrement the session gauge
            cb = getattr(sess, "on_retire", None)
            if cb is not None:
                cb()
        obs.VOD_SESSIONS.set(len(self.sessions))

    # ---------------------------------------------------------------- tick
    def tick(self, now_ms: int) -> list:
        """Fill every session's rings up to the lookahead horizon and
        return the (stream, engine) pairs the pump should step this
        wake.  Finished sessions retire here (their last packet has
        been delivered — ``drained`` checks the bookmarks)."""
        pairs = []
        for sess in list(self.sessions):
            sess.tick(now_ms)
            if sess.done:
                self.retire(sess)
                continue
            for tr in sess.tracks:
                eng = (self.engine_for(tr.stream)
                       if self.engine_for is not None else None)
                pairs.append((tr.stream, eng))
        if self._unprimed:
            self._prime_joined(now_ms)
        if now_ms - self._last_prune_ms >= 1000:
            self._last_prune_ms = now_ms
            for sess in self.sessions:
                for tr in sess.tracks:
                    tr.stream.prune(now_ms)
        return pairs

    # --------------------------------------------------- device-side prime
    def _prime_joined(self, now_ms: int) -> None:
        """Affine prime for just-joined subscribers from the CACHE's
        HBM-resident windows: one stacked ``megabatch_window_step`` per
        padded window shape over device-side row stacks — zero H2D (the
        windows were uploaded once at pack time and are shared by every
        subscriber on them).  Every result goes through the scheduler's
        ``_install_segment`` host-oracle check; any failure here simply
        leaves the join to the scheduler's own zero-window prime in the
        same wake."""
        pending, self._unprimed = self._unprimed, []
        sched = self.scheduler()
        if sched is None or not self.device_prime \
                or self.engine_for is None:
            return
        groups: dict[int, list] = {}
        for sess, tr in pending:
            if sess.stopped or sess.done or tr.window is None:
                continue
            eng = self.engine_for(tr.stream)
            p = eng.plan(tr.stream, now_ms)
            fast, key = p.fast, p.key
            if not fast:
                continue                 # TCP/meta output: no affine set
            mb = eng.megabatch_params
            if key == eng._params_key or (mb is not None
                                          and mb[0] == key):
                continue
            dev = tr.window.device_rows()
            if dev is None:
                continue
            groups.setdefault(int(dev.shape[0]), []).append(
                (eng, fast, key, dev))
        if not groups:
            return
        try:
            import jax.numpy as jnp

            from ..models.relay_pipeline import (megabatch_window_step,
                                                 scatter_affine_segments)
            from ..ops.fanout import STATE_COLS, pack_output_state
            from ..ops.staging import pow2
            for _pad, items in sorted(groups.items()):
                b_pad = pow2(len(items), 1)
                s_pad = pow2(max(len(f) for _e, f, _k, _d in items), 8)
                state = np.zeros((b_pad, s_pad, STATE_COLS), np.uint32)
                for i, (_e, fast, _k, _d) in enumerate(items):
                    state[i, :len(fast)] = np.asarray(
                        pack_output_state(fast))
                stack = jnp.stack([d for _e, _f, _k, d in items])
                if b_pad > len(items):   # pow2 rows: zeros minted ON
                    stack = jnp.concatenate(  # device, still zero H2D
                        [stack, jnp.zeros(
                            (b_pad - len(items),) + stack.shape[1:],
                            stack.dtype)])
                res = megabatch_window_step(stack, state)
                segs = scatter_affine_segments(
                    np.asarray(res), [len(f) for _e, f, _k, _d in items])
                for (eng, _fast, key, _d), seg in zip(items, segs):
                    if sched._install_segment(eng, key, seg):
                        self.device_primes += 1
        except Exception:
            self.prime_failures += 1

    # ---------------------------------------------------------------- misc
    def stats(self) -> dict:
        return {
            "sessions": len(self.sessions),
            "hot_pkts": self.hot_pkts,
            "cold_pkts": self.cold_pkts,
            "device_primes": self.device_primes,
            "prime_failures": self.prime_failures,
            "cache": self.cache.stats(),
        }

    def close(self) -> None:
        """Retire every session.  The cache is NOT closed here — it is
        owned by whoever built it (the app closes both; a bench reuses
        one warm cache across many pacer lifetimes)."""
        for sess in list(self.sessions):
            self.retire(sess)
