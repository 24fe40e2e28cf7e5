"""HLS bitrate rendition via transform-domain H.264 requantization.

``RequantHlsOutput`` is an ``HlsOutput`` whose access units pass through
``codecs.h264_requant.SliceRequantizer`` before muxing: a TRUE
lower-bitrate rendition at the SAME frame rate, next to the temporal
(frame-thinning) rungs (VERDICT r2 item 4).  The split mirrors the MJPEG
ladder: CAVLC entropy recode on the host, the per-level integer requant
batched on the device (``ops.transform.h264_requant``), differential-
tested bit-exact against the scalar oracle.

Parallel harness (VERDICT r3 item 1): ALL requant renditions share one
``ThreadPoolExecutor`` sized to the host's cores — the native CAVLC walk
is a ctypes call, so the GIL is released for its whole duration and
pictures genuinely run in parallel.  Order is preserved per rendition
without serializing it: consecutive AUs of the same rung pipeline
through different workers (each against snapshot parameter sets) and a
reorder buffer emits them in submission order — so ONE 1080p30 rung
scales across cores, not just many rungs across cores.  The reference
analogue is the short/blocking task-thread split
(``Task.cpp:120-146``); here the "blocking pool" is per-picture jobs.

Honest scope notes (also in ``codecs.h264_requant``): CAVLC baseline
intra slices only (I_4x4 + I_16x16, luma AND 4:2:0 chroma residuals);
anything else passes through unchanged and is counted, so the rendition
degrades toward the source bitrate rather than corrupting.  Requant is
open loop: drift is spatial-only and resets at every IDR — for
all-intra camera streams, every frame."""

from __future__ import annotations

import asyncio
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from ..codecs.h264_requant import (FusedRequantDispatch, RequantStats,
                                   SliceRequantizer, device_batch,
                                   device_batch_chroma, gather_slice,
                                   parse_slice_nal, recode_parsed)
from ..obs import (REQUANT_AUS, REQUANT_REASSEMBLY_MISMATCH,
                   REQUANT_RENDITIONS, REQUANT_SHED, REQUANT_STAGE_SECONDS)
from ..relay.output import RelayOutput, WriteResult
from ..vod.depacketize import AccessUnit
from .segmenter import HlsOutput

#: the CLOSED requant-pipeline stage vocabulary behind
#: ``requant_stage_seconds{stage}`` (tools/metrics_lint.py rejects any
#: observed child outside it): ``parse`` = shared entropy decode of a
#: slice, ``entropy`` = the fused native walk (serial CAVLC/CABAC state
#: machines, decode+recode in one pass), ``transform_device`` = the
#: fused device requant dispatch + harvest for every (slice, rendition)
#: of an AU, ``recode`` = one rendition's serial entropy re-encode over
#: the shared parse, ``reassemble`` = the ordered per-AU emit.
REQUANT_STAGES = ("parse", "entropy", "transform_device", "recode",
                  "reassemble")


def _stage(stage: str, t0: float) -> None:
    REQUANT_STAGE_SECONDS.observe(time.perf_counter() - t0, stage=stage)

#: one shared pool for ALL requant renditions, sized to the cores the
#: process may use: the native walk releases the GIL (ctypes), so jobs
#: from one OR many renditions run truly concurrently; the pure-Python
#: fallback path still benefits from staying off the event loop
_pool: ThreadPoolExecutor | None = None
_sizing_cache: dict | None = None


def _own_cgroup_path(proc_cgroup: str, controller: str | None) -> str:
    """This process's cgroup path for ``controller`` (None = the v2
    unified hierarchy) from ``/proc/self/cgroup`` — the effective quota
    lives in OUR cgroup, not the root (a systemd CPUQuota= service sits
    in system.slice/<svc> where the root's cpu.max reads 'max')."""
    try:
        with open(proc_cgroup, encoding="ascii") as f:
            for ln in f:
                parts = ln.strip().split(":", 2)
                if len(parts) != 3:
                    continue
                if controller is None and parts[0] == "0":
                    return parts[2]
                if controller is not None and \
                        controller in parts[1].split(","):
                    return parts[2]
    except OSError:
        pass
    return ""


def _cgroup_quota_cpus(proc_cgroup: str = "/proc/self/cgroup",
                       fs_root: str = "/sys/fs/cgroup") -> float | None:
    """CPU-equivalents allowed by the cgroup's *bandwidth* quota (the
    signal affinity masks cannot see): cgroup v2 ``cpu.max`` or v1
    ``cpu.cfs_quota_us``/``cpu.cfs_period_us``, read from THIS
    process's cgroup and every ancestor up to the root — the effective
    limit is the minimum along the chain.  None = no quota anywhere
    (or not on Linux/cgroups)."""
    best: float | None = None

    def note(v: float) -> None:
        nonlocal best
        best = v if best is None else min(best, v)

    def walk(root: str, rel: str, read) -> None:
        node = root + rel if rel and rel != "/" else root
        while True:
            v = read(node)
            if v is not None:
                note(v)
            if node == root or not node.startswith(root):
                break
            node = os.path.dirname(node)

    def read_v2(node: str) -> float | None:
        try:
            with open(node + "/cpu.max", encoding="ascii") as f:
                quota, _, period = f.read().strip().partition(" ")
            if quota != "max" and float(period) > 0:
                return float(quota) / float(period)
        except (OSError, ValueError):
            pass
        return None

    def read_v1(node: str) -> float | None:
        try:
            with open(node + "/cpu.cfs_quota_us", encoding="ascii") as f:
                quota = float(f.read().strip())
            with open(node + "/cpu.cfs_period_us", encoding="ascii") as f:
                period = float(f.read().strip())
            if quota > 0 and period > 0:
                return quota / period
        except (OSError, ValueError):
            pass
        return None

    walk(fs_root, _own_cgroup_path(proc_cgroup, None), read_v2)
    walk(fs_root + "/cpu", _own_cgroup_path(proc_cgroup, "cpu"), read_v1)
    return best


def _probe_affinity() -> int:
    """CPUs the scheduler will run this process's threads on.  (libtpu
    does not narrow it: measured on a TPU v5e host, the mask reads the
    same before ``import jax``, after backend init and in a thread
    started afterwards — CHANGES.md PR 21.)"""
    try:
        return max(1, len(os.sched_getaffinity(0)))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def pool_sizing(*, affinity: int | None = None,
                quota: float | None = None,
                cpu_count: int | None = None,
                env: str | None = None) -> dict:
    """Worker count for the shared requant pool PLUS the rationale —
    which signal won and what every signal read — surfaced into the
    bench JSON ``extra`` so a wrong sizing is diagnosable from the
    trajectory alone (BENCH_r05 shipped ``workers: 1`` with nothing to
    say why).

    Signals, in precedence order:

    * ``EDTPU_REQUANT_WORKERS`` — explicit operator override;
    * the **affinity mask** — the CPUs the scheduler will actually run
      our threads on;
    * the **cgroup bandwidth quota** (``cpu.max`` / cfs_quota) — the
      signal the affinity mask cannot see.  Where the mask collapses to
      one CPU while the quota provisions several, the quota is trusted;
      on a big node where affinity says 96 but ``cpu.max`` caps at 2,
      sizing to 96 just trades throughput for preemption thrash, so the
      quota caps the pool.

    Keyword arguments override the probed signals (tests); the no-
    argument call is memoized — none of these signals move at runtime."""
    global _sizing_cache
    injected = (affinity is not None or quota is not None
                or cpu_count is not None or env is not None)
    if not injected and _sizing_cache is not None:
        return _sizing_cache
    env = os.environ.get("EDTPU_REQUANT_WORKERS") if env is None else env
    if env:
        try:
            sizing = {"workers": max(1, int(env)), "source": "env",
                      "affinity_cpus": None, "quota_cpus": None,
                      "cpu_count": os.cpu_count() or 1}
            if not injected:
                _sizing_cache = sizing
            return sizing
        except ValueError:
            pass
    ncpu = cpu_count if cpu_count is not None else (os.cpu_count() or 1)
    aff = affinity if affinity is not None else _probe_affinity()
    q = quota if quota is not None else _cgroup_quota_cpus()
    q_cpus = max(1, int(q)) if q is not None and q >= 1 else \
        (1 if q is not None else None)
    if aff <= 1 and q_cpus is not None and q_cpus > 1:
        workers, source = min(q_cpus, ncpu), "cpu_max_quota"
    elif q_cpus is not None and q_cpus < aff:
        workers, source = q_cpus, "cpu_max_cap"
    else:
        workers, source = aff, "affinity"
    sizing = {"workers": max(1, workers), "source": source,
              "affinity_cpus": aff,
              "quota_cpus": round(q, 2) if q is not None else None,
              "cpu_count": ncpu}
    if not injected:
        _sizing_cache = sizing
    return sizing


def pool_workers() -> int:
    """Worker count for the shared requant pool (see ``pool_sizing``
    for the decision rationale)."""
    return pool_sizing()["workers"]


def _get_pool() -> ThreadPoolExecutor:
    global _pool
    if _pool is None:
        _pool = ThreadPoolExecutor(max_workers=pool_workers(),
                                   thread_name_prefix="hls-requant")
    return _pool


class RequantHlsOutput(HlsOutput):
    def __init__(self, delta_qp: int, *, use_device: bool = True, **kw):
        super().__init__(**kw)
        from .. import native as native_mod
        if native_mod.available():
            # the native CAVLC walk (~100x the Python path) is the
            # production engine; it embeds the same exact level shift
            # and the chroma identity/shift/round-trip dispatch
            fn = cfn = None
        else:
            fn = device_batch if use_device else None
            cfn = device_batch_chroma if use_device else None
        self.requant = SliceRequantizer(delta_qp, requant_fn=fn,
                                        chroma_fn=cfn)
        self.delta_qp = delta_qp
        self._ps_fed: tuple[bytes | None, bytes | None] = (None, None)
        #: AUs dropped because the pipeline was too far behind — shedding
        #: keeps the rendition live instead of ever-later.  Depth 2x the
        #: pool keeps every core fed while bounding added latency to
        #: ~2 pictures' work
        self.shed = 0
        self._max_pending = max(4, 2 * pool_workers())
        # per-rendition reorder buffer: workers complete out of order,
        # fMP4 fragments must not
        self._next_submit = 0
        self._next_emit = 0
        self._ready: dict[int, AccessUnit] = {}

    def _transform(self, au: AccessUnit,
                   ps: tuple[bytes | None, bytes | None]) -> AccessUnit:
        # the depacketizer latches SPS/PPS out of band (they are config,
        # not sample data) — feed them to the requantizer when they change
        if ps != self._ps_fed:
            self._ps_fed = ps
            for n in ps:
                if n:
                    self.requant.transform_nal(n)
        return AccessUnit(au.timestamp,
                          [self.requant.transform_nal(n) for n in au.nals])

    def _on_unit(self, au: AccessUnit) -> None:
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        # parameter sets are captured at ENQUEUE time (loop thread): a
        # queued AU must be requantized against the PPS it was coded
        # with, not whatever a later packet latched
        ps = (self.depack.sps, self.depack.pps)
        if loop is None:
            # synchronous caller (tests, offline tools): transform inline
            super()._on_unit(self._transform(au, ps))
            return
        # gate on SUBMITTED-minus-EMITTED, not worker completions: a
        # straggler AU must stall admission too, or fast successors pile
        # up unboundedly in the reorder buffer behind it (added latency
        # then grows with the straggler, breaking the "degrade in frame
        # rate, never in latency" contract)
        if self.pending >= self._max_pending:
            self.shed += 1                 # backlogged: shed, stay live
            from ..obs.ledger import LEDGER
            LEDGER.defer("hls_requant")
            return
        # latch the sets on the loop thread and snapshot the PARSED
        # objects for the worker (requant_with is stateless)
        if ps != self._ps_fed:
            self._ps_fed = ps
            for n in ps:
                if n:
                    self.requant.transform_nal(n)
        sps, pps = self.requant.sps, self.requant.pps
        seq = self._next_submit
        self._next_submit += 1

        def work():
            try:
                deltas = []
                nals = []
                for n in au.nals:
                    out, d = self.requant.requant_with(n, sps, pps)
                    nals.append(out)
                    deltas.append(d)
                out_au = AccessUnit(au.timestamp, nals)
            except Exception:
                # never let a worker error strand the reorder slot (that
                # would shed every future AU forever); pass the unit
                # through — and none of its stats: partially-counted
                # work whose output was discarded must not drift
                # bytes_out away from emitted bytes
                out_au = au
                deltas = []
            loop.call_soon_threadsafe(self._emit, seq, out_au, deltas)

        _get_pool().submit(work)

    @property
    def pending(self) -> int:
        """Submitted-but-not-yet-emitted AUs (in workers OR waiting in
        the reorder buffer) — the admission gate and test barrier."""
        return self._next_submit - self._next_emit

    def _emit(self, seq: int, au: AccessUnit, deltas) -> None:
        for d in deltas:
            self.requant.stats.merge(d)
        self._ready[seq] = au
        while self._next_emit in self._ready:
            super()._on_unit(self._ready.pop(self._next_emit))
            self._next_emit += 1


# ========================================================== the ABR ladder
# ISSUE 9 tentpole: one shared-parse, slice-parallel, device-overlapped
# pipeline feeding EVERY q-rung rendition of a source.
#
#   AU ──► slice NALs ──► [parse ×S across the pool]          (Python path)
#            │                    │
#            │                    └► ONE FusedRequantDispatch (S slices ×
#            │                       N renditions, async device) ──►
#            │                       [recode ×S×N across the pool]
#            │
#            └──────────► [native walk ×S×N across the pool]  (native path)
#                                 │
#                    ordered per-AU reassembly ──► rendition muxers
#
# The native engine keeps its fused decode+requant+recode walk (two
# orders faster than the Python slice walk, so N independent walks beat
# one shared Python parse at any ladder width) — its ladder lever is the
# slice × rendition fan-out across the pool.  The Python engines (device
# or scalar transform) parse each slice ONCE and recode N times, with
# all (slice, rendition) transform rows batched into a single device
# dispatch per AU, double-buffered: the JAX dispatch is asynchronous and
# admission allows ~2×workers AUs in flight, so the device computes AU
# k's rows while the pool entropy-decodes AU k+1 (the PR 4 staging
# pattern).  A single-slice, single-rendition AU degenerates to exactly
# the serial ``SliceRequantizer`` path — bit-identity is pinned by
# tests/test_requant_ladder.py.


class LadderRendition(HlsOutput):
    """One rung's CMAF muxer: fed already-requantized AUs by its ladder
    (never raw packets — ``send_bytes`` on a rendition is a wiring bug).
    Keeps the ``.requant`` / ``.shed`` surface the admin/soak layers
    read on q-rung outputs."""

    def __init__(self, ladder: "RequantLadder", delta_qp: int,
                 engine: SliceRequantizer, **kw):
        super().__init__(**kw)
        self._ladder = ladder
        self.delta_qp = delta_qp
        #: the per-rendition stats container (and serial engine config);
        #: worker deltas merge into ``requant.stats`` once per AU
        self.requant = engine
        #: share the ladder's depacketizer so the init segment sees the
        #: source SPS/PPS (requant never rewrites parameter sets)
        self.depack = ladder.depack

    def send_bytes(self, data: bytes, *, is_rtcp: bool):
        raise RuntimeError("ladder renditions are fed AUs by the "
                           "ladder, not packets")

    @property
    def shed(self) -> int:
        """AUs shed at ladder admission (sheds apply to every rendition
        of the ladder together — degrade in frame rate, never latency)."""
        return self._ladder.shed

    @property
    def pending(self) -> int:
        return self._ladder.pending


class _AuJob:
    """Bookkeeping for one AU in flight through the ladder pool: per-
    rendition output slots (slice-ordered), per-worker stats deltas, and
    the outstanding-unit counter that triggers reassembly."""

    __slots__ = ("seq", "au", "deltas", "sps", "pps", "slice_idx",
                 "outs", "stats", "remaining", "lock", "parsed",
                 "mismatch")

    def __init__(self, seq: int, au: AccessUnit, deltas, sps, pps):
        self.seq = seq
        self.au = au
        self.deltas = deltas
        self.sps = sps
        self.pps = pps
        self.slice_idx = [i for i, n in enumerate(au.nals)
                          if n and (n[0] & 0x1F) in (1, 5)
                          and sps is not None and pps is not None]
        # non-slice NALs ride through in place; slice slots start EMPTY
        # so the reassembly check catches a genuinely lost unit instead
        # of silently emitting the source slice
        slice_set = set(self.slice_idx)
        self.outs = {d: [None if i in slice_set else n
                         for i, n in enumerate(au.nals)]
                     for d in deltas}
        self.stats = {d: [] for d in deltas}
        self.remaining = 0
        self.lock = threading.Lock()
        self.parsed = {}                # slice pos -> (ParsedSlice, gather)
        self.mismatch = False


class RequantLadder(RelayOutput):
    """The multi-rendition transform-domain requant pipeline: ONE relay
    sink per published path that depacketizes once, requantizes each AU
    to every rung of its ladder through the shared worker pool, and
    feeds the per-rendition muxers in source order."""

    def __init__(self, *, use_device: bool = True,
                 target_duration: float = 2.0, window: int = 6,
                 audio=None):
        super().__init__(ssrc=0x415)
        # identity rewrite, same as HlsOutput: every rendition keeps the
        # SOURCE timestamps so ABR switching never jumps in time
        self.rewrite.base_src_seq = 0
        self.rewrite.base_src_ts = 0
        self.rewrite.out_seq_start = 0
        self.rewrite.out_ts_start = 0
        from ..vod.depacketize import H264Depacketizer
        self.depack = H264Depacketizer()
        self.target_duration = target_duration
        self.window = window
        self.audio = audio
        from .. import native as native_mod
        self._use_native = native_mod.available()
        self._use_device = bool(use_device) and not self._use_native
        self._fn = None if self._use_native else \
            (device_batch if use_device else None)
        self._cfn = None if self._use_native else \
            (device_batch_chroma if use_device else None)
        self.renditions: dict[int, LadderRendition] = {}
        self._sps = None
        self._pps = None
        self._sps_raw: bytes | None = None
        self._pps_raw: bytes | None = None
        self.shed = 0
        self._max_pending = max(4, 2 * pool_workers())
        self._next_submit = 0
        self._next_emit = 0
        self._ready: dict[int, _AuJob] = {}

    # -- ladder membership -------------------------------------------------
    def add_rendition(self, delta_qp: int) -> LadderRendition:
        """Get-or-create the rung at ``delta_qp`` (multiples of 6, the
        exact-shift window — SliceRequantizer validates)."""
        out = self.renditions.get(delta_qp)
        if out is None:
            engine = SliceRequantizer(delta_qp, requant_fn=self._fn,
                                      chroma_fn=self._cfn)
            out = LadderRendition(self, delta_qp, engine,
                                  target_duration=self.target_duration,
                                  window=self.window, audio=self.audio)
            self.renditions[delta_qp] = out
        return out

    @property
    def pending(self) -> int:
        """Submitted-but-not-yet-emitted AUs (in workers OR waiting in
        the reorder buffer) — the admission gate and test barrier."""
        return self._next_submit - self._next_emit

    # -- ingest ------------------------------------------------------------
    def send_bytes(self, data: bytes, *, is_rtcp: bool) -> WriteResult:
        if is_rtcp:
            return WriteResult.OK
        self.depack.push(data)
        units = self.depack.pop_units()
        if not units:
            return WriteResult.OK
        # wake-ledger unit (ISSUE 16): AU admission runs nested inside
        # the pump's live-relay pass — bracketing it here (per completed
        # AU, never per packet) lets the ledger subtract it from
        # live_relay and charge the requant class with its own service
        from ..obs.ledger import LEDGER
        tok = LEDGER.unit_start("hls_requant")
        for au in units:
            self._on_unit(au)
        LEDGER.unit_end(tok, items=len(units))
        return WriteResult.OK

    def _latch_ps(self, au: AccessUnit) -> None:
        """Latch SPS/PPS at AU granularity on the ingest thread: the
        depacketizer's out-of-band sets plus any in-band sets riding the
        AU (parameter sets are config, not sample data — conformant
        senders place them before the slices they govern)."""
        from ..codecs.h264_intra import Pps, Sps
        cands = [self.depack.sps, self.depack.pps]
        cands += [n for n in au.nals if n and (n[0] & 0x1F) in (7, 8)]
        for n in cands:
            if not n:
                continue
            t = n[0] & 0x1F
            try:
                if t == 7 and n != self._sps_raw:
                    self._sps, self._sps_raw = Sps.parse(n), n
                elif t == 8 and n != self._pps_raw:
                    self._pps, self._pps_raw = Pps.parse(n), n
            except (ValueError, EOFError, IndexError):
                if t == 7:
                    self._sps = self._sps_raw = None
                else:
                    self._pps = self._pps_raw = None

    def _on_unit(self, au: AccessUnit) -> None:
        if not self.renditions:
            return
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        self._latch_ps(au)
        deltas = tuple(sorted(self.renditions))
        job = None
        if loop is None:
            # synchronous caller (tests, offline tools): run the SAME
            # pipeline inline — sync and pooled output are byte-identical
            job = _AuJob(self._next_submit, au, deltas, self._sps,
                         self._pps)
            self._next_submit += 1
            self._run_job_inline(job)
            self._emit(job)
            return
        if self.pending >= self._max_pending:
            self.shed += 1               # backlogged: shed, stay live
            REQUANT_SHED.inc()
            from ..obs.ledger import LEDGER
            LEDGER.defer("hls_requant")
            return
        job = _AuJob(self._next_submit, au, deltas, self._sps, self._pps)
        self._next_submit += 1
        if not job.slice_idx:
            self._emit(job)              # SEI/PS-only AU: nothing to do,
            return                       # but it keeps its emit slot
        pool = _get_pool()
        if self._use_native:
            # unit granularity adapts to the pool: when the SLICES alone
            # already saturate the workers, one unit per slice (looping
            # the renditions) avoids paying submit/lock overhead for
            # parallelism the pool cannot add; a few-slice AU on a wide
            # pool keeps the full (slice x rendition) fan-out so every
            # worker engages
            if len(job.slice_idx) >= pool_workers():
                job.remaining = len(job.slice_idx)
                for pos in job.slice_idx:
                    pool.submit(self._native_unit, loop, job, pos,
                                deltas)
            else:
                job.remaining = len(job.slice_idx) * len(deltas)
                for pos in job.slice_idx:
                    for d in deltas:
                        pool.submit(self._native_unit, loop, job, pos,
                                    (d,))
        else:
            job.remaining = len(job.slice_idx)
            for pos in job.slice_idx:
                pool.submit(self._parse_unit, loop, job, pos)

    # -- worker units ------------------------------------------------------
    # Every unit takes ``loop``: the pooled path passes the event loop
    # (completion notifies it thread-safely); the synchronous inline
    # path passes None and the caller emits after the last unit — ONE
    # implementation, so sync and pooled can never drift apart.
    def _complete_unit(self, loop, job: _AuJob) -> None:
        with job.lock:
            job.remaining -= 1
            done = job.remaining == 0
        if done and loop is not None:
            loop.call_soon_threadsafe(self._emit, job)

    def _native_unit(self, loop, job: _AuJob, pos: int,
                     unit_deltas: "tuple[int, ...]") -> None:
        """One slice through the fused native walk (the serial entropy
        state machines, decode+requant+recode in one pass) for one or
        more renditions — the slice × rendition fan-out IS the native
        ladder lever."""
        nal = job.au.nals[pos]
        for delta in unit_deltas:
            engine = self.renditions[delta].requant
            try:
                t0 = time.perf_counter()
                out, d = engine.requant_with(nal, job.sps, job.pps)
                _stage("entropy", t0)
            except Exception:
                out = nal                # never strand the slot — and
                d = RequantStats()       # count the pass-through, or
                d.bytes_in += len(nal)   # bytes_out drifts away from
                d.bytes_out += len(nal)  # the bytes actually emitted
                d.slices_passed_through += 1
            with job.lock:
                job.outs[delta][pos] = out
                job.stats[delta].append(d)
        self._complete_unit(loop, job)

    def _parse_unit(self, loop, job: _AuJob, pos: int) -> None:
        """Shared parse of one slice (Python engines): entropy-decode
        ONCE for the whole rendition ladder.  The worker that finishes
        the AU's last parse runs the fused dispatch inline and fans the
        per-(slice, rendition) recodes back across the pool."""
        nal = job.au.nals[pos]
        parsed = None
        try:
            t0 = time.perf_counter()
            p = parse_slice_nal(nal, job.sps, job.pps)
            parsed = (p, gather_slice(p))
            _stage("parse", t0)
        except Exception:
            parsed = None                # out of scope: pass through
        with job.lock:
            if parsed is not None:
                job.parsed[pos] = parsed
            job.remaining -= 1
            last = job.remaining == 0    # this was the AU's final parse
        if last:
            self._dispatch_unit(loop, job)

    def _dispatch_unit(self, loop, job: _AuJob) -> None:
        """The AU's single fused transform dispatch (slices × renditions
        in one call; asynchronous on the device path, so device time
        hides behind the NEXT AU's parses on other workers), then the
        recode fan-out."""
        order = sorted(job.parsed)
        failed = [pos for pos in job.slice_idx if pos not in job.parsed]
        dispatch = None
        if order:
            try:
                t0 = time.perf_counter()
                dispatch = FusedRequantDispatch(
                    [job.parsed[pos][1] for pos in order],
                    job.deltas, requant_fn=self._fn, chroma_fn=self._cfn,
                    chroma_qp_offset=job.pps.chroma_qp_offset,
                    use_device=self._use_device)
                dispatch._harvested()    # device wait lands here, not in
                _stage("transform_device", t0)   # a recode bracket
            except Exception:
                dispatch = None
                failed = list(job.slice_idx)
                order = []
        for pos in failed:
            d = RequantStats()
            d.bytes_in += len(job.au.nals[pos])
            d.slices_passed_through += 1
            d.bytes_out += len(job.au.nals[pos])
            with job.lock:
                for delta in job.deltas:
                    job.outs[delta][pos] = job.au.nals[pos]
                    job.stats[delta].append(
                        d if delta == job.deltas[0] else _copy_delta(d))
        if not order:
            if loop is not None:
                loop.call_soon_threadsafe(self._emit, job)
            return
        with job.lock:
            # swap the exhausted parse budget for the recode budget: one
            # unit per (slice, rendition)
            job.remaining = len(order) * len(job.deltas)
        if loop is None:
            for s_i, pos in enumerate(order):
                for d_i, delta in enumerate(job.deltas):
                    self._recode_unit(None, job, dispatch, s_i, pos,
                                      d_i, delta)
            return
        pool = _get_pool()
        for s_i, pos in enumerate(order):
            for d_i, delta in enumerate(job.deltas):
                pool.submit(self._recode_unit, loop, job, dispatch,
                            s_i, pos, d_i, delta)

    def _recode_unit(self, loop, job: _AuJob, dispatch, s_i: int,
                     pos: int, d_i: int, delta: int) -> None:
        """One rendition's serial entropy re-encode of one slice over
        the shared parse."""
        nal = job.au.nals[pos]
        parsed, gather = job.parsed[pos]
        d = RequantStats()
        d.bytes_in += len(nal)
        try:
            t0 = time.perf_counter()
            out, n_blocks = recode_parsed(parsed, gather, dispatch,
                                          s_i, d_i)
            _stage("recode", t0)
            d.slices_requantized += 1
            d.blocks += n_blocks
        except Exception:
            out = nal
            d.slices_passed_through += 1
        d.bytes_out += len(out)
        with job.lock:
            job.outs[delta][pos] = out
            job.stats[delta].append(d)
        self._complete_unit(loop, job)

    # -- synchronous path --------------------------------------------------
    def _run_job_inline(self, job: _AuJob) -> None:
        """The pooled pipeline, single-threaded (no loop running): same
        primitives, same order, same bytes."""
        if not job.slice_idx:
            return
        if self._use_native:
            job.remaining = len(job.slice_idx)
            for pos in job.slice_idx:
                self._native_unit(None, job, pos, job.deltas)
            return
        job.remaining = len(job.slice_idx)
        for pos in job.slice_idx:
            self._parse_unit(None, job, pos)

    # -- reassembly --------------------------------------------------------
    def _emit(self, job: _AuJob) -> None:
        """Ordered per-AU reassembly (loop/caller thread): verify every
        slice slot, merge each rendition's worker deltas into its stats
        ONCE, and feed the muxers in source order."""
        t0 = time.perf_counter()
        for delta in job.deltas:
            if any(n is None for n in job.outs[delta]):
                # a pipeline bookkeeping bug, never silent corruption:
                # count it, pass the source AU through for this rung,
                # and drop its stats (output was discarded)
                job.mismatch = True
                job.outs[delta] = list(job.au.nals)
                job.stats[delta] = []
        if job.mismatch:
            REQUANT_REASSEMBLY_MISMATCH.inc()
        self._ready[job.seq] = job
        while self._next_emit in self._ready:
            j = self._ready.pop(self._next_emit)
            self._next_emit += 1
            REQUANT_AUS.inc()
            REQUANT_RENDITIONS.inc(len(j.deltas))
            for delta in j.deltas:
                out = self.renditions.get(delta)
                if out is None:
                    continue
                au_delta = RequantStats()
                for d in j.stats[delta]:
                    au_delta.merge(d)
                out.requant.stats.merge(au_delta)
                out._on_unit(AccessUnit(j.au.timestamp,
                                        j.outs[delta]))
        _stage("reassemble", t0)


def _copy_delta(d: RequantStats) -> RequantStats:
    c = RequantStats()
    c.merge(d)
    return c
