"""Cross-stream megabatch relay scheduler (ISSUE 4 tentpole).

The per-stream engine pays a fixed device-dispatch overhead per stream
per pump wake (the PR 3 profiler put the per-pass floor at ~5 ms p50 by
256 flows), so per-wake cost grows linearly with source count.  This
scheduler coalesces every eligible stream's device work into **one
shape-bucketed stacked pass per wake**:

* **collect** — a megabatch-owned stream contributes its ring window
  tail (the packets not yet staged) and its fast-output rewrite state.
  The scheduler reads the plan of the pairs the pump's ready set names
  (``begin_wake`` / ``end_wake``'s ``ready``: new packets and a moved
  params key both mark a stream, ``relay.pump.needs_step``) and of the
  pairs it carries over itself — a wake deferred at ``MAX_INFLIGHT``, a
  dispatch that failed, a pair it holds no record of — not of the
  roster: what a wake costs follows what is ready, not what is owned.
  ``ready=None`` is every pair (a caller with no ready set);
* **bucket** — streams are grouped by padded (window, subscriber)
  shape.  The pads come from three ladders (``_stream_pad``,
  ``PACKET_PADS``, ``_sub_pad``), so the programs the handed pairs can
  reach are a small CLOSED set (``MegabatchScheduler.members``) that
  ``begin_wake`` traces and loads when the pairs first reach it — not
  when a wake first needs one, with packets queued behind the build;
* **stage** — each bucket's windows are gathered into ONE contiguous
  upload buffer (``csrc ed_stage_gather`` when native, numpy otherwise)
  in the fused ``pack_window`` layout — a single H2D transfer per
  bucket.  Buffers are **double-buffered** per bucket shape: the buffer
  dispatched at wake N is never rewritten before its result was
  harvested, so the host gathers wake N+1 while the device/DMA still
  owns wake N's upload;
* **dispatch** — one donated ``models.relay_pipeline.megabatch_window_
  step`` call per bucket, result fetch started asynchronously;
* **harvest** (next wake) — the packed result is scattered back into
  per-stream affine param sets (``scatter_affine_segments``) and
  installed into each engine's ``megabatch_params`` override.  Install
  is keyed by the same ``params_key`` the engine checks, so a stream
  whose membership changed mid-flight simply ignores the stale segment
  and takes the per-stream query fallback for one wake.

Correctness lever: the affine egress params depend ONLY on per-output
rewrite state, never on packet content — so consuming a pass dispatched
one wake earlier is byte-identical to computing it synchronously, and
the overlap (device computes wake N while the host assembles wake N+1)
costs nothing.  Every harvested segment is additionally checked against
the host arithmetic oracle for its key; a disagreement increments
``megabatch_wire_mismatch_total`` and the segment is discarded (the
stream falls back to per-stream stepping), so a device/host divergence
can never reach the wire.

The harvest never blocks a wake: an in-flight result that is not ready
yet simply stays in flight (engines keep their cached params),
bounded by ``max_inflight`` outstanding passes.

**Mesh dispatch (ISSUE 7).**  Given a serving mesh
(``parallel.mesh.make_megabatch_mesh`` — ``src``-only, built once at
server startup from ``megabatch_devices``), each bucket's leading
stream axis is sharded over the mesh instead of landing on the default
device:

* staging is split into PER-DEVICE buffers (``_rows_per`` rows each:
  the pass is as tall as its rung of the stream ladder, split over the
  devices, and its entries are dealt round them), so each shard's H2D
  is one contiguous upload only that device reads;
* one ``models.relay_pipeline.sharded_megabatch_step`` dispatch per
  bucket — the pass is a pure vmap over streams, so the ``src``
  sharding partitions it with zero collectives.  It is the same kernel
  under the same name: the program, and the profiler's module on each
  device's plane, is ``megabatch_window_step``;
* a pass of ONE row has nothing to shard: it rides the single-device
  program on the mesh's first device (three shards of pure padding
  would run in full beside it) and is harvested as a single-device
  pass; the synchronous prime follows the same rule.  The
  ``megabatch_device_*`` counters count such a pass once, on that
  device, so they stay a full account of the mesh's work;
  ``megabatch_sharded_streams_total`` counts the streams that did ride
  a sharded pass;
* harvest stays non-blocking under the same ``MAX_INFLIGHT`` double
  buffer and fetches each device's packed slice independently
  (``addressable_shards``), and the egress scatter is keyed by shard:
  a stream's params are installed from the device that computed them,
  through the SAME ``_install_segment`` host-oracle check — a sharding
  bug degrades that stream to per-stream stepping, never the wire;
* uneven stream counts pad-mask the ``src`` axis exactly as the
  multichip dryrun does: tail rows are zero windows + zero state,
  which stage nothing and install nothing;
* the closed set is loaded at join under a mesh too (``programs``):
  each member mapped to the one program the mesh dispatches for it —
  the single-device program for the first rung, the sharded
  ``(rows_per × n_dev, p_pad, s_pad)`` for every other;
* a shard's upload, wait and fetch are spans (``megabatch.shard_h2d``,
  ``.shard_wait``, ``.shard_fetch``: ``device``, ``rows``) inside
  ``megabatch.h2d`` / ``megabatch.fetch``, and
  ``megabatch_device_phase_seconds`` is fed from their laps.

With no mesh (1-device box, ``megabatch_devices=1``, mesh build
failure) every dispatch takes the original single-device path and the
``megabatch_device_*`` families stay empty.  A mesh dispatch failure
propagates to the pump like any device error (the PR 5 ladder owns the
degradation).
"""

from __future__ import annotations

import time

import numpy as np

from .. import obs
from ..device import listen_builds
from ..models.relay_pipeline import (megabatch_window_step,
                                     scatter_affine_segments,
                                     sharded_megabatch_step)
from ..obs import PROFILER, TRACER
from ..obs.profile import builds
from ..ops import staging
from ..ops.fanout import STATE_COLS, pack_output_state
from ..resilience.inject import INJECTOR
from .fanout import _pow2


#: packet rows a stream stages in one row of a stacked pass.  A stream
#: with more new packets than the widest pad (it fell behind: a frozen
#: host, a burst) rides FURTHER ROWS of that pad, never a wider program
PACKET_PADS = (16, 64)


def _stream_pad(n: int) -> int:
    """Stream rows of a stacked pass: 1, 4, 16, 64, 256, … — powers of
    four, half the rungs of a pow2 ladder.  The device is idle; a pad
    row costs host zeros and H2D bytes (``megabatch_cells_total``)."""
    p = 1
    while p < n:
        p <<= 2
    return p


def _packet_pad(n: int) -> int:
    return PACKET_PADS[0] if n <= PACKET_PADS[0] else PACKET_PADS[-1]


#: the narrowest subscriber pad: every thin stream (a camera and its few
#: viewers) rides it, whatever its audience
SUB_FLOOR = 8


def _sub_pad(n: int) -> int:
    """Subscriber columns: the power of two from ``SUB_FLOOR`` up, the
    pad the per-stream engine stages its own state at."""
    return _pow2(n, SUB_FLOOR)


def _host_affine_params(key) -> tuple:
    """The affine rewrite computed by plain host arithmetic from a
    ``params_key`` — the oracle every harvested device segment is
    checked against (same uint32 formulas as ``ops.fanout.
    affine_params`` over ``pack_output_state``'s max(·, 0) clamping).
    The 6th column is the interleave channel byte (ISSUE 14): a pure
    passthrough, so the oracle is identity — but checking it means a
    device/transfer corruption can never re-channel a TCP frame."""
    st = np.asarray(key, dtype=np.int64).reshape(-1, 6)
    ssrc = (st[:, 0] & 0xFFFFFFFF).astype(np.uint32)
    base_seq = np.maximum(st[:, 1], 0).astype(np.uint32)
    base_ts = np.maximum(st[:, 2], 0).astype(np.uint32)
    seq0 = (st[:, 3] & 0xFFFFFFFF).astype(np.uint32)
    ts0 = (st[:, 4] & 0xFFFFFFFF).astype(np.uint32)
    chan = (st[:, 5] & 0xFFFFFFFF).astype(np.uint32)
    return ((seq0 - base_seq) & np.uint32(0xFFFF), ts0 - base_ts, ssrc,
            chan)


class _InFlight:
    """One dispatched stacked pass awaiting harvest."""

    __slots__ = ("result", "entries", "buf", "dispatch_ns", "rows_per")

    def __init__(self, result, entries, buf, dispatch_ns, rows_per=None):
        self.result = result
        #: per-row (stream, engine, key, n_fast, base_pid, shard)
        self.entries = entries
        #: the host staging this pass was uploaded from — one buffer on
        #: the single-device path, a per-shard buffer LIST on the mesh
        #: path — held until harvest so no later wake can rewrite it
        #: while the device/DMA may still be reading it, then recycled
        self.buf = buf
        self.dispatch_ns = dispatch_ns
        #: mesh passes only: stream rows per shard (the leading-axis
        #: block each device owns); None = single-device pass
        self.rows_per = rows_per


class _Pair:
    """What the scheduler keeps of one owned pair from wake to wake."""

    __slots__ = ("head", "rides", "epoch", "state")

    def __init__(self):
        #: ring id staged up to; None = nothing yet (the live window)
        self.head: int | None = None
        #: what the pair counts as in the closed set
        #: (``MegabatchScheduler.rides``); None = its plan was not read
        self.rides: int | None = None
        #: the stream's plan epoch as ``_collect`` last left its plan
        self.epoch = -1
        #: (params_key, packed out_state row) — the packed state is a
        #: pure function of the key, and the key comparison is paid
        #: anyway; skips the O(S) python pack loop on unchanged membership
        self.state: tuple | None = None


class MegabatchScheduler:
    """One per server; the pump (``relay/pump.py: serve``) marks the
    engines it hands over ``megabatch_owned`` and calls ``begin_wake``
    before the per-stream step loop and ``end_wake`` after it, each with
    the owned roster and the owned pairs of its ready set."""

    #: never stage more than this many packets per stream per pass (a
    #: burst beyond it restages from the newest tail, mirroring the
    #: per-stream resident ring's fell-behind restart)
    MAX_STAGE_ROWS = 1024
    #: outstanding stacked passes before staging pauses (bounded queue
    #: growth when the device falls behind the wake rate)
    MAX_INFLIGHT = 2
    #: an in-flight pass older than this is fetched even though it is
    #: not ready — a blocking wait (safety valve, not the hot path)
    FORCE_FETCH_NS = 2_000_000_000

    def __init__(self, mesh=None):
        #: the serving mesh (``parallel.mesh.make_megabatch_mesh``), or
        #: None for the single-device dispatch path.  Built once by the
        #: caller — the scheduler never probes devices itself, so a
        #: 1-device box constructs in microseconds with zero jax calls
        self.mesh = None
        self._mesh_devices: list = []
        self._sharded_step = None
        #: where a pass that is not sharded runs: the default device
        #: (None) and no shard (-1) off a mesh, the mesh's first under one
        self._home, self._home_shard = None, -1
        if mesh is not None and mesh.devices.size > 1:
            self.mesh = mesh
            # src-major flat order: shard k of the leading stream axis
            # lands on _mesh_devices[k]
            self._mesh_devices = list(mesh.devices.reshape(-1))
            self._sharded_step = sharded_megabatch_step(mesh)
            self._home, self._home_shard = self._mesh_devices[0], 0
            from jax.sharding import NamedSharding, PartitionSpec as P
            #: leading (stream) axis over the mesh's ``src``
            self._src_sharding = NamedSharding(mesh, P("src", None, None))
        #: staging buffers kept per hot shape: 2 per device (the double
        #: buffer), since every shard of a bucket draws from one pool
        self._pool_cap = 2 * max(1, len(self._mesh_devices))
        #: stream → its record, one for every pair of the owned roster
        #: as ``_enrol`` last read it.  Keyed by the stream itself: while
        #: a record lives its stream does, so a torn-down stream's
        #: ``id()`` cannot pass to a new one with its record still here
        self._tracked: dict = {}
        #: pairs by ``_Pair.rides``: the count ``_build_ahead`` closes
        #: the shape set over, kept across wakes (``riders``)
        self._riders: dict[int, int] = {}
        #: stream → engine of the pairs the next wake reads whether the
        #: pump names them or not
        self._carry: dict = {}
        #: pairs of the roster the last ``begin_wake`` was handed; -1
        #: while the scheduler is not engaged
        self._roster_len = -1
        #: the last wake's hand-over: pairs owned, pairs whose plan was
        #: read (``megabatch_pairs_total``, ``pump.wake``'s arguments)
        self.handed = self.walked = 0
        self._inflight: list[_InFlight] = []
        # double-buffered staging: a free pool per (b_pad, p_pad) shape;
        # a buffer leaves the pool at dispatch and returns at harvest,
        # so the upload the device still owns is never rewritten while
        # the host gathers the next wake into a fresh/recycled one
        # (steady state: two buffers per hot shape)
        self._free: dict[tuple, list[np.ndarray]] = {}
        #: every stacked-pass program this process has traced and
        #: loaded: (b_pad, p_pad, s_pad) of a single-device one, with
        #: the mesh's device count as a fourth of a sharded one
        self._built: set[tuple] = set()
        # a bracket that held an XLA build (a bucket-growth retrace) is
        # never a phase sample: obs.profile.builds() tells
        listen_builds()
        self.wakes = 0
        self.passes = 0
        self.sharded_passes = 0            # mesh-dispatched buckets
        self.streams_coalesced = 0
        self.harvests = 0
        self.mismatches = 0

    # ------------------------------------------------------------- wake API
    @property
    def engaged(self) -> bool:
        """Whether the last wake was the scheduler's (``begin_wake``)
        and not an ``idle_wake``."""
        return self._roster_len >= 0

    def begin_wake(self, pairs, now_ms: int, ready=None) -> None:
        """Harvest any finished stacked pass and prime params for
        streams whose membership changed — ONE stacked pass for every
        joined/rebased stream instead of one per-stream query each (the
        mass-join case the per-stream path serves linearly).  ``pairs``
        is the owned roster, ``ready`` the pairs of it the wake steps
        (None: all of them)."""
        self.wakes += 1
        self._harvest()
        self.handed = len(pairs)
        obs.MEGABATCH_PAIRS.inc(len(pairs), kind="handed")
        self._prime_stale(self._walk(pairs, ready), now_ms)

    def idle_wake(self) -> None:
        """Called by the pump on wakes where the megabatch is NOT
        engaged (eligible streams fell below ``megabatch_min_streams``):
        keeps harvesting whatever is still in flight so a mass teardown
        can't pin streams/buffers inside ``_InFlight`` records forever,
        and drops the per-stream records once nothing is in flight (a
        later re-engagement reads every pair anew, from the live
        window)."""
        self._roster_len = -1
        self.handed = self.walked = 0
        if self._inflight:
            self._harvest()
        if not self._inflight and self._tracked:
            self._tracked.clear()
            self._riders.clear()
            self._carry.clear()

    def end_wake(self, pairs, now_ms: int, ready=None) -> None:
        """Collect, bucket, stage and dispatch the next stacked pass."""
        # (a stream that left has lost its record by here, BEFORE any
        # early return: ``_walk``)
        walk = self._walk(pairs, ready)
        self.walked = len(walk)
        obs.MEGABATCH_PAIRS.inc(len(walk), kind="walked")
        if len(self._inflight) >= self.MAX_INFLIGHT:
            # saturated: this wake's dispatch is DEFERRED — the walked
            # pairs' fresh packets wait at least one more wake for device
            # service, and are walked then whether they are ready again
            # or not.  The wake ledger counts the skip per stream (the
            # queue-delay decomposition's megabatch deferral signal).
            from ..obs.ledger import LEDGER
            LEDGER.defer("megabatch", len(walk))
            self._carry.update(walk)
            return
        span = TRACER.open("megabatch.dispatch", "tpu")
        work = self._collect(walk, now_ms)
        # until its bucket is dispatched a pair is carried: a dispatch
        # that raises leaves the rest un-staged, for the next wake
        self._carry = {item[0]: item[1] for item in work}
        if not work:
            TRACER.close(span, buckets=0, streams=0)
            return
        buckets: dict[tuple, list] = {}
        for item in work:
            _stream, _eng, fast, _key, _base, n_new = item
            shape = (_packet_pad(n_new), _sub_pad(len(fast)))
            buckets.setdefault(shape, []).append(item)
        gather_ns = 0
        h2d_ns = 0
        for (p_pad, s_pad), entries in sorted(buckets.items()):
            # further rows of a stream that fell behind may outnumber
            # the streams riding this pad: no pass is taller than their
            # rung, the tallest ``members`` holds for the pad
            top = _stream_pad(len({id(e[0]) for e in entries}))
            for i in range(0, len(entries), top):
                g, h = self._dispatch_bucket(entries[i:i + top], p_pad,
                                             s_pad)
                gather_ns += g
                h2d_ns += h
        self._carry.clear()
        total = TRACER.lap(span, buckets=len(buckets), streams=len(work))
        PROFILER.account_pass("megabatch", total,
                              {"stage_gather": gather_ns, "h2d": h2d_ns})

    # --------------------------------------------------- which pairs are read
    def _walk(self, pairs, ready) -> list:
        """The pairs this wake reads the plan of.  With a ready set:
        ``ready`` and the carry-over — and the records follow the roster
        only when it changed, which shows as another length or a ready
        pair without a record (a pair new to the roster is ready in its
        first wake there: first rostered, or a route move).  Every pair
        for a caller with none, and in the first wake after one that was
        not the scheduler's: what was kept across it is stale."""
        if ready is None:
            self._enrol(pairs)
            return pairs
        tracked = self._tracked
        if self._roster_len < 0:
            self._enrol(pairs)
            self._carry = dict(pairs)
        elif len(pairs) != self._roster_len or any(
                s not in tracked for s, _ in ready):
            self._carry.update(self._enrol(pairs))
        carry = self._carry
        if not carry:
            return ready
        named = {s for s, _ in ready}
        return ready + [(s, e) for s, e in carry.items() if s not in named]

    def _enrol(self, pairs) -> dict:
        """Hold the records to the owned roster: the record of a stream
        that left goes (so does its count in the closed set and its
        place in the carry-over) and every newcomer gets one; returns
        the newcomers."""
        tracked = self._tracked
        new = {s: e for s, e in pairs if s not in tracked}
        if len(tracked) + len(new) != len(pairs):
            live = {s for s, _ in pairs}
            for s in [k for k in tracked if k not in live]:
                self._ride(tracked.pop(s), None)
                self._carry.pop(s, None)
        for s in new:
            tracked[s] = _Pair()
        self._roster_len = len(pairs)
        return new

    def behind(self, stream) -> bool:
        """The guard's question (``relay.pump.Pump.audit``), of an owned
        stream nothing has marked since the last wake: does the
        scheduler's record lag the stream — none, packets not staged, a
        plan epoch not read — with no carry-over to catch it up?  Then a
        mark or a hand-over went missing: the stream's steps take the
        per-stream query meanwhile (``megabatch_fallback_total``)."""
        if self._roster_len < 0:
            return False
        rec = self._tracked.get(stream)
        if rec is None:
            return True
        if stream in self._carry:
            return False
        ring = stream.rtp_ring
        head = rec.head
        if head is None:
            head = max(ring.tail, ring.head - self.MAX_STAGE_ROWS)
        return ring.head > head or stream.plan_epoch != rec.epoch

    # ------------------------------------------------------------- prime
    def _prime_stale(self, walk, now_ms: int) -> None:
        """Synchronous stacked param pass for key-stale streams.

        Reads each walked engine's output plan (``TpuFanoutEngine.
        plan``): its deterministic bookmark/rebase latch runs first,
        over the un-primed residue only (idempotent — the engine's step
        re-runs it as a no-op with the same wake timestamp), so the key
        read here is the key the engine will check moments later in the
        same wake.  The affine params depend only on that rewrite state,
        so the windows staged here are all-zero padding: no packet bytes
        ride the prime.  The same walk corrects what each pair counts as
        in the closed set (``rides``) for ``_build_ahead``; a pair that
        was not walked counts as it did."""
        stale = []
        tracked = self._tracked
        for stream, eng in walk:
            # the engine's own tables: the un-primed residue is latched,
            # nothing else is walked on an unchanged epoch
            p = eng.plan(stream, now_ms)
            self._ride(tracked[stream], self.rides(p))
            fast, key = p.fast, p.key
            if not fast or key == eng._params_key or (
                    eng.megabatch_params is not None
                    and eng.megabatch_params[0] == key):
                continue
            stale.append((eng, fast, key))
        self._build_ahead(*self.riders())
        if not stale:
            return
        span = TRACER.open("megabatch.prime", "tpu", streams=len(stale))
        buckets: dict[int, list] = {}
        for item in stale:
            buckets.setdefault(_sub_pad(len(item[1])), []).append(item)
        p_pad = PACKET_PADS[0]
        n_dev = len(self._mesh_devices)
        for s_pad, items in sorted(buckets.items()):
            rows_per = self._rows_per(len(items))
            b_pad = rows_per * n_dev or _stream_pad(len(items))
            # item i's row of the pass, and the shard that holds it
            rows = [(i % n_dev) * rows_per + i // n_dev if rows_per else i
                    for i in range(len(items))]
            self._built.add(self._program(b_pad, p_pad, s_pad, rows_per))
            # fresh zeros, never a recycled buffer: a stale le32 length
            # row would resurrect a previous wake's packets into the
            # keyframe scan
            win = np.zeros((b_pad, p_pad, staging.ROW_STRIDE), np.uint8)
            state = np.zeros((b_pad, s_pad, STATE_COLS), np.uint32)
            for row, (_eng, fast, _key) in zip(rows, items):
                state[row, :len(fast)] = np.asarray(pack_output_state(fast))
            built0 = builds()
            t_h = time.perf_counter_ns()
            res = self._step(win, state, rows_per)
            t_d = time.perf_counter_ns()
            packed = np.asarray(res)             # the blocking fetch
            t_f = time.perf_counter_ns()         # scatter is host work,
            segs = scatter_affine_segments(      # NOT d2h — unphased
                packed[rows], [len(f) for (_e, f, _k) in items])
            if builds() == built0:
                PROFILER.account_pass(
                    "megabatch", t_f - t_h,
                    {"device_step": t_d - t_h, "d2h": t_f - t_d})
            for row, (eng, _fast, key), seg in zip(rows, items, segs):
                self._install_segment(
                    eng, key, seg,
                    shard=row // rows_per if rows_per else self._home_shard)
            self._note_pass(
                len(items), win.nbytes + state.nbytes, 0,
                b_pad * p_pad * s_pad,
                by_shard=[len(range(k, len(items), n_dev))
                          for k in range(n_dev)] if rows_per else None)
        TRACER.close(span)

    # ------------------------------------------------------- the closed set
    @staticmethod
    def rides(p) -> int:
        """What a pair with output plan ``p`` counts as: the subscriber
        pad of its fast list with media; 0 for a thin stream still
        waiting for its first packet, which will ride the floor; -1, not
        counted, for a fatter one until then — its audience walks through
        every pad on the way up while players join, and loading each
        would cost the join six programs a pad."""
        if p.fast:
            return _sub_pad(len(p.fast))
        return 0 if p.n_outputs <= SUB_FLOOR else -1

    def _ride(self, rec: _Pair, rides: int | None) -> None:
        """``rec``'s pair counts as ``rides`` from here on (None: it
        left)."""
        if rec.rides == rides:
            return
        n = self._riders
        if rec.rides is not None:
            n[rec.rides] -= 1
            if not n[rec.rides]:
                del n[rec.rides]
        if rides is not None:
            n[rides] = n.get(rides, 0) + 1
        rec.rides = rides

    def riders(self) -> tuple[dict, bool]:
        """({subscriber pad: the handed pairs that ride it}, whether
        media flows on any) as kept across wakes — equal to a count over
        every pair's plan."""
        riders: dict[int, int] = {}
        live = False
        for rides, n in self._riders.items():
            if rides < 0:
                continue
            live = live or rides > 0
            pad = rides or SUB_FLOOR
            riders[pad] = riders.get(pad, 0) + n
        return riders, live

    @staticmethod
    def members(riders: dict) -> set:
        """Every (b_pad, p_pad, s_pad) a wake can dispatch for handed
        pairs of which ``riders[s_pad]`` ride each subscriber pad: the
        stream rungs up to that count's own, each packet pad."""
        out = set()
        for s_pad, n in riders.items():
            b_pad = 1
            while True:
                out.update((b_pad, p_pad, s_pad) for p_pad in PACKET_PADS)
                if b_pad >= n:
                    break
                b_pad <<= 2
        return out

    def _rows_per(self, n_rows: int) -> int:
        """Rows each shard holds of a pass of ``n_rows`` stream rows; 0
        for a pass that is not sharded — no mesh, or one row, which has
        nothing to shard (three shards of pure padding would run in
        full beside it) and rides the single-device program on the
        mesh's first device.  A sharded pass is as tall as its rung of
        the stream ladder, split over the devices, so a member of the
        closed set is ONE program under a mesh as off it; entry i rides
        shard ``i % n_dev``, row ``i // n_dev`` of it — dealt round the
        devices, so five rows over four fill all four."""
        if self._sharded_step is None or n_rows < 2:
            return 0
        return staging.rows_per_shard(_stream_pad(n_rows),
                                      len(self._mesh_devices))

    def _program(self, b_pad: int, p_pad: int, s_pad: int,
                 rows_per: int) -> tuple:
        """A program as ``_built`` keys it."""
        if rows_per:
            return (b_pad, p_pad, s_pad, len(self._mesh_devices))
        return (b_pad, p_pad, s_pad)

    def _step(self, win, state, rows_per: int):
        """One pass over host arrays, uploaded whole (the prime, a
        load): sharded over the mesh, or on one device."""
        import jax

        if rows_per:
            return self._sharded_step(
                jax.device_put(win, self._src_sharding),
                jax.device_put(state, self._src_sharding))
        return megabatch_window_step(jax.device_put(win, self._home), state)

    def programs(self, riders: dict) -> set:
        """The programs this scheduler runs for ``members(riders)``, as
        ``_built`` keys them: off a mesh a member is its program; under
        one the first rung's is the single-device program a row alone
        rides, and every other rung's the sharded program of that many
        rows (of one row a device where the devices outnumber them)."""
        n_dev = len(self._mesh_devices)
        out = set()
        for b_pad, p_pad, s_pad in self.members(riders):
            rows_per = self._rows_per(b_pad)
            out.add(self._program(rows_per * n_dev or b_pad, p_pad, s_pad,
                                  rows_per))
        return out

    def _build_ahead(self, riders: dict, live: bool) -> None:
        """Trace and load the programs the handed pairs can reach and
        this process has not built, when a pair joins past a rung or
        brings a new subscriber pad — not at the wake that first stacks
        that many streams.  Before any media (players join before their
        camera's first packet) nothing waits behind a build and every
        missing program loads now; once media flows (``live``) one a
        wake, so a relayed packet waits behind at most one.  A zero pass
        per program, fetched; nothing is staged or installed.  Under a
        mesh the same rule loads what the mesh dispatches
        (``programs``)."""
        missing = sorted(self.programs(riders) - self._built)
        for prog in missing[:1] if live else missing:
            b_pad, p_pad, s_pad = prog[:3]
            np.asarray(self._step(
                np.zeros((b_pad, p_pad, staging.ROW_STRIDE), np.uint8),
                np.zeros((b_pad, s_pad, STATE_COLS), np.uint32),
                len(prog) > 3))
            self._built.add(prog)

    # ------------------------------------------------------------- collect
    def _collect(self, walk, now_ms: int) -> list:
        work = []
        tracked = self._tracked
        for stream, eng in walk:
            ring = stream.rtp_ring
            p = eng.plan(stream, now_ms)
            rec = tracked[stream]
            rec.epoch = stream.plan_epoch
            fast, key = p.fast, p.key
            if not fast:
                rec.head = ring.head
                continue
            base = rec.head
            floor = max(ring.tail, ring.head - self.MAX_STAGE_ROWS)
            if base is None or base > ring.head or base < floor:
                base = rec.head = floor    # new/recycled/fell-behind
            n_new = ring.head - base
            need_params = (key != eng._params_key
                           and not (eng.megabatch_params is not None
                                    and eng.megabatch_params[0] == key))
            if n_new <= 0 and not need_params:
                continue                   # idle stream: zero device work
            while n_new > PACKET_PADS[-1]:
                # fell behind: further rows of the widest pad
                work.append((stream, eng, fast, key, base, PACKET_PADS[-1]))
                base += PACKET_PADS[-1]
                n_new -= PACKET_PADS[-1]
            work.append((stream, eng, fast, key, base, n_new))
        return work

    # ------------------------------------------------------------ dispatch
    def _buffer(self, b_pad: int, p_pad: int) -> np.ndarray:
        pool = self._free.get((b_pad, p_pad))
        if pool:
            return pool.pop()
        return np.zeros((b_pad, p_pad, staging.ROW_STRIDE), np.uint8)

    def _recycle(self, buf: np.ndarray) -> None:
        pool = self._free.setdefault((buf.shape[0], buf.shape[1]), [])
        if len(pool) < self._pool_cap:     # double buffer per shape (per
            pool.append(buf)               # shard under a mesh); a cold
            # shape's extras are GC'd

    def _install_segment(self, eng, key, seg, base=None,
                         shard: int = -1) -> bool:
        """Oracle-check one scattered segment and install it as the
        engine's params override — the ONE definition the harvest (both
        dispatch paths) and the synchronous prime go through, so a
        tightened mismatch check can never apply to one path and not
        the other.  ``shard`` records which mesh device computed the
        segment (-1 = single-device/prime).  Returns False (and counts
        the mismatch) on device/host divergence; the stream then falls
        back to per-stream stepping."""
        seq_off, ts_off, ssrc, chan, kf = seg
        host = _host_affine_params(key)
        if not (np.array_equal(seq_off[0], host[0])
                and np.array_equal(ts_off[0], host[1])
                and np.array_equal(ssrc[0], host[2])
                and np.array_equal(chan[0], host[3])):
            self.mismatches += 1
            obs.MEGABATCH_WIRE_MISMATCH.inc()
            eng.megabatch_params = None
            eng.megabatch_shard = -1
            return False
        eng.megabatch_params = (key, (seq_off, ts_off, ssrc, chan))
        eng.megabatch_shard = shard
        if base is not None and kf >= 0:
            # parity with the per-stream query, which maintains this
            # diagnostic field — an owned stream must not hold it stale
            # just because the scheduler took over
            eng.last_newest_keyframe = max(eng.last_newest_keyframe,
                                           base + kf)
        return True

    def _note_pass(self, n_streams: int, h2d_bytes: int, real: int,
                   staged: int, by_shard=None) -> None:
        """One dispatched pass: ``real`` (new packet, subscriber) cells
        of the ``staged`` = b_pad × p_pad × s_pad its program computes.
        ``by_shard``: a sharded pass's rows on each device.  A mesh
        scheduler's per-device counters take every pass — one that is
        not sharded once, on the device that ran it whole."""
        self.passes += 1
        self.streams_coalesced += n_streams
        obs.MEGABATCH_PASSES.inc()
        obs.MEGABATCH_STREAMS.inc(n_streams)
        obs.TPU_H2D_BYTES.inc(h2d_bytes)
        if real:
            obs.MEGABATCH_CELLS.inc(real, kind="real")
        obs.MEGABATCH_CELLS.inc(staged, kind="staged")
        if self._sharded_step is None:
            return
        if by_shard is None:
            by_shard = (n_streams,)
        else:
            self.sharded_passes += 1
            obs.MEGABATCH_SHARDED_STREAMS.inc(n_streams)
        for k, n in enumerate(by_shard):
            if n:                          # pad-only shards count nothing
                obs.MEGABATCH_DEVICE_PASSES.inc(device=str(k))
                obs.MEGABATCH_DEVICE_STREAMS.inc(n, device=str(k))

    def _packed_state(self, stream, fast, key) -> np.ndarray:
        rec = self._tracked[stream]
        if rec.state is not None and rec.state[0] == key:
            return rec.state[1]
        packed = np.asarray(pack_output_state(fast))
        rec.state = (key, packed)
        return packed

    def _dispatch_bucket(self, entries, p_pad: int,
                         s_pad: int) -> tuple[int, int]:
        import jax

        if INJECTOR.active:
            # chaos site: a stacked-dispatch failure BEFORE staging
            # mutates cursors — the pump catches it, degrades the wake
            # to per-stream stepping and charges the ladder
            INJECTOR.device_dispatch("megabatch.dispatch")
        if self._rows_per(len(entries)):
            return self._dispatch_bucket_mesh(entries, p_pad, s_pad)
        b_pad = _stream_pad(len(entries))
        self._built.add((b_pad, p_pad, s_pad))
        bucket = f"{b_pad}x{p_pad}x{s_pad}"
        tok = TRACER.open("megabatch.gather", "tpu", streams=len(entries),
                          bucket=bucket, sharded=0)
        win = self._buffer(b_pad, p_pad)
        state = np.zeros((b_pad, s_pad, STATE_COLS), np.uint32)
        recs = []
        real = 0
        for i, (stream, eng, fast, key, base, n_new) in enumerate(entries):
            staging.gather_window(stream.rtp_ring, base, n_new, win[i])
            state[i, :len(fast)] = self._packed_state(stream, fast, key)
            # (a stream's further rows may ride a pass dispatched after
            # its last row's: the cursor only moves on)
            rec = self._tracked[stream]
            rec.head = max(rec.head, base + n_new)
            recs.append((stream, eng, key, len(fast), base,
                         self._home_shard))
            real += n_new * len(fast)
        if b_pad > len(entries):
            win[len(entries):] = 0         # bucket padding rows
        gather_ns = TRACER.lap(tok)
        built0 = builds()
        tok = TRACER.open("megabatch.h2d", "tpu", streams=len(entries),
                          bucket=bucket, sharded=0)
        dwin = jax.device_put(win, self._home)
        res = megabatch_window_step(dwin, state)
        res.copy_to_host_async()
        h2d_ns = TRACER.lap(tok)
        if builds() != built0:
            # bucket-growth retrace: a build is never a phase sample
            h2d_ns = 0
        self._inflight.append(
            _InFlight(res, recs, win, time.perf_counter_ns()))
        self._note_pass(len({id(e[0]) for e in entries}),
                        win.nbytes + state.nbytes, real,
                        b_pad * p_pad * s_pad)
        return gather_ns, h2d_ns

    def _dispatch_bucket_mesh(self, entries, p_pad: int,
                              s_pad: int) -> tuple[int, int]:
        """One bucket sharded over the serving mesh's ``src`` axis.

        Entries are dealt round the devices (``_rows_per``): entry i
        rides row i // n_dev of shard i % n_dev, and shard k owns the
        contiguous row block [k·rows_per, (k+1)·rows_per) of the global
        pass, staged into its OWN host
        buffer so each device's upload is one contiguous H2D.  The
        global window is assembled from the per-device uploads without
        any host-side concatenation (``make_array_from_single_device_
        arrays``), then donated to the sharded step.  Trailing rows —
        bucket pow2 padding AND the uneven-stream-count remainder — are
        zero windows + zero state, the dryrun's pad-mask rule."""
        import jax

        n_dev = len(self._mesh_devices)
        rows_per = self._rows_per(len(entries))
        b_pad = rows_per * n_dev
        self._built.add(self._program(b_pad, p_pad, s_pad, rows_per))
        bucket = f"{b_pad}x{p_pad}x{s_pad}"
        tok = TRACER.open("megabatch.gather", "tpu", streams=len(entries),
                          bucket=bucket, sharded=1)
        shard_bufs = [self._buffer(rows_per, p_pad) for _ in range(n_dev)]
        state = np.zeros((b_pad, s_pad, STATE_COLS), np.uint32)
        recs = []
        real = 0
        filled = [0] * n_dev
        for i, (stream, eng, fast, key, base, n_new) in enumerate(entries):
            real += n_new * len(fast)
            k, r = i % n_dev, i // n_dev
            staging.gather_window(stream.rtp_ring, base, n_new,
                                  shard_bufs[k][r])
            state[k * rows_per + r, :len(fast)] = self._packed_state(
                stream, fast, key)
            rec = self._tracked[stream]
            rec.head = max(rec.head, base + n_new)
            recs.append((stream, eng, key, len(fast), base, k))
            filled[k] = r + 1
        for k, buf in enumerate(shard_bufs):
            if filled[k] < rows_per:
                buf[filled[k]:] = 0        # shard/bucket padding rows
        gather_ns = TRACER.lap(tok)
        built0 = builds()
        tok = TRACER.open("megabatch.h2d", "tpu", streams=len(entries),
                          bucket=bucket, sharded=1)
        win_s = self._src_sharding
        arrs = []
        for k, buf in enumerate(shard_bufs):
            tok_k = TRACER.open("megabatch.shard_h2d", "tpu", device=k,
                                rows=filled[k])
            arrs.append(jax.device_put(buf, self._mesh_devices[k]))
            self._shard_lap(tok_k, "h2d")
        dwin = jax.make_array_from_single_device_arrays(
            (b_pad, p_pad, staging.ROW_STRIDE), win_s, arrs)
        dstate = jax.device_put(state, win_s)
        res = self._sharded_step(dwin, dstate)
        res.copy_to_host_async()
        h2d_ns = TRACER.lap(tok)
        if builds() != built0:
            h2d_ns = 0                     # a build is never a phase sample
        self._inflight.append(
            _InFlight(res, recs, shard_bufs, time.perf_counter_ns(),
                      rows_per=rows_per))
        self._note_pass(len({id(e[0]) for e in entries}),
                        sum(b.nbytes for b in shard_bufs) + state.nbytes,
                        real, b_pad * p_pad * s_pad, by_shard=filled)
        return gather_ns, h2d_ns

    @staticmethod
    def _shard_lap(tok, phase: str) -> int:
        """Close one shard's span and give its lap to the per-device
        phase histogram: one clock for both (none with the bracket
        off)."""
        ns = TRACER.lap(tok)
        if tok is not None:
            obs.MEGABATCH_DEVICE_PHASE_SECONDS.observe(
                ns / 1e9, device=str(tok.args["device"]), phase=phase)
        return ns

    def _consume_mesh(self, inf: _InFlight, ready: bool) -> tuple[int, int]:
        """Harvest one mesh pass per device: fetch each shard's packed
        slice independently and scatter/install ONLY the streams that
        shard computed — the egress scatter keyed by device the tentpole
        requires, so a single misplaced shard can corrupt at most its
        own block (and the host oracle then catches every row of it).
        Returns (installed, fetch_ns) where fetch_ns covers the
        wait+copy brackets only (scatter/install stays unphased)."""
        import jax

        installed = 0
        fetch_ns = 0
        shards = sorted(inf.result.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        for k, sh in enumerate(shards):
            ents = inf.entries[k::len(shards)]     # as they were dealt
            if not ents:
                continue               # padding-only shard: nothing to fetch
            dat = sh.data
            # whole array ready ⇒ every shard is
            shard_ready = ready or dat.is_ready()
            if not shard_ready:
                # the un-hidden remainder of THIS device's compute (a
                # skewed shard shows up here, not smeared over the mesh)
                tok = TRACER.open("megabatch.shard_wait", "tpu", device=k,
                                  rows=len(ents))
                jax.block_until_ready(dat)
                fetch_ns += self._shard_lap(tok, "device_step")
            tok = TRACER.open("megabatch.shard_fetch", "tpu", device=k,
                              rows=len(ents), ready=int(shard_ready))
            packed = np.asarray(dat)
            fetch_ns += self._shard_lap(tok, "d2h")
            obs.TPU_D2H_BYTES.inc(packed.nbytes)
            segs = scatter_affine_segments(
                packed, [n for (_s, _e, _k, n, _b, _sh) in ents])
            for (stream, eng, key, n_fast, base, shard), seg in zip(ents,
                                                                    segs):
                if self._install_segment(eng, key, seg, base=base,
                                         shard=shard):
                    installed += 1
        return installed, fetch_ns

    # ------------------------------------------------------------- harvest
    def _harvest(self, *, force: bool = False) -> int:
        if not self._inflight:
            return 0
        span = TRACER.open("megabatch.harvest", "tpu",
                           inflight=len(self._inflight))
        keep: list[_InFlight] = []
        installed = 0
        overlap_ns = 0
        d2h_ns = 0
        for inf in self._inflight:
            age = time.perf_counter_ns() - inf.dispatch_ns
            ready = inf.result.is_ready()
            if not (ready or force or age >= self.FORCE_FETCH_NS):
                keep.append(inf)           # never stall the wake on it
                continue
            tok = TRACER.open("megabatch.fetch", "tpu",
                              streams=len(inf.entries), ready=int(ready))
            if inf.rows_per is not None:
                got, fetch_ns = self._consume_mesh(inf, ready)
                installed += got
                TRACER.close(tok)
            else:
                packed = np.asarray(inf.result)
                fetch_ns = TRACER.lap(tok)
                obs.TPU_D2H_BYTES.inc(packed.nbytes)
                segs = scatter_affine_segments(
                    packed, [n for (_s, _e, _k, n, _b, _sh)
                             in inf.entries])
                for (stream, eng, key, n_fast, base, _sh), seg in zip(
                        inf.entries, segs):
                    if self._install_segment(eng, key, seg, base=base,
                                             shard=_sh):
                        installed += 1
            # honest split (PR 3 attribution discipline): a READY result's
            # fetch is the d2h copy, same meaning as the engine's d2h; a
            # NOT-ready fetch (forced/aged) is the pipeline's un-hidden
            # remainder — h2d_overlap.  The scatter/oracle/install work
            # is host bookkeeping and stays unphased.
            if ready:
                d2h_ns += fetch_ns
            else:
                overlap_ns += fetch_ns
            for b in (inf.buf if isinstance(inf.buf, list)
                      else (inf.buf,)):
                self._recycle(b)
            self.harvests += 1
        self._inflight = keep
        total = TRACER.lap(span, installed=installed)
        if overlap_ns or d2h_ns:
            PROFILER.account_pass(
                "megabatch", total,
                {"h2d_overlap": overlap_ns, "d2h": d2h_ns})
        return installed

    # -------------------------------------------------------------- stats
    def drain(self) -> int:
        """Force-fetch everything in flight (tests/teardown)."""
        return self._harvest(force=True)

    def stats(self) -> dict:
        return {
            "wakes": self.wakes,
            "passes": self.passes,
            "sharded_passes": self.sharded_passes,
            "mesh_devices": len(self._mesh_devices),
            "streams_coalesced": self.streams_coalesced,
            "streams_per_pass": round(
                self.streams_coalesced / self.passes, 2) if self.passes
            else 0.0,
            "inflight": len(self._inflight),
            "harvests": self.harvests,
            "mismatches": self.mismatches,
            "programs": len(self._built),
        }


__all__ = ["MegabatchScheduler"]
