"""The flagship relay pipeline: one configurable, jittable device step.

Wraps the ops tier into a shape-stable callable used by the graft entry,
the bench, and the server's TPU engine.  Two parse backends (fused Pallas
kernel or the jnp reference — bit-identical, differentially tested) and
two output modes:

* ``affine`` (production): O(S+P) rewrite parameters, egress renders;
* ``headers``: full [S, P, 12] rendered headers on device.
"""

from __future__ import annotations

import functools
import time
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..device import listen_builds
from ..obs import PROFILER, TRACER, t0_of
from ..obs.profile import builds
from ..ops import fanout as fanout_ops
from ..ops import gop as gop_ops
from ..ops.parse import PARSE_PREFIX, parse_packets
from ..ops.parse_pallas import parse_packets_pallas


@dataclass(frozen=True)
class RelayPipelineConfig:
    window: int = 256            # packets per source per pass (P)
    subscribers: int = 256       # outputs per source (S)
    prefix_width: int = PARSE_PREFIX
    bucket_delay_ms: int = 73
    use_pallas_parse: bool = False
    mode: str = "affine"         # "affine" | "headers"
    codec: str = "h264"          # "h264" | "mjpeg" (per-stream classifier)


class RelayPipeline:
    def __init__(self, config: RelayPipelineConfig | None = None):
        self.config = config or RelayPipelineConfig()
        #: session correlation id for spans this pipeline records; a
        #: caller that serves one session (graft/bench harnesses, an
        #: embedding engine) stamps it — or passes ``trace_id=`` per
        #: call — so one Perfetto query selects that session across
        #: pipeline/engine/egress hops.  Unset, spans stay uncorrelated
        self.trace_id: str | None = None
        # jit recompiles per arg shape, and a pass that held a build is
        # compile noise, not a phase sample: builds() tells
        listen_builds()
        self._step = jax.jit(functools.partial(
            _pipeline_step,
            use_pallas=self.config.use_pallas_parse,
            mode=self.config.mode,
            bucket_delay_ms=self.config.bucket_delay_ms,
            codec=self.config.codec))

    def __call__(self, prefix, length, age_ms, out_state, buckets, *,
                 trace_id: str | None = None):
        # Phase-bracketed pass (ISSUE 3 satellite).  The pre-profiler
        # timing stopped at dispatch return: jax dispatch is async, so
        # the device pass itself completed inside whichever LATER timer
        # first touched the result — the egress bracket, usually —
        # inflating egress and zeroing device_step.  The pass total now
        # brackets exactly the work the phases cover (explicit H2D
        # staging + device step incl. block-until-ready), and the
        # profiler's Σ(phases) ≈ total invariant keeps it that way.
        span_args = {"mode": self.config.mode}
        tid = trace_id or self.trace_id
        if tid is not None:
            span_args["trace_id"] = tid
        span = TRACER.open("pipeline.step", "tpu", **span_args)
        t0 = t0_of(span)
        args = (prefix, length, age_ms, out_state, buckets)
        if not PROFILER.enabled:
            # profiler off: the original async-dispatch hot path — no
            # explicit staging, no block-until-ready serialization; the
            # device pass overlaps whatever the caller does next
            out = self._step(*args)
            dur = TRACER.close(span) - t0
            obs.TPU_PASS_SECONDS.observe(dur / 1e9,
                                         stage="pipeline_dispatch")
            self._count_bytes(args, out_state, length)
            return out
        built0 = builds()
        staged = jax.device_put(args)
        t_h2d = time.perf_counter_ns()
        out = self._step(*staged)
        t_disp = time.perf_counter_ns()
        jax.block_until_ready(out)
        t_done = TRACER.close(span)
        # dispatch-side accounting (the host cost the pump loop pays to
        # launch one step, compile excluded after the first trace)
        obs.TPU_PASS_SECONDS.observe((t_disp - t_h2d) / 1e9,
                                     stage="pipeline_dispatch")
        self._count_bytes(args, out_state, length)
        if builds() == built0:
            # (a pass that held a build — the cold trace, a new shape —
            # stays out of the phase histograms, whose p99 would keep
            # the compile outlier forever: the fanout engine's rule.)
            # The checked total stamps AFTER the bookkeeping above, so
            # the Σ(phases) ≈ total invariant guards something real:
            # unphased work creeping into this bracket trips the drift
            # counter once it outgrows the tolerance
            total = time.perf_counter_ns() - t0
            PROFILER.account_pass(
                "pipeline", total,
                {"h2d": t_h2d - t0, "device_step": t_done - t_h2d},
                check=True)
        return out

    def _count_bytes(self, args, out_state, length) -> None:
        for a in args:
            obs.TPU_H2D_BYTES.inc(getattr(a, "nbytes", 0))
        if self.config.mode == "headers":
            obs.TPU_HEADERS_RENDERED.inc(out_state.shape[-2]
                                         * length.shape[-1])

    @property
    def step_fn(self):
        return self._step

    def example_args(self, n_src: int = 1):
        from ..parallel.mesh import example_batch
        c = self.config
        prefix, length, age, out_state, buckets = example_batch(
            n_src=n_src, n_sub=c.subscribers, n_pkt=c.window,
            width=c.prefix_width)
        if n_src == 1:
            return (prefix[0], length[0], age[0], out_state[0], buckets[0])
        return (prefix, length, age, out_state, buckets)


# ------------------------------------------------------------- megabatch
# The cross-stream stacked pass (relay/megabatch.py): every eligible
# stream's staged window rides ONE device dispatch per shape bucket
# instead of one per stream.  The leading axis is the STREAM axis; the
# fused pack_window layout means the whole bucket is a single H2D
# transfer.  The staging buffer is donated — once the upload lands, XLA
# may reuse its HBM for the pass's temporaries/result instead of holding
# both live (the scheduler's host-side double buffer is the only copy
# that persists).

@functools.partial(jax.jit, donate_argnums=(0,))
def megabatch_window_step(window, out_state):
    """Stacked relay device pass over a leading stream axis.

    ``window``: [B, P, 96+4] uint8 (``ops.staging`` fused rows, pow2-
    padded in every dimension) · ``out_state``: [B, S, STATE_COLS]
    uint32 → packed egress params [B, 4·S + 1] uint32
    (``seq_off[S] ∥ ts_off[S] ∥ ssrc[S] ∥ chan[S] ∥ newest_keyframe``).

    The window buffer is donated; XLA's "donated buffer was not usable"
    warning is filtered ONCE at import (below) because the uint8 input
    can never alias the uint32 output — the donation still releases the
    staged upload the moment the pass consumes it, which is the point.
    A per-call ``warnings.catch_warnings`` would mutate process-global
    filter state on the pump hot path and is not thread-safe.
    """
    from ..ops.fanout import relay_affine_step_window
    with jax.named_scope("megabatch_window_step"):
        return relay_affine_step_window(window, out_state)


warnings.filterwarnings("ignore", message=".*[Dd]onat.*")


#: built sharded megabatch steps, keyed by the mesh's device ids — a
#: rebuilt-but-identical mesh (server restart path in tests) reuses the
#: jitted step instead of paying a recompile per scheduler instance
_SHARDED_STEPS: dict[tuple, object] = {}


def sharded_megabatch_step(mesh):
    """``megabatch_window_step`` placed across a relay mesh's ``src`` axis.

    The stacked pass is a pure vmap over the leading STREAM axis —
    per-stream parse/affine math with zero cross-stream dependencies —
    so sharding that axis over ``src`` partitions the pass with no
    collectives at all: each device parses and rewrites only its block
    of streams.  In/out shardings reuse the dryrun-proven spec shape
    (``parallel.mesh``: leading axis on ``src``, everything else
    replicated per shard), and ``out_shardings`` keeps the packed result
    sharded so the scheduler's harvest can fetch each device's slice
    independently (per-device D2H, keyed egress scatter).

    The window buffer is donated exactly as in the single-device step:
    the scheduler assembles it from per-device staging buffers
    (``jax.make_array_from_single_device_arrays``), so each shard's
    upload is one contiguous H2D from host memory that device alone
    reads.

    One kernel, one name: what is jitted is ``megabatch_window_step``'s
    own body, so the program — and the profiler's module on every
    device's plane — is ``megabatch_window_step`` whatever places it,
    and a reader of the kernel's trace needs no second name.  A plane
    shows its shard's shapes.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    # keyed by ids AND axis layout: the same devices reshaped (2,2,2)
    # vs (8,1,1) partition the leading axis differently
    key = (tuple(d.id for d in mesh.devices.flat), mesh.devices.shape)
    step = _SHARDED_STEPS.get(key)
    if step is None:
        win_s = NamedSharding(mesh, P("src", None, None))
        out_s = NamedSharding(mesh, P("src", None))
        step = jax.jit(megabatch_window_step.__wrapped__,
                       in_shardings=(win_s, win_s), out_shardings=out_s,
                       donate_argnums=(0,))
        _SHARDED_STEPS[key] = step
    return step


def scatter_affine_segments(packed, n_subs):
    """Segment scatter: split one stacked packed result back into
    per-stream affine param sets.

    ``packed``: the [B, 4·S_pad + 1] device result (any array-like) ·
    ``n_subs``: per-stream REAL subscriber counts (<= S_pad; extra rows
    beyond ``len(n_subs)`` are bucket padding and ignored).  Returns one
    ``(seq_off[1, n], ts_off[1, n], ssrc[1, n], chan[1, n], newest_kf)``
    tuple per stream — the exact ``TpuFanoutEngine._params`` shape,
    contiguous, so the scheduler can install them without further
    massaging.  ``newest_kf`` is the per-stream newest-keyframe SLOT
    index within the staged rows (-1 = none; the uint32 wire sentinel
    wraps back here)."""
    arr = np.asarray(packed)
    s_pad = (arr.shape[1] - 1) // 4
    out = []
    for row, n in zip(arr, n_subs):
        out.append((
            np.ascontiguousarray(row[None, 0:n]),
            np.ascontiguousarray(row[None, s_pad:s_pad + n]),
            np.ascontiguousarray(row[None, 2 * s_pad:2 * s_pad + n]),
            np.ascontiguousarray(row[None, 3 * s_pad:3 * s_pad + n]),
            int(row[4 * s_pad].astype(np.int32))))
    return out


# ------------------------------------------------------------------- FEC
# The lossy-WAN reliability tier's device kernel (ISSUE 11): per-window
# GF(256) parity over fixed-slot ring rows as a log/antilog-table
# matmul.  a·b in GF(256) is antilog[log a + log b] (zero operands
# masked), so the whole parity block is two table gathers, one add and
# an XOR reduction — the same elementwise shape XLA fuses for the
# affine fan-out kernels.  The XOR row (GF(2) parity) is just the
# all-ones coefficient row, so one kernel serves both kinds.  Every row
# the kernel produces is compared against the independent numpy oracle
# (relay.fec.gf_matmul) before it can reach the wire.

@jax.jit
def fec_parity_window_step(rows: jnp.ndarray,
                           coeff: jnp.ndarray) -> jnp.ndarray:
    """GF(256) parity matmul: ``rows [K, B] uint8`` (fixed-slot ring
    rows, zero-padded) × ``coeff [R, K] uint8`` (Vandermonde rows from
    ``relay.fec.coeff_rows``) → ``[R, B] uint8`` parity rows.

    Shapes are pow2-padded by the caller so jit specializations latch
    per (K, R, B) family; zero rows and zero coefficients contribute
    nothing (gf_mul(0, ·) = 0), so window padding is free."""
    from ..relay.fec import GF_EXP512, GF_LOG

    with jax.named_scope("fec_parity_window_step"):
        log = jnp.asarray(GF_LOG)          # [256] int32 (log[0] sentinel)
        exp = jnp.asarray(GF_EXP512)       # [512] int32 (no modulo needed)
        lr = log[rows.astype(jnp.int32)]   # [K, B]
        lc = log[coeff.astype(jnp.int32)]  # [R, K]
        prod = exp[lc[:, :, None] + lr[None, :, :]]           # [R, K, B]
        nz = (rows != 0)[None, :, :] & (coeff != 0)[:, :, None]
        prod = jnp.where(nz, prod, 0).astype(jnp.uint8)
        return jax.lax.reduce(prod, np.uint8(0), jax.lax.bitwise_xor, (1,))


def _pipeline_step(prefix, length, age_ms, out_state, buckets, *,
                   use_pallas: bool, mode: str, bucket_delay_ms: int,
                   codec: str = "h264"):
    # the Pallas kernel is the H.264 hot path; MJPEG classification is a
    # cheap jnp formula, so it always takes the reference path
    from ..ops.parse import normalize_codec
    if normalize_codec(codec) != "h264":
        fields = parse_packets(prefix, length, codec=codec)
    else:
        parse_fn = parse_packets_pallas if use_pallas else parse_packets
        fields = parse_fn(prefix, length)
    valid = length > 0
    kf = fields["keyframe_first"] & valid
    out = {
        "seq": fields["seq"].astype(jnp.uint32),
        "timestamp": fields["timestamp"],
        "keyframe_first": kf,
        "frame_last": fields["frame_last"],
        "newest_keyframe": gop_ops.newest_keyframe(kf, valid),
        "fast_start": gop_ops.fast_start_indices(kf, valid, age_ms, 10_000),
        "mask": (fanout_ops.eligibility(age_ms, buckets, bucket_delay_ms)
                 & (length >= 12)[None, :]),
    }
    if mode == "affine":
        (out["seq_off"], out["ts_off"], out["ssrc"],
         out["chan"]) = fanout_ops.affine_params(out_state)
    else:
        out["headers"] = fanout_ops.fanout_headers(
            prefix[:, :2], fields["seq"], fields["timestamp"], out_state)
    return out
