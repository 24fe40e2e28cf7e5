"""Sharded relay step over a (src, sub, win) device mesh.

Sharding layout (all specs in terms of mesh axes ``src``/``sub``/``win``):

====================  ====================  =============================
array                 shape                 PartitionSpec
====================  ====================  =============================
prefix                [N, P, W]             (src, win, None)
length / age          [N, P]                (src, win)
out_state             [N, S, 5]             (src, sub, None)
bucket_of_output      [N, S]                (src, sub)
headers (out)         [N, S, P, 12]         (src, sub, win, None)
mask (out)            [N, S, P]             (src, sub, win)
newest_keyframe (out) [N]                   (src,)  — pmax over win
====================  ====================  =============================

Fan-out math is (sub × win)-local: each chip renders headers for its
subscriber slice over its packet-window slice with zero communication.  The
only cross-chip dependencies are the keyframe scan (max over the ``win``
axis → ``jax.lax.pmax``) and fleet-level counters (``psum``), both tiny
scalars on ICI.  This is the honest mapping of the reference's scale axes
(SURVEY §2.6): session-parallelism → ``src``, bucket fan-out → ``sub``,
the packet/GOP buffer window → ``win``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import fanout as fanout_ops
from ..ops import parse as parse_ops

AXES = ("src", "sub", "win")


def make_relay_mesh(devices=None, *, src: int | None = None,
                    sub: int | None = None, win: int | None = None) -> Mesh:
    """Build a 3-axis relay mesh over ``devices`` (default: all).

    Unspecified axis sizes are inferred: ``src`` absorbs remaining devices,
    ``sub``/``win`` default to 1 unless given.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = devices.size
    sub = sub or 1
    win = win or 1
    src = src or n // (sub * win)
    if src * sub * win != n:
        raise ValueError(f"mesh {src}x{sub}x{win} != {n} devices")
    return Mesh(devices.reshape(src, sub, win), AXES)


def make_megabatch_mesh(n_devices: int = 0, devices=None) -> Mesh | None:
    """The megabatch scheduler's serving mesh: ``src``-only (streams
    shard over devices; ``sub``/``win`` stay whole because the stacked
    pass is already one fused window per stream).

    ``n_devices``: 0 = every local device, N = the first N local
    devices.  Returns ``None`` when fewer than two devices would
    participate — the caller then keeps the single-device dispatch path
    (a 1-device box degrades to exactly the pre-mesh behavior)."""
    import jax
    devices = list(devices) if devices is not None else jax.local_devices()
    n = len(devices) if n_devices <= 0 else min(n_devices, len(devices))
    if n < 2:
        return None
    return make_relay_mesh(devices[:n], src=n, sub=1, win=1)


def _local_step(prefix, length, age, out_state, buckets, bucket_delay_ms):
    """Per-shard computation: vmap the single-source device step over the
    local source block, then reduce the keyframe scan across ``win``."""

    def one_source(pre, ln, ag, st, bk):
        fields = parse_ops.parse_packets(pre, ln)
        headers = fanout_ops.fanout_headers(pre[:, :2], fields["seq"],
                                            fields["timestamp"], st)
        mask = fanout_ops.eligibility(ag, bk, bucket_delay_ms)
        valid = ln > 0
        kf = fields["keyframe_first"] & valid
        idx = jnp.arange(kf.shape[0], dtype=jnp.int32)
        local_kf = jnp.max(jnp.where(kf, idx, -1))
        return headers, mask & valid[None, :], local_kf

    headers, mask, local_kf = jax.vmap(one_source)(
        prefix, length, age, out_state, buckets)
    # win-axis shards see different window slices: offset local indices by
    # the shard's base, then take the global max over the win axis.
    win_idx = jax.lax.axis_index("win").astype(jnp.int32)
    p_local = prefix.shape[1]
    global_kf = jnp.where(local_kf >= 0, local_kf + win_idx * p_local, -1)
    global_kf = jax.lax.pmax(global_kf, "win")
    # fleet counter: total eligible sends this pass (psum over everything) —
    # feeds the REST getserverinfo load gauge without a host gather.
    eligible = jnp.sum(mask.astype(jnp.int32))
    total_eligible = jax.lax.psum(eligible, AXES)
    return headers, mask, global_kf, total_eligible


def sharded_relay_step(mesh: Mesh, bucket_delay_ms: int = 73):
    """Build the jitted multi-chip relay step for ``mesh``.

    Returns ``fn(prefix, length, age, out_state, buckets)`` →
    ``(headers, mask, newest_keyframe, total_eligible)``.
    """
    in_specs = (P("src", "win", None), P("src", "win"), P("src", "win"),
                P("src", "sub", None), P("src", "sub"))
    out_specs = (P("src", "sub", "win", None), P("src", "sub", "win"),
                 P("src"), P())
    step = jax.shard_map(
        functools.partial(_local_step, bucket_delay_ms=bucket_delay_ms),
        mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return jax.jit(step)


def shard_args(mesh: Mesh, prefix, length, age, out_state, buckets):
    """device_put host arrays with the layout sharded_relay_step expects."""
    specs = (P("src", "win", None), P("src", "win"), P("src", "win"),
             P("src", "sub", None), P("src", "sub"))
    return tuple(jax.device_put(a, NamedSharding(mesh, s))
                 for a, s in zip((prefix, length, age, out_state, buckets),
                                 specs))


def example_batch(n_src=4, n_sub=8, n_pkt=32, width=parse_ops.PARSE_PREFIX,
                  seed=0):
    """Synthetic well-formed relay batch (H.264 single-NAL packets with
    periodic IDRs) for compile checks, dry runs and benches."""
    rng = np.random.default_rng(seed)
    prefix = np.zeros((n_src, n_pkt, width), dtype=np.uint8)
    length = np.full((n_src, n_pkt), 200, dtype=np.int32)
    prefix[:, :, 0] = 0x80                      # V=2
    prefix[:, :, 1] = 96                        # PT=96
    seqs = np.arange(n_pkt, dtype=np.uint16)
    prefix[:, :, 2] = (seqs >> 8)[None, :]
    prefix[:, :, 3] = (seqs & 0xFF)[None, :]
    ts = (np.arange(n_pkt, dtype=np.uint32) * 3000)
    for i in range(4):
        prefix[:, :, 4 + i] = ((ts >> (8 * (3 - i))) & 0xFF)[None, :]
    ssrc = rng.integers(0, 2**32, size=n_src, dtype=np.uint32)
    for i in range(4):
        prefix[:, :, 8 + i] = ((ssrc >> (8 * (3 - i))) & 0xFF)[:, None]
    # NAL header: IDR every 16th packet, else non-IDR slice
    nal = np.where(np.arange(n_pkt) % 16 == 0, (3 << 5) | 5, (3 << 5) | 1)
    prefix[:, :, 12] = nal[None, :]
    age = np.full((n_src, n_pkt), 500, dtype=np.int32)
    out_state = np.zeros((n_src, n_sub, fanout_ops.STATE_COLS), dtype=np.uint32)
    out_state[:, :, 0] = rng.integers(0, 2**32, size=(n_src, n_sub))
    out_state[:, :, 3] = rng.integers(0, 2**16, size=(n_src, n_sub))
    buckets = (np.arange(n_sub, dtype=np.int32) // 16)[None, :].repeat(n_src, 0)
    return prefix, length, age, out_state, buckets
