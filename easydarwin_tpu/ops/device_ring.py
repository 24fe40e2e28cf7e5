"""Device-resident GOP ring — HBM-resident packet window state.

The reference's GOP retention is host-side: the reflector queue +
``CKeyFrameCache`` byte cache (2 MB cap, ``keyframecache.h:45-72``; SURVEY
§5 maps it to "a fixed-shape device-resident GOP ring buffer").  Here the
classification window lives in HBM: ingest appends only the *new* packets'
prefixes each pass (``jax.lax.dynamic_update_slice`` under donation, so XLA
updates in place), and the query step runs over the resident window without
re-staging it.  H2D per pass is O(new packets), not O(window).

State arrays (all device-resident):
  prefix  [C, W] uint8 · length [C] int32 · age base [C] int32 · head scalar
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .parse import PARSE_PREFIX


class RingState(NamedTuple):
    prefix: jnp.ndarray        # [C, W] uint8
    length: jnp.ndarray        # [C] int32
    arrival: jnp.ndarray       # [C] int32 (ms, relative epoch)
    head: jnp.ndarray          # scalar int32: total packets ever appended


def init_ring(capacity: int, width: int = PARSE_PREFIX) -> RingState:
    return RingState(
        prefix=jnp.zeros((capacity, width), dtype=jnp.uint8),
        length=jnp.zeros(capacity, dtype=jnp.int32),
        arrival=jnp.zeros(capacity, dtype=jnp.int32),
        head=jnp.zeros((), dtype=jnp.int32))


# Each served jitted step has a name that is its own, as the program's
# name and as a ``jax.named_scope`` around its body: a profiler trace
# says ``device_ring_append``, not ``append`` (ISSUE 25).

@functools.partial(jax.jit, donate_argnums=(0,))
def device_ring_append(state: RingState, new_prefix: jnp.ndarray,
                       new_length: jnp.ndarray, new_arrival: jnp.ndarray,
                       n_new: jnp.ndarray) -> RingState:
    """Append up to ``new_prefix.shape[0]`` packets (first ``n_new`` valid).

    The batch is written at ``head % C`` with wraparound handled by a double
    dynamic_update_slice (split at the seam).  Donated: XLA reuses the HBM
    buffers in place.
    """
    with jax.named_scope("device_ring_append"):
        C = state.prefix.shape[0]
        B = new_prefix.shape[0]
        pos = state.head % C
        idx = (pos + jnp.arange(B, dtype=jnp.int32)) % C
        keep = jnp.arange(B, dtype=jnp.int32) < n_new
        # scatter rows (B is small; scatter handles the seam uniformly)
        prefix = state.prefix.at[idx].set(
            jnp.where(keep[:, None], new_prefix, state.prefix[idx]))
        length = state.length.at[idx].set(
            jnp.where(keep, new_length, state.length[idx]))
        arrival = state.arrival.at[idx].set(
            jnp.where(keep, new_arrival, state.arrival[idx]))
        return RingState(prefix, length, arrival, state.head + n_new)


append = device_ring_append


@jax.jit
def device_ring_query(state: RingState, out_state: jnp.ndarray,
                      now_ms: jnp.ndarray) -> dict:
    """Run the affine relay step over the resident window.

    Returns the ``relay_affine_step`` outputs plus the newest keyframe as an
    *absolute* packet id (-1 if none in window) — device-side equivalent of
    the host ring's keyframe bookmark.
    """
    from .fanout import relay_affine_step

    with jax.named_scope("device_ring_query"):
        C = state.prefix.shape[0]
        res = relay_affine_step(state.prefix, state.length, out_state)
        # slot index → absolute id: ids in [head-C, head); slot s holds id
        # head - ((head - s - 1) % C) - 1
        slots = jnp.arange(C, dtype=jnp.int32)
        abs_id = state.head - ((state.head - slots - 1) % C) - 1
        valid = (abs_id >= 0) & (abs_id < state.head) & (state.length > 0)
        kf = res["keyframe_first"] & valid
        newest_kf_abs = jnp.max(jnp.where(kf, abs_id, -1))
        age = jnp.asarray(now_ms, jnp.int32) - state.arrival
        return {**res, "abs_id": abs_id, "valid": valid,
                "newest_keyframe_abs": newest_kf_abs, "age_ms": age}


query = device_ring_query
