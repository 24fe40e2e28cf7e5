"""Pallas kernel for the packet parse/classify hot op.

Same contract as ``parse.parse_packets`` (the jnp reference), fused into a
single VMEM pass per tile of packets.  TPU-friendly formulation: the only
data-dependent indices are the header-size-relative byte peeks
(``hs = 12 + 4·CC``), and CC has just 16 possible values — so each needed
byte is computed as a sum of 16 *static* slices masked by ``CC == k``,
avoiding per-row dynamic gathers entirely (Mosaic lowers the whole kernel
to vector selects).

Layout: packets ride the LANE axis.  The wrapper hands the kernel the
prefix transposed, ``[96, N]`` uint8 (byte index on sublanes), and the
lengths as ``[1, N]``; byte ``c`` of every packet in the tile is then the
``[1, TILE]`` row slab ``x[c:c+1, :]``, every intermediate is a
``[1, TILE]`` slab, and the nine result fields are stored as rows of one
``[16, N]`` int32 array (rows 9-15 are sublane padding).  This is the
form Mosaic compiles on a TPU v5e, where all nine fields equalled the
jnp reference in value (CHANGES.md PR 21; ``seq`` is uint32 here and
int32 in the reference, as it always was); the packets-on-sublanes form it
replaced — ``x[:, col]`` 1-D columns, a 1-D ``(TILE,)`` length block,
``(TILE, 4)``/``(TILE, 5)`` outputs — was refused ("XLA layout …
does not match Mosaic layout … for an operand of shape s32[512]").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .parse import PARSE_PREFIX, _AGG_OFFSETS, _KEYFRAME_TYPES, \
    _MIN_CLASSIFY_LEN

TILE = 256
#: result rows, in order; padded to 16 sublanes in the kernel's output
_FIELDS = ("seq", "timestamp", "ssrc", "payload_start", "nal_type",
           "keyframe_first", "frame_first", "frame_last", "marker")
_OUT_ROWS = 16


def _byte_at_hs_plus(x: jnp.ndarray, cc: jnp.ndarray, delta: int
                     ) -> jnp.ndarray:
    """byte ``12 + 4*cc[p] + delta`` of packet p via 16 masked static
    row slabs; ``x`` is ``[W, P]``, the result ``[1, P]``."""
    out = jnp.zeros_like(cc)
    for k in range(16):
        row = 12 + 4 * k + delta
        if row < x.shape[0]:
            out = jnp.where(cc == k, x[row:row + 1, :], out)
    return out


def _parse_tile(x: jnp.ndarray, length: jnp.ndarray) -> list[jnp.ndarray]:
    """``x``: [W, P] int32 bytes · ``length``: [1, P] int32 → the
    ``_FIELDS`` as [1, P] int32 slabs (uint32 fields as bit patterns)."""
    def b(c):
        return x[c:c + 1, :]

    b0, b1 = b(0), b(1)
    cc = b0 & 0x0F
    hs = 12 + 4 * cc
    seq = (b(2) << 8) | b(3)
    ts = (b(4) << 24) | (b(5) << 16) | (b(6) << 8) | b(7)
    ssrc = (b(8) << 24) | (b(9) << 16) | (b(10) << 8) | b(11)
    marker = (b1 & 0x80) != 0
    classifiable = (length >= _MIN_CLASSIFY_LEN) & (length > hs)
    nal0 = _byte_at_hs_plus(x, cc, 0) & 0x1F
    eff = nal0
    for agg_type, off in _AGG_OFFSETS:
        inner = _byte_at_hs_plus(x, cc, off) & 0x1F
        eff = jnp.where((nal0 == agg_type) & (length > hs + off), inner, eff)
    fu_hdr = _byte_at_hs_plus(x, cc, 1)
    is_fu = (nal0 == 28) | (nal0 == 29)
    fu_start = is_fu & (length > hs + 1) & ((fu_hdr & 0x80) != 0)
    eff = jnp.where(fu_start, fu_hdr & 0x1F, eff)
    eff = jnp.where(classifiable, eff, -1)
    kf = jnp.zeros_like(eff, dtype=bool)
    for t in _KEYFRAME_TYPES:
        kf |= eff == t
    kf &= classifiable
    frame_first = classifiable & (((nal0 >= 1) & (nal0 <= 27)) | fu_start)
    frame_last = (length >= _MIN_CLASSIFY_LEN) & marker
    return [seq, ts, ssrc, hs, eff] + [
        v.astype(jnp.int32) for v in (kf, frame_first, frame_last, marker)]


def _kernel(prefix_ref, length_ref, out_ref):
    x = prefix_ref[:].astype(jnp.int32)
    for i, v in enumerate(_parse_tile(x, length_ref[:])):
        out_ref[i:i + 1, :] = v


def parse_packets_pallas(prefix: jnp.ndarray, length: jnp.ndarray,
                         interpret: bool = False
                         ) -> dict[str, jnp.ndarray]:
    """Pallas-fused parse; same results as ``parse.parse_packets``.

    ``interpret=True`` runs the kernel in the Pallas interpreter (the
    CPU tests pass it); the default compiles it with Mosaic, which needs
    a TPU — it never decides to interpret on its own.  Not jitted
    itself — callers jit the surrounding step.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = prefix.shape[0]
    pad = (-n) % TILE
    if pad:
        prefix = jnp.concatenate(
            [prefix, jnp.zeros((pad, prefix.shape[1]), prefix.dtype)])
        length = jnp.concatenate([length, jnp.zeros(pad, length.dtype)])
    n_pad, width = prefix.shape
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((_OUT_ROWS, n_pad), jnp.int32),
        grid=(n_pad // TILE,),
        in_specs=[
            pl.BlockSpec((width, TILE), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, TILE), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_OUT_ROWS, TILE), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(prefix.T, length.astype(jnp.int32).reshape(1, n_pad))
    f = dict(zip(_FIELDS, out[:len(_FIELDS), :n]))
    u32 = lambda v: jax.lax.bitcast_convert_type(v, jnp.uint32)  # noqa: E731
    return {
        "seq": u32(f["seq"]), "timestamp": u32(f["timestamp"]),
        "ssrc": u32(f["ssrc"]), "payload_start": f["payload_start"],
        "nal_type": f["nal_type"],
        "keyframe_first": f["keyframe_first"].astype(bool),
        "frame_first": f["frame_first"].astype(bool),
        "frame_last": f["frame_last"].astype(bool),
        "marker": f["marker"].astype(bool),
    }
