"""Transform-domain ops for the transcode ladder (BASELINE config 5).

The reference has no transcoder (EasyHLS was closed-source — SURVEY §2.3);
this is new, TPU-first machinery: 8×8 DCT/IDCT expressed as ONE batched
``[N, 64] @ [64, 64]`` matmul via the Kronecker identity
``vec(Cᵀ·X·C) = (Cᵀ ⊗ Cᵀ)·vec(X)`` — MXU-shaped (the per-block 8×8 matmul
form would waste the 128×128 systolic array), arbitrary batch, bf16-friendly.
Quantization follows the JPEG/H.263 convention (base table × quality scale).

Scope note: bitstream entropy (CAVLC/CABAC) decode/encode stays on the host
(native tier); the device owns the dense transform/quant math, which is
where the FLOPs are.  ``decode_blocks_pallas`` fuses dequant → IDCT →
+128 level shift → clip in one VMEM pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# ----------------------------------------------------------------- DCT bases

def dct_matrix() -> np.ndarray:
    """Orthonormal 8-point DCT-II matrix C: y = C @ x."""
    C = np.zeros((8, 8), dtype=np.float64)
    for k in range(8):
        a = np.sqrt(1 / 8) if k == 0 else np.sqrt(2 / 8)
        for n in range(8):
            C[k, n] = a * np.cos(np.pi * (2 * n + 1) * k / 16)
    return C


@functools.lru_cache(maxsize=None)
def _kron_mats() -> tuple[np.ndarray, np.ndarray]:
    """(forward, inverse) 64×64 operators on row-major vec'd blocks.

    forward: vec(C X Cᵀ) = (C ⊗ C) vec(X)   (2-D DCT of spatial block X)
    inverse: vec(Cᵀ Y C) = (Cᵀ ⊗ Cᵀ) vec(Y)
    """
    C = dct_matrix()
    fwd = np.kron(C, C)
    inv = np.kron(C.T, C.T)
    return (fwd.astype(np.float32), inv.astype(np.float32))


def dct_blocks(x: jnp.ndarray) -> jnp.ndarray:
    """[N, 64] spatial → [N, 64] coefficients (row-major 8×8 blocks)."""
    fwd, _ = _kron_mats()
    return x @ jnp.asarray(fwd).T


def idct_blocks(y: jnp.ndarray) -> jnp.ndarray:
    """[N, 64] coefficients → [N, 64] spatial."""
    _, inv = _kron_mats()
    return y @ jnp.asarray(inv).T


# -------------------------------------------------------------- quantization

#: JPEG Annex K luminance base table, row-major (the de-facto baseline the
#: reference-era tooling used for intra quant).
JPEG_LUMA_QT = np.array([
    16, 11, 10, 16, 24, 40, 51, 61,
    12, 12, 14, 19, 26, 58, 60, 55,
    14, 13, 16, 24, 40, 57, 69, 56,
    14, 17, 22, 29, 51, 87, 80, 62,
    18, 22, 37, 56, 68, 109, 103, 77,
    24, 35, 55, 64, 81, 104, 113, 92,
    49, 64, 78, 87, 103, 121, 120, 101,
    72, 92, 95, 98, 112, 100, 103, 99], dtype=np.float32)


def quality_table(quality: int) -> np.ndarray:
    """JPEG quality (1-100) → effective quant table [64]."""
    quality = int(np.clip(quality, 1, 100))
    scale = 5000 / quality if quality < 50 else 200 - 2 * quality
    qt = np.floor((JPEG_LUMA_QT * scale + 50) / 100)
    return np.clip(qt, 1, 255).astype(np.float32)


@jax.jit
def quantize(coef: jnp.ndarray, qtable: jnp.ndarray) -> jnp.ndarray:
    """[N,64] float coefficients → int32 levels (round-half-away)."""
    return jnp.round(coef / qtable[None, :]).astype(jnp.int32)


@jax.jit
def dequantize(levels: jnp.ndarray, qtable: jnp.ndarray) -> jnp.ndarray:
    return levels.astype(jnp.float32) * qtable[None, :]


# ------------------------------------------------------------------- zigzag

@functools.lru_cache(maxsize=None)
def zigzag_order() -> np.ndarray:
    """[64] indices mapping raster order → zigzag scan order."""
    # odd diagonals run down-left (i ascending), even ones up-right
    order = sorted(((i + j, i if (i + j) % 2 else j, i, j)
                    for i in range(8) for j in range(8)))
    return np.array([i * 8 + j for (_, _, i, j) in order], dtype=np.int32)


def to_zigzag(levels: jnp.ndarray) -> jnp.ndarray:
    return levels[:, jnp.asarray(zigzag_order())]


def from_zigzag(z: jnp.ndarray) -> jnp.ndarray:
    inv = np.argsort(zigzag_order())
    return z[:, jnp.asarray(inv)]


def to_zigzag_np(natural: np.ndarray) -> np.ndarray:
    """Host-side ``to_zigzag`` ([..., 64] natural → zigzag) — the entropy
    codec and ladder reorder on the host, off the device round-trip."""
    return natural[..., zigzag_order()]


def from_zigzag_np(z: np.ndarray) -> np.ndarray:
    out = np.empty_like(z)
    out[..., zigzag_order()] = z
    return out


# ----------------------------------------------------- encode / decode paths

@jax.jit
def encode_blocks(pixels: jnp.ndarray, qtable: jnp.ndarray) -> jnp.ndarray:
    """uint8 [N,64] spatial blocks → int32 quantized coefficient levels."""
    x = pixels.astype(jnp.float32) - 128.0
    return quantize(dct_blocks(x), qtable)


@jax.jit
def decode_blocks(levels: jnp.ndarray, qtable: jnp.ndarray) -> jnp.ndarray:
    """int32 levels → uint8 [N,64] spatial blocks (dequant+IDCT+shift+clip)."""
    x = idct_blocks(dequantize(levels, qtable)) + 128.0
    return jnp.clip(jnp.round(x), 0, 255).astype(jnp.uint8)


@jax.jit
def requantize(levels: jnp.ndarray, qtable_in: jnp.ndarray,
               qtable_out: jnp.ndarray) -> jnp.ndarray:
    """Transform-domain bitrate step-down: dequant with the source table,
    requant with a coarser one — the inner op of the transcode ladder
    (no IDCT round-trip needed for same-resolution rungs)."""
    return quantize(dequantize(levels, qtable_in), qtable_out)


def transcode_ladder(levels: jnp.ndarray, qtable_in: jnp.ndarray,
                     qualities: tuple[int, ...]) -> list[jnp.ndarray]:
    """One decode-side coefficient block set → N ladder rungs."""
    return [requantize(levels, qtable_in, jnp.asarray(quality_table(q)))
            for q in qualities]


# ------------------------------------------------------------ pallas kernel

TILE = 256     # blocks per grid step ([256, 64] f32 tiles in VMEM)


def _decode_kernel(levels_ref, qt_ref, inv_ref, out_ref):
    x = levels_ref[:].astype(jnp.float32) * qt_ref[:]      # dequant (bcast)
    y = jax.lax.dot_general(x, inv_ref[:],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # int32 out, narrowed to uint8 by the wrapper: Mosaic on a TPU v5e
    # refuses the in-kernel cast ("Unsupported cast: float32 -> uint8")
    out_ref[:] = jnp.clip(jnp.round(y + 128.0), 0, 255).astype(jnp.int32)


def decode_blocks_pallas(levels: jnp.ndarray, qtable: jnp.ndarray,
                         *, interpret: bool = False) -> jnp.ndarray:
    """Fused dequant→IDCT→shift→clip as one Pallas kernel.

    levels [N,64] int32 (N a multiple of TILE — pad with zero blocks),
    qtable [1,64] f32.  The 64×64 inverse operator rides along in VMEM and
    hits the MXU via dot_general.  ``interpret=True`` runs on CPU for tests.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = levels.shape[0]
    pad = (-n) % TILE
    if pad:
        levels = jnp.concatenate(
            [levels, jnp.zeros((pad, 64), levels.dtype)], axis=0)
    _, inv = _kron_mats()
    grid = levels.shape[0] // TILE
    out = pl.pallas_call(
        _decode_kernel,
        out_shape=jax.ShapeDtypeStruct((levels.shape[0], 64), jnp.int32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((TILE, 64), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 64), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((64, 64), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((TILE, 64), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(levels, qtable.reshape(1, 64).astype(jnp.float32),
      jnp.asarray(inv))          # contraction ((1,),(1,)) ≡ x @ inv.T
    return out[:n].astype(jnp.uint8)


# ------------------------------------------------- DCT-domain 2x downscale

@functools.lru_cache(maxsize=None)
def downscale2x_operator() -> np.ndarray:
    """[256, 64] linear map: a 2×2 quad of dequantized 8×8 DCT blocks →
    the 8×8 DCT block of the half-resolution tile.

    Built numerically as DCT ∘ avgpool2 ∘ IDCT over the 16×16 tile the
    quad reconstructs; being a fixed linear operator it turns resolution
    downscaling into ONE ``[N, 256] @ [256, 64]`` matmul on the MXU — no
    pixel round-trip ever materializes.  Quad layout is row-major:
    [top-left, top-right, bottom-left, bottom-right], each block vec'd
    row-major (natural order, not zigzag)."""
    _, inv = _kron_mats()                      # [64, 64] coeff → spatial
    eye = np.eye(256, dtype=np.float64)
    quads = eye.reshape(256, 2, 2, 8, 8)       # [in, qy, qx, 8, 8]
    # IDCT each 8×8 block of each basis vector
    blocks = quads.reshape(256, 4, 64) @ inv.astype(np.float64).T
    blocks = blocks.reshape(256, 2, 2, 8, 8)
    # assemble 16×16 tiles
    tile = np.zeros((256, 16, 16))
    for qy in range(2):
        for qx in range(2):
            tile[:, qy * 8:qy * 8 + 8, qx * 8:qx * 8 + 8] = \
                blocks[:, qy, qx]
    # 2×2 average pool → 8×8
    pooled = tile.reshape(256, 8, 2, 8, 2).mean(axis=(2, 4))
    # forward DCT of the pooled tile
    fwd, _ = _kron_mats()
    out = pooled.reshape(256, 64) @ fwd.astype(np.float64).T
    return out.astype(np.float32)              # [256, 64]


@jax.jit
def downscale2x_blocks(quads: jnp.ndarray) -> jnp.ndarray:
    """[N, 256] dequantized coefficient quads → [N, 64] half-res
    coefficients (natural order)."""
    M = jnp.asarray(downscale2x_operator())
    return jnp.matmul(quads, M, precision="highest")


@jax.jit
def requantize_downscale2x(quads: jnp.ndarray, qtable_in: jnp.ndarray,
                           qtable_out: jnp.ndarray) -> jnp.ndarray:
    """Quantized quad levels → quantized half-res levels: dequant (input
    table broadcast over the 4 blocks), one MXU matmul, requant."""
    deq = quads.reshape(-1, 4, 64) * qtable_in[None, None, :]
    out = jnp.matmul(deq.reshape(-1, 256),
                     jnp.asarray(downscale2x_operator()),
                     precision="highest")
    return jnp.round(out / qtable_out[None, :]).astype(jnp.int32)


# ------------------------------------------------- H.264 4x4 requant (int32)

@jax.jit
def h264_requant(levels: jnp.ndarray, qp_in: jnp.ndarray,
                 qp_out: jnp.ndarray) -> jnp.ndarray:
    """H.264 4×4 transform-domain requant, BIT-EXACT against
    ``codecs.h264_transform.requant_levels_scalar``: a +6k QP step is
    exactly a rounded k-bit right shift of each level (Qstep doubles
    every 6 QP with identical qp%6 multiplier rows):

      l' = sign(l)·((|l| + 2^k/3) >> k),  k = (qp_out − qp_in) // 6.

    levels: int32 [N, 16] block levels (any scan order — the op is
    elementwise).  qp_in: [N] per-block source QP (per-MB qp_delta
    support); qp_out: [N] or scalar target QP, qp_out ≡ qp_in (mod 6).
    The entropy recode around this stays on the host
    (``codecs.h264_requant``) — the same host⇄device split as the MJPEG
    ladder.  The clip bound is the shared overflow contract
    (``codecs.h264_transform.LEVEL_CLIP``)."""
    from ..codecs.h264_transform import LEVEL_CLIP
    lev = jnp.clip(levels.astype(jnp.int32), -LEVEL_CLIP, LEVEL_CLIP)
    k = ((qp_out - qp_in.astype(jnp.int32)) // 6)[:, None]
    f = (jnp.int32(1) << k) // 3
    out = jnp.sign(lev) * ((jnp.abs(lev) + f) >> k)
    return out.astype(jnp.int32)


# --------------------------------------------- H.264 chroma requant (int32)

def _h2x2(v: jnp.ndarray) -> jnp.ndarray:
    """Elementwise 2×2 Hadamard (H2·c·H2) of [..., 4] raster quads."""
    a, b, c, d = v[..., 0], v[..., 1], v[..., 2], v[..., 3]
    return jnp.stack([a + b + c + d, a - b + c - d,
                      a + b - c - d, a - b - c + d], axis=-1)


def _inv_core_1d(a, b, c, d):
    e0, e1 = a + c, a - c
    e2, e3 = (b >> 1) - d, b + (d >> 1)
    return e0 + e3, e1 + e2, e1 - e2, e0 - e3


def _fwd_core_1d(x0, x1, x2, x3):
    t0, t1, t2, t3 = x0 + x3, x1 + x2, x1 - x2, x0 - x3
    return t0 + t1, 2 * t3 + t2, t0 - t1, t3 - 2 * t2


def _rows_cols(w: jnp.ndarray, fn) -> jnp.ndarray:
    """Apply a 4-point butterfly over rows then columns of [..., 4, 4]."""
    r = jnp.stack(fn(*(w[..., i] for i in range(4))), axis=-1)
    return jnp.stack(fn(*(r[..., i, :] for i in range(4))), axis=-2)


@jax.jit
def h264_requant_chroma(dc: jnp.ndarray, ac: jnp.ndarray,
                        qpc_in: jnp.ndarray, qpc_out: jnp.ndarray
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched chroma requant, BIT-EXACT against
    ``codecs.h264_transform.requant_chroma_scalar`` (same clips, same
    integer ops, int32 throughout — the scalar module documents why the
    clips make int32 sufficient).

    dc: int32 [N, 4] chroma DC levels (2×2 raster) per MB component;
    ac: int32 [N, 4, 15] per-block zigzag AC tails; qpc_in/qpc_out: [N].
    Per-row three-way dispatch (identity / exact +6k shift / open-loop
    integer round trip) computed dense and selected — branchless, so one
    trace serves every mix of Table 8-15 deltas."""
    from ..codecs.h264_transform import (LEVEL_CLIP, MF, RES_CLIP, V,
                                         W_CLIP, ZIGZAG4, _CLS)
    n = dc.shape[0]
    dc = jnp.clip(dc.astype(jnp.int32), -LEVEL_CLIP, LEVEL_CLIP)
    ac = jnp.clip(ac.astype(jnp.int32), -LEVEL_CLIP, LEVEL_CLIP)
    qi = qpc_in.astype(jnp.int32)
    qo = qpc_out.astype(jnp.int32)
    delta = qo - qi

    # --- exact-shift arm (delta ≡ 0 mod 6; k=0 degenerates to identity)
    k = jnp.maximum(delta // 6, 0)
    f6 = (jnp.int32(1) << k) // 3

    def shift(x, kk, ff):
        return jnp.sign(x) * ((jnp.abs(x) + ff) >> kk)

    dc_shift = shift(dc, k[:, None], f6[:, None])
    ac_shift = shift(ac, k[:, None, None], f6[:, None, None])

    # --- general arm: dequant (8.5.11 DC + 8.5.12 AC) → inverse core →
    #     forward core → requant at qpc_out
    vpos = jnp.asarray(np.stack([V[m][_CLS] for m in range(6)]),
                       dtype=jnp.int32)                       # [6, 16]
    mfpos = jnp.asarray(np.stack([MF[m][_CLS] for m in range(6)]),
                        dtype=jnp.int32)
    v0 = jnp.asarray(V[:, 0], dtype=jnp.int32)
    mf0 = jnp.asarray(MF[:, 0], dtype=jnp.int32)
    si, so = qi // 6, qo // 6
    mi, mo = qi % 6, qo % 6

    dcc = ((_h2x2(dc) * v0[mi][:, None]) << si[:, None]) >> 1
    lev = jnp.zeros((n, 4, 16), jnp.int32)
    lev = lev.at[:, :, jnp.asarray(ZIGZAG4[1:])].set(ac)
    w = (lev * vpos[mi][:, None, :]) << si[:, None, None]
    w = w.at[:, :, 0].set(dcc)
    x = _rows_cols(w.reshape(n, 4, 4, 4), _inv_core_1d)
    x = jnp.clip((x + 32) >> 6, -RES_CLIP, RES_CLIP)
    big = jnp.clip(_rows_cols(x, _fwd_core_1d),
                   -W_CLIP, W_CLIP).reshape(n, 4, 16)
    qbits = 15 + so
    off = (jnp.int32(1) << qbits) // 3
    q = jnp.sign(big) * ((jnp.abs(big) * mfpos[mo][:, None, :]
                          + off[:, None, None]) >> qbits[:, None, None])
    q = jnp.clip(q, -LEVEL_CLIP, LEVEL_CLIP)
    ac_gen = q[:, :, jnp.asarray(ZIGZAG4[1:])]
    f2 = jnp.clip(_h2x2(jnp.clip(big[:, :, 0], -W_CLIP, W_CLIP)),
                  -W_CLIP, W_CLIP)
    dc_gen = jnp.sign(f2) * ((jnp.abs(f2) * mf0[mo][:, None]
                              + 2 * off[:, None]) >> (qbits + 1)[:, None])
    dc_gen = jnp.clip(dc_gen, -LEVEL_CLIP, LEVEL_CLIP)

    use_shift = (delta % 6 == 0)
    dc_out = jnp.where(use_shift[:, None], dc_shift, dc_gen)
    ac_out = jnp.where(use_shift[:, None, None], ac_shift, ac_gen)
    return dc_out.astype(jnp.int32), ac_out.astype(jnp.int32)
