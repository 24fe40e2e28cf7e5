"""Per-subscriber fan-out as one batched device computation.

Replaces the reference's hot double loop (``ReflectorSender::ReflectPackets``
→ ``SendPacketsToOutput`` → per-output ``WritePacket`` memcpy,
``ReflectorStream.cpp:1024-1185``) with a single ``[S, P]`` broadcast:

* seq rebase   ``(src_seq − base_src_seq + out_seq_start) mod 2¹⁶``
* ts rebase    ``(src_ts − base_src_ts + out_ts_start) mod 2³²``
* SSRC swap    per-output SSRC
* eligibility  ``arrival + bucket(s)·bucket_delay ≤ now`` — the reference's
  staggered-bucket send waves (cpp:1088-1119) as a mask instead of a loop.

The rendered result is ``[S, P, 12]`` big-endian header bytes; byte 0/1
(V/P/X/CC, M/PT) are taken verbatim from the source packet, so
``header ∥ packet[12:]`` is bit-identical to the CPU oracle's
``rtp.rewrite_header`` output.  vmap over the subscriber axis keeps the
kernel readable; XLA fuses the whole thing into one elementwise pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: columns of the per-output state matrix: ssrc, base_src_seq,
#: base_src_ts, out_seq_start, out_ts_start, chan (the RTSP-interleave
#: channel byte for TCP outputs; CHAN_NONE for datagram subscribers).
#: The channel rides the SAME device pass as the UDP rewrite params —
#: the 4-byte ``$``-framing header is affine in (len, channel), so one
#: stacked pass emits every subscriber's egress params, TCP included
#: (ISSUE 14).
STATE_COLS = 6
#: chan column sentinel for outputs with no interleave framing
CHAN_NONE = 0xFFFFFFFF


def pack_output_state(outputs) -> jnp.ndarray:
    """Host helper: RelayOutput list → [S, STATE_COLS] uint32 state."""
    import numpy as np
    st = np.zeros((len(outputs), STATE_COLS), dtype=np.uint32)
    for i, o in enumerate(outputs):
        rw = o.rewrite
        ch = getattr(o, "interleave_chan", None)
        st[i] = (rw.ssrc, max(rw.base_src_seq, 0), max(rw.base_src_ts, 0),
                 rw.out_seq_start, rw.out_ts_start,
                 CHAN_NONE if ch is None else (ch & 0xFF))
    return st


def _rewrite_one(state: jnp.ndarray, seq: jnp.ndarray, ts: jnp.ndarray):
    """One subscriber: state [STATE_COLS] uint32, seq/ts [P] →
    (seq', ts', ssrc) [P]."""
    ssrc, base_seq, base_ts, seq0, ts0 = (state[i] for i in range(5))
    new_seq = (seq - base_seq + seq0) & jnp.uint32(0xFFFF)
    new_ts = ts - base_ts + ts0          # uint32 wraps naturally
    return new_seq, new_ts, jnp.broadcast_to(ssrc, seq.shape)


@jax.jit
def fanout_headers(b01: jnp.ndarray, seq: jnp.ndarray, ts: jnp.ndarray,
                   out_state: jnp.ndarray) -> jnp.ndarray:
    """Render rewritten headers.

    b01: [P, 2] uint8 (source bytes 0-1) · seq: [P] uint32 · ts: [P] uint32 ·
    out_state: [S, 5] uint32 → [S, P, 12] uint8.
    """
    seq = seq.astype(jnp.uint32)
    ts = ts.astype(jnp.uint32)
    new_seq, new_ts, ssrc = jax.vmap(_rewrite_one, in_axes=(0, None, None))(
        out_state.astype(jnp.uint32), seq, ts)
    S, P = new_seq.shape

    def be_bytes(v: jnp.ndarray, n: int) -> list[jnp.ndarray]:
        return [((v >> (8 * (n - 1 - i))) & 0xFF).astype(jnp.uint8)
                for i in range(n)]

    cols = ([jnp.broadcast_to(b01[None, :, 0], (S, P)),
             jnp.broadcast_to(b01[None, :, 1], (S, P))]
            + be_bytes(new_seq, 2) + be_bytes(new_ts, 4) + be_bytes(ssrc, 4))
    return jnp.stack(cols, axis=-1)


@jax.jit
def eligibility(age_ms: jnp.ndarray, bucket_of_output: jnp.ndarray,
                bucket_delay_ms) -> jnp.ndarray:
    """[S, P] bool: packet p may be sent to output s this pass
    (per-bucket delay stagger, ``ReflectorStream.cpp:1088-1119``).

    ``age_ms`` is ``now − arrival`` per packet (int32 — relative times keep
    the device step free of int64)."""
    min_age = (bucket_of_output.astype(jnp.int32) *
               jnp.asarray(bucket_delay_ms, jnp.int32))
    return age_ms[None, :].astype(jnp.int32) >= min_age[:, None]


def affine_params(out_state: jnp.ndarray):
    """[S, STATE_COLS] state → per-output (seq_off, ts_off, ssrc, chan).

    The single definition of the affine rewrite in terms of the state
    layout; every consumer (device step, flagship pipeline) goes through
    here so the column meanings live in one place.  ``chan`` is the
    interleave-framing channel byte (CHAN_NONE for UDP outputs) — a
    pure passthrough on the device, but riding the pass means the host
    oracle check covers the byte that frames the TCP wire."""
    st = out_state.astype(jnp.uint32)
    return ((st[:, 3] - st[:, 1]) & jnp.uint32(0xFFFF),
            st[:, 4] - st[:, 2],
            st[:, 0],
            st[:, 5])


@jax.jit
def relay_affine_step(prefix: jnp.ndarray, length: jnp.ndarray,
                      out_state: jnp.ndarray) -> dict[str, jnp.ndarray]:
    """Bandwidth-lean device step: O(S+P) results instead of O(S·P).

    The per-subscriber rewrite is *affine*: ``seq' = seq + (out_seq_start −
    base_src_seq)``, ``ts' = ts + (out_ts_start − base_src_ts)``, SSRC
    constant per output.  So the device returns per-packet parsed fields and
    per-output offset triples; the egress path (native sender or the
    vectorized host renderer in ``relay.fanout``) applies the patch while
    scattering — at memory bandwidth, with no per-unit host *compute*.
    D2H shrinks from ``S·P·12`` bytes to ``4·(2P + 3S)``.
    """
    from .gop import newest_keyframe
    from .parse import parse_packets

    fields = parse_packets(prefix, length)
    valid = length > 0
    kf = fields["keyframe_first"] & valid
    seq_off, ts_off, ssrc, chan = affine_params(out_state)
    return {
        "seq": fields["seq"].astype(jnp.uint32),
        "timestamp": fields["timestamp"],
        "keyframe_first": kf,
        "frame_first": fields["frame_first"],
        "frame_last": fields["frame_last"],
        "newest_keyframe": newest_keyframe(kf, valid),
        "seq_off": seq_off,
        "ts_off": ts_off,
        "ssrc": ssrc,
        "chan": chan,
    }


@jax.jit
def relay_affine_step_packed(prefix: jnp.ndarray, length: jnp.ndarray,
                             out_state: jnp.ndarray) -> jnp.ndarray:
    """``relay_affine_step`` over a leading source axis, with the egress
    params packed into ONE uint32 array ``[N_SRC, 4·S + 1]``:
    ``seq_off[S] ∥ ts_off[S] ∥ ssrc[S] ∥ chan[S] ∥ newest_keyframe``.

    One array means one D2H transfer instead of five; combined with
    ``copy_to_host_async`` the whole fetch hides behind the previous
    window's egress."""
    out = jax.vmap(relay_affine_step)(prefix, length, out_state)
    kf = out["newest_keyframe"].astype(jnp.uint32)[:, None]
    return jnp.concatenate(
        [out["seq_off"], out["ts_off"], out["ssrc"], out["chan"], kf],
        axis=-1)


#: bytes appended to each packet prefix to carry its length (le32)
WINDOW_EXTRA = 4


def pack_window(prefix, length):
    """Host helper: [..., P, 96] prefixes + [..., P] lengths → ONE uint8
    array [..., P, 100] (length rides as 4 trailing le bytes).

    Fusing the two H2D arrays makes the per-window upload ONE
    transfer."""
    import numpy as np
    prefix = np.asarray(prefix, np.uint8)
    length = np.ascontiguousarray(length, "<u4")  # le bytes match the decode
    lb = length[..., None].view(np.uint8)
    return np.concatenate([prefix, lb], axis=-1)


@jax.jit
def relay_affine_step_window(window: jnp.ndarray,
                             out_state: jnp.ndarray) -> jnp.ndarray:
    """``relay_affine_step_packed`` taking the fused ``pack_window`` layout.

    ``window``: [N_SRC, P, 96+4] uint8 — the only per-pass H2D transfer;
    ``out_state``: [N_SRC, S, STATE_COLS] uint32 — subscriber state, kept
    device-resident by the caller (it changes on subscribe/unsubscribe, not
    per window, so it should never ride the per-window upload)."""
    with jax.named_scope("relay_affine_step_window"):
        prefix = window[:, :, :96]
        lb = window[:, :, 96:].astype(jnp.uint32)
        length = (lb[..., 0] | (lb[..., 1] << 8) | (lb[..., 2] << 16)
                  | (lb[..., 3] << 24)).astype(jnp.int32)
        return relay_affine_step_packed(prefix, length, out_state)


def unpack_affine(packed, n_sub: int):
    """Host-side views into the packed egress params:
    ``(seq_off, ts_off, ssrc, chan, newest_keyframe)``.

    The newest-keyframe column is re-cast to int32 so the -1 "no keyframe
    in window" sentinel survives the uint32 wire format (it rides as
    0xFFFFFFFF and wraps back here)."""
    return (packed[:, :n_sub], packed[:, n_sub:2 * n_sub],
            packed[:, 2 * n_sub:3 * n_sub],
            packed[:, 3 * n_sub:4 * n_sub],
            packed[:, 4 * n_sub].astype("int32"))


@jax.jit
def relay_batch_step(prefix: jnp.ndarray, length: jnp.ndarray,
                     age_ms: jnp.ndarray, out_state: jnp.ndarray,
                     bucket_of_output: jnp.ndarray,
                     bucket_delay_ms) -> dict[str, jnp.ndarray]:
    """The full device step for one source: parse → keyframe scan → fan-out.

    This is the unit the driver compile-checks (``__graft_entry__.entry``) and
    that ``parallel.mesh`` shards over (sources × subscriber-shards).
    """
    from .gop import newest_keyframe
    from .parse import parse_packets

    with jax.named_scope("relay_batch_step"):
        fields = parse_packets(prefix, length)
        headers = fanout_headers(prefix[:, :2], fields["seq"],
                                 fields["timestamp"], out_state)
        mask = eligibility(age_ms, bucket_of_output, bucket_delay_ms)
        valid = (length > 0)
        sendable = (length >= 12)  # runts are never relayed (skipped host-side)
        return {
            "headers": headers,
            "mask": mask & sendable[None, :],
            "keyframe_first": fields["keyframe_first"],
            "newest_keyframe": newest_keyframe(fields["keyframe_first"],
                                               valid),
            "frame_last": fields["frame_last"],
        }
