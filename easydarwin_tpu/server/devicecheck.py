"""``GET /api/v1/devicecheck`` — every served jitted step, on THIS device.

The relay path a smoke drives only reaches the kernels its traffic
selects (the stacked affine pass, mostly).  The other jitted steps the
server can call — the per-stream resident ring, the batch-header step,
the GF(256) parity matmul (wire FEC and erasure storage), the two H.264
requant transforms — are compiled and run here once, at the shapes the
server stages them in, each compared with the host oracle the serving
code already checks it against.  It runs in the server process because
that is the process that holds the chip.

One row per step: ``{"step", "shape", "ok", "first_s", "again_s"}``.
Both are host-clock times of the WHOLE check — inputs built, device
call, result fetched, host oracle computed and compared: ``first_s``
with the trace and the compile (or cache load) in it, ``again_s`` the
same check once more without them.  Their difference is the compile
work; neither is a device time.  A step that raises reports
``ok: false`` with the exception text — never a skipped row.
"""

from __future__ import annotations

import time

import numpy as np


def _gop_packets(rng, n_frames: int = 6, ssrc: int = 0x1234):
    """A small pushed stream: SPS + PPS + an FU-A IDR, then FU-A P
    frames — the packet mix the relay classifies."""
    from ..protocol import nalu
    pkts: list[bytes] = []
    seq, ts = 100, 9000
    for f in range(n_frames):
        nals = []
        if f == 0:
            nals += [bytes((0x67,)) + rng.bytes(20),
                     bytes((0x68,)) + rng.bytes(6),
                     bytes((0x65,)) + rng.bytes(5000)]
        else:
            nals.append(bytes((0x41,)) + rng.bytes(3000))
        for i, nal in enumerate(nals):
            out = nalu.packetize_h264(nal, seq=seq, timestamp=ts, ssrc=ssrc,
                                      marker_on_last=i == len(nals) - 1)
            pkts += out
            seq = (seq + len(out)) & 0xFFFF
        ts += 3000
    return pkts


def _stage(pkts, p_pad: int):
    from ..ops.parse import PARSE_PREFIX
    prefix = np.zeros((p_pad, PARSE_PREFIX), np.uint8)
    length = np.zeros(p_pad, np.int32)
    for i, p in enumerate(pkts):
        w = min(len(p), PARSE_PREFIX)
        prefix[i, :w] = np.frombuffer(p[:w], np.uint8)
        length[i] = len(p)
    return prefix, length


def _outputs(rng, n: int):
    from ..relay.output import CollectingOutput
    outs = []
    for _ in range(n):
        o = CollectingOutput(ssrc=int(rng.integers(0, 2**32)),
                             out_seq_start=int(rng.integers(0, 2**16)),
                             out_ts_start=int(rng.integers(0, 2**32)))
        o.rewrite.base_src_seq = 100
        o.rewrite.base_src_ts = 9000
        outs.append(o)
    return outs


def _host_fields(pkts):
    """The scalar protocol oracle's view of each packet."""
    from ..protocol import nalu, rtp
    return (np.array([rtp.peek_seq(p) for p in pkts], np.uint32),
            np.array([rtp.peek_timestamp(p) for p in pkts], np.uint32),
            np.array([nalu.is_keyframe_first_packet(p) for p in pkts]))


def _check_device_ring(rng) -> tuple[str, bool]:
    """``device_ring.append`` + ``query`` at the engine's shapes (ring
    capacity 4096, 16-row append pad, 256-subscriber state)."""
    from ..ops import device_ring
    from ..ops.fanout import pack_output_state
    from ..relay.fanout import params_key
    from ..relay.megabatch import _host_affine_params
    pkts = _gop_packets(rng)
    outs = _outputs(rng, 256)
    st = device_ring.init_ring(4096)
    for lo in range(0, len(pkts), 16):
        chunk = pkts[lo:lo + 16]
        prefix, length = _stage(chunk, 16)
        st = device_ring.append(st, prefix, length,
                                np.full(16, lo, np.int32),
                                np.int32(len(chunk)))
    q = device_ring.query(st, np.asarray(pack_output_state(outs)),
                          np.int32(1000))
    seq, ts, kf = _host_fields(pkts)
    n = len(pkts)
    host = _host_affine_params(params_key(outs))
    ok = (int(st.head) == n
          and np.array_equal(np.asarray(q["seq"])[:n], seq)
          and np.array_equal(np.asarray(q["timestamp"])[:n], ts)
          and np.array_equal(np.asarray(q["keyframe_first"])[:n], kf)
          and int(q["newest_keyframe_abs"]) == int(np.flatnonzero(kf)[-1])
          and all(np.array_equal(np.asarray(q[k]), h) for k, h in
                  zip(("seq_off", "ts_off", "ssrc", "chan"), host)))
    return f"ring[4096x96] append[16] query[256] pkts={n}", ok


def _check_affine_window(rng) -> tuple[str, bool]:
    """``relay_affine_step_window`` (the megabatch stacked pass) at the
    16-stream x 64-packet x 256-subscriber bucket."""
    from ..models.relay_pipeline import (megabatch_window_step,
                                         scatter_affine_segments)
    from ..ops.fanout import STATE_COLS, pack_output_state, pack_window
    from ..relay.fanout import params_key
    from ..relay.megabatch import _host_affine_params
    import jax
    pkts = _gop_packets(rng)
    prefix, length = _stage(pkts, 64)
    win = np.broadcast_to(pack_window(prefix, length)[None],
                          (16, 64, prefix.shape[1] + 4)).copy()
    state = np.zeros((16, 256, STATE_COLS), np.uint32)
    keys = []
    for b in range(16):
        outs = _outputs(rng, 256)
        state[b] = np.asarray(pack_output_state(outs))
        keys.append(params_key(outs))
    packed = np.asarray(megabatch_window_step(jax.device_put(win), state))
    segs = scatter_affine_segments(packed, [256] * 16)
    kf = _host_fields(pkts)[2]
    ok = True
    for key, (seq_off, ts_off, ssrc, chan, newest) in zip(keys, segs):
        host = _host_affine_params(key)
        ok &= (np.array_equal(seq_off[0], host[0])
               and np.array_equal(ts_off[0], host[1])
               and np.array_equal(ssrc[0], host[2])
               and np.array_equal(chan[0], host[3])
               and newest == int(np.flatnonzero(kf)[-1]))
    return "window[16x64x100] state[16x256x6]", bool(ok)


def _check_batch_step(rng) -> tuple[str, bool]:
    """``relay_batch_step`` (TCP/meta/thinned outputs) at a 64-packet
    window x 8 outputs, against the host header render."""
    from ..ops.fanout import pack_output_state, relay_batch_step
    from ..relay.fanout import params_key, render_headers
    from ..relay.megabatch import _host_affine_params
    pkts = _gop_packets(rng)
    prefix, length = _stage(pkts, 64)
    outs = _outputs(rng, 8)
    age = np.zeros(64, np.int32)
    age[:len(pkts)] = np.arange(len(pkts), dtype=np.int32)[::-1] * 9
    buckets = np.arange(8, dtype=np.int32) % 3
    res = relay_batch_step(prefix, length, age,
                           np.asarray(pack_output_state(outs)), buckets,
                           np.int32(73))
    seq, ts, kf = _host_fields(pkts)
    n = len(pkts)
    seq_off, ts_off, ssrc, _chan = _host_affine_params(params_key(outs))
    want = render_headers(prefix[:n, :2], seq, ts, seq_off, ts_off, ssrc)
    want_mask = (age[None, :n] >= buckets[:, None] * 73)
    ok = (np.array_equal(np.asarray(res["headers"])[:, :n], want)
          and np.array_equal(np.asarray(res["mask"])[:, :n], want_mask)
          and int(res["newest_keyframe"]) == int(np.flatnonzero(kf)[-1]))
    return "window[64x96] outputs[8]", bool(ok)


def _check_parity(rng, k: int, r: int, width: int) -> tuple[str, bool]:
    """``fec_parity_window_step`` against ``relay.fec.gf_matmul``."""
    from ..models.relay_pipeline import fec_parity_window_step
    from ..relay.fec import coeff_rows, gf_matmul
    rows = rng.integers(0, 256, size=(k, width), dtype=np.uint8)
    rows[:, width - 600:] = 0               # the zero-padded byte tail
    coeff = coeff_rows(range(k), r)
    dev = np.asarray(fec_parity_window_step(rows, coeff))
    return (f"rows[{k}x{width}] coeff[{r}x{k}]",
            bool(np.array_equal(dev, gf_matmul(coeff, rows))))


def _check_requant_luma(rng) -> tuple[str, bool]:
    """``ops.transform.h264_requant`` against the scalar level shift."""
    from ..codecs.h264_requant import _scalar_batch, device_batch
    n = 16384
    levels = rng.integers(-2100, 2100, size=(n, 16)).astype(np.int64)
    qp_in = rng.integers(10, 40, size=n).astype(np.int64)
    qp_out = qp_in + 6 * rng.integers(1, 4, size=n)     # q6/q12/q18 rungs
    dev = device_batch(levels, qp_in, qp_out)
    return (f"levels[{n}x16]",
            bool(np.array_equal(dev, _scalar_batch(levels, qp_in, qp_out))))


def _check_requant_chroma(rng) -> tuple[str, bool]:
    """``ops.transform.h264_requant_chroma`` against the scalar chroma
    round trip (identity, exact-shift and general arms all drawn)."""
    from ..codecs.h264_requant import (_scalar_batch_chroma,
                                       device_batch_chroma)
    n = 2048
    dc = rng.integers(-2100, 2100, size=(n, 4)).astype(np.int64)
    ac = rng.integers(-300, 300, size=(n, 4, 15)).astype(np.int64)
    qin = rng.integers(10, 36, size=n).astype(np.int64)
    qout = np.minimum(qin + rng.integers(0, 13, size=n), 39)
    d, a = device_batch_chroma(dc, ac, qin, qout)
    hd, ha = _scalar_batch_chroma(dc, ac, qin, qout)
    return (f"dc[{n}x4] ac[{n}x4x15]",
            bool(np.array_equal(d, hd) and np.array_equal(a, ha)))


#: (step name, check) in the order they run; names are the jitted
#: functions' own, so a trace or a later PR finds them by grep
CHECKS = (
    ("device_ring.append+query", _check_device_ring),
    ("relay_affine_step_window", _check_affine_window),
    ("relay_batch_step", _check_batch_step),
    ("fec_parity_window_step[wire k=16]",
     lambda rng: _check_parity(rng, 16, 4, 2048)),
    ("fec_parity_window_step[storage k=4 m=2]",
     lambda rng: _check_parity(rng, 4, 2, 131072)),
    ("h264_requant", _check_requant_luma),
    ("h264_requant_chroma", _check_requant_chroma),
)


def run(seed: int = 0) -> dict:
    """Run every check; the device is named beside the verdicts."""
    from .. import device
    rows = []
    for name, fn in CHECKS:
        row = {"step": name}
        try:
            t0 = time.perf_counter()
            row["shape"], ok = fn(np.random.default_rng(seed))
            t1 = time.perf_counter()
            _shape, ok2 = fn(np.random.default_rng(seed))
            row["first_s"] = round(t1 - t0, 4)
            row["again_s"] = round(time.perf_counter() - t1, 4)
            row["ok"] = bool(ok and ok2)
        except Exception as e:      # a refused compile IS the finding
            row["ok"] = False
            row["error"] = f"{type(e).__name__}: {e}"[:2000]
        rows.append(row)
    return {"device": device.resolve(),
            "ok": all(r["ok"] for r in rows), "steps": rows}
