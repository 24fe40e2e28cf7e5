"""Megabatch-on-mesh throughput harness (ISSUE 7).

Drives the cross-stream megabatch scheduler over REAL relay streams and
real UDP egress in two interleaved modes — bucket dispatch sharded over
a ``(src)``-axis device mesh vs the single-device dispatch — and
reports packets/s for both plus the scaling efficiency of the mesh.
One harness, three callers:

* ``bench.py`` — the ``extra.multichip`` section (in-process, on the
  devices the bench holds; a one-device box reports a note);
* ``__graft_entry__.dryrun_multichip`` — so MULTICHIP_r*.json reports
  packets/s from the mesh, not just "dryrun OK";
* ``tools/soak.py --devices N`` — the sharded multi-source section.

Method: two identical stream sets fed identical bursts, stepped
alternately with the order flipped per wake (the same shared-VM drift
cancellation the bench headline uses).  Every wake pushes a fresh burst
per stream so each mode's scheduler has real windows to stage and a
real stacked pass to dispatch — rewound-bookmark capacity loops would
leave the device idle behind the params cache and measure only egress.
``scaling_efficiency`` = mesh rate / (n_devices × single-device rate):
1.0 = linear.  On the forced-host CPU mesh the "devices" are host
threads sharing the same cores, so efficiency well below 1 is expected
there; the figure is meaningful on real chips.
"""

from __future__ import annotations

import socket
import time

import numpy as np


def _mk_streams(n_streams: int, n_sub: int, addrs, send_fd: int, seed: int):
    from ..protocol import sdp
    from ..relay.fanout import TpuFanoutEngine
    from ..relay.output import CollectingOutput
    from ..relay.stream import RelayStream, StreamSettings

    sdp_txt = ("v=0\r\ns=m\r\nt=0 0\r\nm=video 0 RTP/AVP 96\r\n"
               "a=rtpmap:96 H264/90000\r\na=control:trackID=1\r\n")
    rng = np.random.default_rng(seed)
    streams, engines = [], []
    for s in range(n_streams):
        st = RelayStream(sdp.parse(sdp_txt).streams[0],
                         StreamSettings(bucket_delay_ms=0))
        for i in range(n_sub):
            o = CollectingOutput(ssrc=int(rng.integers(0, 2**32)),
                                 out_seq_start=int(rng.integers(0, 2**16)))
            o.native_addr = addrs[(s * n_sub + i) % len(addrs)]
            st.add_output(o)
        streams.append(st)
        engines.append(TpuFanoutEngine(egress_fd=send_fd))
    return streams, engines


def _precompile(sched, n_streams: int, n_sub: int, burst: int) -> None:
    """Trace the stacked step for the shapes the loop will use BEFORE
    any packet carries an arrival stamp (cold jit must not ride the
    timed window — the PR 3/4 latch discipline)."""
    import jax

    from ..models.relay_pipeline import (megabatch_window_step,
                                         sharded_megabatch_step)
    from ..ops.fanout import STATE_COLS
    from ..ops.staging import ROW_STRIDE, rows_per_shard
    from ..relay.fanout import _pow2
    s_pad = _pow2(n_sub, 8)
    p_pad = _pow2(max(burst, 1), 16)   # one burst staged per wake

    def trace_single(pp: int) -> None:
        b = _pow2(n_streams, 1)
        np.asarray(megabatch_window_step(
            jax.device_put(np.zeros((b, pp, ROW_STRIDE), np.uint8)),
            np.zeros((b, s_pad, STATE_COLS), np.uint32)))

    # the synchronous prime (begin_wake) dispatches the UNSHARDED step
    # over 16-row zero windows in BOTH modes — without this trace a mesh
    # run cold-jits the prime inside the first stamped wake and the
    # compile wall time lands in the ingest→wire histograms the soak's
    # SLO checks read
    trace_single(16)
    if sched.mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        n_dev = len(sched._mesh_devices)
        b_pad = rows_per_shard(n_streams, n_dev) * n_dev
        sharding = NamedSharding(sched.mesh, P("src", None, None))
        win = jax.device_put(np.zeros((b_pad, p_pad, ROW_STRIDE), np.uint8),
                             sharding)
        state = jax.device_put(np.zeros((b_pad, s_pad, STATE_COLS),
                                        np.uint32), sharding)
        np.asarray(sharded_megabatch_step(sched.mesh)(win, state))
    elif p_pad != 16:
        trace_single(p_pad)            # the dispatch shape, if distinct


def device_phase_means() -> dict:
    """Per-device mean milliseconds of the mesh phases recorded so far
    (``megabatch_device_phase_seconds``): {"0": {"h2d": ms, ...}, ...}."""
    from .. import obs
    out: dict[str, dict[str, float]] = {}
    for (device, phase), st in sorted(
            obs.MEGABATCH_DEVICE_PHASE_SECONDS._states.items()):
        if st.count:
            out.setdefault(device, {})[phase] = round(
                st.sum / st.count * 1e3, 4)
    return out


def measure_mesh_throughput(n_devices: int, *, n_streams: int = 16,
                            n_sub: int = 8, burst: int = 24,
                            seconds: float = 4.0, addrs=None) -> dict:
    """Paired mesh-vs-single-device megabatch throughput (module doc).

    Returns the ``extra.multichip`` schema; ``n_devices: 1`` with a
    ``note`` when no mesh could be built (1-device box) — the caller
    still gets valid single-device numbers."""
    from ..relay import pump
    from ..relay.megabatch import MegabatchScheduler
    from .mesh import make_megabatch_mesh

    recv = None
    if addrs is None:
        recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        recv.bind(("127.0.0.1", 0))
        recv.setblocking(False)
        recv.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 22)
        addrs = [recv.getsockname()]
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    send.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)

    mesh = make_megabatch_mesh(n_devices)
    n_dev = int(mesh.devices.size) if mesh is not None else 1
    sets = {
        "mesh": (_mk_streams(n_streams, n_sub, addrs, send.fileno(), 11),
                 MegabatchScheduler(mesh=mesh)),
        "one": (_mk_streams(n_streams, n_sub, addrs, send.fileno(), 11),
                MegabatchScheduler()),
    }
    pkt = bytes([0x80, 96]) + bytes(10) + bytes(1388)

    def push(streams, seq, t):
        for st in streams:
            for b in range(burst):
                st.push_rtp(pkt[:2] + ((seq + b) & 0xFFFF).to_bytes(2, "big")
                            + pkt[4:], t)
        return seq + burst

    def step(mode, t):
        (streams, engines), sched = sets[mode]
        pump.wake(list(zip(streams, engines)), sched, t)

    def drain_recv():
        if recv is None:
            return
        try:
            while True:
                recv.recv(65536)
        except BlockingIOError:
            pass

    for mode in sets:
        _precompile(sets[mode][1], n_streams, n_sub, burst)
    # prime both modes (GSO probe, rebase latches) outside the timing
    t = int(time.monotonic() * 1000)
    seq = push(sets["mesh"][0][0], 0, t)
    push(sets["one"][0][0], 0, t)
    step("mesh", t)
    step("one", t)
    for _, sched in sets.values():
        sched.drain()
    drain_recv()
    base_sent = {m: sum(e.packets_sent for e in sets[m][0][1])
                 for m in sets}
    elapsed = {m: 0.0 for m in sets}
    wakes = 0
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        t = int(time.monotonic() * 1000)
        seq = push(sets["mesh"][0][0], seq, t)
        push(sets["one"][0][0], seq - burst, t)
        order = ("mesh", "one") if wakes % 2 == 0 else ("one", "mesh")
        for mode in order:
            c0 = time.perf_counter()
            step(mode, t)
            elapsed[mode] += time.perf_counter() - c0
        drain_recv()
        wakes += 1
        if wakes % 16 == 0:
            for m in sets:
                for st in sets[m][0][0]:
                    st.prune(t)
    for _, sched in sets.values():
        sched.drain()
    sent = {m: sum(e.packets_sent for e in sets[m][0][1]) - base_sent[m]
            for m in sets}
    rate = {m: sent[m] / elapsed[m] if elapsed[m] > 0 else 0.0
            for m in sets}
    send.close()
    if recv is not None:
        recv.close()
    sched_mesh = sets["mesh"][1]
    sched_one = sets["one"][1]
    if n_dev <= 1:
        eff = 1.0                      # no mesh: nothing to scale
    elif rate["one"] > 0:
        eff = rate["mesh"] / (n_dev * rate["one"])
    else:
        # a dead single-device baseline must read as BROKEN (0.0 fails
        # bench_gate's positive-finite check), never as linear scaling
        eff = 0.0
    out = {
        "n_devices": n_dev,
        "streams": n_streams,
        "subscribers_per_stream": n_sub,
        "wakes": wakes,
        "packets_per_sec": round(rate["mesh"], 1),
        "packets_per_sec_per_device": round(rate["mesh"] / n_dev, 1),
        "single_device_packets_per_sec": round(rate["one"], 1),
        "scaling_efficiency": round(eff, 4),
        "sharded_passes": sched_mesh.sharded_passes,
        "single_device_passes": sched_one.passes,
        "wire_mismatches": sched_mesh.mismatches + sched_one.mismatches,
        "device_phase_ms": device_phase_means(),
        "method": (
            "Two identical stream sets fed identical bursts, stepped "
            "alternately with per-wake order flip (paired drift "
            "cancellation): one under the mesh-sharded megabatch "
            "scheduler, one under single-device dispatch.  Every wake "
            "pushes a fresh burst so each mode stages and dispatches "
            "real device work; packets/s = subscriber sends / that "
            "mode's summed step wall time.  scaling_efficiency = "
            "mesh rate / (n_devices x single-device rate)."),
    }
    if mesh is None:
        out["note"] = ("no mesh: fewer than 2 devices — single-device "
                       "dispatch on both sides")
    return out


__all__ = ["measure_mesh_throughput", "device_phase_means"]
