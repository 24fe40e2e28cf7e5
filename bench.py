"""Relay fan-out benchmark — BASELINE config-4 shape on real sockets.

Measures *packets delivered to subscriber sockets per second* for one full
relay pass pipeline, 16 sources × 256 subscribers × 256-packet windows of
1400-byte H.264-style RTP:

* **TPU path** (the north star): H2D of the per-source packet prefixes →
  fused device step (RTP parse, H.264 keyframe classification, newest-IDR
  scan, per-subscriber affine rewrite params) → D2H of O(S+P) params →
  native C++ egress (``csrc/``): per-subscriber ``sendmmsg``/UDP-GSO
  batches that render the rewritten 12-byte header on the stack and
  scatter ``[header | shared payload]`` iovecs.  Payload bytes are never
  copied per-subscriber in host memory and never cross PCIe.
* **CPU baseline** (the reference's architecture): per-(subscriber, packet)
  scalar header rewrite + ``sendto`` — the ReflectorSender hot loop
  (``ReflectorStream.cpp:1024-1185``) as a faithful single-thread C loop.

Method (r3, addressing VERDICT r2 items 1 and 7):

* Every logical subscriber is a REAL wire flow: 256 distinct destination
  addresses (64 loopback IPs × 4 UDP ports) — no extrapolation.  The four
  wildcard-bound receiver sockets drain concurrently (GRO-coalesced,
  MSG_TRUNC recvmmsg) and the delivered count is reported.
* The two paths are measured INTERLEAVED, pass by pass, with a drain
  catch-up barrier between timed windows so neither path's receiver work
  bleeds into the other's window; ``vs_baseline`` is the median of
  per-adjacent-pair ratios, which cancels this shared VM's neighbor-load
  drift (sequential medians swing ±30% here).
* ``p50/p99_added_ms`` are MEASURED ingest→wire percentiles: packets are
  stamped at ``push_rtp`` time inside a real asyncio pump (push → event
  wake → engine pass → native egress return), not derived estimates.

Prints ONE JSON line naming the device it ran on (platform, kind,
count).  It runs on the devices JAX gives it — the chip on a chip
machine, the CPU only under an explicit ``JAX_PLATFORMS=cpu`` — holds
them for its whole run (children it starts are pinned to the CPU), and
exits non-zero when the device step or any section has no result.
"""

from __future__ import annotations

import json
import socket
import subprocess
import threading
import time

import numpy as np

N_SRC, N_SUB, N_PKT = 16, 256, 256
N_PORT, N_IP = 4, 64                  # N_PORT × N_IP = N_SUB real flows
PKT_BYTES = 1400
PKTS_PER_SEC_1080P30 = 350.0
SLOT = 2060
SO_RCVBUFFORCE = 33
UDP_GRO = 104
RCVBUF = 1 << 24                      # deep queues: drain batches stay full


def build_load():
    """[capacity, SLOT] ring + lengths for one source (reused per source)."""
    rng = np.random.default_rng(0)
    ring = np.zeros((N_PKT, SLOT), dtype=np.uint8)
    lens = np.full(N_PKT, PKT_BYTES, dtype=np.int32)
    ring[:, 0] = 0x80
    ring[:, 1] = 96
    seqs = np.arange(N_PKT, dtype=np.uint16)
    ring[:, 2] = seqs >> 8
    ring[:, 3] = seqs & 0xFF
    ring[:, 12] = np.where(np.arange(N_PKT) % 30 == 0, 0x65, 0x41)
    ring[:, 13:PKT_BYTES] = rng.integers(0, 256, size=(N_PKT, PKT_BYTES - 13),
                                         dtype=np.uint8)
    return ring, lens


def raise_rmem_cap() -> None:
    """Deep receive buffers need net.core.rmem_max above its 4 MB default;
    best-effort (root in the bench container), SO_RCVBUFFORCE is the
    fallback, and a 4 MB cap only costs drain efficiency, not correctness."""
    try:
        subprocess.run(["sysctl", "-q", "-w",
                        f"net.core.rmem_max={RCVBUF * 2}"],
                       check=False, capture_output=True, timeout=5)
    except (subprocess.SubprocessError, OSError):
        pass


def make_receivers():
    """N_PORT wildcard receiver sockets; their ports × N_IP loopback IPs
    give every one of the N_SUB logical subscribers a distinct REAL
    (ip, port) wire flow."""
    socks, ports = [], []
    for _ in range(N_PORT):
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.bind(("0.0.0.0", 0))
        s.setblocking(False)
        try:
            s.setsockopt(socket.SOL_SOCKET, SO_RCVBUFFORCE, RCVBUF)
        except OSError:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, RCVBUF)
        try:
            # Accept GSO super-datagrams whole (the loopback stand-in for a
            # real NIC's hardware UDP offload: segmentation cost never hits
            # the CPU, as it wouldn't on a wire NIC)
            s.setsockopt(socket.IPPROTO_UDP, UDP_GRO, 1)
        except OSError:
            pass
        socks.append(s)
        ports.append(s.getsockname()[1])
    addrs = [(f"127.0.0.{1 + ip}", ports[p])
             for ip in range(N_IP) for p in range(N_PORT)]
    return socks, addrs


class Drain(threading.Thread):
    """Counts wire packets arriving on the receiver sockets.

    recvmmsg discard-drain (MSG_TRUNC, zero-length iovecs): one syscall per
    128 GRO super-datagrams, no payload copy.  ``count`` is wire packets
    (delivered bytes / wire packet size)."""

    def __init__(self, socks):
        super().__init__(daemon=True)
        self.socks = socks
        self.count = 0
        self.stop_flag = False

    def run(self):
        from easydarwin_tpu import native
        fds = [s.fileno() for s in self.socks]
        if native.available():
            while not self.stop_flag:
                n, nbytes = native.udp_drain_ex(fds)
                self.count += nbytes // PKT_BYTES
                if n == 0:
                    time.sleep(0.002)
            return
        import select
        while not self.stop_flag:
            r, _, _ = select.select(self.socks, [], [], 0.05)
            for s in r:
                try:
                    while True:
                        data = s.recv(65536)
                        self.count += max(1, len(data) // PKT_BYTES)
                except BlockingIOError:
                    pass


def barrier(drain: Drain, target: int, timeout_s: float = 3.0) -> None:
    """Wait (untimed) until the drain has consumed everything sent so far,
    so the next timed window carries only its own receiver work."""
    t0 = time.perf_counter()
    while drain.count < target and time.perf_counter() - t0 < timeout_s:
        time.sleep(0.001)


def settle(drain: Drain, timeout_s: float = 3.0) -> int:
    """Wait until the drain count stops moving (all in-flight warmup
    traffic consumed) and return the settled count — the baseline for the
    sent-vs-drained barriers (the naive `barrier(drain, drain.count)` is a
    no-op that lets warmup packets bleed into the first timed window)."""
    t0 = time.perf_counter()
    last = drain.count
    quiet = 0.0
    while time.perf_counter() - t0 < timeout_s:
        time.sleep(0.02)
        cur = drain.count
        if cur == last:
            quiet += 0.02
            if quiet >= 0.1:
                break
        else:
            quiet = 0.0
            last = cur
    return drain.count


def device_step_fn():
    import jax
    from easydarwin_tpu.ops.fanout import relay_affine_step_window
    dev = jax.devices()[0]
    return jax, dev, relay_affine_step_window


def paired_rates(ring, lens, addrs, drain, *, seconds=14.0):
    """Interleaved measurement: [TPU pass | barrier | scalar pass | barrier]
    repeated.  Returns (tpu_med, scalar_med, pair_ratios, info)."""
    import jax  # noqa: F401
    from easydarwin_tpu import native
    from easydarwin_tpu.ops.fanout import (STATE_COLS, pack_window,
                                           unpack_affine)

    jax_mod, dev, step = device_step_fn()
    prefix = np.broadcast_to(ring[None, :, :96], (N_SRC, N_PKT, 96)).copy()
    length = np.broadcast_to(lens[None, :], (N_SRC, N_PKT)).copy()
    window = pack_window(prefix, length)
    out_state = np.zeros((N_SRC, N_SUB, STATE_COLS), dtype=np.uint32)
    rng = np.random.default_rng(1)
    out_state[:, :, 0] = rng.integers(0, 2**32, size=(N_SRC, N_SUB))
    out_state[:, :, 3] = rng.integers(0, 2**16, size=(N_SRC, N_SUB))
    # subscriber state changes on subscribe/unsubscribe, not per window:
    # it lives on the device, off the per-window upload path
    state_dev = jax_mod.device_put(out_state, dev)

    send_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    send_sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)
    dests = native.make_dests(addrs)
    ops = native.make_ops([(p, s) for s in range(len(addrs))
                           for p in range(N_PKT)])
    n_ops = len(addrs) * N_PKT
    # scalar slice: 32 of the 256 flows per pass keeps the interleave tight
    # (scalar cost is strictly per-op, so its rate is volume-invariant)
    n_s_out = len(addrs) // 8
    s_ops = native.make_ops([(p, s) for s in range(n_s_out)
                             for p in range(N_PKT)])
    s_n_ops = n_s_out * N_PKT

    # warmup/compile
    packed = jax_mod.block_until_ready(step(
        jax_mod.device_put(window, dev), state_dev))
    warm = np.asarray(packed)
    w_seq, w_ts, w_ssrc, _chan, _ = unpack_affine(warm, N_SUB)
    probe = native.fanout_send_udp_gso(
        send_sock.fileno(), ring, lens, w_seq[0].copy(), w_ts[0].copy(),
        w_ssrc[0].copy(), dests, ops, n_ops)
    gso = probe >= 0
    sq1, ts1, sc1 = w_seq[0].copy(), w_ts[0].copy(), w_ssrc[0].copy()
    native.scalar_baseline_send(send_sock.fileno(), ring, lens, sq1, ts1,
                                sc1, dests, s_ops, s_n_ops)

    def dispatch():
        # ONE H2D (fused window) + device step + async D2H of the single
        # packed result; transfers ride out other windows' egress time
        r = step(jax_mod.device_put(window, dev), state_dev)
        r.copy_to_host_async()
        return r

    # keep several windows in flight so dispatch latency amortizes
    # across the pipeline
    DEPTH = 8
    queue = [(dispatch(), time.perf_counter()) for _ in range(DEPTH)]
    sent_total = 0
    t_rates, s_rates, ratios, window_lat = [], [], [], []
    kf = [-1]
    sent_base = settle(drain)            # warmup fully drained first
    t0 = time.perf_counter()
    passes = 0
    # a starved host (2 vCPUs, drain thread sharing the send core) can
    # take >10 s per pass+barrier cycle; the headline needs at least a
    # few pairs (the first pass is discarded cold), so the window
    # stretches on such boxes — bounded, and a no-op on any host that
    # clears multiple passes inside the nominal window
    MIN_PASSES = 4
    while (time.perf_counter() - t0 < seconds or passes < MIN_PASSES) \
            and time.perf_counter() - t0 < seconds * 5:
        # -- timed TPU pass ------------------------------------------------
        c0 = time.perf_counter()
        res_dev, t_dispatch = queue.pop(0)
        res = np.asarray(res_dev)                      # one tiny transfer
        queue.append((dispatch(), time.perf_counter()))  # overlap w/ egress
        seq_off, ts_off, ssrc, _chan, kf_arr = unpack_affine(res, N_SUB)
        u = max(0, native.fanout_send_multi(
            send_sock.fileno(), ring, lens, seq_off, ts_off, ssrc,
            dests, ops, n_ops, use_gso=1 if gso else 0))
        t_el = time.perf_counter() - c0
        kf[0] = int(kf_arr[0])
        sent_total += u
        window_lat.append(time.perf_counter() - t_dispatch)
        barrier(drain, sent_base + sent_total)         # untimed catch-up
        # -- timed scalar pass ---------------------------------------------
        c1 = time.perf_counter()
        v = max(0, native.scalar_baseline_send(
            send_sock.fileno(), ring, lens, sq1, ts1, sc1,
            dests, s_ops, s_n_ops))
        s_el = time.perf_counter() - c1
        sent_total += v
        barrier(drain, sent_base + sent_total)         # untimed catch-up
        passes += 1
        if u and v and passes > 1:                     # skip first (cold)
            t_rates.append(u / t_el)
            s_rates.append(v / s_el)
            ratios.append((u / t_el) / (v / s_el))
    send_sock.close()
    t_rates.sort()
    s_rates.sort()
    ratios.sort()
    wl = sorted(window_lat[1:]) or [0.0]
    loss = 1.0 - (drain.count - sent_base) / max(sent_total, 1)
    m = len(ratios) // 2
    info = {
        "device": str(dev), "device_platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax_mod.devices()),
        "passes": passes, "gso_egress": gso,
        "pairs": len(ratios),
        "ratio_p25": round(ratios[len(ratios) // 4], 2) if ratios else 0.0,
        "ratio_p75": round(ratios[(3 * len(ratios)) // 4], 2) if ratios else 0.0,
        "delivery_loss_pct": round(100 * loss, 3),
        "newest_keyframe_checked": kf[0],
        # dispatch→egress-complete per window through the depth-8
        # pipeline: includes the in-flight depth, so it is not the live
        # server's adder (see measured p99_added_ms at top level)
        "pipeline_window_p50_ms": round(wl[len(wl) // 2] * 1000, 2),
        "pipeline_window_p99_ms": round(
            wl[min(len(wl) - 1, int(len(wl) * 0.99))] * 1000, 2),
    }
    tpu_med = t_rates[len(t_rates) // 2] if t_rates else 0.0
    scalar_med = s_rates[len(s_rates) // 2] if s_rates else 0.0
    ratio_med = ratios[m] if ratios else 0.0
    return tpu_med, scalar_med, ratio_med, info


def server_cost_paired(ring, lens, *, seconds=5.0):
    """Corroborating SERVER-COST-ONLY ratio: both paths send to GRO
    receivers whose queues are saturated (tiny buffers, never drained), so
    the timed cost is exactly what the serving host pays — syscalls,
    header rewrites, kernel copy, loopback traversal, socket delivery —
    while receiver-side consumption (a loopback-testbed artifact; real
    subscribers are remote machines) is excluded from BOTH paths
    identically.  Same paired-interleave drift cancellation as the
    headline.  Reported as an extra, never the headline."""
    from easydarwin_tpu import native

    socks, ports = [], []
    for _ in range(N_PORT):
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.bind(("0.0.0.0", 0))
        s.setblocking(False)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 16)
        try:
            s.setsockopt(socket.IPPROTO_UDP, UDP_GRO, 1)
        except OSError:
            pass
        socks.append(s)
        ports.append(s.getsockname()[1])
    addrs = [(f"127.0.0.{1 + ip}", ports[p])
             for ip in range(N_IP) for p in range(N_PORT)]
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    tx.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)
    dests = native.make_dests(addrs)
    ops = native.make_ops([(p, s) for s in range(len(addrs))
                           for p in range(N_PKT)])
    n_ops = len(addrs) * N_PKT
    rng = np.random.default_rng(7)
    seq = rng.integers(0, 2**16, (N_SRC, len(addrs))).astype(np.uint32)
    ts = rng.integers(0, 2**32, (N_SRC, len(addrs))).astype(np.uint32)
    sc = rng.integers(0, 2**32, (N_SRC, len(addrs))).astype(np.uint32)
    sq1, ts1, sc1 = seq[0].copy(), ts[0].copy(), sc[0].copy()
    n_s_out = len(addrs) // 8
    s_ops = native.make_ops([(p, s) for s in range(n_s_out)
                             for p in range(N_PKT)])
    s_n = n_s_out * N_PKT
    # saturate the queues once; they stay full for the whole comparison
    native.fanout_send_multi(tx.fileno(), ring, lens, seq, ts, sc, dests,
                             ops, n_ops, use_gso=1)
    native.scalar_baseline_send(tx.fileno(), ring, lens, sq1, ts1, sc1,
                                dests, s_ops, s_n)
    ratios = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        c0 = time.perf_counter()
        u = max(0, native.fanout_send_multi(
            tx.fileno(), ring, lens, seq, ts, sc, dests, ops, n_ops,
            use_gso=1))
        t_el = time.perf_counter() - c0
        c1 = time.perf_counter()
        v = max(0, native.scalar_baseline_send(
            tx.fileno(), ring, lens, sq1, ts1, sc1, dests, s_ops, s_n))
        s_el = time.perf_counter() - c1
        if u and v:
            ratios.append((u / t_el) / (v / s_el))
    tx.close()
    for s in socks:
        s.close()
    ratios.sort()
    return ratios[len(ratios) // 2] if ratios else 0.0


def server_engine_rate(addrs, *, n_outputs=256, seconds=2.5
                       ) -> tuple[float, "object"]:
    """CAPACITY of the live server fan-out path: a real RelayStream +
    TpuFanoutEngine + native-addressed outputs stepped back-to-back over a
    full window (bookmarks rewound each pass).  Same semantics as r02's
    field of this name — offered load does not bound it (the pump-driven
    measurement below reports pacing-bounded rate separately)."""
    import socket as socket_mod

    from easydarwin_tpu.protocol import sdp
    from easydarwin_tpu.relay.fanout import TpuFanoutEngine
    from easydarwin_tpu.relay.output import CollectingOutput
    from easydarwin_tpu.relay.stream import RelayStream, StreamSettings

    sdp_txt = ("v=0\r\ns=b\r\nt=0 0\r\nm=video 0 RTP/AVP 96\r\n"
               "a=rtpmap:96 H264/90000\r\na=control:trackID=1\r\n")
    st = RelayStream(sdp.parse(sdp_txt).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    rng = np.random.default_rng(3)
    outs = []
    for i in range(n_outputs):
        o = CollectingOutput(ssrc=int(rng.integers(0, 2**32)),
                             out_seq_start=int(rng.integers(0, 2**16)))
        o.native_addr = addrs[i % len(addrs)]
        st.add_output(o)
        outs.append(o)
    pkt = bytes([0x80, 96]) + bytes(10) + bytes(PKT_BYTES - 12)
    for i in range(N_PKT):
        st.push_rtp(pkt[:2] + i.to_bytes(2, "big") + pkt[4:], 0)
    send_sock = socket_mod.socket(socket_mod.AF_INET, socket_mod.SOCK_DGRAM)
    send_sock.setsockopt(socket_mod.SOL_SOCKET, socket_mod.SO_SNDBUF, 1 << 22)
    eng = TpuFanoutEngine(egress_fd=send_sock.fileno())
    eng.step(st, 10_000)                        # prime + compile + probe
    units = 0
    times = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        for o in outs:                          # rewind: same window again
            o.bookmark = st.rtp_ring.tail
        c0 = time.perf_counter()
        units += eng.step(st, 10_000)
        times.append(time.perf_counter() - c0)
    send_sock.close()
    return units / sum(times) if times else 0.0


def egress_backend_section(addrs, *, n_outputs=128, seconds=1.2) -> dict:
    """ISSUE 8: per-backend paired comparison of the live engine fan-out
    across the egress ladder (scalar sendto / GSO sendmmsg / io_uring
    where the boot probe grants it).  Same CAPACITY semantics as
    ``server_engine_rate`` — bookmarks rewound each pass — measured in
    order-flipped rounds so shared-VM load drift cancels across
    backends.  Byte-identical wire output across the rungs is pinned by
    tests/test_egress_backend.py; this section reports the rates and
    the probe verdict."""
    import errno as errno_mod
    import socket as socket_mod

    from easydarwin_tpu import native
    from easydarwin_tpu.protocol import sdp
    from easydarwin_tpu.relay.fanout import TpuFanoutEngine
    from easydarwin_tpu.relay.output import CollectingOutput
    from easydarwin_tpu.relay.stream import RelayStream, StreamSettings

    caps = native.uring_probe()
    sdp_txt = ("v=0\r\ns=b\r\nt=0 0\r\nm=video 0 RTP/AVP 96\r\n"
               "a=rtpmap:96 H264/90000\r\na=control:trackID=1\r\n")
    st = RelayStream(sdp.parse(sdp_txt).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    rng = np.random.default_rng(8)
    outs = []
    for i in range(n_outputs):
        o = CollectingOutput(ssrc=int(rng.integers(0, 2**32)),
                             out_seq_start=int(rng.integers(0, 2**16)))
        o.native_addr = addrs[i % len(addrs)]
        st.add_output(o)
        outs.append(o)
    pkt = bytes([0x80, 96]) + bytes(10) + bytes(PKT_BYTES - 12)
    for i in range(N_PKT):
        st.push_rtp(pkt[:2] + i.to_bytes(2, "big") + pkt[4:], 0)
    send_sock = socket_mod.socket(socket_mod.AF_INET, socket_mod.SOCK_DGRAM)
    send_sock.setsockopt(socket_mod.SOL_SOCKET, socket_mod.SO_SNDBUF, 1 << 22)

    backends = ["scalar", "gso"]
    uring = None
    if caps >= 0:
        try:
            from easydarwin_tpu.relay.ring import SLOT_SIZE
            # max_pkt must cover the ring slot or a full-slot packet
            # would -EINVAL the whole chain (review-pass catch)
            uring = native.UringEgress(send_sock.fileno(),
                                       max_pkt=SLOT_SIZE)
            backends.append("io_uring")
        except OSError as e:            # probe passed, creation refused
            caps = -(e.errno or 38)
    zc_base = native.get_stats() if uring is not None else {}
    engines = {}
    for b in backends:
        engines[b] = TpuFanoutEngine(
            egress_fd=send_sock.fileno(), egress_backend=b,
            uring=uring if b == "io_uring" else None)
        for o in outs:
            o.bookmark = st.rtp_ring.tail
        engines[b].step(st, 10_000)     # prime + compile + probe
    units = {b: 0 for b in backends}
    times = {b: 0.0 for b in backends}
    t_end = time.perf_counter() + seconds * len(backends)
    flip = False
    while time.perf_counter() < t_end:
        order = backends[::-1] if flip else backends
        flip = not flip
        for b in order:
            for o in outs:              # rewind: same window again
                o.bookmark = st.rtp_ring.tail
            c0 = time.perf_counter()
            units[b] += engines[b].step(st, 10_000)
            times[b] += time.perf_counter() - c0
    result: dict = {
        "backends": {b: round(units[b] / times[b], 1)
                     for b in backends if times[b] > 0},
        "effective": "io_uring" if "io_uring" in backends else "gso",
    }
    if caps >= 0:
        result["probe_caps"] = caps
        result["io_uring_sqpoll"] = bool(caps & native.URING_CAP_SQPOLL)
        result["io_uring_zerocopy"] = bool(caps & native.URING_CAP_SEND_ZC)
    else:
        # the fallback verdict the acceptance pins for older kernels:
        # everything degrades to GSO with unchanged numbers
        result["probe_errno"] = errno_mod.errorcode.get(-caps, str(-caps))
    if uring is not None:
        s = native.get_stats()
        result["io_uring_stats"] = {
            k: s[f"uring_{k}"] - zc_base.get(f"uring_{k}", 0)
            for k in ("sqes", "cqes", "submits", "zc_completions",
                      "zc_copied")}
        uring.close()
    send_sock.close()
    return result


def measured_added_latency(addrs, *, n_outputs=256, seconds=3.0):
    """MEASURED ingest→wire latency through the LIVE SERVER data path:
    a real asyncio pump (the StreamingServer shape — push_rtp stamps, an
    event wake, one engine pass, native egress) on a real RelayStream +
    TpuFanoutEngine + native-addressed outputs.  Returns (pkts_per_s,
    p50_ms, p99_ms, engine) where the percentiles are over per-burst
    (ingest-call → sendmmsg-return) wall times — no assumed scheduling
    terms (VERDICT r2 weak-4)."""
    import asyncio

    from easydarwin_tpu.protocol import sdp
    from easydarwin_tpu.relay.fanout import TpuFanoutEngine
    from easydarwin_tpu.relay.output import CollectingOutput
    from easydarwin_tpu.relay.stream import RelayStream, StreamSettings

    sdp_txt = ("v=0\r\ns=b\r\nt=0 0\r\nm=video 0 RTP/AVP 96\r\n"
               "a=rtpmap:96 H264/90000\r\na=control:trackID=1\r\n")
    st = RelayStream(sdp.parse(sdp_txt).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    rng = np.random.default_rng(3)
    outs = []
    for i in range(n_outputs):
        o = CollectingOutput(ssrc=int(rng.integers(0, 2**32)),
                             out_seq_start=int(rng.integers(0, 2**16)))
        o.native_addr = addrs[i % len(addrs)]
        st.add_output(o)
        outs.append(o)
    send_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    send_sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)
    eng = TpuFanoutEngine(egress_fd=send_sock.fileno())
    pkt = bytes([0x80, 96]) + bytes(10) + bytes(PKT_BYTES - 12)
    BURST = 12                       # ~one pump tick of 1080p30 ingest

    lat, rates = [], []

    async def pump_loop():
        wake = asyncio.Event()
        done = asyncio.Event()
        state = {"t_push": 0.0, "seq": 0}

        async def pump():
            # the server's pump coroutine: wait for ingest, step, repeat
            from easydarwin_tpu.obs import PROFILER
            while not done.is_set():
                await wake.wait()
                wake.clear()
                # wake→pass queueing delay, same stamp the server pump
                # records (obs/profile.py) — burst push time to pass start
                PROFILER.observe(
                    "wake_to_pass", "pump",
                    int((time.perf_counter() - state["t_push"]) * 1e9))
                now = int(time.monotonic() * 1000)
                sent = eng.step(st, now)
                if sent:
                    lat.append(time.perf_counter() - state["t_push"])
                    rates.append(sent)

        async def pusher():
            t_end = time.perf_counter() + seconds
            while time.perf_counter() < t_end:
                state["t_push"] = time.perf_counter()
                now = int(time.monotonic() * 1000)
                for _ in range(BURST):
                    s = state["seq"]
                    state["seq"] = (s + 1) & 0xFFFF
                    st.push_rtp(pkt[:2] + s.to_bytes(2, "big") + pkt[4:],
                                now)
                wake.set()               # the server's wake_pump()
                await asyncio.sleep(0)   # yield: pump runs now
                st.prune(now)
                await asyncio.sleep(0.002)
            done.set()
            wake.set()

        p = asyncio.ensure_future(pump())
        await pusher()
        await p

    # prime (compile + GSO probe) outside the timed loop
    now = int(time.monotonic() * 1000)
    for i in range(4):
        st.push_rtp(pkt[:2] + (60000 + i).to_bytes(2, "big") + pkt[4:], now)
    eng.step(st, now)
    # the prime pass compiled the device query (the profiler files that
    # under compile notes, not the phase histograms); drop the cached
    # params so the timed pump performs one WARM refresh and the
    # device_step/d2h phases carry steady-state samples — the same
    # refresh a live subscribe/unsubscribe would force
    eng._params_key = None
    t_run0 = time.perf_counter()
    asyncio.run(pump_loop())
    elapsed = time.perf_counter() - t_run0
    send_sock.close()
    if not lat:
        return 0.0, 0.0, 0.0, eng
    ls = sorted(lat)
    rate = sum(rates) / max(elapsed, 1e-9)
    return (rate, ls[len(ls) // 2] * 1000,
            ls[min(len(ls) - 1, int(len(ls) * 0.99))] * 1000, eng)


def multi_source_latency(addrs, *, n_src=16, n_sub=16, seconds=6.0):
    """ISSUE 4 multi-source section: per-wake added latency with the
    cross-stream megabatch scheduler vs per-stream stepping, at
    ``n_src`` concurrent sources × ``n_sub`` native-addressed
    subscribers each.

    Two identical stream sets are fed the same bursts and stepped
    ALTERNATELY inside one loop (step order flipped per wake), so this
    shared VM's load drift cancels the same way the headline's paired
    ratios do.  Device passes per wake are counted from the engines'
    own dispatch counters: per-stream = ring appends + param queries;
    megabatch = stacked bucket passes + fallback queries."""
    from easydarwin_tpu.obs import phase_breakdown, phase_snapshot
    from easydarwin_tpu.parallel.megabench import _mk_streams
    from easydarwin_tpu.relay import pump
    from easydarwin_tpu.relay.megabatch import MegabatchScheduler

    send_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    send_sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)
    send_fd = send_sock.fileno()
    set_mb = _mk_streams(n_src, n_sub, addrs, send_fd, 11)
    set_ps = _mk_streams(n_src, n_sub, addrs, send_fd, 11)
    sched = MegabatchScheduler()
    pkt = bytes([0x80, 96]) + bytes(10) + bytes(PKT_BYTES - 12)
    BURST = 4

    def push(streams, seq, t):
        for st in streams:
            for b in range(BURST):
                st.push_rtp(pkt[:2] + ((seq + b) & 0xFFFF).to_bytes(2, "big")
                            + pkt[4:], t)
        return seq + BURST

    def step_mb(t):
        pump.wake(list(zip(*set_mb)), sched, t)

    def step_ps(t):
        pump.wake(list(zip(*set_ps)), None, t)

    # prime both paths (compile + GSO probe) outside the timed loop
    t = int(time.monotonic() * 1000)
    seq = push(set_mb[0], 0, t)
    push(set_ps[0], 0, t)
    step_mb(t)
    step_ps(t)
    sched.drain()
    phase_base = phase_snapshot()
    base_counts = (sched.passes,
                   sum(e.device_param_refreshes + e.dring_appends
                       for e in set_mb[1]),
                   sum(e.device_param_refreshes + e.dring_appends
                       for e in set_ps[1]))
    lat_mb, lat_ps = [], []
    wakes = 0
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        t = int(time.monotonic() * 1000)
        t_push = time.perf_counter()
        seq = push(set_mb[0], seq, t)
        push(set_ps[0], seq - BURST, t)
        # only the FIRST-stepped mode samples this wake (a true
        # push→wire measure, uncontaminated by the other mode's step);
        # the order flip gives both modes the same number of samples
        # under the same conditions
        if wakes % 2 == 0:
            step_mb(t)
            lat_mb.append(time.perf_counter() - t_push)
            step_ps(t)
        else:
            step_ps(t)
            lat_ps.append(time.perf_counter() - t_push)
            step_mb(t)
        wakes += 1
        if wakes % 16 == 0:
            for st in set_mb[0] + set_ps[0]:
                st.prune(t)
        time.sleep(0.002)
    sched.drain()
    send_sock.close()

    def pct(xs, q):
        if not xs:
            return 0.0
        ys = sorted(xs)
        return ys[min(len(ys) - 1, int(len(ys) * q))] * 1000

    mb_passes = sched.passes - base_counts[0]
    mb_extra = (sum(e.device_param_refreshes + e.dring_appends
                    for e in set_mb[1]) - base_counts[1])
    ps_passes = (sum(e.device_param_refreshes + e.dring_appends
                     for e in set_ps[1]) - base_counts[2])
    phases = phase_breakdown(since=phase_base)
    return {
        "sources": n_src,
        "subscribers_per_source": n_sub,
        "wakes": wakes,
        "streams_per_pass": sched.stats()["streams_per_pass"],
        "megabatch_passes": mb_passes,
        "megabatch_p50_added_ms": round(pct(lat_mb, 0.5), 3),
        "megabatch_p99_added_ms": round(pct(lat_mb, 0.99), 3),
        "per_stream_p50_added_ms": round(pct(lat_ps, 0.5), 3),
        "per_stream_p99_added_ms": round(pct(lat_ps, 0.99), 3),
        "megabatch_device_passes_per_wake": round(
            (mb_passes + mb_extra) / max(wakes, 1), 3),
        "per_stream_device_passes_per_wake": round(
            ps_passes / max(wakes, 1), 3),
        "megabatch_wire_mismatches": sched.mismatches,
        "phase_ms": {ph: row["mean_ms"]
                     for ph, row in sorted(phases.items())},
        "method": (
            "Two identical stream sets fed the same bursts, stepped "
            "alternately (order flipped per wake) in one loop: "
            "megabatch set under the cross-stream scheduler, per-stream "
            "set with one engine pass per source.  added_ms = wall time "
            "from the burst push to the mode's last engine-pass return, "
            "sampled only on wakes where that mode steps first (so the "
            "other mode's step never contaminates the sample).  "
            "device_passes_per_wake counts actual dispatches "
            "(stacked bucket passes + fallback queries vs per-stream "
            "ring appends + param queries)."),
    }


def multichip_section(n_devices: int = 8, seconds: float = 4.0) -> dict:
    """ISSUE 7 multi-device section: megabatch-on-mesh packets/s and
    scaling efficiency (``easydarwin_tpu.parallel.megabench``) on the
    devices this process holds — up to ``n_devices`` of them.  With one
    device there is no mesh to measure: the section says so (a note, not
    a number); it never builds a virtual CPU mesh in a child to have
    something to report."""
    import jax
    have = jax.local_device_count()
    if have < 2:
        return {"n_devices": have,
                "note": f"one {jax.devices()[0].platform} device: no "
                        f"mesh to measure"}
    from easydarwin_tpu.parallel.megabench import measure_mesh_throughput
    return measure_mesh_throughput(min(n_devices, have), seconds=seconds)


def cpu_reference_rate(ring, lens, addrs, *, seconds=2.0) -> float:
    """Pure-Python scalar loop (round-1's flattering denominator — kept
    only as a labelled extra)."""
    from easydarwin_tpu.protocol import rtp

    send_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    send_sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)
    pkts = [ring[i, :PKT_BYTES].tobytes() for i in range(N_PKT)]
    units = 0
    t0 = time.perf_counter()
    chunk0 = t0
    chunk_units = 0
    rates = []
    sub = addrs[:64]
    while time.perf_counter() - t0 < seconds:
        for s_idx, addr in enumerate(sub):
            pkt = pkts[units % N_PKT]
            out = rtp.rewrite_header(pkt, seq=(units + s_idx) & 0xFFFF,
                                     timestamp=units & 0xFFFFFFFF,
                                     ssrc=s_idx)
            try:
                send_sock.sendto(out, addr)
            except BlockingIOError:
                pass
            units += 1
        chunk_units += len(sub)
        if chunk_units >= 16384:
            now = time.perf_counter()
            rates.append(chunk_units / (now - chunk0))
            chunk0 = now
            chunk_units = 0
    send_sock.close()
    if rates:
        return sorted(rates)[len(rates) // 2]
    return units / (time.perf_counter() - t0)


def h264_requant_throughput(*, seconds: float = 2.0) -> dict:
    """Native q-rung throughput on a REAL chroma-bearing CAVLC slice:
    macroblocks/s through ``ed_h264_requant_slice``, and the implied
    number of concurrent 1080p30 bitrate renditions that throughput
    sustains (1080p = 8160 MBs/frame).  The slice is encoded once by the
    Python reference encoder (4:2:0, qp 24) and requanted repeatedly —
    the production path for every HLS q-rung frame."""
    from easydarwin_tpu.codecs.h264_intra import encode_iframe
    from easydarwin_tpu.codecs.h264_requant import SliceRequantizer
    from easydarwin_tpu.utils.synth import synth_luma

    n = 192                                   # 12x12 MBs = 144 MBs/frame
    img = synth_luma(n)
    nals = encode_iframe(img, 24, cb=img[::2, ::2], cr=img[1::2, 1::2])
    rq = SliceRequantizer(6)
    for nal in nals[:2]:
        rq.transform_nal(nal)
    slice_nal = nals[2]
    mbs_per_slice = (n // 16) ** 2
    # warm up + verify the native path engages
    rq.transform_nal(slice_nal)
    if rq.stats.native_slices != 1:
        return {"h264_requant_note": "native path unavailable"}
    # median per-slice time, not wall-average: this shared VM preempts
    # the single core (the relay headline cancels that with paired
    # ratios; here the analogous control is the median)
    t0 = time.perf_counter()
    times = []
    while time.perf_counter() - t0 < seconds:
        c0 = time.perf_counter()
        rq.transform_nal(slice_nal)
        times.append(time.perf_counter() - c0)
    times.sort()
    mbs_s = mbs_per_slice / times[len(times) // 2]

    # same slice content through the native CABAC walk (Main/High
    # profile camera streams take this path)
    nals_cb = encode_iframe(img, 24, cb=img[::2, ::2], cr=img[1::2, 1::2],
                            entropy="cabac")
    rq_cb = SliceRequantizer(6)
    for nal in nals_cb[:2]:
        rq_cb.transform_nal(nal)
    rq_cb.transform_nal(nals_cb[2])
    cabac_mbs_s = 0.0
    if rq_cb.stats.native_slices == 1:
        t0 = time.perf_counter()
        ct = []
        while time.perf_counter() - t0 < seconds / 2:
            c0 = time.perf_counter()
            rq_cb.transform_nal(nals_cb[2])
            ct.append(time.perf_counter() - c0)
        ct.sort()
        cabac_mbs_s = mbs_per_slice / ct[len(ct) // 2]

    # the production harness (hls/requant.py): one shared pool, the
    # native walk releases the GIL — measure the AGGREGATE rate with
    # every core fed, which is what a multi-rung ladder gets
    from easydarwin_tpu.hls.requant import pool_sizing
    sizing = pool_sizing()
    workers = sizing["workers"]
    agg_mbs_s = mbs_s
    if workers > 1:
        import threading
        counts = [0] * workers
        stop = [False]

        def grind(i):
            r = SliceRequantizer(6)
            for nal in nals[:2]:
                r.transform_nal(nal)
            while not stop[0]:
                r.transform_nal(slice_nal)
                counts[i] += 1

        ts = [threading.Thread(target=grind, args=(i,))
              for i in range(workers)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        time.sleep(seconds)
        stop[0] = True
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        agg_mbs_s = sum(counts) * mbs_per_slice / dt
    return {
        "h264_requant_mbs_per_sec": round(mbs_s, 0),
        "h264_requant_cabac_mbs_per_sec": round(cabac_mbs_s, 0),
        "h264_requant_workers": workers,
        # which sizing signal won and what every signal read (ISSUE 5
        # satellite: r05 shipped workers=1 with no way to tell whether
        # that was one real CPU or a collapsed probe under a cpu.max
        # bandwidth quota)
        "h264_requant_sizing": sizing,
        "h264_requant_parallel_mbs_per_sec": round(agg_mbs_s, 0),
        "h264_requant_1080p30_renditions":
            round(agg_mbs_s / (8160 * 30), 2),
        "h264_requant_method": (
            "real 192x192 4:2:0 slices (chroma DC+AC coded) through the "
            "native requant walks, CAVLC and CABAC: per-core rate = "
            "mbs_per_slice / MEDIAN per-slice time (wall-average is "
            "contaminated by this shared VM's preemption; the median is "
            "the same control the relay headline's paired ratios "
            "apply).  parallel_mbs_per_sec = aggregate across "
            "pool_workers() GIL-released threads (the hls/requant.py "
            "pool shape).  1080p30 renditions = parallel rate / "
            "(8160 MBs * 30 fps).  The HLS pipeline sheds AUs when the "
            "pool is saturated, so an over-budget ladder degrades in "
            "frame rate, never in latency."),
    }


def h264_requant_ladder_section(*, renditions: int = 3,
                                pairs: int = 5) -> dict:
    """The ABR-ladder serve measurement (ISSUE 9): real multi-slice AUs
    through the production ``hls.requant.RequantLadder`` — shared parse,
    slice × rendition fan-out across the worker pool, ordered per-AU
    reassembly — vs the SAME pipeline single-threaded, in interleaved
    paired windows (the shared-VM control every other section uses).

    Figures:

    * ``renditions_sustained`` — rendition output rate of the pooled
      N-rung ladder divided by one 1080p30 rendition's macroblock rate
      (8160 MBs × 30 fps): how many simultaneous 1080p30 renditions per
      source THIS box's ladder sustains.  Scales with cores: the ladder
      is (slices × renditions)-parallel and admission-pipelined, so a
      wider box lifts it near-linearly until the source's own parse
      saturates one core.
    * ``parallel_speedup`` — median of per-pair pooled/serial ratios
      (workers > 1 "actually engaged" means this is measurably > 1).
    * ``shared_parse_amortization`` — Python-engine fan-out economics:
      time of N independent parse+recode passes over one CABAC slice
      divided by one ``requant_multi`` shared-parse fan-out to the same
      N targets (parse is the dominant CABAC cost, so this approaches
      N×enc/(dec+N×enc) from above as N grows)."""
    import asyncio
    import os

    from easydarwin_tpu.codecs.h264_intra import encode_iframe
    from easydarwin_tpu.codecs.h264_requant import (SliceRequantizer,
                                                    requant_multi)
    from easydarwin_tpu.hls.requant import RequantLadder, pool_workers
    from easydarwin_tpu.utils.synth import synth_luma
    from easydarwin_tpu.vod.depacketize import AccessUnit

    deltas = tuple(6 * (i + 1) for i in range(renditions))
    n = 192                              # 12x12 MBs = 144 MBs per AU
    mbs_per_au = (n // 16) ** 2
    workers = pool_workers()
    n_slices = max(2, min(workers, 4))   # exercise the slice fan-out
    aus = []
    for f in range(8):
        img = synth_luma(n, f)
        nals = encode_iframe(img, 24, cb=img[::2, ::2], cr=img[1::2, 1::2],
                             idr_pic_id=f % 2, slices=n_slices,
                             include_ps=(f == 0))
        aus.append(AccessUnit(f * 3000, nals))

    from easydarwin_tpu.obs import REQUANT_STAGE_SECONDS

    def _stage_busy() -> float:
        """Cumulative worker-side busy seconds across the requant stages
        that run ON the pool (entropy/parse/recode/transform_device)."""
        return sum(st.sum for key, st in
                   REQUANT_STAGE_SECONDS._states.items()
                   if key[0] != "reassemble")

    def make_ladder():
        lad = RequantLadder(use_device=False, target_duration=3600.0)
        for d in deltas:
            lad.add_rendition(d)
        return lad

    window_sec = max(0.8, float(os.environ.get(
        "EDTPU_BENCH_LADDER_WINDOW_SEC", "1.2")))
    lad_p = make_ladder()
    lad_s = make_ladder()
    lad_s._on_unit(aus[0])               # warm serial (sets + native)

    async def pooled_window(sec: float) -> tuple[float, float]:
        """(AUs/s, worker concurrency = pool busy seconds / wall)."""
        lad = lad_p
        if not lad._next_emit:           # warm the pool + sets once
            lad._on_unit(aus[0])
            while lad.pending:
                await asyncio.sleep(0.001)
        base_emit = lad._next_emit
        busy0 = _stage_busy()
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < sec:
            if lad.pending + 1 >= lad._max_pending:
                await asyncio.sleep(0.001)
                continue
            lad._on_unit(aus[i % len(aus)])
            i += 1
        while lad.pending:
            await asyncio.sleep(0.001)
        wall = time.perf_counter() - t0
        return ((lad._next_emit - base_emit) / wall,
                (_stage_busy() - busy0) / wall)

    def serial_window(sec: float) -> float:
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < sec:
            lad_s._on_unit(aus[i % len(aus)])
            i += 1
        return i / (time.perf_counter() - t0)

    ratios, p_rates, concs = [], [], []
    for _ in range(pairs):               # interleaved: VM drift cancels
        rate_p, conc = asyncio.run(pooled_window(window_sec))
        rate_s = serial_window(window_sec)
        p_rates.append(rate_p)
        concs.append(conc)
        ratios.append(rate_p / rate_s if rate_s > 0 else 0.0)
    ratios.sort()
    speedup = ratios[len(ratios) // 2]
    p_med = sorted(p_rates)[len(p_rates) // 2]
    concurrency = sorted(concs)[len(concs) // 2]
    rendition_mbs_s = p_med * len(deltas) * mbs_per_au
    sustained = rendition_mbs_s / (8160 * 30)

    # shared-parse amortization on the Python CABAC engine (the path
    # where the entropy READ dominates; the native walk keeps its fused
    # decode+recode and amortizes by fan-out instead)
    nals_cb = encode_iframe(synth_luma(96), 24, entropy="cabac")
    from easydarwin_tpu.codecs.h264_intra import Pps, Sps
    sps_cb, pps_cb = Sps.parse(nals_cb[0]), Pps.parse(nals_cb[1])
    inds = [SliceRequantizer(d, prefer_native=False) for d in deltas]
    for rq in inds:
        for x in nals_cb[:2]:
            rq.transform_nal(x)
    requant_multi(nals_cb[2], sps_cb, pps_cb, deltas)     # warm
    t_ind, t_sh = [], []
    for _ in range(3):
        c0 = time.perf_counter()
        for rq in inds:
            rq.requant_with(nals_cb[2], rq.sps, rq.pps)
        t_ind.append(time.perf_counter() - c0)
        c0 = time.perf_counter()
        requant_multi(nals_cb[2], sps_cb, pps_cb, deltas)
        t_sh.append(time.perf_counter() - c0)
    amort = (sorted(t_ind)[1] / sorted(t_sh)[1]
             if sorted(t_sh)[1] > 0 else 0.0)

    stats = [lad_p.renditions[d].requant.stats for d in deltas]
    return {
        "renditions_requested": renditions,
        "renditions_sustained": round(sustained, 2),
        "deltas": list(deltas),
        "slices_per_au": n_slices,
        "ladder_rendition_mbs_per_sec": round(rendition_mbs_s, 0),
        "source_mbs_per_sec": round(rendition_mbs_s / len(deltas), 0),
        "workers": workers,
        "parallel_speedup": round(speedup, 2),
        "worker_concurrency": round(concurrency, 2),
        "workers_engaged": workers > 1 and concurrency > 1.1,
        "shared_parse_amortization": round(amort, 2),
        "sheds": lad_p.shed,
        "slices_passed_through": sum(s.slices_passed_through
                                     for s in stats),
        "method": (
            "Real 192x192 4:2:0 multi-slice AUs through the production "
            "RequantLadder at ladder width N: pooled (slice x rendition "
            "fan-out, ordered reassembly) vs the same pipeline "
            "single-threaded, in interleaved time-budgeted paired "
            "windows; parallel_speedup = median of per-pair pooled/"
            "serial AU-rate ratios.  worker_concurrency = pool busy "
            "seconds (requant stage histogram deltas) / wall — the "
            "DIRECT workers-engaged proof: > 1 means multiple workers "
            "ran simultaneously even when shared-vCPU contention (SMT "
            "siblings, hypervisor steal) keeps the wall speedup near 1, "
            "as on this bench box.  renditions_sustained = pooled "
            "rendition-MB rate / (8160 MBs x 30 fps); it grows with "
            "real cores (the ladder is slice x rendition parallel), "
            "with shared parse bounding the per-source serial floor on "
            "the Python engines.  shared_parse_amortization = N "
            "independent CABAC parse+recode passes vs ONE requant_multi "
            "shared-parse fan-out (Python engine, median of 3)."),
    }


def vod_section(addrs, *, n_subs=8, n_assets=2, seconds=8.0) -> dict:
    """ISSUE 10 VOD section: N subscribers × M synthetic assets with
    seek churn, hot segment-cache serving (vectorized window fill +
    megabatch/native engine) vs the cold per-sample mmap path
    (``FileSession``'s asyncio pull-pace loop), in paired order-flipped
    windows so shared-VM load drift cancels like the headline's."""
    import asyncio
    import os
    import tempfile

    from easydarwin_tpu import obs
    from easydarwin_tpu.relay import pump
    from easydarwin_tpu.relay.fanout import TpuFanoutEngine
    from easydarwin_tpu.relay.megabatch import MegabatchScheduler
    from easydarwin_tpu.relay.output import RelayOutput, WriteResult
    from easydarwin_tpu.vod.cache import SegmentCache
    from easydarwin_tpu.vod.mp4 import open_shared
    from easydarwin_tpu.vod.mp4_writer import Mp4Writer
    from easydarwin_tpu.vod.session import FileSession, VodPacerGroup

    SPS = bytes((0x67, 0x42, 0x00, 0x1F, 0xAA, 0xBB, 0xCC, 0xDD))
    PPS = bytes((0x68, 0xCE, 0x3C, 0x80))
    tmp = tempfile.mkdtemp(prefix="edtpu_vodbench_")
    n_frames = 600
    paths = []
    for a in range(n_assets):
        p = os.path.join(tmp, f"asset{a}.mp4")
        w = Mp4Writer(p)
        v = w.add_h264_track(SPS, PPS, 1280, 720, timescale=90000)
        for i in range(n_frames):
            idr = i % 30 == 0
            nal = bytes((0x65 if idr else 0x41,)) \
                + bytes(((i + a) & 0xFF,)) * (1200 if idr else 1100)
            w.write_sample(v, len(nal).to_bytes(4, "big") + nal, 3000,
                           sync=idr)
        w.close()
        paths.append(p)
    files = [open_shared(p) for p in paths]
    cache = SegmentCache(window_samples=64, device=True)
    for f in files:
        cache.warm_asset(f)              # hot = warm by definition
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    send.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)

    class _HotOut(RelayOutput):          # RTP rides the native scatter
        def send_bytes(self, data, *, is_rtcp):
            return WriteResult.OK        # RTCP dropped (bench)

    class _ColdOut(RelayOutput):
        def __init__(self, addr, **kw):
            super().__init__(**kw)
            self.addr = addr

        def send_bytes(self, data, *, is_rtcp):
            if not is_rtcp:
                send.sendto(data, self.addr)
            return WriteResult.OK

    rng = np.random.default_rng(23)
    #: per subscriber: (asset, [seek npts]) — the same schedule drives
    #: both paths, so the byte volume compared is identical
    duration = n_frames / 30.0
    schedule = [(int(rng.integers(0, n_assets)),
                 [float(x) for x in rng.uniform(0, duration * 0.8, 3)])
                for _ in range(n_subs)]
    SPEED = 1e6                          # everything due at once:
    #                                      measures capacity, not pacing
    mm_base = obs.MEGABATCH_WIRE_MISMATCH.value()

    def hot_window() -> tuple[int, float]:
        engines = pump.Pump(new_engine=lambda: TpuFanoutEngine(
            egress_fd=send.fileno()))
        sched = MegabatchScheduler()
        pacer = VodPacerGroup(cache, engine_for=engines.engine_for,
                              engine_drop=engines.engine_drop,
                              scheduler=lambda: sched,
                              lookahead_ms=10_000, device_prime=True)
        outs = []
        state = []                       # (output, asset, remaining seeks)
        t = int(time.monotonic() * 1000)
        for k, (asset, seeks) in enumerate(schedule):
            o = _HotOut(ssrc=0x5000 + k, out_seq_start=101 * k + 1)
            o.native_addr = addrs[k % len(addrs)]
            outs.append(o)
            sess = pacer.open(files[asset], {1: o}, speed=SPEED,
                              start_npt=seeks[0], now_ms=t)
            state.append([sess, asset, list(seeks[1:])])
        t0 = time.perf_counter()
        deadline = t0 + 30.0
        while time.perf_counter() < deadline:
            t = int(time.monotonic() * 1000)
            pump.wake(pacer.tick(t), sched, t, min_streams=2)
            live = False
            for i, rec in enumerate(state):
                sess, asset, seeks = rec
                if not sess.done:
                    live = True
                elif seeks:              # seek churn: reopen at the
                    npt = seeks.pop(0)   # next scheduled position
                    rec[0] = pacer.open(files[asset], {1: outs[i]},
                                        speed=SPEED, start_npt=npt,
                                        now_ms=t)
                    live = True
            if not live:
                break
        sched.drain()
        elapsed = time.perf_counter() - t0
        sent = sum(o.packets_sent for o in outs)
        pacer.close()
        return sent, elapsed

    def cold_window() -> tuple[int, float]:
        outs = [_ColdOut(addrs[k % len(addrs)], ssrc=0x6000 + k,
                         out_seq_start=101 * k + 1)
                for k in range(n_subs)]

        async def one(k):
            asset, seeks = schedule[k]
            for npt in [seeks[0]] + list(seeks[1:]):
                sess = FileSession(files[asset], {1: outs[k]},
                                   start_npt=npt, speed=SPEED)
                await sess.run()

        t0 = time.perf_counter()

        async def all_():
            await asyncio.gather(*(one(k) for k in range(n_subs)))

        asyncio.run(all_())
        elapsed = time.perf_counter() - t0
        return sum(o.packets_sent for o in outs), elapsed

    # warm both paths once (jit traces, GSO probe) outside the timing
    hot_window()
    cold_window()
    hot_s = hot_p = cold_s = cold_p = 0.0
    rounds = 0
    t_end = time.perf_counter() + seconds
    flip = False
    while time.perf_counter() < t_end or rounds < 2:
        order = (hot_window, cold_window) if not flip \
            else (cold_window, hot_window)
        for fn in order:
            n, dt = fn()
            if fn is hot_window:
                hot_p += n
                hot_s += dt
            else:
                cold_p += n
                cold_s += dt
        flip = not flip
        rounds += 1
        if rounds >= 6:
            break
    for f in files:
        f.close()
    send.close()
    st = cache.stats()
    hot_rate = hot_p / max(hot_s, 1e-9)
    cold_rate = cold_p / max(cold_s, 1e-9)
    return {
        "subscribers": n_subs,
        "assets": n_assets,
        "seeks_per_subscriber": 3,
        "rounds": rounds,
        "hot_pkts_per_sec": round(hot_rate, 1),
        "cold_pkts_per_sec": round(cold_rate, 1),
        "hot_vs_cold": round(hot_rate / max(cold_rate, 1e-9), 2),
        "cache_hit_rate": round(
            st["hits"] / max(st["hits"] + st["misses"], 1), 4),
        "cache_windows": st["windows"],
        "cache_bytes": st["bytes"],
        "hbm_window_uploads": st["device_uploads"],
        "wire_mismatches": int(obs.MEGABATCH_WIRE_MISMATCH.value()
                               - mm_base),
        "method": (
            "N subscribers x M one-track 720p30 assets, each subscriber "
            "playing from a seeded start npt then seeking twice "
            "(session reopen, the RTSP re-PLAY shape), at speed=1e6 so "
            "delivery capacity is measured, not wall-clock pacing.  "
            "hot = warm segment cache -> vectorized ring block-fill -> "
            "TpuFanoutEngine native sendmmsg under the megabatch "
            "scheduler; cold = per-session FileSession asyncio "
            "pull-pace loop (per-sample packetize + per-packet "
            "sendto).  Paired order-flipped full-drain windows; rates "
            "are totals over all windows per path.  wire_mismatches = "
            "megabatch_wire_mismatch_total delta (host-oracle check on "
            "every installed VOD affine segment)."),
    }


def dvr_section(addrs, *, record_frames=900, window_pkts=64) -> dict:
    """ISSUE 12 DVR section: record a live push through the window
    spiller, then replay the finalized asset through a time-shift
    session at capacity speed.  The figures the trajectory gate reads
    (``extra.dvr``): spill throughput, the time-shift join rate vs the
    live join rate (spilled windows must serve at hot-cache rates — the
    born-packed design's whole point), and the repack counter across
    the spilled-asset re-open, which must be exactly zero."""
    import tempfile

    from easydarwin_tpu.dvr import DvrManager
    from easydarwin_tpu.protocol import nalu
    from easydarwin_tpu.relay.fanout import TpuFanoutEngine
    from easydarwin_tpu.relay.output import RelayOutput, WriteResult
    from easydarwin_tpu.relay.pump import Pump
    from easydarwin_tpu.relay.session import SessionRegistry, now_ms
    from easydarwin_tpu.vod.cache import SegmentCache, pack_window
    from easydarwin_tpu.vod.session import VodPacerGroup

    SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\n"
           "a=rtpmap:96 H264/90000\r\na=control:trackID=1\r\n")

    class _NatOut(RelayOutput):          # RTP rides the native scatter
        def send_bytes(self, data, *, is_rtcp):
            return WriteResult.OK        # RTCP dropped (bench)

    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    send.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)
    registry = SessionRegistry()
    cache = SegmentCache(budget_bytes=128 << 20, device=False)
    engines = Pump(new_engine=lambda: TpuFanoutEngine(
        egress_fd=send.fileno()))
    pacer = VodPacerGroup(cache, engine_for=engines.engine_for,
                          engine_drop=engines.engine_drop,
                          lookahead_ms=10_000, device_prime=False)
    tmp = tempfile.mkdtemp(prefix="edtpu_dvrbench_")
    dvr = DvrManager(tmp, cache, pacer, registry,
                     window_pkts=window_pkts,
                     retention_bytes=1 << 30, retention_sec=1e9)

    # ---- record + live-join window: a native subscriber rides the
    # engine while every completed ring window spills (timed separately)
    sess = registry.find_or_create("/live/dvrbench", SDP)
    out_live = _NatOut(ssrc=0xD7, out_seq_start=1)
    out_live.native_addr = addrs[0]
    sess.add_output(1, out_live)
    dvr.arm(sess, SDP)
    eng = engines.engine_for(sess.streams[1])
    seq = 0
    spill_s = 0.0
    t0 = time.perf_counter()
    for fidx in range(record_frames):
        nal = bytes((0x65 if fidx % 30 == 0 else 0x41,)) \
            + bytes(((fidx) & 0xFF,)) * 1100
        for p in nalu.packetize_h264(nal, seq=seq, timestamp=fidx * 3000,
                                     ssrc=7, mtu=1400):
            sess.push(1, p, t_ms=now_ms())
            seq += 1
        t = now_ms()
        s0 = time.perf_counter()
        dvr.tick(t)
        spill_s += time.perf_counter() - s0
        eng.step(sess.streams[1], t)
    live_s = time.perf_counter() - t0
    live_pkts = out_live.packets_sent
    spill_bytes = sum(sp.writer.live_bytes + sp.writer.dead_bytes
                      for a in dvr._armed.values()
                      for sp in a.spillers.values())
    res = dvr.finalize("/live/dvrbench")
    registry.remove("/live/dvrbench")

    # ---- time-shift join window: replay the finalized asset (pure
    # spill → zero-repack cache open → pacer block-fill → engine) at
    # capacity speed; pack_window.calls across it is the acceptance pin
    calls0 = pack_window.calls
    out_shift = _NatOut(ssrc=0xD7, out_seq_start=1)
    out_shift.native_addr = addrs[1 % len(addrs)]
    shift = dvr.open_timeshift("/live/dvrbench.dvr", {1: out_shift},
                               start_npt=0.0, speed=1e6)
    ts_pkts = ts_s = 0.0
    if shift is not None:
        t1 = time.perf_counter()
        deadline = t1 + 60.0
        while not shift.done and time.perf_counter() < deadline:
            t = now_ms()
            for st, e in pacer.tick(t):
                e.step(st, t)
        ts_s = time.perf_counter() - t1
        ts_pkts = out_shift.packets_sent
        shift.stop()
    repacks = pack_window.calls - calls0
    st = cache.stats()
    pacer.close()
    cache.close()
    send.close()
    return {
        "recorded_frames": record_frames,
        "recorded_pkts": seq,
        "spilled_windows": (res or {}).get("windows", 0),
        "spill_mbps": round(spill_bytes / max(spill_s, 1e-9) / 1e6, 1),
        "live_join_pps": round(live_pkts / max(live_s, 1e-9), 1),
        "timeshift_join_pps": round(ts_pkts / max(ts_s, 1e-9), 1),
        "timeshift_vs_live": round(
            (ts_pkts / max(ts_s, 1e-9))
            / max(live_pkts / max(live_s, 1e-9), 1e-9), 2),
        "reopen_repacks": repacks,
        "cache_hit_rate": round(
            st["hits"] / max(st["hits"] + st["misses"], 1), 4),
        "method": (
            "Record: one pushed 30fps-shaped stream with a native-"
            "addressed live subscriber stepped per frame burst; "
            "completed ring windows spill inline (spill_mbps = spill "
            "file bytes / accumulated dvr.tick wall time; live_join_pps "
            "= live subscriber packets / record-loop wall time — the "
            "engine fan-out rate under the recording load).  Replay: "
            "the finalized .dvr asset through a TimeShiftSession at "
            "speed=1e6 (capacity, not pacing) — spilled windows enter "
            "the segment cache via the zero-repack from_packed path "
            "and the SAME native engine serves them; reopen_repacks = "
            "pack_window.calls delta across the replay (must be 0)."),
    }


def storage_section(*, n_windows: int = 48, window_bytes: int = 75_000,
                    k: int = 4, m: int = 2) -> dict:
    """ISSUE 20 erasure-storage section: shard one finalized-asset-
    shaped window set into k data + m parity shards (the GF(256) device
    matmul with the host oracle in the loop), then measure the figures
    the trajectory gate reads (``extra.storage``): healthy-replay vs
    degraded-replay window throughput (one data shard lost per stripe —
    the single-holder-loss shape — must stay >= 0.5x direct), the
    two-loss Gaussian-solve read rate (informational), background-
    repair MB/s (each deleted shard re-derived from survivors — math,
    not a byte copy), and the scrub verdict over the repaired store,
    which must be exactly zero errors."""
    import os
    import random
    import shutil
    import tempfile

    from easydarwin_tpu.storage import StorageService

    rng = random.Random(20)

    class _AssetDoc:                 # the DvrManager faces store_asset
        def __init__(self, blobs):   # needs: meta_doc + window_blob
            self.blobs = blobs

        def meta_doc(self, path):
            return {"path": path, "meta": {"gen": 1}, "tracks": {"1": {
                "windows": [{"win": i} for i in range(len(self.blobs))]}}}

        def window_blob(self, path, tid, win):
            return self.blobs[win]

    blobs = [bytes(rng.randrange(256) for _ in range(window_bytes))
             for _ in range(n_windows)]
    tmp = tempfile.mkdtemp(prefix="edtpu_storbench_")
    st = StorageService(tmp, "bench", k=k, m=m, use_device=True)
    try:
        man = st.store_asset("/live/storbench", _AssetDoc(blobs))
        if man is None:
            return {"error": "store_asset produced no shards"}
        # ---- healthy replay: every window served from its local shard
        t0 = time.perf_counter()
        for w in range(n_windows):
            if st.restore_window("/live/storbench", 1, w) != blobs[w]:
                return {"error": f"direct read mismatch at window {w}"}
        direct_s = time.perf_counter() - t0
        # ---- degraded replay: ONE data shard lost per stripe (the
        # single-holder-loss shape the soak SIGKILLs): each stripe's
        # first read gathers the survivors, solves through the XOR
        # parity row and serves the whole stripe from the solve, so
        # the replay touches each shard once, like a healthy one
        deleted = []
        n_stripes = (n_windows + k - 1) // k
        for s in range(n_stripes):
            name = f"t1/s{s}.0"
            p = os.path.join(tmp, "live/storbench", name)
            if os.path.isfile(p):
                os.unlink(p)
                deleted.append(name)
        st._stripe_cache.clear()
        t1 = time.perf_counter()
        for w in range(n_windows):
            if st.restore_window("/live/storbench", 1, w) != blobs[w]:
                return {"error": f"reconstruct mismatch at window {w}"}
        recon_s = time.perf_counter() - t1
        # ---- two-loss reads: a SECOND data shard gone per stripe —
        # the full Gaussian solve on the device, crc-oracle-checked
        # (informational; the gate pins the single-loss ratio)
        for s in range(n_stripes):
            name = f"t1/s{s}.1"
            p = os.path.join(tmp, "live/storbench", name)
            if os.path.isfile(p):
                os.unlink(p)
                deleted.append(name)
        st._stripe_cache.clear()
        rs_wins = [s * k + 1 for s in range(n_stripes)
                   if s * k + 1 < n_windows]
        t2 = time.perf_counter()
        for w in rs_wins:
            if st.restore_window("/live/storbench", 1, w) != blobs[w]:
                return {"error": f"rs read mismatch at window {w}"}
        rs_s = time.perf_counter() - t2
        # ---- repair: re-materialize every deleted shard from the
        # survivors (the dead-holder path, run synchronously)
        t2 = time.perf_counter()
        repaired_bytes = 0
        for name in deleted:
            nb = st.repair_now("/live/storbench", name)
            if not nb:
                return {"error": f"repair failed for shard {name}"}
            repaired_bytes += nb
        repair_s = time.perf_counter() - t2
        # ---- scrub the whole (repaired) store: zero errors expected
        st._scrub_cursor = []
        scrubbed = st.scrub_tick(batch=1 << 20)
        stats = st.stats()
        direct_pps = n_windows / max(direct_s, 1e-9)
        recon_pps = n_windows / max(recon_s, 1e-9)
        rs_pps = len(rs_wins) / max(rs_s, 1e-9)
        return {
            "windows": n_windows,
            "shards": stats["shards_local"],
            "direct_pps": round(direct_pps, 1),
            "reconstruct_pps": round(recon_pps, 1),
            "reconstruct_vs_direct": round(
                recon_pps / max(direct_pps, 1e-9), 3),
            "rs_two_loss_pps": round(rs_pps, 1),
            "repair_mbps": round(
                repaired_bytes / max(repair_s, 1e-9) / 1e6, 2),
            "repaired_shards": len(deleted),
            "scrubbed": scrubbed,
            "scrub_errors": stats["scrub_errors"],
            "oracle_mismatches": stats["oracle_mismatches"],
            "device_passes": stats["device_passes"],
            "method": (
                f"{n_windows} windows x {window_bytes} B sharded "
                f"{k}+{m} per stripe (parity = fec_parity_window_step "
                "device matmul, host-oracle-checked).  direct_pps = "
                "healthy replay, every window from its local shard "
                "(crc-verified); reconstruct_pps = the same replay "
                "after ONE data shard per stripe is lost (the single-"
                "holder-loss shape the soak SIGKILLs): each stripe "
                "gathers survivors once, solves through the XOR parity "
                "row and serves the stripe from the solve.  "
                "rs_two_loss_pps = reads with TWO shards gone per "
                "stripe — the full Gaussian device solve, crc-oracle-"
                "checked (informational).  repair_mbps = bytes re-"
                "materialized / wall time re-deriving every deleted "
                "shard from survivors (data = solve, parity = re-"
                "encode matmul).  scrub re-walks the repaired store "
                "against manifest crc32s + the parity host oracle; "
                "scrub_errors must be 0."),
        }
    finally:
        st.close()
        shutil.rmtree(tmp, ignore_errors=True)


def tcp_delivery_section(*, n_outputs: int = 16, n_new: int = 64,
                         seconds: float = 3.0) -> dict:
    """ISSUE 14 section: interleaved-TCP fan-out through the ENGINE
    path (framed writev/io_uring batches rendered in C from the shared
    affine device pass) vs the per-session batch-header baseline, over
    REAL TCP loopback sockets.

    Phase 1 proves byte-identical framing at the socket level (engine
    vs baseline streams compared per connection); phase 2 measures
    paired order-flipped throughput windows with an untimed drain
    between them, the same interleave discipline as the UDP headline."""
    import random as random_mod
    import socket as socket_mod
    import statistics

    from easydarwin_tpu.protocol import rtp as rtp_mod
    from easydarwin_tpu.protocol import sdp as sdp_mod
    from easydarwin_tpu.relay.fanout import TpuFanoutEngine
    from easydarwin_tpu.relay.output import RelayOutput, WriteResult
    from easydarwin_tpu.relay.stream import RelayStream, StreamSettings

    sdp_txt = ("v=0\r\ns=t\r\nt=0 0\r\nm=video 0 RTP/AVP 96\r\n"
               "a=rtpmap:96 H264/90000\r\na=control:trackID=1\r\n")

    class _Sink(RelayOutput):
        def __init__(self, sock, chan, *, fast, **kw):
            super().__init__(**kw)
            self.sock = sock
            self.rtp_channel = chan
            self.rtcp_channel = chan + 1
            self.stream_fd = sock.fileno() if fast else -1

        @property
        def interleave_chan(self):
            return self.rtp_channel

        def engine_writable(self):
            return True

        def push_tail(self, data):
            self.sock.setblocking(True)
            self.sock.sendall(data)
            self.sock.setblocking(False)
            return True

        def send_bytes(self, data, *, is_rtcp):
            if is_rtcp:
                return WriteResult.OK
            blob = (b"$" + bytes((self.rtp_channel,))
                    + len(data).to_bytes(2, "big") + data)
            try:
                n = self.sock.send(blob)
            except BlockingIOError:
                return WriteResult.WOULD_BLOCK
            while n < len(blob):            # deep buffers: rare
                try:
                    n += self.sock.send(blob[n:])
                except BlockingIOError:
                    time.sleep(0.0005)
            return WriteResult.OK

    def pair():
        srv = socket_mod.socket()
        srv.setsockopt(socket_mod.SOL_SOCKET, socket_mod.SO_RCVBUF,
                       1 << 22)
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        a = socket_mod.socket()
        a.setsockopt(socket_mod.SOL_SOCKET, socket_mod.SO_SNDBUF, 1 << 22)
        a.connect(srv.getsockname())
        b, _ = srv.accept()
        srv.close()
        a.setblocking(False)
        b.setblocking(False)
        a.setsockopt(socket_mod.IPPROTO_TCP, socket_mod.TCP_NODELAY, 1)
        return a, b

    def drain(sock):
        out = b""
        while True:
            try:
                c = sock.recv(1 << 20)
            except BlockingIOError:
                return out
            if not c:
                return out
            out += c

    def build(fast):
        rng = random_mod.Random(3)
        st = RelayStream(sdp_mod.parse(sdp_txt).streams[0],
                         StreamSettings(bucket_delay_ms=0))
        taps = []
        for i in range(n_outputs):
            a, b = pair()
            o = _Sink(a, (2 * i) & 0xFF, fast=fast,
                      ssrc=rng.getrandbits(32),
                      out_seq_start=rng.getrandbits(16),
                      out_ts_start=rng.getrandbits(32))
            st.add_output(o)
            taps.append((o, b))
        return st, taps

    def push_burst(st, base_seq, count):
        for i in range(count):
            pay = bytes(((3 << 5) | (5 if i == 0 else 1),)) \
                + bytes(((base_seq + i) * 7 + j) & 0xFF
                        for j in range(180 + (i % 16) * 8))
            st.push_rtp(rtp_mod.RtpPacket(
                payload_type=96, seq=(base_seq + i) & 0xFFFF,
                timestamp=(base_seq + i) * 3000 & 0xFFFFFFFF,
                ssrc=0x7C7C, payload=pay).to_bytes(), 1000 + base_seq + i)

    st_e, taps_e = build(True)
    st_b, taps_b = build(False)
    eng_e = TpuFanoutEngine()
    eng_b = TpuFanoutEngine()           # fast=False sinks: baseline rung
    # phase 1: socket-level framing identity over one mixed-size window
    push_burst(st_e, 0, n_new)
    push_burst(st_b, 0, n_new)
    now = 1000 + n_new + 100
    eng_e.step(st_e, now)
    eng_b.step(st_b, now)
    mismatches = 0
    for (oe, re_), (ob, rb_) in zip(taps_e, taps_b):
        if drain(re_) != drain(rb_):
            mismatches += 1
    backend = eng_e.stream_backend()
    # phase 2: paired order-flipped throughput windows
    e_rates, b_rates = [], []
    seq = n_new
    t_end = time.perf_counter() + seconds
    flip = False
    while time.perf_counter() < t_end:
        order = [(st_b, eng_b, taps_b, b_rates),
                 (st_e, eng_e, taps_e, e_rates)]
        if flip:
            order.reverse()
        flip = not flip
        push_burst(st_e, seq, n_new)
        push_burst(st_b, seq, n_new)
        seq += n_new
        now = 1000 + seq + 100
        for st, eng, taps, rates in order:
            c0 = time.perf_counter()
            sent = eng.step(st, now)
            el = time.perf_counter() - c0
            if sent and el > 0:
                rates.append(sent / el)
            for _o, r_ in taps:          # untimed catch-up drain
                drain(r_)
    for st, taps in ((st_e, taps_e), (st_b, taps_b)):
        for o, r_ in taps:
            o.sock.close()
            r_.close()
    e_med = statistics.median(e_rates) if e_rates else 0.0
    b_med = statistics.median(b_rates) if b_rates else 0.0
    return {
        "engine_pkts_per_sec": round(e_med, 1),
        "baseline_pkts_per_sec": round(b_med, 1),
        "speedup": round(e_med / b_med, 2) if b_med else 0.0,
        "wire_mismatches": mismatches,
        "stream_backend": backend,
        "outputs": n_outputs,
        "pairs": min(len(e_rates), len(b_rates)),
        "method": (
            "Paired order-flipped [engine framed-writev pass | "
            "per-session batch-header pass] windows over real TCP "
            "loopback (16 connections, mixed sizes, deep buffers, "
            "untimed drain between timed windows); wire identity "
            "proven on drained byte streams before timing."),
    }


def fec_section(*, seconds: float = 3.0, loss_pct: float = 8.0) -> dict:
    """ISSUE 11 reliability-tier section: one FEC-armed subscriber
    behind a seeded ``loss_pct`` drop schedule.  The closed loop is
    driven honestly — the receiver's measured loss feeds the controller
    as RRs, overhead climbs the ladder — and the figures are goodput
    (delivered + recovered), the recovered-vs-lost ratio, and the
    NACK→RTX replay p99 for the residue FEC could not solve.  The
    device parity oracle mismatch count rides along (must be 0)."""
    import random
    import struct

    from easydarwin_tpu import obs
    from easydarwin_tpu.protocol import sdp as sdp_mod
    from easydarwin_tpu.relay.fec import (FecConfig, FecOutputState,
                                          FecReceiver)
    from easydarwin_tpu.relay.output import CollectingOutput
    from easydarwin_tpu.relay.stream import RelayStream, StreamSettings

    mm_base = obs.FEC_PARITY_ORACLE_MISMATCH.value()
    sdp_txt = ("v=0\r\ns=f\r\nt=0 0\r\nm=video 0 RTP/AVP 96\r\n"
               "a=rtpmap:96 H264/90000\r\na=control:trackID=1\r\n")
    st = RelayStream(sdp_mod.parse(sdp_txt).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    cfg = FecConfig(window=16)
    out = CollectingOutput(ssrc=0xFEC0FEC0, out_seq_start=1000)
    out.fec = FecOutputState(cfg)
    st.add_output(out)
    rx = FecReceiver(media_pt=96, fec_pt=cfg.payload_type,
                     rtx_pt=cfg.rtx_payload_type)
    rng = random.Random(11)
    prob = loss_pct / 100.0
    t = 1000
    seq = 0
    delivered = lost = 0
    rtx_lat_ms: list[float] = []
    interval_lost = interval_seen = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        for _ in range(32):                  # one burst per loop turn
            pay = bytes(rng.randrange(256) for _ in range(180))
            pkt = (struct.pack("!BBHII", 0x80, 96, seq & 0xFFFF,
                               seq * 3000 & 0xFFFFFFFF, 0xB) + pay)
            st.push_rtp(pkt, t)
            seq += 1
        st.reflect(t)
        for p in out.rtp_packets:
            is_media = (p[1] & 0x7F) == 96
            if is_media:
                interval_seen += 1
            if rng.random() < prob:
                # the seeded schedule drops EVERYTHING — media, parity
                # and RTX ride the same lossy last mile (the soak's
                # lossy-player semantics); only media loss counts into
                # the recovered-vs-lost denominator
                if is_media:
                    lost += 1
                    interval_lost += 1
                continue
            if is_media:
                delivered += 1
            rx.on_packet(p)
        out.rtp_packets.clear()
        if interval_seen >= 256:
            # honest closed loop: the receiver's measured loss feeds
            # the controller exactly as an RTCP RR would
            out.fec.controller.on_receiver_report(
                interval_lost / interval_seen)
            interval_lost = interval_seen = 0
        t += 20
    elapsed = time.perf_counter() - t0
    # the residue FEC could not solve goes through the NACK→RTX rung,
    # timed per replay (nack issue → restored bytes in hand); RTX
    # replays ride the SAME lossy schedule, so a dropped replay is
    # re-NACKed next round exactly as a real receiver would
    lo = min(rx.media) if rx.media else 0
    hi = max(rx.media) if rx.media else 0
    for _round in range(4):
        miss = rx.missing(lo, hi)
        if not miss:
            break
        for s in miss:
            if rx.have(s) is not None:
                continue        # an earlier replay's parity cascade
                #                 already solved it — don't waste a
                #                 token or record a bogus latency
            t_n = time.perf_counter_ns()
            out.rtp_packets.clear()
            t += 50                       # the bucket refills on the
            st.fec.replay_nacked(out, [s & 0xFFFF], t)   # relay clock
            for p in out.rtp_packets:
                if rng.random() < prob:
                    continue              # the RTX itself was lost
                rx.on_packet(p)
            if s in rx.rtx_restored:      # RTX (not a cascade) solved it
                rtx_lat_ms.append((time.perf_counter_ns() - t_n) / 1e6)
    out.rtp_packets.clear()
    rtx_p99 = (sorted(rtx_lat_ms)[int(len(rtx_lat_ms) * 0.99)
                                  ] if rtx_lat_ms else 0.0)
    # re-snapshot AFTER the rounds: replays can complete parity groups,
    # so FEC-cascade recoveries must count as FEC, not RTX
    recovered_fec = len(rx.recovered)
    recovered = recovered_fec + len(rx.rtx_restored)
    return {
        "loss_pct": loss_pct,
        "seconds": round(elapsed, 2),
        "media_sent": seq,
        "delivered": delivered,
        "lost": lost,
        "recovered_fec": recovered_fec,
        "recovered_rtx": len(rx.rtx_restored),
        "recovered_ratio": round(recovered / max(lost, 1), 4),
        "goodput_pkts_per_sec": round((delivered + recovered)
                                      / max(elapsed, 1e-9), 1),
        "rtx_p99_ms": round(rtx_p99, 3),
        "parity_packets": out.fec.parity_sent,
        "overhead_final": out.fec.controller.overhead,
        "fec_windows": st.fec.windows_emitted if st.fec else 0,
        "oracle_mismatches": int(
            obs.FEC_PARITY_ORACLE_MISMATCH.value() - mm_base),
    }


def requant_drift_stats() -> dict:
    """Open-loop requant drift, QUANTIFIED (VERDICT r3 item 8): PSNR of
    the +6k open-loop rung vs a closed-loop re-encode at the same target
    QP.  The rung is all-intra, so drift is SPATIAL only (DC prediction
    cascades within one picture) and resets at every IDR — successive
    frames do not accumulate error; the cost numbers here are an upper
    bound, amplified by the DC-only measurement codec (every block
    predicts from requanted neighbors)."""
    from easydarwin_tpu.codecs.h264_intra import (decode_iframe,
                                                  encode_iframe, psnr)
    from easydarwin_tpu.codecs.h264_requant import SliceRequantizer
    from easydarwin_tpu.utils.synth import synth_luma

    img = synth_luma(96)
    out = {}
    for dq in (6, 12):
        src = encode_iframe(img, 24)
        rq = SliceRequantizer(dq)
        open_loop = psnr(img, decode_iframe(
            [rq.transform_nal(x) for x in src]))
        # the rung's CLOSED-LOOP mode (round 5): residuals re-derived
        # against the output reconstruction, full 8.3 prediction
        rq_cl = SliceRequantizer(dq, prefer_native=False,
                                 closed_loop=True)
        t0 = time.perf_counter()
        closed_rung = psnr(img, decode_iframe(
            [rq_cl.transform_nal(x) for x in src]))
        cl_dt = time.perf_counter() - t0
        closed = psnr(img, decode_iframe(encode_iframe(img, 24 + dq)))
        out[f"requant_drift_q{dq}"] = {
            "open_loop_psnr_db": round(open_loop, 2),
            "closed_loop_rung_psnr_db": round(closed_rung, 2),
            "closed_loop_psnr_db": round(closed, 2),
            "drift_cost_db": round(closed - open_loop, 2),
            "closed_rung_gap_db": round(closed - closed_rung, 2),
            "closed_rung_mbs_per_sec": round(36 / cl_dt, 0)}
    out["h264_requant_drift_db_q6"] = \
        out["requant_drift_q6"]["drift_cost_db"]
    out["h264_requant_closed_gap_db_q6"] = \
        out["requant_drift_q6"]["closed_rung_gap_db"]
    return out


def composed_section(*, n_nodes: int = 2, seconds: float = 45.0) -> dict:
    """ISSUE 15: the composed-workload observatory round — every engine
    serving together across N REAL server processes (live relay +
    3-rung HLS ladder + hot/cold VOD + DVR time-shift + TCP-interleaved
    + a lossy-UDP player, flash crowd, mid-run owner SIGKILL), measured
    and validated through the fleet observability layer itself.

    The round IS ``tools/soak.py --composed`` (multi-process by
    definition — per-tier rates, scaling efficiency and the gapless
    migration can only be measured against real processes), so this
    section runs it as a child and folds its ``COMPOSED STATS`` JSON
    line into ``extra.composed``.  Any failure verdict in the soak
    fails the section — a composed figure from a broken round would
    poison the trajectory."""
    import os
    import sys

    root = os.path.dirname(os.path.abspath(__file__))
    # THIS process holds the chip for the bench's whole run, so the
    # soak and every server it starts are pinned to the CPU by name
    # (they print it at boot): one process per chip
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "soak.py"),
         "--composed", str(n_nodes), "--duration", str(seconds)],
        capture_output=True, text=True, timeout=seconds + 240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    stats_line = verdict = None
    for line in (out.stdout or "").splitlines():
        if line.startswith("COMPOSED STATS "):
            stats_line = line[len("COMPOSED STATS "):]
        elif line.startswith("SOAK COMPOSED"):
            verdict = line.split()[2] if len(line.split()) > 2 else "?"
    if stats_line is None:
        tail = (out.stdout or out.stderr or "")[-400:]
        return {"error": f"composed soak produced no stats "
                         f"(rc={out.returncode}): {tail!r}"}
    doc = json.loads(stats_line)
    if verdict != "OK":
        fails = [ln.strip() for ln in (out.stdout or "").splitlines()
                 if ln.startswith("  - ")]
        doc["error"] = f"composed soak verdict {verdict}: {fails[:4]}"
    # ISSUE 16: the wake-ledger decomposition must CONSERVE — the
    # per-class wait+service attribution accounts for >= 90% of the
    # measured mixed p99, or the blame table is naming the wrong
    # suspect and the figure would poison the trajectory
    lb = doc.get("latency_blame") or {}
    cons = lb.get("conservation")
    if "error" not in doc and cons is not None and cons < 0.9:
        doc["error"] = (f"latency blame conserves only {cons:.2f} of "
                        f"the measured mixed p99 (need >= 0.9)")
    return doc


def run_with_timeout(fn, args, timeout_s, **kw):
    box = {}

    def target():
        try:
            box["result"] = fn(*args, **kw)
        except Exception as e:           # noqa: BLE001
            box["error"] = repr(e)

    t = threading.Thread(target=target, daemon=True)
    t.start()
    t.join(timeout_s)
    return box


def main() -> int:
    import os
    import sys

    from easydarwin_tpu import device, native
    native.require()        # every section rides the native egress
    cache_dir = device.enable_compile_cache()
    dev_info = device.resolve()
    print(f"bench: platform={dev_info['platform']} "
          f"device_kind={dev_info['kind']!r} devices={dev_info['count']} "
          f"compile_cache={cache_dir}", file=sys.stderr, flush=True)
    # codec probes run FIRST, before the relay machinery exists: the
    # drain threads and receiver queues it spawns contend for this
    # box's cores and depress the measured walk rate.  Called PLAIN,
    # not through the timeout harness — both are wall-clock bounded by
    # construction, and the harness's non-killable daemon thread is
    # exactly what must not leak into the relay measurement.
    rq_box, drift_box, lad_box = {}, {}, {}
    try:
        rq_box = {"result": h264_requant_throughput()}
    except Exception as e:           # noqa: BLE001
        rq_box = {"error": repr(e)}
    # ISSUE 9 ladder section: the production RequantLadder serve
    # (shared parse + slice x rendition fan-out + ordered reassembly)
    # in paired pooled-vs-serial windows
    try:
        lad_box = {"result": h264_requant_ladder_section()}
    except Exception as e:           # noqa: BLE001
        lad_box = {"error": repr(e)}
    try:
        drift_box = {"result": requant_drift_stats()}
    except Exception as e:               # noqa: BLE001
        drift_box = {"error": repr(e)}

    ring, lens = build_load()
    raise_rmem_cap()
    socks, addrs = make_receivers()
    drain = Drain(socks)
    drain.start()
    box = run_with_timeout(paired_rates, (ring, lens, addrs, drain), 180.0)
    if "result" not in box:
        # the headline IS the device step: without it there is nothing
        # to report under these metric names, on any backend
        drain.stop_flag = True
        print(f"bench: device step produced no result on "
              f"{dev_info['platform']}: {box.get('error', 'timeout')}",
              file=sys.stderr, flush=True)
        return 1

    tpu_rate, c_rate, ratio_med, info = box["result"]
    py_rate = cpu_reference_rate(ring, lens, addrs)
    sc_box = run_with_timeout(server_cost_paired, (ring, lens), 60.0)
    ratio_server_cost = sc_box.get("result", 0.0)
    srv_box = run_with_timeout(server_engine_rate, (addrs,), 90.0)
    srv_cap = srv_box.get("result", 0.0)
    # baseline the process-cumulative histograms HERE so the phase/
    # latency export below describes ONLY the pump-driven latency
    # section — server_engine_rate just stepped the same engine class
    # back-to-back and its un-paced passes must not leak into the means
    from easydarwin_tpu.obs import (RELAY_INGEST_TO_WIRE, phase_breakdown,
                                    phase_snapshot)
    phase_base = phase_snapshot()
    itw_base = (RELAY_INGEST_TO_WIRE.total_count(),
                RELAY_INGEST_TO_WIRE.total_sum())
    lat_box = run_with_timeout(measured_added_latency, (addrs,), 120.0)
    if "result" in lat_box:
        pump_rate, srv_p50, srv_p99, eng = lat_box["result"]
        ring_ratio = (eng.h2d_appended_bytes
                      / max(eng.h2d_window_equiv_bytes, 1))
        eng_extra = {
            "h2d_appended_bytes": eng.h2d_appended_bytes,
            "h2d_window_equiv_bytes": eng.h2d_window_equiv_bytes,
            "h2d_ring_savings": round(1.0 - ring_ratio, 4),
            "engine_gso_enabled": not eng._gso_disabled,
            "engine_gso_strikes": eng._gso_strikes,
        }
    else:
        pump_rate = srv_p50 = srv_p99 = 0.0
        eng_extra = {"engine_error": lat_box.get("error", "unavailable")}
    # phase attribution from the SAME pump-driven passes the latency
    # percentiles come from: the snapshots taken just before
    # measured_added_latency difference away every earlier section's
    # passes, so phase_ms / the Σ(phase means) vs ingest→wire mean
    # cross-check describe exactly the latency measurement
    phases_full = phase_breakdown(since=phase_base)
    itw_count = RELAY_INGEST_TO_WIRE.total_count() - itw_base[0]
    itw_mean_ms = ((RELAY_INGEST_TO_WIRE.total_sum() - itw_base[1])
                   / itw_count * 1e3 if itw_count > 0 else 0.0)
    eng_extra["phase_breakdown"] = phases_full
    eng_extra["phase_ms"] = {ph: row["mean_ms"]
                             for ph, row in sorted(phases_full.items())}
    eng_extra["phase_sum_mean_ms"] = round(
        sum(row["mean_ms"] for row in phases_full.values()), 4)
    eng_extra["ingest_to_wire_mean_ms"] = round(itw_mean_ms, 4)

    # ISSUE 4 multi-source section: megabatch vs per-stream at 16
    # concurrent sources (the drain threads are still running, so the
    # receiver queues never overflow)
    ms_box = run_with_timeout(multi_source_latency, (addrs,), 90.0)
    ms_extra = ms_box.get("result",
                          {"error": ms_box.get("error", "unavailable")})

    # ISSUE 7 multi-device section: megabatch-on-mesh packets/s +
    # scaling efficiency on the devices this process holds (a note, not
    # a number, on a one-device box)
    mc_box = run_with_timeout(multichip_section, (), 360.0)
    mc_extra = mc_box.get("result",
                          {"error": mc_box.get("error", "unavailable")})

    # ISSUE 8 egress-backend section: the probe-ladder verdict + paired
    # per-backend capacity (scalar / gso / io_uring where granted)
    eb_box = run_with_timeout(egress_backend_section, (addrs,), 60.0)
    eb_extra = eb_box.get("result",
                          {"error": eb_box.get("error", "unavailable")})

    # ISSUE 10 VOD section: hot segment-cache serving vs the cold
    # per-sample mmap path, N subscribers x M assets with seek churn
    vd_box = run_with_timeout(vod_section, (addrs,), 180.0)
    vd_extra = vd_box.get("result",
                          {"error": vd_box.get("error", "unavailable")})

    # ISSUE 12 DVR section: spill throughput + time-shift join rate vs
    # live join rate + the zero-repack pin across a spilled re-open
    dv2_box = run_with_timeout(dvr_section, (addrs,), 120.0)
    dv2_extra = dv2_box.get("result",
                            {"error": dv2_box.get("error",
                                                  "unavailable")})

    # ISSUE 20 erasure-storage section: reconstruct-read vs direct-read
    # window throughput, repair MB/s over re-derived shards, and the
    # zero-scrub-error pin over the repaired store
    sg_box = run_with_timeout(storage_section, (), 90.0)
    sg_extra = sg_box.get("result",
                          {"error": sg_box.get("error", "unavailable")})

    # ISSUE 11 reliability-tier section: goodput under seeded loss,
    # recovered-vs-lost, NACK→RTX replay p99, parity-oracle verdict
    fc_box = run_with_timeout(fec_section, (), 60.0)
    fc_extra = fc_box.get("result",
                          {"error": fc_box.get("error", "unavailable")})

    # ISSUE 14 TCP delivery section: engine framed-interleave fan-out
    # vs the per-session batch-header baseline over real TCP loopback,
    # with socket-level framing identity proven before timing
    td_box = run_with_timeout(tcp_delivery_section, (), 90.0)
    td_extra = td_box.get("result",
                          {"error": td_box.get("error", "unavailable")})

    # ISSUE 15 composed-observatory section: the full mixed workload
    # across 2 real server processes with a mid-run owner kill, measured
    # through the fleet endpoint (BENCH_r06's new round).  Runs LAST of
    # the heavy sections so its child processes never share the box with
    # a timed in-process window.
    cp_box = run_with_timeout(composed_section, (), 420.0)
    cp_extra = cp_box.get("result",
                          {"error": cp_box.get("error", "unavailable")})

    rq_extra = rq_box.get("result",
                          {"h264_requant_note":
                           rq_box.get("error", "unavailable")})
    rq_extra.update(drift_box.get("result", {}))
    # ISSUE 9: the nested ladder section (extra.h264_requant) carries
    # renditions_requested/sustained, the paired parallel-vs-serial
    # speedup, measured worker concurrency and the shared-parse
    # amortization ratio.  The flat h264_requant_1080p30_renditions key
    # keeps its r01-r05 grind semantics (aggregate raw-walk rate /
    # 1080p30) for trajectory continuity; the section's
    # h264_requant_1080p30_renditions is the PRODUCTION-PATH figure —
    # the pooled ladder's measured rendition rate, pipeline overheads
    # and all — and is the one the ladder acceptance reads.
    rq_extra["h264_requant"] = lad_box.get(
        "result", {"error": lad_box.get("error", "unavailable")})
    if "renditions_sustained" in rq_extra["h264_requant"]:
        rq_extra["h264_requant"]["h264_requant_1080p30_renditions"] = \
            rq_extra["h264_requant"]["renditions_sustained"]

    time.sleep(0.2)
    drain.stop_flag = True
    received = drain.count
    for s in socks:
        s.close()

    value = tpu_rate if tpu_rate > 0 else c_rate
    details = {
        "metric": "relay_packets_to_wire_per_sec",
        "value": round(value, 1),
        "unit": "packets/s",
        "vs_baseline": round(ratio_med, 2),
        "extra": {
            "cpu_c_baseline_rate": round(c_rate, 1),
            "cpu_python_rate": round(py_rate, 1),
            "server_engine_rate": round(srv_cap, 1),
            "server_pump_rate": round(pump_rate, 1),
            "p50_added_ms": round(srv_p50, 2),
            "p99_added_ms": round(srv_p99, 2),
            "latency_method": (
                "MEASURED ingest-to-wire: packets stamped at push_rtp "
                "inside a real asyncio pump; latency = engine-pass native "
                "egress return minus the burst's push stamp (includes the "
                "event-loop wake). No assumed scheduling terms. "
                "server_engine_rate is the engine's back-to-back CAPACITY "
                "(full window re-sent per pass, r02 semantics); "
                "server_pump_rate is the pacing-bounded rate of the "
                "latency pump (offered load ~1080p30 bursts), not "
                "capacity."),
            "datagrams_drained": received,
            "sustainable_1080p30_subscribers_per_source":
                round(value / (PKTS_PER_SEC_1080P30 * N_SRC), 1),
            "config": {"sources": N_SRC, "subscribers": N_SUB,
                       "window_pkts": N_PKT, "pkt_bytes": PKT_BYTES},
            "real_flows": N_SUB,
            "extrapolated": False,
            "vs_baseline_server_cost": round(ratio_server_cost, 2),
            "ratio_ceiling_note": (
                "The headline ratio is LOOPBACK-KERNEL-DELIVERY bound, "
                "not engine bound: raw egress with no device step in the "
                "loop measures ~the same per-packet cost, and prototyped "
                "variants (connected sockets: +1.7%; MSG_ZEROCOPY: parity "
                "— 46-segment supers sit under MAX_SKB_FRAGS) do not move "
                "it. Added-latency targets are met with wheel-deadline "
                "wakeups (p99 well under the r2 37.4 ms)."),
            "server_cost_method": (
                "Corroborating paired ratio with receiver queues "
                "saturated for BOTH paths (GRO receivers, tiny buffers, "
                "never drained): times exactly the serving host's cost — "
                "syscalls, rewrites, kernel copy, loopback traversal, "
                "delivery attempt — excluding receiver-side consumption, "
                "which belongs to (remote) subscribers, not the server. "
                "Extra only; the headline vs_baseline includes full "
                "delivery and concurrent drain."),
            "method": (
                "All 256 logical subscribers/source are REAL wire flows: "
                "64 loopback IPs x 4 UDP ports, received by 4 wildcard "
                "sockets with deep (16MB) buffers, drained concurrently "
                "(GRO + MSG_TRUNC recvmmsg); no extrapolation "
                "(VERDICT r2 item 7). vs_baseline is the MEDIAN OF "
                "PER-PAIR RATIOS from interleaved [TPU pass | scalar pass] "
                "windows with an untimed drain catch-up barrier between "
                "them, so each timed window carries only its own receiver "
                "work and shared-VM load drift cancels "
                "(sequential-median ratios swing +/-30% on this box). "
                "cpu_c_baseline_rate = single-thread C scalar sendto loop "
                "(the reference architecture) over a 16-flow slice per "
                "pass (scalar cost is per-op; rate is volume-invariant). "
                "Loopback UDP GSO/GRO stands in for NIC UDP offload. "
                "p50/p99_added_ms: see latency_method."),
            "multi_source": ms_extra,
            "multichip": mc_extra,
            "egress_backends": eb_extra,
            "vod": vd_extra,
            "dvr": dv2_extra,
            "storage": sg_extra,
            "fec": fc_extra,
            "tcp_delivery": td_extra,
            "composed": cp_extra,
            **eng_extra,
            **rq_extra,
            **info,
        },
    }
    # The driver captures only a bounded TAIL of stdout and must parse a
    # single JSON line from it (BENCH_r03 broke that with a >4 KB line:
    # the captured tail started mid-JSON, parsed: null).  Contract: full
    # prose/method detail goes to bench_details.json; stdout gets ONE
    # compact line with the headline numbers only.
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_details.json"), "w") as f:
        json.dump(details, f, indent=1)
    ex = details["extra"]
    compact_extra = {
        k: ex[k] for k in (
            "cpu_c_baseline_rate", "server_engine_rate", "p50_added_ms",
            "p99_added_ms", "vs_baseline_server_cost", "real_flows",
            "delivery_loss_pct", "h264_requant_mbs_per_sec",
            "h264_requant_cabac_mbs_per_sec",
            "h264_requant_parallel_mbs_per_sec",
            "h264_requant_1080p30_renditions", "h264_requant_workers",
            "h264_requant_sizing", "h264_requant_drift_db_q6",
            "device", "device_platform", "device_kind", "device_count",
            "sustainable_1080p30_subscribers_per_source",
            "phase_ms", "phase_sum_mean_ms", "ingest_to_wire_mean_ms")
        if k in ex}
    ms = ex.get("multi_source") or {}
    compact_extra["multi_source"] = {
        k: ms[k] for k in (
            "sources", "streams_per_pass", "megabatch_p99_added_ms",
            "per_stream_p99_added_ms", "megabatch_device_passes_per_wake",
            "per_stream_device_passes_per_wake",
            # the wire-mismatch scalar and the error marker MUST survive
            # the compact projection: the trajectory gate reads only this
            # line, and a stripped error would read as a malformed round
            "megabatch_wire_mismatches", "error")
        if k in ms}
    mc = ex.get("multichip") or {}
    compact_extra["multichip"] = {
        k: mc[k] for k in (
            "n_devices", "packets_per_sec", "packets_per_sec_per_device",
            "single_device_packets_per_sec", "scaling_efficiency",
            "sharded_passes",
            # the mismatch scalar and the error marker survive the
            # compact projection for the same reason multi_source's do:
            # the trajectory gate reads only this line
            "wire_mismatches", "note", "error")
        if k in mc}
    rq_l = ex.get("h264_requant") or {}
    compact_extra["h264_requant"] = {
        k: rq_l[k] for k in (
            "renditions_requested", "renditions_sustained",
            "h264_requant_1080p30_renditions", "workers",
            "parallel_speedup", "worker_concurrency", "workers_engaged",
            "shared_parse_amortization", "ladder_rendition_mbs_per_sec",
            "slices_per_au", "sheds",
            # the error marker survives the compact projection for the
            # same trajectory-gate reason multi_source's does
            "error")
        if k in rq_l}
    eb = ex.get("egress_backends") or {}
    compact_extra["egress_backends"] = {
        k: eb[k] for k in (
            # the whole section is compact by construction; the error
            # marker survives the projection for the same trajectory-
            # gate reason multi_source's does
            "backends", "effective", "probe_caps", "probe_errno",
            "io_uring_sqpoll", "io_uring_zerocopy", "error")
        if k in eb}
    vd = ex.get("vod") or {}
    compact_extra["vod"] = {
        k: vd[k] for k in (
            "subscribers", "assets", "hot_pkts_per_sec",
            "cold_pkts_per_sec", "hot_vs_cold", "cache_hit_rate",
            "hbm_window_uploads",
            # the mismatch scalar and the error marker survive the
            # compact projection for the same trajectory-gate reason
            # multi_source's do
            "wire_mismatches", "error")
        if k in vd}
    dv2 = ex.get("dvr") or {}
    compact_extra["dvr"] = {
        k: dv2[k] for k in (
            "spill_mbps", "live_join_pps", "timeshift_join_pps",
            "timeshift_vs_live", "reopen_repacks", "spilled_windows",
            # the repack scalar and the error marker survive the
            # compact projection for the same trajectory-gate reason
            # multi_source's do
            "error")
        if k in dv2}
    sg2 = ex.get("storage") or {}
    compact_extra["storage"] = {
        k: sg2[k] for k in (
            "direct_pps", "reconstruct_pps", "reconstruct_vs_direct",
            "rs_two_loss_pps", "repair_mbps", "repaired_shards", "shards",
            # the scrub/oracle scalars and the error marker survive
            # the compact projection for the same trajectory-gate
            # reason multi_source's do
            "scrub_errors", "oracle_mismatches", "error")
        if k in sg2}
    fc = ex.get("fec") or {}
    compact_extra["fec"] = {
        k: fc[k] for k in (
            "loss_pct", "goodput_pkts_per_sec", "recovered_ratio",
            "recovered_fec", "recovered_rtx", "lost", "rtx_p99_ms",
            "overhead_final",
            # the mismatch scalar and the error marker survive the
            # compact projection for the same trajectory-gate reason
            # multi_source's do
            "oracle_mismatches", "error")
        if k in fc}
    td = ex.get("tcp_delivery") or {}
    compact_extra["tcp_delivery"] = {
        k: td[k] for k in (
            "engine_pkts_per_sec", "baseline_pkts_per_sec", "speedup",
            "stream_backend", "outputs",
            # the mismatch scalar and the error marker survive the
            # compact projection for the same trajectory-gate reason
            # multi_source's do
            "wire_mismatches", "error")
        if k in td}
    cp = ex.get("composed") or {}
    compact_extra["composed"] = {
        k: cp[k] for k in (
            "nodes", "tier_rates", "scaling_efficiency",
            "migration_gap_packets", "mixed_p99_ms",
            "e2e_freshness_p99_s", "unresolved_traces",
            "fleet_nodes_live",
            # the mismatch scalar and the error marker survive the
            # compact projection for the same trajectory-gate reason
            # multi_source's do
            "wire_mismatches", "error")
        if k in cp}
    lb = cp.get("latency_blame") or {}
    if lb:
        # the blame headline survives the compact projection: WHO owns
        # the p99 and how much of it the ledger accounts for
        compact_extra["composed"]["latency_blame"] = {
            k: lb[k] for k in (
                "top_offender", "attributed_p99_ms", "measured_p99_ms",
                "conservation")
            if k in lb}
    aud = cp.get("audience") or {}
    if aud:
        # the audience headline survives the compact projection: how
        # the VIEWERS fared (QoE distribution, stall pressure) next to
        # the engine-side figures
        compact_extra["composed"]["audience"] = {
            k: aud[k] for k in (
                "subscribers", "qoe_p50", "qoe_p10", "stall_ratio",
                "stall_storms", "columns_bytes_per_subscriber")
            if k in aud}
    compact_extra["details_file"] = "bench_details.json"
    print(json.dumps({
        "metric": details["metric"],
        "value": details["value"],
        "unit": details["unit"],
        "vs_baseline": details["vs_baseline"],
        "extra": compact_extra,
    }, separators=(",", ":")))
    # every section was asked for: one without a result fails the run
    # (the JSON above still carries its error marker for the trajectory)
    failed = {name: b.get("error", "timeout") for name, b in (
        ("h264_requant_throughput", rq_box), ("requant_drift", drift_box),
        ("h264_requant_ladder", lad_box), ("server_cost", sc_box),
        ("server_engine_rate", srv_box), ("added_latency", lat_box),
        ("multi_source", ms_box), ("multichip", mc_box),
        ("egress_backends", eb_box), ("vod", vd_box), ("dvr", dv2_box),
        ("storage", sg_box), ("fec", fc_box), ("tcp_delivery", td_box),
        ("composed", cp_box)) if "result" not in b}
    failed.update({name: doc["error"] for name, doc in (
        ("multi_source", ms_extra), ("multichip", mc_extra),
        ("egress_backends", eb_extra), ("vod", vd_extra),
        ("dvr", dv2_extra), ("storage", sg_extra), ("fec", fc_extra),
        ("tcp_delivery", td_extra), ("composed", cp_extra))
        if isinstance(doc, dict) and "error" in doc})
    for name, why in failed.items():
        print(f"bench: section {name} has no result: {why}",
              file=sys.stderr, flush=True)
    return 1 if failed else 0




if __name__ == "__main__":
    import sys as _sys
    _sys.exit(main())
