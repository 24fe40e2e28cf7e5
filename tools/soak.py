"""Integration soak: one server, many concurrent features, N seconds.

Exercises simultaneously: TCP-interleaved push + UDP push (native
recvmmsg ingest), interleaved players, UDP players on the shared egress
(one with reliable-UDP, one sending NADU feedback), an HLS viewer
pulling the temporal + requant renditions, and REST polling — then
checks: no error-log growth, all players progressing, requant stats
advancing, zero engine send errors, zero flight-recorder dumps (an
abnormal session teardown during a clean soak IS the regression), no
structured-event ring overflow, live phase-attribution histograms
(``relay_phase_seconds``), and zero SLO burn (no ``slo.violation``
events counted, no ``slo_budget_remaining_ratio`` at or below zero).

``--chaos [SEED]`` runs the same soak under a seeded FaultPlan
(resilience/inject.py: 5% ingest drop, periodic egress ENOBUFS +
latency spikes, device-dispatch failures, stale params) with the engine
paths and the degradation ladder engaged, clears the faults with ~45 s
left, and fails on: zero injected faults, zero ladder degradations, any
``ladder.degrade`` without a matching ``ladder.recover``, any stream
still below full service at exit, recovery slower than 30 s after
clearance, nonzero megabatch wire mismatches, or starved players — the
"never stops serving" half of the contract.  Feature-completeness
checks that the injected drops legitimately break (HLS muxing/requant
stats) are asserted only by the clean soak.

``--dvr N`` adds N interleaved time-shift subscribers on the armed live
push (dvr_enabled: every pushed broadcast records) who continuously
PAUSE and re-PLAY into the past — even players rewind with ``Range:
npt=0.0-``, odd players resume from the PAUSE bookmark, both at Speed 4
so the catch-up state machine rejoins live over and over — plus a
mid-soak ``stoprecord`` whose finalized asset must re-open as instant
VOD (``/live/a.dvr``).  Fails on: any forward out-seq jump at a player
(lost playback across a shift or catch-up join; replays legitimately
re-cover already-sent seqs — duplicates and backward hops are fine),
more than one ssrc per player, any ``pack_window`` invocation (spilled
opens are zero-repack by contract), a spill retention budget overrun,
ring-eviction window loss, zero counted catch-up joins, or a starved
player.

``--cluster N`` runs the multi-server robustness scenario instead
(ISSUE 6): a mini Redis + N real server processes with the cluster tier
on, one pushed stream placed by consistent hash, a UDP subscriber on the
owner, a persistent pull-relay subscriber on a non-owner, subscriber
churn, a flash-crowd join wave — and a seeded SIGKILL of the owner
mid-soak that must recover via checkpoint-driven migration: the UDP
player (which never re-SETUPs) sees the SAME ssrc with ZERO sequence
gap, recovery lands within 10 s, the survivor's metrics show nonzero
``cluster_migrations_total``, and every ladder rung is back at full
service at exit.

Usage: python tools/soak.py [--duration SECONDS] [--chaos [SEED]]
[--cluster N] (default 120; the bare positional form ``soak.py 120``
still works)
"""

from __future__ import annotations

import asyncio
import os
import re
import socket
import struct
import sys
import time
import urllib.request

sys.path.insert(0, ".")

import numpy as np  # noqa: E402

from easydarwin_tpu.codecs.h264_intra import encode_iframe  # noqa: E402
from easydarwin_tpu.protocol import nalu  # noqa: E402
from easydarwin_tpu.relay.reliable import build_ack  # noqa: E402
from easydarwin_tpu.server import ServerConfig, StreamingServer  # noqa: E402
from easydarwin_tpu.utils.client import RtspClient  # noqa: E402

SDP = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=soak\r\nt=0 0\r\n"
       "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
       "a=control:trackID=1\r\n")

# A/V variant for pusher A: real coded video + RFC 3640 AAC audio (the
# HLS entry must mux BOTH tracks — VERDICT r3 item 4)
AV_SDP = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=soak\r\nt=0 0\r\n"
          "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
          "a=control:trackID=1\r\n"
          "m=audio 0 RTP/AVP 97\r\n"
          "a=rtpmap:97 mpeg4-generic/48000/2\r\n"
          "a=fmtp:97 streamtype=5; mode=AAC-hbr; config=1190; "
          "sizeLength=13; indexLength=3; indexDeltaLength=3\r\n"
          "a=control:trackID=2\r\n")


def synth_frame(f: int, n: int = 64) -> np.ndarray:
    from easydarwin_tpu.utils.synth import synth_luma
    return synth_luma(n, f)


def parse_metrics(text: str) -> dict[str, float]:
    """Prometheus text exposition → {sample line name+labels: value}."""
    out: dict[str, float] = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        name, _, val = ln.rpartition(" ")
        try:
            out[name] = float(val)
        except ValueError:
            pass
    return out


def write_vod_assets(folder: str, n_assets: int,
                     n_frames: int = 600, fps: int = 30) -> list[str]:
    """Synthetic VOD fixtures for ``--vod``: H.264 (IDR each second) +
    AAC, written with the repo's own muxer.  Returns the asset names."""
    from easydarwin_tpu.vod.mp4_writer import Mp4Writer
    sps = bytes((0x67, 0x42, 0x00, 0x1F, 0xAA, 0xBB, 0xCC, 0xDD))
    pps = bytes((0x68, 0xCE, 0x3C, 0x80))
    names = []
    os.makedirs(folder, exist_ok=True)
    for a in range(n_assets):
        name = f"vodasset{a}.mp4"
        w = Mp4Writer(os.path.join(folder, name))
        v = w.add_h264_track(sps, pps, 640, 480, timescale=90000)
        au = w.add_aac_track(bytes((0x11, 0x90)), 8000, 1)
        dur = 90000 // fps
        for i in range(n_frames):
            idr = i % fps == 0
            nal = bytes((0x65 if idr else 0x41,)) \
                + bytes(((i + a) & 0xFF,)) * (900 if idr else 160)
            w.write_sample(v, len(nal).to_bytes(4, "big") + nal, dur,
                           sync=idr)
        for i in range(int(n_frames / fps * 8000 / 1024)):
            w.write_sample(au, bytes(((i & 0xFF),)) * 40, 1024,
                           sync=True)
        w.close()
        names.append(name)
    return names


def prewarm_batch_shapes(pads=(16, 32, 64, 128)) -> None:
    """Pre-trace the engine jit shapes a VOD soak exercises, BEFORE the
    clock starts — the same cold-jit protection the multi-source
    section applies to stacked shapes.  Traces the jitted steps
    DIRECTLY (zero inputs, same jit cache keys) rather than stepping a
    real stream: a stepped stream's sends would observe the compile
    wall time into the very ingest→wire histograms the SLO reads."""
    from easydarwin_tpu.models.relay_pipeline import megabatch_window_step
    from easydarwin_tpu.ops import device_ring
    from easydarwin_tpu.ops import fanout as fanout_ops
    from easydarwin_tpu.ops.staging import ROW_STRIDE
    # the batch-header step, per pow2 window pad (1 TCP subscriber)
    for pad in sorted(pads):
        fanout_ops.relay_batch_step(
            np.zeros((pad, 96), np.uint8), np.zeros(pad, np.int32),
            np.zeros(pad, np.int32),
            np.zeros((1, fanout_ops.STATE_COLS), np.uint32),
            np.zeros(1, np.int32), np.int32(10))
    # the stacked megabatch step: VOD sessions push the eligible stream
    # count past megabatch_min_streams, so the scheduler engages
    # mid-soak — its first bucket shapes must not cold-jit inside a
    # stamped wake either
    import jax
    for b in (1, 2):
        for pp in (16, 32, 64):
            np.asarray(megabatch_window_step(
                jax.device_put(np.zeros((b, pp, ROW_STRIDE), np.uint8)),
                np.zeros((b, 8, fanout_ops.STATE_COLS), np.uint32)))
    # the per-stream resident-ring query (the megabatch fallback the
    # plain-UDP player's engine takes at engagement)
    ring = device_ring.init_ring(4096)
    ring = device_ring.append(ring, np.zeros((16, 96), np.uint8),
                              np.zeros(16, np.int32),
                              np.zeros(16, np.int32), np.int32(1))
    device_ring.query(ring, np.zeros((8, fanout_ops.STATE_COLS),
                                     np.uint32), np.int32(0))


def check_metrics(scrapes: list[dict[str, float]], *,
                  expect_megabatch: bool = False,
                  chaos: bool = False,
                  forced_backend: str | None = None,
                  hls_ladder: int = 0, vod: int = 0,
                  lossy: float = 0.0, dvr: int = 0) -> list[str]:
    """Counter-regression checks over the soak's periodic scrapes.

    ``chaos=True`` (a seeded FaultPlan was armed) skips exactly the
    checks the plan deliberately violates — injected ENOBUFS are hard
    errors, injected drops burn the SLO, a shed subscriber dumps its
    flight box — and adds the resilience invariants instead: faults
    actually injected, every ladder rung back at full service, and the
    wire-mismatch/event-hygiene checks that hold under ANY amount of
    chaos."""
    errs: list[str] = []
    if not scrapes:
        return ["no /metrics scrapes completed"]
    last = scrapes[-1]
    if forced_backend and forced_backend != "auto":
        # --egress-backend X: the EFFECTIVE backend (the info gauge's
        # active child) must be exactly the forced one — a forced
        # io_uring that silently served from the GSO rung is a failed
        # soak, not a degraded-but-passing one
        key = f'egress_backend_info{{backend="{forced_backend}"}}'
        if last.get(key, 0) != 1:
            active = [k for k, v in last.items()
                      if k.startswith("egress_backend_info") and v == 1]
            errs.append(f"forced egress backend {forced_backend!r} is not "
                        f"the effective one (active: {active or 'none'})")
    # zerocopy honesty (any run with ZC completions): on loopback the
    # kernel copies every "zerocopy" send — the copied counter must SAY
    # so.  Completions with zero copies on a loopback soak means the
    # copy verdicts are being dropped, not that zerocopy worked.
    zc = last.get("io_uring_zerocopy_completions_total", 0)
    if zc > 0 and last.get("io_uring_zerocopy_copied_total", 0) == 0:
        errs.append(f"{zc:.0f} zerocopy completions but zero counted "
                    "copies on loopback (copy verdicts hidden)")
    if chaos:
        faults = sum(v for k, v in last.items()
                     if k.startswith("fault_injected_total"))
        if faults == 0:
            errs.append("chaos soak injected zero faults (plan never "
                        "engaged — the run proved nothing)")
        for k, v in last.items():
            if k.startswith("resilience_ladder_level") and v != 0:
                errs.append(f"ladder stuck below full service at exit: "
                            f"{k} = {v:.0f}")
    # megabatch invariants (ISSUE 4): a device/host param divergence is
    # a wire-corruption bug at ANY time; and a multi-source soak where
    # the scheduler never coalesced a single pass means the megabatch
    # path silently disengaged
    if last.get("megabatch_wire_mismatch_total", 0) > 0:
        errs.append(f"megabatch wire mismatches: "
                    f"{last['megabatch_wire_mismatch_total']:.0f} "
                    "(device params disagreed with the host oracle)")
    if expect_megabatch and last.get("megabatch_passes_total", 0) == 0:
        errs.append("multi-source soak ran zero megabatched passes "
                    "(scheduler disengaged)")
    # requant-ladder invariants (ISSUE 9): a reassembly mismatch is a
    # pipeline bookkeeping bug at ANY time; a ladder soak must actually
    # have served AUs through every stage, and a CLEAN ladder soak must
    # never shed (the pool is sized for the box; shedding under the
    # soak's paced load means admission or sizing regressed)
    if last.get("requant_reassembly_mismatch_total", 0) > 0:
        errs.append(f"requant slice-reassembly mismatches: "
                    f"{last['requant_reassembly_mismatch_total']:.0f}")
    if hls_ladder:
        if last.get("requant_aus_total", 0) == 0:
            errs.append("hls-ladder soak requanted zero AUs")
        aus = last.get("requant_aus_total", 0)
        rend = last.get("requant_renditions_total", 0)
        if aus and rend < aus * hls_ladder:
            errs.append(f"ladder width shrank: {rend:.0f} rendition-AUs "
                        f"from {aus:.0f} AUs at width {hls_ladder}")
        stage_obs = sum(v for k, v in last.items()
                        if k.startswith("requant_stage_seconds_count"))
        if stage_obs == 0:
            errs.append("requant_stage_seconds histograms stayed empty")
        if not chaos and last.get("requant_shed_total", 0) > 0:
            errs.append(f"ladder shed AUs during a clean soak: "
                        f"{last['requant_shed_total']:.0f}")
    # VOD segment-cache invariants (ISSUE 10): a --vod soak must have
    # actually served from packed windows (zero hits = the cache never
    # engaged and the run proved nothing) and the hot path must have
    # staged packets; the host-oracle mismatch counter is covered by
    # the unconditional megabatch check above
    if vod:
        if last.get("vod_cache_hits_total", 0) == 0:
            errs.append("vod soak recorded zero segment-cache hits "
                        "(hot path never engaged)")
        if last.get('vod_packets_total{path="hot"}', 0) == 0:
            errs.append("vod soak staged zero hot-path packets")
    # reliability-tier invariants (ISSUE 11): a device/host parity
    # divergence is a wire-corruption bug at ANY time; a lossy soak
    # must have actually recovered something, never exhausted an RTX
    # budget, and the closed loop must have visibly raised overhead
    if last.get("fec_parity_oracle_mismatch_total", 0) > 0:
        errs.append(f"fec parity oracle mismatches: "
                    f"{last['fec_parity_oracle_mismatch_total']:.0f} "
                    "(device GF parity disagreed with the host oracle)")
    if lossy:
        rec = last.get("fec_recovered_total", 0) \
            + last.get("rtx_sent_total", 0)
        if rec == 0:
            errs.append("lossy soak recovered zero packets "
                        "(fec_recovered_total + rtx_sent_total == 0)")
        if last.get("rtx_giveup_total", 0) > 0:
            errs.append(f"RTX budget exhausted during the lossy soak: "
                        f"{last['rtx_giveup_total']:.0f} give-ups")
        overhead = max((v for k, v in last.items()
                        if k.startswith("fec_overhead_ratio")),
                       default=0.0)
        if overhead <= 0.0:
            errs.append("closed-loop FEC overhead never left 0 under "
                        f"{lossy:.0f}% injected loss (controller not "
                        "tracking)")
    # DVR / time-shift invariants (ISSUE 12): a --dvr soak must have
    # actually spilled windows, joined back to live at least once (the
    # catch-up state machine is the thing under test), and served its
    # time-shift sessions (gauge may be 0 at exit — all retired)
    if dvr:
        if last.get("dvr_windows_spilled_total", 0) == 0:
            errs.append("dvr soak spilled zero windows (recorder never "
                        "engaged)")
        if last.get("dvr_catchup_joins_total", 0) == 0:
            errs.append("dvr soak counted zero catch-up joins (no "
                        "time-shift session ever rejoined live — the "
                        "run proved nothing)")
    if last.get("ingest_oversize_dropped_total", 0) > 0:
        errs.append(f"ingest drops: "
                    f"{last['ingest_oversize_dropped_total']:.0f}")
    if not chaos and last.get("egress_send_errors_total", 0) > 0:
        errs.append(f"hard egress errors: "
                    f"{last['egress_send_errors_total']:.0f}")
    calls = last.get("egress_sendmmsg_calls_total", 0) \
        + last.get("egress_sendto_calls_total", 0) \
        + last.get("io_uring_submit_calls_total", 0)
    eagain = last.get("egress_eagain_total", 0)
    if not chaos and calls and eagain / calls > 0.5:
        errs.append(f"EAGAIN retry ratio {eagain / calls:.2f} > 0.5 "
                    f"({eagain:.0f}/{calls:.0f})")
    lat = sum(v for k, v in last.items()
              if k.startswith("relay_ingest_to_wire_seconds_count"))
    if lat == 0:
        errs.append("relay_ingest_to_wire_seconds histogram stayed empty")
    if not chaos and last.get("flight_dumps_total", 0) > 0:
        errs.append(f"flight-recorder dumps during a clean soak: "
                    f"{last['flight_dumps_total']:.0f} (a session died "
                    f"abnormally — fetch command=flight for the black box)")
    if last.get("events_dropped_total", 0) > 0:
        errs.append(f"structured-event ring overflowed: "
                    f"{last['events_dropped_total']:.0f} dropped")
    if last.get("events_invalid_total", 0) > 0:
        errs.append(f"schema-invalid events emitted: "
                    f"{last['events_invalid_total']:.0f}")
    # phase attribution must be live: the pump observes wake_to_pass on
    # every ingest-driven pass even on the scalar path, so an empty
    # relay_phase_seconds means the profiler died or was disabled
    phase_count = sum(v for k, v in last.items()
                      if k.startswith("relay_phase_seconds_count"))
    if phase_count == 0:
        errs.append("relay_phase_seconds histograms stayed empty "
                    "(phase profiler not recording)")
    # SLO burn during a clean soak IS the regression: any violation
    # event (counted per objective) or an exhausted error budget fails.
    # Under chaos the injected drops/latency are SUPPOSED to burn — the
    # ladder checks above own the pass/fail there.
    slo_viol = sum(v for k, v in last.items()
                   if k.startswith("slo_violations_total"))
    if not chaos and slo_viol > 0:
        errs.append(f"SLO violations during a clean soak: {slo_viol:.0f} "
                    "(fetch command=events / command=flight for the "
                    "burn evidence)")
    if not chaos:
        for k, v in last.items():
            if k.startswith("slo_budget_remaining_ratio") and v <= 0:
                errs.append(f"SLO error budget exhausted: {k} = {v}")
    # cumulative families must be monotonic across scrapes (a reset
    # mid-run means double-registration or a counter bug)
    for a, b in zip(scrapes, scrapes[1:]):
        for k, v in a.items():
            # match the FAMILY name: labeled samples end in '}', not _total
            if k.split("{")[0].endswith("_total") and b.get(k, v) < v:
                errs.append(f"counter {k} went backwards: {v} -> {b[k]}")
                break
    return errs


def multi_source_section(n_sources: int, seconds: float = 2.0,
                         devices: int = 1) -> list[str]:
    """Drive the cross-stream megabatch scheduler with ``n_sources``
    native-addressed relay streams in-process (same obs globals the
    server scrapes, so megabatch_* counters land in /metrics).  Returns
    failures; success means stacked passes ran, the per-stream device
    path stayed idle, and zero wire mismatches were counted.

    ``devices > 1`` (``--devices N``) places the stacked passes over a
    src-axis device mesh (ISSUE 7) and additionally fails on zero
    SHARDED passes — a mesh run that silently fell back to
    single-device dispatch proves nothing about the mesh path."""
    from easydarwin_tpu.parallel.megabench import _mk_streams, _precompile
    from easydarwin_tpu.relay import pump
    from easydarwin_tpu.relay.megabatch import MegabatchScheduler

    errs: list[str] = []
    mesh = None
    if devices > 1:
        from easydarwin_tpu.parallel.mesh import make_megabatch_mesh
        mesh = make_megabatch_mesh(devices)
        if mesh is None:
            return [f"--devices {devices}: no mesh (box exposes too few "
                    "devices; set XLA_FLAGS="
                    "--xla_force_host_platform_device_count)"]
    OUTS_PER_STREAM = 8
    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("127.0.0.1", 0))
    recv.setblocking(False)
    recv.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 22)
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    streams, engines = _mk_streams(n_sources, OUTS_PER_STREAM,
                                   [recv.getsockname()], send.fileno(), 5)
    sched = MegabatchScheduler(mesh=mesh)
    pkt = bytes([0x80, 96]) + bytes(10) + bytes(188)
    # pre-compile the stacked step for the shapes this section uses,
    # BEFORE any packet carries an arrival stamp: a cold jit trace with
    # a live backlog turns compile time into real ingest→wire latency
    # and burns the SLO budget the soak asserts on (the burst of 3
    # below pads to the same 16-row window the harness traces)
    _precompile(sched, n_sources, OUTS_PER_STREAM, burst=3)
    t = int(time.monotonic() * 1000)
    seq = 0
    t_end = time.time() + seconds
    while time.time() < t_end:
        for st in streams:
            for _ in range(3):
                st.push_rtp(pkt[:2] + (seq & 0xFFFF).to_bytes(2, "big")
                            + pkt[4:], t)
                seq += 1
        pump.wake(list(zip(streams, engines)), sched, t)
        try:                               # keep the receiver queue empty
            while True:
                recv.recv(65536)
        except BlockingIOError:
            pass
        t += 10
        time.sleep(0.005)
    sched.drain()
    recv.close()
    send.close()
    if sched.passes == 0:
        errs.append(f"multi-source section: zero megabatched passes over "
                    f"{n_sources} sources")
    if mesh is not None and sched.sharded_passes == 0:
        errs.append(f"--devices {devices}: zero SHARDED passes (mesh "
                    "dispatch never engaged)")
    if sched.mismatches:
        errs.append(f"multi-source section: {sched.mismatches} megabatch/"
                    "per-stream wire mismatches")
    per_stream = sum(e.device_param_refreshes + e.dring_appends
                     for e in engines)
    if per_stream:
        errs.append(f"multi-source section: {per_stream} per-stream device "
                    "dispatches while megabatch-owned (coalescing leak)")
    return errs


#: the seeded FaultPlan ``--chaos`` arms (ISSUE 5 acceptance shape: 5%
#: ingest drop, periodic egress ENOBUFS + latency spikes, frequent
#: device-dispatch failures, stale-params invalidations)
CHAOS_PLAN = ("ingest_drop=0.05,egress_enobufs_every=300,"
              "egress_latency_every=200,egress_latency_us=2000,"
              "device_error_every=25,stale_params_every=50")


def _check_chaos(app, clear_time: float, t_full: float | None,
                 rx_at_clear: int, fault_window: float,
                 out_stats: dict) -> list[str]:
    """The --chaos verdicts (ISSUE 5 acceptance): the plan provoked at
    least one ladder degradation, every ladder.degrade has a matching
    ladder.recover, and full service returned within 30 s of fault
    clearance.  Fills ``out_stats`` with the chaos headline the bench
    trajectory's optional ``extra.chaos`` section carries (degraded-mode
    throughput + recovery time, validated by bench_gate --check-only)."""
    from easydarwin_tpu import obs as obs_mod
    errs: list[str] = []
    degrades: dict[str, int] = {}
    recovers: dict[str, int] = {}
    for rec in obs_mod.EVENTS.tail():
        path = rec.get("stream")
        if rec.get("event") == "ladder.degrade":
            degrades[path] = degrades.get(path, 0) + 1
        elif rec.get("event") == "ladder.recover":
            recovers[path] = recovers.get(path, 0) + 1
    if not degrades:
        errs.append("chaos soak provoked zero ladder degradations "
                    "(the plan never bit — nothing was proven)")
    for path, n in sorted(degrades.items()):
        if recovers.get(path, 0) != n:
            errs.append(f"unrecovered ladder.degrade on {path}: {n} "
                        f"degrades vs {recovers.get(path, 0)} recovers")
    now = time.time()
    if (t_full is None and clear_time and app.ladder is not None
            and app.ladder.worst_level() == 0):
        # the last rung recovered between the measurement loop's exit
        # and these checks (the 1 Hz maintenance task kept ticking):
        # charge the full elapsed time as an honest UPPER BOUND so a
        # slow recovery cannot slip past the 30 s budget unmeasured
        t_full = now
    if t_full is None:
        recovery_sec = max(now - clear_time, 0.0)   # still not recovered
        if app.ladder is not None and app.ladder.worst_level() > 0:
            errs.append("ladder never returned to full service after "
                        f"fault clearance: {app.ladder.status()}")
    else:
        recovery_sec = max(t_full - clear_time, 0.0)
        if recovery_sec > 30.0:
            errs.append(f"recovery to full service took "
                        f"{recovery_sec:.1f} s (> 30 s budget)")
    out_stats.update({
        "degraded_pkts_per_sec":
            round(rx_at_clear / max(fault_window, 1e-9), 1),
        # always a finite number (bench_gate's extra.chaos schema
        # rejects null) — an unrecovered run already failed above
        "recovery_sec": round(recovery_sec, 2),
        "degrades": sum(degrades.values()),
        "recovers": sum(recovers.values()),
        "ladder": app.ladder.status() if app.ladder is not None else {},
    })
    return errs


async def soak(seconds: float, n_sources: int = 0,
               chaos_seed: int | None = None, devices: int = 1,
               egress_backend: str | None = None,
               hls_ladder: int = 0, vod: int = 0,
               lossy: float = 0.0, dvr: int = 0) -> int:
    chaos = chaos_seed is not None
    hls_ladder = max(0, min(int(hls_ladder), 3))   # q6..q18 in 6-steps
    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       reflect_interval_ms=10, bucket_delay_ms=10,
                       access_log_enabled=False)
    if dvr:
        # --dvr N: N time-shift subscribers on /live/b continuously
        # pausing and seeking into the past while the pusher keeps
        # pushing (ISSUE 12), plus a mid-soak stoprecord on /live/a
        # whose finalized asset must re-open as instant VOD.  Window
        # small enough that windows complete every ~second at the
        # soak's ~33 pps push rate; the duration retention cap is
        # shorter than the default soak so eviction actually runs.
        import tempfile
        cfg.movie_folder = tempfile.mkdtemp(prefix="edtpu_dvr_soak_")
        cfg.dvr_enabled = True
        cfg.dvr_window_pkts = 32
        cfg.dvr_retention_bytes = 32 << 20
        cfg.dvr_retention_sec = 60.0
        # a speed-4 catch-up burst deliberately delivers faster than
        # realtime (the --vod calibration precedent: the seek/replay
        # burst drains through TCP backpressure over a few hundred ms;
        # the gap/starvation verdicts own delivery health)
        cfg.slo_latency_objective_ms = max(
            cfg.slo_latency_objective_ms, 1000.0)
    vod_assets: list[str] = []
    if vod:
        # --vod N: N RTSP players seeking across M synthetic assets
        # served by the segment cache through the ENGINE paths (the
        # --chaos shape: every output TPU-eligible so megabatch + the
        # host-oracle install check actually run)
        import tempfile
        movies = tempfile.mkdtemp(prefix="edtpu_vod_soak_")
        vod_assets = write_vod_assets(movies, n_assets=3)
        cfg.movie_folder = movies
        cfg.tpu_fanout = True
        cfg.tpu_min_outputs = 1
        # a VOD seek deliberately delivers faster than realtime: the
        # sync snap starts up to a GOP behind the requested npt and the
        # catch-up burst drains through TCP backpressure over a few
        # hundred ms.  The live 50 ms objective would count every such
        # burst as an SLO breach; sub-second is the bound a VOD seek is
        # held to (the starved-player floor owns steady-state health)
        cfg.slo_latency_objective_ms = 1000.0
    if egress_backend:
        # --egress-backend X: force the rung AND run the engine paths
        # (tpu_min_outputs=1, same shape as --chaos) so the forced
        # backend actually carries the plain-UDP player's wire traffic
        # — check_metrics then asserts the effective backend matches
        cfg.egress_backend = egress_backend
        cfg.tpu_fanout = True
        cfg.tpu_min_outputs = 1
    if chaos:
        # chaos runs the ENGINE paths (that is what degrades): every
        # output is TPU-eligible, the megabatch engages across the
        # pushers, and the seeded plan is armed by the server at start
        cfg.tpu_fanout = True
        cfg.tpu_min_outputs = 1
        cfg.resilience_fault_plan = f"seed={chaos_seed},{CHAOS_PLAN}"
    if lossy:
        # --lossy PCT: the reliability tier under receiver-side loss,
        # with the ENGINE paths on (parity windows ride the same
        # relay_rtcp tail either way, but the device parity kernel +
        # oracle must actually run against engine-served media)
        cfg.tpu_fanout = True
        cfg.tpu_min_outputs = 1
        # the lossy harness adds a per-datagram Python receiver + the
        # RR/NACK round-trips IN-PROCESS with the pump on this box's
        # two cores, so tail noise past the live 50 ms objective is
        # harness contention, not server regression (the --vod
        # calibration precedent); the gapless-playback and
        # starved-player verdicts own delivery health here
        cfg.slo_latency_objective_ms = 200.0
    app = StreamingServer(cfg)
    await app.start()
    failures: list[str] = []
    try:
        base = f"rtsp://127.0.0.1:{app.rtsp.port}"
        rest = f"http://127.0.0.1:{app.rest.port}"

        # --- pusher A: TCP interleaved, REAL coded frames (feeds HLS q6)
        push_a = RtspClient()
        await push_a.connect("127.0.0.1", app.rtsp.port)
        await push_a.push_start(f"{base}/live/a", AV_SDP)
        # --- pusher C: TCP, REAL CABAC-coded frames (feeds its own q6
        # rung: the CABAC requant path must run, not pass through)
        push_c = RtspClient()
        await push_c.connect("127.0.0.1", app.rtsp.port)
        await push_c.push_start(f"{base}/live/c", SDP)
        # --- pusher B: UDP (native recvmmsg ingest)
        push_b = RtspClient()
        await push_b.connect("127.0.0.1", app.rtsp.port)
        await push_b.push_start(f"{base}/live/b", SDP, tcp=False)
        b_rtp = push_b.push_transports[0].server_port[0]
        b_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)

        # --- players
        tcp_player = RtspClient()
        await tcp_player.connect("127.0.0.1", app.rtsp.port)
        await tcp_player.play_start(f"{base}/live/a")

        udp_rtp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        udp_rtp.bind(("127.0.0.1", 0))
        udp_rtp.setblocking(False)
        udp_rtcp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        udp_rtcp.bind(("127.0.0.1", 0))
        udp_rtcp.setblocking(False)
        rel_player = RtspClient()
        await rel_player.connect("127.0.0.1", app.rtsp.port)
        await rel_player.play_start(
            f"{base}/live/b", tcp=False,
            client_ports=[(udp_rtp.getsockname()[1],
                           udp_rtcp.getsockname()[1])],
            setup_headers={"x-retransmit": "our-retransmit;window=128"})
        egress = app.rtsp.shared_egress
        rel_out = next(cn for cn in app.rtsp.connections
                       if cn.player_tracks and cn is not None
                       and any(hasattr(pt.output, "resender")
                               for pt in cn.player_tracks.values())
                       ).player_tracks[1].output

        # plain UDP player on /live/b (no retransmit wrap): the one
        # output shape that rides the NATIVE sendmmsg fast path, so the
        # engine's device-param dispatch and the csrc egress fault knobs
        # are actually exercised (the reliable player's resender wrap
        # routes it down the batch-header path)
        udp2_rtp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        udp2_rtp.bind(("127.0.0.1", 0))
        udp2_rtp.setblocking(False)
        udp2_rtcp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        udp2_rtcp.bind(("127.0.0.1", 0))
        udp2_rtcp.setblocking(False)
        plain_player = RtspClient()
        await plain_player.connect("127.0.0.1", app.rtsp.port)
        await plain_player.play_start(
            f"{base}/live/b", tcp=False,
            client_ports=[(udp2_rtp.getsockname()[1],
                           udp2_rtcp.getsockname()[1])])
        udp2_rx = [0]

        # --- lossy player (ISSUE 11): a plain-UDP subscriber on
        # /live/b whose receiver LOSES a seeded fraction of everything
        # it is sent (the wire is untouched — the egress_drop site's
        # schedule runs receiver-side), sends HONEST RRs computed from
        # its own loss accounting plus RFC 4585 generic NACKs, and
        # reconstructs the stream through the FEC receiver model.  The
        # verdicts: gapless playback after recovery, nonzero recovered
        # packets, zero RTX budget exhaustion, zero parity-oracle
        # mismatches, and the closed-loop overhead gauge visibly off 0.
        lossy_state: dict = {}
        if lossy:
            from easydarwin_tpu.protocol.rtcp import (GenericNack,
                                                      ReceiverReport,
                                                      ReportBlock)
            from easydarwin_tpu.relay.fec import FecReceiver
            from easydarwin_tpu.resilience.inject import (FaultInjector,
                                                          FaultPlan)
            l_rtp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            l_rtp.bind(("127.0.0.1", 0))
            l_rtp.setblocking(False)
            l_rtcp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            l_rtcp.bind(("127.0.0.1", 0))
            l_rtcp.setblocking(False)
            lossy_player = RtspClient()
            await lossy_player.connect("127.0.0.1", app.rtsp.port)
            await lossy_player.play_start(
                f"{base}/live/b", tcp=False,
                client_ports=[(l_rtp.getsockname()[1],
                               l_rtcp.getsockname()[1])],
                setup_headers={"x-fec": "parity"})
            l_out = next(
                cn for cn in app.rtsp.connections
                if cn.player_tracks
                and getattr(cn.player_tracks[1].output, "rtcp_addr",
                            None) == ("127.0.0.1",
                                      l_rtcp.getsockname()[1])
            ).player_tracks[1].output
            assert getattr(l_out, "fec", None) is not None, \
                "lossy player's output was not FEC-armed"
            # a PRIVATE injector instance: the seeded drop schedule
            # must not interleave with any server-side armed plan
            l_inj = FaultInjector()
            l_inj.arm(FaultPlan.parse(
                f"seed=23,egress_drop={lossy / 100.0}"))
            l_rx = FecReceiver(media_pt=96,
                               fec_pt=cfg.fec_payload_type,
                               rtx_pt=cfg.rtx_payload_type)
            lossy_state = {"rx": l_rx, "out": l_out, "inj": l_inj,
                           "sock": l_rtp, "rtcp": l_rtcp,
                           "player": lossy_player,
                           "seen": 0, "dropped": 0,
                           "int_seen": 0, "int_dropped": 0}

            def lossy_drain() -> None:
                st = lossy_state
                while True:
                    try:
                        d = l_rtp.recv(65536)
                    except BlockingIOError:
                        break
                    if len(d) < 12:
                        continue
                    st["seen"] += 1
                    st["int_seen"] += 1
                    if l_inj.egress_drop():
                        # receiver-side loss: media, parity and RTX
                        # all ride the same lossy last mile
                        st["dropped"] += 1
                        st["int_dropped"] += 1
                        continue
                    l_rx.on_packet(d)

            def lossy_feedback() -> None:
                """Honest RR (measured interval loss) + generic NACKs
                for the gaps FEC has not solved yet."""
                st = lossy_state
                if not l_rx.media:
                    return
                seen, dropped = st["int_seen"], st["int_dropped"]
                st["int_seen"] = st["int_dropped"] = 0
                frac = min(int(min(dropped / seen, 1.0) * 256), 255) \
                    if seen else 0
                hi = max(l_rx.media)
                rr = ReceiverReport(0x7C7C, [ReportBlock(
                    l_out.rewrite.ssrc, frac, st["dropped"],
                    hi & 0xFFFF, 0, 0, 0)]).to_bytes()
                l_rtcp.sendto(rr, ("127.0.0.1", egress.rtcp_port))
                # NACK the residue (skip the newest window: in flight)
                miss = l_rx.missing(min(l_rx.media),
                                    hi - cfg.fec_window)[-32:]
                if miss:
                    l_rtcp.sendto(GenericNack.from_seqs(
                        0x7C7C, l_out.rewrite.ssrc,
                        [m & 0xFFFF for m in miss]).to_bytes(),
                        ("127.0.0.1", egress.rtcp_port))

        # --- VOD players (ISSUE 10): N interleaved-TCP players across
        # the synthetic assets, each re-PLAYing with a seeded Range
        # seek every few seconds (the segment cache must keep serving
        # across session reopens; starved players fail the soak)
        vod_rx = [0] * max(vod, 0)
        vod_tasks: list[asyncio.Task] = []
        vod_clients: list[RtspClient] = []
        if vod:
            import random as _random
            _vrng = _random.Random(11)
            # cold-jit protection BEFORE the clock starts (PR 7 shape)
            await asyncio.to_thread(prewarm_batch_shapes)

            async def vod_player(i: int) -> None:
                c = RtspClient()
                vod_clients.append(c)
                await c.connect("127.0.0.1", app.rtsp.port)
                uri = f"{base}/{vod_assets[i % len(vod_assets)]}"
                await c.play_start(uri)
                next_seek = t0 + 4.0 + i * 1.5
                while time.time() - t0 < seconds:
                    try:
                        await c.recv_interleaved(0, timeout=0.25)
                        vod_rx[i] += 1
                    except asyncio.TimeoutError:
                        pass
                    for _ in range(64):
                        try:
                            await c.recv_interleaved(0, timeout=0.002)
                            vod_rx[i] += 1
                        except asyncio.TimeoutError:
                            break
                    if time.time() >= next_seek:
                        next_seek = time.time() + 5.0
                        npt = _vrng.uniform(0.0, 15.0)
                        r = await c.request(
                            "PLAY", uri, {"range": f"npt={npt:.2f}-"})
                        assert r.status == 200, r.status

        # --- DVR time-shift players (ISSUE 12): N interleaved-TCP
        # subscribers on the armed /live/b who continuously PAUSE and
        # re-PLAY into the past (even index: Range npt=0 — full-history
        # replay; odd: resume from the PAUSE bookmark) at Speed 4, so
        # the catch-up state machine joins back to live over and over.
        # Verdicts: gapless out-seq per player across every shift and
        # join (the affine rewrite makes a replay re-cover already-sent
        # seqs — duplicates, never forward gaps), one ssrc, zero window
        # repacks process-wide, retention budget respected, nonzero
        # catch-up joins counted.
        dvr_rx = [0] * max(dvr, 0)
        dvr_seqs: list[list[int]] = [[] for _ in range(max(dvr, 0))]
        dvr_ssrcs: list[set] = [set() for _ in range(max(dvr, 0))]
        dvr_tasks: list[asyncio.Task] = []
        instant_vod_rx = [0]
        dvr_stopped = [False]
        repack_base = 0
        if dvr:
            from easydarwin_tpu.protocol.rtp import RtpPacket
            from easydarwin_tpu.vod.cache import pack_window
            repack_base = pack_window.calls

            async def dvr_player(i: int) -> None:
                c = RtspClient()
                await c.connect("127.0.0.1", app.rtsp.port)
                uri = f"{base}/live/b"
                await c.play_start(uri)

                def note(d: bytes) -> None:
                    if len(d) >= 12:
                        dvr_rx[i] += 1
                        p = RtpPacket.parse(d)
                        dvr_seqs[i].append(p.seq)
                        dvr_ssrcs[i].add(p.ssrc)

                mode_next = t0 + 8.0 + i * 3.0
                while time.time() - t0 < seconds:
                    try:
                        note(await c.recv_interleaved(0, timeout=0.25))
                    except asyncio.TimeoutError:
                        pass
                    for _ in range(64):
                        try:
                            note(await c.recv_interleaved(0,
                                                          timeout=0.002))
                        except asyncio.TimeoutError:
                            break
                    if time.time() >= mode_next:
                        mode_next = time.time() + 10.0
                        r = await c.request("PAUSE", uri)
                        assert r.status == 200, f"PAUSE {r.status}"
                        await asyncio.sleep(0.8)   # dwell in the past
                        hdrs = {"speed": "4"}      # catch-up accelerator
                        if i % 2 == 0:
                            # rewind to the recording start: always at
                            # or behind the delivered cursor, so the
                            # replay can never force a forward seq jump
                            hdrs["range"] = "npt=0.0-"
                        r = await c.request("PLAY", uri, hdrs)
                        assert r.status == 200, f"PLAY {r.status}"
                await c.teardown(uri)
                await c.close()

            async def instant_vod_reopen() -> None:
                """Mid-soak stoprecord on /live/a: the finalized asset
                must DESCRIBE/SETUP/PLAY instantly as /live/a.dvr (born
                pre-packed — nothing was muxed or repacked)."""
                st, body = await rest_get(
                    "/api/v1/stoprecord?path=/live/a")
                assert st == 200, f"stoprecord {st}"
                import json as _json
                wins = int(_json.loads(body)["EasyDarwin"]["Body"]
                           ["DvrWindows"])
                assert wins > 0, "stoprecord finalized zero windows"
                c = RtspClient()
                await c.connect("127.0.0.1", app.rtsp.port)
                await c.play_start(f"{base}/live/a.dvr")
                t_end_replay = time.time() + 4.0
                while time.time() < t_end_replay:
                    try:
                        d = await c.recv_interleaved(0, timeout=0.5)
                    except asyncio.TimeoutError:
                        continue
                    if len(d) >= 12:
                        instant_vod_rx[0] += 1
                await c.teardown(f"{base}/live/a.dvr")
                await c.close()

        # --- HLS with the requant rung (REST calls must not block the
        # loop the server itself runs on)
        def _get(path):
            with urllib.request.urlopen(rest + path, timeout=5) as r:
                return r.status, r.read()

        async def rest_get(path):
            return await asyncio.to_thread(_get, path)

        # --hls-ladder N widens the q-ladder on BOTH coded pushers: the
        # N renditions share one RequantLadder per path (one parse per
        # AU, slice x rendition fan-out across the pool)
        ladder_rungs = ",".join(f"q{6 * (i + 1)}"
                                for i in range(max(1, hls_ladder)))
        await rest_get(f"/api/v1/starthls?path=/live/a&rungs=1,"
                       f"{ladder_rungs}")
        await rest_get(f"/api/v1/starthls?path=/live/c&rungs="
                       f"{ladder_rungs}")
        ladder_pending_peak = [0, 0]     # [/live/a, /live/c]

        def _ladders():
            out = []
            for i, key in enumerate(("/live/a", "/live/c")):
                e = app.hls.outputs.get(key)
                lad = getattr(e, "requant_ladder", None) if e else None
                if lad is not None:
                    out.append((i, lad))
            return out

        # pre-encode one GOP-ish cycle BEFORE the clock starts and before
        # the drain task runs (pure-Python encode per frame would
        # monopolize the shared event loop and starve the player tasks —
        # the soak measures the SERVER, not the harness's encoder)
        cycle = [encode_iframe(synth_frame(i), 24,
                               cb=synth_frame(i + 7, 32),
                               cr=synth_frame(i + 13, 32))
                 for i in range(16)]
        cycle_cabac = [encode_iframe(synth_frame(i, 48), 24,
                                     entropy="cabac")
                       for i in range(8)]
        seq_c = 0

        from easydarwin_tpu.protocol.aac import packetize_aac_hbr
        t0 = time.time()
        f = 0
        seq_a = seq_b = 0
        seq_aud = 0
        scrapes: list[dict[str, float]] = []
        tcp_rx = [0]
        udp_rx = [0]

        async def tcp_drain():
            # greedy: consume every buffered packet per wake — a
            # one-packet-per-wake drain starves behind the push loop and
            # makes the SERVER's (correct) slow-consumer aging look like
            # a server failure
            while time.time() - t0 < seconds:
                try:
                    await tcp_player.recv_interleaved(0, timeout=0.25)
                    tcp_rx[0] += 1
                except asyncio.TimeoutError:
                    continue
                for _ in range(64):
                    try:
                        await tcp_player.recv_interleaved(0, timeout=0.002)
                        tcp_rx[0] += 1
                    except asyncio.TimeoutError:
                        break

        drain_task = asyncio.ensure_future(tcp_drain())
        if vod:
            vod_tasks = [asyncio.ensure_future(vod_player(i))
                         for i in range(vod)]
        if dvr:
            dvr_tasks = [asyncio.ensure_future(dvr_player(i))
                         for i in range(dvr)]
        last_seen_out_seq = None
        # chaos timeline: faults stay armed until clear_at, then the
        # remainder of the soak (>= ~45 s at the default duration) is
        # the recovery budget the ISSUE acceptance pins at 30 s
        clear_at = max(seconds * 0.4, seconds - 45.0) if chaos else None
        cleared = False
        clear_time = 0.0
        rx_at_clear = 0
        t_full: float | None = None
        while time.time() - t0 < seconds:
            ts = int(f * 3000)
            for nal in cycle[f % 16]:
                for p in nalu.packetize_h264(
                        nal, seq=seq_a, timestamp=ts, ssrc=1,
                        marker_on_last=(nal[0] & 0x1F == 5)):
                    seq_a += 1
                    push_a.push_packet(0, p)
            # pusher B: synthetic 1-packet IDR frames over UDP
            pkt = (struct.pack("!BBHII", 0x80, 96, seq_b & 0xFFFF, ts, 0xB)
                   + bytes([0x65]) + bytes(120))
            seq_b += 1
            b_sock.sendto(pkt, ("127.0.0.1", b_rtp))
            # audio on /live/a track 2: one AAC AU per loop tick
            au = bytes(((f & 0xFF),)) * 96
            push_a.push_packet(1, packetize_aac_hbr(
                au, seq=seq_aud, timestamp=(seq_aud * 1024) & 0xFFFFFFFF,
                ssrc=0xA))
            seq_aud += 1
            if f % 4 == 2:     # ~8 fps CABAC through the native walk
                ts_c = int(f * 3000)
                for nal in cycle_cabac[(f // 4) % 8]:
                    for p in nalu.packetize_h264(
                            nal, seq=seq_c, timestamp=ts_c, ssrc=3,
                            marker_on_last=(nal[0] & 0x1F == 5)):
                        seq_c += 1
                        push_c.push_packet(0, p)
            # drain the plain (native-path) UDP player
            while True:
                try:
                    d = udp2_rtp.recv(65536)
                except BlockingIOError:
                    break
                if len(d) >= 12:
                    udp2_rx[0] += 1
            if lossy:
                lossy_drain()
                if f % 30 == 17:          # ~1 Hz honest RR + NACK round
                    lossy_feedback()
            # drain UDP player + ack its packets (reliable window)
            acked = 0
            while True:
                try:
                    d = udp_rtp.recv(65536)
                except BlockingIOError:
                    break
                if len(d) >= 12 and d[1] & 0x7F == 96:
                    udp_rx[0] += 1
                    last_seen_out_seq = struct.unpack("!H", d[2:4])[0]
                    acked += 1
            if last_seen_out_seq is not None and acked:
                udp_rtcp.sendto(
                    build_ack(rel_out.rewrite.ssrc, last_seen_out_seq,
                              0xFFFFFFFF),
                    ("127.0.0.1", egress.rtcp_port))
            if f % 150 == 5:
                # conformant interleaved player: periodic RR on the RTCP
                # channel (a silent client is CORRECTLY reaped at
                # rtsp_timeout — found by the 26-minute soak)
                tcp_out = next(iter(
                    next(cn for cn in app.rtsp.connections
                         if cn.player_tracks
                         and not hasattr(
                             cn.player_tracks[1].output, "resender")
                         ).player_tracks.values())).output
                rr = struct.pack("!BBHIIIIIII", 0x81, 201, 7, 0x7A7A,
                                 tcp_out.rewrite.ssrc, 0, 0, 0, 0, 0)
                tcp_player.send_interleaved(1, rr)
            if f % 150 == 35:
                # conformant plain-UDP player: periodic RR from its
                # registered RTCP address keeps the session alive past
                # rtsp_timeout (the silent-client reap is CORRECT server
                # behavior; this player predates soak runs long enough
                # to hit it — surfaced by the 120 s forced-backend run)
                plain_out = next(
                    cn for cn in app.rtsp.connections
                    if cn.player_tracks
                    and getattr(cn.player_tracks[1].output,
                                "native_addr", None) is not None
                    and not hasattr(cn.player_tracks[1].output,
                                    "resender")).player_tracks[1].output
                rr = struct.pack("!BBHIIIIIII", 0x81, 201, 7, 0x7B7B,
                                 plain_out.rewrite.ssrc, 0, 0, 0, 0, 0)
                udp2_rtcp.sendto(rr, ("127.0.0.1", egress.rtcp_port))
            if f % 10 == 7:            # ladder pipeline-bound sampling:
                for li, lad in _ladders():   # pending must stay under the
                    ladder_pending_peak[li] = max(   # admission bound
                        ladder_pending_peak[li], lad.pending)
            if f % 30 == 10:           # periodic NADU (comfortable buffer)
                from easydarwin_tpu.protocol.rtcp import Nadu, NaduBlock
                udp_rtcp.sendto(Nadu(9, [NaduBlock(
                    rel_out.rewrite.ssrc, playout_delay_ms=2000,
                    free_buffer_64b=500)]).to_bytes(),
                    ("127.0.0.1", egress.rtcp_port))
            if f % 60 == 20:           # REST polling
                st, _ = await rest_get("/api/v1/getserverinfo")
                assert st == 200
                st, _ = await rest_get("/api/v1/gethlsstreams")
                assert st == 200
            if f % 60 == 40:           # periodic Prometheus scrape
                st, body = await rest_get("/metrics")
                assert st == 200
                scrapes.append(parse_metrics(body.decode()))
            if (dvr and not dvr_stopped[0]
                    and time.time() - t0 >= seconds * 0.6):
                # mid-soak stop → instant stream-to-VOD re-open; runs as
                # a task so the replay drain never blocks the push loop
                dvr_stopped[0] = True
                dvr_tasks.append(
                    asyncio.ensure_future(instant_vod_reopen()))
            if chaos and not cleared and time.time() - t0 >= clear_at:
                from easydarwin_tpu.resilience import INJECTOR
                INJECTOR.disarm()
                cleared = True
                clear_time = time.time()
                rx_at_clear = tcp_rx[0] + udp_rx[0] + udp2_rx[0]
            if (chaos and cleared and t_full is None
                    and app.ladder is not None
                    and app.ladder.worst_level() == 0):
                t_full = time.time()   # every rung back at full service
            f += 1
            await asyncio.sleep(0.03)
        await drain_task
        if lossy:
            # recovery grace: keep draining + NACKing the residue until
            # playback is gapless (bounded — an unrecoverable gap is
            # the failure the verdict below reports)
            l_rx = lossy_state["rx"]
            for _ in range(50):
                lossy_drain()
                if not l_rx.media:
                    break
                gaps = l_rx.missing(min(l_rx.media),
                                    max(l_rx.media) - cfg.fec_window)
                if not gaps:
                    break
                lossy_feedback()
                await asyncio.sleep(0.1)
        for vt in vod_tasks:
            try:
                await vt
            except Exception as e:       # a died player is a failure,
                failures.append(f"vod player crashed: {e!r}")  # not a hang
        for dt in dvr_tasks:
            try:
                await dt
            except Exception as e:
                failures.append(f"dvr player crashed: {e!r}")

        # --- checks.  Feature-completeness checks (HLS muxing, requant
        # throughput, drained reliable windows) hold for the CLEAN soak;
        # under chaos the injected 5% ingest drop legitimately breaks
        # coded AUs, so chaos asserts the resilience invariants instead.
        entry = app.hls.outputs.get("/live/a")
        q6 = entry.renditions.get("q6") if entry else None
        entry_c = app.hls.outputs.get("/live/c")
        q6c = entry_c.renditions.get("q6") if entry_c else None
        # drain the requant ladders before judging them: in-flight AUs
        # at loop end are normal pipelining, stuck ones are a failure
        for _ in range(100):
            if all(lad.pending == 0 for _i, lad in _ladders()):
                break
            await asyncio.sleep(0.05)
        if hls_ladder:
            names = [f"q{6 * (i + 1)}" for i in range(hls_ladder)]
            for key, ent in (("/live/a", entry), ("/live/c", entry_c)):
                lad = getattr(ent, "requant_ladder", None) if ent else None
                if lad is None:
                    failures.append(f"{key}: no requant ladder built")
                    continue
                if sorted(lad.renditions) != [6 * (i + 1)
                                              for i in range(hls_ladder)]:
                    failures.append(f"{key}: ladder rungs "
                                    f"{sorted(lad.renditions)}")
                if lad.pending:
                    failures.append(f"{key}: ladder pending stuck at "
                                    f"{lad.pending} after drain")
                if not chaos and lad.shed:
                    failures.append(f"{key}: ladder shed {lad.shed} AUs "
                                    "(pipeline over budget)")
                for nm in names:
                    rend = ent.renditions.get(nm)
                    if rend is None or not rend.segments:
                        failures.append(
                            f"{key}: rendition {nm} produced no "
                            "segments")
                    elif not chaos \
                            and rend.requant.stats.slices_requantized \
                            < 5:
                        failures.append(
                            f"{key}: rendition {nm} requanted only "
                            f"{rend.requant.stats.slices_requantized} "
                            "slices")
            for li, key in ((0, "/live/a"), (1, "/live/c")):
                ent2 = app.hls.outputs.get(key)
                lad = getattr(ent2, "requant_ladder", None) if ent2 \
                    else None
                if lad is not None \
                        and ladder_pending_peak[li] > lad._max_pending:
                    failures.append(
                        f"{key}: ladder pending peaked at "
                        f"{ladder_pending_peak[li]} above the "
                        f"{lad._max_pending} admission bound "
                        "(unbounded growth)")
        if not chaos:
            st, body = await rest_get("/hls/live/a/q6/index.m3u8")
            if b"#EXTINF" not in body:
                failures.append("q6 rendition produced no segments")
            if q6 is None or q6.requant.stats.slices_requantized < 10:
                failures.append(f"requant stats too low: "
                                f"{q6 and q6.requant.stats}")
            if q6 is not None and q6.requant.stats.native_slices == 0:
                failures.append("native requant engine unused")
            for nm in ("", "q6"):
                rend = entry.renditions.get(nm) if entry else None
                if rend is None or rend.audio_samples_muxed == 0:
                    failures.append(f"rendition {nm!r} muxed no audio")
                elif rend.segments and \
                        rend.segments[-1].data.count(b"traf") != 2:
                    failures.append(f"rendition {nm!r} segments not A/V")
            if q6c is None or q6c.requant.stats.slices_requantized < 5:
                failures.append(f"CABAC requant stats too low: "
                                f"{q6c and q6c.requant.stats}")
            if q6c is not None and q6c.requant.stats.slices_passed_through:
                failures.append(
                    f"CABAC slices passed through unrequanted: "
                    f"{q6c.requant.stats}")
            if q6c is not None and q6c.requant.stats.native_slices == 0:
                failures.append("native CABAC requant engine unused")
        # "never stops serving": players keep progressing even under the
        # plan (threshold scaled to the injected 5% drop + shed risk)
        floor = 0.3 if chaos else 0.5
        if vod:
            # each player streams ~30 fps video + ~8 AU/s audio at 1x;
            # a player that saw under ~5 pkts/s of soak time starved
            vod_floor = seconds * 5
            for i, n in enumerate(vod_rx):
                if n < vod_floor:
                    failures.append(
                        f"vod player {i} starved: {n} pkts "
                        f"(floor {vod_floor:.0f})")
            if app.vod_pacer is not None \
                    and app.vod_pacer.prime_failures:
                failures.append(
                    f"vod device-prime failures: "
                    f"{app.vod_pacer.prime_failures}")
        if dvr:
            # ISSUE 12 acceptance shape: gapless seq per player across
            # every pause/seek/catch-up (a replay re-covers sent seqs —
            # duplicates and backward hops are fine, a FORWARD jump is
            # lost playback), one ssrc, zero repacks process-wide,
            # retention budget respected, and the join machinery must
            # actually have run
            from easydarwin_tpu.vod.cache import pack_window
            if pack_window.calls != repack_base:
                failures.append(
                    f"{pack_window.calls - repack_base} window repacks "
                    "ran during a --dvr soak (spilled opens must be "
                    "zero-repack)")
            for i in range(dvr):
                gap = _seq_gap(dvr_seqs[i])
                if gap:
                    failures.append(
                        f"dvr player {i}: {gap} packets lost across "
                        "pause/seek/catch-up (forward seq jumps)")
                if len(dvr_ssrcs[i]) > 1:
                    failures.append(
                        f"dvr player {i}: ssrc changed across the "
                        f"time-shift ({len(dvr_ssrcs[i])} identities)")
                # /live/b pushes ~33 pps; a shifted player re-receives
                # its replays on top — under ~5 pkts/s means starved
                if dvr_rx[i] < seconds * 5:
                    failures.append(f"dvr player {i} starved: "
                                    f"{dvr_rx[i]} pkts")
            if app.dvr is not None:
                for path, a in app.dvr._armed.items():
                    for tid, sp in a.spillers.items():
                        if sp.writer.live_bytes > sp.writer.retention_bytes:
                            failures.append(
                                f"dvr retention overrun on {path} "
                                f"track {tid}: {sp.writer.live_bytes} "
                                f"> {sp.writer.retention_bytes}")
                        if sp.skipped:
                            failures.append(
                                f"dvr spiller fell behind the ring on "
                                f"{path} track {tid}: {sp.skipped} "
                                "windows lost to ring eviction")
            if not dvr_stopped[0]:
                failures.append("mid-soak stoprecord never fired "
                                "(duration too short for --dvr)")
            elif instant_vod_rx[0] == 0:
                failures.append("instant stream-to-VOD re-open served "
                                "zero packets")
        if tcp_rx[0] < f * floor:
            failures.append(f"tcp player starved: {tcp_rx[0]}/{f}")
        if udp_rx[0] < f * floor:
            failures.append(f"udp player starved: {udp_rx[0]}/{f}")
        if udp2_rx[0] < f * floor:
            failures.append(
                f"native-path udp player starved: {udp2_rx[0]}/{f}")
        if not chaos and rel_out.resender.in_flight > 200:
            failures.append(
                f"reliable window never drains: {rel_out.resender.in_flight}")
        if not chaos:
            for eng in app.pump.engines.values():
                if eng.send_errors:
                    failures.append(f"engine send errors: {eng.send_errors}")
        if lossy:
            # the ISSUE 11 acceptance: gapless playback at the injected
            # loss rate with measurable recovery through FEC and/or RTX
            l_rx = lossy_state["rx"]
            if lossy_state["dropped"] == 0:
                failures.append("lossy schedule dropped nothing (the "
                                "run proved nothing)")
            if not l_rx.media:
                failures.append("lossy player received no media at all")
            else:
                gaps = l_rx.missing(min(l_rx.media),
                                    max(l_rx.media) - cfg.fec_window)
                if gaps:
                    failures.append(
                        f"lossy player playback gaps after recovery: "
                        f"{len(gaps)} seqs (e.g. {gaps[:5]})")
            if len(l_rx.recovered) + len(l_rx.rtx_restored) == 0:
                failures.append("lossy player recovered zero packets "
                                "(neither FEC nor RTX engaged)")
        chaos_stats: dict = {}
        if chaos:
            failures.extend(_check_chaos(app, clear_time, t_full,
                                         rx_at_clear, clear_at,
                                         chaos_stats))
        # multi-source megabatch section BEFORE the final scrape, so its
        # megabatch_* counters are visible to check_metrics (same
        # process-global registry the server exports)
        if n_sources >= 2:
            failures.extend(await asyncio.to_thread(
                multi_source_section, n_sources, 2.0, devices))
        st, body = await rest_get("/metrics")   # final scrape for checks
        if st == 200:
            scrapes.append(parse_metrics(body.decode()))
        failures.extend(check_metrics(scrapes,
                                      expect_megabatch=n_sources >= 2,
                                      chaos=chaos,
                                      forced_backend=egress_backend,
                                      hls_ladder=hls_ladder, vod=vod,
                                      lossy=lossy, dvr=dvr))
        mlast = scrapes[-1] if scrapes else {}
        stats = {
            "frames": f,
            "audio_aus": seq_aud,
            "audio_muxed": entry.renditions[""].audio_samples_muxed
            if entry and "" in entry.renditions else 0,
            "cabac_requant": str(q6c and q6c.requant.stats),
            "cabac_shed": q6c.shed if q6c else None,
            "tcp_rx": tcp_rx[0],
            "udp_rx": udp_rx[0],
            "udp2_rx": udp2_rx[0],
            "reliable_in_flight": rel_out.resender.in_flight,
            "reliable_acks": rel_out.tracker.acks,
            "retransmits": rel_out.resender.resent,
            "requant": str(q6.requant.stats) if q6 else None,
            "hls_shed": q6.shed if q6 else None,
            "ladder_width": hls_ladder,
            "ladder_pending_peak": ladder_pending_peak,
            "ladder_aus": mlast.get("requant_aus_total"),
            "ladder_rendition_aus": mlast.get("requant_renditions_total"),
            "ladder_stage_counts": {
                k[len("requant_stage_seconds_count"):]: v
                for k, v in mlast.items()
                if k.startswith("requant_stage_seconds_count")},
            "rtcp_in": egress.rtcp_in,
            "metrics_scrapes": len(scrapes),
            "wire_bytes": mlast.get("egress_bytes_total"),
            "sendmmsg_calls": mlast.get("egress_sendmmsg_calls_total"),
            "eagain": mlast.get("egress_eagain_total"),
            "flight_dumps": mlast.get("flight_dumps_total"),
            "events_emitted": sum(
                v for k, v in mlast.items()
                if k.startswith("events_emitted_total")),
            "ingest_to_wire_count": sum(
                v for k, v in mlast.items()
                if k.startswith("relay_ingest_to_wire_seconds_count")),
            "phase_counts": {
                k[len("relay_phase_seconds_count"):]: v
                for k, v in mlast.items()
                if k.startswith("relay_phase_seconds_count")},
            "slo_budget": {
                k: v for k, v in mlast.items()
                if k.startswith("slo_budget_remaining_ratio")},
            "native_ingest": {
                s.native_ingest_pkts and "ok" or 0: s.native_ingest_pkts
                for sess in app.registry.sessions.values()
                for s in sess.streams.values()},
        }
        if chaos:
            stats["chaos"] = chaos_stats
        if lossy:
            l_rx = lossy_state["rx"]
            stats["lossy"] = {
                "injected_pct": lossy,
                "datagrams_seen": lossy_state["seen"],
                "dropped": lossy_state["dropped"],
                "media_received": len(l_rx.media),
                "recovered_fec": len(l_rx.recovered),
                "recovered_rtx": len(l_rx.rtx_restored),
                "parity_sent": lossy_state["out"].fec.parity_sent,
                "rtx_giveups": lossy_state["out"].fec.rtx_giveups,
                "overhead_final":
                    lossy_state["out"].fec.controller.overhead,
                "fec_recovered_total":
                    mlast.get("fec_recovered_total"),
                "rtx_sent_total": mlast.get("rtx_sent_total"),
                "oracle_mismatch_total":
                    mlast.get("fec_parity_oracle_mismatch_total"),
            }
        if dvr:
            stats["dvr"] = {
                "players": dvr,
                "rx": dvr_rx,
                "windows_spilled":
                    mlast.get("dvr_windows_spilled_total"),
                "spill_bytes": mlast.get("dvr_spill_bytes"),
                "catchup_joins":
                    mlast.get("dvr_catchup_joins_total"),
                "retention_evictions":
                    mlast.get("dvr_retention_evictions_total"),
                "instant_vod_rx": instant_vod_rx[0],
                "repacks": pack_window.calls - repack_base,
                "manager": (app.dvr.stats()
                            if app.dvr is not None else None),
            }
        if vod:
            stats["vod"] = {
                "players": vod, "assets": len(vod_assets),
                "rx": vod_rx,
                "cache_hits": mlast.get("vod_cache_hits_total"),
                "cache_misses": mlast.get("vod_cache_misses_total"),
                "hot_pkts": mlast.get('vod_packets_total{path="hot"}'),
                "cold_pkts": mlast.get('vod_packets_total{path="cold"}'),
                "pacer": (app.vod_pacer.stats()
                          if app.vod_pacer is not None else None),
            }
        print("SOAK", "FAIL" if failures else "OK", stats)
        for msg in failures:
            print("  -", msg)
        await tcp_player.close()
        await rel_player.close()
        await plain_player.close()
        if lossy and lossy_state.get("player") is not None:
            await lossy_state["player"].close()
            lossy_state["sock"].close()
            lossy_state["rtcp"].close()
        for c in vod_clients:
            await c.close()
        await push_a.close()
        await push_c.close()
        await push_b.close()
        for s in (b_sock, udp_rtp, udp_rtcp, udp2_rtp, udp2_rtcp):
            s.close()
    finally:
        await app.stop()
    return 1 if failures else 0


# ===================================================================== cluster
# The multi-process cluster soak (ISSUE 6 acceptance scenario).

def _node_env(index: int) -> dict:
    """One process per chip.  The launchers below never initialise a
    JAX backend themselves; node 0 inherits the platform selection —
    and takes the chip where there is one — and every other node is
    started on an explicit ``JAX_PLATFORMS=cpu``, which it prints in
    its NODE_READY line."""
    if index == 0:
        return dict(os.environ)
    return dict(os.environ, JAX_PLATFORMS="cpu")


async def _cluster_node_main(node_id: str, redis_port: int,
                             fault_plan: str = "",
                             skewed: bool = False,
                             composed: bool = False) -> None:
    """Child-process entry: one cluster-enabled server that announces
    its bound ports on stdout and serves until killed.  ``skewed``
    (ISSUE 13) tightens the control-plane knobs so the rebalance /
    admission machinery acts within a soak-scale run; ``fault_plan``
    arms a per-node FaultPlan (the --skewed harness forces a lying
    capacity on one node through the capacity_spoof site).
    ``composed`` (ISSUE 15) runs the observatory-round shape: EVERY
    engine on — device fan-out, VOD segment cache + pacer, DVR spill,
    FEC — with a per-node movie folder, so the mixed workload crosses
    nodes with full observability."""
    import os
    base = "edtpu_composed_soak" if composed else "edtpu_cluster_soak"
    log_dir = f"/tmp/{base}/{node_id}"
    os.makedirs(log_dir, exist_ok=True)
    extra = {}
    if not skewed and not composed:
        # ISSUE 20: the plain cluster scenario also records every
        # pushed broadcast and erasure-shards finalized assets across
        # the fleet (k=2+1 spreads a stripe over 3 distinct nodes), so
        # the seeded owner kill doubles as the durability scenario —
        # its finalized .dvr assets must replay from the survivors
        import shutil as _shutil
        movies = os.path.join(log_dir, "movies")
        _shutil.rmtree(movies, ignore_errors=True)   # stale-run assets
        extra = dict(
            dvr_enabled=True,
            movie_folder=movies,
            dvr_window_pkts=32,
            storage_enabled=True,
            storage_data_shards=2,
            storage_parity_shards=1,
            storage_scrub_interval_sec=3.0)
    if skewed:
        extra = dict(
            cluster_admission_high_water=0.8,
            cluster_rebalance_high_water=0.9,
            cluster_rebalance_low_water=0.4,
            # burn window long enough that the flash crowd (harness
            # t≈12-18s) lands while the weak node still owns the hot
            # stream; the drain fires right after, once per run
            cluster_rebalance_burn_sec=22.0,
            cluster_rebalance_cooldown_sec=60.0)
    if composed:
        extra = dict(
            tpu_fanout=True, tpu_min_outputs=2,
            dvr_enabled=True,
            # error logs on: the observatory round's whole point is
            # attributable cross-node failures
            access_log_enabled=True,
            movie_folder=os.path.join(log_dir, "movies"),
            # the rebalancer would fight the harness's deliberate
            # workload placement on a 2-core box; the observatory round
            # exercises the CRASH migration, not the planned drain
            cluster_rebalance_enabled=False,
            cluster_admission_enabled=False)
    cfg = ServerConfig(
        rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
        wan_ip="127.0.0.1", reflect_interval_ms=10, bucket_delay_ms=0,
        log_folder=log_dir, server_id=node_id,
        redis_port=redis_port, cluster_enabled=True,
        cluster_lease_ttl_sec=2.0, cluster_heartbeat_sec=0.5,
        cluster_pull_connect_timeout_sec=3.0,
        cluster_pull_read_timeout_sec=1.5,
        cluster_pull_backoff_ms=150.0,
        resilience_fault_plan=fault_plan,
        **{"access_log_enabled": False, **extra})
    app = StreamingServer(cfg)
    if composed:
        # cold-jit protection (the PR 7 discipline): the first device
        # pass would otherwise block the pump for the whole compile —
        # long enough to starve a peer's pull DESCRIBE window and burn
        # the latency SLO before the soak clock even starts
        await asyncio.to_thread(prewarm_batch_shapes)
    await app.start()
    print(f"NODE_READY rtsp={app.rtsp.port} rest={app.rest.port} "
          f"jax_platforms={os.environ.get('JAX_PLATFORMS') or 'unset'} "
          f"platform={(app.device_info or {}).get('platform') or 'none'}",
          flush=True)
    try:
        while True:
            await asyncio.sleep(3600)
    finally:
        await app.stop()


def _seq_gap(seqs: list[int]) -> int:
    """Missing rewritten seq numbers at the player socket (mod 2^16;
    duplicates — the pusher's resend tail — count as 0)."""
    gap = 0
    for a, b in zip(seqs, seqs[1:]):
        d = (b - a) & 0xFFFF
        if 1 < d < 0x8000:            # forward jump: d-1 packets missing
            gap += d - 1
    return gap


class _ClusterPusher:
    """The soak's source: pushes to the stream's current owner, keeps a
    resend tail, and on owner death re-resolves against Redis and
    re-ANNOUNCEs to the adopter — the reference's re-register/re-push
    recovery, with the tail resent so packets that died inside the old
    owner's socket are not a wire gap (duplicates rewrite to duplicate
    seqs, which the gap check tolerates)."""

    def __init__(self, path: str, redis, rtsp_ports: dict[str, int]):
        from collections import deque

        from easydarwin_tpu.cluster.placement import PlacementService
        self.path = path
        self.redis = redis
        self.rtsp_ports = rtsp_ports
        self.placement = PlacementService(redis, "soak-harness")
        self.seq = 0
        self.tail: deque[bytes] = deque(maxlen=64)
        self.client: RtspClient | None = None
        self.target: str | None = None
        self.reconnects = 0

    def _pkt(self) -> bytes:
        p = (struct.pack("!BBHII", 0x80, 96, self.seq & 0xFFFF,
                         self.seq * 90, 0xFE)
             + bytes([0x65]) + bytes(100))
        self.seq += 1
        return p

    async def connect_to(self, node: str) -> None:
        if self.client is not None:
            try:
                await self.client.close()
            except Exception:
                pass
        self.client = RtspClient()
        port = self.rtsp_ports[node]
        await self.client.connect("127.0.0.1", port)
        await self.client.push_start(
            f"rtsp://127.0.0.1:{port}{self.path}", SDP)
        self.target = node
        for p in list(self.tail):     # cover in-flight loss at the kill
            self.client.push_packet(0, p)

    async def ensure_connected(self, dead: set[str]) -> bool:
        """Reconnect toward the current claimant when our connection
        died or ownership moved to a live node; False while the cluster
        has not re-placed the stream yet."""
        alive = (self.client is not None and self.client.writer is not None
                 and not self.client.writer.is_closing()
                 and self.target not in dead)
        claimant = await self.placement.claimant(self.path)
        want = claimant if claimant and claimant not in dead else None
        if alive and (want is None or want == self.target):
            return True
        if want is None:
            return False              # adoption still in flight
        await self.connect_to(want)
        self.reconnects += 1
        return True

    def push(self) -> None:
        p = self._pkt()
        self.tail.append(p)
        if self.client is not None:
            self.client.push_packet(0, p)


async def cluster_soak(n_nodes: int, seconds: float,
                       seed: int = 7) -> int:
    import json as _json
    import os
    import random

    from easydarwin_tpu.cluster.placement import HashRing
    from easydarwin_tpu.cluster.redis_client import (AsyncRedis,
                                                     MiniRedisServer)

    assert n_nodes >= 2, "--cluster needs at least 2 nodes"
    seconds = max(seconds, 30.0)
    rng = random.Random(seed)
    failures: list[str] = []
    mini = MiniRedisServer()
    await mini.start()
    redis = AsyncRedis("127.0.0.1", mini.port)
    node_ids = [f"soak-node-{i}" for i in range(n_nodes)]
    procs: dict[str, asyncio.subprocess.Process] = {}
    rtsp_ports: dict[str, int] = {}
    rest_ports: dict[str, int] = {}
    here = os.path.abspath(__file__)
    for i, nid in enumerate(node_ids):
        p = await asyncio.create_subprocess_exec(
            sys.executable, here, "--cluster-node", "--node-id", nid,
            "--redis-port", str(mini.port),
            stdout=asyncio.subprocess.PIPE,
            stderr=asyncio.subprocess.DEVNULL, env=_node_env(i))
        procs[nid] = p
        line = await asyncio.wait_for(p.stdout.readline(), 60)
        if not line.startswith(b"NODE_READY"):
            raise RuntimeError(f"{nid} failed to boot: {line!r}")
        kv = dict(t.split("=") for t in line.decode().split()[1:])
        rtsp_ports[nid] = int(kv["rtsp"])
        rest_ports[nid] = int(kv["rest"])

    path = "/live/m"
    ring = HashRing(node_ids, 64)
    owner = ring.owner(path)
    successor = [n for n in ring.rank(path) if n != owner][0]
    pull_node = successor             # a guaranteed non-owner
    dead: set[str] = set()
    stats: dict = {"owner": owner, "successor": successor}

    def _metrics(nid: str) -> dict[str, float]:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{rest_ports[nid]}/metrics",
                timeout=5) as r:
            return parse_metrics(r.read().decode())

    udp_rtp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    udp_rtp.bind(("127.0.0.1", 0))
    udp_rtp.setblocking(False)
    udp_rtcp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    udp_rtcp.bind(("127.0.0.1", 0))
    udp_rtcp.setblocking(False)
    pusher = _ClusterPusher(path, redis, rtsp_ports)
    churn_ok = [0]
    pull_rx = [0]
    flash = []
    try:
        await pusher.connect_to(owner)
        for _ in range(10):           # prime before anyone subscribes
            pusher.push()
            await asyncio.sleep(0.02)
        await asyncio.sleep(1.2)      # ≥2 cluster ticks: claim + ckpt up

        # ISSUE 20: record a short broadcast ON THE OWNER, tear it down
        # so the DVR finalizes and the storage tier stripes the asset
        # across the fleet — after the seeded SIGKILL it must replay
        # from the survivors' shards alone (zero repacks, zero wire
        # mismatches)
        rec = RtspClient()
        await rec.connect("127.0.0.1", rtsp_ports[owner])
        await rec.push_start(
            f"rtsp://127.0.0.1:{rtsp_ports[owner]}/live/s", SDP)
        for i in range(160):
            rec.push_packet(0, struct.pack(
                "!BBHII", 0x80, 96, i & 0xFFFF, i * 90, 0xAB)
                + bytes([0x65]) + bytes(100))
            if i % 8 == 7:
                await asyncio.sleep(0.01)
        await asyncio.sleep(0.3)      # let the spiller drain the ring
        await rec.close()

        # the subscriber that must survive the kill WITHOUT re-SETUP
        udp_player = RtspClient()
        await udp_player.connect("127.0.0.1", rtsp_ports[owner])
        await udp_player.play_start(
            f"rtsp://127.0.0.1:{rtsp_ports[owner]}{path}", tcp=False,
            client_ports=[(udp_rtp.getsockname()[1],
                           udp_rtcp.getsockname()[1])])
        # the cross-server subscriber (pull relay on a non-owner)
        pull_player = RtspClient()
        await pull_player.connect("127.0.0.1", rtsp_ports[pull_node])
        await pull_player.play_start(
            f"rtsp://127.0.0.1:{rtsp_ports[pull_node]}{path}")

        t0 = time.time()
        t_kill = max(seconds * 0.45, seconds - 30.0)
        t_flash_in, t_flash_out = seconds * 0.25, seconds * 0.7
        killed = False
        kill_mono = 0.0
        recovery_sec: float | None = None
        rx_seqs: list[int] = []
        rx_ssrcs: set[bytes] = set()
        pull_rx_after_kill = [0]

        async def _pull_drain() -> None:
            while time.time() - t0 < seconds:
                try:
                    await pull_player.recv_interleaved(0, timeout=0.25)
                except asyncio.TimeoutError:
                    continue
                except (ConnectionError, Exception):
                    return
                pull_rx[0] += 1
                if killed:
                    pull_rx_after_kill[0] += 1

        async def _churn() -> None:
            """Short-lived UDP subscriber joins on random nodes — the
            SETUP/TEARDOWN path must stay healthy under failover."""
            while time.time() - t0 < seconds:
                await asyncio.sleep(rng.uniform(1.5, 2.5))
                nid = rng.choice([n for n in node_ids if n not in dead])
                s1 = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                s1.bind(("127.0.0.1", 0))
                s2 = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                s2.bind(("127.0.0.1", 0))
                c = RtspClient()
                try:
                    await c.connect("127.0.0.1", rtsp_ports[nid])
                    await asyncio.wait_for(c.play_start(
                        f"rtsp://127.0.0.1:{rtsp_ports[nid]}{path}",
                        tcp=False,
                        client_ports=[(s1.getsockname()[1],
                                       s2.getsockname()[1])]), 5)
                    churn_ok[0] += 1
                    await asyncio.sleep(rng.uniform(0.5, 1.0))
                except Exception:
                    pass
                finally:
                    try:
                        await c.close()
                    except Exception:
                        pass
                    s1.close()
                    s2.close()

        drain_task = asyncio.ensure_future(_pull_drain())
        churn_task = asyncio.ensure_future(_churn())
        while time.time() - t0 < seconds:
            now = time.time() - t0
            if await pusher.ensure_connected(dead):
                pusher.push()
            # drain the migrating UDP player, stamping recovery
            while True:
                try:
                    d = udp_rtp.recv(65536)
                except BlockingIOError:
                    break
                if len(d) >= 12:
                    rx_seqs.append(struct.unpack("!H", d[2:4])[0])
                    rx_ssrcs.add(d[8:12])
                    if killed and recovery_sec is None:
                        recovery_sec = time.monotonic() - kill_mono
            if "flash_joined" not in stats and now >= t_flash_in:
                # flash-crowd join wave on the non-owner (one-shot latch:
                # list emptiness would re-fire the wave every iteration
                # after the leave)
                for _ in range(8):
                    c = RtspClient()
                    await c.connect("127.0.0.1", rtsp_ports[pull_node])
                    await c.play_start(
                        f"rtsp://127.0.0.1:{rtsp_ports[pull_node]}{path}")
                    flash.append(c)
                stats["flash_joined"] = len(flash)
            if flash and now >= t_flash_out:
                for c in flash:
                    try:
                        await c.close()
                    except Exception:
                        pass
                flash = []
            if not killed and now >= t_kill:
                # the seeded node-kill: SIGKILL the owner mid-relay
                procs[owner].kill()
                dead.add(owner)
                killed = True
                kill_mono = time.monotonic()
                stats["killed_at"] = round(now, 1)
            await asyncio.sleep(0.03)
        await drain_task
        await churn_task

        # ------------------------------------------------------ verdicts
        if not killed:
            failures.append("node-kill never fired (duration too short)")
        gap = _seq_gap(rx_seqs)
        post_kill = recovery_sec is not None
        if not post_kill:
            failures.append("UDP player never resumed after the kill "
                            "(no migration)")
            recovery_sec = float("inf")
        elif recovery_sec > 10.0:
            failures.append(f"failover recovery {recovery_sec:.1f}s "
                            "exceeds the 10 s budget")
        if gap != 0:
            failures.append(f"sequence gap across migration: {gap} "
                            "packets missing at the player socket")
        if len(rx_ssrcs) != 1:
            failures.append(f"ssrc changed across migration: "
                            f"{len(rx_ssrcs)} identities seen")
        if len(rx_seqs) < 100:
            failures.append(f"UDP player starved: {len(rx_seqs)} packets")
        if pull_rx[0] < 50:
            failures.append(f"pull subscriber starved: {pull_rx[0]}")
        if pull_rx_after_kill[0] == 0:
            failures.append("pull subscriber never progressed after the "
                            "kill (adoption/pull re-resolution failed)")
        if churn_ok[0] == 0:
            failures.append("zero churn subscribers completed SETUP/PLAY")
        # ---- ISSUE 20 durability: the dead owner's finalized .dvr
        # asset replays from the survivors' erasure shards alone
        from easydarwin_tpu.protocol.rtp import RtpPacket
        s_rx = 0
        s_seqs: list[int] = []
        s_ssrcs: set[int] = set()
        if killed and n_nodes >= 3:
            rp = RtspClient()
            try:
                await rp.connect("127.0.0.1", rtsp_ports[pull_node])
                await rp.play_start(f"rtsp://127.0.0.1:"
                                    f"{rtsp_ports[pull_node]}/live/s.dvr")
                t_end = time.monotonic() + 15.0
                while time.monotonic() < t_end and s_rx < 160:
                    try:
                        d = await rp.recv_interleaved(0, timeout=1.0)
                    except asyncio.TimeoutError:
                        continue
                    if len(d) >= 12:
                        s_rx += 1
                        p = RtpPacket.parse(d)
                        s_seqs.append(p.seq)
                        s_ssrcs.add(p.ssrc)
            except Exception as e:
                failures.append(
                    f"dvr replay from survivors failed to start: {e!r}")
            finally:
                try:
                    await rp.close()
                except Exception:
                    pass
            if s_rx < 32:             # at least one full spill window
                failures.append(
                    f"dead owner's .dvr asset not playable from the "
                    f"surviving shards: {s_rx} packets")
            if _seq_gap(s_seqs) != 0:
                failures.append(
                    f"byte-exactness hole in the shard replay: "
                    f"{_seq_gap(s_seqs)} packets missing")
            if len(s_ssrcs) > 1:
                failures.append("ssrc changed across the shard replay")
            for nid in node_ids:
                if nid in dead:
                    continue
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{rest_ports[nid]}"
                        f"/api/v1/storagestats", timeout=5) as r:
                    sst = _json.loads(r.read().decode())
                if sst.get("pack_window_calls", 0) != 0:
                    failures.append(
                        f"{nid}: {sst['pack_window_calls']} repacks "
                        "during the shard replay (must be zero)")
                if sst.get("scrub_errors", 0) != 0:
                    failures.append(f"{nid}: storage scrub errors "
                                    f"{sst['scrub_errors']}")
                if sst.get("oracle_mismatches", 0) != 0:
                    failures.append(f"{nid}: storage oracle mismatches "
                                    f"{sst['oracle_mismatches']}")
                stats.setdefault("storage", {})[nid] = {
                    k: sst.get(k, 0) for k in (
                        "shards_local", "reconstructs", "repairs",
                        "scrubbed")}
            stats["dvr_replay_rx"] = s_rx

        m = _metrics(successor)
        if m.get("cluster_migrations_total", 0) == 0:
            failures.append("survivor counted zero cluster_migrations_total")
        for k, v in m.items():
            if k.startswith("resilience_ladder_level") and v != 0:
                failures.append(f"unrecovered degradation at exit: "
                                f"{k} = {v:.0f}")
        for nid in node_ids:
            if nid not in dead and procs[nid].returncode is not None:
                failures.append(f"{nid} died unexpectedly "
                                f"(rc={procs[nid].returncode})")
        stats.update({
            "udp_rx": len(rx_seqs),
            "pull_rx": pull_rx[0],
            "pull_rx_after_kill": pull_rx_after_kill[0],
            "churn_ok": churn_ok[0],
            "pusher_reconnects": pusher.reconnects,
            "migrations": m.get("cluster_migrations_total"),
            "pull_retries": m.get("cluster_pull_retries_total"),
            "lease_lost": m.get("cluster_lease_lost_total"),
            "redis_errors": m.get("redis_errors_total"),
            # the bench extra.cluster shape bench_gate --check-only
            # validates: {migration_gap_packets == 0,
            # failover_recovery_sec <= 10}
            "cluster": {
                "migration_gap_packets": gap,
                "failover_recovery_sec":
                    round(recovery_sec, 2) if post_kill else None,
            },
        })
        print("SOAK CLUSTER", "FAIL" if failures else "OK",
              _json.dumps(stats))
        for msg in failures:
            print("  -", msg)
    finally:
        for c in flash:
            try:
                await c.close()
            except Exception:
                pass
        for nid, p in procs.items():
            if p.returncode is None:
                p.kill()
        for p in procs.values():
            try:
                await asyncio.wait_for(p.wait(), 10)
            except asyncio.TimeoutError:
                pass
        await redis.close()
        await mini.stop()
        udp_rtp.close()
        udp_rtcp.close()
    return 1 if failures else 0


#: ledger wait-SLO scale (ISSUE 16 satellite 2): the composed round
#: oversubscribes this host hard (N full nodes + the harness on 2
#: vCPUs), so a raw 50 ms bound on a single wake's enqueue→start wait
#: would flag the OS scheduler, not the pump.  The scale admits the
#: same multi-second stalls the round's other latency figures accept
#: (mixed p99 runs in the seconds on this box) while still failing a
#: genuinely wedged pump (a wait past ~20× the mixed p99's own order).
LEDGER_WAIT_SLO_SCALE = 600.0

#: viewer-experience gate floor (ISSUE 18): a live-tier QoE p10 below
#: this without a matching admission/shed event fails the composed soak
AUDIENCE_QOE_FLOOR = 0.5


def qoe_tiers(metrics_docs) -> dict[str, dict]:
    """Per-tier QoE distributions merged across nodes from the
    ``audience_qoe_score_bucket`` series of parsed ``/metrics`` exports
    (cumulative Prometheus buckets; the quantile is the smallest bound
    whose cumulative count reaches q·total — the same upper-bound
    estimate the registry's own ``bucket_quantile`` makes)."""
    pat = re.compile(
        r'audience_qoe_score_bucket\{tier="([^"]+)",le="([^"]+)"\}')
    acc: dict[str, dict[float, float]] = {}
    for m in metrics_docs:
        for k, v in m.items():
            mt = pat.fullmatch(k)
            if not mt:
                continue
            le = mt.group(2)
            bound = float("inf") if le == "+Inf" else float(le)
            d = acc.setdefault(mt.group(1), {})
            d[bound] = d.get(bound, 0.0) + v
    out: dict[str, dict] = {}
    for tier, cum in acc.items():
        bounds = sorted(cum)
        total = cum.get(float("inf"), 0.0)
        if total <= 0:
            continue

        def q_at(q: float) -> float:
            want = q * total
            for b in bounds:
                if cum[b] >= want:
                    return 1.0 if b == float("inf") else b
            return 1.0

        out[tier] = {"count": int(total), "p50": round(q_at(0.50), 4),
                     "p10": round(q_at(0.10), 4)}
    return out


def audience_verdicts(aud: dict, *, shed_evidence: bool,
                      storm_blamed: str = "",
                      qoe_floor: float = AUDIENCE_QOE_FLOOR) -> list[str]:
    """The viewer-experience gate (ISSUE 18): a collapsed live-tier QoE
    p10 is acceptable ONLY when the cluster itself said "shed" —
    admission refusals and ladder/resilience sheds name a deliberate
    trade recorded in counters and events; a bare collapse means the
    viewers silently suffered with no decision on record.  Pure (takes
    the composed audience doc + pre-derived evidence) so tests drive it
    with synthetic rollups."""
    out: list[str] = []
    if not isinstance(aud, dict):
        return out
    live = (aud.get("tiers") or {}).get("live") or {}
    p10 = live.get("p10", aud.get("qoe_p10"))
    watched = live.get("count") or aud.get("subscribers") or 0
    if watched and isinstance(p10, (int, float)) and p10 < qoe_floor \
            and not shed_evidence:
        msg = (f"viewer experience: live-tier QoE p10 {p10:.2f} below "
               f"the {qoe_floor:.2f} floor with no admission/shed "
               "event naming a deliberate trade")
        if storm_blamed:
            msg += f" (stall storm blamed work class: {storm_blamed})"
        out.append(msg)
    return out


async def composed_soak(n_nodes: int, seconds: float,
                        seed: int = 7) -> int:
    """``--composed N`` (ISSUE 15): the observatory round — the FULL
    mixed workload across N real server processes with every engine on,
    a flash-crowd wave and a mid-run owner SIGKILL, validated through
    the fleet observability layer itself.

    Workload: a live relay (/live/m on the ring owner) with a UDP
    subscriber, an interleaved-TCP subscriber and a relay-tree edge
    pull on a non-owner; a 3-rung requant HLS ladder (/live/h) with a
    polling HTTP audience; hot/cold VOD with seek churn; a DVR
    time-shift subscriber pausing/rewinding/catching up on /live/d;
    and one lossy-UDP player (x-FEC negotiated, seeded receiver-side
    loss, honest RRs + NACKs) — all on the work node.

    Verdicts: every hop of the relay-tree subscriber's trace stitches
    under ONE trace_id via ``GET /api/v1/sessions/<id>/trace``; the
    fleet endpoint shows every live node, marks the killed owner's
    rollup STALE inside its TTL window, shows zero idle-peer SLO burn
    and zero wire/oracle mismatches; the owner kill is gapless at the
    UDP player (migration gap 0, same ssrc) and the adopted stream
    keeps its trace id with both nodes in its lineage; the DVR player
    counts a catch-up join, the VOD cache shows hits AND misses, the
    HLS ladder serves 3 renditions, and the FEC tier engages under the
    injected loss.  Exports the ``COMPOSED STATS`` JSON line bench.py
    folds into ``extra.composed`` (BENCH_r06)."""
    import json as _json
    import random
    import shutil
    import urllib.error

    from easydarwin_tpu.cluster.placement import HashRing
    from easydarwin_tpu.cluster.redis_client import (AsyncRedis,
                                                     MiniRedisServer)
    from easydarwin_tpu.codecs.h264_intra import encode_iframe as enc
    from easydarwin_tpu.protocol import nalu as nalu_mod
    from easydarwin_tpu.protocol.rtcp import (GenericNack, ReceiverReport,
                                              ReportBlock)
    from easydarwin_tpu.relay.fec import FecReceiver
    from easydarwin_tpu import obs as _obs

    assert n_nodes >= 2, "--composed needs at least 2 nodes"
    seconds = max(seconds, 40.0)
    rng = random.Random(seed)
    failures: list[str] = []
    stats: dict = {}
    shutil.rmtree("/tmp/edtpu_composed_soak", ignore_errors=True)
    node_ids = [f"comp-node-{i}" for i in range(n_nodes)]
    # VOD fixtures land in each node's movie folder BEFORE boot (the
    # children serve from <log_dir>/movies)
    vod_assets: list[str] = []
    for nid in node_ids:
        vod_assets = write_vod_assets(
            f"/tmp/edtpu_composed_soak/{nid}/movies", 2, n_frames=450)
    mini = MiniRedisServer()
    await mini.start()
    redis = AsyncRedis("127.0.0.1", mini.port)
    procs: dict[str, asyncio.subprocess.Process] = {}
    rtsp_ports: dict[str, int] = {}
    rest_ports: dict[str, int] = {}
    here = os.path.abspath(__file__)
    for i, nid in enumerate(node_ids):
        # child stderr lands next to the node's logs — the composed
        # round exists to make cross-node failures attributable
        err = open(f"/tmp/edtpu_composed_soak/{nid}/stderr.log", "wb")
        p = await asyncio.create_subprocess_exec(
            sys.executable, here, "--cluster-node", "--composed-child",
            "--node-id", nid, "--redis-port", str(mini.port),
            stdout=asyncio.subprocess.PIPE, stderr=err,
            env=_node_env(i))
        err.close()
        procs[nid] = p
        line = await asyncio.wait_for(p.stdout.readline(), 90)
        if not line.startswith(b"NODE_READY"):
            raise RuntimeError(f"{nid} failed to boot: {line!r}")
        kv = dict(t.split("=") for t in line.decode().split()[1:])
        rtsp_ports[nid] = int(kv["rtsp"])
        rest_ports[nid] = int(kv["rest"])

    ring = HashRing(node_ids, 64)
    owner = ring.owner("/live/m")
    pull_node = [n for n in ring.rank("/live/m") if n != owner][0]
    work = pull_node                    # HLS/VOD/DVR/lossy host; never killed
    dead: set[str] = set()
    stats.update({"owner": owner, "work": work})

    def http_get(nid: str, path: str, timeout: float = 5.0):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{rest_ports[nid]}{path}",
                    timeout=timeout) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, b""
        except OSError:
            return 0, b""

    async def aget(nid: str, path: str, timeout: float = 5.0):
        return await asyncio.to_thread(http_get, nid, path, timeout)

    async def metrics_of(nid: str) -> dict[str, float]:
        _st, body = await aget(nid, "/metrics")
        return parse_metrics(body.decode("utf-8", "replace"))

    async def fleet_of(nid: str) -> dict:
        _st, body = await aget(nid, "/api/v1/fleet")
        try:
            return _json.loads(body.decode("utf-8", "replace"))
        except ValueError:
            return {}

    # ------------------------------------------------------- the audience
    udp_rtp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    udp_rtp.bind(("127.0.0.1", 0))
    udp_rtp.setblocking(False)
    udp_rtcp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    udp_rtcp.bind(("127.0.0.1", 0))
    udp_rtcp.setblocking(False)
    l_rtp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    l_rtp.bind(("127.0.0.1", 0))
    l_rtp.setblocking(False)
    l_rtcp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    l_rtcp.bind(("127.0.0.1", 0))
    l_rtcp.setblocking(False)
    pusher_m = _ClusterPusher("/live/m", redis, rtsp_ports)
    pusher_d = _ClusterPusher("/live/d", redis, rtsp_ports)
    cycle = [enc(synth_frame(i), 24) for i in range(8)]
    hls_state = {"seq": 0, "frame": 0, "bytes": 0, "renditions": set()}
    counters = {"udp": 0, "tcp": 0, "pull": 0, "vod": 0, "dvr": 0,
                "lossy_seen": 0, "lossy_dropped": 0, "catchups": 0}
    rx_seqs: list[int] = []
    rx_ssrcs: set[bytes] = set()
    tcp_seqs: list[int] = []
    flash: list[RtspClient] = []
    tasks: list[asyncio.Task] = []
    clients: list[RtspClient] = []
    lrng = random.Random(seed ^ 0x5A5A)
    fec_rx = FecReceiver(media_pt=96, fec_pt=127, rtx_pt=126)
    lossy_media_ssrc = [0]
    lossy_rtcp_dst = [0]
    killed = [False]
    kill_mono = [0.0]
    recovery_sec: list[float | None] = [None]
    t0 = time.time()

    async def drain_tcp(player: RtspClient, key: str,
                        seqs: list[int] | None = None) -> None:
        while time.time() - t0 < seconds:
            try:
                p = await player.recv_interleaved(0, timeout=0.25)
            except asyncio.TimeoutError:
                continue
            except Exception:
                return
            counters[key] += 1
            if seqs is not None and len(p) >= 12:
                seqs.append(struct.unpack("!H", p[2:4])[0])

    def drain_udp() -> None:
        while True:
            try:
                d = udp_rtp.recv(65536)
            except (BlockingIOError, OSError):
                break
            if len(d) >= 12:
                counters["udp"] += 1
                rx_seqs.append(struct.unpack("!H", d[2:4])[0])
                rx_ssrcs.add(d[8:12])
                if killed[0] and recovery_sec[0] is None:
                    recovery_sec[0] = time.monotonic() - kill_mono[0]

    def drain_lossy() -> None:
        while True:
            try:
                d = l_rtp.recv(65536)
            except (BlockingIOError, OSError):
                break
            if len(d) < 12:
                continue
            counters["lossy_seen"] += 1
            if lrng.random() < 0.08:    # seeded receiver-side last mile
                counters["lossy_dropped"] += 1
                continue
            fec_rx.on_packet(d)

    def lossy_feedback() -> None:
        if not fec_rx.media or not lossy_media_ssrc[0]:
            return
        seen, dropped = counters["lossy_seen"], counters["lossy_dropped"]
        frac = min(int(min(dropped / seen, 1.0) * 256), 255) if seen else 0
        hi = max(fec_rx.media)
        rr = ReceiverReport(0x7C7C, [ReportBlock(
            lossy_media_ssrc[0], frac, dropped, hi & 0xFFFF,
            0, 0, 0)]).to_bytes()
        l_rtcp.sendto(rr, ("127.0.0.1", lossy_rtcp_dst[0]))
        miss = fec_rx.missing(min(fec_rx.media), hi - 16)[-32:]
        if miss:
            l_rtcp.sendto(GenericNack.from_seqs(
                0x7C7C, lossy_media_ssrc[0],
                [m & 0xFFFF for m in miss]).to_bytes(),
                ("127.0.0.1", lossy_rtcp_dst[0]))

    def push_hls(pusher: RtspClient) -> None:
        st = hls_state
        ts = int(st["frame"] * 11250)           # ~8 fps cadence
        for nal in cycle[st["frame"] % 8]:
            for p in nalu_mod.packetize_h264(
                    nal, seq=st["seq"], timestamp=ts, ssrc=7,
                    marker_on_last=(nal[0] & 0x1F == 5)):
                st["seq"] += 1
                pusher.push_packet(0, p)
        st["frame"] += 1

    async def hls_poll() -> None:
        await asyncio.sleep(3.0)
        while time.time() - t0 < seconds:
            await asyncio.sleep(1.0)
            st, body = await aget(work, "/hls/live/h/master.m3u8")
            if st != 200:
                continue
            rungs = [ln for ln in body.decode().splitlines()
                     if ln.endswith("index.m3u8")]
            fetched = False
            for rel in rungs:
                st2, idx = await aget(work, f"/hls/live/h/{rel}")
                if st2 != 200 or b"#EXTINF" not in idx:
                    continue
                # a cut segment in the playlist IS the rendition serving
                # (the body fetch below is rationed to one rung per
                # cycle — on a loaded box fetching every rung's segment
                # every second starves the sweep and under-counts the
                # ladder width)
                hls_state["renditions"].add(rel)
                segs = [ln for ln in idx.decode().splitlines()
                        if ln.endswith(".m4s")]
                if not segs or fetched:
                    continue
                base_dir = rel.rsplit("/", 1)[0] + "/" if "/" in rel else ""
                st3, data = await aget(
                    work, f"/hls/live/h/{base_dir}{segs[-1]}")
                if st3 == 200 and data:
                    hls_state["bytes"] += len(data)
                    fetched = True

    async def _join_retry(c: RtspClient, uri: str, tries: int = 4,
                          **kw) -> None:
        """play_start with a real player's retry patience: a 404/45x on
        a loaded box mid-claim is 'not ready yet', and a request
        timeout is a pump busy compiling/serving — neither is a
        failure until it repeats (the CSeq matcher drops any late
        reply, so a timed-out request cannot desync the retry)."""
        for attempt in range(tries):
            try:
                await c.play_start(uri, **kw)
                return
            except (AssertionError, asyncio.TimeoutError):
                if attempt == tries - 1:
                    raise
                await asyncio.sleep(2.0)

    async def vod_player() -> None:
        c = RtspClient()
        clients.append(c)
        await c.connect("127.0.0.1", rtsp_ports[work])
        uri = f"rtsp://127.0.0.1:{rtsp_ports[work]}/{vod_assets[0]}"
        await _join_retry(c, uri)
        next_seek = time.time() + 4.0
        while time.time() - t0 < seconds:
            try:
                await c.recv_interleaved(0, timeout=0.25)
                counters["vod"] += 1
            except asyncio.TimeoutError:
                pass
            except Exception:
                return
            if time.time() >= next_seek:
                next_seek = time.time() + 5.0
                npt = rng.uniform(0.0, 10.0)
                try:
                    await c.request("PLAY", uri,
                                    {"range": f"npt={npt:.2f}-"})
                except Exception:
                    return

    async def dvr_player() -> None:
        """PAUSE → rewind to npt=0 at Speed 4 → catch up → repeat."""
        await asyncio.sleep(5.0)        # let windows spill first
        c = RtspClient()
        clients.append(c)
        await c.connect("127.0.0.1", rtsp_ports[work])
        uri = f"rtsp://127.0.0.1:{rtsp_ports[work]}/live/d"
        await _join_retry(c, uri)
        phase_live_until = time.time() + 4.0
        while time.time() - t0 < seconds - 6.0:
            try:
                await c.recv_interleaved(0, timeout=0.25)
                counters["dvr"] += 1
            except asyncio.TimeoutError:
                pass
            except Exception:
                return
            if time.time() >= phase_live_until:
                try:
                    await c.request("PAUSE", uri)
                    await asyncio.sleep(0.6)
                    r = await c.request("PLAY", uri,
                                        {"range": "npt=0.0-",
                                         "speed": "4"})
                    assert r.status == 200, r.status
                except Exception:
                    return
                counters["catchups"] += 1
                phase_live_until = time.time() + 10.0

    try:
        # ------------------------------------------------ bring-up
        await pusher_m.connect_to(owner)
        await pusher_d.connect_to(work)
        hls_pusher = RtspClient()
        clients.append(hls_pusher)
        await hls_pusher.connect("127.0.0.1", rtsp_ports[work])
        await hls_pusher.push_start(
            f"rtsp://127.0.0.1:{rtsp_ports[work]}/live/h", SDP)
        for _ in range(10):
            pusher_m.push()
            pusher_d.push()
            push_hls(hls_pusher)
            await asyncio.sleep(0.02)
        await asyncio.sleep(1.5)        # claims + checkpoints up
        st, _b = await aget(work, "/api/v1/starthls?path=/live/h"
                                  "&rungs=q6,q12,q18")
        if st != 200:
            failures.append(f"starthls rungs failed: {st}")

        udp_player = RtspClient()
        clients.append(udp_player)
        await udp_player.connect("127.0.0.1", rtsp_ports[owner])
        await udp_player.play_start(
            f"rtsp://127.0.0.1:{rtsp_ports[owner]}/live/m", tcp=False,
            client_ports=[(udp_rtp.getsockname()[1],
                           udp_rtcp.getsockname()[1])])
        udp_sid = udp_player.session_id
        tcp_player = RtspClient()
        clients.append(tcp_player)
        await tcp_player.connect("127.0.0.1", rtsp_ports[owner])
        await tcp_player.play_start(
            f"rtsp://127.0.0.1:{rtsp_ports[owner]}/live/m")
        pull_player = RtspClient()
        clients.append(pull_player)
        await pull_player.connect("127.0.0.1", rtsp_ports[pull_node])
        # the edge's first DESCRIBE races the origin's claim tick + the
        # pull's upstream handshake; a 404 here means "not pulled yet"
        await _join_retry(
            pull_player,
            f"rtsp://127.0.0.1:{rtsp_ports[pull_node]}/live/m")
        pull_sid = pull_player.session_id
        lossy_player = RtspClient()
        clients.append(lossy_player)
        await lossy_player.connect("127.0.0.1", rtsp_ports[work])
        await lossy_player.play_start(
            f"rtsp://127.0.0.1:{rtsp_ports[work]}/live/d", tcp=False,
            client_ports=[(l_rtp.getsockname()[1],
                           l_rtcp.getsockname()[1])],
            setup_headers={"x-fec": "parity"})
        tr = lossy_player.transports[0]
        lossy_media_ssrc[0] = tr.ssrc or 0
        lossy_rtcp_dst[0] = (tr.server_port or (0, 0))[1]
        if not lossy_player.setup_responses[0].headers.get("x-fec"):
            failures.append("lossy player's x-FEC was not granted")

        tasks = [
            asyncio.ensure_future(drain_tcp(tcp_player, "tcp", tcp_seqs)),
            asyncio.ensure_future(drain_tcp(pull_player, "pull")),
            asyncio.ensure_future(hls_poll()),
            asyncio.ensure_future(vod_player()),
            asyncio.ensure_future(dvr_player()),
        ]

        t_kill = max(seconds * 0.55, seconds - 20.0)
        t_flash_in, t_flash_out = seconds * 0.25, seconds * 0.7
        t_trace = seconds * 0.40
        last_fb = 0.0
        traced = False
        eff_sample = None
        stale_seen = [False]
        pre_kill_trace = [None]

        async def check_traces() -> int:
            """Every subscriber's trace must resolve across its hops."""
            bad = 0
            st, body = await aget(pull_node,
                                  f"/api/v1/sessions/{pull_sid}/trace")
            doc = {}
            try:
                doc = _json.loads(body.decode("utf-8", "replace"))
            except ValueError:
                pass
            hops = doc.get("hops") or []
            if st != 200 or len(hops) < 2:
                bad += 1
                failures.append(
                    f"pull subscriber trace did not stitch across hops "
                    f"(status {st}, hops {[h.get('node') for h in hops]})")
            else:
                if not doc.get("trace_stitched"):
                    bad += 1
                    failures.append(
                        "pull subscriber hops disagree on trace_id: "
                        + str([h.get("trace") for h in hops]))
                if hops[0].get("node") != owner \
                        or hops[-1].get("node") != pull_node:
                    bad += 1
                    failures.append(
                        f"stitched hop order wrong: "
                        f"{[h.get('node') for h in hops]}")
                pre_kill_trace[0] = doc.get("stream_trace")
            st2, body2 = await aget(owner,
                                    f"/api/v1/sessions/{udp_sid}/trace")
            doc2 = {}
            try:
                doc2 = _json.loads(body2.decode("utf-8", "replace"))
            except ValueError:
                pass
            if st2 != 200 or not (doc2.get("hops") or []):
                bad += 1
                failures.append(
                    f"udp subscriber trace did not resolve ({st2})")
            return bad

        async def fleet_stale_poll() -> None:
            """The killed owner's rollup must appear STALE on a
            survivor inside its Fleet TTL window."""
            for _ in range(14):
                doc = await fleet_of(work)
                rec = (doc.get("nodes") or {}).get(owner)
                if isinstance(rec, dict) and rec.get("stale"):
                    stale_seen[0] = True
                    return
                await asyncio.sleep(0.5)

        unresolved = 0
        while time.time() - t0 < seconds:
            now = time.time() - t0
            if await pusher_m.ensure_connected(dead):
                pusher_m.push()
            if await pusher_d.ensure_connected(dead):
                pusher_d.push()
            if int(now * 8) > hls_state["frame"]:
                push_hls(hls_pusher)
            drain_udp()
            drain_lossy()
            if time.time() - last_fb >= 1.0:
                last_fb = time.time()
                lossy_feedback()
            if not traced and now >= t_trace:
                traced = True
                unresolved = await check_traces()
                eff_sample = {n: await fleet_of(n)
                              for n in node_ids if n not in dead}
            if "flash_joined" not in stats and now >= t_flash_in:
                for _ in range(6):
                    c = RtspClient()
                    await c.connect("127.0.0.1", rtsp_ports[pull_node])
                    await c.play_start(
                        f"rtsp://127.0.0.1:{rtsp_ports[pull_node]}/live/m")
                    flash.append(c)
                stats["flash_joined"] = len(flash)
            if flash and now >= t_flash_out:
                for c in flash:
                    try:
                        await c.close()
                    except Exception:
                        pass
                flash = []
            if not killed[0] and now >= t_kill:
                procs[owner].kill()
                dead.add(owner)
                killed[0] = True
                kill_mono[0] = time.monotonic()
                stats["killed_at"] = round(now, 1)
                tasks.append(asyncio.ensure_future(fleet_stale_poll()))
            await asyncio.sleep(0.03)
        for t in tasks:
            if not t.done():
                t.cancel()

        # --------------------------------------------------- verdicts
        survivors = [n for n in node_ids if n not in dead]
        metrics = {n: await metrics_of(n) for n in survivors}
        fleets = await fleet_of(survivors[0])
        # per-node wake-ledger blame docs (ISSUE 16): the causal
        # decomposition of the mixed p99 the bench round will gate on
        blames: dict[str, dict] = {}
        for n in survivors:
            _st, body = await aget(n, "/api/v1/admin?command=blame")
            if _st == 200:
                try:
                    blames[n] = _json.loads(body.decode("utf-8",
                                                        "replace"))
                except ValueError:
                    pass
        # per-node audience drill-down docs (ISSUE 18): the columnar
        # QoE store's rollup + worst subscribers, composed below
        audiences: dict[str, dict] = {}
        for n in survivors:
            _st, body = await aget(n, "/api/v1/audience?n=3")
            if _st == 200:
                try:
                    audiences[n] = _json.loads(body.decode("utf-8",
                                                           "replace"))
                except ValueError:
                    pass
        if not killed[0]:
            failures.append("owner kill never fired (duration too short)")
        gap = _seq_gap(rx_seqs)
        if recovery_sec[0] is None:
            failures.append("UDP player never resumed after the kill")
        elif recovery_sec[0] > 10.0:
            failures.append(f"failover recovery {recovery_sec[0]:.1f}s "
                            "exceeds the 10 s budget")
        if gap != 0:
            failures.append(f"migration gap: {gap} packets missing at "
                            "the UDP player")
        if len(rx_ssrcs) != 1:
            failures.append(f"ssrc changed across migration: "
                            f"{len(rx_ssrcs)}")
        if unresolved:
            failures.append(f"{unresolved} subscriber traces failed to "
                            "stitch")
        if not stale_seen[0]:
            failures.append("killed owner's fleet rollup never showed "
                            "stale on a survivor")
        # post-kill trace lineage: the adopted stream keeps its trace id
        # with both nodes in its lineage
        adopt_doc = {}
        for n in survivors:
            st, body = await aget(n, "/api/v1/streamtrace?path=/live/m")
            if st == 200:
                try:
                    cand = _json.loads(body.decode("utf-8", "replace"))
                except ValueError:
                    continue
                if cand.get("trace"):
                    adopt_doc = cand
                    break
        if pre_kill_trace[0] and adopt_doc:
            if adopt_doc.get("trace") != pre_kill_trace[0]:
                failures.append(
                    f"adopted stream lost its trace id: "
                    f"{adopt_doc.get('trace')} != {pre_kill_trace[0]}")
            lineage = adopt_doc.get("lineage") or []
            if owner not in lineage or adopt_doc.get("node") not in lineage:
                failures.append(f"adopted stream lineage {lineage} does "
                                f"not span both nodes")
        elif pre_kill_trace[0]:
            failures.append("adopted stream's trace not retrievable on "
                            "any survivor")
        # fleet health: nodes live, zero idle-peer SLO burn, zero
        # wire/oracle mismatches anywhere
        nodes_doc = fleets.get("nodes") or {}
        live_docs = {n: r for n, r in nodes_doc.items()
                     if isinstance(r, dict) and r.get("live")}
        if len(live_docs) != len(survivors):
            failures.append(f"fleet shows {len(live_docs)} live nodes, "
                            f"expected {len(survivors)}")
        for n, rec in live_docs.items():
            head = rec.get("headline") or {}
            slo = rec.get("slo") or {}
            if not head.get("subscribers") and slo.get("violations"):
                failures.append(f"idle peer {n} burned SLO: "
                                f"{slo['violations']} violations")
            mm = rec.get("mismatches") or {}
            for k, v in mm.items():
                if v:
                    failures.append(f"{n} recorded {v} {k} mismatches")
        # workload health per tier
        if counters["udp"] < 100:
            failures.append(f"UDP player starved: {counters['udp']}")
        if counters["pull"] < 50:
            failures.append(f"pull subscriber starved: {counters['pull']}")
        if counters["tcp"] < 50:
            failures.append(f"TCP player starved: {counters['tcp']}")
        if counters["vod"] < 50:
            failures.append(f"VOD player starved: {counters['vod']}")
        if counters["dvr"] < 50:
            failures.append(f"DVR player starved: {counters['dvr']}")
        if hls_state["bytes"] <= 0:
            failures.append("HLS audience never received a segment")
        if len(hls_state["renditions"]) < 3:
            failures.append(f"HLS ladder served "
                            f"{len(hls_state['renditions'])} renditions, "
                            "wanted 3")
        wm = metrics.get(work, {})
        if wm.get("vod_cache_hits_total", 0) <= 0 \
                or wm.get("vod_cache_misses_total", 0) <= 0:
            failures.append("VOD cache did not serve both hot and cold "
                            f"(hits {wm.get('vod_cache_hits_total')}, "
                            f"misses {wm.get('vod_cache_misses_total')})")
        if wm.get("dvr_windows_spilled_total", 0) <= 0:
            failures.append("DVR spilled zero windows")
        if wm.get("dvr_catchup_joins_total", 0) <= 0:
            failures.append("DVR time-shift never caught up to live")
        fec_engaged = (wm.get('fec_parity_packets_total{kind="rs"}', 0)
                       + wm.get('fec_parity_packets_total{kind="xor"}', 0)
                       + wm.get("rtx_sent_total", 0))
        if counters["lossy_dropped"] > 10 and fec_engaged <= 0:
            failures.append("FEC/RTX tier never engaged under "
                            f"{counters['lossy_dropped']} dropped pkts")
        recovered = int(_obs.FEC_RECOVERED.value())
        freshness2 = sum(
            v for k, v in metrics.get(pull_node, {}).items()
            if k.startswith('relay_e2e_freshness_seconds_count')
            and 'hops="2"' in k)
        if freshness2 <= 0:
            failures.append("relay-tree edge never observed a 2-hop "
                            "freshness chain")
        # wake-ledger wait SLO (ISSUE 16 satellite 2): a live-relay
        # unit whose enqueue→start wait exceeded the latency SLO means
        # the pump starved the data path behind auxiliary work — fail
        # and let the post-mortem below name the offender.  The bound
        # is the child nodes' slo_latency_objective_ms (50 ms) scaled
        # by the same oversubscription the harness accepts everywhere
        # else on this host (n nodes × full workload on a 2-vCPU box
        # yields multi-second scheduler stalls that are not the pump's
        # fault) — see LEDGER_WAIT_SLO_SCALE.
        wait_slo_ms = 50.0 * LEDGER_WAIT_SLO_SCALE
        for n, bd in blames.items():
            cls = ((bd.get("ledger") or {}).get("classes")
                   or {}).get("live_relay") or {}
            wmax = float(cls.get("wait_max_ms", 0.0) or 0.0)
            if wmax > wait_slo_ms:
                failures.append(
                    f"{n}: live_relay unit waited {wmax:.0f} ms — "
                    f"beyond the {wait_slo_ms:.0f} ms ledger wait SLO "
                    f"(top offender: {bd.get('top_offender')})")
        # ------------------------------------------------ bench figures
        eff = 0.0
        if eff_sample:
            rates = []
            for n, doc in eff_sample.items():
                rec = (doc.get("nodes") or {}).get(n) or {}
                rates.append(float((rec.get("headline") or {})
                                   .get("out_pps", 0.0)))
            if rates and max(rates) > 0:
                eff = sum(rates) / (len(rates) * max(rates))
        p99s = [float((r.get("headline") or {}).get("itw_p99_ms", 0.0))
                for r in live_docs.values()]
        fresh_p99 = max(
            (float(r.get("freshness_p99_s", 0.0))
             for r in live_docs.values()), default=0.0)
        dur = max(time.time() - t0, 1.0)
        composed = {
            "nodes": n_nodes,
            "tier_rates": {
                "live": round((counters["udp"] + counters["pull"]
                               + counters["tcp"]) / dur, 1),
                "hls": round(hls_state["bytes"] / dur, 1),
                "vod": round(counters["vod"] / dur, 1),
                "dvr": round(counters["dvr"] / dur, 1),
                "tcp": round(counters["tcp"] / dur, 1),
            },
            "scaling_efficiency": round(eff, 4),
            "migration_gap_packets": gap,
            "mixed_p99_ms": round(max(p99s, default=0.0), 3),
            "e2e_freshness_p99_s": round(fresh_p99, 4),
            "unresolved_traces": unresolved,
            "wire_mismatches": int(sum(
                m.get("megabatch_wire_mismatch_total", 0)
                + m.get("fec_parity_oracle_mismatch_total", 0)
                for m in metrics.values())),
            "fec_recovered": recovered,
            "fleet_nodes_live": len(live_docs),
        }
        # causal decomposition of the mixed p99 (ISSUE 16): the blame
        # doc of the node DEFINING mixed_p99_ms, re-conserved against
        # the composed headline figure (the node-side doc conserves
        # against its own live p99; the bench gate wants the round's)
        if blames and live_docs:
            def_node = max(
                live_docs,
                key=lambda n: float((live_docs[n].get("headline") or {})
                                    .get("itw_p99_ms", 0.0)))
            src = blames.get(def_node) or next(iter(blames.values()))
            lb = dict(src)
            mixed = composed["mixed_p99_ms"]
            if mixed > 0:
                lb["measured_p99_ms"] = mixed
                lb["conservation"] = round(
                    float(lb.get("attributed_p99_ms", 0.0)) / mixed, 4)
            lb["nodes"] = {
                n: {"top_offender": d.get("top_offender"),
                    "worst_wait_p99_ms": d.get("worst_wait_p99_ms")}
                for n, d in blames.items()}
            composed["latency_blame"] = lb
        # audience observatory (ISSUE 18): per-tier QoE distributions
        # merged across nodes from the histogram export, the headline
        # p50/p10 as the WORST populated node's figure (conservative —
        # the gate cares about the suffering node, not the average),
        # and the stall ratio normalised to subscriber-seconds
        aud_subs = sum(int(d.get("subscribers") or 0)
                       for d in audiences.values())
        stall_s = sum(v for m in metrics.values() for k, v in m.items()
                      if k.startswith("audience_stall_seconds_total"))
        aud_doc = {
            "subscribers": aud_subs,
            "qoe_p50": round(min(
                (float(d.get("qoe_p50") or 0.0)
                 for d in audiences.values() if d.get("subscribers")),
                default=1.0), 4),
            "qoe_p10": round(min(
                (float(d.get("qoe_p10") or 0.0)
                 for d in audiences.values() if d.get("subscribers")),
                default=1.0), 4),
            "tiers": qoe_tiers(metrics.values()),
            "stall_ratio": (round(stall_s / (aud_subs * dur), 6)
                            if aud_subs else 0.0),
            "stall_storms": sum(int(d.get("stall_storms") or 0)
                                for d in audiences.values()),
            "columns_bytes_per_subscriber": round(max(
                (float(d.get("columns_bytes_per_subscriber") or 0.0)
                 for d in audiences.values()), default=0.0), 1),
        }
        composed["audience"] = aud_doc
        # the viewer-experience gate: shed evidence = any node's
        # admission or shed counters moved (the deliberate-trade record)
        shed_evidence = any(
            v > 0 for m in metrics.values() for k, v in m.items()
            if k.startswith("cluster_admission_refused_total")
            or k.startswith("resilience_shed_outputs_total")
            or k.startswith("requant_shed_total"))
        storm_blamed = ""
        if aud_doc["stall_storms"]:
            for n in survivors:
                _st, body = await aget(
                    n, "/api/v1/admin?command=events&n=512")
                if _st != 200:
                    continue
                for ln in body.decode("utf-8", "replace").splitlines():
                    if '"audience.stall_storm"' not in ln:
                        continue
                    try:
                        ev = _json.loads(ln)
                    except ValueError:
                        continue
                    storm_blamed = str(ev.get("blamed")
                                       or storm_blamed)
        failures.extend(audience_verdicts(
            aud_doc, shed_evidence=shed_evidence,
            storm_blamed=storm_blamed))
        stats.update({
            "counters": counters,
            "hls_renditions": len(hls_state["renditions"]),
            "recovery_sec": (round(recovery_sec[0], 2)
                             if recovery_sec[0] is not None else None),
            "freshness_2hop_obs": freshness2,
            "composed": composed,
        })
        print("COMPOSED STATS", _json.dumps(composed))
        if failures:
            # post-mortem (ISSUE 16 satellite 2): the top-5 ledger
            # offenders per node — WHO made the pump late — alongside
            # the cluster-event tail — WHEN ownership/pulls churned
            for nid, bd in blames.items():
                for row in (bd.get("rows") or [])[:5]:
                    print(f"LEDGER {nid} class={row.get('work_class')} "
                          f"wait_p99_ms={row.get('wait_p99_ms')} "
                          f"deferred={row.get('deferred')}",
                          file=sys.stderr)
            for nid in survivors:
                _st, body = await aget(
                    nid, "/api/v1/admin?command=events&n=512")
                if _st != 200:
                    continue
                for ln in body.decode("utf-8", "replace").splitlines():
                    if '"cluster.' in ln or '"pull.' in ln \
                            or '"audience.' in ln:
                        print(f"EV {nid} {ln}", file=sys.stderr)
        print("SOAK COMPOSED", "FAIL" if failures else "OK",
              _json.dumps(stats, default=str))
        for msg in failures:
            print("  -", msg)
    finally:
        for t in tasks:
            if not t.done():
                t.cancel()
        for c in flash + clients:
            try:
                await c.close()
            except Exception:
                pass
        for nid, p in procs.items():
            if p.returncode is None:
                p.kill()
        for p in procs.values():
            try:
                await asyncio.wait_for(p.wait(), 10)
            except asyncio.TimeoutError:
                pass
        await redis.close()
        await mini.stop()
        for s in (udp_rtp, udp_rtcp, l_rtp, l_rtcp):
            s.close()
    return 1 if failures else 0


async def skewed_soak(n_nodes: int, seconds: float,
                      seed: int = 7) -> int:
    """ISSUE 13: heterogeneous-capacity cluster under a zipfian stream
    popularity curve with a flash crowd on the hottest stream.

    Node 0's capacity is forced LOW through the ``capacity_spoof`` fault
    site (it believes and publishes the lie), so a modest base load
    drives it past the high-water marks: the flash crowd's new SETUPs
    are answered with 305 redirects to placement-resolved edges (each
    edge runs ONE pull from the origin and fans out locally — the
    origin→edge relay tree), and the rebalancer then drains the hottest
    stream to the least-loaded peer through the PR 6 live-migration
    machinery (gapless seq, same ssrc at a plain-UDP player that never
    re-SETUPs).

    Fails if any node still burns while a peer sits under half
    utilization at exit, on any migration gap packet, or on zero
    admission refusals during the crowd.
    """
    import json as _json
    import os

    from easydarwin_tpu.cluster.placement import PlacementService
    from easydarwin_tpu.cluster.redis_client import (AsyncRedis,
                                                     MiniRedisServer)
    from easydarwin_tpu.protocol import sdp as sdp_mod

    assert n_nodes >= 3, "--skewed needs at least 3 nodes (origin + edges)"
    seconds = max(seconds, 60.0)
    failures: list[str] = []
    mini = MiniRedisServer()
    await mini.start()
    redis = AsyncRedis("127.0.0.1", mini.port)
    node_ids = [f"skew-node-{i}" for i in range(n_nodes)]
    weak = node_ids[0]
    #: the lying capacity (pps): 3 plain-UDP subscribers of a ~33 pps
    #: push read as util ≈ 1.65 — far past both high-water marks, while
    #: every honest peer benches in the tens of thousands
    weak_cap = 60
    procs: dict[str, asyncio.subprocess.Process] = {}
    rtsp_ports: dict[str, int] = {}
    rest_ports: dict[str, int] = {}
    here = os.path.abspath(__file__)
    for i, nid in enumerate(node_ids):
        args = [sys.executable, here, "--cluster-node", "--skewed-child",
                "--node-id", nid, "--redis-port", str(mini.port)]
        if nid == weak:
            args += ["--fault-plan",
                     f"seed={seed},capacity_spoof={weak_cap}"]
        p = await asyncio.create_subprocess_exec(
            *args, stdout=asyncio.subprocess.PIPE,
            stderr=asyncio.subprocess.DEVNULL, env=_node_env(i))
        procs[nid] = p
        line = await asyncio.wait_for(p.stdout.readline(), 60)
        if not line.startswith(b"NODE_READY"):
            raise RuntimeError(f"{nid} failed to boot: {line!r}")
        kv = dict(t.split("=") for t in line.decode().split()[1:])
        rtsp_ports[nid] = int(kv["rtsp"])
        rest_ports[nid] = int(kv["rest"])

    placement = PlacementService(redis, "soak-harness")

    def _metrics(nid: str) -> dict[str, float]:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{rest_ports[nid]}/metrics",
                timeout=5) as r:
            return parse_metrics(r.read().decode())

    def _fam(m: dict[str, float], prefix: str) -> float:
        return sum(v for k, v in m.items() if k.startswith(prefix))

    def _refused_total() -> float:
        return sum(_fam(_metrics(n), "cluster_admission_refused_total")
                   for n in node_ids)

    # wait until every node publishes a capacity into its lease record
    # (the control plane is live once caps + utils ride the records)
    for _ in range(40):
        nodes = await placement.live_nodes()
        if len(nodes) == n_nodes and all(
                isinstance(m.get("cap"), (int, float)) and m["cap"] > 0
                for m in nodes.values()):
            break
        await asyncio.sleep(0.25)
    else:
        raise RuntimeError(f"capacity publishing never settled: {nodes}")
    caps = {n: m["cap"] for n, m in nodes.items()}
    if min(caps, key=caps.get) != weak:
        failures.append(f"capacity spoof did not mark {weak} weakest: "
                        f"{caps}")

    # zipfian popularity: the hot stream carries 3 plain-UDP
    # subscribers ON THE WEAK NODE (first-come claim — placement is
    # sticky on the local source), the cold tail one subscriber each on
    # healthy nodes
    hot = "/live/hot"
    colds = [f"/live/cold{i}" for i in range(max(n_nodes - 1, 2))]
    pushers: dict[str, _ClusterPusher] = {}
    pushers[hot] = _ClusterPusher(hot, redis, rtsp_ports)
    await pushers[hot].connect_to(weak)
    for i, path in enumerate(colds):
        pushers[path] = _ClusterPusher(path, redis, rtsp_ports)
        await pushers[path].connect_to(node_ids[1 + i % (n_nodes - 1)])
    for _ in range(10):                 # prime before anyone subscribes
        for pu in pushers.values():
            pu.push()
        await asyncio.sleep(0.02)
    await asyncio.sleep(1.5)            # claims + first checkpoints up

    udp_socks: list[socket.socket] = []

    def _udp_pair() -> tuple[socket.socket, socket.socket]:
        s1 = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s1.bind(("127.0.0.1", 0))
        s1.setblocking(False)
        s2 = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s2.bind(("127.0.0.1", 0))
        s2.setblocking(False)
        udp_socks.extend((s1, s2))
        return s1, s2

    async def _udp_join(node: str, path: str
                        ) -> tuple[RtspClient, socket.socket]:
        rtp_s, rtcp_s = _udp_pair()
        c = RtspClient()
        await c.connect("127.0.0.1", rtsp_ports[node])
        await c.play_start(
            f"rtsp://127.0.0.1:{rtsp_ports[node]}{path}", tcp=False,
            client_ports=[(rtp_s.getsockname()[1],
                           rtcp_s.getsockname()[1])])
        return c, rtp_s

    async def _try_play_tcp(port: int, path: str):
        """One crowd join: ('ok', client) | ('redirect', location) |
        ('refuse'|'fail', None)."""
        c = RtspClient()
        try:
            await c.connect("127.0.0.1", port)
            uri = f"rtsp://127.0.0.1:{port}{path}"
            r = await c.request("DESCRIBE", uri,
                                {"accept": "application/sdp"})
            if r.status != 200:
                await c.close()
                return ("fail", None)
            st = sdp_mod.parse(r.body).streams[0]
            r = await c.request(
                "SETUP", f"{uri}/trackID={st.track_id}",
                {"transport": "RTP/AVP/TCP;unicast;interleaved=0-1"})
            if r.status == 305:
                loc = r.headers.get("location", "")
                await c.close()
                return ("redirect", loc)
            if r.status != 200:
                await c.close()
                return ("refuse" if r.status == 453 else "fail", None)
            r = await c.request("PLAY", uri)
            if r.status != 200:
                await c.close()
                return ("fail", None)
            return ("ok", c)
        except Exception:
            try:
                await c.close()
            except Exception:
                pass
            return ("fail", None)

    crowd: list[RtspClient] = []
    stats: dict = {"weak": weak, "caps": caps, "hot": hot}
    try:
        # base audience: 3 UDP subscribers on the hot stream at the weak
        # owner (the one that must survive the drain without re-SETUP),
        # one on each cold stream at its own owner
        gap_player, gap_rtp = await _udp_join(weak, hot)
        base_udp = [gap_player]
        for _ in range(2):
            c, _s = await _udp_join(weak, hot)
            base_udp.append(c)
        for path in colds:
            owner = await placement.claimant(path)
            c, _s = await _udp_join(owner or pushers[path].target, path)
            base_udp.append(c)

        t0 = time.time()
        t_crowd_in, crowd_n = 12.0, 10
        t_crowd_out = min(seconds * 0.7, 48.0)
        crowd_started = crowd_done = False
        crowd_next = t_crowd_in
        crowd_direct = 0
        crowd_edge = 0
        crowd_refused_flat = 0
        crowd_failed = 0
        refused_before = refused_after = 0.0
        drained_at: float | None = None
        drain_check_at = 0.0
        #: (t, claimant) transitions of the hot stream — the first thing
        #: to read when a run fails on end-state balance
        claimant_log: list[tuple[float, str | None]] = []
        rx_seqs: list[int] = []
        rx_ssrcs: set[bytes] = set()

        while time.time() - t0 < seconds:
            now = time.time() - t0
            dead: set[str] = set()
            for pu in pushers.values():
                if await pu.ensure_connected(dead):
                    pu.push()
            while True:
                try:
                    d = gap_rtp.recv(65536)
                except BlockingIOError:
                    break
                if len(d) >= 12:
                    rx_seqs.append(struct.unpack("!H", d[2:4])[0])
                    rx_ssrcs.add(d[8:12])
            if not crowd_started and now >= t_crowd_in:
                crowd_started = True
                refused_before = _refused_total()
            if (crowd_started and not crowd_done
                    and len(crowd) + crowd_failed + crowd_refused_flat
                    < crowd_n and now >= crowd_next):
                crowd_next = now + 0.5
                target = await placement.claimant(hot) or weak
                verdict, payload = await _try_play_tcp(
                    rtsp_ports[target], hot)
                if verdict == "ok":
                    crowd_direct += 1
                    crowd.append(payload)
                elif verdict == "redirect":
                    # follow the 305 to the placement-resolved edge
                    try:
                        hostport = payload.split("//", 1)[1].split("/")[0]
                        eport = int(hostport.rsplit(":", 1)[1])
                    except (IndexError, ValueError):
                        eport = None
                    v2, c2 = ("fail", None)
                    if eport is not None:
                        v2, c2 = await _try_play_tcp(eport, hot)
                    if v2 == "ok":
                        crowd_edge += 1
                        crowd.append(c2)
                    else:
                        crowd_failed += 1
                elif verdict == "refuse":
                    crowd_refused_flat += 1
                else:
                    crowd_failed += 1
                if (len(crowd) + crowd_failed + crowd_refused_flat
                        >= crowd_n):
                    crowd_done = True
                    refused_after = _refused_total()
                    stats["crowd_direct"] = crowd_direct
                    stats["crowd_edge"] = crowd_edge
            if crowd and now >= t_crowd_out:
                for c in crowd:
                    try:
                        stats.setdefault("crowd_rx", []).append(
                            c.stats.packets)
                        await c.close()
                    except Exception:
                        pass
                crowd = []
            if now >= drain_check_at:
                drain_check_at = now + 1.0      # scrape at 1 Hz, not per wake
                cl = await placement.claimant(hot)
                if not claimant_log or claimant_log[-1][1] != cl:
                    claimant_log.append((round(now, 1), cl))
                if drained_at is None:
                    try:
                        if _fam(_metrics(weak),
                                "cluster_rebalance_moves_total") >= 1:
                            drained_at = now
                            stats["drained_at"] = round(now, 1)
                    except Exception:
                        pass
            await asyncio.sleep(0.03)

        # ------------------------------------------------------ verdicts
        if not crowd_done:
            refused_after = _refused_total()
        # server-side truth only: the counter delta already includes
        # every 453 the harness saw (adding crowd_refused_flat on top
        # would double-count them) plus the 305 redirects
        refused_during_crowd = int(refused_after - refused_before)
        gap = _seq_gap(rx_seqs)
        served = crowd_direct + crowd_edge
        gain = served / max(crowd_direct, 1)
        crowd_rx = stats.get("crowd_rx", [])
        m_weak = _metrics(weak)
        moves = _fam(m_weak, "cluster_rebalance_moves_total")
        edges = sum(_fam(_metrics(n), "relay_tree_edges_total")
                    for n in node_ids if n != weak)
        if moves < 1:
            failures.append("the rebalancer never drained the burning "
                            "node's hottest stream")
        if drained_at is None and moves >= 1:
            drained_at = seconds
        if gap != 0:
            failures.append(f"sequence gap across the planned drain: "
                            f"{gap} packets missing at the player socket")
        if len(rx_ssrcs) != 1:
            failures.append(f"ssrc changed across the drain: "
                            f"{len(rx_ssrcs)} identities seen")
        if len(rx_seqs) < 200:
            failures.append(f"hot UDP player starved: {len(rx_seqs)}")
        if refused_during_crowd <= 0:
            failures.append("zero admission refusals during the flash "
                            "crowd (the overload gate never fired)")
        if crowd_edge == 0:
            failures.append("no crowd subscriber was served through an "
                            "edge redirect (no relay tree formed)")
        if edges < 1:
            failures.append("no origin→edge relay-tree edge was "
                            "established (relay_tree_edges_total == 0)")
        if gain <= 1.0:
            failures.append(f"tree_fanout_gain {gain:.2f} <= 1: the "
                            "relay tree served no more than the origin")
        starved = sum(1 for n in crowd_rx if n < 15)
        if crowd_rx and starved:
            failures.append(f"{starved}/{len(crowd_rx)} crowd "
                            "subscribers starved (< 15 pkts via edges)")
        # end-state balance: nobody burns while a peer idles
        utils = {}
        for nid in node_ids:
            m = _metrics(nid)
            utils[nid] = m.get("cluster_utilization_ratio", 0.0)
        hw, half = 0.9, 0.45
        if any(u >= hw for u in utils.values()) \
                and any(u < half for u in utils.values()):
            failures.append(f"a node still burns SLO while a peer sits "
                            f"under half utilization: {utils}")
        for nid in node_ids:
            if procs[nid].returncode is not None:
                failures.append(f"{nid} died unexpectedly "
                                f"(rc={procs[nid].returncode})")
        stats.update({
            "udp_rx": len(rx_seqs),
            "rebalance_moves": moves,
            "relay_tree_edges": edges,
            "hot_claimant": await placement.claimant(hot),
            "migrations": {n: _fam(_metrics(n),
                                   "cluster_migrations_total")
                           for n in node_ids},
            "lease_lost": {n: _fam(_metrics(n),
                                   "cluster_lease_lost_total")
                           for n in node_ids},
            "refused_during_crowd": refused_during_crowd,
            "utils": {k: round(v, 3) for k, v in utils.items()},
            "pusher_reconnects": {p: pu.reconnects
                                  for p, pu in pushers.items()},
            "claimant_log": claimant_log,
            # the bench extra.rebalance shape bench_gate --check-only
            # validates: {rebalance_gap_packets == 0,
            # refused_during_crowd > 0, tree_fanout_gain > 1}
            "rebalance": {
                "rebalance_gap_packets": gap,
                "refused_during_crowd": refused_during_crowd,
                "tree_fanout_gain": round(gain, 2),
            },
        })
        if failures:
            # post-mortem: every node's cluster.* event tail — the
            # claimant_log says WHEN the hot stream moved, these say WHY
            for nid in node_ids:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{rest_ports[nid]}"
                            f"/api/v1/admin?command=events&n=512",
                            timeout=5) as r:
                        lines = r.read().decode().splitlines()
                    for ln in lines:
                        if '"cluster.' in ln or '"pull.' in ln:
                            print(f"EV {nid} {ln}", file=sys.stderr)
                except Exception:
                    pass
        print("SOAK SKEWED", "FAIL" if failures else "OK",
              _json.dumps(stats))
        for msg in failures:
            print("  -", msg)
    finally:
        for c in crowd:
            try:
                await c.close()
            except Exception:
                pass
        for nid, p in procs.items():
            if p.returncode is None:
                p.kill()
        for p in procs.values():
            try:
                await asyncio.wait_for(p.wait(), 10)
            except asyncio.TimeoutError:
                pass
        await redis.close()
        await mini.stop()
        for s in udp_socks:
            s.close()
    return 1 if failures else 0


async def mixed_soak(seconds: float) -> int:
    """``--mixed`` (ISSUE 14): a combined UDP + interleaved-TCP + HLS
    audience on ONE server with the engine paths on, and a mid-run
    checkpoint migration — the server restarts on the SAME ports, the
    UDP subscriber hot-restores without re-SETUP, and the TCP player
    re-attaches with its old Session id for a gapless framed seq space.

    Fails on: any TCP session drop (seq gap or ssrc change at the
    interleaved player across the migration), any megabatch wire
    mismatch, zero engine-path TCP packets (the framed writev rung must
    actually serve), a starved player, or an HLS audience that never
    got a segment / whose ETag revalidation never short-circuited."""
    import json as json_mod
    import tempfile

    from easydarwin_tpu.codecs.h264_intra import encode_iframe as enc
    from easydarwin_tpu.protocol import nalu as nalu_mod

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    rtsp_port, rest_port = free_port(), free_port()
    log_folder = tempfile.mkdtemp(prefix="edtpu_mixed_soak_")

    def make_cfg() -> ServerConfig:
        return ServerConfig(
            rtsp_port=rtsp_port, service_port=rest_port,
            bind_ip="127.0.0.1", reflect_interval_ms=10,
            bucket_delay_ms=0, access_log_enabled=False,
            log_folder=log_folder, tpu_fanout=True, tpu_min_outputs=1,
            resilience_checkpoint_enabled=True,
            resilience_checkpoint_interval_sec=1.0)

    failures: list[str] = []
    base = f"rtsp://127.0.0.1:{rtsp_port}"
    rest = f"http://127.0.0.1:{rest_port}"
    # pre-encode the HLS feed's GOP cycle before the clock starts
    cycle = [enc(synth_frame(i), 24) for i in range(8)]
    seq_a = seq_b = 0
    frame = 0
    tcp_seqs: list[int] = []
    tcp_ssrcs: set = set()
    udp_rx = [0]
    hls_state = {"segment_bytes": 0, "etag_304": 0, "etag": None,
                 "seg_url": None}

    async def start_server():
        app = StreamingServer(make_cfg())
        await app.start()
        return app

    async def connect_pushers(app):
        pa = RtspClient()
        await pa.connect("127.0.0.1", rtsp_port)
        await pa.push_start(f"{base}/live/a", SDP)       # HLS feed
        pb = RtspClient()
        await pb.connect("127.0.0.1", rtsp_port)
        await pb.push_start(f"{base}/live/b", SDP)       # audience feed
        return pa, pb

    def http_get(path: str, etag: str | None = None):
        req = urllib.request.Request(rest + path)
        if etag:
            req.add_header("If-None-Match", etag)
        try:
            with urllib.request.urlopen(req, timeout=2.0) as r:
                return r.status, r.read(), r.headers.get("ETag")
        except urllib.error.HTTPError as e:
            return e.code, b"", None

    async def aget(path: str, etag: str | None = None):
        # urllib is BLOCKING and the server shares this event loop — a
        # loop-thread fetch would deadlock against the response it waits
        # for, so every HTTP round-trip rides a worker thread
        return await asyncio.to_thread(http_get, path, etag)

    async def hls_poll():
        # the HLS audience: start the ladder once, then poll playlist +
        # newest segment with conditional GETs (the 304 short-circuit
        # must fire on an unchanged window)
        await aget("/api/v1/starthls?path=/live/a")
        while True:
            await asyncio.sleep(0.5)
            st, body, _e = await aget("/hls/live/a/index.m3u8")
            if st != 200 or b"#EXTINF" not in body:
                continue
            seg = [ln for ln in body.decode().splitlines()
                   if ln.endswith(".m4s")]
            if not seg:
                continue
            url = f"/hls/live/a/{seg[-1]}"
            st2, data, etag = await aget(url)
            if st2 == 200 and data:
                hls_state["segment_bytes"] += len(data)
                if etag:
                    st3, _b3, _e3 = await aget(url, etag=etag)
                    if st3 == 304:
                        hls_state["etag_304"] += 1

    def push_tick(pa, pb):
        nonlocal seq_a, seq_b, frame
        ts = int(frame * 3000)
        for nal in cycle[frame % 8]:
            for p in nalu_mod.packetize_h264(
                    nal, seq=seq_a, timestamp=ts, ssrc=1,
                    marker_on_last=(nal[0] & 0x1F == 5)):
                seq_a += 1
                pa.push_packet(0, p)
        pkt = (struct.pack("!BBHII", 0x80, 96, seq_b & 0xFFFF, ts, 0xB)
               + bytes([0x65]) + bytes(120))
        seq_b += 1
        pb.push_packet(0, pkt)
        frame += 1

    async def tcp_drain(player):
        while True:
            try:
                p = await player.recv_interleaved(0, timeout=0.25)
            except asyncio.TimeoutError:
                continue
            except Exception:
                return
            if len(p) >= 12:
                tcp_seqs.append(struct.unpack("!H", p[2:4])[0])
                tcp_ssrcs.add(p[8:12])

    async def udp_drain(sock):
        while True:
            try:
                sock.recv(65536)
                udp_rx[0] += 1
            except BlockingIOError:
                await asyncio.sleep(0.01)
            except OSError:
                return

    app = await start_server()
    push_a, push_b = await connect_pushers(app)
    tcp_player = RtspClient()
    await tcp_player.connect("127.0.0.1", rtsp_port)
    await tcp_player.play_start(f"{base}/live/b", tcp=True)
    old_sid = tcp_player.session_id
    u_rtp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    u_rtp.bind(("127.0.0.1", 0))
    u_rtp.setblocking(False)
    u_rtcp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    u_rtcp.bind(("127.0.0.1", 0))
    u_rtcp.setblocking(False)
    udp_player = RtspClient()
    await udp_player.connect("127.0.0.1", rtsp_port)
    await udp_player.play_start(
        f"{base}/live/b", tcp=False,
        client_ports=[(u_rtp.getsockname()[1], u_rtcp.getsockname()[1])])
    tasks = [asyncio.ensure_future(tcp_drain(tcp_player)),
             asyncio.ensure_future(udp_drain(u_rtp)),
             asyncio.ensure_future(hls_poll())]
    t0 = time.time()
    migrate_at = t0 + max(5.0, seconds * 0.45)
    migrated = False
    udp_rx_at_migration = 0
    tcp_rx_at_migration = 0
    try:
        while time.time() - t0 < seconds:
            push_tick(push_a, push_b)
            await asyncio.sleep(0.03)
            if not migrated and time.time() >= migrate_at:
                migrated = True
                # --- the migration: checkpoint + restart on same ports
                assert app.checkpoint.write(app.registry)
                tasks[0].cancel()
                await push_a.close()
                await push_b.close()
                await tcp_player.close()
                await app.stop()
                udp_rx_at_migration = udp_rx[0]
                tcp_rx_at_migration = len(tcp_seqs)
                app = await start_server()
                if app.registry.find("/live/b") is None:
                    failures.append("migration: /live/b not restored")
                if not app._pending_tcp:
                    failures.append("migration: no kind=tcp record "
                                    "parked for re-attach")
                # TCP player re-attaches FIRST (old Session id), then
                # the pushers resume their numbering
                tcp_player = RtspClient()
                await tcp_player.connect("127.0.0.1", rtsp_port)
                tcp_player.session_id = old_sid
                await tcp_player.play_start(f"{base}/live/b", tcp=True)
                tasks[0] = asyncio.ensure_future(tcp_drain(tcp_player))
                push_a, push_b = await connect_pushers(app)
                await aget("/api/v1/starthls?path=/live/a")
        await asyncio.sleep(0.5)
    finally:
        for t in tasks:
            t.cancel()
        try:
            _st, _body, _e = await aget("/metrics")
            metrics = parse_metrics(_body.decode())
        except Exception:
            metrics = {}
        try:
            await tcp_player.close()
            await udp_player.close()
            await push_a.close()
            await push_b.close()
        except Exception:
            pass
        await app.stop()
        u_rtp.close()
        u_rtcp.close()

    # ---- verdicts ------------------------------------------------------
    if not migrated:
        failures.append("migration never ran (duration too short)")
    if len(tcp_seqs) < 50:
        failures.append(f"starved TCP player: {len(tcp_seqs)} pkts")
    if len(tcp_seqs) - tcp_rx_at_migration < 10:
        failures.append("TCP session dropped: no packets after the "
                        "migration re-attach")
    if udp_rx[0] - udp_rx_at_migration < 10:
        failures.append("UDP subscriber starved after hot-restore")
    if len(tcp_ssrcs) != 1:
        failures.append(f"TCP player saw {len(tcp_ssrcs)} ssrcs "
                        "(re-attach lost the subscriber identity)")
    deltas = {(b - a) & 0xFFFF for a, b in zip(tcp_seqs, tcp_seqs[1:])}
    if not deltas <= {1}:
        failures.append(f"TCP seq gap/dup across migration: "
                        f"{sorted(deltas)[:8]}")
    mm = metrics.get("megabatch_wire_mismatch_total", 0.0)
    if mm:
        failures.append(f"megabatch_wire_mismatch_total = {mm}")
    tcp_fast = sum(v for k, v in metrics.items()
                   if k.startswith("tcp_egress_packets_total")
                   and 'backend="buffered"' not in k)
    if tcp_fast <= 0:
        failures.append("zero engine-path TCP packets (framed "
                        "writev/io_uring rung never served)")
    if hls_state["segment_bytes"] <= 0:
        failures.append("HLS audience never received a segment")
    if hls_state["etag_304"] <= 0:
        failures.append("HLS ETag revalidation never short-circuited")
    hls_bytes = sum(v for k, v in metrics.items()
                    if k.startswith("hls_segment_egress_bytes_total"))
    if hls_bytes <= 0:
        failures.append("hls_segment_egress_bytes_total never moved")

    stats = {
        "tcp_pkts": len(tcp_seqs), "udp_pkts": udp_rx[0],
        "tcp_pkts_post_migration": len(tcp_seqs) - tcp_rx_at_migration,
        "engine_tcp_pkts": tcp_fast,
        "hls_segment_bytes": hls_state["segment_bytes"],
        "hls_etag_304": hls_state["etag_304"],
        "wire_mismatches": mm,
    }
    print("MIXED STATS", json_mod.dumps(stats))
    if failures:
        print("SOAK MIXED FAILURES:")
        for f_ in failures:
            print(f"  - {f_}")
        return 1
    print("SOAK MIXED OK")
    return 0


def _parse_args(argv: list[str]):
    import argparse
    ap = argparse.ArgumentParser(
        description="integration soak (see module docstring)")
    ap.add_argument("--duration", type=float, default=None,
                    metavar="SECONDS", help="soak length (default 120)")
    ap.add_argument("--sources", type=int, default=16, metavar="N",
                    help="multi-source megabatch section stream count "
                         "(default 16; < 2 disables the section)")
    ap.add_argument("--devices", type=int, default=1, metavar="N",
                    help="shard the multi-source section's stacked "
                         "passes over an N-device src-axis mesh "
                         "(ISSUE 7); on a 1-device box an 8-virtual-"
                         "device CPU mesh is forced via XLA_FLAGS, and "
                         "the run fails on zero sharded passes or any "
                         "megabatch_wire_mismatch_total > 0")
    ap.add_argument("--egress-backend", default=None,
                    choices=("auto", "io_uring", "gso", "scalar"),
                    metavar="BACKEND",
                    help="force an egress backend rung (ISSUE 8) and "
                         "fail the soak if the effective backend (from "
                         "/metrics egress_backend_info) differs from "
                         "the forced one, or if zerocopy completions "
                         "hide their loopback copy verdicts")
    ap.add_argument("--hls-ladder", type=int, default=0, metavar="N",
                    help="serve an N-rendition requant ladder "
                         "(q6,q12,q18 truncated to N, max 3) on the "
                         "coded pushers end-to-end through the "
                         "segmenter (ISSUE 9); fails on any AU "
                         "shedding, unbounded ladder pending() growth, "
                         "or a nonzero slice-reassembly mismatch "
                         "counter")
    ap.add_argument("--vod", type=int, default=0, metavar="N",
                    help="add N RTSP VOD players seeking across 3 "
                         "synthetic assets served by the segment cache "
                         "through the engine paths (ISSUE 10); fails "
                         "on zero cache hits, any host-oracle wire "
                         "mismatch, or a starved player")
    ap.add_argument("--lossy", type=float, nargs="?", const=8.0,
                    default=0.0, metavar="PCT",
                    help="add a plain-UDP player whose receiver loses "
                         "PCT%% of everything on a seeded schedule "
                         "(default 8), sending honest RRs + RFC 4585 "
                         "NACKs (ISSUE 11); fails on playback gaps "
                         "after FEC/RTX recovery, zero recovered "
                         "packets, RTX budget exhaustion, any parity-"
                         "oracle mismatch, or a closed-loop overhead "
                         "that never tracked the loss")
    ap.add_argument("--dvr", type=int, nargs="?", const=2, default=0,
                    metavar="N",
                    help="add N interleaved time-shift subscribers on "
                         "the armed live push who continuously PAUSE "
                         "and re-PLAY into the past (Range rewinds and "
                         "bookmark resumes, Speed-4 catch-up), plus a "
                         "mid-soak stoprecord whose finalized asset "
                         "must re-open as instant VOD (ISSUE 12); "
                         "fails on forward seq gaps across a catch-up "
                         "join, any window repack on a spilled-asset "
                         "open, a retention budget overrun, zero "
                         "catch-up joins, or a starved player "
                         "(default 2)")
    ap.add_argument("--chaos", type=int, nargs="?", const=7, default=None,
                    metavar="SEED",
                    help="run under a seeded FaultPlan (resilience/"
                         "inject.py) and assert the degradation ladder "
                         "recovers to full service; same seed → same "
                         "injection schedule (default seed 7)")
    ap.add_argument("--mixed", action="store_true",
                    help="combined UDP + interleaved-TCP + HLS audience "
                         "on one server with a mid-run checkpoint "
                         "migration (ISSUE 14): the UDP subscriber "
                         "hot-restores, the TCP player re-attaches with "
                         "its old Session id; fails on any TCP session "
                         "drop, seq gap, megabatch wire mismatch, "
                         "starved player, or an HLS audience whose "
                         "ETag revalidation never fired")
    ap.add_argument("--cluster", type=int, default=0, metavar="N",
                    help="multi-process cluster scenario instead: N "
                         "server processes + mini Redis, subscriber "
                         "churn, a flash-crowd wave, and a seeded "
                         "owner SIGKILL that must recover via live "
                         "session migration (ISSUE 6)")
    ap.add_argument("--composed", type=int, default=0, metavar="N",
                    help="the observatory round (ISSUE 15): N server "
                         "processes + mini Redis with EVERY engine on, "
                         "serving the full mixed workload (live relay "
                         "+ 3-rung HLS ladder + hot/cold VOD with seek "
                         "churn + DVR time-shift + TCP-interleaved + "
                         "one lossy-UDP player) with a flash-crowd "
                         "wave and a mid-run owner SIGKILL; validated "
                         "via /api/v1/fleet (stale-marked dead node, "
                         "zero idle-peer SLO burn, zero wire/oracle "
                         "mismatches), gapless migration, and every "
                         "subscriber's trace stitching across its hops")
    ap.add_argument("--skewed", type=int, default=0, metavar="N",
                    help="load-aware control-plane scenario (ISSUE 13): "
                         "N server processes + mini Redis with ONE "
                         "node's capacity forced low via the "
                         "capacity_spoof fault site, a zipfian stream "
                         "popularity curve and a flash crowd on the "
                         "hottest stream; asserts admission "
                         "refusals/redirects during the crowd, an "
                         "origin→edge relay tree serving the crowd, "
                         "and a gapless proactive rebalance drain")
    # hidden child-process mode (spawned by --cluster / --skewed)
    ap.add_argument("--cluster-node", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--skewed-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--composed-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--fault-plan", default="", help=argparse.SUPPRESS)
    ap.add_argument("--node-id", default="", help=argparse.SUPPRESS)
    ap.add_argument("--redis-port", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("seconds", nargs="?", type=float, default=None,
                    help="legacy positional form of --duration")
    ns = ap.parse_args(argv)
    if ns.duration is not None and ns.seconds is not None:
        ap.error("give --duration or the positional seconds, not both")
    if ns.devices > 1 and ns.sources < 2:
        # the mesh section rides the multi-source section; silently
        # printing SOAK OK without a single sharded pass would be a
        # false validation of a multi-device deployment
        ap.error("--devices requires --sources >= 2 (the mesh section "
                 "is the multi-source section)")
    d = ns.duration if ns.duration is not None else ns.seconds
    ns.duration = 120.0 if d is None else d
    return ns


if __name__ == "__main__":
    _ns = _parse_args(sys.argv[1:])
    # every mode, child modes included, before its first jit
    from easydarwin_tpu import device as _device
    _device.enable_compile_cache()
    if _ns.devices > 1:
        # jax backends have not initialized yet (imports above only
        # DEFINE jitted fns) — force the virtual host-device mesh now
        # unless the environment already provides enough devices
        import os as _os
        _flags = _os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in _flags:
            # widens only the HOST (cpu) platform — a real accelerator
            # fleet is untouched and keeps its own device count
            _os.environ["XLA_FLAGS"] = (
                _flags + " --xla_force_host_platform_device_count="
                f"{max(_ns.devices, 8)}").strip()
    if _ns.cluster_node:
        raise SystemExit(asyncio.run(
            _cluster_node_main(_ns.node_id, _ns.redis_port,
                               _ns.fault_plan, _ns.skewed_child,
                               _ns.composed_child)))
    if _ns.mixed:
        raise SystemExit(asyncio.run(mixed_soak(_ns.duration)))
    if _ns.composed:
        raise SystemExit(asyncio.run(
            composed_soak(_ns.composed, _ns.duration,
                          _ns.chaos if _ns.chaos is not None else 7)))
    if _ns.cluster:
        raise SystemExit(asyncio.run(
            cluster_soak(_ns.cluster, _ns.duration,
                         _ns.chaos if _ns.chaos is not None else 7)))
    if _ns.skewed:
        raise SystemExit(asyncio.run(
            skewed_soak(_ns.skewed, _ns.duration,
                        _ns.chaos if _ns.chaos is not None else 7)))
    raise SystemExit(asyncio.run(soak(_ns.duration, _ns.sources,
                                      _ns.chaos, _ns.devices,
                                      _ns.egress_backend,
                                      _ns.hls_ladder, _ns.vod,
                                      _ns.lossy, _ns.dvr)))
