"""Megabatch-on-mesh correctness (ISSUE 7).

The load-bearing guarantee carries over from the single-device
scheduler: wire output — headers + payloads, per-destination order over
real UDP sockets — is byte-identical whether bucket dispatch lands on
one device or is sharded over the (src)-axis mesh, across mixed shapes,
mid-run join, teardown, and UNEVEN stream counts (5 streams over
src=2).  All tests run on the conftest's forced 8-virtual-device CPU
mesh; a 1-device configuration must fall back to the single-device path
with zero ``megabatch_device_*`` children emitted.

A four-chip host as a deployment (ISSUE 37): the sharded pass is the
kernel under its own name, a pass of one row rides one device, the
closed set is loaded at join under a mesh too, a shard's phases are
spans, and the pump's audit holds under the mesh as off it.
"""

import socket

import jax
import numpy as np
import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.parallel.mesh import make_megabatch_mesh
from easydarwin_tpu.relay import pump
from easydarwin_tpu.relay.fanout import TpuFanoutEngine
from easydarwin_tpu.obs import TRACER
from easydarwin_tpu.relay.megabatch import MegabatchScheduler
from test_megabatch import (VIDEO_SDP, _ServedWorld, _Wire, _mk_stream,
                            _scheduled_run, vid_pkt)

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native core unavailable")
needs_devices = pytest.mark.skipif(len(jax.devices()) < 8,
                                   reason="needs 8 (virtual) devices")


def _device_family_counts() -> tuple[int, int, int]:
    """(passes children-total, streams children-total, phase samples) of
    the mesh families — deltas prove mesh engagement or silence."""
    return (int(obs.MEGABATCH_DEVICE_PASSES.total()),
            int(obs.MEGABATCH_DEVICE_STREAMS.total()),
            int(obs.MEGABATCH_DEVICE_PHASE_SECONDS.total_count()))


def _run_mesh_scenario(mesh, wire: _Wire, send_fd: int):
    """The ISSUE 4 differential scenario (mixed shapes, bucket growth,
    mid-run output join, mid-run stream teardown) under a given mesh
    (False = per-stream stepping, no scheduler).  The first two streams
    share a subscriber pad, so their bucket has two rows and is sharded;
    the third rides a pad alone: a pass of one row, on one device."""
    shapes = [(5, 3, 0), (7, 4, 100), (17, 5, 200)]  # (S, burst, seed)
    streams = [_mk_stream(s, wire.addrs, seed) for s, _, seed in shapes]
    engines = [TpuFanoutEngine(egress_fd=send_fd) for _ in streams]
    sched = MegabatchScheduler(mesh=mesh) if mesh is not False else None
    live = [streams[0]]
    t, seq = 1000, 0
    for wake in range(24):
        if wake == 4:
            live.append(streams[1])
        if wake == 8:
            live.append(streams[2])
        if wake == 12:
            from easydarwin_tpu.relay.output import CollectingOutput
            o = CollectingOutput(ssrc=0xABCD, out_seq_start=77)
            o.native_addr = wire.addrs[0]
            streams[0].add_output(o)
        if wake == 18:
            live.remove(streams[1])
        pairs = [(s, engines[streams.index(s)]) for s in live]
        for s in live:
            _S, burst, _seed = shapes[streams.index(s)]
            for _ in range(burst):
                s.push_rtp(vid_pkt(seq, seq * 90,
                                   nal_type=5 if seq % 25 == 0 else 1), t)
                seq += 1
        pump.wake(pairs, sched, t)
        wire.drain()
        t += 20
    if sched is not None:
        sched.drain()
    wire.drain()
    return streams, engines, sched


@needs_native
@needs_devices
def test_mesh_wire_bytes_identical_to_per_stream():
    """Mixed shapes + join + teardown: the 8-device mesh path delivers
    byte-identical wire output, and actually dispatched sharded."""
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire_a, wire_b = _Wire(6), _Wire(6)
    try:
        _run_mesh_scenario(False, wire_a, send.fileno())
        base = _device_family_counts()
        mesh = make_megabatch_mesh(8)
        assert mesh is not None and int(mesh.devices.size) == 8
        _streams, engines, sched = _run_mesh_scenario(
            mesh, wire_b, send.fileno())
        assert [len(r) for r in wire_a.rx] == [len(r) for r in wire_b.rx]
        for ra, rb in zip(wire_a.rx, wire_b.rx):
            assert ra == rb
        assert sum(len(r) for r in wire_b.rx) > 0
        assert 0 < sched.sharded_passes < sched.passes
        assert sched.mismatches == 0
        assert sum(e.device_param_refreshes for e in engines) == 0
        # mesh families moved; device labels are shard indices
        after = _device_family_counts()
        assert after[0] > base[0] and after[1] > base[1]
        for (dev,) in obs.MEGABATCH_DEVICE_PASSES._values:
            assert dev.isdigit() and int(dev) < 8
    finally:
        wire_a.close()
        wire_b.close()
        send.close()


@needs_native
@needs_devices
def test_mesh_uneven_stream_count_pad_masked():
    """5 equal-shape streams over src=2: the pass is as tall as its rung
    (16 rows, 8 a shard) and its rows are dealt round the devices, 3 on
    shard 0 and 2 on shard 1, the rest zero pad rows — wire bytes
    identical, both shards dispatched, pads install nothing."""
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire_a, wire_b = _Wire(5), _Wire(5)

    def run(mesh, wire):
        streams = [_mk_stream(4, wire.addrs, 10 + i) for i in range(5)]
        engines = [TpuFanoutEngine(egress_fd=send.fileno())
                   for _ in streams]
        sched = MegabatchScheduler(mesh=mesh) if mesh is not False \
            else None
        t, seq = 1000, 0
        for _wake in range(10):
            for s in streams:
                for _ in range(3):
                    s.push_rtp(vid_pkt(seq, seq * 90), t)
                    seq += 1
            pump.wake(list(zip(streams, engines)), sched, t)
            wire.drain()
            t += 20
        if sched is not None:
            sched.drain()
        wire.drain()
        return engines, sched

    try:
        run(False, wire_a)
        passes_base = {k: v for k, v
                       in obs.MEGABATCH_DEVICE_PASSES._values.items()}
        mesh = make_megabatch_mesh(2)
        engines, sched = run(mesh, wire_b)
        for ra, rb in zip(wire_a.rx, wire_b.rx):
            assert ra == rb
        assert sum(len(r) for r in wire_b.rx) > 0
        assert sched.sharded_passes > 0 and sched.mismatches == 0
        # both shards carried real rows (4 streams + 1 stream)
        for dev in ("0", "1"):
            assert obs.MEGABATCH_DEVICE_PASSES._values.get((dev,), 0) \
                > passes_base.get((dev,), 0)
        # the shard that computed each stream's params is recorded
        assert sorted({e.megabatch_shard for e in engines}) == [0, 1]
    finally:
        wire_a.close()
        wire_b.close()
        send.close()


@needs_native
def test_single_device_box_falls_back_silently():
    """make_megabatch_mesh(1) refuses; a scheduler without a mesh takes
    the single-device dispatch and emits ZERO mesh-family children."""
    assert make_megabatch_mesh(1) is None
    assert make_megabatch_mesh(0, devices=jax.devices()[:1]) is None
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire = _Wire(4)
    base = _device_family_counts()
    try:
        _streams, _engines, sched = _run_mesh_scenario(
            None, wire, send.fileno())
        assert sched.sharded_passes == 0
        assert sched.passes > 0
        assert _device_family_counts() == base
    finally:
        wire.close()
        send.close()


@needs_devices
def test_sharded_step_matches_single_device_step():
    """The jitted mesh variant is bit-exact vs megabatch_window_step on
    random windows/state (the scheduler-independent differential)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from easydarwin_tpu.models.relay_pipeline import (
        megabatch_window_step, sharded_megabatch_step)
    from easydarwin_tpu.ops.fanout import STATE_COLS
    from easydarwin_tpu.ops.staging import ROW_STRIDE
    mesh = make_megabatch_mesh(8)
    rng = np.random.default_rng(4)
    win = rng.integers(0, 256, (16, 32, ROW_STRIDE), np.uint8)
    state = rng.integers(0, 2**16, (16, 8, STATE_COLS)).astype(np.uint32)
    sharding = NamedSharding(mesh, P("src", None, None))
    step = sharded_megabatch_step(mesh)
    got = np.asarray(step(
        jax.device_put(win, sharding), jax.device_put(state, sharding)))
    want = np.asarray(megabatch_window_step(jax.device_put(win), state))
    np.testing.assert_array_equal(got, want)
    # one kernel, one name: the module the profiler shows on each plane
    # (``jit_<name>``), whatever places the pass
    names = [fn.lower(jax.ShapeDtypeStruct(win.shape, win.dtype),
                      jax.ShapeDtypeStruct(state.shape, state.dtype)
                      ).as_text().split("module @")[1].split()[0]
             for fn in (step, megabatch_window_step)]
    assert names == ["jit_megabatch_window_step"] * 2


def _by_device(family) -> dict:
    return {int(dev): v for (dev,), v in family._values.items()}


def _genlock_run(mesh, wire: _Wire, send_fd: int, instants: int = 8):
    """Frame-locked sources: at each instant fifteen streams push a P
    frame of 11 packets and one, by turns, an IDR of 30.  The scheduler
    buckets by packet pad, so the fifteen ride one pass and the IDR's
    stream a pass of its own."""
    streams = [_mk_stream(2, wire.addrs[2 * (i % 4):], 300 + i)
               for i in range(16)]
    engines = [TpuFanoutEngine(egress_fd=send_fd) for _ in streams]
    sched = MegabatchScheduler(mesh=mesh)
    pairs = list(zip(streams, engines))
    t, seq = 1000, 0
    for n in range(instants):
        for i, s in enumerate(streams):
            for k in range(30 if i == n % 16 else 11):
                s.push_rtp(vid_pkt(seq, seq * 90,
                                   5 if k == 0 and i == n % 16 else 1), t)
                seq += 1
        pump.wake(pairs, sched, t)
        sched.drain()
        wire.drain()
        t += 20
    return engines, sched


@needs_native
@needs_devices
def test_a_one_stream_bucket_rides_one_device_under_genlock():
    """A genlock-shaped instant over src=4 against a single device: the
    same bytes on every socket in the same order; the fifteen-stream
    bucket is sharded and the one-stream bucket rides the single-device
    program on the mesh's first device — no pass on shards 1-3 for it,
    the cells a pass stages are the single device's, and every pass is
    counted on the device that ran it."""
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire_a, wire_b = _Wire(8), _Wire(8)

    def cells():
        return {k: obs.MEGABATCH_CELLS.value(kind=k)
                for k in ("real", "staged")}

    try:
        c0 = cells()
        _engines, single = _genlock_run(None, wire_a, send.fileno())
        c1 = cells()
        passes0 = _by_device(obs.MEGABATCH_DEVICE_PASSES)
        streams0 = _by_device(obs.MEGABATCH_DEVICE_STREAMS)
        sharded0 = obs.MEGABATCH_SHARDED_STREAMS.value()
        engines, sched = _genlock_run(make_megabatch_mesh(4), wire_b,
                                      send.fileno())
        c2 = cells()
        assert [len(r) for r in wire_a.rx] == [len(r) for r in wire_b.rx]
        for ra, rb in zip(wire_a.rx, wire_b.rx):
            assert ra == rb
        assert sum(len(r) for r in wire_b.rx) == 8 * (15 * 11 + 30) * 2
        assert sched.mismatches == 0 and single.sharded_passes == 0
        # eight instants: a prime of sixteen, then a pass of fifteen and
        # a pass of one each; the prime and the fifteens are sharded
        assert (sched.passes, sched.sharded_passes) == (17, 9)
        assert sched.passes == single.passes
        passes = {d: v - passes0.get(d, 0) for d, v in
                  _by_device(obs.MEGABATCH_DEVICE_PASSES).items()}
        streams = {d: v - streams0.get(d, 0) for d, v in
                   _by_device(obs.MEGABATCH_DEVICE_STREAMS).items()}
        # shards 1-3 ran the sharded passes and nothing else; device 0
        # ran every pass, the one-stream passes whole
        assert {d: passes.get(d, 0) for d in range(4)} == {
            0: 17, 1: 9, 2: 9, 3: 9}
        assert {d: streams.get(d, 0) for d in range(4)} == {
            0: 4 + 8 * (4 + 1), 1: 36, 2: 36, 3: 4 + 24}
        assert obs.MEGABATCH_SHARDED_STREAMS.value() - sharded0 \
            == 16 + 8 * 15
        # fill: what the passes staged is what one device stages
        assert {k: c2[k] - c1[k] for k in c0} == {
            k: c1[k] - c0[k] for k in c0}
        # the device that computed a stream's params is recorded
        assert {e.megabatch_shard for e in engines} <= {0, 1, 2, 3}
        assert (1, 64, 8) in sched._built and (16, 16, 8, 4) in sched._built
        assert sched.stats()["programs"] == len(sched._built)
    finally:
        wire_a.close()
        wire_b.close()
        send.close()


@needs_native
@needs_devices
def test_a_shards_phases_are_spans_and_feed_the_device_histogram():
    """Each shard's upload and fetch is a span in the ring with its
    device and rows, inside ``megabatch.h2d`` / ``megabatch.fetch``;
    ``megabatch_device_phase_seconds`` counts what the spans count; the
    gather and h2d spans say whether the pass was sharded."""
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire = _Wire(8)
    hist = obs.MEGABATCH_DEVICE_PHASE_SECONDS
    phases = {"megabatch.shard_h2d": "h2d", "megabatch.shard_wait":
              "device_step", "megabatch.shard_fetch": "d2h"}
    assert set(phases) <= set(obs.trace.SPANS)
    try:
        TRACER.clear()
        n0 = {(d, ph): hist.count(device=str(d), phase=ph)
              for d in range(4) for ph in phases.values()}
        _engines, sched = _genlock_run(make_megabatch_mesh(4), wire,
                                       send.fileno(), instants=4)
        recs = TRACER.records()
        assert TRACER.dropped_hint == 0
        spans: dict = {}
        for name, _cat, _t0, _dur, _tid, args in recs:
            if name in phases:
                key = (args["device"], phases[name])
                spans[key] = spans.get(key, 0) + 1
        grown = {k: hist.count(device=str(k[0]), phase=k[1]) - v
                 for k, v in n0.items()}
        assert {k: v for k, v in grown.items() if v} == spans
        # four sharded passes of the scheduler's (the prime uploads
        # whole): an upload and a fetch on each of four devices (fifteen
        # rows dealt round them: 4, 4, 4, 3)
        assert [spans[(d, "h2d")] for d in range(4)] == [4] * 4
        assert [spans[(d, "d2h")] for d in range(4)] == [4] * 4
        rows = [a["rows"] for n, *_x, a in recs
                if n == "megabatch.shard_h2d"]
        assert rows == [4, 4, 4, 3] * 4
        assert all("ready" in a for n, *_x, a in recs
                   if n == "megabatch.shard_fetch")
        # each lies inside its parent
        for parent, child in (("megabatch.h2d", "megabatch.shard_h2d"),
                              ("megabatch.fetch", "megabatch.shard_fetch")):
            outer = [(t0, t0 + dur) for n, _c, t0, dur, _t, _a in recs
                     if n == parent]
            for n, _c, t0, dur, _t, _a in recs:
                if n == child:
                    assert any(a <= t0 and t0 + dur <= b for a, b in outer)
        sharded = [a["sharded"] for n, *_x, a in recs
                   if n == "megabatch.gather"]
        assert sorted(sharded) == [0] * 4 + [1] * 4
        assert sched.sharded_passes == 4 + 1        # and the prime
    finally:
        wire.close()
        send.close()


@needs_native
@needs_devices
def test_the_closed_set_is_loaded_at_join_under_a_mesh():
    """A 16-stream roster joined before any media: one wake loads every
    program the mesh can dispatch for it, as many as one device's — the
    single-device one a row alone rides, the sharded one of every other
    rung — and frame instants that stack sixteen streams, fifteen and
    one, two and five IDRs and streams that fell behind build nothing.
    With media flowing a seventeenth stream, past the rung, loads its
    rung's programs one a wake."""
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire = _Wire(8)
    built = obs.JAX_EXECUTABLES_BUILT.total
    try:
        sched = MegabatchScheduler(mesh=make_megabatch_mesh(4))
        w = _ServedWorld(sched, send.fileno())
        for k in range(16):
            w.add(_mk_stream(2, wire.addrs[2 * (k % 4):], 500 + k))
        w.wake()
        want = sched.programs({8: 16})
        assert sched._built == want and sched.passes == 0
        assert want == {(1, p, 8) for p in (16, 64)} | {
            (b, p, 8, 4) for b in (4, 16) for p in (16, 64)}
        assert len(want) == len(sched.members({8: 16}))
        b0 = built()
        for n in range(15):
            fat = {0: (), 1: (n,), 2: (n, n + 1), 3: range(n, n + 5),
                   4: ()}[n % 5]
            for i, s in enumerate(w.streams):
                if n % 5 == 4 and i in (n % 16, (n + 7) % 16):
                    w.push(s, 150)          # fell behind: rows of 64
                else:
                    w.push(s, 30 if i in [f % 16 for f in fat] else 11)
            w.wake()
            wire.drain()
        assert built() == b0 and sched._built == want
        assert sched.sharded_passes > 15 and sched.mismatches == 0
        # media flows: a seventeenth stream is past the rung of sixteen
        w.add(_mk_stream(2, wire.addrs, 599))
        more = sched.programs({8: 17}) - want
        assert more == {(64, p, 8, 4) for p in (16, 64)}
        for n in range(len(more) + 2):
            before = len(sched._built)
            for s in w.streams:
                w.push(s, 11)
            w.wake()
            wire.drain()
            assert len(sched._built) - before <= 1
        assert sched._built == want | more
        b1 = built()
        for s in w.streams:
            w.push(s, 11)
        w.wake()                            # seventeen rows of the 64
        assert built() == b1 and sched.mismatches == 0
    finally:
        wire.close()
        send.close()


@needs_native
@needs_devices
def test_the_audit_and_the_records_hold_under_a_mesh():
    """PR 36's schedule — ingest, a join, a leave, ladder moves, a wake
    deferred, a dispatch that fails, a stream torn down and replaced,
    wakes below ``megabatch_min_streams``, a stream that fell behind —
    under src=4 against one device: ``Pump.audit`` counts nothing in
    any wake (``behind``: a staged head only moves on), the closed set
    holds, and the sockets, the staged heads, the rider counts, the
    passes and the fallback queries are the single device's."""
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire_a, wire_b = _Wire(6), _Wire(6)
    try:
        one = _scheduled_run(MegabatchScheduler(), wire_a, send.fileno(), 37)
        sched = MegabatchScheduler(mesh=make_megabatch_mesh(4))
        four = _scheduled_run(sched, wire_b, send.fileno(), 37)
        assert [len(r) for r in wire_a.rx] == [len(r) for r in wire_b.rx]
        for ra, rb in zip(wire_a.rx, wire_b.rx):
            assert ra == rb
        assert sum(len(r) for r in wire_b.rx) > 2_000
        assert four[0] == one[0] and four[2:] == one[2:]
        assert 0 < sched.sharded_passes < sched.passes
        assert {len(prog) for prog in four[1]} == {3, 4}
    finally:
        wire_a.close()
        wire_b.close()
        send.close()


def test_rows_per_shard_split():
    from easydarwin_tpu.ops.staging import rows_per_shard
    assert rows_per_shard(16, 8) == 2
    assert rows_per_shard(5, 2) == 4       # pow2-padded per-shard block
    assert rows_per_shard(1, 8) == 1       # tiny bucket: 1 row/shard
    assert rows_per_shard(0, 4) == 1
    assert rows_per_shard(17, 8) == 4      # 17 -> ceil 3 -> pow2 4


def test_mesh_families_lint_contract():
    from tools.metrics_lint import (MESH_PHASES, lint_megabatch_devices)
    from easydarwin_tpu.obs.profile import PHASES
    assert set(MESH_PHASES) <= set(PHASES)
    assert lint_megabatch_devices(obs.REGISTRY) == []
    # a device-id STRING label must be rejected (cardinality guard)
    obs.MEGABATCH_DEVICE_PASSES.inc(device="TPU_v5litepod_0")
    try:
        errs = lint_megabatch_devices(obs.REGISTRY)
        assert errs and "shard index" in errs[0]
    finally:
        obs.MEGABATCH_DEVICE_PASSES._values.pop(("TPU_v5litepod_0",), None)


def test_bench_gate_accepts_multichip_schema(tmp_path):
    """--check-only validates the optional extra.multichip section; old
    rounds without it stay valid; broken figures fail."""
    import json

    from tools.bench_gate import check_trajectory, load_trajectory
    good = {"metric": "m", "value": 100.0, "unit": "p/s",
            "vs_baseline": 2.0, "extra": {"multichip": {
                "n_devices": 8, "packets_per_sec": 1000.0,
                "packets_per_sec_per_device": 125.0,
                "scaling_efficiency": 0.12, "sharded_passes": 20,
                "wire_mismatches": 0,
                "device_phase_ms": {"0": {"h2d": 0.2, "d2h": 0.01}}}}}
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"rc": 0, "parsed": good}))
    assert check_trajectory(load_trajectory(tmp_path)) == []
    # a round WITHOUT the section stays valid (pre-mesh history)
    old = {"metric": "m", "value": 100.0, "unit": "p/s",
           "vs_baseline": 2.0}
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"rc": 0, "parsed": old}))
    assert check_trajectory(load_trajectory(tmp_path)) == []
    bad = json.loads(json.dumps(good))
    bad["extra"]["multichip"].update(wire_mismatches=1,
                                     scaling_efficiency=float("nan"),
                                     sharded_passes=0)
    bad["extra"]["multichip"]["device_phase_ms"]["0"]["egress_native"] = 1
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"rc": 0, "parsed": bad}))
    errs = check_trajectory(load_trajectory(tmp_path))
    assert len(errs) >= 4


@needs_native
@needs_devices
async def test_server_builds_mesh_and_surfaces_span():
    """megabatch_devices=8 builds the serving mesh at startup, the lazy
    scheduler inherits it, and getserverinfo carries the mesh→process
    span (the distributed.process_span satellite)."""
    import random

    from easydarwin_tpu.relay.output import CollectingOutput
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       tpu_fanout=True, megabatch_enabled=True,
                       megabatch_devices=8, tpu_min_outputs=2,
                       megabatch_min_streams=2, access_log_enabled=False)
    app = StreamingServer(cfg)
    await app.start()
    try:
        assert app.pump.mesh is not None
        for path, seed in (("/live/a", 1), ("/live/b", 2)):
            sess = app.registry.find_or_create(path, VIDEO_SDP)
            st = sess.streams[1]
            rng = random.Random(seed)
            for _ in range(3):
                o = CollectingOutput(ssrc=rng.getrandbits(32))
                st.add_output(o)
            st.push_rtp(vid_pkt(seed, seed * 90), 1000)
        app._reflect_all()
        app._wake_close()
        assert app.pump.megabatch is not None
        assert app.pump.megabatch.mesh is app.pump.mesh
        info = app.server_info()
        assert info["MeshDevices"] == "8"
        assert info["MeshShape"] == "src=8,sub=1,win=1"
        assert info["MeshNonSrcAxisCrossesHosts"] == "0"
        assert "MeshShardedPasses" in info
    finally:
        await app.stop()
