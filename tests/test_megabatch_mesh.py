"""Megabatch-on-mesh correctness (ISSUE 7).

The load-bearing guarantee carries over from the single-device
scheduler: wire output — headers + payloads, per-destination order over
real UDP sockets — is byte-identical whether bucket dispatch lands on
one device or is sharded over the (src)-axis mesh, across mixed shapes,
mid-run join, teardown, and UNEVEN stream counts (5 streams over
src=2).  All tests run on the conftest's forced 8-virtual-device CPU
mesh; a 1-device configuration must fall back to the single-device path
with zero ``megabatch_device_*`` children emitted.
"""

import socket

import jax
import numpy as np
import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.parallel.mesh import make_megabatch_mesh
from easydarwin_tpu.relay import pump
from easydarwin_tpu.relay.fanout import TpuFanoutEngine
from easydarwin_tpu.relay.megabatch import MegabatchScheduler
from test_megabatch import VIDEO_SDP, _Wire, _mk_stream, vid_pkt

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
    (None = per-stream stepping, no scheduler)."""
    shapes = [(5, 3, 0), (9, 4, 100), (17, 5, 200)]  # (S, burst, seed)
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
        assert sched.sharded_passes > 0
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
    """5 equal-shape streams over src=2: rows_per=4 puts 4 streams on
    shard 0 and 1 (+3 zero pad rows) on shard 1 — wire bytes identical,
    both shards dispatched, pads install nothing."""
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
    got = np.asarray(sharded_megabatch_step(mesh)(
        jax.device_put(win, sharding), jax.device_put(state, sharding)))
    want = np.asarray(megabatch_window_step(jax.device_put(win), state))
    np.testing.assert_array_equal(got, want)


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
        assert app.pump.megabatch is not None
        assert app.pump.megabatch.mesh is app.pump.mesh
        info = app.server_info()
        assert info["MeshDevices"] == "8"
        assert info["MeshShape"] == "src=8,sub=1,win=1"
        assert info["MeshNonSrcAxisCrossesHosts"] == "0"
        assert "MeshShardedPasses" in info
    finally:
        await app.stop()
