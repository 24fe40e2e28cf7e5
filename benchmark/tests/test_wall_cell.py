"""The camera wall's cell (PR 30): `wall-256x4.paced` hangs together,
its thirteen per-layer files each name a reader that exists and read a
number from a hand-made window, nothing from a program that lacks the
families (the parent), and a CPU rehearsal at a debug size prints the
contract's last line with the cell's own judged metrics.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import readers, reduce_trace, reference  # noqa: E402

CELL = "wall-256x4.paced"
#: name -> (reader kind, better, what the hand-made window reads)
WALL = {
    "ingest.us_per_packet.wall": ("ratio_of_deltas", "lower", 21.5),
    "engine.idle_steps_pct.wall": ("ratio_of_deltas", "lower", 92.0),
    "megabatch.fill_pct.wall": ("ratio_of_deltas", "higher", 3.90625),
    "megabatch.streams_per_pass.wall": ("ratio_of_deltas", "higher", 20.0),
    "pump.wake_ms.wall": ("ratio_of_deltas", "lower", 40.0),
    "pump.busy_pct.wall": ("ratio_of_deltas", "lower", 93.75),
    "relay.due_to_wire_p95_ms.wall": ("histogram_quantile", "lower", 47.5),
    "egress.datagrams_per_syscall.wall": ("ratio_of_deltas", "higher",
                                          14.25),
    "device.idle_pct.wall": ("trace_idle", "lower", None),
    "compiles_in_window.wall": ("counter_delta", "lower", 0.0),
    "megabatch_window_step_roofline.wall": ("trace_op", "higher", None),
    "delay_p95_ms.wall": ("harness", "lower", 152.3),
    "rtsp.join_s.wall": ("harness", "lower", 6.4),
}
#: every one moves the cell's judged delay but the join, which is set-up
MOVES = {"rtsp.join_s.wall": "setup_s"}
#: what a 30 s window of the cell grows the server's counters by, made by
#: hand: 750 wakes of 40 ms, 256 steps a wake (92 % idle), 750 passes of
#: 20 streams x 4 packets x 4 viewers in a 64 x 16 x 8 program
M1 = {
    "ingest_interleaved_seconds_total": 1.3244,
    "ingest_interleaved_packets_total": 61600.0,
    'engine_steps_total{result="idle"}': 176640.0,
    'engine_steps_total{result="worked"}': 15360.0,
    'megabatch_cells_total{kind="real"}': 240000.0,
    'megabatch_cells_total{kind="staged"}': 6144000.0,
    "megabatch_streams_total": 15000.0,
    "megabatch_passes_total": 750.0,
    "pump_wake_seconds_sum": 30.0,
    "pump_wake_seconds_count": 750.0,
    'pump_loop_seconds_total{state="wake"}': 30.0,
    'pump_loop_seconds_total{state="sleep"}': 2.0,
    'relay_due_to_wire_seconds_bucket{engine="native",le="0.025"}': 100.0,
    'relay_due_to_wire_seconds_bucket{engine="native",le="0.05"}': 200.0,
    'relay_due_to_wire_seconds_bucket{engine="native",le="+Inf"}': 200.0,
    "egress_packets_total": 246240.0,
    "egress_sendmmsg_calls_total": 17280.0,
    "jax_executables_built_total": 13.0,
}
M0 = {"jax_executables_built_total": 13.0}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")


def ctx(m0=None, m1=None, harness=None, trace=None, peaks=None):
    return {"m0": m0 or {}, "m1": m1 or {}, "harness": harness or {},
            "trace": trace, "peaks": peaks}


def test_the_cell_its_config_and_its_judged_metrics():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "wall-256x4",
                    "traffic": "paced-wall", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    conf = next(c for c in BENCH["configs"] if c["name"] == "wall-256x4")
    assert conf["reduced"] == ["fps_per_source"] and len(conf["source"]) <= 200
    cfg = load(conf["file"])
    assert cfg["source"] == conf["source"] and cfg["reduced"] == conf["reduced"]
    assert cfg["guarantees"] == load(
        "benchmark/configs/relay-16x256.json")["guarantees"]
    assert set(cfg["guarantees"]) == set(reference.GUARANTEES)
    assert cfg["sources"] == 256 and cfg["players"] == {
        "per_source": 4, "transport": "udp", "join_wave": 1,
        "stamped_every": 4}
    assert cfg["server"] == {"tpu_fanout": True, "bucket_delay_ms": 73,
                             "tpu_min_outputs": 1,
                             "slo_latency_objective_ms": 200}
    assert {"stream", "players", "media", "tpu_min_outputs"} <= set(
        cfg["assumed"])
    traffic = load("benchmark/traffic/paced-wall.json")
    assert traffic["fps_per_source"] == 1.5 and traffic["warm_frames"] == 16
    # judged on delay_p60_ms and setup_s: in no other end-to-end list
    listed = {m["name"] for m in BENCH["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert listed == {"delay_p60_ms"}
    assert "workloads" not in next(m for m in BENCH["end_to_end"]
                                   if m["name"] == "setup_s")


def test_the_walls_entries_stand_in_the_issues_order():
    """PR 30's thirteen follow the entries accepted before them, in
    order; what a later PR appends follows these."""
    names = [m["name"] for m in BENCH["per_layer"]]
    start = names.index(next(iter(WALL)))
    assert names[start:start + len(WALL)] == list(WALL)
    for m in BENCH["per_layer"][start:start + len(WALL)]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == MOVES.get(m["name"], "delay_p60_ms")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # and no entry accepted before them took the cell in
    for m in BENCH["per_layer"][:start]:
        assert CELL not in m["workloads"]


@pytest.mark.parametrize("name", list(WALL))
def test_wall_metric_file_reads_a_number(name):
    kind, better, want = WALL[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["better"] == better
    spec = load(f"benchmark/layer_metrics/{name}.json")
    assert spec["name"] == name and spec["what"]
    assert spec["reader"]["kind"] == kind
    assert os.path.exists(os.path.join(ROOT, "benchmark", "readers",
                                       kind + ".py"))
    assert callable(importlib.import_module(
        f"benchmark.readers.{kind}").read)
    # a program that has none of it (the parent): nothing, and no raise
    assert readers.read(spec, ctx()) is None
    trace = reduce_trace.reduce(load(
        "benchmark/tests/data/recorded_trace.json"))
    got = readers.read(spec, ctx(
        M0, M1, {"delay_p95_ms": 152.3, "rtsp.join_s": 6.4}, trace,
        load("benchmark/peaks.json")["devices"]["TPU v5 lite"]))
    if want is None:                        # a share read off the trace
        assert 0 < got < 100
    else:
        assert got == pytest.approx(want)


def test_debug_run_prints_p60_and_setup_and_no_p95():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 30), "--seconds", "4",
         "--debug-size", "8x4", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=600)
    tail = r.stdout[-3000:] + r.stderr[-3000:]
    assert r.returncode == 0, tail
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"], tail
    assert list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0, tail
    assert set(last["metrics"]) == {"delay_p60_ms", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert all(v["value"] <= v["limit"] for v in last["compared"].values())
    # every stream went down the device path, none down the scalar loop
    assert ('relay_ingest_to_wire_seconds_count{engine="scalar"} +0'
            in r.stdout), tail
    assert "jax_executables_built_total +0" in r.stdout, tail
