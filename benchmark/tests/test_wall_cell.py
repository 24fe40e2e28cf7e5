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

import file_checks
from file_checks import ROOT, WALL, WALL_CELL as CELL, load

from benchmark import readers, reduce_trace

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


def ctx(m0=None, m1=None, harness=None, trace=None, peaks=None):
    return {"m0": m0 or {}, "m1": m1 or {}, "harness": harness or {},
            "trace": trace, "peaks": peaks}


@pytest.mark.parametrize("check", file_checks.params("wall"))
def test_the_cell_and_its_entries_stand_as_accepted(check):
    """The cell, its configuration and its judged metrics; its thirteen
    entries in the issue's order, the cell leading each one's list
    (file_checks.py: ``check_wall_cell``, ``check_wall_order``)."""
    check()


@pytest.mark.parametrize("name", list(WALL))
def test_wall_metric_file_reads_a_number(name):
    kind, _, want = WALL[name]
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
        "benchmark/tests/data/recorded_trace.json"), chips=1)
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
