"""PR 25's per-layer metrics: each new file loads, names a reader that
exists and matches its ``BENCHMARK.json`` entry; the new reader gives
the known numbers on a small recorded window, and every one of them
nothing on a program that has none of the families.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import readers  # noqa: E402

#: PR 34 appended its cell to every list ``relay-16x256.paced`` is in
GENLOCK = "relay-16x256.genlock"
BELOW = ["relay-16x256.paced", "relay-1x64.live", GENLOCK]
ABOVE = ["relay-16x256.saturated"]
#: name -> (cells, moves, better, reader kind)
NEW = {
    "relay.due_to_wire_ms.below_knee":
        (BELOW, "delay_p95_ms", "lower", "ratio_of_deltas"),
    "relay.due_to_wire_p95_ms.below_knee":
        (BELOW, "delay_p95_ms", "lower", "histogram_quantile"),
    "pump.wake_ms.below_knee":
        (BELOW, "delay_p95_ms", "lower", "ratio_of_deltas"),
    "pump.wake_ms.above_knee":
        (ABOVE, "delivered_per_s", "lower", "ratio_of_deltas"),
    "pump.busy_pct.below_knee":
        (BELOW, "delay_p95_ms", "lower", "ratio_of_deltas"),
    "pump.busy_pct.above_knee":
        (ABOVE, "delivered_per_s", "lower", "ratio_of_deltas"),
    "engine.due_outputs_pct.below_knee":
        (BELOW, "delay_p95_ms", "higher", "ratio_of_deltas"),
    "egress.bracket_ms_per_step.above_knee":
        (ABOVE, "delivered_per_s", "lower", "ratio_of_deltas"),
    "pump.live_relay_ms_per_wake.below_knee":
        (BELOW, "delay_p95_ms", "lower", "ratio_of_deltas"),
    "pump.live_relay_ms_per_wake.above_knee":
        (ABOVE, "delivered_per_s", "lower", "ratio_of_deltas"),
    "pump.megabatch_ms_per_wake.below_knee":
        ([BELOW[0], GENLOCK], "delay_p95_ms", "lower", "ratio_of_deltas"),
    "pump.megabatch_ms_per_wake.above_knee":
        (ABOVE, "delivered_per_s", "lower", "ratio_of_deltas"),
    "egress.bracket_ms_per_wake.below_knee":
        (BELOW, "delay_p95_ms", "lower", "ratio_of_deltas"),
    "pump.timer_wakes_pct.below_knee":
        (BELOW, "delay_p95_ms", "higher", "ratio_of_deltas"),
}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")
WINDOW = load("benchmark/tests/data/metrics_window.json")


def ctx(**window):
    return {"m0": window.get("m0", {}), "m1": window.get("m1", {}),
            "harness": {}, "trace": None, "peaks": None}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_matches_its_entry(name):
    cells, moves, better, kind = NEW[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == cells and entry["moves"] == moves
    assert entry["better"] == better
    spec = load(f"benchmark/layer_metrics/{name}.json")
    assert spec["name"] == name and spec["reader"]["kind"] == kind
    assert spec["what"]
    assert callable(importlib.import_module(
        f"benchmark.readers.{kind}").read)
    # a program that has none of it (the parent): nothing, and no raise
    assert readers.read(spec, ctx()) is None


def test_new_entries_come_last_and_the_old_ones_stand():
    """PR 25's fourteen follow the twelve before them, in order; what a
    later PR appends follows these."""
    names = [m["name"] for m in BENCH["per_layer"]]
    start = names.index("relay.due_to_wire_ms.below_knee")
    assert names[start:start + len(NEW)] == [
        "relay.due_to_wire_ms.below_knee",
        "relay.due_to_wire_p95_ms.below_knee",
        "pump.wake_ms.below_knee", "pump.wake_ms.above_knee",
        "pump.busy_pct.below_knee", "pump.busy_pct.above_knee",
        "engine.due_outputs_pct.below_knee",
        "egress.bracket_ms_per_step.above_knee",
        "pump.live_relay_ms_per_wake.below_knee",
        "pump.live_relay_ms_per_wake.above_knee",
        "pump.megabatch_ms_per_wake.below_knee",
        "pump.megabatch_ms_per_wake.above_knee",
        "egress.bracket_ms_per_wake.below_knee",
        "pump.timer_wakes_pct.below_knee"]
    assert names[:start] == [
        "loadgen.late_p99_ms", "rtsp.join_s", "pump.step_ms.below_knee",
        "pump.step_ms.above_knee", "megabatch.streams_per_pass",
        "egress.us_per_datagram", "egress.datagrams_per_syscall",
        "compiles_in_window", "megabatch_window_step_roofline",
        "device.idle_pct.below_knee", "device.idle_pct.above_knee",
        "pdv_p95_ms"]


def quantile(q, **window):
    return readers.read({"reader": {
        "kind": "histogram_quantile", "family": "relay_due_to_wire_seconds",
        "q": q, "scale": 1000.0}}, ctx(**window))


def test_histogram_quantile_on_the_recorded_window():
    # growth over the window, both engines summed: 40 in (0, 50 ms],
    # 30 in (50, 100], 20 in (100, 250], 8 in (250, 500], 100 in
    # (500, 1000], 2 beyond — 200 in all
    assert quantile(0.10, **WINDOW) == pytest.approx(25.0)     # 20 of 40
    assert quantile(0.20, **WINDOW) == pytest.approx(50.0)
    assert quantile(0.275, **WINDOW) == pytest.approx(75.0)    # 15 of 30
    assert quantile(0.95, **WINDOW) == pytest.approx(960.0)    # 92 of 100
    assert quantile(0.995, **WINDOW) == pytest.approx(1000.0)  # in +Inf
    # what was there before the window does not count
    assert quantile(0.5, m0=WINDOW["m1"], m1=WINDOW["m1"]) is None
    assert quantile(0.5) is None


def test_histogram_quantile_agrees_with_the_programs_own():
    from easydarwin_tpu.obs.metrics import Histogram
    h = Histogram("t_seconds", "t", labels=("engine",))
    for i in range(1, 400):
        h.observe(i * 0.0013, engine="native" if i % 3 else "scalar")
    m1 = {}
    for ln in h.expose_lines():
        key, _, val = ln.rpartition(" ")
        m1[key] = float(val)
    for q in (0.5, 0.95, 0.99):
        got = readers.read({"reader": {"kind": "histogram_quantile",
                                       "family": "t_seconds", "q": q}},
                           ctx(m1=m1))
        assert got == pytest.approx(h.quantile(q))


def test_ratio_of_deltas_over_one_labelled_child_and_the_family():
    spec = load("benchmark/layer_metrics/pump.busy_pct.below_knee.json")
    # wake grew 3 s of the 10 s the loop's two states grew together
    assert readers.read(spec, ctx(**WINDOW)) == pytest.approx(30.0)


def test_the_wake_decomposition_on_the_recorded_window():
    def read(name):
        return readers.read(load(f"benchmark/layer_metrics/{name}.json"),
                            ctx(**WINDOW))
    # 20 wakes in the window: 2.4 s of live_relay units, 0.3 s of
    # megabatch units, 1.6 s of egress brackets; 5 of them timer wakes
    assert read("pump.live_relay_ms_per_wake.below_knee") == \
        pytest.approx(120.0)
    assert read("pump.megabatch_ms_per_wake.above_knee") == \
        pytest.approx(15.0)
    assert read("egress.bracket_ms_per_wake.below_knee") == \
        pytest.approx(80.0)
    assert read("pump.timer_wakes_pct.below_knee") == pytest.approx(25.0)
