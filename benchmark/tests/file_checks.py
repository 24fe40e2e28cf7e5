"""What ``BENCHMARK.json`` has had accepted, and the checks that hold
the file to it: the one place in which a test of this directory says
anything of the committed benchmark (PR 35).

Every check takes the loaded benchmark and the ``Files`` it names, so
the same code judges the committed file and a copy that a later PR's
cell and entry were added to (``test_new_cell_is_data.py``).  A test
module takes its share of them from ``params(part)`` and asserts nothing
of the file itself: an assertion made in a test's body would judge the
committed file alone, and a later PR — which may add files and entries
and may edit no file that is here — could not pass it.

What is pinned is order and membership, never a last place or a whole
list: the accepted entries stand first, in their order; an entry's
accepted cells lead its ``workloads``, in their order; what a later PR
appends, an entry, a cell, or a cell's name to a list, follows them.
A rename or a retirement is made here, once.
"""

import functools
import importlib
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import readers, reference  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRIC_DIR = "benchmark/layer_metrics/"
TRAFFIC_DIR = "benchmark/traffic/"


class Files:
    """The files the benchmark names, as committed; ``extra`` maps a
    path to what a later PR would add there, kept in memory."""

    def __init__(self, extra=None):
        self.extra = dict(extra or {})

    def load(self, path):
        if path in self.extra:
            return self.extra[path]
        with open(os.path.join(ROOT, path)) as f:
            return json.load(f)

    def metric_files(self):
        there = os.listdir(os.path.join(ROOT, METRIC_DIR)) + [
            p[len(METRIC_DIR):] for p in self.extra
            if p.startswith(METRIC_DIR)]
        return sorted(f[:-len(".json")] for f in there)


COMMITTED = Files()
load = COMMITTED.load
BENCH = load("BENCHMARK.json")


def reports(metric, cell):
    return cell in metric.get("workloads", [cell])


def judged_on(bench, cell):
    """The end-to-end metrics a ``--trace 0`` line of the cell carries."""
    return {m["name"] for m in bench["end_to_end"] if reports(m, cell)}


def entry(bench, name):
    return next(m for m in bench["end_to_end"] + bench["per_layer"]
                if m["name"] == name)


def silent_ctx(**window):
    """What a reader is given by a program that has none of it."""
    return {"m0": window.get("m0", {}), "m1": window.get("m1", {}),
            "harness": {}, "trace": None, "peaks": None}


# ------------------------------------------- whatever the cell (PR 24)
def check_workload(bench, files, cell):
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = files.load(conf["file"])
    assert cfg["name"] == conf["name"] and cfg["reduced"] == conf["reduced"]
    assert set(cfg["guarantees"]) == set(reference.GUARANTEES)
    traffic = files.load(f"{TRAFFIC_DIR}{cell['traffic']}.json")
    assert traffic["fps_per_source"] > 0 and traffic["warm_frames"] > 0
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    for name in (cell["name"], cell["config"], cell["traffic"]):
        assert NAME.match(name), name
    e2e = judged_on(bench, cell["name"])
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reports(m, cell["name"]) for m in bench["per_layer"])


def check_four_chip_cap(bench, files=None):
    """The driver's cap: of a benchmark's cells at most half, rounded
    down, may ask for four chips, and one always may.  No two cells have
    one name, or one pair of configuration and mix."""
    cells = bench["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2), four
    assert len({w["name"] for w in cells}) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)


def check_metric(bench, files, metric):
    cells = [w["name"] for w in bench["workloads"]]
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    listed = metric.get("workloads", [])
    assert set(listed) <= set(cells) and len(set(listed)) == len(listed)
    if "moves" not in metric:               # end to end
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
        return
    # per layer: moves an end-to-end metric that each of its cells reports
    moved = entry(bench, metric["moves"])
    assert "moves" not in moved
    for cell in metric.get("workloads", cells):
        assert reports(moved, cell), (metric["name"], cell)
    spec = files.load(f"{METRIC_DIR}{metric['name']}.json")
    assert spec["name"] == metric["name"]
    reader = importlib.import_module(
        f"benchmark.readers.{spec['reader']['kind']}")
    assert callable(reader.read)
    # a reader that finds nothing to read returns nothing
    assert readers.read(spec, silent_ctx()) is None, metric["name"]


def check_metric_files(bench, files):
    """A retired metric's file goes with its entry: no reader file is
    left that no cell's line would ever carry."""
    assert files.metric_files() == sorted(
        m["name"] for m in bench["per_layer"])


# --------------------------------------------------- what was accepted
PACED, LIVE = "relay-16x256.paced", "relay-1x64.live"
SATURATED, WALL_CELL = "relay-16x256.saturated", "wall-256x4.paced"
GENLOCK = "relay-16x256.genlock"
#: the cells, in ``workloads``' order
CELLS = [PACED, LIVE, SATURATED, WALL_CELL, GENLOCK]
#: the end-to-end metrics and their bounds, in order.  PR 29 set the two
#: delays'; PR 35 took ``delivered_per_s`` down from 0.25
BOUNDS = {"delivered_per_s": 0.15, "delay_p60_ms": 0.15,
          "delay_p95_ms": 0.04, "setup_s": 0.25}

#: PR 24's twelve per-layer entries, less ``pump.step_ms.below_knee``,
#: ``pump.step_ms.above_knee`` and ``egress.us_per_datagram`` (retired,
#: PR 35)
OLD = ["loadgen.late_p99_ms", "rtsp.join_s", "megabatch.streams_per_pass",
       "egress.datagrams_per_syscall", "compiles_in_window",
       "megabatch_window_step_roofline", "device.idle_pct.below_knee",
       "device.idle_pct.above_knee", "pdv_p95_ms"]

#: PR 34 appended its cell to every list ``relay-16x256.paced`` is in;
#: PR 41 took ``relay-1x64.live`` out of the lists that move
#: ``delay_p95_ms`` (``LIVE_TWINS``)
BELOW = [PACED, GENLOCK]
ABOVE = [SATURATED]
#: PR 25's fourteen: name -> (cells, moves, better, reader kind), in the
#: entries' order
PR25 = {
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
        ([PACED, GENLOCK], "delay_p95_ms", "lower", "ratio_of_deltas"),
    "pump.megabatch_ms_per_wake.above_knee":
        (ABOVE, "delivered_per_s", "lower", "ratio_of_deltas"),
    "egress.bracket_ms_per_wake.below_knee":
        (BELOW, "delay_p95_ms", "lower", "ratio_of_deltas"),
    "pump.timer_wakes_pct.below_knee":
        (BELOW, "delay_p95_ms", "higher", "ratio_of_deltas"),
}

#: PR 30's thirteen, the camera wall's: name -> (reader kind, better,
#: what test_wall_cell.py's hand-made window reads), in the entries' order
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
#: every one moves the wall's judged delay but the join, which is set-up
WALL_MOVES = {"rtsp.join_s.wall": "setup_s"}

#: PR 34's one entry of the genlock cell's own
FILL = "megabatch.fill_pct.genlock"
#: every per-layer entry accepted so far, in order: PR 24's, PR 25's,
#: PR 27's one, PR 30's, PR 31's two, PR 33's two, PR 34's one.  What a
#: later PR adds follows them
ACCEPTED = [
    *OLD, *PR25, "engine.plan_rebuilds_per_100_steps.below_knee", *WALL,
    "pump.drain_packets_pct.wall", "pump.drain_rounds_per_wake.wall",
    "pump.stepped_streams_pct.wall", "pump.stepped_streams_pct.below_knee",
    FILL]


def check_accepted_stand(bench, files=None):
    """Cells, end-to-end metrics with their bounds, and per-layer
    entries: what was accepted leads each list, in its order."""
    assert [w["name"] for w in bench["workloads"]][:len(CELLS)] == CELLS
    assert [(m["name"], m["bound"])
            for m in bench["end_to_end"]][:len(BOUNDS)] == list(BOUNDS.items())
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[kind]]
        assert len(set(names)) == len(names), kind


# -------------------------------------------- PR 25's entries (spans)
def check_pr25_entry(bench, files, name):
    """The entry as PR 25 (and PR 34, for the cell it appended) had it
    accepted: its pinned cells lead its list, in order — a later cell
    may follow them."""
    cells, moves, better, kind = PR25[name]
    e = entry(bench, name)
    assert e["workloads"][:len(cells)] == cells
    assert e["moves"] == moves and e["better"] == better
    spec = files.load(f"{METRIC_DIR}{name}.json")
    assert spec["name"] == name and spec["reader"]["kind"] == kind
    assert spec["what"]
    assert callable(importlib.import_module(
        f"benchmark.readers.{kind}").read)
    # a program that has none of it (the parent): nothing, and no raise
    assert readers.read(spec, silent_ctx()) is None


def check_pr25_order(bench, files=None):
    """PR 25's fourteen follow the nine that are left of the twelve
    before them, in order; what a later PR appends follows these."""
    names = [m["name"] for m in bench["per_layer"]]
    start = names.index(next(iter(PR25)))
    assert names[start:start + len(PR25)] == list(PR25)
    assert names[:start] == OLD


# ------------------------------------------------ PR 30's camera wall
def check_wall_cell(bench, files):
    cell = next(w for w in bench["workloads"] if w["name"] == WALL_CELL)
    assert cell == {"name": WALL_CELL, "config": "wall-256x4",
                    "traffic": "paced-wall", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    conf = next(c for c in bench["configs"] if c["name"] == "wall-256x4")
    assert conf["reduced"] == ["fps_per_source"] and len(conf["source"]) <= 200
    cfg = files.load(conf["file"])
    assert cfg["source"] == conf["source"] and cfg["reduced"] == conf["reduced"]
    assert cfg["guarantees"] == files.load(
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
    traffic = files.load(f"{TRAFFIC_DIR}paced-wall.json")
    assert traffic["fps_per_source"] == 1.5 and traffic["warm_frames"] == 16
    # judged on delay_p60_ms and setup_s: in no other end-to-end list
    assert judged_on(bench, WALL_CELL) == {"delay_p60_ms", "setup_s"}
    assert "workloads" not in entry(bench, "setup_s")


def check_wall_order(bench, files=None):
    """PR 30's thirteen follow the entries accepted before them, in
    order, and the cell leads each one's list; what a later PR appends,
    an entry or a cell to a list, follows these."""
    names = [m["name"] for m in bench["per_layer"]]
    start = names.index(next(iter(WALL)))
    assert names[start:start + len(WALL)] == list(WALL)
    for m in bench["per_layer"][start:start + len(WALL)]:
        assert m["workloads"][0] == WALL_CELL
        assert m["workloads"].count(WALL_CELL) == 1
        assert m["moves"] == WALL_MOVES.get(m["name"], "delay_p60_ms")
        assert m["better"] == WALL[m["name"]][1]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # and no entry accepted before them took the cell in
    for m in bench["per_layer"][:start]:
        assert WALL_CELL not in m["workloads"]


# ----------------------------------------------- PR 34's genlock cell
def check_genlock_cell(bench, files):
    cell = next(w for w in bench["workloads"] if w["name"] == GENLOCK)
    assert cell == {"name": GENLOCK, "config": "relay-16x256",
                    "traffic": "genlock", "chips": 1, "why": cell["why"]}
    assert 0 < len(cell["why"]) <= 200
    t = files.load(f"{TRAFFIC_DIR}genlock.json")
    paced = files.load(f"{TRAFFIC_DIR}paced.json")
    assert t["frame_phase"] == "locked" and t["name"] == "genlock"
    # .paced's pace and drains; a warm-up long enough that the sources
    # come on line one or two to an instant (the file's "what" says why)
    assert "frame_phase" not in paced
    assert t["fps_per_source"] == paced["fps_per_source"] == 1.1
    assert t["bulk_drain_procs"] == paced["bulk_drain_procs"] == 4
    assert t["warm_frames"] == 24 and "warm_frames 24" in t["what"]
    assert set(t) == set(paced) | {"frame_phase"}
    assert "stream.phases" in t["what"] and "GOP" in t["what"]
    # the configuration is left as it is and says what the mix overrides
    cfg = files.load("benchmark/configs/relay-16x256.json")
    assert "frame_phase" not in json.dumps(cfg)
    assert "own frame phase" in cfg["stream"]["phases"]


def check_genlock_judged(bench, files=None):
    listed = [m["name"] for m in bench["end_to_end"]
              if reports(m, GENLOCK)]
    assert listed == ["delay_p60_ms", "delay_p95_ms", "setup_s"]
    for name, bound in BOUNDS.items():
        assert entry(bench, name)["bound"] == bound, name


def check_genlock_lists(bench, files=None):
    """The cell is in every list ``.paced`` is in, after the cells that
    were there before it; a later cell may follow it."""
    names = [m["name"] for m in bench["per_layer"]]
    # what stood when the cell was accepted, less what PR 35 retired
    accepted = bench["per_layer"][:names.index(FILL) + 1]
    before = CELLS[:CELLS.index(GENLOCK)]
    with_paced = [m for m in accepted if PACED in m["workloads"]]
    assert len(with_paced) == 18        # 19 less pump.step_ms.below_knee
    for e in with_paced:
        wl = e["workloads"]
        at = wl.index(GENLOCK)
        assert wl.count(GENLOCK) == 1
        assert wl[:at] == [c for c in before if c in wl], e["name"]
        assert reports(entry(bench, e["moves"]), GENLOCK)
    with_cell = [m["name"] for m in accepted if GENLOCK in m["workloads"]]
    assert with_cell == [m["name"] for m in with_paced] + [FILL]


def check_genlock_fill_entry(bench, files):
    e = entry(bench, FILL)
    assert dict(e, workloads=e["workloads"][:1]) == {
        "name": FILL, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Megabatch scheduler",
        "moves": "delay_p95_ms", "workloads": [GENLOCK]}
    spec = files.load(f"{METRIC_DIR}{FILL}.json")
    wall = files.load(f"{METRIC_DIR}megabatch.fill_pct.wall.json")
    assert spec["name"] == e["name"] and spec["what"] != wall["what"]
    assert spec["reader"] == wall["reader"]     # no new reader code
    ctx = silent_ctx(m1={
        'megabatch_cells_total{kind="real"}': 53248.0,
        'megabatch_cells_total{kind="staged"}': 262144.0})
    # 16 streams x 13 packets x 256 viewers of a 16 x 64 x 256 program
    assert readers.read(spec, ctx) == pytest.approx(20.3125)
    assert readers.read(spec, silent_ctx()) is None


# ------------------------------------------ PR 41: the live cell's tail
#: the entries that moved ``delay_p95_ms`` in ``relay-1x64.live`` until
#: PR 41 made the cell's 95th percentile a per-layer metric: each keeps
#: its other cells, and the cell reads it as ``<name>.live``, which moves
#: ``delay_p60_ms``.  ``delay_p95_ms.live`` leads the twins, in this order
LIVE_TWINS = [
    "loadgen.late_p99_ms", "compiles_in_window", "device.idle_pct.below_knee",
    "pdv_p95_ms", "relay.due_to_wire_ms.below_knee",
    "relay.due_to_wire_p95_ms.below_knee", "pump.wake_ms.below_knee",
    "pump.busy_pct.below_knee", "engine.due_outputs_pct.below_knee",
    "pump.live_relay_ms_per_wake.below_knee",
    "egress.bracket_ms_per_wake.below_knee", "pump.timer_wakes_pct.below_knee",
    "engine.plan_rebuilds_per_100_steps.below_knee",
    "pump.stepped_streams_pct.below_knee", "egress.hidden_pct.below_knee",
    "loads_in_window", "build_s_in_window"]


def check_live_judged(bench, files=None):
    """The cell is judged on ``delay_p60_ms`` and ``setup_s``; its 95th
    percentile is read per layer, by the harness, as the end-to-end
    metric has it."""
    assert judged_on(bench, LIVE) == {"delay_p60_ms", "setup_s"}
    assert entry(bench, "delay_p95_ms")["workloads"][:len(BELOW)] == BELOW
    e = entry(bench, "delay_p95_ms.live")
    assert e["moves"] == "delay_p60_ms" and e["workloads"][0] == LIVE
    assert (e["unit"], e["better"], e["source"]) == ("ms", "lower",
                                                     "host_clock")
    spec = files.load(f"{METRIC_DIR}delay_p95_ms.live.json")
    assert spec["reader"] == {"kind": "harness", "key": "delay_p95_ms"}


def check_live_twin(bench, files, name):
    """A twin reads what its namesake reads, in the live cell, and moves
    ``delay_p60_ms``; the namesake no longer lists the cell."""
    e, twin = entry(bench, name), entry(bench, f"{name}.live")
    assert e["moves"] == "delay_p95_ms" and LIVE not in e["workloads"]
    assert twin["moves"] == "delay_p60_ms" and twin["workloads"][0] == LIVE
    assert twin["workloads"].count(LIVE) == 1
    assert {k: twin[k] for k in ("unit", "better", "source", "layer")} == {
        k: e[k] for k in ("unit", "better", "source", "layer")}
    spec = files.load(f"{METRIC_DIR}{name}.live.json")
    assert spec["reader"] == files.load(f"{METRIC_DIR}{name}.json")["reader"]


def check_live_order(bench, files=None):
    """``delay_p95_ms.live`` and the twins follow one another in this
    order, after every entry accepted before them."""
    names = [m["name"] for m in bench["per_layer"]]
    start = names.index("delay_p95_ms.live")
    want = ["delay_p95_ms.live"] + [f"{n}.live" for n in LIVE_TWINS]
    assert names[start:start + len(want)] == want
    assert names.index("pump.routed_streams_pct") < start


# ------------------------------------- PR 42: the lossy deployment
#: moved here from ``test_lossy_deployment.py``'s own tests (PR 45)
LOSSY, LOSSY_CONFIG = "relay-16x256-lossy.paced", "relay-16x256-lossy"
LAST_MILE = {"model": "gilbert", "mean_loss": 0.02, "mean_burst_packets": 3,
             "rr_interval_s": 5, "nack": "stamped", "nack_after_ms": 200}
#: the cell's own entries, in order: name -> (unit, better, layer, reader)
LOSSY_OWN = {
    "pump.fec_parity_ms_per_wake.lossy": ("ms", "lower", "Pump",
                                          "ratio_of_deltas"),
    "fec.parity_pass_ms.lossy": ("ms", "lower", "Reliability tier",
                                 "ratio_of_deltas"),
    "fec.parity_per_media_pct.lossy": ("%", "lower", "Reliability tier",
                                       "ratio_of_deltas"),
    "rtx.withheld_pct.lossy": ("%", "lower", "Reliability tier",
                               "ratio_of_deltas"),
    "qos.thins.lossy": ("thins", "lower", "Reliability tier",
                        "counter_delta"),
    "rtx.giveups.lossy": ("seqs", "lower", "Reliability tier",
                          "counter_delta"),
    "fec_parity_window_step_roofline.lossy": ("%", "higher", "Kernel",
                                              "trace_op"),
}
#: the accepted entries that move ``delay_p95_ms`` in ``relay-16x256.paced``:
#: the cell reads each as ``<name>.lossy``, after ``delay_p95_ms.lossy``
LOSSY_TWINS = [
    "loadgen.late_p99_ms", "megabatch.streams_per_pass", "compiles_in_window",
    "megabatch_window_step_roofline", "device.idle_pct.below_knee",
    "pdv_p95_ms", "relay.due_to_wire_ms.below_knee",
    "relay.due_to_wire_p95_ms.below_knee", "pump.wake_ms.below_knee",
    "pump.busy_pct.below_knee", "engine.due_outputs_pct.below_knee",
    "pump.live_relay_ms_per_wake.below_knee",
    "pump.megabatch_ms_per_wake.below_knee",
    "egress.bracket_ms_per_wake.below_knee",
    "pump.timer_wakes_pct.below_knee",
    "engine.plan_rebuilds_per_100_steps.below_knee",
    "pump.stepped_streams_pct.below_knee",
    "megabatch.walked_pairs_pct.below_knee", "egress.hidden_pct.below_knee",
    "loads_in_window", "build_s_in_window"]
#: a window of the cell by hand: 1,000 wakes, 400 parity passes, 57,000
#: media datagrams and 1,140 parity, 300 NACKed seqs of which 30 withheld
LOSSY_M0 = {'pump_service_seconds_sum{work_class="fec_parity"}': 1.0,
            "pump_wake_seconds_count": 500.0,
            'fec_parity_pass_seconds_sum{part="device"}': 0.1,
            'fec_parity_pass_seconds_count{part="device"}': 100.0,
            "fec_parity_packets_total": 60.0, "egress_packets_total": 3000.0,
            "qos_thins_total": 0.0, "rtx_giveup_total": 0.0}
LOSSY_M1 = {'pump_service_seconds_sum{work_class="fec_parity"}': 3.5,
            "pump_wake_seconds_count": 1500.0,
            'fec_parity_pass_seconds_sum{part="device"}': 0.42,
            'fec_parity_pass_seconds_count{part="device"}': 500.0,
            "fec_parity_packets_total": 1200.0,
            "egress_packets_total": 60000.0,
            'rtx_nacked_seqs_total{result="sent"}': 270.0,
            'rtx_nacked_seqs_total{result="giveup"}': 0.0,
            'rtx_nacked_seqs_total{result="withheld"}': 30.0,
            'rtx_nacked_seqs_total{result="evicted"}': 0.0,
            "qos_thins_total": 0.0, "rtx_giveup_total": 0.0}
LOSSY_READS = {"pump.fec_parity_ms_per_wake.lossy": 2.5,
               "fec.parity_pass_ms.lossy": 0.8,
               "fec.parity_per_media_pct.lossy": 2.0,
               "rtx.withheld_pct.lossy": 10.0,
               "qos.thins.lossy": 0.0, "rtx.giveups.lossy": 0.0}


def check_lossy_configuration(bench, files):
    """``relay-16x256`` behind a last mile, and nothing else served
    differs."""
    conf = next(c for c in bench["configs"] if c["name"] == LOSSY_CONFIG)
    base = next(c for c in bench["configs"] if c["name"] == "relay-16x256")
    assert conf["reduced"] == base["reduced"] == ["fps_per_source"]
    assert len(conf["source"]) <= 200 and "\t" not in conf["source"]
    for cites in ("configs[3]", "RFC 5109", "RFC 4588", "RFC 4585",
                  "RFC 3550", "G.1050"):
        assert cites in conf["source"], cites
    cfg, ref = files.load(conf["file"]), files.load(base["file"])
    assert cfg["source"] == conf["source"] and cfg["name"] == LOSSY_CONFIG
    assert cfg["last_mile"] == LAST_MILE
    differs = {k for k in set(cfg) | set(ref) if cfg.get(k) != ref.get(k)}
    assert differs == {"name", "what", "source", "last_mile", "assumed"}
    # the server is relay-16x256's: its FEC keys are stated, not changed
    assert cfg["server"] == ref["server"]
    assert not any(k.startswith(("fec_", "rtx_")) for k in cfg["server"])
    assert {k: cfg["assumed"][k] for k in ref["assumed"]} == ref["assumed"]
    assert {"mean_loss", "mean_burst_packets", "rr_interval_s",
            "nack_after_ms", "fec_for_every_player",
            "nack_from_stamped_players", "server_fec"} == \
        set(cfg["assumed"]) - set(ref["assumed"])
    # no width is cut: the shapes are relay-16x256's
    for key in ("sources", "players", "stream", "guarantees",
                "reduced", "reduced_why"):
        assert cfg[key] == ref[key], key


def check_lossy_mix(bench, files):
    """``paced`` with a longer warm-up."""
    t, paced = (files.load(f"{TRAFFIC_DIR}{n}.json")
                for n in ("paced-lossy", "paced"))
    assert t["name"] == "paced-lossy" and t["warm_frames"] == 24
    assert set(t) == set(paced)
    skip = ("name", "what", "warm_frames")
    assert {k: t[k] for k in t if k not in skip} == {
        k: paced[k] for k in paced if k not in skip}


def check_lossy_tail(bench, files):
    """Judged on ``delay_p60_ms`` and ``setup_s``: its 95th percentile
    and the entries that move it in ``.paced`` are read as twins, each
    over its namesake's reader, moving ``delay_p60_ms``; the namesakes do
    not list the cell."""
    assert judged_on(bench, LOSSY) == {"delay_p60_ms", "setup_s"}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("delay_p95_ms.lossy")
    assert names[at:at + 1 + len(LOSSY_TWINS)] == ["delay_p95_ms.lossy"] + [
        f"{n}.lossy" for n in LOSSY_TWINS]
    assert names.index(list(LOSSY_OWN)[-1]) < at
    e = entry(bench, "delay_p95_ms.lossy")
    assert (e["moves"], e["workloads"][0], e["unit"], e["source"]) == (
        "delay_p60_ms", LOSSY, "ms", "host_clock")
    assert files.load(f"{METRIC_DIR}delay_p95_ms.lossy.json")["reader"] == {
        "kind": "harness", "key": "delay_p95_ms"}
    for name in LOSSY_TWINS:
        base, twin = entry(bench, name), entry(bench, f"{name}.lossy")
        assert base["moves"] == "delay_p95_ms" and PACED in base["workloads"]
        assert LOSSY not in base["workloads"]
        assert twin["moves"] == "delay_p60_ms"
        assert twin["workloads"][0] == LOSSY
        assert twin["workloads"].count(LOSSY) == 1
        assert {k: twin[k] for k in ("unit", "better", "source", "layer")} \
            == {k: base[k] for k in ("unit", "better", "source", "layer")}
        assert files.load(f"{METRIC_DIR}{name}.lossy.json")["reader"] == \
            files.load(f"{METRIC_DIR}{name}.json")["reader"]


def check_lossy_cell(bench, files=None):
    """One chip, after the cells accepted before it."""
    cells = bench["workloads"]
    names = [w["name"] for w in cells]
    cell = cells[names.index(LOSSY)]
    assert cell == {"name": LOSSY, "config": LOSSY_CONFIG,
                    "traffic": "paced-lossy", "chips": 1, "why": cell["why"]}
    assert 0 < len(cell["why"]) <= 200
    assert set(CELLS) <= set(names[:names.index(LOSSY)])
    assert names.index("relay-16x256.genlock-mesh4") < names.index(LOSSY)


def check_lossy_lists(bench, files=None):
    """Every accepted list ``relay-16x256.paced`` is in and whose
    end-to-end metric the cell reports has the cell, after the cells
    accepted before it and in no list twice; it is in no list ``.paced``
    is not in."""
    judged = judged_on(bench, LOSSY)
    names = [m["name"] for m in bench["per_layer"]]
    landed = bench["end_to_end"] + bench["per_layer"][:names.index(
        next(iter(LOSSY_OWN)))]
    for m in landed:
        wl = m.get("workloads")
        if wl is None:
            continue
        want = PACED in wl and (m.get("moves") or m["name"]) in judged
        assert wl.count(LOSSY) == int(want), m["name"]
        if want:
            before = wl[:wl.index(LOSSY)]
            assert LOSSY not in before and PACED in before, m["name"]


def check_lossy_own_entry(bench, files, name):
    """One of the cell's own entries, in its place, reading what a
    hand-made window holds; silent on a program without the families."""
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(next(iter(LOSSY_OWN)))
    assert names[at:at + len(LOSSY_OWN)] == list(LOSSY_OWN)
    assert set(ACCEPTED) <= set(names[:at])
    unit, better, layer, kind = LOSSY_OWN[name]
    e = entry(bench, name)
    assert dict(e, workloads=e["workloads"][:1]) == {
        "name": name, "unit": unit, "better": better,
        "source": e["source"], "layer": layer, "moves": "delay_p60_ms",
        "workloads": [LOSSY]}
    spec = files.load(f"{METRIC_DIR}{name}.json")
    assert spec["name"] == name and spec["what"]
    assert spec["reader"]["kind"] == kind
    assert readers.read(spec, silent_ctx()) is None
    if name in LOSSY_READS:
        assert readers.read(spec, silent_ctx(
            m0=LOSSY_M0, m1=LOSSY_M1)) == pytest.approx(LOSSY_READS[name])


# --------------------------------------------- PR 45: late joiners
JOINS, JOIN_METRIC = "relay-1x64.joins", "join_first_idr_p90_ms"
#: the join metric's bound (PR 45: from two sets of six on the chip)
JOIN_BOUND = 0.05
#: the mix's ``joins`` group (``benchmark/loadgen.py``)
JOINS_KEY = {"source": 0, "count": 64, "burst_size": [1, 8],
             "within_s": [0.5, 24]}
#: the cell's own entries, in order, each the harness's own number:
#: name -> (unit, better, layer)
JOINS_OWN = {
    "join.play_answer_p90_ms": ("ms", "lower", "RTSP front end"),
    "join.burst_p90_ms": ("ms", "lower", "Per-stream engine"),
    "join.newest_keyframe_pct": ("%", "higher", "Per-stream engine"),
}


def check_joins_cell(bench, files):
    """``relay-1x64`` as it is, under ``live`` with a ``joins`` group, on
    one chip, after the cells accepted before it."""
    names = [w["name"] for w in bench["workloads"]]
    cell = bench["workloads"][names.index(JOINS)]
    assert cell == {"name": JOINS, "config": "relay-1x64",
                    "traffic": "live-joins", "chips": 1, "why": cell["why"]}
    assert 0 < len(cell["why"]) <= 200
    assert names.index(LOSSY) < names.index(JOINS)
    t = files.load(f"{TRAFFIC_DIR}live-joins.json")
    live = files.load(f"{TRAFFIC_DIR}live.json")
    assert t["name"] == "live-joins" and t["joins"] == JOINS_KEY
    assert set(t) == set(live) | {"joins"} and "joins" not in live
    skip = ("name", "what", "joins")
    assert {k: t[k] for k in t if k not in skip} == {
        k: live[k] for k in live if k not in skip}


def check_joins_judged(bench, files=None):
    """Judged on the delays of the audience there before media, the
    joiners' wait for their first picture, and set-up."""
    assert judged_on(bench, JOINS) == {"delay_p60_ms", JOIN_METRIC,
                                       "setup_s"}
    e = entry(bench, JOIN_METRIC)
    assert (e["unit"], e["better"], e["source"], e["bound"]) == (
        "ms", "lower", "host_clock", JOIN_BOUND)
    assert e["workloads"][0] == JOINS and e["workloads"].count(JOINS) == 1
    names = [m["name"] for m in bench["end_to_end"]]
    assert names.index(JOIN_METRIC) >= len(BOUNDS)


def check_joins_lists(bench, files=None):
    """The cell is in every list ``relay-1x64.live`` is in, after the
    cells that were there before it, once; and in no other list but its
    own metric's and its own entries'."""
    cells = [w["name"] for w in bench["workloads"]]
    before = cells[:cells.index(JOINS)]
    for m in bench["end_to_end"] + bench["per_layer"]:
        wl = m.get("workloads")
        if wl is None:
            continue
        if LIVE in wl:
            at = wl.index(JOINS)
            assert wl.count(JOINS) == 1 and LIVE in wl[:at], m["name"]
            assert not set(before) & set(wl[at:]), m["name"]
        elif m["name"] not in (JOIN_METRIC, *JOINS_OWN):
            assert JOINS not in wl, m["name"]


def check_joins_own_entry(bench, files, name):
    """One of the cell's own entries, in its place, over the accepted
    ``harness`` reader; it moves the join's own metric."""
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(next(iter(JOINS_OWN)))
    assert names[at:at + len(JOINS_OWN)] == list(JOINS_OWN)
    assert names.index(list(LOSSY_OWN)[-1]) < at
    unit, better, layer = JOINS_OWN[name]
    e = entry(bench, name)
    assert dict(e, workloads=e["workloads"][:1]) == {
        "name": name, "unit": unit, "better": better, "source": "host_clock",
        "layer": layer, "moves": JOIN_METRIC, "workloads": [JOINS]}
    spec = files.load(f"{METRIC_DIR}{name}.json")
    assert spec["name"] == name and spec["what"]
    assert spec["reader"] == {"kind": "harness", "key": name}
    assert readers.read(spec, silent_ctx()) is None
    assert readers.read(spec, dict(silent_ctx(),
                                   harness={name: 12.5})) == 12.5


# ------------------------------------------------------------ the cases
#: part -> the test module that runs it on the committed file
PARTS = ("files", "spans", "wall", "genlock", "accepted", "live", "lossy",
         "joins")


def cases(part, bench=BENCH, files=COMMITTED):
    """A part's checks, spread over the benchmark's own cells and
    entries: ``(id, call)`` pairs.  A copy with one more cell has one
    more case of each kind."""
    def case(name, check, *args):
        return name, functools.partial(check, bench, files, *args)
    if part == "files":
        return [
            *(case(f"workload[{w['name']}]", check_workload, w)
              for w in bench["workloads"]),
            case("four_chip_cap", check_four_chip_cap),
            *(case(f"metric[{m['name']}]", check_metric, m)
              for m in bench["end_to_end"] + bench["per_layer"]),
            case("metric_files", check_metric_files)]
    if part == "spans":
        return [*(case(f"entry[{n}]", check_pr25_entry, n) for n in PR25),
                case("order", check_pr25_order)]
    if part == "wall":
        return [case("cell", check_wall_cell), case("order", check_wall_order)]
    if part == "genlock":
        return [case("cell", check_genlock_cell),
                case("judged", check_genlock_judged),
                case("lists", check_genlock_lists),
                case("fill_entry", check_genlock_fill_entry)]
    if part == "accepted":
        return [case("stand", check_accepted_stand)]
    if part == "live":
        return [case("judged", check_live_judged),
                *(case(f"twin[{n}]", check_live_twin, n) for n in LIVE_TWINS),
                case("order", check_live_order)]
    if part == "lossy":
        return [case("configuration", check_lossy_configuration),
                case("mix", check_lossy_mix), case("tail", check_lossy_tail),
                case("cell", check_lossy_cell),
                case("lists", check_lossy_lists),
                *(case(f"own[{n}]", check_lossy_own_entry, n)
                  for n in LOSSY_OWN)]
    if part == "joins":
        return [case("cell", check_joins_cell),
                case("judged", check_joins_judged),
                case("lists", check_joins_lists),
                *(case(f"own[{n}]", check_joins_own_entry, n)
                  for n in JOINS_OWN)]
    raise KeyError(part)


def params(part):
    """The part's cases on the committed file, for ``parametrize``."""
    return [pytest.param(call, id=name) for name, call in cases(part)]


def check_everything(bench, files):
    for part in PARTS:
        for _, call in cases(part, bench, files):
            call()
