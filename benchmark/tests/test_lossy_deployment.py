"""The lossy last mile as a deployment: configuration
``relay-16x256-lossy`` is ``relay-16x256`` with a ``last_mile`` key and
differs from it in nothing else that is served; its cell
``relay-16x256-lossy.paced`` runs ``paced`` with a longer warm-up on one
chip, joins the accepted lists whose end-to-end metric it reports, and
brings entries of its own, one of them over the parity kernel's reader;
the committed cell, run on the CPU at a debug size, is ``correct`` with
its fourteen compared names at 0 and both kinds of repair.

What a later PR appends — a cell, an entry, a cell's name to a list —
follows what is pinned here: order and membership, never a last place.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

import file_checks
from file_checks import BENCH, CELLS, METRIC_DIR, PACED, ROOT, load

from benchmark import parity_kernel, readers

CELL, CONFIG = "relay-16x256-lossy.paced", "relay-16x256-lossy"
LAST_MILE = {"model": "gilbert", "mean_loss": 0.02, "mean_burst_packets": 3,
             "rr_interval_s": 5, "nack": "stamped", "nack_after_ms": 200}
#: the cell's own entries, in order: name -> (unit, better, layer, reader)
OWN = {
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
                                              "trace_op_parity"),
}
#: the accepted entries that move ``delay_p95_ms`` in ``relay-16x256.paced``:
#: the cell reads each as ``<name>.lossy``, after ``delay_p95_ms.lossy``
TWINS = [
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
M0 = {'pump_service_seconds_sum{work_class="fec_parity"}': 1.0,
      "pump_wake_seconds_count": 500.0,
      'fec_parity_pass_seconds_sum{part="device"}': 0.1,
      'fec_parity_pass_seconds_count{part="device"}': 100.0,
      "fec_parity_packets_total": 60.0, "egress_packets_total": 3000.0,
      "qos_thins_total": 0.0, "rtx_giveup_total": 0.0}
M1 = {'pump_service_seconds_sum{work_class="fec_parity"}': 3.5,
      "pump_wake_seconds_count": 1500.0,
      'fec_parity_pass_seconds_sum{part="device"}': 0.42,
      'fec_parity_pass_seconds_count{part="device"}': 500.0,
      "fec_parity_packets_total": 1200.0, "egress_packets_total": 60000.0,
      'rtx_nacked_seqs_total{result="sent"}': 270.0,
      'rtx_nacked_seqs_total{result="giveup"}': 0.0,
      'rtx_nacked_seqs_total{result="withheld"}': 30.0,
      'rtx_nacked_seqs_total{result="evicted"}': 0.0,
      "qos_thins_total": 0.0, "rtx_giveup_total": 0.0}
READS = {"pump.fec_parity_ms_per_wake.lossy": 2.5,
         "fec.parity_pass_ms.lossy": 0.8,
         "fec.parity_per_media_pct.lossy": 2.0,
         "rtx.withheld_pct.lossy": 10.0,
         "qos.thins.lossy": 0.0, "rtx.giveups.lossy": 0.0}


def entries():
    return {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}


def test_the_configuration_is_relay_16x256_behind_a_last_mile():
    conf = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    base = next(c for c in BENCH["configs"] if c["name"] == "relay-16x256")
    assert conf["reduced"] == base["reduced"] == ["fps_per_source"]
    assert len(conf["source"]) <= 200 and "\t" not in conf["source"]
    for cites in ("configs[3]", "RFC 5109", "RFC 4588", "RFC 4585",
                  "RFC 3550", "G.1050"):
        assert cites in conf["source"], cites
    cfg, ref = load(conf["file"]), load(base["file"])
    assert cfg["source"] == conf["source"] and cfg["name"] == CONFIG
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


def test_the_mix_is_paced_with_a_longer_warm_up():
    t, paced = (load(f"{file_checks.TRAFFIC_DIR}{n}.json")
                for n in ("paced-lossy", "paced"))
    assert t["name"] == "paced-lossy" and t["warm_frames"] == 24
    assert set(t) == set(paced)
    assert {k: t[k] for k in t if k not in ("name", "what",
                                            "warm_frames")} == {
        k: paced[k] for k in paced if k not in ("name", "what",
                                                "warm_frames")}


def test_the_cells_tail_is_read_per_layer_with_twins():
    """Judged on ``delay_p60_ms`` and ``setup_s``: its 95th percentile
    and the entries that move it in ``.paced`` are read as twins, each
    over its namesake's reader, moving ``delay_p60_ms``; the namesakes do
    not list the cell."""
    assert file_checks.judged_on(BENCH, CELL) == {"delay_p60_ms", "setup_s"}
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("delay_p95_ms.lossy")
    assert names[at:at + 1 + len(TWINS)] == ["delay_p95_ms.lossy"] + [
        f"{n}.lossy" for n in TWINS]
    assert names.index(list(OWN)[-1]) < at
    e = entries()["delay_p95_ms.lossy"]
    assert (e["moves"], e["workloads"], e["unit"], e["source"]) == (
        "delay_p60_ms", [CELL], "ms", "host_clock")
    assert load(f"{METRIC_DIR}delay_p95_ms.lossy.json")["reader"] == {
        "kind": "harness", "key": "delay_p95_ms"}
    for name in TWINS:
        base, twin = entries()[name], entries()[f"{name}.lossy"]
        assert base["moves"] == "delay_p95_ms" and PACED in base["workloads"]
        assert CELL not in base["workloads"]
        assert twin["moves"] == "delay_p60_ms" and twin["workloads"] == [CELL]
        assert {k: twin[k] for k in ("unit", "better", "source", "layer")} \
            == {k: base[k] for k in ("unit", "better", "source", "layer")}
        assert load(f"{METRIC_DIR}{name}.lossy.json")["reader"] == load(
            f"{METRIC_DIR}{name}.json")["reader"]


def test_the_cell_takes_one_chip_after_the_accepted_cells():
    cells = BENCH["workloads"]
    names = [w["name"] for w in cells]
    cell = cells[names.index(CELL)]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "paced-lossy",
                    "chips": 1, "why": cell["why"]}
    assert 0 < len(cell["why"]) <= 200
    assert set(CELLS) <= set(names[:names.index(CELL)])
    assert names.index("relay-16x256.genlock-mesh4") < names.index(CELL)
    file_checks.check_four_chip_cap(BENCH)
    assert file_checks.judged_on(BENCH, CELL) == {"delay_p60_ms", "setup_s"}


def test_the_cell_follows_paced_in_every_list_whose_metric_it_reports():
    """Every accepted list ``relay-16x256.paced`` is in and whose
    end-to-end metric the cell reports has the cell, after the cells
    accepted before it and in no list twice; it is in no list ``.paced``
    is not in."""
    judged = file_checks.judged_on(BENCH, CELL)
    names = [m["name"] for m in BENCH["per_layer"]]
    landed = BENCH["end_to_end"] + BENCH["per_layer"][:names.index(
        next(iter(OWN)))]
    for m in landed:
        wl = m.get("workloads")
        if wl is None:
            continue
        want = PACED in wl and (m.get("moves") or m["name"]) in judged
        assert wl.count(CELL) == int(want), m["name"]
        if want:
            before = wl[:wl.index(CELL)]
            assert CELL not in before and PACED in before, m["name"]


def test_its_own_entries_read_what_the_window_holds():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(next(iter(OWN)))
    assert names[at:at + len(OWN)] == list(OWN)
    assert set(file_checks.ACCEPTED) <= set(names[:at])
    for name, (unit, better, layer, kind) in OWN.items():
        e = entries()[name]
        assert dict(e, workloads=e["workloads"][:1]) == {
            "name": name, "unit": unit, "better": better,
            "source": e["source"], "layer": layer, "moves": "delay_p60_ms",
            "workloads": [CELL]}
        spec = load(f"{METRIC_DIR}{name}.json")
        assert spec["name"] == name and spec["what"]
        assert spec["reader"]["kind"] == kind
        # a program without the families (the parent): nothing, no raise
        assert readers.read(spec, file_checks.silent_ctx()) is None
        if name in READS:
            assert readers.read(spec, file_checks.silent_ctx(
                m0=M0, m1=M1)) == pytest.approx(READS[name]), name


#: a traced window's reduced trace by hand: two programs of the kernel
TRACE = {"modules": {"fec_parity_window_step": {
    "count": 30, "seconds": 30 * 20e-6, "programs": {
        "11": {"count": 20, "seconds": 20 * 20e-6, "shapes": {
            "u8": [[16, 2048], [2, 16], [2, 2048]],
            "s32": [[256], [512], [2, 16, 2048]]}},
        "12": {"count": 10, "seconds": 10 * 20e-6, "shapes": {
            "u8": [[1, 2048], [16, 2048], [1, 16]]}}}}}}
PEAKS = {"hbm_bytes_per_s": 819e9}


def test_the_parity_kernels_bytes_and_roofline():
    assert parity_kernel.fec_parity_window_step_bytes(16, 2048, 2) == \
        16 * 2048 + 2 * 16 + 2 * 2048 + 768 * 4
    progs = TRACE["modules"]["fec_parity_window_step"]["programs"]
    assert parity_kernel.parity_shape(progs["11"]["shapes"]) == (16, 2048, 2)
    assert parity_kernel.parity_shape(progs["12"]["shapes"]) == (16, 2048, 1)
    # the 3-D product alone names R where the coefficients do not show
    assert parity_kernel.parity_shape({"u8": [[16, 512], [4, 512]],
                                       "s32": [[4, 16, 512]]}) == (16, 512, 4)
    assert parity_kernel.parity_shape({"u8": [[16, 100]]}) is None
    spec = load(f"{METRIC_DIR}fec_parity_window_step_roofline.lossy.json")
    ctx = dict(file_checks.silent_ctx(), trace=TRACE, peaks=PEAKS)
    need = (20 * parity_kernel.fec_parity_window_step_bytes(16, 2048, 2)
            + 10 * parity_kernel.fec_parity_window_step_bytes(16, 2048, 1)
            ) / 819e9
    got = readers.read(spec, ctx)
    assert got == pytest.approx(100 * need / (30 * 20e-6)) and 0 < got < 100
    # a trace without the module, or with shapes it cannot spell
    assert readers.read(spec, dict(ctx, trace={"modules": {}})) is None
    bad = json.loads(json.dumps(TRACE))
    bad["modules"]["fec_parity_window_step"]["programs"]["12"]["shapes"] = {}
    assert readers.read(spec, dict(ctx, trace=bad)) is None


def test_the_committed_cell_on_the_cpu_is_correct_and_repairs_both_ways():
    """The cell as committed, at 4 sources x 16 players: every stamped
    packet held, fourteen compared names at 0, parity and RTX both sent
    and all the reference's, no output thinned, no give-up, nothing
    built in the window, and the cell's own counted entries read."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 4201), "--seconds", "10",
         "--debug-size", "4x16", "--trace", "1"],
        capture_output=True, text=True, env=env, timeout=600)
    tail = r.stdout[-4000:] + r.stderr[-2000:]
    assert r.returncode == 0, tail
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0, tail
    assert len(last["compared"]) == 14, tail
    assert all(v["value"] == 0 for v in last["compared"].values()), tail
    lm = last["accounting"]["last_mile"]
    assert lm["parity"] == lm["server_parity"] > 0, tail
    assert lm["rtx"] == lm["server_rtx"] > 0 and lm["recovered_rtx"] > 0
    got = {k: v["value"] for k, v in last["metrics"].items()}
    assert got["qos.thins.lossy"] == 0 and got["rtx.giveups.lossy"] == 0
    assert got["compiles_in_window.lossy"] == 0, tail
    assert got["fec.parity_per_media_pct.lossy"] > 0, tail
    assert got["rtx.withheld_pct.lossy"] == 0, tail
    assert got["fec.parity_pass_ms.lossy"] > 0, tail
    assert got["pump.fec_parity_ms_per_wake.lossy"] > 0, tail
