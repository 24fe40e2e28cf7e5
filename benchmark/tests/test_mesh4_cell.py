"""The four-chip host as a deployment (PR 37): configuration
``relay-16x256-mesh4`` is ``relay-16x256`` served by one process with
``megabatch_devices: 4`` and differs from it in nothing else; its cell
``relay-16x256.genlock-mesh4`` asks for four chips, follows the accepted
cells in every list ``relay-16x256.genlock`` was in, and brings three
entries of its own over an accepted reader; a CPU rehearsal of it on four
host devices shards its passes and prints the contract's last line.

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
from file_checks import BENCH, CELLS, GENLOCK, METRIC_DIR, ROOT, load

from benchmark import readers

CELL, CONFIG = "relay-16x256.genlock-mesh4", "relay-16x256-mesh4"
#: the cell's own entries, in order: name -> (unit, better, source, what
#: the hand-made window below reads)
OWN = {
    "megabatch.shard0_streams_pct.mesh4":
        ("%", "lower", "program_counter", 100 * 136 / 480),
    "megabatch.sharded_streams_pct.mesh4":
        ("%", "higher", "program_counter", 100 * 464 / 480),
    "megabatch.shard0_h2d_ms.mesh4": ("ms", "lower", "program_span", 0.35),
}
#: a GOP of the cell by hand, 30 frame instants of 16 streams: 16 hold
#: one source's IDR (fifteen rows sharded 4, 4, 4, 3 and one row whole on
#: device 0), 14 hold none (sixteen rows, four a shard)
M1 = {
    'megabatch_device_streams_total{device="0"}': 16 * 5 + 14 * 4.0,
    'megabatch_device_streams_total{device="1"}': 30 * 4.0,
    'megabatch_device_streams_total{device="2"}': 30 * 4.0,
    'megabatch_device_streams_total{device="3"}': 16 * 3 + 14 * 4.0,
    "megabatch_sharded_streams_total": 16 * 15 + 14 * 16.0,
    "megabatch_streams_total": 480.0,
    'megabatch_device_phase_seconds_sum{device="0",phase="h2d"}': 0.0105,
    'megabatch_device_phase_seconds_count{device="0",phase="h2d"}': 30.0,
    'megabatch_device_phase_seconds_sum{device="0",phase="d2h"}': 9.0,
    'megabatch_device_phase_seconds_count{device="0",phase="d2h"}': 30.0,
}


def entries():
    return {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}


def test_the_configuration_is_relay_16x256_on_four_chips_and_nothing_else():
    conf = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    base = next(c for c in BENCH["configs"] if c["name"] == "relay-16x256")
    assert conf["reduced"] == base["reduced"] == ["fps_per_source"]
    assert len(conf["source"]) <= 200 and "megabatch_devices = 4" in conf[
        "source"]
    cfg, ref = load(conf["file"]), load(base["file"])
    assert cfg["source"] == conf["source"] and cfg["name"] == CONFIG
    assert "four-chip" in cfg["what"] and "one server process" in cfg["what"]
    assert cfg["server"] == dict(ref["server"], megabatch_devices=4)
    assert set(cfg["assumed"]) == set(ref["assumed"]) | {"megabatch_devices"}
    differs = {k for k in cfg if cfg[k] != ref.get(k)}
    assert differs == {"name", "what", "source", "server", "assumed"}
    for key in ("bucket_delay_ms", "slo_latency_objective_ms"):
        assert cfg["assumed"][key] == ref["assumed"][key]
    # shapes, packet sizes, guarantees and the cut's reason are that file's
    for key in ("sources", "players", "stream", "guarantees", "reduced",
                "reduced_why"):
        assert cfg[key] == ref[key], key


def test_the_cell_asks_for_four_chips_under_the_cap():
    cells = BENCH["workloads"]
    names = [w["name"] for w in cells]
    cell = cells[names.index(CELL)]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "genlock",
                    "chips": 4, "why": cell["why"]}
    assert 0 < len(cell["why"]) <= 200 and "shard" in cell["why"]
    # after the cells accepted before it
    assert set(CELLS) - {CELL} <= set(names[:names.index(CELL)])
    file_checks.check_four_chip_cap(BENCH)
    assert [w["name"] for w in cells if w["chips"] == 4][0] == CELL
    assert file_checks.judged_on(BENCH, CELL) == {
        "delay_p60_ms", "delay_p95_ms", "setup_s"}


def test_the_cell_follows_genlock_in_every_list_it_was_in():
    """The two delays and the twenty per-layer entries that listed
    ``relay-16x256.genlock`` when the cell landed: its name comes after
    the accepted cells', in no list twice."""
    names = [m["name"] for m in BENCH["per_layer"]]
    landed = BENCH["end_to_end"] + BENCH["per_layer"][:names.index(
        next(iter(OWN)))]
    with_genlock = [m for m in landed if GENLOCK in m.get("workloads", [])]
    assert len(with_genlock) == 2 + 20
    for m in with_genlock:
        wl = m["workloads"]
        assert wl.count(CELL) == 1, m["name"]
        assert wl[:wl.index(CELL)] == [c for c in CELLS if c in wl
                                       and c != CELL], m["name"]
    for must in ("megabatch_window_step_roofline",
                 "megabatch.fill_pct.genlock", "compiles_in_window",
                 "device.idle_pct.below_knee"):
        assert CELL in entries()[must]["workloads"]
    # and in no list genlock was not in
    assert [m["name"] for m in landed if CELL in m.get("workloads", [])] \
        == [m["name"] for m in with_genlock]


def test_its_own_entries_are_data_over_an_accepted_reader():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(next(iter(OWN)))
    assert names[at:at + len(OWN)] == list(OWN)
    assert set(file_checks.ACCEPTED) <= set(names[:at])
    for name, (unit, better, source, reads) in OWN.items():
        e = entries()[name]
        assert dict(e, workloads=e["workloads"][:1]) == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "Megabatch scheduler", "moves": "delay_p95_ms",
            "workloads": [CELL]}
        spec = load(f"{METRIC_DIR}{name}.json")
        assert spec["name"] == name and spec["what"]
        assert spec["reader"]["kind"] == "ratio_of_deltas"   # no new code
        assert readers.read(spec, file_checks.silent_ctx(m1=M1)) \
            == pytest.approx(reads)
        # a program without the counters (the parent): nothing, no raise
        assert readers.read(spec, file_checks.silent_ctx()) is None
        assert readers.read(spec, file_checks.silent_ctx(
            m1={"megabatch_streams_total": 480.0})) is None
    # the guard: a mesh that failed to build serves single-device
    guard = load(f"{METRIC_DIR}megabatch.sharded_streams_pct.mesh4.json")
    assert readers.read(guard, file_checks.silent_ctx(m1=dict(
        M1, megabatch_sharded_streams_total=0.0))) == 0.0


def test_debug_run_on_four_host_devices_shards_and_prints_the_last_line():
    """The whole cell on the CPU's four forced devices at a debug size:
    the server builds its mesh, the passes of fifteen and sixteen streams
    are sharded and an IDR's rides one device, nothing builds in the
    window, and every counted per-layer entry of the cell reads."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 37), "--seconds", "4",
         "--debug-size", "16x8", "--trace", "1"],
        capture_output=True, text=True, env=env, timeout=600)
    tail = r.stdout[-3000:] + r.stderr[-3000:]
    assert r.returncode == 0, tail
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0, tail
    assert last["device"]["platform"] == "cpu", tail
    assert last["device"]["count"] == 4, tail
    got = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(OWN) <= set(got), tail
    assert got["megabatch.sharded_streams_pct.mesh4"] > 90, tail
    assert 25 <= got["megabatch.shard0_streams_pct.mesh4"] < 35, tail
    assert got["compiles_in_window"] == 0, tail
    # no four-row pass for one stream: .genlock's own fill
    assert 60 < got["megabatch.fill_pct.genlock"] < 70, tail
    assert got["megabatch.streams_per_pass"] > 8, tail
    assert all(v["value"] <= v["limit"] for v in last["compared"].values())
