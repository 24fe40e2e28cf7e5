"""A later PR's cell is data, on one chip or on four (PR 35): a copy of
``BENCHMARK.json``, in memory, gets what such a PR brings — a cell of
four chips appended to ``workloads`` and to the lists of the metrics it
reports, and one per-layer entry appended last with a metric file of its
own — and every file check of this directory passes on it unedited: the
checks the other test modules run on the committed file, from the same
table (``file_checks.cases``).  It is done twice over: on the committed
file, and on a copy on which one such cell has landed already.  The same
checks refuse ``chips: 2``, one four-chip cell over the cap, and a pair
of configuration and mix used twice; ``run.py`` gives a four-chip cell no
result on a host with fewer than four TPUs.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

import file_checks
from file_checks import (BENCH, COMMITTED, GENLOCK, METRIC_DIR, ROOT,
                         Files)

from benchmark import readers
from benchmark import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))


def spec_of(metric):
    """What the new entry's own file would hold: an accepted reader over
    counters no accepted metric reads, so no new code."""
    return {"name": metric,
            "what": "of the streams the mesh path's shards carried, the "
                    "share that rode device 0's",
            "reader": {"kind": "ratio_of_deltas",
                       "num": 'megabatch_device_streams_total{device="0"}',
                       "den": "megabatch_device_streams_total",
                       "scale": 100.0}}


def unused(stem, taken):
    while stem in taken:
        stem = "x" + stem
    return stem


def with_a_new_cell(bench, files, chips=None):
    """``bench`` plus what the next cell's PR brings: a configuration
    (``relay-16x256`` with ``megabatch_devices: 4``, in a file of its
    own) and a cell of it under ``genlock``, under names nothing has, in
    every list ``relay-16x256.genlock`` is in, plus one per-layer entry
    of the cell's own, last.  The cell asks for four chips where the
    driver's cap admits one more such cell, or else for one.  Gives the
    copy, its files, and the cell's and the entry's names."""
    bench = copy.deepcopy(bench)
    cells = bench["workloads"]
    if chips is None:
        four = sum(w["chips"] == 4 for w in cells)
        chips = 4 if four + 1 <= max(1, (len(cells) + 1) // 2) else 1
    cell = unused("x-mesh4", {w["name"] for w in cells})
    accepted = next(c for c in bench["configs"] if c["name"] == "relay-16x256")
    config = unused("x-mesh4", {c["name"] for c in bench["configs"]})
    conf = dict(accepted, name=config,
                file=f"benchmark/configs/{config}.json")
    cfg = copy.deepcopy(files.load(accepted["file"]))
    cfg["name"] = config
    cfg["server"]["megabatch_devices"] = 4
    bench["configs"].append(conf)
    cells.append({"name": cell, "config": config, "traffic": "genlock",
                  "chips": chips,
                  "why": "a later PR's cell: new files and entries"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if GENLOCK in m.get("workloads", []):
            m["workloads"].append(cell)
    metric = unused(f"megabatch.device0_streams_pct.{cell}",
                    {m["name"] for m in bench["per_layer"]})
    bench["per_layer"].append({
        "name": metric, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Megabatch scheduler",
        "moves": "delay_p95_ms", "workloads": [cell]})
    return bench, Files({**files.extra, conf["file"]: cfg,
                         f"{METRIC_DIR}{metric}.json": spec_of(metric)}), \
        cell, metric


#: what the cell is added to: the committed benchmark, and one on which
#: a four-chip cell and its entry have landed already
BASES = {"committed": (BENCH, COMMITTED),
         "a_four_chip_cell_landed": with_a_new_cell(BENCH, COMMITTED)[:2]}


@pytest.fixture(params=list(BASES))
def base(request):
    return BASES[request.param]


@pytest.mark.parametrize("check", file_checks.params("accepted"))
def test_what_was_accepted_leads_every_list(check):
    check()


def test_no_test_module_judges_the_committed_file_itself():
    """An assertion on the loaded benchmark in a test's body judges the
    committed file alone and is never run on the copy: the five accepted
    modules hand it to ``file_checks`` and subscript it nowhere."""
    for module in ("test_benchmark", "test_genlock_cell", "test_span_metrics",
                   "test_new_cell_is_data", "test_wall_cell"):
        with open(os.path.join(HERE, module + ".py")) as f:
            src = f.read()
        for subscript in ("[", ".get("):
            assert "BENCH" + subscript not in src, module


def test_a_four_chip_cell_and_an_entry_appended_pass_every_check(base):
    before = [m["name"] for m in base[0]["end_to_end"] + base[0]["per_layer"]
              if GENLOCK in m.get("workloads", [])]
    bench, files, cell, metric = with_a_new_cell(*base)
    # the two delays and the per-layer entries .genlock is in, and its own
    joined = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if cell in m.get("workloads", [])]
    assert joined == before + [metric] and len(before) >= 2 + 19
    assert joined[:2] == ["delay_p60_ms", "delay_p95_ms"]
    assert bench["per_layer"][-1]["name"] == metric
    assert bench["workloads"][-1]["name"] == cell
    # four chips, unless the four-chip cells there are fill the cap
    n, four = len(bench["workloads"]), sum(
        w["chips"] == 4 for w in base[0]["workloads"])
    assert n == len(base[0]["workloads"]) + 1
    assert bench["workloads"][-1]["chips"] == 4 or four + 1 > max(1, n // 2)
    file_checks.check_everything(bench, files)
    # and the checks did meet the cell and the entry
    ids = [name for part in file_checks.PARTS
           for name, _ in file_checks.cases(part, bench, files)]
    assert f"workload[{cell}]" in ids and f"metric[{metric}]" in ids


#: the tests that judge the committed file, one a module
FILE_TESTS = [
    "test_benchmark.py::test_the_committed_files_hang_together",
    "test_span_metrics.py::test_pr25s_entries_stand_as_accepted",
    "test_wall_cell.py::test_the_cell_and_its_entries_stand_as_accepted",
    "test_genlock_cell.py::test_the_cell_in_the_benchmark",
    "test_new_cell_is_data.py"]


def test_a_later_prs_tree_passes_the_file_tests_unedited(base, tmp_path):
    """The same, on disk: a copy of ``BENCHMARK.json`` and of this
    directory's tree gets the cell, the list memberships, the entry and
    its file, and the tests that judge the committed file — collected
    anew, so parametrised over the copy's cells and entries — pass there
    with no test file changed."""
    bench, files, cell, metric = with_a_new_cell(*base)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    for path, spec in files.extra.items():
        (tmp_path / path).write_text(json.dumps(spec, indent=1))
    tests = tmp_path / "benchmark" / "tests"
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-k", "not test_a_later_prs_tree",     # this test, there
         *(str(tests / t) for t in FILE_TESTS)],
        capture_output=True, text=True, timeout=600, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    tail = r.stdout[-3000:] + r.stderr[-2000:]
    assert r.returncode == 0, tail
    for case in (f"workload[{cell}]", f"metric[{metric}]"):
        assert f"hang_together[{case}] PASSED" in r.stdout, tail
    assert " failed" not in r.stdout.splitlines()[-1], tail


def test_two_chips_are_refused(base):
    bench, files, cell, _ = with_a_new_cell(*base, chips=2)
    with pytest.raises(AssertionError):
        file_checks.check_workload(bench, files, bench["workloads"][-1])
    with pytest.raises(AssertionError):
        file_checks.check_everything(bench, files)
    for w in bench["workloads"][:-1]:
        file_checks.check_workload(bench, files, w)


def test_four_chip_cells_up_to_the_cap_and_not_one_more(base):
    bench = with_a_new_cell(*base)[0]
    cells = bench["workloads"]
    cap = max(1, len(cells) // 2)
    one_chip = [w for w in cells if w["chips"] == 1]
    room = cap - (len(cells) - len(one_chip))
    assert 0 <= room < len(one_chip)
    for w in one_chip[:room]:
        w["chips"] = 4
        file_checks.check_four_chip_cap(bench)
    one_chip[room]["chips"] = 4
    with pytest.raises(AssertionError):
        file_checks.check_four_chip_cap(bench)


@pytest.mark.parametrize("cells, four, ok", [
    (6, 3, True), (6, 4, False), (7, 3, True), (7, 4, False),
    (1, 1, True), (3, 1, True), (3, 2, False), (5, 2, True), (24, 12, True),
    (24, 13, False)])
def test_the_drivers_cap_on_hand_made_cells(cells, four, ok):
    bench = {"workloads": [
        {"name": f"c{k}", "config": "c", "traffic": f"t{k}",
         "chips": 4 if k < four else 1} for k in range(cells)]}
    if ok:
        file_checks.check_four_chip_cap(bench)
    else:
        with pytest.raises(AssertionError):
            file_checks.check_four_chip_cap(bench)


def test_the_same_pair_twice_is_refused(base):
    bench = with_a_new_cell(*base)[0]
    first = bench["workloads"][0]
    bench["workloads"][-1].update(config=first["config"],
                                  traffic=first["traffic"])
    with pytest.raises(AssertionError):
        file_checks.check_four_chip_cap(bench)


def test_an_accepted_entry_moved_or_a_cell_put_first_is_refused(base):
    """What was accepted stays pinned on the copy too: the new cell in
    front of an accepted list's cells, the new entry in front of an
    accepted one, an accepted bound changed — each is refused."""
    bench, files, cell, metric = with_a_new_cell(*base)
    for bend in ("cell_first", "entry_first", "bound", "workloads_first"):
        bent = copy.deepcopy(bench)
        if bend == "cell_first":
            wl = file_checks.entry(bent, "pump.wake_ms.below_knee")["workloads"]
            wl.insert(0, wl.pop(wl.index(cell)))
        elif bend == "entry_first":
            bent["per_layer"].insert(0, bent["per_layer"].pop())
        elif bend == "bound":
            file_checks.entry(bent, "delay_p95_ms")["bound"] = 0.05
        else:
            bent["workloads"].insert(0, bent["workloads"].pop())
        with pytest.raises(AssertionError):
            file_checks.check_everything(bent, files)


def test_the_new_entrys_reader_is_an_accepted_one():
    spec = spec_of("megabatch.device0_streams_pct.x-mesh4")
    ctx = file_checks.silent_ctx(m1={
        'megabatch_device_streams_total{device="0"}': 5.0,
        'megabatch_device_streams_total{device="1"}': 4.0,
        'megabatch_device_streams_total{device="2"}': 4.0,
        'megabatch_device_streams_total{device="3"}': 4.0})
    # an instant's fifteen P frames, four or three a shard, and its
    # IDR's stream alone in shard 0 of a pass of its own
    assert readers.read(spec, ctx) == pytest.approx(100 * 5 / 17)
    assert readers.read(spec, file_checks.silent_ctx()) is None


@pytest.mark.parametrize("reported, ok", [
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, False),
    ({"platform": "tpu", "kind": "TPU v5 lite", "count": 4}, True),
    ({"platform": "cpu", "kind": "cpu", "count": 4}, False),
], ids=["one_tpu", "four_tpus", "four_cpus"])
def test_a_four_chip_cell_wants_four_tpus(monkeypatch, tmp_path, capsys,
                                          reported, ok):
    """``device_ok`` on a four-chip cell; and where it says no, a whole
    ``Run.run`` is NO RESULT and exit 3 before a session is opened: the
    server and the generator are scripted, no socket is made."""
    calls = []

    class ScriptedServer:
        def __init__(self, *a):
            self.device_json = str(tmp_path / "device.json")

        def start(self):
            calls.append("start")

        def wait_boot(self):
            return "scripted listening:"

        def info(self):
            return {"Platform": reported["platform"],
                    "DeviceKind": reported["kind"],
                    "DeviceCount": str(reported["count"])}

        def terminate(self):
            calls.append("terminate")
            return 0

    class ScriptedLoadgen:
        def __init__(self, *a):
            pass

        def start_receivers(self):
            calls.append("receivers")

        def stop_receivers(self):
            calls.append("stopped")

    async def no_drive(self):
        raise RuntimeError("scripted: the sessions would open here")

    bench, files, cell, _ = with_a_new_cell(BENCH, COMMITTED, chips=4)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(bench_run, "ROOT", str(tmp_path))   # benchmark_out
    monkeypatch.setattr(bench_run, "load_json", files.load)
    monkeypatch.setattr(bench_run, "Server", ScriptedServer)
    monkeypatch.setattr(bench_run.loadgen, "Loadgen", ScriptedLoadgen)
    monkeypatch.setattr(bench_run.Run, "drive", no_drive)
    args = bench_run.argparse.Namespace(
        workload=cell, seed=2**31 + 35, seconds=1.0, trace=0,
        debug_size=None, fps=None, control=None, child_script="unused")
    r = bench_run.Run(args, bench)
    assert r.device_ok(reported) is ok
    assert r.run() == bench_run.EXIT_NO_DEVICE
    out = capsys.readouterr().out
    assert "NO RESULT" in out and not any(
        ln.startswith("{") for ln in out.splitlines())
    # torn down either way; only four TPUs get as far as the sessions
    assert calls == ["receivers", "start", "stopped", "terminate"]
    assert ("the cell needs 4 TPU chip(s)" in out) is not ok
    assert ("the sessions would open here" in out) is ok
