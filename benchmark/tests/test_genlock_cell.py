"""The generator's ``frame_phase`` key and the cell it opened (PR 34),
``relay-16x256.genlock``: with ``"locked"`` every source's frame f has
one due instant and leaves in source order; with ``"spread"`` and with
no key the plan and the bytes are the parent's (pinned from its code at
5b40a3f for one seed); any other value is refused before a socket is
opened; the cell hangs together and a CPU rehearsal of it prints the
contract's last line.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import hashlib
import json
import os
import subprocess
import sys

import pytest

import file_checks
from file_checks import GENLOCK as CELL, ROOT, load

from benchmark import loadgen
from benchmark.run import EXIT_NO_DEVICE

SEED, SECONDS, N_SRC, N_SUB = 3400000007, 4.0, 16, 16
#: what the parent's ``push_frames`` planned for SEED at relay-16x256 x
#: ``paced``, 4 s: (entries, sha256 of repr(plan), first three, last)
PARENT_WARM = (104, "e39b6002f28fee1471a85910fc82361cbf736318"
               "37727a8c738cf612fb3a8909",
               [(0.0, 0, 0), (0.056818181818181816, 1, 0),
                (0.11363636363636363, 2, 0)], (7.2159090909090899, 15, 7))
PARENT_WINDOW = (71, "05e607e8c5d9405258bacd56a69dbde3a6dbd6dd"
                 "1966002b2ae4d71bf863e579",
                 [(0.0, 0, 8), (0.056818181818181816, 1, 8),
                  (0.11363636363636363, 2, 8)], (3.977272727272727, 6, 12))
#: sha256 over every packet of the sixteen sources, and their count
PARENT_BYTES = ("8b645180730fdeb39fbde177feb208132857948ad5cbd1a83322d0eb"
                "4672948b", 2617)


CFG = load("benchmark/configs/relay-16x256.json")


def traffic(phase):
    """``paced`` with the key at ``phase``; None leaves it out."""
    t = load("benchmark/traffic/paced.json")
    assert "frame_phase" not in t
    if phase is not None:
        t["frame_phase"] = phase
    return t


def generator(phase, seconds=SECONDS, **keys):
    """A generator for its plan and its sources alone: the receivers'
    sockets (544 a generator) are left unopened."""
    with pytest.MonkeyPatch.context() as mp:
        for name in ("BulkDrains", "Flow", "StampReader"):
            mp.setattr(loadgen, name, lambda *a, **kw: None)
        return loadgen.Loadgen(CFG, dict(traffic(phase), **keys), SEED,
                               seconds, N_SRC, N_SUB)


@pytest.fixture(scope="module")
def generators():
    """One generator per value of the key."""
    return {phase: generator(phase) for phase in (None, "spread", "locked")}


def plans(lg):
    W = lg.warm_frames
    return (lg.frame_plan(0, lambda due: due < W / lg.fps),
            lg.frame_plan(W, lambda due: due < SECONDS))


def digest(plan):
    return hashlib.sha256(repr(plan).encode()).hexdigest()


# ------------------------------------------------------------- the key
@pytest.mark.parametrize("phase", [None, "spread"], ids=["no_key", "spread"])
@pytest.mark.parametrize("which", [0, 1], ids=["warm_up", "window"])
def test_spread_plans_what_the_parent_planned(generators, phase, which):
    plan = plans(generators[phase])[which]
    n, sha, head, last = (PARENT_WARM, PARENT_WINDOW)[which]
    assert len(plan) == n and plan[:3] == head and plan[-1] == last
    assert digest(plan) == sha


@pytest.mark.parametrize("which", [0, 1], ids=["warm_up", "window"])
def test_locked_gives_a_frame_one_instant_and_source_order(generators, which):
    lg = generators["locked"]
    plan, spread = plans(lg)[which], plans(generators[None])[which]
    lo = (0, lg.warm_frames)[which]
    by_frame = {}
    for due, i, f in plan:
        assert due == (f - lo) / lg.fps         # one clock, bit for bit
        by_frame.setdefault(f, []).append(i)
    # within an instant the sources leave in source order, and the plan
    # goes frame by frame
    assert plan == sorted(plan, key=lambda e: (e[2], e[1]))
    assert all(ids == sorted(ids) for ids in by_frame.values())
    # the same frames are pushed, only their due instants moved; a window
    # closes on a whole frame of all sixteen, where the spread one is cut
    # at the source whose phase passes the close
    if which == 0:      # the warm-up's staggered coming-on-line stands
        assert sorted((i, f) for _, i, f in plan) == sorted(
            (i, f) for _, i, f in spread)
        assert len(by_frame[0]) < N_SRC == len(by_frame[lg.warm_frames - 1])
    else:
        assert all(len(ids) == N_SRC for ids in by_frame.values())
        assert {(i, f) for _, i, f in spread} <= {(i, f) for _, i, f in plan}


@pytest.mark.parametrize("phase", [None, "spread", "locked"],
                         ids=["no_key", "spread", "locked"])
def test_source_bytes_are_untouched_by_the_key(generators, phase):
    lg = generators[phase]
    h = hashlib.sha256()
    for s in lg.sources:
        for p in s.packets:
            h.update(p)
    assert (h.hexdigest(), sum(len(s.packets) for s in lg.sources)) == \
        PARENT_BYTES


def test_gop_phases_stay_spread_under_lock():
    """Genlock locks frames, not GOPs: over one GOP period the sixteen
    sources' IDRs fall in sixteen different frames, as they do spread."""
    gop, fps = CFG["stream"]["gop_frames"], traffic(None)["fps_per_source"]
    firsts = {}
    for phase in ("spread", "locked"):
        lg = generator(phase, (gop + 2) / fps, warm_frames=2)
        firsts[phase] = [
            next(f for f in range(lg.warm_frames, len(s.frame_start) - 1)
                 if s.frame_start[f + 1] - s.frame_start[f] > 20)
            for s in lg.sources]
    assert firsts["locked"] == firsts["spread"]
    assert len(set(firsts["locked"])) == N_SRC


@pytest.mark.parametrize("value", ["lock", "", "LOCKED", 0, None],
                         ids=repr)
def test_an_unknown_phase_is_refused_before_a_socket(monkeypatch, value):
    def no_socket(*a, **kw):
        raise AssertionError("a socket was asked for")
    monkeypatch.setattr(loadgen, "udp_socket", no_socket)
    t = dict(load("benchmark/traffic/paced.json"), frame_phase=value)
    with pytest.raises(loadgen.LoadgenError, match="frame_phase"):
        loadgen.Loadgen(CFG, t, SEED, SECONDS, N_SRC, N_SUB)


# ------------------------------------------------------------ the cell
@pytest.mark.parametrize("check", file_checks.params("genlock"))
def test_the_cell_in_the_benchmark(check):
    """The cell and its mix; what it is judged on; the lists it joined,
    after the cells accepted before it; its fill entry, found by name
    (file_checks.py: ``check_genlock_*``)."""
    check()


# ------------------------------------------------------------ whole runs
def test_debug_run_of_the_cell_prints_the_contracts_last_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 34), "--seconds", "4",
         "--debug-size", "4x8", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=600)
    tail = r.stdout[-3000:] + r.stderr[-3000:]
    assert r.returncode == 0, tail
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"], tail
    assert list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0, tail
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"delay_p60_ms", "delay_p95_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert all(v["value"] <= v["limit"] for v in last["compared"].values())
    assert len(last["compared"]) == 10
    assert "compared stamped_missing: 0 limit 0" in tail


def test_an_unknown_phase_is_no_result_and_exit_3():
    """Through ``run.py``: NO RESULT, the exit code of a run without a
    device, no JSON line and no server started."""
    code = (
        "import sys\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from benchmark import run\n"
        "load = run.load_json\n"
        "def bent(path):\n"
        "    d = load(path)\n"
        "    if path.endswith('traffic/genlock.json'):\n"
        "        d['frame_phase'] = 'lock'\n"
        "    return d\n"
        "run.load_json = bent\n"
        "run.Server.start = lambda self: sys.exit('a server was started')\n"
        "sys.exit(run.main())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", code, "--workload", CELL, "--seed", "34",
         "--seconds", "1", "--trace", "0", "--debug-size", "2x2"],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == EXIT_NO_DEVICE, r.stdout[-2000:] + r.stderr[-2000:]
    assert "NO RESULT" in r.stdout and "frame_phase 'lock'" in r.stdout
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())
