"""The benchmark's own tests: its files hang together, its arithmetic is
right on hand-made numbers, its trace reduction gives the known numbers
on one small recorded trace, its comparison fails the control, and a
whole run at a debug size on the CPU prints the contract's last line.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import errno
import hashlib
import json
import os
import subprocess
import sys

import pytest

import file_checks
from file_checks import ROOT, UNIT, load

from benchmark import kernels, loadgen, reduce_trace, reference, stats
from benchmark.run import EXIT_NO_DEVICE

HERE = os.path.dirname(os.path.abspath(__file__))


# ------------------------------------------------------------ the files
@pytest.mark.parametrize("check", file_checks.params("files"))
def test_the_committed_files_hang_together(check):
    """Each cell resolves to its files, the four-chip cap holds, each
    metric has its file, its reader and the metric it moves, and no
    metric file is left without an entry (file_checks.py)."""
    check()


# ------------------------------------------------------- the arithmetic
def test_percentile_interpolates_between_order_statistics():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50.5
    assert stats.percentile(v, 95) == pytest.approx(95.05)
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([3, 1, 2], 50) == 2
    # two clusters of equal weight: the median is their midpoint
    assert stats.percentile([10, 11, 12, 80, 81, 82], 50) == 46.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_delay_and_pdv_on_hand_made_stamps():
    # two flows; due at 0 ms and 100 ms; arrivals in ns
    due = [0, 0, 100_000_000, 100_000_000]
    flow_a = stats.flow_delays_ms(
        [10_000_000, 12_000_000, 115_000_000, 130_000_000], due)
    flow_b = stats.flow_delays_ms(
        [83_000_000, 84_000_000, 183_000_000, 283_000_000], due)
    assert flow_a == [10.0, 12.0, 15.0, 30.0]
    assert flow_b == [83.0, 84.0, 83.0, 183.0]
    # PDV: each flow against its own least delay (RFC 5481)
    assert stats.pdv_ms([flow_a, flow_b]) == [0.0, 2.0, 5.0, 20.0,
                                              0.0, 1.0, 0.0, 100.0]
    m = stats.delay_metrics([flow_a, flow_b])
    assert m["delay_p60_ms"] == pytest.approx(83 + 0.2 * 0)  # 4.2 of 0..7
    assert m["delay_p95_ms"] == pytest.approx(84 + 0.65 * 99)
    assert m["pdv_p95_ms"] == pytest.approx(20 + 0.65 * 80)
    assert stats.pdv_ms([[], [5.0]]) == [0.0]


# ------------------------------------------------- the trace reduction
def test_trace_reduction_on_the_recorded_trace():
    events = load("benchmark/tests/data/recorded_trace.json")
    r = reduce_trace.reduce(events, chips=1)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.94330042)
    assert r["busy_s"] == pytest.approx(2.4909e-05)
    idle = 100.0 * (1 - r["busy_s"] / r["window_s"])
    assert idle == pytest.approx(99.99735938, abs=1e-6)
    assert r["device_ops"][0][0] == "program megabatch_window_step"
    assert r["device_ops"][0][1] == pytest.approx(2.5018e-05)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    # two gaps between three program executions, the longest first
    assert [g[1] for g in r["idle_gaps"]] == sorted(
        (g[1] for g in r["idle_gaps"]), reverse=True)
    assert r["idle_gaps"][0][0].endswith("-> convert_element_type")
    mod = r["modules"]["megabatch_window_step"]
    assert mod["count"] == 2
    shapes = sorted(kernels.megabatch_shape(p["shapes"])
                    for p in mod["programs"].values())
    assert shapes == [(2, 16, 256), (16, 16, 256)]
    # bytes the shapes need: window + state in, packed params out
    assert kernels.megabatch_window_step_bytes(2, 16, 256) == (
        2 * 16 * 100 + 2 * 256 * 6 * 4 + 2 * 1025 * 4)
    need = kernels.least_seconds("megabatch_window_step", mod,
                                 {"hbm_bytes_per_s": 819e9},
                                 "hbm_bytes_per_s")
    assert need == pytest.approx((23688 + 189504) / 819e9)
    assert 0 < 100 * need / mod["seconds"] < 100


#: sha256 of ``json.dumps`` of the parent's (35dcbeb) one-chip reduction
PARENT_REDUCTION = ("7fbb119fc0ee01363287c2d39d00b388322573917b75ea3f60ab5905"
                    "e9dac600", 1291)


def test_a_one_chip_reduction_is_the_parents_byte_for_byte():
    events = load("benchmark/tests/data/recorded_trace.json")
    out = json.dumps(reduce_trace.reduce(events, chips=1))
    assert (hashlib.sha256(out.encode()).hexdigest(),
            len(out)) == PARENT_REDUCTION


SHARD = (4, 16, 256)            # a quarter of a 16 x 16 x 256 pass
#: per plane: (start of the sharded pass, its three ops' durations, the
#: start of the small program that follows it); plane 2 is busy longest
PLANES = {0: (1_000_000, (600, 400, 5000), 400_000_000),
          1: (1_000_900, (600, 400, 5000), 300_000_000),
          2: (1_001_800, (600, 400, 29000), 200_000_000),
          3: (1_002_700, (600, 400, 5000), 100_000_000)}
TAIL_NS = 700                   # the small program's one op


def four_planes(ran=(0, 1, 2, 3), keep_empty=False):
    """A hand-made trace of one sharded ``megabatch_window_step``: the
    program runs once on each plane in ``ran`` with the per-shard shapes
    in its ops' names, then a small program of one op; a plane not in
    ``ran`` ran nothing inside the window (left out, or kept with its
    lines empty)."""
    b, p, s = SHARD
    devices = {}
    for k, (t0, durs, t1) in PLANES.items():
        if k not in ran:
            if keep_empty:
                devices[f"/device:TPU:{k}"] = {"ops": [], "modules": [],
                                               "async": []}
            continue
        names = (
            f"%copy-done = u8[{b},{p},100]{{2,1,0:T(8,128)(4,1)S(1)}} "
            f"copy-done(u8[{b},{p},100]{{2,1,0:T(8,128)(4,1)}} %window.1)",
            f"%copy-done.1 = u32[{b},{s},6]{{1,0,2:T(2,128)S(1)}} "
            f"copy-done(u32[{b},{s},6]{{1,0,2:T(2,128)}} %out_state.1)",
            f"%fusion.1 = u32[{b},{4 * s + 1}]{{1,0:T(2,128)}} fusion("
            f"u32[{b},{s},6]{{1,0,2:T(2,128)S(1)}} %copy-done.1)")
        ops, at = [], t0
        for name, d in zip(names, durs):
            ops.append([name, at, d])
            at += d
        ops.append(["%convert.1 = s32[8]{0} convert(u8[8]{0} %p)", t1,
                    TAIL_NS])
        devices[f"/device:TPU:{k}"] = {
            "ops": ops, "async": [],
            "modules": [
                ["jit_megabatch_window_step(4242)", t0, sum(durs),
                 {"program_id": "4242"}],
                ["jit_convert_element_type(77)", t1, TAIL_NS,
                 {"program_id": "77"}]]}
    host = [["pump.wake", 900_000, 200_000],
            ["pump.sleep", 50_000_000, 340_000_000]]
    return {"window_ns": 1_000_000_000, "devices": devices, "host": host}


@pytest.mark.parametrize("ran, keep_empty", [
    ((0, 1, 2, 3), False), ((0, 1, 2), False), ((0, 1, 2), True)],
    ids=["all_four_ran", "one_ran_nothing", "one_ran_nothing_lines_empty"])
def test_a_trace_of_four_chips_reduces_right(ran, keep_empty):
    r = reduce_trace.reduce(four_planes(ran, keep_empty), chips=4)
    assert r["chips"] == 4 and r["window_s"] == 1.0
    # busy: every plane's own union, summed, over FOUR chips: the chip
    # that ran nothing counts as idle and stays in the average
    busy_ns = sum(sum(PLANES[k][1]) + TAIL_NS for k in ran)
    assert r["busy_s"] == pytest.approx(busy_ns / 4 / 1e9)
    mod = r["modules"]["megabatch_window_step"]
    assert mod["count"] == len(ran) and list(mod["programs"]) == ["4242"]
    assert mod["seconds"] == pytest.approx(
        sum(sum(PLANES[k][1]) for k in ran) / 1e9)
    assert kernels.megabatch_shape(
        mod["programs"]["4242"]["shapes"]) == SHARD
    assert r["device_ops"][0] == ["program megabatch_window_step",
                                  pytest.approx(mod["seconds"])]
    # the roofline of a sharded pass is that of the same work: four
    # shards of 4 x 16 x 256 need the bytes of one 16 x 16 x 256 pass
    need = kernels.least_seconds("megabatch_window_step", mod,
                                 {"hbm_bytes_per_s": 819e9},
                                 "hbm_bytes_per_s")
    assert need * 819e9 == pytest.approx(
        len(ran) * kernels.megabatch_window_step_bytes(*SHARD))
    if len(ran) == 4:
        assert need * 819e9 == pytest.approx(
            kernels.megabatch_window_step_bytes(16, 16, 256))
    assert 0 < 100 * need / mod["seconds"] < 100
    # idle gaps are taken per plane: one a plane that ran, between its
    # two programs, the longest first; none spans two planes (plane 1's
    # pass starts 900 ns after plane 0's and is no gap of 0 ns)
    gaps = {k: PLANES[k][2] - (PLANES[k][0] + sum(PLANES[k][1]))
            for k in ran}
    assert [g[1] for g in r["idle_gaps"]] == [
        pytest.approx(ns / 1e9) for ns in sorted(gaps.values(),
                                                 reverse=True)]
    assert [g[0] for g in r["idle_gaps"]] == [
        "pump.sleep -> convert_element_type"] * len(ran)


def test_the_recorded_trace_of_four_chips():
    """A trimmed recording of the mesh path's rehearsal on a four-chip
    host (PR 35: ``relay-16x256`` with ``megabatch_devices: 4`` under
    ``genlock``; 0.91 s holding a frame instant's two passes and the
    next instant's first).  Every plane runs every pass, pad-only
    shards too, and the sharded step is a program of another name."""
    events = load("benchmark/tests/data/recorded_trace_mesh4.json")
    assert sorted(events["devices"]) == [f"/device:TPU:{k}" for k in range(4)]
    r = reduce_trace.reduce(events, chips=4)
    assert r["chips"] == 4 and r["window_s"] == pytest.approx(0.9121)
    # 20,559 + 20,791 + 20,581 + 20,571 ns of ops, over four chips
    assert r["busy_s"] == pytest.approx(2.06255e-05)
    assert list(r["modules"]) == ["relay_affine_step_window"]
    mod = r["modules"]["relay_affine_step_window"]
    assert mod["count"] == 12 and mod["seconds"] == pytest.approx(
        0.000395675)
    # the instant's fifteen P frames as 4 shards of 4 x 16 x 256, twice,
    # and its IDR's one stream padded to 4 shards of 1 x 64 x 256
    assert sorted((p["count"], kernels.megabatch_shape(p["shapes"]))
                  for p in mod["programs"].values()) == [
        (4, (1, 64, 256)), (8, (4, 16, 256))]
    # two gaps a plane, taken per plane: the frame period, and the 3 ms
    # between the instant's two passes
    gaps = [g[1] for g in r["idle_gaps"]]
    assert len(gaps) == 8 and gaps == sorted(gaps, reverse=True)
    assert all(0.9076 < g < 0.9078 for g in gaps[:4])
    assert all(0.0032 < g < 0.0033 for g in gaps[4:])
    assert {g[0] for g in r["idle_gaps"]} == {
        "pump.wake -> relay_affine_step_window"}
    # no row of KERNELS has that name, so the stacked pass's roofline is
    # silent here; under the kernel's name the same shapes read the work
    # of two 16 x 16 x 256 passes and one 4 x 64 x 256 pass
    from benchmark.readers import trace_op
    args = {"module": "megabatch_window_step", "bound": "hbm_bytes_per_s"}
    peaks = load("benchmark/peaks.json")["devices"]["TPU v5 lite"]
    assert trace_op.read(args, {"trace": r, "peaks": peaks}) is None
    renamed = dict(r, modules={"megabatch_window_step": mod})
    need = (2 * kernels.megabatch_window_step_bytes(16, 16, 256)
            + kernels.megabatch_window_step_bytes(4, 64, 256)) / 819e9
    assert trace_op.read(args, {"trace": renamed, "peaks": peaks}) == \
        pytest.approx(100 * need / mod["seconds"])
    assert 0 < 100 * need / mod["seconds"] < 1


def test_module_name_drops_prefix_and_fingerprint():
    assert reduce_trace.module_name(
        "jit_megabatch_window_step(5320593160282135921)"
    ) == "megabatch_window_step"
    assert reduce_trace.union_ns([(0, 10), (5, 10), (30, 5)])[0] == 20


# ------------------------------------------- the reference, the control
def _pushed(n=40):
    from benchmark.loadgen import Source
    shapes = load("benchmark/configs/relay-16x256.json")["stream"]
    return Source(0, 7, 4, 1.0, shapes).packets[:n]


def test_reference_output_is_judged_clean():
    pushed = _pushed()
    flow = reference.reference_flow(pushed, 65530, 0xDEADBEEF, 12345)
    assert reference.judge_flow(flow, pushed, 65530, 0xDEADBEEF) == {
        "missing": 0, "out_of_order": 0, "altered": 0, "unannounced": 0}
    assert flow[0][12:] == pushed[0][12:] and flow[0][:2] == pushed[0][:2]
    assert int.from_bytes(flow[7][2:4], "big") == (65530 + 7) & 0xFFFF


@pytest.mark.parametrize("guarantee", reference.GUARANTEES)
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 77])
def test_control_fails_the_comparison(guarantee, seed):
    """The control: the reference's own output with one stated guarantee
    broken once.  Some count must pass its limit, 0."""
    pushed = _pushed()
    flow = reference.reference_flow(pushed, 100, 0xDEADBEEF, 999)
    broken = reference.break_guarantee(
        flow, guarantee, seed, int.from_bytes(pushed[0][8:12], "big"))
    got = reference.judge_flow(broken, pushed, 100, 0xDEADBEEF)
    assert sum(got.values()) >= 1, (guarantee, got)


def test_a_session_that_announced_nothing_is_not_correct():
    pushed = _pushed(5)
    got = reference.judge_flow(pushed, pushed, None, None)
    assert got["unannounced"] == 1 and got["missing"] == 5


# ------------------------------------------- the receivers' port pairs
class ScriptedPorts:
    """``loadgen.udp_socket`` with the kernel's part scripted: ephemeral
    binds draw from ``draws`` in turn (the last one for good), a bind to
    a port in ``taken`` is EADDRINUSE, and one past 65535 is refused as
    ``socket.bind`` refuses it.  No real port is drawn."""

    class Sock:
        def __init__(self, port):
            self.port, self.closed = port, False

        def getsockname(self):
            return ("127.0.0.1", self.port)

        def close(self):
            self.closed = True

    def __init__(self, draws, taken=()):
        self.draws, self.taken = list(draws), set(taken)
        self.made = []

    def __call__(self, ip, port=0):
        if port == 0:
            port = self.draws.pop(0) if len(self.draws) > 1 else self.draws[0]
        elif port > 65535:
            raise OverflowError("bind(): port must be 0-65535.")
        elif port in self.taken:
            raise OSError(errno.EADDRINUSE, "Address already in use")
        self.made.append(self.Sock(port))
        return self.made[-1]

    def open_ports(self):
        return [s.port for s in self.made if not s.closed]


@pytest.mark.parametrize("draws, taken, pair", [
    ([65535, 40000], (), (40000, 40001)),       # no successor: drawn again
    ([40000, 40002], (40001,), (40002, 40003)),     # successor taken
    ([65535, 65535, 40001], (), (40001, 40002)),    # RTP parity stays free
], ids=["top_of_range", "successor_taken", "twice_the_top"])
def test_udp_pair_draws_again(monkeypatch, draws, taken, pair):
    ports = ScriptedPorts(draws, taken)
    monkeypatch.setattr(loadgen, "udp_socket", ports)
    a, b = loadgen.udp_pair("127.0.0.1")
    assert (a.port, b.port) == pair
    # every rejected socket was closed; the pair is all that is open
    assert ports.open_ports() == list(pair)


def test_udp_pair_gives_up_as_a_loadgen_error(monkeypatch):
    ports = ScriptedPorts([65535])
    monkeypatch.setattr(loadgen, "udp_socket", ports)
    with pytest.raises(loadgen.LoadgenError):    # and no OverflowError
        loadgen.udp_pair("127.0.0.1")
    assert len(ports.made) == 64 and ports.open_ports() == []


@pytest.mark.parametrize("port, fits", [
    (65535, False), (65534, True), (40000, True)])
def test_both_receiver_kinds_reject_the_same_ports(monkeypatch, port, fits):
    """``udp_pair`` and ``_open_port_group`` (which wants an even RTP
    port besides) share the one predicate."""
    assert loadgen.has_successor(port) is fits
    ports = ScriptedPorts([port, 40002])
    monkeypatch.setattr(loadgen, "udp_socket", ports)
    assert loadgen.udp_pair("127.0.0.1")[0].port == (port if fits else 40002)
    ports = ScriptedPorts([port, 40002])
    monkeypatch.setattr(loadgen, "udp_socket", ports)
    bulk = object.__new__(loadgen.BulkDrains)
    bulk.rtp, bulk.rtcp = [], []
    assert bulk._open_port_group() == (port if fits else 40002)
    assert len(bulk.rtp) == len(bulk.rtcp) == loadgen.N_IP


# ---------------------------------------------------------- whole runs
def _run(*extra):
    """run.py at a debug size on the CPU by name; returns (exit code,
    last stdout line parsed, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "relay-16x256.paced", "--seed", str(2**31 + 11),
         "--seconds", "4", "--debug-size", "2x32", "--fps", "4", *extra],
        capture_output=True, text=True, env=env, timeout=600)
    lines = r.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1])
    except (IndexError, ValueError):
        last = None
    return r.returncode, last, r.stdout[-3000:] + r.stderr[-3000:]


def test_debug_run_prints_the_contracts_last_line():
    rc, last, tail = _run("--trace", "0")
    assert rc == 0 and last is not None, tail
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"], tail
    assert list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0, tail
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == file_checks.judged_on(
        file_checks.BENCH, "relay-16x256.paced")
    for m in last["metrics"].values():
        assert m["value"] > 0 and UNIT.match(m["unit"])
    assert all(v["value"] <= v["limit"] for v in last["compared"].values())
    assert "compared stamped_missing: 0 limit 0" in tail


def test_full_size_run_refuses_a_cpu():
    """No --debug-size: a CPU is no result, whatever JAX_PLATFORMS says."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "relay-1x64.live", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=600)
    assert r.returncode != 0
    assert "NO RESULT" in r.stdout
    assert not r.stdout.strip().splitlines()[-1].startswith("{")


def test_a_harness_that_cannot_set_up_prints_no_result():
    """The harness's own sockets fail for good: NO RESULT and the exit
    code of a run without a device, no traceback's exit 1, no JSON line,
    and nothing left running in the run's process group."""
    code = (
        "import errno, sys\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from benchmark import loadgen, run\n"
        "def refused(ip, port=0):\n"
        "    raise OSError(errno.EADDRINUSE, 'scripted: no port for good')\n"
        "loadgen.udp_socket = refused\n"
        "sys.exit(run.main())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen(
        [sys.executable, "-c", code, "--workload", "relay-1x64.live",
         "--seed", str(2**31 + 29), "--seconds", "1", "--trace", "0",
         "--debug-size", "1x2"], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, env=env, start_new_session=True)
    out, err = p.communicate(timeout=600)
    assert p.returncode == EXIT_NO_DEVICE, out[-2000:] + err[-2000:]
    assert "NO RESULT" in out and "scripted: no port for good" in out
    assert "Traceback" not in err, err[-2000:]
    assert not any(ln.startswith("{") for ln in out.splitlines())
    with pytest.raises(ProcessLookupError):     # the group is empty
        os.killpg(p.pid, 0)


@pytest.mark.parametrize("control", [
    "fault:seed=5,ingest_drop=0.05",        # every_packet
    "fault:seed=5,ingest_corrupt=0.05",     # bit_equal
    "ref:in_order", "ref:header_rewritten"])
def test_control_run_comes_out_not_correct(control):
    """The control through a whole run: the program with its own fault
    path switched on, or the reference with a guarantee broken put in
    one flow's place."""
    rc, last, tail = _run("--trace", "0", "--control", control)
    assert last is not None, tail
    assert last["correct"] is False, tail
    assert any(v["value"] > v["limit"] for v in last["compared"].values())


def test_timed_path_broken_underneath_comes_out_not_correct():
    """An answer altered where it is produced (broken_child.py flips a
    bit of every SSRC on its way into native egress)."""
    rc, last, tail = _run("--trace", "0", "--child-script",
                          os.path.join(HERE, "broken_child.py"))
    assert last is not None, tail
    assert last["correct"] is False, tail
    assert last["compared"]["stamped_altered"]["value"] > 0, tail
