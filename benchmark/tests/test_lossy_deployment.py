"""The lossy last mile as a deployment: configuration
``relay-16x256-lossy`` is ``relay-16x256`` with a ``last_mile`` key and
differs from it in nothing else that is served; its cell
``relay-16x256-lossy.paced`` runs ``paced`` with a longer warm-up on one
chip, joins the accepted lists whose end-to-end metric it reports, and
brings entries of its own, one of them the parity kernel's roofline
(``benchmark/kernels.py``'s row, read by the accepted ``trace_op``); the
committed cell, run on the CPU at a debug size, is ``correct`` with its
fourteen compared names at 0 and both kinds of repair.  What the
committed file must hold is checked by ``file_checks.py``'s ``lossy``
part.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

import file_checks
from file_checks import METRIC_DIR, ROOT, load

from benchmark import kernels, readers, reduce_trace

CELL = file_checks.LOSSY


@pytest.mark.parametrize("check", file_checks.params("lossy"))
def test_the_lossy_cell_stands_as_accepted(check):
    check()


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
    assert kernels.fec_parity_window_step_bytes(16, 2048, 2) == \
        16 * 2048 + 2 * 16 + 2 * 2048 + 768 * 4
    progs = TRACE["modules"]["fec_parity_window_step"]["programs"]
    assert kernels.parity_shape(progs["11"]["shapes"]) == (16, 2048, 2)
    assert kernels.parity_shape(progs["12"]["shapes"]) == (16, 2048, 1)
    # the 3-D product alone names R where the coefficients do not show
    assert kernels.parity_shape({"u8": [[16, 512], [4, 512]],
                                 "s32": [[4, 16, 512]]}) == (16, 512, 4)
    assert kernels.parity_shape({"u8": [[16, 100]]}) is None
    spec = load(f"{METRIC_DIR}fec_parity_window_step_roofline.lossy.json")
    ctx = dict(file_checks.silent_ctx(), trace=TRACE, peaks=PEAKS)
    need = (20 * kernels.fec_parity_window_step_bytes(16, 2048, 2)
            + 10 * kernels.fec_parity_window_step_bytes(16, 2048, 1)
            ) / 819e9
    got = readers.read(spec, ctx)
    assert got == pytest.approx(100 * need / (30 * 20e-6)) and 0 < got < 100
    # a trace without the module, or with shapes it cannot spell
    assert readers.read(spec, dict(ctx, trace={"modules": {}})) is None
    bad = json.loads(json.dumps(TRACE))
    bad["modules"]["fec_parity_window_step"]["programs"]["12"]["shapes"] = {}
    assert readers.read(spec, dict(ctx, trace=bad)) is None


#: what the reader the metric named until PR 45 (``trace_op_parity``: the
#: parity kernel's row added to ``kernels.KERNELS`` at import, then read as
#: ``trace_op``) read on ``data/recorded_trace_lossy.json`` — sixteen
#: program executions, twelve of them the parity kernel's, cut from a
#: traced run of the cell on a TPU v5 lite
RECORDED_ROOFLINE = 0.008563492283110787


def test_the_recorded_lossy_trace_reads_as_it_did():
    """The roofline through the accepted ``trace_op`` reads what the
    retired reader read on the same trace; and it is the kernel's bytes
    over its device time, by hand."""
    trace = reduce_trace.reduce(
        load("benchmark/tests/data/recorded_trace_lossy.json"), chips=1)
    peaks = load("benchmark/peaks.json")["devices"]["TPU v5 lite"]
    spec = load(f"{METRIC_DIR}fec_parity_window_step_roofline.lossy.json")
    assert spec["reader"]["kind"] == "trace_op"
    got = readers.read(spec, dict(file_checks.silent_ctx(), trace=trace,
                                  peaks=peaks))
    assert got == RECORDED_ROOFLINE
    mod = trace["modules"]["fec_parity_window_step"]
    (prog,) = mod["programs"].values()
    assert prog["count"] == 12
    shape = kernels.parity_shape(prog["shapes"])
    assert shape == (16, 2048, 1)
    need = 12 * kernels.fec_parity_window_step_bytes(*shape)
    assert got == pytest.approx(100 * need / peaks["hbm_bytes_per_s"]
                                / mod["seconds"])


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
