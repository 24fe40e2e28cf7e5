#!/usr/bin/env python3
"""A lossy cell that ``BENCHMARK.json`` does not hold: ``relay-16x256``
with a ``last_mile`` key, under ``paced``'s pace, made in memory and run
by ``run.py`` unedited.  The tests drive it at a debug size on the CPU;
on a chip it rehearses the deployment a later configuration brings.

    python3 benchmark/tests/lossy_cell.py --mean-loss 0.02 --burst 3 \\
        --nack-after-ms 200 --warm-frames 24 \\
        -- --seed 4100000001 --seconds 30 --trace 0

Everything after ``--`` is ``run.py``'s own command line, less
``--workload``.
"""

from __future__ import annotations

import argparse
import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import run  # noqa: E402

CONFIG, TRAFFIC = "relay-16x256", "paced"
CELL = "relay-16x256.lossy-rehearsal"


def lossy_bench(last_mile: dict, warm_frames: int | None = None,
                load=run.load_json) -> tuple[dict, dict]:
    """The committed benchmark plus the lossy cell, and the files it
    names that are not on disk (path → contents)."""
    bench = copy.deepcopy(load("BENCHMARK.json"))
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = dict(copy.deepcopy(load(conf["file"])), last_mile=last_mile)
    traffic = copy.deepcopy(load(f"benchmark/traffic/{TRAFFIC}.json"))
    if warm_frames is not None:
        traffic["warm_frames"] = warm_frames
    name = "x-lossy"
    files = {f"benchmark/configs/{name}.json": cfg,
             f"benchmark/traffic/{name}.json": traffic}
    bench["configs"].append(dict(conf, name=name,
                                 file=f"benchmark/configs/{name}.json"))
    bench["workloads"].append({"name": CELL, "config": name, "traffic": name,
                               "chips": 1, "why": "a rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if f"{CONFIG}.{TRAFFIC}" in m.get("workloads", [CELL]):
            m.setdefault("workloads", []).append(CELL)
    files["BENCHMARK.json"] = bench
    return bench, files


def main(argv: list[str]) -> int:
    cut = argv.index("--") if "--" in argv else len(argv)
    mine, rest = argv[:cut], argv[cut + 1:]
    ap =argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mean-loss", type=float, required=True)
    ap.add_argument("--burst", type=float, default=3)
    ap.add_argument("--rr-interval-s", type=float, default=5)
    ap.add_argument("--nack-after-ms", type=float, default=200)
    ap.add_argument("--warm-frames", type=int)
    a = ap.parse_args(mine)
    _, files = lossy_bench(
        {"model": "gilbert", "mean_loss": a.mean_loss,
         "mean_burst_packets": a.burst, "rr_interval_s": a.rr_interval_s,
         "nack": "stamped", "nack_after_ms": a.nack_after_ms},
        a.warm_frames)
    committed = run.load_json
    run.load_json = lambda p: files[p] if p in files else committed(p)
    return run.main(["--workload", CELL, *rest])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
