#!/usr/bin/env python3
"""From a profiler trace to numbers, under stable printed names.

Two steps, so that the second can be checked on a small recorded trace:

* ``load_xplane(path)`` reads an ``.xplane.pb`` (with nothing but JAX's
  ``ProfileData``) into plain ``events``: the traced window and, per
  device plane, its module executions and its ops as ``[name, start_ns,
  duration_ns]``, plus the host's events.
* ``reduce(events, chips)`` gives the summary the readers use:
  ``window_s``, ``busy_s`` (union of device-op intervals, averaged over
  the cell's chips: one that ran nothing in the window counts as idle),
  ``device_ops`` (top 10 by time), ``idle_gaps`` (the 10 longest, named
  by what the host was doing in them and the module that ended them)
  and ``modules`` (per program: executions, seconds, shapes where the
  trace names them).

Run as a program (``reduce_trace.py <trace dir> [<events.json>]
--chips N``) it prints the summary of the newest trace under a
directory as one JSON object.  The benchmark's parent never imports JAX;
it runs this file as a child, on the CPU by name, once the server child
has gone.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
HOST_PLANE = "/host:CPU"


def module_name(event_name: str) -> str:
    """``jit_megabatch_window_step(1234567)`` -> ``megabatch_window_step``:
    the program's name without the ``jit_`` prefix and the fingerprint."""
    name = event_name.split("(")[0].strip()
    return name[4:] if name.startswith("jit_") else name


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    events: dict = {"window_ns": None, "devices": {}, "host": []}
    lo, hi = None, 0
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                events["window_ns"] = int(st["profile_stop_time"]
                                          - st["profile_start_time"])
            continue
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if not is_dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            rows = []
            for e in line.events:
                if e.duration_ns <= 0:
                    continue
                row = [e.name, int(e.start_ns), int(e.duration_ns)]
                if is_dev and line.name == MODULES_LINE:
                    # the number in the name tells one shape's program
                    # from another's
                    row.append({"program_id":
                                e.name.partition("(")[2].rstrip(")")})
                rows.append(row)
                lo = row[1] if lo is None else min(lo, row[1])
                hi = max(hi, row[1] + row[2])
            if is_dev and line.name in (OPS_LINE, MODULES_LINE, ASYNC_LINE):
                dev = events["devices"].setdefault(
                    plane.name, {"ops": [], "modules": [], "async": []})
                dev[{OPS_LINE: "ops", MODULES_LINE: "modules",
                     ASYNC_LINE: "async"}[line.name]] += rows
            elif not is_dev:
                events["host"] += rows
    if events["window_ns"] is None and lo is not None:
        events["window_ns"] = hi - lo
    return events


def union_ns(intervals) -> tuple[int, list[tuple[int, int]]]:
    """Total covered time of ``(start, duration)`` intervals and the
    merged intervals themselves."""
    merged: list[list[int]] = []
    for s, d in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + d)
        else:
            merged.append([s, s + d])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def program_of(row: list) -> str:
    """The program id a module row carries (its name's fingerprint)."""
    return str((row[3] if len(row) > 3 else {}).get("program_id", ""))


SHAPE = re.compile(r"(u8|u32|s32|f32|bf16)\[([\d,]+)\]")


def note_shapes(modules: dict, mods: list, ops: list) -> None:
    """Each program's parameter and result shapes, as the names of the
    ops of its first traced execution spell them: every distinct
    ``dtype[dims]`` in those names, by dtype."""
    ops = sorted(ops, key=lambda r: r[1])
    starts = [r[1] for r in ops]
    for r in mods:
        prog = modules[module_name(r[0])]["programs"][program_of(r)]
        if "shapes" in prog:
            continue
        shapes: dict[str, list] = {}
        i = bisect.bisect_left(starts, r[1])
        while i < len(ops) and ops[i][1] < r[1] + r[2]:
            for dt, dims in SHAPE.findall(ops[i][0]):
                dims = [int(x) for x in dims.split(",")]
                if dims not in shapes.setdefault(dt, []):
                    shapes[dt].append(dims)
            i += 1
        prog["shapes"] = shapes


def reduce(events: dict, chips: int) -> dict:
    """``chips`` is what the cell asks for.  A chip that ran nothing in
    the traced window has no XLA line and so no plane in ``events``: it
    is idle, not absent, and ``busy_s`` is the mean over it too."""
    window_ns = events.get("window_ns") or 0
    devs = events.get("devices", {})
    n_chips = max(len(devs), chips)
    out: dict = {"window_s": window_ns / 1e9, "chips": n_chips,
                 "busy_s": None, "device_ops": [], "idle_gaps": [],
                 "modules": {}}
    if not devs or not window_ns:
        return out
    busy_total, op_time, gaps = 0, {}, []
    host = sorted(events.get("host", []), key=lambda r: r[1])
    for name, dev in sorted(devs.items()):
        rows = dev["ops"] or dev["modules"]
        busy, merged = union_ns((r[1], r[2]) for r in rows)
        busy_total += busy
        for r in rows:
            short = r[0].split(" = ")[0]
            op_time[short] = op_time.get(short, 0) + r[2]
        mods = sorted(dev["modules"], key=lambda r: r[1])
        for r in mods:
            m = out["modules"].setdefault(
                module_name(r[0]), {"count": 0, "seconds": 0.0,
                                    "programs": {}})
            m["count"] += 1
            m["seconds"] += r[2] / 1e9
            p = m["programs"].setdefault(program_of(r),
                                         {"count": 0, "seconds": 0.0})
            p["count"] += 1
            p["seconds"] += r[2] / 1e9
        note_shapes(out["modules"], mods, dev["ops"] + dev.get("async", []))
        # an idle gap lies between two program executions, not between
        # two ops of one
        _, runs = union_ns((r[1], r[2]) for r in (mods or rows))
        for (_, a_end), (b_start, _) in zip(runs, runs[1:]):
            gaps.append((b_start - a_end, a_end, b_start, mods))
    out["busy_s"] = busy_total / n_chips / 1e9
    # programs first, then single ops under their short HLO names
    by_mod = sorted(((n, m["seconds"]) for n, m in out["modules"].items()),
                    key=lambda kv: -kv[1])[:5]
    by_op = sorted(op_time.items(), key=lambda kv: -kv[1])[:10 - len(by_mod)]
    out["device_ops"] = [[f"program {n}", t] for n, t in by_mod] + [
        [n, t / 1e9] for n, t in by_op]
    for length, a, b, mods in sorted(gaps, key=lambda g: -g[0])[:10]:
        nxt = next((module_name(r[0]) for r in mods if r[1] >= b), "end")
        best, best_ov = "host idle", 0
        for r in host:
            if r[1] >= b:
                break
            ov = min(r[1] + r[2], b) - max(r[1], a)
            if ov > best_ov:
                best, best_ov = r[0], ov
        out["idle_gaps"].append([f"{best} -> {nxt}", length / 1e9])
    return out


def newest_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("events_json", nargs="?",
                    help="keep the events here, for a look")
    ap.add_argument("--chips", type=int, required=True,
                    help="the chips the cell asks for")
    args = ap.parse_args(argv[1:])
    path = newest_xplane(args.trace_dir)
    if path is None:
        print(json.dumps({"error": f"no xplane.pb under {args.trace_dir}"}))
        return 1
    events = load_xplane(path)
    if args.events_json:
        with open(args.events_json, "w") as f:
            json.dump(events, f)
    print(json.dumps(reduce(events, args.chips)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
