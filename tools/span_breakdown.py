#!/usr/bin/env python3
"""Where a pump wake's time goes, from the program's own spans.

    python tools/span_breakdown.py <trace dir | x.xplane.pb | ring.json>

Reads the host plane of a profiler trace (``benchmark/server_child.py
--trace-dir D``, or ``benchmark_out/<cell>/trace`` after a ``--trace 1``
run) through ``benchmark/reduce_trace.load_xplane``, or the span ring as
``GET /api/v1/admin?command=trace`` dumps it, and prints per span of
``obs.trace.SPANS``: count, seconds, ms per ``pump.wake`` and share of
the time the pump loop spent awake or asleep, then what ``pump.wake``
carries per wake: the streams it served and, where the program has a
ready set, how many of them it stepped and how many of the owned pairs it
handed the megabatch scheduler had their plan read; then the send jobs it
handed the native sender, their milliseconds of sending and the share of
those the loop thread did not wait for.  Spans nest, so a child's ms are
inside its parent's — but for ``native.egress``, which is the sender
thread's time: ``egress.wait`` beside it is what of that the loop thread
stood still for.  Under the table, for a ring dump (the two are the
ring's alone): the ``boot.*`` phases still in the ring, and every
``jax.build`` with its program, whether it was compiled or loaded, its
three parts, its wake and the chain of spans that enclose it in time on
its thread (``pump.wake > pump.megabatch > megabatch.dispatch >
megabatch.h2d``, ``rtsp.play``, ``boot.listen``): the cause of a build.
A dump taken after boot (``command=trace``) has them until 16,384 later
spans have overrun them; ``/api/v1/events`` keeps a ``jax.build`` and the
``server.boot`` event longer.  Holds no chip: run it with
``JAX_PLATFORMS=cpu``."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OURS = ("pump.", "engine.", "megabatch.", "native.", "egress.",
        "pipeline.", "ingest.")


def host_rows(path: str) -> list:
    """``[name, start_ns, duration_ns]`` rows of the program's spans."""
    if path.endswith(".json"):
        with open(path) as f:
            evs = json.load(f)["traceEvents"]
        return [[e["name"], e["ts"] * 1e3, e["dur"] * 1e3] for e in evs]
    sys.path.insert(0, ROOT)
    from benchmark import reduce_trace
    if os.path.isdir(path):
        path = reduce_trace.newest_xplane(path) or path
    return reduce_trace.load_xplane(path)["host"]


def ring_events(path: str) -> list[dict]:
    """The events of a ring dump; none for a profiler trace, which has
    neither ``boot.*`` nor ``jax.build``."""
    if not path.endswith(".json"):
        return []
    with open(path) as f:
        return json.load(f)["traceEvents"]


def boot_phases(events: list[dict]) -> list[dict]:
    """The ``boot.*`` spans in the order they ran: ``name``, ``seconds``
    and their arguments."""
    return [{"name": e["name"], "seconds": e["dur"] / 1e6,
             "args": e.get("args", {})}
            for e in sorted(events, key=lambda e: e["ts"])
            if e["name"].startswith("boot.")]


def builds(events: list[dict]) -> list[dict]:
    """Every ``jax.build`` in the order they ended, each with ``chain``:
    the names of the spans of its thread that hold its whole interval,
    outermost first."""
    by_tid: dict = {}
    for e in events:
        # native.egress is the sender thread's time, filed from the loop
        # thread: it is beside what that thread does, not around it
        if e["name"] not in ("jax.build", "native.egress"):
            by_tid.setdefault(e["tid"], []).append(e)
    out = []
    for b in (e for e in events if e["name"] == "jax.build"):
        t0, t1 = b["ts"], b["ts"] + b["dur"]
        around = [e for e in by_tid.get(b["tid"], ())
                  if e["ts"] <= t0 and t1 <= e["ts"] + e["dur"]]
        around.sort(key=lambda e: (e["ts"], -e["dur"]))
        out.append({"at_s": t1 / 1e6, "seconds": b["dur"] / 1e6,
                    "chain": [e["name"] for e in around],
                    **b.get("args", {})})
    return sorted(out, key=lambda b: b["at_s"])


def wake_args(path: str) -> list[dict]:
    """The arguments of every ``pump.wake`` span (``streams``, ``sent``
    and, where the wake has a ready set, ``stepped``, ``handed`` and
    ``walked``)."""
    if path.endswith(".json"):
        with open(path) as f:
            return [e.get("args", {}) for e in json.load(f)["traceEvents"]
                    if e["name"] == "pump.wake"]
    sys.path.insert(0, ROOT)
    from benchmark import reduce_trace
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = reduce_trace.newest_xplane(path) or path
    return [dict(e.stats) for plane in ProfileData.from_file(path).planes
            if plane.name == reduce_trace.HOST_PLANE
            for line in plane.lines for e in line.events
            if e.name == "pump.wake" and e.duration_ns > 0]


def per_wake(args: list[dict]) -> dict:
    """Mean of each numeric ``pump.wake`` argument over the wakes that
    carry it."""
    out = {}
    for key in ("streams", "stepped", "handed", "walked", "sent", "jobs",
                "send_us", "hidden_us"):
        vals = [float(a[key]) for a in args if key in a]
        if vals:
            out[key] = sum(vals) / len(vals)
    return out


def breakdown(rows: list, wakes_args: list[dict] | None = None) -> dict:
    """``native.egress`` is filed from the sender thread's own stamps,
    after the fact, so only the ring has it; for a profiler trace the
    row is made of what ``pump.wake`` carries (``jobs``, ``send_us``)
    and stands beside ``egress.wait``, the loop thread blocked on it."""
    spans: dict[str, list] = {}
    for name, _start, dur in rows:
        if name.startswith(OURS):
            c = spans.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += dur / 1e9
    if "native.egress" not in spans and wakes_args:
        jobs = sum(int(a.get("jobs", 0)) for a in wakes_args)
        if jobs:
            spans["native.egress"] = [jobs, sum(
                float(a.get("send_us", 0)) for a in wakes_args) / 1e6]
    wakes = spans.get("pump.wake", [0, 0.0])[0]
    loop_s = sum(spans.get(n, [0, 0.0])[1]
                 for n in ("pump.wake", "pump.sleep"))
    return {"wakes": wakes, "loop_s": loop_s, "spans": {
        name: {"count": n, "seconds": s,
               "ms_per_wake": 1e3 * s / wakes if wakes else None,
               "loop_pct": 100.0 * s / loop_s if loop_s else None}
        for name, (n, s) in sorted(spans.items())}}


def main(argv) -> int:
    carried = wake_args(argv[1])
    doc = breakdown(host_rows(argv[1]), carried)
    print(f"{doc['wakes']} wakes, {doc['loop_s']:.3f} s of pump loop")
    for name, row in doc["spans"].items():
        print(f"{name:22s} {row['count']:7d} {row['seconds']:10.4f} s "
              f"{row['ms_per_wake'] or 0:10.3f} ms/wake "
              f"{row['loop_pct'] or 0:6.2f} %")
    mean = per_wake(carried)
    if "streams" in mean:
        line = f"a wake: streams {mean['streams']:.1f}"
        if "stepped" in mean:
            line += (f", stepped {mean['stepped']:.2f} "
                     f"({100 * mean['stepped'] / mean['streams']:.1f} %)"
                     if mean["streams"] else ", stepped 0")
        if "handed" in mean:
            line += (f", pairs handed {mean['handed']:.1f}, walked "
                     f"{mean.get('walked', 0):.2f}")
        line += f", sent {mean.get('sent', 0):.1f}"
        if "jobs" in mean:
            send = mean.get("send_us", 0.0)
            line += (f", send jobs {mean['jobs']:.2f} ({send / 1e3:.2f} ms "
                     f"of sending, "
                     f"{100 * mean.get('hidden_us', 0) / send if send else 0:.1f}"
                     f" % of it hidden behind the loop thread)")
        print(line)
    events = ring_events(argv[1])
    boot = boot_phases(events)
    if boot:
        print("boot: " + ", ".join(
            f"{p['name'][5:]} {p['seconds']:.3f} s" + "".join(
                f" ({k}={v})" for k, v in p["args"].items())
            for p in boot)
            + f"; in all {sum(p['seconds'] for p in boot):.3f} s")
    built = builds(events)
    if built:
        part = {k: sum(b.get(f"{k}_us", 0) for b in built) / 1e6
                for k in ("trace", "lower", "backend")}
        print(f"{len(built)} builds, "
              f"{sum(b.get('source') == 'cache' for b in built)} of them "
              f"loaded from the cache: trace {part['trace']:.3f} s, lower "
              f"{part['lower']:.3f} s, backend {part['backend']:.3f} s")
        for b in built:
            print(f"  {b['at_s']:10.3f} s {b.get('program', '?'):40s} "
                  f"{b.get('source', '?'):7s} "
                  f"trace {b.get('trace_us', 0) / 1e3:8.1f} ms lower "
                  f"{b.get('lower_us', 0) / 1e3:8.1f} ms backend "
                  f"{b.get('backend_us', 0) / 1e3:8.1f} ms "
                  f"wake {b.get('wake', '-')} in "
                  f"{' > '.join(b['chain']) or '(no span)'}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
