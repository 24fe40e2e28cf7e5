#!/usr/bin/env python3
"""The system under test, as one child process: ``easydarwin_tpu``'s own
``main`` unchanged, plus what only the process that holds the chip can
give the benchmark.

* ``--trace-dir D``: SIGUSR1 starts ``jax.profiler.start_trace(D)``,
  SIGUSR2 stops it and then writes ``D/trace_done`` (the parent sends
  both, a few seconds apart, inside the measured window).  Without the
  option nothing is installed: an untraced run adds nothing.
* ``--device-json F``: after ``main`` returns (SIGTERM, clean shutdown)
  the device as JAX reports it and ``peak_bytes_in_use`` of the fullest
  chip are written to ``F``.

Everything after ``--`` is ``easydarwin_tpu``'s own command line.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def install_tracer(trace_dir: str) -> None:
    """Start/stop the profiler from a helper thread: a signal handler
    only sets an event, so the server's event loop is never blocked in
    the profiler."""
    start, stop = threading.Event(), threading.Event()

    def worker() -> None:
        import jax

        start.wait()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host Python frames: huge, unused
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        stop.wait()
        jax.profiler.stop_trace()
        with open(os.path.join(trace_dir, "trace_done"), "w") as f:
            f.write("1\n")

    threading.Thread(target=worker, daemon=True, name="bench-tracer").start()
    signal.signal(signal.SIGUSR1, lambda *_: start.set())
    signal.signal(signal.SIGUSR2, lambda *_: stop.set())


def write_device(path: str) -> None:
    import jax

    devs = jax.local_devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    with open(path, "w") as f:
        json.dump({"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs), "memory_peak_bytes": peak}, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace-dir")
    ap.add_argument("--device-json")
    ap.add_argument("server_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    rest = [a for a in args.server_args if a != "--"]
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
        install_tracer(args.trace_dir)
    from easydarwin_tpu.__main__ import main as server_main

    rc = server_main(rest)
    if args.device_json and rc == 0:
        write_device(args.device_json)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
