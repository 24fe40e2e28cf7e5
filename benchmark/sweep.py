#!/usr/bin/env python3
"""The one sweep that finds the knee: the same cell at several paces,
one run each, in one call.  The knee is the highest pace at which the
server's sent count keeps up with pushed x joined through the window
(no backlog beyond the buckets' hold at its close) and the generator's
lateness does not grow.

    python3 benchmark/sweep.py relay-16x256.paced 15 1.0 1.3 1.6 2.0
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv) -> int:
    cell, seconds, paces = argv[1], argv[2], argv[3:]
    for i, fps in enumerate(paces):
        r = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
             "--seed", str(4000000000 + i), "--seconds", seconds,
             "--trace", "0", "--fps", fps], capture_output=True, text=True)
        keep = [ln for ln in r.stdout.splitlines()
                if any(k in ln for k in ("window", "loss:", "OVER", "INVALID",
                                         "NO RESULT", "joined in"))]
        print(f"== pace {fps} fps/source (exit {r.returncode})")
        print("\n".join(keep))
        try:
            line = json.loads(r.stdout.strip().splitlines()[-1])
            print(json.dumps({"correct": line["correct"],
                              "metrics": line["metrics"]}))
        except (IndexError, ValueError):
            print(r.stdout[-1500:], r.stderr[-1500:])
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
