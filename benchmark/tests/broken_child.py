#!/usr/bin/env python3
"""The server child with the timed path broken underneath: every answer
is altered where it is produced — native egress renders each relayed
packet's header from the per-subscriber SSRC array, and here that array
has one bit flipped on its way in.  ``run.py --child-script`` puts this
in ``server_child.py``'s place; ``correct`` has to come out false."""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from easydarwin_tpu import native  # noqa: E402


def alter(fn):
    def wrapped(*args, **kw):
        args = list(args)               # (fd|self, data, len, seq, ts, ssrc,
        args[5] = np.asarray(args[5], np.uint32) ^ np.uint32(1)
        return fn(*args, **kw)
    return wrapped


native.fanout_send_multi = alter(native.fanout_send_multi)
native.UringEgress.send_multi = alter(native.UringEgress.send_multi)

from benchmark.server_child import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
