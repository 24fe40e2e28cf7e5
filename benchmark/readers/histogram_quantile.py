"""A quantile of one server histogram's growth over the window, times
``scale``: from its cumulative ``_bucket{...le="..."}`` children (summed
over every other label), interpolated inside the bucket that crosses
the rank as ``obs.metrics.bucket_quantile`` does; the top finite bound
where the rank falls in ``+Inf``.  No such family, or no growth:
nothing to read."""

import math
import re

LE = re.compile(r'le="([^"]+)"')


def read(args: dict, ctx: dict) -> float | None:
    prefix = args["family"] + "_bucket{"
    grown: dict[float, float] = {}          # upper bound -> cumulative
    for key, v in ctx["m1"].items():
        le = LE.search(key) if key.startswith(prefix) else None
        if le is None:
            continue
        bound = math.inf if le.group(1) == "+Inf" else float(le.group(1))
        grown[bound] = grown.get(bound, 0.0) + v - ctx["m0"].get(key, 0.0)
    total = grown.get(math.inf)
    if not total:
        return None
    rank, lo, below = args["q"] * total, 0.0, 0.0
    for bound in sorted(grown):
        cum = grown[bound]
        if cum > below and cum >= rank:
            if bound == math.inf:
                break
            frac = min(max((rank - below) / (cum - below), 0.0), 1.0)
            return args.get("scale", 1.0) * (lo + (bound - lo) * frac)
        if bound != math.inf:
            lo, below = bound, cum
    return args.get("scale", 1.0) * lo
