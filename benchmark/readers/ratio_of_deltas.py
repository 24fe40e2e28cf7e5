"""Growth of one server counter over the growth of another, times
``scale``.  Nothing to divide by: nothing to read."""

from . import delta


def read(args: dict, ctx: dict) -> float | None:
    num, den = delta(ctx, args["num"]), delta(ctx, args["den"])
    if num is None or not den:
        return None
    return args.get("scale", 1.0) * num / den
