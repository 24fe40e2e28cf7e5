"""A server counter's growth over the window."""

from . import delta


def read(args: dict, ctx: dict) -> float | None:
    return delta(ctx, args["counter"])
