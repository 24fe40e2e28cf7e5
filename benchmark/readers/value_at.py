"""A server family's value at one end of the window: ``at`` is ``m0``
(the scrape at the window's start: what the process did before it, its
boot, the join and the warm-up) or ``m1`` (at its end).  ``metric``
names a family, whose children are summed, or one labelled child.  A
program without the family reports nothing."""

from . import fam


def read(args: dict, ctx: dict) -> float | None:
    return fam(ctx[args["at"]], args["metric"])
