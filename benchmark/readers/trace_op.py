"""A kernel's share of its roofline, in %: the least time the chip could
take for the traced executions of one program (the bytes or operations
its shapes need, by ``benchmark/kernels.py``, over the device's peak)
over the device time the trace gives them."""

from .. import kernels


def read(args: dict, ctx: dict) -> float | None:
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    if not tr or not peaks:
        return None
    mod = tr.get("modules", {}).get(args["module"])
    if not mod or not mod.get("seconds"):
        return None
    need = kernels.least_seconds(args["module"], mod, peaks, args["bound"])
    if not need:
        return None
    return 100.0 * need / mod["seconds"]
