"""The device's idle share of the traced window, in %: 1 minus the
union of device-op intervals over the window, averaged over chips."""


def read(args: dict, ctx: dict) -> float | None:
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s") or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
