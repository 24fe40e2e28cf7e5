"""Per-layer metric readers, one module per kind.  A reader is
``read(args, ctx) -> float | None``: ``args`` is the ``reader`` group of
the metric's own file, ``ctx`` holds what a run gathered — ``m0`` and
``m1`` (the server's ``/metrics`` at the window's start and end),
``harness`` (numbers the harness took itself), ``trace`` (the reduced
profiler trace, or None) and ``peaks`` (the device's row of
``peaks.json``).  A reader that finds nothing to read returns None and
the metric is left out of the line."""

from __future__ import annotations

import importlib


def fam(m: dict[str, float], name: str) -> float | None:
    """Sum of every child of metric family ``name`` (or of the one
    labelled child ``name{...}`` names); None when absent."""
    vals = [v for k, v in m.items()
            if k == name or k.startswith(name + "{")]
    return sum(vals) if vals else None


def delta(ctx: dict, name: str) -> float | None:
    a, b = fam(ctx["m0"], name), fam(ctx["m1"], name)
    if b is None:
        return None
    return b - (a or 0.0)


def read(metric: dict, ctx: dict) -> float | None:
    args = metric["reader"]
    mod = importlib.import_module(f"{__name__}.{args['kind']}")
    return mod.read(args, ctx)
