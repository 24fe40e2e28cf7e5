"""A process's life before it listens, as five spans that follow one
another: ``boot.interpreter`` (the OS starts the process → the first
line of ``easydarwin_tpu/__main__.py``: Python itself and whatever a
launcher did first), ``boot.imports`` (this package's imports, JAX
among them — ``server.app`` → ``relay.fanout`` → ``ops.fanout`` — then
arguments, configuration, the compile cache, the server object),
``boot.native`` (``native.require``: the native core built or loaded),
``boot.backend`` (``device.resolve``: the JAX backend, on a chip the TPU
runtime) and ``boot.listen`` (the rest, to the ``listening:`` line).

``main`` makes one ``BootPhases`` and hands it to the server it starts;
a server started any other way (a test's) has none and records nothing.
``done`` sets ``server_boot_seconds{phase}`` — the five and ``total``,
their sum — and emits ``server.boot`` with the same six numbers: a slow
restart is read from ``/api/v1/events``.  A phase a boot does not go
through (``tpu_fanout`` off: no ``native``, no ``backend``) reads 0.
"""

from __future__ import annotations

import os
import time

from . import families
from .events import EVENTS
from .trace import TRACER

PHASES = ("interpreter", "imports", "native", "backend", "listen")
_SPAN = {p: f"boot.{p}" for p in PHASES}


def process_start_ns(first_line_ns: int) -> int:
    """When the OS started this process, on ``perf_counter_ns``'s clock:
    ``/proc/self/stat``'s start time against ``CLOCK_BOOTTIME`` (both
    count from the machine's boot), a clock tick fine.  Where the OS's
    record cannot be read, or says the process is younger than its own
    code, ``first_line_ns`` (the caller's first line) stands in."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command name, which may hold spaces
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age_s = (time.clock_gettime(time.CLOCK_BOOTTIME)
                 - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return first_line_ns
    return min(time.perf_counter_ns() - int(age_s * 1e9), first_line_ns)


class BootPhases:
    """The boot in progress: one phase at a time, each starting where
    the last one ended, so the five sum to the whole.  Filed post hoc
    (``TRACER.add``): the first two start before any line of this
    package has run."""

    def __init__(self, first_line_ns: int):
        self.seconds = dict.fromkeys(PHASES, 0.0)
        self._phase = "interpreter"
        self._t0 = process_start_ns(first_line_ns)
        self.enter("imports", at_ns=first_line_ns)

    def enter(self, phase: str, at_ns: int | None = None, **ended) -> None:
        """``phase`` starts now (or at ``at_ns``) and the one in progress
        ends there; ``ended`` are that one's span arguments."""
        if phase not in _SPAN:
            raise KeyError(phase)           # the vocabulary is closed
        self._end(time.perf_counter_ns() if at_ns is None else at_ns,
                  **ended)
        self._phase = phase

    def _end(self, now: int, **args) -> None:
        TRACER.add(_SPAN[self._phase], self._t0, now - self._t0, cat="boot",
                   **args)
        self.seconds[self._phase] += (now - self._t0) / 1e9
        self._t0 = now

    def done(self) -> dict[str, float]:
        """The process listens: the last phase ends, the gauge is set
        and ``server.boot`` emitted.  Returns the six numbers."""
        self._end(time.perf_counter_ns())
        doc = {p: round(s, 6) for p, s in self.seconds.items()}
        doc["total"] = round(sum(doc.values()), 6)
        for phase, s in doc.items():
            families.SERVER_BOOT_SECONDS.set(s, phase=phase)
        EVENTS.emit("server.boot", **doc)
        return doc
