"""Which device this process runs on — resolved once, said out loud.

Everything that decides or reports the JAX backend lives here so no
entry point can serve from the CPU while claiming the chip:

* ``enable_compile_cache()`` — the ONE persistent-compilation-cache
  switch every entry point calls before its first jit.
* ``resolve(require_tpu=...)`` — initialise the backend NOW (not inside
  the first pump wake), return ``{"platform", "kind", "count"}`` as JAX
  reports it, and refuse a non-TPU backend nobody asked for by name.
  It also feeds ``jax.monitoring`` into the ``jax_*_total`` counters
  (executables built, their seconds by part, persistent-cache hits)
  that every ``/metrics`` scrape carries, and into one ``jax.build``
  span and event a built executable: which program, compiled or
  loaded, and the seconds of its trace, lowering and backend part.
* ``note_swallowed()`` — the one way a handler may keep serving past a
  device-path exception: counted and logged, so a zero-check sees it.

Importing this module does not import JAX.
"""

from __future__ import annotations

import os
import threading
import time

#: the in-checkout cache directory used when ``JAX_COMPILATION_CACHE_DIR``
#: is not set.  Fixed on purpose: the path is part of how a later process
#: finds what an earlier one compiled, so it is never derived from a
#: tempdir, a pid or the clock.  Listed in ``.gitignore``.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

#: the events JAX times a first call under (``jax/_src/dispatch.py``),
#: each by the part of a ``jax.build`` span it is
_PARTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "backend"}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_listening = False


class DeviceError(RuntimeError):
    """The backend this process would serve from is not the one asked
    for — a boot error, never a fallback."""


class _FirstCalls(threading.local):
    """One thread's first calls in progress.  JAX marks each timed part
    where it starts and reports its seconds where it ends, and parts
    nest (an inner ``jit``'s trace lies inside its caller's; an eager
    helper met while tracing is a whole build inside a trace), so every
    part is charged its own seconds only — its duration less what ended
    inside it — and a second is counted once."""

    def __init__(self):
        #: per part still open, innermost last: the seconds of the
        #: parts that ended inside it
        self.inner: list[float] = []
        #: when the first part since the last build started
        self.t0_ns: int | None = None
        #: own seconds of ended traces and lowerings no build has taken
        #: yet: they go to the next executable this thread builds, which
        #: is the outermost program's where an inner one was inlined
        self.trace_s = self.lower_s = 0.0
        #: a cache-hit event precedes the duration event of the build
        self.cache_hit = False


_calls = _FirstCalls()


def _on_start(event: str, _start_time: float, **_kw) -> None:
    if event in _PARTS:
        if _calls.t0_ns is None:
            _calls.t0_ns = time.perf_counter_ns()
        _calls.inner.append(0.0)


def _on_duration(event: str, duration: float, fun_name: str = "",
                 **_kw) -> None:
    part = _PARTS.get(event)
    if part is None:
        return
    c, duration = _calls, float(duration)
    own = max(duration - (c.inner.pop() if c.inner else 0.0), 0.0)
    if c.inner:
        c.inner[-1] += duration
    if part == "trace":
        c.trace_s += own
    elif part == "lower":
        c.lower_s += own
    else:
        _built(str(fun_name), own)


def _built(program: str, backend_s: float) -> None:
    """An executable exists: one ``jax.build`` span, [the start of its
    trace, now], one event, and the counters."""
    from . import obs
    c, now = _calls, time.perf_counter_ns()
    t0 = c.t0_ns if c.t0_ns is not None else now - int(backend_s * 1e9)
    parts = {"trace": c.trace_s, "lower": c.lower_s, "backend": backend_s}
    source = "cache" if c.cache_hit else "compile"
    c.trace_s = c.lower_s = 0.0
    c.cache_hit = False
    # a build inside a part still open: the rest of that part starts here
    c.t0_ns = now if c.inner else None
    obs.JAX_EXECUTABLES_BUILT.inc()
    for phase, s in parts.items():
        obs.JAX_EXECUTABLE_BUILD_SECONDS.inc(s, phase=phase)
    us = {f"{phase}_us": round(s * 1e6) for phase, s in parts.items()}
    # post hoc and ring only: XLA's own compile events are already on
    # the profiler's host plane
    obs.TRACER.add("jax.build", t0, now - t0, cat="jax", program=program,
                   source=source, **us)
    obs.EVENTS.emit("jax.build", program=program, source=source,
                    seconds=round(sum(parts.values()), 6),
                    wake=obs.TRACER.wake, **us)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        from . import obs
        obs.JAX_CACHE_HITS.inc()
        _calls.cache_hit = True


def listen_builds() -> None:
    """Feed ``jax.monitoring`` into the ``jax_*_total`` counters, the
    ``jax.build`` spans and events (once a process).  Every engine calls
    it when built: the phase histograms keep a compiling pass out by
    watching ``jax_executables_built_total`` across it
    (``obs.profile.builds``)."""
    global _listening
    from jax import monitoring

    with _lock:
        if not _listening:
            monitoring.register_scalar_listener(_on_start)
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            _listening = True


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    no directory is set in code; otherwise the cache goes to
    ``CACHE_DIR``.  The persist threshold is dropped to zero because the
    served kernels are dozens of sub-second pow2 bucket specialisations
    — at JAX's default (1 s) none of them would ever be kept."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    listen_builds()
    return jax.config.jax_compilation_cache_dir


def cpu_requested() -> bool:
    """True when ``JAX_PLATFORMS`` asks for the CPU by name — its FIRST
    entry is ``cpu``.  A fallback entry does not count: the chip
    machines export ``JAX_PLATFORMS=tpu,cpu``, and there a CPU backend
    means libtpu found no chip, which is exactly the case to refuse."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def resolve(*, require_tpu: bool = False) -> dict:
    """Initialise the backend and describe it as JAX does.

    ``require_tpu``: raise ``DeviceError`` unless the platform is
    ``tpu`` or ``JAX_PLATFORMS`` asked for the CPU first — libtpu
    finding no chip must stop the boot, not move the engine onto the
    host behind a ``tpu_fanout=on`` banner."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise DeviceError(f"JAX backend failed to initialise: {e}") from e
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu" and not cpu_requested():
        raise DeviceError(
            f"tpu_fanout is on but JAX resolved platform "
            f"{info['platform']!r} ({info['kind']} x{info['count']}); "
            f"set JAX_PLATFORMS=cpu to run the engine on the CPU on "
            f"purpose")
    return info


def note_swallowed(site: str, exc: BaseException) -> None:
    """A handler caught ``exc`` on a device path and the host path will
    serve instead: count it (``device_errors_swallowed_total{site}``)
    and emit ``device.error_swallowed`` so it is never silent."""
    from . import obs
    obs.DEVICE_ERRORS_SWALLOWED.inc(site=site)
    obs.EVENTS.emit("device.error_swallowed", level="warn", site=site,
                    error=repr(exc)[:300])
