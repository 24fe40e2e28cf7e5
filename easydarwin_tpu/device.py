"""Which device this process runs on — resolved once, said out loud.

Everything that decides or reports the JAX backend lives here so no
entry point can serve from the CPU while claiming the chip:

* ``enable_compile_cache()`` — the ONE persistent-compilation-cache
  switch every entry point calls before its first jit.
* ``resolve(require_tpu=...)`` — initialise the backend NOW (not inside
  the first pump wake), return ``{"platform", "kind", "count"}`` as JAX
  reports it, and refuse a non-TPU backend nobody asked for by name.
  It also feeds ``jax.monitoring`` into the ``jax_*_total`` counters
  (executables built, their seconds, persistent-cache hits) that every
  ``/metrics`` scrape carries.
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

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_listening = False
#: a cache-hit event precedes the duration event of the same build
_hit_pending = False


class DeviceError(RuntimeError):
    """The backend this process would serve from is not the one asked
    for — a boot error, never a fallback."""


def _on_duration(event: str, duration: float, **_kw) -> None:
    global _hit_pending
    if event == _BACKEND_COMPILE_EVENT:
        from . import obs
        obs.JAX_EXECUTABLES_BUILT.inc()
        obs.JAX_EXECUTABLE_BUILD_SECONDS.inc(float(duration))
        # post hoc and ring only: XLA's own compile events are already
        # on the profiler's host plane
        dur_ns = int(duration * 1e9)
        obs.TRACER.add("jax.build", time.perf_counter_ns() - dur_ns, dur_ns,
                       cat="jax", seconds=round(float(duration), 6),
                       cache_hit=int(_hit_pending))
        _hit_pending = False


def _on_event(event: str, **_kw) -> None:
    global _hit_pending
    if event == _CACHE_HIT_EVENT:
        from . import obs
        obs.JAX_CACHE_HITS.inc()
        _hit_pending = True


def listen_builds() -> None:
    """Feed ``jax.monitoring`` into the ``jax_*_total`` counters (once a
    process).  Every engine calls it when built: the phase histograms
    keep a compiling pass out by watching ``jax_executables_built_total``
    across it (``obs.profile.builds``)."""
    global _listening
    from jax import monitoring

    with _lock:
        if not _listening:
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
