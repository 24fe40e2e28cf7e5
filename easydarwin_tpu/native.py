"""ctypes bridge to the C++ data-plane (csrc/libedtpu_core.so).

The library is built from the tracked sources with ``make`` and is only
ever loaded when the file on disk can be tied to them AND to this
machine: ``csrc/Makefile`` compiles in a digest of every build input and
a key of the CPU ``-march=native`` specialised the code for;
``_load`` reads both out of the file's bytes before ``dlopen`` and
rebuilds when either differs (a checkout copied from another machine, a
source edited under an existing ``.so``).

Callers that can serve without it check ``available()`` and take their
numpy path; the TPU engine cannot — ``require()`` turns a core that
will not build or load into a boot error (``server/app.py``).
"""

from __future__ import annotations

import ctypes
import errno
import glob
import hashlib
import os
import re
import socket
import struct
import subprocess
import threading

import numpy as np

from .obs import TRACER

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "csrc")
# EDTPU_CORE_SO overrides the library path (sanitizer builds: make
# asan/tsan in csrc/ produce instrumented .so variants for the CI jobs
# the reference never had); an override is loaded as given, never rebuilt
_SO_OVERRIDE = os.environ.get("EDTPU_CORE_SO")
_SO = _SO_OVERRIDE or os.path.join(_CSRC, "libedtpu_core.so")
_lock = threading.Lock()
_lib = None
_tried = False
#: why the last ``_load`` returned None ("" while loaded / not tried)
_load_error = ""
#: True when THIS process ran ``make`` (the smoke prints it: the library
#: on a chip machine must have been built there)
_built_here = False
#: (source digest, cpu key) of the library this process loaded
_build_tag: tuple[str, str] | None = None
_BUILD_MARK = re.compile(rb"EDTPU_BUILD\{([0-9a-z]+)\|([0-9a-z]+)\}")


class NativeCoreError(RuntimeError):
    """The native core is required and will not build or load."""


class SendOp(ctypes.Structure):
    _fields_ = [("slot", ctypes.c_int32), ("out", ctypes.c_int32)]


#: field order MUST match struct ed_stats in csrc/edtpu_core.h
#: (send_ns/ingest_ns are the clock_gettime timing tail; stage_gather_ns/
#: staged_bytes are the megabatch staging tail — second ABI bump;
#: fault_injections is the resilience subsystem's egress fault counter —
#: third ABI bump; the uring_* fields are the io_uring backend tail —
#: fourth ABI bump; the loader refuses any library whose field count
#: disagrees — ed_stats_fields check)
_STAT_FIELDS = ("sendmmsg_calls", "sendto_calls", "send_packets",
                "gso_supers", "gso_segments", "eagain_stops",
                "hard_errors", "bytes_to_wire", "recvmmsg_calls",
                "recv_datagrams", "recv_bytes", "oversize_dropped",
                "send_ns", "ingest_ns", "stage_gather_ns", "staged_bytes",
                "fault_injections", "uring_sqes", "uring_cqes",
                "uring_submits", "uring_zc_completions", "uring_zc_copied",
                # stream-socket egress tail (fifth ABI bump, ISSUE 14)
                "stream_writev_calls", "stream_packets", "stream_bytes")

#: capability bits reported by ``uring_probe()`` (csrc ED_URING_CAP_*)
URING_CAP_RING = 1
URING_CAP_SQPOLL = 2
URING_CAP_SEND_ZC = 4
URING_CAP_RECV_MULTI = 8
URING_CAP_FIXED_BUFS = 16
#: creation-request flags (csrc ED_URING_F_*)
URING_F_SQPOLL = 1
URING_F_ZEROCOPY = 2


class EdStats(ctypes.Structure):
    _fields_ = [(n, ctypes.c_int64) for n in _STAT_FIELDS]


#: scratch for the egress spans' ``syscalls`` arg (pump thread only)
_SPAN_STATS = EdStats()


def _egress_syscalls(lib) -> int:
    """Send-side syscalls the library has made so far, of every rung."""
    lib.ed_get_stats(ctypes.byref(_SPAN_STATS))
    st = _SPAN_STATS
    return (st.sendmmsg_calls + st.sendto_calls + st.uring_submits
            + st.stream_writev_calls)


def _egress_open(lib, name: str, trace_id: str | None, **args):
    """Open a ``native.egress`` / ``native.stream_egress`` span
    (``obs.trace``) around one library call; ``_egress_close`` ends it
    with what the call sent and, while a profiler session is live (the
    span is on its host plane), the syscalls it took — two stats reads
    that an untraced send does not pay."""
    if trace_id is not None:
        args["trace_id"] = trace_id
    span = TRACER.open(name, "native", **args)
    traced = span is not None and span.tm is not None
    return span, (_egress_syscalls(lib) if traced else None)


def _egress_close(lib, opened, r: int) -> None:
    span, sys0 = opened
    if sys0 is not None:
        TRACER.close(span, sent=int(r), datagrams=max(int(r), 0),
                     syscalls=_egress_syscalls(lib) - sys0)
    elif span is not None:
        TRACER.close(span, sent=int(r), datagrams=max(int(r), 0))


class Dest(ctypes.Structure):
    _fields_ = [("ip_be", ctypes.c_uint32), ("port_be", ctypes.c_uint16),
                ("_pad", ctypes.c_uint16)]


class _SendJobC(ctypes.Structure):
    """``struct ed_send_job`` (csrc/edtpu_core.h), field for field; the
    loader checks its size against the library's."""
    _fields_ = [("ring_data", ctypes.c_void_p), ("ring_len", ctypes.c_void_p),
                ("seq_off", ctypes.c_void_p), ("ts_off", ctypes.c_void_p),
                ("ssrc", ctypes.c_void_p), ("dest", ctypes.c_void_p),
                ("ops", ctypes.c_void_p),
                ("fd", ctypes.c_int32), ("capacity", ctypes.c_int32),
                ("slot_size", ctypes.c_int32), ("n_src", ctypes.c_int32),
                ("param_stride", ctypes.c_int32), ("n_outs", ctypes.c_int32),
                ("n_ops", ctypes.c_int32), ("use_gso", ctypes.c_int32),
                ("result", ctypes.c_int32), ("err", ctypes.c_int32),
                ("submit_ns", ctypes.c_int64), ("start_ns", ctypes.c_int64),
                ("done_ns", ctypes.c_int64), ("syscalls", ctypes.c_int64),
                ("state", ctypes.c_int32), ("_pad", ctypes.c_int32)]


class SendJob:
    """One ``fanout_send_multi`` call handed to the native sender thread
    (``submit=True``): the ticket ``wait`` takes.  Holds every array the
    job points into until it is dropped — drop it only once ``done``.

    ``result`` is what the inline call returns; ``err`` is the JOB's
    errno (``last_send_errno()`` is the calling thread's and knows
    nothing of a send another thread made); ``start_ns`` / ``done_ns``
    are the send's own stamps on ``CLOCK_MONOTONIC`` — the clock
    ``time.perf_counter_ns`` reads here."""

    __slots__ = ("c", "n_ops", "use_gso", "_ref", "_keep", "_lib")

    def __init__(self, lib, c: _SendJobC, keep: tuple):
        self.c = c
        self.n_ops = c.n_ops
        self.use_gso = c.use_gso
        self._ref = ctypes.byref(c)
        self._keep = keep
        self._lib = lib

    @property
    def done(self) -> bool:
        return self.c.state == 2

    def wait(self) -> "SendJob":
        """Block (the GIL released) until the sender is through with it."""
        if self._lib.ed_sender_wait(self._ref) < 0:
            raise RuntimeError("wait on a send job that was never submitted")
        return self

    result = property(lambda self: self.c.result)
    err = property(lambda self: self.c.err)
    submit_ns = property(lambda self: self.c.submit_ns)
    start_ns = property(lambda self: self.c.start_ns)
    done_ns = property(lambda self: self.c.done_ns)
    syscalls = property(lambda self: self.c.syscalls)


def source_digest() -> str:
    """sha256 (first 16 hex) over every tracked build input, in the
    order ``csrc/Makefile`` hashes them."""
    names = ["Makefile"] + sorted(
        os.path.basename(p) for pat in ("*.cpp", "*.h")
        for p in glob.glob(os.path.join(_CSRC, pat)))
    h = hashlib.sha256()
    for n in names:
        with open(os.path.join(_CSRC, n), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def cpu_key() -> str:
    """Key of the CPU ``-march=native`` targets: sha256 of the first
    ``flags`` line of /proc/cpuinfo (``csrc/Makefile`` CPUKEY)."""
    line = b""
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for ln in f:
                if ln.startswith(b"flags"):
                    line = ln
                    break
    except OSError:
        pass
    return hashlib.sha256(line).hexdigest()[:16]


def _embedded_build(path: str) -> tuple[str, str] | None:
    """(source digest, cpu key) compiled into the library at ``path``,
    read from its bytes — nothing in the file is executed."""
    try:
        with open(path, "rb") as f:
            m = _BUILD_MARK.search(f.read())
    except OSError:
        return None
    return (m.group(1).decode(), m.group(2).decode()) if m else None


def _build() -> str:
    """Run ``make`` for the default library; "" on success, else why."""
    global _built_here
    try:
        subprocess.run(
            ["make", "-s", "-B", "-C", _CSRC, f"DIGEST={source_digest()}",
             f"CPUKEY={cpu_key()}"],
            check=True, capture_output=True, timeout=300)
    except subprocess.CalledProcessError as e:
        return ("make failed: "
                + (e.stderr or b"").decode("utf-8", "replace")[-600:])
    except (subprocess.SubprocessError, OSError) as e:
        return f"make failed: {e!r}"
    _built_here = True
    return ""


def _load():
    global _lib, _tried, _load_error, _build_tag
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if _SO_OVERRIDE is None:
            want = (source_digest(), cpu_key())
            if _embedded_build(_SO) != want:
                # missing, built from other sources, or for another CPU:
                # rebuild in place (make renames a fresh inode over the
                # old file) and insist the result carries OUR marker
                err = _build()
                if not err and _embedded_build(_SO) != want:
                    err = "rebuilt library does not carry this tree's digest"
                if err:
                    _load_error = err
                    return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            _load_error = f"dlopen {_SO}: {e}"
            return None
        # ABI handshake: the library must write EXACTLY the fields our
        # EdStats buffer holds.  Fewer would read the tail as zeros;
        # more would write past our buffer — heap corruption, the one
        # failure mode worse than refusing.  (Only an EDTPU_CORE_SO
        # override can still trip this; the digest covers the default.)
        if not hasattr(lib, "ed_stats_fields"):
            _load_error = "library has no ed_stats_fields (stale ABI)"
            return None
        lib.ed_stats_fields.restype = ctypes.c_int32
        lib.ed_stats_fields.argtypes = []
        if lib.ed_stats_fields() != len(_STAT_FIELDS):
            _load_error = (f"ed_stats ABI mismatch: library has "
                           f"{lib.ed_stats_fields()} fields, bridge "
                           f"expects {len(_STAT_FIELDS)}")
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        lib.ed_version.restype = ctypes.c_char_p
        lib.ed_fanout_send_udp.restype = ctypes.c_int32
        lib.ed_fanout_send_udp.argtypes = [
            ctypes.c_int, u8p, i32p, ctypes.c_int32, ctypes.c_int32,
            u32p, u32p, u32p, ctypes.POINTER(Dest), ctypes.c_int32,
            ctypes.POINTER(SendOp), ctypes.c_int32]
        lib.ed_fanout_send_udp_gso.restype = ctypes.c_int32
        lib.ed_fanout_send_udp_gso.argtypes = lib.ed_fanout_send_udp.argtypes
        lib.ed_fanout_send_multi.restype = ctypes.c_int32
        lib.ed_fanout_send_multi.argtypes = [
            ctypes.c_int, u8p, i32p, ctypes.c_int32, ctypes.c_int32,
            u32p, u32p, u32p, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(Dest), ctypes.c_int32, ctypes.POINTER(SendOp),
            ctypes.c_int32, ctypes.c_int32]
        lib.ed_scalar_baseline_send.restype = ctypes.c_int32
        lib.ed_scalar_baseline_send.argtypes = lib.ed_fanout_send_udp.argtypes
        # the send pipeline (ISSUE 38): one sender thread, jobs in order
        lib.ed_send_job_size.restype = ctypes.c_int32
        lib.ed_send_job_size.argtypes = []
        if lib.ed_send_job_size() != ctypes.sizeof(_SendJobC):
            _load_error = (f"ed_send_job ABI mismatch: library has "
                           f"{lib.ed_send_job_size()} bytes, bridge "
                           f"expects {ctypes.sizeof(_SendJobC)}")
            return None
        jobp = ctypes.POINTER(_SendJobC)
        lib.ed_sender_submit.restype = ctypes.c_int32
        lib.ed_sender_submit.argtypes = [jobp]
        lib.ed_sender_wait.restype = ctypes.c_int32
        lib.ed_sender_wait.argtypes = [jobp]
        lib.ed_sender_drain.restype = None
        lib.ed_sender_drain.argtypes = []
        lib.ed_sender_stop.restype = None
        lib.ed_sender_stop.argtypes = []
        lib.ed_sender_stats.restype = None
        lib.ed_sender_stats.argtypes = [i64p]
        lib.ed_last_send_errno.restype = ctypes.c_int32
        lib.ed_last_send_errno.argtypes = []
        lib.ed_udp_drain.restype = ctypes.c_int64
        lib.ed_udp_drain.argtypes = [i32p, ctypes.c_int32]
        lib.ed_udp_drain_ex.restype = ctypes.c_int64
        lib.ed_udp_drain_ex.argtypes = [i32p, ctypes.c_int32, i64p]
        lib.ed_fanout_render.restype = ctypes.c_int32
        lib.ed_fanout_render.argtypes = [
            u8p, i32p, ctypes.c_int32, ctypes.c_int32,
            u32p, u32p, u32p, ctypes.c_int32,
            ctypes.POINTER(SendOp), ctypes.c_int32,
            u8p, ctypes.c_int32, i32p]
        for fname in ("ed_h264_requant_slice",
                      "ed_h264_requant_slice_cabac"):
            fn = getattr(lib, fname)
            fn.restype = ctypes.c_int32
            fn.argtypes = [
                u8p, ctypes.c_int32, u8p, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32)]
        lib.ed_stage_gather.restype = ctypes.c_int32
        lib.ed_stage_gather.argtypes = [
            u8p, i32p, ctypes.c_int32, ctypes.c_int32, i32p,
            ctypes.c_int32, ctypes.c_int32, u8p, ctypes.c_int32,
            ctypes.c_int32]
        lib.ed_get_stats.restype = None
        lib.ed_get_stats.argtypes = [ctypes.POINTER(EdStats)]
        lib.ed_reset_stats.restype = None
        lib.ed_reset_stats.argtypes = []
        lib.ed_fault_set.restype = None
        lib.ed_fault_set.argtypes = [ctypes.c_int64] * 4
        lib.ed_fault_clear.restype = None
        lib.ed_fault_clear.argtypes = []
        lib.ed_udp_ingest.restype = ctypes.c_int32
        lib.ed_udp_ingest.argtypes = [
            ctypes.c_int, u8p, i32p, i64p, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int64, i64p, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32)]
        # io_uring backend (ISSUE 8): probe + persistent egress/ingest rings
        lib.ed_uring_probe.restype = ctypes.c_int32
        lib.ed_uring_probe.argtypes = []
        lib.ed_uring_egress_new.restype = ctypes.c_void_p
        lib.ed_uring_egress_new.argtypes = [
            ctypes.c_int, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32)]
        lib.ed_uring_free.restype = None
        lib.ed_uring_free.argtypes = [ctypes.c_void_p]
        lib.ed_uring_caps.restype = ctypes.c_int32
        lib.ed_uring_caps.argtypes = [ctypes.c_void_p]
        lib.ed_uring_fd.restype = ctypes.c_int32
        lib.ed_uring_fd.argtypes = [ctypes.c_void_p]
        lib.ed_uring_send_multi.restype = ctypes.c_int32
        lib.ed_uring_send_multi.argtypes = [
            ctypes.c_void_p, u8p, i32p, ctypes.c_int32, ctypes.c_int32,
            u32p, u32p, u32p, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(Dest), ctypes.c_int32, ctypes.POINTER(SendOp),
            ctypes.c_int32]
        # stream-socket egress (ISSUE 14): framed interleave + byte blobs
        lib.ed_stream_send.restype = ctypes.c_int32
        lib.ed_stream_send.argtypes = [
            ctypes.c_int, u8p, i32p, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.c_int32, i32p, ctypes.c_int32, i32p]
        lib.ed_stream_write.restype = ctypes.c_int64
        lib.ed_stream_write.argtypes = [ctypes.c_int, u8p, ctypes.c_int64]
        lib.ed_uring_stream_send.restype = ctypes.c_int32
        lib.ed_uring_stream_send.argtypes = [
            ctypes.c_void_p, ctypes.c_int, u8p, i32p, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.c_uint32, ctypes.c_int32, i32p, ctypes.c_int32, i32p]
        lib.ed_uring_stream_write.restype = ctypes.c_int64
        lib.ed_uring_stream_write.argtypes = [
            ctypes.c_void_p, ctypes.c_int, u8p, ctypes.c_int64]
        lib.ed_uring_ingest_new.restype = ctypes.c_void_p
        lib.ed_uring_ingest_new.argtypes = [
            ctypes.c_int, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32)]
        lib.ed_uring_ingest_drain.restype = ctypes.c_int32
        lib.ed_uring_ingest_drain.argtypes = [
            ctypes.c_void_p, u8p, i32p, i64p, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int64, i64p, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32)]
        lib.ed_wheel_new.restype = ctypes.c_void_p
        lib.ed_wheel_new.argtypes = [ctypes.c_int64]
        lib.ed_wheel_free.argtypes = [ctypes.c_void_p]
        lib.ed_wheel_schedule.restype = ctypes.c_int64
        lib.ed_wheel_schedule.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                          ctypes.c_int64]
        lib.ed_wheel_cancel.restype = ctypes.c_int
        lib.ed_wheel_cancel.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.ed_wheel_advance.restype = ctypes.c_int32
        lib.ed_wheel_advance.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                         i64p, ctypes.c_int32]
        lib.ed_wheel_next.restype = ctypes.c_int64
        lib.ed_wheel_next.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.ed_wheel_pending.restype = ctypes.c_int32
        lib.ed_wheel_pending.argtypes = [ctypes.c_void_p]
        _build_tag = _embedded_build(_SO)
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def require() -> None:
    """Raise ``NativeCoreError`` unless the core is loaded — for callers
    (the TPU engine's boot) that have no honest fallback."""
    if _load() is None:
        raise NativeCoreError(
            f"native core unavailable: {_load_error or 'unknown'}")


def build_info() -> dict:
    """How the library ties to this tree and machine (the marker is read
    from the file once per process, at load)."""
    emb = _build_tag or _embedded_build(_SO) or ("?", "?")
    return {"so": _SO, "loaded": _lib is not None,
            "source_digest": emb[0], "cpu_key": emb[1],
            "built_this_process": _built_here,
            "error": _load_error}


def loaded() -> bool:
    """True if the library is ALREADY loaded — never triggers a build
    (metric scrapes must not spend 100 ms compiling C++)."""
    return _lib is not None


def version() -> str | None:
    lib = _load()
    return lib.ed_version().decode() if lib else None


def get_stats() -> dict[str, int]:
    """Cumulative native data-plane counters (struct ed_stats)."""
    lib = _load()
    assert lib is not None
    s = EdStats()
    lib.ed_get_stats(ctypes.byref(s))
    return {n: getattr(s, n) for n in _STAT_FIELDS}


def reset_stats() -> None:
    lib = _load()
    assert lib is not None
    lib.ed_reset_stats()


def fault_set(eagain_every: int, enobufs_every: int,
              latency_every: int, latency_us: int) -> None:
    """Arm the deterministic egress fault knobs (resilience/inject.py):
    every Nth send-call attempt fails EAGAIN / ENOBUFS or sleeps a
    latency spike before its syscall; setting restarts the schedule."""
    lib = _load()
    assert lib is not None
    lib.ed_fault_set(int(eagain_every), int(enobufs_every),
                     int(latency_every), int(latency_us))


def fault_clear() -> None:
    lib = _load()
    assert lib is not None
    lib.ed_fault_clear()


# ------------------------------------------------------- io_uring backend
_uring_probe_cache: int | None = None


def uring_probe(*, refresh: bool = False) -> int:
    """Boot-time io_uring capability probe (csrc ``ed_uring_probe``).

    Returns a bitmask of ``URING_CAP_*`` (>= 0) when the kernel supports
    io_uring with sendmsg/recvmsg, or ``-errno`` (``-ENOSYS`` pre-5.1,
    ``-EPERM`` under a seccomp deny) — the probe outcome callers turn
    into the GSO fallback rung, never into a hard error.  Cached per
    process: one throwaway ring at boot, zero probes on the hot path."""
    global _uring_probe_cache
    if _uring_probe_cache is not None and not refresh:
        return _uring_probe_cache
    lib = _load()
    if lib is None:
        _uring_probe_cache = -int(getattr(errno, "ENOSYS", 38))
        return _uring_probe_cache
    _uring_probe_cache = int(lib.ed_uring_probe())
    return _uring_probe_cache


class UringEgress:
    """Persistent io_uring over one egress fd (registered send arena,
    linked-SQE batched submission, optional SQPOLL/zerocopy).

    Construction raising ``OSError`` is a PROBE outcome — callers land
    on the GSO rung with one ``egress.backend_fallback`` event, exactly
    the GSO EINVAL probe's shape (never a counted hard_error)."""

    def __init__(self, fd: int, *, depth: int = 256, max_pkt: int = 2048,
                 sqpoll: bool = True, zerocopy: bool = True):
        lib = _load()
        if lib is None:
            raise OSError(errno.ENOSYS, "native core unavailable")
        flags = (URING_F_SQPOLL if sqpoll else 0) | \
                (URING_F_ZEROCOPY if zerocopy else 0)
        err = ctypes.c_int32(0)
        self._lib = lib
        self._h = lib.ed_uring_egress_new(fd, depth, max_pkt, flags,
                                          ctypes.byref(err))
        if not self._h:
            e = -err.value if err.value < 0 else (err.value or errno.ENOSYS)
            raise OSError(e, os.strerror(e))
        self.fd = fd
        self.caps = int(lib.ed_uring_caps(self._h))

    def close(self) -> None:
        if self._h:
            self._lib.ed_uring_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    @property
    def active(self) -> bool:
        return bool(self._h)

    def send_multi(self, ring_data: np.ndarray, ring_len: np.ndarray,
                   seq_off: np.ndarray, ts_off: np.ndarray,
                   ssrc: np.ndarray, dests, ops, n_ops: int,
                   *, trace_id: str | None = None) -> int:
        """``fanout_send_multi``'s contract over the io_uring ring: one
        linked-SQE chain per batch instead of one sendmmsg slot per
        datagram run.  EAGAIN stops report the delivered count (bookmark
        replay); ``last_send_errno`` explains a short return."""
        assert self._h, "closed"
        assert ring_data.dtype == np.uint8 and ring_data.flags.c_contiguous
        seq = np.ascontiguousarray(seq_off, np.uint32)
        ts = np.ascontiguousarray(ts_off, np.uint32)
        sc = np.ascontiguousarray(ssrc, np.uint32)
        assert seq.ndim == 2 and seq.shape == ts.shape == sc.shape
        assert seq.shape[1] >= len(dests)
        opened = _egress_open(self._lib, "native.egress", trace_id,
                              ops=n_ops, backend="io_uring")
        r = self._lib.ed_uring_send_multi(
            self._h, _u8(ring_data),
            _i32(np.ascontiguousarray(ring_len, np.int32)),
            ring_data.shape[0], ring_data.shape[1],
            _u32(seq), _u32(ts), _u32(sc), seq.shape[0], seq.shape[1],
            dests, len(dests), ops, n_ops)
        _egress_close(self._lib, opened, r)
        return int(r)

    def stream_send(self, fd: int, ring_data: np.ndarray,
                    ring_len: np.ndarray, seq_off: int, ts_off: int,
                    ssrc: int, channel: int, slots: np.ndarray,
                    *, trace_id: str | None = None) -> tuple[int, int]:
        """``native.stream_send``'s contract over the ring: the framed
        batch rides one SEND SQE per arena-sized chunk (``fd`` is the
        TARGET stream socket — SQEs carry their own fd, so one shared
        ring serves every TCP connection)."""
        assert self._h, "closed"
        assert ring_data.dtype == np.uint8 and ring_data.flags.c_contiguous
        slots32 = np.ascontiguousarray(slots, np.int32)
        partial = ctypes.c_int32(0)
        opened = _egress_open(self._lib, "native.stream_egress", trace_id,
                              ops=int(len(slots32)), backend="io_uring")
        r = self._lib.ed_uring_stream_send(
            self._h, fd, _u8(ring_data),
            _i32(np.ascontiguousarray(ring_len, np.int32)),
            ring_data.shape[0], ring_data.shape[1],
            seq_off & 0xFFFFFFFF, ts_off & 0xFFFFFFFF, ssrc & 0xFFFFFFFF,
            channel, _i32(slots32), len(slots32), ctypes.byref(partial))
        _egress_close(self._lib, opened, r)
        return int(r), partial.value

    def stream_write(self, fd: int, data) -> int:
        """One byte blob through the ring (HLS bodies on the io_uring
        rung).  Returns bytes written or negative errno."""
        assert self._h, "closed"
        buf = np.frombuffer(data, dtype=np.uint8)
        return int(self._lib.ed_uring_stream_write(self._h, fd, _u8(buf),
                                                   len(buf)))


def stream_send(fd: int, ring_data: np.ndarray, ring_len: np.ndarray,
                seq_off: int, ts_off: int, ssrc: int, channel: int,
                slots: np.ndarray,
                *, trace_id: str | None = None) -> tuple[int, int]:
    """Framed interleaved egress onto one TCP connection: renders the
    4-byte ``$``-channel frame + rewritten RTP header per ring slot in C
    and writes the whole batch through writev — no per-packet Python.

    Returns ``(packets_fully_written, partial_bytes)``; when
    ``partial_bytes > 0`` the next packet is torn mid-frame on the wire
    and the CALLER must deliver its remaining bytes before anything else
    on the connection.  ``last_send_errno`` explains a short return; a
    hard stop with nothing written returns ``(-errno, 0)``."""
    lib = _load()
    assert lib is not None
    assert ring_data.dtype == np.uint8 and ring_data.flags.c_contiguous
    slots32 = np.ascontiguousarray(slots, np.int32)
    partial = ctypes.c_int32(0)
    opened = _egress_open(lib, "native.stream_egress", trace_id,
                          ops=int(len(slots32)), backend="writev")
    r = lib.ed_stream_send(
        fd, _u8(ring_data), _i32(np.ascontiguousarray(ring_len, np.int32)),
        ring_data.shape[0], ring_data.shape[1],
        seq_off & 0xFFFFFFFF, ts_off & 0xFFFFFFFF, ssrc & 0xFFFFFFFF,
        channel, _i32(slots32), len(slots32), ctypes.byref(partial))
    _egress_close(lib, opened, r)
    return int(r), partial.value


def stream_write(fd: int, data) -> int:
    """Plain byte-blob write to a stream socket through the native
    egress accounting (the HLS body path's writev rung).  Returns bytes
    written (short on EAGAIN) or negative errno on a hard stop with
    nothing written."""
    lib = _load()
    assert lib is not None
    buf = np.frombuffer(data, dtype=np.uint8)
    return int(lib.ed_stream_write(fd, _u8(buf), len(buf)))


class UringIngest:
    """Multishot-recvmsg ingest ring for one pusher socket: datagrams
    land in CQEs from one persistent armed SQE; ``drain`` admits them
    into the packet ring with ``ed_udp_ingest`` semantics."""

    def __init__(self, fd: int, *, max_pkt: int = 2048):
        lib = _load()
        if lib is None:
            raise OSError(errno.ENOSYS, "native core unavailable")
        err = ctypes.c_int32(0)
        self._lib = lib
        self._h = lib.ed_uring_ingest_new(fd, max_pkt, ctypes.byref(err))
        if not self._h:
            e = -err.value if err.value < 0 else (err.value or errno.ENOSYS)
            raise OSError(e, os.strerror(e))
        self.fd = fd
        #: the ring's pollable fd — the event-loop wakeup source (the
        #: SOCKET goes quiet once the multishot arm consumes its queue)
        self.ring_fd = int(lib.ed_uring_fd(self._h))

    def close(self) -> None:
        if self._h:
            self._lib.ed_uring_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def drain(self, ring_data: np.ndarray, ring_len: np.ndarray,
              ring_arrival: np.ndarray, now_ms: int, head: int,
              max_pkts: int = 256) -> tuple[int, int, int]:
        """Returns (n_admitted, new_head, oversize_dropped)."""
        assert self._h, "closed"
        h = ctypes.c_int64(head)
        drops = ctypes.c_int32(0)
        n = self._lib.ed_uring_ingest_drain(
            self._h, _u8(ring_data), _i32(ring_len), _i64(ring_arrival),
            ring_data.shape[0], ring_data.shape[1], now_ms,
            ctypes.byref(h), max_pkts, ctypes.byref(drops))
        if n < 0:
            raise OSError(-n, os.strerror(-n))
        return n, h.value, drops.value


#: fd → UringIngest for sockets the server armed for io_uring ingest
#: (server/app.py arms this when the effective egress backend is
#: io_uring and the probe reports multishot recvmsg).  ``udp_ingest``
#: routes through it transparently so every ring-drain call site keeps
#: its recvmmsg fallback untouched.
_uring_ingests: dict[int, "UringIngest"] = {}


def uring_ingest_arm(fd: int, *, max_pkt: int = 2048) -> int | None:
    """Arm multishot io_uring ingest for ``fd``.  Returns the ring's
    pollable fd (the event-loop wakeup source — the SOCKET fd goes
    quiet once the multishot arm consumes its queue, so watching it
    would strand completions until the buffer pool exhausted), or None
    (recvmmsg stays in charge) when the kernel lacks the caps —
    callers treat that as a probe outcome, not an error."""
    ing = _uring_ingests.get(fd)
    if ing is not None:
        return ing.ring_fd
    caps = uring_probe()
    if caps < 0 or not caps & URING_CAP_RECV_MULTI:
        return None
    try:
        ing = _uring_ingests[fd] = UringIngest(fd, max_pkt=max_pkt)
    except OSError:
        return None
    return ing.ring_fd


def uring_ingest_disarm(fd: int | None = None) -> None:
    """Drop one armed ingest ring (or all of them when fd is None)."""
    if fd is None:
        for ing in _uring_ingests.values():
            ing.close()
        _uring_ingests.clear()
        return
    ing = _uring_ingests.pop(fd, None)
    if ing is not None:
        ing.close()


def uring_ingest_armed(fd: int) -> bool:
    """True while ``fd`` still routes through an armed ingest ring.
    Watchers poll this after a drain: ``udp_ingest`` disarms (and closes
    the ring fd) on any io_uring failure, and the closed fd number must
    be dropped from the event loop before a new socket recycles it."""
    return fd in _uring_ingests


def _u8(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _i32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _i64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _u32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))


def make_dests(addrs: list[tuple[str, int]]) -> ctypes.Array:
    arr = (Dest * len(addrs))()
    for i, (ip, port) in enumerate(addrs):
        arr[i].ip_be = struct.unpack("=I", socket.inet_aton(ip))[0]
        arr[i].port_be = socket.htons(port)
    return arr


def make_ops(pairs: list[tuple[int, int]]) -> ctypes.Array:
    arr = (SendOp * len(pairs))()
    for i, (slot, out) in enumerate(pairs):
        arr[i].slot = slot
        arr[i].out = out
    return arr


def ops_from_numpy(arr: np.ndarray):
    """[N, 2] int32 C-contiguous (slot, out) rows → SendOp pointer.

    The live fan-out builds its op list with numpy slicing (no per-op
    Python); the int32 pair layout matches ``struct ed_sendop`` exactly.
    The array must stay alive for the duration of the native call."""
    assert arr.dtype == np.int32 and arr.ndim == 2 and arr.shape[1] == 2
    assert arr.flags.c_contiguous
    return ctypes.cast(arr.ctypes.data, ctypes.POINTER(SendOp))


def fanout_send_udp(fd: int, ring_data: np.ndarray, ring_len: np.ndarray,
                    seq_off: np.ndarray, ts_off: np.ndarray,
                    ssrc: np.ndarray, dests, ops, n_ops: int) -> int:
    lib = _load()
    assert lib is not None
    assert ring_data.dtype == np.uint8 and ring_data.flags.c_contiguous
    return lib.ed_fanout_send_udp(
        fd, _u8(ring_data), _i32(np.ascontiguousarray(ring_len, np.int32)),
        ring_data.shape[0], ring_data.shape[1],
        _u32(np.ascontiguousarray(seq_off, np.uint32)),
        _u32(np.ascontiguousarray(ts_off, np.uint32)),
        _u32(np.ascontiguousarray(ssrc, np.uint32)),
        dests, len(dests), ops, n_ops)


def fanout_send_udp_gso(fd: int, ring_data: np.ndarray, ring_len: np.ndarray,
                        seq_off: np.ndarray, ts_off: np.ndarray,
                        ssrc: np.ndarray, dests, ops, n_ops: int) -> int:
    """GSO egress: same-subscriber runs coalesce into UDP_SEGMENT
    super-datagrams (~40x fewer udp_sendmsg traversals). Negative return
    may mean the kernel lacks GSO — callers fall back to fanout_send_udp."""
    lib = _load()
    assert lib is not None
    assert ring_data.dtype == np.uint8 and ring_data.flags.c_contiguous
    return lib.ed_fanout_send_udp_gso(
        fd, _u8(ring_data), _i32(np.ascontiguousarray(ring_len, np.int32)),
        ring_data.shape[0], ring_data.shape[1],
        _u32(np.ascontiguousarray(seq_off, np.uint32)),
        _u32(np.ascontiguousarray(ts_off, np.uint32)),
        _u32(np.ascontiguousarray(ssrc, np.uint32)),
        dests, len(dests), ops, n_ops)


def fanout_send_multi(fd: int, ring_data: np.ndarray, ring_len: np.ndarray,
                      seq_off: np.ndarray, ts_off: np.ndarray,
                      ssrc: np.ndarray, dests, ops, n_ops: int,
                      *, use_gso: bool | int = True,
                      trace_id: str | None = None,
                      submit: bool = False) -> "int | SendJob":
    """Multi-source egress: ``seq_off``/``ts_off``/``ssrc`` are
    [n_src, n_outs]; ONE C call sends every source's window (the hot loop
    makes one Python→C transition per pass instead of n_src).

    ``use_gso``: 0/False plain sendmmsg, 1/True UDP_SEGMENT, 2 the
    scalar sendto baseline (the forced ``egress_backend="scalar"``
    rung).  ``trace_id`` stamps the egress span for session correlation
    (the engine passes the stream's session trace).

    ``submit``: hand the call to the native sender thread and return its
    ``SendJob`` at once (the engine's way: the loop thread plans and
    settles other streams while this one is sent; jobs are sent one at
    a time, in submission order).  Every array passed must stay
    unwritten until the job is done; the job keeps them alive.  (A flag
    and not a function of its own: the benchmark's control,
    ``benchmark/tests/broken_child.py``, alters the answers by wrapping
    this one name, so the engine's sends have to enter through it.)"""
    lib = _load()
    assert lib is not None
    assert ring_data.dtype == np.uint8 and ring_data.flags.c_contiguous
    seq = np.ascontiguousarray(seq_off, np.uint32)
    ts = np.ascontiguousarray(ts_off, np.uint32)
    sc = np.ascontiguousarray(ssrc, np.uint32)
    assert seq.ndim == 2 and seq.shape == ts.shape == sc.shape
    # the param row may be wider than the dest table (fewer real sockets
    # than logical subscribers); ops only reference outs < len(dests)
    assert seq.shape[1] >= len(dests)
    if submit:
        rlen = np.ascontiguousarray(ring_len, np.int32)
        c = _SendJobC(
            ring_data.ctypes.data, rlen.ctypes.data, seq.ctypes.data,
            ts.ctypes.data, sc.ctypes.data, ctypes.addressof(dests),
            ctypes.cast(ops, ctypes.c_void_p).value,
            fd, ring_data.shape[0], ring_data.shape[1], seq.shape[0],
            seq.shape[1], len(dests), n_ops, int(use_gso))
        job = SendJob(lib, c, (ring_data, rlen, seq, ts, sc, dests, ops))
        rc = lib.ed_sender_submit(job._ref)
        if rc < 0:
            raise OSError(-rc, "native sender thread: "
                          + os.strerror(-rc))
        return job
    # gso: 0 = plain sendmmsg, 1 = GSO, 2 = scalar sendto rung
    opened = _egress_open(lib, "native.egress", trace_id, ops=n_ops,
                          gso=int(use_gso))
    r = lib.ed_fanout_send_multi(
        fd, _u8(ring_data), _i32(np.ascontiguousarray(ring_len, np.int32)),
        ring_data.shape[0], ring_data.shape[1],
        _u32(seq), _u32(ts), _u32(sc), seq.shape[0], seq.shape[1],
        dests, len(dests), ops, n_ops, int(use_gso))
    _egress_close(lib, opened, r)
    return r


def h264_requant_slice(nal: bytes, *, width_mbs: int, height_mbs: int,
                       log2_max_frame_num: int, poc_type: int,
                       log2_max_poc_lsb: int, pic_init_qp: int,
                       pps_id: int, deblocking_control: bool,
                       bottom_field_poc: bool, delta_qp: int,
                       chroma_qp_offset: int = 0,
                       cabac: bool = False,
                       num_ref_l0_default: int = 0,
                       weighted_pred: bool = False
                       ) -> tuple[bytes, int, int] | None:
    """Native slice requant — CAVLC, or the CABAC walk when
    ``cabac=True`` (the caller passes the PPS's entropy flag) →
    (nal, mbs_in_slice, level_blocks);
    level_blocks counts exactly what the Python path batches (17 rows
    per I_16x16 MB, 16 per I_4x4, +8 chroma rows per chroma-bearing MB)
    so RequantStats.blocks is engine-independent.  None = unsupported/
    malformed (caller passes the slice through or falls back to the
    Python path)."""
    lib = _load()
    assert lib is not None
    entry = (lib.ed_h264_requant_slice_cabac if cabac
             else lib.ed_h264_requant_slice)
    src = np.frombuffer(nal, dtype=np.uint8)
    cap = len(nal) * 2 + 256
    out = np.zeros(cap, dtype=np.uint8)
    mbs = ctypes.c_int32(0)
    blocks = ctypes.c_int32(0)
    n = entry(
        _u8(src), len(nal), _u8(out), cap, width_mbs, height_mbs,
        log2_max_frame_num, poc_type, log2_max_poc_lsb, pic_init_qp,
        pps_id, 1 if deblocking_control else 0,
        1 if bottom_field_poc else 0, delta_qp, chroma_qp_offset,
        num_ref_l0_default, 1 if weighted_pred else 0,
        ctypes.byref(mbs), ctypes.byref(blocks))
    if n == -3:                      # tiny chance: expansion past 2x
        cap = len(nal) * 4 + 4096
        out = np.zeros(cap, dtype=np.uint8)
        n = entry(
            _u8(src), len(nal), _u8(out), cap, width_mbs, height_mbs,
            log2_max_frame_num, poc_type, log2_max_poc_lsb, pic_init_qp,
            pps_id, 1 if deblocking_control else 0,
            1 if bottom_field_poc else 0, delta_qp, chroma_qp_offset,
            num_ref_l0_default, 1 if weighted_pred else 0,
            ctypes.byref(mbs), ctypes.byref(blocks))
    return (out[:n].tobytes(), mbs.value, blocks.value) if n > 0 else None


def stage_gather(ring_data: np.ndarray, ring_len: np.ndarray,
                 slots: np.ndarray, prefix_width: int,
                 out_rows_buf: np.ndarray) -> int:
    """Pack ``slots``' ring prefixes + le32 lengths into the rows of
    ``out_rows_buf`` ([rows, stride] uint8, C-contiguous) — the megabatch
    scheduler's H2D staging gather (one memcpy walk per stream per wake;
    padding rows are zeroed).  Returns rows written, negative on bad
    arguments."""
    lib = _load()
    assert lib is not None
    assert ring_data.dtype == np.uint8 and ring_data.flags.c_contiguous
    assert out_rows_buf.dtype == np.uint8 and out_rows_buf.flags.c_contiguous
    slots32 = np.ascontiguousarray(slots, np.int32)
    return lib.ed_stage_gather(
        _u8(ring_data), _i32(np.ascontiguousarray(ring_len, np.int32)),
        ring_data.shape[0], ring_data.shape[1], _i32(slots32), len(slots32),
        prefix_width, _u8(out_rows_buf), out_rows_buf.shape[1],
        out_rows_buf.shape[0])


def last_send_errno() -> int:
    """Why the calling thread's last send stopped short (see C header)."""
    lib = _load()
    assert lib is not None
    return lib.ed_last_send_errno()


def sender_drain() -> None:
    """Block until the native sender has nothing queued and nothing in
    flight (a wake's barrier on its way out through an exception)."""
    if _lib is not None:
        _lib.ed_sender_drain()


def sender_stop() -> None:
    """Send what is queued, then end the sender thread (server stop);
    a later ``submit`` starts another."""
    if _lib is not None:
        _lib.ed_sender_stop()


def sender_stats() -> dict[str, int]:
    """``starts`` (threads started so far), ``jobs`` (submitted),
    ``running`` and ``max_in_flight`` — the most ``fanout_send_multi``
    calls ever running at one instant, on any thread."""
    lib = _load()
    assert lib is not None
    out = (ctypes.c_int64 * 4)()
    lib.ed_sender_stats(out)
    return dict(zip(("starts", "jobs", "running", "max_in_flight"),
                    (int(v) for v in out)))


def scalar_baseline_send(fd: int, ring_data: np.ndarray,
                         ring_len: np.ndarray, seq_off: np.ndarray,
                         ts_off: np.ndarray, ssrc: np.ndarray,
                         dests, ops, n_ops: int) -> int:
    """The reference's scalar hot loop in C (one sendto per packet per
    output, single thread) — the honest vs_baseline denominator."""
    lib = _load()
    assert lib is not None
    assert ring_data.dtype == np.uint8 and ring_data.flags.c_contiguous
    return lib.ed_scalar_baseline_send(
        fd, _u8(ring_data), _i32(np.ascontiguousarray(ring_len, np.int32)),
        ring_data.shape[0], ring_data.shape[1],
        _u32(np.ascontiguousarray(seq_off, np.uint32)),
        _u32(np.ascontiguousarray(ts_off, np.uint32)),
        _u32(np.ascontiguousarray(ssrc, np.uint32)),
        dests, len(dests), ops, n_ops)


def udp_drain(fds: list[int]) -> int:
    """Discard-drain all pending datagrams on the given sockets."""
    lib = _load()
    assert lib is not None
    arr = np.asarray(fds, dtype=np.int32)
    return lib.ed_udp_drain(_i32(arr), len(fds))


def udp_drain_ex(fds: list[int]) -> tuple[int, int]:
    """Discard-drain; returns (messages, total_bytes).  With UDP_GRO
    receivers, messages are coalesced super-datagrams and
    bytes // wire_packet_size recovers the wire-packet count."""
    lib = _load()
    assert lib is not None
    arr = np.asarray(fds, dtype=np.int32)
    b = ctypes.c_int64(0)
    n = lib.ed_udp_drain_ex(_i32(arr), len(fds), ctypes.byref(b))
    return n, b.value


def fanout_render(ring_data: np.ndarray, ring_len: np.ndarray,
                  seq_off: np.ndarray, ts_off: np.ndarray, ssrc: np.ndarray,
                  ops, n_ops: int, out_stride: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    lib = _load()
    assert lib is not None
    out = np.zeros((n_ops, out_stride), dtype=np.uint8)
    lens = np.zeros(n_ops, dtype=np.int32)
    r = lib.ed_fanout_render(
        _u8(ring_data), _i32(np.ascontiguousarray(ring_len, np.int32)),
        ring_data.shape[0], ring_data.shape[1],
        _u32(np.ascontiguousarray(seq_off, np.uint32)),
        _u32(np.ascontiguousarray(ts_off, np.uint32)),
        _u32(np.ascontiguousarray(ssrc, np.uint32)),
        len(ssrc), ops, n_ops, _u8(out), out_stride, _i32(lens))
    if r < 0:
        raise OSError(-r, os.strerror(-r))
    return out, lens


def udp_ingest(fd: int, ring_data: np.ndarray, ring_len: np.ndarray,
               ring_arrival: np.ndarray, now_ms: int, head: int,
               max_pkts: int = 256) -> tuple[int, int, int]:
    """Returns (n_admitted, new_head, oversize_dropped).

    Routes through an armed multishot io_uring ingest ring when
    ``uring_ingest_arm(fd)`` succeeded for this socket; any io_uring
    failure disarms the fd and falls back to the recvmmsg drain for the
    rest of the process (a degradation, never a dropped drain)."""
    ing = _uring_ingests.get(fd)
    if ing is not None:
        try:
            return ing.drain(ring_data, ring_len, ring_arrival, now_ms,
                             head, max_pkts)
        except OSError:
            uring_ingest_disarm(fd)
    lib = _load()
    assert lib is not None
    h = ctypes.c_int64(head)
    drops = ctypes.c_int32(0)
    n = lib.ed_udp_ingest(
        fd, _u8(ring_data), _i32(ring_len), _i64(ring_arrival),
        ring_data.shape[0], ring_data.shape[1], now_ms,
        ctypes.byref(h), max_pkts, ctypes.byref(drops))
    if n < 0:
        raise OSError(-n, os.strerror(-n))
    return n, h.value, drops.value


class TimerWheel:
    """1 ms hashed timer wheel (finer than the reference's 10 ms floor)."""

    def __init__(self, now_ms: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError("native core unavailable")
        self._lib = lib
        self._w = lib.ed_wheel_new(now_ms)

    def close(self):
        if self._w:
            self._lib.ed_wheel_free(self._w)
            self._w = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def schedule(self, delay_ms: int, user_data: int) -> int:
        return self._lib.ed_wheel_schedule(self._w, delay_ms, user_data)

    def cancel(self, timer_id: int) -> bool:
        return bool(self._lib.ed_wheel_cancel(self._w, timer_id))

    def advance(self, now_ms: int, max_out: int | None = None) -> list[int]:
        """Keys of the timers due by ``now_ms``.  Room for every pending
        timer by default: the C walk stops at ``max_out`` yet moves its
        clock to ``now_ms``, so a timer it had no room for would fire a
        revolution (4,096 ms) late — and the pump readies a stream by
        its timer."""
        if max_out is None:
            max_out = max(self.pending, 1)
        out = np.zeros(max_out, dtype=np.int64)
        n = self._lib.ed_wheel_advance(self._w, now_ms, _i64(out), max_out)
        return out[:n].tolist()

    def next_deadline(self, now_ms: int) -> int:
        return self._lib.ed_wheel_next(self._w, now_ms)

    @property
    def pending(self) -> int:
        return self._lib.ed_wheel_pending(self._w)


# ------------------------------------------------------------- observability
def _collect_native_stats() -> None:
    """Pre-scrape collector: mirror the C data-plane's cumulative
    ``ed_stats`` snapshot into the obs counter families.  A no-op until
    the library is loaded — a metrics scrape must never trigger a
    compile; the families simply read 0 like any idle counter."""
    if _lib is None:
        return
    from . import obs
    s = get_stats()
    obs.EGRESS_SENDMMSG_CALLS.set_to(s["sendmmsg_calls"])
    obs.EGRESS_SENDTO_CALLS.set_to(s["sendto_calls"])
    obs.EGRESS_PACKETS.set_to(s["send_packets"])
    obs.EGRESS_BYTES.set_to(s["bytes_to_wire"])
    obs.EGRESS_GSO_SUPERS.set_to(s["gso_supers"])
    obs.EGRESS_GSO_SEGMENTS.set_to(s["gso_segments"])
    obs.EGRESS_EAGAIN.set_to(s["eagain_stops"])
    obs.EGRESS_SEND_ERRORS.set_to(s["hard_errors"])
    obs.INGEST_RECVMMSG_CALLS.set_to(s["recvmmsg_calls"])
    obs.INGEST_DATAGRAMS.set_to(s["recv_datagrams"])
    obs.INGEST_BYTES.set_to(s["recv_bytes"])
    obs.INGEST_OVERSIZE_DROPPED.set_to(s["oversize_dropped"])
    # per-call clock_gettime deltas → cumulative busy-seconds counters
    # (the native half of the egress_native phase attribution)
    obs.EGRESS_BUSY_SECONDS.set_to(s["send_ns"] / 1e9)
    obs.INGEST_BUSY_SECONDS.set_to(s["ingest_ns"] / 1e9)
    obs.STAGE_GATHER_BUSY_SECONDS.set_to(s["stage_gather_ns"] / 1e9)
    obs.STAGE_GATHER_BYTES.set_to(s["staged_bytes"])
    # io_uring backend tail (ISSUE 8): submission/completion volume plus
    # the zerocopy honesty pair — completions AND how many the kernel
    # copied anyway (loopback copies by design; hiding that would make
    # the zerocopy figure a lie)
    obs.IO_URING_SQE.set_to(s["uring_sqes"])
    obs.IO_URING_CQE.set_to(s["uring_cqes"])
    obs.IO_URING_SUBMITS.set_to(s["uring_submits"])
    obs.IO_URING_ZC_COMPLETIONS.set_to(s["uring_zc_completions"])
    obs.IO_URING_ZC_COPIED.set_to(s["uring_zc_copied"])
    # egress faults injected by the C-side ed_fault_* knobs land under
    # their own site label next to the Python-side injection sites
    if s["fault_injections"]:
        obs.FAULT_INJECTED.set_to(s["fault_injections"],
                                  site="egress_native")


def _register_collector() -> None:
    from .obs import REGISTRY
    REGISTRY.add_collector(_collect_native_stats)


_register_collector()
