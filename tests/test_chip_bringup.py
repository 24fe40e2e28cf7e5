"""PR 21 bring-up: the device is named, a CPU run cannot pass for a chip
run, and the pieces that used to hide a fallback now count it.

* ``chip_smoke.py`` at its debug size passes under ``JAX_PLATFORMS=cpu``
  and its parent process never imports JAX;
* ``--tpu-fanout`` refuses to boot on a non-TPU backend nobody asked for
  by name, and boots (reporting ``platform cpu``) when asked;
* the compile-cache switch sets no directory where
  ``JAX_COMPILATION_CACHE_DIR`` is set and the fixed in-checkout path
  where it is not;
* the three formerly silent device handlers count what they swallow;
* the native loader rebuilds when a tracked source changes under an
  existing ``.so``.
"""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from easydarwin_tpu import device, native, obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, env_drop=(), timeout=300):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


# ------------------------------------------------------------ chip_smoke
def test_chip_smoke_tiny_passes_on_named_cpu_and_parent_stays_off_jax(
        tmp_path):
    code = (
        "import runpy, sys\n"
        f"sys.argv = ['chip_smoke.py', '--sources', '2', '--players', "
        f"'8', '--fps', '15', '--out', {str(tmp_path)!r}]\n"
        "try:\n"
        "    runpy.run_path('chip_smoke.py', run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    rc = e.code\n"
        "print('JAX_IN_PARENT', 'jax' in sys.modules, flush=True)\n"
        "sys.exit(rc)\n")
    r = _run(["-c", code], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-1500:]
    lines = r.stdout.strip().splitlines()
    assert lines[-1] == "JAX_IN_PARENT False"
    import json
    last = json.loads(lines[-2])
    assert last["ok"] is True and set(last) == {"ok", "device"}
    assert last["device"]["platform"] == "cpu"
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert "server reports platform cpu" in r.stdout


def test_chip_smoke_full_width_refuses_a_cpu(tmp_path):
    """No size argument = the chip run: a CPU backend fails it fast,
    with no result line — even a CPU asked for by name."""
    r = _run(["chip_smoke.py", "--out", str(tmp_path)],
             {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not a TPU" in r.stdout


# ------------------------------------------------------- boot resolution
def test_cpu_must_be_asked_for_first(monkeypatch):
    for val, want in (("cpu", True), ("cpu,tpu", True), (" CPU ", True),
                      ("tpu,cpu", False), ("tpu", False), ("", False)):
        monkeypatch.setenv("JAX_PLATFORMS", val)
        assert device.cpu_requested() is want, val
    monkeypatch.delenv("JAX_PLATFORMS")
    assert device.cpu_requested() is False


def test_tpu_fanout_boot_refused_without_named_cpu():
    args = ["-m", "easydarwin_tpu", "--tpu-fanout", "-x", "-p", "0",
            "--service-port", "0", "--bind-ip", "127.0.0.1"]
    r = _run(args, env_drop=("JAX_PLATFORMS",))
    if "platform=tpu" in r.stdout:
        pytest.skip("a TPU is present: the refusal cannot be shown here")
    assert r.returncode == 3, r.stdout[-800:] + r.stderr[-800:]
    assert "boot refused" in r.stderr and "listening" not in r.stdout
    # a fallback entry is not asking for the CPU (the chip machines
    # export exactly this)
    r = _run(args, {"JAX_PLATFORMS": "tpu,cpu"})
    if "platform=tpu" not in r.stdout:
        assert r.returncode == 3, r.stdout[-800:] + r.stderr[-800:]
    r = _run(args, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-800:]
    assert "tpu_fanout=on platform=cpu" in r.stdout
    assert "JAX_PLATFORMS=cpu" in r.stdout


# ----------------------------------------------------------- compile cache
def test_compile_cache_directory_rule(monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    device.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in dict(calls)
    assert dict(calls)["jax_persistent_cache_min_compile_time_secs"] == 0.0
    calls.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    device.enable_compile_cache()
    assert dict(calls)["jax_compilation_cache_dir"] == device.CACHE_DIR
    # fixed, inside the checkout, git-ignored
    assert device.CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_launchers_give_one_child_the_chip(monkeypatch):
    """soak's node 0 inherits the platform selection, every other node
    is started on the CPU by name; bench.py holds the chip itself and
    pins its composed soak the same way."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import soak
    finally:
        sys.path.pop(0)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert soak._node_env(0)["JAX_PLATFORMS"] == "tpu,cpu"
    assert soak._node_env(1)["JAX_PLATFORMS"] == "cpu"
    assert soak._node_env(2)["JAX_PLATFORMS"] == "cpu"
    with open(os.path.join(REPO, "bench.py")) as f:
        assert 'env=dict(os.environ, JAX_PLATFORMS="cpu")' in f.read()


# ------------------------------------------------ the smoke's ladder gate
def _ladder_verdict(down, up, level, events):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    smoke = chip_smoke.Smoke.__new__(chip_smoke.Smoke)
    smoke.failures, smoke.facts = [], {}
    smoke.events = [dict(e, event="ladder.degrade") for e in events]
    smoke.check_ladder({
        'resilience_transitions_total{direction="down"}': down,
        'resilience_transitions_total{direction="up"}': up,
        'resilience_ladder_level{stream="/live/cam00"}': level})
    return smoke.failures


def test_smoke_ladder_gate_tolerates_only_a_recovered_slo_burn_step():
    burn = {"stream": "/live/cam00", "from_rung": "megabatch",
            "rung": "device", "reason": "slo_burn"}
    assert _ladder_verdict(0, 0, 0, []) == []
    assert _ladder_verdict(1, 1, 0, [burn]) == []
    # a device reason, a rung the CPU serves, an unrecovered step, a
    # stream off rung 0, and a move nobody can explain all fail
    assert _ladder_verdict(1, 1, 0, [dict(burn, reason="device_errors")])
    assert _ladder_verdict(1, 1, 0, [dict(burn, from_rung="device",
                                          rung="cpu")])
    assert _ladder_verdict(1, 0, 0, [burn])
    assert _ladder_verdict(1, 1, 1, [burn])
    assert _ladder_verdict(1, 1, 0, [])


# ------------------------------------------- formerly silent handlers
def _swallowed(site: str) -> float:
    return obs.DEVICE_ERRORS_SWALLOWED.value(site=site)


def test_vod_device_rows_failure_is_counted(monkeypatch):
    import jax
    from easydarwin_tpu.vod.cache import CachedWindow
    win = CachedWindow.__new__(CachedWindow)
    win._device = None
    win._on_device = None
    win.device_uploads = 0
    win.staged = np.zeros((16, 100), np.uint8)

    def boom(_x):
        raise RuntimeError("no device")
    monkeypatch.setattr(jax, "device_put", boom)
    before = _swallowed("vod_device_rows")
    assert win.device_rows() is None
    assert _swallowed("vod_device_rows") == before + 1
    assert win.device_uploads == 0


def test_storage_parity_failure_is_counted(monkeypatch):
    from easydarwin_tpu.models import relay_pipeline
    from easydarwin_tpu.storage.codec import StripeCodec

    def boom(_rows, _coeff):
        raise RuntimeError("no device")
    blobs = [bytes([i + 1]) * 300 for i in range(4)]
    want = StripeCodec(4, 2, use_device=False).parity(blobs)
    monkeypatch.setattr(relay_pipeline, "fec_parity_window_step", boom)
    before = _swallowed("storage_parity")
    codec = StripeCodec(4, 2, use_device=True)
    assert codec.parity(blobs) == want          # host parity served
    assert _swallowed("storage_parity") == before + 1
    assert codec.device_passes == 0


async def test_megabatch_mesh_failure_is_counted(monkeypatch, tmp_path):
    from easydarwin_tpu.parallel import mesh as mesh_mod
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    monkeypatch.setattr(mesh_mod, "make_megabatch_mesh", lambda n: None)
    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       tpu_fanout=True, megabatch_devices=4,
                       log_folder=str(tmp_path))
    app = StreamingServer(cfg)
    before = _swallowed("megabatch_mesh")
    await app.start()
    try:
        assert app.pump.mesh is None
        assert _swallowed("megabatch_mesh") == before + 1
        assert app.device_info["platform"] == "cpu"
        info = app.server_info()
        assert info["Platform"] == "cpu" and info["NativeCore"] == "1"
    finally:
        await app.stop()
    with open(tmp_path / "error.log") as f:
        assert "megabatch mesh unavailable" in f.read()


# ------------------------------------------------------------ native core
def test_native_loader_rebuilds_when_a_tracked_source_changes(
        monkeypatch, tmp_path):
    csrc = tmp_path / "csrc"
    shutil.copytree(os.path.join(REPO, "csrc"), csrc,
                    ignore=shutil.ignore_patterns("*.so", "*.tmp"))
    monkeypatch.setenv("CXXFLAGS", "-O0 -fPIC -std=c++17")   # a fast build
    for name, val in (("_CSRC", str(csrc)),
                      ("_SO", str(csrc / "libedtpu_core.so")),
                      ("_SO_OVERRIDE", None), ("_lib", None),
                      ("_tried", False), ("_load_error", ""),
                      ("_built_here", False)):
        monkeypatch.setattr(native, name, val)
    assert native._load() is not None, native._load_error
    first = native._embedded_build(native._SO)
    assert first == (native.source_digest(), native.cpu_key())
    assert native._built_here

    # an edit under the existing .so: the next load must not trust it
    with open(csrc / "edtpu_core.h", "a") as f:
        f.write("\n/* edited under an existing library */\n")
    assert native.source_digest() != first[0]
    for name, val in (("_lib", None), ("_tried", False),
                      ("_built_here", False)):
        monkeypatch.setattr(native, name, val)
    assert native._load() is not None, native._load_error
    assert native._built_here
    assert native._embedded_build(native._SO)[0] == native.source_digest()

    # a library built for another CPU is rebuilt too
    monkeypatch.setattr(native, "cpu_key", lambda: "0123456789abcdef")
    for name, val in (("_lib", None), ("_tried", False),
                      ("_built_here", False)):
        monkeypatch.setattr(native, name, val)
    native._load()
    assert native._built_here


def test_native_core_is_required_not_optional(monkeypatch):
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)
    monkeypatch.setattr(native, "_load_error", "make failed: no g++")
    with pytest.raises(native.NativeCoreError, match="no g\\+\\+"):
        native.require()
