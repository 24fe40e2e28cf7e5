"""CLI entry: ``python -m easydarwin_tpu [-c config.toml] [options]``.

The ``main.cpp`` equivalent (CLI parse ``main.cpp:323-385``) minus the fork
watchdog (see ``server.supervisor`` for the restart loop).
"""

from __future__ import annotations

import time

#: stands in for the process's start where the OS keeps no record of it
_FIRST_LINE_NS = time.perf_counter_ns()

import argparse                              # noqa: E402
import asyncio                               # noqa: E402
import os                                    # noqa: E402
import signal                                # noqa: E402
import sys                                   # noqa: E402

from .obs.boot import BootPhases             # noqa: E402
from .server import ServerConfig, StreamingServer  # noqa: E402


#: exit code when tpu_fanout is on and the engine tier cannot run where
#: it was told to (no TPU and no explicit JAX_PLATFORMS=cpu, or a native
#: core that will not build/load) — distinct from EXIT_RESTART and from
#: argparse's 2
EXIT_NO_DEVICE = 3


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="easydarwin_tpu",
        description="TPU-native RTSP streaming/relay server")
    p.add_argument("-c", "--config", help="TOML config file")
    p.add_argument("-p", "--rtsp-port", type=int, help="RTSP listen port")
    p.add_argument("--service-port", type=int, help="REST API port")
    p.add_argument("--bind-ip", help="bind address")
    p.add_argument("--movie-folder", help="VOD media directory")
    p.add_argument("--module-folder",
                   help="directory of plugin .py modules (LoadModules)")
    p.add_argument("--tpu-fanout", action="store_true",
                   help="enable the TPU batch fan-out engine")
    p.add_argument("-S", "--stats-interval", type=int, metavar="N",
                   help="print status columns every N seconds (-S display)")
    p.add_argument("--status-file", help="write a JSON status snapshot here "
                   "on an interval (server_status equivalent)")
    p.add_argument("-x", "--exit-after-boot", action="store_true",
                   help="boot, print status, exit (config check)")
    p.add_argument("-w", "--watchdog", action="store_true",
                   help="run under the auto-restart supervisor")
    return p


def config_from_args(args) -> ServerConfig:
    is_xml = False
    if args.config:
        with open(args.config, "rb") as f:
            head = f.read(256).lstrip()
        # sniff content, not filename: reference configs travel under
        # arbitrary names (easydarwin.conf, EASYDARWIN.XML, ...)
        is_xml = head.startswith((b"<?xml", b"<!DOCTYPE", b"<CONFIGURATION"))
    if is_xml:
        # reference easydarwin.xml migration path
        from .server.config import load_reference_xml
        cfg, unmapped = load_reference_xml(args.config)
        if unmapped:
            print(f"note: {len(unmapped)} reference prefs have no "
                  f"counterpart here (first few: {unmapped[:5]})",
                  flush=True)
    elif args.config:
        cfg = ServerConfig.from_toml(args.config)
    else:
        cfg = ServerConfig()
    for k in ("rtsp_port", "service_port", "bind_ip", "movie_folder",
              "module_folder"):
        v = getattr(args, k)
        if v is not None:
            setattr(cfg, k, v)
    if args.tpu_fanout:
        cfg.tpu_fanout = True
    if args.stats_interval is not None:
        cfg.stats_interval_sec = args.stats_interval
    if args.status_file is not None:
        cfg.status_file_path = args.status_file
    return cfg


async def amain(cfg: ServerConfig, exit_after_boot: bool = False,
                boot: BootPhases | None = None) -> int:
    app = StreamingServer(cfg)
    app.boot = boot
    await app.start()
    print(f"easydarwin-tpu listening: rtsp://{cfg.bind_ip}:{app.rtsp.port} "
          f"service http://{cfg.bind_ip}:{app.rest.port}/api/v1 "
          f"tpu_fanout={'on' if cfg.tpu_fanout else 'off'} "
          f"{app.engine_banner()}", flush=True)
    if boot is not None:
        boot.done()
    if exit_after_boot:
        await app.stop()
        return 0
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    loop.add_signal_handler(signal.SIGHUP,
                            lambda: cfg.update())   # RereadPrefs rebroadcast
    done, _ = await asyncio.wait(
        [asyncio.create_task(stop.wait()),
         asyncio.create_task(app.restart_event.wait())],
        return_when=asyncio.FIRST_COMPLETED)
    restarting = app.restart_event.is_set() and not stop.is_set()
    print("restarting..." if restarting else "shutting down...", flush=True)
    await app.stop()
    from .server.supervisor import EXIT_RESTART
    return EXIT_RESTART if restarting else 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.watchdog:
        from .server.supervisor import run_supervised
        child = [sys.executable, "-m", "easydarwin_tpu"] + [
            a for a in (sys.argv[1:] if argv is None else argv)
            if a not in ("-w", "--watchdog")]
        return run_supervised(child)
    # post hoc from the module's first line: nothing before this is lost
    boot = BootPhases(_FIRST_LINE_NS)
    cfg = config_from_args(args)
    from . import device, native
    print(f"jax: JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '(unset)')} "
          f"compile_cache={device.enable_compile_cache()}", flush=True)
    try:
        return asyncio.run(amain(cfg, args.exit_after_boot, boot))
    except (device.DeviceError, native.NativeCoreError) as e:
        # the engine tier cannot run where it was told to: stop, loudly
        print(f"easydarwin-tpu: boot refused: {e}", file=sys.stderr,
              flush=True)
        return EXIT_NO_DEVICE
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    raise SystemExit(main())
