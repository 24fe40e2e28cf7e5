#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``: starts ONE server child
(``benchmark/server_child.py``: ``easydarwin_tpu``'s own ``main``), joins
the cell's sessions through real RTSP, warms up at the cell's own pace,
measures for ``--seconds`` at the players' sockets, waits for what is
still owed, compares it with the plain reference, and prints one JSON
object last.  This parent never imports JAX: the child holds the chip.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.  ``JAX_PLATFORMS=cpu`` with an explicit
``--debug-size SOURCESxPLAYERS`` is the CPU rehearsal and says
``platform cpu``.
"""

from __future__ import annotations

import time

T_START_NS = time.perf_counter_ns()         # set-up is counted from here

import argparse                              # noqa: E402
import asyncio                               # noqa: E402
import bisect                                # noqa: E402
import gc                                    # noqa: E402
import json                                  # noqa: E402
import os                                    # noqa: E402
import resource                              # noqa: E402
import shutil                                # noqa: E402
import signal                                # noqa: E402
import subprocess                            # noqa: E402
import sys                                   # noqa: E402
import threading                             # noqa: E402
import urllib.request                        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import loadgen, readers, reference, stats  # noqa: E402

EXIT_NO_DEVICE = 3
DRAIN_TIMEOUT_S = 60.0          # how long a delivery may come late
TRACE_AFTER_S, TRACE_MAX_S = 2.0, 8.0


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


# ------------------------------------------------------------- the server
_OPENER = urllib.request.build_opener(urllib.request.ProxyHandler({}))


def parse_metrics(text: str) -> dict[str, float]:
    out: dict[str, float] = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        key, _, val = ln.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            pass
    return out


class Server:
    """The server child and its REST surface."""

    def __init__(self, out_dir: str, keys: dict, child_script: str,
                 trace_dir: str | None):
        self.out_dir = out_dir
        self.log_dir = os.path.join(out_dir, "logs")
        os.makedirs(self.log_dir)
        os.makedirs(os.path.join(out_dir, "movies"))
        self.device_json = os.path.join(out_dir, "device.json")
        self.trace_dir = trace_dir
        cfg = {"rtsp_port": 0, "service_port": 0, "bind_ip": "127.0.0.1",
               "wan_ip": "127.0.0.1", "log_folder": self.log_dir,
               "movie_folder": os.path.join(out_dir, "movies"), **keys}
        self.cfg_path = os.path.join(out_dir, "server.toml")
        with open(self.cfg_path, "w") as f:
            for k, v in cfg.items():
                f.write(f"{k} = {json.dumps(v)}\n")   # JSON scalars are TOML
        self.child_script = child_script
        self.stdout_lines: list[str] = []
        self.proc: subprocess.Popen | None = None
        self.rtsp_port = self.rest_port = 0

    def start(self) -> None:
        cmd = [sys.executable, self.child_script,
               "--device-json", self.device_json]
        if self.trace_dir:
            cmd += ["--trace-dir", self.trace_dir]
        cmd += ["--", "-c", self.cfg_path]
        with open(os.path.join(self.out_dir, "server.stderr"), "wb") as err:
            self.proc = subprocess.Popen(
                cmd, cwd=ROOT, stdout=subprocess.PIPE, stderr=err, text=True,
                env=dict(os.environ, PYTHONUNBUFFERED="1"))
        threading.Thread(target=self._pump_stdout, daemon=True).start()

    def _pump_stdout(self) -> None:
        with open(os.path.join(self.out_dir, "server.stdout"), "w") as f:
            for ln in self.proc.stdout:
                f.write(ln)
                f.flush()
                self.stdout_lines.append(ln.rstrip("\n"))

    def wait_boot(self, timeout: float = 600.0) -> str:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            for ln in self.stdout_lines:
                if "listening:" in ln:
                    self.rtsp_port = int(
                        ln.split("rtsp://")[1].split()[0].rsplit(":", 1)[1])
                    self.rest_port = int(
                        ln.split("http://")[1].split("/")[0]
                        .rsplit(":", 1)[1])
                    return ln
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited {self.proc.returncode} before it "
                    f"listened: {self.stderr_tail()}")
            time.sleep(0.05)
        raise RuntimeError(f"server did not listen within {timeout:.0f} s: "
                           f"{self.stderr_tail()}")

    def stderr_tail(self, n: int = 1500) -> str:
        try:
            with open(os.path.join(self.out_dir, "server.stderr"),
                      errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def get(self, path: str, timeout: float = 60.0) -> bytes:
        with _OPENER.open(f"http://127.0.0.1:{self.rest_port}{path}",
                          timeout=timeout) as r:
            return r.read()

    def info(self) -> dict:
        return json.loads(self.get("/api/v1/getserverinfo")
                          )["EasyDarwin"]["Body"]

    def metrics(self) -> dict[str, float]:
        return parse_metrics(self.get("/metrics").decode())

    def terminate(self) -> int | None:
        """SIGTERM and wait; SIGKILL only if it will not go."""
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                return self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                return None
        return self.proc.returncode


# -------------------------------------------------------------------- run
class Run:
    def __init__(self, args, bench: dict):
        self.args = args
        self.bench = bench
        self.cell = next((w for w in bench["workloads"]
                          if w["name"] == args.workload), None)
        if self.cell is None:
            raise SystemExit(f"no workload {args.workload!r} in "
                             f"BENCHMARK.json")
        conf = next(c for c in bench["configs"]
                    if c["name"] == self.cell["config"])
        self.cfg = load_json(conf["file"])
        self.traffic = load_json(
            f"benchmark/traffic/{self.cell['traffic']}.json")
        if args.fps:                        # the sweep's pace, not a cell's
            self.traffic["fps_per_source"] = args.fps
        self.n_src = self.cfg["sources"]
        self.n_sub = self.cfg["players"]["per_source"]
        if args.debug_size:
            self.n_src, self.n_sub = (int(x) for x in
                                      args.debug_size.lower().split("x"))
        self.out_dir = os.path.join(ROOT, "benchmark_out", args.workload)
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir)
        self.trace_dir = (os.path.join(self.out_dir, "trace")
                          if args.trace else None)
        keys = dict(self.cfg["server"])
        if args.control and args.control.startswith("fault:"):
            keys["resilience_fault_plan"] = args.control[len("fault:"):]
        self.server = Server(self.out_dir, keys, args.child_script,
                             self.trace_dir)
        self.lg: loadgen.Loadgen | None = None      # built in run()
        self.harness: dict[str, float] = {}
        self.compared: dict[str, list] = {}     # name -> [value, limit]
        self.notes: list[str] = []
        self.device: dict = {}

    # -- checks ------------------------------------------------------------
    def compare(self, name: str, value, limit=0) -> None:
        """One number held to its limit; printed on its own line."""
        self.compared[name] = [value, limit]
        log(f"check {name}: {value} (limit {limit})"
            + ("" if value <= limit else "  <-- OVER"))

    def device_ok(self, dev: dict) -> bool:
        cpu_by_name = os.environ.get("JAX_PLATFORMS", "").split(
            ",")[0].strip().lower() == "cpu"
        if dev["platform"] == "tpu" and dev["count"] >= self.cell["chips"]:
            return True
        return bool(dev["platform"] == "cpu" and cpu_by_name
                    and self.args.debug_size)

    # -- the run -----------------------------------------------------------
    async def drive(self) -> None:
        srv, lg, S = self.server, self.lg, float(self.args.seconds)

        # 1. sessions: pushers, then every player in waves, before media
        t0 = time.perf_counter()
        await lg.start_pushers(srv.rtsp_port)
        await lg.join_players(srv.rtsp_port)
        self.harness["rtsp.join_s"] = time.perf_counter() - t0
        log(f"{self.n_src} pushers recording, {len(lg.players)} UDP PLAY "
            f"sessions joined in {self.harness['rtsp.join_s']:.2f} s "
            f"({len(lg.flows)} stamped)")

        # 2. warm-up at the cell's own pace: one IDR and a few P frames
        W = lg.warm_frames
        warm_s = W / lg.fps
        await lg.push_frames(0, lambda due: due < warm_s,
                             time.perf_counter_ns())
        keep = asyncio.ensure_future(lg.pusher_keepalive())
        sent = await self.wait_owed(120.0)
        keep.cancel()
        log(f"warm-up: {W} frames/source at {lg.fps:g} fps, server sent "
            f"{sent} of {lg.expected_deliveries()}")
        lg.lateness.clear()

        # 3. the measured window.  No collector pause of this process's
        # own falls into it: what is alive now stays, nothing made in the
        # window is cyclic
        gc.collect()
        gc.freeze()
        gc.disable()
        lag = asyncio.ensure_future(self.loop_lag())
        m0 = await asyncio.to_thread(srv.metrics)
        k0 = loadgen.udp_kernel_counters()
        got0 = lg.received()
        t0_ns = time.perf_counter_ns()
        self.harness["setup_s"] = (t0_ns - T_START_NS) / 1e9
        tracer = (asyncio.ensure_future(self.trace_bracket(S))
                  if self.trace_dir else None)
        await lg.push_frames(W, lambda due: due < S, t0_ns)
        await asyncio.sleep(max(
            (t0_ns + int(S * 1e9) - time.perf_counter_ns()) / 1e9, 0))
        t1_ns = time.perf_counter_ns()
        lag.cancel()
        bulk1 = lg.bulk.counts()[0]
        m1 = await asyncio.to_thread(srv.metrics)
        sent1 = int(readers.fam(m1, "egress_packets_total") or 0)
        self.harness["backlog_at_close"] = lg.expected_deliveries() - sent1
        log(f"at the window's close: owed {lg.expected_deliveries()}, "
            f"server sent {sent1}, backlog "
            f"{self.harness['backlog_at_close']}")
        self.m0, self.m1 = m0, m1
        self.window = (t0_ns, t1_ns)
        self.got_window = (got0, bulk1)
        late = sorted(x * 1e3 for x in lg.lateness)
        self.harness["loadgen.late_p99_ms"] = stats.percentile(late, 99)
        log(f"window: {S:g} s, {len(late)} frames pushed, pusher lateness "
            f"median {stats.percentile(late, 50):.3f} ms p99 "
            f"{self.harness['loadgen.late_p99_ms']:.3f} ms max "
            f"{late[-1]:.3f} ms; this process's event loop woke at most "
            f"{self.harness['loadgen.loop_lag_max_ms']:.3f} ms late")

        # 4. what is still owed: wait for it, a minute if need be
        keep = asyncio.ensure_future(lg.pusher_keepalive())
        sent = await self.wait_owed(DRAIN_TIMEOUT_S)
        keep.cancel()
        if tracer is not None:
            await tracer
        m_end = await asyncio.to_thread(srv.metrics)
        k_end = loadgen.udp_kernel_counters()
        self.account(m_end, k0, k_end)
        self.watch(m0, m_end)

    async def loop_lag(self) -> None:
        """How late this process's own event loop wakes from a 10 ms
        sleep: a stall of the generator shows here, not as the
        server's."""
        self.harness["loadgen.loop_lag_max_ms"] = 0.0
        while True:
            t = time.perf_counter()
            await asyncio.sleep(0.01)
            over = (time.perf_counter() - t - 0.01) * 1e3
            if over > self.harness["loadgen.loop_lag_max_ms"]:
                self.harness["loadgen.loop_lag_max_ms"] = over

    async def wait_owed(self, timeout: float) -> int:
        """Until the server's sent count and the harness's received
        count both reach pushed x joined (or ``timeout`` passes)."""
        want = self.lg.expected_deliveries()
        t0 = time.monotonic()
        sent = -1
        while time.monotonic() - t0 < timeout:
            m = await asyncio.to_thread(self.server.metrics)
            sent = int(readers.fam(m, "egress_packets_total") or 0)
            if sent >= want and self.lg.received() >= want:
                break
            await asyncio.sleep(0.5)
        return sent

    async def trace_bracket(self, seconds: float) -> None:
        """SIGUSR1 / SIGUSR2 to the child: a few traced seconds inside
        the window, then wait for the trace to be written."""
        span = min(TRACE_MAX_S, max(seconds - 2 * TRACE_AFTER_S, 1.0))
        await asyncio.sleep(min(TRACE_AFTER_S, seconds / 4))
        self.server.proc.send_signal(signal.SIGUSR1)
        await asyncio.sleep(span)
        self.server.proc.send_signal(signal.SIGUSR2)
        done = os.path.join(self.trace_dir, "trace_done")
        t0 = time.monotonic()
        while not os.path.exists(done) and time.monotonic() - t0 < 120:
            await asyncio.sleep(0.25)

    def account(self, m_end, k0, k_end) -> None:
        """Loss is attributed before it is judged: the server's sent
        count, the harness's received count and the kernel's receive-
        queue drops, side by side."""
        lg = self.lg
        owed = lg.expected_deliveries()
        sent = int(readers.fam(m_end, "egress_packets_total") or 0)
        errs = int(readers.fam(m_end, "egress_send_errors_total") or 0)
        bulk_got = lg.bulk.counts()[0]
        bulk_owed = sum(s.pushed for s in lg.sources) * (
            self.n_sub - len(lg.flows) // self.n_src)
        stamped_got = sum(len(f.stamps) for f in lg.flows)
        kd = {k: k_end.get(k, 0) - k0.get(k, 0)
              for k in ("RcvbufErrors", "InErrors")} if k_end else {}
        self.acct = {"owed": owed, "server_sent": sent,
                     "server_send_errors": errs,
                     "harness_received": bulk_got + stamped_got,
                     "bulk_received": bulk_got, "bulk_owed": bulk_owed,
                     "kernel_udp_drops": kd}
        log(f"loss: owed (pushed x joined) {owed}, server sent {sent} "
            f"(send errors {errs}), harness received "
            f"{bulk_got + stamped_got} (bulk {bulk_got} of {bulk_owed}, "
            f"stamped {stamped_got}), kernel UDP receive-queue drops over "
            f"window and drain {kd or 'not given by this kernel'}")

    def watch(self, m0, m_end) -> None:
        """What the server did to itself over the run: printed, not
        judged (an overloaded server's ladder may move honestly)."""
        names = ("slo_violations_total", "resilience_transitions_total",
                 "resilience_retries_total", "device_errors_swallowed_total",
                 "megabatch_wire_mismatch_total", "megabatch_fallback_total",
                 "jax_executables_built_total",
                 "jax_persistent_cache_hits_total",
                 'relay_ingest_to_wire_seconds_count{engine="scalar"}')
        grew = {n: (readers.fam(m_end, n) or 0) - (readers.fam(m0, n) or 0)
                for n in names}
        log("server over window and drain: " + ", ".join(
            f"{n} +{v:g}" for n, v in grew.items()))
        try:
            since = 0
            while True:
                recs = [json.loads(ln) for ln in self.server.get(
                    f"/api/v1/events?n=1024&since={since}"
                ).decode().splitlines()]
                for e in recs:
                    if e["event"].split(".")[0] in ("ladder", "slo"):
                        log(f"server event: {json.dumps(e)[:300]}")
                if len(recs) < 1024:
                    break
                since = recs[-1]["seq"]
        except Exception as e:              # a look, never a failure
            log(f"server events not read: {e!r}")

    def judge(self) -> bool:
        """The comparison with the plain reference.  Every number has
        the limit 0: the comparison is exact."""
        lg, a = self.lg, self.acct
        tot = {"missing": 0, "out_of_order": 0, "altered": 0,
               "unannounced": 0}
        ctl = self.args.control or ""
        for n, f in enumerate(lg.flows):
            pushed = f.src.packets[:f.src.pushed]
            got = f.packets
            if ctl.startswith("ref:") and n == self.args.seed % len(lg.flows):
                # the control: the reference in the program's place,
                # one guarantee broken once
                got = reference.break_guarantee(
                    reference.reference_flow(pushed, f.first_seq or 0,
                                             f.ssrc or 0, 0),
                    ctl[4:], self.args.seed,
                    int.from_bytes(pushed[0][8:12], "big"))
            for k, v in reference.judge_flow(
                    got, pushed, f.first_seq, f.ssrc).items():
                tot[k] += v
        self.compare("stamped_missing", tot["missing"])
        self.compare("stamped_out_of_order", tot["out_of_order"])
        self.compare("stamped_altered", tot["altered"])
        self.compare("sessions_unannounced", tot["unannounced"])
        self.compare("server_sent_short", max(a["owed"] - a["server_sent"], 0))
        self.compare("server_sent_excess",
                     max(a["server_sent"] - a["owed"], 0))
        self.compare("server_send_errors", a["server_send_errors"])
        self.compare("bulk_excess", max(a["bulk_received"] - a["bulk_owed"],
                                        0))
        short = max(a["bulk_owed"] - a["bulk_received"], 0)
        self.compare("bulk_short", short)
        self.failed = tot["missing"] + tot["altered"] + short
        ok = all(v <= lim for v, lim in self.compared.values())
        if short and a["server_sent"] >= a["owed"]:
            # the server put them on the wire and this process's own
            # receive queues lost them: the generator's fault
            self.notes.append("generator_dropped")
            log(f"INVALID RUN: the server sent all {a['owed']} owed "
                f"datagrams and the harness's own sockets lost {short} of "
                f"them: the generator's loss, not the program's wrong "
                f"output")
        return ok

    def end_to_end(self) -> dict[str, float]:
        """Every stamped delivery of a frame due in the window: delay
        from the frame's due instant to arrival at the socket."""
        lg = self.lg
        t0_ns, t1_ns = self.window
        by_flow, in_window = [], 0
        for f in lg.flows:
            if f.first_seq is None:
                continue
            fs, last, arrived, due = f.src.frame_start, -1, [], []
            for g, at in zip(f.packets, f.stamps):
                if t0_ns <= at < t1_ns:
                    in_window += 1
                if len(g) < 12:
                    continue
                k = reference.place_of(g, f.first_seq, f.src.pushed, last)
                last = max(last, k)
                frame = bisect.bisect_right(fs, k) - 1
                if frame >= lg.warm_frames and (f.src.idx, frame) in lg.due_ns:
                    arrived.append(at)
                    due.append(lg.due_ns[(f.src.idx, frame)])
            d = stats.flow_delays_ms(arrived, due)
            by_flow.append(d)
        got0, bulk1 = self.got_window
        stamped0 = sum(1 for f in lg.flows for at in f.stamps if at < t0_ns)
        delivered = bulk1 + stamped0 + in_window - got0
        out = {"setup_s": self.harness["setup_s"],
               "delivered_per_s": delivered / ((t1_ns - t0_ns) / 1e9)}
        if any(by_flow):
            out.update(stats.delay_metrics(by_flow))
            self.harness["stamped_deliveries"] = sum(map(len, by_flow))
        return out

    def reduce_trace(self) -> dict | None:
        if not self.trace_dir:
            return None
        r = subprocess.run(
            [sys.executable, os.path.join(HERE, "reduce_trace.py"),
             self.trace_dir, "--chips", str(self.cell["chips"])],
            capture_output=True, text=True, timeout=240,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        try:
            return json.loads(r.stdout.strip().splitlines()[-1])
        except (IndexError, ValueError):
            log(f"trace reduction gave nothing: {r.stderr[-500:]}")
            return None

    def run(self) -> int:
        _, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
        sys.setswitchinterval(0.0005)
        a = self.args
        log(f"cell {a.workload}: {self.n_src} sources x {self.n_sub} "
            f"players at {float(self.traffic['fps_per_source']):g} "
            f"fps/source, seed {a.seed}, {a.seconds:g} s, trace {a.trace}")
        rc, err = None, None
        try:
            # the harness's own set-up fails as the server's does: no
            # result, and whatever was started is torn down
            self.lg = loadgen.Loadgen(self.cfg, self.traffic, a.seed,
                                      a.seconds, self.n_src, self.n_sub)
            self.lg.start_receivers()
            self.server.start()
            log("server boot: " + self.server.wait_boot())
            info = self.server.info()
            self.device = {"platform": info.get("Platform", ""),
                           "kind": info.get("DeviceKind", ""),
                           "count": int(info.get("DeviceCount", "0") or 0)}
            log(f"server reports {self.device}")
            if not self.device_ok(self.device):
                err = (f"server runs on {self.device}, the cell needs "
                       f"{self.cell['chips']} TPU chip(s)")
            else:
                asyncio.run(self.drive())
        except Exception as e:
            err = f"run failed: {e!r}"
        finally:
            if self.lg is not None:
                self.lg.stop_receivers()
            rc = self.server.terminate()
        if err is not None:
            log(f"NO RESULT: {err}")
            return EXIT_NO_DEVICE
        if rc != 0:
            log(f"server exit code on SIGTERM: {rc} "
                f"({self.server.stderr_tail(400)!r})")
        try:
            with open(self.server.device_json) as f:
                self.device = json.load(f)
        except (OSError, ValueError):
            self.device["memory_peak_bytes"] = 0
        ok = self.judge() and rc == 0
        self.compare("server_exit_code", -1 if rc is None else rc)
        e2e = self.end_to_end()
        trace = self.reduce_trace()
        return self.report(ok, e2e, trace)

    def report(self, ok: bool, e2e: dict, trace: dict | None) -> int:
        cell, bench = self.cell["name"], self.bench
        ctx = {"m0": self.m0, "m1": self.m1, "trace": trace,
               "harness": {**self.harness, **e2e},
               "peaks": load_json("benchmark/peaks.json")["devices"].get(
                   self.device.get("kind", ""))}
        metrics: dict[str, dict] = {}
        if self.args.trace:
            if self.device.get("platform") == "tpu" and ctx["peaks"] is None:
                raise SystemExit(f"device kind {self.device.get('kind')!r} "
                                 f"is not in benchmark/peaks.json")
            for m in bench["per_layer"]:
                if cell not in m.get("workloads", [cell]):
                    continue
                spec = load_json(f"benchmark/layer_metrics/{m['name']}.json")
                v = readers.read(spec, ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            if trace:
                self.device["busy_s"] = trace.get("busy_s")
                self.device["window_s"] = trace.get("window_s")
        else:
            for m in bench["end_to_end"]:
                if cell in m.get("workloads", [cell]) and m["name"] in e2e:
                    metrics[m["name"]] = {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
        for name, m in metrics.items():
            log(f"metric {name}: {m['value']} {m['unit']}")
        line = {"correct": ok, "attempted": self.acct["owed"],
                "failed": self.failed, "metrics": metrics,
                "device": self.device}
        if trace and self.args.trace:
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
        if self.notes:
            line["notes"] = self.notes
        line["accounting"] = self.acct
        line["compared"] = {k: {"value": v, "limit": lim}
                            for k, (v, lim) in self.compared.items()}
        sys.stdout.flush()
        for k, (v, lim) in self.compared.items():
            print(f"compared {k}: {v} limit {lim}", file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(line), flush=True)
        return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--debug-size", metavar="SxP",
                    help="sources x players; marks a CPU rehearsal")
    ap.add_argument("--fps", type=float,
                    help="override the mix's pace (benchmark/sweep.py)")
    ap.add_argument("--control",
                    help="the control: 'ref:<guarantee>' puts the reference "
                         "with that guarantee broken in one flow's place; "
                         "'fault:<plan>' arms the program's own fault plan")
    ap.add_argument("--child-script",
                    default=os.path.join(HERE, "server_child.py"),
                    help="the server child (tests put a broken one here)")
    args = ap.parse_args(argv)
    return Run(args, load_json("BENCHMARK.json")).run()


if __name__ == "__main__":
    sys.exit(main())
