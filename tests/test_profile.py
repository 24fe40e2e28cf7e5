"""Phase profiler + SLO watchdog (ISSUE 3): overhead bound, phase-sum
invariant, native timing counters, single-fire burn semantics, the
command=top / REST / pprof surfaces, and the bench_gate trajectory check.

The e2e spike test is the acceptance path: an induced latency burn
produces exactly one ``slo.violation`` event plus a flight dump for the
offending session, retrievable via BOTH the admin command and the REST
trace route.
"""

import gzip
import importlib.util
import json
import pathlib
import socket
import time

import numpy as np
import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.obs import (PHASES, PROFILER, Registry, SloConfig,
                                SloWatchdog, SpanTracer, build_pprof)
from easydarwin_tpu.obs.profile import PhaseProfiler

REPO = pathlib.Path(__file__).resolve().parents[1]


def _load_tool(name):
    p = REPO / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _private_profiler():
    reg = Registry()
    hist = reg.histogram("relay_phase_seconds", "phases",
                         labels=("engine", "phase"))
    drift = reg.counter("profile_phase_drift_total", "drift")
    return PhaseProfiler(hist=hist, drift_counter=drift), hist, drift


# ----------------------------------------------------------------- profiler
def test_profiler_phases_and_session_attribution():
    prof, hist, _ = _private_profiler()
    prof.account_pass("native", 1_000_000,
                      {"h2d": 100_000, "egress_native": 850_000},
                      path="/live/a", wire_bytes=5000)
    prof.account_pass("native", 2_000_000, {"egress_native": 1_900_000},
                      path="/live/b", wire_bytes=9000)
    prof.account_latency("/live/a", np.array([0.001, 0.002]))
    prof.account_latency("/live/b", np.array([0.2, 0.4]))
    snap = prof.snapshot()
    assert snap["phases"]["egress_native"]["native"]["count"] == 2
    assert snap["top_by_bytes"][0]["path"] == "/live/b"
    # /live/b's packets are ~100x slower: it owns the p99 ranking
    assert snap["top_by_p99"][0]["path"] == "/live/b"
    assert snap["top_by_p99"][0]["p99_ms"] > \
        snap["top_by_p99"][1]["p99_ms"]
    assert snap["top_by_bytes"][0]["phase_ms"]["egress_native"] > 0


def test_profiler_session_map_is_bounded():
    prof, _, _ = _private_profiler()
    prof._max_sessions = 8
    for i in range(50):
        prof.account_pass("native", 1000, {"h2d": 1000}, path=f"/p{i}")
    assert len(prof._sessions) == 8
    assert "/p49" in prof._sessions and "/p0" not in prof._sessions


def test_phase_sum_invariant_checked_pass():
    prof, _, drift = _private_profiler()
    # covered pass: phases bracket the whole total → no drift
    prof.account_pass("pipeline", 10_000_000,
                      {"h2d": 1_000_000, "device_step": 8_900_000},
                      check=True)
    assert prof.drift_checks == 1 and prof.drift_violations == 0
    # phases cover barely half the bracketing total → drift counted
    prof.account_pass("pipeline", 10_000_000, {"device_step": 5_000_000},
                      check=True)
    assert prof.drift_violations == 1
    assert drift.value() == 1
    assert prof.last_drift["total_ns"] == 10_000_000
    # tiny passes are noise, never drift (absolute slack)
    prof.account_pass("pipeline", 10_000, {"h2d": 1_000}, check=True)
    assert prof.drift_violations == 1


def test_relay_pipeline_pass_brackets_device_work():
    """Satellite: the pipeline's pass timer must cover the same work its
    phases do — device block-until-ready inside device_step, drift-free
    after the first (compile) trace."""
    from easydarwin_tpu.models.relay_pipeline import (RelayPipeline,
                                                      RelayPipelineConfig)
    before_checks = PROFILER.drift_checks
    before_viol = PROFILER.drift_violations
    built0 = obs.JAX_EXECUTABLES_BUILT.total()
    pipe = RelayPipeline(RelayPipelineConfig(window=64, subscribers=8))
    args = pipe.example_args()
    for _ in range(9):
        pipe(*args)
    # first call is the compile trace (unchecked); eight checked.
    # Drift is an aggregate signal: a loaded CI box can preempt inside
    # the unphased bookkeeping tail on an occasional pass, so judge the
    # rate — systematic drift (the bug this pins) would flag EVERY pass
    assert PROFILER.drift_checks >= before_checks + 8
    assert PROFILER.drift_violations - before_viol <= 2
    # the pass that held the build is told by jax_executables_built_total
    # (exact, fed by jax.monitoring) and stays out of the histograms
    assert obs.JAX_EXECUTABLES_BUILT.total() > built0
    assert PROFILER.drift_checks == before_checks + 8
    # the histogram carries both phases for the pipeline engine
    states = obs.RELAY_PHASE_SECONDS._states
    assert ("pipeline", "device_step") in states
    assert ("pipeline", "h2d") in states


def test_profiler_overhead_bound_on_cpu_engine():
    """Steady-state engine pass with the profiler ON stays within 5% of
    OFF (paired interleave, median-of-ratios — the same shared-VM drift
    control bench.py uses)."""
    from easydarwin_tpu.protocol import sdp
    from easydarwin_tpu.relay.fanout import TpuFanoutEngine
    from easydarwin_tpu.relay.output import CollectingOutput
    from easydarwin_tpu.relay.stream import RelayStream, StreamSettings

    sdp_txt = ("v=0\r\ns=b\r\nt=0 0\r\nm=video 0 RTP/AVP 96\r\n"
               "a=rtpmap:96 H264/90000\r\na=control:trackID=1\r\n")
    st = RelayStream(sdp.parse(sdp_txt).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    # production-shaped pass (64 outs x 256 pkts, several ms on CPU):
    # the profiler's cost is FIXED per pass (a few stamps + observes),
    # so the bound must be taken against a realistic pass, not a toy
    # one where 10 µs of bookkeeping is 10% all by itself
    outs = [CollectingOutput(ssrc=i, out_seq_start=i) for i in range(64)]
    for o in outs:
        st.add_output(o)
    pkt = bytes([0x80, 96]) + bytes(10) + bytes(188)
    for i in range(256):
        st.push_rtp(pkt[:2] + i.to_bytes(2, "big") + pkt[4:], 0)
    eng = TpuFanoutEngine()          # no egress fd → batch-header path
    eng.step(st, 10_000)             # compile + first-trace capture

    from easydarwin_tpu.obs import TRACER

    def one_pass(enabled: bool) -> float:
        # one switch (EDTPU_PROFILE) for the profiler and the span
        # bracket its phases now come from (ISSUE 25)
        PROFILER.enabled = TRACER.enabled = enabled
        for o in outs:
            o.bookmark = st.rtp_ring.tail
            o.rtp_packets.clear()
        c0 = time.perf_counter()
        eng.step(st, 10_000)
        return time.perf_counter() - c0

    was = PROFILER.enabled
    ratios = []
    try:
        for _ in range(3):           # warm both variants
            one_pass(True)
            one_pass(False)
        # Deterministic retry (the seed-flaky bound): up to 3 rounds of
        # 25 interleaved pairs; the contract holds if ANY round's
        # min-ratio clears the bound.  Scheduler noise only ever ADDS
        # time, so min-of-25 is the clean per-pass cost — but on a
        # loaded 2-vCPU box a noisy-neighbor burst can still taint one
        # whole round, which is exactly what a bounded retry absorbs
        # without weakening the 5% overhead contract itself.
        for _attempt in range(3):
            on, off = [], []
            for _ in range(25):      # interleaved: drift hits both alike
                on.append(one_pass(True))
                off.append(one_pass(False))
            ratios.append(min(on) / max(min(off), 1e-9))
            if ratios[-1] < 1.05:
                break
    finally:
        PROFILER.enabled = TRACER.enabled = was
    # 5% bound; the profiler's work is a handful of span brackets (two
    # perf_counter reads and a ring append each) plus a few histogram
    # observes vs a multi-ms pass
    assert min(ratios) < 1.05, f"profiler overhead ratios {ratios}"


# ------------------------------------------------------------ native timing
def test_ed_stats_send_ns_monotone_across_multi_calls():
    if not native.available():
        pytest.skip("native core unavailable")
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        native.reset_stats()
        assert native.get_stats()["send_ns"] == 0
        ring = np.zeros((4, 64), np.uint8)
        ring[:, 0] = 0x80
        lens = np.full(4, 40, np.int32)
        dests = native.make_dests([rx.getsockname()])
        ops = native.make_ops([(i, 0) for i in range(4)])
        one = np.zeros((1, 1), np.uint32)
        seen = []
        for _ in range(3):
            r = native.fanout_send_multi(tx.fileno(), ring, lens, one,
                                         one, one, dests, ops, 4,
                                         use_gso=False)
            assert r == 4
            seen.append(native.get_stats()["send_ns"])
        assert seen[0] > 0 and seen[0] < seen[1] < seen[2]
        # the GSO path brackets too
        native.fanout_send_multi(tx.fileno(), ring, lens, one, one, one,
                                 dests, ops, 4, use_gso=True)
        assert native.get_stats()["send_ns"] > seen[2]
        # mirrored into the busy-seconds counter at collect time
        obs.REGISTRY.collect()
        assert obs.EGRESS_BUSY_SECONDS.value() == \
            pytest.approx(native.get_stats()["send_ns"] / 1e9)
    finally:
        rx.close()
        tx.close()


# -------------------------------------------------------------- SLO watchdog
def _watchdog(events, *, compiles=lambda: 0, **cfg_kw):
    """Private watchdog over private families + event log (and a private
    executables-built source, so no real jit moves ``note_wake``)."""
    reg = Registry()
    lat = reg.histogram("lat_seconds", "lat", labels=("engine",))
    viol = reg.counter("slo_violations_total", "v", labels=("slo",))
    gauge = reg.gauge("slo_budget_remaining_ratio", "b", labels=("slo",))

    class _NoFlight:
        def dump_path(self, path, *, reason):
            return []

    cfg = SloConfig(**{**dict(latency_objective_ms=10.0,
                              latency_target=0.99,
                              fast_window_s=10.0, slow_window_s=30.0,
                              fast_burn=10.0, slow_burn=2.0), **cfg_kw})
    w = SloWatchdog(cfg, clock=lambda: 0.0, latency_hist=lat,
                    flight=_NoFlight(), events=events, violations=viol,
                    budget_gauge=gauge, compiles=compiles)
    return w, lat, viol, gauge


def test_slo_watchdog_fires_exactly_once_per_burn_window():
    from easydarwin_tpu.obs.events import EventLog
    ev = EventLog()
    w, lat, viol, gauge = _watchdog(ev)
    # healthy traffic: 1000 good packets
    lat.observe_many(np.full(1000, 0.001), engine="test")
    assert w.tick(now=0.0) == []
    # induced spike: 40% of new packets blow the 10 ms objective —
    # burn rate 40x the 1% budget on both windows
    lat.observe_many(np.full(600, 0.001), engine="test")
    lat.observe_many(np.full(400, 0.5), engine="test")
    fired = w.tick(now=1.0)
    assert len(fired) == 1 and fired[0]["slo"] == "latency"
    assert viol.value(slo="latency") == 1
    # the burn persists: NO event storm while latched (cooldown 10 s)
    for t in range(2, 10):
        assert w.tick(now=float(t)) == []
    assert viol.value(slo="latency") == 1
    # still burning past the cooldown → one re-fire (once per window)
    lat.observe_many(np.full(400, 0.5), engine="test")
    assert len(w.tick(now=12.0)) == 1
    assert viol.value(slo="latency") == 2
    # budget exhausted: gauge at/below zero while burning
    assert gauge.value(slo="latency") <= 0
    names = [r["event"] for r in ev.tail()]
    assert names.count("slo.violation") == 2
    # recovery: windows roll past the spike with only good traffic
    for t in range(13, 60):
        lat.observe_many(np.full(500, 0.001), engine="test")
        w.tick(now=float(t))
    assert "slo.recover" in [r["event"] for r in ev.tail()]
    assert viol.value(slo="latency") == 2


def test_slo_watchdog_min_events_guards_sparse_traffic():
    """A near-idle server (one player join delivering fast-start
    backlog) must not page: windows under min_events are never
    evaluated — the false positive the live verify drive caught."""
    from easydarwin_tpu.obs.events import EventLog
    ev = EventLog()
    w, lat, viol, _ = _watchdog(ev, min_events=200)
    lat.observe_many(np.full(60, 0.001), engine="test")
    w.tick(now=0.0)
    # 20 of 80 packets are stale backlog — 25% "bad", but only 80 events
    lat.observe_many(np.full(60, 0.001), engine="test")
    lat.observe_many(np.full(20, 2.0), engine="test")
    assert w.tick(now=1.0) == []
    assert viol.total() == 0


def test_slo_watchdog_ignores_slow_window_blip():
    """A fast-window spike the slow window never confirms must not fire
    (the multi-window recipe's noise immunity)."""
    from easydarwin_tpu.obs.events import EventLog
    ev = EventLog()
    w, lat, viol, _ = _watchdog(ev, fast_burn=2.0, slow_burn=20.0)
    lat.observe_many(np.full(10_000, 0.001), engine="test")
    w.tick(now=0.0)
    for t in range(1, 25):
        lat.observe_many(np.full(1000, 0.001), engine="test")
        if t == 20:                  # one polluted tick: fast burn ~3x
            lat.observe_many(np.full(300, 0.5), engine="test")
        w.tick(now=float(t))
    assert viol.total() == 0


# -- cold compiles stay out of the latency objective (note_wake, PR 21) ----
def _spike_wake(w, lat):
    """One pump wake that delivers a burning mix: 40 % past objective."""
    lat.observe_many(np.full(600, 0.001), engine="test")
    lat.observe_many(np.full(400, 0.5), engine="test")
    w.note_wake()


def test_slo_compile_wake_and_the_next_are_exempt():
    """A wake in which XLA built an executable, and the wake after it
    (a bucket compiles at the END of a wake; what it held back leaves in
    the next), never reach the burn windows."""
    from easydarwin_tpu.obs.events import EventLog
    built = [0]
    w, lat, viol, gauge = _watchdog(EventLog(), compiles=lambda: built[0])
    lat.observe_many(np.full(1000, 0.001), engine="test")
    w.note_wake()
    assert w.tick(now=0.0) == []
    built[0] += 1                       # the compile lands in this wake
    _spike_wake(w, lat)
    _spike_wake(w, lat)                 # ...and its stall drains in this
    assert w.tick(now=1.0) == []
    assert viol.total() == 0
    # both wakes' 2,000 observations are excluded, nothing else is
    assert w._read()["latency"] == (1000, 0)
    assert gauge.value(slo="latency") == 1.0


def test_slo_burn_without_a_compile_still_fires():
    """The same traffic with no executable built is a real burn."""
    from easydarwin_tpu.obs.events import EventLog
    w, lat, viol, _ = _watchdog(EventLog(), compiles=lambda: 0)
    lat.observe_many(np.full(1000, 0.001), engine="test")
    w.note_wake()
    w.tick(now=0.0)
    _spike_wake(w, lat)
    _spike_wake(w, lat)
    assert len(w.tick(now=1.0)) == 1
    assert viol.value(slo="latency") == 1


def test_slo_compile_grace_expires_after_one_more_wake():
    """Two wakes are exempt, the third counts in full: a compile cannot
    blank the objective for longer than its own stall."""
    from easydarwin_tpu.obs.events import EventLog
    built = [0]
    w, lat, viol, _ = _watchdog(EventLog(), compiles=lambda: built[0])
    lat.observe_many(np.full(1000, 0.001), engine="test")
    w.note_wake()
    w.tick(now=0.0)
    built[0] += 1
    _spike_wake(w, lat)                 # exempt: compiled
    _spike_wake(w, lat)                 # exempt: the wake after
    assert w.tick(now=1.0) == []
    _spike_wake(w, lat)                 # counts
    assert w._read()["latency"] == (2000, 400)
    assert len(w.tick(now=2.0)) == 1
    assert viol.value(slo="latency") == 1
    # a build between wakes (another thread) is charged to the wake that
    # sees it, once
    built[0] += 1
    _spike_wake(w, lat)
    _spike_wake(w, lat)
    _spike_wake(w, lat)
    assert w._read()["latency"] == (3000, 800)


# --------------------------------------------------- e2e spike → flight dump
@pytest.mark.asyncio
async def test_induced_spike_fires_violation_and_flight_dump(tmp_path):
    """Acceptance: an induced latency spike produces ONE slo.violation
    plus a flight dump for the offending session, retrievable via both
    the admin command and the REST trace route."""
    from easydarwin_tpu.obs import EVENTS, FLIGHT
    from easydarwin_tpu.server import admin
    from easydarwin_tpu.server.config import ServerConfig
    from easydarwin_tpu.server.rest import RestApi

    path = "/live/spiky"
    sid = "feedc0de"
    old_dir = FLIGHT.dump_dir
    FLIGHT.dump_dir = str(tmp_path)
    try:
        FLIGHT.register(sid, trace_id="tr-spike", path=path,
                        client_ip="10.0.0.9")
        EVENTS.emit("rtsp.play", session_id=sid, stream=path, status=200)
        # the spiking session must be THE top offender: drop attribution
        # left behind by earlier tests in this process (suite order must
        # not decide who gets flagged)
        with PROFILER._lock:
            PROFILER._sessions.clear()
        # the engine attributes the spike to the session (top offender)
        PROFILER.account_latency(path, np.full(64, 0.75))
        # private latency source so the global histogram's history does
        # not dilute the induced burn; offender resolves via PROFILER
        reg = Registry()
        lat = reg.histogram("lat_seconds", "lat")
        viol = reg.counter("slo_violations_total", "v", labels=("slo",))
        gauge = reg.gauge("slo_budget_remaining_ratio", "b",
                          labels=("slo",))
        w = SloWatchdog(
            SloConfig(latency_objective_ms=50.0, fast_window_s=5.0,
                      slow_window_s=10.0, fast_burn=5.0, slow_burn=2.0,
                      min_events=50),
            latency_hist=lat, offender=PROFILER.top_offender,
            violations=viol, budget_gauge=gauge)
        lat.observe_many(np.full(100, 0.001))
        assert w.tick(now=0.0) == []
        lat.observe_many(np.full(64, 0.75))          # the spike
        fired = w.tick(now=1.0)
        assert len(fired) == 1
        assert fired[0]["event"] == "slo.violation"
        assert fired[0]["flagged"] == [sid]
        w.tick(now=2.0)                              # latched: no storm
        # flagging SNAPSHOTS the box: the session stays live (a later
        # real crash must still produce its own dump) and the SLO dump
        # is stored + on disk
        assert sid in FLIGHT.live_sessions()
        stored = FLIGHT.dumps[sid]
        assert stored["reason"].startswith("slo: latency burn")
        assert stored["meta"]["path"] == path
        assert any(r["event"] == "rtsp.play" for r in stored["events"])
        # while live, retrieval answers with the CURRENT ring…
        status, doc = admin.flight_query(None, sid)
        assert status == 200 and doc.get("live") is True
        # …and after a clean teardown the SLO dump is what remains —
        # abnormal-QUALITY black boxes survive a clean TEARDOWN
        FLIGHT.discard(sid)
        status, doc = admin.flight_query(None, sid)
        assert status == 200
        assert doc["reason"].startswith("slo: latency burn")
        # --- and via the REST trace route ---
        api = RestApi(ServerConfig(), None)
        st, body, ctype = await api.route(
            "GET", f"/api/v1/sessions/{sid}/trace", {}, b"")
        assert st == 200 and ctype == "application/json"
        rest_doc = json.loads(body)
        assert rest_doc["session"] == sid
        assert rest_doc["reason"].startswith("slo: latency burn")
        viols = [r for r in EVENTS.tail()
                 if r.get("event") == "slo.violation"
                 and r.get("stream") == path]
        assert len(viols) == 1
    finally:
        FLIGHT.dump_dir = old_dir
        FLIGHT.discard(sid)
        with FLIGHT._lock:
            FLIGHT.dumps.pop(sid, None)


# ------------------------------------------------------------------ surfaces
@pytest.mark.asyncio
async def test_rest_profile_and_top_snapshot_shape():
    from easydarwin_tpu.server.config import ServerConfig
    from easydarwin_tpu.server.rest import RestApi
    PROFILER.account_pass("native", 1_000_000, {"egress_native": 900_000},
                          path="/live/shape", wire_bytes=100)
    api = RestApi(ServerConfig(), None)
    for target in ("/api/v1/profile", "/api/v1/admin?command=top"):
        st, body, ctype = await api.route("GET", target, {}, b"")
        assert st == 200 and ctype == "application/json"
        doc = json.loads(body)
        assert set(doc) >= {"enabled", "phases", "top_by_bytes",
                            "top_by_p99", "drift"}
        assert all(ph in PHASES for ph in doc["phases"])
        assert any(r["path"] == "/live/shape"
                   for r in doc["top_by_bytes"])


@pytest.mark.asyncio
async def test_debug_profile_serves_gzipped_pprof():
    from easydarwin_tpu.obs import TRACER
    from easydarwin_tpu.server.config import ServerConfig
    from easydarwin_tpu.server.rest import RestApi
    TRACER.close(TRACER.open("engine.step", "tpu"))
    api = RestApi(ServerConfig(), None)
    st, body, ctype = await api.route("GET", "/debug/profile", {}, b"")
    assert st == 200 and ctype == "application/octet-stream"
    raw = gzip.decompress(body)
    for needle in (b"engine.step", b"cat:tpu", b"samples", b"count",
                   b"nanoseconds", b"wall"):
        assert needle in raw, needle


def test_pprof_aggregates_span_ring():
    tr = SpanTracer(capacity=64)
    for i in range(10):
        tr.add("pass", 1000 * i, 500, cat="tpu")
    tr.add("egress", 0, 250, cat="native")
    raw = gzip.decompress(build_pprof(tr))
    assert b"pass" in raw and b"egress" in raw
    # 10 aggregated spans → the count varint 10 next to total ns 5000
    # appears inside one packed sample payload
    assert bytes([10]) + b"\x88\x27" in raw    # varint(10), varint(5000)


# ---------------------------------------------------------------- tool gates
def test_bench_gate_check_only_from_tests():
    gate = _load_tool("bench_gate")
    assert gate.main(["--check-only"]) == 0


def test_bench_gate_detects_regression(tmp_path):
    gate = _load_tool("bench_gate")
    traj = gate.load_trajectory()
    good = [t["parsed"] for t in traj if isinstance(t["parsed"], dict)][-1]
    slow = json.loads(json.dumps(good))
    slow["value"] = good["value"] * 0.5
    run = tmp_path / "run.json"
    run.write_text(json.dumps(slow))
    assert gate.main(["--run", str(run)]) == 1
    run.write_text(json.dumps(good))
    assert gate.main(["--run", str(run)]) == 0


def test_metrics_lint_phase_vocabulary():
    lint_mod = _load_tool("metrics_lint")
    assert lint_mod.lint_phases(obs.REGISTRY) == []
    # an out-of-vocabulary child is caught
    reg = Registry()
    h = reg.histogram("relay_phase_seconds", "phases",
                      labels=("engine", "phase"))
    reg.histogram("relay_ingest_to_wire_seconds", "lat",
                  labels=("engine",))
    h.observe(0.1, engine="native", phase="mystery_phase")
    errs = lint_mod.lint_phases(reg)
    assert any("mystery_phase" in e for e in errs)
    # a clipped bucket ladder is caught (must cover TIME_BUCKETS range)
    reg2 = Registry()
    reg2.histogram("relay_phase_seconds", "phases",
                   labels=("engine", "phase"), buckets=(0.01, 0.1))
    reg2.histogram("relay_ingest_to_wire_seconds", "lat",
                   labels=("engine",))
    errs = lint_mod.lint_phases(reg2)
    assert any("TIME_BUCKETS" in e for e in errs)
