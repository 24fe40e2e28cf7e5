"""Wake-loop ledger (ISSUE 16): the closed work-class vocabulary, the
nested-subtraction conservation invariant, queue-age attribution to the
wire classes with item-weighted wait mass, deferred/shed accounting, the
EDTPU_PROFILE=0 no-op contract, resilience fault sites surfacing as the
correct blamed class (slow-subscriber latency spike and pull_stall →
live_relay; redis_partition → cluster_tick), the REST/admin/status
surfaces, the bench_gate latency_blame section, and the ≤5% overhead
bound on a production-shaped engine pass.
"""

import asyncio
import importlib.util
import json
import pathlib
import re
import sys
import time

import pytest

from easydarwin_tpu import obs
from easydarwin_tpu.obs import Registry, WORK_CLASSES, WorkLedger, blame_doc
from easydarwin_tpu.obs.ledger import _WIRE_CLASSES, suspect_flags
from easydarwin_tpu.obs.metrics import TIME_BUCKETS
from easydarwin_tpu.protocol import rtp, sdp

REPO = pathlib.Path(__file__).resolve().parents[1]

VIDEO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=control:trackID=1\r\n")
PUSH_SDP = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=s\r\nt=0 0\r\n"
            "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
            "a=control:trackID=1\r\n")


def _load_tool(name):
    p = REPO / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _private_ledger(clock=None):
    """A WorkLedger on a private registry — exactly the PhaseProfiler
    injectable-families pattern, so tests never dirty the process
    families."""
    reg = Registry()
    wait = reg.histogram("pump_wait_seconds", "w", labels=("work_class",))
    svc = reg.histogram("pump_service_seconds", "s",
                        labels=("work_class",))
    dfr = reg.counter("pump_deferred_total", "d", labels=("work_class",))
    kw = dict(wait_hist=wait, service_hist=svc, deferred_counter=dfr)
    if clock is not None:
        kw["clock_ns"] = clock
    return WorkLedger(**kw), wait, svc, dfr


def vid_pkt(seq, ts=None, nal_type=1):
    payload = bytes(((3 << 5) | nal_type,)) + bytes(
        (seq * 7 + i) & 0xFF for i in range(80))
    return rtp.RtpPacket(payload_type=96, seq=seq & 0xFFFF,
                         timestamp=(seq * 90 if ts is None else ts),
                         ssrc=0x1234, payload=payload).to_bytes()


@pytest.fixture
def injector():
    from easydarwin_tpu.resilience import INJECTOR
    try:
        yield INJECTOR
    finally:
        INJECTOR.disarm()


# ------------------------------------------------------- vocabulary + lint
def test_work_classes_closed_vocab_and_lint():
    assert len(set(WORK_CLASSES)) == len(WORK_CLASSES)
    for c in WORK_CLASSES:
        assert re.fullmatch(r"[a-z][a-z0-9_]*", c), c
    assert set(_WIRE_CLASSES) <= set(WORK_CLASSES)
    ml = _load_tool("metrics_lint")
    assert ml.lint_ledger(obs.REGISTRY) == []
    # the pump families obey the global naming lint (and 'n' stays a
    # reserved label — it is the weighted-observe parameter)
    assert ml.lint(obs.REGISTRY) == []


def test_time_buckets_cover_slo_worst_window():
    """Satellite: the wait histograms must resolve a multi-second p99 —
    the top bucket exceeds the SLO watchdog's worst window, so an 8.1 s
    backlog lands in a real bucket instead of +Inf."""
    from easydarwin_tpu.obs.slo import SloConfig
    cfg = SloConfig()
    assert TIME_BUCKETS[-1] > max(cfg.fast_window_s, cfg.slow_window_s)
    assert TIME_BUCKETS == tuple(sorted(TIME_BUCKETS))


# --------------------------------------------------- conservation invariant
def test_nested_service_telescopes_to_wake_duration():
    """A nested class's service is subtracted from its parent, so the
    per-class figures SUM to the wake duration — the phase-sum
    discipline, applied to work classes."""
    t = [1_000_000_000]
    led, _, _, _ = _private_ledger(lambda: t[0])
    led.begin_wake()
    lu = led.unit_start("live_relay")
    t[0] += 2_000_000                 # 2 ms of relay work…
    fu = led.unit_start("fec_parity")
    t[0] += 5_000_000                 # …5 ms inside nested FEC…
    led.unit_end(fu)
    t[0] += 3_000_000                 # …3 ms more relay work
    led.unit_end(lu)
    led.end_wake()
    snap = led.snapshot()
    lr = snap["classes"]["live_relay"]
    fp = snap["classes"]["fec_parity"]
    assert fp["service_total_ms"] == pytest.approx(5.0)
    assert lr["service_total_ms"] == pytest.approx(5.0)  # 10 elapsed - 5
    assert lr["service_total_ms"] + fp["service_total_ms"] \
        == pytest.approx(snap["last_wake_ms"])


# -------------------------------------------- queue age + item weighting
def test_queue_age_attributed_to_wire_class_and_item_weighted():
    """The delivering unit's true queue delay is the age of the oldest
    item it put on the wire; the mass is the wire sample count.  A
    nested non-wire unit closing between the send and the enclosing
    relay unit's end must NOT steal the attribution."""
    t = [1_000_000_000]
    led, _, _, _ = _private_ledger(lambda: t[0])
    for _ in range(99):               # healthy wakes: ~2 ms, 5 items
        enq = t[0]
        t[0] += 1_000_000
        led.begin_wake(enq)
        u = led.unit_start("live_relay")
        t[0] += 500_000
        led.note_queue_age(0.002, 5)
        led.unit_end(u)
        led.end_wake()
    # the backlog wake: 500 queued packets drained, oldest 8.1 s old
    enq = t[0]
    t[0] += 1_000_000
    led.begin_wake(enq)
    u = led.unit_start("live_relay")
    fu = led.unit_start("fec_parity")
    t[0] += 200_000
    led.note_queue_age(8.1, 500)
    led.unit_end(fu)    # non-wire: must not consume
    t[0] += 800_000
    led.unit_end(u, trace_id="tr-burst")
    led.end_wake()
    snap = led.snapshot()
    lr = snap["classes"]["live_relay"]
    assert lr["wait_max_ms"] == pytest.approx(8100.0, rel=0.01)
    assert lr["worst_trace_id"] == "tr-burst"
    assert lr["count"] == 99 * 5 + 500
    # item weighting: 500 of 995 items are 8.1 s late → the wait p99 is
    # in the multi-second regime even though only 1% of WAKES were late
    assert lr["wait_p99_ms"] > 4000.0
    assert snap["classes"]["fec_parity"]["wait_max_ms"] < 100.0


# ------------------------------------------------------- deferred counting
def test_deferred_counts_fold_and_feed_counter():
    t = [1_000_000_000]
    led, _, _, dfr = _private_ledger(lambda: t[0])
    led.defer("megabatch", 3)         # no wake open → pending
    led.begin_wake()
    u = led.unit_start("megabatch")
    t[0] += 1_000_000
    led.unit_end(u)
    led.defer("hls_requant")          # open-wake path
    led.end_wake()
    snap = led.snapshot()
    assert snap["classes"]["megabatch"]["deferred"] == 3
    assert snap["classes"]["hls_requant"]["deferred"] == 1
    assert dfr.value(work_class="megabatch") == 3
    assert dfr.value(work_class="hls_requant") == 1


# ------------------------------------------------------ EDTPU_PROFILE=0
def test_profile_off_is_noop(monkeypatch):
    monkeypatch.setenv("EDTPU_PROFILE", "0")
    led, wait, _, _ = _private_ledger()
    assert led.enabled is False
    led.begin_wake()
    assert led.unit_start("live_relay") is None
    led.unit_end(None)  # None token: no-op, no branch
    led.note_queue_age(9.0, 100)
    led.defer("megabatch")
    led.record("cluster_tick", service_ns=1_000_000)
    led.end_wake()
    snap = led.snapshot()
    assert snap["enabled"] is False and snap["wakes"] == 0
    assert snap["classes"] == {} and snap["ring_len"] == 0
    assert wait.total_count() == 0


# --------------------------------------- cluster tick + suspect heuristics
def test_standalone_cluster_tick_redis_rollup_and_suspects():
    t = [1_000_000_000]
    led, _, _, _ = _private_ledger(lambda: t[0])
    led.begin_wake()                  # one cheap relay wake for contrast
    u = led.unit_start("live_relay")
    t[0] += 1_000_000
    led.unit_end(u)
    led.end_wake()
    for _ in range(4):                # tick coroutine: NO wake open
        led.record("cluster_tick", service_ns=80_000_000,
                   redis_ops=20, redis_ns=40_000_000)
    led.record("checkpoint", service_ns=120_000_000)
    snap = led.snapshot()
    assert snap["wakes"] == 1         # standalone records are not wakes
    assert snap["classes"]["cluster_tick"]["count"] == 4
    assert snap["redis"]["roundtrips_per_tick"] == 20.0
    flags = suspect_flags(snap)
    assert any(f.startswith("redis_roundtrips") for f in flags)
    assert any(f.startswith("auxiliary_ticks") for f in flags)
    assert any(f.startswith("checkpoint") for f in flags)


def test_blame_doc_ranks_rows_and_conserves():
    t = [1_000_000_000]
    led, _, _, _ = _private_ledger(lambda: t[0])
    enq = t[0]
    t[0] += 1_000_000
    led.begin_wake(enq)
    u = led.unit_start("live_relay")
    led.note_queue_age(6.0, 50)
    t[0] += 2_000_000
    led.unit_end(u)
    u = led.unit_start("dvr_spill")
    t[0] += 500_000
    led.unit_end(u)
    led.end_wake()
    doc = blame_doc(led.snapshot(), measured_p99_ms=7000.0,
                    baseline_p50_ms=10.0)
    assert doc["top_offender"] == "live_relay"
    assert doc["rows"][0]["work_class"] == "live_relay"
    assert set(doc["rows"][0]) == {
        "work_class", "wait_p50_ms", "wait_p99_ms", "wait_max_ms",
        "service_p99_ms", "count", "deferred"}
    assert all(r["work_class"] in WORK_CLASSES for r in doc["rows"])
    assert doc["attributed_p99_ms"] == pytest.approx(
        10.0 + doc["worst_wait_p99_ms"] + doc["relay_service_p99_ms"],
        abs=0.01)
    assert doc["conservation"] == pytest.approx(
        doc["attributed_p99_ms"] / 7000.0, abs=0.001)


# --------------------------------------------- fault sites → blamed class
async def test_redis_partition_surfaces_as_cluster_tick(monkeypatch,
                                                        injector):
    """An injected Redis partition aborts the tick, but the tick's
    thread time was spent either way — the ledger records the
    cluster_tick class even on the timeout path."""
    from easydarwin_tpu.cluster.redis_client import (InMemoryRedis,
                                                     RedisTimeout)
    from easydarwin_tpu.cluster.service import ClusterConfig, ClusterService
    from easydarwin_tpu.relay.session import SessionRegistry
    from easydarwin_tpu.resilience.inject import FaultPlan
    led, _, _, _ = _private_ledger()
    monkeypatch.setattr(obs, "LEDGER", led)
    r = InMemoryRedis()
    svc = ClusterService(r, ClusterConfig("n1"), registry=SessionRegistry())
    await svc.lease.acquire()
    injector.arm(FaultPlan.parse("seed=3,redis_partition_every=1"))
    with pytest.raises(RedisTimeout):
        await svc.tick()
    injector.disarm()
    snap = led.snapshot()
    assert snap["classes"]["cluster_tick"]["count"] == 1
    # a healthy tick lands in the ring's tick rollup too (roundtrip
    # counts come from the socket client; InMemoryRedis has none)
    await svc.tick()
    snap = led.snapshot()
    assert snap["classes"]["cluster_tick"]["count"] == 2
    assert snap["redis"]["ticks_in_ring"] == 2


def test_slow_subscriber_latency_spike_blames_live_relay(monkeypatch,
                                                         injector):
    """Injected slow work on the delivery path (every write
    WOULD_BLOCKed) backs the ring up; the catch-up drain after the
    fault clears carries the aged packets, and the ledger pins the
    spike on live_relay through the real egress note_queue_age path."""
    from easydarwin_tpu.relay.output import CollectingOutput
    from easydarwin_tpu.relay.stream import RelayStream, StreamSettings
    from easydarwin_tpu.resilience.inject import FaultPlan
    led, _, _, _ = _private_ledger()
    monkeypatch.setattr(obs, "LEDGER", led)
    st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    out = CollectingOutput(ssrc=1)
    st.add_output(out)
    for i in range(8):
        st.push_rtp(vid_pkt(i), 1000)
    injector.arm(FaultPlan(seed=3, slow_sub_every=1))
    led.begin_wake()
    u = led.unit_start("live_relay")
    st.reflect(1000)                  # every write blocks: nothing out
    led.unit_end(u)
    led.end_wake()
    assert out.stalls > 0 and not out.rtp_packets
    injector.disarm()
    time.sleep(0.7)                   # the queued packets age for real
    led.begin_wake()
    u = led.unit_start("live_relay")
    st.reflect(1000)                  # catch-up drain: 8 aged packets
    led.unit_end(u)
    led.end_wake()
    assert len(out.rtp_packets) == 8
    snap = led.snapshot()
    lr = snap["classes"]["live_relay"]
    assert lr["wait_max_ms"] > 500.0
    assert lr["count"] >= 8           # wire-sample weighted
    assert blame_doc(snap)["top_offender"] == "live_relay"


async def test_pull_stall_backlog_blames_live_relay(injector):
    """The pull_stall site tears the cross-server pull down; packets
    pushed during the retry window age in the origin's ring, and the
    re-pull's fast-start drains them through the real relay egress —
    the global ledger must blame live_relay with a wait spike covering
    the stall."""
    from easydarwin_tpu.cluster.pull import PullConfig, RemotePull
    from easydarwin_tpu.resilience.inject import FaultPlan
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    from easydarwin_tpu.utils.client import RtspClient

    async def _server(**kw):
        cfg = ServerConfig(rtsp_port=0, service_port=0,
                           reflect_interval_ms=5, bind_ip="127.0.0.1",
                           access_log_enabled=False, **kw)
        app = StreamingServer(cfg)
        await app.start()
        return app

    obs.LEDGER.reset()
    a = await _server()
    b = await _server()
    rp = None
    pusher = RtspClient()
    try:
        a_uri = f"rtsp://127.0.0.1:{a.rtsp.port}/live/src"
        await pusher.connect("127.0.0.1", a.rtsp.port)
        await pusher.push_start(a_uri, PUSH_SDP)
        for i in range(4):
            pusher.push_packet(0, vid_pkt(40 + i, i * 3000,
                                          nal_type=5 if i == 0 else 1))

        async def _resolve():
            return a_uri

        # the monitored envelope the cluster service drives — the
        # pull_stall site lives in ITS liveness probe
        rp = RemotePull("/relayed/src", _resolve, b.pulls,
                        PullConfig(read_timeout_sec=0.2, backoff_ms=100.0,
                                   backoff_cap_ms=300.0), seed=1)
        rp.start()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not (
                rp.alive and rp._pull is not None
                and rp._pull.client.stats.packets >= 4):
            await asyncio.sleep(0.05)
        assert rp.alive
        injector.arm(FaultPlan(seed=5, pull_stall_every=1))
        for i in range(6):            # backlog accrues at the origin
            pusher.push_packet(0, vid_pkt(50 + i, (10 + i) * 3000))
            await asyncio.sleep(0.1)
        await asyncio.sleep(0.5)
        injector.disarm()
        spike = 0.0
        deadline = time.monotonic() + 12
        while time.monotonic() < deadline:
            cls = obs.LEDGER.snapshot()["classes"].get("live_relay", {})
            spike = cls.get("wait_max_ms", 0.0)
            if spike > 400.0:
                break
            await asyncio.sleep(0.1)
        assert spike > 400.0, f"no catch-up wait spike (max {spike} ms)"
        assert blame_doc(obs.LEDGER.snapshot())["top_offender"] \
            == "live_relay"
    finally:
        injector.disarm()
        if rp is not None:
            await rp.stop()
        await pusher.close()
        await b.stop()
        await a.stop()


# ------------------------------------------------------------------ surfaces
async def test_rest_ledger_and_blame_surfaces():
    from easydarwin_tpu.server.config import ServerConfig
    from easydarwin_tpu.server.rest import RestApi
    # feed the process ledger one wake so the documents are non-trivial
    obs.LEDGER.begin_wake()
    u = obs.LEDGER.unit_start("live_relay")
    obs.LEDGER.unit_end(u)
    obs.LEDGER.end_wake()
    api = RestApi(ServerConfig(), None)
    st, body, ctype = await api.route("GET", "/api/v1/ledger", {}, b"")
    assert st == 200 and ctype == "application/json"
    doc = json.loads(body)
    assert set(doc) >= {"enabled", "wakes", "classes", "redis", "node"}
    assert "live_relay" in doc["classes"]
    assert set(doc["classes"]) <= set(WORK_CLASSES)
    st, body, _ = await api.route("GET", "/api/v1/admin?command=blame",
                                  {}, b"")
    assert st == 200
    doc = json.loads(body)
    assert set(doc) >= {"top_offender", "rows", "suspects", "ledger",
                        "attributed_p99_ms"}
    assert all(r["work_class"] in WORK_CLASSES for r in doc["rows"])


async def test_status_monitor_surfaces_ledger_summary(monkeypatch):
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    from easydarwin_tpu.server.status import StatusMonitor
    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       access_log_enabled=False)
    app = StreamingServer(cfg)
    await app.start()
    try:
        # patch + book + sample with NO await in between: the live
        # server's pump books wakes into whatever obs.LEDGER points at
        # (see test_pump_books_into_the_global_ledger below), so doing
        # this before/across app.start() let a pump wake race the
        # wakes==1 assertion — the suite-flaky failure PR 16 noted
        led, _, _, _ = _private_ledger()
        monkeypatch.setattr(obs, "LEDGER", led)
        led.begin_wake()
        u = led.unit_start("hls_requant")
        time.sleep(0.002)
        led.unit_end(u)
        led.end_wake()
        d = StatusMonitor(app).sample()
        assert d["ledger_top_wait_class"] == "hls_requant"
        assert d["ledger_wakes"] == 1
        assert d["ledger_last_wake_ms"] >= 0.0
    finally:
        await app.stop()


async def test_pump_books_into_the_global_ledger(monkeypatch):
    """Regression pin for the shared-global hazard: a LIVE server's
    pump books wakes into ``obs.LEDGER`` — whatever it points at.  A
    test that patches the global and then awaits (server startup, a
    client roundtrip) shares its 'private' ledger with the pump and
    must not assert exact wake counts across that boundary."""
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    led, _, _, _ = _private_ledger()
    monkeypatch.setattr(obs, "LEDGER", led)
    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       reflect_interval_ms=5, access_log_enabled=False)
    app = StreamingServer(cfg)
    await app.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and led.wakes == 0:
            await asyncio.sleep(0.02)
        assert led.wakes > 0, "pump never booked into the patched global"
    finally:
        await app.stop()


def test_bench_gate_accepts_and_rejects_latency_blame():
    sys.path.insert(0, str(REPO))
    from tools.bench_gate import check_trajectory

    def traj(composed):
        return [{"file": "BENCH_rX.json", "rc": 0, "parsed": {
            "metric": "relay_packets_to_wire_per_sec", "value": 1000.0,
            "unit": "packets/s", "vs_baseline": 2.0,
            "extra": {"composed": composed}}}]

    base = {"nodes": 2,
            "tier_rates": {"live": 100.0, "hls": 5000.0, "vod": 30.0,
                           "dvr": 25.0, "tcp": 40.0},
            "scaling_efficiency": 0.6, "migration_gap_packets": 0,
            "mixed_p99_ms": 42.0, "e2e_freshness_p99_s": 0.4,
            "unresolved_traces": 0, "wire_mismatches": 0}
    lb = {"top_offender": "live_relay", "baseline_p50_ms": 1.0,
          "worst_wait_p99_ms": 40.0, "relay_service_p99_ms": 5.0,
          "attributed_p99_ms": 46.0, "measured_p99_ms": 42.0,
          "conservation": 1.0952,
          "rows": [{"work_class": "live_relay", "wait_p99_ms": 40.0,
                    "service_p99_ms": 5.0, "count": 10, "deferred": 0}]}
    assert check_trajectory(traj(dict(base, latency_blame=lb))) == []
    bad = dict(base, latency_blame=dict(lb, conservation=0.5))
    assert any("conservation" in e for e in check_trajectory(traj(bad)))
    bad = dict(base, latency_blame=dict(lb, top_offender=""))
    assert any("top offender" in e for e in check_trajectory(traj(bad)))
    bad = dict(base, latency_blame=dict(
        lb, rows=[{"work_class": "live_relay",
                   "wait_p99_ms": float("nan"), "service_p99_ms": 1.0}]))
    assert any("not finite" in e for e in check_trajectory(traj(bad)))
    # rounds predating the ledger stay valid
    assert check_trajectory(traj(base)) == []


# ------------------------------------------------------------ overhead bound
def test_ledger_overhead_bound_on_cpu_engine(monkeypatch):
    """The full wake bracketing (begin_wake + four unit brackets +
    end_wake + the egress queue-age note) stays within 5% of the
    disabled ledger on a production-shaped pass — paired interleave,
    min-of-25, bounded retry (the PR 3 overhead discipline)."""
    from easydarwin_tpu.relay.fanout import TpuFanoutEngine
    from easydarwin_tpu.relay.output import CollectingOutput
    from easydarwin_tpu.relay.stream import RelayStream, StreamSettings
    led, _, _, _ = _private_ledger()
    monkeypatch.setattr(obs, "LEDGER", led)
    st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    outs = [CollectingOutput(ssrc=i, out_seq_start=i) for i in range(64)]
    for o in outs:
        st.add_output(o)
    pkt = bytes([0x80, 96]) + bytes(10) + bytes(188)
    for i in range(256):
        st.push_rtp(pkt[:2] + i.to_bytes(2, "big") + pkt[4:], 0)
    eng = TpuFanoutEngine()
    eng.step(st, 10_000)              # compile + first-trace capture

    def one_pass(enabled: bool) -> float:
        led.enabled = enabled         # EDTPU_PROFILE=0 semantics
        for o in outs:
            o.bookmark = st.rtp_ring.tail
            o.rtp_packets.clear()
        c0 = time.perf_counter()
        led.begin_wake()
        u = led.unit_start("live_relay")
        eng.step(st, 10_000)
        led.unit_end(u, items=64)
        for cls in ("vod_fill", "dvr_spill", "checkpoint"):
            tok = led.unit_start(cls)
            led.unit_end(tok)
        led.end_wake()
        return time.perf_counter() - c0

    ratios = []
    for _ in range(3):                # warm both variants
        one_pass(True)
        one_pass(False)
    for _attempt in range(3):
        on, off = [], []
        for _ in range(25):           # interleaved: drift hits both alike
            on.append(one_pass(True))
            off.append(one_pass(False))
        ratios.append(min(on) / max(min(off), 1e-9))
        if ratios[-1] < 1.05:
            break
    assert min(ratios) < 1.05, f"ledger overhead ratios {ratios}"
