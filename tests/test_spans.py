"""One span source on the profiler's clock (ISSUE 25).

* the served path's spans land on the ``/host:CPU`` plane of a real
  ``jax.profiler`` trace, nested as the code nests, and the benchmark's
  own reduction names a device-idle gap by them;
* ``relay_due_to_wire_seconds`` is ingest→wire net of the bucket's
  declared hold, over the same deliveries as
  ``relay_ingest_to_wire_seconds``, at every egress site;
* the span vocabulary is closed, and a wake allocates no TraceMe with no
  profiler session live or with ``EDTPU_PROFILE=0``.
"""

import asyncio
import importlib.util
import pathlib
import random
import socket

import numpy as np
import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.obs import SPANS, TRACER
from easydarwin_tpu.obs.trace import SPAN_PREFIXES, SpanTracer
from easydarwin_tpu.protocol import rtp, sdp
from easydarwin_tpu.relay.fanout import TpuFanoutEngine
from easydarwin_tpu.relay.output import CollectingOutput
from easydarwin_tpu.relay.stream import RelayStream, StreamSettings

REPO = pathlib.Path(__file__).resolve().parent.parent
VIDEO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=control:trackID=1\r\n")

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native core unavailable")


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, REPO / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def vid_pkt(seq: int, ts: int) -> bytes:
    payload = bytes(((3 << 5) | 1,)) + bytes(
        (seq * 7 + i) & 0xFF for i in range(80))
    return rtp.RtpPacket(payload_type=96, seq=seq & 0xFFFF, timestamp=ts,
                         ssrc=0x1234, payload=payload).to_bytes()


class _Rx:
    """A few UDP receivers for native-addressed outputs, and the socket
    the engine sends from."""

    def __init__(self, n: int = 4):
        self.socks = []
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.bind(("127.0.0.1", 0))
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 22)
            self.socks.append(s)
        self.addrs = [s.getsockname() for s in self.socks]
        self.send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.send.setblocking(False)

    def close(self) -> None:
        for s in self.socks + [self.send]:
            s.close()


def _server(rx: _Rx, n_streams: int = 2, n_outputs: int = 32):
    """An unstarted StreamingServer with ``n_streams`` live streams of
    ``n_outputs`` native-addressed outputs each, on the engine path."""
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    cfg = ServerConfig(tpu_fanout=True, megabatch_enabled=True,
                       tpu_min_outputs=2, megabatch_min_streams=2,
                       reflect_interval_ms=20, bucket_delay_ms=0,
                       slo_enabled=False, access_log_enabled=False)
    app = StreamingServer(cfg)
    app.rtsp.shared_egress = rx.send
    streams = []
    for k in range(n_streams):
        sess = app.registry.find_or_create(f"/live/s{k}", VIDEO_SDP)
        st = sess.streams[1]
        sess.set_trace(f"trace-{k}")
        rng = random.Random(k)
        for i in range(n_outputs):
            o = CollectingOutput(ssrc=rng.getrandbits(32))
            o.native_addr = rx.addrs[i % len(rx.addrs)]
            st.add_output(o)
        streams.append(st)
    return app, streams


def _inside(child, parent) -> bool:
    return (parent[1] <= child[1]
            and child[1] + child[2] <= parent[1] + parent[2])


# ------------------------------------------------- (a) the profiler's plane
@needs_native
async def test_served_spans_land_on_the_profilers_host_plane(tmp_path):
    import jax

    reduce_trace = _load("benchmark/reduce_trace.py", "reduce_trace")
    rx = _Rx()
    app, streams = _server(rx)
    TRACER.clear()
    # (a test before this one, in this process, may have left a wake of
    # its own open: ``_reflect_all`` without ``_wake_close``)
    TRACER.wake = None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        app._running = True
        pump = asyncio.create_task(app._pump_loop())
        seq = 0
        for _wake in range(6):
            for st in streams:
                for _ in range(4):
                    seq += 1
                    st.push_rtp(vid_pkt(seq, seq * 3000), app_now())
            app._wake()
            await asyncio.sleep(0.04)
        app._running = False
        app._wake()
        await asyncio.wait_for(pump, 5)
    finally:
        jax.profiler.stop_trace()
        rx.close()
    events = reduce_trace.load_xplane(reduce_trace.newest_xplane(
        str(tmp_path)))
    host: dict[str, list] = {}
    for row in events["host"]:
        host.setdefault(row[0], []).append(row)
    for name in ("pump.sleep", "pump.wake", "pump.live_relay",
                 "pump.megabatch", "engine.step", "engine.egress",
                 "engine.settle", "engine.account"):
        assert host.get(name), f"{name} is not on the host plane"
    # the send is the native sender thread's: its span is filed from the
    # job's own stamps at settle, so the ring has it and this plane has
    # ``egress.wait`` where the loop thread stood still for it (a send
    # already done when its settle came leaves none)
    assert "native.egress" not in host
    # the program's own names and nothing else of its making
    ours = {n for n in host if n.startswith(
        ("pump.", "engine.", "megabatch.", "native.", "egress.",
         "ingest."))}
    assert ours <= set(SPANS)
    # each child inside its parent's interval, on the profiler's clock
    for child, parent in (("engine.egress", "engine.step"),
                          ("engine.account", "engine.settle"),
                          ("egress.wait", "engine.settle"),
                          ("engine.step", "pump.live_relay"),
                          ("engine.settle", "pump.live_relay"),
                          ("pump.live_relay", "pump.wake"),
                          ("pump.megabatch", "pump.wake")):
        for row in host.get(child, ()):
            assert any(_inside(row, p) for p in host[parent]), (child, row)
    # no wake overlaps a sleep: the loop is in one state at a time
    for w in host["pump.wake"]:
        for s in host["pump.sleep"]:
            assert w[1] >= s[1] + s[2] or s[1] >= w[1] + w[2]

    # tools/span_breakdown.py reads the same plane: a wake's decomposition
    tool = _load("tools/span_breakdown.py", "span_breakdown")
    # and what each wake carries: the roster and how much of it the ready
    # set had it step (two streams, pushed in six of the wakes), the send
    # jobs and their seconds — of which the tool makes the plane's
    # ``native.egress`` row
    carried = tool.wake_args(str(tmp_path))
    doc = tool.breakdown(events["host"], carried)
    assert doc["wakes"] == len(host["pump.wake"]) >= 6
    assert len(carried) == doc["wakes"]
    mean = tool.per_wake(carried)
    assert mean["streams"] == 2 and 0 < mean["stepped"] <= 2
    assert mean["sent"] * doc["wakes"] == pytest.approx(2 * 32 * 24)
    assert mean["jobs"] * doc["wakes"] == 12
    assert 0 <= mean["hidden_us"] <= mean["send_us"] > 0
    assert doc["spans"]["native.egress"]["count"] == 12
    per_wake = {n: r["ms_per_wake"] for n, r in doc["spans"].items()}
    assert (per_wake["pump.wake"] >= per_wake["pump.live_relay"]
            >= per_wake["engine.step"] >= per_wake["engine.egress"] > 0)
    assert (per_wake["pump.live_relay"] >= per_wake["engine.settle"]
            >= per_wake["engine.account"] > 0)
    assert per_wake["native.egress"] > 0
    assert doc["spans"]["pump.wake"]["loop_pct"] \
        + doc["spans"]["pump.sleep"]["loop_pct"] == pytest.approx(100.0)

    # the ring holds the same spans; one wake number per wake's spans
    by_wake: dict[int, list] = {}
    for name, _cat, t0, dur, _tid, args in TRACER.records():
        if args and "wake" in args:
            by_wake.setdefault(args["wake"], []).append((name, t0, dur))
        else:
            assert name in ("pump.sleep", "jax.build"), name
    assert len(by_wake) >= 6
    stepped = 0
    for wake, spans in by_wake.items():
        wakes = [s for s in spans if s[0] == "pump.wake"]
        assert len(wakes) == 1, (wake, [s[0] for s in spans])
        for s in spans:
            assert _inside(s, wakes[0]), (wake, s)
        stepped += sum(s[0] == "engine.step" for s in spans)
    assert stepped >= 12                    # 2 streams x 6 pushed wakes
    steps = [args for name, *_x, args in TRACER.records()
             if name == "engine.step"]
    assert {a["trace_id"] for a in steps} == {"trace-0", "trace-1"}
    assert all(a["outputs"] == 32 and a["due_outputs"] <= 32
               for a in steps)
    # the two halves of a step, and between them the sender's own span
    for name, key in (("engine.settle", "sent"),
                      ("native.egress", "datagrams")):
        rows = [args for n, *_x, args in TRACER.records() if n == name]
        assert {a["trace_id"] for a in rows} == {"trace-0", "trace-1"}
        assert sum(a[key] for a in rows) == 2 * 32 * 24, name

    # the benchmark's reduction names a device-idle gap by what the
    # host was doing in it: put two program executions of a chip either
    # side of the longest sleep
    sleep = max(host["pump.sleep"], key=lambda r: r[2])
    events["devices"]["/device:TPU:0"] = {"ops": [], "async": [], "modules": [
        ["jit_megabatch_window_step(1)", sleep[1] - 2000, 1000, {}],
        ["jit_megabatch_window_step(1)", sleep[1] + sleep[2] + 1000, 1000,
         {}]]}
    gaps = reduce_trace.reduce(events, chips=1)["idle_gaps"]
    assert gaps[0][0] == "pump.sleep -> megabatch_window_step"


def app_now() -> int:
    from easydarwin_tpu.relay.session import now_ms
    return now_ms()


def test_span_breakdown_reads_a_ring_dump(tmp_path):
    tool = _load("tools/span_breakdown.py", "span_breakdown")
    tr = SpanTracer(capacity=64)
    for wake in range(4):
        t0 = wake * 10_000_000
        tr.add("pump.sleep", t0, 6_000_000, cat="pump")
        tr.add("pump.wake", t0 + 6_000_000, 4_000_000, cat="pump",
               streams=256, stepped=wake, sent=8 * wake)
        tr.add("pump.live_relay", t0 + 6_000_000, 3_000_000, cat="pump")
        tr.add("rtsp.options", t0, 1_000, cat="rtsp")
    path = tmp_path / "ring.json"
    path.write_text(__import__("json").dumps(tr.dump()))
    doc = tool.breakdown(tool.host_rows(str(path)))
    assert doc["wakes"] == 4 and doc["loop_s"] == pytest.approx(0.04)
    assert set(doc["spans"]) == {"pump.sleep", "pump.wake",
                                 "pump.live_relay"}
    assert doc["spans"]["pump.live_relay"]["ms_per_wake"] == \
        pytest.approx(3.0)
    assert doc["spans"]["pump.wake"]["loop_pct"] == pytest.approx(40.0)
    assert tool.per_wake(tool.wake_args(str(path))) == {
        "streams": 256.0, "stepped": 1.5, "sent": 12.0}
    assert tool.per_wake([{"streams": 3}]) == {"streams": 3.0}  # a parent


def test_span_breakdown_closes_with_the_pairs_handed_and_walked(
        tmp_path, capsys):
    """``pump.wake`` carries the scheduler's hand-over beside the ready
    set's count, and the tool's closing line prints both; a program
    whose wakes carry neither (a parent) prints the line it printed."""
    tool = _load("tools/span_breakdown.py", "span_breakdown")
    for carried, want in (
            (dict(stepped=4, handed=256, walked=5),
             "a wake: streams 256.0, stepped 4.00 (1.6 %), pairs handed "
             "256.0, walked 5.00, sent 61.0"),
            (dict(stepped=4),
             "a wake: streams 256.0, stepped 4.00 (1.6 %), sent 61.0")):
        tr = SpanTracer(capacity=16)
        for wake in range(3):
            tr.add("pump.wake", wake * 10_000_000, 4_000_000, cat="pump",
                   streams=256, sent=61, **carried)
        path = tmp_path / "ring.json"
        path.write_text(__import__("json").dumps(tr.dump()))
        assert tool.main(["span_breakdown.py", str(path)]) == 0
        assert capsys.readouterr().out.splitlines()[-1] == want


# ------------------------------------------------------ (b) due → wire
class _Recorder:
    """Keep every array a histogram's ``observe_many`` is given, one
    entry per delivery (a weighted value repeated by its weight)."""

    def __init__(self, monkeypatch, hist):
        self.seen: list[tuple[np.ndarray, str]] = []
        inner = hist.observe_many

        def observe_many(values, weights=None, **labels):
            vals = np.array(values, dtype=np.float64)
            self.seen.append((vals if weights is None
                              else np.repeat(vals, weights),
                              labels["engine"]))
            inner(values, weights, **labels)
        monkeypatch.setattr(hist, "observe_many", observe_many)


def _held_stream(n_outputs: int, addrs=None) -> RelayStream:
    st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                     StreamSettings(bucket_delay_ms=73))
    for i in range(n_outputs):
        o = CollectingOutput(ssrc=i + 1, out_seq_start=i)
        if addrs:
            o.native_addr = addrs[i % len(addrs)]
        st.add_output(o)
    return st


@pytest.mark.parametrize("engine", ["scalar", "batch", "native"])
def test_due_to_wire_is_ingest_to_wire_net_of_the_hold(monkeypatch, engine):
    if engine == "native" and not native.available():
        pytest.skip("native core unavailable")
    n_out, n_pkt, now = 64, 5, 10_000        # buckets 0-3, 16 outputs each
    rx = _Rx() if engine == "native" else None
    try:
        def one_pass() -> int:
            st = _held_stream(n_out, rx.addrs if rx else None)
            assert [len(b) for b in st.buckets] == [16, 16, 16, 16]
            for i in range(n_pkt):
                st.push_rtp(vid_pkt(i, i * 3000), now - 250)
            # the packets came in 200 ms ago on the latency clock too:
            # past the hold of buckets 0-2 (0, 73, 146 ms), inside
            # bucket 3's (219 ms)
            st.rtp_ring.arrival_ns[:] -= 200_000_000
            if engine == "scalar":
                return st.reflect(now)
            return TpuFanoutEngine(
                egress_fd=rx.send.fileno() if rx else None).step(st, now)

        one_pass()                          # compiles the pass's shapes
        lat = _Recorder(monkeypatch, obs.RELAY_INGEST_TO_WIRE)
        due = _Recorder(monkeypatch, obs.RELAY_DUE_TO_WIRE)
        c0 = (obs.RELAY_INGEST_TO_WIRE.total_count(),
              obs.RELAY_DUE_TO_WIRE.total_count())
        sent = one_pass()
        assert sent == n_out * n_pkt
    finally:
        if rx:
            rx.close()
    # same count, always: one helper observes the pair
    assert (obs.RELAY_INGEST_TO_WIRE.total_count() - c0[0]
            == obs.RELAY_DUE_TO_WIRE.total_count() - c0[1] == sent)
    assert len(lat.seen) == len(due.seen) == 1
    (lat_s, e1), (due_s, e2) = lat.seen[0], due.seen[0]
    assert e1 == e2 == engine and lat_s.shape == due_s.shape == (sent,)
    # deliveries go out output by output, in bucket order; the cohort
    # step files a bucket's packets once, each weighted by its 16 outputs
    bucket = np.repeat(np.arange(n_out) // 16, n_pkt)
    assert np.allclose(due_s, np.maximum(lat_s - bucket * 0.073, 0.0),
                       atol=1e-12)
    assert 0.2 <= lat_s.min() and lat_s.max() < 0.219
    assert (due_s[bucket == 3] == 0).all()            # clamped
    assert (due_s[bucket == 2] > 0.05).all()          # 200+ - 146 ms


# ------------------------------------------ (c) closed, and free when off
def test_span_vocabulary_is_closed(tmp_path):
    lint = _load("tools/metrics_lint.py", "metrics_lint")
    assert lint.lint_spans(obs.REGISTRY, REPO / "easydarwin_tpu") == []
    assert len(set(SPANS)) == len(SPANS)
    for wc in obs.WORK_CLASSES:
        assert f"pump.{wc}" in SPANS
    from easydarwin_tpu.obs.boot import PHASES
    assert [s for s in SPANS if s.startswith("boot.")] == [
        f"boot.{p}" for p in PHASES]
    # a stray name at a call site is caught, an rtsp.<method> is not
    (tmp_path / "x.py").write_text(
        'TRACER.open("pump.mystery", "pump")\n'
        'tok = self._open("engine.step")\n'
        'TRACER.add(f"rtsp.{m}", t0)\n'
        'LEDGER.unit_start("live_relay")\n'
        'LEDGER.unit_start("tea_break")\n'
        'opened = _egress_open(lib, "native.egress", tid)\n'
        'opened = _egress_open(self._lib, "native.nothing", tid)\n')
    errs = lint.lint_spans(obs.REGISTRY, tmp_path)
    assert len(errs) == 3
    assert "pump.mystery" in errs[0] and "pump.tea_break" in errs[1]
    assert "native.nothing" in errs[2]
    assert SPAN_PREFIXES == ("rtsp.",)


def test_span_families_are_in_the_lints_inventory():
    lint = _load("tools/metrics_lint.py", "metrics_lint")
    assert lint.lint(obs.REGISTRY) == []
    reg = obs.Registry()
    reg.counter("pump_loop_seconds_total", "s", labels=("state",)).inc(
        1.0, state="napping")
    reg.histogram("relay_due_to_wire_seconds", "d", labels=("engine",),
                  buckets=(0.01, 0.1))
    errs = lint.lint_spans(reg)
    assert any("napping" in e for e in errs)
    assert any("relay_due_to_wire_seconds: bucket bounds" in e for e in errs)
    assert any("pump_wakes_total missing" in e for e in errs)
    # ISSUE 39's: the boot gauge, the build seconds by part, the RTSP pair
    for fam in ("server_boot_seconds", "jax_executable_build_seconds_total",
                "rtsp_request_seconds_total", "rtsp_requests_total"):
        assert any(f"{fam} missing" in e for e in errs), fam
    reg.counter("jax_executable_build_seconds_total", "s",
                labels=("phase",)).inc(1.0, phase="linking")
    reg.gauge("server_boot_seconds", "s", labels=("phase",)).set(
        1.0, phase="coffee")
    errs = lint.lint_spans(reg)
    assert any("linking" in e for e in errs)
    assert any("coffee" in e for e in errs)


class _CountingAnnotation:
    made = 0

    def __init__(self, name, **kw):
        type(self).made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kw):
        pass


@needs_native
def test_a_wake_allocates_no_traceme_when_nobody_listens(monkeypatch):
    rx = _Rx()
    try:
        app, streams = _server(rx)

        def wake() -> int:
            for st in streams:
                st.push_rtp(vid_pkt(1, 3000), app_now())
            n0 = len(TRACER)
            app._reflect_all()
            app._wake_close()
            return len(TRACER) - n0

        wake()                              # compile outside the counts
        TRACER.open("engine.prime")         # (the lazy JAX probe is done)
        monkeypatch.setattr(TRACER, "_annotate", _CountingAnnotation)
        # a session is live: every span is a TraceMe too
        monkeypatch.setattr(TRACER, "_session_live", lambda: True)
        _CountingAnnotation.made = 0
        spans = wake()
        # (native.egress is the sender thread's, filed after the fact
        # like engine.plan and jax.build: the ring's alone)
        post_hoc = [r[0] for r in TRACER.records()[-spans:]
                    if r[0] in ("native.egress", "engine.plan",
                                "jax.build")]
        assert post_hoc.count("native.egress") == len(streams)
        assert spans >= 12
        assert _CountingAnnotation.made == spans - len(post_hoc)
        # at most 12 spans a stream-step plus 12 a wake
        assert spans <= 12 * len(streams) + 12
        # no session: the ring only, one flag test a span
        monkeypatch.setattr(TRACER, "_session_live", lambda: False)
        _CountingAnnotation.made = 0
        assert wake() >= 12 and _CountingAnnotation.made == 0
        # EDTPU_PROFILE=0: the bracket early-returns, ring and all
        monkeypatch.setattr(TRACER, "_session_live", lambda: True)
        for inst in (TRACER, obs.PROFILER, obs.LEDGER):
            monkeypatch.setattr(inst, "enabled", False)
        assert TRACER.open("pump.wake") is None
        assert wake() == 0 and _CountingAnnotation.made == 0
        # ... and the relay relays all the same: 4 wakes, 4 packets each
        assert all(o.packets_sent == 4 for st in streams
                   for o in st.outputs)
    finally:
        rx.close()


def test_open_close_stamps_the_ring_and_the_annotation_alike():
    tr = SpanTracer(capacity=8)
    tr._annotate, tr._session_live = _CountingAnnotation, lambda: True
    _CountingAnnotation.made = 0
    tr.wake = 7
    tok = tr.open("engine.egress", "tpu", trace_id="t")
    end = tr.close(tok, sent=3)
    (name, cat, t0, dur, _tid, args), = tr.records()
    assert (name, cat) == ("engine.egress", "tpu")
    assert args == {"trace_id": "t", "wake": 7, "sent": 3}
    assert t0 == tok.t0 and t0 + dur == end
    assert _CountingAnnotation.made == 1
    assert tr.lap(tr.open("engine.prime")) >= 0 and tr.lap(None) == 0
    tr.enabled = False
    assert tr.open("engine.step") is None and tr.close(None) >= end
    assert len(tr) == 2
