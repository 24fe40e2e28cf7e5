"""ISSUE 39: what a process did before it listened, and what its RTSP
handlers cost the event-loop thread.

A boot is a process's, so it is a child's here: ``main`` with
``--exit-after-boot`` on the CPU backend, then the child prints its own
ring, gauge and event log.
"""

import json
import os
import subprocess
import sys

import pytest

from easydarwin_tpu import obs
from easydarwin_tpu.obs.boot import PHASES, BootPhases, process_start_ns
from easydarwin_tpu.server import ServerConfig, StreamingServer
from easydarwin_tpu.utils.client import RtspClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import json, sys, time
from easydarwin_tpu.__main__ import main
rc = main(sys.argv[1:])
from easydarwin_tpu import obs
print("BOOT " + json.dumps({
    "rc": rc, "now_ns": time.perf_counter_ns(),
    "spans": [[n, t0, dur, args] for n, _c, t0, dur, _t, args
              in obs.TRACER.records() if n.startswith("boot.")],
    "gauge": obs.SERVER_BOOT_SECONDS.as_value(),
    "events": [e for e in obs.EVENTS.tail() if e["event"] == "server.boot"],
    "builds": [e for e in obs.EVENTS.tail() if e["event"] == "jax.build"],
    "built": obs.JAX_EXECUTABLES_BUILT.total()}))
"""


def boot(tmp_path, *flags):
    r = subprocess.run(
        [sys.executable, "-c", CHILD, "-x", "-p", "0", "--service-port", "0",
         "--bind-ip", "127.0.0.1", *flags],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert r.returncode == 0, r.stdout[-800:] + r.stderr[-800:]
    assert "listening:" in r.stdout
    return json.loads(next(ln for ln in r.stdout.splitlines()
                           if ln.startswith("BOOT "))[5:])


@pytest.mark.parametrize("flags, through", [
    (("--tpu-fanout",), PHASES),
    ((), ("interpreter", "imports", "listen")),
], ids=["tpu_fanout", "host_only"])
def test_a_boot_leaves_its_phases_in_order(tmp_path, flags, through):
    doc = boot(tmp_path, *flags)
    assert doc["rc"] == 0
    spans = doc["spans"]
    assert [s[0] for s in spans] == [f"boot.{p}" for p in through]
    # each starts where the last one ended, and none lasts longer than
    # the process has lived
    for (_n, t0, dur, _a), nxt in zip(spans, spans[1:]):
        assert t0 + dur == nxt[1]
    assert all(dur >= 0 for _n, _t0, dur, _a in spans)
    assert spans[-1][1] + spans[-1][2] <= doc["now_ns"]
    # the gauge: the five sum to total, one not gone through reads 0
    g = doc["gauge"]
    assert set(g) == set(PHASES) | {"total"}
    assert g["total"] == pytest.approx(sum(g[p] for p in PHASES), abs=1e-5)
    for p in PHASES:
        if p in through:
            span = next(s for s in spans if s[0] == f"boot.{p}")
            assert g[p] == pytest.approx(span[2] / 1e9, abs=1e-5)
            assert g[p] > 0
        else:
            assert g[p] == 0
    # Python's own start and this package's imports (JAX among them)
    # are seconds, not the age of a machine
    assert 0 < g["interpreter"] < 60 and 0.05 < g["imports"] < 240
    # one valid event with the same six numbers
    (ev,) = doc["events"]
    assert "invalid" not in ev
    assert {k: ev[k] for k in g} == g
    if "native" in through:
        args = {s[0]: s[3] for s in spans}
        assert args["boot.native"]["built"] in (0, 1)
        assert args["boot.backend"]["devices"] >= 1
    # what the boot built is in the event log under its name
    assert len(doc["builds"]) == doc["built"]
    assert all(b["program"] and b["source"] in ("compile", "cache")
               for b in doc["builds"])


def test_process_start_is_the_oss_record_or_the_first_line(monkeypatch):
    import time
    first = time.perf_counter_ns()
    got = process_start_ns(first)
    # this process started before this line, and not before the machine
    assert got <= first
    assert first - got < time.clock_gettime(time.CLOCK_BOOTTIME) * 1e9

    def no_proc(*a, **kw):
        raise OSError("no /proc here")
    monkeypatch.setattr("builtins.open", no_proc)
    assert process_start_ns(first) == first


def test_phases_sum_whatever_the_order_of_entry():
    import time
    obs.TRACER.clear()
    b = BootPhases(time.perf_counter_ns())
    b.enter("native")
    b.enter("backend", built=1)
    b.enter("listen", devices=4)
    doc = b.done()
    assert doc["total"] == pytest.approx(sum(doc[p] for p in PHASES),
                                         abs=1e-5)
    ring = {n: a for n, *_x, a in obs.TRACER.records()}
    assert ring["boot.native"] == {"built": 1}
    assert ring["boot.backend"] == {"devices": 4}
    assert obs.SERVER_BOOT_SECONDS.value(phase="total") == doc["total"]
    with pytest.raises(KeyError):
        b.enter("coffee")


PUSH_SDP = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=pushtest\r\n"
            "c=IN IP4 0.0.0.0\r\nt=0 0\r\na=control:*\r\n"
            "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
            "a=control:trackID=1\r\n")


def by_method(fam):
    return dict(fam.as_value())


async def test_an_rtsp_round_trip_is_counted_by_method():
    n0, s0 = by_method(obs.RTSP_REQUESTS), by_method(obs.RTSP_REQUEST_SECONDS)
    obs.TRACER.clear()
    app = StreamingServer(ServerConfig(
        rtsp_port=0, service_port=0, reflect_interval_ms=5,
        bind_ip="127.0.0.1"))
    await app.start()
    try:
        assert app.boot is None                 # not main's: no boot spans
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/live/issue39.sdp"
        pusher, player = RtspClient(), RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, PUSH_SDP)
        await player.connect("127.0.0.1", app.rtsp.port)
        await player.play_start(uri)
        await player.teardown(uri)
        await pusher.close()
        await player.close()
    finally:
        await app.stop()
    n1, s1 = by_method(obs.RTSP_REQUESTS), by_method(obs.RTSP_REQUEST_SECONDS)
    grew = {m: n1[m] - n0.get(m, 0) for m in n1 if n1[m] != n0.get(m, 0)}
    # the pusher: ANNOUNCE, SETUP, RECORD; the player: DESCRIBE, SETUP,
    # PLAY, TEARDOWN — each counted under its own method
    for method, n in (("announce", 1), ("record", 1), ("describe", 1),
                      ("setup", 2), ("play", 1), ("teardown", 1)):
        assert grew.get(method, 0) >= n, (method, grew)
    # the seconds are the rtsp.<method> spans' own, from the same reads
    spans: dict[str, float] = {}
    for name, _c, _t0, dur, _t, _a in obs.TRACER.records():
        if name.startswith("rtsp."):
            spans[name[5:]] = spans.get(name[5:], 0.0) + dur / 1e9
    assert set(spans) == set(grew)
    for method, seconds in spans.items():
        assert s1[method] - s0.get(method, 0.0) == pytest.approx(seconds)
        assert seconds > 0
    assert not any(n.startswith("boot.") for n in obs.TRACER.names())
