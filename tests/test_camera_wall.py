"""The camera wall (ISSUE 30): many thin streams on the device path.

* thin streams (1-4 outputs) through ``_reflect_all`` with
  ``tpu_min_outputs = 1`` put the bytes ``RelayStream.reflect`` does on
  every output, in its order;
* the stacked pass's shapes are a closed set, built when the handed
  pairs first reach a member: all of it before any media, one member a
  wake once media flows;
* the three counters the deployment brought add up.
"""

import asyncio
import random
import socket

import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.protocol import rtp, sdp
from easydarwin_tpu.relay import megabatch as mb
from easydarwin_tpu.relay import pump
from easydarwin_tpu.relay.fanout import TpuFanoutEngine
from easydarwin_tpu.relay.megabatch import (PACKET_PADS, MegabatchScheduler,
                                            _packet_pad, _stream_pad,
                                            _sub_pad)
from easydarwin_tpu.relay.output import CollectingOutput
from easydarwin_tpu.relay.session import now_ms
from easydarwin_tpu.relay.stream import RelayStream, StreamSettings

VIDEO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=control:trackID=1\r\n")

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native core unavailable")


def _frame(rng: random.Random, seq: int, ts: int, idr: bool) -> list[bytes]:
    """One frame of 1-13 packets (an IDR of 10-13), marker on the last."""
    n = rng.randint(10, 13) if idr else rng.randint(1, 13)
    out = []
    for k in range(n):
        payload = bytes(((3 << 5) | (5 if idr else 1),)) + rng.randbytes(
            rng.randint(20, 1400))
        out.append(rtp.RtpPacket(
            payload_type=96, seq=(seq + k) & 0xFFFF, timestamp=ts,
            ssrc=0xCA3E0000 | (seq & 0xFF), marker=k == n - 1,
            payload=payload).to_bytes())
    return out


class _Sockets:
    """One bound UDP receiver per output, and the socket the engine
    sends from: what arrives at receiver i is output i's wire."""

    def __init__(self, n: int):
        self.socks = []
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.bind(("127.0.0.1", 0))
            s.setblocking(False)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 22)
            self.socks.append(s)
        self.addrs = [s.getsockname() for s in self.socks]
        self.rx: list[list[bytes]] = [[] for _ in self.socks]
        self.send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.send.setblocking(False)

    def drain(self) -> None:
        for i, s in enumerate(self.socks):
            while True:
                try:
                    self.rx[i].append(s.recv(65536))
                except BlockingIOError:
                    break

    def close(self) -> None:
        for s in self.socks + [self.send]:
            s.close()


def _outputs(rng: random.Random, n: int, addrs=None) -> list:
    outs = []
    for i in range(n):
        o = CollectingOutput(ssrc=rng.getrandbits(32),
                             out_seq_start=rng.getrandbits(16),
                             out_ts_start=rng.getrandbits(32))
        if addrs is not None:
            o.native_addr = addrs[i]
        outs.append(o)
    return outs


# ------------------------------------- (1) the device path == the scalar loop
@needs_native
@pytest.mark.parametrize("ready_set", [False, True],
                         ids=["every_stream", "ready_set"])
@pytest.mark.parametrize("n_out", [1, 2, 3, 4])
def test_thin_streams_on_the_device_path_equal_the_scalar_loop(n_out,
                                                               ready_set):
    """12 cameras x ``n_out`` UDP viewers, un-locked phases, through
    ``_reflect_all`` with ``tpu_min_outputs = 1``, against the same
    pushes through ``RelayStream.reflect``: every output's wire bytes
    equal, in order, sequence numbers and SSRC as announced.
    ``ready_set``: with the pump's wheel, as ``_pump_loop`` runs it — a
    wake steps the cameras that pushed (a quarter of them), the
    scheduler is still handed all twelve."""
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    n_src, wakes = 12, 40
    wire = _Sockets(n_src * n_out)
    try:
        cfg = ServerConfig(tpu_fanout=True, tpu_min_outputs=1,
                           bucket_delay_ms=73, slo_enabled=False,
                           access_log_enabled=False)
        app = StreamingServer(cfg)
        app.rtsp.shared_egress = wire.send
        if ready_set:
            app.pump.wheel = native.TimerWheel(now_ms())
        roster0 = obs.PUMP_ROSTER_STREAMS.value()
        stepped0 = obs.PUMP_STEPPED_STREAMS.value()
        dev, ref, dev_outs, ref_outs = [], [], [], []
        for k in range(n_src):
            st = app.registry.find_or_create(f"/wall/cam{k}",
                                             VIDEO_SDP).streams[1]
            twin = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                               StreamSettings(bucket_delay_ms=73))
            addrs = wire.addrs[k * n_out:(k + 1) * n_out]
            for o in _outputs(random.Random(1000 + k), n_out, addrs):
                st.add_output(o)
                dev_outs.append(o)
            for o in _outputs(random.Random(1000 + k), n_out):
                twin.add_output(o)
                ref_outs.append(o)
            dev.append(st)
            ref.append(twin)
        rng = random.Random(30 + n_out)
        phase = [rng.randrange(4) for _ in range(n_src)]   # un-locked
        seq = [rng.getrandbits(16) for _ in range(n_src)]
        frames = [0] * n_src
        scalar0 = obs.RELAY_INGEST_TO_WIRE.count(engine="scalar")
        fallback0 = obs.MEGABATCH_FALLBACK.total()
        pushed = 0
        for wake in range(wakes):
            t = now_ms()
            for k in range(n_src):
                if wake % 4 != phase[k]:
                    continue
                pkts = _frame(rng, seq[k], frames[k] * 3600,
                              idr=frames[k] % 5 == 0)
                seq[k] += len(pkts)
                frames[k] += 1
                pushed += len(pkts) * n_out
                for p in pkts:
                    dev[k].push_rtp(p, t)
                    ref[k].push_rtp(p, t)
            app._reflect_all()
            if ready_set:
                app.pump.arm(app.registry.sessions)
                assert app.pump.audit() == 0
            app._wake_close()
            for twin in ref:
                twin.reflect(now_ms())
            wire.drain()
        for _ in range(3):                  # nothing new: flush what is due
            app._reflect_all()
            app._wake_close()
            wire.drain()
        assert sum(len(r) for r in wire.rx) == pushed
        roster = obs.PUMP_ROSTER_STREAMS.value() - roster0
        stepped = obs.PUMP_STEPPED_STREAMS.value() - stepped0
        assert roster == n_src * (wakes + 3)
        # all twelve in the first wake, then the three that pushed and
        # any whose SR came due
        assert stepped == roster if not ready_set else stepped < roster / 3
        for i, (o_dev, o_ref) in enumerate(zip(dev_outs, ref_outs)):
            assert wire.rx[i] == o_ref.rtp_packets, f"output {i}"
            first = rtp.RtpPacket.parse(wire.rx[i][0])
            assert first.ssrc == o_dev.rewrite.ssrc
            assert first.seq == o_dev.rewrite.out_seq_start
        # the device path did it: stacked passes, no scalar delivery from
        # the server's streams, no per-stream query behind the scheduler
        assert app.pump.megabatch is not None and app.pump.megabatch.passes > 0
        assert app.pump.megabatch.mismatches == 0
        assert not any(o.rtp_packets for o in dev_outs)
        assert obs.MEGABATCH_FALLBACK.total() == fallback0
        assert (obs.RELAY_INGEST_TO_WIRE.count(engine="scalar")
                - scalar0) == pushed        # the twins' own, all of it
    finally:
        wire.close()


def test_the_servers_default_still_sends_thin_streams_down_the_scalar_loop():
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    assert ServerConfig().tpu_min_outputs == 8
    app = StreamingServer(ServerConfig(tpu_fanout=True,
                                       access_log_enabled=False))
    st = app.registry.find_or_create("/wall/cam", VIDEO_SDP).streams[1]
    for o in _outputs(random.Random(1), 7):
        st.add_output(o)
    assert not app._on_device(st)
    st.add_output(_outputs(random.Random(2), 1)[0])
    assert app._on_device(st)
    app.config.tpu_fanout = False
    assert not app._on_device(st)


# ------------------------------------------------ (2) the closed shape set
def test_the_pad_ladders():
    assert [_stream_pad(n) for n in (1, 2, 4, 5, 16, 17, 64, 65, 256)] == \
        [1, 4, 4, 16, 16, 64, 64, 256, 256]
    assert [_packet_pad(n) for n in (0, 1, 16, 17, 64)] == \
        [16, 16, 16, 64, 64]
    assert [_sub_pad(n) for n in (1, 4, 8, 9, 64, 256, 257)] == \
        [8, 8, 8, 16, 64, 256, 512]
    members = MegabatchScheduler.members
    assert members({8: 1}) == {(1, 16, 8), (1, 64, 8)}
    wall = members({8: 256})
    assert len(wall) == 10 and (256, 64, 8) in wall
    assert len(members({256: 16})) == 6
    # a ninth viewer on one camera of the wall: its pad's first rung only
    assert members({8: 255, 16: 1}) - wall == {(1, 16, 16), (1, 64, 16)}


def _push(st, n: int, seq: int, t: int) -> int:
    for _ in range(n):
        st.push_rtp(rtp.RtpPacket(
            payload_type=96, seq=seq & 0xFFFF, timestamp=seq, ssrc=1,
            payload=bytes((0x65,)) + bytes(40)).to_bytes(), t)
        seq += 1
    return seq


@needs_native
def test_the_shape_set_is_closed_and_built_ahead(monkeypatch):
    """Stream counts 1..40 and packet counts 1..40 through the scheduler
    with media flowing, one stream whose fast list (12) is shorter than
    its outputs (20) and one that fell 150 packets behind: a wake's
    ``begin_wake`` loads at most one member, every dispatched shape is a
    member of the set the riders give, and once the set is built nothing
    builds."""
    n_max = 40
    wire = _Sockets(4)
    dispatched = set()
    real_step = mb.megabatch_window_step

    def spy(window, state):
        dispatched.add((window.shape[0], window.shape[1], state.shape[1]))
        return real_step(window, state)

    monkeypatch.setattr(mb, "megabatch_window_step", spy)
    try:
        rng = random.Random(7)
        streams = []
        for k in range(n_max):
            st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                             StreamSettings(bucket_delay_ms=0))
            n_out, n_fast = (20, 12) if k == 2 else (1 + k % 4,) * 2
            outs = _outputs(rng, n_out)
            for i, o in enumerate(outs[:n_fast]):
                o.native_addr = wire.addrs[i % 4]
            for o in outs:
                st.add_output(o)
            streams.append(st)
        engines = [TpuFanoutEngine(egress_fd=wire.send.fileno())
                   for _ in streams]
        sched = MegabatchScheduler()
        built = obs.JAX_EXECUTABLES_BUILT.total
        members = MegabatchScheduler.members({8: n_max - 1, 16: 1})
        seq, t = 0, 1000
        for n in list(range(1, n_max + 1)) + [n_max] * 8:
            pairs = list(zip(streams[:n], engines[:n]))
            for k, st in enumerate(streams[:n]):
                seq = _push(st, 1 + (n * 7 + k * 3 + seq) % 40, seq, t)
            if t == 1000 + 20 * (n_max + 3):
                seq = _push(streams[0], 150, seq, t)     # fell behind
            b0, m0 = built(), len(sched._built)
            complete = sched._built == members
            # asserts between the scheduler's phases: drives them itself
            for _st, eng in pairs:
                eng.megabatch_owned = True
            sched.begin_wake(pairs, t)
            ahead = len(sched._built) - m0
            for st, eng in pairs:
                eng.step(st, t)
            sched.end_wake(pairs, t)
            sched.drain()
            wire.drain()
            # media flows: at most one member ahead of need a wake (the
            # prime may meet one more at first use)
            assert ahead <= 2
            if complete:
                assert built() == b0, f"a build at n={n} with the set built"
            t += 20
        assert dispatched <= members
        assert sched._built == members
        # the fast list's pad, the wide packet pad and tall passes were used
        assert {s for _b, _p, s in dispatched} == {8, 16}
        assert any(p == PACKET_PADS[-1] for _b, p, _s in dispatched)
        assert any(b == 64 for b, _p, _s in dispatched)
        assert sched.mismatches == 0
    finally:
        wire.close()


@needs_native
def test_a_stream_that_fell_behind_rides_more_passes_and_counts_once():
    wire = _Sockets(2)
    try:
        streams = []
        for k in range(2):
            st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                             StreamSettings(bucket_delay_ms=0))
            for o in _outputs(random.Random(k), 2, wire.addrs):
                st.add_output(o)
            streams.append(st)
        engines = [TpuFanoutEngine(egress_fd=wire.send.fileno())
                   for _ in streams]
        pairs = list(zip(streams, engines))
        sched = MegabatchScheduler()
        seq = _push(streams[0], 3, 0, 1000)
        seq = _push(streams[1], 3, seq, 1000)
        for t, behind in ((1000, 0), (1020, 150)):
            if behind:
                seq = _push(streams[0], behind, seq, t)
                _push(streams[1], 20, seq, t)
                passes, coalesced = sched.passes, sched.streams_coalesced
            pump.wake(pairs, sched, t)
            sched.drain()
        # 64 + 64 + 22 of one stream and 20 of the other: four rows of
        # the wide pad in one pass of the pair's rung, two streams
        assert sched.passes - passes == 1
        assert sched.streams_coalesced - coalesced == 2
        assert all(p in PACKET_PADS for _b, p, _s in sched._built)
        assert sched.mismatches == 0
        wire.drain()
        # both cameras' viewer i listens on socket i: nothing was lost
        assert [len(r) for r in wire.rx] == [3 + 150 + 3 + 20] * 2
    finally:
        wire.close()


def test_the_set_is_built_while_players_join_before_the_first_packet():
    """20 cameras with their viewers and no media yet: one wake of the
    server's pump loads every member the pairs can reach.  A fat stream
    still waiting for its first packet is not planned for: its audience
    walks through every pad while players join."""
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    app = StreamingServer(ServerConfig(
        tpu_fanout=True, tpu_min_outputs=1, slo_enabled=False,
        access_log_enabled=False))
    for k in range(20):
        st = app.registry.find_or_create(f"/wall/cam{k}",
                                         VIDEO_SDP).streams[1]
        for o in _outputs(random.Random(k), 1 + k % 4):
            st.add_output(o)
    fat = app.registry.find_or_create("/wall/lobby", VIDEO_SDP).streams[1]
    for o in _outputs(random.Random(99), 30):
        fat.add_output(o)
    app._reflect_all()
    app._wake_close()
    assert app.pump.megabatch._built == MegabatchScheduler.members({8: 20})
    assert app.pump.megabatch.passes == 0        # nothing staged, nothing primed


# ------------------------------------------------ (3) the counters add up
@needs_native
def test_engine_steps_and_megabatch_cells_add_up():
    wire = _Sockets(4)
    try:
        rng = random.Random(11)
        streams = []
        for k in range(6):
            st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                             StreamSettings(bucket_delay_ms=0))
            for o in _outputs(rng, 1 + k % 4,
                              [wire.addrs[i] for i in range(1 + k % 4)]):
                st.add_output(o)
            streams.append(st)
        engines = [TpuFanoutEngine(egress_fd=wire.send.fileno())
                   for _ in streams]
        sched = MegabatchScheduler()
        pairs = list(zip(streams, engines))
        for _st, eng in pairs:      # counts inside the step loop: drives
            eng.megabatch_owned = True      # the phases itself
        steps0 = {r: obs.ENGINE_STEPS.value(result=r)
                  for r in ("idle", "worked")}
        cells0 = {k: obs.MEGABATCH_CELLS.value(kind=k)
                  for k in ("real", "staged")}
        calls = want_real = want_worked = 0
        seq, t = 0, 1000
        for wake in range(12):
            fed = set()
            for k, st in enumerate(streams):
                if (wake + k) % 3:
                    continue            # two wakes in three find nothing
                fed.add(k)
                for _ in range(1 + k):
                    st.push_rtp(rtp.RtpPacket(
                        payload_type=96, seq=seq & 0xFFFF, timestamp=seq,
                        ssrc=1, payload=bytes((0x65,)) + bytes(40)
                    ).to_bytes(), t)
                    seq += 1
                want_real += (1 + k) * st.num_outputs
            sched.begin_wake(pairs, t)
            for k, (st, eng) in enumerate(pairs):
                eng.step(st, t)
                calls += 1
                want_worked += k in fed
            sched.end_wake(pairs, t)
            sched.drain()
            t += 20
        idle = obs.ENGINE_STEPS.value(result="idle") - steps0["idle"]
        worked = obs.ENGINE_STEPS.value(result="worked") - steps0["worked"]
        assert idle + worked == calls
        assert worked == want_worked and idle > worked
        real = obs.MEGABATCH_CELLS.value(kind="real") - cells0["real"]
        staged = obs.MEGABATCH_CELLS.value(kind="staged") - cells0["staged"]
        assert real == want_real
        assert 0 < real <= staged
        assert staged % (PACKET_PADS[0] * 8) == 0
    finally:
        wire.close()


async def test_interleaved_ingest_counts_what_was_pushed():
    """``ingest.read``: per read, never per packet; its packets add up
    to what the pusher wrote, and so does the counter."""
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    from easydarwin_tpu.utils.client import RtspClient
    push_sdp = ("v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=wall\r\n"
                "c=IN IP4 0.0.0.0\r\nt=0 0\r\na=control:*\r\n" + VIDEO_SDP[5:])
    app = StreamingServer(ServerConfig(rtsp_port=0, service_port=0,
                                       bind_ip="127.0.0.1",
                                       access_log_enabled=False))
    await app.start()
    try:
        uri = f"rtsp://127.0.0.1:{app.rtsp.port}/wall/cam0"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(uri, push_sdp)
        pk0 = obs.INGEST_INTERLEAVED_PACKETS.total()
        s0 = obs.INGEST_INTERLEAVED_SECONDS.total()
        obs.TRACER.clear()
        rng = random.Random(5)
        n = 0
        for f in range(6):
            for p in _frame(rng, 100 + n, f * 3600, idr=f == 0):
                pusher.push_packet(0, p)
                n += 1
            await asyncio.sleep(0.02)
        for _ in range(100):
            if obs.INGEST_INTERLEAVED_PACKETS.total() - pk0 >= n:
                break
            await asyncio.sleep(0.01)
        assert obs.INGEST_INTERLEAVED_PACKETS.total() - pk0 == n
        assert obs.INGEST_INTERLEAVED_SECONDS.total() > s0
        reads = [r for r in obs.TRACER.records() if r[0] == "ingest.read"]
        assert sum(r[5]["packets"] for r in reads) == n
        assert len(reads) < n               # a read holds a frame's packets
        st = app.registry.find("/wall/cam0").streams[1]
        assert st.rtp_ring.head - st.rtp_ring.tail == n
        await pusher.close()
    finally:
        await app.stop()


def test_the_new_families_are_in_the_lints_vocabulary():
    import importlib.util
    import pathlib
    repo = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "metrics_lint", repo / "tools/metrics_lint.py")
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    assert lint.lint_spans(obs.REGISTRY, repo / "easydarwin_tpu") == []
    assert "ingest.read" in obs.SPANS
    reg = obs.Registry()
    reg.counter("engine_steps_total", "s", labels=("result",)).inc(
        result="napped")
    reg.counter("megabatch_cells_total", "c", labels=("kind",)).inc(
        kind="imagined")
    errs = lint.lint_spans(reg)
    assert any("napped" in e for e in errs)
    assert any("imagined" in e for e in errs)
    assert any("ingest_interleaved_packets_total missing" in e for e in errs)
