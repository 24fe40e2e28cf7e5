"""Resilience subsystem (ISSUE 5): deterministic fault injection,
degradation ladder, session checkpoint/hot-restore.

Load-bearing guarantees pinned here:

* same FaultPlan seed → same injection schedule (chaos is a regression
  test, not a dice roll), per site, independent of call interleaving;
* the ladder retries transient device errors with bounded backoff
  before any rung change, degrades under persistent ones, recovers with
  time hysteresis, and sheds newest-first;
* a mid-relay kill + checkpoint restore resumes subscriber wire bytes
  seq/ts-continuous and BYTE-IDENTICAL to an uninterrupted oracle run —
  at the 16 src × 16 sub megabatch shape, over real UDP sockets;
* the native ``ed_fault_*`` knobs fail sends through the production
  EAGAIN/hard-error paths and count ``ed_stats.fault_injections``.
"""

import json
import random
import socket

import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.obs.events import EventLog
from easydarwin_tpu.obs.metrics import Counter, Gauge
from easydarwin_tpu.protocol import sdp
from easydarwin_tpu.relay import pump
from easydarwin_tpu.relay.fanout import TpuFanoutEngine
from easydarwin_tpu.relay.megabatch import MegabatchScheduler
from easydarwin_tpu.relay.output import CollectingOutput, WriteResult
from easydarwin_tpu.relay.session import SessionRegistry
from easydarwin_tpu.relay.stream import RelayStream, StreamSettings
from easydarwin_tpu.resilience import checkpoint as ckpt_mod
from easydarwin_tpu.resilience.inject import (INJECTOR, FaultInjector,
                                              FaultPlan, InjectedFault)
from easydarwin_tpu.resilience.ladder import (LEVEL_CPU, LEVEL_DEVICE,
                                              LEVEL_FULL, LEVEL_SHED,
                                              DegradationLadder,
                                              LadderConfig)

VIDEO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=control:trackID=1\r\n")

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native core unavailable")


def vid_pkt(seq: int, ts: int | None = None, nal_type: int = 1) -> bytes:
    from easydarwin_tpu.protocol import rtp
    payload = bytes(((3 << 5) | nal_type,)) + bytes(
        (seq * 7 + i) & 0xFF for i in range(80))
    return rtp.RtpPacket(payload_type=96, seq=seq & 0xFFFF,
                         timestamp=(seq * 90 if ts is None else ts),
                         ssrc=0x1234, payload=payload).to_bytes()


@pytest.fixture
def global_injector():
    """The PROCESS-WIDE injector the relay hooks consult — always
    disarmed afterwards so no other test runs under a fault plan."""
    try:
        yield INJECTOR
    finally:
        INJECTOR.disarm()


def _private_injector(**plan_kw) -> FaultInjector:
    inj = FaultInjector(events=EventLog(),
                        counter=Counter("test_fault_injected_total", "t",
                                        labels=("site",)))
    inj.arm(FaultPlan(**plan_kw))
    return inj


# ----------------------------------------------------------- fault plan
def test_fault_plan_parse_roundtrip():
    spec = "seed=7,ingest_drop=0.05,egress_enobufs_every=300"
    p = FaultPlan.parse(spec)
    assert p.seed == 7 and p.ingest_drop == 0.05
    assert p.egress_enobufs_every == 300
    assert FaultPlan.parse(p.to_spec()) == p
    assert not FaultPlan.parse("").any_active()


def test_fault_plan_rejects_unknown_key():
    with pytest.raises(ValueError, match="ingest_dorp"):
        FaultPlan.parse("ingest_dorp=0.1")


def _decision_trace(seed: int, n: int = 300) -> list:
    inj = _private_injector(seed=seed, ingest_drop=0.3, ingest_corrupt=0.2,
                            slow_sub_every=7, device_error_every=11)
    out = []
    hold: list = []
    for i in range(n):
        pkts = inj.ingest(vid_pkt(i), hold)
        out.append(tuple(pkts))
        out.append(inj.slow_subscriber())
        try:
            inj.device_dispatch("t")
            out.append(False)
        except InjectedFault:
            out.append(True)
    return out


def test_fault_schedule_deterministic_per_seed():
    assert _decision_trace(42) == _decision_trace(42)
    assert _decision_trace(42) != _decision_trace(43)


def test_fault_schedule_independent_of_other_sites():
    """One site's decision stream must not shift when ANOTHER site is
    exercised in between — per-site rng streams, not one shared one."""
    a = _private_injector(seed=5, ingest_drop=0.5)
    b = _private_injector(seed=5, ingest_drop=0.5, slow_sub_every=2)
    seq_a, seq_b = [], []
    for i in range(200):
        seq_a.append(len(a.ingest(vid_pkt(i), [])))
        b.slow_subscriber()            # interleaved other-site traffic
        seq_b.append(len(b.ingest(vid_pkt(i), [])))
    assert seq_a == seq_b


def test_ingest_drop_corrupt_reorder_sites():
    drop = _private_injector(seed=1, ingest_drop=1.0)
    assert drop.ingest(vid_pkt(0), []) == []
    assert drop.counts()["ingest_drop"] == 1

    cor = _private_injector(seed=1, ingest_corrupt=1.0)
    pkt = vid_pkt(0)
    (mut,) = cor.ingest(pkt, [])
    assert mut[:12] == pkt[:12]        # the RTP header is never touched
    assert mut != pkt and len(mut) == len(pkt)

    ro = _private_injector(seed=1, ingest_reorder=1.0)
    hold: list = []
    p0, p1 = vid_pkt(0), vid_pkt(1)
    assert ro.ingest(p0, hold) == []           # held
    assert ro.ingest(p1, hold) == [p1, p0]     # adjacent swap
    assert hold == []                          # slot drained


def test_reorder_hold_is_stream_owned(global_injector):
    """A held packet lives on ITS stream and dies with it — an id-reuse
    release into an unrelated stream's ring is structurally impossible
    (the megabatch cursor-pruning hazard class)."""
    global_injector.arm(FaultPlan(seed=2, ingest_reorder=1.0))
    a = RelayStream(sdp.parse(VIDEO_SDP).streams[0])
    held_pkt = vid_pkt(0)
    a.push_rtp(held_pkt, 1000)
    assert len(a.rtp_ring) == 0 and a._chaos_hold == [held_pkt]
    b = RelayStream(sdp.parse(VIDEO_SDP).streams[0])
    b.push_rtp(vid_pkt(100), 1000)     # B's own FIRST push gets held
    assert b._chaos_hold == [vid_pkt(100)]
    b.push_rtp(vid_pkt(101), 1000)     # …and released as B's own swap
    assert len(b.rtp_ring) == 2
    assert b.rtp_ring.get(0) == vid_pkt(101)   # never A's held packet
    assert a._chaos_hold == [held_pkt]         # still with its owner


def test_device_dispatch_count_and_period():
    inj = _private_injector(seed=1, device_error_every=3)
    fired = []
    for _ in range(6):
        try:
            inj.device_dispatch("x")
            fired.append(False)
        except InjectedFault:
            fired.append(True)
    assert fired == [False, False, True, False, False, True]

    clk = [0.0]
    inj = FaultInjector(events=EventLog(),
                        counter=Counter("test_fault2_total", "t",
                                        labels=("site",)),
                        clock=lambda: clk[0])
    inj.arm(FaultPlan(seed=1, device_error_period_s=60.0))
    with pytest.raises(InjectedFault):
        inj.device_dispatch("x")       # period timer starts expired
    clk[0] = 30.0
    inj.device_dispatch("x")           # mid-period: quiet
    clk[0] = 61.0
    with pytest.raises(InjectedFault):
        inj.device_dispatch("x")


def test_rearm_same_seed_replays_schedule():
    inj = _private_injector(seed=9, ingest_drop=0.4)
    first = [len(inj.ingest(vid_pkt(i), [])) for i in range(100)]
    inj.arm(FaultPlan(seed=9, ingest_drop=0.4))
    assert [len(inj.ingest(vid_pkt(i), []))
            for i in range(100)] == first


# -------------------------------------------------- site wiring (hooks)
def test_push_rtp_injection_wiring(global_injector):
    st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    global_injector.arm(FaultPlan(seed=3, ingest_drop=1.0))
    assert st.push_rtp(vid_pkt(0), 1000) == -1
    assert len(st.rtp_ring) == 0
    global_injector.disarm()
    assert st.push_rtp(vid_pkt(1), 1000) >= 0


def test_slow_subscriber_wiring(global_injector):
    st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    out = CollectingOutput(ssrc=1)
    st.add_output(out)
    for i in range(8):
        st.push_rtp(vid_pkt(i), 1000)
    global_injector.arm(FaultPlan(seed=3, slow_sub_every=2))
    st.reflect(1000)
    assert out.stalls > 0              # every 2nd write WOULD_BLOCKed
    global_injector.disarm()
    st.reflect(1000)
    assert len(out.rtp_packets) == 8   # bookmark replay delivered all


@needs_native
def test_engine_device_dispatch_and_stale_params_wiring(global_injector):
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("127.0.0.1", 0))
    recv.setblocking(False)
    try:
        st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                         StreamSettings(bucket_delay_ms=0))
        out = CollectingOutput(ssrc=7)
        out.native_addr = recv.getsockname()
        st.add_output(out)
        eng = TpuFanoutEngine(egress_fd=send.fileno())
        t, seq = 1000, 0

        def wake():
            nonlocal t, seq
            st.push_rtp(vid_pkt(seq), t)
            seq += 1
            eng.step(st, t)
            t += 20

        wake()                         # warm: params cached
        global_injector.arm(FaultPlan(seed=3, device_error_every=1))
        with pytest.raises(InjectedFault):
            wake()                     # every device dispatch raises
        global_injector.arm(FaultPlan(seed=3, stale_params_every=1))
        pre = eng.device_param_refreshes
        wake()
        wake()
        # stale-params invalidation forces a device refresh EVERY pass
        # (steady state without it: zero — the key is cached)
        assert eng.device_param_refreshes >= pre + 2
    finally:
        global_injector.disarm()
        send.close()
        recv.close()


@needs_native
def test_native_fault_knobs(global_injector):
    import numpy as np
    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("127.0.0.1", 0))
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        ring = np.zeros((4, 64), np.uint8)
        ring[:, 0] = 0x80
        lens = np.full(4, 40, np.int32)
        dests = native.make_dests([recv.getsockname()])
        ops = native.make_ops([(i % 4, 0) for i in range(4)])
        z = np.zeros(1, np.uint32)

        def send_once():
            return native.fanout_send_udp(send.fileno(), ring, lens,
                                          z, z, z, dests, ops, 4)

        pre = native.get_stats()["fault_injections"]
        native.fault_set(2, 0, 0, 0)   # every 2nd send call → EAGAIN
        results = [send_once() for _ in range(4)]
        assert results == [4, 0, 4, 0]
        import errno as errno_mod
        native.fault_set(0, 3, 0, 0)   # every 3rd send call → ENOBUFS
        results = [send_once() for _ in range(3)]
        assert results[2] == -errno_mod.ENOBUFS
        assert native.get_stats()["fault_injections"] >= pre + 3
        native.fault_clear()
        assert send_once() == 4        # schedule gone
    finally:
        native.fault_clear()
        send.close()
        recv.close()


# ---------------------------------------------------------------- ladder
class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _mk_ladder(clock, **cfg_kw):
    events = EventLog()
    lad = DegradationLadder(
        LadderConfig(**cfg_kw), clock=clock, events=events,
        gauge=Gauge("test_ladder_level", "t", labels=("stream",)),
        transitions=Counter("test_trans_total", "t", labels=("direction",)),
        retries=Counter("test_retries_total", "t"))
    return lad, events


def test_ladder_bounded_retry_before_rung_change():
    clk = _Clock()
    lad, events = _mk_ladder(clk, max_retries=2, backoff_ms=100)
    path = "/live/x"
    lad.note_device_error(path)        # retry 1: backoff 100 ms
    assert lad.level(path) == LEVEL_FULL
    assert lad.engine_mode(path) == LEVEL_CPU      # inside backoff
    clk.t = 0.2
    assert lad.engine_mode(path) == LEVEL_FULL     # backoff expired
    clk.t = 0.3
    lad.note_device_error(path)        # retry 2 (no clean window since)
    assert lad.level(path) == LEVEL_FULL
    clk.t = 0.6
    lad.note_device_error(path)        # budget blown → rung drop
    assert lad.level(path) == LEVEL_DEVICE
    evs = [r["event"] for r in events.tail()]
    assert evs == ["ladder.degrade"]
    rec = events.tail()[0]
    assert rec["rung"] == "device" and rec["from_rung"] == "megabatch"
    assert lad.engine_mode(path) == LEVEL_DEVICE   # own engine, unowned


def test_ladder_interleaved_successes_do_not_reset_budget():
    """A fault every few seconds with successes in between is a sick
    device: note_device_ok resets the retry budget only after a FULL
    clean window, so the rung still drops."""
    clk = _Clock()
    lad, _ = _mk_ladder(clk, max_retries=2, backoff_ms=10,
                        recover_sec=10.0)
    path = "/live/x"
    for i in range(3):
        clk.t = i * 2.0                # errors 2 s apart, ok between
        lad.note_device_error(path)
        clk.t += 1.0
        lad.note_device_ok(path)
    assert lad.level(path) == LEVEL_DEVICE

    # a genuinely clean stretch DOES reset: one later error only retries
    clk.t = 100.0
    lad.note_device_ok(path)
    lad.note_device_error(path)
    assert lad.level(path) == LEVEL_DEVICE         # retry, no 2nd drop


def test_ladder_recovery_hysteresis_one_rung_per_tick():
    clk = _Clock()
    lad, events = _mk_ladder(clk, max_retries=0, recover_sec=10.0)
    path = "/live/x"
    for t in (0.0, 1.0):               # max_retries=0: every error drops
        clk.t = t
        lad.note_device_error(path)
    assert lad.level(path) == LEVEL_CPU
    clk.t = 5.0
    lad.tick({path: 0})
    assert lad.level(path) == LEVEL_CPU            # not clean long enough
    clk.t = 12.0
    lad.tick({path: 0})
    assert lad.level(path) == LEVEL_DEVICE         # one rung per tick…
    clk.t = 13.0
    lad.tick({path: 0})
    assert lad.level(path) == LEVEL_FULL           # …then the next
    names = [r["event"] for r in events.tail()]
    assert names.count("ladder.degrade") == 2
    assert names.count("ladder.recover") == 2
    assert lad.worst_level() == 0


def test_ladder_stall_growth_sheds_newest():
    clk = _Clock()
    lad, events = _mk_ladder(clk, max_retries=0, recover_sec=10.0,
                             shed_stall_growth=50)
    path = "/live/x"
    clk.t = 0.0
    lad.note_device_error(path)
    lad.note_device_error(path)        # → cpu rung
    assert lad.level(path) == LEVEL_CPU
    clk.t = 1.0
    lad.tick({path: 100})              # baseline sample
    clk.t = 2.0
    lad.tick({path: 200})              # +100 stalls in one tick → shed
    assert lad.level(path) == LEVEL_SHED

    st = RelayStream(sdp.parse(VIDEO_SDP).streams[0])
    outs = [CollectingOutput(ssrc=i) for i in range(3)]
    for o in outs:
        st.add_output(o)
    assert lad.shed_candidate(st) is outs[-1]      # newest first
    st.remove_output(outs[-1])
    st.remove_output(outs[-2])
    assert lad.shed_candidate(st) is None          # never the last one


def test_ladder_slo_edge_degrades_offender_once():
    clk = _Clock()
    lad, events = _mk_ladder(clk)
    burning = {"objectives": {"latency": {"in_violation": True}}}
    lad.tick({}, slo_status=burning, offender="/live/worst")
    assert lad.level("/live/worst") == LEVEL_DEVICE
    clk.t = 1.0
    lad.tick({"/live/worst": 0}, slo_status=burning,
             offender="/live/worst")
    assert lad.level("/live/worst") == LEVEL_DEVICE    # edge-latched
    calm = {"objectives": {"latency": {"in_violation": False}}}
    clk.t = 2.0
    lad.tick({"/live/worst": 0}, slo_status=calm, offender=None)
    clk.t = 3.0
    lad.tick({"/live/worst": 0}, slo_status=burning,
             offender="/live/worst")   # new rising edge → one more rung
    assert lad.level("/live/worst") == LEVEL_CPU


def test_ladder_scheduler_error_charges_engaged_streams():
    clk = _Clock()
    lad, _ = _mk_ladder(clk, max_retries=0)
    lad.note_scheduler_error(["/a", "/b", None])
    assert lad.level("/a") == LEVEL_DEVICE
    assert lad.level("/b") == LEVEL_DEVICE
    # rung-1 streams are NOT re-charged by scheduler failures (they no
    # longer ride the scheduler)
    lad.note_scheduler_error(["/a"])
    assert lad.level("/a") == LEVEL_DEVICE


def test_ladder_cpu_rung_errors_do_not_pin_recovery():
    """A non-device exception leaking into note_device_error while the
    stream already sits on the CPU oracle (e.g. one broken output
    raising every wake) must not refresh the clean-window clock — the
    stream would otherwise be pinned on rung 2 forever."""
    clk = _Clock()
    lad, _ = _mk_ladder(clk, max_retries=0, recover_sec=10.0)
    path = "/live/x"
    clk.t = 0.0
    lad.note_device_error(path)
    lad.note_device_error(path)        # → cpu rung
    assert lad.level(path) == LEVEL_CPU
    for t in range(1, 12):             # errors keep arriving every tick
        clk.t = float(t)
        if lad.level(path) >= LEVEL_CPU:
            lad.note_device_error(path)     # the leaking output bug
        lad.tick({path: 0})
    assert lad.level(path) < LEVEL_CPU  # recovery proceeded regardless


def test_ladder_prunes_dead_paths():
    clk = _Clock()
    lad, _ = _mk_ladder(clk, max_retries=0)
    lad.note_device_error("/dead")
    assert "/dead" in lad.status()
    lad.tick({"/live": 0})
    assert "/dead" not in lad.status()


# ------------------------------------------------------------ checkpoint
def _mk_registry(n_streams: int, outs_per: int, addrs=None):
    reg = SessionRegistry(StreamSettings(bucket_delay_ms=0))
    streams = []
    for i in range(n_streams):
        sess = reg.find_or_create(f"/live/s{i}", VIDEO_SDP)
        st = sess.streams[1]
        rng = random.Random(100 + i)
        for j in range(outs_per):
            o = CollectingOutput(ssrc=rng.getrandbits(32),
                                 out_seq_start=rng.getrandbits(16),
                                 out_ts_start=rng.getrandbits(32))
            if addrs is not None:
                o.native_addr = addrs[j % len(addrs)]
            st.add_output(o)
        streams.append(st)
    return reg, streams


def _collecting_factory(rec):
    o = CollectingOutput()
    if rec.get("rtp_addr"):
        o.native_addr = tuple(rec["rtp_addr"])
    return o


def test_checkpoint_roundtrip_restores_bookkeeping(tmp_path):
    reg, streams = _mk_registry(2, 3, addrs=[("127.0.0.1", 5004)])
    t, seq = 1000, 0
    for _ in range(7):
        for st in streams:
            st.push_rtp(vid_pkt(seq), t)
            seq += 1
        for st in streams:
            st.reflect(t)              # latches rewrites, sends, counts
        t += 20
    doc = json.loads(json.dumps(ckpt_mod.snapshot_registry(reg)))
    assert doc["version"] == ckpt_mod.CKPT_VERSION

    reg2 = SessionRegistry(StreamSettings(bucket_delay_ms=0))
    n_sess, n_out = ckpt_mod.restore_registry(
        reg2, doc, output_factory=_collecting_factory)
    assert n_sess == 2 and n_out == 6
    for i, st in enumerate(streams):
        st2 = reg2.find(f"/live/s{i}").streams[1]
        assert st2.rtp_ring.head == st.rtp_ring.head
        assert st2.rtp_ring.tail == st2.rtp_ring.head   # bytes are gone
        assert st2.reporter_ssrc == st.reporter_ssrc
        assert st2._rr_base_seq == st._rr_base_seq
        assert st2._rr_max_seq == st._rr_max_seq
        for o, o2 in zip(st.outputs, st2.outputs):
            assert o2.rewrite == o.rewrite
            assert o2.packets_sent == o.packets_sent
            assert o2.payload_octets == o.payload_octets
            assert o2.bookmark == st.rtp_ring.head


def test_checkpoint_manager_staleness_and_version(tmp_path):
    reg, _ = _mk_registry(1, 1)
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), max_age_sec=60.0)
    assert mgr.load() is None          # nothing written yet
    assert mgr.write(reg)
    assert mgr.load() is not None
    doc = json.load(open(mgr.path))
    doc["saved_wall"] = doc["saved_wall"] - 3600   # an hour stale
    json.dump(doc, open(mgr.path, "w"))
    assert mgr.load() is None
    doc["saved_wall"] = doc["saved_wall"] + 3600
    doc["version"] = 99
    json.dump(doc, open(mgr.path, "w"))
    assert mgr.load() is None
    open(mgr.path, "w").write("{not json")
    assert mgr.load() is None


def test_checkpoint_write_never_stamps_the_future(tmp_path, monkeypatch):
    """Regression: ``round(time.time(), 3)`` could stamp ``saved_wall``
    up to 0.5 ms in the FUTURE, so a load() inside that window computed
    a negative age and rejected the checkpoint it just wrote (the
    suite-flaky failure mode of the staleness test above)."""
    reg, _ = _mk_registry(1, 1)
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), max_age_sec=60.0)
    frozen = 1_700_000_000.0004999    # round() would stamp .001 — future
    monkeypatch.setattr(ckpt_mod.time, "time", lambda: frozen)
    assert mgr.write(reg)
    doc = json.load(open(mgr.path))
    assert doc["saved_wall"] <= frozen
    assert mgr.load() is not None     # load at the same instant succeeds


def test_checkpoint_maybe_write_throttles(tmp_path):
    clk = _Clock()
    reg, _ = _mk_registry(1, 1)
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), interval_sec=5.0,
                                     clock=clk)
    assert mgr.maybe_write(reg)
    assert not mgr.maybe_write(reg)    # inside the interval
    clk.t = 6.0
    assert mgr.maybe_write(reg)
    assert mgr.writes == 2


class _Wire:
    """N receiver sockets; per-destination byte order is observable."""

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

    def drain(self) -> None:
        for i, s in enumerate(self.socks):
            while True:
                try:
                    self.rx[i].append(s.recv(65536))
                except BlockingIOError:
                    break

    def close(self) -> None:
        for s in self.socks:
            s.close()


@needs_native
def test_kill_restore_resumes_byte_identical_16x16():
    """The ISSUE acceptance shape: 16 sources × 16 subscribers through
    the megabatch scheduler, killed mid-relay, restored from the
    checkpoint — the post-restore wire bytes must be BYTE-IDENTICAL to
    an uninterrupted oracle run, per destination, in order."""
    N_SRC, N_SUB = 16, 16
    PHASE_A, PHASE_B = 6, 6

    def run(kill_restore: bool, wire: _Wire, send_fd: int):
        reg, streams = _mk_registry(N_SRC, N_SUB, addrs=wire.addrs)
        engines = [TpuFanoutEngine(egress_fd=send_fd) for _ in streams]
        sched = MegabatchScheduler()
        state = {"t": 1000, "seq": 0}

        def wakes(n):
            nonlocal streams, engines, sched
            for _ in range(n):
                for st in streams:
                    for _ in range(2):
                        st.push_rtp(vid_pkt(state["seq"]), state["t"])
                        state["seq"] += 1
                pump.wake(list(zip(streams, engines)), sched, state["t"])
                wire.drain()
                state["t"] += 20

        wakes(PHASE_A)
        sched.drain()
        wire.drain()
        mark = [len(r) for r in wire.rx]
        if kill_restore:
            # the "kill": serialize, throw EVERY live object away, and
            # rebuild the relay from the checkpoint document alone
            doc = json.loads(json.dumps(ckpt_mod.snapshot_registry(reg)))
            reg2 = SessionRegistry(StreamSettings(bucket_delay_ms=0))
            ckpt_mod.restore_registry(reg2, doc,
                                      output_factory=_collecting_factory)
            streams = [reg2.find(f"/live/s{i}").streams[1]
                       for i in range(N_SRC)]
            engines = [TpuFanoutEngine(egress_fd=send_fd)
                       for _ in streams]
            sched = MegabatchScheduler()
        wakes(PHASE_B)
        sched.drain()
        # a final no-ingest wake flushes params harvested in flight
        pump.wake(list(zip(streams, engines)), sched, state["t"])
        wire.drain()
        return mark, [list(r) for r in wire.rx]

    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    wire_o, wire_r = _Wire(N_SUB), _Wire(N_SUB)
    try:
        mark_o, rx_o = run(False, wire_o, send.fileno())
        mark_r, rx_r = run(True, wire_r, send.fileno())
        assert mark_o == mark_r        # phase A identical runs
        total_b = 0
        for d in range(N_SUB):
            a = rx_o[d][mark_o[d]:]
            b = rx_r[d][mark_r[d]:]
            assert a == b, f"post-restore bytes diverge at dest {d}"
            total_b += len(b)
        # the comparison must have covered real traffic, and the seq
        # rewrite must be CONTINUOUS across the kill (first post-restore
        # packet continues the phase-A numbering, no reset to out_seq0)
        assert total_b >= N_SRC * N_SUB * PHASE_B
        import struct
        for d in range(N_SUB):
            pre = rx_r[d][mark_r[d] - 1]
            post = rx_r[d][mark_r[d]]
            # same subscriber SSRC keeps flowing on this destination
            assert pre[8:12] == post[8:12] or len(rx_r[d]) > mark_r[d]
        assert struct is not None
    finally:
        send.close()
        wire_o.close()
        wire_r.close()


def test_restore_skips_tcp_outputs_without_factory():
    reg, streams = _mk_registry(1, 2)          # no native_addr → opaque
    doc = ckpt_mod.snapshot_registry(reg)
    assert all(o["kind"] == "opaque"
               for o in doc["sessions"][0]["streams"][0]["outputs"])
    reg2 = SessionRegistry(StreamSettings())
    n_sess, n_out = ckpt_mod.restore_registry(reg2, doc)
    assert n_sess == 1 and n_out == 0          # session yes, outputs no


# ------------------------------------------- review-pass regression pins
@needs_native
def test_arming_plan_pushes_native_egress_knobs(global_injector):
    """Arming a plan WITH egress knobs must reach csrc even though the
    server arms before anything else touches the native library — a
    loaded()-only guard left the whole chaos run egress-fault-free."""
    import numpy as np
    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("127.0.0.1", 0))
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        global_injector.arm(FaultPlan(seed=1, egress_eagain_every=2))
        ring = np.zeros((4, 64), np.uint8)
        ring[:, 0] = 0x80
        lens = np.full(4, 40, np.int32)
        dests = native.make_dests([recv.getsockname()])
        ops = native.make_ops([(i % 4, 0) for i in range(4)])
        z = np.zeros(1, np.uint32)
        results = [native.fanout_send_udp(send.fileno(), ring, lens,
                                          z, z, z, dests, ops, 4)
                   for _ in range(4)]
        assert results == [4, 0, 4, 0]     # the armed schedule, live
        global_injector.disarm()
        assert native.fanout_send_udp(send.fileno(), ring, lens, z, z,
                                      z, dests, ops, 4) == 4
    finally:
        native.fault_clear()
        send.close()
        recv.close()


@needs_native
def test_native_ingest_drain_applies_ingest_faults(global_injector):
    """The recvmmsg drain path must run the ingest gauntlet too — the
    chaos soak's native-path pusher is exactly the source that used to
    bypass it."""
    st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    out = CollectingOutput(ssrc=5)
    st.add_output(out)
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    rx.setblocking(False)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        global_injector.arm(FaultPlan(seed=1, ingest_drop=1.0))
        for i in range(6):
            tx.sendto(vid_pkt(i), rx.getsockname())
        import time
        time.sleep(0.05)
        n = st.drain_rtp_native(rx.fileno(), 1000)
        assert n == 6                      # consumed from the socket…
        st.reflect(1000)
        assert out.rtp_packets == []       # …but every slot was runt'd
        global_injector.disarm()
        tx.sendto(vid_pkt(99), rx.getsockname())
        time.sleep(0.05)
        st.drain_rtp_native(rx.fileno(), 1000)
        st.reflect(1000)
        assert len(out.rtp_packets) == 1   # clean path unaffected
    finally:
        global_injector.disarm()
        rx.close()
        tx.close()


def test_restore_preserves_bucket_placement():
    """The delay-stagger bucket a subscriber was in is serving state:
    restore must pin it, not first-fit-repack over holes."""
    reg = SessionRegistry(StreamSettings(bucket_size=2))
    sess = reg.find_or_create("/live/bk", VIDEO_SDP)
    st = sess.streams[1]
    outs = [CollectingOutput(ssrc=i) for i in range(4)]
    for o in outs:
        o.native_addr = ("127.0.0.1", 6000)
        st.add_output(o)                   # buckets: [2, 2]
    st.remove_output(outs[0])              # hole: buckets [1, 2]
    doc = json.loads(json.dumps(ckpt_mod.snapshot_registry(reg)))
    reg2 = SessionRegistry(StreamSettings(bucket_size=2))
    ckpt_mod.restore_registry(reg2, doc,
                              output_factory=_collecting_factory)
    st2 = reg2.find("/live/bk").streams[1]
    assert [len(b) for b in st2.buckets] == [1, 2]


def test_restored_output_keeps_rtcp_host(tmp_path):
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    app = StreamingServer(ServerConfig(log_folder=str(tmp_path),
                                       access_log_enabled=False))

    class _Egress:
        active = True

    app.rtsp.shared_egress = _Egress()
    out = app._restored_output({
        "kind": "udp", "rtp_addr": ["10.0.0.2", 5004],
        "rtcp_addr": ["10.0.0.9", 5005]})
    assert out.rtp_addr == ("10.0.0.2", 5004)
    assert out.rtcp_addr == ("10.0.0.9", 5005)   # its OWN host survives


# ------------------------------------------------- lint / gate contracts
def test_metrics_lint_resilience_contract():
    from tools.metrics_lint import (lint, lint_emit_sites, lint_events,
                                    lint_resilience)
    import pathlib
    from easydarwin_tpu.obs import events as ev
    assert lint(obs.REGISTRY) == []
    assert lint_events(ev.SCHEMA) == []
    assert lint_resilience(obs.REGISTRY, ev.SCHEMA) == []
    pkg = pathlib.Path(ckpt_mod.__file__).resolve().parents[1]
    assert lint_emit_sites(pkg, ev.SCHEMA) == []


def test_bench_gate_accepts_optional_chaos_section():
    from tools.bench_gate import check_trajectory

    def entry(extra):
        return [{"file": "BENCH_rT.json", "rc": 0,
                 "parsed": {"metric": "m", "value": 100.0, "unit": "pps",
                            "vs_baseline": 2.0, "extra": extra}}]

    assert check_trajectory(entry({})) == []           # old rounds valid
    ok = {"chaos": {"degraded_pkts_per_sec": 150.0, "recovery_sec": 4.2}}
    assert check_trajectory(entry(ok)) == []
    bad_rate = {"chaos": {"degraded_pkts_per_sec": 0,
                          "recovery_sec": 4.2}}
    assert any("degraded_pkts_per_sec" in e
               for e in check_trajectory(entry(bad_rate)))
    slow = {"chaos": {"degraded_pkts_per_sec": 150.0,
                      "recovery_sec": 45.0}}
    assert any("30 s" in e for e in check_trajectory(entry(slow)))
    errd = {"chaos": {"error": "section skipped"}}
    assert check_trajectory(entry(errd)) == []
