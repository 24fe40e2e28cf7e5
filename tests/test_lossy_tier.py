"""The reliability tier behind a lossy last mile: receiver
reports judged over enough packets, no thinning for loss the tier
repairs, a give-up charged to its output and not to its stream, no
replay of a hole thinning made, and the parity programs as a closed set
loaded with a stream's first FEC output."""

import random
import types

import numpy as np
import pytest

from easydarwin_tpu import obs
from easydarwin_tpu.protocol import rtcp, rtp, sdp
from easydarwin_tpu.relay import fec as fec_mod
from easydarwin_tpu.relay.fanout import TpuFanoutEngine
from easydarwin_tpu.relay.fec import FecConfig, FecOutputState, gf_matmul
from easydarwin_tpu.relay.output import CollectingOutput
from easydarwin_tpu.relay.quality import MIN_JUDGED_PACKETS, ReportFold
from easydarwin_tpu.relay.ring import SLOT_SIZE
from easydarwin_tpu.relay.stream import RelayStream, StreamSettings
from easydarwin_tpu.server.rtsp import RtspServer

VIDEO_SDP = ("v=0\r\ns=l\r\nt=0 0\r\nm=video 0 RTP/AVP 96\r\n"
             "a=rtpmap:96 H264/90000\r\na=control:trackID=1\r\n")


def vid_pkt(seq, nal_type=1):
    return rtp.RtpPacket(payload_type=96, seq=seq, timestamp=seq * 3000,
                         ssrc=0x11, marker=True,
                         payload=bytes(((3 << 5) | nal_type,)) + bytes(30)
                         ).to_bytes()


def fec_output(ssrc, seq0=100, **cfg) -> CollectingOutput:
    out = CollectingOutput(ssrc=ssrc, out_seq_start=seq0)
    out.fec = FecOutputState(FecConfig(**cfg))
    return out


def player(*outs, relay=None):
    """An RTSP connection holding ``outs`` as its tracks 1, 2, …"""
    return types.SimpleNamespace(
        player_tracks={i + 1: types.SimpleNamespace(output=o)
                       for i, o in enumerate(outs)},
        last_activity=0.0, relay=relay, path="/live/lossy")


def rtcp_in(conn, pkt, srv=None):
    RtspServer.on_client_rtcp(srv or types.SimpleNamespace(stats={}),
                              conn, pkt.to_bytes(), None)


def rr(out, highest, lost):
    return rtcp.ReceiverReport(0x77, [rtcp.ReportBlock(
        out.rewrite.ssrc, 0, lost, highest, 0, 0, 0)])


# ------------------------------------------------------------ the fold
def test_fold_judges_only_enough_packets():
    f = ReportFold()
    # a viewer's first report: five packets, three of them lost
    assert f.add(104, 3, base_seq=100) is None
    assert f.add(104 + 40, 3, 100) is None               # 45 covered
    frac = f.add(104 + 40 + 19, 3, 100)                   # 64 covered
    assert MIN_JUDGED_PACKETS == 64 and frac == pytest.approx(3 / 64)
    # judged and begun again
    assert f.add(163 + 10, 3, 100) is None


def test_fold_highest_seq_wraps():
    # 16-bit: the base is near the top, the receiver counts a cycle
    f = ReportFold()
    assert f.add(0x10000 + 60, 2, base_seq=65530) == pytest.approx(2 / 67)
    # 32-bit: the extended seq itself wraps
    f = ReportFold()
    assert f.add(0xFFFFFFF0, 0, base_seq=0xFFFFFFF0 - 63) == 0.0
    assert f.add(0x40, 8, 0) == pytest.approx(8 / 0x50)
    # a base of 0: the receiver's first extended seq is 0
    assert ReportFold().add(63, 1, base_seq=0) == pytest.approx(1 / 64)


def test_fold_cumulative_lost_goes_negative():
    f = ReportFold()
    f.add(999 + 64, 5, base_seq=1000)
    # duplicates: the receiver's cumulative count falls by two
    assert f.add(1063 + 70, 3, 1000) == 0.0
    assert f.add(1063 + 140, 10, 1000) == pytest.approx(7 / 70)


def test_fold_rebases_on_a_report_of_no_interval():
    f = ReportFold()
    f.add(5000, 0, base_seq=5000 - 64)
    assert f.add(5000, 9, 0) is None                     # nothing grew
    assert f.add(5000 - 10, 9, 0) is None                # went back
    assert f.add(5000 - 10 + 64, 9, 0) == 0.0


# ------------------------------------------------- thinning, per output
def test_small_first_report_does_not_thin():
    out = CollectingOutput(ssrc=5, out_seq_start=100)
    conn = player(out)
    folded = obs.RTCP_REPORT_BLOCKS.value(result="folded")
    rtcp_in(conn, rr(out, 104, 3))                       # 60 % of five
    assert out.thinning.controller.level == 0
    assert obs.RTCP_REPORT_BLOCKS.value(result="folded") == folded + 1


def test_no_thinning_at_two_percent_over_covered_reports():
    """π 0.02 in bursts of three, a report every 70 packets: parity
    covers it, so thinning hears nothing and the FEC rung climbs."""
    out = fec_output(7)
    conn = player(out)
    rng = random.Random(42)
    hi, lost, bad = 99, 0, False
    judged = obs.RTCP_REPORT_BLOCKS.value(result="judged")
    for _ in range(40):
        for _ in range(70):
            hi += 1
            # two-state chain: mean burst 3, mean loss 2 %
            bad = rng.random() < (2 / 3 if bad else 0.02 / 3 / 0.98)
            lost += bad
        rtcp_in(conn, rr(out, hi, lost))
    assert obs.RTCP_REPORT_BLOCKS.value(result="judged") == judged + 40
    assert out.thinning.controller.level == 0
    assert out.thinning.controller.thins == 0


def test_spoofed_heavy_loss_thins_that_output_only():
    from easydarwin_tpu.resilience.inject import INJECTOR, FaultPlan
    a, b = fec_output(8), fec_output(9)
    conn_a, conn_b = player(a), player(b)
    INJECTOR.arm(FaultPlan.parse("seed=3,rr_loss_spoof=0.40"))
    try:
        for n in range(3):
            rtcp_in(conn_a, rr(a, 170 + 70 * n, 0))
        # three reports climb the rung 0 → 0.20: the tier answers first
        assert a.thinning.controller.level == 0
        rtcp_in(conn_a, rr(a, 380, 0))
    finally:
        INJECTOR.disarm()
    # the fourth reaches the ceiling (0.30, 5 rows of 16), whose parity
    # leaves 31 % of 0.40 unrepaired: it thins, one output alone
    assert a.thinning.controller.level == 1
    assert a.fec.controller.at_ceiling
    assert b.thinning.controller.level == 0
    rtcp_in(conn_b, rr(b, 300, 0))
    assert b.thinning.controller.level == 0



def test_rtcp_socket_reads_a_backlog_in_a_few_turns():
    """4,096 viewers' reports outrun an event loop that reads one RTCP
    datagram a turn: the shared socket reads what it holds each time it
    is readable, so a backlog of reports and NACKs is read in a few
    turns of the loop, not one turn a datagram."""
    import asyncio
    import socket

    from easydarwin_tpu.server.egress import SharedUdpEgress

    async def main():
        eg = SharedUdpEgress("127.0.0.1")
        await eg.start()
        got = []
        eg._on_rtcp = lambda data, addr: got.append(data)
        tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for i in range(120):
            tx.sendto(b"rr%d" % i, ("127.0.0.1", eg.rtcp_port))
        turns = 0
        while len(got) < 120 and turns < 50:
            await asyncio.sleep(0)
            turns += 1
        eg.close()
        tx.close()
        return got, turns

    got, turns = asyncio.run(main())
    assert got == [b"rr%d" % i for i in range(120)] and turns <= 3

# ------------------------------------------------------------ give-ups
def make_stream():
    st = RelayStream(sdp.parse(VIDEO_SDP).streams[0],
                     StreamSettings(bucket_delay_ms=0))
    st.session_path = "/live/lossy"
    return st


def push_frames(st, seq0, n, t=1000):
    for i in range(n):
        st.push_rtp(vid_pkt(seq0 + i, nal_type=5 if i == 0 else 1),
                    t + i)
        st.reflect(t + i + 1)


def test_giveup_moves_neither_the_ladder_nor_other_outputs():
    """A black-holed NACKer exhausts its own budget: its give-ups are
    heard with its next judged report, three such intervals thin it; the
    stream's ladder has no record of it and the other output is
    untouched."""
    from easydarwin_tpu.relay.quality import LOSS_THIN_SLOW
    from easydarwin_tpu.server import ServerConfig, StreamingServer
    app = StreamingServer(ServerConfig(rtsp_port=0, service_port=0,
                                       bind_ip="127.0.0.1",
                                       access_log_enabled=False))
    st = make_stream()
    a = fec_output(10, rtx_burst=2, rtx_budget_per_sec=0.0)
    b = fec_output(11, seq0=500)
    for o in (a, b):
        st.add_output(o)
    push_frames(st, 100, 32)
    conn = player(a, relay=types.SimpleNamespace(streams={1: st}))
    gu = obs.RTX_GIVEUP.value()
    for n in range(3):                  # it asks for everything, anew
        rtcp_in(conn, rtcp.GenericNack.from_seqs(
            0x77, a.rewrite.ssrc, range(100 + 10 * n, 110 + 10 * n)),
            srv=app.rtsp)
        assert a.fec.giveups_unjudged > 0
        # its report says nothing was lost: the tier's failure is heard
        rtcp_in(conn, rr(a, 99 + 70 * (n + 1), 0), srv=app.rtsp)
        assert a.fec.giveups_unjudged == 0
        assert a.thinning.controller.level == (1 if n == 2 else 0)
    assert obs.RTX_GIVEUP.value() == gu + 28 and a.fec.rtx_sent == 2
    assert b.thinning.controller.level == 0 and b.fec.rtx_giveups == 0
    assert app.ladder is not None
    assert "/live/lossy" not in app.ladder._streams
    assert app.ladder.level("/live/lossy") == 0
    # one interval of give-ups, and then a clean one, thins nothing
    c = fec_output(17)
    c.fec.giveups_unjudged = 5
    assert c.fec.unrepaired(0.0) == LOSS_THIN_SLOW
    assert c.fec.unrepaired(0.0) == 0.0
    # below its ceiling the tier answers loss with a rung; at it, the
    # quality controller hears what 5 rows of 16 leave lost
    assert c.fec.unrepaired(0.45) == 0.0
    c.fec.controller._idx = len(fec_mod.OVERHEAD_LADDER) - 1
    assert c.fec.unrepaired(0.45) == pytest.approx(
        fec_mod.residual_loss(0.45, 16, 5))


def test_residual_loss_after_parity():
    r = fec_mod.residual_loss
    assert r(0.3, 16, 0) == pytest.approx(0.3)       # no rows: all of it
    assert r(0.9, 16, 16) == 0.0                      # every erasure
    assert r(0.02, 16, 1) < 0.02 / 3                  # one row: most of 2 %
    # 5 rows of 16: the quality controller's slow edge (10 %) near 27 %
    # raw loss, its immediate edge (30 %) below 40 %
    assert r(0.25, 16, 5) < 0.10 < r(0.28, 16, 5)
    assert r(0.35, 16, 5) < 0.30 < r(0.40, 16, 5)


@pytest.mark.parametrize("loss,thins", [(0.25, False), (0.35, True)])
def test_reports_only_output_at_sustained_loss(loss, thins):
    """An FEC output that sends RRs and never NACKs: its rung climbs to
    the ceiling, and then it hears what that parity leaves lost.  At
    35 % that thins it; at 25 % it does not, and a thinned output does
    not read it as clean and thicken back."""
    out = fec_output(21)
    conn = player(out)
    hi, lost = 99, 0
    for _ in range(12):
        hi += 100
        lost += round(100 * loss)
        rtcp_in(conn, rr(out, hi, lost))
    q = out.thinning.controller
    assert out.fec.controller.at_ceiling
    assert (q.thins > 0) is thins and q.level == q.thins
    if not thins:
        q.level = 1                        # thinned before, by a spike
        for _ in range(12):
            hi += 100
            lost += 25
            rtcp_in(conn, rr(out, hi, lost))
        assert q.level == 1 and q.thickens == 0


# ------------------------------------------------------ withheld holes
@pytest.mark.parametrize("engine", ["scalar", "tpu"])
def test_withheld_seqs_are_never_replayed(engine):
    st = make_stream()
    out = fec_output(12, rtx_burst=1, rtx_budget_per_sec=0.0)
    st.add_output(out)
    out.thinning.controller.level = 2               # key frames only
    for i in range(6):                              # IDR + five P frames
        st.push_rtp(vid_pkt(100 + i, nal_type=5 if i == 0 else 1),
                    1000 + i)
    if engine == "scalar":
        st.reflect(2000)
    else:
        TpuFanoutEngine().step(st, 2000)
    assert len(out.rtp_packets) == 1
    holes = list(range(101, 106))                   # out seq = src seq
    assert all(out.thinning.withheld(s) for s in holes)
    assert not out.thinning.withheld(100)
    # thickened: the player NACKs the holes and the one packet it lost
    out.thinning.controller.level = 0
    out.thinning._dropping_frame = False
    out.rtp_packets.clear()
    before = {r: obs.RTX_NACKED_SEQS.value(result=r)
              for r in ("sent", "giveup", "withheld", "evicted")}
    assert st.fec.replay_nacked(out, holes + [100], 50_000) == 1
    [r] = out.rtp_packets
    assert fec_mod.restore_rtx_packet(r, media_pt=96)[0] == 100
    assert out.fec.rtx_giveups == 0 and out.fec._tokens == 0.0
    grew = {r: obs.RTX_NACKED_SEQS.value(result=r) - v
            for r, v in before.items()}
    assert grew == {"sent": 1, "giveup": 0, "withheld": 5, "evicted": 0}


def test_withheld_record_is_bounded_and_wraps():
    from easydarwin_tpu.relay.quality import WITHHELD_RUNS, ThinningFilter
    f = ThinningFilter()
    for s in (65534, 65535, 0, 1):
        f.withhold(s)
    assert len(f._runs) == 1 and f.withheld(0) and f.withheld(65534)
    assert not f.withheld(2) and not f.withheld(65533)
    for n in range(WITHHELD_RUNS + 5):
        f.withhold(10 + 2 * n)                      # runs of one
    assert len(f._runs) == WITHHELD_RUNS and not f.withheld(10)


# ------------------------------------------------ the parity closed set
def test_parity_closed_set_loads_at_first_registration():
    from easydarwin_tpu.models.relay_pipeline import fec_parity_window_step
    cfg = FecConfig(window=16)
    want = fec_mod.parity_programs(cfg, SLOT_SIZE)
    assert {(k, r) for k, _b, r in want} == {(16, r) for r in (1, 2, 4, 8)}
    assert sorted({b for _k, b, _r in want}) == [256, 512, 1024, 2048, 4096]
    st = make_stream()
    out = fec_output(13, window=16)
    out.fec.controller._idx = 4                     # 30 %: five rows
    st.add_output(out)
    assert want <= fec_mod._BUILT and st.fec._to_load == []
    built = fec_parity_window_step._cache_size()
    push_frames(st, 100, 48)
    assert st.fec.device_passes >= 2
    assert fec_parity_window_step._cache_size() == built


def test_parity_programs_load_one_a_pass_once_media_flows():
    st = make_stream()
    push_frames(st, 100, 4)
    out = fec_output(14, window=12)                 # a window of its own
    st.add_output(out)
    pending = len(st.fec._to_load)
    assert pending == len(fec_mod.parity_programs(out.fec.cfg, SLOT_SIZE)
                          - fec_mod._BUILT)
    st.reflect(5000)
    assert len(st.fec._to_load) == max(pending - 1, 0)


@pytest.mark.parametrize("shape", sorted(fec_mod.parity_programs(
    FecConfig(), SLOT_SIZE)), ids=lambda s: "x".join(map(str, s)))
def test_device_parity_equals_oracle_at_every_loaded_shape(shape):
    from easydarwin_tpu.models.relay_pipeline import fec_parity_window_step
    k, b, r = shape
    rng = np.random.default_rng(k * 7919 + b * 31 + r)
    rows = rng.integers(0, 256, (k, b)).astype(np.uint8)
    rows[rng.integers(0, k)] = 0                    # a padding row
    coeff = np.zeros((r, k), np.uint8)
    coeff[:, :k - 1] = fec_mod.coeff_rows(list(range(k - 1)), r)
    assert np.array_equal(np.asarray(fec_parity_window_step(rows, coeff)),
                          gf_matmul(coeff, rows))


# -------------------------------------------------------- instruments
def test_tier_spans_and_families_pass_the_lint():
    import pathlib

    from easydarwin_tpu.obs import events as ev
    from tools.metrics_lint import (TIER_FAMILIES, TIER_SPANS, lint_fec,
                                    lint_spans)
    assert lint_fec(obs.REGISTRY, ev.SCHEMA) == []
    pkg = pathlib.Path(fec_mod.__file__).resolve().parents[1]
    assert lint_spans(obs.REGISTRY, pkg) == []
    assert set(TIER_SPANS) <= set(obs.SPANS)
    for name in TIER_FAMILIES:
        obs.REGISTRY.get(name)
    # an observed value outside a closed set is refused
    from easydarwin_tpu.obs.metrics import Registry
    r = Registry()
    r.counter("rtx_nacked_seqs_total", "x", labels=("result",)).inc(
        result="lost")
    assert any("'lost'" in e for e in lint_fec(r, ev.SCHEMA))


def test_parity_pass_times_device_and_oracle_apart():
    st = make_stream()
    out = fec_output(15, window=8)
    out.fec.controller._idx = 2
    st.add_output(out)
    fam = obs.FEC_PARITY_PASS_SECONDS
    dev0, orc0 = fam.count(part="device"), fam.count(part="oracle")
    obs.TRACER.clear()
    push_frames(st, 100, 16)
    passes = st.fec.device_passes
    assert passes >= 1
    assert fam.count(part="device") - dev0 == passes
    assert fam.count(part="oracle") - orc0 == passes
    spans = [a for n, _c, _t, _d, _tid, a in obs.TRACER.records()
             if n == "fec.parity_pass"]
    assert len(spans) == passes
    assert spans[0]["source"] == "device" and spans[0]["r_pad"] == 1
    assert spans[0]["b_pad"] == 256 and spans[0]["rows"] == 8


def test_a_repeated_nack_is_served_by_the_replay_just_sent():
    """After the loop stood still a player's repeats arrive back to back:
    the first replays, the others take no token; once RTX_REPEAT_MS has
    passed, a repeat is a new request."""
    st = make_stream()
    out = fec_output(16, rtx_burst=4, rtx_budget_per_sec=0.0)
    st.add_output(out)
    push_frames(st, 100, 8)
    out.rtp_packets.clear()
    rep = obs.RTX_NACKED_SEQS.value(result="repeat")
    assert st.fec.replay_nacked(out, [101, 102], 50_000) == 2
    for t in (50_000, 50_040, 50_099):
        assert st.fec.replay_nacked(out, [101, 102], t) == 0
    assert obs.RTX_NACKED_SEQS.value(result="repeat") == rep + 6
    assert out.fec._tokens == 2.0 and out.fec.rtx_giveups == 0
    assert st.fec.replay_nacked(out, [101, 102],
                                50_000 + fec_mod.RTX_REPEAT_MS) == 2
    assert len(out.rtp_packets) == 4


def test_a_clean_streak_at_level_0_leaves_the_plan_alone():
    """A write of ``level`` moves the stream's plan epoch: six clean
    reports at level 0 write nothing."""
    from easydarwin_tpu.relay.quality import NUM_CLEAN_TO_THICK
    st = make_stream()
    out = CollectingOutput(ssrc=18)
    st.add_output(out)
    epoch = st.plan_epoch
    for _ in range(2 * NUM_CLEAN_TO_THICK):
        out.on_receiver_report(0.0)
    assert st.plan_epoch == epoch
    out.on_receiver_report(0.5)                     # a thin does move it
    assert out.thinning.controller.level == 1 and st.plan_epoch > epoch


def test_a_rung_0_cursor_is_parked_and_restarts_at_the_live_edge():
    st = make_stream()
    out = fec_output(19, window=8)
    st.add_output(out)
    push_frames(st, 100, 20)                        # rung 0: no parity
    assert out.fec.next_window is None and out.fec.parity_sent == 0
    out.fec.controller._idx = 4                     # the rung rises
    push_frames(st, 120, 20, t=3000)
    pars = [fec_mod.parse_parity_packet(p) for p in out.rtp_packets
            if (p[1] & 0x7F) == out.fec.cfg.payload_type]
    assert pars and min(p["snbase"] for p in pars) >= 120
    assert out.fec.next_window * 8 >= 32


# ------------------------------------------- what the tier owes the pump
def pump_step(st, t):
    """One step of ``st`` by the pump's own loop (``relay.pump._step``)
    on a ready set of its own: True where the step carried the stream
    into the next wake."""
    from easydarwin_tpu.relay import pump
    c = st._plan_cell
    if c.ready is None:
        c.install(set(), 1)
    c.ready.clear()
    pump._step([(st.session_path, st, None, pump.SCALAR)], t, None, None,
               "", False)
    return c.key in c.ready


def parity_of(out):
    return [p for p in out.rtp_packets
            if (p[1] & 0x7F) == out.fec.cfg.payload_type]


def test_fec_outputs_with_nothing_due_are_no_reason_for_a_step():
    """Rule 5 asks what the tier owes, not whether it has an output: a
    step that sent every complete window leaves nothing for the next."""
    from easydarwin_tpu.relay.pump import needs_step
    st = make_stream()
    outs = [fec_output(30 + i, window=4, use_device=False) for i in range(2)]
    for o in outs:
        o.fec.controller._idx = 2
        st.add_output(o)
    for i in range(8):
        st.push_rtp(vid_pkt(100 + i, nal_type=5 if i == 0 else 1), 1000)
    assert pump_step(st, 1001) is False
    # two windows, one row each (10 % of 4)
    assert all(len(parity_of(o)) == 2 for o in outs)
    assert st.fec.owes_pass is None and st.fec.owes() is None
    assert not needs_step(st, 1002)
    assert pump_step(st, 1002) is False
    assert all(len(parity_of(o)) == 2 for o in outs)


def test_a_backlog_past_the_budget_is_carried_until_it_drains():
    """Twenty windows' worth sent at once: the tick emits eight a pass,
    the step carries the stream twice, and the third pass drains it."""
    st = make_stream()
    out = fec_output(32, window=2, use_device=False)
    out.fec.controller._idx = 1
    st.add_output(out)
    for i in range(40):
        st.push_rtp(vid_pkt(100 + i, nal_type=5 if i == 0 else 1), 1000)
    budget = obs.FEC_TIER_MARKS.value(reason="budget")
    carried = [pump_step(st, 1001 + n) for n in range(4)]
    assert carried == [True, True, False, False]
    assert len(parity_of(out)) == 20 and out.fec.next_window == 20
    assert obs.FEC_TIER_MARKS.value(reason="budget") - budget == 2
    assert st.fec.owes() is None


def test_programs_to_load_are_carried_one_a_pass(monkeypatch):
    loaded = []
    monkeypatch.setattr(fec_mod, "load_parity_programs",
                        lambda progs: loaded.extend(progs))
    st = make_stream()
    push_frames(st, 100, 4)
    out = fec_output(33, window=11)                 # shapes no test built
    st.add_output(out)
    pending = list(st.fec._to_load)
    assert len(pending) > 2 and st.fec.owes() == "load"
    load = obs.FEC_TIER_MARKS.value(reason="load")
    carried = [pump_step(st, 5000 + n) for n in range(len(pending) + 1)]
    assert carried == [True] * (len(pending) - 1) + [False, False]
    assert loaded == pending and st.fec.owes() is None
    assert obs.FEC_TIER_MARKS.value(reason="load") - load == len(pending) - 1


def test_a_report_that_moves_a_rung_off_0_marks_the_stream():
    """An RR that lifts an output's FEC rung off 0 marks its stream: the
    next wake's tick places the cursor at the ring head as it stands
    then, as a tick in every wake would; a clean streak that brings the
    rung back to 0 marks it again, and the cursor is parked."""
    from easydarwin_tpu.relay.fec import NUM_CLEAN_TO_STEP
    from easydarwin_tpu.relay.pump import needs_step
    st = make_stream()
    out = fec_output(34, window=8, use_device=False)
    st.add_output(out)
    for i in range(20):
        st.push_rtp(vid_pkt(100 + i, nal_type=5 if i == 0 else 1), 1000)
    assert pump_step(st, 1001) is False
    assert out.fec.next_window is None              # rung 0: parked
    c, conn = st._plan_cell, player(out)
    rung = obs.FEC_TIER_MARKS.value(reason="rung")
    rtcp_in(conn, rr(out, 99 + 70, 20))             # 29 % lost: up at once
    assert out.fec.controller._idx == 1 and c.key in c.ready
    assert obs.FEC_TIER_MARKS.value(reason="rung") == rung + 1
    assert st.fec.owes() == "cursor" and needs_step(st, 1002)
    head = st.rtp_ring.head
    assert pump_step(st, 1002) is False
    assert out.fec.next_window == (head + 7) // 8
    hi = 99 + 70
    for _ in range(NUM_CLEAN_TO_STEP):
        hi += 70
        c.ready.clear()
        rtcp_in(conn, rr(out, hi, 20))              # nothing more lost
    assert out.fec.controller._idx == 0 and c.key in c.ready
    assert obs.FEC_TIER_MARKS.value(reason="rung") == rung + 2
    assert st.fec.owes() == "cursor"
    pump_step(st, 1003)
    assert out.fec.next_window is None and st.fec.owes() is None


def test_a_dropped_overhead_gauge_is_set_again_at_the_next_step():
    """A departing viewer's connection drops its path's gauge child; the
    removal marks the stream, and its step sets the gauge again for the
    subscriber that stays."""
    st = make_stream()
    stay, leave = (fec_output(35 + i, window=4, use_device=False)
                   for i in range(2))
    stay.fec.controller._idx = 2
    for o in (stay, leave):
        st.add_output(o)
    push_frames(st, 100, 4)
    key = f"{st.session_path},{st.info.track_id}"
    pump_step(st, 2000)
    assert obs.FEC_OVERHEAD_RATIO.as_value()[key] == 0.10
    st.remove_output(leave)
    fec_mod.drop_overhead_gauge(st.session_path, st.info.track_id)
    assert key not in obs.FEC_OVERHEAD_RATIO.as_value()
    assert st._plan_cell.key in st._plan_cell.ready
    pump_step(st, 2001)
    assert obs.FEC_OVERHEAD_RATIO.as_value()[key] == 0.10


def test_the_tier_marks_family_passes_the_lint():
    from easydarwin_tpu.obs import events as ev
    from easydarwin_tpu.obs.metrics import Registry
    from tools.metrics_lint import lint, lint_fec
    assert not [e for e in lint(obs.REGISTRY)
                if e.startswith("fec_tier_marks_total")]
    assert lint_fec(obs.REGISTRY, ev.SCHEMA) == []
    fam = obs.REGISTRY.get("fec_tier_marks_total")
    assert fam.kind == "counter" and fam.label_names == ("reason",)
    # every reason exposed from the start: a window without one reads 0
    assert set(fam.as_value()) == {"budget", "load", "cursor", "rung"}
    r = Registry()
    r.counter("fec_tier_marks_total", "x", labels=("reason",)).inc(
        reason="idle")
    assert any("'idle'" in e for e in lint_fec(r, ev.SCHEMA))
