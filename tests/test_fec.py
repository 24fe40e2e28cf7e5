"""Lossy-WAN reliability tier (ISSUE 11): GF(256) parity matmul +
device-vs-host oracle, byte-exact FEC recovery on BOTH the scalar and
native-engine paths, NACK→RTX ring replay with budget, the closed-loop
rate controller, the signed cumulative_lost round-trip satellite, the
receiver-side injection sites, and the lint/gate contracts."""

import random
import socket
import struct

import numpy as np
import pytest

from easydarwin_tpu import obs
from easydarwin_tpu.protocol import rtcp, sdp
from easydarwin_tpu.relay import fec as fec_mod
from easydarwin_tpu.relay.fec import (FecConfig, FecOutputState,
                                      FecRateController, FecReceiver,
                                      OVERHEAD_LADDER, coeff_rows,
                                      gf_inv, gf_matmul, gf_mul, gf_solve)
from easydarwin_tpu.relay.output import CollectingOutput, WriteResult
from easydarwin_tpu.relay.stream import RelayStream, StreamSettings

SDP_TXT = ("v=0\r\ns=f\r\nt=0 0\r\nm=video 0 RTP/AVP 96\r\n"
           "a=rtpmap:96 H264/90000\r\na=control:trackID=1\r\n")


def make_stream(**settings) -> RelayStream:
    return RelayStream(sdp.parse(SDP_TXT).streams[0],
                       StreamSettings(bucket_delay_ms=0, **settings))


def make_fec_output(cfg=None, *, overhead_idx=2, ssrc=0xAABBCCDD,
                    seq0=100) -> CollectingOutput:
    out = CollectingOutput(ssrc=ssrc, out_seq_start=seq0)
    out.fec = FecOutputState(cfg or FecConfig(window=8))
    out.fec.controller._idx = overhead_idx
    return out


def push_media(st: RelayStream, n: int, *, seed=3, t0=1000, step=10,
               pay_len=50, reflect=True, seq0=0) -> int:
    rng = random.Random(seed)
    t = t0
    for i in range(n):
        pay = bytes(rng.randrange(256)
                    for _ in range(pay_len + (i % 7)))
        pkt = struct.pack("!BBHII", 0x80, 96, (seq0 + i) & 0xFFFF,
                          (i * 3000) & 0xFFFFFFFF, 0xB) + pay
        st.push_rtp(pkt, t)
        t += step
        if reflect:
            st.reflect(t)
    return t


def split_wire(pkts, cfg):
    media = [p for p in pkts if (p[1] & 0x7F) == 96]
    par = [p for p in pkts if (p[1] & 0x7F) == cfg.payload_type]
    rtx = [p for p in pkts if (p[1] & 0x7F) == cfg.rtx_payload_type]
    return media, par, rtx


# ------------------------------------------------------------ GF arithmetic
def test_gf_field_properties():
    rng = np.random.default_rng(7)
    for _ in range(300):
        a, b, c = (int(x) for x in rng.integers(0, 256, 3))
        assert gf_mul(a, b) == gf_mul(b, a)
        assert gf_mul(a, gf_mul(b, c)) == gf_mul(gf_mul(a, b), c)
        assert gf_mul(a, b ^ c) == gf_mul(a, b) ^ gf_mul(a, c)
        if a:
            assert gf_mul(a, gf_inv(a)) == 1
    assert gf_mul(1, 213) == 213 and gf_mul(0, 99) == 0
    # row 0 of the Vandermonde matrix is the GF(2) XOR row
    c = coeff_rows([0, 1, 5, 9], 3)
    assert (c[0] == 1).all()
    with pytest.raises(ZeroDivisionError):
        gf_inv(0)


def test_gf_solve_vandermonde_erasures():
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (10, 40)).astype(np.uint8)
    deltas = list(range(10))
    par = gf_matmul(coeff_rows(deltas, 4), data)
    miss = [1, 4, 8, 9]
    known = [i for i in range(10) if i not in miss]
    synd = par[:4].copy()
    synd ^= gf_matmul(fec_mod.coeff_for_indices(
        [deltas[i] for i in known], range(4)), data[known])
    sol = gf_solve(fec_mod.coeff_for_indices(
        [deltas[i] for i in miss], range(4)), synd)
    assert sol is not None and np.array_equal(sol, data[miss])


def test_device_parity_matches_host_oracle_across_shapes():
    from easydarwin_tpu.models.relay_pipeline import fec_parity_window_step
    rng = np.random.default_rng(11)
    for k, b, r in ((8, 256, 1), (16, 512, 4), (48, 2048, 8)):
        rows = rng.integers(0, 256, (k, b)).astype(np.uint8)
        rows[k // 2] = 0                      # zero (padding) row
        coeff = np.zeros((r, k), np.uint8)
        coeff[:, :k - 2] = coeff_rows(list(range(k - 2)), r)
        host = gf_matmul(coeff, rows)
        dev = np.asarray(fec_parity_window_step(rows, coeff))
        assert np.array_equal(host, dev), (k, b, r)


# ----------------------------------------------------------- wire formats
def test_parity_packet_roundtrip():
    p = fec_mod.build_parity_packet(
        fec_pt=127, fec_seq=42, ts=90_000, ssrc=0xDEADBEEF,
        snbase=65_530, deltas=[0, 2, 3, 47], idx=1, kind=fec_mod.KIND_RS,
        payload=b"\x01\x02\x03")
    d = fec_mod.parse_parity_packet(p)
    assert d == {"seq": 42, "snbase": 65_530, "deltas": [0, 2, 3, 47],
                 "idx": 1, "kind": fec_mod.KIND_RS,
                 "payload": b"\x01\x02\x03"}
    assert fec_mod.parse_parity_packet(p[:20]) is None


def test_rtx_packet_roundtrip_preserves_marker():
    orig = struct.pack("!BBHII", 0x80, 96 | 0x80, 777, 123456,
                       0xCAFE) + b"payload-bytes"
    r = fec_mod.build_rtx_packet(orig, rtx_pt=126, rtx_seq=9)
    assert (r[1] & 0x7F) == 126 and (r[1] & 0x80)       # marker kept
    assert struct.unpack_from("!H", r, 2)[0] == 9
    osn, restored = fec_mod.restore_rtx_packet(r, media_pt=96)
    assert osn == 777 and restored == orig


def test_generic_nack_roundtrip():
    seqs = [100, 101, 105, 116, 118, 400]
    n = rtcp.GenericNack.from_seqs(0x11, 0x22, seqs)
    [parsed] = rtcp.parse_compound(n.to_bytes())
    assert isinstance(parsed, rtcp.GenericNack)
    assert parsed.sender_ssrc == 0x11 and parsed.media_ssrc == 0x22
    assert sorted(parsed.lost_seqs()) == seqs
    # 100..116 span one (PID, BLP) pair; 118 (delta 18 > 16) and 400
    # each start a fresh pair
    assert len(parsed.pairs) == 3
    assert parsed.pairs[0] == (100, (1 << 0) | (1 << 4) | (1 << 15))


# ------------------------------------------- satellite: signed cumulative
def test_cumulative_lost_signed_roundtrip():
    for lost in (-1, -77, 0, 3, 0x7FFFFF, -0x800000):
        rb = rtcp.ReportBlock(5, 10, lost, 99, 0, 0, 0)
        rr = rtcp.ReceiverReport(1, [rb]).to_bytes()
        [parsed] = rtcp.parse_compound(rr)
        assert parsed.reports[0].cumulative_lost == lost, lost
    # out-of-range values clamp to the RFC 3550 signed 24-bit bounds
    rb = rtcp.ReportBlock(5, 10, 0x900000, 99, 0, 0, 0)
    [parsed] = rtcp.parse_compound(rtcp.ReceiverReport(1, [rb]).to_bytes())
    assert parsed.reports[0].cumulative_lost == 0x7FFFFF
    # the raw wire pattern 0xFFFFFF is -1, not ~16.7M lost
    raw = struct.pack("!IIIIII", 5, (10 << 24) | 0xFFFFFF, 99, 0, 0, 0)
    assert rtcp.ReportBlock.parse(raw, 0).cumulative_lost == -1


def test_upstream_rr_goes_negative_on_duplicates():
    st = make_stream()
    sent = []
    st.upstream_rtcp = sent.append
    pkt = struct.pack("!BBHII", 0x80, 96, 7, 0, 0xB) + bytes(20)
    for seq in (7, 8, 8, 8, 9):               # two duplicates
        st.push_rtp(pkt[:2] + struct.pack("!H", seq) + pkt[4:], 1000)
    assert st.send_upstream_rr(999_999)
    [rr] = rtcp.parse_compound(sent[0])
    assert rr.reports[0].cumulative_lost == -2


# --------------------------------------------------- recovery: scalar path
def test_recovery_byte_exact_scalar_path():
    st = make_stream()
    cfg = FecConfig(window=8)
    out = make_fec_output(cfg, overhead_idx=4)    # 30% → 3 rows per 8
    st.add_output(out)
    assert st.fec is not None
    push_media(st, 64)
    media, par, _ = split_wire(out.rtp_packets, cfg)
    assert len(media) == 64 and st.fec.windows_emitted == 8
    assert st.fec.device_passes > 0 and st.fec.oracle_mismatches == 0
    rx = FecReceiver(media_pt=96, fec_pt=cfg.payload_type,
                     rtx_pt=cfg.rtx_payload_type)
    dropped = {}
    for p in media:
        seq = struct.unpack_from("!H", p, 2)[0]
        if seq % 8 in (1, 4, 6):                  # 3 losses per window
            dropped[seq] = p
            continue
        rx.on_packet(p)
    for p in par:
        rx.on_packet(p)
    assert len(dropped) == 24
    for seq, orig in dropped.items():
        assert rx.recovered.get(seq) == orig, seq


def test_recovery_byte_exact_native_engine_path():
    """The acceptance's native half: media served by TpuFanoutEngine
    through real UDP sockets (sendmmsg scatter), parity through the
    output's scalar rung — the recovered bytes equal the never-dropped
    WIRE capture."""
    from easydarwin_tpu import native
    from easydarwin_tpu.relay.fanout import TpuFanoutEngine
    if not native.available():
        pytest.skip("native core unavailable")
    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("127.0.0.1", 0))
    recv.setblocking(False)
    recv.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 22)
    send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        st = make_stream()
        cfg = FecConfig(window=8)
        out = make_fec_output(cfg, overhead_idx=4)
        out.native_addr = recv.getsockname()
        st.add_output(out)
        eng = TpuFanoutEngine(egress_fd=send.fileno())
        rng = random.Random(3)
        t = 1000
        wire_media = []
        for i in range(48):
            pay = bytes(rng.randrange(256) for _ in range(60 + (i % 5)))
            pkt = struct.pack("!BBHII", 0x80, 96, i & 0xFFFF,
                              (i * 3000) & 0xFFFFFFFF, 0xB) + pay
            st.push_rtp(pkt, t)
            t += 10
            eng.step(st, t)
            while True:
                try:
                    wire_media.append(recv.recv(65536))
                except BlockingIOError:
                    break
        assert len(wire_media) == 48 and eng.native_sent == 48
        _, par, _ = split_wire(out.rtp_packets, cfg)
        assert len(par) >= 15 and st.fec.oracle_mismatches == 0
        rx = FecReceiver(media_pt=96, fec_pt=cfg.payload_type,
                         rtx_pt=cfg.rtx_payload_type)
        dropped = {}
        for p in wire_media:
            seq = struct.unpack_from("!H", p, 2)[0]
            if seq % 8 in (2, 5):                 # 2 losses per window
                dropped[seq] = p
                continue
            rx.on_packet(p)
        for p in par:
            rx.on_packet(p)
        assert dropped
        for seq, orig in dropped.items():
            assert rx.recovered.get(seq) == orig, seq
    finally:
        recv.close()
        send.close()


def test_late_joiner_windows_start_after_join():
    st = make_stream()
    push_media(st, 20, reflect=False)
    out = make_fec_output()
    st.add_output(out)
    # first protected window begins at the next boundary past join
    assert out.fec.next_window * 8 >= 20
    push_media(st, 20, t0=2000, seq0=20)
    _, par, _ = split_wire(out.rtp_packets, out.fec.cfg)
    for p in par:
        d = fec_mod.parse_parity_packet(p)
        # snbase maps a ring id >= the join head (out seq space starts
        # at seq0=100 and the output fast-starts from the newest
        # keyframe, so every protected seq is one it actually sent)
        assert d is not None and d["snbase"] >= 100


def test_window_with_duplicate_seqs_is_skipped():
    st = make_stream()
    out = make_fec_output()
    st.add_output(out)
    pkt = struct.pack("!BBHII", 0x80, 96, 5, 0, 0xB) + bytes(30)
    t = 1000
    for _ in range(16):                       # 16 copies of seq 5
        st.push_rtp(pkt, t)
        t += 10
        st.reflect(t)
    _, par, _ = split_wire(out.rtp_packets, out.fec.cfg)
    assert par == [] and st.fec.windows_skipped >= 1


# ------------------------------------------------------------- NACK / RTX
def test_nack_replay_byte_exact_and_budget():
    st = make_stream()
    cfg = FecConfig(window=8, rtx_burst=4, rtx_budget_per_sec=1000.0)
    out = make_fec_output(cfg, overhead_idx=0)
    st.add_output(out)
    push_media(st, 16)
    media, _, _ = split_wire(out.rtp_packets, cfg)
    out.rtp_packets.clear()
    base = obs.RTX_SENT.value()
    n = st.fec.replay_nacked(out, [103, 110], 50_000)
    assert n == 2 and obs.RTX_SENT.value() == base + 2
    _, _, rtxs = split_wire(out.rtp_packets, cfg)
    rx = FecReceiver(media_pt=96, fec_pt=cfg.payload_type,
                     rtx_pt=cfg.rtx_payload_type)
    for p in rtxs:
        rx.on_packet(p)
    for seq in (103, 110):
        orig = next(m for m in media
                    if struct.unpack_from("!H", m, 2)[0] == seq)
        assert rx.have(seq) == orig
    # bucket exhaustion: drain the remaining tokens at a FROZEN clock,
    # then every further NACKed seq is a counted give-up, which the
    # output's own quality controller hears with its next judged report
    st.fec.replay_nacked(out, [100, 101], 50_000)
    gu = obs.RTX_GIVEUP.value()
    st.fec.replay_nacked(out, [104, 105], 50_000)
    assert out.fec.rtx_giveups == 2
    assert obs.RTX_GIVEUP.value() == gu + 2
    assert out.fec.giveups_unjudged == 2
    # evicted/never-ingested seqs are silently skipped, never replayed
    assert st.fec.replay_nacked(out, [9999], 60_000) == 0


def test_nack_resolves_through_inverse_affine():
    st = make_stream()
    cfg = FecConfig(window=8)
    out = make_fec_output(cfg, overhead_idx=0, seq0=40_000)
    st.add_output(out)
    push_media(st, 8, seq0=65_530)            # source seqs wrap 65530..1
    out.rtp_packets.clear()
    assert st.fec.replay_nacked(out, [40_003], 50_000) == 1
    _, _, [r] = split_wire(out.rtp_packets, cfg)
    osn, wire = fec_mod.restore_rtx_packet(r, media_pt=96)
    assert osn == 40_003
    # the replayed packet's payload is the ring packet for src seq
    # (65530 + 3) & 0xffff = 65533
    src = st.rtp_ring.get(3)
    assert wire[12:] == src[12:]


# ------------------------------------------------------------ closed loop
def test_rate_controller_hysteresis_and_tracking():
    c = FecRateController()
    assert c.overhead == 0.0
    c.on_receiver_report(0.5)                 # one heavy report: now
    assert c.overhead == OVERHEAD_LADDER[1]
    for _ in range(12):                       # 8% sustained → climbs to
        c.on_receiver_report(0.08)            # the covering rung, then
    assert c.overhead == 0.10                 # HOLDS (residual = RTX)
    for _ in range(3):
        c.on_receiver_report(0.08)
    assert c.overhead == 0.10
    for _ in range(6 * 4):
        c.on_receiver_report(0.0)             # sustained clean decays
    assert c.overhead == 0.0
    # the in-between band resets both counters
    c.on_receiver_report(0.08)
    c.on_receiver_report(0.08)
    c.on_receiver_report(0.01)
    c.on_receiver_report(0.08)
    assert c.overhead == 0.0


def test_rate_controller_nadu_shifts_split_toward_rtx():
    c = FecRateController()
    for _ in range(3):
        c.on_receiver_report(0.25)
    assert c.overhead > 0.10
    start = c.overhead
    for _ in range(3):                        # buffer distress: parity
        c.on_nadu(50, 500)                    # is bitrate → step DOWN
    assert c.overhead < start
    c.on_nadu(0xFFFF, 500)                    # unknown delay, roomy: no-op
    assert c.overhead < start


def test_rate_controller_max_overhead_cap():
    c = FecRateController(max_overhead=0.10)
    for _ in range(20):
        c.on_receiver_report(0.9)
    assert c.overhead == 0.10
    assert c.parity_rows(16) == 2
    assert c.parity_rows(16, kind=fec_mod.KIND_XOR) == 1
    with pytest.raises(ValueError):
        FecConfig(window=64).validate()
    with pytest.raises(ValueError):
        FecConfig(kind="raid6").validate()


def test_host_fallback_on_oracle_mismatch(monkeypatch):
    st = make_stream()
    out = make_fec_output(FecConfig(window=8), overhead_idx=2)
    st.add_output(out)
    import easydarwin_tpu.models.relay_pipeline as rp

    def bad_kernel(rows, coeff):              # a deliberately wrong device
        import jax.numpy as jnp
        return jnp.zeros((coeff.shape[0], rows.shape[1]), jnp.uint8) + 1

    monkeypatch.setattr(rp, "fec_parity_window_step", bad_kernel)
    base = obs.FEC_PARITY_ORACLE_MISMATCH.value()
    push_media(st, 16)
    assert st.fec.host_fallback                  # latched
    assert obs.FEC_PARITY_ORACLE_MISMATCH.value() == base + 1
    # the wire still carries ORACLE-TRUE parity: recovery works
    media, par, _ = split_wire(out.rtp_packets, out.fec.cfg)
    rx = FecReceiver(media_pt=96)
    for p in media[1:]:
        rx.on_packet(p)
    for p in par:
        rx.on_packet(p)
    seq = struct.unpack_from("!H", media[0], 2)[0]
    assert rx.recovered.get(seq) == media[0]
    # subsequent windows never touch the device again
    passes = st.fec.device_passes
    push_media(st, 16, t0=5000, seq0=16)
    assert st.fec.device_passes == passes


# --------------------------------------------------- receiver-side sites
def test_inject_receiver_sites_deterministic():
    from easydarwin_tpu.resilience.inject import (SITES, FaultInjector,
                                                  FaultPlan)
    assert "egress_drop" in SITES and "rr_loss_spoof" in SITES
    plan = FaultPlan.parse("seed=9,egress_drop=0.2,rr_loss_spoof=0.3")
    a, b = FaultInjector(), FaultInjector()
    a.arm(plan)
    b.arm(plan)
    seq_a = [a.egress_drop() for _ in range(200)]
    assert seq_a == [b.egress_drop() for _ in range(200)]
    assert 10 < sum(seq_a) < 80
    assert a.counts()["egress_drop"] == sum(seq_a)
    assert a.rr_loss_spoof() == pytest.approx(0.3)
    assert a.counts()["rr_loss_spoof"] == 1
    a.disarm()
    assert a.egress_drop() is False and a.rr_loss_spoof() is None


def test_egress_drop_site_accounts_like_a_sent_packet():
    from easydarwin_tpu.resilience.inject import (INJECTOR, FaultPlan)
    out = CollectingOutput(ssrc=1, out_seq_start=1)
    pkt = struct.pack("!BBHII", 0x80, 96, 5, 0, 0xB) + bytes(20)
    INJECTOR.arm(FaultPlan.parse("seed=1,egress_drop=1.0"))
    try:
        assert out.write_rtp(pkt) is WriteResult.OK
        assert out.packets_sent == 1 and out.rtp_packets == []
        assert out.send_rewritten(pkt[:12], pkt[12:]) is WriteResult.OK
        assert out.rtp_packets == []
    finally:
        INJECTOR.disarm()
    assert out.write_rtp(pkt) is WriteResult.OK
    assert len(out.rtp_packets) == 1          # disarmed: wire flows


# ------------------------------------------------------- gauges + wiring
def test_stream_fec_registration_and_gauge():
    st = make_stream()
    st.session_path = "/live/t"
    out = make_fec_output(overhead_idx=2)
    st.add_output(out)
    plain = CollectingOutput(ssrc=2, out_seq_start=2)
    st.add_output(plain)                      # no .fec: not registered
    assert st.fec.outputs == [out]
    push_media(st, 8)
    key = {"path": "/live/t", "track": "1"}
    assert obs.FEC_OVERHEAD_RATIO._values.get(
        ("/live/t", "1")) == pytest.approx(0.10)
    st.remove_output(out)
    assert st.fec.outputs == []
    fec_mod.drop_overhead_gauge(key["path"], key["track"])
    assert ("/live/t", "1") not in obs.FEC_OVERHEAD_RATIO._values


def test_thinned_output_emits_no_parity():
    st = make_stream()
    out = make_fec_output(overhead_idx=2)
    out.thinning.controller.level = 2         # keyframes only
    st.add_output(out)
    push_media(st, 32)
    _, par, _ = split_wire(out.rtp_packets, out.fec.cfg)
    assert par == []


def test_thinned_output_never_replays_rtx():
    """A thinned output's seq gaps are DELIBERATE drops; replaying them
    would defeat thinning and drain the token bucket on a healthy
    client (review finding)."""
    st = make_stream()
    out = make_fec_output(overhead_idx=0)
    st.add_output(out)
    push_media(st, 16)
    out.thinning.controller.level = 1
    out.rtp_packets.clear()
    assert st.fec.replay_nacked(out, [103, 104], 50_000) == 0
    assert out.rtp_packets == [] and out.fec.rtx_giveups == 0


def test_parity_cache_hard_bound_survives_stalled_subscriber():
    """One stalled output must not pin the window-parity cache (review
    finding: min(next_window) eviction never moves while a bookmark is
    frozen)."""
    st = make_stream()
    fast = make_fec_output(overhead_idx=2)
    stalled = make_fec_output(overhead_idx=2, ssrc=2, seq0=7)
    st.add_output(fast)
    st.add_output(stalled)
    t = push_media(st, 8, reflect=True)       # both primed + window 0
    stalled.block_next = 10**9                # WOULD_BLOCK forever
    push_media(st, 256, t0=t, seq0=8)
    assert len(st.fec._cache) <= st.fec.CACHE_WINDOWS
    assert len(st.fec._cached_rows) <= st.fec.CACHE_WINDOWS


def test_payload_type_collision_rejected():
    with pytest.raises(ValueError):
        FecConfig(payload_type=126, rtx_payload_type=126).validate()
    with pytest.raises(ValueError):
        FecConfig(payload_type=200).validate()
    # a STREAM whose media PT equals the parity/RTX PT stays
    # unprotected instead of emitting parity that parses as media
    st = make_stream()
    st.info.payload_type = 127
    out = make_fec_output()
    st.add_output(out)
    assert out.fec is None
    assert st.fec is None or st.fec.outputs == []


# --------------------------------------------------------- tool contracts
def test_lint_fec_contract():
    import pathlib

    from easydarwin_tpu.obs import events as ev
    from tools.metrics_lint import lint_emit_sites, lint_fec
    assert lint_fec(obs.REGISTRY, ev.SCHEMA) == []
    pkg = pathlib.Path(fec_mod.__file__).resolve().parents[1]
    assert lint_emit_sites(pkg, ev.SCHEMA) == []
    # a registry without the families is rejected
    from easydarwin_tpu.obs.metrics import Registry
    errs = lint_fec(Registry(), ev.SCHEMA)
    assert any("fec_parity_packets_total" in e for e in errs)
    # an open kind vocabulary is rejected
    r = Registry()
    fam = r.counter("fec_parity_packets_total", "x", labels=("kind",))
    r.counter("fec_recovered_total", "x")
    r.counter("fec_parity_oracle_mismatch_total", "x")
    r.gauge("fec_overhead_ratio", "x", labels=("path", "track"))
    r.counter("rtx_sent_total", "x")
    r.counter("rtx_giveup_total", "x")
    fam.inc(kind="raid6")
    assert any("raid6" in e for e in lint_fec(r, ev.SCHEMA))


def test_bench_gate_accepts_and_rejects_fec_section():
    from tools.bench_gate import check_trajectory

    def entry(extra):
        return [{"file": "BENCH_rT.json", "rc": 0,
                 "parsed": {"metric": "m", "value": 100.0, "unit": "pps",
                            "vs_baseline": 2.0, "extra": extra}}]

    assert check_trajectory(entry({})) == []          # old rounds valid
    ok = {"fec": {"goodput_pkts_per_sec": 1200.0, "recovered_ratio": 1.0,
                  "rtx_p99_ms": 0.4, "oracle_mismatches": 0}}
    assert check_trajectory(entry(ok)) == []
    bad = {"fec": {"goodput_pkts_per_sec": 0.0, "recovered_ratio": 1.0,
                   "rtx_p99_ms": 0.4}}
    assert any("goodput" in e for e in check_trajectory(entry(bad)))
    bad = {"fec": {"goodput_pkts_per_sec": 10.0, "recovered_ratio": 1.5,
                   "rtx_p99_ms": 0.4}}
    assert any("recovered_ratio" in e
               for e in check_trajectory(entry(bad)))
    bad = {"fec": {"goodput_pkts_per_sec": 10.0, "recovered_ratio": 1.0,
                   "rtx_p99_ms": 0.4, "oracle_mismatches": 2}}
    assert any("oracle" in e for e in check_trajectory(entry(bad)))
    errd = {"fec": {"error": "section skipped"}}
    assert check_trajectory(entry(errd)) == []


async def test_server_e2e_nack_rtx_and_loss_driven_parity():
    """End-to-end through a real server: a plain-UDP player is
    FEC-armed at SETUP, a generic NACK through the shared RTCP socket
    comes back as a byte-exact RTX replay, and RRs reporting loss ramp
    the closed loop until parity packets reach the player socket."""
    import asyncio

    from easydarwin_tpu.server import ServerConfig, StreamingServer
    from easydarwin_tpu.utils.client import RtspClient

    cfg = ServerConfig(rtsp_port=0, service_port=0, bind_ip="127.0.0.1",
                       reflect_interval_ms=5, bucket_delay_ms=0,
                       access_log_enabled=False, fec_window=8)
    app = StreamingServer(cfg)
    await app.start()
    try:
        base = f"rtsp://127.0.0.1:{app.rtsp.port}"
        pusher = RtspClient()
        await pusher.connect("127.0.0.1", app.rtsp.port)
        await pusher.push_start(f"{base}/live/fec", SDP_TXT)
        rtp_s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rtp_s.bind(("127.0.0.1", 0))
        rtp_s.setblocking(False)
        rtcp_s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rtcp_s.bind(("127.0.0.1", 0))
        rtcp_s.setblocking(False)
        player = RtspClient()
        await player.connect("127.0.0.1", app.rtsp.port)
        await player.play_start(
            f"{base}/live/fec", tcp=False,
            client_ports=[(rtp_s.getsockname()[1],
                           rtcp_s.getsockname()[1])],
            setup_headers={"x-fec": "parity"})
        out = next(cn for cn in app.rtsp.connections
                   if cn.player_tracks).player_tracks[1].output
        assert getattr(out, "fec", None) is not None   # opt-in granted
        # a player that does NOT opt in is never armed: un-negotiated
        # parity on the media SSRC would corrupt a conformant
        # receiver's per-SSRC loss statistics (review finding)
        r2 = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        r2.bind(("127.0.0.1", 0))
        r3 = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        r3.bind(("127.0.0.1", 0))
        plain = RtspClient()
        await plain.connect("127.0.0.1", app.rtsp.port)
        await plain.play_start(
            f"{base}/live/fec", tcp=False,
            client_ports=[(r2.getsockname()[1], r3.getsockname()[1])])
        plain_out = next(
            cn for cn in app.rtsp.connections
            if cn.player_tracks and cn.player_tracks[1].output is not out
        ).player_tracks[1].output
        assert getattr(plain_out, "fec", None) is None
        await plain.close()
        r2.close()
        r3.close()
        egress = app.rtsp.shared_egress
        rx = FecReceiver(media_pt=96, fec_pt=cfg.fec_payload_type,
                         rtx_pt=cfg.rtx_payload_type)
        rng = random.Random(4)
        got_parity = got_rtx = False
        dropped_wire: dict[int, bytes] = {}
        nacked = False
        for i in range(400):
            pay = bytes(rng.randrange(256) for _ in range(40))
            pusher.push_packet(0, struct.pack(
                "!BBHII", 0x80, 96, i & 0xFFFF,
                (i * 3000) & 0xFFFFFFFF, 0xB) + pay)
            await asyncio.sleep(0.01)
            while True:
                try:
                    d = rtp_s.recv(65536)
                except BlockingIOError:
                    break
                if (d[1] & 0x7F) == 96:
                    seq = struct.unpack_from("!H", d, 2)[0]
                    n_media = len(rx.media) + len(dropped_wire)
                    if 50 <= n_media < 53 and seq not in dropped_wire:
                        dropped_wire[seq] = d  # receiver-side loss
                        continue
                kind = rx.on_packet(d)
                got_parity |= kind == "fec"
                got_rtx |= kind == "rtx"
            if not nacked and len(dropped_wire) == 3 and rx.media:
                nacked = True                 # NACK the dropped seqs
                rtcp_s.sendto(rtcp.GenericNack.from_seqs(
                    0x77, out.rewrite.ssrc,
                    sorted(dropped_wire)).to_bytes(),
                    ("127.0.0.1", egress.rtcp_port))
            if i % 25 == 10:
                # RRs reporting 8% loss (2 of every 25 packets, by
                # RFC 3550's cumulative count and extended highest
                # seq) ramp the FEC ladder; the server judges them
                # once folded over 64 packets, and thinning hears
                # none of it — parity covers 8%
                rr = rtcp.ReceiverReport(0x77, [rtcp.ReportBlock(
                    out.rewrite.ssrc, 20, 2 * (i // 25 + 1),
                    out.rewrite.out_seq_start + i, 0, 0, 0)]
                ).to_bytes()
                rtcp_s.sendto(rr, ("127.0.0.1", egress.rtcp_port))
            if got_parity and got_rtx:
                break
        assert got_rtx, "NACK never came back as an RTX replay"
        for seq, orig in dropped_wire.items():
            # the receiver keys by UNWRAPPED seq; the output's random
            # seq0 may have wrapped mid-test
            cand = [v for k in (seq, seq + 0x10000)
                    for v in (rx.rtx_restored.get(k),
                              rx.recovered.get(k)) if v is not None]
            assert cand and cand[0] == orig, seq   # byte-exact replay
        assert got_parity, "loss-reporting RRs never produced parity"
        assert out.fec.controller.overhead > 0
        assert out.thinning.controller.level == 0
        rtp_s.close()
        rtcp_s.close()
        await player.close()
        await pusher.close()
    finally:
        await app.stop()


def test_soak_check_metrics_lossy_contract():
    from tools.soak import check_metrics
    base = {"relay_ingest_to_wire_seconds_count{engine=\"native\"}": 5.0,
            "relay_phase_seconds_count{engine=\"pump\","
            "phase=\"wake_to_pass\"}": 5.0}
    clean = dict(base, **{"fec_recovered_total": 3.0,
                          "rtx_sent_total": 1.0,
                          "fec_overhead_ratio"
                          "{path=\"/live/b\",track=\"1\"}": 0.1})
    assert check_metrics([clean], lossy=8.0) == []
    # oracle mismatch fails ANY soak
    bad = dict(clean, fec_parity_oracle_mismatch_total=1.0)
    assert any("oracle" in e for e in check_metrics([bad]))
    # zero recovery / budget exhaustion / flat overhead fail lossy runs
    bad = dict(base, **{"fec_recovered_total": 0.0,
                        "rtx_sent_total": 0.0})
    errs = check_metrics([bad], lossy=8.0)
    assert any("recovered zero" in e for e in errs)
    assert any("overhead" in e for e in errs)
    bad = dict(clean, rtx_giveup_total=2.0)
    assert any("budget" in e for e in check_metrics([bad], lossy=8.0))
