"""The lossy last mile: the chain, the harness's own GF(256) reference
against the program's, recovery, RTX, the reports and NACKs the players
send, the judge on hand-made flows, whole runs on the CPU (clean, and the
two controls ``ref:parity`` / ``ref:rtx``), and a configuration without
``last_mile`` on today's path.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import ast
import itertools
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from file_checks import BENCH, ROOT, load

from benchmark import lastmile, loadgen, reference, reference_fec
from benchmark import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
LM = {"model": "gilbert", "mean_loss": 0.05, "mean_burst_packets": 2,
      "rr_interval_s": 2, "nack": "stamped", "nack_after_ms": 1000}


def last_mile(**kw):
    return lastmile.LastMile(dict(LM, **kw), {})


# ------------------------------------------------------------ the chain
@pytest.mark.parametrize("loss, burst", [(0.02, 3), (0.04, 3), (0.05, 2),
                                         (0.1, 1.5)])
def test_chain_keeps_its_mean_loss_and_mean_burst(loss, burst):
    chain = last_mile(mean_loss=loss, mean_burst_packets=burst).chain(
        2**31 + 41, "stamped", 0)
    lost = [chain.step() for _ in range(10**6)]
    runs = [len(list(g)) for bad, g in itertools.groupby(lost) if bad]
    assert sum(lost) / len(lost) == pytest.approx(loss, rel=0.03)
    assert np.mean(runs) == pytest.approx(burst, rel=0.03)


def test_chain_is_the_seeds():
    lm = last_mile()
    draws = [[c.step() for _ in range(5000)] for c in (
        lm.chain(7, "stamped", 3), lm.chain(7, "stamped", 3),
        lm.chain(7, "stamped", 4), lm.chain(8, "stamped", 3))]
    assert draws[0] == draws[1]
    assert draws[0] != draws[2] and draws[0] != draws[3]


@pytest.mark.parametrize("spec", [
    {"model": "markov"}, {"nack": "all"}, {"mean_loss": 0},
    {"mean_burst_packets": 0.5}, {"mean_loss": 0.9,
                                  "mean_burst_packets": 1}])
def test_a_last_mile_it_cannot_play_is_refused(spec):
    with pytest.raises(lastmile.LastMileError):
        lastmile.LastMile(dict(LM, **spec), {})


# ------------------------------------------------- the GF(256) reference
def test_the_field_is_the_programs():
    from easydarwin_tpu.relay import fec
    a = np.arange(256)
    for b in (0, 1, 2, 29, 142, 255):
        assert [reference_fec.MUL[x, b] for x in a] == [
            fec.gf_mul(int(x), b) for x in a]
    assert all(reference_fec.MUL[x, reference_fec.INV[x]] == 1
               for x in range(1, 256))


@pytest.mark.parametrize("seed", range(6))
def test_parity_matches_the_programs_gf_matmul(seed):
    """Random windows: every parity row the reference computes over
    ``len ∥ wire`` rows is the program's host oracle's."""
    from easydarwin_tpu.relay import fec
    rng = np.random.default_rng([seed, 41])
    m, r = int(rng.integers(2, 17)), int(rng.integers(1, 9))
    deltas = sorted(rng.choice(reference_fec.MASK_BITS, m,
                               replace=False).tolist())
    wires = [rng.bytes(int(rng.integers(12, 1415))) for _ in range(m)]
    width = max(map(len, wires)) + 2
    rows = np.stack([reference_fec.row_of(w, width) for w in wires])
    want = fec.gf_matmul(fec.coeff_rows(deltas, r), rows)
    for p in range(r):
        assert reference_fec.parity_payload(wires, deltas, p) == \
            want[p].tobytes()
    # and the whole datagram is what the program builds around it
    pkt = reference_fec.parity_packet(wires, deltas, 1, snbase=65530,
                                      pt=127, seq=9, ts=77, ssrc=0xABCDEF01)
    assert pkt == fec.build_parity_packet(
        fec_pt=127, fec_seq=9, ts=77, ssrc=0xABCDEF01, snbase=65530,
        deltas=deltas, idx=1, kind=fec.KIND_RS,
        payload=reference_fec.parity_payload(wires, deltas, 1))


@pytest.mark.parametrize("n_parity", [1, 2, 3])
def test_every_pattern_of_up_to_r_losses_in_a_window_is_recovered(n_parity):
    rng = np.random.default_rng([n_parity, 16])
    wires = [rng.bytes(int(rng.integers(12, 1415))) for _ in range(16)]
    deltas = list(range(16))
    rows = {p: reference_fec.parity_payload(wires, deltas, p)
            for p in range(n_parity)}
    for m in range(1, n_parity + 1):
        for lost in itertools.combinations(deltas, m):
            known = {d: wires[d] for d in deltas if d not in lost}
            got = reference_fec.solve(known, list(lost), rows)
            assert got == {d: wires[d] for d in lost}, lost
    lost = list(range(n_parity + 1))            # one too many: no answer
    assert reference_fec.solve({d: wires[d] for d in deltas[n_parity + 1:]},
                               lost, rows) is None


def test_rtx_restores_what_the_program_replays():
    from easydarwin_tpu.relay import fec
    wire = bytes((0x80, 0xE0, 0x12, 0x34)) + bytes(range(8)) + b"payload"
    rtx = fec.build_rtx_packet(wire, rtx_pt=126, rtx_seq=700)
    assert reference_fec.rtx_packet(wire, rtx_pt=126, rtx_seq=700) == rtx
    assert reference_fec.restore_rtx(rtx, 96) == (0x1234, wire)
    assert reference_fec.restore_rtx(rtx[:13], 96) is None


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference_fec.py", "lastmile.py", "reference.py",
                 "loadgen.py"):
        with open(os.path.join(ROOT, "benchmark", name)) as f:
            tree = ast.parse(f.read())
        mods = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names] + [
            n.module or "" for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom)]
        assert not [m for m in mods if m.startswith("easydarwin_tpu")], name
    r = subprocess.run(
        [sys.executable, "-c", "import sys; sys.path.insert(0, %r); "
         "import benchmark.reference_fec, benchmark.lastmile; "
         "print(sorted(m for m in sys.modules if 'easydarwin' in m))" % ROOT],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and r.stdout.strip() == "[]", r.stderr


# ------------------------------------------------------ hand-made flows
FIRST, SSRC, TS = 65530, 0xDEADBEEF, 4242


def _flow(n=40):
    from benchmark.loadgen import Source
    shapes = load("benchmark/configs/relay-16x256.json")["stream"]
    pushed = Source(0, 7, 4, 1.0, shapes).packets[:n]
    return pushed, reference.reference_flow(pushed, FIRST, SSRC, TS)


def _expected_at(pushed):
    def at(seq):
        k = (seq - FIRST) & 0xFFFF
        return (reference.expected_packet(pushed[k], k, FIRST, SSRC, TS)
                if k < len(pushed) else None)
    return at


def _parity(wire, lo, n, rows):
    deltas = list(range(n))
    return [reference_fec.parity_packet(
        wire[lo:lo + n], deltas, p, snbase=(FIRST + lo) & 0xFFFF, pt=127,
        seq=p, ts=0, ssrc=SSRC) for p in range(rows)]


def test_the_judge_on_hand_made_flows():
    """Clean; one parity byte flipped; an RTX packet altered; a packet
    missing after recovery."""
    pushed, wire = _flow()
    at = _expected_at(pushed)
    parity = _parity(wire, 3, 16, 3) + _parity(wire, 19, 16, 2)
    rtx = [reference_fec.rtx_packet(wire[k], rtx_pt=126, rtx_seq=k)
           for k in (5, 30)]
    clean = (reference_fec.parity_altered(parity, at, SSRC, 127),
             reference_fec.rtx_altered(rtx, at, 96))
    assert clean == (0, 0)
    flipped = reference_fec.break_one(parity, 7)
    assert reference_fec.parity_altered(flipped, at, SSRC, 127) == 1
    assert reference_fec.parity_altered(parity, at, SSRC ^ 1, 127) == 5
    bent = list(rtx)
    bent[1] = bent[1][:-1] + bytes((bent[1][-1] ^ 0x80,))
    assert reference_fec.rtx_altered(bent, at, 96) == 1
    # held: everything but 7 and 8 arrived; 7 was restored, 8 never was
    got = [w for k, w in enumerate(wire) if k not in (7, 8)]
    assert reference.judge_flow(got, pushed, FIRST, SSRC, [wire[7]]) == {
        "missing": 1, "out_of_order": 0, "altered": 0, "unannounced": 0}
    # a restored packet that is wrong, or restored twice, is altered
    assert reference.judge_flow(got, pushed, FIRST, SSRC,
                                [wire[8], wire[7][:-1] + b"\x00"]
                                )["altered"] == 1
    assert reference.judge_flow(wire, pushed, FIRST, SSRC,
                                [wire[3]])["altered"] == 1


class _Sent(list):
    def sendto(self, data, addr):
        self.append(data)


def _receiver(drop, nack_after_ms=1000):
    lm = last_mile(nack_after_ms=nack_after_ms)
    flow = types.SimpleNamespace(first_seq=FIRST, ssrc=SSRC, packets=[],
                                 stamps=[], rtcp=_Sent())
    rx = lastmile.StampedReceiver(flow, lm, 5, 0)
    steps = iter(drop)
    rx.chain.step = lambda: next(steps)
    rx.server_rtcp = ("127.0.0.1", 9)
    return rx


def test_the_stamped_receiver_repairs_reports_and_asks():
    """A scripted chain drops 4, 5 and 20 of 32 packets: two parity rows
    of the window 0-15 restore 4 and 5 at the instant they come; 20 has
    none, so after ``nack_after_ms`` a generic NACK asks for it, and an
    RTX restores it; the RR says what arrived before repair."""
    from easydarwin_tpu.protocol import rtcp
    pushed, wire = _flow(32)
    rx = _receiver([k in (4, 5, 20) for k in range(32)])
    rx.start(0)
    rx.next_rr = 0                      # the seeded phase, made 0
    for k in range(32):
        rx.on_rtp(wire[k], 1000 + k)
    assert sorted(rx.missing) == [4, 5, 20] and rx.lost_by_chain == 3
    for pkt in _parity(wire, 0, 16, 2):
        rx.on_rtp(pkt, 5000)
    assert rx.recovered_fec == 2 and sorted(rx.missing) == [20]
    assert rx.flow.stamps[-2:] == [5000, 5000]
    rx.sweep(10**9 - 1)                 # not yet due, but the RR is
    assert rx.nacks_sent == 0 and rx.rrs_sent == 1
    rr = rtcp.parse_compound(rx.flow.rtcp[-1])[0]
    block = rr.reports[0]
    assert (block.ssrc, block.cumulative_lost) == (SSRC, 3)
    assert block.fraction_lost == (3 << 8) // 32
    rx.sweep(10**9 + 2000)
    nack = rtcp.parse_compound(rx.flow.rtcp[-1])[0]
    assert rx.nacks_sent == 1 and nack.lost_seqs() == [(FIRST + 20) & 0xFFFF]
    assert nack.media_ssrc == SSRC
    rx.on_rtp(reference_fec.rtx_packet(wire[20], rtx_pt=126, rtx_seq=0), 7)
    assert rx.recovered_rtx == 1 and not rx.missing
    assert reference.judge_flow(rx.received, pushed, FIRST, SSRC,
                                rx.repaired) == {
        "missing": 0, "out_of_order": 0, "altered": 0, "unannounced": 0}


def test_a_loss_just_after_a_window_whose_parity_came_is_missed():
    pushed, wire = _flow(20)
    rx = _receiver([k == 16 for k in range(20)])
    for k in range(16):
        rx.on_rtp(wire[k], k)
    for pkt in _parity(wire, 0, 16, 1):
        rx.on_rtp(pkt, 16)
    for k in range(16, 20):
        rx.on_rtp(wire[k], k)
    assert sorted(rx.missing) == [16] and rx.lost_by_chain == 1


def test_a_loss_at_the_tail_is_asked_for_once_the_push_ends():
    pushed, wire = _flow(20)
    rx = _receiver([k >= 18 for k in range(20)], nack_after_ms=1)
    for k in range(20):
        rx.on_rtp(wire[k], k)
    assert not rx.missing               # nothing later shows the loss
    rx.end = 20
    rx.sweep(100)
    rx.sweep(10**7)
    from easydarwin_tpu.protocol import rtcp
    nack = rtcp.parse_compound(rx.flow.rtcp[-1])[0]
    assert nack.lost_seqs() == [(FIRST + 18) & 0xFFFF, (FIRST + 19) & 0xFFFF]


# ------------------------------------------------------- whole runs
def _lossy_run(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "lossy_cell.py"),
         "--mean-loss", "0.05", "--burst", "2", "--rr-interval-s", "2",
         "--nack-after-ms", "1000", "--warm-frames", "8", "--",
         "--seed", str(2**31 + 4101), "--seconds", "8", "--trace", "0",
         "--debug-size", "2x8", "--fps", "8", *extra],
        capture_output=True, text=True, env=env, timeout=600)
    try:
        last = json.loads(r.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        last = None
    return last, r.stdout[-4000:] + r.stderr[-2000:]


def test_a_lossy_run_on_the_cpu_is_correct_and_repairs_both_ways():
    """2 sources x 8 viewers, mean loss 5 %: every stamped packet held at
    the end, parity and RTX both used, their datagrams all the
    reference's, and as many read as the server counts sending."""
    last, tail = _lossy_run()
    assert last is not None and last["correct"] is True, tail
    lm = last["accounting"]["last_mile"]
    assert lm["recovered_fec"] > 0 and lm["recovered_rtx"] > 0, tail
    assert lm["lost_by_chain"] > 0 and lm["rrs_sent"] > 0, tail
    assert lm["parity"] == lm["server_parity"] > 0, tail
    assert lm["rtx"] == lm["server_rtx"] > 0, tail
    # media only: the server's egress count is what was owed, parity
    # and RTX left outside it
    assert last["accounting"]["server_sent"] == last["attempted"], tail
    assert list(last["compared"])[-5:] == [
        "parity_altered", "rtx_altered", "parity_unaccounted",
        "rtx_unaccounted", "server_exit_code"]


@pytest.mark.parametrize("control, number", [("ref:parity", "parity_altered"),
                                             ("ref:rtx", "rtx_altered")])
def test_the_lossy_controls_come_out_not_correct(control, number):
    last, tail = _lossy_run("--control", control)
    assert last is not None and last["correct"] is False, tail
    assert last["compared"][number]["value"] >= 1, tail
    assert f"control {control}: the reference's" in tail


# --------------------------------------------- without the key: today's
TODAYS = ["stamped_missing", "stamped_out_of_order", "stamped_altered",
          "sessions_unannounced", "server_sent_short", "server_sent_excess",
          "server_send_errors", "bulk_excess", "bulk_short"]


def test_a_configuration_without_last_mile_takes_todays_path(monkeypatch,
                                                              tmp_path):
    """On ``relay-16x256`` as committed: plain readers and bulk workers,
    no extra SETUP header, and the judge's names and its ``bulk_*`` /
    ``server_sent_*`` arithmetic as before the key existed."""
    cfg = load("benchmark/configs/relay-16x256.json")
    assert "last_mile" not in cfg and lastmile.LastMile.of(cfg) is None
    lg = loadgen.Loadgen(cfg, load("benchmark/traffic/paced.json"), 3, 1.0,
                         1, 17)
    try:
        assert lg.last_mile is None and lg.setup_extra is None
        assert {type(r) for r in lg.readers} == {loadgen.StampReader}
        assert lg.bulk.stride == 3 and len(lg.bulk.ports) == loadgen.N_PORT
        assert all(f.lm is None for f in lg.flows)
    finally:
        for s in lg.bulk.rtp + lg.bulk.rtcp + [
                s for f in lg.flows for s in (f.rtp, f.rtcp)]:
            s.close()

    monkeypatch.setattr(bench_run, "ROOT", str(tmp_path))   # benchmark_out
    monkeypatch.setattr(bench_run, "load_json", load)
    args = bench_run.argparse.Namespace(
        workload="relay-16x256.paced", seed=9, seconds=1.0, trace=0,
        debug_size="2x17", fps=None, control=None, child_script="unused")
    r = bench_run.Run(args, BENCH)
    pushed, wire = _flow(12)
    src = types.SimpleNamespace(packets=pushed, pushed=12, idx=0)
    flows = [types.SimpleNamespace(src=src, packets=wire[:10] + wire[11:],
                                   stamps=[], first_seq=FIRST, ssrc=SSRC,
                                   lm=None, fast_start=False)]

    class Bulk:
        def counts(self):
            return (12 * 16 * 2 - 5, 0, 0)      # two sources, five short

    r.lg = types.SimpleNamespace(
        flows=flows * 2, joiners=[], sources=[src, src], last_mile=None,
        bulk=Bulk(), expected_deliveries=lambda: 12 * 17 * 2,
        stamped_read=lambda: 0)
    r.account({"egress_packets_total": 12 * 17 * 2 + 3,
               "egress_send_errors_total": 0}, {}, {})
    assert r.acct["bulk_owed"] == 12 * 2 * (17 - 1)
    assert not r.judge()
    assert list(r.compared) == TODAYS
    got = {k: v for k, (v, _) in r.compared.items()}
    assert got == {"stamped_missing": 2, "stamped_out_of_order": 0,
                   "stamped_altered": 0, "sessions_unannounced": 0,
                   "server_sent_short": 0, "server_sent_excess": 3,
                   "server_send_errors": 0, "bulk_excess": 0,
                   "bulk_short": 5}
    assert r.failed == 2 + 5 and "last_mile" not in r.acct
