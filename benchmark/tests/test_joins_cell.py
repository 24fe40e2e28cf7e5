"""Late joiners (PR 45): the generator's ``joins`` key, the plain
reference's fast-start rule for a player who joins while the stream
runs, its three controls, the RTP/JPEG packetiser, and the cell
``relay-1x64.joins`` with its metric ``join_first_idr_p90_ms``.

Without a ``joins`` key and without an RTP/JPEG codec the generator's
bytes, plan and sessions are the parent's (pinned from its code at
7e514e1 for one seed).  What the committed file must hold is checked by
``file_checks.py``'s ``joins`` part.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import asyncio
import copy
import hashlib
import json
import os
import subprocess
import sys
import time

import pytest

import file_checks
from file_checks import JOINS_KEY, ROOT, load

from benchmark import loadgen, reference

SEED = 4500000007
CFG = load("benchmark/configs/relay-1x64.json")
LIVE = load("benchmark/traffic/live.json")
JOINS = load("benchmark/traffic/live-joins.json")
#: the parent's generator at SEED, 30 s, per (configuration, mix): sha256
#: over every packet and their count, sha256 of repr(warm-up plan, window
#: plan) and its length, sha256 of repr([(source, ip)] of the stamped
#: sessions) and their count, bulk sessions a source
PARENT = {
    ("relay-1x64", "live"): (
        "22fa4c7623a200e4ad3feffa61a835b20e91b28fe30145b67a7655a2b55db751",
        11345,
        "956d24e89957d876ce7d933891cf2a469c54aa800165fe37c13001069fdc11ef",
        960,
        "7917e5489df2540c31ecebdc542163b747395ce615a6278ee97dac476f1eb1ae",
        16, 48),
    ("relay-16x256", "paced"): (
        "fe94c7f38d934b4f74bdeddc0513fd27eda8f096ad88508565f3af3168324786",
        7895,
        "61ddaeaefa31fd1086680867a8cc3cfebc3a4724ae9d4672597e9f4821256b76",
        633,
        "d017576504e855028fceddbcd33990603dcbc9355a6b7e969ad4d65d246fdc89",
        256, 240)}
#: the committed mix's bursts at SEED: (seconds into the window, players)
PLAN = [(1.309017, 1), (2.927051, 2), (3.545085, 3), (5.163119, 4),
        (6.781153, 5), (7.399187, 6), (9.017221, 7), (10.635255, 8),
        (11.253289, 1), (15.871323, 2), (17.489357, 3), (19.107391, 4),
        (20.725425, 5), (21.343459, 6), (22.961493, 7)]


class FakeSock:
    """A socket the generator holds but never reads here."""

    def getsockname(self):
        return ("127.0.0.1", 40000)


def generator(cfg, traffic, seconds=30.0, n_src=None, n_sub=None):
    """A generator for its plan, sources and sessions alone: no socket
    is opened and no receiver started."""
    with pytest.MonkeyPatch.context() as mp:
        for name in ("BulkDrains", "StampReader"):
            mp.setattr(loadgen, name, lambda *a, **kw: None)
        mp.setattr(loadgen, "udp_pair", lambda ip: (FakeSock(), FakeSock()))
        return loadgen.Loadgen(cfg, traffic, SEED, seconds,
                               n_src or cfg["sources"],
                               n_sub or cfg["players"]["per_source"])


def digests(lg, seconds=30.0):
    h = hashlib.sha256()
    for s in lg.sources:
        for p in s.packets:
            h.update(p)
    W = lg.warm_frames
    plan = (lg.frame_plan(0, lambda due: due < W / lg.fps),
            lg.frame_plan(W, lambda due: due < seconds))
    sess = [(f.src.idx, f.ip) for f in lg.flows]
    return (h.hexdigest(), sum(len(s.packets) for s in lg.sources),
            hashlib.sha256(repr(plan).encode()).hexdigest(),
            len(plan[0]) + len(plan[1]),
            hashlib.sha256(repr(sess).encode()).hexdigest(), len(sess),
            lg.n_bulk)


@pytest.mark.parametrize("check", file_checks.params("joins"))
def test_the_joins_cell_stands_as_accepted(check):
    check()


# ------------------------------------------------- the parent's generator
@pytest.mark.parametrize("config, mix", list(PARENT))
def test_without_joins_or_jpeg_the_generator_is_the_parents(config, mix):
    lg = generator(load(f"benchmark/configs/{config}.json"),
                   load(f"benchmark/traffic/{mix}.json"))
    assert digests(lg) == PARENT[(config, mix)]
    assert lg.joiners == [] and lg.join_plan == []
    assert all(s.sdp == loadgen.SDP for s in lg.sources)
    assert loadgen.SDP == (
        "v=0\r\no=- 1 1 IN IP4 127.0.0.1\r\ns=benchmark\r\nt=0 0\r\n"
        "m=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
        "a=control:trackID=1\r\n")


def test_the_joins_add_sessions_and_change_no_byte():
    """``live-joins`` pushes ``live``'s bytes on ``live``'s plan to
    ``live``'s sessions, and has 64 stamped joiners besides."""
    base, joined = generator(CFG, LIVE), generator(CFG, JOINS)
    assert digests(joined) == digests(base) == PARENT[("relay-1x64", "live")]
    assert len(joined.joiners) == 64
    assert {j.src.idx for j in joined.joiners} == {0}
    assert all(isinstance(j, loadgen.Joiner) for j in joined.joiners)
    assert not any(isinstance(f, loadgen.Joiner) for f in joined.flows)


# ------------------------------------------------------------ the schedule
def test_burst_sizes_climb_the_range_again_and_again():
    assert loadgen.burst_sizes(JOINS_KEY) == [*range(1, 9), *range(1, 8)]
    assert sum(loadgen.burst_sizes(JOINS_KEY)) == 64
    assert loadgen.burst_sizes({"count": 10, "burst_size": [3, 4]}) == [
        3, 4, 3]


def test_the_committed_mix_at_a_seed():
    plan = generator(CFG, JOINS).join_plan
    assert [(round(at, 6), n) for at, n in plan] == PLAN
    # 15 bursts, 64 players, each burst in a GOP of its own inside 0.5-24 s
    assert len(plan) == 15 and sum(n for _, n in plan) == 64
    assert [int(at) for at, _ in plan] == sorted({int(at) for at, _ in plan})
    assert all(0.5 <= at < 24 for at, _ in plan)


@pytest.mark.parametrize("seed", [1, 4500000008, 2**31 + 4501, 2**33 + 7])
def test_every_seed_gets_the_same_bursts_at_the_same_phases(seed):
    """What a joiner waits for — the newest keyframe's age at its PLAY,
    the output bucket its place in the joining order gives it — is the
    same for every seed; the seed draws which GOPs the bursts fall in."""
    ours = loadgen.join_schedule(JOINS_KEY, SEED, 1.0, 0.0)
    theirs = loadgen.join_schedule(JOINS_KEY, seed, 1.0, 0.0)
    assert theirs == loadgen.join_schedule(JOINS_KEY, seed, 1.0, 0.0)
    assert [n for _, n in theirs] == [n for _, n in ours]
    assert [round(at % 1, 9) for at, _ in theirs] == [
        round(at % 1, 9) for at, _ in ours]
    gops = [int(at) for at, _ in theirs]
    assert gops == sorted(set(gops)) and 1 <= gops[0] and gops[-1] <= 23


def test_the_phases_follow_the_sources_gop():
    """A GOP of 2 s that starts 0.25 s into the window: every burst lies
    the same fraction of a GOP past one of its keyframes."""
    plan = loadgen.join_schedule(dict(JOINS_KEY, within_s=[0.5, 60]),
                                 SEED, 2.0, 0.25)
    ref = loadgen.join_schedule(JOINS_KEY, SEED, 1.0, 0.0)
    assert [round((at - 0.25) % 2.0 / 2.0, 9) for at, _ in plan] == [
        round(at % 1.0, 9) for at, _ in ref]


@pytest.mark.parametrize("bad", [
    {"count": 0}, {"burst_size": [0, 3]}, {"burst_size": [5, 2]},
    {"within_s": [0.5, 31]}, {"within_s": [3, 3]}, {"source": 1},
    {"within_s": [0.5, 10]},            # 15 bursts, 9 GOPs
    {"count": "many"}])
def test_a_joins_group_it_cannot_play_is_refused(bad):
    with pytest.raises(loadgen.LoadgenError):
        generator(CFG, dict(JOINS, joins=dict(JOINS_KEY, **bad)))


def test_joins_behind_a_last_mile_are_refused():
    cfg = dict(CFG, last_mile=load(
        "benchmark/configs/relay-16x256-lossy.json")["last_mile"])
    with pytest.raises(loadgen.LoadgenError):
        generator(cfg, JOINS)


# ------------------------------------------- the reference and controls
@pytest.fixture(scope="module")
def stream():
    """Source 0 of ``relay-1x64`` for 120 frames, a keyframe every 30 from
    frame 0; 100 frames pushed; a joiner's PLAY after frame 75's push."""
    src = loadgen.Source(0, SEED, 120, 30.0, CFG["stream"], first_gop_at=30)
    src.pushed = src.frame_start[100]
    pushed = src.packets[:src.pushed]
    src.pushed = src.frame_start[76]
    key_head = src.newest_key_head()
    src.pushed = len(pushed)
    return src, pushed, key_head


def start_of(src, got, first_seq):
    return src.start_of(got, first_seq)


def test_the_newest_keyframe_run_at_a_play(stream):
    src, pushed, key_head = stream
    assert src.key_frames == [0, 30, 60, 90]
    assert key_head == src.frame_start[60] == src.key_heads[2]
    # the run is SPS, PPS, IDR: the head is the SPS
    assert pushed[key_head][12] & 0x1F == 7
    assert pushed[key_head + 1][12] & 0x1F == 8


@pytest.mark.parametrize("at_frame", [60, 90])
def test_a_start_at_the_newest_run_or_a_later_one_is_clean(stream,
                                                           at_frame):
    src, pushed, key_head = stream
    start = src.frame_start[at_frame]
    got = reference.reference_flow(pushed[start:], 4242, 0xABCD, 99)
    assert start_of(src, got, 4242) == start
    assert reference.judge_joiner(got, pushed, src.key_heads, key_head,
                                  4242, 0xABCD, start) == dict.fromkeys(
        ("mid_gop", "stale", "missing", "out_of_order", "altered",
         "unannounced"), 0)


@pytest.mark.parametrize("control, fault", [
    ("join_mid_gop", "mid_gop"), ("join_stale", "stale"),
    ("join_every_packet", "missing")])
@pytest.mark.parametrize("seed", [3, 4500000011, 2**31 + 45])
def test_each_control_is_exactly_one_fault(stream, control, fault, seed):
    src, pushed, key_head = stream
    got = reference.join_control(pushed, src.key_heads, key_head, control,
                                 777, 0x1234, seed)
    counts = reference.judge_joiner(got, pushed, src.key_heads, key_head,
                                    777, 0x1234, start_of(src, got, 777))
    assert counts == dict(dict.fromkeys(counts, 0), **{fault: 1})


def test_a_keyframe_the_server_may_not_hold_yet_is_not_owed(stream):
    src = stream[0]
    assert src.newest_key_head() == src.frame_start[90]
    src.unsure.add(src.frame_start[90])
    try:
        assert src.newest_key_head() == src.frame_start[60]
    finally:
        src.unsure.clear()


class FakeWriter:
    """A pusher's stream writer: takes bytes, and keeps ``buffered`` of
    them in its transport."""

    def __init__(self, buffered=0):
        self.transport = self
        self.buffered = buffered

    def write(self, data):
        pass

    def get_write_buffer_size(self):
        return self.buffered

    async def drain(self):
        pass


@pytest.mark.parametrize("behind_s, buffered, unsure", [
    (0.0, 0, False), (0.5, 0, True), (0.0, 1400, True)],
    ids=["on_time", "late", "kept_in_the_transport"])
def test_a_push_the_server_may_not_hold_is_noted(behind_s, buffered, unsure):
    """A frame that leaves more than a frame period after it was due, or
    whose bytes the transport keeps, is no keyframe a joiner is owed."""
    lg = generator(CFG, LIVE)
    src = lg.sources[0]
    src.conn = type("Conn", (), {"writer": FakeWriter(buffered)})()
    t0 = time.perf_counter_ns() - int(behind_s * 1e9)
    asyncio.run(lg.push_frames(0, lambda due: due < 0.1, t0))
    heads = {src.frame_start[f] for _, _, f in lg.frame_plan(
        0, lambda due: due < 0.1)}
    assert heads and (src.unsure == heads if unsure else not src.unsure)


def test_a_joiner_that_read_nothing_is_missing_all_it_was_owed(stream):
    src, pushed, key_head = stream
    counts = reference.judge_joiner([], pushed, src.key_heads, key_head,
                                    5, 6, None)
    assert counts["missing"] == len(pushed) - key_head
    assert counts["mid_gop"] == counts["stale"] == 0


def test_judge_flow_from_a_start_is_judge_flow_of_the_rest(stream):
    src, pushed, key_head = stream
    got = reference.reference_flow(pushed[key_head:], 9, 10, 11)
    del got[5]
    assert reference.judge_flow(got, pushed, 9, 10, start=key_head) == \
        reference.judge_flow(got, pushed[key_head:], 9, 10)


# ------------------------------------------------------------ RTP/JPEG
MJPEG = {"codec": "MJPEG, RTP/JPEG (RFC 2435), 90 kHz", "published_fps": 30,
         "rtp_payload_bytes": 1400, "frame_bytes": [9000, 12000],
         "width": 1280, "height": 720}


def test_rtp_jpeg_packets_carry_rfc_2435s_main_header():
    src = loadgen.Source(1, SEED, 10, 30.0, MJPEG)
    assert src.mjpeg and src.sdp == loadgen.SDP_JPEG
    assert "RTP/AVP 26" in src.sdp and "JPEG/90000" in src.sdp
    assert src.key_frames == list(range(10))
    for f in range(10):
        frame = src.packets[src.frame_start[f]:src.frame_start[f + 1]]
        offsets = [int.from_bytes(p[13:16], "big") for p in frame]
        sizes = [len(p) - 20 for p in frame]
        assert offsets == [sum(sizes[:i]) for i in range(len(frame))]
        assert 9000 <= sum(sizes) < 12000
        for i, p in enumerate(frame):
            assert p[1] & 0x7F == 26
            assert bool(p[1] & 0x80) is (i == len(frame) - 1)
            assert p[12] == 0 and p[16] == 1 and p[17] < 128
            assert (p[18] * 8, p[19] * 8) == (1280, 720)
            assert len(p) <= 12 + 1400
    assert src.key_heads == src.frame_start[:10]


# ----------------------------------------------------- runs on the CPU
#: a cell of the committed configuration, at 1 source x 8 players, with
#: 8 joiners in 2 s to 8 s of a 10 s window; or 2 RTP/JPEG sources
SMALL = {"source": 0, "count": 8, "burst_size": [1, 4],
         "within_s": [0.5, 8]}


def small_run(control=None, mjpeg=False, seed=2**31 + 4511):
    """``run.py`` on a cell made in memory (the committed benchmark, plus
    one cell whose files are not on disk), on the CPU."""
    bench = copy.deepcopy(file_checks.BENCH)
    cfg = copy.deepcopy(CFG)
    traffic = dict(JOINS, joins=SMALL)
    name = "x-joins"
    if mjpeg:
        # the relay starts each player of an RTP/JPEG source where its
        # first step finds the newest frame, read off the player's stamps
        cfg["stream"] = MJPEG
        cfg["players"]["stamped_every"] = 1
        traffic.pop("warm_idr_bytes")
    files = {f"benchmark/configs/{name}.json": dict(cfg, name=name),
             f"benchmark/traffic/{name}.json": traffic}
    bench["configs"].append({"name": name, "source": "a test",
                             "file": f"benchmark/configs/{name}.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "x.joins", "config": name,
                               "traffic": name, "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if file_checks.JOINS in m.get("workloads", ["x.joins"]):
            m.setdefault("workloads", []).append("x.joins")
    files["BENCHMARK.json"] = bench
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from benchmark import run\n"
        f"files = json.loads({json.dumps(json.dumps(files))})\n"
        "committed = run.load_json\n"
        "run.load_json = lambda p: files[p] if p in files else committed(p)\n"
        "sys.exit(run.main())\n")
    argv = ["--workload", "x.joins", "--seed", str(seed), "--seconds", "10",
            "--trace", "0", "--debug-size", "2x8" if mjpeg else "1x8"]
    if control:
        argv += ["--control", control]
    r = subprocess.run([sys.executable, "-c", code, *argv],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    tail = r.stdout[-4000:] + r.stderr[-2000:]
    assert r.returncode == 0, tail
    return json.loads(r.stdout.strip().splitlines()[-1]), tail


JOINED = ("joined_mid_gop", "joined_stale", "joiner_missing",
          "joiner_out_of_order", "joiner_altered")


@pytest.mark.parametrize("mjpeg", [False, True], ids=["h264", "mjpeg"])
def test_a_small_run_with_joiners_is_correct(mjpeg):
    """Every joiner starts at a keyframe run no older than the newest at
    its PLAY and holds every packet from there; the metric is read."""
    last, tail = small_run(mjpeg=mjpeg)
    assert last["correct"] is True and last["failed"] == 0, tail
    assert set(JOINED) <= set(last["compared"]), tail
    assert all(v["value"] == 0 for v in last["compared"].values()), tail
    got = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(got) == {"delay_p60_ms", "join_first_idr_p90_ms",
                        "setup_s"}, tail
    assert got["join_first_idr_p90_ms"] > 0, tail
    assert "8 of 8 joiners showed a picture" in tail


@pytest.mark.parametrize("control", reference.JOIN_CONTROLS)
def test_a_joiners_control_comes_out_not_correct_by_one(control):
    last, tail = small_run(control="ref:" + control)
    assert last["correct"] is False, tail
    over = {k: v["value"] for k, v in last["compared"].items()
            if v["value"] > v["limit"]}
    fault = {"join_mid_gop": "joined_mid_gop", "join_stale": "joined_stale",
             "join_every_packet": "joiner_missing"}[control]
    assert over == {fault: 1}, tail


# ----------------------------------------------------- the stamped readers
def test_batch_recv_reads_what_is_queued_in_order():
    rx = loadgen.BatchRecv(n=4)
    a = loadgen.udp_socket("127.0.0.1")
    tx = loadgen.udp_socket("127.0.0.1")
    try:
        sent = [bytes([k]) * (12 + k) for k in range(6)]
        for d in sent:
            tx.sendto(d, a.getsockname())
        got = []
        while len(got) < 6:
            got += rx.recv(a.fileno())
        assert got == sent and rx.recv(a.fileno()) == []
    finally:
        a.close()
        tx.close()


def test_stamp_reader_processes_bring_every_datagram_home():
    """Three stamped flows read by two forked workers: the counts and
    each flow's first datagram are seen while they run, and every
    datagram comes home in order with a stamp of the instant it was
    read."""
    src = loadgen.Source(0, SEED, 40, 30.0, CFG["stream"])
    flows = [loadgen.Flow(src, "127.0.0.1") for _ in range(3)]
    reader = loadgen.StampReader(flows, 2)
    tx = loadgen.udp_socket("127.0.0.1")
    try:
        reader.start()
        t0 = time.perf_counter_ns()
        for n, f in enumerate(flows):
            for p in src.packets[n:n + 20]:
                tx.sendto(p, ("127.0.0.1", f.port))
        deadline = time.monotonic() + 30
        while reader.read() < 60 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert reader.read() == 60
        for n, f in enumerate(flows):
            f.first_seq = int.from_bytes(src.packets[n][2:4], "big")
            assert reader.first(n) == (n, f.first_seq)
        # a fast start's place, before the datagrams are home
        flows[2].fast_start = True
        assert flows[2].start() == 2
    finally:
        reader.stop()
        for s in [tx] + [s for f in flows for s in (f.rtp, f.rtcp)]:
            s.close()
    for n, f in enumerate(flows):
        assert f.packets == src.packets[n:n + 20]
        assert len(f.stamps) == 20 and f.stamps == sorted(f.stamps)
        assert t0 <= f.stamps[0] <= time.perf_counter_ns()
    assert reader.pids == []
