"""The live cell as PR 41 left it: its 95th percentile read per layer
(``delay_p95_ms.live`` and the twins, file_checks.py ``check_live_*``),
and the generator's ``warm_idr_bytes`` key with the ``live`` mix that
states it: the warm-up's last frames carry IDRs of the stated sizes, one every
six frames, pushed once the server has sent all it owes for the frames
before them, so that an IDR's append to the device ring meets either
pad (an IDR is 30-34 packets, astride 32) before the window opens.
Without the key the bytes and the plan are the parent's (pinned from its
code at b2195c9 for one seed); sizes outside the stream's ``idr_bytes``,
or a tail the warm-up cannot hold, are refused before a socket is opened.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import hashlib

import pytest

import file_checks
from file_checks import load

from benchmark import loadgen

SEED, SECONDS = 4100000007, 4.0
CFG = load("benchmark/configs/relay-1x64.json")
LIVE = load("benchmark/traffic/live.json")
#: the parent's ``live`` mix at SEED, 4 s: sha256 over every packet and
#: their count; sha256 of repr(warm-up plan + window plan) and its length
PARENT = ("8af152369611667ec746ac4fb7564a32868e43f732df7ba1400d137ec56ebdb3",
          2115,
          "7ef0cf7a639e869791c25ece1358fce7a4d427577d7a2c51200267dbe18e7c16",
          180)


def generator(traffic, seconds=SECONDS):
    """A generator for its plan and its sources alone: no socket."""
    with pytest.MonkeyPatch.context() as mp:
        for name in ("BulkDrains", "Flow", "StampReader"):
            mp.setattr(loadgen, name, lambda *a, **kw: None)
        return loadgen.Loadgen(CFG, traffic, SEED, seconds, CFG["sources"],
                               CFG["players"]["per_source"])


def frame_packets(src, f):
    return src.packets[src.frame_start[f]:src.frame_start[f + 1]]


def is_idr(packets):
    """The frame opens with an SPS (NAL type 7), as every IDR here does."""
    return packets[0][12] & 0x1F == 7


def pad(n):
    """The device ring's append pad for ``n`` new packets: the next power
    of two, at least 16."""
    p = 16
    while p < n:
        p *= 2
    return p


def digests(lg):
    h = hashlib.sha256()
    for s in lg.sources:
        for p in s.packets:
            h.update(p)
    W = lg.warm_frames
    plan = (lg.frame_plan(0, lambda due: due < W / lg.fps)
            + lg.frame_plan(W, lambda due: due < SECONDS))
    return (h.hexdigest(), sum(len(s.packets) for s in lg.sources),
            hashlib.sha256(repr(plan).encode()).hexdigest(), len(plan))


def test_without_the_key_the_mix_is_the_parents():
    traffic = {k: v for k, v in LIVE.items() if k != "warm_idr_bytes"}
    lg = generator(traffic)
    assert lg.warm_tail == 0
    assert digests(lg) == PARENT


def test_the_live_mix_states_a_tail_of_idrs_astride_the_pad():
    lg = generator(LIVE)
    W, src = lg.warm_frames, lg.sources[0]
    sizes = LIVE["warm_idr_bytes"]
    assert lg.warm_tail == 6 * len(sizes) == 24
    tail = range(W - lg.warm_tail, W)
    idrs = [f for f in tail if is_idr(frame_packets(src, f))]
    assert idrs == [36, 42, 48, 54]
    counts = [len(frame_packets(src, f)) for f in idrs]
    assert counts == [30, 34, 30, 34]
    assert {pad(n) for n in counts} == {32, 64}
    # the IDR carries the stated size: FU-A fragments of 1,398 bytes
    for f, size in zip(idrs, sizes):
        body = frame_packets(src, f)[2:]
        assert sum(len(p) - 14 for p in body) == size
    # the window's IDRs stand where the GOP puts them, seeded, in range
    window = [f for f in range(W, len(src.frame_start) - 1)
              if is_idr(frame_packets(src, f))]
    gop = CFG["stream"]["gop_frames"]
    assert window and all((f - W) % gop == 0 for f in window)
    assert all(30 <= len(frame_packets(src, f)) <= 34 for f in window)


def test_the_tail_leaves_everything_else_as_the_seed_drew_it():
    """Frames outside the tail's IDRs are P frames or the opening IDR, as
    without the key; only the draws that follow an IDR move."""
    lg, plain = generator(LIVE), generator(
        {k: v for k, v in LIVE.items() if k != "warm_idr_bytes"})
    a, b = lg.sources[0], plain.sources[0]
    W = lg.warm_frames
    assert frame_packets(a, 0) == frame_packets(b, 0)
    assert [f for f in range(W) if is_idr(frame_packets(a, f))] == [
        0, 36, 42, 48, 54]
    assert [f for f in range(W) if is_idr(frame_packets(b, f))] == [0]
    assert digests(lg)[3] == PARENT[3]      # the same frames, pushed alike


@pytest.mark.parametrize("change", [
    {"warm_idr_bytes": [37999]}, {"warm_idr_bytes": [44001]},
    {"warm_idr_bytes": [38000] * 10},
    {"warm_idr_bytes": [38000, 44000], "warm_frames": 12}],
    ids=["under", "over", "longer_than_the_warm_up", "short_warm_up"])
def test_a_tail_that_cannot_stand_is_refused_before_a_socket(monkeypatch,
                                                             change):
    def no_socket(*a, **kw):
        raise AssertionError("a socket was asked for")
    monkeypatch.setattr(loadgen, "udp_socket", no_socket)
    with pytest.raises(loadgen.LoadgenError, match="warm_idr_bytes"):
        loadgen.Loadgen(CFG, dict(LIVE, **change), SEED, SECONDS,
                        CFG["sources"], CFG["players"]["per_source"])


@pytest.mark.parametrize("check", file_checks.params("live"))
def test_the_cell_in_the_benchmark(check):
    """Judged on ``delay_p60_ms`` and ``setup_s``; ``delay_p95_ms.live``
    and seventeen twins, in order, each reading what its namesake reads."""
    check()
