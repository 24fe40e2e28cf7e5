"""The send pipeline (ISSUE 38): a wake's UDP sends are jobs of ONE native
sender thread, begun in roster order and settled when their results are
in — the same datagrams, bytes and accounting in another order of events.

(a) the twin: a pipelined wake against ``step`` one stream at a time,
(b) the barrier, (c) the per-stream guard in either half, (d) the job's
own errno, (e) ``wire_ns`` is the job's done stamp, (f) the sender's
life, (g) the counters and what ``pump.wake`` carries.
"""

import errno
import random
import socket
import time
import types

import numpy as np
import pytest

from easydarwin_tpu import native, obs
from easydarwin_tpu.obs import TRACER
from easydarwin_tpu.relay import pump
from easydarwin_tpu.relay.fanout import TpuFanoutEngine

from test_engine_plan import (SockOut, _drain, _rx_socket, _stream, _Twin,
                              needs_native, vid_pkt)

N_STREAMS = 16


def _tx() -> socket.socket:
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    tx.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)
    return tx


def _cohorts(eng, st, t):
    """The cached cohort tables, in a form two engines' compare by."""
    return eng.plan(st, t).tables()[3:6]


def _step_all(entries, t):
    return pump._step(entries, t, None, None, "", False)


# ------------------------------------------------------------ (a) the twin
@needs_native
def test_pipelined_wake_equals_step_one_stream_at_a_time():
    """Sixteen streams x ~24 outputs, 300 wakes of joins, leaves, a
    thinning flip, EAGAIN every n-th send call and one hard-failing
    destination, stepped by ``pump._step`` (begin all, settle as the
    sender delivers) and, on a twin, by ``engine.step`` one stream at a
    time: after every wake the wire bytes per flow in order, every
    output's bookmark / packets_sent / bytes_sent / payload_octets /
    stalls, the streams' stall counts and the cohort tables are equal.

    The fault knob counts send CALLS across both sides, so it is armed
    for one side at a time with the schedule restarted: both see the
    same refusals at the same ops.  (The bad destination joins after
    that stretch: its remainder retry is a second job submitted at
    settle, behind the later streams' first jobs, so under a schedule
    that counts calls the two sides' refusals would fall on different
    ops — the order of CALLS across streams is what the pipeline
    changes; a stream's own order it keeps.)"""
    tx = _tx()
    pairs = [(_Twin(tx, 300 + k), _Twin(tx, 300 + k))
             for k in range(N_STREAMS)]
    eng_a = [TpuFanoutEngine(egress_fd=tx.fileno()) for _ in pairs]
    eng_b = [TpuFanoutEngine(egress_fd=tx.fileno()) for _ in pairs]
    rng = random.Random(38)
    for a, b in pairs:
        for _ in range(20):
            a.join()
            b.join()
    entries = [(f"/live/s{k}", a.st, eng_a[k], pump.DEVICE)
               for k, (a, _b) in enumerate(pairs)]
    t, seq = 1000, 0
    jobs = refused_wakes = 0
    try:
        for wake in range(300):
            for k, (a, b) in enumerate(pairs):
                if (wake + k) % 9 == 3 and len(a.outs) < 28:
                    a.join()
                    b.join()
                if (wake + k) % 13 == 5 and len(a.outs) > 16:
                    i = rng.randrange(len(a.outs))
                    if a.outs[i] is not getattr(a, "bad_out", None):
                        a.leave(i)
                        b.leave(i)
                if wake in (60, 75) and k == 2:         # thinning flip
                    for tw in (a, b):
                        tw.outs[5].thinning.controller.level = \
                            1 if wake == 60 else 0
                if wake == 200 and k == 7:              # bad destination
                    for tw in (a, b):
                        tw.bad_out = tw.join(bad=True, bucket=4)
                for _ in range(rng.choice((0, 0, 2, 3))):
                    p = vid_pkt(seq, seq * 90, key=seq % 25 == 0)
                    a.st.push_rtp(p, t)
                    b.st.push_rtp(p, t)
                    seq += 1
                if wake % 10 == 9:
                    assert a.st.prune(t) == b.st.prune(t)
            # EAGAIN every 5th send call, a stretch of wakes
            refuse = 150 <= wake < 190
            refused_wakes += refuse
            j0 = native.sender_stats()["jobs"]
            if refuse:
                native.fault_set(5, 0, 0, 0)
            try:
                sent_a, _w, tally = _step_all(entries, t)
            finally:
                native.fault_clear()
            jobs += tally[0]
            assert native.sender_stats()["jobs"] - j0 == tally[0]
            if refuse:
                native.fault_set(5, 0, 0, 0)
            try:
                sent_b = sum(eng_b[k].step(b.st, t)
                             for k, (_a, b) in enumerate(pairs))
            finally:
                native.fault_clear()
            assert sent_a == sent_b, wake
            for k, (a, b) in enumerate(pairs):
                a.drain()
                b.drain()
                assert a.state() == b.state(), (wake, k)
                assert [o.stalls for o in a.outs] == \
                    [o.stalls for o in b.outs], (wake, k)
                assert a.st.stats.stalls == b.st.stats.stalls
                assert a.st.stats.packets_out == b.st.stats.packets_out
                for oa in a.outs:
                    assert a.got[oa.sid] == b.got[oa.sid], (wake, k, oa.sid)
                assert _cohorts(eng_a[k], a.st, t) == \
                    _cohorts(eng_b[k], b.st, t), (wake, k)
                # what needs_step reads: a stalled stream is retried
                if a.st._last_pass_stalled:
                    assert a.st._plan_cell.retry, (wake, k)
            t += 20
        assert jobs > 1000 and refused_wakes == 40
        assert sum(a.st.stats.stalls for a, _b in pairs) > 50
        assert sum(len(g) for a, _b in pairs for g in a.got.values()) > 20000
        bad_a, bad_b = pairs[7][0].bad_out, pairs[7][1].bad_out
        assert bad_a.packets_sent == bad_b.packets_sent == 0
        assert eng_a[7].send_errors == eng_b[7].send_errors > 0
        # never two sends at one instant, on any thread
        assert native.sender_stats()["max_in_flight"] == 1
    finally:
        native.fault_clear()
        for a, b in pairs:
            a.close()
            b.close()
        tx.close()


# ------------------------------------------------------------ the fixtures
class _World:
    """``n`` streams of ``n_out`` outputs on real sockets, one engine
    each, and the entries ``pump._step`` takes."""

    def __init__(self, n: int = N_STREAMS, n_out: int = 8, delay_ms=30):
        self.tx = _tx()
        self.rx = _rx_socket()
        self.streams, self.engines = [], []
        for k in range(n):
            st = _stream(delay_ms=delay_ms)
            st.session_path = f"/live/s{k}"
            for i in range(n_out):
                st.add_output(SockOut(self.tx, self.rx.getsockname(),
                                      ssrc=k * 1000 + i))
            self.streams.append(st)
            self.engines.append(TpuFanoutEngine(egress_fd=self.tx.fileno()))
        self.entries = [(st.session_path, st, eng, pump.DEVICE)
                        for st, eng in zip(self.streams, self.engines)]
        self.seq = 0

    def push(self, t: int, n: int = 3, only=None) -> None:
        for k, st in enumerate(self.streams):
            if only is None or k in only:
                for _ in range(n):
                    st.push_rtp(vid_pkt(self.seq, self.seq * 90), t)
                    self.seq += 1

    def close(self) -> None:
        self.rx.close()
        self.tx.close()


@pytest.fixture
def world():
    w = _World()
    yield w
    native.fault_clear()
    w.close()


# ------------------------------------------------------------ (b) barrier
@needs_native
def test_a_wake_returns_with_no_job_in_flight(world):
    """Write over every ring slot the wake sent from, the instant it
    returns: the wire carries what was pushed, not what was written
    after (a job still in flight would read the slot late)."""
    w = world
    t = 1000
    for wake in range(12):
        w.push(t, n=4)
        sent, _w, (jobs, send_ns, hidden_ns) = _step_all(w.entries, t + 200)
        for st in w.streams:                    # the vandal
            st.rtp_ring.data[:, 12:] = 0xEE
        assert sent == N_STREAMS * 8 * 4 and jobs == N_STREAMS
        assert 0 <= hidden_ns <= send_ns
        t += 20
    got = _drain(w.rx)
    assert len(got) == 12 * N_STREAMS * 8 * 4
    assert not any(b"\xee\xee\xee\xee" in g for g in got)
    st = native.sender_stats()
    assert st["running"] == 1 and st["max_in_flight"] == 1


@needs_native
def test_a_base_exception_in_the_last_settle_still_drains_the_sender(world):
    """The last entry is popped before it is finished: a KeyboardInterrupt
    in its settle, its job still with the sender (every send call sleeps
    5 ms first), leaves nothing pending — the barrier holds all the
    same."""
    w = world
    w.push(1000, n=2)
    native.fault_set(0, 0, 1, 5000)
    eng, met = w.engines[-1], []

    def interrupted(ps):
        met.append((ps.udp.job, ps.udp.job.done))
        raise KeyboardInterrupt

    eng._udp_settle = interrupted
    with pytest.raises(KeyboardInterrupt):
        _step_all(w.entries, 1200)
    (job, was_done), = met
    assert job.done                 # …by the time ``_step`` let go
    assert not was_done             # (and it was in flight at the raise)
    assert job.result == job.n_ops == 8 * 2


@needs_native
def test_a_submit_that_meets_a_stop_is_sent_by_another_thread(world):
    """``sender_stop`` from another thread at any instant of a wake: a
    submit in the gap between the stop's flag and its join waits the
    stop out and starts a thread of its own; no wake hangs on a queue
    nobody reads, none loses a datagram."""
    import threading
    w = world
    halt = threading.Event()

    def stopper():
        while not halt.is_set():
            native.sender_stop()

    def wakes():
        t = 1000
        for _ in range(150):
            w.push(t, n=1)
            assert _step_all(w.entries, t + 200)[0] == N_STREAMS * 8
            got.append(len(_drain(w.rx)))
            t += 20
        done.append(True)

    done: list = []
    got: list = []
    starts0 = native.sender_stats()["starts"]
    th = [threading.Thread(target=f, daemon=True) for f in (stopper, wakes)]
    for x in th:
        x.start()
    th[1].join(120)
    halt.set()
    th[0].join(10)
    assert done == [True], "a wake hung on the sender"
    st = native.sender_stats()
    assert st["starts"] > starts0 + 1 and st["max_in_flight"] == 1
    assert got == [N_STREAMS * 8] * 150


# ---------------------------------------------- (c) the per-stream guard
@needs_native
@pytest.mark.parametrize("half", ["plan", "settle"])
def test_one_streams_exception_leaves_the_others_sent_and_settled(world,
                                                                  half):
    w = world
    w.push(1000, n=3)
    eng = w.engines[5]
    if half == "plan":
        eng._device_params = lambda *a, **kw: 1 / 0
    else:
        eng._udp_settle = lambda ps: 1 / 0
    errors, oks = [], []
    ladder = types.SimpleNamespace(note_device_ok=oks.append,
                                   note_device_error=errors.append)
    log = types.SimpleNamespace(warning=lambda msg: None)
    sent, _w, (jobs, _s, _h) = pump._step(w.entries, 1200, ladder, log, "",
                                          False)
    assert sent == 15 * 8 * 3
    assert errors == ["/live/s5"] and len(oks) == 15
    # a plan that raised submitted nothing; a settle that raised had its
    # job sent all the same (the barrier does not depend on the settle)
    assert native.sender_stats()["running"] == 1
    for k, st in enumerate(w.streams):
        c = st._plan_cell
        assert c.retry == (k == 5), k
        want = 0 if k == 5 else 3
        assert all(o.packets_sent == want for b in st.buckets for o in b), k
    got = len(_drain(w.rx))
    assert got == (15 if half == "plan" else 16) * 8 * 3


# ---------------------------------------------------- (d) the job's errno
@needs_native
def test_the_results_errno_is_the_jobs_own(world):
    """EAGAIN met on the sender thread travels in the job's result: the
    loop thread's ``last_send_errno()`` reads 0, and a partial send under
    flow control is a stall (bookmarks kept), not a hard error."""
    w = world
    st, eng = w.streams[0], w.engines[0]
    eng.egress_backend = "scalar"               # one send call a datagram
    for _ in range(4):
        st.push_rtp(vid_pkt(w.seq, w.seq * 90), 1000)
        w.seq += 1
    native.fault_set(11, 0, 0, 0)               # the 11th datagram: EAGAIN
    ps = eng.begin(st, 1200)
    job = ps.udp.job.wait()
    native.fault_clear()
    assert (job.result, job.err) == (10, errno.EAGAIN)
    assert native.last_send_errno() == 0        # this thread sent nothing
    assert eng.finish(ps) == 10
    assert eng.send_errors == 0 and st.stats.stalls == 6
    outs = [o for b in st.buckets for o in b]
    assert [o.packets_sent for o in outs] == [4, 4, 2, 0, 0, 0, 0, 0]
    assert [o.stalls for o in outs] == [0, 0, 1, 1, 1, 1, 1, 1]
    # the replay delivers the rest, once
    assert eng.step(st, 1220) == 22
    assert len(_drain(w.rx)) == 32
    # and a hard errno is read as hard from the same place
    native.fault_set(0, 3, 0, 0)                # the 3rd datagram: ENOBUFS
    st.push_rtp(vid_pkt(w.seq, w.seq * 90), 1230)
    assert eng.step(st, 1400) == 2
    native.fault_clear()
    assert eng.send_errors == 1                 # that output's, skipped
    assert eng.step(st, 1420) == 5              # the ones behind it: replayed


# ------------------------------------------------------- (e) the wire stamp
@needs_native
def test_wire_ns_is_the_jobs_done_stamp_not_the_settles_clock(world):
    """Settle 50 ms after the send is done: ingest→wire reads the send's
    own instant."""
    w = world
    st, eng = w.streams[0], w.engines[0]
    t_push = time.perf_counter_ns()
    st.push_rtp(vid_pkt(0, 0), 1000)
    seen = []
    real = obs.profile.observe_wire
    obs.profile.observe_wire = lambda engine, lat_s, *a, **kw: seen.append(
        np.array(lat_s))
    import easydarwin_tpu.relay.fanout as fanout_mod
    fanout_mod.obs.observe_wire, keep = obs.profile.observe_wire, \
        fanout_mod.obs.observe_wire
    try:
        ps = eng.begin(st, 1200)
        job = ps.udp.job.wait()
        time.sleep(0.05)
        t_settle = time.perf_counter_ns()
        assert eng.finish(ps) == 8
    finally:
        obs.profile.observe_wire = real
        fanout_mod.obs.observe_wire = keep
    # the stamps are on perf_counter_ns's clock, in order
    assert t_push < job.submit_ns <= job.start_ns <= job.done_ns < t_settle
    assert t_settle - job.done_ns >= 45_000_000
    (lat_s,) = seen
    assert lat_s.max() * 1e9 <= job.done_ns - t_push
    assert lat_s.max() * 1e9 <= t_settle - t_push - 45_000_000


# ------------------------------------------------------ (f) the sender's life
@needs_native
async def test_the_sender_starts_once_and_stops_with_the_server(world):
    from test_spans import _Rx, _server, app_now
    w = world
    native.sender_stop()
    s0 = native.sender_stats()
    assert s0["running"] == 0
    w.push(1000)
    _step_all(w.entries, 1200)
    native.fault_set(3, 0, 0, 0)
    native.fault_clear()                        # survives the knobs
    w.push(1220)
    _step_all(w.entries, 1400)
    s1 = native.sender_stats()
    assert s1["starts"] == s0["starts"] + 1 and s1["running"] == 1
    assert s1["jobs"] == s0["jobs"] + 2 * N_STREAMS
    # a server's wakes use the same one thread, and its stop ends it
    rx = _Rx()
    app, streams = _server(rx)
    try:
        for seq in range(4):
            for st in streams:
                st.push_rtp(vid_pkt(seq, seq * 3000), app_now())
        assert app._reflect_all() > 0
        app._wake_close()
        s2 = native.sender_stats()
        assert s2["starts"] == s1["starts"] and s2["jobs"] > s1["jobs"]
        await app.stop()
        assert native.sender_stats()["running"] == 0
    finally:
        rx.close()
    # first use after a stop starts another, never a second beside one
    w.push(1420)
    _step_all(w.entries, 1600)
    s3 = native.sender_stats()
    assert s3["starts"] == s1["starts"] + 1 and s3["running"] == 1
    assert s3["max_in_flight"] == 1


# ------------------------------------------- (g) counters, and pump.wake
@needs_native
def test_hidden_is_at_most_send_and_the_wake_carries_its_jobs(world):
    w = world
    fam = obs.EGRESS_PIPELINE_SECONDS
    send0, hid0 = fam.value(part="send"), fam.value(part="hidden")
    jobs0 = obs.EGRESS_PIPELINE_JOBS.value()
    phase = obs.REGISTRY.get("relay_phase_seconds")._state(
        dict(engine="native", phase="egress_native"))
    phase0 = phase.sum
    total_send = total_hidden = 0
    for wake in range(6):
        w.push(1000 + 20 * wake, n=5)
        _s, _w, (jobs, send_ns, hidden_ns) = _step_all(w.entries,
                                                       1300 + 20 * wake)
        assert jobs == N_STREAMS and 0 <= hidden_ns <= send_ns > 0
        total_send += send_ns
        total_hidden += hidden_ns
    assert obs.EGRESS_PIPELINE_JOBS.value() - jobs0 == 6 * N_STREAMS
    assert fam.value(part="send") - send0 == pytest.approx(total_send / 1e9)
    assert fam.value(part="hidden") - hid0 == pytest.approx(
        total_hidden / 1e9)
    # the egress_native phase goes on holding the op-list build AND the
    # sending: the bracket's seconds plus every job's own
    assert phase.sum - phase0 > total_send / 1e9
    # an idle wake counts nothing
    _s, _w, tally = _step_all(w.entries, 1500)
    assert tally == (0, 0, 0)
    assert obs.EGRESS_PIPELINE_JOBS.value() - jobs0 == 6 * N_STREAMS
    # a step alone hides nothing it did not overlap: one job, waited at once
    st, eng = w.streams[0], w.engines[0]
    st.push_rtp(vid_pkt(1, 90), 1600)
    ps = eng.begin(st, 1800)
    assert eng.finish(ps) == 8
    assert ps.jobs == 1 and ps.hidden_ns + ps.wait_ns >= ps.send_ns


@needs_native
async def test_pump_wake_span_carries_jobs_and_the_ring_has_native_egress():
    from test_spans import _Rx, _server, app_now
    rx = _Rx()
    app, streams = _server(rx)
    TRACER.clear()
    TRACER.wake = None
    try:
        for seq in range(4):
            for st in streams:
                st.push_rtp(vid_pkt(seq, seq * 3000), app_now())
        app._reflect_all()
        app._wake_close()
    finally:
        await app.stop()
        rx.close()
    recs = {}
    for name, _cat, t0, dur, _tid, args in TRACER.records():
        recs.setdefault(name, []).append((t0, dur, args))
    (wake,) = recs["pump.wake"]
    assert wake[2]["jobs"] == len(streams) == len(recs["native.egress"])
    assert wake[2]["hidden_us"] <= wake[2]["send_us"]
    for t0, dur, args in recs["native.egress"]:
        # the sender's own interval: inside the wake, after its step
        assert wake[0] <= t0 and t0 + dur <= wake[0] + wake[1]
        assert args["wake"] == wake[2]["wake"] and args["queued_us"] >= 0
        assert args["datagrams"] == args["sent"] == args["ops"] > 0
    assert sum(a["sent"] for _t, _d, a in recs["engine.settle"]) == \
        sum(a["datagrams"] for _t, _d, a in recs["native.egress"])
    # no two sends overlap: the jobs' intervals are disjoint, in order
    spans = sorted((t0, t0 + dur) for t0, dur, _a in recs["native.egress"])
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))
    for name in ("engine.step", "engine.egress", "engine.settle",
                 "engine.account", "engine.rtcp"):
        assert len(recs[name]) == len(streams), name
