"""The pump lets the readers run dry before a wake starts (ISSUE 31).

A test of an ORDER, not of a timing.  asyncio's ready queue is FIFO and a
stream read takes two loop iterations (the transport's read, then the
connection task it resumed), so a pump that goes from its wait straight
into a blocking wake runs at the head of every iteration once some
straggler's task has set its event: a whole wake in front of the reads
``select`` had just found, and another in front of the tasks those
resumed.  With the drain (``StreamingServer._drain_readers``) everything
that lay in a socket when a wake ended is pushed before the next begins,
and a sender that never pauses cannot hold the pump out.
"""

import asyncio
import socket
import threading
import time

import pytest

from easydarwin_tpu import obs
from easydarwin_tpu.obs import TRACER
from easydarwin_tpu.server import ServerConfig, StreamingServer

N_CONN = 64
MSG = 64                    # bytes a message; its id in the first four
WAKE_S = 0.015              # the stub wake blocks the loop this long
MARGIN_NS = 5_000_000       # a send this long before a wake's end is "in"


class _Rig:
    """An unstarted server whose real ``_pump_loop`` runs on the test's
    loop with ``_reflect_all`` replaced by a blocking stub, and
    ``N_CONN`` local TCP connections whose handlers stamp each message
    and wake the pump as ``rtsp.py``'s ``_on_interleaved`` does."""

    def __init__(self, interval_ms: int, handlers_wake: bool = True):
        self.handlers_wake = handlers_wake
        self.app = StreamingServer(ServerConfig(
            reflect_interval_ms=interval_ms, slo_enabled=False,
            access_log_enabled=False))
        self.wakes: list[tuple[int, int]] = []      # (start, end) ns
        self.handled: dict[int, int] = {}
        self.sent: dict[int, int] = {}
        self.app._reflect_all = self._wake_stub
        self.stop = threading.Event()
        #: set once every connection's handler runs: an accept takes
        #: more stages than a read, and is not what is tested
        self.joined = threading.Event()
        self._handlers = 0

    def _wake_stub(self) -> int:
        t0 = time.perf_counter_ns()
        self.app._wake_ns = None
        time.sleep(WAKE_S)
        self.wakes.append((t0, time.perf_counter_ns()))
        return 0

    async def _handle(self, reader, writer) -> None:
        self._handlers += 1
        if self._handlers == N_CONN:
            self.joined.set()
        try:
            while True:
                d = await reader.readexactly(MSG)
                self.handled[int.from_bytes(d[:4], "big")] = \
                    time.perf_counter_ns()
                self.app.rtsp.stats["packets_in"] += 1
                if self.handlers_wake:
                    self.app._wake()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()

    def _sender(self, port: int, gap_s: float) -> None:
        socks = []
        try:
            for _ in range(N_CONN):
                s = socket.create_connection(("127.0.0.1", port))
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                socks.append(s)
            pad = bytes(MSG - 4)
            mid = 0
            self.joined.wait(10)
            while not self.stop.is_set():
                socks[mid % N_CONN].sendall(mid.to_bytes(4, "big") + pad)
                self.sent[mid] = time.perf_counter_ns()
                mid += 1
                if gap_s:
                    time.sleep(gap_s)
        finally:
            for s in socks:
                s.close()

    async def run(self, n_wakes: int, gap_s: float) -> None:
        """Pump, server and sender until ``n_wakes`` wakes have run;
        everything stopped and joined on the way out."""
        srv = await asyncio.start_server(self._handle, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        TRACER.clear()
        self.app._running = True
        pump = asyncio.create_task(self.app._pump_loop())
        th = threading.Thread(target=self._sender, args=(port, gap_s),
                              daemon=True)
        th.start()
        try:
            deadline = time.monotonic() + 20
            while len(self.wakes) < n_wakes and time.monotonic() < deadline:
                await asyncio.sleep(0.05)
        finally:
            self.stop.set()
            await asyncio.get_running_loop().run_in_executor(
                None, th.join, 10)
            self.app._running = False
            self.app._wake()
            await asyncio.wait_for(pump, 10)
            srv.close()
            await srv.wait_closed()
        assert not th.is_alive()

    def waited_a_wake(self) -> list[int]:
        """Messages whose send returned ``MARGIN_NS`` before some wake k
        ended and that were not handled before wake k + 1 started."""
        late = []
        ends = [e for _, e in self.wakes]
        for mid, t_sent in self.sent.items():
            k = next((i for i, e in enumerate(ends)
                      if e - MARGIN_NS >= t_sent), None)
            if k is None or k + 1 >= len(self.wakes):
                continue            # no later wake to be in time for
            t_handled = self.handled.get(mid)
            if t_handled is None or t_handled > self.wakes[k + 1][0]:
                late.append(mid)
        return late

    def sleeps(self) -> list[dict]:
        return [rec[5] for rec in TRACER.records()
                if rec[0] == "pump.sleep"]


def _timeouts() -> float:
    return (obs.PUMP_WAKES.value(cause="timer")
            + obs.PUMP_WAKES.value(cause="interval"))


#: what ends the pump's wait: a handler's event; the event or a 1 ms
#: timeout, whichever is first (what the wheel gives the real pump); the
#: timeout alone (handlers that do not wake the pump)
CAUSES = {"event": (5000, True), "event_or_1ms": (1, True),
          "timer": (1, False)}


@pytest.mark.parametrize("cause", list(CAUSES))
async def test_what_lay_in_a_socket_is_pushed_before_the_next_wake(cause):
    """Every message whose send returned 5 ms before wake k ended is
    handled before wake k + 1 starts, whatever ended the wait.  On the
    parent's order (the control below) a wake runs in front of each of
    the batch's two stages."""
    rig = _Rig(*CAUSES[cause])
    t_out, rounds0, pk0 = (_timeouts(), obs.PUMP_DRAIN_ROUNDS.value(),
                           obs.PUMP_DRAIN_PACKETS.value())
    await rig.run(60, gap_s=0.0004)
    assert len(rig.wakes) >= 60 and len(rig.sent) >= 500
    assert rig.waited_a_wake() == []
    sleeps = rig.sleeps()
    if cause == "event":
        assert _timeouts() == t_out
    elif cause == "timer":
        # (all but the last, which the rig's own stop may have woken)
        assert _timeouts() - t_out >= len(sleeps) - 1
    # the drain's own account: a round or more a wake, its packets the
    # handlers' between the wait's return and the wake's start
    assert sleeps and all(
        1 <= s["drain_rounds"] <= rig.app._DRAIN_ROUNDS_MAX for s in sleeps)
    drained = sum(s["drain_packets"] for s in sleeps)
    assert 0 <= drained <= len(rig.handled)
    assert obs.PUMP_DRAIN_PACKETS.value() - pk0 == drained
    assert obs.PUMP_DRAIN_ROUNDS.value() - rounds0 == sum(
        s["drain_rounds"] for s in sleeps)


@pytest.mark.parametrize("cause", ["event", "event_or_1ms"])
async def test_the_parents_order_makes_a_batch_wait_a_wake(cause):
    """The control: with no round between the wait and the wake (the
    parent's ``_pump_loop``) the same traffic leaves what lay in the
    sockets a whole wake behind — the test above can fail.  (Where only
    the timeout ends the wait the parent's order is sound too: the pump
    is never at the head of the queue.)"""
    rig = _Rig(*CAUSES[cause])
    rig.app._DRAIN_ROUNDS_MAX = 0
    await rig.run(60, gap_s=0.0004)
    assert len(rig.wakes) >= 60
    assert len(rig.waited_a_wake()) >= 20
    assert all(s["drain_rounds"] == 0 for s in rig.sleeps())


async def test_a_sender_that_never_pauses_cannot_hold_the_pump_out():
    """Ingest in EVERY loop iteration (a callback that wakes the pump and
    schedules itself again, beside the sockets' traffic): every round
    finds the event set, so each drain stops at its ceiling — two
    iterations a round and the wake starts — and the readers are still
    served in their order."""
    rig = _Rig(5000)
    loop = asyncio.get_running_loop()
    iterations = [0]
    at_wake: list[tuple[int, int]] = []     # iterations at (start, end)
    stub = rig.app._reflect_all

    def flood() -> None:
        iterations[0] += 1
        rig.app._wake()
        if not rig.stop.is_set():
            loop.call_soon(flood)

    def counted_wake() -> int:
        n0 = iterations[0]
        stub()
        at_wake.append((n0, iterations[0]))
        return 0

    rig.app._reflect_all = counted_wake
    loop.call_soon(flood)
    await rig.run(30, gap_s=0.0004)
    ceiling = rig.app._DRAIN_ROUNDS_MAX
    assert len(rig.wakes) >= 30
    assert {s["drain_rounds"] for s in rig.sleeps()[:30]} == {ceiling}
    held_out = [b[0] - a[1] for a, b in zip(at_wake[:29], at_wake[1:30])]
    assert max(held_out) <= 2 * ceiling + 1, held_out
    assert rig.handled and rig.waited_a_wake() == []
