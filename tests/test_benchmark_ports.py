"""The receivers' port pairs (PR 29's scripted-port cases, as tier-1
tests: ``benchmark/tests/`` is not collected by the tier-1 command).

The kernel hands out 65535 as an ephemeral port about once in 200 runs
of a 256-flow cell; a pair that asks for its successor must draw again,
in the benchmark's ``loadgen.udp_pair`` and in ``chip_smoke.udp_pair``
alike, and give up as its own error, never an ``OverflowError``.
"""

import errno

import pytest

import chip_smoke
from benchmark import loadgen


class ScriptedPorts:
    """A ``udp_socket`` with the kernel's part scripted: ephemeral binds
    draw from ``draws`` in turn (the last one for good), a bind to a
    port in ``taken`` is EADDRINUSE, and one past 65535 is refused as
    ``socket.bind`` refuses it.  No real port is drawn."""

    class Sock:
        def __init__(self, port):
            self.port, self.closed = port, False

        def getsockname(self):
            return ("127.0.0.1", self.port)

        def close(self):
            self.closed = True

    def __init__(self, draws, taken=()):
        self.draws, self.taken = list(draws), set(taken)
        self.made = []

    def __call__(self, ip, port=0):
        if port == 0:
            port = self.draws.pop(0) if len(self.draws) > 1 else self.draws[0]
        elif port > 65535:
            raise OverflowError("bind(): port must be 0-65535.")
        elif port in self.taken:
            raise OSError(errno.EADDRINUSE, "Address already in use")
        self.made.append(self.Sock(port))
        return self.made[-1]

    def open_ports(self):
        return [s.port for s in self.made if not s.closed]


#: (module, the name of its socket maker, the error it gives up with)
PAIRS = [(loadgen, "udp_socket", loadgen.LoadgenError),
         (chip_smoke, "_udp_socket", chip_smoke.SmokeFailure)]
IDS = ["benchmark", "chip_smoke"]


@pytest.mark.parametrize("mod, maker, _err", PAIRS, ids=IDS)
@pytest.mark.parametrize("draws, taken, pair", [
    ([65535, 40000], (), (40000, 40001)),       # no successor: drawn again
    ([40000, 40002], (40001,), (40002, 40003)),     # successor taken
    ([65535, 65535, 40001], (), (40001, 40002)),    # RTP parity stays free
], ids=["top_of_range", "successor_taken", "twice_the_top"])
def test_udp_pair_draws_again(monkeypatch, mod, maker, _err, draws, taken,
                              pair):
    ports = ScriptedPorts(draws, taken)
    monkeypatch.setattr(mod, maker, ports)
    a, b = mod.udp_pair("127.0.0.1")
    assert (a.port, b.port) == pair
    # every rejected socket was closed; the pair is all that is open
    assert ports.open_ports() == list(pair)


@pytest.mark.parametrize("mod, maker, err", PAIRS, ids=IDS)
def test_udp_pair_gives_up_as_its_own_error(monkeypatch, mod, maker, err):
    ports = ScriptedPorts([65535])
    monkeypatch.setattr(mod, maker, ports)
    with pytest.raises(err):                    # and no OverflowError
        mod.udp_pair("127.0.0.1")
    assert len(ports.made) == 64 and ports.open_ports() == []


@pytest.mark.parametrize("port, fits", [
    (65535, False), (65534, True), (40000, True)])
def test_both_receiver_kinds_reject_the_same_ports(monkeypatch, port, fits):
    """``udp_pair`` and ``_open_port_group`` (which wants an even RTP
    port besides) share the one predicate."""
    assert loadgen.has_successor(port) is fits
    ports = ScriptedPorts([port, 40002])
    monkeypatch.setattr(loadgen, "udp_socket", ports)
    assert loadgen.udp_pair("127.0.0.1")[0].port == (port if fits else 40002)
    ports = ScriptedPorts([port, 40002])
    monkeypatch.setattr(loadgen, "udp_socket", ports)
    bulk = object.__new__(loadgen.BulkDrains)
    bulk.rtp, bulk.rtcp = [], []
    assert bulk._open_port_group() == (port if fits else 40002)
    assert len(bulk.rtp) == len(bulk.rtcp) == loadgen.N_IP
