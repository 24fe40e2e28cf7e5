"""Shared UDP egress pair + native scatter sender for player outputs.

The reference serves every RTP client from a *shared* UDP socket pair per
NIC (``QTSServer::SetupUDPSockets`` → ``RTPSocketPool``,
``QTSServer.cpp:668,1259-1290``), demultiplexing inbound RTCP by source
address (``UDPDemuxer.h``).  Round 1 of this build allocated one socket
pair per player instead, which made per-packet asyncio ``sendto`` the only
egress path.  This module restores the reference's shared-pair shape and
uses it as the doorway to the native batched egress: every UDP player's
packets leave through ONE unconnected socket via ``csrc``'s
sendmmsg/UDP-GSO scatter (``native.fanout_send_multi``), so the TPU
engine's affine rewrite params drive the wire directly — no per-packet
Python, no per-subscriber payload copies.

RTCP rides the event loop: one shared socket receives player receiver
reports and NACKs and demuxes them to the owning connection by source
address, exactly the UDPDemuxer role.  Each time the socket is readable
the loop reads what it holds, not one datagram (asyncio's datagram
transport reads one a turn): 4,096 viewers send ≈ 820 reports a second,
and a turn a datagram falls behind a busy pump until the socket's queue
overflows and a NACK waits seconds for its retransmission.
"""

from __future__ import annotations

import asyncio
import socket

from ..relay.output import RelayOutput, WriteResult

#: datagrams one readable turn of the RTCP socket reads at most, so that
#: a flood cannot hold the loop
RTCP_READS_PER_TURN = 256
#: the RTCP socket's receive buffer, asked of the kernel (which may cap it)
RTCP_RCVBUF_BYTES = 1 << 22


class NativeUdpOutput(RelayOutput):
    """One subscriber × one track on the shared egress pair.

    The TPU engine recognizes these by ``native_addr`` and routes their
    packets through the native scatter sender; the scalar oracle path
    still works (``send_bytes`` below) so differential tests and the
    CPU fallback see identical behavior."""

    def __init__(self, egress: "SharedUdpEgress", client_ip: str,
                 client_rtp_port: int, client_rtcp_port: int, **kw):
        super().__init__(**kw)
        self.egress = egress
        self.rtp_addr = (client_ip, client_rtp_port)
        self.rtcp_addr = (client_ip, client_rtcp_port)

    @property
    def native_addr(self) -> tuple[str, int]:
        return self.rtp_addr

    def send_bytes(self, data: bytes, *, is_rtcp: bool) -> WriteResult:
        if is_rtcp:
            return self.egress.send_rtcp(data, self.rtcp_addr)
        return self.egress.send_rtp(data, self.rtp_addr)


class SharedUdpEgress:
    """The server's shared (RTP, RTCP) UDP pair.

    * RTP: a plain non-blocking socket.  The engine's native path writes
      it with sendmmsg/GSO; the scalar path with ``sendto`` (WouldBlock
      surfaces as a bookmark replay, same contract as the reference's
      ``RTPStream::Write``).
    * RTCP: a non-blocking socket the event loop reads in batches;
      inbound receiver reports demux by source address to the registered
      connection (UDPDemuxer equivalent).
    """

    def __init__(self, bind_ip: str = "0.0.0.0"):
        self.bind_ip = bind_ip
        self.rtp_sock: socket.socket | None = None
        self.rtcp_sock: socket.socket | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self.rtp_port = 0
        self.rtcp_port = 0
        #: (ip, port) → (conn, handler) exact-address demux
        self._demux: dict[tuple[str, int], object] = {}
        #: ip → set of registered conns (fallback when the client sends
        #: RTCP from an ephemeral port, which NATs and stacks often do)
        self._by_ip: dict[str, list] = {}
        self.on_rtcp = None             # set by the server: (conn, data) -> None
        self.rtcp_in = 0
        self.send_errors = 0

    @property
    def active(self) -> bool:
        return self.rtp_sock is not None

    async def start(self) -> None:
        self.rtp_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.rtp_sock.setblocking(False)
        self.rtp_sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 22)
        self.rtp_sock.bind((self.bind_ip, 0))
        self.rtp_port = self.rtp_sock.getsockname()[1]
        self.rtcp_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.rtcp_sock.setblocking(False)
        self.rtcp_sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF,
                                  RTCP_RCVBUF_BYTES)
        self.rtcp_sock.bind((self.bind_ip, 0))
        self.rtcp_port = self.rtcp_sock.getsockname()[1]
        self._loop = asyncio.get_running_loop()
        self._loop.add_reader(self.rtcp_sock.fileno(), self._read_rtcp)

    def close(self) -> None:
        if self.rtp_sock is not None:
            self.rtp_sock.close()
            self.rtp_sock = None
        if self.rtcp_sock is not None:
            self._loop.remove_reader(self.rtcp_sock.fileno())
            self.rtcp_sock.close()
            self.rtcp_sock = None
        self._demux.clear()
        self._by_ip.clear()

    # -- registration (UDPDemuxer) ----------------------------------------
    def register(self, out: NativeUdpOutput, conn) -> None:
        prev = self._demux.get(out.rtcp_addr)
        self._demux[out.rtcp_addr] = conn
        conns = self._by_ip.setdefault(out.rtcp_addr[0], [])
        if prev is conn:
            return                  # re-SETUP of the same addr: idempotent
        if prev is not None and prev in conns:
            conns.remove(prev)      # addr re-claimed by a new connection
        conns.append(conn)

    def unregister(self, out: NativeUdpOutput, conn) -> None:
        if self._demux.get(out.rtcp_addr) is conn:
            del self._demux[out.rtcp_addr]
        conns = self._by_ip.get(out.rtcp_addr[0])
        if conns and conn in conns:
            conns.remove(conn)
            if not conns:
                del self._by_ip[out.rtcp_addr[0]]

    def _read_rtcp(self) -> None:
        """What the RTCP socket holds, up to ``RTCP_READS_PER_TURN``."""
        for _ in range(RTCP_READS_PER_TURN):
            try:
                data, addr = self.rtcp_sock.recvfrom(65536)
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                continue        # a pending ICMP error: the next datagram
            self._on_rtcp(data, addr)

    def _on_rtcp(self, data: bytes, addr) -> None:
        self.rtcp_in += 1
        conn = self._demux.get((addr[0], addr[1]))
        if conn is None:
            # ephemeral source port: fall back to ip when unambiguous
            conns = self._by_ip.get(addr[0])
            if not conns:
                return
            if len(set(map(id, conns))) == 1:
                conn = conns[0]
            else:
                # several connections behind one IP (NAT): match the RR's
                # report-block SSRCs against each candidate's output SSRCs
                # instead of dropping the feedback (ADVICE r2)
                conn = self._match_by_ssrc(conns, data)
            if conn is None:
                return
        if self.on_rtcp is not None:
            self.on_rtcp(conn, data, addr)

    @staticmethod
    def _match_by_ssrc(conns, data: bytes):
        """The connection whose outputs own an SSRC this compound reports
        on — None when zero or several match (still ambiguous)."""
        from ..protocol import rtcp as rtcp_mod
        try:
            pkts = rtcp_mod.parse_compound(data)
        except rtcp_mod.RtcpError:
            return None
        reported = {rb.ssrc for p in pkts
                    if isinstance(p, rtcp_mod.ReceiverReport)
                    for rb in p.reports}
        if not reported:
            return None
        matches = []
        for conn in conns:
            tracks = getattr(conn, "player_tracks", None) or {}
            owned = {pt.output.rewrite.ssrc for pt in tracks.values()}
            if owned & reported:
                matches.append(conn)
        return matches[0] if len(matches) == 1 else None

    # -- scalar sends ------------------------------------------------------
    def send_rtp(self, data: bytes, addr) -> WriteResult:
        if self.rtp_sock is None:
            return WriteResult.ERROR
        try:
            self.rtp_sock.sendto(data, addr)
        except BlockingIOError:
            return WriteResult.WOULD_BLOCK
        except OSError:
            self.send_errors += 1
            return WriteResult.ERROR
        return WriteResult.OK

    def send_rtcp(self, data: bytes, addr) -> WriteResult:
        if self.rtcp_sock is None:
            return WriteResult.ERROR
        try:
            self.rtcp_sock.sendto(data, addr)
        except BlockingIOError:
            return WriteResult.WOULD_BLOCK
        except OSError:
            return WriteResult.ERROR
        return WriteResult.OK

    def fileno(self) -> int:
        return self.rtp_sock.fileno() if self.rtp_sock is not None else -1
