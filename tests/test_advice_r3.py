"""Regression tests for the round-2 advisor findings (ADVICE.md):

* partial GSO send stopping on a hard errno retries the remainder through
  plain sendmmsg before condemning a destination (medium)
* the native fast path stages no payload copies (window_meta)
* originated SR NTP time is real wall clock, not epoch-1970 monotonic
* upstream RRs carry a per-stream random reporter SSRC
* shared-egress RTCP demux disambiguates NAT'd connections by SSRC
"""

import struct
import time
import types

import numpy as np
import pytest

from easydarwin_tpu.protocol import rtcp, rtp, sdp
from easydarwin_tpu.relay import RelayStream, StreamSettings
from easydarwin_tpu.relay.fanout import TpuFanoutEngine
from easydarwin_tpu.relay.output import CollectingOutput

VIDEO_SDP = ("v=0\r\nm=video 0 RTP/AVP 96\r\na=rtpmap:96 H264/90000\r\n"
             "a=control:trackID=1\r\n")


def mkstream(**kw):
    return RelayStream(sdp.parse(VIDEO_SDP).streams[0], StreamSettings(**kw))


def vid_pkt(seq, ts=0, nal_type=1):
    payload = bytes(((3 << 5) | nal_type,)) + bytes(30)
    return rtp.RtpPacket(payload_type=96, seq=seq, timestamp=ts, ssrc=0x77,
                         payload=payload).to_bytes()


def test_partial_gso_hard_error_retries_remainder_plain(monkeypatch):
    """A GSO pass that delivers some ops then stops on a hard errno (the
    no-UDP_SEGMENT kernel shape: single-segment super fine, multi-segment
    EINVAL) must retry the unsent remainder without GSO — not silently
    drop it while GSO stays enabled (ADVICE r2 medium)."""
    from easydarwin_tpu import native
    if not native.available():
        pytest.skip("native core unavailable")
    from easydarwin_tpu.relay import fanout as fanout_mod

    st = mkstream(bucket_delay_ms=0)
    outs = []
    for i in range(2):
        o = CollectingOutput(ssrc=i + 1, out_seq_start=10 * (i + 1))
        o.native_addr = ("127.0.0.1", 40000 + i)
        st.add_output(o)
        outs.append(o)
    n = 3
    for i in range(n):
        st.push_rtp(vid_pkt(100 + i), 0)
    total = n * 2

    calls = []

    def fake_send_multi(fd, data, length, seq_off, ts_off, ssrc, dests,
                        ops, n_ops, *, use_gso=True, trace_id=None,
                        submit=False):
        """The engine's sends are jobs of the native sender: a finished
        one, with the job's own errno in its result."""
        assert submit
        calls.append((n_ops, use_gso))
        # GSO: EINVAL after a partial delivery; plain drains the rest
        r, err = (2, 22) if use_gso else (n_ops, 0)
        return types.SimpleNamespace(
            done=True, result=r, err=err, n_ops=n_ops, use_gso=use_gso,
            submit_ns=1, start_ns=2, done_ns=3, syscalls=1)

    fake = types.SimpleNamespace(
        available=lambda: True,
        make_dests=native.make_dests,
        ops_from_numpy=native.ops_from_numpy,
        fanout_send_multi=fake_send_multi,
        last_send_errno=lambda: 0)          # the loop thread sent nothing
    monkeypatch.setattr(fanout_mod, "_native_mod", lambda: fake)
    # the engine resolves `native` lazily inside _native_step too
    import easydarwin_tpu
    monkeypatch.setattr(easydarwin_tpu, "native", fake)

    eng = TpuFanoutEngine(egress_fd=1)
    sent = eng.step(st, 1000)
    assert sent == total                   # nothing silently dropped
    assert eng.send_errors == 0            # no destination condemned
    assert [c for c in calls] == [(total, True), (total - 2, False)]
    assert eng._gso_strikes == 1
    for o in outs:
        assert o.bookmark == st.rtp_ring.head


def test_window_meta_copies_no_payload():
    st = mkstream()
    for i in range(8):
        st.push_rtp(vid_pkt(i), 0)
    ring = st.rtp_ring
    ids, lengths, flags = ring.window_meta(ring.tail, len(ring))
    ids2, data, lengths2, flags2 = ring.window_arrays(ring.tail, len(ring))
    assert np.array_equal(ids, ids2)
    assert np.array_equal(lengths, lengths2)
    assert np.array_equal(flags, flags2)


def test_originated_sr_ntp_is_wall_clock():
    st = mkstream(bucket_delay_ms=0)
    out = CollectingOutput(ssrc=0xAA, out_seq_start=1)
    st.add_output(out)
    st.push_rtp(vid_pkt(1, ts=9000), 5_000)
    st.reflect(5_000)                      # latch rebase + originate SR
    srs = [p for raw in out.rtcp_packets
           for p in rtcp.parse_compound(raw)
           if isinstance(p, rtcp.SenderReport)]
    assert srs
    ntp_secs = (srs[-1].ntp_ts >> 32) - 2208988800
    assert abs(ntp_secs - time.time()) < 120.0


def test_sr_ntp_advances_on_monotonic_clock():
    st = mkstream(bucket_delay_ms=0)
    out = CollectingOutput(ssrc=0xAB, out_seq_start=1)
    st.add_output(out)
    st.push_rtp(vid_pkt(1, ts=9000), 1_000)
    st.reflect(1_000)
    st.push_rtp(vid_pkt(2, ts=18000), 7_000)
    st.reflect(7_000)                      # second SR 6 s later
    srs = [p for raw in out.rtcp_packets
           for p in rtcp.parse_compound(raw)
           if isinstance(p, rtcp.SenderReport)]
    assert len(srs) >= 2
    d = ((srs[-1].ntp_ts - srs[0].ntp_ts) / 2**32)
    assert abs(d - 6.0) < 0.01             # wall base + monotonic delta


def test_upstream_rr_reporter_ssrc_is_per_stream():
    ssrcs = {mkstream().reporter_ssrc for _ in range(8)}
    assert len(ssrcs) > 1                  # random, not a shared constant
    assert 0x45445450 not in ssrcs or len(ssrcs) == 8

    st = mkstream()
    st.push_rtp(vid_pkt(1), 0)
    got = []
    st.upstream_rtcp = got.append
    assert st.send_upstream_rr(10_000)
    rr = rtcp.parse_compound(got[0])[0]
    assert isinstance(rr, rtcp.ReceiverReport)
    assert rr.ssrc == st.reporter_ssrc


class _FakeOut:
    def __init__(self, ssrc):
        self.rewrite = types.SimpleNamespace(ssrc=ssrc)


class _FakeConn:
    def __init__(self, ssrc):
        self.player_tracks = {1: types.SimpleNamespace(output=_FakeOut(ssrc))}


def _rr_for(ssrc):
    return (struct.pack("!BBHI", 0x81, 201, 7, 0x1234)
            + struct.pack("!I", ssrc) + bytes([10]) + b"\x00\x00\x00"
            + struct.pack("!IIII", 0, 0, 0, 0))


def test_shared_ip_rtcp_demux_matches_by_ssrc():
    """Two NAT'd connections share an IP; RTCP from an ephemeral port must
    reach the connection whose output SSRC the RR reports on (ADVICE r2:
    previously dropped for both)."""
    from easydarwin_tpu.server.egress import SharedUdpEgress

    eg = SharedUdpEgress()
    a, b = _FakeConn(0x111), _FakeConn(0x222)
    eg._by_ip["10.0.0.9"] = [a, b]
    hits = []
    eg.on_rtcp = lambda conn, data, addr=None: hits.append(conn)
    eg._on_rtcp(_rr_for(0x222), ("10.0.0.9", 59999))
    assert hits == [b]
    eg._on_rtcp(_rr_for(0x111), ("10.0.0.9", 58888))
    assert hits == [b, a]
    eg._on_rtcp(_rr_for(0x999), ("10.0.0.9", 58887))   # unknown: dropped
    assert hits == [b, a]
