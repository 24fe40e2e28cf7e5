// edtpu_core — native data-plane for easydarwin_tpu. See edtpu_core.h.
#include "edtpu_core.h"

#include <algorithm>
#include <arpa/inet.h>
#include <atomic>
#include <cerrno>
#include <condition_variable>
#include <cstring>
#include <ctime>
#include <deque>
#include <map>
#include <mutex>
#include <netinet/in.h>
#include <netinet/udp.h>
#include <pthread.h>
#include <sys/mman.h>
#include <sys/resource.h>
#include <sys/socket.h>
#include <sys/syscall.h>
#include <sys/uio.h>
#include <unistd.h>
#include <vector>

namespace {
constexpr int kSendBatch = 512;
constexpr int kRecvBatch = 64;

inline void render_header(uint8_t *dst, const uint8_t *src, uint32_t seq_off,
                          uint32_t ts_off, uint32_t ssrc) {
  // bytes 0-1 verbatim (V/P/X/CC, M/PT)
  dst[0] = src[0];
  dst[1] = src[1];
  uint16_t seq = static_cast<uint16_t>((src[2] << 8) | src[3]);
  seq = static_cast<uint16_t>(seq + seq_off);
  dst[2] = static_cast<uint8_t>(seq >> 8);
  dst[3] = static_cast<uint8_t>(seq);
  uint32_t ts = (static_cast<uint32_t>(src[4]) << 24) |
                (static_cast<uint32_t>(src[5]) << 16) |
                (static_cast<uint32_t>(src[6]) << 8) | src[7];
  ts += ts_off;
  dst[4] = static_cast<uint8_t>(ts >> 24);
  dst[5] = static_cast<uint8_t>(ts >> 16);
  dst[6] = static_cast<uint8_t>(ts >> 8);
  dst[7] = static_cast<uint8_t>(ts);
  dst[8] = static_cast<uint8_t>(ssrc >> 24);
  dst[9] = static_cast<uint8_t>(ssrc >> 16);
  dst[10] = static_cast<uint8_t>(ssrc >> 8);
  dst[11] = static_cast<uint8_t>(ssrc);
}
}  // namespace

namespace {
// why the last send path stopped short: 0 = completed, EAGAIN/EWOULDBLOCK
// = flow control (caller keeps bookmarks and replays), anything else = a
// hard per-datagram error (caller skips past it, oracle ERROR semantics).
// Partial counts alone cannot distinguish the two cases.
thread_local int g_stop_errno = 0;

// Cumulative data-plane counters (see ed_stats in the header).  Relaxed
// atomics: each increment sits next to a syscall, so the cost is noise,
// and cross-thread snapshot skew of a few counts is acceptable for
// metrics.
struct StatCells {
  std::atomic<int64_t> sendmmsg_calls{0}, sendto_calls{0}, send_packets{0},
      gso_supers{0}, gso_segments{0}, eagain_stops{0}, hard_errors{0},
      bytes_to_wire{0}, recvmmsg_calls{0}, recv_datagrams{0}, recv_bytes{0},
      oversize_dropped{0}, send_ns{0}, ingest_ns{0}, stage_gather_ns{0},
      staged_bytes{0}, fault_injections{0}, uring_sqes{0}, uring_cqes{0},
      uring_submits{0}, uring_zc_completions{0}, uring_zc_copied{0},
      stream_writev_calls{0}, stream_packets{0}, stream_bytes{0};
};
StatCells g_stat;

// ed_fanout_send_multi calls in flight at this instant, over every thread,
// and the most there ever were (ed_sender_stats: the pipeline's "never two
// sends at once" is read off this, not assumed)
std::atomic<int64_t> g_sends_in_flight{0}, g_sends_in_flight_max{0};

inline void stat_add(std::atomic<int64_t> &c, int64_t v) {
  c.fetch_add(v, std::memory_order_relaxed);
}

// A stopped send still ISSUED its syscall: count the call too, so the
// calls counter is a true denominator for the EAGAIN/error ratios
// (under pure backpressure, eagain_stops/sendmmsg_calls must read 1.0,
// not divide by zero).
inline void note_send_stop(int err) {
  if (err == EAGAIN || err == EWOULDBLOCK)
    stat_add(g_stat.eagain_stops, 1);
  else
    stat_add(g_stat.hard_errors, 1);
}

inline int64_t mono_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<int64_t>(ts.tv_sec) * 1000000000 + ts.tv_nsec;
}

// RAII bracket: adds the entry point's wall time to one timing counter on
// every exit path (returns, EAGAIN stops, hard errors).  One
// clock_gettime pair per CALL — noise next to the sendmmsg/recvmmsg the
// call exists to issue — feeding the obs layer's egress_native phase
// attribution (ed_fanout_send_multi's children each bracket themselves,
// so the wrapper adds nothing and never double-counts).
struct StatTimer {
  std::atomic<int64_t> &cell;
  int64_t t0;
  explicit StatTimer(std::atomic<int64_t> &c) : cell(c), t0(mono_ns()) {}
  ~StatTimer() { stat_add(cell, mono_ns() - t0); }
};

// Deterministic egress fault knobs (ed_fault_set): counter-based — every
// Nth send-call attempt fails/sleeps — so a chaos run with one
// configuration replays one schedule.  Relaxed atomics: the counters sit
// next to syscalls, and cross-thread skew of a count is acceptable for a
// fault schedule the same way it is for metrics.
struct FaultCells {
  std::atomic<int64_t> eagain_every{0}, enobufs_every{0}, latency_every{0},
      latency_us{0};
  std::atomic<int64_t> eagain_calls{0}, enobufs_calls{0}, latency_calls{0};
};
FaultCells g_fault;

inline bool fault_due(std::atomic<int64_t> &every,
                      std::atomic<int64_t> &calls) {
  int64_t n = every.load(std::memory_order_relaxed);
  if (n <= 0) return false;
  int64_t c = calls.fetch_add(1, std::memory_order_relaxed) + 1;
  return c % n == 0;
}

// Run before each egress syscall attempt.  Returns 0 = proceed, or the
// errno the attempt should fail with (EAGAIN / ENOBUFS) — the caller
// takes exactly its real-kernel error path, so injected faults exercise
// the production bookmark/skip machinery, not a parallel one.
inline int fault_egress_gate() {
  if (fault_due(g_fault.latency_every, g_fault.latency_calls)) {
    stat_add(g_stat.fault_injections, 1);
    int64_t us = g_fault.latency_us.load(std::memory_order_relaxed);
    if (us > 0) {
      timespec ts{us / 1000000, (us % 1000000) * 1000};
      nanosleep(&ts, nullptr);
    }
  }
  if (fault_due(g_fault.eagain_every, g_fault.eagain_calls)) {
    stat_add(g_stat.fault_injections, 1);
    return EAGAIN;
  }
  if (fault_due(g_fault.enobufs_every, g_fault.enobufs_calls)) {
    stat_add(g_stat.fault_injections, 1);
    return ENOBUFS;
  }
  return 0;
}
}  // namespace

extern "C" {

const char *ed_version(void) { return "edtpu_core 0.1.0"; }

// What this binary was built FROM and FOR (csrc/Makefile passes both).
// native.py reads the marker out of the file's bytes before dlopen, so a
// library built from other sources or for another CPU (-march=native) is
// rebuilt instead of executed.
#ifndef ED_SOURCE_DIGEST
#define ED_SOURCE_DIGEST "unknown"
#endif
#ifndef ED_BUILD_CPU
#define ED_BUILD_CPU "unknown"
#endif
const char *ed_build_info(void) {
  return "EDTPU_BUILD{" ED_SOURCE_DIGEST "|" ED_BUILD_CPU "}";
}

int32_t ed_last_send_errno(void) { return g_stop_errno; }

void ed_get_stats(ed_stats *out) {
  out->sendmmsg_calls = g_stat.sendmmsg_calls.load(std::memory_order_relaxed);
  out->sendto_calls = g_stat.sendto_calls.load(std::memory_order_relaxed);
  out->send_packets = g_stat.send_packets.load(std::memory_order_relaxed);
  out->gso_supers = g_stat.gso_supers.load(std::memory_order_relaxed);
  out->gso_segments = g_stat.gso_segments.load(std::memory_order_relaxed);
  out->eagain_stops = g_stat.eagain_stops.load(std::memory_order_relaxed);
  out->hard_errors = g_stat.hard_errors.load(std::memory_order_relaxed);
  out->bytes_to_wire = g_stat.bytes_to_wire.load(std::memory_order_relaxed);
  out->recvmmsg_calls = g_stat.recvmmsg_calls.load(std::memory_order_relaxed);
  out->recv_datagrams = g_stat.recv_datagrams.load(std::memory_order_relaxed);
  out->recv_bytes = g_stat.recv_bytes.load(std::memory_order_relaxed);
  out->oversize_dropped =
      g_stat.oversize_dropped.load(std::memory_order_relaxed);
  out->send_ns = g_stat.send_ns.load(std::memory_order_relaxed);
  out->ingest_ns = g_stat.ingest_ns.load(std::memory_order_relaxed);
  out->stage_gather_ns =
      g_stat.stage_gather_ns.load(std::memory_order_relaxed);
  out->staged_bytes = g_stat.staged_bytes.load(std::memory_order_relaxed);
  out->fault_injections =
      g_stat.fault_injections.load(std::memory_order_relaxed);
  out->uring_sqes = g_stat.uring_sqes.load(std::memory_order_relaxed);
  out->uring_cqes = g_stat.uring_cqes.load(std::memory_order_relaxed);
  out->uring_submits = g_stat.uring_submits.load(std::memory_order_relaxed);
  out->uring_zc_completions =
      g_stat.uring_zc_completions.load(std::memory_order_relaxed);
  out->uring_zc_copied =
      g_stat.uring_zc_copied.load(std::memory_order_relaxed);
  out->stream_writev_calls =
      g_stat.stream_writev_calls.load(std::memory_order_relaxed);
  out->stream_packets = g_stat.stream_packets.load(std::memory_order_relaxed);
  out->stream_bytes = g_stat.stream_bytes.load(std::memory_order_relaxed);
}

// Correct by construction: adding an ed_stats field updates this
// automatically, so the Python-side ABI handshake can never desync from
// the struct it guards (every field is int64_t by design).
int32_t ed_stats_fields(void) {
  return static_cast<int32_t>(sizeof(ed_stats) / sizeof(int64_t));
}

void ed_reset_stats(void) {
  g_stat.sendmmsg_calls.store(0, std::memory_order_relaxed);
  g_stat.sendto_calls.store(0, std::memory_order_relaxed);
  g_stat.send_packets.store(0, std::memory_order_relaxed);
  g_stat.gso_supers.store(0, std::memory_order_relaxed);
  g_stat.gso_segments.store(0, std::memory_order_relaxed);
  g_stat.eagain_stops.store(0, std::memory_order_relaxed);
  g_stat.hard_errors.store(0, std::memory_order_relaxed);
  g_stat.bytes_to_wire.store(0, std::memory_order_relaxed);
  g_stat.recvmmsg_calls.store(0, std::memory_order_relaxed);
  g_stat.recv_datagrams.store(0, std::memory_order_relaxed);
  g_stat.recv_bytes.store(0, std::memory_order_relaxed);
  g_stat.oversize_dropped.store(0, std::memory_order_relaxed);
  g_stat.send_ns.store(0, std::memory_order_relaxed);
  g_stat.ingest_ns.store(0, std::memory_order_relaxed);
  g_stat.stage_gather_ns.store(0, std::memory_order_relaxed);
  g_stat.staged_bytes.store(0, std::memory_order_relaxed);
  g_stat.fault_injections.store(0, std::memory_order_relaxed);
  g_stat.uring_sqes.store(0, std::memory_order_relaxed);
  g_stat.uring_cqes.store(0, std::memory_order_relaxed);
  g_stat.uring_submits.store(0, std::memory_order_relaxed);
  g_stat.uring_zc_completions.store(0, std::memory_order_relaxed);
  g_stat.uring_zc_copied.store(0, std::memory_order_relaxed);
  g_stat.stream_writev_calls.store(0, std::memory_order_relaxed);
  g_stat.stream_packets.store(0, std::memory_order_relaxed);
  g_stat.stream_bytes.store(0, std::memory_order_relaxed);
}

void ed_fault_set(int64_t eagain_every, int64_t enobufs_every,
                  int64_t latency_every, int64_t latency_us) {
  g_fault.eagain_every.store(eagain_every, std::memory_order_relaxed);
  g_fault.enobufs_every.store(enobufs_every, std::memory_order_relaxed);
  g_fault.latency_every.store(latency_every, std::memory_order_relaxed);
  g_fault.latency_us.store(latency_us, std::memory_order_relaxed);
  // fresh schedule: counters restart so one configuration is one
  // deterministic sequence regardless of what ran before arming
  g_fault.eagain_calls.store(0, std::memory_order_relaxed);
  g_fault.enobufs_calls.store(0, std::memory_order_relaxed);
  g_fault.latency_calls.store(0, std::memory_order_relaxed);
}

void ed_fault_clear(void) { ed_fault_set(0, 0, 0, 0); }

int32_t ed_fanout_send_udp(int fd, const uint8_t *ring_data,
                           const int32_t *ring_len, int32_t capacity,
                           int32_t slot_size, const uint32_t *seq_off,
                           const uint32_t *ts_off, const uint32_t *ssrc,
                           const ed_dest *dest, int32_t n_outs,
                           const ed_sendop *ops, int32_t n_ops) {
  g_stop_errno = 0;
  if (n_ops <= 0) return 0;
  StatTimer timer(g_stat.send_ns);
  std::vector<mmsghdr> msgs(kSendBatch);
  std::vector<iovec> iovs(static_cast<size_t>(kSendBatch) * 2);
  std::vector<sockaddr_in> addrs(kSendBatch);
  // stack of rendered headers for the in-flight batch
  std::vector<uint8_t> hdrs(static_cast<size_t>(kSendBatch) * 12);
  std::vector<int32_t> blens(kSendBatch);  // per-msg bytes for accounting

  int32_t done = 0;
  while (done < n_ops) {
    int batch = 0;
    for (; batch < kSendBatch && done + batch < n_ops; ++batch) {
      const ed_sendop &op = ops[done + batch];
      if (op.slot < 0 || op.slot >= capacity || op.out < 0 ||
          op.out >= n_outs)
        return -EINVAL;
      const uint8_t *pkt = ring_data +
                           static_cast<size_t>(op.slot) * slot_size;
      int32_t len = ring_len[op.slot];
      if (len < 12 || len > slot_size) return -EINVAL;
      blens[batch] = len;
      uint8_t *h = hdrs.data() + static_cast<size_t>(batch) * 12;
      render_header(h, pkt, seq_off[op.out], ts_off[op.out], ssrc[op.out]);
      iovec *iv = &iovs[static_cast<size_t>(batch) * 2];
      iv[0].iov_base = h;
      iv[0].iov_len = 12;
      iv[1].iov_base = const_cast<uint8_t *>(pkt) + 12;
      iv[1].iov_len = static_cast<size_t>(len - 12);
      sockaddr_in &sa = addrs[batch];
      std::memset(&sa, 0, sizeof(sa));
      sa.sin_family = AF_INET;
      sa.sin_addr.s_addr = dest[op.out].ip_be;
      sa.sin_port = dest[op.out].port_be;
      mmsghdr &m = msgs[batch];
      std::memset(&m, 0, sizeof(m));
      m.msg_hdr.msg_name = &sa;
      m.msg_hdr.msg_namelen = sizeof(sa);
      m.msg_hdr.msg_iov = iv;
      m.msg_hdr.msg_iovlen = 2;
    }
    int sent = 0;
    while (sent < batch) {
      int ferr = fault_egress_gate();
      if (ferr) {  // injected: the caller takes its real-kernel path
        g_stop_errno = ferr;
        stat_add(g_stat.sendmmsg_calls, 1);
        note_send_stop(ferr);
        if (ferr == EAGAIN) return done + sent;
        int32_t got = done + sent;
        return got > 0 ? got : -ferr;
      }
      int n = sendmmsg(fd, msgs.data() + sent, batch - sent, 0);
      if (n < 0) {
        if (errno == EINTR) continue;
        g_stop_errno = errno;
        stat_add(g_stat.sendmmsg_calls, 1);
        note_send_stop(errno);
        if (errno == EAGAIN || errno == EWOULDBLOCK)
          return done + sent;  // WouldBlock: caller keeps its bookmark
        // hard mid-batch error: report what WAS delivered (callers advance
        // bookmarks past it and never re-send delivered datagrams) — the
        // same contract as the GSO path's `done > 0 ? done : -flush_err`;
        // ed_last_send_errno() tells the caller the stop was hard
        int32_t got = done + sent;
        return got > 0 ? got : -errno;
      }
      stat_add(g_stat.sendmmsg_calls, 1);
      stat_add(g_stat.send_packets, n);
      int64_t nb = 0;
      for (int i = sent; i < sent + n; ++i) nb += blens[i];
      stat_add(g_stat.bytes_to_wire, nb);
      sent += n;
    }
    done += batch;
  }
  return done;
}

#ifndef UDP_SEGMENT
#define UDP_SEGMENT 103
#endif
#ifndef UDP_MAX_SEGMENTS
#define UDP_MAX_SEGMENTS 64
#endif
// Copy-avoidance was evaluated for this path and rejected with data:
// MSG_ZEROCOPY + UDP_SEGMENT returns EMSGSIZE for multi-frag supers (the
// zerocopy skb is limited to MAX_SKB_FRAGS page frags; our 46-segment
// supers are ~92 scattered iovecs), and MSG_SPLICE_PAGES is a
// kernel-internal flag masked off for userspace sendmsg — measured
// throughput is identical to the copying path.  The copy itself runs at
// cache speed (the ring's hot window), so GSO batching, not copy
// avoidance, is where the win is.
int32_t ed_fanout_send_udp_gso(int fd, const uint8_t *ring_data,
                               const int32_t *ring_len, int32_t capacity,
                               int32_t slot_size, const uint32_t *seq_off,
                               const uint32_t *ts_off, const uint32_t *ssrc,
                               const ed_dest *dest, int32_t n_outs,
                               const ed_sendop *ops, int32_t n_ops) {
  g_stop_errno = 0;
  if (n_ops <= 0) return 0;
  StatTimer timer(g_stat.send_ns);
  const int send_flags = 0;
  // One super-send = one msg_hdr with [hdr|payload] iovec pairs for a run of
  // same-subscriber, same-size packets, plus a UDP_SEGMENT cmsg.
  constexpr int kSupers = 64;  // super-sends per sendmmsg flush
  constexpr size_t kMaxGsoBytes = 65000;  // < 65507 UDP payload ceiling
  struct Super {
    sockaddr_in sa;
    alignas(cmsghdr) char ctl[CMSG_SPACE(sizeof(uint16_t))];
    int n_segs = 0;
    int n_ops = 0;  // ops consumed by this super (== n_segs)
    int64_t bytes = 0;
  };
  // per-thread scratch: this runs once per source per window
  static thread_local std::vector<mmsghdr> msgs(kSupers);
  static thread_local std::vector<Super> supers(kSupers);
  // worst case: every segment is its own iovec pair
  static thread_local std::vector<iovec> iovs(
      static_cast<size_t>(kSupers) * 2 * UDP_MAX_SEGMENTS);
  static thread_local std::vector<uint8_t> hdrs(
      static_cast<size_t>(kSupers) * UDP_MAX_SEGMENTS * 12);
  size_t iov_used = 0, hdr_used = 0;

  int32_t done = 0;  // ops fully handed to the kernel
  int32_t staged = 0;  // ops rendered into the current flush window
  int n_super = 0;
  int flush_err = 0;  // hard errno from the last flush (0 = none)

  // Returns ops actually handed to the kernel (counting partially-flushed
  // windows), sets flush_err on a hard error.  Callers add the count to
  // `done` before acting on the error, so a caller retrying the remainder
  // through the non-GSO path never duplicates a delivered datagram.
  auto flush = [&]() -> int32_t {
    int sent = 0;
    flush_err = 0;
    while (sent < n_super) {
      int ferr = fault_egress_gate();
      if (ferr) {  // injected: mirror the real stop accounting exactly
        g_stop_errno = ferr;
        stat_add(g_stat.sendmmsg_calls, 1);
        note_send_stop(ferr);
        if (ferr != EAGAIN) flush_err = ferr;
        int32_t ops_sent = 0;
        for (int i = 0; i < sent; ++i) ops_sent += supers[i].n_ops;
        return ops_sent;
      }
      int n = sendmmsg(fd, msgs.data() + sent, n_super - sent, send_flags);
      if (n < 0) {
        if (errno == EINTR) continue;
        g_stop_errno = errno;
        stat_add(g_stat.sendmmsg_calls, 1);
        // EINVAL/EOPNOTSUPP on the UDP_SEGMENT path is "this kernel has
        // no UDP GSO" — a capability probe outcome the caller handles by
        // falling back to the plain path, not a destination failure;
        // counting it into hard_errors would page operators on every
        // boot of a pre-4.18 kernel
        if (errno != EINVAL && errno != EOPNOTSUPP) note_send_stop(errno);
        if (errno != EAGAIN && errno != EWOULDBLOCK) flush_err = errno;
        int32_t ops_sent = 0;
        for (int i = 0; i < sent; ++i) ops_sent += supers[i].n_ops;
        return ops_sent;
      }
      stat_add(g_stat.sendmmsg_calls, 1);
      int64_t pk = 0, nb = 0, sup = 0, seg = 0;
      for (int i = sent; i < sent + n; ++i) {
        pk += supers[i].n_ops;
        nb += supers[i].bytes;
        if (supers[i].n_segs > 1) {
          sup += 1;
          seg += supers[i].n_segs;
        }
      }
      stat_add(g_stat.send_packets, pk);
      stat_add(g_stat.bytes_to_wire, nb);
      if (sup) {
        stat_add(g_stat.gso_supers, sup);
        stat_add(g_stat.gso_segments, seg);
      }
      sent += n;
    }
    int32_t ops_sent = 0;
    for (int i = 0; i < n_super; ++i) ops_sent += supers[i].n_ops;
    n_super = 0;
    staged = 0;
    iov_used = 0;
    hdr_used = 0;
    return ops_sent;
  };

  while (done + staged < n_ops) {
    // start a new run: consecutive ops with one subscriber and uniform size
    const ed_sendop &first = ops[done + staged];
    if (first.slot < 0 || first.slot >= capacity || first.out < 0 ||
        first.out >= n_outs)
      return -EINVAL;
    int32_t gs_len = ring_len[first.slot];
    if (gs_len < 12 || gs_len > slot_size) return -EINVAL;
    uint16_t gs_size = static_cast<uint16_t>(gs_len);  // 12B hdr + payload

    Super &sp = supers[n_super];
    sp.n_segs = 0;
    sp.n_ops = 0;
    sp.bytes = 0;
    std::memset(&sp.sa, 0, sizeof(sp.sa));
    sp.sa.sin_family = AF_INET;
    sp.sa.sin_addr.s_addr = dest[first.out].ip_be;
    sp.sa.sin_port = dest[first.out].port_be;
    iovec *run_iov = &iovs[iov_used];
    size_t bytes = 0;

    while (done + staged < n_ops && sp.n_segs < UDP_MAX_SEGMENTS) {
      const ed_sendop &op = ops[done + staged];
      if (op.out != first.out) break;
      if (op.slot < 0 || op.slot >= capacity) return -EINVAL;
      int32_t len = ring_len[op.slot];
      if (len < 12 || len > slot_size) return -EINVAL;
      // every segment but the last must be exactly gs_size; a shorter
      // packet may close the run, a longer one must start a new run
      if (len > gs_size) break;
      if (bytes + static_cast<size_t>(len) > kMaxGsoBytes) break;
      const uint8_t *pkt = ring_data + static_cast<size_t>(op.slot) * slot_size;
      uint8_t *h = hdrs.data() + hdr_used;
      hdr_used += 12;
      render_header(h, pkt, seq_off[op.out], ts_off[op.out], ssrc[op.out]);
      iovec *iv = &iovs[iov_used];
      iov_used += 2;
      iv[0].iov_base = h;
      iv[0].iov_len = 12;
      iv[1].iov_base = const_cast<uint8_t *>(pkt) + 12;
      iv[1].iov_len = static_cast<size_t>(len - 12);
      bytes += static_cast<size_t>(len);
      sp.n_segs++;
      sp.n_ops++;
      staged++;
      if (len < gs_size) break;  // short segment ends the super-datagram
    }
    sp.bytes = static_cast<int64_t>(bytes);

    mmsghdr &m = msgs[n_super];
    std::memset(&m, 0, sizeof(m));
    m.msg_hdr.msg_name = &sp.sa;
    m.msg_hdr.msg_namelen = sizeof(sp.sa);
    m.msg_hdr.msg_iov = run_iov;
    m.msg_hdr.msg_iovlen = static_cast<size_t>(sp.n_segs) * 2;
    if (sp.n_segs > 1) {
      m.msg_hdr.msg_control = sp.ctl;
      m.msg_hdr.msg_controllen = sizeof(sp.ctl);
      cmsghdr *cm = CMSG_FIRSTHDR(&m.msg_hdr);
      cm->cmsg_level = SOL_UDP;
      cm->cmsg_type = UDP_SEGMENT;
      cm->cmsg_len = CMSG_LEN(sizeof(uint16_t));
      std::memcpy(CMSG_DATA(cm), &gs_size, sizeof(uint16_t));
    }
    n_super++;

    if (n_super == kSupers ||
        iov_used + 2 * UDP_MAX_SEGMENTS > iovs.size()) {
      int32_t r = flush();
      done += r;
      if (flush_err) return done > 0 ? done : -flush_err;
      if (r < staged) return done;  // EAGAIN mid-window: bookmark kept
      staged = 0;
    }
  }
  if (n_super > 0) {
    int32_t r = flush();
    done += r;
    if (flush_err && done == 0) return -flush_err;
  }
  return done;
}

// Multi-source egress: one call sends `n_src` sources sharing a ring and
// op list, with per-source rewrite params laid out as [n_src, n_outs]
// row-major (exactly the packed device result after unpack).  Cuts the
// per-window Python->C transition count from n_src to 1 on the hot loop.
// `use_gso` selects the UDP_SEGMENT path.  Returns total ops sent or
// -errno on a hard error with nothing sent.
int32_t ed_fanout_send_multi(int fd, const uint8_t *ring_data,
                             const int32_t *ring_len, int32_t capacity,
                             int32_t slot_size, const uint32_t *seq_off,
                             const uint32_t *ts_off, const uint32_t *ssrc,
                             int32_t n_src, int32_t param_stride,
                             const ed_dest *dest,
                             int32_t n_outs, const ed_sendop *ops,
                             int32_t n_ops, int32_t use_gso) {
  if (param_stride < n_outs) return -EINVAL;
  struct InFlight {
    InFlight() {
      int64_t n = g_sends_in_flight.fetch_add(1, std::memory_order_relaxed);
      if (n + 1 > g_sends_in_flight_max.load(std::memory_order_relaxed))
        g_sends_in_flight_max.store(n + 1, std::memory_order_relaxed);
    }
    ~InFlight() { g_sends_in_flight.fetch_sub(1, std::memory_order_relaxed); }
  } in_flight;
  int64_t total = 0;
  for (int32_t s = 0; s < n_src; ++s) {
    const uint32_t *sq = seq_off + static_cast<size_t>(s) * param_stride;
    const uint32_t *ts = ts_off + static_cast<size_t>(s) * param_stride;
    const uint32_t *sc = ssrc + static_cast<size_t>(s) * param_stride;
    int32_t r;
    if (use_gso == 2)        // forced scalar rung (egress_backend=scalar)
      r = ed_scalar_baseline_send(fd, ring_data, ring_len, capacity,
                                  slot_size, sq, ts, sc, dest, n_outs,
                                  ops, n_ops);
    else if (use_gso == 1)
      r = ed_fanout_send_udp_gso(fd, ring_data, ring_len, capacity,
                                 slot_size, sq, ts, sc, dest, n_outs, ops,
                                 n_ops);
    else
      r = ed_fanout_send_udp(fd, ring_data, ring_len, capacity, slot_size,
                             sq, ts, sc, dest, n_outs, ops, n_ops);
    if (r < 0) return total > 0 ? static_cast<int32_t>(total) : r;
    total += r;
  }
  return static_cast<int32_t>(total);
}

int32_t ed_scalar_baseline_send(int fd, const uint8_t *ring_data,
                                const int32_t *ring_len, int32_t capacity,
                                int32_t slot_size, const uint32_t *seq_off,
                                const uint32_t *ts_off, const uint32_t *ssrc,
                                const ed_dest *dest, int32_t n_outs,
                                const ed_sendop *ops, int32_t n_ops) {
  g_stop_errno = 0;
  StatTimer timer(g_stat.send_ns);
  uint8_t scratch[65536];
  for (int32_t i = 0; i < n_ops; ++i) {
    const ed_sendop &op = ops[i];
    if (op.slot < 0 || op.slot >= capacity || op.out < 0 || op.out >= n_outs)
      return -EINVAL;
    const uint8_t *pkt = ring_data + static_cast<size_t>(op.slot) * slot_size;
    int32_t len = ring_len[op.slot];
    if (len < 12 || len > slot_size ||
        len > static_cast<int32_t>(sizeof(scratch)))
      return -EINVAL;
    std::memcpy(scratch, pkt, static_cast<size_t>(len));
    render_header(scratch, pkt, seq_off[op.out], ts_off[op.out],
                  ssrc[op.out]);
    sockaddr_in sa;
    std::memset(&sa, 0, sizeof(sa));
    sa.sin_family = AF_INET;
    sa.sin_addr.s_addr = dest[op.out].ip_be;
    sa.sin_port = dest[op.out].port_be;
    for (;;) {
      int ferr = fault_egress_gate();
      if (ferr) {
        g_stop_errno = ferr;
        stat_add(g_stat.sendto_calls, 1);
        note_send_stop(ferr);
        if (ferr == EAGAIN) return i;
        return i > 0 ? i : -ferr;
      }
      ssize_t r = sendto(fd, scratch, static_cast<size_t>(len), 0,
                         reinterpret_cast<sockaddr *>(&sa), sizeof(sa));
      if (r >= 0) {
        stat_add(g_stat.sendto_calls, 1);
        stat_add(g_stat.send_packets, 1);
        stat_add(g_stat.bytes_to_wire, len);
        break;
      }
      if (errno == EINTR) continue;
      g_stop_errno = errno;
      stat_add(g_stat.sendto_calls, 1);
      note_send_stop(errno);
      if (errno == EAGAIN || errno == EWOULDBLOCK) return i;
      return i > 0 ? i : -errno;
    }
  }
  return n_ops;
}

// ---------------------------------------------------------- send pipeline
// One sender thread, jobs in submission order (ISSUE 38).  Heap-allocated
// and never freed: a thread asleep on its condition variable at process
// exit must not meet a static destructor.  A forked child starts from a
// fresh one (the thread did not come along).
}  // extern "C"

namespace {
struct Sender {
  std::mutex mu;
  std::condition_variable work, done;
  std::deque<ed_send_job *> q;
  pthread_t th{};
  bool running = false, stop = false;
  ed_send_job *cur = nullptr;  // the job being sent
  int64_t starts = 0, jobs = 0;
};
Sender *g_sender = new Sender;

void sender_run(ed_send_job *j) {
  int64_t calls0 = g_stat.sendmmsg_calls.load(std::memory_order_relaxed) +
                   g_stat.sendto_calls.load(std::memory_order_relaxed);
  g_stop_errno = 0;
  j->start_ns = mono_ns();
  j->result = ed_fanout_send_multi(
      j->fd, j->ring_data, j->ring_len, j->capacity, j->slot_size,
      j->seq_off, j->ts_off, j->ssrc, j->n_src, j->param_stride, j->dest,
      j->n_outs, j->ops, j->n_ops, j->use_gso);
  j->done_ns = mono_ns();
  j->err = g_stop_errno;
  j->syscalls = g_stat.sendmmsg_calls.load(std::memory_order_relaxed) +
                g_stat.sendto_calls.load(std::memory_order_relaxed) - calls0;
}

void *sender_main(void *arg) {
  Sender &s = *static_cast<Sender *>(arg);
  std::unique_lock<std::mutex> lk(s.mu);
  for (;;) {
    // asleep only on an empty queue: between two jobs of one wake the
    // next is already waiting
    s.work.wait(lk, [&] { return s.stop || !s.q.empty(); });
    if (s.q.empty()) break;  // stop, and everything queued was sent
    ed_send_job *j = s.q.front();
    s.q.pop_front();
    s.cur = j;
    lk.unlock();
    sender_run(j);
    lk.lock();
    s.cur = nullptr;
    // last touch of *j: its owner may free it once this reads done
    __atomic_store_n(&j->state, 2, __ATOMIC_RELEASE);
    s.done.notify_all();
  }
  return nullptr;
}

void sender_after_fork_child() { g_sender = new Sender; }
}  // namespace

extern "C" {

int32_t ed_send_job_size(void) {
  return static_cast<int32_t>(sizeof(ed_send_job));
}

int32_t ed_sender_submit(ed_send_job *j) {
  static const int atfork =
      pthread_atfork(nullptr, nullptr, sender_after_fork_child);
  (void)atfork;
  Sender &s = *g_sender;
  j->result = 0;
  j->err = 0;
  j->start_ns = j->done_ns = j->syscalls = 0;
  j->submit_ns = mono_ns();
  std::unique_lock<std::mutex> lk(s.mu);
  // a stop in progress: its thread may already be past its last look at
  // the queue, so wait the stop out and start another
  s.done.wait(lk, [&] { return !s.stop; });
  if (!s.running) {
    int rc = pthread_create(&s.th, nullptr, sender_main, &s);
    if (rc) return -rc;
    s.running = true;
    s.stop = false;
    s.starts++;
  }
  __atomic_store_n(&j->state, 1, __ATOMIC_RELAXED);
  s.q.push_back(j);
  s.jobs++;
  s.work.notify_one();
  return 0;
}

int32_t ed_sender_wait(ed_send_job *j) {
  int32_t st = __atomic_load_n(&j->state, __ATOMIC_ACQUIRE);
  if (st == 2) return 0;
  if (st != 1) return -EINVAL;
  Sender &s = *g_sender;
  std::unique_lock<std::mutex> lk(s.mu);
  s.done.wait(lk, [&] {
    return __atomic_load_n(&j->state, __ATOMIC_ACQUIRE) == 2;
  });
  return 0;
}

void ed_sender_drain(void) {
  Sender &s = *g_sender;
  std::unique_lock<std::mutex> lk(s.mu);
  s.done.wait(lk, [&] { return s.q.empty() && s.cur == nullptr; });
}

void ed_sender_stop(void) {
  Sender &s = *g_sender;
  pthread_t th;
  {
    std::lock_guard<std::mutex> lk(s.mu);
    if (!s.running || s.stop) return;
    s.stop = true;
    th = s.th;
    s.work.notify_one();
  }
  pthread_join(th, nullptr);
  std::lock_guard<std::mutex> lk(s.mu);
  s.running = false;
  s.stop = false;
  s.done.notify_all();  // a submit that met the stop goes on
}

void ed_sender_stats(int64_t out[4]) {
  Sender &s = *g_sender;
  std::lock_guard<std::mutex> lk(s.mu);
  out[0] = s.starts;
  out[1] = s.jobs;
  out[2] = s.running && !s.stop ? 1 : 0;
  out[3] = g_sends_in_flight_max.load(std::memory_order_relaxed);
}

// ---------------------------------------------------------- stream egress
// Framed interleaved egress (ISSUE 14): the 4-byte $-channel frame is
// affine in (len, channel) exactly as the RTP header is affine in the
// rewrite params, so one renderer emits [frame | header] per packet and
// writev scatters it with the shared payload — the stream sibling of
// the sendmmsg path.  A short write tears at a BYTE boundary (TCP is a
// byte sequence), reported via *partial_bytes_out so the caller can
// finish the torn packet through its buffered transport.
int32_t ed_stream_send(int fd, const uint8_t *ring_data,
                       const int32_t *ring_len, int32_t capacity,
                       int32_t slot_size, uint32_t seq_off,
                       uint32_t ts_off, uint32_t ssrc, int32_t channel,
                       const int32_t *slots, int32_t n_slots,
                       int32_t *partial_bytes_out) {
  g_stop_errno = 0;
  if (partial_bytes_out) *partial_bytes_out = 0;
  if (n_slots <= 0) return 0;
  if (channel < 0 || channel > 255) return -EINVAL;
  StatTimer timer(g_stat.send_ns);
  constexpr int kStreamBatch = 256;       // 512 iovecs < IOV_MAX (1024)
  std::vector<iovec> iovs(static_cast<size_t>(kStreamBatch) * 2);
  std::vector<uint8_t> hdrs(static_cast<size_t>(kStreamBatch) * 16);
  std::vector<int32_t> plens(kStreamBatch);  // framed length per packet
  std::vector<iovec> window(static_cast<size_t>(kStreamBatch) * 2);
  int32_t done = 0;
  while (done < n_slots) {
    int batch = 0;
    size_t batch_bytes = 0;
    for (; batch < kStreamBatch && done + batch < n_slots; ++batch) {
      int32_t slot = slots[done + batch];
      if (slot < 0 || slot >= capacity) {
        g_stop_errno = EINVAL;
        return done > 0 ? done : -EINVAL;
      }
      const uint8_t *pkt = ring_data + static_cast<size_t>(slot) * slot_size;
      int32_t len = ring_len[slot];
      if (len < 12 || len > slot_size || len > 0xFFFF) {
        g_stop_errno = EINVAL;
        return done > 0 ? done : -EINVAL;
      }
      uint8_t *h = hdrs.data() + static_cast<size_t>(batch) * 16;
      h[0] = 0x24;  // '$'
      h[1] = static_cast<uint8_t>(channel);
      h[2] = static_cast<uint8_t>(len >> 8);
      h[3] = static_cast<uint8_t>(len);
      render_header(h + 4, pkt, seq_off, ts_off, ssrc);
      iovec *iv = &iovs[static_cast<size_t>(batch) * 2];
      iv[0].iov_base = h;
      iv[0].iov_len = 16;
      iv[1].iov_base = const_cast<uint8_t *>(pkt) + 12;
      iv[1].iov_len = static_cast<size_t>(len - 12);
      plens[batch] = len + 4;
      batch_bytes += static_cast<size_t>(len) + 4;
    }
    size_t written = 0;
    for (;;) {
      int ferr = fault_egress_gate();
      if (ferr) {
        g_stop_errno = ferr;
        stat_add(g_stat.stream_writev_calls, 1);
        note_send_stop(ferr);
        break;
      }
      // iovec window starting at `written` (rebuilt only on retry after
      // a partial write — the hot path runs this once per batch)
      size_t skip = written;
      size_t first = 0;
      const size_t n_iov = static_cast<size_t>(batch) * 2;
      while (first < n_iov && skip >= iovs[first].iov_len)
        skip -= iovs[first++].iov_len;
      if (first >= n_iov) break;           // batch fully written
      size_t n_cur = n_iov - first;
      for (size_t i = 0; i < n_cur; ++i) window[i] = iovs[first + i];
      window[0].iov_base = static_cast<uint8_t *>(window[0].iov_base) + skip;
      window[0].iov_len -= skip;
      ssize_t w = writev(fd, window.data(), static_cast<int>(n_cur));
      if (w < 0) {
        if (errno == EINTR) continue;
        g_stop_errno = errno;
        stat_add(g_stat.stream_writev_calls, 1);
        note_send_stop(errno);
        break;
      }
      stat_add(g_stat.stream_writev_calls, 1);
      stat_add(g_stat.stream_bytes, w);
      written += static_cast<size_t>(w);
      if (written >= batch_bytes) break;
      // short write on a non-blocking stream socket: the send buffer is
      // full — stop with flow-control semantics instead of spinning
      // into a guaranteed EAGAIN
      g_stop_errno = EAGAIN;
      stat_add(g_stat.eagain_stops, 1);
      break;
    }
    int full = 0;
    size_t acc = 0;
    while (full < batch && acc + static_cast<size_t>(plens[full]) <= written) {
      acc += static_cast<size_t>(plens[full]);
      ++full;
    }
    if (full) stat_add(g_stat.stream_packets, full);
    done += full;
    if (written < batch_bytes || g_stop_errno) {
      if (partial_bytes_out)
        *partial_bytes_out = static_cast<int32_t>(written - acc);
      if (done == 0 && written == 0 && g_stop_errno &&
          g_stop_errno != EAGAIN && g_stop_errno != EWOULDBLOCK)
        return -g_stop_errno;
      return done;
    }
  }
  return done;
}

int64_t ed_stream_write(int fd, const uint8_t *buf, int64_t len) {
  g_stop_errno = 0;
  if (len <= 0) return 0;
  StatTimer timer(g_stat.send_ns);
  int64_t written = 0;
  while (written < len) {
    int ferr = fault_egress_gate();
    if (ferr) {
      g_stop_errno = ferr;
      stat_add(g_stat.stream_writev_calls, 1);
      note_send_stop(ferr);
      break;
    }
    ssize_t w = send(fd, buf + written,
                     static_cast<size_t>(len - written), MSG_NOSIGNAL);
    if (w < 0) {
      if (errno == EINTR) continue;
      g_stop_errno = errno;
      stat_add(g_stat.stream_writev_calls, 1);
      note_send_stop(errno);
      break;
    }
    stat_add(g_stat.stream_writev_calls, 1);
    stat_add(g_stat.stream_bytes, w);
    written += w;
    if (w == 0) break;
  }
  if (written == 0 && g_stop_errno && g_stop_errno != EAGAIN &&
      g_stop_errno != EWOULDBLOCK)
    return -g_stop_errno;
  return written;
}

int32_t ed_fanout_render(const uint8_t *ring_data, const int32_t *ring_len,
                         int32_t capacity, int32_t slot_size,
                         const uint32_t *seq_off, const uint32_t *ts_off,
                         const uint32_t *ssrc, int32_t n_outs,
                         const ed_sendop *ops, int32_t n_ops, uint8_t *out,
                         int32_t out_stride, int32_t *out_lens) {
  for (int32_t i = 0; i < n_ops; ++i) {
    const ed_sendop &op = ops[i];
    if (op.slot < 0 || op.slot >= capacity || op.out < 0 || op.out >= n_outs)
      return -EINVAL;
    const uint8_t *pkt = ring_data + static_cast<size_t>(op.slot) * slot_size;
    int32_t len = ring_len[op.slot];
    if (len < 12 || len > slot_size || len > out_stride) return -EINVAL;
    uint8_t *dst = out + static_cast<size_t>(i) * out_stride;
    render_header(dst, pkt, seq_off[op.out], ts_off[op.out], ssrc[op.out]);
    std::memcpy(dst + 12, pkt + 12, static_cast<size_t>(len - 12));
    out_lens[i] = len;
  }
  return n_ops;
}

int32_t ed_stage_gather(const uint8_t *ring_data, const int32_t *ring_len,
                        int32_t capacity, int32_t slot_size,
                        const int32_t *slots, int32_t n_slots,
                        int32_t prefix_width, uint8_t *out,
                        int32_t out_stride, int32_t out_rows) {
  if (n_slots < 0 || out_rows < n_slots || prefix_width <= 0 ||
      prefix_width > slot_size || out_stride < prefix_width + 4)
    return -EINVAL;
  StatTimer timer(g_stat.stage_gather_ns);
  for (int32_t i = 0; i < n_slots; ++i) {
    int32_t slot = slots[i];
    if (slot < 0 || slot >= capacity) return -EINVAL;
    uint8_t *row = out + static_cast<size_t>(i) * out_stride;
    // ring slots are zero-padded past their length (the ingest paths
    // maintain that invariant), so a straight prefix_width copy never
    // leaks a previous occupant's bytes
    std::memcpy(row, ring_data + static_cast<size_t>(slot) * slot_size,
                static_cast<size_t>(prefix_width));
    uint32_t len = static_cast<uint32_t>(ring_len[slot]);
    row[prefix_width + 0] = static_cast<uint8_t>(len);
    row[prefix_width + 1] = static_cast<uint8_t>(len >> 8);
    row[prefix_width + 2] = static_cast<uint8_t>(len >> 16);
    row[prefix_width + 3] = static_cast<uint8_t>(len >> 24);
    if (out_stride > prefix_width + 4)
      std::memset(row + prefix_width + 4, 0,
                  static_cast<size_t>(out_stride - prefix_width - 4));
  }
  // zero the pow2 padding rows so a reused double buffer never re-uploads
  // a previous wake's packets as live rows
  if (out_rows > n_slots)
    std::memset(out + static_cast<size_t>(n_slots) * out_stride, 0,
                static_cast<size_t>(out_rows - n_slots) * out_stride);
  stat_add(g_stat.staged_bytes,
           static_cast<int64_t>(n_slots) * (prefix_width + 4));
  return n_slots;
}

int32_t ed_udp_ingest(int fd, uint8_t *ring_data, int32_t *ring_len,
                      int64_t *ring_arrival, int32_t capacity,
                      int32_t slot_size, int64_t now_ms, int64_t *head,
                      int32_t max_pkts, int32_t *oversize_dropped) {
  StatTimer timer(g_stat.ingest_ns);
  int32_t total = 0;      // datagrams ADMITTED into the ring
  int32_t processed = 0;  // datagrams consumed from the socket — this is
                          // what max_pkts bounds, so an oversize flood
                          // (every datagram dropped) cannot extend one
                          // drain call past the caller's work budget
  std::vector<mmsghdr> msgs(kRecvBatch);
  std::vector<iovec> iovs(kRecvBatch);
  while (processed < max_pkts) {
    int want = std::min<int32_t>(kRecvBatch, max_pkts - processed);
    for (int i = 0; i < want; ++i) {
      int64_t slot = (*head + i) % capacity;
      iovs[i].iov_base = ring_data + slot * slot_size;
      iovs[i].iov_len = static_cast<size_t>(slot_size);
      std::memset(&msgs[i], 0, sizeof(mmsghdr));
      msgs[i].msg_hdr.msg_iov = &iovs[i];
      msgs[i].msg_hdr.msg_iovlen = 1;
    }
    int n = recvmmsg(fd, msgs.data(), want, MSG_DONTWAIT, nullptr);
    if (n < 0) {
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) break;
      // hard error after earlier successful batches: those datagrams are
      // already consumed from the socket — report them so the caller
      // commits the ring head instead of silently losing them
      return total > 0 ? total : -errno;
    }
    if (n == 0) break;
    stat_add(g_stat.recvmmsg_calls, 1);
    int wrote = 0;
    int64_t admitted_bytes = 0;
    for (int i = 0; i < n; ++i) {
      int64_t src = (*head + i) % capacity;
      // a kernel-truncated datagram (larger than the slot) is DROPPED,
      // not admitted capped — a truncated slot would relay a corrupt
      // packet to every consumer (mirrors PacketRing.push's oversize
      // drop on the Python ingest path)
      if (msgs[i].msg_hdr.msg_flags & MSG_TRUNC) {
        if (oversize_dropped) ++*oversize_dropped;
        stat_add(g_stat.oversize_dropped, 1);
        continue;
      }
      int32_t len = static_cast<int32_t>(msgs[i].msg_len);
      admitted_bytes += len;
      int64_t dst = (*head + wrote) % capacity;
      if (dst != src)                      // compact over dropped slots
        std::memmove(ring_data + dst * slot_size,
                     ring_data + src * slot_size,
                     static_cast<size_t>(len));
      ring_len[dst] = len;
      ring_arrival[dst] = now_ms;
      // preserve the ring's zero-padded-slot invariant (a reused slot
      // would otherwise leak its previous occupant's bytes past len into
      // the device prefix staging)
      if (len < slot_size)
        std::memset(ring_data + dst * slot_size + len, 0,
                    static_cast<size_t>(slot_size - len));
      ++wrote;
    }
    *head += wrote;
    total += wrote;
    processed += n;
    if (wrote) {
      stat_add(g_stat.recv_datagrams, wrote);
      stat_add(g_stat.recv_bytes, admitted_bytes);
    }
    if (n < want) break;
  }
  return total;
}

int64_t ed_udp_drain_ex(const int32_t *fds, int32_t n_fds,
                        int64_t *out_bytes) {
  // Zero-length iovecs + MSG_TRUNC: recvmmsg consumes each datagram but
  // copies no payload bytes, while msg_len still reports the true datagram
  // size — so a UDP_GRO receiver can account coalesced super-datagrams
  // (bytes / segment-size = wire packets) without touching the payload.
  constexpr int kBatch = 128;
  mmsghdr msgs[kBatch];
  iovec iovs[kBatch];
  for (int i = 0; i < kBatch; ++i) {
    iovs[i].iov_base = nullptr;
    iovs[i].iov_len = 0;
  }
  int64_t total = 0;
  int64_t bytes = 0;
  for (int32_t f = 0; f < n_fds; ++f) {
    for (;;) {
      for (int i = 0; i < kBatch; ++i) {
        std::memset(&msgs[i], 0, sizeof(mmsghdr));
        msgs[i].msg_hdr.msg_iov = &iovs[i];
        msgs[i].msg_hdr.msg_iovlen = 1;
      }
      int n = recvmmsg(fds[f], msgs, kBatch, MSG_DONTWAIT | MSG_TRUNC,
                       nullptr);
      if (n < 0) {
        if (errno == EINTR) continue;
        break;  // EAGAIN or a dead socket: move on
      }
      if (n == 0) break;
      total += n;
      for (int i = 0; i < n; ++i) bytes += msgs[i].msg_len;
      if (n < kBatch) break;
    }
  }
  if (out_bytes) *out_bytes = bytes;
  return total;
}

int64_t ed_udp_drain(const int32_t *fds, int32_t n_fds) {
  return ed_udp_drain_ex(fds, n_fds, nullptr);
}

}  // extern "C"

/* ---------------------------------------------------- io_uring backend */
//
// Raw-syscall io_uring (no liburing dependency) with self-defined ABI
// structs: the kernel ABI is frozen, while this box's <linux/io_uring.h>
// predates SEND_ZC/multishot — defining the layouts here means one
// source builds identically against any header vintage, and the runtime
// capability PROBE (not compile-time ifdefs) decides what is used.
// Shares g_stat / g_stop_errno / fault_egress_gate with the sendmmsg
// paths so the accounting contract and the chaos knobs are identical
// across backends.

namespace {

#ifndef __NR_io_uring_setup
#define __NR_io_uring_setup 425
#define __NR_io_uring_enter 426
#define __NR_io_uring_register 427
#endif

// setup flags
constexpr uint32_t kSetupSqpoll = 1u << 1;
constexpr uint32_t kSetupCqsize = 1u << 3;
constexpr uint32_t kSetupClamp = 1u << 4;
// features
constexpr uint32_t kFeatSingleMmap = 1u << 0;
constexpr uint32_t kFeatNodrop = 1u << 1;
// mmap offsets
constexpr uint64_t kOffSqRing = 0;
constexpr uint64_t kOffCqRing = 0x8000000ULL;
constexpr uint64_t kOffSqes = 0x10000000ULL;
// sq ring flags
constexpr uint32_t kSqNeedWakeup = 1u << 0;
// enter flags
constexpr uint32_t kEnterGetevents = 1u << 0;
constexpr uint32_t kEnterSqWakeup = 1u << 1;
// register opcodes
constexpr uint32_t kRegBuffers = 0;
constexpr uint32_t kRegProbe = 8;
// sqe flags
constexpr uint8_t kSqeIoLink = 1u << 2;
constexpr uint8_t kSqeBufferSelect = 1u << 4;
// opcodes (ABI-stable ids)
constexpr uint8_t kOpNop = 0;
constexpr uint8_t kOpSendmsg = 9;
constexpr uint8_t kOpRecvmsg = 10;
constexpr uint8_t kOpProvideBuffers = 31;
// (26 and 30, as first written here, are IORING_OP_SEND and
// IORING_OP_SPLICE: the probe then granted "zerocopy" on every 5.6+
// kernel and each send carried SEND_ZC ioprio flags on a plain SEND,
// which 6.x kernels reject with EINVAL — a hard per-datagram error)
constexpr uint8_t kOpSendZc = 47;
constexpr uint8_t kOpSendmsgZc = 48;
// cqe flags
constexpr uint32_t kCqeFBuffer = 1u << 0;
constexpr uint32_t kCqeFMore = 1u << 1;
constexpr uint32_t kCqeFNotif = 1u << 3;
constexpr uint32_t kCqeBufferShift = 16;
// sqe->ioprio flags for send/recv ops (IORING_RECVSEND_POLL_FIRST is
// 1<<0 — NOT used here; a review pass caught FIXED_BUF mis-assigned to
// that bit, which would have silently pinned pages per send)
constexpr uint16_t kRecvMultishot = 1u << 1;      // multishot recvmsg
constexpr uint16_t kRecvsendFixedBuf = 1u << 2;   // SEND_ZC fixed buffer
constexpr uint16_t kSendZcReportUsage = 1u << 3;  // notif res carries copy bit
constexpr uint32_t kNotifUsageZcCopied = 1u << 31;
// probe op flag
constexpr uint16_t kOpSupported = 1u << 0;

struct EdSqe {  // struct io_uring_sqe (64 bytes, unioned fields flattened)
  uint8_t opcode;
  uint8_t flags;
  uint16_t ioprio;
  int32_t fd;
  uint64_t off;        // off / addr2 (SEND_ZC: sockaddr pointer)
  uint64_t addr;       // buffer / msghdr pointer
  uint32_t len;
  uint32_t op_flags;   // msg_flags / rw_flags / ...
  uint64_t user_data;
  uint16_t buf_index;  // fixed-buffer index / buf_group
  uint16_t personality;
  uint16_t addr_len;   // SEND_ZC: sockaddr length (low half of splice_fd_in)
  uint16_t pad1;
  uint64_t addr3;
  uint64_t pad2;
};
static_assert(sizeof(EdSqe) == 64, "io_uring_sqe ABI is 64 bytes");

struct EdCqe {  // struct io_uring_cqe
  uint64_t user_data;
  int32_t res;
  uint32_t flags;
};
static_assert(sizeof(EdCqe) == 16, "io_uring_cqe ABI is 16 bytes");

struct EdSqOffsets {
  uint32_t head, tail, ring_mask, ring_entries, flags, dropped, array, resv1;
  uint64_t user_addr;
};
struct EdCqOffsets {
  uint32_t head, tail, ring_mask, ring_entries, overflow, cqes, flags, resv1;
  uint64_t user_addr;
};
struct EdUringParams {
  uint32_t sq_entries, cq_entries, flags, sq_thread_cpu, sq_thread_idle,
      features, wq_fd, resv[3];
  EdSqOffsets sq_off;
  EdCqOffsets cq_off;
};
static_assert(sizeof(EdUringParams) == 120, "io_uring_params ABI");

struct EdProbeOp {
  uint8_t op, resv;
  uint16_t flags;
  uint32_t resv2;
};
struct EdProbe {
  uint8_t last_op, ops_len;
  uint16_t resv;
  uint32_t resv2[3];
  EdProbeOp ops[256];
};

inline int sys_uring_setup(unsigned entries, EdUringParams *p) {
  return static_cast<int>(syscall(__NR_io_uring_setup, entries, p));
}
inline int sys_uring_enter(int fd, unsigned to_submit, unsigned min_complete,
                           unsigned flags) {
  return static_cast<int>(syscall(__NR_io_uring_enter, fd, to_submit,
                                  min_complete, flags, nullptr, 0));
}
inline int sys_uring_register(int fd, unsigned opcode, const void *arg,
                              unsigned nr_args) {
  return static_cast<int>(syscall(__NR_io_uring_register, fd, opcode, arg,
                                  nr_args));
}

// multishot recvmsg payload header (struct io_uring_recvmsg_out)
struct EdRecvmsgOut {
  uint32_t namelen, controllen, payloadlen, flags;
};

inline uint32_t aload(const unsigned *p) {
  return __atomic_load_n(p, __ATOMIC_ACQUIRE);
}
inline void rstore(unsigned *p, uint32_t v) {
  __atomic_store_n(p, v, __ATOMIC_RELEASE);
}

}  // namespace

// One mapped ring + its arenas.  Lives outside the anonymous namespace
// because the public API hands out `ed_uring *`.
struct ed_uring {
  int ring_fd = -1;
  int sock_fd = -1;
  int caps = 0;          // ED_URING_CAP_* actually active on this ring
  bool sqpoll = false;
  bool zerocopy = false;
  uint32_t features = 0;
  unsigned sq_entries = 0, cq_entries = 0;
  // mappings
  void *sq_ptr = nullptr;
  size_t sq_map_sz = 0;
  void *cq_ptr = nullptr;   // == sq_ptr under FEAT_SINGLE_MMAP
  size_t cq_map_sz = 0;
  EdSqe *sqes = nullptr;
  size_t sqes_sz = 0;
  // ring pointers (into the mappings)
  unsigned *sq_head = nullptr, *sq_tail = nullptr, *sq_mask = nullptr,
           *sq_array = nullptr, *sq_flags = nullptr;
  unsigned *cq_head = nullptr, *cq_tail = nullptr, *cq_mask = nullptr;
  EdCqe *cqes = nullptr;
  unsigned queued = 0;   // SQEs filled via get_sqe, published by submit()
  // egress arenas, sized to sq_entries ops in flight
  int32_t max_pkt = 0;
  std::vector<uint8_t> arena;        // rendered packets / headers
  bool arena_registered = false;     // arena is fixed-buffer index 0
  std::vector<iovec> iovs;           // 2 per op (hdr | payload)
  std::vector<msghdr> msgs;
  std::vector<sockaddr_in> addrs;
  std::vector<int32_t> results;      // per-chain-index CQE res
  int zc_pending = 0;                // ZC notifs not yet reaped
  // ingest state
  bool ingest = false;
  int32_t n_bufs = 0;
  std::vector<uint8_t> recv_bufs;    // n_bufs x (16B hdr + max_pkt)
  msghdr recv_msg{};                 // multishot template
  bool armed = false;

  ~ed_uring() {
    if (sq_ptr) munmap(sq_ptr, sq_map_sz);
    if (cq_ptr && cq_ptr != sq_ptr) munmap(cq_ptr, cq_map_sz);
    if (sqes) munmap(sqes, sqes_sz);
    if (ring_fd >= 0) close(ring_fd);
  }
};

namespace {

constexpr unsigned kProbeEntries = 8;
constexpr int32_t kDepthMin = 16, kDepthMax = 1024;
constexpr int kCqSpin = 4096;  // SQPOLL userspace completion spins

// mmap the three ring regions; returns 0 or -errno (ring_fd stays owned
// by the caller's ed_uring and is closed by its destructor).
int map_ring(ed_uring *u, const EdUringParams &p) {
  u->features = p.features;
  u->sq_entries = p.sq_entries;
  u->cq_entries = p.cq_entries;
  size_t sq_sz = p.sq_off.array + p.sq_entries * sizeof(uint32_t);
  size_t cq_sz = p.cq_off.cqes + p.cq_entries * sizeof(EdCqe);
  if (p.features & kFeatSingleMmap) sq_sz = cq_sz = std::max(sq_sz, cq_sz);
  void *sq = mmap(nullptr, sq_sz, PROT_READ | PROT_WRITE,
                  MAP_SHARED | MAP_POPULATE, u->ring_fd, kOffSqRing);
  if (sq == MAP_FAILED) return -errno;
  u->sq_ptr = sq;
  u->sq_map_sz = sq_sz;
  if (p.features & kFeatSingleMmap) {
    u->cq_ptr = sq;
    u->cq_map_sz = sq_sz;
  } else {
    void *cq = mmap(nullptr, cq_sz, PROT_READ | PROT_WRITE,
                    MAP_SHARED | MAP_POPULATE, u->ring_fd, kOffCqRing);
    if (cq == MAP_FAILED) return -errno;
    u->cq_ptr = cq;
    u->cq_map_sz = cq_sz;
  }
  size_t sqes_sz = p.sq_entries * sizeof(EdSqe);
  void *sqes = mmap(nullptr, sqes_sz, PROT_READ | PROT_WRITE,
                    MAP_SHARED | MAP_POPULATE, u->ring_fd, kOffSqes);
  if (sqes == MAP_FAILED) return -errno;
  u->sqes = static_cast<EdSqe *>(sqes);
  u->sqes_sz = sqes_sz;
  auto *sqb = static_cast<uint8_t *>(u->sq_ptr);
  u->sq_head = reinterpret_cast<unsigned *>(sqb + p.sq_off.head);
  u->sq_tail = reinterpret_cast<unsigned *>(sqb + p.sq_off.tail);
  u->sq_mask = reinterpret_cast<unsigned *>(sqb + p.sq_off.ring_mask);
  u->sq_flags = reinterpret_cast<unsigned *>(sqb + p.sq_off.flags);
  u->sq_array = reinterpret_cast<unsigned *>(sqb + p.sq_off.array);
  auto *cqb = static_cast<uint8_t *>(u->cq_ptr);
  u->cq_head = reinterpret_cast<unsigned *>(cqb + p.cq_off.head);
  u->cq_tail = reinterpret_cast<unsigned *>(cqb + p.cq_off.tail);
  u->cq_mask = reinterpret_cast<unsigned *>(cqb + p.cq_off.ring_mask);
  u->cqes = reinterpret_cast<EdCqe *>(cqb + p.cq_off.cqes);
  return 0;
}

// Queue one SQE (caller fills the returned slot; published by the next
// submit()).  The SQ is always drained before the next batch, so a full
// queue cannot happen by construction — nullptr-guarded anyway.
EdSqe *get_sqe(ed_uring *u) {
  uint32_t head = aload(u->sq_head);
  uint32_t tail = *u->sq_tail + u->queued;  // single submitter: plain read
  if (tail - head >= u->sq_entries) return nullptr;
  uint32_t idx = tail & *u->sq_mask;
  u->sq_array[idx] = idx;
  EdSqe *sqe = &u->sqes[idx];
  std::memset(sqe, 0, sizeof(*sqe));
  u->queued++;
  return sqe;
}

// The last SQE queued since the last submit (for terminating a link
// chain).  Only valid while queued > 0.
EdSqe *last_sqe(ed_uring *u) {
  return &u->sqes[(*u->sq_tail + u->queued - 1) & *u->sq_mask];
}

// Publish every queued SQE and issue (or skip, under SQPOLL) the submit
// syscall.  wait_for > 0 blocks until that many CQEs are available.
// Returns 0 or -errno from io_uring_enter.
int submit(ed_uring *u, unsigned wait_for) {
  unsigned n = u->queued;
  u->queued = 0;
  rstore(u->sq_tail, *u->sq_tail + n);
  stat_add(g_stat.uring_sqes, n);
  unsigned flags = 0;
  unsigned to_submit = n;
  if (u->sqpoll) {
    // the poller thread consumes the SQ; only a sleeping poller needs a
    // syscall — the "steady-state wire pushes need zero syscalls" leg
    if (aload(u->sq_flags) & kSqNeedWakeup) flags |= kEnterSqWakeup;
    else if (wait_for == 0) return 0;
    to_submit = 0;
  }
  if (wait_for > 0) flags |= kEnterGetevents;
  for (;;) {
    int r = sys_uring_enter(u->ring_fd, to_submit, wait_for, flags);
    if (r >= 0) {
      stat_add(g_stat.uring_submits, 1);
      return 0;
    }
    if (errno == EINTR) continue;
    return -errno;
  }
}

// Pop every available CQE through `fn(cqe)`; returns the count reaped.
template <typename Fn>
int reap_available(ed_uring *u, Fn &&fn) {
  uint32_t head = *u->cq_head;
  uint32_t tail = aload(u->cq_tail);
  int n = 0;
  while (head != tail) {
    const EdCqe &cqe = u->cqes[head & *u->cq_mask];
    fn(cqe);
    ++head;
    ++n;
  }
  if (n) {
    rstore(u->cq_head, head);
    stat_add(g_stat.uring_cqes, n);
  }
  return n;
}

// Reap until `pred()` is satisfied, entering the kernel as needed.
// Under SQPOLL a bounded userspace spin usually observes the completion
// without any syscall.  Bounded (a CQE lost to pre-NODROP overflow must
// surface as -EIO, not a hung pump).  Returns 0 or -errno.
template <typename Fn, typename Pred>
int reap_until(ed_uring *u, Fn &&fn, Pred &&pred) {
  for (int rounds = 0; rounds < 100000; ++rounds) {
    reap_available(u, fn);
    if (pred()) return 0;
    if (u->sqpoll) {
      bool got = false;
      for (int i = 0; i < kCqSpin && !got; ++i)
        got = aload(u->cq_tail) != *u->cq_head;
      if (got) continue;
    }
    for (;;) {
      int r = sys_uring_enter(u->ring_fd, 0, 1, kEnterGetevents);
      if (r >= 0) {
        stat_add(g_stat.uring_submits, 1);
        break;
      }
      if (errno == EINTR) continue;
      return -errno;
    }
  }
  return -EIO;
}

// Drain outstanding zerocopy notification CQEs so the arena slots (and
// the ring slots the kernel may still reference) are reusable when the
// caller returns — registered-buffer lifetime is serialized with the
// send call instead of with ring recycling (ARCHITECTURE "Egress
// backends" discusses the tradeoff).
int drain_zc_notifs(ed_uring *u) {
  auto on_cqe = [u](const EdCqe &cqe) {
    if (cqe.flags & kCqeFNotif) {
      u->zc_pending--;
      stat_add(g_stat.uring_zc_completions, 1);
      if (cqe.res & static_cast<int32_t>(kNotifUsageZcCopied))
        stat_add(g_stat.uring_zc_copied, 1);
    }
  };
  return reap_until(u, on_cqe, [u] { return u->zc_pending <= 0; });
}

int probe_ops(int ring_fd, EdProbe *probe) {
  std::memset(probe, 0, sizeof(*probe));
  return sys_uring_register(ring_fd, kRegProbe, probe, 256) < 0 ? -errno : 0;
}

bool op_supported(const EdProbe &p, uint8_t op) {
  return op <= p.last_op && (p.ops[op].flags & kOpSupported);
}

}  // namespace

extern "C" {

int32_t ed_uring_probe(void) {
  EdUringParams params;
  std::memset(&params, 0, sizeof(params));
  params.flags = kSetupClamp;
  int fd = sys_uring_setup(kProbeEntries, &params);
  if (fd < 0) return -errno;  // ENOSYS / seccomp EPERM / EMFILE
  int32_t caps = ED_URING_CAP_RING;
  EdProbe probe;
  if (probe_ops(fd, &probe) == 0) {
    if (!op_supported(probe, kOpSendmsg) ||
        !op_supported(probe, kOpRecvmsg)) {
      close(fd);
      return -ENOSYS;  // a ring without sendmsg/recvmsg is useless here
    }
    if (op_supported(probe, kOpSendmsgZc)) caps |= ED_URING_CAP_SEND_ZC;
    // multishot recvmsg (6.0) predates SEND_ZC (6.0/6.1) — the ZC probe
    // doubles as the multishot gate (no direct probe exists for flags)
    if (op_supported(probe, kOpSendZc) &&
        op_supported(probe, kOpProvideBuffers))
      caps |= ED_URING_CAP_RECV_MULTI;
  } else {
    // REGISTER_PROBE itself needs 5.6; a ring that predates it has
    // sendmsg/recvmsg (5.3) but none of the newer toys
  }
  // fixed buffers: one page under the current RLIMIT_MEMLOCK — the
  // registration either fits or the backend runs unregistered
  static uint8_t page[4096] __attribute__((aligned(4096)));
  iovec iov{page, sizeof(page)};
  if (sys_uring_register(fd, kRegBuffers, &iov, 1) == 0)
    caps |= ED_URING_CAP_FIXED_BUFS;
  close(fd);
  // SQPOLL needs its own setup (the flag changes ring construction);
  // modern kernels allow unprivileged SQPOLL, old ones want CAP_SYS_NICE
  EdUringParams sp;
  std::memset(&sp, 0, sizeof(sp));
  sp.flags = kSetupClamp | kSetupSqpoll;
  sp.sq_thread_idle = 50;  // ms before the poller sleeps
  int sfd = sys_uring_setup(kProbeEntries, &sp);
  if (sfd >= 0) {
    caps |= ED_URING_CAP_SQPOLL;
    close(sfd);
  }
  return caps;
}

ed_uring *ed_uring_egress_new(int fd, int32_t depth, int32_t max_pkt,
                              int32_t flags, int32_t *err_out) {
  auto fail = [err_out](int err) -> ed_uring * {
    if (err_out) *err_out = err < 0 ? err : -err;
    return nullptr;
  };
  if (max_pkt < 64 || max_pkt > 65536) return fail(EINVAL);
  depth = std::max(kDepthMin, std::min(kDepthMax, depth));
  int32_t caps = ed_uring_probe();
  if (caps < 0) return fail(caps);
  auto u = new ed_uring();
  u->sock_fd = fd;
  u->max_pkt = max_pkt;
  u->sqpoll = (flags & ED_URING_F_SQPOLL) && (caps & ED_URING_CAP_SQPOLL);
  u->zerocopy = (flags & ED_URING_F_ZEROCOPY) &&
                (caps & ED_URING_CAP_SEND_ZC) &&
                (caps & ED_URING_CAP_FIXED_BUFS);
  EdUringParams params;
  std::memset(&params, 0, sizeof(params));
  params.flags = kSetupClamp | kSetupCqsize;
  // ZC posts two CQEs per send (completion + notif); 4x headroom keeps
  // NODROP kernels from stalling and pre-NODROP kernels from dropping
  params.cq_entries = static_cast<uint32_t>(depth) * 4;
  if (u->sqpoll) {
    params.flags |= kSetupSqpoll;
    params.sq_thread_idle = 50;
  }
  int rfd = sys_uring_setup(static_cast<unsigned>(depth), &params);
  if (rfd < 0 && u->sqpoll) {
    // SQPOLL passed the probe but failed with these params (rlimits,
    // cgroup cpu policy): degrade to interrupt-driven, not to GSO
    u->sqpoll = false;
    params.flags &= ~kSetupSqpoll;
    rfd = sys_uring_setup(static_cast<unsigned>(depth), &params);
  }
  if (rfd < 0) {
    int e = -errno;
    delete u;
    return fail(e);
  }
  u->ring_fd = rfd;
  int mr = map_ring(u, params);
  if (mr < 0) {
    delete u;
    return fail(mr);
  }
  // The send arena: every in-flight datagram's rendered bytes live here
  // (ZC: full packet; SENDMSG: the 12-byte header, payload iovec'd from
  // the packet ring).  Registered as fixed buffer 0 when the memlock
  // budget allows, which is what lets SEND_ZC pin pages once instead of
  // per send.  Sized from sq_entries, NOT the requested depth: the
  // kernel rounds the ring up to a power of two and ed_uring_send
  // chains up to sq_entries ops — arenas sized to a smaller requested
  // depth would overflow on the rounded-up tail.
  const size_t entries = u->sq_entries;
  u->arena.assign(entries * max_pkt, 0);
  if (caps & ED_URING_CAP_FIXED_BUFS) {
    iovec iov{u->arena.data(), u->arena.size()};
    if (sys_uring_register(rfd, kRegBuffers, &iov, 1) == 0)
      u->arena_registered = true;
    else if (errno == ENOMEM || errno == EPERM)
      u->zerocopy = false;  // RLIMIT_MEMLOCK too small for the real arena
    else
      u->zerocopy = false;
  } else {
    u->zerocopy = false;
  }
  u->iovs.resize(entries * 2);
  u->msgs.resize(entries);
  u->addrs.resize(entries);
  u->results.resize(entries);
  u->caps = (caps & (ED_URING_CAP_RING | ED_URING_CAP_SEND_ZC |
                     ED_URING_CAP_RECV_MULTI)) |
            (u->sqpoll ? ED_URING_CAP_SQPOLL : 0) |
            (u->arena_registered ? ED_URING_CAP_FIXED_BUFS : 0);
  if (err_out) *err_out = 0;
  return u;
}

void ed_uring_free(ed_uring *u) {
  if (!u) return;
  if (u->zc_pending > 0) drain_zc_notifs(u);
  delete u;
}

int32_t ed_uring_caps(const ed_uring *u) { return u ? u->caps : 0; }

int32_t ed_uring_fd(const ed_uring *u) { return u ? u->ring_fd : -1; }

int32_t ed_uring_send(ed_uring *u, const uint8_t *ring_data,
                      const int32_t *ring_len, int32_t capacity,
                      int32_t slot_size, const uint32_t *seq_off,
                      const uint32_t *ts_off, const uint32_t *ssrc,
                      const ed_dest *dest, int32_t n_outs,
                      const ed_sendop *ops, int32_t n_ops) {
  if (!u || u->ingest) return -EINVAL;
  g_stop_errno = 0;
  if (n_ops <= 0) return 0;
  StatTimer timer(g_stat.send_ns);
  const int depth = static_cast<int>(u->sq_entries);
  int32_t done = 0;
  while (done < n_ops) {
    int ferr = fault_egress_gate();
    if (ferr) {
      // injected fault surfaces through the SAME completion-path
      // bookkeeping a real first-CQE failure takes: count the submit,
      // classify the stop, honor the EAGAIN-vs-hard return contract
      g_stop_errno = ferr;
      stat_add(g_stat.uring_submits, 1);
      note_send_stop(ferr);
      if (ferr == EAGAIN) return done;
      return done > 0 ? done : -ferr;
    }
    // A mid-chain validation failure must DISCARD the SQEs queued so
    // far (u->queued = 0 un-publishes them — the tail was never
    // advanced) or the next submission would publish stale entries
    // whose arena/msghdr slots have been reused: duplicate datagrams
    // with colliding user_data.  g_stop_errno = EINVAL makes a partial
    // return read as a hard per-datagram stop, so the caller skips the
    // poisoned op instead of replaying it forever.
    auto abort_chain = [&](int err) -> int32_t {
      u->queued = 0;
      g_stop_errno = err;
      return done > 0 ? done : -err;
    };
    int chain = 0;
    for (; chain < depth && done + chain < n_ops; ++chain) {
      const ed_sendop &op = ops[done + chain];
      if (op.slot < 0 || op.slot >= capacity || op.out < 0 ||
          op.out >= n_outs)
        return abort_chain(EINVAL);
      const uint8_t *pkt = ring_data + static_cast<size_t>(op.slot) * slot_size;
      int32_t len = ring_len[op.slot];
      if (len < 12 || len > slot_size || len > u->max_pkt)
        return abort_chain(EINVAL);
      uint8_t *slot_arena =
          u->arena.data() + static_cast<size_t>(chain) * u->max_pkt;
      sockaddr_in &sa = u->addrs[chain];
      std::memset(&sa, 0, sizeof(sa));
      sa.sin_family = AF_INET;
      sa.sin_addr.s_addr = dest[op.out].ip_be;
      sa.sin_port = dest[op.out].port_be;
      EdSqe *sqe = get_sqe(u);
      if (!sqe) return abort_chain(EBUSY);  // cannot happen: SQ drained
      if (u->zerocopy) {
        // render the whole datagram into the registered arena and send
        // it as ONE fixed-buffer SEND_ZC: the kernel pins the
        // pre-registered pages instead of copying payload into skb
        // frags — the copy that remains is ours, at cache speed, once
        render_header(slot_arena, pkt, seq_off[op.out], ts_off[op.out],
                      ssrc[op.out]);
        std::memcpy(slot_arena + 12, pkt + 12,
                    static_cast<size_t>(len - 12));
        sqe->opcode = kOpSendZc;
        sqe->fd = u->sock_fd;
        sqe->addr = reinterpret_cast<uint64_t>(slot_arena);
        sqe->len = static_cast<uint32_t>(len);
        sqe->op_flags = MSG_DONTWAIT;
        sqe->ioprio = kRecvsendFixedBuf | kSendZcReportUsage;
        sqe->buf_index = 0;
        sqe->off = reinterpret_cast<uint64_t>(&sa);  // addr2 = dest
        sqe->addr_len = sizeof(sa);
      } else {
        // header in the arena, payload straight from the packet ring —
        // the same scatter shape the sendmmsg path uses, minus the
        // per-datagram syscall slot
        render_header(slot_arena, pkt, seq_off[op.out], ts_off[op.out],
                      ssrc[op.out]);
        iovec *iv = &u->iovs[static_cast<size_t>(chain) * 2];
        iv[0].iov_base = slot_arena;
        iv[0].iov_len = 12;
        iv[1].iov_base = const_cast<uint8_t *>(pkt) + 12;
        iv[1].iov_len = static_cast<size_t>(len - 12);
        msghdr &m = u->msgs[chain];
        std::memset(&m, 0, sizeof(m));
        m.msg_name = &sa;
        m.msg_namelen = sizeof(sa);
        m.msg_iov = iv;
        m.msg_iovlen = 2;
        sqe->opcode = kOpSendmsg;
        sqe->fd = u->sock_fd;
        sqe->addr = reinterpret_cast<uint64_t>(&m);
        sqe->op_flags = MSG_DONTWAIT;
      }
      // IOSQE_IO_LINK serializes the chain in the kernel: a failure
      // cancels everything after it, so "ops delivered" is a PREFIX of
      // the chain and bookmark replay can never duplicate a datagram
      sqe->flags |= kSqeIoLink;
      sqe->user_data = static_cast<uint64_t>(chain);
    }
    last_sqe(u)->flags &=
        static_cast<uint8_t>(~kSqeIoLink);  // last link terminates chain
    std::fill(u->results.begin(), u->results.begin() + chain, INT32_MIN);
    int pending = chain;
    int zc_expected = 0;
    auto on_cqe = [&](const EdCqe &cqe) {
      if (cqe.flags & kCqeFNotif) {
        u->zc_pending--;
        stat_add(g_stat.uring_zc_completions, 1);
        if (cqe.res & static_cast<int32_t>(kNotifUsageZcCopied))
          stat_add(g_stat.uring_zc_copied, 1);
        return;
      }
      int idx = static_cast<int>(cqe.user_data);
      if (idx >= 0 && idx < chain && u->results[idx] == INT32_MIN) {
        u->results[idx] = cqe.res;
        pending--;
        if (cqe.flags & kCqeFMore) {  // ZC: a notif will follow
          u->zc_pending++;
          zc_expected++;
        }
      }
    };
    // SQPOLL: publish and let reap_until's bounded spin observe the
    // completions — the steady-state zero-syscall path.  Interrupt-
    // driven rings wait for the whole chain in the submit itself.
    int sr = submit(u, u->sqpoll ? 0 : static_cast<unsigned>(chain));
    if (sr < 0) {
      g_stop_errno = -sr;
      note_send_stop(-sr);
      return done > 0 ? done : sr;
    }
    int rr = reap_until(u, on_cqe, [&] { return pending <= 0; });
    if (rr < 0) {
      g_stop_errno = -rr;
      note_send_stop(-rr);
      return done > 0 ? done : rr;
    }
    // ops delivered = prefix of successes (linked execution order)
    int k = 0;
    int stop_err = 0;
    for (; k < chain; ++k) {
      int32_t res = u->results[k];
      if (res < 0) {
        stop_err = -res;  // first failure in chain order = the stop errno
        break;
      }
    }
    if (k > 0) {
      int64_t nb = 0;
      for (int i = 0; i < k; ++i) nb += ring_len[ops[done + i].slot];
      stat_add(g_stat.send_packets, k);
      stat_add(g_stat.bytes_to_wire, nb);
    }
    // ZC buffer lifetime: wait out the notifications before the arena
    // (and the ring slots) can be touched again
    if (u->zc_pending > 0) {
      int dr = drain_zc_notifs(u);
      if (dr < 0 && k == 0 && done == 0) return dr;
    }
    done += k;
    if (k < chain) {
      g_stop_errno = stop_err;
      note_send_stop(stop_err);
      if (stop_err == EAGAIN || stop_err == EWOULDBLOCK)
        return done;  // flow control: caller keeps its bookmark
      return done > 0 ? done : -stop_err;
    }
  }
  return done;
}

int32_t ed_uring_send_multi(ed_uring *u, const uint8_t *ring_data,
                            const int32_t *ring_len, int32_t capacity,
                            int32_t slot_size, const uint32_t *seq_off,
                            const uint32_t *ts_off, const uint32_t *ssrc,
                            int32_t n_src, int32_t param_stride,
                            const ed_dest *dest, int32_t n_outs,
                            const ed_sendop *ops, int32_t n_ops) {
  if (param_stride < n_outs) return -EINVAL;
  int64_t total = 0;
  for (int32_t s = 0; s < n_src; ++s) {
    const uint32_t *sq = seq_off + static_cast<size_t>(s) * param_stride;
    const uint32_t *ts = ts_off + static_cast<size_t>(s) * param_stride;
    const uint32_t *sc = ssrc + static_cast<size_t>(s) * param_stride;
    int32_t r = ed_uring_send(u, ring_data, ring_len, capacity, slot_size,
                              sq, ts, sc, dest, n_outs, ops, n_ops);
    if (r < 0) return total > 0 ? static_cast<int32_t>(total) : r;
    total += r;
  }
  return static_cast<int32_t>(total);
}

// One SEND SQE over the FIRST `chunk` bytes of the ring's arena: a TCP
// stream is a byte sequence, so one send of N framed packets is
// wire-identical to per-packet writes — and a short completion is
// simply a byte count, with none of the torn-chain hazard linked
// per-packet SQEs would have (a partial SENDMSG counts as SUCCESS and
// would not cancel its link).  `fd` rides the SQE itself, so one
// shared ring serves every stream socket.  The caller renders/copies
// into the arena BEFORE the call; this submits without touching the
// bytes.  Returns bytes the kernel took, or -errno when nothing was.
static int64_t uring_arena_submit(ed_uring *u, int fd, size_t chunk) {
  int ferr = fault_egress_gate();
  if (ferr) {
    g_stop_errno = ferr;
    stat_add(g_stat.uring_submits, 1);
    note_send_stop(ferr);
    return ferr == EAGAIN ? 0 : -ferr;
  }
  iovec *iv = &u->iovs[0];
  iv->iov_base = u->arena.data();
  iv->iov_len = chunk;
  msghdr &m = u->msgs[0];
  std::memset(&m, 0, sizeof(m));
  m.msg_iov = iv;
  m.msg_iovlen = 1;
  EdSqe *sqe = get_sqe(u);
  if (!sqe) {
    g_stop_errno = EBUSY;
    return -EBUSY;
  }
  sqe->opcode = kOpSendmsg;
  sqe->fd = fd;
  sqe->addr = reinterpret_cast<uint64_t>(&m);
  sqe->op_flags = MSG_DONTWAIT | MSG_NOSIGNAL;
  sqe->user_data = 0xEDu;
  int32_t res = INT32_MIN;
  auto on_cqe = [&](const EdCqe &cqe) {
    if (cqe.flags & kCqeFNotif) {
      u->zc_pending--;
      stat_add(g_stat.uring_zc_completions, 1);
      return;
    }
    if (cqe.user_data == 0xEDu && res == INT32_MIN) res = cqe.res;
  };
  int sr = submit(u, u->sqpoll ? 0 : 1);
  if (sr < 0) {
    g_stop_errno = -sr;
    note_send_stop(-sr);
    return sr;
  }
  int rr = reap_until(u, on_cqe, [&] { return res != INT32_MIN; });
  if (rr < 0) {
    g_stop_errno = -rr;
    note_send_stop(-rr);
    return rr;
  }
  if (res < 0) {
    g_stop_errno = -res;
    note_send_stop(-res);
    if (res == -EAGAIN || res == -EWOULDBLOCK) return 0;
    return res;
  }
  stat_add(g_stat.stream_bytes, res);
  if (static_cast<size_t>(res) < chunk) {
    // short completion: stream send buffer full — flow control
    g_stop_errno = EAGAIN;
    stat_add(g_stat.eagain_stops, 1);
  }
  return res;
}

// External byte blob (HLS bodies): the one copy into the arena is
// unavoidable — the source buffer is not ours to register.
static int64_t uring_blob_send(ed_uring *u, int fd, const uint8_t *buf,
                               int64_t len) {
  if (!u || u->ingest) return -EINVAL;
  g_stop_errno = 0;
  if (len <= 0) return 0;
  StatTimer timer(g_stat.send_ns);
  const size_t arena_cap = u->arena.size();
  int64_t written = 0;
  while (written < len) {
    size_t chunk = std::min<size_t>(arena_cap,
                                    static_cast<size_t>(len - written));
    std::memcpy(u->arena.data(), buf + written, chunk);
    int64_t r = uring_arena_submit(u, fd, chunk);
    if (r < 0) break;
    written += r;
    if (static_cast<size_t>(r) < chunk) break;   // flow control
  }
  if (written == 0 && g_stop_errno && g_stop_errno != EAGAIN &&
      g_stop_errno != EWOULDBLOCK)
    return -g_stop_errno;
  return written;
}

int32_t ed_uring_stream_send(ed_uring *u, int fd,
                             const uint8_t *ring_data,
                             const int32_t *ring_len, int32_t capacity,
                             int32_t slot_size, uint32_t seq_off,
                             uint32_t ts_off, uint32_t ssrc,
                             int32_t channel, const int32_t *slots,
                             int32_t n_slots,
                             int32_t *partial_bytes_out) {
  if (partial_bytes_out) *partial_bytes_out = 0;
  if (!u || u->ingest) return -EINVAL;
  if (n_slots <= 0) return 0;
  if (channel < 0 || channel > 255) return -EINVAL;
  for (int32_t i = 0; i < n_slots; ++i) {
    int32_t slot = slots[i];
    if (slot < 0 || slot >= capacity) return -EINVAL;
    int32_t len = ring_len[slot];
    if (len < 12 || len > slot_size || len > 0xFFFF) return -EINVAL;
  }
  g_stop_errno = 0;
  StatTimer timer(g_stat.send_ns);
  // render framed packets DIRECTLY into the ring's arena, one
  // packet-boundary chunk per SEND SQE (no intermediate blob — the
  // payload bytes move once, ring → arena)
  const size_t arena_cap = u->arena.size();
  int32_t full = 0;
  int64_t partial = 0;
  int32_t i = 0;
  while (i < n_slots) {
    size_t chunk = 0;
    int32_t first = i;
    for (; i < n_slots; ++i) {
      int32_t slot = slots[i];
      const uint8_t *pkt = ring_data + static_cast<size_t>(slot) * slot_size;
      int32_t len = ring_len[slot];
      size_t framed = static_cast<size_t>(len) + 4;
      if (chunk + framed > arena_cap) {
        if (chunk == 0) {           // one packet larger than the arena
          g_stop_errno = EINVAL;
          return full > 0 ? full : -EINVAL;
        }
        break;                      // chunk full: submit what we have
      }
      uint8_t *h = u->arena.data() + chunk;
      h[0] = 0x24;
      h[1] = static_cast<uint8_t>(channel);
      h[2] = static_cast<uint8_t>(len >> 8);
      h[3] = static_cast<uint8_t>(len);
      render_header(h + 4, pkt, seq_off, ts_off, ssrc);
      std::memcpy(h + 16, pkt + 12, static_cast<size_t>(len - 12));
      chunk += framed;
    }
    int64_t w = uring_arena_submit(u, fd, chunk);
    if (w < 0) {
      if (full > 0) return full;
      if (partial_bytes_out) *partial_bytes_out = 0;
      return static_cast<int32_t>(w);
    }
    // walk the chunk's packets past the bytes the kernel took
    size_t acc = 0;
    int32_t j = first;
    while (j < i) {
      size_t framed = static_cast<size_t>(ring_len[slots[j]]) + 4;
      if (acc + framed > static_cast<size_t>(w)) break;
      acc += framed;
      ++j;
    }
    full += j - first;
    partial = w - static_cast<int64_t>(acc);
    if (static_cast<size_t>(w) < chunk) break;   // flow control stop
    partial = 0;
  }
  if (full) stat_add(g_stat.stream_packets, full);
  if (partial_bytes_out)
    *partial_bytes_out = static_cast<int32_t>(partial);
  return full;
}

int64_t ed_uring_stream_write(ed_uring *u, int fd, const uint8_t *buf,
                              int64_t len) {
  return uring_blob_send(u, fd, buf, len);
}

}  // extern "C"

namespace {

// Re-post drained ingest pool buffers and, when `rearm`, a fresh
// multishot RECVMSG; one submit covers both.  PROVIDE_BUFFERS ABI:
// fd = number of buffers, addr = base, len = per-buffer size, off =
// starting buffer id, buf_index = buffer group.  One-buffer posts keep
// the bid bookkeeping trivial (recycled bids are rarely contiguous).
int ingest_post(ed_uring *u, const std::vector<int> &bids, bool rearm) {
  const size_t stride = sizeof(EdRecvmsgOut) + u->max_pkt;
  for (int bid : bids) {
    EdSqe *sqe = get_sqe(u);
    if (!sqe) return -EBUSY;
    sqe->opcode = kOpProvideBuffers;
    sqe->fd = 1;
    sqe->addr = reinterpret_cast<uint64_t>(u->recv_bufs.data() +
                                           static_cast<size_t>(bid) * stride);
    sqe->len = static_cast<uint32_t>(stride);
    sqe->off = static_cast<uint64_t>(bid);
    sqe->buf_index = 0;  // buffer group id
    sqe->user_data = ~0ULL;  // bookkeeping sqe: ignored at reap
  }
  if (rearm) {
    EdSqe *sqe = get_sqe(u);
    if (!sqe) return -EBUSY;
    sqe->opcode = kOpRecvmsg;
    sqe->fd = u->sock_fd;
    sqe->addr = reinterpret_cast<uint64_t>(&u->recv_msg);
    sqe->op_flags = 0;
    sqe->flags |= kSqeBufferSelect;
    sqe->ioprio = kRecvMultishot;
    sqe->buf_index = 0;  // buf_group
    sqe->user_data = 1;  // the multishot anchor
    u->armed = true;
  }
  if (!u->queued) return 0;
  return submit(u, 0);
}

}  // namespace

extern "C" {

ed_uring *ed_uring_ingest_new(int fd, int32_t max_pkt, int32_t *err_out) {
  auto fail = [err_out](int err) -> ed_uring * {
    if (err_out) *err_out = err < 0 ? err : -err;
    return nullptr;
  };
  if (max_pkt < 64 || max_pkt > 65536) return fail(EINVAL);
  int32_t caps = ed_uring_probe();
  if (caps < 0) return fail(caps);
  if (!(caps & ED_URING_CAP_RECV_MULTI)) return fail(ENOSYS);
  auto u = new ed_uring();
  u->ingest = true;
  u->sock_fd = fd;
  u->max_pkt = max_pkt;
  u->n_bufs = 64;
  EdUringParams params;
  std::memset(&params, 0, sizeof(params));
  params.flags = kSetupClamp | kSetupCqsize;
  params.cq_entries = 256;  // a burst larger than the pool re-arms, never drops
  int rfd = sys_uring_setup(128, &params);
  if (rfd < 0) {
    int e = -errno;
    delete u;
    return fail(e);
  }
  u->ring_fd = rfd;
  int mr = map_ring(u, params);
  if (mr < 0) {
    delete u;
    return fail(mr);
  }
  const size_t stride = sizeof(EdRecvmsgOut) + max_pkt;
  u->recv_bufs.assign(static_cast<size_t>(u->n_bufs) * stride, 0);
  std::memset(&u->recv_msg, 0, sizeof(u->recv_msg));
  // msg_namelen/controllen = 0: the pool buffer carries only the 16-byte
  // io_uring_recvmsg_out header + payload (source addr is not demuxed
  // here — the server binds one ingest socket per pusher)
  std::vector<int> bids(u->n_bufs);
  for (int i = 0; i < u->n_bufs; ++i) bids[i] = i;
  int pr = ingest_post(u, bids, true);
  if (pr < 0) {
    delete u;
    return fail(pr);
  }
  u->caps = caps;
  if (err_out) *err_out = 0;
  return u;
}

int32_t ed_uring_ingest_drain(ed_uring *u, uint8_t *ring_data,
                              int32_t *ring_len, int64_t *ring_arrival,
                              int32_t capacity, int32_t slot_size,
                              int64_t now_ms, int64_t *head,
                              int32_t max_pkts, int32_t *oversize_dropped) {
  if (!u || !u->ingest) return -EINVAL;
  StatTimer timer(g_stat.ingest_ns);
  // flush task_work so completed datagrams become visible CQEs (the
  // multishot arm itself means no per-batch recvmsg submission)
  int er = sys_uring_enter(u->ring_fd, 0, 0, kEnterGetevents);
  if (er < 0 && errno != EINTR && errno != EAGAIN) return -errno;
  stat_add(g_stat.uring_submits, 1);
  const size_t stride = sizeof(EdRecvmsgOut) + u->max_pkt;
  int32_t admitted = 0;
  int64_t admitted_bytes = 0;
  bool rearm = false;
  std::vector<int> recycle;
  auto on_cqe = [&](const EdCqe &cqe) {
    if (cqe.user_data == ~0ULL) return;       // PROVIDE_BUFFERS ack
    if (!(cqe.flags & kCqeFMore)) rearm = true;
    if (cqe.res < 0) return;                  // ENOBUFS burst / transient
    if (!(cqe.flags & kCqeFBuffer)) return;
    int bid = static_cast<int>(cqe.flags >> kCqeBufferShift);
    if (bid < 0 || bid >= u->n_bufs) return;
    recycle.push_back(bid);
    const uint8_t *buf =
        u->recv_bufs.data() + static_cast<size_t>(bid) * stride;
    EdRecvmsgOut out;
    std::memcpy(&out, buf, sizeof(out));
    int32_t len = static_cast<int32_t>(out.payloadlen);
    if ((out.flags & MSG_TRUNC) || len > slot_size) {
      // kernel-truncated datagram: dropped, never admitted capped —
      // identical policy to the recvmmsg path
      if (oversize_dropped) ++*oversize_dropped;
      stat_add(g_stat.oversize_dropped, 1);
      return;
    }
    int64_t dst = (*head + admitted) % capacity;
    std::memcpy(ring_data + dst * slot_size, buf + sizeof(EdRecvmsgOut),
                static_cast<size_t>(len));
    if (len < slot_size)
      std::memset(ring_data + dst * slot_size + len, 0,
                  static_cast<size_t>(slot_size - len));
    ring_len[dst] = len;
    ring_arrival[dst] = now_ms;
    admitted_bytes += len;
    ++admitted;
  };
  // Budget-aware reap: STOP (cq_head un-advanced) at the first datagram
  // CQE past max_pkts so the excess genuinely stays for the next drain
  // call — reaping it and recycling its buffer unread would be silent,
  // uncounted packet loss (the recvmmsg path bounds intake inside the
  // syscall; this is the CQE-world equivalent).
  {
    uint32_t h = *u->cq_head;
    uint32_t tail = aload(u->cq_tail);
    int reaped = 0;
    while (h != tail) {
      const EdCqe &cqe = u->cqes[h & *u->cq_mask];
      if (admitted >= max_pkts && cqe.user_data != ~0ULL &&
          cqe.res >= 0 && (cqe.flags & kCqeFBuffer))
        break;
      on_cqe(cqe);
      ++h;
      ++reaped;
    }
    if (reaped) {
      rstore(u->cq_head, h);
      stat_add(g_stat.uring_cqes, reaped);
    }
  }
  *head += admitted;
  if (admitted) {
    stat_add(g_stat.recv_datagrams, admitted);
    stat_add(g_stat.recv_bytes, admitted_bytes);
  }
  if (!recycle.empty() || rearm) {
    int pr = ingest_post(u, recycle, rearm);
    if (pr < 0 && admitted == 0) return pr;
  }
  return admitted;
}

}  // extern "C"

extern "C" {

/* ------------------------------------------------------------- timer wheel */

struct ed_wheel {
  // 1 ms hashed wheel: 4096 buckets; overflow handled by re-hashing rounds.
  static constexpr int kSlots = 4096;
  struct Entry {
    int64_t id;
    int64_t fire_ms;
    int64_t user_data;
  };
  std::vector<Entry> slots[kSlots];
  std::map<int64_t, int> where;  // id -> slot (for cancel)
  int64_t now_ms;
  int64_t next_id = 1;
  int32_t pending = 0;
};

ed_wheel *ed_wheel_new(int64_t now_ms) {
  auto *w = new ed_wheel();
  w->now_ms = now_ms;
  return w;
}

void ed_wheel_free(ed_wheel *w) { delete w; }

int64_t ed_wheel_schedule(ed_wheel *w, int64_t delay_ms, int64_t user_data) {
  if (delay_ms < 0) delay_ms = 0;
  int64_t fire = w->now_ms + delay_ms;
  int slot = static_cast<int>(fire % ed_wheel::kSlots);
  int64_t id = w->next_id++;
  w->slots[slot].push_back({id, fire, user_data});
  w->where[id] = slot;
  w->pending++;
  return id;
}

int ed_wheel_cancel(ed_wheel *w, int64_t timer_id) {
  auto it = w->where.find(timer_id);
  if (it == w->where.end()) return 0;
  auto &vec = w->slots[it->second];
  for (auto e = vec.begin(); e != vec.end(); ++e) {
    if (e->id == timer_id) {
      vec.erase(e);
      w->where.erase(it);
      w->pending--;
      return 1;
    }
  }
  w->where.erase(it);
  return 0;
}

int32_t ed_wheel_advance(ed_wheel *w, int64_t now_ms, int64_t *out,
                         int32_t max_out) {
  int32_t fired = 0;
  if (now_ms <= w->now_ms) return 0;
  // bound the walk: never more than one full wheel revolution
  int64_t steps = now_ms - w->now_ms;
  if (steps > ed_wheel::kSlots) steps = ed_wheel::kSlots;
  // if we jumped more than a revolution, every slot needs a scan anyway
  for (int64_t t = 0; t < steps && fired < max_out; ++t) {
    int64_t tick = w->now_ms + 1 + t;
    auto &vec = w->slots[tick % ed_wheel::kSlots];
    for (size_t i = 0; i < vec.size() && fired < max_out;) {
      if (vec[i].fire_ms <= now_ms) {
        out[fired++] = vec[i].user_data;
        w->where.erase(vec[i].id);
        vec[i] = vec.back();
        vec.pop_back();
        w->pending--;
      } else {
        ++i;
      }
    }
  }
  w->now_ms = now_ms;
  return fired;
}

int64_t ed_wheel_next(const ed_wheel *w, int64_t now_ms) {
  int64_t best = -1;
  for (int s = 0; s < ed_wheel::kSlots; ++s) {
    for (const auto &e : w->slots[s]) {
      int64_t d = e.fire_ms - now_ms;
      if (d < 0) d = 0;
      if (best < 0 || d < best) best = d;
    }
  }
  if (best > 3600000) best = 3600000;
  return best;
}

int32_t ed_wheel_pending(const ed_wheel *w) { return w->pending; }

}  // extern "C"

