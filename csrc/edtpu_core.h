/* edtpu_core — native data-plane for easydarwin_tpu.
 *
 * C ABI consumed via ctypes (easydarwin_tpu/native.py).  Covers the pieces
 * the reference implements natively and that Python cannot do at line rate
 * (SURVEY §2.1): the reflector egress loop (SendPacketsToOutput /
 * RTPStream::Write — here one sendmmsg batch with per-packet affine header
 * render + shared-payload iovecs), the ingest socket pump
 * (ReflectorSocket::GetIncomingData — here recvmmsg straight into ring
 * slots), and the timer machinery (Task.cpp heap + 10 ms floor — here a
 * hashed wheel at 1 ms granularity).
 */
#ifndef EDTPU_CORE_H
#define EDTPU_CORE_H

#include <stdint.h>
#include <stddef.h>

#ifdef __cplusplus
extern "C" {
#endif

const char *ed_version(void);
/* "EDTPU_BUILD{<source digest>|<build cpu key>}" — see csrc/Makefile. */
const char *ed_build_info(void);

/* Why the calling thread's last send entry point stopped short of n_ops:
 * 0 = completed, EAGAIN/EWOULDBLOCK = flow control (keep bookmarks,
 * replay), anything else = a hard per-datagram error (skip past it —
 * the scalar oracle's WriteResult.ERROR advance).  Thread-local. */
int32_t ed_last_send_errno(void);

/* ---------------------------------------------------------------- stats */

/* Process-wide cumulative data-plane counters, maintained with relaxed
 * atomics on every egress/ingest entry point (negligible next to the
 * syscalls they count).  Python mirrors this snapshot into the obs
 * metric registry (easydarwin_tpu/obs) at scrape time.  The discard
 * drains (ed_udp_drain*) are bench receivers, not server ingest, and
 * are deliberately NOT counted. */
typedef struct {
  int64_t sendmmsg_calls;   /* sendmmsg(2) syscalls (plain + GSO paths) */
  int64_t sendto_calls;     /* sendto(2) syscalls (scalar baseline) */
  int64_t send_packets;     /* wire datagram-equivalents handed to kernel */
  int64_t gso_supers;       /* multi-segment UDP_SEGMENT super-datagrams */
  int64_t gso_segments;     /* wire segments inside those supers */
  int64_t eagain_stops;     /* sends stopped by EAGAIN/EWOULDBLOCK */
  int64_t hard_errors;      /* sends stopped by a hard errno */
  int64_t bytes_to_wire;    /* bytes handed to the kernel by sends */
  int64_t recvmmsg_calls;   /* recvmmsg(2) syscalls (ring ingest) */
  int64_t recv_datagrams;   /* datagrams admitted into rings */
  int64_t recv_bytes;       /* bytes admitted into rings */
  int64_t oversize_dropped; /* kernel-truncated datagrams dropped */
  /* Per-call CLOCK_MONOTONIC deltas (phase attribution, obs/profile.py):
   * cumulative wall ns spent INSIDE the egress send entry points
   * (ed_fanout_send_udp / _gso / ed_scalar_baseline_send — the _multi
   * wrapper accumulates through its children, never double-counts) and
   * the ring ingest.  Appended at the struct tail so older readers of
   * the 12-field prefix keep working; ed_stats_fields() is the ABI
   * handshake the Python bridge checks before trusting the tail. */
  int64_t send_ns;          /* cumulative ns inside egress entry points */
  int64_t ingest_ns;        /* cumulative ns inside ed_udp_ingest */
  /* Megabatch staging tail (second ABI bump, fields 15-16): the
   * ed_stage_gather upload packer's cumulative cost and volume.  Same
   * handshake discipline — ed_stats_fields() now reports 16 and the
   * Python bridge refuses a library that disagrees. */
  int64_t stage_gather_ns;  /* cumulative ns inside ed_stage_gather */
  int64_t staged_bytes;     /* prefix+length bytes packed for upload */
  /* Fault-injection tail (third ABI bump, field 17): egress faults
   * deliberately provoked by the ed_fault_* knobs (chaos testing).
   * ed_stats_fields() now reports 17. */
  int64_t fault_injections; /* injected EAGAIN/ENOBUFS/latency events */
  /* io_uring backend tail (fourth ABI bump, fields 18-22): the
   * per-backend counters behind io_uring_{sqe,cqe,...}_total.  Same
   * handshake discipline — ed_stats_fields() now reports 22 and the
   * Python bridge refuses a library that disagrees. */
  int64_t uring_sqes;       /* SQEs queued for submission */
  int64_t uring_cqes;       /* CQEs reaped (completions + ZC notifs) */
  int64_t uring_submits;    /* io_uring_enter(2) syscalls issued */
  int64_t uring_zc_completions; /* zerocopy notification CQEs reaped */
  int64_t uring_zc_copied;  /* ZC notifs reporting the kernel copied
                             * anyway (expected on loopback — counted,
                             * never hidden) */
  /* Stream-socket egress tail (fifth ABI bump, fields 23-25; ISSUE 14):
   * the framed interleave/HTTP-body writers behind the TCP delivery
   * tier.  ed_stats_fields() now reports 25. */
  int64_t stream_writev_calls; /* writev(2)/send syscalls on stream fds */
  int64_t stream_packets;   /* framed packets fully written to streams */
  int64_t stream_bytes;     /* bytes written to stream sockets (framing
                             * included; partial-write bytes count) */
} ed_stats;

void ed_get_stats(ed_stats *out);
void ed_reset_stats(void);
/* Number of int64 fields in ed_stats — the newest symbol; its presence
 * tells the ctypes bridge this library writes the timing tail. */
int32_t ed_stats_fields(void);

/* ---------------------------------------------------- fault injection */

/* Deterministic egress fault knobs (the resilience subsystem's chaos
 * schedule, easydarwin_tpu/resilience/inject.py).  Counter-based, never
 * random: every `eagain_every`-th send CALL (one sendmmsg/sendto batch
 * attempt) stops with EAGAIN before issuing the syscall (WouldBlock
 * semantics: callers keep bookmarks and replay); every
 * `enobufs_every`-th stops with ENOBUFS (a hard per-datagram error:
 * callers skip past it); every `latency_every`-th sleeps `latency_us`
 * before the syscall (a latency spike, not a failure).  0 disables a
 * knob.  Injections count into ed_stats.fault_injections (and the
 * EAGAIN/hard-error counters, exactly as a real kernel stop would).
 * Each knob keeps its own call counter, reset by ed_fault_set/clear, so
 * a given configuration yields one deterministic schedule. */
void ed_fault_set(int64_t eagain_every, int64_t enobufs_every,
                  int64_t latency_every, int64_t latency_us);
void ed_fault_clear(void);

/* ---------------------------------------------------------------- egress */

/* One send op: packet (ring slot) -> subscriber (output index). */
typedef struct {
  int32_t slot;      /* ring slot index */
  int32_t out;       /* subscriber index */
} ed_sendop;

/* Batched UDP fan-out with on-the-fly affine header rewrite.
 *
 * ring_data:  [capacity, slot_size] uint8 — packet bytes (RTP from byte 0)
 * ring_len:   [capacity] int32
 * seq_off/ts_off/ssrc: [n_outs] uint32 — per-subscriber affine params
 * dest_addr:  [n_outs] {uint32 be_ip, uint16 be_port} packed (see ed_dest)
 * ops:        [n_ops] ed_sendop
 * fd:         one unconnected UDP socket used for all sends
 *
 * For each op: renders the 12-byte rewritten header on the stack
 * (seq+=seq_off mod 2^16, ts+=ts_off, ssrc=ssrc[out]; bytes 0-1 copied)
 * and sends [header | payload(12..len)] as a 2-element iovec, batched
 * through sendmmsg in groups of ED_SEND_BATCH.  Returns ops sent, or
 * negative errno.  EAGAIN stops the batch and returns the count so far
 * (callers keep bookmarks, reference WouldBlock semantics). */
typedef struct {
  uint32_t ip_be;    /* network byte order IPv4 */
  uint16_t port_be;  /* network byte order */
  uint16_t _pad;
} ed_dest;

int32_t ed_fanout_send_udp(int fd,
                           const uint8_t *ring_data, const int32_t *ring_len,
                           int32_t capacity, int32_t slot_size,
                           const uint32_t *seq_off, const uint32_t *ts_off,
                           const uint32_t *ssrc, const ed_dest *dest,
                           int32_t n_outs,
                           const ed_sendop *ops, int32_t n_ops);

/* Same contract as ed_fanout_send_udp, but runs of consecutive ops that
 * target the same subscriber are coalesced into UDP_SEGMENT (GSO)
 * super-datagrams: one udp_sendmsg carries up to ~46 equal-size segments
 * (last may be shorter), cutting per-datagram syscall/route/skb setup ~40x.
 * A mid-run length change or subscriber change flushes the current
 * super-send, so variable-size traffic degrades gracefully toward the
 * plain path.  Returns ops handed to the kernel (EAGAIN and hard errors
 * both stop at a super-send boundary and report the delivered count, so
 * a caller retrying the remainder never duplicates a datagram);
 * negative errno only when NOTHING was sent — -EINVAL/-EOPNOTSUPP there
 * means no kernel GSO and callers fall back to ed_fanout_send_udp. */
int32_t ed_fanout_send_udp_gso(int fd,
                               const uint8_t *ring_data,
                               const int32_t *ring_len,
                               int32_t capacity, int32_t slot_size,
                               const uint32_t *seq_off, const uint32_t *ts_off,
                               const uint32_t *ssrc, const ed_dest *dest,
                               int32_t n_outs,
                               const ed_sendop *ops, int32_t n_ops);

/* Multi-source egress: n_src sources share ring_data/ops; rewrite params
 * are [n_src, param_stride] row-major (the packed device result; the
 * stride may exceed n_outs when fewer sockets stand in for the logical
 * subscriber population).  One Python->C transition per window instead
 * of n_src.  use_gso selects the egress rung: 0 = plain sendmmsg,
 * 1 = UDP_SEGMENT (GSO), 2 = the scalar sendto baseline (the forced
 * `egress_backend = "scalar"` rung).  Returns total ops sent; negative
 * errno only when nothing was sent. */
int32_t ed_fanout_send_multi(int fd, const uint8_t *ring_data,
                             const int32_t *ring_len, int32_t capacity,
                             int32_t slot_size, const uint32_t *seq_off,
                             const uint32_t *ts_off, const uint32_t *ssrc,
                             int32_t n_src, int32_t param_stride,
                             const ed_dest *dest,
                             int32_t n_outs, const ed_sendop *ops,
                             int32_t n_ops, int32_t use_gso);

/* ------------------------------------------------------ send pipeline
 * ONE native sender thread and a FIFO of jobs (ISSUE 38).  A job is one
 * ed_fanout_send_multi call — same arguments, same rungs, same prefix
 * contract — run by the sender while the submitting thread plans and
 * settles other streams.  Never two sends at once: jobs run one at a
 * time, in the order they were submitted.  The caller owns the job's
 * memory and everything it points into until `state` reads done; the
 * sender never touches a job after that.  The thread is started by the
 * first submit and sleeps only on an empty queue. */
typedef struct ed_send_job {
  /* in: ed_fanout_send_multi's arguments */
  const uint8_t *ring_data;
  const int32_t *ring_len;
  const uint32_t *seq_off;
  const uint32_t *ts_off;
  const uint32_t *ssrc;
  const ed_dest *dest;
  const ed_sendop *ops;
  int32_t fd, capacity, slot_size, n_src, param_stride, n_outs, n_ops,
      use_gso;
  /* out: what the inline call returns, the JOB's errno (the thread-local
   * ed_last_send_errno() of the submitting thread knows nothing of it),
   * its stamps on CLOCK_MONOTONIC and the send syscalls it made */
  int32_t result;
  int32_t err;
  int64_t submit_ns, start_ns, done_ns, syscalls;
  int32_t state;            /* 0 new, 1 queued or being sent, 2 done */
  int32_t _pad;
} ed_send_job;

int32_t ed_send_job_size(void);     /* sizeof: the bridge's ABI handshake */
/* Queue `job` (returns at once; a stop in progress is waited out first):
 * 0, or -errno if no thread could start. */
int32_t ed_sender_submit(ed_send_job *job);
/* Block until `job` is done: 0, or -EINVAL for a job never submitted. */
int32_t ed_sender_wait(ed_send_job *job);
/* Block until nothing is queued and nothing is being sent. */
void ed_sender_drain(void);
/* Finish what is queued, then end the thread (the next submit starts
 * another). */
void ed_sender_stop(void);
/* out[0] threads started so far, [1] jobs submitted, [2] 1 while a
 * thread runs, [3] the most sends ever in flight at one instant. */
void ed_sender_stats(int64_t out[4]);

/* Framed interleaved-RTSP egress onto ONE stream (TCP) socket
 * (ISSUE 14).  For each slot in `slots`: renders the 4-byte interleave
 * frame ($ | channel | be16 packet-length) plus the 12-byte rewritten
 * RTP header into a scratch arena and writes
 * [frame | header | payload(12..len)] through writev(2) in IOV_MAX-
 * bounded batches — the stream sibling of ed_fanout_send_udp (one
 * affine render at memory bandwidth, no per-packet caller work, payload
 * bytes never copied).
 *
 * Returns the count of packets FULLY written.  *partial_bytes_out
 * reports how many bytes of the NEXT packet (index = return value) are
 * already on the wire when a short write tore it — the caller MUST
 * deliver that packet's remaining bytes before anything else on the
 * connection (the engine hands them to the buffered transport, which
 * then owns ordering).  EAGAIN stops the batch (bookmark replay);
 * negative errno only when nothing was written and the stop was hard.
 * ed_last_send_errno() explains any short return. */
int32_t ed_stream_send(int fd, const uint8_t *ring_data,
                       const int32_t *ring_len, int32_t capacity,
                       int32_t slot_size, uint32_t seq_off,
                       uint32_t ts_off, uint32_t ssrc, int32_t channel,
                       const int32_t *slots, int32_t n_slots,
                       int32_t *partial_bytes_out);

/* Plain byte-blob write to a stream socket through the same accounting
 * (HLS segment bodies ride the egress ladder too).  Returns bytes
 * written (possibly short on EAGAIN), or negative errno when nothing
 * was written and the stop was hard. */
int64_t ed_stream_write(int fd, const uint8_t *buf, int64_t len);

/* ----------------------------------------------------- io_uring backend */

/* Capability bits reported by ed_uring_probe() (>= 0) and
 * ed_uring_caps().  The probe attacks the syscall boundary the same way
 * the GSO EINVAL probe does: one throwaway ring at boot answers every
 * "does this kernel/seccomp/RLIMIT_MEMLOCK combination support X"
 * question, so steady-state sends never discover a capability the hard
 * way.  A negative probe return is -errno (ENOSYS = no io_uring at all,
 * EPERM = seccomp denied it) and callers drop to the GSO rung. */
#define ED_URING_CAP_RING        1   /* io_uring_setup + mmap worked */
#define ED_URING_CAP_SQPOLL      2   /* kernel-side submission polling */
#define ED_URING_CAP_SEND_ZC     4   /* IORING_OP_SEND_ZC (MSG_ZEROCOPY) */
#define ED_URING_CAP_RECV_MULTI  8   /* multishot recvmsg ingest */
#define ED_URING_CAP_FIXED_BUFS 16   /* IORING_REGISTER_BUFFERS allowed
                                      * under this RLIMIT_MEMLOCK */
int32_t ed_uring_probe(void);

/* Flags for ed_uring_egress_new (requests; silently degraded to what the
 * probe allows — a request the kernel cannot honor must never turn into
 * a hard error on the data path). */
#define ED_URING_F_SQPOLL 1
#define ED_URING_F_ZEROCOPY 2

typedef struct ed_uring ed_uring;

/* Persistent ring for one egress fd: `depth` SQ entries (clamped to
 * [16, 1024]), a registered (fixed) send arena of depth x max_pkt bytes
 * covering the rendered hot window, optional SQPOLL and SEND_ZC.  On
 * failure returns NULL with -errno in *err_out.  Free with
 * ed_uring_free (also drains outstanding zerocopy notifications). */
ed_uring *ed_uring_egress_new(int fd, int32_t depth, int32_t max_pkt,
                              int32_t flags, int32_t *err_out);
void ed_uring_free(ed_uring *u);
int32_t ed_uring_caps(const ed_uring *u);
/* The ring's own pollable fd (readable when CQEs are pending).  For
 * armed multishot ingest this — not the SOCKET fd — is the event-loop
 * wakeup source: the ring consumes the socket's queue before epoll sees
 * it, so watching the socket would strand completions until the
 * provided-buffer pool exhausted. */
int32_t ed_uring_fd(const ed_uring *u);

/* Same contract as ed_fanout_send_udp — ops sent, EAGAIN stops the
 * batch and returns the count so far (bookmark replay), hard errors
 * return the delivered count (or -errno when nothing was sent) — but
 * the datagrams ride one io_uring submission per chain of up to `depth`
 * linked SQEs instead of one sendmmsg slot each.  IOSQE_IO_LINK keeps
 * kernel execution in op order, so "count so far" is exact and a replay
 * never duplicates a delivered datagram (the property the bookmark
 * invariants rest on).  Faults from ed_fault_set surface through the
 * same completion-path accounting as real CQE errors. */
int32_t ed_uring_send(ed_uring *u, const uint8_t *ring_data,
                      const int32_t *ring_len, int32_t capacity,
                      int32_t slot_size, const uint32_t *seq_off,
                      const uint32_t *ts_off, const uint32_t *ssrc,
                      const ed_dest *dest, int32_t n_outs,
                      const ed_sendop *ops, int32_t n_ops);

/* Multi-source wrapper over ed_uring_send — the io_uring sibling of
 * ed_fanout_send_multi (one Python->C transition per window). */
int32_t ed_uring_send_multi(ed_uring *u, const uint8_t *ring_data,
                            const int32_t *ring_len, int32_t capacity,
                            int32_t slot_size, const uint32_t *seq_off,
                            const uint32_t *ts_off, const uint32_t *ssrc,
                            int32_t n_src, int32_t param_stride,
                            const ed_dest *dest, int32_t n_outs,
                            const ed_sendop *ops, int32_t n_ops);

/* ed_stream_send's contract over an io_uring ring: the whole framed
 * batch is rendered into the ring's registered arena as ONE contiguous
 * byte blob and submitted as a single SEND SQE per arena-sized chunk —
 * a TCP stream is a byte sequence, so one send of N framed packets is
 * wire-identical to N writes, and a short completion is simply a byte
 * count (no torn-chain hazard).  `fd` is the TARGET stream socket (SQEs
 * carry their own fd; the ring's bound socket is not used).  Same
 * return/partial contract as ed_stream_send. */
int32_t ed_uring_stream_send(ed_uring *u, int fd,
                             const uint8_t *ring_data,
                             const int32_t *ring_len, int32_t capacity,
                             int32_t slot_size, uint32_t seq_off,
                             uint32_t ts_off, uint32_t ssrc,
                             int32_t channel, const int32_t *slots,
                             int32_t n_slots,
                             int32_t *partial_bytes_out);

/* One byte blob through a single SEND SQE per chunk (HLS bodies on the
 * io_uring rung).  Returns bytes written or negative errno. */
int64_t ed_uring_stream_write(ed_uring *u, int fd, const uint8_t *buf,
                              int64_t len);

/* Multishot-recvmsg ingest ring for one UDP socket: a provided-buffer
 * pool of `max_pkt`-sized slots and one persistent multishot RECVMSG
 * SQE — datagrams land in CQEs without a per-batch recvmmsg syscall.
 * Requires ED_URING_CAP_RECV_MULTI; returns NULL/-errno otherwise. */
ed_uring *ed_uring_ingest_new(int fd, int32_t max_pkt, int32_t *err_out);

/* Same contract as ed_udp_ingest: drains completed datagrams into ring
 * slots at *head, returns datagrams admitted (oversize dropped +
 * counted), advances *head.  One io_uring_enter flushes pending
 * completions; buffer recycling and multishot re-arm ride the same
 * submission. */
int32_t ed_uring_ingest_drain(ed_uring *u, uint8_t *ring_data,
                              int32_t *ring_len, int64_t *ring_arrival,
                              int32_t capacity, int32_t slot_size,
                              int64_t now_ms, int64_t *head,
                              int32_t max_pkts, int32_t *oversize_dropped);

/* The REFERENCE architecture in C, for an honest vs_baseline: one thread,
 * one sendto(2) per (packet, output) with a scalar in-buffer header patch —
 * the ReflectorSender hot loop (ReflectorStream.cpp:1024-1185 →
 * RTPStream.cpp:1145 UDP send) with zero batching, exactly what a faithful
 * C port of the reference would execute per datagram.  A per-op ~len-byte
 * scratch memcpy stands in for the reference's in-place header rewrite
 * (sub-1us next to the syscall).  Returns ops sent; EAGAIN stops and
 * returns the count so far; negative errno only when nothing was sent. */
int32_t ed_scalar_baseline_send(int fd, const uint8_t *ring_data,
                                const int32_t *ring_len, int32_t capacity,
                                int32_t slot_size, const uint32_t *seq_off,
                                const uint32_t *ts_off, const uint32_t *ssrc,
                                const ed_dest *dest, int32_t n_outs,
                                const ed_sendop *ops, int32_t n_ops);

/* Same render, but into a caller buffer instead of the wire: out must hold
 * n_ops * (12 + max payload) — used for interleaved/TCP paths and tests.
 * out_lens[i] receives each rendered packet's length.  Returns n rendered. */
int32_t ed_fanout_render(const uint8_t *ring_data, const int32_t *ring_len,
                         int32_t capacity, int32_t slot_size,
                         const uint32_t *seq_off, const uint32_t *ts_off,
                         const uint32_t *ssrc, int32_t n_outs,
                         const ed_sendop *ops, int32_t n_ops,
                         uint8_t *out, int32_t out_stride,
                         int32_t *out_lens);

/* ------------------------------------------------------- megabatch staging */

/* Pack `n_slots` ring slots into consecutive rows of a contiguous upload
 * buffer (the megabatch scheduler's H2D staging gather): row i receives
 * the first `prefix_width` bytes of slot slots[i] followed by the slot's
 * length as 4 little-endian bytes (the ops.fanout pack_window layout the
 * device step decodes).  Rows [n_slots, out_rows) are zeroed so a
 * pow2-padded stage never leaks a previous wake's bytes into the pad.
 * out_stride must be >= prefix_width + 4.  Returns n_slots, or -EINVAL
 * on bad slot/stride arguments.  One memcpy walk per stream per wake —
 * the host half of double-buffered staging, counted into
 * ed_stats.stage_gather_ns / staged_bytes. */
int32_t ed_stage_gather(const uint8_t *ring_data, const int32_t *ring_len,
                        int32_t capacity, int32_t slot_size,
                        const int32_t *slots, int32_t n_slots,
                        int32_t prefix_width, uint8_t *out,
                        int32_t out_stride, int32_t out_rows);

/* ---------------------------------------------------------------- ingest */

/* Drain up to max_pkts datagrams from fd (non-blocking, recvmmsg) directly
 * into ring slots starting at *head (mod capacity), writing lengths and
 * arrival_ms.  Returns datagrams ADMITTED (0 if none), negative errno on
 * error; *head is advanced.  Kernel-truncated datagrams (larger than the
 * slot) are dropped, compacted over, and counted into *oversize_dropped
 * (nullable) — a truncated slot would relay a corrupt packet. */
int32_t ed_udp_ingest(int fd, uint8_t *ring_data, int32_t *ring_len,
                      int64_t *ring_arrival, int32_t capacity,
                      int32_t slot_size, int64_t now_ms,
                      int64_t *head, int32_t max_pkts,
                      int32_t *oversize_dropped);

/* Discard-drain every pending datagram on each fd (recvmmsg, MSG_DONTWAIT).
 * A cheap stand-in for N subscriber read loops: one syscall drains a batch,
 * no per-datagram userspace work (zero-length iovecs + MSG_TRUNC — the
 * kernel frees each datagram without copying payload).  Returns total
 * datagrams discarded. */
int64_t ed_udp_drain(const int32_t *fds, int32_t n_fds);

/* As ed_udp_drain, but also sums the true (pre-truncation) datagram sizes
 * into *out_bytes.  With UDP_GRO receivers a "datagram" here is a coalesced
 * super-datagram; bytes / wire-packet-size recovers the wire count. */
int64_t ed_udp_drain_ex(const int32_t *fds, int32_t n_fds,
                        int64_t *out_bytes);

/* -------------------------------------------------------- H.264 requant */

/* Native CAVLC slice requantizer (the HLS q-rung hot path) — decodes a
 * baseline-intra slice (I_4x4 + I_16x16, luma and 4:2:0 chroma
 * residuals, multi-slice pictures via first_mb_in_slice + the 7.3.4
 * stop-bit walk), requantizes every level delta_qp steps coarser (luma:
 * exact +6k shift; chroma: Table 8-15 QPc mapping with identity /
 * shift / integer-round-trip dispatch), re-encodes with recomputed
 * CBP/nC contexts and QP chain.  Bit-exact vs the Python oracle
 * (codecs/h264_requant.py); tables generated from the Python source
 * (gen_h264_tables.py).  Returns the output NAL length written to out,
 * or negative: -1 unsupported feature (caller passes through), -2
 * malformed bitstream, -3 out buffer too small. */
int32_t ed_h264_requant_slice(
    const uint8_t *nal, int32_t nal_len, uint8_t *out, int32_t out_cap,
    int32_t width_mbs, int32_t height_mbs, int32_t log2_max_frame_num,
    int32_t poc_type, int32_t log2_max_poc_lsb, int32_t pic_init_qp,
    int32_t pps_id, int32_t deblocking_control, int32_t bottom_field_poc,
    int32_t delta_qp, int32_t chroma_qp_offset,
    int32_t num_ref_l0_default, int32_t weighted_pred, int32_t *mbs_out,
    int32_t *blocks_out);

/* CABAC variant of the requant walk (mirrors codecs/h264_cabac.py
 * bit-exactly; same contract/returns). */
int32_t ed_h264_requant_slice_cabac(
    const uint8_t *nal, int32_t nal_len, uint8_t *out, int32_t out_cap,
    int32_t width_mbs, int32_t height_mbs, int32_t log2_max_frame_num,
    int32_t poc_type, int32_t log2_max_poc_lsb, int32_t pic_init_qp,
    int32_t pps_id, int32_t deblocking_control, int32_t bottom_field_poc,
    int32_t delta_qp, int32_t chroma_qp_offset,
    int32_t num_ref_l0_default, int32_t weighted_pred, int32_t *mbs_out,
    int32_t *blocks_out);

/* ------------------------------------------------------------- timer wheel */

/* Hashed timer wheel, 1 ms ticks (vs the reference's 10 ms scheduler floor,
 * Task.cpp:334).  Single-threaded use from the owner loop. */
typedef struct ed_wheel ed_wheel;

ed_wheel *ed_wheel_new(int64_t now_ms);
void ed_wheel_free(ed_wheel *w);
/* schedule returns a timer id (>0) firing at now+delay_ms */
int64_t ed_wheel_schedule(ed_wheel *w, int64_t delay_ms, int64_t user_data);
int ed_wheel_cancel(ed_wheel *w, int64_t timer_id);
/* advance to now_ms; expired user_data values are copied into out (up to
 * max_out); returns number expired */
int32_t ed_wheel_advance(ed_wheel *w, int64_t now_ms, int64_t *out,
                         int32_t max_out);
/* ms until next timer from now_ms, or -1 if none (capped at 3600000) */
int64_t ed_wheel_next(const ed_wheel *w, int64_t now_ms);
int32_t ed_wheel_pending(const ed_wheel *w);

#ifdef __cplusplus
}
#endif
#endif
