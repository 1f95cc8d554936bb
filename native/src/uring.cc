// uring.cc — see uring.h.  Raw-syscall io_uring: setup + two mmaps (SQ
// incl. SQE array, CQ), a provided-buffer ring for multishot RECV, and a
// single engine thread that owns the submission queue.  Cross-thread op
// requests queue behind a mutex and the thread is woken through an
// eventfd that is itself read via the ring.
#include "uring.h"

#include <arpa/inet.h>
#include <errno.h>
#include <linux/io_uring.h>
#include <netinet/in.h>
#include <string.h>
#include <stdio.h>
#include <stdlib.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <sys/mman.h>
#include <sys/syscall.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <deque>
#include <mutex>
#include <thread>
#include <unordered_map>
#include <vector>

#include "metrics.h"
#include "sched_perturb.h"
#include "shard.h"
#include "timer_thread.h"

// --- uapi compat -----------------------------------------------------------
// The engine tracks io_uring uapi newer than some build hosts ship in
// /usr/include.  Everything below is kernel-ABI-stable; macros are
// guarded, and constants that upstream defines as ENUMERATORS (which
// #ifdef cannot see) are mirrored as local constexprs and used
// exclusively, so the same source builds against 5.1x and 6.x headers.
#ifndef IORING_RECV_MULTISHOT  // absent => pre-5.19 header
#define IORING_RECV_MULTISHOT (1U << 1)
#define IORING_ACCEPT_MULTISHOT (1U << 0)
struct io_uring_buf {
  __u64 addr;
  __u32 len;
  __u16 bid;
  __u16 resv;
};
struct io_uring_buf_ring {
  // header-only view: the kernel reads entries at 16-byte stride from
  // offset 0; entry 0's tail bytes alias this header (see AddProvidedBuf)
  __u64 resv1;
  __u32 resv2;
  __u16 resv3;
  __u16 tail;
};
struct io_uring_buf_reg {
  __u64 ring_addr;
  __u32 ring_entries;
  __u16 bgid;
  __u16 flags;
  __u64 resv[3];
};
#endif
#ifndef IORING_RECVSEND_FIXED_BUF  // absent => pre-6.0 header
#define IORING_RECVSEND_FIXED_BUF (1U << 2)
#endif
#ifndef IORING_CQE_F_NOTIF
#define IORING_CQE_F_NOTIF (1U << 3)
#endif
#ifndef IORING_SEND_ZC_REPORT_USAGE  // absent => pre-6.2 header
#define IORING_SEND_ZC_REPORT_USAGE (1U << 3)
#endif
#ifndef IORING_NOTIF_USAGE_ZC_COPIED
#define IORING_NOTIF_USAGE_ZC_COPIED (1U << 31)
#endif

namespace trpc {

namespace {

// Enumerators in the uapi header (not detectable with #ifdef): mirrored
// by ABI value and used everywhere below.
constexpr uint8_t kOpSendZc = 47;         // IORING_OP_SEND_ZC (6.0)
constexpr unsigned kRegBuffers = 0;       // IORING_REGISTER_BUFFERS
constexpr unsigned kRegProbe = 8;         // IORING_REGISTER_PROBE
constexpr unsigned kRegPbufRing = 22;     // IORING_REGISTER_PBUF_RING

int sys_io_uring_setup(unsigned entries, struct io_uring_params* p) {
  return (int)syscall(__NR_io_uring_setup, entries, p);
}

int sys_io_uring_enter(int fd, unsigned to_submit, unsigned min_complete,
                       unsigned flags) {
  return (int)syscall(__NR_io_uring_enter, fd, to_submit, min_complete,
                      flags, nullptr, 0);
}

int sys_io_uring_register(int fd, unsigned opcode, void* arg,
                          unsigned nr_args) {
  return (int)syscall(__NR_io_uring_register, fd, opcode, arg, nr_args);
}

// user_data tags
constexpr uint64_t kTagWake = 1ULL << 62;
constexpr uint64_t kTagAccept = 2ULL << 62;
constexpr uint64_t kTagRecv = 3ULL << 62;
constexpr uint64_t kTagMask = 3ULL << 62;

constexpr unsigned kEntries = 256;
constexpr int kBufGroup = 7;
constexpr unsigned kNumBufs = 256;   // provided buffers
constexpr size_t kBufSize = 16384;

// Registered-buffer pool defaults (env: TRPC_ZC_POOL_SLOTS /
// TRPC_ZC_SLOT_BYTES).  Slot size fits a 4MB attachment landing zone
// plus header slack; 8 slots ≈ 32MB pinned once at bring-up.
constexpr int kZcPoolSlotsDefault = 8;
constexpr size_t kZcSlotBytesDefault = (4u << 20) + 4096;

// Cap on SQEs per send batch: a linked chain must fit the SQ ring in one
// submission (splitting a chain across io_uring_enter would sever the
// link and reorder bytes).
constexpr int kMaxBatchOps = (int)kEntries - 8;
constexpr int kGatherIovs = 64;  // small refs coalesced per SENDMSG op

struct SendBatch;

struct PendingOp {
  // 0 accept, 1 recv, 2 cancel-recv, 3 remove-acceptor, 4 send,
  // 5 rearm-acceptor (multishot re-issue after an EMFILE backoff pause)
  int kind;
  SocketId id = INVALID_SOCKET_ID;
  int fd = -1;
  void (*on_accept)(void*, int) = nullptr;
  void* user = nullptr;
  SendBatch* batch = nullptr;  // kind 4: ownership passes to the engine
};

// One drained write queue riding the ring as a linked SQE chain.  The
// IOBuf pins every block until the LAST zerocopy notification lands —
// that is the lifetime rule the whole rail hangs on: a socket close,
// call cancel or stream RST can drop every other reference to these
// blocks while the NIC still reads them, and the bytes stay valid.
struct SendBatch {
  SocketId id = INVALID_SOCKET_ID;
  int fd = -1;
  IOBuf data;
  SendTicket* ticket = nullptr;
  size_t threshold = 16384;  // snapshot: submitter and builder agree
  int nops = 0;            // SQEs this batch submits
  int pending_cqes = 0;    // first-completion CQEs outstanding
  int pending_notifs = 0;  // zerocopy-notification CQEs outstanding
  int result = 0;          // first real error (-errno)
  bool signaled = false;   // ticket already woken
  // stable storage for SENDMSG gather segments (deque: no reallocation
  // while the kernel reads the iovecs)
  std::deque<std::vector<iovec>> iovs;
  std::deque<msghdr> hdrs;
};

// Egress switches (cross-thread; the engine thread and submitters read).
std::atomic<bool> g_sendzc_enabled{true};
std::atomic<size_t> g_sendzc_threshold{16384};

struct Acceptor {
  void (*on_accept)(void*, int);
  void* user;
  int fd;
  // EMFILE/ENFILE backoff (exponential, reset on a successful accept).
  // Only the engine thread touches it.
  int backoff_ms = 0;
};

// Timer-plane trampoline for the acceptor backoff: re-issue the multishot
// accept after the pause.  arg packs [shard:32][fd:32]; a listener removed
// in the meantime is caught by the acceptors_ lookup in Drain().
void RingRearmAcceptCb(void* arg) {
  uint64_t packed = (uint64_t)(uintptr_t)arg;
  uring_rearm_acceptor((int)(uint32_t)packed, (int)(packed >> 32));
}

class RingEngine {
 public:
  // One engine per shard (shard.h): shard 0 is the pre-shard singleton;
  // the others come up lazily on first use.  Leaked on purpose.
  static RingEngine* Shard(int k) {
    // lint:allow-blocking-bounded (taken only on a shard engine's lazy
    // first bring-up; steady state is the lock-free atomic load below)
    static std::mutex mu;
    static std::atomic<RingEngine*> engines[kMaxShards];
    if (k < 0 || k >= shard_count()) {
      k = 0;
    }
    RingEngine* e = engines[k].load(std::memory_order_acquire);
    if (e != nullptr) {
      return e;
    }
    std::lock_guard<std::mutex> lk(mu);
    e = engines[k].load(std::memory_order_acquire);
    if (e == nullptr) {
      e = new RingEngine(k);
      engines[k].store(e, std::memory_order_release);
    }
    return e;
  }

  static RingEngine* Instance() { return Shard(0); }

  bool ok() const { return ring_fd_ >= 0; }

  int Add(PendingOp op) {
    if (!ok()) {
      return -ENOSYS;
    }
    {
      std::lock_guard<std::mutex> lk(mu_);
      pending_.push_back(op);
      ++ops_enqueued_;
    }
    uint64_t one = 1;
    (void)!write(event_fd_, &one, sizeof(one));
    return 0;
  }

  // Wait until every op enqueued before this call has been processed by
  // the engine thread (teardown barrier: after it, no acceptor callback
  // can fire for a removed listener).
  void Quiesce() {
    if (!ok()) {
      return;
    }
    uint64_t target;
    {
      std::lock_guard<std::mutex> lk(mu_);
      target = ops_enqueued_;
    }
    while (ops_done_.load(std::memory_order_acquire) < target) {
      usleep(200);
    }
  }

 private:
  explicit RingEngine(int shard_idx) : shard_idx_(shard_idx) {
    // flag-cached: the ONE env read for debug logging — every later
    // site consults debug_ (a per-CQE getenv was a hot-path environ
    // scan, flagged by tools/lint.py)
    debug_ = getenv("TRPC_URING_DEBUG") != nullptr;
    struct io_uring_params p;
    memset(&p, 0, sizeof(p));
    int fd = sys_io_uring_setup(kEntries, &p);
    if (fd < 0) {
      return;
    }
    // required: buffer selection (5.7+), multishot accept/recv (5.19/6.0)
    if (!(p.features & IORING_FEAT_FAST_POLL)) {
      close(fd);
      return;
    }
    size_t sq_sz = p.sq_off.array + p.sq_entries * sizeof(uint32_t);
    size_t cq_sz = p.cq_off.cqes + p.cq_entries * sizeof(io_uring_cqe);
    if (p.features & IORING_FEAT_SINGLE_MMAP) {
      sq_sz = cq_sz = sq_sz > cq_sz ? sq_sz : cq_sz;
    }
    sq_ptr_ = mmap(nullptr, sq_sz, PROT_READ | PROT_WRITE,
                   MAP_SHARED | MAP_POPULATE, fd, IORING_OFF_SQ_RING);
    if (sq_ptr_ == MAP_FAILED) {
      close(fd);
      return;
    }
    cq_ptr_ = (p.features & IORING_FEAT_SINGLE_MMAP)
                  ? sq_ptr_
                  : mmap(nullptr, cq_sz, PROT_READ | PROT_WRITE,
                         MAP_SHARED | MAP_POPULATE, fd, IORING_OFF_CQ_RING);
    if (cq_ptr_ == MAP_FAILED) {
      close(fd);
      return;
    }
    sqes_ = (io_uring_sqe*)mmap(
        nullptr, p.sq_entries * sizeof(io_uring_sqe),
        PROT_READ | PROT_WRITE, MAP_SHARED | MAP_POPULATE, fd,
        IORING_OFF_SQES);
    if (sqes_ == MAP_FAILED) {
      close(fd);
      return;
    }
    sq_head_ = (std::atomic<uint32_t>*)((char*)sq_ptr_ + p.sq_off.head);
    sq_tail_ = (std::atomic<uint32_t>*)((char*)sq_ptr_ + p.sq_off.tail);
    sq_mask_ = *(uint32_t*)((char*)sq_ptr_ + p.sq_off.ring_mask);
    sq_array_ = (uint32_t*)((char*)sq_ptr_ + p.sq_off.array);
    cq_head_ = (std::atomic<uint32_t>*)((char*)cq_ptr_ + p.cq_off.head);
    cq_tail_ = (std::atomic<uint32_t>*)((char*)cq_ptr_ + p.cq_off.tail);
    cq_mask_ = *(uint32_t*)((char*)cq_ptr_ + p.cq_off.ring_mask);
    cqes_ = (io_uring_cqe*)((char*)cq_ptr_ + p.cq_off.cqes);

    // provided-buffer ring for multishot RECV.  The recv buffers and the
    // zero-copy egress slots share ONE pool mmap: the recv ring draws
    // from its head, d2h landing zones (uring_zc_alloc) from its tail —
    // the tail slots are additionally registered as fixed buffers below.
    size_t br_sz = kNumBufs * sizeof(io_uring_buf);
    buf_ring_ = (io_uring_buf_ring*)mmap(
        nullptr, br_sz, PROT_READ | PROT_WRITE,
        MAP_ANONYMOUS | MAP_PRIVATE, -1, 0);
    zc_slots_ = kZcPoolSlotsDefault;
    zc_slot_size_ = kZcSlotBytesDefault;
    // flag-cached: engine-ctor reads (the singleton constructs once per
    // process; the values live in zc_slots_/zc_slot_size_ after)
    if (const char* e = getenv("TRPC_ZC_POOL_SLOTS")) {
      long v = strtol(e, nullptr, 10);
      if (v >= 0 && v <= 256) {
        zc_slots_ = (int)v;
      }
    }
    if (const char* e = getenv("TRPC_ZC_SLOT_BYTES")) {  // flag-cached: ditto
      long long v = strtoll(e, nullptr, 10);
      if (v >= 4096 && v <= (1ll << 30)) {
        zc_slot_size_ = (size_t)v;
      }
    }
    if (shard_idx_ != 0) {
      // the zc landing-zone pool lives on shard 0 only: uring_zc_alloc
      // callers are shard-blind, and pinning ~32MB per shard would
      // multiply the footprint for a pool the d2h plane taps rarely.
      // Shard>0 SEND_ZC still works — just without FIXED_BUF (ZcBufIndex
      // returns -1 here).
      zc_slots_ = 0;
    }
    size_t recv_sz = kNumBufs * kBufSize;
    size_t pool_sz = recv_sz + (size_t)zc_slots_ * zc_slot_size_;
    pool_base_ = (char*)mmap(nullptr, pool_sz, PROT_READ | PROT_WRITE,
                             MAP_ANONYMOUS | MAP_PRIVATE, -1, 0);
    if (buf_ring_ == MAP_FAILED || pool_base_ == MAP_FAILED) {
      close(fd);
      return;
    }
    buf_base_ = pool_base_;
    zc_base_ = pool_base_ + recv_sz;
    // fault the pages in BEFORE registration: pinning a never-written
    // private anonymous page can pin the shared zero page, and later
    // stores COW onto a page the kernel no longer reads
    memset(buf_ring_, 0, br_sz);
    memset(pool_base_, 0, pool_sz);
    struct io_uring_buf_reg reg;
    memset(&reg, 0, sizeof(reg));
    reg.ring_addr = (uint64_t)(uintptr_t)buf_ring_;
    reg.ring_entries = kNumBufs;
    reg.bgid = kBufGroup;
    int rrc = sys_io_uring_register(fd, kRegPbufRing, &reg, 1);
    if (debug_)
      fprintf(stderr, "[uring] pbuf register rc=%d on fd=%d ring_addr=%p\n",
              rrc, fd, (void*)buf_ring_);
    if (rrc != 0) {
      close(fd);
      return;
    }
    br_tail_ = 0;
    for (unsigned i = 0; i < kNumBufs; ++i) {
      AddProvidedBuf(i);
    }
    PublishBufTail();

    event_fd_ = eventfd(0, EFD_CLOEXEC);
    if (event_fd_ < 0) {
      close(fd);
      return;  // engine unusable without its wake channel
    }
    // self-test: a multishot RECV with buffer selection must actually
    // work on THIS kernel (feature bits alone don't prove 6.0+ multishot
    // recv; on older kernels it fails -EINVAL and we must fall back to
    // epoll instead of killing every connection)
    {
      int sv[2];
      if (socketpair(AF_UNIX, SOCK_STREAM, 0, sv) != 0) {
        close(fd);
        return;
      }
      io_uring_sqe* sqe = &sqes_[sq_tail_local_ & sq_mask_];
      memset(sqe, 0, sizeof(*sqe));
      sq_array_[sq_tail_local_ & sq_mask_] = sq_tail_local_ & sq_mask_;
      sqe->opcode = IORING_OP_RECV;
      sqe->fd = sv[0];
      sqe->ioprio = IORING_RECV_MULTISHOT;
      sqe->flags = IOSQE_BUFFER_SELECT;
      sqe->buf_group = kBufGroup;
      sqe->user_data = kTagWake | 1;
      ++sq_tail_local_;
      sq_tail_->store(sq_tail_local_, std::memory_order_release);
      (void)!write(sv[1], "x", 1);
      sys_io_uring_enter(fd, 1, 1, IORING_ENTER_GETEVENTS);
      bool self_ok = false;
      uint32_t h = cq_head_->load(std::memory_order_acquire);
      uint32_t t = cq_tail_->load(std::memory_order_acquire);
      while (h != t) {
        io_uring_cqe* cqe = &cqes_[h & cq_mask_];
        if (cqe->user_data == (kTagWake | 1)) {
          self_ok = cqe->res == 1 &&
                    (cqe->flags & IORING_CQE_F_BUFFER) != 0;
          if (self_ok) {
            AddProvidedBuf(cqe->flags >> IORING_CQE_BUFFER_SHIFT);
            PublishBufTail();
          }
        }
        ++h;
        cq_head_->store(h, std::memory_order_release);
        t = cq_tail_->load(std::memory_order_acquire);
      }
      close(sv[0]);
      close(sv[1]);
      if (!self_ok) {
        close(fd);
        return;
      }
    }
    // zero-copy egress bring-up: probe SEND_ZC support, register the
    // pool's egress slots as fixed buffers, then self-test one SEND_ZC
    // on a real loopback TCP pair to learn whether this kernel also
    // takes IORING_SEND_ZC_REPORT_USAGE (6.2+; rejected with -EINVAL
    // before that — probing per-op would poison real traffic).
    ring_fd_ = fd;  // needed by Submit() inside the self-test
    ProbeSendZc();
    if (sendzc_ok_ && zc_slots_ > 0) {
      std::vector<iovec> iovs((size_t)zc_slots_);
      for (int i = 0; i < zc_slots_; ++i) {
        iovs[(size_t)i].iov_base = zc_base_ + (size_t)i * zc_slot_size_;
        iovs[(size_t)i].iov_len = zc_slot_size_;
      }
      zc_registered_ = sys_io_uring_register(fd, kRegBuffers, iovs.data(),
                                             (unsigned)zc_slots_) == 0;
      if (debug_) {
        fprintf(stderr, "[uring] fixed-buffer register %s (%d x %zu)\n",
                zc_registered_ ? "ok" : "FAILED", zc_slots_, zc_slot_size_);
      }
    }
    {
      std::lock_guard<std::mutex> lk(zc_mu_);
      for (int i = 0; i < zc_slots_; ++i) {
        zc_free_.push_back(i);
      }
    }
    if (shard_idx_ == 0) {  // the pool (and its /vars gauge) is shard 0's
      native_metrics().uring_zc_pool_slots.store(zc_slots_,
                                                 std::memory_order_relaxed);
    }
    SelfTestSendZc();
    std::thread t([this] {
      char name[16];
      snprintf(name, sizeof(name), "trpc_uring%d", shard_idx_);
      pthread_setname_np(pthread_self(), name);
      Loop();
    });
    t.detach();
  }

  // IORING_REGISTER_PROBE: does this kernel implement IORING_OP_SEND_ZC?
  void ProbeSendZc() {
    // io_uring_probe ends in a flexible array member, so it cannot sit
    // before another member of a struct (g++ 12 refuses): probe into an
    // aligned byte buffer sized for 64 ops instead
    alignas(io_uring_probe) unsigned char
        buf[sizeof(io_uring_probe) + 64 * sizeof(io_uring_probe_op)];
    memset(buf, 0, sizeof(buf));
    io_uring_probe* pr = reinterpret_cast<io_uring_probe*>(buf);
    if (sys_io_uring_register(ring_fd_, kRegProbe, pr, 64) != 0) {
      return;
    }
    sendzc_ok_ = pr->ops_len > kOpSendZc &&
                 (pr->ops[kOpSendZc].flags & IO_URING_OP_SUPPORTED) != 0;
  }

  static bool MakeTcpPair(int* a, int* b) {
    int lfd = ::socket(AF_INET, SOCK_STREAM | SOCK_CLOEXEC, 0);
    if (lfd < 0) {
      return false;
    }
    sockaddr_in addr;
    memset(&addr, 0, sizeof(addr));
    addr.sin_family = AF_INET;
    addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
    socklen_t alen = sizeof(addr);
    if (bind(lfd, (sockaddr*)&addr, sizeof(addr)) != 0 ||
        listen(lfd, 1) != 0 ||
        getsockname(lfd, (sockaddr*)&addr, &alen) != 0) {
      close(lfd);
      return false;
    }
    int cfd = ::socket(AF_INET, SOCK_STREAM | SOCK_CLOEXEC, 0);
    if (cfd < 0 || connect(cfd, (sockaddr*)&addr, sizeof(addr)) != 0) {
      if (cfd >= 0) close(cfd);
      close(lfd);
      return false;
    }
    int sfd = accept(lfd, nullptr, nullptr);
    close(lfd);
    if (sfd < 0) {
      close(cfd);
      return false;
    }
    *a = cfd;
    *b = sfd;
    return true;
  }

  // One real SEND_ZC on a loopback TCP pair, pre-engine-thread: learns
  // REPORT_USAGE support and double-checks the opcode end to end.  Runs
  // with the CQ drained manually; its notification CQE (tag kTagWake|3)
  // is ignored by the main loop if it arrives late.
  void SelfTestSendZc() {
    if (!sendzc_ok_) {
      return;
    }
    int a = -1, b = -1;
    if (!MakeTcpPair(&a, &b)) {
      return;  // keep probe verdict; assume no usage reporting
    }
    static const char byte = 'z';
    for (int usage = 1; usage >= 0; --usage) {
      io_uring_sqe* sqe = GetSqe();
      sqe->opcode = kOpSendZc;
      sqe->fd = a;
      sqe->addr = (uint64_t)(uintptr_t)&byte;
      sqe->len = 1;
      sqe->msg_flags = MSG_NOSIGNAL;
      sqe->ioprio = usage ? IORING_SEND_ZC_REPORT_USAGE : 0;
      sqe->user_data = kTagWake | 3;
      Submit();
      int32_t res = 0;
      bool main_seen = false, more = false;
      int64_t deadline = monotonic_us() + 500 * 1000;
      while (!main_seen && monotonic_us() < deadline) {
        sys_io_uring_enter(ring_fd_, 0, 0, 0);
        uint32_t h = cq_head_->load(std::memory_order_acquire);
        uint32_t t = cq_tail_->load(std::memory_order_acquire);
        while (h != t) {
          io_uring_cqe* cqe = &cqes_[h & cq_mask_];
          if (cqe->user_data == (kTagWake | 3) &&
              !(cqe->flags & IORING_CQE_F_NOTIF)) {
            res = cqe->res;
            more = (cqe->flags & IORING_CQE_F_MORE) != 0;
            main_seen = true;
          }
          ++h;
          cq_head_->store(h, std::memory_order_release);
          t = cq_tail_->load(std::memory_order_acquire);
        }
        if (!main_seen) {
          // lint:allow-blocking (one-shot SEND_ZC bring-up self-test,
          // deadline-bounded; no fibers run on this engine yet)
          usleep(1000);
        }
      }
      if (main_seen && res == 1) {
        zc_report_usage_ = usage == 1;
        char sink;
        (void)!read(b, &sink, 1);
        if (more) {
          // bounded wait for the notification so it retires before the
          // engine thread starts; a late one is ignored by the loop
          int64_t nd = monotonic_us() + 200 * 1000;
          bool notif_seen = false;
          while (!notif_seen && monotonic_us() < nd) {
            sys_io_uring_enter(ring_fd_, 0, 0, 0);
            uint32_t h = cq_head_->load(std::memory_order_acquire);
            uint32_t t = cq_tail_->load(std::memory_order_acquire);
            while (h != t) {
              io_uring_cqe* cqe = &cqes_[h & cq_mask_];
              if (cqe->user_data == (kTagWake | 3) &&
                  (cqe->flags & IORING_CQE_F_NOTIF)) {
                notif_seen = true;
              }
              ++h;
              cq_head_->store(h, std::memory_order_release);
              t = cq_tail_->load(std::memory_order_acquire);
            }
            if (!notif_seen) {
              // lint:allow-blocking (bring-up self-test, bounded to
              // 200ms by the deadline above — as the sleep above)
              usleep(1000);
            }
          }
        }
        break;
      }
      if (main_seen && res == -EINVAL && usage == 1) {
        continue;  // kernel refuses REPORT_USAGE (6.0/6.1): retry bare
      }
      sendzc_ok_ = false;  // opcode advertised but unusable: stay off
      break;
    }
    close(a);
    close(b);
  }

  void AddProvidedBuf(unsigned bid) {
    // NOT buf_ring_->bufs[]: __DECLARE_FLEX_ARRAY pads the flex member
    // to offset 8 under C++, while the kernel reads entries from offset
    // 0 with a 16-byte stride (entry 0's tail bytes alias the header)
    io_uring_buf* entries = (io_uring_buf*)buf_ring_;
    io_uring_buf* b = &entries[br_tail_ & (kNumBufs - 1)];
    b->addr = (uint64_t)(uintptr_t)(buf_base_ + (size_t)bid * kBufSize);
    b->len = kBufSize;
    b->bid = (uint16_t)bid;
    ++br_tail_;
  }

  void PublishBufTail() {
    __atomic_store_n(&buf_ring_->tail, (uint16_t)br_tail_,
                     __ATOMIC_RELEASE);
  }

  io_uring_sqe* GetSqe() {
    uint32_t head = sq_head_->load(std::memory_order_acquire);
    if (sq_tail_local_ - head >= kEntries) {
      Submit();  // ring full: flush what we have
    }
    uint32_t idx = sq_tail_local_ & sq_mask_;
    io_uring_sqe* sqe = &sqes_[idx];
    memset(sqe, 0, sizeof(*sqe));
    sq_array_[idx] = idx;
    ++sq_tail_local_;
    ++unsubmitted_;
    return sqe;
  }

  void Submit() {
    if (unsubmitted_ == 0) {
      return;
    }
    sq_tail_->store(sq_tail_local_, std::memory_order_release);
    sys_io_uring_enter(ring_fd_, unsubmitted_, 0, 0);
    unsubmitted_ = 0;
  }

  void ArmWake() {
    io_uring_sqe* sqe = GetSqe();
    sqe->opcode = IORING_OP_READ;
    sqe->fd = event_fd_;
    sqe->addr = (uint64_t)(uintptr_t)&wake_buf_;
    sqe->len = sizeof(wake_buf_);
    sqe->user_data = kTagWake;
  }

  void ArmAccept(int fd) {
    io_uring_sqe* sqe = GetSqe();
    sqe->opcode = IORING_OP_ACCEPT;
    sqe->fd = fd;
    sqe->ioprio = IORING_ACCEPT_MULTISHOT;
    sqe->accept_flags = SOCK_NONBLOCK | SOCK_CLOEXEC;
    sqe->user_data = kTagAccept | (uint64_t)(uint32_t)fd;
  }

  // 2 tag bits + 30 truncated generation bits + 32 slot bits: a late
  // CQE from a recycled slot can never be mistaken for the slot's new
  // occupant (the stored user_data differs in the generation field)
  static uint64_t RecvUserData(SocketId id) {
    return kTagRecv | (((id >> 32) & 0x3fffffffULL) << 32) |
           (uint64_t)(uint32_t)id;
  }

  void ArmRecv(SocketId id, int fd) {
    io_uring_sqe* sqe = GetSqe();
    sqe->opcode = IORING_OP_RECV;
    sqe->fd = fd;
    sqe->ioprio = IORING_RECV_MULTISHOT;
    sqe->flags = IOSQE_BUFFER_SELECT;
    sqe->buf_group = kBufGroup;
    sqe->user_data = RecvUserData(id);
  }

  void Drain() {
    std::vector<PendingOp> ops;
    {
      std::lock_guard<std::mutex> lk(mu_);
      ops.swap(pending_);
    }
    for (PendingOp& op : ops) {
      if (op.kind == 0) {
        acceptors_[op.fd] = Acceptor{op.on_accept, op.user, op.fd};
        ArmAccept(op.fd);
      } else if (op.kind == 1) {
        recv_uds_[(uint32_t)op.id] = RecvEntry{op.id, RecvUserData(op.id)};
        native_metrics().uring_active_recvs.fetch_add(
            1, std::memory_order_relaxed);
        ArmRecv(op.id, op.fd);
      } else if (op.kind == 2) {
        io_uring_sqe* sqe = GetSqe();
        sqe->opcode = IORING_OP_ASYNC_CANCEL;
        sqe->addr = RecvUserData(op.id);
        sqe->user_data = kTagWake | 2;  // completion ignored
        auto rit = recv_uds_.find((uint32_t)op.id);
        if (rit != recv_uds_.end() &&
            rit->second.ud == RecvUserData(op.id)) {
          recv_uds_.erase(rit);
          native_metrics().uring_active_recvs.fetch_sub(
              1, std::memory_order_relaxed);
        }
      } else if (op.kind == 4) {
        SendBatch* sb = op.batch;
        QueueSendBatch(sb);
        // submit THIS batch's chain now (the "single io_uring_enter per
        // drained write queue" contract): once enter returns, every op
        // holds its own struct-file reference, so the submitting fiber
        // may abandon a failing socket — a recycled fd NUMBER can no
        // longer be mistaken for this batch's file
        Submit();
        sb->ticket->submitted.store(1, std::memory_order_release);
        butex_value(sb->ticket->done)
            .fetch_add(1, std::memory_order_release);
        butex_wake_all(sb->ticket->done);
      } else if (op.kind == 5) {
        // rearm-acceptor after an EMFILE backoff pause; the acceptor may
        // have been removed while the timer was pending — then this is a
        // no-op (never re-arm a dead listener fd)
        if (acceptors_.count(op.fd) != 0) {
          ArmAccept(op.fd);
        }
      } else {  // remove-acceptor: no accept callback may fire after this
        io_uring_sqe* sqe = GetSqe();
        sqe->opcode = IORING_OP_ASYNC_CANCEL;
        sqe->addr = kTagAccept | (uint64_t)(uint32_t)op.fd;
        sqe->user_data = kTagWake | 2;
        acceptors_.erase(op.fd);
      }
      ops_done_.fetch_add(1, std::memory_order_release);
    }
  }

  // --- zero-copy egress (engine thread) ------------------------------------

  // Count the SQEs `data` needs at a given large-block threshold: one
  // SEND_ZC per big ref, one SENDMSG per run of up to kGatherIovs small
  // refs.  Shared by the submitter (pre-flight cap check) and the
  // builder, with the threshold snapshotted in the batch so both count
  // the same segments.
 public:
  static int CountSendOps(const IOBuf& data, size_t thresh) {
    int nops = 0, run = 0;
    for (size_t i = 0; i < data.block_count(); ++i) {
      if (data.ref_at(i).length >= thresh) {
        if (run > 0) {
          ++nops;
          run = 0;
        }
        ++nops;
      } else if (++run == kGatherIovs) {
        ++nops;
        run = 0;
      }
    }
    return run > 0 ? nops + 1 : nops;
  }

  void QueueSendBatch(SendBatch* b) {
    NativeMetrics& nm = native_metrics();
    int nops = CountSendOps(b->data, b->threshold);
    b->nops = nops;
    // the whole linked chain must land in ONE submission — a chain split
    // across io_uring_enter calls severs the link and reorders bytes
    uint32_t head = sq_head_->load(std::memory_order_acquire);
    if (sq_tail_local_ - head + (uint32_t)nops > kEntries) {
      Submit();
    }
    nm.uring_sendzc_batches.fetch_add(1, std::memory_order_relaxed);
    std::vector<iovec> gather;
    gather.reserve(8);
    size_t gather_len = 0;
    int built = 0;
    auto flush_gather = [&]() {
      if (gather.empty()) {
        return;
      }
      b->iovs.emplace_back(std::move(gather));
      gather.clear();
      b->hdrs.emplace_back();
      msghdr& mh = b->hdrs.back();
      memset(&mh, 0, sizeof(mh));
      mh.msg_iov = b->iovs.back().data();
      mh.msg_iovlen = b->iovs.back().size();
      io_uring_sqe* sqe = GetSqe();
      sqe->opcode = IORING_OP_SENDMSG;
      sqe->fd = b->fd;
      sqe->addr = (uint64_t)(uintptr_t)&mh;
      sqe->len = 1;  // sendmsg convention: the msghdr carries the iovecs
      sqe->msg_flags = MSG_WAITALL | MSG_NOSIGNAL;
      uint64_t ud = send_seq_++;
      sqe->user_data = ud;
      if (++built < b->nops) {
        sqe->flags |= IOSQE_IO_LINK;
      }
      send_ops_[ud] = SendOpState{b, (uint32_t)gather_len, false, false,
                                  false};
      ++b->pending_cqes;
      gather_len = 0;
    };
    for (size_t i = 0; i < b->data.block_count(); ++i) {
      const BlockRef& r = b->data.ref_at(i);
      if (r.length < b->threshold) {
        gather.push_back(
            iovec{r.block->data + r.offset, (size_t)r.length});
        gather_len += r.length;
        if (gather.size() == (size_t)kGatherIovs) {
          flush_gather();
        }
        continue;
      }
      flush_gather();
      char* addr = r.block->data + r.offset;
      int fixed = ZcBufIndex(addr, r.length);
      io_uring_sqe* sqe = GetSqe();
      sqe->opcode = kOpSendZc;
      sqe->fd = b->fd;
      sqe->addr = (uint64_t)(uintptr_t)addr;
      sqe->len = r.length;
      sqe->msg_flags = MSG_WAITALL | MSG_NOSIGNAL;
      sqe->ioprio = zc_report_usage_ ? IORING_SEND_ZC_REPORT_USAGE : 0;
      if (fixed >= 0) {
        sqe->ioprio |= IORING_RECVSEND_FIXED_BUF;
        sqe->buf_index = (uint16_t)fixed;
        nm.uring_sendzc_fixed.fetch_add(1, std::memory_order_relaxed);
      }
      uint64_t ud = send_seq_++;
      sqe->user_data = ud;
      if (++built < b->nops) {
        sqe->flags |= IOSQE_IO_LINK;
      }
      send_ops_[ud] = SendOpState{b, r.length, true, false, false};
      ++b->pending_cqes;
      ++b->pending_notifs;  // walked back if the first CQE lacks F_MORE
      nm.uring_sendzc_submitted.fetch_add(1, std::memory_order_relaxed);
    }
    flush_gather();
  }

  void FinishBatchIfIdle(SendBatch* b) {
    if (b->pending_cqes == 0 && !b->signaled) {
      b->signaled = true;
      SendTicket* t = b->ticket;
      b->ticket = nullptr;
      t->result = b->result;
      t->state.store(1, std::memory_order_release);
      butex_value(t->done).fetch_add(1, std::memory_order_release);
      butex_wake_all(t->done);
      SendTicket::Drop(t);
    }
    if (b->pending_cqes == 0 && b->pending_notifs == 0) {
      // LAST notification retired: only now do the IOBuf's block refs
      // drop — the pages were the kernel's until this point
      delete b;
    }
  }

  void OnSendCqe(io_uring_cqe* cqe) {
    auto it = send_ops_.find(cqe->user_data);
    if (it == send_ops_.end()) {
      return;  // late duplicate — nothing sane to do
    }
    SendOpState& op = it->second;
    SendBatch* b = op.batch;
    NativeMetrics& nm = native_metrics();
    if (cqe->flags & IORING_CQE_F_NOTIF) {
      // second CQE: the kernel released the pages
      op.seen_notif = true;
      --b->pending_notifs;
      nm.uring_sendzc_retired.fetch_add(1, std::memory_order_relaxed);
      if (zc_report_usage_ &&
          ((uint32_t)cqe->res & IORING_NOTIF_USAGE_ZC_COPIED) != 0) {
        // the kernel copied after all: zerocopy machinery is pure
        // overhead on THIS route (loopback / non-SG device), so mark
        // the CONNECTION — other sockets (e.g. NIC-backed peers) keep
        // the rail; whether zerocopy works is a route property
        nm.uring_sendzc_copied.fetch_add(1, std::memory_order_relaxed);
        Socket* cs = Socket::Address(b->id);
        if (cs != nullptr) {
          cs->sendzc_copied.store(true, std::memory_order_release);
          cs->Dereference();
        }
      }
    } else {
      op.seen_main = true;
      --b->pending_cqes;
      if (cqe->res < 0) {
        // keep the FIRST real error; -ECANCELED is just the rest of the
        // chain collapsing behind it
        if (b->result == 0 ||
            (b->result == -ECANCELED && cqe->res != -ECANCELED)) {
          b->result = cqe->res;
        }
      } else if ((uint32_t)cqe->res < op.len && b->result == 0) {
        // MSG_WAITALL makes short success mean the socket died mid-op
        b->result = -EPIPE;
      }
      if (op.zc && !(cqe->flags & IORING_CQE_F_MORE) && !op.seen_notif) {
        // no notification coming (failed before pinning): retire now
        op.seen_notif = true;
        --b->pending_notifs;
        nm.uring_sendzc_retired.fetch_add(1, std::memory_order_relaxed);
      }
    }
    if (op.seen_main && (!op.zc || op.seen_notif)) {
      send_ops_.erase(it);
    }
    FinishBatchIfIdle(b);
  }

  void OnRecvCqe(io_uring_cqe* cqe) {
    uint32_t slot = (uint32_t)cqe->user_data;
    auto it = recv_uds_.find(slot);
    int32_t res = cqe->res;
    bool has_buf = (cqe->flags & IORING_CQE_F_BUFFER) != 0;
    unsigned bid =
        has_buf ? (cqe->flags >> IORING_CQE_BUFFER_SHIFT) : 0;
    if (it == recv_uds_.end() || it->second.ud != cqe->user_data) {
      // stale completion from a canceled/recycled generation: recycle
      // the buffer and nothing else — the slot may already belong to a
      // NEW connection this CQE must not touch
      if (has_buf) {
        AddProvidedBuf(bid);
        PublishBufTail();
      }
      return;
    }
    NativeMetrics& nm = native_metrics();
    nm.uring_recv_completions.fetch_add(1, std::memory_order_relaxed);
    if (res > 0) {
      nm.uring_recv_bytes.fetch_add((uint64_t)res,
                                    std::memory_order_relaxed);
    }
    SocketId sid = it->second.id;
    Socket* s = Socket::Address(sid);
    if (s != nullptr && s->ring_feed != nullptr) {
      RingFeed* f = (RingFeed*)s->ring_feed;
      {
        std::lock_guard<std::mutex> lk(f->mu);
        if (res > 0 && has_buf) {
          f->staged.append(buf_base_ + (size_t)bid * kBufSize,
                           (size_t)res);
        } else if (res == 0) {
          f->eof = true;
        } else if (res < 0 && res != -ENOBUFS) {
          f->err = -res;
          f->eof = true;
        }
      }
      Socket::StartInputEvent(sid);
    }
    if (has_buf) {
      AddProvidedBuf(bid);
      PublishBufTail();
    }
    if (!(cqe->flags & IORING_CQE_F_MORE)) {
      // multishot terminated.  EOF (res 0) and real errors are terminal;
      // everything else (ENOBUFS, benign kernel retirement with data)
      // re-arms — a silently un-armed live connection would stall
      bool terminal = res == 0 || (res < 0 && res != -ENOBUFS);
      if (!terminal && s != nullptr) {
        nm.uring_rearms.fetch_add(1, std::memory_order_relaxed);
        ArmRecv(sid, s->fd);
      } else {
        recv_uds_.erase(slot);
        nm.uring_active_recvs.fetch_sub(1, std::memory_order_relaxed);
      }
    }
    if (s != nullptr) {
      s->Dereference();
    }
  }

  void Loop() {
    if (debug_) fprintf(stderr, "[uring] loop start ring_fd=%d\n", ring_fd_);
    ArmWake();
    Submit();
    while (true) {
      sys_io_uring_enter(ring_fd_, 0, 1, IORING_ENTER_GETEVENTS);
      uint32_t head = cq_head_->load(std::memory_order_acquire);
      uint32_t tail = cq_tail_->load(std::memory_order_acquire);
      bool rearm_wake = false;
      uint32_t drain_budget = UINT32_MAX;
      if (TRPC_UNLIKELY(sched_perturb_enabled())) {
        // seeded drain-batch cap: CQE batch boundaries — and the
        // Drain()/Submit() interleave between batches — become
        // seed-driven; leftover CQEs return on the next iteration
        drain_budget = 1 + (uint32_t)(sched_perturb_next(SCHED_PP_CQE) & 7);
        if (sched_perturb_point(SCHED_PP_CQE)) {
          std::this_thread::yield();  // engine-thread pause
        }
      }
      while (head != tail && drain_budget-- != 0) {
        io_uring_cqe* cqe = &cqes_[head & cq_mask_];
        shard_counters(shard_idx_).ring_cqes.fetch_add(
            1, std::memory_order_relaxed);
        uint64_t tag = cqe->user_data & kTagMask;
        if (debug_) fprintf(stderr, "[uring] cqe ud=%llx res=%d flags=%x\n",
                            (unsigned long long)cqe->user_data, cqe->res,
                            cqe->flags);
        if (tag == kTagWake) {
          if (cqe->user_data == kTagWake) {
            rearm_wake = true;
          }
        } else if (tag == kTagAccept) {
          int lfd = (int)(uint32_t)cqe->user_data;
          auto it = acceptors_.find(lfd);
          if (it != acceptors_.end()) {
            if (cqe->res >= 0) {
              native_metrics().uring_accepts.fetch_add(
                  1, std::memory_order_relaxed);
              it->second.backoff_ms = 0;
              it->second.on_accept(it->second.user, cqe->res);
            }
            if (!(cqe->flags & IORING_CQE_F_MORE)) {
              if (cqe->res >= 0) {
                ArmAccept(lfd);  // kernel dropped multishot benignly
              } else if (cqe->res == -EMFILE || cqe->res == -ENFILE ||
                         cqe->res == -ENOBUFS || cqe->res == -ENOMEM) {
                // fd/buffer exhaustion killed the multishot: erasing the
                // acceptor here would deafen the listener FOREVER (the
                // old bug) — keep it and re-arm off the timer plane with
                // exponential backoff instead of hot-spinning completions
                Acceptor& a = it->second;
                a.backoff_ms =
                    a.backoff_ms > 0 ? std::min(a.backoff_ms * 2, 1000) : 10;
                native_metrics().accept_backoffs.fetch_add(
                    1, std::memory_order_relaxed);
                uint64_t packed =
                    ((uint64_t)(uint32_t)shard_idx_ << 32) | (uint32_t)lfd;
                timer_add_oneshot(
                    monotonic_us() + (int64_t)a.backoff_ms * 1000,
                    RingRearmAcceptCb, (void*)(uintptr_t)packed);
              } else {
                // canceled or listener closed: re-arming a dead fd
                // would spin -EBADF completions forever
                acceptors_.erase(it);
              }
            }
          } else if (cqe->res >= 0) {
            close(cqe->res);  // accepted for a gone listener
          }
        } else if (tag == kTagRecv) {
          OnRecvCqe(cqe);
        } else {  // tag 00: egress send op (first CQE or notification)
          OnSendCqe(cqe);
        }
        ++head;
        cq_head_->store(head, std::memory_order_release);
        tail = cq_tail_->load(std::memory_order_acquire);
      }
      Drain();
      if (rearm_wake) {
        ArmWake();
      }
      Submit();
    }
  }

  int shard_idx_ = 0;  // which shard's reactor this engine serves
  int ring_fd_ = -1;
  int event_fd_ = -1;
  uint64_t wake_buf_ = 0;
  void* sq_ptr_ = nullptr;
  void* cq_ptr_ = nullptr;
  io_uring_sqe* sqes_ = nullptr;
  std::atomic<uint32_t>* sq_head_ = nullptr;
  std::atomic<uint32_t>* sq_tail_ = nullptr;
  uint32_t sq_mask_ = 0;
  uint32_t* sq_array_ = nullptr;
  uint32_t sq_tail_local_ = 0;
  unsigned unsubmitted_ = 0;
  std::atomic<uint32_t>* cq_head_ = nullptr;
  std::atomic<uint32_t>* cq_tail_ = nullptr;
  uint32_t cq_mask_ = 0;
  io_uring_cqe* cqes_ = nullptr;
  io_uring_buf_ring* buf_ring_ = nullptr;
  char* buf_base_ = nullptr;
  uint32_t br_tail_ = 0;

  bool debug_ = false;
  std::mutex mu_;
  std::vector<PendingOp> pending_;
  // engine-thread-only state
  std::unordered_map<int, Acceptor> acceptors_;
  struct RecvEntry {
    SocketId id;
    uint64_t ud;  // the exact user_data armed for this generation
  };
  std::unordered_map<uint32_t, RecvEntry> recv_uds_;
  uint64_t ops_enqueued_ = 0;               // guarded by mu_
  std::atomic<uint64_t> ops_done_{0};

  // zero-copy egress state
  struct SendOpState {
    SendBatch* batch;
    uint32_t len;  // bytes this op must move (short == socket died)
    bool zc;       // SEND_ZC: retires on its notification CQE
    bool seen_main;
    bool seen_notif;
  };
  uint64_t send_seq_ = 1;  // engine-thread op ids (tag bits 00)
  std::unordered_map<uint64_t, SendOpState> send_ops_;
  bool sendzc_ok_ = false;       // kernel implements IORING_OP_SEND_ZC
  bool zc_report_usage_ = false; // kernel takes IORING_SEND_ZC_REPORT_USAGE
  bool zc_registered_ = false;   // fixed-buffer table registered
  char* pool_base_ = nullptr;    // recv pbufs + zc slots, one mmap
  char* zc_base_ = nullptr;
  int zc_slots_ = 0;
  size_t zc_slot_size_ = 0;
  // lint:allow-blocking-bounded (O(1) zc-slot freelist push/pop, no
  // parks under it; the boot-time registered-buffer setup under it runs
  // once per engine before traffic exists)
  std::mutex zc_mu_;
  std::vector<int> zc_free_;

 public:
  bool sendzc() const { return sendzc_ok_; }
  bool report_usage() const { return zc_report_usage_; }

  void* ZcAlloc(size_t len) {
    if (len == 0 || len > zc_slot_size_) {
      return nullptr;
    }
    std::lock_guard<std::mutex> lk(zc_mu_);
    if (zc_free_.empty()) {
      return nullptr;
    }
    int s = zc_free_.back();
    zc_free_.pop_back();
    native_metrics().uring_zc_pool_in_use.fetch_add(
        1, std::memory_order_relaxed);
    return zc_base_ + (size_t)s * zc_slot_size_;
  }

  bool ZcFree(void* p) {
    if (zc_base_ == nullptr || (char*)p < zc_base_) {
      return false;
    }
    size_t off = (size_t)((char*)p - zc_base_);
    if (off >= (size_t)zc_slots_ * zc_slot_size_ ||
        off % zc_slot_size_ != 0) {
      return false;
    }
    std::lock_guard<std::mutex> lk(zc_mu_);
    zc_free_.push_back((int)(off / zc_slot_size_));
    native_metrics().uring_zc_pool_in_use.fetch_sub(
        1, std::memory_order_relaxed);
    return true;
  }

  // Registered-buffer index covering [p, p+len), -1 when the range is
  // not fully inside one pool slot (read-only after bring-up: safe from
  // both the engine thread and submitters).
  int ZcBufIndex(const void* p, size_t len) const {
    if (!zc_registered_ || zc_base_ == nullptr ||
        (const char*)p < zc_base_) {
      return -1;
    }
    size_t off = (size_t)((const char*)p - zc_base_);
    if (off >= (size_t)zc_slots_ * zc_slot_size_) {
      return -1;
    }
    size_t idx = off / zc_slot_size_;
    if (off + len > (idx + 1) * zc_slot_size_) {
      return -1;
    }
    return (int)idx;
  }
};

std::atomic<bool> g_uring_enabled{false};

}  // namespace

bool uring_available() {
  static bool avail = [] {
    struct io_uring_params p;
    memset(&p, 0, sizeof(p));
    int fd = sys_io_uring_setup(4, &p);
    if (fd < 0) {
      return false;
    }
    close(fd);
    // multishot recv + pbuf rings landed by 6.0; gate on the feature
    // bits we can see plus a kernel new enough to have EXT_ARG
    return (p.features & IORING_FEAT_FAST_POLL) != 0 &&
           (p.features & IORING_FEAT_EXT_ARG) != 0;
  }();
  return avail;
}

void uring_set_enabled(bool on) {
  g_uring_enabled.store(on, std::memory_order_release);
}

bool uring_enabled() {
  return g_uring_enabled.load(std::memory_order_acquire) &&
         uring_available() && RingEngine::Instance()->ok();
}

void ring_feed_release(void* feed) { delete (RingFeed*)feed; }

ssize_t ring_feed_drain(Socket* s, bool* eof) {
  RingFeed* f = (RingFeed*)s->ring_feed;
  std::lock_guard<std::mutex> lk(f->mu);
  size_t n = f->staged.size();
  if (n > 0) {
    IOBuf tmp;
    f->staged.cutn(&tmp, n);
    s->read_buf.append(std::move(tmp));
    s->bytes_in.fetch_add((uint64_t)n, std::memory_order_relaxed);
  }
  if (n == 0 && f->err != 0) {
    // staged data drains first; a recv error then surfaces exactly like
    // the epoll path: -1 with errno (NOT a clean EOF)
    errno = f->err;
    return -1;
  }
  if (f->eof) {
    *eof = true;
  }
  if (n == 0 && !f->eof) {
    errno = EAGAIN;
    return -1;
  }
  return (ssize_t)n;
}

int uring_add_acceptor(SocketId id, int fd, void (*on_accept)(void*, int),
                       void* user, int shard) {
  (void)id;
  PendingOp op;
  op.kind = 0;
  op.fd = fd;
  op.on_accept = on_accept;
  op.user = user;
  return RingEngine::Shard(shard)->Add(op);
}

int uring_add_recv(SocketId id, int fd) {
  Socket* s = Socket::Address(id);
  if (s == nullptr) {
    return -EINVAL;
  }
  int shard = s->shard;  // the socket's owning reactor holds its recv
  if (s->ring_feed == nullptr) {
    s->ring_feed = new RingFeed();
  }
  s->Dereference();
  PendingOp op;
  op.kind = 1;
  op.id = id;
  op.fd = fd;
  return RingEngine::Shard(shard)->Add(op);
}

void uring_cancel(SocketId id, int shard) {
  PendingOp op;
  op.kind = 2;
  op.id = id;
  RingEngine::Shard(shard)->Add(op);
}

void uring_rearm_acceptor(int fd, int shard) {
  PendingOp op;
  op.kind = 5;
  op.fd = fd;
  RingEngine::Shard(shard)->Add(op);
}

void uring_remove_acceptor(int fd, int shard) {
  PendingOp op;
  op.kind = 3;
  op.fd = fd;
  RingEngine* e = RingEngine::Shard(shard);
  if (e->Add(op) == 0) {
    // barrier: when this returns, no accept callback can fire for fd —
    // the Server that owned it may be freed right after
    e->Quiesce();
  }
}

// --- zero-copy egress rail -------------------------------------------------

namespace {
// TRPC_SENDZC_FORCE=1 pins the rail on even after a notification
// reported a kernel copy — for A/B benchmarking the raw SEND_ZC path on
// loopback, where the kernel always copies at delivery.
bool sendzc_forced() {
  static bool f = [] {
    const char* e = getenv("TRPC_SENDZC_FORCE");
    return e != nullptr && e[0] == '1';
  }();
  return f;
}
}  // namespace

bool uring_sendzc_available() {
  if (!uring_available()) {
    return false;
  }
  RingEngine* e = RingEngine::Instance();
  return e->ok() && e->sendzc();
}

void uring_set_sendzc(bool on) {
  g_sendzc_enabled.store(on, std::memory_order_release);
}

void uring_set_sendzc_threshold(size_t bytes) {
  if (bytes < 1024) {
    bytes = 1024;  // below this the ZC bookkeeping costs more than memcpy
  }
  g_sendzc_threshold.store(bytes, std::memory_order_release);
}

size_t uring_sendzc_threshold() {
  return g_sendzc_threshold.load(std::memory_order_relaxed);
}

bool uring_egress_ready() {
  if (!g_sendzc_enabled.load(std::memory_order_acquire)) {
    return false;
  }
  // NOTE: the per-ROUTE copied verdict lives on each Socket
  // (sendzc_copied, set from the notification CQEs); callers combine it
  // with this process-wide capability check
  return uring_enabled() && RingEngine::Instance()->sendzc();
}

bool uring_sendzc_forced() { return sendzc_forced(); }

SendTicket* SendTicket::New() {
  SendTicket* t = new SendTicket();
  t->done = butex_create();
  return t;
}

void SendTicket::Drop(SendTicket* t) {
  if (t->refs.fetch_sub(1, std::memory_order_acq_rel) == 1) {
    butex_destroy(t->done);
    delete t;
  }
}

SendTicket* uring_sendzc_submit(SocketId id, int fd, IOBuf* data,
                                int shard) {
  if (data->empty()) {
    return nullptr;
  }
  RingEngine* e = RingEngine::Shard(shard);
  if (!e->ok()) {
    return nullptr;
  }
  size_t thresh = g_sendzc_threshold.load(std::memory_order_relaxed);
  int nops = RingEngine::CountSendOps(*data, thresh);
  if (nops <= 0 || nops > kMaxBatchOps) {
    return nullptr;  // pathological ref chain: writev handles it fine
  }
  SendBatch* b = new SendBatch();
  b->id = id;
  b->fd = fd;
  b->threshold = thresh;
  b->data = std::move(*data);
  SendTicket* t = SendTicket::New();
  b->ticket = t;
  PendingOp op;
  op.kind = 4;
  op.id = id;
  op.fd = fd;
  op.batch = b;
  if (e->Add(op) != 0) {
    *data = std::move(b->data);  // hand the bytes back for the fallback
    delete b;
    SendTicket::Drop(t);
    SendTicket::Drop(t);  // engine never took its reference
    return nullptr;
  }
  return t;
}

void* uring_zc_alloc(size_t len) {
  if (!uring_enabled()) {
    return nullptr;  // pool exists only with the ring transport up
  }
  return RingEngine::Instance()->ZcAlloc(len);
}

bool uring_zc_free(void* p) {
  if (!uring_available()) {
    return false;
  }
  RingEngine* e = RingEngine::Instance();
  return e->ok() && e->ZcFree(p);
}

int uring_zc_buf_index(const void* p, size_t len) {
  if (!uring_available()) {
    return -1;
  }
  RingEngine* e = RingEngine::Instance();
  return e->ok() ? e->ZcBufIndex(p, len) : -1;
}

void uring_zc_pool_stats(int64_t* slots, int64_t* in_use) {
  NativeMetrics& m = native_metrics();
  *slots = m.uring_zc_pool_slots.load(std::memory_order_relaxed);
  *in_use = m.uring_zc_pool_in_use.load(std::memory_order_relaxed);
}

}  // namespace trpc
