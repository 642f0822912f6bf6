// engine_uring.cc — the io_uring zero-copy transport engine.
//
// Design (docs/design.md "Transport engine"): the epoll loop pays one
// syscall per socket event and one kernel-socket-buffer copy per
// payload byte. This engine replaces both on capable kernels:
//
//   * The pool arenas are registered as FIXED BUFFERS once at startup
//     (IORING_REGISTER_BUFFERS over MM::pool_spans) — the TCP analogue
//     of ibv_reg_mr, and exactly the register-once/use-forever
//     MR-cache argument NP-RDMA and fabric-lib make (PAPERS.md): the
//     kernel pins and translates the arena pages once, so hot-path IO
//     carries no per-op get_user_pages cost.
//   * OP_WRITE/OP_PUT payloads land straight in the carved pool blocks
//     via READ_FIXED (single-run plans inside a registered arena) or
//     READV — no staging buffer, no bounce copy.
//   * OP_READ responses leave via SEND_ZC / SENDMSG_ZC. Zero-copy
//     sends complete TWICE: a data CQE (bytes handed to the NIC path)
//     and a NOTIFICATION CQE (the kernel no longer references the
//     pages). Block pins are held in a slot table until the NOTIF
//     arrives — releasing on the data CQE alone could recycle a pool
//     block into a retransmit window.
//   * Header traffic rides MULTISHOT RECV over a provided-buffer ring
//     where supported (one submission serves many arrivals); entering
//     a bulk-payload state cancels the multishot and switches to
//     direct pool reads, so only header-sized tails ever get copied.
//   * ISTPU_URING_SQPOLL=1 adds a kernel submission-poller thread so a
//     saturated worker issues no syscalls at all (costs one busy core;
//     see the SQPOLL tradeoffs note in docs/design.md).
//
// liburing is deliberately not a dependency (the build image lacks it,
// and the container kernels this repo targets often lack io_uring
// entirely): the ring is managed with raw syscalls + mmap, and opcodes
// newer than the build header are compiled from their fixed kernel ABI
// numbers. Everything feature-detects at runtime and falls back —
// auto-selection falls back to epoll before this engine is even
// constructed (uring_runtime_supported), and within the engine each
// optional feature (fixed buffers, ZC sends, multishot) degrades to
// the portable submission independently.
//
// Threading: one ring per worker, touched only by the owning worker
// thread (init/shutdown run before spawn / after join) — no locks, no
// ranks, same serialization contract as the epoll engine.
#include <errno.h>
#include <string.h>

#include <string>
#include <unordered_map>
#include <vector>

#include "engine.h"
#include "failpoint.h"
#include "log.h"
#include "server.h"
#include "utils.h"

#if defined(__linux__) && __has_include(<linux/io_uring.h>)
#define ISTPU_HAVE_URING 1
#include <linux/io_uring.h>
#include <linux/time_types.h>
#include <poll.h>
#include <sys/eventfd.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/syscall.h>
#include <sys/uio.h>
#include <time.h>
#include <unistd.h>
#endif

namespace istpu {

bool uring_runtime_supported(std::string* why) {
    // Forced-fallback testing: the failpoint makes `auto` pick epoll
    // (and `uring` fail loudly) on any host, capable or not.
    if (IST_FAILPOINT("engine.uring_setup")) {
        if (why) *why = "engine.uring_setup failpoint armed";
        return false;
    }
#ifdef ISTPU_HAVE_URING
    struct io_uring_params p;
    memset(&p, 0, sizeof(p));
    int fd = int(syscall(__NR_io_uring_setup, 4, &p));
    if (fd < 0) {
        // ENOSYS: pre-5.1 kernel. EPERM: seccomp/sysctl blocked —
        // both common in CI containers; auto falls back to epoll.
        if (why) *why = std::string("io_uring_setup: ") + strerror(errno);
        return false;
    }
    close(fd);
    return true;
#else
    if (why) *why = "built without <linux/io_uring.h>";
    return false;
#endif
}

#ifndef ISTPU_HAVE_URING

namespace {
// Build-gated stub (the hard "no new deps" constraint): init() always
// fails, so auto falls back to epoll and forced uring fails start().
class EngineUringUnavailable final : public Engine {
   public:
    const char* name() const override { return "uring"; }
    bool init() override { return false; }
    void shutdown() override {}
    void poll() override {}
    void conn_added(Conn&) override {}
    void conn_closing(Conn&) override {}
    void output_ready(Conn&) override {}
};
}  // namespace

std::unique_ptr<Engine> make_engine_uring(Server&, Worker&) {
    return std::make_unique<EngineUringUnavailable>();
}

#else  // ISTPU_HAVE_URING

namespace {

// ---------------------------------------------------------------------------
// Kernel-ABI numbers newer than the build image's <linux/io_uring.h>
// (5.10-era). These are frozen uapi values; runtime probes decide
// whether the running kernel honors them.
// ---------------------------------------------------------------------------
constexpr uint8_t kOpSendZc = 47;     // IORING_OP_SEND_ZC      (6.0)
constexpr uint8_t kOpSendmsgZc = 48;  // IORING_OP_SENDMSG_ZC   (6.1)
constexpr uint16_t kRecvMultishot = 1u << 1;    // IORING_RECV_MULTISHOT
constexpr uint16_t kRecvsendFixedBuf = 1u << 2; // IORING_RECVSEND_FIXED_BUF
constexpr uint16_t kAcceptMultishot = 1u << 0;  // IORING_ACCEPT_MULTISHOT (5.19)
constexpr uint32_t kCqeFBuffer = 1u << 0;       // IORING_CQE_F_BUFFER
constexpr uint32_t kCqeFMore = 1u << 1;         // IORING_CQE_F_MORE
constexpr uint32_t kCqeFNotif = 1u << 3;        // IORING_CQE_F_NOTIF
constexpr int kCqeBufferShift = 16;             // IORING_CQE_BUFFER_SHIFT
constexpr unsigned kRegisterPbufRing = 22;      // (5.19)
constexpr unsigned kUnregisterPbufRing = 23;
// IORING_FEAT_SQPOLL_NONFIXED (5.11) — may be absent from the build
// header; the value is frozen uapi like the opcodes above
// (POLL_32BITS holds 1u<<6; NONFIXED is the next bit up).
#ifdef IORING_FEAT_SQPOLL_NONFIXED
constexpr uint32_t kFeatSqpollNonfixed = IORING_FEAT_SQPOLL_NONFIXED;
#else
constexpr uint32_t kFeatSqpollNonfixed = 1u << 7;
#endif

struct PbufRingReg {  // struct io_uring_buf_reg (5.19 uapi)
    uint64_t ring_addr;
    uint32_t ring_entries;
    uint16_t bgid;
    uint16_t flags;
    uint64_t resv[3];
};
struct Pbuf {  // struct io_uring_buf; entry 0's resv doubles as tail
    uint64_t addr;
    uint32_t len;
    uint16_t bid;
    uint16_t resv;
};
static_assert(sizeof(Pbuf) == 16, "io_uring_buf ABI");

int sys_uring_setup(unsigned entries, io_uring_params* p) {
    return int(syscall(__NR_io_uring_setup, entries, p));
}
int sys_uring_enter(int fd, unsigned to_submit, unsigned min_complete,
                    unsigned flags) {
    return int(syscall(__NR_io_uring_enter, fd, to_submit, min_complete,
                       flags, nullptr, 0));
}
int sys_uring_register(int fd, unsigned opcode, const void* arg,
                       unsigned nr) {
    return int(syscall(__NR_io_uring_register, fd, opcode, arg, nr));
}

// Minimal liburing-free ring: setup + the three mmaps, a shadow SQ
// tail, release/acquire publication exactly as the io_uring ABI
// specifies. Single-threaded by construction (worker-owned).
//
// SQE allocation follows liburing's model: get_sqe() only advances the
// PRIVATE local_tail; the shared *sq_tail is published in submit(),
// after the caller has finished writing every allocated SQE. Under
// SQPOLL the kernel poller consumes entries the instant the shared
// tail moves, so publishing at allocation would let it read a zeroed
// or half-written SQE (a dropped NOP at best, IO against the wrong
// fd/addr at worst).
struct RawRing {
    int fd = -1;
    io_uring_params p{};
    void* sq_ptr = nullptr;
    size_t sq_len = 0;
    void* cq_ptr = nullptr;
    size_t cq_len = 0;
    void* sqe_ptr = nullptr;
    size_t sqe_len = 0;
    unsigned* sq_head = nullptr;
    unsigned* sq_tail = nullptr;
    unsigned* sq_mask = nullptr;
    unsigned* sq_flags = nullptr;
    unsigned* sq_array = nullptr;
    io_uring_sqe* sqes = nullptr;
    unsigned* cq_head = nullptr;
    unsigned* cq_tail = nullptr;
    unsigned* cq_mask = nullptr;
    io_uring_cqe* cqes = nullptr;
    unsigned local_tail = 0;  // shadow of *sq_tail
    unsigned pending = 0;     // written, not yet submitted
    bool wedged = false;      // unrecoverable enter failure

    bool open(unsigned entries, bool sqpoll, std::string* why) {
        if (sqpoll) {
            memset(&p, 0, sizeof(p));
            p.flags |= IORING_SETUP_SQPOLL;
            p.sq_thread_idle = 2000;  // ms before the poller naps
            fd = sys_uring_setup(entries, &p);
            if (fd >= 0 && (p.features & kFeatSqpollNonfixed) == 0) {
                // Pre-5.11 SQPOLL only accepts IOSQE_FIXED_FILE
                // (registered) fds; this engine submits plain socket
                // fds, so every recv/send would EBADF. Setup succeeds
                // there for privileged processes, so the feature bit —
                // not the setup result — is the gate.
                IST_WARN("io_uring SQPOLL lacks SQPOLL_NONFIXED "
                         "(pre-5.11 kernel); using the plain ring");
                close(fd);
                fd = -1;
            } else if (fd < 0) {
                // SQPOLL needs privileges on pre-5.13 kernels: degrade
                // to the plain ring rather than refusing the engine.
                IST_WARN("io_uring SQPOLL setup failed (%s); retrying "
                         "without SQPOLL",
                         strerror(errno));
            }
        }
        if (fd < 0) {
            memset(&p, 0, sizeof(p));
            fd = sys_uring_setup(entries, &p);
        }
        if (fd < 0) {
            if (why) {
                *why = std::string("io_uring_setup: ") + strerror(errno);
            }
            return false;
        }
        sq_len = p.sq_off.array + p.sq_entries * sizeof(unsigned);
        cq_len = p.cq_off.cqes + p.cq_entries * sizeof(io_uring_cqe);
        sqe_len = p.sq_entries * sizeof(io_uring_sqe);
        sq_ptr = mmap(nullptr, sq_len, PROT_READ | PROT_WRITE,
                      MAP_SHARED | MAP_POPULATE, fd, IORING_OFF_SQ_RING);
        cq_ptr = mmap(nullptr, cq_len, PROT_READ | PROT_WRITE,
                      MAP_SHARED | MAP_POPULATE, fd, IORING_OFF_CQ_RING);
        sqe_ptr = mmap(nullptr, sqe_len, PROT_READ | PROT_WRITE,
                       MAP_SHARED | MAP_POPULATE, fd, IORING_OFF_SQES);
        if (sq_ptr == MAP_FAILED || cq_ptr == MAP_FAILED ||
            sqe_ptr == MAP_FAILED) {
            if (why) *why = std::string("ring mmap: ") + strerror(errno);
            close_ring();
            return false;
        }
        auto* sqb = static_cast<uint8_t*>(sq_ptr);
        sq_head = reinterpret_cast<unsigned*>(sqb + p.sq_off.head);
        sq_tail = reinterpret_cast<unsigned*>(sqb + p.sq_off.tail);
        sq_mask = reinterpret_cast<unsigned*>(sqb + p.sq_off.ring_mask);
        sq_flags = reinterpret_cast<unsigned*>(sqb + p.sq_off.flags);
        sq_array = reinterpret_cast<unsigned*>(sqb + p.sq_off.array);
        sqes = static_cast<io_uring_sqe*>(sqe_ptr);
        auto* cqb = static_cast<uint8_t*>(cq_ptr);
        cq_head = reinterpret_cast<unsigned*>(cqb + p.cq_off.head);
        cq_tail = reinterpret_cast<unsigned*>(cqb + p.cq_off.tail);
        cq_mask = reinterpret_cast<unsigned*>(cqb + p.cq_off.ring_mask);
        cqes = reinterpret_cast<io_uring_cqe*>(cqb + p.cq_off.cqes);
        // Identity-fill the indirection array once; publishing is then
        // a single tail store.
        for (unsigned i = 0; i < p.sq_entries; ++i) sq_array[i] = i;
        local_tail = *sq_tail;
        return true;
    }

    void close_ring() {
        if (sq_ptr != nullptr && sq_ptr != MAP_FAILED) munmap(sq_ptr, sq_len);
        if (cq_ptr != nullptr && cq_ptr != MAP_FAILED) munmap(cq_ptr, cq_len);
        if (sqe_ptr != nullptr && sqe_ptr != MAP_FAILED) {
            munmap(sqe_ptr, sqe_len);
        }
        sq_ptr = cq_ptr = sqe_ptr = nullptr;
        if (fd >= 0) close(fd);
        fd = -1;
    }

    bool sqpoll() const { return (p.flags & IORING_SETUP_SQPOLL) != 0; }

    // Submit what is pending; wait_nr > 0 additionally blocks for
    // completions (bounded by the engine's persistent TIMEOUT SQE).
    // This is the single publication point for the shared SQ tail —
    // every SQE up to local_tail is fully written by now.
    bool submit(unsigned wait_nr) {
        __atomic_store_n(sq_tail, local_tail, __ATOMIC_RELEASE);
        while (true) {
            unsigned flags = 0;
            unsigned to_submit = pending;
            if (sqpoll()) {
                to_submit = 0;
                if (__atomic_load_n(sq_flags, __ATOMIC_ACQUIRE) &
                    IORING_SQ_NEED_WAKEUP) {
                    flags |= IORING_ENTER_SQ_WAKEUP;
                }
                pending = 0;  // the kernel poller consumes the tail
                if (wait_nr == 0 && flags == 0) return true;
            }
            if (wait_nr > 0) flags |= IORING_ENTER_GETEVENTS;
            int r = sys_uring_enter(fd, to_submit, wait_nr, flags);
            if (r >= 0) {
                if (!sqpoll()) {
                    pending -= pending < unsigned(r) ? pending
                                                     : unsigned(r);
                }
                return true;
            }
            if (errno == EINTR) continue;
            if (errno == EBUSY || errno == EAGAIN) {
                // CQ backpressure: completions must drain first. The
                // caller reaps and the pending SQEs go next round.
                return true;
            }
            IST_ERROR("io_uring_enter: %s", strerror(errno));
            wedged = true;
            return false;
        }
    }

    io_uring_sqe* get_sqe() {
        for (int tries = 0; tries < 3; ++tries) {
            unsigned head = __atomic_load_n(sq_head, __ATOMIC_ACQUIRE);
            if (local_tail - head < p.sq_entries) {
                io_uring_sqe* e = &sqes[local_tail & *sq_mask];
                memset(e, 0, sizeof(*e));
                // Shadow-tail only: the entry is not visible to the
                // kernel until submit() publishes *sq_tail, after the
                // caller has filled it in.
                local_tail++;
                pending++;
                return e;
            }
            // SQ full: push what we have (waiting once if the kernel
            // is genuinely behind).
            if (!submit(tries == 0 ? 0u : 1u)) break;
        }
        return nullptr;
    }

    template <typename Fn>
    void reap(Fn&& fn) {
        // *cq_head is re-read every iteration rather than shadowed in
        // a local: fn can reap again underneath us (flush_for_close
        // drains the CQ mid-dispatch when a close hits CQ
        // backpressure), and a stale local head would re-deliver
        // entries the nested reap already consumed.
        while (true) {
            unsigned head = *cq_head;
            unsigned tail = __atomic_load_n(cq_tail, __ATOMIC_ACQUIRE);
            if (head == tail) break;
            io_uring_cqe cqe = cqes[head & *cq_mask];
            __atomic_store_n(cq_head, head + 1, __ATOMIC_RELEASE);
            fn(cqe);
        }
    }
};

// user_data: one routing tag byte + a 56-bit payload (connection id or
// zero-copy slot index). Connection ids are process-unique and only
// ever compared — stale completions for closed connections miss the
// map and are dropped.
enum UdTag : uint64_t {
    kTagRx = 1,       // oneshot staged recv / direct READV / READ_FIXED
    kTagMsRx = 2,     // multishot recv (provided buffers)
    kTagTx = 3,       // plain SEND/SENDMSG
    kTagZc = 4,       // SEND_ZC/SENDMSG_ZC (payload = slot index)
    kTagWake = 5,
    kTagListen = 6,
    kTagTimeout = 7,
    kTagCancel = 8,
    kTagMsAccept = 9, // multishot accept (CQE res = accepted fd)
};
constexpr uint64_t make_ud(uint64_t tag, uint64_t v) {
    return (tag << 56) | (v & ((1ull << 56) - 1));
}

constexpr size_t kStageBytes = 16u << 10;   // oneshot header staging
constexpr unsigned kPbufEntries = 64;       // provided-buffer ring
constexpr size_t kPbufBytes = 16u << 10;
constexpr uint16_t kBgid = 7;
// Below this many remaining payload bytes a zero-copy send is not
// worth the notification round trip (kernel guidance: ZC wins from
// ~10 KB); smaller responses take the plain gather submission.
constexpr size_t kZcMinBytes = 16u << 10;

}  // namespace

class EngineUring final : public Engine {
   public:
    EngineUring(Server& srv, Worker& w) : s_(srv), w_(w) {}
    ~EngineUring() override { shutdown(); }

    const char* name() const override { return "uring"; }

    bool init() override;
    void shutdown() override;
    void poll() override;
    void conn_added(Conn& c) override;
    void conn_closing(Conn& c) override;
    void output_ready(Conn& c) override;

   private:
    enum RxMode : uint8_t {
        RX_IDLE = 0,
        RX_STAGED,    // oneshot recv into the staging buffer
        RX_DIRECT,    // READV/READ_FIXED straight into pool blocks
        RX_MS,        // multishot recv armed (provided buffers)
        RX_MS_CANCEL, // multishot being cancelled before a direct read
    };

    // Engine-private per-connection state. Owned by the ENGINE (not
    // the Conn): it anchors the iovec/msghdr storage in-flight SQEs
    // point at, so it must outlive a closed connection until every
    // completion for it has drained.
    struct UConn {
        Conn* c = nullptr;  // null once the server closed the conn
        uint64_t id = 0;
        int fd = -1;
        int outstanding = 0;  // CQEs still owed to this state
        RxMode rx = RX_IDLE;
        bool tx_inflight = false;
        std::vector<uint8_t> stage;
        struct iovec riov[64];
        int rn = 0;
        std::shared_ptr<OutMsg> sending;  // popped front of c->outq
        struct iovec siov[64];
        struct msghdr smsg {};
    };

    // Zero-copy send slot: pins the OutMsg (pool BlockRefs + heap
    // refs) until BOTH the data CQE and the kernel's F_NOTIF CQE have
    // arrived — the notification, not the data completion, is when the
    // kernel stops referencing the pages.
    struct ZcSlot {
        bool used = false;
        bool data_done = false;
        bool notif_done = false;
        // Count this send's bytes into uring_copies_avoided at the
        // data CQE (from cqe.res, the bytes actually transmitted) —
        // counting at submission would tally the full remainder again
        // on every partial-send resubmit.
        bool count_copies = false;
        uint64_t conn_id = 0;
        std::shared_ptr<OutMsg> msg;
    };

    UConn* find(uint64_t id) {
        auto it = conns_.find(id);
        return it == conns_.end() ? nullptr : it->second.get();
    }
    void maybe_gc(uint64_t id) {
        auto it = conns_.find(id);
        if (it != conns_.end() && it->second->c == nullptr &&
            it->second->outstanding == 0) {
            conns_.erase(it);
        }
    }

    io_uring_sqe* sqe(uint8_t opcode, int fd, uint64_t ud) {
        io_uring_sqe* e = r_.get_sqe();
        if (e == nullptr) {
            if (!sq_wedged_logged_) {
                sq_wedged_logged_ = true;
                IST_ERROR("io_uring submission queue wedged");
            }
            return nullptr;
        }
        e->opcode = opcode;
        e->fd = fd;
        e->user_data = ud;
        w_.eng_sqes.fetch_add(1, std::memory_order_relaxed);
        return e;
    }

    void arm_poll(int fd, uint64_t ud) {
        io_uring_sqe* e = sqe(IORING_OP_POLL_ADD, fd, ud);
        if (e != nullptr) e->poll_events = POLLIN;
    }
    // Multishot accept (5.19+): ONE standing SQE yields a CQE per
    // accepted socket (res = the new fd) until the kernel clears
    // F_MORE — the 10k-conn accept path stops paying one POLL_ADD
    // re-arm + accept4 syscall per connection. Support is not
    // probeable (it rides the ioprio flag, not an opcode), so the
    // first completion's -EINVAL demotes PERMANENTLY to the classic
    // poll+accept4 path.
    void arm_ms_accept() {
        io_uring_sqe* e = sqe(IORING_OP_ACCEPT, w_.listen_fd,
                              make_ud(kTagMsAccept, 0));
        if (e == nullptr) return;
        e->ioprio = kAcceptMultishot;
        e->accept_flags = SOCK_NONBLOCK | SOCK_CLOEXEC;
    }
    void arm_timeout() {
        ts_.tv_sec = 0;
        ts_.tv_nsec = 500ll * 1000 * 1000;  // the epoll_wait(500ms) twin
        io_uring_sqe* e = sqe(IORING_OP_TIMEOUT, -1,
                              make_ud(kTagTimeout, 0));
        if (e != nullptr) {
            e->addr = uint64_t(uintptr_t(&ts_));
            e->len = 1;
            timeout_armed_ = true;
        }
    }
    void submit_cancel(uint64_t target_ud) {
        io_uring_sqe* e = sqe(IORING_OP_ASYNC_CANCEL, -1,
                              make_ud(kTagCancel, 0));
        if (e != nullptr) e->addr = target_ud;
    }

    bool register_pool_buffers();
    bool setup_pbuf_ring();
    void pbuf_recycle(uint16_t bid);
    const uint8_t* pbuf_ptr(uint16_t bid) const {
        return pbuf_mem_.data() + size_t(bid) * kPbufBytes;
    }
    // The registered-buffer index covering [p, p+len), or -1.
    int find_regbuf(const void* p, size_t len) const;

    void arm_rx(UConn& u);
    void arm_staged(UConn& u);
    void arm_direct(UConn& u);
    void arm_ms(UConn& u);
    void rearm_rx(UConn& u);
    // `mode` is the RxMode the completed submission was issued under
    // (captured before dispatch resets it): it decides whether the
    // bytes landed in pool blocks (direct) or a staging/provided
    // buffer (ingest) — the connection state alone cannot, since an
    // ENOBUFS fallback can run a staged recv mid-payload.
    void on_rx(UConn& u, const io_uring_cqe& cqe, bool multishot,
               RxMode mode);

    void start_tx(UConn& u);
    void advance_tx(UConn& u, size_t n);
    uint32_t alloc_zc_slot(UConn& u);
    void finish_zc_slot(uint32_t idx);
    void finish_zc_slot_on_abort(uint32_t idx);
    void on_tx(UConn& u, const io_uring_cqe& cqe);
    void on_zc(uint32_t slot, const io_uring_cqe& cqe);

   public:
    size_t inflight_slots() const override {
        return zc_live_.load(std::memory_order_relaxed);
    }
    bool healthy() const override { return !r_.wedged; }

   private:

    void dispatch(const io_uring_cqe& cqe);
    void flush_for_close();

    Server& s_;
    Worker& w_;
    RawRing r_;
    bool inited_ = false;
    bool armed_initial_ = false;  // first-poll arming (worker thread)
    bool timeout_armed_ = false;
    bool sq_wedged_logged_ = false;
    // Runtime feature set (probed in init(); each degrades alone).
    bool zc_ok_ = false;       // IORING_OP_SEND_ZC
    bool zc_msg_ok_ = false;   // IORING_OP_SENDMSG_ZC
    bool ms_ok_ = false;       // multishot recv + provided-buffer ring
    // Multishot accept: wanted (ISTPU_URING_MS_ACCEPT, default on,
    // probed as "op ACCEPT supported" in init) and still believed to
    // work (flipped off permanently by a runtime -EINVAL — the flag
    // predates any probe surface).
    bool ms_accept_ok_ = false;
    bool bufs_registered_ = false;
    struct RegBuf {
        uint8_t* base;
        size_t len;
    };
    std::vector<RegBuf> regbufs_;
    // Provided-buffer ring memory (shared with the kernel).
    void* pbuf_ring_ = nullptr;
    size_t pbuf_ring_len_ = 0;
    uint16_t pbuf_tail_ = 0;
    std::vector<uint8_t> pbuf_mem_;
    std::unordered_map<uint64_t, std::unique_ptr<UConn>> conns_;
    // CQEs reaped inside flush_for_close (which can run inside
    // dispatch) are parked here and dispatched at the top of the next
    // poll() — dispatching them in place would re-enter the connection
    // handlers mid-frame.
    std::vector<io_uring_cqe> deferred_;
    std::vector<ZcSlot> zc_slots_;
    std::vector<uint32_t> zc_free_;
    // Live zc-slot count, mirrored atomically so the deep-state
    // endpoint can read occupancy from the control plane while the
    // worker churns the table.
    std::atomic<size_t> zc_live_{0};
    struct __kernel_timespec ts_ {};
};

// ---------------------------------------------------------------------------
// setup / teardown
// ---------------------------------------------------------------------------

bool EngineUring::init() {
    bool sqpoll = false;
    if (const char* env = getenv("ISTPU_URING_SQPOLL")) {
        sqpoll = env[0] == '1';
    }
    std::string why;
    if (!r_.open(256, sqpoll, &why)) {
        IST_WARN("io_uring ring setup failed: %s", why.c_str());
        return false;
    }
    inited_ = true;
    // Op support probe (IORING_REGISTER_PROBE, 5.6+). A kernel too old
    // to probe is also too old for any of the optional ops.
    {
        // io_uring_probe ends in a flexible ops[] array, so it cannot
        // be a member followed by storage: carve both out of one
        // aligned byte buffer.
        constexpr unsigned kProbeOps = 256;
        alignas(io_uring_probe) unsigned char
            buf[sizeof(io_uring_probe) +
                kProbeOps * sizeof(io_uring_probe_op)] = {};
        auto* pr = reinterpret_cast<io_uring_probe*>(buf);
        if (sys_uring_register(r_.fd, IORING_REGISTER_PROBE, pr,
                               kProbeOps) == 0) {
            auto supported = [&](uint8_t op) {
                return pr->last_op >= op &&
                       (pr->ops[op].flags & IO_URING_OP_SUPPORTED) != 0;
            };
            zc_ok_ = supported(kOpSendZc);
            zc_msg_ok_ = supported(kOpSendmsgZc);
        }
    }
    bufs_registered_ = register_pool_buffers();
    bool want_ms = true;
    if (const char* env = getenv("ISTPU_URING_MULTISHOT")) {
        want_ms = env[0] != '0';
    }
    // Multishot recv shipped after SEND_ZC's prerequisites; gate it on
    // the pbuf-ring registration succeeding (5.19+) AND the ZC probe
    // (6.0+) so a 5.19-6.0 kernel never sees an EINVAL storm.
    ms_ok_ = want_ms && zc_ok_ && setup_pbuf_ring();
    // Multishot accept (ISSUE 18): the flag is unprobeable (it rides
    // ioprio, not an opcode), so attempt it whenever wanted — an old
    // kernel answers the standing SQE with one -EINVAL CQE and the
    // dispatch demotes permanently to the classic poll+accept4 path.
    ms_accept_ok_ = true;
    if (const char* env = getenv("ISTPU_URING_MS_ACCEPT")) {
        ms_accept_ok_ = env[0] != '0';
    }
    // NOTE: no SQE is armed (and nothing is submitted) here. init()
    // runs on the STARTING thread, and io_uring binds each request's
    // completion task-work to the task that submitted it — arming the
    // wake/listen polls from here hands their (and their accepted
    // connections') task-work to the embedding process's main thread,
    // which modern kernels interrupt with TWA_SIGNAL: every blocking
    // syscall on that thread — a same-process native client's
    // connect(), a Python control-plane read — starts failing EINTR
    // for the ring's whole lifetime. The first poll() on the OWNING
    // worker thread arms them instead (arm_initial below).
    IST_INFO("worker %d io_uring engine: sqpoll=%d fixed_bufs=%zu "
             "send_zc=%d sendmsg_zc=%d multishot=%d",
             w_.idx, r_.sqpoll() ? 1 : 0, regbufs_.size(), zc_ok_ ? 1 : 0,
             zc_msg_ok_ ? 1 : 0, ms_ok_ ? 1 : 0);
    return true;
}

bool EngineUring::register_pool_buffers() {
    if (s_.mm_ == nullptr) return false;
    auto spans = s_.mm_->pool_spans();
    if (spans.empty()) return false;
    std::vector<struct iovec> iov(spans.size());
    for (size_t i = 0; i < spans.size(); ++i) {
        iov[i].iov_base = spans[i].first;
        iov[i].iov_len = spans[i].second;
    }
    if (sys_uring_register(r_.fd, IORING_REGISTER_BUFFERS, iov.data(),
                           unsigned(iov.size())) != 0) {
        // Registration pins the arenas against RLIMIT_MEMLOCK — multi-GB
        // pools routinely exceed it for unprivileged processes. Plain
        // READV/SENDMSG_ZC still avoid the bounce copy; only the
        // per-op page-pin saving is lost.
        IST_INFO("io_uring fixed-buffer registration failed (%s); "
                 "running without registered arenas",
                 strerror(errno));
        return false;
    }
    regbufs_.reserve(spans.size());
    for (auto& sp : spans) regbufs_.push_back(RegBuf{sp.first, sp.second});
    return true;
}

bool EngineUring::setup_pbuf_ring() {
    pbuf_ring_len_ = kPbufEntries * sizeof(Pbuf);
    pbuf_ring_ = mmap(nullptr, pbuf_ring_len_, PROT_READ | PROT_WRITE,
                      MAP_ANONYMOUS | MAP_PRIVATE, -1, 0);
    if (pbuf_ring_ == MAP_FAILED) {
        pbuf_ring_ = nullptr;
        return false;
    }
    PbufRingReg reg{};
    reg.ring_addr = uint64_t(uintptr_t(pbuf_ring_));
    reg.ring_entries = kPbufEntries;
    reg.bgid = kBgid;
    if (sys_uring_register(r_.fd, kRegisterPbufRing, &reg, 1) != 0) {
        munmap(pbuf_ring_, pbuf_ring_len_);
        pbuf_ring_ = nullptr;
        return false;
    }
    pbuf_mem_.resize(size_t(kPbufEntries) * kPbufBytes);
    pbuf_tail_ = 0;
    for (uint16_t i = 0; i < kPbufEntries; ++i) pbuf_recycle(i);
    return true;
}

void EngineUring::pbuf_recycle(uint16_t bid) {
    auto* ring = static_cast<Pbuf*>(pbuf_ring_);
    Pbuf& e = ring[pbuf_tail_ & (kPbufEntries - 1)];
    e.addr = uint64_t(uintptr_t(pbuf_mem_.data())) +
             uint64_t(bid) * kPbufBytes;
    e.len = uint32_t(kPbufBytes);
    e.bid = bid;
    pbuf_tail_++;
    // The ring tail lives in entry 0's resv slot (io_uring_buf_ring
    // ABI); release-publish so the kernel sees the entry before the
    // tail bump.
    __atomic_store_n(&ring[0].resv, pbuf_tail_, __ATOMIC_RELEASE);
}

int EngineUring::find_regbuf(const void* p, size_t len) const {
    if (!bufs_registered_) return -1;
    const uint8_t* q = static_cast<const uint8_t*>(p);
    for (size_t i = 0; i < regbufs_.size(); ++i) {
        if (q >= regbufs_[i].base &&
            q + len <= regbufs_[i].base + regbufs_[i].len) {
            return int(i);
        }
    }
    return -1;
}

void EngineUring::shutdown() {
    if (!inited_) return;
    inited_ = false;
    if (pbuf_ring_ != nullptr) {
        sys_uring_register(r_.fd, kUnregisterPbufRing, nullptr, 0);
        munmap(pbuf_ring_, pbuf_ring_len_);
        pbuf_ring_ = nullptr;
    }
    r_.close_ring();
    // Drop engine-held pins NOW (the pool still exists at every
    // shutdown call site): queued sends, zero-copy holds, per-conn
    // state. The ring fd is closed, so the kernel no longer touches
    // the pages.
    conns_.clear();
    deferred_.clear();  // parked CQEs index state that just died
    zc_slots_.clear();
    zc_free_.clear();
    zc_live_.store(0, std::memory_order_relaxed);
    regbufs_.clear();
    pbuf_mem_.clear();
}

// ---------------------------------------------------------------------------
// poll + dispatch
// ---------------------------------------------------------------------------

void EngineUring::poll() {
    if (!armed_initial_) {
        // First poll() on the owning worker thread: arm the wake and
        // listen polls HERE so their completion task-work targets this
        // thread, never the thread that ran init() (see the init()
        // note — arming there EINTR-storms the embedder's main
        // thread on TWA_SIGNAL kernels).
        armed_initial_ = true;
        arm_poll(w_.wake_fd, make_ud(kTagWake, 0));
        if (w_.listen_fd >= 0) {
            if (ms_accept_ok_) {
                arm_ms_accept();
            } else {
                arm_poll(w_.listen_fd, make_ud(kTagListen, 0));
            }
        }
        arm_timeout();
    }
    if (r_.wedged) {
        // Unrecoverable enter failure: behave like a stalled loop (the
        // outer loop still re-checks running_ for shutdown).
        struct timespec ts {0, 100 * 1000 * 1000};
        nanosleep(&ts, nullptr);
        return;
    }
    if (!deferred_.empty()) {
        // CQEs parked by flush_for_close; dispatching can park more
        // (a handler closing another connection under backpressure),
        // so swap the batch out first.
        std::vector<io_uring_cqe> batch;
        batch.swap(deferred_);
        for (const io_uring_cqe& cqe : batch) dispatch(cqe);
    }
    if (!timeout_armed_) arm_timeout();
    // Don't block waiting for a fresh completion if dispatching the
    // batch above parked MORE CQEs (a handler closed a connection
    // under backpressure): they are already-completed work and must
    // not sit behind a GETEVENTS wait for up to the 500ms timeout.
    if (!r_.submit(deferred_.empty() ? 1u : 0u)) return;
    r_.reap([this](const io_uring_cqe& cqe) { dispatch(cqe); });
}

// Hand every written SQE to the kernel before the caller closes an fd
// they may reference. submit() alone is not enough: EBUSY/EAGAIN from
// io_uring_enter (CQ backpressure) returns without submitting, and
// under SQPOLL the poller consumes the published tail asynchronously —
// either way an unsubmitted recv/send/cancel could survive the close,
// get picked up after the fd number is reused by a new accept, and
// silently consume the new connection's bytes. Loop until the kernel
// owns everything: drain the CQ (into deferred_, never dispatched
// here — this runs inside dispatch()) to relieve backpressure, and
// for SQPOLL wait for sq_head to reach the published tail.
void EngineUring::flush_for_close() {
    for (int spins = 0; !r_.wedged; ++spins) {
        if (!r_.submit(0)) return;  // wedged: the ring is dead
        bool drained =
            r_.sqpoll() ? __atomic_load_n(r_.sq_head, __ATOMIC_ACQUIRE) ==
                              r_.local_tail
                        : r_.pending == 0;
        if (drained) return;
        r_.reap(
            [this](const io_uring_cqe& cqe) { deferred_.push_back(cqe); });
        if (spins >= 10000) {
            // ~1s of refusal (dead SQPOLL poller?): give up loudly
            // rather than hang the worker; the close may now race an
            // unsubmitted SQE, but a wedged ring is already fatal.
            IST_ERROR("io_uring pre-close flush did not drain");
            return;
        }
        if (spins >= 100) {
            struct timespec ts {0, 100 * 1000};
            nanosleep(&ts, nullptr);
        }
    }
}

void EngineUring::dispatch(const io_uring_cqe& cqe) {
    uint64_t tag = cqe.user_data >> 56;
    uint64_t v = cqe.user_data & ((1ull << 56) - 1);
    switch (tag) {
        case kTagTimeout:
            timeout_armed_ = false;
            return;
        case kTagCancel:
            return;  // result of ASYNC_CANCEL itself: uninteresting
        case kTagWake: {
            uint64_t tmp;
            ssize_t r = read(w_.wake_fd, &tmp, sizeof(tmp));
            (void)r;
            s_.adopt_pending(w_);
            arm_poll(w_.wake_fd, make_ud(kTagWake, 0));
            return;
        }
        case kTagListen:
            s_.accept_ready(w_, w_.listen_fd);
            arm_poll(w_.listen_fd, make_ud(kTagListen, 0));
            return;
        case kTagMsAccept: {
            if (cqe.res >= 0) {
                // One accepted socket per CQE (already NONBLOCK|CLOEXEC
                // from accept_flags): straight into the shared adopt
                // path — failpoints, cap/shed, Conn construction.
                s_.adopt_accepted(w_, int(cqe.res));
            } else if (cqe.res == -EINVAL) {
                // Kernel without IORING_ACCEPT_MULTISHOT (or without
                // OP_ACCEPT at all): permanent demotion to the classic
                // poll+accept4 path.
                if (ms_accept_ok_) {
                    ms_accept_ok_ = false;
                    IST_INFO("worker %d: multishot accept unsupported; "
                             "using poll+accept4",
                             w_.idx);
                }
                arm_poll(w_.listen_fd, make_ud(kTagListen, 0));
                return;
            }
            // Transient errors (ECONNABORTED, EMFILE...) surface as a
            // terminal CQE; re-arm the standing accept either way when
            // the kernel stopped the multishot.
            if ((cqe.flags & kCqeFMore) == 0) arm_ms_accept();
            return;
        }
        case kTagZc:
            on_zc(uint32_t(v), cqe);
            return;
        case kTagRx:
        case kTagMsRx: {
            UConn* u = find(v);
            bool multishot = tag == kTagMsRx;
            if (u == nullptr) return;  // stale completion, state gone
            RxMode mode = u->rx;  // the mode this CQE was issued under
            bool terminal = !multishot || (cqe.flags & kCqeFMore) == 0;
            if (terminal) {
                u->outstanding--;
                u->rx = RX_IDLE;
            }
            on_rx(*u, cqe, multishot, mode);
            maybe_gc(v);
            return;
        }
        case kTagTx: {
            UConn* u = find(v);
            if (u == nullptr) return;
            u->outstanding--;
            u->tx_inflight = false;
            on_tx(*u, cqe);
            maybe_gc(v);
            return;
        }
        default:
            return;
    }
}

// ---------------------------------------------------------------------------
// connection lifecycle
// ---------------------------------------------------------------------------

void EngineUring::conn_added(Conn& c) {
    auto st = std::make_unique<UConn>();
    st->c = &c;
    st->id = c.id;
    st->fd = c.fd;
    c.eng = st.get();
    UConn* u = st.get();
    conns_[c.id] = std::move(st);
    arm_rx(*u);
}

void EngineUring::conn_closing(Conn& c) {
    auto it = conns_.find(c.id);
    c.eng = nullptr;
    if (it == conns_.end()) return;
    UConn* u = it->second.get();
    u->c = nullptr;
    // Cancel whatever read is pending so its CQE drains promptly; an
    // in-flight send is left to complete (its SQE references u's iovec
    // storage, which this state object keeps alive until then; a
    // zero-copy send's pins live in the slot table until its NOTIF).
    if (u->rx == RX_MS || u->rx == RX_MS_CANCEL) {
        submit_cancel(make_ud(kTagMsRx, u->id));
    } else if (u->rx == RX_STAGED || u->rx == RX_DIRECT) {
        submit_cancel(make_ud(kTagRx, u->id));
    }
    if (!u->tx_inflight) u->sending.reset();
    // Flush every SQE referencing this fd NOW, while the number still
    // names this file: the server closes the fd right after this call,
    // and an accept later in the same reap batch could reuse it — an
    // UNSUBMITTED recv/send SQE would then resolve against the new
    // connection's socket and silently consume its bytes. Once
    // submitted, the kernel holds the file (not the fd), stale CQEs
    // drop on the conn-id lookup, and the queued cancels unblock any
    // parked read so the file reference drains. flush_for_close (not
    // a bare submit) because CQ backpressure and the SQPOLL poller
    // both let a plain submit return with SQEs still unowned.
    flush_for_close();
    if (u->outstanding == 0) conns_.erase(it);
}

// ---------------------------------------------------------------------------
// receive pump
// ---------------------------------------------------------------------------

void EngineUring::arm_rx(UConn& u) {
    Conn& c = *u.c;
    if ((c.state == RState::PAYLOAD || c.state == RState::DRAIN) &&
        c.payload_left > 0) {
        arm_direct(u);
    } else if (ms_ok_) {
        arm_ms(u);
    } else {
        arm_staged(u);
    }
}

void EngineUring::arm_staged(UConn& u) {
    if (u.stage.size() < kStageBytes) u.stage.resize(kStageBytes);
    io_uring_sqe* e = sqe(IORING_OP_RECV, u.fd, make_ud(kTagRx, u.id));
    if (e == nullptr) {
        if (u.c != nullptr) u.c->dead = true;
        return;
    }
    e->addr = uint64_t(uintptr_t(u.stage.data()));
    e->len = uint32_t(u.stage.size());
    u.rx = RX_STAGED;
    u.outstanding++;
}

void EngineUring::arm_direct(UConn& u) {
    Conn& c = *u.c;
    u.rn = s_.payload_iov(c, u.riov, 64);
    int rb = -1;
    if (c.state == RState::PAYLOAD && u.rn == 1) {
        rb = find_regbuf(u.riov[0].iov_base, u.riov[0].iov_len);
    }
    io_uring_sqe* e;
    if (rb >= 0) {
        // Single-run plan inside a registered arena: READ_FIXED uses
        // the pre-pinned pages — no per-op get_user_pages at all.
        e = sqe(IORING_OP_READ_FIXED, u.fd, make_ud(kTagRx, u.id));
        if (e == nullptr) {
            c.dead = true;
            return;
        }
        e->addr = uint64_t(uintptr_t(u.riov[0].iov_base));
        e->len = uint32_t(u.riov[0].iov_len);
        e->buf_index = uint16_t(rb);
    } else {
        e = sqe(IORING_OP_READV, u.fd, make_ud(kTagRx, u.id));
        if (e == nullptr) {
            c.dead = true;
            return;
        }
        e->addr = uint64_t(uintptr_t(u.riov));
        e->len = uint32_t(u.rn);
    }
    u.rx = RX_DIRECT;
    u.outstanding++;
}

void EngineUring::arm_ms(UConn& u) {
    io_uring_sqe* e = sqe(IORING_OP_RECV, u.fd, make_ud(kTagMsRx, u.id));
    if (e == nullptr) {
        if (u.c != nullptr) u.c->dead = true;
        return;
    }
    e->flags |= IOSQE_BUFFER_SELECT;
    e->ioprio = kRecvMultishot;
    e->buf_group = kBgid;
    u.rx = RX_MS;
    u.outstanding++;
}

void EngineUring::rearm_rx(UConn& u) {
    Conn& c = *u.c;
    bool bulk = (c.state == RState::PAYLOAD || c.state == RState::DRAIN) &&
                c.payload_left > 0;
    if (bulk) {
        if (u.rx == RX_MS) {
            // A multishot is live and would race the direct read for
            // the socket bytes: cancel it and switch on its terminal
            // CQE. Bytes it delivers meanwhile take the copied ingest
            // path — bounded by the provided-buffer size.
            submit_cancel(make_ud(kTagMsRx, u.id));
            u.rx = RX_MS_CANCEL;
            return;
        }
        if (u.rx == RX_MS_CANCEL) return;  // waiting for the terminal
        if (u.rx == RX_IDLE) arm_direct(u);
        return;
    }
    if (u.rx == RX_MS || u.rx == RX_MS_CANCEL) return;  // still armed
    if (u.rx != RX_IDLE) return;  // oneshot still in flight
    if (ms_ok_) {
        arm_ms(u);
    } else {
        arm_staged(u);
    }
}

void EngineUring::on_rx(UConn& u, const io_uring_cqe& cqe,
                        bool multishot, RxMode mode) {
    int res = cqe.res;
    bool have_buf = multishot && (cqe.flags & kCqeFBuffer) != 0;
    uint16_t bid =
        have_buf ? uint16_t(cqe.flags >> kCqeBufferShift) : uint16_t(0);
    Conn* c = u.c;
    if (c == nullptr) {  // closed while the recv was in flight
        if (have_buf) pbuf_recycle(bid);
        return;
    }
    if (res == 0) {  // orderly peer close
        if (have_buf) pbuf_recycle(bid);
        s_.close_conn(w_, c->fd);
        return;
    }
    if (res < 0) {
        if (have_buf) pbuf_recycle(bid);
        switch (-res) {
            case EAGAIN:
            case EINTR:
                if (u.rx == RX_IDLE) arm_rx(u);
                return;
            case ECANCELED:
                // Our own multishot cancel completing (ms → direct
                // switch); rearm picks direct for the bulk state.
                if (u.rx == RX_IDLE) rearm_rx(u);
                return;
            case ENOBUFS:
                // Provided buffers momentarily exhausted: take one
                // staged round (recycling happens as CQEs process),
                // then rearm_rx returns to multishot.
                if (u.rx == RX_IDLE) arm_staged(u);
                return;
            case EINVAL:
                if (multishot) {
                    // Kernel has pbuf rings but not multishot recv (a
                    // 5.19..6.0 window): stop arming it anywhere and
                    // fall this connection back to staged. Keyed on
                    // the SUBMISSION being multishot, not on ms_ok_ —
                    // the first connection to hit this clears the
                    // global, and the others' armed multishots must
                    // still degrade instead of being dropped.
                    ms_ok_ = false;
                    if (u.rx == RX_IDLE) arm_staged(u);
                    return;
                }
                s_.close_conn(w_, c->fd);
                return;
            default:
                s_.close_conn(w_, c->fd);
                return;
        }
    }
    // Injected receive failure: same close semantics as the epoll
    // engine's readable path.
    if (IST_FAILPOINT("sock.recv")) {
        IST_WARN("sock.recv failpoint: dropping fd=%d", c->fd);
        if (have_buf) pbuf_recycle(bid);
        s_.close_conn(w_, c->fd);
        return;
    }
    if (mode == RX_DIRECT) {
        // Direct pool read completed: pure cursor advance, zero copies.
        if (c->state == RState::PAYLOAD) {
            s_.bytes_in_ += uint64_t(res);
            w_.bytes_in.fetch_add(uint64_t(res),
                                  std::memory_order_relaxed);
            w_.eng_copies_avoided.fetch_add(uint64_t(res),
                                            std::memory_order_relaxed);
        }
        s_.payload_advance(*c, size_t(res));
        if (c->payload_left == 0) {
            if (c->state == RState::PAYLOAD) {
                s_.finish_write(*c);
                if (c->dead) {
                    s_.close_conn(w_, c->fd);
                    return;
                }
            } else {
                c->state = RState::HDR;
                c->hdr_got = 0;
                s_.diet_conn_bufs(*c);
            }
        }
    } else {
        // Staged / provided-buffer bytes: push through the shared
        // state machine (header parse, dispatch, bounded payload
        // copies; the direct path takes over below for the rest).
        const uint8_t* ptr = have_buf ? pbuf_ptr(bid) : u.stage.data();
        size_t drained = 0;
        bool ok = s_.ingest_bytes(*c, ptr, size_t(res), &drained);
        // DRAIN-state bytes are excluded to match the epoll engine
        // (and the direct path above), which only count live protocol
        // bytes — stats parity between engines is part of the A/B
        // contract.
        uint64_t counted = uint64_t(res) - uint64_t(drained);
        if (counted > 0) {
            s_.bytes_in_ += counted;
            w_.bytes_in.fetch_add(counted, std::memory_order_relaxed);
        }
        if (have_buf) pbuf_recycle(bid);
        if (!ok) {
            s_.close_conn(w_, c->fd);
            return;
        }
    }
    if (u.c == nullptr) return;  // closed during processing
    rearm_rx(u);
}

// ---------------------------------------------------------------------------
// transmit pump
// ---------------------------------------------------------------------------

void EngineUring::output_ready(Conn& c) {
    UConn* u = static_cast<UConn*>(c.eng);
    if (u == nullptr || u->tx_inflight) return;
    start_tx(*u);
}

uint32_t EngineUring::alloc_zc_slot(UConn& u) {
    uint32_t idx;
    if (!zc_free_.empty()) {
        idx = zc_free_.back();
        zc_free_.pop_back();
    } else {
        idx = uint32_t(zc_slots_.size());
        zc_slots_.emplace_back();
    }
    zc_live_.fetch_add(1, std::memory_order_relaxed);
    ZcSlot& s = zc_slots_[idx];
    s.used = true;
    s.data_done = false;
    s.notif_done = false;
    s.count_copies = false;
    s.conn_id = u.id;
    s.msg = u.sending;
    return idx;
}

void EngineUring::finish_zc_slot(uint32_t idx) {
    ZcSlot& s = zc_slots_[idx];
    if (!s.used || !s.data_done || !s.notif_done) return;
    s.msg.reset();  // pins release here — after the kernel's NOTIF
    s.used = false;
    s.conn_id = 0;
    zc_free_.push_back(idx);
    zc_live_.fetch_sub(1, std::memory_order_relaxed);
}

namespace {
// Gather the unsent remainder of `m` into iov: meta first while it is
// still pending, then the payload runs from the cursors — the one
// writev-shaped construction every non-fixed submission shares (it
// mirrors the epoll engine's flush_out build; skew between the copies
// would be wire corruption, so there is exactly one).
int build_seg_iov(OutMsg& m, struct iovec* iov, int max) {
    int n = 0;
    if (!m.meta_done) {
        iov[n].iov_base = m.meta.data() + m.off;
        iov[n].iov_len = m.meta.size() - m.off;
        n++;
    }
    for (size_t s = m.seg_idx; s < m.segs.size() && n < max; ++s) {
        size_t skip = (s == m.seg_idx && m.meta_done) ? m.off : 0;
        iov[n].iov_base = const_cast<uint8_t*>(m.segs[s].first) + skip;
        iov[n].iov_len = m.segs[s].second - skip;
        n++;
    }
    return n;
}
}  // namespace

void EngineUring::start_tx(UConn& u) {
    Conn& c = *u.c;
    if (!u.sending) {
        if (c.outq.empty()) return;
        // Injected send failure (parity with the epoll flush path):
        // only MARK the connection dead — output_ready runs inside
        // respond(), whose op-handler caller still holds the Conn, so
        // the actual close is deferred to the unwind (the RX pump and
        // on_tx both check the flag).
        if (IST_FAILPOINT("sock.send")) {
            IST_WARN("sock.send failpoint: dropping fd=%d", c.fd);
            c.dead = true;
            return;
        }
        u.sending = std::make_shared<OutMsg>(std::move(c.outq.front()));
        c.outq.pop_front();
    }
    OutMsg& m = *u.sending;
    // Remaining payload bytes decide the zero-copy eligibility.
    size_t prem = 0;
    for (size_t s = m.seg_idx; s < m.segs.size(); ++s) {
        size_t skip = (s == m.seg_idx && m.meta_done) ? m.off : 0;
        prem += m.segs[s].second - skip;
    }
    bool zc_eligible = prem >= kZcMinBytes && (zc_ok_ || zc_msg_ok_);
    io_uring_sqe* e = nullptr;
    if (!m.meta_done) {
        if (zc_eligible) {
            // Meta alone (small); the payload follows zero-copy.
            e = sqe(IORING_OP_SEND, u.fd, make_ud(kTagTx, u.id));
            if (e == nullptr) {
                c.dead = true;
                return;
            }
            e->addr = uint64_t(uintptr_t(m.meta.data() + m.off));
            e->len = uint32_t(m.meta.size() - m.off);
            e->msg_flags = MSG_NOSIGNAL;
        } else {
            // The writev analogue: meta + payload runs in one gather.
            int n = build_seg_iov(m, u.siov, 64);
            memset(&u.smsg, 0, sizeof(u.smsg));
            u.smsg.msg_iov = u.siov;
            u.smsg.msg_iovlen = size_t(n);
            e = sqe(IORING_OP_SENDMSG, u.fd, make_ud(kTagTx, u.id));
            if (e == nullptr) {
                c.dead = true;
                return;
            }
            e->addr = uint64_t(uintptr_t(&u.smsg));
            e->len = 1;
            e->msg_flags = MSG_NOSIGNAL;
        }
    } else {
        const uint8_t* p = m.segs[m.seg_idx].first + m.off;
        size_t slen = m.segs[m.seg_idx].second - m.off;
        int rb = -1;
        if (zc_eligible && zc_ok_ && m.seg_idx + 1 == m.segs.size()) {
            rb = find_regbuf(p, slen);
        }
        if (rb >= 0) {
            // The headline path: one registered-arena run leaves via
            // SEND_ZC with the FIXED_BUF flag — no copy, no per-op
            // page pin, pins parked in the slot until the NOTIF.
            uint32_t slot = alloc_zc_slot(u);
            e = sqe(kOpSendZc, u.fd, make_ud(kTagZc, slot));
            if (e == nullptr) {
                finish_zc_slot_on_abort(slot);
                c.dead = true;
                return;
            }
            e->ioprio = kRecvsendFixedBuf;
            e->addr = uint64_t(uintptr_t(p));
            e->len = uint32_t(slen);
            e->msg_flags = MSG_NOSIGNAL;
            e->buf_index = uint16_t(rb);
            w_.eng_zc_sends.fetch_add(1, std::memory_order_relaxed);
            zc_slots_[slot].count_copies = true;
        } else if (zc_eligible && zc_msg_ok_ && m.segs.size() > 1) {
            // Scattered runs: vectored zero-copy.
            int n = build_seg_iov(m, u.siov, 64);
            memset(&u.smsg, 0, sizeof(u.smsg));
            u.smsg.msg_iov = u.siov;
            u.smsg.msg_iovlen = size_t(n);
            uint32_t slot = alloc_zc_slot(u);
            e = sqe(kOpSendmsgZc, u.fd, make_ud(kTagZc, slot));
            if (e == nullptr) {
                finish_zc_slot_on_abort(slot);
                c.dead = true;
                return;
            }
            e->addr = uint64_t(uintptr_t(&u.smsg));
            e->len = 1;
            e->msg_flags = MSG_NOSIGNAL;
            w_.eng_zc_sends.fetch_add(1, std::memory_order_relaxed);
        } else if (zc_eligible && zc_ok_) {
            // Unregistered single run: plain SEND_ZC (still no copy).
            uint32_t slot = alloc_zc_slot(u);
            e = sqe(kOpSendZc, u.fd, make_ud(kTagZc, slot));
            if (e == nullptr) {
                finish_zc_slot_on_abort(slot);
                c.dead = true;
                return;
            }
            e->addr = uint64_t(uintptr_t(p));
            e->len = uint32_t(slen);
            e->msg_flags = MSG_NOSIGNAL;
            w_.eng_zc_sends.fetch_add(1, std::memory_order_relaxed);
            zc_slots_[slot].count_copies = true;
        } else {
            int n = build_seg_iov(m, u.siov, 64);
            memset(&u.smsg, 0, sizeof(u.smsg));
            u.smsg.msg_iov = u.siov;
            u.smsg.msg_iovlen = size_t(n);
            e = sqe(IORING_OP_SENDMSG, u.fd, make_ud(kTagTx, u.id));
            if (e == nullptr) {
                c.dead = true;
                return;
            }
            e->addr = uint64_t(uintptr_t(&u.smsg));
            e->len = 1;
            e->msg_flags = MSG_NOSIGNAL;
        }
    }
    u.tx_inflight = true;
    u.outstanding++;
}

// Abort path for a slot whose SQE never got submitted.
void EngineUring::finish_zc_slot_on_abort(uint32_t idx) {
    ZcSlot& s = zc_slots_[idx];
    s.msg.reset();
    s.used = false;
    s.conn_id = 0;
    zc_free_.push_back(idx);
    zc_live_.fetch_sub(1, std::memory_order_relaxed);
}

void EngineUring::advance_tx(UConn& u, size_t n) {
    OutMsg& m = *u.sending;
    s_.bytes_out_ += uint64_t(n);
    w_.bytes_out.fetch_add(uint64_t(n), std::memory_order_relaxed);
    size_t left = n;
    if (!m.meta_done) {
        size_t take = std::min(left, m.meta.size() - m.off);
        m.off += take;
        left -= take;
        if (m.off == m.meta.size()) {
            m.meta_done = true;
            m.off = 0;
        }
    }
    while (left > 0 && m.seg_idx < m.segs.size()) {
        size_t take = std::min(left, m.segs[m.seg_idx].second - m.off);
        m.off += take;
        left -= take;
        if (m.off == m.segs[m.seg_idx].second) {
            m.seg_idx++;
            m.off = 0;
        }
    }
    if (m.meta_done && m.seg_idx == m.segs.size()) {
        Conn& c = *u.c;
        c.outq_bytes -= m.total;
        s_.outq_total_.fetch_sub(m.total, std::memory_order_relaxed);
        u.sending.reset();  // ZC slots keep their own reference
    }
}

void EngineUring::on_tx(UConn& u, const io_uring_cqe& cqe) {
    if (u.c == nullptr) {
        u.sending.reset();  // CQE arrived: the kernel is done with it
        return;
    }
    Conn& c = *u.c;
    int res = cqe.res;
    if (res < 0) {
        if (-res == EAGAIN || -res == EINTR) {
            start_tx(u);  // resubmit from the same cursors
            return;
        }
        s_.close_conn(w_, c.fd);
        return;
    }
    advance_tx(u, size_t(res));
    if (u.c != nullptr && (u.sending || !u.c->outq.empty())) start_tx(u);
    // start_tx may only MARK a failpoint-injected death (it can run
    // under a live handler frame); in this dispatch context the close
    // is safe to take now.
    if (u.c != nullptr && u.c->dead) s_.close_conn(w_, u.c->fd);
}

void EngineUring::on_zc(uint32_t slot, const io_uring_cqe& cqe) {
    if (slot >= zc_slots_.size() || !zc_slots_[slot].used) return;
    if ((cqe.flags & kCqeFNotif) != 0) {
        // The kernel no longer references the pages: pins may drop.
        zc_slots_[slot].notif_done = true;
        finish_zc_slot(slot);
        return;
    }
    // Data completion. F_MORE promises a later NOTIF CQE; without it,
    // none is coming (e.g. a failed send) and the slot closes on this
    // completion alone. NOTE: no reference into zc_slots_ may be held
    // past this point — start_tx below can allocate a fresh slot and
    // reallocate the vector; every later touch re-indexes.
    uint64_t conn_id = zc_slots_[slot].conn_id;
    zc_slots_[slot].data_done = true;
    if ((cqe.flags & kCqeFMore) == 0) zc_slots_[slot].notif_done = true;
    if (cqe.res > 0 && zc_slots_[slot].count_copies) {
        w_.eng_copies_avoided.fetch_add(uint64_t(cqe.res),
                                        std::memory_order_relaxed);
    }
    UConn* u = find(conn_id);
    if (u != nullptr) {
        u->outstanding--;
        u->tx_inflight = false;
        if (u->c != nullptr) {
            int res = cqe.res;
            if (res < 0) {
                if (-res == EAGAIN || -res == EINTR) {
                    start_tx(*u);
                } else {
                    s_.close_conn(w_, u->c->fd);
                }
            } else {
                advance_tx(*u, size_t(res));
                if (u->c != nullptr &&
                    (u->sending || !u->c->outq.empty())) {
                    start_tx(*u);
                }
                if (u->c != nullptr && u->c->dead) {
                    s_.close_conn(w_, u->c->fd);
                }
            }
        } else {
            if (!u->tx_inflight) u->sending.reset();
        }
        maybe_gc(conn_id);
    }
    finish_zc_slot(slot);
}

std::unique_ptr<Engine> make_engine_uring(Server& srv, Worker& w) {
    return std::make_unique<EngineUring>(srv, w);
}

#endif  // ISTPU_HAVE_URING

}  // namespace istpu
