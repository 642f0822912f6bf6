#include "client.h"

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sched.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <unistd.h>

#include <cstdlib>
#include <cstring>

#include "events.h"
#include "log.h"

namespace istpu {

namespace {

int connect_tcp(const std::string& host, uint16_t port, int timeout_ms) {
    addrinfo hints{}, *res = nullptr;
    hints.ai_family = AF_INET;
    hints.ai_socktype = SOCK_STREAM;
    std::string port_s = std::to_string(port);
    if (getaddrinfo(host.c_str(), port_s.c_str(), &hints, &res) != 0) return -1;
    int fd = socket(res->ai_family, SOCK_STREAM | SOCK_CLOEXEC, 0);
    if (fd < 0) {
        freeaddrinfo(res);
        return -1;
    }
    timeval tv{timeout_ms / 1000, (timeout_ms % 1000) * 1000};
    setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
    setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
    int rc = connect(fd, res->ai_addr, res->ai_addrlen);
    freeaddrinfo(res);
    if (rc != 0 && errno == EINTR) {
        // A signal (e.g. io_uring task work of a server in this same
        // process) interrupted the call; the handshake carries on in
        // the kernel. Wait for it and read its outcome.
        pollfd pfd{fd, POLLOUT, 0};
        int pr;
        do {
            pr = poll(&pfd, 1, timeout_ms);
        } while (pr < 0 && errno == EINTR);
        int err = pr == 0 ? ETIMEDOUT : errno;
        socklen_t elen = sizeof(err);
        if (pr > 0) getsockopt(fd, SOL_SOCKET, SO_ERROR, &err, &elen);
        rc = err == 0 ? 0 : -1;
        errno = err;
    }
    if (rc != 0) {
        close(fd);
        return -1;
    }
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    int buf = int(SOCK_BUF_BYTES);
    setsockopt(fd, SOL_SOCKET, SO_SNDBUF, &buf, sizeof(buf));
    setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &buf, sizeof(buf));
    return fd;
}

// Blocking exact send/recv for the bootstrap HELLO (reference
// send_exact/recv_exact, src/utils.cpp:19-46).
bool send_exact(int fd, const void* p, size_t n) {
    const uint8_t* b = static_cast<const uint8_t*>(p);
    while (n > 0) {
        ssize_t r = send(fd, b, n, MSG_NOSIGNAL);
        if (r <= 0) {
            if (r < 0 && errno == EINTR) continue;
            return false;
        }
        b += r;
        n -= size_t(r);
    }
    return true;
}

bool recv_exact(int fd, void* p, size_t n) {
    uint8_t* b = static_cast<uint8_t*>(p);
    while (n > 0) {
        ssize_t r = recv(fd, b, n, 0);
        if (r <= 0) {
            if (r < 0 && errno == EINTR) continue;
            return false;
        }
        b += r;
        n -= size_t(r);
    }
    return true;
}

}  // namespace

// ---------------------------------------------------------------------------
// CopyPool — parallel memcpy engine for the lease fast path
// ---------------------------------------------------------------------------

namespace {
// Below this total the handoff costs more than the copy saves.
constexpr size_t kParallelCopyBytes = 1u << 20;
// Workers pull pieces of at most this size (large coalesced runs are
// split so the tail of one huge seg cannot serialize the batch).
constexpr size_t kCopyChunkBytes = 512u << 10;
}  // namespace

CopyPool& CopyPool::inst() {
    static CopyPool pool;
    return pool;
}

CopyPool::CopyPool() {
    // Workers only help when there are spare cores BEYOND the caller,
    // the server loop and the client IO thread: on a 1-2 core host the
    // handoff turns into pure context-switch overhead and a descheduled
    // worker holding the last chunk serializes the whole batch
    // (measured ~2x slower than inline memcpy on the 2-core CI VM).
    // ISTPU_COPY_THREADS overrides the heuristic (0 forces inline).
    unsigned n;
    const char* env = getenv("ISTPU_COPY_THREADS");
    if (env != nullptr) {
        long v = atol(env);
        n = v > 0 ? unsigned(v) : 0;
    } else {
        unsigned hw = std::thread::hardware_concurrency();
        n = hw >= 4 ? hw - 2 : 0;
    }
    if (n > 4) n = 4;
    for (unsigned i = 0; i < n; ++i) {
        threads_.emplace_back([this] { worker(); });
    }
}

CopyPool::~CopyPool() {
    {
        std::lock_guard<std::mutex> lk(mu_);
        stop_ = true;
    }
    cv_.notify_all();
    for (auto& t : threads_) t.join();
}

void CopyPool::add_seg(std::vector<Seg>& segs, uint8_t* dst,
                       const uint8_t* src, size_t len) {
    if (len == 0) return;
    if (!segs.empty() && segs.back().dst + segs.back().len == dst &&
        segs.back().src + segs.back().len == src) {
        segs.back().len += len;  // coalesce adjacent runs
        return;
    }
    segs.push_back(Seg{dst, src, len});
}

void CopyPool::worker() {
    uint64_t seen = 0;
    while (true) {
        std::shared_ptr<Round> round;
        {
            std::unique_lock<std::mutex> lk(mu_);
            cv_.wait(lk, [&] { return stop_ || (round_ && gen_ != seen); });
            if (stop_) return;
            seen = gen_;
            round = round_;
        }
        const size_t n = round->segs.size();
        size_t i;
        size_t local = 0;
        while ((i = round->next.fetch_add(1, std::memory_order_relaxed)) <
               n) {
            const Seg& s = round->segs[i];
            memcpy(s.dst, s.src, s.len);
            local++;
        }
        if (local &&
            round->done.fetch_add(local, std::memory_order_acq_rel) +
                    local ==
                n) {
            std::lock_guard<std::mutex> lk(mu_);
            done_cv_.notify_all();
        }
    }
}

void CopyPool::run(std::vector<Seg> segs) {
    if (segs.empty()) return;
    size_t total = 0;
    for (const Seg& s : segs) total += s.len;
    if (threads_.empty() || total < kParallelCopyBytes) {
        for (const Seg& s : segs) memcpy(s.dst, s.src, s.len);
        return;
    }
    // Split big runs so every thread gets work.
    std::vector<Seg> chunks;
    chunks.reserve(segs.size() + total / kCopyChunkBytes + 1);
    for (const Seg& s : segs) {
        size_t off = 0;
        while (off < s.len) {
            size_t take = std::min(kCopyChunkBytes, s.len - off);
            chunks.push_back(Seg{s.dst + off, s.src + off, take});
            off += take;
        }
    }
    std::lock_guard<std::mutex> rlk(run_mu_);  // one batch at a time
    auto round = std::make_shared<Round>();
    round->segs = std::move(chunks);
    const size_t n = round->segs.size();
    {
        std::lock_guard<std::mutex> lk(mu_);
        round_ = round;
        gen_++;
    }
    cv_.notify_all();
    // The caller is a worker too.
    size_t i;
    size_t local = 0;
    while ((i = round->next.fetch_add(1, std::memory_order_relaxed)) < n) {
        const Seg& s = round->segs[i];
        memcpy(s.dst, s.src, s.len);
        local++;
    }
    round->done.fetch_add(local, std::memory_order_acq_rel);
    {
        std::unique_lock<std::mutex> lk(mu_);
        done_cv_.wait(lk, [&] {
            return round->done.load(std::memory_order_acquire) == n;
        });
        round_.reset();  // stragglers hold their own shared_ptr
    }
}

// rdrain_ is sized lazily at its first use (handle_readable's
// beyond-the-plan branch): most connections never over-read a scatter
// plan, and eagerly paying 1 MB per Connection here is exactly the
// per-conn fixed cost the connection-scale work removes.
Connection::Connection(const ClientConfig& cfg) : cfg_(cfg) {}

Connection::~Connection() { close_conn(); }

int Connection::connect_server() {
    fd_ = connect_tcp(cfg_.host, cfg_.port, cfg_.timeout_ms);
    if (fd_ < 0) {
        IST_ERROR("connect to %s:%u failed: %s", cfg_.host.c_str(),
                  cfg_.port, strerror(errno));
        return -1;
    }
    // Bootstrap HELLO on the still-blocking socket.
    WireHeader h = make_header(OP_HELLO, 0, 0, 0);
    if (!send_exact(fd_, &h, sizeof(h))) return -1;
    WireHeader rh;
    if (!recv_exact(fd_, &rh, sizeof(rh)) || !header_valid(rh)) return -1;
    std::vector<uint8_t> body(rh.body_len);
    if (!recv_exact(fd_, body.data(), body.size())) return -1;
    BufReader r(body.data(), body.size());
    uint32_t status = r.u32();
    if (status != OK) return -1;
    server_block_size_ = r.u32();
    uint32_t shm_enabled = r.u32();
    {
        std::lock_guard<std::mutex> lk(pools_mu_);
        if (cfg_.use_shm && shm_enabled) {
            if (map_pools_locked(r) == 0 && !pools_.empty()) {
                shm_active_ = true;
            }
        }
    }
    // Trailing lease-protocol fields (absent from older servers: the
    // reader just latches !ok and lease mode stays off). The ctl page
    // carries the live store epoch; mapping it is what makes zero-RTT
    // pin-cache validation possible.
    if (cfg_.use_lease && shm_active_) {
        uint32_t has_ctl = r.u32();
        std::string ctl_name = r.str();
        if (r.ok() && has_ctl && !ctl_name.empty()) {
            int cfd = shm_open(("/" + ctl_name).c_str(), O_RDONLY, 0);
            if (cfd >= 0) {
                void* mem = mmap(nullptr, CTL_PAGE_BYTES, PROT_READ,
                                 MAP_SHARED, cfd, 0);
                close(cfd);
                if (mem != MAP_FAILED) {
                    auto* page = static_cast<CtlPage*>(mem);
                    if (page->magic == CTL_MAGIC) {
                        ctl_map_ = page;
                    } else {
                        munmap(mem, CTL_PAGE_BYTES);
                    }
                }
            }
        }
        if (ctl_map_ == nullptr) {
            IST_DEBUG("lease mode requested but ctl page unavailable; "
                      "falling back to legacy ops");
        }
    }
    // One-sided fabric negotiation, still on the blocking bootstrap
    // socket (like HELLO): probes OP_FABRIC_ATTACH support, maps the
    // shm commit ring when the server's fabric engine granted one,
    // and enables the cross-host OP_FABRIC_WRITE mode when there is
    // no shm to write through one-sided. Only a transport failure
    // aborts the connect; "no fabric here" degrades silently.
    if (cfg_.use_fabric && cfg_.use_lease) {
        if (!fabric_bootstrap_attach()) return -1;
    }

    // Switch to the IO thread regime.
    int fl = fcntl(fd_, F_GETFL, 0);
    fcntl(fd_, F_SETFL, fl | O_NONBLOCK);
    epoll_fd_ = epoll_create1(EPOLL_CLOEXEC);
    wake_fd_ = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.fd = wake_fd_;
    epoll_ctl(epoll_fd_, EPOLL_CTL_ADD, wake_fd_, &ev);
    ev.events = EPOLLIN;
    ev.data.fd = fd_;
    epoll_ctl(epoll_fd_, EPOLL_CTL_ADD, fd_, &ev);
    running_.store(true);
    broken_.store(false);
    io_exited_.store(false);
    io_thread_ = std::thread([this] { io_loop(); });
    IST_INFO("connected to %s:%u (shm=%s, block=%u)", cfg_.host.c_str(),
             cfg_.port, shm_active_ ? "on" : "off", server_block_size_);
    return 0;
}

int Connection::map_pools_locked(BufReader& r) {
    uint32_t npools = r.u32();
    if (!r.ok() || npools > 4096) return -1;
    for (uint32_t i = 0; i < npools; ++i) {
        std::string name = r.str();
        uint64_t size = r.u64();
        if (!r.ok()) return -1;
        if (i < pools_.size()) continue;  // already mapped
        if (name.empty()) return -1;      // anonymous pool: no SHM path
        int fd = shm_open(("/" + name).c_str(), O_RDWR, 0);
        if (fd < 0) {
            IST_DEBUG("shm_open %s failed (remote server?)", name.c_str());
            return -1;
        }
        // MAP_POPULATE pre-faults this client's page tables for the whole
        // pool at map time: without it every first-touch of a 4 KB pool
        // page during a copy takes a minor fault (~1-2 us), which
        // dominates small-block throughput (4096 faults per 16 MB batch).
        // The server already faulted the backing pages, so this only
        // fills PTEs — no extra physical memory.
        void* mem = mmap(nullptr, size, PROT_READ | PROT_WRITE,
                         MAP_SHARED | MAP_POPULATE, fd, 0);
        close(fd);
        if (mem == MAP_FAILED) return -1;
        pools_.push_back(PoolMap{name, static_cast<uint8_t*>(mem), size});
    }
    return 0;
}

void Connection::close_conn() {
    if (running_.exchange(false)) {
        wake();
        if (io_thread_.joinable()) io_thread_.join();
    }
    // The IO thread has unwound (fail_all completed every pending op, so
    // inflight drained through finish_op) — but a sync_async registered
    // between the drain and here would otherwise wait forever.
    std::vector<DoneFn> waiters;
    {
        std::lock_guard<std::mutex> lk(sync_mu_);
        waiters.swap(sync_waiters_);
    }
    for (auto& w : waiters) w(INTERNAL_ERROR, {});
    if (fd_ >= 0) close(fd_);
    if (epoll_fd_ >= 0) close(epoll_fd_);
    if (wake_fd_ >= 0) close(wake_fd_);
    fd_ = epoll_fd_ = wake_fd_ = -1;
    {
        // Lease/pin state dies with the connection (the server reclaims
        // the lease blocks when it sees the close). Un-flushed deferred
        // puts are LOST — latch that as an error so a caller that
        // reconnects and syncs learns about it (lib.py harvests the old
        // handle's latch on reconnect), mirroring how in-flight legacy
        // writes fail loudly through their completion callbacks.
        std::lock_guard<std::mutex> llk(lease_mu_);
        if (pend_nkeys_ != 0) {
            uint32_t expected = 0;
            lease_err_.compare_exchange_strong(expected, INTERNAL_ERROR);
        }
        lease_valid_ = false;
        lease_runs_.clear();
        pend_blob_.clear();
        pend_locs_.clear();
        pend_nkeys_ = 0;
        pend_bytes_ = 0;
    }
    {
        std::lock_guard<std::mutex> clk(cache_mu_);
        pin_cache_.clear();
    }
    // Fabric ring teardown: the IO thread (its only writer) has
    // joined, so the unmap cannot race a post; the server unlinks the
    // shm object when it sees the close.
    fab_ring_.store(false);
    fabric_stream_ = false;
    if (fab_hdr_ != nullptr) {
        munmap(fab_hdr_, fab_map_bytes_);
        fab_hdr_ = nullptr;
        fab_map_bytes_ = 0;
    }
    fab_detached_ = false;
    fab_attach_inflight_ = false;
    fab_reattach_backoff_ = 0;
    // Unmap pools AND the ctl page under pools_mu_: cached_read holds
    // that mutex across its pool copies and epoch loads, so a reader
    // mid-copy on another thread excludes this teardown (the same
    // protection the legacy shm copy paths get from their pools_mu_
    // hold).
    std::lock_guard<std::mutex> lk(pools_mu_);
    for (auto& p : pools_) munmap(p.base, p.size);
    pools_.clear();
    shm_active_ = false;
    if (ctl_map_ != nullptr) {
        munmap(ctl_map_, CTL_PAGE_BYTES);
        ctl_map_ = nullptr;
    }
}

void Connection::wake() {
    if (wake_fd_ >= 0) {
        uint64_t one = 1;
        ssize_t n = write(wake_fd_, &one, sizeof(one));
        (void)n;
    }
}

size_t Connection::pool_count() {
    std::lock_guard<std::mutex> lk(pools_mu_);
    return pools_.size();
}

uint8_t* Connection::pool_base(uint32_t idx, size_t* size_out) {
    std::lock_guard<std::mutex> lk(pools_mu_);
    if (idx >= pools_.size()) return nullptr;
    if (size_out) *size_out = pools_[idx].size;
    return pools_[idx].base;
}

int Connection::refresh_pools() {
    std::vector<uint8_t> resp;
    uint32_t st = rpc(OP_HELLO, {}, &resp);
    if (st != OK) return -1;
    BufReader r(resp.data(), resp.size());
    r.u32();  // block size
    uint32_t shm_enabled = r.u32();
    if (!shm_enabled) return -1;
    std::lock_guard<std::mutex> lk(pools_mu_);
    return map_pools_locked(r);
}

// ---------------------------------------------------------------------------
// Submission plumbing
// ---------------------------------------------------------------------------

void Connection::rpc_async(uint8_t op, std::vector<uint8_t> body, DoneFn done) {
    if (broken_.load() || !running_.load()) {
        if (done) done(INTERNAL_ERROR, {});
        return;
    }
    auto body_p = std::make_shared<std::vector<uint8_t>>(std::move(body));
    Submit s;
    s.fn = [this, op, body_p, done = std::move(done)]() mutable {
        Pending p;
        p.op = op;
        p.done = std::move(done);
        enqueue_msg(op, std::move(*body_p), {}, std::move(p));
    };
    {
        std::lock_guard<std::mutex> lk(submit_mu_);
        submits_.push_back(std::move(s));
    }
    wake();
}

uint32_t Connection::rpc(uint8_t op, std::vector<uint8_t> body,
                         std::vector<uint8_t>* resp_body) {
    struct WaitState {
        std::mutex mu;
        std::condition_variable cv;
        bool done = false;
        uint32_t status = TIMEOUT_ERR;
        std::vector<uint8_t> body;
    };
    auto st = std::make_shared<WaitState>();
    rpc_async(op, std::move(body),
              [st](uint32_t status, std::vector<uint8_t> b) {
                  std::lock_guard<std::mutex> lk(st->mu);
                  st->status = status;
                  st->body = std::move(b);
                  st->done = true;
                  st->cv.notify_all();
              });
    std::unique_lock<std::mutex> lk(st->mu);
    if (!st->cv.wait_for(lk, std::chrono::milliseconds(cfg_.timeout_ms),
                         [&] { return st->done; })) {
        return TIMEOUT_ERR;
    }
    if (resp_body) *resp_body = std::move(st->body);
    return st->status;
}

void Connection::write_async(uint32_t block_size, std::vector<uint64_t> tokens,
                             std::vector<const void*> srcs, DoneFn done) {
    inflight_++;
    if (broken_.load() || !running_.load()) {
        if (done) done(INTERNAL_ERROR, {});
        finish_op();
        return;
    }
    uint64_t payload = uint64_t(block_size) * tokens.size();
    auto toks = std::make_shared<std::vector<uint64_t>>(std::move(tokens));
    auto sp = std::make_shared<std::vector<const void*>>(std::move(srcs));
    Submit s;
    s.window_cost = payload;
    s.fn = [this, block_size, toks, sp, payload,
            done = std::move(done)]() mutable {
        std::vector<uint8_t> body;
        BufWriter w(body);
        w.u32(block_size);
        w.u32(uint32_t(toks->size()));
        for (uint64_t t : *toks) w.u64(t);
        std::vector<std::pair<const uint8_t*, size_t>> segs;
        segs.reserve(sp->size());
        for (const void* p : *sp) {
            segs.emplace_back(static_cast<const uint8_t*>(p), block_size);
        }
        Pending pend;
        pend.op = OP_WRITE;
        pend.payload_bytes = payload;
        // Keep gather sources alive until completion.
        pend.done = [this, sp, done = std::move(done)](
                        uint32_t status, std::vector<uint8_t> b) {
            if (done) done(status, std::move(b));
            finish_op();
        };
        enqueue_msg(OP_WRITE, std::move(body), std::move(segs),
                    std::move(pend));
    };
    {
        std::lock_guard<std::mutex> lk(submit_mu_);
        submits_.push_back(std::move(s));
    }
    wake();
}

void Connection::put_async(uint32_t block_size,
                           std::vector<uint8_t> keys_body,
                           std::vector<const void*> srcs, DoneFn done) {
    // One-RTT streamed put: allocate+write+commit server-side (OP_PUT).
    // Dedup'd keys' payload is sunk by the server (first-writer-wins).
    inflight_++;
    if (broken_.load() || !running_.load()) {
        if (done) done(INTERNAL_ERROR, {});
        finish_op();
        return;
    }
    uint64_t payload = uint64_t(block_size) * srcs.size();
    auto ks = std::make_shared<std::vector<uint8_t>>(std::move(keys_body));
    auto sp = std::make_shared<std::vector<const void*>>(std::move(srcs));
    Submit s;
    s.window_cost = payload;
    s.fn = [this, block_size, ks, sp, payload,
            done = std::move(done)]() mutable {
        std::vector<uint8_t> body;
        BufWriter w(body);
        w.u32(block_size);
        w.bytes(ks->data(), ks->size());
        std::vector<std::pair<const uint8_t*, size_t>> segs;
        segs.reserve(sp->size());
        for (const void* p : *sp) {
            segs.emplace_back(static_cast<const uint8_t*>(p), block_size);
        }
        Pending pend;
        pend.op = OP_PUT;
        pend.payload_bytes = payload;
        pend.done = [this, sp, done = std::move(done)](
                        uint32_t status, std::vector<uint8_t> b) {
            if (done) done(status, std::move(b));
            finish_op();
        };
        enqueue_msg(OP_PUT, std::move(body), std::move(segs),
                    std::move(pend));
    };
    {
        std::lock_guard<std::mutex> lk(submit_mu_);
        submits_.push_back(std::move(s));
    }
    wake();
}

void Connection::read_async(uint32_t block_size,
                            std::vector<uint8_t> keys_body,
                            std::vector<void*> dsts, DoneFn done) {
    inflight_++;
    if (broken_.load() || !running_.load()) {
        if (done) done(INTERNAL_ERROR, {});
        finish_op();
        return;
    }
    auto ks = std::make_shared<std::vector<uint8_t>>(std::move(keys_body));
    auto dp = std::make_shared<std::vector<void*>>(std::move(dsts));
    Submit s;
    s.fn = [this, block_size, ks, dp, done = std::move(done)]() mutable {
        std::vector<uint8_t> body;
        BufWriter w(body);
        w.u32(block_size);
        w.bytes(ks->data(), ks->size());
        Pending pend;
        pend.op = OP_READ;
        pend.scatter.reserve(dp->size());
        for (void* p : *dp) {
            pend.scatter.emplace_back(static_cast<uint8_t*>(p), block_size);
        }
        pend.done = [this, dp, done = std::move(done)](
                        uint32_t status, std::vector<uint8_t> b) {
            if (done) done(status, std::move(b));
            finish_op();
        };
        enqueue_msg(OP_READ, std::move(body), {}, std::move(pend));
    };
    {
        std::lock_guard<std::mutex> lk(submit_mu_);
        submits_.push_back(std::move(s));
    }
    wake();
}

void Connection::shm_write_async(uint32_t block_size,
                                 std::vector<RemoteBlock> blocks,
                                 std::vector<const void*> srcs, DoneFn done) {
    inflight_++;
    if (broken_.load() || !running_.load()) {
        if (done) done(INTERNAL_ERROR, {});
        finish_op();
        return;
    }
    // One-sided copies into the mapped pool (CUDA-IPC memcpy analogue,
    // reference write_cache infinistore.cpp:702-804 — but client-side).
    // The copies run INLINE on the caller's thread (the Python caller
    // holds no GIL): on a single-core host routing bulk memcpy through
    // the IO thread would just add context switches, and copying before
    // return means the caller may reuse its buffer immediately. Only the
    // COMMIT rpc is pipelined through the IO thread.
    //
    // A block in a pool this client has not mapped (server extended
    // after our HELLO) is NOT silently skipped: its token is excluded
    // from the commit and the op fails so the caller can
    // refresh_pools() and retry — committing an unwritten block would
    // serve garbage under that key forever.
    std::vector<uint64_t> ok_toks;
    bool copy_failed = false;
    {
        std::lock_guard<std::mutex> lk(pools_mu_);
        // Coalesce runs of blocks that are adjacent both in the pool and
        // in the source buffer into single large memcpys. First-fit
        // allocation hands out sequential offsets, and batched writers
        // pass slices of one contiguous buffer, so a 512-block batch
        // typically collapses to a handful of multi-MB copies.
        size_t i = 0;
        const size_t nblk = blocks.size();
        while (i < nblk) {
            const RemoteBlock& b = blocks[i];
            if (b.token == FAKE_TOKEN) {  // dedup: skip
                ++i;
                continue;
            }
            // Bounds: inside the mapped pool AND inside the allocated
            // entry — a page larger than the allocation must fail, not
            // overwrite the neighbouring keys' blocks.
            if (!(b.pool_idx < pools_.size() &&
                  b.offset + block_size <= pools_[b.pool_idx].size &&
                  block_size <= b.size)) {
                copy_failed = true;
                ++i;
                continue;
            }
            size_t j = i + 1;
            while (j < nblk) {
                const RemoteBlock& nb = blocks[j];
                if (!(nb.token != FAKE_TOKEN &&
                      nb.pool_idx == b.pool_idx &&
                      nb.offset == b.offset + (j - i) * block_size &&
                      nb.offset + block_size <= pools_[b.pool_idx].size &&
                      block_size <= nb.size &&
                      static_cast<const uint8_t*>(srcs[j]) ==
                          static_cast<const uint8_t*>(srcs[i]) +
                              (j - i) * block_size)) {
                    break;
                }
                ++j;
            }
            memcpy(pools_[b.pool_idx].base + b.offset, srcs[i],
                   (j - i) * size_t(block_size));
            for (size_t k = i; k < j; ++k) ok_toks.push_back(blocks[k].token);
            i = j;
        }
    }
    std::vector<uint8_t> body;
    BufWriter w(body);
    w.u32(uint32_t(ok_toks.size()));
    for (uint64_t t : ok_toks) w.u64(t);
    auto body_p = std::make_shared<std::vector<uint8_t>>(std::move(body));
    Submit s;
    s.fn = [this, body_p, copy_failed, done = std::move(done)]() mutable {
        Pending pend;
        pend.op = OP_COMMIT;
        pend.done = [this, copy_failed, done = std::move(done)](
                        uint32_t status, std::vector<uint8_t> b) {
            if (copy_failed && status == OK) status = INTERNAL_ERROR;
            if (done) done(status, std::move(b));
            finish_op();
        };
        enqueue_msg(OP_COMMIT, std::move(*body_p), {}, std::move(pend));
    };
    {
        std::lock_guard<std::mutex> lk(submit_mu_);
        submits_.push_back(std::move(s));
    }
    wake();
}

uint32_t Connection::shm_read_blocking(uint32_t block_size,
                                       std::vector<uint8_t> keys_body,
                                       std::vector<void*> dsts,
                                       const std::vector<std::string>*
                                           cache_keys) {
    if (broken_.load() || !running_.load()) return INTERNAL_ERROR;
    std::vector<uint8_t> body(std::move(keys_body));
    // PIN with an abandonment-aware wait: if the caller times out before
    // the response lands, the late callback (on the IO thread) must still
    // release the lease — otherwise the pinned blocks stay unevictable
    // and undeletable forever.
    struct PinWait {
        std::mutex mu;
        std::condition_variable cv;
        bool fired = false;
        bool abandoned = false;
        uint32_t st = TIMEOUT_ERR;
        std::vector<uint8_t> body;
    };
    auto pw = std::make_shared<PinWait>();
    rpc_async(OP_PIN, std::move(body),
              [this, pw](uint32_t status, std::vector<uint8_t> b) {
                  std::unique_lock<std::mutex> lk(pw->mu);
                  if (pw->abandoned) {
                      lk.unlock();
                      // Late PIN response on the IO thread: release the
                      // lease the caller will never use.
                      if (status == OK && b.size() >= 8) {
                          BufReader lr(b.data(), b.size());
                          enqueue_release(lr.u64());
                      }
                      return;
                  }
                  pw->st = status;
                  pw->body = std::move(b);
                  pw->fired = true;
                  pw->cv.notify_all();
              });
    {
        std::unique_lock<std::mutex> lk(pw->mu);
        if (!pw->cv.wait_for(lk, std::chrono::milliseconds(cfg_.timeout_ms),
                             [&] { return pw->fired; })) {
            pw->abandoned = true;
            return TIMEOUT_ERR;
        }
    }
    uint32_t st = pw->st;
    std::vector<uint8_t> resp = std::move(pw->body);
    if (st != OK) return st;
    BufReader r(resp.data(), resp.size());
    uint64_t lease = r.u64();
    uint32_t n = r.u32();
    const uint8_t* raw = r.raw(size_t(n) * sizeof(RemoteBlock));
    // Trailing store epoch (for pin-cache population; 0 from servers
    // that predate the lease protocol — entries then never validate,
    // which is the safe direction).
    uint64_t srv_epoch = 0;
    if (raw != nullptr && r.remaining() >= 8) srv_epoch = r.u64();
    uint32_t rc = OK;
    if (raw == nullptr || n != dsts.size()) {
        rc = INTERNAL_ERROR;
    } else {
        std::vector<RemoteBlock> blks(n);
        memcpy(blks.data(), raw, size_t(n) * sizeof(RemoteBlock));
        bool need_refresh = false;
        {
            std::lock_guard<std::mutex> lk(pools_mu_);
            for (const RemoteBlock& blk : blks) {
                if (blk.pool_idx >= pools_.size()) need_refresh = true;
            }
        }
        if (need_refresh) {
            // Server auto-extended into pools we haven't mapped; a
            // blocking HELLO rpc is fine on this (caller) thread.
            std::vector<uint8_t> hb;
            if (rpc(OP_HELLO, {}, &hb) == OK) {
                BufReader hr(hb.data(), hb.size());
                hr.u32();  // block size
                uint32_t shm_enabled = hr.u32();
                if (shm_enabled) {
                    std::lock_guard<std::mutex> lk(pools_mu_);
                    map_pools_locked(hr);
                }
            }
        }
        std::lock_guard<std::mutex> lk(pools_mu_);
        // Same run-coalescing as the write path: adjacent pool blocks
        // read into adjacent destinations collapse into one memcpy.
        size_t i = 0;
        while (i < blks.size()) {
            const RemoteBlock& blk = blks[i];
            if (blk.size < block_size) {
                // Entry smaller than the requested page: mirror the
                // STREAM path's KEY_NOT_FOUND (server.cc op_read).
                rc = KEY_NOT_FOUND;
                ++i;
                continue;
            }
            if (!(blk.pool_idx < pools_.size() &&
                  blk.offset + block_size <= pools_[blk.pool_idx].size)) {
                rc = INTERNAL_ERROR;
                ++i;
                continue;
            }
            size_t j = i + 1;
            while (j < blks.size()) {
                const RemoteBlock& nb = blks[j];
                if (!(nb.size >= block_size && nb.pool_idx == blk.pool_idx &&
                      nb.offset == blk.offset + (j - i) * block_size &&
                      nb.offset + block_size <= pools_[blk.pool_idx].size &&
                      static_cast<uint8_t*>(dsts[j]) ==
                          static_cast<uint8_t*>(dsts[i]) +
                              (j - i) * block_size)) {
                    break;
                }
                ++j;
            }
            memcpy(dsts[i], pools_[blk.pool_idx].base + blk.offset,
                   (j - i) * size_t(block_size));
            i = j;
        }
        // Seed the pin cache from this PIN's locations so the next read
        // of these keys skips the rpc entirely (validated against the
        // shared epoch at read time).
        if (rc == OK && cache_keys != nullptr) {
            cache_pins(*cache_keys, blks.data(), n, srv_epoch);
        }
    }
    // Fire-and-forget release; the lease served its purpose.
    std::vector<uint8_t> rbody;
    BufWriter rw(rbody);
    rw.u64(lease);
    rpc_async(OP_RELEASE, std::move(rbody),
              [](uint32_t, std::vector<uint8_t>) {});
    return rc;
}

void Connection::shm_read_async(uint32_t block_size,
                                std::vector<uint8_t> keys_body,
                                std::vector<void*> dsts, DoneFn done) {
    inflight_++;
    if (broken_.load() || !running_.load()) {
        if (done) done(INTERNAL_ERROR, {});
        finish_op();
        return;
    }
    auto ks = std::make_shared<std::vector<uint8_t>>(std::move(keys_body));
    auto dp = std::make_shared<std::vector<void*>>(std::move(dsts));
    Submit s;
    s.fn = [this, block_size, ks, dp, done = std::move(done)]() mutable {
        std::vector<uint8_t> body(*ks);
        Pending pend;
        pend.op = OP_PIN;
        pend.done = [this, block_size, dp, done = std::move(done)](
                        uint32_t status, std::vector<uint8_t> b) mutable {
            if (status != OK) {
                if (done) done(status, std::move(b));
                finish_op();
                return;
            }
            BufReader r(b.data(), b.size());
            uint64_t lease = r.u64();
            uint32_t n = r.u32();
            const uint8_t* raw = r.raw(size_t(n) * sizeof(RemoteBlock));
            auto blks = std::make_shared<std::vector<RemoteBlock>>();
            bool parse_ok = raw != nullptr && n == dp->size();
            if (parse_ok) {
                blks->resize(n);
                memcpy(blks->data(), raw, size_t(n) * sizeof(RemoteBlock));
            }
            // The copy step, shared between the direct path and the
            // retry-after-HELLO path (server may have auto-extended into
            // pools we haven't mapped yet).
            auto do_copy = std::make_shared<std::function<void()>>();
            *do_copy = [this, block_size, dp, blks, lease, parse_ok,
                        done]() mutable {
                uint32_t st = parse_ok ? OK : INTERNAL_ERROR;
                if (parse_ok) {
                    std::lock_guard<std::mutex> lk(pools_mu_);
                    for (size_t i = 0; i < blks->size(); ++i) {
                        const RemoteBlock& blk = (*blks)[i];
                        if (blk.size < block_size) {
                            // Entry smaller than the requested page:
                            // mirror the STREAM path's KEY_NOT_FOUND
                            // (server.cc op_read size check).
                            st = KEY_NOT_FOUND;
                        } else if (blk.pool_idx < pools_.size() &&
                                   blk.offset + block_size <=
                                       pools_[blk.pool_idx].size) {
                            memcpy((*dp)[i],
                                   pools_[blk.pool_idx].base + blk.offset,
                                   block_size);
                        } else {
                            st = INTERNAL_ERROR;
                        }
                    }
                }
                // Unblock the caller before the fire-and-forget RELEASE:
                // the lease only pins pool blocks server-side, and the
                // copy is already done — no reason to charge the reader
                // for the release's socket write.
                if (done) done(st, {});
                finish_op();
                enqueue_release(lease);
            };
            bool need_refresh = false;
            if (parse_ok) {
                std::lock_guard<std::mutex> lk(pools_mu_);
                for (const RemoteBlock& blk : *blks) {
                    if (blk.pool_idx >= pools_.size()) need_refresh = true;
                }
            }
            if (!need_refresh) {
                (*do_copy)();
                return;
            }
            // Refresh the pool table inline on the IO thread (a sync rpc
            // here would deadlock — responses complete on this thread).
            Pending hp;
            hp.op = OP_HELLO;
            hp.done = [this, do_copy](uint32_t hst, std::vector<uint8_t> hb) {
                if (hst == OK) {
                    BufReader hr(hb.data(), hb.size());
                    hr.u32();  // block size
                    uint32_t shm_enabled = hr.u32();
                    if (shm_enabled) {
                        std::lock_guard<std::mutex> lk(pools_mu_);
                        map_pools_locked(hr);
                    }
                }
                (*do_copy)();
            };
            enqueue_msg(OP_HELLO, {}, {}, std::move(hp));
        };
        enqueue_msg(OP_PIN, std::move(body), {}, std::move(pend));
    };
    {
        std::lock_guard<std::mutex> lk(submit_mu_);
        submits_.push_back(std::move(s));
    }
    wake();
}

// ---------------------------------------------------------------------------
// Lease fast path: zero-RTT puts + batched deferred commit + pin cache
// ---------------------------------------------------------------------------

void Connection::commit_batch_async(std::vector<uint8_t> body, DoneFn done) {
    // Like rpc_async but inflight-accounted: sync() must barrier the
    // deferred commits or a caller could observe its own put missing.
    inflight_++;
    if (broken_.load() || !running_.load()) {
        if (done) done(INTERNAL_ERROR, {});
        finish_op();
        return;
    }
    auto body_p = std::make_shared<std::vector<uint8_t>>(std::move(body));
    Submit s;
    s.fn = [this, body_p, done = std::move(done)]() mutable {
        Pending p;
        p.op = OP_COMMIT_BATCH;
        p.done = [this, done = std::move(done)](uint32_t st,
                                                std::vector<uint8_t> b) {
            if (done) done(st, std::move(b));
            finish_op();
        };
        // Fabric ring first: the record lands one-sided in shm and
        // only a rare doorbell touches the socket; the response (and
        // so sync()/error-latch semantics) is identical. A full ring
        // falls through to the TCP frame — safe in THAT direction
        // because the server drains the ring before any TCP op. The
        // reverse needs the fab_tcp_inflight_ gate: once a fallback
        // frame is in flight, later commits must NOT take the ring
        // (the server's poll-tick drain could apply their carve
        // replay before the frame arrives off the socket — silent
        // cross-batch divergence of the mirrored cursor); they stay
        // on TCP until every fallback has its response.
        maybe_request_ring();  // async re-attach after a pool reclaim
        const bool ring = fab_ring_.load(std::memory_order_relaxed);
        if (ring && fab_tcp_inflight_ == 0 && try_ring_post(*body_p, p)) {
            return;
        }
        if (ring) {
            fab_tcp_inflight_++;
            p.done = [this, inner = std::move(p.done)](
                         uint32_t st, std::vector<uint8_t> b) {
                fab_tcp_inflight_--;  // IO thread (completion context)
                if (inner) inner(st, std::move(b));
            };
        }
        enqueue_msg(OP_COMMIT_BATCH, std::move(*body_p), {}, std::move(p));
    };
    {
        std::lock_guard<std::mutex> lk(submit_mu_);
        submits_.push_back(std::move(s));
    }
    wake();
}

void Connection::put_hash_async(std::vector<uint8_t> body, DoneFn done) {
    // Hash-first put probe (OP_PUT_HASH). Inflight-accounted like the
    // deferred commits — a sync() must barrier HAVE-committed keys the
    // same as payload-carrying puts. Ring-first when the fabric ring
    // is attached: the probe lands one-sided in shm as a flagged
    // hash-first record and only the verdict response touches the
    // socket, so a same-host dedup'd put keeps the one-sided shape
    // with no extra RTT. The fab_tcp_inflight_ gate is carried over
    // from the commit path for uniformity (hash records replay no
    // carve, so ordering is not load-bearing here).
    inflight_++;
    if (broken_.load() || !running_.load()) {
        if (done) done(INTERNAL_ERROR, {});
        finish_op();
        return;
    }
    auto body_p = std::make_shared<std::vector<uint8_t>>(std::move(body));
    Submit s;
    s.fn = [this, body_p, done = std::move(done)]() mutable {
        Pending p;
        p.op = OP_PUT_HASH;
        p.done = [this, done = std::move(done)](uint32_t st,
                                                std::vector<uint8_t> b) {
            if (done) done(st, std::move(b));
            finish_op();
        };
        maybe_request_ring();  // async re-attach after a pool reclaim
        const bool ring = fab_ring_.load(std::memory_order_relaxed);
        if (ring && fab_tcp_inflight_ == 0 &&
            try_ring_post(*body_p, p, /*hash_rec=*/true)) {
            return;
        }
        enqueue_msg(OP_PUT_HASH, std::move(*body_p), {}, std::move(p));
    };
    {
        std::lock_guard<std::mutex> lk(submit_mu_);
        submits_.push_back(std::move(s));
    }
    wake();
}

uint32_t Connection::put_hash(std::vector<uint8_t> body,
                              std::vector<uint8_t>* resp_body) {
    struct WaitState {
        std::mutex mu;
        std::condition_variable cv;
        bool done = false;
        uint32_t status = TIMEOUT_ERR;
        std::vector<uint8_t> body;
    };
    auto st = std::make_shared<WaitState>();
    put_hash_async(std::move(body),
                   [st](uint32_t status, std::vector<uint8_t> b) {
                       std::lock_guard<std::mutex> lk(st->mu);
                       st->status = status;
                       st->body = std::move(b);
                       st->done = true;
                       st->cv.notify_all();
                   });
    std::unique_lock<std::mutex> lk(st->mu);
    if (!st->cv.wait_for(lk, std::chrono::milliseconds(cfg_.timeout_ms),
                         [&] { return st->done; })) {
        return TIMEOUT_ERR;
    }
    // Verdict telemetry: HAVE = payload never left this process.
    // (The IO thread already stripped the leading u32 status, so the
    // delivered body is {u32 n, n x u8 verdicts}.)
    if (st->status == OK) {
        BufReader r(st->body.data(), st->body.size());
        uint32_t n = r.u32();
        const uint8_t* v = r.raw(n);
        if (r.ok() && v != nullptr) {
            uint64_t have = 0, need = 0;
            for (uint32_t i = 0; i < n; ++i) {
                if (v[i] == 1) {
                    have++;
                } else if (v[i] == 0) {
                    need++;
                }
            }
            dedup_have_.fetch_add(have, std::memory_order_relaxed);
            dedup_need_.fetch_add(need, std::memory_order_relaxed);
        }
    }
    if (resp_body) *resp_body = std::move(st->body);
    return st->status;
}

uint32_t Connection::acquire_lease_locked(uint32_t min_blocks) {
    if (lease_valid_) {
        // Return the old lease's unconsumed remainder. Fire-and-forget,
        // but ordered AFTER any commit batch already submitted for it
        // (both ride the same FIFO submit queue and socket).
        std::vector<uint8_t> rb;
        BufWriter rw(rb);
        rw.u64(lease_id_);
        rpc_async(OP_LEASE_REVOKE, std::move(rb), {});
        lease_valid_ = false;
    }
    uint64_t want = std::max<uint64_t>(min_blocks, cfg_.lease_blocks);
    if (want > MAX_LEASE_BLOCKS) want = MAX_LEASE_BLOCKS;
    if (want < min_blocks) return PARTIAL;  // key bigger than any lease
    std::vector<uint8_t> body;
    BufWriter w(body);
    w.u32(uint32_t(want));
    std::vector<uint8_t> resp;
    uint32_t st = rpc(OP_LEASE, std::move(body), &resp);
    // BUSY = per-connection grant cap (we hold too many unconsumed
    // blocks): let the caller fall back to the legacy path, which the
    // cap does not gate, instead of surfacing a hard error.
    if (st == BUSY) return PARTIAL;
    if (st != OK) return st;
    BufReader r(resp.data(), resp.size());
    uint64_t id = r.u64();
    r.u64();  // epoch snapshot; the live word is in the ctl page
    uint32_t nruns = r.u32();
    if (!r.ok() || nruns == 0 || nruns > 64) return INTERNAL_ERROR;
    std::vector<ClientRun> runs(nruns);
    uint32_t max_pool = 0;
    for (auto& run : runs) {
        run.pool_idx = r.u32();
        run.offset = r.u64();
        run.nblocks = r.u32();
        if (run.pool_idx > max_pool) max_pool = run.pool_idx;
    }
    if (!r.ok()) return INTERNAL_ERROR;
    // Cross-host fabric mode never dereferences the grant locally (the
    // server scatters OP_FABRIC_WRITE payload itself), so the runs
    // only need to be a valid carve cursor — no mapping required.
    bool mapped = !shm_active_;
    if (!mapped) {
        std::lock_guard<std::mutex> plk(pools_mu_);
        mapped = max_pool < pools_.size();
    }
    if (!mapped) {
        // Granted out of a pool the server auto-extended after our
        // HELLO: map it before carving (never write blind).
        refresh_pools();
        std::lock_guard<std::mutex> plk(pools_mu_);
        mapped = max_pool < pools_.size();
    }
    if (!mapped) {
        std::vector<uint8_t> rb;
        BufWriter rw(rb);
        rw.u64(id);
        rpc_async(OP_LEASE_REVOKE, std::move(rb), {});
        return PARTIAL;
    }
    lease_id_ = id;
    lease_runs_ = std::move(runs);
    lease_run_idx_ = 0;
    lease_block_off_ = 0;
    lease_valid_ = true;
    return OK;
}

void Connection::post_task(std::function<void()> fn) {
    {
        std::lock_guard<std::mutex> lk(submit_mu_);
        Submit s;
        s.fn = std::move(fn);
        submits_.push_back(std::move(s));
    }
    wake();
}

void Connection::flush_locked() {
    if (pend_nkeys_ == 0) return;
    std::vector<uint8_t> body;
    BufWriter w(body);
    w.u64(lease_id_);
    w.u32(pend_bsize_);
    w.u32(pend_nkeys_);
    w.bytes(pend_blob_.data(), pend_blob_.size());
    auto blob =
        std::make_shared<std::vector<uint8_t>>(std::move(pend_blob_));
    auto locs =
        std::make_shared<std::vector<CachedLoc>>(std::move(pend_locs_));
    const uint32_t nkeys = pend_nkeys_;
    pend_blob_.clear();
    pend_locs_.clear();
    pend_nkeys_ = 0;
    pend_bytes_ = 0;
    commit_batch_async(
        std::move(body),
        [this, blob, locs, nkeys](uint32_t st, std::vector<uint8_t> b) {
            if (st != OK) {
                // Latch the FIRST failure; surfaced at the next sync()
                // exactly like pipelined write errors.
                uint32_t expected = 0;
                lease_err_.compare_exchange_strong(expected, st);
                return;
            }
            BufReader r(b.data(), b.size());
            r.u32();  // committed count
            uint64_t epoch = r.u64();
            uint32_t nd = r.u32();
            auto dedup = std::make_shared<std::vector<bool>>(nkeys, false);
            for (uint32_t i = 0; i < nd && r.ok(); ++i) {
                uint32_t idx = r.u32();
                if (idx < nkeys) (*dedup)[idx] = true;
            }
            if (!r.ok()) return;
            // Seed the pin cache OFF the sync() critical path: this
            // completion holds up the caller's barrier, so the per-key
            // parse + inserts run as a follow-up IO-thread task (a read
            // racing the seeding just misses and takes the PIN path).
            post_task([this, blob, locs, dedup, nkeys, epoch] {
                BufReader kr(blob->data(), blob->size());
                std::lock_guard<std::mutex> clk(cache_mu_);
                for (uint32_t i = 0; i < nkeys; ++i) {
                    std::string key = kr.str();
                    if (!kr.ok()) return;
                    // Dedup'd keys live at ANOTHER writer's location,
                    // which we do not know — skip them.
                    if ((*dedup)[i]) continue;
                    CachedLoc loc = (*locs)[i];
                    loc.epoch = epoch;
                    cache_insert_locked(std::move(key), loc);
                }
            });
        });
}

uint32_t Connection::lease_put(uint32_t block_size,
                               std::vector<uint8_t> keys_wire,
                               uint32_t nkeys,
                               std::vector<const void*> srcs) {
    if (broken_.load() || !running_.load()) return INTERNAL_ERROR;
    if (!lease_ready() || !shm_active_ || server_block_size_ == 0 ||
        block_size == 0 || keys_wire.size() < 4 || nkeys != srcs.size()) {
        return PARTIAL;  // caller falls back to the legacy path
    }
    uint32_t wire_count = 0;
    memcpy(&wire_count, keys_wire.data(), 4);
    if (wire_count != nkeys) return BAD_REQUEST;
    // Structural pre-scan (u32 reads only, no allocation): the per-key
    // append below must never run off a malformed blob, and pend_blob_/
    // pend_locs_/pend_nkeys_ must stay in lockstep even across the
    // mid-loop flushes a lease transition triggers.
    {
        size_t pos = 4;
        for (uint32_t i = 0; i < nkeys; ++i) {
            if (pos + 4 > keys_wire.size()) return BAD_REQUEST;
            uint32_t len = 0;
            memcpy(&len, keys_wire.data() + pos, 4);
            pos += 4 + size_t(len);
            if (pos > keys_wire.size()) return BAD_REQUEST;
        }
        if (pos != keys_wire.size()) return BAD_REQUEST;
    }
    size_t kpos = 4;  // cursor over the wire entries
    const uint32_t bs = server_block_size_;
    const uint32_t nb = uint32_t((uint64_t(block_size) + bs - 1) / bs);
    std::vector<CopyPool::Seg> segs;
    segs.reserve(nkeys);
    std::lock_guard<std::mutex> lk(lease_mu_);
    // Bytes must be IN the pool before their commit batch is on the
    // wire (a reader may see the entry the instant the server applies
    // the commit), so drain pending copies ahead of every flush.
    auto drain = [&] {
        if (!segs.empty()) {
            CopyPool::inst().run(std::move(segs));
            segs.clear();
        }
    };
    if (pend_nkeys_ != 0 && pend_bsize_ != block_size) {
        drain();
        flush_locked();
    }
    for (size_t i = 0; i < nkeys; ++i) {
        // Mirror carve (server replays this exactly): skip run
        // remainders too small for one key, consume nb blocks.
        bool carved = false;
        for (int attempt = 0; attempt < 2 && !carved; ++attempt) {
            if (lease_valid_) {
                while (lease_run_idx_ < lease_runs_.size() &&
                       lease_runs_[lease_run_idx_].nblocks -
                               lease_block_off_ <
                           nb) {
                    lease_run_idx_++;
                    lease_block_off_ = 0;
                }
                if (lease_run_idx_ < lease_runs_.size()) {
                    carved = true;
                    break;
                }
            }
            if (attempt == 1) break;
            // Lease exhausted: flush what pends (it belongs to the old
            // lease), then buy the next N allocations with one RTT.
            drain();
            flush_locked();
            uint32_t st = acquire_lease_locked(nb);
            if (st != OK) {
                drain();
                return st;
            }
        }
        if (!carved) {  // fragmented grant: fall back
            drain();
            return PARTIAL;
        }
        const ClientRun& run = lease_runs_[lease_run_idx_];
        CachedLoc loc;
        loc.pool_idx = run.pool_idx;
        loc.offset = run.offset + uint64_t(lease_block_off_) * bs;
        loc.size = block_size;
        loc.epoch = 0;  // stamped by the commit response
        lease_block_off_ += nb;
        if (lease_block_off_ == run.nblocks) {
            lease_run_idx_++;
            lease_block_off_ = 0;
        }
        {
            std::lock_guard<std::mutex> plk(pools_mu_);
            if (!(loc.pool_idx < pools_.size() &&
                  loc.offset + block_size <=
                      pools_[loc.pool_idx].size)) {
                // Cannot happen (the grant was mapped at acquire) — but
                // if it ever does, the carve cursor above already moved
                // while the server's mirror will not: drop the lease so
                // the next put re-acquires instead of committing every
                // later key at a shifted location.
                lease_valid_ = false;
                drain();
                return INTERNAL_ERROR;
            }
            CopyPool::add_seg(
                segs, pools_[loc.pool_idx].base + loc.offset,
                static_cast<const uint8_t*>(srcs[i]), block_size);
        }
        // Append this key's raw wire entry (validated by the pre-scan) —
        // no per-key parse on this path; the server decodes once.
        uint32_t klen = 0;
        memcpy(&klen, keys_wire.data() + kpos, 4);
        pend_blob_.insert(pend_blob_.end(), keys_wire.begin() + kpos,
                          keys_wire.begin() + kpos + 4 + klen);
        kpos += 4 + size_t(klen);
        pend_locs_.push_back(loc);
        pend_nkeys_++;
        pend_bsize_ = block_size;
        pend_bytes_ += block_size;
    }
    drain();
    if (pend_bytes_ >= cfg_.flush_bytes) flush_locked();
    return OK;
}

uint32_t Connection::lease_flush() {
    std::lock_guard<std::mutex> lk(lease_mu_);
    flush_locked();
    return OK;
}

uint32_t Connection::lease_take_error() { return lease_err_.exchange(0); }

void Connection::cache_insert_locked(std::string key,
                                     const CachedLoc& loc) {
    // Crude-but-bounded: a full cache is cleared wholesale (correctness
    // is epoch-guarded either way; this only trades hit rate).
    if (pin_cache_.size() >= kPinCacheCap) pin_cache_.clear();
    pin_cache_[std::move(key)] = loc;
}

void Connection::cache_pins(const std::vector<std::string>& keys,
                            const RemoteBlock* blocks, size_t n,
                            uint64_t epoch) {
    if (!lease_ready() || n != keys.size()) return;
    std::lock_guard<std::mutex> clk(cache_mu_);
    for (size_t i = 0; i < n; ++i) {
        CachedLoc loc;
        loc.pool_idx = blocks[i].pool_idx;
        loc.offset = blocks[i].offset;
        loc.size = blocks[i].size;
        loc.epoch = epoch;
        cache_insert_locked(keys[i], loc);
    }
}

bool Connection::cached_read(uint32_t block_size,
                             const std::vector<std::string>& keys,
                             const std::vector<void*>& dsts) {
    // Telemetry wrapper: one hit/miss per read CALL (not per key) —
    // the ratio is what client_stats() reports, and a partial batch
    // miss falls back to the pinned rpc path for the whole call anyway.
    bool ok = cached_read_impl(block_size, keys, dsts);
    (ok ? pin_cache_hits_ : pin_cache_misses_)
        .fetch_add(1, std::memory_order_relaxed);
    return ok;
}

bool Connection::cached_read_impl(uint32_t block_size,
                                  const std::vector<std::string>& keys,
                                  const std::vector<void*>& dsts) {
    // A broken connection must MISS, not serve: the mappings outlive the
    // socket, and a dead server's orphaned pool pages would otherwise
    // keep validating against the frozen epoch word forever — hiding
    // the failure from the reconnect machinery.
    if (broken_.load() || !running_.load()) return false;
    if (!lease_ready() || !shm_active_ || keys.empty() ||
        keys.size() != dsts.size()) {
        return false;
    }
    // Optimistic one-sided read: epoch before, copy, epoch after. Any
    // evict/spill/delete/purge between the two loads bumps the shared
    // word (release store under the server's store lock), so equality
    // proves every cached location stayed valid for the whole copy.
    //
    // pools_mu_ is held across the WHOLE sequence — lookup, copy and
    // both epoch loads — because close_conn/reconnect on another thread
    // munmaps the pools and the ctl page under the same mutex: a
    // concurrent close must fail this read safely, never let it copy
    // from (or validate against) unmapped memory. The legacy shm copy
    // paths hold pools_mu_ across their memcpys for the same reason.
    std::lock_guard<std::mutex> plk(pools_mu_);
    if (ctl_map_ == nullptr) return false;  // torn down under us
    const uint64_t e1 = ctl_epoch(std::memory_order_acquire);
    std::vector<CopyPool::Seg> segs;
    segs.reserve(keys.size());
    {
        // Lock order pools_mu_ -> cache_mu_ everywhere (shm_read_blocking
        // seeds the cache while holding pools_mu_).
        std::lock_guard<std::mutex> clk(cache_mu_);
        for (size_t i = 0; i < keys.size(); ++i) {
            auto it = pin_cache_.find(keys[i]);
            if (it == pin_cache_.end()) return false;
            const CachedLoc& loc = it->second;
            if (loc.epoch != e1) {
                // The store epoch moved since this location was
                // learned (evict/spill/delete/purge): the one-sided
                // read is invalid, fall back to the pinned RPC path
                // (which re-seeds at the current epoch). Recorded —
                // for fabric connections only, the plane the event
                // row documents — so an epoch storm pushing every
                // read onto RPC is visible in the flight recorder.
                if (cfg_.use_fabric) {
                    events_emit(EV_FABRIC_EPOCH_MISS, e1, loc.epoch);
                }
                return false;
            }
            if (loc.size < block_size ||
                loc.pool_idx >= pools_.size() ||
                loc.offset + block_size > pools_[loc.pool_idx].size) {
                return false;
            }
            CopyPool::add_seg(segs, static_cast<uint8_t*>(dsts[i]),
                              pools_[loc.pool_idx].base + loc.offset,
                              block_size);
        }
    }
    CopyPool::inst().run(std::move(segs));
    // Acquire fence: the e2 load must not be ordered before the copy's
    // reads (an ARM host could otherwise validate against a pre-copy
    // epoch while the bytes raced an eviction).
    std::atomic_thread_fence(std::memory_order_acquire);
    const uint64_t e2 = ctl_epoch(std::memory_order_acquire);
    if (e2 != e1) {
        // Epoch moved under the copy (evict/spill/delete/purge): the
        // one-sided read is invalid and the caller falls back to the
        // pinned RPC path — the detected-and-retried half of the
        // optimistic protocol, flight-recorded (fabric connections
        // only) so a fabric epoch storm (churning pool forcing every
        // read back onto RPC) is visible.
        if (cfg_.use_fabric) events_emit(EV_FABRIC_EPOCH_MISS, e1, e2);
        return false;
    }
    return true;
}

// ---------------------------------------------------------------------------
// One-sided fabric plane (fabric.h; docs/design.md "One-sided fabric
// engine")
// ---------------------------------------------------------------------------

bool Connection::fabric_bootstrap_attach() {
    // want_ring=0 from a STREAM connection: negotiate the protocol
    // (OP_FABRIC_WRITE support) without making the server carve a shm
    // ring this client could never map.
    uint32_t want_ring = shm_active_ ? 1 : 0;
    WireHeader h = make_header(OP_FABRIC_ATTACH, 0, 4, 0);
    uint8_t frame[sizeof(WireHeader) + 4];
    memcpy(frame, &h, sizeof(h));
    memcpy(frame + sizeof(h), &want_ring, 4);
    if (!send_exact(fd_, frame, sizeof(frame))) return false;
    WireHeader rh;
    if (!recv_exact(fd_, &rh, sizeof(rh)) || !header_valid(rh) ||
        rh.payload_len != 0) {
        return false;
    }
    std::vector<uint8_t> body(rh.body_len);
    if (!recv_exact(fd_, body.data(), body.size())) return false;
    BufReader r(body.data(), body.size());
    if (r.u32() != OK) {
        // Pre-fabric server (BAD_REQUEST from the unknown-op default):
        // stay on the legacy paths, the connection itself is fine.
        return true;
    }
    uint32_t active = r.u32();
    std::string name = r.str();
    uint64_t bytes = r.u64();
    if (!r.ok()) return true;
    // Protocol negotiated. Without a ring grant (non-fabric engine,
    // cross-host, no shm) the stream mode carries the one-sided puts.
    if (!shm_active_) {
        fabric_stream_ = true;
        return true;
    }
    if (!active || name.empty() || bytes == 0) return true;
    int fd = shm_open(("/" + name).c_str(), O_RDWR, 0);
    if (fd < 0) return true;  // remote server: ring not reachable
    size_t total = kFabricHdrBytes + size_t(bytes);
    void* mem =
        mmap(nullptr, total, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
    close(fd);
    if (mem == MAP_FAILED) return true;
    auto* hdr = static_cast<FabricRingHdr*>(mem);
    if (hdr->magic != FABRIC_MAGIC || hdr->version != FABRIC_VERSION ||
        hdr->data_cap != bytes) {
        munmap(mem, total);
        return true;
    }
    fab_hdr_ = hdr;
    fab_map_bytes_ = total;
    fab_ring_.store(true);
    IST_INFO("fabric commit ring attached (%s, %llu B)", name.c_str(),
             (unsigned long long)bytes);
    return true;
}

bool Connection::try_ring_post(std::vector<uint8_t>& body,
                               Pending& pending, bool hash_rec) {
    FabricRingHdr* h = fab_hdr_;
    if (h == nullptr) return false;
    // fail_all() fails queued submissions by RUNNING them, relying on
    // enqueue_msg's broken_ check to complete each Pending with an
    // error. The ring path must refuse the same way: posting here
    // would hand the server a record for a batch the client is about
    // to report failed, and register a Pending that can never
    // complete (pending_ was already cleared) — wedging sync().
    if (broken_.load()) return false;
    // Ring-pool detach, quiet half: the server flipped the ring to
    // DETACHING (LRU reclaim under pool pressure) before this post
    // started. Nothing of ours is in flight — drop the carcass mapping
    // and take the TCP path; maybe_request_ring() re-attaches later.
    if (h->state.load(std::memory_order_relaxed) != kFabricRingActive) {
        handle_ring_detach();
        return false;
    }
    const uint64_t cap = h->data_cap;
    uint64_t seq = next_seq_++;
    // Record = u32 len + u64 client_seq + the OP_COMMIT_BATCH body
    // bytes exactly as the TCP frame would carry them.
    const uint64_t rec = 8 + body.size();
    const uint64_t need = 4 + rec;
    if (rec > cap / 2) {
        next_seq_--;  // oversized: the TCP path takes this batch
        return false;
    }
    uint64_t tail = h->tail.load(std::memory_order_relaxed);
    uint64_t head = h->head.load(std::memory_order_acquire);
    uint8_t* data = fabric_data(h);
    uint64_t pos = tail % cap;
    uint64_t run = fabric_run_to_end(tail, cap);
    uint64_t pad = run < need ? run : 0;  // wrap: skip the sliver
    if ((tail - head) + pad + need > cap) {
        // Ring full — the server is behind. Fall back to a TCP commit
        // frame (drained in order server-side) and flight-record the
        // stall: a persistently full ring means the doorbell plane is
        // not keeping up with offered load.
        next_seq_--;
        fab_fallbacks_.fetch_add(1, std::memory_order_relaxed);
        events_emit(EV_FABRIC_DOORBELL_STALL, tail - head, need);
        return false;
    }
    if (pad > 0) {
        if (run >= 4) {
            uint32_t mark = kFabricWrapMark;
            memcpy(data + pos, &mark, 4);
        }
        tail += pad;
        pos = 0;
    }
    // Ring v2: the high bit of the len word flags a hash-first record
    // (fabric.h). Real lengths are < cap/2, so the bit is never
    // ambiguous; the server masks it after its wrap-mark check.
    uint32_t len = uint32_t(rec) | (hash_rec ? kFabricHashRecFlag : 0);
    memcpy(data + pos, &len, 4);
    memcpy(data + pos + 4, &seq, 8);
    if (!body.empty()) {
        memcpy(data + pos + 12, body.data(), body.size());
    }
    // seq_cst publication pairs with the consumer's need_kick store /
    // tail re-load (fabric.h doorbell protocol): either the server's
    // run-dry re-check sees this tail, or the load below sees
    // need_kick=1 and we kick it over TCP.
    h->tail.store(tail + need, std::memory_order_seq_cst);
    // Ring-pool detach, racing half (fabric.h documents the Dekker):
    // the seq_cst tail publish above against the server's seq_cst
    // state store means exactly one of two worlds holds — either the
    // server's final ordered drain sees our tail (record consumed),
    // or we see state=DETACHING here and classify. Wait for the
    // drain's completion flag, then read the FINAL head: past our
    // record's end cursor means it was applied server-side (the TCP
    // response for `seq` is coming — register pending and report
    // posted); short of it means the record was never seen (give the
    // seq back and let the caller resend the same body over TCP — no
    // double-commit in either world).
    if (h->state.load(std::memory_order_seq_cst) ==
        kFabricRingDetaching) {
        for (uint32_t spin = 0;
             h->detach_done.load(std::memory_order_acquire) == 0;
             ++spin) {
            // The drain is a bounded in-memory walk; this only trips
            // if the server died mid-detach, where the socket is
            // about to break and fail this op anyway.
            if (spin > (1u << 20)) break;
            sched_yield();
        }
        const bool consumed =
            h->head.load(std::memory_order_acquire) >= tail + need;
        handle_ring_detach();
        if (!consumed) {
            next_seq_--;
            return false;
        }
        fab_posts_.fetch_add(1, std::memory_order_relaxed);
        pending_[seq] = std::move(pending);
        return true;  // no doorbell: the drain already ran
    }
    fab_posts_.fetch_add(1, std::memory_order_relaxed);
    pending_[seq] = std::move(pending);
    uint32_t armed = 1;
    if (h->need_kick.load(std::memory_order_seq_cst) == 1 &&
        h->need_kick.compare_exchange_strong(armed, 0)) {
        fab_doorbells_.fetch_add(1, std::memory_order_relaxed);
        Pending bell;
        bell.op = OP_FABRIC_DOORBELL;
        bell.done = [](uint32_t, std::vector<uint8_t>) {};
        enqueue_msg(OP_FABRIC_DOORBELL, {}, {}, std::move(bell));
    }
    return true;
}

void Connection::handle_ring_detach() {
    if (fab_hdr_ == nullptr) return;
    fab_ring_.store(false);
    munmap(fab_hdr_, fab_map_bytes_);
    fab_hdr_ = nullptr;
    fab_map_bytes_ = 0;
    fab_detached_ = true;
    fab_reattach_backoff_ = 0;  // first re-attach ask is immediate
    fab_detaches_.fetch_add(1, std::memory_order_relaxed);
    IST_INFO("fabric ring detached by server (pool reclaim); "
             "commits fall back to TCP");
}

void Connection::maybe_request_ring() {
    if (fab_hdr_ != nullptr || !fab_detached_ || fab_attach_inflight_ ||
        !shm_active_ || broken_.load()) {
        return;
    }
    if (fab_reattach_backoff_ > 0) {
        fab_reattach_backoff_--;
        return;
    }
    fab_attach_inflight_ = true;
    std::vector<uint8_t> body(4);
    uint32_t want_ring = 1;
    memcpy(body.data(), &want_ring, 4);
    Pending p;
    p.op = OP_FABRIC_ATTACH;
    p.done = [this](uint32_t st, std::vector<uint8_t> b) {
        // IO thread (completion context), like the fab_tcp_inflight_
        // bookkeeping.
        fab_attach_inflight_ = false;
        // A denial (pool still saturated → active=0) backs off by
        // post count, not time: under load the retry cadence scales
        // with traffic, and an idle client stops asking entirely.
        fab_reattach_backoff_ = 256;
        if (st != OK) return;
        BufReader r(b.data(), b.size());
        uint32_t active = r.u32();
        std::string name = r.str();
        uint64_t bytes = r.u64();
        if (!r.ok() || !active || name.empty() || bytes == 0) return;
        int fd = shm_open(("/" + name).c_str(), O_RDWR, 0);
        if (fd < 0) return;
        size_t total = kFabricHdrBytes + size_t(bytes);
        void* mem = mmap(nullptr, total, PROT_READ | PROT_WRITE,
                         MAP_SHARED, fd, 0);
        close(fd);
        if (mem == MAP_FAILED) return;
        auto* hdr = static_cast<FabricRingHdr*>(mem);
        if (hdr->magic != FABRIC_MAGIC ||
            hdr->version != FABRIC_VERSION || hdr->data_cap != bytes) {
            munmap(mem, total);
            return;
        }
        fab_hdr_ = hdr;
        fab_map_bytes_ = total;
        fab_reattaches_.fetch_add(1, std::memory_order_relaxed);
        fab_ring_.store(true);
        IST_INFO("fabric commit ring re-attached (%s)", name.c_str());
    };
    enqueue_msg(OP_FABRIC_ATTACH, std::move(body), {}, std::move(p));
}

uint32_t Connection::fabric_put(uint32_t block_size,
                                std::vector<uint8_t> keys_wire,
                                uint32_t nkeys,
                                std::vector<const void*> srcs,
                                DoneFn done) {
    if (broken_.load() || !running_.load()) return INTERNAL_ERROR;
    if (!fabric_stream_ || server_block_size_ == 0 || block_size == 0 ||
        nkeys == 0 || keys_wire.size() < 4 || nkeys != srcs.size()) {
        return PARTIAL;  // caller falls back to the legacy put
    }
    uint32_t wire_count = 0;
    memcpy(&wire_count, keys_wire.data(), 4);
    if (wire_count != nkeys) return BAD_REQUEST;
    const uint32_t bs = server_block_size_;
    const uint32_t nb = uint32_t((uint64_t(block_size) + bs - 1) / bs);
    std::lock_guard<std::mutex> lk(lease_mu_);
    // The frame carries ONE lease id, so the whole batch must carve
    // from one grant. Count what the current grant still fits WITHOUT
    // consuming (the same skip-small-runs rule the carve applies),
    // re-leasing once when short.
    auto fits = [&]() -> uint64_t {
        if (!lease_valid_) return 0;
        uint64_t n = 0;
        uint32_t off = lease_block_off_;
        for (size_t ri = lease_run_idx_; ri < lease_runs_.size(); ++ri) {
            n += (lease_runs_[ri].nblocks - off) / nb;
            off = 0;
        }
        return n;
    };
    if (fits() < nkeys) {
        uint64_t want = uint64_t(nkeys) * nb;
        if (want > MAX_LEASE_BLOCKS) return PARTIAL;
        uint32_t st = acquire_lease_locked(
            uint32_t(want > cfg_.lease_blocks ? want
                                              : cfg_.lease_blocks));
        if (st != OK) return st;
        if (fits() < nkeys) return PARTIAL;  // fragmented grant
    }
    const uint64_t lease_id = lease_id_;
    // Mirror carve: advance the cursor exactly as the server replays
    // it when the frame arrives (fits() above guarantees bounds).
    for (uint32_t i = 0; i < nkeys; ++i) {
        while (lease_run_idx_ < lease_runs_.size() &&
               lease_runs_[lease_run_idx_].nblocks - lease_block_off_ <
                   nb) {
            lease_run_idx_++;
            lease_block_off_ = 0;
        }
        lease_block_off_ += nb;
        if (lease_block_off_ == lease_runs_[lease_run_idx_].nblocks) {
            lease_run_idx_++;
            lease_block_off_ = 0;
        }
    }
    // Submit while still under lease_mu_: fabric frames must hit the
    // FIFO submit queue (and hence the socket) in carve order, and the
    // next put's possible lease acquire/revoke must queue after this
    // frame.
    inflight_++;
    uint64_t payload = uint64_t(block_size) * nkeys;
    auto ks = std::make_shared<std::vector<uint8_t>>(std::move(keys_wire));
    auto sp = std::make_shared<std::vector<const void*>>(std::move(srcs));
    Submit s;
    s.window_cost = payload;
    s.fn = [this, lease_id, block_size, ks, sp, payload,
            done = std::move(done)]() mutable {
        std::vector<uint8_t> body;
        BufWriter w(body);
        w.u64(lease_id);
        w.u32(block_size);
        w.bytes(ks->data(), ks->size());
        std::vector<std::pair<const uint8_t*, size_t>> segs;
        segs.reserve(sp->size());
        for (const void* p : *sp) {
            segs.emplace_back(static_cast<const uint8_t*>(p),
                              block_size);
        }
        Pending pend;
        pend.op = OP_FABRIC_WRITE;
        pend.payload_bytes = payload;
        pend.done = [this, sp, done = std::move(done)](
                        uint32_t status, std::vector<uint8_t> b) {
            if (done) done(status, std::move(b));
            finish_op();
        };
        enqueue_msg(OP_FABRIC_WRITE, std::move(body), std::move(segs),
                    std::move(pend));
    };
    {
        std::lock_guard<std::mutex> slk(submit_mu_);
        submits_.push_back(std::move(s));
    }
    wake();
    return OK;
}

void Connection::hard_fail() {
    // Reject new submissions, then force the IO thread off the socket:
    // shutdown makes its next recv/readv return 0, so it unwinds through
    // fail_all and can no longer scatter payload into caller memory.
    broken_.store(true);
    if (fd_ >= 0) shutdown(fd_, SHUT_RDWR);
    wake();
    std::unique_lock<std::mutex> lk(sync_mu_);
    bool unwound = sync_cv_.wait_for(lk, std::chrono::seconds(2), [&] {
        return io_exited_.load() || !running_.load();
    });
    lk.unlock();
    if (!unwound) {
        // The IO thread did not unwind (e.g. a completion callback stalled
        // on the GIL). Our caller will free its buffers on return, so a
        // later resumed scatter readv must not be able to touch them:
        // clear the scatter plan under the same mutex the scatter loop
        // holds across its readv — after this, payload can only land in
        // the drain buffer.
        std::lock_guard<std::mutex> slk(scatter_mu_);
        rscatter_.clear();
    }
}

uint32_t Connection::sync(int timeout_ms) {
    if (timeout_ms <= 0) timeout_ms = cfg_.timeout_ms;
    std::unique_lock<std::mutex> lk(sync_mu_);
    bool ok = sync_cv_.wait_for(lk, std::chrono::milliseconds(timeout_ms),
                                [&] { return inflight_.load() == 0; });
    if (!ok) return TIMEOUT_ERR;
    return broken_.load() ? INTERNAL_ERROR : OK;
}

void Connection::sync_async(DoneFn done) {
    if (!done) return;
    {
        std::lock_guard<std::mutex> lk(sync_mu_);
        if (inflight_.load() != 0) {
            sync_waiters_.push_back(std::move(done));
            return;
        }
    }
    done(broken_.load() ? INTERNAL_ERROR : OK, {});
}

void Connection::finish_op() {
    std::vector<DoneFn> waiters;
    {
        std::lock_guard<std::mutex> lk(sync_mu_);
        inflight_--;
        if (inflight_.load() == 0 && !sync_waiters_.empty()) {
            waiters.swap(sync_waiters_);
        }
    }
    sync_cv_.notify_all();
    if (!waiters.empty()) {
        // Outside sync_mu_: a waiter may immediately submit new ops (which
        // take sync_mu_ in their own finish_op) or call back into Python.
        uint32_t st = broken_.load() ? INTERNAL_ERROR : OK;
        for (auto& w : waiters) w(st, {});
    }
}

// ---------------------------------------------------------------------------
// IO thread
// ---------------------------------------------------------------------------

void Connection::enqueue_msg(uint8_t op, std::vector<uint8_t> body,
                             std::vector<std::pair<const uint8_t*, size_t>> segs,
                             Pending pending) {
    if (broken_.load()) {
        if (pending.done) pending.done(INTERNAL_ERROR, {});
        return;
    }
    uint64_t seq = next_seq_++;
    // Tracing: append the current trace id as the body's last 8 bytes
    // and flag it, so the server can stitch this frame to the client's
    // logical op. flags == 0 frames (id unset / old builds) are
    // byte-identical to the historical wire format.
    uint64_t trace_id = trace_id_.load(std::memory_order_relaxed);
    if (trace_id != 0) {
        size_t off = body.size();
        body.resize(off + 8);
        memcpy(body.data() + off, &trace_id, 8);
    }
    uint64_t payload = 0;
    for (auto& s : segs) payload += s.second;
    // Merge contiguous gather segments: batched put sources are slices of
    // one buffer, so the whole payload usually collapses to a single iovec
    // and flush_send's 64-iovec writev window covers it in one syscall.
    size_t out = 0;
    for (size_t i = 0; i < segs.size(); ++i) {
        if (out > 0 &&
            segs[out - 1].first + segs[out - 1].second == segs[i].first) {
            segs[out - 1].second += segs[i].second;
        } else {
            segs[out++] = segs[i];
        }
    }
    segs.resize(out);
    OutMsg m;
    m.meta.resize(sizeof(WireHeader) + body.size());
    WireHeader h = make_header(op, seq, uint32_t(body.size()), payload);
    if (trace_id != 0) h.flags |= FLAG_TRACE;
    memcpy(m.meta.data(), &h, sizeof(h));
    if (!body.empty()) memcpy(m.meta.data() + sizeof(h), body.data(), body.size());
    m.segs = std::move(segs);
    m.payload_bytes = pending.payload_bytes;
    window_used_ += pending.payload_bytes;
    pending_[seq] = std::move(pending);
    sendq_.push_back(std::move(m));
}

void Connection::enqueue_release(uint64_t lease) {
    std::vector<uint8_t> rbody;
    BufWriter rw(rbody);
    rw.u64(lease);
    Pending rel;
    rel.op = OP_RELEASE;
    rel.done = [](uint32_t, std::vector<uint8_t>) {};
    enqueue_msg(OP_RELEASE, std::move(rbody), {}, std::move(rel));
}

void Connection::drain_submits() {
    // Window-gated drain (reference overflow queue drained from the CQ
    // thread, libinfinistore.cpp:334-360).
    while (true) {
        Submit s;
        {
            std::lock_guard<std::mutex> lk(submit_mu_);
            if (!overflow_.empty()) {
                if (overflow_.front().window_cost + window_used_ >
                        cfg_.window_bytes &&
                    window_used_ > 0) {
                    return;  // wait for credit
                }
                s = std::move(overflow_.front());
                overflow_.pop_front();
            } else if (!submits_.empty()) {
                s = std::move(submits_.front());
                submits_.pop_front();
                if (s.window_cost + window_used_ > cfg_.window_bytes &&
                    window_used_ > 0) {
                    overflow_.push_front(std::move(s));
                    return;
                }
            } else {
                return;
            }
        }
        s.fn();
    }
}

void Connection::io_loop() {
    constexpr int kMaxEvents = 8;
    epoll_event events[kMaxEvents];
    bool want_write = false;
    while (running_.load()) {
        drain_submits();
        if (!flush_send()) {
            fail_all(INTERNAL_ERROR);
            return;
        }
        bool need_write = !sendq_.empty();
        if (need_write != want_write) {
            want_write = need_write;
            epoll_event ev{};
            ev.events = EPOLLIN | (want_write ? uint32_t(EPOLLOUT) : 0u);
            ev.data.fd = fd_;
            epoll_ctl(epoll_fd_, EPOLL_CTL_MOD, fd_, &ev);
        }
        int n = epoll_wait(epoll_fd_, events, kMaxEvents, 200);
        if (n < 0) {
            if (errno == EINTR) continue;
            fail_all(INTERNAL_ERROR);
            return;
        }
        for (int i = 0; i < n; ++i) {
            int fd = events[i].data.fd;
            if (fd == wake_fd_) {
                uint64_t v;
                ssize_t r = read(wake_fd_, &v, sizeof(v));
                (void)r;
                continue;
            }
            if (events[i].events & (EPOLLHUP | EPOLLERR)) {
                fail_all(INTERNAL_ERROR);
                return;
            }
            if (events[i].events & EPOLLIN) {
                if (!handle_readable()) {
                    fail_all(INTERNAL_ERROR);
                    return;
                }
            }
        }
    }
    // Graceful shutdown: fail anything still pending.
    fail_all(INTERNAL_ERROR);
}

bool Connection::flush_send() {
    while (!sendq_.empty()) {
        OutMsg& m = sendq_.front();
        iovec iov[64];
        int niov = 0;
        if (!m.meta_done) {
            iov[niov].iov_base = m.meta.data() + m.off;
            iov[niov].iov_len = m.meta.size() - m.off;
            niov++;
        }
        for (size_t s = m.seg_idx; s < m.segs.size() && niov < 64; ++s) {
            size_t skip = (s == m.seg_idx && m.meta_done) ? m.off : 0;
            iov[niov].iov_base = const_cast<uint8_t*>(m.segs[s].first) + skip;
            iov[niov].iov_len = m.segs[s].second - skip;
            niov++;
        }
        ssize_t w = writev(fd_, iov, niov);
        if (w < 0) {
            if (errno == EAGAIN || errno == EWOULDBLOCK) return true;
            return false;
        }
        size_t left = size_t(w);
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
            sendq_.pop_front();
        } else if (w == 0) {
            return true;
        }
    }
    return true;
}

bool Connection::handle_readable() {
    while (true) {
        // hard_fail() sets broken_ from another thread; bail before
        // starting the next message so a payload that was already queued
        // in the kernel receive buffer (SHUT_RD does not discard it) can
        // never be scattered into buffers a timed-out caller has freed.
        if (!in_payload_ && broken_.load()) return false;
        if (in_payload_) {
            // Scatter the response payload into user buffers with one readv
            // per up-to-64 destination runs (adjacent destinations merge),
            // mirroring the server's write-side scatter. Each iteration
            // holds scatter_mu_ so hard_fail can atomically retarget a
            // wedged scatter at the drain buffer (see below).
            while (rpayload_left_ > 0) {
                std::lock_guard<std::mutex> slk(scatter_mu_);
                // Same hazard mid-scatter as the pre-message broken_
                // check: once broken, dump the rest of this payload into
                // the drain buffer — every pending completes with an
                // error via fail_all, so the data is unwanted either way.
                if (broken_.load()) rscatter_.clear();
                iovec iov[64];
                int niov = 0;
                uint64_t planned = 0;
                size_t seg = rseg_, seg_off = rseg_off_;
                while (niov < 64 && seg < rscatter_.size() &&
                       planned < rpayload_left_) {
                    uint8_t* p = rscatter_[seg].first + seg_off;
                    size_t room = rscatter_[seg].second - seg_off;
                    if (room > rpayload_left_ - planned) {
                        room = size_t(rpayload_left_ - planned);
                    }
                    if (niov > 0 &&
                        static_cast<uint8_t*>(iov[niov - 1].iov_base) +
                                iov[niov - 1].iov_len == p) {
                        iov[niov - 1].iov_len += room;
                    } else {
                        iov[niov].iov_base = p;
                        iov[niov].iov_len = room;
                        niov++;
                    }
                    planned += room;
                    seg++;
                    seg_off = 0;
                }
                if (niov == 0) {  // beyond the scatter plan: drain
                    if (rdrain_.empty()) rdrain_.resize(1 << 20);
                    iov[0].iov_base = rdrain_.data();
                    iov[0].iov_len = rdrain_.size() > rpayload_left_
                                         ? size_t(rpayload_left_)
                                         : rdrain_.size();
                    niov = 1;
                }
                ssize_t r = readv(fd_, iov, niov);
                if (r == 0) return false;
                if (r < 0) {
                    if (errno == EAGAIN || errno == EWOULDBLOCK) return true;
                    return false;
                }
                rpayload_left_ -= uint64_t(r);
                size_t left = size_t(r);
                while (left > 0 && rseg_ < rscatter_.size()) {
                    size_t take = rscatter_[rseg_].second - rseg_off_;
                    if (take > left) take = left;
                    rseg_off_ += take;
                    left -= take;
                    if (rseg_off_ == rscatter_[rseg_].second) {
                        rseg_++;
                        rseg_off_ = 0;
                    }
                }
            }
            in_payload_ = false;
            // Payload complete → finish the response.
            uint32_t status = INTERNAL_ERROR;
            std::vector<uint8_t> rest;
            if (rbody_.size() >= 4) {
                BufReader br(rbody_.data(), rbody_.size());
                status = br.u32();
                rest.assign(rbody_.begin() + 4, rbody_.end());
            }
            complete(rseq_, status, std::move(rest));
            rhdr_got_ = 0;
            continue;
        }
        if (rhdr_got_ < sizeof(WireHeader)) {
            ssize_t r = recv(fd_, reinterpret_cast<uint8_t*>(&rhdr_) + rhdr_got_,
                             sizeof(WireHeader) - rhdr_got_, 0);
            if (r == 0) return false;
            if (r < 0) {
                if (errno == EAGAIN || errno == EWOULDBLOCK) return true;
                return false;
            }
            rhdr_got_ += size_t(r);
            if (rhdr_got_ < sizeof(WireHeader)) continue;
            if (!header_valid(rhdr_)) return false;
            rbody_.resize(rhdr_.body_len);
            rbody_got_ = 0;
        }
        if (rbody_got_ < rbody_.size()) {
            ssize_t r = recv(fd_, rbody_.data() + rbody_got_,
                             rbody_.size() - rbody_got_, 0);
            if (r == 0) return false;
            if (r < 0) {
                if (errno == EAGAIN || errno == EWOULDBLOCK) return true;
                return false;
            }
            rbody_got_ += size_t(r);
            if (rbody_got_ < rbody_.size()) continue;
        }
        // Full header+body.
        rseq_ = rhdr_.seq;
        if (rhdr_.payload_len > 0) {
            auto it = pending_.find(rseq_);
            rscatter_ = it != pending_.end()
                            ? it->second.scatter
                            : std::vector<std::pair<uint8_t*, size_t>>{};
            rpayload_left_ = rhdr_.payload_len;
            rseg_ = 0;
            rseg_off_ = 0;
            in_payload_ = true;
            continue;
        }
        BufReader br(rbody_.data(), rbody_.size());
        uint32_t status = rbody_.size() >= 4 ? br.u32() : INTERNAL_ERROR;
        std::vector<uint8_t> rest;
        if (rbody_.size() > 4) rest.assign(rbody_.begin() + 4, rbody_.end());
        complete(rseq_, status, std::move(rest));
        rhdr_got_ = 0;
    }
}

void Connection::complete(uint64_t seq, uint32_t status,
                          std::vector<uint8_t> body) {
    auto it = pending_.find(seq);
    if (it == pending_.end()) return;
    Pending p = std::move(it->second);
    pending_.erase(it);
    window_used_ -= p.payload_bytes;
    if (p.done) p.done(status, std::move(body));
}

void Connection::fail_all(uint32_t status) {
    broken_.store(true);
    // Complete pendings.
    std::vector<Pending> ps;
    ps.reserve(pending_.size());
    for (auto& [seq, p] : pending_) ps.push_back(std::move(p));
    pending_.clear();
    window_used_ = 0;
    for (auto& p : ps) {
        if (p.done) p.done(status, {});
    }
    // Fail queued submissions by running them — enqueue_msg sees broken_
    // and completes them with INTERNAL_ERROR immediately.
    while (true) {
        Submit s;
        {
            std::lock_guard<std::mutex> lk(submit_mu_);
            if (!overflow_.empty()) {
                s = std::move(overflow_.front());
                overflow_.pop_front();
            } else if (!submits_.empty()) {
                s = std::move(submits_.front());
                submits_.pop_front();
            } else {
                break;
            }
        }
        s.fn();
    }
    {
        // Hold sync_mu_ around store+notify so hard_fail cannot check the
        // predicate, miss this transition, and sleep its full deadline.
        std::lock_guard<std::mutex> lk(sync_mu_);
        io_exited_.store(true);
    }
    sync_cv_.notify_all();
}

}  // namespace istpu
