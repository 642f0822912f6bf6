#include "server.h"

#include <arpa/inet.h>
#include <dirent.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/eventfd.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/uio.h>
#include <time.h>
#include <unistd.h>

#include <algorithm>
#include <chrono>
#include <cstdlib>
#include <cstring>

#include "engine.h"
#include "events.h"
#include "failpoint.h"
#include "log.h"
#include "utils.h"

namespace istpu {

namespace {

// Cap on disk-tier promotions a single OP_READ/OP_PIN may trigger: tier
// IO runs synchronously on the owning worker (under the key's stripe
// lock), so a batched request over thousands of spilled keys would
// head-of-line block that worker's other connections for hundreds of ms.
// Past the cap the op fails with BUSY; promoted entries stay resident, so
// the client's retry makes monotonic progress in bounded slices.
constexpr uint64_t kMaxPromotesPerOp = 64;

// Accepts drained per readiness event (accept_ready): bounds the time
// one accept storm can hold a worker away from its established
// connections. Level-triggered readiness re-fires until the backlog is
// empty, so nothing is lost by stopping at the bound.
constexpr int kAcceptBurst = 64;

void set_nonblock(int fd) {
    int fl = fcntl(fd, F_GETFL, 0);
    fcntl(fd, F_SETFL, fl | O_NONBLOCK);
}

void tune_socket(int fd) {
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    int buf = int(SOCK_BUF_BYTES);
    setsockopt(fd, SOL_SOCKET, SO_SNDBUF, &buf, sizeof(buf));
    setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &buf, sizeof(buf));
}

uint32_t resolve_workers(uint32_t configured) {
    // ISTPU_SERVER_WORKERS overrides the config (operator escape hatch,
    // same spirit as INFINISTORE_LOG_LEVEL). Unparseable values are
    // IGNORED with a warning — a typo must not silently switch a
    // workers=1 deployment into auto multi-worker mode.
    if (const char* env = getenv("ISTPU_SERVER_WORKERS")) {
        char* end = nullptr;
        long v = strtol(env, &end, 10);
        if (end != env && *end == '\0' && v >= 0) {
            configured = uint32_t(v);  // 0 = explicit auto
        } else if (env[0] != '\0') {
            IST_WARN("ignoring unparseable ISTPU_SERVER_WORKERS='%s'", env);
        }
    }
    if (configured == 0) {
        unsigned hw = std::thread::hardware_concurrency();
        configured = hw > 2 ? (hw - 2 < 4 ? hw - 2 : 4) : 1;
    }
    if (configured < 1) configured = 1;
    if (configured > 64) configured = 64;
    return configured;
}

// Resolve the transport-engine request (ServerConfig.engine overridden
// by ISTPU_ENGINE). An unknown value falls back to auto WITH a warning
// — a typo must not silently force (or forbid) io_uring; `forced` is
// true only for an explicit "uring", which must then fail loudly when
// the probe says no.
EngineKind resolve_engine_kind(const std::string& configured,
                               bool* forced) {
    std::string want = configured;
    if (const char* env = getenv("ISTPU_ENGINE")) {
        if (env[0] != '\0') want = env;
    }
    EngineKind kind = EngineKind::kAuto;
    if (!parse_engine_kind(want, &kind)) {
        IST_WARN("ignoring unknown engine '%s' "
                 "(auto|epoll|uring|fabric); probing as auto",
                 want.c_str());
        kind = EngineKind::kAuto;
    }
    *forced = kind == EngineKind::kUring;
    return kind;
}

uint64_t env_u64(const char* name, uint64_t dflt) {
    const char* env = getenv(name);
    if (env == nullptr || env[0] == '\0') return dflt;
    char* end = nullptr;
    unsigned long long v = strtoull(env, &end, 10);
    if (end == env || *end != '\0') {
        IST_WARN("ignoring unparseable %s='%s'", name, env);
        return dflt;
    }
    return uint64_t(v);
}

bool write_text_file(const std::string& path, const std::string& body) {
    FILE* f = fopen(path.c_str(), "wb");
    if (f == nullptr) return false;
    bool ok = body.empty() ||
              fwrite(body.data(), 1, body.size(), f) == body.size();
    if (fclose(f) != 0) ok = false;
    return ok;
}

// Bundle directory naming: bundle-<%08u seq>-<kind>. Zero-padded so
// lexicographic order IS age order — the keep-last-K prune and the
// restart seq scan both lean on it.
uint64_t bundle_name_seq(const char* name) {
    if (strncmp(name, "bundle-", 7) != 0) return 0;
    return strtoull(name + 7, nullptr, 10);
}

std::vector<std::string> list_bundles(const std::string& dir) {
    std::vector<std::string> out;
    DIR* d = opendir(dir.c_str());
    if (d == nullptr) return out;
    while (struct dirent* e = readdir(d)) {
        if (strncmp(e->d_name, "bundle-", 7) == 0) {
            out.push_back(e->d_name);
        }
    }
    closedir(d);
    std::sort(out.begin(), out.end());
    return out;
}

void remove_bundle_dir(const std::string& path) {
    DIR* d = opendir(path.c_str());
    if (d != nullptr) {
        while (struct dirent* e = readdir(d)) {
            if (strcmp(e->d_name, ".") == 0 || strcmp(e->d_name, "..") == 0) {
                continue;
            }
            unlink((path + "/" + e->d_name).c_str());
        }
        closedir(d);
    }
    rmdir(path.c_str());
}

// Minimal JSON string escape for watchdog manifest details.
std::string json_escape(const std::string& in) {
    std::string out;
    out.reserve(in.size());
    for (char ch : in) {
        unsigned char c = (unsigned char)ch;
        if (c == '"' || c == '\\') {
            out += '\\';
            out += char(c);
        } else if (c >= 0x20 && c < 0x7f) {
            out += char(c);
        }
    }
    return out;
}

// Stop listening BEFORE dropping our descriptor: the uring engine's
// standing accept/poll holds its own reference to the socket until the
// kernel finishes tearing the ring down, which happens asynchronously
// after close(ring_fd) — a restart on the same port would meet the
// still-listening socket and fail with EADDRINUSE. shutdown() takes
// the socket out of LISTEN at once; SO_REUSEADDR then lets the next
// bind through whoever still references it.
void close_listener(int fd) {
    shutdown(fd, SHUT_RDWR);
    close(fd);
}

}  // namespace

Server::Server(const ServerConfig& cfg) : cfg_(cfg) {
    if (cfg_.shm_prefix.empty() && cfg_.enable_shm) {
        // pid + process-wide serial: several servers in one process (tests,
        // sharded deployments) and ephemeral ports must not collide.
        static std::atomic<uint64_t> serial{0};
        cfg_.shm_prefix = "istpu_" + std::to_string(getpid()) + "_" +
                          std::to_string(cfg_.port) + "_" +
                          std::to_string(serial.fetch_add(1));
    }
    // Tracing: compiled in, off by default; ISTPU_TRACE=1/0 overrides
    // the config (operator escape hatch, same spirit as
    // ISTPU_SERVER_WORKERS). Constructed HERE — not in start() — so
    // every control-plane entry point (stats_json on a never-started
    // server included) can rely on tracer_ being non-null, like the
    // cfg_ fields. The Tracer is always built: the stripe-lock and
    // handoff-queue wait histograms it owns are always-on stats; span
    // rings exist (and record) only when tracing is enabled.
    {
        bool trace_on = cfg_.trace;
        if (const char* env = getenv("ISTPU_TRACE")) {
            trace_on = env[0] == '1';
        }
        cfg_.trace = trace_on;
        tracer_ = std::make_unique<Tracer>(trace_on);
    }
    // Async read pipeline: ISTPU_PROMOTE=0/1 overrides the config
    // (operator escape hatch, same spirit as ISTPU_TRACE).
    if (const char* env = getenv("ISTPU_PROMOTE")) {
        cfg_.promote = env[0] == '1';
    }
}

Server::~Server() {
    stop();
    // start() may have failed after creating the ctl page but before
    // running_ flipped (stop() then early-returns): release it here.
    if (ctl_ != nullptr) {
        if (ctl_is_shm_) {
            munmap(ctl_, CTL_PAGE_BYTES);
            shm_unlink(("/" + ctl_name_).c_str());
        } else {
            delete ctl_;
        }
        ctl_ = nullptr;
    }
}

bool Server::start() {
    install_crash_handler();
    // Fault injection (failpoint.h): arm whatever ISTPU_FAILPOINTS
    // names before ANY subsystem is constructed, so even pool/tier
    // bring-up runs under the chaos spec. Runtime arming goes through
    // ist_server_fault / POST /fault.
    failpoints_arm_from_env();
    // Flight recorder (events.h): always on; ISTPU_EVENTS=0 exists
    // only for the bench overhead denominator, re-read per start so
    // an A/B bench in one process measures what it thinks it does.
    events_arm_from_env();
    // Crashed predecessors may have left multi-GB pools in /dev/shm.
    if (cfg_.enable_shm) reclaim_stale_pools();
    // Pool construction first — this is the slow, once-per-process part
    // (reference: MemoryPool ctor malloc+pin+ibv_reg_mr, mempool.cpp:13-46).
    try {
        mm_ = std::make_unique<MM>(cfg_.prealloc_bytes, cfg_.block_size,
                                   cfg_.enable_shm ? cfg_.shm_prefix : "",
                                   cfg_.auto_extend, cfg_.extend_bytes);
    } catch (const std::exception& e) {
        IST_ERROR("pool init failed: %s", e.what());
        return false;
    }
    if (cfg_.ssd_bytes > 0 && !cfg_.ssd_path.empty()) {
        std::string f = cfg_.ssd_path + "/istpu_spill_" +
                        std::to_string(getpid()) + "_" +
                        std::to_string(cfg_.port) + ".dat";
        disk_ = std::make_unique<DiskTier>(f, cfg_.ssd_bytes,
                                           cfg_.block_size);
        if (!disk_->ok()) {
            IST_WARN("disk tier unavailable, continuing without spill");
            disk_.reset();
        }
    }
    // Store-epoch control page: shared with same-host clients so their
    // pin caches validate reads with two local loads instead of an rpc.
    // Falls back to private heap memory if the shm object cannot be
    // created (epoch then travels only in responses — still correct,
    // clients just cannot take the zero-RTT cached-read path).
    if (cfg_.enable_shm) {
        ctl_name_ = cfg_.shm_prefix + "_ctl";
        std::string path = "/" + ctl_name_;
        int fd = shm_open(path.c_str(), O_CREAT | O_EXCL | O_RDWR, 0600);
        if (fd < 0 && errno == EEXIST && shm_owner_dead(ctl_name_)) {
            shm_unlink(path.c_str());
            fd = shm_open(path.c_str(), O_CREAT | O_EXCL | O_RDWR, 0600);
        }
        if (fd >= 0 && ftruncate(fd, (off_t)CTL_PAGE_BYTES) == 0) {
            void* mem = mmap(nullptr, CTL_PAGE_BYTES, PROT_READ | PROT_WRITE,
                             MAP_SHARED, fd, 0);
            if (mem != MAP_FAILED) {
                ctl_ = static_cast<CtlPage*>(mem);
                ctl_is_shm_ = true;
            }
        }
        if (fd >= 0) close(fd);
        if (!ctl_is_shm_) {
            shm_unlink(path.c_str());
            ctl_name_.clear();
            IST_WARN("ctl page shm unavailable; pin-cache epoch degrades "
                     "to response-carried only");
        }
    }
    if (ctl_ == nullptr) ctl_ = new CtlPage{};
    ctl_->magic = CTL_MAGIC;
    ctl_->epoch = 0;
    index_ = std::make_unique<KVIndex>(mm_.get(), cfg_.enable_eviction,
                                       disk_.get(), epoch_word(),
                                       tracer_.get());
    // Unified background-IO scheduler (io_sched.h): env knobs resolved
    // here and the scheduler wired into the index/promoter BEFORE the
    // background threads spawn. ISTPU_IOSCHED=0 is the bench overhead
    // denominator; ISTPU_IO_BUDGET_MBPS=0 (default) means unlimited
    // bandwidth — classes are still accounted but never wait.
    {
        bool io_on = true;
        if (const char* env = getenv("ISTPU_IOSCHED")) {
            if (env[0] != '\0') io_on = env[0] == '1';
        }
        iosched_.configure(io_on, env_u64("ISTPU_IO_BUDGET_MBPS", 0));
        iosched_autotune_ = io_on;
        if (const char* env = getenv("ISTPU_IOSCHED_AUTOTUNE")) {
            if (env[0] != '\0' && io_on) {
                iosched_autotune_ = env[0] == '1';
            }
        }
        // Knob bases seed from the configured watermarks so the first
        // controller tick adjusts from reality, not from zero.
        iosched_.set_knob(kKnobReclaimLow,
                          uint64_t(cfg_.reclaim_low * 1000.0));
        iosched_.set_knob(kKnobPromoteCap,
                          uint64_t(cfg_.reclaim_high * 1000.0));
        iosched_.set_knob(kKnobPrefetchDepth, 256);
        iosched_.set_knob(kKnobSpillBatchMult, 1);
        io_tick_prev_ = IoTickPrev{};
        index_->set_io_scheduler(&iosched_);
    }
    // Background reclaim pipeline (no-op unless eviction/spill is
    // configured and the watermarks enable it): puts should normally
    // find free blocks without ever paying reclaim inline. With a disk
    // tier, cfg_.promote also starts the async promotion worker — the
    // read-side mirror (promote.h).
    index_->start_background(cfg_.reclaim_high, cfg_.reclaim_low,
                             cfg_.promote);

    uint32_t nworkers = resolve_workers(cfg_.workers);
    cfg_.workers = nworkers;
    // Connection-scale knobs (ISSUE 18), resolved HERE — before the
    // listeners (backlog) and before engine construction (EngineFabric
    // reads fabric_ring_pool_ in init). The kernel clamps the backlog
    // to net.core.somaxconn itself; the bound below only keeps the
    // int cast sane.
    {
        uint64_t bl = env_u64("ISTPU_LISTEN_BACKLOG", uint64_t(SOMAXCONN));
        if (bl == 0) bl = uint64_t(SOMAXCONN);
        if (bl > (1u << 20)) bl = 1u << 20;
        listen_backlog_ = uint32_t(bl);
        conn_cap_ = env_u64("ISTPU_CONN_CAP", 0);
        debug_conn_cap_ = env_u64("ISTPU_DEBUG_CONN_CAP", 256);
        if (debug_conn_cap_ == 0) debug_conn_cap_ = 256;
        fabric_ring_pool_ = env_u64("ISTPU_FABRIC_RING_POOL", 64);
        if (fabric_ring_pool_ == 0) fabric_ring_pool_ = 1;
    }
    // SO_REUSEPORT acceptors: with several workers, each gets its own
    // listen socket bound to the same port so the KERNEL spreads
    // accepts and a new connection lands directly on its owning worker
    // (no worker-0 pending-queue + eventfd handoff hop). Fallback to
    // the classic single-acceptor handoff when the socket option is
    // unavailable or ISTPU_NO_REUSEPORT=1 (operator escape hatch /
    // fallback-path testing).
    bool want_reuseport = nworkers > 1;
    if (const char* env = getenv("ISTPU_NO_REUSEPORT")) {
        if (env[0] == '1') want_reuseport = false;
    }
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons(cfg_.port);
    if (inet_pton(AF_INET, cfg_.host.c_str(), &addr.sin_addr) != 1) {
        addr.sin_addr.s_addr = INADDR_ANY;
    }
    auto make_listener = [&](bool reuseport) -> int {
        int fd = socket(AF_INET, SOCK_STREAM | SOCK_CLOEXEC, 0);
        if (fd < 0) return -1;
        int one = 1;
        setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
        if (reuseport &&
            setsockopt(fd, SOL_SOCKET, SO_REUSEPORT, &one, sizeof(one)) !=
                0) {
            close(fd);
            return -1;
        }
        if (bind(fd, (sockaddr*)&addr, sizeof(addr)) != 0 ||
            listen(fd, int(listen_backlog_)) != 0) {
            close(fd);
            return -1;
        }
        set_nonblock(fd);
        return fd;
    };
    reuseport_ = false;
    if (want_reuseport) {
        listen_fd_ = make_listener(true);
        if (listen_fd_ >= 0) {
            reuseport_ = true;
        } else {
            IST_WARN("SO_REUSEPORT unavailable; falling back to "
                     "single-acceptor handoff");
        }
    }
    if (listen_fd_ < 0) listen_fd_ = make_listener(false);
    if (listen_fd_ < 0) {
        IST_ERROR("bind %s:%u failed: %s", cfg_.host.c_str(), cfg_.port,
                  strerror(errno));
        return false;
    }
    socklen_t alen = sizeof(addr);
    getsockname(listen_fd_, (sockaddr*)&addr, &alen);
    bound_port_ = ntohs(addr.sin_port);
    // Ephemeral-port case: the extra listeners must bind the SAME port
    // the first socket got.
    addr.sin_port = htons(bound_port_);

    // Transport engine (engine.h): resolved ONCE, for every worker.
    // auto = probe io_uring support (kernel/seccomp and the
    // engine.uring_setup failpoint) and fall back to epoll with one
    // log line; a forced engine=uring on an unsupported host fails
    // start() here — loudly, never mid-op.
    bool force_uring = false;
    EngineKind ekind = resolve_engine_kind(cfg_.engine, &force_uring);
    if (ekind == EngineKind::kFabric) {
        // The fabric plane needs POSIX shm for its commit rings (and
        // the engine.fabric_setup failpoint forces this probe down for
        // fallback testing anywhere). Unlike forced uring — where
        // degrading would silently change syscall behavior mid-fleet —
        // a host without shm still serves every fabric CONTROL op on
        // the auto-selected engine, so the documented contract is a
        // LOUD fallback: one warning plus the engine.fallback event,
        // and stats report the engine actually selected.
        std::string why;
        if (!fabric_runtime_supported(&why)) {
            events_emit(EV_ENGINE_FALLBACK, /*phase=fabric*/ 2, 0);
            IST_WARN("engine=fabric unavailable here (%s); falling "
                     "back to the auto selection",
                     why.c_str());
            ekind = EngineKind::kAuto;
        }
    }
    if (ekind == EngineKind::kAuto || ekind == EngineKind::kUring) {
        std::string why;
        if (uring_runtime_supported(&why)) {
            ekind = EngineKind::kUring;
        } else if (force_uring) {
            IST_ERROR("engine=uring requested but io_uring is "
                      "unavailable here: %s (use engine=auto for the "
                      "epoll fallback)",
                      why.c_str());
            close(listen_fd_);
            listen_fd_ = -1;
            return false;
        } else {
            events_emit(EV_ENGINE_FALLBACK, /*phase=probe*/ 0, 0);
            IST_INFO("engine=auto: io_uring unavailable (%s); using "
                     "epoll",
                     why.c_str());
            ekind = EngineKind::kEpoll;
        }
    }
    engine_name_ = ekind == EngineKind::kUring
                       ? "uring"
                       : (ekind == EngineKind::kFabric ? "fabric"
                                                       : "epoll");

    // Tears down the half-built worker set on an engine-init failure so
    // a failed start() leaks no fds (the caller may retry with another
    // config in the same process).
    auto teardown_workers = [&]() {
        for (auto& w : workers_) {
            if (w->engine) w->engine->shutdown();
            if (w->wake_fd >= 0) close(w->wake_fd);
            if (w->listen_fd >= 0 && w->listen_fd != listen_fd_) {
                close(w->listen_fd);
            }
        }
        workers_.clear();
        close(listen_fd_);
        listen_fd_ = -1;
    };

    workers_.clear();
    for (uint32_t i = 0; i < nworkers; ++i) {
        auto w = std::make_unique<Worker>();
        w->idx = int(i);
        if (cfg_.trace) {
            w->ring = tracer_->add_track("worker " + std::to_string(i));
        }
        w->wake_fd = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
        if (i == 0) {
            // Worker 0 watches the first listener either way.
            w->listen_fd = listen_fd_;
        } else if (reuseport_) {
            w->listen_fd = make_listener(true);
            if (w->listen_fd < 0) {
                // Mid-setup failure (port raced away?): this worker
                // simply accepts nothing; worker 0's socket still
                // serves every connection.
                IST_WARN("worker %u SO_REUSEPORT listener failed: %s", i,
                         strerror(errno));
            }
        }
        workers_.push_back(std::move(w));
    }
    // Engines second (all fds exist): if any worker's ring setup fails
    // under auto — probe passed but full init did not, e.g. a memlock
    // limit — EVERY worker drops to epoll together, so the selected
    // engine is one fact, not a per-worker lottery.
    for (uint32_t pass = 0; pass < 2; ++pass) {
        bool ok = true;
        for (auto& w : workers_) {
            w->engine = ekind == EngineKind::kUring
                            ? make_engine_uring(*this, *w)
                        : ekind == EngineKind::kFabric
                            ? make_engine_fabric(*this, *w)
                            : make_engine_epoll(*this, *w);
            if (!w->engine || !w->engine->init()) {
                ok = false;
                break;
            }
        }
        if (ok) break;
        for (auto& w : workers_) {
            if (w->engine) w->engine->shutdown();
            w->engine.reset();
        }
        if ((ekind == EngineKind::kUring && !force_uring) ||
            ekind == EngineKind::kFabric) {
            events_emit(EV_ENGINE_FALLBACK, /*phase=init*/ 1, 0);
            IST_WARN("%s engine init failed; falling back to epoll",
                     engine_name_.c_str());
            ekind = EngineKind::kEpoll;
            engine_name_ = "epoll";
            continue;  // second pass builds epoll engines
        }
        IST_ERROR("transport engine '%s' init failed", engine_name_.c_str());
        teardown_workers();
        return false;
    }

    running_.store(true);
    start_us_ = now_us();
    for (auto& w : workers_) {
        Worker* wp = w.get();
        wp->heartbeat_us.store(start_us_, std::memory_order_relaxed);
        wp->thread = std::thread([this, wp] { loop(*wp); });
    }
    // Anomaly watchdog + diagnostic bundles (server.h knobs; env
    // overrides are the operator/test escape hatch). The crash fd is
    // pre-opened NOW so a later SIGSEGV needs no allocation or path
    // resolution inside the signal handler.
    wd_enabled_ = cfg_.watchdog;
    if (const char* env = getenv("ISTPU_WATCHDOG")) {
        if (env[0] != '\0') wd_enabled_ = env[0] == '1';
    }
    bundle_dir_ = cfg_.bundle_dir;
    if (bundle_dir_.empty()) {
        // Default, not override: an explicitly configured bundle_dir
        // (tests, operators) wins; the env var exists so CI can point
        // EVERY server of a whole test job at one well-known
        // directory and upload it on failure.
        if (const char* env = getenv("ISTPU_BUNDLE_DIR")) {
            if (env[0] != '\0') bundle_dir_ = env;
        }
    }
    bundle_keep_ = cfg_.bundle_keep > 0 ? cfg_.bundle_keep : 1;
    wd_interval_us_ =
        env_u64("ISTPU_WATCHDOG_INTERVAL_MS", cfg_.watchdog_interval_ms) *
        1000;
    if (wd_interval_us_ < 10000) wd_interval_us_ = 10000;
    wd_stall_us_ = env_u64("ISTPU_WATCHDOG_STALL_US",
                           cfg_.watchdog_stall_us);
    wd_p99_us_ = env_u64("ISTPU_WATCHDOG_P99_US", cfg_.watchdog_p99_us);
    wd_cooldown_us_ =
        env_u64("ISTPU_WATCHDOG_COOLDOWN_MS", cfg_.watchdog_cooldown_ms) *
        1000;
    if (!bundle_dir_.empty()) {
        mkdir(bundle_dir_.c_str(), 0755);  // EEXIST is fine
        {
            // Pre-thread, but the seq is bundle_mu_-guarded now that
            // slo_trip can capture from the control plane.
            ScopedLock blk(bundle_mu_);
            for (const std::string& b : list_bundles(bundle_dir_)) {
                uint64_t q = bundle_name_seq(b.c_str());
                if (q > wd_bundle_seq_) wd_bundle_seq_ = q;
            }
        }
        std::string crash = bundle_dir_ + "/crash_events.bin";
        int fd = open(crash.c_str(),
                      O_CREAT | O_TRUNC | O_WRONLY | O_CLOEXEC, 0644);
        if (fd >= 0) {
            crash_fd_ = fd;
            events_set_crash_fd(fd);
        } else {
            IST_WARN("cannot open crash dump %s: %s", crash.c_str(),
                     strerror(errno));
        }
    }
    wd_stop_.store(false, std::memory_order_relaxed);
    wd_prev_ = WdPrev{};
    wd_queue_streak_ = 0;
    wd_thrash_streak_ = 0;
    // Thrash verdict threshold (premature evictions per interval,
    // from the workload profiler's ghost ring; 0 disables the kind).
    wd_thrash_ = env_u64("ISTPU_WATCHDOG_THRASH", 64);
    slo_last_trip_us_.store(0, std::memory_order_relaxed);
    // Metrics-history ring: on by default; ISTPU_HISTORY=0 (re-read
    // per start, like ISTPU_EVENTS) exists ONLY as the bench --obs-leg
    // overhead denominator. The sampler rides the watchdog thread, so
    // that thread now runs whenever history OR verdicts are wanted.
    hist_enabled_ = true;
    if (const char* env = getenv("ISTPU_HISTORY")) {
        if (env[0] != '\0') hist_enabled_ = env[0] == '1';
    }
    {
        ScopedLock hlk(hist_mu_);
        hist_ring_.clear();
        hist_ring_.reserve(kHistCap);
        hist_recorded_ = 0;
    }
    hist_prev_ = HistPrev{};
    if (hist_enabled_) {
        // Baseline sample at t=start (all counters zero): the first
        // TIMED sample then carries real deltas for the startup
        // window instead of silently swallowing it into the baseline.
        history_sample();
    }
    // The controller tick rides the watchdog thread too, so autotune
    // alone (verdicts and history both off) still gets its ~1 Hz loop.
    if (wd_enabled_ || hist_enabled_ || iosched_autotune_) {
        wd_thread_ = std::thread([this] { watchdog_loop(); });
    }
    events_emit(EV_ENGINE_SELECTED,
                ekind == EngineKind::kUring
                    ? 1
                    : (ekind == EngineKind::kFabric ? 2 : 0),
                nworkers);
    events_emit(EV_SERVER_START, bound_port_, nworkers);
    IST_INFO("server listening on %s:%u (pool %llu MB, block %llu KB, "
             "shm=%s, workers=%u, reuseport=%d, engine=%s)",
             cfg_.host.c_str(), bound_port_,
             (unsigned long long)(cfg_.prealloc_bytes >> 20),
             (unsigned long long)(cfg_.block_size >> 10),
             cfg_.enable_shm ? cfg_.shm_prefix.c_str() : "off", nworkers,
             reuseport_ ? 1 : 0, engine_name_.c_str());
    return true;
}

void Server::stop() {
    if (!running_.exchange(false)) return;
    events_emit(EV_SERVER_STOP, bound_port_, 0);
    // Watchdog first: it samples through the store getters and must
    // not race the teardown below (joined before store_mu_ is taken).
    wd_stop_.store(true, std::memory_order_relaxed);
    {
        ScopedLock lk(wd_mu_);
    }
    wd_cv_.notify_all();
    if (wd_thread_.joinable()) wd_thread_.join();
    if (crash_fd_ >= 0) {
        // Owner-checked unregister: another in-process server sharing
        // the bundle dir may have registered (and closed ours) since —
        // its live fd must survive this stop().
        events_clear_crash_fd(crash_fd_);
        crash_fd_ = -1;
    }
    for (auto& w : workers_) {
        uint64_t one = 1;
        ssize_t n = write(w->wake_fd, &one, sizeof(one));
        (void)n;
    }
    for (auto& w : workers_) {
        if (w->thread.joinable()) w->thread.join();
    }
    for (auto& w : workers_) {
        {
            // conns_mu: a concurrent /debug/state may be iterating.
            ScopedLock clk(w->conns_mu);
            for (auto& [fd, c] : w->conns) close(fd);
            w->conns.clear();
        }
        // Handed-off connections never adopted before shutdown.
        for (auto& c : w->pending) close(c->fd);
        w->pending.clear();
        // Engine resources (epoll fd / io_uring ring + registered
        // buffers + any zero-copy pins awaiting notification) go now,
        // BEFORE the store teardown below: dropped OutMsgs release
        // BlockRefs into a pool that must still exist.
        if (w->engine) w->engine->shutdown();
        if (w->wake_fd >= 0) close(w->wake_fd);
        // Per-worker SO_REUSEPORT listeners (worker 0 aliases
        // listen_fd_, closed below).
        if (w->listen_fd >= 0 && w->listen_fd != listen_fd_) {
            close_listener(w->listen_fd);
        }
    }
    if (listen_fd_ >= 0) close_listener(listen_fd_);
    listen_fd_ = -1;
    {
        // Control-plane threads may still be inside kvmap_len/stats or a
        // snapshot (whose BlockRefs deallocate into mm_); serialize
        // teardown with both. Order matters: entries reference the disk
        // tier (DiskSpan) and the pool (Block), so the index goes first.
        // workers_ clears under store_mu_ too — stats_json reads the
        // per-worker counters through it.
        ScopedLock slk(snap_mu_);
        ScopedLock lk(store_mu_);
        workers_.clear();
        // Join the reclaimer/spill threads (they reference mm_/disk_)
        // before any of those die.
        if (index_) index_->stop_background();
        index_.reset();
        disk_.reset();
        mm_.reset();
        if (ctl_ != nullptr) {
            if (ctl_is_shm_) {
                munmap(ctl_, CTL_PAGE_BYTES);
                shm_unlink(("/" + ctl_name_).c_str());
            } else {
                delete ctl_;
            }
            ctl_ = nullptr;
            ctl_is_shm_ = false;
        }
    }
}

size_t Server::kvmap_len() {
    ScopedLock lk(store_mu_);
    return index_ ? index_->size() : 0;
}

size_t Server::purge() {
    ScopedLock lk(store_mu_);
    return index_ ? index_->purge() : 0;
}

// Snapshot file layout: magic u64, version u32, count u64, then per
// entry: klen u32, key bytes, size u32, data bytes. Little-endian (the
// wire protocol's convention). The item list is collected before any
// byte is written, so the up-front count is final.
static constexpr uint64_t SNAP_MAGIC = 0x50414e5355505453ULL;  // "STPUSNAP"
static constexpr uint32_t SNAP_VERSION = 1;

long long Server::snapshot(const std::string& path, uint64_t ring_lo,
                           uint64_t ring_hi) {
    // snap_mu_ serializes concurrent snapshots (a shared tmp would let
    // two writers publish an interleaved file) and blocks stop()'s
    // teardown while the collected refs below are alive (their
    // destructors deallocate into mm_, which must still exist; the
    // deallocation itself is thread-safe against the data plane).
    ScopedLock snap_lk(snap_mu_);
    std::vector<KVIndex::SnapshotItem> items;
    {
        // store_mu_ only pins the index_ pointer against stop();
        // snapshot_items() takes the stripe locks itself and returns
        // refs, so serialization below runs without stalling the
        // data plane.
        ScopedLock lk(store_mu_);
        if (!index_) return -1;
        items = index_->snapshot_items(ring_lo, ring_hi);
    }
    std::string tmp = path + ".tmp." + std::to_string(getpid());
    FILE* f = fopen(tmp.c_str(), "wb");
    if (f == nullptr) {
        IST_WARN("snapshot: cannot open %s: %s", tmp.c_str(),
                 strerror(errno));
        return -1;
    }
    uint64_t count = uint64_t(items.size());
    fwrite(&SNAP_MAGIC, sizeof(SNAP_MAGIC), 1, f);
    fwrite(&SNAP_VERSION, sizeof(SNAP_VERSION), 1, f);
    fwrite(&count, sizeof(count), 1, f);
    std::vector<uint8_t> tmpbuf;
    bool ok = true;
    for (const auto& it : items) {
        const uint8_t* p = nullptr;
        if (it.block) {
            p = static_cast<const uint8_t*>(it.block->loc.ptr);
        } else if (it.heap) {
            p = it.heap->data();
        } else {  // disk-resident: read back through the tier (pread —
                  // safe alongside the workers' bitmap mutations)
            tmpbuf.resize(it.size);
            if (!disk_ || !disk_->load(it.disk->off, tmpbuf.data(),
                                       it.size)) {
                ok = false;
                break;
            }
            p = tmpbuf.data();
        }
        // Snapshot-class budget (io_sched.h): lowest priority — a
        // saturating snapshot must never delay a demand promote.
        // snap_mu_ (rank 10) < kRankIoSched (240): in-order acquire.
        iosched_.acquire(kIoSnapshot, it.size);
        uint32_t klen = uint32_t(it.key.size());
        fwrite(&klen, sizeof(klen), 1, f);
        fwrite(it.key.data(), 1, klen, f);
        fwrite(&it.size, sizeof(it.size), 1, f);
        fwrite(p, 1, it.size, f);
        if (ferror(f) != 0) {
            ok = false;
            break;
        }
    }
    // Crash-durable atomic replace: flush to the kernel AND the
    // device before the rename publishes the file, then persist the
    // directory entry — fclose alone only reaches the page cache.
    if (ok) ok = fflush(f) == 0 && fsync(fileno(f)) == 0;
    if (fclose(f) != 0) ok = false;
    if (!ok || rename(tmp.c_str(), path.c_str()) != 0) {
        remove(tmp.c_str());
        IST_WARN("snapshot to %s failed", path.c_str());
        return -1;
    }
    std::string dir = path;
    size_t slash = dir.find_last_of('/');
    dir = slash == std::string::npos ? "." : dir.substr(0, slash);
    int dfd = open(dir.c_str(), O_RDONLY | O_DIRECTORY);
    if (dfd >= 0) {
        fsync(dfd);
        close(dfd);
    }
    return (long long)count;
}

long long Server::restore(const std::string& path) {
    FILE* f = fopen(path.c_str(), "rb");
    if (f == nullptr) return -1;
    // File size bounds every length field below: a corrupt count/klen/
    // size cannot trigger a multi-GB resize/reserve (whose bad_alloc
    // would otherwise cross the C ABI) — anything larger than the file
    // itself is corruption by definition.
    fseek(f, 0, SEEK_END);
    long fsize_l = ftell(f);
    fseek(f, 0, SEEK_SET);
    uint64_t fsize = fsize_l > 0 ? uint64_t(fsize_l) : 0;
    uint64_t magic = 0;
    uint32_t version = 0;
    uint64_t count = 0;
    long long loaded = -1;
    if (fread(&magic, sizeof(magic), 1, f) == 1 && magic == SNAP_MAGIC &&
        fread(&version, sizeof(version), 1, f) == 1 &&
        version == SNAP_VERSION &&
        fread(&count, sizeof(count), 1, f) == 1 &&
        count <= fsize / 8) {  // each entry costs >= 8 header bytes
        loaded = 0;
        std::string key;
        std::vector<uint8_t> data;
        {
            ScopedLock lk(store_mu_);
            if (index_) index_->reserve(size_t(count));
        }
        for (uint64_t i = 0; i < count; ++i) {
            // File IO runs WITHOUT the store lock (a multi-GB restore
            // on a live server must not stall the data plane); only the
            // per-entry insert takes it.
            uint32_t klen = 0, size = 0;
            bool entry_ok =
                fread(&klen, sizeof(klen), 1, f) == 1 && klen <= fsize;
            if (entry_ok) {
                key.resize(klen);
                entry_ok = klen == 0 ||
                           fread(&key[0], 1, klen, f) == klen;
            }
            if (entry_ok) {
                entry_ok = fread(&size, sizeof(size), 1, f) == 1 &&
                           size <= fsize;
            }
            if (entry_ok) {
                data.resize(size);
                // Migration-class budget (io_sched.h): restore/adopt is
                // bulk ingest — above spill/snapshot (the cluster tier
                // wants ranges moved), below demand promote/prefetch.
                // No locks held here.
                iosched_.acquire(kIoMigration, size);
                entry_ok = size == 0 ||
                           fread(data.data(), 1, size, f) == size;
            }
            if (!entry_ok) {
                // Truncated/corrupt tail: keep the valid prefix (the
                // partial count is reported honestly — returning -1
                // here would claim total failure for a store that now
                // holds entries).
                IST_WARN("restore: corrupt snapshot tail after %lld "
                         "entries; keeping them",
                         loaded);
                break;
            }
            Status st;
            {
                ScopedLock lk(store_mu_);
                if (!index_) break;
                st = index_->insert_committed(key, data.data(), size);
            }
            if (st == OK) {
                loaded++;
            } else if (st == OUT_OF_MEMORY) {
                // Pool smaller than the snapshot: keep what fits.
                IST_WARN("restore: pool full after %lld entries",
                         loaded);
                break;
            }  // CONFLICT: live key wins, skip silently
        }
    }
    fclose(f);
    return loaded;
}

long long Server::delete_range(uint64_t ring_lo, uint64_t ring_hi) {
    ScopedLock lk(store_mu_);
    if (!index_) return -1;
    return (long long)index_->erase_range(ring_lo, ring_hi);
}

namespace {
// Wall clock for the epoch-propagation lag math: the pusher stamps
// the directory blob with ITS wall clock (pushed_at_unix_us) and the
// aggregator subtracts this shard's adoption stamp — monotonic clocks
// never compare across processes.
long long unix_us() {
    struct timespec ts;
    clock_gettime(CLOCK_REALTIME, &ts);
    return (long long)ts.tv_sec * 1000000 + ts.tv_nsec / 1000;
}
}  // namespace

int Server::cluster_set(uint64_t epoch, const std::string& dir_json,
                        long long phase, uint64_t cursor,
                        uint64_t total) {
    // The whole read-modify-write runs under cluster_mu_: two
    // concurrent pushes (ThreadingHTTPServer handles POSTs in
    // parallel threads) must never interleave the epoch check with
    // the blob store, or a stale retry racing a fresh push could roll
    // the shard's map backwards — exactly what WRONG_EPOCH promises
    // cannot happen. The scalars stay atomics only so stats/history
    // read them lock-free.
    bool bumped = false;
    uint64_t cur;
    {
        ScopedLock lk(cluster_mu_);
        cur = cluster_epoch_.load(std::memory_order_relaxed);
        if (epoch < cur) {
            // Stale push refused: count + flight-record it (the
            // epoch-propagation telemetry the aggregator scrapes — a
            // coordinator stuck re-pushing an old map shows up here,
            // not as silent retries), then the caller answers
            // WRONG_EPOCH.
            cluster_wrong_epoch_.fetch_add(1, std::memory_order_relaxed);
            events_emit(EV_CLUSTER_WRONG_EPOCH, epoch, cur);
            return -1;
        }
        if (!dir_json.empty()) cluster_dir_json_ = dir_json;
        cluster_phase_.store(phase, std::memory_order_relaxed);
        cluster_cursor_.store(cursor, std::memory_order_relaxed);
        cluster_total_.store(total, std::memory_order_relaxed);
        if (epoch > cur) {
            cluster_epoch_.store(epoch, std::memory_order_relaxed);
            cluster_adopt_unix_us_.store(unix_us(),
                                         std::memory_order_relaxed);
            bumped = true;
        }
    }
    if (bumped) {
        events_emit(EV_CLUSTER_EPOCH_BUMP, cur, epoch);
        IST_INFO("cluster: directory epoch %llu -> %llu",
                 (unsigned long long)cur, (unsigned long long)epoch);
    }
    if (phase >= 0) {
        events_emit(EV_CLUSTER_MIGRATION_PHASE, uint64_t(phase), cursor);
    }
    return 0;
}

std::string Server::cluster_json() const {
    char head[320];
    snprintf(head, sizeof(head),
             "{\"epoch\": %llu, \"migration_phase\": %lld, "
             "\"migration_cursor\": %llu, \"migration_total\": %llu, "
             "\"wrong_epoch_rejections\": %llu, "
             "\"adopt_unix_us\": %lld, "
             "\"directory\": ",
             (unsigned long long)cluster_epoch_.load(
                 std::memory_order_relaxed),
             cluster_phase_.load(std::memory_order_relaxed),
             (unsigned long long)cluster_cursor_.load(
                 std::memory_order_relaxed),
             (unsigned long long)cluster_total_.load(
                 std::memory_order_relaxed),
             (unsigned long long)cluster_wrong_epoch_.load(
                 std::memory_order_relaxed),
             cluster_adopt_unix_us_.load(std::memory_order_relaxed));
    std::string out = head;
    {
        ScopedLock lk(cluster_mu_);
        out += cluster_dir_json_.empty() ? "null" : cluster_dir_json_;
    }
    out += "}";
    return out;
}

bool Server::migration_trip(const std::string& detail, uint64_t a0,
                            uint64_t a1) {
    // Control-plane entry (the rebalance coordinator's stalled-range
    // verdict) — same CAS-cooldown shape as slo_trip, so a coordinator
    // retry loop cannot burn a bundle per poll.
    long long now = now_us();
    long long prev = migration_last_trip_us_.load(std::memory_order_relaxed);
    if (prev != 0 && now - prev < (long long)wd_cooldown_us_) {
        return false;
    }
    if (!migration_last_trip_us_.compare_exchange_strong(
            prev, now, std::memory_order_relaxed)) {
        return false;  // a concurrent coordinator call won the trip
    }
    events_emit(EV_WATCHDOG_MIGRATION, a0, a1);
    wd_trips_[kWdMigration].fetch_add(1, std::memory_order_relaxed);
    wd_last_kind_.store(int(kWdMigration), std::memory_order_relaxed);
    wd_last_trip_us_.store(now, std::memory_order_relaxed);
    IST_WARN("watchdog migration: %s", detail.c_str());
    if (!bundle_dir_.empty()) capture_bundle("migration", detail);
    return true;
}

bool Server::cluster_trip(int kind, const std::string& detail,
                          uint64_t a0, uint64_t a1) {
    // Fleet-aggregator verdicts (ISSUE 15). Per-kind CAS cooldown
    // like slo_trip/migration_trip — an aggregator scraping at 1 Hz
    // must not burn a bundle per scrape while a divergence persists.
    const bool div = kind == 0;
    std::atomic<long long>& stamp =
        div ? divergence_last_trip_us_ : epoch_lag_last_trip_us_;
    long long now = now_us();
    long long prev = stamp.load(std::memory_order_relaxed);
    if (prev != 0 && now - prev < (long long)wd_cooldown_us_) {
        return false;
    }
    if (!stamp.compare_exchange_strong(prev, now,
                                       std::memory_order_relaxed)) {
        return false;  // a concurrent aggregator call won the trip
    }
    if (div) {
        events_emit(EV_WATCHDOG_DIVERGENCE, a0, a1);
    } else {
        events_emit(EV_WATCHDOG_EPOCH_LAG, a0, a1);
    }
    WdKind wk = div ? kWdDivergence : kWdEpochLag;
    wd_trips_[wk].fetch_add(1, std::memory_order_relaxed);
    wd_last_kind_.store(int(wk), std::memory_order_relaxed);
    wd_last_trip_us_.store(now, std::memory_order_relaxed);
    IST_WARN("watchdog %s: %s",
             div ? "replica_divergence" : "epoch_lag", detail.c_str());
    if (!bundle_dir_.empty()) {
        capture_bundle(div ? "replica_divergence" : "epoch_lag", detail);
    }
    return true;
}

int Server::digest_range(uint64_t ring_lo, uint64_t ring_hi,
                         uint64_t* digest, uint64_t* count,
                         uint64_t* bytes) {
    ScopedLock lk(store_mu_);
    if (!index_) return -1;
    uint64_t d = index_->digest_range(ring_lo, ring_hi, count, bytes);
    if (digest != nullptr) *digest = d;
    return 0;
}

std::string Server::stats_json() {
    ScopedLock lk(store_mu_);
    // Transport-engine counters aggregated across workers (per-worker
    // breakdown below): SQEs submitted, zero-copy sends, payload bytes
    // moved with no bounce copy. All zero under epoll.
    uint64_t eng_sqes = 0, eng_zc = 0, eng_nocopy = 0;
    for (const auto& w : workers_) {
        eng_sqes += w->eng_sqes.load(std::memory_order_relaxed);
        eng_zc += w->eng_zc_sends.load(std::memory_order_relaxed);
        eng_nocopy += w->eng_copies_avoided.load(std::memory_order_relaxed);
    }
    char head[8192];
    snprintf(
        head, sizeof(head),
        "{\"kvmap_len\": %zu, \"inflight\": %zu, \"leases\": %zu, "
        "\"pools\": %zu, \"pool_bytes\": %zu, \"used_bytes\": %zu, "
        "\"ops\": %llu, \"bytes_in\": %llu, \"bytes_out\": %llu, "
        "\"connections\": %zu, \"workers\": %zu, \"reuseport\": %d, "
        "\"engine\": \"%s\", \"uring_sqes\": %llu, "
        "\"uring_zc_sends\": %llu, \"uring_copies_avoided\": %llu, "
        "\"fabric_attaches\": %llu, \"fabric_commit_records\": %llu, "
        "\"fabric_one_sided_puts\": %llu, \"fabric_doorbells\": %llu, "
        "\"fabric_writes\": %llu, "
        "\"fabric_ring_detaches\": %llu, "
        "\"fabric_ring_attach_denied\": %llu, "
        "\"fabric_ring_pool\": %llu, "
        "\"accepts_total\": %llu, \"conns_shed\": %llu, "
        "\"conn_buf_bytes\": %llu, \"bytes_per_conn\": %llu, "
        "\"evictions\": %llu, \"spills\": %llu, "
        "\"promotes\": %llu, \"disk_bytes\": %llu, \"disk_used\": %llu, "
        "\"reclaim_runs\": %llu, \"hard_stalls\": %llu, "
        "\"spill_queue_depth\": %llu, \"spills_cancelled\": %llu, "
        "\"promotes_async\": %llu, \"promote_queue_depth\": %llu, "
        "\"promotes_cancelled\": %llu, \"disk_reads_inline\": %llu, "
        "\"disk_io_errors\": %llu, \"tier_breaker_open\": %d, "
        "\"workers_dead\": %llu, \"failpoints_fired\": %llu, "
        "\"reclaim_heartbeat_age_us\": %lld, "
        "\"spill_heartbeat_age_us\": %lld, "
        "\"promote_heartbeat_age_us\": %lld, "
        "\"outq_bytes\": %llu, \"outq_cap\": %llu, \"reads_busy\": %llu, "
        "\"lease_bytes\": %llu, \"pins_busy\": %llu, "
        "\"lease_blocks_out\": %llu, \"leases_oom\": %llu, "
        "\"leases_busy\": %llu, \"epoch\": %llu, "
        "\"op_stats\": {",
        index_ ? index_->size() : 0, index_ ? index_->inflight() : 0,
        index_ ? index_->leases() : 0, mm_ ? mm_->num_pools() : 0,
        mm_ ? mm_->total_bytes() : 0, mm_ ? mm_->used_bytes() : 0,
        (unsigned long long)ops_.load(),
        (unsigned long long)bytes_in_.load(),
        (unsigned long long)bytes_out_.load(), size_t(n_conns_.load()),
        size_t(cfg_.workers), reuseport_ ? 1 : 0, engine_name_.c_str(),
        (unsigned long long)eng_sqes, (unsigned long long)eng_zc,
        (unsigned long long)eng_nocopy,
        (unsigned long long)fabric_attaches_.load(
            std::memory_order_relaxed),
        (unsigned long long)fabric_commit_records_.load(
            std::memory_order_relaxed),
        (unsigned long long)fabric_one_sided_puts_.load(
            std::memory_order_relaxed),
        (unsigned long long)fabric_doorbells_.load(
            std::memory_order_relaxed),
        (unsigned long long)fabric_writes_.load(
            std::memory_order_relaxed),
        (unsigned long long)fabric_ring_detaches_.load(
            std::memory_order_relaxed),
        (unsigned long long)fabric_ring_attach_denied_.load(
            std::memory_order_relaxed),
        (unsigned long long)fabric_ring_pool_,
        (unsigned long long)accepts_total_.load(std::memory_order_relaxed),
        (unsigned long long)conns_shed_.load(std::memory_order_relaxed),
        (unsigned long long)conn_buf_bytes_.load(std::memory_order_relaxed),
        (unsigned long long)(conn_buf_bytes_.load(std::memory_order_relaxed) /
                             (n_conns_.load(std::memory_order_relaxed) > 0
                                  ? n_conns_.load(std::memory_order_relaxed)
                                  : 1)),
        (unsigned long long)(index_ ? index_->evictions() : 0),
        (unsigned long long)(index_ ? index_->spills() : 0),
        (unsigned long long)(index_ ? index_->promotes() : 0),
        (unsigned long long)(disk_ ? disk_->capacity_bytes() : 0),
        (unsigned long long)(disk_ ? disk_->used_bytes() : 0),
        (unsigned long long)(index_ ? index_->reclaim_runs() : 0),
        (unsigned long long)(index_ ? index_->hard_stalls() : 0),
        (unsigned long long)(index_ ? index_->spill_queue_depth() : 0),
        (unsigned long long)(index_ ? index_->spills_cancelled() : 0),
        (unsigned long long)(index_ ? index_->promotes_async() : 0),
        (unsigned long long)(index_ ? index_->promote_queue_depth() : 0),
        (unsigned long long)(index_ ? index_->promotes_cancelled() : 0),
        (unsigned long long)(index_ ? index_->disk_reads_inline() : 0),
        (unsigned long long)(disk_ ? disk_->io_errors() : 0),
        disk_ && disk_->breaker_open() ? 1 : 0,
        (unsigned long long)(index_ ? index_->workers_dead() : 0),
        (unsigned long long)failpoints_fired_total(),
        (long long)(index_ ? index_->reclaim_heartbeat_age_us() : -1),
        (long long)(index_ ? index_->spill_heartbeat_age_us() : -1),
        (long long)(index_ ? index_->promote_heartbeat_age_us() : -1),
        (unsigned long long)outq_total_.load(std::memory_order_relaxed),
        (unsigned long long)cfg_.max_outq_bytes,
        (unsigned long long)reads_busy_.load(std::memory_order_relaxed),
        (unsigned long long)lease_total_.load(std::memory_order_relaxed),
        (unsigned long long)pins_busy_.load(std::memory_order_relaxed),
        (unsigned long long)lease_blocks_out_.load(std::memory_order_relaxed),
        (unsigned long long)leases_oom_.load(std::memory_order_relaxed),
        (unsigned long long)leases_busy_.load(std::memory_order_relaxed),
        (unsigned long long)(index_ ? index_->epoch() : 0));
    std::string out = head;
    // One LatHist as JSON: percentiles for humans, raw power-of-two
    // buckets for /metrics' true Prometheus histograms (bucket b
    // covers [2^b, 2^(b+1)) µs).
    auto hist_entry = [](const LatHist& h) {
        char tmp[160];
        snprintf(tmp, sizeof(tmp),
                 "{\"count\": %llu, \"total_us\": %llu, "
                 "\"p50_us\": %llu, \"p99_us\": %llu, \"hist\": [",
                 (unsigned long long)h.count(),
                 (unsigned long long)h.total_us(),
                 (unsigned long long)h.percentile_us(0.50),
                 (unsigned long long)h.percentile_us(0.99));
        std::string s = tmp;
        for (int b = 0; b < LatHist::kBuckets; ++b) {
            snprintf(tmp, sizeof(tmp), "%s%llu", b ? ", " : "",
                     (unsigned long long)h.bucket(b));
            s += tmp;
        }
        s += "]}";
        return s;
    };
    // Per-op handler-time table with histogram percentiles (the reference
    // logs per-op latency ad hoc, infinistore.cpp:1114,1162-1166; here it
    // is queryable).
    bool first = true;
    for (int op = 1; op < kMaxOp; ++op) {
        if (op_lat_[op].count() == 0) continue;
        out += first ? "\"" : ", \"";
        out += op_name(uint8_t(op));
        out += "\": ";
        out += hist_entry(op_lat_[op]);
        first = false;
    }
    out += "}, \"per_worker\": [";
    // Per-worker traffic (ROADMAP item): one hot connection pinning one
    // worker shows up here instead of hiding in the aggregates. Safe
    // under store_mu_ — stop() clears workers_ under the same lock.
    for (size_t i = 0; i < workers_.size(); ++i) {
        const Worker& w = *workers_[i];
        long long hb = w.heartbeat_us.load(std::memory_order_relaxed);
        char entry[384];
        snprintf(entry, sizeof(entry),
                 "%s{\"worker\": %zu, \"connections\": %u, "
                 "\"ops\": %llu, \"bytes_in\": %llu, \"bytes_out\": %llu, "
                 "\"engine\": \"%s\", \"uring_sqes\": %llu, "
                 "\"uring_zc_sends\": %llu, "
                 "\"uring_copies_avoided\": %llu, "
                 "\"heartbeat_age_us\": %lld}",
                 i ? ", " : "", i,
                 w.nconns.load(std::memory_order_relaxed),
                 (unsigned long long)w.ops.load(std::memory_order_relaxed),
                 (unsigned long long)w.bytes_in.load(
                     std::memory_order_relaxed),
                 (unsigned long long)w.bytes_out.load(
                     std::memory_order_relaxed),
                 w.engine ? w.engine->name() : "epoll",
                 (unsigned long long)w.eng_sqes.load(
                     std::memory_order_relaxed),
                 (unsigned long long)w.eng_zc_sends.load(
                     std::memory_order_relaxed),
                 (unsigned long long)w.eng_copies_avoided.load(
                     std::memory_order_relaxed),
                 hb > 0 ? now_us() - hb : -1);
        out += entry;
    }
    out += "]";
    // Always-on wait histograms (same LatHist shape as op_stats):
    // stripe-lock wait is recorded only on CONTENDED acquisitions of
    // the data-plane stripe locks; handoff-queue wait only for
    // connections that actually rode the acceptor handoff queue.
    out += ", \"wait_stats\": {\"stripe_lock_wait\": ";
    out += hist_entry(tracer_->lock_wait_hist());
    out += ", \"handoff_queue_wait\": ";
    out += hist_entry(tracer_->queue_wait_hist());
    out += "}";
    {
        // Tracing state: with tracing off, `spans` MUST stay 0 across
        // any workload (the zero-overhead contract tests pin).
        char entry[160];
        snprintf(entry, sizeof(entry),
                 ", \"trace\": {\"enabled\": %d, \"spans\": %llu, "
                 "\"dropped\": %llu, \"ring_capacity\": %zu}",
                 cfg_.trace ? 1 : 0,
                 (unsigned long long)tracer_->spans_recorded(),
                 (unsigned long long)tracer_->spans_dropped(),
                 TraceRing::kCap);
        out += entry;
    }
    {
        // Flight recorder + anomaly watchdog (events.h; docs/design.md
        // "Flight recorder & watchdog"). last_event_age_us lets /health
        // age the black box without draining it.
        long long last = events_last_us();
        static const char* kKindNames[] = {"stall", "slow_op",
                                           "queue_growth", "slo_burn",
                                           "thrash", "migration",
                                           "replica_divergence",
                                           "epoch_lag", "io_deadline"};
        int lk = wd_last_kind_.load(std::memory_order_relaxed);
        long long lt = wd_last_trip_us_.load(std::memory_order_relaxed);
        uint64_t trips = 0;
        for (int i = 0; i < kWdKinds; ++i) {
            trips += wd_trips_[i].load(std::memory_order_relaxed);
        }
        uint64_t hist_rec = 0;
        {
            ScopedLock hlk(hist_mu_);
            hist_rec = hist_recorded_;
        }
        char entry[1280];
        snprintf(
            entry, sizeof(entry),
            ", \"events\": {\"recorded\": %llu, \"overwritten\": %llu, "
            "\"enabled\": %d, \"last_event_age_us\": %lld}"
            ", \"history\": {\"enabled\": %d, \"recorded\": %llu, "
            "\"capacity\": %zu, \"interval_ms\": %llu}"
            ", \"watchdog\": {\"enabled\": %d, \"stalled\": %d, "
            "\"trips\": %llu, \"stall_trips\": %llu, "
            "\"slow_op_trips\": %llu, \"queue_trips\": %llu, "
            "\"slo_trips\": %llu, \"thrash_trips\": %llu, "
            "\"migration_trips\": %llu, "
            "\"divergence_trips\": %llu, \"epoch_lag_trips\": %llu, "
            "\"io_deadline_trips\": %llu, "
            "\"bundles\": %llu, \"last_trigger\": \"%s\", "
            "\"last_trip_age_us\": %lld}",
            (unsigned long long)events_recorded_total(),
            (unsigned long long)events_overwritten_total(),
            events_enabled() ? 1 : 0,
            last > 0 ? now_us() - last : -1, hist_enabled_ ? 1 : 0,
            (unsigned long long)hist_rec, kHistCap,
            (unsigned long long)(wd_interval_us_ / 1000),
            wd_enabled_ ? 1 : 0,
            wd_stalled_.load(std::memory_order_relaxed) ? 1 : 0,
            (unsigned long long)trips,
            (unsigned long long)wd_trips_[kWdStall].load(
                std::memory_order_relaxed),
            (unsigned long long)wd_trips_[kWdSlowOp].load(
                std::memory_order_relaxed),
            (unsigned long long)wd_trips_[kWdQueue].load(
                std::memory_order_relaxed),
            (unsigned long long)wd_trips_[kWdSlo].load(
                std::memory_order_relaxed),
            (unsigned long long)wd_trips_[kWdThrash].load(
                std::memory_order_relaxed),
            (unsigned long long)wd_trips_[kWdMigration].load(
                std::memory_order_relaxed),
            (unsigned long long)wd_trips_[kWdDivergence].load(
                std::memory_order_relaxed),
            (unsigned long long)wd_trips_[kWdEpochLag].load(
                std::memory_order_relaxed),
            (unsigned long long)wd_trips_[kWdIoDeadline].load(
                std::memory_order_relaxed),
            (unsigned long long)wd_bundles_.load(
                std::memory_order_relaxed),
            (lk >= 0 && lk < kWdKinds) ? kKindNames[lk] : "",
            lt > 0 ? now_us() - lt : -1);
        out += entry;
    }
    {
        // Background-IO scheduler (io_sched.h): one headline plus a
        // per-class breakdown in priority order. budget_tokens is
        // SIGNED — negative means deadline-expired grants put the
        // bucket into deficit.
        char head[384];
        snprintf(head, sizeof(head),
                 ", \"iosched\": {\"enabled\": %d, \"autotune\": %d, "
                 "\"budget_mbps\": %llu, \"budget_tokens\": %lld, "
                 "\"iosched_served\": %llu, "
                 "\"iosched_deadline_misses\": %llu, "
                 "\"iosched_decisions\": %llu, \"classes\": [",
                 iosched_.enabled() ? 1 : 0, iosched_autotune_ ? 1 : 0,
                 (unsigned long long)iosched_.budget_mbps(),
                 (long long)iosched_.budget_tokens(),
                 (unsigned long long)iosched_.served_total(),
                 (unsigned long long)iosched_.deadline_misses_total(),
                 (unsigned long long)iosched_.decisions());
        out += head;
        for (int c = 0; c < kIoClasses; ++c) {
            IoScheduler::ClassStats cs = iosched_.class_stats(c);
            char entry[320];
            snprintf(entry, sizeof(entry),
                     "%s{\"name\": \"%s\", \"depth\": %llu, "
                     "\"served\": %llu, \"bytes\": %llu, "
                     "\"deadline_misses\": %llu, \"max_wait_us\": %llu, "
                     "\"deadline_bound_us\": %llu}",
                     c == 0 ? "" : ", ", io_class_name(c),
                     (unsigned long long)cs.waiting,
                     (unsigned long long)cs.served,
                     (unsigned long long)cs.bytes,
                     (unsigned long long)cs.deadline_misses,
                     (unsigned long long)cs.max_wait_us,
                     (unsigned long long)iosched_.deadline_bound_us(c));
            out += entry;
        }
        out += "]}";
    }
    if (index_ != nullptr) {
        // Content-addressed dedup (docs/design.md "Content-addressed
        // dedup"): logical vs physical occupancy plus the measured
        // capacity multiplier that the workload estimator's
        // dedup_ratio_milli PREDICTION (below) is scored against.
        // dedup_wire_* count HAVE verdicts whose payload never crossed
        // the transport; dedup_hits also include commit-time adoption
        // of payload that did arrive.
        char entry[512];
        snprintf(entry, sizeof(entry),
                 ", \"dedup\": {\"enabled\": %d, "
                 "\"dedup_hits\": %llu, "
                 "\"dedup_bytes_saved\": %llu, "
                 "\"dedup_hash_hits\": %llu, "
                 "\"dedup_hash_misses\": %llu, "
                 "\"dedup_wire_hits\": %llu, "
                 "\"dedup_wire_bytes_saved\": %llu, "
                 "\"logical_bytes\": %llu, "
                 "\"dedup_saved_live\": %llu, "
                 "\"dedup_measured_milli\": %llu}",
                 index_->dedup_enabled() ? 1 : 0,
                 (unsigned long long)index_->dedup_hits(),
                 (unsigned long long)index_->dedup_bytes_saved(),
                 (unsigned long long)index_->dedup_hash_hits(),
                 (unsigned long long)index_->dedup_hash_misses(),
                 (unsigned long long)dedup_wire_hits_.load(
                     std::memory_order_relaxed),
                 (unsigned long long)dedup_wire_bytes_saved_.load(
                     std::memory_order_relaxed),
                 (unsigned long long)index_->logical_bytes(),
                 (unsigned long long)index_->dedup_saved_live(),
                 (unsigned long long)index_->dedup_measured_milli());
        out += entry;
    }
    if (index_ != nullptr) {
        // Workload headline (GET /workload has the full model): the
        // demand facts a dashboard wants next to the system gauges —
        // working-set estimate, predicted miss at the current pool,
        // eviction quality and the projected dedup multiplier.
        const WorkloadProfiler& wl = index_->workload();
        char entry[512];
        snprintf(entry, sizeof(entry),
                 ", \"workload\": {\"enabled\": %d, "
                 "\"wss_bytes\": %llu, "
                 "\"predicted_miss_1x_milli\": %llu, "
                 "\"premature_evictions\": %llu, "
                 "\"thrash_cycles\": %llu, "
                 "\"dedup_ratio_milli\": %llu, "
                 "\"accesses\": %llu, \"misses\": %llu}",
                 wl.enabled() ? 1 : 0,
                 (unsigned long long)wl.wss_bytes(),
                 (unsigned long long)wl.predicted_miss_milli(),
                 (unsigned long long)wl.premature_evictions(),
                 (unsigned long long)wl.thrash_cycles(),
                 (unsigned long long)wl.dedup_ratio_milli(),
                 (unsigned long long)wl.accesses(),
                 (unsigned long long)wl.misses());
        out += entry;
    }
    {
        // Cluster tier headline (GET /directory serves the full
        // directory blob): the epoch the dashboards correlate with
        // re-routing, plus the live migration cursor.
        char entry[320];
        snprintf(entry, sizeof(entry),
                 ", \"cluster\": {\"epoch\": %llu, "
                 "\"migration_phase\": %lld, "
                 "\"migration_cursor\": %llu, "
                 "\"migration_total\": %llu, "
                 "\"wrong_epoch_rejections\": %llu, "
                 "\"adopt_unix_us\": %lld}",
                 (unsigned long long)cluster_epoch_.load(
                     std::memory_order_relaxed),
                 cluster_phase_.load(std::memory_order_relaxed),
                 (unsigned long long)cluster_cursor_.load(
                     std::memory_order_relaxed),
                 (unsigned long long)cluster_total_.load(
                     std::memory_order_relaxed),
                 (unsigned long long)cluster_wrong_epoch_.load(
                     std::memory_order_relaxed),
                 cluster_adopt_unix_us_.load(std::memory_order_relaxed));
        out += entry;
    }
    out += "}";
    return out;
}

std::string Server::workload_json() {
    ScopedLock lk(store_mu_);
    std::string out = "{";
    if (index_ != nullptr) {
        index_->workload_json(out);
    } else {
        out += "\"enabled\": 0";
    }
    out += "}";
    return out;
}

std::string Server::trace_json() {
    // The tracer outlives stop() (member teardown order), so the drain
    // is safe against shutdown; store_mu_ only orders it with the
    // final destructor.
    ScopedLock lk(store_mu_);
    if (!tracer_) return "{\"traceEvents\": []}";
    return tracer_->to_chrome_json();
}

void Server::loop(Worker& w) {
    // Bind this thread to its span ring once; every span recorded on
    // this worker (op lifecycles, stripe-lock waits, foreground disk
    // promotions) lands there with zero lookup cost. The transport
    // engine owns the event loop itself (readiness dispatch or
    // completion reaping — engine.h); each poll() is bounded so
    // running_ is re-checked at least twice a second.
    Tracer::bind_thread(w.ring);
    events_bind_thread(("worker " + std::to_string(w.idx)).c_str());
    while (running_.load()) {
        // Heartbeat BEFORE the poll: a handler wedged inside dispatch
        // leaves a stale stamp for the watchdog's stall verdict; the
        // bounded poll itself (<= ~500 ms) keeps an idle worker fresh.
        // A WEDGED engine (unrecoverable ring failure — its poll only
        // sleeps) must NOT stay fresh: every connection on it is dead,
        // which is exactly the silent wedge the stall verdict exists
        // to name.
        if (w.engine->healthy()) {
            w.heartbeat_us.store(now_us(), std::memory_order_relaxed);
        }
        w.engine->poll();
    }
}

void Server::adopt_pending(Worker& w) {
    std::vector<std::unique_ptr<Conn>> adopted;
    {
        ScopedLock lk(w.pending_mu);
        adopted.swap(w.pending);
    }
    for (auto& c : adopted) {
        // Handoff-queue wait: enqueue (acceptor) -> adoption (here).
        // Only handed-off connections are measured — the SO_REUSEPORT
        // zero-hop path never queues, and counting its zeros would
        // bury the histogram the wait exists to expose.
        if (c->handoff_t0 != 0) {
            long long t1 = now_us();
            tracer_->queue_wait(uint64_t(c->handoff_t0),
                                uint64_t(t1 - c->handoff_t0));
            c->handoff_t0 = 0;
        }
        int fd = c->fd;
        Conn& ref = *c;
        {
            ScopedLock clk(w.conns_mu);
            w.conns[fd] = std::move(c);
        }
        w.engine->conn_added(ref);
        IST_DEBUG("worker %d adopted fd=%d", w.idx, fd);
    }
}

void Server::accept_ready(Worker& w, int ready_fd) {
    // Bounded accept burst: level-triggered epoll (and the uring
    // engine's re-armed POLL_ADD) re-fires while the backlog is
    // non-empty, so draining a bounded batch per readiness event lets
    // an accept storm interleave with established connections' IO
    // instead of head-of-line blocking this worker for the whole
    // backlog.
    for (int burst = 0; burst < kAcceptBurst; ++burst) {
        int fd = accept4(ready_fd, nullptr, nullptr,
                         SOCK_NONBLOCK | SOCK_CLOEXEC);
        if (fd < 0) return;
        adopt_accepted(w, fd);
    }
}

void Server::adopt_accepted(Worker& w, int fd) {
    accepts_total_.fetch_add(1, std::memory_order_relaxed);
    // conn.accept: a storm-time resource failure (EMFILE, allocation)
    // right after accept — the socket closes before a Conn exists, so
    // churn handling is exercisable without real fd exhaustion.
    if (IST_FAILPOINT("conn.accept")) {
        close(fd);
        return;
    }
    tune_socket(fd);
    // SO_REUSEPORT mode: the kernel already spread this connection
    // to THIS worker's socket — adopt it locally, zero cross-thread
    // hops. Fallback mode (worker 0 accepts everything): least-
    // loaded assignment by live connection count; ties go to the
    // lowest index, so workers=1 puts everything on worker 0
    // exactly like the historical single loop.
    Worker* target = &w;
    if (!reuseport_) {
        target = workers_[0].get();
        for (auto& wk : workers_) {
            if (wk->nconns.load(std::memory_order_relaxed) <
                target->nconns.load(std::memory_order_relaxed)) {
                target = wk.get();
            }
        }
    }
    // Per-worker connection cap: over-cap connects are SHED — closed
    // immediately with a WARN-severity conn.shed event and a counter —
    // instead of accepted into a worker that can no longer serve them
    // or left to time out invisibly in the listen backlog. conn.shed
    // (the failpoint) forces the same decision at any occupancy so the
    // chaos suite can exercise the shed path without 10k real fds.
    uint32_t occ = target->nconns.load(std::memory_order_relaxed);
    bool shed = conn_cap_ != 0 && occ >= conn_cap_;
    if (IST_FAILPOINT("conn.shed")) shed = true;
    if (shed) {
        uint64_t nshed =
            conns_shed_.fetch_add(1, std::memory_order_relaxed) + 1;
        events_emit(EV_CONN_SHED, uint64_t(target->idx), occ);
        // Loud but bounded: an accept storm sheds thousands — log the
        // first and every 64th (the event + counter carry the rest).
        if (nshed == 1 || nshed % 64 == 0) {
            IST_WARN(
                "shedding connection: worker %d at %u conns (cap %llu, "
                "%llu shed total)",
                target->idx, occ, (unsigned long long)conn_cap_,
                (unsigned long long)nshed);
        }
        close(fd);
        return;
    }
    auto c = std::make_unique<Conn>();
    c->fd = fd;
    c->id = next_conn_id_.fetch_add(1, std::memory_order_relaxed);
    c->w = target;
    target->nconns.fetch_add(1, std::memory_order_relaxed);
    n_conns_++;
    events_emit(EV_CONN_ACCEPT, c->id, uint64_t(target->idx));
    IST_DEBUG("accepted fd=%d -> worker %d", fd, target->idx);
    if (target == &w) {
        Conn& ref = *c;
        {
            ScopedLock clk(target->conns_mu);
            target->conns[fd] = std::move(c);
        }
        target->engine->conn_added(ref);
    } else {
        c->handoff_t0 = now_us();
        {
            ScopedLock lk(target->pending_mu);
            target->pending.push_back(std::move(c));
        }
        uint64_t one = 1;
        ssize_t r = write(target->wake_fd, &one, sizeof(one));
        (void)r;
    }
}

void Server::close_conn(Worker& w, int fd) {
    auto it = w.conns.find(fd);
    if (it == w.conns.end()) return;
    // Abort allocations this client never committed, drop any pin
    // leases it still holds, and return its block leases' unconsumed
    // blocks to the pool (a dead client's leased blocks are reclaimed
    // exactly like its uncommitted allocations). All of it goes through
    // the internally locked index/pool — safe alongside other workers.
    index_->abort_all_for_owner(it->second->id);
    // An OP_FABRIC_WRITE dying mid-payload leaves carved-but-
    // uncommitted destinations: return them like uncommitted allocs.
    free_fabric_pending(*it->second);
    for (auto& [lease, bytes] : it->second->open_leases) {
        index_->release(lease);
    }
    for (auto& [lease, bl] : it->second->block_leases) {
        free_lease_remainder(bl);
    }
    it->second->block_leases.clear();
    outq_total_.fetch_sub(it->second->outq_bytes, std::memory_order_relaxed);
    lease_total_.fetch_sub(it->second->lease_bytes, std::memory_order_relaxed);
    conn_buf_bytes_.fetch_sub(it->second->buf_accounted,
                              std::memory_order_relaxed);
    // Engine teardown before the fd closes: epoll unregisters; uring
    // cancels in-flight submissions and keeps any zero-copy pins alive
    // until their kernel notifications drain.
    w.engine->conn_closing(*it->second);
    close(fd);
    // close-with-reason: 0 = clean EOF, 1 = protocol/transport error
    // (the handler or engine marked the connection dead).
    events_emit(EV_CONN_CLOSE, it->second->id,
                it->second->dead ? 1 : 0);
    {
        ScopedLock clk(w.conns_mu);
        w.conns.erase(it);
    }
    w.nconns.fetch_sub(1, std::memory_order_relaxed);
    n_conns_--;
    IST_DEBUG("closed fd=%d", fd);
}

// ---------------------------------------------------------------------------
// Connection memory diet (ISSUE 18). Staging buffers (body + sink) are
// born empty, grow in size classes on demand (size_class_reserve), and
// are trimmed back at message completion when a bulk op left them
// oversized — so the steady-state heap cost of a connection tracks its
// CURRENT message, not the largest one it ever handled, and an idle
// connection's staging cost is zero. The aggregate gauge feeds
// bytes_per_conn in /stats and /debug/state.
// ---------------------------------------------------------------------------

// Capacity a connection may retain across messages without being
// trimmed: covers the sink's 64 KB working size and every small
// control-op body, so only genuinely bulk ops pay a re-allocation on
// their next use.
static constexpr size_t kConnBufRetain = size_t(64) << 10;

void Server::account_conn_bufs(Conn& c) {
    size_t now = c.body.capacity() + c.sink.capacity();
    if (now == c.buf_accounted) return;
    // Unsigned wraparound makes one fetch_add both directions.
    conn_buf_bytes_.fetch_add(uint64_t(now) - uint64_t(c.buf_accounted),
                              std::memory_order_relaxed);
    c.buf_accounted = now;
}

void Server::diet_conn_bufs(Conn& c) {
    if (c.body.capacity() > kConnBufRetain) {
        c.body.clear();
        c.body.shrink_to_fit();
    }
    if (c.sink.capacity() > kConnBufRetain) {
        c.sink.clear();
        c.sink.shrink_to_fit();
    }
    account_conn_bufs(c);
}

// ---------------------------------------------------------------------------
// Engine-shared RX state machine (engine.h). The epoll engine pulls
// through payload_iov/payload_advance synchronously; the io_uring
// engine submits payload_iov plans as READV/READ_FIXED SQEs and pushes
// staged header bytes through ingest_bytes. Exactly one state machine,
// two transports — the parity suite (tests/test_engine.py) pins the
// wire behavior as byte-identical.
// ---------------------------------------------------------------------------

int Server::payload_iov(Conn& c, struct iovec* iov, int max) {
    // DRAIN (malformed WRITE/PUT whose declared payload must be
    // consumed) always reads into the sink; PAYLOAD scatters into the
    // planned pool-block runs and falls back to the sink once the plan
    // is exhausted (excess payload beyond the plan).
    if (c.state == RState::PAYLOAD) {
        int niov = 0;
        uint64_t planned = 0;
        size_t seg = c.wseg, seg_off = c.wseg_off;
        while (niov < max && seg < c.wdest.size() &&
               planned < c.payload_left) {
            uint8_t* p = c.wdest[seg].first + seg_off;
            size_t room = c.wdest[seg].second - seg_off;
            if (room > c.payload_left - planned) {
                room = size_t(c.payload_left - planned);
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
        if (niov > 0) return niov;
    }
    // Sink path (DRAIN, or PAYLOAD past the plan): bounded buffer,
    // sized before any pointer capture and never resized mid-scatter.
    if (c.sink.size() < (1u << 16)) {
        c.sink.resize(1u << 16);
        account_conn_bufs(c);
    }
    iov[0].iov_base = c.sink.data();
    iov[0].iov_len = c.sink.size() > c.payload_left
                         ? size_t(c.payload_left)
                         : c.sink.size();
    return 1;
}

void Server::payload_advance(Conn& c, size_t n) {
    c.payload_left -= uint64_t(n);
    if (c.state != RState::PAYLOAD) return;  // DRAIN: nothing planned
    size_t left = n;
    while (left > 0 && c.wseg < c.wdest.size()) {
        size_t take = c.wdest[c.wseg].second - c.wseg_off;
        if (take > left) take = left;
        c.wseg_off += take;
        left -= take;
        if (c.wseg_off == c.wdest[c.wseg].second) {
            c.wseg++;
            c.wseg_off = 0;
        }
    }
}

bool Server::ingest_bytes(Conn& c, const uint8_t* p, size_t n,
                          size_t* drained) {
    while (n > 0) {
        if (c.state == RState::HDR) {
            size_t take = sizeof(WireHeader) - c.hdr_got;
            if (take > n) take = n;
            memcpy(reinterpret_cast<uint8_t*>(&c.hdr) + c.hdr_got, p,
                   take);
            c.hdr_got += take;
            p += take;
            n -= take;
            if (c.hdr_got < sizeof(WireHeader)) return true;
            if (!header_valid(c.hdr)) {
                IST_WARN("bad header from fd=%d, closing", c.fd);
                return false;
            }
            size_class_reserve(c.body, c.hdr.body_len);
            c.body.resize(c.hdr.body_len);
            account_conn_bufs(c);
            c.body_got = 0;
            c.state = RState::BODY;
            if (c.hdr.body_len == 0) {
                handle_message(c);
                if (c.dead) return false;
            }
        } else if (c.state == RState::BODY) {
            size_t take = c.body.size() - c.body_got;
            if (take > n) take = n;
            memcpy(c.body.data() + c.body_got, p, take);
            c.body_got += take;
            p += take;
            n -= take;
            if (c.body_got < c.body.size()) return true;
            handle_message(c);
            if (c.dead) return false;
        } else {
            // PAYLOAD/DRAIN bytes that already landed in a staging or
            // provided buffer: the copied slow path (bounded by the
            // engine's staging size — the engine switches to direct
            // pool reads for the remainder). Scatter through the same
            // cursor walk the direct path uses; bytes past the plan
            // (or all of DRAIN) are simply dropped, matching the sink.
            size_t take = c.payload_left < n ? size_t(c.payload_left) : n;
            size_t done = 0;
            if (c.state == RState::DRAIN && drained != nullptr) {
                *drained += take;
            }
            if (c.state == RState::PAYLOAD) {
                while (done < take && c.wseg < c.wdest.size()) {
                    size_t room = c.wdest[c.wseg].second - c.wseg_off;
                    size_t m = take - done < room ? take - done : room;
                    memcpy(c.wdest[c.wseg].first + c.wseg_off, p + done,
                           m);
                    c.wseg_off += m;
                    done += m;
                    if (c.wseg_off == c.wdest[c.wseg].second) {
                        c.wseg++;
                        c.wseg_off = 0;
                    }
                }
            }
            c.payload_left -= uint64_t(take);
            p += take;
            n -= take;
            if (c.payload_left == 0) {
                if (c.state == RState::PAYLOAD) {
                    finish_write(c);
                    if (c.dead) return false;
                } else {
                    c.state = RState::HDR;
                    c.hdr_got = 0;
                    diet_conn_bufs(c);
                }
            } else {
                return true;  // engine reads the rest directly
            }
        }
    }
    return true;
}

void Server::respond(Conn& c, uint64_t seq, uint8_t op,
                     std::vector<uint8_t> body_bytes,
                     std::vector<std::pair<const uint8_t*, size_t>> segs,
                     std::vector<BlockRef> refs,
                     std::vector<std::shared_ptr<const void>> hrefs) {
    uint64_t payload = 0;
    for (auto& s : segs) payload += s.second;
    // Merge runs of segments that are contiguous in memory (first-fit
    // allocation makes batch reads mostly sequential in the pool) so
    // flush_out's 64-iovec writev window covers far more bytes per syscall.
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
    m.meta.resize(sizeof(WireHeader) + body_bytes.size());
    WireHeader h = make_header(op, seq, uint32_t(body_bytes.size()), payload);
    memcpy(m.meta.data(), &h, sizeof(h));
    if (!body_bytes.empty()) {
        memcpy(m.meta.data() + sizeof(h), body_bytes.data(), body_bytes.size());
    }
    m.segs = std::move(segs);
    m.refs = std::move(refs);
    m.hrefs = std::move(hrefs);
    m.total = m.meta.size() + size_t(payload);
    c.outq_bytes += m.total;
    outq_total_.fetch_add(m.total, std::memory_order_relaxed);
    c.outq.push_back(std::move(m));
    // Transmission belongs to the transport engine: epoll flushes
    // opportunistically inline (and arms EPOLLOUT for the rest), uring
    // submits a send SQE. A fatal transport error surfaces as c.dead
    // and the caller's close path unwinds the pins.
    c.w->engine->output_ready(c);
}

void Server::handle_message(Conn& c) {
    // Fabric connections: drain the shm commit ring BEFORE this TCP op
    // so ring-posted commits and socket ops apply in the client's
    // submission order (an OP_LEASE_REVOKE must never overtake the
    // ring records committing out of that lease — the mirrored carve
    // cursor depends on it). One branch on a plain bool for everyone
    // else.
    if (c.fabric) {
        // `ordered` except for the doorbell op itself: the doorbell's
        // whole purpose is to trigger a drain, so it is exactly the
        // drain the fabric.doorbell failpoint simulates losing.
        c.w->engine->fabric_drain(
            c, /*ordered=*/c.hdr.op != OP_FABRIC_DOORBELL);
        if (c.dead) return;
    }
    ops_++;
    c.w->ops.fetch_add(1, std::memory_order_relaxed);
    long long t0 = now_us();
    c.op_t0 = t0;
    uint8_t op = c.hdr.op;
    c.dbg_op = op;  // deep-state mirror (hdr is not readable cross-thread)
    // FLAG_TRACE: the body's last 8 bytes are the client's trace id.
    // Strip them BEFORE any handler parses, so handlers see exactly the
    // historical body layout; old clients (flags == 0) take neither
    // branch. The id rides thread-local state so sub-spans recorded
    // inside the index (lock waits, promotions) stitch to this op.
    c.trace_id = 0;
    if ((c.hdr.flags & FLAG_TRACE) != 0 && c.body.size() >= 8) {
        memcpy(&c.trace_id, c.body.data() + c.body.size() - 8, 8);
        c.body.resize(c.body.size() - 8);
    }
    Tracer::set_thread_trace_id(c.trace_id);
    if (op == OP_PUT) {
        begin_put(c);
        return;
    }
    if (op == OP_FABRIC_WRITE) {
        begin_fabric_write(c);
        return;
    }
    // WRITE transitions to payload scatter; everything else handles inline.
    if (op == OP_WRITE) {
        BufReader r(c.body.data(), c.body.size());
        uint32_t block_size = r.u32();
        uint32_t n = r.u32();
        c.wdest.clear();
        c.wtokens.clear();
        c.wblock_size = block_size;
        bool ok = r.ok() && n <= MAX_KEYS_PER_OP &&
                  c.hdr.payload_len == uint64_t(n) * block_size;
        if (ok) {
            // Size the per-connection sink FIRST: pointers captured below
            // must stay stable for the whole payload scatter.
            if (c.sink.size() < block_size) {
                size_class_reserve(c.sink, block_size);
                c.sink.resize(block_size);
                account_conn_bufs(c);
            }
            for (uint32_t i = 0; i < n; ++i) {
                uint64_t tok = r.u64();
                c.wtokens.push_back(tok);
                uint32_t sz = 0;
                // Stripe-locked inside; the returned pointer stays valid
                // across the scatter because the inflight entry pins the
                // block and only this (worker-serialized) connection can
                // release the token.
                uint8_t* dst = index_->write_dest(tok, &sz, c.id);
                if (dst != nullptr && sz >= block_size) {
                    c.wdest.emplace_back(dst, block_size);
                } else {
                    // Unknown/purged/foreign token: payload lands in the
                    // sink (another connection's inflight block is never a
                    // write destination).
                    c.wdest.emplace_back(c.sink.data(), block_size);
                }
            }
            ok = r.ok();
        }
        if (!ok) {
            // Drain the declared payload, then answer BAD_REQUEST.
            c.payload_left = c.hdr.payload_len;
            c.state = RState::DRAIN;
            c.hdr_got = 0;
            std::vector<uint8_t> body;
            BufWriter w(body);
            w.u32(BAD_REQUEST);
            respond(c, c.hdr.seq, op, std::move(body));
            return;
        }
        c.payload_left = c.hdr.payload_len;
        c.wseg = 0;
        c.wseg_off = 0;
        // Gated clock read: the tracing-off put path must stay
        // byte-identical to before (the documented zero-overhead
        // contract), not just span-free.
        c.payload_t0 = tracer_->enabled() ? now_us() : 0;
        c.state = RState::PAYLOAD;
        if (c.payload_left == 0) finish_write(c);
        return;
    }

    switch (op) {
        case OP_HELLO: op_hello(c); break;
        case OP_ALLOCATE: op_allocate(c); break;
        case OP_LEASE: op_lease(c); break;
        case OP_COMMIT_BATCH: op_commit_batch(c); break;
        case OP_LEASE_REVOKE: op_lease_revoke(c); break;
        case OP_READ: op_read(c); break;
        case OP_COMMIT: op_commit(c); break;
        case OP_PIN: op_pin(c); break;
        case OP_RELEASE: op_release(c); break;
        case OP_PREFETCH: op_prefetch(c); break;
        case OP_PUT_HASH: op_put_hash(c); break;
        case OP_FABRIC_ATTACH: op_fabric_attach(c); break;
        case OP_FABRIC_DOORBELL: op_fabric_doorbell(c); break;
        case OP_CHECK_EXIST: op_check_exist(c); break;
        case OP_GET_MATCH_LAST_IDX: op_match(c); break;
        case OP_ABORT: op_abort(c); break;
        case OP_SYNC:
        case OP_PURGE:
        case OP_STATS:
        case OP_DELETE:
        case OP_RECLAIM: op_simple(c); break;
        default: {
            std::vector<uint8_t> body;
            BufWriter w(body);
            w.u32(BAD_REQUEST);
            respond(c, c.hdr.seq, op, std::move(body));
        }
    }
    finish_op_stats(c, op);
    c.state = RState::HDR;
    c.hdr_got = 0;
    diet_conn_bufs(c);
}

void Server::account_op(uint8_t op, long long us) {
    if (op >= kMaxOp) return;
    op_lat_[op].record(us > 0 ? uint64_t(us) : 0);
}

void Server::finish_op_stats(Conn& c, uint8_t op) {
    long long t1 = now_us();
    account_op(op, t1 - c.op_t0);
    // Whole-op span (handler time, same quantity as the histogram),
    // tagged with the client's trace id. One predicted branch when
    // tracing is off.
    tracer_->record(SPAN_OP, op, uint64_t(c.op_t0),
                    uint64_t(t1 - c.op_t0));
    Tracer::set_thread_trace_id(0);
}

void Server::begin_put(Conn& c) {
    // Body: u32 block_size, keys. Allocates on the spot; duplicate keys
    // (first-writer-wins dedup) sink their payload slice. Reference
    // analogue: the local path's one-call write with server-side
    // allocate+dedup (infinistore.cpp:732-754).
    BufReader r(c.body.data(), c.body.size());
    uint32_t block_size = r.u32();
    std::vector<std::string> keys;
    r.keys(&keys);
    bool ok = r.ok() && block_size > 0 &&
              c.hdr.payload_len == uint64_t(keys.size()) * block_size;
    c.wdest.clear();
    c.wtokens.clear();
    c.wblock_size = block_size;
    if (!ok) {
        c.payload_left = c.hdr.payload_len;
        c.state = RState::DRAIN;
        c.hdr_got = 0;
        std::vector<uint8_t> body;
        BufWriter w(body);
        w.u32(BAD_REQUEST);
        respond(c, c.hdr.seq, OP_PUT, std::move(body));
        return;
    }
    if (c.sink.size() < block_size) {
        size_class_reserve(c.sink, block_size);
        c.sink.resize(block_size);
        account_conn_bufs(c);
    }
    c.wput_oom = false;
    index_->reserve(keys.size());
    for (auto& k : keys) {
        RemoteBlock b;
        Status st = index_->allocate(k, block_size, &b, c.id);
        if (st == OK) {
            c.wtokens.push_back(b.token);
            // The scatter destination is derivable from the allocation
            // itself — no second stripe-locked lookup on the hot path.
            uint8_t* dst = mm_->pool(b.pool_idx).base() + b.offset;
            c.wdest.emplace_back(dst, block_size);
        } else {
            // Dedup (CONFLICT): sink this key's slice, first writer
            // wins. OOM: sink too, but fail the whole op below so the
            // client sees the loss (all-or-nothing like the
            // allocate+write path).
            if (st == OUT_OF_MEMORY) c.wput_oom = true;
            c.wdest.emplace_back(c.sink.data(), block_size);
        }
    }
    mm_->maybe_extend();
    c.payload_left = c.hdr.payload_len;
    c.wseg = 0;
    c.wseg_off = 0;
    c.payload_t0 = tracer_->enabled() ? now_us() : 0;
    c.state = RState::PAYLOAD;
    if (c.payload_left == 0) finish_write(c);
}

void Server::finish_write(Conn& c) {
    // OP_FABRIC_WRITE rides the same PAYLOAD scatter machinery but
    // commits through the lease-carve path, not inflight tokens.
    if (c.hdr.op == OP_FABRIC_WRITE) return finish_fabric_write(c);
    // Re-arm the thread's trace id: the payload scatter spans epoll
    // wakeups, and other connections' ops on this worker ran (and
    // cleared the TLS id) in between.
    Tracer::set_thread_trace_id(c.trace_id);
    const bool trace = tracer_->enabled();  // gates the clock reads too
    long long tcommit = trace ? now_us() : 0;
    // COPY sub-span: first payload byte -> fully scattered into pool
    // blocks (wall time, including socket waits — that IS the
    // socket->pool copy phase a tail-latency hunt needs to see).
    if (trace && c.hdr.payload_len > 0 && c.payload_t0 != 0) {
        tracer_->record(SPAN_COPY, c.hdr.op, uint64_t(c.payload_t0),
                        uint64_t(tcommit - c.payload_t0));
    }
    c.payload_t0 = 0;
    uint32_t committed = 0;
    bool fail_oom = c.hdr.op == OP_PUT && c.wput_oom;
    if (fail_oom) {
        // All-or-nothing: some keys of this PUT could not be
        // allocated, so abort the ones that could — a partial commit
        // would be invisible data loss behind an error the caller
        // might retry wholesale.
        for (uint64_t tok : c.wtokens) {
            index_->abort(tok, c.id);
        }
    } else {
        // Commit everything that landed (two-phase visibility:
        // entries become readable only now, after the bytes are in
        // the pool; each commit publishes under its key's stripe
        // lock, so the ack below orders before any reader's lookup).
        for (uint64_t tok : c.wtokens) {
            if (index_->commit(tok, c.id) == OK) committed++;
        }
    }
    // COMMIT sub-span: the two-phase publication loop alone.
    if (trace && !c.wtokens.empty()) {
        tracer_->record(SPAN_COMMIT, c.hdr.op, uint64_t(tcommit),
                        uint64_t(now_us() - tcommit),
                        uint16_t(committed > 0xFFFF ? 0xFFFF : committed));
    }
    std::vector<uint8_t> body;
    BufWriter w(body);
    w.u32(fail_oom ? OUT_OF_MEMORY : OK);
    w.u32(committed);
    respond(c, c.hdr.seq, c.hdr.op, std::move(body));
    // Handler time spans parse + allocate + payload scatter + commit
    // (op_t0 stashed when the message header was handled).
    finish_op_stats(c, c.hdr.op);
    c.state = RState::HDR;
    c.hdr_got = 0;
    diet_conn_bufs(c);
}

void Server::op_hello(Conn& c) {
    std::vector<uint8_t> body;
    BufWriter w(body);
    w.u32(OK);
    w.u32(uint32_t(mm_->block_size()));
    w.u32(cfg_.enable_shm ? 1 : 0);
    w.u32(uint32_t(mm_->num_pools()));
    for (size_t i = 0; i < mm_->num_pools(); ++i) {
        w.str(mm_->pool(i).shm_name());
        w.u64(mm_->pool(i).pool_size());
    }
    // Trailing lease-protocol fields (older readers simply stop before
    // them): the ctl shm object carrying the store epoch, if shared.
    w.u32(ctl_is_shm_ ? 1 : 0);
    w.str(ctl_name_);
    w.u64(index_->epoch());
    respond(c, c.hdr.seq, OP_HELLO, std::move(body));
}

uint64_t Server::free_lease_remainder(Conn::BlockLease& l) {
    const size_t bs = mm_->block_size();
    uint64_t freed = 0;
    for (size_t ri = l.run_idx; ri < l.runs.size(); ++ri) {
        const Conn::LeaseRun& run = l.runs[ri];
        uint32_t off_blocks = (ri == l.run_idx) ? l.block_off : 0;
        if (off_blocks >= run.nblocks) continue;
        uint32_t n = run.nblocks - off_blocks;
        PoolLoc loc;
        loc.pool_idx = run.pool_idx;
        loc.offset = run.offset + uint64_t(off_blocks) * bs;
        loc.ptr = mm_->pool(run.pool_idx).base() + loc.offset;
        mm_->deallocate(loc, size_t(n) * bs);
        freed += n;
    }
    l.run_idx = l.runs.size();
    l.block_off = 0;
    lease_blocks_out_.fetch_sub(l.blocks_left, std::memory_order_relaxed);
    l.blocks_left = 0;
    return freed;
}

void Server::op_lease(Conn& c) {
    // Body: u32 nblocks wanted (granularity = the pool block size the
    // client learned from HELLO). Grants up to nblocks as few contiguous
    // runs; a short grant (pool pressure) is OK — the client re-leases
    // when its cursor runs out. One RTT here buys the client N future
    // allocations carved locally with zero RTTs.
    BufReader r(c.body.data(), c.body.size());
    uint32_t nblocks = r.u32();
    std::vector<uint8_t> body;
    BufWriter w(body);
    if (!r.ok() || nblocks == 0 || nblocks > MAX_LEASE_BLOCKS) {
        w.u32(BAD_REQUEST);
        respond(c, c.hdr.seq, OP_LEASE, std::move(body));
        return;
    }
    // Per-connection grant backpressure, mirroring the pin-lease cap: a
    // client's granted-but-unconsumed blocks are bounded by
    // max_outq_bytes, so leasing-without-committing cannot take the
    // whole pool off the free list (server.h's "cannot pin the whole
    // pool" property extends to block leases). Requests are clamped to
    // the remaining allowance; at the cap they get BUSY — retryable
    // once the client commits or revokes.
    {
        uint64_t held = 0;
        for (const auto& [lid, bl] : c.block_leases) held += bl.blocks_left;
        uint64_t cap_blocks = cfg_.max_outq_bytes / mm_->block_size();
        if (cap_blocks == 0) cap_blocks = 1;
        if (held >= cap_blocks) {
            leases_busy_.fetch_add(1, std::memory_order_relaxed);
            w.u32(BUSY);
            respond(c, c.hdr.seq, OP_LEASE, std::move(body));
            return;
        }
        if (uint64_t(nblocks) > cap_blocks - held) {
            nblocks = uint32_t(cap_blocks - held);
        }
    }
    constexpr size_t kMaxLeaseRuns = 64;
    std::vector<Conn::LeaseRun> runs;
    uint64_t granted = 0;
    uint64_t epoch = 0;
    {
        const size_t bs = mm_->block_size();
        uint64_t want = nblocks;
        bool evicted_once = false;
        while (want > 0 && runs.size() < kMaxLeaseRuns) {
            uint64_t try_blocks = want;
            PoolLoc loc;
            bool got = false;
            while (try_blocks > 0) {
                if (mm_->allocate(size_t(try_blocks) * bs, &loc)) {
                    got = true;
                    break;
                }
                try_blocks >>= 1;
            }
            if (!got) {
                // Pool exhausted (not even one block): make room from
                // the cold end once, like op_allocate does.
                if (!evicted_once && runs.empty()) {
                    evicted_once = true;
                    if (index_->evict_lru(size_t(want) * bs) > 0) continue;
                }
                break;
            }
            runs.push_back(Conn::LeaseRun{loc.pool_idx, loc.offset,
                                          uint32_t(try_blocks)});
            granted += try_blocks;
            want -= try_blocks;
        }
        mm_->maybe_extend();
        // Lease grants consume pool blocks without passing through
        // KVIndex::allocate — run the watermark check here.
        index_->maybe_wake_reclaimer();
        epoch = index_->epoch();
        if (granted > 0) {
            uint64_t id =
                next_block_lease_.fetch_add(1, std::memory_order_relaxed);
            Conn::BlockLease& bl = c.block_leases[id];
            bl.runs = runs;
            bl.blocks_left = granted;
            lease_blocks_out_.fetch_add(granted, std::memory_order_relaxed);
            w.u32(OK);
            w.u64(id);
            w.u64(epoch);
            w.u32(uint32_t(runs.size()));
            for (const auto& run : runs) {
                w.u32(run.pool_idx);
                w.u64(run.offset);
                w.u32(run.nblocks);
            }
        }
    }
    if (granted == 0) {
        leases_oom_.fetch_add(1, std::memory_order_relaxed);
        w.u32(OUT_OF_MEMORY);
    }
    respond(c, c.hdr.seq, OP_LEASE, std::move(body));
}

void Server::op_commit_batch(Conn& c) {
    // Body: u64 lease_id, u32 block_size (payload bytes per key), keys.
    // The server carves destinations from the lease with EXACTLY the
    // client's deterministic rule (sequential, skipping run remainders
    // too small for one key), so the wire never carries offsets — a
    // client cannot point a commit at memory it was not leased. Entries
    // become visible here, after the client's one-sided writes: the
    // two-phase contract is unchanged, with the lease cursor playing
    // the role of the inflight token. The lease cursor is connection
    // state (this worker's), so only insert_leased and the pool frees
    // below touch shared state — both internally locked.
    BufReader r(c.body.data(), c.body.size());
    uint64_t lease_id = r.u64();
    uint32_t block_size = r.u32();
    std::vector<std::string> keys;
    r.keys(&keys);
    std::vector<uint8_t> body;
    BufWriter w(body);
    if (!r.ok() || block_size == 0) {
        w.u32(BAD_REQUEST);
        respond(c, c.hdr.seq, OP_COMMIT_BATCH, std::move(body));
        return;
    }
    std::vector<PoolLoc> locs;
    bool overrun = false;
    if (!carve_batch(c, lease_id, block_size, keys.size(), &locs,
                     &overrun)) {
        // Unknown, fully-consumed or revoked lease (replay): fail closed
        // — nothing is committed and no pool memory is touched.
        w.u32(CONFLICT);
        respond(c, c.hdr.seq, OP_COMMIT_BATCH, std::move(body));
        return;
    }
    commit_insert(c, c.hdr.seq, OP_COMMIT_BATCH, keys, locs, block_size,
                  overrun, /*one_sided=*/false);
}

bool Server::carve_batch(Conn& c, uint64_t lease_id,
                         uint32_t block_size, size_t nkeys,
                         std::vector<PoolLoc>* locs, bool* overrun) {
    auto lit = c.block_leases.find(lease_id);
    if (lit == c.block_leases.end()) return false;
    Conn::BlockLease& bl = lit->second;
    const size_t bs = mm_->block_size();
    const uint32_t nb = uint32_t((uint64_t(block_size) + bs - 1) / bs);
    locs->reserve(nkeys);
    *overrun = false;
    for (size_t i = 0; i < nkeys; ++i) {
        PoolLoc loc;
        if (!lease_carve(bl, nb, &loc)) {
            // More keys than the lease can hold: a mirroring client
            // never does this (it tracks the same cursor), so fail
            // closed. Destinations already carved this batch stand —
            // the caller decides whether they still commit.
            *overrun = true;
            break;
        }
        locs->push_back(loc);
    }
    if (bl.blocks_left == 0) c.block_leases.erase(lit);
    return true;
}

bool Server::lease_carve(Conn::BlockLease& bl, uint32_t nb,
                         PoolLoc* out) {
    const size_t bs = mm_->block_size();
    // Mirror carve (the client replays this exactly): skip — and free —
    // run remainders too small for one key, then consume nb blocks
    // sequentially. The wire/ring never carries offsets: this
    // deterministic replay is the only way a commit can address pool
    // memory, so a client can only ever commit into blocks it was
    // leased.
    while (bl.run_idx < bl.runs.size() &&
           bl.runs[bl.run_idx].nblocks - bl.block_off < nb) {
        uint32_t rem = bl.runs[bl.run_idx].nblocks - bl.block_off;
        if (rem > 0) {
            PoolLoc loc;
            loc.pool_idx = bl.runs[bl.run_idx].pool_idx;
            loc.offset = bl.runs[bl.run_idx].offset +
                         uint64_t(bl.block_off) * bs;
            loc.ptr = mm_->pool(loc.pool_idx).base() + loc.offset;
            mm_->deallocate(loc, size_t(rem) * bs);
            bl.blocks_left -= rem;
            lease_blocks_out_.fetch_sub(rem, std::memory_order_relaxed);
        }
        bl.run_idx++;
        bl.block_off = 0;
    }
    if (bl.run_idx >= bl.runs.size()) return false;
    const Conn::LeaseRun& run = bl.runs[bl.run_idx];
    out->pool_idx = run.pool_idx;
    out->offset = run.offset + uint64_t(bl.block_off) * bs;
    out->ptr = mm_->pool(run.pool_idx).base() + out->offset;
    bl.block_off += nb;
    bl.blocks_left -= nb;
    lease_blocks_out_.fetch_sub(nb, std::memory_order_relaxed);
    if (bl.block_off == run.nblocks) {
        bl.run_idx++;
        bl.block_off = 0;
    }
    return true;
}

void Server::commit_insert(Conn& c, uint64_t seq, uint8_t resp_op,
                           const std::vector<std::string>& keys,
                           const std::vector<PoolLoc>& locs,
                           uint32_t block_size, bool overrun,
                           bool one_sided) {
    // Injected commit-replay failure (lease.commit): the carve already
    // ran — client and server mirror the same deterministic cursor,
    // and skipping it would shift every later batch's destinations
    // onto earlier bytes (silent corruption). The carved blocks are
    // returned to the pool uncommitted: the keys never become visible,
    // and the client sees INTERNAL_ERROR in its deferred-commit error
    // latch (ist_lease_take_error) at the next sync — a VISIBLE loss,
    // never a torn or wrong payload.
    const bool inject_fail = bool(IST_FAILPOINT("lease.commit"));
    const bool trace = tracer_->enabled();  // gates the clock reads too
    long long tcommit = trace ? now_us() : 0;
    uint32_t committed = 0;
    std::vector<uint32_t> dedup;
    index_->reserve(locs.size());
    for (size_t i = 0; i < locs.size(); ++i) {
        if (inject_fail) {
            mm_->deallocate(locs[i], block_size);
            continue;
        }
        Status st = index_->insert_leased(keys[i], locs[i], block_size);
        if (st == OK) {
            committed++;
        } else {
            // First-writer-wins dedup: the existing entry stands, the
            // client's bytes in its own leased blocks are discarded
            // and the blocks return to the pool.
            mm_->deallocate(locs[i], block_size);
            dedup.push_back(uint32_t(i));
        }
    }
    uint64_t epoch = index_->epoch();
    // COMMIT sub-span: the insert_leased loop — where a deferred
    // leased put's data actually becomes visible.
    if (trace) {
        tracer_->record(SPAN_COMMIT, resp_op, uint64_t(tcommit),
                        uint64_t(now_us() - tcommit),
                        uint16_t(committed > 0xFFFF ? 0xFFFF : committed));
    }
    // The acceptance counter: keys published whose payload bytes the
    // server never read — the client placed them one-sided and the
    // commit record arrived through the shm ring.
    if (one_sided && committed > 0) {
        fabric_one_sided_puts_.fetch_add(committed,
                                         std::memory_order_relaxed);
    }
    std::vector<uint8_t> body;
    BufWriter w(body);
    w.u32(inject_fail ? INTERNAL_ERROR : (overrun ? BAD_REQUEST : OK));
    w.u32(committed);
    w.u64(epoch);
    w.u32(uint32_t(dedup.size()));
    for (uint32_t d : dedup) w.u32(d);
    respond(c, seq, resp_op, std::move(body));
}

bool Server::fabric_ingest_record(Conn& c, const uint8_t* p, size_t n,
                                  bool hash_rec) {
    // One ring-posted commit record (fabric.h): u64 client_seq,
    // u64 lease_id, u32 block_size, keys. The record IS a wire op that
    // happened to arrive through shared memory — it gets the same
    // accounting, the same carve replay and the same response shape as
    // OP_COMMIT_BATCH (the response rides the TCP control channel, so
    // sync()/error-latch semantics on the client are unchanged).
    // Ring v2 hash-first records (flag bit on the len word) are the
    // same idea for OP_PUT_HASH: a same-host dedup'd put stays
    // one-sided — probe posted through shm, verdicts on TCP — with no
    // extra RTT ahead of the payload path.
    if (hash_rec) {
        BufReader hr(p, n);
        uint64_t seq = hr.u64();
        uint32_t block_size = hr.u32();
        uint32_t nk = hr.u32();
        if (!hr.ok() || block_size == 0 || nk > MAX_KEYS_PER_OP) {
            return false;
        }
        ops_++;
        c.w->ops.fetch_add(1, std::memory_order_relaxed);
        long long t0 = now_us();
        std::vector<uint8_t> verdicts(nk, 0);
        for (uint32_t i = 0; i < nk; ++i) {
            std::string key = hr.str();
            uint64_t h1 = hr.u64();
            uint64_t h2 = hr.u64();
            if (!hr.ok()) return false;
            int v = index_->put_by_hash(key, block_size, h1, h2);
            verdicts[i] = uint8_t(v);
            if (v == 1) {
                dedup_wire_hits_.fetch_add(1, std::memory_order_relaxed);
                dedup_wire_bytes_saved_.fetch_add(
                    block_size, std::memory_order_relaxed);
            }
        }
        std::vector<uint8_t> body;
        BufWriter w(body);
        w.u32(OK);
        w.u32(nk);
        w.bytes(verdicts.data(), verdicts.size());
        respond(c, seq, OP_PUT_HASH, std::move(body));
        account_op(OP_PUT_HASH, now_us() - t0);
        return true;
    }
    BufReader r(p, n);
    uint64_t seq = r.u64();
    uint64_t lease_id = r.u64();
    uint32_t block_size = r.u32();
    std::vector<std::string> keys;
    r.keys(&keys);
    if (!r.ok() || block_size == 0) return false;
    ops_++;
    c.w->ops.fetch_add(1, std::memory_order_relaxed);
    long long t0 = now_us();
    fabric_commit_records_.fetch_add(1, std::memory_order_relaxed);
    std::vector<PoolLoc> locs;
    bool overrun = false;
    if (!carve_batch(c, lease_id, block_size, keys.size(), &locs,
                     &overrun)) {
        std::vector<uint8_t> body;
        BufWriter w(body);
        w.u32(CONFLICT);
        respond(c, seq, OP_COMMIT_BATCH, std::move(body));
        account_op(OP_COMMIT_BATCH, now_us() - t0);
        return true;
    }
    commit_insert(c, seq, OP_COMMIT_BATCH, keys, locs, block_size,
                  overrun, /*one_sided=*/true);
    account_op(OP_COMMIT_BATCH, now_us() - t0);
    return true;
}

void Server::op_fabric_attach(Conn& c) {
    // Negotiate this connection's shm commit ring. Engines without a
    // fabric plane (epoll/uring), servers without shm pools, and ring
    // setup failures all answer active=0 — the client then keeps its
    // TCP commit path silently (the same graceful shape as an SHM
    // probe failing). Status stays OK so old/fuzzing clients see a
    // well-formed response either way.
    // Optional body: u32 want_ring. A cross-host (STREAM) client
    // negotiates the OP_FABRIC_WRITE protocol with want_ring=0 — no
    // point carving a shm ring it can never map. Absent body (probe
    // from minimal clients) means "want one".
    uint32_t want_ring = 1;
    if (c.body.size() >= 4) {
        BufReader r(c.body.data(), c.body.size());
        want_ring = r.u32();
    }
    std::string name;
    uint64_t bytes = 0;
    bool was_attached = c.fabric;
    bool active = want_ring != 0 && cfg_.enable_shm &&
                  c.w->engine->fabric_attach(c, &name, &bytes);
    std::vector<uint8_t> body;
    BufWriter w(body);
    w.u32(OK);
    w.u32(active ? 1 : 0);
    w.str(name);
    w.u64(bytes);
    if (active) {
        c.fabric = true;
        if (!was_attached) {
            fabric_attaches_.fetch_add(1, std::memory_order_relaxed);
            events_emit(EV_FABRIC_ATTACH, c.id, bytes);
        }
    }
    respond(c, c.hdr.seq, OP_FABRIC_ATTACH, std::move(body));
}

void Server::op_fabric_doorbell(Conn& c) {
    // Header-only kick: the client posted a commit record after this
    // worker advertised need_kick. The pre-dispatch drain in
    // handle_message usually consumed the ring already; this drain
    // catches anything posted since. Responses for the records
    // themselves were sent by the drain — this reply only closes the
    // doorbell's own seq.
    fabric_doorbells_.fetch_add(1, std::memory_order_relaxed);
    size_t drained =
        c.fabric ? c.w->engine->fabric_drain(c, /*ordered=*/false) : 0;
    if (c.dead) return;
    std::vector<uint8_t> body;
    BufWriter w(body);
    w.u32(OK);
    w.u32(uint32_t(drained));
    respond(c, c.hdr.seq, OP_FABRIC_DOORBELL, std::move(body));
}

void Server::begin_fabric_write(Conn& c) {
    // Cross-host emulated one-sided write: {lease_id, block_size,
    // keys} + payload. The server replays the deterministic carve to
    // derive the scatter destinations (the frame carries NO offsets —
    // same forgery-proofing as OP_COMMIT_BATCH), scatters the payload
    // straight into the carved pool blocks through the shared
    // payload_iov plan (READ_FIXED under the uring engine — no bounce
    // copy, no per-byte state-machine wakeup), and commits at payload
    // end. This is the SEND_ZC-framed {pool_offset, len, payload}
    // protocol with the offset replaced by the carve replay.
    BufReader r(c.body.data(), c.body.size());
    uint64_t lease_id = r.u64();
    uint32_t block_size = r.u32();
    std::vector<std::string> keys;
    r.keys(&keys);
    c.fab_keys.clear();
    c.fab_locs.clear();
    c.wdest.clear();
    c.wtokens.clear();
    c.wblock_size = block_size;
    c.fab_bsize = block_size;
    bool ok = r.ok() && block_size > 0 &&
              c.hdr.payload_len == uint64_t(keys.size()) * block_size;
    uint32_t status = BAD_REQUEST;
    if (ok) {
        bool overrun = false;
        if (!carve_batch(c, lease_id, block_size, keys.size(),
                         &c.fab_locs, &overrun)) {
            ok = false;
            status = CONFLICT;  // unknown/consumed/revoked lease
        } else if (overrun) {
            // Overrun: a mirroring client never does this. Blocks
            // carved for THIS frame return to the pool (nothing was
            // committed yet) and the whole op fails closed.
            ok = false;
            free_fabric_pending(c);
        } else {
            for (size_t i = 0; i < keys.size(); ++i) {
                c.wdest.emplace_back(
                    static_cast<uint8_t*>(c.fab_locs[i].ptr),
                    block_size);
                c.fab_keys.push_back(std::move(keys[i]));
            }
        }
    }
    if (!ok) {
        c.wdest.clear();
        c.payload_left = c.hdr.payload_len;
        c.state = RState::DRAIN;
        c.hdr_got = 0;
        std::vector<uint8_t> body;
        BufWriter w(body);
        w.u32(status);
        respond(c, c.hdr.seq, OP_FABRIC_WRITE, std::move(body));
        return;
    }
    c.payload_left = c.hdr.payload_len;
    c.wseg = 0;
    c.wseg_off = 0;
    c.payload_t0 = tracer_->enabled() ? now_us() : 0;
    c.state = RState::PAYLOAD;
    if (c.payload_left == 0) finish_write(c);
}

void Server::finish_fabric_write(Conn& c) {
    Tracer::set_thread_trace_id(c.trace_id);
    const bool trace = tracer_->enabled();
    if (trace && c.hdr.payload_len > 0 && c.payload_t0 != 0) {
        tracer_->record(SPAN_COPY, c.hdr.op, uint64_t(c.payload_t0),
                        uint64_t(now_us() - c.payload_t0));
    }
    c.payload_t0 = 0;
    fabric_writes_.fetch_add(c.fab_keys.size(),
                             std::memory_order_relaxed);
    std::vector<std::string> keys = std::move(c.fab_keys);
    std::vector<PoolLoc> locs = std::move(c.fab_locs);
    c.fab_keys.clear();
    c.fab_locs.clear();
    commit_insert(c, c.hdr.seq, OP_FABRIC_WRITE, keys, locs,
                  c.fab_bsize, /*overrun=*/false, /*one_sided=*/false);
    finish_op_stats(c, c.hdr.op);
    c.state = RState::HDR;
    c.hdr_got = 0;
    diet_conn_bufs(c);
}

void Server::free_fabric_pending(Conn& c) {
    for (const PoolLoc& loc : c.fab_locs) {
        mm_->deallocate(loc, c.fab_bsize ? c.fab_bsize
                                         : mm_->block_size());
    }
    c.fab_locs.clear();
    c.fab_keys.clear();
}

void Server::op_lease_revoke(Conn& c) {
    BufReader r(c.body.data(), c.body.size());
    uint64_t lease_id = r.u64();
    std::vector<uint8_t> body;
    BufWriter w(body);
    if (!r.ok()) {
        w.u32(BAD_REQUEST);
        respond(c, c.hdr.seq, OP_LEASE_REVOKE, std::move(body));
        return;
    }
    auto lit = c.block_leases.find(lease_id);
    if (lit == c.block_leases.end()) {
        w.u32(CONFLICT);  // unknown/already revoked: nothing to free
        w.u64(0);
    } else {
        uint64_t freed = free_lease_remainder(lit->second);
        c.block_leases.erase(lit);
        events_emit(EV_LEASE_REVOKE, lease_id, freed);
        w.u32(OK);
        w.u64(freed);
    }
    respond(c, c.hdr.seq, OP_LEASE_REVOKE, std::move(body));
}

void Server::op_allocate(Conn& c) {
    BufReader r(c.body.data(), c.body.size());
    uint32_t block_size = r.u32();
    std::vector<std::string> keys;
    r.keys(&keys);
    std::vector<uint8_t> body;
    BufWriter w(body);
    if (!r.ok() || block_size == 0) {
        w.u32(BAD_REQUEST);
        respond(c, c.hdr.seq, OP_ALLOCATE, std::move(body));
        return;
    }
    std::vector<RemoteBlock> blocks(keys.size());
    index_->reserve(keys.size());
    for (size_t i = 0; i < keys.size(); ++i) {
        index_->allocate(keys[i], block_size, &blocks[i], c.id);
    }
    mm_->maybe_extend();
    w.u32(OK);
    w.u32(uint32_t(blocks.size()));
    w.bytes(blocks.data(), blocks.size() * sizeof(RemoteBlock));
    respond(c, c.hdr.seq, OP_ALLOCATE, std::move(body));
}

void Server::op_read(Conn& c) {
    BufReader r(c.body.data(), c.body.size());
    uint32_t block_size = r.u32();
    std::vector<std::string> keys;
    r.keys(&keys);
    std::vector<uint8_t> body;
    BufWriter w(body);
    if (!r.ok()) {
        w.u32(BAD_REQUEST);
        respond(c, c.hdr.seq, OP_READ, std::move(body));
        return;
    }
    // Cheap metadata pass first: definitive answers (missing key, size
    // mismatch) must not be masked by retryable BUSY, and a read that
    // will be refused must not pay disk promotion (or churn the cache
    // making pool room for it). Under multi-worker concurrency a key can
    // still vanish between the passes; the acquire pass below then
    // answers KEY_NOT_FOUND — the same answer a pre-op delete gives.
    for (auto& k : keys) {
        uint32_t sz = 0;
        if (!index_->peek_committed(k, &sz) || sz < block_size) {
            w.u32(KEY_NOT_FOUND);
            respond(c, c.hdr.seq, OP_READ, std::move(body));
            return;
        }
    }
    // Backpressure: refuse the whole read (retryably, before any
    // pinning or disk promotion) if it would push this connection's
    // queued bytes past the cap. A single over-cap read against an
    // empty queue is still admitted so progress is always possible;
    // the queue then being non-empty blocks further reads, so
    // per-connection pinned memory is bounded by cap + one op.
    uint64_t planned = uint64_t(keys.size()) * block_size;
    if (c.outq_bytes > 0 &&
        c.outq_bytes + planned > cfg_.max_outq_bytes) {
        reads_busy_.fetch_add(1, std::memory_order_relaxed);
        w.u32(BUSY);
        respond(c, c.hdr.seq, OP_READ, std::move(body));
        return;
    }
    std::vector<std::pair<const uint8_t*, size_t>> segs;
    std::vector<BlockRef> refs;
    std::vector<std::shared_ptr<const void>> hrefs;
    segs.reserve(keys.size());
    refs.reserve(keys.size());
    // Read pipeline ACTIVE (promotion worker running): a disk-resident
    // key is served straight from its extent — the pread runs on this
    // worker but OUTSIDE every index lock, from a queue-pinned DiskRef
    // — and promotion (second-touch policy) happens on the worker
    // thread. No pool allocation, no OOM, no promotion budget on the
    // read path at all. Pipeline OFF: the historical bounded inline
    // promotion below.
    const bool pipeline = index_->async_promote_active();
    uint64_t promoted = 0;
    for (auto& k : keys) {
        BlockRef b;
        uint32_t sz = 0;
        Status st;
        if (pipeline) {
            DiskRef d;
            std::shared_ptr<std::vector<uint8_t>> hp;
            st = index_->acquire_read(k, &b, &d, &hp, &sz);
            // Shrink revalidation (same as below): a delete + smaller
            // re-put between the passes must not leak adjacent bytes.
            if (st == OK && sz < block_size) st = KEY_NOT_FOUND;
            if (st == OK && !b) {
                const uint8_t* src = nullptr;
                std::shared_ptr<const void> own;
                if (hp) {  // limbo bytes: serve the heap ref directly
                    src = hp->data();
                    own = std::move(hp);
                } else if (d) {
                    // Disk-served cold read: only the block_size bytes
                    // the response carries are loaded, into an owned
                    // UNINITIALIZED buffer (load() overwrites exactly
                    // that span; a vector's value-init would memset
                    // the whole payload first) the OutMsg keeps alive
                    // until sent.
                    std::shared_ptr<uint8_t> buf(
                        new uint8_t[block_size],
                        std::default_delete<uint8_t[]>());
                    const bool trace = tracer_->enabled();
                    long long tio = trace ? now_us() : 0;
                    bool ok = d->tier->load(d->off, buf.get(),
                                            block_size);
                    if (trace) {
                        tracer_->record(SPAN_DISK_IO, OP_READ,
                                        uint64_t(tio),
                                        uint64_t(now_us() - tio));
                    }
                    if (!ok) {
                        st = INTERNAL_ERROR;
                    } else {
                        src = buf.get();
                        own = std::move(buf);
                    }
                } else {
                    st = INTERNAL_ERROR;  // contract guard
                }
                if (st == OK) {
                    segs.emplace_back(src, size_t(block_size));
                    hrefs.push_back(std::move(own));
                    continue;
                }
            }
        } else {
            // Bounded promotion slice per request (kMaxPromotesPerOp):
            // once the budget is spent, a non-resident entry answers
            // BUSY instead of paying more tier IO. The budget counts
            // THIS op's promotions — a global-counter delta would let
            // other workers' concurrent promotions starve this op. A
            // failed promotion surfaces as its own (retryable) status,
            // not KEY_NOT_FOUND — the data is still there.
            bool did_promote = false;
            st = index_->acquire_block(k, promoted < kMaxPromotesPerOp,
                                       &b, &sz, &did_promote);
            if (did_promote) promoted++;
            // Re-validate the size from the acquire itself: between
            // the metadata pass and here another worker may have
            // deleted K and re-put it SMALLER — gathering block_size
            // bytes from the new (shorter) block would leak adjacent
            // pool memory onto the wire.
            if (st == OK && sz < block_size) st = KEY_NOT_FOUND;
        }
        if (st == BUSY) {
            reads_busy_.fetch_add(1, std::memory_order_relaxed);
            w.u32(BUSY);
            respond(c, c.hdr.seq, OP_READ, std::move(body));
            return;
        }
        if (st != OK) {
            w.u32(st);
            respond(c, c.hdr.seq, OP_READ, std::move(body));
            return;
        }
        segs.emplace_back(static_cast<const uint8_t*>(b->loc.ptr),
                          size_t(block_size));
        refs.push_back(std::move(b));  // pin until sent
    }
    w.u32(OK);
    w.u32(uint32_t(keys.size()));
    respond(c, c.hdr.seq, OP_READ, std::move(body), std::move(segs),
            std::move(refs), std::move(hrefs));
}

void Server::op_commit(Conn& c) {
    BufReader r(c.body.data(), c.body.size());
    uint32_t n = r.u32();
    std::vector<uint8_t> body;
    BufWriter w(body);
    if (!r.ok() || n > MAX_KEYS_PER_OP) {
        w.u32(BAD_REQUEST);
        respond(c, c.hdr.seq, OP_COMMIT, std::move(body));
        return;
    }
    uint32_t committed = 0;
    for (uint32_t i = 0; i < n && r.ok(); ++i) {
        uint64_t tok = r.u64();
        if (index_->commit(tok, c.id) == OK) committed++;
    }
    w.u32(r.ok() ? OK : BAD_REQUEST);
    w.u32(committed);
    respond(c, c.hdr.seq, OP_COMMIT, std::move(body));
}

void Server::op_abort(Conn& c) {
    BufReader r(c.body.data(), c.body.size());
    uint32_t n = r.u32();
    std::vector<uint8_t> body;
    BufWriter w(body);
    if (!r.ok() || n > MAX_KEYS_PER_OP) {
        w.u32(BAD_REQUEST);
        respond(c, c.hdr.seq, OP_ABORT, std::move(body));
        return;
    }
    for (uint32_t i = 0; i < n && r.ok(); ++i) {
        uint64_t tok = r.u64();
        index_->abort(tok, c.id);
    }
    w.u32(r.ok() ? OK : BAD_REQUEST);
    respond(c, c.hdr.seq, OP_ABORT, std::move(body));
}

void Server::op_pin(Conn& c) {
    BufReader r(c.body.data(), c.body.size());
    std::vector<std::string> keys;
    r.keys(&keys);
    std::vector<uint8_t> body;
    BufWriter w(body);
    if (!r.ok()) {
        w.u32(BAD_REQUEST);
        respond(c, c.hdr.seq, OP_PIN, std::move(body));
        return;
    }
    // Backpressure, mirroring op_read: bound the bytes a connection can
    // hold pinned via leases. Metadata pre-pass so an over-cap pin is
    // refused before paying disk promotion; a single over-cap pin
    // against zero held leases is admitted (progress guarantee).
    uint64_t planned = 0;
    for (auto& k : keys) {
        uint32_t sz = 0;
        if (!index_->peek_committed(k, &sz)) {
            w.u32(KEY_NOT_FOUND);
            respond(c, c.hdr.seq, OP_PIN, std::move(body));
            return;
        }
        planned += sz;
    }
    if (c.lease_bytes > 0 &&
        c.lease_bytes + planned > cfg_.max_outq_bytes) {
        pins_busy_.fetch_add(1, std::memory_order_relaxed);
        w.u32(BUSY);
        respond(c, c.hdr.seq, OP_PIN, std::move(body));
        return;
    }
    std::vector<BlockRef> refs;
    std::vector<RemoteBlock> blocks;
    refs.reserve(keys.size());
    blocks.reserve(keys.size());
    // Read pipeline ACTIVE: a pin of a disk-resident key queues the
    // async promote and answers BUSY — the client's backoff retry
    // (lib.py _retry_busy) lands after the promotion worker adopted
    // the pool copy, so the tier IO never runs on this worker thread.
    // Pipeline OFF: the historical bounded inline promotion.
    const bool pipeline = index_->async_promote_active();
    uint64_t promoted = 0;
    for (auto& k : keys) {
        // Bounded promotion slice per request (see kMaxPromotesPerOp),
        // counting THIS op's promotions (a global-counter delta would
        // let other workers starve this op — see op_read); failed
        // promotion is a retryable status, not KEY_NOT_FOUND.
        BlockRef bref;
        uint32_t sz = 0;
        bool did_promote = false;
        Status st;
        if (pipeline) {
            st = index_->acquire_resident(k, &bref, &sz);
        } else {
            st = index_->acquire_block(k, promoted < kMaxPromotesPerOp,
                                       &bref, &sz, &did_promote);
        }
        if (did_promote) promoted++;
        if (st == BUSY) {
            pins_busy_.fetch_add(1, std::memory_order_relaxed);
            w.u32(BUSY);
            respond(c, c.hdr.seq, OP_PIN, std::move(body));
            return;
        }
        if (st != OK) {
            w.u32(st);
            respond(c, c.hdr.seq, OP_PIN, std::move(body));
            return;
        }
        RemoteBlock b;
        b.status = OK;
        b.pool_idx = bref->loc.pool_idx;
        b.token = 0;
        b.offset = bref->loc.offset;
        b.size = sz;
        blocks.push_back(b);
        refs.push_back(std::move(bref));
    }
    // The refs were gathered under their stripe locks (now released);
    // the pin itself lives under the index's lease mutex.
    uint64_t lease = index_->pin(std::move(refs));
    c.open_leases[lease] = planned;
    c.lease_bytes += planned;
    lease_total_.fetch_add(planned, std::memory_order_relaxed);
    w.u32(OK);
    w.u64(lease);
    w.u32(uint32_t(blocks.size()));
    w.bytes(blocks.data(), blocks.size() * sizeof(RemoteBlock));
    // Trailing store epoch (older readers stop before it): lets the
    // client cache these locations for future zero-RTT reads.
    w.u64(index_->epoch());
    respond(c, c.hdr.seq, OP_PIN, std::move(body));
}

void Server::op_prefetch(Conn& c) {
    // OP_PREFETCH (promote.h): kick disk→pool promotion for a key
    // batch and reply IMMEDIATELY — one status byte per key (0 missing,
    // 1 resident, 2 promotion queued, 3 on disk but not queued). The
    // promotion itself runs on the worker thread; clients treat the
    // call as fire-and-forget. Admission is bounded by pool headroom
    // inside the index, so a hostile prefetch storm cannot promote the
    // pool past the reclaim watermark.
    BufReader r(c.body.data(), c.body.size());
    std::vector<std::string> keys;
    r.keys(&keys);
    std::vector<uint8_t> body;
    BufWriter w(body);
    if (!r.ok()) {
        w.u32(BAD_REQUEST);
        respond(c, c.hdr.seq, OP_PREFETCH, std::move(body));
        return;
    }
    std::vector<uint8_t> st(keys.size(), 0);
    if (!keys.empty()) index_->prefetch(keys, st.data());
    w.u32(OK);
    w.u32(uint32_t(keys.size()));
    w.bytes(st.data(), st.size());
    respond(c, c.hdr.seq, OP_PREFETCH, std::move(body));
}

void Server::op_put_hash(Conn& c) {
    // OP_PUT_HASH (docs/design.md "Content-addressed dedup"): the
    // hash-first half of the two-phase put. Per key the index answers
    // 0 NEED (payload must follow on the normal put/lease path — no
    // reservation is made, first-writer-wins resolves probe races),
    // 1 HAVE (the key was committed HERE by pinning the block already
    // holding these bytes: zero payload transferred, zero pool bytes),
    // or 2 EXISTS (key already present). A HAVE trusts the client's
    // 128-bit hash claim — see the design.md security note.
    BufReader r(c.body.data(), c.body.size());
    uint32_t block_size = r.u32();
    uint32_t n = r.u32();
    std::vector<uint8_t> body;
    BufWriter w(body);
    if (!r.ok() || block_size == 0 || n > MAX_KEYS_PER_OP) {
        w.u32(BAD_REQUEST);
        respond(c, c.hdr.seq, OP_PUT_HASH, std::move(body));
        return;
    }
    std::vector<uint8_t> verdicts(n, 0);
    for (uint32_t i = 0; i < n; ++i) {
        std::string key = r.str();
        uint64_t h1 = r.u64();
        uint64_t h2 = r.u64();
        if (!r.ok()) {
            std::vector<uint8_t> bad;
            BufWriter bw(bad);
            bw.u32(BAD_REQUEST);
            respond(c, c.hdr.seq, OP_PUT_HASH, std::move(bad));
            return;
        }
        int v = index_->put_by_hash(key, block_size, h1, h2);
        verdicts[i] = uint8_t(v);
        if (v == 1) {
            dedup_wire_hits_.fetch_add(1, std::memory_order_relaxed);
            dedup_wire_bytes_saved_.fetch_add(block_size,
                                              std::memory_order_relaxed);
        }
    }
    w.u32(OK);
    w.u32(n);
    w.bytes(verdicts.data(), verdicts.size());
    respond(c, c.hdr.seq, OP_PUT_HASH, std::move(body));
}

void Server::op_release(Conn& c) {
    BufReader r(c.body.data(), c.body.size());
    uint64_t lease = r.u64();
    std::vector<uint8_t> body;
    BufWriter w(body);
    // Leases are releasable only by the connection that took them
    // (ids are sequential and therefore guessable; a foreign release
    // would unpin blocks out from under the owner's one-sided copy).
    auto lit = c.open_leases.find(lease);
    bool ok = false;
    if (lit != c.open_leases.end()) {
        ok = index_->release(lease);
        c.lease_bytes -= lit->second;
        lease_total_.fetch_sub(lit->second, std::memory_order_relaxed);
        c.open_leases.erase(lit);
    }
    w.u32(ok ? OK : KEY_NOT_FOUND);
    respond(c, c.hdr.seq, OP_RELEASE, std::move(body));
}

void Server::op_check_exist(Conn& c) {
    BufReader r(c.body.data(), c.body.size());
    std::string key = r.str();
    std::vector<uint8_t> body;
    BufWriter w(body);
    bool exists = r.ok() && index_->check_exist(key);
    w.u32(exists ? OK : KEY_NOT_FOUND);
    respond(c, c.hdr.seq, OP_CHECK_EXIST, std::move(body));
}

void Server::op_match(Conn& c) {
    BufReader r(c.body.data(), c.body.size());
    std::vector<std::string> keys;
    r.keys(&keys);
    std::vector<uint8_t> body;
    BufWriter w(body);
    if (!r.ok()) {
        w.u32(BAD_REQUEST);
        w.i32(-1);
    } else {
        w.u32(OK);
        w.i32(index_->match_last_index(keys));
    }
    respond(c, c.hdr.seq, OP_GET_MATCH_LAST_IDX, std::move(body));
}

void Server::op_simple(Conn& c) {
    std::vector<uint8_t> body;
    BufWriter w(body);
    switch (c.hdr.op) {
        case OP_SYNC:
            // The owning worker is serial per connection: by the time
            // SYNC is handled, every earlier op on this connection has
            // been applied (and, because writes commit under their stripe
            // lock before their ack, is visible to every worker's
            // connections). Reference analogue: sync_stream remain count
            // polling (infinistore.cpp:1070-1075).
            w.u32(OK);
            break;
        case OP_PURGE: {
            size_t n = index_->purge();
            w.u32(OK);
            w.u64(n);
            break;
        }
        case OP_STATS: {
            std::string s = stats_json();
            w.u32(OK);
            w.str(s);
            break;
        }
        case OP_DELETE:
        case OP_RECLAIM: {
            BufReader r(c.body.data(), c.body.size());
            std::vector<std::string> keys;
            r.keys(&keys);
            size_t n = 0;
            if (r.ok()) {
                n = c.hdr.op == OP_DELETE ? index_->erase(keys)
                                          : index_->reclaim_orphans(keys);
            }
            w.u32(r.ok() ? OK : BAD_REQUEST);
            w.u64(n);
            break;
        }
    }
    respond(c, c.hdr.seq, c.hdr.op, std::move(body));
}


// ---------------------------------------------------------------------------
// Deep-state introspection (GET /debug/state). Everything here reads
// relaxed mirrors (RelaxedCell, atomic gauges) or takes short
// per-structure locks one at a time — the data plane never waits on a
// debugger-shaped consumer.
// ---------------------------------------------------------------------------

std::string Server::debug_state_json() {
    ScopedLock lk(store_mu_);
    std::string out = "{";
    char buf[512];
    snprintf(buf, sizeof(buf),
             "\"engine\": \"%s\", \"workers\": %zu, "
             "\"uptime_us\": %lld, \"connections\": [",
             engine_name_.c_str(), workers_.size(),
             start_us_ > 0 ? now_us() - start_us_ : 0);
    out += buf;
    // Per-conn rows are capped at ISTPU_DEBUG_CONN_CAP (ISSUE 18): at
    // 10k connections an uncapped snapshot is megabytes of JSON and
    // O(conns) string work on the control plane — past the cap the
    // remainder is SUMMARIZED (count + aggregate cursors), keeping the
    // observability cost O(cap) while losing no aggregate signal.
    bool first = true;
    uint64_t listed = 0, omitted = 0;
    uint64_t om_outq = 0, om_lease = 0, om_payload = 0;
    for (const auto& w : workers_) {
        ScopedLock clk(w->conns_mu);
        for (const auto& [fd, c] : w->conns) {
            if (listed >= debug_conn_cap_) {
                omitted++;
                om_outq += uint64_t(c->outq_bytes);
                om_lease += uint64_t(c->lease_bytes);
                om_payload += uint64_t(c->payload_left);
                continue;
            }
            const char* phase = "hdr";
            switch (RState(c->state)) {
                case RState::HDR: phase = "hdr"; break;
                case RState::BODY: phase = "body"; break;
                case RState::PAYLOAD: phase = "payload"; break;
                case RState::DRAIN: phase = "drain"; break;
            }
            uint8_t op = uint8_t(c->dbg_op);
            snprintf(buf, sizeof(buf),
                     "%s{\"id\": %llu, \"fd\": %d, \"worker\": %d, "
                     "\"phase\": \"%s\", \"op\": \"%s\", "
                     "\"payload_left\": %llu, \"outq_bytes\": %llu, "
                     "\"lease_bytes\": %llu}",
                     first ? "" : ", ", (unsigned long long)c->id, fd,
                     w->idx, phase, op != 0 ? op_name(op) : "-",
                     (unsigned long long)uint64_t(c->payload_left),
                     (unsigned long long)uint64_t(c->outq_bytes),
                     (unsigned long long)uint64_t(c->lease_bytes));
            out += buf;
            first = false;
            listed++;
        }
    }
    uint64_t cbb = conn_buf_bytes_.load(std::memory_order_relaxed);
    uint64_t nc = n_conns_.load(std::memory_order_relaxed);
    snprintf(buf, sizeof(buf),
             "], \"connections_listed\": %llu, "
             "\"connections_omitted\": %llu, "
             "\"omitted\": {\"outq_bytes\": %llu, \"lease_bytes\": %llu, "
             "\"payload_left\": %llu}, "
             "\"conn_cap\": %llu, \"debug_conn_cap\": %llu, "
             "\"conn_buf_bytes\": %llu, \"bytes_per_conn\": %llu, "
             "\"worker_state\": [",
             (unsigned long long)listed, (unsigned long long)omitted,
             (unsigned long long)om_outq, (unsigned long long)om_lease,
             (unsigned long long)om_payload,
             (unsigned long long)conn_cap_,
             (unsigned long long)debug_conn_cap_,
             (unsigned long long)cbb,
             (unsigned long long)(cbb / (nc > 0 ? nc : 1)));
    out += buf;
    for (size_t i = 0; i < workers_.size(); ++i) {
        Worker& w = *workers_[i];
        size_t pending = 0;
        {
            ScopedLock plk(w.pending_mu);
            pending = w.pending.size();
        }
        long long hb = w.heartbeat_us.load(std::memory_order_relaxed);
        snprintf(buf, sizeof(buf),
                 "%s{\"worker\": %zu, \"engine\": \"%s\", "
                 "\"connections\": %u, \"pending\": %zu, "
                 "\"heartbeat_age_us\": %lld, "
                 "\"uring_inflight_slots\": %zu}",
                 i ? ", " : "", i, w.engine ? w.engine->name() : "epoll",
                 w.nconns.load(std::memory_order_relaxed), pending,
                 hb > 0 ? now_us() - hb : -1,
                 w.engine ? w.engine->inflight_slots() : 0);
        out += buf;
    }
    out += "], ";
    if (index_ != nullptr) {
        index_->debug_json(out);
    } else {
        out += "\"stripes\": []";
    }
    out += ", ";
    if (mm_ != nullptr) {
        mm_->debug_json(out);
    } else {
        out += "\"pools\": []";
    }
    snprintf(buf, sizeof(buf),
             ", \"disk\": {\"bytes\": %llu, \"used_bytes\": %llu, "
             "\"io_errors\": %llu, \"breaker_open\": %d}",
             (unsigned long long)(disk_ ? disk_->capacity_bytes() : 0),
             (unsigned long long)(disk_ ? disk_->used_bytes() : 0),
             (unsigned long long)(disk_ ? disk_->io_errors() : 0),
             disk_ && disk_->breaker_open() ? 1 : 0);
    out += buf;
    out += "}";
    return out;
}

// ---------------------------------------------------------------------------
// Anomaly watchdog. One native thread, one sample per interval; the
// verdicts and their thresholds are deliberately simple — the value is
// the BUNDLE captured at the moment of anomaly, not a clever detector.
// ---------------------------------------------------------------------------

void Server::watchdog_loop() {
    events_bind_thread("watchdog");
    UniqueLock lk(wd_mu_);
    while (!wd_stop_.load(std::memory_order_relaxed)) {
        wd_cv_.wait_for(lk, std::chrono::microseconds(wd_interval_us_),
                        [this] {
                            return wd_stop_.load(
                                std::memory_order_relaxed);
                        });
        if (wd_stop_.load(std::memory_order_relaxed)) break;
        // Sample OUTSIDE wd_mu_ (rank 15): the getters below take
        // store_mu_ (rank 20) and the per-structure locks themselves.
        // History first, so a verdict's bundle capture already sees
        // the tick's sample in history.json.
        lk.unlock();
        if (hist_enabled_) history_sample();
        if (wd_enabled_) watchdog_sample();
        // Closed loop LAST: the controller consumes the tick's fresh
        // history deltas and verdict state when retuning the knobs.
        if (iosched_autotune_ && iosched_.enabled()) iosched_tick();
        lk.lock();
    }
}

void Server::history_sample() {
    HistSample s;
    s.t_us = now_us();
    {
        ScopedLock lk(store_mu_);  // pins index_/mm_/workers_ vs stop()
        s.used_bytes = mm_ ? mm_->used_bytes() : 0;
        s.pool_bytes = mm_ ? mm_->total_bytes() : 0;
        s.kvmap = index_ ? index_->size() : 0;
        s.conns = n_conns_.load(std::memory_order_relaxed);
        if (index_ != nullptr) {
            s.spill_q = index_->spill_queue_depth();
            s.promote_q = index_->promote_queue_depth();
            s.workers_dead = uint32_t(index_->workers_dead());
        }
        s.breaker = disk_ && disk_->breaker_open() ? 1 : 0;
        uint64_t sqes = 0;
        for (const auto& w : workers_) {
            sqes += w->eng_sqes.load(std::memory_order_relaxed);
        }
        // Cumulative counters → deltas against the sampler's memory.
        uint64_t ops = ops_.load(std::memory_order_relaxed);
        uint64_t bin = bytes_in_.load(std::memory_order_relaxed);
        uint64_t bout = bytes_out_.load(std::memory_order_relaxed);
        uint64_t busy = reads_busy_.load(std::memory_order_relaxed);
        uint64_t ioerr = disk_ ? disk_->io_errors() : 0;
        uint64_t hs = index_ ? index_->hard_stalls() : 0;
        uint64_t ev = index_ ? index_->evictions() : 0;
        uint64_t sp = index_ ? index_->spills() : 0;
        uint64_t pr = index_ ? (index_->promotes() +
                                index_->promotes_async()) : 0;
        // Workload demand (ISSUE 13): eviction-quality counters +
        // working-set gauge, so a bundle's history shows the demand
        // lead-up, not just the system's reaction.
        uint64_t prem = 0, thr = 0;
        if (index_ != nullptr) {
            const WorkloadProfiler& wl = index_->workload();
            prem = wl.premature_evictions();
            thr = wl.thrash_cycles();
            s.wss_bytes = wl.wss_bytes();
        }
        // Content-addressed dedup (ISSUE 16): hit/savings deltas plus
        // the logical-occupancy gauges.
        uint64_t dh = index_ ? index_->dedup_hits() : 0;
        uint64_t ds = index_ ? index_->dedup_bytes_saved() : 0;
        if (index_ != nullptr) {
            s.logical_bytes = index_->logical_bytes();
            s.dedup_saved_live = index_->dedup_saved_live();
        }
        // Background-IO scheduler activity (grants, deadline misses,
        // controller decisions).
        uint64_t ios = iosched_.served_total();
        uint64_t iom = iosched_.deadline_misses_total();
        uint64_t iod = iosched_.decisions();
        uint64_t lat[LatHist::kBuckets] = {};
        uint64_t opc[kMaxOp] = {};
        for (int op = 1; op < kMaxOp; ++op) {
            opc[op] = op_lat_[op].count();
            for (int b = 0; b < kNumBuckets; ++b) {
                lat[b] += op_lat_[op].bucket(b);
            }
        }
        if (hist_prev_.valid) {
            s.ops_delta = ops - hist_prev_.ops;
            s.bytes_in_delta = bin - hist_prev_.bytes_in;
            s.bytes_out_delta = bout - hist_prev_.bytes_out;
            s.reads_busy_delta = busy - hist_prev_.reads_busy;
            s.disk_io_errors_delta = ioerr - hist_prev_.disk_io_errors;
            s.hard_stalls_delta = hs - hist_prev_.hard_stalls;
            s.evictions_delta = ev - hist_prev_.evictions;
            s.spills_delta = sp - hist_prev_.spills;
            s.promotes_delta = pr - hist_prev_.promotes;
            s.uring_sqes_delta = sqes - hist_prev_.uring_sqes;
            s.premature_evictions_delta = prem - hist_prev_.premature;
            s.thrash_cycles_delta = thr - hist_prev_.thrash;
            s.dedup_hits_delta = dh - hist_prev_.dedup_hits;
            s.dedup_bytes_saved_delta = ds - hist_prev_.dedup_saved;
            s.iosched_served_delta = ios - hist_prev_.iosched_served;
            s.iosched_misses_delta = iom - hist_prev_.iosched_misses;
            s.iosched_decisions_delta =
                iod - hist_prev_.iosched_decisions;
            for (int b = 0; b < kNumBuckets; ++b) {
                s.lat_delta[b] = lat[b] - hist_prev_.lat[b];
            }
            for (int op = 0; op < kMaxOp; ++op) {
                s.op_count_delta[op] = opc[op] - hist_prev_.op_count[op];
            }
        }
        hist_prev_.ops = ops;
        hist_prev_.bytes_in = bin;
        hist_prev_.bytes_out = bout;
        hist_prev_.reads_busy = busy;
        hist_prev_.disk_io_errors = ioerr;
        hist_prev_.hard_stalls = hs;
        hist_prev_.evictions = ev;
        hist_prev_.spills = sp;
        hist_prev_.promotes = pr;
        hist_prev_.uring_sqes = sqes;
        hist_prev_.premature = prem;
        hist_prev_.thrash = thr;
        hist_prev_.dedup_hits = dh;
        hist_prev_.dedup_saved = ds;
        hist_prev_.iosched_served = ios;
        hist_prev_.iosched_misses = iom;
        hist_prev_.iosched_decisions = iod;
        memcpy(hist_prev_.lat, lat, sizeof(lat));
        memcpy(hist_prev_.op_count, opc, sizeof(opc));
        hist_prev_.valid = true;
    }
    s.stalled = wd_stalled_.load(std::memory_order_relaxed) ? 1 : 0;
    s.cluster_epoch = cluster_epoch_.load(std::memory_order_relaxed);
    ScopedLock lk(hist_mu_);
    if (hist_ring_.size() < kHistCap) {
        hist_ring_.push_back(s);
    } else {
        hist_ring_[size_t(hist_recorded_ % kHistCap)] = s;
    }
    hist_recorded_++;
}

std::string Server::history_json() {
    // Oldest-first drain of the overwrite-oldest ring, one JSON object
    // per sample. Latency buckets serialize in full (burn-rate math
    // needs the distribution); per-op count deltas only for ops that
    // actually moved, to keep 512-sample blobs small.
    std::string out;
    // Sized for the worst case: the per-sample format literal is
    // ~520 bytes and its 17 integer fields are u64s (<= 20 digits
    // each), so a sample can legitimately exceed 512 bytes on a
    // long-uptime host with a TB-scale pool — a truncated object
    // would corrupt the whole JSON blob. The append below also uses
    // snprintf's return value, never strlen of a clipped buffer.
    char buf[1536];
    int m = snprintf(buf, sizeof(buf),
                     "{\"enabled\": %d, \"capacity\": %zu, "
                     "\"interval_ms\": %llu, \"now_us\": %lld, "
                     "\"buckets\": %d, \"history\": [",
                     hist_enabled_ ? 1 : 0, kHistCap,
                     (unsigned long long)(wd_interval_us_ / 1000),
                     now_us(), LatHist::kBuckets);
    out.append(buf, size_t(m));
    ScopedLock lk(hist_mu_);
    size_t n = hist_ring_.size();
    size_t start = hist_recorded_ > kHistCap
                       ? size_t(hist_recorded_ % kHistCap)
                       : 0;
    for (size_t i = 0; i < n; ++i) {
        const HistSample& s = hist_ring_[(start + i) % n];
        m = snprintf(
            buf, sizeof(buf),
            "%s{\"t_us\": %lld, \"used_bytes\": %llu, "
            "\"pool_bytes\": %llu, \"kvmap_len\": %llu, "
            "\"connections\": %llu, \"spill_queue_depth\": %llu, "
            "\"promote_queue_depth\": %llu, \"ops_delta\": %llu, "
            "\"bytes_in_delta\": %llu, \"bytes_out_delta\": %llu, "
            "\"reads_busy_delta\": %llu, "
            "\"disk_io_errors_delta\": %llu, "
            "\"hard_stalls_delta\": %llu, \"evictions_delta\": %llu, "
            "\"spills_delta\": %llu, \"promotes_delta\": %llu, "
            "\"uring_sqes_delta\": %llu, "
            "\"premature_evictions_delta\": %llu, "
            "\"thrash_cycles_delta\": %llu, \"wss_bytes\": %llu, "
            "\"dedup_hits_delta\": %llu, "
            "\"dedup_bytes_saved_delta\": %llu, "
            "\"logical_bytes\": %llu, \"dedup_saved_live\": %llu, "
            "\"iosched_served_delta\": %llu, "
            "\"iosched_deadline_misses_delta\": %llu, "
            "\"iosched_decisions_delta\": %llu, "
            "\"cluster_epoch\": %llu, "
            "\"workers_dead\": %u, "
            "\"tier_breaker_open\": %u, \"stalled\": %u, "
            "\"lat_delta\": [",
            i ? ", " : "", s.t_us, (unsigned long long)s.used_bytes,
            (unsigned long long)s.pool_bytes,
            (unsigned long long)s.kvmap, (unsigned long long)s.conns,
            (unsigned long long)s.spill_q,
            (unsigned long long)s.promote_q,
            (unsigned long long)s.ops_delta,
            (unsigned long long)s.bytes_in_delta,
            (unsigned long long)s.bytes_out_delta,
            (unsigned long long)s.reads_busy_delta,
            (unsigned long long)s.disk_io_errors_delta,
            (unsigned long long)s.hard_stalls_delta,
            (unsigned long long)s.evictions_delta,
            (unsigned long long)s.spills_delta,
            (unsigned long long)s.promotes_delta,
            (unsigned long long)s.uring_sqes_delta,
            (unsigned long long)s.premature_evictions_delta,
            (unsigned long long)s.thrash_cycles_delta,
            (unsigned long long)s.wss_bytes,
            (unsigned long long)s.dedup_hits_delta,
            (unsigned long long)s.dedup_bytes_saved_delta,
            (unsigned long long)s.logical_bytes,
            (unsigned long long)s.dedup_saved_live,
            (unsigned long long)s.iosched_served_delta,
            (unsigned long long)s.iosched_misses_delta,
            (unsigned long long)s.iosched_decisions_delta,
            (unsigned long long)s.cluster_epoch, s.workers_dead,
            unsigned(s.breaker), unsigned(s.stalled));
        out.append(buf, size_t(m));
        for (int b = 0; b < LatHist::kBuckets; ++b) {
            m = snprintf(buf, sizeof(buf), "%s%llu", b ? ", " : "",
                         (unsigned long long)s.lat_delta[b]);
            out.append(buf, size_t(m));
        }
        out += "], \"op_deltas\": {";
        bool first = true;
        for (int op = 1; op < kMaxOp; ++op) {
            if (s.op_count_delta[op] == 0) continue;
            m = snprintf(buf, sizeof(buf), "%s\"%s\": %llu",
                         first ? "" : ", ", op_name(uint8_t(op)),
                         (unsigned long long)s.op_count_delta[op]);
            out.append(buf, size_t(m));
            first = false;
        }
        out += "}}";
    }
    m = snprintf(buf, sizeof(buf), "], \"recorded\": %llu}",
                 (unsigned long long)hist_recorded_);
    out.append(buf, size_t(m));
    return out;
}

void Server::iosched_tick() {
    // Closed-loop knob retune (~1 Hz, watchdog thread; docs/design.md
    // "Background-IO scheduler"). Inputs are the same signals the
    // watchdog and history sampler already consume — background queue
    // depths, the workload plane's premature-eviction (thrash) rate,
    // demand-class deadline misses. Every knob CHANGE is a flight-
    // recorder decision event (a0 = IoKnob id, a1 = the new value), so
    // a bundle shows exactly what the controller did and when. All
    // moves are single bounded steps per tick: the loop converges by
    // small corrections, never slams a knob across its range.
    uint64_t spill_q = 0, premature = 0;
    {
        ScopedLock lk(store_mu_);  // pins index_ against stop()
        if (index_ == nullptr) return;
        spill_q = index_->spill_queue_depth();
        premature = index_->workload().premature_evictions();
    }
    uint64_t misses = iosched_.promote_deadline_misses();
    uint64_t prem_delta =
        io_tick_prev_.valid && premature > io_tick_prev_.premature
            ? premature - io_tick_prev_.premature
            : 0;
    uint64_t miss_delta =
        io_tick_prev_.valid && misses > io_tick_prev_.promote_misses
            ? misses - io_tick_prev_.promote_misses
            : 0;
    bool first = !io_tick_prev_.valid;
    io_tick_prev_.premature = premature;
    io_tick_prev_.promote_misses = misses;
    io_tick_prev_.valid = true;
    if (first) return;  // no deltas yet — observe one interval first

    auto update = [&](IoKnob k, uint64_t v) {
        if (iosched_.knob(k) == v) return;
        iosched_.set_knob(k, v);
        iosched_.count_decision();
        events_emit(EV_IOSCHED_DECISION, uint64_t(k), v);
    };
    const uint64_t low_base = uint64_t(cfg_.reclaim_low * 1000.0);
    const uint64_t high_milli = uint64_t(cfg_.reclaim_high * 1000.0);

    // SPILL AGGRESSIVENESS: a deep spill backlog widens the per-round
    // victim budget (longer extent-merge runs, fewer syscalls); a
    // drained queue decays it back so idle stores keep small batches.
    uint64_t mult = iosched_.knob(kKnobSpillBatchMult);
    if (mult < 1) mult = 1;
    if (spill_q > 128 && mult < 4) {
        update(kKnobSpillBatchMult, mult + 1);
    } else if (spill_q < 16 && mult > 1) {
        update(kKnobSpillBatchMult, mult - 1);
    }

    // PREFETCH DEPTH: speculative reads are the first thing to shed
    // when the demand class misses deadlines or the pool is churning
    // (premature evictions); headroom grows it back multiplicatively.
    uint64_t pd = iosched_.knob(kKnobPrefetchDepth);
    if (pd == 0) pd = 256;
    if (miss_delta > 0 || prem_delta >= wd_thrash_) {
        uint64_t next = pd / 2;
        update(kKnobPrefetchDepth, next < 16 ? 16 : next);
    } else if (prem_delta == 0 && pd < 1024) {
        uint64_t next = pd * 2;
        update(kKnobPrefetchDepth, next > 1024 ? 1024 : next);
    }

    // PROMOTION ADMISSION: thrash means promotion and reclaim are
    // cycling the same bytes — tighten the cap a step (floor midway
    // between the watermarks); calm intervals relax it back toward
    // the configured high-watermark base.
    uint64_t cap = iosched_.knob(kKnobPromoteCap);
    if (cap == 0) cap = high_milli;
    uint64_t cap_floor = (low_base + high_milli) / 2;
    if (prem_delta >= wd_thrash_ && cap > cap_floor) {
        update(kKnobPromoteCap,
               cap >= cap_floor + 10 ? cap - 10 : cap_floor);
    } else if (prem_delta == 0 && cap < high_milli) {
        update(kKnobPromoteCap,
               cap + 10 > high_milli ? high_milli : cap + 10);
    }

    // RECLAIM LOW WATERMARK: premature evictions say reclaim digs too
    // deep — lift the effective low a step (shallower passes keep the
    // re-fetched keys resident); calm intervals decay it back to the
    // configured base so a one-off burst does not pin the pool full.
    uint64_t lo = iosched_.knob(kKnobReclaimLow);
    if (lo == 0) lo = low_base;
    uint64_t lo_ceil = high_milli > 20 ? high_milli - 20 : low_base;
    if (prem_delta > 0 && lo < lo_ceil) {
        update(kKnobReclaimLow, lo + 10 > lo_ceil ? lo_ceil : lo + 10);
    } else if (prem_delta == 0 && lo > low_base) {
        update(kKnobReclaimLow,
               lo >= low_base + 10 ? lo - 10 : low_base);
    }
}

bool Server::slo_trip(const std::string& detail, uint64_t a0,
                      uint64_t a1) {
    // Control-plane entry (the Python SLO tracker's burn-rate verdict).
    // Cooldown via CAS on an atomic stamp — kWdSlo never rides the
    // watchdog thread's plain cooldown array.
    long long now = now_us();
    long long prev = slo_last_trip_us_.load(std::memory_order_relaxed);
    if (prev != 0 && now - prev < (long long)wd_cooldown_us_) {
        return false;
    }
    if (!slo_last_trip_us_.compare_exchange_strong(
            prev, now, std::memory_order_relaxed)) {
        return false;  // a concurrent tracker call won the trip
    }
    events_emit(EV_SLO_BURN, a0, a1);
    wd_trips_[kWdSlo].fetch_add(1, std::memory_order_relaxed);
    wd_last_kind_.store(int(kWdSlo), std::memory_order_relaxed);
    wd_last_trip_us_.store(now, std::memory_order_relaxed);
    IST_WARN("watchdog slo_burn: %s", detail.c_str());
    if (!bundle_dir_.empty()) capture_bundle("slo_burn", detail);
    return true;
}

void Server::watchdog_sample() {
    long long now = now_us();
    std::string detail;

    // ---- stall: IO-worker + background heartbeats, worker deaths.
    bool stalled = false;
    uint64_t dead = 0;
    uint64_t spill_q = 0, promote_q = 0, spills = 0, promotes = 0;
    uint64_t premature = 0;
    {
        ScopedLock lk(store_mu_);  // pins workers_/index_ against stop()
        for (const auto& w : workers_) {
            long long hb = w->heartbeat_us.load(std::memory_order_relaxed);
            if (hb > 0 && now - hb > (long long)wd_stall_us_) {
                stalled = true;
                detail = "worker " + std::to_string(w->idx) +
                         " heartbeat age " +
                         std::to_string(now - hb) + " us";
                break;
            }
        }
        if (index_ != nullptr) {
            dead = index_->workers_dead();
            spill_q = index_->spill_queue_depth();
            promote_q = index_->promote_queue_depth();
            spills = index_->spills() + index_->evictions();
            promotes = index_->promotes_async() + index_->promotes();
            premature = index_->workload().premature_evictions();
            // The spill/promote loops stamp their heartbeat only when
            // WOKEN (their cv waits are untimed), so an idle worker's
            // age grows without bound — a stale heartbeat is a stall
            // verdict only when the worker has work it is not doing.
            // The reclaimer's wait is a 200 ms tick, so it stamps
            // continuously while alive (backlog 1 = always eligible).
            struct {
                const char* who;
                long long age;
                uint64_t backlog;
            } bg[] = {
                {"reclaim", index_->reclaim_heartbeat_age_us(), 1},
                {"spill", index_->spill_heartbeat_age_us(), spill_q},
                {"promote", index_->promote_heartbeat_age_us(),
                 promote_q},
            };
            for (const auto& b : bg) {
                if (!stalled && b.backlog > 0 &&
                    b.age > (long long)wd_stall_us_) {
                    stalled = true;
                    detail = std::string(b.who) +
                             " worker heartbeat age " +
                             std::to_string(b.age) + " us with " +
                             std::to_string(b.backlog) +
                             " queued items";
                }
            }
        }
    }
    // A dead background worker's heartbeat reads -1 (not running), so
    // the age checks above can never see it — the death itself is the
    // stall. The TRIP fires on the transition (against a zero baseline
    // before the first sample, so a death during startup still trips);
    // the CURRENT verdict gauge stays raised while any worker is dead.
    uint64_t prev_dead = wd_prev_.valid ? wd_prev_.workers_dead : 0;
    bool stall_trip = stalled;
    if (!stall_trip && dead > prev_dead) {
        stall_trip = true;
        detail = "background worker died (workers_dead " +
                 std::to_string(prev_dead) + " -> " +
                 std::to_string(dead) + ")";
    }
    wd_stalled_.store(stalled || dead > 0, std::memory_order_relaxed);

    // ---- slow op: p99 of the per-op histogram DELTA since the last
    // sample (all ops aggregated; the bundle's stats.json has the
    // per-op split). Midpoint convention matches LatHist.
    uint64_t cur[kNumBuckets] = {};
    uint64_t cur_count = 0;
    for (int op = 1; op < kMaxOp; ++op) {
        for (int b = 0; b < kNumBuckets; ++b) {
            cur[b] += op_lat_[op].bucket(b);
        }
    }
    for (int b = 0; b < kNumBuckets; ++b) cur_count += cur[b];
    uint64_t delta_p99 = 0, delta_count = 0;
    if (wd_prev_.valid && cur_count > wd_prev_.op_count) {
        uint64_t delta[kNumBuckets];
        for (int b = 0; b < kNumBuckets; ++b) {
            delta[b] = cur[b] - wd_prev_.op_buckets[b];
            delta_count += delta[b];
        }
        uint64_t rank = uint64_t(0.99 * double(delta_count - 1)) + 1;
        uint64_t seen = 0;
        for (int b = 0; b < kNumBuckets; ++b) {
            seen += delta[b];
            if (seen >= rank) {
                delta_p99 = (1ull << b) + (1ull << b) / 2;
                break;
            }
        }
    }
    constexpr uint64_t kMinSlowOpSamples = 8;
    bool slow = wd_p99_us_ > 0 && delta_count >= kMinSlowOpSamples &&
                delta_p99 > wd_p99_us_;

    // ---- queue growth without drain: a background queue that stays
    // populated (or grows) across consecutive samples while its drain
    // counters stand still is wedged, whatever its thread state says.
    constexpr uint64_t kQueueFloor = 4;
    constexpr int kQueueStreak = 3;
    bool queue_suspect = false;
    if (wd_prev_.valid) {
        bool spill_wedged = spill_q >= kQueueFloor &&
                            spill_q >= wd_prev_.spill_q &&
                            spills == wd_prev_.spills;
        bool promote_wedged = promote_q >= kQueueFloor &&
                              promote_q >= wd_prev_.promote_q &&
                              promotes == wd_prev_.promotes;
        queue_suspect = spill_wedged || promote_wedged;
    }
    wd_queue_streak_ = queue_suspect ? wd_queue_streak_ + 1 : 0;
    bool queue_growth = wd_queue_streak_ >= kQueueStreak;

    // ---- thrash: SUSTAINED premature-eviction rate. The workload
    // profiler's ghost ring counts get-misses on recently-evicted
    // keys; a rate over ISTPU_WATCHDOG_THRASH per interval for two
    // consecutive samples means the reclaimer is evicting keys the
    // workload re-fetches — the pool is undersized (or the eviction
    // order is fighting the access pattern), and the bundle's
    // workload.json carries the MRC that says WHICH.
    constexpr int kThrashStreak = 2;
    uint64_t prem_delta =
        wd_prev_.valid && premature > wd_prev_.premature
            ? premature - wd_prev_.premature
            : 0;
    bool thrash_suspect =
        wd_thrash_ > 0 && wd_prev_.valid && prem_delta >= wd_thrash_;
    wd_thrash_streak_ = thrash_suspect ? wd_thrash_streak_ + 1 : 0;
    bool thrash_trip = wd_thrash_streak_ >= kThrashStreak;

    // ---- io_deadline: demand-promote grants that blew their deadline
    // bound this interval. The bound is the scheduler's hard contract
    // (strict priority keeps the demand class ahead of any snapshot/
    // spill backlog), so ANY miss delta is a verdict — no streak; the
    // per-kind cooldown below still caps it at one trip per window,
    // which is what the exactly-one-verdict test pins.
    uint64_t io_misses = iosched_.promote_deadline_misses();
    uint64_t io_miss_delta =
        wd_prev_.valid && io_misses > wd_prev_.io_promote_misses
            ? io_misses - wd_prev_.io_promote_misses
            : 0;
    bool io_deadline_trip = iosched_.enabled() && io_miss_delta > 0;

    wd_prev_.valid = true;
    wd_prev_.op_count = cur_count;
    memcpy(wd_prev_.op_buckets, cur, sizeof(cur));
    wd_prev_.spill_q = spill_q;
    wd_prev_.promote_q = promote_q;
    wd_prev_.spills = spills;
    wd_prev_.promotes = promotes;
    wd_prev_.workers_dead = dead;
    wd_prev_.premature = premature;
    wd_prev_.io_promote_misses = io_misses;

    // Per-kind cooldown gates BOTH the event and the bundle: a
    // persistent stall must not burn a bundle per interval. The
    // events_emit calls stay LITERAL per kind (not routed through the
    // helper) so the invariant linter can pin each watchdog.* catalog
    // row to its real emit site.
    auto cooled = [&](WdKind kind) {
        return now - wd_last_per_kind_[kind] >= (long long)wd_cooldown_us_;
    };
    // fire() runs AFTER the kind's events_emit so the captured
    // bundle's events.json contains the verdict event itself.
    auto fire = [&](WdKind kind, const char* kind_name,
                    const std::string& det) {
        wd_last_per_kind_[kind] = now;
        wd_trips_[kind].fetch_add(1, std::memory_order_relaxed);
        wd_last_kind_.store(int(kind), std::memory_order_relaxed);
        wd_last_trip_us_.store(now, std::memory_order_relaxed);
        IST_WARN("watchdog %s: %s", kind_name, det.c_str());
        if (!bundle_dir_.empty()) capture_bundle(kind_name, det);
    };
    if (stall_trip && cooled(kWdStall)) {
        events_emit(EV_WATCHDOG_STALL, dead, 0);
        fire(kWdStall, "stall", detail);
    }
    if (slow && cooled(kWdSlowOp)) {
        events_emit(EV_WATCHDOG_SLOW_OP, delta_p99, delta_count);
        fire(kWdSlowOp, "slow_op",
             "op p99 delta " + std::to_string(delta_p99) + " us over " +
                 std::to_string(delta_count) + " ops (deadline " +
                 std::to_string(wd_p99_us_) + " us)");
    }
    if (queue_growth) {
        wd_queue_streak_ = 0;  // re-arm after the trigger
        if (cooled(kWdQueue)) {
            events_emit(EV_WATCHDOG_QUEUE_GROWTH, spill_q, promote_q);
            fire(kWdQueue, "queue_growth",
                 "spill_q " + std::to_string(spill_q) + " promote_q " +
                     std::to_string(promote_q) +
                     " held without drain progress");
        }
    }
    if (thrash_trip) {
        wd_thrash_streak_ = 0;  // re-arm after the trigger
        if (cooled(kWdThrash)) {
            events_emit(EV_WATCHDOG_THRASH, prem_delta, premature);
            fire(kWdThrash, "thrash",
                 std::to_string(prem_delta) +
                     " premature evictions this interval (threshold " +
                     std::to_string(wd_thrash_) + ", total " +
                     std::to_string(premature) +
                     "): the reclaimer is evicting keys the workload "
                     "re-fetches");
        }
    }
    if (io_deadline_trip && cooled(kWdIoDeadline)) {
        events_emit(EV_WATCHDOG_IO_DEADLINE, io_miss_delta, io_misses);
        fire(kWdIoDeadline, "io_deadline",
             std::to_string(io_miss_delta) +
                 " demand-promote deadline misses this interval (bound " +
                 std::to_string(iosched_.deadline_bound_us(kIoPromote)) +
                 " us, total " + std::to_string(io_misses) +
                 "): the IO budget is too small for the demand-path "
                 "load");
    }
}

void Server::capture_bundle(const char* kind, const std::string& detail) {
    // bundle_mu_ (rank 17, below the store getters' store_mu_):
    // the watchdog thread and a control-plane slo_trip may capture
    // concurrently, and wd_bundle_seq_/keep-last-K pruning need one
    // writer at a time.
    ScopedLock blk(bundle_mu_);
    char name[96];
    snprintf(name, sizeof(name), "bundle-%08llu-%s",
             (unsigned long long)(++wd_bundle_seq_), kind);
    std::string dir = bundle_dir_ + "/" + name;
    if (mkdir(dir.c_str(), 0755) != 0 && errno != EEXIST) {
        IST_WARN("watchdog: cannot create bundle dir %s: %s",
                 dir.c_str(), strerror(errno));
        return;
    }
    long long t0 = now_us();
    bool ok = write_text_file(dir + "/stats.json", stats_json());
    ok &= write_text_file(dir + "/events.json", events_json());
    ok &= write_text_file(dir + "/trace.json", trace_json());
    ok &= write_text_file(dir + "/debug_state.json", debug_state_json());
    // The metrics-history ring: the bundle now shows the minutes of
    // LEAD-UP to the trigger, not just the captured instant.
    ok &= write_text_file(dir + "/history.json", history_json());
    // The workload demand model at capture time (ISSUE 13): the MRC /
    // WSS / eviction-quality / dedup facts that say whether the
    // anomaly was the STORE misbehaving or the DEMAND shifting.
    ok &= write_text_file(dir + "/workload.json", workload_json());
    // Cluster tier (ISSUE 14): the directory + migration cursor in
    // force at capture time — a migration-stall bundle answers "which
    // range, how far, under which epoch" without a live server.
    ok &= write_text_file(dir + "/cluster.json", cluster_json());
    char manifest[512];
    snprintf(manifest, sizeof(manifest),
             "{\"trigger\": \"%s\", \"detail\": \"%s\", "
             "\"captured_at_us\": %lld, \"capture_us\": %lld, "
             "\"seq\": %llu, \"files\": [\"stats.json\", "
             "\"events.json\", \"trace.json\", "
             "\"debug_state.json\", \"history.json\", "
             "\"workload.json\", \"cluster.json\"]}",
             kind, json_escape(detail).c_str(), t0, now_us() - t0,
             (unsigned long long)wd_bundle_seq_);
    ok &= write_text_file(dir + "/manifest.json", manifest);
    if (!ok) {
        IST_WARN("watchdog: bundle %s incomplete (disk?)", dir.c_str());
    }
    wd_bundles_.fetch_add(1, std::memory_order_relaxed);
    events_emit(EV_BUNDLE_CAPTURED, wd_bundle_seq_, 0);
    IST_WARN("watchdog: diagnostic bundle captured at %s (%s)",
             dir.c_str(), kind);
    // Keep-last-K: bounded evidence, not a disk leak. Lexicographic
    // order is age order (zero-padded seq).
    std::vector<std::string> bundles = list_bundles(bundle_dir_);
    while (bundles.size() > bundle_keep_) {
        remove_bundle_dir(bundle_dir_ + "/" + bundles.front());
        bundles.erase(bundles.begin());
    }
}

}  // namespace istpu
