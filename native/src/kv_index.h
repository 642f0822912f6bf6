// kv_index.h — content-keyed block index with two-phase visibility.
//
// Parity target: reference kv_map machinery (src/infinistore.h:30-46 and
// usage throughout src/infinistore.cpp):
//   - kv_map: unordered_map<string, intrusive_ptr<PTR>> where PTR frees its
//     pool block on last deref (infinistore.h:38-43) — here Block +
//     shared_ptr with the pool deallocation in ~Block.
//   - two-phase visibility via the `committed` flag: allocate creates an
//     uncommitted entry; readers/check_exist only see committed entries
//     (infinistore.cpp:436-454, :1077-1090); get_match_last_index counts
//     uncommitted entries too (quirk preserved, :1092-1108).
//   - first-writer-wins dedup: allocating an existing key (committed OR
//     inflight) yields a FAKE sentinel the client skips
//     (infinistore.cpp:353-359, :740-746).
//   - inflight tracking: the reference keys inflight writes by remote addr
//     (infinistore.cpp:63); we hand out opaque u64 tokens instead, each
//     pinning its Block so a purge mid-write can never free memory that a
//     write is landing in.
//   - pins: during server-push reads the reference carries
//     vector<intrusive_ptr<PTR>> in the verbs wr_id to keep blocks alive
//     (infinistore.cpp:432,492,320-324). Here the send queue holds
//     BlockRefs; for one-sided SHM reads clients take an explicit pin
//     lease (OP_PIN/OP_RELEASE) — a primitive the reference's CUDA-IPC
//     path performs implicitly inside the server.
//
// Thread safety (multi-worker data plane): the index is LOCK-STRIPED.
// Keys hash to one of kStripes stripes; each stripe owns its own
// unordered_map, inflight slab and mutex, so workers touching different
// keys never contend. Inflight tokens embed their stripe
// ([gen:32][stripe:4][slot:28]) so token-addressed ops (write_dest /
// commit / abort — the put hot path) lock exactly one stripe. Rules:
//   - Entry fields are guarded by their stripe's mutex.
//   - The LRU is SEGMENTED: each stripe keeps its own recency list under
//     the stripe's own mutex, so lru_touch on the get/put hot path locks
//     nothing beyond the already-held stripe lock (PR 2's single global
//     list serialized every recency update on one lru_mu_). Every touch
//     stamps a global monotonically increasing age; a per-stripe atomic
//     tail-age mirrors the age of the stripe's coldest entry so victim
//     selection can pre-filter stripes without locks. Eviction picks the
//     stripe whose tail is globally oldest and drains victims whose age
//     stays below every other stripe's tail — exact global LRU order
//     whenever no entries are pinned and no stripe is try-lock busy,
//     an approximation otherwise (pinned tails hide younger evictables
//     behind them). ISTPU_EXACT_LRU=1 restores exact order under pins
//     too (per-victim eligibility walks; eviction tests assert order).
//     Victim stripes are TRY-locked (a busy stripe's victims are skipped
//     for the pass) so no lock-order cycle exists; with one worker the
//     try always succeeds.
//   - Cross-stripe ops (purge, snapshot_items, match_last_index, reserve)
//     take stripe locks in INDEX ORDER.
//   - Pool-arena locks (mempool.h) are leaves, taken after any stripe
//     lock; pin leases live under their own leases_mu_ leaf; the spill
//     queue's spill_mu_ is a leaf taken after a stripe lock (the writer
//     thread takes spill_mu_ and stripe locks strictly in sequence,
//     never nested).
// All public methods lock internally; none return raw Entry pointers
// (BlockRefs keep bytes alive after the stripe lock drops).
//
// Background reclaim pipeline (PR 3): with eviction and/or a disk tier,
// reclaim is normally NOT paid on the put path. A reclaimer thread wakes
// when pool occupancy crosses a high watermark and evicts/spills down to
// a low watermark in batches; spill victims move through a SPILLING state
// and are queued to an async writer that performs the DiskTier IO outside
// all index locks (a get on a SPILLING key reads the still-resident block
// and cancels the spill). The inline evict path in allocate/promote
// survives only as the last-resort slow path when the reclaimer cannot
// keep up; those "hard stalls" are counted.
#pragma once

#include <atomic>
#include <cstdint>
#include <deque>
#include <functional>
#include <list>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include "common.h"
#include "disk_tier.h"
#include "events.h"
#include "io_sched.h"
#include "lock_rank.h"
#include "mempool.h"
#include "promote.h"  // Block/BlockRef, DiskSpan/DiskRef, Promoter
#include "thread_annotations.h"
#include "trace.h"
#include "workload.h"

namespace istpu {

// One node of a stripe's segmented-LRU list: the key plus the global
// age stamped at the entry's last touch (front = most recent).
struct LruNode {
    std::string key;
    uint64_t age = 0;
};

struct Entry {
    BlockRef block;  // set while resident in the DRAM pool
    DiskRef disk;    // set while spilled to the disk tier
    // Last-resort limbo: holds the bytes when a bounce-swap promote freed
    // the disk extent but could neither land in the pool nor re-store
    // (pathological fragmentation). Committed data is never dropped.
    std::shared_ptr<std::vector<uint8_t>> heap;
    uint32_t size = 0;
    bool committed = false;
    // SPILLING: the async writer holds a BlockRef and is copying the
    // bytes to the disk tier. The entry stays fully readable (block is
    // still set); a read clears the flag, cancelling the spill at the
    // writer's completion check. Guarded by the stripe mutex.
    bool spilling = false;
    // PROMOTING: the async promotion worker holds a DiskRef and is
    // reading the bytes back toward a pool block; the entry stays
    // disk-served meanwhile. The worker revalidates (same DiskSpan,
    // still non-resident) under the stripe mutex before adopting —
    // erase/purge/re-put/inline-promote races cancel. Guarded by the
    // stripe mutex.
    bool promoting = false;
    // Second-touch memory (meaningful only while disk-resident; reset
    // whenever the entry goes non-resident): the FIRST cold get serves
    // from disk without promoting — one-shot scans must not churn the
    // pool — and the second touch queues the async promote. Guarded by
    // the stripe mutex.
    bool touched = false;
    // Position in the stripe's LRU list (valid when committed and
    // resident; guarded by the stripe mutex).
    std::list<LruNode>::iterator lru_it{};
    bool in_lru = false;
    // Content-addressed dedup sharing is tracked per BLOCK, not per
    // entry (Block::dedup_sharers): the first writer can die while
    // sharers remain, so "who owns the physical bytes" is a property
    // of the block's committed-holder count, not of any one entry.
};

class KVIndex {
   public:
    static constexpr uint32_t kStripeBits = 4;
    static constexpr uint32_t kStripes = 1u << kStripeBits;
    static constexpr uint32_t kSlotBits = 32 - kStripeBits;  // 28

    // eviction=true enables LRU eviction of committed, unpinned entries
    // when the pool is exhausted (beyond reference parity: the reference
    // simply returns OOM forever once full — SURVEY.md §5 notes its only
    // capacity answer is "capacity + chunking").
    //
    // disk (optional) adds the spill tier: under pool pressure cold
    // entries move to disk instead of being dropped, and reads promote
    // them back (the reference's aspirational "SSD tier",
    // design.rst:36). With disk but eviction=false, no committed entry
    // is ever lost (first-writer-wins preserved); with both, disk-full
    // falls back to hard eviction.
    // epoch (optional) points at the store epoch word (the server's
    // shared CtlPage): bumped whenever a committed entry's pool blocks
    // may stop being valid at their last-advertised location (evict,
    // spill, delete, purge). SHM clients validate their pin cache
    // against it without a round trip.
    // tracer (optional) wires the observability plane in (trace.h):
    // contended stripe-lock acquisitions feed its always-on wait
    // histogram (and, when tracing is enabled, lock-wait spans on the
    // acquiring worker's ring); the reclaimer and spill writer get
    // their own span tracks so reclaim interference with foreground
    // ops is attributable.
    explicit KVIndex(MM* mm, bool eviction = false, DiskTier* disk = nullptr,
                     std::atomic<uint64_t>* epoch = nullptr,
                     Tracer* tracer = nullptr);
    ~KVIndex();

    // Start the background reclaim pipeline: a reclaimer thread that
    // wakes when pool occupancy crosses `high` (fraction of pool bytes)
    // and evicts/spills down to `low`, plus — when a disk tier is
    // present — an async spill writer that performs the tier IO outside
    // all index locks. No-op unless eviction/spill is configured and
    // 0 < high < 1 (high >= 1 or <= 0 disables background reclaim; the
    // inline last-resort path still works). With a disk tier and
    // `promote` (the async read pipeline, promote.h), a promotion
    // worker also starts: gets serve disk-resident keys straight from
    // their extents and promotion happens on ITS thread
    // (promote-on-second-touch), admission-bounded by `high`.
    // promote=false keeps the historical inline promotion.
    void start_background(double high, double low, bool promote = true);
    // Stop + join the background threads; queued spills are dropped
    // (their entries simply stay resident). Idempotent.
    void stop_background();

    // Wire the server's background-IO scheduler in (before
    // start_background). The index reads EFFECTIVE tuning through it —
    // reclaim-low watermark, prefetch admission depth, spill batch
    // multiplier, sized-to-backlog reclaim headroom — while high_/low_
    // stay the configured bases. Null / disabled scheduler: historical
    // behavior, bit for bit.
    void set_io_scheduler(IoScheduler* s) {
        io_sched_ = s;
        if (promoter_) promoter_->set_io_scheduler(s);
    }

    uint64_t epoch() const {
        return epoch_ ? epoch_->load(std::memory_order_relaxed) : 0;
    }

    // Reserve an uncommitted block for `key`, owned by connection `owner`.
    // Tokens are usable only by their owning connection (the reference
    // keys inflight state per client, infinistore.cpp:63,361-371 — without
    // this, client A could commit or overwrite client B's in-flight
    // allocation). Returns:
    //   OK        — new block; out filled, token registered
    //   CONFLICT  — key already present (committed or inflight): dedup, the
    //               caller should emit FAKE_TOKEN
    //   OUT_OF_MEMORY — pool exhausted
    Status allocate(const std::string& key, uint32_t size, RemoteBlock* out,
                    uint64_t owner);

    // Destination for an inflight token's payload (OP_WRITE scatter).
    // Returns nullptr if the token is unknown or owned by another
    // connection (the forged payload lands in the sink). The returned
    // pointer stays valid while the token is live: the inflight entry
    // pins the Block, and only the owning connection — whose ops are
    // serialized on its worker — can commit/abort the token.
    uint8_t* write_dest(uint64_t token, uint32_t* size_out, uint64_t owner);

    // Abort every live inflight token owned by `owner` (dead-connection
    // cleanup). O(slab capacity) summed over stripes — the slabs only
    // ever hold the peak concurrent inflight count, and connection death
    // is rare.
    size_t abort_all_for_owner(uint64_t owner);

    // Second phase: make the entry visible. OK, or CONFLICT if the entry
    // was purged/replaced since allocation (write is discarded safely) or
    // the token belongs to another connection (the real owner's inflight
    // state is left untouched).
    Status commit(uint64_t token, uint64_t owner);
    // Abort an inflight allocation (client died mid-write). No-op on
    // another connection's token.
    void abort(uint64_t token, uint64_t owner);

    // Committed-size probe for read/pin admission passes: true (and
    // *size_out set) iff the key exists and is committed. Refreshes LRU
    // recency like a read.
    bool peek_committed(const std::string& key, uint32_t* size_out);

    // Acquire a pinned, RESIDENT block reference for a committed key —
    // the whole get path (lookup + disk promotion + pin) under one
    // stripe lock, returning a BlockRef that stays valid after the lock
    // drops. allow_promote=false makes a non-resident entry answer BUSY
    // instead of paying tier IO; promoted_out (optional) is set to true
    // iff THIS call paid a promotion — per-op promotion budgets must
    // count their own promotions, not the global counter, which other
    // workers advance concurrently. Returns OK / KEY_NOT_FOUND / BUSY /
    // OUT_OF_MEMORY (promotion failed, retryable) / INTERNAL_ERROR
    // (tier IO error).
    Status acquire_block(const std::string& key, bool allow_promote,
                         BlockRef* out, uint32_t* size_out,
                         bool* promoted_out = nullptr);

    // True while the async promotion worker is running AND alive — the
    // server's read/pin paths then use acquire_read/acquire_resident
    // below instead of the inline-promoting acquire_block. A worker
    // that DIED (induced by the worker.promote failpoint, or a real
    // crash) flips this false, so reads/pins degrade to the historical
    // inline paths instead of wedging behind a dead queue.
    bool async_promote_active() const {
        return promoter_ != nullptr && promoter_->running() &&
               promoter_->alive();
    }

    // Read-pipeline get (OP_READ, STREAM server-push): never pays tier
    // IO or pool allocation under the stripe lock. Exactly one of the
    // three handles is set on OK:
    //   *out      — resident: pinned BlockRef (the fast path);
    //   *disk_out — disk-resident: the caller serves the bytes from the
    //               extent OUTSIDE all locks (the DiskRef pins it, so a
    //               concurrent delete/purge cannot free it mid-read);
    //               second-touch policy + admission decide whether this
    //               call also queued an async promote;
    //   *heap_out — limbo bytes (pathological both-tiers-full parking):
    //               served directly from the heap ref.
    // Returns OK / KEY_NOT_FOUND.
    Status acquire_read(const std::string& key, BlockRef* out,
                        DiskRef* disk_out,
                        std::shared_ptr<std::vector<uint8_t>>* heap_out,
                        uint32_t* size_out);

    // Pin-path get (OP_PIN — one-sided SHM clients memcpy from the
    // pool, so the entry MUST be pool-resident). Resident → OK.
    // Disk-resident → queue the async promote (PIN is an explicit
    // will-read signal, so it bypasses second-touch) and answer BUSY;
    // the client's backoff retry lands after the worker adopts the
    // pool copy. When admission refuses (pool at the watermark) or the
    // worker is not running, falls back to the historical inline
    // promotion so progress is never lost.
    Status acquire_resident(const std::string& key, BlockRef* out,
                            uint32_t* size_out);

    // OP_PREFETCH: per-key pipeline kick, replies immediately. out[i]:
    //   0 missing (not committed)   1 resident (recency refreshed)
    //   2 promotion queued (or already in flight)
    //   3 disk-resident but not queued (admission refused / worker off)
    // — the get path still serves 3s from disk.
    void prefetch(const std::vector<std::string>& keys, uint8_t* out);

    bool check_exist(const std::string& key);  // exists && committed

    // Reference algorithm verbatim in behavior (infinistore.cpp:1092-1108):
    // binary search assuming presence is monotone over the key list
    // (vLLM prefix pages); does NOT check committed. Takes every stripe
    // lock in index order for a consistent cut — a vector-held lock set
    // outside the static lattice (runtime rank checker covers it).
    int match_last_index(const std::vector<std::string>& keys) const
        NO_THREAD_SAFETY_ANALYSIS;

    // Pre-size the index + inflight slabs for `extra` upcoming
    // allocations (batched allocate/put ops insert thousands of keys in
    // one loop; without this the tables rehash mid-loop under the stripe
    // locks). Locks stripes one at a time.
    void reserve(size_t extra);

    // Pin committed blocks for one-sided SHM reads; returns lease id.
    uint64_t pin(std::vector<BlockRef> blocks);
    bool release(uint64_t lease_id);

    // One committed entry's refcounted byte handle — snapshot support.
    // Exactly one of block/heap/disk is set; the shared_ptrs keep the
    // bytes alive after the stripe locks are released, so serialization
    // never stalls the data plane.
    struct SnapshotItem {
        std::string key;
        BlockRef block;
        DiskRef disk;
        std::shared_ptr<std::vector<uint8_t>> heap;
        uint32_t size = 0;
    };
    // Collect handles to every committed entry (cheap: refs only; locks
    // all stripes in index order — a vector-held lock set outside the
    // static lattice — serialize afterwards without them). The
    // optional [lo, hi) ring-hash window (ring_hash(key), the cluster
    // tier's key-range codec) filters to one migrating range; lo > hi
    // wraps around the ring. Defaults cover the whole ring (the
    // historical full snapshot).
    std::vector<SnapshotItem> snapshot_items(
        uint64_t ring_lo = 0, uint64_t ring_hi = kRingSpan) const
        NO_THREAD_SAFETY_ANALYSIS;

    // The cluster tier's key-placement hash: CRC-32 (zlib polynomial),
    // chosen because the Python client routes with zlib.crc32 — both
    // sides MUST agree on the ring coordinate of every key or a range
    // migration would move the wrong keys. Distinct from the index's
    // own stripe/workload hash on purpose: placement is wire-visible
    // surface, stripe hashing is an internal detail free to change.
    static uint32_t ring_hash(const std::string& key);
    static constexpr uint64_t kRingSpan = 1ull << 32;
    // True when ring_hash(key) falls in [lo, hi) with wrap-around
    // semantics (lo > hi spans the ring's origin).
    static bool ring_in_range(uint32_t h, uint64_t lo, uint64_t hi) {
        if (lo <= hi) return h >= lo && uint64_t(h) < hi;
        return uint64_t(h) >= lo || uint64_t(h) < hi;
    }

    // Erase every COMMITTED entry whose ring_hash falls in [lo, hi)
    // (wrap-around like snapshot_items): the migration commit's
    // source-side cleanup. Inflight entries are never touched — a
    // writer racing the migration keeps its token; first-writer-wins
    // resolves it exactly like any other race. Epoch-bump-per-entry
    // mirrors erase() (pin caches must never serve a moved key's
    // recycled blocks).
    size_t erase_range(uint64_t ring_lo, uint64_t ring_hi);

    // Replica-divergence digest over the committed entries of one
    // ring-hash range (the measurement half of anti-entropy — ISSUE
    // 15): an ORDER-INDEPENDENT xor of a per-entry mix of a
    // deterministic key hash (FNV-1a 64, never std::hash — two shards
    // must agree byte-for-byte across processes and builds) and the
    // entry size. Two replicas holding the same {key -> size} set for
    // the range produce the same digest regardless of stripe layout
    // or insertion order; a key present on one side only (written
    // while a replica was down) flips it. Payload CONTENT is not
    // hashed — entries are immutable once committed (first-writer-
    // wins), so key identity + size is the divergence signal at a
    // cost the aggregator can afford per scrape. Stripe at a time
    // like erase_range; `count`/`bytes` (optional) report the
    // range's population for the fleet gauges.
    uint64_t digest_range(uint64_t ring_lo, uint64_t ring_hi,
                          uint64_t* count = nullptr,
                          uint64_t* bytes = nullptr) const;

    // Directly insert a COMMITTED entry (snapshot restore): pool
    // allocate + copy + visible immediately, no token round-trip.
    // CONFLICT when the key exists (first-writer-wins: live data beats
    // snapshot data), OUT_OF_MEMORY when the pool cannot hold it.
    // Never evicts live entries to make room — a restore must not churn
    // hot data out in favor of stale snapshot data.
    Status insert_committed(const std::string& key, const uint8_t* data,
                            uint32_t size);

    // Commit a key whose pool blocks were carved from a block lease and
    // written one-sided by the client: the entry ADOPTS the
    // already-allocated range at `loc` (no copy, no token) and becomes
    // visible immediately. CONFLICT when the key already exists
    // (committed OR inflight — first-writer-wins; the caller frees the
    // leased blocks). This is the second phase of OP_COMMIT_BATCH.
    Status insert_leased(const std::string& key, const PoolLoc& loc,
                         uint32_t size);

    // --- content-addressed dedup (docs/design.md "Content-addressed
    // dedup"). Commit-time: every committed publication computes
    // content_hash128 over the full payload; a byte-verified match
    // against a live canonical block ADOPTS it (the duplicate's own
    // bytes free back to the pool), otherwise the new block registers
    // as canonical. Hash-first: OP_PUT_HASH answers below WITHOUT any
    // payload on the wire.
    //
    // put_by_hash verdicts (the OP_PUT_HASH wire bytes):
    //   0 NEED   — no canonical match; payload must follow on the
    //              normal put path (nothing was reserved: first-
    //              writer-wins resolves the race if two clients probe
    //              the same key).
    //   1 HAVE   — key committed by adopting the canonical block for
    //              (h1, h2, size); zero pool bytes, zero payload
    //              (counted dedup_hits / dedup_bytes_saved).
    //   2 EXISTS — key already present (committed or inflight); the
    //              put is already satisfied first-writer-wins style.
    // HAVE trusts the 128-bit client hash claim — see the design.md
    // security note (commit-time adoption always memcmp-verifies; the
    // hash-first path has no bytes to compare).
    int put_by_hash(const std::string& key, uint32_t size, uint64_t h1,
                    uint64_t h2);

    bool dedup_enabled() const { return dedup_enabled_; }
    uint64_t dedup_hits() const {
        return dedup_hits_.load(std::memory_order_relaxed);
    }
    uint64_t dedup_bytes_saved() const {
        return dedup_bytes_saved_.load(std::memory_order_relaxed);
    }
    uint64_t dedup_hash_hits() const {
        return dedup_hash_hits_.load(std::memory_order_relaxed);
    }
    uint64_t dedup_hash_misses() const {
        return dedup_hash_misses_.load(std::memory_order_relaxed);
    }
    // Sum of committed entry sizes (what clients think they stored)
    // vs the live bytes dedup is currently saving — the unique-vs-
    // logical gauge pair istpu_top renders as logical/physical
    // occupancy.
    uint64_t logical_bytes() const {
        return logical_bytes_.load(std::memory_order_relaxed);
    }
    uint64_t dedup_saved_live() const {
        return dedup_saved_live_.load(std::memory_order_relaxed);
    }
    // MEASURED capacity multiplier in milli (1000 = no dedup):
    // logical / (logical - saved_live). Exact on delete-free traces;
    // after first-writer deletions it is the live-entry approximation
    // (savings follow the surviving adopters). The workload plane's
    // sampled dedup_ratio_milli is the PREDICTION this is scored
    // against.
    uint64_t dedup_measured_milli() const {
        uint64_t logical = logical_bytes();
        uint64_t saved = dedup_saved_live();
        if (logical == 0 || saved >= logical) return 1000;
        return logical * 1000 / (logical - saved);
    }

    // Drops all entries; inflight tokens survive harmlessly. All-stripe
    // vector-held lock set (see match_last_index).
    size_t purge() NO_THREAD_SAFETY_ANALYSIS;
    size_t erase(const std::vector<std::string>& keys);
    // Erase only ORPHANED entries among `keys`: uncommitted AND not backed
    // by any live inflight token (their writer's connection died between
    // allocate and commit, before the server processed the close). A
    // concurrent writer's in-progress allocation is never disturbed.
    size_t reclaim_orphans(const std::vector<std::string>& keys);
    size_t size() const;
    size_t inflight() const;
    size_t leases() const;
    uint64_t evictions() const {
        return evictions_.load(std::memory_order_relaxed);
    }
    uint64_t spills() const { return spills_.load(std::memory_order_relaxed); }
    uint64_t promotes() const {
        return promotes_.load(std::memory_order_relaxed);
    }
    uint64_t reclaim_runs() const {
        return reclaim_runs_.load(std::memory_order_relaxed);
    }
    uint64_t hard_stalls() const {
        return hard_stalls_.load(std::memory_order_relaxed);
    }
    uint64_t spill_queue_depth() const {
        return spill_queue_depth_.load(std::memory_order_relaxed);
    }
    uint64_t spills_cancelled() const {
        return spills_cancelled_.load(std::memory_order_relaxed);
    }
    // Disk reads paid on the data plane (cold gets served from their
    // extents + any surviving inline promotion's tier load). After
    // warmup on a promoted working set this stops growing — the
    // pipeline's acceptance signal.
    uint64_t disk_reads_inline() const {
        return disk_reads_inline_.load(std::memory_order_relaxed);
    }
    uint64_t promotes_async() const {
        return promoter_ ? promoter_->promotes_async() : 0;
    }
    uint64_t promote_queue_depth() const {
        return promoter_ ? promoter_->queue_depth() : 0;
    }
    uint64_t promotes_cancelled() const {
        return promoter_ ? promoter_->cancelled() : 0;
    }
    // Background workers that DIED unexpectedly (induced kill via the
    // worker.{reclaim,spill,promote} failpoints, or a real crash that
    // unwound the loop) — never counts clean stop_background() exits.
    // Every kick path consults the matching liveness flag and degrades
    // to its inline fallback (inline evict / inline spill selection /
    // inline promote or BUSY) instead of feeding a dead queue.
    uint64_t workers_dead() const {
        return (reclaim_died_.load(std::memory_order_relaxed) ? 1 : 0) +
               (spill_died_.load(std::memory_order_relaxed) ? 1 : 0) +
               (promoter_ && promoter_->died() ? 1 : 0);
    }
    // Heartbeat ages (µs since each worker's last loop iteration;
    // -1 = not running). Control-plane visibility for "alive but
    // wedged" — distinct from the died flags above. The anomaly
    // watchdog (server.cc) samples all three.
    long long reclaim_heartbeat_age_us() const;
    long long spill_heartbeat_age_us() const;
    long long promote_heartbeat_age_us() const {
        return promoter_ ? promoter_->heartbeat_age_us() : -1;
    }
    uint64_t spill_inflight_bytes() const {
        return spill_inflight_bytes_.load(std::memory_order_relaxed);
    }
    uint64_t promote_inflight_bytes() const {
        return promoter_ ? promoter_->inflight_bytes() : 0;
    }

    // Workload observability plane (workload.h; docs/design.md
    // "Workload observability"): the always-on profiler fed by the
    // commit/get/evict paths below. The server's control plane reads
    // it for /workload, the stats "workload" section, the history
    // ring's demand deltas and the watchdog.thrash verdict.
    WorkloadProfiler& workload() { return workload_; }
    const WorkloadProfiler& workload() const { return workload_; }
    // Append the /workload JSON body (profiler state against the
    // CURRENT pool size) as object members.
    void workload_json(std::string& out) const {
        workload_.json(out, mm_->total_bytes());
    }

    // Deep-state introspection (GET /debug/state): append per-stripe
    // entry/byte counts, location mix (pool/disk/limbo + transitional
    // SPILLING/PROMOTING flags), inflight-token counts and an LRU-age
    // histogram (power-of-two buckets over the logical age clock), plus
    // the spill/promote queue summaries, as JSON object members. Locks
    // stripes ONE AT A TIME (never a cross-stripe set): the view may be
    // a non-atomic cut across stripes, which a debug endpoint prefers
    // over stalling the data plane for a consistent one.
    void debug_json(std::string& out) const;

    // Evict least-recently-used committed entries whose blocks are not
    // pinned (use_count()==1) until `want` bytes could plausibly be
    // freed or nothing evictable remains. Returns entries evicted.
    // This is the INLINE (synchronous) path — a caller needing pool
    // space NOW (op_lease's last resort); it counts as a hard stall.
    size_t evict_lru(size_t want) {
        hard_stalls_.fetch_add(1, std::memory_order_relaxed);
        events_emit(EV_HARD_STALL, want, /*promote=*/2);
        kick_reclaimer();
        return evict_internal(want, -1, false);
    }

    // Cheap occupancy probe: kicks the reclaimer when pool usage is at
    // or above the high watermark. Called by the server after bulk
    // allocations (op_lease grants) — KVIndex::allocate checks
    // internally.
    void maybe_wake_reclaimer();

   private:
    friend class Promoter;  // finish_promote / cancel_promote_flag /
                            // maybe_wake_reclaimer from the worker thread

    // Inflight tokens live in per-stripe SLABS, not hash maps: a token is
    // (generation << 32) | (stripe << kSlotBits) | slot, so
    // write_dest/commit/abort — three calls per written block on the put
    // hot path — are O(1) array indexing with a generation check, under
    // exactly one stripe lock, instead of hash probes. Generations keep
    // stale/forged tokens fail-closed: a freed slot's generation
    // advances, so an old token mismatches. The key stays a COPY (not a
    // pointer into the map) so purge()/erase() need no slab fix-ups;
    // commit still validates against the live map entry. A key's token
    // always lives in the key's own stripe (allocate creates both
    // together), so token ops see the map entry under the same lock.
    struct Inflight {
        std::string key;
        BlockRef block;
        uint32_t size = 0;
        uint64_t owner = 0;  // connection id that allocated this token
        uint32_t gen = 0;    // matches the token's high half when live
        bool live = false;
    };

    struct Stripe {
        // Rank stamped per index at construction (kRankStripeBase + s):
        // cross-stripe ops lock in index order, which the lock-rank
        // checker (lock_rank.h) verifies as ascending ranks; the
        // reverse-order victim paths only ever TRY-lock.
        mutable Mutex mu{kRankStripeBase};
        std::unordered_map<std::string, Entry> map GUARDED_BY(mu);
        std::vector<Inflight> islab GUARDED_BY(mu);
        std::vector<uint32_t> ifree GUARDED_BY(mu);
        size_t inflight_live GUARDED_BY(mu) = 0;
        // Segmented LRU (front = most recent), guarded by mu — recency
        // updates on the hot path lock nothing beyond the stripe.
        std::list<LruNode> lru GUARDED_BY(mu);
        // Age of lru.back() (UINT64_MAX when empty): the lock-free
        // victim-selection pre-filter. Written under mu, read anywhere.
        std::atomic<uint64_t> tail_age{UINT64_MAX};
    };

    // Committed entries holding `e`'s block (content-addressed dedup:
    // one hold a sharer; 1 without dedup), and whether anything beyond
    // them — a read in flight, a queued spill — pins it. Under the
    // entry's stripe lock; a sharer attaching or leaving in another
    // stripe moves use_count before / after the holder count, so a
    // race reads as pinned, never as free.
    long block_holders(const Entry& e) const {
        if (!dedup_enabled_) return 1;
        long n = long(e.block->dedup_sharers.load(std::memory_order_relaxed));
        return n > 1 ? n : 1;
    }
    bool block_shared(const Entry& e) const { return block_holders(e) > 1; }
    bool block_pinned(const Entry& e) const {
        return e.block.use_count() > block_holders(e);
    }

    // One hash per op: the hooked hot paths compute hash_of(key) once
    // and derive both the stripe (low bits — identical to the
    // historical stripe_of) and the workload-profiler key from it.
    static uint64_t hash_of(const std::string& key) {
        return uint64_t(std::hash<std::string>{}(key));
    }
    static uint32_t stripe_of(const std::string& key) {
        return uint32_t(hash_of(key)) & (kStripes - 1);
    }
    // Block-rounded pool footprint — the byte weight the reuse-
    // distance sampler stacks (matches what eviction actually frees).
    uint64_t wl_round(uint32_t size) const {
        size_t bs = mm_->block_size();
        return (uint64_t(size) + bs - 1) / bs * bs;
    }
    // Stripe-lock acquisition with contention accounting: an
    // UNCONTENDED acquisition is a plain try_lock (no clock read, no
    // record); only the contended path pays two clock reads and feeds
    // the always-on stripe-lock-wait histogram (+ a span when tracing
    // is on). Used on the data-plane hot sites.
    UniqueLock lock_stripe(Stripe& st) ACQUIRE(st.mu);
    // Decode a token; returns nullptr unless live with matching gen.
    // Caller must hold the token's stripe mutex (stripe_of_token).
    static uint32_t stripe_of_token(uint64_t token) {
        return uint32_t(token >> kSlotBits) & (kStripes - 1);
    }
    Inflight* islot(Stripe& st, uint64_t token) REQUIRES(st.mu) {
        uint32_t idx = uint32_t(token) & ((1u << kSlotBits) - 1);
        uint32_t gen = uint32_t(token >> 32);
        if (idx >= st.islab.size()) return nullptr;
        Inflight& s = st.islab[idx];
        if (!s.live || s.gen != gen) return nullptr;
        return &s;
    }
    void ifree(Stripe& st, Inflight* s) REQUIRES(st.mu) {
        s->live = false;
        s->block.reset();
        s->key.clear();
        st.ifree.push_back(uint32_t(s - st.islab.data()));
        st.inflight_live--;
    }

    // Both require the entry's stripe mutex held; touch the stripe's
    // own LRU list only (no further locks).
    void lru_touch(Stripe& st, Entry& e, const std::string& key)
        REQUIRES(st.mu);
    void lru_drop(Stripe& st, Entry& e) REQUIRES(st.mu);
    // Promote a non-resident entry back into the pool, under the
    // entry's stripe mutex (`st` IS stripes_[stripe_idx]; both are
    // passed so the lock fact stays statically provable while the
    // eviction fallback keeps its held-stripe index).
    Status ensure_resident(Stripe& st, uint32_t stripe_idx, Entry& e,
                           const std::string& key) REQUIRES(st.mu);
    // Eviction/spill victim selection over the segmented LRU.
    // held_stripe >= 0 names a stripe mutex the CALLER already holds
    // (victims there are evicted directly); other stripes are
    // try-locked, busy ones skipped for the pass. async_spill=true
    // (reclaimer only) queues spill victims to the writer instead of
    // paying the tier IO inline. age_cap bounds victim ages: the
    // reclaimer passes the LRU clock snapshot taken when its PASS
    // began, so entries touched or promotion-adopted DURING the pass
    // can never be selected by it — without the cap, a long
    // reclaim-to-low pass raced freshly promoted entries right back
    // out (the prefetch_hit_rate ~0.87 decay; ROADMAP item 5
    // follow-on). Inline last-resort callers keep UINT64_MAX — they
    // need progress NOW over strict ordering.
    // NO_THREAD_SAFETY_ANALYSIS (here and on the two helpers below):
    // victim selection holds a DYNAMIC stripe set — the caller's
    // already-held stripe plus try-locked others — which the static
    // lattice cannot express; deadlock-freedom is by construction
    // (try-locks only on the out-of-order path) and enforced at
    // runtime by the lock-rank checker in the sanitizer builds.
    size_t evict_internal(size_t want, int held_stripe, bool async_spill,
                          uint64_t age_cap = UINT64_MAX)
        NO_THREAD_SAFETY_ANALYSIS;
    // Drain victims from one stripe's cold end: entries whose age is
    // <= age_limit, up to want bytes / max_victims. Returns
    // block-rounded bytes freed (or queued). 0 with *progress=false
    // means the stripe holds nothing evictable right now.
    size_t evict_from_stripe(uint32_t si, bool held, size_t want,
                             uint64_t age_limit, size_t max_victims,
                             uint32_t* disk_min_fail, bool async_spill,
                             size_t* victims) NO_THREAD_SAFETY_ANALYSIS;
    // Exact-mode helper: age of the stripe's oldest ELIGIBLE entry
    // (unpinned, resident, spillable/evictable), UINT64_MAX when none
    // or the stripe is try-lock busy.
    uint64_t oldest_eligible_age(uint32_t si, bool held,
                                 uint32_t disk_min_fail)
        NO_THREAD_SAFETY_ANALYSIS;

    // --- background reclaim pipeline ---------------------------------
    void kick_reclaimer();
    void reclaim_loop();
    void spill_loop();
    struct SpillItem {
        std::string key;
        BlockRef block;  // pins the bytes for the out-of-lock IO
        uint32_t size = 0;
        uint32_t stripe = 0;
        // Causal attribution (ISSUE 11): the trace id of the FOREGROUND
        // op whose thread enqueued this item, and the key's hash —
        // spill_batch/spill_write spans record under the id, and the
        // spill.cancel catalog event carries the hash, so "this put's
        // latency paid for spilling key H" reads straight off the
        // merged timeline. Tag lifetime: enqueue → finish_spill; a
        // re-queued victim gets the NEW trigger's id.
        uint64_t trace_id = 0;
        uint64_t key_hash = 0;
    };
    // Rebalance the queue-depth/inflight-bytes gauges for spill items
    // pulled off the queue without being written (clean stop, induced
    // writer death, purge cancel). The items' BlockRefs drop when the
    // caller's deque destructs; this only fixes the accounting, in ONE
    // place, because the inflight-bytes rounding must match
    // enqueue_spill's exactly or the reclaimer's overshoot guard drifts.
    void account_dropped_spills(std::deque<SpillItem>& items,
                                bool cancelled);
    // Requires the victim's stripe mutex held — a dynamic fact the
    // victim-scan callers cannot expose statically; spill_mu_ is a
    // leaf ranked above every stripe (lock_rank.h).
    void enqueue_spill(const std::string& key, const BlockRef& block,
                       uint32_t size, uint32_t si);
    void process_spill_batch(std::vector<SpillItem>& batch);
    // Re-locks the item's stripe and either adopts the stored extent
    // (entry still SPILLING and unpinned) or cancels (extent released
    // by DiskSpan RAII). off < 0 = the store itself failed.
    void finish_spill(SpillItem& item, int64_t off);
    // Drop every queued-but-unstarted spill and wait for the writer's
    // in-flight batch to finish (purge's determinism barrier: after
    // purge returns, no writer ref keeps purged pool blocks alive).
    void cancel_queued_spills();

    // --- async promotion pipeline (promote.{h,cc}) --------------------
    // Queue a disk-resident entry to the promotion worker if admission
    // (pool headroom vs the high watermark) allows. `st` is the
    // entry's stripe, held; the promote queue mutex is a leaf.
    // `prefetch` tags the queued item with the prefetch IO class
    // (OP_PREFETCH kicks) instead of demand-promote, and subjects it
    // to the controller's prefetch-depth knob. True iff queued (the
    // PROMOTING flag is set).
    bool maybe_enqueue_promote(Stripe& st, Entry& e,
                               const std::string& key, uint32_t si,
                               bool prefetch = false)
        REQUIRES(st.mu);
    // Worker-side adoption: re-locks the item's stripe and adopts
    // `block` only if the entry is unchanged (same DiskSpan, still
    // committed and non-resident, still PROMOTING). Everything else —
    // erased, purged, re-put, inline-promoted, null block (alloc/IO
    // failure) — cancels; the extent and block free by RAII. Returns
    // true iff adopted.
    bool finish_promote(PromoteItem& item, BlockRef block);
    // Clear a dropped queue item's PROMOTING flag (stop/cancel paths)
    // so the key stays promotable.
    void cancel_promote_flag(const PromoteItem& item);
    // Invalidate every client's pin cache (release store so a client
    // observing the new value also observes any writes that preceded
    // the bump, across the shared mapping).
    void bump_epoch() {
        if (epoch_) epoch_->fetch_add(1, std::memory_order_release);
    }

    // LRU bookkeeping is needed for eviction and for spill-victim
    // selection alike.
    bool track_lru() const { return eviction_ || disk_ != nullptr; }

    MM* mm_;
    bool eviction_ = false;
    DiskTier* disk_ = nullptr;
    std::atomic<uint64_t>* epoch_ = nullptr;
    Tracer* tracer_ = nullptr;
    // Background-thread span tracks (created in start_background when
    // tracing is enabled; the threads bind them at loop entry).
    TraceRing* reclaim_ring_ = nullptr;
    TraceRing* spill_ring_ = nullptr;
    // ISTPU_EXACT_LRU=1 (read once at construction): per-victim global
    // eligibility scans restore exact global LRU order even under pins.
    bool exact_lru_ = false;
    std::atomic<uint64_t> evictions_{0};
    std::atomic<uint64_t> spills_{0};
    std::atomic<uint64_t> promotes_{0};
    std::atomic<uint64_t> reclaim_runs_{0};
    std::atomic<uint64_t> hard_stalls_{0};
    std::atomic<uint64_t> spills_cancelled_{0};
    std::atomic<uint64_t> disk_reads_inline_{0};
    // Global age clock for the segmented LRU (every touch stamps one).
    std::atomic<uint64_t> lru_clock_{1};
    Stripe stripes_[kStripes];
    // Pin leases: own leaf mutex (never nested inside a stripe lock by
    // callers; the server gathers refs first, then pins).
    mutable Mutex leases_mu_{kRankPinLeases};
    std::unordered_map<uint64_t, std::vector<BlockRef>> leases_
        GUARDED_BY(leases_mu_);
    uint64_t next_lease_ GUARDED_BY(leases_mu_) = 1;

    // Background reclaim pipeline state.
    std::atomic<bool> bg_running_{false};
    std::atomic<bool> bg_stop_{false};
    // Liveness (failure model): alive_ flips false when a loop exits —
    // cleanly OR by induced death; died_ records only unexpected
    // exits (the workers_dead gauge). Heartbeats stamp each loop
    // iteration so a wedged-but-alive worker is distinguishable.
    std::atomic<bool> reclaim_alive_{false};
    std::atomic<bool> spill_alive_{false};
    std::atomic<bool> reclaim_died_{false};
    std::atomic<bool> spill_died_{false};
    std::atomic<long long> reclaim_heartbeat_us_{0};
    std::atomic<long long> spill_heartbeat_us_{0};
    double high_ = 0.0, low_ = 0.0;
    // Background-IO scheduler (server-owned; null in bare-index tests).
    // Spill-class admission, sized-to-backlog headroom and the
    // controller knobs all route through it when enabled.
    IoScheduler* io_sched_ = nullptr;
    std::thread reclaim_thread_;
    Mutex reclaim_mu_{kRankReclaim};
    CondVar reclaim_cv_;
    std::atomic<bool> reclaim_kick_{false};
    // Trace id of the foreground op whose kick won the dedup exchange
    // (0 = untraced/idle wake): the next reclaim pass records its
    // reclaim_pass/victim_scan spans under it, so the pass is
    // attributable to the put that crossed the watermark. Consumed
    // (reset to 0) at pass start.
    std::atomic<uint64_t> reclaim_kick_trace_{0};
    // Promotion pressure (see maybe_enqueue_promote): a refused
    // promotion admission asks the reclaimer for a to-LOW pass even
    // when occupancy never crossed HIGH.
    std::atomic<bool> promote_pressure_{false};
    // Spill writer: queue under its own leaf mutex (taken after a
    // stripe lock on enqueue; the writer takes spill_mu_ and stripe
    // locks strictly in sequence).
    std::thread spill_thread_;
    Mutex spill_mu_{kRankSpillQueue};
    CondVar spill_cv_;
    std::deque<SpillItem> spill_q_ GUARDED_BY(spill_mu_);
    bool spill_busy_ GUARDED_BY(spill_mu_) = false;
    // Bumped per finished batch (cancel barrier).
    uint64_t spill_batch_gen_ GUARDED_BY(spill_mu_) = 0;
    std::atomic<uint64_t> spill_queue_depth_{0};
    // Block-rounded bytes queued/being written: the reclaimer subtracts
    // these from its deficit so it does not over-select victims whose
    // memory is already on its way back to the pool.
    std::atomic<uint64_t> spill_inflight_bytes_{0};
    // Tier-full memory for ASYNC selection: the writer discovers store
    // failures after the victim was queued, so without this the
    // reclaimer would re-queue the same victims forever against a full
    // tier. Sizes >= spill_fail_min_ are skipped until the tier's
    // usage drops below what it was at the failure (something freed) or
    // a store succeeds.
    std::atomic<uint32_t> spill_fail_min_{UINT32_MAX};
    std::atomic<uint64_t> spill_fail_used_{0};
    // Fail-min backoff re-probe (see spill_may_fit): one victim per
    // window retries the tier so a transient error below the
    // breaker's threshold cannot suppress spilling forever.
    static constexpr long long kSpillFailRetryUs = 500 * 1000;
    std::atomic<long long> spill_fail_retry_at_us_{0};
    bool spill_may_fit(uint32_t size);

    // Async promotion worker (promote.{h,cc}); constructed with the
    // disk tier, started by start_background when `promote` is on.
    std::unique_ptr<Promoter> promoter_;

    // --- content-addressed dedup index --------------------------------
    // content-hash -> canonical block. weak_ptr: the index never keeps
    // a block alive (a freed canonical simply expires out — lazily on
    // lookup, wholesale in an amortized sweep). dedup_mu_ is a STRICT
    // leaf (kRankDedup): held only across the map op + weak_ptr::lock,
    // NEVER across a BlockRef drop — dropping the last ref takes a
    // pool-arena mutex (rank 300+a < 370), so refs acquired under it
    // are moved out and released under the caller's stripe lock.
    struct DedupSlot {
        std::weak_ptr<Block> block;
        uint64_t h2 = 0;
        uint32_t size = 0;
    };
    // Lookup (h1, h2, size): true iff a live canonical block with that
    // identity exists; *canon pinned. Expired slots are erased lazily.
    // Does NOT memcmp — callers with payload bytes verify before
    // adopting (hash-first callers have nothing to compare).
    bool dedup_lookup(uint64_t h1, uint64_t h2, uint32_t size,
                      BlockRef* canon);
    // Register `b` as the canonical block for (h1, h2, size); first
    // writer wins on h1 collision with a still-live slot. Amortized
    // expired-slot sweep every kDedupSweepEvery registrations.
    void dedup_register(uint64_t h1, uint64_t h2, uint32_t size,
                        const BlockRef& b);
    // Payload-verified adoption attempt for the commit-time paths:
    // hashes `payload`, looks up a canonical, memcmp-verifies, and on
    // a match swaps it into *slot (counting the hit). Registers the
    // caller's block as canonical on a miss (when *slot is set).
    // Returns true iff adopted. Call under the entry's stripe mutex.
    bool dedup_adopt_or_register(BlockRef* slot, const uint8_t* payload,
                                 uint32_t size);
    // A committed entry took hold of block `b` (fresh commit,
    // adoption, promote re-materialization): bump the block's
    // committed-sharer count; a second-or-later sharer's bytes are
    // live savings. Stripe mutex held. Exactly one release below must
    // pair with every attach — the sharer count, NOT use_count()
    // (inflated by transient read/spill refs), drives the exact
    // invariant used_bytes == logical_bytes - dedup_saved_live on
    // disk-free workloads.
    void dedup_block_attached(const BlockRef& b, uint32_t size);
    // A committed entry's hold on its block ends while the entry
    // survives (spill adoption: the disk copy is private): drop the
    // sharer count; if sharers remain, the DEPARTING bytes were the
    // shared ones. Stripe mutex held.
    void dedup_block_released(Entry& e);
    // A committed entry is dying (erase/evict-drop/erase_range):
    // retire its logical bytes + release its block hold. Stripe mutex
    // held.
    void dedup_entry_removed(Entry& e);
    static constexpr uint64_t kDedupSweepEvery = 4096;
    mutable Mutex dedup_mu_{kRankDedup};
    std::unordered_map<uint64_t, DedupSlot> dedup_map_
        GUARDED_BY(dedup_mu_);
    uint64_t dedup_registrations_ GUARDED_BY(dedup_mu_) = 0;
    // ISTPU_DEDUP=0 (read once at construction) disables content
    // addressing end to end — the bench --dedup-leg denominator.
    bool dedup_enabled_ = true;
    std::atomic<uint64_t> dedup_hits_{0};
    std::atomic<uint64_t> dedup_bytes_saved_{0};
    std::atomic<uint64_t> dedup_hash_hits_{0};
    std::atomic<uint64_t> dedup_hash_misses_{0};
    std::atomic<uint64_t> logical_bytes_{0};
    std::atomic<uint64_t> dedup_saved_live_{0};

    // Always-on workload profiler (ISTPU_WORKLOAD=0 disables — the
    // bench denominator only). Locks internally (wl_mu_, a leaf above
    // the stripe locks); the non-sampled hot path is one mix + a
    // predicted branch.
    WorkloadProfiler workload_;
};

}  // namespace istpu
