#include "kv_index.h"

#include <algorithm>
#include <chrono>
#include <cstdlib>
#include <cstring>
#include <unordered_set>

#include "events.h"
#include "failpoint.h"
#include "log.h"
#include "utils.h"

namespace istpu {

KVIndex::KVIndex(MM* mm, bool eviction, DiskTier* disk,
                 std::atomic<uint64_t>* epoch, Tracer* tracer)
    : mm_(mm), eviction_(eviction), disk_(disk), epoch_(epoch),
      tracer_(tracer) {
    // ISTPU_EXACT_LRU=1: exact global victim order even under pins
    // (per-victim eligibility walks) — the escape hatch for tests and
    // deployments that need the pre-segmentation semantics verbatim.
    const char* env = getenv("ISTPU_EXACT_LRU");
    exact_lru_ = env != nullptr && env[0] == '1';
    // ISTPU_DEDUP=0: disable content addressing end to end (commit-time
    // adoption AND put_by_hash answer as if no canonical ever matches).
    // The bench --dedup-leg's off denominator; on by default.
    const char* denv = getenv("ISTPU_DEDUP");
    dedup_enabled_ = denv == nullptr || denv[0] != '0';
    // Per-index stripe ranks (single-threaded here): cross-stripe ops
    // lock in index order = ascending rank for the runtime checker.
    for (uint32_t i = 0; i < kStripes; ++i) {
        stripes_[i].mu.set_rank(int(kRankStripeBase + i));
    }
    if (disk_ != nullptr) {
        promoter_ = std::make_unique<Promoter>(this, mm_, disk_, tracer_);
    }
}

KVIndex::~KVIndex() { stop_background(); }

// NO_THREAD_SAFETY_ANALYSIS inside: the try-then-block shape (the
// uncontended path must not read a clock) confuses the analysis; the
// ACQUIRE(st.mu) contract on the declaration is what call sites check.
UniqueLock KVIndex::lock_stripe(Stripe& st) NO_THREAD_SAFETY_ANALYSIS {
    UniqueLock lk(st.mu, std::try_to_lock);
    if (!lk.owns_lock()) {
        // Contended: time the wait. The uncontended path above reads
        // no clock and records nothing — the instrumentation's cost
        // lives entirely on the path it exists to measure.
        long long t0 = now_us();
        lk.lock();
        if (tracer_ != nullptr) {
            tracer_->lock_wait(uint64_t(t0), uint64_t(now_us() - t0));
        }
    }
    return lk;
}

Status KVIndex::allocate(const std::string& key, uint32_t size,
                         RemoteBlock* out, uint64_t owner) {
    uint32_t si = stripe_of(key);
    Stripe& st = stripes_[si];
    auto lk = lock_stripe(st);
    // Single hash probe: try_emplace both answers the dedup check and
    // reserves the slot (allocate is the server's hottest op — 4096
    // keys per benchmark batch).
    auto [mit, inserted] = st.map.try_emplace(key);
    if (!inserted) {
        out->status = CONFLICT;
        out->pool_idx = 0;
        out->token = FAKE_TOKEN;
        out->offset = 0;
        out->size = 0;
        return CONFLICT;
    }
    PoolLoc loc;
    bool got = mm_->allocate(size, &loc);
    if (!got && track_lru()) {
        // LAST-RESORT inline reclaim: the background reclaimer normally
        // keeps free blocks ahead of the put path (watermark eviction),
        // so landing here means it could not keep up — count the hard
        // stall, kick it, and make room synchronously from the cold end
        // (spill to the disk tier when present, hard-evict otherwise),
        // then retry once. (Eviction cannot invalidate mit: it only
        // touches committed entries, and this one is uncommitted and
        // not in the LRU.)
        hard_stalls_.fetch_add(1, std::memory_order_relaxed);
        events_emit(EV_HARD_STALL, size, /*promote=*/0);
        kick_reclaimer();
        if (evict_internal(size, int(si), false) > 0) {
            got = mm_->allocate(size, &loc);
        }
    }
    if (!got) {
        st.map.erase(mit);
        out->status = OUT_OF_MEMORY;
        out->pool_idx = 0;
        out->token = FAKE_TOKEN;
        out->offset = 0;
        out->size = 0;
        return OUT_OF_MEMORY;
    }
    auto block = std::make_shared<Block>(mm_, loc, size);
    uint32_t idx;
    if (!st.ifree.empty()) {
        idx = st.ifree.back();
        st.ifree.pop_back();
    } else {
        idx = uint32_t(st.islab.size());
        st.islab.emplace_back();
    }
    Inflight& s = st.islab[idx];
    if (++s.gen == 0) s.gen = 1;  // gen >= 1 keeps every token != FAKE
    s.key = key;
    s.block = block;
    s.size = size;
    s.owner = owner;
    s.live = true;
    st.inflight_live++;
    uint64_t token =
        (uint64_t(s.gen) << 32) | (uint64_t(si) << kSlotBits) | idx;
    Entry e;
    e.block = block;
    e.size = size;
    mit->second = std::move(e);
    out->status = OK;
    out->pool_idx = loc.pool_idx;
    out->token = token;
    out->offset = loc.offset;
    out->size = size;
    // Watermark check AFTER a successful allocation: wake the reclaimer
    // so the NEXT put finds free blocks without ever touching reclaim.
    maybe_wake_reclaimer();
    return OK;
}

uint8_t* KVIndex::write_dest(uint64_t token, uint32_t* size_out,
                             uint64_t owner) {
    Stripe& st = stripes_[stripe_of_token(token)];
    auto lk = lock_stripe(st);
    Inflight* s = islot(st, token);
    if (s == nullptr || s->owner != owner) return nullptr;
    *size_out = s->size;
    // Valid after unlock: the inflight entry pins the Block, and only the
    // owning connection (serialized on its worker) can release the token.
    return static_cast<uint8_t*>(s->block->loc.ptr);
}

Status KVIndex::commit(uint64_t token, uint64_t owner) {
    Stripe& st = stripes_[stripe_of_token(token)];
    auto lk = lock_stripe(st);
    Inflight* s = islot(st, token);
    if (s == nullptr) return CONFLICT;
    // A forged commit must fail closed AND leave the real owner's inflight
    // entry intact so the owner's own commit still lands.
    if (s->owner != owner) return CONFLICT;
    auto mit = st.map.find(s->key);
    Status rc = CONFLICT;
    // Only commit if the map still holds the exact block this token
    // allocated (a purge+reallocate between allocate and commit must not
    // make someone else's bytes visible under this key).
    if (mit != st.map.end() && mit->second.block == s->block) {
        Entry& e = mit->second;
        // Content-addressed dedup: if a live canonical block holds
        // byte-identical content, the entry adopts it and the fresh
        // block frees when the inflight ref drops below (zero extra
        // pool bytes for the duplicate). Otherwise this block becomes
        // the canonical for its content.
        dedup_adopt_or_register(
            &e.block, static_cast<const uint8_t*>(s->block->loc.ptr),
            s->size);
        e.committed = true;
        dedup_block_attached(e.block, s->size);
        logical_bytes_.fetch_add(s->size, std::memory_order_relaxed);
        lru_touch(st, e, mit->first);
        workload_.record_commit(
            hash_of(mit->first),
            static_cast<const uint8_t*>(e.block->loc.ptr),
            wl_round(s->size), mm_, s->size);
        rc = OK;
    }
    // Drops the inflight ref under the stripe lock: for an adopted
    // commit this is the fresh block's LAST ref, returning its bytes
    // to the pool (arena rank 300+a > stripe rank — legal here, and
    // exactly why dedup_mu_ was released before this point).
    ifree(st, s);
    return rc;
}

void KVIndex::abort(uint64_t token, uint64_t owner) {
    Stripe& st = stripes_[stripe_of_token(token)];
    auto lk = lock_stripe(st);
    Inflight* s = islot(st, token);
    if (s == nullptr || s->owner != owner) return;
    auto mit = st.map.find(s->key);
    if (mit != st.map.end() && mit->second.block == s->block &&
        !mit->second.committed) {
        st.map.erase(mit);
    }
    ifree(st, s);
}

size_t KVIndex::abort_all_for_owner(uint64_t owner) {
    size_t n = 0;
    for (Stripe& st : stripes_) {
        ScopedLock lk(st.mu);
        for (Inflight& s : st.islab) {
            if (!s.live || s.owner != owner) continue;
            auto mit = st.map.find(s.key);
            if (mit != st.map.end() && mit->second.block == s.block &&
                !mit->second.committed) {
                st.map.erase(mit);
            }
            ifree(st, &s);
            n++;
        }
    }
    return n;
}

bool KVIndex::peek_committed(const std::string& key, uint32_t* size_out) {
    // Workload recording is split across the two read passes so each
    // logical reference lands EXACTLY once: op_read/op_pin peek here
    // for admission (size/backpressure) and answer a MISS from this
    // pass alone (the acquire below never runs), so the miss records
    // here; a HIT continues into acquire_*, which records it — a hit
    // hook here too would double-count every successful read.
    uint64_t h = hash_of(key);
    Stripe& st = stripes_[uint32_t(h) & (kStripes - 1)];
    auto lk = lock_stripe(st);
    auto it = st.map.find(key);
    if (it == st.map.end() || !it->second.committed) {
        workload_.record_get_miss(h);
        return false;
    }
    // Reads refresh recency (and cancel an in-flight spill — the touch
    // proves the entry hot, so the writer abandons it at completion).
    lru_touch(st, it->second, it->first);
    if (size_out) *size_out = it->second.size;
    return true;
}

Status KVIndex::acquire_block(const std::string& key, bool allow_promote,
                              BlockRef* out, uint32_t* size_out,
                              bool* promoted_out) {
    uint64_t h = hash_of(key);
    uint32_t si = uint32_t(h) & (kStripes - 1);
    Stripe& st = stripes_[si];
    auto lk = lock_stripe(st);
    auto it = st.map.find(key);
    if (it == st.map.end() || !it->second.committed) {
        workload_.record_get_miss(h);
        return KEY_NOT_FOUND;
    }
    Entry& e = it->second;
    const bool nonresident = !e.block;
    if (nonresident && !allow_promote) return BUSY;  // budget spent
    Status rc = ensure_resident(st, si, e, it->first);
    if (rc != OK) return rc;
    // Hit recorded only on the OK path: a BUSY/OOM answer is retried
    // by the client, and counting every retry would inflate the
    // demand model with duplicate zero-distance references for ONE
    // logical reference — exactly in the spill/thrash scenarios this
    // plane exists to diagnose.
    workload_.record_get_hit(h, wl_round(e.size), mm_);
    if (promoted_out) *promoted_out = nonresident;
    *out = e.block;
    if (size_out) *size_out = e.size;
    return OK;
}

Status KVIndex::acquire_read(const std::string& key, BlockRef* out,
                             DiskRef* disk_out,
                             std::shared_ptr<std::vector<uint8_t>>* heap_out,
                             uint32_t* size_out) {
    uint64_t h = hash_of(key);
    uint32_t si = uint32_t(h) & (kStripes - 1);
    Stripe& st = stripes_[si];
    auto lk = lock_stripe(st);
    auto it = st.map.find(key);
    if (it == st.map.end() || !it->second.committed) {
        workload_.record_get_miss(h);
        return KEY_NOT_FOUND;
    }
    Entry& e = it->second;
    workload_.record_get_hit(h, wl_round(e.size), mm_);
    if (size_out) *size_out = e.size;
    if (e.block) {
        lru_touch(st, e, it->first);
        *out = e.block;
        return OK;
    }
    if (e.disk) {
        // Serve straight from the extent, outside all locks (the
        // DiskRef pins it against a concurrent delete/purge/release).
        // Promote on the SECOND touch only: a one-shot scan of a cold
        // working set must not churn hot entries out of the pool.
        *disk_out = e.disk;
        disk_reads_inline_.fetch_add(1, std::memory_order_relaxed);
        if (!e.promoting) {
            if (e.touched) {
                maybe_enqueue_promote(st, e, it->first, si);
            } else {
                e.touched = true;
            }
        }
        return OK;
    }
    if (e.heap) {
        *heap_out = e.heap;
        return OK;
    }
    return INTERNAL_ERROR;  // no location at all: cannot happen
}

Status KVIndex::acquire_resident(const std::string& key, BlockRef* out,
                                 uint32_t* size_out) {
    uint64_t h = hash_of(key);
    uint32_t si = uint32_t(h) & (kStripes - 1);
    Stripe& st = stripes_[si];
    auto lk = lock_stripe(st);
    auto it = st.map.find(key);
    if (it == st.map.end() || !it->second.committed) {
        workload_.record_get_miss(h);
        return KEY_NOT_FOUND;
    }
    Entry& e = it->second;
    if (!e.block && e.disk != nullptr) {
        // Async-promote-and-retry: a PIN is an explicit "I will read
        // this from the pool", so it bypasses second-touch. BUSY is
        // the client's documented retry status — by the backoff retry
        // the worker has adopted the pool copy, and the tier IO never
        // ran on this worker thread.
        const bool worker_live =
            promoter_ != nullptr && promoter_->running() &&
            promoter_->alive();
        if (e.promoting) {
            if (worker_live) return BUSY;
            // The worker died with this key queued (or mid-batch): a
            // BUSY here would wedge the client's retry loop forever.
            // Clear the stale flag and promote inline below — the
            // degraded mode the workers_dead gauge announces.
            e.promoting = false;
        } else if (maybe_enqueue_promote(st, e, it->first, si)) {
            return BUSY;
        }
        if (!e.promoting && worker_live) {
            // Admission refused: the enqueue attempt above already set
            // promotion pressure (the reclaimer frees toward LOW), so
            // BUSY here too — the retry lands with headroom and the
            // promote admits. Falling back to inline promotion instead
            // would put the tier IO right back on this worker under
            // the stripe lock, exactly what the pipeline exists to
            // prevent. If the reclaimer truly cannot free anything
            // (everything pinned), the client's bounded retry surfaces
            // BUSY — retryable, never data loss.
            return BUSY;
        }
        // No worker at all: inline promotion below keeps the
        // historical progress guarantee.
    }
    Status rc = ensure_resident(st, si, e, it->first);
    if (rc != OK) return rc;
    // OK path only (see acquire_block): a BUSY promote-and-retry
    // answer records nothing — the retry that finally lands records
    // the one logical reference.
    workload_.record_get_hit(h, wl_round(e.size), mm_);
    *out = e.block;
    if (size_out) *size_out = e.size;
    return OK;
}

void KVIndex::prefetch(const std::vector<std::string>& keys, uint8_t* out) {
    for (size_t i = 0; i < keys.size(); ++i) {
        uint32_t si = stripe_of(keys[i]);
        Stripe& st = stripes_[si];
        auto lk = lock_stripe(st);
        auto it = st.map.find(keys[i]);
        if (it == st.map.end() || !it->second.committed) {
            out[i] = 0;  // missing
            continue;
        }
        Entry& e = it->second;
        if (e.block) {
            // Resident: refresh recency — the prefetch names pages the
            // engine is about to read; letting the reclaimer evict
            // them now would be self-defeating.
            lru_touch(st, e, it->first);
            out[i] = 1;
        } else if (e.promoting && promoter_ != nullptr &&
                   promoter_->alive()) {
            out[i] = 2;  // already on its way
        } else if (e.disk != nullptr &&
                   maybe_enqueue_promote(st, e, it->first, si,
                                         /*prefetch=*/true)) {
            // Explicit future-use signal: bypass second-touch.
            out[i] = 2;
        } else {
            out[i] = 3;  // disk/limbo, not queued (admission/worker off)
        }
    }
}

bool KVIndex::maybe_enqueue_promote(Stripe& st, Entry& e,
                                    const std::string& key, uint32_t si,
                                    bool prefetch) {
    (void)st;  // the lock fact (REQUIRES(st.mu)) is the parameter's job
    // alive(): a dead worker's queue must not keep accepting items —
    // every DiskRef queued there would pin its extent forever.
    if (promoter_ == nullptr || !promoter_->running() ||
        !promoter_->alive()) {
        return false;
    }
    if (!e.disk || e.promoting) return false;
    // Prefetch-depth knob (controller-tuned): OP_PREFETCH kicks are
    // speculative, so once the promote queue is this deep, further
    // prefetches are refused (out[i]=3 — the get path still serves
    // them from disk). Demand promotes are never depth-gated.
    if (prefetch && io_sched_ != nullptr && io_sched_->enabled()) {
        uint64_t depth = io_sched_->knob(kKnobPrefetchDepth);
        if (depth != 0 && promoter_->queue_depth() >= depth) {
            return false;
        }
    }
    if (!promoter_->may_admit(e.size)) {
        // PROMOTION PRESSURE: the pool rests anywhere in [low, high)
        // between reclaim passes, so headroom to the high watermark can
        // be ~zero indefinitely — without this kick, admission would
        // deadlock promotion on a full-but-not-over-high pool. The flag
        // gives the reclaimer a secondary trigger: drive down to LOW
        // even though HIGH was never crossed, opening (high - low) of
        // headroom for the next prefetch/touch. Still no fighting:
        // promotion never pushes past high, the reclaimer never digs
        // below low — the working set cycles through the pool in
        // bounded, LRU-ordered chunks.
        promote_pressure_.store(true, std::memory_order_relaxed);
        kick_reclaimer();
        return false;
    }
    e.promoting = true;
    promoter_->enqueue(PromoteItem{key, e.disk, e.size, si,
                                   Tracer::thread_trace_id(),
                                   uint64_t(std::hash<std::string>{}(key)),
                                   prefetch});
    return true;
}

bool KVIndex::finish_promote(PromoteItem& item, BlockRef block) {
    Stripe& st = stripes_[item.stripe];
    ScopedLock lk(st.mu);
    auto mit = st.map.find(item.key);
    if (mit == st.map.end()) return false;  // erased/purged: RAII frees
    Entry& e = mit->second;
    if (block && e.promoting && e.committed && !e.block &&
        e.disk == item.disk) {
        // Adopt: the bytes are already in the block (read from the
        // queue-pinned extent outside every lock). No epoch bump —
        // promotion never invalidates a cached pool location (the
        // entry had none while disk-resident).
        e.block = std::move(block);
        dedup_block_attached(e.block, e.size);  // re-materialized hold
        e.disk.reset();  // item.disk still pins the extent until dropped
        e.promoting = false;
        e.touched = false;
        promotes_.fetch_add(1, std::memory_order_relaxed);
        // Thrash detection: a promote of a recently-SPILLED key is a
        // spill->promote round trip that paid two tier IOs for
        // nothing the reclaimer could not have predicted... except it
        // could, which is what the workload.thrash_cycles counter
        // (and the watchdog.thrash verdict over it) exists to say.
        workload_.record_promote(item.key_hash);
        lru_touch(st, e, mit->first);
        return true;
    }
    // Cancelled (re-put under a new extent, inline-promoted meanwhile,
    // alloc/IO failure): clear the flag only when it belongs to THIS
    // promotion cycle — a newer spill cycle's queued promote owns it
    // otherwise.
    if (e.promoting && (e.disk == item.disk || e.disk == nullptr)) {
        e.promoting = false;
    }
    return false;
}

void KVIndex::cancel_promote_flag(const PromoteItem& item) {
    Stripe& st = stripes_[item.stripe];
    ScopedLock lk(st.mu);
    auto mit = st.map.find(item.key);
    if (mit == st.map.end()) return;
    Entry& e = mit->second;
    if (e.promoting && (e.disk == item.disk || e.disk == nullptr)) {
        e.promoting = false;
    }
}

Status KVIndex::ensure_resident(Stripe& st, uint32_t stripe_idx, Entry& e,
                                const std::string& key) {
    if (!e.block) {
        // PROMOTE span: the whole disk->pool promotion (pool alloc +
        // tier IO + adoption), recorded on the calling WORKER's ring —
        // this runs inline on the reading worker under the stripe
        // lock, which is exactly the cold-read tail the ROADMAP's
        // async-promotion item wants made visible. The clock reads are
        // gated: a promotion is already tier-IO-slow, but the
        // tracing-off path stays byte-identical to before.
        const bool trace = tracer_ != nullptr && tracer_->enabled();
        long long tp0 = trace ? now_us() : 0;
        // Spilled (disk) or in heap limbo: promote back into the pool
        // (which may itself spill or evict colder entries — this entry
        // is not in the LRU while non-resident, so it cannot become its
        // own victim).
        PoolLoc loc;
        bool got = mm_->allocate(e.size, &loc);
        if (!got) {
            // Promotion found no free blocks: another hard stall the
            // watermark reclaimer should have prevented.
            hard_stalls_.fetch_add(1, std::memory_order_relaxed);
            events_emit(EV_HARD_STALL, e.size, /*promote=*/1);
            kick_reclaimer();
            if (evict_internal(e.size, int(stripe_idx), false) > 0) {
                got = mm_->allocate(e.size, &loc);
            }
        }
        if (got) {
            auto block = std::make_shared<Block>(mm_, loc, e.size);
            if (e.heap) {
                memcpy(loc.ptr, e.heap->data(), e.size);
                e.heap.reset();
            } else {
                long long tio = trace ? now_us() : 0;
                disk_reads_inline_.fetch_add(1, std::memory_order_relaxed);
                bool io_ok = e.disk != nullptr &&
                             e.disk->tier->load(e.disk->off, loc.ptr,
                                                e.size);
                if (trace) {
                    tracer_->record(SPAN_DISK_IO, 0, uint64_t(tio),
                                    uint64_t(now_us() - tio));
                }
                if (!io_ok) {
                    return INTERNAL_ERROR;  // IO error; block freed by RAII
                }
            }
            e.block = std::move(block);
            dedup_block_attached(e.block, e.size);  // re-materialized
            e.disk.reset();  // frees the disk extent
        } else if (e.heap) {
            // Already in limbo and the pool is still full: retryable.
            return OUT_OF_MEMORY;
        } else if (e.disk) {
            // Pool AND disk full: bounce-swap. Lift this entry's bytes
            // into a temp buffer, free its disk extent, spill a cold
            // resident victim into that space, then land here in the pool
            // — a read must not fail just because both tiers are at
            // capacity.
            std::vector<uint8_t> tmp(e.size);
            disk_reads_inline_.fetch_add(1, std::memory_order_relaxed);
            if (!e.disk->tier->load(e.disk->off, tmp.data(), e.size)) {
                return INTERNAL_ERROR;
            }
            e.disk.reset();
            if (evict_internal(e.size, int(stripe_idx), false) > 0) {
                got = mm_->allocate(e.size, &loc);
            }
            if (!got) {
                // Could not land in the pool (everything pinned, or the
                // freed blocks are not contiguous). Park the bytes back:
                // on disk if the extent is still free, else in RAM limbo
                // — a committed entry is never dropped.
                int64_t off = disk_->store(tmp.data(), e.size);
                if (off >= 0) {
                    e.disk = std::make_shared<DiskSpan>(disk_, off, e.size);
                } else {
                    e.heap = std::make_shared<std::vector<uint8_t>>(
                        std::move(tmp));
                }
                return OUT_OF_MEMORY;  // retryable
            }
            auto block = std::make_shared<Block>(mm_, loc, e.size);
            memcpy(loc.ptr, tmp.data(), e.size);
            e.block = std::move(block);
            dedup_block_attached(e.block, e.size);  // re-materialized
        } else {
            return INTERNAL_ERROR;  // no location at all: cannot happen
        }
        promotes_.fetch_add(1, std::memory_order_relaxed);
        workload_.record_promote(hash_of(key));
        // An inline promotion supersedes any queued async one (its
        // finish finds the entry resident and cancels); the flags
        // restart for the next spill cycle.
        e.promoting = false;
        e.touched = false;
        if (trace) {
            tracer_->record(SPAN_PROMOTE, 0, uint64_t(tp0),
                            uint64_t(now_us() - tp0));
        }
    }
    lru_touch(st, e, key);
    return OK;
}

bool KVIndex::check_exist(const std::string& key) {
    // A demand signal in its own right: the serving engine's admission
    // probes land here, and a miss on a recently-evicted key is
    // exactly the premature eviction the ghost ring exists to name.
    // Own lookup (not peek_committed): one hash serves the stripe,
    // the ghost probe and the sampler — and both workload hooks run
    // AFTER the stripe lock drops.
    uint64_t h = hash_of(key);
    Stripe& st = stripes_[uint32_t(h) & (kStripes - 1)];
    uint32_t sz = 0;
    bool hit = false;
    {
        auto lk = lock_stripe(st);
        auto it = st.map.find(key);
        if (it != st.map.end() && it->second.committed) {
            lru_touch(st, it->second, it->first);
            sz = it->second.size;
            hit = true;
        }
    }
    if (!hit) {
        workload_.record_get_miss(h);
        return false;
    }
    workload_.record_get_hit(h, wl_round(sz), mm_);
    return true;
}

int KVIndex::match_last_index(const std::vector<std::string>& keys) const {
    // Cross-stripe read: take every stripe lock in index order so the
    // probe sequence sees one consistent cut of the store.
    std::vector<UniqueLock> locks;
    locks.reserve(kStripes);
    for (const Stripe& st : stripes_) locks.emplace_back(st.mu);
    auto present = [this](const std::string& k) {
        return stripes_[stripe_of(k)].map.count(k) > 0;
    };
    if (eviction_) {
        // LRU eviction can remove any key, so presence is no longer
        // monotone over the chain and a binary search could report a
        // prefix whose middle keys are gone. Linear scan for the first
        // hole instead — n is small (pages of one sequence) and each
        // probe is one hash lookup.
        int last = -1;
        for (size_t i = 0; i < keys.size(); ++i) {
            if (!present(keys[i])) break;
            last = int(i);
        }
        return last;
    }
    // Without eviction keys are only removed by explicit purge/delete, so
    // the reference's binary-search semantics hold (prefix chains are
    // written front-to-back; infinistore.cpp:1092-1108).
    int left = 0, right = int(keys.size());
    while (left < right) {
        int mid = left + (right - left) / 2;
        if (present(keys[size_t(mid)])) {
            left = mid + 1;
        } else {
            right = mid;
        }
    }
    return left - 1;
}

void KVIndex::reserve(size_t extra) {
    size_t per = extra / kStripes + 1;
    for (Stripe& st : stripes_) {
        ScopedLock lk(st.mu);
        st.map.reserve(st.map.size() + per);
        st.islab.reserve(st.islab.size() + per);
    }
}

uint64_t KVIndex::pin(std::vector<BlockRef> blocks) {
    ScopedLock lk(leases_mu_);
    uint64_t id = next_lease_++;
    leases_[id] = std::move(blocks);
    return id;
}

bool KVIndex::release(uint64_t lease_id) {
    ScopedLock lk(leases_mu_);
    return leases_.erase(lease_id) > 0;
}

uint32_t KVIndex::ring_hash(const std::string& key) {
    // Standard CRC-32 (reflected 0xEDB88320), byte-identical to
    // Python's zlib.crc32 — the shared ring coordinate. Table built
    // once; the cluster paths that call this are control-plane-rate.
    static const uint32_t* table = [] {
        static uint32_t t[256];
        for (uint32_t i = 0; i < 256; ++i) {
            uint32_t c = i;
            for (int k = 0; k < 8; ++k) {
                c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
            }
            t[i] = c;
        }
        return t;
    }();
    uint32_t crc = 0xFFFFFFFFu;
    for (unsigned char ch : key) {
        crc = table[(crc ^ ch) & 0xFFu] ^ (crc >> 8);
    }
    return crc ^ 0xFFFFFFFFu;
}

std::vector<KVIndex::SnapshotItem> KVIndex::snapshot_items(
    uint64_t ring_lo, uint64_t ring_hi) const {
    const bool whole_ring = ring_lo == 0 && ring_hi >= kRingSpan;
    std::vector<UniqueLock> locks;
    locks.reserve(kStripes);
    for (const Stripe& st : stripes_) locks.emplace_back(st.mu);
    std::vector<SnapshotItem> out;
    for (const Stripe& st : stripes_) {
        out.reserve(out.size() + st.map.size());
        for (const auto& [key, e] : st.map) {
            if (!e.committed) continue;
            if (!whole_ring &&
                !ring_in_range(ring_hash(key), ring_lo, ring_hi)) {
                continue;
            }
            SnapshotItem it;
            it.key = key;
            it.block = e.block;
            it.disk = e.disk;
            it.heap = e.heap;
            it.size = e.size;
            if (it.block || it.disk || it.heap) out.push_back(std::move(it));
        }
    }
    return out;
}

Status KVIndex::insert_committed(const std::string& key, const uint8_t* data,
                                 uint32_t size) {
    Stripe& st = stripes_[stripe_of(key)];
    ScopedLock lk(st.mu);
    auto [mit, inserted] = st.map.try_emplace(key);
    if (!inserted) return CONFLICT;  // live data beats snapshot data
    Entry e;
    // Snapshot/migration restore re-dedups: hash BEFORE allocating so
    // a restored duplicate adopts the canonical block with ZERO pool
    // allocation — a snapshot round-trip of refcounted blocks restores
    // the physical sharing, not N private copies.
    uint64_t h1 = 0, h2 = 0;
    const bool hashed = dedup_enabled_ && size > 0;
    if (hashed) content_hash128(data, size, &h1, &h2);
    BlockRef canon;
    if (hashed && dedup_lookup(h1, h2, size, &canon) &&
        memcmp(canon->loc.ptr, data, size) == 0) {
        e.block = std::move(canon);
        dedup_hits_.fetch_add(1, std::memory_order_relaxed);
        dedup_bytes_saved_.fetch_add(size, std::memory_order_relaxed);
    } else {
        canon.reset();  // aliased lookup survivor, if any (stripe held)
        PoolLoc loc;
        // no evict_lru: see header contract
        if (!mm_->allocate(size, &loc)) {
            st.map.erase(mit);
            return OUT_OF_MEMORY;
        }
        memcpy(loc.ptr, data, size);
        e.block = std::make_shared<Block>(mm_, loc, size);
        if (hashed) dedup_register(h1, h2, size, e.block);
    }
    e.size = size;
    e.committed = true;
    mit->second = std::move(e);
    dedup_block_attached(mit->second.block, size);
    logical_bytes_.fetch_add(size, std::memory_order_relaxed);
    if (track_lru()) lru_touch(st, mit->second, mit->first);
    return OK;
}

Status KVIndex::insert_leased(const std::string& key, const PoolLoc& loc,
                              uint32_t size) {
    uint64_t h = hash_of(key);
    Stripe& st = stripes_[uint32_t(h) & (kStripes - 1)];
    auto lk = lock_stripe(st);
    auto [mit, inserted] = st.map.try_emplace(key);
    if (!inserted) return CONFLICT;  // first-writer-wins
    Entry e;
    e.block = std::make_shared<Block>(mm_, loc, size);
    // Content-addressed dedup: adopting a canonical drops the ONLY ref
    // to the fresh wrapper right here (stripe held, arena ranks above
    // stripes) — the client's leased blocks return to the pool and the
    // duplicate costs zero pool bytes.
    dedup_adopt_or_register(
        &e.block, static_cast<const uint8_t*>(loc.ptr), size);
    e.size = size;
    e.committed = true;
    mit->second = std::move(e);
    dedup_block_attached(mit->second.block, size);
    logical_bytes_.fetch_add(size, std::memory_order_relaxed);
    if (track_lru()) lru_touch(st, mit->second, mit->first);
    workload_.record_commit(
        h, static_cast<const uint8_t*>(mit->second.block->loc.ptr),
        wl_round(size), mm_, size);
    return OK;
}

// --- content-addressed dedup (docs/design.md "Content-addressed
// dedup") ------------------------------------------------------------

bool KVIndex::dedup_lookup(uint64_t h1, uint64_t h2, uint32_t size,
                           BlockRef* canon) {
    if (!dedup_enabled_ || size == 0) return false;
    BlockRef cand;
    {
        // STRICT leaf discipline (lock_rank.h rank 370): only the map
        // probe and the weak->strong upgrade happen under dedup_mu_.
        // The ref moves OUT before any drop can happen — dropping a
        // last BlockRef takes a pool-arena mutex (rank 300+a), which
        // would invert the order under this lock.
        ScopedLock lk(dedup_mu_);
        auto it = dedup_map_.find(h1);
        if (it == dedup_map_.end()) return false;
        if (it->second.h2 != h2 || it->second.size != size) return false;
        cand = it->second.block.lock();
        if (!cand) {
            dedup_map_.erase(it);  // canonical died: lazy cleanup
            return false;
        }
    }
    *canon = std::move(cand);
    return true;
}

void KVIndex::dedup_register(uint64_t h1, uint64_t h2, uint32_t size,
                             const BlockRef& b) {
    if (!dedup_enabled_ || size == 0 || !b) return;
    ScopedLock lk(dedup_mu_);
    DedupSlot& s = dedup_map_[h1];
    // First writer wins while the incumbent lives (mirrors the key
    // map's rule); an expired incumbent is replaced in place.
    if (s.block.expired()) {
        s.block = b;
        s.h2 = h2;
        s.size = size;
    }
    if (++dedup_registrations_ % kDedupSweepEvery == 0) {
        // Amortized sweep: expired weak_ptrs cost only control-block
        // frees (heap, no pool locks), safe under the leaf mutex.
        for (auto it = dedup_map_.begin(); it != dedup_map_.end();) {
            if (it->second.block.expired()) {
                it = dedup_map_.erase(it);
            } else {
                ++it;
            }
        }
    }
}

bool KVIndex::dedup_adopt_or_register(BlockRef* slot,
                                      const uint8_t* payload,
                                      uint32_t size) {
    if (!dedup_enabled_ || size == 0 || !*slot) return false;
    uint64_t h1 = 0, h2 = 0;
    content_hash128(payload, size, &h1, &h2);
    BlockRef canon;
    if (dedup_lookup(h1, h2, size, &canon) && canon != *slot &&
        memcmp(canon->loc.ptr, payload, size) == 0) {
        // Byte-verified duplicate: adopt. The swapped-out ref drops
        // here or at the caller's unwind — under the stripe lock,
        // where pool-arena acquisition is legal.
        *slot = std::move(canon);
        dedup_hits_.fetch_add(1, std::memory_order_relaxed);
        dedup_bytes_saved_.fetch_add(size, std::memory_order_relaxed);
        return true;
    }
    // Miss (or a 128-bit alias that failed the memcmp — counted
    // nowhere: the workload estimator's aliasing is exactly what the
    // cross-validation test scores): this block becomes canonical.
    dedup_register(h1, h2, size, *slot);
    return false;
}

void KVIndex::dedup_block_attached(const BlockRef& b, uint32_t size) {
    if (!dedup_enabled_ || !b) return;
    // Second-or-later committed sharer: these bytes ride an existing
    // block — live savings grow. First sharer owns the physical bytes.
    if (b->dedup_sharers.fetch_add(1, std::memory_order_relaxed) >= 1) {
        dedup_saved_live_.fetch_add(size, std::memory_order_relaxed);
    }
}

void KVIndex::dedup_block_released(Entry& e) {
    if (!dedup_enabled_ || !e.block) return;
    // Sharers remain after this hold ends: the DEPARTING entry's
    // bytes were the shared ones (ownership of the physical bytes
    // passes to a survivor — which entry attached first is
    // irrelevant). Last hold out: the block leaves with its owner,
    // savings unchanged.
    if (e.block->dedup_sharers.fetch_sub(1, std::memory_order_relaxed)
        >= 2) {
        dedup_saved_live_.fetch_sub(e.size, std::memory_order_relaxed);
    }
}

void KVIndex::dedup_entry_removed(Entry& e) {
    if (!e.committed) return;
    logical_bytes_.fetch_sub(e.size, std::memory_order_relaxed);
    dedup_block_released(e);
}

int KVIndex::put_by_hash(const std::string& key, uint32_t size,
                         uint64_t h1, uint64_t h2) {
    uint64_t h = hash_of(key);
    Stripe& st = stripes_[uint32_t(h) & (kStripes - 1)];
    auto lk = lock_stripe(st);
    auto mit = st.map.find(key);
    if (mit != st.map.end()) {
        // Committed or inflight: the put is already satisfied
        // first-writer-wins style (the allocate path would have
        // answered CONFLICT/FAKE_TOKEN) — no payload wanted.
        return 2;  // EXISTS
    }
    BlockRef canon;
    if (!dedup_lookup(h1, h2, size, &canon)) {
        // No canonical: payload must follow on the normal put path.
        // Nothing is reserved here on purpose — two clients probing
        // the same key race to the ordinary allocate, where
        // first-writer-wins already resolves it; a reservation would
        // only add an orphan state to clean up.
        dedup_hash_misses_.fetch_add(1, std::memory_order_relaxed);
        return 0;  // NEED
    }
    // HAVE: commit the key by adopting the canonical block — zero
    // pool bytes, zero payload transfer. This trusts the client's
    // 128-bit hash claim (there are no bytes to memcmp); see the
    // design.md security note.
    Entry e;
    e.block = std::move(canon);
    e.size = size;
    e.committed = true;
    const uint8_t* payload =
        static_cast<const uint8_t*>(e.block->loc.ptr);
    auto [nit, inserted] = st.map.try_emplace(key, std::move(e));
    (void)inserted;  // find() above miss + stripe lock held => inserts
    dedup_block_attached(nit->second.block, size);
    logical_bytes_.fetch_add(size, std::memory_order_relaxed);
    dedup_hits_.fetch_add(1, std::memory_order_relaxed);
    dedup_hash_hits_.fetch_add(1, std::memory_order_relaxed);
    dedup_bytes_saved_.fetch_add(size, std::memory_order_relaxed);
    if (track_lru()) lru_touch(st, nit->second, nit->first);
    workload_.record_commit(h, payload, wl_round(size), mm_, size);
    return 1;  // HAVE
}

size_t KVIndex::purge() {
    size_t n = 0;
    {
        // Cross-stripe write: all stripe locks in index order; each
        // stripe's LRU segment clears with its map.
        std::vector<UniqueLock> locks;
        locks.reserve(kStripes);
        for (Stripe& st : stripes_) locks.emplace_back(st.mu);
        for (Stripe& st : stripes_) {
            n += st.map.size();
            st.map.clear();
            st.lru.clear();
            st.tail_age.store(UINT64_MAX, std::memory_order_relaxed);
        }
        // Dedup plane resets with the entries (no commit can race: all
        // stripe locks are held). Cumulative hit counters survive like
        // the other counters; the live gauges and the canonical map
        // go with the data they described.
        logical_bytes_.store(0, std::memory_order_relaxed);
        dedup_saved_live_.store(0, std::memory_order_relaxed);
        {
            ScopedLock dlk(dedup_mu_);
            dedup_map_.clear();
        }
    }
    // Determinism barrier, after the stripe locks drop (the writer
    // needs them): queued spills of now-purged entries are dropped and
    // the writer's in-flight batch finishes, so when purge returns no
    // writer ref keeps purged pool blocks (or disk extents) alive —
    // used_bytes/disk_used read 0 immediately after a purge. The
    // promotion queue gets the same treatment: its items pin disk
    // extents (DiskRefs) and its in-flight batch holds fresh pool
    // blocks.
    cancel_queued_spills();
    if (promoter_) promoter_->cancel_queued();
    // Workload profiler: ghost rings + reuse stacks clear (the keys
    // are gone; cross-purge distances are meaningless), cumulative
    // demand counters survive — pinned by tests/test_workload.py.
    workload_.on_purge();
    if (n) bump_epoch();
    return n;
}

size_t KVIndex::reclaim_orphans(const std::vector<std::string>& keys) {
    // Group per stripe: a key's inflight token always lives in the key's
    // own stripe, so each stripe's live-block set is built once under
    // that stripe's lock and consulted only for its own keys.
    std::vector<const std::string*> per_stripe[kStripes];
    for (const auto& k : keys) per_stripe[stripe_of(k)].push_back(&k);
    size_t n = 0;
    for (uint32_t si = 0; si < kStripes; ++si) {
        if (per_stripe[si].empty()) continue;
        Stripe& st = stripes_[si];
        ScopedLock lk(st.mu);
        std::unordered_set<const Block*> live;
        live.reserve(st.inflight_live);
        for (const Inflight& s : st.islab) {
            if (s.live) live.insert(s.block.get());
        }
        for (const std::string* k : per_stripe[si]) {
            auto it = st.map.find(*k);
            if (it == st.map.end() || it->second.committed) continue;
            if (it->second.block && live.count(it->second.block.get())) {
                continue;
            }
            lru_drop(st, it->second);
            st.map.erase(it);
            n++;
        }
    }
    return n;
}

size_t KVIndex::erase(const std::vector<std::string>& keys) {
    size_t n = 0;
    for (auto& k : keys) {
        Stripe& st = stripes_[stripe_of(k)];
        auto lk = lock_stripe(st);
        auto it = st.map.find(k);
        if (it == st.map.end()) continue;
        // Bump BEFORE the entry's blocks are freed, once PER committed
        // entry: with per-stripe locking another worker can reallocate
        // the blocks the instant the erase drops the BlockRef, and a
        // pin-cache client validating against a not-yet-bumped epoch
        // would accept a stale read — including a client that cached a
        // LATER key of this same batch after an earlier bump. (Only
        // committed entries can live in a pin cache; deleting
        // uncommitted ones never invalidates a cached location. Under
        // the old single store lock this ordering came for free —
        // reallocation needed the same lock.)
        if (it->second.committed) bump_epoch();
        // Explicit delete: clear any ghost/spill-ring slot so a later
        // miss on this key is the CLIENT's doing, never counted
        // against the reclaimer's eviction quality.
        workload_.forget(hash_of(k));
        dedup_entry_removed(it->second);
        lru_drop(st, it->second);
        st.map.erase(it);
        n++;
    }
    return n;
}

size_t KVIndex::erase_range(uint64_t ring_lo, uint64_t ring_hi) {
    // Migration-commit cleanup: drop the moved range from this (source)
    // shard. Stripe at a time — the moved keys' readers have already
    // been re-routed by the directory epoch bump, so there is no
    // consistency window to close beyond the per-entry epoch bump
    // erase() also does.
    size_t n = 0;
    for (Stripe& st : stripes_) {
        std::vector<std::string> victims;
        {
            ScopedLock lk(st.mu);
            for (const auto& [key, e] : st.map) {
                if (e.committed &&
                    ring_in_range(ring_hash(key), ring_lo, ring_hi)) {
                    victims.push_back(key);
                }
            }
        }
        // Reuse erase(): per-key stripe lock, epoch-bump-before-free,
        // ghost-ring forget — the migration evict must not read as the
        // reclaimer's eviction quality.
        n += erase(victims);
    }
    return n;
}

uint64_t KVIndex::digest_range(uint64_t ring_lo, uint64_t ring_hi,
                               uint64_t* count, uint64_t* bytes) const {
    // splitmix64 finalizer over the per-entry word before the xor
    // accumulate: raw xor of structured hashes cancels too easily
    // (two entries differing only in one size bit), the finalizer
    // decorrelates every input bit first.
    auto fin = [](uint64_t x) {
        x ^= x >> 30;
        x *= 0xBF58476D1CE4E5B9ull;
        x ^= x >> 27;
        x *= 0x94D049BB133111EBull;
        x ^= x >> 31;
        return x;
    };
    uint64_t acc = 0, n = 0, b = 0;
    for (const Stripe& st : stripes_) {
        ScopedLock lk(st.mu);
        for (const auto& [key, e] : st.map) {
            if (!e.committed ||
                !ring_in_range(ring_hash(key), ring_lo, ring_hi)) {
                continue;
            }
            // FNV-1a 64 over the key bytes: deterministic across
            // processes (std::hash is not contractually so).
            uint64_t h = 0xCBF29CE484222325ull;
            for (unsigned char ch : key) {
                h = (h ^ ch) * 0x100000001B3ull;
            }
            acc ^= fin(h ^ (uint64_t(e.size) * 0x9E3779B97F4A7C15ull));
            n++;
            b += e.size;
        }
    }
    if (count != nullptr) *count = n;
    if (bytes != nullptr) *bytes = b;
    return acc;
}

size_t KVIndex::size() const {
    size_t n = 0;
    for (const Stripe& st : stripes_) {
        ScopedLock lk(st.mu);
        n += st.map.size();
    }
    return n;
}

size_t KVIndex::inflight() const {
    size_t n = 0;
    for (const Stripe& st : stripes_) {
        ScopedLock lk(st.mu);
        n += st.inflight_live;
    }
    return n;
}

size_t KVIndex::leases() const {
    ScopedLock lk(leases_mu_);
    return leases_.size();
}

void KVIndex::lru_touch(Stripe& st, Entry& e, const std::string& key) {
    // Disk-resident entries stay out of the LRU: there is nothing to
    // evict or spill until a read promotes them back.
    if (!track_lru() || !e.block) return;
    // A touch proves the entry hot: cancel any in-flight spill (the
    // writer abandons it at its completion check and releases the
    // extent) — a get on a SPILLING key reads the still-resident block.
    e.spilling = false;
    uint64_t age = lru_clock_.fetch_add(1, std::memory_order_relaxed);
    if (e.in_lru) {
        // splice: move the node in place, no allocation on the hot path.
        st.lru.splice(st.lru.begin(), st.lru, e.lru_it);
        e.lru_it->age = age;
    } else {
        st.lru.push_front(LruNode{key, age});
        e.lru_it = st.lru.begin();
        e.in_lru = true;
    }
    st.tail_age.store(st.lru.back().age, std::memory_order_relaxed);
}

void KVIndex::lru_drop(Stripe& st, Entry& e) {
    if (!track_lru() || !e.in_lru) return;
    st.lru.erase(e.lru_it);
    e.in_lru = false;
    st.tail_age.store(st.lru.empty() ? UINT64_MAX : st.lru.back().age,
                      std::memory_order_relaxed);
}

uint64_t KVIndex::oldest_eligible_age(uint32_t si, bool held,
                                      uint32_t disk_min_fail) {
    Stripe& st = stripes_[si];
    UniqueLock slk;
    if (!held) {
        slk = UniqueLock(st.mu, std::try_to_lock);
        if (!slk.owns_lock()) return UINT64_MAX;  // busy: skip this pass
    }
    for (auto it = st.lru.rbegin(); it != st.lru.rend(); ++it) {
        auto mit = st.map.find(it->key);
        if (mit == st.map.end() || !mit->second.block) continue;
        const Entry& e = mit->second;
        if (block_pinned(e)) continue;  // pinned / queued spill
        if (block_shared(e) && disk_ != nullptr) continue;  // never spills
        if (!eviction_ && !(disk_ != nullptr && e.size < disk_min_fail)) {
            continue;  // spill-only mode and the tier refused this size
        }
        return it->age;
    }
    return UINT64_MAX;
}

size_t KVIndex::evict_from_stripe(uint32_t si, bool held, size_t want,
                                  uint64_t age_limit, size_t max_victims,
                                  uint32_t* disk_min_fail, bool async_spill,
                                  size_t* victims) {
    Stripe& st = stripes_[si];
    UniqueLock slk;
    if (!held) {
        slk = UniqueLock(st.mu, std::try_to_lock);
        if (!slk.owns_lock()) return 0;  // busy: skipped this pass
    }
    const size_t bs = mm_->block_size();
    // spill_alive_ (not joinable()): a writer thread that DIED is
    // still joinable, and queueing to it would pin victims' blocks
    // behind a queue nothing drains.
    const bool use_async =
        async_spill && disk_ != nullptr &&
        spill_alive_.load(std::memory_order_relaxed);
    size_t freed = 0;
    size_t local_victims = 0;
    auto it = st.lru.rbegin();
    while (it != st.lru.rend() && freed < want &&
           local_victims < max_victims && it->age <= age_limit) {
        auto mit = st.map.find(it->key);
        if (mit == st.map.end() || !mit->second.block ||
            !mit->second.in_lru) {
            // Defensive only: every erase/spill drops its node in place.
            if (mit != st.map.end() && mit->second.in_lru) {
                mit->second.in_lru = false;  // node dies below
            }
            it = std::reverse_iterator(st.lru.erase(std::next(it).base()));
            continue;
        }
        Entry& e = mit->second;
        // Skip entries whose blocks are pinned (reads in flight — or a
        // queued spill — hold extra refs): their memory would not
        // return to the pool yet.
        // A block shared through content-addressed dedup is held once
        // by EVERY committed sharer's entry: those holds are no pins.
        // Counting them as pins left every sharer unevictable, its
        // node stuck at its stripe's cold tail, the tail's age stale —
        // and with one such node a stripe the strict pass below found
        // nothing, so the relaxed pass swept whole stripes, fresh
        // entries included (PERF.md, PR 35). Without a disk tier a
        // sharer is hard-evicted like any victim (its bytes stay with
        // the survivors, so nothing is counted as freed); with a tier
        // it is skipped as before (a shared block never spills).
        const bool shared = block_shared(e);
        if (block_pinned(e) || (shared && disk_ != nullptr)) {
            ++it;
            continue;
        }
        // use_count()==1 with the flag still set means the writer
        // dropped the item (shutdown) or completion raced a cancel:
        // stale — this is a normal victim again.
        e.spilling = false;
        // Spill to the disk tier first; hard-evict only when there is no
        // tier or this victim cannot be stored (full/fragmented/EIO).
        // Epoch ordering, both branches: bump BEFORE this victim's pool
        // blocks are released, once PER victim — another worker's
        // allocate can reuse the blocks the instant they free, and a
        // pin-cache client that cached a later victim between two
        // releases of this same pass would otherwise validate a stale
        // read against the earlier bump.
        bool spilled = false;
        if (disk_ != nullptr && e.size < *disk_min_fail) {
            if (use_async && spill_may_fit(e.size)) {
                // SPILLING: the entry stays readable (block still set);
                // the writer pays the IO outside all index locks and
                // frees the pool blocks at completion. It stays in the
                // LRU so a failed/cancelled spill remains evictable;
                // later selection passes skip it via the queue's ref.
                // (The workload profiler notes the spill at ADOPTION,
                // finish_spill — a cancelled spill is not a round
                // trip.)
                e.spilling = true;
                enqueue_spill(it->key, e.block, e.size, si);
                freed += (size_t(e.size) + bs - 1) / bs * bs;
                local_victims++;
                ++it;
                continue;
            }
            if (use_async) {
                // Tier known-full for this size since the last release:
                // skip the futile queue round trip — treat exactly like
                // a failed synchronous store below.
                *disk_min_fail = e.size;
            } else {
                int64_t off = disk_->store(e.block->loc.ptr, e.size);
                if (off >= 0) {
                    e.disk = std::make_shared<DiskSpan>(disk_, off, e.size);
                    bump_epoch();  // before the blocks return to the pool
                    dedup_block_released(e);  // disk copy is private again
                    e.block.reset();  // frees the pool blocks
                    e.touched = false;  // second-touch restarts per cycle
                    spilled = true;
                    spills_.fetch_add(1, std::memory_order_relaxed);
                    workload_.record_spill(hash_of(it->key));
                } else {
                    // Smallest size the tier refused this pass: a failed
                    // 4-block store must not stop 1-block victims from
                    // spilling into remaining space.
                    *disk_min_fail = e.size;
                }
            }
        }
        if (!spilled && !eviction_) {
            // Spill-only mode (SSD tier without enable_eviction): never
            // drop committed data — keep walking, a smaller victim may
            // still fit the tier.
            ++it;
            continue;
        }
        // Count the block-granular pool footprint, not the logical size —
        // a 4 KB value in a 64 KB-block pool frees a whole block.
        if (!shared) freed += (size_t(e.size) + bs - 1) / bs * bs;
        // Remove the victim from the LRU in place and keep walking
        // coldward from the same position (restarting at rbegin would
        // re-scan every pinned cold entry per eviction).
        auto fwd = std::next(it).base();
        e.in_lru = false;
        if (!spilled) {
            // Ghost the victim BEFORE the erase: a later get-miss on
            // this hash reads as a premature eviction (the reclaimer
            // dropped something the workload still wanted).
            workload_.record_evict(hash_of(it->key));
            bump_epoch();  // before map.erase drops the blocks
            dedup_entry_removed(e);
            st.map.erase(mit);
            evictions_.fetch_add(1, std::memory_order_relaxed);
        }
        it = std::reverse_iterator(st.lru.erase(fwd));
        local_victims++;
    }
    st.tail_age.store(st.lru.empty() ? UINT64_MAX : st.lru.back().age,
                      std::memory_order_relaxed);
    *victims += local_victims;
    return freed;
}

size_t KVIndex::evict_internal(size_t want, int held_stripe,
                               bool async_spill, uint64_t age_cap) {
    size_t victims = 0;
    size_t freed = 0;
    uint32_t disk_min_fail = UINT32_MAX;
    if (exact_lru_) {
        // Exact global order (ISTPU_EXACT_LRU=1): re-pick the globally
        // oldest ELIGIBLE entry for every single victim. Each pick walks
        // the stripes' cold ends under their locks — O(stripes + pinned)
        // per victim, the price of exactness.
        int stale = 0;
        while (freed < want) {
            int best = -1;
            uint64_t best_age = UINT64_MAX;
            for (uint32_t si = 0; si < kStripes; ++si) {
                uint64_t age = oldest_eligible_age(
                    si, int(si) == held_stripe, disk_min_fail);
                if (age < best_age) {
                    best_age = age;
                    best = int(si);
                }
            }
            if (best < 0 || best_age > age_cap) break;
            uint32_t prev_fail = disk_min_fail;
            size_t got = evict_from_stripe(
                uint32_t(best), best == held_stripe, want - freed, best_age,
                1, &disk_min_fail, async_spill, &victims);
            freed += got;
            if (got == 0 && disk_min_fail == prev_fail) {
                // The candidate raced away between the eligibility scan
                // and the evict re-lock (another worker touched it, or
                // grabbed the stripe). Other stripes still hold eligible
                // victims — re-scan, bounded so a persistently busy
                // stripe cannot spin this pass forever.
                if (++stale > int(kStripes) * 4) break;
                continue;
            }
            stale = 0;
        }
        return victims;
    }
    // Approximate (default): the lock-free per-stripe tail-age counters
    // pick the stripe whose coldest entry is globally oldest; victims
    // then drain from that stripe's cold end while still older than
    // every OTHER stripe's tail. With no pinned entries and no try-lock
    // skips this equals exact global order (each drained victim is
    // older than everything in every other stripe); pinned cold tails
    // are where it deviates — they can hide younger evictables, and a
    // busy stripe's victims wait for the next pass.
    bool exhausted[kStripes] = {};
    while (freed < want) {
        int best = -1;
        uint64_t best_age = UINT64_MAX;
        uint64_t second = UINT64_MAX;
        for (uint32_t si = 0; si < kStripes; ++si) {
            if (exhausted[si]) continue;
            uint64_t age =
                stripes_[si].tail_age.load(std::memory_order_relaxed);
            if (age == UINT64_MAX) {
                exhausted[si] = true;
                continue;
            }
            if (age < best_age) {
                second = best_age;
                best_age = age;
                best = int(si);
            } else if (age < second) {
                second = age;
            }
        }
        if (best < 0 || best_age > age_cap) break;
        uint32_t prev_fail = disk_min_fail;
        size_t got = evict_from_stripe(
            uint32_t(best), best == held_stripe, want - freed,
            second < age_cap ? second : age_cap,
            SIZE_MAX, &disk_min_fail, async_spill, &victims);
        freed += got;
        if (got == 0 && disk_min_fail == prev_fail) exhausted[best] = true;
    }
    if (freed < want) {
        // Relaxed pass: the strict walk's age limits come from raw tail
        // ages, and a cold tail that is PINNED (in-flight read, or a
        // victim the reclaimer already queued to the spill writer)
        // satisfies the limit while hiding evictable entries behind it —
        // the strict pass can then report "nothing evictable" with the
        // pool full of ordinary cold data. For the last-resort path,
        // progress beats strict order: sweep the stripes again with no
        // age limit (still coldest-first within each stripe; exact mode
        // never needs this — its selection is eligibility-aware).
        for (uint32_t si = 0; si < kStripes && freed < want; ++si) {
            freed += evict_from_stripe(si, int(si) == held_stripe,
                                       want - freed, age_cap, SIZE_MAX,
                                       &disk_min_fail, async_spill,
                                       &victims);
        }
    }
    return victims;
}

// --- background reclaim pipeline ---------------------------------------

void KVIndex::start_background(double high, double low, bool promote) {
    if (!track_lru() || !(high > 0.0 && high < 1.0)) return;
    if (bg_running_.load(std::memory_order_relaxed)) return;
    high_ = high;
    low_ = low;
    if (low_ > high_) low_ = high_;
    if (low_ < 0.0) low_ = 0.0;
    bg_stop_.store(false, std::memory_order_relaxed);
    bg_running_.store(true, std::memory_order_relaxed);
    reclaim_alive_.store(true, std::memory_order_relaxed);
    reclaim_died_.store(false, std::memory_order_relaxed);
    spill_alive_.store(disk_ != nullptr, std::memory_order_relaxed);
    spill_died_.store(false, std::memory_order_relaxed);
    reclaim_heartbeat_us_.store(now_us(), std::memory_order_relaxed);
    spill_heartbeat_us_.store(now_us(), std::memory_order_relaxed);
    // Background tracks, created BEFORE the threads spawn (thread
    // creation orders the ring pointers for the loops' bind calls).
    if (tracer_ != nullptr && tracer_->enabled()) {
        reclaim_ring_ = tracer_->add_track("reclaim");
        if (disk_ != nullptr) {
            spill_ring_ = tracer_->add_track("spill-writer");
        }
    }
    reclaim_thread_ = std::thread([this] { reclaim_loop(); });
    if (disk_ != nullptr) {
        spill_thread_ = std::thread([this] { spill_loop(); });
        // Async read pipeline: admission is bounded by the SAME high
        // watermark the reclaimer defends, so queued promotions can
        // never push occupancy into reclaim territory.
        if (promote && promoter_) promoter_->start(high_);
    }
}

void KVIndex::stop_background() {
    // The promoter first: it allocates pool blocks and takes stripe
    // locks from its own thread; joining it here means nothing below
    // races a late adoption.
    if (promoter_) promoter_->stop();
    bg_running_.store(false, std::memory_order_relaxed);
    bg_stop_.store(true, std::memory_order_relaxed);
    // Lock-then-notify so a thread between its predicate check and its
    // wait cannot miss the wake.
    {
        ScopedLock lk(reclaim_mu_);
    }
    reclaim_cv_.notify_all();
    {
        ScopedLock lk(spill_mu_);
    }
    spill_cv_.notify_all();
    if (reclaim_thread_.joinable()) reclaim_thread_.join();
    if (spill_thread_.joinable()) spill_thread_.join();
    // Drop leftover queued spills: their entries simply stay resident
    // (a stale SPILLING flag is cleared at the entry's next touch or
    // eviction pass).
    std::deque<SpillItem> dropped;
    {
        ScopedLock lk(spill_mu_);
        dropped.swap(spill_q_);
    }
    account_dropped_spills(dropped, /*cancelled=*/false);
}

void KVIndex::account_dropped_spills(std::deque<SpillItem>& items,
                                     bool cancelled) {
    const size_t bs = mm_->block_size();
    for (SpillItem& item : items) {
        spill_queue_depth_.fetch_sub(1, std::memory_order_relaxed);
        spill_inflight_bytes_.fetch_sub(
            (size_t(item.size) + bs - 1) / bs * bs,
            std::memory_order_relaxed);
        if (cancelled)
            spills_cancelled_.fetch_add(1, std::memory_order_relaxed);
    }
}

void KVIndex::maybe_wake_reclaimer() {
    if (!bg_running_.load(std::memory_order_relaxed)) return;
    size_t total = mm_->total_bytes();
    if (total == 0) return;
    if (double(mm_->used_bytes()) < high_ * double(total)) return;
    kick_reclaimer();
}

void KVIndex::kick_reclaimer() {
    if (!bg_running_.load(std::memory_order_relaxed)) return;
    // Attribution BEFORE the flag: the reclaimer may consume the flag
    // the instant it is set (its 200 ms poll races this call), and a
    // store published after the exchange could be read as 0 by the
    // pass it woke — then leak onto a later unrelated pass. Storing
    // first means any kick pending at pass start has its id in place;
    // among concurrent traced kicks the last writer wins, and all of
    // them are true causes of the pass. Untraced kicks (id 0) never
    // erase a pending traced attribution.
    uint64_t kick_tid = Tracer::thread_trace_id();
    if (kick_tid != 0) {
        reclaim_kick_trace_.store(kick_tid, std::memory_order_relaxed);
    }
    // Exchange dedupes the notify: under sustained pressure the put
    // path sets the flag once per reclaimer wake, not once per key.
    if (reclaim_kick_.exchange(true, std::memory_order_relaxed)) return;
    // One flight-recorder mark per wake (the same dedup): occupancy at
    // the moment the watermark (or promotion pressure) asked for a pass.
    events_emit(EV_WATERMARK_HIGH, mm_->used_bytes(), mm_->total_bytes());
    {
        ScopedLock lk(reclaim_mu_);
    }
    reclaim_cv_.notify_one();
}

void KVIndex::reclaim_loop() {
    Tracer::bind_thread(reclaim_ring_);
    events_bind_thread("reclaim");
    const bool trace = reclaim_ring_ != nullptr;
    // Evict in bounded batches so stop() stays responsive and the
    // stripe try-locks are released between rounds.
    const size_t batch_bytes = 64 * mm_->block_size();
    UniqueLock lk(reclaim_mu_);
    while (!bg_stop_.load(std::memory_order_relaxed)) {
        reclaim_cv_.wait_for(lk, std::chrono::milliseconds(200), [this] {
            return bg_stop_.load(std::memory_order_relaxed) ||
                   reclaim_kick_.load(std::memory_order_relaxed);
        });
        reclaim_kick_.store(false, std::memory_order_relaxed);
        // Consume the kick's attribution TOGETHER with the kick flag:
        // a traced kick whose pass is then skipped (usage already back
        // under HIGH) must not leak its id onto a later unrelated
        // pass. 0 on timer/pressure wakes with no pending traced kick.
        uint64_t pass_tid = reclaim_kick_trace_.exchange(
            0, std::memory_order_relaxed);
        if (bg_stop_.load(std::memory_order_relaxed)) break;
        reclaim_heartbeat_us_.store(now_us(), std::memory_order_relaxed);
        // Induced reclaimer death (chaos suite): allocation falls back
        // to the inline last-resort path (counted hard_stalls), the
        // workers_dead gauge announces the degradation.
        if (IST_FAILPOINT("worker.reclaim").action == FAIL_KILL) {
            reclaim_died_.store(true, std::memory_order_relaxed);
            events_emit(EV_WORKER_DEATH, /*kind=*/0, 0);
            IST_ERROR("reclaimer killed by failpoint; eviction degrades "
                      "to inline hard stalls");
            break;
        }
        lk.unlock();
        size_t total = mm_->total_bytes();
        // Secondary trigger: refused promotion admission (see
        // maybe_enqueue_promote) reclaims down to LOW even when HIGH
        // was never crossed — the pool resting just under high would
        // otherwise starve promotion of headroom forever.
        bool pressure =
            promote_pressure_.exchange(false, std::memory_order_relaxed);
        if (total != 0 &&
            (double(mm_->used_bytes()) >= high_ * double(total) ||
             (pressure &&
              double(mm_->used_bytes()) > low_ * double(total)))) {
            reclaim_runs_.fetch_add(1, std::memory_order_relaxed);
            // RECLAIM_PASS span: watermark wake -> pool back under the
            // low watermark (or nothing evictable); VICTIM_SCAN spans
            // nest inside it, one per bounded evict_internal batch, so
            // a foreground op's stall lines up with exactly the scan
            // that caused it.
            long long tpass = trace ? now_us() : 0;
            size_t pass_victims = 0;
            // Effective low watermark: the controller can lift it above
            // the configured base (reclaim-low knob, milli-fraction)
            // when premature evictions say the pool is churning.
            double eff_low = low_;
            if (io_sched_ != nullptr && io_sched_->enabled()) {
                uint64_t milli = io_sched_->knob(kKnobReclaimLow);
                if (milli != 0) {
                    double k = double(milli) / 1000.0;
                    if (k > low_ && k < high_) eff_low = k;
                }
            }
            // Sized-to-backlog floor: instead of bluntly evicting down
            // to LOW every pass, free only the headroom the observed
            // spill drain rate says the backlog needs —
            // floor = max(low*total, high*total - headroom). A null or
            // disabled scheduler reports the full (high-low) band, so
            // this degenerates to the historical reclaim-to-low.
            size_t high_bytes = size_t(high_ * double(total));
            size_t floor_lo = size_t(eff_low * double(total));
            uint64_t headroom =
                io_sched_ != nullptr
                    ? io_sched_->headroom_bytes(total, high_, eff_low)
                    : uint64_t(high_bytes - floor_lo);
            size_t floor_bytes = uint64_t(high_bytes) > headroom
                                     ? size_t(high_bytes - headroom)
                                     : floor_lo;
            if (floor_bytes < floor_lo) floor_bytes = floor_lo;
            // Spill batch multiplier (controller knob): a deep backlog
            // widens the per-round victim budget so the writer's
            // extent-merge batching sees longer runs.
            size_t eff_batch = batch_bytes;
            if (io_sched_ != nullptr && io_sched_->enabled()) {
                uint64_t mult = io_sched_->knob(kKnobSpillBatchMult);
                if (mult > 8) mult = 8;
                if (mult > 1) eff_batch = batch_bytes * size_t(mult);
            }
            // Thread-bind the kick's id (consumed at wake, above):
            // spill items the pass enqueues (enqueue_spill reads the
            // thread id) inherit it, so the whole kick → scan → spill
            // chain carries one trace id.
            Tracer::set_thread_trace_id(pass_tid);
            // a0 = this pass's headroom TARGET (bytes to hold free
            // below high), a1 = ACTUAL headroom at pass start.
            size_t used_now = mm_->used_bytes();
            events_emit(EV_RECLAIM_PASS_BEGIN, headroom,
                        high_bytes > used_now ? high_bytes - used_now
                                              : 0);
            // Victim-age cap for the WHOLE pass: entries touched — or
            // promotion-adopted — after this snapshot are off-limits,
            // so a reclaim-to-low pass can never race a fresh
            // promotion straight back to disk (the promote→spill→
            // promote thrash behind the prefetch_hit_rate decay).
            uint64_t pass_cap =
                lru_clock_.load(std::memory_order_relaxed);
            while (!bg_stop_.load(std::memory_order_relaxed)) {
                size_t used = mm_->used_bytes();
                // Bytes already queued to the writer are on their way
                // back to the pool — selecting more victims for them
                // would overshoot the low watermark.
                size_t inflight =
                    spill_inflight_bytes_.load(std::memory_order_relaxed);
                if (used <= floor_bytes + inflight) break;
                size_t want = used - floor_bytes - inflight;
                if (want > eff_batch) want = eff_batch;
                long long tscan = trace ? now_us() : 0;
                size_t victims = evict_internal(want, -1, true, pass_cap);
                if (trace) {
                    tracer_->record_id(
                        SPAN_VICTIM_SCAN, 0, uint64_t(tscan),
                        uint64_t(now_us() - tscan), pass_tid,
                        uint16_t(victims > 0xFFFF ? 0xFFFF : victims));
                }
                pass_victims += victims;
                if (victims == 0) break;
            }
            if (trace) {
                tracer_->record_id(SPAN_RECLAIM_PASS, 0, uint64_t(tpass),
                                   uint64_t(now_us() - tpass), pass_tid,
                                   uint16_t(pass_victims > 0xFFFF
                                                ? 0xFFFF
                                                : pass_victims));
            }
            size_t used_after = mm_->used_bytes();
            Tracer::set_thread_trace_id(0);
            // a0 = victims, a1 = ACTUAL headroom after the pass (pair
            // with pass_begin's target to see how close reclaim came).
            events_emit(EV_RECLAIM_PASS_END, pass_victims,
                        high_bytes > used_after ? high_bytes - used_after
                                                : 0);
            if (used_after <= floor_bytes) {
                events_emit(EV_WATERMARK_LOW, used_after, total);
            }
        }
        lk.lock();
    }
    reclaim_alive_.store(false, std::memory_order_relaxed);
}

long long KVIndex::reclaim_heartbeat_age_us() const {
    if (!reclaim_alive_.load(std::memory_order_relaxed)) return -1;
    return now_us() - reclaim_heartbeat_us_.load(std::memory_order_relaxed);
}

long long KVIndex::spill_heartbeat_age_us() const {
    if (!spill_alive_.load(std::memory_order_relaxed)) return -1;
    return now_us() - spill_heartbeat_us_.load(std::memory_order_relaxed);
}

void KVIndex::enqueue_spill(const std::string& key, const BlockRef& block,
                            uint32_t size, uint32_t si) {
    const size_t bs = mm_->block_size();
    spill_queue_depth_.fetch_add(1, std::memory_order_relaxed);
    spill_inflight_bytes_.fetch_add((size_t(size) + bs - 1) / bs * bs,
                                    std::memory_order_relaxed);
    {
        ScopedLock lk(spill_mu_);
        // Attribution tags: the enqueuing thread's trace id (a
        // foreground op on the inline path; the reclaim pass's kick id
        // on the async path — the reclaimer thread-binds it for the
        // pass) and the victim key's hash for the cancel event.
        spill_q_.push_back(SpillItem{
            key, block, size, si, Tracer::thread_trace_id(),
            uint64_t(std::hash<std::string>{}(key))});
    }
    spill_cv_.notify_one();
    // Lost race with an induced writer death (the caller's liveness
    // check passed before the kill drained the queue): nothing will
    // ever drain what we just queued, and each item's BlockRef would
    // pin its victim un-evictable forever. Pull it back out here; the
    // stale SPILLING flags clear at the entries' next touch/evict.
    if (!spill_alive_.load(std::memory_order_relaxed)) {
        std::deque<SpillItem> orphans;
        {
            ScopedLock lk(spill_mu_);
            orphans.swap(spill_q_);
        }
        account_dropped_spills(orphans, /*cancelled=*/true);
    }
}

void KVIndex::spill_loop() {
    Tracer::bind_thread(spill_ring_);
    events_bind_thread("spill");
    constexpr size_t kSpillBatch = 64;
    UniqueLock lk(spill_mu_);
    while (true) {
        spill_cv_.wait(lk, [this] {
            return bg_stop_.load(std::memory_order_relaxed) ||
                   !spill_q_.empty();
        });
        if (bg_stop_.load(std::memory_order_relaxed)) break;
        spill_heartbeat_us_.store(now_us(), std::memory_order_relaxed);
        // Induced spill-writer death: drain the queue under the lock
        // (counters rebalance, refs drop below) so queued BlockRefs do
        // not pin pool blocks forever; victim selection observes
        // spill_alive_==false and degrades to the inline spill/evict
        // path. Stale SPILLING flags clear at the next touch/evict.
        if (IST_FAILPOINT("worker.spill").action == FAIL_KILL) {
            std::deque<SpillItem> orphans;
            orphans.swap(spill_q_);
            account_dropped_spills(orphans, /*cancelled=*/true);
            spill_died_.store(true, std::memory_order_relaxed);
            spill_alive_.store(false, std::memory_order_relaxed);
            events_emit(EV_WORKER_DEATH, /*kind=*/1, orphans.size());
            IST_ERROR("spill writer killed by failpoint; reclaim "
                      "degrades to inline spill/evict");
            lk.unlock();
            orphans.clear();  // refs drop outside spill_mu_
            spill_cv_.notify_all();  // unblock a cancel barrier waiter
            return;
        }
        std::vector<SpillItem> batch;
        size_t take = spill_q_.size();
        if (take > kSpillBatch) take = kSpillBatch;
        batch.reserve(take);
        for (size_t i = 0; i < take; ++i) {
            batch.push_back(std::move(spill_q_.front()));
            spill_q_.pop_front();
        }
        spill_busy_ = true;
        lk.unlock();
        {
            const bool trace = spill_ring_ != nullptr;
            long long tb0 = trace ? now_us() : 0;
            size_t n = batch.size();
            // Attribution: the batch span carries the first item's
            // foreground trace id (a reclaim pass enqueues its whole
            // batch under one id; mixed inline items still get the
            // per-write spans below under their own ids).
            uint64_t btid = n ? batch[0].trace_id : 0;
            process_spill_batch(batch);
            if (trace) {
                tracer_->record_id(SPAN_SPILL_BATCH, 0, uint64_t(tb0),
                                   uint64_t(now_us() - tb0), btid,
                                   uint16_t(n > 0xFFFF ? 0xFFFF : n));
            }
        }
        batch.clear();
        lk.lock();
        spill_busy_ = false;
        spill_batch_gen_++;  // cancel_queued_spills' bounded barrier
        spill_cv_.notify_all();
    }
    spill_alive_.store(false, std::memory_order_relaxed);
}

void KVIndex::process_spill_batch(std::vector<SpillItem>& batch) {
    const size_t bs = mm_->block_size();
    // The LRU's cold end is often a contiguous put batch: the shared
    // extent-merge helper (promote.h, also used by the promotion
    // worker's pread batching) sorts by POOL address and groups runs
    // of back-to-back victims into ONE reserve + pwrite (store_batch
    // carves per-victim extents out of the combined one). Payload
    // adjacency is exact (ptr + size == next ptr), so only
    // block-aligned sizes ever join a run — an unaligned payload's
    // rounding gap would shift the carved offsets off block
    // boundaries.
    std::vector<MergeSpan> spans;
    spans.reserve(batch.size());
    for (size_t k = 0; k < batch.size(); ++k) {
        spans.push_back(MergeSpan{
            uint64_t(reinterpret_cast<uintptr_t>(batch[k].block->loc.ptr)),
            batch[k].size, k});
    }
    constexpr uint64_t kMaxGroupBytes = 64ull << 20;  // store() is u32
    auto groups = merge_adjacent(spans, kMaxGroupBytes);
    std::vector<int64_t> offs(batch.size(), -1);
    const bool trace = spill_ring_ != nullptr;
    // Pool-FRAGMENTED leftovers (singleton groups): gathered below into
    // single reserved extents + one pwritev each, so fragmentation
    // degrades to one syscall per run instead of one per victim — and
    // the victims land DISK-adjacent, which the promotion worker's
    // merged preads then exploit on the way back.
    std::vector<size_t> singles;
    for (auto [gi, gj] : groups) {
        if (gi == gj) {
            singles.push_back(spans[gi].idx);
            continue;
        }
        long long tw0 = trace ? now_us() : 0;
        uint32_t n = uint32_t(gj - gi + 1);
        std::vector<uint32_t> sizes(n);
        uint64_t group_bytes = 0;
        for (uint32_t k = 0; k < n; ++k) {
            sizes[k] = batch[spans[gi + k].idx].size;
            group_bytes += sizes[k];
        }
        // Spill-class budget for the whole merged write (io_sched.h):
        // charged before the IO, outside all locks; the per-victim
        // fallback below reuses the grant (same bytes either way).
        if (io_sched_ != nullptr) {
            io_sched_->acquire(kIoSpill, group_bytes);
        }
        std::vector<int64_t> sub(n, -1);
        const SpillItem& first = batch[spans[gi].idx];
        if (disk_->store_batch(first.block->loc.ptr, sizes.data(), n,
                               sub.data()) >= 0) {
            for (uint32_t k = 0; k < n; ++k) offs[spans[gi + k].idx] = sub[k];
        } else {  // no contiguous combined fit: per-victim fallback
            for (uint32_t k = 0; k < n; ++k) {
                const SpillItem& it = batch[spans[gi + k].idx];
                offs[spans[gi + k].idx] =
                    disk_->store(it.block->loc.ptr, it.size);
            }
        }
        if (trace) {
            tracer_->record_id(SPAN_SPILL_WRITE, 0, uint64_t(tw0),
                               uint64_t(now_us() - tw0),
                               first.trace_id, uint16_t(n));
        }
    }
    // Gather runs over the leftovers. store_gather's carve contract:
    // every size but a run's LAST must be block-aligned, so an
    // unaligned single always ends its run (and a run of one simply
    // falls through to plain store()).
    size_t i = 0;
    while (i < singles.size()) {
        size_t j = i;
        uint64_t total = batch[singles[i]].size;
        while (j + 1 < singles.size() && batch[singles[j]].size % bs == 0 &&
               total + batch[singles[j + 1]].size <= kMaxGroupBytes) {
            ++j;
            total += batch[singles[j]].size;
        }
        long long tw0 = trace ? now_us() : 0;
        uint32_t n = uint32_t(j - i + 1);
        std::vector<const void*> srcs(n);
        std::vector<uint32_t> sizes(n);
        for (uint32_t k = 0; k < n; ++k) {
            const SpillItem& it = batch[singles[i + k]];
            srcs[k] = it.block->loc.ptr;
            sizes[k] = it.size;
        }
        // Spill-class budget for the gather run (see above).
        if (io_sched_ != nullptr) {
            io_sched_->acquire(kIoSpill, total);
        }
        std::vector<int64_t> sub(n, -1);
        if (disk_->store_gather(srcs.data(), sizes.data(), n,
                                sub.data()) >= 0) {
            for (uint32_t k = 0; k < n; ++k) offs[singles[i + k]] = sub[k];
        } else {  // no contiguous extent that big: per-victim fallback
            for (uint32_t k = 0; k < n; ++k) {
                offs[singles[i + k]] = disk_->store(srcs[k], sizes[k]);
            }
        }
        if (trace) {
            tracer_->record_id(SPAN_SPILL_WRITE, 0, uint64_t(tw0),
                               uint64_t(now_us() - tw0),
                               batch[singles[i]].trace_id, uint16_t(n));
        }
        i = j + 1;
    }
    for (size_t k = 0; k < batch.size(); ++k) finish_spill(batch[k], offs[k]);
}

void KVIndex::finish_spill(SpillItem& item, int64_t off) {
    const size_t bs = mm_->block_size();
    // Declared before the stripe lock so a cancelled spill's extent is
    // released (DiskSpan RAII) after the lock drops.
    DiskRef span;
    if (off >= 0) {
        span = std::make_shared<DiskSpan>(disk_, off, item.size);
    } else if (!disk_->breaker_open() &&
               !disk_->last_store_failure_was_io()) {
        // Remember a CAPACITY refusal so async selection stops queueing
        // sizes the tier cannot hold until its usage drops (see
        // spill_may_fit). NOT for device write errors (even below the
        // breaker's 3-consecutive threshold) and NOT under an open
        // breaker: those failures are the DEVICE's, recovery is the
        // breaker's consecutive-error count + backoff re-probe, and a
        // fail-min poisoned by them would suppress the very writes the
        // breaker needs to observe (1-2 transient EIOs against an
        // empty tier used to wedge spilling forever — the fail-min
        // recovery conditions were unreachable there).
        uint32_t cur = spill_fail_min_.load(std::memory_order_relaxed);
        if (item.size < cur) {
            spill_fail_min_.store(item.size, std::memory_order_relaxed);
        }
        spill_fail_used_.store(disk_->used_bytes(),
                               std::memory_order_relaxed);
        // Arm the fail-min re-probe window (spill_may_fit): the next
        // retry attempt waits out the backoff instead of storming, but
        // DOES eventually happen even against an empty tier.
        spill_fail_retry_at_us_.store(now_us() + kSpillFailRetryUs,
                                      std::memory_order_relaxed);
    }
    {
        Stripe& st = stripes_[item.stripe];
        ScopedLock lk(st.mu);
        auto mit = st.map.find(item.key);
        // Adopt the extent only if this is still the same entry (same
        // Block), still SPILLING (no read touched it since selection)
        // and unpinned (use_count 2 = the entry's ref + ours). Anything
        // else — erased, re-put, read-cancelled, newly pinned — keeps
        // the entry resident and the extent is released.
        if (mit != st.map.end() && mit->second.block == item.block) {
            Entry& e = mit->second;
            if (span && e.spilling && e.committed &&
                e.block.use_count() == 2) {
                bump_epoch();  // before the blocks can return to the pool
                lru_drop(st, e);
                e.disk = std::move(span);
                e.spilling = false;
                e.touched = false;  // second-touch restarts per cycle
                // A spilled entry has a PRIVATE disk copy: any dedup
                // saving this entry carried ends here. (A SHARED block
                // never reaches this point — use_count would be > 2 —
                // so this fires only after sharing already dropped.)
                dedup_block_released(e);
                e.block.reset();  // our item.block still pins the bytes
                spills_.fetch_add(1, std::memory_order_relaxed);
                workload_.record_spill(item.key_hash);
                spill_fail_min_.store(UINT32_MAX,
                                      std::memory_order_relaxed);
            } else if (!span && eviction_ && e.spilling && e.committed &&
                       e.block.use_count() == 2) {
                // WRITE FAILED (EIO/ENOSPC/short, extent reservation
                // already rolled back by DiskTier) and the victim is
                // still untouched: hard-evict it NOW instead of leaving
                // it parked in SPILLING state for the reclaimer to
                // re-select against a failing tier forever. Only with
                // eviction enabled — spill-only mode never drops
                // committed data, so there the entry simply stays
                // resident (and evictable by a future pass).
                workload_.record_evict(item.key_hash);
                bump_epoch();  // before the blocks can return to the pool
                dedup_entry_removed(e);
                lru_drop(st, e);
                st.map.erase(mit);
                evictions_.fetch_add(1, std::memory_order_relaxed);
                spills_cancelled_.fetch_add(1, std::memory_order_relaxed);
                // a0 = the victim key's hash (attribution: grep the
                // same hash out of a client log / merged trace),
                // a1 = evicted flag.
                events_emit(EV_SPILL_CANCEL, item.key_hash, /*evicted=*/1);
            } else {
                e.spilling = false;
                spills_cancelled_.fetch_add(1, std::memory_order_relaxed);
                events_emit(EV_SPILL_CANCEL, item.key_hash, /*evicted=*/0);
            }
        }
    }
    item.block.reset();  // pool blocks actually free here (epoch already bumped)
    spill_inflight_bytes_.fetch_sub(
        (size_t(item.size) + bs - 1) / bs * bs, std::memory_order_relaxed);
    spill_queue_depth_.fetch_sub(1, std::memory_order_relaxed);
}

bool KVIndex::spill_may_fit(uint32_t size) {
    // Admission by actual tier room FIRST: queued-but-unwritten spills
    // (spill_inflight_bytes_) already claim part of the free space, and
    // over-queueing would pin every resident entry's block behind a
    // doomed write — a read promotion in that window would find nothing
    // evictable and fail OOM.
    const size_t bs = mm_->block_size();
    // Breaker-open tier: refuse queueing (the write is doomed) except
    // when the backoff window owes a probe — that one victim carries
    // the re-probe store that can close the breaker.
    if (!disk_->store_likely_admitted()) return false;
    uint64_t rounded = (uint64_t(size) + bs - 1) / bs * bs;
    uint64_t used = disk_->used_bytes();
    uint64_t cap = disk_->capacity_bytes();
    uint64_t claimed =
        spill_inflight_bytes_.load(std::memory_order_relaxed);
    if (cap < used + claimed + rounded) return false;
    uint32_t fmin = spill_fail_min_.load(std::memory_order_relaxed);
    if (size < fmin) return true;
    if (used < spill_fail_used_.load(std::memory_order_relaxed)) {
        // Something was released since the failure: forget it and retry.
        spill_fail_min_.store(UINT32_MAX, std::memory_order_relaxed);
        return true;
    }
    // Backoff re-probe (PR 10): the two recovery conditions above are
    // unreachable when the failure happened against an EMPTY tier —
    // usage cannot drop below 0 and no store is ever attempted once
    // fmin blocks everything — so one or two transient write errors
    // (below the breaker's threshold of 3) would wedge spilling
    // FOREVER. Mirror the breaker's probe: admit ONE victim per
    // backoff window (CAS moves the deadline, so exactly one caller
    // per window wins); its store either succeeds (clearing fmin) or
    // feeds the consecutive-error count toward the breaker, whose own
    // backoff then takes over.
    long long now = now_us();
    long long at = spill_fail_retry_at_us_.load(std::memory_order_relaxed);
    if (now < at) return false;
    return spill_fail_retry_at_us_.compare_exchange_strong(
        at, now + kSpillFailRetryUs, std::memory_order_relaxed);
}

void KVIndex::cancel_queued_spills() {
    if (!spill_thread_.joinable()) return;
    std::deque<SpillItem> dropped;
    {
        UniqueLock lk(spill_mu_);
        dropped.swap(spill_q_);
        account_dropped_spills(dropped, /*cancelled=*/true);
        // Wait out the writer's in-flight batch — AT MOST one: under
        // sustained pressure concurrent puts refill the queue the
        // moment we cleared it, and the writer grabs the next batch
        // (flipping spill_busy_ back on) without ever dropping
        // spill_mu_ in between, so "wait until idle" could starve
        // forever. The batch GENERATION bounds the wait to the batch
        // that was in flight at entry; items queued after our clear
        // belong to post-purge entries and are not our concern. The
        // writer needs stripe locks (finish_spill) and spill_mu_ (to
        // bump the generation) — the caller holds neither while
        // waiting here.
        uint64_t gen = spill_batch_gen_;
        spill_cv_.wait(lk, [this, gen] {
            return !spill_busy_ || spill_batch_gen_ != gen;
        });
    }
    dropped.clear();  // refs drop outside spill_mu_
}

void KVIndex::debug_json(std::string& out) const {
    // One stripe at a time: a debug snapshot must never assemble the
    // cross-stripe lock set (that is reserved for ops that need a
    // consistent cut); a slightly skewed view is the right trade for a
    // data plane that never notices the introspection.
    constexpr int kAgeBuckets = 16;
    uint64_t clock = lru_clock_.load(std::memory_order_relaxed);
    char buf[256];
    out += "\"stripes\": [";
    for (uint32_t si = 0; si < kStripes; ++si) {
        const Stripe& st = stripes_[si];
        size_t entries = 0, resident = 0, on_disk = 0, limbo = 0;
        size_t spilling = 0, promoting = 0, uncommitted = 0, inflight = 0;
        uint64_t bytes = 0;
        uint64_t age_hist[kAgeBuckets] = {};
        size_t lru_len = 0;
        {
            ScopedLock lk(st.mu);
            entries = st.map.size();
            inflight = st.inflight_live;
            for (const auto& [key, e] : st.map) {
                (void)key;
                bytes += e.size;
                if (!e.committed) uncommitted++;
                if (e.block) {
                    resident++;
                } else if (e.disk) {
                    on_disk++;
                } else if (e.heap) {
                    limbo++;
                }
                if (e.spilling) spilling++;
                if (e.promoting) promoting++;
            }
            lru_len = st.lru.size();
            for (const auto& node : st.lru) {
                uint64_t age =
                    clock > node.age ? clock - node.age : 0;
                int b = 0;
                while (age > 1 && b < kAgeBuckets - 1) {
                    age >>= 1;
                    b++;
                }
                age_hist[b]++;
            }
        }
        snprintf(buf, sizeof(buf),
                 "%s{\"stripe\": %u, \"entries\": %zu, \"bytes\": %llu, "
                 "\"resident\": %zu, \"disk\": %zu, \"limbo\": %zu, "
                 "\"spilling\": %zu, \"promoting\": %zu, "
                 "\"uncommitted\": %zu, \"inflight\": %zu, "
                 "\"lru_len\": %zu, \"lru_age_hist\": [",
                 si ? ", " : "", si, entries, (unsigned long long)bytes,
                 resident, on_disk, limbo, spilling, promoting,
                 uncommitted, inflight, lru_len);
        out += buf;
        for (int b = 0; b < kAgeBuckets; ++b) {
            snprintf(buf, sizeof(buf), "%s%llu", b ? ", " : "",
                     (unsigned long long)age_hist[b]);
            out += buf;
        }
        out += "]}";
    }
    snprintf(buf, sizeof(buf),
             "], \"lru_clock\": %llu, \"queues\": {\"spill\": "
             "{\"depth\": %llu, \"inflight_bytes\": %llu, "
             "\"heartbeat_age_us\": %lld}, \"promote\": {\"depth\": "
             "%llu, \"inflight_bytes\": %llu, \"heartbeat_age_us\": "
             "%lld}}",
             (unsigned long long)clock,
             (unsigned long long)spill_queue_depth(),
             (unsigned long long)spill_inflight_bytes(),
             spill_heartbeat_age_us(),
             (unsigned long long)promote_queue_depth(),
             (unsigned long long)promote_inflight_bytes(),
             promote_heartbeat_age_us());
    out += buf;
}

}  // namespace istpu
