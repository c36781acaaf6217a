// Native pack: the host side of the check hot path, behind a C ABI
// (keto_tpu/check/native_pack.py binds it with ctypes, which releases the
// GIL for every call). ABI version 4. Two generations of `pack` live here,
// and since ABI 4 the `resolve` state before them (keto_resolve_chunk, at the
// very end: a chunk's raw node ids to its device rows, closure bytes and the
// running sums of its entry counts, one call):
//
//  1. keto_pack_walk / keto_sink_gather / keto_pairs_member: the pieces of
//     keto_tpu/check/pack.py's pack_chunk and device_part that were worth a
//     native call by themselves, each over a whole chunk's frontier with
//     numpy passes between them (the BFS route, the sharded riders, every
//     chunk the pass below declines);
//  2. keto_pack_labeled (+ _pairs, _riders; at the end of this file): the
//     label route's whole `pack` as ONE call, query by query: the host walk
//     with a visited stamp a row instead of a chunk-wide hash set, the seed
//     rows, the sink target's answer or relay rows, the route's decisions
//     (self_hit, pair_cap, uncertifiable, whole_slice), the certified pairs,
//     copied by the second call straight into the staging lease label_step
//     ships, and by the third pack_chunk's seven arrays for the queries that
//     fell back. What it must equal, set for set, is pack_chunk followed by
//     pack.py's label_pairs (tests/test_pack_fused.py fuzzes it on the graph
//     shapes the benchmark serves). The caller declines it, by what it
//     observes and never by a setting, for: no (or a stale) library, a mesh,
//     host-visible overlay state, a wildcard or multi-start query in the
//     chunk (check/dispatch.py _fused_decline).
//
// The rest of this header is the first generation's contract.
//
// keto_tpu/check/pack.py:pack_chunk expands host-propagated starts
// (static, peeled-interior, overlay nodes) through the forward CSR until
// every path either seeds the device bitmap (interior rows), decides a
// query on host (a traversed edge landing on its target), or dies out.
// The numpy implementation is vectorized but single-threaded AND holds
// the GIL for the whole walk — it serializes in front of every dispatch,
// so resolve/pack of chunk k+2 fights the GIL instead of overlapping
// exec of chunk k+1. This file is the same walk behind a C ABI: ctypes
// releases the GIL for the call, the per-hop CSR gather fans out across
// worker threads, and the (query, row) seen/seed bookkeeping lives in
// open-addressed hash sets (amortized O(1) per key — the numpy path's
// sorted-insert seen set was the quadratic tail the issue names).
//
// **Bit-identical contract.** The output must equal the numpy path byte
// for byte (tests/test_native_pack.py fuzzes the comparison):
//
//  - per hop the frontier dedups by key ((q << 32) | row) keeping the
//    FIRST occurrence in frontier order, then filters keys already seen
//    (all survivors are inserted before gathering) — one ordered pass
//    over a hash set reproduces numpy's unique/searchsorted dance;
//  - neighbors gather in frontier order, CSR order within a row; rows
//    >= n_base (overlay ids) and rows with no out-edges contribute
//    nothing, exactly like out_neighbors_bulk on an overlay-free base;
//  - a neighbor equal to the query's target sets host_ans[q] (the
//    "reached via >= 1 edge" rule; target -1 never matches);
//  - neighbors < ni append to the seed stream, neighbors in [ni, sb)
//    continue the frontier; the final seed list dedups by key keeping
//    first occurrence over the concatenated per-hop streams;
//  - the walk stops when a hop's total neighbor count is zero (numpy's
//    `if not nbrs.size: break`), or the frontier empties.
//
// Threading merges per-chunk results IN CHUNK ORDER (the ingest.cpp
// pattern), so the seed stream the serial dedup consumes is identical
// to a single-threaded walk. Thread count: KETO_TPU_PACK_THREADS, else
// min(hardware_concurrency, 8); hops under ~64k gathered neighbors stay
// serial (spawn cost dominates).
//
// The sink answer gather (sink reverse CSR rows of sink-class targets)
// rides the same library: one contiguous CSR gather, C ABI so the whole
// pack stays off the GIL on the eligible (overlay-free) path.
//
// Ownership of result handles stays with the caller (keto_pack_free /
// keto_gather_free).

#include <algorithm>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <thread>
#include <vector>

namespace {

// Open-addressed set of uint64 keys (slots hold key+1; 0 = empty).
// Linear probing over a pow2 table; grow at 50% load. Keys here are
// ((q << 32) | row) pairs — already well mixed enough for the low bits
// after a multiplicative scramble.
struct KeySet {
    std::vector<uint64_t> slots;
    size_t mask = 0;
    size_t count = 0;

    static inline size_t mix(uint64_t k) {
        k *= 0x9e3779b97f4a7c15ULL;
        k ^= k >> 29;
        return (size_t)k;
    }

    void reserve(size_t n) {
        size_t cap = 16;
        while (cap < n * 2) cap <<= 1;
        if (cap > slots.size()) rehash(cap);
    }

    void rehash(size_t cap) {
        std::vector<uint64_t> old;
        old.swap(slots);
        slots.assign(cap, 0);
        mask = cap - 1;
        for (uint64_t v : old) {
            if (!v) continue;
            size_t i = mix(v - 1) & mask;
            while (slots[i]) i = (i + 1) & mask;
            slots[i] = v;
        }
    }

    // true when newly inserted (false: already present)
    bool insert(uint64_t key) {
        if (slots.empty() || (count + 1) * 2 > slots.size())
            rehash(slots.empty() ? 16 : slots.size() * 2);
        size_t i = mix(key) & mask;
        while (slots[i]) {
            if (slots[i] == key + 1) return false;
            i = (i + 1) & mask;
        }
        slots[i] = key + 1;
        ++count;
        return true;
    }
};

struct PackResult {
    std::vector<int64_t> seed_rows;
    std::vector<int64_t> seed_q;
    std::vector<uint8_t> host_ans;  // [nq]
};

struct GatherResult {
    std::vector<int32_t> rows;
    std::vector<int64_t> cnts;
};

// Per-thread chunk output of one hop's gather: raw (pre-dedup) seeds,
// next-hop frontier entries, and target hits — merged in chunk order.
struct HopChunk {
    std::vector<int64_t> seed_rows, seed_q;
    std::vector<int64_t> next_rows, next_q;
    std::vector<int64_t> hit_q;
};

void gather_range(
    const int64_t* indptr, const int32_t* indices, int64_t n_base,
    int64_t ni, int64_t sb, const int64_t* tgc,
    const int64_t* rows, const int64_t* qs, size_t lo, size_t hi,
    HopChunk* out) {
    for (size_t i = lo; i < hi; ++i) {
        int64_t row = rows[i];
        if (row >= n_base) continue;  // overlay id: no base out-edges
        int64_t q = qs[i];
        int64_t tg = tgc[q];
        for (int64_t e = indptr[row]; e < indptr[row + 1]; ++e) {
            int64_t nbr = indices[e];
            if (nbr == tg) out->hit_q.push_back(q);
            if (nbr < ni) {
                out->seed_rows.push_back(nbr);
                out->seed_q.push_back(q);
            } else if (nbr < sb) {
                out->next_rows.push_back(nbr);
                out->next_q.push_back(q);
            }
        }
    }
}

int pack_threads() {
    if (const char* env = std::getenv("KETO_TPU_PACK_THREADS")) {
        int n = std::atoi(env);
        if (n > 0) return n;
    }
    unsigned hw = std::thread::hardware_concurrency();
    return (int)(hw ? (hw < 8 ? hw : 8) : 1);
}

// frontier work below this many gathered neighbors stays serial
constexpr int64_t kParallelThreshold = 1 << 16;

// What one keto_pack_labeled call leaves for the two fetch calls that
// follow it on the same thread: every query's seed rows and answer rows
// (a CSR by query), its target, whether it left the label route, and the
// pairs of those that did not. One a thread, reused from chunk to chunk:
// nothing is allocated once the vectors have grown to a chunk's size.
struct LabeledScratch {
    std::vector<int32_t> seed_rows, ans_rows;  // by query, seed_off / ans_off
    std::vector<int64_t> seed_off, ans_off;    // [nq + 1]
    std::vector<int32_t> targets;              // [nq]: interior target or ni
    std::vector<uint8_t> e1;                   // [nq]: the seed is the start itself
    std::vector<uint8_t> fallback;             // [nq]
    std::vector<int32_t> pa, pb, pq;
    std::vector<int64_t> stack;
    // stamp[row] == epoch: this query's walk has been at row (< sink_base)
    std::vector<uint32_t> stamp;
    uint32_t epoch = 0;
    int64_t nq = 0, ni = 0;
};

thread_local LabeledScratch labeled_scratch;

}  // namespace

// A snapshot's arrays as keto_pack_labeled reads them: filled once a
// snapshot by the binding (native_pack.PackView), which keeps the arrays
// alive. hub_ptr is null where answer entries may not name relay rows.
struct KetoPackView {
    const int64_t* fwd_indptr;
    const int32_t* fwd_indices;
    const int64_t* sink_indptr;
    const int32_t* sink_indices;
    const int64_t* hub_ptr;
    const int64_t* hub_rows;
    const uint8_t* out_ok;
    const uint8_t* in_ok;
    const uint8_t* processed;
    int64_t n_base, ni, sb, nl, n_lab, pair_cap;
};

// A snapshot's arrays as keto_resolve_chunk reads them: filled once a
// snapshot by the binding (native_pack.ResolveView), which keeps the arrays
// alive. Null where the snapshot has none: hub_ptr (a sink's answer names its
// own rows), reach (every target's entries count towards a cut), flags (no
// rewrite plan: no closure byte is written).
struct KetoResolveView {
    const int64_t* raw2dev;
    const int64_t* fwd_indptr;
    const int64_t* sink_indptr;
    const int64_t* hub_ptr;
    const uint8_t* reach;
    const uint8_t* flags;
    int64_t n_raw, n_base, ni, sb, nl, n_flags, rewritten;
};

extern "C" {

// ABI version probe: the Python binding refuses a stale .so.
int64_t keto_pack_version() { return 4; }

void* keto_pack_walk(
    const int64_t* fwd_indptr, const int32_t* fwd_indices, int64_t n_base,
    int64_t ni, int64_t sb,
    const int64_t* prop_rows, const int64_t* prop_q, int64_t n_prop,
    const int64_t* tgc, int64_t nq, int64_t n_threads) {
    auto* res = new PackResult();
    res->host_ans.assign((size_t)nq, 0);
    if (n_prop <= 0) return res;
    int threads = n_threads > 0 ? (int)n_threads : pack_threads();

    std::vector<int64_t> rows(prop_rows, prop_rows + n_prop);
    std::vector<int64_t> qs(prop_q, prop_q + n_prop);
    KeySet seen;
    seen.reserve((size_t)n_prop);
    KeySet seed_seen;
    std::vector<int64_t> next_rows, next_q;

    while (!rows.empty()) {
        // frontier dedup + seen filter, first occurrence wins (one pass:
        // a key rejected by `seen` is either a prior hop's or an earlier
        // duplicate this hop — dropped either way, order preserved)
        size_t w = 0;
        for (size_t i = 0; i < rows.size(); ++i) {
            uint64_t key = ((uint64_t)qs[i] << 32) | (uint64_t)rows[i];
            if (seen.insert(key)) {
                rows[w] = rows[i];
                qs[w] = qs[i];
                ++w;
            }
        }
        rows.resize(w);
        qs.resize(w);
        if (rows.empty()) break;

        // total gathered neighbors this hop (numpy breaks on zero)
        int64_t total = 0;
        for (size_t i = 0; i < rows.size(); ++i) {
            int64_t r = rows[i];
            if (r < n_base) total += fwd_indptr[r + 1] - fwd_indptr[r];
        }
        if (total == 0) break;

        int t = (total >= kParallelThreshold && rows.size() > 1) ? threads : 1;
        if ((size_t)t > rows.size()) t = (int)rows.size();
        std::vector<HopChunk> chunks((size_t)t);
        if (t == 1) {
            gather_range(fwd_indptr, fwd_indices, n_base, ni, sb, tgc,
                         rows.data(), qs.data(), 0, rows.size(), &chunks[0]);
        } else {
            std::vector<std::thread> pool;
            pool.reserve((size_t)t);
            size_t per = (rows.size() + (size_t)t - 1) / (size_t)t;
            for (int k = 0; k < t; ++k) {
                size_t lo = (size_t)k * per;
                size_t hi = lo + per < rows.size() ? lo + per : rows.size();
                if (lo >= hi) break;
                pool.emplace_back(gather_range, fwd_indptr, fwd_indices,
                                  n_base, ni, sb, tgc, rows.data(), qs.data(),
                                  lo, hi, &chunks[(size_t)k]);
            }
            for (auto& th : pool) th.join();
        }

        // serial merge IN CHUNK ORDER: hits, deduped seeds (first
        // occurrence over the concatenated stream), next frontier
        next_rows.clear();
        next_q.clear();
        for (auto& c : chunks) {
            for (int64_t q : c.hit_q) res->host_ans[(size_t)q] = 1;
            for (size_t i = 0; i < c.seed_rows.size(); ++i) {
                uint64_t key =
                    ((uint64_t)c.seed_q[i] << 32) | (uint64_t)c.seed_rows[i];
                if (seed_seen.insert(key)) {
                    res->seed_rows.push_back(c.seed_rows[i]);
                    res->seed_q.push_back(c.seed_q[i]);
                }
            }
            next_rows.insert(next_rows.end(), c.next_rows.begin(),
                             c.next_rows.end());
            next_q.insert(next_q.end(), c.next_q.begin(), c.next_q.end());
        }
        rows.swap(next_rows);
        qs.swap(next_q);
    }
    return res;
}

int64_t keto_pack_n_seeds(void* h) {
    return (int64_t)static_cast<PackResult*>(h)->seed_rows.size();
}

void keto_pack_fetch(void* h, int64_t* seed_rows, int64_t* seed_q,
                     uint8_t* host_ans) {
    auto* r = static_cast<PackResult*>(h);
    if (!r->seed_rows.empty()) {
        std::memcpy(seed_rows, r->seed_rows.data(),
                    r->seed_rows.size() * sizeof(int64_t));
        std::memcpy(seed_q, r->seed_q.data(),
                    r->seed_q.size() * sizeof(int64_t));
    }
    if (!r->host_ans.empty())
        std::memcpy(host_ans, r->host_ans.data(), r->host_ans.size());
}

void keto_pack_free(void* h) { delete static_cast<PackResult*>(h); }

// Sink answer gather: concatenated sink-reverse-CSR rows of each target
// (device ids, already offset by sink_base on the Python side) plus the
// per-target counts — the overlay-free arm of sink_in_rows_bulk.
void* keto_sink_gather(const int64_t* sink_indptr, const int32_t* sink_indices,
                       const int64_t* sinks, int64_t n) {
    auto* res = new GatherResult();
    res->cnts.resize((size_t)n);
    int64_t total = 0;
    for (int64_t i = 0; i < n; ++i) {
        int64_t s = sinks[i];
        int64_t c = sink_indptr[s + 1] - sink_indptr[s];
        res->cnts[(size_t)i] = c;
        total += c;
    }
    res->rows.reserve((size_t)total);
    for (int64_t i = 0; i < n; ++i) {
        int64_t s = sinks[i];
        for (int64_t e = sink_indptr[s]; e < sink_indptr[s + 1]; ++e)
            res->rows.push_back(sink_indices[e]);
    }
    return res;
}

int64_t keto_gather_n(void* h) {
    return (int64_t)static_cast<GatherResult*>(h)->rows.size();
}

void keto_gather_fetch(void* h, int32_t* rows, int64_t* cnts) {
    auto* r = static_cast<GatherResult*>(h);
    if (!r->rows.empty())
        std::memcpy(rows, r->rows.data(), r->rows.size() * sizeof(int32_t));
    if (!r->cnts.empty())
        std::memcpy(cnts, r->cnts.data(), r->cnts.size() * sizeof(int64_t));
}

void keto_gather_free(void* h) { delete static_cast<GatherResult*>(h); }

// Which of the probe (row, query) pairs are among the set's pairs:
// out[i] = 1 where (probe_rows[i], probe_q[i]) is one of them. What
// tpu_engine._device_part asks of a packed chunk: is a row that a sink
// target gathers its answer from one of its query's own seed rows.
void keto_pairs_member(const int32_t* set_rows, const int32_t* set_q,
                       int64_t n_set, const int32_t* probe_rows,
                       const int32_t* probe_q, int64_t n_probe, uint8_t* out) {
    KeySet set;
    set.reserve((size_t)n_set);
    for (int64_t i = 0; i < n_set; ++i)
        set.insert(((uint64_t)(uint32_t)set_q[i] << 32) | (uint32_t)set_rows[i]);
    for (int64_t i = 0; i < n_probe; ++i) {
        uint64_t key = ((uint64_t)(uint32_t)probe_q[i] << 32) | (uint32_t)probe_rows[i];
        size_t j = KeySet::mix(key) & set.mask;
        uint8_t hit = 0;
        while (!set.slots.empty() && set.slots[j]) {
            if (set.slots[j] == key + 1) { hit = 1; break; }
            j = (j + 1) & set.mask;
        }
        out[i] = hit;
    }
}

// The label route's whole pack of one chunk [i0, i1) of a resolved batch,
// query by query (check/dispatch.py _device_batch_labeled; pack_chunk and
// the numpy pairing are the contract, set for set):
//
//  - the host walk from a host-propagated start, a visited stamp a row in
//    place of a hash set; a traversed edge landing on the target grants;
//    rows < ni seed the device (e2), the start itself where it is one (e1);
//  - a sink target's answer rows from the sink reverse CSR, or its relay
//    rows (ni + 1 + k) where the view has them;
//  - the route, first cause wins: self_hit (an e1 seed that is the interior
//    target), pair_cap (seeds x target-side rows over the cap, or a relay
//    row), then, unless the fallbacks so far fill a sub-batch as wide as the
//    slice (n_fb >= whole_min, the caller's rule), the cross-join with the
//    e2-seed == target pair dropped and every pair certified
//    (out_ok[a] & in_ok[b] & (processed[a] | processed[b])): one miss and
//    the query falls back (uncertifiable); whole_slice for the rest where
//    the fallbacks then fill the slice.
//
// host_ans and fallback are uint8[nq]; counts is int64[13]:
//   0 any seed at all (pack_chunk's packed is not None)   1 pairs
//   2 queries fallen back   3 the whole slice rides
//   4-7 self_hit, pair_cap, uncertifiable, whole_slice
//   8-9 seed rows and target-side rows of the chunk (a relay row counts
//       the rows it holds)   10-12 e1, e2 and answer entries of the riders
// The pairs and the riders' entries stay in this thread's scratch for
// keto_pack_labeled_pairs / keto_pack_labeled_riders.
void keto_pack_labeled(const KetoPackView* v, const int64_t* sd,
                       const int64_t* tg, int64_t i0, int64_t i1,
                       int64_t whole_min, uint8_t* host_ans,
                       uint8_t* fallback_out, int64_t* counts) {
    LabeledScratch& S = labeled_scratch;
    const int64_t nq = i1 - i0, ni = v->ni, sb = v->sb, nl = v->nl;
    const int64_t n_base = v->n_base, n_lab = v->n_lab;
    const int64_t* const fwd_indptr = v->fwd_indptr;
    const int32_t* const fwd_indices = v->fwd_indices;
    const int64_t* const sink_indptr = v->sink_indptr;
    const int32_t* const sink_indices = v->sink_indices;
    const int64_t* const hub_ptr = v->hub_ptr;
    sd += i0;
    tg += i0;
    S.nq = nq;
    S.ni = ni;
    S.seed_rows.clear();
    S.ans_rows.clear();
    S.seed_off.resize((size_t)nq + 1);
    S.ans_off.resize((size_t)nq + 1);
    S.targets.resize((size_t)nq);
    S.e1.assign((size_t)nq, 0);
    S.fallback.assign((size_t)nq, 0);
    if ((int64_t)S.stamp.size() != sb) {
        S.stamp.assign((size_t)sb, 0);
        S.epoch = 0;
    }
    uint32_t* const stamp = S.stamp.data();
    std::vector<int32_t>& seeds = S.seed_rows;
    std::vector<int32_t>& answers = S.ans_rows;
    std::vector<int64_t>& stack = S.stack;
    for (int k = 0; k < 13; ++k) counts[k] = 0;
    int64_t n_fb = 0, target_rows = 0, pairs_most = 0;

    // the walks are 4,096 short chains of dependent cache misses: the first
    // two links of a later query's chain are asked for while this one walks
    constexpr int64_t kAhead = 8;
    auto prefetch_rows = [&](int64_t li) {
        const int64_t s = sd[li], t = tg[li];
        if (s >= ni && s < n_base) __builtin_prefetch(fwd_indptr + s);
        if (t >= sb && t < nl) __builtin_prefetch(sink_indptr + (t - sb));
    };
    auto prefetch_lists = [&](int64_t li) {
        const int64_t s = sd[li], t = tg[li];
        if (s >= ni && s < n_base) __builtin_prefetch(fwd_indices + fwd_indptr[s]);
        if (t >= sb && t < nl) __builtin_prefetch(sink_indices + sink_indptr[t - sb]);
    };
    for (int64_t li = 0; li < nq && li < kAhead; ++li) prefetch_rows(li);
    for (int64_t li = 0; li < nq && li < kAhead / 2; ++li) prefetch_lists(li);

    for (int64_t li = 0; li < nq; ++li) {
        if (li + kAhead < nq) prefetch_rows(li + kAhead);
        if (li + kAhead / 2 < nq) prefetch_lists(li + kAhead / 2);
        const int64_t s = sd[li], t = tg[li];
        const bool t_int = t >= 0 && t < ni;
        S.targets[(size_t)li] = (int32_t)(t_int ? t : ni);
        const int64_t seed0 = (int64_t)seeds.size(), ans0 = (int64_t)answers.size();
        S.seed_off[(size_t)li] = seed0;
        S.ans_off[(size_t)li] = ans0;
        bool has_start = false, hit = false, e1 = false;
        if (s >= 0 && s < ni) {
            has_start = e1 = true;
            S.e1[(size_t)li] = 1;
            seeds.push_back((int32_t)s);
        } else if ((s >= ni && s < sb) || s >= nl) {
            has_start = true;
            if (++S.epoch == 0) {  // wrapped: old stamps could read as new
                std::fill(S.stamp.begin(), S.stamp.end(), 0u);
                S.epoch = 1;
            }
            const uint32_t ep = S.epoch;
            if (s < sb) stamp[s] = ep;
            stack.clear();
            stack.push_back(s);
            while (!stack.empty()) {
                const int64_t row = stack.back();
                stack.pop_back();
                if (row >= n_base) continue;  // overlay id: no base out-edges
                for (int64_t e = fwd_indptr[row], end = fwd_indptr[row + 1]; e < end; ++e) {
                    const int64_t nbr = fwd_indices[e];
                    if (nbr == t) hit = true;
                    if (nbr >= sb || stamp[nbr] == ep) continue;
                    stamp[nbr] = ep;
                    if (nbr < ni) {
                        seeds.push_back((int32_t)nbr);
                    } else {
                        __builtin_prefetch(fwd_indptr + nbr);
                        stack.push_back(nbr);
                    }
                }
            }
        }
        host_ans[li] = hit;
        bool relay = false;
        if (has_start && t >= sb && t < nl) {
            const int64_t sink = t - sb;
            if (hub_ptr && hub_ptr[sink + 1] > hub_ptr[sink]) {
                relay = true;
                for (int64_t k = hub_ptr[sink]; k < hub_ptr[sink + 1]; ++k) {
                    answers.push_back((int32_t)(ni + 1 + k));
                    target_rows += v->hub_rows[k];
                }
            } else {
                const int64_t lo = sink_indptr[sink], hi = sink_indptr[sink + 1];
                answers.insert(answers.end(), sink_indices + lo, sink_indices + hi);
                target_rows += hi - lo;
            }
        }
        const int64_t n_s = (int64_t)seeds.size() - seed0;
        const int64_t n_r = (int64_t)answers.size() - ans0 + (t_int ? 1 : 0);
        target_rows += t_int ? 1 : 0;
        bool fb = false;
        if (e1 && t_int && s == t) {
            fb = true;
            ++counts[4];
        }
        if (n_s * n_r > v->pair_cap || relay) {
            if (!fb) ++counts[5];
            fb = true;
        }
        if (fb) {
            S.fallback[(size_t)li] = 1;
            ++n_fb;
        } else {
            pairs_most += n_s * n_r;
        }
    }
    S.seed_off[(size_t)nq] = (int64_t)seeds.size();
    S.ans_off[(size_t)nq] = (int64_t)answers.size();
    counts[8] = (int64_t)seeds.size();
    counts[9] = target_rows;
    counts[0] = counts[8] > 0;

    bool whole = n_fb >= whole_min;
    int64_t n_pairs = 0;
    if (!whole && counts[0]) {
        S.pa.resize((size_t)pairs_most);
        S.pb.resize((size_t)pairs_most);
        S.pq.resize((size_t)pairs_most);
        int32_t* const pa = S.pa.data();
        int32_t* const pb = S.pb.data();
        int32_t* const pq = S.pq.data();
        const uint8_t* const out_ok = v->out_ok;
        const uint8_t* const in_ok = v->in_ok;
        const uint8_t* const processed = v->processed;
        const int32_t* const seed_rows = seeds.data();
        for (int64_t li = 0; li < nq; ++li) {
            if (S.fallback[(size_t)li]) continue;
            const int32_t t = S.targets[(size_t)li];
            const bool t_int = t < ni;
            const int32_t* rows = t_int ? &t : answers.data() + S.ans_off[(size_t)li];
            const int64_t n_r =
                t_int ? 1 : S.ans_off[(size_t)li + 1] - S.ans_off[(size_t)li];
            const int64_t mark = n_pairs;
            bool cert = true;
            for (int64_t k = S.seed_off[(size_t)li]; cert && k < S.seed_off[(size_t)li + 1]; ++k) {
                const int32_t a = seed_rows[k];
                const bool a_lab = a < n_lab;
                const bool a_ok = a_lab && out_ok[a], a_done = a_lab && processed[a];
                for (int64_t j = 0; j < n_r; ++j) {
                    const int32_t b = rows[j];
                    // an e2 seed that is the target was reached over a real
                    // edge: the host granted it, reach0 would count 0 edges
                    if (t_int && a == b) continue;
                    if (a_lab && b < n_lab
                        && !(a_ok && in_ok[b] && (a_done || processed[b]))) {
                        cert = false;
                        break;
                    }
                    pa[n_pairs] = a;
                    pb[n_pairs] = b;
                    pq[n_pairs] = (int32_t)li;
                    ++n_pairs;
                }
            }
            if (!cert) {
                n_pairs = mark;
                S.fallback[(size_t)li] = 1;
                ++n_fb;
                ++counts[6];
            }
        }
        whole = n_fb >= whole_min;
    }
    if (whole) {
        counts[7] = nq - n_fb;
        n_fb = nq;
        std::fill(S.fallback.begin(), S.fallback.end(), (uint8_t)1);
        n_pairs = 0;
    }
    S.pa.resize((size_t)n_pairs);
    S.pb.resize((size_t)n_pairs);
    S.pq.resize((size_t)n_pairs);
    counts[1] = n_pairs;
    counts[2] = n_fb;
    counts[3] = whole;
    for (int64_t li = 0; li < nq; ++li) {
        fallback_out[li] = S.fallback[(size_t)li];
        if (!S.fallback[(size_t)li]) continue;
        const int64_t n_s = S.seed_off[(size_t)li + 1] - S.seed_off[(size_t)li];
        counts[S.e1[(size_t)li] ? 10 : 11] += n_s;
        counts[12] += S.ans_off[(size_t)li + 1] - S.ans_off[(size_t)li];
    }
}

// The last keto_pack_labeled's pairs as label_step reads them: out is
// int32[3 * P], pa at 0, pb at P, pq at 2 * P, padded (ni, ni, 0).
// Returns their count, or -1 with nothing written where P is under it.
int64_t keto_pack_labeled_pairs(int32_t* out, int64_t P) {
    const LabeledScratch& S = labeled_scratch;
    const size_t n = S.pa.size();
    if ((int64_t)n > P) return -1;
    const std::vector<int32_t>* parts[3] = {&S.pa, &S.pb, &S.pq};
    for (int k = 0; k < 3; ++k) {
        int32_t* dst = out + (int64_t)k * P;
        if (n) std::memcpy(dst, parts[k]->data(), n * sizeof(int32_t));
        std::fill(dst + n, dst + P, k < 2 ? (int32_t)S.ni : 0);
    }
    return (int64_t)n;
}

// The last keto_pack_labeled's riders as pack_chunk's seven arrays hold
// them, unpadded: the entries of the queries that fell back, under their
// positions in the chunk; targets is int32[B], ni past the chunk.
void keto_pack_labeled_riders(int32_t* e1r, int32_t* e1q, int32_t* e2r,
                              int32_t* e2q, int32_t* ar, int32_t* aq,
                              int32_t* targets, int64_t B) {
    const LabeledScratch& S = labeled_scratch;
    std::memcpy(targets, S.targets.data(), (size_t)S.nq * sizeof(int32_t));
    std::fill(targets + S.nq, targets + B, (int32_t)S.ni);
    for (int64_t li = 0; li < S.nq; ++li) {
        if (!S.fallback[(size_t)li]) continue;
        int32_t*& rows = S.e1[(size_t)li] ? e1r : e2r;
        int32_t*& qs = S.e1[(size_t)li] ? e1q : e2q;
        for (int64_t k = S.seed_off[(size_t)li]; k < S.seed_off[(size_t)li + 1]; ++k) {
            *rows++ = S.seed_rows[(size_t)k];
            *qs++ = (int32_t)li;
        }
        for (int64_t k = S.ans_off[(size_t)li]; k < S.ans_off[(size_t)li + 1]; ++k) {
            *ar++ = S.ans_rows[(size_t)k];
            *aq++ = (int32_t)li;
        }
    }
}

// The dispatch thread's `resolve` of one chunk of n queries, in one call
// (check/dispatch.py _resolve_chunk; _resolve_records, _entry_counts, the
// reach mask of _dispatch_piece and _rewrite_split's closure bytes are the
// contract, array for array: tests/test_resolve_fused.py fuzzes it).
//
//  - sd, tg (int64[n]): the raw node ids start_raw / sub_raw (-1: the tables
//    hold no such node) through raw2dev; a target only where the query has a
//    start and the row is live; sd = tg = -1 at dead, tg = -1 at no_target.
//    With start_raw null, sd and tg are the caller's and only read: the
//    positions a gate expansion made of a chunk, counted anew;
//  - csum, csum_reach (int64[n + 1], running sums from 0): a query's device
//    entries (an interior start 1, a host-propagated start its base
//    out-degree or 1 past the base, a sink target of a query with a start
//    its rows or, where hub_ptr names some, its relay rows), and the same
//    with the queries zeroed whose live target no pull can change (reach).
//    Both null: nothing is counted (under a plan with gates the caller
//    counts the positions the expansion makes of the chunk, not its queries);
//  - flags_out (uint8[n], where the view has flags and flags_out is not
//    null): flags[sd], 0 without a start row under n_flags.
//
// counts is int64[4]: 0 queries other than the dead that miss a start or a
// target (what the overlay asks before it re-resolves anything), 1 closure
// bytes with the view's `rewritten` bit, 2 starts at or past n_flags (an
// overlay start: its byte is not the table's to give), 3 inputs out of range
// (a raw id past n_raw, a mark outside the chunk): nothing to trust then.
void keto_resolve_chunk(const KetoResolveView* v, int64_t n,
                        const int64_t* start_raw, const int64_t* sub_raw,
                        const int64_t* dead, int64_t n_dead,
                        const int64_t* no_target, int64_t n_no_target,
                        int64_t* sd, int64_t* tg, uint8_t* flags_out,
                        int64_t* csum, int64_t* csum_reach, int64_t* counts) {
    const int64_t ni = v->ni, sb = v->sb, nl = v->nl, n_base = v->n_base;
    const int64_t* const r2d = v->raw2dev;
    const int64_t* const ip = v->fwd_indptr;
    const int64_t* const sp = v->sink_indptr;
    const int64_t* const hub_ptr = v->hub_ptr;
    const uint8_t* const reach = v->reach;
    const uint8_t* const flags = flags_out ? v->flags : nullptr;
    for (int k = 0; k < 4; ++k) counts[k] = 0;
    constexpr int64_t kAhead = 16;
    if (start_raw) {
        // 2n independent reads of a table far larger than the cache
        auto ask = [&](int64_t i) {
            if (start_raw[i] >= 0) __builtin_prefetch(r2d + start_raw[i]);
            if (sub_raw[i] >= 0) __builtin_prefetch(r2d + sub_raw[i]);
        };
        for (int64_t i = 0; i < n && i < kAhead; ++i) ask(i);
        for (int64_t i = 0; i < n; ++i) {
            if (i + kAhead < n) ask(i + kAhead);
            const int64_t a = start_raw[i], b = sub_raw[i];
            if (a >= v->n_raw || b >= v->n_raw) {
                ++counts[3];
                sd[i] = tg[i] = -1;
                continue;
            }
            const int64_t s = a >= 0 ? r2d[a] : -1;
            const int64_t t = b >= 0 ? r2d[b] : -1;
            sd[i] = s;
            // a target only matters when the query has starts
            tg[i] = (t >= 0 && t < nl && s >= 0) ? t : -1;
        }
        for (int64_t k = 0; k < n_dead; ++k) {
            const int64_t i = dead[k];
            if (i < 0 || i >= n) { ++counts[3]; continue; }
            sd[i] = tg[i] = -1;
        }
        for (int64_t k = 0; k < n_no_target; ++k) {
            const int64_t i = no_target[k];
            if (i < 0 || i >= n) { ++counts[3]; continue; }
            tg[i] = -1;
        }
    }
    const bool count = csum != nullptr;
    auto ask_rows = [&](int64_t i) {
        const int64_t s = sd[i], t = tg[i];
        if (flags && s >= 0 && s < v->n_flags) __builtin_prefetch(flags + s);
        if (!count) return;
        if (s >= ni && s < n_base) __builtin_prefetch(ip + s);
        if (t >= sb && t < nl) {
            __builtin_prefetch(sp + (t - sb));
            if (hub_ptr) __builtin_prefetch(hub_ptr + (t - sb));
        }
        if (reach && t >= 0 && t < nl) __builtin_prefetch(reach + t);
    };
    for (int64_t i = 0; i < n && i < kAhead; ++i) ask_rows(i);
    int64_t total = 0, total_reach = 0, misses = 0, rewritten = 0;
    if (count) csum[0] = csum_reach[0] = 0;
    for (int64_t i = 0; i < n; ++i) {
        if (i + kAhead < n) ask_rows(i + kAhead);
        const int64_t s = sd[i], t = tg[i];
        misses += (s == -1 || t == -1);
        if (count) {
            int64_t c = 0;
            bool has_start = false;
            if (s >= 0 && s < ni) {
                has_start = true;
                c = 1;
            } else if ((s >= ni && s < sb) || s >= nl) {
                has_start = true;
                c = s < n_base ? ip[s + 1] - ip[s] : 1;  // overlay adjacency is small
            }
            if (has_start && t >= sb && t < nl) {
                const int64_t k = t - sb;
                int64_t rows = sp[k + 1] - sp[k];
                // a hub sink sends its relay rows, not its rows
                if (hub_ptr && hub_ptr[k + 1] > hub_ptr[k]) rows = hub_ptr[k + 1] - hub_ptr[k];
                c += rows;
            }
            total += c;
            csum[i + 1] = total;
            // a query whose target side has no row that a pull changes sends
            // the device nothing: its entries do not count towards a cut
            if (!(reach && t >= 0 && t < nl && !reach[t])) total_reach += c;
            csum_reach[i + 1] = total_reach;
        }
        if (flags) {
            uint8_t f = 0;
            if (s >= v->n_flags) ++counts[2];
            else if (s >= 0) f = flags[s];
            flags_out[i] = f;
            rewritten += (f & v->rewritten) != 0;
        }
    }
    counts[0] = misses - (start_raw ? n_dead : 0);
    counts[1] = rewritten;
}

}  // extern "C"
