/* Merged-bottom-k MinHash pair statistics over a dense sketch matrix.
 *
 * Compiled-C twin of the reference's host pair loop (the reference runs
 * finch's merge walk in compiled Rust on a rayon pool; reference:
 * src/finch.rs:53-73). This is the honest CPU baseline for bench.py —
 * the strongest available stand-in given no Rust toolchain in the image
 * — and doubles as a production CPU fallback for the all-pairs pass.
 *
 * Semantics mirror the device extraction exactly: walk the two sorted
 * sketches in merge order over the smallest `sketch_size` distinct
 * union hashes (galah_tpu/ops/minhash_np.py::mash_jaccard), then apply
 * the SAME f64 rational keep-check as ops/pairwise.threshold_pairs'
 * host pass — common >= j_thr * total with j_thr precomputed by
 * ani_to_jaccard (no per-pair exp/log in the decision, so borderline
 * pairs cannot order differently from the device path) — and report
 * ANI = 1 + ln(2j/(1+j))/k for the survivors. total == 0 pairs (two
 * empty sketches) are never emitted, matching the device extraction.
 * Rows are sorted ascending with 0xFFFF..FF sentinel padding; per-row
 * valid lengths arrive precomputed.
 */

#include <math.h>
#include <pthread.h>
#include <stdint.h>
#include <stdlib.h>
#include <string.h>

#if defined(__x86_64__) && defined(__GNUC__)
#include <immintrin.h>
#define GALAH_HAVE_AVX512_BUILD 1
#endif

typedef struct {
    const uint64_t *mat;
    const int64_t *lens;
    int64_t n, width;
    int sketch_size, kmer;
    double j_thr;        /* Jaccard-domain threshold (ani_to_jaccard) */
    int tid, n_threads;
    int64_t *out_i, *out_j;
    double *out_ani;
    int64_t cap;
    int64_t *next_slot;  /* shared atomic append cursor */
    int64_t found;       /* per-thread total (incl. past-cap) */
} ps_job;

static void pair_stats(const uint64_t *a, int64_t la, const uint64_t *b,
                       int64_t lb, int size, int64_t *common_out,
                       int64_t *total_out) {
    int64_t i = 0, j = 0, common = 0, total = 0;
    while (i < la && j < lb && total < size) {
        uint64_t x = a[i], y = b[j];
        if (x < y) {
            i++;
        } else if (y < x) {
            j++;
        } else {
            common++;
            i++;
            j++;
        }
        total++;
    }
    while (i < la && total < size) {
        i++;
        total++;
    }
    while (j < lb && total < size) {
        j++;
        total++;
    }
    *common_out = common;
    *total_out = total;
}

static void *worker(void *arg) {
    ps_job *w = (ps_job *)arg;
    /* interleaved rows: balances the shrinking upper triangle */
    for (int64_t r = w->tid; r < w->n; r += w->n_threads) {
        const uint64_t *ra = w->mat + r * w->width;
        int64_t la = w->lens[r];
        for (int64_t c = r + 1; c < w->n; c++) {
            int64_t common, total;
            pair_stats(ra, la, w->mat + c * w->width, w->lens[c],
                       w->sketch_size, &common, &total);
            if (total == 0 ||
                (double)common < w->j_thr * (double)total)
                continue;
            double jac = (double)common / (double)total;
            double ani =
                common > 0
                    ? 1.0 - (-log(2.0 * jac / (1.0 + jac)) /
                             (double)w->kmer)
                    : 0.0;
            w->found++;
            int64_t slot =
                __sync_fetch_and_add(w->next_slot, (int64_t)1);
            if (slot < w->cap) {
                w->out_i[slot] = r;
                w->out_j[slot] = c;
                w->out_ani[slot] = ani;
            }
        }
    }
    return NULL;
}

/* Exact merged-bottom-k stats for an EXPLICIT pair list (the sparse
 * screened path): for each (pi[x], pj[x]) run the same merge walk and
 * f64 rational keep-check as the all-pairs kernel; out_ani[x] = ANI for
 * keepers, -inf for non-keepers (a real ANI is always finite). Pairs
 * are split across threads. */

typedef struct {
    const uint64_t *mat;
    const int64_t *lens, *pi, *pj;
    int64_t n_pairs, width;
    int sketch_size, kmer;
    double j_thr;
    int tid, n_threads;
    double *out_ani;
} pl_job;

static void *pl_worker(void *arg) {
    pl_job *w = (pl_job *)arg;
    for (int64_t x = w->tid; x < w->n_pairs; x += w->n_threads) {
        int64_t i = w->pi[x], j = w->pj[x];
        int64_t common, total;
        pair_stats(w->mat + i * w->width, w->lens[i],
                   w->mat + j * w->width, w->lens[j], w->sketch_size,
                   &common, &total);
        if (total == 0 ||
            (double)common < w->j_thr * (double)total) {
            w->out_ani[x] = -HUGE_VAL; /* impossible ANI = rejected */
            continue;
        }
        double jac = (double)common / (double)total;
        w->out_ani[x] =
            common > 0
                ? 1.0 - (-log(2.0 * jac / (1.0 + jac)) /
                         (double)w->kmer)
                : 0.0;
    }
    return NULL;
}

void galah_pair_stats_for_pairs(
    const uint64_t *mat, int64_t n_pairs, int64_t width,
    const int64_t *lens, const int64_t *pi, const int64_t *pj,
    int sketch_size, int kmer, double j_thr, int n_threads,
    double *out_ani) {
    if (n_threads < 1) n_threads = 1;
    if (n_threads > 64) n_threads = 64;
    pl_job jobs[64];
    pthread_t tids[64];
    for (int t = 0; t < n_threads; t++)
        jobs[t] = (pl_job){mat, lens, pi, pj, n_pairs, width,
                           sketch_size, kmer, j_thr, t, n_threads,
                           out_ani};
    if (n_threads == 1) {
        pl_worker(&jobs[0]);
        return;
    }
    for (int t = 0; t < n_threads; t++)
        pthread_create(&tids[t], NULL, pl_worker, &jobs[t]);
    for (int t = 0; t < n_threads; t++)
        pthread_join(tids[t], NULL);
}

/* Per-window fragment membership counts: for each row of `wins`
 * (SENTINEL-masked positional hash windows, ops/fragment_ani
 * GenomeProfile.windows layout), count valid hashes and how many are
 * present in the sorted distinct `ref` set (binary search) — the C twin
 * of ops/fragment_ani._window_match_counts_impl for CPU backends.
 * Rows are split across n_threads (each row is independent). */

typedef struct {
    const uint64_t *wins, *ref;
    int64_t W, L, H;
    int32_t *matched, *total;
    int tid, n_threads;
} wm_job;

static void *wm_worker(void *arg) {
    wm_job *w = (wm_job *)arg;
    const uint64_t SENT = 0xFFFFFFFFFFFFFFFFull;
    const uint64_t *ref = w->ref;
    const int64_t H = w->H;
    for (int64_t r = w->tid; r < w->W; r += w->n_threads) {
        const uint64_t *row = w->wins + r * w->L;
        int32_t m = 0, t = 0;
        for (int64_t i = 0; i < w->L; i++) {
            uint64_t h = row[i];
            if (h == SENT) continue;
            t++;
            /* branchless lower_bound: the compare compiles to cmov,
             * halving the branchy version's misprediction stalls */
            int64_t lo = 0, len = H;
            while (len > 1) {
                int64_t half = len >> 1;
                lo += (ref[lo + half - 1] < h) ? half : 0;
                len -= half;
            }
            if (H > 0 && ref[lo] == h) m++;
        }
        w->matched[r] = m;
        w->total[r] = t;
    }
    return NULL;
}

void galah_window_match_counts(const uint64_t *wins, int64_t W,
                               int64_t L, const uint64_t *ref,
                               int64_t H, int n_threads,
                               int32_t *matched, int32_t *total) {
    if (n_threads < 1) n_threads = 1;
    if (n_threads > 64) n_threads = 64;
    if ((int64_t)n_threads > W) n_threads = W > 0 ? (int)W : 1;
    /* pthread spawn (~100 us each) swamps small membership tests —
     * typical greedy-phase calls are a few dozen windows */
    if (W * L < (int64_t)1 << 16) n_threads = 1;
    wm_job jobs[64];
    pthread_t tids[64];
    for (int t = 0; t < n_threads; t++)
        jobs[t] = (wm_job){wins, ref, W, L, H, matched, total,
                           t, n_threads};
    if (n_threads == 1) {
        wm_worker(&jobs[0]);
        return;
    }
    for (int t = 0; t < n_threads; t++)
        pthread_create(&tids[t], NULL, wm_worker, &jobs[t]);
    for (int t = 0; t < n_threads; t++)
        pthread_join(tids[t], NULL);
}

/* Returns the TOTAL number of passing pairs (callers detect overflow by
 * comparing against `cap`); the first min(total, cap) pairs are written
 * to the output arrays in nondeterministic thread order. */
int64_t galah_pair_stats_threshold(
    const uint64_t *mat, int64_t n, int64_t width, const int64_t *lens,
    int sketch_size, int kmer, double j_thr, int n_threads,
    int64_t *out_i, int64_t *out_j, double *out_ani, int64_t cap) {
    if (n_threads < 1) n_threads = 1;
    if (n_threads > 64) n_threads = 64;
    int64_t next_slot = 0;
    ps_job jobs[64];
    pthread_t tids[64];
    for (int t = 0; t < n_threads; t++) {
        jobs[t] = (ps_job){mat, lens, n, width, sketch_size, kmer,
                           j_thr, t, n_threads, out_i, out_j,
                           out_ani, cap, &next_slot, 0};
    }
    if (n_threads == 1) {
        worker(&jobs[0]);
        return jobs[0].found;
    }
    for (int t = 0; t < n_threads; t++)
        pthread_create(&tids[t], NULL, worker, &jobs[t]);
    int64_t total = 0;
    for (int t = 0; t < n_threads; t++) {
        pthread_join(tids[t], NULL);
        total += jobs[t].found;
    }
    return total;
}

/* Compacted positional-hash window builder — the C twin of the
 * subsample_c > 1 branch of ops/fragment_ani GenomeProfile.windows():
 * surviving (non-sentinel) hashes move to the front of each fragment
 * row, k-mers crossing the fragment boundary (in-row position >=
 * L - (k - 1)) are dropped, row order of survivors is preserved. The
 * numpy formulation is a stable argsort over the full (W, L) array
 * (~150 ms per 3 Mbp genome); these two streaming passes replace it.
 *
 * Pass 1: per-row survivor counts (galah_window_survivor_counts) so
 * the caller can size `slots`. Pass 2: fill `wins` (W x slots,
 * prefilled with the sentinel by the caller). */

void galah_window_survivor_counts(const uint64_t *flat, int64_t n_flat,
                                  int64_t W, int64_t L, int k,
                                  int64_t *counts) {
    const uint64_t SENT = 0xFFFFFFFFFFFFFFFFull;
    const int64_t keep = L - (k - 1);
    for (int64_t r = 0; r < W; r++) counts[r] = 0;
    for (int64_t i = 0; i < n_flat; i++) {
        if (flat[i] == SENT) continue;
        int64_t r = i / L;
        if (i - r * L < keep) counts[r]++;
    }
}

void galah_fill_compact_windows(const uint64_t *flat, int64_t n_flat,
                                int64_t W, int64_t L, int k,
                                int64_t slots, uint64_t *wins) {
    const int64_t keep = L - (k - 1);
    const uint64_t SENT = 0xFFFFFFFFFFFFFFFFull;
    int64_t fill = 0, row = 0;
    for (int64_t i = 0; i < n_flat; i++) {
        int64_t r = i / L;
        if (r != row) {
            row = r;
            fill = 0;
        }
        if (flat[i] == SENT || i - r * L >= keep) continue;
        wins[r * slots + fill++] = flat[i];
    }
    (void)W;
    (void)SENT;
}

/* Sorted-merge membership counter — the per-pair fast path of the
 * fragment-ANI membership test. The matrix walker above pays
 * O(valid_slots * log H) binary searches per pair; with the query's
 * surviving hashes pre-sorted once per profile (cached host-side),
 * one linear merge against the sorted distinct ref set costs
 * O(nq + H) per pair. matched must be zeroed by the caller; totals
 * are pair-independent (per-window valid counts) and are computed by
 * the caller once per profile. Bit-identical matched counts to
 * galah_window_match_counts on the same windows. */
static void merge_count_scalar(const uint64_t *qh, const int32_t *qw,
                               int64_t nq, const uint64_t *ref,
                               int64_t H, int32_t *matched) {
    int64_t r = 0;
    for (int64_t i = 0; i < nq; i++) {
        uint64_t h = qh[i];
        while (r < H && ref[r] < h) r++;
        /* branchless increment — see the batch worker's note */
        matched[qw[i]] += (int32_t)(r < H && ref[r] == h);
    }
}

#ifdef GALAH_HAVE_AVX512_BUILD
/* AVX-512 block merge: compare 8-element query blocks against
 * 8-element ref blocks, all 64 lane combinations per block pair via 7
 * in-register rotations (valignq) + cmpeq, then advance the block
 * whose max is smaller. Ties advance the QUERY block only — the ref
 * block holding the equal element stays resident, so query duplicates
 * in later blocks still see it (ref values are distinct, query values
 * need not be). Match bits accumulate per query block and are flushed
 * as matched[qw[...]] increments at block retirement; the masked
 * flush preserves exact per-window counts. Scalar tails finish the
 * sub-block remainders from the block cursors — safe because every
 * retired ref block's max is strictly below some retired query max,
 * so no remaining query element can equal a retired ref element.
 * Bit-identical to merge_count_scalar by construction (and pinned by
 * tests/test_cpairstats.py across regimes and odd sizes). */
__attribute__((target("avx512f")))
static void merge_count_avx512(const uint64_t *qh, const int32_t *qw,
                               int64_t nq, const uint64_t *ref,
                               int64_t H, int32_t *matched) {
    int64_t qi = 0, ri = 0;
    const int64_t nqb = nq & ~(int64_t)7, nrb = H & ~(int64_t)7;
    if (nqb > 0 && nrb > 0) {
        __m512i qv = _mm512_loadu_si512((const void *)(qh + qi));
        __m512i rv = _mm512_loadu_si512((const void *)(ref + ri));
        unsigned m = 0;
        for (;;) {
            m |= (unsigned)_mm512_cmpeq_epu64_mask(qv, rv);
            m |= (unsigned)_mm512_cmpeq_epu64_mask(
                qv, _mm512_alignr_epi64(rv, rv, 1));
            m |= (unsigned)_mm512_cmpeq_epu64_mask(
                qv, _mm512_alignr_epi64(rv, rv, 2));
            m |= (unsigned)_mm512_cmpeq_epu64_mask(
                qv, _mm512_alignr_epi64(rv, rv, 3));
            m |= (unsigned)_mm512_cmpeq_epu64_mask(
                qv, _mm512_alignr_epi64(rv, rv, 4));
            m |= (unsigned)_mm512_cmpeq_epu64_mask(
                qv, _mm512_alignr_epi64(rv, rv, 5));
            m |= (unsigned)_mm512_cmpeq_epu64_mask(
                qv, _mm512_alignr_epi64(rv, rv, 6));
            m |= (unsigned)_mm512_cmpeq_epu64_mask(
                qv, _mm512_alignr_epi64(rv, rv, 7));
            if (ref[ri + 7] < qh[qi + 7]) {
                ri += 8;
                if (ri >= nrb) break;
                rv = _mm512_loadu_si512((const void *)(ref + ri));
            } else {
                while (m) {
                    int l = __builtin_ctz(m);
                    matched[qw[qi + l]]++;
                    m &= m - 1;
                }
                qi += 8;
                if (qi >= nqb) break;
                qv = _mm512_loadu_si512((const void *)(qh + qi));
            }
        }
        /* ref-exhausted exit leaves the current query block's bits
         * unflushed (query-exhausted exit left m == 0) */
        while (m) {
            int l = __builtin_ctz(m);
            matched[qw[qi + l]]++;
            m &= m - 1;
        }
    }
    /* scalar tails: double counting is impossible — a lane counted by
     * the mask matched a distinct ref value at index < ri, which the
     * offset scalar walk (equivalent to starting at r = ri) never
     * revisits */
    merge_count_scalar(qh + qi, qw + qi, nq - qi, ref + ri, H - ri,
                       matched);
}
#endif

typedef void (*merge_count_t)(const uint64_t *, const int32_t *,
                              int64_t, const uint64_t *, int64_t,
                              int32_t *);

/* Resolve the dispatch ONCE per public entry (not per pair — the
 * batched path exists because pair volume reaches N^2/2, and a getenv
 * environ scan per pair from concurrent threads is pure overhead).
 * Re-resolving per entry keeps GALAH_TPU_NO_AVX512 togglable within a
 * process (the A/B tests rely on that). */
static merge_count_t merge_count_resolve(void) {
#ifdef GALAH_HAVE_AVX512_BUILD
    if (__builtin_cpu_supports("avx512f") &&
        !getenv("GALAH_TPU_NO_AVX512"))
        return merge_count_avx512;
#endif
    return merge_count_scalar;
}

void galah_window_match_counts_merge(
    const uint64_t *qh, const int32_t *qw, int64_t nq,
    const uint64_t *ref, int64_t H, int32_t *matched) {
    merge_count_resolve()(qh, qw, nq, ref, H, matched);
}

/* Per-window matched counts from per-element 0/1 membership flags:
 * matched[qw[i]] += flags[i]. The host fold after the device
 * window-match kernel (ops/pallas_fragment.py) — one pass, outside the
 * Python lock, so several pairs fold at once while the device runs. */
void galah_fold_window_flags(const int8_t *flags, const int32_t *qw,
                             int64_t n, int32_t *matched) {
    for (int64_t i = 0; i < n; i++)
        matched[qw[i]] += flags[i];
}

/* Capability probe for the test harness: 1 iff the merge counter
 * would dispatch to the AVX-512 kernel right now (build support +
 * CPU support + GALAH_TPU_NO_AVX512 unset). Lets the A/B identity
 * test SKIP with an explicit reason instead of silently comparing
 * scalar against scalar on hosts without avx512f. */
int galah_merge_uses_avx512(void) {
#ifdef GALAH_HAVE_AVX512_BUILD
    return merge_count_resolve() != merge_count_scalar;
#else
    return 0;
#endif
}

/* Batched sorted-merge membership counter: the per-PAIR-LIST twin of
 * galah_window_match_counts_merge, for the exact-ANI stage when the
 * pair volume is large (the dense-similarity regime can carry N^2/2
 * screened pairs — a 5k-genome mega-family is 12.5M of them, and the
 * Python per-pair loop around the single-pair entry costs ~100x the
 * merge itself at typical small-genome sizes).
 *
 * Per-genome query data (qh/qw concatenated, offset by q_off) and
 * per-genome sorted distinct ref sets (ref concatenated, offset by
 * r_off) are laid out once by the caller; pair p counts query
 * pair_q[p] against ref pair_r[p] into the concatenated matched
 * output at m_off[p] (caller-computed prefix of each query's window
 * count; the output buffer must be zeroed). Pairs are independent —
 * split across threads; when H is much smaller than nq the merge
 * degenerates gracefully (it is O(nq + H) either way). */
typedef struct {
    const uint64_t *qh_cat;
    const int32_t *qw_cat;
    const int64_t *q_off;     /* per-genome [g, g+1) into qh/qw */
    const uint64_t *ref_cat;
    const int64_t *r_off;     /* per-genome [g, g+1) into ref_cat */
    const int32_t *pair_q, *pair_r;
    const int64_t *m_off;     /* per-pair output offset */
    int64_t n_pairs;
    int32_t *matched_cat;
    int tid, n_threads;
} wmb_job;

static void *wmb_worker(void *arg) {
    wmb_job *w = (wmb_job *)arg;
    merge_count_t mc = merge_count_resolve(); /* once per worker */
    for (int64_t p = w->tid; p < w->n_pairs; p += w->n_threads) {
        int64_t qg = w->pair_q[p], rg = w->pair_r[p];
        const uint64_t *qh = w->qh_cat + w->q_off[qg];
        const int32_t *qw = w->qw_cat + w->q_off[qg];
        int64_t nq = w->q_off[qg + 1] - w->q_off[qg];
        const uint64_t *ref = w->ref_cat + w->r_off[rg];
        int64_t H = w->r_off[rg + 1] - w->r_off[rg];
        int32_t *matched = w->matched_cat + w->m_off[p];
        /* AVX-512 block merge when the CPU has it, scalar walk
         * otherwise (branchless increment: in the dense-similarity
         * regime ~all query hashes match, in the sparse regime ~none —
         * either way compare-to-increment beats a data-dependent
         * branch) */
        mc(qh, qw, nq, ref, H, matched);
    }
    return NULL;
}

void galah_window_match_counts_merge_batch(
    const uint64_t *qh_cat, const int32_t *qw_cat,
    const int64_t *q_off, const uint64_t *ref_cat,
    const int64_t *r_off, const int32_t *pair_q,
    const int32_t *pair_r, const int64_t *m_off, int64_t n_pairs,
    int n_threads, int32_t *matched_cat) {
    if (n_threads < 1) n_threads = 1;
    if (n_threads > 64) n_threads = 64;
    if ((int64_t)n_threads > n_pairs)
        n_threads = n_pairs > 0 ? (int)n_pairs : 1;
    wmb_job jobs[64];
    pthread_t tids[64];
    for (int t = 0; t < n_threads; t++)
        jobs[t] = (wmb_job){qh_cat, qw_cat, q_off, ref_cat, r_off,
                            pair_q, pair_r, m_off, n_pairs,
                            matched_cat, t, n_threads};
    if (n_threads == 1) {
        wmb_worker(&jobs[0]);
        return;
    }
    for (int t = 0; t < n_threads; t++)
        pthread_create(&tids[t], NULL, wmb_worker, &jobs[t]);
    for (int t = 0; t < n_threads; t++)
        pthread_join(tids[t], NULL);
}

/* Window assembly from the profile walk's kept (pos, hash) pairs —
 * O(n_valid) twins of galah_window_survivor_counts /
 * galah_fill_compact_windows, which each stream the full
 * 8-byte-per-bp flat array. Semantics identical: positions whose
 * in-window column is >= L - (k - 1) (a k-mer crossing the window
 * boundary) are dropped; survivors keep genome order within their
 * window. counts must be zeroed; wins must be SENTINEL-filled. */
void galah_window_counts_pairs(const int64_t *pos, int64_t nv,
                               int64_t W, int64_t L, int k,
                               int64_t *counts) {
    int64_t tail = L - (k - 1);
    for (int64_t i = 0; i < nv; i++) {
        int64_t col = pos[i] % L;
        if (col < tail) counts[pos[i] / L]++;
    }
    (void)W;
}

void galah_fill_windows_pairs(const int64_t *pos, const uint64_t *h,
                              int64_t nv, int64_t W, int64_t L, int k,
                              int64_t slots, int64_t *cursors,
                              uint64_t *wins) {
    int64_t tail = L - (k - 1);
    for (int64_t i = 0; i < nv; i++) {
        int64_t col = pos[i] % L;
        if (col >= tail) continue;
        int64_t w = pos[i] / L;
        int64_t c = cursors[w]++;
        if (c < slots) wins[w * slots + c] = h[i];
    }
    (void)W;
}

/* Sorted exact-ANI query of one profile — the C twin of
 * fragment_ani.sorted_query_numpy (windows() -> mask -> nonzero ->
 * stable argsort -> gathers). Row w of the window matrix
 * is flat[w*L, w*L + L - (k - 1)) clipped to n_flat (the tail k-mers
 * cross the fragment boundary); SENTINEL entries do not survive.
 *
 * galah_sorted_query_totals: per-window survivor counts and the OR of
 * every surviving hash (its highest set bit places the first radix
 * digit, so a FracMinHash-subsampled profile, whose hashes all sit
 * below 2^64/c, still spreads over every bucket). Returns the
 * survivor count nq, which sizes qh/qw.
 *
 * galah_sorted_query: the survivors as (hash, window) pairs sorted by
 * hash, equal hashes in ascending window order — the stable argsort's
 * output. Most-significant-digit radix sort: the window walk scatters
 * into qh/qw by the top digit (stable, window order kept); each bucket
 * goes through a scratch the size of the largest bucket and back into
 * place by the next digit, sized to the bucket so a sub-bucket holds a
 * pair or two; a stable insertion (merge, past SQ_SMALL pairs — a
 * repeat's run of equal hashes) sort finishes each sub-bucket. The
 * hashes are uniform (murmur3), so the digits split evenly and the
 * scratch stays cache-sized. Returns 0; -1 if the scratch could not be
 * allocated; -2, with qh/qw untouched, if nq is not the survivor
 * count. */

#define SQ_DIGIT_BITS 11
#define SQ_SMALL 32

int64_t galah_sorted_query_totals(const uint64_t *flat, int64_t n_flat,
                                  int64_t W, int64_t L, int k,
                                  int32_t *totals, uint64_t *hash_or) {
    const uint64_t SENT = 0xFFFFFFFFFFFFFFFFull;
    const int64_t keep = L - (k - 1);
    int64_t nq = 0;
    uint64_t acc = 0;
    for (int64_t w = 0; w < W; w++) {
        int64_t s = w * L, e = s + keep;
        if (e > n_flat) e = n_flat;
        int32_t c = 0;
        for (int64_t i = s; i < e; i++) {
            uint64_t h = flat[i];
            if (h == SENT) continue;
            c++;
            acc |= h;
        }
        totals[w] = c;
        nq += c;
    }
    *hash_or = acc;
    return nq;
}

static void sq_isort(uint64_t *h, int32_t *w, int64_t n) {
    for (int64_t i = 1; i < n; i++) {
        uint64_t kh = h[i];
        int32_t kw = w[i];
        int64_t j = i;
        while (j > 0 && h[j - 1] > kh) {
            h[j] = h[j - 1];
            w[j] = w[j - 1];
            j--;
        }
        h[j] = kh;
        w[j] = kw;
    }
}

/* stable merge sort of h/w[0, n) in place, sh/sw[0, n) as scratch */
static void sq_msort(uint64_t *h, int32_t *w, uint64_t *sh, int32_t *sw,
                     int64_t n) {
    if (n <= SQ_SMALL) {
        sq_isort(h, w, n);
        return;
    }
    int64_t mid = n / 2;
    sq_msort(h, w, sh, sw, mid);
    sq_msort(h + mid, w + mid, sh + mid, sw + mid, n - mid);
    if (h[mid - 1] <= h[mid]) return;
    memcpy(sh, h, (size_t)mid * sizeof(uint64_t));
    memcpy(sw, w, (size_t)mid * sizeof(int32_t));
    /* the output cursor o = i + (j - mid) never passes j */
    int64_t i = 0, j = mid, o = 0;
    while (i < mid && j < n) {
        if (h[j] < sh[i]) {
            h[o] = h[j];
            w[o++] = w[j++];
        } else {
            h[o] = sh[i];
            w[o++] = sw[i++];
        }
    }
    while (i < mid) {
        h[o] = sh[i];
        w[o++] = sw[i++];
    }
}

static int sq_floor_log2(int64_t n) {
    return 63 - __builtin_clzll((uint64_t)n);
}

int galah_sorted_query(const uint64_t *flat, int64_t n_flat, int64_t W,
                       int64_t L, int k, uint64_t hash_or, int64_t nq,
                       uint64_t *qh, int32_t *qw) {
    const uint64_t SENT = 0xFFFFFFFFFFFFFFFFull;
    if (nq <= 0) return 0;
    const int64_t keep = L - (k - 1);
    int hb = hash_or ? 64 - __builtin_clzll(hash_or) : 0;
    int b1 = hb < SQ_DIGIT_BITS ? hb : SQ_DIGIT_BITS;
    int shift1 = hb - b1;
    int64_t nb1 = (int64_t)1 << b1;
    int64_t *off = (int64_t *)calloc((size_t)nb1 + 1, sizeof(int64_t));
    int64_t *sub = (int64_t *)malloc(
        (((size_t)1 << SQ_DIGIT_BITS) + 1) * sizeof(int64_t));
    if (!off || !sub) {
        free(off);
        free(sub);
        return -1;
    }

    /* top digit: histogram, then a stable scatter in window order
     * straight into qh/qw */
    for (int64_t w = 0; w < W; w++) {
        int64_t s = w * L, e = s + keep;
        if (e > n_flat) e = n_flat;
        for (int64_t i = s; i < e; i++)
            if (flat[i] != SENT) off[(flat[i] >> shift1) + 1]++;
    }
    int64_t big = 0;
    for (int64_t b = 0; b < nb1; b++) {
        if (off[b + 1] > big) big = off[b + 1];
        off[b + 1] += off[b];
    }
    if (off[nb1] != nq) {
        /* not the count galah_sorted_query_totals gave: qh/qw are
         * sized for nq */
        free(off);
        free(sub);
        return -2;
    }
    for (int64_t w = 0; w < W; w++) {
        int64_t s = w * L, e = s + keep;
        if (e > n_flat) e = n_flat;
        for (int64_t i = s; i < e; i++) {
            uint64_t h = flat[i];
            if (h == SENT) continue;
            int64_t p = off[h >> shift1]++;
            qh[p] = h;
            qw[p] = (int32_t)w;
        }
    }
    if (shift1 == 0) {
        /* every hash of a bucket is equal: stable as it is */
        free(off);
        free(sub);
        return 0;
    }

    /* off[b] is now bucket b's end. Each bucket is copied to a
     * cache-sized scratch and scattered back by its next digit; the
     * scratch is then free for the sub-buckets' merge sorts. */
    uint64_t *th = (uint64_t *)malloc((size_t)big * sizeof(uint64_t));
    int32_t *tw = (int32_t *)malloc((size_t)big * sizeof(int32_t));
    if (!th || !tw) {
        free(th);
        free(tw);
        free(off);
        free(sub);
        return -1;
    }
    int64_t start = 0;
    for (int64_t b = 0; b < nb1; b++) {
        int64_t end = off[b], m = end - start;
        uint64_t *bh = qh + start;
        int32_t *bw = qw + start;
        if (m <= SQ_SMALL) {
            sq_isort(bh, bw, m);
            start = end;
            continue;
        }
        int b2 = sq_floor_log2(m);
        if (b2 > SQ_DIGIT_BITS) b2 = SQ_DIGIT_BITS;
        if (b2 > shift1) b2 = shift1;
        int shift2 = shift1 - b2;
        int64_t nb2 = (int64_t)1 << b2;
        uint64_t mask2 = (uint64_t)nb2 - 1;
        memcpy(th, bh, (size_t)m * sizeof(uint64_t));
        memcpy(tw, bw, (size_t)m * sizeof(int32_t));
        memset(sub, 0, (size_t)(nb2 + 1) * sizeof(int64_t));
        for (int64_t i = 0; i < m; i++)
            sub[((th[i] >> shift2) & mask2) + 1]++;
        for (int64_t d = 0; d < nb2; d++) sub[d + 1] += sub[d];
        for (int64_t i = 0; i < m; i++) {
            int64_t p = sub[(th[i] >> shift2) & mask2]++;
            bh[p] = th[i];
            bw[p] = tw[i];
        }
        /* sub[d] is now sub-bucket d's end */
        if (shift2) {
            int64_t s2 = 0;
            for (int64_t d = 0; d < nb2; d++) {
                int64_t e2 = sub[d];
                if (e2 - s2 > 1)
                    sq_msort(bh + s2, bw + s2, th + s2, tw + s2, e2 - s2);
                s2 = e2;
            }
        }
        start = end;
    }
    free(th);
    free(tw);
    free(off);
    free(sub);
    return 0;
}
