// The float32 distances of a compressed dispatch's candidates, in one pass
// over the rows the host keeps (weaviate_tpu/index/rescore_native.py;
// index/tpu.py _rescore_f32).
//
// For each (query, candidate slot) the slot's row is read from `vecs` ONCE
// and scored against the query in registers; the call writes `[b, r]`
// float32 and nothing else. The numpy path it replaces gathered the rows
// into a [b, r, dim] buffer (31.5 MB a dispatch of 256 x 40 x 768) and read
// that again for the contraction.
//
// Arithmetic: ops/topk.rescore_distances', as index/tpu.py _host_distances
// serves it. cosine 1 - sum(row * q) (rows and queries arrive normalized),
// dot -sum(row * q), l2-squared sum((row - q)^2), manhattan sum(|row - q|).
//
// SUMMATION ORDER (fixed, so the same inputs give the same bits on every
// call and at every thread count): a pair's sum is kept in LANES = 32
// partial sums; element j of the row is added to partial j % 32, in
// ascending j (whole blocks of 32, then the tail of dim % 32 elements into
// partials 0 ..); the partials are then folded 32 -> 16 -> 8 -> 4 -> 2 -> 1,
// partial l taking partial l + w at each width w. One pair is one thread's
// work from its first element to its last, so how the pairs are split over
// threads cannot move a bit. (The compiler may fuse a multiply and an add
// into one rounding where the target has FMA: the bits belong to a build,
// not to a call.)
//
// Threads: the b * r pairs are independent. A call that reads enough bytes
// splits them into contiguous ranges over a few threads of its own (see
// rescore_threads), started for the call and joined before it returns; the
// caller's thread scores the first range. No pool, no state between calls.

#include <sched.h>

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <limits>
#include <system_error>
#include <thread>
#include <vector>

namespace {

constexpr int LANES = 32;
// rows a thread asks the memory system for ahead of the one it scores: the
// candidates of a query are random rows of a slab far larger than any
// cache, so without it every row starts with a miss the core waits out
constexpr int64_t AHEAD = 3;
// a thread of its own has to be worth its start: bytes of rows a thread
// should have before the call takes another
constexpr int64_t BYTES_A_THREAD = 4 << 20;
constexpr int MAX_THREADS = 4;

enum Metric { COSINE = 0, DOT = 1, L2 = 2, MANHATTAN = 3 };

template <int M>
inline float term(float a, float b) {
    if (M == L2) {
        float t = a - b;
        return t * t;
    }
    if (M == MANHATTAN) return std::fabs(a - b);
    return a * b;
}

template <int M>
inline float score(const float* __restrict row, const float* __restrict q,
                   int64_t dim) {
    float acc[LANES];
    for (int l = 0; l < LANES; ++l) acc[l] = 0.0f;
    int64_t j = 0;
    for (; j + LANES <= dim; j += LANES)
        for (int l = 0; l < LANES; ++l) acc[l] += term<M>(row[j + l], q[j + l]);
    for (int l = 0; j + l < dim; ++l) acc[l] += term<M>(row[j + l], q[j + l]);
    for (int w = LANES / 2; w >= 1; w >>= 1)
        for (int l = 0; l < w; ++l) acc[l] += acc[l + w];
    if (M == COSINE) return 1.0f - acc[0];
    if (M == DOT) return -acc[0];
    return acc[0];
}

inline void prefetch_row(const float* row, int64_t dim) {
    const char* p = reinterpret_cast<const char*>(row);
    const char* end = p + dim * 4;
    for (; p < end; p += 64) __builtin_prefetch(p, 0, 3);
}

struct Call {
    const float* vecs;
    int64_t capacity, dim;
    const int32_t* slots;
    const float* q;
    int64_t r;
    float* out;
};

inline const float* row_of(const Call& c, int64_t pair) {
    int32_t s = c.slots[pair];
    return (s < 0 || s >= c.capacity) ? nullptr : c.vecs + int64_t(s) * c.dim;
}

template <int M>
void score_range(const Call& c, int64_t lo, int64_t hi) {
    const float inf = std::numeric_limits<float>::infinity();
    for (int64_t p = lo; p < hi; ++p) {
        if (p + AHEAD < hi)
            if (const float* row = row_of(c, p + AHEAD)) prefetch_row(row, c.dim);
        const float* row = row_of(c, p);
        c.out[p] = row ? score<M>(row, c.q + (p / c.r) * c.dim, c.dim) : inf;
    }
}

void run_range(const Call& c, int metric, int64_t lo, int64_t hi) {
    switch (metric) {
        case COSINE: score_range<COSINE>(c, lo, hi); break;
        case DOT: score_range<DOT>(c, lo, hi); break;
        case L2: score_range<L2>(c, lo, hi); break;
        default: score_range<MANHATTAN>(c, lo, hi); break;
    }
}

int cores_allowed() {
    cpu_set_t set;
    if (sched_getaffinity(0, sizeof(set), &set) == 0) {
        int n = CPU_COUNT(&set);
        if (n > 0) return n;
    }
    unsigned n = std::thread::hardware_concurrency();
    return n ? int(n) : 1;
}

}  // namespace

extern "C" {

// Threads a call over `pairs` rows of `dim` float32 takes when the caller
// leaves the choice to it: one for each BYTES_A_THREAD of rows it reads, at
// most MAX_THREADS, and at most a quarter of the cores the process may use
// (a server answers several dispatches at once, each on its caller's
// thread: four such calls must not ask for more cores than there are).
int rescore_threads(int64_t pairs, int64_t dim) {
    int64_t by_bytes = pairs * dim * 4 / BYTES_A_THREAD;
    int by_cores = cores_allowed() / 4;
    int64_t t = std::min<int64_t>({by_bytes, by_cores, MAX_THREADS});
    return int(std::max<int64_t>(t, 1));
}

// vecs [capacity, dim] f32 C-contiguous; slots [b, r] i32 (a slot outside
// [0, capacity): no row, +inf); q [b, dim] f32; out [b, r] f32. metric: 0
// cosine, 1 dot, 2 l2-squared, 3 manhattan. threads: 0 = rescore_threads'
// choice (what the index passes); a test may state one.
// -> the threads that ran it, or -1 on an argument no call can serve.
int rescore_f32(const float* vecs, int64_t capacity, int64_t dim,
                const int32_t* slots, const float* q, int64_t b, int64_t r,
                int metric, float* out, int threads) {
    if (!vecs || !slots || !q || !out || capacity < 0 || dim < 1 || b < 0 ||
        r < 0 || metric < COSINE || metric > MANHATTAN)
        return -1;
    int64_t pairs = b * r;
    if (pairs == 0) return 0;
    Call c{vecs, capacity, dim, slots, q, r, out};
    int64_t t = threads > 0 ? threads : rescore_threads(pairs, dim);
    t = std::min<int64_t>(t, pairs);
    if (t <= 1) {
        run_range(c, metric, 0, pairs);
        return 1;
    }
    std::vector<std::thread> others;
    others.reserve(t - 1);
    int64_t step = (pairs + t - 1) / t;
    for (int64_t i = 1; i < t; ++i) {
        int64_t lo = std::min(i * step, pairs), hi = std::min(lo + step, pairs);
        if (lo >= hi) continue;
        try {
            others.emplace_back(run_range, std::cref(c), metric, lo, hi);
        } catch (const std::system_error&) {
            run_range(c, metric, lo, hi);  // no thread to be had: this one
        }
    }
    run_range(c, metric, 0, std::min(step, pairs));
    for (auto& th : others) th.join();
    return int(others.size()) + 1;
}

}  // extern "C"
