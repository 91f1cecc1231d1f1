// Native host kernels for hyperspace_tpu.
//
// The TPU compute path is JAX/XLA; these kernels cover the HOST side of
// the build/serve pipeline (the dispatch policy in ops/sort.py keeps
// host-resident batches off the device because host<->device transfer
// dwarfed the compute when it was measured). The hot host op is the stable multi-plane lexsort
// behind the bucketed sorted write (reference: the sort-within-bucket of
// index/DataFrameWriterExtensions.scala:58-67); numpy's lexsort runs one
// full stable argsort per plane with an index gather each time, while
// this kernel runs one adaptive LSD radix sort over all planes and skips
// byte passes whose digits are constant across rows — on real index
// workloads most passes are (bucket ids span a few bits, the hi word of
// a small int64 key is the constant sign bit).
//
// Contract: identical output to np.lexsort(planes[::-1]) — stable,
// ascending, plane 0 major. Ties keep input order; counting sort is
// stable by construction and planes are processed least-significant
// first, so the composition is stable overall.
//
// Threading: pass n_threads > 1 to split histogram+scatter by contiguous
// input chunks (per-chunk digit offsets keep stability). The caller
// picks n_threads from the machine; 1 means plain loops with no thread
// machinery at all.

#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

struct Buffers {
  std::vector<int64_t> perm_a, perm_b;
  std::vector<uint32_t> key_a, key_b;
};

// One stable counting-sort pass by byte `shift` of key_a, moving
// (key, perm) pairs into (key_b, perm_b). Single-threaded.
void pass_serial(Buffers& buf, int64_t n, int shift) {
  int64_t count[256] = {0};
  const uint32_t* ka = buf.key_a.data();
  for (int64_t i = 0; i < n; ++i) ++count[(ka[i] >> shift) & 0xFF];
  int64_t offset[256];
  int64_t running = 0;
  for (int d = 0; d < 256; ++d) {
    offset[d] = running;
    running += count[d];
  }
  const int64_t* pa = buf.perm_a.data();
  uint32_t* kb = buf.key_b.data();
  int64_t* pb = buf.perm_b.data();
  for (int64_t i = 0; i < n; ++i) {
    int64_t pos = offset[(ka[i] >> shift) & 0xFF]++;
    kb[pos] = ka[i];
    pb[pos] = pa[i];
  }
}

// Run fn(0..T-1), fn(0) on the calling thread. If a spawn fails
// (std::system_error from pthread_create under a pids cgroup limit),
// already-spawned threads are joined BEFORE the exception propagates —
// destroying a joinable std::thread calls std::terminate, which would
// abort the process instead of reaching the extern "C" catch(...) that
// turns resource exhaustion into rc=2 / numpy fallback.
template <typename F>
void run_on_threads(int T, F&& fn) {
  std::vector<std::thread> ts;
  ts.reserve(T > 1 ? T - 1 : 0);
  try {
    for (int t = 1; t < T; ++t) ts.emplace_back(fn, t);
  } catch (...) {
    for (auto& th : ts) th.join();
    throw;
  }
  fn(0);
  for (auto& th : ts) th.join();
}

// Threaded variant: per-chunk histograms, then global offsets laid out
// digit-major chunk-minor so each chunk scatters into disjoint, stably
// ordered slots.
void pass_threaded(Buffers& buf, int64_t n, int shift, int n_threads) {
  const int T = n_threads;
  std::vector<int64_t> counts(static_cast<size_t>(T) * 256, 0);
  const uint32_t* ka = buf.key_a.data();
  const int64_t chunk = (n + T - 1) / T;
  auto hist = [&](int t) {
    int64_t lo = t * chunk, hi = std::min<int64_t>(n, lo + chunk);
    int64_t* c = counts.data() + static_cast<size_t>(t) * 256;
    for (int64_t i = lo; i < hi; ++i) ++c[(ka[i] >> shift) & 0xFF];
  };
  run_on_threads(T, hist);
  // offsets[t][d]: digit-major, chunk-minor prefix sum
  std::vector<int64_t> offsets(static_cast<size_t>(T) * 256);
  int64_t running = 0;
  for (int d = 0; d < 256; ++d) {
    for (int t = 0; t < T; ++t) {
      offsets[static_cast<size_t>(t) * 256 + d] = running;
      running += counts[static_cast<size_t>(t) * 256 + d];
    }
  }
  const int64_t* pa = buf.perm_a.data();
  uint32_t* kb = buf.key_b.data();
  int64_t* pb = buf.perm_b.data();
  auto scatter = [&](int t) {
    int64_t lo = t * chunk, hi = std::min<int64_t>(n, lo + chunk);
    int64_t* off = offsets.data() + static_cast<size_t>(t) * 256;
    for (int64_t i = lo; i < hi; ++i) {
      int64_t pos = off[(ka[i] >> shift) & 0xFF]++;
      kb[pos] = ka[i];
      pb[pos] = pa[i];
    }
  };
  run_on_threads(T, scatter);
}

// Shared range-term predicate: one conjunct of the serve-path residual
// mask (ops/filter.py lower_range_terms + native_range_bounds) — an
// int64 or float64 column, optional lo/hi bounds with strictness, an
// optional validity byte mask. Used by hs_range_mask,
// hs_fused_filter_select and hs_fused_filter_agg so the three kernels
// evaluate EXACTLY the same predicate semantics (IEEE float compares:
// NaN fails every bound, same as the numpy twin).
struct RangeTerms {
  const void** cols;
  const uint8_t** valids;  // may be nullptr / entries may be nullptr
  const uint8_t* is_f64;
  const int64_t* lo_i;
  const int64_t* hi_i;
  const double* lo_f;
  const double* hi_f;
  const uint8_t* has_lo;
  const uint8_t* has_hi;
  const uint8_t* lo_strict;
  const uint8_t* hi_strict;
  int32_t k;
};

inline bool terms_pass(const RangeTerms& t, int64_t r) {
  for (int32_t i = 0; i < t.k; ++i) {
    if (t.valids != nullptr && t.valids[i] != nullptr && !t.valids[i][r])
      return false;
    if (t.is_f64[i]) {
      const double v = static_cast<const double*>(t.cols[i])[r];
      if (t.has_lo[i] && !(t.lo_strict[i] ? v > t.lo_f[i] : v >= t.lo_f[i]))
        return false;
      if (t.has_hi[i] && !(t.hi_strict[i] ? v < t.hi_f[i] : v <= t.hi_f[i]))
        return false;
    } else {
      const int64_t v = static_cast<const int64_t*>(t.cols[i])[r];
      if (t.has_lo[i] && !(t.lo_strict[i] ? v > t.lo_i[i] : v >= t.lo_i[i]))
        return false;
      if (t.has_hi[i] && !(t.hi_strict[i] ? v < t.hi_i[i] : v <= t.hi_i[i]))
        return false;
    }
  }
  return true;
}

// splitmix64 finalizer: the fused-aggregate group hash. Quality matters
// only for probe-length distribution; identity never depends on it (full
// rep/null equality is compared on every probe hit).
inline uint64_t mix64(uint64_t x) {
  x ^= x >> 30;
  x *= 0xBF58476D1CE4E5B9ull;
  x ^= x >> 27;
  x *= 0x94D049BB133111EBull;
  x ^= x >> 31;
  return x;
}

// Rows [lo, hi) of hs_order_u64, one loop a dtype and validity case.
template <bool F64, bool VALID>
void order_u64_rows(const uint64_t* src, const uint8_t* valid, uint64_t* out,
                    int64_t lo, int64_t hi) {
  const uint64_t sign = uint64_t{1} << 63;
  for (int64_t i = lo; i < hi; ++i) {
    const uint64_t b = src[i];
    uint64_t e = F64 ? ((b >> 63) ? ~b : (b | sign)) : (b ^ sign);
    if (VALID) e = valid[i] ? (e ? e : 1) : 0;
    out[i] = e;
  }
}

}  // namespace

extern "C" {

// Stable ascending lexsort of n rows by k uint32 planes; planes[0] is
// the MAJOR key. Writes the permutation into out (int64, length n).
// Returns 0 on success, 1 on bad arguments, 2 on resource exhaustion
// (std::bad_alloc / thread spawn failure — the Python wrapper falls back
// to numpy, whose MemoryError is catchable, instead of std::terminate
// aborting the process at the extern "C" boundary).
int hs_lexsort_u32(const uint32_t** planes, int32_t k, int64_t n,
                   int64_t* out, int32_t n_threads) {
  if (n < 0 || k < 0 || (n > 0 && out == nullptr)) return 1;
  for (int64_t i = 0; i < n; ++i) out[i] = i;
  if (n <= 1 || k == 0) return 0;
  if (n_threads < 1) n_threads = 1;

  try {
    Buffers buf;
    buf.perm_a.resize(n);
    buf.perm_b.resize(n);
    buf.key_a.resize(n);
    buf.key_b.resize(n);
    std::memcpy(buf.perm_a.data(), out, static_cast<size_t>(n) * 8);

    for (int p = k - 1; p >= 0; --p) {
      const uint32_t* plane = planes[p];
      // Byte-activity mask: a byte position where every row agrees cannot
      // change the order — skip its pass. Order-independent, so it runs on
      // the raw plane BEFORE paying the random gather; a constant plane
      // (e.g. the hi word of small int64 keys) costs one sequential scan.
      uint32_t mask = 0;
      const uint32_t v0 = plane[0];
      for (int64_t i = 1; i < n; ++i) mask |= plane[i] ^ v0;
      if (mask == 0) continue;
      // Gather the plane into the current permutation order (sequential
      // writes; the random reads are the unavoidable cost of composing
      // with the earlier planes' order).
      const int64_t* pa = buf.perm_a.data();
      uint32_t* ka = buf.key_a.data();
      for (int64_t i = 0; i < n; ++i) ka[i] = plane[pa[i]];
      for (int shift = 0; shift < 32; shift += 8) {
        if (((mask >> shift) & 0xFF) == 0) continue;
        if (n_threads > 1) {
          pass_threaded(buf, n, shift, n_threads);
        } else {
          pass_serial(buf, n, shift);
        }
        buf.perm_a.swap(buf.perm_b);
        buf.key_a.swap(buf.key_b);
      }
    }
    std::memcpy(out, buf.perm_a.data(), static_cast<size_t>(n) * 8);
  } catch (...) {
    return 2;
  }
  return 0;
}

// Stable counting scatter: partition n row indices by their int32 bucket
// id. out_order receives the indices grouped bucket-major (ascending
// bucket id), original order preserved within each bucket; out_offsets
// (length num_buckets + 1) receives the run boundaries, so bucket b's
// rows are out_order[out_offsets[b] .. out_offsets[b+1]).
//
// This is the partition-first half of the covering-index build: instead
// of one global lexsort by (bucket, keys) whose permutation gathers walk
// the whole working set, the build histograms bucket ids (sequential
// read), scatters row indices into contiguous per-bucket runs
// (sequential writes per bucket cursor), then sorts each bucket
// independently with a working set of ~total/num_buckets.
//
// Returns 0 on success, 1 on bad arguments (including any bucket id
// outside [0, num_buckets)), 2 on resource exhaustion.
int hs_partition_by_bucket(const int32_t* bucket_ids, int64_t n,
                           int32_t num_buckets, int64_t* out_order,
                           int64_t* out_offsets, int32_t n_threads) {
  if (n < 0 || num_buckets <= 0 || out_offsets == nullptr ||
      (n > 0 && (bucket_ids == nullptr || out_order == nullptr)))
    return 1;
  if (n_threads < 1) n_threads = 1;
  const int T = n_threads;
  try {
    // Per-chunk histograms (also validates ids: one branchy pass is
    // cheaper than scattering through a poisoned offset table).
    std::vector<int64_t> counts(static_cast<size_t>(T) * num_buckets, 0);
    const int64_t chunk = T > 1 ? (n + T - 1) / T : n;
    std::vector<uint8_t> bad(T, 0);
    auto hist = [&](int t) {
      int64_t lo = t * chunk, hi = std::min<int64_t>(n, lo + chunk);
      int64_t* c = counts.data() + static_cast<size_t>(t) * num_buckets;
      for (int64_t i = lo; i < hi; ++i) {
        const int32_t b = bucket_ids[i];
        if (b < 0 || b >= num_buckets) {
          bad[t] = 1;
          return;
        }
        ++c[b];
      }
    };
    run_on_threads(T, hist);
    for (int t = 0; t < T; ++t)
      if (bad[t]) return 1;
    // Bucket-major chunk-minor offsets: chunk t's slots for bucket b
    // follow chunk t-1's, so the scatter is stable across chunks.
    std::vector<int64_t> offsets(static_cast<size_t>(T) * num_buckets);
    int64_t running = 0;
    for (int32_t b = 0; b < num_buckets; ++b) {
      out_offsets[b] = running;
      for (int t = 0; t < T; ++t) {
        offsets[static_cast<size_t>(t) * num_buckets + b] = running;
        running += counts[static_cast<size_t>(t) * num_buckets + b];
      }
    }
    out_offsets[num_buckets] = running;
    auto scatter = [&](int t) {
      int64_t lo = t * chunk, hi = std::min<int64_t>(n, lo + chunk);
      int64_t* off = offsets.data() + static_cast<size_t>(t) * num_buckets;
      for (int64_t i = lo; i < hi; ++i) out_order[off[bucket_ids[i]]++] = i;
    };
    run_on_threads(T, scatter);
  } catch (...) {
    return 2;
  }
  return 0;
}

// Inner-join pair count of two ASCENDING-sorted int64 key arrays
// (duplicates allowed on both sides): one linear merge, no allocation.
// This is the serve-side payoff of the co-bucketed covering index — both
// bucket slices come off disk key-sorted (reference: the no-shuffle SMJ
// of covering/JoinIndexRule.scala:619-634), so matching is O(n+m+pairs)
// sequential instead of n binary searches into m.
int64_t hs_merge_join_count_i64(const int64_t* l, int64_t n,
                                const int64_t* r, int64_t m) {
  int64_t total = 0;
  int64_t i = 0, j = 0;
  while (i < n && j < m) {
    if (l[i] < r[j]) {
      ++i;
    } else if (l[i] > r[j]) {
      ++j;
    } else {
      const int64_t v = l[i];
      int64_t i2 = i, j2 = j;
      while (i2 < n && l[i2] == v) ++i2;
      while (j2 < m && r[j2] == v) ++j2;
      total += (i2 - i) * (j2 - j);
      i = i2;
      j = j2;
    }
  }
  return total;
}

// Emit the matching pairs of two ASCENDING-sorted int64 key arrays into
// li/ri (capacity = hs_merge_join_count_i64's result), with l_bias/r_bias
// added to every emitted index. Order: left index ascending, right index
// ascending within each left row — identical to the numpy
// searchsorted+repeat expansion it replaces. The biases let a per-bucket
// caller emit GLOBAL row ids straight into one preallocated output,
// skipping the per-bucket offset-add and concatenate passes entirely.
int64_t hs_merge_join_emit_i64(const int64_t* l, int64_t n,
                               const int64_t* r, int64_t m, int64_t l_bias,
                               int64_t r_bias, int64_t* li, int64_t* ri) {
  int64_t out = 0;
  int64_t i = 0, j = 0;
  while (i < n && j < m) {
    if (l[i] < r[j]) {
      ++i;
    } else if (l[i] > r[j]) {
      ++j;
    } else {
      const int64_t v = l[i];
      int64_t j2 = j;
      while (j2 < m && r[j2] == v) ++j2;
      for (; i < n && l[i] == v; ++i) {
        for (int64_t jj = j; jj < j2; ++jj) {
          li[out] = i + l_bias;
          ri[out] = jj + r_bias;
          ++out;
        }
      }
      j = j2;
    }
  }
  return out;
}

// Expand per-left-row match ranges into explicit (li, ri) pairs — the
// serve-side half of the merge join that the numpy path spends ~6 full
// array passes on (repeat + cumsum + arange + repeat + gather; the
// "repeat/cumsum chain" of execution/join_exec.py). One pass here: for
// left row i with cnt[i] matches starting at sorted-right position
// lo[i], emit cnt[i] pairs. Optional l_map/r_map (nullptr = identity)
// compose the argsort/rowmap indirections the callers otherwise apply
// as separate gather passes: li = l_map[i] + l_bias, ri =
// r_map[lo[i]+j] + r_bias. Pair order: left row ascending, right
// position ascending within each left row — identical to the numpy
// expansion (ops/join.expand_match_ranges_numpy, the registered twin).
//
// Threading: rows are chunked by a serial prefix sum of cnt, so each
// thread writes a disjoint contiguous output slice. `capacity` is the
// caller's li/ri allocation (= cnt's sum, which the Python wrapper
// already computed): it is validated BEFORE any write, so a
// miscomputed caller total can never overrun the buffers — the same
// defensive posture as the gathers' bounds check. Map lengths are
// validated too (l_map positionally: l_map_len >= n; r_map per element,
// since lo+cnt ranges are data-dependent); a violation returns -1 and
// the Python fallback surfaces numpy's own IndexError. Returns the
// emitted pair count, -1 on bad arguments, -2 on resource exhaustion.
int64_t hs_expand_match_ranges_i64(const int64_t* lo, const int64_t* cnt,
                                   int64_t n, const int64_t* l_map,
                                   int64_t l_map_len, const int64_t* r_map,
                                   int64_t r_map_len, int64_t l_bias,
                                   int64_t r_bias, int64_t* li, int64_t* ri,
                                   int64_t capacity, int32_t n_threads) {
  if (n < 0 || (n > 0 && (lo == nullptr || cnt == nullptr))) return -1;
  if (l_map != nullptr && l_map_len < n) return -1;
  if (n == 0) return capacity == 0 ? 0 : -1;
  if (n_threads < 1) n_threads = 1;
  try {
    // Serial prefix sum: out_off[i] = pairs emitted before row i.
    std::vector<int64_t> out_off(static_cast<size_t>(n) + 1);
    int64_t running = 0;
    for (int64_t i = 0; i < n; ++i) {
      if (cnt[i] < 0) return -1;
      out_off[i] = running;
      running += cnt[i];
    }
    out_off[n] = running;
    const int64_t total = running;
    if (total != capacity) return -1;
    if (total > 0 && (li == nullptr || ri == nullptr)) return -1;
    const int T =
        total < (1 << 16) ? 1 : std::min<int64_t>(n_threads, n);
    const int64_t chunk = (n + T - 1) / T;
    std::vector<uint8_t> bad(T, 0);
    auto expand = [&](int t) {
      int64_t lo_row = t * chunk;
      if (lo_row >= n) return;  // ceil-chunking can overshoot for tiny n
      int64_t hi_row = std::min<int64_t>(n, lo_row + chunk);
      int64_t out = out_off[lo_row];
      for (int64_t i = lo_row; i < hi_row; ++i) {
        const int64_t l = (l_map ? l_map[i] : i) + l_bias;
        const int64_t base = lo[i];
        if (r_map != nullptr &&
            cnt[i] > 0 && (base < 0 || base + cnt[i] > r_map_len)) {
          bad[t] = 1;
          return;
        }
        for (int64_t j = 0; j < cnt[i]; ++j) {
          li[out] = l;
          ri[out] = (r_map ? r_map[base + j] : base + j) + r_bias;
          ++out;
        }
      }
    };
    run_on_threads(T, expand);
    for (int t = 0; t < T; ++t)
      if (bad[t]) return -1;
    return total;
  } catch (...) {
    return -2;
  }
}

// Bounds-checked threaded gathers: out[i] = src[idx[i]]. numpy's fancy
// indexing is single-threaded and the serve join's assemble stage is a
// string of multi-million-row gathers (one per output column), so the
// random-access latency is worth spreading over cores. Any idx outside
// [0, n_src) returns 1 (the Python wrapper falls back to numpy, which
// preserves numpy's negative-index and IndexError semantics exactly).
// Returns 0 on success, 2 on resource exhaustion.
static int gather64(const uint64_t* src, int64_t n_src, const int64_t* idx,
                    int64_t n_idx, uint64_t* out, int32_t n_threads) {
  if (n_src < 0 || n_idx < 0 ||
      (n_idx > 0 && (src == nullptr || idx == nullptr || out == nullptr)))
    return 1;
  if (n_idx == 0) return 0;
  if (n_threads < 1) n_threads = 1;
  const int T = static_cast<int>(std::min<int64_t>(n_threads, n_idx));
  try {
    const int64_t chunk = (n_idx + T - 1) / T;
    std::vector<uint8_t> bad(T, 0);
    auto work = [&](int t) {
      int64_t lo = t * chunk, hi = std::min<int64_t>(n_idx, lo + chunk);
      for (int64_t i = lo; i < hi; ++i) {
        const int64_t j = idx[i];
        if (j < 0 || j >= n_src) {
          bad[t] = 1;
          return;
        }
        out[i] = src[j];
      }
    };
    run_on_threads(T, work);
    for (int t = 0; t < T; ++t)
      if (bad[t]) return 1;
  } catch (...) {
    return 2;
  }
  return 0;
}

int hs_gather_i64(const int64_t* src, int64_t n_src, const int64_t* idx,
                  int64_t n_idx, int64_t* out, int32_t n_threads) {
  return gather64(reinterpret_cast<const uint64_t*>(src), n_src, idx, n_idx,
                  reinterpret_cast<uint64_t*>(out), n_threads);
}

int hs_gather_f64(const double* src, int64_t n_src, const int64_t* idx,
                  int64_t n_idx, double* out, int32_t n_threads) {
  // same 8-byte move as the int64 gather; a distinct export keeps the
  // ctypes signatures honest (and the parity registry explicit per type)
  return gather64(reinterpret_cast<const uint64_t*>(src), n_src, idx, n_idx,
                  reinterpret_cast<uint64_t*>(out), n_threads);
}

// Fused range mask: out[r] = 1 iff row r passes EVERY term's bound
// checks and validity. A term is one numeric range/Eq conjunct of the
// serve-path residual predicate (ops/filter.py lower_range_terms): an
// int64 or float64 column, optional lo/hi bounds with strictness, and
// an optional validity byte mask. The numpy twin
// (ops/filter.range_mask_numpy) makes ~2 full-array passes per term
// plus the AND passes; this is one pass over the rows total, threaded
// by contiguous row chunks. Float compares are IEEE (NaN fails every
// bound — identical to the engine's mask semantics). Returns 0 on
// success, 1 on bad arguments, 2 on resource exhaustion.
int hs_range_mask(const void** cols, const uint8_t** valids,
                  const uint8_t* is_f64, const int64_t* lo_i,
                  const int64_t* hi_i, const double* lo_f,
                  const double* hi_f, const uint8_t* has_lo,
                  const uint8_t* has_hi, const uint8_t* lo_strict,
                  const uint8_t* hi_strict, int32_t k, int64_t n,
                  uint8_t* out, int32_t n_threads) {
  if (n < 0 || k <= 0 || (n > 0 && (cols == nullptr || out == nullptr)))
    return 1;
  for (int32_t t = 0; t < k; ++t)
    if (cols[t] == nullptr) return 1;
  if (n == 0) return 0;
  if (n_threads < 1) n_threads = 1;
  const int T = static_cast<int>(
      std::min<int64_t>(n < (1 << 16) ? 1 : n_threads, n));
  const RangeTerms terms{cols,   valids, is_f64,    lo_i,      hi_i,
                         lo_f,   hi_f,   has_lo,    has_hi,    lo_strict,
                         hi_strict, k};
  try {
    const int64_t chunk = (n + T - 1) / T;
    auto work = [&](int th) {
      int64_t lo = th * chunk, hi = std::min<int64_t>(n, lo + chunk);
      for (int64_t r = lo; r < hi; ++r) out[r] = terms_pass(terms, r) ? 1 : 0;
    };
    run_on_threads(T, work);
  } catch (...) {
    return 2;
  }
  return 0;
}

// Fused filter-select: the passing ROW INDICES of the range-term
// conjunction, ascending, written into out_idx (capacity n). The first
// half of the Filter→Project lowering (docs/serve-compiler.md): one
// pass computing pass/fail AND compacting indices replaces the
// interpreted chain's materialized bool mask + np.nonzero; the caller
// gathers the projected columns through the indices (the existing
// threaded hs_gather kernels). Threaded two-phase (per-chunk count,
// then disjoint fills), so the output order is deterministic and equal
// to np.nonzero(mask). Returns the index count, -1 on bad arguments,
// -2 on resource exhaustion.
int64_t hs_fused_filter_select(const void** cols, const uint8_t** valids,
                               const uint8_t* is_f64, const int64_t* lo_i,
                               const int64_t* hi_i, const double* lo_f,
                               const double* hi_f, const uint8_t* has_lo,
                               const uint8_t* has_hi,
                               const uint8_t* lo_strict,
                               const uint8_t* hi_strict, int32_t k,
                               int64_t n, int64_t* out_idx,
                               int32_t n_threads) {
  if (n < 0 || k <= 0 || (n > 0 && (cols == nullptr || out_idx == nullptr)))
    return -1;
  for (int32_t t = 0; t < k; ++t)
    if (cols[t] == nullptr) return -1;
  if (n == 0) return 0;
  if (n_threads < 1) n_threads = 1;
  const int T = static_cast<int>(
      std::min<int64_t>(n < (1 << 16) ? 1 : n_threads, n));
  const RangeTerms terms{cols,   valids, is_f64,    lo_i,      hi_i,
                         lo_f,   hi_f,   has_lo,    has_hi,    lo_strict,
                         hi_strict, k};
  try {
    const int64_t chunk = (n + T - 1) / T;
    std::vector<int64_t> counts(T, 0);
    auto count = [&](int t) {
      int64_t lo = t * chunk, hi = std::min<int64_t>(n, lo + chunk);
      int64_t c = 0;
      for (int64_t r = lo; r < hi; ++r) c += terms_pass(terms, r) ? 1 : 0;
      counts[t] = c;
    };
    run_on_threads(T, count);
    std::vector<int64_t> offsets(T);
    int64_t total = 0;
    for (int t = 0; t < T; ++t) {
      offsets[t] = total;
      total += counts[t];
    }
    auto fill = [&](int t) {
      int64_t lo = t * chunk, hi = std::min<int64_t>(n, lo + chunk);
      int64_t out = offsets[t];
      for (int64_t r = lo; r < hi; ++r)
        if (terms_pass(terms, r)) out_idx[out++] = r;
    };
    run_on_threads(T, fill);
    return total;
  } catch (...) {
    return -2;
  }
}

// Fused filter-aggregate: the serve-pipeline compiler's inner pass
// (docs/serve-compiler.md). For every row passing the range-term
// conjunction, compute the group slot from the key columns' canonical
// int64 reps (NULL/NaN/-0.0 canonicalization identical to
// io/columnar.Column.key_rep) and fold the row into per-group partial
// aggregates — COUNT(*)/COUNT(col)/SUM/MIN/MAX over int64-view and
// float64 columns — without materializing the mask, the filtered batch,
// or any per-row intermediate. The Python driver streams row-group
// chunks through this kernel in file order with the SAME state arrays,
// so accumulation order equals the interpreted chain's row order
// (np.add.at / np.minimum.at are sequential; float sums are therefore
// bit-identical, and deliberately single-threaded here).
//
// State contract (all owned/allocated by the caller):
//   ht[ht_size]      open-addressing table (power of two, -1 = empty),
//                    always strictly larger than g_cap so a probe always
//                    finds an empty slot;
//   g_hash/g_reps/g_nulls[n_keys*g_cap]/g_kvals/g_kvalid  per-group key
//                    identity (hash, canonical rep, null flag) plus the
//                    FIRST-OCCURRENCE raw key value + validity (what the
//                    interpreted chain's batch.take(first) gathers);
//   acc_i/acc_f/acc_cnt/acc_aux[n_aggs*g_cap]  accumulators, caller-
//                    initialized per op (sum: 0, min: +sentinel, max:
//                    -sentinel; cnt/aux: 0);
//   rebuild != 0     re-insert the existing groups into a FRESH (all -1)
//                    ht from g_hash before processing — how the caller
//                    grows capacity without re-hashing in Python.
//
// Agg ops: 0 COUNT(*)  1 COUNT(col)  2 SUM i64  3 SUM f64
//          4 MIN i64   5 MAX i64     6 MIN f64  7 MAX f64
// Accumulation replicates the numpy twins exactly: int sums wrap mod
// 2^64 (accumulated as uint64), float sums add 0.0 for passing-but-null
// rows (np.add.at over zero-filled values), min/max use numpy's
// replace-on-equal rule (acc = acc<v ? acc : v), float min/max track
// has-clean / has-NaN flags for the Spark NaN ordering applied at
// finalize time.
//
// Returns the number of rows CONSUMED starting at row_start (< n - row_start
// when the group table fills mid-chunk: the caller grows the state and
// re-calls at the returned offset; no row is ever half-applied), or -1 on
// bad arguments.
int64_t hs_fused_filter_agg(
    const void** f_cols, const uint8_t** f_valids, const uint8_t* f_is_f64,
    const int64_t* f_lo_i, const int64_t* f_hi_i, const double* f_lo_f,
    const double* f_hi_f, const uint8_t* f_has_lo, const uint8_t* f_has_hi,
    const uint8_t* f_lo_strict, const uint8_t* f_hi_strict, int32_t n_terms,
    const void** k_cols, const uint8_t** k_valids, const uint8_t* k_is_f64,
    int32_t n_keys, const void** a_cols, const uint8_t** a_valids,
    const uint8_t* a_ops, int32_t n_aggs, int64_t n, int64_t row_start,
    int64_t* ht, int64_t ht_size, int64_t* g_hash, int64_t* g_reps,
    uint8_t* g_nulls, int64_t* g_kvals, uint8_t* g_kvalid, int64_t* acc_i,
    double* acc_f, int64_t* acc_cnt, int64_t* acc_aux, int64_t g_cap,
    int64_t* n_groups_io, int64_t* rows_passed_io, int32_t rebuild) {
  if (n < 0 || row_start < 0 || row_start > n || n_terms < 0 ||
      n_keys < 0 || n_keys > 16 || n_aggs < 0 || g_cap <= 0 ||
      n_groups_io == nullptr || rows_passed_io == nullptr)
    return -1;
  if (n_terms > 0 && f_cols == nullptr) return -1;
  if (n_keys > 0 &&
      (k_cols == nullptr || ht == nullptr || ht_size <= g_cap ||
       (ht_size & (ht_size - 1)) != 0 || g_hash == nullptr ||
       g_reps == nullptr || g_nulls == nullptr || g_kvals == nullptr ||
       g_kvalid == nullptr))
    return -1;
  if (n_aggs > 0 &&
      (a_cols == nullptr || a_ops == nullptr || acc_i == nullptr ||
       acc_f == nullptr || acc_cnt == nullptr || acc_aux == nullptr))
    return -1;
  int64_t n_groups = *n_groups_io;
  if (n_groups < 0 || n_groups > g_cap) return -1;
  if (n_keys == 0 && n_groups != 1) return -1;  // driver pre-seeds slot 0
  for (int32_t a = 0; a < n_aggs; ++a) {
    if (a_ops[a] > 7) return -1;
    // ops 2..7 read the column; COUNT(*) / COUNT(col) only count
    if (a_ops[a] >= 2 && a_cols[a] == nullptr) return -1;
  }
  const RangeTerms terms{f_cols,   f_valids, f_is_f64,    f_lo_i,
                         f_hi_i,   f_lo_f,   f_hi_f,      f_has_lo,
                         f_has_hi, f_lo_strict, f_hi_strict, n_terms};
  const int64_t NULL_REP = -0x7FFFFFFFFFFFFF13LL;  // columnar.NULL_KEY_REP
  const uint64_t hmask = n_keys > 0 ? static_cast<uint64_t>(ht_size) - 1 : 0;
  if (rebuild && n_keys > 0) {
    for (int64_t g = 0; g < n_groups; ++g) {
      uint64_t s = static_cast<uint64_t>(g_hash[g]) & hmask;
      while (ht[s] >= 0) s = (s + 1) & hmask;
      ht[s] = g;
    }
  }
  int64_t rep[16];
  uint8_t nul[16];
  int64_t passed = 0;
  for (int64_t r = row_start; r < n; ++r) {
    if (!terms_pass(terms, r)) continue;
    int64_t g = 0;
    if (n_keys > 0) {
      uint64_t h = 0x9E3779B97F4A7C15ull;
      for (int32_t j = 0; j < n_keys; ++j) {
        const bool valid =
            k_valids == nullptr || k_valids[j] == nullptr || k_valids[j][r];
        if (!valid) {
          rep[j] = NULL_REP;
          nul[j] = 1;
        } else {
          nul[j] = 0;
          if (k_is_f64[j]) {
            const double v = static_cast<const double*>(k_cols[j])[r];
            if (v != v) {
              rep[j] = 0x7FF8000000000000LL;  // canonical NaN (key_rep)
            } else if (v == 0.0) {
              rep[j] = 0;  // -0.0 and 0.0 group together (key_rep)
            } else {
              std::memcpy(&rep[j], &v, 8);
            }
          } else {
            rep[j] = static_cast<const int64_t*>(k_cols[j])[r];
          }
        }
        h = mix64(h ^ static_cast<uint64_t>(rep[j]));
        h = mix64(h ^ nul[j]);
      }
      uint64_t s = h & hmask;
      while (true) {
        const int64_t cand = ht[s];
        if (cand < 0) {
          if (n_groups >= g_cap) {
            // table full: stop BEFORE touching row r; the caller grows
            // the state and re-calls at this offset
            *n_groups_io = n_groups;
            *rows_passed_io += passed;
            return r - row_start;
          }
          g = n_groups++;
          ht[s] = g;
          g_hash[g] = static_cast<int64_t>(h);
          for (int32_t j = 0; j < n_keys; ++j) {
            g_reps[static_cast<size_t>(j) * g_cap + g] = rep[j];
            g_nulls[static_cast<size_t>(j) * g_cap + g] = nul[j];
            int64_t raw;
            std::memcpy(&raw,
                        static_cast<const char*>(k_cols[j]) +
                            static_cast<size_t>(r) * 8,
                        8);
            g_kvals[static_cast<size_t>(j) * g_cap + g] = raw;
            g_kvalid[static_cast<size_t>(j) * g_cap + g] = nul[j] ? 0 : 1;
          }
          break;
        }
        if (g_hash[cand] == static_cast<int64_t>(h)) {
          bool eq = true;
          for (int32_t j = 0; j < n_keys; ++j) {
            if (g_reps[static_cast<size_t>(j) * g_cap + cand] != rep[j] ||
                g_nulls[static_cast<size_t>(j) * g_cap + cand] != nul[j]) {
              eq = false;
              break;
            }
          }
          if (eq) {
            g = cand;
            break;
          }
        }
        s = (s + 1) & hmask;
      }
    }
    ++passed;
    for (int32_t a = 0; a < n_aggs; ++a) {
      const size_t slot = static_cast<size_t>(a) * g_cap + g;
      const bool av =
          a_valids == nullptr || a_valids[a] == nullptr || a_valids[a][r];
      switch (a_ops[a]) {
        case 0:  // COUNT(*)
          ++acc_cnt[slot];
          break;
        case 1:  // COUNT(col)
          acc_cnt[slot] += av ? 1 : 0;
          break;
        case 2: {  // SUM i64 (wraps mod 2^64, same as numpy int64 adds)
          const int64_t v =
              av ? static_cast<const int64_t*>(a_cols[a])[r] : 0;
          acc_i[slot] = static_cast<int64_t>(
              static_cast<uint64_t>(acc_i[slot]) + static_cast<uint64_t>(v));
          acc_cnt[slot] += av ? 1 : 0;
          break;
        }
        case 3: {  // SUM f64 (+0.0 for null rows, like np.add.at)
          const double v =
              av ? static_cast<const double*>(a_cols[a])[r] : 0.0;
          acc_f[slot] += v;
          acc_cnt[slot] += av ? 1 : 0;
          break;
        }
        case 4:  // MIN i64
          if (av) {
            const int64_t v = static_cast<const int64_t*>(a_cols[a])[r];
            ++acc_cnt[slot];
            acc_i[slot] = acc_i[slot] < v ? acc_i[slot] : v;
          }
          break;
        case 5:  // MAX i64
          if (av) {
            const int64_t v = static_cast<const int64_t*>(a_cols[a])[r];
            ++acc_cnt[slot];
            acc_i[slot] = acc_i[slot] > v ? acc_i[slot] : v;
          }
          break;
        case 6:  // MIN f64 (np.minimum replace-on-equal; NaN excluded,
                 // aux counts clean rows for the Spark NaN rule)
          if (av) {
            const double v = static_cast<const double*>(a_cols[a])[r];
            ++acc_cnt[slot];
            if (!(v != v)) {
              ++acc_aux[slot];
              acc_f[slot] = acc_f[slot] < v ? acc_f[slot] : v;
            }
          }
          break;
        case 7:  // MAX f64 (any valid NaN wins at finalize; aux counts NaNs)
          if (av) {
            const double v = static_cast<const double*>(a_cols[a])[r];
            ++acc_cnt[slot];
            if (v != v) {
              ++acc_aux[slot];
            } else {
              acc_f[slot] = acc_f[slot] > v ? acc_f[slot] : v;
            }
          }
          break;
        default:  // unreachable: ops validated before the row loop
          break;
      }
    }
  }
  *n_groups_io = n_groups;
  *rows_passed_io += passed;
  return n - row_start;
}

// MurmurHash3-32 bucket ids over k int64 key columns, one pass per row.
// Bit-exact twin of ops/hash.bucket_ids_host (numpy) and the XLA kernel:
// each key rep contributes its lo then hi uint32 word to the block
// stream, fmix length is 8*k bytes, bucket = h % num_buckets. The numpy
// twin makes ~10 full-array passes over the mix pipeline; this is one.
static inline uint32_t rotl32(uint32_t x, int r) {
  return (x << r) | (x >> (32 - r));
}

static inline uint32_t mm3_mix(uint32_t h, uint32_t w) {
  uint32_t k1 = w * 0xCC9E2D51u;
  k1 = rotl32(k1, 15);
  k1 *= 0x1B873593u;
  h ^= k1;
  h = rotl32(h, 13);
  return h * 5u + 0xE6546B64u;
}

int hs_bucket_ids_i64(const int64_t** keys, int32_t k, int64_t n,
                      uint32_t seed, uint32_t num_buckets, int32_t* out) {
  if (n < 0 || k <= 0 || num_buckets == 0) return 1;
  const uint32_t len = 8u * static_cast<uint32_t>(k);
  for (int64_t i = 0; i < n; ++i) {
    uint32_t h = seed;
    for (int32_t j = 0; j < k; ++j) {
      const uint64_t v = static_cast<uint64_t>(keys[j][i]);
      h = mm3_mix(h, static_cast<uint32_t>(v));
      h = mm3_mix(h, static_cast<uint32_t>(v >> 32));
    }
    h ^= len;
    h ^= h >> 16;
    h *= 0x85EBCA6Bu;
    h ^= h >> 13;
    h *= 0xC2B2AE35u;
    h ^= h >> 16;
    out[i] = static_cast<int32_t>(h % num_buckets);
  }
  return 0;
}

// The int64 -> uint32 word split of ONE contiguous key column, one pass:
// lo_out[i] = low word of src[i], hi_out[i] = high word ^ hi_xor for
// i < n, and both rows' slots [n, row_len) = pad. The two layouts the
// build needs are the same pass: the hash's word block (hi_xor 0, lo row
// before hi row, zero tail up to the padded length the device program is
// compiled for; twin ops/hash.split_words_np + its np.concatenate) and
// the sort's order words (hi_xor 0x80000000 flips the sign bit so that
// unsigned plane order is signed int64 order, hi row first, no tail;
// twin ops/sort._order_words_numpy). The numpy twins make five or six
// full-array passes with a fresh temporary each on one thread; this
// reads 8 B and writes 8 B a row, threaded by contiguous row chunks so
// that the freshly mapped output pages are first touched in parallel.
// Returns 0 on success, 1 on bad arguments, 2 on resource exhaustion.
int hs_split_words_i64(const int64_t* src, int64_t n, uint32_t* lo_out,
                       uint32_t* hi_out, int64_t row_len, uint32_t hi_xor,
                       uint32_t pad, int32_t n_threads) {
  if (n < 0 || row_len < n || (n > 0 && src == nullptr) ||
      (row_len > 0 && (lo_out == nullptr || hi_out == nullptr)))
    return 1;
  if (row_len == 0) return 0;
  if (n_threads < 1) n_threads = 1;
  const int T = static_cast<int>(
      std::min<int64_t>(row_len < (1 << 16) ? 1 : n_threads, row_len));
  try {
    // whole cache lines of both outputs a chunk: no line has two writers
    const int64_t chunk = ((row_len + T - 1) / T + 15) & ~int64_t{15};
    auto work = [&](int t) {
      const int64_t lo = std::min<int64_t>(row_len, t * chunk);
      const int64_t hi = std::min<int64_t>(row_len, lo + chunk);
      const int64_t mid = std::max(lo, std::min(hi, n));
      for (int64_t i = lo; i < mid; ++i) {
        const uint64_t v = static_cast<uint64_t>(src[i]);
        lo_out[i] = static_cast<uint32_t>(v);
        hi_out[i] = static_cast<uint32_t>(v >> 32) ^ hi_xor;
      }
      for (int64_t i = mid; i < hi; ++i) {
        lo_out[i] = pad;
        hi_out[i] = pad;
      }
    };
    run_on_threads(T, work);
  } catch (...) {
    return 2;
  }
  return 0;
}

// The order-preserving uint64 of ONE contiguous 8-byte numeric column
// (the z-order build's per-column encoding), one pass: float64 bits in
// IEEE total order (sign bit set -> all bits flipped, else the sign bit
// set), int64 with its sign bit flipped (dates and timestamps arrive as
// int64), and where `valid` is given, max(enc, 1) on a valid row and 0
// on a null. Bit-exact twin of ops/zorder.order_u64_numpy, which makes
// two or three whole-array passes with a temporary each on one thread;
// this reads 8 B and writes 8 B a row, threaded by contiguous row
// chunks so that the fresh output's pages are first touched in
// parallel. Returns 0 on success, 1 on bad arguments, 2 on resource
// exhaustion.
int hs_order_u64(const void* src, int64_t n, int32_t is_f64,
                 const uint8_t* valid, uint64_t* out, int32_t n_threads) {
  if (n < 0 || (n > 0 && (src == nullptr || out == nullptr))) return 1;
  if (n == 0) return 0;
  if (n_threads < 1) n_threads = 1;
  const int T = static_cast<int>(
      std::min<int64_t>(n < (1 << 16) ? 1 : n_threads, n));
  const uint64_t* s = static_cast<const uint64_t*>(src);
  try {
    // whole cache lines of the output a chunk: no line has two writers
    const int64_t chunk = ((n + T - 1) / T + 7) & ~int64_t{7};
    auto work = [&](int t) {
      const int64_t lo = std::min<int64_t>(n, t * chunk);
      const int64_t hi = std::min<int64_t>(n, lo + chunk);
      if (is_f64) {
        if (valid) order_u64_rows<true, true>(s, valid, out, lo, hi);
        else order_u64_rows<true, false>(s, valid, out, lo, hi);
      } else {
        if (valid) order_u64_rows<false, true>(s, valid, out, lo, hi);
        else order_u64_rows<false, false>(s, valid, out, lo, hi);
      }
    };
    run_on_threads(T, work);
  } catch (...) {
    return 2;
  }
  return 0;
}

// The z-word of ONE column under a range spec, one pass, written into
// one row of the block the interleave uploads: out[i] =
// trunc(clip(double(enc[i] - mn) * scale, 0, top)) for i < n (the
// subtraction wraps as numpy's uint64 does), and slots [n, row_len) =
// top, the block's fill. `scale` is computed by the caller, once, the
// twin's way; here the same IEEE operations in the same order (the
// uint64 -> double conversion, one multiply, the clip, truncation) and
// no flag that reassociates or contracts them. Bit-exact twin of
// ops/zorder.range_words_np plus the tail its caller fills, which make
// five whole-array passes with four temporaries on one thread. Returns
// 0 on success, 1 on bad arguments, 2 on resource exhaustion.
int hs_zorder_words(const uint64_t* enc, int64_t n, uint64_t mn,
                    double scale, uint32_t top, uint32_t* out,
                    int64_t row_len, int32_t n_threads) {
  if (n < 0 || row_len < n || (n > 0 && enc == nullptr) ||
      (row_len > 0 && out == nullptr))
    return 1;
  if (row_len == 0) return 0;
  if (n_threads < 1) n_threads = 1;
  const int T = static_cast<int>(
      std::min<int64_t>(row_len < (1 << 16) ? 1 : n_threads, row_len));
  const double top_f = static_cast<double>(top);
  try {
    // whole cache lines of the output a chunk: no line has two writers
    const int64_t chunk = ((row_len + T - 1) / T + 15) & ~int64_t{15};
    auto work = [&](int t) {
      const int64_t lo = std::min<int64_t>(row_len, t * chunk);
      const int64_t hi = std::min<int64_t>(row_len, lo + chunk);
      const int64_t mid = std::max(lo, std::min(hi, n));
      for (int64_t i = lo; i < mid; ++i) {
        double x = static_cast<double>(enc[i] - mn) * scale;
        x = x < 0.0 ? 0.0 : x;
        x = x > top_f ? top_f : x;
        out[i] = static_cast<uint32_t>(x);
      }
      for (int64_t i = mid; i < hi; ++i) out[i] = top;
    };
    run_on_threads(T, work);
  } catch (...) {
    return 2;
  }
  return 0;
}

}  // extern "C"
