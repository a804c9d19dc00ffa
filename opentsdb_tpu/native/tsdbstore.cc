// Native host column store for opentsdb_tpu.
//
// The storage-engine role the reference delegates to HBase region
// servers + the asynchbase client (SURVEY.md L0): append-optimized
// per-series column buffers with lazy sort/dedupe and a parallel
// range-materialize that fills flat (series_idx, ts, value) arrays
// ready for device upload. The Python MemoryBackend is the portable
// twin; this engine removes the per-series Python loop from the
// query path (ref analogue: SaltScanner's 20-way parallel scan,
// src/core/SaltScanner.java:70 — here a thread pool over series).
//
// C ABI (ctypes-friendly), no exceptions across the boundary.
// Build: g++ -O3 -march=native -shared -fPIC -std=c++17 -pthread
//        tsdbstore.cc -o libtsdbstore.so

#include <algorithm>
#include <atomic>
#include <charconv>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <limits>
#include <mutex>
#include <shared_mutex>
#include <string>
#include <system_error>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

struct SeriesBuffer {
  std::vector<int64_t> ts;
  std::vector<double> vals;
  std::vector<uint8_t> is_int;
  bool sorted = true;
  std::mutex mu;

  void append(int64_t t, double v, uint8_t ii) {
    std::lock_guard<std::mutex> lock(mu);
    if (sorted && !ts.empty() && t <= ts.back()) sorted = false;
    ts.push_back(t);
    vals.push_back(v);
    is_int.push_back(ii);
  }

  void append_many(int64_t n, const int64_t* t, const double* v,
                   const uint8_t* ii) {
    std::lock_guard<std::mutex> lock(mu);
    for (int64_t i = 0; i < n; ++i) {
      if (sorted && !ts.empty() && t[i] <= ts.back()) sorted = false;
      ts.push_back(t[i]);
      vals.push_back(v[i]);
      is_int.push_back(ii ? ii[i] : 0);
    }
  }

  // Sort by timestamp, last-write-wins dedupe (matches the Python
  // SeriesBuffer and the reference's fix_duplicates semantics).
  void ensure_sorted_locked() {
    if (sorted) return;
    const size_t n = ts.size();
    std::vector<uint32_t> order(n);
    for (size_t i = 0; i < n; ++i) order[i] = (uint32_t)i;
    std::stable_sort(order.begin(), order.end(),
                     [&](uint32_t a, uint32_t b) { return ts[a] < ts[b]; });
    std::vector<int64_t> nts;
    std::vector<double> nvals;
    std::vector<uint8_t> nint;
    nts.reserve(n);
    nvals.reserve(n);
    nint.reserve(n);
    for (size_t i = 0; i < n; ++i) {
      uint32_t idx = order[i];
      if (!nts.empty() && nts.back() == ts[idx]) {
        nvals.back() = vals[idx];  // last write wins
        nint.back() = is_int[idx];
      } else {
        nts.push_back(ts[idx]);
        nvals.push_back(vals[idx]);
        nint.push_back(is_int[idx]);
      }
    }
    ts.swap(nts);
    vals.swap(nvals);
    is_int.swap(nint);
    sorted = true;
  }

  // [lo, hi] inclusive range bounds after sorting.
  void range_bounds(int64_t start_ms, int64_t end_ms, int64_t* lo,
                    int64_t* hi) {
    std::lock_guard<std::mutex> lock(mu);
    ensure_sorted_locked();
    *lo = std::lower_bound(ts.begin(), ts.end(), start_ms) - ts.begin();
    *hi = std::upper_bound(ts.begin(), ts.end(), end_ms) - ts.begin();
  }
};

struct Store {
  // The directory vector REALLOCATES on growth, so every indexing
  // access holds the shared lock; the SeriesBuffer objects themselves
  // are heap-stable for the store's lifetime, so captured pointers
  // stay valid after the lock drops (each buffer has its own mutex).
  std::vector<SeriesBuffer*> series;
  std::shared_mutex dir_mu;
  std::atomic<int64_t> points_written{0};

  // Where in time the recent appends landed (tss_oldest_written_since;
  // the Python twin is core/store.py WrittenLog, rule for rule): one
  // entry an append call, {points_written after it, the oldest
  // timestamp it wrote}, pushed in the critical section that bumps the
  // counter. A reader asks for a suffix minimum, so a push first pops
  // the entries whose timestamp is not older than its own, and the log
  // ascends in both fields; beyond kWrittenLogMax the oldest entry is
  // folded into written_floor, below which the answer is "everything".
  struct Written { int64_t version, oldest; };
  static constexpr size_t kWrittenLogMax = 4096;
  std::mutex written_mu;
  std::deque<Written> written_log;
  int64_t written_floor = 0;

  // n points are in their buffers, the oldest at oldest_ts: count them
  // and log where they landed, under one lock, AFTER they became
  // readable. points_written moves nowhere else.
  void note_written(int64_t n, int64_t oldest_ts) {
    if (n <= 0) return;
    std::lock_guard<std::mutex> lock(written_mu);
    while (!written_log.empty() && written_log.back().oldest >= oldest_ts)
      written_log.pop_back();
    const int64_t v = points_written.load(std::memory_order_relaxed) + n;
    written_log.push_back({v, oldest_ts});
    if (written_log.size() > kWrittenLogMax) {
      written_floor = written_log.front().version;
      written_log.pop_front();
    }
    points_written.store(v);
  }

  // nullptr on a bad sid.
  SeriesBuffer* lookup(int64_t sid) {
    std::shared_lock<std::shared_mutex> lock(dir_mu);
    if (sid < 0 || sid >= (int64_t)series.size()) return nullptr;
    return series[sid];
  }

  // Validate + capture all pointers under ONE shared lock (the
  // threaded bulk paths). Returns false on any bad sid.
  bool snapshot(const int64_t* sids, int64_t n,
                std::vector<SeriesBuffer*>* out) {
    std::shared_lock<std::shared_mutex> lock(dir_mu);
    out->resize(n);
    for (int64_t i = 0; i < n; ++i) {
      if (sids[i] < 0 || sids[i] >= (int64_t)series.size())
        return false;
      (*out)[i] = series[sids[i]];
    }
    return true;
  }

  ~Store() {
    for (auto* s : series) delete s;
  }
};

// ---- the process's worker pool --------------------------------------
//
// Every parallel pass of this file is one parallel_for: the caller and
// up to cap - 1 helpers claim chunks of `grain` items until none is
// left. The helpers are parked threads of ONE pool for the process
// (several stores live in it: raw, rollup tiers, pre-aggregates),
// min(16, cores) - 1 of them, created by the first pass that wants
// one, and joined when the last store is destroyed. No pass creates a
// thread of its own: a panel's grid of 8 rows spent 3 ms creating and
// joining 12 threads that found nothing to do.
//
// Under concurrent callers (a live request's two sub-queries, writers
// beside them) the caller always works itself, so a pass never waits
// for a helper that has not started; helpers take the oldest pass with
// a seat left; the pass returns only after every helper that entered
// its body has left it (the body lives on the caller's stack).
class WorkerPool {
 public:
  std::atomic<int64_t> inline_passes{0}, pooled_passes{0};
  std::atomic<int64_t> threads_created{0};
  std::atomic<int64_t> stores{0};

  // fn(ctx) on the caller and on up to `helpers` pool threads.
  void run(int helpers, void (*fn)(void*), void* ctx) {
    Pass pass;
    pass.fn = fn;
    pass.ctx = ctx;
    if (helpers > 0) {
      std::lock_guard<std::mutex> lock(mu_);
      try {  // the first pass that wants a helper makes them all
        while (!stop_ && (int)threads_.size() < max_threads_) {
          threads_.emplace_back([this] { park(); });
          threads_created.fetch_add(1, std::memory_order_relaxed);
        }
      } catch (const std::system_error&) {
        // no thread to be had: the pass runs on what there is
      }
      helpers = pass.seats = std::min(helpers, (int)threads_.size());
      if (helpers > 0) open_.push_back(&pass);
    }
    if (helpers <= 0) {
      inline_passes.fetch_add(1, std::memory_order_relaxed);
      fn(ctx);
      return;
    }
    pooled_passes.fetch_add(1, std::memory_order_relaxed);
    for (int i = 0; i < helpers; ++i) wake_.notify_one();
    fn(ctx);
    std::unique_lock<std::mutex> lock(mu_);
    if (pass.seats > 0)  // nothing is left to claim: admit no one else
      open_.erase(std::find(open_.begin(), open_.end(), &pass));
    pass.left.wait(lock, [&] { return pass.inside == 0; });
  }

  // Join every parked thread (tss_destroy of the last store). A pass in
  // flight keeps its caller; the next pass that wants helpers makes them.
  void shutdown() {
    std::lock_guard<std::mutex> one(shutdown_mu_);
    std::vector<std::thread> gone;
    {
      std::lock_guard<std::mutex> lock(mu_);
      stop_ = true;
      gone.swap(threads_);
    }
    wake_.notify_all();
    for (auto& th : gone) th.join();
    std::lock_guard<std::mutex> lock(mu_);
    stop_ = false;
  }

 private:
  struct Pass {
    void (*fn)(void*);
    void* ctx;
    int seats = 0;   // helpers still admitted
    int inside = 0;  // helpers that entered fn and have not left
    std::condition_variable left;
  };

  void park() {
    std::unique_lock<std::mutex> lock(mu_);
    for (;;) {
      wake_.wait(lock, [&] { return stop_ || !open_.empty(); });
      if (stop_) return;
      Pass* pass = open_.front();
      if (--pass->seats == 0) open_.erase(open_.begin());
      ++pass->inside;
      lock.unlock();
      pass->fn(pass->ctx);
      lock.lock();
      if (--pass->inside == 0) pass->left.notify_one();
    }
  }

  std::mutex mu_, shutdown_mu_;
  std::condition_variable wake_;
  std::vector<Pass*> open_;  // passes with a seat left, oldest first
  std::vector<std::thread> threads_;
  bool stop_ = false;
  const int max_threads_ =
      (int)std::min(16u, std::max(1u, std::thread::hardware_concurrency())) - 1;
};

// Never destroyed: a thread of the interpreter may still be in a pass
// while the process exits.
WorkerPool& pool() {
  static WorkerPool* p = new WorkerPool();
  return *p;
}

// body(begin, end) over [0, items) in chunks of `grain`, on
// min(cap, ceil(items / grain)) participants, the caller included: a
// pass of one chunk touches no other thread.
template <typename Body>
void parallel_for(int cap, int64_t items, int64_t grain, Body&& body) {
  std::atomic<int64_t> next{0};
  auto work = [&]() {
    for (;;) {
      int64_t begin = next.fetch_add(grain, std::memory_order_relaxed);
      if (begin >= items) return;
      body(begin, std::min(begin + grain, items));
    }
  };
  const int64_t chunks = (items + grain - 1) / grain;
  pool().run((int)std::min<int64_t>(cap, chunks) - 1,
             [](void* w) { (*static_cast<decltype(work)*>(w))(); }, &work);
}

// Grains: what one participant must find to be worth its wake. On the
// benchmark's host (gVisor, 13 cores; PR 39's probe, PERF.md section 6)
// a caller pays 36 us to wake one helper and 13 us for each further one,
// and a helper starts claiming ~0.3 ms after its wake: two participants
// lose to one up to ~0.4 ms of work. So a grain is >= 0.25 ms at the
// item's cost there: a grid row of 360 points 1.2 us, a bucket_reduce
// row 0.85-1.05 us (a bucket_columns row of two cut buckets about a
// quarter of that: PERF.md section 6, PR 50), a series of count_range
// 0.04 us, a copied point
// ~1 ns; an appended cell 40 ns and a MB of import text 6.5 ms, both
// with more room because their participants fight over fresh memory
// (vectors that grow, a group table a chunk that the merge walks alone).
constexpr int64_t kGridRows = 256;         // bucket_grid: rows of the grid
constexpr int64_t kColumnRows = 1024;      // bucket_columns: rows
constexpr int64_t kColumnsAhead = 8;  // series whose buffer it prefetches
constexpr int64_t kReduceRows = 256;       // bucket_reduce: series
constexpr int64_t kCountSeries = 8192;     // count_range: series
constexpr int64_t kFillPoints = 1 << 18;   // fill_range: points copied
constexpr int64_t kAppendCells = 1 << 15;  // append_grid: cells of the grid
constexpr int64_t kImportBytes = 1 << 18;  // parse_import: text

// The grain in items where an item's cost is known in smaller units.
inline int64_t items_per(int64_t units, int64_t units_per_item) {
  return std::max<int64_t>(1, units / std::max<int64_t>(1, units_per_item));
}

}  // namespace

extern "C" {

void* tss_create() {
  pool().stores.fetch_add(1);
  return new Store();
}

void tss_destroy(void* h) {
  delete static_cast<Store*>(h);
  if (h && pool().stores.fetch_sub(1) == 1) pool().shutdown();
}

// out[0..2]: passes that ran on their caller alone, passes that woke
// helpers, threads the pool has created since the process began (it
// stops growing after start-up: no request creates a thread).
void tss_pool_stats(int64_t* out) {
  out[0] = pool().inline_passes.load(std::memory_order_relaxed);
  out[1] = pool().pooled_passes.load(std::memory_order_relaxed);
  out[2] = pool().threads_created.load(std::memory_order_relaxed);
}

// Returns the new series id. Series identity (metric+tags -> sid) is
// managed by the Python wrapper; this just allocates the buffer.
int64_t tss_add_series(void* h) {
  Store* s = static_cast<Store*>(h);
  std::unique_lock<std::shared_mutex> lock(s->dir_mu);
  s->series.push_back(new SeriesBuffer());
  return (int64_t)s->series.size() - 1;
}

// Bulk allocation: n new contiguous series ids, one lock take.
// Returns the first new id.
int64_t tss_add_series_n(void* h, int64_t n) {
  Store* s = static_cast<Store*>(h);
  std::unique_lock<std::shared_mutex> lock(s->dir_mu);
  int64_t first = (int64_t)s->series.size();
  s->series.reserve(s->series.size() + (size_t)n);
  for (int64_t i = 0; i < n; ++i) s->series.push_back(new SeriesBuffer());
  return first;
}

int64_t tss_series_count(void* h) {
  Store* s = static_cast<Store*>(h);
  std::shared_lock<std::shared_mutex> lock(s->dir_mu);
  return (int64_t)s->series.size();
}

int tss_append(void* h, int64_t sid, int64_t ts_ms, double value,
               int is_int) {
  Store* s = static_cast<Store*>(h);
  SeriesBuffer* buf = s->lookup(sid);
  if (!buf) return -1;
  buf->append(ts_ms, value, (uint8_t)is_int);
  s->note_written(1, ts_ms);
  return 0;
}

int tss_append_many(void* h, int64_t sid, int64_t n, const int64_t* ts,
                    const double* vals, const uint8_t* is_int) {
  Store* s = static_cast<Store*>(h);
  SeriesBuffer* buf = s->lookup(sid);
  if (!buf) return -1;
  buf->append_many(n, ts, vals, is_int);
  if (n > 0) s->note_written(n, *std::min_element(ts, ts + n));
  return 0;
}

int64_t tss_points_written(void* h) {
  return static_cast<Store*>(h)->points_written.load();
}

// The smallest timestamp any append has written since points_written
// read `version`: INT64_MAX if nothing was written, INT64_MIN where
// the log no longer reaches back that far ("everything").
int64_t tss_oldest_written_since(void* h, int64_t version) {
  Store* s = static_cast<Store*>(h);
  std::lock_guard<std::mutex> lock(s->written_mu);
  if (version < s->written_floor)
    return std::numeric_limits<int64_t>::min();
  // ascending in both fields: the first entry past `version` is the
  // oldest of all that follow it
  auto it = std::upper_bound(
      s->written_log.begin(), s->written_log.end(), version,
      [](int64_t v, const Store::Written& w) { return v < w.version; });
  return it == s->written_log.end()
             ? std::numeric_limits<int64_t>::max() : it->oldest;
}

// out[0..1]: the entries of the write log and its floor version.
void tss_written_log_stats(void* h, int64_t* out) {
  Store* s = static_cast<Store*>(h);
  std::lock_guard<std::mutex> lock(s->written_mu);
  out[0] = (int64_t)s->written_log.size();
  out[1] = s->written_floor;
}

// fsck in-place repair (ref: Fsck.java:99-119 repairing bad values /
// timestamps in storage): drop points whose timestamp falls outside
// [min_ts, max_ts], and — when drop_nonfinite — points whose value is
// NaN/Inf. Returns the number of points removed, or -1 on a bad sid.
int64_t tss_repair_series(void* h, int64_t sid, int64_t min_ts,
                          int64_t max_ts, int drop_nonfinite) {
  Store* s = static_cast<Store*>(h);
  SeriesBuffer* buf = s->lookup(sid);
  if (!buf) return -1;
  std::lock_guard<std::mutex> lock(buf->mu);
  buf->ensure_sorted_locked();
  const size_t n = buf->ts.size();
  size_t w = 0;
  for (size_t i = 0; i < n; ++i) {
    bool ok = buf->ts[i] >= min_ts && buf->ts[i] <= max_ts;
    if (ok && drop_nonfinite && !std::isfinite(buf->vals[i])) ok = false;
    if (ok) {
      if (w != i) {
        buf->ts[w] = buf->ts[i];
        buf->vals[w] = buf->vals[i];
        buf->is_int[w] = buf->is_int[i];
      }
      ++w;
    }
  }
  buf->ts.resize(w);
  buf->vals.resize(w);
  buf->is_int.resize(w);
  return (int64_t)(n - w);
}

// fsck in-place repair: overwrite the value stored at an exact
// timestamp. Returns 0 on success, -1 on a bad sid, -2 when no point
// has that timestamp.
int tss_patch_value(void* h, int64_t sid, int64_t ts_ms, double value,
                    int is_int) {
  Store* s = static_cast<Store*>(h);
  SeriesBuffer* buf = s->lookup(sid);
  if (!buf) return -1;
  std::lock_guard<std::mutex> lock(buf->mu);
  buf->ensure_sorted_locked();
  auto it = std::lower_bound(buf->ts.begin(), buf->ts.end(), ts_ms);
  if (it == buf->ts.end() || *it != ts_ms) return -2;
  size_t i = it - buf->ts.begin();
  buf->vals[i] = value;
  buf->is_int[i] = (uint8_t)is_int;
  return 0;
}

// Bulk grid write (the rollup job's output path): for every row i,
// append the mask-selected cells of grid[i, :] (shared bucket_ts
// columns) onto series sids[i]. Threaded over rows; one lock take per
// row instead of per cell. Returns the number of points written, or
// -1 on any invalid sid.
int64_t tss_append_grid(void* h, const int64_t* sids, int64_t nsids,
                        const int64_t* bucket_ts, int64_t nbuckets,
                        const double* grid, const uint8_t* mask,
                        int threads) {
  Store* s = static_cast<Store*>(h);
  std::vector<SeriesBuffer*> bufs;
  if (!s->snapshot(sids, nsids, &bufs)) return -1;
  std::atomic<int64_t> total{0};
  // the oldest timestamp any row wrote (bucket_ts need not ascend)
  std::atomic<int64_t> oldest{std::numeric_limits<int64_t>::max()};
  parallel_for(threads, nsids, items_per(kAppendCells, nbuckets),
               [&](int64_t r0, int64_t r1) {
    int64_t local = 0;
    int64_t first = std::numeric_limits<int64_t>::max();
    for (int64_t i = r0; i < r1; ++i) {
      SeriesBuffer* buf = bufs[i];
      const double* row = grid + i * nbuckets;
      const uint8_t* m = mask + i * nbuckets;
      std::lock_guard<std::mutex> lock(buf->mu);
      for (int64_t b = 0; b < nbuckets; ++b) {
        if (!m[b]) continue;
        if (buf->sorted && !buf->ts.empty() &&
            bucket_ts[b] <= buf->ts.back())
          buf->sorted = false;
        buf->ts.push_back(bucket_ts[b]);
        buf->vals.push_back(row[b]);
        buf->is_int.push_back(0);
        first = std::min(first, bucket_ts[b]);
        ++local;
      }
    }
    total.fetch_add(local);
    int64_t seen = oldest.load();
    while (first < seen && !oldest.compare_exchange_weak(seen, first)) {}
  });
  s->note_written(total.load(), oldest.load());
  return total.load();
}

int64_t tss_series_length(void* h, int64_t sid) {
  Store* s = static_cast<Store*>(h);
  SeriesBuffer* buf = s->lookup(sid);
  if (!buf) return -1;
  std::lock_guard<std::mutex> lock(buf->mu);
  buf->ensure_sorted_locked();
  return (int64_t)buf->ts.size();
}

// Remove points with start_ms <= ts <= end_ms from one series; returns
// the number deleted (ref: TsdbQuery delete=true issuing
// DeleteRequests per scanned row). -1 on a bad sid.
int64_t tss_delete_range(void* h, int64_t sid, int64_t start_ms,
                         int64_t end_ms) {
  Store* s = static_cast<Store*>(h);
  SeriesBuffer* buf = s->lookup(sid);
  if (!buf) return -1;
  std::lock_guard<std::mutex> lock(buf->mu);
  buf->ensure_sorted_locked();
  auto lo = std::lower_bound(buf->ts.begin(), buf->ts.end(), start_ms);
  auto hi = std::upper_bound(buf->ts.begin(), buf->ts.end(), end_ms);
  int64_t n = hi - lo;
  if (n > 0) {
    buf->vals.erase(buf->vals.begin() + (lo - buf->ts.begin()),
                    buf->vals.begin() + (hi - buf->ts.begin()));
    buf->is_int.erase(buf->is_int.begin() + (lo - buf->ts.begin()),
                      buf->is_int.begin() + (hi - buf->ts.begin()));
    buf->ts.erase(lo, hi);
  }
  return n;
}

// Copy one series' sorted columns into caller-provided arrays of
// capacity `cap` (from a prior tss_series_length call). Returns the
// number of elements actually copied — concurrent appends between the
// two calls can grow the buffer past cap (copy truncates) and
// concurrent deletes/dedupes can shrink it (caller trims to the
// return value); never writes past cap. -1 on a bad sid.
int64_t tss_read_series(void* h, int64_t sid, int64_t cap,
                        int64_t* ts_out, double* vals_out,
                        uint8_t* int_out) {
  Store* s = static_cast<Store*>(h);
  SeriesBuffer* buf = s->lookup(sid);
  if (!buf) return -1;
  std::lock_guard<std::mutex> lock(buf->mu);
  buf->ensure_sorted_locked();
  int64_t n = (int64_t)buf->ts.size();
  if (n > cap) n = cap;
  if (n > 0) {
    std::memcpy(ts_out, buf->ts.data(), n * sizeof(int64_t));
    std::memcpy(vals_out, buf->vals.data(), n * sizeof(double));
    if (int_out) std::memcpy(int_out, buf->is_int.data(), n);
  }
  return n;
}

// Phase 1 of materialize: per-series point counts within
// [start_ms, end_ms] (inclusive). Parallel over a thread pool — the
// reference's per-salt-bucket scanner fan-out.
int tss_count_range(void* h, const int64_t* sids, int64_t nsids,
                    int64_t start_ms, int64_t end_ms,
                    int64_t* counts_out, int threads) {
  Store* s = static_cast<Store*>(h);
  std::vector<SeriesBuffer*> bufs;
  if (!s->snapshot(sids, nsids, &bufs)) return -1;
  parallel_for(threads, nsids, kCountSeries, [&](int64_t r0, int64_t r1) {
    for (int64_t i = r0; i < r1; ++i) {
      int64_t lo, hi;
      bufs[i]->range_bounds(start_ms, end_ms, &lo, &hi);
      counts_out[i] = hi - lo;
    }
  });
  return 0;
}

// Phase 2: fill flat output arrays. offsets[i] must hold the exclusive
// prefix sum of the phase-1 counts and counts[i] the phase-1 count
// itself: the copy is capped at counts[i] so appends that land between
// the two phases can never overflow the caller's buffers (they are
// picked up by the next query). series_idx_out gets the *dense*
// position i (0..nsids-1), matching PointBatch.
int tss_fill_range(void* h, const int64_t* sids, int64_t nsids,
                   int64_t start_ms, int64_t end_ms,
                   const int64_t* offsets, const int64_t* counts,
                   int64_t* ts_out, double* vals_out,
                   int32_t* series_idx_out, int threads) {
  Store* s = static_cast<Store*>(h);
  std::vector<SeriesBuffer*> bufs;
  if (!s->snapshot(sids, nsids, &bufs)) return -1;
  int64_t points = 0;
  for (int64_t i = 0; i < nsids; ++i) points += counts[i];
  const int64_t grain =  // by the mean series: one long one is one item
      items_per(kFillPoints, points / std::max<int64_t>(nsids, 1));
  parallel_for(threads, nsids, grain, [&](int64_t r0, int64_t r1) {
    for (int64_t i = r0; i < r1; ++i) {
      SeriesBuffer* buf = bufs[i];
      std::lock_guard<std::mutex> lock(buf->mu);
      buf->ensure_sorted_locked();
      int64_t lo =
          std::lower_bound(buf->ts.begin(), buf->ts.end(), start_ms) -
          buf->ts.begin();
      int64_t hi =
          std::upper_bound(buf->ts.begin(), buf->ts.end(), end_ms) -
          buf->ts.begin();
      int64_t off = offsets[i];
      int64_t n = hi - lo;
      if (n > counts[i]) n = counts[i];
      if (n > 0) {
        std::memcpy(ts_out + off, buf->ts.data() + lo,
                    n * sizeof(int64_t));
        std::memcpy(vals_out + off, buf->vals.data() + lo,
                    n * sizeof(double));
        std::fill(series_idx_out + off, series_idx_out + off + n,
                  (int32_t)i);
      }
      // fewer points than counted (concurrent repair/delete): pad the
      // remainder with NaN placeholders the compute path skips
      for (int64_t j = n < 0 ? 0 : n; j < counts[i]; ++j) {
        ts_out[off + j] = start_ms;
        vals_out[off + j] = std::numeric_limits<double>::quiet_NaN();
        series_idx_out[off + j] = (int32_t)i;
      }
    }
  });
  return 0;
}

}  // extern "C"

namespace {

// The per-series walk tss_bucket_reduce and tss_bucket_grid share:
// every point of one series with start_ms <= ts <= end_ms lands in
// bucket b = (ts - t0) / interval_ms (caller guarantees t0 <= start_ms
// and the last bucket covers end_ms). ``emit(b, sum, cnt, mn, mx)`` is
// called once for each bucket that holds a stored point, in rising
// order of b; cnt counts the non-NaN ones (NaN stored values are
// skipped, matching the device bucketize's NaN guard, ref:
// Aggregators.runDouble skipping NaN), so cnt may be 0. mn/mx are
// +inf/-inf unless MINMAX. Holds the buffer's lock for the walk.
// One bucket's arithmetic, what every pass below shares so that a cell
// is the same bits whichever pass wrote it: the f64 sum and count of the
// non-NaN values of [v, ve) in stored order, with min / max when MINMAX
// (+inf / -inf otherwise). A fixed-bound loop the compiler can
// vectorize; the NaN guard is a branchless blend.
template <bool MINMAX>
inline void reduce_points(const double* v, const double* ve, double* sum,
                          double* cnt, double* mn, double* mx) {
  const double inf = std::numeric_limits<double>::infinity();
  double s = 0.0, c = 0.0, lo = inf, hi = -inf;
  for (; v < ve; ++v) {
    double x = *v;
    bool ok = x == x;
    s += ok ? x : 0.0;
    c += ok ? 1.0 : 0.0;
    if (MINMAX) {
      lo = (ok && x < lo) ? x : lo;
      hi = (ok && x > hi) ? x : hi;
    }
  }
  *sum = s;
  *cnt = c;
  *mn = lo;
  *mx = hi;
}

template <bool MINMAX, typename Emit>
inline void reduce_series(SeriesBuffer* buf, int64_t start_ms,
                          int64_t end_ms, int64_t t0,
                          int64_t interval_ms, int64_t nbuckets,
                          Emit&& emit) {
  std::lock_guard<std::mutex> lock(buf->mu);
  buf->ensure_sorted_locked();
  int64_t lo =
      std::lower_bound(buf->ts.begin(), buf->ts.end(), start_ms) -
      buf->ts.begin();
  int64_t hi =
      std::upper_bound(buf->ts.begin(), buf->ts.end(), end_ms) -
      buf->ts.begin();
  // timestamps are sorted: resolve each bucket's point range with
  // a binary search, then accumulate over reduce_points' fixed-bound
  // loop (no per-point divide or data-dependent exit)
  const int64_t* tsd = buf->ts.data();
  const double* vd = buf->vals.data();
  int64_t p = lo;
  while (p < hi) {
    // floor division (C++ '/' truncates toward zero): a point just
    // below t0 must be DROPPED like the Python twin's '//' does,
    // not folded into bucket 0
    int64_t d = tsd[p] - t0;
    int64_t b = d >= 0 ? d / interval_ms : -1;
    if (b < 0) {  // cannot happen when t0 <= start_ms; be safe
      ++p;
      continue;
    }
    if (b >= nbuckets) break;
    int64_t bucket_end = t0 + (b + 1) * interval_ms;
    int64_t pe = std::lower_bound(tsd + p, tsd + hi, bucket_end) - tsd;
    double sum, cnt, mn, mx;
    reduce_points<MINMAX>(vd + p, vd + pe, &sum, &cnt, &mn, &mx);
    emit(b, sum, cnt, mn, mx);
    p = pe;
  }
}

template <bool MINMAX>
void bucket_reduce_rows(const std::vector<SeriesBuffer*>& bufs,
                        int64_t start_ms, int64_t end_ms, int64_t t0,
                        int64_t interval_ms, int64_t nbuckets,
                        double* sum_out, double* cnt_out,
                        double* min_out, double* max_out, int threads) {
  const int64_t nsids = (int64_t)bufs.size();
  const double inf = std::numeric_limits<double>::infinity();
  parallel_for(threads, nsids, kReduceRows, [&](int64_t r0, int64_t r1) {
    for (int64_t i = r0; i < r1; ++i) {
      double* srow = sum_out + i * nbuckets;
      double* crow = cnt_out + i * nbuckets;
      double* mnrow = MINMAX ? min_out + i * nbuckets : nullptr;
      double* mxrow = MINMAX ? max_out + i * nbuckets : nullptr;
      for (int64_t b = 0; b < nbuckets; ++b) {
        srow[b] = 0.0;
        crow[b] = 0.0;
        if (MINMAX) {
          mnrow[b] = inf;
          mxrow[b] = -inf;
        }
      }
      reduce_series<MINMAX>(
          bufs[i], start_ms, end_ms, t0, interval_ms, nbuckets,
          [&](int64_t b, double sum, double cnt, double mn, double mx) {
            srow[b] = sum;
            crow[b] = cnt;
            if (MINMAX) {
              mnrow[b] = mn;
              mxrow[b] = mx;
            }
          });
    }
  });
}

// tss_bucket_grid's statistic of one bucket (fn codes, the Python
// wrapper's _GRID_FN_CODES): avg divides in f64; the caller rounds the
// result to the output type once.
enum GridFn { kSum = 0, kCount = 1, kAvg = 2, kMin = 3, kMax = 4 };

inline double grid_stat(int fn, double sum, double cnt, double mn,
                        double mx) {
  return fn == kSum     ? sum
         : fn == kCount ? cnt
         : fn == kAvg   ? sum / cnt
         : fn == kMin   ? mn
                        : mx;
}

// Rows [0, nsids) of the [s_pad, b_pad] grid are series, each written
// once, left to right: NaN / 0 up to the next bucket with data, the
// statistic / 1 there, NaN / 0 to the end of the padded row. Rows
// [nsids, s_pad) are padding. Participants claim rows in chunks, so
// the page faults of the caller's fresh buffers spread over them.
template <typename T, bool MINMAX>
int64_t bucket_grid_rows(const std::vector<SeriesBuffer*>& bufs,
                         int64_t start_ms, int64_t end_ms, int64_t t0,
                         int64_t interval_ms, int64_t nbuckets, int fn,
                         int64_t s_pad, int64_t b_pad, T* grid,
                         uint8_t* mask, int threads) {
  const int64_t nsids = (int64_t)bufs.size();
  const T nan = std::numeric_limits<T>::quiet_NaN();
  std::atomic<int64_t> num_points{0};
  parallel_for(threads, s_pad, kGridRows, [&](int64_t r0, int64_t r1) {
    int64_t points = 0;
    for (int64_t i = r0; i < r1; ++i) {
      T* grow = grid + i * b_pad;
      uint8_t* mrow = mask + i * b_pad;
      int64_t done = 0;  // columns of this row already written
      if (i < nsids) {
        reduce_series<MINMAX>(
            bufs[i], start_ms, end_ms, t0, interval_ms, nbuckets,
            [&](int64_t b, double sum, double cnt, double mn, double mx) {
              if (cnt == 0.0) return;  // only NaNs stored: a hole
              std::fill(grow + done, grow + b, nan);
              std::memset(mrow + done, 0, b - done);
              grow[b] = static_cast<T>(grid_stat(fn, sum, cnt, mn, mx));
              mrow[b] = 1;
              done = b + 1;
              points += (int64_t)cnt;
            });
      }
      std::fill(grow + done, grow + b_pad, nan);
      std::memset(mrow + done, 0, b_pad - done);
    }
    num_points.fetch_add(points, std::memory_order_relaxed);
  });
  return num_points.load();
}

// std::lower_bound over the sorted [b, e), begun where a series of even
// cadence would hold `key` and widened from there by doubling: such a
// series costs its first and last timestamp and the line of the guess,
// where halving from the middle touches four or five lines of a
// hundred points; any other series still ends in a halving search, of
// the stretch the doubling fenced in.
inline const int64_t* guessed_lower_bound(const int64_t* b, const int64_t* e,
                                          int64_t key) {
  if (b == e || key <= *b) return b;
  const int64_t n = e - b;
  const int64_t first = *b, last = e[-1];
  if (key > last) return e;
  // first < key <= last, so n >= 2 and last > first
  const int64_t at = (int64_t)((double)(key - first) / (double)(last - first) *
                               (double)(n - 1));
  const int64_t* p = b + std::min(std::max<int64_t>(at, 0), n - 1);
  int64_t step = 1;
  if (*p < key) {  // the answer lies after p
    const int64_t* lo = p + 1;
    while (lo + step < e && lo[step - 1] < key) {
      lo += step;
      step <<= 1;
    }
    return std::lower_bound(lo, std::min(lo + step, e), key);
  }
  const int64_t* hi = p;  // *hi >= key: the answer lies at or before p
  while (hi - step > b && hi[-step] >= key) {
    hi -= step;
    step <<= 1;
  }
  return std::lower_bound(std::max(hi - step, b), hi, key);
}

// tss_bucket_columns' walk. Participants claim chunks of rows; a chunk
// first writes NaN / 0 down its stretch of every column (contiguous: a
// column is [s_pad]), then each of its series takes its lock ONCE:
// the window's two searches give the row's point count, and each wanted
// bucket's range inside them is one search more (none where the bucket
// before it was wanted too: its end is this one's beginning). The pass
// is bound by the misses it takes a series (the buffer, then lines of
// its timestamps and values), not by the points it reads: hence the
// guessed searches, and the buffers of the series ahead fetched early.
template <typename T, bool MINMAX>
void bucket_columns_rows(const std::vector<SeriesBuffer*>& bufs,
                         int64_t start_ms, int64_t end_ms, int64_t t0,
                         int64_t interval_ms, int fn,
                         const int64_t* wanted, int64_t nwanted,
                         int64_t s_pad, T* cols, uint8_t* masks,
                         int64_t* counts, int threads) {
  const int64_t nsids = (int64_t)bufs.size();
  const T nan = std::numeric_limits<T>::quiet_NaN();
  parallel_for(threads, s_pad, kColumnRows, [&](int64_t r0, int64_t r1) {
    for (int64_t w = 0; w < nwanted; ++w) {
      std::fill(cols + w * s_pad + r0, cols + w * s_pad + r1, nan);
      std::memset(masks + w * s_pad + r0, 0, r1 - r0);
    }
    const int64_t rows = std::min(r1, nsids);
    for (int64_t i = r0; i < rows; ++i) {
      if (i + kColumnsAhead < rows) {  // the buffer's two lines, early
        const char* ahead = reinterpret_cast<const char*>(bufs[i + kColumnsAhead]);
        __builtin_prefetch(ahead);
        __builtin_prefetch(ahead + 64);
      }
      SeriesBuffer* buf = bufs[i];
      std::lock_guard<std::mutex> lock(buf->mu);
      buf->ensure_sorted_locked();
      const int64_t* tsd = buf->ts.data();
      const int64_t* tse = tsd + buf->ts.size();
      const double* vd = buf->vals.data();
      const int64_t* lo = guessed_lower_bound(tsd, tse, start_ms);
      // upper_bound(end) of whole milliseconds
      const int64_t* hi = end_ms == std::numeric_limits<int64_t>::max()
                              ? tse
                              : guessed_lower_bound(tsd, tse, end_ms + 1);
      counts[i] = hi - lo;  // count_range's: NaN points counted
      if (hi < lo) hi = lo;
      const int64_t* pe = lo;
      int64_t prev = -2;
      for (int64_t w = 0; w < nwanted; ++w) {
        const int64_t b = wanted[w];
        const int64_t* p =
            b == prev + 1 ? pe
                          : guessed_lower_bound(pe, hi, t0 + b * interval_ms);
        pe = guessed_lower_bound(p, hi, t0 + (b + 1) * interval_ms);
        prev = b;
        if (p == pe) continue;
        double sum, cnt, mn, mx;
        reduce_points<MINMAX>(vd + (p - tsd), vd + (pe - tsd), &sum, &cnt,
                              &mn, &mx);
        if (cnt == 0.0) continue;  // only NaNs stored: a hole
        cols[w * s_pad + i] = static_cast<T>(grid_stat(fn, sum, cnt, mn, mx));
        masks[w * s_pad + i] = 1;
      }
    }
  });
}

}  // namespace

extern "C" {

// Fused range-scan + fixed-interval downsample pre-reduction: for
// each series i, every point with start_ms <= ts <= end_ms lands in
// bucket b = (ts - t0) / interval_ms (see reduce_series),
// accumulating sum / count / min / max. Outputs are [nsids, nbuckets]
// row-major; cells with count 0 hold sum 0, min +inf, max -inf.
// min_out/max_out may be null when the caller only needs sum/count.
// Threaded over series. Returns -1 on a bad sid, else 0.
//
// This removes the [N]-point materialize + host->device upload for
// simple-function downsamples: the device receives S*B cells instead
// of N points (60x smaller for 1m data in 1h buckets) and starts at
// the grid stage of the pipeline.
int tss_bucket_reduce(void* h, const int64_t* sids, int64_t nsids,
                      int64_t start_ms, int64_t end_ms, int64_t t0,
                      int64_t interval_ms, int64_t nbuckets,
                      double* sum_out, double* cnt_out, double* min_out,
                      double* max_out, int threads) {
  Store* s = static_cast<Store*>(h);
  std::vector<SeriesBuffer*> bufs;
  if (!s->snapshot(sids, nsids, &bufs)) return -1;
  if (interval_ms <= 0 || nbuckets <= 0) return -1;
  if (min_out && max_out)
    bucket_reduce_rows<true>(bufs, start_ms, end_ms, t0, interval_ms,
                             nbuckets, sum_out, cnt_out, min_out,
                             max_out, threads);
  else
    bucket_reduce_rows<false>(bufs, start_ms, end_ms, t0, interval_ms,
                              nbuckets, sum_out, cnt_out, nullptr,
                              nullptr, threads);
  return 0;
}

// tss_bucket_reduce's walk, finished where the tail program reads it:
// ONE statistic (fn: GridFn) of each bucket, written once into the
// caller's [s_pad, b_pad] grid of f32 (f64 when out_f64) with the
// presence mask beside it; a bucket without a (non-NaN) point, the
// columns past nbuckets and the rows past nsids hold NaN / 0. Returns
// the number of points reduced, -1 on a bad sid or bad dimensions.
int64_t tss_bucket_grid(void* h, const int64_t* sids, int64_t nsids,
                        int64_t start_ms, int64_t end_ms, int64_t t0,
                        int64_t interval_ms, int64_t nbuckets, int fn,
                        int64_t s_pad, int64_t b_pad, int out_f64,
                        void* grid_out, uint8_t* mask_out,
                        int threads) {
  Store* s = static_cast<Store*>(h);
  std::vector<SeriesBuffer*> bufs;
  if (!s->snapshot(sids, nsids, &bufs)) return -1;
  if (interval_ms <= 0 || nbuckets <= 0 || s_pad < nsids ||
      b_pad < nbuckets || fn < kSum || fn > kMax)
    return -1;
  const bool minmax = fn == kMin || fn == kMax;
#define TSS_GRID(T, MM)                                                \
  bucket_grid_rows<T, MM>(bufs, start_ms, end_ms, t0, interval_ms,     \
                          nbuckets, fn, s_pad, b_pad,                  \
                          static_cast<T*>(grid_out), mask_out, threads)
  if (out_f64)
    return minmax ? TSS_GRID(double, true) : TSS_GRID(double, false);
  return minmax ? TSS_GRID(float, true) : TSS_GRID(float, false);
#undef TSS_GRID
}

// tss_bucket_grid's buckets ONE AT A TIME, for a caller that keeps a
// metric's whole buckets and asks only for those it lacks: the same
// statistic of each of the `nwanted` buckets `wanted` (indexes into the
// window's nbuckets, strictly rising), written column-major into
// [nwanted, s_pad] values (f32, f64 when out_f64) and masks, NaN / 0
// in the holes and in rows [nsids, s_pad). A bucket the window cuts
// holds the points inside [start_ms, end_ms] alone. counts_out[i] is
// series i's point count of the whole window (tss_count_range's: the
// two searches the walk makes anyway). One lock take a series. Returns
// 0, -1 on a bad sid or bad dimensions.
int tss_bucket_columns(void* h, const int64_t* sids, int64_t nsids,
                       int64_t start_ms, int64_t end_ms, int64_t t0,
                       int64_t interval_ms, int64_t nbuckets, int fn,
                       const int64_t* wanted, int64_t nwanted,
                       int64_t s_pad, int out_f64, void* cols_out,
                       uint8_t* masks_out, int64_t* counts_out,
                       int threads) {
  Store* s = static_cast<Store*>(h);
  std::vector<SeriesBuffer*> bufs;
  if (!s->snapshot(sids, nsids, &bufs)) return -1;
  if (interval_ms <= 0 || nbuckets <= 0 || s_pad < nsids || nwanted < 0 ||
      fn < kSum || fn > kMax)
    return -1;
  for (int64_t w = 0; w < nwanted; ++w)
    if (wanted[w] < 0 || wanted[w] >= nbuckets ||
        (w > 0 && wanted[w] <= wanted[w - 1]))
      return -1;
  const bool minmax = fn == kMin || fn == kMax;
#define TSS_COLUMNS(T, MM)                                             \
  bucket_columns_rows<T, MM>(bufs, start_ms, end_ms, t0, interval_ms,  \
                             fn, wanted, nwanted, s_pad,               \
                             static_cast<T*>(cols_out), masks_out,     \
                             counts_out, threads)
  if (out_f64) {
    if (minmax) TSS_COLUMNS(double, true); else TSS_COLUMNS(double, false);
  } else {
    if (minmax) TSS_COLUMNS(float, true); else TSS_COLUMNS(float, false);
  }
#undef TSS_COLUMNS
  return 0;
}

}  // extern "C"

namespace {

// Charset the reference allows in metric/tag names and values
// (Tags.validateString: alphanumerics plus -_./ and unicode letters
// via Character.isLetter). Bytes >= 0x80 (UTF-8 sequences) pass here;
// the Python side re-validates non-ASCII names precisely.
inline bool valid_name_char(unsigned char c) {
  return (c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z') ||
         (c >= '0' && c <= '9') || c == '-' || c == '_' || c == '.' ||
         c == '/' || c >= 0x80;
}

inline bool valid_name(const char* p, int64_t n) {
  if (n <= 0) return false;
  for (int64_t i = 0; i < n; ++i)
    if (!valid_name_char((unsigned char)p[i])) return false;
  return true;
}

// One thread's share of the import parse: lines in [pos, limit) of
// the buffer, writing per-line outputs at global line index
// line_base.., building a LOCAL group table (keys + first-line byte
// ranges). Local group ids are remapped to global ids after the merge.
struct LocalGroups {
  std::unordered_map<std::string, int64_t> map;
  std::vector<int64_t> rep_off, rep_len;
};

void parse_import_range(const char* buf, int64_t pos, int64_t limit,
                        int64_t line_base, int64_t* ts_out,
                        double* val_out, uint8_t* int_out,
                        int64_t* group_out, int32_t* err_out,
                        LocalGroups* lg) {
  std::string key;
  key.reserve(256);
  std::string prev_key;
  int64_t prev_gid = -1;
  struct Tok {
    const char* p;
    int64_t n;
  };
  int64_t line = line_base;
  const int64_t kMaxTs = (int64_t)1 << 47;
  while (pos < limit) {
    int64_t eol = pos;
    while (eol < limit && buf[eol] != '\n') ++eol;
    int64_t lstart = pos;
    int64_t lend = eol;
    if (lend > lstart && buf[lend - 1] == '\r') --lend;
    pos = eol + 1;
    int64_t i = line++;
    ts_out[i] = 0;
    val_out[i] = 0.0;
    int_out[i] = 0;
    group_out[i] = -1;
    err_out[i] = 0;
    // tokenize on runs of space/tab
    Tok toks[16];
    int ntok = 0;
    int64_t q = lstart;
    bool overflow = false;
    while (q < lend) {
      while (q < lend && (buf[q] == ' ' || buf[q] == '\t')) ++q;
      if (q >= lend) break;
      int64_t t0 = q;
      while (q < lend && buf[q] != ' ' && buf[q] != '\t') ++q;
      if (ntok < 16) {
        toks[ntok].p = buf + t0;
        toks[ntok].n = q - t0;
        ++ntok;
      } else {
        overflow = true;
      }
    }
    // blank or comment: first NON-SPACE char decides, so indented
    // comments skip like the line.strip().startswith('#') fallback
    {
      int64_t fs = lstart;
      while (fs < lend && (buf[fs] == ' ' || buf[fs] == '\t')) ++fs;
      if (fs >= lend || buf[fs] == '#') {
        err_out[i] = -1;
        continue;
      }
    }
    if (ntok == 0) {
      err_out[i] = -1;
      continue;
    }
    if (ntok < 4 || overflow) {
      err_out[i] = ntok < 4 ? 1 : 4;
      continue;
    }
    if (!valid_name(toks[0].p, toks[0].n)) {
      err_out[i] = 5;
      continue;
    }
    // timestamp: plain digits (seconds or epoch-ms)
    {
      int64_t ts = 0;
      bool ok = toks[1].n > 0 && toks[1].n < 15;
      for (int64_t c = 0; ok && c < toks[1].n; ++c) {
        char ch = toks[1].p[c];
        if (ch < '0' || ch > '9') ok = false;
        else ts = ts * 10 + (ch - '0');
      }
      if (!ok || ts <= 0 || ts > kMaxTs) {
        err_out[i] = 2;
        continue;
      }
      ts_out[i] = ts;
    }
    // value: inline integer fast path, strtod for the rest
    {
      const char* vp = toks[2].p;
      int64_t vn = toks[2].n;
      int64_t st = (vn && (vp[0] == '-' || vp[0] == '+')) ? 1 : 0;
      bool neg = vn && vp[0] == '-';
      bool isint = vn - st > 0 && vn - st < 19;
      int64_t acc = 0;
      for (int64_t c = st; isint && c < vn; ++c) {
        char ch = vp[c];
        if (ch < '0' || ch > '9') isint = false;
        else acc = acc * 10 + (ch - '0');
      }
      if (isint) {
        val_out[i] = neg ? -(double)acc : (double)acc;
        int_out[i] = 1;
      } else {
        // decimal float shape only: strtod alone would accept 'nan',
        // 'inf', and hex floats, which the reference (and the NaN-as-
        // missing engine sentinel) must reject
        bool shape_ok = vn > 0 && vn < 64;
        for (int64_t c = 0; shape_ok && c < vn; ++c) {
          char ch = vp[c];
          if (!((ch >= '0' && ch <= '9') || ch == '.' || ch == '+' ||
                ch == '-' || ch == 'e' || ch == 'E'))
            shape_ok = false;
        }
        if (!shape_ok) {
          err_out[i] = 3;
          continue;
        }
        char tmp[64];
        std::memcpy(tmp, vp, vn);
        tmp[vn] = 0;
        char* end = nullptr;
        double v = std::strtod(tmp, &end);
        if (end != tmp + vn || v != v) {
          err_out[i] = 3;
          continue;
        }
        val_out[i] = v;
        int_out[i] = 0;
      }
    }
    // tags: validate k=v, sort for a canonical key
    int ntags = ntok - 3;
    if (ntags > 8) {  // the reference's hard tag cap (Const.java:28)
      err_out[i] = 4;
      continue;
    }
    Tok* tags = toks + 3;
    bool bad = false;
    for (int t = 0; t < ntags && !bad; ++t) {
      const char* eq =
          (const char*)memchr(tags[t].p, '=', (size_t)tags[t].n);
      if (!eq || eq == tags[t].p ||
          eq == tags[t].p + tags[t].n - 1) {
        err_out[i] = 4;
        bad = true;
        break;
      }
      if (!valid_name(tags[t].p, eq - tags[t].p) ||
          !valid_name(eq + 1, tags[t].p + tags[t].n - eq - 1)) {
        err_out[i] = 5;
        bad = true;
      }
    }
    if (bad) continue;
    std::sort(tags, tags + ntags, [](const Tok& a, const Tok& b) {
      int c = std::memcmp(a.p, b.p, (size_t)std::min(a.n, b.n));
      return c < 0 || (c == 0 && a.n < b.n);
    });
    key.assign(toks[0].p, (size_t)toks[0].n);
    for (int t = 0; t < ntags; ++t) {
      key.push_back(' ');
      key.append(tags[t].p, (size_t)tags[t].n);
    }
    // import files overwhelmingly write one series' points in runs
    // (scan --import emits them that way): the previous line's key
    // skips the hash lookup for the common case
    int64_t gid;
    if (prev_gid >= 0 && key == prev_key) {
      gid = prev_gid;
    } else {
      auto it = lg->map.find(key);
      if (it == lg->map.end()) {
        gid = (int64_t)lg->map.size();
        lg->map.emplace(key, gid);
        lg->rep_off.push_back(lstart);
        lg->rep_len.push_back(lend - lstart);
      } else {
        gid = it->second;
      }
      prev_key = key;
      prev_gid = gid;
    }
    group_out[i] = gid;
  }
}

// Shortest-round-trip double formatting, portable to libstdc++ < 11:
// gcc-10 hosts ship INTEGER std::to_chars only, so the double call is
// ambiguous among the integer overloads (the build failed outright
// there until this guard). Feature-test the floating-point overload;
// without it, walk %.*g precisions until strtod round-trips — the
// same shortest-digits contract to_chars guarantees by construction,
// so the emitted text parses to the identical double either way (the
// exponent spelling may differ: "1e16" vs "1e+16" — both valid JSON).
inline char* fmt_double_chars(char* p, char* end, double v) {
#if defined(__cpp_lib_to_chars) && __cpp_lib_to_chars >= 201611L
  return std::to_chars(p, end, v).ptr;
#else
  char tmp[40];
  // three-step walk, not 1..17 (this path serves every large
  // response on gcc-10 hosts, so it must stay near to_chars speed):
  // %g strips trailing zeros, so %.15g already prints "human" values
  // (0.1, 42.5) at their shortest and round-trips most doubles; 16
  // covers the next band; 17 round-trips everything by construction
  // (no verify needed). A precision-p print that round-trips implies
  // the shortest form needs <= p digits, so this walk reproduces the
  // shortest text (and Python repr) for practical value populations.
  int n = 0;
  for (int prec = 15; prec <= 17; ++prec) {
    n = std::snprintf(tmp, sizeof tmp, "%.*g", prec, v);
    if (prec == 17 || (n > 0 && n < (int)sizeof tmp &&
                       std::strtod(tmp, nullptr) == v))
      break;
  }
  if (n <= 0 || n > end - p) return p;  // caller reserves headroom
  for (int i = 0; i < n; ++i)  // locale hardening: ',' decimal point
    if (tmp[i] == ',') tmp[i] = '.';
  std::memcpy(p, tmp, n);
  return p + n;
#endif
}

}  // namespace

extern "C" {

// 1 when doubles format through real std::to_chars (libstdc++ >= 11),
// 0 on the snprintf round-trip fallback (gcc-10 hosts). The Python
// serializer prefers its own columnar bulk formatter over a slow
// native one — the fallback's strtod verification makes it ~2x the
// cost of the pure-Python path, inverting the reason the native
// formatter exists.
int64_t tss_fmt_fast() {
#if defined(__cpp_lib_to_chars) && __cpp_lib_to_chars >= 201611L
  return 1;
#else
  return 0;
#endif
}

// JSON-format a series' datapoints: entries joined by ',' with no
// surrounding braces (the Python serializer owns the envelope).
// seconds != 0 emits ts/1000 (the query's ms_resolution choice);
// as_arrays != 0 emits "[ts,val]" rows instead of "\"ts\":val".
// Value forms match the Python serializer's _format_value: NaN ->
// "NaN" (quoted), +/-inf -> quoted Infinity, integral |v| < 2^53 ->
// integer digits, else shortest round-trip (std::to_chars) with a
// ".0" float marker when the digits carry no '.'/'e' — byte-identical
// to Python repr except the exponent-style choice at |v| >= 1e16
// (both forms parse to the same double).
// Returns bytes written, or -1 if cap is too small.
// Why native: Python pays ~1.3us per point building response JSON;
// a 3M-point response costs 4s of serialization on one core. This
// loop does it ~20x faster.
int64_t tss_format_dps(const int64_t* ts_ms, const double* vals,
                       int64_t n, int seconds, int as_arrays,
                       char* out, int64_t cap) {
  char* p = out;
  char* end = out + cap;
  const double kMaxInt = 9007199254740992.0;  // 2^53
  for (int64_t i = 0; i < n; ++i) {
    if (end - p < 64) return -1;
    if (i) *p++ = ',';
    int64_t t = seconds ? ts_ms[i] / 1000 : ts_ms[i];
    if (as_arrays) {
      *p++ = '[';
      auto r = std::to_chars(p, end, t);
      p = r.ptr;
      *p++ = ',';
    } else {
      *p++ = '"';
      auto r = std::to_chars(p, end, t);
      p = r.ptr;
      *p++ = '"';
      *p++ = ':';
    }
    double v = vals[i];
    if (v != v) {
      std::memcpy(p, "\"NaN\"", 5);
      p += 5;
    } else if (v == std::numeric_limits<double>::infinity()) {
      std::memcpy(p, "\"Infinity\"", 10);
      p += 10;
    } else if (v == -std::numeric_limits<double>::infinity()) {
      std::memcpy(p, "\"-Infinity\"", 11);
      p += 11;
    } else if (v > -kMaxInt && v < kMaxInt &&
               v == (double)(int64_t)v) {
      // range-guard BEFORE the int64 cast: converting an
      // unrepresentable double is UB
      auto r = std::to_chars(p, end, (int64_t)v);
      p = r.ptr;
    } else {
      char* start = p;
      p = fmt_double_chars(p, end, v);
      // Python repr always marks floats (".0" or an exponent);
      // integral doubles >= 2^53 would otherwise print bare digits
      bool marked = false;
      for (char* q = start; q < p; ++q)
        if (*q == '.' || *q == 'e' || *q == 'E') marked = true;
      if (!marked) {
        *p++ = '.';
        *p++ = '0';
      }
    }
    if (as_arrays) *p++ = ']';
  }
  return p - out;
}

// Count '\n' + 1 (array sizing for tss_parse_import without a Python
// bytes.count pass).
int64_t tss_count_lines(const char* buf, int64_t len) {
  int64_t n = 1;
  const char* p = buf;
  const char* end = buf + len;
  while ((p = (const char*)memchr(p, '\n', end - p)) != nullptr) {
    ++n;
    ++p;
  }
  return n;
}

// Scatter-append: line i appends (ts_ms[i], vals[i], ints[i]) onto
// series sids[i]; sids[i] < 0 skips the line (parse errors / rejected
// groups). One call lands a whole parsed import buffer — the per-group
// Python loop with one ctypes call per series cost ~3 s per 10M points
// at 50k series. Returns the number appended, -1 on a bad sid.
int64_t tss_append_lines(void* h, const int64_t* sids, int64_t n,
                         const int64_t* ts_ms, const double* vals,
                         const uint8_t* ints) {
  Store* s = static_cast<Store*>(h);
  int64_t written = 0;
  int64_t oldest = std::numeric_limits<int64_t>::max();
  SeriesBuffer* buf = nullptr;
  int64_t cur = -2;  // current locked-in sid (runs are the common case)
  for (int64_t i = 0; i < n; ++i) {
    int64_t sid = sids[i];
    if (sid < 0) continue;
    if (sid != cur) {
      SeriesBuffer* nb = s->lookup(sid);
      if (buf) buf->mu.unlock();
      if (!nb) {
        s->note_written(written, oldest);
        return -1;
      }
      nb->mu.lock();
      buf = nb;
      cur = sid;
    }
    if (buf->sorted && !buf->ts.empty() && ts_ms[i] <= buf->ts.back())
      buf->sorted = false;
    buf->ts.push_back(ts_ms[i]);
    buf->vals.push_back(vals[i]);
    buf->is_int.push_back(ints ? ints[i] : 0);
    oldest = std::min(oldest, ts_ms[i]);
    ++written;
  }
  if (buf) buf->mu.unlock();
  s->note_written(written, oldest);
  return written;
}

// Bulk text-import parser (the reference's TextImporter line format:
// "metric ts value tagk=tagv [tagk=tagv ...]"). Parallel over
// newline-aligned byte chunks:
//   per line i: ts_out[i] (raw, seconds or ms as written), val_out[i],
//   int_out[i] (the value token had integer form), err_out[i]
//   (0 = ok, -1 = blank/comment, >0 = error code), group_out[i] =
//   id of the line's distinct (metric, sorted tags) key or -1.
// rep_off/rep_len[g] give the byte range of group g's first line so
// the caller can parse metric/tag STRINGS once per distinct series
// (UID resolution is per-series, not per-point).
// Error codes: 1 too few fields (a tag is required, like the
// reference), 2 bad timestamp, 3 bad value, 4 malformed tag or too
// many tags, 5 invalid character.
// Returns the number of distinct groups, or -1 if group capacity
// (max_groups) was exceeded. nlines_out gets the number of lines seen
// (caller sizes arrays by tss_count_lines, which is always enough).
int64_t tss_parse_import(const char* buf, int64_t len, int64_t* ts_out,
                         double* val_out, uint8_t* int_out,
                         int64_t* group_out, int32_t* err_out,
                         int64_t* rep_off, int64_t* rep_len,
                         int64_t max_groups, int64_t* nlines_out,
                         int threads) {
  // chunk boundaries aligned to line starts: a chunk a participant, and
  // none under kImportBytes (a telnet batch of a few lines is one chunk)
  const int64_t parts =
      std::clamp<int64_t>(len / kImportBytes, 1, std::max(threads, 1));
  std::vector<int64_t> starts;
  starts.push_back(0);
  for (int64_t t = 1; t < parts; ++t) {
    int64_t pos = len * t / parts;
    const char* nl =
        (const char*)memchr(buf + pos, '\n', (size_t)(len - pos));
    int64_t aligned = nl ? (nl - buf) + 1 : len;
    // aligned == len would create an empty final chunk whose
    // "trailing line without newline" credit (below) belongs to the
    // chunk that actually owns the final bytes — skip it.
    if (aligned > starts.back() && aligned < len) starts.push_back(aligned);
  }
  starts.push_back(len);
  int nchunks = (int)starts.size() - 1;
  // per-chunk line counts -> global line bases
  std::vector<int64_t> nlines(nchunks), base(nchunks);
  parallel_for(nchunks, nchunks, 1, [&](int64_t c, int64_t) {
    int64_t cnt = 0;
    const char* p = buf + starts[c];
    const char* e = buf + starts[c + 1];
    // each line ends with '\n' except possibly the buffer's last
    while ((p = (const char*)memchr(p, '\n', e - p)) != nullptr) {
      ++cnt;
      ++p;
    }
    if (c == nchunks - 1 && len > 0 && buf[len - 1] != '\n')
      ++cnt;  // trailing line without newline
    nlines[c] = cnt;
  });
  int64_t total_lines = 0;
  for (int c = 0; c < nchunks; ++c) {
    base[c] = total_lines;
    total_lines += nlines[c];
  }
  *nlines_out = total_lines;
  // parse each chunk with a local group table
  std::vector<LocalGroups> locals(nchunks);
  parallel_for(nchunks, nchunks, 1, [&](int64_t c, int64_t) {
    parse_import_range(buf, starts[c], starts[c + 1], base[c], ts_out,
                       val_out, int_out, group_out, err_out, &locals[c]);
  });
  // merge local tables into the global numbering and remap gids
  std::unordered_map<std::string, int64_t> global;
  std::vector<std::vector<int64_t>> remap(nchunks);
  for (int c = 0; c < nchunks; ++c) {
    remap[c].resize(locals[c].map.size());
    for (auto& kv : locals[c].map) {
      auto it = global.find(kv.first);
      int64_t gid;
      if (it == global.end()) {
        gid = (int64_t)global.size();
        if (gid >= max_groups) return -1;
        global.emplace(kv.first, gid);
        rep_off[gid] = locals[c].rep_off[kv.second];
        rep_len[gid] = locals[c].rep_len[kv.second];
      } else {
        gid = it->second;
      }
      remap[c][kv.second] = gid;
    }
  }
  // local gid -> global gid, every chunk (the merge renumbers in
  // hash-iteration order even for a single chunk)
  parallel_for(nchunks, nchunks, 1, [&](int64_t c, int64_t) {
    for (int64_t i = base[c]; i < base[c] + nlines[c]; ++i)
      if (group_out[i] >= 0) group_out[i] = remap[c][group_out[i]];
  });
  return (int64_t)global.size();
}

}  // extern "C"
