// Native host sparse-table store: the mem + SSD tiers of BoxPS in C++.
//
// The reference keeps its 1e10..1e11-key feature table inside the closed
// libbox_ps.so, tiered across SSD and host RAM and promoted to HBM per pass
// (box_wrapper.cc:1325 LoadSSD2Mem; cmake/external/box_ps.cmake). This file
// is the open TPU-side equivalent of that host tier: a sharded open-
// addressing uint64 -> fp32-row store with
//
//   - batch pull_or_create / push (the pass finalize + writeback hot path;
//     the Python-dict fallback measured ~160k keys/s, this runs tens of
//     millions/s and threads across shards with the GIL released),
//   - deterministic per-key initialization (splitmix64 counter RNG, so
//     init is order- and shard-independent — stronger than the reference's
//     sequential RNG, and required for multi-host reproducibility),
//   - touched-row tracking for delta saves (SaveDelta parity,
//     box_wrapper.cc:1288-1331),
//   - pass-boundary decay+shrink (pslib show_click_decay_rate + shrink),
//   - a per-shard disk spill tier: cold rows are evicted to append-only
//     shard files and lazily promoted (with catch-up decay) when a later
//     pass touches them — LoadSSD2Mem semantics inverted for the host side.
//
// Beside the store, one entry point that needs no table handle:
// pbx_lookup_rows, the pass working set's key -> row search
// (PassWorkingSet.lookup / DistributedWorkingSet.lookup): a pass's keys
// against its sorted unique keys, threaded over slices of the queries,
// each thread running kLookupLanes binary searches in lock step so their
// cache misses overlap.
//
// ABI: plain C, handle-based, ctypes-bound (utils/native.py); all calls are
// thread-safe via per-shard mutexes.

#include <algorithm>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <mutex>
#include <string>
#include <thread>
#include <utility>
#include <vector>

namespace {

constexpr uint64_t kHashMult = 0x9E3779B97F4A7C15ull;

inline uint64_t mix_shard(uint64_t key) { return (key * kHashMult) >> 33; }

inline uint64_t splitmix64(uint64_t x) {
  x += 0x9E3779B97F4A7C15ull;
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ull;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBull;
  return x ^ (x >> 31);
}

// Hash-slot states. kDisk entries hold a byte offset into the shard's
// spill file instead of a mem row id.
enum : uint8_t { kEmpty = 0, kMem = 1, kDisk = 2 };

struct SpillRec {  // on-disk record header, followed by width floats
  uint64_t key;
  int64_t epoch;    // table pass-epoch at spill time (for catch-up decay)
  uint64_t touched; // delta-save flag survives the disk tier
};

struct Shard {
  // open-addressing hash: slot -> (key, where)
  std::vector<uint64_t> hkeys;
  std::vector<int64_t> hval;  // mem row id (kMem) or file offset (kDisk)
  std::vector<uint8_t> hstate;
  uint64_t mask = 0;  // capacity - 1 (power of two)
  int64_t n_used = 0;  // mem + disk entries in the hash

  // mem tier rows
  std::vector<float> values;        // [n_rows * width]
  std::vector<uint64_t> row_key;    // [n_rows]
  std::vector<uint8_t> row_touched; // [n_rows]
  std::vector<int64_t> row_epoch;   // [n_rows] last-touched table epoch
  int64_t n_rows = 0;

  // cumulative tier counters (monotone; exported via pbx_table_tier_stats)
  int64_t n_spilled = 0;        // mem rows written to the disk tier
  int64_t n_promoted = 0;       // disk rows brought back to mem
  int64_t n_admit_spilled = 0;  // spills forced by the admission threshold
  int64_t n_lazy_shrunk = 0;    // disk rows dropped at promote (decayed out)

  // disk tier
  FILE* spill = nullptr;
  std::string spill_path;
  int64_t n_disk = 0;
  int64_t n_disk_touched = 0;
  // records in the spill file no longer referenced by any hash entry
  // (promotes and lazy shrinks leave their bytes behind — the file is
  // append-only between compactions). When dead outnumber live, the
  // shard's file is rewritten (compact_spill) so a many-pass run's spill
  // stays bounded by its LIVE cold set, not its history.
  int64_t dead_disk = 0;

  std::mutex mtx;

  ~Shard() {
    if (spill) fclose(spill);
  }
};

// Cumulative IO-overlap telemetry (pbx_table_io_stats). Atomics because
// shard workers update them concurrently; pure observation — none of these
// feed back into table state, so they cannot perturb bitwise results.
struct IoStats {
  std::atomic<int64_t> spill_gather_ns{0};   // row serialize into staging
  std::atomic<int64_t> spill_fwrite_ns{0};   // staged fwrite (flusher side)
  std::atomic<int64_t> prepass_read_ns{0};   // push pre-pass header freads
  std::atomic<int64_t> stage_flushes{0};     // staged buffers handed off
  std::atomic<int64_t> stage_bytes{0};       // bytes through the stage path
};

struct Table {
  int n_shards;
  int width;
  int show_col;
  int clk_col;
  uint64_t seed;
  std::vector<int32_t> init_cols;  // columns getting uniform(-r, r) init
  float init_range;
  std::string spill_dir;  // empty => spill disabled
  int64_t epoch = 0;      // incremented by decay_shrink (pass boundary)
  float last_decay = 1.0f;
  float last_threshold = 0.0f;
  IoStats io;
  std::vector<Shard> shards;

  Table(int ns) : shards(ns) {}
};

inline int64_t now_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

inline int shard_of(const Table* t, uint64_t key) {
  return (int)(mix_shard(key) % (uint64_t)t->n_shards);
}

void shard_grow_hash(Shard* s) {
  uint64_t new_cap = s->mask ? (s->mask + 1) * 2 : 1024;
  std::vector<uint64_t> nk(new_cap);
  std::vector<int64_t> nv(new_cap);
  std::vector<uint8_t> ns(new_cap, kEmpty);
  uint64_t nmask = new_cap - 1;
  if (s->mask) {
    for (uint64_t i = 0; i <= s->mask; ++i) {
      if (s->hstate[i] == kEmpty) continue;
      uint64_t j = splitmix64(s->hkeys[i]) & nmask;
      while (ns[j] != kEmpty) j = (j + 1) & nmask;
      nk[j] = s->hkeys[i];
      nv[j] = s->hval[i];
      ns[j] = s->hstate[i];
    }
  }
  s->hkeys.swap(nk);
  s->hval.swap(nv);
  s->hstate.swap(ns);
  s->mask = nmask;
}

// find slot of key; returns slot index, or the empty slot to insert into.
// *found says whether the key is present.
inline uint64_t shard_find(Shard* s, uint64_t key, bool* found) {
  uint64_t j = splitmix64(key) & s->mask;
  while (true) {
    if (s->hstate[j] == kEmpty) {
      *found = false;
      return j;
    }
    if (s->hkeys[j] == key) {
      *found = true;
      return j;
    }
    j = (j + 1) & s->mask;
  }
}

inline void shard_maybe_grow(Shard* s) {
  if (s->mask == 0 || (uint64_t)s->n_used * 10 >= (s->mask + 1) * 7)
    shard_grow_hash(s);
}

int64_t shard_new_row(const Table* t, Shard* s, uint64_t key) {
  int64_t row = s->n_rows++;
  if ((int64_t)s->row_key.size() < s->n_rows) {
    int64_t cap = s->row_key.size() ? (int64_t)s->row_key.size() * 2 : 1024;
    if (cap < s->n_rows) cap = s->n_rows;
    s->row_key.resize(cap);
    s->row_touched.resize(cap, 0);
    s->row_epoch.resize(cap, 0);
    s->values.resize(cap * (int64_t)t->width);
  }
  s->row_key[row] = key;
  s->row_touched[row] = 0;
  s->row_epoch[row] = t->epoch;
  return row;
}

void init_row(const Table* t, uint64_t key, float* dst) {
  std::memset(dst, 0, sizeof(float) * t->width);
  // one full mix per key, then a cheap counter advance per column — the
  // sequence is a pure function of (seed, key, column order), so init stays
  // deterministic and shard/host-count independent
  uint64_t st = splitmix64(t->seed ^ splitmix64(key));
  for (int32_t c : t->init_cols) {
    st += 0x9E3779B97F4A7C15ull;
    uint64_t r = splitmix64(st);  // full finalizer: real avalanche per column
    float u = (float)(r >> 40) * (1.0f / 16777216.0f);
    dst[c] = (2.0f * u - 1.0f) * t->init_range;
  }
}

bool shard_open_spill(Table* t, int si) {
  Shard* s = &t->shards[si];
  if (s->spill) return true;
  if (t->spill_dir.empty()) return false;
  char buf[64];
  snprintf(buf, sizeof(buf), "/spill-%05d.bin", si);
  s->spill_path = t->spill_dir + buf;
  s->spill = fopen(s->spill_path.c_str(), "w+b");
  return s->spill != nullptr;
}

// Promote a disk entry at hash slot j to a mem row, applying catch-up
// decay for the passes it slept through. Returns the new row id, or -1 if
// the decayed row falls below the shrink threshold (entry is dropped).
// seek_end=false defers the append-position restore (batched promotes
// seek once at the end so stdio read-ahead survives across reads).
int64_t promote(Table* t, Shard* s, uint64_t j, bool seek_end = true) {
  int64_t off = s->hval[j];
  SpillRec rec;
  std::vector<float> buf(t->width);
  fseeko(s->spill, off, SEEK_SET);
  if (fread(&rec, sizeof(rec), 1, s->spill) != 1 ||
      fread(buf.data(), sizeof(float), t->width, s->spill) != (size_t)t->width)
    return -2;  // IO error
  if (seek_end) fseeko(s->spill, 0, SEEK_END);
  int64_t missed = t->epoch - rec.epoch;
  if (missed > 0 && t->last_decay < 1.0f) {
    // one multiply per slept-through pass, in pass order — NOT an
    // accumulated power: (s*d)*d != s*(d*d) in fp32 for non-pow2 rates,
    // and a promoted row must match its never-spilled twin bitwise
    for (int64_t i = 0; i < missed; ++i) {
      buf[t->show_col] *= t->last_decay;
      buf[t->clk_col] *= t->last_decay;
    }
  }
  s->n_disk--;
  s->dead_disk++;  // the on-disk bytes at `off` are now garbage
  if (rec.touched) s->n_disk_touched--;
  if (missed > 0 && buf[t->show_col] < t->last_threshold) {
    // lazily shrunk: delete the entry entirely
    s->hstate[j] = kEmpty;
    s->n_used--;
    // re-insert any displaced linear-probe followers
    uint64_t k = (j + 1) & s->mask;
    while (s->hstate[k] != kEmpty) {
      uint64_t kk = s->hkeys[k];
      int64_t vv = s->hval[k];
      uint8_t st = s->hstate[k];
      s->hstate[k] = kEmpty;
      s->n_used--;
      bool f;
      uint64_t slot = shard_find(s, kk, &f);
      s->hkeys[slot] = kk;
      s->hval[slot] = vv;
      s->hstate[slot] = st;
      s->n_used++;
      k = (k + 1) & s->mask;
    }
    s->n_lazy_shrunk++;
    return -1;
  }
  int64_t row = shard_new_row(t, s, s->hkeys[j]);
  std::memcpy(&s->values[row * t->width], buf.data(),
              sizeof(float) * t->width);
  s->row_touched[row] = rec.touched ? 1 : 0;
  s->hval[j] = row;
  s->hstate[j] = kMem;
  s->n_promoted++;
  return row;
}

// Partition keys by shard once, then run fn(shard_id, key_positions) over
// shards on a thread pool (ctypes released the GIL for us). Each worker
// owns the strided shard set {w, w+nt, ...} — disjoint ownership, so any
// per-shard side output (shard_ns below) is written race-free without a
// merge lock; per-shard mutexes still guard against concurrent API calls.
//
// `threads` <= 0 picks the legacy auto heuristic (hardware concurrency
// capped at 16, serial below 64k keys); `threads` == 1 forces the serial
// path; larger values request an explicit pool (capped at n_shards). The
// shard visit ORDER inside a worker and the per-shard work are identical
// at every thread count — only interleaving differs, which per-shard locks
// make unobservable — so results are bitwise-equal across `threads`.
//
// `shard_ns`, when non-null, receives per-shard wall nanoseconds spent in
// fn (length n_shards; written by the owning worker only).
// A batch call's pool size: `threads` > 0 is taken as asked; otherwise
// hardware concurrency capped at 16, and one below 64k keys.
inline int pool_size(int threads, int64_t n_keys) {
  if (threads > 0) return threads;
  if (n_keys < 65536) return 1;
  return (int)std::min(16u, std::thread::hardware_concurrency());
}

template <typename Fn>
int for_shards_ex(const Table* t, const uint64_t* keys, int64_t n,
                  int threads, int64_t* shard_ns, Fn fn) {
  int ns = t->n_shards;
  std::vector<int64_t> count(ns, 0);
  std::vector<int> sh((size_t)n);
  for (int64_t i = 0; i < n; ++i) {
    int s = shard_of(t, keys[i]);
    sh[i] = s;
    count[s]++;
  }
  std::vector<int64_t> start(ns + 1, 0);
  for (int s = 0; s < ns; ++s) start[s + 1] = start[s] + count[s];
  std::vector<int64_t> pos(start.begin(), start.end() - 1);
  std::vector<int64_t> order((size_t)n);
  for (int64_t i = 0; i < n; ++i) order[pos[sh[i]]++] = i;
  if (shard_ns)
    for (int s = 0; s < ns; ++s) shard_ns[s] = 0;

  int nt = pool_size(threads, n);
  if (nt > ns) nt = ns;
  if (nt < 1) nt = 1;
  std::vector<int> rc(nt, 0);
  auto work = [&](int w) {
    for (int s = w; s < ns; s += nt) {
      int64_t t0 = shard_ns ? now_ns() : 0;
      int r = fn(s, order.data() + start[s], count[s]);
      if (shard_ns) shard_ns[s] = now_ns() - t0;
      if (r != 0) rc[w] = r;
    }
  };
  if (nt == 1) {
    work(0);
  } else {
    std::vector<std::thread> th;
    for (int w = 0; w < nt; ++w) th.emplace_back(work, w);
    for (auto& x : th) x.join();
  }
  for (int w = 0; w < (int)rc.size(); ++w)
    if (rc[w] != 0) return rc[w];
  return 0;
}

template <typename Fn>
int for_shards(const Table* t, const uint64_t* keys, int64_t n, Fn fn) {
  return for_shards_ex(t, keys, n, /*threads=*/0, /*shard_ns=*/nullptr, fn);
}

// ---- pass working set lookup -------------------------------------------
//
// kLookupLanes binary searches side by side: every round halves all of
// them, and each lane's next probe is prefetched before any lane reads
// its own, so a core keeps that many cache misses in flight where one
// search has one. All lanes of a call share the halving sequence (it
// depends on n alone), so the rounds are one loop. The width is timed
// (a v5e host's 13 cores, 56.9M queries against 47.6M sorted keys, us a
// key at 4 / 8 / 16 / 32 / 64 lanes: 0.029 / 0.016 / 0.0105 / 0.017 /
// 0.018; one thread 0.33 / 0.18 / 0.125 / 0.21 / 0.21; PERF.md, PR 41).
constexpr int kLookupLanes = 16;
constexpr int kLookupMissing = 5;  // first missing query indices reported

struct LookupMiss {
  int64_t n = 0;
  int64_t first[kLookupMissing];
  void note(int64_t i) {
    if (n < kLookupMissing) first[n] = i;
    ++n;
  }
};

// out[i] = (int32) row_of_sorted[pos(keys[i])] for i in [lo, hi), where
// pos is the numpy body's: the first position whose sorted key is not
// below the query, clipped to n - 1. A query whose key is not at its
// position is noted in `miss`. n >= 1.
void lookup_slice(const uint64_t* sorted, const int64_t* row_of_sorted,
                  int64_t n, const uint64_t* keys, int32_t* out, int64_t lo,
                  int64_t hi, LookupMiss* miss) {
  for (int64_t i0 = lo; i0 < hi; i0 += kLookupLanes) {
    const int w = (int)std::min<int64_t>(kLookupLanes, hi - i0);
    const uint64_t* k = keys + i0;
    int64_t base[kLookupLanes];
    for (int j = 0; j < w; ++j) base[j] = 0;
    // the position lies in [base, base + len] before and after a round
    for (int64_t len = n; len > 1;) {
      const int64_t half = len >> 1;
      for (int j = 0; j < w; ++j)
        __builtin_prefetch(sorted + base[j] + half - 1, 0, 0);
      for (int j = 0; j < w; ++j)
        base[j] += sorted[base[j] + half - 1] < k[j] ? half : 0;
      len -= half;
    }
    for (int j = 0; j < w; ++j) {
      const int64_t pos = base[j] + (sorted[base[j]] < k[j] ? 1 : 0);
      base[j] = pos < n ? pos : n - 1;
      __builtin_prefetch(row_of_sorted + base[j], 0, 0);
    }
    // the search's last cache line is still hot: the proof that the key
    // is there and the row read happen at this one position
    for (int j = 0; j < w; ++j) {
      if (sorted[base[j]] != k[j]) miss->note(i0 + j);
      out[i0 + j] = (int32_t)row_of_sorted[base[j]];
    }
  }
}

// Rewrite one shard's spill file with only the LIVE records (hash entries
// in kDisk state). Caller holds the shard lock. Failure-safe: hash offsets
// are staged in a side vector and applied only after the tmp file is fully
// flushed and renamed over the old one — any IO error (short read, ENOSPC
// at write or flush time, failed rename) leaves the shard exactly as it
// was, old file and offsets intact. Live records are read in OFFSET order
// (sequential IO, same trick as the batched-promote path). Returns live
// records kept, or negative on IO error.
int64_t compact_spill(Table* t, Shard* s) {
  if (!s->spill) return 0;
  std::vector<std::pair<int64_t, uint64_t>> live;  // (old offset, hash slot)
  for (uint64_t j = 0; j <= s->mask && s->mask; ++j)
    if (s->hstate[j] == kDisk) live.push_back({s->hval[j], j});
  std::sort(live.begin(), live.end());
  std::string tmp = s->spill_path + ".tmp";
  FILE* nf = fopen(tmp.c_str(), "w+b");
  if (!nf) return -2;
  std::vector<float> buf(t->width);
  std::vector<int64_t> new_off(live.size());
  auto fail = [&]() {
    fclose(nf);
    remove(tmp.c_str());
    fseeko(s->spill, 0, SEEK_END);
    return (int64_t)-2;
  };
  for (size_t i = 0; i < live.size(); ++i) {
    SpillRec rec;
    fseeko(s->spill, live[i].first, SEEK_SET);
    if (fread(&rec, sizeof(rec), 1, s->spill) != 1 ||
        fread(buf.data(), sizeof(float), t->width, s->spill) !=
            (size_t)t->width)
      return fail();
    new_off[i] = ftello(nf);
    if (fwrite(&rec, sizeof(rec), 1, nf) != 1 ||
        fwrite(buf.data(), sizeof(float), t->width, nf) != (size_t)t->width)
      return fail();
  }
  if (fflush(nf) != 0) return fail();
  if (rename(tmp.c_str(), s->spill_path.c_str()) != 0) return fail();
  fclose(s->spill);
  s->spill = nf;  // nf refers to the renamed (now canonical) file on POSIX
  fseeko(s->spill, 0, SEEK_END);
  for (size_t i = 0; i < live.size(); ++i)
    s->hval[live[i].second] = new_off[i];
  s->dead_disk = 0;
  return (int64_t)live.size();
}

enum : int { kSpillFifo = 0, kSpillFreq = 1 };

// Serialize victims[lo..hi) of one shard into `out` as the exact byte
// stream the legacy per-record fwrite loop produced: SpillRec header
// followed by width floats, in victim order.
void gather_spill_chunk(const Table* t, const Shard* s,
                        const std::vector<int64_t>& victims, int64_t lo,
                        int64_t hi, size_t recsz, std::vector<char>* out) {
  out->resize((size_t)(hi - lo) * recsz);
  char* p = out->data();
  for (int64_t i = lo; i < hi; ++i) {
    int64_t r = victims[i];
    SpillRec rec{s->row_key[r], t->epoch, s->row_touched[r] ? 1ull : 0ull};
    std::memcpy(p, &rec, sizeof(rec));
    std::memcpy(p + sizeof(rec), &s->values[r * (int64_t)t->width],
                sizeof(float) * t->width);
    p += recsz;
  }
}

// Write the given mem rows (any order) of one shard to its spill file,
// convert their hash entries to kDisk, and compact the surviving mem rows
// in place. Caller holds the shard lock and has opened the spill file.
// Returns rows spilled, or -2 on IO error.
//
// The write is double-buffered: records are append-only with a fixed size,
// so every victim's disk offset is analytic (base + i*recsz) and the next
// chunk's row gather can run while a flusher thread has the previous
// chunk's fwrite in flight. The byte stream is identical to the legacy
// per-record loop; on an IO error the hash/counter state is untouched
// (strictly cleaner than the legacy mid-loop bail, which had already
// bumped n_disk_touched for the records it got through).
int64_t shard_spill_rows(Table* t, Shard* s,
                         const std::vector<int64_t>& victims) {
  if (victims.empty()) return 0;
  fseeko(s->spill, 0, SEEK_END);
  const int64_t base = ftello(s->spill);
  const size_t recsz = sizeof(SpillRec) + sizeof(float) * (size_t)t->width;
  const int64_t nv = (int64_t)victims.size();
  std::vector<uint8_t> is_victim(s->n_rows, 0);
  std::vector<int64_t> disk_off(s->n_rows, 0);
  int64_t touched_delta = 0;
  for (int64_t i = 0; i < nv; ++i) {
    int64_t r = victims[i];
    is_victim[r] = 1;
    disk_off[r] = base + i * (int64_t)recsz;
    if (s->row_touched[r]) touched_delta++;
  }
  // ~1 MiB staging chunks: big enough that fwrite syscall/lock overhead
  // amortizes, small enough that two buffers stay cache-friendly
  int64_t chunk = (int64_t)((1u << 20) / recsz);
  if (chunk < 64) chunk = 64;
  int64_t gather_ns = 0, fwrite_ns = 0, flushes = 0;
  bool werr = false;
  if (nv <= chunk) {
    // small spill: one gather, one fwrite — no thread, same bytes
    std::vector<char> buf;
    int64_t t0 = now_ns();
    gather_spill_chunk(t, s, victims, 0, nv, recsz, &buf);
    gather_ns = now_ns() - t0;
    t0 = now_ns();
    if (fwrite(buf.data(), 1, buf.size(), s->spill) != buf.size()) werr = true;
    fwrite_ns = now_ns() - t0;
    flushes = 1;
  } else {
    // two staging buffers in ping-pong: the main thread gathers chunk k+1
    // while the flusher writes chunk k. Only the flusher touches s->spill
    // between here and the join.
    std::vector<char> bufs[2];
    std::mutex m;
    std::condition_variable cv;
    int pending = -1;  // buffer index handed to the flusher, -1 = none
    bool done = false;
    std::thread flusher([&] {
      std::unique_lock<std::mutex> lk(m);
      while (true) {
        cv.wait(lk, [&] { return pending >= 0 || done; });
        if (pending < 0) return;
        int b = pending;
        lk.unlock();
        int64_t t0 = now_ns();
        size_t wr = fwrite(bufs[b].data(), 1, bufs[b].size(), s->spill);
        int64_t dt = now_ns() - t0;
        lk.lock();
        fwrite_ns += dt;
        pending = -1;
        if (wr != bufs[b].size()) {
          werr = true;
          done = true;
        }
        cv.notify_all();
      }
    });
    int cur = 0;
    for (int64_t lo = 0; lo < nv; lo += chunk) {
      int64_t hi = std::min(nv, lo + chunk);
      int64_t t0 = now_ns();
      gather_spill_chunk(t, s, victims, lo, hi, recsz, &bufs[cur]);
      gather_ns += now_ns() - t0;
      std::unique_lock<std::mutex> lk(m);
      cv.wait(lk, [&] { return pending < 0; });
      if (werr) break;
      pending = cur;
      flushes++;
      cv.notify_all();
      cur ^= 1;
    }
    {
      std::unique_lock<std::mutex> lk(m);
      cv.wait(lk, [&] { return pending < 0; });  // drain the last chunk
      done = true;
      cv.notify_all();
    }
    flusher.join();
  }
  t->io.spill_gather_ns += gather_ns;
  t->io.spill_fwrite_ns += fwrite_ns;
  t->io.stage_flushes += flushes;
  t->io.stage_bytes += nv * (int64_t)recsz;
  if (werr) return -2;
  s->n_disk_touched += touched_delta;
  fflush(s->spill);
  // compact survivors
  std::vector<int64_t> remap(s->n_rows, -1);
  int64_t keep = 0;
  for (int64_t r = 0; r < s->n_rows; ++r)
    if (!is_victim[r]) remap[r] = keep++;
  for (int64_t r = 0; r < s->n_rows; ++r) {
    int64_t nr = remap[r];
    if (nr < 0 || nr == r) continue;
    std::memcpy(&s->values[nr * t->width], &s->values[r * t->width],
                sizeof(float) * t->width);
    s->row_key[nr] = s->row_key[r];
    s->row_touched[nr] = s->row_touched[r];
    s->row_epoch[nr] = s->row_epoch[r];
  }
  for (uint64_t j = 0; j <= s->mask && s->mask; ++j) {
    if (s->hstate[j] != kMem) continue;
    int64_t r = s->hval[j];
    if (is_victim[r]) {
      s->hstate[j] = kDisk;
      s->hval[j] = disk_off[r];
      s->n_disk++;
    } else {
      s->hval[j] = remap[r];
    }
  }
  s->n_rows = keep;
  s->n_spilled += (int64_t)victims.size();
  // opportunistic space reclaim: once dead records outnumber live ones
  // the file is mostly garbage — rewrite it now, while we already hold
  // the shard lock at a pass boundary
  if (s->dead_disk > s->n_disk && s->dead_disk >= 1024) {
    if (compact_spill(t, s) < 0) return -2;
  }
  return (int64_t)victims.size();
}

// Coldness-ranked victim pick for one shard: every row under the admission
// threshold goes first (disk-first admission — sub-threshold keys don't get
// to occupy RAM past a cap sweep), then the coldest rows by (lowest decayed
// show, oldest last-touched epoch, lowest row id) until `want` victims.
// Rows at or above the pin threshold are spilled only once every colder
// candidate is gone. Caller holds the shard lock.
void pick_victims_freq(const Table* t, const Shard* s, int64_t want,
                       float pin_show, float admit_show,
                       std::vector<int64_t>* victims, int64_t* admitted) {
  std::vector<int64_t> ranked;  // below pin threshold: normal candidates
  std::vector<int64_t> pinned;  // at/above pin threshold: last resort
  for (int64_t r = 0; r < s->n_rows; ++r) {
    float show = s->values[r * t->width + t->show_col];
    if (admit_show > 0.0f && show < admit_show) {
      victims->push_back(r);
      continue;
    }
    if (pin_show > 0.0f && show >= pin_show)
      pinned.push_back(r);
    else
      ranked.push_back(r);
  }
  *admitted = (int64_t)victims->size();
  auto colder = [&](int64_t a, int64_t b) {
    float sa = s->values[a * t->width + t->show_col];
    float sb = s->values[b * t->width + t->show_col];
    if (sa != sb) return sa < sb;
    if (s->row_epoch[a] != s->row_epoch[b])
      return s->row_epoch[a] < s->row_epoch[b];
    return a < b;
  };
  int64_t extra = want - *admitted;
  for (auto* pool : {&ranked, &pinned}) {
    if (extra <= 0) break;
    if ((int64_t)pool->size() > extra) {
      std::partial_sort(pool->begin(), pool->begin() + extra, pool->end(),
                        colder);
      pool->resize(extra);
    } else {
      std::sort(pool->begin(), pool->end(), colder);
    }
    victims->insert(victims->end(), pool->begin(), pool->end());
    extra -= (int64_t)pool->size();
  }
}

int64_t spill_cold_impl(Table* t, int64_t max_mem_rows, int policy,
                        float pin_show, float admit_show) {
  if (t->spill_dir.empty()) return -1;
  std::vector<int64_t> shard_mem(t->n_shards, 0);
  int64_t mem = 0;
  for (int si = 0; si < t->n_shards; ++si) {
    Shard* s = &t->shards[si];
    std::lock_guard<std::mutex> g(s->mtx);
    shard_mem[si] = s->n_rows;
    mem += s->n_rows;
  }
  int64_t over = mem - max_mem_rows;
  if (over <= 0) return 0;
  int64_t spilled_total = 0;
  if (policy == kSpillFreq) {
    // exact largest-remainder apportionment of `over` across shards in
    // proportion to their occupancy: the post-sweep mem tier stays
    // balanced by shard and totals exactly max_mem_rows (admission
    // evictions may push it lower — that's the point of admission)
    std::vector<int64_t> want(t->n_shards, 0);
    int64_t assigned = 0;
    for (int si = 0; si < t->n_shards; ++si) {
      want[si] = over * shard_mem[si] / mem;
      assigned += want[si];
    }
    int64_t rem = over - assigned;
    while (rem > 0) {
      bool progress = false;
      for (int si = 0; si < t->n_shards && rem > 0; ++si) {
        if (want[si] < shard_mem[si]) {
          want[si]++;
          rem--;
          progress = true;
        }
      }
      if (!progress) break;
    }
    for (int si = 0; si < t->n_shards; ++si) {
      Shard* s = &t->shards[si];
      std::lock_guard<std::mutex> g(s->mtx);
      if (s->n_rows == 0) continue;
      if (want[si] <= 0 && admit_show <= 0.0f) continue;
      if (!shard_open_spill(t, si)) return -2;
      std::vector<int64_t> victims;
      int64_t admitted = 0;
      pick_victims_freq(t, s, want[si], pin_show, admit_show, &victims,
                        &admitted);
      int64_t n = shard_spill_rows(t, s, victims);
      if (n < 0) return n;
      s->n_admit_spilled += admitted;
      spilled_total += n;
    }
    return spilled_total;
  }
  // fifo (legacy, kept as the A/B baseline): untouched rows in creation
  // order, then touched rows, greedily shard by shard until under cap
  int64_t need = over;
  for (int si = 0; si < t->n_shards && need > 0; ++si) {
    Shard* s = &t->shards[si];
    std::lock_guard<std::mutex> g(s->mtx);
    if (s->n_rows == 0) continue;
    if (!shard_open_spill(t, si)) return -2;
    std::vector<int64_t> victims;
    for (int64_t r = 0; r < s->n_rows && (int64_t)victims.size() < need; ++r)
      if (!s->row_touched[r]) victims.push_back(r);
    for (int64_t r = 0; r < s->n_rows && (int64_t)victims.size() < need; ++r)
      if (s->row_touched[r]) victims.push_back(r);
    if (victims.empty()) continue;
    int64_t n = shard_spill_rows(t, s, victims);
    if (n < 0) return n;
    need -= n;
    spilled_total += n;
  }
  return spilled_total;
}

}  // namespace

extern "C" {

void* pbx_table_create(int n_shards, int width, int show_col, int clk_col,
                       uint64_t seed, const int32_t* init_cols,
                       int n_init_cols, float init_range,
                       const char* spill_dir) {
  Table* t = new Table(n_shards);
  t->n_shards = n_shards;
  t->width = width;
  t->show_col = show_col;
  t->clk_col = clk_col;
  t->seed = seed;
  t->init_cols.assign(init_cols, init_cols + n_init_cols);
  t->init_range = init_range;
  if (spill_dir && spill_dir[0]) t->spill_dir = spill_dir;
  return (void*)t;
}

void pbx_table_free(void* h) { delete (Table*)h; }

int64_t pbx_table_size(void* h) {
  Table* t = (Table*)h;
  int64_t n = 0;
  for (auto& s : t->shards) {
    std::lock_guard<std::mutex> g(s.mtx);
    n += s.n_used;
  }
  return n;
}

int64_t pbx_table_mem_rows(void* h) {
  Table* t = (Table*)h;
  int64_t n = 0;
  for (auto& s : t->shards) {
    std::lock_guard<std::mutex> g(s.mtx);
    n += s.n_used - s.n_disk;
  }
  return n;
}

int64_t pbx_table_disk_rows(void* h) {
  Table* t = (Table*)h;
  int64_t n = 0;
  for (auto& s : t->shards) {
    std::lock_guard<std::mutex> g(s.mtx);
    n += s.n_disk;
  }
  return n;
}

// Batch pull: rows for keys[i] -> out[i*width .. ], creating (with
// deterministic init) or promoting from disk as needed. Returns 0, or
// negative on IO error.
int pbx_table_pull_or_create(void* h, const uint64_t* keys, int64_t n,
                             float* out) {
  Table* t = (Table*)h;
  return for_shards(t, keys, n, [&](int si, const int64_t* idx, int64_t m) {
    Shard* s = &t->shards[si];
    std::lock_guard<std::mutex> g(s->mtx);
    // reserve for the worst case (every key new) upfront: one rehash
    // instead of ~log2(m) incremental doublings on first-pass creates
    while ((s->mask + 1) * 7 < (uint64_t)(s->n_used + m + 1) * 10)
      shard_grow_hash(s);
    // pass-finalize pattern: a pass's working set promotes MANY disk rows
    // at once — read them in file-offset order (sequential-ish IO, no
    // per-read seek-to-end) instead of key order. Skipped when the disk
    // tier is tiny: the extra O(m) probe pass would cost more than the few
    // inline promotes the main loop handles anyway.
    if (s->n_disk >= 64) {
      std::vector<std::pair<int64_t, uint64_t>> hits;  // (offset, key)
      for (int64_t q = 0; q < m; ++q) {
        bool found;
        uint64_t j = shard_find(s, keys[idx[q]], &found);
        if (found && s->hstate[j] == kDisk)
          hits.emplace_back(s->hval[j], s->hkeys[j]);
      }
      std::sort(hits.begin(), hits.end());
      for (auto& hit : hits) {
        bool found;
        uint64_t j = shard_find(s, hit.second, &found);
        if (!found || s->hstate[j] != kDisk) continue;
        int64_t r = promote(t, s, j, /*seek_end=*/false);
        if (r == -2) return -2;  // IO error (-1 lazily shrunk: main loop
                                 // recreates the key fresh below)
      }
      if (!hits.empty()) fseeko(s->spill, 0, SEEK_END);
    }
    for (int64_t q = 0; q < m; ++q) {
      int64_t i = idx[q];
      uint64_t key = keys[i];
      bool found;
      uint64_t j = shard_find(s, key, &found);
      int64_t row;
      if (!found) {
        row = shard_new_row(t, s, key);
        init_row(t, key, &s->values[row * t->width]);
        s->hkeys[j] = key;
        s->hval[j] = row;
        s->hstate[j] = kMem;
        s->n_used++;
      } else if (s->hstate[j] == kDisk) {
        row = promote(t, s, j);
        if (row == -2) return -2;
        if (row == -1) {  // lazily shrunk: recreate fresh
          shard_maybe_grow(s);
          bool f2;
          j = shard_find(s, key, &f2);
          row = shard_new_row(t, s, key);
          init_row(t, key, &s->values[row * t->width]);
          s->hkeys[j] = key;
          s->hval[j] = row;
          s->hstate[j] = kMem;
          s->n_used++;
        }
      } else {
        row = s->hval[j];
      }
      s->row_epoch[row] = t->epoch;  // a pull is a touch (recency signal)
      std::memcpy(out + i * t->width, &s->values[row * t->width],
                  sizeof(float) * t->width);
    }
    return 0;
  });
}

namespace {

// One shard's slice of a push batch. Caller dispatch holds nothing; the
// shard lock is taken here. Shared by pbx_table_push (auto thread
// heuristic) and pbx_table_push_mt (explicit writer pool).
int push_shard_batch(Table* t, int si, const uint64_t* keys,
                     const float* rows, const int64_t* idx, int64_t m) {
  Shard* s = &t->shards[si];
  std::lock_guard<std::mutex> g(s->mtx);
  while ((s->mask + 1) * 7 < (uint64_t)(s->n_used + m + 1) * 10)
    shard_grow_hash(s);
  // disk-resident keys in this batch are fully overwritten below — only
  // the header's touched bit matters. Read those headers in file-offset
  // order (one sequential sweep, same trick as the batched promote in
  // pull) instead of an fseeko pair per superseded record. The reads are
  // double-buffered: a reader thread freads chunk k+1's headers while
  // this thread applies chunk k's hash/counter updates (the apply side
  // never touches the FILE*, so the handoff is the only sync point).
  if (s->n_disk >= 64) {
    std::vector<std::pair<int64_t, uint64_t>> hits;  // (offset, key)
    for (int64_t q = 0; q < m; ++q) {
      bool found;
      uint64_t j = shard_find(s, keys[idx[q]], &found);
      if (found && s->hstate[j] == kDisk)
        hits.emplace_back(s->hval[j], s->hkeys[j]);
    }
    std::sort(hits.begin(), hits.end());
    const int64_t nh = (int64_t)hits.size();
    const int64_t chunk = 512;
    auto read_chunk = [&](int64_t lo, int64_t hi,
                          std::vector<SpillRec>* out) -> int {
      out->resize((size_t)(hi - lo));
      int64_t t0 = now_ns();
      for (int64_t i = lo; i < hi; ++i) {
        fseeko(s->spill, hits[i].first, SEEK_SET);
        if (fread(&(*out)[i - lo], sizeof(SpillRec), 1, s->spill) != 1) {
          t->io.prepass_read_ns += now_ns() - t0;
          return -2;
        }
      }
      t->io.prepass_read_ns += now_ns() - t0;
      return 0;
    };
    auto apply_chunk = [&](int64_t lo, int64_t hi,
                           const std::vector<SpillRec>& recs) {
      for (int64_t i = lo; i < hi; ++i) {
        bool found;
        uint64_t j = shard_find(s, hits[i].second, &found);
        if (!found || s->hstate[j] != kDisk) continue;  // dup in batch
        if (recs[i - lo].touched) s->n_disk_touched--;
        s->n_disk--;
        s->dead_disk++;  // the superseded on-disk record is garbage now
        // row contents stay undefined until the main loop's memcpy — every
        // pre-pass key is in this batch, so each gets overwritten below
        int64_t row = shard_new_row(t, s, hits[i].second);
        s->hval[j] = row;
        s->hstate[j] = kMem;
      }
    };
    if (nh <= 2 * chunk) {
      std::vector<SpillRec> recs;
      if (nh > 0) {
        if (read_chunk(0, nh, &recs) != 0) return -2;
        apply_chunk(0, nh, recs);
      }
    } else {
      std::vector<SpillRec> bufs[2];
      int rerr = read_chunk(0, chunk, &bufs[0]);
      int cur = 0;
      for (int64_t lo = 0; lo < nh; lo += chunk) {
        if (rerr != 0) return -2;
        int64_t hi = std::min(nh, lo + chunk);
        int64_t nlo = hi, nhi = std::min(nh, hi + chunk);
        std::thread reader;
        if (nlo < nhi)
          reader = std::thread(
              [&, nlo, nhi, cur] { rerr = read_chunk(nlo, nhi, &bufs[cur ^ 1]); });
        apply_chunk(lo, hi, bufs[cur]);
        if (reader.joinable()) reader.join();
        cur ^= 1;
      }
    }
    if (nh > 0) fseeko(s->spill, 0, SEEK_END);
  }
  for (int64_t q = 0; q < m; ++q) {
    int64_t i = idx[q];
    uint64_t key = keys[i];
    bool found;
    uint64_t j = shard_find(s, key, &found);
    int64_t row;
    if (!found) {
      row = shard_new_row(t, s, key);
      s->hkeys[j] = key;
      s->hval[j] = row;
      s->hstate[j] = kMem;
      s->n_used++;
    } else if (s->hstate[j] == kDisk) {
      // full-row overwrite: only the header's touched bit matters
      SpillRec rec;
      fseeko(s->spill, s->hval[j], SEEK_SET);
      if (fread(&rec, sizeof(rec), 1, s->spill) != 1) return -2;
      fseeko(s->spill, 0, SEEK_END);
      if (rec.touched) s->n_disk_touched--;
      s->n_disk--;
      s->dead_disk++;  // the superseded on-disk record is garbage now
      row = shard_new_row(t, s, key);
      s->hval[j] = row;
      s->hstate[j] = kMem;
    } else {
      row = s->hval[j];
    }
    std::memcpy(&s->values[row * t->width], rows + i * t->width,
                sizeof(float) * t->width);
    s->row_touched[row] = 1;
    s->row_epoch[row] = t->epoch;  // a push is a touch
  }
  return 0;
}

}  // namespace

// Pass working set lookup: out[i] = (int32) row_of_sorted[position of
// keys[i] in sorted], one pass over the queries, nothing allocated per key.
// `sorted` is strictly ascending, n >= 1 (the caller answers an empty
// working set itself). The queries are cut into contiguous slices over a
// pool of for_shards_ex's size (pool_size: `threads` <= 0 = hardware
// concurrency capped at 16, serial below 64k keys), capped at one block of
// kLookupLanes keys a thread. Every
// out[i] is a function of keys[i] alone, so the result is the same at
// every thread count. Returns the number of queries whose key is not in
// `sorted`; the first min(that, 5) of their indices, in query order, are
// written to first_missing[0..5); *threads_used receives the pool size.
int64_t pbx_lookup_rows(const uint64_t* sorted, const int64_t* row_of_sorted,
                        int64_t n, const uint64_t* keys, int64_t m,
                        int32_t* out, int threads, int64_t* first_missing,
                        int* threads_used) {
  int nt = pool_size(threads, m);
  const int64_t blocks = (m + kLookupLanes - 1) / kLookupLanes;
  if (nt > blocks) nt = (int)blocks;
  if (nt < 1) nt = 1;
  *threads_used = nt;
  // slices are whole blocks of lanes, so a key's lane neighbours do not
  // depend on the thread count either
  const int64_t per = (blocks + nt - 1) / nt * kLookupLanes;
  std::vector<LookupMiss> miss(nt);
  auto work = [&](int w) {
    const int64_t lo = std::min<int64_t>(m, (int64_t)w * per);
    const int64_t hi = std::min<int64_t>(m, lo + per);
    lookup_slice(sorted, row_of_sorted, n, keys, out, lo, hi, &miss[w]);
  };
  if (nt == 1) {
    work(0);
  } else {
    std::vector<std::thread> th;
    for (int w = 0; w < nt; ++w) th.emplace_back(work, w);
    for (auto& x : th) x.join();
  }
  int64_t total = 0;
  int filled = 0;
  for (const LookupMiss& ms : miss) {  // slices in query order
    for (int q = 0; q < ms.n && q < kLookupMissing && filled < kLookupMissing; ++q)
      first_missing[filled++] = ms.first[q];
    total += ms.n;
  }
  return total;
}

// Batch push (upsert full rows) + mark touched. Returns 0 or negative.
int pbx_table_push(void* h, const uint64_t* keys, const float* rows,
                   int64_t n) {
  Table* t = (Table*)h;
  return for_shards(t, keys, n, [&](int si, const int64_t* idx, int64_t m) {
    return push_shard_batch(t, si, keys, rows, idx, m);
  });
}

// Batch push with an explicit writer pool: `threads` <= 0 = auto heuristic
// (identical to pbx_table_push), 1 = forced serial, else a fixed pool of
// min(threads, n_shards) workers each owning a disjoint strided shard set.
// Bitwise-equal to pbx_table_push at every thread count (see for_shards_ex).
// `shard_ns`, when non-null, receives per-shard wall nanoseconds (length
// n_shards) — the per-shard histogram feed. Returns 0 or negative.
int pbx_table_push_mt(void* h, const uint64_t* keys, const float* rows,
                      int64_t n, int threads, int64_t* shard_ns) {
  Table* t = (Table*)h;
  return for_shards_ex(t, keys, n, threads, shard_ns,
                       [&](int si, const int64_t* idx, int64_t m) {
                         return push_shard_batch(t, si, keys, rows, idx, m);
                       });
}

// Cumulative IO-overlap telemetry, 5 int64 slots:
//   [spill_gather_ns, spill_fwrite_ns, prepass_read_ns, stage_flushes,
//    stage_bytes]
void pbx_table_io_stats(void* h, int64_t* out) {
  Table* t = (Table*)h;
  out[0] = t->io.spill_gather_ns.load();
  out[1] = t->io.spill_fwrite_ns.load();
  out[2] = t->io.prepass_read_ns.load();
  out[3] = t->io.stage_flushes.load();
  out[4] = t->io.stage_bytes.load();
}

// Pass-boundary decay + shrink over the MEM tier (disk rows catch up
// lazily at promotion). Returns number of mem rows dropped.
int64_t pbx_table_decay_shrink(void* h, float decay, float threshold) {
  Table* t = (Table*)h;
  t->epoch++;
  t->last_decay = decay;
  t->last_threshold = threshold;
  int64_t dropped = 0;
  std::mutex dm;
  int nt = (int)std::thread::hardware_concurrency();
  if (nt > t->n_shards) nt = t->n_shards;
  if (nt > 16) nt = 16;
  if (nt < 1) nt = 1;
  auto work = [&](int w) {
    int64_t local = 0;
    for (int si = w; si < t->n_shards; si += nt) {
      Shard* s = &t->shards[si];
      std::lock_guard<std::mutex> g(s->mtx);
      // decay all rows; collect keep mask
      int64_t keep = 0;
      std::vector<int64_t> remap(s->n_rows, -1);
      for (int64_t r = 0; r < s->n_rows; ++r) {
        float* v = &s->values[r * t->width];
        v[t->show_col] *= decay;
        v[t->clk_col] *= decay;
        if (v[t->show_col] >= threshold) remap[r] = keep++;
      }
      if (keep == s->n_rows) continue;
      local += s->n_rows - keep;
      // compact rows in place (remap is monotone)
      for (int64_t r = 0; r < s->n_rows; ++r) {
        int64_t nr = remap[r];
        if (nr < 0 || nr == r) continue;
        std::memcpy(&s->values[nr * t->width], &s->values[r * t->width],
                    sizeof(float) * t->width);
        s->row_key[nr] = s->row_key[r];
        s->row_touched[nr] = s->row_touched[r];
        s->row_epoch[nr] = s->row_epoch[r];
      }
      s->n_rows = keep;
      // rebuild the hash from scratch: survivors remapped, disk entries
      // carried over, dropped rows simply not reinserted (O(cap), no
      // probe-chain deletion subtleties)
      std::vector<uint64_t> ok;
      std::vector<int64_t> ov;
      std::vector<uint8_t> os;
      ok.swap(s->hkeys);
      ov.swap(s->hval);
      os.swap(s->hstate);
      uint64_t omask = s->mask;
      s->mask = 0;
      s->n_used = 0;
      shard_grow_hash(s);
      while ((s->mask + 1) * 7 < (uint64_t)(keep + s->n_disk) * 10)
        shard_grow_hash(s);
      for (uint64_t j = 0; j <= omask && omask; ++j) {
        if (os[j] == kEmpty) continue;
        int64_t v = os[j] == kMem ? remap[ov[j]] : ov[j];
        if (os[j] == kMem && v < 0) continue;  // dropped
        bool f;
        uint64_t slot = shard_find(s, ok[j], &f);
        s->hkeys[slot] = ok[j];
        s->hval[slot] = v;
        s->hstate[slot] = os[j];
        s->n_used++;
      }
    }
    std::lock_guard<std::mutex> g(dm);
    dropped += local;
  };
  if (nt == 1) {
    work(0);
  } else {
    std::vector<std::thread> th;
    for (int w = 0; w < nt; ++w) th.emplace_back(work, w);
    for (auto& x : th) x.join();
  }
  return dropped;
}

// Spill cold mem rows to the shard disk files until total mem rows <=
// max_mem_rows, with the touched bit preserved in the on-disk record so
// delta saves stay exact. Victim selection by policy: kSpillFifo keeps the
// legacy creation-order sweep (untouched rows first); kSpillFreq ranks by
// coldness — admission-threshold rows disk-first, then lowest decayed
// show / oldest last-touched epoch, with rows at/above pin_show spilled
// only when no colder victim remains, and the sweep apportioned across
// shards in proportion to occupancy. Returns rows spilled, or negative if
// spill is disabled (-1) / IO fails (-2).
int64_t pbx_table_spill_cold_ex(void* h, int64_t max_mem_rows, int policy,
                                float pin_show, float admit_show) {
  return spill_cold_impl((Table*)h, max_mem_rows, policy, pin_show,
                         admit_show);
}

// Legacy entry point: creation-order (fifo) sweep, no thresholds.
int64_t pbx_table_spill_cold(void* h, int64_t max_mem_rows) {
  return spill_cold_impl((Table*)h, max_mem_rows, kSpillFifo, 0.0f, 0.0f);
}

// Per-shard tier stats, 8 int64 slots per shard:
//   [mem_rows, disk_rows, spilled_total, promoted_total,
//    admit_spilled_total, lazy_shrunk_total, dead_records,
//    spill_file_bytes]
// `out` must hold n_shards * 8 entries. Returns n_shards.
int64_t pbx_table_tier_stats(void* h, int64_t* out) {
  Table* t = (Table*)h;
  for (int si = 0; si < t->n_shards; ++si) {
    Shard* s = &t->shards[si];
    std::lock_guard<std::mutex> g(s->mtx);
    int64_t bytes = 0;
    if (s->spill) {
      fflush(s->spill);
      off_t cur = ftello(s->spill);
      fseeko(s->spill, 0, SEEK_END);
      bytes = (int64_t)ftello(s->spill);
      fseeko(s->spill, cur, SEEK_SET);
    }
    int64_t* o = out + (int64_t)si * 8;
    o[0] = s->n_used - s->n_disk;
    o[1] = s->n_disk;
    o[2] = s->n_spilled;
    o[3] = s->n_promoted;
    o[4] = s->n_admit_spilled;
    o[5] = s->n_lazy_shrunk;
    o[6] = s->dead_disk;
    o[7] = bytes;
  }
  return t->n_shards;
}

// Force-compact every shard's spill file that holds any dead records.
// Returns live records kept across all shards, or negative on IO error.
int64_t pbx_table_compact_spill(void* h) {
  Table* t = (Table*)h;
  if (t->spill_dir.empty()) return -1;
  int64_t live = 0;
  for (int si = 0; si < t->n_shards; ++si) {
    Shard* s = &t->shards[si];
    std::lock_guard<std::mutex> g(s->mtx);
    if (!s->spill || s->dead_disk == 0) {
      live += s->n_disk;
      continue;
    }
    int64_t r = compact_spill(t, s);
    if (r < 0) return r;
    live += r;
  }
  return live;
}

// Spill-tier occupancy: live records, dead (reclaimable) records, and the
// total on-disk bytes across shard files.
void pbx_table_spill_stats(void* h, int64_t* live, int64_t* dead,
                           int64_t* bytes) {
  Table* t = (Table*)h;
  int64_t l = 0, d = 0, b = 0;
  for (int si = 0; si < t->n_shards; ++si) {
    Shard* s = &t->shards[si];
    std::lock_guard<std::mutex> g(s->mtx);
    l += s->n_disk;
    d += s->dead_disk;
    if (s->spill) {
      fflush(s->spill);
      off_t cur = ftello(s->spill);
      fseeko(s->spill, 0, SEEK_END);
      b += (int64_t)ftello(s->spill);
      fseeko(s->spill, cur, SEEK_SET);
    }
  }
  *live = l;
  *dead = d;
  *bytes = b;
}

// Export only the SHOW column of one shard (cache-threshold scans): at
// most `cap` floats are written (the caller sized the buffer from
// snapshot_count; a concurrent push between the two calls must clamp, not
// overrun). Disk rows get catch-up decay. Returns floats written, or
// negative on IO error.
int64_t pbx_table_shard_shows(void* h, int shard, float* out, int64_t cap) {
  Table* t = (Table*)h;
  Shard* s = &t->shards[shard];
  std::lock_guard<std::mutex> g(s->mtx);
  int64_t n = 0;
  for (int64_t r = 0; r < s->n_rows && n < cap; ++r)
    out[n++] = s->values[r * t->width + t->show_col];
  if (s->n_disk > 0 && s->spill) {
    // batched sequential read: visit records in file-offset order (the
    // caller only wants the show distribution, so order is free) instead
    // of a random seek per hash slot — at scale the cache_threshold scan
    // was dominating pass-end time
    std::vector<int64_t> offs;
    offs.reserve((size_t)s->n_disk);
    for (uint64_t j = 0; j <= s->mask && s->mask; ++j)
      if (s->hstate[j] == kDisk) offs.push_back(s->hval[j]);
    std::sort(offs.begin(), offs.end());
    SpillRec rec;
    float show;
    for (int64_t off : offs) {
      if (n >= cap) break;
      fseeko(s->spill, off, SEEK_SET);
      if (fread(&rec, sizeof(rec), 1, s->spill) != 1 ||
          fseeko(s->spill, t->show_col * (off_t)sizeof(float), SEEK_CUR) != 0 ||
          fread(&show, sizeof(float), 1, s->spill) != 1)
        return -2;
      int64_t missed = t->epoch - rec.epoch;
      if (missed > 0 && t->last_decay < 1.0f)
        for (int64_t i = 0; i < missed; ++i) show *= t->last_decay;
      out[n++] = show;
    }
    fseeko(s->spill, 0, SEEK_END);
  }
  return n;
}

// Read-only show peek for a key batch: out[i] = the decayed show of keys[i]
// if it is resident on the MEM tier, else 0 (disk rows and absent keys both
// read cold). No creation, no promotion, no touch, no decay catch-up — this
// feeds the adaptive-ICI-wire hotness bit, which must never perturb tier
// state (spill policy only evicts cold rows, so a hot key reading 0 from
// disk just rides the int8 region until its next pull — the same graceful
// degrade as hot-fraction overflow).
int pbx_table_shows_peek(void* h, const uint64_t* keys, int64_t n, float* out) {
  Table* t = (Table*)h;
  return for_shards(t, keys, n, [&](int si, const int64_t* idx, int64_t m) {
    Shard* s = &t->shards[si];
    std::lock_guard<std::mutex> g(s->mtx);
    for (int64_t q = 0; q < m; ++q) {
      int64_t i = idx[q];
      float show = 0.0f;
      if (s->mask) {  // shard_find on an empty hash would scan forever
        bool found;
        uint64_t j = shard_find(s, keys[i], &found);
        if (found && s->hstate[j] == kMem)
          show = s->values[s->hval[j] * t->width + t->show_col];
      }
      out[i] = show;
    }
    return 0;
  });
}

// Export one shard's keys (mem + disk — all live in the hash, no file
// reads). At most `cap` keys written; returns the count.
int64_t pbx_table_shard_keys(void* h, int shard, uint64_t* out, int64_t cap) {
  Table* t = (Table*)h;
  Shard* s = &t->shards[shard];
  std::lock_guard<std::mutex> g(s->mtx);
  int64_t n = 0;
  for (uint64_t j = 0; j <= s->mask && s->mask && n < cap; ++j)
    if (s->hstate[j] != kEmpty) out[n++] = s->hkeys[j];
  return n;
}

// Drop all touched flags (after a load, which arrives via push).
void pbx_table_clear_touched(void* h) {
  Table* t = (Table*)h;
  for (auto& s : t->shards) {
    std::lock_guard<std::mutex> g(s.mtx);
    for (int64_t r = 0; r < s.n_rows; ++r) s.row_touched[r] = 0;
    // disk rows: touched bits live in the file; a load never spills, so
    // n_disk_touched entries (if any) are rewritten lazily at next
    // snapshot — clear the counter's view by scanning only if needed
    if (s.n_disk_touched > 0 && s.spill) {
      for (uint64_t j = 0; j <= s.mask && s.mask; ++j) {
        if (s.hstate[j] != kDisk) continue;
        SpillRec rec;
        fseeko(s.spill, s.hval[j], SEEK_SET);
        if (fread(&rec, sizeof(rec), 1, s.spill) != 1) break;
        if (rec.touched) {
          rec.touched = 0;
          fseeko(s.spill, s.hval[j], SEEK_SET);
          fwrite(&rec, sizeof(rec), 1, s.spill);
          if (--s.n_disk_touched == 0) break;
        }
      }
      fflush(s.spill);
      fseeko(s.spill, 0, SEEK_END);
    }
  }
}

// Snapshot item count for one shard: touched rows (mem + disk) when
// only_touched, everything otherwise.
int64_t pbx_table_snapshot_count(void* h, int shard, int only_touched) {
  Table* t = (Table*)h;
  Shard* s = &t->shards[shard];
  std::lock_guard<std::mutex> g(s->mtx);
  if (only_touched) {
    int64_t n = s->n_disk_touched;
    for (int64_t r = 0; r < s->n_rows; ++r) n += s->row_touched[r] ? 1 : 0;
    return n;
  }
  return s->n_used;
}

// Fill keys_out / vals_out (caller-sized via snapshot_count with the same
// only_touched under no concurrent mutation). Disk rows are read back with
// catch-up decay applied so a base save reflects current semantics; with
// clear_touched the on-disk header's touched bit is rewritten in place.
// Returns count written, or negative on IO error.
int64_t pbx_table_snapshot(void* h, int shard, int only_touched,
                           int clear_touched, uint64_t* keys_out,
                           float* vals_out) {
  Table* t = (Table*)h;
  Shard* s = &t->shards[shard];
  std::lock_guard<std::mutex> g(s->mtx);
  int64_t n = 0;
  for (int64_t r = 0; r < s->n_rows; ++r) {
    if (only_touched && !s->row_touched[r]) continue;
    keys_out[n] = s->row_key[r];
    std::memcpy(vals_out + n * t->width, &s->values[r * t->width],
                sizeof(float) * t->width);
    n++;
    if (clear_touched) s->row_touched[r] = 0;
  }
  bool scan_disk =
      s->spill && (only_touched ? s->n_disk_touched > 0 : s->n_disk > 0);
  if (scan_disk) {
    // offset-ordered scan (sequential IO, same trick as batched promote);
    // disk rows land in the snapshot in file order, which no caller
    // depends on — loads replay records through push, order-insensitive
    std::vector<std::pair<int64_t, uint64_t>> drecs;  // (offset, hash slot)
    for (uint64_t j = 0; j <= s->mask && s->mask; ++j)
      if (s->hstate[j] == kDisk) drecs.push_back({s->hval[j], j});
    std::sort(drecs.begin(), drecs.end());
    std::vector<float> buf(t->width);
    for (auto& dr : drecs) {
      SpillRec rec;
      fseeko(s->spill, dr.first, SEEK_SET);
      if (fread(&rec, sizeof(rec), 1, s->spill) != 1 ||
          fread(buf.data(), sizeof(float), t->width, s->spill) !=
              (size_t)t->width)
        return -2;
      if (only_touched && !rec.touched) continue;
      int64_t missed = t->epoch - rec.epoch;
      if (missed > 0 && t->last_decay < 1.0f) {
        // sequential multiplies: bitwise parity with the mem-tier decay
        for (int64_t i = 0; i < missed; ++i) {
          buf[t->show_col] *= t->last_decay;
          buf[t->clk_col] *= t->last_decay;
        }
      }
      keys_out[n] = s->hkeys[dr.second];
      std::memcpy(vals_out + n * t->width, buf.data(),
                  sizeof(float) * t->width);
      n++;
      if (clear_touched && rec.touched) {
        rec.touched = 0;
        fseeko(s->spill, dr.first, SEEK_SET);
        if (fwrite(&rec, sizeof(rec), 1, s->spill) != 1) return -2;
        s->n_disk_touched--;
      }
    }
    fflush(s->spill);
    fseeko(s->spill, 0, SEEK_END);
  }
  return n;
}

}  // extern "C"
