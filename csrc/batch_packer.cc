// Native per-batch packer: the MiniBatchGpuPack hot loop in C++.
//
// The reference packs minibatches on pinned host memory in C++ worker
// threads (MiniBatchGpuPack::pack_instance, data_feed.h:1418-1542) and
// dedups keys on device (DedupKeysAndFillIdx, box_wrapper_impl.h:103). On
// TPU the whole resolution happens host-side once per batch: keys were
// already mapped to pass-local table rows when the pass was finalized
// (PassWorkingSet), so packing a batch is a ragged gather over the
// columnar record store + first-occurrence dedup + segment-id emission —
// one native call, no Python per-record work.
//
// Dedup uses an epoch-stamped scratch table sized by the pass row count:
// O(L) per batch, no clearing, no hashing (rows are dense pass-local ids).
//
// ABI: C, handle-based; one handle per packer thread (the scratch is the
// only mutable state). ctypes releases the GIL during calls, so packer
// threads genuinely overlap with each other and the device step.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <vector>

namespace {

struct Packer {
  // borrowed pass-scoped views (owned by numpy on the Python side; the
  // pass object must outlive the handle)
  const int32_t* rows;         // [total_keys] pass-local row per key
  const int64_t* rec_base;     // [n_records] record base into rows
  const uint32_t* rec_off;     // [n_records * (n_sparse+1)] record-local
  int n_sparse;
  int64_t n_records;
  // dedup scratch, epoch-stamped
  std::vector<int64_t> stamp;
  std::vector<int32_t> uniq_of_row;
  int64_t epoch = 0;
};

}  // namespace

extern "C" {

void* pbx_packer_create(const int32_t* rows, const int64_t* rec_base,
                        const uint32_t* rec_off, int64_t n_records,
                        int n_sparse, int64_t n_table_rows) {
  Packer* p = new Packer();
  p->rows = rows;
  p->rec_base = rec_base;
  p->rec_off = rec_off;
  p->n_sparse = n_sparse;
  p->n_records = n_records;
  p->stamp.assign((size_t)n_table_rows, -1);
  p->uniq_of_row.resize((size_t)n_table_rows);
  return (void*)p;
}

// Pack records `indices[0..B)` into slot-major arrays. Caller buffers:
// uniq_rows [>=L], inverse [>=L], segments [>=L] where L = total key count
// of the batch (caller computes it from the offsets; returns -1 if a
// record index or row is out of range). Writes the first-occurrence unique
// rows and per-key (uniq index, slot*B+ins segment); returns U, the unique
// count. No padding here — the Python wrapper buckets and pads.
int64_t pbx_pack_batch(void* h, const int64_t* indices, int64_t B,
                       int32_t* uniq_rows, int32_t* inverse,
                       int32_t* segments) {
  Packer* p = (Packer*)h;
  const int S1 = p->n_sparse + 1;
  const int64_t epoch = ++p->epoch;
  int64_t* stamp = p->stamp.data();
  int32_t* uniq_of_row = p->uniq_of_row.data();
  const int64_t n_rows = (int64_t)p->stamp.size();
  int64_t k = 0, U = 0;
  for (int s = 0; s < p->n_sparse; ++s) {
    for (int64_t i = 0; i < B; ++i) {
      const int64_t r = indices[i];
      if (r < 0 || r >= p->n_records) return -1;
      const uint32_t* off = p->rec_off + r * S1;
      const int64_t a = p->rec_base[r] + off[s];
      const int64_t b = p->rec_base[r] + off[s + 1];
      const int32_t seg = (int32_t)(s * B + i);
      for (int64_t j = a; j < b; ++j) {
        const int32_t row = p->rows[j];
        if (row < 0 || row >= n_rows) return -1;
        if (stamp[row] != epoch) {
          stamp[row] = epoch;
          uniq_of_row[row] = (int32_t)U;
          uniq_rows[U++] = row;
        }
        inverse[k] = uniq_of_row[row];
        segments[k] = seg;
        ++k;
      }
    }
  }
  return U;
}

void pbx_packer_free(void* h) { delete (Packer*)h; }

// --- pass-scoped helpers (vectorized host work that is awkward/slow in
// numpy but trivial here) ------------------------------------------------

// Ragged gather: out[i] = concat of values[base[idx]+off[idx][slot]..+1)
// for one slot over many records — used for whole-pass label extraction
// and columnar select(). Lengths must be uniform (dim) per record.
void pbx_gather_f32_slot(const float* values, const int64_t* base,
                         const uint32_t* off, int n_float_p1,
                         const int64_t* indices, int64_t n, int slot, int dim,
                         float* out) {
  for (int64_t i = 0; i < n; ++i) {
    const int64_t r = indices[i];
    const uint32_t* o = off + r * n_float_p1;
    const int64_t a = base[r] + o[slot];
    const int64_t len = (int64_t)(o[slot + 1] - o[slot]);
    const int64_t c = len < dim ? len : dim;
    for (int64_t d = 0; d < c; ++d) out[i * dim + d] = values[a + d];
    for (int64_t d = c; d < dim; ++d) out[i * dim + d] = 0.0f;
  }
}

// Pass-prepare pad sweep: per device-block (L, max unique rows per shard)
// for the resident feed's shape freeze (ensure_sharded). The reference
// equalizes pass shapes with counters + one allreduce
// (compute_thread_batch_nccl, data_set.cc:2069-2135); this is the
// counter side — one GIL-released native sweep over the whole block
// matrix replaces a per-(device, batch) Python unique/bincount loop.
//
// rows: int32 [n_keys] pass-local row per key occurrence;
// base/counts: int64 [n_records] flat key span per record (every span
// must lie inside [0, n_keys));
// indices: int64 [n_blocks * b] record ids, row-major blocks.
// Dedup is a per-block gather + sort + run walk: work scales with the
// block's key count, never with the table's row count (an epoch-stamp
// table over the row id space would memset O(n_rows) per CALL — at a
// 45M-row pass that is 365 MB of writes before any work). The scratch
// buffer reuses its high-water allocation across blocks. Returns 0, or
// -1 on an out-of-range record/row/key span.
int pbx_block_stats(const int32_t* rows, int64_t n_keys, const int64_t* base,
                    const int64_t* counts, int64_t n_records,
                    const int64_t* indices, int64_t n_blocks, int64_t b,
                    int64_t cap, int64_t ns, int64_t n_rows,
                    int64_t* L_out, int64_t* bmax_out) {
  std::vector<uint32_t> buf, tmp;
  for (int64_t blk = 0; blk < n_blocks; ++blk) {
    const int64_t* idx = indices + blk * b;
    int64_t L = 0;
    for (int64_t i = 0; i < b; ++i) {
      const int64_t r = idx[i];
      if (r < 0 || r >= n_records || counts[r] < 0 || base[r] < 0 ||
          counts[r] > n_keys - base[r])
        return -1;
      L += counts[r];
    }
    buf.resize((size_t)L);
    tmp.resize((size_t)L);
    // gather: each record's key rows are contiguous -> one memcpy per
    // record (rows are validated against n_rows during the run walk via
    // the max; negative values wrap to huge uint32 and fail the check)
    size_t w = 0;
    for (int64_t i = 0; i < b; ++i) {
      const int64_t r = idx[i];
      const int64_t c = counts[r];
      std::memcpy(buf.data() + w, rows + base[r], (size_t)c * sizeof(int32_t));
      w += (size_t)c;
    }
    // LSD radix sort, 4x8-bit passes: ~3-5x faster than comparison sort
    // at the 1e5-1e6 keys a device block carries
    uint32_t maxv = 0;
    for (size_t k = 0; k < w; ++k) maxv = buf[k] > maxv ? buf[k] : maxv;
    // compare in int64: a uint32-truncated n_rows would falsely reject
    // everything at exactly 2^32 rows (negative int32 rows arrive here
    // wrapped to huge uint32 values, so they fail this check too)
    if ((int64_t)maxv >= n_rows) return -1;
    uint32_t cnt[256];
    for (int shift = 0; shift < 32 && (maxv >> shift); shift += 8) {
      std::memset(cnt, 0, sizeof(cnt));
      for (size_t k = 0; k < w; ++k) ++cnt[(buf[k] >> shift) & 0xFF];
      uint32_t run = 0;
      for (int v = 0; v < 256; ++v) {
        const uint32_t c = cnt[v];
        cnt[v] = run;
        run += c;
      }
      for (size_t k = 0; k < w; ++k) tmp[cnt[(buf[k] >> shift) & 0xFF]++] = buf[k];
      buf.swap(tmp);
    }
    // unique runs, counted per shard (rows are shard-major: shard=row/cap)
    int64_t bmax = 0, scur = -1, c = 0;
    uint32_t prev = 0xFFFFFFFFu;
    for (size_t k = 0; k < w; ++k) {
      const uint32_t row = buf[k];
      if (row == prev) continue;
      prev = row;
      const int64_t s = (int64_t)row / cap;
      if (s >= ns) return -1;  // row beyond the [ns, cap] shard grid
      if (s != scur) {
        scur = s;
        c = 0;
      }
      if (++c > bmax) bmax = c;
    }
    L_out[blk] = L;
    bmax_out[blk] = bmax;
  }
  return 0;
}

}  // extern "C"
