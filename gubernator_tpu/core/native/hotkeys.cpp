// Native space-saving table: the hot-key sketch's per-key update.
//
// utils/hotkeys.py groups an RPC's rows with numpy and hands the
// grouped columns here in ONE call; ctypes drops the interpreter lock
// for it, so the per-unique-key walk (a lookup and, for a key not in
// the table, an eviction) no longer holds that lock for milliseconds
// while the other RPC threads — one of them inside the engine lock —
// wait for it.  The Python table in utils/hotkeys.py stays as the
// reference and the fallback; tests/test_hotkeys.py holds the two to
// the same answers on every read.
//
// Same table, same answers.  What the Python tier's structures decide
// implicitly is explicit here:
//   * the victim of an eviction is the live entry with the least
//     (count, key bytes) — what the Python lazy heap of (count, key)
//     tuples always ends on — kept as an indexed binary min-heap;
//   * reads list entries in the Python dict's order (insertion order;
//     an evicting newcomer goes to the end), kept as a sequence number;
//   * a newcomer inherits the victim's count as `err`, its window
//     counters start fresh, it stores limit/duration as offered; an
//     existing entry takes them only when limit is non-zero.
// Rates are computed in Python from the integers returned here, so no
// floating-point result depends on this compiler.
//
// Own mutex: callers are the RPC threads, the ledger's settle path and
// the scrape/debug readers, none of which share another lock.
//
// C ABI only (consumed via ctypes; no pybind11 in this image).

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <string>
#include <vector>

namespace {

constexpr uint64_t kFnvOffset = 0xCBF29CE484222325ull;
constexpr uint64_t kFnvPrime = 0x100000001B3ull;

inline uint64_t hash_bytes(const uint8_t* data, int64_t len) {
  uint64_t h = kFnvOffset;
  for (int64_t i = 0; i < len; ++i) h = (h ^ data[i]) * kFnvPrime;
  return h ^ (h >> 29);  // fold the well-mixed high bits into the mask
}

// Field order of a snapshot row — utils/hotkeys.py's _items layout.
constexpr int kFields = 7;

struct Entry {
  std::string key;  // storage reused across evictions (assign())
  uint64_t hash = 0;
  uint64_t seq = 0;  // insertion order == the Python dict's order
  int64_t count = 0, err = 0, wid = 0, win = 0, prev = 0;
  int64_t limit = 0, dur = 0;
  int32_t heap_pos = 0;
};

struct Sketch {
  std::mutex mu;
  int64_t capacity = 1;  // guberlint: guarded-by mu
  int64_t offered = 0;  // guberlint: guarded-by mu
  int64_t key_bytes = 0;  // guberlint: guarded-by mu
  uint64_t next_seq = 0;  // guberlint: guarded-by mu
  std::vector<Entry> entries;  // guberlint: guarded-by mu
  // Min-heap of entry indices ordered by (count, key bytes).
  std::vector<int32_t> heap;  // guberlint: guarded-by mu
  // Open-addressing index (linear probing, backward-shift delete):
  // bucket -> entry index, -1 empty.  Kept at most half full.
  std::vector<int32_t> buckets;  // guberlint: guarded-by mu
  uint64_t mask = 0;  // guberlint: guarded-by mu

  explicit Sketch(int64_t cap) {
    if (cap > 1) capacity = cap;
    rebuild_index_locked(16);
  }

  // -- index ---------------------------------------------------------

  void rebuild_index_locked(uint64_t n) {
    while (n < (entries.size() + 1) * 2) n <<= 1;
    buckets.assign(n, -1);
    mask = n - 1;
    for (size_t i = 0; i < entries.size(); ++i) {
      index_insert_locked(static_cast<int32_t>(i));
    }
  }

  void index_insert_locked(int32_t idx) {
    uint64_t b = entries[idx].hash & mask;
    while (buckets[b] >= 0) b = (b + 1) & mask;
    buckets[b] = idx;
  }

  int32_t find_locked(uint64_t h, const uint8_t* p, int64_t len) const {
    for (uint64_t b = h & mask; buckets[b] >= 0; b = (b + 1) & mask) {
      const Entry& e = entries[buckets[b]];
      if (e.hash == h && static_cast<int64_t>(e.key.size()) == len &&
          std::memcmp(e.key.data(), p, static_cast<size_t>(len)) == 0) {
        return buckets[b];
      }
    }
    return -1;
  }

  void index_erase_locked(int32_t idx) {
    uint64_t hole = entries[idx].hash & mask;
    while (buckets[hole] != idx) hole = (hole + 1) & mask;
    // Backward shift: pull later members of the probe run into the
    // hole unless their home bucket lies cyclically after it.
    for (uint64_t b = (hole + 1) & mask; buckets[b] >= 0;
         b = (b + 1) & mask) {
      uint64_t home = entries[buckets[b]].hash & mask;
      bool stays = hole <= b ? (hole < home && home <= b)
                             : (hole < home || home <= b);
      if (stays) continue;
      buckets[hole] = buckets[b];
      hole = b;
    }
    buckets[hole] = -1;
  }

  // -- heap ----------------------------------------------------------

  bool less_locked(int32_t a, int32_t b) const {
    const Entry& x = entries[a];
    const Entry& y = entries[b];
    if (x.count != y.count) return x.count < y.count;
    // Python bytes order: unsigned lexicographic, a prefix sorts first.
    size_t n = std::min(x.key.size(), y.key.size());
    int c = std::memcmp(x.key.data(), y.key.data(), n);
    if (c != 0) return c < 0;
    return x.key.size() < y.key.size();
  }

  void heap_place_locked(size_t pos, int32_t idx) {
    heap[pos] = idx;
    entries[idx].heap_pos = static_cast<int32_t>(pos);
  }

  void sift_up_locked(size_t pos) {
    int32_t idx = heap[pos];
    while (pos > 0) {
      size_t parent = (pos - 1) / 2;
      if (!less_locked(idx, heap[parent])) break;
      heap_place_locked(pos, heap[parent]);
      pos = parent;
    }
    heap_place_locked(pos, idx);
  }

  void sift_down_locked(size_t pos) {
    int32_t idx = heap[pos];
    size_t n = heap.size();
    for (;;) {
      size_t child = 2 * pos + 1;
      if (child >= n) break;
      if (child + 1 < n && less_locked(heap[child + 1], heap[child])) {
        ++child;
      }
      if (!less_locked(heap[child], idx)) break;
      heap_place_locked(pos, heap[child]);
      pos = child;
    }
    heap_place_locked(pos, idx);
  }

  // -- the algorithm ---------------------------------------------------

  static void rotate(Entry& e, int64_t wid) {
    int64_t gap = wid - e.wid;
    if (gap == 0) return;
    e.prev = gap == 1 ? e.win : 0;
    e.win = 0;
    e.wid = wid;
  }

  void fill_new_locked(Entry& e, const uint8_t* p, int64_t len, uint64_t h,
                       int64_t count, int64_t err, int64_t n, int64_t wid,
                       int64_t lim, int64_t dur) {
    key_bytes += len - static_cast<int64_t>(e.key.size());
    e.key.assign(reinterpret_cast<const char*>(p), static_cast<size_t>(len));
    e.hash = h;
    e.seq = next_seq++;
    e.count = count;
    e.err = err;
    e.wid = wid;
    e.win = n;
    e.prev = 0;
    e.limit = lim;
    e.dur = dur;
  }

  void offer_locked(const uint8_t* p, int64_t len, int64_t n, int64_t wid,
                    int64_t lim, int64_t dur) {
    offered += n;
    uint64_t h = hash_bytes(p, len);
    int32_t idx = find_locked(h, p, len);
    if (idx >= 0) {
      Entry& e = entries[idx];
      e.count += n;
      rotate(e, wid);
      e.win += n;
      if (lim != 0) {
        e.limit = lim;
        e.dur = dur;
      }
      size_t pos = static_cast<size_t>(e.heap_pos);
      if (n >= 0) sift_down_locked(pos); else sift_up_locked(pos);
      return;
    }
    if (static_cast<int64_t>(entries.size()) < capacity) {
      idx = static_cast<int32_t>(entries.size());
      entries.emplace_back();
      fill_new_locked(entries[idx], p, len, h, n, 0, n, wid, lim, dur);
      if ((entries.size() + 1) * 2 > buckets.size()) {
        rebuild_index_locked(buckets.size() * 2);
      } else {
        index_insert_locked(idx);
      }
      heap.push_back(idx);
      sift_up_locked(heap.size() - 1);
      return;
    }
    // Evict the minimum counter; the newcomer inherits its count as
    // the over-estimate bound (Metwally et al. 2005).
    idx = heap[0];
    int64_t min_count = entries[idx].count;
    index_erase_locked(idx);
    fill_new_locked(entries[idx], p, len, h, min_count + n, min_count, n,
                    wid, lim, dur);
    index_insert_locked(idx);
    // min_count + n may sort either side of the children (n <= 0 is
    // legal), and at the root only downward movement exists.
    sift_down_locked(0);
  }
};

inline Sketch* S(void* h) { return static_cast<Sketch*>(h); }

}  // namespace

extern "C" {

void* hk_new(int64_t capacity) { return new Sketch(capacity); }

void hk_free(void* h) { delete S(h); }

void hk_set_capacity(void* h, int64_t capacity) {
  Sketch* s = S(h);
  std::lock_guard<std::mutex> g(s->mu);
  // Shrinking evicts nothing by itself (the Python tier's rule): every
  // newcomer then replaces one entry until a reader notices.
  s->capacity = capacity < 1 ? 1 : capacity;
}

// One key.  The caller computed `wid` from its clock.
// guberlint: gil-free
void hk_offer(void* h, const uint8_t* key, int64_t len, int64_t n,
              int64_t wid, int64_t lim, int64_t dur) {
  Sketch* s = S(h);
  std::lock_guard<std::mutex> g(s->mu);
  s->offer_locked(key, len, n, wid, lim, dur);
}

// `rows` keys applied IN ORDER under one lock: key i is
// buf[starts[i] : starts[i] + lens[i]] (clamped to buf_len, like a
// Python slice), offered weight[i] with limit[i]/duration[i]
// (either column may be null: zeros).
// guberlint: gil-free
void hk_offer_batch(void* h, const uint8_t* buf, int64_t buf_len,
                    const int64_t* starts, const int64_t* lens,
                    const int64_t* weight, const int64_t* limit,
                    const int64_t* duration, int64_t rows, int64_t wid) {
  Sketch* s = S(h);
  std::lock_guard<std::mutex> g(s->mu);
  for (int64_t i = 0; i < rows; ++i) {
    int64_t a = std::min(std::max<int64_t>(starts[i], 0), buf_len);
    int64_t l = std::min(std::max<int64_t>(lens[i], 0), buf_len - a);
    s->offer_locked(buf + a, l, weight[i], wid, limit ? limit[i] : 0,
                    duration ? duration[i] : 0);
  }
}

// out[4]: capacity, tracked, offered, total key bytes.
void hk_stats(void* h, int64_t* out) {
  Sketch* s = S(h);
  std::lock_guard<std::mutex> g(s->mu);
  out[0] = s->capacity;
  out[1] = static_cast<int64_t>(s->entries.size());
  out[2] = s->offered;
  out[3] = s->key_bytes;
}

// Every entry in insertion order: fields[row * 7 ..] = count, err,
// wid, win, prev, limit, duration; key bytes packed in key_buf with
// key_offsets[rows + 1].  `rotate` != 0 first shifts every entry's
// window counters to `wid` (what a rate read does).  Returns the row
// count, or -1 when either buffer is too small (the table grew since
// the caller sized them from hk_stats: size again and retry).
int64_t hk_snapshot(void* h, int32_t rotate, int64_t wid, int64_t* fields,
                    int64_t row_cap, uint8_t* key_buf, int64_t key_cap,
                    int64_t* key_offsets) {
  Sketch* s = S(h);
  std::lock_guard<std::mutex> g(s->mu);
  int64_t rows = static_cast<int64_t>(s->entries.size());
  if (rows > row_cap || s->key_bytes > key_cap) return -1;
  std::vector<int32_t> order(static_cast<size_t>(rows));
  for (int32_t i = 0; i < rows; ++i) order[i] = i;
  std::sort(order.begin(), order.end(), [s](int32_t a, int32_t b) {
    return s->entries[a].seq < s->entries[b].seq;
  });
  int64_t off = 0;
  for (int64_t r = 0; r < rows; ++r) {
    Entry& e = s->entries[order[r]];
    if (rotate) Sketch::rotate(e, wid);
    int64_t* f = fields + r * kFields;
    f[0] = e.count;
    f[1] = e.err;
    f[2] = e.wid;
    f[3] = e.win;
    f[4] = e.prev;
    f[5] = e.limit;
    f[6] = e.dur;
    key_offsets[r] = off;
    std::memcpy(key_buf + off, e.key.data(), e.key.size());
    off += static_cast<int64_t>(e.key.size());
  }
  key_offsets[rows] = off;
  return rows;
}

// One key's window counters shifted to `wid`: out[2] = prev, win.
// Returns 1 when tracked, 0 otherwise.
int32_t hk_window(void* h, const uint8_t* key, int64_t len, int64_t wid,
                  int64_t* out) {
  Sketch* s = S(h);
  std::lock_guard<std::mutex> g(s->mu);
  int32_t idx = s->find_locked(hash_bytes(key, len), key, len);
  if (idx < 0) return 0;
  Entry& e = s->entries[idx];
  Sketch::rotate(e, wid);
  out[0] = e.prev;
  out[1] = e.win;
  return 1;
}

}  // extern "C"
