// Native LSM point-get plane: mmap'd segment readers + batched multi-get.
//
// The serving hot path hydrates thousands of winners per batch with two
// point lookups each (docid -> uuid, uuid -> object image). In Python that
// is a bisect over per-segment key lists under the bucket lock WITH the GIL
// held. Here a batch is one C call with the GIL released (ctypes), so
// concurrent hydrations overlap:
//
//   lsm_multi_get  finds every key ONCE: the key is hashed once, then each
//               segment (newest first) is asked with one probe of its
//               hash table until one holds the key. It writes where each
//               value lives and the prefix sums of their lengths, and
//               then, if the caller's arena holds them all, copies them.
//   lsm_copy    the copy alone, for the caller whose arena was too small:
//               it comes back with a larger one and nothing is searched
//               again.
//
// A segment's table is built once, in lsm_seg_open: open addressing,
// linear probing, at most half full, one uint32 a slot (8 bytes a key). A
// slot holds the entry's index plus one in its low bits and the high bits
// of the key's hash above them, so a probe that meets another key's slot
// almost never touches that key's bytes; a slot whose tag matches is
// confirmed with a full length-and-bytes compare, so a collision can never
// return another key's value. What a batch costs is then the number of
// segments a key has to ask (about half of them: no bloom filter, no
// fence) and the copy; lsm_multi_get counts both probes and compares.
//
// Reference analog: the batched hydration seam of
// entities/storobj/storage_object.go:211 (ObjectsByDocID) over lsmkv's
// compiled segment readers — the same tier for the Python runtime.
//
// Segment layout (storage/lsm.py Segment):
//   "WTSG" | strategy u8 | entries... | footer | footer_off u64
//   footer: count u64, then per entry: klen u32 | key | off u64 | len u64
// STRATEGY_REPLACE (index 0) segments serve the point gets,
// STRATEGY_ROARINGSET (index 3) segments the posting walk:
//
//   lsm_posting_locate  finds ONE key in every segment of a roaring-set
//               bucket, oldest first, and writes where each layer's
//               additions live and how many ids they hold; it refuses
//               a key one of whose layers deletes from what came
//               before it, which the Python walk settles.
//   lsm_posting_copy    copies the located layers into ONE uint64 buffer
//               and says whether the joined ids ascend (they do where doc
//               ids come from the counter: then the posting needs no sort).
//   lsm_ids_gallop  the ids two ascending arrays share: the smaller
//               gallops through the larger.
//   lsm_bits_build, lsm_bits_probe  the same through a bitset of the
//               larger, made once and asked by every filter that holds it.
//
//   lsm_group_locate, lsm_group_fill  a filtered GROUP's device operands
//               from its distinct allowLists (index/tpu.py
//               search_by_vectors_multi_async): where each list lies in
//               the snapshot's docs and how many slots it holds, then,
//               once the plan is made, each gathered slot's int32 rows
//               and each scanned slot's mask bits, written in place.
//
//   roaring-set payload (storage/lsm.py _enc_roaring, storage/bitmap.py):
//     la u32 | ld u32 | additions | deletions, each
//     "WTBM" | n u64 | n ids u64 (ascending)
//
//   lsm_mem_open, lsm_mem_put, lsm_mem_close  a REPLACE bucket's memtable
//               as a layer lsm_multi_get asks BEFORE the segments: a hash
//               table over the same hash_key whose records (key, then
//               value or tombstone) are appended to chunks the handle owns
//               and never move. The mirror holds its own copy of the bytes
//               (the memtable's Python objects are not referenced), so what
//               a probe located stays readable whatever the dict does next.
//
// Concurrency contract with the Python side (storage/lsm.py Bucket):
//   - the caller snapshots the segment handle list, and the memtable
//     handle where the memtable has one, under the bucket lock and bumps
//     an in-flight counter;
//   - compaction retires (never closes) segments, and a memtable flush
//     retires (never closes) the memtable handle, while calls are in
//     flight, so every handle passed in, and every value address
//     lsm_multi_get wrote, stays valid until the caller leaves;
//   - segment handles are immutable after open — no locking needed;
//   - a memtable handle has ONE inserting thread at a time (lsm_mem_put is
//     called under the bucket lock, by put / delete, in the same hold that
//     changes the dict) beside any number of probing threads, which hold no
//     lock at all: a record is written whole before the slot that names it
//     is stored (release), a probe loads slots with acquire, an
//     overwritten key's slot is re-pointed at the newer record and the
//     older one stays where it is, a table that grows is built aside and
//     published by one pointer store while the older table stays readable
//     until lsm_mem_close. So a probe sees, key by key, a whole value that
//     was the key's newest at some instant of the call, and a get that
//     starts after a put returned sees that put. No inserter ever waits
//     for a reader: it holds the interpreter's lock. lsm_mem_race.cpp
//     runs exactly this (one inserter, four probers, a growing table)
//     under ThreadSanitizer and AddressSanitizer
//     (tests/test_property_lsm_native.py).

#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

#include <memory>
#include <new>
#include <vector>

namespace {

constexpr unsigned char kMagic[4] = {'W', 'T', 'S', 'G'};

// storage/lsm.py _TOMBSTONE = b"\x00__wt_tombstone__"
constexpr unsigned char kTomb[] = "\x00__wt_tombstone__";
constexpr int64_t kTombLen = 17;

// storage/lsm.py STRATEGIES
constexpr uint8_t kReplace = 0;
constexpr uint8_t kRoaringSet = 3;

// storage/bitmap.py _MAGIC
constexpr unsigned char kBitmapMagic[4] = {'W', 'T', 'B', 'M'};

struct Entry {
    const uint8_t* key;
    uint64_t key_len;
    uint64_t off;
    uint64_t len;
};

struct Seg {
    int fd = -1;
    uint8_t strategy = 0;  // storage/lsm.py STRATEGIES index
    const uint8_t* base = nullptr;
    size_t size = 0;
    std::vector<Entry> entries;  // footer order (sorted by key)
    // key hash -> entry: slot = tag << idx_bits | (entry index + 1), 0 = empty
    std::vector<uint32_t> table;
    uint64_t mask = 0;      // table.size() - 1 (a power of two)
    uint32_t idx_bits = 1;  // bits that hold entry index + 1
};

// murmur3's 64-bit finalizer over 8-byte words, the length mixed in first
// (so a key and the same key with a zero byte appended differ)
inline uint64_t fmix64(uint64_t x) {
    x ^= x >> 33;
    x *= 0xff51afd7ed558ccdULL;
    x ^= x >> 33;
    x *= 0xc4ceb9fe1a85ec53ULL;
    x ^= x >> 33;
    return x;
}

inline uint64_t hash_key(const uint8_t* p, uint64_t n) {
    uint64_t h = fmix64(n + 0x9e3779b97f4a7c15ULL);
    for (; n >= 8; p += 8, n -= 8) {
        uint64_t w;
        std::memcpy(&w, p, 8);
        h = fmix64(h ^ w);
    }
    if (n) {
        uint64_t w = 0;
        std::memcpy(&w, p, n);
        h = fmix64(h ^ w);
    }
    return h;
}

// the hash's high bits, as many as a slot has left above the index
inline uint32_t slot_tag(const Seg& s, uint64_t h) {
    return static_cast<uint32_t>(h >> (32 + s.idx_bits));
}

// false when the segment holds more entries than a uint32 slot can name
bool build_table(Seg& s) {
    const uint64_t count = s.entries.size();
    if (count >= (1ULL << 31)) return false;
    while ((1ULL << s.idx_bits) <= count) s.idx_bits++;
    uint64_t slots = 2;
    while (slots < 2 * count) slots <<= 1;  // at most half full
    s.mask = slots - 1;
    s.table.assign(slots, 0);
    for (uint64_t i = 0; i < count; i++) {
        const uint64_t h = hash_key(s.entries[i].key, s.entries[i].key_len);
        uint64_t at = h & s.mask;
        while (s.table[at] != 0) at = (at + 1) & s.mask;
        s.table[at] = (slot_tag(s, h) << s.idx_bits) |
                      static_cast<uint32_t>(i + 1);
    }
    return true;
}

// One probe of one segment's table -> the entry or nullptr. `compares`
// counts the slots whose tag matched and whose key bytes were read.
inline const Entry* seg_find(const Seg& s, uint64_t h, const uint8_t* key,
                             uint64_t klen, int64_t& compares) {
    const uint32_t tag = slot_tag(s, h);
    const uint32_t idx_mask = (1u << s.idx_bits) - 1;
    for (uint64_t at = h & s.mask;; at = (at + 1) & s.mask) {
        const uint32_t v = s.table[at];
        if (v == 0) return nullptr;
        if ((v >> s.idx_bits) != tag) continue;
        const Entry& e = s.entries[(v & idx_mask) - 1];
        compares++;
        if (e.key_len == klen && std::memcmp(e.key, key, klen) == 0) return &e;
    }
}

inline bool is_tombstone(const uint8_t* p, uint64_t len) {
    return len == static_cast<uint64_t>(kTombLen) &&
           std::memcmp(p, kTomb, kTombLen) == 0;
}

// -- a memtable's mirror -------------------------------------------------------

// One put, appended to a chunk and never moved: this header, the key's
// bytes, the value's bytes (none for a tombstone).
struct MemRec {
    uint64_t hash;
    uint64_t val_len;
    uint32_t key_len;
    uint32_t tomb;
    const uint8_t* key() const {
        return reinterpret_cast<const uint8_t*>(this + 1);
    }
    const uint8_t* val() const { return key() + key_len; }
};

// Open addressing, linear probing, at most half full; a slot is a record's
// address or null. Slots go from null to a record, and from a record to a
// newer record of the same key: a probe never meets a hole it must skip.
struct MemTable {
    uint64_t mask;
    std::unique_ptr<std::atomic<const MemRec*>[]> slots;
    explicit MemTable(uint64_t n)
        : mask(n - 1), slots(new std::atomic<const MemRec*>[n]) {
        for (uint64_t i = 0; i < n; i++)
            slots[i].store(nullptr, std::memory_order_relaxed);
    }
};

constexpr uint64_t kMemChunk = 1 << 20;
constexpr uint64_t kMemSlots = 1 << 10;

struct Mem {
    std::atomic<MemTable*> table{nullptr};
    // every table this handle has published, the live one last: a probe
    // that loaded an older one may still be reading it
    std::vector<std::unique_ptr<MemTable>> tables;
    std::vector<uint8_t*> chunks;
    uint64_t chunk_used = 0, chunk_cap = 0;
    uint64_t keys = 0;   // distinct keys
    int64_t held = 0;    // bytes of all chunks and tables
    int64_t dead = 0;    // bytes of the records a newer put superseded

    ~Mem() {
        for (uint8_t* c : chunks) std::free(c);
    }

    // room for a record of `need` bytes, 8-aligned; nullptr = no memory
    uint8_t* room(uint64_t need) {
        need = (need + 7) & ~7ULL;
        if (chunk_cap - chunk_used < need) {
            const uint64_t cap = need > kMemChunk ? need : kMemChunk;
            auto* c = static_cast<uint8_t*>(std::malloc(cap));
            if (c == nullptr) return nullptr;
            chunks.push_back(c);
            chunk_used = 0;
            chunk_cap = cap;
            held += static_cast<int64_t>(cap);
        }
        uint8_t* at = chunks.back() + chunk_used;
        chunk_used += need;
        return at;
    }

    // the slot that holds `key`, or the empty one where it would go
    static std::atomic<const MemRec*>& slot_of(MemTable& t, uint64_t h,
                                               const uint8_t* key,
                                               uint64_t klen) {
        for (uint64_t at = h & t.mask;; at = (at + 1) & t.mask) {
            const MemRec* r = t.slots[at].load(std::memory_order_relaxed);
            if (r == nullptr ||
                (r->hash == h && r->key_len == klen &&
                 std::memcmp(r->key(), key, klen) == 0))
                return t.slots[at];
        }
    }

    // a table of `n` slots holding what `old` holds, published
    void publish(uint64_t n, const MemTable* old) {
        auto t = std::make_unique<MemTable>(n);
        if (old != nullptr)
            for (uint64_t i = 0; i <= old->mask; i++) {
                const MemRec* r = old->slots[i].load(std::memory_order_relaxed);
                if (r != nullptr)
                    slot_of(*t, r->hash, r->key(), r->key_len)
                        .store(r, std::memory_order_relaxed);
            }
        // kept before it is published: a push_back that throws must not
        // free a table a probe can already load
        tables.push_back(std::move(t));
        held += static_cast<int64_t>(n * sizeof(const MemRec*));
        table.store(tables.back().get(), std::memory_order_release);
    }
};

// A probing thread's look-up: the key's newest record, or nullptr.
inline const MemRec* mem_find(const Mem& m, uint64_t h, const uint8_t* key,
                              uint64_t klen) {
    const MemTable* t = m.table.load(std::memory_order_acquire);
    for (uint64_t at = h & t->mask;; at = (at + 1) & t->mask) {
        const MemRec* r = t->slots[at].load(std::memory_order_acquire);
        if (r == nullptr) return nullptr;
        if (r->hash == h && r->key_len == klen &&
            std::memcmp(r->key(), key, klen) == 0)
            return r;
    }
}

// One serialized Bitmap at p (`len` bytes) -> its ids and their count, or
// false where the bytes are no bitmap.
bool bitmap_at(const uint8_t* p, uint64_t len, const uint8_t** ids,
               uint64_t* n) {
    if (len < 12 || std::memcmp(p, kBitmapMagic, 4) != 0) return false;
    std::memcpy(n, p + 4, 8);
    if (*n > (len - 12) / 8) return false;
    *ids = p + 12;
    return true;
}

}  // namespace

extern "C" {

// -> opaque handle, or nullptr on any parse/IO failure (caller falls back
// to the Python reader).
void* lsm_seg_open(const char* path) {
    int fd = ::open(path, O_RDONLY | O_CLOEXEC);
    if (fd < 0) return nullptr;
    struct stat st;
    if (::fstat(fd, &st) != 0 || st.st_size < 4 + 1 + 8 + 8) {
        ::close(fd);
        return nullptr;
    }
    void* base = ::mmap(nullptr, static_cast<size_t>(st.st_size), PROT_READ,
                        MAP_SHARED, fd, 0);
    if (base == MAP_FAILED) {
        ::close(fd);
        return nullptr;
    }
    auto* s = new Seg();
    s->fd = fd;
    s->base = static_cast<const uint8_t*>(base);
    s->size = static_cast<size_t>(st.st_size);
    // all bounds checks below are written subtraction-style against the
    // remaining byte count: `off + len > size` can WRAP for a corrupt file
    // whose offsets decode near UINT64_MAX, passing the check and crashing
    // the process — the contract here is nullptr-and-fallback, never a crash
    const uint8_t* p = s->base;
    const uint64_t size = s->size;
    bool ok = std::memcmp(p, kMagic, 4) == 0 &&
              (p[4] == kReplace || p[4] == kRoaringSet);
    s->strategy = p[4];
    if (ok) {
        uint64_t footer_off;
        std::memcpy(&footer_off, p + size - 8, 8);
        ok = footer_off <= size - 8 && size - 8 - footer_off >= 8;
        if (ok) {
            uint64_t count;
            std::memcpy(&count, p + footer_off, 8);
            uint64_t off = footer_off + 8;
            ok = count <= (size - off) / (4 + 16);  // min bytes per entry
            if (ok) s->entries.reserve(count);
            for (uint64_t i = 0; i < count && ok; i++) {
                if (size - off < 4) { ok = false; break; }
                uint32_t klen;
                std::memcpy(&klen, p + off, 4);
                off += 4;
                if (size - off < klen || size - off - klen < 16) { ok = false; break; }
                Entry e;
                e.key = p + off;
                e.key_len = klen;
                off += klen;
                std::memcpy(&e.off, p + off, 8);
                std::memcpy(&e.len, p + off + 8, 8);
                off += 16;
                if (e.off > size || size - e.off < e.len) { ok = false; break; }
                s->entries.push_back(e);
            }
        }
    }
    ok = ok && build_table(*s);
    if (!ok) {
        ::munmap(const_cast<uint8_t*>(s->base), s->size);
        ::close(s->fd);
        delete s;
        return nullptr;
    }
    return s;
}

void lsm_seg_close(void* h) {
    if (h == nullptr) return;
    auto* s = static_cast<Seg*>(h);
    ::munmap(const_cast<uint8_t*>(s->base), s->size);
    ::close(s->fd);
    delete s;
}

int64_t lsm_seg_count(void* h) {
    return h ? static_cast<int64_t>(static_cast<Seg*>(h)->entries.size()) : 0;
}

// -> a memtable mirror with nothing in it, or nullptr (no memory).
void* lsm_mem_open() {
    try {
        auto m = std::make_unique<Mem>();
        m->publish(kMemSlots, nullptr);
        return m.release();
    } catch (const std::bad_alloc&) {
        return nullptr;
    }
}

void lsm_mem_close(void* h) { delete static_cast<Mem*>(h); }

// The memtable's word on `key` from now on: `val`, or, where `val` is the
// tombstone marker, "deleted". -> the bytes of the records that newer puts
// of their keys have superseded (they stay held until lsm_mem_close: the
// caller decides when a fresh mirror is the cheaper one), or -1 where
// memory ran out and the handle no longer mirrors its memtable. The ONE
// inserting thread's call (the header has the contract).
int64_t lsm_mem_put(void* h, const uint8_t* key, int64_t klen,
                    const uint8_t* val, int64_t vlen) {
    auto& m = *static_cast<Mem*>(h);
    const bool tomb = is_tombstone(val, static_cast<uint64_t>(vlen));
    if (tomb) vlen = 0;
    try {
        uint8_t* at = m.room(sizeof(MemRec) + klen + vlen);
        if (at == nullptr) return -1;
        auto* r = reinterpret_cast<MemRec*>(at);
        r->hash = hash_key(key, static_cast<uint64_t>(klen));
        r->val_len = static_cast<uint64_t>(vlen);
        r->key_len = static_cast<uint32_t>(klen);
        r->tomb = tomb;
        std::memcpy(at + sizeof(MemRec), key, klen);
        std::memcpy(at + sizeof(MemRec) + klen, val, vlen);
        MemTable* t = m.table.load(std::memory_order_relaxed);
        auto* slot = &Mem::slot_of(*t, r->hash, key, klen);
        const MemRec* was = slot->load(std::memory_order_relaxed);
        if (was != nullptr) {
            m.dead += static_cast<int64_t>(sizeof(MemRec) + was->key_len +
                                           was->val_len);
        } else {
            if (2 * (m.keys + 1) > t->mask + 1) {
                m.publish(2 * (t->mask + 1), t);
                t = m.table.load(std::memory_order_relaxed);
                slot = &Mem::slot_of(*t, r->hash, key, klen);
            }
            m.keys++;
        }
        slot->store(r, std::memory_order_release);
    } catch (const std::bad_alloc&) {
        return -1;
    }
    return m.dead;
}

// stats <- {distinct keys, bytes held (chunks and tables), dead bytes}
void lsm_mem_stats(void* h, int64_t* stats) {
    const auto& m = *static_cast<Mem*>(h);
    stats[0] = static_cast<int64_t>(m.keys);
    stats[1] = m.held;
    stats[2] = m.dead;
}

// Copy what lsm_multi_get located into `out` (at least out_offs[n_keys]
// bytes), while the segments it read are still protected by the same
// in-flight hold.
void lsm_copy(const uint8_t* const* srcs, const int64_t* out_offs,
              int64_t n_keys, uint8_t* out) {
    for (int64_t i = 0; i < n_keys; i++) {
        const int64_t len = out_offs[i + 1] - out_offs[i];
        if (len > 0) std::memcpy(out + out_offs[i], srcs[i], len);
    }
}

// Batched replace-strategy point gets over a NEWEST-FIRST segment list.
//   mem:      the memtable's mirror (lsm_mem_open), or nullptr: the layer
//             asked first. A value found there is the answer, a tombstone
//             found there is a miss, and either ends the key's search.
//   keys/key_offs: concatenated key bytes, n_keys+1 prefix offsets; a
//     zero-length key means "missing upstream" and stays missing.
//   srcs:     per key: where its value lives, in a segment's mapping or
//             in the mirror's chunks (undefined for a miss).
//   out_offs: n_keys+1 prefix sums of the found values' lengths: where
//             each value goes in the arena (equal offsets = miss or empty
//             value).
//   flags:    per key: 1 found, 0 missing (absent OR tombstoned).
//   stats:    {segment probes, key compares, keys the memtable answered}
//             of this call.
//   out/out_cap: the caller's arena. Every key is located first; the
//             values are copied only if all of them fit.
// -> total value bytes (out_offs[n_keys]). If > out_cap nothing was copied:
// the caller brings an arena that large to lsm_copy, which needs no search.
int64_t lsm_multi_get(void** segs, int64_t n_segs, void* mem,
                      const uint8_t* keys, const int64_t* key_offs,
                      int64_t n_keys, const uint8_t** srcs, int64_t* out_offs,
                      int8_t* flags, int64_t* stats, uint8_t* out,
                      int64_t out_cap) {
    int64_t total = 0, probes = 0, compares = 0, mem_keys = 0;
    const Mem* m = static_cast<const Mem*>(mem);
    // a roaring-set segment's payloads are no values: the Python reader's
    for (int64_t si = 0; si < n_segs; si++)
        if (static_cast<Seg*>(segs[si])->strategy != kReplace) return -1;
    out_offs[0] = 0;
    for (int64_t i = 0; i < n_keys; i++) {
        const uint8_t* key = keys + key_offs[i];
        const uint64_t klen = static_cast<uint64_t>(key_offs[i + 1] - key_offs[i]);
        flags[i] = 0;
        srcs[i] = nullptr;
        if (klen > 0) {
            const uint64_t h = hash_key(key, klen);
            const MemRec* r = m ? mem_find(*m, h, key, klen) : nullptr;
            if (r != nullptr) {
                mem_keys++;
                if (!r->tomb) {
                    srcs[i] = r->val();
                    total += static_cast<int64_t>(r->val_len);
                    flags[i] = 1;
                }
            }
            for (int64_t si = 0; r == nullptr && si < n_segs; si++) {
                const Seg& s = *static_cast<Seg*>(segs[si]);
                probes++;
                const Entry* ent = seg_find(s, h, key, klen, compares);
                if (ent == nullptr) continue;
                // a tombstone in a newer segment shadows older values
                if (is_tombstone(s.base + ent->off, ent->len)) break;
                srcs[i] = s.base + ent->off;
                total += static_cast<int64_t>(ent->len);
                flags[i] = 1;
                break;
            }
        }
        out_offs[i + 1] = total;
    }
    stats[0] = probes;
    stats[1] = compares;
    stats[2] = mem_keys;
    if (total <= out_cap) lsm_copy(srcs, out_offs, n_keys, out);
    return total;
}

// One key's posting over an OLDEST-FIRST list of roaring-set segments.
//   srcs/counts: per segment that holds additions for the key, in order:
//             where its ids live in the mapping, and how many.
//   stats:    {layers written to srcs/counts, segment probes}.
// -> the ids of all layers together (what lsm_posting_copy will write).
// Where the walk is not this plane's to serve, the Python walk serves the
// key: -1 a layer deletes ids from what older layers added; -2 a payload
// does not parse, or a segment is of another strategy.
int64_t lsm_posting_locate(void** segs, int64_t n_segs, const uint8_t* key,
                           int64_t klen, const uint8_t** srcs,
                           int64_t* counts, int64_t* stats) {
    const uint64_t h = hash_key(key, static_cast<uint64_t>(klen));
    int64_t total = 0, layers = 0, probes = 0, compares = 0;
    for (int64_t si = 0; si < n_segs; si++) {
        const Seg& s = *static_cast<Seg*>(segs[si]);
        if (s.strategy != kRoaringSet) return -2;
        probes++;
        const Entry* ent =
            seg_find(s, h, key, static_cast<uint64_t>(klen), compares);
        if (ent == nullptr) continue;
        const uint8_t* p = s.base + ent->off;
        if (ent->len < 8) return -2;
        uint32_t la, ld;
        std::memcpy(&la, p, 4);
        std::memcpy(&ld, p + 4, 4);
        if (ent->len - 8 < la || ent->len - 8 - la < ld) return -2;
        const uint8_t *adds, *dels;
        uint64_t n_adds, n_dels;
        if (!bitmap_at(p + 8, la, &adds, &n_adds) ||
            !bitmap_at(p + 8 + la, ld, &dels, &n_dels))
            return -2;
        if (n_dels > 0 && total > 0) return -1;
        if (n_adds == 0) continue;
        srcs[layers] = adds;
        counts[layers] = static_cast<int64_t>(n_adds);
        layers++;
        total += static_cast<int64_t>(n_adds);
    }
    stats[0] = layers;
    stats[1] = probes;
    return total;
}

// The located layers, joined in `out` (the sum of `counts` ids), under the
// in-flight hold lsm_posting_locate ran under. -> 1 where the joined ids
// ascend strictly (sorted and unique as they stand), else 0.
int64_t lsm_posting_copy(const uint8_t* const* srcs, const int64_t* counts,
                         int64_t layers, uint64_t* out) {
    int64_t at = 0, ascends = 1;
    uint64_t last = 0;
    for (int64_t i = 0; i < layers; i++) {
        const int64_t n = counts[i];
        std::memcpy(out + at, srcs[i], static_cast<size_t>(n) * 8);
        // a layer ascends by construction (a Bitmap's ids): only the seam
        // between two layers is in question
        if (at > 0 && out[at] <= last) ascends = 0;
        last = out[at + n - 1];
        at += n;
    }
    return ascends;
}

// out (holds na) <- the ids in both a and b (each ascending and unique,
// na <= nb) -> how many. The smaller gallops through the larger: doubling
// steps from the last match, then a binary search.
int64_t lsm_ids_gallop(const uint64_t* a, int64_t na, const uint64_t* b,
                       int64_t nb, uint64_t* out) {
    int64_t n = 0, lo = 0;
    for (int64_t i = 0; i < na && lo < nb; i++) {
        const uint64_t x = a[i];
        int64_t step = 1, hi = lo;
        while (hi < nb && b[hi] < x) {
            lo = hi + 1;
            hi += step;
            step <<= 1;
        }
        if (hi > nb) hi = nb;
        // first index in [lo, hi] whose id is not below x
        while (lo < hi) {
            const int64_t mid = lo + ((hi - lo) >> 1);
            if (b[mid] < x) lo = mid + 1; else hi = mid;
        }
        if (lo < nb && b[lo] == x) out[n++] = x;
    }
    return n;
}

// bits (zeroed, ((b[nb-1] - base) >> 6) + 1 words) <- one bit an id of b,
// id `base` (a multiple of 64, not above b[0]) the first: the look-up
// table of a posting that several intersections ask (doc ids come from a
// counter, so a popular posting's span is dense).
void lsm_bits_build(const uint64_t* b, int64_t nb, uint64_t base,
                    uint64_t* bits) {
    for (int64_t j = 0; j < nb; j++) {
        const uint64_t d = b[j] - base;
        bits[d >> 6] |= 1ULL << (d & 63);
    }
}

// out (holds na) <- the ids of a (ascending) whose bit is set -> how many.
int64_t lsm_bits_probe(const uint64_t* a, int64_t na, const uint64_t* bits,
                       uint64_t base, int64_t words, uint64_t* out) {
    int64_t n = 0;
    for (int64_t i = 0; i < na; i++) {
        const uint64_t x = a[i];
        if (x < base) continue;
        const uint64_t d = x - base;
        if ((d >> 6) >= static_cast<uint64_t>(words)) break;
        out[n] = x;
        n += (bits[d >> 6] >> (d & 63)) & 1;
    }
    return n;
}

// -- a filtered group's device operands ------------------------------------
//
// A snapshot's doc -> slot rule is its `docs` (slot_to_doc[:n], ascending
// strictly): slot s holds doc docs[s]. `consecutive` says docs[n-1] -
// docs[0] == n - 1 (rows put once, from the shard's counter: the served
// case), and then the slot of doc d is d - docs[0]; else a doc is looked up
// in docs, galloping on from the last one found (both sides ascend). Ids
// are the allowLists' uint64 arrays read as int64, as docs are.

}  // extern "C"

namespace {

// first index in [lo, n) of ascending `a` whose value is not below x
inline int64_t lower_bound_i64(const int64_t* a, int64_t lo, int64_t n,
                               int64_t x) {
    int64_t hi = n;
    while (lo < hi) {
        const int64_t mid = lo + ((hi - lo) >> 1);
        if (a[mid] < x) lo = mid + 1; else hi = mid;
    }
    return lo;
}

// fn(slot) for every id of ids[lo, hi) that `docs` holds, ascending
template <typename F>
inline void each_slot(const int64_t* ids, int64_t lo, int64_t hi,
                      const int64_t* docs, int64_t n, bool consecutive,
                      F&& fn) {
    if (consecutive) {
        const int64_t first = docs[0];
        for (int64_t i = lo; i < hi; i++) fn(ids[i] - first);
        return;
    }
    int64_t at = 0;
    for (int64_t i = lo; i < hi && at < n; i++) {
        const int64_t x = ids[i];
        int64_t step = 1, top = at;
        while (top < n && docs[top] < x) {
            at = top + 1;
            top += step;
            step <<= 1;
        }
        at = lower_bound_i64(docs, at, top < n ? top : n, x);
        if (at < n && docs[at] == x) fn(at);
    }
}

}  // namespace

extern "C" {

// Each list's ids that can be a doc of the snapshot, ids[l][lo[l], hi[l])
// (two binary searches a list), and how many of them are: sizes[l], its
// slots. Where docs are consecutive that is hi - lo; else the walk
// lsm_group_fill will make again. -> the sum over the lists of hi - lo.
int64_t lsm_group_locate(const int64_t* const* ids, const int64_t* lens,
                         int64_t n_lists, const int64_t* docs, int64_t n,
                         int64_t consecutive, int64_t* lo, int64_t* hi,
                         int64_t* sizes) {
    int64_t walked = 0;
    for (int64_t l = 0; l < n_lists; l++) {
        lo[l] = hi[l] = sizes[l] = 0;
        if (n == 0 || lens[l] == 0) continue;
        lo[l] = lower_bound_i64(ids[l], 0, lens[l], docs[0]);
        // docs[n-1] + 1 would overflow at the top of the range
        hi[l] = lower_bound_i64(ids[l], lo[l], lens[l], docs[n - 1]);
        if (hi[l] < lens[l] && ids[l][hi[l]] == docs[n - 1]) hi[l]++;
        walked += hi[l] - lo[l];
        if (consecutive) {
            sizes[l] = hi[l] - lo[l];
        } else {
            int64_t m = 0;
            each_slot(ids[l], lo[l], hi[l], docs, n, false,
                      [&](int64_t) { m++; });
            sizes[l] = m;
        }
    }
    return walked;
}

// The operands of a group's dispatches, written in place. A job is six
// int64: {kind, dst, aux, height, width, nsel}, and takes its next `nsel`
// entries of `sel` (indices into the lists), one a row of dst.
//   kind 0, a gather bucket: dst int32 [height, width] rows, aux the
//     address of its int32 [height] counts. Row j gets list sel[j]'s slots
//     and counts[j] their number. counts is also what the buffer's LAST
//     use left in each row: what lies between the new count and the old is
//     zeroed, so a reused buffer reads as a fresh one.
//   kind 1, the masked scan: dst uint32 [height, width] words, aux the
//     number of rows its last use dirtied. Row j gets bit s % 32 of word
//     s / 32 set for every slot s of list sel[j]; a dirty row is zeroed
//     first, whether or not this use fills it.
void lsm_group_fill(const int64_t* const* ids, const int64_t* lo,
                    const int64_t* hi, const int64_t* docs, int64_t n,
                    int64_t consecutive, const int64_t* jobs, int64_t n_jobs,
                    const int64_t* sel) {
    for (int64_t q = 0; q < n_jobs; q++, jobs += 6) {
        const int64_t height = jobs[3], width = jobs[4], nsel = jobs[5];
        if (jobs[0] == 0) {
            auto* rows = reinterpret_cast<int32_t*>(jobs[1]);
            auto* counts = reinterpret_cast<int32_t*>(jobs[2]);
            for (int64_t j = 0; j < height; j++) {
                int32_t* row = rows + j * width;
                int64_t m = 0;
                if (j < nsel) {
                    const int64_t l = sel[j];
                    each_slot(ids[l], lo[l], hi[l], docs, n, consecutive != 0,
                              [&](int64_t s) {
                                  if (m < width)
                                      row[m++] = static_cast<int32_t>(s);
                              });
                }
                if (counts[j] > m)
                    std::memset(row + m, 0,
                                static_cast<size_t>(counts[j] - m) * 4);
                counts[j] = static_cast<int32_t>(m);
            }
        } else {
            auto* words = reinterpret_cast<uint32_t*>(jobs[1]);
            const int64_t dirty = jobs[2];
            for (int64_t j = 0; j < height && (j < nsel || j < dirty); j++) {
                uint32_t* row = words + j * width;
                if (j < dirty)
                    std::memset(row, 0, static_cast<size_t>(width) * 4);
                if (j >= nsel) continue;
                const int64_t l = sel[j];
                each_slot(ids[l], lo[l], hi[l], docs, n, consecutive != 0,
                          [&](int64_t s) { row[s >> 5] |= 1u << (s & 31); });
            }
        }
        sel += nsel;
    }
}

// The table's hash of a key (tests search it for colliding keys).
uint64_t lsm_key_hash(const uint8_t* key, int64_t len) {
    return hash_key(key, static_cast<uint64_t>(len));
}

}  // extern "C"
