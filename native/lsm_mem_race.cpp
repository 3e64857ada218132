// A memtable mirror's concurrency contract (lsm_get.cpp's header) under a
// sanitizer: ONE inserting thread beside four probing ones that take no
// lock, the table growing from 1,024 slots under them. Built and run by
// tests/test_property_lsm_native.py with -fsanitize=thread and with
// -fsanitize=address,undefined; any report, or a value that is torn,
// missing or older than acknowledged, fails it.
//
//   g++ -std=c++17 -O1 -g -fsanitize=thread -o race native/lsm_mem_race.cpp
//   ./race [seconds]
//
// Every key's value is `kValLen` bytes that all hold its version's low
// byte: a copy that mixes two records shows. `acked[k]` is the version the
// last lsm_mem_put of key k returned from (the bucket's lock in Python: a
// get that starts after a put returned must see that put or a later one).
// Odd keys are deleted and put again, so tombstones are probed too.

#include "lsm_get.cpp"

#include <chrono>
#include <cstdio>
#include <thread>

namespace {

constexpr int kKeys = 6000;   // over half of 1,024, 2,048, 4,096, 8,192 slots
constexpr int kBatch = 512;   // keys a probing call asks
constexpr int kValLen = 3300;
constexpr int kReaders = 4;
// 130 MB of records: nothing is freed before close
constexpr int64_t kMaxPuts = 40000;
// in acked: the key's last acknowledged word is a delete
constexpr uint32_t kGone = 0x80000000u;

std::atomic<uint32_t> acked[kKeys];
std::atomic<bool> stop{false};
std::atomic<int> failures{0};

void key_of(int k, uint8_t* out) {   // 16 bytes, as a uuid
    std::memset(out, 0, 16);
    std::memcpy(out, &k, sizeof k);
    out[15] = static_cast<uint8_t>(k * 31);
}

void fail(const char* what, int k, uint32_t got, uint32_t floor) {
    std::fprintf(stderr, "FAIL %s key=%d got=%u floor=%u\n", what, k, got,
                 floor);
    failures++;
    stop = true;
}

void reader(void* mem, unsigned seed) {
    std::vector<uint8_t> keys(kBatch * 16), out(kBatch * kValLen);
    std::vector<int64_t> key_offs(kBatch + 1), out_offs(kBatch + 1);
    std::vector<const uint8_t*> srcs(kBatch);
    std::vector<int8_t> flags(kBatch);
    std::vector<int> ks(kBatch);
    std::vector<uint32_t> floor(kBatch);
    int64_t stats[3];
    for (int i = 0; i <= kBatch; i++) key_offs[i] = 16 * i;
    while (!stop) {
        for (int i = 0; i < kBatch; i++) {
            seed = seed * 1664525u + 1013904223u;
            ks[i] = static_cast<int>((seed >> 8) % kKeys);
            key_of(ks[i], &keys[16 * i]);
            floor[i] = acked[ks[i]].load(std::memory_order_acquire);
        }
        const int64_t need = lsm_multi_get(
            nullptr, 0, mem, keys.data(), key_offs.data(), kBatch,
            srcs.data(), out_offs.data(), flags.data(), stats, out.data(),
            static_cast<int64_t>(out.size()));
        if (need < 0 || need > static_cast<int64_t>(out.size()))
            return fail("need", -1, static_cast<uint32_t>(need), 0);
        for (int i = 0; i < kBatch && !stop; i++) {
            const int64_t len = out_offs[i + 1] - out_offs[i];
            const uint32_t f = floor[i];
            if (!flags[i]) {
                // never put yet, or an odd key between its delete and its
                // next put; a key whose acknowledged word is a value is there
                if (len != 0) fail("miss with bytes", ks[i], 0, f);
                if (f != 0 && !(f & kGone) && ks[i] % 2 == 0)
                    fail("missing", ks[i], 0, f);
                continue;
            }
            if (len != kValLen) {
                fail("length", ks[i], static_cast<uint32_t>(len), f);
                continue;
            }
            const uint8_t* v = &out[out_offs[i]];
            for (int j = 1; j < kValLen; j++)
                if (v[j] != v[0]) {
                    fail("torn", ks[i], v[j], v[0]);
                    break;
                }
            // versions only rise: compared modulo 256 over a span the
            // writer cannot lap inside one call
            if (static_cast<uint8_t>(v[0] - static_cast<uint8_t>(f)) > 127)
                fail("stale", ks[i], v[0], f);
        }
    }
}

void writer(void* mem, double seconds) {
    std::vector<uint8_t> val(kValLen);
    uint8_t key[16];
    unsigned seed = 12345;
    const auto end = std::chrono::steady_clock::now() +
                     std::chrono::duration<double>(seconds);
    int64_t puts = 0;
    int reach = 64;   // the keys in play: grows, so that the table does
    while (!stop && puts < kMaxPuts &&
           std::chrono::steady_clock::now() < end) {
        seed = seed * 1664525u + 1013904223u;
        const int k = static_cast<int>((seed >> 8) % reach);
        if (reach < kKeys && puts % 2 == 0) reach++;
        key_of(k, key);
        const uint32_t was = acked[k].load(std::memory_order_relaxed);
        const uint32_t version = (was & ~kGone) + 1;
        const bool del = k % 2 == 1 && !(was & kGone) && was != 0 &&
                         (seed >> 28) < 4;
        int64_t rc;
        if (del) {
            rc = lsm_mem_put(mem, key, 16, kTomb, kTombLen);
        } else {
            std::memset(val.data(), static_cast<int>(version & 0xff), kValLen);
            rc = lsm_mem_put(mem, key, 16, val.data(), kValLen);
        }
        if (rc < 0) return fail("put", k, 0, 0);
        acked[k].store(del ? (was | kGone) : version,
                       std::memory_order_release);
        puts++;
    }
    stop = true;
    int64_t stats[3];
    lsm_mem_stats(mem, stats);
    std::printf("puts=%lld keys=%lld held=%lld dead=%lld\n",
                static_cast<long long>(puts), static_cast<long long>(stats[0]),
                static_cast<long long>(stats[1]),
                static_cast<long long>(stats[2]));
}

}  // namespace

int main(int argc, char** argv) {
    const double seconds = argc > 1 ? std::atof(argv[1]) : 3.0;
    void* mem = lsm_mem_open();
    if (mem == nullptr) return 2;
    std::vector<std::thread> readers;
    for (int i = 0; i < kReaders; i++)
        readers.emplace_back(reader, mem, 1000u + i);
    writer(mem, seconds);
    for (auto& t : readers) t.join();
    lsm_mem_close(mem);
    if (failures) return 1;
    std::printf("ok\n");
    return 0;
}
