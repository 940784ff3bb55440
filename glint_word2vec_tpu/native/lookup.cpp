// A vocabulary's words as a read-only hash table, and the ids of a batch of
// tokens looked up in it (data/vocab.py: Vocabulary.lookup has the rule and
// the dict form this must agree with: a token's id is the LAST position of
// that word in the vocabulary, -1 where no word is the token).
//
// Plain C ABI, no Python headers; the table is built once and only read
// afterwards, so any number of threads may look up at once, and one call
// splits its tokens over threads whose outputs are disjoint.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

struct Slot {
    uint32_t tag;   // the hash's high half; compared before the bytes
    int32_t id;     // -1: empty
};

struct Table {
    std::vector<char> bytes;      // every word back to back
    std::vector<int64_t> end;     // end[w]: byte end of word w in bytes
    std::vector<Slot> slots;      // open addressing, linear probing
    uint64_t mask = 0;
};

inline uint64_t hash_bytes(const char* p, int64_t n) {
    uint64_t h = 1469598103934665603ull;            // FNV-1a, then a mix of the
    for (int64_t i = 0; i < n; ++i)                 // high bits into the low
        h = (h ^ uint8_t(p[i])) * 1099511628211ull;
    h ^= h >> 29;
    return h * 0xBF58476D1CE4E5B9ull;
}

inline bool same(const Table& t, int32_t id, const char* p, int64_t n) {
    const int64_t s = id ? t.end[id - 1] : 0;
    return t.end[id] - s == n && std::memcmp(t.bytes.data() + s, p, size_t(n)) == 0;
}

inline int32_t find(const Table& t, const char* p, int64_t n) {
    const uint64_t h = hash_bytes(p, n);
    const uint32_t tag = uint32_t(h >> 32);
    for (uint64_t i = h & t.mask;; i = (i + 1) & t.mask) {
        const Slot s = t.slots[i];
        if (s.id < 0) return -1;
        if (s.tag == tag && same(t, s.id, p, n)) return s.id;
    }
}

// tokens of buf[lo, hi): hi ends a token (a separator or the buffer's end)
void lookup_range(const Table* t, const char* buf, int64_t lo, int64_t hi,
                  char sep, int32_t* out) {
    while (lo <= hi) {
        const char* e = static_cast<const char*>(std::memchr(buf + lo, sep, size_t(hi - lo)));
        const int64_t stop = e ? e - buf : hi;
        *out++ = find(*t, buf + lo, stop - lo);
        lo = stop + 1;
    }
}

}  // namespace

extern "C" {

void* glint_lookup_build(const char* bytes, const int64_t* end, int64_t n_words) {
    Table* t = new Table;
    t->bytes.assign(bytes, bytes + (n_words ? end[n_words - 1] : 0));
    t->end.assign(end, end + n_words);
    uint64_t cap = 16;
    while (cap < uint64_t(n_words) * 2) cap <<= 1;
    t->slots.assign(cap, Slot{0, -1});
    t->mask = cap - 1;
    for (int64_t w = 0; w < n_words; ++w) {
        const int64_t s = w ? end[w - 1] : 0, n = end[w] - s;
        const uint64_t h = hash_bytes(bytes + s, n);
        const uint32_t tag = uint32_t(h >> 32);
        for (uint64_t i = h & t->mask;; i = (i + 1) & t->mask) {
            Slot& slot = t->slots[i];
            // a word the vocabulary holds twice keeps its last position
            if (slot.id < 0 || (slot.tag == tag && same(*t, slot.id, bytes + s, n))) {
                slot = Slot{tag, int32_t(w)};
                break;
            }
        }
    }
    return t;
}

void glint_lookup_free(void* table) { delete static_cast<Table*>(table); }

// buf: n_tokens tokens joined by sep; out[i]: the id of token i. Returns
// n_tokens, or -1 with nothing written where buf holds another number of
// separators than n_tokens - 1 (a token holds one: the caller's dict answers).
int64_t glint_lookup_tokens(const void* table, const char* buf, int64_t len,
                            char sep, int64_t n_tokens, int32_t* out,
                            int32_t n_threads) {
    const Table* t = static_cast<const Table*>(table);
    if (n_tokens <= 0 || std::count(buf, buf + len, sep) != n_tokens - 1) return -1;
    n_threads = int32_t(std::max<int64_t>(1, std::min<int64_t>(n_threads, n_tokens / 32768 + 1)));
    if (n_threads == 1) {
        lookup_range(t, buf, 0, len, sep, out);
        return n_tokens;
    }
    // cut the bytes into n_threads parts at separators, count each part's
    // tokens, then look the parts up side by side
    std::vector<int64_t> cut(n_threads + 1, len);
    cut[0] = -1;                                    // a part starts past its cut
    for (int32_t k = 1; k < n_threads; ++k) {
        const int64_t at = std::max(cut[k - 1] + 1, len * k / n_threads);
        const char* e = at < len
            ? static_cast<const char*>(std::memchr(buf + at, sep, size_t(len - at))) : nullptr;
        cut[k] = e ? e - buf : len;
    }
    std::vector<int64_t> first(n_threads + 1, 0);
    std::vector<std::thread> threads;
    for (int32_t k = 0; k < n_threads; ++k)
        threads.emplace_back([&, k] {
            const int64_t lo = cut[k] + 1, hi = cut[k + 1];
            first[k + 1] = lo > hi ? 0 : 1 + std::count(buf + lo, buf + hi, sep);
        });
    for (auto& th : threads) th.join();
    threads.clear();
    for (int32_t k = 0; k < n_threads; ++k) first[k + 1] += first[k];
    for (int32_t k = 0; k < n_threads; ++k)
        if (cut[k] + 1 <= cut[k + 1])
            threads.emplace_back(lookup_range, t, buf, cut[k] + 1, cut[k + 1], sep,
                                 out + first[k]);
    for (auto& th : threads) th.join();
    return n_tokens;
}

int32_t glint_lookup_abi_version() { return 1; }

}  // extern "C"
