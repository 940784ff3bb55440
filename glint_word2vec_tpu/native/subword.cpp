// Hashed character n-grams of a vocabulary (data/subword.py has the rule and
// the bit-identical NumPy form): for every word, written "<w>" in one byte
// buffer, the bucket row of every substring of min_n..max_n characters, by
// start then by length, into the word's slots of a table the caller laid out.
//
// Plain C ABI, no Python headers; threads split the words, whose outputs are
// disjoint.

#include <algorithm>
#include <cstdint>
#include <thread>
#include <vector>

namespace {

inline bool is_start(uint8_t b) { return (b & 0xC0) != 0x80; }

void fill_range(const uint8_t* buf, const int64_t* wend, int64_t lo, int64_t hi,
                int32_t min_n, int32_t max_n, uint32_t buckets, int32_t row0,
                const int64_t* slot0, int32_t* flat) {
    for (int64_t w = lo; w < hi; ++w) {
        const int64_t s = w ? wend[w - 1] : 0, e = wend[w];
        int32_t* out = flat + slot0[w];
        for (int64_t i = s; i < e; ++i) {
            if (!is_start(buf[i])) continue;
            uint32_t h = 2166136261u;
            int64_t j = i;
            for (int32_t n = 1; j < e && n <= max_n; ++n) {
                do {
                    // fastText hashes int8_t bytes: sign-extended before the xor
                    h = (h ^ uint32_t(int32_t(int8_t(buf[j])))) * 16777619u;
                    ++j;
                } while (j < e && !is_start(buf[j]));
                if (n >= min_n) *out++ = row0 + int32_t(h % buckets);
            }
        }
    }
}

}  // namespace

extern "C" {

// buf: every "<w>" back to back; wend[w]: byte end of word w in buf;
// slot0[w]: where word w's first n-gram goes in flat (its slots are sized by
// the caller from the same rule: sum over n of max(chars - n + 1, 0)).
void glint_subword_fill(const uint8_t* buf, const int64_t* wend, int64_t n_words,
                        int32_t min_n, int32_t max_n, uint32_t buckets,
                        int32_t row0, const int64_t* slot0, int32_t* flat,
                        int32_t n_threads) {
    n_threads = int32_t(std::max<int64_t>(1, std::min<int64_t>(n_threads, n_words / 4096 + 1)));
    std::vector<std::thread> threads;
    const int64_t per = (n_words + n_threads - 1) / n_threads;
    for (int32_t t = 0; t < n_threads; ++t) {
        const int64_t lo = t * per, hi = std::min<int64_t>(n_words, lo + per);
        if (lo >= hi) break;
        threads.emplace_back(fill_range, buf, wend, lo, hi, min_n, max_n, buckets,
                             row0, slot0, flat);
    }
    for (auto& th : threads) th.join();
}

int32_t glint_subword_abi_version() { return 1; }

}  // extern "C"
