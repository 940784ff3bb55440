// Hashed character n-grams of a vocabulary (data/subword.py has the rule and
// the bit-identical NumPy form): for every word, written "<w>" in one byte
// buffer, the bucket row of every substring of min_n..max_n characters, by
// start then by length, into the word's slots of a table the caller laid out.
// And the same hashes of a batch of loose strings (a slide's tokens that no
// word of the vocabulary is: glint_subword_hash_strings), as one flat list.
//
// Plain C ABI, no Python headers; threads split the words, whose outputs are
// disjoint.

#include <algorithm>
#include <cstdint>
#include <thread>
#include <vector>

namespace {

inline bool is_start(uint8_t b) { return (b & 0xC0) != 0x80; }

// the n-grams of one marked word, buf[s:e], written from out on; where out ends
int32_t* hash_marked(const uint8_t* buf, int64_t s, int64_t e, int32_t min_n,
                     int32_t max_n, uint32_t buckets, int32_t row0, int32_t* out) {
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
    return out;
}

void fill_range(const uint8_t* buf, const int64_t* wend, int64_t lo, int64_t hi,
                int32_t min_n, int32_t max_n, uint32_t buckets, int32_t row0,
                const int64_t* slot0, int32_t* flat) {
    for (int64_t w = lo; w < hi; ++w)
        hash_marked(buf, w ? wend[w - 1] : 0, wend[w], min_n, max_n, buckets, row0,
                    flat + slot0[w]);
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

// bytes: n_strings strings back to back, NOT marked ("<" and ">" are put round
// each here); end[i]: byte end of string i. ids: the BUCKET (row0 is 0) of
// every n-gram of every string, string after string, by start then by length;
// counts[i]: how many string i has. Room in ids: an n-gram of every length
// from every byte of every marked string, (max_n - min_n + 1) * (bytes + 2 *
// n_strings), is never passed. Returns the ids written. One thread: a slide's
// ~16,000 strings are a millisecond or two.
int64_t glint_subword_hash_strings(const uint8_t* bytes, const int64_t* end,
                                   int64_t n_strings, int32_t min_n, int32_t max_n,
                                   uint32_t buckets, int32_t* ids, int32_t* counts) {
    std::vector<uint8_t> marked;
    int32_t* out = ids;
    for (int64_t i = 0; i < n_strings; ++i) {
        const int64_t s = i ? end[i - 1] : 0;
        marked.assign(1, uint8_t('<'));
        marked.insert(marked.end(), bytes + s, bytes + end[i]);
        marked.push_back(uint8_t('>'));
        int32_t* const before = out;
        out = hash_marked(marked.data(), 0, int64_t(marked.size()), min_n, max_n, buckets,
                          0, out);
        counts[i] = int32_t(out - before);
    }
    return int64_t(out - ids);
}

int32_t glint_subword_abi_version() { return 2; }

}  // extern "C"
