// A vocabulary's words as a read-only hash table, and the ids of a batch of
// tokens looked up in it (data/vocab.py: Vocabulary.lookup has the rule and
// the dict form this must agree with: a token's id is the LAST position of
// that word in the vocabulary, -1 where no word is the token).
//
// A batch is taken as the caller holds it, in two calls. glint_lookup_walk,
// WITH the interpreter lock held (data/vocab.py calls it through a
// ctypes.PyDLL handle), goes once over the list of str, or the list of
// sentences of str, and copies every token's UTF-8 bytes into a buffer of
// its own: no pointer into a Python object outlives the call. Then
// glint_lookup_walked, with the lock released, looks the buffer's tokens up;
// glint_lookup_walked_misses does the same and hands on the bytes of the
// tokens no word is, for a caller that hashes their n-grams (a subword
// model's sentence vectors), still with the lock released.
//
// Plain C ABI, no Python headers: the walk's handful of interpreter entry
// points are stable-ABI symbols the process already exports, handed over by
// address once at load (glint_lookup_bind). The table is built once and only
// read afterwards, so any number of threads may look up at once, and one
// call splits its tokens over threads whose outputs are disjoint.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <memory>
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

// The interpreter's entry points, in the order data/vocab.py hands them over
// (_INTERPRETER_SYMBOLS): a struct of nine pointers.
struct Interpreter {
    void* list_type;                              // &PyList_Type
    void* (*type_of)(void*);                      // PyObject_Type: a new reference
    void (*drop)(void*);                          // Py_DecRef
    intptr_t (*list_size)(void*);                 // PyList_Size
    void* (*list_item)(void*, intptr_t);          // PyList_GetItem: borrowed, nullptr past the end
    int (*is_sequence)(void*);                    // PySequence_Check
    void* (*as_list)(void*);                      // PySequence_List: a new reference
    const char* (*utf8)(void*, intptr_t*);        // PyUnicode_AsUTF8AndSize
    void (*clear_error)();                        // PyErr_Clear
};
Interpreter py{};

// One walked batch: what the lookup reads with the lock released.
struct Walk {
    std::vector<char> bytes;      // every token back to back
    std::vector<int64_t> end;     // end[i]: byte end of token i in bytes
    std::vector<int64_t> lengths; // a batch of sentences: the tokens of each
};

bool is_list(void* obj) {
    void* type = py.type_of(obj);
    py.drop(type);                // obj keeps its type alive
    return type == py.list_type;
}

// obj as a list the walk may index: obj where it is exactly a list, else a
// new list of the items of a sequence (a tuple, an array, a subclass by its
// own iterator); nullptr, with or without an error set, for anything else (a
// generator, a set: the dict's route says what they are worth). `made` says
// which.
void* listed(void* obj, bool* made) {
    *made = !is_list(obj);
    if (!*made) return obj;
    return py.is_sequence(obj) ? py.as_list(obj) : nullptr;
}

// room for n tokens more: a batch grown from nothing moves its bytes at every
// doubling, which costs as much again as the walk (~10 ns a token)
void reserve_tokens(Walk& w, int64_t n) {
    w.end.reserve(w.end.size() + size_t(n));
    w.bytes.reserve(w.bytes.size() + size_t(n) * 8);
}

// the str items of list appended to w: their count, or -1 with an error set at
// an item that is no str or does not encode (a lone surrogate)
int64_t take_tokens(void* list, Walk& w) {
    const intptr_t n = py.list_size(list);
    for (intptr_t i = 0; i < n; ++i) {
        void* item = py.list_item(list, i);
        intptr_t len = 0;
        const char* p = item ? py.utf8(item, &len) : nullptr;
        if (!p) return -1;
        w.bytes.insert(w.bytes.end(), p, p + len);
        w.end.push_back(int64_t(w.bytes.size()));
    }
    return n;
}

// seq's tokens into w: a sequence of str where n_sentences < 0, else a sequence
// of n_sentences sequences of str. False, with or without an error set, where
// it is anything else.
bool take_batch(void* seq, int64_t n_sentences, Walk& w) {
    bool made = false;
    void* outer = listed(seq, &made);
    if (!outer) return false;
    bool ok = true;
    if (n_sentences < 0) {
        reserve_tokens(w, py.list_size(outer));
        ok = take_tokens(outer, w) >= 0;
    } else {
        ok = py.list_size(outer) == n_sentences;
        int64_t in_lists = 0;
        for (int64_t s = 0; ok && s < n_sentences; ++s) {
            void* sentence = py.list_item(outer, intptr_t(s));
            if (sentence && is_list(sentence)) in_lists += py.list_size(sentence);
        }
        reserve_tokens(w, in_lists);
        w.lengths.reserve(size_t(n_sentences));
        for (int64_t s = 0; ok && s < n_sentences; ++s) {
            // making a list of a sentence may run the caller's code: the
            // outer list is asked anew for every sentence
            void* sentence = py.list_item(outer, intptr_t(s));
            bool inner_made = false;
            void* inner = sentence ? listed(sentence, &inner_made) : nullptr;
            const int64_t taken = inner ? take_tokens(inner, w) : -1;
            if (inner && inner_made) py.drop(inner);
            w.lengths.push_back(taken);
            ok = taken >= 0;
        }
    }
    if (made) py.drop(outer);
    return ok;
}

// tokens [lo, hi) of a walked batch
void lookup_range(const Table* t, const Walk* w, int64_t lo, int64_t hi, int32_t* out) {
    const char* bytes = w->bytes.data();
    int64_t at = lo ? w->end[lo - 1] : 0;
    for (int64_t i = lo; i < hi; ++i) {
        out[i] = find(*t, bytes + at, w->end[i] - at);
        at = w->end[i];
    }
}

// every token of a walked batch, split over at most n_threads threads
void lookup_all(const Table* t, const Walk* w, int32_t* out, int32_t n_threads) {
    const int64_t n = int64_t(w->end.size());
    n_threads = int32_t(std::max<int64_t>(1, std::min<int64_t>(n_threads, n / 32768 + 1)));
    if (n_threads == 1) {
        lookup_range(t, w, 0, n, out);
        return;
    }
    std::vector<std::thread> threads;
    for (int32_t k = 0; k < n_threads; ++k)
        threads.emplace_back(lookup_range, t, w, n * k / n_threads,
                             n * (k + 1) / n_threads, out);
    for (auto& th : threads) th.join();
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

// symbols: the Interpreter's pointers in its order. Returns whether n was
// their number (nothing is bound otherwise).
int32_t glint_lookup_bind(const void* const* symbols, int32_t n) {
    if (size_t(n) * sizeof(void*) != sizeof py) return 0;
    std::memcpy(&py, symbols, sizeof py);
    return 1;
}

// CALLED WITH THE INTERPRETER LOCK HELD. seq: a sequence of str where
// n_sentences < 0, else a sequence of n_sentences sequences of str. Returns
// the walked batch, for glint_lookup_walked to look up and free, and its
// tokens in *n_tokens; or nullptr, any error cleared, where the batch is not
// this file's to answer (an item that is no str or does not encode, no
// sequence, another number of sentences): the caller's dict answers.
void* glint_lookup_walk(void* seq, int64_t n_sentences, int64_t* n_tokens) {
    std::unique_ptr<Walk> w(new Walk);
    if (!take_batch(seq, n_sentences, *w)) {
        py.clear_error();
        return nullptr;
    }
    *n_tokens = int64_t(w->end.size());
    return w.release();
}

// out[i]: the id of token i of the walked batch, which is freed (a null table
// only frees it). With counts, one for every sentence the batch was walked
// as: the ids under 0 are then dropped from out, the rest moved up in their
// order, and counts[s] is what sentence s keeps. Returns the ids left in out.
int64_t glint_lookup_walked(const void* table, void* walk, int32_t* out, int32_t* counts,
                            int32_t n_threads) {
    const std::unique_ptr<Walk> w(static_cast<Walk*>(walk));
    const Table* t = static_cast<const Table*>(table);
    if (!t) return 0;
    lookup_all(t, w.get(), out, n_threads);
    if (!counts) return int64_t(w->end.size());
    int64_t kept = 0, at = 0;
    for (size_t s = 0; s < w->lengths.size(); ++s) {
        const int64_t before = kept;
        for (const int64_t stop = at + w->lengths[s]; at < stop; ++at)
            if (out[at] >= 0) out[kept++] = out[at];
        counts[s] = int32_t(kept - before);
    }
    return kept;
}

// The bytes a walked batch holds: the room glint_lookup_walked_misses needs
// in miss_bytes at most.
int64_t glint_lookup_walk_bytes(const void* walk) {
    return int64_t(static_cast<const Walk*>(walk)->bytes.size());
}

// glint_lookup_walked over a batch walked as sentences, the tokens under 0
// handed on and not only dropped: miss_counts[s] of them in sentence s, their
// bytes back to back in miss_bytes in the order sent (room:
// glint_lookup_walk_bytes) and miss_end[i] the byte end of the i-th (room: a
// token each). Returns the ids left in out; *n_missed the tokens handed on.
int64_t glint_lookup_walked_misses(const void* table, void* walk, int32_t* out,
                                   int32_t* counts, int32_t* miss_counts,
                                   uint8_t* miss_bytes, int64_t* miss_end,
                                   int64_t* n_missed, int32_t n_threads) {
    const std::unique_ptr<Walk> w(static_cast<Walk*>(walk));
    lookup_all(static_cast<const Table*>(table), w.get(), out, n_threads);
    const char* bytes = w->bytes.data();
    int64_t kept = 0, missed = 0, at = 0, byte_at = 0, miss_at = 0;
    for (size_t s = 0; s < w->lengths.size(); ++s) {
        const int64_t before = kept, missed_before = missed;
        for (const int64_t stop = at + w->lengths[s]; at < stop; ++at) {
            const int64_t byte_end = w->end[at];
            if (out[at] >= 0) {
                out[kept++] = out[at];
            } else {
                std::memcpy(miss_bytes + miss_at, bytes + byte_at, size_t(byte_end - byte_at));
                miss_at += byte_end - byte_at;
                miss_end[missed++] = miss_at;
            }
            byte_at = byte_end;
        }
        counts[s] = int32_t(kept - before);
        miss_counts[s] = int32_t(missed - missed_before);
    }
    *n_missed = missed;
    return kept;
}

int32_t glint_lookup_abi_version() { return 3; }

}  // extern "C"
