"""Kind ``query_subword``: closed-loop callers of ``synonyms(string, k)`` against
a SUBWORD model's exact scan, a share of the strings unknown to the vocabulary.

As kind ``query`` (kinds/query.py, whose caller threads and closing this
imports): the model is served by ``EmbeddingService(model=..., ann=False)`` at
the program's defaults, ``callers`` threads each send the next query when the
reply arrives, the window opens once the warm-up batches have come back and
closes ``--seconds`` later, and once the service is closed and its tables freed
the plain reference (reference/subword_query_ref.py) scores a seeded sample of
the window's replies. What the subword model changes:

- The vocabulary's words are strings with characters to read
  (harness/words.py, from ``--seed``). The model is handed the three things a
  fit hands it: the words' own rows [V, D], the bucket rows (K of them, at whole
  lanes as a trainer keeps them) and syn1, all made on the device from the
  seed; it builds the composed table it scans inside its constructor (its
  seconds are the counter ``compose_s``).
- A query is, by seed, with probability ``unseen_share`` a string the
  vocabulary lacks (harness/unseen.py: a typo of a Zipf-drawn word) and else a
  Zipf-drawn word. The warm-up runs, at every batch size, the program of a
  batch of words and the program of a batch that holds such a string.
- ``correct`` compares the scores and ranks of a sample drawn half from the
  unseen strings' replies and half from the words', and three readings more:
  the program's composed rows of 1,000 seeded words against the reference's
  h(w); the unseen strings the program says it composed inside its scan while
  the callers ran against the number they sent (equal, or the counter lies);
  and the strings that took the overflow form (none).
"""

import gc
import time

import numpy as np

from harness import unseen, weights, words, zipf
from harness.common import Checks, CompileLog, Laps, TracedSlice, memory_peak_bytes
from harness.loader import sizes as sizes_of
from kinds.query import Callers, _close

SAMPLE_WORDS = 1000


def build_service(cell: dict, seed: int, tiny: bool, table_dtype=None, laps=None):
    """The served subword model and its service. ``table_dtype`` overrides the
    configuration's only for the lower-precision control."""
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.serve.service import EmbeddingService

    lap = laps.lap if laps else (lambda name: None)
    lap("imports")
    cfg = sizes_of(cell["config"], tiny)
    v, d, k = cfg["vocab_size"], cfg["vector_size"], cfg["subword_buckets"]
    half_width = cfg["table_half_width"]
    strings = words.make_words(seed, v)
    index = {w: i for i, w in enumerate(strings)}
    vocab = Vocabulary.from_words_and_counts(strings, zipf.zipf_counts(v).astype(np.int64))
    lap("vocabulary")
    dtype = jnp.dtype(table_dtype or cfg["param_dtype"])
    # one input table of V + K rows by the formula, handed over as a fit hands
    # it: the words' rows, and the bucket rows widened to whole lanes (the
    # narrow ones are dropped before the model is built)
    lanes = -(-d // 128) * 128
    buckets = jax.jit(lambda s: jnp.pad(
        weights.rows_uniform(s, 0, v + jax.lax.iota(jnp.int32, k), d, d, half_width, dtype),
        ((0, 0), (0, lanes - d))))(weights.seed32(seed))
    raw0 = weights.make_table(seed, 0, v, d, d, half_width, dtype)
    syn1 = weights.make_table(seed, 1, v, d, d, half_width, dtype)
    syn1.block_until_ready()
    lap("tables on device")
    wcfg = Word2VecConfig(
        vector_size=d, min_count=1, subword=True, subword_min_n=cfg["subword_min_n"],
        subword_max_n=cfg["subword_max_n"], subword_buckets=k)
    model = Word2VecModel(vocab, raw0, syn1, config=wcfg, subword_buckets=buckets)
    del raw0, syn1, buckets
    lap("Word2VecModel()")
    sizes = dict(v=v, d=d, k=k, half_width=half_width, strings=strings, index=index,
                 ngram=(cfg["subword_min_n"], cfg["subword_max_n"]))
    return EmbeddingService(model=model, ann=False), model, sizes


class StringCallers(Callers):
    """``Callers`` whose queries are strings: caller i sends ``mine[0]`` in
    turn, and every finished query is kept as (sent, received, (string, word id
    or -1), reply or exception)."""

    def _loop(self, service, i, k, mine):
        import jax.profiler as jp
        strings, ids = mine
        out, at = self.done[i], 0
        while not self._stop.is_set():
            j = at % len(strings)
            at += 1
            sent = time.perf_counter()
            try:
                with jp.TraceAnnotation("bench.synonyms"):
                    reply = service.synonyms(strings[j], k)
            except Exception as e:  # noqa: BLE001 — counted as failed
                reply = e
            out.append((sent, time.perf_counter(), (strings[j], int(ids[j])), reply))


def serve_window(service, model, tf: dict, seed: int, sizes: dict, seconds: float,
                 slice_=None, lap=lambda name: None):
    """Warm up, then measure for ``seconds``. Returns the window's finished
    queries, its bounds, and the program's counters over it."""
    n, k, per = tf["callers"], tf["num_synonyms"], tf["queries_per_caller"]
    strings, index = sizes["strings"], sizes["index"]
    rng = np.random.default_rng([seed, 0x9E4])
    queries, ids = unseen.mixed_queries(rng, strings, index, n * per, tf["unseen_share"])
    ids = ids.reshape(n, per)
    lap("queries")
    mine = [(queries[c * per:(c + 1) * per], ids[c]) for c in range(n)]
    # a batch can have any size up to the callers (or the batcher's cap), and
    # the scan is one compiled program per size (per tile of 8 on a TPU) and
    # per kind of batch: every size runs once as words alone and once with an
    # unseen string in it, before the window
    some = [q for q, i in zip(queries, ids.reshape(-1)) if i < 0][:1] or ["zq"]
    plain = [q for q, i in zip(queries, ids.reshape(-1)) if i >= 0]
    for q in range(1, min(n, service.stats()["max_batch"]) + 1):
        model.find_synonyms_batch(plain[:q], k)
        model.find_synonyms_batch(some + plain[:q - 1], k)
    lap("every program run once")
    gc.collect()
    gc.freeze()      # see kinds/train.py: no full collection over 2.5M words mid-window
    composed_before = dict(model.query_counts)
    callers = StringCallers(service, n, k, mine)
    callers.start()
    while callers.completed() < tf["warmup_batches"] * n:
        time.sleep(0.005)
    s_open, t_open = service.stats(), time.perf_counter()
    marks = {}
    while (now := time.perf_counter()) - t_open < seconds or (
            slice_ is not None and slice_.started and not slice_.stopped):
        # the callers go on while the profiler starts and stops (seconds, both),
        # so the batches of the slice are counted inside those two calls
        if slice_ is not None and not slice_.started and now - t_open >= seconds / 3.0:
            slice_.start()
            marks["open"], marks["t"] = service.stats(), time.perf_counter()
            marks["composed_open"] = dict(model.query_counts)
        elif (slice_ is not None and slice_.started and not slice_.stopped
              and now - marks["t"] >= tf["trace_slice_s"]):
            marks["close"], marks["composed_close"] = service.stats(), dict(model.query_counts)
            slice_.stop()
        time.sleep(0.002)
    t_close, s_close = time.perf_counter(), service.stats()
    callers.stop()
    sent = [q for d in callers.done for q in d]
    composed = {name: model.query_counts[name] - composed_before[name]
                for name in composed_before}
    counters = {"batch_items": s_close["completed"] - s_open["completed"],
                "batch_slots": (s_close["batches"] - s_open["batches"]) * s_open["max_batch"],
                "compose_s": model.compose_time, "one": 1,
                # over the callers' whole run, the window inside it: every
                # query a caller sent has come back by now
                "unseen_sent": sum(q[2][1] < 0 for q in sent),
                "unseen_composed": composed["unseen"],
                "overflow": composed["overflow"]}
    if "close" in marks:
        counters["slice_calls"] = marks["close"]["batches"] - marks["open"]["batches"]
        counters["slice_items"] = marks["close"]["completed"] - marks["open"]["completed"]
        for name in ("unseen", "list_rows"):
            counters["slice_" + name] = (marks["composed_close"][name]
                                         - marks["composed_open"][name])
    return callers.between(t_open, t_close), (t_open, t_close), counters


def program_rows(model, sizes: dict, seed: int):
    """The program's composed rows of a seeded sample of words, fetched one
    ``transform`` a word while the model still stands."""
    rng = np.random.default_rng([seed, 0x5A4])
    sample = rng.choice(sizes["v"], size=min(SAMPLE_WORDS, sizes["v"]), replace=False)
    return sample, np.stack([model.transform(sizes["strings"][int(w)]) for w in sample])


def compare_with_reference(seed: int, sizes: dict, finished: list, tf: dict,
                           limits: dict, checks: Checks, composed):
    """A seeded sample of the window's replies, half of them to strings the
    vocabulary lacks, against the reference's exact cosines over the composed
    table it makes itself from the seed; and the program's composed rows
    (``composed``: word ids and rows) against the reference's."""
    import jax
    import jax.numpy as jnp

    from reference import subword_query_ref as ref

    v, d, buckets, k = sizes["v"], sizes["d"], sizes["k"], tf["num_synonyms"]
    strings, index, (min_n, max_n) = sizes["strings"], sizes["index"], sizes["ngram"]
    s32 = weights.seed32(seed)

    made = jax.jit(lambda ids: weights.rows_uniform(s32, 0, ids, d, d, sizes["half_width"]))

    def rows_fn(ids):
        # a query's list has any length: host ids go in whole pieces of 64, so
        # that one compiled program makes them all
        short = -ids.shape[0] % 64
        if not short:
            return made(ids)
        return np.asarray(made(np.pad(np.asarray(ids), (0, short))))[:ids.shape[0]]

    t0 = time.perf_counter()
    lists = ref.bucket_lists(strings, buckets, min_n, max_n)
    held, rows = composed
    checks.add("reference_hasher_mismatches",
               ref.hasher_mismatches(strings, lists, held, v, buckets, min_n, max_n), 0)
    t1 = time.perf_counter()
    table = ref.composed_table(rows_fn, lists, v)
    table.block_until_ready()
    t2 = time.perf_counter()
    want = np.asarray(table[jnp.asarray(held, jnp.int32)])
    compose_err = float(np.max(np.linalg.norm(rows - want, axis=1)
                               / np.maximum(np.linalg.norm(want, axis=1), 1e-30)))

    replies = [q for q in finished if not isinstance(q[3], Exception)]
    rng = np.random.default_rng([seed, 0x5A3])
    sample = []
    for is_unseen in (True, False):
        pool = [q for q in replies if (q[2][1] < 0) == is_unseen]
        take = rng.choice(len(pool), size=min(tf["check_queries"] // 2, len(pool)),
                          replace=False)
        sample += [pool[i] for i in take]
    qrows = np.stack([ref.vector(rows_fn, q[2][0], None if q[2][1] < 0 else q[2][1],
                                 v, buckets, min_n, max_n) for q in sample])
    scores = ref.cosine_scores(table, qrows)
    errs, rank_gaps, shape_faults = [], [], 0
    for row, (_, _, (_, wid), reply) in zip(scores, sample):
        row = row.copy()
        if wid >= 0:
            row[wid] = -np.inf                  # a word is not its own synonym
        kth = np.partition(row, v - k)[v - k]
        got = [index.get(w, -1) for w, _ in reply]
        shape_faults += int(len(reply) != k or -1 in got or (wid >= 0 and wid in got)
                            or len(set(got)) != len(got))
        for w, (_, score) in zip(got, reply):
            errs.append(abs(score - row[w]))
            rank_gaps.append(max(0.0, kth - row[w]))
    n_unseen = sum(q[2][1] < 0 for q in sample)
    print(f"check query_subword: {len(sample)} replies of {len(replies)} compared "
          f"({n_unseen} to unseen strings), {len(errs)} neighbours; score error mean "
          f"{np.mean(errs):.3e} max {np.max(errs):.3e}; widest gap below the true "
          f"{k}-th best {np.max(rank_gaps):.3e}; composed rows of {len(held)} words "
          f"within {compose_err:.3e} of the reference's; the reference took "
          f"{t1 - t0:.1f}s for its lists, {t2 - t1:.1f}s for its table, "
          f"{time.perf_counter() - t2:.1f}s for its scores", flush=True)
    checks.add("score_abs_err_mean", float(np.mean(errs)), limits["score_abs_err_mean"])
    checks.add("score_abs_err_max", float(np.max(errs)), limits["score_abs_err_max"])
    checks.add("rank_gap_max", float(np.max(rank_gaps)), limits["rank_gap_max"])
    checks.add("composed_row_rel_err_max", compose_err, limits["composed_row_rel_err_max"])
    checks.add("reply_shape_faults", shape_faults, 0)
    checks.add("unseen_replies_compared_short", tf["check_queries"] // 2 - n_unseen, 0)


_NO_LIMIT = {"score_abs_err_mean": float("inf"), "score_abs_err_max": float("inf"),
             "rank_gap_max": float("inf"), "composed_row_rel_err_max": float("inf")}


def add_counter_checks(checks: Checks, counters: dict):
    checks.add("unseen_composed_minus_sent",
               abs(counters["unseen_composed"] - counters["unseen_sent"]), 0)
    checks.add("overflow_strings", counters["overflow"], 0)


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed, each over a
    short window at the cell's own load (benchmark/sweep_checks.py). The control
    is handed bfloat16 tables."""
    tf = sizes_of(cell["traffic"], tiny)
    out = []
    for seed in seeds:
        service, model, sizes = build_service(
            cell, seed, tiny, table_dtype="bfloat16" if control else None)
        finished, _, counters = serve_window(service, model, tf, seed, sizes,
                                             tf["check_window_s"])
        composed = program_rows(model, sizes, seed)
        _close(service, model)
        del service, model
        checks = Checks()
        compare_with_reference(seed, sizes, finished, tf, _NO_LIMIT, checks, composed)
        add_counter_checks(checks, counters)
        out.append({name: value for name, value, _, _ in checks.rows})
        gc.unfreeze()
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, tiny: bool,
        t_start: float) -> dict:
    import jax

    clog, laps = CompileLog(), Laps(t_start)
    tf = sizes_of(cell["traffic"], tiny)
    service, model, sizes = build_service(cell, seed, tiny, laps=laps)
    slice_ = TracedSlice() if trace else None
    try:
        finished, (t_open, t_close), counters = serve_window(
            service, model, tf, seed, sizes, seconds, slice_, laps.lap)
    finally:
        if slice_ is not None and not slice_.stopped:
            slice_.abandon()
    laps.show()
    window_s = t_close - t_open
    late = clog.between(t_open, t_close)
    failed = sum(isinstance(q[3], Exception) for q in finished)
    lat_ms = np.sort([1e3 * (q[1] - q[0]) for q in finished
                      if not isinstance(q[3], Exception)])
    print(f"query_subword: window {window_s:.3f}s, {len(finished)} queries finished "
          f"({failed} failed), {sum(q[2][1] < 0 for q in finished)} of them for unseen "
          f"strings, p50 {np.percentile(lat_ms, 50):.3f} ms p95 "
          f"{np.percentile(lat_ms, 95):.3f} ms; composed table built in "
          f"{model.compose_time:.2f}s; {len(late)} compilations inside the window {late}; "
          f"persistent cache {clog.cache_hits} hits / {clog.cache_misses} misses", flush=True)
    peak = memory_peak_bytes()
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    calls = max(counters.get("slice_calls", 0), 1)
    # one dispatch scans for as many queries as the batcher coalesced and reads
    # the listed rows of its unseen strings: the traced slice's means
    shapes = dict(queries=counters.get("slice_items", 0) / calls, vocab=sizes["v"],
                  dim=sizes["d"], list_rows=counters.get("slice_list_rows", 0) / calls)
    composed = program_rows(model, sizes, seed)
    _close(service, model)
    del service, model
    checks = Checks()
    compare_with_reference(seed, sizes, finished, tf,
                           sizes_of(cell["config"], tiny)["check"]["query_subword"],
                           checks, composed)
    add_counter_checks(checks, counters)
    checks.add("compilations_in_window", len(late), 0)
    end_to_end = {"query_per_s": len(lat_ms) / window_s,
                  "query_p95_ms": float(np.percentile(lat_ms, 95)),
                  "setup_s": t_open - t_start}
    return dict(correct=checks.ok and failed == 0, attempted=len(finished),
                failed=failed, end_to_end=end_to_end, counters=counters,
                shapes=shapes, slice=sl, memory_peak_bytes=peak)
