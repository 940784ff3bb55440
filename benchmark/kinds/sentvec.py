"""Kind ``sentvec``: closed-loop callers of ``sentence_vectors(slide)``, fastText's
``get_sentence_vector`` over a subword model: one vector a sentence, the mean of
its tokens' UNIT vectors, a token the vocabulary lacks composed from its
n-grams; a slide of ``slide_rows`` sentences a call.

The loop, the window, the traced slice, the warm-up watchdog and the sample of
the replies are ``kinds/transform.py``'s, imported: its callers call
``model.transform_sentences(slide)``, so they are handed the model's
``sentence_vectors`` under that name (:class:`SentenceVectors`). What is this
kind's own:

- Set-up: the vocabulary's words are strings with characters to read
  (harness/words.py, from ``--seed``). The model is handed the trained input
  table's two parts, the words' own rows [V, D] and the bucket rows (K of them,
  at whole lanes as a trainer keeps them), made on the device from the seed
  (stream 0, as ``kinds/query_subword.py`` makes them), through the normal
  constructor with ``resident="rows"``: it composes its table inside, straight
  at whole lanes, and holds no syn1.
- Traffic: every caller owns ``slides_per_caller`` distinct seeded slides:
  sentence lengths by the traffic file's law, tokens by the vocabulary's Zipf
  counts as fresh strings, a share of the tokens strings the vocabulary lacks
  (harness/unseen.py: one edit of a Zipf-drawn word), a share of the sentences
  empty (a blank line), and one planted sentence of ONE unseen token a slide
  (its row is that token's unit vector).
- ``correct``: of each call's reply ``check_rows_per_call`` rows are copied
  aside (the slide's last sentence, an empty one, the planted one, the rest by
  seed); once the model is stopped the plain reference
  (reference/sentvec_ref.py) scores a seeded sample of them: the rows' error
  over their norm, exact zeros for the empty ones, the one-token rows' norm
  against 1, the program's hasher against the reference's on the sample's
  unseen strings, the capacities' engagement.

A program that cannot run the cell ends with a message and a non-zero exit
before anything large is built: asked for a 64-row subword model with
``resident="rows"`` and its ``sentence_vectors``.
"""

import gc

import numpy as np

from harness import unseen, weights, words, zipf
from harness.common import Checks, CompileLog, Laps, TracedSlice, memory_peak_bytes
from harness.loader import sizes as sizes_of
from kinds.transform import draw_sample, slide_engagement, transform_window


class SentenceVectors:
    """The model's ``sentence_vectors`` under the name ``kinds/transform.py``'s
    callers, warm-up and engagement probe call."""

    def __init__(self, model):
        self.transform_sentences = model.sentence_vectors


def _subword_config(d: int, k: int, min_n: int, max_n: int):
    from glint_word2vec_tpu.config import Word2VecConfig
    return Word2VecConfig(vector_size=d, min_count=1, subword=True, subword_min_n=min_n,
                          subword_max_n=max_n, subword_buckets=k)


def build_model(cell: dict, seed: int, tiny: bool, table_dtype=None, laps=None):
    """The model whose ``sentence_vectors`` is called. ``table_dtype``
    overrides the configuration's only for the lower-precision control."""
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel

    lap = laps.lap if laps else (lambda name: None)
    lap("imports")
    cfg = sizes_of(cell["config"], tiny)
    v, d, k = cfg["vocab_size"], cfg["vector_size"], cfg["subword_buckets"]
    half_width = cfg["table_half_width"]
    strings = words.make_words(seed, v)
    vocab = Vocabulary.from_words_and_counts(strings, zipf.zipf_counts(v).astype(np.int64))
    lap("vocabulary")
    dtype = jnp.dtype(table_dtype or cfg["param_dtype"])
    # one input table of V + K rows by the formula, handed over as a fit hands
    # it: the words' rows, and the bucket rows at whole lanes
    lanes = -(-d // 128) * 128
    buckets = jax.jit(lambda s: jnp.pad(
        weights.rows_uniform(s, 0, v + jax.lax.iota(jnp.int32, k), d, d, half_width, dtype),
        ((0, 0), (0, lanes - d))))(weights.seed32(seed))
    raw0 = weights.make_table(seed, 0, v, d, d, half_width, dtype)
    raw0.block_until_ready()
    lap("tables on device")
    ngram = (cfg["subword_min_n"], cfg["subword_max_n"])
    model = Word2VecModel(vocab, raw0, None, config=_subword_config(d, k, *ngram),
                          subword_buckets=buckets, resident="rows")
    del raw0, buckets
    lap("Word2VecModel()")
    return model, dict(v=v, d=d, k=k, half_width=half_width, strings=strings, ngram=ngram)


def require_sentence_vectors():
    """Before anything large is built: a program without ``resident="rows"``,
    without ``sentence_vectors``, or whose ``transform.enqueue`` says nothing of
    a list capacity cannot run this cell, and the run ends here, with a message
    and exit code 1. Asked of a subword model of 64 words and 16 buckets."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel

    rows = np.asarray(weights.rows_uniform(
        weights.seed32(0), 0, jnp.arange(80, dtype=jnp.int32), 8, 8, 0.5))
    vocab = Vocabulary.from_words_and_counts(zipf.words_of(64), np.ones(64, np.int64))
    try:
        model = Word2VecModel(vocab, rows[:64], None, config=_subword_config(8, 16, 3, 6),
                              subword_buckets=rows[64:], resident="rows")
        said = slide_engagement(SentenceVectors(model), [["w1", "w2", "nope"], ["w3"]])
    except (TypeError, AttributeError) as e:
        raise SystemExit(
            f"benchmark: this program cannot run the cell ({type(e).__name__}: {e}): it "
            "has no Word2VecModel(resident='rows') whose sentence_vectors to measure")
    model.stop()
    if "list_cap" not in said:
        raise SystemExit(
            f"benchmark: this program's transform.enqueue says {said} and nothing of a "
            "list capacity: it has no flat-list slide program to measure in this cell")


def make_slides(seed: int, strings: list, known, tf: dict):
    """``callers`` x ``slides_per_caller`` slides, each (sentences, empty
    flags), and beside them where each slide's planted sentence lies.
    ``slide_rows`` lists of fresh strings (a tokenizer hands over strings it
    made, not the vocabulary's own objects); ``known`` answers ``in`` for the
    vocabulary. A caller's slides are drawn together: one draw of its tokens
    and one of its typos (``zipf.draw`` is a pass over the vocabulary's counts
    whatever it draws, and ``unseen.typos`` draws again for every edit that
    made nothing new)."""
    law, v, s = tf["sentence_len"], len(strings), tf["slide_rows"]
    slides, planted = [], []
    for c in range(tf["callers"]):
        rng = np.random.default_rng([seed, 0x7F2, c])
        lens, empty, plants = [], [], []
        for _ in range(tf["slides_per_caller"]):
            lens.append(np.clip(
                np.rint(rng.lognormal(np.log(law["median"]), law["sigma"], s)),
                law["min"], law["max"]).astype(np.int64))
            empty.append(rng.random(s) < tf["empty_share"])
            plants.append(int(rng.integers(0, s)))
            empty[-1][plants[-1]] = False
            lens[-1][empty[-1]], lens[-1][plants[-1]] = 0, 1
        lens = np.concatenate(lens)
        ends = np.cumsum(lens)
        n = int(ends[-1])
        ids = zipf.draw(rng, v, n)
        lost = rng.random(n) < tf["unseen_share"]
        lost[ends[np.arange(len(plants)) * s + plants] - 1] = True
        tokens = " ".join([strings[i] for i in ids.tolist()]).split(" ")
        at = np.flatnonzero(lost)
        for i, made in zip(at.tolist(), unseen.typos(rng, strings, known, ids[at])):
            tokens[i] = made
        sentences = [tokens[a:b] for a, b in zip((ends - lens).tolist(), ends.tolist())]
        slides.append([(sentences[j * s:(j + 1) * s], flags)
                       for j, flags in enumerate(empty)])
        planted.append(plants)
    return slides, planted


def check_rows_of(seed: int, slides: list, planted: list, tf: dict) -> list:
    """Which rows of a slide's reply are copied aside, the same at every call of
    it: its last sentence (where a capacity would cut), one of its empty
    sentences where it has one (the zero-vector branch), its planted one-token
    sentence (one composed token's unit vector), the rest by seed."""
    out = []
    for c, mine in enumerate(slides):
        out.append([])
        for j, (sentences, empty) in enumerate(mine):
            rng = np.random.default_rng([seed, 0x7F3, c, j])
            rows = [len(sentences) - 1]
            if empty.any():
                rows.append(int(rng.choice(np.flatnonzero(empty))))
            if planted[c][j] not in rows:
                rows.append(planted[c][j])
            while len(rows) < tf["check_rows_per_call"]:
                r = int(rng.integers(0, len(sentences)))
                if r not in rows:
                    rows.append(r)
            out[-1].append(np.asarray(rows[:tf["check_rows_per_call"]]))
    return out


def span_counters(events: list) -> dict:
    """Sums over the program's ``transform.*`` spans of the traced slice, for
    the ratios no one span holds (reader ``counter``): live word rows and list
    rows, what the program was handed of each, composed tokens, slides in
    flight."""
    enqueued = [e["args"] for e in events if e["name"] == "transform.enqueue"]
    if not enqueued or "list_cap" not in enqueued[0]:
        return {}
    return {"slice_rows_live": sum(a["rows"] for a in enqueued),
            "slice_rows_handed": sum(a["rows_cap"] * a["passes"] for a in enqueued),
            "slice_list_rows": sum(a["list_rows"] for a in enqueued),
            "slice_list_slots": sum(a["list_cap"] * a["passes"] for a in enqueued),
            "slice_unseen": sum(a["unseen"] for a in enqueued),
            "slice_inflight_and_self": sum(a["inflight"] + 1 for a in enqueued),
            "slice_enqueues": len(enqueued)}


def compare_with_reference(seed: int, sizes: dict, index: dict, sample: list,
                           limits: dict, checks: Checks, rows_fn=None):
    """The sampled rows against the reference's over the table it makes itself
    from the seed (``rows_fn``: another table's rows, for the tests): the
    widest and the mean |served - true| over the true row's own norm; the
    sentences with no vector (empty, or of zero-norm tokens alone) answered
    with exact zeros; the one-token sentences' rows of norm 1; and the
    program's hasher held to the reference's on the sample's unseen strings."""
    from glint_word2vec_tpu.data.subword import ngram_rows
    from reference import sentvec_ref as ref

    d, k, (min_n, max_n) = sizes["d"], sizes["k"], sizes["ngram"]
    rows_fn = rows_fn or ref.seeded_rows(seed, d, sizes["half_width"])
    true = ref.sentence_vectors([s for s, _, _ in sample], index, rows_fn, d, k,
                                min_n, max_n)
    errs, unit, not_zero, empties, planted = [], [], 0, 0, 0
    for want, (tokens, empty, got) in zip(true, sample):
        norm = float(np.linalg.norm(want))
        if empty or norm == 0.0:
            empties += 1
            not_zero += int(np.any(got != 0.0) or norm != 0.0)
            continue
        errs.append(float(np.linalg.norm(got.astype(np.float64) - want)) / norm)
        if len(tokens) == 1:
            unit.append(abs(float(np.linalg.norm(got.astype(np.float64))) - 1.0))
            planted += int(tokens[0] not in index)
    strange = sorted({t for s, _, _ in sample for t in s if t not in index})
    ids, counts, native = ngram_rows(strange, min_n, max_n, k)
    mismatches = ref.hasher_mismatches(
        strange, np.split(ids, np.cumsum(counts)[:-1]), k, min_n, max_n)
    print(f"check sentvec: {len(sample)} sentences compared, {empties} of them with no "
          f"vector, {len(unit)} of one token ({planted} of those unseen); row error over "
          f"the row's norm mean {np.mean(errs):.3e} max {np.max(errs):.3e}; one-token "
          f"rows' norm within {max(unit, default=float('nan')):.3e} of 1; "
          f"{len(strange)} unseen strings hashed {'natively' if native else 'in Python'}, "
          f"{mismatches} unlike the reference's", flush=True)
    checks.add("row_rel_err_mean", float(np.mean(errs)), limits["row_rel_err_mean"])
    checks.add("row_rel_err_max", float(np.max(errs)), limits["row_rel_err_max"])
    checks.add("unit_norm_err_max", max(unit, default=float("inf")),
               limits["unit_norm_err_max"])
    checks.add("empty_rows_not_zero", not_zero, 0)
    checks.add("empty_rows_compared_short", int(empties == 0), 0)
    checks.add("planted_rows_compared_short", int(planted == 0), 0)
    checks.add("reference_hasher_mismatches", mismatches, 0)


def add_engagement_checks(checks: Checks, engaged: dict, expect: dict):
    """The program's own account of a slide, held to the configuration's: one
    pass, and each gather handed at most ``*_max`` slots a live row."""
    print(f"check sentvec: transform.enqueue says {engaged}", flush=True)
    passes = engaged.get("passes", 0)
    checks.add("slide_passes_off", abs(passes - 1), 0)
    for name, live, cap in (("rows_per_word", "rows", "rows_cap"),
                            ("list_slots_per_row", "list_rows", "list_cap")):
        handed = engaged.get(cap, 0) * passes
        checks.add(name, handed / max(engaged.get(live, 0), 1) if handed else float("inf"),
                   expect[name + "_max"])


_NO_LIMIT = {"row_rel_err_mean": float("inf"), "row_rel_err_max": float("inf"),
             "unit_norm_err_max": float("inf")}


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed, each over a
    short window at the cell's own load (benchmark/sweep_checks.py), the model
    rebuilt per seed. The control is handed bfloat16 tables."""
    from reference import sentvec_ref as ref

    tf = sizes_of(cell["traffic"], tiny)
    expect = sizes_of(cell["config"], tiny)["check"]["sentvec"]
    out = []
    for seed in seeds:
        model, sizes = build_model(cell, seed, tiny,
                                   table_dtype="bfloat16" if control else None)
        index = ref.dictionary(sizes["strings"])
        slides, planted = make_slides(seed, sizes["strings"], index, tf)
        rows = check_rows_of(seed, slides, planted, tf)
        finished, _, _, engaged = transform_window(
            SentenceVectors(model), slides, rows, tf, sizes["d"], tf["check_window_s"])
        model.stop()
        del model
        checks = Checks()
        compare_with_reference(seed, sizes, index,
                               draw_sample(seed, slides, rows, finished, tf),
                               _NO_LIMIT, checks)
        add_engagement_checks(checks, engaged, expect)
        checks.add("reply_shape_faults", sum(q[4] for q in finished), 0)
        out.append({name: value for name, value, _, _ in checks.rows})
        del slides, finished, index, sizes
        gc.unfreeze()
        gc.collect()
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, tiny: bool,
        t_start: float) -> dict:
    import jax

    from glint_word2vec_tpu.obs.spans import default_tracer
    from reference import sentvec_ref as ref

    clog, laps = CompileLog(), Laps(t_start)
    tf = sizes_of(cell["traffic"], tiny)
    cfg = sizes_of(cell["config"], tiny)
    require_sentence_vectors()
    model, sizes = build_model(cell, seed, tiny, laps=laps)
    index = ref.dictionary(sizes["strings"])
    slides, planted = make_slides(seed, sizes["strings"], index, tf)
    rows = check_rows_of(seed, slides, planted, tf)
    laps.lap("slides")
    slice_ = TracedSlice() if trace else None
    try:
        finished, (t_open, t_close), counters, engaged = transform_window(
            SentenceVectors(model), slides, rows, tf, sizes["d"], seconds, slice_, laps.lap)
    finally:
        if slice_ is not None and not slice_.stopped:
            slice_.abandon()
    laps.lap("warm-up and window")
    laps.show()
    window_s = t_close - t_open
    late = clog.between(t_open, t_close)
    ok = [q for q in finished if not isinstance(q[3], Exception)]
    per_call = tf["slide_rows"]
    failed = (len(finished) - len(ok)) * per_call
    lat_ms = np.sort([1e3 * (q[1] - q[0]) for q in ok])
    print(f"sentvec: window {window_s:.3f}s, {len(finished)} calls of {per_call} "
          f"sentences finished ({len(finished) - len(ok)} failed), call latency p50 "
          f"{np.percentile(lat_ms, 50):.3f} ms p95 {np.percentile(lat_ms, 95):.3f} ms; "
          f"composed table built in {model.compose_time:.2f}s; "
          f"{len(late)} compilations inside the window {late}; persistent cache "
          f"{clog.cache_hits} hits / {clog.cache_misses} misses", flush=True)
    peak = memory_peak_bytes()
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    if trace:
        counters.update(span_counters(default_tracer().events()))
    calls = max(counters.get("slice_enqueues", 0), 1)
    # one slide's program at the traced slice's mean live rows
    shapes = dict(rows=counters.get("slice_rows_live", 0) / calls,
                  list_rows=counters.get("slice_list_rows", 0) / calls,
                  unseen=counters.get("slice_unseen", 0) / calls,
                  sentences=per_call, dim=sizes["d"], table_dtype=cfg["param_dtype"])
    model.stop()
    del model
    checks = Checks()
    compare_with_reference(seed, sizes, index,
                           draw_sample(seed, slides, rows, finished, tf),
                           cfg["check"]["sentvec"], checks)
    add_engagement_checks(checks, engaged, cfg["check"]["sentvec"])
    checks.add("reply_shape_faults", sum(q[4] for q in finished), 0)
    checks.add("compilations_in_window", len(late), 0)
    # every sentence of a call shares its latency, and every call holds as many
    end_to_end = {"query_per_s": len(ok) * per_call / window_s,
                  "query_p95_ms": float(np.percentile(lat_ms, 95)),
                  "setup_s": t_open - t_start}
    return dict(correct=checks.ok and failed == 0, attempted=len(finished) * per_call,
                failed=failed, end_to_end=end_to_end, counters=counters,
                shapes=shapes, slice=sl, memory_peak_bytes=peak)
