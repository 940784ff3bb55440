"""Kind ``transform_sharded``: closed-loop callers of ``transform_sentences(slide)``
against a table that no one chip holds, row-sharded over the configuration's mesh:
the upstream ``transform(dataset)`` as its parameter servers run it
(``pullAverage``, ml:453: every server sums the rows it holds, the partial sums
are added).

As kind ``transform`` (kinds/transform.py, whose slides, caller threads, window,
watchdog and sample this imports): ``callers`` threads each call
``model.transform_sentences(slide)`` on a slide of ``slide_rows`` sentences and
send their next when the array comes back; a "query" is one sentence. What the
mesh changes:

- Both tables are made shard by shard under ``plan.embedding`` (no [V, D] array
  on one chip or on the host), as kinds/query_sharded.py makes them, and handed
  to ``Word2VecModel(plan=plan)`` placed, as a fit on the mesh or
  ``load(path, plan=)`` hands them over.
- A program that cannot run the cell ends with a message and a non-zero exit,
  not a hang: at once where its ``transform.enqueue`` does not say over how
  many ``shards`` the slide's program ran (asked of a 64-row table on the
  configuration's mesh before anything large is built), and at
  ``warmup_deadline_s`` where its warm-up has not come back.
- Every slide carries ``last_shard_sentences`` planted sentences whose tokens
  all lie in the LAST shard's rows (the Zipf draw sends ~94% of a slide's ids
  to the first): their means come from that shard's partial sums alone. The
  callers copy their rows aside with the slide's last sentence, one all-OOV
  sentence and the rest by seed.
- ``correct`` holds beside the sample's row errors (against
  reference/sharded_transform_ref.py, in which no partition appears): the planted
  sentences of the sample non-zero and inside ``row_rel_err_max`` each; from one
  slide run with the recorder on before the window, ``shards`` as the
  configuration states, ``passes`` 1, the row capacity's engagement, and the
  owned counts: the kind counts, from the slide's own tokens, the live ids each
  shard owns; they sum to the program's ``rows`` and their greatest is its
  ``owned_max``, exactly.
"""

import gc

import numpy as np

from harness import weights, zipf
from harness.common import Checks, CompileLog, Laps, TracedSlice, memory_peak_bytes
from harness.loader import sizes as sizes_of
from kinds import transform as slides_kind


def build_model(cell: dict, seed: int, tiny: bool, table_dtype=None, laps=None,
                vocab=None):
    """The model whose ``transform_sentences`` is called, on the configuration's
    mesh. ``table_dtype`` overrides the configuration's only for the
    lower-precision control; ``vocab``: one built before (the sweep's seeds
    share it: it is no function of the seed)."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.parallel.mesh import make_mesh, pad_vocab_for_sharding

    lap = laps.lap if laps else (lambda name: None)
    lap("imports")
    cfg = sizes_of(cell["config"], tiny)
    v, d, half_width = cfg["vocab_size"], cfg["vector_size"], cfg["table_half_width"]
    plan = make_mesh(*cfg["mesh"])
    if pad_vocab_for_sharding(v, plan.num_model) != v:
        raise SystemExit(f"benchmark: {v} rows do not divide over {plan.num_model} "
                         "shards; the formula's tables have no padding rows")
    if vocab is None:
        vocab = Vocabulary.from_words_and_counts(
            zipf.words_of(v), zipf.zipf_counts(v).astype(np.int64))
    lap("vocabulary")
    dtype = jnp.dtype(table_dtype or cfg["param_dtype"])
    syn0 = weights.make_table(seed, 0, v, d, d, half_width, dtype, sharding=plan.embedding)
    syn1 = weights.make_table(seed, 1, v, d, d, half_width, dtype, sharding=plan.embedding)
    syn1.block_until_ready()
    lap("tables on the mesh")
    model = Word2VecModel(vocab, syn0, syn1, config=Word2VecConfig(vector_size=d),
                          plan=plan)
    del syn0, syn1
    return model, dict(v=v, d=d, half_width=half_width, shards=plan.num_model)


def require_sharded_slide(mesh: list):
    """Before anything large is built: a program whose ``transform.enqueue`` does
    not say over how many shards the slide's program ran cannot be held to "every
    shard's rows are candidates", and the run ends here, with a message and exit
    code 1. Asked of a table of 64 rows on the configuration's mesh."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    rows = weights.rows_uniform(weights.seed32(0), 0, jnp.arange(64, dtype=jnp.int32),
                                8, 8, 0.5)
    model = Word2VecModel(
        Vocabulary.from_words_and_counts(zipf.words_of(64), np.ones(64, np.int64)),
        np.asarray(rows), plan=make_mesh(*mesh))
    said = slides_kind.slide_engagement(model, [["w1", "w63", "nope"], ["w3"]])
    model.stop()
    if "shards" not in said:
        raise SystemExit(
            f"benchmark: this program's transform.enqueue says {said} and not over how "
            "many shards the slide's program ran: it has no sharded slide to measure in "
            "this cell")


def make_slides(seed: int, v: int, shards: int, tf: dict):
    """kinds/transform.py's slides by the same law (every slide's lengths, empty
    sentences and lost tokens from its own seed, as there), with two differences.
    The words of ALL slides come from ONE ``zipf.draw`` dealt out slide by slide: a
    shuffled multinomial sample is a run of independent draws, so each slide's ids
    are in law what a draw of its own gives, and a draw a slide is a multinomial
    over all ``v`` counts and a ``v``-wide repeat each time (42 of 78 s of set-up at
    10M words and 32 slides; PERF.md §6, PR 59). And ``last_shard_sentences``
    sentences of each slide are replaced by a planted one: ``last_shard_tokens``
    words drawn uniformly from the last shard's rows. Returns the slides and, slide
    by slide, the planted sentences' places (never the slide's last sentence nor
    an all-OOV one)."""
    law = tf["sentence_len"]
    drawn = []
    for c in range(tf["callers"]):
        for j in range(tf["slides_per_caller"]):
            rng = np.random.default_rng([seed, 0x7F0, c, j])
            lens = np.clip(np.rint(rng.lognormal(np.log(law["median"]), law["sigma"],
                                                 tf["slide_rows"])),
                           law["min"], law["max"]).astype(np.int64)
            empty = rng.random(tf["slide_rows"]) < tf["empty_share"]
            lost = (rng.random(int(lens.sum())) < tf["oov_share"]) | np.repeat(empty, lens)
            drawn.append((lens, empty, lost))
    ids = zipf.draw(np.random.default_rng([seed, 0x7F3]), v,
                    sum(len(lost) for _, _, lost in drawn))
    first = (shards - 1) * (-(-v // shards))
    slides, planted, at = [], [], 0
    for c in range(tf["callers"]):
        slides.append([])
        planted.append([])
        for j in range(tf["slides_per_caller"]):
            lens, empty, lost = drawn[c * tf["slides_per_caller"] + j]
            words = [f"w{i}" for i in ids[at:at + len(lost)].tolist()]
            at += len(lost)
            for i in np.flatnonzero(lost).tolist():
                words[i] = f"q{i}"        # no vocabulary word starts with q
            ends = np.cumsum(lens)
            sentences = [words[a:b] for a, b in zip((ends - lens).tolist(), ends.tolist())]
            rng = np.random.default_rng([seed, 0x7F2, c, j])
            free = np.flatnonzero(~empty[:-1])
            places = rng.choice(free, size=tf["last_shard_sentences"], replace=False)
            for p in places.tolist():
                sentences[p] = [f"w{i}" for i in rng.integers(
                    first, v, tf["last_shard_tokens"]).tolist()]
            slides[-1].append((sentences, empty))
            planted[-1].append(np.sort(places))
    return slides, planted


def check_rows_of(seed: int, slides: list, planted: list, tf: dict) -> list:
    """Which rows of a slide's reply are copied aside, the same at every call of
    it: its last sentence (where a capacity would cut), one of its all-OOV
    sentences where it has one (the zero-vector branch), its planted sentences
    (the last shard's partial alone), the rest by seed."""
    out = []
    for c, mine in enumerate(slides):
        out.append([])
        for j, (sentences, empty) in enumerate(mine):
            rng = np.random.default_rng([seed, 0x7F1, c, j])
            rows = [len(sentences) - 1]
            if empty.any():
                rows.append(int(rng.choice(np.flatnonzero(empty))))
            rows += planted[c][j].tolist()
            while len(rows) < tf["check_rows_per_call"]:
                r = int(rng.integers(0, len(sentences)))
                if r not in rows:
                    rows.append(r)
            out[-1].append(np.asarray(rows[:tf["check_rows_per_call"]]))
    return out


def compare_with_reference(seed: int, sizes: dict, sample: list, planted: set,
                           limits: dict, checks: Checks, rows_fn=None):
    """The sampled rows against the reference's means over the table it makes
    itself from the seed (``rows_fn``: another table's rows, for the tests): the
    widest and the mean |served - true| over the true row's own norm; the all-OOV
    and empty sentences of the sample answered with exact zeros; and the planted
    sentences of the sample (``planted``: their lists' ``id``), every one
    non-zero and inside the widest error's limit."""
    from reference import sharded_transform_ref as ref

    rows_fn = rows_fn or ref.seeded_rows(seed, sizes["d"], sizes["half_width"])
    true = ref.sentence_vectors([s for s, _, _ in sample], ref.dictionary(sizes["v"]),
                                rows_fn, sizes["d"])
    errs, planted_errs, not_zero, empties, planted_zero = [], [], 0, 0, 0
    for want, (tokens, empty, got) in zip(true, sample):
        norm = float(np.linalg.norm(want))
        if empty or norm == 0.0:
            empties += 1
            not_zero += int(np.any(got != 0.0) or norm != 0.0)
            continue
        errs.append(float(np.linalg.norm(got.astype(np.float64) - want)) / norm)
        if id(tokens) in planted:
            planted_errs.append(errs[-1])
            planted_zero += int(not np.any(got != 0.0))
    print(f"check transform_sharded: {len(sample)} sentences compared, {empties} of "
          f"them all out of vocabulary, {len(planted_errs)} planted in the last of "
          f"{sizes['shards']} shards; row error over the row's norm mean "
          f"{np.mean(errs):.3e} max {np.max(errs):.3e}, over the planted ones max "
          f"{max(planted_errs, default=float('nan')):.3e}", flush=True)
    checks.add("row_rel_err_mean", float(np.mean(errs)), limits["row_rel_err_mean"])
    checks.add("row_rel_err_max", float(np.max(errs)), limits["row_rel_err_max"])
    checks.add("empty_rows_not_zero", not_zero, 0)
    checks.add("empty_rows_compared_short", int(empties == 0), 0)
    checks.add("last_shard_rows_compared_short", int(not planted_errs), 0)
    checks.add("last_shard_rows_zero", planted_zero, 0)
    checks.add("last_shard_row_rel_err_max", max(planted_errs, default=float("inf")),
               limits["row_rel_err_max"])


def owned_counts(slide: list, v: int, shards: int) -> np.ndarray:
    """The live ids of ``slide`` each shard owns, counted here from the tokens:
    a word is ``w<rank>`` with rank < v, its row its rank, its shard the range of
    ``ceil(v / shards)`` rows the row lies in."""
    ranks = np.asarray([int(w[1:]) for s in slide for w in s
                        if w[0] == "w" and w[1:].isdigit() and int(w[1:]) < v], np.int64)
    return np.bincount(ranks // (-(-v // shards)), minlength=shards)


def add_engagement_checks(checks: Checks, engaged: dict, slide: list, sizes: dict,
                          expect: dict):
    """The program's own account of a slide, held to the configuration's and to
    the kind's own count of who owns the slide's ids."""
    owned = owned_counts(slide, sizes["v"], sizes["shards"])
    print(f"check transform_sharded: transform.enqueue says {engaged}; the slide's "
          f"live ids by owner {owned.tolist()}", flush=True)
    slides_kind.add_engagement_checks(checks, engaged, expect)
    checks.add("slide_shards_off", abs(engaged.get("shards", 0) - expect["shards"]), 0)
    checks.add("owned_rows_sum_off", abs(int(owned.sum()) - engaged.get("rows", -1)), 0)
    checks.add("owned_max_off", abs(int(owned.max()) - engaged.get("owned_max", -1)), 0)


def span_counters(events: list) -> dict:
    """kinds/transform.py's sums over the traced slice's ``transform.*`` spans,
    and the busiest shard's live ids summed beside the slides' (the hot shard's
    share; a program whose span has no ``owned_max`` leaves it out)."""
    counters = slides_kind.span_counters(events)
    owned = [e["args"]["owned_max"] for e in events
             if e["name"] == "transform.enqueue" and "owned_max" in e.get("args", {})]
    if counters and owned:
        counters["slice_owned_max"] = sum(owned)
    return counters


_NO_LIMIT = {"row_rel_err_mean": float("inf"), "row_rel_err_max": float("inf")}


def _planted_ids(slides: list, planted: list) -> set:
    return {id(slides[c][j][0][p]) for c, mine in enumerate(planted)
            for j, at in enumerate(mine) for p in at.tolist()}


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed, each over a
    short window at the cell's own load (benchmark/sweep_checks.py), the tables
    rebuilt per seed (the vocabulary, no function of the seed, once). The control
    transforms over bfloat16 tables."""
    tf = sizes_of(cell["traffic"], tiny)
    expect = sizes_of(cell["config"], tiny)["check"]["transform_sharded"]
    out, vocab = [], None
    for seed in seeds:
        model, sizes = build_model(cell, seed, tiny, vocab=vocab,
                                   table_dtype="bfloat16" if control else None)
        vocab = model.vocab
        slides, planted = make_slides(seed, sizes["v"], sizes["shards"], tf)
        rows = check_rows_of(seed, slides, planted, tf)
        finished, _, _, engaged = slides_kind.transform_window(
            model, slides, rows, tf, sizes["d"], tf["check_window_s"])
        model.stop()
        del model
        checks = Checks()
        compare_with_reference(
            seed, sizes, slides_kind.draw_sample(seed, slides, rows, finished, tf),
            _planted_ids(slides, planted), _NO_LIMIT, checks)
        add_engagement_checks(checks, engaged, slides[0][0][0], sizes, expect)
        checks.add("reply_shape_faults", sum(q[4] for q in finished), 0)
        out.append({name: value for name, value, _, _ in checks.rows})
        del slides, finished
        gc.unfreeze()
        gc.collect()
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, tiny: bool,
        t_start: float) -> dict:
    import jax

    from glint_word2vec_tpu.obs.spans import default_tracer

    clog, laps = CompileLog(), Laps(t_start)
    tf = sizes_of(cell["traffic"], tiny)
    cfg = sizes_of(cell["config"], tiny)
    require_sharded_slide(cfg["mesh"])
    model, sizes = build_model(cell, seed, tiny, laps=laps)
    slides, planted = make_slides(seed, sizes["v"], sizes["shards"], tf)
    rows = check_rows_of(seed, slides, planted, tf)
    laps.lap("slides")
    slice_ = TracedSlice() if trace else None
    try:
        finished, (t_open, t_close), counters, engaged = slides_kind.transform_window(
            model, slides, rows, tf, sizes["d"], seconds, slice_, laps.lap)
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
    print(f"transform_sharded: window {window_s:.3f}s, {len(finished)} calls of "
          f"{per_call} sentences finished ({len(finished) - len(ok)} failed), call "
          f"latency p50 {np.percentile(lat_ms, 50):.3f} ms p95 "
          f"{np.percentile(lat_ms, 95):.3f} ms; {len(late)} compilations inside the "
          f"window {late}; persistent cache {clog.cache_hits} hits / "
          f"{clog.cache_misses} misses", flush=True)
    peak = memory_peak_bytes()
    print(f"transform_sharded: memory peak on the fullest of {sizes['shards']} chips "
          f"{peak} B", flush=True)
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    if trace:
        counters.update(span_counters(default_tracer().events()))
    enqueues = max(counters.get("slice_enqueues", 0), 1)
    shapes = dict(owned_rows=counters.get("slice_owned_max", 0) / enqueues,
                  rows=counters.get("slice_rows_live", 0) / enqueues,
                  sentences=per_call, dim=sizes["d"], table_dtype=cfg["param_dtype"],
                  chips=sizes["shards"])
    model.stop()
    del model
    checks = Checks()
    limits = cfg["check"]["transform_sharded"]
    compare_with_reference(
        seed, sizes, slides_kind.draw_sample(seed, slides, rows, finished, tf),
        _planted_ids(slides, planted), limits, checks)
    add_engagement_checks(checks, engaged, slides[0][0][0], sizes, limits)
    checks.add("reply_shape_faults", sum(q[4] for q in finished), 0)
    checks.add("compilations_in_window", len(late), 0)
    # every sentence of a call shares its latency, and every call holds as many
    end_to_end = {"query_per_s": len(ok) * per_call / window_s,
                  "query_p95_ms": float(np.percentile(lat_ms, 95)),
                  "setup_s": t_open - t_start}
    return dict(correct=checks.ok and failed == 0, attempted=len(finished) * per_call,
                failed=failed, end_to_end=end_to_end, counters=counters,
                shapes=shapes, slice=sl, memory_peak_bytes=peak)
