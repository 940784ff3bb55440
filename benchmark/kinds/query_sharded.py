"""Kind ``query_sharded``: closed-loop callers of ``synonyms(word, k)`` against a
table that no one chip holds, row-sharded over the configuration's mesh.

As kind ``query`` (kinds/query.py, whose caller threads, window and closing this
imports): the model is served by ``EmbeddingService(model=..., ann=False)`` at
the program's defaults, ``callers`` threads each send the next query when the
reply arrives, the window opens once the warm-up batches have come back and
closes ``--seconds`` later, and once the service is closed and its tables freed
the plain reference (reference/sharded_query_ref.py, one device, no shards)
scores a seeded sample of the window's replies. What the mesh changes:

- Both tables are made shard by shard under ``plan.embedding`` (no [V, D]
  array on one chip or on the host) and handed to ``Word2VecModel(plan=plan)``
  placed, as a fit on the mesh or ``load(path, plan=)`` hands them over.
- A program that cannot run the cell ends with a message and a non-zero exit,
  not a hang: at once where its scan does not say how many shards it ran over
  (asked of a 64-row table before anything large is built), and at
  ``warmup_deadline_s`` where its warm-up has not come back.
- ``correct`` draws its sample half from replies to words whose row lies
  beyond the first shard (the Zipf draw sends 6% there) and holds three
  readings more: what the scan's program says it ran over (``shards`` and the
  per-shard ``topk_rows`` of ``serve.scan_enqueue``, read from one batch run
  with the recorder on before the window) against what the configuration
  states; every shard among the sample's neighbours; no row past the
  vocabulary in a reply.
"""

import gc
import os
import sys
import threading
import time

import numpy as np

from harness import weights, zipf
from harness.common import Checks, CompileLog, Laps, TracedSlice, memory_peak_bytes
from harness.loader import sizes as sizes_of
from kinds.query import _close, serve_window


def build_service(cell: dict, seed: int, tiny: bool, table_dtype=None, laps=None):
    """The served model, on the configuration's mesh, and its service.
    ``table_dtype`` overrides the configuration's only for the lower-precision
    control."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.parallel.mesh import make_mesh, pad_vocab_for_sharding
    from glint_word2vec_tpu.serve.service import EmbeddingService

    lap = laps.lap if laps else (lambda name: None)
    lap("imports")
    cfg = sizes_of(cell["config"], tiny)
    v, d, half_width = cfg["vocab_size"], cfg["vector_size"], cfg["table_half_width"]
    plan = make_mesh(*cfg["mesh"])
    if pad_vocab_for_sharding(v, plan.num_model) != v:
        raise SystemExit(f"benchmark: {v} rows do not divide over {plan.num_model} "
                         "shards; the formula's tables have no padding rows")
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
    model.norms.block_until_ready()
    lap("Word2VecModel() and norms")
    sizes = dict(v=v, d=d, half_width=half_width, shards=plan.num_model)
    return EmbeddingService(model=model, ann=False), model, sizes


def start_warmup_deadline(service, queries: int, seconds: float) -> threading.Event:
    """A watchdog that ends the process, with a message and exit code 3, where
    the service has not answered ``queries`` warm-up queries ``seconds`` from
    now: a thread of its own, since the thread that waits may be inside a
    device call. Setting the returned event calls it off."""
    off, until = threading.Event(), time.perf_counter() + seconds

    def watch():
        while not off.wait(0.25):
            if service.stats()["completed"] >= queries:
                return
            if time.perf_counter() > until:
                print(f"benchmark: the warm-up did not finish inside its deadline of "
                      f"{seconds:.0f} s: this program cannot serve the table",
                      file=sys.stderr, flush=True)
                os._exit(3)

    threading.Thread(target=watch, name="bench-warmup-deadline", daemon=True).start()
    return off


def require_sharded_scan(mesh: list, k: int):
    """Before anything large is built: a program whose ``serve.scan_enqueue``
    does not say over how many shards it scanned cannot be held to "no shard
    left out", and the run ends here, with a message and exit code 1. Asked of
    a table of 64 rows on the configuration's mesh."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    rows = weights.rows_uniform(weights.seed32(0), 0, jnp.arange(64, dtype=jnp.int32),
                                8, 8, 0.5)
    model = Word2VecModel(
        Vocabulary.from_words_and_counts(zipf.words_of(64), np.ones(64, np.int64)),
        np.asarray(rows), plan=make_mesh(*mesh))
    said = scan_engagement(model, k)
    model.stop()
    if "shards" not in said:
        raise SystemExit(
            f"benchmark: this program's serve.scan_enqueue says {said} and not over how "
            "many shards it scanned: it has no sharded scan to measure in this cell")


def scan_engagement(model, k: int) -> dict:
    """What the scan's program says it ran over: the args of
    ``serve.scan_enqueue`` for one batch of eight words, with the program's
    span recorder on for that batch alone."""
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    tracer.configure(True)
    try:
        model.find_synonyms_batch([f"w{i}" for i in range(8)], k)
        spans = [e for e in tracer.events() if e["name"] == "serve.scan_enqueue"]
    finally:
        tracer.configure(False)
        tracer.clear()
    return dict(spans[-1].get("args", {})) if spans else {}


def compare_with_reference(seed: int, sizes: dict, finished: list, tf: dict,
                           limits: dict, checks: Checks, rows_fn=None):
    """A seeded sample of the window's replies, ``check_offshard_share`` of it
    to words whose row lies beyond the first shard, against the reference's
    exact answer over ALL rows of the table it makes itself from the seed
    (``rows_fn``: another table's rows, for the tests)."""
    from reference import sharded_query_ref as ref

    v, d, k, shards = sizes["v"], sizes["d"], tf["num_synonyms"], sizes["shards"]
    per_shard = -(-v // shards)
    rows_fn = rows_fn or ref.seeded_rows(seed, d, sizes["half_width"])
    replies = [q for q in finished if not isinstance(q[3], Exception)]
    rng = np.random.default_rng([seed, 0x5A3])
    beyond = int(round(tf["check_queries"] * tf["check_offshard_share"]))
    sample = []
    for off, size in ((True, beyond), (False, tf["check_queries"] - beyond)):
        pool = [q for q in replies if (q[2] >= per_shard) == off]
        take = rng.choice(len(pool), size=min(size, len(pool)), replace=False)
        sample += [pool[i] for i in take]
    n_beyond = sum(q[2] >= per_shard for q in sample)
    qids = np.asarray([q[2] for q in sample], np.int32)
    t0 = time.perf_counter()
    true_scores, true_rows = ref.top_k(rows_fn, v, qids, k)
    # the served neighbours, as rows: a reply of another length than k is a
    # shape fault, and is cut or filled with the query's own row to be scored
    got = np.repeat(qids[:, None], k, axis=1).astype(np.int64)
    shape_faults = past_vocabulary = order_faults = 0
    for i, (_, _, wid, reply) in enumerate(sample):
        rows = [int(w[1:]) for w, _ in reply]
        past_vocabulary += sum(r >= v for r in rows)
        shape_faults += int(len(reply) != k or wid in rows or len(set(rows)) != len(rows))
        # best first, and of two neighbours with the same served score the
        # lower row first
        served = [score for _, score in reply]
        order_faults += sum(s0 < s1 or (s0 == s1 and r0 > r1) for r0, r1, s0, s1
                            in zip(rows, rows[1:], served, served[1:]))
        rows = [min(r, v - 1) for r in rows[:k]]
        got[i, :len(rows)] = rows
    served_true = ref.pair_scores(rows_fn, qids, got)
    errs, rank_gaps = [], []
    for i, (_, _, _, reply) in enumerate(sample):
        for j, (_, score) in enumerate(reply[:k]):
            errs.append(abs(score - served_true[i, j]))
            rank_gaps.append(max(0.0, float(true_scores[i, -1] - served_true[i, j])))
    seen = {int(r) // per_shard for r in got.reshape(-1)}
    same = sum(bool((got[i] == true_rows[i]).all()) for i in range(len(sample)))
    print(f"check query_sharded: {len(sample)} replies of {len(replies)} compared "
          f"({n_beyond} to words beyond the first shard's {per_shard} rows), "
          f"{len(errs)} neighbours from shards {sorted(seen)}; score error mean "
          f"{np.mean(errs):.3e} max {np.max(errs):.3e}; widest gap below the true "
          f"{k}-th best over all {v} rows {np.max(rank_gaps):.3e}; {same} replies are "
          f"the reference's rows in its order; the reference took "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    checks.add("score_abs_err_mean", float(np.mean(errs)), limits["score_abs_err_mean"])
    checks.add("score_abs_err_max", float(np.max(errs)), limits["score_abs_err_max"])
    checks.add("rank_gap_max", float(np.max(rank_gaps)), limits["rank_gap_max"])
    checks.add("reply_shape_faults", shape_faults, 0)
    checks.add("reply_order_faults", order_faults, 0)
    checks.add("rows_past_vocabulary", past_vocabulary, 0)
    checks.add("shards_missing_from_neighbours", shards - len(seen & set(range(shards))), 0)
    checks.add("offshard_replies_compared_short", beyond - n_beyond, 0)


def add_engagement_checks(checks: Checks, engaged: dict, sizes: dict, expect: dict):
    """The program's own account of its scan, held to the configuration's."""
    print(f"check query_sharded: serve.scan_enqueue says {engaged}", flush=True)
    checks.add("scan_shards_off", abs(engaged.get("shards", 0) - sizes["shards"]), 0)
    checks.add("scan_topk_rows_off",
               abs(engaged.get("topk_rows", 0) - expect["topk_rows_per_shard"]), 0)


_NO_LIMIT = {"score_abs_err_mean": float("inf"), "score_abs_err_max": float("inf"),
             "rank_gap_max": float("inf")}


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed, each over a
    short window at the cell's own load (benchmark/sweep_checks.py), the tables
    rebuilt per seed. The control serves bfloat16 tables."""
    tf = sizes_of(cell["traffic"], tiny)
    expect = sizes_of(cell["config"], tiny)["check"]["query_sharded"]
    out = []
    for seed in seeds:
        service, model, sizes = build_service(
            cell, seed, tiny, table_dtype="bfloat16" if control else None)
        engaged = scan_engagement(model, tf["num_synonyms"])
        finished, _, _ = serve_window(service, model, tf, seed, sizes["v"],
                                      tf["check_window_s"])
        _close(service, model)
        del service, model
        checks = Checks()
        compare_with_reference(seed, sizes, finished, tf, _NO_LIMIT, checks)
        add_engagement_checks(checks, engaged, sizes, expect)
        out.append({name: value for name, value, _, _ in checks.rows})
        gc.unfreeze()
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, tiny: bool,
        t_start: float) -> dict:
    import jax

    clog, laps = CompileLog(), Laps(t_start)
    tf = sizes_of(cell["traffic"], tiny)
    cfg = sizes_of(cell["config"], tiny)
    require_sharded_scan(cfg["mesh"], tf["num_synonyms"])
    service, model, sizes = build_service(cell, seed, tiny, laps=laps)
    deadline = start_warmup_deadline(service, tf["warmup_batches"] * tf["callers"],
                                     tf["warmup_deadline_s"])
    engaged = scan_engagement(model, tf["num_synonyms"])
    laps.lap("first batch")
    slice_ = TracedSlice() if trace else None
    try:
        finished, (t_open, t_close), counters = serve_window(
            service, model, tf, seed, sizes["v"], seconds, slice_)
    finally:
        deadline.set()
        if slice_ is not None and not slice_.stopped:
            slice_.abandon()
    laps.lap("warm-up and window")
    laps.show()
    window_s = t_close - t_open
    late = clog.between(t_open, t_close)
    failed = sum(isinstance(q[3], Exception) for q in finished)
    lat_ms = np.sort([1e3 * (q[1] - q[0]) for q in finished
                      if not isinstance(q[3], Exception)])
    per_shard = -(-sizes["v"] // sizes["shards"])
    print(f"query_sharded: window {window_s:.3f}s, {len(finished)} queries finished "
          f"({failed} failed), "
          f"{sum(q[2] >= per_shard for q in finished) / max(len(finished), 1):.4f} of "
          f"them for a row beyond the first of {sizes['shards']} shards, p50 "
          f"{np.percentile(lat_ms, 50):.3f} ms p95 {np.percentile(lat_ms, 95):.3f} ms; "
          f"{len(late)} compilations inside the window {late}; persistent cache "
          f"{clog.cache_hits} hits / {clog.cache_misses} misses", flush=True)
    peak = memory_peak_bytes()
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    # one dispatch scans, on every chip, a quarter of the table for as many
    # queries as the batcher coalesced: the mean batch of the traced slice
    shapes = dict(queries=counters.get("slice_items", 0) / max(counters.get("slice_calls", 0), 1),
                  vocab=sizes["v"], dim=sizes["d"], table_dtype=cfg["param_dtype"],
                  chips=sizes["shards"])
    _close(service, model)
    del service, model
    checks = Checks()
    compare_with_reference(seed, sizes, finished, tf, cfg["check"]["query_sharded"], checks)
    add_engagement_checks(checks, engaged, sizes, cfg["check"]["query_sharded"])
    checks.add("compilations_in_window", len(late), 0)
    end_to_end = {"query_per_s": len(lat_ms) / window_s,
                  "query_p95_ms": float(np.percentile(lat_ms, 95)),
                  "setup_s": t_open - t_start}
    return dict(correct=checks.ok and failed == 0, attempted=len(finished),
                failed=failed, end_to_end=end_to_end, counters=counters,
                shapes=shapes, slice=sl, memory_peak_bytes=peak)
