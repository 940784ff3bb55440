"""Kind ``train_subword``: a seeded Zipf corpus through ``Trainer.fit`` with the
subword row source on (fastText's skip-gram; config.subword).

As kind ``train`` (kinds/train.py, whose corpus, step-twin names and
window-closing exception this imports): tables made on the device from
``--seed`` and handed over placed, a check of three steps through the trainer's
own compiled dispatch before the fit, ``gc.freeze()``, a window opened and
closed on ``block_until_ready`` of the params at a heartbeat, and the plain
reference (reference/subword_ref.py) after the trainer's tables are freed. What
the subword model changes:

- The vocabulary's words are strings with characters to read
  (harness/words.py, from ``--seed``), syn0 has the vocabulary's rows and the
  bucket rows after them, and the trainer builds and places the row table
  (span ``vocab.subword_table``; its seconds are the counter
  ``subword_table_s``).
- The check's three batches are the pair feed's own first three (real center
  runs, real repeated contexts), so the branch of the step that the window
  runs is the branch the check holds. The reference gets the same pairs, each
  center as the list of rows ITS n-gram function gives, and the program's own
  negatives.
- Compared per twin: the first step's and the three steps' change norm of
  three leaves (syn0's word rows, syn0's bucket rows, syn1), each against its
  own reference norm; and the row table's rows for a seeded sample of 1,000
  words against the reference's n-gram function, exactly.
"""

import gc
import math
import time

import numpy as np

from harness import weights, words, zipf
from harness.common import Checks, CompileLog, Laps, TracedSlice, memory_peak_bytes
from harness.loader import sizes as sizes_of
from kinds.train import TWINS, _WindowClosed, make_corpus

CHECK_STEPS = 3
LEAVES = ("word_rows", "bucket_rows", "syn1")
SAMPLE_WORDS = 1000


def build_trainer(cell: dict, seed: int, tiny: bool, param_dtype=None, laps=None):
    """The subword trainer over its vocabulary of strings, holding the check's
    tables for ``seed``, and ``tables(seed, for_check)``, which makes it
    another pair in their place. ``param_dtype`` overrides the configuration's
    only for the lower-precision control."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.ops.sgns import EmbeddingPair
    from glint_word2vec_tpu.parallel.mesh import (
        make_mesh, pad_dim_to_lanes, pad_vocab_for_sharding)
    from glint_word2vec_tpu.train.trainer import Trainer

    lap = laps.lap if laps else (lambda name: None)
    lap("imports")
    cfg, tf = sizes_of(cell["config"], tiny), sizes_of(cell["traffic"], tiny)
    v, d, k = cfg["vocab_size"], cfg["vector_size"], cfg["subword_buckets"]
    counts = zipf.zipf_counts(v)
    strings = words.make_words(seed, v)
    vocab = Vocabulary.from_words_and_counts(strings, counts.astype(np.int64))
    lap("vocabulary")

    nd, nm = cfg["mesh"]
    wcfg = Word2VecConfig(
        vector_size=d, window=cfg["window"], negatives=cfg["negatives"], min_count=1,
        subword=cfg["subword"], subword_min_n=cfg["subword_min_n"],
        subword_max_n=cfg["subword_max_n"], subword_buckets=k,
        param_dtype=param_dtype or cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"], logits_dtype=cfg["logits_dtype"],
        pairs_per_batch=tf["pairs_per_batch"],
        steps_per_dispatch=tf["steps_per_dispatch"],
        heartbeat_every_steps=tf["heartbeat_every_steps"],
        num_iterations=tf["num_iterations"], seed=cfg["program_seed"],
        num_data_shards=nd, num_model_shards=nm,
        # AUTO unless the sizes say otherwise (the tiny block does: AUTO turns
        # the shared pool off at toy batches)
        **{key: tf[key] for key in ("negative_pool", "subsample_ratio") if key in tf})
    plan = make_mesh(nd, nm)
    pv = pad_vocab_for_sharding(v, nm)
    rows0 = pad_vocab_for_sharding(v + k, nm)
    pd = pad_dim_to_lanes(d, wcfg.pad_vector_to_lanes)
    dtype = jnp.dtype(wcfg.param_dtype)
    half_width = cfg["check_state"]["half_width"]

    def tables(seed: int, for_check: bool):
        """The check's pair (both seeded, a trained model's magnitude) or the
        pair a fit starts from (syn0 small, word and bucket rows alike, as
        fastText starts its input matrix; syn1 zero)."""
        if for_check:
            return EmbeddingPair(
                weights.make_table(seed, 0, rows0, d, pd, half_width, dtype,
                                   plan.embedding),
                weights.make_table(seed, 1, pv, d, pd, half_width, dtype,
                                   plan.embedding))
        return EmbeddingPair(
            weights.make_table(seed, 0, rows0, d, pd, 0.5 / d, dtype, plan.embedding),
            weights.make_zeros(pv, pd, dtype, plan.embedding))

    params = tables(seed, True)
    params.syn1.block_until_ready()
    lap("tables on device")
    trainer = Trainer(wcfg, vocab, plan=plan, params=params)
    lap("Trainer()")
    if trainer.params.syn0 is not params.syn0:
        raise RuntimeError("the trainer re-placed tables that were already placed")
    return trainer, tables, dict(v=v, d=d, pd=pd, k=k, half_width=half_width,
                                 strings=strings,
                                 ngram=(cfg["subword_min_n"], cfg["subword_max_n"]))


def feed_batches(trainer, sentences, steps: int):
    """The pair feed's own first ``steps`` full batches of the fit's first
    iteration: what the window's first steps will train on."""
    b = trainer.config.pairs_per_batch
    centers, contexts = [], []
    stream = trainer._batch_stream(sentences, 1)
    try:
        for batch in stream:
            if batch["real"] == b:
                centers.append(np.array(batch["centers"], np.int32))
                contexts.append(np.array(batch["contexts"], np.int32))
            if len(centers) == steps:
                break
    finally:
        stream.close()
    if len(centers) < steps:
        raise RuntimeError("the corpus is too short for the check's batches")
    return np.stack(centers), np.stack(contexts)


def reference_lists(sizes: dict, centers):
    """Every pair's center as the reference sees it: the rows ITS n-gram
    function lists for the word's string, [steps, B, longest] with the count."""
    from reference import subword_ref

    v, k = sizes["v"], sizes["k"]
    ids = np.unique(centers)
    of_word = {int(w): subword_ref.word_rows(sizes["strings"][w], w, v, k,
                                             *sizes["ngram"]) for w in ids}
    longest = max(len(r) for r in of_word.values())
    table = np.zeros((ids.shape[0], longest), np.int32)
    count = np.zeros(ids.shape[0], np.int32)
    for i, w in enumerate(ids):
        rows = of_word[int(w)]
        table[i, :len(rows)], count[i] = rows, len(rows)
    at = np.searchsorted(ids, centers)
    return table[at], count[at]


def drive_check_steps(trainer, tables, seed: int, centers, contexts, lists):
    """Three steps through the trainer's OWN compiled dispatch, staged as the
    fit stages it (kinds/train.py ``drive_check_steps``: one dispatch whose
    first step is real and the rest masked, then one with two real steps), once
    through each twin from the same tables. ``trainer.params`` holds the
    check's tables on entry and nothing on return."""
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.ops.sampler import sample_negatives_hash
    from glint_word2vec_tpu.parallel.distributed import put_global

    cfg = trainer.config
    k, b = cfg.steps_per_dispatch, cfg.pairs_per_batch
    assert centers.shape == (CHECK_STEPS, b) and k >= 2
    pool = cfg.negative_pool
    draw = jax.jit(lambda prob, alias, base: sample_negatives_hash(
        prob, alias, np.uint32(cfg.seed & 0xFFFFFFFF), base, (k, pool)))
    plan = ((1, [0]), (2, [1, 2]))          # (PRNG base step, the real steps)
    negatives = np.concatenate([
        np.asarray(draw(trainer._table_prob, trainer._table_alias,
                        np.int32(base)))[:len(steps)] for base, steps in plan])
    # the rows compared: every row a step touches, filled up with untouched
    # rows to a round count (so that it moves with the seed rarely, and the
    # gathers and the reference compile anew rarely); an untouched row may
    # not move
    def fill(touched, total_rows, unit):
        count = -(-(touched.shape[0] + 1) // unit) * unit
        spare = np.setdiff1d(np.arange(min(2 * count, total_rows)), touched)
        return np.sort(np.concatenate(
            [touched, spare[:count - touched.shape[0]]])).astype(np.int32)

    rows0 = fill(np.unique(lists[0][np.arange(lists[0].shape[-1])[None, None, :]
                                    < lists[1][:, :, None]]),
                 trainer.params.syn0.shape[0], 1 << 16)
    rows1 = fill(np.unique(np.concatenate([contexts.ravel(), negatives.ravel()])),
                 trainer.params.syn1.shape[0], 1 << 14)
    rows0_dev, rows1_dev = jnp.asarray(rows0), jnp.asarray(rows1)

    lr = float(np.float32(cfg.learning_rate))
    losses, snaps, rows_per_pair = [], {}, []
    for prefix, name in TWINS:
        step_fn = getattr(trainer, name)
        if trainer.params is None:
            trainer.params = tables(seed, True)
        snaps[prefix] = []
        for base, steps in plan:
            pairs = np.zeros((k, 2, b), trainer._pair_dtype)
            meta = np.zeros((2, k), np.float32)     # rows: learning rate, real pairs
            for slot, s in enumerate(steps):
                pairs[slot, 0], pairs[slot, 1] = centers[s], contexts[s]
                meta[:, slot] = lr, b
            staged = put_global(trainer._chunk_shardings, {"pairs": pairs})
            meta_dev, base_dev = trainer._stage_dispatch_meta(meta, base)
            trainer.params, metrics = step_fn(
                trainer.params, staged, meta_dev, base_dev,
                trainer._table_prob, trainer._table_alias, *trainer._step_extra)
            if not prefix:
                losses += [float(x) for x in np.asarray(metrics.loss)[:len(steps)]]
                rows_per_pair += [float(x) / b for x in
                                  np.asarray(metrics.subword_rows)[:len(steps)]]
            snaps[prefix].append((trainer.params.syn0[rows0_dev],
                                  trainer.params.syn1[rows1_dev]))
        # to the host, and wait: rows kept on the device, or tables still in use
        # by a step in flight when the next pair is made, would raise the memory
        # peak above the fit's own
        snaps[prefix] = jax.device_get(snaps[prefix])
        trainer.params = None
    return dict(losses=losses, negatives=negatives, rows0=rows0, rows1=rows1,
                snaps=snaps, lr=lr, rows_per_pair=rows_per_pair)


def row_table_mismatches(trainer, sizes: dict, seed: int) -> int:
    """Words of a seeded sample whose rows in the program's table (read back
    from the device) are not the reference's n-gram function's, order aside."""
    import jax

    from reference import subword_ref

    offsets, rows, counts = trainer._step_extra
    v, k = sizes["v"], sizes["k"]
    sample = np.random.default_rng([seed, 0x5AB]).integers(
        0, v, min(SAMPLE_WORDS, v))
    host_off, host_cnt = jax.device_get((offsets, counts))
    # the sample's groups in one gather of one shape, whatever the sample
    longest = int((host_off[1:v + 1] - host_off[:v]).max())
    at = host_off[sample][:, None] + np.arange(longest)[None, :]
    groups = np.asarray(rows[np.minimum(at, rows.shape[0] - 1)])
    wrong = 0
    for i, w in enumerate(sample):
        got = groups[i, :int(host_off[w + 1] - host_off[w])].reshape(-1)
        live, pad = got[:int(host_cnt[w])], got[int(host_cnt[w]):]
        want = subword_ref.word_rows(sizes["strings"][w], int(w), v, k,
                                     *sizes["ngram"])
        wrong += int(sorted(live.tolist()) != sorted(want)
                     or bool((pad < trainer.params.syn0.shape[0]).any()))
    return wrong


def compare_with_reference(seed: int, sizes: dict, contexts, lists, got: dict,
                           num_negatives: int, limits: dict, checks: Checks):
    """The plain reference follows the same three steps on the rows they touch
    (made from the seed by the benchmark's own formula), and each number of
    the program's, for each twin of its step, is held to its limit."""
    import jax.numpy as jnp

    from reference import subword_ref

    d, pd, v = sizes["d"], sizes["pd"], sizes["v"]
    rows0, rows1 = got["rows0"], got["rows1"]
    list_rows, list_count = lists
    l_idx = np.searchsorted(rows0, list_rows).astype(np.int32)
    x_idx = np.searchsorted(rows1, contexts).astype(np.int32)
    n_idx = np.searchsorted(rows1, got["negatives"]).astype(np.int32)
    s32 = weights.seed32(seed)
    init0, init1 = (weights.rows_uniform(s32, stream, jnp.asarray(rows), d, pd,
                                         sizes["half_width"])[:, :d]
                    for stream, rows in ((0, rows0), (1, rows1)))
    is_word = rows0 < v
    ref = subword_ref.follow_steps(
        init0, init1, jnp.asarray(l_idx), jnp.asarray(list_count),
        jnp.asarray(x_idx), jnp.asarray(n_idx), [got["lr"]] * CHECK_STEPS,
        num_negatives, is_word)

    def change_norms(snap):
        s0, s1 = snap
        d0 = jnp.asarray(s0[:, :d], jnp.float32) - init0
        return (subword_ref.leaf_norm(jnp.where(is_word[:, None], d0, 0.0)),
                subword_ref.leaf_norm(jnp.where(is_word[:, None], 0.0, d0)),
                subword_ref.leaf_norm(jnp.asarray(s1[:, :d], jnp.float32) - init1))

    print(f"check train: losses program {got['losses']} reference {ref['losses']}; "
          f"reference change norms {LEAVES} after one step "
          f"{ref['first_change_norm']}, after three {ref['change_norm']}; subword "
          f"rows a pair {got['rows_per_pair']}", flush=True)
    checks.add("loss_rel_gap", max(abs(p - w) / abs(w) for p, w in
                                   zip(got["losses"], ref["losses"])),
               limits["loss_rel_gap"])
    for prefix, snaps in got["snaps"].items():
        first, total = change_norms(snaps[0]), change_norms(snaps[1])
        print(f"check train: {prefix or 'metrics_'}twin change norms {LEAVES} "
              f"after one step {first}, after three {total}", flush=True)
        # each leaf against its own reference norm: from these tables no leaf's
        # change is near zero, and a leaf that never moved reads 1. The first
        # gradient as the optimizer gets it is the first step's change over
        # its learning rate: the rate cancels in the relative gap
        for leaf, p1, w1, p3, w3 in zip(LEAVES, first, ref["first_change_norm"],
                                        total, ref["change_norm"]):
            checks.add(f"{prefix}first_gradient_norm_gap.{leaf}",
                       abs(p1 - w1) / max(w1, 1e-30),
                       limits["first_gradient_norm_gap"])
            checks.add(f"{prefix}change_norm_gap.{leaf}",
                       abs(p3 - w3) / max(w3, 1e-30), limits["change_norm_gap"])
    pad = max(float(np.abs(s[:, d:].astype(np.float32)).max()) if pd > d else 0.0
              for snaps in got["snaps"].values() for snap in snaps for s in snap)
    checks.add("padding_abs_max", pad, 0.0)


_NO_LIMIT = {"loss_rel_gap": float("inf"), "first_gradient_norm_gap": float("inf"),
             "change_norm_gap": float("inf")}


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed
    (benchmark/sweep_checks.py). The control is the program with its tables in
    bfloat16. The words are the first seed's (one trainer, one row table); each
    seed brings its own corpus, feed batches and tables."""
    tf = sizes_of(cell["traffic"], tiny)
    trainer, tables, sizes = build_trainer(cell, seeds[0], tiny,
                                           param_dtype="bfloat16" if control else None)
    cfg = trainer.config
    out = []
    for seed in seeds:
        if trainer.params is None:
            trainer.params = tables(seed, True)
        centers, contexts = feed_batches(
            trainer, make_corpus(seed, sizes["v"], tf), CHECK_STEPS)
        lists = reference_lists(sizes, centers)
        got = drive_check_steps(trainer, tables, seed, centers, contexts, lists)
        gc.collect()
        checks = Checks()
        compare_with_reference(seed, sizes, contexts, lists, got, cfg.negatives,
                               _NO_LIMIT, checks)
        out.append({name: value for name, value, _, _ in checks.rows})
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, tiny: bool,
        t_start: float) -> dict:
    import jax
    import jax.profiler as jp

    from glint_word2vec_tpu.data.native import native_available

    clog = CompileLog()
    laps = Laps(t_start)
    tf = sizes_of(cell["traffic"], tiny)
    trainer, tables, sizes = build_trainer(cell, seed, tiny, laps=laps)
    cfg = trainer.config
    sentences = make_corpus(seed, sizes["v"], tf)
    laps.lap("corpus")
    if not native_available():
        # the numpy pair generator is a 4-5x slower stand-in: not the system under test
        raise RuntimeError("native pair generator did not build (g++?)")
    print(f"train_subword: resolved negative_pool={cfg.negative_pool} subsample_ratio="
          f"{cfg.subsample_ratio:.3e} params {list(trainer.params.syn0.shape)} + "
          f"{list(trainer.params.syn1.shape)} {trainer.params.syn0.dtype}; row table "
          f"{list(trainer._step_extra[1].shape)} in {trainer.subword_table_time:.2f}s, "
          f"{trainer._subword_shape}", flush=True)

    centers, contexts = feed_batches(trainer, sentences, CHECK_STEPS)
    lists = reference_lists(sizes, centers)
    table_wrong = row_table_mismatches(trainer, sizes, seed)
    got = drive_check_steps(trainer, tables, seed, centers, contexts, lists)
    trainer.params = tables(seed, False)
    laps.lap("check steps")

    budget = tf["loss_budget_steps"]
    slice_ = TracedSlice()
    st = dict(beats=0, nonfinite=0, open=None, close=None, loss_at_budget=None,
              slice_open=None, slice_close=None, losses=[])

    def mark(rec):
        jax.block_until_ready(trainer.params)
        return dict(t=time.perf_counter(), step=rec.global_step,
                    pairs=trainer.pairs_trained, wait=trainer.host_wait_time)

    def on_heartbeat(rec):
        with jp.TraceAnnotation("bench.heartbeat"):
            st["beats"] += 1
            st["losses"].append((rec.global_step, rec.loss))
            if st["open"] is not None and not math.isfinite(rec.loss):
                st["nonfinite"] += 1
            if rec.global_step == budget and len(st["losses"]) >= tf["loss_mean_heartbeats"]:
                last = st["losses"][-tf["loss_mean_heartbeats"]:]
                st["loss_at_budget"] = sum(loss for _, loss in last) / len(last)
            if st["open"] is None:
                if st["beats"] == 1:
                    laps.lap("fit to first heartbeat")
                if st["beats"] >= tf["warmup_heartbeats"]:
                    st["open"] = mark(rec)
                    laps.lap("warm-up to window")
                return
            since = time.perf_counter() - st["open"]["t"]
            if trace and not slice_.started and since >= seconds / 3.0:
                st["slice_open"] = mark(rec)
                slice_.start()
            elif (slice_.started and not slice_.stopped and
                  time.perf_counter() - st["slice_open"]["t"] >= tf["trace_slice_s"]):
                st["slice_close"] = mark(rec)
                slice_.stop()
            if since >= seconds and (not trace or slice_.stopped):
                st["close"] = mark(rec)
                raise _WindowClosed()

    # the vocabulary and the corpus are millions of Python objects: a full
    # collection that walks them stalls the fit loop for a tenth of a second at
    # a moment of its own choosing, so they are put out of the collector's sight
    gc.collect()
    gc.freeze()
    try:
        trainer.fit(sentences, on_heartbeat=on_heartbeat)
        raise RuntimeError("the corpus ran out before the window closed: raise "
                           "num_iterations or corpus_tokens in the traffic file")
    except _WindowClosed:
        pass
    finally:
        if not slice_.stopped:
            slice_.abandon()

    laps.show()
    o, c = st["open"], st["close"]
    window_s = c["t"] - o["t"]
    steps, pairs = c["step"] - o["step"], c["pairs"] - o["pairs"]
    late = clog.between(o["t"], c["t"])
    twins = {trainer._step_fn._cache_size(), trainer._step_fn_fast._cache_size()}
    print(f"train_subword: window {window_s:.3f}s, {steps} steps, {pairs:.0f} pairs, "
          f"{len(late)} compilations inside it {late}; set-up compiled "
          f"{len(clog.compiles)} programs, persistent cache {clog.cache_hits} hits / "
          f"{clog.cache_misses} misses; step programs per twin {twins}", flush=True)
    print("train_subword: heartbeat (step, loss) "
          f"{[(s, round(x, 5)) for s, x in st['losses']]}", flush=True)
    peak = memory_peak_bytes()
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    counters = {"host_wait_s": c["wait"] - o["wait"], "window_s": window_s,
                "subword_table_s": trainer.subword_table_time, "one": 1}
    if sl:
        counters["slice_calls"] = st["slice_close"]["step"] - st["slice_open"]["step"]

    # free the program's tables, then let the reference follow the three steps
    num_negatives = cfg.negatives
    shapes = dict(pairs_per_batch=cfg.pairs_per_batch, pool=cfg.negative_pool,
                  padded_dim=sizes["pd"], param_dtype=str(cfg.param_dtype),
                  subword_rows_per_pair=float(np.mean(got["rows_per_pair"])))
    trainer.params = None
    trainer._step_extra = ()
    del trainer
    gc.collect()
    checks = Checks()
    compare_with_reference(seed, sizes, contexts, lists, got, num_negatives,
                           sizes_of(cell["config"], tiny)["check"]["train"], checks)
    checks.add("row_table_mismatches", table_wrong, 0)
    checks.add("compilations_in_window", len(late), 0)
    checks.add("step_programs_per_twin", max(twins), 1)
    # every full batch holds exactly B real pairs: the pair count the rate rests
    # on may not run ahead of the steps the heartbeats counted
    checks.add("pairs_over_steps_times_batch",
               pairs / (steps * cfg.pairs_per_batch) if steps else 2.0, 1.0)
    reached = st["loss_at_budget"] is not None
    if not reached:
        print(f"train_subword: the fit never reached the budget step {budget}",
              flush=True)
    end_to_end = {"train_pairs_per_s": pairs / window_s,
                  "setup_s": o["t"] - t_start}
    if reached:
        end_to_end["train_loss_at_budget"] = st["loss_at_budget"]
    return dict(correct=checks.ok and reached and st["nonfinite"] == 0,
                attempted=steps, failed=st["nonfinite"] + (0 if reached else 1),
                end_to_end=end_to_end, counters=counters, shapes=shapes, slice=sl,
                memory_peak_bytes=peak)
