"""Kind ``train_hs``: a seeded Zipf corpus through ``Trainer.fit`` under
hierarchical softmax (``word2vec.c -hs 1 -negative 0``; config.loss="hs").

As kind ``train_subword`` (kinds/train_subword.py: the same window, marks,
trace slice and budget; kinds/train.py's corpus, step-twin names and
window-closing exception): tables made on the device from ``--seed`` and handed
over placed, a check of three steps through the trainer's own compiled
dispatch before the fit, ``gc.freeze()``, a window opened and closed on
``block_until_ready`` of the params at a heartbeat, and the plain reference
(reference/hs_ref.py) after the trainer's tables are freed. What the
hierarchical softmax changes:

- The trainer builds the vocabulary's Huffman tree and places the path table
  (span ``vocab.huffman_tree``; its seconds are the counter ``hs_tree_s``);
  there is no sampler, so the check draws no negatives and the dispatch takes
  no alias tables.
- The check's three batches are the pair feed's own first three (real center
  runs, real repeated contexts, the root in every pair), so the branch of the
  step that the window runs is the branch the check holds. The reference gets
  the same pairs, each context as the path ITS tree gives the word.
- Compared per twin: the first step's and the three steps' change norm of
  three leaves (syn0, syn1's top :data:`TOP_NODES` node rows, syn1's other
  rows), each against its own reference norm, so that an error in the hot rows
  is not drowned by a hundred thousand cold ones, nor the reverse; the path
  table's lists for a seeded sample of 1,000 words against the reference's own
  tree, exactly; and the step's count of live (pair, node) terms against the
  reference's, exactly.
"""

import gc
import math
import time

import numpy as np

from harness import weights, zipf
from harness.common import Checks, CompileLog, Laps, TracedSlice, memory_peak_bytes
from harness.loader import sizes as sizes_of
from kinds.train import TWINS, _WindowClosed, make_corpus
from kinds.train_subword import feed_batches

CHECK_STEPS = 3
LEAVES = ("syn0", "top_nodes", "other_nodes")
# the tree's highest node ids: the root and the ~10.5 levels under it
TOP_NODES = 2048
SAMPLE_WORDS = 1000


def build_trainer(cell: dict, seed: int, tiny: bool, param_dtype=None, laps=None):
    """The hierarchical-softmax trainer over its vocabulary, holding the
    check's tables for ``seed``, and ``tables(seed, for_check)``, which makes
    it another pair in their place. ``param_dtype`` overrides the
    configuration's only for the lower-precision control."""
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
    v, d = cfg["vocab_size"], cfg["vector_size"]
    nd, nm = cfg["mesh"]
    # first, so that a program without the option fails at once
    wcfg = Word2VecConfig(
        vector_size=d, window=cfg["window"], loss=cfg["loss"],
        negatives=cfg["negatives"], min_count=1,
        param_dtype=param_dtype or cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"], logits_dtype=cfg["logits_dtype"],
        pairs_per_batch=tf["pairs_per_batch"],
        steps_per_dispatch=tf["steps_per_dispatch"],
        heartbeat_every_steps=tf["heartbeat_every_steps"],
        num_iterations=tf["num_iterations"], seed=cfg["program_seed"],
        num_data_shards=nd, num_model_shards=nm,
        **{key: tf[key] for key in ("subsample_ratio",) if key in tf})
    counts = zipf.zipf_counts(v).astype(np.int64)
    vocab = Vocabulary.from_words_and_counts(zipf.words_of(v), counts)
    lap("vocabulary")
    plan = make_mesh(nd, nm)
    pv = pad_vocab_for_sharding(v, nm)
    pd = pad_dim_to_lanes(d, wcfg.pad_vector_to_lanes)
    dtype = jnp.dtype(wcfg.param_dtype)
    half_width = cfg["check_state"]["half_width"]

    def tables(seed: int, for_check: bool):
        """The check's pair (both seeded, a trained model's magnitude) or the
        pair a fit starts from (syn0 small, syn1 zero, as word2vec.c starts)."""
        if for_check:
            return EmbeddingPair(*(
                weights.make_table(seed, stream, pv, d, pd, half_width, dtype,
                                   plan.embedding) for stream in (0, 1)))
        return EmbeddingPair(
            weights.make_table(seed, 0, pv, d, pd, 0.5 / d, dtype, plan.embedding),
            weights.make_zeros(pv, pd, dtype, plan.embedding))

    params = tables(seed, True)
    params.syn1.block_until_ready()
    lap("tables on device")
    trainer = Trainer(wcfg, vocab, plan=plan, params=params)
    lap("Trainer()")
    if trainer.params.syn0 is not params.syn0:
        raise RuntimeError("the trainer re-placed tables that were already placed")
    return trainer, tables, dict(v=v, d=d, pd=pd, half_width=half_width,
                                 counts=counts,
                                 max_node_pairs=cfg["hs_max_node_pairs"])


def reference_paths(sizes: dict, contexts, tree=None):
    """Every pair's context as the reference sees it: the path ITS tree gives
    the word, ``(points, codes)`` [steps, B, longest] and the lengths."""
    from reference import hs_ref

    tree = tree or hs_ref.create_binary_tree(sizes["counts"])
    ids = np.unique(contexts)
    of_word = [hs_ref.word_path(tree, int(w)) for w in ids]
    longest = max(len(p) for p, _ in of_word)
    points = np.zeros((ids.shape[0], longest), np.int32)
    codes = np.zeros((ids.shape[0], longest), np.int32)
    lengths = np.zeros(ids.shape[0], np.int32)
    for i, (p, c) in enumerate(of_word):
        points[i, :len(p)], codes[i, :len(c)], lengths[i] = p, c, len(p)
    at = np.searchsorted(ids, contexts)
    return points[at], codes[at], lengths[at]


def drive_check_steps(trainer, tables, seed: int, centers, contexts, paths):
    """Three steps through the trainer's OWN compiled dispatch, staged as the
    fit stages it (kinds/train.py ``drive_check_steps``: one dispatch whose
    first step is real and the rest masked, then one with two real steps), once
    through each twin from the same tables. ``trainer.params`` holds the
    check's tables on entry and nothing on return."""
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.parallel.distributed import put_global

    cfg = trainer.config
    k, b = cfg.steps_per_dispatch, cfg.pairs_per_batch
    assert centers.shape == (CHECK_STEPS, b) and k >= 2
    plan = ((1, [0]), (2, [1, 2]))          # (base step, the real steps)

    # the rows compared: every row a step touches, filled up with untouched
    # rows to a round count (kinds/train_subword.py has the why); an untouched
    # row may not move
    def fill(touched, total_rows, unit):
        count = -(-(touched.shape[0] + 1) // unit) * unit
        spare = np.setdiff1d(np.arange(min(2 * count, total_rows)), touched)
        return np.sort(np.concatenate(
            [touched, spare[:count - touched.shape[0]]])).astype(np.int32)

    points, _, lengths = paths
    on_path = np.arange(points.shape[-1])[None, None, :] < lengths[:, :, None]
    rows0 = fill(np.unique(centers), trainer.params.syn0.shape[0], 1 << 14)
    rows1 = fill(np.unique(points[on_path]), trainer.params.syn1.shape[0], 1 << 16)
    rows0_dev, rows1_dev = jnp.asarray(rows0), jnp.asarray(rows1)

    lr = float(np.float32(cfg.learning_rate))
    losses, snaps, nodes, rows_per_pair = [], {}, [], []
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
                trainer.params, staged, meta_dev, base_dev, *trainer._step_extra)
            if not prefix:
                losses += [float(x) for x in np.asarray(metrics.loss)[:len(steps)]]
                rows_per_pair += [float(x) / b for x in
                                  np.asarray(metrics.syn1_rows)[:len(steps)]]
            nodes += [(prefix, float(x)) for x in
                      np.asarray(metrics.hs_nodes)[:len(steps)]]
            snaps[prefix].append((trainer.params.syn0[rows0_dev],
                                  trainer.params.syn1[rows1_dev]))
        # to the host, and wait (kinds/train_subword.py has the why)
        snaps[prefix] = jax.device_get(snaps[prefix])
        trainer.params = None
    return dict(losses=losses, rows0=rows0, rows1=rows1, snaps=snaps, lr=lr,
                nodes=nodes, rows_per_pair=rows_per_pair)


def path_table_mismatches(trainer, sizes: dict, seed: int, tree) -> int:
    """Words of a seeded sample whose path in the program's table (read back
    from the device) is not the reference's own tree's, slot for slot."""
    import jax

    from reference import hs_ref

    offsets, rows, counts = trainer._step_extra
    v = sizes["v"]
    sample = np.random.default_rng([seed, 0x5AB]).integers(
        0, v, min(SAMPLE_WORDS, v))
    host_off, host_cnt = jax.device_get((offsets, counts))
    longest = int((host_off[1:v + 1] - host_off[:v]).max())
    at = host_off[sample][:, None] + np.arange(longest)[None, :]
    groups = np.asarray(rows[np.minimum(at, rows.shape[0] - 1)])
    wrong = 0
    for i, w in enumerate(sample):
        got = groups[i, :int(host_off[w + 1] - host_off[w])].reshape(-1)
        live, pad = got[:int(host_cnt[w])], got[int(host_cnt[w]):]
        points, codes = hs_ref.word_path(tree, int(w))
        wrong += int(live.tolist() != [2 * p + c for p, c in zip(points, codes)]
                     or bool((pad != np.iinfo(np.int32).max).any()))
    return wrong


def leaf_limit(limits: dict, name: str, leaf: str) -> float:
    """A norm limit of the configuration's ``check`` block: one number for
    every leaf, or one a leaf."""
    limit = limits[name]
    return limit[leaf] if isinstance(limit, dict) else limit


def compare_with_reference(seed: int, sizes: dict, centers, paths, got: dict,
                           limits: dict, checks: Checks):
    """The plain reference follows the same three steps on the rows they touch
    (made from the seed by the benchmark's own formula), and each number of
    the program's, for each twin of its step, is held to its limit."""
    import jax.numpy as jnp

    from reference import hs_ref

    d, pd, v = sizes["d"], sizes["pd"], sizes["v"]
    rows0, rows1 = got["rows0"], got["rows1"]
    points, codes, lengths = paths
    c_idx = np.searchsorted(rows0, centers).astype(np.int32)
    # a slot past a path's end is never read: it may point anywhere in bounds
    p_idx = np.minimum(np.searchsorted(rows1, points), rows1.shape[0] - 1
                       ).astype(np.int32)
    s32 = weights.seed32(seed)
    init0, init1 = (weights.rows_uniform(s32, stream, jnp.asarray(rows), d, pd,
                                         sizes["half_width"])[:, :d]
                    for stream, rows in ((0, rows0), (1, rows1)))
    is_top = rows1 >= v - 1 - TOP_NODES
    ref = hs_ref.follow_steps(
        init0, init1, jnp.asarray(c_idx), jnp.asarray(p_idx), jnp.asarray(codes),
        jnp.asarray(lengths), [got["lr"]] * CHECK_STEPS, is_top,
        max_node_pairs=sizes["max_node_pairs"])

    def change_norms(snap):
        s0, s1 = snap
        d1 = jnp.asarray(s1[:, :d], jnp.float32) - init1
        return (hs_ref.leaf_norm(jnp.asarray(s0[:, :d], jnp.float32) - init0),
                hs_ref.leaf_norm(jnp.where(is_top[:, None], d1, 0.0)),
                hs_ref.leaf_norm(jnp.where(is_top[:, None], 0.0, d1)))

    print(f"check train: losses program {got['losses']} reference {ref['losses']}; "
          f"reference change norms {LEAVES} after one step "
          f"{ref['first_change_norm']}, after three {ref['change_norm']}; syn1 "
          f"rows a pair {got['rows_per_pair']}", flush=True)
    checks.add("loss_rel_gap", max(abs(p - w) / abs(w) for p, w in
                                   zip(got["losses"], ref["losses"])),
               limits["loss_rel_gap"])
    for prefix, snaps in got["snaps"].items():
        first, total = change_norms(snaps[0]), change_norms(snaps[1])
        print(f"check train: {prefix or 'metrics_'}twin change norms {LEAVES} "
              f"after one step {first}, after three {total}", flush=True)
        # each leaf against its own reference norm (kinds/train_subword.py),
        # and under its own limit: the top nodes' summed updates are large
        # beside a rounding of their rows, the other nodes' are not
        for leaf, p1, w1, p3, w3 in zip(LEAVES, first, ref["first_change_norm"],
                                        total, ref["change_norm"]):
            checks.add(f"{prefix}first_gradient_norm_gap.{leaf}",
                       abs(p1 - w1) / max(w1, 1e-30),
                       leaf_limit(limits, "first_gradient_norm_gap", leaf))
            checks.add(f"{prefix}change_norm_gap.{leaf}",
                       abs(p3 - w3) / max(w3, 1e-30),
                       leaf_limit(limits, "change_norm_gap", leaf))
    # the step's own count of live (pair, node) terms, each twin, each step
    want = [float(n) for n in lengths.sum(axis=1)]
    checks.add("hs_nodes_mismatches", sum(
        int(n != want[i % CHECK_STEPS]) for i, (_, n) in enumerate(got["nodes"])), 0)
    pad = max(float(np.abs(s[:, d:].astype(np.float32)).max()) if pd > d else 0.0
              for snaps in got["snaps"].values() for snap in snaps for s in snap)
    checks.add("padding_abs_max", pad, 0.0)


_NO_LIMIT = {"loss_rel_gap": float("inf"), "first_gradient_norm_gap": float("inf"),
             "change_norm_gap": float("inf")}


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed
    (benchmark/sweep_checks.py). The control is the program with its tables in
    bfloat16. One trainer and one tree; each seed brings its own corpus, feed
    batches and tables."""
    from reference import hs_ref

    tf = sizes_of(cell["traffic"], tiny)
    trainer, tables, sizes = build_trainer(cell, seeds[0], tiny,
                                           param_dtype="bfloat16" if control else None)
    tree = hs_ref.create_binary_tree(sizes["counts"])
    out = []
    for seed in seeds:
        if trainer.params is None:
            trainer.params = tables(seed, True)
        centers, contexts = feed_batches(
            trainer, make_corpus(seed, sizes["v"], tf), CHECK_STEPS)
        paths = reference_paths(sizes, contexts, tree)
        got = drive_check_steps(trainer, tables, seed, centers, contexts, paths)
        gc.collect()
        checks = Checks()
        compare_with_reference(seed, sizes, centers, paths, got, _NO_LIMIT, checks)
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
    print(f"train_hs: resolved subsample_ratio={cfg.subsample_ratio:.3e} params "
          f"{list(trainer.params.syn0.shape)} + {list(trainer.params.syn1.shape)} "
          f"{trainer.params.syn0.dtype}; path table "
          f"{list(trainer._step_extra[1].shape)} in {trainer.hs_tree_time:.2f}s, "
          f"{trainer._hs_shape}", flush=True)

    from reference import hs_ref
    tree = hs_ref.create_binary_tree(sizes["counts"])
    centers, contexts = feed_batches(trainer, sentences, CHECK_STEPS)
    paths = reference_paths(sizes, contexts, tree)
    table_wrong = path_table_mismatches(trainer, sizes, seed, tree)
    del tree
    got = drive_check_steps(trainer, tables, seed, centers, contexts, paths)
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
    print(f"train_hs: window {window_s:.3f}s, {steps} steps, {pairs:.0f} pairs, "
          f"{len(late)} compilations inside it {late}; set-up compiled "
          f"{len(clog.compiles)} programs, persistent cache {clog.cache_hits} hits / "
          f"{clog.cache_misses} misses; step programs per twin {twins}", flush=True)
    print("train_hs: heartbeat (step, loss) "
          f"{[(s, round(x, 5)) for s, x in st['losses']]}", flush=True)
    peak = memory_peak_bytes()
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    counters = {"host_wait_s": c["wait"] - o["wait"], "window_s": window_s,
                "hs_tree_s": trainer.hs_tree_time, "one": 1}
    if sl:
        counters["slice_calls"] = st["slice_close"]["step"] - st["slice_open"]["step"]

    # free the program's tables, then let the reference follow the three steps
    shapes = dict(pairs_per_batch=cfg.pairs_per_batch, padded_dim=sizes["pd"],
                  param_dtype=str(cfg.param_dtype),
                  path_nodes_per_pair=float(np.mean([n for _, n in got["nodes"]]))
                  / cfg.pairs_per_batch,
                  syn1_rows_per_pair=float(np.mean(got["rows_per_pair"])))
    trainer.params = None
    trainer._step_extra = ()
    del trainer
    gc.collect()
    checks = Checks()
    compare_with_reference(seed, sizes, centers, paths, got,
                           sizes_of(cell["config"], tiny)["check"]["train"], checks)
    checks.add("path_table_mismatches", table_wrong, 0)
    checks.add("compilations_in_window", len(late), 0)
    checks.add("step_programs_per_twin", max(twins), 1)
    # every full batch holds exactly B real pairs: the pair count the rate rests
    # on may not run ahead of the steps the heartbeats counted
    checks.add("pairs_over_steps_times_batch",
               pairs / (steps * cfg.pairs_per_batch) if steps else 2.0, 1.0)
    reached = st["loss_at_budget"] is not None
    if not reached:
        print(f"train_hs: the fit never reached the budget step {budget}",
              flush=True)
    end_to_end = {"train_pairs_per_s": pairs / window_s,
                  "setup_s": o["t"] - t_start}
    if reached:
        end_to_end["train_loss_at_budget"] = st["loss_at_budget"]
    return dict(correct=checks.ok and reached and st["nonfinite"] == 0,
                attempted=steps, failed=st["nonfinite"] + (0 if reached else 1),
                end_to_end=end_to_end, counters=counters, shapes=shapes, slice=sl,
                memory_peak_bytes=peak)
