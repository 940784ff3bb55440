"""Kind ``train_cbow_subword``: a seeded Zipf corpus of seeded word strings
through ``Trainer.fit`` with banded CBOW, the subword row source of a token
block and position weights on (fastText's ``cc.<lang>.300`` recipe).

As kinds ``train_cbow`` and ``train_subword``, from which this imports what
they expose (the corpus, the step twins' names, the window-closing exception,
the example-counting wrapper, the row table's comparison): tables made on the
device from ``--seed`` and handed over placed, a check of three steps through
the trainer's own compiled dispatch before the fit, ``gc.freeze()``, a window
opened and closed on ``block_until_ready`` of the params at a heartbeat, and
the plain reference (reference/cbow_subword_ref.py) after the trainer's tables
are freed. What this model changes:

- The trained state has three leaves: syn0 (the vocabulary's rows and the
  bucket rows), syn1, and the position weights ``d`` [2*window, D]. The check
  starts ``d`` from seeded U(0.5, 1.5), so that a mirrored or dropped position
  shows; the fit starts it from ones.
- The check's three blocks are the token feed's own first three (repeated
  words, real sentence ends, empty windows, the halo), so the branch of the
  step that the window runs is the branch the check holds. The reference gets
  each live center as a list of (position, word) from the program's own window
  draws, each word as the rows ITS n-gram function lists, and the program's
  own negatives.
- Compared per twin: the first step's and the three steps' change norm of four
  leaves (syn0's word rows, syn0's bucket rows, syn1, ``d``), each against its
  own reference norm; both twins' example counts against the draws' live
  centers, exactly; the row table's rows for a seeded 1,000 words, exactly.
"""

import gc
import math
import time

import numpy as np

from harness import weights, words, zipf
from harness.common import Checks, CompileLog, Laps, TracedSlice, memory_peak_bytes
from harness.loader import sizes as sizes_of
from kinds.train import TWINS, _WindowClosed, make_corpus
from kinds.train_cbow import _CountingStep
from kinds.train_subword import row_table_mismatches

CHECK_STEPS = 3
LEAVES = ("word_rows", "bucket_rows", "syn1", "positions")
POSITION_STREAM = 2         # weights.py's stream of the position weights


def position_rows(seed, window: int, dim: int, padded_dim: int, dtype=None,
                  sharding=None):
    """The check's position weights [2*window, padded_dim]: seeded U(0.5, 1.5)
    in the first ``dim`` columns, the lane padding exactly 0."""
    import jax
    import jax.numpy as jnp

    away = weights.rows_uniform(weights.seed32(seed), POSITION_STREAM,
                                jnp.arange(2 * window, dtype=jnp.int32), dim,
                                padded_dim, 0.5)
    cols = jnp.arange(padded_dim)[None, :]
    rows = jnp.where(cols < dim, 1.0 + away, 0.0).astype(dtype or jnp.float32)
    # placed as the step returns the leaf: another placement is another program
    return rows if sharding is None else jax.device_put(rows, sharding)


def build_trainer(cell: dict, seed: int, tiny: bool, param_dtype=None, laps=None):
    """The trainer over its vocabulary of strings, holding the check's tables
    for ``seed``, and ``tables(seed, for_check)``, which makes it another
    three leaves in their place. ``param_dtype`` overrides the configuration's
    only for the lower-precision control."""
    import jax
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
    nd, nm = cfg["mesh"]
    wcfg = Word2VecConfig(
        vector_size=d, window=cfg["window"], negatives=cfg["negatives"], min_count=1,
        cbow=cfg["cbow"], cbow_update=cfg["cbow_update"],
        cbow_position_weights=cfg["cbow_position_weights"],
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
    # the configuration first: a program that lacks one of its options (the
    # parent of the PR that brought this kind) fails here, at once
    counts = zipf.zipf_counts(v)
    strings = words.make_words(seed, v)
    vocab = Vocabulary.from_words_and_counts(strings, counts.astype(np.int64))
    lap("vocabulary")
    plan = make_mesh(nd, nm)
    pv = pad_vocab_for_sharding(v, nm)
    rows0 = pad_vocab_for_sharding(v + k, nm)
    pd = pad_dim_to_lanes(d, wcfg.pad_vector_to_lanes)
    dtype = jnp.dtype(wcfg.param_dtype)
    half_width = cfg["check_state"]["half_width"]

    def tables(seed: int, for_check: bool):
        """The check's leaves (all seeded, a trained model's magnitude) or the
        leaves a fit starts from (syn0 small, word and bucket rows alike; syn1
        zero; every position weight one)."""
        if for_check:
            return EmbeddingPair(
                weights.make_table(seed, 0, rows0, d, pd, half_width, dtype,
                                   plan.embedding),
                weights.make_table(seed, 1, pv, d, pd, half_width, dtype,
                                   plan.embedding),
                position_rows(seed, wcfg.window, d, pd, dtype, plan.replicated))
        return EmbeddingPair(
            weights.make_table(seed, 0, rows0, d, pd, 0.5 / d, dtype, plan.embedding),
            weights.make_zeros(pv, pd, dtype, plan.embedding),
            jax.device_put((jnp.arange(pd)[None, :] < d).astype(dtype)
                           * jnp.ones((2 * wcfg.window, 1), dtype),
                           plan.replicated))

    params = tables(seed, True)
    params.syn1.block_until_ready()
    lap("tables on device")
    trainer = Trainer(wcfg, vocab, plan=plan, params=params)
    lap("Trainer()")
    if trainer.params.syn0 is not params.syn0:
        raise RuntimeError("the trainer re-placed tables that were already placed")
    return trainer, tables, dict(v=v, d=d, pd=pd, k=k, half_width=half_width,
                                 strings=strings, window=wcfg.window,
                                 ngram=(cfg["subword_min_n"], cfg["subword_max_n"]))


def feed_blocks(trainer, sentences, steps: int):
    """The token feed's own first ``steps`` full blocks of the fit's first
    iteration (what the window's first steps train on), as the packer ships
    them: tokens [steps, T], sentence-start bits, ordinal bases."""
    t = trainer._tokens_per_step
    tokens, bits, obases = [], [], []
    stream = trainer._device_seg_blocks(sentences, 1, 0)
    try:
        for tok, start_bits, nvalid, obase, _ in stream:
            if nvalid != t:
                raise RuntimeError("the corpus is too short for the check's blocks")
            tokens.append(np.array(tok, np.int32))
            bits.append(np.array(start_bits, np.uint8))
            obases.append(int(obase))
            if len(tokens) == steps:
                break
    finally:
        stream.close()
    if len(tokens) < steps:
        raise RuntimeError("the corpus is too short for the check's blocks")
    return dict(tokens=np.stack(tokens), bits=np.stack(bits),
                obases=np.asarray([[ob & 0xFFFFFFFF, ob >> 32] for ob in obases],
                                  np.uint32).view(np.int32))


def window_examples(left, right, center, window: int):
    """Window draws [T] as plain examples: for every slot how many context
    words it has (0 where ``center`` is off and for a draw that gives none)
    and, left-packed in [T, 2*window], each context's slot of the block and
    its row of ``d`` (``cbow_subword_ref.position_row``)."""
    t = left.shape[0]
    left, right = (np.asarray(x, np.int64)[:, None] for x in (left, right))
    at = np.arange(t, dtype=np.int64)[:, None]
    p = np.asarray([q for q in range(-window, window + 1) if q], np.int64)[None, :]
    row = np.where(p < 0, p + window, p + window - 1)
    has = (np.asarray(center)[:, None] > 0) & np.where(p < 0, -p <= left, p <= right)
    order = np.argsort(~has, axis=1, kind="stable")             # left-packed
    return (has.sum(axis=1).astype(np.int32),
            np.take_along_axis(np.where(has, np.clip(at + p, 0, t - 1), 0),
                               order, axis=1).astype(np.int32),
            np.take_along_axis(np.where(has, row, 0), order, axis=1).astype(np.int32))


def draw_examples(trainer, blocks: dict):
    """The program's own window draws (``device_cbow_windows`` over its hash
    lattice, cut at the feed's own sentence starts) for each block, as
    :func:`window_examples` makes them plain."""
    import jax

    from glint_word2vec_tpu.data.hashrng import STREAM_WINDOW, stream_base
    from glint_word2vec_tpu.ops.pairgen import device_cbow_windows

    w = trainer.config.window
    t = blocks["tokens"].shape[1]
    win_base = np.uint32(stream_base(trainer.config.seed, STREAM_WINDOW, 1, 0))
    draw = jax.jit(lambda tk, sb, lo, hi: device_cbow_windows(
        tk, sb, np.int32(t), lo, hi, win_base, window=w, halo=w))
    out = []
    for tokens, bits, ob in zip(blocks["tokens"], blocks["bits"],
                                blocks["obases"].view(np.uint32)):
        band = draw(tokens, bits, ob[0], ob[1])
        left, right = np.asarray(band.left), np.asarray(band.right)
        assert left.max() <= w >= right.max() and np.asarray(band.token).all()
        out.append(window_examples(left, right, np.asarray(band.center), w))
    nctx, slots, rows = (np.stack(x) for x in zip(*out))
    return dict(nctx=nctx, ctx_slot=slots, ctx_pos=rows, win_base=win_base)


def reference_lists(sizes: dict, tokens, subword: bool = True):
    """Every block's words as the reference sees them: per step the block's
    distinct words (filled up to T entries that list nothing), each as the
    rows ITS n-gram function gives for the word's string, and every slot's
    index into them. ``subword`` off: a word lists its own row alone."""
    from reference import subword_ref

    v, k = sizes["v"], sizes["k"]
    steps, t = tokens.shape
    of_word = {int(w): (subword_ref.word_rows(sizes["strings"][w], int(w), v, k,
                                              *sizes["ngram"]) if subword else [int(w)])
               for w in np.unique(tokens)}
    longest = -(-max(len(r) for r in of_word.values()) // 8) * 8
    lists = np.zeros((steps, t, longest), np.int32)
    nrows = np.zeros((steps, t), np.int32)
    slot_word = np.zeros((steps, t), np.int32)
    for s in range(steps):
        ids = np.unique(tokens[s])
        for i, w in enumerate(ids):
            rows = of_word[int(w)]
            lists[s, i, :len(rows)], nrows[s, i] = rows, len(rows)
        slot_word[s] = np.searchsorted(ids, tokens[s])
    return dict(lists=lists, nrows=nrows, slot_word=slot_word)


def _fill(touched, total_rows: int, unit: int):
    """``touched`` rows filled up with untouched ones to a round count (so
    that it moves with the seed rarely, and the gathers and the reference
    compile anew rarely); an untouched row may not move."""
    count = -(-(touched.shape[0] + 1) // unit) * unit
    spare = np.setdiff1d(np.arange(min(2 * count, total_rows)), touched)
    return np.sort(np.concatenate(
        [touched, spare[:count - touched.shape[0]]])).astype(np.int32)


def drive_check_steps(trainer, tables, seed: int, blocks: dict, lists: dict):
    """Three steps through the trainer's OWN compiled dispatch, staged as the
    fit stages it (one dispatch whose first step is real and the rest masked,
    then one with two real steps), once through each twin from the same
    leaves. ``trainer.params`` holds the check's leaves on entry and nothing
    on return."""
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.ops.sampler import sample_negatives_hash
    from glint_word2vec_tpu.parallel.distributed import put_global

    cfg = trainer.config
    k, pool = cfg.steps_per_dispatch, cfg.negative_pool
    t = blocks["tokens"].shape[1]
    assert blocks["tokens"].shape[0] == CHECK_STEPS and k >= 2 and pool > 0
    draw = jax.jit(lambda prob, alias, base: sample_negatives_hash(
        prob, alias, np.uint32(cfg.seed & 0xFFFFFFFF), base, (k, pool)))
    plan = ((1, [0]), (2, [1, 2]))          # (PRNG base step, the real steps)
    negatives = np.concatenate([
        np.asarray(draw(trainer._table_prob, trainer._table_alias,
                        np.int32(base)))[:len(steps)] for base, steps in plan])
    in_list = (np.arange(lists["lists"].shape[-1])[None, None, :]
               < lists["nrows"][:, :, None])
    rows0 = _fill(np.unique(lists["lists"][in_list]),
                  trainer.params.syn0.shape[0], 1 << 16)
    rows1 = _fill(np.unique(np.concatenate([blocks["tokens"].ravel(),
                                            negatives.ravel()])),
                  trainer.params.syn1.shape[0], 1 << 14)
    rows0_dev, rows1_dev = jnp.asarray(rows0), jnp.asarray(rows1)
    win_bases = (np.zeros(1, np.uint32), np.asarray([blocks["win_base"]], np.uint32))

    lr = float(np.float32(cfg.learning_rate))
    losses, examples, snaps, rows_per_example = [], [], {}, []
    for prefix, name in TWINS:
        step_fn = getattr(trainer, name)
        if trainer.params is None:
            trainer.params = tables(seed, True)
        snaps[prefix] = []
        for base, steps in plan:
            arrays = {"tokens": np.zeros((k, 1, t), trainer._pair_dtype),
                      "starts": np.zeros((k, 1, blocks["bits"].shape[1]), np.uint8),
                      "obase": np.zeros((k, 1, 2), np.int32)}
            meta = np.zeros((2, k), np.float32)     # rows: learning rate, valid tokens
            for slot, s in enumerate(steps):
                arrays["tokens"][slot, 0] = blocks["tokens"][s]
                arrays["starts"][slot, 0] = blocks["bits"][s]
                arrays["obase"][slot, 0] = blocks["obases"][s]
                meta[:, slot] = lr, t
            staged = put_global(trainer._chunk_shardings, arrays)
            meta_dev, base_dev, *bases_dev = trainer._stage_dispatch_meta(
                meta, base, *win_bases)
            trainer.params, (metrics, _) = step_fn(
                trainer.params, staged, meta_dev, base_dev, trainer._table_prob,
                trainer._table_alias, trainer._keep_prob_dev, *bases_dev,
                *trainer._step_extra)
            pairs = np.asarray(metrics.pairs)[:len(steps)]
            examples += [float(x) for x in pairs]
            if not prefix:
                losses += [float(x) for x in np.asarray(metrics.loss)[:len(steps)]]
                rows_per_example += [float(x) / max(float(n), 1.0) for x, n in zip(
                    np.asarray(metrics.subword_rows)[:len(steps)], pairs)]
            snaps[prefix].append((trainer.params.syn0[rows0_dev],
                                  trainer.params.syn1[rows1_dev],
                                  jnp.copy(trainer.params.pos)))   # donated next
        # to the host, and wait: rows kept on the device, or tables still in use
        # by a step in flight when the next leaves are made, would raise the
        # memory peak above the fit's own
        snaps[prefix] = jax.device_get(snaps[prefix])
        trainer.params = None
    return dict(losses=losses, examples=examples, negatives=negatives, rows0=rows0,
                rows1=rows1, snaps=snaps, lr=lr, rows_per_example=rows_per_example)


def compare_with_reference(seed: int, sizes: dict, blocks: dict, ex: dict, lists: dict,
                           got: dict, num_negatives: int, limits: dict, checks: Checks):
    """The plain reference follows the same three steps on the rows they touch
    (made from the seed by the benchmark's own formula), and each number of
    the program's, for each twin of its step, is held to its limit."""
    import jax.numpy as jnp

    from reference import cbow_subword_ref as ref_model

    d, pd, v = sizes["d"], sizes["pd"], sizes["v"]
    rows0, rows1 = got["rows0"], got["rows1"]

    def into(rows, ids):
        return jnp.asarray(np.searchsorted(rows, ids).astype(np.int32))

    s32 = weights.seed32(seed)
    init0, init1 = (weights.rows_uniform(s32, stream, jnp.asarray(rows), d, pd,
                                         sizes["half_width"])[:, :d]
                    for stream, rows in ((0, rows0), (1, rows1)))
    init_d = position_rows(seed, sizes["window"], d, pd)[:, :d]
    is_word = rows0 < v
    ctx_word = np.stack([lists["slot_word"][s][ex["ctx_slot"][s]]
                         for s in range(CHECK_STEPS)])
    ref = ref_model.follow_steps(
        init0, init1, init_d, into(rows1, blocks["tokens"]), jnp.asarray(ctx_word),
        jnp.asarray(ex["ctx_pos"]), jnp.asarray(ex["nctx"]),
        into(rows0, lists["lists"]), jnp.asarray(lists["nrows"]),
        into(rows1, got["negatives"]), [got["lr"]] * CHECK_STEPS, num_negatives,
        is_word)

    def change_norms(snap):
        s0, s1, sd = snap
        d0 = jnp.asarray(s0[:, :d], jnp.float32) - init0
        return (ref_model.leaf_norm(jnp.where(is_word[:, None], d0, 0.0)),
                ref_model.leaf_norm(jnp.where(is_word[:, None], 0.0, d0)),
                ref_model.leaf_norm(jnp.asarray(s1[:, :d], jnp.float32) - init1),
                ref_model.leaf_norm(jnp.asarray(sd[:, :d], jnp.float32) - init_d))

    live = (ex["nctx"] > 0).sum(axis=1)
    print(f"check train_cbow_subword: losses program {got['losses']} reference "
          f"{ref['losses']}; live examples a step {live.tolist()} of "
          f"{ex['nctx'].shape[1]} slots; reference change norms {LEAVES} after one "
          f"step {ref['first_change_norm']}, after three {ref['change_norm']}; "
          f"subword rows an example {got['rows_per_example']}", flush=True)
    checks.add("loss_rel_gap", max(abs(p - w) / abs(w) for p, w in
                                   zip(got["losses"], ref["losses"])),
               limits["loss_rel_gap"])
    # both twins count the examples they trained: the draws' live centers, exactly
    checks.add("examples_abs_gap", max(abs(p - w) for p, w in zip(
        got["examples"], live.tolist() * len(TWINS))), 0.0)
    for prefix, snaps in got["snaps"].items():
        first, total = change_norms(snaps[0]), change_norms(snaps[1])
        print(f"check train_cbow_subword: {prefix or 'metrics_'}twin change norms "
              f"{LEAVES} after one step {first}, after three {total}", flush=True)
        # each leaf against its own reference norm: from these leaves no leaf's
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


def _check_inputs(trainer, sizes: dict, sentences):
    blocks = feed_blocks(trainer, sentences, CHECK_STEPS)
    ex = draw_examples(trainer, blocks)
    blocks["win_base"] = ex["win_base"]
    return blocks, ex, reference_lists(sizes, blocks["tokens"])


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed
    (benchmark/sweep_checks.py). The control is the program with its leaves in
    bfloat16. The words are the first seed's (one trainer, one row table); each
    seed brings its own corpus, feed blocks and leaves."""
    tf = sizes_of(cell["traffic"], tiny)
    trainer, tables, sizes = build_trainer(cell, seeds[0], tiny,
                                           param_dtype="bfloat16" if control else None)
    out = []
    for seed in seeds:
        if trainer.params is None:
            trainer.params = tables(seed, True)
        blocks, ex, lists = _check_inputs(trainer, sizes,
                                          make_corpus(seed, sizes["v"], tf))
        got = drive_check_steps(trainer, tables, seed, blocks, lists)
        gc.collect()
        checks = Checks()
        compare_with_reference(seed, sizes, blocks, ex, lists, got,
                               trainer.config.negatives, _NO_LIMIT, checks)
        out.append({name: value for name, value, _, _ in checks.rows})
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, tiny: bool,
        t_start: float) -> dict:
    import jax
    import jax.profiler as jp

    clog = CompileLog()
    laps = Laps(t_start)
    tf = sizes_of(cell["traffic"], tiny)
    trainer, tables, sizes = build_trainer(cell, seed, tiny, laps=laps)
    cfg = trainer.config
    sentences = make_corpus(seed, sizes["v"], tf)
    laps.lap("corpus")
    print(f"train_cbow_subword: resolved negative_pool={cfg.negative_pool} "
          f"subsample_ratio={cfg.subsample_ratio:.3e} params "
          f"{list(trainer.params.syn0.shape)} + {list(trainer.params.syn1.shape)} + "
          f"{list(trainer.params.pos.shape)} {trainer.params.syn0.dtype}; row table "
          f"{list(trainer._step_extra[1].shape)} in {trainer.subword_table_time:.2f}s, "
          f"{trainer._subword_shape}; {trainer._tokens_per_step} token slots a block",
          flush=True)

    blocks, ex, lists = _check_inputs(trainer, sizes, sentences)
    table_wrong = row_table_mismatches(trainer, sizes, seed)
    got = drive_check_steps(trainer, tables, seed, blocks, lists)
    trainer.params = tables(seed, False)
    laps.lap("check steps")

    step_fns = [getattr(trainer, name) for _, name in TWINS]
    counted = []
    for _, name in TWINS:
        setattr(trainer, name, _CountingStep(getattr(trainer, name), counted))
    if step_fns[0] is step_fns[1]:
        raise RuntimeError("this configuration has no metrics-elided step twin")

    budget = tf["loss_budget_steps"]
    slice_ = TracedSlice()
    st = dict(beats=0, nonfinite=0, open=None, close=None, loss_at_budget=None,
              slice_open=None, slice_close=None, losses=[])

    def mark(rec):
        jax.block_until_ready(trainer.params)
        return dict(t=time.perf_counter(), step=rec.global_step,
                    calls=len(counted), wait=trainer.host_wait_time)

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
    steps = c["step"] - o["step"]
    # the device's own count of the examples each dispatch of the window trained
    examples = float(np.sum(jax.device_get(counted[o["calls"]:c["calls"]])))
    slots = steps * cfg.pairs_per_batch
    late = clog.between(o["t"], c["t"])
    twins = {fn._cache_size() for fn in step_fns}
    print(f"train_cbow_subword: window {window_s:.3f}s, {steps} steps in "
          f"{c['calls'] - o['calls']} dispatches, {examples:.0f} examples in {slots} "
          f"slots, {len(late)} compilations inside it {late}; set-up compiled "
          f"{len(clog.compiles)} programs, persistent cache {clog.cache_hits} hits / "
          f"{clog.cache_misses} misses; step programs per twin {twins}", flush=True)
    print("train_cbow_subword: heartbeat (step, loss) "
          f"{[(s, round(x, 5)) for s, x in st['losses']]}", flush=True)
    peak = memory_peak_bytes()
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    counters = {"host_wait_s": c["wait"] - o["wait"], "window_s": window_s,
                "examples": examples, "slots": slots,
                "subword_table_s": trainer.subword_table_time, "one": 1}
    if sl:
        counters["slice_calls"] = st["slice_close"]["step"] - st["slice_open"]["step"]

    # free the program's leaves, then let the reference follow the three steps
    num_negatives = cfg.negatives
    live = (ex["nctx"] > 0).sum(axis=1)
    shapes = dict(tokens_per_block=blocks["tokens"].shape[1], window=cfg.window,
                  pool=cfg.negative_pool, padded_dim=sizes["pd"],
                  param_dtype=str(cfg.param_dtype),
                  subword_rows_per_block=float(np.mean(
                      [r * n for r, n in zip(got["rows_per_example"], live)])))
    counted.clear()
    trainer.params = None
    trainer._step_extra = ()
    del trainer
    gc.collect()
    checks = Checks()
    compare_with_reference(seed, sizes, blocks, ex, lists, got, num_negatives,
                           sizes_of(cell["config"], tiny)["check"]["train"], checks)
    checks.add("row_table_mismatches", table_wrong, 0)
    checks.add("compilations_in_window", len(late), 0)
    checks.add("step_programs_per_twin", max(twins), 1)
    # a step trains at most B examples: the count the rate rests on may not run
    # ahead of the steps the heartbeats counted
    checks.add("examples_over_steps_times_batch",
               examples / slots if steps else 2.0, 1.0)
    reached = st["loss_at_budget"] is not None
    if not reached:
        print(f"train_cbow_subword: the fit never reached the budget step {budget}",
              flush=True)
    end_to_end = {"train_pairs_per_s": examples / window_s,
                  "setup_s": o["t"] - t_start}
    if reached:
        end_to_end["train_loss_at_budget"] = st["loss_at_budget"]
    return dict(correct=checks.ok and reached and st["nonfinite"] == 0,
                attempted=steps, failed=st["nonfinite"] + (0 if reached else 1),
                end_to_end=end_to_end, counters=counters, shapes=shapes, slice=sl,
                memory_peak_bytes=peak)
