"""Kind ``train_cbow``: a seeded Zipf corpus through ``Trainer.fit`` with CBOW on.

As kind ``train`` (kinds/train.py, whose corpus, step-twin names and
window-closing exception this imports): tables made on the device from
``--seed`` and handed over placed, a check of three steps through the
trainer's own compiled dispatch before the fit, ``gc.freeze()``, a window opened and closed on ``block_until_ready`` of the
params at a heartbeat, and the plain reference (reference/cbow_ref.py) after the
trainer's tables are freed. What CBOW changes:

- The configuration names the step form, ``cbow_update``: ``scatter`` (grouped
  [B, 2*window] context batches from the host feed) or ``banded`` (kept-token
  blocks, windows derived on the device). Each has its own dispatch signature,
  and the check stages either as the fit stages it.
- One "pair" of ``train_pairs_per_s`` is one CBOW example: a center that has at
  least one context word. The banded fit's ``pairs_trained`` is an estimate
  while it runs (its exact books are settled at the end of a fit, which a window
  never reaches), so examples are counted from the device's own ``pairs`` of
  every dispatch, on both forms: the two step functions are wrapped to keep them.
- The check's examples are windows over a seeded block of B + 2*window tokens
  (all different within a block, in sentences of the corpus's length), drawn by
  the program's own ``device_cbow_windows`` and handed to both forms and, as plain
  (center, context list) examples, to the reference. A center whose draw gives no
  context is among them and trains nothing, in either form.
"""

import gc
import math
import time

import numpy as np

from harness import weights, zipf
from harness.common import Checks, CompileLog, Laps, TracedSlice, memory_peak_bytes
from harness.loader import sizes as sizes_of
from kinds.train import _NO_LIMIT, TWINS, _WindowClosed, make_corpus

CHECK_STEPS = 3


def build_trainer(cell: dict, seed: int, tiny: bool, param_dtype=None, laps=None):
    """The CBOW trainer over its vocabulary, holding the check's tables for
    ``seed``, and ``tables(seed, for_check)``, which makes it another pair in
    their place. ``param_dtype`` overrides the configuration's only for the
    lower-precision control."""
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
    counts = zipf.zipf_counts(v)
    vocab = Vocabulary.from_words_and_counts(zipf.words_of(v), counts.astype(np.int64))
    lap("vocabulary")

    nd, nm = cfg["mesh"]
    wcfg = Word2VecConfig(
        vector_size=d, window=cfg["window"], negatives=cfg["negatives"], min_count=1,
        cbow=cfg["cbow"], cbow_update=cfg["cbow_update"],
        param_dtype=param_dtype or cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"], logits_dtype=cfg["logits_dtype"],
        pairs_per_batch=tf["pairs_per_batch"],
        steps_per_dispatch=tf["steps_per_dispatch"],
        heartbeat_every_steps=tf["heartbeat_every_steps"],
        num_iterations=tf["num_iterations"], seed=cfg["program_seed"],
        num_data_shards=nd, num_model_shards=nm,
        # AUTO unless the sizes say otherwise (the tiny block does: AUTO turns
        # the shared pool off at toy batches)
        **{k: tf[k] for k in ("negative_pool", "subsample_ratio") if k in tf})
    plan = make_mesh(nd, nm)
    pv = pad_vocab_for_sharding(v, nm)
    pd = pad_dim_to_lanes(d, wcfg.pad_vector_to_lanes)
    dtype = jnp.dtype(wcfg.param_dtype)
    half_width = cfg["check_state"]["half_width"]

    def tables(seed: int, for_check: bool):
        """The check's pair (both seeded, a trained model's magnitude) or the
        pair word2vec starts a fit from (syn0 small, syn1 zero)."""
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
                                 sentence_length=tf["sentence_length"])


def check_blocks(seed: int, v: int, t: int, steps: int):
    """``steps`` blocks of T tokens that all differ within a block; every step
    holds the same words in a new order, so later steps see rows that earlier
    ones moved. Windows overlap, so a word is context to several examples."""
    rng = np.random.default_rng([seed, 0xCB0])
    base = rng.permutation(v)[:t]
    return np.stack([base[rng.permutation(t)] for _ in range(steps)]).astype(np.int32)


def draw_windows(trainer, blocks, sentence_length: int, seed: int):
    """The program's own window draws (``device_cbow_windows`` over its hash
    lattice) for each block, cut at sentences of ``sentence_length``, turned into
    plain examples: for each of the B core slots its center word, its context
    words left-packed in [B, 2*window], and how many of them there are (0 for a
    draw that gives no context). Also what the banded dispatch ships: the packed
    sentence-start bits and each block's ordinal base."""
    import jax

    from glint_word2vec_tpu.data.hashrng import STREAM_WINDOW, stream_base
    from glint_word2vec_tpu.ops.pairgen import device_cbow_windows

    w = trainer.config.window
    steps, t = blocks.shape
    core = t - 2 * w
    bits = np.packbits(np.arange(t) % sentence_length == 0, bitorder="little")
    win_base = np.uint32(stream_base(trainer.config.seed, STREAM_WINDOW, 1, 0))
    # kept-token ordinals as the feed counts them, from an offset of the seed's
    first = (int(seed) * 0x9E3779B1) & 0x3FFFFFFFFFFF
    obases = [(first + s * core) & 0xFFFFFFFFFFFFFFFF for s in range(steps)]
    draw = jax.jit(lambda tk, sb, lo, hi: device_cbow_windows(
        tk, sb, np.int32(t), lo, hi, win_base, window=w, halo=w))
    slot = np.arange(w, t - w, dtype=np.int64)[:, None]
    j = np.arange(2 * w, dtype=np.int64)[None, :]
    centers, contexts, nctx = [], [], []
    for tokens, ob in zip(blocks, obases):
        band = draw(tokens, bits, np.uint32(ob & 0xFFFFFFFF), np.uint32(ob >> 32))
        left = np.asarray(band.left, np.int64)[w:t - w, None]
        right = np.asarray(band.right, np.int64)[w:t - w, None]
        assert np.asarray(band.center)[w:t - w].all() and left.max() <= w > right.max()
        at = np.where(j < left, slot - left + j, slot + j - left + 1)
        listed = j < left + right
        centers.append(tokens[w:t - w])
        contexts.append(np.where(listed, tokens[np.clip(at, 0, t - 1)], 0))
        nctx.append((left + right)[:, 0])
    return dict(centers=np.stack(centers).astype(np.int32),
                contexts=np.stack(contexts).astype(np.int32),
                nctx=np.stack(nctx).astype(np.int32), bits=bits, win_base=win_base,
                obases=np.asarray([[ob & 0xFFFFFFFF, ob >> 32] for ob in obases],
                                  np.uint32).view(np.int32))


def _stage_check_dispatch(trainer, blocks, ex, steps, base, lr):
    """One dispatch of the check as the fit stages one: ``steps`` are real, the
    other slots of the K are masked (no tokens, no examples, rate 0). Returns the
    arguments of the step function after ``params``."""
    from glint_word2vec_tpu.parallel.distributed import put_global

    cfg = trainer.config
    k, b = cfg.steps_per_dispatch, cfg.pairs_per_batch
    if trainer._banded_cbow:
        t = blocks.shape[1]
        arrays = {"tokens": np.zeros((k, 1, t), trainer._pair_dtype),
                  "starts": np.zeros((k, 1, ex["bits"].shape[0]), np.uint8),
                  "obase": np.zeros((k, 1, 2), np.int32)}
        meta = np.zeros((2, k), np.float32)       # rows: learning rate, valid tokens
        for slot, s in enumerate(steps):
            arrays["tokens"][slot, 0] = blocks[s]
            arrays["starts"][slot, 0] = ex["bits"]
            arrays["obase"][slot, 0] = ex["obases"][s]
            meta[:, slot] = lr, t
        bases = (np.zeros(1, np.uint32), np.asarray([ex["win_base"]], np.uint32))
    else:
        arrays = {"centers": np.zeros((k, b), trainer._pair_dtype),
                  "contexts": np.zeros((k, b, 2 * cfg.window), trainer._pair_dtype),
                  "nctx": np.zeros((k, b), np.uint8)}
        meta = np.zeros((2, k), np.float32)       # rows: learning rate, real examples
        for slot, s in enumerate(steps):
            for name in arrays:
                arrays[name][slot] = ex[name][s]
            meta[:, slot] = lr, b
        bases = ()
    staged = put_global(trainer._chunk_shardings, arrays)
    meta_dev, base_dev, *bases_dev = trainer._stage_dispatch_meta(meta, base, *bases)
    args = [staged, meta_dev, base_dev, trainer._table_prob, trainer._table_alias]
    if trainer._banded_cbow:
        args += [trainer._keep_prob_dev, *bases_dev]
    return args


def drive_check_steps(trainer, tables, seed: int, blocks, ex):
    """Three steps through the trainer's OWN compiled dispatch, staged as the fit
    stages it: one dispatch whose first step is real and the rest masked, then
    one with two real steps. The fit alternates two compiled twins of the step
    (``_step_fn`` reports the loss, ``_step_fn_fast`` elides it), so the three
    steps go through each, from the same tables; ``trainer.params`` holds the
    check's tables on entry and nothing on return. Returns the program's side of
    the comparison and the negatives its sampler drew."""
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.ops.sampler import sample_negatives_hash

    cfg = trainer.config
    k, b, pool = cfg.steps_per_dispatch, cfg.pairs_per_batch, cfg.negative_pool
    assert ex["centers"].shape == (CHECK_STEPS, b) and k >= 2 and pool > 0
    draw = jax.jit(lambda prob, alias, base: sample_negatives_hash(
        prob, alias, np.uint32(cfg.seed & 0xFFFFFFFF), base, (k, pool)))
    plan = ((1, [0]), (2, [1, 2]))          # (PRNG base step, the real steps)
    negatives = np.concatenate([
        np.asarray(draw(trainer._table_prob, trainer._table_alias,
                        np.int32(base)))[:len(steps)] for base, steps in plan])
    # the rows compared: every row a step touches, filled up with untouched rows
    # to one fixed count (a count that moved with the seed would compile the
    # gathers and the reference anew in every run); an untouched row may not move
    rows0 = np.unique(blocks)
    touched = np.unique(np.concatenate([rows0, negatives.ravel()]))
    count = rows0.shape[0] + CHECK_STEPS * pool
    spare = np.setdiff1d(np.arange(2 * count), touched)[:count - touched.shape[0]]
    rows1 = np.sort(np.concatenate([touched, spare])).astype(np.int32)
    assert rows0.shape[0] == blocks.shape[1] and rows1.shape[0] == count
    rows0_dev, rows1_dev = jnp.asarray(rows0), jnp.asarray(rows1)

    lr = float(np.float32(cfg.learning_rate))
    losses, examples, snaps = [], [], {}
    for prefix, name in TWINS:
        step_fn = getattr(trainer, name)
        if trainer.params is None:
            trainer.params = tables(seed, True)
        snaps[prefix] = []
        for base, steps in plan:
            args = _stage_check_dispatch(trainer, blocks, ex, steps, base, lr)
            trainer.params, out = step_fn(trainer.params, *args)
            metrics = out if hasattr(out, "pairs") else out[0]
            if not prefix:
                losses += [float(x) for x in np.asarray(metrics.loss)[:len(steps)]]
            examples += [float(x) for x in np.asarray(metrics.pairs)[:len(steps)]]
            snaps[prefix].append((trainer.params.syn0[rows0_dev],
                                  trainer.params.syn1[rows1_dev]))
        # to the host, and wait: rows kept on the device, or tables still in use
        # by a step in flight when the next pair is made, would raise the memory
        # peak above the fit's own
        snaps[prefix] = jax.device_get(snaps[prefix])
        trainer.params = None
    return dict(losses=losses, examples=examples, negatives=negatives, rows0=rows0,
                rows1=rows1, snaps=snaps, lr=lr)


def compare_with_reference(seed: int, sizes: dict, ex: dict, got: dict,
                           num_negatives: int, limits: dict, checks: Checks):
    """The plain reference follows the same three steps on the rows they touch
    (made from the seed by the benchmark's own formula), and each number of the
    program's, for each twin of its step, is held to its limit."""
    import jax.numpy as jnp

    from reference import cbow_ref

    d, pd = sizes["d"], sizes["pd"]
    rows0, rows1 = got["rows0"], got["rows1"]

    def into(rows, words):
        return jnp.asarray(np.minimum(np.searchsorted(rows, words),
                                      rows.shape[0] - 1).astype(np.int32))

    s32 = weights.seed32(seed)
    init0, init1 = (weights.rows_uniform(s32, stream, jnp.asarray(rows), d, pd,
                                         sizes["half_width"])[:, :d]
                    for stream, rows in ((0, rows0), (1, rows1)))
    ref = cbow_ref.follow_steps(
        init0, init1, into(rows1, ex["centers"]), into(rows0, ex["contexts"]),
        jnp.asarray(ex["nctx"]), into(rows1, got["negatives"]),
        [got["lr"]] * CHECK_STEPS, num_negatives)

    def change_norms(snap):
        s0, s1 = snap
        return (cbow_ref.leaf_norm(jnp.asarray(s0[:, :d], jnp.float32) - init0),
                cbow_ref.leaf_norm(jnp.asarray(s1[:, :d], jnp.float32) - init1))

    def worst_leaf_gap(prog, want):
        # each leaf against its own reference norm: from these tables neither
        # leaf's change is near zero, and a leaf that never moved reads 1
        return max(abs(p - w) / max(w, 1e-30) for p, w in zip(prog, want))

    live = (ex["nctx"] > 0).sum(axis=1)
    print(f"check train_cbow: losses program {got['losses']} reference "
          f"{ref['losses']}; live examples a step {live.tolist()} of "
          f"{ex['nctx'].shape[1]}; reference change norms (syn0, syn1) after one "
          f"step {ref['first_change_norm']}, after three {ref['change_norm']}",
          flush=True)
    checks.add("loss_rel_gap", max(abs(p - w) / abs(w) for p, w in
                                   zip(got["losses"], ref["losses"])),
               limits["loss_rel_gap"])
    # both twins count the examples they trained: the draws' live centers, exactly
    checks.add("examples_abs_gap", max(abs(p - w) for p, w in zip(
        got["examples"], live.tolist() * len(TWINS))), 0.0)
    for prefix, snaps in got["snaps"].items():
        first, total = change_norms(snaps[0]), change_norms(snaps[1])
        print(f"check train_cbow: {prefix or 'metrics_'}twin change norms (syn0, "
              f"syn1) after one step {first}, after three {total}", flush=True)
        # the first gradient as the optimizer gets it is the first step's change
        # over its learning rate: the rate cancels in the relative gap
        checks.add(prefix + "first_gradient_norm_gap",
                   worst_leaf_gap(first, ref["first_change_norm"]),
                   limits["first_gradient_norm_gap"])
        checks.add(prefix + "change_norm_gap", worst_leaf_gap(total, ref["change_norm"]),
                   limits["change_norm_gap"])
    pad = max(float(np.abs(s[:, d:].astype(np.float32)).max()) if pd > d else 0.0
              for snaps in got["snaps"].values() for snap in snaps for s in snap)
    checks.add("padding_abs_max", pad, 0.0)


def _check_inputs(trainer, sizes: dict, seed: int):
    cfg = trainer.config
    blocks = check_blocks(seed, sizes["v"], cfg.pairs_per_batch + 2 * cfg.window,
                          CHECK_STEPS)
    return blocks, draw_windows(trainer, blocks, sizes["sentence_length"], seed)


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed, all on one
    trainer (benchmark/sweep_checks.py). The control is the program with its
    tables in bfloat16."""
    trainer, tables, sizes = build_trainer(cell, seeds[0], tiny,
                                           param_dtype="bfloat16" if control else None)
    out = []
    for seed in seeds:
        if trainer.params is None:
            trainer.params = tables(seed, True)
        blocks, ex = _check_inputs(trainer, sizes, seed)
        got = drive_check_steps(trainer, tables, seed, blocks, ex)
        gc.collect()
        checks = Checks()
        compare_with_reference(seed, sizes, ex, got, trainer.config.negatives,
                               _NO_LIMIT, checks)
        out.append({name: value for name, value, _, _ in checks.rows})
    return out


class _CountingStep:
    """A step function of the trainer's that keeps the device's own count of
    examples (``StepMetrics.pairs``, [K] a dispatch) of every call, unfetched."""

    def __init__(self, fn, log: list):
        self.fn, self.log = fn, log

    def __call__(self, *args):
        out = self.fn(*args)
        metrics = out[1] if hasattr(out[1], "pairs") else out[1][0]
        self.log.append(metrics.pairs)
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
    print(f"train_cbow: cbow_update={cfg.cbow_update} resolved negative_pool="
          f"{cfg.negative_pool} subsample_ratio={cfg.subsample_ratio:.3e} params "
          f"{list(trainer.params.syn0.shape)} {trainer.params.syn0.dtype} mesh "
          f"{trainer.plan.num_data}x{trainer.plan.num_model}", flush=True)

    blocks, ex = _check_inputs(trainer, sizes, seed)
    got = drive_check_steps(trainer, tables, seed, blocks, ex)
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
    print(f"train_cbow: window {window_s:.3f}s, {steps} steps in "
          f"{c['calls'] - o['calls']} dispatches, {examples:.0f} examples in {slots} "
          f"slots, {len(late)} compilations inside it {late}; set-up compiled "
          f"{len(clog.compiles)} programs, persistent cache {clog.cache_hits} hits / "
          f"{clog.cache_misses} misses; step programs per twin {twins}", flush=True)
    print("train_cbow: heartbeat (step, loss) "
          f"{[(s, round(x, 5)) for s, x in st['losses']]}", flush=True)
    peak = memory_peak_bytes()
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    counters = {"host_wait_s": c["wait"] - o["wait"], "window_s": window_s,
                "examples": examples, "slots": slots}
    if sl:
        counters["slice_calls"] = st["slice_close"]["step"] - st["slice_open"]["step"]

    # free the program's tables, then let the reference follow the three steps
    num_negatives = cfg.negatives
    shapes = dict(pairs_per_batch=cfg.pairs_per_batch, window=cfg.window,
                  pool=cfg.negative_pool, padded_dim=sizes["pd"],
                  param_dtype=str(cfg.param_dtype))
    counted.clear()
    trainer.params = None
    del trainer
    gc.collect()
    checks = Checks()
    compare_with_reference(seed, sizes, ex, got, num_negatives,
                           sizes_of(cell["config"], tiny)["check"]["train"], checks)
    checks.add("compilations_in_window", len(late), 0)
    checks.add("step_programs_per_twin", max(twins), 1)
    # a step trains at most B examples: the count the rate rests on may not run
    # ahead of the steps the heartbeats counted
    checks.add("examples_over_steps_times_batch",
               examples / slots if steps else 2.0, 1.0)
    reached = st["loss_at_budget"] is not None
    if not reached:
        print(f"train_cbow: the fit never reached the budget step {budget}", flush=True)
    end_to_end = {"train_pairs_per_s": examples / window_s,
                  "setup_s": o["t"] - t_start}
    if reached:
        end_to_end["train_loss_at_budget"] = st["loss_at_budget"]
    return dict(correct=checks.ok and reached and st["nonfinite"] == 0,
                attempted=steps, failed=st["nonfinite"] + (0 if reached else 1),
                end_to_end=end_to_end, counters=counters, shapes=shapes, slice=sl,
                memory_peak_bytes=peak)
