"""Kind ``train``: a seeded Zipf corpus through the program's own ``Trainer.fit``.

Set-up: vocabulary from counts, corpus of int32 sentences from ``--seed``, both
tables made on the device from ``--seed`` (harness/weights.py) and handed to the
``Trainer`` already padded and placed. The check's three steps go through that
trainer's own compiled dispatch, once through each of its two step twins, from
tables of a trained model's magnitude (both seeded and non-zero: at word2vec's
start, syn1 = 0, every logit is 0 and syn0 hardly moves, so a check from there
cannot see syn0's update). The same trainer then gets the tables word2vec starts
from (syn0 small, syn1 zero), the fit warms up through its first heartbeats,
and the window opens and closes on ``block_until_ready`` of the params at a
heartbeat. The fit is ended by an exception of the benchmark's own raised from
the heartbeat callback. Once the trainer's tables are freed, the plain
reference follows the same three steps.
"""

import gc
import math
import time

import numpy as np

from harness import weights, zipf
from harness.loader import sizes as sizes_of
from harness.common import Checks, CompileLog, Laps, TracedSlice, memory_peak_bytes


class _WindowClosed(Exception):
    pass


def make_corpus(seed: int, v: int, tf: dict) -> list:
    tokens = zipf.draw(np.random.default_rng(seed), v, tf["corpus_tokens"])
    step = tf["sentence_length"]
    return [tokens[i:i + step] for i in range(0, tokens.shape[0], step)]


def build_trainer(cell: dict, seed: int, tiny: bool, param_dtype=None, laps=None):
    """The trainer over its vocabulary, holding the check's tables for ``seed``,
    and ``tables(seed, for_check)``, which makes it another pair in their place.
    ``param_dtype`` overrides the configuration's only for the lower-precision
    control."""
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
    return trainer, tables, dict(v=v, d=d, pd=pd, half_width=half_width)


def check_batches(seed: int, v: int, b: int, steps: int):
    """``steps`` batches of B pairs whose rows all differ within a batch; every
    step pairs the same rows anew, so later steps see rows that earlier ones moved."""
    rng = np.random.default_rng([seed, 0xC4EC])
    base_c, base_x = rng.permutation(v)[:b], rng.permutation(v)[:b]
    centers = np.stack([base_c[rng.permutation(b)] for _ in range(steps)])
    contexts = np.stack([base_x[rng.permutation(b)] for _ in range(steps)])
    return centers.astype(np.int32), contexts.astype(np.int32)


TWINS = (("", "_step_fn"), ("fast_", "_step_fn_fast"))


def drive_check_steps(trainer, tables, seed: int, centers, contexts):
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
    from glint_word2vec_tpu.parallel.distributed import put_global

    cfg = trainer.config
    k, b = cfg.steps_per_dispatch, cfg.pairs_per_batch
    assert centers.shape == (3, b) and k >= 2
    pool = cfg.negative_pool
    draw = jax.jit(lambda prob, alias, base: sample_negatives_hash(
        prob, alias, np.uint32(cfg.seed & 0xFFFFFFFF), base, (k, pool)))
    plan = ((1, [0]), (2, [1, 2]))          # (PRNG base step, the real steps)
    negatives = np.concatenate([
        np.asarray(draw(trainer._table_prob, trainer._table_alias,
                        np.int32(base)))[:len(steps)] for base, steps in plan])
    # the rows compared: every row a step touches, filled up with untouched rows
    # to one fixed count (a count that moved with the seed would compile the
    # gathers and the reference anew in every run); an untouched row may not move
    rows0 = np.unique(centers)
    touched = np.unique(np.concatenate([contexts.ravel(), negatives.ravel()]))
    count = b + 3 * pool
    spare = np.setdiff1d(np.arange(2 * count), touched)[:count - touched.shape[0]]
    rows1 = np.sort(np.concatenate([touched, spare])).astype(np.int32)
    assert rows0.shape[0] == b and rows1.shape[0] == count
    rows0_dev, rows1_dev = jnp.asarray(rows0), jnp.asarray(rows1)

    lr = float(np.float32(cfg.learning_rate))
    losses, snaps = [], {}
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
                trainer._table_prob, trainer._table_alias)
            if not prefix:
                losses += [float(x) for x in np.asarray(metrics.loss)[:len(steps)]]
            snaps[prefix].append((trainer.params.syn0[rows0_dev],
                                  trainer.params.syn1[rows1_dev]))
        # to the host, and wait: rows kept on the device, or tables still in use
        # by a step in flight when the next pair is made, would raise the memory
        # peak above the fit's own
        snaps[prefix] = jax.device_get(snaps[prefix])
        trainer.params = None
    return dict(losses=losses, negatives=negatives, rows0=rows0, rows1=rows1,
                snaps=snaps, lr=lr)


def compare_with_reference(seed: int, sizes: dict, centers, contexts, got: dict,
                           num_negatives: int, limits: dict, checks: Checks):
    """The plain reference follows the same three steps on the rows they touch
    (made from the seed by the benchmark's own formula), and each number of the
    program's, for each twin of its step, is held to its limit."""
    import jax.numpy as jnp

    from reference import sgns_ref

    d, pd = sizes["d"], sizes["pd"]
    negs = got["negatives"]
    rows0, rows1 = got["rows0"], got["rows1"]
    c_idx = np.searchsorted(rows0, centers).astype(np.int32)
    x_idx = np.searchsorted(rows1, contexts).astype(np.int32)
    n_idx = np.searchsorted(rows1, negs).astype(np.int32)
    s32 = weights.seed32(seed)
    init0, init1 = (weights.rows_uniform(s32, stream, jnp.asarray(rows), d, pd,
                                         sizes["half_width"])[:, :d]
                    for stream, rows in ((0, rows0), (1, rows1)))
    ref = sgns_ref.follow_steps(init0, init1, jnp.asarray(c_idx), jnp.asarray(x_idx),
                                jnp.asarray(n_idx), [got["lr"]] * 3, num_negatives)

    def change_norms(snap):
        s0, s1 = snap
        return (sgns_ref.leaf_norm(jnp.asarray(s0[:, :d], jnp.float32) - init0),
                sgns_ref.leaf_norm(jnp.asarray(s1[:, :d], jnp.float32) - init1))

    def worst_leaf_gap(prog, want):
        # each leaf against its own reference norm: from these tables neither
        # leaf's change is near zero, and a leaf that never moved reads 1
        return max(abs(p - w) / max(w, 1e-30) for p, w in zip(prog, want))

    print(f"check train: losses program {got['losses']} reference {ref['losses']}; "
          f"reference change norms (syn0, syn1) after one step "
          f"{ref['first_change_norm']}, after three {ref['change_norm']}", flush=True)
    checks.add("loss_rel_gap", max(abs(p - w) / abs(w) for p, w in
                                   zip(got["losses"], ref["losses"])),
               limits["loss_rel_gap"])
    for prefix, snaps in got["snaps"].items():
        first, total = change_norms(snaps[0]), change_norms(snaps[1])
        print(f"check train: {prefix or 'metrics_'}twin change norms (syn0, syn1) "
              f"after one step {first}, after three {total}", flush=True)
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


_NO_LIMIT = {"loss_rel_gap": float("inf"), "first_gradient_norm_gap": float("inf"),
             "change_norm_gap": float("inf")}


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed, all on one
    trainer (benchmark/sweep_checks.py). The control is the program with its
    tables in bfloat16."""
    trainer, tables, sizes = build_trainer(cell, seeds[0], tiny,
                                           param_dtype="bfloat16" if control else None)
    cfg = trainer.config
    out = []
    for seed in seeds:
        if trainer.params is None:
            trainer.params = tables(seed, True)
        centers, contexts = check_batches(seed, sizes["v"], cfg.pairs_per_batch, 3)
        got = drive_check_steps(trainer, tables, seed, centers, contexts)
        gc.collect()
        checks = Checks()
        compare_with_reference(seed, sizes, centers, contexts, got, cfg.negatives,
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
    print(f"train: resolved negative_pool={cfg.negative_pool} subsample_ratio="
          f"{cfg.subsample_ratio:.3e} params {list(trainer.params.syn0.shape)} "
          f"{trainer.params.syn0.dtype} mesh {trainer.plan.num_data}x"
          f"{trainer.plan.num_model}", flush=True)

    centers, contexts = check_batches(seed, sizes["v"], cfg.pairs_per_batch, 3)
    got = drive_check_steps(trainer, tables, seed, centers, contexts)
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
    print(f"train: window {window_s:.3f}s, {steps} steps, {pairs:.0f} pairs, "
          f"{len(late)} compilations inside it {late}; set-up compiled "
          f"{len(clog.compiles)} programs, persistent cache {clog.cache_hits} hits / "
          f"{clog.cache_misses} misses; step programs per twin {twins}", flush=True)
    print("train: heartbeat (step, loss) "
          f"{[(s, round(x, 5)) for s, x in st['losses']]}", flush=True)
    peak = memory_peak_bytes()
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    counters = {"host_wait_s": c["wait"] - o["wait"], "window_s": window_s}
    if sl:
        counters["slice_calls"] = st["slice_close"]["step"] - st["slice_open"]["step"]

    # free the program's tables, then let the reference follow the three steps
    num_negatives = cfg.negatives
    shapes = dict(pairs_per_batch=cfg.pairs_per_batch, pool=cfg.negative_pool,
                  padded_dim=sizes["pd"], param_dtype=str(cfg.param_dtype),
                  chips=cell["chips"] if not tiny else 1)
    trainer.params = None
    del trainer
    gc.collect()
    checks = Checks()
    compare_with_reference(seed, sizes, centers, contexts, got, num_negatives,
                           sizes_of(cell["config"], tiny)["check"]["train"], checks)
    checks.add("compilations_in_window", len(late), 0)
    checks.add("step_programs_per_twin", max(twins), 1)
    # every full batch holds exactly B real pairs: the pair count the rate rests
    # on may not run ahead of the steps the heartbeats counted
    checks.add("pairs_over_steps_times_batch",
               pairs / (steps * cfg.pairs_per_batch) if steps else 2.0, 1.0)
    reached = st["loss_at_budget"] is not None
    if not reached:
        print(f"train: the fit never reached the budget step {budget}", flush=True)
    end_to_end = {"train_pairs_per_s": pairs / window_s,
                  "setup_s": o["t"] - t_start}
    if reached:
        end_to_end["train_loss_at_budget"] = st["loss_at_budget"]
    return dict(correct=checks.ok and reached and st["nonfinite"] == 0,
                attempted=steps, failed=st["nonfinite"] + (0 if reached else 1),
                end_to_end=end_to_end, counters=counters, shapes=shapes, slice=sl,
                memory_peak_bytes=peak)
