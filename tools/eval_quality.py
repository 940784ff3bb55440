"""Realistic-scale training quality evaluation (round-3, VERDICT item 3).

The BASELINE real corpora (text8, enwiki) are NOT reachable in this environment: the
build sandbox has zero network egress and no copy exists on disk (verified by a
filesystem-wide search). This harness substitutes the closest honest thing: a corpus at
**text8 scale** (17M words, ~70k effective vocabulary) drawn from a fully-specified
generative topic model, so embedding quality is *quantitatively* measurable against the
generator's ground truth instead of eyeballed. When a real text8 is available, drop it
at --corpus and the same pipeline trains on it unchanged (quality metrics then need an
external word-sim dataset; the throughput numbers stay comparable).

Generative model (deterministic given --seed):
    - V_raw word types with Zipf marginals p(r) ∝ 1/(r+10)^1.05 (text8-like head/tail)
    - the S most frequent types are topic-neutral "stopwords"
    - every other type r belongs to topic (r mod T); names encode the topic
      ("t017_w000421") so ground truth travels with the corpus file itself
    - each sentence draws one topic z; every word is, with prob λ, drawn from the
      renormalized marginals of topic z's own words, else from the global marginals
      (stopword/noise mass)

A good embedding must therefore cluster same-topic words. Metrics (ground truth = the
name prefix; random-vector baseline ≈ 1/T):
    - purity@10: fraction of a word's 10 cosine-nearest non-stopword neighbors sharing
      its topic, averaged over 2,000 mid-frequency probe words
    - margin: mean within-topic cosine minus mean cross-topic cosine over the probes

The run exercises the production ingestion path end-to-end: token FILE →
TokenFileCorpus → streaming vocab pass → encode_corpus (memory-mapped shards) →
Trainer → model ops. Prints one JSON line on stdout; progress goes to stderr.

Usage:
    python tools/eval_quality.py [--words 17000000] [--out /tmp/eval_corpus]
                                 [--corpus existing.txt] [--dim 100] [--iters 3]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_here))

T_TOPICS = 128
STOPWORDS = 200
LAMBDA = 0.72
SENT_LEN = 35
V_RAW = 90_000   # raw types; min_count=5 trims the tail to ~text8's ~70k

# Relational structure — the synthetic analog of the toy corpus's country/capital
# pairs (it spec:22-37): entity pair members co-occur with a topic's words; a-words
# additionally co-occur with a role-A word set, b-words with role-B, so the
# embedding must place b_i - a_i ≈ roleB - roleA — exactly what the reference's
# analogy gate (wien - österreich + deutschland ≈ berlin, it spec:327-352)
# measures, quantitatively at 90k-vocab scale with accuracy@1.
#
# v2 (round-5, VERDICT item 4): the v1 gate saturated (every 90k headline run
# scored acc@1 = 1.000 — it could no longer rank configs). v2 hardens it with
# THREE relation families, each with its OWN role-word sets (family offsets
# differ, so cross-family confusion is possible), a 15x lower total relation-
# sentence rate (0.06 -> 0.004), a 1:many family, and a rare family:
#   freq — 40 one-to-one pairs, 60% of relation sentences (the v1 regime, thinner)
#   many — 32 a-entities x 2 b-entities each (1:many), 32%
#   rare — 24 one-to-one pairs, 8% (~0.0013% of ALL sentences per pair —
#          ~23 sentences per pair / ~11 per side at 60M words: an
#          undertraining probe)
GEN_VERSION = 2
# Tuned DOWN until the 60M-word/d300 headline config lands off the ceiling
# (the first v2 candidate at 2.5%/0.18/0.30 still scored 1.0 everywhere):
REL_SENT_FRAC = 0.004  # fraction of sentences that are relation sentences (v1: 0.06)
FAMILIES = (
    {"key": "freq", "na": 40, "nb_per_a": 1, "weight": 0.60},
    {"key": "many", "na": 32, "nb_per_a": 2, "weight": 0.32},
    {"key": "rare", "na": 24, "nb_per_a": 1, "weight": 0.08},
)
ROLE_WORDS = 60        # per role set (each family has its own A and B sets)
REL_LAMBDA_ENTITY = 0.06  # slots holding the entity word itself
REL_LAMBDA_ROLE = 0.10    # slots drawn from the role word set; rest: topic/noise

# v1 layout (kept so --rescore still scores round-4 models)
N_ENTITIES = 96


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def topic_of(rank: np.ndarray) -> np.ndarray:
    """Ground-truth topic of a word rank; stopwords get -1."""
    return np.where(rank < STOPWORDS, -1, rank % T_TOPICS)


def word_names(v: int) -> np.ndarray:
    ranks = np.arange(v)
    topics = topic_of(ranks)
    return np.asarray([
        f"s_w{r:06d}" if t < 0 else f"t{t:03d}_w{r:06d}"
        for r, t in zip(ranks, topics)])


def relation_names():
    """v1 entity/role word types (kept for --rescore of round-4 models)."""
    ea = [f"ea_{i:03d}" for i in range(N_ENTITIES)]
    eb = [f"eb_{i:03d}" for i in range(N_ENTITIES)]
    ra = [f"ra_w{i:03d}" for i in range(ROLE_WORDS)]
    rb = [f"rb_w{i:03d}" for i in range(ROLE_WORDS)]
    return ea, eb, ra, rb


def family_names():
    """v2 per-family entity/role word types, appended after the V_RAW topic
    types in family order: a-entities, b-entities (a-major: b's of a-entity i
    are indices i*nb_per_a .. i*nb_per_a+nb_per_a-1), role-A, role-B."""
    fams = []
    for f_idx, fam in enumerate(FAMILIES):
        nb = fam["na"] * fam["nb_per_a"]
        fams.append({
            "key": fam["key"],
            "a": [f"f{f_idx}a_{i:03d}" for i in range(fam["na"])],
            "b": [f"f{f_idx}b_{i:03d}" for i in range(nb)],
            "ra": [f"r{f_idx}a_w{i:03d}" for i in range(ROLE_WORDS)],
            "rb": [f"r{f_idx}b_w{i:03d}" for i in range(ROLE_WORDS)],
            "nb_per_a": fam["nb_per_a"],
        })
    return fams


def generate_corpus(path: str, n_words: int, seed: int, v_raw: int = V_RAW) -> None:
    """Write the topic-model corpus as a token file, one sentence per line
    (v2 relation structure — see the constants block).

    A REL_SENT_FRAC fraction of sentences are relation sentences: one family
    drawn by weight, one of its entity words (a_i, or one of a_i's b's) + that
    family's role-set draws + the entity's topic words + noise."""
    rng = np.random.default_rng(seed)
    p = 1.0 / (np.arange(v_raw) + 10.0) ** 1.05
    p /= p.sum()
    names = word_names(v_raw)
    fams = family_names()
    all_names = np.concatenate(
        [names] + [np.asarray(f[k]) for f in fams for k in ("a", "b", "ra", "rb")])
    topics = topic_of(np.arange(v_raw))
    topic_words = [np.where(topics == z)[0] for z in range(T_TOPICS)]
    topic_probs = [p[w] / p[w].sum() for w in topic_words]
    # id layout mirrors all_names: per family, a / b / role-A / role-B blocks
    base = v_raw
    fam_ids = []
    fam_off = []
    for f_idx, f in enumerate(fams):
        ids = {"a": base + np.arange(len(f["a"]))}
        base += len(f["a"])
        ids["b"] = base + np.arange(len(f["b"]))
        base += len(f["b"])
        ids["ra"] = base + np.arange(ROLE_WORDS)
        base += ROLE_WORDS
        ids["rb"] = base + np.arange(ROLE_WORDS)
        base += ROLE_WORDS
        fam_ids.append(ids)
        # family topic offset: a-entity i of family f sits in topic
        # (17*f + i) mod T — distinct families' entities spread over topics
        fam_off.append(17 * f_idx)
    weights = np.asarray([f["weight"] for f in FAMILIES], np.float64)
    weights /= weights.sum()

    n_sents = n_words // SENT_LEN
    t0 = time.perf_counter()
    with open(path, "w", encoding="utf-8") as f:
        block = 20_000
        for start in range(0, n_sents, block):
            nb = min(block, n_sents - start)
            z = rng.integers(0, T_TOPICS, nb)
            words = np.empty((nb, SENT_LEN), np.int32)
            # global (stopword/noise) draws for every slot, then overwrite the
            # topic-bound slots per topic group
            words[:] = rng.choice(v_raw, size=(nb, SENT_LEN), p=p)
            from_topic = rng.random((nb, SENT_LEN)) < LAMBDA
            # relation sentences: family by weight, entity within family,
            # side a/b 50:50 (b: uniform over the a-entity's b's); the topic is
            # forced to the entity's own topic
            is_rel = rng.random(nb) < REL_SENT_FRAC
            fam_draw = rng.choice(len(FAMILIES), size=nb, p=weights)
            ent_word = np.zeros(nb, np.int32)
            for f_idx, (fam, ids) in enumerate(zip(FAMILIES, fam_ids)):
                rows = np.where(is_rel & (fam_draw == f_idx))[0]
                if not rows.size:
                    continue
                ai = rng.integers(0, fam["na"], rows.size)
                side_b = rng.random(rows.size) < 0.5
                bk = ai * fam["nb_per_a"] + rng.integers(
                    0, fam["nb_per_a"], rows.size)
                ent_word[rows] = np.where(side_b, ids["b"][bk], ids["a"][ai])
                z[rows] = (fam_off[f_idx] + ai) % T_TOPICS
                # role draws happen below against the row's family sets
            for zz in np.unique(z):
                rows = np.where(z == zz)[0]
                m = from_topic[rows]
                words[np.repeat(rows, m.sum(1)),
                      np.concatenate([np.where(r)[0] for r in m])] = rng.choice(
                    topic_words[zz], size=int(m.sum()), p=topic_probs[zz])
            # overwrite entity/role slots of relation sentences
            rel_rows = np.where(is_rel)[0]
            if rel_rows.size:
                u = rng.random((rel_rows.size, SENT_LEN))
                ent_slot = u < REL_LAMBDA_ENTITY
                role_slot = (u >= REL_LAMBDA_ENTITY) & (
                    u < REL_LAMBDA_ENTITY + REL_LAMBDA_ROLE)
                # the entity's side decides the role set: a-side rows draw from
                # the family's role-A set, b-side from role-B
                rw = np.empty((rel_rows.size, SENT_LEN), np.int32)
                for f_idx, ids in enumerate(fam_ids):
                    sub = np.where(fam_draw[rel_rows] == f_idx)[0]
                    if not sub.size:
                        continue
                    on_b = np.isin(ent_word[rel_rows[sub]], ids["b"])
                    draw = rng.integers(0, ROLE_WORDS, (sub.size, SENT_LEN))
                    rw[sub] = np.where(on_b[:, None], ids["rb"][draw],
                                       ids["ra"][draw])
                sub = words[rel_rows]
                sub = np.where(ent_slot, ent_word[rel_rows, None], sub)
                sub = np.where(role_slot, rw, sub)
                words[rel_rows] = sub
            lines = [" ".join(all_names[row]) for row in words]
            f.write("\n".join(lines) + "\n")
    n_pairs = sum(f["na"] * f["nb_per_a"] for f in FAMILIES)
    log(f"corpus v{GEN_VERSION}: {n_sents:,} sentences / "
        f"{n_sents * SENT_LEN:,} words ({REL_SENT_FRAC:.1%} relation sentences, "
        f"{n_pairs} entity pairs over {len(FAMILIES)} families) "
        f"written in {time.perf_counter() - t0:.1f}s -> {path}")


def evaluate(words, emb: np.ndarray, index=None) -> dict:
    """Topic purity@10 + cosine margin over 2,000 mid-frequency probe words, with a
    random-embedding baseline for scale. All big reductions (similarities, top-k,
    masked means) run ON DEVICE and only tiny results come back — a
    [probes, content] matrix is 8 GB of f32 at 1M vocab, not something to fetch."""
    import jax
    import jax.numpy as jnp

    # entity/role types (ea_/eb_/ra_/rb_) carry no topic; exclude from purity
    is_topic_word = np.asarray(
        [w.startswith(("t", "s_")) and "_w" in w for w in words])
    ranks_in_vocab = np.asarray(
        [int(w.split("_w")[1]) if ok else -1
         for w, ok in zip(words, is_topic_word)])
    topics = np.where(is_topic_word, topic_of(ranks_in_vocab), -1)
    content = np.where(topics >= 0)[0]
    if content.size > 250_000:
        # a fixed 250k-content sample keeps neighbor statistics intact
        content = np.sort(np.random.default_rng(3).choice(
            content, size=250_000, replace=False))
    # mid-frequency probes: skip the hottest 2k (near-uniform co-occurrence) and the
    # rarest tail (too few updates); small --vocab runs fall back to all content
    lo = min(2000, content.size // 4)
    hi = max(30000, lo + 1)
    probe_pool = content[(content >= lo) & (content < hi)]
    if probe_pool.size == 0:
        probe_pool = content
    rng = np.random.default_rng(0)
    probes = rng.choice(probe_pool, size=min(2000, probe_pool.size), replace=False)
    # probe position within content (probes are drawn from content)
    self_pos = np.searchsorted(content, probes)
    topics_probes = jnp.asarray(topics[probes])
    topics_content = jnp.asarray(topics[content])

    @jax.jit
    def device_purity(q, base, self_idx, t_probes, t_content):
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        bn = base / jnp.maximum(jnp.linalg.norm(base, axis=1, keepdims=True), 1e-12)
        sims = qn @ bn.T                                    # [P, C] — stays on device
        rows = jnp.arange(sims.shape[0])
        sims = sims.at[rows, self_idx].set(-jnp.inf)
        _, top = jax.lax.top_k(sims, 10)                    # [P, 10]
        neigh = t_content[top]
        pur = (neigh == t_probes[:, None]).mean()
        sub = sims[:, :4000]
        same = t_content[None, :4000] == t_probes[:, None]
        finite = jnp.isfinite(sub)
        sub0 = jnp.where(finite, sub, 0.0)
        within = (sub0 * (same & finite)).sum() / jnp.maximum(
            (same & finite).sum(), 1)
        cross = (sub0 * (~same & finite)).sum() / jnp.maximum(
            (~same & finite).sum(), 1)
        return pur, within - cross

    def purity(e):
        pur, margin = device_purity(
            jnp.asarray(e[probes]), jnp.asarray(e[content]),
            jnp.asarray(self_pos), topics_probes, topics_content)
        return float(pur), float(margin)

    if np.isnan(emb).any():
        return {"diverged": True,
                "nan_rows": int(np.isnan(emb).any(axis=1).sum())}
    # finite-overflow telemetry: a slow-burn instability can wreck the geometry
    # without ever reaching NaN (bf16 saturates at ~3.4e38 but quality collapses
    # orders of magnitude earlier); record the scale so collapsed-purity rows
    # are interpretable as blowup vs undertraining
    row_max = np.abs(emb).max(axis=1)
    rows_inf = int(np.isinf(row_max).sum())
    if rows_inf:
        # bf16 saturation reached ±inf: mask the blown entries out of the
        # scoring (an inf row would NaN-poison every cosine it touches, the
        # same silent distortion the NaN path guards against) and clamp the
        # telemetry to a finite float so the EVAL_RUNS.jsonl row stays strict
        # JSON ('Infinity' is a json.dumps extension strict parsers reject)
        emb = np.where(np.isfinite(emb), emb, 0.0).astype(emb.dtype, copy=False)
    abs_max = float(np.minimum(row_max.max(), np.finfo(np.float32).max))
    blown = int((row_max > 100.0).sum())
    # norm channels (ISSUE 6 / ROADMAP item 2): the same row-L2-norm signals
    # the trainer's fused health probe reports (obs/probe.py), computed on the
    # final embedding so EVAL_RUNS rows let the large-vocab ladder correlate
    # quality collapse with the norm trajectory the watchdog thresholds watch.
    # Computed AFTER the inf-masking above so the row stays strict JSON; the
    # rows_inf field already counts what the mask removed. Threshold 100.0 ==
    # the config default norm_watch_threshold (provenance in the config doc).
    row_norm = np.linalg.norm(emb.astype(np.float64), axis=1)
    fmax = float(np.finfo(np.float32).max)
    norm_channels = {
        "row_norm_max": round(float(min(row_norm.max(), fmax)), 3),
        "row_norm_p99": round(float(min(np.percentile(row_norm, 99), fmax)), 3),
        "row_norm_mean": round(float(min(row_norm.mean(), fmax)), 4),
        "rows_norm_over_100": int((row_norm > 100.0).sum()),
    }
    pur, margin = purity(emb)
    rnd = np.random.default_rng(1).standard_normal(
        emb.shape, dtype=np.float32)
    pur0, margin0 = purity(rnd)
    # serving-index health (ISSUE 10 / docs/serving.md): build the IVF
    # index exactly as checkpoint-publish does (serve/ann.py) and record
    # its oracle-checked recall@10, so the quality ladder catches index
    # degradation — a geometry that breaks IVF's clustering assumption
    # (e.g. post-norm-blowup spread) shows up here before a deployment
    # serves it. Best-effort: a failed build must not kill the quality row.
    ann_channels = {}
    try:
        from glint_word2vec_tpu.serve.ann import build_ivf
        t_ann = time.perf_counter()
        ivf = build_ivf(emb, seed=0, recall_queries=256, recall_k=10)
        ann_channels = {
            "ann_recall_at_10": ivf.stats.get("recall_at_10"),
            "ann_centroids": ivf.stats["centroids"],
            "ann_nprobe": ivf.stats["nprobe"],
            "ann_build_s": round(time.perf_counter() - t_ann, 2),
            "ann_index_bytes": ivf.stats.get("index_bytes"),
        }
        # quantized-arm recall channels (ISSUE 18): the same oracle
        # discipline for the int8/PQ builds a deployment would actually
        # serve — a geometry that quantizes badly (e.g. heavy-tailed rows
        # blowing the per-row int8 scale) shows up here before a
        # RecallFloorError does at publish. Floors off: this is the
        # MEASUREMENT channel; refusal is the serving tier's job.
        for quant in ("int8", "pq"):
            try:
                qix = build_ivf(emb, seed=0, recall_queries=256,
                                recall_k=10, quant=quant, recall_floor=0.0)
                ann_channels[f"ann_{quant}_recall_at_10"] = (
                    qix.stats.get("recall_at_10"))
                ann_channels[f"ann_{quant}_index_bytes"] = (
                    qix.stats.get("index_bytes"))
            except Exception as e:  # noqa: BLE001 — additive channel
                log(f"ann {quant} channel skipped: "
                    f"{type(e).__name__}: {e}")
    except Exception as e:  # noqa: BLE001 — index health is additive
        log(f"ann recall channel skipped: {type(e).__name__}: {e}")
    out = {
        "purity_at_10": round(pur, 4),
        "emb_abs_max": round(abs_max, 3),
        "rows_inf": rows_inf,
        "rows_abs_over_100": blown,
        **norm_channels,
        **ann_channels,
        "purity_at_10_random_baseline": round(pur0, 4),
        "cosine_margin": round(margin, 4),
        "cosine_margin_random_baseline": round(margin0, 4),
        "probes": int(probes.size),
        "topics": T_TOPICS,
    }
    if index is None:
        index = {w: i for i, w in enumerate(words)}
    out.update(evaluate_analogies(index, emb))
    return out


def evaluate_analogies(index, emb: np.ndarray) -> dict:
    """The reference's analogy gate (wien − österreich + deutschland ≈ berlin,
    it spec:327-352) run quantitatively over the generator's entity pairs:
    for a-entities (i, j), query v = b_i − a_i + a_j and check that the
    cosine-nearest word over the FULL vocabulary (query words excluded, like the
    reference's findSynonyms excludes the query) is one of a_j's b-entities.
    v2: scored PER FAMILY (freq / many / rare) plus the overall mean, so the
    gate ranks configs instead of saturating. Device-side: at 1M vocab the
    [queries, V] similarity matrix must not cross to the host."""
    import jax
    import jax.numpy as jnp

    fams = family_names()
    if fams[0]["a"][0] not in index and relation_names()[0][0] in index:
        return _evaluate_analogies_v1(index, emb)

    en_all = jnp.asarray(emb)

    @jax.jit
    def device_analogy(e, a_i, b_ik, a_j, b_j_set):
        # b_j_set: [n_q, nb] — ALL correct answers for a_j (1:many families)
        en = e / jnp.maximum(jnp.linalg.norm(e, axis=1, keepdims=True), 1e-12)
        v = en[b_ik] - en[a_i] + en[a_j]
        v = v / jnp.maximum(jnp.linalg.norm(v, axis=1, keepdims=True), 1e-12)
        sims = v @ en.T                       # [n_q, V] — stays on device
        rows = jnp.arange(sims.shape[0])
        cos_correct = jnp.take_along_axis(sims, b_j_set, axis=1).max(axis=1)
        sims = sims.at[rows, a_i].set(-jnp.inf)
        sims = sims.at[rows, b_ik].set(-jnp.inf)
        sims = sims.at[rows, a_j].set(-jnp.inf)
        top1 = sims.argmax(axis=1)
        hit = (top1[:, None] == b_j_set).any(axis=1)
        return hit.mean(), cos_correct.mean()

    rng = np.random.default_rng(7)
    out = {}
    accs, total_pairs, total_q = [], 0, 0
    for fam in fams:
        ia = np.asarray([index.get(w, -1) for w in fam["a"]])
        ib = np.asarray([index.get(w, -1) for w in fam["b"]])
        nb = fam["nb_per_a"]
        ok_a = (ia >= 0) & (ib.reshape(-1, nb) >= 0).all(axis=1)
        a_ids = ia[ok_a]
        b_sets = ib.reshape(-1, nb)[ok_a]     # [na_ok, nb]
        n = a_ids.size
        total_pairs += int((ib >= 0).sum())
        if n < 4:
            out[f"analogy_{fam['key']}_pairs"] = int(n)
            continue
        n_q = min(256, n * (n - 1) * nb)
        qi = rng.integers(0, n, n_q)
        qk = rng.integers(0, nb, n_q)
        qj = rng.integers(0, n - 1, n_q)
        qj = np.where(qj >= qi, qj + 1, qj)   # j != i
        acc, cos_mean = device_analogy(
            en_all, jnp.asarray(a_ids[qi]), jnp.asarray(b_sets[qi, qk]),
            jnp.asarray(a_ids[qj]), jnp.asarray(b_sets[qj]))
        out[f"analogy_{fam['key']}_accuracy_at_1"] = round(float(acc), 4)
        out[f"analogy_{fam['key']}_mean_cosine"] = round(float(cos_mean), 4)
        accs.append(float(acc))
        total_q += n_q
    if accs:
        out["analogy_accuracy_at_1"] = round(float(np.mean(accs)), 4)
    out["analogy_pairs_in_vocab"] = total_pairs
    out["analogy_queries"] = total_q
    out["gen_version"] = GEN_VERSION
    return out


def _evaluate_analogies_v1(index, emb: np.ndarray) -> dict:
    """v1 single-relation scoring — kept so --rescore works on round-4 models."""
    import jax
    import jax.numpy as jnp

    ea, eb, _, _ = relation_names()
    ia = np.asarray([index.get(w, -1) for w in ea])
    ib = np.asarray([index.get(w, -1) for w in eb])
    ok = (ia >= 0) & (ib >= 0)
    ia, ib = ia[ok], ib[ok]
    n = ia.size
    if n < 4:
        return {"analogy_pairs_in_vocab": int(n)}
    rng = np.random.default_rng(7)
    n_q = min(512, n * (n - 1))
    qi = rng.integers(0, n, n_q)
    qj = rng.integers(0, n - 1, n_q)
    qj = np.where(qj >= qi, qj + 1, qj)       # j != i

    @jax.jit
    def device_analogy(e, a_i, b_i, a_j, b_j):
        en = e / jnp.maximum(jnp.linalg.norm(e, axis=1, keepdims=True), 1e-12)
        v = en[b_i] - en[a_i] + en[a_j]
        v = v / jnp.maximum(jnp.linalg.norm(v, axis=1, keepdims=True), 1e-12)
        sims = v @ en.T                       # [n_q, V] — stays on device
        rows = jnp.arange(sims.shape[0])
        cos_correct = sims[rows, b_j]
        sims = sims.at[rows, a_i].set(-jnp.inf)
        sims = sims.at[rows, b_i].set(-jnp.inf)
        sims = sims.at[rows, a_j].set(-jnp.inf)
        top1 = sims.argmax(axis=1)
        return (top1 == b_j).mean(), cos_correct.mean()

    acc, cos_mean = device_analogy(
        jnp.asarray(emb), jnp.asarray(ia[qi]), jnp.asarray(ib[qi]),
        jnp.asarray(ia[qj]), jnp.asarray(ib[qj]))
    return {
        "analogy_pairs_in_vocab": int(n),
        "analogy_queries": int(n_q),
        "analogy_accuracy_at_1": round(float(acc), 4),
        "analogy_mean_cosine_to_answer": round(float(cos_mean), 4),
        "gen_version": 1,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--words", type=int, default=17_000_000)
    ap.add_argument("--out", default="/tmp/eval_corpus")
    ap.add_argument("--corpus", default=None,
                    help="existing token file (e.g. a real text8); skips generation "
                         "AND the ground-truth quality metrics")
    ap.add_argument("--dim", type=int, default=100)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--param-dtype", default="float32")
    ap.add_argument("--logits-dtype", default=None,
                    help="negative-logit chain dtype; defaults to float32 "
                         "(bfloat16 = the PERF.md fast path)")
    ap.add_argument("--batch", type=int, default=65536)
    ap.add_argument("--vocab", type=int, default=V_RAW,
                    help="raw word types in the generator (before min_count)")
    ap.add_argument("--min-count", type=int, default=5)
    ap.add_argument("--subsample", type=float, default=1e-4)
    ap.add_argument("--lr", type=float, default=None,
                    help="learning rate; default 0.025 (the 1.6M-vocab "
                         "120M-word ladder rung measured a finite blowup at "
                         "that default — lower lr is the mitigation probe). "
                         "--rescore rows record this only when given "
                         "explicitly (the saved model's lr is unknowable)")
    ap.add_argument("--device-pairgen", action="store_true",
                    help="use the on-device pair generator feed")
    ap.add_argument("--cbow", action="store_true",
                    help="train the CBOW variant (BASELINE config 5)")
    ap.add_argument("--rescore", action="store_true",
                    help="skip training: score the syn0.npy + vocab_words.txt "
                         "already saved under --out (e.g. after an interrupted "
                         "metrics pass)")
    ap.add_argument("--pool", type=int, default=512,
                    help="shared negative pool. Scale it with the batch: every pool "
                         "row absorbs all pairs' negative gradients x negatives/pool, "
                         "and the pool + duplicate-context channels compound on "
                         "frequent rows over long runs (measured: B=64k/P=64 NaNs at "
                         "17M words; B=64k/P=256 is stable at 17M but NaNs at 60M; "
                         "P>=512 holds at 60M; see EVAL.md)")
    # --- in-step stabilizers + recovery (ISSUE 7 / ROADMAP 2): the ladder's
    # judge. Rows carry both the REQUESTED knobs and the ENGAGED end state
    # (recoveries_performed, lr_scale_final, engaged_max_row_norm) so the
    # collapse-rung ladder compares mitigation variants on purity/analogy
    # instead of vibes ---
    ap.add_argument("--max-row-norm", type=float, default=0.0,
                    help="per-touched-row L2 clamp on the update path (0=off)")
    ap.add_argument("--update-clip", type=float, default=0.0,
                    help="per-row L2 ceiling on each pair's update rows (0=off)")
    ap.add_argument("--row-l2", type=float, default=0.0,
                    help="touched-row weight decay (0=off)")
    ap.add_argument("--norm-watch", default="off",
                    choices=["off", "warn", "recover", "halt"],
                    help="finite-blowup watchdog policy for the trained run "
                         "('recover' = the full auto-recovery ladder)")
    # --- continual forgetting gate (ISSUE 11 / docs/continual.md): train a
    # base model, run ONE continual increment over a drifted corpus tail
    # (new word types + shifted frequencies), and score the ORIGINAL
    # vocabulary's purity/analogy before and after — catastrophic
    # forgetting as a gated number (two EVAL_RUNS rows), not a vibe ---
    ap.add_argument("--continual-ab", action="store_true",
                    help="base fit -> one continual increment on a drifted "
                         "tail -> score the ORIGINAL vocab pre/post; emits "
                         "one EVAL_RUNS row per arm (continual_ab_arm="
                         "pre/post)")
    ap.add_argument("--continual-tail-words", type=int, default=None,
                    help="drift-tail size in words (default: --words // 4)")
    ap.add_argument("--continual-new-types", type=int, default=2000,
                    help="extra raw word types in the tail generator "
                         "(ranks past --vocab become NEW words)")
    ap.add_argument("--continual-lr-rewarm", type=float, default=1.0,
                    help="continual_lr_rewarm for the increment")
    ap.add_argument("--continual-iterations", type=int, default=1,
                    help="continual_iterations for the increment")
    # --- local-SGD staleness gate (ISSUE 17 / docs/sharding.md §Local-SGD):
    # sync_every=k trades k× fewer data-axis collective bytes (priced by
    # tools/collectives.py --sync-every) for k−1 steps of gradient staleness
    # per shard, so the knob ships default-off behind THIS measured A/B: two
    # shard_map arms on the identical corpus/seed over a mesh with a real
    # data axis, sync_every=1 vs sync_every=--sync-every, scored on the same
    # ladder. Documented tolerance: the local arm fails the gate when its
    # purity@10 drops more than 0.03 absolute below the synchronous arm ---
    ap.add_argument("--localsgd-ab", action="store_true",
                    help="train TWO shard_map arms on the identical "
                         "corpus/seed — sync_every=1 and "
                         "sync_every=--sync-every — on a data-parallel mesh "
                         "and emit one EVAL_RUNS row per arm "
                         "(localsgd_ab_arm=sync/local) plus a staleness "
                         "verdict (purity drop > 0.03 absolute fails)")
    ap.add_argument("--sync-every", type=int, default=8,
                    help="sync_every for the local arm of --localsgd-ab "
                         "(must divide steps_per_dispatch=32)")
    ap.add_argument("--stab-ab", action="store_true",
                    help="train TWO arms on the identical corpus/seed — the "
                         "unmitigated baseline (all stabilizers off, "
                         "norm_watch off) and the stabilized arm (the "
                         "--max-row-norm/--update-clip/--row-l2/--norm-watch "
                         "knobs; defaults to max_row_norm=100 + "
                         "norm_watch=recover when none given) — and emit one "
                         "EVAL_RUNS row per arm, so the collapse rung judges "
                         "the clamp/backoff variants on measured purity")
    args = ap.parse_args()

    if args.localsgd_ab and "jax" not in sys.modules:
        # the A/B needs a data axis; on a host with no accelerator the CPU
        # backend exposes 1 device, so self-provision the virtual 8-device
        # mesh BEFORE jax initializes (the flag is a no-op on real TPU runs)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    from glint_word2vec_tpu.data.corpus import TokenFileCorpus
    from glint_word2vec_tpu.models.estimator import Word2Vec

    lr = args.lr if args.lr is not None else 0.025

    os.makedirs(args.out, exist_ok=True)
    if args.rescore:
        emb = np.load(os.path.join(args.out, "syn0.npy"))
        with open(os.path.join(args.out, "vocab_words.txt")) as f:
            words = f.read().splitlines()
        if not any(w.startswith(("t0", "t1", "s_")) and "_w" in w
                   for w in words[:1000]):
            ap.error("--rescore needs a model trained on the synthetic ground-truth "
                     "corpus (vocab_words.txt has no t###_w##### names); external-"
                     "corpus models have no labels to score against")
        result = {"metric": "topic_recovery_at_text8_scale", "rescored": True,
                  "corpus_words": args.words, "vocab_raw": args.vocab,
                  "vocab_size": len(words), "dim": int(emb.shape[1]),
                  "iterations": args.iters, "param_dtype": args.param_dtype,
                  "logits_dtype": args.logits_dtype or "float32",
                  "pairs_per_batch": args.batch, "negative_pool": args.pool,
                  "subsample_ratio": args.subsample,
                  "device_pairgen": bool(args.device_pairgen),
                  "cbow": bool(args.cbow), "min_count": args.min_count,
                  # generator-constants provenance: gen_version alone cannot
                  # distinguish tuning iterations of the same version
                  "rel_sent_frac": REL_SENT_FRAC,
                  "rel_lambda_entity": REL_LAMBDA_ENTITY,
                  "rel_lambda_role": REL_LAMBDA_ROLE,
                  # only when given explicitly: the saved model's lr is
                  # unknowable here, and a default would fake provenance
                  **({"learning_rate": args.lr} if args.lr is not None else {})}
        result.update(evaluate(words, emb.astype(np.float32)))
        print(json.dumps(result))
        with open(os.path.join(os.path.dirname(_here), "EVAL_RUNS.jsonl"),
                  "a") as f:
            f.write(json.dumps(result) + "\n")
        return
    if args.corpus:
        corpus_path = args.corpus
    else:
        # cache key carries the tunable constants: a retune without a version
        # bump must NOT silently reuse a stale corpus while the row records the
        # new constants (the false-provenance hole the fields exist to prevent)
        gen_tag = (f"v{GEN_VERSION}-{REL_SENT_FRAC:g}-{REL_LAMBDA_ENTITY:g}"
                   f"-{REL_LAMBDA_ROLE:g}")
        corpus_path = os.path.join(
            args.out,
            f"corpus_{gen_tag}_{args.words}_{args.vocab}_{args.seed}.txt")
        if not os.path.exists(corpus_path):
            generate_corpus(corpus_path, args.words, args.seed, args.vocab)
        else:
            log(f"reusing corpus at {corpus_path}")

    sents = TokenFileCorpus(corpus_path)
    cache_dir = os.path.join(
        args.out, (f"encoded_{gen_tag}_{args.words}_{args.vocab}"
                   f"_{args.min_count}") if not args.corpus else
        f"encoded_ext_{args.words}_{args.min_count}")

    def run_arm(stab: dict, save_arrays: bool, arm: str = "",
                arm_field: str = "stab_ab_arm", plan=None):
        """Train one configuration and score it; appends the EVAL_RUNS row
        (ground-truth corpora only) carrying the requested stabilizer knobs
        AND the engaged end state, and returns the result dict. ``arm_field``
        names the A/B-arm key the row carries (stab_ab_arm /
        localsgd_ab_arm), so every A/B harness funnels through this one
        trainer. ``plan`` pins the mesh (the local-SGD A/B needs a real data
        axis; every other caller takes the default)."""
        est = Word2Vec(
            vector_size=args.dim, min_count=args.min_count, window=5,
            negatives=5, negative_pool=args.pool,
            pairs_per_batch=args.batch, steps_per_dispatch=32,
            num_iterations=args.iters,
            learning_rate=lr, subsample_ratio=args.subsample, seed=args.seed,
            param_dtype=args.param_dtype,
            compute_dtype=args.param_dtype,
            logits_dtype=args.logits_dtype or "float32",
            # the EVAL suite's whole job is to MEASURE the divergence
            # boundary, so it must be allowed to train configs the trainer
            # would refuse
            allow_unstable=True,
            device_pairgen=args.device_pairgen, cbow=args.cbow, **stab)
        from glint_word2vec_tpu.train.faults import (
            NonFiniteParamsError, NormBlowupError)
        t0 = time.perf_counter()
        try:
            model = est.fit(sents, plan=plan, encode_cache_dir=cache_dir)
        except (NonFiniteParamsError, NormBlowupError) as e:
            # an unmitigated arm may halt mid-run (that IS the measurement:
            # the boundary); record the divergence as a row instead of
            # killing the other arm's result
            log(f"arm {arm or 'run'} diverged: {type(e).__name__}: "
                f"{str(e)[:160]}")
            result = {
                "metric": "topic_recovery_at_text8_scale",
                "corpus_words": args.words, "vocab_raw": args.vocab,
                "dim": args.dim, "iterations": args.iters,
                "pairs_per_batch": args.batch, "negative_pool": args.pool,
                "subsample_ratio": args.subsample, "min_count": args.min_count,
                "learning_rate": lr, "diverged": type(e).__name__,
                **stab, **({arm_field: arm} if arm else {})}
            if not args.corpus:
                with open(os.path.join(os.path.dirname(_here),
                                       "EVAL_RUNS.jsonl"), "a") as f:
                    f.write(json.dumps(result) + "\n")
            return result
        train_s = time.perf_counter() - t0
        log(f"trained{f' [{arm}]' if arm else ''}: vocab "
            f"{model.num_words:,}, d={args.dim}, {args.iters} iters "
            f"in {train_s:.0f}s (incl. vocab+encode passes)")
        if save_arrays:
            np.save(os.path.join(args.out, "syn0.npy"),
                    np.asarray(model.syn0, np.float32))
            with open(os.path.join(args.out, "vocab_words.txt"), "w") as f:
                f.write("\n".join(model.vocab.words))
        result = {
            "metric": "topic_recovery_at_text8_scale",
            "corpus_words": args.words,
            "vocab_raw": args.vocab,
            "vocab_size": model.num_words,
            "dim": args.dim,
            "iterations": args.iters,
            "train_seconds_total": round(train_s, 1),
            "param_dtype": args.param_dtype,
            "logits_dtype": args.logits_dtype or "float32",
            "pairs_per_batch": args.batch,
            "negative_pool": args.pool,
            "subsample_ratio": args.subsample,
            "device_pairgen": bool(args.device_pairgen),
            "cbow": bool(args.cbow),
            "min_count": args.min_count,
            # generator-constants provenance (rows are only comparable within
            # one constants set; gen_version alone cannot distinguish tuning
            # rounds)
            "rel_sent_frac": REL_SENT_FRAC,
            "rel_lambda_entity": REL_LAMBDA_ENTITY,
            "rel_lambda_role": REL_LAMBDA_ROLE,
            "learning_rate": lr,
            # requested stabilizer/recovery knobs + the ENGAGED end state
            # (recovery may have backed lr off / engaged the clamp mid-run)
            **stab,
            **getattr(est, "last_run_stats", {}),
            **({arm_field: arm} if arm else {}),
        }
        if not args.corpus:
            result.update(evaluate(model.vocab.words,
                                   np.asarray(model.syn0, np.float32),
                                   model.vocab.index))
            # machine-readable run log: bench.py's headline cross-check
            # refuses configs this file marks divergent or has never seen.
            # Only ground-truth (synthetic corpus) runs qualify as stability
            # evidence — external-corpus runs have no divergence metrics and
            # are not appended.
            repo_root = os.path.dirname(_here)
            with open(os.path.join(repo_root, "EVAL_RUNS.jsonl"), "a") as f:
                f.write(json.dumps(result) + "\n")
        return result

    if args.continual_ab:
        if args.corpus:
            ap.error("--continual-ab needs the synthetic ground-truth corpus "
                     "(external corpora have no labels to score forgetting "
                     "against)")
        import shutil

        from glint_word2vec_tpu.continual import ContinualRunner
        from glint_word2vec_tpu.models.word2vec import Word2VecModel

        est = Word2Vec(
            vector_size=args.dim, min_count=args.min_count, window=5,
            negatives=5, negative_pool=args.pool,
            pairs_per_batch=args.batch, steps_per_dispatch=32,
            num_iterations=args.iters, learning_rate=lr,
            subsample_ratio=args.subsample, seed=args.seed,
            param_dtype=args.param_dtype, compute_dtype=args.param_dtype,
            logits_dtype=args.logits_dtype or "float32",
            allow_unstable=True, device_pairgen=args.device_pairgen,
            cbow=args.cbow,
            continual_lr_rewarm=args.continual_lr_rewarm,
            continual_iterations=args.continual_iterations)
        t0 = time.perf_counter()
        model = est.fit(sents, encode_cache_dir=cache_dir)
        base_s = round(time.perf_counter() - t0, 1)
        words_base = list(model.vocab.words)
        index_base = dict(model.vocab.index)
        v_base = model.num_words
        log(f"continual-ab base: vocab {v_base:,} in {base_s}s")
        common = {
            "metric": "topic_recovery_at_text8_scale",
            "corpus_words": args.words, "vocab_raw": args.vocab,
            "vocab_size": v_base, "dim": args.dim,
            "iterations": args.iters, "param_dtype": args.param_dtype,
            "logits_dtype": args.logits_dtype or "float32",
            "pairs_per_batch": args.batch, "negative_pool": args.pool,
            "subsample_ratio": args.subsample, "min_count": args.min_count,
            "learning_rate": lr, "rel_sent_frac": REL_SENT_FRAC,
            "rel_lambda_entity": REL_LAMBDA_ENTITY,
            "rel_lambda_role": REL_LAMBDA_ROLE,
            "continual_tail_words": (args.continual_tail_words
                                     or args.words // 4),
            "continual_new_types": args.continual_new_types,
            "continual_lr_rewarm": args.continual_lr_rewarm,
            "continual_iterations": args.continual_iterations,
        }
        row_pre = {**common, "continual_ab_arm": "pre",
                   "train_seconds_total": base_s}
        row_pre.update(evaluate(
            words_base, np.asarray(model.syn0, np.float32), index_base))

        croot = os.path.join(args.out, "continual")
        shutil.rmtree(croot, ignore_errors=True)
        ckpath = os.path.join(croot, "publish", "ck")
        model.save(ckpath)
        stream_dir = os.path.join(croot, "stream")
        os.makedirs(stream_dir, exist_ok=True)
        # the drifted tail: extra raw types past --vocab are NEW words
        # (their names encode their topics, so ground truth still travels
        # with the corpus); Zipf over the larger support shifts every
        # surviving word's frequency too
        generate_corpus(
            os.path.join(stream_dir, "seg-001.txt"),
            common["continual_tail_words"], args.seed + 1000,
            args.vocab + args.continual_new_types)
        runner = ContinualRunner(
            ckpath, stream_dir, os.path.join(croot, "work"),
            config_overrides=dict(
                allow_unstable=True,
                continual_lr_rewarm=args.continual_lr_rewarm,
                continual_iterations=args.continual_iterations))
        inc = runner.run_once()
        runner.close()
        log(f"continual-ab increment: {inc}")
        post = Word2VecModel.load(ckpath)
        emb_post = np.asarray(post.syn0, np.float32)[:v_base]
        row_post = {**common, "continual_ab_arm": "post",
                    "continual_new_words": inc["new_words"],
                    "continual_vocab_size": inc["vocab_size"],
                    "train_seconds_total": inc["train_seconds"]}
        # scored over the ORIGINAL vocabulary's rows only — the identity-
        # prefix contract makes emb_post[:v_base] exactly those words
        row_post.update(evaluate(words_base, emb_post, index_base))
        repo_root = os.path.dirname(_here)
        with open(os.path.join(repo_root, "EVAL_RUNS.jsonl"), "a") as f:
            f.write(json.dumps(row_pre) + "\n")
            f.write(json.dumps(row_post) + "\n")
        delta = None
        if "purity_at_10" in row_pre and "purity_at_10" in row_post:
            delta = round(row_post["purity_at_10"] - row_pre["purity_at_10"],
                          4)
        print(json.dumps({
            "metric": "continual_ab", "purity_delta": delta,
            "purity_pre": row_pre.get("purity_at_10"),
            "purity_post": row_post.get("purity_at_10"),
            "analogy_pre": row_pre.get("analogy_accuracy_at_1"),
            "analogy_post": row_post.get("analogy_accuracy_at_1"),
            "vocab_base": v_base, "vocab_grown": inc["vocab_size"],
            "new_words": inc["new_words"],
            "arms": [row_pre, row_post]}))
        return

    if args.localsgd_ab:
        # the ISSUE-17 staleness-vs-throughput gate: synchronous shard_map vs
        # the sync_every=k owner-local window, identical corpus/seed/mesh,
        # scored on the same ladder. Documented tolerance: local-arm
        # purity@10 more than 0.03 absolute below the sync arm fails the
        # gate (the knob then stays at 1 for that geometry).
        import jax

        from glint_word2vec_tpu.parallel.mesh import make_mesh
        if args.sync_every <= 1 or 32 % args.sync_every:
            ap.error("--sync-every must be > 1 and divide "
                     f"steps_per_dispatch=32 (got {args.sync_every})")
        n_dev = len(jax.devices())
        if n_dev < 2:
            ap.error("--localsgd-ab needs >= 2 devices for a data axis "
                     f"(have {n_dev})")
        # widest data axis the device count allows, capped at 2 shards of
        # model parallelism — matches the headline 2x4 pricing geometry on 8
        # devices while still degrading to 2x1 on a 2-device host
        plan = make_mesh(max(2, n_dev // 4))
        log(f"localsgd-ab mesh: {plan.num_data}x{plan.num_model}, "
            f"sync_every={args.sync_every}")
        r_sync = run_arm(dict(step_lowering="shard_map", sync_every=1),
                         save_arrays=False, arm="sync",
                         arm_field="localsgd_ab_arm", plan=plan)
        r_local = run_arm(dict(step_lowering="shard_map",
                               sync_every=args.sync_every),
                          save_arrays=True, arm="local",
                          arm_field="localsgd_ab_arm", plan=plan)
        delta = analogy_delta = None
        if "purity_at_10" in r_sync and "purity_at_10" in r_local:
            delta = round(r_local["purity_at_10"] - r_sync["purity_at_10"], 4)
        if ("analogy_accuracy_at_1" in r_sync
                and "analogy_accuracy_at_1" in r_local):
            analogy_delta = round(r_local["analogy_accuracy_at_1"]
                                  - r_sync["analogy_accuracy_at_1"], 4)
        print(json.dumps({
            "metric": "localsgd_ab",
            "sync_every": args.sync_every,
            "mesh": [plan.num_data, plan.num_model],
            "purity_delta": delta,
            "analogy_delta": analogy_delta,
            "staleness_ok": (delta is not None and delta >= -0.03),
            "staleness_rule": "local purity_at_10 >= sync - 0.03 absolute",
            "arms": [r_sync, r_local]}))
        return

    stab = dict(max_row_norm=args.max_row_norm, update_clip=args.update_clip,
                row_l2=args.row_l2, norm_watch=args.norm_watch)
    if args.stab_ab:
        if not (args.max_row_norm or args.update_clip or args.row_l2
                or args.norm_watch != "off"):
            # the default stabilized arm: the clamp at the watchdog-threshold
            # provenance value + the full recovery ladder
            stab = dict(max_row_norm=100.0, update_clip=0.0, row_l2=0.0,
                        norm_watch="recover")
        # the unmitigated arm is UNMITIGATED: no stabilizers, no watchdog,
        # and no non-finite guardrail either (nonfinite_policy="none", the
        # round-5 measurement posture) — a run that NaNs still trains to the
        # end and scores, with the non-finite rows masked out of purity and
        # counted in rows_inf, so the A/B always compares purity to purity
        off = dict(max_row_norm=0.0, update_clip=0.0, row_l2=0.0,
                   norm_watch="off", nonfinite_policy="none")
        r_off = run_arm(off, save_arrays=False, arm="unmitigated")
        r_stab = run_arm(stab, save_arrays=True, arm="stabilized")
        delta = None
        if "purity_at_10" in r_off and "purity_at_10" in r_stab:
            delta = round(r_stab["purity_at_10"] - r_off["purity_at_10"], 4)
        print(json.dumps({"metric": "stabilizer_ab",
                          "purity_delta": delta,
                          "arms": [r_off, r_stab]}))
        return
    print(json.dumps(run_arm(stab, save_arrays=True)))


if __name__ == "__main__":
    main()
