"""Kind ``analogy``: ONE closed-loop caller of ``analogy_accuracy(section)``,
word2vec's own accuracy test (``compute-accuracy.c`` over ``questions-words.txt``:
3CosAdd, 19,544 questions in 14 sections) against a whole table, a section a call
in the file's order, cycling.

Set-up: vocabulary from counts; both tables made on the device from ``--seed`` as
``kinds/query.py`` makes them, syn0 then given the PLANTED RELATIONS in place (a
seeded table of independent rows answers every analogy wrong; one where every
relation holds exactly answers every one right): for each section P word pairs
(x_i, y_i), the words drawn without replacement from the first ``word_ranks`` rows
by seed, row(y_i) := row(x_i) + r_section + noise_i, |r| = ``relation_norm`` and
|noise_i| uniform up to ``noise_norm_max`` row norms, so that some pairs answer and
some do not. The section's questions are ordered pairs of distinct word pairs
(x_i y_i x_j y_j), shuffled by seed and cut to the section's size; a share of them
carries, in one of its four places, a string the vocabulary lacks. Every section
runs once before the window (a capacity's program is compiled there). A question
counts toward ``query_per_s`` where its call returned inside the window;
``query_p95_ms`` is the 95th percentile over the window's CALLS.

``correct``: the window's own counts (seen, scored, skipped, correct) of a seeded
sample of its calls, the largest section always among them, against the plain
reference (reference/analogy_ref.py) over the table it makes itself; and the same
sections asked once more through ``analogies`` before the model is stopped (the
same program: its answers must give the window's counts again), whose rows and
cosines are held to the reference's.

A program that cannot run the cell (no ``Word2VecModel.analogy_accuracy``) ends
with a message and exit code 1 before anything large is built.
"""

import gc
import threading
import time

import numpy as np

from harness import weights, zipf
from harness.common import Checks, CompileLog, Laps, TracedSlice, memory_peak_bytes
from harness.loader import sizes as sizes_of


def require_operation():
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    if not hasattr(Word2VecModel, "analogy_accuracy"):
        raise SystemExit(
            "benchmark: this program has no Word2VecModel.analogy_accuracy: it cannot "
            "run word2vec's accuracy test as one operation, which this cell measures")


def pairs_of(size: int) -> int:
    """The fewest word pairs whose ordered pairs of distinct pairs number ``size``."""
    p = 2
    while p * (p - 1) < size:
        p += 1
    return p


def plant_relations(seed: int, cfg: dict, tf: dict, d: int, half_width: float):
    """The planted rows and the questions, from the seed alone (the reference makes
    them again): ``y_ids`` (``int32[K]``), their rows (``float32[K, d]``) and, for
    each section, its questions as ``int32[n, 4]`` row ids (a b c d)."""
    import jax.numpy as jnp

    sizes = cfg["section_sizes"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 0xA7A])
    pairs = [pairs_of(n) for n in sizes]
    words = rng.choice(min(tf["word_ranks"], cfg["vocab_size"]), size=2 * sum(pairs),
                       replace=False).astype(np.int32)
    x_rows = np.asarray(weights.rows_uniform(
        weights.seed32(seed), 0, jnp.asarray(words[0::2]), d, d, half_width))
    row_norm = half_width * np.sqrt(d / 3.0)
    y_ids, y_rows, sections, at = words[1::2], [], [], 0
    for n, p in zip(sizes, pairs):
        x, y = words[0::2][at:at + p], words[1::2][at:at + p]
        r = rng.normal(size=d)
        r *= tf["relation_norm"] * row_norm / np.linalg.norm(r)
        noise = rng.normal(size=(p, d))
        noise *= (rng.uniform(0, tf["noise_norm_max"], p) * row_norm
                  / np.linalg.norm(noise, axis=1))[:, None]
        y_rows.append((x_rows[at:at + p] + (r + noise).astype(np.float32))
                      .astype(np.float32))
        i, j = np.nonzero(~np.eye(p, dtype=bool))
        keep = rng.permutation(len(i))[:n]
        keep.sort()
        sections.append(np.stack([x[i[keep]], y[i[keep]], x[j[keep]], y[j[keep]]],
                                 axis=1).astype(np.int32))
        at += p
    return y_ids, np.concatenate(y_rows), sections


def section_strings(seed: int, sections: list, tf: dict):
    """What the caller sends: every section a list of 4-tuples of fresh strings,
    ``missing_share`` of the questions with one place taken by a string the
    vocabulary lacks; and the ids the reference is given (-1 in that place)."""
    out, ids_out = [], []
    for s, ids in enumerate(sections):
        rng = np.random.default_rng([seed & 0xFFFFFFFF, 0xA7B, s])
        ids = ids.copy()
        lost = np.flatnonzero(rng.random(len(ids)) < tf["missing_share"])
        if not len(lost):
            lost = np.array([int(rng.integers(0, len(ids)))])
        ids[lost, rng.integers(0, 4, len(lost))] = -1
        # no vocabulary word starts with q
        out.append([tuple(f"w{w}" if w >= 0 else f"q{n}" for w in row)
                    for n, row in enumerate(ids.tolist())])
        ids_out.append(ids)
    return out, ids_out


def build_model(cell: dict, seed: int, tiny: bool, table_dtype=None, laps=None):
    """The model whose ``analogy_accuracy`` is called, and the questions.
    ``table_dtype`` overrides the configuration's only for the lower-precision
    control."""
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel

    lap = laps.lap if laps else (lambda name: None)
    lap("imports")
    cfg, tf = sizes_of(cell["config"], tiny), sizes_of(cell["traffic"], tiny)
    v, d, half_width = cfg["vocab_size"], cfg["vector_size"], cfg["table_half_width"]
    vocab = Vocabulary.from_words_and_counts(
        zipf.words_of(v), zipf.zipf_counts(v).astype(np.int64))
    lap("vocabulary")
    dtype = jnp.dtype(table_dtype or cfg["param_dtype"])
    y_ids, y_rows, sections = plant_relations(seed, cfg, tf, d, half_width)

    def planted(table, ids, rows):
        # row by row into the table where it lies: the whole lane tile of 128
        # rows that holds the word's is read, the row replaced and the tile
        # written back (a scatter first copies a [V, 300] table row-major)
        def body(i, table):
            first = jnp.minimum(ids[i] // 128 * 128, max(v - 128, 0))
            tile = jax.lax.dynamic_slice_in_dim(table, first, min(128, v))
            mine = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == ids[i] - first
            return jax.lax.dynamic_update_slice_in_dim(
                table, jnp.where(mine, rows[i][None, :].astype(table.dtype), tile), first, 0)
        return jax.lax.fori_loop(0, ids.shape[0], body, table)

    syn0 = jax.jit(planted, donate_argnums=0)(
        weights.make_table(seed, 0, v, d, d, half_width, dtype), y_ids, y_rows)
    syn1 = weights.make_table(seed, 1, v, d, d, half_width, dtype)
    syn1.block_until_ready()
    lap("tables on device")
    model = Word2VecModel(vocab, syn0, syn1, config=Word2VecConfig(vector_size=d))
    del syn0, syn1
    strings, ids = section_strings(seed, sections, tf)
    lap("questions")
    return model, dict(v=v, d=d, half_width=half_width), strings, ids


class Caller:
    """The one closed-loop caller; every finished call is kept as (sent,
    received, section, the counts or the exception)."""

    def __init__(self, model, sections: list):
        self.done = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, args=(model, sections),
                                        name="bench-caller-0", daemon=True)

    def _loop(self, model, sections):
        import jax.profiler as jp
        at = 0
        while not self._stop.is_set():
            s = at % len(sections)
            at += 1
            sent = time.perf_counter()
            try:
                with jp.TraceAnnotation("bench.analogy_accuracy"):
                    reply = model.analogy_accuracy(sections[s])
            except Exception as e:  # noqa: BLE001 — counted as failed
                reply = e
            self.done.append((sent, time.perf_counter(), s, reply))

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=120)
        if self._thread.is_alive():
            raise RuntimeError("the caller never returned")

    def between(self, lo: float, hi: float) -> list:
        return [q for q in list(self.done) if lo < q[1] <= hi]


def span_counters(events: list) -> dict:
    """Sums over the program's ``eval.*`` spans of the traced slice, for the
    ratios no one span holds (reader ``counter``)."""
    calls = [e for e in events if e["name"] == "eval.call"]
    enqueued = [e["args"] for e in events if e["name"] == "eval.enqueue"]
    if not calls or not enqueued:
        return {}
    waited = sum(e["dur_s"] for e in events if e["name"] == "eval.fetch")
    return {"slice_calls": len(enqueued),
            "slice_eval_calls": len(calls),
            "slice_questions": sum(a["questions"] for a in enqueued),
            "slice_capacity": sum(a["cap"] for a in enqueued),
            "slice_inflight_and_self": sum(a["inflight"] + 1 for a in enqueued),
            "slice_seen": sum(e["args"]["questions"] for e in calls),
            "slice_skipped": sum(e["args"]["skipped"] for e in calls),
            "slice_host_ms": 1e3 * (sum(e["dur_s"] for e in calls) - waited)}


def analogy_window(model, strings: list, tf: dict, seconds: float, slice_=None,
                   lap=lambda name: None):
    """Warm up, then measure for ``seconds``. Returns the window's finished
    calls and its bounds."""
    # a section's program is compiled for its capacity: every section runs once
    # before the window
    for section in strings:
        model.analogy_accuracy(section)
    lap("every section run once")
    gc.collect()
    gc.freeze()      # see kinds/train.py: no full collection over 3M words mid-window
    caller = Caller(model, strings)
    caller.start()
    while len(caller.done) < tf["warmup_calls"]:
        time.sleep(0.005)
    t_open = time.perf_counter()
    opened = None
    while (now := time.perf_counter()) - t_open < seconds or (
            slice_ is not None and slice_.started and not slice_.stopped):
        # the caller goes on while the profiler starts and stops (seconds, both)
        if slice_ is not None and not slice_.started and now - t_open >= seconds / 3.0:
            slice_.start()
            opened = time.perf_counter()
        elif (slice_ is not None and slice_.started and not slice_.stopped
              and now - opened >= tf["trace_slice_s"]):
            slice_.stop()
        time.sleep(0.002)
    t_close = time.perf_counter()
    caller.stop()
    return caller.between(t_open, t_close), (t_open, t_close)


def draw_sample(seed: int, finished: list, ids: list, tf: dict) -> list:
    """``check_calls`` of the window's calls by seed, one of the largest section
    always among them: (section, the call's counts)."""
    calls = [q for q in finished if not isinstance(q[3], Exception)]
    if not calls:
        return []
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 0x5A3])
    largest = int(np.argmax([len(s) for s in ids]))
    first = [i for i, q in enumerate(calls) if q[2] == largest][:1]
    rest = [i for i in rng.permutation(len(calls)).tolist() if i not in first]
    take = (first + rest)[:tf["check_calls"]]
    return [(calls[i][2], calls[i][3]) for i in take]


def replay(model, sample: list, ids: list) -> list:
    """The sampled sections asked once more, through ``analogies`` (the same
    program, ``num`` = 1): each live question's row and cosine."""
    out = []
    for s, _ in sample:
        answers = model.analogies(ids[s][(ids[s] >= 0).all(axis=1)][:, :3])
        out.append((np.asarray([int(a[0][0][1:]) for a in answers], np.int64),
                    np.asarray([a[0][1] for a in answers], np.float64)))
    return out


def compare_with_reference(seed: int, sizes: dict, cell: dict, tiny: bool, sample: list,
                           replayed: list, ids: list, limits: dict, checks: Checks):
    """The sampled calls against the reference over the table it makes itself from
    the seed, planted rows and all."""
    import jax.numpy as jnp

    from reference import analogy_ref as ref

    cfg, tf = sizes_of(cell["config"], tiny), sizes_of(cell["traffic"], tiny)
    v, d = sizes["v"], sizes["d"]
    y_ids, y_rows, _ = plant_relations(seed, cfg, tf, d, sizes["half_width"])
    s32 = weights.seed32(seed)
    slot = np.full(v, -1, np.int32)
    slot[y_ids] = np.arange(len(y_ids), dtype=np.int32)
    slot, y_rows = jnp.asarray(slot), jnp.asarray(y_rows)

    def table(rows):
        base = weights.rows_uniform(s32, 0, rows, d, d, sizes["half_width"])
        at = slot[rows]
        return jnp.where((at >= 0)[:, None], y_rows[jnp.maximum(at, 0)], base)

    errs, gaps, counts_off, replay_off, excluded = [], [], 0, 0, 0
    served_right = true_right = scored = 0
    accuracies = []
    for (s, said), (rows, cosines) in zip(sample, replayed):
        asked = ids[s][(ids[s] >= 0).all(axis=1)]
        true = ref.accuracy(table, v, ids[s])
        at_served = true["score_of"](rows[:, None])[:, 0]
        errs.extend(np.abs(cosines - at_served).tolist())
        gaps.extend(np.maximum(0.0, true["cosines"] - at_served).tolist())
        excluded += int((rows[:, None] == asked[:, :3]).any(axis=1).sum())
        counts_off += sum(abs(int(said[key]) - int(true[key]))
                          for key in ("seen", "scored", "skipped"))
        replay_off += abs(int((rows == asked[:, 3]).sum()) - int(said["correct"]))
        served_right += int(said["correct"])
        true_right += int(true["correct"])
        scored += int(true["scored"])
        accuracies.append(true["accuracy"])
    print(f"check analogy: {len(sample)} calls compared, {scored} questions scored; the "
          f"reference's accuracy {min(accuracies):.4f} to {max(accuracies):.4f} a section, "
          f"{true_right / max(scored, 1):.4f} pooled, the program's "
          f"{served_right / max(scored, 1):.4f}; cosine error mean {np.mean(errs):.3e} max "
          f"{np.max(errs):.3e}; widest gap below the true best {np.max(gaps):.3e}",
          flush=True)
    checks.add("score_abs_err_mean", float(np.mean(errs)), limits["score_abs_err_mean"])
    checks.add("score_abs_err_max", float(np.max(errs)), limits["score_abs_err_max"])
    checks.add("rank_gap_max", float(np.max(gaps)), limits["rank_gap_max"])
    checks.add("accuracy_gap", abs(served_right - true_right) / max(scored, 1),
               limits["accuracy_gap"])
    checks.add("excluded_words_returned", excluded, 0)
    checks.add("counts_differ", counts_off, 0)
    checks.add("replay_differs", replay_off, 0)
    # a table of independent rows, or one where every relation holds exactly,
    # tells an exact scan from a wrong one in nothing
    checks.add("accuracy_not_between", int(not 0.02 < true_right / max(scored, 1) < 0.98), 0)


_NO_LIMIT = {"score_abs_err_mean": float("inf"), "score_abs_err_max": float("inf"),
             "rank_gap_max": float("inf"), "accuracy_gap": float("inf")}


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed, each over a
    short window at the cell's own load (benchmark/sweep_checks.py), the tables
    rebuilt per seed. The control scans bfloat16 tables."""
    tf = sizes_of(cell["traffic"], tiny)
    out = []
    for seed in seeds:
        model, sizes, strings, ids = build_model(
            cell, seed, tiny, table_dtype="bfloat16" if control else None)
        finished, _ = analogy_window(model, strings, tf, tf["check_window_s"])
        sample = draw_sample(seed, finished, ids, tf)
        replayed = replay(model, sample, ids)
        model.stop()
        del model
        checks = Checks()
        compare_with_reference(seed, sizes, cell, tiny, sample, replayed, ids, _NO_LIMIT, checks)
        out.append({name: value for name, value, _, _ in checks.rows})
        gc.unfreeze()
        gc.collect()
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, tiny: bool,
        t_start: float) -> dict:
    import jax

    from glint_word2vec_tpu.obs.spans import default_tracer

    require_operation()
    clog, laps = CompileLog(), Laps(t_start)
    tf = sizes_of(cell["traffic"], tiny)
    cfg = sizes_of(cell["config"], tiny)
    model, sizes, strings, ids = build_model(cell, seed, tiny, laps=laps)
    slice_ = TracedSlice() if trace else None
    try:
        finished, (t_open, t_close) = analogy_window(model, strings, tf, seconds, slice_,
                                                     laps.lap)
    finally:
        if slice_ is not None and not slice_.stopped:
            slice_.abandon()
    laps.lap("warm-up and window")
    laps.show()
    window_s = t_close - t_open
    late = clog.between(t_open, t_close)
    ok = [q for q in finished if not isinstance(q[3], Exception)]
    questions = sum(len(strings[q[2]]) for q in ok)
    failed = sum(len(strings[q[2]]) for q in finished) - questions
    lat_ms = np.sort([1e3 * (q[1] - q[0]) for q in ok])
    print(f"analogy: window {window_s:.3f}s, {len(finished)} calls of {questions + failed} "
          f"questions finished ({len(finished) - len(ok)} calls failed), call latency p50 "
          f"{np.percentile(lat_ms, 50):.3f} ms p95 {np.percentile(lat_ms, 95):.3f} ms, "
          f"{len(lat_ms) - int(0.95 * len(lat_ms))} calls beyond the 95th percentile; "
          f"{len(late)} compilations inside the window {late}; persistent cache "
          f"{clog.cache_hits} hits / {clog.cache_misses} misses", flush=True)
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    counters = span_counters(default_tracer().events()) if trace else {}
    sample = draw_sample(seed, finished, ids, tf)
    replayed = replay(model, sample, ids)
    peak = memory_peak_bytes()
    # one program scans for the part's live questions (its capacity's padding is
    # work the answer does not need): the mean of the traced slice
    shapes = dict(queries=counters.get("slice_questions", 0) / max(counters.get("slice_calls", 0), 1),
                  vocab=sizes["v"], dim=sizes["d"], table_dtype=cfg["param_dtype"])
    model.stop()
    del model
    checks = Checks()
    compare_with_reference(seed, sizes, cell, tiny, sample, replayed, ids,
                           cfg["check"]["analogy"], checks)
    checks.add("compilations_in_window", len(late), 0)
    end_to_end = {"query_per_s": questions / window_s,
                  "query_p95_ms": float(np.percentile(lat_ms, 95)),
                  "setup_s": t_open - t_start}
    return dict(correct=checks.ok and failed == 0, attempted=questions + failed,
                failed=failed, end_to_end=end_to_end, counters=counters,
                shapes=shapes, slice=sl, memory_peak_bytes=peak)
