"""Kind ``transform``: closed-loop callers of ``transform_sentences(slide)``, the
upstream model's ``transform(dataset)`` (ml:428-460): one averaged vector a
sentence, a slide of ``slide_rows`` sentences a call.

Set-up: vocabulary from counts; both tables made on the device from ``--seed``
in the shape a fit or ``load`` hands to its model ([V, D], no lane padding;
syn1 resident beside syn0), wrapped in a ``Word2VecModel``. Every caller owns
``slides_per_caller`` distinct seeded slides (sentence lengths by the traffic
file's law, tokens by the vocabulary's Zipf counts as fresh strings, a share of
the tokens strings the vocabulary lacks, a share of the sentences such strings
alone), built before the window and cycled. ``callers`` threads each call
``model.transform_sentences(slide)`` and send their next slide when the array
comes back. A "query" is one sentence: ``query_per_s`` counts the sentences
whose vector came back inside the window, ``query_p95_ms`` is the 95th
percentile over those sentences of their CALL's latency (every sentence of a
slide shares it). The replies are dropped (12 MB each); ``check_rows_per_call``
rows of each are copied aside: the slide's last sentence, one of its all-OOV
sentences, the rest by seed. Once the model is stopped and its tables freed, the
plain reference (reference/transform_ref.py) scores a seeded sample of them.

A program that cannot run the cell ends with a message and a non-zero exit, not
a hang: at once where its ``transform.enqueue`` span says nothing of
``rows_cap`` (asked of a 64-row table before anything large is built: it has
no fixed-shape slide program to measure), and at ``warmup_deadline_s`` where its
warm-up has not come back (a watchdog thread of the kind's own).
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


def build_model(cell: dict, seed: int, tiny: bool, table_dtype=None, laps=None):
    """The model whose ``transform_sentences`` is called. ``table_dtype``
    overrides the configuration's only for the lower-precision control."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel

    lap = laps.lap if laps else (lambda name: None)
    lap("imports")
    cfg = sizes_of(cell["config"], tiny)
    v, d, half_width = cfg["vocab_size"], cfg["vector_size"], cfg["table_half_width"]
    vocab = Vocabulary.from_words_and_counts(
        zipf.words_of(v), zipf.zipf_counts(v).astype(np.int64))
    lap("vocabulary")
    dtype = jnp.dtype(table_dtype or cfg["param_dtype"])
    syn0 = weights.make_table(seed, 0, v, d, d, half_width, dtype)
    syn1 = weights.make_table(seed, 1, v, d, d, half_width, dtype)
    syn1.block_until_ready()
    lap("tables on device")
    model = Word2VecModel(vocab, syn0, syn1, config=Word2VecConfig(vector_size=d))
    del syn0, syn1
    return model, dict(v=v, d=d, half_width=half_width)


def slide_engagement(model, slide) -> dict:
    """What the program says of one slide: the args of its ``transform.enqueue``
    span, with the program's span recorder on for that call alone."""
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    tracer.configure(True)
    try:
        model.transform_sentences(slide)
        spans = [e for e in tracer.events() if e["name"] == "transform.enqueue"]
    finally:
        tracer.configure(False)
        tracer.clear()
    return dict(spans[-1].get("args", {})) if spans else {}


def require_row_capacity():
    """Before anything large is built: a program whose ``transform.enqueue``
    says nothing of ``rows_cap`` has no fixed-shape program a slide, and the
    run ends here, with a message and exit code 1. Asked of a table of 64 rows."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel

    rows = weights.rows_uniform(weights.seed32(0), 0, jnp.arange(64, dtype=jnp.int32),
                                8, 8, 0.5)
    model = Word2VecModel(
        Vocabulary.from_words_and_counts(zipf.words_of(64), np.ones(64, np.int64)),
        np.asarray(rows))
    said = slide_engagement(model, [["w1", "w2", "nope"], ["w3"]])
    model.stop()
    if "rows_cap" not in said:
        raise SystemExit(
            f"benchmark: this program's transform.enqueue says {said} and nothing of a "
            "row capacity: it has no fixed-shape slide program to measure in this cell")


def make_slides(seed: int, v: int, tf: dict) -> list:
    """``callers`` x ``slides_per_caller`` slides, each (sentences, all-OOV
    flags): ``slide_rows`` lists of fresh strings (a tokenizer hands over
    strings it made, not the vocabulary's own objects)."""
    law = tf["sentence_len"]
    slides = []
    for c in range(tf["callers"]):
        mine = []
        for j in range(tf["slides_per_caller"]):
            rng = np.random.default_rng([seed, 0x7F0, c, j])
            lens = np.clip(np.rint(rng.lognormal(np.log(law["median"]), law["sigma"],
                                                 tf["slide_rows"])),
                           law["min"], law["max"]).astype(np.int64)
            ends = np.cumsum(lens)
            n = int(ends[-1])
            empty = rng.random(tf["slide_rows"]) < tf["empty_share"]
            lost = (rng.random(n) < tf["oov_share"]) | np.repeat(empty, lens)
            words = [f"w{i}" for i in zipf.draw(rng, v, n).tolist()]
            for i in np.flatnonzero(lost).tolist():
                words[i] = f"q{i}"        # no vocabulary word starts with q
            starts = (ends - lens).tolist()
            mine.append(([words[a:b] for a, b in zip(starts, ends.tolist())], empty))
        slides.append(mine)
    return slides


def check_rows_of(seed: int, slides: list, tf: dict) -> list:
    """Which rows of a slide's reply are copied aside, the same at every call of
    it: its last sentence (where a capacity would cut), one of its all-OOV
    sentences where it has one (the zero-vector branch), the rest by seed."""
    out = []
    for c, mine in enumerate(slides):
        out.append([])
        for j, (sentences, empty) in enumerate(mine):
            rng = np.random.default_rng([seed, 0x7F1, c, j])
            rows = [len(sentences) - 1]
            if empty.any():
                rows.append(int(rng.choice(np.flatnonzero(empty))))
            while len(rows) < tf["check_rows_per_call"]:
                r = int(rng.integers(0, len(sentences)))
                if r not in rows:
                    rows.append(r)
            out[-1].append(np.asarray(rows[:tf["check_rows_per_call"]]))
    return out


def keep_rows(reply, rows: np.ndarray, sentences: int, dim: int):
    """What is kept of one reply before it is dropped: whether it is anything
    but ``float32[sentences, dim]`` (a shape fault), and a copy of its ``rows``
    (of a reply with fewer, the last it has)."""
    fault = int(reply.shape != (sentences, dim) or reply.dtype != np.float32)
    return fault, reply[np.minimum(rows, len(reply) - 1)].copy()


class Callers:
    """``n`` closed-loop caller threads; every finished call is kept as (sent,
    received, (caller, slide), the copied rows or the exception, shape fault)."""

    def __init__(self, model, slides: list, check_rows: list, dim: int):
        self.done = [[] for _ in slides]
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._loop, args=(model, c, slides[c], check_rows[c], dim),
                             name=f"bench-caller-{c}", daemon=True)
            for c in range(len(slides))]

    def _loop(self, model, c, mine, rows, dim):
        import jax.profiler as jp
        out, at = self.done[c], 0
        while not self._stop.is_set():
            j = at % len(mine)
            at += 1
            fault = 0
            sent = time.perf_counter()
            try:
                with jp.TraceAnnotation("bench.transform"):
                    reply = model.transform_sentences(mine[j][0])
                received = time.perf_counter()
                fault, kept = keep_rows(reply, rows[j], len(mine[j][0]), dim)
            except Exception as e:  # noqa: BLE001 — counted as failed
                received, kept = time.perf_counter(), e
            out.append((sent, received, (c, j), kept, fault))

    def start(self):
        for t in self._threads:
            t.start()

    def completed(self) -> list:
        return [len(d) for d in self.done]

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=120)
        if any(t.is_alive() for t in self._threads):
            raise RuntimeError("a caller never returned")

    def between(self, lo: float, hi: float) -> list:
        return [q for d in self.done for q in d if lo < q[1] <= hi]


def start_warmup_deadline(callers: Callers, calls: int, seconds: float) -> threading.Event:
    """A watchdog that ends the process, with a message and exit code 3, where
    the set-up has not come as far as every caller's ``calls`` warm-up calls
    ``seconds`` from now: a thread of its own, since the thread that waits may
    be inside a device call. Setting the returned event calls it off."""
    off, until = threading.Event(), time.perf_counter() + seconds

    def watch():
        while not off.wait(0.25):
            if min(callers.completed()) >= calls:
                return
            if time.perf_counter() > until:
                print(f"benchmark: the warm-up did not finish inside its deadline of "
                      f"{seconds:.0f} s: this program cannot transform the slides",
                      file=sys.stderr, flush=True)
                os._exit(3)

    threading.Thread(target=watch, name="bench-warmup-deadline", daemon=True).start()
    return off


def span_counters(events: list) -> dict:
    """Sums over the program's ``transform.*`` spans of the traced slice, for
    the ratios no one span holds (reader ``counter``): tokens sent and dropped
    as OOV, live ids and rows handed to the program, slides in flight."""
    slides = [e["args"] for e in events if e["name"] == "transform.slide"]
    enqueued = [e["args"] for e in events if e["name"] == "transform.enqueue"]
    if not slides or not enqueued:
        return {}
    return {"slice_oov": sum(a["oov"] for a in slides),
            "slice_tokens": sum(a["oov"] + a["words"] for a in slides),
            "slice_rows_live": sum(a["rows"] for a in enqueued),
            "slice_rows_handed": sum(a["rows_cap"] * a["passes"] for a in enqueued),
            "slice_inflight_and_self": sum(a["inflight"] + 1 for a in enqueued),
            "slice_enqueues": len(enqueued)}


def transform_window(model, slides: list, check_rows: list, tf: dict, dim: int,
                     seconds: float, slice_=None, lap=lambda name: None):
    """Warm up, then measure for ``seconds``. Returns the window's finished
    calls, its bounds, the counters over it, and what the program said of its
    first slide (the whole-lane form's build and the first compile are there)."""
    callers = Callers(model, slides, check_rows, dim)
    deadline = start_warmup_deadline(callers, tf["warmup_calls"], tf["warmup_deadline_s"])
    try:
        engaged = slide_engagement(model, slides[0][0][0])
        lap("first slide")
        # a slide's program is compiled for its row capacity, which follows its
        # live words: every slide runs once before the window
        for mine in slides:
            for sentences, _ in mine:
                model.transform_sentences(sentences)
        lap("every slide run once")
        gc.collect()
        gc.freeze()      # see kinds/train.py: no full collection over 3M words mid-window
        callers.start()
        while min(callers.completed()) < tf["warmup_calls"]:
            time.sleep(0.005)
    finally:
        deadline.set()
    t_open = time.perf_counter()
    marks = {}
    while (now := time.perf_counter()) - t_open < seconds or (
            slice_ is not None and slice_.started and not slice_.stopped):
        # the callers go on while the profiler starts and stops (seconds, both),
        # so the slice's calls are those received between the two marks
        if slice_ is not None and not slice_.started and now - t_open >= seconds / 3.0:
            slice_.start()
            marks["open"] = time.perf_counter()
        elif (slice_ is not None and slice_.started and not slice_.stopped
              and now - marks["open"] >= tf["trace_slice_s"]):
            marks["close"] = time.perf_counter()
            slice_.stop()
        time.sleep(0.002)
    t_close = time.perf_counter()
    callers.stop()
    counters = {}
    if "close" in marks:
        counters["slice_calls"] = len(callers.between(marks["open"], marks["close"]))
    return callers.between(t_open, t_close), (t_open, t_close), counters, engaged


def draw_sample(seed: int, slides: list, check_rows: list, finished: list, tf: dict) -> list:
    """``check_sentences`` of the window's copied rows, whole calls at a time by
    seed: (tokens, whether all-OOV, served row)."""
    calls = [q for q in finished if not isinstance(q[3], Exception)]
    rng = np.random.default_rng([seed, 0x5A3])
    want = -(-tf["check_sentences"] // tf["check_rows_per_call"])
    take = rng.choice(len(calls), size=min(want, len(calls)), replace=False)
    sample = []
    for i in take:
        _, _, (c, j), kept, _ = calls[i]
        sentences, empty = slides[c][j]
        for r, row in zip(check_rows[c][j], kept):
            sample.append((sentences[r], bool(empty[r]), row))
    return sample[:tf["check_sentences"]]


def compare_with_reference(seed: int, sizes: dict, sample: list, limits: dict,
                           checks: Checks, rows_fn=None):
    """The sampled rows against the reference's means over the table it makes
    itself from the seed (``rows_fn``: another table's rows, for the tests):
    the widest and the mean |served - true| over the true row's own norm, and
    the all-OOV sentences of the sample answered with exact zeros."""
    from reference import transform_ref as ref

    rows_fn = rows_fn or ref.seeded_rows(seed, sizes["d"], sizes["half_width"])
    true = ref.sentence_vectors([s for s, _, _ in sample], ref.dictionary(sizes["v"]),
                                rows_fn, sizes["d"])
    errs, not_zero, empties = [], 0, 0
    for want, (_, empty, got) in zip(true, sample):
        norm = float(np.linalg.norm(want))
        if empty or norm == 0.0:
            empties += 1
            not_zero += int(np.any(got != 0.0) or norm != 0.0)
        else:
            errs.append(float(np.linalg.norm(got.astype(np.float64) - want)) / norm)
    print(f"check transform: {len(sample)} sentences compared, {empties} of them "
          f"all out of vocabulary; row error over the row's norm mean "
          f"{np.mean(errs):.3e} max {np.max(errs):.3e}", flush=True)
    checks.add("row_rel_err_mean", float(np.mean(errs)), limits["row_rel_err_mean"])
    checks.add("row_rel_err_max", float(np.max(errs)), limits["row_rel_err_max"])
    checks.add("empty_rows_not_zero", not_zero, 0)
    checks.add("empty_rows_compared_short", int(empties == 0), 0)


def add_engagement_checks(checks: Checks, engaged: dict, expect: dict):
    """The program's own account of a slide, held to the configuration's."""
    print(f"check transform: transform.enqueue says {engaged}", flush=True)
    handed = engaged.get("rows_cap", 0) * engaged.get("passes", 0)
    checks.add("slide_passes_off", abs(engaged.get("passes", 0) - 1), 0)
    checks.add("rows_per_word", handed / max(engaged.get("rows", 0), 1) if handed
               else float("inf"), expect["rows_per_word_max"])


_NO_LIMIT = {"row_rel_err_mean": float("inf"), "row_rel_err_max": float("inf")}


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed, each over a
    short window at the cell's own load (benchmark/sweep_checks.py), the tables
    rebuilt per seed. The control transforms over bfloat16 tables."""
    tf = sizes_of(cell["traffic"], tiny)
    expect = sizes_of(cell["config"], tiny)["check"]["transform"]
    out = []
    for seed in seeds:
        model, sizes = build_model(cell, seed, tiny,
                                   table_dtype="bfloat16" if control else None)
        slides = make_slides(seed, sizes["v"], tf)
        rows = check_rows_of(seed, slides, tf)
        finished, _, _, engaged = transform_window(model, slides, rows, tf, sizes["d"],
                                                   tf["check_window_s"])
        model.stop()
        del model
        checks = Checks()
        compare_with_reference(seed, sizes, draw_sample(seed, slides, rows, finished, tf),
                               _NO_LIMIT, checks)
        add_engagement_checks(checks, engaged, expect)
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
    require_row_capacity()
    model, sizes = build_model(cell, seed, tiny, laps=laps)
    slides = make_slides(seed, sizes["v"], tf)
    rows = check_rows_of(seed, slides, tf)
    laps.lap("slides")
    slice_ = TracedSlice() if trace else None
    try:
        finished, (t_open, t_close), counters, engaged = transform_window(
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
    print(f"transform: window {window_s:.3f}s, {len(finished)} calls of {per_call} "
          f"sentences finished ({len(finished) - len(ok)} failed), call latency p50 "
          f"{np.percentile(lat_ms, 50):.3f} ms p95 {np.percentile(lat_ms, 95):.3f} ms, "
          f"{len(lat_ms) - int(0.95 * len(lat_ms))} calls beyond the 95th percentile; "
          f"{len(late)} compilations inside the window {late}; persistent cache "
          f"{clog.cache_hits} hits / {clog.cache_misses} misses", flush=True)
    peak = memory_peak_bytes()
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    if trace:
        counters.update(span_counters(default_tracer().events()))
    live = counters.get("slice_rows_live", 0) / max(counters.get("slice_enqueues", 0), 1)
    shapes = dict(rows=live, sentences=per_call, dim=sizes["d"],
                  table_dtype=cfg["param_dtype"])
    model.stop()
    del model
    checks = Checks()
    compare_with_reference(seed, sizes, draw_sample(seed, slides, rows, finished, tf),
                           cfg["check"]["transform"], checks)
    add_engagement_checks(checks, engaged, cfg["check"]["transform"])
    checks.add("reply_shape_faults", sum(q[4] for q in finished), 0)
    checks.add("compilations_in_window", len(late), 0)
    # every sentence of a call shares its latency, and every call holds as many
    end_to_end = {"query_per_s": len(ok) * per_call / window_s,
                  "query_p95_ms": float(np.percentile(lat_ms, 95)),
                  "setup_s": t_open - t_start}
    return dict(correct=checks.ok and failed == 0, attempted=len(finished) * per_call,
                failed=failed, end_to_end=end_to_end, counters=counters,
                shapes=shapes, slice=sl, memory_peak_bytes=peak)
