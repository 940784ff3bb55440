"""Kind ``query``: closed-loop callers against ``EmbeddingService``'s exact scan.

Set-up: vocabulary from counts; both tables made on the device from ``--seed`` in
the shape a fit hands to its model ([V, D], no lane padding), wrapped in a
``Word2VecModel`` and served by ``EmbeddingService(model=..., ann=False)`` at the
program's defaults. ``callers`` threads each call ``synonyms(word, k)`` and send
the next query when the reply arrives; query words are drawn by seed from the
vocabulary's own Zipf counts. The window opens once the warm-up batches have
come back and closes ``--seconds`` later; a query counts where its reply arrived
inside it. Once the service is closed and its tables freed, the plain reference
scores a seeded sample of the window's replies.
"""

import gc
import threading
import time

import numpy as np

from harness import weights, zipf
from harness.loader import sizes as sizes_of
from harness.common import Checks, CompileLog, TracedSlice, memory_peak_bytes


def build_service(cell: dict, seed: int, tiny: bool, table_dtype=None):
    """The served model and its service. ``table_dtype`` overrides the
    configuration's only for the lower-precision control."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.serve.service import EmbeddingService

    cfg = sizes_of(cell["config"], tiny)
    v, d = cfg["vocab_size"], cfg["vector_size"]
    vocab = Vocabulary.from_words_and_counts(
        zipf.words_of(v), zipf.zipf_counts(v).astype(np.int64))
    dtype = jnp.dtype(table_dtype or cfg["param_dtype"])
    syn0 = weights.make_table(seed, 0, v, d, d, 0.5, dtype)
    syn1 = weights.make_table(seed, 1, v, d, d, 0.5, dtype)
    model = Word2VecModel(vocab, syn0, syn1, config=Word2VecConfig(vector_size=d))
    return EmbeddingService(model=model, ann=False), model, dict(v=v, d=d)


class Callers:
    """``n`` closed-loop caller threads; every finished query is kept as
    (sent, received, word id, reply or exception)."""

    def __init__(self, service, n: int, k: int, word_ids: np.ndarray):
        self.done = [[] for _ in range(n)]
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._loop, args=(service, i, k, word_ids[i]),
                             name=f"bench-caller-{i}", daemon=True)
            for i in range(n)]

    def _loop(self, service, i, k, ids):
        import jax.profiler as jp
        out, at = self.done[i], 0
        while not self._stop.is_set():
            wid = int(ids[at % ids.shape[0]])
            at += 1
            sent = time.perf_counter()
            try:
                with jp.TraceAnnotation("bench.synonyms"):
                    reply = service.synonyms(f"w{wid}", k)
            except Exception as e:  # noqa: BLE001 — counted as failed
                reply = e
            out.append((sent, time.perf_counter(), wid, reply))

    def start(self):
        for t in self._threads:
            t.start()

    def completed(self) -> int:
        return sum(len(d) for d in self.done)

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=120)
        if any(t.is_alive() for t in self._threads):
            raise RuntimeError("a caller never returned")

    def between(self, lo: float, hi: float) -> list:
        return [q for d in self.done for q in d if lo < q[1] <= hi]


def serve_window(service, service_model, tf: dict, seed: int, v: int, seconds: float,
                 slice_=None):
    """Warm up, then measure for ``seconds``. Returns the window's finished
    queries, its bounds, and the batcher's counters over it."""
    n, k = tf["callers"], tf["num_synonyms"]
    rng = np.random.default_rng([seed, 0x9E4])
    ids = zipf.draw(rng, v, n * tf["queries_per_caller"]).reshape(n, -1)
    # the batcher dispatches whatever has arrived when its delay runs out, so a
    # batch can have any size up to the callers (or its cap), and the scan is one
    # compiled program per size: every size runs once before the window
    model_sizes = min(n, service.stats()["max_batch"])
    for q in range(1, model_sizes + 1):
        service_model.find_synonyms_batch([f"w{int(i)}" for i in ids[:q, -1]], k)
    gc.collect()
    gc.freeze()      # see kinds/train.py: no full collection over 3M words mid-window
    callers = Callers(service, n, k, ids)
    callers.start()
    while callers.completed() < tf["warmup_batches"] * n:
        time.sleep(0.005)
    s_open, t_open = service.stats(), time.perf_counter()
    marks = {}
    while (now := time.perf_counter()) - t_open < seconds or (
            slice_ is not None and slice_.started and not slice_.stopped):
        # the callers go on while the profiler starts and stops (seconds, both),
        # so the batches of the slice are counted inside those two calls
        if slice_ is not None and not slice_.started and now - t_open >= seconds / 3.0:
            slice_.start()
            marks["open"], marks["t"] = service.stats(), time.perf_counter()
        elif (slice_ is not None and slice_.started and not slice_.stopped
              and now - marks["t"] >= tf["trace_slice_s"]):
            marks["close"] = service.stats()
            slice_.stop()
        time.sleep(0.002)
    t_close, s_close = time.perf_counter(), service.stats()
    callers.stop()
    counters = {"batch_items": s_close["completed"] - s_open["completed"],
                "batch_slots": (s_close["batches"] - s_open["batches"]) * s_open["max_batch"]}
    if "close" in marks:
        counters["slice_calls"] = marks["close"]["batches"] - marks["open"]["batches"]
        counters["slice_items"] = marks["close"]["completed"] - marks["open"]["completed"]
    return callers.between(t_open, t_close), (t_open, t_close), counters


def compare_with_reference(seed: int, sizes: dict, finished: list, tf: dict,
                           limits: dict, checks: Checks):
    """A seeded sample of the window's replies against the reference's exact
    cosines over the table it makes itself from the seed."""
    import jax.numpy as jnp

    from reference import sgns_ref

    v, d, k = sizes["v"], sizes["d"], tf["num_synonyms"]
    replies = [q for q in finished if not isinstance(q[3], Exception)]
    rng = np.random.default_rng([seed, 0x5A3])
    take = rng.choice(len(replies), size=min(tf["check_queries"], len(replies)),
                      replace=False)
    sample = [replies[i] for i in take]
    s32 = weights.seed32(seed)

    def table(ids):
        return weights.rows_uniform(s32, 0, ids, d, d, 0.5)

    qids = np.asarray([q[2] for q in sample], np.int32)
    scores = sgns_ref.cosine_scores(table, v, table(jnp.asarray(qids)))
    errs, rank_gaps, shape_faults = [], [], 0
    for row, (_, _, wid, reply) in zip(scores, sample):
        row = row.copy()
        row[wid] = -np.inf                      # a word is not its own synonym
        kth = np.partition(row, v - k)[v - k]
        got = [int(w[1:]) for w, _ in reply]
        shape_faults += int(len(reply) != k or wid in got or len(set(got)) != len(got))
        for w, (_, score) in zip(got, reply):
            errs.append(abs(score - row[w]))
            rank_gaps.append(max(0.0, kth - row[w]))
    print(f"check query: {len(sample)} replies of {len(replies)} compared, "
          f"{len(errs)} neighbours; score error mean {np.mean(errs):.3e} max "
          f"{np.max(errs):.3e}; widest gap below the true {k}-th best "
          f"{np.max(rank_gaps):.3e}", flush=True)
    checks.add("score_abs_err_mean", float(np.mean(errs)), limits["score_abs_err_mean"])
    checks.add("score_abs_err_max", float(np.max(errs)), limits["score_abs_err_max"])
    checks.add("rank_gap_max", float(np.max(rank_gaps)), limits["rank_gap_max"])
    checks.add("reply_shape_faults", shape_faults, 0)


_NO_LIMIT = {"score_abs_err_mean": float("inf"), "score_abs_err_max": float("inf"),
             "rank_gap_max": float("inf")}


def _close(service, model):
    service.close()
    model.stop()
    gc.collect()


def check_readings(cell: dict, seeds: list, control: bool, tiny: bool) -> list:
    """The check's numbers with no limit applied, one dict per seed, each over a
    short window at the cell's own load (benchmark/sweep_checks.py). The control
    serves bfloat16 tables."""
    tf = sizes_of(cell["traffic"], tiny)
    out = []
    for seed in seeds:
        service, model, sizes = build_service(
            cell, seed, tiny, table_dtype="bfloat16" if control else None)
        finished, _, _ = serve_window(service, model, tf, seed, sizes["v"],
                                      tf["check_window_s"])
        _close(service, model)
        del service, model
        checks = Checks()
        compare_with_reference(seed, sizes, finished, tf, _NO_LIMIT, checks)
        out.append({name: value for name, value, _, _ in checks.rows})
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, tiny: bool,
        t_start: float) -> dict:
    import jax

    clog = CompileLog()
    tf = sizes_of(cell["traffic"], tiny)
    service, model, sizes = build_service(cell, seed, tiny)
    slice_ = TracedSlice() if trace else None
    try:
        finished, (t_open, t_close), counters = serve_window(
            service, model, tf, seed, sizes["v"], seconds, slice_)
    finally:
        if slice_ is not None and not slice_.stopped:
            slice_.abandon()
    window_s = t_close - t_open
    late = clog.between(t_open, t_close)
    failed = sum(isinstance(q[3], Exception) for q in finished)
    lat_ms = np.sort([1e3 * (q[1] - q[0]) for q in finished
                      if not isinstance(q[3], Exception)])
    print(f"query: window {window_s:.3f}s, {len(finished)} queries finished "
          f"({failed} failed), {len(lat_ms)} latencies, p50 "
          f"{np.percentile(lat_ms, 50):.3f} ms p95 {np.percentile(lat_ms, 95):.3f} ms, "
          f"{len(lat_ms) - int(0.95 * len(lat_ms))} samples beyond the 95th percentile; "
          f"{len(late)} compilations inside the window {late}; persistent cache "
          f"{clog.cache_hits} hits / {clog.cache_misses} misses", flush=True)
    peak = memory_peak_bytes()
    sl = slice_.reduce(jax.devices()[0].platform) if trace else None
    # one dispatch scans for as many queries as the batcher coalesced: the mean
    # batch of the traced slice (the table's bytes, which bound it, do not change)
    shapes = dict(queries=counters.get("slice_items", 0) / max(counters.get("slice_calls", 0), 1),
                  vocab=sizes["v"], dim=sizes["d"],
                  table_dtype=cell["config"]["param_dtype"])
    _close(service, model)
    del service, model
    checks = Checks()
    compare_with_reference(seed, sizes, finished, tf,
                           sizes_of(cell["config"], tiny)["check"]["query"], checks)
    checks.add("compilations_in_window", len(late), 0)
    end_to_end = {"query_per_s": len(lat_ms) / window_s,
                  "query_p95_ms": float(np.percentile(lat_ms, 95)),
                  "setup_s": t_open - t_start}
    return dict(correct=checks.ok and failed == 0, attempted=len(finished),
                failed=failed, end_to_end=end_to_end, counters=counters,
                shapes=shapes, slice=sl, memory_peak_bytes=peak)
