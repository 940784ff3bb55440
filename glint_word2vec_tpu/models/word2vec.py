"""Word2Vec model — transform, sentence averaging, synonym/analogy search, persistence.

The TPU-native model API with the capabilities of both reference model layers:

- MLlib model (C8, mllib:460-669): ``transform`` (word → vector; batched iterator),
  ``find_synonyms`` (word and vector overloads), ``get_vectors``, ``to_local``, ``save``,
  ``stop``.
- ML model (C12, ml:322-497): sentence ``transform`` = **average of in-vocab word
  vectors** (ml:428-460, server-side pullAverage), ``find_synonyms_array``,
  ``get_vectors`` as a streaming iterator.

Where the reference pays an RPC per op (pull/pullAverage/norms/multiply with 1-5 min
Await timeouts, mllib:486-652), every op here is a jitted gather/reduction on the sharded
embedding array; ``find_synonyms``'s full-vocab matvec + top-k (mllib:583-630: client-side
O(V) scan over a PS matvec) runs as one sharded ``cosine = (syn0 @ q) / ‖rows‖`` + top-k
on device.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import logging
from typing import (
    Dict, Iterable, Iterator, List, NamedTuple, Optional, Sequence, Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.subword import NO_ROW as _NO_ROW
from glint_word2vec_tpu.data.vocab import Vocabulary
from glint_word2vec_tpu.lockcheck import make_lock
from glint_word2vec_tpu.obs.spans import default_tracer, pinned_call
from glint_word2vec_tpu.ops.scan import (
    _LISTED, _VECTOR, _analogy_topk, _row_shards, _scan_counts, _topk_dispatch)
from glint_word2vec_tpu.ops.transform import (
    _segment_means, _sentence_means, _sharded_rows)
from glint_word2vec_tpu.parallel.mesh import (
    MeshPlan, pad_dim_to_lanes, pad_vocab_for_sharding)
from glint_word2vec_tpu.train import checkpoint as ckpt

logger = logging.getLogger("glint_word2vec_tpu")


class Word2VecModel:
    """Trained word embeddings with the full reference model-op surface.

    Resident on the device, until :meth:`stop`: syn0 ``[V, D]`` (a subword
    model's composed table) and syn1 as they were handed over, the row norms
    once a scan has asked for them, a subword model's own rows and bucket rows
    (``_compose``), and, once ``transform_sentences``, ``transform_words`` or
    ``pull`` has read rows, syn0 again with D widened to whole lanes of 128
    (:meth:`_row_table`: 4.61 GB beside syn0's 3.60 at 3M x 300 float32; none
    where D is a multiple of 128; over a table partitioned by rows the form
    is partitioned as the table is, 3.84 GB a chip beside 3.0 at 10M x 300
    over four).

    ``resident="rows"`` builds a subword model that holds only what a row
    read reads: the composed table written straight at whole lanes, the
    bucket rows at whole lanes and the rows' norms (6.95 GB at wiki.en's
    2,519,370 + 2,000,000 rows x 300, where the five tables above are 16.0).
    ``sentence_vectors``, ``transform_sentences``, ``transform_words``,
    ``pull`` and ``transform(word)`` work on it; what scans, saves or exports
    a table it does not hold (``find_synonyms*``, ``multiply``, ``save``,
    ``syn0``, ``syn1``, the exports) raises, naming the argument."""

    @pinned_call("model.init", lambda self: dict(
        words=self.vocab.size, subword=int(self._buckets is not None),
        resident=self.resident))
    def __init__(
        self,
        vocab: Vocabulary,
        syn0: jax.Array,
        syn1: Optional[jax.Array] = None,
        config: Optional[Word2VecConfig] = None,
        plan: Optional[MeshPlan] = None,
        train_state: Optional["ckpt.TrainState"] = None,
        subword_buckets: Optional[jax.Array] = None,
        position_weights: Optional[np.ndarray] = None,
        subword_rows=None,
        resident: str = "all",
    ):
        """The whole of it is the pinned span ``model.init`` (obs/spans.py),
        over ``model.compose`` where the model is a subword one.
        ``resident``: ``"all"``, or ``"rows"`` for a subword model on one
        device that only reads rows (the class docstring): ``syn1`` is not
        placed, and the words' own rows are let go once the composed table
        is written (a caller that lets go of ``syn0`` too frees them)."""
        if resident not in ("all", "rows"):
            raise ValueError(f"resident is 'all' or 'rows', not {resident!r}")
        if resident == "rows" and (subword_buckets is None or plan is not None):
            raise ValueError(
                "resident='rows' builds a subword model on one device for row "
                "reads alone: it needs subword_buckets and takes no plan")
        self.resident = resident
        # a position-weighted CBOW model's third leaf
        # (config.cbow_position_weights): trained state kept so that a saved
        # model can be resumed; no query reads it and no export writes it
        self.position_weights = (None if position_weights is None
                                 else np.asarray(position_weights, np.float32))
        # a subword model (config.subword) answers with COMPOSED vectors: what
        # arrives is syn0's words' own rows and its bucket rows; every query
        # below scans h_w, the mean of a word's listed rows, made once here.
        # Resident afterwards: the composed table (every scan), the bucket
        # rows at whole lanes (an unseen string's vector is their mean, read
        # inside the scan's program) and the words' own rows (``save`` writes
        # what was trained, and the composed table cannot give them back)
        self._raw0 = self._buckets = None
        self._list_cap = 0
        self.compose_time = 0.0
        # strings composed from n-grams inside the scan's program, the live
        # bucket rows handed over for them, and the strings sent round
        # through the vector block, over the model's life
        self.query_counts = {"unseen": 0, "list_rows": 0, "overflow": 0}
        # the row reads' whole-lane form of syn0 (_row_table) and the transform
        # slides whose result is not in yet, both under the one lock
        self._lock = make_lock("model.rows")
        self._lanes: Optional[jax.Array] = None
        # the analogy scan's bfloat16 form of syn0 (_scan_table), on a TPU
        self._scan0: Optional[jax.Array] = None
        self._slides_inflight = 0
        self._norms: Optional[jax.Array] = None
        # a word's row's scale, 1 / its norm (0 for a row of zero norm): on
        # the device for the analogy scan, on the host for sentence_vectors'
        # encode (which takes a slide's scales there), and the ids of the
        # rows of zero norm, on the host too
        self._inv_norms: Optional[jax.Array] = None
        self._host_inv: Optional[np.ndarray] = None
        self._zero_rows: Optional[np.ndarray] = None
        self._ann = None
        self._stopped = False
        self.vocab = vocab
        self.plan = plan
        self.train_state = train_state
        if subword_buckets is not None:
            syn0 = self._compose(vocab, config, syn0, subword_buckets,
                                 subword_rows)
        self.config = config or Word2VecConfig(vector_size=int(syn0.shape[1]))
        if resident == "rows":
            # syn0 is the composed table at whole lanes: the one table kept
            self._dim = int(self._raw0.shape[1])
            self._raw0 = self._full0 = self._full1 = None
            self._lanes = syn0
            self._inverse_norms()
            return
        self._dim = int(syn0.shape[1])
        Vp = (pad_vocab_for_sharding(vocab.size, plan.num_model)
              if plan is not None else vocab.size)
        if syn0.shape[0] not in (vocab.size, Vp):
            raise ValueError(
                f"syn0 has {syn0.shape[0]} rows but vocabulary has {vocab.size} words")
        if plan is not None:
            # Row-sharding needs rows % num_model == 0: pad with zero rows (zero norm →
            # cosine 0 and explicitly masked out of top-k), the model-ops analog of the
            # trainer's pad_vocab_for_sharding. Arrays that arrive already padded AND
            # placed (the streaming load_params_into_plan path) are used as-is — no
            # host round-trip.
            placed = (isinstance(syn0, jax.Array) and syn0.shape[0] == Vp
                      and syn0.sharding.is_equivalent_to(plan.embedding, 2)
                      and (syn1 is None or (
                          isinstance(syn1, jax.Array)
                          and syn1.shape[0] == Vp
                          and syn1.sharding.is_equivalent_to(plan.embedding, 2))))
            if not placed:
                syn0 = jnp.asarray(syn0)
                syn1 = jnp.asarray(syn1) if syn1 is not None else None
                pad = Vp - syn0.shape[0]
                if pad:
                    zeros = jnp.zeros((pad, syn0.shape[1]), syn0.dtype)
                    syn0 = jnp.concatenate([syn0, zeros])
                    if syn1 is not None:
                        syn1 = jnp.concatenate([syn1, zeros])
                syn0 = jax.device_put(syn0, plan.embedding)
                if syn1 is not None:
                    syn1 = jax.device_put(syn1, plan.embedding)
        else:
            syn0 = jnp.asarray(syn0)
            syn1 = jnp.asarray(syn1) if syn1 is not None else None
        self._full0 = syn0
        self._full1 = syn1

    def _compose(self, vocab: Vocabulary, config: Word2VecConfig, syn0,
                 buckets, subword_rows=None) -> jax.Array:
        """[V, D] h_w of every word (fastText's ``get_word_vector``;
        ``precomputeWordVectors``), in row blocks on the device
        (ops/subword.compose_vectors): the pinned span ``model.compose``,
        whose ``dur`` is ``compose_time`` (a constructor runs before any
        trace is live). The two parts of the trained input table are read
        where they lie, the words' own rows as slices and the bucket rows gathered from
        their lane-padded form (ops/subword.lane_padded: made here, once,
        and kept for the unseen strings' lists): no [V + K, D] array, and no
        row-major copy of a table whose D is no multiple of 128. The row
        table is ``subword_rows`` (an ops/subword.SubwordTable on the device
        and its ``max_groups``, from a caller that has one: the estimator
        after a fit) or built here, and freed with the call. A model with
        ``resident="rows"`` gets the table written straight at whole lanes
        ([V, 384] for D = 300; the span's ``lanes``), the form its row reads
        gather from: no [V, D] table is made beside it."""
        from glint_word2vec_tpu.data.subword import (
            build_subword_table, groups_in_whole_units, list_capacity)
        from glint_word2vec_tpu.ops.subword import (
            COMPOSE_BLOCK, SubwordTable, compose_vectors, lane_padded)
        if config is None or not config.subword:
            raise ValueError("subword_buckets need a config with subword=True")
        if buckets.shape[0] != config.subword_buckets:
            raise ValueError(
                f"{buckets.shape[0]} bucket rows but config.subword_buckets is "
                f"{config.subword_buckets}")
        with default_tracer().span("model.compose", pinned=True,
                                   words=vocab.size) as sp:
            raw0 = jnp.asarray(syn0)
            self._raw0 = (raw0 if raw0.shape[0] == vocab.size
                          else raw0[: vocab.size])
            # [K, D] as a checkpoint holds them, or already at whole lanes
            # (a trainer's own): then nothing is copied
            buckets = jnp.asarray(buckets)
            if buckets.shape[1] not in (
                    raw0.shape[1], pad_dim_to_lanes(raw0.shape[1])):
                raise ValueError(
                    f"bucket rows are {buckets.shape[1]} wide but syn0's are "
                    f"{raw0.shape[1]}")
            self._buckets = lane_padded(buckets)
            del raw0, buckets
            if subword_rows is None:
                rows = build_subword_table(
                    vocab.words, config.subword_min_n, config.subword_max_n,
                    config.subword_buckets)
                subword_rows = (SubwordTable(
                    jnp.asarray(rows.offsets),
                    jnp.asarray(groups_in_whole_units(rows.rows)),
                    jnp.asarray(rows.counts)), rows.max_groups)
                del rows
            table, max_groups = subword_rows
            composed = compose_vectors(
                self._raw0, self._buckets, table, max_groups,
                whole_lanes=self.resident == "rows")
            composed.block_until_ready()
            sp.set(slots=int(table.counts.sum()),
                   blocks=-(-vocab.size // COMPOSE_BLOCK),
                   lanes=int(composed.shape[1]))
            self._list_cap = list_capacity(
                max(map(len, vocab.words)), config.subword_min_n,
                config.subword_max_n)
        self.compose_time = sp.dur
        return composed

    @property
    def composes_unseen(self) -> bool:
        """Whether a string the vocabulary lacks has a vector here, composed
        from its n-grams (a subword model), and with it neighbours."""
        return self._buckets is not None

    @property
    def subword_buckets(self) -> Optional[jax.Array]:
        """A subword model's bucket rows [K, D] as trained, else None."""
        if self._buckets is None:
            return None
        return self._buckets[:, : self._dim]

    def _unseen_vector(self, word: str) -> np.ndarray:
        """A string the vocabulary has never seen, on a subword model: the
        mean of its n-grams' bucket rows (zeros where it has none), fetched:
        one device operation a string."""
        from glint_word2vec_tpu.data.subword import ngram_buckets
        cfg = self.config
        ids = ngram_buckets(word, cfg.subword_min_n, cfg.subword_max_n,
                            cfg.subword_buckets)
        if not ids:
            return np.zeros(self.vector_size, np.float32)
        # the listed rows first, then their trained width: the other order
        # would copy every bucket row
        return np.asarray(
            self._buckets[jnp.asarray(ids, jnp.int32)][:, : self.vector_size]
            .astype(jnp.float32).mean(axis=0))

    @property
    def syn0(self) -> jax.Array:
        """Input embeddings, unpadded view [vocab_size, D] (a subword model's
        composed vectors)."""
        self._check_alive("syn0")
        return self._full0[: self.vocab.size]

    @property
    def syn1(self) -> Optional[jax.Array]:
        self._check_alive("syn1")
        if self._full1 is None:
            return None
        return self._full1[: self.vocab.size]

    # -- basic properties --------------------------------------------------------------

    @property
    def vector_size(self) -> int:
        return self._dim

    @property
    def num_words(self) -> int:
        return self.vocab.size

    def _check_alive(self, tables_of: Optional[str] = None) -> None:
        """Raises where the model was stopped; and, for ``tables_of`` (the
        name of an operation that scans, saves or exports syn0 or syn1),
        where it was built with ``resident="rows"`` and holds neither."""
        if self._stopped:
            raise RuntimeError("model has been stopped; its buffers were released")
        if tables_of is not None and self.resident == "rows":
            raise RuntimeError(
                f"{tables_of} needs a table this model does not hold: it was "
                "built with resident='rows' (the composed table at whole "
                "lanes, the bucket rows and the norms, for row reads alone); "
                "build it with resident='all'")

    # -- transform (C8 mllib:511-546; C12 ml:432-460) ----------------------------------

    def transform(self, word: str) -> np.ndarray:
        """Vector of a single word. Raises on OOV like the reference
        (mllib:516-518), except on a subword model, which composes one from
        the string's n-grams."""
        self._check_alive()
        idx = self.vocab.get(word)
        if idx < 0 and self.composes_unseen:
            return self._unseen_vector(word)
        if idx < 0:
            raise KeyError(f"{word} not in vocabulary")
        if self._full0 is None:     # resident="rows": the one table it holds
            return self._read_rows([idx])[0]
        return np.asarray(self.syn0[idx])

    def transform_words(self, words: Iterable[str], batch_size: int = 10_000
                        ) -> Iterator[np.ndarray]:
        """Batched word → vector stream (the reference's 10k-word batched iterator path,
        mllib:529-546, noted there as the efficient variant). Raises ``KeyError``
        naming the first word of a batch the vocabulary lacks."""
        self._check_alive()
        buf: List[str] = []

        def emit(buf: List[str]) -> Iterator[np.ndarray]:
            ids = self.vocab.lookup(buf)
            if (ids < 0).any():
                raise KeyError(f"{buf[int(np.argmax(ids < 0))]} not in vocabulary")
            yield from self._read_rows(ids)

        for w in words:
            buf.append(w)
            if len(buf) >= batch_size:
                yield from emit(buf)
                buf = []
        if buf:
            yield from emit(buf)

    def transform_sentences(
        self, sentences: Sequence[Sequence[str]], batch_size: int = 10_000
    ) -> np.ndarray:
        """Sentence → mean of in-vocab word vectors (the ML transform semantics,
        ml:428-460): ``float32[len(sentences), D]``, rows in the order sent. OOV
        words are dropped from sum and count alike (ml:451-452), repeats count,
        and a sentence with no in-vocab word maps to the zero vector. Processed
        in slides of ``batch_size`` sentences like the reference's 10k-row
        mapPartitions slides (ml:449-450), each ONE device program of fixed
        shapes (:func:`..ops.transform._segment_means`; the server-side
        ``pullAverage``, ml:453):

        - *encode* (host, :meth:`_encode_slide`): the slide's tokens to row ids
          in one ``Vocabulary.lookup_sentences`` of the slide as it lies, OOV
          tokens dropped there, and every sentence's count of live ids;
        - *enqueue*: the live ids padded to a row capacity derived from the
          slide (:func:`_grid_up`: a whole number of tiles, a sixteenth of the
          power of two under the live ids each, so slides of like size share a
          program and at most 1/16 of the rows handed over is padding), the
          rows gathered in place from the whole-lane form of syn0
          (:meth:`_row_table`), summed by sentence and divided on the device,
          trimmed to ``[S, D]`` there, the copy to the host begun. A slide
          with more live ids than ``_TRANSFORM_MAX_ROWS`` runs the same
          program in further passes over equal parts of them, the sums
          carried from pass to pass;
        - *fetch*: one ``np.asarray`` of the ``[S, D]`` block, written
          straight into the result's rows.

        A call of several slides encodes and enqueues slide n + 1 while slide
        n's program and fetch are outstanding (``_SLIDES_IN_FLIGHT``); several
        threads may call at once. Spans: ``transform.slide``, its three
        children and the encode's ``transform.encode.walk``
        (docs/observability.md §4).

        Over a table partitioned by rows (a model on a mesh with a model
        axis) the slide's program runs under ``shard_map`` over the axis that
        partitions them, as the upstream servers run ``pullAverage``: every
        shard gathers the ids it owns from its own block of the whole-lane
        form and sums them by sentence, one psum adds the ``[S, lanes]``
        partial sums, and the division follows it
        (:func:`..ops.transform._sharded_segment_sums`). The host halves are
        the same; ``transform.enqueue`` says ``shards`` and ``owned_max``.
        Along a data axis every replica does the whole slide.

        On a subword model a token the vocabulary lacks is dropped here too
        (upstream's rule); :meth:`sentence_vectors` is the operation that
        composes it from its n-grams."""
        return self._slides(sentences, batch_size, self._transform_begin)

    def sentence_vectors(
        self, sentences: Sequence[Sequence[str]], batch_size: int = 10_000
    ) -> np.ndarray:
        """fastText's sentence vector (``FastText::getSentenceVector``, the
        branch for unsupervised models; Python ``get_sentence_vector``, CLI
        ``print-sentence-vectors``): ``float32[len(sentences), D]``, rows in
        the order sent, each the mean of its tokens' UNIT vectors,

            v(s) = (1 / c) * sum over the tokens t of s with |h(t)| > 0 of h(t) / |h(t)|

        c their number (zeros where it is 0: an empty sentence). h(t) is the
        model's vector of the string t: a word's row of syn0 (a subword
        model's composed row), and on a subword model, for a token the
        vocabulary lacks, the mean of ALL its n-grams' bucket rows, however
        many (:meth:`transform`'s vector of it). A token of zero norm is left
        out of sum and count alike; on a model without subwords that is every
        token the vocabulary lacks (it has no rows), so nothing else is
        dropped as out of vocabulary. Every token counts each time it occurs;
        the caller splits the text and no end-of-sentence token is added.

        Slides of ``batch_size`` sentences on :meth:`transform_sentences`'
        machinery (its halves, capacity rule, in-flight bound and spans),
        each ONE device program (:func:`..ops.transform._sentence_means`), a
        two-level ragged reduction:

        - *encode* (host, :meth:`_encode_tokens`): one walk of the slide as
          it lies, the lookup with the interpreter lock released, and in the
          same lock-free stretch the missing tokens' n-grams hashed from the
          bytes the walk kept (``native/subword.cpp``; span
          ``transform.ngram_hash``) into ONE FLAT list of bucket ids and
          every token's count of them: no ``[U, L]`` block, no Python
          statement a token, a character or an n-gram;
        - *enqueue*: three capacities derived from the slide, the word rows'
          and the list rows' by :func:`_grid_up` and the unseen tokens' the
          power of two over them (coarse, so slides share programs: its
          block is a twentieth of the others). The word rows are gathered in
          place from the whole-lane table and scaled by their inverse norms,
          which the encode took on the host and the program is handed slot
          for slot beside the ids; the list rows are gathered in place from
          the bucket rows,
          summed by token (sorted segments), divided by |G|, normalised; both
          are summed by sentence and divided by the live count on the device,
          trimmed to ``[S, D]`` there. A slide with more word rows or list
          rows than ``_TRANSFORM_MAX_ROWS`` runs further passes of the same
          program, the lists cut between tokens, the sums and the composed
          tokens' counts carried;
        - *fetch*: as :meth:`transform_sentences`."""
        return self._slides(sentences, batch_size, self._sentvec_begin)

    def _slides(self, sentences: Sequence[Sequence[str]], batch_size: int,
                begin) -> np.ndarray:
        """``sentences`` in slides of ``batch_size`` through ``begin`` (one
        slide's first half: :meth:`_transform_begin`, :meth:`_sentvec_begin`)
        and :meth:`_transform_finish`, ``_SLIDES_IN_FLIGHT`` at a time."""
        self._check_alive()
        out = np.empty((len(sentences), self.vector_size), np.float32)
        pending: "collections.deque[_PendingSlide]" = collections.deque()
        try:
            for lo in range(0, len(sentences), batch_size):
                pending.append(begin(sentences[lo:lo + batch_size], lo, batch_size))
                if len(pending) >= _SLIDES_IN_FLIGHT:
                    self._transform_finish(pending.popleft(), out)
            while pending:
                self._transform_finish(pending.popleft(), out)
        finally:
            with self._lock:  # slides an exception left unfetched
                self._slides_inflight -= sum(p.result is not None for p in pending)
        return out

    def _encode_slide(self, slide: Sequence[Sequence[str]]
                      ) -> Tuple[np.ndarray, np.ndarray, int, bool]:
        """One slide's tokens as the program wants them: the ``int32`` row ids
        of its in-vocabulary tokens, sentence after sentence in the order
        sent, every sentence's count of them (``int32[len(slide)]``; 0 for an
        empty or all-OOV sentence), the tokens dropped as OOV, and whether the
        native table resolved them: ``Vocabulary.lookup_sentences``, which
        takes the slide as it lies, with no Python statement a token, under
        ``transform.encode.walk`` the part that holds the interpreter lock."""
        return self.vocab.lookup_sentences(
            slide, default_tracer().span("transform.encode.walk"))

    def _transform_begin(self, slide: Sequence[Sequence[str]], lo: int,
                         batch_size: int) -> "_PendingSlide":
        """The first half of one slide: encode, and every pass of its program
        enqueued with the result on its way back. A slide shorter than
        ``batch_size`` (a call's last) is handed over at :func:`_grid_up` of
        its sentences."""
        tracer = default_tracer()
        n = len(slide)
        span = tracer.open("transform.slide", sentences=n)
        pending = _PendingSlide(lo, n, span)
        with tracer.span("transform.encode") as encode:
            ids, counts, oov, by_objects = self._encode_slide(slide)
            encode.set(by_objects=int(by_objects))
        live = int(ids.shape[0])
        if span is not None:
            span.set(words=live, oov=oov, empty=int((counts == 0).sum()))
        if live:
            table = self._row_table()
            shards = _row_shards(table)
            segments = batch_size if n == batch_size else _grid_up(n, 8)
            passes = -(-live // _TRANSFORM_MAX_ROWS)
            cap = _grid_up(-(-live // passes), 128)
            with self._lock:
                inflight = self._slides_inflight
                self._slides_inflight += 1
            with tracer.span("transform.enqueue", rows=live, rows_cap=cap,
                             passes=passes, inflight=inflight,
                             **self._owned(shards, table.shape[0], ids, span)):
                seg = np.repeat(np.arange(n, dtype=np.int32), counts)
                counts = np.concatenate(
                    [counts, np.zeros(segments - n, np.int32)])
                sums = None
                for at in range(0, passes * cap, cap):
                    # past the live ids: a row no table has (read as zeros)
                    # in a sentence no slide has (dropped)
                    # (on a mesh the PADDED row count, which no shard owns)
                    part_ids = np.full(cap, table.shape[0], np.int32)
                    part_seg = np.full(cap, segments, np.int32)
                    part_ids[:live - at] = ids[at:at + cap]
                    part_seg[:live - at] = seg[at:at + cap]
                    sums = _segment_means(
                        table, part_ids, part_seg,
                        counts if at + cap >= live else None, sums,
                        segments, self.vector_size, shards)
                sums.copy_to_host_async()
            pending.result = sums
        if span is not None:
            span.detach()  # the next slide's spans are no children of this one
        return pending

    @staticmethod
    def _owned(shards, rows: int, ids: np.ndarray, span) -> Dict[str, int]:
        """What ``transform.enqueue`` says of a slide's program over a table
        of ``rows`` rows partitioned by rows (``shards``, its sharding;
        nothing on one device): the ``shards`` it ran over and, where the
        slide is recorded (``span``), ``owned_max``: the live ids the
        busiest shard owns. Its gather reads that many rows where an even
        spread would read ``rows / shards`` (rows in frequency order
        partitioned by range put ~94% of a Zipf slide's on shard 0)."""
        if shards is None:
            return {}
        n = shards.mesh.shape[shards.spec[0]]
        if span is None:
            return {"shards": n}
        return {"shards": n, "owned_max": int(
            np.bincount(ids // (rows // n), minlength=n).max())}

    def _encode_tokens(self, slide: Sequence[Sequence[str]]) -> "_SlideTokens":
        """One slide's tokens as :func:`..ops.transform._sentence_means` wants
        them (:class:`_SlideTokens`). A model without subwords takes
        :meth:`_encode_slide`'s lookup and leaves the missing tokens out (they
        have no rows: h = 0). A subword model takes
        ``Vocabulary.lookup_sentences_misses``, which hands the missing
        tokens on, and hashes their n-grams (``data/subword.ngram_rows``,
        span ``transform.ngram_hash``). Left out here, and counted as
        ``zero_norm``: a string with no n-gram at all (``""``), and a word
        whose row has zero norm (:meth:`_inverse_norms` keeps their ids; a
        trained table has none). A composed token whose rows sum to zero is
        left out by the program. The ids that stay take their scales here,
        from the host's copy of the inverse norms (span ``transform.scale``,
        ``rows`` the live ids): the program is handed them slot for slot
        beside the ids and gathers nothing but rows."""
        tracer = default_tracer()
        walk = tracer.span("transform.encode.walk")
        n = len(slide)
        if not self.composes_unseen:
            ids, counts, zero, by_objects = self.vocab.lookup_sentences(slide, walk)
            unseen = np.zeros(n, np.int32)
            list_rows = list_counts = np.zeros(0, np.int32)
        else:
            from glint_word2vec_tpu.data.subword import ngram_rows
            cfg = self.config
            ids, counts, unseen, missing, by_objects = (
                self.vocab.lookup_sentences_misses(slide, walk))
            with tracer.span("transform.ngram_hash") as sp:
                list_rows, list_counts, native = ngram_rows(
                    missing, cfg.subword_min_n, cfg.subword_max_n,
                    cfg.subword_buckets)
                sp.set(strings=int(list_counts.shape[0]),
                       list_rows=int(list_rows.shape[0]), native=int(native))
            bare = list_counts == 0
            zero = int(bare.sum())
            if zero:
                of = np.repeat(np.arange(n, dtype=np.int32), unseen)
                unseen = np.bincount(of[~bare], minlength=n).astype(np.int32)
                list_counts = list_counts[~bare]
        self._inverse_norms()
        if self._zero_rows.size:
            dead = np.isin(ids, self._zero_rows)
            if dead.any():
                of = np.repeat(np.arange(n, dtype=np.int32), counts)
                counts = np.bincount(of[~dead], minlength=n).astype(np.int32)
                ids, zero = ids[~dead], zero + int(dead.sum())
        with tracer.span("transform.scale", rows=int(ids.shape[0])):
            # the vocabulary's own rows, so no bound to check: "clip" is the
            # take that checks none and leaves the interpreter lock alone
            # (a[ids] of int32 ids: twice the time with four callers)
            scales = np.take(self._host_inv, ids, mode="clip")
        return _SlideTokens(ids, scales, counts, unseen, list_rows, list_counts,
                            zero, by_objects)

    def _sentvec_begin(self, slide: Sequence[Sequence[str]], lo: int,
                       batch_size: int) -> "_PendingSlide":
        """:meth:`_transform_begin` of a :meth:`sentence_vectors` slide."""
        tracer = default_tracer()
        n = len(slide)
        span = tracer.open("transform.slide", sentences=n)
        pending = _PendingSlide(lo, n, span)
        with tracer.span("transform.encode") as encode:
            tokens = self._encode_tokens(slide)
            encode.set(by_objects=int(tokens.by_objects))
        live, listed = int(tokens.ids.shape[0]), int(tokens.list_rows.shape[0])
        composed = int(tokens.list_counts.shape[0])
        if span is not None:
            span.set(words=live, oov=0, unseen=composed, zero_norm=tokens.zero_norm,
                     empty=int(((tokens.counts + tokens.unseen) == 0).sum()))
        if live or composed:
            table = self._row_table()
            segments = batch_size if n == batch_size else _grid_up(n, 8)
            passes = max(-(-live // _TRANSFORM_MAX_ROWS),
                         -(-listed // _TRANSFORM_MAX_ROWS), 1)
            cap = _grid_up(-(-live // passes), 128)
            # the lists in ``passes`` parts cut between tokens, of about as
            # many rows each: tokens [cut[i], cut[i + 1]) and their rows
            row_end = np.cumsum(tokens.list_counts, dtype=np.int64)
            cut = np.searchsorted(
                row_end, -(-listed // passes) * np.arange(passes + 1), side="right")
            cut[-1] = composed
            row_cut = np.concatenate([[0], row_end])[cut]
            list_cap = _grid_up(int(np.diff(row_cut).max()), 128)
            token_cap = max(128, 1 << (int(np.diff(cut).max()) - 1).bit_length())
            with self._lock:
                inflight = self._slides_inflight
                self._slides_inflight += 1
            with tracer.span("transform.enqueue", rows=live, rows_cap=cap,
                             passes=passes, inflight=inflight, list_rows=listed,
                             list_cap=list_cap if composed else 0,
                             unseen=composed,
                             unseen_cap=token_cap if composed else 0):
                seg = np.repeat(np.arange(n, dtype=np.int32), tokens.counts)
                counts = np.concatenate(
                    [tokens.counts, np.zeros(segments - n, np.int32)])
                if composed:
                    token_seg = np.repeat(np.arange(n, dtype=np.int32), tokens.unseen)
                    token_of_row = np.repeat(
                        np.arange(composed, dtype=np.int32), tokens.list_counts)

                def part(values, lo, hi, size, fill, dtype=np.int32):
                    out = np.full(size, fill, dtype)
                    out[:max(hi - lo, 0)] = values[lo:hi]
                    return out

                carried = None
                for i in range(passes):
                    # past the live ids: a row no table has (read as zeros)
                    # in a sentence no slide has (dropped), whatever its scale
                    lo, hi = i * cap, min((i + 1) * cap, live)
                    lists = None
                    if composed:
                        # past a part's list rows and tokens: a bucket row no
                        # table has, in a token the part does not hold, of a
                        # sentence no slide has
                        (t0, t1), (r0, r1) = cut[i:i + 2], row_cut[i:i + 2]
                        lists = (
                            self._buckets,
                            part(tokens.list_rows, r0, r1, list_cap,
                                 self._buckets.shape[0]),
                            part(token_of_row - t0, r0, r1, list_cap, token_cap),
                            part(token_seg, t0, t1, token_cap, segments))
                    carried = _sentence_means(
                        table,
                        part(tokens.scales, lo, hi, cap, 0, tokens.scales.dtype),
                        part(tokens.ids, lo, hi, cap, table.shape[0]),
                        part(seg, lo, hi, cap, segments), lists,
                        counts if i == passes - 1 else None, carried,
                        segments, self.vector_size)
                carried.copy_to_host_async()
            pending.result = carried
        if span is not None:
            span.detach()  # the next slide's spans are no children of this one
        return pending

    def _transform_finish(self, pending: "_PendingSlide",
                          out: np.ndarray) -> None:
        """The second half of one slide: its means fetched into its rows of
        ``out`` (zeros where it held no in-vocabulary token at all)."""
        rows = out[pending.lo:pending.lo + pending.sentences]
        if pending.result is None:
            rows[:] = 0.0
        else:
            parent = None if pending.span is None else pending.span.id
            with default_tracer().span("transform.fetch", parent=parent):
                rows[:] = np.asarray(pending.result)[:pending.sentences]
            pending.result = None
            with self._lock:
                self._slides_inflight -= 1
        if pending.span is not None:
            pending.span.close()

    # -- pull / norms / multiply (G5, mllib:486,514,598) -------------------------------

    def pull(self, indices: Sequence[int]) -> np.ndarray:
        """Row gather — the PS ``pull`` (mllib:514,539)."""
        self._check_alive()
        return self._read_rows(indices)

    def _row_table(self) -> jax.Array:
        """The table ``transform_sentences``, ``transform_words`` and ``pull``
        gather their rows from: syn0 with D widened to whole lanes of 128
        (ops/subword.lane_padded, as the bucket rows are kept), which the
        TPU's gather reads in place, where a gather from the [V, 300] table
        first copies ALL of it row-major (3.6 GB of temporaries and ~13 ms a
        call at 3M rows, before one row is read: PERF.md §6). Made at the
        first such read, under the model's lock (a model that only answers
        ``find_synonyms*`` never holds it: the scan reads the table as it
        lies), kept until :meth:`stop`. Over a table partitioned by rows it
        is made shard by shard under the table's own sharding (the jitted pad
        hands it out as the table lies: every chip widens the rows it holds, the
        padding rows of a vocabulary that does not divide with them; at the
        most a quarter of the 300-wide table and a quarter of the 384-wide
        form on a chip of four, no ``[V, .]`` array on one chip or the
        host), and the readers' programs run under ``shard_map`` over it. A
        table that lies whole on each of several devices is gathered as it
        lies, the ``[:V]`` view."""
        self._check_alive()
        if self._lanes is not None:     # made, or all a resident="rows" model holds
            return self._lanes
        shards = _row_shards(self._full0)
        if shards is None and len(self._full0.sharding.device_set) != 1:
            return self.syn0
        from glint_word2vec_tpu.ops.subword import lane_padded
        said = {} if shards is None else {
            "shards": shards.mesh.shape[shards.spec[0]]}
        return self._once("_lanes", lane_padded, "model.row_table", **said)

    def _once(self, attr: str, make, span: Optional[str] = None,
              **said) -> jax.Array:
        """The resident form ``attr`` of the model's table: what is there, or
        ``make(table)`` of the table the model holds (syn0; the whole-lane
        form where ``resident="rows"`` kept nothing else, whose rows have the
        same norms), kept until :meth:`stop`. Made once a model whatever the
        callers' threads: under the model's lock (not re-entrant: ``make``
        asks for no other form), looked for again there, waited for before
        it is stored, inside the pinned span ``span`` (obs/spans.py; its
        ``rows`` the table's, and what else the caller ``said``) where one
        is named."""
        made = getattr(self, attr)
        if made is not None:
            return made
        with self._lock:
            made = getattr(self, attr)
            if made is None:
                table = self._lanes if self._full0 is None else self._full0
                with (default_tracer().span(span, pinned=True,
                                            rows=int(table.shape[0]), **said)
                      if span else contextlib.nullcontext()):
                    made = make(table)
                    made.block_until_ready()
                setattr(self, attr, made)
        return made

    def _read_rows(self, ids: Sequence[int]) -> np.ndarray:
        """Rows ``ids`` of syn0, fetched: one gather from :meth:`_row_table`;
        over a table partitioned by rows, every shard's gather of the rows it
        owns and one psum (:func:`..ops.transform._sharded_rows`; a negative
        id counts from the vocabulary's end there too)."""
        table, ids = self._row_table(), np.asarray(ids, np.int32)
        shards = _row_shards(table)
        if shards is None:
            return np.asarray(table[jnp.asarray(ids)][:, : self.vector_size])
        return np.asarray(_sharded_rows(
            table, np.where(ids < 0, ids + self.vocab.size, ids),
            self.vector_size, shards))

    @property
    def norms(self) -> jax.Array:
        """Per-row Euclidean norms, computed once and cached (mllib:486,600-609)."""
        self._check_alive()
        return self._once("_norms", lambda table: jnp.linalg.norm(table, axis=1),
                          "model.norms")[: self.vocab.size]

    def _inverse_norms(self) -> jax.Array:
        """The scale that makes every row of the model's table a unit
        vector: 1 / :attr:`norms`, 0 for a row of zero norm; made once
        (:meth:`_once`). On the device for the analogy scan, which reads it
        inside its program; fetched once for ``sentence_vectors``, whose
        encode takes a slide's scales from the host's copy (``_host_inv``,
        the words' alone) and leaves out of the count the tokens of the rows
        of zero norm (``_zero_rows``, their ids)."""
        if self._inv_norms is None:
            self.norms  # the norms first: _once's lock is not re-entrant

        def make(_table) -> jax.Array:
            norms = self._norms
            inv = jnp.where(norms > 0, 1.0 / jnp.where(norms > 0, norms, 1.0), 0.0)
            self._host_inv = np.asarray(inv[: self.vocab.size])
            self._zero_rows = np.flatnonzero(self._host_inv == 0).astype(np.int32)
            return inv

        return self._once("_inv_norms", make)

    def _scan_table(self) -> jax.Array:
        """The table the analogy scan's matmul reads. On a TPU a float32
        syn0's bfloat16 rounding, made once at the first :meth:`analogies`
        call under the model's lock and kept until :meth:`stop` (1.8 GB
        beside syn0's 3.6 at 3M x 300): at the default precision the MXU
        takes float32 operands rounded to bfloat16 (the compiler converts the
        table itself, WHOLE and once a program where the blocks are scored
        under a loop: 1.8 GB of temporaries a program in flight and a second
        pass over the table; PERF.md §6, PR 55), so the scores are the ones
        the float32 table gives, accumulated and returned in float32, and a
        program reads half the bytes. Elsewhere, and for a table of another
        dtype, syn0 as it lies (a CPU's float32 matmul rounds nothing)."""
        if jax.default_backend() != "tpu" or self._full0.dtype != jnp.float32:
            return self._full0
        return self._once("_scan0", lambda table: table.astype(jnp.bfloat16),
                          "model.scan_table")

    def multiply(self, vector: np.ndarray) -> np.ndarray:
        """Full matrix–vector product syn0 @ v (the PS ``multiply`` powering cosine
        search, mllib:598). One matvec on device; over a mesh one sharded
        matvec, every shard over the rows it holds: the table as it lies, its
        padding rows' zeros dropped from the fetched vector (the ``syn0`` view
        of a vocabulary that does not divide over the mesh is a slice along
        the partitioned rows, which all-gathers the table)."""
        self._check_alive("multiply")
        v = jnp.asarray(vector, jnp.float32)
        return np.asarray(self._full0 @ v)[: self.vocab.size]

    # -- ANN index attach (serving tier, serve/ann.py) ---------------------------------

    def attach_ann(self, index) -> None:
        """Attach a built :class:`~glint_word2vec_tpu.serve.ann.IvfIndex`
        so :meth:`find_synonyms_batch` can serve the approximate arm
        (``ann=True``). The exact path stays the ground-truth oracle; the
        index is a serving-time accessory, never persisted with the model
        (it rebuilds from the matrix at load/publish time).

        Refuses an index whose row count differs from the vocabulary — with
        continual publishes the vocabulary GROWS across reloads, and a stale
        index carried over from the previous generation would silently
        mis-rank (new rows unreachable, row-id → word lookups shifted only
        by luck of the identity-prefix contract). A vocab-size change forces
        a full rebuild by construction."""
        self._check_alive()
        if index is not None:
            rows = getattr(index, "num_rows", None)
            if rows is not None and int(rows) != self.vocab.size:
                raise ValueError(
                    f"ANN index covers {rows} rows but the vocabulary has "
                    f"{self.vocab.size} words — a stale index from a "
                    f"previous publish (the vocabulary grew?); rebuild with "
                    f"serve.ann.build_ivf(np.asarray(model.syn0))")
        self._ann = index

    @property
    def ann(self):
        """The attached ANN index, or None."""
        return self._ann

    # -- synonym / analogy search (C8 mllib:554-630, C12 ml:375-420) -------------------

    def find_synonyms(
        self, query: Union[str, np.ndarray], num: int
    ) -> List[Tuple[str, float]]:
        """Top-``num`` cosine-similar words. String query excludes the query word itself
        (mllib:621-629); vector queries (for analogies) do not."""
        return self.find_synonyms_batch([query], num)[0]

    find_synonyms_array = find_synonyms  # ml:405-420 naming alias

    def find_synonyms_batch(
        self,
        queries: Sequence[Union[str, np.ndarray]],
        num: int,
        chunk: int = 128,
        ann: bool = False,
        nprobe: Optional[int] = None,
        begun: Optional["_PendingSynonyms"] = None,
    ) -> List[List[Tuple[str, float]]]:
        """Batched :meth:`find_synonyms`: ONE device program per ``chunk``
        queries, word ids in and the top-k out. The host resolves the words
        to row ids (dictionary lookups) and hands over one ``int32[Q]``
        array; the program gathers the rows from the table it already
        holds, normalises them, and runs the [chunk, V] cosine matmul and
        the top-k (:func:`..ops.scan._gather_topk_batch`). The scores are
        ranked in two exact stages: the maxima of runs of ~sqrt(V / k)
        columns, then the k winning runs' members
        (:func:`..ops.scan._two_stage_topk`; what comes back is
        ``lax.top_k``'s over the same scores, ties included); one
        ``lax.top_k`` over all V where the vocabulary is small. Over a table
        partitioned by rows on a mesh the one program runs under
        ``shard_map`` (:func:`..ops.scan._sharded_scan`): every shard scans
        and ranks its own rows so, and the shards' k candidates each are
        merged; the replies are the one-device program's. On a TPU the
        chunk's queries are handed over in whole tiles of 8 rows
        (:func:`..ops.scan._topk_dispatch`), so one program serves 8 batch
        sizes. No per-query device operation: a launch costs more than this
        scan's share of a query.
        A chunk that holds a vector query (``np.ndarray``; analogies)
        additionally sends one float32 ``[Q, D]`` block, and the program
        takes row ``i`` from the gather where ``ids[i] >= 0`` and from the
        block otherwise — chosen from what the chunk holds, so the second
        program per batch size is compiled only where vectors are sent.
        Word queries exclude themselves (mllib:621-629); vector queries do not.
        An unknown word raises ``KeyError`` before anything is dispatched,
        except on a subword model, which answers a string its vocabulary
        lacks as fastText's ``nn`` does: by the mean of its n-grams' bucket
        rows, nothing excluded (it is no row of the table). The host hashes
        the chunk's unseen strings into one ``int32[Q, L]`` block of bucket
        ids (:meth:`_unseen_lists`) and the same one program averages the
        listed rows where ``ids[i]`` says so: still no per-query device
        operation, and a third (and fourth) program per batch size only
        where such strings are sent. A string with more n-grams than the
        block's capacity L is composed on its own and sent as a vector
        (``query_counts["overflow"]`` counts them).
        ``chunk`` bounds device memory at chunk·V·4 bytes of scores a part,
        two parts at a time (the score block is still written whole,
        whatever ranks it): the call is :meth:`find_synonyms_finish` of
        :meth:`find_synonyms_begin`, which enqueues a part's scan while the
        one before it is fetched. A caller that ran the first half itself
        (the serve dispatcher, which begins the next batch meanwhile) hands
        its result in as ``begun``, and this call is the second half alone:
        every reply, whoever began it, is handed out here.

        ``ann=True`` routes the batch through the attached IVF index
        (:meth:`attach_ann`) instead of the exact full-vocab scan — the
        serving tier's fast arm (docs/serving.md): approximate top-k over
        the ``nprobe`` nearest coarse cells, same result shape and the same
        self-exclusion semantics; scores remain true cosines (candidates
        are ranked exactly, only the candidate SET is approximate)."""
        return self.find_synonyms_finish(
            begun if begun is not None
            else self.find_synonyms_begin(queries, num, chunk, ann, nprobe))

    def find_synonyms_begin(
        self,
        queries: Sequence[Union[str, np.ndarray]],
        num: int,
        chunk: int = 128,
        ann: bool = False,
        nprobe: Optional[int] = None,
    ) -> "_PendingSynonyms":
        """The first half of :meth:`find_synonyms_batch`: everything up to
        and including the scan's enqueue (words to row ids, the unseen
        strings' lists, the vector block, ``_topk_dispatch``) and the start
        of the result's copy back to the host. Returns what
        :meth:`find_synonyms_finish` turns into the replies, on this thread
        or another: a caller with more batches than one (the serve batcher)
        begins the next while this one's scan runs. A call of more than one
        ``chunk`` has at most two parts enqueued at a time; ``finish``
        enqueues the rest as it fetches. The ANN arm leaves nothing pending
        on the device: all of its work is done here and ``finish`` hands it
        back."""
        self._check_alive("find_synonyms")
        tracer = default_tracer()
        # the caller's span: parent of the spans ``finish`` records, which may
        # run on a thread whose stack does not hold it
        pending = _PendingSynonyms(num, tracer.current())
        if ann:
            if self._ann is None:
                raise RuntimeError(
                    "ann=True but no index attached — build one with "
                    "serve.ann.build_ivf(np.asarray(model.syn0)) and "
                    "model.attach_ann(index)")
            pending.replies = self._find_synonyms_batch_ann(
                queries, num, nprobe)
            return pending
        if self._norms is None:
            self.norms  # materialize the cached full-row norms
        k = pending.k = min(num + 1, self.num_words)
        # spans of the serve table (obs/spans.py, docs/observability.md §4):
        # the host side of the scan, region by region
        with tracer.span("serve.row_fetch") as sp:
            words = pending.words
            ids = np.full(len(queries), _VECTOR, np.int32)
            block: Optional[np.ndarray] = None
            unseen: List[int] = []

            def vector_row(i: int, row) -> None:
                nonlocal block
                if block is None:
                    block = np.zeros((len(queries), self.vector_size), np.float32)
                ids[i] = _VECTOR
                block[i] = row

            for i, q in enumerate(queries):
                if isinstance(q, str):
                    idx = self.vocab.get(q)
                    if idx < 0 and not self.composes_unseen:
                        raise KeyError(f"{q} not in vocabulary")
                    words.append(q)
                    if idx < 0:
                        unseen.append(i)
                    else:
                        ids[i] = idx
                else:
                    words.append(None)
                    vector_row(i, q)
            lists, overflow = self._unseen_lists(queries, unseen)
            if lists is not None:
                ids[unseen] = _LISTED
            for i in overflow:
                vector_row(i, self._unseen_vector(queries[i]))
            counts = {}
            if self.composes_unseen:
                counts = {"unseen": len(unseen) - len(overflow),
                          "list_rows": (0 if lists is None
                                        else int((lists != _NO_ROW).sum())),
                          "overflow": len(overflow)}
                for name, n in counts.items():
                    self.query_counts[name] += n
            # what each chunk's program is handed: its ids, the list block
            # only where the chunk holds an unseen string, the vector block
            # only where it holds a vector query
            parts = pending.parts
            for lo in range(0, len(queries), chunk):
                part_ids = ids[lo:lo + chunk]
                parts.append((
                    lo, part_ids,
                    block[lo:lo + chunk] if (part_ids == _VECTOR).any() else None,
                    lists[lo:lo + chunk] if (part_ids == _LISTED).any() else None))
            # device operations issued to build the query blocks: one put
            # per host array above, whatever the number of queries
            sp.set(ops=sum(1 + (b is not None) + (l is not None)
                           for _, _, b, l in parts), **counts)
        pending.scan = _scan_counts(self._full0, k)
        for _ in parts[:_PARTS_IN_FLIGHT]:
            self._enqueue_part(pending)
        return pending

    def _enqueue_part(self, pending: "_PendingSynonyms") -> None:
        """Enqueue the scan of ``pending``'s next part and start its result
        on the way back to the host."""
        _, part_ids, part_block, part_lists = pending.parts[
            len(pending.results)]
        with default_tracer().span(
                "serve.scan_enqueue", parent=pending.parent,
                queries=len(part_ids), **pending.scan):
            result = _topk_dispatch(
                self._full0, self._norms, part_ids, part_block,
                pending.k, self.num_words,
                *(() if part_lists is None
                  else (self._buckets, part_lists)))
            for a in result:
                a.copy_to_host_async()
        pending.results.append(result)

    def find_synonyms_finish(
        self, pending: "_PendingSynonyms") -> List[List[Tuple[str, float]]]:
        """The second half of :meth:`find_synonyms_batch`: fetch the scans
        :meth:`find_synonyms_begin` enqueued, in order, and build the
        replies. Once for each ``pending``."""
        if pending.replies is not None:
            return pending.replies
        self._check_alive()
        tracer = default_tracer()
        out: List[List[Tuple[str, float]]] = []
        for (lo, part_ids, _, _), (scores, idxs) in self._fetched_parts(
                pending, self._enqueue_part, "serve.result_fetch"):
            with tracer.span("serve.reply_build", parent=pending.parent):
                # rows past the chunk's queries are _topk_dispatch's padding
                out.extend(self._replies(
                    pending.words[lo:lo + len(part_ids)],
                    scores[:len(part_ids)], idxs[:len(part_ids)],
                    pending.num))
        return out

    def _fetched_parts(self, pending: "_PendingSynonyms", enqueue,
                       fetch_span: str):
        """Every part of ``pending`` with its results fetched (span
        ``fetch_span``), in order; as one is fetched ``enqueue(pending)``
        sends the next that is not enqueued yet, so a call of many parts
        keeps :data:`_PARTS_IN_FLIGHT` on the device until its last."""
        tracer = default_tracer()
        for at, part in enumerate(pending.parts):
            result = pending.results[at]
            pending.results[at] = None
            with tracer.span(fetch_span, parent=pending.parent):
                fetched = tuple(np.asarray(a) for a in result)
            if len(pending.results) < len(pending.parts):
                enqueue(pending)
            yield part, fetched

    def _unseen_lists(self, queries, unseen: List[int]):
        """The batch's strings the vocabulary lacks (positions ``unseen``;
        a subword model's) made ready for the scan's program: their n-grams
        hashed on the host (span ``serve.ngram_hash``) into one ``int32[Q,
        L]`` block of bucket ids, empty rows elsewhere. L is a capacity
        derived once (data/subword.list_capacity). Returns the block (None
        where nothing is listed) and the positions of the strings it does NOT
        hold: those whose list is longer than L, and every unseen string of a
        model whose table lies on several devices (its bucket rows lie on
        one). They are the one overflow form: composed by
        :meth:`_unseen_vector`, one device operation each, and sent in the
        vector block."""
        if not unseen or len(self._full0.sharding.device_set) != 1:
            return None, unseen
        from glint_word2vec_tpu.data.subword import ngram_lists
        cfg = self.config
        with default_tracer().span("serve.ngram_hash", strings=len(unseen)):
            rows, over = ngram_lists(
                [queries[i] for i in unseen], cfg.subword_min_n,
                cfg.subword_max_n, cfg.subword_buckets, self._list_cap)
        lists = np.full((len(queries), self._list_cap), _NO_ROW, np.int32)
        lists[unseen] = rows
        return lists, [unseen[j] for j in over]

    def _replies(self, words: List[Optional[str]], scores, idxs,
                 num: int) -> List[List[Tuple[str, float]]]:
        """Rows of (score, row id) as ``(word, score)`` lists, the query
        word itself left out; a negative id ends a row (the ANN arm found
        fewer candidates than k in the probed cells)."""
        out: List[List[Tuple[str, float]]] = []
        for word, srow, irow in zip(words, scores, idxs):
            res: List[Tuple[str, float]] = []
            for i, s in zip(irow, srow):
                if i < 0:
                    break
                w = self.vocab.words[int(i)]
                if w == word:
                    continue
                res.append((w, float(s)))
            out.append(res[:num])
        return out

    def _find_synonyms_batch_ann(
        self, queries: Sequence[Union[str, np.ndarray]], num: int,
        nprobe: Optional[int] = None) -> List[List[Tuple[str, float]]]:
        """The ANN arm of :meth:`find_synonyms_batch`: host-side probe over
        the attached index. Word queries read their vector from the index's
        own normalized copy (no device gather); vector queries are
        normalized by the index (cosine is scale-invariant)."""
        index = self._ann
        tracer = default_tracer()
        with tracer.span("serve.row_fetch", ops=0):
            words: List[Optional[str]] = []
            rows: List[np.ndarray] = []
            for q in queries:
                if isinstance(q, str):
                    idx = self.vocab.get(q)
                    if idx < 0 and not self.composes_unseen:
                        raise KeyError(f"{q} not in vocabulary")
                    words.append(q)
                    rows.append(index.vector(idx) if idx >= 0
                                else self._unseen_vector(q))
                else:
                    words.append(None)
                    rows.append(np.asarray(q, np.float32))
            block = np.stack(rows)
        k = min(num + 1, self.num_words)
        with tracer.span("serve.ann_search", queries=len(rows)):
            scores, idxs = index.search(block, k, nprobe)
        with tracer.span("serve.reply_build"):
            return self._replies(words, scores, idxs, num)

    def analogy(self, a: str, b: str, c: str, num: int = 10) -> List[Tuple[str, float]]:
        """b − a + c vector arithmetic, excluding the three query words — the analogy
        pattern from the reference's integration gates (it spec:327-352)."""
        va, vb, vc = self.transform(a), self.transform(b), self.transform(c)
        res = self.find_synonyms(vb - va + vc, num + 3)
        return [(w, s) for w, s in res if w not in (a, b, c)][:num]

    def analogies(
        self,
        questions: Union[Sequence[Sequence[str]], np.ndarray],
        num: int = 1,
        restrict_vocab: Optional[int] = None,
    ) -> List[Optional[List[Tuple[str, float]]]]:
        """Batched 3CosAdd, word2vec's ``compute-accuracy.c`` and gensim's
        ``most_similar(positive=[b, c], negative=[a], topn=num)``
        (https://code.google.com/archive/p/word2vec/; Mikolov et al. 2013,
        arXiv:1301.3781 §4.1) as ONE operation over every question of the
        call. With û_w row w of syn0 over its norm (0 for a row of zero
        norm), a question (a, b, c) asks for the ``num`` rows w, a, b and c
        excluded, of the largest cos(q, û_w), q = û_b − û_a + û_c, over the
        first ``restrict_vocab`` rows of the vocabulary (the tool's
        ``threshold``; all of them where None). ``questions``: (a, b, c)
        strings, or an ``int32[N, 3]`` of row ids. Returns, in the order
        asked, the ``(word, cosine)`` pairs best first, and ``None`` for a
        question with a word outside those rows (skipped, never an error).

        Exact: every candidate row is scored, in float32 at the scan's own
        matmul precision (:meth:`find_synonyms_batch`'s), and what comes back
        is ``lax.top_k``'s over the masked scores, ties toward the lower row.
        Departures from the C tool: it upper-cases the vocabulary and the
        questions (the caller's business here); it answers nothing where no
        score is positive (its ``bestd`` starts at 0), where this returns the
        best row whatever its sign. Its strict ``>`` over an ascending loop
        breaks ties toward the lower row, as here.

        Per call: one :meth:`Vocabulary.lookup` of all the words (span
        ``eval.encode``); then programs of at most
        :data:`_ANALOGY_MAX_QUESTIONS` questions each, at a capacity on
        :func:`_grid_up`'s grid (:func:`..ops.scan._analogy_topk`: the
        question rows read in place, each DISTINCT word once, the table scored
        in blocks of rows so that no ``[Q, V]`` block is ever resident, a, b
        and c masked by row id before the selection), at most
        :data:`_PARTS_IN_FLIGHT` enqueued at a time (``eval.enqueue``) and
        the next sent as one is fetched (``eval.fetch``): the machinery of
        :meth:`find_synonyms_begin` / :meth:`find_synonyms_finish`. A table
        partitioned by rows over a mesh raises ``NotImplementedError``
        (``find_synonyms_batch`` of host-built vectors answers there).
        :meth:`analogy` is the one-question form on RAW rows (upstream's
        integration spec) and is left as it was."""
        ids, live, scores, rows = self._analogy_scan(questions, 3, num, restrict_vocab)
        out: List[Optional[List[Tuple[str, float]]]] = [None] * len(ids)
        words = self.vocab.words
        for at, srow, irow in zip(np.flatnonzero(live).tolist(), scores, rows):
            # -inf: an excluded row, ranked only where fewer than ``num``
            # candidates were left
            out[at] = [(words[int(i)], float(sc)) for sc, i in zip(srow, irow)
                       if sc != -np.inf]
        return out

    def analogy_accuracy(
        self,
        questions: Union[Sequence[Sequence[str]], np.ndarray],
        restrict_vocab: Optional[int] = None,
    ) -> Dict[str, Union[int, float]]:
        """The accuracy test of ``compute-accuracy.c`` / gensim's
        ``evaluate_word_analogies`` over (a, b, c, d) questions (strings, or
        an ``int32[N, 4]`` of row ids): :meth:`analogies` at ``num`` = 1; a
        question is correct where the answer is d. A question any of whose
        four words lies outside the first ``restrict_vocab`` rows is skipped:
        counted as ``seen``, never scored. Returns ``seen``, ``scored``,
        ``skipped``, ``correct`` and ``accuracy`` = correct / scored (0.0
        where nothing was scored). Sections are the caller's: one call a
        section, as both public tools report."""
        ids, live, _, rows = self._analogy_scan(questions, 4, 1, restrict_vocab)
        scored = len(rows)
        correct = int((rows[:, 0] == ids[live, 3]).sum())
        return {"seen": len(ids), "scored": scored, "skipped": len(ids) - scored,
                "correct": correct,
                "accuracy": correct / scored if scored else 0.0}

    def _analogy_scan(self, questions, width: int, num: int,
                      restrict_vocab: Optional[int]):
        """Both analogy operations up to the fetched answers: the questions'
        row ids (``int32[N, width]``, -1 a word the vocabulary lacks), which
        of them were scored, and the live ones' ``[L, k]`` cosines and rows."""
        self._check_alive("analogies")
        if _row_shards(self._full0) is not None:
            raise NotImplementedError(
                "analogies / analogy_accuracy scan a table that lies on one "
                "device; this one is partitioned by rows over a mesh")
        if num < 1:
            raise ValueError(f"num must be at least 1, not {num}")
        tracer = default_tracer()
        candidates = self.num_words if restrict_vocab is None else max(
            0, min(int(restrict_vocab), self.num_words))
        with tracer.span("eval.call", num=num, candidates=candidates) as call:
            with tracer.span("eval.encode") as sp:
                if isinstance(questions, np.ndarray):
                    ids = np.ascontiguousarray(questions, np.int32)
                    if ids.ndim != 2 or ids.shape[1] != width:
                        raise ValueError(f"expected int32[N, {width}] row ids, "
                                         f"got {ids.shape}")
                else:
                    if any(len(q) != width for q in questions):
                        raise ValueError(f"every question holds {width} words")
                    ids = self.vocab.lookup(list(
                        itertools.chain.from_iterable(questions))).reshape(-1, width)
                live = ((ids >= 0) & (ids < candidates)).all(axis=1)
                # every span of the call is taken on this thread, inside
                # ``eval.call``: the stack names their parent
                pending = _PendingSynonyms(num, None)
                pending.k = max(1, min(num, candidates))
                asked = ids[live, :3]
                distinct = 0
                for lo in range(0, len(asked), _ANALOGY_MAX_QUESTIONS):
                    part = asked[lo:lo + _ANALOGY_MAX_QUESTIONS]
                    cap = min(_grid_up(len(part), _ANALOGY_CAP_FLOOR),
                              _ANALOGY_MAX_QUESTIONS)
                    # each distinct word's row is read once; a question names
                    # its three by their place among them
                    words, at = np.unique(part, return_inverse=True)
                    distinct += len(words)
                    held = np.zeros(3 * cap, np.int32)
                    held[:len(words)] = words
                    pos = np.zeros((cap, 3), np.int32)
                    pos[:len(part)] = at.reshape(-1, 3)
                    pending.parts.append((lo, len(part), cap, held,
                                          np.int32(len(words)), pos))
                pending.scan = dict(candidates=candidates)
                sp.set(words=ids.size, distinct=distinct)
            call.set(questions=len(ids), scored=len(asked),
                     skipped=len(ids) - len(asked))
            scores = np.empty((len(asked), pending.k), np.float32)
            rows = np.empty((len(asked), pending.k), np.int32)
            if pending.parts:
                self._inverse_norms()
                for _ in pending.parts[:_PARTS_IN_FLIGHT]:
                    self._enqueue_analogy_part(pending)
            for (lo, n, *_), (part_scores, part_rows) in self._fetched_parts(
                    pending, self._enqueue_analogy_part, "eval.fetch"):
                # rows past the part's questions are the capacity's padding
                scores[lo:lo + n] = part_scores[:n]
                rows[lo:lo + n] = part_rows[:n]
        return ids, live, scores, rows

    def _enqueue_analogy_part(self, pending: "_PendingSynonyms") -> None:
        """Enqueue the program of ``pending``'s next part and start its
        answers on the way back to the host."""
        _, n, cap, held, distinct, pos = pending.parts[len(pending.results)]
        with default_tracer().span(
                "eval.enqueue", parent=pending.parent, questions=n, cap=cap,
                programs=len(pending.parts),
                inflight=sum(r is not None for r in pending.results)):
            result = _analogy_topk(
                self._full0, self._scan_table(), self._inv_norms, held,
                distinct, pos, pending.k, pending.scan["candidates"],
                _ANALOGY_BLOCK_ROWS)
            for a in result:
                a.copy_to_host_async()
        pending.results.append(result)

    # -- exports (C8 mllib:638-662) ----------------------------------------------------

    def get_vectors(self) -> Dict[str, np.ndarray]:
        """word → vector for the whole vocabulary (mllib:638-649; mind the reference's
        caveat that this pulls everything to the client, mllib:635-637)."""
        self._check_alive("get_vectors")
        mat = np.asarray(self.syn0)
        return {w: mat[i] for i, w in enumerate(self.vocab.words)}

    def iter_vectors(self, batch_size: int = 10_000
                     ) -> Iterator[Tuple[str, np.ndarray]]:
        """Streaming variant of get_vectors — the analog of the ML layer's distributed
        per-partition pulls (ml:342-364) for vocabularies too large for one dict."""
        self._check_alive("iter_vectors")
        for start in range(0, self.num_words, batch_size):
            stop = min(start + batch_size, self.num_words)
            block = np.asarray(self.syn0[start:stop])
            for i in range(stop - start):
                yield self.vocab.words[start + i], block[i]

    def to_local(self) -> Tuple[List[str], np.ndarray]:
        """Dense host-side export (words, matrix) — the ``toLocal`` analog
        (mllib:651-662) without the Spark model wrapper. For the ecosystem
        hand-off the reference's Spark ``Word2VecModel`` provided (usable by
        downstream tooling), see :meth:`export_word2vec`."""
        self._check_alive("to_local")
        return list(self.vocab.words), np.asarray(self.syn0)

    def export_word2vec(self, path: str, binary: bool = False,
                        batch_size: int = 65536,
                        io_workers: Optional[int] = None) -> None:
        """Write the classic word2vec vectors file — the ecosystem interop the
        reference's ``toLocal`` delivers by producing a stock Spark model
        (mllib:651-662): gensim ``KeyedVectors.load_word2vec_format``, fastText
        tooling, and the original word2vec.c distance tools all read this.

        Format (word2vec.c's writer): header line ``"<vocab> <dim>\\n"``; then per
        word, ``word`` + ``' '`` + (text: space-joined decimals + ``'\\n'``;
        binary: dim little-endian float32s followed by ``'\\n'``). Streams in row
        blocks — no full-matrix host copy beyond the in-flight blocks.

        ``io_workers`` (default ``config.io_workers``) runs the byte
        formatting of ~4k-row sub-chunks on a thread pool overlapped with the
        serial in-order file write (pipeline.ordered_pool_map) — small jobs
        keep the in-flight memory bounded (large whole-block jobs measurably
        REGRESSED under allocator churn, hostbench). Device fetches stay on
        the calling thread, and the bytes written are identical at any worker
        count."""
        self._check_alive("export_word2vec")
        import io

        from glint_word2vec_tpu.data.pipeline import ordered_pool_map
        if io_workers is None:
            io_workers = getattr(self.config, "io_workers", 1)
        D = int(self.syn0.shape[1])
        sub = max(1, min(batch_size, 4096))

        def jobs():
            for start in range(0, self.num_words, batch_size):
                stop = min(start + batch_size, self.num_words)
                block = np.asarray(self.syn0[start:stop], np.float32)
                for lo in range(start, stop, sub):
                    hi = min(lo + sub, stop)
                    yield lo, block[lo - start:hi - start]

        words = self.vocab.words

        def format_chunk(job) -> bytes:
            lo, rows = job
            buf = io.BytesIO()
            if binary:
                raw = rows.astype("<f4")
                for i in range(rows.shape[0]):
                    buf.write(words[lo + i].encode())
                    buf.write(b" ")
                    buf.write(raw[i].tobytes())
                    buf.write(b"\n")
            else:
                for i in range(rows.shape[0]):
                    vec = " ".join(repr(float(x)) for x in rows[i])
                    buf.write(f"{words[lo + i]} {vec}\n".encode())
            return buf.getvalue()

        with open(path, "wb") as f:
            f.write(f"{self.num_words} {D}\n".encode())
            for data in ordered_pool_map(format_chunk, jobs(), io_workers):
                f.write(data)

    # -- persistence (G9/C13) ----------------------------------------------------------

    def save(self, path: str) -> None:
        self._check_alive("save")
        # a subword model saves what it trained (own rows and bucket rows),
        # not the composed table its queries scan
        raw0 = self.syn0 if self._raw0 is None else self._raw0
        ckpt.save_model(
            path, self.vocab.words, self.vocab.counts,
            np.asarray(raw0),
            np.asarray(self.syn1) if self.syn1 is not None else None,
            self.config, self.train_state,
            subword_buckets=(None if self._buckets is None
                             else np.asarray(self.subword_buckets)),
            position_weights=self.position_weights)

    @classmethod
    def load(cls, path: str, plan: Optional[MeshPlan] = None,
             verify: bool = True,
             io_workers: Optional[int] = None,
             resident: str = "all") -> "Word2VecModel":
        """Load a saved model; ``plan`` retargets the arrays onto a different mesh — the
        analog of the reference's load-onto-different-PS-topology overloads
        (mllib:696-725, ml:584-599).

        With a ``plan``, a row-shards checkpoint streams each device's row block
        straight from the mmap'd shard files onto the target mesh
        (:func:`..train.checkpoint.load_params_into_plan`) — the full [V, D] matrices
        never materialize on any single host, so model ops (transform/find_synonyms)
        work at vocabularies that exceed one host's memory.

        ``verify=False`` skips the digest (re-)hash on both layouts — for
        callers that just verified (e.g. :meth:`load_latest`), or for skipping
        the extra sequential shard read on a trusted very large row-shards
        checkpoint.

        ``io_workers``: thread fan-out for digest hashing and shard reads on
        THIS host (default: the worker count recorded in the checkpoint's
        config — pass your own on hosts that differ from the writer's).

        ``resident="rows"`` (a subword model's checkpoint, no ``plan``): the
        constructor's, for a process that only reads rows; syn1 is read from
        the file and never placed on the device."""
        header = None
        if plan is not None:
            header = ckpt.load_model_header(path)
            if header["layout"] == "row-shards":
                vocab = Vocabulary.from_words_and_counts(
                    header["words"], header["counts"])
                Vp = pad_vocab_for_sharding(vocab.size, plan.num_model)
                syn0, syn1 = ckpt.load_params_into_plan(
                    path, plan, Vp, header["vector_size"], verify=verify,
                    io_workers=io_workers)
                return cls(vocab=vocab, syn0=syn0, syn1=syn1,
                           config=header["config"], plan=plan,
                           train_state=header["train_state"])
        data = ckpt.load_model(path, header=header, verify=verify,
                               io_workers=io_workers)
        vocab = Vocabulary.from_words_and_counts(data["words"], data["counts"])
        return cls(
            vocab=vocab,
            syn0=jnp.asarray(data["syn0"]),
            syn1=(jnp.asarray(data["syn1"])
                  if data["syn1"] is not None and resident != "rows" else None),
            config=data["config"],
            plan=plan,
            train_state=data["train_state"],
            subword_buckets=data.get("subword_buckets"),
            position_weights=data.get("position_weights"),
            resident=resident,
        )

    @classmethod
    def load_latest(cls, directory: str, plan: Optional[MeshPlan] = None,
                    reclaim: bool = False) -> "Word2VecModel":
        """Serving-side recovery load: scan ``directory`` and load the newest
        checkpoint whose content passes digest verification
        (:func:`..train.checkpoint.load_latest_valid`). Non-destructive by
        default (``reclaim=False``): safe to call while a trainer may still be
        saving into the directory — debris is left alone, and a torn-swap
        predecessor is loaded from its ``*.old-*`` path without renaming.
        Pass ``reclaim=True`` only when the writer is known dead (true crash
        recovery) to also clean the directory up. The scan already verified
        the winner's digests, so the load itself skips the re-hash."""
        return cls.load(ckpt.load_latest_valid(directory, reclaim=reclaim),
                        plan=plan, verify=False)

    def stop(self) -> None:
        """Release device buffers — the analog of the reference's PS teardown
        (client.terminateOnSpark + matrix.destroy, mllib:655-667). Idempotent."""
        if self._stopped:
            return
        for arr in (self._full0, self._full1, self._norms, self._inv_norms,
                    self._raw0, self._buckets, self._lanes, self._scan0):
            if arr is not None:
                try:
                    arr.delete()
                except Exception:
                    pass
        self._full0 = None  # type: ignore[assignment]
        self._full1 = None
        self._norms = self._inv_norms = None
        self._host_inv = None
        self._raw0 = self._buckets = self._lanes = self._scan0 = None
        self._ann = None
        self._stopped = True


# parts of one call whose scans are enqueued and not yet fetched: one running
# and one queued behind it, the bound the serve batcher keeps for its batches
# (a [128, V] score block is 1.5 GB at 3M rows)
_PARTS_IN_FLIGHT = 2


class _PendingSynonyms:
    """What ``Word2VecModel.find_synonyms_begin`` hands to
    ``find_synonyms_finish``: the query words, the parts (one per chunk: its
    offset and host arrays), the results of the parts enqueued so far (device
    arrays on their way back), and the span that enclosed the begin.
    ``replies`` is set where begin did all the work (the ANN arm)."""

    __slots__ = ("num", "k", "parent", "words", "parts", "results",
                 "scan", "replies")

    def __init__(self, num: int, parent: Optional[int]):
        self.num = num
        self.k = 0
        self.parent = parent
        self.words: List[Optional[str]] = []
        self.parts: list = []
        self.results: list = []
        self.scan: Dict[str, int] = {}
        self.replies: Optional[List[List[Tuple[str, float]]]] = None


# questions one program of the analogy scan answers at most; a call of more
# runs further programs, two in flight. With _ANALOGY_BLOCK_ROWS it sizes the
# [questions, rows] float32 score block (0.54 GB) a program of num > 1 holds;
# at num = 1 the TPU's compiler keeps the block inside the matmul's own fusion
_ANALOGY_MAX_QUESTIONS = 2048
# rows of the table one block of the analogy scan scores
_ANALOGY_BLOCK_ROWS = 1 << 16
# the least capacity, and so the tile of _grid_up's grid up to 4,096 questions:
# the public file's 14 sections share six programs (512, 1,024, 1,280, 1,536,
# 1,792, 2,048) at nine tenths of their capacity live
_ANALOGY_CAP_FLOOR = 256


# rows one pass of a transform slide's program gathers at most: where the
# gathered block is written (off the TPU, whose sorted scatter-add takes the
# gather as a producer) it is [rows, 384] float32, 0.8 GB, and several slides
# may be in flight
_TRANSFORM_MAX_ROWS = 1 << 19

# slides of one transform_sentences call that are encoded and enqueued before
# the oldest is fetched: one running, one queued behind it
_SLIDES_IN_FLIGHT = 2


class _PendingSlide:
    """One slide of ``Word2VecModel.transform_sentences`` between its halves:
    where its rows go (``lo``, ``sentences``), its ``[S, D]`` means on their
    way back (None where it held no in-vocabulary token) and its
    ``transform.slide`` span (None where nothing records)."""

    __slots__ = ("lo", "sentences", "span", "result")

    def __init__(self, lo: int, sentences: int, span):
        self.lo = lo
        self.sentences = sentences
        self.span = span
        self.result: Optional[jax.Array] = None


class _SlideTokens(NamedTuple):
    """One slide of ``Word2VecModel.sentence_vectors``, encoded
    (``_encode_tokens``): what its program is handed, before the capacities."""

    ids: np.ndarray          # int32 [W] rows of the in-vocabulary tokens, as sent
    scales: np.ndarray       # [W] 1 / the norm of each of those rows, as the norms are kept
    counts: np.ndarray       # int32 [S] how many of them each sentence holds
    unseen: np.ndarray       # int32 [S] composed tokens each sentence holds
    list_rows: np.ndarray    # int32 [L] the composed tokens' bucket rows, flat
    list_counts: np.ndarray  # int32 [U] bucket rows of each composed token (> 0)
    zero_norm: int           # tokens left out here: no vector, or one of zero norm
    by_objects: bool         # the native table resolved the slide


def _grid_up(n: int, floor: int) -> int:
    """``n`` rounded up to a whole number of tiles, a tile a sixteenth of the
    power of two at or under ``n`` and at least ``floor``: the sizes a
    transform slide is handed over at. Sixteen sizes an octave: slides of
    real text, whose lengths differ, share a few programs, and at most 1/16
    of what is handed over is padding (327,680 rows for 313,000 live ids)."""
    tile = max(floor, (1 << (max(n, 1).bit_length() - 1)) // 16)
    return -(-n // tile) * tile
