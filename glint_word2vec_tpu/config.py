"""Hyperparameter surface.

Mirrors the reference's 16-knob surface (mllib/feature/ServerSideGlintWord2Vec.scala:67-244,
ml/feature/ServerSideGlintWord2Vec.scala:40-222) with the same semantics and defaults, plus
TPU-native knobs the reference had no analog for (mesh shape, pair-batch size, dtype).

Reference defaults (mllib:67-81,251): vectorSize 100, learningRate 0.01875, numPartitions 1,
numIterations 1, minCount 5, maxSentenceLength 1000, window 5, batchSize 50, n 5,
subsampleRatio 1e-6, numParameterServers 5, parameterServerHost "", unigramTableSize 1e8,
seed random.

Knobs that existed only to work around the reference's Akka transport — the
``batchSize * n * window <= 10000`` payload constraint (mllib:83-85,154-188) and
``parameterServerHost``/``parameterServerConfig`` (mllib:196-231) — are accepted by the
compat layer (:mod:`glint_word2vec_tpu.models.compat`) for drop-in familiarity but have no
effect here: there is no RPC.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional, Tuple

logger = logging.getLogger("glint_word2vec_tpu")

# Fields Word2VecConfig no longer has, with the defaults they had: the stored
# configs of older checkpoints still carry them (Word2VecConfig.from_dict).
_RETIRED_KEYS = {"use_pallas": False, "hot_rows": 0, "hot_flush_every": 0}


@dataclasses.dataclass
class Word2VecConfig:
    """Configuration for TPU-native word2vec training.

    Attributes whose names differ from the reference keep a comment mapping them back.
    """

    # --- core hyperparameters (reference-parity; defaults mllib:67-81,251) ---
    vector_size: int = 100          # vectorSize (mllib:67)
    learning_rate: float = 0.01875  # stepSize/learningRate (mllib:68)
    num_partitions: int = 1         # numPartitions (mllib:69) — scales the lr-decay clock
                                    # (mllib:406-410); on TPU it is the data-parallel degree
    num_iterations: int = 1         # numIterations (mllib:70)
    min_count: int = 5              # minCount (mllib:76)
    max_sentence_length: int = 1000  # maxSentenceLength (mllib:73,88-97)
    window: int = 5                 # window (mllib:251)
    batch_size: int = 50            # batchSize (mllib:74) — reference centers-per-minibatch;
                                    # kept for decay/compat; device batching uses pairs_per_batch
    negatives: int = 5              # n (mllib:75)
    subsample_ratio: float = -1.0   # subsampleRatio (mllib:77,190-194). 0 disables.
                                    # -1 (default) = AUTO: resolves to 1e-3 at
                                    # construction, and the Trainer may LOWER it
                                    # further when the corpus + batch geometry would
                                    # exceed the measured duplicate-overload
                                    # divergence boundary (expected top-word
                                    # duplicates per batch > 300 trains to NaN —
                                    # EVAL.md round-4 addendum). An explicit value is
                                    # never silently changed: explicit unstable
                                    # configs are refused unless allow_unstable=True.
                                    # Why 1e-3 (word2vec.c's/gensim's default):
                                    # bounds EVAL.md's duplicate-overload channel — a
                                    # frequent word's summed scatter updates in one
                                    # large batch diverge with subsampling OFF — while
                                    # staying sane on small corpora (1e-4 starves a
                                    # 161k-word corpus below the reference's own
                                    # semantic gates; 1e-3 passes them AND holds
                                    # purity 1.0 at 17M words in EVAL_RUNS — though
                                    # the same 17M rows measure analogy acc@1 0.71 at
                                    # 1e-3 vs 0.99 at 1e-4, so tune per corpus.
                                    # HARD boundary, measured: 1e-3 with B=64k
                                    # diverges at 60M words (duplicate channel, 336
                                    # expected dups > the 300 threshold — the
                                    # construction-time warning names exactly this);
                                    # large-batch long runs want ~1e-4, which is also
                                    # the best relational quality at scale.
                                    # NOTE: the reference's default is 1e-6, but its
                                    # formula divides Int/Long (mllib:374-376) so its
                                    # subsampling is a silent no-op — the compat layer
                                    # pins 0.0 to mirror that observed behavior. Setting
                                    # >0 uses the intended float formula (pipeline.py).
    seed: int = 0                   # seed (mllib:71; random by default there, fixed here for
                                    # reproducibility — sync training makes runs deterministic)

    # --- sharding / deployment (replaces numParameterServers & PS plumbing) ---
    num_model_shards: int = 1       # ≈ numParameterServers (mllib:78,204-212): how many ways
                                    # the embedding rows are sharded over the mesh 'model' axis
    num_data_shards: int = 1        # data-parallel degree over the mesh 'data' axis
    embedding_partition: str = "rows"  # "rows" (production: V/N rows per device) or
                                       # "cols" (CIKM'16: D/N columns per device,
                                       # partial dots + psum — the reference PS
                                       # layout, G2/SURVEY §7.4). Identical math
                                       # (cross-layout loss check in the dryrun).
                                       # "cols" is EXPERIMENTAL, single-host only:
                                       # the design verdict (PERF.md §7) is that
                                       # rows divides the per-update-row scatter
                                       # bound by N and enables row-shards
                                       # checkpoints, while cols only wins
                                       # collective bytes below pool ≈ 2·D (its
                                       # blowout case, per-pair sampling, is the
                                       # reference's thin-network regime, not ICI)
    mesh_shape: Optional[Tuple[int, int]] = None  # explicit (data, model) mesh; default derives
                                                  # from num_data_shards × num_model_shards
    step_lowering: str = "gspmd"    # how the sharded SGNS step lowers onto the mesh:
                                    # "gspmd" (default): one jitted program, GSPMD
                                    # inserts whatever collectives it derives from the
                                    # sharding constraints — the pre-round-9 behavior,
                                    # bit-identical to it.
                                    # "shard_map": the hand-lowered explicit schedule
                                    # (ops/sgns_shard.py, docs/sharding.md) — each
                                    # model shard gathers rows it owns (index −
                                    # row_offset, OOB-masked) with ONE psum over the
                                    # model axis assembling e_in/e_pos/pool rows, the
                                    # backward applies OWNER-LOCAL scatters only (zero
                                    # update bytes cross the model axis — the TPU
                                    # analog of the reference's ship-indices-and-
                                    # scalars collective schedule, CIKM'16), and the
                                    # data axis exchanges the per-shard update payload
                                    # with one all-gather. HLO-audited collective
                                    # bytes: tools/collectives.py; mesh-shape A/B:
                                    # tools/shard_ab.py. Identical math (f64 ~1e-12
                                    # equivalence tested at every 8-device mesh
                                    # shape); each lowering is run-to-run
                                    # deterministic, but the two lowerings are NOT
                                    # bit-identical to each other (different FP
                                    # reduction orders). Shared-pool skip-gram rows
                                    # layout only (pool > 0, no cbow/
                                    # duplicate_scaling/cols — refused at
                                    # construction). GSPMD stays the default until a
                                    # hardware A/B lands (the audited collective
                                    # profile is the evidence so far, PERF.md §7)
    sync_every: int = 1             # local-SGD merge cadence (docs/sharding.md
                                    # §Local-SGD): 1 (default) = fully synchronous,
                                    # bit-identical to the pre-knob step. k > 1 = each
                                    # data shard runs k OWNER-LOCAL steps against its
                                    # own params replica (the shard_map schedule's
                                    # owner-local gather/scatter machinery, so zero
                                    # update bytes cross the model axis AND zero bytes
                                    # cross the data axis inside the window), then ONE
                                    # delta-merge collective reconciles the data axis:
                                    # merged = start + psum(local − start, data)/nd —
                                    # the reference's Hogwild-across-partitions
                                    # network-thrift discipline (PAPER.md §0, CIKM'16)
                                    # in its deterministic periodic-averaging form.
                                    # Per-shard negative lattices are DISJOINT, so a
                                    # merged run is deterministic per (seed, mesh, k).
                                    # shard_map lowering only (the owner-local window
                                    # doesn't exist under GSPMD — refused at
                                    # construction); must divide steps_per_dispatch so
                                    # every dispatch boundary is a merge boundary
                                    # (snapshot/rollback/preemption-save never see an
                                    # unmerged shard). Priced: tools/collectives.py
                                    # --sync-every; quality-gated: tools/eval_quality
                                    # --localsgd-ab

    # --- negative-sampling table (G7; mllib:81,234-244) ---
    unigram_table_size: int = 100_000_000  # kept for compat; the alias sampler is O(2·vocab)
                                           # and exact, so this only sizes the optional
                                           # table-based sampler used in parity tests
    sample_power: float = 0.75      # classic word2vec counts^0.75 (fork-side in the reference)

    # --- TPU-native knobs (no reference analog) ---
    pairs_per_batch: int = 8192     # (center, context) pairs per device step; the reference's
                                    # RPC-bound batchSize*window pairs/minibatch becomes one
                                    # large fixed-shape jit step. Sized for realistic
                                    # corpora (millions of words up); on toy corpora use
                                    # a small batch (~256) — a 161k-word corpus at 8192
                                    # pairs/step gets only ~20 coarse updates per epoch,
                                    # too few for sharp analogy geometry (the toy
                                    # integration suite's settings)
    sigmoid_mode: str = "exact"     # "exact" = jax.nn.sigmoid; "clipped" mirrors the reference
                                    # LUT clipping at |f| > 6 (mllib:246-248,292-302)
    allow_unstable: bool = False    # override the construction-time REFUSAL of configs
                                    # inside a measured divergence region (today: the
                                    # duplicate-overload channel — explicit
                                    # subsample_ratio whose expected top-word
                                    # duplicates per batch exceed 300, the boundary
                                    # EVAL measured training to NaN at 60M words).
                                    # With the override the trainer only warns, for
                                    # boundary research and short runs
    duplicate_scaling: bool = False  # opt-in stabilizer: average (not sum) a row's updates
                                     # over its in-batch multiplicity. Off by default —
                                     # textbook word2vec semantics; realistic vocabs have
                                     # low duplicate density after subsampling. Turn on for
                                     # tiny-vocab/large-batch regimes where summed
                                     # duplicates would diverge (slows differentiation;
                                     # see ops/sgns.py)
    negative_pool: int = -1         # >0: share one pool of this many negatives across the
                                    # whole batch (reweighted by negatives/pool to keep the
                                    # expected gradient) — turns the dominant negative row
                                    # traffic into MXU matmuls, ~2-3x step speedup. 0 = the
                                    # reference's exact per-pair sampling (G3 semantics;
                                    # the compat layer pins this). -1 (default) = AUTO:
                                    # resolved at construction to the smallest multiple of
                                    # 128 keeping the pool-row load pairs_per_batch *
                                    # negatives / pool <= 600 — the measured 60M-word
                                    # stability rule (EVAL.md; a fixed small pool under a
                                    # large batch provably diverges, e.g. B=64k/P=64).
                                    # That 600 band was CALIBRATED AT 90k VOCAB; at
                                    # large vocabularies a pool row is re-corrected
                                    # orders of magnitude less often and the measured
                                    # safe band tightens to load <= 160 (EVAL.md
                                    # round-5: load 640 collapsed purity 0.99 -> 0.14
                                    # at 1.6M vocab, load 160 fixed it at the same
                                    # lr). Config cannot see the vocabulary, so the
                                    # Trainer re-resolves a STILL-AUTO pool upward at
                                    # construction once vocab.size > 500k
                                    # (trainer._resolve_vocab_scaled_pool; explicit
                                    # pools are never changed, only warned about) —
                                    # except batches < 4096 pairs, which resolve to 0:
                                    # per-pair is fast enough there and shared negatives
                                    # cost quality on small corpora (toy bf16 gate)
    pad_vector_to_lanes: bool = True  # pad the embedding minor dim to a multiple of 128
                                      # (TPU lane width) — D=300 rows are misaligned and
                                      # measurably slower than padded 384; exports are
                                      # sliced back to vector_size
    param_dtype: str = "float32"    # embedding storage dtype
    compute_dtype: str = "float32"  # dot-product dtype ("bfloat16" rides the MXU)
    logits_dtype: str = "float32"   # dtype of the [B, pool] negative-logit chain on
                                    # the shared-pool paths (f_neg → sigmoid → g_neg).
                                    # f32 matches the reference's client-side math
                                    # (mllib:421-425); "bfloat16" halves what is, at
                                    # pool >= 512, several full passes over a [B, pool]
                                    # array (PERF.md §4) — coefficients are O(lr·n/pool)
                                    # and tolerate the ~0.4% relative noise
    # --- step restructurings (ISSUE 14, PERF.md §11 — the emitter-ceiling
    # levers; all off by default, and OFF ELIDES THE NEW OPS: the default
    # step is bit-identical to the pre-restructure release, tested) ---
    fused_logits: bool = False      # fuse the negative-logit coefficient chain
                                    # (ops/sgns.py shared_pool_coeffs): validity
                                    # + batch mask fold into ONE select and the
                                    # alpha·negatives/pool reweight into one
                                    # precomputed scalar, so the [B, pool] (or
                                    # per-pair [B, n]) chain materializes only
                                    # the dot output and the coefficient array
                                    # instead of also a float validity array
                                    # and its mask/alpha/reweight passes.
                                    # Identical math (f64-oracle tested), not
                                    # bit-identical (multiply association
                                    # changes). SGNS paths only (per-pair,
                                    # shared-pool GSPMD + shard_map); refused
                                    # beside cbow/duplicate_scaling
    bf16_chain: bool = False        # end-to-end reduced-precision update chain:
                                    # the logit dots accumulate in
                                    # promote(compute, f32) via
                                    # preferred_element_type instead of a
                                    # multiply + convert + reduce, so bf16 mode
                                    # materializes NO dense f32 [B, D]
                                    # intermediate (stepaudit dtype-contract
                                    # row pins this) while keeping the R4 f32
                                    # accumulation discipline. Requires
                                    # compute_dtype='bfloat16' (with f32
                                    # compute there is no chain to narrow) and,
                                    # on the shared-pool paths, logits_dtype=
                                    # 'bfloat16'. SGNS paths only; refused
                                    # beside cbow
    sharded_checkpoint: bool = False  # row-shards save (each process writes its own
                                      # rows, no host gather — G9 analog); forced on
                                      # for multi-process runs
    cbow: bool = False              # CBOW variant (context-mean → center) instead of skip-gram
    cbow_update: str = "scatter"    # CBOW step formulation (cbow=True only):
                                    # "scatter" (default): grouped [B, 2·window]
                                    # context batches, gather/scatter B·C syn0
                                    # rows per step (ops/sgns.cbow_step_*). The
                                    # reference formulation; required for
                                    # duplicate_scaling=True and the only one
                                    # multi-feed-agnostic (host pair feed).
                                    # "banded": sentence-contiguous token-block
                                    # feed + prefix-sum interval accumulation
                                    # (ops/cbow_banded.py) — ~B context rows
                                    # instead of B·C: 2.5× the scatter form's
                                    # examples/s on a TPU v5 lite at 3M × 300,
                                    # B=64k (2.17 M against 0.85 M; PERF.md §6,
                                    # PR 27).
                                    # Identical update math (float64-equivalence
                                    # tested); needs the shared-pool estimator
                                    # (negative_pool > 0), window ≥ 2, and no
                                    # duplicate_scaling — unsupported combos
                                    # are refused at construction, never
                                    # silently downgraded. Stays opt-in until
                                    # EVAL evidence lands (acceptance rule)
    cbow_position_weights: bool = False  # CBOW with position weights (Mikolov
                                    # et al. 2018, arXiv:1712.09405 §2.2;
                                    # fastText's cc.*.300 recipe): the window
                                    # is summed under a learned vector per
                                    # relative position, a third trainable
                                    # leaf [2·window, D] initialised to ones
                                    # and moved by the mean of its examples'
                                    # updates (ops/cbow_banded.py). The banded
                                    # form only, with or without subword;
                                    # refused beside sharded_checkpoint
    subword: bool = False           # subword skip-gram (fastText; Bojanowski et
                                    # al. 2017, arXiv:1607.04606): a word's
                                    # input vector is the mean of its own row
                                    # and the bucket rows of its hashed
                                    # character n-grams (data/subword.py,
                                    # ops/subword.py); syn0 then has vocabulary
                                    # + subword_buckets rows. The shared-pool
                                    # skip-gram step, or beside cbow the banded
                                    # step (a context TOKEN's vector is that
                                    # mean), on one device only: refused
                                    # beside cbow_update='scatter',
                                    # negative_pool=0,
                                    # step_lowering='shard_map', a mesh larger
                                    # than 1x1, device_pairgen,
                                    # duplicate_scaling, sharded_checkpoint and
                                    # the touched-row stabilizers
    subword_min_n: int = 3          # shortest and longest character n-gram of
    subword_max_n: int = 6          # "<word>" (fastText's minn / maxn)
    subword_buckets: int = 2_000_000  # hash buckets the n-grams share (its bucket)
    loss: str = "ns"                # the output side's loss: "ns", negative
                                    # sampling (every other knob's ground), or
                                    # "hs", hierarchical softmax over the
                                    # vocabulary's Huffman tree (word2vec.c
                                    # -hs 1 -negative 0, the loss of Spark
                                    # MLlib's Word2Vec; data/huffman.py,
                                    # ops/hs.py): syn1's rows are the tree's
                                    # V - 1 inner nodes and a pair's output
                                    # side is its context's path. No sampler
                                    # and no pool: negatives must be 0 and
                                    # negative_pool resolves to 0. Skip-gram
                                    # over the host pair feed on one device
                                    # only: refused beside cbow, subword,
                                    # device_pairgen, step_lowering=
                                    # 'shard_map', a mesh larger than 1x1,
                                    # duplicate_scaling, sharded_checkpoint,
                                    # fused_logits / bf16_chain and the
                                    # stabilizers
    shuffle: bool = True            # shuffle sentence order each iteration (reference order is
                                    # whatever repartition() produced, i.e. arbitrary; mllib:345)

    # --- lr decay semantics (mllib:405-413) ---
    min_alpha_factor: float = 1e-4  # floor alpha at learning_rate * 1e-4 (mllib:410)
    decay_interval_words: int = 10_000  # reference alpha cadence (mllib:404) — here alpha
                                        # updates every batch (host-side, free); kept for
                                        # compat surface
    steps_per_dispatch: int = 16    # train steps scanned inside one device dispatch;
                                    # amortizes per-dispatch host->device launch and
                                    # transfer latency against a ~ms step; the last
                                    # chunk of an epoch is padded with masked batches
    heartbeat_every_steps: int = 100  # telemetry cadence. The reference logs every 10k
                                      # words (one 50-pair minibatch era); fetching device
                                      # metrics forces a host sync, so at 8k-pair batches a
                                      # word-based cadence would sync nearly every step and
                                      # halve throughput
    prefetch_chunks: int = 8        # dispatch chunks buffered by the background batch
                                    # producer thread: host pair-generation overlaps device
                                    # compute (the reference pipelines one minibatch deep
                                    # for the same reason, mllib:428-429). 0 = synchronous
                                    # (producer thread off; debugging aid)
    profile_dir: str = ""           # non-empty: capture a jax.profiler trace of every
                                    # fit() into this directory (view with TensorBoard
                                    # or xprof; complements the host-wait/dispatch
                                    # split the trainer always records)
    feed_consistency_check: bool = False  # debug: on multi-process runs, fingerprint
                                    # every assembled global batch and compare across
                                    # processes (one tiny extra allgather per round) —
                                    # catches SPMD feed divergence (nondeterministic
                                    # host pipelines, clock drift) at the round it
                                    # happens instead of as silent training divergence.
                                    # The aux-subsystem analog of race detection: the
                                    # reference ACCEPTED data races by design
                                    # (Hogwild, SURVEY §5); the synchronous design can
                                    # verify its no-divergence contract instead
    shard_input: bool = True        # multi-process runs: each process generates only its
                                    # own sentence shard (the repartition analog,
                                    # mllib:345) and per-round allgathers assemble the
                                    # global batch — host pipeline work scales 1/N with
                                    # hosts. False = every process regenerates the full
                                    # stream (zero-coordination fallback). Both skip-gram
                                    # (packed pairs) and CBOW (centers/contexts/counts)
                                    # feeds ride the same protocol.
    device_pairgen: bool = False    # generate training pairs ON DEVICE (ops/pairgen.py):
                                    # the host subsamples and ships kept-token blocks
                                    # (~1 byte/pair on the wire vs 4 for packed pairs)
                                    # and the jitted step derives window draws from the
                                    # same position-keyed hash lattice as the host
                                    # pipeline. The stream is deterministic per seed but
                                    # NOT bit-identical to the host feed's (windows are
                                    # keyed by kept-token ordinals and blocks cut at the
                                    # token budget — statistically identical; contract
                                    # + tests in ops/pairgen.py). Use when the
                                    # host→device feed link is the bottleneck (thin
                                    # PCIe/DCN links). Skip-gram only (CBOW
                                    # batches are grouped windows the device generator
                                    # does not produce). Multi-process: combine with
                                    # shard_input=True — each process packs token
                                    # blocks for its own data segments and the
                                    # iteration-barrier allgather keeps training
                                    # bit-identical to single-process
                                    # (train/feeds.GatheredTokenBlocks)
    tokens_per_step: int = 0        # device_pairgen: raw token slots per step; 0 sizes
                                    # automatically from pairs_per_batch, window, and the
                                    # subsample keep ratio (targeting ~93% pair-slot fill;
                                    # overflow pairs are dropped and counted)

    # --- parallel host data plane (PERF.md §10; no reference analog — the
    # reference gets host parallelism from Spark partitions, mllib:345,428) ---
    producer_workers: int = 1       # feed-producer thread pool width. 1 (default) =
                                    # the serial producer, bit-identical to every
                                    # prior release. >1 fans the per-slab pair/token
                                    # generation (and, on multi-segment device
                                    # feeds, the per-segment block streams) across
                                    # this many threads — numpy releases the GIL in
                                    # the hot loops, so production genuinely
                                    # parallelizes. The stream is position-keyed
                                    # (hashrng), so ANY worker count produces the
                                    # bit-identical stream (tested); the knob only
                                    # changes throughput. Sized to the host: ~4 on
                                    # an 8-core host feeding a co-located device
                                    # (PERF.md §5: the serial producer tops out at
                                    # 9.5M pairs/s against a 12.4-13.2M pairs/s step)
    io_workers: int = 1             # checkpoint/export I/O thread pool width. 1
                                    # (default) = serial writes/reads. >1 fans
                                    # independent file writes, shard reads, digest
                                    # verification, and export block formatting
                                    # across this many threads (train/checkpoint.py,
                                    # models/word2vec.py) and parallelizes the
                                    # cold-start builds (vocab counting slabs,
                                    # alias-table partitions). Outputs are
                                    # byte-identical at ANY worker count — the knob
                                    # only changes wall clock. Hashing always
                                    # happens in the same pass as the write
                                    # (single-pass digests; this is unconditional,
                                    # it needs no workers). One CROSS-RELEASE
                                    # caveat, worker-independent: round 8's
                                    # vectorized alias builder (ops/sampler.py)
                                    # produces a DIFFERENT (equally exact,
                                    # deterministic) table than rounds <= 7 at any
                                    # worker count, so the realized negative-sample
                                    # stream differs from prior releases —
                                    # distribution unchanged (tested), PERF.md §10.
                                    # SCOPE caveat for the vocab-counting slab fan-out:
                                    # counting PYTHON string tokens under the GIL is
                                    # pure contention — MEASURED 0.66x at workers=4
                                    # (hostbench, PERF.md §10; Counter.update never
                                    # releases the lock) — so build_vocab engages the
                                    # slab pool only when data.vocab.
                                    # parallel_counting_profitable() says the runtime
                                    # can profit (free-threaded CPython). A session on
                                    # a free-threaded host flips it by re-measuring
                                    # there, not by editing a guess: the helper + its
                                    # evidence live in one place (data/vocab.py)
    sharded_prefetch: bool = True   # multi-process device-feed runs: stage each
                                    # round's allgather + assembly + device put one
                                    # round ahead on a background thread so the
                                    # wire transfer overlaps device compute (the
                                    # single-process _stage_to_device analog). The
                                    # stager and the main loop alternate under a
                                    # strict ticket handshake, so every process
                                    # keeps ONE deterministic program-launch order
                                    # (allgather_r, touch_r, dispatch_r, ...) — the
                                    # invariant that makes cross-host collectives
                                    # deadlock-free (see feeds._one_ahead_iter).
                                    # False = the pre-round-8 consumer-thread put.
                                    # No effect single-process or at
                                    # prefetch_chunks=0

    # --- fault tolerance (docs/robustness.md; no reference analog — the
    # reference leans on Spark task re-execution, SURVEY §5) ---
    nonfinite_policy: str = "halt"  # what the trainer does when the params carry goes
                                    # non-finite (bf16 blowup, divergence). Probed at
                                    # heartbeat/checkpoint cadence on the params the
                                    # heartbeat fetch already syncs on, so the fast
                                    # metrics-elided step twin stays elided.
                                    # "halt" (default): raise NonFiniteParamsError with
                                    # a diagnostic instead of burning accelerator-hours
                                    # training NaNs or overwriting a good checkpoint;
                                    # "rollback": restore the newest in-memory good
                                    # snapshot and jump the negative-sample counter
                                    # lattice so the retried stretch draws a different
                                    # sample path; "none": pre-round-6 behavior (no
                                    # probe, NaNs train on silently)
    rollback_history: int = 2       # nonfinite_policy="rollback": how many good param
                                    # snapshots the in-memory ring holds. A rollback
                                    # pops the newest; repeated blowups before the next
                                    # finite probe step back through the older entries.
                                    # Each snapshot is a device-resident copy of the
                                    # padded [V, D] syn0+syn1 pair — budget HBM
                                    # accordingly
    max_rollbacks: int = 8          # nonfinite_policy="rollback": give up (raise) after
                                    # this many rollbacks in one fit() — a run that
                                    # keeps diverging needs a config change
                                    # (lr/pool/subsample), not infinite retries

    # --- run telemetry (docs/observability.md; no reference analog — its only
    # observability is the every-10k-words driver log line, mllib:411-412) ---
    telemetry_path: str = ""        # non-empty: write the schema-versioned JSONL
                                    # run log here (obs/sink.py — rotating file,
                                    # NEVER stdout: the driver tools' one-JSON-
                                    # line contract, graftlint R7, must survive a
                                    # telemetry-on trainer inside any of them).
                                    # Carries run_start/run_end, extended
                                    # heartbeats (norm channels, per-phase host
                                    # timings), and watchdog records; the host
                                    # trace spans export beside it as
                                    # <telemetry_path>.trace.json (Chrome trace
                                    # format — Perfetto-loadable). Empty
                                    # (default) = telemetry off, zero cost
    telemetry_rotate_bytes: int = 64 << 20  # rotate the run log past this size
                                    # (<path>.1..<path>.3 kept) so long-run
                                    # telemetry is disk-bounded
    heartbeat_ring: int = 512       # in-memory Trainer.heartbeats capacity (a
                                    # bounded ring — pre-round-11 this list grew
                                    # one record per heartbeat forever, ~weeks-
                                    # long runs leaked). The full history
                                    # persists in the telemetry sink file
    norm_watch: str = "off"         # finite-blowup watchdog over the fused
                                    # health probe's row-norm channels
                                    # (obs/probe.py, heartbeat cadence — the
                                    # guardrail for the measured 1.6M-vocab
                                    # FINITE collapse where nonfinite_policy
                                    # never fires, EVAL.md round-5 / ROADMAP 2).
                                    # "off" (default): probe channels still
                                    # recorded when telemetry is on, nothing
                                    # fires. "warn": log + telemetry record per
                                    # firing probe, training continues.
                                    # "recover": the self-stabilizing ladder
                                    # (docs/robustness.md) — emit a telemetry
                                    # recovery record, roll back to the newest
                                    # snapshot-ring entry (the ring arms for
                                    # ANY consumer, not only nonfinite
                                    # rollback), re-seed the negative-sample
                                    # counter lattice, back the lr off by
                                    # recover_lr_backoff, engage max_row_norm
                                    # (at norm_watch_threshold) if it was off,
                                    # and continue — up to max_recoveries per
                                    # fit, then degrade to "halt". "halt":
                                    # raise NormBlowupError (fail-fast, the
                                    # nonfinite_policy="halt" contract)
    norm_watch_threshold: float = 100.0  # row-L2-norm boundary of the
                                    # frac_over channel. Provenance: the EVAL
                                    # harness's blown-row heuristic (rows with
                                    # |emb| > 100, tools/eval_quality.py) —
                                    # healthy trained rows sit at norm ~1-15
                                    # across every EVAL_RUNS config; collapsed
                                    # 1.6M-vocab rows measured orders of
                                    # magnitude past 100 (docs/observability.md)
    norm_watch_frac: float = 0.01   # watchdog fires when this fraction of a
                                    # matrix's rows exceed the threshold — the
                                    # collapse shows in a hot-row subset first
    norm_watch_max: float = 1000.0  # hard ceiling on any single row norm —
                                    # catches a lone runaway row the fraction
                                    # channel dilutes at large vocabularies
    # --- in-step stabilizers + watchdog auto-recovery (docs/robustness.md
    # escalation ladder; the mitigation half of the ROADMAP-2 finite-blowup
    # response — the knobs the watchdog diagnostic used to recommend by hand).
    # ALL off by default: the 0.0 defaults elide every stabilizer op from the
    # compiled step, so the default step is bit-identical to pre-stabilizer
    # releases (tested). Implemented on every step path (per-pair, shared
    # pool, both CBOW formulations, both sharded lowerings).
    max_row_norm: float = 0.0       # > 0: per-TOUCHED-row L2 clamp applied on
                                    # the update path after each step's
                                    # scatter (touched rows only — NEVER a
                                    # dense [V, D] renorm pass; ops/sgns.py
                                    # stabilize_rows). The direct counter to
                                    # the measured finite blowup: healthy
                                    # trained rows sit at norm ~1-15 across
                                    # every EVAL_RUNS config, the round-5
                                    # collapse runs orders of magnitude past
                                    # 100 — a clamp anywhere in [15, 100]
                                    # bounds the channel without touching
                                    # healthy geometry. norm_watch="recover"
                                    # engages this at norm_watch_threshold
                                    # when it was off
    update_clip: float = 0.0        # > 0: per-row L2 ceiling on each pair's/
                                    # example's update contribution (the
                                    # d_in/d_pos SGNS rows, d_hidden/d_out
                                    # CBOW rows), applied before the scatter-
                                    # add. Pool-row deltas are deliberately
                                    # exempt — under shard_map each data
                                    # shard holds only a partial pool delta,
                                    # so clipping there would make the
                                    # lowerings drift; pool rows are bounded
                                    # by the n/P reweight + max_row_norm
                                    # (ops/sgns.py Stabilizers)
    row_l2: float = 0.0             # > 0: L2 weight decay on touched rows —
                                    # each touched row scales by
                                    # (1 − alpha·row_l2) once per step
                                    # regardless of in-batch multiplicity.
                                    # Decay pressure scales with how often a
                                    # row trains, exactly matching the hot-
                                    # row mechanism of the blowup channel
    recover_lr_backoff: float = 0.5  # norm_watch="recover": multiply the
                                    # effective learning rate by this factor
                                    # at each recovery (compounding across
                                    # recoveries; applied to the dispatched
                                    # alphas, so no step recompile). Lowering
                                    # lr is the third measured mitigation in
                                    # the watchdog diagnostic
    max_recoveries: int = 4         # norm_watch="recover": recovery budget
                                    # per fit(); exhaustion degrades to the
                                    # "halt" contract (NormBlowupError with
                                    # the full diagnostic) — a run that keeps
                                    # blowing through recoveries needs a
                                    # config change, not infinite retries
    profile_steps: int = 0          # with profile_dir set: stop the jax.profiler
                                    # trace once this many steps complete after
                                    # fit() starts (0 = trace the whole fit, the
                                    # pre-round-11 behavior). A bounded window
                                    # keeps pod traces loadable — whole-fit
                                    # traces at production step counts are
                                    # multi-GB
    status_port: int = 0            # > 0: serve a read-only live-inspection
                                    # HTTP endpoint on 127.0.0.1:<port> for
                                    # the duration of each fit
                                    # (obs/statusd.py): /status.json (the
                                    # gauge snapshot as JSON), /metrics
                                    # (Prometheus text format, glint_*
                                    # gauges), /healthz. 0 (default) = off
                                    # with ZERO cost — no thread is created
                                    # and no socket bound (tested). The
                                    # endpoint only READS trainer state; it
                                    # can never interleave device work into
                                    # the dispatch pipeline
    blackbox_ring: int = 256        # flight-recorder capacity (obs/
                                    # blackbox.py): how many per-dispatch
                                    # metadata records the in-memory ring
                                    # holds (recent heartbeats and watchdog/
                                    # recovery events keep a quarter of this
                                    # each). The ring dumps atomically to
                                    # <telemetry_path>.blackbox.json when a
                                    # fit dies (exception, NormBlowupError,
                                    # SIGTERM), so a remote death leaves a
                                    # diagnosis artifact instead of a
                                    # truncated JSONL. Only active when
                                    # telemetry_path is set (the dump path
                                    # derives from it)

    # --- preemption + training supervisor (docs/robustness.md §supervisor;
    # train/supervisor.py, tools/train_run.py). checkpoint_on_preempt /
    # preempt_deadline_s / peer_beacon_s are read by the trainer's SIGNAL
    # and round-bookkeeping paths only (host-side, after the dispatch is
    # staged); the supervisor_* knobs are read by the SUPERVISOR process,
    # never by the trainer — all dispatch-inert ---
    checkpoint_on_preempt: bool = False  # True: a SIGTERM during fit() no
                                    # longer kills the run on the spot —
                                    # the handler records a deadline and
                                    # the trainer finishes the in-flight
                                    # dispatch, drains the carry, runs the
                                    # nonfinite/norm guard, and writes an
                                    # EMERGENCY checkpoint through the
                                    # normal digest-verified atomic save
                                    # path, then exits resumable (run_end
                                    # status "preempted", rc = -SIGTERM).
                                    # Past the deadline (or if the guard
                                    # refuses the carry) it degrades to
                                    # the blackbox-only dump — never a
                                    # torn or unverified save. False
                                    # (default): dump-and-die, the
                                    # pre-supervisor behavior
    preempt_deadline_s: float = 30.0  # budget between the first SIGTERM
                                    # and the forced exit: the emergency
                                    # save only STARTS while inside it
                                    # (a TPU preemption sends SIGKILL
                                    # ~30s after the warning; k8s default
                                    # grace is 30s)
    peer_beacon_s: float = 0.0      # > 0 (multi-process sharded fits):
                                    # each process touches a liveness
                                    # beacon beside the checkpoint dir
                                    # this often and checks its peers'
                                    # before each allgather round. A peer
                                    # stale past 6x this aborts the fit
                                    # cleanly (PeerDeathError) instead of
                                    # hanging in the collective rendezvous
                                    # forever; a process WEDGED inside the
                                    # collective hard-exits (rc 43) from
                                    # the beacon watcher thread at 12x.
                                    # 0 (default) = off, zero cost
    supervisor_stall_s: float = 300.0  # hang watchdog: no step advance
                                    # observed (telemetry tail /
                                    # status.json) within this many
                                    # seconds => the supervisor captures a
                                    # diagnostic (SIGTERM = blackbox dump,
                                    # then SIGKILL), counts a failure, and
                                    # resumes from the last valid
                                    # checkpoint
    supervisor_max_restarts: int = 8  # total restart budget per
                                    # TrainingSupervisor.run(); exhaustion
                                    # halts with a machine-readable
                                    # verdict — never an unbounded
                                    # restart loop
    supervisor_loop_window: int = 3  # crash-loop quarantine rule: this
                                    # many CONSECUTIVE failures with the
                                    # same signature (exception/signal
                                    # type + same step, +- one dispatch
                                    # chunk) classify a deterministic
                                    # crash-loop — escalate per the
                                    # documented ladder (engage
                                    # stabilizers / lr backoff, then halt
                                    # quarantined) instead of restarting
                                    # forever

    # --- serving tier (docs/serving.md; serve/ — read by the SERVING
    # process, never by the trainer: dispatch-inert by construction. The
    # knobs travel with the checkpoint like every other field, so a
    # deployment's serving geometry is pinned beside the model it serves;
    # EmbeddingService constructor arguments override per process) ---
    serve_max_batch: int = 64       # micro-batcher coalescing cap: concurrent
                                    # queries batch up to this many per device
                                    # dispatch (the 13-16 ms batched path vs
                                    # 230-375 ms per-query, PERF.md §6)
    serve_max_delay_ms: float = 2.0  # batching deadline: a batch dispatches at
                                    # most this long after its FIRST request
                                    # arrived (bounds added latency; 0 =
                                    # dispatch immediately, batch only what is
                                    # already queued)
    serve_queue_depth: int = 256    # bounded admission queue; a full queue
                                    # refuses new requests FAST
                                    # (ServerOverloaded, the 429 analog) —
                                    # never unbounded buffering into latency
                                    # collapse
    serve_ann_centroids: int = 0    # IVF coarse cells. 0 = AUTO ~4·sqrt(V)
                                    # (serve/ann.py auto_centroids: clamped so
                                    # cells average >= 8 rows, ceiling 4096)
    serve_ann_nprobe: int = 0       # cells probed per query. 0 = AUTO
                                    # ~centroids/12 (~8% of the vocabulary
                                    # scanned — the measured recall >= 0.95
                                    # operating point on clustered embedding
                                    # geometry, tools/servebench.py)
    serve_ann_quant: str = "f32"    # index storage arm (docs/serving.md §6):
                                    # "f32" one normalized float copy (exact
                                    # scores), "int8" per-row-scaled int8
                                    # codes (~4x smaller, bandwidth-bound
                                    # scan speedup), "pq" product-quantized
                                    # codes + ADC scan (~16-32x smaller,
                                    # exact re-rank restores recall)
    serve_ann_pq_m: int = 0         # PQ subspaces (x256 centroids each).
                                    # 0 = AUTO ~D/8 (serve/quant.py
                                    # auto_pq_m; pq arm only)
    serve_ann_rerank: int = 0       # exact-re-rank shortlist for quantized
                                    # arms: top-N by quantized score re-
                                    # scored against lazily fetched float
                                    # rows. 0 = AUTO (pq: max(100, 40k),
                                    # int8: max(32, 4k)), -1 = off
                                    # (forfeits the recall floor)
    serve_ann_recall_floor: float = -1.0  # measured-recall@10 refusal gate
                                    # per build: below floor raises
                                    # RecallFloorError instead of serving a
                                    # silently degraded index. -1 = AUTO
                                    # (documented per-arm floors: int8 0.99,
                                    # pq 0.95, f32 ungated), 0 = disabled
    serve_ann_max_densify_bytes: int = 8 << 30  # refuse an in-memory index
                                    # build whose dense normalized [V, D]
                                    # f32 copy exceeds this many bytes —
                                    # the error names the shard-native
                                    # build as the migration. 0 = unlimited
    serve_reload_poll_s: float = 0.5  # hot-reload watcher poll cadence over
                                    # the checkpoint publish signal
                                    # (metadata.json identity; serve/reload.py)
    # --- serving fleet (docs/serving.md §5; serve/fleet.py — read by the
    # fleet ROUTER process (FleetRouter / tools/fleet_run.py), never by the
    # trainer or a single replica: dispatch-inert by construction, same
    # contract as the serve_* tier above. The knobs travel with the
    # checkpoint so a deployment's fleet policy is pinned beside the model
    # it serves; FleetRouter constructor arguments override per process. ---
    serve_fleet_replicas: int = 3   # replica processes behind the router;
                                    # the rolling-reload capacity floor is
                                    # N-1, so N >= 2 is where the fleet
                                    # starts buying anything (N = 1 is the
                                    # single-service deployment with router
                                    # overhead — allowed, benched as the
                                    # baseline arm in servebench --fleet)
    serve_fleet_probe_s: float = 0.5  # health-probe cadence: the router's
                                    # prober sends each replica a cheap
                                    # stats op this often (liveness +
                                    # publish-generation staleness; an
                                    # OPEN breaker's half-open trial rides
                                    # the same tick, so recovery costs
                                    # zero client queries)
    serve_fleet_breaker_failures: int = 3  # consecutive failures/timeouts
                                    # that open a replica's circuit
                                    # breaker (closed -> open); client
                                    # traffic routes only to CLOSED
                                    # breakers
    serve_fleet_breaker_reset_s: float = 2.0  # open-breaker cooldown before
                                    # the half-open trial probe; trial
                                    # success closes the breaker, failure
                                    # reopens it and re-arms the cooldown
    serve_fleet_hedge_ms: float = -1.0  # tail-latency hedging delay: a
                                    # single query unanswered past this
                                    # many ms goes to a SECOND replica,
                                    # first response wins (the loser's
                                    # reply is discarded). -1 (default) =
                                    # AUTO: derive from the router's own
                                    # measured p99 (re-derived every 64
                                    # samples, floored at 2 ms — hedges
                                    # stay rare by construction). 0 = off.
                                    # Cheap because the CIKM'16 discipline
                                    # keeps per-request payloads tiny
                                    # (PAPER.md §0)
    serve_fleet_retry_deadline_s: float = 10.0  # per-request retry budget:
                                    # failed attempts retry on OTHER
                                    # replicas (decorrelated-jitter
                                    # backoff once all were tried) until
                                    # this deadline, then the request
                                    # fails with NoHealthyReplicas.
                                    # ServerOverloaded replies don't burn
                                    # backoff — they mark the replica
                                    # saturated and move on immediately

    # --- continual training (docs/continual.md; continual/ — read by the
    # continual DRIVER (ContinualRunner / tools/continual_run.py), never by
    # trainer construction or dispatch: dispatch-inert by construction, like
    # the serve_* tier. The knobs travel with the checkpoint so a
    # deployment's increment policy is pinned beside the model it grows.) ---
    continual_min_new_words: int = 1  # vocab-extension trigger: grow
                                    # syn0/syn1 only when at least this many
                                    # NEW words pass min_count in the corpus
                                    # tail; below it the increment trains
                                    # under the existing vocabulary (counts
                                    # still merge, alias table still rebuilt)
    continual_lr_rewarm: float = 1.0  # learning-rate re-warm per increment:
                                    # each incremental fit starts at
                                    # learning_rate * this and decays over
                                    # the increment's own word clock (the
                                    # reference decays alpha over ONE corpus
                                    # pass; a continual deployment needs the
                                    # clock re-armed per increment). Applied
                                    # through the trainer's dispatch-time lr
                                    # scale (the recovery ladder's staging
                                    # point), NEVER by rewriting
                                    # learning_rate — the config persists
                                    # into every publish, and a rewritten lr
                                    # would compound to rewarm^k after k
                                    # increments
    continual_iterations: int = 1   # epochs per incremental fit over the
                                    # new corpus tail (+ replay segments)
    continual_replay_segments: int = 0  # how many of the most recent
                                    # already-consumed segments to re-train
                                    # alongside each new tail — the
                                    # forgetting mitigation (the
                                    # eval_quality --continual-ab gate
                                    # measures what 0 costs); replayed
                                    # segments reuse their cached encodes
    continual_poll_s: float = 2.0   # driver poll cadence over the
                                    # append-only corpus directory between
                                    # increments (continual/loop.py)

    def __post_init__(self) -> None:
        if self.embedding_partition not in ("rows", "cols"):
            raise ValueError(
                f"embedding_partition must be 'rows' or 'cols', "
                f"got {self.embedding_partition!r}")
        if self.vector_size <= 0:
            raise ValueError(f"vector_size must be positive but got {self.vector_size}")
        if self.learning_rate <= 0:
            raise ValueError(f"learning_rate must be positive but got {self.learning_rate}")
        if self.num_partitions <= 0:
            raise ValueError(f"num_partitions must be positive but got {self.num_partitions}")
        if self.num_iterations < 0:
            raise ValueError(
                f"num_iterations must be nonnegative but got {self.num_iterations}")
        if self.min_count < 0:
            raise ValueError(f"min_count must be nonnegative but got {self.min_count}")
        if self.max_sentence_length <= 0:
            raise ValueError(
                f"max_sentence_length must be positive but got {self.max_sentence_length}")
        if self.window <= 0:
            raise ValueError(f"window must be positive but got {self.window}")
        if self.window > 127:
            # CBOW context counts ship as uint8 (2*window slots) and the reference
            # caps useful windows far below this anyway (default 5, mllib:251)
            raise ValueError(f"window must be <= 127 but got {self.window}")
        if self.batch_size <= 0:
            raise ValueError(f"batch_size must be positive but got {self.batch_size}")
        if self.loss not in ("ns", "hs"):
            raise ValueError(f"loss must be 'ns' or 'hs' but got {self.loss!r}")
        if self.loss == "hs":
            self._refuse_beside_hs()
        elif self.negatives <= 0:
            raise ValueError(f"negatives must be positive but got {self.negatives}")
        # remembered so the Trainer may auto-lower an AUTO ratio into the measured
        # stability region (explicit values are refused instead, see trainer.py)
        self._auto_subsample = self.subsample_ratio == -1.0
        if self._auto_subsample:
            self.subsample_ratio = 1e-3
        if not (0 <= self.subsample_ratio <= 1):
            raise ValueError(
                f"subsample_ratio must be in [0, 1] (or -1 for auto) "
                f"but got {self.subsample_ratio}")
        if self.unigram_table_size <= 0:
            raise ValueError(
                f"unigram_table_size must be positive but got {self.unigram_table_size}")
        if self.pairs_per_batch <= 0:
            raise ValueError(
                f"pairs_per_batch must be positive but got {self.pairs_per_batch}")
        if self.sigmoid_mode not in ("exact", "clipped"):
            raise ValueError(
                f"sigmoid_mode must be 'exact' or 'clipped' but got {self.sigmoid_mode!r}")
        if self.num_model_shards <= 0:
            raise ValueError(
                f"num_model_shards must be positive but got {self.num_model_shards}")
        # --- CBOW update-path selection matrix (trainer._build_step has the
        # dispatch-side twin of this table). Every unsupported combination is
        # an ERROR here, not a silent fallback:
        #   banded  × duplicate_scaling → refuse (mean semantics are
        #       per-materialized-context-set; only the scatter path has them)
        #   banded  × cbow=False        → refuse (knob is meaningless)
        #   banded  × negative_pool=0   → refuse (banded is built on the
        #       shared-pool estimator; per-example pools would re-create the
        #       [B, n, D] row traffic the path exists to remove)
        #   banded  × tokens_per_step   → refuse (banded derives its block size
        #       from pairs_per_batch + window; the knob is device_pairgen's)
        #   banded  × window=1          → refuse (legacy window b=nextInt(1)=0
        #       yields no contexts at all — same rule as device_pairgen)
        #   scatter × duplicate_scaling → per-example negatives
        #       (explicit negative_pool>0 alongside it is refused below;
        #       an AUTO pool resolves to 0)
        if self.cbow_update not in ("scatter", "banded"):
            raise ValueError(
                f"cbow_update must be 'scatter' or 'banded' "
                f"but got {self.cbow_update!r}")
        if self.cbow_update == "banded":
            if not self.cbow:
                raise ValueError(
                    "cbow_update='banded' requires cbow=True — the knob "
                    "selects the CBOW step formulation")
            if self.duplicate_scaling:
                raise ValueError(
                    "cbow_update='banded' does not support "
                    "duplicate_scaling=True: mean-update semantics are only "
                    "implemented on the scatter path (its per-context-set "
                    "occurrence counts have no banded form) — use "
                    "cbow_update='scatter'")
            if self.negative_pool == 0:
                raise ValueError(
                    "cbow_update='banded' requires the shared-pool estimator "
                    "(negative_pool > 0, or -1 for auto); per-example "
                    "negatives (negative_pool=0) are scatter-path only")
            if self.tokens_per_step:
                raise ValueError(
                    "cbow_update='banded' derives its token-block size from "
                    "pairs_per_batch + window; tokens_per_step is the "
                    "device_pairgen knob — leave it 0")
            if self.window < 2:
                raise ValueError(
                    "cbow_update='banded' with window=1 emits no contexts at "
                    "all under the reference's legacy asymmetric window "
                    "(b = nextInt(1) = 0 always) — use window >= 2")
        # position weights (select_step's table of options): the banded step
        # alone, with or without subword:
        #   cbow_position_weights × skip-gram / cbow "scatter" → refuse (the
        #       scatter form's [B, 2·window] context sets carry no position)
        #   cbow_position_weights × sharded_checkpoint → refuse (the
        #       row-shards layout has no file for the leaf)
        if self.cbow_position_weights:
            if not (self.cbow and self.cbow_update == "banded"):
                raise ValueError(
                    "cbow_position_weights=True requires cbow=True with "
                    "cbow_update='banded': the scatter form's context sets "
                    "are left-packed and carry no relative position")
            if self.sharded_checkpoint:
                raise ValueError(
                    "cbow_position_weights=True does not support "
                    "sharded_checkpoint=True: the position weights are saved "
                    "beside the dense layout only")
        if (self.cbow and self.duplicate_scaling and self.negative_pool > 0):
            raise ValueError(
                "CBOW with duplicate_scaling=True implements mean semantics "
                "per-example only; an explicit negative_pool > 0 would be "
                "silently ignored — set negative_pool=0 (or -1 for auto, "
                "which resolves to 0 here)")
        # remembered so replace() re-derives the pool when the batch geometry
        # changes (a resolved auto pool must not stick to a new pairs_per_batch)
        self._auto_pool = self.negative_pool == -1
        if self.negative_pool == -1:
            if self.loss == "hs":
                self.negative_pool = 0      # no sampler, no pool
            elif self.cbow and self.duplicate_scaling:
                # mean semantics exist only on the per-example scatter path
                self.negative_pool = 0
            elif (self.pairs_per_batch < 4096
                    and self.cbow_update != "banded"
                    and self.step_lowering != "shard_map"):
                # Small batches take the per-pair exact path (the reference's G3
                # semantics): the shared pool's matmul amortization buys nothing at
                # this scale, and shared negatives measurably cost quality on small
                # corpora (the bf16 toy-corpus gate fails at B=256/P=128 but passes
                # per-pair — tests/test_integration_toy.py). NB the
                # per-pair path always runs its logit chain in f32 (trainer.py);
                # logits_dtype applies to the shared-pool paths.
                self.negative_pool = 0
            else:
                # AUTO: scale the shared pool with the batch so the per-row load
                # stays inside the measured 60M-word stability boundary
                # (load <= 600, EVAL.md), rounded up to the 128-lane MXU tile
                p_min = -(-self.pairs_per_batch * self.negatives // 600)
                self.negative_pool = max(128, 128 * (-(-p_min // 128)))
        if self.negative_pool < 0:
            raise ValueError(
                f"negative_pool must be nonnegative (or -1 for auto) "
                f"but got {self.negative_pool}")
        # --- step-restructuring selection matrix (ISSUE 14 / PERF.md §11;
        # trainer._build_step carries the dispatch-side twins — graftlint R8
        # refusal parity, graftcheck executes the empirical sweep). Every
        # unsupported combination is an ERROR here, never a silent fallback:
        #   fused_logits × cbow                → refuse (SGNS chains only; the
        #       CBOW chain keeps the classic form until its own EVAL evidence)
        #   fused_logits × duplicate_scaling   → refuse (mean semantics read
        #       the per-pair coefficient arrays the fusion eliminates)
        #   bf16_chain   × cbow                → refuse (as above)
        #   bf16_chain   × compute f32         → refuse (no chain to narrow)
        #   bf16_chain   × pool>0 + logits f32 → refuse (the [B, pool] chain
        #       would silently stay f32 — exactly the half-applied state the
        #       _build_step logits warning exists to avoid; per-pair pool=0
        #       has no logits_dtype surface and is exempt)
        if self.fused_logits:
            if self.cbow:
                raise ValueError(
                    "fused_logits=True is implemented for the SGNS logit "
                    "chains only (per-pair and shared-pool); CBOW keeps the "
                    "classic chain — set fused_logits=False")
            if self.duplicate_scaling:
                raise ValueError(
                    "fused_logits=True does not support duplicate_scaling="
                    "True: mean-update semantics read the per-pair "
                    "coefficient arrays the fused chain eliminates — use "
                    "the classic chain")
        if self.bf16_chain:
            if self.cbow:
                raise ValueError(
                    "bf16_chain=True is implemented for the SGNS paths "
                    "only; CBOW keeps the classic chain — set "
                    "bf16_chain=False")
            if self.compute_dtype != "bfloat16":
                raise ValueError(
                    "bf16_chain=True requires compute_dtype='bfloat16' — "
                    "with float32 compute there is no reduced-precision "
                    "chain to carry end-to-end")
            if self.negative_pool != 0 and self.logits_dtype != "bfloat16":
                raise ValueError(
                    "bf16_chain=True with a shared negative pool requires "
                    "logits_dtype='bfloat16': a float32 [B, pool] logit "
                    "chain would silently keep the dense traffic the knob "
                    "exists to remove")
        # --- step_lowering selection matrix (trainer._build_step dispatches on
        # it; every unsupported combination is an ERROR here, never a silent
        # fallback — same discipline as the CBOW matrix above):
        #   shard_map × cbow              → refuse (the explicit schedule is the
        #       shared-pool SGNS step only; CBOW keeps GSPMD)
        #   shard_map × duplicate_scaling → refuse (mean semantics need global
        #       in-batch occurrence counts — a [V]-sized cross-shard psum the
        #       schedule exists to avoid)
        #   shard_map × negative_pool=0   → refuse (per-pair negatives re-create
        #       the [B, n, D] row traffic; the schedule assembles ONE pool)
        #   shard_map × cols              → refuse (owner-local row scatters are
        #       the rows layout's property; cols owns columns, not rows)
        if self.step_lowering not in ("gspmd", "shard_map"):
            raise ValueError(
                f"step_lowering must be 'gspmd' or 'shard_map' "
                f"but got {self.step_lowering!r}")
        if self.step_lowering == "shard_map":
            if self.cbow:
                raise ValueError(
                    "step_lowering='shard_map' is implemented for the "
                    "shared-pool skip-gram step only; CBOW runs under GSPMD "
                    "(step_lowering='gspmd')")
            if self.duplicate_scaling:
                raise ValueError(
                    "step_lowering='shard_map' does not support "
                    "duplicate_scaling=True: mean-update semantics need global "
                    "in-batch occurrence counts, a [V]-sized cross-shard psum "
                    "the explicit schedule exists to avoid — use 'gspmd'")
            if self.negative_pool == 0:
                raise ValueError(
                    "step_lowering='shard_map' requires the shared-pool "
                    "estimator (negative_pool > 0, or -1 for auto at "
                    "pairs_per_batch >= 4096); per-pair negatives "
                    "(negative_pool=0) are GSPMD-path only")
            if self.embedding_partition != "rows":
                raise ValueError(
                    "step_lowering='shard_map' is the rows-layout schedule "
                    "(owner-local row scatters); embedding_partition="
                    f"{self.embedding_partition!r} keeps GSPMD")
        # --- sync_every (local-SGD) selection matrix (docs/sharding.md
        # §Local-SGD; trainer._build_step keeps the dispatch-side twin —
        # graftlint R8 refusal parity):
        #   sync_every>1 × gspmd lowering  → refuse (the owner-local window is
        #       the shard_map schedule's property; GSPMD has no owner-local
        #       k-step form — and with it no CBOW either, since CBOW keeps
        #       GSPMD)
        #   sync_every>1 × device_pairgen  → refuse (the windowed chunk is the
        #       host packed-pair feed; the device generator's token blocks
        #       would need their own window plumbing)
        #   sync_every ∤ steps_per_dispatch → refuse (the window lives inside
        #       the dispatch chunk's scan; a merge must land on every dispatch
        #       boundary so recovery never resurrects an unmerged shard)
        if self.sync_every <= 0:
            raise ValueError(
                f"sync_every must be positive (1 = synchronous) "
                f"but got {self.sync_every}")
        if self.sync_every > 1:
            if self.step_lowering != "shard_map":
                raise ValueError(
                    f"sync_every={self.sync_every} (local-SGD) requires "
                    f"step_lowering='shard_map': the k owner-local steps "
                    f"reuse the explicit schedule's owner-local gather/"
                    f"scatter machinery, which has no GSPMD form (and no "
                    f"CBOW form — CBOW runs under GSPMD); got "
                    f"step_lowering={self.step_lowering!r}")
            if self.device_pairgen:
                raise ValueError(
                    f"sync_every={self.sync_every} (local-SGD) supports the "
                    f"host packed-pair feed only; device_pairgen's token-"
                    f"block chunks have no windowed form")
            if self.steps_per_dispatch % self.sync_every:
                raise ValueError(
                    f"sync_every={self.sync_every} must divide "
                    f"steps_per_dispatch={self.steps_per_dispatch}: the "
                    f"local-SGD window lives inside the dispatch chunk's "
                    f"scan and every chunk ends merged, so the merge cadence "
                    f"cannot exceed or straddle the chunk (snapshot-ring/"
                    f"rollback/preemption saves land on merge boundaries "
                    f"only)")
        # --- device_pairgen selection matrix (graftcheck first-run findings,
        # tools/graftcheck/ — these three refusals lived only in
        # Trainer.__init__, so a config could be constructed/serialized that
        # every Trainer would later reject; same parity discipline as the
        # CBOW/step_lowering matrices above):
        #   device_pairgen × cbow          → refuse (CBOW batches are grouped
        #       windows the device generator does not produce)
        #   device_pairgen × window=1      → refuse (legacy asymmetric window
        #       b = nextInt(1) = 0 emits no pairs at all)
        #   device_pairgen × explicit tokens_per_step × window past the
        #       2^24 exact-f32 prefix-sum bound → refuse (ops/pairgen
        #       _cumsum_i32 exactness; an AUTO tokens_per_step=0 is sized by
        #       the Trainer, which re-checks the derived value at dispatch)
        if self.device_pairgen:
            if self.cbow:
                raise ValueError(
                    "device_pairgen is skip-gram only (CBOW batches are "
                    "grouped windows the device generator does not produce)")
            if self.window == 1:
                raise ValueError(
                    "device_pairgen with window=1 emits no pairs at all "
                    "under the reference's legacy asymmetric window "
                    "(b = nextInt(1) = 0 always, and the right bound is "
                    "exclusive) — use window >= 2")
            if (self.tokens_per_step > 0
                    and self.tokens_per_step * (2 * self.window - 1) >= 1 << 24):
                raise ValueError(
                    f"tokens_per_step={self.tokens_per_step} with window="
                    f"{self.window} overflows the device generator's "
                    f"exact-f32 prefix-sum bound (T * (2*window - 1) must "
                    f"stay below 2^24); lower tokens_per_step or split the "
                    f"batch")
        # --- subword selection matrix (trainer.select_step's docstring has the
        # table of options; Trainer.__init__ keeps the runtime twin for a mesh
        # handed in as a plan). The row source rides the shared-pool skip-gram
        # step (the center's lists) or the banded CBOW step (a token block's,
        # with or without cbow_position_weights), on one device; every other
        # combination is an ERROR here:
        #   subword × cbow "scatter"     → refuse (the [B, 2·window] context
        #       sets of the scatter forms have no list per entry)
        #   subword × negative_pool=0    → refuse (the per-pair step gathers
        #       one row a center; the row source lives in the shared-pool step)
        #   subword × shard_map          → refuse (a list's rows live on other
        #       chips; ops/sgns_shard.py gathers owner-locally one row a center)
        #   subword × mesh > 1x1         → refuse (the same, under GSPMD)
        #   subword × device_pairgen     → refuse (center runs are the host
        #       pair feed's order; the skip-gram token-block chunk has no
        #       table argument)
        #   subword × duplicate_scaling  → refuse (occurrence counts are per
        #       word row; a list's rows have none)
        #   subword × sharded_checkpoint → refuse (bucket rows ride a file of
        #       their own beside the dense layout)
        #   subword × max_row_norm / row_l2 / norm_watch="recover" → refuse
        #       (the touched-row pass walks centers, not their lists' rows;
        #       recover would engage max_row_norm)
        #   subword × loss="hs"          → refused above (_refuse_beside_hs)
        if self.subword:
            if not (0 < self.subword_min_n <= self.subword_max_n):
                raise ValueError(
                    f"subword needs 0 < subword_min_n <= subword_max_n but got "
                    f"{self.subword_min_n}..{self.subword_max_n}")
            if self.subword_buckets <= 0:
                raise ValueError(
                    f"subword_buckets must be positive but got "
                    f"{self.subword_buckets}")
            if self.cbow and self.cbow_update != "banded":
                raise ValueError(
                    "subword=True beside cbow=True needs cbow_update='banded': "
                    "a context token's row list is the banded step's row "
                    "source, and the scatter form's context sets have none")
            if self.negative_pool == 0:
                raise ValueError(
                    "subword=True requires the shared-pool estimator "
                    "(negative_pool > 0, or -1 for auto at pairs_per_batch >= "
                    "4096): the per-pair step has no row source")
            if self.step_lowering == "shard_map":
                raise ValueError(
                    "subword=True does not support step_lowering='shard_map': "
                    "a word's listed rows live on other chips, and the "
                    "explicit schedule gathers one owner-local row a center")
            mesh = self.mesh_shape or (self.num_data_shards,
                                       self.num_model_shards)
            if tuple(mesh) != (1, 1):
                raise ValueError(
                    f"subword=True trains on one device: a {mesh[0]}x{mesh[1]} "
                    f"mesh would spread a word's listed rows over chips (no "
                    f"sharded row source yet)")
            if self.device_pairgen:
                raise ValueError(
                    "subword=True does not support device_pairgen: the row "
                    "source works per center run of the host pair feed")
            if self.duplicate_scaling:
                raise ValueError(
                    "subword=True does not support duplicate_scaling=True: "
                    "mean-update counts are per word row, and a center moves "
                    "every row of its list")
            if self.sharded_checkpoint:
                raise ValueError(
                    "subword=True does not support sharded_checkpoint=True: "
                    "the bucket rows are saved beside the dense layout only")
            if self.max_row_norm or self.row_l2 or self.norm_watch == "recover":
                raise ValueError(
                    "subword=True does not support max_row_norm, row_l2 or "
                    "norm_watch='recover' (which engages max_row_norm): the "
                    "touched-row pass walks center words, not the rows of "
                    "their lists; update_clip and norm_watch='warn'/'halt' "
                    "are available")
        # cols × sharded_checkpoint: row-shards checkpoints need each process
        # to own whole ROWS — the cols layout owns columns (design rationale:
        # PERF.md §7). Trainer.__init__ keeps the runtime twin (cols ×
        # multi-process), which depends on jax.process_count().
        if self.embedding_partition == "cols" and self.sharded_checkpoint:
            raise ValueError(
                "embedding_partition='cols' does not support "
                "sharded_checkpoint=True: row-shards checkpoints need each "
                "process to own whole rows (design rationale: PERF.md §7); "
                "use 'rows'")
        if self.num_data_shards <= 0:
            raise ValueError(
                f"num_data_shards must be positive but got {self.num_data_shards}")
        # dtype strings validated HERE, not first at jnp.dtype() inside
        # _build_step: a typo'd dtype used to construct (and serialize)
        # cleanly and then crash dispatch with a TypeError — the exact
        # construction/dispatch gap class graftcheck's probe executes for
        if self.param_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"param_dtype must be 'float32' or 'bfloat16' "
                f"but got {self.param_dtype!r}")
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"compute_dtype must be 'float32' or 'bfloat16' "
                f"but got {self.compute_dtype!r}")
        if self.logits_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"logits_dtype must be 'float32' or 'bfloat16' "
                f"but got {self.logits_dtype!r}")
        # dispatch-geometry range checks (graftcheck registry audit): these
        # three used to be unvalidated — steps_per_dispatch=0 or
        # heartbeat_every_steps=0 constructed cleanly and died at fit() with
        # a ZeroDivisionError, past every refusal surface
        if self.steps_per_dispatch <= 0:
            raise ValueError(
                f"steps_per_dispatch must be positive "
                f"but got {self.steps_per_dispatch}")
        if self.heartbeat_every_steps <= 0:
            raise ValueError(
                f"heartbeat_every_steps must be positive "
                f"but got {self.heartbeat_every_steps}")
        if self.prefetch_chunks < 0:
            raise ValueError(
                f"prefetch_chunks must be nonnegative (0 = synchronous) "
                f"but got {self.prefetch_chunks}")
        if self.tokens_per_step < 0:
            raise ValueError(
                f"tokens_per_step must be nonnegative but got {self.tokens_per_step}")
        if self.producer_workers < 1:
            raise ValueError(
                f"producer_workers must be >= 1 (1 = serial producer) "
                f"but got {self.producer_workers}")
        if self.io_workers < 1:
            raise ValueError(
                f"io_workers must be >= 1 (1 = serial I/O) "
                f"but got {self.io_workers}")
        if self.nonfinite_policy not in ("halt", "rollback", "none"):
            raise ValueError(
                f"nonfinite_policy must be 'halt', 'rollback', or 'none' "
                f"but got {self.nonfinite_policy!r}")
        if self.rollback_history <= 0:
            raise ValueError(
                f"rollback_history must be positive but got {self.rollback_history}")
        if self.max_rollbacks < 0:
            raise ValueError(
                f"max_rollbacks must be nonnegative but got {self.max_rollbacks}")
        if self.norm_watch not in ("off", "warn", "recover", "halt"):
            raise ValueError(
                f"norm_watch must be 'off', 'warn', 'recover', or 'halt' "
                f"but got {self.norm_watch!r}")
        if self.max_row_norm < 0:
            raise ValueError(
                f"max_row_norm must be nonnegative (0 = off) "
                f"but got {self.max_row_norm}")
        if self.update_clip < 0:
            raise ValueError(
                f"update_clip must be nonnegative (0 = off) "
                f"but got {self.update_clip}")
        if not (0 <= self.row_l2 < 1):
            # (1 − alpha·row_l2) must stay a contraction for any alpha <= 1;
            # realistic decay sits orders of magnitude below 1 anyway
            raise ValueError(
                f"row_l2 must be in [0, 1) (0 = off) but got {self.row_l2}")
        if not (0 < self.recover_lr_backoff <= 1):
            raise ValueError(
                f"recover_lr_backoff must be in (0, 1] "
                f"but got {self.recover_lr_backoff}")
        if self.max_recoveries < 0:
            raise ValueError(
                f"max_recoveries must be nonnegative "
                f"but got {self.max_recoveries}")
        if self.norm_watch_threshold <= 0:
            raise ValueError(
                f"norm_watch_threshold must be positive "
                f"but got {self.norm_watch_threshold}")
        if self.norm_watch_max <= 0:
            raise ValueError(
                f"norm_watch_max must be positive but got {self.norm_watch_max}")
        if not (0 < self.norm_watch_frac <= 1):
            raise ValueError(
                f"norm_watch_frac must be in (0, 1] but got {self.norm_watch_frac}")
        if self.heartbeat_ring <= 0:
            raise ValueError(
                f"heartbeat_ring must be positive but got {self.heartbeat_ring}")
        if self.telemetry_rotate_bytes <= 0:
            raise ValueError(
                f"telemetry_rotate_bytes must be positive "
                f"but got {self.telemetry_rotate_bytes}")
        if self.profile_steps < 0:
            raise ValueError(
                f"profile_steps must be nonnegative but got {self.profile_steps}")
        if not (0 <= self.status_port <= 65535):
            raise ValueError(
                f"status_port must be in [0, 65535] (0 = off) "
                f"but got {self.status_port}")
        if self.blackbox_ring <= 0:
            raise ValueError(
                f"blackbox_ring must be positive but got {self.blackbox_ring}")
        if self.preempt_deadline_s <= 0:
            raise ValueError(
                f"preempt_deadline_s must be positive "
                f"but got {self.preempt_deadline_s}")
        if self.peer_beacon_s < 0:
            raise ValueError(
                f"peer_beacon_s must be nonnegative (0 = off) "
                f"but got {self.peer_beacon_s}")
        if self.supervisor_stall_s <= 0:
            raise ValueError(
                f"supervisor_stall_s must be positive "
                f"but got {self.supervisor_stall_s}")
        if self.supervisor_max_restarts < 0:
            raise ValueError(
                f"supervisor_max_restarts must be nonnegative "
                f"but got {self.supervisor_max_restarts}")
        if self.supervisor_loop_window < 2:
            # 1 would classify every SECOND failure as a deterministic loop
            # (a single repeat proves nothing about determinism)
            raise ValueError(
                f"supervisor_loop_window must be >= 2 "
                f"but got {self.supervisor_loop_window}")
        if self.serve_max_batch <= 0:
            raise ValueError(
                f"serve_max_batch must be positive "
                f"but got {self.serve_max_batch}")
        if self.serve_max_delay_ms < 0:
            raise ValueError(
                f"serve_max_delay_ms must be nonnegative (0 = dispatch "
                f"immediately) but got {self.serve_max_delay_ms}")
        if self.serve_queue_depth <= 0:
            raise ValueError(
                f"serve_queue_depth must be positive "
                f"but got {self.serve_queue_depth}")
        if self.serve_ann_centroids < 0:
            raise ValueError(
                f"serve_ann_centroids must be nonnegative (0 = auto) "
                f"but got {self.serve_ann_centroids}")
        if self.serve_ann_nprobe < 0:
            raise ValueError(
                f"serve_ann_nprobe must be nonnegative (0 = auto) "
                f"but got {self.serve_ann_nprobe}")
        if self.serve_ann_quant not in ("f32", "int8", "pq"):
            raise ValueError(
                f"serve_ann_quant must be one of 'f32', 'int8', 'pq' "
                f"but got {self.serve_ann_quant!r}")
        if self.serve_ann_pq_m < 0:
            raise ValueError(
                f"serve_ann_pq_m must be nonnegative (0 = auto ~D/8) "
                f"but got {self.serve_ann_pq_m}")
        if self.serve_ann_rerank < -1:
            raise ValueError(
                f"serve_ann_rerank must be -1 (off), 0 (auto), or a "
                f"positive shortlist size but got {self.serve_ann_rerank}")
        if not (self.serve_ann_recall_floor == -1.0
                or 0.0 <= self.serve_ann_recall_floor <= 1.0):
            raise ValueError(
                f"serve_ann_recall_floor must be -1 (auto per-arm floor) "
                f"or in [0, 1] (0 = disabled) "
                f"but got {self.serve_ann_recall_floor}")
        if self.serve_ann_max_densify_bytes < 0:
            raise ValueError(
                f"serve_ann_max_densify_bytes must be nonnegative "
                f"(0 = unlimited) but got {self.serve_ann_max_densify_bytes}")
        if self.serve_reload_poll_s <= 0:
            raise ValueError(
                f"serve_reload_poll_s must be positive "
                f"but got {self.serve_reload_poll_s}")
        if self.serve_fleet_replicas <= 0:
            raise ValueError(
                f"serve_fleet_replicas must be positive "
                f"but got {self.serve_fleet_replicas}")
        if self.serve_fleet_probe_s <= 0:
            raise ValueError(
                f"serve_fleet_probe_s must be positive "
                f"but got {self.serve_fleet_probe_s}")
        if self.serve_fleet_breaker_failures <= 0:
            raise ValueError(
                f"serve_fleet_breaker_failures must be positive "
                f"but got {self.serve_fleet_breaker_failures}")
        if self.serve_fleet_breaker_reset_s <= 0:
            raise ValueError(
                f"serve_fleet_breaker_reset_s must be positive "
                f"but got {self.serve_fleet_breaker_reset_s}")
        if self.serve_fleet_hedge_ms < 0 and self.serve_fleet_hedge_ms != -1.0:
            raise ValueError(
                f"serve_fleet_hedge_ms must be -1 (auto: p99-derived), "
                f"0 (off), or a positive delay in ms "
                f"but got {self.serve_fleet_hedge_ms}")
        if self.serve_fleet_retry_deadline_s <= 0:
            raise ValueError(
                f"serve_fleet_retry_deadline_s must be positive "
                f"but got {self.serve_fleet_retry_deadline_s}")
        if self.continual_min_new_words <= 0:
            # 0 would make every increment a (pointless) zero-growth
            # extension pass; "never grow" is not a policy this knob
            # expresses (drop the driver instead)
            raise ValueError(
                f"continual_min_new_words must be positive "
                f"but got {self.continual_min_new_words}")
        if self.continual_lr_rewarm <= 0:
            raise ValueError(
                f"continual_lr_rewarm must be positive "
                f"but got {self.continual_lr_rewarm}")
        if self.continual_iterations <= 0:
            raise ValueError(
                f"continual_iterations must be positive "
                f"but got {self.continual_iterations}")
        if self.continual_replay_segments < 0:
            raise ValueError(
                f"continual_replay_segments must be nonnegative "
                f"but got {self.continual_replay_segments}")
        if self.continual_poll_s <= 0:
            raise ValueError(
                f"continual_poll_s must be positive "
                f"but got {self.continual_poll_s}")

    def _refuse_beside_hs(self) -> None:
        """The hierarchical-softmax selection matrix (trainer.select_step's
        docstring has the row; Trainer.__init__ keeps the runtime twin for a
        mesh handed in as a plan). The step is skip-gram over the host pair
        feed on one device; every other combination is an ERROR here:

          hs × negatives != 0        → refuse (word2vec.c can run both losses
              at once; this program runs one: no sampler is built)
          hs × negative_pool > 0     → refuse (there is no pool; -1 resolves to 0)
          hs × cbow                  → refuse (the path's logits are dots with
              one center row; the CBOW steps' window sum has no path side)
          hs × subword               → refuse (a list on both sides of a pair:
              the two row sources have not been composed)
          hs × device_pairgen        → refuse (the token-block chunk has no
              table argument, and the paths work on the host feed's pairs)
          hs × shard_map             → refuse (a path's nodes live on other
              chips; ops/sgns_shard.py gathers one owner-local row a context)
          hs × mesh > 1x1            → refuse (the same, under GSPMD)
          hs × duplicate_scaling     → refuse (occurrence counts are per word
              row; ops/hs.py has its own rule for a node many pairs share)
          hs × sharded_checkpoint    → refuse (syn1's rows are nodes: the
              row-shards layout and its loaders know words alone)
          hs × fused_logits / bf16_chain → refuse (restructurings of the
              [B, P] chain, which this step does not have)
          hs × max_row_norm / row_l2 / update_clip / norm_watch="recover" →
              refuse (the touched-row pass walks contexts as syn1 rows, and
              here they are words, not nodes; recover would engage
              max_row_norm)"""
        if self.negatives != 0:
            raise ValueError(
                f"loss='hs' needs negatives=0 but got {self.negatives}: "
                "word2vec.c can train both losses at once, this program "
                "trains one, and no sampler is built beside the tree")
        if self.negative_pool > 0:
            raise ValueError(
                "loss='hs' has no negative pool: leave negative_pool at -1 "
                "(it resolves to 0) or set 0")
        if self.cbow:
            raise ValueError(
                "loss='hs' does not support cbow=True: a path's logits are "
                "dots with one center row, and the CBOW steps have no path side")
        if self.subword:
            raise ValueError(
                "loss='hs' does not support subword=True: a list on both "
                "sides of a pair (n-gram rows in, path nodes out) is not "
                "composed yet")
        if self.device_pairgen:
            raise ValueError(
                "loss='hs' does not support device_pairgen: the path side "
                "works on the host pair feed's batches, and the token-block "
                "chunk takes no table")
        if self.step_lowering == "shard_map":
            raise ValueError(
                "loss='hs' does not support step_lowering='shard_map': a "
                "path's nodes live on other chips, and the explicit schedule "
                "gathers one owner-local row a context")
        mesh = self.mesh_shape or (self.num_data_shards, self.num_model_shards)
        if tuple(mesh) != (1, 1):
            raise ValueError(
                f"loss='hs' trains on one device: a {mesh[0]}x{mesh[1]} mesh "
                f"would spread a path's nodes over chips (no sharded path "
                f"side yet)")
        if self.duplicate_scaling:
            raise ValueError(
                "loss='hs' does not support duplicate_scaling=True: "
                "mean-update counts are per word row; the rule for a node "
                "that many pairs share lives in ops/hs.py")
        if self.sharded_checkpoint:
            raise ValueError(
                "loss='hs' does not support sharded_checkpoint=True: syn1's "
                "rows are the tree's nodes, saved in the dense layout only")
        if self.fused_logits or self.bf16_chain:
            raise ValueError(
                "loss='hs' does not support fused_logits or bf16_chain: they "
                "restructure the [B, P] negative chain, which this step lacks")
        if (self.max_row_norm or self.row_l2 or self.update_clip
                or self.norm_watch == "recover"):
            raise ValueError(
                "loss='hs' does not support max_row_norm, row_l2, update_clip "
                "or norm_watch='recover' (which engages max_row_norm): the "
                "touched-row pass walks contexts as syn1 rows, and syn1's "
                "rows are nodes; norm_watch='warn'/'halt' are available")

    def replace(self, **kwargs) -> "Word2VecConfig":
        if (getattr(self, "_auto_pool", False)
                and "negative_pool" not in kwargs):
            # the pool was auto-derived — re-derive it on the new config
            # instead of freezing the resolved value. Pre-graftcheck this
            # re-derived only when the flipped knob changed the AUTO rule's
            # geometry/path inputs; any OTHER flip (seed, telemetry, ...)
            # froze the resolved pool, which then read as EXPLICIT on the
            # derived config — to_dict(auto_markers=True) stored it, and the
            # Trainer's vocab-scaled re-resolution (load <= 160 past 500k
            # vocab) silently skipped it. Re-resolution is deterministic in
            # the geometry/path knobs, so under an unchanged geometry the
            # value is unchanged too — only the AUTO-ness is (now correctly)
            # preserved. graftcheck property (c) holds replace() to exactly
            # this: equivalent to fresh construction from the auto-marker
            # dict with the flip applied.
            kwargs["negative_pool"] = -1
        if (getattr(self, "_auto_subsample", False)
                and "subsample_ratio" not in kwargs):
            # keep auto-ness: the Trainer's stability auto-lowering must still
            # apply to the derived config (a frozen 1e-3 would read as explicit)
            kwargs["subsample_ratio"] = -1.0
        return dataclasses.replace(self, **kwargs)

    def to_dict(self, auto_markers: bool = True) -> dict:
        d = dataclasses.asdict(self)
        if auto_markers and getattr(self, "_auto_subsample", False):
            # preserve AUTO-ness across serialization (symmetric with replace()):
            # a pre-resolution config shipped to a worker must auto-lower there,
            # not read as an explicitly chosen 1e-3 and be refused.
            # auto_markers=False (checkpoints) stores the RESOLVED value instead:
            # a trained model's metadata must pin the semantics it trained with,
            # and format-version-1 readers reject a -1.0 sentinel
            d["subsample_ratio"] = -1.0
        if auto_markers and getattr(self, "_auto_pool", False):
            # same rule for the pool: a round-tripped AUTO pool must stay AUTO
            # so the Trainer's vocab-scaled re-resolution (load <= 160 past
            # 500k vocab) still applies on the receiving side — a frozen
            # resolved value would read as explicit and skip the safety rule
            d["negative_pool"] = -1
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Word2VecConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        clean = {k: v for k, v in d.items() if k in fields}
        for key, default in _RETIRED_KEYS.items():
            if d.get(key, default) != default:
                logger.warning(
                    "stored config sets %s=%r, a step variant this version "
                    "no longer has; the run continues on the XLA shared-pool "
                    "step", key, d[key])
        if "mesh_shape" in clean and clean["mesh_shape"] is not None:
            clean["mesh_shape"] = tuple(clean["mesh_shape"])
        if (clean.get("cbow") and clean.get("duplicate_scaling")
                # > 0: only RESOLVED stored pools need the normalization; a
                # -1 AUTO marker (to_dict round-trip) resolves itself to 0
                # beside cbow+duplicate_scaling and must stay AUTO
                and clean.get("negative_pool", 0) > 0
                and clean.get("cbow_update", "scatter") == "scatter"):
            # pre-selection-matrix checkpoints stored a resolved auto pool next
            # to cbow+duplicate_scaling; the old trainer IGNORED that pool
            # (warn-only, per-example negatives), so normalizing to 0 preserves
            # the exact trained semantics — refusing would brick the checkpoint
            clean["negative_pool"] = 0
        return cls(**clean)
