"""The R1–R8 repo-specific rules. Each encodes one documented invariant and
names the document/PR that established it — the catalogue with examples is
docs/static-analysis.md.

| id | invariant | established by |
|----|-----------|----------------|
| R1 | no ad-hoc thread pools in library code (determinism contract)   | PERF.md §10 |
| R2 | counter-hash PRNG only in the library (no random./unseeded np)  | ops/prng.py |
| R3 | no host-sync ops inside jit/shard_map-wrapped functions         | PERF.md §4 |
| R4 | prefix accumulation reachable from params must carry ≥f32 proof | cbow_banded |
| R5 | data-plane reads go through retry_io                            | robustness  |
| R6 | trainer and feed placement only via the staging discipline      | sharding.md |
| R7 | contract tools print exactly one JSON line to stdout            | BASELINE.md |
| R8 | every knob-pair refused at dispatch is refused in config too    | config.py   |
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set

from tools.graftlint.engine import Finding, ModuleContext

_LIB = "glint_word2vec_tpu/"


def _name_of(func: ast.AST) -> str:
    """Dotted text of a call's func node: Name → 'x', Attribute → 'a.b.c'."""
    parts: List[str] = []
    cur = func
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
    return ".".join(reversed(parts))


def _walk_names(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


# ---------------------------------------------------------------------------
# R1 — determinism contract: no ad-hoc thread pools / threads in library code.
# The only blessed owners: pipeline.ordered_pool_map (the ordered-merge pool
# primitive every parallel host path routes through) and the feeds' documented
# producer/stager iterator (train/feeds.py). Anything else re-introduces the
# unordered-merge nondeterminism PERF.md §10 paid to remove.
# ---------------------------------------------------------------------------
class R1ThreadPools:
    id = "R1"
    _POOLS = {"ThreadPoolExecutor", "ProcessPoolExecutor", "Pool"}
    _ALLOW = {
        ("glint_word2vec_tpu/data/pipeline.py", "ordered_pool_map"),
        # the feeds' producer thread; _one_ahead_iter, the round stager, is
        # the same thread under an ack ticket (a subclass: no Thread of its own)
        ("glint_word2vec_tpu/train/feeds.py", "_threaded_iter.__init__"),
        # the status endpoint's serving thread (obs/statusd.py): READ-only —
        # it renders snapshots of trainer state and never produces or orders
        # training data, so the worker-count determinism contract R1 guards
        # is untouched (docs/observability.md)
        ("glint_word2vec_tpu/obs/statusd.py", "StatusServer.start"),
        # the serving tier's documented owners (docs/serving.md): the
        # micro-batcher's worker and its completer, both started by
        # BatchingScheduler.start (the worker closes a batch and runs the
        # handler's first half, the completer runs the second and releases
        # the callers, in the order the batches were closed), order
        # request/response PAIRING only (each caller gets exactly its own
        # result; batch composition is timing-dependent by design), and the
        # hot-reload watcher stats a file + invokes the swap callback — all
        # READ-only on params, the training determinism contract untouched
        ("glint_word2vec_tpu/serve/batcher.py", "BatchingScheduler.start"),
        ("glint_word2vec_tpu/serve/reload.py", "CheckpointWatcher.start"),
        # the serving FLEET's two documented owners (docs/serving.md §5,
        # ISSUE 12): each SubprocessReplica runs one stdout READER thread
        # (pairs wire responses to tickets by id — read-only on
        # everything, orders nothing), and the router runs ONE
        # prober/orchestrator thread (health probes, breaker trials,
        # dead-replica restarts, rolling reloads — read-only on model
        # params; hedging is ticket-based and spawns NO threads). Neither
        # produces or orders training data, so the worker-count
        # determinism contract R1 guards is untouched
        ("glint_word2vec_tpu/serve/fleet.py", "SubprocessReplica.start"),
        ("glint_word2vec_tpu/serve/fleet.py", "FleetRouter.__init__"),
        # the peer-liveness BEACON writer (docs/robustness.md §supervisor,
        # ISSUE 16): one daemon thread per sharded-fit process touching a
        # liveness file every peer_beacon_s and watchdogging the main
        # thread — touches no training data, orders nothing; it exists
        # precisely for when the main thread is wedged in a dead peer's
        # collective and nothing deterministic can run at all
        ("glint_word2vec_tpu/train/supervisor.py", "BeaconBoard.start"),
    }

    def applies(self, path: str) -> bool:
        return path.startswith(_LIB)

    def check(self, ctx: ModuleContext) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _name_of(node.func)
            tail = name.rsplit(".", 1)[-1]
            is_pool = tail in self._POOLS
            is_thread = name in ("threading.Thread", "Thread")
            if not (is_pool or is_thread):
                continue
            qn = ctx.qualname(node)
            if any(ctx.path == p and (qn == q or qn.endswith("." + q))
                   for p, q in self._ALLOW):
                continue
            kind = "thread pool" if is_pool else "thread"
            out.append(Finding(
                rule=self.id, path=ctx.path, line=node.lineno,
                col=node.col_offset,
                message=f"ad-hoc {kind} creation ({name}) in library code — "
                        f"route through pipeline.ordered_pool_map (the "
                        f"ordered-merge determinism contract, PERF.md §10) "
                        f"or allowlist a documented owner"))
        return out


# ---------------------------------------------------------------------------
# R2 — PRNG discipline: the library draws randomness from the counter-hash
# PRNG (ops/prng.py, position-keyed) or an explicitly seeded
# np.random.Generator. Stdlib `random` and unseeded np.random module calls
# make streams depend on process state — the exact reference bug
# (XORShift-seeded async chaos) this repo was built to remove.
# ---------------------------------------------------------------------------
class R2Prng:
    id = "R2"
    _NP_OK = {"default_rng", "SeedSequence", "Generator", "BitGenerator",
              "PCG64", "Philox"}

    def applies(self, path: str) -> bool:
        return path.startswith(_LIB)

    def check(self, ctx: ModuleContext) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "random":
                        out.append(Finding(
                            rule=self.id, path=ctx.path, line=node.lineno,
                            col=node.col_offset,
                            message="stdlib `random` import in library code "
                                    "— use the counter-hash PRNG "
                                    "(ops/prng.py) or a seeded "
                                    "np.random.Generator"))
            elif isinstance(node, ast.ImportFrom):
                if node.module == "random":
                    out.append(Finding(
                        rule=self.id, path=ctx.path, line=node.lineno,
                        col=node.col_offset,
                        message="stdlib `random` import in library code — "
                                "counter-hash PRNG only"))
            elif isinstance(node, ast.Call):
                name = _name_of(node.func)
                if (name.startswith(("np.random.", "numpy.random."))
                        and name.rsplit(".", 1)[-1] not in self._NP_OK):
                    out.append(Finding(
                        rule=self.id, path=ctx.path, line=node.lineno,
                        col=node.col_offset,
                        message=f"unseeded module-level numpy RNG ({name}) — "
                                f"draw from an explicit "
                                f"np.random.default_rng(seed) Generator or "
                                f"the counter-hash PRNG"))
        return out


def _jit_wrapped_functions(ctx: ModuleContext):
    """FunctionDef/Lambda nodes that are jit/shard_map targets: decorated
    (`@jax.jit`, `@partial(jax.jit, ...)`), or passed by name/inline to a
    `jax.jit(...)` / `jit(...)` / `shard_map(...)` call in this module —
    PLUS the transitive closure of same-module helpers they call by name
    (ISSUE 8 satellite: obs/probe.py's `_matrix_stats` runs inside the
    jitted fused probe but is not itself a jit target, so the pre-closure
    rule never walked it)."""
    wrapper_names = ("jit", "shard_map")

    def is_wrapper(call: ast.Call) -> bool:
        tail = _name_of(call.func).rsplit(".", 1)[-1]
        return tail in wrapper_names

    wrapped_names: Set[str] = set()
    inline: List[ast.AST] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and is_wrapper(node) and node.args:
            target = node.args[0]
            if isinstance(target, ast.Name):
                wrapped_names.add(target.id)
            elif isinstance(target, (ast.Lambda,)):
                inline.append(target)
    out: List[ast.AST] = list(inline)
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name in wrapped_names:
                out.append(node)
                continue
            for dec in node.decorator_list:
                txt = ast.unparse(dec)
                if "jit" in txt.split("(")[0].split(".") or (
                        isinstance(dec, ast.Call) and any(
                            isinstance(a, (ast.Name, ast.Attribute))
                            and _name_of(a).rsplit(".", 1)[-1] == "jit"
                            for a in dec.args)):
                    out.append(node)
                    break
    # transitive closure over same-module helpers called by simple name from
    # any wrapped function (nested defs are already inside ast.walk(fn); this
    # adds the module-level/sibling helpers a trace reaches). Cross-module
    # calls stay out of scope — each module is linted on its own. Class
    # METHODS are excluded from the name map: a bare-name call cannot reach
    # them (they need an instance), and a host-only method sharing a helper's
    # name would otherwise be linted as jit context (false positives).
    defs_by_name: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                not isinstance(ctx.parents.get(node), ast.ClassDef):
            defs_by_name.setdefault(node.name, []).append(node)
    seen = set(id(fn) for fn in out)
    frontier = list(out)
    while frontier:
        fn = frontier.pop()
        for call in ast.walk(fn):
            if not (isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Name)):
                continue
            for helper in defs_by_name.get(call.func.id, []):
                if id(helper) not in seen:
                    seen.add(id(helper))
                    out.append(helper)
                    frontier.append(helper)
    return out


# ---------------------------------------------------------------------------
# R3 — tracer discipline: float()/.item()/np.asarray()/time.* inside a
# jit/shard_map-wrapped function either crashes at trace time (tracer
# concretization) or, worse, silently constant-folds host state into the
# compiled program. Caught statically so it fails review, not a TPU session.
# ---------------------------------------------------------------------------
class R3TracerDiscipline:
    id = "R3"
    _BAD_CALLS = {"float", "int", "bool"}
    _BAD_ATTRS = {"np.asarray", "numpy.asarray", "np.array", "numpy.array"}

    def applies(self, path: str) -> bool:
        return path.startswith(_LIB)

    def check(self, ctx: ModuleContext) -> List[Finding]:
        out: List[Finding] = []
        for fn in _jit_wrapped_functions(ctx):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = _name_of(node.func)
                bad = None
                if name in self._BAD_CALLS and node.args and not isinstance(
                        node.args[0], ast.Constant):
                    bad = f"{name}() concretizes its argument"
                elif name in self._BAD_ATTRS:
                    bad = f"{name}() forces a device→host copy"
                elif name.endswith(".item") and isinstance(
                        node.func, ast.Attribute):
                    bad = ".item() forces a device→host sync"
                elif name.startswith("time.") or name == "perf_counter":
                    bad = (f"{name}() reads the host clock at TRACE time — "
                           f"it becomes a compile-time constant")
                if bad:
                    out.append(Finding(
                        rule=self.id, path=ctx.path, line=node.lineno,
                        col=node.col_offset,
                        message=f"host-sync op inside a jit/shard_map-wrapped "
                                f"function: {bad}"))
        return out


# ---------------------------------------------------------------------------
# R4 — dtype discipline for prefix accumulation: a cumsum/segment-sum chain
# fed from bf16 params cancels away the very interval it computes
# (ops/cbow_banded.py module docstring has the numerics). Every
# prefix-accumulation call in the library must carry STATIC evidence of a
# ≥f32 (or integer) accumulation dtype in its argument's def-use chain.
# ---------------------------------------------------------------------------
class R4PrefixDtype:
    id = "R4"
    _TARGET_TAILS = {"cumsum", "cumsum_rows", "segment_sum",
                     "associative_scan", "cummax", "cumlogsumexp"}
    _HOST_PREFIXES = ("np.", "numpy.")  # host numpy accumulates in f64/int
    _MARKERS = ("float32", "float64", "int32", "int64", "uint32", "uint64",
                "promote_types", "f32", "f64")

    def applies(self, path: str) -> bool:
        return path.startswith(_LIB)

    def _has_marker(self, node: ast.AST, assigns: Dict[str, ast.AST],
                    depth: int = 0) -> bool:
        if depth > 4:
            return False
        txt = ast.unparse(node)
        if any(m in txt for m in self._MARKERS):
            return True
        for name in _walk_names(node):
            rhs = assigns.get(name)
            if rhs is not None and self._has_marker(
                    rhs, {k: v for k, v in assigns.items() if k != name},
                    depth + 1):
                return True
        return False

    def check(self, ctx: ModuleContext) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _name_of(node.func)
            if name.rsplit(".", 1)[-1] not in self._TARGET_TAILS:
                continue
            if name.startswith(self._HOST_PREFIXES):
                continue
            fn = ctx.enclosing_function(node)
            assigns: Dict[str, ast.AST] = {}
            if fn is not None and not isinstance(fn, ast.Lambda):
                for stmt in ast.walk(fn):
                    if isinstance(stmt, ast.Assign) and len(
                            stmt.targets) == 1 and isinstance(
                            stmt.targets[0], ast.Name):
                        assigns[stmt.targets[0].id] = stmt.value
            args_ok = node.args and all(
                self._has_marker(a, assigns) for a in node.args[:1])
            if not args_ok:
                out.append(Finding(
                    rule=self.id, path=ctx.path, line=node.lineno,
                    col=node.col_offset,
                    message=f"prefix accumulation ({name}) without static "
                            f"≥f32/int dtype evidence on its input — a bf16 "
                            f"prefix cancels the interval "
                            f"(ops/cbow_banded.py); add an explicit "
                            f".astype(...) upcast or suppress with the "
                            f"reasoning"))
        return out


def _retry_protected(ctx: ModuleContext, node: ast.AST) -> bool:
    """True if `node` is lexically inside (a) the argument subtree of a
    retry_io(...) call, or (b) a def/lambda whose NAME is passed to
    retry_io(...) anywhere in this module."""
    retry_calls = [n for n in ast.walk(ctx.tree)
                   if isinstance(n, ast.Call)
                   and _name_of(n.func).rsplit(".", 1)[-1] == "retry_io"]
    retried_names: Set[str] = set()
    for call in retry_calls:
        for arg in call.args:
            if isinstance(arg, ast.Name):
                retried_names.add(arg.id)
            for sub in ast.walk(arg):
                if sub is node:
                    return True
    cur = node
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                cur.name in retried_names:
            return True
        cur = ctx.parents.get(cur)
    return False


# ---------------------------------------------------------------------------
# R5 — robust ingest: data-plane READS (open/np.memmap in data/) go through
# train.faults.retry_io so a transient FS hiccup retries with backoff instead
# of killing an hours-long run (docs/robustness.md). Writes are exempt: the
# one-shot encode passes must NOT retry (a blind re-run would silently
# truncate — the PR-1 review finding), and they restart-from-scratch instead.
# ---------------------------------------------------------------------------
class R5RetryIO:
    id = "R5"

    def applies(self, path: str) -> bool:
        return path.startswith(_LIB + "data/")

    def check(self, ctx: ModuleContext) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _name_of(node.func)
            if name == "open":
                mode = "r"
                if len(node.args) >= 2 and isinstance(
                        node.args[1], ast.Constant):
                    mode = str(node.args[1].value)
                for kw in node.keywords:
                    if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                        mode = str(kw.value.value)
                if not mode.startswith("r"):
                    continue  # write passes restart from scratch by design
            elif name.rsplit(".", 1)[-1] not in ("memmap", "fromfile"):
                continue
            if _retry_protected(ctx, node):
                continue
            out.append(Finding(
                rule=self.id, path=ctx.path, line=node.lineno,
                col=node.col_offset,
                message=f"bare data-plane read ({name}) not routed through "
                        f"retry_io — transient FS errors kill long runs "
                        f"(docs/robustness.md); wrap the open/mmap in "
                        f"retry_io(...)"))
        return out


# ---------------------------------------------------------------------------
# R6 — dispatch discipline: the trainer and its feeds (train/feeds.py) place
# host data on device ONLY via put_global / the _stage_to_device staging path,
# so every placement respects
# the collective-program serialization gate (_sync_collectives /
# _after_dispatch — the rendezvous-starvation deadlock, docs/sharding.md) and
# stays an EXPLICIT transfer under the stepaudit transfer contract.
# ---------------------------------------------------------------------------
class R6DispatchDiscipline:
    id = "R6"
    _BAD = {"jax.device_put", "device_put",
            "jax.make_array_from_callback",
            "jax.make_array_from_single_device_arrays"}
    _ALLOW_FNS = {"_stage_to_device"}

    def applies(self, path: str) -> bool:
        return path in (_LIB + "train/trainer.py", _LIB + "train/feeds.py")

    def check(self, ctx: ModuleContext) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if _name_of(node.func) not in self._BAD:
                continue
            qn = ctx.qualname(node)
            if any(qn == a or qn.endswith("." + a) for a in self._ALLOW_FNS):
                continue
            out.append(Finding(
                rule=self.id, path=ctx.path, line=node.lineno,
                col=node.col_offset,
                message="raw device placement in the trainer — use "
                        "put_global/_stage_dispatch_meta (the staging "
                        "discipline that keeps transfers explicit and "
                        "respects the collective serialization gate, "
                        "docs/sharding.md)"))
        return out


# ---------------------------------------------------------------------------
# R7 — the exactly-one-JSON-line stdout contract of the driver-facing tools:
# the driver parses ONE machine-readable line from stdout; everything human
# goes to stderr. A stray print() corrupts the BENCH/MULTICHIP artifacts.
# ---------------------------------------------------------------------------
class R7JsonStdout:
    id = "R7"
    _CONTRACT_MODULES = {
        "bench.py", "__graft_entry__.py", "tools/hostbench.py",
        "tools/collectives.py", "tools/shard_ab.py", "tools/stepaudit.py",
        "tools/telemetry_run.py", "tools/graftcheck/__main__.py",
        "tools/run_report.py", "tools/perfgate.py", "tools/servebench.py",
        "tools/continual_run.py", "tools/fleet_run.py",
        "tools/obs_collect.py", "tools/racecheck.py",
    }

    def applies(self, path: str) -> bool:
        return path in self._CONTRACT_MODULES

    @staticmethod
    def _is_json_print(node: ast.Call) -> bool:
        return (len(node.args) == 1 and isinstance(node.args[0], ast.Call)
                and _name_of(node.args[0].func).endswith("json.dumps"))

    def check(self, ctx: ModuleContext) -> List[Finding]:
        out: List[Finding] = []
        json_prints_per_fn: Dict[str, int] = {}
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and _name_of(node.func) == "print"):
                continue
            has_file_kw = any(kw.arg == "file" for kw in node.keywords)
            if has_file_kw:
                continue  # stderr-routed (or tests would catch a stdout dup)
            if self._is_json_print(node):
                qn = ctx.qualname(node)
                json_prints_per_fn[qn] = json_prints_per_fn.get(qn, 0) + 1
                if json_prints_per_fn[qn] > 1:
                    out.append(Finding(
                        rule=self.id, path=ctx.path, line=node.lineno,
                        col=node.col_offset,
                        message=f"second print(json.dumps(...)) in {qn} — "
                                f"the stdout contract is exactly ONE JSON "
                                f"line"))
                continue
            out.append(Finding(
                rule=self.id, path=ctx.path, line=node.lineno,
                col=node.col_offset,
                message="bare print() to stdout in a JSON-contract tool — "
                        "route human output to stderr (file=sys.stderr); "
                        "stdout carries exactly one JSON line"))
        return out


# ---------------------------------------------------------------------------
# R8 — refusal-matrix parity (repo rule): every knob combination the trainer
# refuses at dispatch (__init__ path selection or _build_step) must also be
# refused by config.__post_init__ validation, so an unsupported config fails
# at CONSTRUCTION (cheap, local, before any accelerator time) and a
# checkpoint can never be written with knobs the dispatch will later refuse.
# Both matrices are parsed from the AST (conditions on config attributes
# guarding a `raise ValueError`) and diffed; dispatch-side guards that also
# test non-config state (mesh size, process count) are runtime conditions
# and are exempt from the diff.
#
# R8 is the STATIC half of the parity discipline; tools/graftcheck/ is the
# empirical twin that actually executes the lattice (it catches the guards
# this AST diff must exempt — conditions mixing config and runtime state —
# by probing a real Trainer). The cross-reference enforced here: graftcheck's
# knob registry must enumerate every config field, so the executing checker
# can never silently under-cover the surface this rule parses.
# ---------------------------------------------------------------------------
class R8RefusalParity:
    id = "R8"
    repo_rule = True

    _CONFIG = _LIB + "config.py"
    _TRAINER = _LIB + "train/trainer.py"
    _DISPATCH_FNS = {"_build_step", "_build_banded_cbow_chunk", "__init__"}
    _GRAFTCHECK_REGISTRY = "tools/graftcheck/registry.py"

    @staticmethod
    def _knobs_in(test: ast.AST, selves: Set[str],
                  fields: Set[str]) -> Optional[Set[str]]:
        """Config-field names referenced in a condition; None if the
        condition also references non-config runtime state."""
        knobs: Set[str] = set()
        pure = True
        for node in ast.walk(test):
            if isinstance(node, ast.Attribute) and isinstance(
                    node.value, ast.Name):
                if node.value.id in selves:
                    if node.attr in fields:
                        knobs.add(node.attr)
                    else:
                        pure = False
                elif node.value.id not in ("np", "jnp", "numpy"):
                    pure = False
            elif isinstance(node, ast.Call):
                pure = False
        return knobs if pure and knobs else None

    def _raise_matrix(self, tree: ast.Module, fn_names: Set[str],
                      selves: Set[str], fields: Set[str],
                      parents: Dict[ast.AST, ast.AST]):
        """set of frozensets: the knob set guarding each pure-config raise
        (union of every enclosing `if` condition's knobs)."""
        out = set()
        fns = [n for n in ast.walk(tree)
               if isinstance(n, ast.FunctionDef) and n.name in fn_names]
        for fn in fns:
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Raise) and node.exc is not None
                        and "ValueError" in ast.unparse(node.exc)):
                    continue
                knobs: Set[str] = set()
                pure = True
                cur = parents.get(node)
                while cur is not None and cur is not fn:
                    if isinstance(cur, ast.If):
                        k = self._knobs_in(cur.test, selves, fields)
                        if k is None:
                            pure = False
                            break
                        knobs |= k
                    cur = parents.get(cur)
                if pure and knobs:
                    out.add(frozenset(knobs))
        return out

    def check_repo(self, root: str) -> List[Finding]:
        cfg_path = os.path.join(root, *self._CONFIG.split("/"))
        tr_path = os.path.join(root, *self._TRAINER.split("/"))
        findings: List[Finding] = []
        try:
            with open(cfg_path, "r", encoding="utf-8") as f:
                cfg_tree = ast.parse(f.read())
            with open(tr_path, "r", encoding="utf-8") as f:
                tr_tree = ast.parse(f.read())
        except (OSError, SyntaxError) as e:
            return [Finding(rule=self.id, path=self._CONFIG, line=0, col=0,
                            message=f"cannot parse matrix sources: {e}")]

        # config dataclass fields = the knob universe
        fields: Set[str] = set()
        for node in ast.walk(cfg_tree):
            if isinstance(node, ast.ClassDef) and node.name == "Word2VecConfig":
                for stmt in node.body:
                    if isinstance(stmt, ast.AnnAssign) and isinstance(
                            stmt.target, ast.Name):
                        fields.add(stmt.target.id)
        if not fields:
            return [Finding(rule=self.id, path=self._CONFIG, line=0, col=0,
                            message="Word2VecConfig fields not found")]

        def parent_map(tree):
            p = {}
            for node in ast.walk(tree):
                for child in ast.iter_child_nodes(node):
                    p[child] = node
            return p

        cfg_matrix = self._raise_matrix(
            cfg_tree, {"__post_init__"}, {"self"}, fields,
            parent_map(cfg_tree))
        disp_matrix = self._raise_matrix(
            tr_tree, self._DISPATCH_FNS, {"cfg", "config", "self"}, fields,
            parent_map(tr_tree))

        for combo in sorted(disp_matrix, key=sorted):
            if len(combo) < 2:
                continue  # single-knob range checks live in config by design
            # covered only by a MULTI-knob config raise over a subset of these
            # knobs. Single-knob config raises are range checks (negative_pool
            # < 0, window > 127, ...) whose conditions say nothing about the
            # knob-COMBINATION the dispatch refuses — counting them as
            # coverage would blind the rule to exactly the gap class it
            # exists to catch. A config that is legitimately stricter with a
            # single-knob refusal can carry a justified suppression.
            if not any(len(cfg_combo) >= 2 and cfg_combo <= combo
                       for cfg_combo in cfg_matrix):
                findings.append(Finding(
                    rule=self.id, path=self._TRAINER, line=0, col=0,
                    message=f"knob combination refused at trainer dispatch "
                            f"but not in config.__post_init__ "
                            f"validation: {sorted(combo)} — add the "
                            f"construction-time refusal (selection-matrix "
                            f"parity; graftcheck executes the empirical "
                            f"twin of this check)"))
        findings.extend(self._check_graftcheck_registry(root, fields))
        return findings

    def _check_graftcheck_registry(self, root: str,
                                   fields: Set[str]) -> List[Finding]:
        """Cross-reference to the EXECUTING checker: every config field must
        have a knob entry in tools/graftcheck/registry.py, else graftcheck's
        lattice silently under-covers the refusal surface this rule parses.
        Skipped when the graftcheck package is absent (the R8 fixture
        mini-repos); the real tree always carries it.

        DELIBERATELY redundant with registry.registry_drift(): that gate
        runs by importing the live config (and therefore jax); this one is
        pure AST, so the lint layer keeps working when graftcheck itself is
        broken or unimportable — the two gates cross-check each other. The
        AST scan only recognizes literal ``_K("name", ...)`` entries, which
        the registry's own docstring mandates (a knob built by loop/variable
        would be flagged here — that is the desired outcome, not a bug)."""
        reg_dir = os.path.join(root, "tools", "graftcheck")
        if not os.path.isdir(reg_dir):
            return []
        reg_path = os.path.join(root, *self._GRAFTCHECK_REGISTRY.split("/"))
        try:
            with open(reg_path, "r", encoding="utf-8") as f:
                reg_tree = ast.parse(f.read())
        except (OSError, SyntaxError) as e:
            return [Finding(
                rule=self.id, path=self._GRAFTCHECK_REGISTRY, line=0, col=0,
                message=f"cannot parse the graftcheck knob registry: {e}")]
        declared: Set[str] = set()
        for node in ast.walk(reg_tree):
            if (isinstance(node, ast.Call)
                    and _name_of(node.func) in ("_K", "Knob")
                    and node.args and isinstance(node.args[0], ast.Constant)):
                declared.add(str(node.args[0].value))
        out: List[Finding] = []
        for name in sorted(fields - declared):
            out.append(Finding(
                rule=self.id, path=self._GRAFTCHECK_REGISTRY, line=0, col=0,
                message=f"config field {name!r} has no knob entry in the "
                        f"graftcheck registry — the executing lattice "
                        f"under-covers the refusal surface; declare its "
                        f"sampled domain"))
        for name in sorted(declared - fields):
            out.append(Finding(
                rule=self.id, path=self._GRAFTCHECK_REGISTRY, line=0, col=0,
                message=f"graftcheck registry knob {name!r} does not exist "
                        f"on Word2VecConfig — drop the stale entry"))
        return out


from tools.graftlint.concurrency import CONCURRENCY_RULES  # noqa: E402 — the
# graftrace layer (R9–R11 + R1 staleness) lives in its own module; imported
# at the bottom so concurrency.py can use _name_of/R1ThreadPools from here

ALL_RULES = [R1ThreadPools(), R2Prng(), R3TracerDiscipline(), R4PrefixDtype(),
             R5RetryIO(), R6DispatchDiscipline(), R7JsonStdout(),
             R8RefusalParity()] + CONCURRENCY_RULES
