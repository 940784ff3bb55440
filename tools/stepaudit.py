"""Compiled-step contract auditor — layer 2 of the static-analysis subsystem.

Where graftlint (layer 1, tools/graftlint/) checks what the SOURCE promises,
this tool checks what the COMPILED ARTIFACT actually does. It builds
production ``Trainer`` objects for the four step variants — rows-GSPMD,
explicit shard_map, cols layout, banded CBOW — runs a scripted multi-chunk
fit through the real feed plumbing, captures the exact per-dispatch argument
avals, AOT-lowers the production step with them, and asserts four contracts
that prose and reviewers used to carry alone:

(a) **donation** — the params carry is ACTUALLY donated in the compiled
    executable (``input_output_alias`` present for both matrices). A silently
    dropped ``donate_argnums`` doubles peak HBM at the headline [V, D] pair;
    nothing else in the repo would notice.
(b) **transfers** — the scripted fit runs under
    ``jax.transfer_guard("disallow")``: every host→device byte moves through
    the explicit staging discipline (put_global / _stage_dispatch_meta), zero
    implicit transfers anywhere in the steady-state loop.
(c) **dtype** — no f64 anywhere in the lowered step module (x64 creep), and
    in bf16 mode no dense ``[V_padded, D_padded]`` f32 intermediate (a dense
    upcast would silently double the step's HBM traffic). Checked on the
    platform-neutral lowered module, NOT the CPU-compiled one — the CPU
    backend's float-normalization pass rewrites bf16 compute to f32 and would
    poison the check (same caveat as tools/collectives.py).
(d) **recompilation** — the scripted fit performs EXACTLY one jit compilation
    across both step twins: shape/static-arg churn (a new pad shape, a meta
    row added without staging, an accidental python-scalar argument) fails
    tier-1 here instead of surfacing as mystery recompiles in a hardware
    session.

Baseline: the committed ``STEPAUDIT.json`` snapshot pins the structural
fields; tests/test_stepaudit.py fails on drift. The dryrun_multichip artifact
embeds the same fields so every MULTICHIP JSON certifies the compiled-step
contracts next to the collective-bytes fields.

Run:  python tools/stepaudit.py [--smoke] [--mesh 2x4] [--json-out F]
Prints progress on stderr and exactly ONE JSON line on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

# self-provision the virtual multi-device CPU mesh BEFORE jax initializes — CPU by
# design: the contracts are read off lowered/compiled modules, not timed
if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VARIANTS = ("rows_gspmd", "shard_map", "cols", "cbow_banded",
            # stabilizer-on twins (ISSUE 7): the clamp/clip/decay ops ride
            # inside the jitted chunk, so they must hold the same four
            # contracts — donation (the touched-row scatter-set must not
            # break aliasing), transfers, dtype (stabilizer norm math is
            # promote(dtype, f32) — no f64 creep), one-compile
            "rows_gspmd_stab", "shard_map_stab",
            # ISSUE-14 step restructurings: the fused coefficient chain and
            # the end-to-end bf16 chain twin, which additionally carries the
            # NEW dtype contract — no dense f32 [B, D] intermediate in the
            # lowered bf16 module (dense_f32_bd_free)
            "rows_gspmd_fused", "rows_gspmd_bf16_chain",
            # ISSUE-17 local-SGD: the sync_every=k owner-local window — the
            # k-step unrolled shard_map body plus the delta-merge psum must
            # keep donation (window params carry aliased), transfers, dtype,
            # and one-compile (the window is ONE jitted program, never a
            # separate merge dispatch)
            "localsgd")
# the bf16 twin of the rows step carries the dense-f32 check (contract c)
BF16_VARIANT = "rows_gspmd_bf16"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def donation_summary(compiled_text: str) -> dict:
    """Contract (a) parser: input/output aliasing from a compiled module's
    one-line HloModule header::

        input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, ...), ... }

    A dropped ``donate_argnums`` (or a donation silently discarded by an
    aval/sharding mismatch) leaves the header absent → 0 aliased params.
    Exposed standalone so tests can assert the auditor catches exactly that
    on a toy step."""
    header = next((ln for ln in compiled_text.splitlines()
                   if "input_output_alias" in ln), "")
    aliased = len(re.findall(r"(?:may|must)-alias", header))
    return {"present": bool(header), "aliased_params": aliased,
            "ok": aliased >= 2}   # the params carry = syn0 + syn1


def _variant_config_kwargs(variant: str) -> dict:
    if variant == "rows_gspmd":
        return {}
    if variant == "shard_map":
        return dict(step_lowering="shard_map", negative_pool=16)
    if variant == "cols":
        return dict(embedding_partition="cols")
    if variant == "cbow_banded":
        return dict(cbow=True, cbow_update="banded", negative_pool=16)
    if variant == "rows_gspmd_stab":
        return dict(negative_pool=16, max_row_norm=50.0, update_clip=0.5,
                    row_l2=1e-4)
    if variant == "shard_map_stab":
        return dict(step_lowering="shard_map", negative_pool=16,
                    max_row_norm=50.0, update_clip=0.5, row_l2=1e-4)
    if variant == "rows_gspmd_fused":
        return dict(negative_pool=16, fused_logits=True)
    if variant == "rows_gspmd_bf16_chain":
        return dict(negative_pool=16, param_dtype="bfloat16",
                    compute_dtype="bfloat16", logits_dtype="bfloat16",
                    fused_logits=True, bf16_chain=True)
    if variant == "localsgd":
        # sync_every must divide the audit cfg's steps_per_dispatch=2
        return dict(step_lowering="shard_map", negative_pool=16, sync_every=2)
    if variant == BF16_VARIANT:
        return dict(param_dtype="bfloat16", compute_dtype="bfloat16")
    raise ValueError(f"unknown variant {variant!r}")


def _toy_problem(geom: dict):
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import Vocabulary

    rng = np.random.default_rng(0)
    V = geom["v"]
    words = [f"w{i}" for i in range(V)]
    vocab = Vocabulary.from_words_and_counts(words, rng.integers(1, 100, V))
    sents = [[f"w{i}" for i in rng.integers(0, V, 12)]
             for _ in range(geom["sentences"])]
    return vocab, encode_sentences(sents, vocab, 1000)


def _capture_wrap(trainer):
    """Replace the trainer's step twins with wrappers that record the aval
    (ShapeDtypeStruct + sharding) pytree of the first dispatch's arguments —
    the exact production signature the AOT lowering re-traces below."""
    import jax

    orig_full, orig_fast = trainer._step_fn, trainer._step_fn_fast
    cap = {}

    def to_sds(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        # a non-device leaf here IS the regression the transfer guard then
        # reports — keep capturing so the other contracts still run
        return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)

    def wrap(fn):
        def wrapped(*args):
            if "sds" not in cap:
                cap["sds"] = jax.tree.map(to_sds, args)
            return fn(*args)
        return wrapped

    trainer._step_fn = wrap(orig_full)
    trainer._step_fn_fast = (trainer._step_fn if orig_fast is orig_full
                             else wrap(orig_fast))
    return orig_full, orig_fast, cap


def audit_variant(variant: str, mesh_shape, geom: dict) -> dict:
    """Run the four contract checks for one step variant; returns the result
    dict (every leaf JSON-serializable). Raises nothing on contract failure —
    callers assert on the ``ok`` fields so one broken contract still reports
    the other three."""
    import jax

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.train.trainer import Trainer

    vocab, enc = _toy_problem(geom)
    plan = make_mesh(*mesh_shape)
    cfg = Word2VecConfig(
        vector_size=geom["d"], min_count=1, pairs_per_batch=geom["b"],
        num_iterations=1, window=2, steps_per_dispatch=2,
        **_variant_config_kwargs(variant))
    trainer = Trainer(cfg, vocab, plan=plan)
    orig_full, orig_fast, cap = _capture_wrap(trainer)

    # (b) transfers: the scripted fit must be implicit-transfer-free
    transfer_ok, transfer_err = True, None
    try:
        with jax.transfer_guard("disallow"):
            trainer.fit(enc)
    except Exception as e:  # noqa: BLE001 — reported, not raised (see docstring)
        transfer_ok, transfer_err = False, f"{type(e).__name__}: {e}"[:500]
    trainer._step_fn, trainer._step_fn_fast = orig_full, orig_fast

    # (d) recompilation tripwire: exactly ONE compile across both twins.
    # Reported independently of contract (b): when the guarded fit aborted
    # the count is not meaningful, so (d) reports ok=None ("not assessed"),
    # never a phantom violation — one broken contract must not masquerade
    # as another.
    compiles = orig_full._cache_size()
    if orig_fast is not orig_full:
        compiles += orig_fast._cache_size()
    recompile = {"compiles": int(compiles), "expected": 1,
                 "ok": (compiles == 1) if transfer_ok else None}

    donation = {"present": False, "aliased_params": 0, "ok": False}
    dtype = {"f64_free": None, "dense_f32_vd_free": None, "ok": False}
    if "sds" in cap:
        dispatched = (orig_full if orig_full._cache_size() else orig_fast)
        lowered = dispatched.lower(*cap["sds"])

        # (c) dtype audit on the platform-neutral lowered module
        lowered_text = lowered.as_text()
        dtype["f64_free"] = "f64" not in lowered_text
        dtype["ok"] = dtype["f64_free"]
        if cfg.param_dtype == "bfloat16":
            dense = f"tensor<{trainer.padded_vocab}x{trainer.padded_dim}xf32>"
            dtype["dense_f32_vd_free"] = dense not in lowered_text
            dtype["ok"] = dtype["ok"] and dtype["dense_f32_vd_free"]
        if cfg.bf16_chain:
            # the ISSUE-14 dtype-contract row: the end-to-end bf16 chain
            # must leave NO dense f32 [B, D] intermediate in the lowered
            # module (the classic chain's f_pos path converts the [B, D]
            # product to f32 before its reduce; the chain accumulates in
            # the dot via preferred_element_type instead)
            dense_bd = f"tensor<{geom['b']}x{trainer.padded_dim}xf32>"
            dtype["dense_f32_bd_free"] = dense_bd not in lowered_text
            dtype["ok"] = dtype["ok"] and dtype["dense_f32_bd_free"]

        # (a) donation: input/output aliasing in the compiled artifact
        donation = donation_summary(lowered.compile().as_text())

    return {
        "variant": variant,
        "mesh": list(mesh_shape),
        "steps": int(trainer.global_step),
        "donation": donation,
        "transfers": {"ok": transfer_ok, "error": transfer_err,
                      "dispatches": int(trainer.global_step)
                      // cfg.steps_per_dispatch},
        "dtype": dtype,
        "recompile": recompile,
        "ok": bool(donation["ok"] and transfer_ok and dtype["ok"]
                   and recompile["ok"] is True),
    }


def audit_recover_rebuild(geom: dict) -> dict:
    """ISSUE 8 satellite: the ``norm_watch="recover"`` escalation ladder
    auto-engages ``max_row_norm`` on first firing, which REBUILDS the step
    twins — documented as "one recompile per engagement, logged"
    (trainer._perform_recovery), but until now nothing machine-checked it.
    This audit drives a real recovery through a scripted finite blowup
    (train.faults scale injection — the same deterministic hook the chaos
    schedule uses) and asserts the one-logged-recompile contract:

    - exactly ONE recovery fires and the step twins are rebuilt once;
    - the pre-recovery twins hold the usual one-compile contract;
    - the REBUILT twins compile exactly once more — total 2 compiles for the
      whole blowup-and-recover fit, not a recompile-per-dispatch storm;
    - the engaged clamp is the watchdog threshold (the boundary the firing
      measured health by).
    """
    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.train import faults
    from glint_word2vec_tpu.train.trainer import Trainer

    vocab, enc = _toy_problem(geom)
    cfg = Word2VecConfig(
        vector_size=geom["d"], min_count=1, pairs_per_batch=geom["b"],
        num_iterations=2, window=2, steps_per_dispatch=2,
        heartbeat_every_steps=2, prefetch_chunks=0, subsample_ratio=0.0,
        norm_watch="recover", nonfinite_policy="halt")
    trainer = Trainer(cfg, vocab, plan=make_mesh(1, 1))
    pre_full, pre_fast = trainer._step_fn, trainer._step_fn_fast

    rebuilds = []
    orig_build = trainer._build_step

    def counting_build(with_metrics: bool = True):
        rebuilds.append(with_metrics)
        return orig_build(with_metrics)

    trainer._build_step = counting_build

    error = None
    faults.configure(scale_params_at_step=8)
    try:
        trainer.fit(enc)
    except Exception as e:  # noqa: BLE001 — reported, not raised (audit style)
        error = f"{type(e).__name__}: {e}"[:500]
    finally:
        faults.reset()
        trainer._build_step = orig_build

    post_full, post_fast = trainer._step_fn, trainer._step_fn_fast
    rebuilt = post_full is not pre_full

    def twin_compiles(full, fast):
        n = full._cache_size()
        if fast is not full:
            n += fast._cache_size()
        return int(n)

    compiles_before = twin_compiles(pre_full, pre_fast)
    compiles_after = twin_compiles(post_full, post_fast) if rebuilt else 0
    engaged = float(trainer._stabilizers.max_row_norm)
    result = {
        "error": error,
        "recoveries": int(trainer.recoveries_performed),
        "watchdog_fires": int(trainer.norm_watchdog.fires),
        "rebuilt": bool(rebuilt),
        "rebuild_calls": len(rebuilds),
        "compiles_before": compiles_before,
        "compiles_after": compiles_after,
        "total_compiles": compiles_before + compiles_after,
        "engaged_max_row_norm": engaged,
        "expected_total_compiles": 2,
    }
    result["ok"] = bool(
        error is None
        and result["recoveries"] == 1
        and rebuilt
        and compiles_before == 1
        and compiles_after == 1
        and engaged == cfg.norm_watch_threshold)
    return result


def audit(mesh_shape=(2, 4), geom=None, variants=None) -> dict:
    """Audit the given variants (default: all four + the bf16 dtype twin) at
    one mesh shape. Importable — __graft_entry__.dryrun_multichip embeds a
    two-variant subset in the MULTICHIP JSON line."""
    geom = geom or smoke_geometry()
    variants = variants or (VARIANTS + (BF16_VARIANT,))
    out = {"geometry": geom, "mesh": list(mesh_shape), "variants": {}}
    for v in variants:
        log(f"stepaudit: auditing {v} at mesh "
            f"{mesh_shape[0]}x{mesh_shape[1]} ...")
        res = audit_variant(v, mesh_shape, geom)
        out["variants"][v] = res
        log(f"  {v:16s} donation={res['donation']['ok']} "
            f"transfers={res['transfers']['ok']} dtype={res['dtype']['ok']} "
            f"recompile={res['recompile']['ok']}")
    out["ok"] = all(r["ok"] for r in out["variants"].values())
    return out


def smoke_geometry() -> dict:
    return dict(v=64, d=16, b=16, sentences=64)


def full_geometry() -> dict:
    # still CPU-feasible; a larger vocab exercises real padding geometry
    return dict(v=1000, d=32, b=64, sentences=192)


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometry (the tier-1 wiring)")
    ap.add_argument("--mesh", default="2x4", help="'NDxNM', e.g. 2x4")
    ap.add_argument("--only", default="",
                    help="comma-separated variant subset (e.g. 'localsgd'); "
                         "skips the recover-rebuild audit — the full run "
                         "(and the STEPAUDIT.json baseline) covers all "
                         "variants")
    ap.add_argument("--json-out", default="",
                    help="also write the JSON result to this path")
    args = ap.parse_args(argv)

    import jax
    n = len(jax.devices())
    shape = tuple(int(x) for x in args.mesh.split("x"))
    if n < shape[0] * shape[1]:
        raise SystemExit(
            f"need {shape[0] * shape[1]} devices (have {n}); run as a script "
            "so the CPU mesh self-provisions, or set "
            "--xla_force_host_platform_device_count")

    geom = smoke_geometry() if args.smoke else full_geometry()
    only = None
    if args.only:
        only = tuple(s.strip() for s in args.only.split(",") if s.strip())
        known = VARIANTS + (BF16_VARIANT,)
        bad = [v for v in only if v not in known]
        if bad:
            raise SystemExit(f"unknown variant(s) {bad}; known: {known}")
    result = audit(shape, geom, variants=only)
    if only is None:
        log("stepaudit: auditing the norm_watch='recover' rebuild "
            "contract ...")
        result["recover_rebuild"] = audit_recover_rebuild(geom)
        rr = result["recover_rebuild"]
        log(f"  recover_rebuild  recoveries={rr['recoveries']} "
            f"rebuilt={rr['rebuilt']} total_compiles={rr['total_compiles']} "
            f"ok={rr['ok']}")
        result["ok"] = bool(result["ok"] and rr["ok"])
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main(argv=None) -> None:
    result = run(argv)
    print(json.dumps(result))
    if not result["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
