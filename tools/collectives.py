"""HLO collective audit: GSPMD vs shard_map sharded-step collective bytes.

Every multi-chip number in PERF.md §7 was, until round 9, an ESTIMATE from
byte formulas — the GSPMD lowering's actual collective profile had never been
inspected. This tool closes that: it AOT-compiles BOTH step lowerings
(``config.step_lowering="gspmd"`` — jit + sharding constraints, the compiler
chooses the schedule; and ``"shard_map"`` — the explicit schedule of
ops/sgns_shard.py) at a given geometry and mesh shape, walks the compiled
HLO, and tabulates every ``all-gather`` / ``all-reduce`` / ``reduce-scatter``
/ ``all-to-all`` / ``collective-permute`` with its shape, bytes, and which
mesh axis its replica groups span (parallel/mesh.classify_replica_groups).

No hardware or execution is involved — compiled HLO is a static artifact, so
the collective *structure and bytes* are measurable on the forced-device CPU
mesh (``--xla_force_host_platform_device_count``). The SPMD partitioner is
the same platform-independent pass that runs for TPU; backend-specific
rewrites (e.g. async pairs, ICI-topology-aware algorithms) can change HOW the
bytes move, not how many a collective op names. Numbers from this tool are
labeled "HLO-measured collective bytes" in PERF.md §7, distinct from both the
old formula estimates and a future on-hardware traffic profile.

Bytes metric, stated precisely: for each collective instruction,
``max(sum of operand bytes, result bytes)`` — the payload the op names, a
lower bound on link traffic (ring/tree algorithms move a small multiple).

The step audited is the metrics-elided twin (``with_metrics=False`` — the
production steady state; the full twin adds three f32 scalars over `data`).

Run:  python tools/collectives.py [--smoke] [--mesh 2x4|all] [--json-out F]
      (defaults to the headline geometry: V=1M rows padded, B=64k, D=384,
       bf16 params, pool=512)
Prints per-collective tables on stderr and exactly ONE JSON line on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

# self-provision the virtual multi-device CPU mesh BEFORE jax initializes — CPU by
# design: collective BYTES are counted from compiled HLO, nothing is timed
if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NEG = 5

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all-reduce-start", "all-gather-start",
    "reduce-scatter-start", "collective-permute-start",
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string — handles tuples and layouts:
    ``bf16[65536,384]{1,0}``, ``(f32[8], f32[8])``, ``f32[]`` (scalar)."""
    total = 0
    for dtype, dims in re.findall(r"([a-z]\d+|pred|bf16)\[([0-9,]*)\]",
                                  shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _parse_replica_groups(text: str):
    """Parse the two HLO replica-group syntaxes into a list of id lists:
    explicit ``{{0,1},{2,3}}`` and iota ``[2,4]<=[8]`` /
    ``[4,2]<=[2,2,2]T(2,1,0)`` (reshape iota to the bound dims, transpose by
    the perm, flatten, regroup to the group shape)."""
    m = re.search(r"replica_groups=\{(\{[^=]*?\})\}", text)
    if m:
        return [[int(x) for x in g.split(",") if x.strip() != ""]
                for g in re.findall(r"\{([^{}]*)\}", m.group(1))]
    m = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?",
        text)
    if m:
        ngroups, gsize = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(x) for x in m.group(4).split(",")])
        return ids.reshape(-1).reshape(ngroups, gsize).tolist()
    return None


def parse_collectives(hlo_text: str, num_data: int, num_model: int) -> list:
    """Walk HLO text; return one row per collective instruction:
    {op, shape, bytes, axis, replica_groups}."""
    from glint_word2vec_tpu.parallel.mesh import classify_replica_groups

    # name -> result shape, for operand-bytes lookup
    shapes = {}
    defline = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
        r"(\([^)]*\)|[a-z]\d*[a-z0-9]*\[[^\]]*\](?:\{[^}]*\})?)\s")
    for line in hlo_text.splitlines():
        m = defline.match(line)
        if m:
            shapes[m.group(1)] = m.group(2)

    opline = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
        r"(\([^)]*\)|[a-z]\d*[a-z0-9]*\[[^\]]*\](?:\{[^}]*\})?)\s+"
        r"(" + "|".join(re.escape(o) for o in _COLLECTIVE_OPS) + r")\(([^)]*)\)")
    rows = []
    for line in hlo_text.splitlines():
        m = opline.match(line)
        if not m:
            continue
        _, out_shape, op, operands = m.groups()
        in_bytes = 0
        for name in re.findall(r"%?([\w.\-]+)", operands):
            in_bytes += shape_bytes(shapes.get(name, ""))
        groups = _parse_replica_groups(line)
        if groups is None or not any(groups):
            # empty replica_groups={} = one group over every participant
            axis = "all"
        else:
            axis = classify_replica_groups(num_data, num_model, groups)
        # a size-1 axis makes "all devices" and "the other axis" the same set
        if axis == "all" and num_data == 1 and num_model > 1:
            axis = "model"
        elif axis == "all" and num_model == 1 and num_data > 1:
            axis = "data"
        rows.append({
            "op": op.replace("-start", ""),
            "shape": out_shape,
            "bytes": max(shape_bytes(out_shape), in_bytes),
            "axis": axis,
        })
    return rows


def summarize(rows: list, assembly_rows: int = None,
              assembly_count: int = 1) -> dict:
    by_axis = {}
    for r in rows:
        by_axis[r["axis"]] = by_axis.get(r["axis"], 0) + r["bytes"]
    out = {
        "collectives": rows,
        "count": len(rows),
        "total_bytes": sum(r["bytes"] for r in rows),
        "bytes_by_axis": by_axis,
    }
    if assembly_rows is not None:
        # shard_map schedule claim: the ONLY model-axis collectives are the
        # forward row-assembly psums -> model-axis UPDATE bytes are zero.
        # Computed, not asserted: subtract every model-axis all-reduce whose
        # leading dim is the assembly row count (2·Bl + P; matched on ROWS,
        # not bytes — CPU float normalization can rewrite a bf16 collective
        # to f32, see run()); anything left over is flagged.
        # ``assembly_count``: how many assembly psums the program legitimately
        # carries — 1 for the synchronous step, k for a sync_every=k local-SGD
        # window (its k-step loop is PYTHON-UNROLLED precisely so each
        # in-window step's psum appears in the HLO text and is counted here;
        # a lax.scan body would show its collectives once regardless of trip
        # count and the tabulated bytes would be a lie).
        residual = 0
        matched = 0
        matched_n = 0
        for r in [r for r in rows if r["axis"] == "model"]:
            dims = re.search(r"\[(\d+)", r["shape"])
            if (r["op"] == "all-reduce" and matched_n < assembly_count
                    and dims and int(dims.group(1)) == assembly_rows):
                matched += r["bytes"]
                matched_n += 1
            else:
                residual += r["bytes"]
        out["forward_assembly_bytes"] = matched
        out["forward_assembly_count"] = matched_n
        out["model_axis_update_bytes"] = residual
    return out


def build_geometry(args) -> dict:
    if args.smoke:
        return dict(v=4096, d=64, b=512, pool=128, param_dtype="float32")
    return dict(v=1_000_000, d=384, b=65536, pool=512, param_dtype="bfloat16")


def audit_mesh(geom: dict, shape: tuple, sync_every: int = 1) -> dict:
    """Compile both lowerings at one mesh shape; return their summaries.
    ``sync_every=k > 1`` additionally compiles the local-SGD WINDOW program
    (k owner-local steps + one delta-merge — config.sync_every) and prices
    its per-window data-axis bytes against both k=1 schedules."""
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.ops.sgns import (
        EmbeddingPair, sgns_step_shared_core)
    from glint_word2vec_tpu.ops.sgns_shard import make_shard_map_sgns_step
    from glint_word2vec_tpu.parallel.mesh import (
        make_mesh, pad_vocab_for_sharding)

    nd, nm = shape
    plan = make_mesh(nd, nm)
    v = pad_vocab_for_sharding(geom["v"], nm)
    d, b, pool = geom["d"], geom["b"], geom["pool"]
    pdt = jnp.dtype(geom["param_dtype"])
    cdt = ldt = pdt
    alpha_sds = jax.ShapeDtypeStruct((), jnp.float32, sharding=plan.replicated)
    batch_sds = {
        "centers": jax.ShapeDtypeStruct((b,), jnp.int32, sharding=plan.batch),
        "contexts": jax.ShapeDtypeStruct((b,), jnp.int32, sharding=plan.batch),
        "mask": jax.ShapeDtypeStruct((b,), jnp.float32, sharding=plan.batch),
    }
    negs_sds = jax.ShapeDtypeStruct((pool,), jnp.int32,
                                    sharding=plan.replicated)

    def make_gspmd_step(emb_sharding):
        # the production GSPMD path: core step + the same sharding constraint
        # trainer._build_step applies to the scan carry, metrics elided
        def gspmd_step(params, batch, negatives, alpha):
            new_p, m = sgns_step_shared_core(
                params, batch["centers"], batch["contexts"], batch["mask"],
                negatives, alpha, NEG, "exact", cdt, False, ldt,
                with_metrics=False)
            new_p = jax.lax.with_sharding_constraint(
                new_p, EmbeddingPair(emb_sharding, emb_sharding))
            return new_p, m.pairs
        return gspmd_step

    sm_inner = make_shard_map_sgns_step(
        plan.mesh, NEG, "exact", cdt, ldt, with_metrics=False)

    def shard_map_step(params, batch, negatives, alpha):
        new_p, m = sm_inner(params, batch, negatives, alpha)
        return new_p, m.pairs

    variants = [("gspmd", make_gspmd_step(plan.embedding), plan.embedding),
                ("shard_map", shard_map_step, plan.embedding)]
    if d % nm == 0:
        # the CIKM'16 column layout (embedding_partition='cols'), GSPMD-
        # lowered — audited so PERF.md §7's rows-vs-cols verdict rests on
        # measured bytes for BOTH layouts, not formulas
        variants.append(("gspmd_cols", make_gspmd_step(plan.embedding_cols),
                         plan.embedding_cols))

    out = {}
    for name, fn, emb in variants:
        p_sds = EmbeddingPair(
            jax.ShapeDtypeStruct((v, d), pdt, sharding=emb),
            jax.ShapeDtypeStruct((v, d), pdt, sharding=emb))
        compiled = jax.jit(fn, donate_argnums=(0,)).lower(
            p_sds, batch_sds, negs_sds, alpha_sds).compile()
        rows = parse_collectives(compiled.as_text(), nd, nm)
        fwd = None
        if name == "shard_map":
            fwd = 2 * (b // nd) + pool   # assembly psum row count
        out[name] = summarize(rows, assembly_rows=fwd)
    out["mesh"] = list(shape)
    out["padded_vocab"] = v
    g, s = out["gspmd"]["total_bytes"], out["shard_map"]["total_bytes"]
    out["bytes_ratio_shard_map_over_gspmd"] = (s / g) if g else None

    if sync_every > 1:
        # --- the local-SGD window (config.sync_every=k): ONE program = k
        # owner-local steps + the delta-merge. Its whole point is priced per
        # WINDOW: the window's data-axis bytes replace what a k-step
        # synchronous schedule pays k times ---
        k = sync_every
        ls_inner = make_shard_map_sgns_step(
            plan.mesh, NEG, "exact", cdt, ldt, with_metrics=False,
            sync_every=k)

        def localsgd_window(params, batch, negatives, alphas):
            new_p, m = ls_inner(params, batch, negatives, alphas)
            return new_p, m.pairs

        win_batch_sds = {
            name: jax.ShapeDtypeStruct((k, b), dt,
                                       sharding=plan.batch_stacked)
            for name, dt in (("centers", jnp.int32), ("contexts", jnp.int32),
                             ("mask", jnp.float32))}
        # disjoint per-shard lattices: [k, nd·pool], pool per shard unchanged
        win_negs_sds = jax.ShapeDtypeStruct(
            (k, nd * pool), jnp.int32, sharding=plan.batch_stacked)
        win_alpha_sds = jax.ShapeDtypeStruct(
            (k,), jnp.float32, sharding=plan.replicated)
        p_sds = EmbeddingPair(
            jax.ShapeDtypeStruct((v, d), pdt, sharding=plan.embedding),
            jax.ShapeDtypeStruct((v, d), pdt, sharding=plan.embedding))
        compiled = jax.jit(localsgd_window, donate_argnums=(0,)).lower(
            p_sds, win_batch_sds, win_negs_sds, win_alpha_sds).compile()
        rows = parse_collectives(compiled.as_text(), nd, nm)
        ls = summarize(rows, assembly_rows=2 * (b // nd) + pool,
                       assembly_count=k)
        ls["sync_every"] = k
        # per-WINDOW data-axis bytes vs what each k=1 schedule pays over the
        # same k steps. The acceptance ratio is against the DEFAULT (gspmd)
        # synchronous schedule — "the k=1 schedule" a data-parallel run pays
        # today; the shard_map-baseline ratio is reported beside it (that
        # schedule's per-step payload all_gather is batch-sized, so the dense
        # [Vs, D] merge amortizes against it more slowly).
        win_data = ls["bytes_by_axis"].get("data", 0)
        g_data = out["gspmd"]["bytes_by_axis"].get("data", 0)
        s_data = out["shard_map"]["bytes_by_axis"].get("data", 0)
        ls["window_data_bytes"] = win_data
        ls["window_data_over_gspmd_k1_schedule"] = (
            win_data / (k * g_data) if g_data else None)
        ls["window_data_over_shard_map_k1_schedule"] = (
            win_data / (k * s_data) if s_data else None)
        out["localsgd"] = ls
    return out


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometry (the tier-1 wiring)")
    ap.add_argument("--mesh", default="all",
                    help="'NDxNM' (e.g. 2x4) or 'all' (1x8,2x4,4x2,8x1)")
    ap.add_argument("--sync-every", type=int, default=8,
                    help="local-SGD window length k for the 'localsgd' "
                         "variant (config.sync_every; 0/1 = skip the "
                         "variant). The window program is audited per "
                         "WINDOW — k steps + one delta-merge")
    ap.add_argument("--json-out", default="",
                    help="also write the JSON result to this path")
    args = ap.parse_args(argv)

    import jax
    n = len(jax.devices())
    if n < 8:
        raise SystemExit(
            f"need 8 devices (have {n}); run as a script so the CPU mesh "
            "self-provisions, or set --xla_force_host_platform_device_count=8")

    geom = build_geometry(args)
    shapes = ([(1, 8), (2, 4), (4, 2), (8, 1)] if args.mesh == "all"
              else [tuple(int(x) for x in args.mesh.split("x"))])
    result = {"geometry": geom, "meshes": []}
    if geom["param_dtype"] == "bfloat16":
        # the CPU backend's float-normalization pass rewrites bf16 compute
        # (collectives included) to f32, so the audited payloads appear at
        # 4 bytes/element: absolute bytes here are 2x the TPU bf16 wire
        # payloads, UNIFORMLY for both lowerings — the per-axis structure,
        # op counts, and every ratio are dtype-independent
        result["note"] = ("bf16 collectives observed as f32 (CPU float "
                          "normalization); absolute bytes are 2x the TPU "
                          "bf16 payloads, ratios unaffected")
    for shape in shapes:
        log(f"compiling both lowerings at mesh {shape[0]}x{shape[1]} "
            f"(V={geom['v']:,}, B={geom['b']}, D={geom['d']}, "
            f"pool={geom['pool']}, {geom['param_dtype']}) ...")
        res = audit_mesh(geom, shape, sync_every=max(args.sync_every, 1))
        result["meshes"].append(res)
        for name in ("gspmd", "shard_map", "gspmd_cols", "localsgd"):
            if name not in res:
                continue
            s = res[name]
            log(f"  {name:9s} total {s['total_bytes'] / 1e6:10.2f} MB over "
                f"{s['count']} collectives  by-axis: "
                + ", ".join(f"{a}={v / 1e6:.2f} MB"
                            for a, v in sorted(s["bytes_by_axis"].items())))
            for r in s["collectives"]:
                log(f"      {r['op']:20s} {r['axis']:6s} "
                    f"{r['bytes'] / 1e6:10.3f} MB  {r['shape'][:60]}")
        sm = res["shard_map"]
        log(f"  shard_map model-axis UPDATE bytes: "
            f"{sm['model_axis_update_bytes']} "
            f"(forward assembly matched: "
            f"{sm['forward_assembly_bytes'] / 1e6:.2f} MB); "
            f"bytes ratio shard_map/gspmd: "
            f"{res['bytes_ratio_shard_map_over_gspmd']:.3f}"
            if res["bytes_ratio_shard_map_over_gspmd"] is not None else
            "  gspmd emitted no collectives at this mesh")
        if "localsgd" in res:
            ls = res["localsgd"]
            rg = ls["window_data_over_gspmd_k1_schedule"]
            rs = ls["window_data_over_shard_map_k1_schedule"]
            log(f"  localsgd (k={ls['sync_every']}) per-WINDOW data bytes "
                f"{ls['window_data_bytes'] / 1e6:.2f} MB; model-axis UPDATE "
                f"bytes {ls['model_axis_update_bytes']} (assembly psums "
                f"matched: {ls['forward_assembly_count']}); window/k-step "
                f"ratios: vs gspmd k=1 "
                + (f"{rg:.4f}" if rg is not None else "n/a (no data axis)")
                + ", vs shard_map k=1 "
                + (f"{rs:.4f}" if rs is not None else "n/a"))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main(argv=None) -> None:
    result = run(argv)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
