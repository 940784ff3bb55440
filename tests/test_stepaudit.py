"""stepaudit (layer-2 compiled-step contract auditor, ISSUE 5): the four step
variants (rows-GSPMD, shard_map, cols, banded CBOW) plus the bf16 dtype twin
pass all four compiled-artifact contracts — donation present, zero implicit
transfers under jax.transfer_guard("disallow"), no f64 / no dense f32 [V, D]
in bf16 mode, exactly one jit compilation — and the auditor demonstrably
CATCHES each regression class (dropped donate_argnums; dropped explicit
staging)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import stepaudit  # noqa: E402


def test_stepaudit_smoke_all_variants():
    """Subprocess run of the tier-1/CI wiring: all variants pass all four
    contracts and the structural fields match the committed STEPAUDIT.json
    baseline (drift = a contract changed — review it, then regenerate with
    `python tools/stepaudit.py --smoke --json-out STEPAUDIT.json`)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "stepaudit.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"]
    assert set(result["variants"]) == set(
        stepaudit.VARIANTS) | {stepaudit.BF16_VARIANT}
    for name, r in result["variants"].items():
        assert r["donation"]["ok"] and r["donation"]["aliased_params"] >= 2, (
            name, r)
        assert r["transfers"]["ok"] and r["transfers"]["dispatches"] >= 2, (
            name, r)
        assert r["dtype"]["f64_free"], (name, r)
        assert r["recompile"]["compiles"] == 1, (name, r)
    bf16 = result["variants"][stepaudit.BF16_VARIANT]
    assert bf16["dtype"]["dense_f32_vd_free"] is True
    # the ISSUE-14 end-to-end bf16 chain: no dense f32 [B, D] intermediate
    # survives in the lowered module (the classic chain's f_pos convert)
    chain = result["variants"]["rows_gspmd_bf16_chain"]
    assert chain["dtype"]["dense_f32_bd_free"] is True
    assert chain["dtype"]["dense_f32_vd_free"] is True
    # the recover-rebuild contract (ISSUE 8): one recovery, twins rebuilt
    # once, exactly one extra compile — 2 total for the whole
    # blowup-and-recover fit
    rr = result["recover_rebuild"]
    assert rr["ok"] and rr["recoveries"] == 1 and rr["rebuilt"], rr
    assert rr["total_compiles"] == rr["expected_total_compiles"] == 2, rr

    with open(os.path.join(REPO, "STEPAUDIT.json"), "r") as f:
        baseline = json.load(f)
    assert set(baseline["variants"]) == set(result["variants"])
    for name in result["variants"]:
        for field in ("donation", "dtype", "recompile"):
            assert result["variants"][name][field] == \
                baseline["variants"][name][field], (name, field)
    assert result["recover_rebuild"] == baseline["recover_rebuild"]


def test_auditor_catches_dropped_donation():
    """The ISSUE's regression test: a toy step compiled WITHOUT
    donate_argnums must be flagged by the donation parser; the same step
    WITH donation passes."""
    def step(params, batch):
        syn0, syn1 = params
        return (syn0 + batch.sum(), syn1 * 2), batch

    params = (jnp.ones((16, 8)), jnp.ones((16, 8)))
    batch = jnp.ones((4,))

    donated = jax.jit(step, donate_argnums=(0,)).lower(
        params, batch).compile().as_text()
    ok = stepaudit.donation_summary(donated)
    assert ok["ok"] and ok["aliased_params"] >= 2, ok

    dropped = jax.jit(step).lower(params, batch).compile().as_text()
    bad = stepaudit.donation_summary(dropped)
    assert not bad["ok"] and bad["aliased_params"] == 0, bad


def test_auditor_catches_dropped_staging(monkeypatch):
    """Re-introducing an implicit host→device transfer at dispatch (the exact
    regression the explicit _stage_dispatch_meta discipline prevents) must
    fail the transfer-guard contract — while donation and dtype still report,
    so one broken contract does not mask the others."""
    from glint_word2vec_tpu.train.trainer import Trainer

    monkeypatch.setattr(
        Trainer, "_stage_dispatch_meta",
        lambda self, meta, base_step, *bases: (
            np.asarray(meta, np.float32), np.int32(base_step), *bases))
    res = stepaudit.audit_variant(
        "rows_gspmd", (2, 4), stepaudit.smoke_geometry())
    assert not res["transfers"]["ok"]
    assert "transfer" in (res["transfers"]["error"] or "").lower()
    assert not res["ok"]


def test_auditor_catches_recovery_without_rebuild(monkeypatch):
    """The recover-rebuild audit's own regression coverage: a recovery that
    rolls back and backs lr off but never rebuilds the step twins (so the
    engaged clamp would silently not exist in the compiled step) must fail
    the contract."""
    from glint_word2vec_tpu.train.trainer import Trainer

    def no_rebuild(self, reason, channels):
        self._restore_snapshot()
        self.recoveries_performed += 1
        self._lr_scale *= self.config.recover_lr_backoff

    monkeypatch.setattr(Trainer, "_perform_recovery", no_rebuild)
    res = stepaudit.audit_recover_rebuild(stepaudit.smoke_geometry())
    assert res["recoveries"] == 1
    assert not res["rebuilt"] and res["compiles_after"] == 0
    assert not res["ok"], res


def test_audit_variant_in_process_shard_map():
    """One in-process audit (shard_map — the lowering whose schedule the
    collective auditor guards) so contract failures debug without subprocess
    indirection."""
    res = stepaudit.audit_variant(
        "shard_map", (2, 4), stepaudit.smoke_geometry())
    assert res["ok"], res
    assert res["recompile"] == {"compiles": 1, "expected": 1, "ok": True}
