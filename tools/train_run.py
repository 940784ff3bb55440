#!/usr/bin/env python
"""Supervised-training driver CLI (docs/robustness.md §supervisor): run a
fit under the TrainingSupervisor's die→diagnose→resume loop — preemption-
deadline checkpointing, decorrelated-jitter restarts, hang detection,
crash-loop quarantine, peer-death gang restarts.

Stdout carries exactly ONE JSON line (graftlint R7 — the driver contract);
human progress goes to stderr.

Usage::

    # supervise an arbitrary training command (gang: repeat --cmd/--log)
    python tools/train_run.py --cmd "python my_fit.py" --log run.jsonl \
        --checkpoint-dir ckpts [--max-restarts N] [--stall-s S]
        [--loop-window W] [--workdir DIR]

    # the self-contained supervisor drills (tier-1 + CI): a SIGTERM'd fit
    # emergency-checkpoints within its deadline and resumes to match an
    # uninterrupted twin's purity gate; an injected in-step stall is
    # detected and killed+resumed; a deterministic crash loop is
    # quarantined with a machine-readable verdict in bounded attempts
    python tools/train_run.py --smoke
    python tools/train_run.py --drill preempt|stall|crashloop

Exit code 0 iff the supervised run ended "ok" (or the drill's every
assertion passed).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_here))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- drill corpus / config ------------------------------------------------

# two co-occurrence clusters that NEVER share a sentence: the purity gate
# below only needs nearest neighbors to stay inside their own cluster — a
# structure even a two-iteration toy fit learns, and one that a resumed
# run that lost real progress (or re-trained the wrong batches) breaks
_CLUSTER_A = [f"a{i}" for i in range(15)]
_CLUSTER_B = [f"b{i}" for i in range(15)]


def cluster_sentences(n_sentences: int, seed: int = 0):
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    for s in range(n_sentences):
        pool = _CLUSTER_A if s % 2 == 0 else _CLUSTER_B
        out.append([pool[j] for j in rng.integers(0, len(pool), 20)])
    return out


def drill_config(**kw):
    from glint_word2vec_tpu.config import Word2VecConfig
    return Word2VecConfig(
        vector_size=16, pairs_per_batch=128, window=3, num_iterations=2,
        steps_per_dispatch=2, heartbeat_every_steps=2, subsample_ratio=0.0,
        prefetch_chunks=0, seed=1, min_count=1, **kw)


def _cluster_purity(words, syn0) -> float:
    """Mean fraction of each probe word's top-4 cosine neighbors that sit
    in its own cluster (the continual-drift phase's neighbor rule, as a
    scalar both arms of the preempt drill must clear)."""
    import numpy as np
    idx = {w: i for i, w in enumerate(words)}
    emb = np.asarray(syn0, np.float64)
    emb = emb / np.maximum(np.linalg.norm(emb, axis=1, keepdims=True), 1e-12)
    fracs = []
    for cluster in (_CLUSTER_A, _CLUSTER_B):
        for probe in cluster[:3]:
            i = idx[probe]
            sims = emb @ emb[i]
            sims[i] = -np.inf
            top = np.argsort(-sims)[:4]
            same = sum(1 for j in top if words[j] in cluster)
            fracs.append(same / 4.0)
    return float(np.mean(fracs))


# -- the worker leg -------------------------------------------------------

def worker_fit(workdir: str, n_sentences: int) -> int:
    """One supervised fit attempt: resume from the newest verified
    checkpoint under <workdir>/ckpt when one exists, else fit fresh —
    exactly the ``load_latest_valid`` resume contract the supervisor
    restarts around. Honors the supervisor's mitigation ladder
    (GLINT_SUPERVISOR_MITIGATE=1 engages the trainer's existing
    norm_watch="recover" stabilizer/lr-backoff arm) and exits
    PEER_ABORT_EXIT on a peer-death abort so the supervisor can tell the
    victim from the cause."""
    from glint_word2vec_tpu.models.estimator import Word2Vec
    from glint_word2vec_tpu.train.checkpoint import load_latest_valid
    from glint_word2vec_tpu.train.supervisor import (
        MITIGATE_ENV, PEER_ABORT_EXIT, PeerDeathError)

    ckdir = os.path.join(workdir, "ckpt")
    ck = os.path.join(ckdir, "model")
    os.makedirs(ckdir, exist_ok=True)
    sentences = cluster_sentences(n_sentences, seed=3)
    mitigate = os.environ.get(MITIGATE_ENV) == "1"
    overrides = {"norm_watch": "recover"} if mitigate else {}
    try:
        existing = load_latest_valid(ckdir)
    except FileNotFoundError:
        existing = None
    try:
        if existing is not None:
            log(f"[worker] resuming from {existing}"
                + (" (mitigations engaged)" if mitigate else ""))
            Word2Vec.resume(existing, sentences, checkpoint_every_steps=4,
                            config_overrides=overrides or None)
        else:
            log("[worker] fresh fit"
                + (" (mitigations engaged)" if mitigate else ""))
            cfg = drill_config(
                telemetry_path=os.path.join(workdir, "run.jsonl"),
                checkpoint_on_preempt=True, **overrides)
            Word2Vec(cfg).fit(sentences, checkpoint_path=ck,
                              checkpoint_every_steps=4)
    except PeerDeathError as e:
        log(f"[worker] peer death: {e}")
        return PEER_ABORT_EXIT
    return 0


# -- supervision plumbing shared by the drills ----------------------------

def _drill_supervisor(workdir: str, n_sentences: int, telemetry,
                      **kw):
    from glint_word2vec_tpu.train.supervisor import TrainingSupervisor
    cmd = [sys.executable, os.path.abspath(__file__), "--worker", "fit",
           "--workdir", workdir, "--sentences", str(n_sentences)]
    return TrainingSupervisor(
        [cmd], workdir, child_logs=[os.path.join(workdir, "run.jsonl")],
        checkpoint_dir=os.path.join(workdir, "ckpt"),
        telemetry=telemetry, poll_s=0.1, term_grace_s=2.0,
        backoff_base_s=0.02, backoff_cap_s=0.2, seed=7, **kw)


def _final_header(workdir: str):
    from glint_word2vec_tpu.train.checkpoint import (
        load_latest_valid, load_model_header)
    return load_model_header(
        load_latest_valid(os.path.join(workdir, "ckpt")))


# -- drills ---------------------------------------------------------------

def run_preempt_drill(workdir: str, n_sentences: int = 200) -> dict:
    """train-preempt: SIGTERM mid-fit (scripted crash_at_step — the
    handler defers it into the preemption-deadline path) → emergency
    checkpoint published + verified with ≤ one dispatch chunk lost →
    supervisor resumes → the final model reaches the uninterrupted twin's
    exact final step and passes the same purity gate."""
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.obs.sink import TelemetrySink
    from glint_word2vec_tpu.train.checkpoint import load_model
    from glint_word2vec_tpu.train.trainer import Trainer

    # the uninterrupted twin, in-process: same corpus, same config seed —
    # its final step and purity are the bar the supervised arm must meet
    sentences = cluster_sentences(n_sentences, seed=3)
    vocab = build_vocab(sentences, min_count=1)
    twin = Trainer(drill_config(), vocab)
    twin.fit(encode_sentences(sentences, vocab, 1000))
    twin_step = int(twin.global_step)
    twin_purity = _cluster_purity(vocab.words,
                                  twin.unpadded_params().syn0)
    log(f"[preempt] twin finished: step={twin_step} "
        f"purity={twin_purity:.3f}")
    assert twin_purity >= 0.75, \
        f"twin purity {twin_purity:.3f} too weak to gate on"

    def fault_env(attempt: int) -> dict:
        if attempt == 0:
            # deterministic preemption: the scripted self-SIGTERM fires in
            # _finish_round, the fit-scoped handler defers it, and the SAME
            # round's tail drains the emergency save — no timing races
            return {"GLINT_FAULT_CRASH_AT_STEP": "6",
                    "GLINT_FAULT_CRASH_SIGNAL": "TERM"}
        return {"GLINT_FAULT_CRASH_AT_STEP": ""}

    sink = TelemetrySink(os.path.join(workdir, "supervisor.jsonl"))
    try:
        sup = _drill_supervisor(workdir, n_sentences, sink,
                                max_restarts=3, stall_s=60.0,
                                env_for_attempt=fault_env)
        verdict = sup.run()
    finally:
        sink.close()
    assert verdict.status == "ok", f"supervised run failed: {verdict}"
    assert verdict.attempts == 2, \
        f"expected exactly 2 attempts (preempt + resume), got {verdict}"
    first = verdict.history[0]
    assert first["cls"] == "preempt", \
        f"first attempt classified {first['cls']!r}, want preempt: {verdict}"
    # the trainer's own preempt record: emergency save made the deadline
    pre = None
    with open(os.path.join(workdir, "run.jsonl"), encoding="utf-8") as f:
        for line in f:
            r = json.loads(line)
            if r.get("kind") == "preempt":
                pre = r
    assert pre is not None, "no preempt record in the worker sink"
    assert pre["saved"], f"emergency checkpoint missed the deadline: {pre}"
    # ≤ one dispatch chunk (steps_per_dispatch=2) of progress at risk
    assert pre["steps_since_save"] <= 2, f"lost too much progress: {pre}"
    header = _final_header(workdir)
    ts = header["train_state"]
    assert ts.finished, f"final checkpoint not finished: {ts}"
    assert int(ts.global_step) == twin_step, \
        f"resumed final step {ts.global_step} != twin {twin_step}"
    data = load_model(os.path.join(workdir, "ckpt", "model"))
    purity = _cluster_purity(data["words"], data["syn0"])
    gate = min(0.75, twin_purity)
    assert purity >= gate, \
        f"resumed purity {purity:.3f} under the twin's gate {gate:.3f}"
    log(f"[preempt] PASS: resumed to step {ts.global_step}, "
        f"purity {purity:.3f} (twin {twin_purity:.3f})")
    return {"ok": True, "twin_step": twin_step,
            "final_step": int(ts.global_step),
            "purity": round(purity, 4), "twin_purity": round(twin_purity, 4),
            "preempt": {k: pre[k] for k in
                        ("step", "saved", "steps_since_save")},
            "attempts": verdict.attempts}


def run_stall_drill(workdir: str, n_sentences: int = 200) -> dict:
    """train-stall: an injected in-step stall (faults.stall_at_step) wedges
    the fit; the supervisor's hang watchdog must detect the silence within
    2×stall_s, capture a diagnostic (SIGTERM → flight-recorder dump, then
    SIGKILL), count it as a failure, and resume to completion."""
    from glint_word2vec_tpu.obs.sink import TelemetrySink

    def fault_env(attempt: int) -> dict:
        if attempt == 0:
            return {"GLINT_FAULT_STALL_AT_STEP": "6",
                    "GLINT_FAULT_STALL_S": "120"}
        return {"GLINT_FAULT_STALL_AT_STEP": ""}

    # above a worker's boot (import jax alone is ~2 s on this installation —
    # at 2.0 every attempt was killed as stalled at step 0, before its first
    # telemetry record), far below the injected 120 s wedge
    stall_s = 8.0
    sink = TelemetrySink(os.path.join(workdir, "supervisor.jsonl"))
    try:
        sup = _drill_supervisor(workdir, n_sentences, sink,
                                max_restarts=3, stall_s=stall_s,
                                env_for_attempt=fault_env)
        verdict = sup.run()
    finally:
        sink.close()
    assert verdict.status == "ok", f"supervised run failed: {verdict}"
    assert verdict.attempts == 2, \
        f"expected exactly 2 attempts (stall + resume), got {verdict}"
    first = verdict.history[0]
    assert first["cls"] == "stall", \
        f"first attempt classified {first['cls']!r}, want stall: {verdict}"
    # detection bound + diagnostic: the supervisor_stall record and the
    # dump the TERM-first kill requested from the wedged child
    stall_rec = None
    with open(os.path.join(workdir, "supervisor.jsonl"),
              encoding="utf-8") as f:
        for line in f:
            r = json.loads(line)
            if r.get("kind") == "supervisor_stall":
                stall_rec = r
    assert stall_rec is not None, "no supervisor_stall record"
    assert stall_rec["stalled_s"] <= 2 * stall_s + 1.0, \
        f"stall detected too late: {stall_rec}"
    dump = os.path.join(workdir, "run.jsonl.blackbox.json")
    assert os.path.exists(dump), \
        "stalled child left no flight-recorder dump (TERM diagnostic lost)"
    header = _final_header(workdir)
    assert header["train_state"].finished, "resumed run did not finish"
    log(f"[stall] PASS: detected after {stall_rec['stalled_s']:.1f}s at "
        f"step {stall_rec['last_step']}, resumed to completion")
    return {"ok": True, "stalled_s": stall_rec["stalled_s"],
            "last_step": stall_rec["last_step"],
            "final_step": int(header["train_state"].global_step),
            "attempts": verdict.attempts}


def run_crashloop_drill(workdir: str, n_sentences: int = 200) -> dict:
    """train-crashloop: the same deterministic crash (SIGKILL at a scripted
    step) on EVERY attempt — the supervisor must classify the repeated
    (step, cause) signature as a deterministic loop, walk the escalation
    ladder (stage 1 mitigations, stage 2 halt), and quarantine with a
    machine-readable verdict in bounded attempts — never an unbounded
    restart loop."""
    from glint_word2vec_tpu.obs.sink import TelemetrySink

    env = {"GLINT_FAULT_CRASH_AT_STEP": "6",
           "GLINT_FAULT_CRASH_SIGNAL": "KILL"}
    max_restarts = 6
    sink = TelemetrySink(os.path.join(workdir, "supervisor.jsonl"))
    try:
        sup = _drill_supervisor(workdir, n_sentences, sink,
                                max_restarts=max_restarts, stall_s=60.0,
                                loop_window=2, env=env)
        verdict = sup.run()
    finally:
        sink.close()
    assert verdict.status == "quarantined", \
        f"deterministic loop not quarantined: {verdict}"
    assert verdict.classification == "deterministic-crash-loop", \
        f"wrong classification: {verdict}"
    assert verdict.attempts <= max_restarts, \
        f"quarantine took {verdict.attempts} attempts (> {max_restarts})"
    stages = [l["stage"] for l in verdict.ladder]
    assert stages == [1, 2], \
        f"escalation ladder did not walk 1→2: {verdict.ladder}"
    vpath = os.path.join(workdir, "verdict.json")
    assert os.path.exists(vpath), "no machine-readable verdict.json"
    with open(vpath, encoding="utf-8") as f:
        doc = json.load(f)
    assert doc["status"] == "quarantined" and doc["signature"], \
        f"verdict.json incomplete: {doc}"
    log(f"[crashloop] PASS: quarantined {doc['signature']!r} after "
        f"{verdict.attempts} attempts")
    return {"ok": True, "attempts": verdict.attempts,
            "signature": doc["signature"], "ladder": stages}


def run_smoke(workdir: str, n_sentences: int = 200) -> dict:
    """All three supervisor drills, one report (the CI supervisor job's
    single artifact)."""
    report = {}
    for name, fn in (("preempt", run_preempt_drill),
                     ("stall", run_stall_drill),
                     ("crashloop", run_crashloop_drill)):
        sub = os.path.join(workdir, name)
        os.makedirs(sub, exist_ok=True)
        log(f"[smoke] --- {name} drill ---")
        report[name] = fn(sub, n_sentences)
    # the supervisor sinks must be schema-valid end to end (the new
    # supervisor_* kinds are registered, not grandfathered)
    from glint_word2vec_tpu.obs.schema import validate_file
    for name in ("preempt", "stall", "crashloop"):
        v = validate_file(os.path.join(workdir, name, "supervisor.jsonl"))
        assert v["ok"], f"{name} supervisor sink schema-invalid: " \
                        f"{v['errors'][:3]}"
    report["ok"] = all(r.get("ok") for r in report.values())
    return report


# -- generic supervised-run mode ------------------------------------------

def run_supervised(args) -> dict:
    from glint_word2vec_tpu.obs.sink import TelemetrySink
    from glint_word2vec_tpu.train.supervisor import TrainingSupervisor
    workdir = args.workdir or tempfile.mkdtemp(prefix="glint_train_run_")
    os.makedirs(workdir, exist_ok=True)
    commands = [c.split() if isinstance(c, str) else c for c in args.cmd]
    sink = None
    if args.telemetry:
        sink = TelemetrySink(args.telemetry)
    try:
        sup = TrainingSupervisor(
            commands, workdir, child_logs=args.log,
            checkpoint_dir=args.checkpoint_dir, telemetry=sink,
            max_restarts=args.max_restarts, stall_s=args.stall_s,
            loop_window=args.loop_window, seed=args.seed)
        if args.status_port:
            from glint_word2vec_tpu.obs.statusd import (
                StatusServer, supervisor_prometheus_text)
            statusd = StatusServer(
                args.status_port, sup.status_snapshot,
                metrics_fn=supervisor_prometheus_text).start()
        else:
            statusd = None
        try:
            verdict = sup.run()
        finally:
            if statusd is not None:
                statusd.stop()
    finally:
        if sink is not None:
            sink.close()
    return {"ok": verdict.status == "ok", "mode": "supervise",
            **verdict.to_dict()}


def main() -> int:
    from glint_word2vec_tpu.config import Word2VecConfig
    defaults = Word2VecConfig()
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--cmd", action="append", default=[],
                    help="training command to supervise (repeat for a "
                         "multi-process gang)")
    ap.add_argument("--log", action="append", default=[],
                    help="telemetry sink path the matching --cmd writes "
                         "(the supervisor's progress/classification window)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="directory load_latest_valid resumes from "
                         "(and the restart audit verifies)")
    ap.add_argument("--telemetry", default="",
                    help="write supervisor_* telemetry records here")
    ap.add_argument("--status-port", type=int, default=0,
                    help="> 0: serve glint_supervisor_* gauges on "
                         "127.0.0.1:<port>")
    ap.add_argument("--max-restarts", type=int,
                    default=defaults.supervisor_max_restarts)
    ap.add_argument("--stall-s", type=float,
                    default=defaults.supervisor_stall_s)
    ap.add_argument("--loop-window", type=int,
                    default=defaults.supervisor_loop_window)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="run the three supervisor drills (tier-1/CI) "
                         "in a temp dir")
    ap.add_argument("--drill", choices=["preempt", "stall", "crashloop"],
                    help="run ONE drill (the chaos phases call these)")
    ap.add_argument("--worker", choices=["fit"],
                    help="internal: one supervised fit attempt")
    ap.add_argument("--workdir", default="")
    ap.add_argument("--sentences", type=int, default=200)
    args = ap.parse_args()

    if args.worker == "fit":
        return worker_fit(args.workdir, args.sentences)

    if args.smoke or args.drill:
        workdir = args.workdir or tempfile.mkdtemp(prefix="glint_sup_")
        os.makedirs(workdir, exist_ok=True)
        try:
            if args.drill:
                fn = {"preempt": run_preempt_drill,
                      "stall": run_stall_drill,
                      "crashloop": run_crashloop_drill}[args.drill]
                out = fn(workdir, args.sentences)
            else:
                out = run_smoke(workdir, args.sentences)
        except AssertionError as e:
            out = {"ok": False, "error": str(e)}
        finally:
            if not args.workdir:
                shutil.rmtree(workdir, ignore_errors=True)
        print(json.dumps(out))
        return 0 if out.get("ok") else 1

    if not args.cmd:
        ap.error("pass --cmd (with --log per command) to supervise a run, "
                 "or --smoke / --drill for the self-contained drills")
    if len(args.log) != len(args.cmd):
        ap.error("need exactly one --log per --cmd")
    out = run_supervised(args)
    print(json.dumps(out))
    return 0 if out.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
