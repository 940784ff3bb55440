"""Preemption-safe training supervisor suite (docs/robustness.md
§supervisor): SIGTERM landing INSIDE the checkpoint-save window (both
orderings — the atomic protocol must leave old-or-new verified, never
torn), the restart/quarantine state machine over scripted subprocess
children, the peer-liveness beacon board, the injected in-step stall
fault, and the "preempted" run-report status.

The save-window crashes run as subprocesses because the default SIGTERM
disposition is the fault model under test: no handler installed, the
process dies mid-save exactly where the signal lands. The supervisor
state-machine tests use trivial ``python -c`` children — the
classification/ladder logic needs exit codes and silence, not a real
fit (the real-fit proof is tools/train_run.py's drills, wired into
chaos --smoke)."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from glint_word2vec_tpu.train import faults
from glint_word2vec_tpu.train.checkpoint import (
    load_latest_valid,
    verify_checkpoint,
)
from glint_word2vec_tpu.train.supervisor import (
    MITIGATE_ENV,
    PEER_ABORT_EXIT,
    BeaconBoard,
    PeerDeathError,
    TrainingSupervisor,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# -- SIGTERM inside the checkpoint-save window -----------------------------


@pytest.mark.parametrize("point", ["save:staged@2", "save:swap@2"])
def test_sigterm_during_save_window(tmp_path, point):
    """A preemption SIGTERM landing mid-save — before the staged tmp is
    blessed ("staged") or inside the swap's torn window ("swap") — must
    leave a recoverable directory either way: ``load_latest_valid``
    reclaims the debris and returns a checkpoint that VERIFIES (the old
    one or the new one, never a torn hybrid)."""
    workdir = str(tmp_path / "w")
    os.makedirs(workdir)
    rc = subprocess.call(
        [sys.executable, os.path.join(_REPO, "tools", "chaos_run.py"),
         "--worker", "crash", "--workdir", workdir, "--sentences", "120"],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 GLINT_FAULT_CRASH_POINT=point,
                 GLINT_FAULT_CRASH_SIGNAL="TERM"),
        cwd=_REPO, timeout=300,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    assert rc in (-15, 143), f"worker exited {rc}, expected SIGTERM"
    # the interrupted save left debris; recovery must step around it
    ck = load_latest_valid(workdir)
    meta = verify_checkpoint(ck)
    step = meta["train_state"]["global_step"]
    assert step > 0 and not meta["train_state"]["finished"], meta
    # reclaim happened: a fresh scan sees no staging/old debris
    entries = os.listdir(workdir)
    assert not any(".tmp-" in e for e in entries), entries


# -- the supervisor state machine (scripted children) ----------------------


def _child(script: str) -> list:
    return [sys.executable, "-c", script]


def _supervisor(tmp_path, commands, **kw):
    kw.setdefault("poll_s", 0.02)
    kw.setdefault("term_grace_s", 0.3)
    kw.setdefault("backoff_base_s", 0.01)
    kw.setdefault("backoff_cap_s", 0.05)
    workdir = str(tmp_path)
    logs = kw.pop("child_logs",
                  [os.path.join(workdir, f"c{i}.jsonl")
                   for i in range(len(commands))])
    return TrainingSupervisor(commands, workdir, child_logs=logs, **kw)


def test_clean_child_is_ok(tmp_path):
    sup = _supervisor(tmp_path, [_child("raise SystemExit(0)")],
                      max_restarts=3, stall_s=30.0)
    v = sup.run()
    assert v.status == "ok" and v.attempts == 1
    assert not os.path.exists(os.path.join(str(tmp_path), "verdict.json"))


def test_deterministic_crash_loop_quarantines(tmp_path):
    """The same exit code at the same (step-bucketed) position on every
    attempt: after ``loop_window`` identical signatures the ladder
    engages mitigations and clears the window; after a second full
    window it halts with a machine-readable quarantine verdict — never
    an unbounded restart loop."""
    sup = _supervisor(tmp_path, [_child("raise SystemExit(7)")],
                      max_restarts=6, stall_s=30.0, loop_window=2)
    v = sup.run()
    assert v.status == "quarantined"
    assert v.classification == "deterministic-crash-loop"
    assert v.attempts == 4 <= 6  # 2 per ladder stage, well under budget
    assert [l["stage"] for l in v.ladder] == [1, 2]
    assert "rc7" in v.signature
    # stage 1 engaged the mitigation env for every later attempt
    assert sup.env.get(MITIGATE_ENV) == "1"
    with open(os.path.join(str(tmp_path), "verdict.json")) as f:
        doc = json.load(f)
    assert doc["status"] == "quarantined" and doc["signature"] == v.signature


def test_nondeterministic_crashes_exhaust_budget(tmp_path):
    """DIFFERENT failure signatures never match the loop window — the
    supervisor keeps restarting until the budget runs out and reports
    gave-up (restarting might have helped; it just didn't)."""
    script = "import os; raise SystemExit(int(os.environ['RC']))"
    sup = _supervisor(tmp_path, [_child(script)], max_restarts=2,
                      stall_s=30.0, loop_window=2,
                      env_for_attempt=lambda a: {"RC": str(40 + a)})
    v = sup.run()
    assert v.status == "gave-up"
    assert v.classification == "restart-budget-exhausted"
    assert v.attempts == 3  # initial + max_restarts


def test_stall_detected_killed_and_resumed(tmp_path):
    """A child that goes silent past ``stall_s`` is killed (counted as a
    stall, not a crash) and the run is retried; the retry succeeding
    ends the whole supervised run ok."""
    script = ("import os, time\n"
              "if os.environ.get('STALL') == '1':\n"
              "    time.sleep(60)\n")
    sup = _supervisor(tmp_path, [_child(script)], max_restarts=3,
                      stall_s=0.4,
                      env_for_attempt=lambda a:
                      {"STALL": "1" if a == 0 else "0"})
    t0 = time.monotonic()
    v = sup.run()
    took = time.monotonic() - t0
    assert v.status == "ok" and v.attempts == 2
    assert v.history[0]["cls"] == "stall"
    assert sup.stalls == 1
    assert took < 10.0, f"stall kill path took {took:.1f}s"


def test_peer_death_restarts_whole_gang(tmp_path):
    """In a gang, one member exiting with the peer-abort code (a survivor
    fleeing a dead peer's collective) is NOT the root cause: the attempt
    classifies as peer-death and the WHOLE gang restarts together."""
    # the clean attempt's two children leave together (each waits until both
    # have checked in): started on a loaded host they would otherwise exit
    # as far apart as their interpreters came up
    script = ("import os, time\n"
              "rc = int(os.environ['MY_RC'])\n"
              "if rc == 0:\n"
              "    d = os.environ['BARRIER']\n"
              "    os.makedirs(d, exist_ok=True)\n"
              "    open(os.path.join(d, str(os.getpid())), 'w').close()\n"
              "    until = time.time() + 20\n"
              "    while len(os.listdir(d)) < 2 and time.time() < until:\n"
              "        time.sleep(0.005)\n"
              "raise SystemExit(rc)\n")
    calls = []

    def env_for(attempt):
        calls.append(attempt)
        return {"MY_RC": str(PEER_ABORT_EXIT) if attempt == 0 else "0",
                "BARRIER": str(tmp_path / f"barrier{attempt}")}

    # poll_s well above the few ms between the two children's exits: a poll
    # that lands between two CLEAN exits trips the gang rule (the rc-0 race
    # _classify documents) and costs a third attempt — at the default 0.02 s
    # that happened in 2 of 5 serial tier-1 runs
    sup = _supervisor(tmp_path, [_child(script), _child(script)],
                      max_restarts=3, stall_s=30.0, env_for_attempt=env_for,
                      poll_s=0.5)
    v = sup.run()
    assert v.status == "ok" and v.history[0]["cls"] == "peer-death"
    assert v.history[-1]["cls"] == "ok" and calls == list(range(v.attempts))
    # two attempts, or one more for each time the rc-0 race still landed (a
    # loaded host starts the two children further apart than any poll): such
    # an attempt failed by the gang rule's own TERM and by nothing else
    assert 2 <= v.attempts <= 4
    assert all(h["cls"] == "crash" and h["rc"] == -signal.SIGTERM
               for h in v.history[1:-1]), v.history


def test_gang_partial_death_kills_survivors(tmp_path):
    """One gang member crashing while the other would run on forever: the
    supervisor must reap the survivor itself (it would otherwise hang in
    a collective that can never complete) and classify by the member
    that died on its own."""
    crasher = _child("raise SystemExit(9)")
    sleeper = _child("import time; time.sleep(60)")
    sup = _supervisor(tmp_path, [crasher, sleeper], max_restarts=0,
                      stall_s=30.0)
    t0 = time.monotonic()
    v = sup.run()
    took = time.monotonic() - t0
    assert v.status == "gave-up" and v.attempts == 1
    assert v.history[0]["cls"] == "crash"
    assert "rc9" in v.history[0]["signature"]
    assert took < 10.0, f"survivor reap took {took:.1f}s"


# -- beacon board ----------------------------------------------------------


def test_beacons_fresh_and_not_yet_joined(tmp_path):
    b0 = BeaconBoard(str(tmp_path), 0, 3, interval_s=10.0)
    b0._touch()
    # peer 1 joined and is fresh; peer 2 never joined (slow start) — only
    # a beacon that was SEEN and then went quiet may count as dead
    BeaconBoard(str(tmp_path), 1, 3, interval_s=10.0)._touch()
    assert b0.stale_peers(60.0) == []
    b0.check_or_raise()


def test_beacon_stale_mtime_raises(tmp_path):
    b0 = BeaconBoard(str(tmp_path), 0, 2, interval_s=0.1)
    b0._touch()
    b1 = BeaconBoard(str(tmp_path), 1, 2, interval_s=0.1)
    b1._touch()
    old = time.time() - 3600
    os.utime(b1.path_for(1), (old, old))
    assert b0.stale_peers(b0.stale_after) == [1]
    with pytest.raises(PeerDeathError):
        b0.check_or_raise()


def test_beacon_seen_then_vanished_is_dead(tmp_path):
    b0 = BeaconBoard(str(tmp_path), 0, 2, interval_s=10.0)
    b0._touch()
    b1 = BeaconBoard(str(tmp_path), 1, 2, interval_s=10.0)
    b1._touch()
    assert b0.stale_peers(60.0) == []          # observes peer 1
    os.remove(b1.path_for(1))                  # clean file, dead process
    assert b0.stale_peers(60.0) == [1]


def test_beacon_stop_removes_own_file(tmp_path):
    b0 = BeaconBoard(str(tmp_path), 0, 1, interval_s=0.05).start()
    assert os.path.exists(b0.path_for(0))
    b0.stop()
    assert not os.path.exists(b0.path_for(0))


# -- the injected stall fault ----------------------------------------------


def test_maybe_stall_fires_once_at_step(tmp_path):
    faults.configure(stall_at_step=3, stall_s=0.3)
    assert faults.maybe_stall(2) == 0.0
    t0 = time.monotonic()
    assert faults.maybe_stall(3) == pytest.approx(0.3)
    assert time.monotonic() - t0 >= 0.3
    assert faults.maybe_stall(3) == 0.0  # once-semantics: resume must run


# -- run_report: the "preempted" status ------------------------------------


def test_run_report_preempted_status(tmp_path):
    """A deadline-checkpointed preemption reports status "preempted"
    (distinct from "truncated"), carries steps-saved vs steps-lost, and
    still exits nonzero — resuming is the supervisor's job."""
    from glint_word2vec_tpu.obs.sink import TelemetrySink
    log = str(tmp_path / "run.jsonl")
    sink = TelemetrySink(log)
    sink.emit("run_start", run_id="r1", vocab_size=10, mesh=[1, 1],
              config={})
    sink.emit("heartbeat", step=6, words=60, alpha=0.02, loss=0.1,
              mean_f_pos=0.5, pairs_per_sec=100.0, host_wait_s=0.0,
              dispatch_s=0.1)
    sink.emit("preempt", step=6, saved=True, checkpoint="ck",
              deadline_s=30.0, steps_since_save=0)
    sink.emit("run_end", run_id="r1", status="preempted", steps=6,
              pairs_trained=600, host_wait_s_total=0.0,
              dispatch_s_total=0.1, watchdog_fires=0)
    sink.close()
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "run_report.py"), log],
        cwd=_REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["schema_valid"], rep["schema_errors"]
    assert rep["status"] == "preempted" and not rep["ok"]
    assert rep["preempt"] == {"saved": True, "step": 6, "steps_saved": 6,
                              "steps_lost": 0, "checkpoint": "ck"}


def test_run_report_preempted_deadline_missed(tmp_path):
    from glint_word2vec_tpu.obs.sink import TelemetrySink
    log = str(tmp_path / "run.jsonl")
    sink = TelemetrySink(log)
    sink.emit("run_start", run_id="r1", vocab_size=10, mesh=[1, 1],
              config={})
    sink.emit("preempt", step=10, saved=False, checkpoint="ck",
              deadline_s=5.0, steps_since_save=3)
    sink.emit("run_end", run_id="r1", status="preempted", steps=10,
              pairs_trained=0, host_wait_s_total=0.0, dispatch_s_total=0.0,
              watchdog_fires=0)
    sink.close()
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "run_report.py"), log],
        cwd=_REPO, capture_output=True, text=True, timeout=120)
    rep = json.loads(proc.stdout)
    assert rep["preempt"]["steps_lost"] == 3
    assert rep["preempt"]["steps_saved"] == 7


# -- chaos CLI surface -----------------------------------------------------


def test_chaos_list_and_unknown_only():
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "chaos_run.py"),
         "--list"], cwd=_REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0
    names = out.stdout.split()
    for want in ("train-preempt", "train-stall", "train-crashloop",
                 "crash-resume"):
        assert want in names, names
    bad = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "chaos_run.py"),
         "--only", "no-such-phase"],
        cwd=_REPO, capture_output=True, text=True, timeout=120)
    assert bad.returncode == 2
    assert "no-such-phase" in bad.stdout and "available:" in bad.stdout


# -- supervisor gauges -----------------------------------------------------


def test_supervisor_prometheus_text(tmp_path):
    from glint_word2vec_tpu.obs.statusd import supervisor_prometheus_text
    sup = _supervisor(tmp_path, [_child("raise SystemExit(0)")],
                      max_restarts=0, stall_s=30.0)
    sup.run()
    text = supervisor_prometheus_text(sup.status_snapshot())
    assert "glint_supervisor_up 1" in text
    assert "glint_supervisor_attempts_total 1" in text
    assert "glint_supervisor_quarantined 0" in text
