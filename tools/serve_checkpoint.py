"""Mode-B deployment surface: serve model ops from a checkpoint in a separate process.

The reference's mode B runs a standalone Glint PS cluster that training apps and query
clients both attach to (README.md:45-57, it spec:108-135). The TPU-native analog
(documented design call, models/compat.py): training owns the pod; QUERY serving reads
checkpoints — any number of serving processes can load the same checkpoint directory
(dense or row-shards; row-shards stream onto this process's mesh without a dense host
copy) and answer transform/find_synonyms while training continues writing newer
checkpoints alongside.

This CLI is a THIN CLIENT of the serving subsystem (glint_word2vec_tpu/serve/,
docs/serving.md): the swap-window retry logic lives in serve/reload.py (the single
owner), queries ride the request batcher, and ``--ann`` serves the IVF index arm
built at load time. The JSON-lines request/response contract below is unchanged.

Protocol: JSON-lines over stdin/stdout — one request object per line, one response
object per line (the process-boundary analog of the reference's Akka query RPCs, with
the same ops the PS served: pull / multiply+top-k, mllib:514,598):

    {"op": "synonyms", "word": "berlin", "num": 10}
    {"op": "synonyms_batch", "words": ["berlin", "wien"], "num": 10}
    {"op": "synonyms_vec", "vector": [...], "num": 10}
    {"op": "vector", "word": "berlin"}
    {"op": "reload"}                      # pick up a newer checkpoint at the same path
    {"op": "info"}
    {"op": "stats"}                       # serving-tier gauges (batcher/ANN/reloads,
                                          # incl. publish_sig — the served generation)

Any request may carry an ``"id"``: it is echoed verbatim on the response, which is
what lets the fleet router (serve/fleet.py) pair responses to tickets and discard
abandoned hedge-loser replies. Error responses are machine-readable:
``{"error": "...", "error_type": "ServerOverloaded", "retry_after_s": 0.12}`` —
the type name routes the caller's retry policy and ``retry_after_s`` is the
admission queue's measured drain-time hint (serve/batcher.py).

Usage:
    python tools/serve_checkpoint.py /path/to/checkpoint [--mesh DATAxMODEL]
        [--ann] [--nprobe N] [--watch] [--status-port P] [--telemetry PATH]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("checkpoint")
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL, e.g. 1x8: load row-shards straight onto this "
                         "mesh (no dense host copy)")
    ap.add_argument("--ann", action="store_true",
                    help="serve synonym queries from the IVF ANN index (built at "
                         "load/reload time; exact remains the oracle default)")
    ap.add_argument("--nprobe", type=int, default=0,
                    help="ANN cells probed per query (0 = the config/auto value)")
    ap.add_argument("--watch", action="store_true",
                    help="hot-reload automatically on the trainer's checkpoint "
                         "publish signal (the explicit reload op still works)")
    ap.add_argument("--status-port", type=int, default=0,
                    help="> 0: serve glint_serve_* gauges on 127.0.0.1:<port> "
                         "(/status.json, /metrics, /healthz)")
    ap.add_argument("--telemetry", default="",
                    help="non-empty: write serve_* telemetry records to this "
                         "JSONL path (obs/sink.py); also arms the serving "
                         "flight recorder (<path>.blackbox.json on death) "
                         "and cross-process trace spans (obs/trace.py)")
    ap.add_argument("--process-name", default="",
                    help="fleet-timeline track label for this replica's "
                         "telemetry (default serve-<pid>; the fleet spawner "
                         "passes r0/r1/...)")
    args = ap.parse_args()

    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.serve import EmbeddingService

    plan = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.lower().split("x"))
        plan = make_mesh(d, m)

    service = EmbeddingService(
        checkpoint=args.checkpoint, plan=plan, ann=args.ann,
        nprobe=args.nprobe or None, watch=args.watch,
        telemetry_path=args.telemetry, status_port=args.status_port,
        process_name=args.process_name)

    if args.telemetry:
        # the serving flight recorder's signal trigger (ISSUE-13 satellite;
        # same contract as trainer._install_run_signals): SIGTERM — the
        # graceful half of a kill, the half SIGKILL can't exercise — dumps
        # <telemetry>.blackbox.json with a serve-scoped signal cause, then
        # restores the prior disposition and re-raises so exit semantics
        # (rc -15, the fleet prober's dead-process detection) are untouched
        import signal

        from glint_word2vec_tpu.obs.blackbox import FlightRecorder

        prev_handler = signal.getsignal(signal.SIGTERM)

        def _on_sigterm(signum, frame):
            # include_stats=False: the handler may have interrupted the
            # main thread INSIDE the batcher's non-reentrant _cv block —
            # a stats snapshot here would deadlock the dump
            service.dump_blackbox(FlightRecorder.signal_cause(signum),
                                  include_stats=False)
            signal.signal(signal.SIGTERM,
                          prev_handler if callable(prev_handler)
                          else signal.SIG_DFL)
            os.kill(os.getpid(), signum)

        signal.signal(signal.SIGTERM, _on_sigterm)

    def out(obj, req=None):
        # a request carrying an "id" gets it echoed on its response — the
        # fleet router (serve/fleet.py) pairs responses to tickets by id so
        # abandoned hedge-loser replies can be discarded safely
        if req is not None and "id" in req:
            obj = {**obj, "id": req["id"]}
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    info = service.info()
    out({"ready": True, "num_words": info["num_words"],
         "vector_size": info["vector_size"]})
    try:
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            req = None
            try:
                req = json.loads(line)
                op = req["op"]
                # cross-process trace context (obs/trace.py): a request
                # carrying {"trace": {"tid", "ps"}} gets its queue-wait /
                # batch-service / ANN-scan spans emitted into THIS replica's
                # sink under the router's trace id — the collector joins
                # them back into one causal timeline. Absent (tracing off),
                # nothing is allocated and the payloads are byte-identical.
                trace = req.get("trace")
                if op == "synonyms":
                    res = service.synonyms(req["word"], int(req.get("num", 10)),
                                           trace=trace)
                    out({"synonyms": [[w, s] for w, s in res]}, req)
                elif op == "synonyms_vec":
                    import numpy as np
                    vec = np.asarray(req["vector"], np.float32)
                    res = service.synonyms(vec, int(req.get("num", 10)))
                    out({"synonyms": [[w, s] for w, s in res]}, req)
                elif op == "synonyms_batch":
                    # many queries, one device dispatch per coalesced batch —
                    # through a thin link per-query round trips dominate
                    # (PERF.md §6); the batcher owns the coalescing now
                    res = service.synonyms_batch(
                        list(req["words"]), int(req.get("num", 10)),
                        trace=trace)
                    out({"synonyms": [[[w, s] for w, s in row] for row in res]},
                        req)
                elif op == "vector":
                    out({"vector": service.vector(req["word"]).tolist()}, req)
                elif op == "reload":
                    model = service.reload_now()
                    out({"reloaded": True, "num_words": model.num_words}, req)
                elif op == "info":
                    i = service.info()
                    out({"num_words": i["num_words"],
                         "vector_size": i["vector_size"],
                         "iteration": i["iteration"],
                         "finished": i["finished"]}, req)
                elif op == "stats":
                    out(service.stats(), req)
                elif op == "quit":
                    out({"bye": True}, req)
                    break
                else:
                    out({"error": f"unknown op {op!r}",
                         "error_type": "ValueError"}, req)
            except Exception as e:  # noqa: BLE001 — protocol errors go to the client
                # machine-readable error payload: the type name routes the
                # caller's policy (ServerOverloaded → retry elsewhere,
                # KeyError → the caller's own error) and retry_after_s is
                # the admission queue's drain-time hint (serve/batcher.py)
                # — pre-ISSUE-12 callers could only blind-retry
                err = {"error": f"{type(e).__name__}: {e}",
                       "error_type": type(e).__name__}
                retry_after = getattr(e, "retry_after_s", None)
                if retry_after is not None:
                    err["retry_after_s"] = retry_after
                out(err, req)
    except BaseException as e:
        # a fatal serve-loop error (not a per-request one — those were
        # answered above) leaves the same dump a dying trainer does
        from glint_word2vec_tpu.obs.blackbox import FlightRecorder
        service.dump_blackbox(FlightRecorder.exception_cause(e))
        raise
    finally:
        service.close()


if __name__ == "__main__":
    main()
