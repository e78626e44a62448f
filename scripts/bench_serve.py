"""Serving latency/throughput bench: p50/p99 + rows/s per request size.

Trains a small synthetic model, freezes it into a
``serve.PredictorArtifact`` (AOT bucket programs), then measures:

- **direct path**: per-request latency (p50/p99/mean) and rows/s at each
  request size in ``--rows-list`` (default 1k -> 1M rows/request — the
  1k-row end prices the interactive case, the 1M end the bulk-scoring
  case);
- **micro-batched path**: many small concurrent requests pushed through a
  ``MicroBatcher`` by client threads — achieved request rate, rows/s and
  per-request p50/p99 (the "millions of users" shape: tiny requests,
  shared buckets).

CPU-runnable today; on a TPU backend the same script prices the hardware.
One jsonl record per measurement is appended to ``WATCHER_PERF_LOG`` (or
``perf_results.jsonl``) as it lands, and the LAST stdout line is a single
JSON summary (the bench one-JSON-line contract, extracted by
``supervise.extract_json_line`` in the suite/watcher).

Run:
    python scripts/bench_serve.py [--rows-list 1024,16384,262144,1048576]
                                  [--iters 10] [--quick]
"""
import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import load_obs  # noqa: E402

# the single perf-journal writer (obs.events resolves WATCHER_PERF_LOG or
# the repo default)
LOG = load_obs().EventLog.default(echo=True)


def emit(**kv):
    LOG.emit(kv.pop("stage", "bench_record"), **kv)


def _pctl(xs, q):
    xs = sorted(xs)
    if not xs:
        return None           # json null, never a non-strict NaN token
    i = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
    return xs[i]


def _ms(v):
    return None if v is None else round(v * 1e3, 3)


def build_model(rows: int, feats: int, trees: int, leaves: int):
    import numpy as np
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(0)
    X = rng.normal(size=(rows, feats)).astype(np.float32)
    logit = (X[:, 0] + np.sin(2 * X[:, 1]) + 0.5 * X[:, 2] * X[:, 3]
             + 0.3 * rng.normal(size=rows))
    y = (logit > 0).astype(np.float64)
    p = {"objective": "binary", "num_leaves": leaves, "verbose": -1,
         "learning_rate": 0.1}
    t0 = time.perf_counter()
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=trees)
    emit(stage="serve_train", rows=rows, feats=feats, trees=trees,
         secs=round(time.perf_counter() - t0, 2))
    return bst, rng


def bench_direct(art, rng, feats: int, rows_list, iters: int):
    import numpy as np
    best_rps = 0.0
    for req in rows_list:
        X = rng.normal(size=(req, feats)).astype(np.float32)
        art.predict(X[: min(req, 256)])          # warm transfer paths
        art.predict(X)                           # warm the request bucket
        lat = []
        for _ in range(iters):
            t0 = time.perf_counter()
            art.predict(X)
            lat.append(time.perf_counter() - t0)
        rps = req / (sum(lat) / len(lat))
        best_rps = max(best_rps, rps)
        emit(stage="serve_direct", rows_per_request=req, iters=iters,
             p50_ms=_ms(_pctl(lat, 0.50)), p99_ms=_ms(_pctl(lat, 0.99)),
             mean_ms=round(sum(lat) / len(lat) * 1e3, 3),
             rows_per_sec=round(rps, 1),
             bucket=art._bucket_for(min(req, art.buckets[-1])))
    return best_rps


def bench_batched(art, rng, feats: int, *, req_rows: int, clients: int,
                  seconds: float, deadline_ms: float, queue_depth: int):
    import threading

    import numpy as np
    from lightgbm_tpu.serve import MicroBatcher, QueueSaturatedError
    mb = MicroBatcher(art.predict, max_batch_rows=art.buckets[-1],
                      deadline_ms=deadline_ms, queue_depth=queue_depth,
                      name="bench")
    X = rng.normal(size=(req_rows, feats)).astype(np.float32)
    art.predict(X)                               # warm the smallest bucket
    lat, shed, errs = [], [0], []
    lock = threading.Lock()
    stop = time.monotonic() + seconds

    def client():
        while time.monotonic() < stop:
            t0 = time.perf_counter()
            try:
                mb.predict(X, timeout=30)
            except QueueSaturatedError:
                with lock:
                    shed[0] += 1
                time.sleep(deadline_ms / 1e3)    # backoff, like a real client
                continue
            except Exception as e:
                # a timeout/crash must not silently kill the client thread
                # and leave the record undercounting — say so and stop
                with lock:
                    errs.append(f"{type(e).__name__}: {e}"[:120])
                return
            dt = time.perf_counter() - t0
            with lock:
                lat.append(dt)

    threads = [threading.Thread(target=client) for _ in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    mb.close()
    served = len(lat)
    emit(stage="serve_batched", rows_per_request=req_rows, clients=clients,
         wall_secs=round(wall, 2), requests=served, shed=shed[0],
         qps=round(served / wall, 1),
         rows_per_sec=round(served * req_rows / wall, 1),
         p50_ms=_ms(_pctl(lat, 0.50)), p99_ms=_ms(_pctl(lat, 0.99)),
         coalesced_batches=mb.stats["batches"],
         max_batch_requests=mb.stats["max_batch_requests"],
         **({"client_errors": errs[:4]} if errs else {}))
    return served * req_rows / wall if wall > 0 else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="serving latency/throughput bench")
    ap.add_argument("--rows-list", default="1024,16384,262144,1048576",
                    help="request sizes for the direct path")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--train-rows", type=int, default=50000)
    ap.add_argument("--feats", type=int, default=20)
    ap.add_argument("--trees", type=int, default=30)
    ap.add_argument("--leaves", type=int, default=63)
    ap.add_argument("--buckets", default=None,
                    help="comma-separated AOT bucket row counts (default: "
                         "lightgbm_tpu.config.SERVE_DEFAULT_BUCKETS)")
    ap.add_argument("--batch-seconds", type=float, default=3.0)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--req-rows", type=int, default=128,
                    help="rows per request on the micro-batched path")
    ap.add_argument("--quick", action="store_true",
                    help="small shapes for CI/smoke (seconds, not minutes)")
    args = ap.parse_args(argv)
    if args.quick:
        args.rows_list = "256,4096"
        args.buckets = "256,4096"
        args.train_rows, args.trees, args.iters = 5000, 10, 3
        args.batch_seconds = 1.0

    import jax
    backend = jax.default_backend()
    rows_list = [int(r) for r in args.rows_list.split(",") if r.strip()]
    if args.buckets is None:
        from lightgbm_tpu.config import SERVE_DEFAULT_BUCKETS
        buckets = list(SERVE_DEFAULT_BUCKETS)
    else:
        buckets = [int(b) for b in args.buckets.split(",") if b.strip()]

    bst, rng = build_model(args.train_rows, args.feats, args.trees,
                           args.leaves)
    from lightgbm_tpu.serve import PredictorArtifact
    t0 = time.perf_counter()
    art = PredictorArtifact.freeze(bst, buckets=buckets)
    compile_secs = time.perf_counter() - t0
    emit(stage="serve_freeze", backend=backend, buckets=buckets,
         trees=args.trees, compiles=art.compile_count,
         secs=round(compile_secs, 2))

    direct_rps = bench_direct(art, rng, args.feats, rows_list, args.iters)
    batched_rps = bench_batched(
        art, rng, args.feats, req_rows=args.req_rows, clients=args.clients,
        seconds=args.batch_seconds,
        deadline_ms=bst._gbdt.config.serve_batch_deadline_ms,
        queue_depth=bst._gbdt.config.serve_queue_depth)

    # one-JSON-line contract: summary() appends to the journal AND prints
    # the schema-stamped record as the LAST stdout line
    LOG.summary(
        metric="serve_throughput", unit="rows/sec",
        value=round(max(direct_rps, batched_rps), 1),
        backend=backend,
        detail={"direct_rows_per_sec": round(direct_rps, 1),
                "batched_rows_per_sec": round(batched_rps, 1),
                "trees": args.trees, "feats": args.feats,
                "buckets": buckets,
                "aot_compile_secs": round(compile_secs, 2)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
