"""Job kind ``train``: grow trees with ``lgb.Booster.update()`` for a window.

Set-up (all of it counted in ``setup_s``): data from the seed, ``lgb.Dataset``
construction (bin mappers, binning), ``lgb.Booster`` (transfer, variant
election), one warm-up ``update()`` (compiles or loads from the cache).  The
window: ``update()`` (with ``eval_valid()`` where the mix has validation rows),
always two and then on until the tree in flight will carry the clock past
``--seconds``; closed when the host holds every tree and the device has
nothing queued.  Only the public API is used (``Dataset``, ``Booster``,
``update``, ``num_trees``, ``eval_train``, ``dump_model``); reading the trees
back is what forces the drain.  After the clock has stopped and the peak
memory has been read, the program's state is dropped and the plain reference
(``benchmarks/reference.py``) follows the same trees.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np


def _device_barrier(jax):
    """The device runs programs in order: a trivial one queued now ends after
    everything queued before it."""
    jax.block_until_ready(jax.device_put(np.zeros((), np.float32)) + 1)


def _program_loss(bst):
    for _, name, value, _ in bst.eval_train():
        if name == "binary_logloss":
            return float(value)
    raise RuntimeError("the program reports no binary_logloss for its training data")


def run(ctx) -> dict:
    """``ctx``: config (dict of the configuration's file), traffic (dict of the
    traffic file), seed, seconds, trace (bool), rows (int), clock (callable,
    seconds since process start), log (callable), control (list of str: the
    controls and faults to read after the run, for whoever sets the limits)."""
    import jax
    from jax import monitoring

    import lightgbm_tpu as lgb
    from benchmarks import datagen, reference, trace_reduce

    cfg, clock, log = ctx["config"], ctx["clock"], ctx["log"]
    params = {**cfg["params"], **ctx["traffic"].get("params", {}), "verbose": -1}
    phases = {"import_s": clock()}

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)

    n = int(ctx["rows"])
    n_valid = int(n * float(ctx["traffic"].get("valid_share", 0.0)))
    t = clock()
    x, y = datagen.make(cfg["data"], n, ctx["seed"])
    if n_valid:
        x_valid, y_valid = datagen.make(cfg["data"], n_valid, ctx["seed"], stream=1)
    phases["generate_s"] = clock() - t

    t = clock()
    train_set = lgb.Dataset(x, label=y, params=params)
    train_set.construct()
    valid_set = None
    if n_valid:
        valid_set = lgb.Dataset(x_valid, label=y_valid, reference=train_set,
                                params=params)
        valid_set.construct()
    phases["ingest_s"] = clock() - t

    def step():
        """One boosting iteration as ``lgb.train`` runs it: grow a tree, then
        score and evaluate the validation set where the job has one."""
        with jax.profiler.TraceAnnotation("bench/update"):
            bst.update()    # issues a tree; returns when the one before is on the host
        if valid_set is not None:
            with jax.profiler.TraceAnnotation("bench/eval_valid"):
                valid_metrics.append(bst.eval_valid())

    valid_metrics = []
    t = clock()
    bst = lgb.Booster(params, train_set)
    if valid_set is not None:
        bst.add_valid(valid_set, "valid")
    phases["booster_s"] = clock() - t
    for _ in range(int(ctx["traffic"].get("warmup_trees", 1))):
        step()
    warm_trees = bst.num_trees()
    _device_barrier(jax)
    phases["first_tree_s"] = clock() - t
    phases["warm_tree_s"] = phases["first_tree_s"] - phases["booster_s"]

    compiles_in_setup = len(compiles)

    trace_dir = None
    if ctx["trace"]:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)

    # ------------------------------------------------------------ the window
    seconds = float(ctx["seconds"])
    setup_s = clock()
    t0 = time.perf_counter()
    issued = 0
    while True:
        step()
        issued += 1
        elapsed = time.perf_counter() - t0
        done = issued - 1       # at least: a step that waits has finished its own too
        if done and elapsed + elapsed / done >= seconds:
            break               # the tree in flight ends past --seconds
    while True:
        with jax.profiler.TraceAnnotation("bench/drain"):
            trees_total = bst.num_trees()   # the host holds every tree
            _device_barrier(jax)
        window_s = time.perf_counter() - t0
        if window_s >= seconds:
            break
        step()      # a step that waits for its own tree leaves none in flight
        issued += 1
    # ------------------------------------------------------------ closed
    if trace_dir is not None:
        jax.profiler.stop_trace()
    compiles_in_window = len(compiles) - compiles_in_setup
    stats = jax.devices()[0].memory_stats() or {}
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in jax.devices())
    attempted = trees_total - warm_trees
    assert attempted == issued, (attempted, issued)

    t = clock()
    loss = _program_loss(bst)
    dump = bst.dump_model()
    trees = [reference.parse_tree(tj) for tj in dump["tree_info"]]
    want = int(params["num_leaves"])
    failed = sum(1 for tr in trees[warm_trees:]
                 if tr["num_leaves"] < want
                 or not np.all(np.isfinite(tr["leaf_value"])))
    said = {name: float(value) for _, name, value, _ in valid_metrics[-1]} \
        if valid_metrics else None
    del bst, train_set, valid_set, dump
    gc.collect()
    phases["readback_s"] = clock() - t

    trace = None
    if trace_dir is not None:
        t = clock()
        trace = trace_reduce.read_profile(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        phases["trace_read_s"] = clock() - t

    # ------------------------------------------------------------ the reference
    t = clock()
    rows = reference.Rows(x, y)
    valid_rows = reference.Rows(x_valid, y_valid) if n_valid else None
    ref = reference.follow(rows, trees, params, valid=valid_rows)
    got = reference.record_of_dump(trees, loss, said)
    numbers = reference.compare(got, ref)
    correct, table = reference.verdict(numbers, cfg["limits"])
    phases["reference_s"] = clock() - t
    log({"compared": numbers, "loss": {"program": loss, "reference": ref["loss"]},
         "valid": {"program": said, "reference": {k: ref[k] for k in ref
                                                  if k.startswith("valid_")}},
         "per_tree": reference.per_tree(got, ref)})
    # a builder's controls: the reference put in the program's place, in the
    # precision below or with a fault planted, judged by the same comparison
    for mode in ctx.get("control", []):
        kw = {"bfloat16": {"precision": "bfloat16"}, "half": {"leave_out": "half"},
              "frozen": {"frozen": True}}[mode]
        theirs = reference.compare(
            reference.follow(rows, trees, params, valid=valid_rows, **kw), ref)
        log({"control": mode, "correct": reference.verdict(theirs, cfg["limits"])[0],
             "compared": theirs})

    s_per_tree = window_s / attempted
    log({"phases_s": phases, "compiles_in_setup": compiles_in_setup,
         "compiles_in_window": compiles_in_window,
         "window_s": window_s, "trees": attempted,
         "row_iters_per_s": ctx["rows"] / s_per_tree,
         "leaves": [tr["num_leaves"] for tr in trees],
         "depth": [tr["depth"] for tr in trees],
         "bytes_in_use_after_window": stats.get("bytes_in_use")})
    return {
        "correct": bool(correct and failed == 0),
        "attempted": attempted, "failed": failed, "compared": table,
        "end_to_end": {"setup_s": setup_s, "s_per_tree": s_per_tree},
        "memory_peak_bytes": int(peak_bytes),
        # what the per-layer readers read
        "phases": phases, "window_s": window_s, "trace": trace,
        "trees": trees[warm_trees:], "columns": int(x.shape[1]),
    }
