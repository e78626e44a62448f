"""Job kind ``train_dp``: the ``train`` job (``benchmarks/jobs/train.py``) with
its rows in shards over the data-parallel mesh.

The configuration's ``params`` name ``tree_learner=data``, and the program
divides the rows over every device it finds.  This job holds the table as
that mesh does, one shard a device (``datagen_rows.shard_bounds``): each
shard is made alone (``datagen_rows.make_rows``; laid end to end the shards
are ``datagen.make``'s table byte for byte) and the shards go to
``lgb.Dataset`` as a list of arrays, the library's form for a table in
pieces; the validation rows likewise.  Set-up, window, drain and read-back
are ``train``'s; the reference is ``reference_dp``, each shard followed on
the chip that holds it.

Before any row is made, two shards of two rows ask the program whether it
takes a table in shards, so a program that does not fails at once.
"""
from __future__ import annotations

import gc
import resource
import shutil
import tempfile
import time

import numpy as np

from benchmarks.jobs.train import _program_loss


def _barrier(jax):
    """Every chip has run what it was given (``train._device_barrier`` on
    each device)."""
    for dev in jax.devices():
        jax.block_until_ready(jax.device_put(np.zeros((), np.float32), dev) + 1)


def _shards(spec, n, seed, shards, stream=0):
    from benchmarks import datagen, datagen_rows
    model = datagen.label_model(spec)
    parts = [datagen_rows.make_rows(spec, n, lo, hi, seed, stream, model=model)
             for lo, hi in datagen_rows.shard_bounds(n, shards)]
    return [x for x, _ in parts], [y for _, y in parts]


def run(ctx) -> dict:
    """``ctx`` as ``train.run`` takes it."""
    import jax
    from jax import monitoring

    import lightgbm_tpu as lgb
    from benchmarks import reference, reference_dp, trace_reduce

    cfg, clock, log = ctx["config"], ctx["clock"], ctx["log"]
    params = {**cfg["params"], **ctx["traffic"].get("params", {}), "verbose": -1}
    phases = {"import_s": clock()}
    columns = sum(int(g["count"]) for g in cfg["data"]["columns"])
    probe = lgb.Dataset([np.zeros((2, columns)), np.ones((2, columns))],
                        label=np.array([0.0, 1.0, 0.0, 1.0]), params=params)
    if probe.construct().num_data() != 4:
        raise RuntimeError("the program does not read a list of arrays as one table")

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)

    shards = jax.device_count()
    n = int(ctx["rows"])
    n_valid = int(n * float(ctx["traffic"].get("valid_share", 0.0)))
    t = clock()
    xs, ys = _shards(cfg["data"], n, ctx["seed"], shards)
    if n_valid:
        vxs, vys = _shards(cfg["data"], n_valid, ctx["seed"], shards, stream=1)
    phases["generate_s"] = clock() - t

    t = clock()
    train_set = lgb.Dataset(xs, label=np.concatenate(ys), params=params)
    train_set.construct()
    valid_set = None
    if n_valid:
        valid_set = lgb.Dataset(vxs, label=np.concatenate(vys), reference=train_set,
                                params=params)
        valid_set.construct()
    phases["ingest_s"] = clock() - t
    host_peak = {"ingest": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}

    def step():
        with jax.profiler.TraceAnnotation("bench/update"):
            bst.update()
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
    _barrier(jax)
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
        done = issued - 1
        if done and elapsed + elapsed / done >= seconds:
            break
    while True:
        with jax.profiler.TraceAnnotation("bench/drain"):
            trees_total = bst.num_trees()
            _barrier(jax)
        window_s = time.perf_counter() - t0
        if window_s >= seconds:
            break
        step()
        issued += 1
    # ------------------------------------------------------------ closed
    if trace_dir is not None:
        jax.profiler.stop_trace()
    compiles_in_window = len(compiles) - compiles_in_setup
    stats = [(d.memory_stats() or {}) for d in jax.devices()]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
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
    rows = reference_dp.Shards(xs, ys)
    valid_rows = reference_dp.Shards(vxs, vys) if n_valid else None
    ref = reference_dp.follow(rows, trees, params, valid=valid_rows)
    got = reference.record_of_dump(trees, loss, said)
    numbers = reference.compare(got, ref)
    correct, table = reference.verdict(numbers, cfg["limits"])
    phases["reference_s"] = clock() - t
    log({"compared": numbers, "loss": {"program": loss, "reference": ref["loss"]},
         "valid": {"program": said, "reference": {k: ref[k] for k in ref
                                                  if k.startswith("valid_")}},
         "per_tree": reference.per_tree(got, ref)})
    for mode in ctx.get("control", []):
        kw = {"bfloat16": {"precision": "bfloat16"}, "half": {"leave_out": "half"},
              "frozen": {"frozen": True}}[mode]
        theirs = reference.compare(
            reference_dp.follow(rows, trees, params, valid=valid_rows, **kw), ref)
        log({"control": mode, "correct": reference.verdict(theirs, cfg["limits"])[0],
             "compared": theirs})

    s_per_tree = window_s / attempted
    log({"phases_s": phases, "compiles_in_setup": compiles_in_setup,
         "compiles_in_window": compiles_in_window, "shards": shards,
         "window_s": window_s, "trees": attempted,
         "row_iters_per_s": n / s_per_tree,
         "leaves": [tr["num_leaves"] for tr in trees],
         "depth": [tr["depth"] for tr in trees],
         "bytes_in_use_after_window": [s.get("bytes_in_use") for s in stats],
         "host_peak_bytes": {**host_peak, "run": resource.getrusage(
             resource.RUSAGE_SELF).ru_maxrss * 1024},
         "peak_bytes_by_chip": [s.get("peak_bytes_in_use") for s in stats]})
    return {
        "correct": bool(correct and failed == 0),
        "attempted": attempted, "failed": failed, "compared": table,
        "end_to_end": {"setup_s": setup_s, "s_per_tree": s_per_tree},
        "memory_peak_bytes": int(peak_bytes),
        "phases": phases, "window_s": window_s, "trace": trace,
        "trees": trees[warm_trees:], "columns": columns,
    }
