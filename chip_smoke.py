"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the main path once through the public API on ONE TPU process (no
children, no probe) at full width: synthetic Higgs geometry
(``make_higgs_like``), 28 columns, ``max_bin=255``,
``num_leaves=255``, ``min_data_in_leaf=100``, binary objective, every speed
knob at its default (``hist_variant=auto``, ``tree_grower=auto``).  Depth is
cut to 5 trees; columns, bins and leaves are never cut on the chip.

Phases, each of which must pass or the run exits non-zero:

  1. parity   both production Pallas kernels vs the exact scatter-add, on
              device, at this width, masked rows and fractional weights
  2. train    ``lgb.Dataset`` -> ``lgb.Booster`` -> ``update()`` x 5
  3. predict  held-out AUC above a floor, device vs host predict,
              ``save_model`` -> ``Booster(model_file=...)`` round trip
  4. serve    ``PredictorArtifact.freeze`` -> ``parity_check`` ->
              ``Predictor`` answers requests in at least two buckets
  (``--devices N`` adds train_dp/predict_dp: the same two phases with
  ``tree_learner=data`` on an N-device mesh, compared with the one-chip run)

It exits non-zero, printing no result line, when jax finds no TPU.  A run
that reached the phases ends its stdout with two JSON lines: the report
(device, versions, phases with status and seconds, parity errors, elected
variant, grower, compile seconds, cache entries; also written to
``chip_smoke.json`` under ``--out``), and LAST the verdict, exactly
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as jax reports it; ``"ok"`` is true only if every phase passed.
Every time in the report is an observation of a smoke run (one sample,
compile included where it says so), never a benchmark number.

``--dry-run`` is the rehearsal the chip tool's budget asks for: the same
code end to end at toy size on whatever backend jax finds (here the CPU,
Pallas in interpret mode).  Its report says ``"dry_run": true`` and both
lines name the platform, so it cannot be mistaken for a chip run; rows,
trees and leaves can be reduced only under it, and no environment variable
switches the device check off.

    python chip_smoke.py                  # one chip
    python chip_smoke.py --devices 4      # four-chip host
    JAX_PLATFORMS=cpu python chip_smoke.py --dry-run
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

ROWS, TREES, LEAVES = 1_000_000, 5, 255
N_FEAT, MAX_BIN = 28, 255          # never cut
VALID_ROWS = 200_000
# Held-out AUC floor for the 5-tree model on make_higgs_like(200_000,
# seed=43).  The CPU backend (exact scatter-add histograms) trained on the
# same seeds and parameters reaches 0.78878 after 5 trees, 0.78577 after 3
# and 0.78050 after 1, all trees at 255 leaves (measured in the sandbox,
# PR 22).  0.784 is cleared with margin by a working 5-tree model and
# missed by one whose later trees do not learn.
AUC_FLOOR = 0.784
# the toy model of a dry run only has to beat chance clearly
DRY_RUN_AUC_FLOOR = 0.65


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class PhaseFailed(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=1,
                    help="also train tree_learner=data on an N-device mesh")
    ap.add_argument("--rows", type=int, default=ROWS,
                    help=f"training rows (below {ROWS} only with --dry-run)")
    ap.add_argument("--trees", type=int, default=TREES,
                    help="boosting rounds (other than 5 only with --dry-run)")
    ap.add_argument("--leaves", type=int, default=LEAVES,
                    help="num_leaves (other than 255 only with --dry-run)")
    ap.add_argument("--valid-rows", type=int, default=VALID_ROWS,
                    help="held-out rows (other than 200000 only with "
                         "--dry-run)")
    ap.add_argument("--dry-run", action="store_true",
                    help="toy-size rehearsal on whatever backend jax finds")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out"),
                    help="directory for the model file and the result JSON")
    args = ap.parse_args(argv)
    if not args.dry_run and (args.rows < ROWS or args.trees != TREES
                             or args.leaves != LEAVES
                             or args.valid_rows != VALID_ROWS):
        ap.error("rows, trees, leaves and valid rows can be reduced only "
                 "under --dry-run")
    if args.devices < 1 or args.trees < 2:
        ap.error("--devices must be >= 1 and --trees >= 2")
    return args


def make_higgs_like(n_rows: int, n_feat: int = 28, seed: int = 42):
    """Synthetic stand-in with Higgs geometry (dense floats, ~even classes)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n_rows, n_feat)).astype(np.float32)
    # nonlinear signal over a few features so trees have structure to find
    logit = (1.2 * X[:, 0] - 0.8 * X[:, 1] + X[:, 2] * X[:, 3]
             + 0.5 * np.sin(3.0 * X[:, 4]) + 0.3 * X[:, 5] ** 2)
    y = (logit + rng.logistic(size=n_rows) > 0).astype(np.float32)
    return X, y


def auc_of(scores, labels) -> float:
    """AUC by the package's own metric (the one training gates on)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.metric.base import AUCMetric
    md = Metadata(len(labels))
    md.set_field("label", labels)
    m = AUCMetric(Config())
    m.init(md, len(labels))
    (_, v, _), = m.eval(np.asarray(scores, np.float64))
    return v


def versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def placements(gbdt) -> dict:
    """Where the training arrays live: sharding type, device ids, spec."""
    def where(a):
        sh = a.sharding
        return (f"{type(sh).__name__} devices="
                f"{sorted(d.id for d in sh.device_set)}"
                + (f" spec={sh.spec}" if hasattr(sh, "spec") else ""))
    g, _ = gbdt._compute_gradients(gbdt._train_score)
    return {name: where(a) for name, a in (
        ("bins", gbdt._dd.bins), ("train_score", gbdt._train_score),
        ("label", gbdt._label_dev), ("grad", g))}


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def run_kernel_checks(variants=("base",), max_bin=256, n_feat=28, slots=16,
                      block_rows=512, rows=200_000) -> dict:
    """The two production Pallas kernels (``_hist_pallas``, the whole-data
    pass, and ``_hist_leaves_pallas``, the frontier grower's batched-leaf
    pass) against the EXACT scatter-add at one width, for each one-hot
    variant named.  Rows are both masked (weight 0) and fractionally
    weighted, as bagging and GOSS make them.  Defaults are this file's
    shape: 28 columns, the 256-wide kernel histogram ``max_bin=255`` trains
    with, 16 leaf slots of 512-row blocks.  The chip runs it as phase 1 and
    tier-1 runs it in interpret mode (``tests/test_onehot_variants.py``).

    Returns ``{"hist_pallas/<variant>": relerr, "hist_leaves_pallas/<variant>":
    relerr}``; a kernel that fails to compile or run records ``inf``.  The
    caller compares against ``HIST_PARITY_TOL``."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.histogram import (_hist_leaves_pallas, _hist_pallas,
                                            _hist_scatter,
                                            build_histogram_leaves, fold_hist)
    rng = np.random.default_rng(3)

    def emit(**kv):
        log("  " + json.dumps({"stage": "kernel_parity", **kv}))

    def weights(n):
        keep = rng.uniform(size=n) < 0.8
        return jnp.asarray(np.where(keep, rng.uniform(0.25, 1.0, size=n),
                                    0.0).astype(np.float32))

    def relerr(a, b):          # of two pair histograms
        a, b = fold_hist(a), fold_hist(b)
        return float(jnp.max(jnp.abs(a - b) / (jnp.abs(b) + 1.0)))

    # whole-data kernel: a row count that is no block multiple (pad path)
    n = rows
    bins = jnp.asarray(rng.integers(0, max_bin - 1, size=(n, n_feat),
                                    dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
    m = weights(n)
    ref = jax.jit(lambda *x: _hist_scatter(*x, max_bin))(bins, g, h, m)

    # batched-leaf kernel: gathered rows carry 4 trailing packed-gradient
    # columns the kernel must skip (f_limit); one slot is deliberately left
    # EMPTY: a slot with no row blocks must come back as zeros (the kernel
    # zero-inits its whole VMEM-resident accumulator at grid step 0), not
    # stale HBM
    nb = 4 * slots
    c = block_rows * nb
    comb = jnp.asarray(rng.integers(0, max_bin - 1, size=(c, n_feat + 4),
                                    dtype=np.uint8))
    gl = jnp.asarray(rng.normal(size=c).astype(np.float32))
    hl = jnp.asarray(rng.uniform(0.1, 1.0, size=c).astype(np.float32))
    ml = weights(c)
    bl = np.sort(rng.integers(0, slots, size=nb)).astype(np.int32)
    bl = jnp.asarray(np.where(bl == slots - 2, slots - 1, bl))
    ref_l = jax.jit(lambda *x: build_histogram_leaves(
        *x, slots, max_bin, method="scatter", block_rows=block_rows,
        f_limit=n_feat))(comb, gl, hl, ml, bl)

    cases = (
        ("hist_pallas",
         lambda v, *x: _hist_pallas(*x, max_bin, variant=v),
         (bins, g, h, m), ref),
        ("hist_leaves_pallas",
         lambda v, *x: _hist_leaves_pallas(*x, slots, max_bin, block_rows,
                                           n_feat, variant=v),
         (comb, gl, hl, ml, bl), ref_l))
    errs = {}
    for v in variants:
        for name, fn, args, want in cases:
            key = f"{name}/{v}"
            try:
                errs[key] = relerr(
                    jax.jit(functools.partial(fn, v))(*args), want)
                emit(kernel=key, relerr=errs[key], n_feat=n_feat,
                     max_bin=max_bin)
            except Exception as e:     # a lowering crash is a result too
                errs[key] = float("inf")
                emit(kernel=key, error=f"{type(e).__name__}: {e}"[:300])
    return errs


def phase_parity(ctx) -> dict:
    from lightgbm_tpu.ops import onehot_variants as ov
    from lightgbm_tpu.ops.histogram import (HIST_PARITY_TOL,
                                            _pallas_interpret_default)

    on_tpu = ctx["device"]["platform"] == "tpu"
    check(_pallas_interpret_default() is (not on_tpu),
          "Pallas interpret mode does not follow the backend")
    kernel_bins = MAX_BIN + 1      # the width GBDT._make_grower_cfg picks
    variants = [v for v in ov.AUTO_CANDIDATES
                if ov.VARIANTS[v].supports(kernel_bins)]
    errs = run_kernel_checks(
        variants, max_bin=kernel_bins, n_feat=N_FEAT,
        rows=3000 if ctx["args"].dry_run else 200_000,
        slots=6 if ctx["args"].dry_run else 16)
    # any of these can be elected, so any of them being wrong fails the run
    bad = {k: e for k, e in errs.items() if not e < HIST_PARITY_TOL}
    check(not bad, f"kernel parity above {HIST_PARITY_TOL}: {bad}")
    return {"parity_relerr": {k: float(f"{e:.3e}") for k, e in errs.items()},
            "parity_tol": HIST_PARITY_TOL,
            "pallas_interpret": _pallas_interpret_default()}


def _train(ctx, extra_params: dict, tag: str) -> dict:
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb
    from lightgbm_tpu import native
    from lightgbm_tpu.ops import onehot_variants as ov
    from lightgbm_tpu.utils.random_gen import key_for_iteration

    args, on_tpu = ctx["args"], ctx["device"]["platform"] == "tpu"
    X, y = ctx["train_xy"]
    params = {"objective": "binary", "num_leaves": args.leaves,
              "learning_rate": 0.1, "max_bin": MAX_BIN,
              "min_data_in_leaf": 100, "min_sum_hessian_in_leaf": 100.0,
              "verbose": 1, **extra_params}
    elected_before = set(ov._AUTO_SECONDS)
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    init_s = time.perf_counter() - t0
    gbdt = bst._gbdt
    gc = gbdt._grower_cfg
    grower = gbdt._grower_name()
    # the election runs once per (device kind, kernel width) and process:
    # seconds are reported by the phase whose Booster ran it
    election_s = sum(v for k, v in ov._AUTO_SECONDS.items()
                     if k not in elected_before)
    log(f"  {tag}: hist_method={gc.hist_method} hist_variant={gc.hist_variant}"
        f" (election {election_s:.1f}s) grower={grower} "
        f"dataset+booster {init_s:.1f}s "
        f"native_parser_built={native.get_lib() is not None}")
    out = {"hist_method": gc.hist_method, "hist_variant": gc.hist_variant,
           "election_seconds": round(election_s, 2), "grower": grower,
           "dataset_booster_seconds": round(init_s, 2),
           "native_parser_built": native.get_lib() is not None,
           "kernel_bins": gc.bundle_bins or gc.max_bin}
    check(gc.max_bin == MAX_BIN + 1 and X.shape[1] == N_FEAT,
          f"width was cut: max_bin={gc.max_bin} cols={X.shape[1]}")
    if on_tpu:
        check(gc.hist_method == "pallas",
              f"hist_method is {gc.hist_method!r} on a TPU")
    if extra_params.get("tree_learner") == "data":
        n_dev = args.devices
        check(gbdt._mesh is not None and gbdt._mesh.devices.size == n_dev
              and gc.num_shards == n_dev and gc.parallel_mode == "data",
              f"no {n_dev}-device data mesh: mesh={gbdt._mesh} "
              f"num_shards={gc.num_shards} mode={gc.parallel_mode}")
        out["mesh_devices"] = int(gbdt._mesh.devices.size)
    out["placement_before"] = placements(gbdt)
    log(f"  {tag}: placement before a step: {out['placement_before']}")

    # the Mosaic call must be IN the grow program, not assumed to be
    n = X.shape[0]
    g0, h0 = gbdt._compute_gradients(gbdt._train_score)
    t0 = time.perf_counter()
    text = gbdt._grow_jit.lower(
        gbdt._dd.bins, g0[0], h0[0], jnp.ones(n, jnp.float32),
        gbdt._feature_mask(0),
        key_for_iteration(gbdt.config.seed, 0, salt=1), None, None).as_text()
    out["mosaic_calls_in_grow_program"] = text.count("tpu_custom_call")
    out["lower_seconds"] = round(time.perf_counter() - t0, 2)
    if on_tpu:
        check(out["mosaic_calls_in_grow_program"] > 0,
              "no tpu_custom_call in the lowered grow program")

    t0 = time.perf_counter()
    bst.update()
    gbdt._train_score.block_until_ready()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(args.trees - 1):
        bst.update()
    gbdt._train_score.block_until_ready()
    steady_s = (time.perf_counter() - t0) / (args.trees - 1)
    out["placement_after"] = placements(gbdt)
    log(f"  {tag}: placement after {args.trees} steps: "
        f"{out['placement_after']}")

    leaves = [int(t.num_leaves) for t in gbdt.models]
    score = np.asarray(gbdt._train_score)
    out.update(compile_plus_first_tree_seconds=round(first_s, 2),
               steady_seconds_per_tree=round(steady_s, 3),
               leaf_counts=leaves)
    log(f"  {tag}: compile+first tree {first_s:.1f}s, then "
        f"{steady_s:.3f} s/tree over {args.trees - 1} trees (smoke "
        f"observation, not a benchmark); leaves per tree {leaves}")
    check(len(leaves) == args.trees, f"{len(leaves)} trees, not {args.trees}")
    check(all(nl > 1 for nl in leaves), f"a tree did not split: {leaves}")
    check(np.all(np.isfinite(score)), "non-finite training scores")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        out["device_peak_bytes_in_use"] = int(stats["peak_bytes_in_use"])
    ctx[tag] = bst
    return out


def phase_train(ctx) -> dict:
    return _train(ctx, {}, "train")


def _predict(ctx, tag: str) -> dict:
    import lightgbm_tpu as lgb

    bst = ctx[tag]
    Xv, yv = ctx["valid_xy"]
    raw = bst.predict(Xv, raw_score=True)
    check(raw.shape == (Xv.shape[0],) and np.all(np.isfinite(raw)),
          f"predict returned shape {raw.shape} or non-finite values")
    auc = float(auc_of(raw, yv))
    floor = DRY_RUN_AUC_FLOOR if ctx["args"].dry_run else AUC_FLOOR
    log(f"  {tag}: held-out AUC {auc:.5f} (floor {floor})")
    check(auc > floor, f"held-out AUC {auc:.5f} is not above {floor}")
    out = {"auc_holdout": round(auc, 6), "auc_floor": floor,
           "valid_rows": int(Xv.shape[0])}

    # save -> load round trip, then the loaded model down both predict paths
    path = os.path.join(ctx["args"].out, f"chip_smoke_{tag}_model.txt")
    bst.save_model(path)
    loaded = lgb.Booster(model_file=path)
    sample = Xv[:20_000]
    want = raw[:sample.shape[0]]
    rt = float(np.max(np.abs(loaded.predict(sample, raw_score=True) - want)))
    check(rt <= 1e-9, f"save/load round trip moved predictions by {rt:g}")
    got = {}
    for mode in ("device", "host"):
        loaded.reset_parameter({"pred_device": mode})
        got[mode] = loaded.predict(sample, raw_score=True)
    diff = float(np.max(np.abs(got["device"] - got["host"])))
    check(diff <= 1e-5, f"device and host predict differ by {diff:g}")
    out.update(roundtrip_max_abs_diff=rt, device_vs_host_max_abs_diff=diff,
               model_file=os.path.relpath(path, HERE))
    return out


def phase_predict(ctx) -> dict:
    return _predict(ctx, "train")


def phase_serve(ctx) -> dict:
    from lightgbm_tpu.serve import Predictor, PredictorArtifact

    bst = ctx["train"]
    Xv, _ = ctx["valid_xy"]
    t0 = time.perf_counter()
    art = PredictorArtifact.freeze(bst)
    freeze_s = time.perf_counter() - t0
    compiles = art.compile_count
    check(compiles == len(art.buckets),
          f"{compiles} compiles for {len(art.buckets)} buckets")
    ok, reason = art.parity_check(Xv[:2048])
    check(ok, f"artifact parity_check: {reason}")
    srv = Predictor(art)
    try:
        b0 = art.buckets[0]         # b0 + 1 rows must take the next bucket
        sizes = [n for n in (1, 700, b0, b0 + 1, 20_000)
                 if n <= Xv.shape[0]]
        used = set()
        for n in sizes:
            got = srv.predict(Xv[:n])
            want = bst.predict(Xv[:n])
            check(got.shape == (n,) and np.all(np.isfinite(got)),
                  f"request of {n} rows: shape {got.shape} or non-finite")
            d = float(np.max(np.abs(got - want)))
            check(d <= 1e-5, f"request of {n} rows is {d:g} off Booster.predict")
            used.add(art._bucket_for(n))
    finally:
        srv.close()
    check(len(used) >= 2, f"requests landed in one bucket only: {used}")
    check(art.compile_count == compiles,
          f"compile_count moved after freeze: {compiles} -> "
          f"{art.compile_count}")
    return {"buckets": list(art.buckets), "buckets_hit": sorted(used),
            "request_rows": sizes, "compile_count": compiles,
            "freeze_seconds": round(freeze_s, 2)}


def phase_train_dp(ctx) -> dict:
    out = _train(ctx, {"tree_learner": "data",
                       "mesh_shape": [ctx["args"].devices]}, "train_dp")
    one = ctx["results"]["train"]["leaf_counts"]
    check(out["leaf_counts"] == one,
          f"leaf counts differ from the one-chip run: {out['leaf_counts']} "
          f"vs {one}")
    return out


def phase_predict_dp(ctx) -> dict:
    out = _predict(ctx, "train_dp")
    one = ctx["results"]["predict"]["auc_holdout"]
    check(abs(out["auc_holdout"] - one) <= 1e-3,
          f"held-out AUC {out['auc_holdout']} is more than 1e-3 from the "
          f"one-chip run's {one}")
    return out


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    args = parse_args(argv)
    t_start = time.perf_counter()

    # The device check comes first and runs in THIS process: with
    # JAX_PLATFORMS unset jax falls back to the CPU with a warning when the
    # TPU does not start, and this is what turns that into a failure.
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    vers = versions()
    log(f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"device_count={jax.device_count()} jax={vers['jax']} "
        f"jaxlib={vers['jaxlib']} libtpu={vers['libtpu']}"
        + (" DRY RUN (toy size)" if args.dry_run else ""))
    if dev.platform != "tpu" and not args.dry_run:
        sys.exit(f"chip_smoke.py: needs a TPU, jax found platform="
                 f"{dev.platform!r} ({dev.device_kind}).  --dry-run "
                 "rehearses the same code at toy size on this backend.")
    if jax.device_count() < args.devices:
        sys.exit(f"chip_smoke.py: --devices {args.devices} but jax has "
                 f"{jax.device_count()} {dev.platform} device(s)")

    from lightgbm_tpu.utils import compile_cache

    os.makedirs(args.out, exist_ok=True)
    cache = {"dir": compile_cache.cache_dir(),
             "from_env": bool(os.environ.get(compile_cache.ENV_VAR)),
             "entries_before": compile_cache.entry_count()}
    log(f"compile cache {cache['dir']} "
        f"({'JAX_COMPILATION_CACHE_DIR' if cache['from_env'] else 'default'})"
        f": {cache['entries_before']} entries")

    ctx = {"args": args, "device": device, "results": {},
           "train_xy": make_higgs_like(args.rows),
           "valid_xy": make_higgs_like(args.valid_rows, seed=43)}
    plan = [("parity", phase_parity), ("train", phase_train),
            ("predict", phase_predict), ("serve", phase_serve)]
    if args.devices > 1:
        plan += [("train_dp", phase_train_dp), ("predict_dp", phase_predict_dp)]

    phases, ok = [], True
    for name, fn in plan:
        if not ok:
            phases.append({"name": name, "status": "not_run"})
            continue
        log(f"phase {name} ...")
        t0 = time.perf_counter()
        try:
            detail, status = fn(ctx), "ok"
        except Exception as e:  # noqa: BLE001 — a phase boundary: record, fail
            traceback.print_exc()
            detail = {"error": f"{type(e).__name__}: {e}"[:2000]}
            status, ok = "failed", False
        secs = time.perf_counter() - t0
        log(f"phase {name}: {status} in {secs:.1f}s")
        ctx["results"][name] = detail
        phases.append({"name": name, "status": status,
                       "seconds": round(secs, 2), **detail})

    cache["entries_after"] = compile_cache.entry_count()
    result = {"ok": ok, "device": device, "platform": device["platform"],
              "dry_run": args.dry_run, "versions": vers,
              "config": {"rows": args.rows, "cols": N_FEAT,
                         "max_bin": MAX_BIN, "num_leaves": args.leaves,
                         "trees": args.trees, "devices": args.devices},
              "phases": phases, "compile_cache": cache,
              "total_seconds": round(time.perf_counter() - t_start, 1),
              "note": "times are single smoke observations, not benchmarks"}
    line = json.dumps(result)
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    # the verdict is the LAST stdout line and holds these keys and no others
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
