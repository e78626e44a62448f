"""Dual-kernel / dual-grower parity on the default backend (the TPU).

The hardware half of ``tests/test_dual.py``: the CPU CI backend runs the
Pallas kernels in interpret mode, so the r02-class failure (a lowering crash
only a real TPU invocation surfaces) and the miscompile class (a kernel that
compiles and is wrong) are caught here.  ``chip_smoke.py`` runs
:func:`run_kernel_checks` as its first phase.

Checks, in order (each emits one JSON line; any failure exits nonzero):
  0. both production kernels vs the exact scatter-add at the bench width,
     masked rows and fractional weights          (``run_kernel_checks``)
  1. pallas row-major one-hot kernel vs XLA one-hot         (both layouts)
  2. pallas feature-major blocked kernel vs XLA one-hot     (wide features)
  3. frontier-vs-serial grower: identical trees on the TPU

Run (the ONLY process touching the TPU):
    python scripts/bench_dual.py
"""
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import load_obs  # noqa: E402


def main() -> int:
    log = load_obs().EventLog.default(echo=True)

    def emit(**kv):
        log.emit(kv.pop("stage", "bench_record"), **kv)

    import jax
    backend = jax.default_backend()
    emit(stage="sanity", backend=backend)
    rc = run_checks(emit)
    # one-JSON-line contract: the LAST stdout line is the schema summary
    log.summary(bench="dual_parity", ok=rc == 0, rc=rc, backend=backend)
    return rc


def run_kernel_checks(emit, n_feat=28, max_bin=256, variants=("base",),
                      slots=16, block_rows=512, rows=200_000) -> dict:
    """The two production Pallas kernels (``_hist_pallas``, the whole-data
    pass, and ``_hist_leaves_pallas``, the frontier grower's batched-leaf
    pass) against the EXACT scatter-add at one width, for each one-hot
    variant named.  Rows are both masked (weight 0) and fractionally
    weighted, as bagging and GOSS make them.  Defaults are the bench shape:
    28 columns, the 256-wide kernel histogram ``max_bin=255`` trains with,
    16 leaf slots of 512-row blocks.

    Returns ``{"hist_pallas/<variant>": relerr, "hist_leaves_pallas/<variant>":
    relerr}``; a kernel that fails to compile or run records ``inf``.  The
    caller compares against ``HIST_PARITY_TOL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.ops.histogram import (_hist_leaves_pallas, _hist_pallas,
                                            _hist_scatter,
                                            build_histogram_leaves, fold_hist)
    rng = np.random.default_rng(3)

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
                emit(stage="kernel_parity", kernel=key, relerr=errs[key],
                     n_feat=n_feat, max_bin=max_bin)
            except Exception as e:     # a lowering crash is a result too
                errs[key] = float("inf")
                emit(stage="kernel_parity", kernel=key,
                     error=f"{type(e).__name__}: {e}"[:300])
    return errs


def run_checks(emit) -> int:
    """All dual checks, in-process (importable by tpu_perf_suite so only ONE
    process ever touches the TPU).  Returns 0 when every check passes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.ops.histogram import (_hist_onehot, _hist_pallas,
                                            fold_hist)
    rng = np.random.default_rng(3)

    def data(n, f, b):
        bins = jnp.asarray(rng.integers(0, b, size=(n, f), dtype=np.uint8))
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        h = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
        m = jnp.asarray((rng.uniform(size=n) < 0.8).astype(np.float32))
        return bins, g, h, m

    def relerr(a, b):
        return float(jnp.max(jnp.abs(a - b) / (jnp.abs(b) + 1.0)))

    rc = 0

    # Parity threshold: the shared lo-residual-floor constant from
    # ops/histogram.py (its derivation lives on the constant) — ONE number
    # for every kernel parity gate, hardware or interpret.
    from lightgbm_tpu.ops.histogram import HIST_PARITY_TOL as TOL

    # 0: both production kernels vs the exact scatter-add at the bench width
    for err in run_kernel_checks(emit).values():
        rc |= 0 if err < TOL else 1

    # 1/2: one-hot kernel, both layouts (rowmajor is bench-opt-in but must
    # stay numerically correct while it exists)
    for name, (n, f, b) in (("rowmajor", (200_000, 28, 255)),
                            ("featmajor", (100_000, 200, 255))):
        bins, g, h, m = data(n, f, b)
        try:
            a = jax.jit(lambda *x: _hist_pallas(*x, b, layout=name))(
                bins, g, h, m)
            ref = jax.jit(lambda *x: _hist_onehot(*x, b, 65536))(bins, g, h, m)
            err = relerr(fold_hist(a), fold_hist(ref))
            ok = err < TOL
            emit(stage=f"pallas_{name}", ok=ok, relerr=err)
            rc |= 0 if ok else 1
        except Exception as e:
            emit(stage=f"pallas_{name}", ok=False, error=str(e)[:300])
            rc |= 1

    # 3: frontier-vs-serial grower on hardware — identical trees
    try:
        from sklearn.datasets import make_classification
        import lightgbm_tpu as lgb
        X, y = make_classification(n_samples=20000, n_features=12,
                                   n_informative=7, random_state=7)
        X = X.astype(np.float32)
        out = {}
        for grower in ("serial", "frontier"):
            p = {"objective": "binary", "num_leaves": 63, "verbose": -1,
                 "tree_grower": grower, "min_data_in_leaf": 20}
            ds = lgb.Dataset(X, label=y, params=p)
            out[grower] = lgb.train(p, ds, num_boost_round=3)
        d = float(np.abs(out["serial"].predict(X)
                         - out["frontier"].predict(X)).max())
        ok = d < 1e-4
        emit(stage="grower_dual", ok=ok, max_pred_diff=d)
        rc |= 0 if ok else 1
    except Exception as e:
        emit(stage="grower_dual", ok=False, error=str(e)[:300])
        rc |= 1

    emit(stage="done", rc=rc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
