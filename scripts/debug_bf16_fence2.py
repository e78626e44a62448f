"""Confirm: the parity 'reference' (_hist_onehot) runs at bf16 matmul
precision on TPU by default; against a truly-f32 reference the fenced
split-precision kernels are accurate."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def emit(**kv):
    kv["ts"] = time.time()
    print(json.dumps(kv), flush=True)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.ops import histogram as H

    emit(stage="sanity", backend=jax.default_backend())
    rng = np.random.default_rng(3)
    n, f, b = 200_000, 28, 255
    bins = jnp.asarray(rng.integers(0, b, size=(n, f), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
    m = jnp.asarray((rng.uniform(size=n) < 0.8).astype(np.float32))

    def relerr(a, bb):         # of two pair histograms
        a, bb = H.fold_hist(a), H.fold_hist(bb)
        return float(jnp.max(jnp.abs(a - bb) / (jnp.abs(bb) + 1.0)))

    # truly-f32 references: scatter-add, and onehot at 'highest' precision
    ref_sc = jax.jit(lambda *x: H._hist_scatter(*x, b))(bins, g, h, m)
    with jax.default_matmul_precision("highest"):
        ref_oh = jax.jit(lambda *x: H._hist_onehot(*x, b, 65536))(bins, g, h, m)
    emit(stage="scatter_vs_onehot_highest", relerr=relerr(ref_oh, ref_sc))

    ref_oh_default = jax.jit(lambda *x: H._hist_onehot(*x, b, 65536))(
        bins, g, h, m)
    emit(stage="onehot_default_vs_scatter", relerr=relerr(ref_oh_default, ref_sc))

    got = jax.jit(lambda *x: H._hist_pallas(*x, b))(bins, g, h, m)
    emit(stage="pallas_fenced_vs_scatter", relerr=relerr(got, ref_sc))

    # batched-leaf kernel vs scatter ref (the gate that caught the collapse)
    BR, NB, NC, B, k = 512, 24, 32, 255, 6
    C = BR * NB
    comb = jnp.asarray(rng.integers(0, B, size=(C, NC), dtype=np.uint8))
    g2 = jnp.asarray(rng.normal(size=C).astype(np.float32))
    h2 = jnp.asarray(rng.uniform(0.1, 1.0, size=C).astype(np.float32))
    m2 = jnp.asarray((rng.uniform(size=C) < 0.8).astype(np.float32))
    bl = np.sort(rng.integers(0, k, size=NB)).astype(np.int32)
    bl = jnp.asarray(np.where(bl == k - 2, k - 1, bl))
    got = jax.jit(lambda *x: H._hist_leaves_pallas(*x, k, B, BR, 28))(
        comb, g2, h2, m2, bl)
    ref = jax.jit(lambda *x: H.build_histogram_leaves(
        *x, k, B, method="scatter", block_rows=BR, f_limit=28))(
        comb, g2, h2, m2, bl)
    emit(stage="batched_leaves_vs_scatter", relerr=relerr(got, ref[:, :28]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
