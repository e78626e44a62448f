"""Gradient/hessian histogram construction — the hot op.

This replaces the reference's CPU histogram loops (``dense_bin.hpp:97-142``),
its col-wise/row-wise auto-tuner (``train_share_states.h``) and its three
OpenCL/CUDA kernels (``src/treelearner/ocl/histogram{16,64,256}.cl``).

TPUs have no fast scatter atomics, so the scatter-add is reformulated as a
**one-hot matmul on the MXU**: for each feature, ``hist[f] = onehotᵀ @ [g,h,m]``
where the one-hot is built per row-chunk and never materialized in HBM
(``lax.scan`` over chunks; a Pallas kernel with VMEM-resident one-hot is the
planned fast path).  An XLA scatter-add variant is kept for CPU tests and as a
fallback (``method='scatter'``).

Output layout: ``[num_features, max_bin, 6]`` float32, the channels
(sum_grad, sum_hess, count) as a pair (below; ``fold_hist`` gives ``[...,
3]``) — dense and uniform so the whole tree learner is one compiled program
(features with fewer bins simply leave the tail at zero).

Every builder adds its row blocks with a compensated float32 sum
(``two_sum``), so a bin's sum is right to a float32 ulp of *itself* however
many millions of rows went into it, and every builder returns ``[..., 6]``:
the float32 sum in channels 0:3 and what it rounds away in 3:6.  The
growers store that pair and subtract siblings in it (``sub_hist``), so a
child that is what a 17M-row parent leaves over keeps sums that are right
relative to its own size; ``fold_hist`` gives the float32 view the split
search reads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .onehot_variants import accumulate_block, two_sum

# Shared parity bar for every one-hot/Pallas histogram kernel vs the exact
# scatter-add (or the true-f32 XLA one-hot): the kernels accumulate a bf16
# (hi, lo) split-precision pair — or the int8 variant's multi-level
# quantized pair — whose lo-residual rounding is ~2^-18 per row; summed over ~N/B rows
# per bin this measures 1.2e-4 at 200k rows on v5e.  5e-4 gives shape
# headroom while still
# rejecting bare-bf16 accumulation by >200x (the lo-collapse bug class
# measures ~1e-1 against a true-f32 reference).  The reference side MUST be
# true f32: _hist_onehot pins precision=HIGHEST internally — at DEFAULT TPU
# matmul precision it is itself bf16-grade (relerr 0.13 vs the exact
# scatter-add), which once masked that very bug.  Import this constant
# everywhere a kernel parity check lives (chip_smoke.py, tests/test_dual.py,
# tests/test_onehot_variants.py) — a tolerance re-derived in one place and
# drifted in another is how the round-4 incident stayed hidden.
HIST_PARITY_TOL = 5e-4


# rows ``_hist_scatter`` sums at once (``_scatter_block``)
_XLA_BLOCK_ROWS = 512


def fold_hist(h: jax.Array) -> jax.Array:
    """``[..., 6]`` pair -> its ``[..., 3]`` float32 value."""
    return h[..., :3] + h[..., 3:]


def add_hist(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a + b`` of two ``[..., 6]`` pairs, as a normalized pair: the error
    is relative to the *result's* size times float32's epsilon squared, not
    to the operands'."""
    s, e = two_sum(a[..., :3], b[..., :3])
    hi, lo = two_sum(s, e + (a[..., 3:] + b[..., 3:]))
    return jnp.concatenate([hi, lo], axis=-1)


def sub_hist(parent: jax.Array, child: jax.Array) -> jax.Array:
    """Sibling histogram by subtraction (reference
    ``FeatureHistogram::Subtract``, ``feature_histogram.hpp:79``), in pairs."""
    return add_hist(parent, -child)


def psum_hist(h: jax.Array, axis_name: str) -> jax.Array:
    """Sum of every shard's ``[..., 6]`` pair over ``axis_name``, as a pair:
    a plain ``psum`` would round each bin to an ulp of the global sum, which
    is the error the pairs exist to keep out of the store."""
    parts = jax.lax.all_gather(h, axis_name)             # [shards, ..., 6]
    return functools.reduce(add_hist, list(parts))


def psum_scatter_hist(h: jax.Array, axis_name: str, shards: int) -> jax.Array:
    """``psum_hist`` of which each of the ``shards`` keeps its own block of
    the leading axis (``lax.psum_scatter(..., tiled=True)`` for pairs): the
    shards exchange blocks, then each adds up its own."""
    parts = jax.lax.all_to_all(
        h.reshape((shards, h.shape[0] // shards) + h.shape[1:]), axis_name,
        split_axis=0, concat_axis=0)                     # [shards, block, ...]
    return functools.reduce(add_hist, list(parts))


def hist_totals(h: jax.Array) -> jax.Array:
    """``[..., 3]`` (sum_grad, sum_hess, count) of the rows a ``[..., C, B,
    6]`` pair histogram was built from: the sum of its first column's bins.
    Every row falls in one bin of every column, so any column would do; a
    leaf's totals taken here are right relative to the leaf's own sums, and
    its count is exact up to 2**24 rows."""
    return jnp.sum(fold_hist(h[..., 0, :, :]), axis=-2)


def _scatter_block(flat, gh, size):
    """One row block's histogram by scatter-add, exactly: ``flat`` [R, F] flat
    bin indices below ``size``, ``gh`` [R, 3] -> the pair ``(sum [size, 3],
    what it rounds away [size, 3])``.

    A float32 scatter-add rounds every add to an ulp of the running sum, so a
    row of weight 0.01 that lands on a row of weight 100 loses half its
    digits.  Here each channel's values are cut into limbs on a
    power-of-two grid under the block's largest (integers of so few bits
    that a bin's sum over the block stays under 2**24, which float32 adds
    exactly, in any order), the limbs are scattered, and their exact sums
    are put together with ``two_sum``: at least 40 bits under the block's
    largest value (3 x 14 at 512 rows), so that the same rows give the same
    float32 sums in whatever blocks they come."""
    r, f = flat.shape
    bits = 23 - max(0, (r - 1).bit_length())
    n_limbs = -(-40 // bits)
    _, e = jnp.frexp(jnp.max(jnp.abs(gh), axis=0))            # [3]: max < 2**e
    q = jnp.ldexp(jnp.float32(1.0), jnp.maximum(e, -60) - bits)
    limbs, scales, rest = [], [], gh
    for _ in range(n_limbs):
        limb = jnp.round(rest / q)
        rest = rest - limb * q                               # exact
        limbs.append(limb)
        scales.append(q)
        q = q * jnp.float32(2.0 ** -(bits + 1))
    vals = jnp.concatenate(limbs, axis=-1)                    # [R, 3 limbs]
    vals = jnp.broadcast_to(vals[:, None, :], (r, f, 3 * n_limbs)).reshape(
        r * f, 3 * n_limbs)
    sums = jnp.zeros((size, 3 * n_limbs), jnp.float32).at[
        flat.reshape(-1)].add(vals)
    x, xe = two_sum(sums[:, :3] * scales[0], sums[:, 3:6] * scales[1])
    for i in range(2, n_limbs):
        xe = xe + sums[:, 3 * i:3 * i + 3] * scales[i]
    return x, xe


def _as_pair(s, c) -> jax.Array:
    """A running sum and its compensation -> the normalized ``[..., 6]``."""
    hi, lo = two_sum(s, c)
    return jnp.concatenate([hi, lo], axis=-1)


def _compensated_sum(block_fn, xs, shape) -> jax.Array:
    """Sum over the leading axis of the pytree ``xs`` of ``block_fn(x)`` (a
    sum ``shape = [..., 3]`` and what it rounds away), as a ``[..., 6]``
    pair."""
    def body(carry, x):
        s, c = carry
        x, xe = block_fn(x)
        s, e = two_sum(s, x)
        return (s, c + (e + xe)), None

    zero = jnp.zeros(shape, jnp.float32)
    (s, c), _ = jax.lax.scan(body, (zero, zero), xs)
    return _as_pair(s, c)


def _pallas_interpret_default() -> bool:
    """Off-TPU the Pallas kernels run in interpret mode (pure-XLA
    emulation): the CPU tier-1 suite can parity-check every variant of the
    PRODUCTION kernels without hardware.  On TPU they lower for real."""
    return jax.default_backend() != "tpu"


def build_histogram(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                    mask: jax.Array, max_bin: int, *,
                    method: str = "onehot", chunk_rows: int = 65536,
                    f_limit: "int | None" = None,
                    variant: str = "base") -> jax.Array:
    """Dispatch over histogram kernels; see module docstring.

    method: 'pallas' (fused VMEM one-hot, TPU), 'onehot' (XLA matmul),
    'scatter' (XLA scatter-add, CPU tests).

    f_limit: only the first ``f_limit`` columns carry real bins (the grower
    packs gradient bytes into trailing columns); the pallas kernel skips the
    rest at one-hot build time, the XLA fallbacks return them as garbage for
    the caller to slice off.

    variant: one-hot build strategy for the pallas kernels (a registry name
    from ops/onehot_variants.py — lane packing, staged compare, int8 MXU,
    ...); ignored by the XLA fallbacks.

    Returns ``[F, B, 6]``, the sums and what float32 rounds off them (module
    docstring)."""
    if method == "pallas":
        return _hist_pallas(bins, grad, hess, mask, max_bin, f_limit=f_limit,
                            variant=variant)
    return _build_histogram_xla(bins, grad, hess, mask, max_bin,
                                method=method, chunk_rows=chunk_rows)


def _build_histogram_xla(bins, grad, hess, mask, max_bin, *,
                         method="onehot", chunk_rows=65536):
    """Compute per-feature (grad, hess, count) histograms over masked rows.

    Args:
      bins: ``[N, F]`` uint8/uint16 binned features.
      grad, hess: ``[N]`` float32.
      mask: ``[N]`` float32 row weights (0.0 excludes a row; bagging uses
        fractional weights for GOSS-style scaling of the count channel too).
      max_bin: static histogram width ``B``.
      method: 'onehot' (MXU matmul) or 'scatter' (XLA scatter-add).

    Returns: ``[F, B, 6]`` float32 (module docstring).
    """
    if method == "scatter":
        return _hist_scatter(bins, grad, hess, mask, max_bin)
    return _hist_onehot(bins, grad, hess, mask, max_bin, chunk_rows)


def _hist_scatter(bins, grad, hess, mask, max_bin):
    n, f = bins.shape
    gh = jnp.stack([grad * mask, hess * mask, mask], axis=-1)        # [N, 3]
    # clip keeps out-of-range values (e.g. the grower's packed gh byte-columns)
    # inside their own column's space; the one-hot paths drop them by compare
    clipped = jnp.minimum(bins.astype(jnp.int32), max_bin - 1)
    flat = clipped + max_bin * jnp.arange(f, dtype=jnp.int32)[None, :]
    br = max(1, min(_XLA_BLOCK_ROWS, n))
    pad = (-n) % br
    if pad:     # padded rows weigh nothing
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
        gh = jnp.pad(gh, ((0, pad), (0, 0)))

    out = _compensated_sum(
        lambda x: _scatter_block(*x, f * max_bin),
        (flat.reshape(-1, br, f), gh.reshape(-1, br, 3)), (f * max_bin, 3))
    return out.reshape(f, max_bin, 6)


def _hist_onehot(bins, grad, hess, mask, max_bin, chunk_rows):
    # gh on the LEFT of the dot: [3, chunk] @ [chunk, F*B].  The tiny "3" dim
    # lands on M (MXU sublane granularity 8) instead of N (lane granularity
    # 128), not the [F*B, chunk] @ [chunk, 3] orientation.
    #
    # precision=HIGHEST: on TPU the DEFAULT matmul precision rounds f32
    # inputs to bf16 (one MXU pass), which silently degrades this "f32
    # fallback" to bare-bf16 histograms — measured relerr 0.13 vs the exact
    # scatter-add on v5e (scripts/debug_bf16_fence2.py).  This path is the
    # CPU fallback and the accuracy reference for the Pallas kernels, so it
    # must be truly f32; HIGHEST is a no-op on CPU and costs extra MXU
    # passes only where this non-hot path runs on TPU.
    n, f = bins.shape
    gh = jnp.stack([grad * mask, hess * mask, mask], axis=0).astype(jnp.float32)  # [3, N]
    chunk = min(chunk_rows, n)
    pad = (-n) % chunk
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        gh = jnp.pad(gh, ((0, 0), (0, pad)))
    n_chunks = (n + pad) // chunk
    bins_c = bins.reshape(n_chunks, chunk, f)
    gh_c = gh.reshape(3, n_chunks, chunk).transpose(1, 0, 2)        # [nc, 3, chunk]

    def block(xs):
        b, g = xs                                   # [chunk, F], [3, chunk]
        onehot = (b.astype(jnp.int32)[:, :, None] ==
                  jnp.arange(max_bin, dtype=jnp.int32)[None, None, :])
        onehot = onehot.astype(jnp.float32).reshape(chunk, f * max_bin)
        return jax.lax.dot_general(
            g, onehot,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32).T, 0.0      # [F*B, 3]

    hist = _compensated_sum(block, (bins_c, gh_c), (f * max_bin, 3))
    return hist.reshape(f, max_bin, 6)


def _split_bf16_pair(gh: jax.Array) -> jax.Array:
    """Split-precision prep for the bf16 histogram matmuls: stack the f32
    channel rows into (hi, lo) bf16 halves with hi = bf16(x),
    lo = bf16(x - f32(hi)) so the pair carries ~16 mantissa bits.

    The rounding MUST be fenced with ``optimization_barrier``: under jit,
    XLA's excess-precision simplification rewrites ``f32(bf16(x))`` back to
    ``x`` (allowed by ``xla_allow_excess_precision``, default on), which
    collapses ``lo`` to exactly zero and silently degrades every histogram
    to bare-bf16 accuracy (relerr ~1e-2 — caught on v5e hardware by the
    batched-leaf parity check ``chip_smoke.py`` now runs, round 4; the
    repro is ``lo == 0`` in-jit but not eagerly)."""
    hi = jax.lax.optimization_barrier(gh.astype(jnp.bfloat16))
    lo = (gh - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, lo], axis=0)


def _gh6(grad, hess, mask):
    """Channel prologue shared by the Pallas kernels: stack the three f32
    channels (g·m, h·m, m) and split each into the bf16 (hi, lo) pair."""
    gh = jnp.stack([grad * mask, hess * mask, mask], axis=0).astype(jnp.float32)
    return _split_bf16_pair(gh)


def build_histogram_leaves(comb: jax.Array, grad: jax.Array, hess: jax.Array,
                           mask: jax.Array, block_leaf: jax.Array,
                           num_slots: int, max_bin: int, *,
                           method: str = "onehot", block_rows: int = 512,
                           f_limit: "int | None" = None,
                           variant: str = "base") -> jax.Array:
    """Per-leaf histograms of leaf-grouped row blocks — the frontier grower's
    batched analog of ``build_histogram``.

    ``comb`` is ``[C, NC]`` gathered rows laid out as consecutive
    ``block_rows``-sized blocks, each block belonging to ONE leaf slot
    (``block_leaf[C // block_rows]`` i32, sorted ascending); padded rows
    carry ``mask == 0``.  Returns ``[num_slots, F, B, 6]`` (pairs, as
    ``build_histogram``) where ``F = f_limit or NC`` on every path (both the Pallas kernel and the XLA
    fallback slice the trailing packed-gradient columns off before any
    histogramming, so neither pays for columns the caller discards).

    The Pallas path transposes the gathered rows ONCE in XLA and feeds the
    one-hot MXU kernel ``(f, BR)`` feature-major blocks, with the whole
    ``[num_slots, 6, F*Bp]`` accumulator VMEM-resident for the full grid;
    each row block accumulates into its ``block_leaf``-indexed slot row and
    the buffer flushes to HBM once (the reference GPU kernels' per-workgroup
    shared-memory accumulation, ``histogram256.cl:100``, with the slot index
    replacing the workgroup->feature-group map).  ``block_leaf`` need not be
    sorted and slots may be empty (they come back zero).
    """
    n, nc = comb.shape
    f = min(f_limit, nc) if f_limit is not None else nc
    _lanes = f * (-(-max_bin // 128) * 128)
    if method == "pallas" and _lanes <= _PALLAS_LEAVES_MAX_LANES \
            and num_slots * 6 * _lanes * 4 <= _PALLAS_LEAFACC_BYTES:
        return _hist_leaves_pallas(comb, grad, hess, mask, block_leaf,
                                   num_slots, max_bin, block_rows, f,
                                   variant=variant)
    # XLA fallback: a scatter-add a row block (fast on CPU, exact:
    # ``_scatter_block``), each block's histogram added to its leaf slot's
    # running pair.  The packed-gradient tail columns are sliced off BEFORE the flat
    # index is built: scattering them too made the CPU test path pay
    # gh_cols * max_bin extra scatter targets for garbage the caller
    # discarded anyway.
    comb_f = comb[:, :f] if f < nc else comb
    gh = jnp.stack([grad * mask, hess * mask, mask], axis=-1)       # [C, 3]
    clipped = jnp.minimum(comb_f.astype(jnp.int32), max_bin - 1)
    flat = jnp.arange(f, dtype=jnp.int32)[None, :] * max_bin + clipped
    br = block_rows

    def body(carry, x):
        s, c = carry                                # [num_slots, F*B, 3]
        fl, g, slot = x
        h, he = _scatter_block(fl, g, f * max_bin)
        new, e = two_sum(s[slot], h)
        return (s.at[slot].set(new), c.at[slot].add(e + he)), None

    zero = jnp.zeros((num_slots, f * max_bin, 3), jnp.float32)
    (s, c), _ = jax.lax.scan(
        body, (zero, zero),
        (flat.reshape(-1, br, f), gh.reshape(-1, br, 3), block_leaf))
    return _as_pair(s, c).reshape(num_slots, f, max_bin, 6)


def _hist_leaves_pallas(comb, grad, hess, mask, block_leaf, num_slots,
                        max_bin, block_rows, f, variant="base",
                        interpret=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .onehot_variants import VARIANTS, feat_geometry, finish_hist

    spec = VARIANTS[variant]
    n, nc = comb.shape
    B = max_bin
    Bp = -(-B // 128) * 128
    BR = block_rows
    assert n % BR == 0 and BR % 128 == 0
    nb = n // BR
    if interpret is None:
        interpret = _pallas_interpret_default()

    f_pad, lanes = feat_geometry(spec, f, B, Bp)   # lane-pack group align

    rows = spec.prep(grad, hess, mask)                        # [R, C]
    # transpose ONCE in XLA (one u8 relayout), NOT per block in the kernel:
    # Mosaic lowers an in-kernel small-tile [BR, f].T to lane/sublane
    # shuffles that dominate the whole kernel
    comb_t = comb[:, :f].T                                        # [f, C] u8
    if f_pad > f:
        # padded features histogram real rows at bin 0 of their own lane
        # slot, which finish_hist's [:f] slice drops
        comb_t = jnp.pad(comb_t, ((0, f_pad - f), (0, 0)))

    # The WHOLE [num_slots, 6, f*Bp] accumulator rides one constant-index
    # output block: it stays VMEM-resident across the entire grid (k=16
    # slots x 28 feats x 256 bins f32 = 2.8MB) and flushes to HBM once.
    # This zeroes every slot up front — a slot with no row blocks is
    # well-defined zeros, not stale HBM.  The per-block accumulate never
    # indexes out_ref dynamically: both dynamic-index formulations
    # miscompiled data-dependently on real v5e hardware (a [1,6,f*Bp]
    # output block keyed on bl[i], and an out_ref[pl.ds(sl,1)] += store
    # whose 6-sublane slot slabs are not (8,128)-tile aligned, each dropped
    # the lo-half bf16-residual contributions for some block_leaf patterns:
    # relerr ~1.8e-2 vs the ~3e-5 this split-precision design gives —
    # caught twice by the hardware parity check, round 4).  A block's slot is copied to a scratch and back under scalar
    # predicates, one statically indexed copy a slot, and the compensated
    # add is written once: sixteen copies of it made the Mosaic program (and
    # the host's time to load it) several times the size.  A block touches
    # one slot's rows, so a bad block's NaNs stay in its own slot.
    def kernel(bl_ref, bins_ref, gh_ref, out_ref, cur_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        slot = bl_ref[i]
        for sl in range(num_slots):
            @pl.when(slot == sl)
            def _load(sl=sl):
                cur_ref[:] = out_ref[sl]

        # the one-hot build + dot live in the variant registry
        # (ops/onehot_variants.py) — ONE set of kernel bodies shared with
        # _hist_pallas and the shootout
        cur_ref[:] = accumulate_block(
            cur_ref[:], spec.contrib(bins_ref[:], gh_ref[:],
                                     fc=f_pad, B=B, Bp=Bp, BR=BR))  # [6, lanes]
        for sl in range(num_slots):
            @pl.when(slot == sl)
            def _store(sl=sl):
                out_ref[sl] = cur_ref[:]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[pl.BlockSpec((f_pad, BR), lambda i, bl: (0, i)),
                  pl.BlockSpec((rows.shape[0], BR), lambda i, bl: (0, i))],
        out_specs=pl.BlockSpec((num_slots, 6, lanes),
                               lambda i, bl: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((6, lanes), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_slots, 6, lanes), jnp.float32),
        interpret=interpret,
    )(block_leaf.astype(jnp.int32), comb_t, rows)

    return finish_hist(out, f, B, Bp, spec)                   # [k, f, B, 6]


def unrolled_rank(sorted_vals: jax.Array, targets: jax.Array,
                  strict: bool) -> jax.Array:
    """Per-target count of entries in ``sorted_vals`` that are ``< target``
    (strict) or ``<= target``.  A statically-unrolled batched binary search:
    no while-loop sync overhead, and the probe is clamped so a span reaching
    past the array can never advance the count (the overshoot bug class)."""
    m = sorted_vals.shape[0]
    lo = jnp.zeros(targets.shape, jnp.int32)
    span = 1 << max(0, (m - 1).bit_length())
    while span >= 1:
        idx = lo + span - 1
        v = jnp.take(sorted_vals, jnp.minimum(idx, m - 1))
        cmp = (v < targets) if strict else (v <= targets)
        lo = jnp.where((idx < m) & cmp, lo + span, lo)
        span >>= 1
    return lo


_PALLAS_BLOCK_ROWS = 1024
# lane budget per feature block: FC features of Bp padded bins ride the MXU
# as one [6, BR] x [FC*Bp, BR]^T dot.  FC has an 8-sublane floor (the bins
# block is (FC, BR)), so for wide bins (Bp > 256) the lane budget alone
# cannot bound the one-hot tile — _hist_pallas also shrinks BR to keep
# FC*Bp*BR bf16 within _PALLAS_ONEHOT_BYTES of VMEM.
_PALLAS_BLOCK_LANES = 2048
# No pallas_call here passes vmem_limit_bytes, so every kernel lives under
# Mosaic's default scoped VMEM limit (about 16 MB a core), not the chip's
# physical VMEM.  8MB for the tile lets BR (grid-step row count) stay large
# enough to amortize per-step overheads, and at the bench shape (28 x 256)
# the kernels compile and pass parity on v5e under that limit
# (chip_smoke.py, PR 22).  Wider shapes have not met the chip.
_PALLAS_ONEHOT_BYTES = 8 * 1024 * 1024


# cap on the batched-leaf kernel, whose bins block spans all f at once (a
# single feature block), so that the 128-row BR floor never busts
# _PALLAS_ONEHOT_BYTES: f*Bp*128 bf16 <= 8MiB  =>  f*Bp <= 32768
_PALLAS_LEAVES_MAX_LANES = 32768

# the batched-leaf kernel keeps its whole [num_slots, 6, f*Bp] f32
# accumulator VMEM-resident for the full grid.  This cap was written against
# physical VMEM and is far above the default scoped limit the kernel
# actually compiles under (see above): the accumulator is 2.8MB at the bench
# shape (16 slots x 28 x 256), and nothing near the cap has ever compiled.
_PALLAS_LEAFACC_BYTES = 48 * 1024 * 1024


def _hist_pallas(bins, grad, hess, mask, max_bin, block_rows=None,
                 f_limit=None, variant="base", interpret=None):
    """Fused histogram: Pallas TPU kernel, bf16 split-precision one-hot matmul.

    TPUs have no fast scatter atomics, so the scatter-add is a one-hot matmul
    on the MXU.  The key design point vs a naive formulation:

    - **bf16 at f32 accuracy**: the one-hot is exactly representable in bf16,
      and each f32 channel value is split into hi = bf16(x) plus
      lo = bf16(x - hi), giving ~16 mantissa bits across the pair.  The six
      rows (g_hi, h_hi, m_hi, g_lo, h_lo, m_lo) ride the SAME matmul (M <= 8
      sublanes is free) with f32 accumulation, so the whole histogram runs at
      the MXU's bf16 rate with ~1e-5 relative error.
    The layout is **feature-major blocked**: bins are transposed ONCE in
    XLA to ``[f_pad, Npad]`` (one u8 relayout) and the block is
    ``(FC, BR)`` — FC on sublanes (8-aligned), BR on lanes (128-aligned) —
    with grid (feature_blocks, row_blocks), rows minor, so each [6, FC*Bp]
    output block accumulates in VMEM while the one-hot only ever exists as
    a [FC*Bp, BR] tile.  (Feeding ``(BR, f)`` row-major blocks and
    transposing each tile inside the kernel lowers to lane/sublane shuffles
    that dominate the kernel on v5e; what the ledger holds of the kernels'
    speed is in ``PERF.md`` section 5.)

    The one-hot build + dot bodies live in the variant registry
    (``ops/onehot_variants.py``) — ``variant`` selects the build strategy
    (lane packing, staged compare, int8 MXU, ...); this function owns only
    the grid/BlockSpec shells and the fixed layout lessons above.

    This replaces the reference's CPU hot loop (``dense_bin.hpp:97-142``) and
    its per-workgroup local-memory GPU kernels
    (``src/treelearner/ocl/histogram256.cl:100``).
    """
    from jax.experimental import pallas as pl

    from .onehot_variants import VARIANTS, finish_hist

    spec = VARIANTS[variant]
    n, f_cols = bins.shape
    f = min(f_limit, f_cols) if f_limit is not None else f_cols
    B = max_bin
    Bp = -(-B // 128) * 128                      # lane-tile aligned bin width
    if not spec.supports(B):
        raise ValueError(
            f"hist variant {variant!r} does not support max_bin={B} "
            "(resolve the variant with onehot_variants.resolve first)")
    gf = spec.group_feats(B, Bp)                 # features per lane group
    lpf = spec.group_lanes(B, Bp) // gf          # output lanes per feature
    if interpret is None:
        interpret = _pallas_interpret_default()

    rows = spec.prep(grad, hess, mask)           # [R, N]: bf16 pair or f32

    if f < f_cols:
        bins = bins[:, :f]                       # drop packed-gradient cols
    # features per block: 8-sublane floor, lane-pack group multiple
    align = max(8, gf)
    FC = max(align, (_PALLAS_BLOCK_LANES // lpf) // align * align)
    n_fb = -(-f // FC)
    f_pad = n_fb * FC
    lanes = FC * lpf                             # output lanes per block
    # bound the VMEM-resident one-hot tile: FC*lpf*BR (2-byte worst
    # case; the int8 variant's tile is half that) <= budget
    br_cap = max(128, (_PALLAS_ONEHOT_BYTES // (2 * FC * lpf)) // 128 * 128)
    BR = max(128, min(block_rows or _PALLAS_BLOCK_ROWS, br_cap,
                      -(-n // 128) * 128))
    pad = (-n) % BR
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    bins_t = jnp.pad(bins.T, ((0, f_pad - f), (0, pad)))      # [f_pad, Npad]
    n_rb = (n + pad) // BR

    def kernel_fm(bins_ref, gh_ref, out_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        out_ref[:] = accumulate_block(
            out_ref[:], spec.contrib(bins_ref[:], gh_ref[:],
                                     fc=FC, B=B, Bp=Bp, BR=BR))

    out = pl.pallas_call(
        kernel_fm,
        out_shape=jax.ShapeDtypeStruct((6, n_fb * lanes), jnp.float32),
        grid=(n_fb, n_rb),
        in_specs=[pl.BlockSpec((FC, BR), lambda fb, i: (fb, i)),
                  pl.BlockSpec((rows.shape[0], BR), lambda fb, i: (0, i))],
        out_specs=pl.BlockSpec((6, lanes), lambda fb, i: (0, fb)),
        interpret=interpret,
    )(bins_t, rows)

    return finish_hist(out, f, B, Bp, spec)


def gather_rows(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                mask: jax.Array, cap: int):
    """Compact the rows with ``mask > 0`` into fixed-capacity buffers.

    The TPU analog of the reference's per-leaf index ranges
    (``data_partition.hpp:21-170``): instead of histogramming all N rows with
    a mask, gather the (≤ cap) active rows so downstream cost is O(cap).
    Rows beyond ``cap`` would be silently dropped — callers must guarantee
    ``sum(mask > 0) <= cap``.

    Returns (bins[cap, F], grad[cap], hess[cap], mask[cap]).
    """
    n = bins.shape[0]
    active = mask > 0
    # scatter-free compaction: the k-th active row is the first index whose
    # running count reaches k+1 — a batched binary search over the monotone
    # cumsum.  (A scatter formulation benched 5x slower on TPU: scatters
    # serialize; jnp.searchsorted's while-loop benched ~1ms of per-step sync
    # overhead, so the search is unrolled; scripts/profile_gather.py.)
    cs = jnp.cumsum(active.astype(jnp.int32))
    targets = jnp.arange(1, cap + 1, dtype=jnp.int32)         # [cap]
    row_ids = jnp.minimum(unrolled_rank(cs, targets, strict=True), n - 1)
    filled = targets <= cs[-1]
    return (jnp.take(bins, row_ids, axis=0),
            jnp.take(grad, row_ids),
            jnp.take(hess, row_ids),
            jnp.where(filled, jnp.take(mask, row_ids), 0.0))


def accumulate_histogram(acc: jax.Array, bins: jax.Array, grad: jax.Array,
                         hess: jax.Array, mask: jax.Array, max_bin: int, *,
                         method: str = "onehot", chunk_rows: int = 65536,
                         variant: str = "base") -> jax.Array:
    """Block-accumulating entry point: ``acc + histogram(block)``, in pairs.

    The out-of-core trainer (lightgbm_tpu/stream, docs/STREAMING.md) folds
    one streamed row block into a running ``[F, B, 6]`` pair with this op —
    the same kernels as ``build_histogram``, so the accumulated
    result is subtracted (``sub_hist``) and folded for
    ``split.find_best_split`` like an in-memory grower's, and the streamed
    blocks add up as exactly as a kernel's own row blocks do."""
    return add_hist(acc, build_histogram(bins, grad, hess, mask, max_bin,
                                         method=method, chunk_rows=chunk_rows,
                                         variant=variant))
