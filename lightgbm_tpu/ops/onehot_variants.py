"""One-hot build variants for the histogram MXU kernels — the single registry.

The histogram build is a one-hot matmul on the MXU (ops/histogram.py), and
the one-hot construction is the kernel's bound: the production build is an
iota-compare-select over ``f*Bp*BR`` elements per block on the VPU, ~6 MXU
MACs of useful work per VPU-built element (docs/COMPONENTS.md, "Histogram
kernel layout").  Each registry entry changes how the one-hot tile is
built — or what rides the dot — so both production kernels and the
election draw from ONE set of kernel bodies that cannot drift apart.
This registry plus ``pick_variant`` replaces the
reference's col-wise/row-wise histogram auto-tuner (``train_share_states.h``)
with a TPU-native equivalent: the candidate axes are one-hot build
strategies, and the timed election runs once on device at first fit.

Variant families (``VARIANTS``):

  base      int32 iota compare -> bf16 select (the production shape)
  bf16cmp   bf16 iota + bf16 bins compare (2-byte compare lanes)
  i16cmp    int16 iota + int16 bins compare
  u8cmp     uint8 iota + raw u8 bins compare (1-byte compare lanes)
  sub1abs   onehot = max(0, 1 - |b - j|) in bf16 (no select, all-arith)
  staged    hierarchical hi/lo one-hot: outer product of a ``Bp/16``-wide
            hi-digit one-hot and a 16-wide lo-digit one-hot — ~Bp/16 + 16
            VPU compares per element instead of Bp, one multiply to combine
  packed    multi-feature lane packing (``128 % B == 0``, ``B <= 64``):
            k = 128//B features share one 128-lane group via the
            ``bin + f_local*B`` lane offset, cutting both the VPU one-hot
            element count and the MXU N-dim by k (at ``max_bin=64`` the
            unpacked kernel wastes 2x lanes on Bp=128 padding outright)
  int8      int8-MXU with f32 fixup: the one-hot is exact in int8 and the
            (g,h,m) rows are per-block three-level quantized (primary +
            two residual int8 fixups, per-row f32 scales) with int32
            accumulation — rides the int8 MXU rate at the same parity bar
            as the production bf16 (hi, lo) pair

Every variant is interchangeable at the ``build_histogram`` call site and
parity-checks against the exact scatter-add in Pallas interpret mode on CPU
(tests/test_onehot_variants.py), so no variant can land or drift without
tier-1 coverage; on the chip ``chip_smoke.py`` runs the same check.

jax is imported inside the kernel-body/prep functions: registry METADATA —
names, geometry, the VPU-work model — is plain-int machinery, and nothing
heavier loads until a kernel is actually built.
"""
from __future__ import annotations

from typing import Callable, NamedTuple


def padded_bins(max_bin: int) -> int:
    """Lane-tile-aligned bin width Bp (128-multiple)."""
    return -(-max_bin // 128) * 128


def pack_k(max_bin: int) -> int:
    """Features per 128-lane group for the lane-packing variant, or 0 when
    packing does not apply.  Packing slots are exactly ``max_bin`` lanes wide
    (the ``bin + f_local*B`` offset), so groups must tile 128 lanes with no
    remainder — otherwise the per-group pad would need an in-kernel lane
    concatenate, which Mosaic relayouts.  Supported widths are the divisors
    of 128 up to 64 (2/4/8/16/32/64); other kernel widths are reachable
    (gbdt rounds the kernel width to a 4-multiple, e.g. 60) and an explicit
    ``hist_variant=packed`` there falls back to 'base' with a warning via
    ``resolve``."""
    if max_bin <= 0 or max_bin > 64 or 128 % max_bin:
        return 0
    return 128 // max_bin


class VariantSpec(NamedTuple):
    """One one-hot build strategy, pluggable into every histogram kernel.

    The kernel shells (grid/BlockSpec plumbing in ops/histogram.py) stay
    generic; everything variant-specific lives here:

      prep(grad, hess, mask) -> [R, N] rows for the dot's LHS (R and dtype
          set the MXU rate: 6 bf16 rows for the split-precision pair, 3 f32
          rows for int8 — quantized per block inside the kernel).
      group_lanes/group_feats: output-lane geometry.  ``group_feats``
          features share one ``group_lanes``-wide lane group (1/Bp for the
          unpacked variants, k/128 for lane packing); feature-block sizes
          must be ``group_feats``-multiples.
      contrib(b, gh, fc, B, Bp, BR) -> [6, fc//group_feats*group_lanes] f32
          in-kernel per-block contribution (one-hot build + dot), to be
          accumulated by the shell (plain ``+=`` or the batched-leaf
          kernel's slot-select).  Rows are the (hi, lo) triple pairs that
          ``finish_hist`` sums.
      supports(B): static eligibility for a kernel bin width.
      vpu_compares(f, B, BR): per-row-block VPU compare count, the work
          model of docs/COMPONENTS.md "Histogram kernel layout".
    """
    name: str
    description: str
    prep: Callable
    group_lanes: Callable      # (B, Bp) -> int
    group_feats: Callable      # (B, Bp) -> int
    contrib: Callable          # kernel-side
    supports: Callable         # (B) -> bool
    vpu_compares: Callable     # (f, B, BR) -> int


# --------------------------------------------------------------------------
# shared pieces
# --------------------------------------------------------------------------

def _prep_bf16_pair(grad, hess, mask):
    """The production LHS: (g·m, h·m, m) split into a fenced bf16 (hi, lo)
    pair — see histogram._split_bf16_pair for why the fence is load-bearing."""
    from .histogram import _gh6
    return _gh6(grad, hess, mask)


def _prep_f32(grad, hess, mask):
    """Raw f32 channel rows; the int8 variant quantizes them per block
    INSIDE the kernel (scales are per row-block, so they cannot be baked
    outside the grid loop)."""
    import jax.numpy as jnp
    return jnp.stack([grad * mask, hess * mask, mask],
                     axis=0).astype(jnp.float32)


def _dot6(gh, onehot):
    """[R, BR] x [lanes, BR]^T -> [R, lanes] f32 (rows on M: <=8 sublanes
    ride free; lanes on N)."""
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(
        gh, onehot,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def two_sum(a, b):
    """Error-free addition (Knuth): ``s = fl(a + b)`` and the ``e`` with
    ``a + b == s + e`` exactly, whatever the magnitudes.  Six flops, no
    branch; neither XLA nor Mosaic reassociates float adds, so it survives
    compilation."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def accumulate_block(acc, contrib):
    """Add one row block's ``contrib`` ([6, lanes]: the hi triple, the lo
    triple) to the running ``acc`` ([6, lanes]: the sums, their
    compensation) without losing what a float32 add rounds away.

    A pass over 17M rows adds ~17,000 block sums into each accumulator; a
    plain ``+=`` rounds each add to an ulp of the *running* sum, and that
    error (1e-5 of a root bin) is what histogram subtraction later hands
    whole to a 20-row leaf.  Here both adds (hi + lo of the block, then the
    block onto the sums) are ``two_sum`` and what they lose goes into the
    compensation rows, which hold nothing else: their own rounding is
    relative to *their* size, a few ulps of the sums.  ``finish_hist``
    returns the pair.  Shared by every kernel shell, like ``contrib``."""
    import jax.numpy as jnp
    x, xe = two_sum(contrib[:3], contrib[3:])
    s, e = two_sum(acc[:3], x)
    return jnp.concatenate([s, acc[3:] + (e + xe)], axis=0)


def feat_geometry(spec: "VariantSpec", f: int, B: int, Bp: int):
    """(f_pad, lanes): the feature count padded to a lane-group multiple
    and the resulting output lane count (= MXU N-dim).  THE forward lane
    mapping — every kernel shell sizes its blocks through this one
    function, and ``finish_hist`` is its inverse.  Pure int math."""
    gf = spec.group_feats(B, Bp)
    f_pad = -(-f // gf) * gf
    return f_pad, (f_pad // gf) * spec.group_lanes(B, Bp)


def total_lanes(name: str, f: int, max_bin: int) -> int:
    """Output lane count (= MXU N-dim) a variant needs for ``f`` features —
    the structural size the lane-packing variant shrinks."""
    spec = VARIANTS[name]
    return feat_geometry(spec, f, max_bin, padded_bins(max_bin))[1]


# --------------------------------------------------------------------------
# contrib implementations (kernel-side bodies)
# --------------------------------------------------------------------------

def _contrib_base(b, gh, *, fc, B, Bp, BR):
    import jax
    import jax.numpy as jnp
    bi = b.astype(jnp.int32)
    bin_id = jax.lax.broadcasted_iota(jnp.int32, (fc, Bp, BR), 1)
    onehot = (bi[:, None, :] == bin_id).astype(jnp.bfloat16)
    return _dot6(gh, onehot.reshape(fc * Bp, BR))


def _contrib_bf16cmp(b, gh, *, fc, B, Bp, BR):
    import jax
    import jax.numpy as jnp
    bb = b.astype(jnp.bfloat16)                  # bins < 256: exact in bf16
    bin_id = jax.lax.broadcasted_iota(jnp.bfloat16, (fc, Bp, BR), 1)
    onehot = (bb[:, None, :] == bin_id).astype(jnp.bfloat16)
    return _dot6(gh, onehot.reshape(fc * Bp, BR))


def _contrib_i16cmp(b, gh, *, fc, B, Bp, BR):
    import jax
    import jax.numpy as jnp
    bi = b.astype(jnp.int16)
    bin_id = jax.lax.broadcasted_iota(jnp.int16, (fc, Bp, BR), 1)
    onehot = (bi[:, None, :] == bin_id).astype(jnp.bfloat16)
    return _dot6(gh, onehot.reshape(fc * Bp, BR))


def _contrib_u8cmp(b, gh, *, fc, B, Bp, BR):
    # 1-byte compare domain (u8 lanes pack 4x vs i32; Bp=256 spans u8 exactly)
    import jax
    import jax.numpy as jnp
    bin_id = jax.lax.broadcasted_iota(jnp.uint8, (fc, Bp, BR), 1)
    onehot = (b.astype(jnp.uint8)[:, None, :] == bin_id).astype(jnp.bfloat16)
    return _dot6(gh, onehot.reshape(fc * Bp, BR))


def _contrib_sub1abs(b, gh, *, fc, B, Bp, BR):
    import jax
    import jax.numpy as jnp
    bb = b.astype(jnp.bfloat16)
    bin_id = jax.lax.broadcasted_iota(jnp.bfloat16, (fc, Bp, BR), 1)
    d = bb[:, None, :] - bin_id
    onehot = jnp.maximum(jnp.bfloat16(1.0) - jnp.abs(d), jnp.bfloat16(0.0))
    return _dot6(gh, onehot.reshape(fc * Bp, BR))


_STAGED_LO = 16           # lo-digit width (Bp is a 128-multiple, so 16 | Bp)


def _contrib_staged(b, gh, *, fc, B, Bp, BR):
    # hierarchical one-hot: bin = hi*16 + lo, so
    #   onehot[f, hi*16+lo, r] = onehot_hi[f, hi, r] * onehot_lo[f, lo, r]
    # — (Bp/16 + 16) VPU compares per element instead of Bp, one bf16
    # multiply to combine (the outer product over disjoint digit supports
    # reproduces the one-hot EXACTLY: both factors are 0/1, exact in bf16).
    # Out-of-range bins (B <= bin < 256-domain garbage) get hi >= Bp/16 and
    # match nothing, same drop-by-compare semantics as base.
    import jax
    import jax.numpy as jnp
    W = _STAGED_LO
    H = Bp // W
    bi = b.astype(jnp.int32)
    hi = bi >> (W.bit_length() - 1)        # bin // W (W is a power of two)
    lo = bi & (W - 1)
    hi_id = jax.lax.broadcasted_iota(jnp.int32, (fc, H, BR), 1)
    lo_id = jax.lax.broadcasted_iota(jnp.int32, (fc, W, BR), 1)
    oh_hi = (hi[:, None, :] == hi_id).astype(jnp.bfloat16)      # [fc, H, BR]
    oh_lo = (lo[:, None, :] == lo_id).astype(jnp.bfloat16)      # [fc, W, BR]
    onehot = (oh_hi[:, :, None, :] * oh_lo[:, None, :, :])      # [fc,H,W,BR]
    return _dot6(gh, onehot.reshape(fc * Bp, BR))


def _contrib_packed(b, gh, *, fc, B, Bp, BR):
    # k = 128//B features share one 128-lane group: feature j of a group
    # owns lanes [j*B, (j+1)*B).  Rows land on k DISJOINT lanes per group
    # (one per feature), so the "one-hot" is a k-hot whose dot still yields
    # per-(feature, bin) sums — and it is built with fc*B*BR compares
    # instead of fc*Bp*BR: only each feature's OWN B lanes are compared,
    # a k-fold VPU cut on top of the k-fold MXU N-dim cut.
    import jax
    import jax.numpy as jnp
    k = 128 // B
    ng = fc // k                       # shell guarantees fc % k == 0
    bi = b.astype(jnp.int32).reshape(ng, k, BR)
    bin_id = jax.lax.broadcasted_iota(jnp.int32, (ng, k, B, BR), 2)
    khot = (bi[:, :, None, :] == bin_id).astype(jnp.bfloat16)   # [ng,k,B,BR]
    return _dot6(gh, khot.reshape(ng * 128, BR))


def _contrib_int8(b, gh, *, fc, B, Bp, BR):
    # int8 MXU with f32 fixup: the one-hot is exactly representable in int8;
    # the f32 (g,h,m) rows are per-block THREE-level quantized — primary
    # q1 = round(x/s1) plus two residual fixups q2, q3, each capturing the
    # previous level's rounding with its own per-row f32 scale — and all
    # nine rows ride ONE int8 dot with int32 accumulation (M = 9 is still
    # under the MXU sublane granularity, so the extra residual rows are
    # free).  Two levels alone leave ~1.5e-5·max|x| per element — 4x the
    # bf16 (hi, lo) pair's floor, which measured right AT HIST_PARITY_TOL
    # on dense 64-bin histograms; the third level drops the floor to
    # ~6e-8·max|x|, comfortably inside the shared parity bar.
    import jax
    import jax.numpy as jnp
    bi = b.astype(jnp.int32)
    bin_id = jax.lax.broadcasted_iota(jnp.int32, (fc, Bp, BR), 1)
    onehot = (bi[:, None, :] == bin_id).astype(jnp.int8).reshape(fc * Bp, BR)

    def level(x):
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0,
                        jnp.float32(1e-30))
        q = jnp.round(x / s)
        return s, q, x - q * s

    s1, q1, r1 = level(gh)                                     # [3, BR] f32
    s2, q2, r2 = level(r1)
    s3, q3, _ = level(r2)
    q = jnp.concatenate([q1, q2, q3], axis=0).astype(jnp.int8)  # [9, BR]
    acc = jax.lax.dot_general(
        q, onehot,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)  # [9, lanes]
    # fold to the (hi, lo) triple-pair layout finish_hist expects: the two
    # residual levels sum into the lo triple
    hi = acc[:3] * s1
    lo = acc[3:6] * s2 + acc[6:9] * s3
    return jnp.concatenate([hi, lo], axis=0)                   # [6, lanes]


# --------------------------------------------------------------------------
# the registry
# --------------------------------------------------------------------------

def _geom_plain(B, Bp):
    return Bp


def _one(B, Bp):
    return 1


VARIANTS = {
    "base": VariantSpec(
        "base", "int32 iota compare -> bf16 select (production shape)",
        _prep_bf16_pair, _geom_plain, _one, _contrib_base,
        lambda B: True,
        lambda f, B, BR: f * padded_bins(B) * BR),
    "bf16cmp": VariantSpec(
        "bf16cmp", "bf16 iota + bf16 bins compare (2-byte lanes)",
        _prep_bf16_pair, _geom_plain, _one, _contrib_bf16cmp,
        lambda B: B <= 256,            # integers exact in bf16 up to 256
        lambda f, B, BR: f * padded_bins(B) * BR),
    "i16cmp": VariantSpec(
        "i16cmp", "int16 iota + int16 bins compare",
        _prep_bf16_pair, _geom_plain, _one, _contrib_i16cmp,
        lambda B: B <= 32768,          # int16 iota domain
        lambda f, B, BR: f * padded_bins(B) * BR),
    "u8cmp": VariantSpec(
        "u8cmp", "uint8 iota + raw u8 bins compare (1-byte lanes)",
        _prep_bf16_pair, _geom_plain, _one, _contrib_u8cmp,
        lambda B: B <= 256,            # u8 compare domain
        lambda f, B, BR: f * padded_bins(B) * BR),
    "sub1abs": VariantSpec(
        "sub1abs", "onehot = max(0, 1 - |b - j|) in bf16 (all-arith)",
        _prep_bf16_pair, _geom_plain, _one, _contrib_sub1abs,
        lambda B: B <= 256,
        lambda f, B, BR: f * padded_bins(B) * BR),
    "staged": VariantSpec(
        "staged", "hi/lo-digit outer-product one-hot (~Bp/16+16 compares/elt)",
        _prep_bf16_pair, _geom_plain, _one, _contrib_staged,
        lambda B: True,
        lambda f, B, BR: f * (padded_bins(B) // _STAGED_LO + _STAGED_LO) * BR),
    "packed": VariantSpec(
        "packed", "k=128//B features per 128-lane group (B <= 64, B | 128)",
        _prep_bf16_pair,
        lambda B, Bp: 128,
        lambda B, Bp: 128 // B,
        _contrib_packed,
        lambda B: pack_k(B) >= 2,
        lambda f, B, BR: f * B * BR),
    "int8": VariantSpec(
        "int8", "int8-MXU one-hot, per-block quantized gh + residual fixups",
        _prep_f32, _geom_plain, _one, _contrib_int8,
        lambda B: True,
        lambda f, B, BR: f * padded_bins(B) * BR),
}

VARIANT_NAMES = tuple(VARIANTS)

# candidates the first-fit auto-tuner times (pick_variant): one entrant per
# family that can plausibly win on hardware.  The pure-compare-dtype variants
# (bf16cmp, i16cmp, u8cmp, sub1abs) share base's work model, and Mosaic on
# v5e refuses each of them (8/16-bit iota, u8->bf16 cast), so none is an
# entrant: a candidate that cannot compile costs every first fit a failed
# compile.  tests/test_chip_smoke.py compiles every name below for v5e.
AUTO_CANDIDATES = ("base", "staged", "packed", "int8")


def resolve(name: str, max_bin: int):
    """Validate ``name`` against the registry and the kernel bin width;
    returns a supported variant name (falling back to 'base' with a warning
    when the requested family cannot serve this width)."""
    if name not in VARIANTS:
        raise ValueError(f"unknown hist_variant {name!r}; "
                         f"known: {', '.join(VARIANT_NAMES)}")
    if not VARIANTS[name].supports(max_bin):
        from ..utils.log import Log
        Log.warning("hist_variant=%s does not support max_bin=%d; "
                    "using 'base'", name, max_bin)
        return "base"
    return name


def finish_hist(out, f, B, Bp, spec: VariantSpec):
    """[..., 6, n_lanes] kernel output -> [..., f, B, 6] histograms:
    normalize the sums and their compensation (``accumulate_block``) into
    the float32 sum (channels 0:3) and what it rounds away (3:6;
    ``histogram.fold_hist`` adds them) and undo the lane layout (plain
    Bp-wide slots, or the packed ``group*128 + f_local*B + bin`` layout).
    Shared by every kernel shell so the lane mapping exists exactly once."""
    import jax.numpy as jnp
    gl = spec.group_lanes(B, Bp)
    gf = spec.group_feats(B, Bp)
    lead = out.shape[:-2]
    ng = out.shape[-1] // gl
    o = out.reshape(lead + (2, 3, ng, gl))
    hist, lo = two_sum(o[..., 0, :, :, :], o[..., 1, :, :, :])  # [..., 3, ng, gl]
    hist = jnp.concatenate([hist, lo], axis=-3)          # [..., 6, ng, gl]
    hist = hist[..., :gf * B].reshape(lead + (6, ng * gf, B))
    hist = hist[..., :f, :]
    # [..., C, f, B] -> [..., f, B, C]
    return jnp.moveaxis(hist, -3, -1)


# --------------------------------------------------------------------------
# first-fit auto-tuner (the reference train_share_states analog)
# --------------------------------------------------------------------------

_AUTO_CACHE: dict = {}
#: wall seconds each election in _AUTO_CACHE took (same keys) — compile of
#: every candidate included; read by chip_smoke.py
_AUTO_SECONDS: dict = {}


def _auto_bench_data(max_bin: int, f: int, rows: int = 262144):
    """Synthetic (bins, g, h, m) for the election micro-bench.  The width
    is capped: the RANKING is what matters, and a Criteo-wide first fit
    must not spend its budget timing a 13k-column micro-bench."""
    import jax.numpy as jnp
    import numpy as np
    f = max(8, min(f, 128))
    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, max_bin, size=(rows, f),
                                    dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=rows).astype(np.float32))
    h = jnp.asarray(np.full(rows, 0.25, np.float32))
    m = jnp.ones(rows, jnp.float32)
    return bins, g, h, m


def _time_auto_candidate(variant, bins, g, h, m, max_bin, ref,
                         iters: int = 5):
    """(seconds-per-pass, relerr-vs-ref) for one candidate ON DEVICE.

    The parity number is load-bearing, not diagnostic: a Mosaic miscompile
    is frequently FASTER than the correct lowering (this kernel family
    miscompiled data-dependently on real v5e twice in round 4, caught only
    by hardware parity gates), so an election by speed alone would crown
    exactly the broken candidate.  _run_auto_bench disqualifies on relerr
    before looking at the clock."""
    import time

    import jax
    import jax.numpy as jnp
    from .histogram import _hist_pallas, fold_hist

    jfn = jax.jit(lambda b_, g_: fold_hist(_hist_pallas(
        b_, g_, h, m, max_bin, variant=variant)))
    out = jfn(bins, g).block_until_ready()         # compile + warm
    err = float(jnp.max(jnp.abs(out - ref) / (jnp.abs(ref) + 1.0)))
    t0 = time.perf_counter()
    for _ in range(iters):
        r = jfn(bins, g + 1e-12)
    r.block_until_ready()
    return (time.perf_counter() - t0) / iters, err


def pick_variant(max_bin: int, num_features: int, *,
                 backend: "str | None" = None) -> str:
    """``hist_variant=auto``: one-time on-device micro-bench electing the
    fastest supported variant for this (device kind, bin width) — cached at
    module scope so later fits (and every tree of this fit) reuse the
    winner without re-timing or retracing.  Off-TPU the Pallas kernels are
    not the production path, so 'base' is returned without timing."""
    import time

    import jax
    backend = backend or jax.default_backend()
    if backend != "tpu":
        return "base"
    key = (jax.devices()[0].device_kind, int(max_bin))
    if key in _AUTO_CACHE:
        return _AUTO_CACHE[key]
    t0 = time.perf_counter()
    choice = _run_auto_bench(max_bin, num_features)
    _AUTO_CACHE[key] = choice
    _AUTO_SECONDS[key] = time.perf_counter() - t0
    return choice


def _run_auto_bench(max_bin: int, num_features: int) -> str:
    """Elect the production variant: every supported AUTO_CANDIDATE must
    FIRST parity-check on device against the true-f32 XLA one-hot
    (precision-pinned — the same reference the hardware dual gate uses)
    before its timing counts; the fastest parity-clean candidate wins.  A
    candidate that fails to lower or fails parity is skipped with a
    warning.  There is no floor: an election in which NO candidate passes
    raises, because the only thing left to return would be a kernel that
    just failed on this device."""
    from ..utils.log import Log
    from .histogram import HIST_PARITY_TOL, _hist_onehot, fold_hist
    import jax

    bins, g, h, m = _auto_bench_data(max_bin, max(1, num_features))
    ref = jax.jit(lambda b_, g_: fold_hist(_hist_onehot(
        b_, g_, h, m, max_bin, 65536)))(bins, g)
    ref = ref.block_until_ready()
    best, best_t = None, float("inf")
    failures = []
    for name in AUTO_CANDIDATES:
        if not VARIANTS[name].supports(max_bin):
            continue
        try:
            t, err = _time_auto_candidate(name, bins, g, h, m, max_bin, ref)
        except Exception as e:             # noqa: BLE001 — lowering failures
            Log.warning("hist_variant auto-tune: %s failed (%s)", name,
                        str(e)[:120])
            failures.append(f"{name}: {str(e)[:200]}")
            continue
        if not err <= HIST_PARITY_TOL:     # NaN must disqualify too
            Log.warning("hist_variant auto-tune: %s FAILED on-device parity "
                        "(relerr %.2e > %.0e) — disqualified", name, err,
                        HIST_PARITY_TOL)
            failures.append(f"{name}: relerr {err:.2e}")
            continue
        Log.info("hist_variant auto-tune: %s %.3f ms (relerr %.2e)", name,
                 t * 1e3, err)
        if t < best_t:
            best, best_t = name, t
    if best is None:
        raise RuntimeError(
            "hist_variant=auto: no candidate compiled and passed on-device "
            f"parity at max_bin={max_bin} ({'; '.join(failures)})")
    Log.info("hist_variant auto-tune: picked %s for max_bin=%d", best,
             max_bin)
    return best
