"""Best-split search over histograms.

Replaces the reference's sequential per-bin sweeps
(``FeatureHistogram::FindBestThresholdSequentially``,
``src/treelearner/feature_histogram.hpp:856-1050``) with vectorized cumulative
sums over the whole ``[F, B]`` histogram — both missing-value directions are
evaluated as two cumsum variants instead of two sequential passes.

Semantics preserved from the reference:
- leaf output / gain closed forms with L1 thresholding, L2, ``max_delta_step``
  clipping and path smoothing (``CalculateSplittedLeafOutput:743``,
  ``GetSplitGains:785``, ``GetLeafGain:826``);
- missing handling: NaN-bin or zero-bin contents are assigned to either side,
  the better direction wins, reported as ``default_left``
  (the REVERSE / NA_AS_MISSING / SKIP_DEFAULT_BIN template lattice);
- gates: ``min_data_in_leaf``, ``min_sum_hessian_in_leaf``,
  ``min_gain_to_split`` (as the ``min_gain_shift`` on parent gain);
- categorical one-hot splits (``FindBestThresholdCategoricalInner:278``
  one-hot branch; the sorted many-category scan is in the grower roadmap);
- monotone constraint (basic): candidate rejected when child outputs violate
  the feature's direction, with per-leaf output bounds applied.

Which side is summed.  The reference sweeps one side in float64 and takes the
other as ``total - swept``.  In float32 that hands what ``total`` is off by
(an ulp of a 400,000 hessian sum is 0.03) whole to the other side, however
small it is.  Here every candidate's two sides are both summed over their own
bins (a forward and a reverse cumulative sum), the side with fewer rows keeps
its own sum and the larger one is ``total - smaller`` (``derive_larger``):
each side's error is relative to its own size.  ``total`` must be the sum of
the rows the histogram was built from (``histogram.hist_totals``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


class SplitParams(NamedTuple):
    """Static gain-formula parameters (subset of Config)."""
    lambda_l1: float
    lambda_l2: float
    min_data_in_leaf: int
    min_sum_hessian_in_leaf: float
    min_gain_to_split: float
    max_delta_step: float
    path_smooth: float
    cat_smooth: float
    cat_l2: float
    max_cat_to_onehot: int
    max_cat_threshold: int = 32
    min_data_per_group: int = 100


class SplitResult(NamedTuple):
    """Best split of one leaf (the analog of ``SplitInfo``,
    ``src/treelearner/split_info.hpp:51``)."""
    gain: jax.Array          # f32 — improvement over parent (NEG_INF if none)
    feature: jax.Array       # i32 inner feature index
    threshold: jax.Array     # i32 bin threshold (<=: left); category bin for cat
    default_left: jax.Array  # bool — missing goes left
    left_sum_g: jax.Array
    left_sum_h: jax.Array
    left_count: jax.Array    # f32 (weighted count)
    right_sum_g: jax.Array
    right_sum_h: jax.Array
    right_count: jax.Array
    left_output: jax.Array
    right_output: jax.Array
    # categorical membership bitset over BIN ids ([ceil(B/32)] int32): for a
    # categorical split, bins with a set bit go LEFT (one-hot = single bit;
    # sorted many-category subsets = the elected prefix).  Zeros for numeric
    # splits.  The analog of SplitInfo::cat_threshold.
    cat_bits: jax.Array


def threshold_l1(s, l1):
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output(sum_g, sum_h, p: SplitParams, parent_output=0.0, count=None,
                lo=None, hi=None):
    """Closed-form leaf output with L1/L2/max_delta_step/path smoothing and
    optional monotone bounds (reference ``CalculateSplittedLeafOutput``)."""
    raw = -threshold_l1(sum_g, p.lambda_l1) / (sum_h + p.lambda_l2 + 1e-35)
    if p.max_delta_step > 0:
        raw = jnp.clip(raw, -p.max_delta_step, p.max_delta_step)
    if p.path_smooth > 0 and count is not None:
        smooth = count / (count + p.path_smooth)
        raw = raw * smooth + parent_output * (1.0 - smooth)
    if lo is not None:
        raw = jnp.clip(raw, lo, hi)
    return raw


def leaf_gain_given_output(sum_g, sum_h, out, p: SplitParams):
    """Reference ``GetLeafGainGivenOutput``: -(2·G̃·w + (H+λ₂)·w²)."""
    g1 = threshold_l1(sum_g, p.lambda_l1)
    return -(2.0 * g1 * out + (sum_h + p.lambda_l2) * out * out)


def leaf_gain(sum_g, sum_h, p: SplitParams, parent_output=0.0, count=None,
              lo=None, hi=None):
    if p.max_delta_step > 0 or p.path_smooth > 0 or lo is not None:
        out = leaf_output(sum_g, sum_h, p, parent_output, count, lo, hi)
        return leaf_gain_given_output(sum_g, sum_h, out, p)
    g1 = threshold_l1(sum_g, p.lambda_l1)
    return g1 * g1 / (sum_h + p.lambda_l2 + 1e-35)


def _sum_after(x):
    """``[F, B, 3]`` -> the sum over the bins after each bin (axis 1)."""
    at_or_after = jnp.flip(jnp.cumsum(jnp.flip(x, 1), axis=1), 1)
    return jnp.concatenate([at_or_after[:, 1:], jnp.zeros_like(x[:, :1])],
                           axis=1)


def derive_larger(left, right, total):
    """Of a candidate's two directly summed sides ``[..., 3]``, keep the one
    with fewer rows and make the other ``total - it`` (module docstring).
    The sides then add up to ``total`` to an ulp of it."""
    left_small = (left[..., 2] <= right[..., 2])[..., None]
    return (jnp.where(left_small, left, total - right),
            jnp.where(left_small, total - left, right))


def _split_gain_matrix(hist, num_bins, nan_bins, is_categorical, monotone,
                       total, p: SplitParams, feature_mask,
                       parent_output, output_lo, output_hi,
                       gain_penalty=None, rand_threshold=None, contri=None):
    """Candidate gains over all (feature, threshold) pairs.

    Returns (gain_fb [F, B], use_left [F, B], sides): ``sides`` = (cum,
    above, miss, others), the direct sums a chosen candidate's child sums are
    rebuilt from (``find_best_split``): bins ``<= t`` and ``> t`` without the
    missing bin [F, B, 3], the missing bin [F, 3], every bin but ``t``
    [F, B, 3].
    """
    f, b, _ = hist.shape
    bin_ids = jnp.arange(b, dtype=jnp.int32)[None, :]                  # [1, B]

    # --- extract "missing" bin per feature, zero it out of the sweep ---
    # NaN-missing features: the trailing NaN bin; zero-as-missing features:
    # the zero bin (mid-range in general).  Either way the bin is excluded
    # from the ordered sweep and trialed on both sides (the reference's
    # REVERSE/NA_AS_MISSING + SKIP_DEFAULT_BIN cases in one formulation).
    miss_bin = nan_bins                                                # [F]
    has_miss = miss_bin >= 0
    miss_sel = (bin_ids == miss_bin[:, None]) & has_miss[:, None]      # [F, B]
    miss = jnp.sum(jnp.where(miss_sel[:, :, None], hist, 0.0), axis=1) # [F, 3]
    swept = jnp.where(miss_sel[:, :, None], 0.0, hist)                 # [F, B, 3]

    cum = jnp.cumsum(swept, axis=1)                                    # [F, B, 3]
    above = _sum_after(swept)                                          # bins > t

    # threshold t means: bins <= t go left (t in [0, num_bin-2]); when the
    # missing bin is the TRAILING bin the last real threshold drops with it,
    # but a mid-range missing bin (zero_as_missing) keeps the full range
    trailing_miss = has_miss & (miss_bin == num_bins - 1)
    valid_t = bin_ids < (num_bins[:, None] - 1 - trailing_miss[:, None])

    def eval_direction(missing_left):
        left = cum + jnp.where(missing_left, miss[:, None, :], 0.0)    # [F, B, 3]
        right = above + jnp.where(missing_left, 0.0, miss[:, None, :])
        left, right = derive_larger(left, right, total)
        return _gain_at(left, right, total, monotone, p,
                        parent_output, output_lo, output_hi, valid_t)

    gain_r, out_r = eval_direction(False)   # missing -> right
    gain_l, out_l = eval_direction(True)    # missing -> left
    use_left = gain_l > gain_r
    num_gain = jnp.where(use_left, gain_l, gain_r)                     # [F, B]

    # --- categorical one-hot: left = (bin == k) -------------------------------
    # only for low-cardinality features (reference use_onehot dispatch,
    # feature_histogram.hpp:316); larger cardinalities use the sorted scan
    # bin 0 is the unseen/other/NaN catch-all (io/bin.py categorical layout):
    # it cannot be expressed in a category-VALUE bitset, so it is never a
    # left-set member — those rows always go right, like unseen categories
    # at predict time
    others = (cum - swept) + above + jnp.where(miss_sel[:, :, None], 0.0,
                                               miss[:, None, :])
    cat_left, cat_right = derive_larger(hist, others, total)          # [F, B, 3]
    cat_valid = (bin_ids >= 1) & (bin_ids < num_bins[:, None]) & \
        (num_bins[:, None] <= p.max_cat_to_onehot)
    cat_gain, cat_out = _gain_at(cat_left, cat_right, total, monotone, p,
                                 parent_output, output_lo, output_hi, cat_valid,
                                 extra_l2=p.cat_l2)
    is_cat = is_categorical[:, None]
    gain_fb = jnp.where(is_cat, cat_gain, num_gain)                    # [F, B]
    if contri is not None:
        # feature_contri scales the min_gain-shifted improvement BEFORE the
        # CEGB delta-gain is subtracted (reference order: FindBestThreshold
        # applies meta_->penalty internally, feature_histogram.hpp:94, and
        # serial_tree_learner.cpp:740 subtracts CEGB after)
        pivot = leaf_gain(total[0], total[1], p, parent_output, total[2],
                          output_lo, output_hi) + p.min_gain_to_split
        gain_fb = jnp.where(gain_fb > NEG_INF / 2,
                            pivot + (gain_fb - pivot) * contri[:, None],
                            gain_fb)
    if gain_penalty is not None:
        # CEGB: per-feature penalty subtracted from the candidate gain before
        # the argmax (reference ``new_split.gain -= cegb_->DetlaGain(...)``,
        # serial_tree_learner.cpp:740-744)
        gain_fb = jnp.where(gain_fb > NEG_INF / 2,
                            gain_fb - gain_penalty[:, None], gain_fb)
    if rand_threshold is not None:
        # extra_trees: each feature offers exactly ONE random threshold
        # (reference USE_RAND specialization, feature_histogram.hpp:115-217);
        # categorical features keep the full scan like the reference
        keep = (bin_ids == rand_threshold[:, None]) | is_cat
        gain_fb = jnp.where(keep, gain_fb, NEG_INF)
    gain_fb = jnp.where(feature_mask[:, None] > 0, gain_fb, NEG_INF)
    return gain_fb, use_left, (cum, above, miss, others)


def cat_words(b: int) -> int:
    """Bitset words needed for ``b`` bins."""
    return max(1, -(-b // 32))


def pack_bin_bitset(member: jax.Array) -> jax.Array:
    """Pack a ``[..., B]`` membership mask into ``[..., ceil(B/32)]`` i32."""
    b = member.shape[-1]
    cw = cat_words(b)
    pad = cw * 32 - b
    if pad:
        member = jnp.pad(member, [(0, 0)] * (member.ndim - 1) + [(0, pad)])
    m = member.reshape(member.shape[:-1] + (cw, 32)).astype(jnp.uint32)
    packed = jnp.sum(m << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                     dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(packed, jnp.int32)


def bitset_contains(bits: jax.Array, idx: jax.Array) -> jax.Array:
    """Test bit ``idx`` of a ``[CW]`` i32 bitset (vectorized over ``idx``)."""
    word = jnp.take(bits, idx >> 5, mode="clip")
    return ((word >> (idx & 31)) & 1) == 1


def _sorted_cat_best(hist, num_bins, is_categorical, monotone, total,
                     p: SplitParams, feature_mask, parent_output,
                     output_lo, output_hi, gain_penalty=None, contri=None):
    """Sorted many-category split scan, vectorized over features.

    Reference ``FindBestThresholdCategoricalInner`` sorted branch
    (``feature_histogram.hpp:378-474``): bins with enough data are sorted by
    ``sum_grad/(sum_hess + cat_smooth)`` and prefixes from BOTH ends (up to
    ``min(max_cat_threshold, (used+1)/2)`` categories) are candidate left
    sets, with ``min_data_per_group`` gating candidate prefixes.  One
    deviation: the reference estimates bin counts from hessians
    (``cnt_factor``); the count channel here is exact.

    Returns ``(gain [F], bits [F, CW] i32, left_sums [F, 3], right_sums
    [F, 3])``, both sides summed over their own bins, with ``NEG_INF`` gain
    for features where the sorted scan does not apply.
    """
    f, b, _ = hist.shape
    cw = cat_words(b)
    if f == 0:
        z = jnp.zeros((0,), jnp.float32)
        z3 = jnp.zeros((0, 3), jnp.float32)
        return z, jnp.zeros((0, cw), jnp.int32), z3, z3
    maxT = max(1, min(p.max_cat_threshold, b))
    g, h, c = hist[..., 0], hist[..., 1], hist[..., 2]
    bin_ids = jnp.arange(b, dtype=jnp.int32)[None, :]
    active = (is_categorical & (num_bins > p.max_cat_to_onehot)
              & (feature_mask > 0))                                 # [F]
    # bin 0 (unseen/other/NaN catch-all) is excluded from left-set
    # membership — see the one-hot branch in _split_gain_matrix
    elig = ((c >= p.cat_smooth) & (bin_ids >= 1)
            & (bin_ids < num_bins[:, None]))                        # [F, B]
    used_bin = jnp.sum(elig, axis=1)                                # [F]
    max_num_cat = jnp.minimum(p.max_cat_threshold, (used_bin + 1) // 2)
    score = jnp.where(elig, g / (h + p.cat_smooth), jnp.inf)
    left_out = jnp.sum(jnp.where(elig[:, :, None], 0.0, hist), axis=1)  # [F, 3]
    p_eff = p._replace(lambda_l2=p.lambda_l2 + p.cat_l2)
    pen = gain_penalty if gain_penalty is not None else jnp.zeros(f, jnp.float32)
    mono = monotone

    def scan_dir(order_score):
        idx = jnp.argsort(order_score, axis=1, stable=True)         # [F, B]
        srt = jnp.take_along_axis(jnp.where(elig[:, :, None], hist, 0.0),
                                  idx[:, :, None], axis=1)          # [F, B, 3]
        cum = jnp.cumsum(srt, axis=1)[:, :maxT]
        cum = cum.at[:, :, 1].add(1e-15)                            # kEpsilon
        # the right side: the sorted bins after i, and every bin left out
        after = _sum_after(srt)[:, :maxT] + left_out[:, None, :]
        cum, after = derive_larger(cum, after, total)
        sc_step = srt[:, :maxT, 2]

        def body(i, carry):
            cnt_grp, best_gain, best_i = carry
            lg, lh, lc = cum[:, i, 0], cum[:, i, 1], cum[:, i, 2]
            rg, rh, rc = after[:, i, 0], after[:, i, 1], after[:, i, 2]
            cnt_grp = cnt_grp + sc_step[:, i]
            in_range = i < jnp.minimum(used_bin, max_num_cat)
            gate1 = (lc >= p.min_data_in_leaf) & (lh >= p.min_sum_hessian_in_leaf)
            nobrk = ((rc >= p.min_data_in_leaf) & (rc >= p.min_data_per_group)
                     & (rh >= p.min_sum_hessian_in_leaf))
            grp_ok = cnt_grp >= p.min_data_per_group
            considered = active & in_range & gate1 & nobrk & grp_ok
            cnt_grp = jnp.where(in_range & gate1 & nobrk & grp_ok,
                                0.0, cnt_grp)
            lo_out = leaf_output(lg, lh, p_eff, parent_output, lc,
                                 output_lo, output_hi)
            ro_out = leaf_output(rg, rh, p_eff, parent_output, rc,
                                 output_lo, output_hi)
            bad = ((mono > 0) & (lo_out > ro_out)) | ((mono < 0) & (lo_out < ro_out))
            raw = (leaf_gain(lg, lh, p_eff, parent_output, lc,
                             output_lo, output_hi)
                   + leaf_gain(rg, rh, p_eff, parent_output, rc,
                               output_lo, output_hi))
            if contri is not None:
                pivot = leaf_gain(total[0], total[1], p, parent_output,
                                  total[2], output_lo, output_hi) \
                    + p.min_gain_to_split
                raw = pivot + (raw - pivot) * contri
            gain = raw - pen
            gain = jnp.where(considered & ~bad, gain, NEG_INF)
            better = gain > best_gain
            return (cnt_grp,
                    jnp.where(better, gain, best_gain),
                    jnp.where(better, i, best_i))

        init = (jnp.zeros(f, jnp.float32), jnp.full(f, NEG_INF, jnp.float32),
                jnp.zeros(f, jnp.int32))
        _, best_gain, best_i = jax.lax.fori_loop(0, maxT, body, init)
        return best_gain, best_i, idx

    g_asc, i_asc, idx_asc = scan_dir(score)
    g_dsc, i_dsc, idx_dsc = scan_dir(jnp.where(elig, -score, jnp.inf))
    use_dsc = g_dsc > g_asc
    best_gain = jnp.where(use_dsc, g_dsc, g_asc)
    best_i = jnp.where(use_dsc, i_dsc, i_asc)
    idx = jnp.where(use_dsc[:, None], idx_dsc, idx_asc)

    memb_sorted = jnp.arange(b, dtype=jnp.int32)[None, :] <= best_i[:, None]
    memb_bins = jnp.zeros((f, b), bool).at[
        jnp.arange(f, dtype=jnp.int32)[:, None], idx].set(memb_sorted)
    bits = pack_bin_bitset(memb_bins)                               # [F, CW]
    left = jnp.sum(jnp.where(memb_bins[:, :, None], hist, 0.0), axis=1)
    right = jnp.sum(jnp.where(memb_bins[:, :, None], 0.0, hist), axis=1)
    return best_gain, bits, left, right


def per_feature_gains(hist, num_bins, nan_bins, is_categorical, monotone,
                      sum_g, sum_h, count, p: SplitParams, feature_mask,
                      parent_output=0.0, output_lo=NEG_INF, output_hi=-NEG_INF,
                      sorted_cat: bool = True, gain_mult=None,
                      contri=None) -> jax.Array:
    """Best candidate gain per feature — ``[F]``.  Used by the voting-parallel
    learner's local top-k proposal (reference ``VotingParallelTreeLearner``,
    ``voting_parallel_tree_learner.cpp:151``).  Penalty-aware: the election
    must rank features by PENALIZED gains (the reference votes on
    SplitInfo gains that already include FeatureMetainfo::penalty), else a
    muted feature could crowd the elected set."""
    total = jnp.stack([sum_g, sum_h, count]).astype(jnp.float32)
    gain_fb, _, _ = _split_gain_matrix(
        hist, num_bins, nan_bins, is_categorical, monotone, total, p,
        feature_mask, parent_output, output_lo, output_hi, contri=contri)
    best = jnp.max(gain_fb, axis=1)
    if sorted_cat:
        gain_sorted, _, _, _ = _sorted_cat_best(
            hist, num_bins, is_categorical, monotone, total, p, feature_mask,
            parent_output, output_lo, output_hi, contri=contri)
        best = jnp.maximum(best, gain_sorted)
    if gain_mult is not None:
        pivot = leaf_gain(total[0], total[1], p, parent_output, total[2],
                          output_lo, output_hi) + p.min_gain_to_split
        best = jnp.where(best > NEG_INF / 2,
                         pivot + (best - pivot) * gain_mult, best)
    return best


def find_best_split(hist: jax.Array, num_bins: jax.Array, default_bins: jax.Array,
                    nan_bins: jax.Array, is_categorical: jax.Array,
                    monotone: jax.Array, sum_g, sum_h, count,
                    p: SplitParams, feature_mask: jax.Array,
                    parent_output=0.0, output_lo=NEG_INF, output_hi=-NEG_INF,
                    gain_penalty=None, rand_threshold=None,
                    sorted_cat: bool = True, gain_mult=None,
                    contri=None) -> SplitResult:
    """Find the best split of a leaf given its histogram.

    Args:
      hist: ``[F, B, 3]`` (grad, hess, count) histogram of the leaf (a
        grower's pair store folded, ``histogram.fold_hist``).
      num_bins/default_bins/nan_bins/is_categorical/monotone: ``[F]`` feature
        metadata from ``Dataset.device_data``.
      sum_g/sum_h/count: leaf totals (scalars): the sums of the rows ``hist``
        was built from (``histogram.hist_totals``), so that a candidate's
        larger side, ``total - smaller``, is right relative to its own size.
      feature_mask: ``[F]`` f32/bool — column sampling / interaction constraints.
      output_lo/output_hi: monotone bounds for this leaf's subtree.
    """
    f, b, _ = hist.shape
    cw = cat_words(b)
    total = jnp.stack([sum_g, sum_h, count]).astype(jnp.float32)       # [3]
    gain_fb, use_left, (cum, above, miss, others) = _split_gain_matrix(
        hist, num_bins, nan_bins, is_categorical, monotone, total, p,
        feature_mask, parent_output, output_lo, output_hi, gain_penalty,
        rand_threshold, contri=contri)
    # statically no many-category feature in the dataset (sorted_cat=False):
    # the sorted scan (2 argsorts + 2 maxT-step fori_loops of tiny ops) is
    # pure per-split overhead — skip it at trace time, and trace NO
    # placeholder candidate arrays either: constant NEG_INF candidates fed
    # through argmax/where under a vmapped shard_map crash XLA:CPU's
    # sharding propagation (TileAssignment::Reshape 0-element CHECK,
    # jaxlib 0.4.37) besides being dead weight
    if sorted_cat:
        gain_sorted, bits_sorted, left_sorted, right_sorted = _sorted_cat_best(
            hist, num_bins, is_categorical, monotone, total, p, feature_mask,
            parent_output, output_lo, output_hi, gain_penalty,
            contri=contri)

    if gain_mult is not None:
        # monotone split penalty (ComputeMonotoneSplitGainPenalty,
        # monotone_constraints.hpp:355) scales the min_gain-shifted
        # improvement AFTER any CEGB subtraction (serial_tree_learner.cpp:
        # 745-749); rebasing around parent_gain + min_gain makes the final
        # ``best - parent - min_gain`` exactly the reference's scaled gain
        pivot = leaf_gain(total[0], total[1], p, parent_output, total[2],
                          output_lo, output_hi) + p.min_gain_to_split
        gain_fb = jnp.where(gain_fb > NEG_INF / 2,
                            pivot + (gain_fb - pivot) * gain_mult[:, None],
                            gain_fb)
        if sorted_cat:
            gain_sorted = jnp.where(
                gain_sorted > NEG_INF / 2,
                pivot + (gain_sorted - pivot) * gain_mult, gain_sorted)

    # --- argmax over (feature, threshold) ------------------------------------
    flat = gain_fb.reshape(-1)
    best_idx = jnp.argmax(flat)
    grid_gain = flat[best_idx]
    if sorted_cat:
        # sorted-subset candidates compete per feature
        sorted_f = (jnp.argmax(gain_sorted).astype(jnp.int32) if f
                    else jnp.int32(0))
        use_sorted = ((gain_sorted[sorted_f] > grid_gain) if f
                      else jnp.asarray(False))
        best_gain = jnp.where(use_sorted, gain_sorted[sorted_f], grid_gain)
        best_f = jnp.where(use_sorted, sorted_f,
                           (best_idx // b).astype(jnp.int32))
        best_t = jnp.where(use_sorted, 0, (best_idx % b).astype(jnp.int32))
    else:
        best_gain = grid_gain
        best_f = (best_idx // b).astype(jnp.int32)
        best_t = (best_idx % b).astype(jnp.int32)
    bf_cat = is_categorical[best_f]
    bf_missing_left = jnp.where(bf_cat, False, use_left[best_f, best_t])

    # categorical membership bitset: sorted prefix, or the one-hot bin's bit
    onehot_bits = pack_bin_bitset(
        jnp.arange(b, dtype=jnp.int32) == best_t)                      # [CW]
    cat_bits = jnp.where(bf_cat, onehot_bits, jnp.zeros(cw, jnp.int32))
    if sorted_cat:
        cat_bits = jnp.where(use_sorted, bits_sorted[sorted_f], cat_bits)

    # the chosen split's child sums, each side from its own bins
    def pick(arr):
        return arr[best_f, best_t]
    left = jnp.where(
        bf_cat, pick(hist),
        pick(cum) + jnp.where(bf_missing_left, miss[best_f], 0.0))
    right = jnp.where(
        bf_cat, pick(others),
        pick(above) + jnp.where(bf_missing_left, 0.0, miss[best_f]))
    if sorted_cat:
        left = jnp.where(use_sorted, left_sorted[sorted_f], left)
        right = jnp.where(use_sorted, right_sorted[sorted_f], right)
    left, right = derive_larger(left, right, total)

    # categorical outputs use the categorical L2 (reference computes
    # CalculateSplittedLeafOutput with l2 += cat_l2 for cat splits)
    p_cat = p._replace(lambda_l2=p.lambda_l2 + p.cat_l2)

    def out_of(s):
        return jnp.where(
            bf_cat,
            leaf_output(s[0], s[1], p_cat, parent_output, s[2],
                        output_lo, output_hi),
            leaf_output(s[0], s[1], p, parent_output, s[2],
                        output_lo, output_hi))
    lo_out = out_of(left)
    hi_out = out_of(right)

    # parent gain baseline: reported gain is improvement over parent
    parent_gain = leaf_gain(total[0], total[1], p, parent_output, total[2],
                            output_lo, output_hi)
    improvement = best_gain - parent_gain - p.min_gain_to_split
    ok = improvement > 0.0
    return SplitResult(
        gain=jnp.where(ok, improvement + p.min_gain_to_split, NEG_INF),
        feature=best_f,
        threshold=best_t,
        default_left=bf_missing_left,
        left_sum_g=left[0], left_sum_h=left[1], left_count=left[2],
        right_sum_g=right[0], right_sum_h=right[1], right_count=right[2],
        left_output=lo_out, right_output=hi_out,
        cat_bits=cat_bits,
    )


def _gain_at(left, right, total, monotone, p: SplitParams,
             parent_output, output_lo, output_hi, valid, extra_l2=0.0):
    """Gain of candidate (left, right) sums [..., 3]; returns ([F,B] gain,
    ([F,B] left_out, [F,B] right_out) is folded into monotone check only)."""
    p_eff = p._replace(lambda_l2=p.lambda_l2 + extra_l2) if extra_l2 else p
    gl, hl, cl = left[..., 0], left[..., 1], left[..., 2]
    gr, hr, cr = right[..., 0], right[..., 1], right[..., 2]
    gain = (leaf_gain(gl, hl, p_eff, parent_output, cl, output_lo, output_hi) +
            leaf_gain(gr, hr, p_eff, parent_output, cr, output_lo, output_hi))
    ok = (valid
          & (cl >= p.min_data_in_leaf) & (cr >= p.min_data_in_leaf)
          & (hl >= p.min_sum_hessian_in_leaf) & (hr >= p.min_sum_hessian_in_leaf))
    mono = monotone[:, None]
    if True:  # monotone basic mode: reject direction violations
        lo = leaf_output(gl, hl, p_eff, parent_output, cl, output_lo, output_hi)
        ro = leaf_output(gr, hr, p_eff, parent_output, cr, output_lo, output_hi)
        bad = ((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro))
        ok = ok & ~bad
    return jnp.where(ok, gain, NEG_INF), None


def voting_elect(hist, num_bins, nan_bins, is_categorical, monotone,
                 sum_g, sum_h, count, p: SplitParams, feature_mask,
                 axis_name: str, top_k: int, num_shards: int,
                 parent_output=0.0, output_lo=NEG_INF, output_hi=-NEG_INF,
                 sorted_cat: bool = True, gain_mult=None, contri=None):
    """Voting-parallel election: local top-k proposal -> global vote ->
    psum only the ELECTED feature histograms
    (``voting_parallel_tree_learner.cpp:151-345``).  Returns
    ``(hist_elected, elected_mask)`` for the caller's final
    ``find_best_split`` — shared by the sequential grower and the frontier
    grower so the election dataflow lives exactly once.

    Local gains run with min-data/hessian gates scaled to the shard
    (reference scales by 1/num_machines, ``:61-63``); the election ranks
    PENALIZED gains (gain_mult/contri) like the reference's SplitInfo vote.
    """
    import jax

    ns = max(1, num_shards)
    p_loc = p._replace(
        min_data_in_leaf=max(1, p.min_data_in_leaf // ns),
        min_sum_hessian_in_leaf=p.min_sum_hessian_in_leaf / ns)
    fg = per_feature_gains(hist, num_bins, nan_bins, is_categorical,
                           monotone, sum_g / ns, sum_h / ns, count / ns,
                           p_loc, feature_mask, parent_output, output_lo,
                           output_hi, sorted_cat=sorted_cat,
                           gain_mult=gain_mult, contri=contri)
    f_full = feature_mask.shape[0]
    kv = min(top_k, f_full)
    topv, topi = jax.lax.top_k(fg, kv)
    votes = jnp.zeros(f_full, jnp.float32).at[topi].add(
        jnp.where(topv > NEG_INF / 2, 1.0, 0.0))
    votes = jax.lax.psum(votes, axis_name)
    # elect 2k features (GlobalVoting); deterministic tie-break by index
    score = votes * (f_full + 1.0) - jnp.arange(f_full, dtype=jnp.float32)
    k2 = min(2 * kv, f_full)
    _, elected = jax.lax.top_k(score, k2)
    h_glob = jax.lax.psum(hist[elected], axis_name)
    hist_e = jnp.zeros_like(hist).at[elected].set(h_glob)
    emask = jnp.zeros(f_full, jnp.float32).at[elected].set(1.0)
    emask = jnp.where(feature_mask > 0, emask, 0.0)
    return hist_e, emask
