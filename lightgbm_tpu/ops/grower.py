"""Leaf-wise (best-first) tree growth as ONE compiled XLA program.

TPU-native re-design of the reference's ``SerialTreeLearner::Train``
(``src/treelearner/serial_tree_learner.cpp:158-209``).  Semantics preserved:

- best-first growth: each step splits the active leaf with the max split gain
  (``serial_tree_learner.cpp:194-201``);
- the smaller child's histogram is computed, the larger sibling's obtained by
  subtraction (the histogram-subtraction trick, ``:306-320``);
- the left child keeps the parent's leaf id, the right child gets the next
  fresh id (the reference ``Tree::Split`` leaf-numbering convention);
- depth / min-data / min-hessian / min-gain gates;
- monotone-constraint (basic mode) output-bound propagation
  (``monotone_constraints.hpp`` BasicConstraint).

Mechanics replaced: no per-leaf index partition (``data_partition.hpp``) — a
dense ``node_assignment[num_data]`` vector and masked histogram passes keep
every shape static so the whole ``num_leaves-1`` split loop is a single
``lax.fori_loop`` compiled once; no histogram LRU pool — a dense
``[num_leaves, F, B, 3]`` store (HBM is the pool).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .histogram import (build_histogram, fold_hist, gather_rows, hist_totals,
                        psum_hist, psum_scatter_hist, sub_hist)
from .split import (NEG_INF, SplitParams, SplitResult, bitset_contains,
                    cat_words, derive_larger, find_best_split, leaf_gain,
                    leaf_output, pack_bin_bitset, per_feature_gains)


def _reduce_split_global(s: SplitResult, axis_name: str) -> SplitResult:
    """Allreduce-max of a per-shard best split: the TPU analog of the
    reference's ``SyncUpGlobalBestSplit`` serialized-SplitInfo allreduce
    (``parallel_tree_learner.h:191-214``) — a pmax on the gain picks the
    winner, ties break to the lowest shard, and the winner's scalar payload
    is broadcast by masked psum (no byte packing needed)."""
    gain_max = jax.lax.pmax(s.gain, axis_name)
    dev = jax.lax.axis_index(axis_name)
    n_dev = jax.lax.psum(1, axis_name)
    claim = jnp.where(s.gain >= gain_max, dev, n_dev)
    winner = jax.lax.pmin(claim, axis_name)
    mine = (dev == winner)

    def bc(x):
        if jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == bool:
            # integer payloads (ids, bitsets) ride an exact integer psum —
            # a float cast would corrupt bitset words above 2^24
            xi = x.astype(jnp.int32)
            out = jax.lax.psum(jnp.where(mine, xi, jnp.zeros_like(xi)),
                               axis_name)
            return out.astype(x.dtype)
        xf = x.astype(jnp.float32)
        out = jax.lax.psum(jnp.where(mine, xf, jnp.zeros_like(xf)), axis_name)
        return out.astype(x.dtype) if x.dtype != jnp.float32 else out

    return SplitResult(
        gain=gain_max,
        feature=bc(s.feature), threshold=bc(s.threshold),
        default_left=bc(s.default_left),
        left_sum_g=bc(s.left_sum_g), left_sum_h=bc(s.left_sum_h),
        left_count=bc(s.left_count),
        right_sum_g=bc(s.right_sum_g), right_sum_h=bc(s.right_sum_h),
        right_count=bc(s.right_count),
        left_output=bc(s.left_output), right_output=bc(s.right_output),
        cat_bits=bc(s.cat_bits))


def _rect_comparability(rect_lo, rect_hi, c_lo_row, c_hi_row, mono_f):
    """Monotone comparability masks of every leaf rect vs one child rect.

    Two leaves are comparable along monotone dim k when their rects overlap
    in every other dim and are strictly ordered along k (in an axis-aligned
    partition, all-but-k overlap implies strict k-ordering).  Returns
    ``(upper, lower)`` ``[L, F]`` masks: ``upper[m, k]`` — leaf m sits on
    the child's greater side along k (so ``out_child <= out_m``),
    ``lower`` mirrored."""
    ovl_d = ((rect_lo <= c_hi_row[None, :])
             & (rect_hi >= c_lo_row[None, :]))               # [L, F]
    miss_cnt = jnp.sum(~ovl_d, axis=1)                       # [L]
    # overlap in all dims except k: no misses, or the only miss is k itself
    ovl_exc = ((miss_cnt == 0)[:, None]
               | ((miss_cnt == 1)[:, None] & ~ovl_d))        # [L, F]
    m_right = rect_lo > c_hi_row[None, :]                    # [L, F]
    m_left = rect_hi < c_lo_row[None, :]
    upper = ovl_exc & (((mono_f > 0)[None, :] & m_right)
                       | ((mono_f < 0)[None, :] & m_left))
    lower = ovl_exc & (((mono_f > 0)[None, :] & m_left)
                       | ((mono_f < 0)[None, :] & m_right))
    return upper, lower


class GrowerConfig(NamedTuple):
    """Static (compile-time) grower parameters."""
    num_leaves: int
    max_depth: int            # <=0: unlimited
    max_bin: int              # histogram width B
    split: SplitParams
    feature_fraction_bynode: float
    hist_method: str          # 'pallas' (TPU) | 'onehot' | 'scatter'
    hist_chunk_rows: int
    # one-hot build strategy for the pallas kernels: a registry name from
    # ops/onehot_variants.py (resolved from the user-facing
    # ``hist_variant`` param — 'auto' is resolved to a concrete name by a
    # one-time cached on-device micro-bench BEFORE this config is built, so
    # the compiled tree program never retraces over it)
    hist_variant: str = "base"
    # data-parallel mesh axis: rows are sharded across this axis and the
    # reference's histogram ReduceScatter + global-sum collectives
    # (data_parallel_tree_learner.cpp:155-173, network.h:168) become a psum
    axis_name: "str | None" = None
    # parallel strategy over axis_name (SURVEY.md §2.9):
    #   'data'    — rows sharded; full-histogram psum (DataParallelTreeLearner)
    #   'feature' — features sharded, rows replicated; split search sharded,
    #               winning SplitInfo reduced (FeatureParallelTreeLearner)
    #   'voting'  — rows sharded; local top-k vote elects 2k features, only
    #               their histograms are reduced (VotingParallelTreeLearner)
    # None with axis_name set defaults to 'data'.
    parallel_mode: "str | None" = None
    top_k: int = 20               # voting: local proposals per leaf
    num_shards: int = 1           # static axis size (gates scaling in voting)
    # CEGB (cost_effective_gradient_boosting.hpp): per-split penalty scaled by
    # leaf row count, pre-multiplied by cegb_tradeoff
    cegb_split_penalty: float = 0.0
    # adaptive leaf compaction (see Config.hist_compact): gather the smaller
    # sibling's rows into the tightest power-of-4 bucket before histogramming
    hist_compact: bool = True
    hist_compact_min_cap: int = 8192
    # capacity-ladder growth factor: smaller factors shrink the average
    # bucket round-up waste (expected waste ~ (ladder-1)/2 of every gathered
    # segment) at the cost of more switch branches to compile; fractional
    # values are allowed (caps round up to 1024-multiples)
    hist_compact_ladder: float = 2
    # extremely-randomized trees: one random threshold per feature per node
    # (reference USE_RAND, feature_histogram.hpp:115-217)
    extra_trees: bool = False
    # static: dataset has a many-category feature (num_bins > max_cat_to_onehot)
    # — when False the sorted-categorical scan is skipped at trace time,
    # removing ~128 sequential tiny ops + 4 argsorts from every split step
    sorted_cat: bool = True
    extra_seed: int = 0       # extra-trees threshold stream (Config::extra_seed)
    # depth-scaled gain penalty for splits on monotone features
    # (reference ComputeMonotoneSplitGainPenalty)
    monotone_penalty: float = 0.0
    # EFB (io/efb.py): histogram width of the BUNDLE columns the kernel sees;
    # 0 = bins are plain per-feature columns.  Feature-space histograms of
    # width max_bin are expanded from bundle space before each split search.
    bundle_bins: int = 0
    # monotone constraint mode (reference monotone_constraints.hpp):
    # 'basic' pinches child output bounds at the midpoint;
    # 'intermediate' bounds children with the ACTUAL sibling outputs and
    # propagates to overlapping leaves (see apply_split), re-validating each
    # chosen split against current bounds at apply time.  Only takes effect
    # when has_monotone is True (static, so unconstrained models trace none
    # of the machinery).
    monotone_mode: str = "basic"
    has_monotone: bool = False
    # round-batched best-first growth (ops/frontier.py): 'auto' takes the
    # frontier grower whenever the feature set allows (see
    # _frontier_eligible), 'serial' forces the one-split-at-a-time loop,
    # 'frontier' asks for batching and warns+falls back when ineligible
    grower_mode: str = "auto"
    frontier_k: int = 16          # leaves expanded per round
    frontier_block_rows: int = 512  # rows per kernel block (128-multiple)


class TreeArrays(NamedTuple):
    """Flat-array tree (device layout of the reference ``Tree``, ``tree.h:25``).

    Internal node ``j`` is created at split step ``j``; child pointers encode
    leaves as ``~leaf_id`` (the reference's negative-leaf convention).
    """
    split_feature: jax.Array   # [L-1] i32, -1 = unused node
    threshold: jax.Array       # [L-1] i32 bin threshold
    default_left: jax.Array    # [L-1] bool
    is_cat_split: jax.Array    # [L-1] bool
    cat_bits: jax.Array        # [L-1, CW] i32 bin-bitset for cat splits
    split_gain: jax.Array      # [L-1] f32
    left_child: jax.Array      # [L-1] i32
    right_child: jax.Array     # [L-1] i32
    leaf_value: jax.Array      # [L] f32
    leaf_count: jax.Array      # [L] f32 (weighted)
    leaf_weight: jax.Array     # [L] f32 (sum of hessians)
    internal_value: jax.Array  # [L-1] f32 (node output, for model IO / SHAP)
    internal_count: jax.Array  # [L-1] f32
    num_leaves: jax.Array      # scalar i32 (actual leaves grown)


class _BestSplits(NamedTuple):
    """Per-leaf pending best split (SoA of SplitResult over leaves)."""
    gain: jax.Array; feature: jax.Array; threshold: jax.Array
    default_left: jax.Array
    lg: jax.Array; lh: jax.Array; lc: jax.Array
    rg: jax.Array; rh: jax.Array; rc: jax.Array
    lout: jax.Array; rout: jax.Array
    cat_bits: jax.Array       # [n, CW] i32

    @classmethod
    def empty(cls, n: int, cw: int) -> "_BestSplits":
        z = jnp.zeros(n, jnp.float32)
        return cls(gain=jnp.full(n, NEG_INF, jnp.float32),
                   feature=jnp.zeros(n, jnp.int32), threshold=jnp.zeros(n, jnp.int32),
                   default_left=jnp.zeros(n, bool),
                   lg=z, lh=z, lc=z, rg=z, rh=z, rc=z, lout=z, rout=z,
                   cat_bits=jnp.zeros((n, cw), jnp.int32))

    def set_leaf(self, i, s: SplitResult, ok=None) -> "_BestSplits":
        def u(arr, v):
            if ok is None:
                return arr.at[i].set(v)
            return arr.at[i].set(jnp.where(ok, v, arr[i]))
        return _BestSplits(
            gain=u(self.gain, s.gain),
            feature=u(self.feature, s.feature),
            threshold=u(self.threshold, s.threshold),
            default_left=u(self.default_left, s.default_left),
            lg=u(self.lg, s.left_sum_g), lh=u(self.lh, s.left_sum_h),
            lc=u(self.lc, s.left_count),
            rg=u(self.rg, s.right_sum_g), rh=u(self.rh, s.right_sum_h),
            rc=u(self.rc, s.right_count),
            lout=u(self.lout, s.left_output),
            rout=u(self.rout, s.right_output),
            cat_bits=u(self.cat_bits, s.cat_bits))


def node_feature_mask_for(key, step, feature_mask, frac: float):
    """Per-node feature subset (reference ``col_sampler.hpp:91`` GetByNode):
    keep ``max(1, round(frac * n_allowed))`` of the still-allowed
    (bytree-selected) features — the fraction applies to the ALLOWED count,
    not the full width — keyed by ``fold_in(key, step)``.  ONE
    implementation shared by the sequential grower (step = split index) and
    the frontier grower (step = split-record index) so their streams cannot
    silently desynchronize in structure."""
    k = jax.random.fold_in(key, step)
    f_full = feature_mask.shape[0]
    allowed = feature_mask > 0
    # the fraction applies to the STILL-ALLOWED (bytree-selected) subset,
    # not the full feature count (col_sampler.hpp:94 draws from
    # used_feature_indices_): sizing from f_full made bynode a silent
    # no-op whenever feature_fraction < 1 already thinned the mask
    n_allowed = jnp.sum(allowed.astype(jnp.int32))
    n_take = jnp.clip(
        jnp.floor(frac * n_allowed.astype(jnp.float32) + 0.5).astype(
            jnp.int32), 1, f_full)
    u = jax.random.uniform(k, (f_full,))
    u = jnp.where(allowed, u, -jnp.inf)
    thresh = jax.lax.top_k(u, f_full)[0][n_take - 1]
    return jnp.where(u >= thresh, feature_mask, 0.0)


def rand_thresholds_for(key, step, extra_seed: int, num_bins, nan_bins):
    """extra_trees: one random valid numeric threshold per feature
    (reference ExtremelyRandomizedTrees path).  ``extra_seed`` decorrelates
    the stream from every other seeded draw (Config::extra_seed); a
    TRAILING missing bin removes the last real threshold (must stay in sync
    with split.py's valid_t).  Shared by both growers like
    ``node_feature_mask_for``."""
    k = jax.random.fold_in(
        jax.random.fold_in(jax.random.fold_in(key, 7919), step), extra_seed)
    hi = jnp.maximum(num_bins - 2 - (nan_bins == num_bins - 1), 0)
    u = jax.random.uniform(k, (num_bins.shape[0],))
    return jnp.floor(u * (hi + 1).astype(jnp.float32)).astype(jnp.int32)


def monotone_gain_mult(depth, monotone, pen: float):
    """[F] monotone-split gain penalty factor at a leaf of ``depth``
    (reference ``ComputeMonotoneSplitGainPenalty``,
    monotone_constraints.hpp:355-364).  ONE implementation shared by the
    sequential grower (closure ``gain_mult_for``) and the frontier grower
    so the two streams cannot drift."""
    d = jnp.asarray(depth, jnp.float32)
    factor = jnp.where(
        pen >= d + 1.0, 1e-15,
        jnp.where(pen <= 1.0, 1.0 - pen / jnp.exp2(d),
                  1.0 - jnp.exp2(pen - 1.0 - d)) + 1e-15)
    return jnp.where(monotone != 0, factor, 1.0)


def _frontier_eligible(cfg: "GrowerConfig", n_cols: int, interaction_sets,
                       cegb_coupled, cegb_lazy, forced,
                       efb=None) -> bool:
    """True when the round-batched frontier grower (ops/frontier.py) can
    serve this call.  Cross-leaf-coupled features (monotone intermediate/
    advanced bounds, CEGB refunds, interaction branch masks, forced-split
    prefixes) depend on the sequential split order and take the one-split
    loop; per-node RNG features (feature_fraction_bynode, extra_trees) are
    served by the frontier with a split-record-keyed stream, and
    monotone-BASIC is served natively: its output bounds pinch at the
    midpoint down the root path, which is exactly the per-leaf state the
    frontier already tracks (no cross-leaf propagation to order against)."""
    if cfg.grower_mode == "serial":
        return False
    mode = cfg.parallel_mode or ("data" if cfg.axis_name is not None else None)
    ok = ((not cfg.has_monotone or cfg.monotone_mode == "basic")
          and interaction_sets is None
          and cegb_coupled is None and cegb_lazy is None
          and not forced
          and cfg.cegb_split_penalty == 0.0
          and mode in (None, "data", "feature", "voting")
          and (efb is None or mode in (None, "data")))
    if ok and cfg.hist_method == "pallas":
        # the batched-leaf kernel's bins block spans all features at once
        # (single feature block); very wide feature sets exceed its lane
        # budget
        from .histogram import _PALLAS_LEAVES_MAX_LANES
        bb = cfg.bundle_bins or cfg.max_bin
        ok = n_cols * (-(-bb // 128) * 128) <= _PALLAS_LEAVES_MAX_LANES
    if not ok and cfg.grower_mode == "frontier":
        from ..utils.log import Log
        Log.warning("tree_grower=frontier is not compatible with the "
                    "requested features; using the serial grower")
    return ok


def node_assign_from_ranges(perm, leaf_begin, leaf_nrows):
    """Leaf id of every row from the leaves' ranges of ``perm``.

    Leaf ``i`` owns the positions ``[leaf_begin[i], leaf_begin[i] +
    leaf_nrows[i])``; the non-empty leaves' ranges tile ``[0, n)`` and an
    empty leaf owns nothing.  A position's leaf is piecewise constant, so it
    is a running sum of its steps: each non-empty leaf writes, at its begin,
    its id less the id of the range before it (a scatter of ``L`` values),
    and one ``cumsum`` over positions spreads them.  Nothing is looked up per
    position; the one ``[n]``-sized scatter takes the ids to row order.
    """
    n = perm.shape[0]
    begins = jnp.where(leaf_nrows > 0, leaf_begin, n)       # empty: dropped
    order = jnp.argsort(begins).astype(jnp.int32)           # leaf ids by begin
    before = jnp.concatenate([jnp.zeros(1, jnp.int32), order[:-1]])
    steps = jnp.zeros(n, jnp.int32).at[begins[order]].set(order - before,
                                                          mode="drop")
    return jnp.zeros(n, jnp.int32).at[perm].set(jnp.cumsum(steps))


def grow_tree(bins: jax.Array, grad: jax.Array, hess: jax.Array,
              row_weight: jax.Array, feature_mask: jax.Array,
              num_bins: jax.Array, default_bins: jax.Array, nan_bins: jax.Array,
              is_categorical: jax.Array, monotone: jax.Array,
              key: jax.Array, cfg: GrowerConfig,
              interaction_sets: "jax.Array | None" = None,
              cegb_coupled: "jax.Array | None" = None,
              cegb_lazy: "jax.Array | None" = None,
              cegb_used_data: "jax.Array | None" = None,
              forced: "Tuple[Tuple[int, int, int], ...]" = (),
              efb: "tuple | None" = None,
              feature_contri: "jax.Array | None" = None,
              with_stats: bool = False,
              ) -> Tuple[TreeArrays, jax.Array]:
    """Grow one tree.  Returns (tree, node_assignment[num_data]), and with
    ``with_stats`` a third ``int32[3]``: the frontier grower's counters
    (``frontier.grow_tree_frontier``), zeros from the serial grower.

    Optional feature-gating state:
      interaction_sets: ``[C, F]`` 0/1 — each row one interaction-constraint
        group; a leaf may only split on features in some group containing all
        its branch features (``col_sampler.hpp:91`` ``GetByNode``).
      cegb_coupled: ``[F]`` tradeoff×coupled-penalty, already zeroed for
        features used by earlier trees (``cegb_penalty_feature_coupled``).
      cegb_lazy: ``[F]`` tradeoff×lazy-penalty (``cegb_penalty_feature_lazy``).
      cegb_used_data: ``[N, F]`` bool — rows×features already "paid for" by
        earlier trees (the reference's ``feature_used_in_data_`` bitset).
      forced: static BFS-ordered forced splits as (side, inner_feature,
        threshold_bin, parent_forced_idx) tuples
        (``SerialTreeLearner::ForceSplits``, serial_tree_learner.cpp:450-562);
        ``side`` is 0 for the root/left child of the parent forced split and
        1 for its right child — target leaf ids are resolved at runtime so
        a forced split that fails its validity gates (skipped, as the
        reference erases negative-gain forced splits from forceSplitMap)
        does not shift later forced splits' leaf numbering.
      efb: static ``(feat_bundle [F], feat_off [F], num_bins [F])`` numpy
        arrays when ``bins`` is an EFB bundle matrix (io/efb.py): histograms
        are built and stored in bundle space and expanded to feature space
        for each split search; the split column decodes through the uniform
        ``col - off + 1`` mapping (identity for singleton bundles).
    """
    if _frontier_eligible(cfg, bins.shape[1], interaction_sets,
                          cegb_coupled, cegb_lazy, forced, efb):
        from .frontier import grow_tree_frontier
        return grow_tree_frontier(bins, grad, hess, row_weight, feature_mask,
                                  num_bins, default_bins, nan_bins,
                                  is_categorical, monotone, key, cfg,
                                  efb=efb, feature_contri=feature_contri,
                                  with_stats=with_stats)
    n, n_cols = bins.shape
    if efb is not None:
        efb_bundle_np, efb_off_np, efb_nb_np = efb
        f = int(efb_bundle_np.shape[0])
        if cfg.parallel_mode in ("feature", "voting"):
            raise NotImplementedError(
                "EFB is not supported with feature/voting parallel learners")
    else:
        f = n_cols
    L = cfg.num_leaves
    B = cfg.max_bin                    # feature-space histogram width
    Bb = cfg.bundle_bins or B          # kernel (bundle-column) width
    cw = cat_words(B)
    p = cfg.split
    axis = cfg.axis_name
    mode = cfg.parallel_mode or ("data" if axis is not None else None)

    # ---- EFB decode tables (identity when efb is None) ---------------------
    # split-column mapping: feature bin = col - off + 1 when
    # off <= col < off + (nb-1), else 0.  With off = 1 and col the feature's
    # own column this is the identity, so ONE code path serves both layouts.
    if efb is not None:
        col_of_feat = jnp.asarray(efb_bundle_np.astype(np.int32))
        off_of_feat = jnp.asarray(efb_off_np.astype(np.int32))
        # static gather indices: hist_f[f, b] = hist_b[bundle_f, off_f+b-1]
        _spans = efb_nb_np.astype(np.int64) - 1
        _bidx = np.arange(B - 1, dtype=np.int64)[None, :]
        _valid = _bidx < _spans[:, None]
        _idx = (efb_bundle_np.astype(np.int64)[:, None] * Bb
                + efb_off_np.astype(np.int64)[:, None] + _bidx)
        _idx = np.where(_valid, _idx, 0)
        _efb_idx = jnp.asarray(_idx.reshape(-1).astype(np.int32))
        _efb_valid = jnp.asarray(_valid.astype(np.float32))
        _efb_bundle = jnp.asarray(efb_bundle_np.astype(np.int32))

        def expand_hist(hb):
            """[n_cols, Bb, 3] bundle hists -> [F, B, 3] feature hists
            (bin 0 recovered as total-minus-rest: the reference's
            FixHistogram, dataset.cpp:1239)."""
            flat = hb.reshape(-1, 3)
            g = jnp.take(flat, _efb_idx, axis=0).reshape(f, B - 1, 3)
            g = g * _efb_valid[:, :, None]
            totals = jnp.sum(hb, axis=1)                       # [n_cols, 3]
            bin0 = jnp.take(totals, _efb_bundle, axis=0) - jnp.sum(g, axis=1)
            return jnp.concatenate([bin0[:, None, :], g], axis=1)
    else:
        col_of_feat = off_of_feat = None

        def expand_hist(hb):
            return hb

    def split_column_bins(colv_raw, feat):
        """Decode a gathered (bundle) column into feature bins for ``feat``."""
        if efb is None:
            return colv_raw
        from ..io.efb import decode_bundle_column
        return decode_bundle_column(colv_raw, off_of_feat[feat],
                                    num_bins[feat]).astype(jnp.int32)

    # --- data-parallel comm shape: reduce-scatter + sharded search ----------
    # Instead of allreducing the full [F, B, 3] histogram per split, each
    # shard receives (and stores, and searches) only its OWN feature block:
    # lax.psum_scatter moves F*B/ndev per device where a psum moved F*B, and
    # the winning SplitInfo rides the existing _reduce_split_global pmax —
    # the reference DataParallelTreeLearner dataflow (ReduceScatter +
    # SyncUpGlobalBestSplit, data_parallel_tree_learner.cpp:155-251).
    # Falls back to the full psum for the paths that need a full-width
    # histogram store on every shard (EFB bundles, forced splits, CEGB-lazy).
    dp_scatter = (mode == "data" and efb is None and not forced
                  and cegb_lazy is None and cfg.num_shards > 1)
    if dp_scatter:
        shard_w = -(-f // cfg.num_shards)        # owned features per shard
        shard_wp = shard_w * cfg.num_shards

    # --- sharded-search bookkeeping (feature-parallel + data-scatter) -------
    # metadata arrays arrive FULL-width [F_total]; the histogram axis is the
    # local shard.  Local slices feed the split search, full arrays feed the
    # partition step (which sees the globally-reduced winning feature id).
    if mode == "feature":
        dev = jax.lax.axis_index(axis)
        f_start = dev * f

        def lslice(a):
            return jax.lax.dynamic_slice_in_dim(a, f_start, f)
        num_bins_l = lslice(num_bins)
        default_bins_l = lslice(default_bins)
        nan_bins_l = lslice(nan_bins)
        is_cat_l = lslice(is_categorical)
        mono_l = lslice(monotone)
        f_full = feature_mask.shape[0]
    elif dp_scatter:
        dev = jax.lax.axis_index(axis)
        f_start = dev * shard_w

        def lslice(a, fill):
            ap = jnp.pad(a, (0, shard_wp - f), constant_values=fill)
            return jax.lax.dynamic_slice_in_dim(ap, f_start, shard_w)
        num_bins_l = lslice(num_bins, 1)
        default_bins_l = lslice(default_bins, 0)
        nan_bins_l = lslice(nan_bins, -1)
        is_cat_l = lslice(is_categorical, False)
        mono_l = lslice(monotone, 0)
        f_full = f
    else:
        num_bins_l, default_bins_l, nan_bins_l = num_bins, default_bins, nan_bins
        is_cat_l, mono_l = is_categorical, monotone
        f_full = f

    # capacity ladder for adaptive leaf compaction: per-split histogram cost
    # tracks the smaller sibling's size (the reference computes only over
    # per-leaf index ranges, data_partition.hpp; full-mask passes would make
    # every split O(N))
    caps: "list[int]" = []
    if cfg.hist_compact:
        c = min(cfg.hist_compact_min_cap, n)
        factor = max(1.2, float(cfg.hist_compact_ladder))
        while c < n:
            caps.append(c)
            c = max(c + 1024, -(-int(c * factor) // 1024) * 1024)
    caps.append(n)

    # Row-partition mode: maintain a permutation of local rows grouped by
    # leaf (the TPU analog of the reference's DataPartition index ranges,
    # data_partition.hpp:21-170).  Per split, only the parent's contiguous
    # segment is touched: every O(N)-per-split pass (leaf masks, decision
    # vectors, compaction searches) collapses to O(parent rows), bucketed by
    # the same capacity ladder.  Feature mode broadcasts the owner shard's
    # split column per segment (see partition_and_hist); voting partitions
    # its local row shard exactly like data mode.  Disabled only for
    # CEGB-lazy (its per-row cost bitset needs leaf masks).
    use_partition = (cfg.hist_compact and len(caps) > 1
                     and cegb_lazy is None)

    def _seg_window(begin, cap):
        """Clamped cap-sized window covering [begin, begin+cap) and the
        offset of ``begin`` inside it."""
        start = jnp.clip(begin, 0, max(n - cap, 0))
        return start, begin - start

    # Per-tree combined row payload for the fused partition+histogram pass:
    # the 12 bytes of (grad, hess, row_weight) ride INSIDE the bins rows as
    # extra bin-typed columns, so ONE row gather moves everything.  On v5e a
    # u8 [N, F] row is lane-padded to a 128-byte tile row for any F<=128, so
    # the extra byte-columns are free at gather time, while a separate f32
    # [N, 3] gather benched ~2x the bins gather (XLA lays [N, small] out
    # column-major, scattering each row's fields 4MB apart).
    _gh_cols = 12 // bins.dtype.itemsize          # 12 bytes as bin-typed cols
    _gh_packed = jax.lax.bitcast_convert_type(
        jnp.stack([grad, hess, row_weight], axis=1), bins.dtype
    ).reshape(n, _gh_cols)
    comb = jnp.concatenate([bins, _gh_packed], axis=1)    # [N, F + gh_cols]

    def _unpack_gh(combb):
        """[cap, 3] f32 (grad, hess, row_weight) back out of a gathered
        combined block."""
        cap = combb.shape[0]
        raw = combb[:, n_cols:].reshape(cap, 3, _gh_cols // 3)
        return jax.lax.bitcast_convert_type(raw, jnp.float32)

    def reduce_hist(h):
        """Join shard-local histograms: reduce-scatter to the owned feature
        block (dp_scatter) or full allreduce.  No-op outside data mode."""
        if mode != "data":
            return h
        if dp_scatter:
            hp = jnp.pad(h, ((0, shard_wp - n_cols), (0, 0), (0, 0)))
            return psum_scatter_hist(hp, axis, cfg.num_shards)
        return psum_hist(h, axis)

    @jax.named_scope("lgbm/sum_repair")
    def totals_of(h):
        """[..., 3] (sum_g, sum_h, count) of the leaves whose stored pair
        histograms are ``h`` [..., store_w, Bb, 6], the same on every shard:
        a leaf's sums come from its own histogram (frontier.py, "Sums")."""
        t = hist_totals(h)
        if mode == "voting":        # rows are sharded and the store is local
            t = jax.lax.psum(t, axis)
        elif mode == "feature" or dp_scatter:   # shard 0 holds column 0
            t = jax.lax.psum(jnp.where(dev == 0, t, 0.0), axis)
        return t

    # lgbm/* named scopes label the phases inside the single fused program
    # so a device trace (jax.profiler, read through obs.device_scopes())
    # decomposes the grower the way the host-paced streaming loop does
    @jax.named_scope("lgbm/partition")
    def partition_and_hist(perm, begin, rows, feat, thr, dleft, f_is_cat,
                           cbits, ok, left_smaller):
        """One switch over the parent-cap ladder: gather the parent segment's
        rows ONCE, decide the split, stable-partition the perm segment, and
        histogram the smaller child from the gathered block with a side mask.

        Fuses the reference's ``DataPartition::Split`` + smaller-child
        ``ConstructHistograms`` (serial_tree_learner.cpp:324-372,564-682).
        The fusion is the point: a per-split flat ``bins.reshape(-1)`` column
        gather benched at a fixed ~0.7 ms relayout of the whole bins array,
        and the separate child histogram paid a second row gather — here the
        parent block is gathered once and both consumers read it from VMEM-
        friendly layout.  Returns (perm', nleft, small_hist)."""
        def mk(cap):
            def br(perm):
                start, off = _seg_window(begin, cap)
                seg = jax.lax.dynamic_slice(perm, (start,), (cap,))
                combb = jnp.take(comb, seg, axis=0)       # [cap, NC+gh_cols]
                ghb = _unpack_gh(combb)                   # [cap, 3]
                # split column via one-hot reduce — a dynamic minor-axis
                # take would relayout the whole block
                if mode == "feature":
                    # columns are sharded: the owner selects its local
                    # column, the psum broadcasts it.  The collective is
                    # safe INSIDE the cap switch only because feature mode
                    # replicates rows — begin/rows (hence the switch index)
                    # are identical on every shard.
                    local_ix = jnp.clip(feat - f_start, 0, f - 1)
                    fsel = ((jnp.arange(combb.shape[1], dtype=jnp.int32)
                             == local_ix)
                            & (feat >= f_start) & (feat < f_start + f))
                    colv = jax.lax.psum(
                        jnp.sum(combb.astype(jnp.int32) * fsel[None, :],
                                axis=1), axis)
                else:
                    col_id = col_of_feat[feat] if efb is not None else feat
                    fsel = (jnp.arange(combb.shape[1], dtype=jnp.int32)
                            == col_id)
                    colv = split_column_bins(
                        jnp.sum(combb.astype(jnp.int32) * fsel[None, :],
                                axis=1), feat)
                is_miss = (colv == nan_bins[feat]) & (nan_bins[feat] >= 0)
                gl = jnp.where(f_is_cat, bitset_contains(cbits, colv),
                               jnp.where(is_miss, dleft, colv <= thr))
                ar = jnp.arange(cap, dtype=jnp.int32)
                valid = (ar >= off) & (ar < off + rows)
                gl_v = gl & valid
                nleft = jnp.sum(gl_v.astype(jnp.int32))
                # stable partition via position scatter (a gather-based
                # double binary search benched 7x slower: large-array
                # gathers are the slow primitive on TPU)
                cl = jnp.cumsum(gl_v.astype(jnp.int32))
                cr = jnp.cumsum((valid & ~gl).astype(jnp.int32))
                pos = jnp.where(gl_v, off + cl - 1,
                                jnp.where(valid, off + nleft + cr - 1, ar))
                new_seg = jnp.zeros(cap, jnp.int32).at[pos].set(seg)
                if ok is not None:
                    new_seg = jnp.where(ok, new_seg, seg)
                    nleft = jnp.where(ok, nleft, 0)
                new_perm = jax.lax.dynamic_update_slice(perm, new_seg,
                                                        (start,))
                m = jnp.where(valid & (gl == left_smaller), ghb[:, 2], 0.0)
                # histogram the combined block in place: the pallas kernel
                # skips the gh byte-columns via f_limit, the XLA fallbacks
                # histogram them as garbage and the [:n_cols] slice drops it
                # — either way cheaper than a minor-axis slice relayout
                h = build_histogram(combb, ghb[:, 0], ghb[:, 1], m, Bb,
                                    method=cfg.hist_method,
                                    chunk_rows=cfg.hist_chunk_rows,
                                    f_limit=n_cols,
                                    variant=cfg.hist_variant)
                return new_perm, nleft, h[:n_cols]
            return br
        idx = jnp.searchsorted(jnp.asarray(caps, jnp.int32), rows)
        new_perm, nleft, h = jax.lax.switch(idx, [mk(c) for c in caps], perm)
        # collective stays OUTSIDE the data-dependent switch: shards may
        # pick different buckets, all join here
        return new_perm, nleft, reduce_hist(h)

    @jax.named_scope("lgbm/hist")
    def hist_of(mask, nrows=None):
        def full(m):
            return build_histogram(bins, grad, hess, m, Bb,
                                   method=cfg.hist_method,
                                   chunk_rows=cfg.hist_chunk_rows,
                                   variant=cfg.hist_variant)

        if nrows is None or len(caps) == 1:
            h = full(mask)
        else:
            def mk(cap):
                def br(m):
                    bc, gc, hc, mc = gather_rows(bins, grad, hess, m, cap)
                    return build_histogram(bc, gc, hc, mc, Bb,
                                           method=cfg.hist_method,
                                           chunk_rows=cfg.hist_chunk_rows,
                                           variant=cfg.hist_variant)
                return br
            branches = [mk(c) for c in caps[:-1]] + [full]
            idx = jnp.searchsorted(jnp.asarray(caps, jnp.int32),
                                   nrows.astype(jnp.int32))
            h = jax.lax.switch(idx, branches, mask)
        # collective stays OUTSIDE the data-dependent switch: shards may
        # pick different buckets, all join here
        return reduce_hist(h)

    def node_feature_mask(step):
        if cfg.feature_fraction_bynode >= 1.0:
            return feature_mask
        return node_feature_mask_for(key, step, feature_mask,
                                     cfg.feature_fraction_bynode)

    def rand_thresholds(step):
        if not cfg.extra_trees:
            return None
        return rand_thresholds_for(key, step, cfg.extra_seed,
                                   num_bins_l, nan_bins_l)

    def gain_mult_for(depth):
        """[F] monotone-split penalty factor at a leaf of ``depth``
        (ComputeMonotoneSplitGainPenalty, monotone_constraints.hpp:355-364);
        applied AFTER CEGB like the reference.  feature_contri flows
        separately (BEFORE CEGB) via find()'s ``contri``."""
        if not (cfg.has_monotone and cfg.monotone_penalty > 0.0):
            return None
        return monotone_gain_mult(depth, monotone, cfg.monotone_penalty)

    @jax.named_scope("lgbm/split_search")
    def find(hist, sum_g, sum_h, count, fmask, parent_output=0.0,
             lo=NEG_INF, hi=-NEG_INF, penalty=None, rand=None, mult=None):
        """Mode-dispatched best-split search (the analog of the reference's
        learner-specific FindBestSplitsFromHistograms overrides) of a leaf
        whose stored pair histogram is ``hist`` and whose totals
        (``totals_of``) are ``sum_g``, ``sum_h``, ``count``."""
        hist = expand_hist(fold_hist(hist))
        if mode == "feature" or dp_scatter:
            w = f if mode == "feature" else shard_w

            def lsl(a):
                if dp_scatter:
                    a = jnp.pad(a, (0, shard_wp - a.shape[0]))
                return jax.lax.dynamic_slice_in_dim(a, f_start, w)
            fmask_l = lsl(fmask)
            pen_l = lsl(penalty) if penalty is not None else None
            mult_l = lsl(mult) if mult is not None else None
            contri_l = (lsl(feature_contri) if feature_contri is not None
                        else None)
            # rand_thresholds is built from num_bins_l: already shard-local
            s = find_best_split(hist, num_bins_l, default_bins_l, nan_bins_l,
                                is_cat_l, mono_l, sum_g, sum_h, count, p,
                                fmask_l, parent_output, lo, hi, pen_l, rand,
                                sorted_cat=cfg.sorted_cat, gain_mult=mult_l,
                                contri=contri_l)
            # local winner carries a shard-local feature id; globalize and
            # allreduce-max the packed SplitInfo (parallel_tree_learner.h:191)
            s = s._replace(feature=s.feature + f_start)
            return _reduce_split_global(s, axis)
        if mode == "voting":
            return _find_voting(hist, sum_g, sum_h, count, fmask,
                                parent_output, lo, hi, penalty, rand,
                                mult=mult)
        return find_best_split(hist, num_bins_l, default_bins_l, nan_bins_l,
                               is_cat_l, mono_l, sum_g, sum_h, count, p,
                               fmask, parent_output, lo, hi, penalty, rand,
                               sorted_cat=cfg.sorted_cat, gain_mult=mult,
                               contri=feature_contri)

    def _find_voting(hist, sum_g, sum_h, count, fmask, parent_output, lo, hi,
                     penalty=None, rand=None, mult=None):
        """Local top-k proposal → global vote → reduce only elected
        histograms (voting_parallel_tree_learner.cpp:151-345; the election
        dataflow lives once in split.voting_elect, shared with the frontier
        grower)."""
        from .split import voting_elect
        hist_e, emask = voting_elect(
            hist, num_bins_l, nan_bins_l, is_cat_l, mono_l, sum_g, sum_h,
            count, p, fmask, axis, cfg.top_k, cfg.num_shards, parent_output,
            lo, hi, sorted_cat=cfg.sorted_cat, gain_mult=mult,
            contri=feature_contri)
        return find_best_split(hist_e, num_bins_l, default_bins_l, nan_bins_l,
                               is_cat_l, mono_l, sum_g, sum_h, count, p,
                               emask, parent_output, lo, hi, penalty, rand,
                               sorted_cat=cfg.sorted_cat, gain_mult=mult,
                               contri=feature_contri)

    # monotone 'intermediate' (reference IntermediateLeafConstraints,
    # monotone_constraints.hpp:514): output bounds come from the ACTUAL
    # sibling outputs instead of the midpoint, and tighten OTHER leaves
    # whose bin-rectangles overlap the new children in every non-split
    # dimension.  The overlap test is a vectorized superset of the
    # reference's contiguity tree-walk (GoUpToFindLeavesToUpdate): sound —
    # every constraint it adds is implied by monotonicity — at worst
    # slightly more constraining, and it trades the data-dependent
    # recursion for one [L, F] broadcast per split.  Cached best splits can
    # go stale when bounds tighten, so the growth loop re-validates the
    # chosen leaf's split against current bounds before applying (the
    # analog of RecomputeBestSplitForLeaf, serial_tree_learner.cpp:673-681).
    # intermediate AND advanced share the rect-tracking machinery; advanced
    # additionally RE-DERIVES each new child's output bounds from current
    # rect comparability over all active leaves (see apply_split), instead
    # of inheriting the parent's pinched scalars — the analog of the
    # reference's AdvancedLeafConstraints precision
    # (monotone_constraints.hpp:230-375): a child created by a split on a
    # NON-monotone feature can shed comparable neighbors, and the inherited
    # whole-parent bound would over-tighten it.
    mono_inter = cfg.has_monotone and cfg.monotone_mode in ("intermediate",
                                                            "advanced")
    mono_adv = cfg.has_monotone and cfg.monotone_mode == "advanced"

    use_cegb = (cegb_coupled is not None or cegb_lazy is not None
                or cfg.cegb_split_penalty > 0.0)
    if cegb_lazy is not None and cegb_used_data is None:
        cegb_used_data = jnp.zeros((n, f_full), bool)
    rw_pos = (row_weight > 0).astype(jnp.float32)

    def interaction_allowed(branch):
        """[F] 0/1 mask of features a leaf with branch-feature indicator
        ``branch`` may split on: the union of constraint groups that contain
        every branch feature (``col_sampler.hpp:91`` ``GetByNode``)."""
        ok_c = ~jnp.any((branch[None, :] > 0) & (interaction_sets <= 0), axis=1)
        return jnp.any((interaction_sets > 0) & ok_c[:, None], axis=0) \
            .astype(jnp.float32)

    def cegb_penalty(leaf_mask, count, feat_used, used_data):
        """[F] CEGB gain penalty for splitting the leaf covered by
        ``leaf_mask`` (reference ``DetlaGain``,
        cost_effective_gradient_boosting.hpp:67-85)."""
        pen = jnp.full(f_full, cfg.cegb_split_penalty * count, jnp.float32)
        if cegb_coupled is not None:
            pen = pen + jnp.where(feat_used, 0.0, cegb_coupled)
        if cegb_lazy is not None:
            # on-demand cost: rows in the leaf that never paid for feature f
            unused = leaf_mask @ (1.0 - used_data.astype(jnp.float32))  # [F]
            if mode in ("data", "voting"):
                unused = jax.lax.psum(unused, axis)
            pen = pen + cegb_lazy * unused
        return pen

    # ---- degenerate case: no usable features -> single-leaf tree -----------
    if f == 0:
        cnt = jnp.sum(row_weight)
        wgt = jnp.sum(hess * row_weight)
        if mode in ("data", "voting"):
            cnt = jax.lax.psum(cnt, axis)
            wgt = jax.lax.psum(wgt, axis)
        empty = TreeArrays(
            split_feature=jnp.full(L - 1, -1, jnp.int32),
            threshold=jnp.zeros(L - 1, jnp.int32),
            default_left=jnp.zeros(L - 1, bool),
            is_cat_split=jnp.zeros(L - 1, bool),
            cat_bits=jnp.zeros((L - 1, cw), jnp.int32),
            split_gain=jnp.zeros(L - 1, jnp.float32),
            left_child=jnp.full(L - 1, -1, jnp.int32),
            right_child=jnp.full(L - 1, -1, jnp.int32),
            leaf_value=jnp.zeros(L, jnp.float32),
            leaf_count=jnp.zeros(L, jnp.float32).at[0].set(cnt),
            leaf_weight=jnp.zeros(L, jnp.float32).at[0].set(wgt),
            internal_value=jnp.zeros(L - 1, jnp.float32),
            internal_count=jnp.zeros(L - 1, jnp.float32),
            num_leaves=jnp.int32(1))
        return empty, jnp.zeros(n, jnp.int32)

    # ---- root --------------------------------------------------------------
    root_hist = hist_of(row_weight)
    tot = totals_of(root_hist)
    fmask0 = node_feature_mask(0)
    if interaction_sets is not None:
        fmask0 = fmask0 * interaction_allowed(jnp.zeros(f_full, jnp.float32))
    pen0 = None
    if use_cegb:
        pen0 = cegb_penalty(
            rw_pos, tot[2],
            jnp.zeros(f_full, bool) if cegb_coupled is not None else None,
            cegb_used_data)
    root_split = find(root_hist, tot[0], tot[1], tot[2], fmask0,
                      penalty=pen0, rand=rand_thresholds(0),
                      mult=gain_mult_for(0))

    # histogram store stays in BUNDLE space (subtraction is linear there);
    # searches expand to feature space on the fly.  Under dp_scatter each
    # shard stores only its owned feature block: memory / num_shards.
    store_w = shard_w if dp_scatter else n_cols
    hist_store = jnp.zeros((L, store_w, Bb, 6), jnp.float32).at[0].set(root_hist)
    best = _BestSplits.empty(L, cw).set_leaf(0, root_split)
    # depth gate for root handled trivially (max_depth >= 1 always allows root)

    state = dict(
        hist=hist_store,
        best=best,
        leaf_depth=jnp.zeros(L, jnp.int32),
        leaf_value=jnp.zeros(L, jnp.float32),
        leaf_count=jnp.zeros(L, jnp.float32).at[0].set(tot[2]),
        leaf_weight=jnp.zeros(L, jnp.float32).at[0].set(tot[1]),
        leaf_sum_g=jnp.zeros(L, jnp.float32).at[0].set(tot[0]),
        leaf_lo=jnp.full(L, NEG_INF, jnp.float32),
        leaf_hi=jnp.full(L, -NEG_INF, jnp.float32),
        leaf_parent=jnp.full(L, -1, jnp.int32),     # node that created the leaf
        leaf_is_left=jnp.zeros(L, bool),
        node_feature=jnp.full(L - 1, -1, jnp.int32),
        node_threshold=jnp.zeros(L - 1, jnp.int32),
        node_default_left=jnp.zeros(L - 1, bool),
        node_is_cat=jnp.zeros(L - 1, bool),
        node_cat_bits=jnp.zeros((L - 1, cw), jnp.int32),
        node_gain=jnp.zeros(L - 1, jnp.float32),
        node_parent=jnp.full(L - 1, -1, jnp.int32),  # parent internal node
        node_is_left=jnp.zeros(L - 1, bool),
        node_value=jnp.zeros(L - 1, jnp.float32),
        node_count=jnp.zeros(L - 1, jnp.float32),
        num_leaves=jnp.int32(1),
    )
    if use_partition:
        state["perm"] = jnp.arange(n, dtype=jnp.int32)
        state["leaf_begin"] = jnp.zeros(L, jnp.int32)
        state["leaf_nrows"] = jnp.zeros(L, jnp.int32).at[0].set(n)
    else:
        state["node_assign"] = jnp.zeros(n, jnp.int32)
    if mono_inter:
        # per-leaf bin rectangles for the overlap-propagation pass
        state["rect_lo"] = jnp.zeros((L, f_full), jnp.int32)
        state["rect_hi"] = jnp.full((L, f_full), B - 1, jnp.int32)
        # the step whose per-node feature mask / extra-trees thresholds the
        # leaf's cached best split was searched under: the re-validation
        # must re-key with the SAME step, not resample
        state["leaf_step"] = jnp.zeros(L, jnp.int32)
    if mono_adv:
        # current output of every active leaf (advanced bound derivation);
        # root output from the unconstrained totals
        root_out = leaf_output(state["leaf_sum_g"][0], state["leaf_weight"][0],
                               p, 0.0, state["leaf_count"][0])
        state["leaf_out"] = jnp.zeros(L, jnp.float32).at[0].set(root_out)
    if interaction_sets is not None:
        state["leaf_branch"] = jnp.zeros((L, f_full), jnp.float32)
    if cegb_coupled is not None:
        state["feat_used"] = jnp.zeros(f_full, bool)
    if cegb_lazy is not None:
        state["used_data"] = cegb_used_data

    def forced_split_info(st, leaf, feat, thr):
        """SplitInfo for a forced (feature, threshold-bin) split of a leaf,
        from its stored histogram (the reference's
        ``GatherInfoForThreshold``, feature_histogram.hpp).

        Parallel modes (``feat`` is a static global id): under feature
        parallel only the shard owning the feature's histogram computes the
        info and the result is pmax-broadcast; under voting parallel the
        histogram store is shard-local, so the forced feature's column is
        psum'd first and every shard computes identically (the reference
        runs ForceSplits on every rank over full local histograms —
        serial_tree_learner.cpp:543 — which feature-sharded storage here
        replaces)."""
        owns = None
        if mode == "feature":
            local_ix = jnp.clip(feat - f_start, 0, f - 1)
            owns = (feat >= f_start) & (feat < f_start + f)
            h = expand_hist(fold_hist(st["hist"][leaf]))[local_ix]   # [B, 3]
        elif mode == "voting":
            h = jax.lax.psum(expand_hist(fold_hist(st["hist"][leaf]))[feat],
                             axis)
        else:
            h = expand_hist(fold_hist(st["hist"][leaf]))[feat]       # [B, 3]
        total = jnp.stack([st["leaf_sum_g"][leaf], st["leaf_weight"][leaf],
                           st["leaf_count"][leaf]])
        bin_ids = jnp.arange(B)
        miss_b = nan_bins[feat]
        # numeric: missing rows go LEFT, matching the reference's forced-split
        # gather which excludes the NaN bin from the RIGHT accumulation and
        # sets default_left=true (GatherInfoForThresholdNumericalInner,
        # feature_histogram.hpp)
        f_cat = is_categorical[feat]
        goes_left = jnp.where(f_cat, bin_ids == thr,
                              (bin_ids <= thr) | (bin_ids == miss_b))[:, None]
        left, right = derive_larger(
            jnp.sum(jnp.where(goes_left, h, 0.0), axis=0),
            jnp.sum(jnp.where(goes_left, 0.0, h), axis=0), total)
        lo, hi = st["leaf_lo"][leaf], st["leaf_hi"][leaf]
        lout = leaf_output(left[0], left[1], p, 0.0, left[2], lo, hi)
        rout = leaf_output(right[0], right[1], p, 0.0, right[2], lo, hi)
        gain = (leaf_gain(left[0], left[1], p, 0.0, left[2], lo, hi)
                + leaf_gain(right[0], right[1], p, 0.0, right[2], lo, hi)
                - leaf_gain(total[0], total[1], p, 0.0, total[2], lo, hi))
        # the reference gates forced splits only on the gain threshold
        # (min_gain_to_split), not on min-data/min-hessian
        ok = gain > p.min_gain_to_split
        if owns is not None:
            ok = ok & owns
        res = SplitResult(
            gain=jnp.where(ok, gain, NEG_INF),
            feature=jnp.int32(feat), threshold=jnp.int32(thr),
            default_left=~f_cat,
            left_sum_g=left[0], left_sum_h=left[1], left_count=left[2],
            right_sum_g=right[0], right_sum_h=right[1], right_count=right[2],
            left_output=lout, right_output=rout,
            cat_bits=jnp.where(
                f_cat, pack_bin_bitset(jnp.arange(B, dtype=jnp.int32) == thr),
                jnp.zeros(cw, jnp.int32)))
        if owns is not None:
            res = _reduce_split_global(res, axis)
        return res

    @jax.named_scope("lgbm/apply_split")
    def apply_split(j, st, leaf, gain, ok):
        """Apply the pending best split of ``leaf`` as node ``j``.

        ``ok is None`` means the caller guarantees the split is valid (the
        while-loop body, whose condition already checked gain > 0) and every
        write is unconditional — this keeps the loop free of ``lax.cond``,
        which would copy the multi-MB histogram store every step instead of
        updating it in place.  The forced-split prefix passes a traced ``ok``
        and all writes are predicated."""
        unconditional = ok is None

        def setw(arr, idx, val):
            if unconditional:
                return arr.at[idx].set(val)
            return arr.at[idx].set(jnp.where(ok, val, arr[idx]))

        def gate(cond):
            return cond if unconditional else (cond & ok)

        b = st["best"]
        feat = b.feature[leaf]
        thr = b.threshold[leaf]
        dleft = b.default_left[leaf]
        cbits = b.cat_bits[leaf]
        f_is_cat = is_categorical[feat]
        new_id = st["num_leaves"]

        # --- update node arrays + parent linkage ---
        parent_node = st["leaf_parent"][leaf]
        st_nf = setw(st["node_feature"], j, feat)
        st_nt = setw(st["node_threshold"], j, thr)
        st_nd = setw(st["node_default_left"], j, dleft)
        st_nc = setw(st["node_is_cat"], j, f_is_cat)
        st_ncb = setw(st["node_cat_bits"], j, cbits)
        st_ng = setw(st["node_gain"], j, gain)
        st_np = setw(st["node_parent"], j, parent_node)
        st_nl = setw(st["node_is_left"], j, st["leaf_is_left"][leaf])
        st_nv = setw(st["node_value"], j, leaf_output(
            st["leaf_sum_g"][leaf], st["leaf_weight"][leaf], p,
            0.0, st["leaf_count"][leaf]))
        st_ncount = setw(st["node_count"], j, st["leaf_count"][leaf])

        # --- partition rows of this leaf ---
        left_smaller = b.lc[leaf] <= b.rc[leaf]
        if use_partition:
            # reorder only the parent leaf's segment of the row permutation
            # (DataPartition::Split, data_partition.hpp) and histogram the
            # smaller child from the same gathered block: O(parent rows)
            pbegin = st["leaf_begin"][leaf]
            prows = st["leaf_nrows"][leaf]
            perm, nleft, small_hist = partition_and_hist(
                st["perm"], pbegin, prows, feat, thr, dleft, f_is_cat,
                cbits, ok, left_smaller)
            extra_part = dict(
                perm=perm,
                leaf_begin=setw(st["leaf_begin"], new_id, pbegin + nleft),
                leaf_nrows=setw(setw(st["leaf_nrows"], leaf, nleft),
                                new_id, prows - nleft))
            in_leaf = goes_left = None
        else:
            if mode == "feature":
                # only the shard owning the winning feature can decide; it
                # broadcasts the decision (the reference avoids this because
                # every rank holds every column — here columns are sharded,
                # so one [n] psum replaces replicated column storage)
                local_ix = jnp.clip(feat - f_start, 0, f - 1)
                owns = (feat >= f_start) & (feat < f_start + f)
                col = jnp.take(bins, local_ix, axis=1).astype(jnp.int32)
            else:
                col_id = col_of_feat[feat] if efb is not None else feat
                col = split_column_bins(
                    jnp.take(bins, col_id, axis=1).astype(jnp.int32), feat)
            is_miss = (col == nan_bins[feat]) & (nan_bins[feat] >= 0)
            goes_left = jnp.where(
                f_is_cat, bitset_contains(cbits, col),
                jnp.where(is_miss, dleft, col <= thr))
            if mode == "feature":
                goes_left = jax.lax.psum(
                    jnp.where(owns, goes_left.astype(jnp.float32), 0.0),
                    axis) > 0.5
            in_leaf = st["node_assign"] == leaf
            extra_part = dict(node_assign=jnp.where(
                gate(in_leaf & ~goes_left), new_id, st["node_assign"]))

            # --- child histograms: compute smaller, subtract for larger ---
            small_mask = jnp.where(in_leaf & (goes_left == left_smaller),
                                   row_weight, 0.0)
            small_hist = hist_of(small_mask, jnp.sum(small_mask > 0))
        with jax.named_scope("lgbm/sum_repair"):
            parent_hist = st["hist"][leaf]
            large_hist = sub_hist(parent_hist, small_hist)
            lhist = jnp.where(left_smaller, small_hist, large_hist)
            rhist = jnp.where(left_smaller, large_hist, small_hist)
            hist = setw(setw(st["hist"], leaf, lhist), new_id, rhist)
            hist2 = jnp.stack([lhist, rhist])
            tot2 = totals_of(hist2)      # each child's sums, from its own rows
            g2, h2, c2 = tot2[:, 0], tot2[:, 1], tot2[:, 2]

        # --- child bookkeeping ---
        depth = st["leaf_depth"][leaf] + 1
        leaf_depth = setw(setw(st["leaf_depth"], leaf, depth), new_id, depth)
        leaf_value = setw(setw(st["leaf_value"], leaf, b.lout[leaf]),
                          new_id, b.rout[leaf])
        leaf_count = setw(setw(st["leaf_count"], leaf, c2[0]), new_id, c2[1])
        leaf_weight = setw(setw(st["leaf_weight"], leaf, h2[0]), new_id, h2[1])
        leaf_sum_g = setw(setw(st["leaf_sum_g"], leaf, g2[0]), new_id, g2[1])
        leaf_parent = setw(setw(st["leaf_parent"], leaf, j), new_id, j)
        leaf_is_left = setw(setw(st["leaf_is_left"], leaf, True),
                            new_id, False)

        mono = monotone[feat]
        lo, hi = st["leaf_lo"][leaf], st["leaf_hi"][leaf]
        is_num = ~f_is_cat
        if mono_inter:
            # intermediate: children bounded by the ACTUAL sibling outputs
            # (UpdateConstraintsWithOutputs, monotone_constraints.hpp:543)
            lo_out, ro_out = b.lout[leaf], b.rout[leaf]
            l_lo = jnp.where(is_num & (mono < 0), jnp.maximum(lo, ro_out), lo)
            l_hi = jnp.where(is_num & (mono > 0), jnp.minimum(hi, ro_out), hi)
            r_lo = jnp.where(is_num & (mono > 0), jnp.maximum(lo, lo_out), lo)
            r_hi = jnp.where(is_num & (mono < 0), jnp.minimum(hi, lo_out), hi)
        else:
            # basic: pinch both children at the midpoint of the child outputs
            mid = (b.lout[leaf] + b.rout[leaf]) * 0.5
            l_lo = jnp.where(mono < 0, jnp.maximum(lo, mid), lo)
            l_hi = jnp.where(mono > 0, jnp.minimum(hi, mid), hi)
            r_lo = jnp.where(mono > 0, jnp.maximum(lo, mid), lo)
            r_hi = jnp.where(mono < 0, jnp.minimum(hi, mid), hi)
        leaf_lo = setw(setw(st["leaf_lo"], leaf, l_lo), new_id, r_lo)
        leaf_hi = setw(setw(st["leaf_hi"], leaf, l_hi), new_id, r_hi)

        extra_mono = {}
        if mono_inter:
            # children rectangles: a numeric split partitions dimension
            # `feat` at thr; categorical children conservatively keep the
            # parent rect (more overlaps -> never fewer constraints)
            fsel = jnp.arange(f_full, dtype=jnp.int32) == feat
            prl, prh = st["rect_lo"][leaf], st["rect_hi"][leaf]      # [F]
            l_rh = jnp.where(fsel & is_num, thr, prh)
            r_rl = jnp.where(fsel & is_num, thr + 1, prl)
            rect_lo = setw(setw(st["rect_lo"], leaf, prl), new_id, r_rl)
            rect_hi = setw(setw(st["rect_hi"], leaf, l_rh), new_id, prh)
            extra_mono = dict(rect_lo=rect_lo, rect_hi=rect_hi)

            if mono_adv:
                # ADVANCED: re-derive each child's bounds from current rect
                # comparability over all active leaves, instead of the
                # inherited parent scalars — a child of a split on a
                # non-monotone feature sheds comparable neighbors, and the
                # inherited bound would keep constraining it by them
                # (reference AdvancedLeafConstraints precision).
                new_out = setw(setw(st["leaf_out"], leaf, lo_out),
                               new_id, ro_out)
                lid = jnp.arange(L, dtype=jnp.int32)
                act = lid <= st["num_leaves"]        # old leaves + new slot
                mono_f = monotone.astype(jnp.int32)

                def derive(c_lo_row, c_hi_row, self_id):
                    upper, lower = _rect_comparability(
                        rect_lo, rect_hi, c_lo_row, c_hi_row, mono_f)
                    elig = (act & (lid != self_id))[:, None]
                    hi_c = jnp.min(jnp.where(upper & elig,
                                             new_out[:, None], -NEG_INF))
                    lo_c = jnp.max(jnp.where(lower & elig,
                                             new_out[:, None], NEG_INF))
                    return lo_c, hi_c

                def set_children(arr, left_val, right_val):
                    # a select over leaf ids, not ``.at[].set``: with the
                    # indexed update XLA:CPU's optimized program handed the
                    # next search a stale, looser bound (a leaf pinched to
                    # [b, b] split with an output under b; not at
                    # --xla_backend_optimization_level=0), which broke
                    # monotonicity on every seed of tests/test_constraints.py
                    new = jnp.where(lid == leaf, left_val,
                                    jnp.where(lid == new_id, right_val, arr))
                    return new if unconditional else jnp.where(ok, new, arr)

                al_lo, al_hi = derive(prl, l_rh, leaf)
                ar_lo, ar_hi = derive(r_rl, prh, new_id)
                leaf_lo = set_children(st["leaf_lo"], al_lo, ar_lo)
                leaf_hi = set_children(st["leaf_hi"], al_hi, ar_hi)
                extra_mono["leaf_out"] = new_out

            # Propagate the new child outputs to every active leaf that
            # overlaps a child in all dims except SOME monotone dim k and
            # sits strictly to one side of it along k — for ANY monotone k,
            # not just the split feature: the reference's up-walk crosses
            # every monotone ancestor boundary regardless of what feature
            # the triggering split used (GoUpToFindLeavesToUpdate).
            lid = jnp.arange(L, dtype=jnp.int32)
            is_active = lid <= st["num_leaves"]      # incl. the new leaf slot
            do_prop = gate(jnp.asarray(True))
            mono_f = monotone.astype(jnp.int32)                  # [F]

            def prop(llo, lhi, c_lo_row, c_hi_row, out_c):
                # upper[m]: m sits on the child's GREATER side (it bounds
                # the child's hi) — symmetrically the child's output is a
                # LOWER bound on m.  lower[m] mirrors.  prop updates the
                # NEIGHBORS; derive() uses the same masks to update the
                # child itself.
                upper, lower = _rect_comparability(
                    rect_lo, rect_hi, c_lo_row, c_hi_row, mono_f)
                in_upper = jnp.any(upper, axis=1)
                in_lower = jnp.any(lower, axis=1)
                llo = jnp.where(do_prop & is_active & in_upper,
                                jnp.maximum(llo, out_c), llo)
                lhi = jnp.where(do_prop & is_active & in_lower,
                                jnp.minimum(lhi, out_c), lhi)
                return llo, lhi

            leaf_lo, leaf_hi = prop(leaf_lo, leaf_hi, prl, l_rh, lo_out)
            leaf_lo, leaf_hi = prop(leaf_lo, leaf_hi, r_rl, prh, ro_out)

        # --- feature-gating state: interaction branch sets, CEGB ---
        extra = {}
        fmask = node_feature_mask(j + 1)
        if interaction_sets is not None:
            # both children share the branch = parent branch + this feature
            branch = jnp.where(jnp.arange(f_full) == feat, 1.0,
                               st["leaf_branch"][leaf])
            fmask = fmask * interaction_allowed(branch)
            extra["leaf_branch"] = setw(
                setw(st["leaf_branch"], leaf, branch), new_id, branch)
        cur_best = st["best"]
        feat_used = None
        if cegb_coupled is not None:
            # the coupled penalty is paid once per feature per model: mark
            # it used and refund the penalty in other leaves' cached best
            # gains that proposed the same feature.  This approximates the
            # reference's UpdateLeafBestSplits: leaves whose cached best used
            # a DIFFERENT feature are not re-searched here, so a refunded
            # feature that would now overtake a leaf's cached best is missed
            # until that leaf is next split (the reference re-runs the search
            # for such leaves)
            refund = jnp.where(st["feat_used"][feat], 0.0, cegb_coupled[feat])
            cur_best = cur_best._replace(gain=jnp.where(
                gate((cur_best.feature == feat)
                     & (cur_best.gain > NEG_INF / 2)),
                cur_best.gain + refund, cur_best.gain))
            feat_used = st["feat_used"].at[feat].set(
                st["feat_used"][feat] | (True if unconditional else ok))
            extra["feat_used"] = feat_used
        used_data = None
        if cegb_lazy is not None:
            # rows of the split leaf have now paid feature `feat`'s
            # on-demand cost (feature_used_in_data_ bitset insert)
            used_data = st["used_data"] | (
                gate(in_leaf & (row_weight > 0))[:, None]
                & (jnp.arange(f_full) == feat)[None, :])
            extra["used_data"] = used_data

        # --- new best splits for both children ---
        depth_ok = (cfg.max_depth <= 0) | (depth < cfg.max_depth)

        rand = rand_thresholds(j + 1)

        if use_partition:
            # CEGB-lazy (the only penalty needing row masks) is mask-path-only
            lmask = rmask = None
        else:
            lmask = jnp.where(in_leaf & goes_left, rw_pos, 0.0)
            rmask = jnp.where(in_leaf & ~goes_left, rw_pos, 0.0)

        # both children's split searches ride ONE vmapped call: the search is
        # dominated by fixed small-op overhead at [F, B] scale, so batching
        # the pair halves the per-split serial op count
        # search under the FINAL stored bounds: advanced re-derivation and
        # cross-leaf propagation may have moved them past the inherited
        # pinch (cached gains computed under stale-tighter bounds would
        # silently lose exactly the splits advanced mode admits)
        lo2 = jnp.stack([leaf_lo[leaf], leaf_lo[new_id]])
        hi2 = jnp.stack([leaf_hi[leaf], leaf_hi[new_id]])
        if use_cegb:
            pen2 = jnp.stack([cegb_penalty(lmask, c2[0], feat_used, used_data),
                              cegb_penalty(rmask, c2[1], feat_used, used_data)])
        mult2 = gain_mult_for(depth)        # both children share the depth
        if use_cegb:
            s2 = jax.vmap(
                lambda hc, g_, h_, c_, lo_, hi_, pen_: find(
                    hc, g_, h_, c_, fmask, 0.0, lo_, hi_,
                    penalty=pen_, rand=rand, mult=mult2)
            )(hist2, g2, h2, c2, lo2, hi2, pen2)
        else:
            s2 = jax.vmap(
                lambda hc, g_, h_, c_, lo_, hi_: find(
                    hc, g_, h_, c_, fmask, 0.0, lo_, hi_,
                    rand=rand, mult=mult2)
            )(hist2, g2, h2, c2, lo2, hi2)
        s2 = s2._replace(gain=jnp.where(depth_ok, s2.gain, NEG_INF))
        sl = jax.tree.map(lambda a: a[0], s2)
        sr = jax.tree.map(lambda a: a[1], s2)
        best = cur_best.set_leaf(leaf, sl, ok).set_leaf(new_id, sr, ok)
        if mono_inter:
            # both children's cached splits were searched under step j+1's
            # mask/thresholds (see fmask/rand above)
            jt = jnp.asarray(j, jnp.int32) + 1
            extra_mono["leaf_step"] = setw(
                setw(st["leaf_step"], leaf, jt), new_id, jt)

        return dict(
            **extra,
            **extra_part,
            **extra_mono,
            hist=hist, best=best,
            leaf_depth=leaf_depth, leaf_value=leaf_value,
            leaf_count=leaf_count, leaf_weight=leaf_weight,
            leaf_sum_g=leaf_sum_g, leaf_lo=leaf_lo, leaf_hi=leaf_hi,
            leaf_parent=leaf_parent, leaf_is_left=leaf_is_left,
            node_feature=st_nf, node_threshold=st_nt,
            node_default_left=st_nd, node_is_cat=st_nc, node_cat_bits=st_ncb,
            node_gain=st_ng,
            node_parent=st_np, node_is_left=st_nl, node_value=st_nv,
            node_count=st_ncount,
            num_leaves=st["num_leaves"] + (
                1 if unconditional else ok.astype(jnp.int32)),
        )

    # forced splits first (unrolled BFS prefix with runtime-tracked leaf ids
    # and node slots, so a forced split that fails its gates leaves no gap in
    # the node arrays and does not shift later siblings' leaf numbering),
    # then best-gain growth
    forced_ok = []
    forced_leaf_id = []      # traced leaf id each forced node targets
    forced_right_id = []     # traced leaf id of each forced node's right child
    for j in range(min(len(forced), L - 1)):
        fside, ffeat, fthr, fpar = forced[j]
        if fpar < 0:
            fleaf = jnp.int32(0)
        elif fside == 0:     # left child keeps the parent's leaf id
            fleaf = forced_leaf_id[fpar]
        else:                # right child got the fresh id at the parent split
            fleaf = forced_right_id[fpar]
        forced_leaf_id.append(fleaf)
        forced_right_id.append(state["num_leaves"])  # id if this split lands
        nl_before = state["num_leaves"]
        finfo = forced_split_info(state, fleaf, ffeat, fthr)
        if fpar >= 0:
            # a forced split whose forced ancestor failed is dropped (the
            # reference aborts the subtree, serial_tree_learner.cpp:543-553)
            finfo = finfo._replace(
                gain=jnp.where(forced_ok[fpar], finfo.gain, NEG_INF))
        natural = state["best"]
        state = dict(state, best=natural.set_leaf(fleaf, finfo))
        fgain = state["best"].gain[fleaf]
        # node slot = number of successful splits so far: failures leave the
        # node arrays gapless
        state = apply_split(state["num_leaves"] - 1, state, fleaf, fgain,
                            fgain > 0.0)
        ok = state["num_leaves"] > nl_before
        forced_ok.append(ok)
        # failed forced split: restore the leaf's natural best so the
        # best-gain phase can still split it (forceSplitMap erase)
        restored = _BestSplits(*[
            c.at[fleaf].set(jnp.where(ok, c[fleaf], nat[fleaf]))
            for c, nat in zip(state["best"], natural)])
        state = dict(state, best=restored)

    # best-gain growth: a while_loop that EXITS when no positive-gain split
    # remains, so finished trees don't pay for dead iterations, and whose
    # body is branch-free so XLA aliases the loop-carried histogram store
    # in place (a lax.cond here copied the multi-MB buffers every step)
    def loop_cond(carry):
        jj, st = carry
        active = jnp.where(jnp.arange(L) < st["num_leaves"],
                           st["best"].gain, NEG_INF)
        return (jj < L - 1) & (jnp.max(active) > 0.0)

    def loop_body(carry):
        jj, st = carry
        active = jnp.where(jnp.arange(L) < st["num_leaves"],
                           st["best"].gain, NEG_INF)
        leaf = jnp.argmax(active).astype(jnp.int32)
        if not mono_inter:
            st = apply_split(jj, st, leaf, active[leaf], None)
            return jj + 1, st
        # intermediate monotone mode: the cached split may violate bounds
        # tightened since it was found — re-search against CURRENT bounds
        # (RecomputeBestSplitForLeaf analog), with the same feature gates
        # the cached search had: per-node mask and extra-trees thresholds
        # re-keyed by the step the cache was built at (leaf_step), the
        # interaction branch mask, and CEGB penalties.  A leaf whose
        # re-search finds nothing is retired (gain -> NEG_INF) without
        # consuming a node slot.
        step0 = st["leaf_step"][leaf]
        fmask_j = node_feature_mask(step0)
        if interaction_sets is not None:
            fmask_j = fmask_j * interaction_allowed(st["leaf_branch"][leaf])
        pen_j = None
        if use_cegb:
            lm = None
            if cegb_lazy is not None:
                lm = jnp.where(st["node_assign"] == leaf, rw_pos, 0.0)
            pen_j = cegb_penalty(
                lm, st["leaf_count"][leaf],
                st["feat_used"] if cegb_coupled is not None else None,
                st["used_data"] if cegb_lazy is not None else None)
        s_new = find(st["hist"][leaf], st["leaf_sum_g"][leaf],
                     st["leaf_weight"][leaf], st["leaf_count"][leaf],
                     fmask_j, 0.0,
                     st["leaf_lo"][leaf], st["leaf_hi"][leaf],
                     penalty=pen_j, rand=rand_thresholds(step0),
                     mult=gain_mult_for(st["leaf_depth"][leaf]))
        depth_ok = (cfg.max_depth <= 0) | (st["leaf_depth"][leaf]
                                           < cfg.max_depth)
        s_new = s_new._replace(gain=jnp.where(depth_ok, s_new.gain, NEG_INF))
        st = dict(st, best=st["best"].set_leaf(leaf, s_new))
        ok = s_new.gain > 0.0
        st = apply_split(jj, st, leaf, s_new.gain, ok)
        return jj + ok.astype(jnp.int32), st

    _, state = jax.lax.while_loop(
        loop_cond, loop_body, (state["num_leaves"] - 1, state))

    # ---- reconstruct child pointers ----------------------------------------
    # node j's children: initially leaves (~leaf ids); later splits of those
    # leaves overwrite with internal node ids.
    left_child = jnp.full(L - 1, -1, jnp.int32)
    right_child = jnp.full(L - 1, -1, jnp.int32)

    def scatter_claims(child, idx, cond, val):
        # route non-claiming writes out of bounds so they are dropped —
        # each (node, side) slot has exactly one final claimant
        return child.at[jnp.where(cond, idx, L)].set(val, mode="drop")

    # leaves claim the slot of their creating node
    leaf_ids = jnp.arange(L, dtype=jnp.int32)
    lp = state["leaf_parent"]
    valid_leaf = lp >= 0
    left_child = scatter_claims(left_child, lp, valid_leaf & state["leaf_is_left"], ~leaf_ids)
    right_child = scatter_claims(right_child, lp, valid_leaf & ~state["leaf_is_left"], ~leaf_ids)
    # internal nodes overwrite the slot they were grown from
    node_ids = jnp.arange(L - 1, dtype=jnp.int32)
    npar = state["node_parent"]
    valid_node = (npar >= 0) & (state["node_feature"] >= 0)
    left_child = scatter_claims(left_child, npar, valid_node & state["node_is_left"], node_ids)
    right_child = scatter_claims(right_child, npar, valid_node & ~state["node_is_left"], node_ids)

    tree = TreeArrays(
        split_feature=state["node_feature"],
        threshold=state["node_threshold"],
        default_left=state["node_default_left"],
        is_cat_split=state["node_is_cat"],
        cat_bits=state["node_cat_bits"],
        split_gain=state["node_gain"],
        left_child=left_child,
        right_child=right_child,
        leaf_value=state["leaf_value"],
        leaf_count=state["leaf_count"],
        leaf_weight=state["leaf_weight"],
        internal_value=state["node_value"],
        internal_count=state["node_count"],
        num_leaves=state["num_leaves"],
    )
    no_stats = (jnp.zeros(3, jnp.int32),) if with_stats else ()
    if not use_partition:
        return (tree, state["node_assign"]) + no_stats

    node_assign = node_assign_from_ranges(state["perm"], state["leaf_begin"],
                                          state["leaf_nrows"])
    return (tree, node_assign) + no_stats
