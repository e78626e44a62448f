"""Level-batched best-first tree growth — the fast path of the grower.

Re-designs ``SerialTreeLearner::Train``'s one-split-at-a-time loop
(``src/treelearner/serial_tree_learner.cpp:158-209``) into rounds that grow
**k leaves per compiled step** while preserving exact best-first semantics.
The enabling observation: in the best-first priority-queue process a node's
pop position is the descending order of

    g_hat(v) = min(gain(v), g_hat(parent(v)))

— children enter the queue only after their parent pops, so a node's
effective priority is the minimum gain along its root path (non-increasing
down any path).  Therefore:

- expanding the top-k pending leaves by ``g_hat`` each round visits splits
  in a superset of the true best-first prefix,
- growth can stop exactly when every pending ``g_hat`` is below the
  ``(num_leaves-1)``-th largest applied ``g_hat`` (no pending split can
  displace an applied one), and
- ONE sort by ``(g_hat desc, creation seq asc)`` at the end reproduces the
  sequential grower's split order — and with it the reference's node/leaf
  numbering (left child keeps the parent's leaf id, right child takes the
  next fresh id) — with no sequential priority queue anywhere.

Splits applied beyond the budget ("overshoot") revert for free: every row
carries the leaf slot it ended in, a slot knows the split record that made
it, and a dropped record's rows belong to the nearest selected record above
it (``_leaf_of_slots``), so the parent simply remains a leaf.

Per round the heavy work is batched and stays in row order: every row
compares its slot with the k selected ones and reads its bin at its own
index in that slot's split column (k contiguous column reads, no per-row
gather), ONE sort groups the rows of the k smaller children and its keys
give the children's counts, ONE leaf-grouped row gather feeds the batched Pallas
histogram kernel (``build_histogram_leaves``), and the 2k child split
searches ride a single vmapped ``find_best_split``.  This amortizes the
sequential tail (per-split small-op overhead, ~33% of round-3 tree time)
and halves gather traffic (only smaller-sibling rows are ever row-gathered).

Scope: serial, data-, feature- and voting-parallel modes without
cross-leaf-COUPLED features.  Monotone constraints, CEGB, interaction
constraints and forced splits couple leaves to the sequential split order
and take the sequential grower (``grower.grow_tree``);
``grower._frontier_eligible`` is the gate.  Per-node RNG features
(``feature_fraction_bynode``, ``extra_trees``) ARE served here: their draws
are re-keyed by split-record index (see ``node_mask_for``), giving a valid
stream of the same structure as the sequential grower's step-keyed one.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .histogram import (build_histogram, build_histogram_leaves, fold_hist,
                        hist_totals, leaves_feature_block, psum_hist,
                        sub_hist, unrolled_rank)
from .split import (NEG_INF, SplitResult, cat_words, find_best_split,
                    pack_bin_bitset)

POS_INF = -NEG_INF


def hist_store_shape(cfg, n_cols: int) -> tuple:
    """The per-leaf histogram store's shape: a float32 pair histogram
    (``histogram.py``) for each of the ``num_leaves + 2 * frontier_k`` leaf
    slots, of the kernel's ``n_cols`` columns."""
    L = cfg.num_leaves
    k = max(1, min(cfg.frontier_k, L - 1))
    return (L + 2 * k, n_cols, cfg.bundle_bins or cfg.max_bin, 6)


def exchange_bytes(cfg, n_cols: int, rounds: int) -> int:
    """Bytes one device receives in one tree's exchange under
    ``tree_learner=data``, reckoned from the exchanged shapes as an
    all-gather moves them, from each of the other shards: the root's
    histogram and each round's ``k`` smaller children's (``[n_cols, bins,
    6]`` float32 each, ``histogram.psum_hist``).  Nothing else crosses: every
    shard searches the same summed histograms with the same program, so
    every shard picks the same splits."""
    k = max(1, min(cfg.frontier_k, cfg.num_leaves - 1))
    hist = n_cols * (cfg.bundle_bins or cfg.max_bin) * 6 * 4
    return (cfg.num_shards - 1) * hist * (1 + rounds * k)


def grow_tree_frontier(bins, grad, hess, row_weight, feature_mask,
                       num_bins, default_bins, nan_bins, is_categorical,
                       monotone, key, cfg, efb=None, feature_contri=None,
                       with_stats=False, hist_store=None):
    """Grow one tree with round-batched best-first expansion.

    Same contract as ``grower.grow_tree`` (returns ``(TreeArrays,
    node_assignment)``) for the eligible feature subset; trees are
    identical to the sequential grower's up to float-summation order in
    histograms and tie-breaks between exactly-equal gains.

    ``with_stats`` adds a third result, ``int32[5]``: the frontier rounds the
    tree took and the rows of the leaves those rounds split, as
    ``hi * 2**20 + lo`` (no 64-bit integers without x64; exact up to 2047
    rounds of 2**31 rows), 1 (this grower grew the tree), and the feature
    blocks of the batched-leaf kernel's grid (0 where the XLA fallback
    builds the histograms).  Each round passes every one of the ``n`` rows
    whatever it splits, so the first two say what share of that work was
    useful.

    ``hist_store`` (``hist_store_shape``) is a store to grow in, whatever
    it holds: a slot is written before it is read.  Given, the store as the
    tree left it is a last result, so that a caller that donates it grows
    every tree in one buffer (``GBDT._grow``), as the reference allocates
    its ``HistogramPool`` once; without it the store is the program's own.

    The invariant the rounds rest on: a row keeps its leaf, not its place.
    The loop carries ``row_slot``, every row's leaf slot, and nothing else of
    size ``n``; rows are never moved.  A round compares each row's slot with
    the at most ``frontier_k`` slots it splits (``_spread_by_slot``), reads
    the row's bin at its own index in the split column (``_bin_of_rows``) and
    writes the right child's slot with a select.  Only the histograms need
    rows grouped by leaf, and only the smaller children's: one sort a round
    (``_group_smaller_children``) puts their ids in front, ascending inside a
    child as a stable partition would leave them, and counts them.  The final node assignment
    is a table from slot to leaf (``_leaf_of_slots``) read by selects.

    Sums: the histogram store holds pairs (``histogram.py``: a float32 sum
    and what it rounds away), siblings are subtracted in pairs, a leaf's
    totals are the sum of its own histogram's first column
    (``hist_totals``), and the split search sums the smaller side of every
    candidate from its own bins.  So a leaf's gradient sum, hessian sum and
    count are right relative to that leaf's size, down to 20 rows under a
    root of 2**25, and nothing is carried down from a parent but its rows.

    The device phases carry ``jax.named_scope`` names (``obs/scopes.py``
    lists them): compile-time metadata, nothing at run time.
    """
    from .grower import TreeArrays, _BestSplits

    n, n_cols = bins.shape
    if efb is not None:
        efb_bundle_np, efb_off_np, efb_nb_np = efb
        f = int(efb_bundle_np.shape[0])
    else:
        f = n_cols
    L = cfg.num_leaves
    B = cfg.max_bin
    Bb = cfg.bundle_bins or B
    cw = cat_words(B)
    p = cfg.split
    axis = cfg.axis_name
    mode = cfg.parallel_mode or ("data" if axis is not None else None)
    k = max(1, min(cfg.frontier_k, L - 1))
    BR = cfg.frontier_block_rows
    S = (L - 1) + 2 * k              # split-record capacity (overshoot slack)
    LS = hist_store_shape(cfg, n_cols)[0]   # leaf slots, L + 2k

    # ---- EFB decode tables (identity when efb is None); see grower.py -----
    if efb is not None:
        col_of_feat = jnp.asarray(efb_bundle_np.astype(np.int32))
        off_of_feat = jnp.asarray(efb_off_np.astype(np.int32))
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
            flat = hb.reshape(-1, 3)
            g = jnp.take(flat, _efb_idx, axis=0).reshape(f, B - 1, 3)
            g = g * _efb_valid[:, :, None]
            totals = jnp.sum(hb, axis=1)
            bin0 = jnp.take(totals, _efb_bundle, axis=0) - jnp.sum(g, axis=1)
            return jnp.concatenate([bin0[:, None, :], g], axis=1)

        def col_tables(feat):
            # per split feature: its bundle column, and what decode_col needs
            # of it (its offset inside the bundle, its bin count)
            return col_of_feat[feat], (off_of_feat[feat], num_bins[feat])

        def decode_col(colv, off, nbf):
            return jnp.where((colv >= off) & (colv < off + nbf - 1),
                             colv - off + 1, 0)
    else:
        def expand_hist(hb):
            return hb

        def col_tables(feat):
            return feat, ()

        def decode_col(colv):
            return colv

    # ---- combined row payload: (grad, hess, row_weight) packed as trailing
    # bin-typed columns so one row gather moves everything (see grower.py) --
    _gh_cols = 12 // bins.dtype.itemsize
    _gh_packed = jax.lax.bitcast_convert_type(
        jnp.stack([grad, hess, row_weight], axis=1), bins.dtype
    ).reshape(n, _gh_cols)
    comb = jnp.concatenate([bins, _gh_packed], axis=1)    # [N, NC + gh_cols]
    # [NC, N]: a split column is one contiguous row (the chip keeps ``bins``
    # feature-major, so the transposition is a bitcast there)
    bins_t = bins.T

    def _unpack_gh(combb):
        cap = combb.shape[0]
        raw = combb[:, n_cols:].reshape(cap, 3, _gh_cols // 3)
        return jax.lax.bitcast_convert_type(raw, jnp.float32)

    # --- shard-local feature metadata + mode-dispatched search ------------
    # Mirrors the sequential grower's learner dispatch (grower.py find /
    # _find_voting / _reduce_split_global = the reference's per-learner
    # FindBestSplitsFromHistograms + SyncUpGlobalBestSplit).
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
        contri_l = (lslice(feature_contri) if feature_contri is not None
                    else None)

    def reduce_hist(h):
        # data: full-histogram allreduce; feature/voting keep shard-local
        # stores (voting reduces only ELECTED slices inside the search)
        if mode != "data":
            return h
        with jax.named_scope("lgbm/collective/hist"):
            return psum_hist(h, axis)

    @jax.named_scope("lgbm/sum_repair")
    def totals_of(h):
        """[..., 3] (sum_g, sum_h, count) of the leaves whose stored pair
        histograms are ``h`` [..., n_cols, Bb, 6], the same on every shard."""
        t = hist_totals(h)
        if mode == "voting":        # rows are sharded and the store is local
            t = jax.lax.psum(t, axis)
        elif mode == "feature":     # columns are sharded: shard 0's first
            t = jax.lax.psum(jnp.where(dev == 0, t, 0.0), axis)
        return t

    # --- per-node RNG streams (feature_fraction_bynode, extra_trees) ------
    # The sequential grower keys both draws by the split-step index; the
    # frontier keys them by the expansion's split-record index s_idx (root =
    # step 0, children of record i = step i+1) — a deterministic, replay-
    # stable stream with the same structure (siblings share a draw, every
    # split event gets a fresh one), though not bit-identical to the
    # sequential grower's stream (the pop order differs, so no keying can
    # reproduce it without sequentializing).
    bynode = cfg.feature_fraction_bynode < 1.0
    _nb_r = None
    if cfg.extra_trees:
        _nb_r = num_bins_l if mode == "feature" else num_bins
        _nanb_r = nan_bins_l if mode == "feature" else nan_bins

    def node_mask_for(step):
        if not bynode:
            return feature_mask
        from .grower import node_feature_mask_for
        return node_feature_mask_for(key, step, feature_mask,
                                     cfg.feature_fraction_bynode)

    def rand_thr_for(step):
        if not cfg.extra_trees:
            return None
        from .grower import rand_thresholds_for
        return rand_thresholds_for(key, step, cfg.extra_seed, _nb_r, _nanb_r)

    # --- monotone-basic: output bounds pinch at the midpoint down the root
    # path (grower.py apply_split basic branch), which is per-leaf state the
    # frontier already carries — intermediate/advanced (cross-leaf
    # propagation) stay on the sequential grower (_frontier_eligible)
    use_mono = cfg.has_monotone
    use_pen = cfg.has_monotone and cfg.monotone_penalty > 0.0

    def mult_for(depth):
        if not use_pen:
            return None
        from .grower import monotone_gain_mult
        return monotone_gain_mult(depth, monotone, cfg.monotone_penalty)

    @jax.named_scope("lgbm/split_search")
    def find(hist_pair, sum_g, sum_h, count, fmask=None, rand=None,
             lo=NEG_INF, hi=POS_INF, mult=None):
        fmask = feature_mask if fmask is None else fmask
        hist_fb = expand_hist(fold_hist(hist_pair))
        if mode == "feature":
            from .grower import _reduce_split_global
            s = find_best_split(hist_fb, num_bins_l, default_bins_l,
                                nan_bins_l, is_cat_l, mono_l, sum_g, sum_h,
                                count, p, lslice(fmask),
                                output_lo=lo, output_hi=hi,
                                rand_threshold=rand,
                                sorted_cat=cfg.sorted_cat,
                                gain_mult=(lslice(mult) if mult is not None
                                           else None),
                                contri=contri_l)
            s = s._replace(feature=s.feature + f_start)
            return _reduce_split_global(s, axis)
        if mode == "voting":
            return _find_voting(hist_fb, sum_g, sum_h, count, fmask, rand,
                                lo, hi, mult)
        return find_best_split(hist_fb, num_bins, default_bins, nan_bins,
                               is_categorical, monotone, sum_g, sum_h, count,
                               p, fmask, output_lo=lo, output_hi=hi,
                               rand_threshold=rand,
                               sorted_cat=cfg.sorted_cat, gain_mult=mult,
                               contri=feature_contri)

    def _find_voting(hist, sum_g, sum_h, count, fmask, rand=None,
                     lo=NEG_INF, hi=POS_INF, mult=None):
        """Local top-k proposal -> global vote -> reduce only elected
        histograms (the election dataflow lives once in split.voting_elect,
        shared with the sequential grower)."""
        from .split import voting_elect
        hist_e, emask = voting_elect(
            hist, num_bins, nan_bins, is_categorical, monotone, sum_g,
            sum_h, count, p, fmask, axis, cfg.top_k, cfg.num_shards,
            output_lo=lo, output_hi=hi,
            sorted_cat=cfg.sorted_cat, gain_mult=mult,
            contri=feature_contri)
        return find_best_split(hist_e, num_bins, default_bins, nan_bins,
                               is_categorical, monotone, sum_g, sum_h, count,
                               p, emask, output_lo=lo, output_hi=hi,
                               rand_threshold=rand,
                               sorted_cat=cfg.sorted_cat, gain_mult=mult,
                               contri=feature_contri)

    # ---- degenerate: no usable features -> single-leaf tree ---------------
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
        return ((empty, jnp.zeros(n, jnp.int32))
                + ((jnp.array([0, 0, 0, 1, 0], jnp.int32),) if with_stats
                   else ())
                + ((hist_store,) if hist_store is not None else ()))

    # ---- root -------------------------------------------------------------
    with jax.named_scope("lgbm/root"):
        root_hist = reduce_hist(
            build_histogram(bins, grad, hess, row_weight, Bb,
                            method=cfg.hist_method,
                            chunk_rows=cfg.hist_chunk_rows,
                            variant=cfg.hist_variant))
        tot = totals_of(root_hist)
        root_split = find(root_hist, tot[0], tot[1], tot[2],
                          fmask=node_mask_for(0), rand=rand_thr_for(0),
                          mult=mult_for(0))

        # histogram blocks ladder: rungs over the per-round leaf-grouped
        # gather capacity (block-aligned); every rung a BR multiple
        cap_max = -(-(n // 2 + k * BR) // BR) * BR
        caps2: "list[int]" = []
        c = max(8 * BR, min(16384, cap_max))
        c = -(-c // BR) * BR
        while c < cap_max:
            caps2.append(c)
            c = -(-(c * 4) // BR) * BR
        caps2.append(cap_max)

        pend0 = _BestSplits.empty(LS, cw)
        pend0 = _batch_set(pend0, jnp.array([0]), _as_batch(root_split, 1),
                           jnp.array([True]))

        state = dict(
            row_slot=jnp.zeros(n, jnp.int32),     # every row's leaf slot
            leaf_nrows=jnp.zeros(LS, jnp.int32).at[0].set(n),   # raw, local
            leaf_depth=jnp.zeros(LS, jnp.int32),
            leaf_sum_g=jnp.zeros(LS, jnp.float32).at[0].set(tot[0]),
            leaf_weight=jnp.zeros(LS, jnp.float32).at[0].set(tot[1]),
            leaf_count=jnp.zeros(LS, jnp.float32).at[0].set(tot[2]),
            leaf_cghat=jnp.full(LS, POS_INF, jnp.float32),  # creator's g_hat
            leaf_cs=jnp.full(LS, -1, jnp.int32),            # creator split idx
            leaf_il=jnp.zeros(LS, bool),                    # was left child
            pend=pend0,
            pend_ghat=jnp.full(LS, NEG_INF, jnp.float32).at[0].set(
                jnp.minimum(root_split.gain, POS_INF)),
            hist=(jnp.zeros(hist_store_shape(cfg, n_cols), jnp.float32)
                  if hist_store is None else hist_store).at[0].set(root_hist),
            # split records
            sp_ghat=jnp.full(S, NEG_INF, jnp.float32),
            sp_parent=jnp.full(S, -1, jnp.int32),
            sp_is_left=jnp.zeros(S, bool),
            sp_feature=jnp.zeros(S, jnp.int32),
            sp_threshold=jnp.zeros(S, jnp.int32),
            sp_dleft=jnp.zeros(S, bool),
            sp_iscat=jnp.zeros(S, bool),
            sp_catbits=jnp.zeros((S, cw), jnp.int32),
            sp_gain=jnp.zeros(S, jnp.float32),
            sp_lout=jnp.zeros(S, jnp.float32),
            sp_rout=jnp.zeros(S, jnp.float32),
            sp_lweight=jnp.zeros(S, jnp.float32),
            sp_rweight=jnp.zeros(S, jnp.float32),
            sp_lcount=jnp.zeros(S, jnp.float32),
            sp_rcount=jnp.zeros(S, jnp.float32),
            sp_value=jnp.zeros(S, jnp.float32),   # split-leaf output
            sp_count=jnp.zeros(S, jnp.float32),   # split-leaf weighted count
            n_applied=jnp.int32(0),
            # counters (with_stats): rounds taken, rows of the leaves split
            n_rounds=jnp.int32(0),
            rows_sel_hi=jnp.int32(0), rows_sel_lo=jnp.int32(0),
        )
        if use_mono:
            # per-leaf monotone output bounds (basic mode: root-path state)
            state["leaf_lo"] = jnp.full(LS, NEG_INF, jnp.float32)
            state["leaf_hi"] = jnp.full(LS, POS_INF, jnp.float32)

    from .split import leaf_output

    # One round.  The loop runs under ``lgbm/frontier_round`` and each phase
    # of the body under its own scope below it, so a device trace can be read
    # by phase (obs.device_scopes()).
    def round_body(st):
        applied = st["n_applied"]
        with jax.named_scope("select"):
            # expansion priority: g_hat primary, RAW gain secondary.  Structural
            # g_hat ties (child gain > parent gain caps the child at the parent's
            # g_hat) are popped by the true process in raw-gain cascade order, so
            # expanding tie classes in raw order keeps the applied set a superset
            # of the true prefix without blowing the overshoot slack.
            sel = jnp.lexsort((-st["pend"].gain, -st["pend_ghat"]))[:k]
            ghat_sel = st["pend_ghat"][sel]
            i_ar = jnp.arange(k, dtype=jnp.int32)
            t_full = jax.lax.top_k(st["sp_ghat"], L - 1)[0][-1]
            # >= on the threshold: when a child's raw gain exceeds its parent's,
            # g_hat(child) == g_hat(parent) EXACTLY (structural tie), and such a
            # child can pop before an applied record with the same g_hat — it
            # must be expanded so the replay can consider it
            valid = ((ghat_sel > 0.0)
                     & (applied + i_ar < S)
                     & ((applied + i_ar < L - 1) | (ghat_sel >= t_full)))
            v = jnp.sum(valid.astype(jnp.int32))

            b = st["pend"]
            sel_feat = b.feature[sel]
            sel_thr = b.threshold[sel]
            sel_dleft = b.default_left[sel]
            sel_cbits = b.cat_bits[sel]                       # [k, CW]
            sel_iscat = is_categorical[sel_feat]
            sel_nanbin = nan_bins[sel_feat]
            sel_col, sel_decode = col_tables(sel_feat)
            sel_rows = st["leaf_nrows"][sel]
            sel_gain = b.gain[sel]
            sp_ghat_i = jnp.minimum(sel_gain, st["leaf_cghat"][sel])
            right_slot = applied + 1 + i_ar                   # leaf slot of right child
            s_idx = applied + i_ar                            # split record index
            # the weighted-count comparison is GLOBAL (identical on every shard),
            # so all shards histogram the same side (grower.py apply_split)
            left_smaller = b.lc[sel] <= b.rc[sel]

        # ---- [N]-pass, in row order: decide, count, group ------------------
        # A row carries its leaf slot, so everything it needs of its leaf's
        # split is spread by comparing that slot with the at most k selected
        # ones (_spread_by_slot), and its bin is at its own index in the
        # split column, a contiguous row of ``bins_t``.  Nothing is gathered
        # or scattered per row; the one data movement is the sort that groups
        # the smaller children's rows for the histograms.
        with jax.named_scope("partition"):
            row_slot = st["row_slot"]
            with jax.named_scope("decide"):
                in_slot = [(row_slot == sel[i]) & valid[i] for i in range(k)]
                act, (nb_p, thr_p, dleft_p, iscat_p, right_p,
                      *decode_p) = _spread_by_slot(
                    in_slot, (sel_nanbin, sel_thr, sel_dleft, sel_iscat,
                              right_slot, *sel_decode))
                if mode == "feature":
                    # columns are sharded (sel_col is the split feature's
                    # global index): the owner shard reads its local column
                    # and ONE [N] psum broadcasts it (rows are replicated, so
                    # every shard's row_slot is identical; grower.py
                    # partition_and_hist does the same per split — here it is
                    # once per ROUND)
                    owns = (sel_col >= f_start) & (sel_col < f_start + f)
                    colv = jax.lax.psum(_bin_of_rows(
                        bins_t, jnp.clip(sel_col - f_start, 0, f - 1),
                        [m & owns[i] for i, m in enumerate(in_slot)]), axis)
                else:
                    colv = decode_col(_bin_of_rows(bins_t, sel_col, in_slot),
                                      *decode_p)
                is_miss = (colv == nb_p) & (nb_p >= 0)
                # word min(colv >> 5, cw - 1) of the leaf's categorical bit set
                _, words_p = _spread_by_slot(
                    in_slot, [sel_cbits[:, w] for w in range(cw)])
                wsel = words_p[0]
                for w in range(1, cw):
                    wsel = jnp.where(colv >> 5 >= w, words_p[w], wsel)
                gl_cat = ((wsel >> (colv & 31)) & 1) > 0
                gl = jnp.where(iscat_p, gl_cat,
                               jnp.where(is_miss, dleft_p, colv <= thr_p))
            with jax.named_scope("scatter"):
                rows_small, small_n = _group_smaller_children(
                    in_slot, gl, left_smaller)
            with jax.named_scope("rank"):
                # [k] raw left, from the smaller child's count: no sum over
                # the rows, which would decide every row again
                nl_i = jnp.where(left_smaller, small_n, sel_rows - small_n)
                row_slot_new = jnp.where(act & ~gl, right_p, row_slot)

        # ---- leaf bookkeeping --------------------------------------------
        with jax.named_scope("bookkeeping"):
            def upd(arr, idx, val, pred):
                return arr.at[jnp.where(pred, idx, LS)].set(val, mode="drop")
            nr_i = sel_rows - nl_i
            rows_sel = jnp.sum(jnp.where(valid, sel_rows, 0))   # <= n
            depth_c = st["leaf_depth"][sel] + 1
            leaf_nrows = upd(upd(st["leaf_nrows"], sel, nl_i, valid),
                             right_slot, nr_i, valid)
            leaf_depth = upd(upd(st["leaf_depth"], sel, depth_c, valid),
                             right_slot, depth_c, valid)
            leaf_cghat = upd(upd(st["leaf_cghat"], sel, sp_ghat_i, valid),
                             right_slot, sp_ghat_i, valid)
            leaf_cs = upd(upd(st["leaf_cs"], sel, s_idx, valid),
                          right_slot, s_idx, valid)
            leaf_il = upd(upd(st["leaf_il"], sel, jnp.ones(k, bool), valid),
                          right_slot, jnp.zeros(k, bool), valid)

            extra_mono = {}
            if use_mono:
                # basic mode: pinch both children at the midpoint of the child
                # outputs (grower.py apply_split, reference BasicConstraint) —
                # depends only on the expansion's own path, so batching k
                # expansions cannot reorder it
                mono_sel = monotone[sel_feat]
                lo_p, hi_p = st["leaf_lo"][sel], st["leaf_hi"][sel]
                mid = (b.lout[sel] + b.rout[sel]) * 0.5
                l_lo = jnp.where(mono_sel < 0, jnp.maximum(lo_p, mid), lo_p)
                l_hi = jnp.where(mono_sel > 0, jnp.minimum(hi_p, mid), hi_p)
                r_lo = jnp.where(mono_sel > 0, jnp.maximum(lo_p, mid), lo_p)
                r_hi = jnp.where(mono_sel < 0, jnp.minimum(hi_p, mid), hi_p)
                extra_mono = dict(
                    leaf_lo=upd(upd(st["leaf_lo"], sel, l_lo, valid),
                                right_slot, r_lo, valid),
                    leaf_hi=upd(upd(st["leaf_hi"], sel, l_hi, valid),
                                right_slot, r_hi, valid))

            # ---- split records ------------------------------------------------
            def rec(arr, val):
                return arr.at[jnp.where(valid, s_idx, S)].set(val, mode="drop")
            sp_value_i = leaf_output(st["leaf_sum_g"][sel], st["leaf_weight"][sel],
                                     p, 0.0, st["leaf_count"][sel])
            recs = dict(
                sp_ghat=rec(st["sp_ghat"], sp_ghat_i),
                sp_parent=rec(st["sp_parent"], st["leaf_cs"][sel]),
                sp_is_left=rec(st["sp_is_left"], st["leaf_il"][sel]),
                sp_feature=rec(st["sp_feature"], sel_feat),
                sp_threshold=rec(st["sp_threshold"], sel_thr),
                sp_dleft=rec(st["sp_dleft"], sel_dleft),
                sp_iscat=rec(st["sp_iscat"], sel_iscat),
                sp_catbits=rec(st["sp_catbits"], sel_cbits),
                sp_gain=rec(st["sp_gain"], sel_gain),
                sp_lout=rec(st["sp_lout"], b.lout[sel]),
                sp_rout=rec(st["sp_rout"], b.rout[sel]),
                sp_value=rec(st["sp_value"], sp_value_i),
                sp_count=rec(st["sp_count"], st["leaf_count"][sel]),
            )

        # ---- batched smaller-child histograms -----------------------------
        with jax.named_scope("hist_gather"):
            small_beg = jnp.cumsum(small_n) - small_n    # in rows_small
            nblocks = jnp.maximum(-(-small_n // BR), 1)   # >=1: every slot inits
            blk_start = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                         jnp.cumsum(nblocks)])[:-1]
            nb_tot = blk_start[-1] + nblocks[-1]

        def mk_branch(C2):
            NB = C2 // BR

            def br(rows_arg):
                with jax.named_scope("hist_gather"):
                    blk = jnp.arange(NB, dtype=jnp.int32)
                    i_of_blk = jnp.clip(
                        unrolled_rank(blk_start, blk, strict=False) - 1, 0, k - 1)
                    q = jnp.arange(C2, dtype=jnp.int32)
                    qb = q // BR
                    i_of_q = i_of_blk[qb]
                    local = (qb - blk_start[i_of_q]) * BR + (q % BR)
                    okrow = (local < small_n[i_of_q]) & (qb < nb_tot)
                    row_pos = jnp.clip(small_beg[i_of_q] + local, 0, n - 1)
                    rid = jnp.take(rows_arg, row_pos)
                    combb = jnp.take(comb, jnp.where(okrow, rid, 0), axis=0)
                    ghb = _unpack_gh(combb)
                    m = jnp.where(okrow, ghb[:, 2], 0.0)
                with jax.named_scope("hist"):
                    return build_histogram_leaves(
                        combb, ghb[:, 0], ghb[:, 1], m, i_of_blk, k, Bb,
                        method=cfg.hist_method, block_rows=BR,
                        f_limit=n_cols,
                        variant=cfg.hist_variant)[:, :n_cols]
            return br

        with jax.named_scope("hist_gather"):
            idx = jnp.searchsorted(jnp.asarray(caps2, jnp.int32),
                                   nb_tot * BR)
        hist_small = jax.lax.switch(idx, [mk_branch(c) for c in caps2],
                                    rows_small)
        with jax.named_scope("hist"):
            hist_small = reduce_hist(hist_small)              # [k, NC, Bb, 6]

        # the larger child is what the smaller leaves of the parent, in
        # pairs; each child's totals are its own histogram's
        with jax.named_scope("lgbm/sum_repair"):
            # the k parents, one slice each: XLA on TPU reads a gather
            # ``hist[sel]`` by first copying the whole store in column chunks
            parent_hist = jnp.stack([
                jax.lax.dynamic_index_in_dim(st["hist"], sel[i], keepdims=False)
                for i in range(k)])
            large_hist = sub_hist(parent_hist, hist_small)
            ls4 = left_smaller[:, None, None, None]
            lhist = jnp.where(ls4, hist_small, large_hist)
            rhist = jnp.where(ls4, large_hist, hist_small)
        with jax.named_scope("hist_store"):
            # the children into the store, in place: [LS, NC, Bb, 6] is the
            # largest buffer of the program (3.5 GB at 2,000 columns)
            v4 = valid[:, None, None, None]
            hist = st["hist"].at[sel].set(jnp.where(v4, lhist, parent_hist))
            hist = hist.at[jnp.where(valid, right_slot, LS)].set(
                rhist, mode="drop")
        with jax.named_scope("lgbm/sum_repair"):
            hist2 = jnp.concatenate([lhist, rhist])       # [2k, NC, Bb, 6]
            tot2 = totals_of(hist2)                       # [2k, 3]
            g2, h2, c2 = tot2[:, 0], tot2[:, 1], tot2[:, 2]

        # ---- 2k child split searches (one vmapped program) ----------------
        if use_mono:
            # bounds per child, penalty factor per child depth; the step
            # keying rides along (node_mask_for/rand_thr_for ignore the
            # step when their feature is off)
            steps2 = jnp.concatenate([s_idx, s_idx]) + 1
            lo2 = jnp.concatenate([l_lo, r_lo])
            hi2 = jnp.concatenate([l_hi, r_hi])
            d2 = jnp.concatenate([depth_c, depth_c])
            s2 = jax.vmap(lambda hc, g_, h_, c_, st_, lo_, hi_, d_: find(
                hc, g_, h_, c_,
                fmask=node_mask_for(st_), rand=rand_thr_for(st_),
                lo=lo_, hi=hi_, mult=mult_for(d_)))(
                hist2, g2, h2, c2, steps2, lo2, hi2, d2)
        elif bynode or cfg.extra_trees:
            # children of the expansion recorded at s_idx draw their mask /
            # random thresholds from step s_idx+1 (both siblings share it,
            # like the sequential grower's per-step draw)
            steps2 = jnp.concatenate([s_idx, s_idx]) + 1
            s2 = jax.vmap(lambda hc, g_, h_, c_, st_: find(
                hc, g_, h_, c_,
                fmask=node_mask_for(st_), rand=rand_thr_for(st_)))(
                hist2, g2, h2, c2, steps2)
        else:
            s2 = jax.vmap(find)(hist2, g2, h2, c2)
        with jax.named_scope("bookkeeping"):
            depth_ok = (cfg.max_depth <= 0) | (depth_c < cfg.max_depth)
            dok2 = jnp.concatenate([depth_ok, depth_ok])
            s2 = s2._replace(gain=jnp.where(dok2, s2.gain, NEG_INF))
            sl = jax.tree.map(lambda a: a[:k], s2)
            sr = jax.tree.map(lambda a: a[k:], s2)
            pend = _batch_set(st["pend"], sel, sl, valid)
            pend = _batch_set(pend, jnp.where(valid, right_slot, LS), sr, valid)
            pend_ghat = upd(upd(st["pend_ghat"], sel,
                                jnp.minimum(sl.gain, sp_ghat_i), valid),
                            right_slot, jnp.minimum(sr.gain, sp_ghat_i), valid)
            # the children's sums, into their leaf slots and the split record
            (lh, rh), (lc, rc) = ((t[:k], t[k:]) for t in (h2, c2))
            leaf_sum_g = upd(upd(st["leaf_sum_g"], sel, g2[:k], valid),
                             right_slot, g2[k:], valid)
            leaf_weight = upd(upd(st["leaf_weight"], sel, lh, valid),
                              right_slot, rh, valid)
            leaf_count = upd(upd(st["leaf_count"], sel, lc, valid),
                             right_slot, rc, valid)
            recs.update(
                sp_lweight=rec(st["sp_lweight"], lh),
                sp_rweight=rec(st["sp_rweight"], rh),
                sp_lcount=rec(st["sp_lcount"], lc),
                sp_rcount=rec(st["sp_rcount"], rc))

        return dict(
            row_slot=row_slot_new, leaf_nrows=leaf_nrows,
            leaf_depth=leaf_depth, leaf_sum_g=leaf_sum_g,
            leaf_weight=leaf_weight, leaf_count=leaf_count,
            leaf_cghat=leaf_cghat, leaf_cs=leaf_cs, leaf_il=leaf_il,
            pend=pend, pend_ghat=pend_ghat, hist=hist,
            **extra_mono,
            **recs,
            n_applied=applied + v,
            n_rounds=st["n_rounds"] + 1,
            rows_sel_hi=st["rows_sel_hi"] + (rows_sel >> 20),
            rows_sel_lo=st["rows_sel_lo"] + (rows_sel & 0xFFFFF),
        )

    @jax.named_scope("select")
    def round_cond(st):
        applied = st["n_applied"]
        t_full = jax.lax.top_k(st["sp_ghat"], L - 1)[0][-1]
        mx = jnp.max(st["pend_ghat"])
        return ((mx > 0.0) & (applied < S)
                & ((applied < L - 1) | (mx >= t_full)))

    if L > 1:
        with jax.named_scope("lgbm/frontier_round"):
            state = jax.lax.while_loop(round_cond, round_body, state)

    # ---- exact best-first selection + numbering: tiny PQ replay -----------
    # The applied records are a superset of the true best-first prefix.  A
    # replay over ONLY leaf-slot argmaxes — the very operation the
    # sequential grower's loop performs, including its lowest-leaf-id
    # tie-break — recovers the exact split order and with it the reference
    # numbering (left child keeps the parent's leaf id, right child of the
    # j-th split is leaf j+1).  [L]-sized ops per step: ~L x 8 tiny ops
    # total, vs the full histogram+search pipeline the sequential loop
    # pays per step.
    with jax.named_scope("lgbm/finalize"):
        appl = jnp.arange(S, dtype=jnp.int32) < state["n_applied"]
        rec_ids = jnp.arange(S, dtype=jnp.int32)
        child_left = jnp.full(S, -1, jnp.int32).at[
            jnp.where(appl & (state["sp_parent"] >= 0) & state["sp_is_left"],
                      jnp.clip(state["sp_parent"], 0), S)].set(
            rec_ids, mode="drop")
        child_right = jnp.full(S, -1, jnp.int32).at[
            jnp.where(appl & (state["sp_parent"] >= 0) & ~state["sp_is_left"],
                      jnp.clip(state["sp_parent"], 0), S)].set(
            rec_ids, mode="drop")

        def gain_of(r):
            return jnp.where(r >= 0, state["sp_gain"][jnp.clip(r, 0)], NEG_INF)

        have_root = state["n_applied"] > 0      # record 0 is always the root split
        cur_rec0 = jnp.full(L, -1, jnp.int32).at[0].set(
            jnp.where(have_root, 0, -1))
        gains0 = jnp.full(L, NEG_INF, jnp.float32).at[0].set(
            gain_of(cur_rec0[0]))

        def replay_step(j, carry):
            cur_rec, gains, order, leaf_of_node, cnt = carry
            pop = jnp.argmax(gains).astype(jnp.int32)
            ok = gains[pop] > 0.0
            rec = cur_rec[pop]
            order = order.at[j].set(jnp.where(ok, rec, -1))
            leaf_of_node = leaf_of_node.at[j].set(jnp.where(ok, pop, -1))
            lc = child_left[jnp.clip(rec, 0)]
            rc = child_right[jnp.clip(rec, 0)]
            new_id = jnp.minimum(j + 1, L - 1)
            cur_rec = cur_rec.at[pop].set(jnp.where(ok, lc, cur_rec[pop]))
            cur_rec = cur_rec.at[new_id].set(
                jnp.where(ok, rc, cur_rec[new_id]))
            gains = gains.at[pop].set(jnp.where(ok, gain_of(lc), NEG_INF))
            gains = gains.at[new_id].set(
                jnp.where(ok, gain_of(rc), gains[new_id]))
            return cur_rec, gains, order, leaf_of_node, cnt + ok.astype(jnp.int32)

        _, _, order, leaf_of_node, nsel = jax.lax.fori_loop(
            0, L - 1, replay_step,
            (cur_rec0, gains0,
             jnp.full(L - 1, -1, jnp.int32), jnp.full(L - 1, -1, jnp.int32),
             jnp.int32(0)))

        node_on = order >= 0
        src = jnp.clip(order, 0)                                  # node j <- record
        leaf_id_of_node = jnp.maximum(leaf_of_node, 0)
        node_ids = jnp.arange(L - 1, dtype=jnp.int32)

        # children pointers: a selected child record overwrites the leaf default
        pos_of_rec = jnp.full(S, -1, jnp.int32).at[
            jnp.where(node_on, src, S)].set(node_ids, mode="drop")

        def child_ptr(crec, default_leaf):
            c = crec[src]                                          # child record
            cpos = pos_of_rec[jnp.clip(c, 0)]
            return jnp.where(node_on,
                             jnp.where((c >= 0) & (cpos >= 0), cpos,
                                       ~default_leaf),
                             -1)

        left_child = child_ptr(child_left, leaf_id_of_node)
        right_child = child_ptr(child_right, node_ids + 1)

        # leaf stats: node j writes its left/right child's final-leaf slot when
        # that child was not (selected-)split
        lleaf = node_on & (left_child < 0)
        rleaf = node_on & (right_child < 0)
        lids = jnp.clip(leaf_id_of_node, 0, L - 1)
        rids = jnp.clip(node_ids + 1, 0, L - 1)

        def leafset(init, vl, vr):
            a = jnp.zeros(L, init.dtype) + init
            a = a.at[jnp.where(lleaf, lids, L)].set(vl, mode="drop")
            a = a.at[jnp.where(rleaf, rids, L)].set(vr, mode="drop")
            return a

        no_split = nsel == 0
        leaf_value = leafset(jnp.zeros(L, jnp.float32),
                             state["sp_lout"][src], state["sp_rout"][src])
        leaf_count = leafset(jnp.zeros(L, jnp.float32),
                             state["sp_lcount"][src], state["sp_rcount"][src])
        leaf_count = leaf_count.at[0].set(
            jnp.where(no_split, tot[2], leaf_count[0]))
        leaf_weight = leafset(jnp.zeros(L, jnp.float32),
                              state["sp_lweight"][src], state["sp_rweight"][src])
        leaf_weight = leaf_weight.at[0].set(
            jnp.where(no_split, tot[1], leaf_weight[0]))

        tree = TreeArrays(
            split_feature=jnp.where(node_on, state["sp_feature"][src], -1),
            threshold=jnp.where(node_on, state["sp_threshold"][src], 0),
            default_left=node_on & state["sp_dleft"][src],
            is_cat_split=node_on & state["sp_iscat"][src],
            cat_bits=jnp.where(node_on[:, None], state["sp_catbits"][src], 0),
            split_gain=jnp.where(node_on, state["sp_gain"][src], 0.0),
            left_child=left_child,
            right_child=right_child,
            leaf_value=leaf_value,
            leaf_count=leaf_count,
            leaf_weight=leaf_weight,
            internal_value=jnp.where(node_on, state["sp_value"][src], 0.0),
            internal_count=jnp.where(node_on, state["sp_count"][src], 0.0),
            num_leaves=(nsel + 1).astype(jnp.int32),
        )

        # ---- node assignment: a row's leaf from its last slot --------------
        node_assign = _leaf_of_rows(
            state["row_slot"],
            _leaf_of_slots(state["leaf_cs"], state["leaf_il"],
                           state["sp_parent"], state["sp_is_left"],
                           pos_of_rec, leaf_id_of_node))
    out = (tree, node_assign)
    if with_stats:
        fb = (leaves_feature_block(n_cols, Bb, k, cfg.hist_variant)
              if cfg.hist_method == "pallas" else 0)
        stats = jnp.stack([state["n_rounds"], state["rows_sel_hi"],
                           state["rows_sel_lo"], jnp.int32(1),
                           jnp.int32(-(-n_cols // fb) if fb else 0)])
        if mode in ("data", "voting"):
            # rows are sharded: the leaves' rows are local counts
            stats = stats.at[1:3].set(jax.lax.psum(stats[1:3], axis))
        out += (stats,)
    return out + ((state["hist"],) if hist_store is not None else ())


def _spread_by_slot(in_slot, tables):
    """Per-row values of per-slot tables, by the rows' slot masks.

    ``in_slot[i]`` is a bool ``[n]``: the rows that sit in the ``i``-th
    selected leaf slot (all false for a slot that is not valid); a row is in
    at most one.  Returns ``(act, values)``: ``act[r]`` says that some slot
    holds row ``r``, and ``values[j][r]`` is ``tables[j][i]`` of that slot
    (zero where there is none).  The loop over the ``k`` slots is unrolled
    into selects that XLA fuses into elementwise work over the rows: no
    per-row gather, no ``[N, k]`` intermediate.
    """
    shape = in_slot[0].shape
    act = jnp.zeros(shape, bool)
    values = [jnp.zeros(shape, t.dtype) for t in tables]
    for i, in_i in enumerate(in_slot):
        act = act | in_i
        values = [jnp.where(in_i, t[i], v) for t, v in zip(tables, values)]
    return act, values


def _bin_of_rows(bins_t, cols, in_slot):
    """int32 ``[n]``: row ``r``'s bin in column ``cols[i]`` of the slot ``i``
    whose mask holds it, 0 for a row in no slot.  ``bins_t`` is
    ``[n_cols, n]``, so a slot's column is one contiguous row of it, read at
    the rows' own indices."""
    colv = jnp.zeros(bins_t.shape[1:], bins_t.dtype)
    for i, in_i in enumerate(in_slot):
        col_i = jax.lax.dynamic_index_in_dim(bins_t, cols[i], 0, keepdims=False)
        colv = jnp.where(in_i, col_i, colv)
    return colv.astype(jnp.int32)


def _group_smaller_children(in_slot, go_left, left_smaller):
    """Row ids with the rows of the round's smaller children in front.

    The rows of slot 0's smaller child come first, then slot 1's, and so on,
    ascending inside each child: the order a stable partition of ascending
    ids leaves them in.  Behind them, every other row.  One sort on
    ``(child, row id)``: the pair is unique, so the result is determined.
    Returns the ids and ``int32[k]``, the rows of each smaller child, read
    off the sorted keys (slot ``i``'s start in the ids is the sum before it).
    """
    k = len(in_slot)
    n = go_left.shape[0]
    child = jnp.full(n, k, jnp.int32)
    for i, in_i in enumerate(in_slot):
        child = jnp.where(in_i & (go_left == left_smaller[i]), i, child)
    child, rows = jax.lax.sort((child, jnp.arange(n, dtype=jnp.int32)),
                               num_keys=2)
    # counted on the sorted keys, which no fusion can compute again
    counts = jnp.sum(child[None, :] == jnp.arange(k, dtype=jnp.int32)[:, None],
                     axis=1, dtype=jnp.int32)
    return rows, counts


def _leaf_of_slots(leaf_cs, leaf_il, sp_parent, sp_is_left, pos_of_rec,
                   leaf_id_of_node):
    """int32 ``[LS]``: the final tree's leaf of the rows in each leaf slot.

    A slot was made by split record ``leaf_cs`` as its left (``leaf_il``) or
    right child.  From there, up ``sp_parent``/``sp_is_left`` to the first
    record the replay selected as a node (``pos_of_rec >= 0``): records
    below it were applied beyond the budget, and their rows stay in that
    node's child.  The left child keeps the leaf id the node split
    (``leaf_id_of_node``), the right child of node ``j`` is leaf ``j + 1``;
    a slot under no selected record is leaf 0.
    """
    def dropped(cs):
        return (cs >= 0) & (pos_of_rec[jnp.clip(cs, 0)] < 0)

    def up(c):
        cs, il = c
        r = jnp.clip(cs, 0)
        d = dropped(cs)
        return jnp.where(d, sp_parent[r], cs), jnp.where(d, sp_is_left[r], il)

    cs, il = jax.lax.while_loop(lambda c: jnp.any(dropped(c[0])), up,
                                (leaf_cs, leaf_il))
    node = pos_of_rec[jnp.clip(cs, 0)]
    return jnp.where(cs >= 0,
                     jnp.where(il, leaf_id_of_node[jnp.clip(node, 0)], node + 1),
                     0)


def _leaf_of_rows(row_slot, leaf_of_slot):
    """``leaf_of_slot[row_slot]`` as selects over the slots (a table of a few
    hundred entries is never gathered per row)."""
    out = jnp.zeros(row_slot.shape, jnp.int32)
    for s in range(leaf_of_slot.shape[0]):
        out = jnp.where(row_slot == s, leaf_of_slot[s], out)
    return out


def _as_batch(s: SplitResult, m: int) -> SplitResult:
    """Broadcast a scalar SplitResult to a [m]-batched one."""
    def bc(x):
        x = jnp.asarray(x)
        return jnp.broadcast_to(x, (m,) + x.shape)
    return SplitResult(*[bc(c) for c in s])


def _batch_set(best, idx, s: SplitResult, pred):
    """Scatter a [m]-batched SplitResult into per-leaf _BestSplits slots
    ``idx``, predicated by ``pred`` (dropped via out-of-range index)."""
    from .grower import _BestSplits
    n_slots = best.gain.shape[0]
    tgt = jnp.where(pred, idx, n_slots)

    def u(arr, val):
        return arr.at[tgt].set(val, mode="drop")
    return _BestSplits(
        gain=u(best.gain, s.gain),
        feature=u(best.feature, s.feature),
        threshold=u(best.threshold, s.threshold),
        default_left=u(best.default_left, s.default_left),
        lg=u(best.lg, s.left_sum_g), lh=u(best.lh, s.left_sum_h),
        lc=u(best.lc, s.left_count),
        rg=u(best.rg, s.right_sum_g), rh=u(best.rh, s.right_sum_h),
        rc=u(best.rc, s.right_count),
        lout=u(best.lout, s.left_output), rout=u(best.rout, s.right_output),
        cat_bits=u(best.cat_bits, s.cat_bits))
