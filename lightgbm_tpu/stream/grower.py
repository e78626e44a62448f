"""Streaming tree growth: the serial grower's semantics over host blocks.

One tree is grown with EXACTLY the structural semantics of the in-HBM
serial grower (``ops/grower.grow_tree``): best-first expansion of the
max-gain leaf, smaller-child histogram + sibling subtraction, left child
keeps the parent's leaf id, per-node feature sampling / extra-trees
thresholds keyed by the same split-step stream, basic monotone pinching —
so the streamed model is the same tree, verified structurally by
tests/test_stream.py.  What changes is WHERE the data lives:

- bins stay in host RAM (``HostBinMatrix``); each histogram pass streams
  row blocks through the ``RowBlockPipeline`` (H2D of block k+1 behind the
  pass on block k);
- per-leaf histograms accumulate block-wise into the same ``[F, B, 6]``
  pairs ``ops/histogram.build_histogram`` produces (a float32
  sum and what it rounds away), siblings are subtracted in pairs and a
  leaf's totals are its own histogram's (``hist_totals``), as in the
  in-memory growers, so the split search (``ops/split.find_best_split``)
  is byte-for-byte the shared one and sees the same contract;
- leaf membership is a per-shard host ``leaf_vec`` int32 vector updated
  incrementally after each split (no device-resident permutation), and a
  per-(block, leaf) row-count table lets later passes SKIP blocks that
  hold no rows of the splitting leaf — deep-tree passes shrink toward the
  touched blocks only;
- the split loop itself runs on the host (the stream is host-paced
  anyway); each split costs one device sync to read the two children's
  candidate splits.

Multi-shard: ``shards`` may hold several host matrices (the data-parallel
row partition).  Histogram accumulation sums over all local shards'
blocks, then ``cross_reduce`` (optional) joins processes — the streaming
analog of ``DataParallelTreeLearner``'s histogram allreduce; split
DECISIONS are taken on the reduced histograms, so every rank applies the
identical split to its local rows.

Float caveat (shared with every sharded learner, see
tests/test_parallel.py): block/shard summation order differs from the
single-pass in-HBM kernels in final ulps, so split GAINS match to ~1e-5
relative and genuinely near-tied splits could in principle flip; split
features/thresholds/structure are asserted exact on tie-free data.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, List, NamedTuple, Optional, Sequence

import numpy as np

from ..ops.grower import (GrowerConfig, TreeArrays, monotone_gain_mult,
                          node_feature_mask_for, rand_thresholds_for)
from ..ops.histogram import (accumulate_histogram, fold_hist, hist_totals,
                             sub_hist)
from ..ops.split import (NEG_INF, bitset_contains, cat_words,
                         find_best_split)
from ..utils.log import LightGBMError, check
from .host_matrix import HostBinMatrix
from .pipeline import PipelineStats, RowBlockPipeline


class StreamShard(NamedTuple):
    """One host-resident row partition (a rank's local rows)."""
    matrix: HostBinMatrix
    pipeline: RowBlockPipeline


def make_shards(matrices: Sequence[HostBinMatrix], prefetch: int,
                stats: Optional[PipelineStats] = None) -> List[StreamShard]:
    stats = stats if stats is not None else PipelineStats()
    return [StreamShard(m, RowBlockPipeline(m, prefetch, stats))
            for m in matrices]


class StreamTreeGrower:
    """Grows trees from host-resident bin shards.

    Args:
      shards: local row partitions (one for single-host training).
      meta: numpy per-feature metadata — num_bins, default_bins, nan_bins,
        is_categorical, monotone (the ``Dataset.device_meta()`` fields).
      cfg: the shared ``GrowerConfig`` (serial semantics; parallel-mode
        fields are ignored — cross-rank joins ride ``cross_reduce``).
      cross_reduce: optional host-level reduction joining processes'
        histogram/total partials (data-parallel streaming).  Takes and
        returns a numpy array.
    """

    def __init__(self, shards: Sequence[StreamShard], meta: dict,
                 cfg: GrowerConfig,
                 cross_reduce: Optional[Callable] = None) -> None:
        import jax
        import jax.numpy as jnp

        check(len(shards) >= 1, "StreamTreeGrower needs >= 1 shard")
        widths = {s.matrix.num_cols for s in shards}
        check(len(widths) == 1, "stream shards must share the column width")
        self.shards = list(shards)
        self.cfg = cfg
        self.cross_reduce = cross_reduce
        self._f = int(widths.pop())
        self._B = cfg.max_bin
        self._cw = cat_words(self._B)
        self._L = cfg.num_leaves
        if cfg.bundle_bins:
            raise LightGBMError(
                "streaming training does not support EFB bundle columns; "
                "the Dataset disables bundling when a stream budget is "
                "configured")

        self._meta_host = {k: np.asarray(v) for k, v in meta.items()}
        self._meta_dev = {k: jnp.asarray(v)
                          for k, v in self._meta_host.items()}
        # per-(shard, block, leaf) row counts: blocks with zero rows of the
        # splitting leaf are skipped entirely (never transferred)
        self._counts = [np.zeros((s.matrix.num_blocks, self._L), np.int64)
                        for s in self.shards]
        # per-shard leaf membership, updated incrementally per split
        self._leaf_vecs = [np.zeros(s.matrix.num_data, np.int32)
                           for s in self.shards]
        # phase histograms (docs/OBSERVABILITY.md): the streamed loop is
        # host-paced, so these wall-clock spans are genuine per-phase cost
        # (unlike the fused in-HBM growers, which are one compiled program)
        from ..obs import metrics as _obs_metrics
        self._m_hist = _obs_metrics.histogram("stream.hist_seconds")
        self._m_partition = _obs_metrics.histogram("stream.partition_seconds")
        self._m_split = _obs_metrics.histogram("stream.split_seconds")
        self._build_jits()

    # ------------------------------------------------------------------
    def _build_jits(self) -> None:
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        md = self._meta_dev
        B = self._B
        p = cfg.split

        def hist_accum(acc, bins_blk, g, h, m):
            return accumulate_histogram(acc, bins_blk, g, h, m, B,
                                        method=cfg.hist_method,
                                        chunk_rows=cfg.hist_chunk_rows,
                                        variant=cfg.hist_variant)

        self._root_pass = jax.jit(hist_accum)

        @jax.jit
        def split_pass(hist_acc, bins_blk, leafv, g, h, rw, rows, leaf,
                       new_id, feat, thr, dleft, cbits, left_smaller):
            """Decide + repartition one block of the splitting leaf and
            accumulate the smaller child's histogram — the streamed fusion
            of the serial grower's partition_and_hist."""
            col = jnp.take(bins_blk, feat, axis=1).astype(jnp.int32)
            f_is_cat = md["is_categorical"][feat]
            nan_b = md["nan_bins"][feat]
            is_miss = (col == nan_b) & (nan_b >= 0)
            goes_left = jnp.where(f_is_cat, bitset_contains(cbits, col),
                                  jnp.where(is_miss, dleft, col <= thr))
            valid = jnp.arange(bins_blk.shape[0], dtype=jnp.int32) < rows
            in_leaf = (leafv == leaf) & valid
            new_vec = jnp.where(in_leaf & ~goes_left, new_id, leafv)
            small_mask = jnp.where(in_leaf & (goes_left == left_smaller),
                                   rw, 0.0)
            nl_blk = jnp.sum((in_leaf & goes_left).astype(jnp.int32))
            nin_blk = jnp.sum(in_leaf.astype(jnp.int32))
            return (hist_accum(hist_acc, bins_blk, g, h, small_mask),
                    new_vec, nl_blk, nin_blk)
        self._split_pass = split_pass

        use_pen = cfg.has_monotone and cfg.monotone_penalty > 0.0

        def find_inner(hist, sum_g, sum_h, count, fmask, key, step, depth,
                       lo, hi):
            if cfg.feature_fraction_bynode < 1.0:
                fmask = node_feature_mask_for(key, step, fmask,
                                              cfg.feature_fraction_bynode)
            rand = None
            if cfg.extra_trees:
                rand = rand_thresholds_for(key, step, cfg.extra_seed,
                                           md["num_bins"], md["nan_bins"])
            mult = None
            if use_pen:
                mult = monotone_gain_mult(depth, md["monotone"],
                                          cfg.monotone_penalty)
            return find_best_split(
                fold_hist(hist), md["num_bins"], md["default_bins"],
                md["nan_bins"],
                md["is_categorical"], md["monotone"], sum_g, sum_h, count,
                p, fmask, 0.0, lo, hi, rand_threshold=rand,
                sorted_cat=cfg.sorted_cat, gain_mult=mult)

        @jax.jit
        def root_find(hist, fmask, key):
            tot = hist_totals(hist)
            return tot, find_inner(hist, tot[0], tot[1], tot[2], fmask, key,
                                   jnp.int32(0), jnp.int32(0),
                                   jnp.float32(NEG_INF), jnp.float32(-NEG_INF))
        self._root_find = root_find

        # donate the [L, F, B, 6] store (the largest device resident) so
        # the functional .at[].set updates alias in place instead of
        # transiently doubling it every split; CPU doesn't implement
        # donation and would warn per call, so only donate off-CPU
        _donate = (0,) if jax.default_backend() != "cpu" else ()

        @functools.partial(jax.jit, donate_argnums=_donate)
        def child_step(store, small_hist, leaf, new_id, left_smaller,
                       lo2, hi2, step, depth, fmask, key):
            """Histogram subtraction + both children's split searches in one
            program (one device sync per split reads the pair).

            lo2/hi2: [2] bounds.  Returns the store, the two searches and
            ``sums2`` [2, 3], each child's (sum_g, sum_h, count) from its
            own histogram.
            """
            large = sub_hist(store[leaf], small_hist)
            lhist = jnp.where(left_smaller, small_hist, large)
            rhist = jnp.where(left_smaller, large, small_hist)
            store = store.at[leaf].set(lhist).at[new_id].set(rhist)
            hist2 = jnp.stack([lhist, rhist])
            sums2 = hist_totals(hist2)
            s2 = jax.vmap(
                lambda hc, s_, lo_, hi_: find_inner(
                    hc, s_[0], s_[1], s_[2], fmask, key, step, depth,
                    lo_, hi_))(hist2, sums2, lo2, hi2)
            return store, s2, sums2
        self._child_step = child_step

    # ------------------------------------------------------------------
    def _reduce(self, arr):
        out = np.asarray(arr, np.float32)
        if self.cross_reduce is not None:
            out = np.asarray(self.cross_reduce(out), np.float32)
        return out

    def _accumulate_root(self, g, h, rw):
        """Root histogram (a pair) over every shard's blocks."""
        import jax.numpy as jnp
        hist = jnp.zeros((self._f, self._B, 6), jnp.float32)
        for si, sh in enumerate(self.shards):
            off = self._shard_offsets[si]
            extras = {"g": g[off:off + sh.matrix.num_data],
                      "h": h[off:off + sh.matrix.num_data],
                      "rw": rw[off:off + sh.matrix.num_data]}
            for blk in sh.pipeline.blocks(extras):
                hist = self._root_pass(hist, blk.bins, blk.extras["g"],
                                       blk.extras["h"], blk.extras["rw"])
            self._counts[si][:, :] = 0
            for b in range(sh.matrix.num_blocks):
                self._counts[si][b, 0] = sh.matrix.block_rows_actual(b)
        return self._reduce(hist)

    def _accumulate_split(self, si_extras, leaf, new_id, feat, thr, dleft,
                          cbits, left_smaller):
        """One streamed pass applying the chosen split: updates every
        shard's leaf_vec + count table, returns the smaller child's
        (locally accumulated) histogram."""
        import jax.numpy as jnp
        hist = jnp.zeros((self._f, self._B, 6), jnp.float32)
        cbits_dev = jnp.asarray(cbits)
        for si, sh in enumerate(self.shards):
            touched = np.nonzero(self._counts[si][:, leaf] > 0)[0]
            extras = dict(si_extras[si])
            extras["leafv"] = self._leaf_vecs[si]
            for blk in sh.pipeline.blocks(extras, only=touched):
                hist, new_vec, nl, nin = self._split_pass(
                    hist, blk.bins, blk.extras["leafv"], blk.extras["g"],
                    blk.extras["h"], blk.extras["rw"], np.int32(blk.rows),
                    np.int32(leaf), np.int32(new_id), np.int32(feat),
                    np.int32(thr), np.bool_(dleft), cbits_dev,
                    np.bool_(left_smaller))
                self._leaf_vecs[si][blk.start:blk.start + blk.rows] = \
                    np.asarray(new_vec)[:blk.rows]
                nl = int(nl)
                self._counts[si][blk.index, leaf] = nl
                self._counts[si][blk.index, new_id] = int(nin) - nl
        return hist

    # ------------------------------------------------------------------
    def grow(self, g: np.ndarray, h: np.ndarray, rw: np.ndarray,
             feature_mask, key):
        """Grow one tree from host gradients; returns
        ``(TreeArrays-of-numpy, node_assign[num_data] int32)``.

        ``g``/``h``/``rw`` are host float32 vectors over the concatenated
        shard rows (shard 0's rows first).
        """
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        L, cw, f = self._L, self._cw, self._f
        p = cfg.split
        self._shard_offsets = np.concatenate(
            [[0], np.cumsum([s.matrix.num_data for s in self.shards])]
        ).astype(np.int64)
        n_local = int(self._shard_offsets[-1])
        g = np.ascontiguousarray(np.asarray(g, np.float32))
        h = np.ascontiguousarray(np.asarray(h, np.float32))
        rw = np.ascontiguousarray(np.asarray(rw, np.float32))
        for vec in self._leaf_vecs:
            vec[:] = 0

        # ---- host-side tree state (mirrors grow_tree's state dict) -------
        best = dict(
            gain=np.full(L, NEG_INF, np.float32),
            feature=np.zeros(L, np.int32), threshold=np.zeros(L, np.int32),
            default_left=np.zeros(L, bool),
            lg=np.zeros(L, np.float32), lh=np.zeros(L, np.float32),
            lc=np.zeros(L, np.float32),
            rg=np.zeros(L, np.float32), rh=np.zeros(L, np.float32),
            rc=np.zeros(L, np.float32),
            lout=np.zeros(L, np.float32), rout=np.zeros(L, np.float32),
            cat_bits=np.zeros((L, cw), np.int32))
        leaf_depth = np.zeros(L, np.int32)
        leaf_value = np.zeros(L, np.float32)
        leaf_count = np.zeros(L, np.float32)
        leaf_weight = np.zeros(L, np.float32)
        leaf_sum_g = np.zeros(L, np.float32)
        leaf_lo = np.full(L, NEG_INF, np.float32)
        leaf_hi = np.full(L, -NEG_INF, np.float32)
        leaf_parent = np.full(L, -1, np.int32)
        leaf_is_left = np.zeros(L, bool)
        node_feature = np.full(L - 1, -1, np.int32)
        node_threshold = np.zeros(L - 1, np.int32)
        node_default_left = np.zeros(L - 1, bool)
        node_is_cat = np.zeros(L - 1, bool)
        node_cat_bits = np.zeros((L - 1, cw), np.int32)
        node_gain = np.zeros(L - 1, np.float32)
        node_value = np.zeros(L - 1, np.float32)
        node_count = np.zeros(L - 1, np.float32)
        left_child = np.full(L - 1, -1, np.int32)
        right_child = np.full(L - 1, -1, np.int32)

        def assemble(num_leaves: int):
            return TreeArrays(
                split_feature=node_feature, threshold=node_threshold,
                default_left=node_default_left, is_cat_split=node_is_cat,
                cat_bits=node_cat_bits, split_gain=node_gain,
                left_child=left_child, right_child=right_child,
                leaf_value=leaf_value, leaf_count=leaf_count,
                leaf_weight=leaf_weight, internal_value=node_value,
                internal_count=node_count,
                num_leaves=np.int32(num_leaves))

        node_assign = np.concatenate(self._leaf_vecs) if n_local else \
            np.zeros(0, np.int32)

        # ---- degenerate: no usable features -> single-leaf tree ----------
        if f == 0:
            tot = self._reduce(np.asarray(
                [np.sum(g * rw), np.sum(h * rw), np.sum(rw)], np.float32))
            leaf_count[0], leaf_weight[0] = tot[2], tot[1]
            return assemble(1), node_assign

        fmask_dev = jnp.asarray(np.asarray(feature_mask, np.float32))

        # ---- root --------------------------------------------------------
        t0 = time.perf_counter()
        root_hist = self._accumulate_root(g, h, rw)
        self._m_hist.observe(time.perf_counter() - t0)
        store = jnp.zeros((L, f, self._B, 6), jnp.float32
                          ).at[0].set(jnp.asarray(root_hist))
        tot, s0 = jax.device_get(self._root_find(jnp.asarray(root_hist),
                                                 fmask_dev, key))
        leaf_count[0], leaf_weight[0], leaf_sum_g[0] = tot[2], tot[1], tot[0]
        _set_best(best, 0, s0)

        si_extras = []
        for si, sh in enumerate(self.shards):
            off = self._shard_offsets[si]
            end = off + sh.matrix.num_data
            si_extras.append({"g": g[off:end], "h": h[off:end],
                              "rw": rw[off:end]})

        # ---- best-first growth (grow_tree's while loop, host-paced) ------
        num_leaves = 1
        while num_leaves < L:
            active = best["gain"][:num_leaves]
            leaf = int(np.argmax(active))
            gain = float(active[leaf])
            if not gain > 0.0:
                break
            j = num_leaves - 1                     # node slot of this split
            new_id = num_leaves
            feat = int(best["feature"][leaf])
            thr = int(best["threshold"][leaf])
            dleft = bool(best["default_left"][leaf])
            f_is_cat = bool(self._meta_host["is_categorical"][feat])
            cbits = best["cat_bits"][leaf]
            left_smaller = bool(best["lc"][leaf] <= best["rc"][leaf])

            # --- node arrays + parent linkage (scatter_claims, host form)
            node_feature[j] = feat
            node_threshold[j] = thr
            node_default_left[j] = dleft
            node_is_cat[j] = f_is_cat
            node_cat_bits[j] = cbits
            node_gain[j] = gain
            node_value[j] = _leaf_output_np(
                leaf_sum_g[leaf], leaf_weight[leaf], leaf_count[leaf], p)
            node_count[j] = leaf_count[leaf]
            par = leaf_parent[leaf]
            if par >= 0:
                if leaf_is_left[leaf]:
                    left_child[par] = j
                else:
                    right_child[par] = j
            left_child[j] = ~leaf
            right_child[j] = ~new_id

            # --- streamed partition + smaller-child histogram -------------
            t0 = time.perf_counter()
            small_local = self._accumulate_split(
                si_extras, leaf, new_id, feat, thr, dleft, cbits,
                left_smaller)
            small_hist = jnp.asarray(self._reduce(small_local))
            self._m_partition.observe(time.perf_counter() - t0)

            # --- child bookkeeping (apply_split, host form) ---------------
            depth = leaf_depth[leaf] + 1
            leaf_depth[leaf] = leaf_depth[new_id] = depth
            leaf_value[leaf] = best["lout"][leaf]
            leaf_value[new_id] = best["rout"][leaf]
            leaf_parent[leaf] = leaf_parent[new_id] = j
            leaf_is_left[leaf], leaf_is_left[new_id] = True, False

            # basic monotone: pinch children at the midpoint (f32 math
            # matches the device op bit-for-bit)
            lo, hi = leaf_lo[leaf], leaf_hi[leaf]
            if cfg.has_monotone:
                mono = int(self._meta_host["monotone"][feat])
                mid = np.float32(
                    (best["lout"][leaf] + best["rout"][leaf])
                    * np.float32(0.5))
                l_lo = max(lo, mid) if mono < 0 else lo
                l_hi = min(hi, mid) if mono > 0 else hi
                r_lo = max(lo, mid) if mono > 0 else lo
                r_hi = min(hi, mid) if mono < 0 else hi
            else:
                l_lo = r_lo = lo
                l_hi = r_hi = hi
            leaf_lo[leaf], leaf_hi[leaf] = l_lo, l_hi
            leaf_lo[new_id], leaf_hi[new_id] = r_lo, r_hi

            # --- both children's next best splits (one device sync) -------
            t0 = time.perf_counter()
            store, s2, sums2 = self._child_step(
                store, small_hist, np.int32(leaf), np.int32(new_id),
                np.bool_(left_smaller),
                jnp.asarray(np.asarray([l_lo, r_lo], np.float32)),
                jnp.asarray(np.asarray([l_hi, r_hi], np.float32)),
                np.int32(j + 1), np.int32(depth), fmask_dev, key)
            s2, (lsums, rsums) = jax.device_get((s2, sums2))
            leaf_sum_g[leaf], leaf_weight[leaf], leaf_count[leaf] = lsums
            leaf_sum_g[new_id], leaf_weight[new_id], leaf_count[new_id] = \
                rsums
            self._m_split.observe(time.perf_counter() - t0)
            depth_ok = cfg.max_depth <= 0 or depth < cfg.max_depth
            sl = jax.tree.map(lambda a: a[0], s2)
            sr = jax.tree.map(lambda a: a[1], s2)
            if not depth_ok:
                sl = sl._replace(gain=np.float32(NEG_INF))
                sr = sr._replace(gain=np.float32(NEG_INF))
            _set_best(best, leaf, sl)
            _set_best(best, new_id, sr)
            num_leaves += 1

        node_assign = (np.concatenate(self._leaf_vecs) if n_local
                       else node_assign)
        return assemble(num_leaves), node_assign


def _leaf_output_np(sum_g, sum_h, count, p) -> np.float32:
    """Host float32 replica of ``ops.split.leaf_output`` (unbounded,
    parent_output=0) for the per-split node_value — a device call here
    would add one sync per split to the host-paced loop.  Same IEEE f32
    ops as the device version, so model-text internal_value matches."""
    g = np.float32(sum_g)
    h = np.float32(sum_h)
    thr = np.float32(np.sign(g)) * np.maximum(
        np.abs(g) - np.float32(p.lambda_l1), np.float32(0.0))
    raw = -thr / (h + np.float32(p.lambda_l2) + np.float32(1e-35))
    if p.max_delta_step > 0:
        raw = np.clip(raw, np.float32(-p.max_delta_step),
                      np.float32(p.max_delta_step))
    if p.path_smooth > 0:
        c = np.float32(count)
        smooth = c / (c + np.float32(p.path_smooth))
        raw = raw * smooth          # parent_output = 0 at the split leaf
    return np.float32(raw)


def _set_best(best: dict, i: int, s) -> None:
    """Record a SplitResult (host pytree) as leaf ``i``'s pending split."""
    best["gain"][i] = s.gain
    best["feature"][i] = s.feature
    best["threshold"][i] = s.threshold
    best["default_left"][i] = s.default_left
    best["lg"][i] = s.left_sum_g
    best["lh"][i] = s.left_sum_h
    best["lc"][i] = s.left_count
    best["rg"][i] = s.right_sum_g
    best["rh"][i] = s.right_sum_h
    best["rc"][i] = s.right_count
    best["lout"][i] = s.left_output
    best["rout"][i] = s.right_output
    best["cat_bits"][i] = s.cat_bits
