"""GOSS: Gradient-based One-Side Sampling (reference ``src/boosting/goss.hpp``).

Keeps the top ``top_rate`` fraction of rows by |g·h| and a random
``other_rate`` fraction of the rest, scaling the sampled rows' gradients and
hessians by ``(1-top_rate)/other_rate`` (``goss.hpp:103-152``) — expressed as
device-side ``top_k`` + masked scaling instead of a partial sort.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.random_gen import key_for_iteration
from .gbdt import GBDT


def goss_mask_from_importance(cfg, imp, u, k_top: int):
    """(mask, amplify) from per-row |g·h| importance and a per-row uniform
    draw: EXACTLY ``k_top`` top rows plus an ``other_rate`` random sample of
    the rest, sampled rows amplified by ``(1-top_rate)/other_rate``
    (goss.hpp:103-152).  The shared math of GOSS._bagging_weights and the
    distributed trainer — the two paths must stay byte-identical for
    multi-process parity.  An ``imp >= threshold`` mask would inflate
    unboundedly on ties (identical |g*h| is the norm in early iterations),
    which both deviates from the reference's partial sort and defeats the
    subset-capacity bound."""
    n = imp.shape[0]
    _, top_idx = jax.lax.top_k(imp, k_top)
    is_top = jnp.zeros(n, bool).at[top_idx].set(True)
    sampled = (u < cfg.other_rate) & ~is_top
    mask = (is_top | sampled).astype(jnp.float32)
    scale = (1.0 - cfg.top_rate) / max(cfg.other_rate, 1e-12)
    return mask, jnp.where(sampled, scale, 1.0)


class GOSS(GBDT):
    def _bagging_weights(self, iteration, grad, hess):
        cfg = self.config
        if cfg.top_rate + cfg.other_rate >= 1.0:
            return None, grad, hess
        key = key_for_iteration(cfg.bagging_seed, iteration)
        out = self._goss_jit(grad, hess, key)
        self._record_program("train.sample", self._goss_jit, grad, hess, key)
        return out

    @functools.cached_property
    def _goss_jit(self):
        cfg = self.config
        n = self.train_data.num_data

        @jax.jit
        @jax.named_scope("lgbm/sample")
        def goss_sample(grad, hess, key):
            # importance = sum over classes of |g*h| (goss.hpp:115)
            imp = jnp.sum(jnp.abs(grad * hess), axis=0)
            mask, amplify = goss_mask_from_importance(
                cfg, imp, jax.random.uniform(key, (n,)),
                max(1, int(cfg.top_rate * n)))
            amplify = amplify[None, :]
            return mask, grad * amplify, hess * amplify
        return goss_sample

    # -- bagging-subset compaction (models/gbdt.py): GOSS keeps
    # top_rate + ~other_rate of the rows and re-bags EVERY iteration, so the
    # compacted grower pass pays one re-gather per iteration but shrinks
    # every histogram/partition pass to O(kept rows)
    def _bag_subset_capacity(self):
        cfg = self.config
        if (cfg.top_rate + cfg.other_rate >= self._BAG_SUBSET_MAX_FRACTION
                or getattr(self, "_mesh", None) is not None):
            return None
        n = self.train_data.num_data
        k_top = max(1, int(cfg.top_rate * n))
        return self._capacity_with_margin(k_top + (n - k_top) * cfg.other_rate,
                                          n)

    def _bag_subset_refresh(self, iteration: int) -> bool:
        return True                 # gradient-based membership: every iter
