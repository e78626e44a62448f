"""Objective function interface.

Analog of the reference ``ObjectiveFunction``
(``include/LightGBM/objective_function.h``): per-row gradients/hessians from
scores, automatic initial score (``BoostFromScore``), output transform
(``ConvertOutput``) and optional leaf-output renewal for L1-style objectives
(``RenewTreeOutput``).  Gradient math is pure ``jax.numpy`` so it fuses into
the boosting step's compiled program.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config


class ObjectiveFunction:
    name: str = "base"
    #: ``get_gradients`` is a pure function of its arguments and of values
    #: fixed at ``init`` (no state that moves between calls, no host work):
    #: the booster may compile it once into one program.  False unless a
    #: class says otherwise; a subclass that adds state must reset it.
    pure_gradients: bool = False

    def __init__(self, config: Config):
        self.config = config
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None

    # -- lifecycle ------------------------------------------------------
    def init(self, metadata, num_data: int) -> None:
        """Bind dataset metadata (reference ``ObjectiveFunction::Init``)."""
        self.num_data = num_data
        self.label = metadata.label
        self.weight = metadata.weight
        self.query_boundaries = metadata.query_boundaries

    # -- core -----------------------------------------------------------
    def get_gradients(self, score: jax.Array, label: jax.Array,
                      weight: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def boost_from_score(self, class_id: int = 0) -> float:
        """Initial constant score (reference ``BoostFromScore``); 0 if the
        objective does not support boosting from average."""
        return 0.0

    def convert_output(self, score):
        return score

    @property
    def num_model_per_iteration(self) -> int:
        return 1

    @property
    def is_constant_hessian(self) -> bool:
        return False

    def need_renew_tree_output(self) -> bool:
        return False

    def renew_leaf_values(self, leaf_pred: np.ndarray, score: np.ndarray,
                          leaf_values: np.ndarray, num_leaves: int) -> np.ndarray:
        """Percentile re-fit of leaf outputs (reference ``RenewTreeOutput``,
        used by L1/quantile/MAPE)."""
        return leaf_values

    def _weights(self, n: int):
        return self.weight if self.weight is not None else None


def _percentile_of(values: np.ndarray, weights: Optional[np.ndarray], alpha: float) -> float:
    """Weighted percentile (reference ``PercentileFun``/``WeightedPercentileFun``,
    ``regression_objective.hpp:23-70``)."""
    if len(values) == 0:
        return 0.0
    order = np.argsort(values)
    v = values[order]
    if weights is None:
        # reference PercentileFun: linear interpolation on positions
        pos = alpha * (len(v) - 1)
        lo = int(np.floor(pos))
        hi = min(lo + 1, len(v) - 1)
        return float(v[lo] + (pos - lo) * (v[hi] - v[lo]))
    w = weights[order]
    cw = np.cumsum(w)
    threshold = alpha * cw[-1]
    idx = int(np.searchsorted(cw, threshold))
    return float(v[min(idx, len(v) - 1)])
