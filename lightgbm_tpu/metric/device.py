"""The built-in binary metrics as compiled programs over device-resident scores.

Where the scores already live on the device (``GBDT._valid_scores``,
``GBDT._train_score``), copying millions of them to the host and sorting
them there once a tree leaves the device idle for the whole of it.  These
programs compute the same numbers where the scores are and return a few
thousand block partials, which the host sums in 64 bits:

- **AUC**, exact: the scores sorted with the labels as payload; tie groups
  are runs of equal scores, as the host's float64 copy of a float32 score
  keeps their order and their ties; each positive row counts
  ``2 * (negatives below its group) + (negatives in its group)`` in int32,
  summed in blocks small enough that no partial can overflow.  The host
  divides the int64 total by ``2 * pos * neg`` in float64: the same
  rational number, rounded once, as ``AUCMetric.eval`` computes.
- **log-loss**: each row's loss in the stable softplus form, capped where
  the host's probability clip caps it, summed in float32 blocks.  The
  softplus is built from additions, multiplications and one division: the
  v5e's float32 ``exp`` and ``log`` are approximations (measured on the
  chip against float64: ``log`` up to 3.7e-4 off and 2.9e-6 low on average,
  ``jax.nn.softplus`` summed over a click log's rows 6.7e-6 low), and a
  mean that far off is not the host's number.

Both run under the scope ``lgbm/valid_metric`` (``obs/scopes.py``), over
``padded(n)`` rows: the labels are staged once at that length, the rows past
``n`` labelled ``PAD`` and counted by neither metric.  So no operation of
theirs has the shape of the scores, and none shares its key (name and result
shape, ``obs.scopes.op_key``) with an operation of the score updates, whose
time would then read as ambiguous.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

SCOPE = "lgbm/valid_metric"
#: rows a float32 log-loss partial sums, and the most an AUC partial does
LOSS_BLOCK = 256
#: the AUC's int32 counts reach 2 * n; past this many rows they would not fit
MAX_ROWS = 2 ** 30
#: the host's clip of the probability to [1e-15, 1 - 1e-15], as a loss
CAP_POS = -math.log(1e-15)
CAP_NEG = -math.log(1.0 - (1.0 - 1e-15))
#: the label of a padding row
PAD = 2


def padded(n: int) -> int:
    """Rows the programs run over: past ``n`` by at least one, a multiple of
    ``LOSS_BLOCK`` and so of every ``auc_block``."""
    return (n // LOSS_BLOCK + 1) * LOSS_BLOCK


def stage_labels(label: np.ndarray):
    """The int8 device labels the programs take: 1 positive, 0 negative,
    ``PAD`` past the data."""
    out = np.full(padded(len(label)), PAD, np.int8)
    out[:len(label)] = label > 0
    return jnp.asarray(out)


def auc_block(n: int) -> int:
    """Rows an AUC partial sums over ``n`` rows (the padded count, at least
    the data's): the most, a power of two up to ``LOSS_BLOCK``, for which
    ``block * 2 * n <= 2**31``, since each positive row counts under
    ``2 * n``."""
    block = LOSS_BLOCK
    while block > 1 and block * 2 * n > 2 ** 31:
        block //= 2
    return block


_LN2_HI = 0.693145751953125         # ln 2 to 16 bits: k * _LN2_HI is exact
_LN2_LO = 1.4286068203094172e-06
_INV_LN2 = 1.4426950408889634


def _exp_nonpositive(a):
    """``exp(a)`` for ``a <= 0``, in float32 arithmetic: ``a = k ln 2 + r``
    with ``|r| <= ln 2 / 2``, ``exp(r)`` by its Taylor series to ``r**8``
    (truncation under 3e-10), ``2**k`` written into the exponent bits.
    Below -87 it returns ``exp(-87)``, a loss term under 2e-38."""
    a = jnp.maximum(a, -87.0)
    k = jnp.round(a * _INV_LN2)
    r = (a - k * _LN2_HI) - k * _LN2_LO
    p = 1.0 / 40320
    for c in (1.0 / 5040, 1.0 / 720, 1.0 / 120, 1.0 / 24, 1.0 / 6, 0.5, 1.0, 1.0):
        p = c + r * p
    two_k = lax.bitcast_convert_type((k.astype(jnp.int32) + 127) << 23,
                                     jnp.float32)
    return p * two_k


def _log1p_unit(u):
    """``log(1 + u)`` for ``0 <= u <= 1``: ``2 atanh(s)`` with
    ``s = u / (2 + u) <= 1/3``, by its series to ``s**19`` (truncation under
    2e-11)."""
    s = u / (2.0 + u)
    s2 = s * s
    p = 1.0 / 19
    for j in range(17, 0, -2):
        p = 1.0 / j + s2 * p
    return 2.0 * s * p


def softplus(t):
    """``log(1 + exp(t))`` in float32 to a few units in the last place."""
    return jnp.maximum(t, 0.0) + _log1p_unit(_exp_nonpositive(-jnp.abs(t)))


def _pad_to(score, label, fill):
    flat = score.reshape(-1)
    return jnp.pad(flat, (0, label.shape[0] - flat.shape[0]),
                   constant_values=fill)


def _block_sums(per_row, block: int):
    return per_row.reshape(-1, block).sum(axis=1)


@jax.jit
def auc_partials(score, label):
    """``(partials, positives)``: int32 sums, over blocks of
    ``auc_block`` rows, of each positive row's ``2 * below + within`` over
    the raw scores ``score`` (N, any shape) and the staged labels ``label``
    (``stage_labels``)."""
    with jax.named_scope(SCOPE):
        # ties are grouped by value, so their order is free: an unstable
        # sort compiles in half the time of a stable one for the v5e
        s, lab = lax.sort((_pad_to(score, label, jnp.inf), label), num_keys=1,
                          is_stable=False)
        y = (lab == 1).astype(jnp.int32)
        neg = (lab == 0).astype(jnp.int32)
        upto = jnp.cumsum(neg)          # negatives at or below each row
        differs = s[1:] != s[:-1]
        first = jnp.concatenate([jnp.ones(1, bool), differs])
        last = jnp.concatenate([differs, jnp.ones(1, bool)])
        # negatives below the row's tie group, and below it or in it
        below = lax.cummax(jnp.where(first, upto - neg, 0))
        through = lax.cummin(jnp.where(last, upto, label.shape[0]),
                             reverse=True)
        return (_block_sums(y * (below + through), auc_block(label.shape[0])),
                jnp.sum(y))


def auc_value(partials, positives, n: int) -> float:
    pos = int(positives)
    neg = n - pos
    if pos == 0 or neg == 0:
        return 0.5
    total = int(np.asarray(partials).sum(dtype=np.int64))
    return float(np.float64(total) / np.float64(2 * pos * neg))


@jax.jit
def logloss_partials(score, label, sigmoid):
    """float32 sums, over blocks of ``LOSS_BLOCK`` rows, of each row's
    log-loss through ``sigmoid * score``."""
    with jax.named_scope(SCOPE):
        z = sigmoid * _pad_to(score, label, 0.0)
        pos = label == 1
        loss = jnp.minimum(softplus(jnp.where(pos, -z, z)),
                           jnp.where(pos, jnp.float32(CAP_POS),
                                     jnp.float32(CAP_NEG)))
        return _block_sums(jnp.where(label == PAD, 0.0, loss), LOSS_BLOCK)


def logloss_value(partials, n: int) -> float:
    return float(np.asarray(partials).sum(dtype=np.float64) / n)
