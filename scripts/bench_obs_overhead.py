"""Telemetry overhead bench: boosting-loop cost with obs on vs off.

The acceptance bar for the observability subsystem is that telemetry
OFF (the default) costs nothing measurable — the boosting loop holds a
``None`` and pays one attribute check per iteration — and telemetry ON
stays under a few percent, because iteration events ride host phase-timer
deltas instead of forcing device syncs (models/gbdt.py keeps its lazy
``_pending`` drain).

Trials are INTERLEAVED (off, on, off, on, ...) so machine drift —
thermal, other tenants, allocator state — lands on both arms, and each
arm reports median ± MAD over the repeats.  A few-percent overhead is
near the noise floor of a shared CPU box, so the summary carries a
``sign_ambiguous`` verdict: when the arms' MAD bands overlap the
measured delta, the sign of the overhead is not resolved by this run
and the number must not be read as a regression (or an improvement).

Usage:
    JAX_PLATFORMS=cpu python scripts/bench_obs_overhead.py \
        [--rows 100000] [--rounds 8] [--repeats 5]
"""
import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import load_obs  # noqa: E402

LOG = load_obs().EventLog.default(echo=True)


def emit(**kv):
    LOG.emit(kv.pop("stage", "bench_record"), **kv)


def median(xs):
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def mad(xs):
    """Median absolute deviation — the robust spread for tiny samples
    where one GC pause would wreck a standard deviation."""
    m = median(xs)
    return median([abs(x - m) for x in xs])


def train_secs(params, X, y, rounds):
    import lightgbm_tpu as lgb
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    bst.update()                                  # compile outside the clock
    bst._gbdt._train_score.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(rounds):
        bst.update()
    bst._gbdt._train_score.block_until_ready()
    return time.perf_counter() - t0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--feats", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--leaves", type=int, default=63)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    backend = jax.default_backend()
    rng = np.random.default_rng(0)
    X = rng.normal(size=(args.rows, args.feats)).astype(np.float32)
    y = (X[:, 0] + np.sin(X[:, 1] * 3) + X[:, 2] * X[:, 3]
         + 0.1 * rng.normal(size=args.rows)).astype(np.float64)
    base = {"objective": "regression", "num_leaves": args.leaves,
            "max_bin": 63, "verbose": -1, "seed": 7}

    import tempfile
    evpath = os.path.join(tempfile.mkdtemp(prefix="obs_overhead_"),
                          "events.jsonl")
    configs = {"off": dict(base),
               "on": dict(base, obs_telemetry=True, obs_events_path=evpath)}
    # interleave repeats so drift (thermal, other tenants) hits both arms
    times = {k: [] for k in configs}
    for _ in range(max(1, args.repeats)):
        for name, params in configs.items():
            times[name].append(train_secs(params, X, y, args.rounds))
    med = {k: median(v) for k, v in times.items()}
    spread = {k: mad(v) for k, v in times.items()}
    overhead_on = (med["on"] - med["off"]) / med["off"] * 100.0
    # propagate each arm's MAD into the delta (conservative: sum, not
    # quadrature — MADs of 3-5 samples are too coarse for quadrature)
    noise_s = spread["on"] + spread["off"]
    overhead_mad = noise_s / med["off"] * 100.0
    # when the noise band covers the measured delta, this run cannot even
    # resolve WHICH arm was faster — say so instead of printing a signed
    # percentage that a reader (or the regression sentinel) would trust
    sign_ambiguous = abs(med["on"] - med["off"]) <= noise_s

    for name in configs:
        emit(stage="obs_overhead_arm", arm=name, backend=backend,
             median_s=round(med[name], 4), mad_s=round(spread[name], 4),
             all_s=[round(t, 4) for t in times[name]])

    note = (f"overhead {overhead_on:+.2f}% ± {overhead_mad:.2f}% (MAD); "
            + ("sign NOT resolved at this repeat count"
               if sign_ambiguous else "sign resolved"))
    # one-JSON-line contract: summary() appends to the journal AND prints
    # the schema-stamped record as the LAST stdout line
    LOG.summary(
        metric="obs_telemetry_overhead", unit="pct",
        value=round(overhead_on, 2), backend=backend,
        detail={"rows": args.rows, "rounds": args.rounds,
                "repeats": args.repeats,
                "median_off_s": round(med["off"], 4),
                "median_on_s": round(med["on"], 4),
                "mad_off_s": round(spread["off"], 4),
                "mad_on_s": round(spread["on"], 4),
                "overhead_mad_pct": round(overhead_mad, 2),
                "sign_ambiguous": sign_ambiguous,
                "note": note,
                "events_path": evpath})
    return 0


if __name__ == "__main__":
    sys.exit(main())
