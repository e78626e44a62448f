"""Head-to-head vs the compiled reference binary, same data, same machine.

Trains ``/tmp/lgbm_src/lightgbm`` (reference CLI, ``docs/Experiments.rst:
110-135`` methodology) on the exact dataset ``chip_smoke.py`` trains on
(``make_higgs_like``) with its parameters, times it from the reference's
own per-iteration log lines (``src/boosting/gbdt.cpp:275`` prints
cumulative elapsed per iteration), and scores held-out AUC on a fresh
200k-row split via ``task=predict``.

Results land in ``docs/ref_headtohead.json`` keyed by row count, the only
whole-model comparison with the compiled reference; the entry is also the
last line of stdout.

Run: ``python scripts/bench_vs_ref.py [--rows 1000000] [--iters 22]``
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from chip_smoke import make_higgs_like  # noqa: E402

REF_BIN = os.environ.get("REF_LGBM_BIN", "/tmp/lgbm_src/lightgbm")
OUT_JSON = os.path.join(REPO, "docs", "ref_headtohead.json")

# one row per line, label first (the reference default: label=column 0).
# %.9g round-trips float32 bit-exactly (9 significant digits uniquely
# identify any binary32; %.7g did NOT, so the reference trained on data
# that differed from ours in the last ulps — weakening the "identical
# data" head-to-head claim).  tests/test_chip_smoke.py locks the round trip.
def _write_csv(path: str, X: np.ndarray, y: np.ndarray | None) -> None:
    cols = X if y is None else np.column_stack([y, X])
    np.savetxt(path, cols, delimiter=",", fmt="%.9g")


def _run(cmd, **kw):
    p = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                       text=True, **kw)
    if p.returncode != 0:
        sys.exit(f"reference binary failed ({p.returncode}):\n{p.stdout[-3000:]}")
    return p.stdout


def _auc(y_true: np.ndarray, score: np.ndarray) -> float:
    order = np.argsort(score, kind="mergesort")
    y = y_true[order]
    # tie-corrected rank AUC
    ranks = np.empty(len(y), np.float64)
    s = score[order]
    i = 0
    while i < len(s):
        j = i
        while j + 1 < len(s) and s[j + 1] == s[i]:
            j += 1
        ranks[i:j + 1] = 0.5 * (i + j) + 1.0
        i = j + 1
    npos = y.sum()
    nneg = len(y) - npos
    return float((ranks[y > 0].sum() - npos * (npos + 1) / 2) / (npos * nneg))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--iters", type=int, default=22)
    ap.add_argument("--valid-rows", type=int, default=200_000)
    ap.add_argument("--warmup", type=int, default=2,
                    help="iterations excluded from sec/tree (the reference"
                         " compiles nothing; ours are timed after a"
                         " warm-up)")
    args = ap.parse_args()

    if not os.path.exists(REF_BIN):
        sys.exit(f"reference binary not found at {REF_BIN}")

    Xtr, ytr = make_higgs_like(args.rows)
    Xva, yva = make_higgs_like(args.valid_rows, seed=43)

    tmp = tempfile.mkdtemp(prefix="ref_h2h_")
    train_csv = os.path.join(tmp, "train.csv")
    valid_csv = os.path.join(tmp, "valid.csv")
    model_txt = os.path.join(tmp, "model.txt")
    pred_txt = os.path.join(tmp, "pred.txt")
    print(f"writing CSVs to {tmp} ...", flush=True)
    _write_csv(train_csv, Xtr, ytr)
    _write_csv(valid_csv, Xva, yva)

    nthreads = os.cpu_count() or 1
    conf = {
        "task": "train", "objective": "binary",
        "data": train_csv, "output_model": model_txt,
        "num_iterations": args.iters, "num_leaves": 255,
        "learning_rate": 0.1, "max_bin": 255,
        "min_data_in_leaf": 100, "min_sum_hessian_in_leaf": 100.0,
        "num_threads": nthreads, "verbosity": 1, "header": "false",
    }
    cmd = [REF_BIN] + [f"{k}={v}" for k, v in conf.items()]
    print("training reference ...", flush=True)
    t0 = time.perf_counter()
    out = _run(cmd)
    wall = time.perf_counter() - t0

    elapsed = {int(m.group(2)): float(m.group(1)) for m in re.finditer(
        r"([0-9.]+) seconds elapsed, finished iteration (\d+)", out)}
    load = re.search(r"Finished loading data in ([0-9.]+) seconds", out)
    if args.iters not in elapsed:
        sys.exit(f"could not parse reference timing from log:\n{out[-2000:]}")
    w = min(args.warmup, args.iters - 1)
    sec_per_tree = (elapsed[args.iters] - elapsed.get(w, 0.0)) / (args.iters - w)

    print("predicting held-out ...", flush=True)
    _run([REF_BIN, "task=predict", f"data={valid_csv}",
          f"input_model={model_txt}", f"output_result={pred_txt}",
          "header=false", f"num_threads={nthreads}"])
    pred = np.loadtxt(pred_txt)
    ref_auc = _auc(yva.astype(np.float64), pred)

    import shutil
    shutil.rmtree(tmp, ignore_errors=True)

    entry = {
        "rows": args.rows, "iters": args.iters, "valid_rows": args.valid_rows,
        "num_leaves": conf["num_leaves"],
        "ref_sec_per_tree": round(sec_per_tree, 4),
        "ref_train_sec": round(elapsed[args.iters], 3),
        "ref_load_sec": round(float(load.group(1)), 3) if load else None,
        "ref_wall_sec": round(wall, 3),
        "ref_auc_holdout": round(ref_auc, 6),
        "threads": nthreads,
        "ref_version": "LightGBM v3.1.1.99 (compiled on this VM)",
    }
    table = {}
    if os.path.exists(OUT_JSON):
        with open(OUT_JSON) as f:
            table = json.load(f)
    table[str(args.rows)] = entry
    os.makedirs(os.path.dirname(OUT_JSON), exist_ok=True)
    with open(OUT_JSON, "w") as f:
        json.dump(table, f, indent=1)
    print(f"recorded -> {OUT_JSON}")
    print(json.dumps({"bench": "ref_headtohead", **entry}), flush=True)


if __name__ == "__main__":
    main()
