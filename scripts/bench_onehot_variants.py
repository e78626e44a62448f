"""One-hot histogram kernel variants — timing shootout on the TPU.

The production kernel (ops/histogram.py:_hist_pallas) is VPU-bound building
the one-hot (iota-compare-select over f*Bp*BR elements per block; measured
~12% MFU at the bench shape).  Every candidate build lives in the SHARED
variant registry (lightgbm_tpu/ops/onehot_variants.py) — the same kernel
bodies the production kernels run — so the shootout prices exactly what
training would ship and nothing can drift between the two (the pre-registry
shootout duplicated kernel code by hand).

Per (variant, BR, max_bin) entry: parity vs the true-f32 XLA one-hot at the
shared tolerance (HIST_PARITY_TOL), then a 10-iteration timing.  Results
append to perf_results.jsonl (stage "onehot_variant") with the structural
work model alongside the wall-clock: ``mxu_lanes`` (the dot's N-dim) and
``onehot_elems_per_row`` (VPU compare count) — see docs/PERF.md "ceiling
attack" for how to read them.

Run (the ONLY process touching the TPU):
    python scripts/bench_onehot_variants.py [rows] [--max-bin 255,64]

``--max-bin`` takes a comma list; the default sweeps 255 (the Higgs bench
width) and 64 (exercising the lane-packing variant).  The watcher's
onehot_shootout stage runs this unchanged.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import load_obs  # noqa: E402

# the watcher points every stage at one results file (WATCHER_PERF_LOG);
# obs.events owns that resolution now — one writer for every bench
OBS = load_obs()
LOG = OBS.EventLog.default(echo=True)
# achieved/peak math: obs.costs is the ONE peak table + MFU formula
COSTS = OBS.costs


def emit(**kv):
    LOG.emit(kv.pop("stage", "bench_record"), **kv)


# (variant, BR) grid: every registry family at the production BR, plus a
# BR sweep for the families whose VMEM one-hot budget trade-off moved the
# needle in earlier rounds
def entry_grid(variant_names):
    entries = [(name, 512) for name in variant_names]
    entries += [("base", 256), ("base", 1024), ("base", 2048),
                ("u8cmp", 1024), ("u8cmp", 2048),
                ("staged", 1024), ("packed", 1024), ("int8", 1024)]
    return entries


def run_shootout(rows, max_bins, emit=emit, interpret=False):
    """All (variant, BR) entries at each requested max_bin; importable so
    the perf suite / tests can drive the same sweep in-process."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.ops import onehot_variants as ov
    from lightgbm_tpu.ops.histogram import (HIST_PARITY_TOL, _hist_onehot,
                                            fold_hist)

    F = 28
    chip = COSTS.current_chip()
    # Per-entry failures (parity or lowering) are fully recorded as their
    # own ok:false jsonl entries and must NOT fail the stage: a nonzero
    # exit would make the watcher mark the whole onehot_shootout stage
    # failed — and re-run the entire 60-min sweep under stage retries —
    # because ONE experimental variant refused to lower, discarding every
    # valid timing already captured.  Nonzero is reserved for the sweep
    # itself crashing (main's probe abort / an unhandled error).
    tally = {"ok": 0, "failed": 0, "skipped": 0, "best": None}
    for B in max_bins:
        rng = np.random.default_rng(0)
        # pad rows to a multiple of the largest BR so every entry divides
        N = -(-rows // 2048) * 2048
        bins = rng.integers(0, B, size=(N, F), dtype=np.uint8)
        g_np = rng.normal(size=N).astype(np.float32)
        g_np[rows:] = 0.0
        g = jnp.asarray(g_np)
        h = jnp.asarray(np.full(N, 0.25, np.float32))
        m = jnp.asarray((np.arange(N) < rows).astype(np.float32))
        bins_t = jnp.asarray(np.ascontiguousarray(bins.T))  # [F, N] u8, once
        bins_d = jnp.asarray(bins)

        ref = jax.jit(lambda b_, g_: fold_hist(
            _hist_onehot(b_, g_, h, m, B, 65536)))(bins_d, g)
        ref = ref.block_until_ready()

        for name, BR in entry_grid(ov.VARIANT_NAMES):
            spec = ov.VARIANTS[name]
            tag = f"{name}_br{BR}"
            if not spec.supports(B):
                emit(stage="onehot_variant", name=tag, max_bin=B,
                     skipped="unsupported_max_bin")
                tally["skipped"] += 1
                continue
            try:
                prep, run = ov.make_bench_kernel(name, F, B, BR,
                                                 interpret=interpret)
                rows_arr = jax.jit(prep)(g, h, m).block_until_ready()
                jfn = jax.jit(run)
                hist = jfn(bins_t, rows_arr).block_until_ready()
                err = float(jnp.max(jnp.abs(fold_hist(hist) - ref)
                                    / (jnp.abs(ref) + 1.0)))
                if err > HIST_PARITY_TOL:
                    emit(stage="onehot_variant", name=tag, max_bin=B,
                         ok=False, relerr=err)
                    tally["failed"] += 1
                    continue
                t0 = time.perf_counter()
                for _ in range(10):
                    r = jfn(bins_t, rows_arr)
                r.block_until_ready()
                dt = (time.perf_counter() - t0) / 10
                lanes = ov.total_lanes(name, F, B)
                emit(stage="onehot_variant", name=tag, variant=name, br=BR,
                     max_bin=B, ok=True, relerr=err,
                     ms=round(dt * 1e3, 3),
                     # useful-FLOPs MFU vs the bf16 peak: 2 * 6 rows * N *
                     # the dot's actual N-dim (lane packing SHRINKS it)
                     mfu=round(COSTS.mfu(2.0 * 6 * rows * lanes, dt,
                                         chip), 4),
                     # analytical VPU-work-model bound (docs/PERF.md):
                     # predicted-vs-achieved prices the ceiling attack
                     predicted_mfu=round(ov.predicted_mfu(name, F, B), 4),
                     chip=chip, mxu_lanes=lanes,
                     onehot_elems_per_row=spec.vpu_compares(F, B, 1))
                tally["ok"] += 1
                if (tally["best"] is None
                        or dt * 1e3 < tally["best"]["ms"]):
                    tally["best"] = {"name": tag, "max_bin": B,
                                     "ms": round(dt * 1e3, 3)}
            except Exception as e:
                emit(stage="onehot_variant", name=tag, max_bin=B, ok=False,
                     error=str(e)[:250])
                tally["failed"] += 1
    return tally


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("rows", nargs="?", type=int, default=1_000_000)
    ap.add_argument("--max-bin", default="255,64",
                    help="comma list of histogram widths to sweep")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    max_bins = [int(b) for b in str(args.max_bin).split(",") if b.strip()]

    tally = run_shootout(args.rows, max_bins,
                         interpret=bool(os.environ.get("ONEHOT_INTERPRET")))
    # one-JSON-line contract: summary() appends to the journal AND prints
    # the schema-stamped record as the LAST stdout line.  Per-entry
    # failures are informational (see run_shootout) — exit 0 regardless.
    LOG.summary(bench="onehot_variants", rows=args.rows, max_bins=max_bins,
                **tally)
    return 0


if __name__ == "__main__":
    sys.exit(main())
