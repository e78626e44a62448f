"""One-shot TPU perf diagnosis: sanity → kernel micro → headline bench.

One process that holds the chip for its whole run (it starts no child that
needs it): run it as one chip command.  The suite is a sequence of NAMED
PHASES —

    sanity → parity → hist_micro → grow_sweep → headline → regress

— each wrapped so a crash records an error and degrades to the next phase
(parity is the exception: a wrong kernel must abort before any perf number
is recorded off it).  Results append to ``perf_results.jsonl`` as they
land, bracketed by resumable markers: ``suite_start`` at entry and one
``suite_phase_done`` per completed phase, so an interrupted run leaves an
exact record of what is still owed.

Serving, streaming and the 10.5M-row headline are chip commands of their
own (``scripts/bench_serve.py``, ``scripts/bench_stream.py``,
``BENCH_ROWS=10500000 python bench.py``), not phases here: a child started
from this process could not have the chip this process holds.

Resume knobs (used by scripts/tpu_window_watcher.py and by hand):
  TPU_SUITE_RESUME=1        skip phases with a ``suite_phase_done`` marker
                            (same row count) since the last ``suite_start``
  TPU_SUITE_SKIP_PHASES=a,b explicit skip list (wins over resume)
  TPU_SUITE_ONLY_PHASES=a,b run only these phases

Run (ONLY process touching the TPU):
    python scripts/tpu_perf_suite.py [rows]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import load_obs  # noqa: E402

# the watcher points every stage at one results file (WATCHER_PERF_LOG);
# obs.events owns that resolution now — one writer for every bench.
OBS = load_obs()
LOG = OBS.EventLog.default(echo=True)
# achieved/peak math: obs.costs is the ONE peak table + MFU formula
# (tests/test_obs.py greps the tree to keep peak constants out of here)
COSTS = OBS.costs
OUT = LOG.path
ROWS = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000

PHASES = ("sanity", "parity", "hist_micro", "grow_sweep",
          "headline", "regress")


def emit(**kv):
    LOG.emit(kv.pop("stage", "suite_record"), **kv)


class SuiteAbort(RuntimeError):
    """Raised by a phase whose failure poisons everything downstream."""


def _completed_phases_since_last_start():
    """(done, saved): phase names with a ``suite_phase_done`` marker (same
    row count) since the most recent ``suite_start`` — the resume set —
    plus any side state a completed phase recorded into its marker (the
    grow_sweep tuning).  ``resumed_done`` on a suite_start seeds ``done``
    so a SECOND interruption still remembers phases captured two runs ago
    (deliberate user skips are NOT in that field: a phase skipped by
    TPU_SUITE_ONLY_PHASES never ran and must not count as landed)."""
    done, saved = set(), {}
    try:
        with open(OUT) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("rows") != ROWS:
                    continue
                if rec.get("stage") == "suite_start":
                    done = set(rec.get("resumed_done") or [])
                elif rec.get("stage") == "suite_end":
                    # that run finished: nothing to resume
                    done, saved = set(), {}
                elif rec.get("stage") == "suite_phase_done":
                    done.add(rec.get("phase"))
                    if rec.get("bench_params_extra") is not None:
                        saved["bench_params_extra"] = \
                            rec["bench_params_extra"]
    except OSError:
        pass
    return done, saved


def _phases_to_skip(resume_done: set) -> set:
    skip = set(resume_done)
    if os.environ.get("TPU_SUITE_SKIP_PHASES"):
        skip |= {p.strip() for p in
                 os.environ["TPU_SUITE_SKIP_PHASES"].split(",") if p.strip()}
    only = os.environ.get("TPU_SUITE_ONLY_PHASES")
    if only:
        keep = {p.strip() for p in only.split(",") if p.strip()}
        skip |= set(PHASES) - keep
    return skip


# --------------------------------------------------------------------------
# phases (each takes the shared mutable context dict)
# --------------------------------------------------------------------------

def phase_sanity(ctx):
    import jax
    import jax.numpy as jnp
    t0 = time.perf_counter()
    x = jnp.ones((512, 512))
    (x @ x).block_until_ready()
    emit(stage="sanity", backend=jax.default_backend(),
         secs=round(time.perf_counter() - t0, 2))


def phase_parity(ctx):
    # kernel parity FIRST (the r02 lowering crash was only visible on
    # hardware): both one-hot layouts + the frontier batched-leaf kernel +
    # grower dual.  A parity failure aborts before any perf number could
    # be recorded off a wrong kernel.
    import jax
    if jax.default_backend() != "tpu":
        emit(stage="dual_skip", reason="cpu backend")
        return
    import bench_dual

    def emit_dual(**kv):
        emit(stage="dual_" + kv.pop("stage", "?"), **kv)
    if bench_dual.run_checks(emit_dual) != 0:
        raise SuiteAbort("kernel_parity_failed")


def phase_hist_micro(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import bench
    from lightgbm_tpu.ops.histogram import _hist_onehot, _hist_pallas
    rng = np.random.default_rng(0)
    N, F, B = ROWS, 28, 255
    bins = jnp.asarray(rng.integers(0, B, size=(N, F), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    h = jnp.asarray(np.full(N, 0.25, np.float32))
    m = jnp.ones(N, jnp.float32)
    ctx.update(bins=bins, g=g, h=h, m=m, N=N, F=F, B=B)

    def timed_jfn(jfn, mk_args, iters=10):
        """Warm once, then average ``iters`` timed calls; ``mk_args(eps)``
        builds the call args with a gradient cache-buster perturbation."""
        float(jfn(*mk_args(0.0)))
        t = time.perf_counter()
        for _ in range(iters):
            float(jfn(*mk_args(1e-12)))
        return (time.perf_counter() - t) / iters

    def timed(fn, iters=10):
        jfn = jax.jit(lambda b_, g_: jnp.sum(fn(b_, g_, h, m, B)))
        return timed_jfn(jfn, lambda eps: (bins, g + eps), iters)

    if jax.default_backend() == "tpu":
        chip = COSTS.current_chip()
        try:
            t_pallas = timed(_hist_pallas)
            Bp = -(-B // 128) * 128
            emit(stage="hist_pallas", ms=round(t_pallas * 1e3, 3),
                 grows_per_sec=round(N / t_pallas / 1e9, 3),
                 mfu=round(COSTS.mfu(2.0 * 6 * N * F * Bp, t_pallas,
                                     chip), 4),
                 chip=chip)
        except Exception as e:        # lowering failure must be visible
            emit(stage="hist_pallas", error=str(e)[:300])
        # production-kernel variant sweep from the SHARED registry
        # (ops/onehot_variants.py) at the bench width AND max_bin=64 (the
        # lane-packing width): these numbers price exactly what
        # hist_variant=<name> would ship, because _hist_pallas and the
        # shootout run the same registry bodies.  The full (variant, BR)
        # grid lives in scripts/bench_onehot_variants.py (the watcher's
        # onehot_shootout stage sweeps --max-bin the same way).
        from lightgbm_tpu.ops import onehot_variants as ov
        rng_v = np.random.default_rng(1)
        for vb in (B, 64):
            vbins = bins if vb == B else jnp.asarray(
                rng_v.integers(0, vb, size=(N, F), dtype=np.uint8))
            for vname in ov.AUTO_CANDIDATES:
                if not ov.VARIANTS[vname].supports(vb):
                    continue
                try:
                    jv = jax.jit(lambda b_, g_, v=vname, bb=vb: jnp.sum(
                        _hist_pallas(b_, g_, h, m, bb, variant=v)))
                    t_v = timed_jfn(jv, lambda eps: (vbins, g + eps))
                    lanes = ov.total_lanes(vname, F, vb)
                    emit(stage="hist_pallas_variant", variant=vname,
                         max_bin=vb, ms=round(t_v * 1e3, 3),
                         mxu_lanes=lanes,
                         mfu=round(COSTS.mfu(2.0 * 6 * N * lanes, t_v,
                                             chip), 4),
                         # the VPU-work-model bound next to the achieved
                         # figure prices each variant's remaining headroom
                         predicted_mfu=round(
                             ov.predicted_mfu(vname, F, vb), 4))
                except Exception as e:
                    emit(stage="hist_pallas_variant", variant=vname,
                         max_bin=vb, error=str(e)[:250])
        # batched-leaf kernel at the frontier shape: same rows split over
        # 16 slots in 512-row blocks (the per-round frontier workload)
        try:
            from lightgbm_tpu.ops.histogram import _hist_leaves_pallas
            BRL, KSL = 512, 16
            nbl = N // BRL
            bl = jnp.asarray((np.arange(nbl) * KSL // nbl).astype(np.int32))
            # slice ONCE outside the timed loop so the number is comparable
            # to hist_pallas (a per-call 28MB device copy would skew it)
            bins_l, g_l = bins[:nbl * BRL], g[:nbl * BRL]
            h_l, m_l = h[:nbl * BRL], m[:nbl * BRL]
            jfn = jax.jit(lambda b_, g_: jnp.sum(_hist_leaves_pallas(
                b_, g_, h_l, m_l, bl, KSL, B, BRL, F)))
            t_leaves = timed_jfn(jfn, lambda eps: (bins_l, g_l + eps))
            emit(stage="hist_leaves_pallas", ms=round(t_leaves * 1e3, 3),
                 slots=KSL, block_rows=BRL)
        except Exception as e:
            emit(stage="hist_leaves_pallas", error=str(e)[:300])
    t_onehot = timed(lambda b_, g_, h_, m_, B_: _hist_onehot(
        b_, g_, h_, m_, B_, 65536))
    emit(stage="hist_onehot", ms=round(t_onehot * 1e3, 3))


def phase_grow_sweep(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.ops.grower import GrowerConfig, grow_tree
    from lightgbm_tpu.ops.split import SplitParams
    if "bins" not in ctx:             # hist_micro skipped: rebuild inputs
        rng = np.random.default_rng(0)
        N, F, B = ROWS, 28, 255
        ctx.update(
            bins=jnp.asarray(rng.integers(0, B, size=(N, F), dtype=np.uint8)),
            g=jnp.asarray(rng.normal(size=N).astype(np.float32)),
            h=jnp.asarray(np.full(N, 0.25, np.float32)),
            m=jnp.ones(N, jnp.float32), N=N, F=F, B=B)
    bins, g, h = ctx["bins"], ctx["g"], ctx["h"]
    N, F = ctx["N"], ctx["F"]
    sp = SplitParams(lambda_l1=0.0, lambda_l2=0.0, min_data_in_leaf=100,
                     min_sum_hessian_in_leaf=100.0, min_gain_to_split=0.0,
                     max_delta_step=0.0, path_smooth=0.0, cat_smooth=10.0,
                     cat_l2=10.0, max_cat_to_onehot=4)
    hist_method = "pallas" if jax.default_backend() == "tpu" else "onehot"
    cfg = GrowerConfig(num_leaves=255, max_depth=-1, max_bin=256, split=sp,
                       feature_fraction_bynode=1.0, hist_method=hist_method,
                       hist_chunk_rows=65536, sorted_cat=False)
    meta = dict(num_bins=jnp.full(F, 256, jnp.int32),
                default_bins=jnp.zeros(F, jnp.int32),
                nan_bins=jnp.full(F, -1, jnp.int32),
                is_categorical=jnp.zeros(F, bool),
                monotone=jnp.zeros(F, jnp.int32))
    rw = jnp.ones(N, jnp.float32)
    fm = jnp.ones(F, jnp.float32)
    key = jax.random.PRNGKey(0)

    def time_grow(cfg_m, tag, iters):
        grow = jax.jit(lambda b_, g_, h_, rw_, fm_, k_, c=cfg_m: grow_tree(
            b_, g_, h_, rw_, fm_, **meta, key=k_, cfg=c))
        t = time.perf_counter()
        tree, _ = grow(bins, g, h, rw, fm, key)
        tree.leaf_value.block_until_ready()
        emit(stage=f"grow_{tag}_compile_plus_first",
             secs=round(time.perf_counter() - t, 1))
        t = time.perf_counter()
        for _ in range(iters):
            tree, _ = grow(bins, g + 1e-12, h, rw, fm, key)
        tree.leaf_value.block_until_ready()
        ms = (time.perf_counter() - t) / iters * 1e3
        emit(stage=f"grow_{tag}_steady", ms_per_tree=round(ms, 1))
        return ms

    best = (None, float("inf"))
    # frontier_k sweep: the batch width trades per-round fixed cost against
    # block-padding waste — pick the winner for the headline bench
    for fk, br in ((32, 512), (16, 512), (64, 512), (32, 1024)):
        cfg_m = cfg._replace(grower_mode="frontier", frontier_k=fk,
                             frontier_block_rows=br)
        ms = time_grow(cfg_m, f"frontier_k{fk}_br{br}", iters=4)
        if ms < best[1]:
            best = ((fk, br), ms)
    emit(stage="frontier_best", k=best[0][0], block_rows=best[0][1],
         ms_per_tree=round(best[1], 1))
    time_grow(cfg._replace(grower_mode="serial"), "serial", iters=2)
    # merge the sweep winner UNDER any user-provided knobs (theirs win);
    # returning it records the tuning in this phase's suite_phase_done
    # marker, so a RESUMED run that skips grow_sweep still benches the
    # headline with the same knobs instead of silently reverting
    extra = {"frontier_k": best[0][0], "frontier_block_rows": best[0][1],
             **json.loads(os.environ.get("BENCH_PARAMS_EXTRA", "{}"))}
    os.environ["BENCH_PARAMS_EXTRA"] = json.dumps(extra)
    return {"bench_params_extra": extra}


def phase_headline(ctx):
    # in-process, same params as bench.py; one coherent shape for the
    # whole story (a leftover BENCH_ROWS env var must not decouple the
    # headline from the micro stages)
    os.environ["BENCH_ROWS"] = str(ROWS)
    import contextlib
    import io
    import bench

    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    except SystemExit as e:
        if isinstance(e.code, str):      # bench refused to run (no TPU)
            emit(stage="headline_bench", error=e.code[:300])
            return
        # auc-floor exit: the JSON line is already in buf
    except Exception as e:
        # a lowering/OOM failure must still leave a record — the suite's
        # contract is append-as-they-land
        emit(stage="headline_bench", error=f"{type(e).__name__}: {e}"[:300])
        return
    payload = bench._load_supervise().extract_json_line(buf.getvalue())
    emit(stage="headline_bench",
         **(payload if payload is not None
            else {"error": buf.getvalue()[-300:]}))


def phase_regress(ctx):
    # CLOSING self-judgment (jax-free: obs.regress loaded via load_obs):
    # every number this suite just appended is classified against the
    # accumulated journal + BENCH_r* history, so a slower-than-last-window
    # result flags loudly WHILE the window is still open.  Degrade-only by
    # construction — the phase loop already records an error and moves on,
    # and a verdict never aborts: the captured numbers are the product.
    res = OBS.regress.scan(journal_path=OUT)
    worst = [v for v in res["verdicts"]
             if v["verdict"] in ("regressed", "improved")][:10]
    emit(stage="regress_verdict", rows=ROWS, counts=res["counts"],
         regressed=res["regressed"], worst=worst)


PHASE_FNS = {"sanity": phase_sanity, "parity": phase_parity,
             "regress": phase_regress,
             "hist_micro": phase_hist_micro, "grow_sweep": phase_grow_sweep,
             "headline": phase_headline}


def main():
    resume_done, saved = (set(), {})
    if os.environ.get("TPU_SUITE_RESUME"):
        resume_done, saved = _completed_phases_since_last_start()
    skip = _phases_to_skip(resume_done)
    if "grow_sweep" in skip and saved.get("bench_params_extra"):
        # resuming past a completed sweep: restore its tuning (any
        # user-provided knobs still win)
        os.environ["BENCH_PARAMS_EXTRA"] = json.dumps(
            {**saved["bench_params_extra"],
             **json.loads(os.environ.get("BENCH_PARAMS_EXTRA", "{}"))})
    emit(stage="suite_start", rows=ROWS, skipped=sorted(skip),
         resumed_done=sorted(resume_done))
    ctx = {}
    rc = 0
    for name in PHASES:
        if name in skip:
            continue
        try:
            marker_extra = PHASE_FNS[name](ctx) or {}
        except SuiteAbort as e:
            emit(stage="abort", reason=str(e), phase=name, rows=ROWS)
            return 1
        except Exception as e:       # degrade: later phases still run
            emit(stage="suite_phase_error", phase=name, rows=ROWS,
                 error=f"{type(e).__name__}: {e}"[:300])
            rc = 1
            continue
        emit(stage="suite_phase_done", phase=name, rows=ROWS, **marker_extra)
    emit(stage="suite_end", rows=ROWS, rc=rc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
