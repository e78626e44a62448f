"""What can be said about the chip path without a chip.

- ``chip_smoke.py``: the dry-run flag rehearses all four phases at toy size
  and says so in its JSON; without the flag a CPU backend is a non-zero exit
  that names the platform and prints no result.
- the compile cache is placeable from outside and otherwise sits at one
  fixed in-checkout path (``lightgbm_tpu/utils/compile_cache.py``).
- libtpu can compile ahead of time for a described topology, so the Pallas
  kernels are compiled by Mosaic for ``v5e:2x2`` at the bench width for
  every variant the first-fit election may pick.  That proves they compile;
  whether they run and give the right numbers only the chip can say
  (``chip_smoke.py`` phase 1).
- ``chip_smoke.auc_of`` and the jax-free ``scripts/bench_vs_ref.py`` (which
  drives the compiled reference binary on ``chip_smoke.make_higgs_like``'s
  data) agree on AUC, and the CSV the reference reads is our float32 matrix
  bit for bit.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, env_over=None, drop=(), cwd=None, timeout=600):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_over or {})
    return subprocess.run([sys.executable] + argv, env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


# --------------------------------------------------------------------------
# chip_smoke.py
# --------------------------------------------------------------------------

def test_dry_run_passes_and_cannot_be_mistaken_for_a_chip_run(tmp_path):
    p = _run([SMOKE, "--dry-run", "--rows", "6000", "--trees", "2",
              "--leaves", "15", "--valid-rows", "4000",
              "--out", str(tmp_path)], {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    report, verdict = p.stdout.splitlines()[-2:]
    # the last line is the verdict: these keys and no others
    verdict = json.loads(verdict)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    res = json.loads(report)
    assert res["ok"] is True and res["dry_run"] is True
    assert res["device"] == verdict["device"]
    assert res["platform"] == "cpu" and res["device"]["platform"] == "cpu"
    assert [ph["name"] for ph in res["phases"]] == [
        "parity", "train", "predict", "serve"]
    assert all(ph["status"] == "ok" for ph in res["phases"])
    # width is never cut, not even in the rehearsal
    assert res["config"]["cols"] == 28 and res["config"]["max_bin"] == 255
    assert (tmp_path / "chip_smoke.json").exists()


def test_without_the_flag_a_cpu_backend_is_a_failure(tmp_path):
    p = _run([SMOKE, "--out", str(tmp_path)], {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "platform='cpu'" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
    # and the sizes cannot be cut outside a dry run
    p = _run([SMOKE, "--rows", "1000"], {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and "--dry-run" in p.stderr


# --------------------------------------------------------------------------
# the head-to-head with the compiled reference (scripts/bench_vs_ref.py)
# --------------------------------------------------------------------------

def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_csv_roundtrips_float32_bit_exact(tmp_path):
    """The head-to-head's "identical data" claim requires the CSV handed to
    the reference binary to reproduce our float32 matrix BIT-exactly:
    %.9g guarantees that (9 significant digits uniquely identify any
    binary32); the old %.7g did not."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 4)).astype(np.float32)
    # adversarial values: last-ulp neighbors, huge/tiny exponents, denormal
    X[0, :] = [np.float32(1/3), np.nextafter(np.float32(1/3), np.float32(1)),
               np.float32(3.4e38), np.float32(1.2e-38)]
    X[1, :] = [np.float32(1e-45), np.float32(-0.0), np.float32(2**-24),
               np.nextafter(np.float32(1.0), np.float32(2.0))]
    y = (rng.random(200) > 0.5).astype(np.float32)
    path = str(tmp_path / "t.csv")
    _load("bench_vs_ref", "scripts", "bench_vs_ref.py")._write_csv(path, X, y)
    back = np.loadtxt(path, delimiter=",")
    cols = np.column_stack([y, X])
    np.testing.assert_array_equal(
        back.astype(np.float32).view(np.uint32),
        cols.view(np.uint32),
        err_msg="CSV write/read must round-trip float32 bit-exactly")


def test_script_auc_matches_package_metric():
    """The script's standalone AUC agrees with ``chip_smoke.auc_of``, the
    package's AUCMetric that ``chip_smoke.py`` holds its floor against."""
    script_auc = _load("bench_vs_ref", "scripts", "bench_vs_ref.py")._auc
    auc_of = _load("chip_smoke", "chip_smoke.py").auc_of
    rng = np.random.default_rng(0)
    for n, tie in [(500, False), (500, True), (50, True)]:
        y = (rng.random(n) > 0.4).astype(np.float64)
        s = rng.normal(size=n)
        if tie:                      # heavy ties exercise the midrank path
            s = np.round(s, 1)
        np.testing.assert_allclose(script_auc(y, s), auc_of(s, y),
                                   atol=1e-12)


# --------------------------------------------------------------------------
# compile cache placement
# --------------------------------------------------------------------------

_CACHE_PROBE = ("import json, lightgbm_tpu, jax;"
                "from lightgbm_tpu.utils import compile_cache as cc;"
                "print(json.dumps([cc.cache_dir(), jax.config."
                "jax_persistent_cache_min_compile_time_secs]))")


def _cache_dir_of(env_over, drop=(), cwd=None):
    p = _run(["-c", _CACHE_PROBE], {"PYTHONPATH": REPO, **env_over},
             drop=drop, cwd=cwd, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    cache_dir, min_compile_secs = json.loads(p.stdout.splitlines()[-1])
    assert min_compile_secs == 0.0        # every program is cached
    return cache_dir


def test_cache_dir_from_env_is_the_only_one(tmp_path):
    d = str(tmp_path / "placed")
    assert _cache_dir_of({"JAX_COMPILATION_CACHE_DIR": d},
                         drop=("JAX_PLATFORMS",)) == d


def test_default_cache_dir_is_fixed_and_in_the_checkout(tmp_path):
    # no backend is initialised by the import, so leaving JAX_PLATFORMS out
    # does not reach for a TPU
    drop = ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
    a = _cache_dir_of({}, drop=drop, cwd=str(tmp_path))
    b = _cache_dir_of({}, drop=drop, cwd=REPO)
    assert a == b == os.path.join(REPO, ".jax_cache")
    # a process pinned to the CPU gets none unless one is placed for it
    assert _cache_dir_of({"JAX_PLATFORMS": "cpu"},
                         drop=("JAX_COMPILATION_CACHE_DIR",)) is None


# --------------------------------------------------------------------------
# Mosaic compiles the kernels for v5e (ahead of time, no chip)
# --------------------------------------------------------------------------

N_ROWS, N_FEAT, SLOTS, BLOCK_ROWS = 200_000, 28, 16, 512


@pytest.fixture(scope="module")
def v5e_spec():
    """ShapeDtypeStruct factory placed on a described v5e device."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:     # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"no TPU topology description available: {e}")
    dev = topo.devices[0]
    assert dev.device_kind == "TPU v5 lite"
    sh = SingleDeviceSharding(dev)
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sh)


def _aot_cases():
    from lightgbm_tpu.ops import onehot_variants as ov
    cases = []
    for bins in (256, 64):       # bench width; the lane-packing width
        for v in ov.AUTO_CANDIDATES:
            if bins == 64 and v != "packed":
                continue
            if ov.VARIANTS[v].supports(bins):
                cases += [("hist_pallas", v, bins),
                          ("hist_leaves_pallas", v, bins)]
    return cases


@pytest.mark.parametrize("kernel,variant,bins", _aot_cases())
def test_mosaic_compiles_kernel_for_v5e(v5e_spec, kernel, variant, bins):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import _hist_leaves_pallas, _hist_pallas
    S, f32 = v5e_spec, jnp.float32
    if kernel == "hist_pallas":
        n = N_ROWS
        fn = jax.jit(lambda b, g, h, m: _hist_pallas(
            b, g, h, m, bins, variant=variant, interpret=False))
        specs = (S((n, N_FEAT), jnp.uint8), S((n,), f32), S((n,), f32),
                 S((n,), f32))
    else:
        c = BLOCK_ROWS * 64
        fn = jax.jit(lambda comb, g, h, m, bl: _hist_leaves_pallas(
            comb, g, h, m, bl, SLOTS, bins, BLOCK_ROWS, N_FEAT,
            variant=variant, interpret=False))
        specs = (S((c, N_FEAT + 4), jnp.uint8), S((c,), f32), S((c,), f32),
                 S((c,), f32), S((c // BLOCK_ROWS,), jnp.int32))
    lowered = fn.lower(*specs)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()            # Mosaic refusing the kernel raises here


def test_every_election_candidate_is_covered():
    """The election must not hold a candidate the AOT test above skips."""
    from lightgbm_tpu.ops import onehot_variants as ov
    covered = {v for _, v, _ in _aot_cases()}
    assert covered == set(ov.AUTO_CANDIDATES)


@pytest.mark.slow
@pytest.mark.parametrize("grower", ["frontier", "serial"])
def test_grow_program_compiles_for_v5e(v5e_spec, grower, monkeypatch):
    """The whole grow program at the bench shape (1M x 28, 256-wide
    histograms, 255 leaves, Pallas kernels): 44 s (frontier) and 90 s
    (serial) of compile in the sandbox."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import histogram
    from lightgbm_tpu.ops.grower import GrowerConfig, grow_tree
    from lightgbm_tpu.ops.split import SplitParams
    # the growers ask the backend; this process's backend is the CPU
    monkeypatch.setattr(histogram, "_pallas_interpret_default", lambda: False)
    S, f32, i32 = v5e_spec, jnp.float32, jnp.int32
    n, f = 1_000_000, N_FEAT
    sp = SplitParams(lambda_l1=0.0, lambda_l2=0.0, min_data_in_leaf=100,
                     min_sum_hessian_in_leaf=100.0, min_gain_to_split=0.0,
                     max_delta_step=0.0, path_smooth=0.0, cat_smooth=10.0,
                     cat_l2=10.0, max_cat_to_onehot=4)
    cfg = GrowerConfig(num_leaves=255, max_depth=-1, max_bin=256, split=sp,
                       feature_fraction_bynode=1.0, hist_method="pallas",
                       hist_chunk_rows=8192, sorted_cat=False,
                       hist_compact_ladder=1.41, grower_mode=grower)

    def grow(bins, g, h, rw, fm, nb, db, nanb, cat, mono, key):
        return grow_tree(bins, g, h, rw, fm, nb, db, nanb, cat, mono,
                         jax.random.wrap_key_data(key), cfg)

    lowered = jax.jit(grow).lower(
        S((n, f), jnp.uint8), S((n,), f32), S((n,), f32), S((n,), f32),
        S((f,), f32), S((f,), i32), S((f,), i32), S((f,), i32),
        S((f,), jnp.bool_), S((f,), i32), S((2,), jnp.uint32))
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()
