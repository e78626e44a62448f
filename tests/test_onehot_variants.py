"""One-hot variant registry: parity, structure, and end-to-end plumbing.

Every registry variant (ops/onehot_variants.py) must parity-check against
the exact scatter-add — masked rows AND fractional GOSS-style weights — in
Pallas interpret mode on CPU, at BOTH a lane-packing width (max_bin=64) and
the bench width (max_bin=255).  No variant can land or drift without this
gate; hardware pricing is the shootout's job (scripts/bench_onehot_variants
.py under the watcher).

The interpret-mode checks run in clean subprocesses (the pattern of
tests/test_frontier.py).  Pallas imports in-process as well; Mosaic's view
of the same kernels is tests/test_chip_smoke.py (AOT compile for v5e).

Registry STRUCTURE (geometry, work model, tuner caching) is asserted
in-process — that metadata is deliberately importable without jax kernels.
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.onehot

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_clean(code: str, timeout=600) -> str:
    env = {k: v for k, v in os.environ.items() if "PYTHONPATH" not in k}
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


# --------------------------------------------------------------------------
# registry structure (in-process, jax-free metadata)
# --------------------------------------------------------------------------

def test_registry_has_all_families():
    from lightgbm_tpu.ops import onehot_variants as ov
    # the 5 pre-registry shootout variants + the 3 new attack families
    for name in ("base", "bf16cmp", "i16cmp", "u8cmp", "sub1abs",
                 "staged", "packed", "int8"):
        assert name in ov.VARIANTS
    for name in ov.AUTO_CANDIDATES:
        assert name in ov.VARIANTS
    # Mosaic refuses these on v5e: none may cost a first fit a failed compile
    assert not {"u8cmp", "i16cmp", "bf16cmp", "sub1abs"} & set(
        ov.AUTO_CANDIDATES)


def test_lane_packing_shrinks_onehot_at_max_bin_64():
    """The acceptance claim, structurally: at max_bin=64 the packed variant
    halves BOTH the MXU N-dim and the VPU one-hot element count vs base
    (base pads 64 bins to 128 lanes — 2x waste packing reclaims)."""
    from lightgbm_tpu.ops import onehot_variants as ov
    f, B, BR = 28, 64, 512
    assert ov.pack_k(64) == 2
    assert ov.total_lanes("packed", f, B) * 2 == ov.total_lanes("base", f, B)
    base_cmp = ov.VARIANTS["base"].vpu_compares(f, B, BR)
    packed_cmp = ov.VARIANTS["packed"].vpu_compares(f, B, BR)
    assert packed_cmp * 2 == base_cmp
    # staged cuts compares even at full width: Bp/16 + 16 per element
    staged_cmp = ov.VARIANTS["staged"].vpu_compares(f, 255, BR)
    assert staged_cmp < ov.VARIANTS["base"].vpu_compares(f, 255, BR) // 5


def test_supports_gates():
    from lightgbm_tpu.ops import onehot_variants as ov
    assert not ov.VARIANTS["packed"].supports(255)    # needs B | 128, B<=64
    assert not ov.VARIANTS["packed"].supports(100)
    assert ov.VARIANTS["packed"].supports(32)
    assert not ov.VARIANTS["u8cmp"].supports(300)     # u8 compare domain
    for name in ("base", "staged", "int8", "i16cmp"):
        assert ov.VARIANTS[name].supports(255)
        assert ov.VARIANTS[name].supports(64)


def test_resolve_falls_back_with_warning():
    from lightgbm_tpu.ops import onehot_variants as ov
    assert ov.resolve("packed", 64) == "packed"
    assert ov.resolve("packed", 255) == "base"        # unsupported width
    with pytest.raises(ValueError):
        ov.resolve("nope", 64)


def test_hist_variant_param_validation():
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    cfg = Config.from_params({"hist_variant": "PACKED"})
    assert cfg.hist_variant == "packed"
    with pytest.raises(lgb.LightGBMError):
        Config.from_params({"hist_variant": "onehotty"})


def test_auto_tuner_caches_one_bench_per_key():
    """hist_variant=auto: the micro-bench runs ONCE per (device, width) —
    later fits reuse the cached winner (and off-TPU it short-circuits to
    'base' without timing anything)."""
    from unittest import mock

    from lightgbm_tpu.ops import onehot_variants as ov
    assert ov.pick_variant(255, 28) == "base"          # cpu backend: no bench
    calls = []

    def fake_bench(max_bin, f):
        calls.append(max_bin)
        return "staged"

    with mock.patch.object(ov, "_run_auto_bench", fake_bench), \
            mock.patch.object(ov, "_AUTO_CACHE", {}):
        import jax
        with mock.patch.object(jax, "default_backend", return_value="tpu"):
            assert ov.pick_variant(64, 28) == "staged"
            assert ov.pick_variant(64, 28) == "staged"
            assert ov.pick_variant(64, 99) == "staged"  # same key: no re-run
    assert calls == [64]


def test_election_has_no_floor():
    """A candidate that fails to compile or fails parity (NaN included) is
    never returned, 'base' included; when nothing passes the election
    raises."""
    from unittest import mock

    from lightgbm_tpu.ops import onehot_variants as ov
    small = ov._auto_bench_data(16, 8, rows=256)
    names = [n for n in ov.AUTO_CANDIDATES if ov.VARIANTS[n].supports(16)]
    assert names == ["base", "staged", "packed", "int8"]

    def elect(outcomes):
        with mock.patch.object(ov, "_auto_bench_data",
                               lambda *a, **k: small), \
                mock.patch.object(ov, "_time_auto_candidate",
                                  side_effect=outcomes):
            return ov._run_auto_bench(16, 8)

    # base refuses to lower, int8 is fastest but wrong: packed wins
    assert elect([RuntimeError("Mosaic refuses"), (2e-3, 1e-5),
                  (1e-3, 1e-5), (5e-4, 1.0)]) == "packed"
    with pytest.raises(RuntimeError, match="no candidate"):
        elect([RuntimeError("Mosaic refuses"), (1e-3, 1.0),
               (1e-3, float("nan")), (1e-3, 6e-4)])


# --------------------------------------------------------------------------
# interpret-mode parity (clean subprocesses)
# --------------------------------------------------------------------------

_PARITY_CHECK = r"""
import numpy as np, jax, jax.numpy as jnp
import lightgbm_tpu.ops.histogram as H
from lightgbm_tpu.ops import onehot_variants as ov

rng = np.random.default_rng(3)
for B in (64, 255):
    n, f = 2560, 9
    bins = jnp.asarray(rng.integers(0, B, size=(n, f), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
    # masked rows AND fractional GOSS-style weights in one mask vector
    m = jnp.asarray(np.where(rng.uniform(size=n) < 0.8,
                             rng.uniform(0.1, 2.5, size=n),
                             0.0).astype(np.float32))
    ref = H.fold_hist(H._hist_scatter(bins, g, h, m, B))
    for name, spec in ov.VARIANTS.items():
        if not spec.supports(B):
            assert name == "packed" and B == 255
            continue
        got = jax.jit(lambda *x, v=name: H.fold_hist(
            H._hist_pallas(*x, B, variant=v)))(bins, g, h, m)
        err = float(jnp.max(jnp.abs(got - ref) / (jnp.abs(ref) + 1.0)))
        assert err < H.HIST_PARITY_TOL, (name, B, err)
        print("PROD_OK", name, B, err)
    # the shootout's single-block shell must match too (registry shell #2)
    bins_t = jnp.asarray(np.ascontiguousarray(np.asarray(bins).T))
    for name in ("base", "packed", "staged", "int8"):
        spec = ov.VARIANTS[name]
        if not spec.supports(B):
            continue
        prep, run = ov.make_bench_kernel(name, f, B, 128, interpret=True)
        got = H.fold_hist(jax.jit(run)(bins_t, jax.jit(prep)(g, h, m)))
        err = float(jnp.max(jnp.abs(got - ref) / (jnp.abs(ref) + 1.0)))
        assert err < H.HIST_PARITY_TOL, ("bench", name, B, err)
        print("BENCH_OK", name, B, err)
print("PARITY_DONE")
"""


def test_every_variant_interpret_parity_vs_scatter():
    out = _run_clean(_PARITY_CHECK)
    assert "PARITY_DONE" in out
    # every registry family must have been exercised on the production shell
    from lightgbm_tpu.ops import onehot_variants as ov
    for name in ov.VARIANT_NAMES:
        assert f"PROD_OK {name}" in out, out


_LEAVES_CHECK = r"""
import numpy as np, jax, jax.numpy as jnp
import lightgbm_tpu.ops.histogram as H
from lightgbm_tpu.ops import onehot_variants as ov

rng = np.random.default_rng(0)
BR, NB, NC, k = 128, 6, 10, 4
C = BR * NB
for B, names in ((64, ("base", "packed", "staged", "int8")),
                 (255, ("base", "int8"))):
    comb = jnp.asarray(rng.integers(0, B, size=(C, NC)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=C).astype(np.float32))
    h = jnp.asarray(rng.random(C).astype(np.float32))
    m = jnp.asarray(np.where(rng.random(C) > 0.2,
                             rng.uniform(0.5, 1.5, size=C), 0.0)
                    .astype(np.float32))
    # slot k-2 deliberately empty: must come back zeros, not stale memory
    bl = np.sort(rng.integers(0, k, size=NB)).astype(np.int32)
    bl = jnp.asarray(np.where(bl == k - 2, k - 1, bl))
    ref = H.fold_hist(H.build_histogram_leaves(
        comb, g, h, m, bl, k, B, method="scatter", block_rows=BR, f_limit=7))
    assert ref.shape[1] == 7       # fallback slices BEFORE scattering now
    for name in names:
        got = jax.jit(lambda *x, v=name: H.fold_hist(H._hist_leaves_pallas(
            *x, k, B, BR, 7, variant=v)))(comb, g, h, m, bl)
        err = float(jnp.max(jnp.abs(got - ref) / (jnp.abs(ref) + 1.0)))
        assert err < H.HIST_PARITY_TOL, (name, B, err)
        assert float(jnp.abs(got[k - 2]).max()) == 0.0
        print("LEAVES_OK", name, B, err)
print("LEAVES_DONE")
"""


def test_leaves_kernel_variants_interpret_parity():
    out = _run_clean(_LEAVES_CHECK)
    assert "LEAVES_DONE" in out
    assert "LEAVES_OK packed 64" in out


_E2E_CHECK = r"""
import numpy as np, jax
from unittest import mock
import lightgbm_tpu as lgb
import lightgbm_tpu.ops.onehot_variants as ov

rng = np.random.default_rng(11)
X = rng.normal(size=(2000, 8)).astype(np.float32)
y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.1 * rng.normal(size=2000)
     > 0).astype(np.float64)

models = {}
for variant in ("base", "packed"):
    p = {"objective": "binary", "num_leaves": 8, "verbose": -1,
         "max_bin": 63, "min_data_in_leaf": 20, "hist_variant": variant}
    ds = lgb.Dataset(X, label=y, params=p)
    # the public param must reach the production Pallas kernels: patch the
    # backend probe so _make_grower_cfg picks hist_method='pallas' (the
    # kernels themselves then run in interpret mode on this cpu backend)
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        bst = lgb.Booster(params=p, train_set=ds)
    cfg = bst._gbdt._grower_cfg
    assert cfg.hist_method == "pallas", cfg.hist_method
    assert cfg.hist_variant == variant, cfg.hist_variant
    for _ in range(2):
        bst.update()
    models[variant] = bst

# identical trees under both variants: same splits, same leaf values (the
# dump differs ONLY in the recorded hist_variant param line, by design)
def dump(bst):
    return "\n".join(l for l in bst.model_to_string().splitlines()
                     if "hist_variant" not in l)
assert dump(models["base"]) == dump(models["packed"]), \
    "packed variant changed the trained trees"
pb = models["base"].predict(X[:300])
pp = models["packed"].predict(X[:300])
assert float(np.abs(pb - pp).max()) == 0.0
print("E2E_VARIANTS_OK")

# hist_variant=auto: one cached election, concrete variant in the config,
# no retrace per tree (the config is a static string before compile)
calls = []
def fake_bench(max_bin, f):
    calls.append(max_bin)
    return "staged"
with mock.patch.object(ov, "_run_auto_bench", fake_bench), \
     mock.patch.object(ov, "_AUTO_CACHE", {}):
    for _ in range(2):
        p = {"objective": "binary", "num_leaves": 8, "verbose": -1,
             "max_bin": 63, "min_data_in_leaf": 20, "hist_variant": "auto"}
        ds = lgb.Dataset(X, label=y, params=p)
        with mock.patch.object(jax, "default_backend",
                               return_value="tpu"):
            bst = lgb.Booster(params=p, train_set=ds)
        assert bst._gbdt._grower_cfg.hist_variant == "staged"
    bst.update()          # trains fine under the elected variant
assert calls == [64], calls   # ONE election, second fit hit the cache
print("E2E_AUTO_OK")
"""


def test_hist_variant_end_to_end_grower():
    """Acceptance: hist_variant reaches the production Pallas kernels end
    to end — identical trees under two variants, and auto elects + caches
    once."""
    out = _run_clean(_E2E_CHECK, timeout=900)
    assert "E2E_VARIANTS_OK" in out
    assert "E2E_AUTO_OK" in out
