"""One-hot variant registry: parity, structure, and end-to-end plumbing.

Every registry variant (ops/onehot_variants.py) must parity-check against
the exact scatter-add — masked rows AND fractional GOSS-style weights — in
Pallas interpret mode on CPU, at BOTH a lane-packing width (max_bin=64) and
the 256-wide kernel histogram that max_bin=255 trains with.  No variant can
land or drift without this gate.  The check is ``chip_smoke.py``'s own
routine, the one the chip runs as its first phase; Mosaic's view of the
same kernels is tests/test_chip_smoke.py (AOT compile for v5e).

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
# interpret-mode parity: the routine the chip runs (chip_smoke.py phase 1)
# --------------------------------------------------------------------------

def _parity_cases():
    from lightgbm_tpu.ops import onehot_variants as ov
    return [(v, b) for b in (64, 256) for v in ov.VARIANT_NAMES
            if ov.VARIANTS[v].supports(b)]


@pytest.mark.parametrize("variant,max_bin", _parity_cases())
def test_kernel_parity_vs_scatter(variant, max_bin):
    """Both production kernels, every variant, at the lane-packing width and
    at the 256-wide kernel histogram ``max_bin=255`` trains with: a row
    count that is no block multiple, one empty leaf slot, masked and
    fractionally weighted rows (``chip_smoke.run_kernel_checks`` makes all
    three)."""
    import chip_smoke
    from lightgbm_tpu.ops.histogram import HIST_PARITY_TOL
    errs = chip_smoke.run_kernel_checks(
        (variant,), max_bin=max_bin, n_feat=9, slots=4, block_rows=128,
        rows=2500)
    assert set(errs) == {f"hist_pallas/{variant}",
                         f"hist_leaves_pallas/{variant}"}
    bad = {k: e for k, e in errs.items() if not e < HIST_PARITY_TOL}
    assert not bad, bad


def test_parity_cases_cover_the_registry():
    from lightgbm_tpu.ops import onehot_variants as ov
    cases = _parity_cases()
    assert {v for v, _ in cases} == set(ov.VARIANT_NAMES)
    assert ("packed", 64) in cases and ("packed", 256) not in cases


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
