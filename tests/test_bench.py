"""Bench harness invariants: the standalone AUC in scripts/bench_vs_ref.py
(jax-free: the script drives the compiled reference binary) must agree exactly
with the package's AUCMetric that bench.py gates on — the 0.002-slack
head-to-head comparison feeds on both."""
import importlib.util
import os

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench_vs_ref():
    spec = importlib.util.spec_from_file_location(
        "bench_vs_ref", os.path.join(REPO, "scripts", "bench_vs_ref.py"))
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
    _load_bench_vs_ref()._write_csv(path, X, y)
    back = np.loadtxt(path, delimiter=",")
    cols = np.column_stack([y, X])
    np.testing.assert_array_equal(
        back.astype(np.float32).view(np.uint32),
        cols.view(np.uint32),
        err_msg="CSV write/read must round-trip float32 bit-exactly")


def test_script_auc_matches_package_metric():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.metric.base import AUCMetric

    script_auc = _load_bench_vs_ref()._auc
    rng = np.random.default_rng(0)
    for n, tie in [(500, False), (500, True), (50, True)]:
        y = (rng.random(n) > 0.4).astype(np.float64)
        s = rng.normal(size=n)
        if tie:                      # heavy ties exercise the midrank path
            s = np.round(s, 1)
        md = Metadata(n)
        md.set_field("label", y)
        m = AUCMetric(Config())
        m.init(md, n)
        (_, pkg, _), = m.eval(s.astype(np.float64))
        np.testing.assert_allclose(script_auc(y, s), pkg, atol=1e-12)


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_headline", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_metric_name_is_self_consistent():
    """Honest labeling: the emitted metric carries the ACTUAL row count — a
    200k-row run can never print the 1M-row headline name.  There is no
    fallback token any more: bench.py does not run without a TPU."""
    bench = _load_bench()
    assert bench.metric_name(200_000) == "higgs_200k_train_throughput"
    assert bench.metric_name(1_000_000) == "higgs_1m_train_throughput"
    assert "10p5m" in bench.metric_name(10_500_000)
    assert bench.metric_name(12_345) == "higgs_12345_train_throughput"
    # the sentinel strips the size token so renamed series keep their history
    regress = bench.load_obs().regress
    assert (regress.canonical_metric(bench.metric_name(200_000))
            == regress.canonical_metric(bench.metric_name(1_000_000)))


def test_bench_refuses_to_run_without_a_tpu():
    """No chip -> non-zero exit naming the platform found, before any work
    and without starting (or becoming) another process."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode != 0
    assert "platform='cpu'" in p.stderr and p.stdout.strip() == ""
