"""Test configuration: force an 8-device virtual CPU mesh so sharding tests
run without TPU hardware (SURVEY.md §4 implication)."""
import os

# tests run on the CPU backend; both variables must be set before jax is
# imported (by the package or by a test module)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


@pytest.fixture(scope="session")
def binary_data():
    from sklearn.datasets import make_classification
    X, y = make_classification(n_samples=2000, n_features=10, n_informative=6,
                               random_state=42)
    return X[:1500], y[:1500], X[1500:], y[1500:]


@pytest.fixture(scope="session")
def regression_data():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(2000, 8))
    y = (X[:, 0] * 2 + np.sin(X[:, 1] * 3) + X[:, 2] * X[:, 3]
         + 0.1 * rng.normal(size=2000)).astype(np.float64)
    return X[:1500], y[:1500], X[500:], y[500:]


@pytest.fixture(scope="session")
def multiclass_data():
    from sklearn.datasets import make_classification
    X, y = make_classification(n_samples=2400, n_features=12, n_informative=8,
                               n_classes=4, n_clusters_per_class=1, random_state=3)
    return X[:1800], y[:1800], X[1800:], y[1800:]


# --- quick tier -------------------------------------------------------------
# `pytest -m quick` runs a <3-minute cross-section (kernel unit tests, native
# parser, param docs, plus one smoke test per major surface).  Whole fast
# modules + named smoke tests; anything unlisted is excluded.
_QUICK_MODULES = {"test_ops", "test_native", "test_param_docs"}
_QUICK_TESTS = {
    ("test_engine", "test_binary"),
    ("test_engine", "test_early_stopping"),
    ("test_sklearn", "test_classifier_binary"),
    ("test_booster_api", "test_attr_roundtrip"),
    ("test_frontier", "test_regression_weighted_parity"),
    ("test_pandas", "test_dataframe_train_matches_manual_codes"),
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        name = item.originalname if hasattr(item, "originalname") else item.name
        if mod in _QUICK_MODULES or (mod, name) in _QUICK_TESTS:
            item.add_marker(pytest.mark.quick)


# --- capability gate: CPU multi-process collectives -------------------------
# A handful of tests spawn REAL worker processes that join a
# jax.distributed cluster and run cross-process psum collectives on the
# CPU backend.  Some jaxlib builds/hosts pass the coordination handshake
# (so set_network-style tests succeed) but hang or crash on the first
# actual collective — and each gated test then burns its full multi-minute
# subprocess timeout, which kills the tier-1 wall-clock budget long before
# the suite finishes.  Probe the capability ONCE with a minimal
# two-process psum; when it is absent, skip exactly these tests with a
# reason instead of letting them time the suite out.
_CAPABILITY_GATED = {
    ("test_distributed", "test_two_process_distributed_binning"),
    ("test_distributed", "test_two_process_data_parallel_step"),
    ("test_distributed", "test_two_process_end_to_end_training"),
    ("test_distributed", "test_two_process_multiclass_weighted_training"),
    ("test_distributed", "test_two_process_valid_early_stopping"),
    ("test_distributed", "test_two_process_bagging_matches_single"),
    ("test_distributed", "test_two_process_goss_matches_single"),
    ("test_distributed", "test_two_process_lambdarank_with_pooled_ndcg"),
    ("test_distributed", "test_two_process_pooled_auc_exact"),
    ("test_distributed", "test_three_process_unequal_shards_with_bagging"),
    ("test_distributed", "test_two_process_efb_matches_single"),
    ("test_consistency", "test_parallel_learning_example"),
    ("test_bagging_subset", "test_goss_subset_matches_masked_path"),
}

_PROBE_WORKER = r"""
import os, sys
proc_id = int(sys.argv[1]); coord = sys.argv[2]
sys.path.insert(0, sys.argv[3])
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=2,
                 process_id=proc_id)
import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
assert jax.process_count() == 2
mesh = Mesh(np.array(jax.devices()), ("dp",))
f = jax.shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
                  in_specs=(P("dp"),), out_specs=P(), check_vma=False)
local = np.full(1, float(proc_id + 1), np.float32)
g = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("dp")), local, (2,))
out = jax.jit(f)(g)
assert float(np.asarray(out)[0]) == 3.0, out
print("PROBE_OK", proc_id)
"""

_collectives_ok = None     # session cache: the probe runs at most once


def _cpu_collectives_ok():
    global _collectives_ok
    if _collectives_ok is not None:
        return _collectives_ok
    import signal
    import socket
    import subprocess
    import sys as _sys
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    with tempfile.TemporaryDirectory(prefix="collectives_probe_") as td:
        script = os.path.join(td, "probe_worker.py")
        with open(script, "w") as f:
            f.write(_PROBE_WORKER)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = repo
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env.pop("_LGBM_TPU_DRYRUN_CHILD", None)
        procs = [subprocess.Popen(
            [_sys.executable, script, str(pid), coord, repo],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, start_new_session=True) for pid in range(2)]
        outs = []
        ok = True
        for p in procs:
            try:
                # the hang IS the failure mode being probed for: a wedged
                # collective never returns, so kill the whole process
                # group (workers spawn XLA threads) and report "absent"
                out, _ = p.communicate(timeout=90)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    p.kill()
                out, _ = p.communicate()
                ok = False
            outs.append(out or "")
            ok = ok and p.returncode == 0 and "PROBE_OK" in outs[-1]
    _collectives_ok = ok
    return ok


def pytest_runtest_setup(item):
    mod = os.path.splitext(os.path.basename(str(item.fspath)))[0]
    name = item.originalname if hasattr(item, "originalname") else item.name
    if (mod, name) in _CAPABILITY_GATED and not _cpu_collectives_ok():
        pytest.skip("host jaxlib cannot run CPU multi-process collectives "
                    "(two-process psum probe failed/hung); skipping "
                    "cross-process collective test")
