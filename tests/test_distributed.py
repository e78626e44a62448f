"""Multi-process distributed smoke test (SURVEY §4 implication: the
reference exercises its socket collectives for real via a local Dask
cluster, tests/python_package_test/test_dask.py:21-47).

Here: two OS processes bring up ``jax.distributed`` over a localhost
coordinator (``mesh.init_distributed`` — the analog of LGBM_NetworkInit +
machine lists), build a global 2-device CPU mesh, and run one data-parallel
training step with cross-process psum collectives.  Each process pins ONE
virtual CPU device, so the mesh genuinely spans processes.
"""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os, sys
import numpy as np

proc_id = int(sys.argv[1])
coord = sys.argv[2]

sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=2,
                 process_id=proc_id)

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 2, jax.devices()

from lightgbm_tpu.ops.grower import GrowerConfig, grow_tree
from lightgbm_tpu.ops.split import SplitParams

n, f, B, L = 512, 6, 16, 7
rng = np.random.default_rng(0)
bins_np = rng.integers(0, B, size=(n, f), dtype=np.uint8)
g_np = rng.normal(size=n).astype(np.float32)

mesh = Mesh(np.array(jax.devices()), ("dp",))
sp = SplitParams(0.0, 0.0, 5, 1e-3, 0.0, 0.0, 0.0, 10.0, 10.0, 4)
cfg = GrowerConfig(num_leaves=L, max_depth=-1, max_bin=B, split=sp,
                   feature_fraction_bynode=1.0, hist_method="onehot",
                   hist_chunk_rows=65536, axis_name="dp",
                   parallel_mode="data", num_shards=2, sorted_cat=False)
meta = dict(num_bins=jnp.full(f, B, jnp.int32),
            default_bins=jnp.zeros(f, jnp.int32),
            nan_bins=jnp.full(f, -1, jnp.int32),
            is_categorical=jnp.zeros(f, bool),
            monotone=jnp.zeros(f, jnp.int32))


def grow(bins, g, h, rw, fm, key):
    return grow_tree(bins, g, h, rw, fm, **meta, key=key, cfg=cfg)



sharded = jax.shard_map(
    grow, mesh=mesh,
    in_specs=(P("dp"), P("dp"), P("dp"), P("dp"), P(), P()),
    out_specs=(P(), P("dp")), check_vma=False)

# globally-sharded inputs: each process provides its local half
def gshard(arr, spec):
    sh = NamedSharding(mesh, spec)
    return jax.make_array_from_process_local_data(sh, arr, arr.shape)

half = n // 2
lo, hi = (0, half) if proc_id == 0 else (half, n)
bins_g = gshard(bins_np[lo:hi], P("dp"))
g_g = gshard(g_np[lo:hi], P("dp"))
h_g = gshard(np.full(half, 0.25, np.float32), P("dp"))
rw_g = gshard(np.ones(half, np.float32), P("dp"))
fm = jnp.ones(f, jnp.float32)

tree, na = jax.jit(sharded)(bins_g, g_g, h_g, rw_g, fm,
                            jax.random.PRNGKey(0))
nl = int(tree.num_leaves)
assert nl > 1, nl
vals = np.asarray(tree.leaf_value)
print("proc{} OK nl={} checksum={:.6f}".format(
    proc_id, nl, float(np.abs(vals).sum())))
"""


_BINNING_WORKER = r"""
import hashlib, json, os, sys
import numpy as np

proc_id = int(sys.argv[1])
coord = sys.argv[2]
sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=2,
                 process_id=proc_id)
import jax
assert jax.process_count() == 2

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.distributed import distributed_dataset

# both processes generate the same global data, then keep disjoint halves
# with DIFFERENT distributions per half (so pooled-vs-local binning differs)
rng = np.random.default_rng(42)
n, f = 4000, 12
X = rng.normal(size=(n, f))
X[: n // 2] *= 3.0                      # half 0 is wide, half 1 narrow
X[:, 3] = rng.integers(0, 6, n)         # a categorical-ish column
y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
lo, hi = (0, n // 2) if proc_id == 0 else (n // 2, n)

cfg = Config.from_params({"max_bin": 63, "min_data_in_bin": 1})
ds = distributed_dataset(X[lo:hi], cfg, label=y[lo:hi],
                         categorical_feature=[3])
state = json.dumps([m.to_state() for m in ds.bin_mappers], sort_keys=True)
h = hashlib.sha256(state.encode()).hexdigest()[:16]
print("proc{} MAPPERHASH {}".format(proc_id, h))

# local binning is exactly value_to_bin of the shared mappers
for i, feat in enumerate(ds.used_features[:4]):
    manual = ds.bin_mappers[feat].value_to_bin(X[lo:hi, feat])
    got = ds.unbundled_bins()[:, i]
    assert np.array_equal(got.astype(np.int64), manual.astype(np.int64)), feat

# sparse shard path agrees with dense shard path (same pooled mappers)
import scipy.sparse as sps
Xs = X.copy(); Xs[np.abs(Xs) < 1.0] = 0.0
ds_d = distributed_dataset(Xs[lo:hi], cfg, label=y[lo:hi])
ds_s = distributed_dataset(sps.csr_matrix(Xs[lo:hi]), cfg, label=y[lo:hi])
assert np.array_equal(np.asarray(ds_d.bins), np.asarray(ds_s.bins))
hs = hashlib.sha256(json.dumps(
    [m.to_state() for m in ds_s.bin_mappers],
    sort_keys=True).encode()).hexdigest()[:16]
print("proc{} SPARSEHASH {}".format(proc_id, hs))
print("proc{} BINOK".format(proc_id))
"""


def _run_n_procs(tmp_path, src, n_procs, timeout=420):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    script = tmp_path / "worker.py"
    script.write_text(src.replace("@REPO@", REPO))
    procs = []
    for pid in range(n_procs):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env.pop("_LGBM_TPU_DRYRUN_CHILD", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(pid), coord],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = [p.communicate(timeout=timeout)[0] for p in procs]
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc{pid} failed:\n{out}"
    return outs


def _run_two_procs(tmp_path, src, timeout=240):
    return _run_n_procs(tmp_path, src, 2, timeout)


def test_two_process_distributed_binning(tmp_path):
    """Sharded ingest: mappers and EFB layout must be bit-identical across
    processes even though each shard's local distribution differs
    (reference: pooled-sample construction, dataset_loader.cpp:950)."""
    outs = _run_two_procs(tmp_path, _BINNING_WORKER)
    for pid, out in enumerate(outs):
        assert f"proc{pid} BINOK" in out, out
    for tag in ("MAPPERHASH", "SPARSEHASH"):
        hashes = sorted(line.split()[-1] for out in outs
                        for line in out.splitlines() if tag in line)
        assert len(hashes) == 2 and hashes[0] == hashes[1], (tag, outs)


def test_two_process_data_parallel_step(tmp_path):
    outs = _run_two_procs(tmp_path, _WORKER)
    for pid, out in enumerate(outs):
        assert f"proc{pid} OK" in out, out
    # both processes computed the same (replicated) tree
    chk = [line for out in outs for line in out.splitlines()
           if "checksum=" in line]
    assert len(chk) == 2
    assert chk[0].split("checksum=")[1] == chk[1].split("checksum=")[1]


_TRAIN_WORKER = r"""
import hashlib, sys
import numpy as np

proc_id = int(sys.argv[1]); coord = sys.argv[2]
sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=2,
                 process_id=proc_id)
import jax
from lightgbm_tpu.parallel import train_distributed

rng = np.random.default_rng(21)
n, f = 3000, 8
X = rng.normal(size=(n, f))
y = (X[:, 0] + 0.5 * X[:, 1] ** 2 - 1.0 * (X[:, 2] > 0.5)
     + rng.logistic(size=n) * 0.3 > 0).astype(np.float32)
lo, hi = (0, 1400) if proc_id == 0 else (1400, n)   # UNEQUAL shards

bst = train_distributed(
    {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
     "max_bin": 63, "verbose": -1, "seed": 5},
    X[lo:hi], y[lo:hi], num_boost_round=8)

ms = bst.model_to_string()
h = hashlib.sha256(ms.encode()).hexdigest()[:16]
p = bst.predict(X)
from sklearn.metrics import roc_auc_score
auc = roc_auc_score(y, p)
print("proc{} MODELHASH {}".format(proc_id, h))
print("proc{} AUC {:.4f}".format(proc_id, auc))
assert auc > 0.85, auc
print("proc{} TRAINOK".format(proc_id))
"""


def test_two_process_end_to_end_training(tmp_path):
    """Full multi-process train(): distributed binning + cross-process
    shard_map collectives + identical Booster on every rank (the
    reference's Dask-training contract, dask.py)."""
    outs = _run_two_procs(tmp_path, _TRAIN_WORKER, timeout=420)
    for pid, out in enumerate(outs):
        assert f"proc{pid} TRAINOK" in out, out
    hashes = sorted(line.split()[-1] for out in outs
                    for line in out.splitlines() if "MODELHASH" in line)
    assert len(hashes) == 2 and hashes[0] == hashes[1], outs


_MULTICLASS_WORKER = r"""
import hashlib, sys
import numpy as np

proc_id = int(sys.argv[1]); coord = sys.argv[2]
sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=2,
                 process_id=proc_id)
from lightgbm_tpu.parallel import train_distributed

rng = np.random.default_rng(31)
n = 2400
X = rng.normal(size=(n, 6))
y = (X[:, 0] > 0.4).astype(int) + (X[:, 1] > 0.2).astype(int)   # 3 classes
w = rng.uniform(0.5, 1.5, n).astype(np.float32)
lo, hi = (0, 1000) if proc_id == 0 else (1000, n)

bst = train_distributed(
    {"objective": "multiclass", "num_class": 3, "num_leaves": 15,
     "min_data_in_leaf": 5, "max_bin": 63, "verbose": -1, "seed": 2},
    X[lo:hi], y[lo:hi], num_boost_round=5, weight=w[lo:hi])
assert bst.num_trees() == 15                 # 5 iters x 3 classes
p = bst.predict(X)
assert p.shape == (n, 3)
np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-5)
acc = float(np.mean(p.argmax(axis=1) == y))
h = hashlib.sha256(bst.model_to_string().encode()).hexdigest()[:16]
print("proc{} MCHASH {}".format(proc_id, h))
print("proc{} ACC {:.3f}".format(proc_id, acc))
assert acc > 0.8, acc
print("proc{} MCOK".format(proc_id))
"""


def test_two_process_multiclass_weighted_training(tmp_path):
    """Multi-process multiclass + sample weights end to end: 3 trees per
    iteration grown in one scanned program, identical model on each rank."""
    outs = _run_two_procs(tmp_path, _MULTICLASS_WORKER, timeout=420)
    for pid, out in enumerate(outs):
        assert f"proc{pid} MCOK" in out, out
    hashes = sorted(line.split()[-1] for out in outs
                    for line in out.splitlines() if "MCHASH" in line)
    assert len(hashes) == 2 and hashes[0] == hashes[1], outs


_VALID_WORKER = r"""
import sys
import numpy as np

proc_id = int(sys.argv[1]); coord = sys.argv[2]
sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=2,
                 process_id=proc_id)
from lightgbm_tpu.parallel import train_distributed

rng = np.random.default_rng(17)
n, nv = 2000, 600
X = rng.normal(size=(n + nv, 6))
y = (X[:, 0] - X[:, 1] + rng.logistic(size=n + nv) * 0.4 > 0).astype(np.float32)
Xt, yt, Xv, yv = X[:n], y[:n], X[n:], y[n:]
lo, hi = (0, 900) if proc_id == 0 else (900, n)
vlo, vhi = (0, 250) if proc_id == 0 else (250, nv)

hist = {}
bst = train_distributed(
    {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 2,
     "max_bin": 63, "verbose": -1, "seed": 4, "learning_rate": 0.3},
    Xt[lo:hi], yt[lo:hi], num_boost_round=60,
    valid_data=(Xv[vlo:vhi], yv[vlo:vhi]),
    early_stopping_rounds=5, evals_result=hist)
curve = hist["valid"]["binary_logloss"]
print("proc{} ROUNDS {}".format(proc_id, len(curve)))
print("proc{} CURVE0 {:.6f} CURVEEND {:.6f}".format(
    proc_id, curve[0], curve[-1]))
assert len(curve) < 60, "early stopping never fired"
assert min(curve) < curve[0]
print("proc{} VALOK".format(proc_id))
"""


def test_two_process_valid_early_stopping(tmp_path):
    """Pooled additive valid metric: identical curve on both ranks, so
    early stopping fires consistently (reference Dask eval_set contract)."""
    outs = _run_two_procs(tmp_path, _VALID_WORKER, timeout=420)
    for pid, out in enumerate(outs):
        assert f"proc{pid} VALOK" in out, out
    rounds = {line.split()[-1] for out in outs
              for line in out.splitlines() if "ROUNDS" in line}
    curves = {line.split("CURVE0 ")[1] for out in outs
              for line in out.splitlines() if "CURVE0" in line}
    assert len(rounds) == 1 and len(curves) == 1, outs


_SETNET_WORKER = r"""
import sys
import numpy as np

proc_id = int(sys.argv[1]); coord = sys.argv[2]
sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel import set_network, free_network
port = coord.split(":")[1]
# both entries resolve to this host; rank disambiguation falls to the
# FIRST matching entry, so proc 1 assigns explicitly via init_distributed
if proc_id == 0:
    set_network(f"127.0.0.1:{port},127.0.0.2:{port}")
else:
    from lightgbm_tpu.parallel import init_distributed
    init_distributed(coordinator_address=coord, num_processes=2,
                     process_id=1)
import jax
assert jax.process_count() == 2
print("proc{} NETOK".format(proc_id))
free_network()
"""


def test_set_network_brings_up_cluster(tmp_path):
    """set_network (machine-list grammar) wires the jax.distributed client
    (reference Booster.set_network / LGBM_NetworkInit analog)."""
    outs = _run_two_procs(tmp_path, _SETNET_WORKER, timeout=240)
    for pid, out in enumerate(outs):
        assert f"proc{pid} NETOK" in out, out


_BAGGING_WORKER = r"""
import sys
import numpy as np

proc_id = int(sys.argv[1]); coord = sys.argv[2]; outdir = sys.argv[3]
sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=2,
                 process_id=proc_id)
from lightgbm_tpu.parallel import train_distributed

rng = np.random.default_rng(77)
n, f = 3000, 8
X = rng.normal(size=(n, f))
y = (X[:, 0] + 0.5 * X[:, 1] + rng.logistic(size=n) * 0.3 > 0
     ).astype(np.float32)
lo, hi = (0, n // 2) if proc_id == 0 else (n // 2, n)   # equal: no padding

params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
          "max_bin": 63, "verbose": -1, "seed": 5, "bagging_fraction": 0.6,
          "bagging_freq": 1, "bagging_seed": 3, "feature_fraction": 0.75}
bst = train_distributed(params, X[lo:hi], y[lo:hi], num_boost_round=6)
if proc_id == 0:
    bst.save_model(outdir + "/bagged.txt")
print("proc{} BAGOK".format(proc_id))
"""


def test_two_process_bagging_matches_single(tmp_path):
    """Per-rank Bernoulli bagging + feature_fraction with the agreed seed:
    the 2-process model must equal the single-process model over the
    concatenated rows (reference gbdt.cpp:228-262 — bagging happens on the
    shared row partition)."""
    import lightgbm_tpu as lgb
    outs = _run_two_procs(tmp_path, _BAGGING_WORKER.replace(
        "sys.argv[3]", f"'{tmp_path}'"), timeout=420)
    for pid, out in enumerate(outs):
        assert f"proc{pid} BAGOK" in out, out

    rng = np.random.default_rng(77)
    n, f = 3000, 8
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] + rng.logistic(size=n) * 0.3 > 0
         ).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
              "max_bin": 63, "verbose": -1, "seed": 5,
              "bagging_fraction": 0.6, "bagging_freq": 1, "bagging_seed": 3,
              "feature_fraction": 0.75}
    single = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                       num_boost_round=6)
    dist = lgb.Booster(model_file=str(tmp_path / "bagged.txt"))
    np.testing.assert_allclose(dist.predict(X), single.predict(X),
                               rtol=1e-5, atol=1e-6)


_GOSS_WORKER = r"""
import sys
import numpy as np

proc_id = int(sys.argv[1]); coord = sys.argv[2]; outdir = sys.argv[3]
sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=2,
                 process_id=proc_id)
from lightgbm_tpu.parallel import train_distributed

rng = np.random.default_rng(78)
n, f = 3000, 8
X = rng.normal(size=(n, f))
y = (X[:, 0] - 0.7 * X[:, 2] + rng.logistic(size=n) * 0.3 > 0
     ).astype(np.float32)
lo, hi = (0, n // 2) if proc_id == 0 else (n // 2, n)

params = {"objective": "binary", "boosting": "goss", "num_leaves": 15,
          "min_data_in_leaf": 5, "max_bin": 63, "verbose": -1, "seed": 5,
          "top_rate": 0.25, "other_rate": 0.15, "bagging_seed": 3}
bst = train_distributed(params, X[lo:hi], y[lo:hi], num_boost_round=6)
if proc_id == 0:
    bst.save_model(outdir + "/goss.txt")
print("proc{} GOSSOK".format(proc_id))
"""


def test_two_process_goss_matches_single(tmp_path):
    """GOSS's top-rate cut as a global top_k over the sharded |g*h|: the
    2-process model equals the single-process exact-top-k model."""
    import lightgbm_tpu as lgb
    outs = _run_two_procs(tmp_path, _GOSS_WORKER.replace(
        "sys.argv[3]", f"'{tmp_path}'"), timeout=420)
    for pid, out in enumerate(outs):
        assert f"proc{pid} GOSSOK" in out, out

    rng = np.random.default_rng(78)
    n, f = 3000, 8
    X = rng.normal(size=(n, f))
    y = (X[:, 0] - 0.7 * X[:, 2] + rng.logistic(size=n) * 0.3 > 0
         ).astype(np.float32)
    params = {"objective": "binary", "boosting": "goss", "num_leaves": 15,
              "min_data_in_leaf": 5, "max_bin": 63, "verbose": -1, "seed": 5,
              "top_rate": 0.25, "other_rate": 0.15, "bagging_seed": 3}
    single = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                       num_boost_round=6)
    dist = lgb.Booster(model_file=str(tmp_path / "goss.txt"))
    np.testing.assert_allclose(dist.predict(X), single.predict(X),
                               rtol=1e-5, atol=1e-6)


_RANK_WORKER = r"""
import json, sys
import numpy as np

proc_id = int(sys.argv[1]); coord = sys.argv[2]; outdir = sys.argv[3]
sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=2,
                 process_id=proc_id)
from lightgbm_tpu.parallel import train_distributed

rng = np.random.default_rng(79)
nq, qsize = 60, 25                      # queries are rank-local
n = nq * qsize
X = rng.normal(size=(n, 6))
rel = np.clip((X[:, 0] + 0.8 * X[:, 1]
               + rng.normal(size=n) * 0.4) * 1.2 + 1.5, 0, 4)
y = np.floor(rel).astype(np.float32)
group = np.full(nq, qsize, np.int64)
half_q = nq // 2
lo, hi = (0, half_q * qsize) if proc_id == 0 else (half_q * qsize, n)
g_local = group[:half_q] if proc_id == 0 else group[half_q:]
# local validation shard: last 10 local queries
vq = 10
vlo = hi - vq * qsize
ev = {}
bst = train_distributed(
    {"objective": "lambdarank", "num_leaves": 15, "min_data_in_leaf": 3,
     "max_bin": 63, "verbose": -1, "seed": 5, "metric": ["ndcg"],
     "eval_at": [5], "label_gain": list(np.power(2.0, np.arange(32)) - 1)},
    X[lo:hi], y[lo:hi], group=g_local, num_boost_round=6,
    valid_data=(X[vlo:hi], y[vlo:hi]),
    valid_group=np.full(vq, qsize, np.int64), evals_result=ev)
if proc_id == 0:
    bst.save_model(outdir + "/rank.txt")
    json.dump(ev, open(outdir + "/rank_ev.json", "w"))
print("proc{} RANKOK".format(proc_id))
"""


def test_two_process_lambdarank_with_pooled_ndcg(tmp_path):
    """lambdarank end-to-end across processes: rank-local queries, globally
    identical trees, and the pooled NDCG@5 equals the single-process NDCG
    over the union of the validation queries."""
    import json
    import lightgbm_tpu as lgb
    outs = _run_two_procs(tmp_path, _RANK_WORKER.replace(
        "sys.argv[3]", f"'{tmp_path}'"), timeout=420)
    for pid, out in enumerate(outs):
        assert f"proc{pid} RANKOK" in out, out

    rng = np.random.default_rng(79)
    nq, qsize = 60, 25
    n = nq * qsize
    X = rng.normal(size=(n, 6))
    rel = np.clip((X[:, 0] + 0.8 * X[:, 1]
                   + rng.normal(size=n) * 0.4) * 1.2 + 1.5, 0, 4)
    y = np.floor(rel).astype(np.float32)
    group = np.full(nq, qsize, np.int64)
    params = {"objective": "lambdarank", "num_leaves": 15,
              "min_data_in_leaf": 3, "max_bin": 63, "verbose": -1,
              "seed": 5, "metric": ["ndcg"], "eval_at": [5],
              "label_gain": list(np.power(2.0, np.arange(32)) - 1)}
    single = lgb.train(params, lgb.Dataset(X, label=y, group=group,
                                           params=params),
                       num_boost_round=6)
    dist = lgb.Booster(model_file=str(tmp_path / "rank.txt"))
    np.testing.assert_allclose(dist.predict(X), single.predict(X),
                               rtol=1e-4, atol=1e-5)

    # pooled NDCG@5 equals the single-process metric over the SAME union
    # of validation queries (the two ranks' last 10 local queries each)
    ev = json.load(open(tmp_path / "rank_ev.json"))["valid"]
    key = [k for k in ev if "ndcg" in k][0]
    half_q = nq // 2
    vq = 10
    keep_q = list(range(half_q - vq, half_q)) + list(range(nq - vq, nq))
    rows = np.concatenate([np.arange(q * qsize, (q + 1) * qsize)
                           for q in keep_q])
    from lightgbm_tpu.metric.rank import NDCGMetric
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.config import Config
    md = Metadata(len(rows))
    md.set_field("label", y[rows])
    md.set_field("group", np.full(2 * vq, qsize, np.int64))
    m = NDCGMetric(Config.from_params({"eval_at": [5]}))
    m.init(md, len(rows))
    (_, expect, _), = m.eval(single.predict(X[rows], raw_score=True))
    assert abs(ev[key][-1] - expect) < 5e-3, (ev[key][-1], expect)


_AUC_WORKER = r"""
import json, sys
import numpy as np

proc_id = int(sys.argv[1]); coord = sys.argv[2]; outdir = sys.argv[3]
sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=2,
                 process_id=proc_id)
from lightgbm_tpu.parallel import train_distributed

rng = np.random.default_rng(80)
n, f = 2400, 6
X = rng.normal(size=(n, f))
y = (X[:, 0] + 0.6 * X[:, 1] + rng.logistic(size=n) * 0.5 > 0
     ).astype(np.float32)
lo, hi = (0, n // 2) if proc_id == 0 else (n // 2, n)
# UNEQUAL valid shards exercise the padded allgather
vsz = 300 if proc_id == 0 else 200
ev = {}
bst = train_distributed(
    {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
     "max_bin": 63, "verbose": -1, "seed": 5, "metric": ["auc"]},
    X[lo:hi], y[lo:hi], num_boost_round=5,
    valid_data=(X[hi - vsz:hi], y[hi - vsz:hi]), evals_result=ev)
if proc_id == 0:
    json.dump(ev, open(outdir + "/auc_ev.json", "w"))
    bst.save_model(outdir + "/auc.txt")
print("proc{} AUCPOOL {:.10f}".format(proc_id, ev["valid"]["auc"][-1]))
"""


def test_two_process_pooled_auc_exact(tmp_path):
    """Distributed AUC pools the raw (score, label) pairs: both ranks see
    the identical value, and it equals the exact single-machine AUC over
    the union of the (unequal!) validation shards."""
    import json
    import lightgbm_tpu as lgb
    outs = _run_two_procs(tmp_path, _AUC_WORKER.replace(
        "sys.argv[3]", f"'{tmp_path}'"), timeout=420)
    vals = [line.split()[-1] for out in outs
            for line in out.splitlines() if "AUCPOOL" in line]
    assert len(vals) == 2 and vals[0] == vals[1], outs

    rng = np.random.default_rng(80)
    n, f = 2400, 6
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.6 * X[:, 1] + rng.logistic(size=n) * 0.5 > 0
         ).astype(np.float32)
    dist = lgb.Booster(model_file=str(tmp_path / "auc.txt"))
    rows = np.concatenate([np.arange(1200 - 300, 1200),
                           np.arange(n - 200, n)])
    from sklearn.metrics import roc_auc_score
    expect = roc_auc_score(y[rows], dist.predict(X[rows]))
    assert abs(float(vals[0]) - expect) < 1e-9, (vals[0], expect)


_THREE_PROC_WORKER = r"""
import hashlib, sys
import numpy as np

proc_id = int(sys.argv[1]); coord = sys.argv[2]
sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=3,
                 process_id=proc_id)
import jax
assert jax.process_count() == 3
from lightgbm_tpu.parallel import train_distributed

rng = np.random.default_rng(91)
n, f = 3000, 7                     # 7 features: non-divisible by 3 shards
X = rng.normal(size=(n, f))
y = (X[:, 0] - 0.8 * X[:, 1] + rng.logistic(size=n) * 0.4 > 0
     ).astype(np.float32)
# UNEQUAL thirds: padding + the global-order mask draws both exercised
cuts = [0, 900, 2100, n]
lo, hi = cuts[proc_id], cuts[proc_id + 1]
bst = train_distributed(
    {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
     "max_bin": 63, "verbose": -1, "seed": 5, "bagging_fraction": 0.7,
     "bagging_freq": 1, "bagging_seed": 11},
    X[lo:hi], y[lo:hi], num_boost_round=5)
h = hashlib.sha256(bst.model_to_string().encode()).hexdigest()[:16]
print("proc{} HASH3 {}".format(proc_id, h))
print("proc{} THREEOK".format(proc_id))
"""


def test_three_process_unequal_shards_with_bagging(tmp_path):
    """Rank-count edge cases beyond 2 processes: unequal thirds (padding),
    a feature count not divisible by the shard count, and bagging's
    global-order mask draws — identical model on all three ranks."""
    outs = _run_n_procs(tmp_path, _THREE_PROC_WORKER, 3)
    for pid, out in enumerate(outs):
        assert f"proc{pid} THREEOK" in out, out
    hashes = sorted(line.split()[-1] for out in outs
                    for line in out.splitlines() if "HASH3" in line)
    assert len(hashes) == 3 and len(set(hashes)) == 1, outs


_EFB_WORKER = r"""
import sys
import numpy as np

proc_id = int(sys.argv[1]); coord = sys.argv[2]; outdir = sys.argv[3]
sys.path.insert(0, "@REPO@")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed(coordinator_address=coord, num_processes=2,
                 process_id=proc_id)
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.distributed import distributed_dataset
from lightgbm_tpu.parallel import train_distributed

rng = np.random.default_rng(83)
n, fd, fs = 3000, 4, 6
X = np.zeros((n, fd + fs), np.float64)
X[:, :fd] = rng.normal(size=(n, fd))
# six mutually exclusive sparse columns (a one-hot-ish block): EFB must
# bundle them, multi-process included
cat = rng.integers(-1, fs, size=n)          # -1 = all-zero row
rows = np.arange(n)[cat >= 0]
X[rows, fd + cat[cat >= 0]] = rng.uniform(0.5, 2.0, size=len(rows))
y = (X[:, 0] + 0.8 * (cat == 2) - 0.6 * (cat == 4)
     + rng.logistic(size=n) * 0.4 > 0).astype(np.float32)
lo, hi = (0, n // 2) if proc_id == 0 else (n // 2, n)

params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
          "max_bin": 63, "verbose": -1, "seed": 5}
ds = distributed_dataset(X[lo:hi], Config.from_params(dict(params)),
                         label=y[lo:hi])
assert ds.bundles is not None and len(ds.bundles) < fd + fs, ds.bundles
print("proc{} BUNDLES {}".format(proc_id, len(ds.bundles)))

bst = train_distributed(params, X[lo:hi], y[lo:hi], num_boost_round=6)
if proc_id == 0:
    bst.save_model(outdir + "/efb.txt")
print("proc{} EFBOK".format(proc_id))
"""


def test_two_process_efb_matches_single(tmp_path):
    """EFB bundling stays ON under multi-process training: the pooled
    planning sample gives every rank the identical bundle layout
    (io/distributed.py), the shard_map step trains in bundle space, and
    the 2-process model equals the single-process model (which bundles
    the same columns) over the concatenated rows."""
    import lightgbm_tpu as lgb
    outs = _run_two_procs(tmp_path, _EFB_WORKER.replace(
        "sys.argv[3]", f"'{tmp_path}'"), timeout=420)
    for pid, out in enumerate(outs):
        assert f"proc{pid} EFBOK" in out, out
    nb = sorted(line.split()[-1] for out in outs
                for line in out.splitlines() if "BUNDLES" in line)
    assert len(set(nb)) == 1, outs

    rng = np.random.default_rng(83)
    n, fd, fs = 3000, 4, 6
    X = np.zeros((n, fd + fs), np.float64)
    X[:, :fd] = rng.normal(size=(n, fd))
    cat = rng.integers(-1, fs, size=n)
    rows = np.arange(n)[cat >= 0]
    X[rows, fd + cat[cat >= 0]] = rng.uniform(0.5, 2.0, size=len(rows))
    y = (X[:, 0] + 0.8 * (cat == 2) - 0.6 * (cat == 4)
         + rng.logistic(size=n) * 0.4 > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
              "max_bin": 63, "verbose": -1, "seed": 5}
    single = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                       num_boost_round=6)
    dist = lgb.Booster(model_file=str(tmp_path / "efb.txt"))
    np.testing.assert_allclose(dist.predict(X), single.predict(X),
                               rtol=1e-5, atol=1e-6)
