"""The built-in binary metrics over device scores (``metric/device.py``) held
to the host ``Metric.eval`` on the same fetched scores: the AUC bit for bit,
the log-loss within 1e-6 relative; and the cases that keep the host path."""
import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.metric import device
from lightgbm_tpu.metric.base import AUCMetric, BinaryLoglossMetric
from lightgbm_tpu.objective.binary import BinaryLogloss
from lightgbm_tpu.obs import get_tracer
from lightgbm_tpu.obs import metrics as obs_metrics


class _Meta:
    def __init__(self, label, weight=None):
        self.label, self.weight, self.query_boundaries = label, weight, None


def _scores(kind, n, rng):
    """(float32 scores, labels) of one case."""
    y = (rng.random(n) < 0.3).astype(np.float64)
    s = rng.normal(scale=3.0, size=n)
    if kind == "ties":          # a handful of values, most rows tied
        s = np.round(s)
    elif kind == "wide":        # a third of the rows past |40|, either sign
        far = rng.random(n) < 0.3
        s = np.where(far, np.sign(s) * (40.0 + 20.0 * rng.random(n)), s)
    elif kind == "all_pos":
        y[:] = 1.0
    elif kind == "all_neg":
        y[:] = 0.0
    elif kind == "half":
        y = (np.arange(n) % 2).astype(np.float64)
        s = np.round(s, 1)
    return s.astype(np.float32), y


CASES = ([(kind, n) for kind in ("ties", "wide") for n in (1, 255, 257, 100_003)]
         + [("all_pos", 257), ("all_neg", 257), ("all_pos", 1),
            ("half", 100_000)])


@pytest.mark.parametrize("kind,n", CASES)
def test_device_form_equals_the_host_metric(kind, n):
    rng = np.random.default_rng(n + len(kind))
    s, y = _scores(kind, n, rng)
    cfg = Config()
    objective = BinaryLogloss(cfg)
    meta = _Meta(y)
    objective.init(meta, n)
    auc, loss = AUCMetric(cfg), BinaryLoglossMetric(cfg)
    for m in (auc, loss):
        m.init(meta, n)
        assert m.has_device_form(objective)
    score_dev = jnp.asarray(s[None])
    fetched = np.asarray(score_dev, np.float64)[0]

    want = auc.eval(fetched, objective)[0][1]
    got = auc.device_eval(score_dev, objective)[0][1]
    assert got == want                              # bit for bit
    if kind.startswith("all"):
        assert got == 0.5
    want = loss.eval(fetched, objective)[0][1]
    got = loss.device_eval(score_dev, objective)[0][1]
    assert np.isfinite(want)
    assert abs(got - want) <= 1e-6 * abs(want)

    if kind == "half":
        # 2 * pos * neg and the numerator lie past int32: the block sums hold
        pos = int(y.sum())
        assert 2 * pos * (n - pos) > 2 ** 31
        assert device.auc_block(device.padded(n)) * 2 * n <= 2 ** 31
        partials, _ = device.auc_partials(score_dev, auc._label_dev)
        assert partials.dtype == jnp.int32
        assert int(np.asarray(partials).sum(dtype=np.int64)) > 2 ** 31


@pytest.mark.parametrize("kind,n", [("ties", 257), ("wide", 100_003), ("all_neg", 257)])
def test_scores_over_a_mesh_give_the_host_metric(kind, n):
    """Scores whose rows lie on 4 devices (padded to a multiple of them, as
    ``GBDT._place_valid`` places a validation set's): the AUC gathered and
    counted on every device equals the host's on the fetched scores bit for
    bit, and the log-loss, block sums added on the host, is within the one
    device's tolerance."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.parallel.mesh import put_rows
    rng = np.random.default_rng(n + len(kind))
    s, y = _scores(kind, n, rng)
    cfg = Config()
    objective = BinaryLogloss(cfg)
    meta = _Meta(y)
    objective.init(meta, n)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    score_dev = put_rows(s[None], NamedSharding(mesh, P(None, "data")), row_axis=1)
    assert score_dev.shape[1] == -(-n // 4) * 4
    assert device.rows_mesh(score_dev) == (mesh, "data")
    fetched = np.asarray(score_dev, np.float64)[0, :n]
    for m in (AUCMetric(cfg), BinaryLoglossMetric(cfg)):
        m.init(meta, n)
        want = m.eval(fetched, objective)[0][1]
        got = m.device_eval(score_dev, objective)[0][1]
        if isinstance(m, AUCMetric):
            assert got == want
        else:
            assert abs(got - want) <= 1e-6 * abs(want)
        assert m._label_dev.sharding.spec == P("data")


def test_auc_block_keeps_every_partial_in_int32():
    for n in (1, 1000, 100_000, 3_541_666, 17_708_333, device.MAX_ROWS):
        block = device.auc_block(n)
        assert block >= 1 and block * 2 * n <= 2 ** 31
    assert device.auc_block(100) == device.LOSS_BLOCK
    assert device.auc_block(17_708_333) == 32


def _binary_data(seed=0, rows=900, valid=300):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows + valid, 6))
    y = (X[:, 0] + X[:, 1] ** 2 + 0.5 * rng.normal(size=len(X)) > 0.7
         ).astype(np.float64)
    return X[:rows], y[:rows], X[rows:], y[rows:]


def _on_device_by_metric(data="valid"):
    """{metric class: on_device} of the ``lgbm/eval/metric`` spans kept."""
    spans = get_tracer().spans()
    evals = {s.id for s in spans if s.name == "lgbm/eval"
             and s.args["data"] == data}
    return {s.args["metric"]: s.args["on_device"] for s in spans
            if s.name == "lgbm/eval/metric" and s.parent in evals}


def _custom_fobj(preds, dataset):
    y = dataset.get_label()
    p = 1.0 / (1.0 + np.exp(-preds))
    return p - y, p * (1.0 - p)


def _custom_feval(preds, dataset):
    return "mean_score", float(np.mean(preds)), False


@pytest.mark.parametrize("case,want", [
    ("weighted", {"BinaryLoglossMetric": 0, "AUCMetric": 0}),
    ("multiclass", {"MultiLoglossMetric": 0}),
    ("fobj", {"BinaryLoglossMetric": 0, "AUCMetric": 1}),
    ("feval", {"BinaryLoglossMetric": 1, "AUCMetric": 1}),
])
def test_host_path_keeps_what_the_device_form_does_not_cover(case, want):
    """Weights, several trees an iteration and an objective other than the
    built-in binary one keep ``Metric.eval`` on the host; ``feval`` runs on
    the fetched scores beside the device metrics.  The counters and each
    span's ``on_device`` say which ran where."""
    X, y, Xv, yv = _binary_data(seed=3)
    params = {"objective": "binary", "metric": "binary_logloss,auc",
              "num_leaves": 7, "verbose": -1}
    kw = {}
    if case == "weighted":
        w = np.random.default_rng(1).uniform(0.5, 2.0, size=len(yv))
    if case == "multiclass":
        y, yv = np.digitize(X[:, 0], [-0.5, 0.5]), np.digitize(Xv[:, 0], [-0.5, 0.5])
        params.update(objective="multiclass", num_class=3, metric="multi_logloss")
    if case == "fobj":
        kw["fobj"] = _custom_fobj
    if case == "feval":
        kw["feval"] = _custom_feval
    train = lgb.Dataset(X, label=y, params=params)
    valid = lgb.Dataset(Xv, label=yv, reference=train, params=params,
                        weight=w if case == "weighted" else None)
    bst = lgb.train(params, train, 2, valid_sets=[valid], valid_names=["valid"],
                    verbose_eval=False, **kw)
    obs_metrics.reset()
    get_tracer().reset()
    got = bst.eval_valid(feval=kw.get("feval"))
    assert _on_device_by_metric() == want
    snap = obs_metrics.snapshot()
    on_device = sum(want.values())
    assert snap.get("eval.metrics_on_device", {}).get("value", 0) == on_device
    assert snap.get("eval.metrics_on_host", {}).get("value", 0) == \
        len(want) - on_device
    if case == "feval":
        score = np.asarray(bst._gbdt._valid_scores[0], np.float64)[0]
        assert ("valid", "mean_score", float(np.mean(score)), False) in got


def test_train_reports_the_host_values_and_a_second_eval_compiles_nothing():
    """``lgb.train`` with a validation set reports, each iteration, what the
    host metrics compute from ``_valid_scores``; once warm, ``eval_valid()``
    compiles nothing."""
    X, y, Xv, yv = _binary_data(seed=7, rows=1100, valid=413)
    params = {"objective": "binary", "metric": "binary_logloss,auc",
              "num_leaves": 15, "verbose": -1}
    train = lgb.Dataset(X, label=y, params=params)
    valid = lgb.Dataset(Xv, label=yv, reference=train, params=params)
    seen = []

    def host_values(env):
        gb = env.model._gbdt
        score = np.asarray(gb._valid_scores[0], np.float64)[0]
        host = {name: value for m in gb.valid_metrics[0]
                for name, value, _ in m.eval(score, gb.objective)}
        said = {metric: value for _, metric, value, _
                in env.evaluation_result_list}
        seen.append((said, host))

    bst = lgb.train(params, train, 5, valid_sets=[valid], valid_names=["v"],
                    verbose_eval=False, callbacks=[host_values])
    assert len(seen) == 5
    for said, host in seen:
        assert said["auc"] == host["auc"]
        assert abs(said["binary_logloss"] - host["binary_logloss"]) <= \
            1e-6 * host["binary_logloss"]
    assert _on_device_by_metric("v") == {"BinaryLoglossMetric": 1,
                                         "AUCMetric": 1}
    # the scope table holds the metric programs' operations
    from lightgbm_tpu.obs import device_scopes
    assert device.SCOPE in set(device_scopes().values())

    def compiles():
        return sum(1 for s in get_tracer().spans() if s.name == "lgbm/compile")

    bst.eval_valid()
    before = compiles()
    again = bst.eval_valid()
    assert compiles() == before
    assert {m: v for _, m, v, _ in again} == seen[-1][0]
    # no copy of the scores: the wait, then the two metrics
    spans = get_tracer().spans()
    last = [s for s in spans if s.name == "lgbm/eval"][-1]
    assert [s.name for s in spans if s.parent == last.id] == [
        "lgbm/eval/wait", "lgbm/eval/metric", "lgbm/eval/metric"]


@pytest.mark.parametrize("lo,hi", [(-90.0, -20.0), (-20.0, 0.0), (0.0, 20.0),
                                   (20.0, 90.0)])
def test_softplus_from_arithmetic_alone(lo, hi):
    """The log-loss's softplus takes no ``exp`` or ``log`` of the device's
    own: within a few float32 units of float64 on every row, and its sum
    unbiased."""
    rng = np.random.default_rng(int(hi - lo))
    t = rng.uniform(lo, hi, 200_000).astype(np.float32)
    want = np.logaddexp(0.0, t.astype(np.float64))
    got = np.asarray(device.softplus(jnp.asarray(t)), np.float64)
    big = want > 1e-30            # below, float32 holds no such number
    rel = np.abs(got - want)[big] / want[big]
    assert rel.max() <= 1e-6
    assert abs(got[big].sum() - want[big].sum()) <= 1e-8 * want[big].sum()


def test_metric_programs_share_no_operation_key_with_the_booster():
    """A key (name and result shape) two programs share reads as ambiguous,
    and the trace then books both programs' time to neither's scope: the
    metric programs run over padded rows so that no key of theirs is a
    score update's."""
    from lightgbm_tpu.obs import device_scopes, scopes

    scopes.reset_scopes()
    X, y, Xv, yv = _binary_data(seed=9, rows=1024, valid=256)
    params = {"objective": "binary", "metric": "binary_logloss,auc",
              "num_leaves": 7, "verbose": -1}
    train = lgb.Dataset(X, label=y, params=params)
    valid = lgb.Dataset(Xv, label=yv, reference=train, params=params)
    bst = lgb.Booster(params, train)
    bst.add_valid(valid, "valid")
    for _ in range(2):
        bst.update()
    bst.eval_valid()
    bst.eval_train()
    table = device_scopes()
    assert {device.SCOPE, "lgbm/score_update", "lgbm/valid_traverse"} <= \
        set(table.values())
    # scalars inside reducers and comparators share names across programs
    # and never show as operations of their own in a trace
    assert [key for key, scope in table.items()
            if scope == scopes.AMBIGUOUS and not key.endswith("[]")] == []
