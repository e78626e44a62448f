"""The benchmark's own tests: CPU only, about a minute.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They rehearse every cell's command at 20k rows (a result that says it is a
rehearsal), check that every name in BENCHMARK.json resolves to a file, hold
the generator to one population a configuration (the label model is the
configuration's, the rows are the seed's), check the trace reduction on a
small recorded trace and the work functions on a hand case, and drive the job
under every configuration's own limits with the timed path broken underneath
to see ``correct`` come out false: once for the control (the reference in
bfloat16) and once for each fault a training cell can have.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import datagen, reference, run as bench_run, trace_reduce, work  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = {c["name"]: c["file"] for c in BENCH["configs"]}


def _config(name):
    with open(os.path.join(ROOT, CONFIGS[name])) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return BENCH


def _last_json(text):
    return json.loads(text.strip().splitlines()[-1])


# ------------------------------------------------------------ the command
@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_command_rehearsal(bench, capfd, cell, trace):
    rc = bench_run.main(["--workload", cell, "--seed", "3000000011", "--seconds", "1",
                         "--trace", str(trace), "--rehearse", "20000"])
    out, err = capfd.readouterr()
    assert rc == 0
    line = _last_json(out)
    assert RESULT_KEYS <= set(line)
    assert set(line) - RESULT_KEYS <= {"rehearsal", "compared", "breakdown"}
    assert list(line)[-1] == "compared"
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    # 20k rows may not hold 255 leaves of a cell's minimum weight: such trees count as failed
    assert line["attempted"] >= 1 and 0 <= line["failed"] <= line["attempted"]
    assert err.strip().splitlines()[-1].startswith("compared ")
    for entry in line["compared"].values():
        assert set(entry) == {"value", "limit"}
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in bench[kind]}
    assert set(line["metrics"]) <= names
    if trace:
        # a reader that finds no device trace returns nothing, never 0
        assert "ingest_s.train" in line["metrics"]
        assert "hist_kernel_roofline.train" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"setup_s", "s_per_tree"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_no_accelerator_no_result(bench, capfd):
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--workload", bench["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert capfd.readouterr().out.strip() == ""


# ------------------------------------------------------------ BENCHMARK.json
def test_names_resolve_and_are_well_formed(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["reduced"] == c["reduced"] and "limits" in conf
        # the population is the configuration's: one label model for every --seed
        assert isinstance(conf["data"]["label"]["model_seed"], int)
    cells = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        with open(os.path.join(ROOT, "benchmarks", "traffic", w["traffic"] + ".json")) as f:
            job = json.load(f)["job"]
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "jobs", job + ".py"))
        cells.add(w["name"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        # a metric without a list binds every cell a later PR adds
        assert m.get("workloads"), m["name"]
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                           m["name"] + ".py"))
    assert len(json.dumps(bench)) < 64 * 1024


# ------------------------------------------------------------ the generator
# sha256 of make(data, rows, model_seed, stream) as the parent of PR 33 made it
# from --seed alone (x, y): the configuration's seed still gives those bytes
PARENT_BYTES = {
    ("criteo67", 300_000, 0):
        ("cd3deb1e7ccc948942e217b6a1fe62af809f29c9808e991ccfa3f822c1beece2",
         "760cf8dba295cc5f1682c98d27e6aec7a43e0d28e24d90761d37ac33a1e0cbf7"),
    ("criteo67", 60_000, 1):
        ("b2c1679d8298ff87990dc3f70e362ebfa330c21c19578795c0bfa504f3ebe4dc",
         "fcf545dea43469dce5b85d5af47a6b51edbe087dd31fca50dfade6461e864140"),
    ("criteo67-msh100", 300_000, 0):
        ("5b644559a319c43e8e6451aeea0f3a03a712e76b735f9bf176a9631bcc5725c0",
         "08b38b0b15a341952f73a44b509a1f13118b6565857f32d5b00181805e369f14"),
    ("criteo67-msh100", 60_000, 1):
        ("5a59f49f38918f776fe0389245f3d49d092b23efdf89efb3c3aa3c7fe15a8da5",
         "b404f38581933ba048f0711f2bcfd3096b55d730e84c4a16d1a61f2cae29a5a4"),
}


def _sha(a):
    return hashlib.sha256(a.tobytes()).hexdigest()


@pytest.mark.parametrize("config_name,rows,stream", list(PARENT_BYTES))
def test_model_seed_gives_the_bytes_the_seed_gave(config_name, rows, stream):
    """300,000 rows span two blocks, so the intercept's block and a later one."""
    spec = _config(config_name)["data"]
    x, y = datagen.make(spec, rows, spec["label"]["model_seed"], stream=stream)
    assert (_sha(x), _sha(y)) == PARENT_BYTES[config_name, rows, stream]


@pytest.mark.parametrize("stream", [0, 1])
@pytest.mark.parametrize("config_name", list(CONFIGS))
def test_two_seeds_are_two_samples_of_one_population(config_name, stream, monkeypatch):
    spec = _config(config_name)["data"]
    models, real = [], datagen.label_model
    monkeypatch.setattr(datagen, "label_model",
                        lambda spec: models.append(real(spec)) or models[-1])
    rate = float(spec["label"]["rate"])
    drawn = {}
    for seed in (spec["label"]["model_seed"], 3000003301, 7):
        drawn[seed] = x, y = datagen.make(spec, 40_000, seed, stream=stream)
        # the intercept was solved on the model's own block: it holds on any seed's rows
        assert abs(float(y.mean()) - rate) < 0.1 * rate
    want = real(spec)       # columns, weights, pairs, pair weights, intercept
    assert len(models) == 3
    for got in models:
        assert all(np.array_equal(a, b) for a, b in zip(got, want))
    (xa, ya), (xb, yb), (xc, _) = drawn.values()
    assert not np.array_equal(xa, xb) and not np.array_equal(ya, yb)
    assert not np.array_equal(xb, xc)
    # the other stream of one seed is other rows too
    assert not np.array_equal(xa, datagen.make(spec, 40_000, spec["label"]["model_seed"],
                                               stream=1 - stream)[0])


def test_two_model_seeds_are_two_populations_and_none_raises():
    name = next(iter(CONFIGS))
    spec, other = _config(name)["data"], _config(name)["data"]
    other["label"]["model_seed"] += 1
    a, b = datagen.label_model(spec), datagen.label_model(other)
    assert not np.array_equal(a[1], b[1])
    # one path: no fall-back to --seed
    del other["label"]["model_seed"]
    with pytest.raises(KeyError, match="model_seed"):
        datagen.make(other, 1000, 3000000128)


# ------------------------------------------------------------ trace reduction
def test_trace_reduction_on_recorded_trace():
    with open(os.path.join(os.path.dirname(__file__), "trace_small.json")) as f:
        rec = json.load(f)
    trace, want = rec["trace"], rec["expect"]
    lo, hi = trace_reduce.window_of(trace)
    assert (hi - lo) / 1e9 == pytest.approx(want["window_s"])
    assert trace_reduce.busy_seconds(trace) == pytest.approx(want["busy_s"])
    ops = trace_reduce.op_seconds(trace)
    for name, sec in want["op_seconds"].items():
        assert ops[name] == pytest.approx(sec)
    assert trace_reduce.seconds_matching(trace, ["no-such-kernel"]) is None
    # the Mosaic call, and not the fusion that merely reads a custom call's result
    assert trace_reduce.kernel_seconds(trace, "hist_kernel") == pytest.approx(want["hist_kernel_s"])
    got = trace_reduce.breakdown(trace, top=3)
    assert [n.split(" = ")[0] for n, _ in got["device_ops"]] == want["top_ops"]
    assert got["idle_gaps"][0][0] == want["longest_gap_during"]
    assert got["idle_gaps"][0][1] == pytest.approx(want["longest_gap_s"])


# ------------------------------------------------------------ least work
def _hand_tree():
    # root (100 rows) -> leaf 0 (30) | node 1 (70) -> leaf 1 (60) | leaf 2 (10)
    return {"num_leaves": 3, "left": np.array([-1, -2]), "right": np.array([1, -3]),
            "leaf_count": np.array([30, 60, 10])}


def test_least_work_hand_case():
    t = _hand_tree()
    assert work.rows_read(t) == 100 + 30 + 10
    assert work.least_bytes(t, columns=67) == 140 * (67 + 8)
    bw = work.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert work.least_seconds([t, t], 67, "TPU v5 lite") == pytest.approx(2 * 140 * 75 / bw)
    with pytest.raises(KeyError):
        work.peaks("TPU v0 imaginary")


def test_auc_counts_ties_half():
    score = np.array([0.1, 0.4, 0.4, 0.8], np.float32)
    label = np.array([0, 0, 1, 1], np.float32)
    # pairs (pos, neg): (0.4, 0.1) right, (0.4, 0.4) tie, (0.8, 0.1) and (0.8, 0.4) right
    assert reference.auc_of(score, label) == pytest.approx(3.5 / 4)


def test_median_gap_ignores_a_few_small_leaves_and_sees_every_leaf_shifted():
    ref = np.array([1.0, 2.0, -1.0, 0.5])
    rows = np.array([40, 50, 1, 9])
    one_small_leaf_off = ref * np.array([1.0, 1.0, 1.5, 1.0])
    assert reference._worst_gap(one_small_leaf_off, ref) == pytest.approx(0.5)
    assert reference._median_gap(one_small_leaf_off, ref, rows) == 0.0
    every_leaf_shifted = ref * 1.002
    assert reference._median_gap(every_leaf_shifted, ref, rows) == pytest.approx(0.002)
    # half of the rows sit at or under the gap of the leaf that holds them
    assert reference._median_gap(ref * np.array([1.0, 1.001, 1.5, 1.0]), ref, rows) \
        == pytest.approx(0.001)
    assert reference._median_gap(ref[:3], ref, rows) == float("inf")


# ------------------------------------------------------------ control and faults
def _ctx(config_name, rows=6000, seconds=0.5):
    config = _config(config_name)     # its own limits, untouched
    config["params"].update(num_leaves=15, min_sum_hessian_in_leaf=1e-3,
                            min_data_in_leaf=20)     # a size a test run can hold
    with open(os.path.join(ROOT, "benchmarks", "traffic", "train-valid.json")) as f:
        traffic = json.load(f)
    import time
    t0 = time.perf_counter()
    lines = []
    return {"config": config, "traffic": traffic, "seed": 3000000013,
            "seconds": seconds, "trace": False, "rows": rows,
            "clock": lambda: time.perf_counter() - t0, "log": lines.append,
            "control": ["bfloat16", "half", "frozen"]}, lines


@pytest.mark.parametrize("config_name", list(CONFIGS))
def test_sound_run_is_correct_and_controls_are_not(config_name):
    """The program as it is passes at test size under the configuration's own
    limits; the reference put in its place in bfloat16, on half of the rows,
    or with its state frozen fails at least one of them."""
    from benchmarks.jobs import train
    ctx, lines = _ctx(config_name)
    out = train.run(ctx)
    assert out["correct"], out["compared"]
    limits = ctx["config"]["limits"]
    controls = {rec["control"]: rec for rec in lines if "control" in rec}
    assert set(controls) == {"bfloat16", "half", "frozen"}
    for mode, rec in controls.items():
        assert rec["correct"] is False, (mode, rec["compared"])
        assert reference.verdict(rec["compared"], limits)[0] is False
    # validation rows are followed and held to limits of their own
    assert {"valid_loss_gap", "valid_auc_gap"} <= set(out["compared"])


def _frozen_state(monkeypatch):
    """A step that returns its state unchanged: the trees come, the scores stay."""
    import lightgbm_tpu as lgb
    real = lgb.Booster.update

    def update(self, *a, **kw):
        before = self._gbdt._train_score
        stop = real(self, *a, **kw)
        self._gbdt._train_score = before
        return stop
    monkeypatch.setattr(lgb.Booster, "update", update)


def _half_batch(monkeypatch):
    """Half of the batch left out: the booster is handed every second row."""
    import lightgbm_tpu as lgb
    real = lgb.Dataset.__init__

    def init(self, data, label=None, **kw):
        real(self, data[::2], label=label[::2], **kw)
    monkeypatch.setattr(lgb.Dataset, "__init__", init)


def _altered_answer(monkeypatch):
    """An answer altered where it is produced: as the host builds each tree,
    its two largest leaves trade values."""
    from lightgbm_tpu.models import tree as tree_mod
    real = tree_mod.Tree.from_arrays.__func__

    def from_arrays(cls, arrays, dataset, learning_rate=1.0):
        t = real(cls, arrays, dataset, learning_rate)
        if t.num_leaves > 1:
            a, b = np.argsort(t.leaf_count[:t.num_leaves])[-2:]
            t.leaf_value[a], t.leaf_value[b] = t.leaf_value[b], t.leaf_value[a]
        return t
    monkeypatch.setattr(tree_mod.Tree, "from_arrays", classmethod(from_arrays))


def _altered_valid_metric(monkeypatch):
    """An answer altered where it is produced: the validation metrics the
    program reports are a thousandth off."""
    import lightgbm_tpu as lgb
    real = lgb.Booster.eval_valid

    def eval_valid(self, *a, **kw):
        return [(d, n, v * 1.001, hb) for d, n, v, hb in real(self, *a, **kw)]
    monkeypatch.setattr(lgb.Booster, "eval_valid", eval_valid)


@pytest.mark.parametrize("fault", [_frozen_state, _half_batch, _altered_answer,
                                   _altered_valid_metric])
@pytest.mark.parametrize("config_name", list(CONFIGS))
def test_broken_timed_path_is_not_correct(config_name, monkeypatch, fault):
    from benchmarks.jobs import train
    fault(monkeypatch)
    ctx, _ = _ctx(config_name)
    ctx["control"] = []
    out = train.run(ctx)
    assert out["attempted"] >= 1
    assert not out["correct"], out["compared"]
