"""``benchmarks/phase_reduce.py`` on a recorded run: a device trace of fourteen
events, a scope table and a span list (``phase_fixture.json``, beside this
file), every expected number reckoned by hand from the fixture's figures.

The window is the span of the ``bench/...`` annotations, 900 000 to
12 500 000 ns: 11.6 ms.  The spans' clock runs 7 s ahead of the trace's, and
the two ``bench/update`` annotations open 20.0 and 20.4 us before their
``lgbm/update`` spans.
"""
import copy
import importlib.util
import json
import os

import pytest

from benchmarks import phase_reduce
from lightgbm_tpu.obs.scopes import op_key

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WINDOW_S = 11.6e-3
KERNEL_WORDS = ['custom_call_target="tpu_custom_call"']
NEW_METRICS = (
    "partition_share", "hist_gather_share", "split_search_share",
    "round_select_share", "boost_step_share", "unscoped_share",
    "frontier_rounds_per_tree", "partition_useful_row_share", "find_bins_s",
    "bin_values_s", "compile_load_s", "election_s", "compiles_in_window",
    "host_metric_s_per_tree", "idle_unattributed_share")


@pytest.fixture
def fixture():
    with open(os.path.join(HERE, "phase_fixture.json")) as f:
        return json.load(f)


def _reduce(fx):
    return phase_reduce.reduce(fx["trace"], WINDOW_S, fx["scopes"],
                               fx["spans"], op_key, KERNEL_WORDS)


def test_shares_by_scope(fixture):
    out = _reduce(fixture)
    m = out["metrics"]
    # decide 3.0 + rank 1.0 + scatter 0.5 ms of 11.6
    assert m["partition_share"] == pytest.approx(100 * 4.5 / 11.6)
    assert m["hist_gather_share"] == pytest.approx(100 * 0.8 / 11.6)
    assert m["split_search_share"] == pytest.approx(100 * 0.2 / 11.6)
    # the sort under select 0.3 + finalize 0.4
    assert m["round_select_share"] == pytest.approx(100 * 0.7 / 11.6)
    # valid_traverse 0.6 + gradients 0.15
    assert m["boost_step_share"] == pytest.approx(100 * 0.75 / 11.6)
    sec = out["scope_seconds"]
    # the three children of the partition are printed apart
    assert sec["lgbm/frontier_round/partition/decide"] == pytest.approx(3.0e-3)
    assert sec["lgbm/frontier_round/partition/rank"] == pytest.approx(1.0e-3)
    assert sec["lgbm/frontier_round/partition/scatter"] == pytest.approx(0.5e-3)
    # the Mosaic call is hist_kernel_share's, whatever scope it carries
    assert sec["mosaic"] == pytest.approx(1.0e-3)
    assert "lgbm/frontier_round/hist" not in sec
    # self time: the loop's 8 ms less the 6.8 ms of its body's operations; a
    # scope no share reads is printed, not hidden
    assert sec["lgbm/frontier_round"] == pytest.approx(1.2e-3)
    assert out["seconds_no_share_reads"] == pytest.approx(1.2e-3)
    # everything the device did is in one place and one only
    assert sum(sec.values()) == pytest.approx(9.55e-3)
    assert out["idle_s"] == pytest.approx(11.6e-3 - 9.55e-3)


def test_ambiguous_unscoped_and_unknown_land_in_unscoped_share(fixture):
    out = _reduce(fixture)
    sec = out["scope_seconds"]
    assert sec["ambiguous"] == pytest.approx(0.25e-3)   # %fusion.50: two programs' key
    assert sec[""] == pytest.approx(0.1e-3)             # %copy.7: under no lgbm/ scope
    assert sec["unknown"] == pytest.approx(0.05e-3)     # %fusion.99: not in the table
    assert out["metrics"]["unscoped_share"] == pytest.approx(100 * 0.4 / 11.6)
    # with no table at all every operation outside the Mosaic calls is unknown
    fixture["scopes"] = {}
    m = _reduce(fixture)["metrics"]
    assert m["unscoped_share"] == pytest.approx(100 * 8.55 / 11.6)
    assert m["partition_share"] == 0.0


def test_idle_gaps_are_named_by_the_deepest_covering_span(fixture):
    out = _reduce(fixture)
    gaps = out["idle_gaps"]
    # longest first: 1.4 ms between the validation update and the next
    # tree's gradients; lgbm/eval covers 1.27 ms of it and its AUC child 0.98,
    # both more than half, and the child is deeper
    assert gaps[0] == ["lgbm/eval/metric", pytest.approx(1.4e-3)]
    assert gaps[1] == ["lgbm/eval/metric", pytest.approx(0.25e-3)]
    named = {round(sec * 1e9): name for name, sec in gaps}
    assert len(gaps) == 7
    assert sorted(named) == [50_000, 100_000, 250_000, 1_400_000]
    # the gap before the first operation: lgbm/update covers it whole, its
    # grow_dispatch child 95.2 us of its 100
    assert ["lgbm/update/grow_dispatch", pytest.approx(0.1e-3)] in gaps
    assert ["lgbm/eval/wait", pytest.approx(0.1e-3)] in gaps
    # 4.8 us before grow_dispatch opens, 100 us between the AUC and the next
    # drain, 50.2 us after the last metric: under no leaf span
    assert out["metrics"]["idle_unattributed_share"] == pytest.approx(
        100 * 155_000 / 2_050_000)


def test_gap_under_no_span_is_called_so():
    assert phase_reduce.name_gap((0, 10), [], 0) == "no span"
    far = [{"id": 1, "parent": None, "name": "lgbm/eval", "start": 50,
            "end": 60, "depth": 0}]
    assert phase_reduce.name_gap((0, 10), far, 0) == "no span"
    assert phase_reduce.unattributed_ns([(0, 10)], far, 0) == 10
    # a span that covers less than half still names the gap when it is alone
    assert phase_reduce.name_gap((0, 10), far, -47) == "lgbm/eval"


def test_clock_offset_from_the_pairs(fixture):
    out = _reduce(fixture)
    # the median of -7 s - 20.0 us and -7 s - 20.4 us
    assert out["clock"] == {"offset_ns": -7_000_020_200, "residual_ns": 200,
                            "pairs": 2}


def test_no_value_when_the_pairs_disagree_by_2ms(fixture):
    second = [s for s in fixture["spans"] if s["name"] == "lgbm/update"][-1]
    second["start"] += 2_000_000
    assert phase_reduce.clock_offset(fixture["trace"], fixture["spans"]) is None
    assert _reduce(fixture) is None
    # and none without a pair to read the clock from
    fixture["trace"]["host"] = [e for e in fixture["trace"]["host"]
                                if e[0] != "bench/update"]
    assert _reduce(fixture) is None


def test_counters_and_host_spans(fixture):
    m = _reduce(fixture)["metrics"]
    # the window issued trees 1 and 2; their counters came with the drains
    # (tree 2's after the window, with the dump); tree 0 is the warm-up's
    assert m["frontier_rounds_per_tree"] == (20 + 22) / 2
    assert m["partition_useful_row_share"] == pytest.approx(
        100 * (36864 + 45056) / (81920 + 90112))
    assert m["find_bins_s"] == pytest.approx(2.0)
    # bin_values 3.0 + reference_bin 0.5 + to_2d_float 2 x 0.25
    assert m["bin_values_s"] == pytest.approx(4.0)
    assert m["election_s"] == pytest.approx(1.5)
    assert m["compile_load_s"] == pytest.approx(5.0)
    assert m["compiles_in_window"] == 0
    # lgbm/eval 10.78 + 0.42 ms, less its waits 9.51 + 0.2 ms, over two trees
    assert m["host_metric_s_per_tree"] == pytest.approx(
        (11.2e-3 - 9.71e-3) / 2)
    assert set(m) == set(NEW_METRICS)


def test_compile_in_the_window_is_counted_with_its_parent(fixture):
    spans = fixture["spans"]
    spans.append({"id": 70, "parent": 40, "name": "lgbm/compile",
                  "start": 7_000_000_000 + 11_990_000,
                  "end": 7_000_000_000 + 12_020_000, "tid": 1, "depth": 1,
                  "iteration": 2,
                  "args": {"fun": "jit(bag_sample)", "seconds": 3e-5,
                           "cache_hit": False}})
    out = _reduce(fixture)
    assert out["metrics"]["compiles_in_window"] == 1
    assert out["metrics"]["compile_load_s"] == pytest.approx(5.0)
    assert out["compiles_in_window"] == [
        {"fun": "jit(bag_sample)", "seconds": 3e-5, "cache_hit": False,
         "parent": "lgbm/update"}]


def test_table_is_none_without_a_device_plane(capsys):
    run = {"trace": None, "window_s": 1.0}
    assert phase_reduce.table(run) is None
    assert phase_reduce.value(run, "partition_share") is None
    assert capsys.readouterr().out == ""


def test_table_reads_the_process(fixture, monkeypatch, capsys):
    """``table`` joins the run's trace with what ``lightgbm_tpu.obs`` holds in
    this process, prints the whole table as one JSON line, once."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs.tracer import Span, Tracer

    tracer = Tracer()
    for s in fixture["spans"]:
        tracer._keep(Span(**s))
    monkeypatch.setattr(obs, "get_tracer", lambda: tracer)
    monkeypatch.setattr(obs, "device_scopes", lambda: dict(fixture["scopes"]))
    run = {"trace": fixture["trace"], "window_s": WINDOW_S}
    want = _reduce(copy.deepcopy(fixture))["metrics"]
    assert phase_reduce.value(run, "partition_share") == want["partition_share"]
    assert phase_reduce.value(run, "election_s") == want["election_s"]
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    table = json.loads(lines[0])["phase_table"]
    assert table["metrics"] == pytest.approx(want)
    assert table["ops_by_scope"][0][2] == "lgbm/frontier_round/partition/decide"
    assert table["span_seconds"]["lgbm/eval/wait"] == pytest.approx(
        [9.71e-3, 9.71e-3])


def _reader(name):
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_takes_its_number_from_the_table(metric, fixture):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert metric + ".train" in listed
    read = _reader(metric + ".train")
    run = {"trace": fixture["trace"], "window_s": WINDOW_S,
           "_phase_table": _reduce(fixture)}
    assert read(run) == run["_phase_table"]["metrics"][metric]
    # no table (an older program, a rehearsal on the CPU): no value, no raise
    assert read({"trace": None, "window_s": 1.0}) is None


# ---- PR 28's readers: counters on the drain spans, a scope of their own ----
def _program_with(monkeypatch, spans):
    """Put ``spans`` where ``benchmarks/leaf_reduce.py`` looks for them."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs.tracer import Span, Tracer

    tracer = Tracer()
    for s in spans:
        tracer._keep(Span(**s))
    monkeypatch.setattr(obs, "get_tracer", lambda: tracer)


LEAF_COUNTERS = {      # by tree_iteration; 0 is the warm-up's, outside the window
    0: {"leaves": 255, "leaves_under_100_rows": 10, "tree_depth": 30},
    1: {"leaves": 255, "leaves_under_100_rows": 200, "tree_depth": 14},
    2: {"leaves": 200, "leaves_under_100_rows": 164, "tree_depth": 17}}


@pytest.mark.parametrize("metric,want", [
    ("small_leaf_share", 100.0 * (200 + 164) / (255 + 200)),
    ("tree_depth", (14 + 17) / 2)])
def test_leaf_counter_readers(metric, want, fixture, monkeypatch):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert set(listed[metric + ".train"]["workloads"]) == {
        "criteo67-msh100.train-valid", "criteo67.train-valid"}
    read = _reader(metric + ".train")
    run = {"trace": fixture["trace"], "window_s": WINDOW_S}
    # a program whose drains carry no leaf counters (the parent's): no value
    _program_with(monkeypatch, fixture["spans"])
    assert read(run) is None
    for s in fixture["spans"]:
        if s["name"] == "lgbm/update/drain":
            s["args"].update(LEAF_COUNTERS[s["args"]["tree_iteration"]],
                             min_leaf_hessian=0.6)
    _program_with(monkeypatch, fixture["spans"])
    assert read(run) == pytest.approx(want)
    # a rehearsal on the CPU has no device plane: no value, no raise
    assert read({"trace": None, "window_s": 1.0}) is None


def test_sum_repair_share_reader(fixture, monkeypatch):
    from lightgbm_tpu.obs import scopes
    assert "lgbm/sum_repair" in scopes.SCOPES
    read = _reader("sum_repair_share.train")
    run = {"trace": fixture["trace"], "window_s": WINDOW_S,
           "_phase_table": _reduce(fixture)}
    # the program names the scope and no operation carries it
    assert read(run) == 0.0
    # copy.7, 0.05 ms in the fixture, put under the scope
    fixture["scopes"]["copy.7 s32[4096]"] = "lgbm/sum_repair"
    run["_phase_table"] = table = _reduce(fixture)
    assert table["scope_seconds"]["lgbm/sum_repair"] > 0
    assert read(run) == pytest.approx(
        100 * table["scope_seconds"]["lgbm/sum_repair"] / WINDOW_S)
    # a program that names no such scope (the parent's), and no device plane
    monkeypatch.setattr(scopes, "SCOPES", tuple(
        s for s in scopes.SCOPES if s != "lgbm/sum_repair"))
    assert read(run) is None
    assert read({"trace": None, "window_s": 1.0}) is None
