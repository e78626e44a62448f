"""Observability-subsystem tests (lightgbm_tpu/obs, docs/OBSERVABILITY.md).

CPU-only and fast.  Covers ISSUE 16's acceptance criteria: the structured
event schema round-trips and is thread-safe; the report layer tolerates
the legacy (pre-schema) journal lines the six old writers produced; the
serve-path metrics are correct under concurrent load; and a CPU training
run emits one schema-valid event per boosting iteration and leaves nested
spans in the process tracer.
"""
import glob
import json
import os
import threading

import numpy as np
import pytest

from lightgbm_tpu.obs import (EventLog, SCHEMA_VERSION, classify_record,
                              make_event, new_run_id, validate_event)
from lightgbm_tpu import obs
from lightgbm_tpu.obs import metrics as obs_metrics
from lightgbm_tpu.obs import report as obs_report
from lightgbm_tpu.obs.events import SUMMARY_EVENT, perf_log_path
from lightgbm_tpu.obs.tracer import Tracer, get_tracer

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# events: schema round-trip, classification, EventLog
def test_make_event_envelope_and_validate():
    rec = make_event("train_iter", new_run_id(), iteration=3, trees=1)
    assert validate_event(rec) == []
    assert rec["schema_version"] == SCHEMA_VERSION
    assert rec["event"] == "train_iter"
    assert rec["stage"] == "train_iter"      # legacy-reader mirror
    assert rec["iteration"] == 3
    # envelope keys are reserved: caller values must not survive
    rec2 = make_event("x", "rid", schema_version=99, ts="forged")
    assert rec2["schema_version"] == SCHEMA_VERSION
    assert isinstance(rec2["ts"], float)
    assert validate_event(rec2) == []
    # a caller-carried stage wins over the mirror
    rec3 = make_event("bench_record", "rid", stage="train_stream")
    assert rec3["stage"] == "train_stream"


def test_validate_event_rejects_malformed():
    assert validate_event("not a dict")
    assert validate_event({"event": "x"})                 # missing envelope
    bad = make_event("x", new_run_id())
    bad["ts"] = "noon"
    assert any("ts" in e for e in validate_event(bad))
    bad2 = make_event("x", new_run_id())
    bad2["run_id"] = ""
    assert any("run_id" in e for e in validate_event(bad2))


def test_classify_record_three_kinds():
    ev = make_event("suite_record", new_run_id())
    assert classify_record(json.dumps(ev))[0] == "event"
    # pre-schema writer shapes from the repo journal
    kind, rec = classify_record('{"stage": "bench_stream", "ok": true}')
    assert kind == "legacy" and rec["stage"] == "bench_stream"
    assert classify_record("not json {")[0] == "bad"
    assert classify_record("[1, 2]")[0] == "bad"
    assert classify_record("")[0] == "bad"
    # schema-stamped but invalid: classified bad, record still returned
    forged = dict(ev, run_id=7)
    assert classify_record(json.dumps(forged))[0] == "bad"


def test_eventlog_round_trip_and_summary_contract(tmp_path, capsys):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path, echo=True)
    log.emit("suite_record", phase="hist", ms=1.5)
    log.summary(metric="throughput", unit="rows/sec", value=1e6)
    out = capsys.readouterr().out.strip().splitlines()
    # echo printed both; the summary is the LAST stdout line and is valid
    last = json.loads(out[-1])
    assert last["event"] == SUMMARY_EVENT and validate_event(last) == []
    with open(path) as f:
        lines = f.readlines()
    assert len(lines) == 2
    kinds = [classify_record(ln) for ln in lines]
    assert [k for k, _ in kinds] == ["event", "event"]
    assert kinds[0][1]["phase"] == "hist"
    # one run_id correlates every record of the log
    assert kinds[0][1]["run_id"] == kinds[1][1]["run_id"] == log.run_id


def test_eventlog_summary_refuses_unserializable(tmp_path):
    log = EventLog(str(tmp_path / "e.jsonl"))
    with pytest.raises(TypeError):
        log.summary(metric="x", value=object())   # fails loudly, not later
    assert not os.path.exists(log.path) or not open(log.path).read()


def test_eventlog_default_honors_watcher_perf_log(tmp_path, monkeypatch):
    target = str(tmp_path / "window" / "perf.jsonl")
    monkeypatch.setenv("WATCHER_PERF_LOG", target)
    assert perf_log_path() == target
    log = EventLog.default()
    assert log.path == target
    assert EventLog.default() is log          # one default per path
    log.emit("watcher_probe", ok=True)        # creates parent dirs
    assert classify_record(open(target).read())[0] == "event"


def test_eventlog_concurrent_writers_interleave_whole_lines(tmp_path):
    path = str(tmp_path / "c.jsonl")
    log = EventLog(path)
    n_threads, n_each = 8, 50

    def writer(i):
        for j in range(n_each):
            log.emit("stress", thread=i, seq=j)

    ts = [threading.Thread(target=writer, args=(i,)) for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    with open(path) as f:
        recs = [classify_record(ln) for ln in f]
    assert len(recs) == n_threads * n_each
    assert all(k == "event" for k, _ in recs)   # no torn/fragmented lines
    seen = {(r["thread"], r["seq"]) for _, r in recs}
    assert len(seen) == n_threads * n_each


# ---------------------------------------------------------------------------
# report: legacy tolerance, rendering
def test_report_tolerates_mixed_journal(tmp_path):
    path = str(tmp_path / "perf.jsonl")
    rid = new_run_id()
    with open(path, "w") as f:
        f.write('{"stage": "bench_stream", "rows": 100, "ok": true}\n')
        f.write('{"metric": "serve_throughput", "value": 5.0, '
                '"unit": "rows/sec"}\n')
        f.write("garbage line\n")
        f.write("\n")                                     # blanks skipped
        f.write(json.dumps(make_event("train_iter", rid, iteration=0)) + "\n")
        f.write(json.dumps(make_event(SUMMARY_EVENT, rid, metric="m",
                                      value=1)) + "\n")
    loaded = obs_report.load_perf_log(path)
    assert loaded["total"] == 5                           # blank not counted
    assert len(loaded["events"]) == 2
    assert len(loaded["legacy"]) == 2
    assert loaded["bad"] == 1
    summ = obs_report.summarize(loaded)
    assert summ["counts"] == {"total": 5, "schema_events": 2, "legacy": 2,
                              "bad": 1}
    assert summ["runs"] == 1
    assert summ["by_stage"]["bench_stream"] == 1
    # legacy metric-style line and the schema summary both count as results
    assert len(summ["recent_summaries"]) == 2
    md = obs_report.render_markdown(summ)
    assert "bench_stream" in md and "train_iter" in md
    json.loads(obs_report.render_json(summ))              # valid JSON


def test_report_renders_repo_journal_and_missing_file(tmp_path):
    # the real pre-subsystem journal: every line must classify, none lost
    repo_journal = os.path.join(REPO, "perf_results.jsonl")
    if os.path.exists(repo_journal):
        loaded = obs_report.load_perf_log(repo_journal)
        with open(repo_journal) as f:
            n_lines = sum(1 for ln in f if ln.strip())
        assert loaded["total"] == n_lines
        assert loaded["bad"] == 0
        obs_report.render_markdown(obs_report.summarize(loaded))
    # a fresh checkout has no journal: report still renders
    empty = obs_report.load_perf_log(str(tmp_path / "absent.jsonl"))
    assert empty["total"] == 0
    md = obs_report.render_markdown(obs_report.summarize(empty))
    assert md


def test_obs_report_cli(tmp_path, capsys):
    path = str(tmp_path / "p.jsonl")
    EventLog(path).emit("train_iter", iteration=0)
    assert obs_report.main(["--path", path, "--format", "json",
                            "--no-metrics"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["counts"]["schema_events"] == 1
    out_md = str(tmp_path / "report.md")
    assert obs_report.main(["--path", path, "--out", out_md]) == 0
    assert "train_iter" in open(out_md).read()


# ---------------------------------------------------------------------------
# metrics: registry semantics + concurrency
def test_metrics_registry_types_and_reset():
    obs_metrics.reset()
    c = obs_metrics.counter("t.count")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = obs_metrics.gauge("t.depth")
    g.set(3.0)
    g.set_max(2.0)        # lower: keeps max
    g.set_max(7.0)
    assert g.value == 7.0
    with pytest.raises(TypeError):
        obs_metrics.gauge("t.count")       # name registered as a counter
    snap = obs_metrics.snapshot()
    assert snap["t.count"] == {"type": "counter", "value": 5}
    obs_metrics.reset()
    assert obs_metrics.counter("t.count").value == 0


def test_histogram_percentiles_exact_then_sampled():
    h = obs_metrics.Histogram("h", reservoir_size=1000)
    for v in range(100):                   # below reservoir: exact
        h.observe(float(v))
    assert h.count == 100
    snap = h.snapshot()
    assert snap["min"] == 0.0 and snap["max"] == 99.0
    assert snap["p50"] == pytest.approx(50.0, abs=1)
    assert snap["p99"] == pytest.approx(98.0, abs=1)
    # beyond the reservoir the percentiles stay statistically sane
    small = obs_metrics.Histogram("s", reservoir_size=64)
    for v in range(10_000):
        small.observe(float(v % 1000))
    assert small.count == 10_000
    assert 200.0 <= small.snapshot()["p50"] <= 800.0


def test_counters_thread_safe():
    c = obs_metrics.Counter("race")
    n_threads, n_each = 8, 2000

    def bump():
        for _ in range(n_each):
            c.inc()

    ts = [threading.Thread(target=bump) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * n_each


# ---------------------------------------------------------------------------
# serve-path metrics under concurrent load
def test_batcher_metrics_under_concurrent_load():
    from lightgbm_tpu.serve import MicroBatcher

    obs_metrics.reset()
    mb = MicroBatcher(lambda xb: xb[:, 0] * 2.0, max_batch_rows=64,
                      deadline_ms=2.0, queue_depth=256, name="obs")
    n_threads, n_each = 4, 20
    errs = []

    def client(i):
        rng = np.random.default_rng(i)
        for _ in range(n_each):
            x = rng.normal(size=(3, 5))
            try:
                out = mb.predict(x, timeout=30)
                assert np.array_equal(out, x[:, 0] * 2.0)
            except Exception as e:      # pragma: no cover - diagnostic
                errs.append(e)

    try:
        ts = [threading.Thread(target=client, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        mb.close()
    assert not errs
    total = n_threads * n_each
    snap = obs_metrics.snapshot()
    assert snap["serve.requests"]["value"] == total
    assert snap["serve.shed"]["value"] == 0
    lat = snap["serve.request_ms"]
    assert lat["count"] == total
    assert 0.0 < lat["p50"] <= lat["p99"]       # online p50-p99 populated
    rows = snap["serve.batch_rows"]
    assert rows["count"] >= 1
    # coalescing conserves rows: batched rows == submitted rows
    assert rows["sum"] == pytest.approx(total * 3)
    assert snap["serve.batch_requests"]["max"] <= 64 / 3 + 1


def test_batcher_shed_metric():
    from lightgbm_tpu.serve import MicroBatcher, QueueSaturatedError

    obs_metrics.reset()
    release = threading.Event()
    mb = MicroBatcher(lambda xb: (release.wait(10), np.zeros(xb.shape[0]))[1],
                      max_batch_rows=1, deadline_ms=0.0, queue_depth=1,
                      name="shed")
    try:
        first = mb.submit(np.zeros((1, 2)))   # worker blocks inside predict
        import time as _time
        _time.sleep(0.1)
        pend = mb.submit(np.zeros((1, 2)))    # queue now full
        with pytest.raises(QueueSaturatedError):
            mb.submit(np.zeros((1, 2)))
        release.set()
        first.result(10)
        pend.result(10)
    finally:
        release.set()
        mb.close()
    snap = obs_metrics.snapshot()
    assert snap["serve.shed"]["value"] == 1
    assert snap["serve.requests"]["value"] == 2   # shed request not counted


# ---------------------------------------------------------------------------
# tracer
def test_tracer_nested_spans_parent_and_iteration():
    tr = Tracer()
    with tr.span("outer", iteration=7):
        with tr.span("inner", leaf=3):
            pass
        tr.begin("inner")
        tr.end("inner", found="late")       # end() may add what it learned
    with tr.span("alone"):
        pass
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "inner", "outer", "alone"]
    assert [s.depth for s in spans] == [1, 1, 0, 0]
    outer = spans[2]
    assert outer.parent is None and spans[3].parent is None
    assert spans[0].parent == outer.id and spans[1].parent == outer.id
    assert len({s.id for s in spans}) == 4
    # children inherit the iteration of the span that caused them
    assert [s.iteration for s in spans] == [7, 7, 7, None]
    assert spans[0].args == {"leaf": 3} and spans[1].args == {"found": "late"}
    # on time.time_ns(), the clock the profiler stamps host events with
    import time
    assert abs(outer.end - time.time_ns()) < 60e9
    assert outer.start <= spans[0].start <= spans[0].end <= outer.end
    assert outer.duration == (outer.end - outer.start) / 1e9
    agg = tr.aggregate()
    assert agg["inner"]["count"] == 2
    assert set(outer.as_dict()) == {"id", "parent", "name", "start", "end",
                                    "tid", "depth", "iteration", "args"}


def test_tracer_unbalanced_end_is_ignored_and_capacity_bounds():
    tr = Tracer(capacity=2)
    tr.end("never-opened")                      # must not raise
    for i in range(4):
        with tr.span(f"s{i}"):
            pass
    # a ring: the oldest go, the newest stay
    assert [s.name for s in tr.spans()] == ["s2", "s3"] and tr.dropped == 2
    tr.reset()
    assert tr.spans() == [] and tr.dropped == 0


def test_tracer_threads_get_independent_stacks():
    tr = Tracer()
    barrier = threading.Barrier(2)

    def worker(i):
        with tr.span("work", who=i):
            barrier.wait(5)                     # both spans open at once

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    spans = tr.spans()
    assert len(spans) == 2
    assert spans[0].tid != spans[1].tid
    assert all(s.depth == 0 for s in spans)     # no cross-thread nesting


def test_spans_record_without_telemetry_and_annotate_the_profiler(tmp_path):
    """What replaced the timer bridge: the span sites of the boosting loop
    feed the process tracer with ``obs_telemetry`` off, and every span is
    also a ``jax.profiler`` annotation with no knob (an operator's own
    capture shows them)."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config

    # no parameter turns the spans or their annotations on or off
    assert not [f for f in vars(Config()) if "trace" in f]
    get_tracer().reset()
    rng = np.random.default_rng(3)
    X = rng.normal(size=(300, 5))
    y = X[:, 0] - X[:, 1]
    p = {"objective": "regression", "num_leaves": 7, "verbose": -1}
    jax.profiler.start_trace(str(tmp_path))
    try:
        bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                        num_boost_round=3)
        bst.dump_model()
    finally:
        jax.profiler.stop_trace()
    spans = get_tracer().spans()
    names = [s.name for s in spans]
    by_id = {s.id: s for s in spans}
    for want in ("lgbm/dataset/construct", "lgbm/dataset/construct/find_bins",
                 "lgbm/dataset/construct/bin_values", "lgbm/booster/init",
                 "lgbm/booster/init/to_device", "lgbm/update",
                 "lgbm/update/gradients", "lgbm/update/grow_dispatch",
                 "lgbm/update/score_dispatch", "lgbm/update/drain",
                 "lgbm/dump"):
        assert want in names, want
    updates = [s for s in spans if s.name == "lgbm/update"]
    assert [s.iteration for s in updates] == [0, 1, 2]
    for s in spans:
        if s.name.startswith("lgbm/update/") and s.parent is not None:
            # the last tree's drain is caused by the dump, not by an update
            assert s.name.startswith(by_id[s.parent].name) or (
                s.name == "lgbm/update/drain"
                and by_id[s.parent].name == "lgbm/dump")
    # a dozen spans a tree, not one per split or row
    assert len([s for s in spans if s.iteration == 1]) <= 16
    # the capture holds the same names as host events
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert paths
    seen = {ev.name for plane in ProfileData.from_file(paths[-1]).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for ev in line.events}
    assert {"lgbm/update", "lgbm/update/grow_dispatch"} <= seen


# ---------------------------------------------------------------------------
# boosting loop: per-iteration events + nested training trace
@pytest.fixture
def train_telemetry_env(tmp_path):
    """Isolated event sink + clean global tracer around one run."""
    path = str(tmp_path / "train_events.jsonl")
    obs_metrics.reset()
    get_tracer().reset()
    yield path
    get_tracer().reset()


def test_training_emits_one_event_per_iteration(train_telemetry_env, tmp_path):
    import lightgbm_tpu as lgb

    path = train_telemetry_env
    rounds = 5
    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.1 * rng.normal(size=400))
    p = {"objective": "regression", "num_leaves": 7, "verbose": -1,
         "obs_telemetry": True, "obs_events_path": path}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=rounds)
    bst.predict(X[:10])                   # materialize pending host trees
    with open(path) as f:
        recs = [classify_record(ln) for ln in f]
    assert all(k == "event" for k, _ in recs)
    iters = [r for _, r in recs if r["event"] == "train_iter"]
    trees = [r for _, r in recs if r["event"] == "train_tree"]
    assert len(iters) == rounds           # exactly one per boosting round
    assert [r["iteration"] for r in iters] == list(range(rounds))
    assert len({r["run_id"] for _, r in recs}) == 1
    # the iteration's spans under their own names: host seconds of issuing
    # and waiting, not a device phase's seconds
    assert {"lgbm/update", "lgbm/update/gradients",
            "lgbm/update/grow_dispatch",
            "lgbm/update/score_dispatch"} <= set(iters[0]["span_seconds"])
    assert "lgbm/update/drain" in iters[1]["span_seconds"]
    # per-tree stats landed via the async drain (no forced sync)
    assert len(trees) >= rounds - 1
    assert all(t["num_leaves"] >= 2 for t in trees)
    assert all(t["split_gain"]["splits"] == t["num_leaves"] - 1
               for t in trees)
    # metrics registry mirrors the stream
    snap = obs_metrics.snapshot()
    assert snap["train.iterations"]["value"] == rounds
    assert snap["train.grow_dispatch_seconds"]["count"] == rounds
    assert snap["train.update_seconds"]["count"] == rounds
    assert snap["train.num_leaves"]["count"] == len(trees)
    # the global tracer holds nested spans: the loop's steps under the
    # per-iteration span, each tagged with its iteration
    spans = get_tracer().spans()
    step_spans = [s for s in spans if s.name == "lgbm/update"]
    assert [s.iteration for s in step_spans] == list(range(rounds))
    ids = {s.id for s in step_spans}
    nested = [s for s in spans if s.name.startswith("lgbm/update/")
              and s.name != "lgbm/update/drain"]
    assert nested and all(s.depth >= 1 and s.parent in ids for s in nested)


def _binary_job(seed=0, rows=1500, **params):
    """A small binary job on the one-chip frontier fast path with a
    validation set."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows + 500, 8))
    y = (X[:, 0] + X[:, 1] ** 2 + 0.3 * rng.normal(size=len(X)) > 0.5
         ).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "min_data_in_leaf": 5, "metric": "binary_logloss,auc", **params}
    train = lgb.Dataset(X[:rows], label=y[:rows], params=p)
    valid = lgb.Dataset(X[rows:], label=y[rows:], reference=train, params=p)
    bst = lgb.Booster(p, train)
    bst.add_valid(valid, "valid")
    return bst, train, valid


def test_compile_span_is_parented_to_the_step_that_compiled(
        train_telemetry_env):
    """One ``jax.monitoring`` listener turns every compilation into an
    ``lgbm/compile`` span under whatever span was open: a shape first jitted
    inside ``update()`` shows with that step as its parent."""
    assert obs.install_compile_listener()       # idempotent
    bst, _, _ = _binary_job(seed=11, rows=1237)     # shapes no other test has
    bst.update()
    bst.eval_valid()
    spans = get_tracer().spans()
    by_id = {s.id: s for s in spans}
    compiles = [s for s in spans if s.name == "lgbm/compile"]
    grow = [s for s in compiles if "grow_tree_step" in s.args["fun"]]
    assert len(grow) == 1               # compiled once; the scope table read
    #                                     the executable jax already held
    assert by_id[grow[0].parent].name == "lgbm/update/grow_dispatch"
    assert grow[0].iteration == 0
    assert grow[0].args["seconds"] == pytest.approx(grow[0].duration)
    assert "cache_hit" in grow[0].args
    for s in compiles:
        assert s.parent is not None and by_id[s.parent].name.startswith("lgbm/")
    snap = obs_metrics.snapshot()
    assert snap["compile.count"]["value"] == len(compiles)
    # a second tree compiles nothing
    bst.update()
    assert len([s for s in get_tracer().spans()
                if s.name == "lgbm/compile"]) == len(compiles)
    # the metrics and their wait for the device are apart
    names = [s.name for s in get_tracer().spans()]
    for want in ("lgbm/eval", "lgbm/eval/wait", "lgbm/eval/fetch",
                 "lgbm/eval/metric", "lgbm/booster/init",
                 "lgbm/dataset/construct/reference_bin"):
        assert want in names, want
    metrics = [s.args["metric"] for s in get_tracer().spans()
               if s.name == "lgbm/eval/metric"]
    assert metrics[:2] == ["BinaryLoglossMetric", "AUCMetric"]


def test_device_scopes_hold_every_phase_and_outlive_the_booster():
    import gc

    import jax
    from lightgbm_tpu.obs import scopes

    gc.collect()
    live_before = len(jax.live_arrays())
    scopes.reset_scopes()
    bst, train, valid = _binary_job(seed=5, rows=1100, bagging_fraction=0.5,
                                    bagging_freq=1)
    for _ in range(2):
        bst.update()
    bst.eval_valid()
    table = obs.device_scopes()
    assert table and all(type(k) is str and type(v) is str
                         for k, v in table.items())
    found = set(table.values())
    R = "lgbm/frontier_round/"
    # partition/decide is elementwise work in row order (no gather of its
    # own since PR 31): XLA:CPU fuses all of it into rank's sums and
    # scatter's sort key, and a fusion carries one scope; on the chip its
    # column reads are operations of their own
    for scope in ("lgbm/gradients", "lgbm/sample", "lgbm/root",
                  R + "select", R + "partition/rank",
                  R + "partition/scatter", R + "bookkeeping",
                  R + "hist_gather", R + "hist", "lgbm/split_search",
                  "lgbm/finalize", "lgbm/score_update",
                  "lgbm/valid_traverse"):
        assert scope in found, scope
    assert found <= set(scopes.SCOPES) | {"", scopes.AMBIGUOUS}
    # filled during the first update() and not again
    assert obs.device_scopes() == table
    recorded = [s.args["program"] for s in get_tracer().spans()
                if s.name == "lgbm/scope_table"]
    assert "train.grow_tree" in recorded
    assert len(recorded) == len(set(recorded))
    del bst, train, valid
    gc.collect()
    assert obs.device_scopes() == table         # the table is still there
    assert len(jax.live_arrays()) <= live_before   # and holds no device array


def test_op_key_and_scope_of_read_hlo_lines():
    from lightgbm_tpu.obs.scopes import op_key, scope_of

    line = ("  %fusion.968 = s32[13281250]{0:T(1024)} fusion(s32[13281251]"
            "{0:T(1024)S(1)} %custom-call.262), kind=kCustom, calls=%f, "
            'metadata={op_name="jit(grow_tree_step)/jit(main)/lgbm/'
            'frontier_round/while/body/partition/rank/gather"}')
    assert op_key(line) == "fusion.968 s32[13281250]"
    # a device trace names the event by the same line without the metadata
    assert op_key(line.split(", metadata")[0].strip()) == op_key(line)
    assert op_key("ROOT %t = (s32[], f32[4]{0}) tuple(%a, %b)") == "t (...)"
    assert op_key("not an instruction") is None
    pre = "jit(grow_tree_step)/jit(main)/"
    assert scope_of(pre + "lgbm/frontier_round/while/body/partition/rank/"
                    "gather") == "lgbm/frontier_round/partition/rank"
    # control flow and transforms are no scopes
    assert scope_of(pre + "lgbm/frontier_round/while/body/cond/branch_1_fun/"
                    "hist/mul") == "lgbm/frontier_round/hist"
    assert scope_of(pre + "lgbm/frontier_round/while/body/hist_gather/"
                    "jit(_take)/jit(_where)/select_n") \
        == "lgbm/frontier_round/hist_gather"
    assert scope_of(pre + "lgbm/frontier_round/while/body/lgbm/split_search/"
                    "vmap(reduce_max)") == "lgbm/split_search"
    assert scope_of(pre + "lgbm/frontier_round/while/cond/select/top_k") \
        == "lgbm/frontier_round/select"
    assert scope_of(pre + "lgbm/frontier_round/while/body/copy") \
        == "lgbm/frontier_round"
    assert scope_of(pre + "convert_element_type") == ""


def _frontier_counts(num_leaves, seed=0, rows=2000):
    """(rounds, rows_passed, rows_selected, dumped trees) of a two-tree job."""
    obs_metrics.reset()
    bst, _, _ = _binary_job(seed=seed, rows=rows, num_leaves=num_leaves)
    bst.update()
    bst.update()
    trees = bst.dump_model()["tree_info"]       # drains: the counters land
    snap = obs_metrics.snapshot()
    assert snap["train.frontier_rounds_per_tree"]["count"] == 2
    return (snap["train.frontier_rounds"]["value"],
            snap["train.rows_passed"]["value"],
            snap["train.rows_selected"]["value"], trees)


def _internal_counts(node):
    if "split_index" not in node:
        return []
    return ([node["internal_count"]] + _internal_counts(node["left_child"])
            + _internal_counts(node["right_child"]))


def test_frontier_counters_agree_with_the_dumped_trees():
    rows = 2000
    # one round a tree: the root is the only leaf there is to split, and the
    # round passes and selects every row
    rounds, passed, selected, trees = _frontier_counts(2, rows=rows)
    assert [t["num_leaves"] for t in trees] == [2, 2]
    assert rounds == 2 and passed == 2 * rows
    assert selected == sum(t["tree_structure"]["internal_count"]
                           for t in trees) == 2 * rows
    # monotone in num_leaves, and bounded by the trees' own structure: every
    # split the tree kept was a selected leaf of some round (a round may
    # select more than the replay keeps), and a round selects no row twice
    last = (rounds, passed, selected)
    for num_leaves in (8, 31):
        rounds, passed, selected, trees = _frontier_counts(num_leaves,
                                                           rows=rows)
        assert passed == rounds * rows
        kept = sum(sum(_internal_counts(t["tree_structure"])) for t in trees)
        assert kept <= selected <= passed
        assert (rounds, passed, selected) >= last
        assert rounds > last[0]
        last = (rounds, passed, selected)
    # 30 splits at up to 16 a round after the doubling rounds
    assert rounds >= 2 * 6


_GROW_N, _GROW_F, _GROW_BINS = 3000, 6, 32


@pytest.fixture(scope="module")
def grow_with_and_without_stats():
    """The frontier grower jitted twice, ``with_stats`` off and on; compiled
    once for every seed."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.grower import GrowerConfig, grow_tree
    from lightgbm_tpu.ops.split import SplitParams

    n, f, nb = _GROW_N, _GROW_F, _GROW_BINS
    sp = SplitParams(lambda_l1=0.0, lambda_l2=0.0, min_data_in_leaf=5,
                     min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
                     max_delta_step=0.0, path_smooth=0.0, cat_smooth=10.0,
                     cat_l2=10.0, max_cat_to_onehot=4)
    cfg = GrowerConfig(num_leaves=31, max_depth=-1, max_bin=nb, split=sp,
                       feature_fraction_bynode=1.0, hist_method="scatter",
                       hist_chunk_rows=65536, grower_mode="frontier")

    def grow(with_stats):
        return jax.jit(lambda b, g, h, key: grow_tree(
            b, g, h, jnp.ones(n, jnp.float32), jnp.ones(f, bool),
            jnp.full(f, nb, jnp.int32), jnp.zeros(f, jnp.int32),
            jnp.full(f, -1, jnp.int32), jnp.zeros(f, bool),
            jnp.zeros(f, jnp.int32), key, cfg, with_stats=with_stats))
    return grow(False), grow(True)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_counters_in_the_loop_state_change_no_tree(
        seed, grow_with_and_without_stats):
    """``with_stats`` adds three scalars to the frontier loop's state and a
    third result; the tree and the row assignment are bit for bit what the
    program without them gives."""
    import jax
    import jax.numpy as jnp

    plain, with_stats = grow_with_and_without_stats
    rng = np.random.default_rng(seed)
    n = _GROW_N
    args = (jnp.asarray(rng.integers(0, _GROW_BINS, size=(n, _GROW_F)),
                        jnp.uint8),
            jnp.asarray(rng.normal(size=n), jnp.float32),
            jnp.asarray(rng.uniform(0.1, 1.0, size=n), jnp.float32),
            jax.random.PRNGKey(seed))
    tree, assign = plain(*args)
    tree_s, assign_s, stats = with_stats(*args)
    assert int(tree.num_leaves) == 31
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(tree_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(assign), np.asarray(assign_s))
    rounds, hi, lo = (int(v) for v in np.asarray(stats))
    assert rounds >= 6 and n <= (hi << 20) + lo <= rounds * n


def test_telemetry_off_keeps_journal_untouched(train_telemetry_env):
    import lightgbm_tpu as lgb

    path = train_telemetry_env
    rng = np.random.default_rng(1)
    X = rng.normal(size=(200, 4))
    y = X[:, 0] * 2.0
    p = {"objective": "regression", "num_leaves": 7, "verbose": -1,
         "obs_events_path": path}          # telemetry NOT enabled
    lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=2)
    assert not os.path.exists(path)
    assert obs_metrics.snapshot().get("train.iterations") is None


def test_no_peak_rate_constants_outside_costs():
    """ONE peak table in Python (ISSUE 18): every MFU / peak-rate figure the
    package prints prices against lightgbm_tpu/obs/costs.py:PEAK_RATES (the
    benchmark's are data, ``benchmarks/peaks.json``)."""
    import re
    # multi-digit (or fractional) mantissas with e9..e19 exponents — the
    # shape of every published peak rate (275e12, 819e9, 3.3e12, ...) but
    # NOT of unit conversions (/ 1e9) or test literals (1e12)
    peak_pat = re.compile(
        r"(\b\d+\.\d+e(?:9|1[0-9])\b"
        r"|\b\d{2,}e(?:9|1[0-9])\b"
        r"|PEAK_BF16|_PEAK_FLOPS|PEAK_HBM)")
    allowed = {os.path.join("lightgbm_tpu", "obs", "costs.py"),
               os.path.join("tests", "test_obs.py")}
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if d not in (".git", "__pycache__", ".pytest_cache",
                                # unpacked `git archive` copy for chip runs
                                "_chip_tree")]
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            rel = os.path.relpath(path, REPO)
            if rel in allowed:
                continue
            for i, line in enumerate(open(path, errors="replace"), 1):
                code = line.split("#", 1)[0]
                if peak_pat.search(code):
                    offenders.append(f"{rel}:{i}: {line.strip()}")
    assert not offenders, (
        "peak-rate constants outside obs/costs.py (route through "
        "PEAK_RATES / costs.mfu):\n" + "\n".join(offenders))
