"""Device-truth performance attribution (ISSUE 18): cost ledger, roofline
math and watermark gauges.

CPU-only and fast.  Covers the acceptance criteria: the ledger records
XLA cost/memory analysis for a jitted histogram call on CPU and
``obs-report --roofline`` renders its MFU row; watermark gauges populate
during a short boosting run (via the injectable stats provider — CPU
publishes no ``memory_stats``).
"""
import json

import numpy as np
import pytest

from lightgbm_tpu.obs import costs
from lightgbm_tpu.obs import metrics as obs_metrics
from lightgbm_tpu.obs import report as obs_report
from lightgbm_tpu.obs.events import EventLog, classify_record
from lightgbm_tpu.obs.tracer import get_tracer

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------------------
# roofline math: peaks, MFU, bound classification
def test_normalize_chip_and_peak_table():
    assert costs.normalize_chip("TPU v4") == "tpu v4"
    assert costs.normalize_chip("TPU v5 lite") == "tpu v5 lite"   # a v5e chip
    # a device the table does not hold is an error, never a default: no CPU
    # "peak", no pricing of an unknown TPU kind as some other chip
    assert "cpu" not in costs.PEAK_RATES
    for kind in ("cpu", None, "", "TPU v99x"):
        with pytest.raises(KeyError, match="PEAK_RATES"):
            costs.normalize_chip(kind)
        with pytest.raises(KeyError):
            costs.peak_flops(kind)
    with pytest.raises(KeyError):
        costs.current_chip()                  # this process runs on the CPU
    for kind, peaks in costs.PEAK_RATES.items():
        assert peaks["flops"] > 0 and peaks["bytes_per_sec"] > 0, kind
        assert costs.ridge_intensity(kind) == pytest.approx(
            peaks["flops"] / peaks["bytes_per_sec"])


def test_mfu_and_bound_classification():
    chip = "tpu v5e"
    pf = costs.peak_flops(chip)
    assert costs.mfu(pf, 1.0, chip) == pytest.approx(1.0)
    assert costs.mfu(pf / 2, 1.0, chip) == pytest.approx(0.5)
    assert costs.mfu(1e12, 0.0, chip) == 0.0      # zero time is not inf MFU
    ridge = costs.ridge_intensity(chip)
    assert costs.classify_bound(2 * ridge, chip) == "compute"
    assert costs.classify_bound(0.5 * ridge, chip) == "bandwidth"

    low = costs.roofline(1e9, 1e9, 0.01, chip)     # AI=1 << ridge
    assert low["bound"] == "bandwidth"
    assert low["achieved_flops_per_sec"] == pytest.approx(1e11)
    assert low["mfu"] == pytest.approx(1e11 / pf)
    assert low["hbm_util"] == pytest.approx(1e11 / costs.peak_bandwidth(chip))
    high = costs.roofline(1e9, 10.0, 0.01, chip)   # AI huge
    assert high["bound"] == "compute"
    # bytes_accessed=0 -> infinite intensity, still classifies
    assert costs.roofline(1e9, 0.0, 0.01, chip)["bound"] == "compute"


# ---------------------------------------------------------------------------
# cost ledger: XLA analysis of a jitted CPU histogram call
def test_ledger_records_jitted_hist_cost_and_memory(tmp_path):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import _hist_onehot

    rng = np.random.default_rng(0)
    n, f, b = 2048, 8, 32
    bins = jnp.asarray(rng.integers(0, b, size=(n, f), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    ones = jnp.ones(n, jnp.float32)

    fn = jax.jit(lambda bb, gg: jnp.sum(
        _hist_onehot(bb, gg, gg, ones, b, 65536)))
    model_flops = 2.0 * 6 * n * f * b
    # recorded where it ran (the CPU): the compiler's analysis is kept, but
    # there is no peak to price a timing against
    cpu_led = costs.CostLedger()
    costs.analyze_jitted("test.hist_onehot", fn, bins, g, ledger=cpu_led)
    assert cpu_led.entry("test.hist_onehot")["chip"] == "cpu"
    cpu_led.observe("test.hist_onehot", 0.02)
    with pytest.raises(KeyError, match="PEAK_RATES"):
        cpu_led.rooflines()

    # the join math, on a ledger told which chip its (synthetic) seconds
    # are for
    led = costs.CostLedger(chip="tpu v5e")
    ent = costs.analyze_jitted("test.hist_onehot", fn, bins, g, ledger=led,
                               model_flops=model_flops, rows=n, features=f,
                               max_bin=b)
    assert "test.hist_onehot" in led
    assert ent["cost"]["flops"] > 0                    # XLA's own count
    assert ent["cost"]["bytes_accessed"] > 0
    mem = ent["memory"]
    assert mem["argument_bytes"] >= bins.nbytes
    assert "peak_bytes" in mem                         # derived planning number
    assert mem["peak_bytes"] >= mem["temp_bytes"]
    assert ent["meta"] == {"rows": n, "features": f, "max_bin": b}

    # analysis without timings is not a roofline row (no wall time, no rate)
    assert led.rooflines() == []
    led.observe("unknown.program", 1.0)                # no-op, never raises
    assert "unknown.program" not in led

    led.observe("test.hist_onehot", 0.02, calls=2)
    rows = led.rooflines()
    assert len(rows) == 1
    r = rows[0]
    assert r["program"] == "test.hist_onehot" and r["calls"] == 2
    assert r["flops_source"] == "xla"
    assert r["seconds_per_call"] == pytest.approx(0.01)
    assert 0.0 < r["mfu"] < 1.0
    assert r["model_mfu"] == pytest.approx(
        costs.mfu(model_flops * 2, 0.02, r["chip"]))
    assert r["bound"] in ("compute", "bandwidth")

    # emit -> one schema-valid program_cost event per observed program
    path = str(tmp_path / "events.jsonl")
    assert led.emit(EventLog(path)) == 1
    kind, rec = classify_record(open(path).read().splitlines()[0])
    assert kind == "event"
    assert rec["event"] == costs.COST_EVENT
    assert rec["program"] == "test.hist_onehot"
    assert rec["memory"]["peak_bytes"] == mem["peak_bytes"]


# ---------------------------------------------------------------------------
# watermark gauges during a boosting run (injected stats: CPU has none)
@pytest.fixture
def clean_obs_state(tmp_path):
    obs_metrics.reset()
    get_tracer().reset()
    saved = costs.get_ledger()
    costs.reset_ledger()
    yield str(tmp_path / "train_events.jsonl")
    costs.set_stats_provider(None)
    costs._LEDGER = saved
    get_tracer().reset()
    obs_metrics.reset()


def test_roofline_report_renders_hist_program(tmp_path, clean_obs_state):
    """Acceptance: ``obs-report --roofline`` renders an MFU/roofline row
    for the production hist kernel from journal ``program_cost`` events."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import _hist_onehot

    rng = np.random.default_rng(1)
    n, f, b = 1024, 4, 16
    bins = jnp.asarray(rng.integers(0, b, size=(n, f), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    ones = jnp.ones(n, jnp.float32)
    fn = jax.jit(lambda bb, gg: jnp.sum(
        _hist_onehot(bb, gg, gg, ones, b, 65536)))

    # a rendering test: the chip is named and the seconds are synthetic (a
    # CPU timing has no peak to be priced against)
    led = costs.CostLedger(chip="tpu v5e")
    costs.analyze_jitted("bench.hist_onehot", fn, bins, g, ledger=led,
                         model_flops=2.0 * 6 * n * f * b)
    led.observe("bench.hist_onehot", 0.01)

    journal = str(tmp_path / "perf.jsonl")
    led.emit(EventLog(journal))
    out = str(tmp_path / "report.md")
    rc = obs_report.main(["--path", journal, "--roofline", "--out", out])
    assert rc == 0
    text = open(out).read()
    assert "Roofline" in text
    assert "bench.hist_onehot" in text
    assert "MFU" in text and ("bandwidth" in text or "compute" in text)
    # json mode carries the raw rows
    outj = str(tmp_path / "report.json")
    assert obs_report.main(["--path", journal, "--roofline",
                            "--format", "json", "--out", outj]) == 0
    rows = json.load(open(outj))["roofline"]
    assert any(r["program"] == "bench.hist_onehot" for r in rows)


def test_watermark_gauges_populate_during_boosting(clean_obs_state):
    import lightgbm_tpu as lgb

    path = clean_obs_state
    fake = {"bytes_in_use": 123_456, "peak_bytes_in_use": 654_321}
    costs.set_stats_provider(lambda: dict(fake))
    rng = np.random.default_rng(2)
    X = rng.normal(size=(400, 6))
    y = X[:, 0] * 2.0 + 0.5 * X[:, 1] ** 2
    p = {"objective": "regression", "num_leaves": 7, "verbose": -1,
         "obs_telemetry": True, "obs_events_path": path}
    lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=3)

    snap = obs_metrics.snapshot()
    assert snap["train.device_bytes_in_use"]["value"] == 123_456
    assert snap["train.device_peak_bytes_in_use"]["value"] == 654_321
    iters = [r for r in map(json.loads, open(path))
             if r.get("event") == "train_iter"]
    assert len(iters) == 3
    assert all(r["device_memory"]["bytes_in_use"] == 123_456 for r in iters)
    # the grow program landed in the ledger: XLA analysis + observed calls
    led = costs.get_ledger()
    assert "train.grow_tree" in led
    ent = led.entry("train.grow_tree")
    assert ent["calls"] >= 1
    assert ent["cost"].get("flops", 0) > 0
    assert ent["chip"] == "cpu"       # named as found; not priceable


def test_record_watermarks_empty_when_backend_has_no_stats():
    costs.set_stats_provider(lambda: None)     # CPU: memory_stats() is None
    try:
        assert costs.record_watermarks("nowhere") == {}
    finally:
        costs.set_stats_provider(None)
    assert "nowhere.device_bytes_in_use" not in obs_metrics.snapshot()
