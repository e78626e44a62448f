"""Runtime health plane tests (lightgbm_tpu/obs/{health,flight}.py,
docs/OBSERVABILITY.md "Live health & forensics").

CPU-only.  Covers ISSUE 20's acceptance criteria: a live training run
with ``obs_health_port`` set answers ``/metrics`` and ``/healthz`` from
another process; a SIGKILLed, signalled or raising child leaves a
schema-valid ``flight_*.jsonl``; and a NaN-gradient objective raises
:class:`DivergenceError` within ``obs_health_check_iters`` rounds.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lightgbm_tpu.obs import flight as obs_flight  # noqa: E402
from lightgbm_tpu.obs import health as obs_health  # noqa: E402
from lightgbm_tpu.obs import metrics as obs_metrics  # noqa: E402
from lightgbm_tpu.obs import report as obs_report  # noqa: E402
from lightgbm_tpu.obs.events import EventLog, classify_record  # noqa: E402
from lightgbm_tpu.obs.flight import FlightRecorder  # noqa: E402
from lightgbm_tpu.obs.health import DivergenceError, SLOMonitor  # noqa: E402
from lightgbm_tpu.obs.tracer import get_tracer  # noqa: E402

pytestmark = pytest.mark.health


@pytest.fixture(autouse=True)
def _clean_health_state():
    """Health plane is process-global state: server, status board, SLO
    registry, metrics — every test starts and ends clean."""
    yield
    obs_health.stop_health_server()
    obs_health._reset_status()
    for name in list(obs_health._SLOS):
        obs_health.unregister_slo(name)
    obs_metrics.reset()
    get_tracer().reset()


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url, timeout=5):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def _assert_schema_lines(path):
    lines = [l for l in open(path).read().splitlines() if l.strip()]
    assert lines, path
    for line in lines:
        kind, rec = classify_record(line)
        assert kind == "event", (line, rec)
    return [classify_record(l)[1] for l in lines]


# ---------------------------------------------------------------------------
# numeric sentinels: verdict, check_numeric, live training
# ---------------------------------------------------------------------------

def test_numeric_verdict():
    ok, bad = obs_health.numeric_verdict(
        {"grad": {"finite_frac": 1.0, "max_abs": 3.5},
         "hess": {"finite_frac": 1.0, "max_abs": 0.25}})
    assert ok and bad == []
    ok, bad = obs_health.numeric_verdict(
        {"grad": {"finite_frac": 0.99, "max_abs": 1.0},
         "leaf_value": {"finite_frac": 1.0, "max_abs": float("inf")}})
    assert not ok and bad == ["grad", "leaf_value"]


def test_check_numeric_emits_event_and_raises(tmp_path):
    log = EventLog(str(tmp_path / "ev.jsonl"))
    assert obs_health.check_numeric(
        {"grad": {"finite_frac": 1.0, "max_abs": 2.0}},
        iteration=4, kind="train", log=log)
    st = obs_health.get_status()
    assert st["numeric_ok"] is True and st["last_numeric_check"] == 4
    with pytest.raises(DivergenceError) as ei:
        obs_health.check_numeric(
            {"grad": {"finite_frac": 0.5, "max_abs": 1.0}},
            iteration=7, kind="train", log=log)
    assert ei.value.iteration == 7
    assert "grad" in str(ei.value)
    assert obs_health.get_status()["numeric_ok"] is False
    evs = _assert_schema_lines(log.path)
    health = [e for e in evs if e["event"] == "numeric_health"]
    assert [e["ok"] for e in health] == [True, False]
    assert health[1]["grad_finite_frac"] == 0.5


def test_training_numeric_sentinel_healthy_no_divergence():
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(0)
    X = rng.normal(size=(600, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "obs_health_check_iters": 2}
    lgb.train(params, ds, num_boost_round=6)
    st = obs_health.get_status()
    assert st["numeric_ok"] is True
    assert st["last_numeric_check"] in (4, 5)   # last due round
    assert st["iteration"] == 5


def test_training_nan_gradients_raise_divergence_error():
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(1)
    X = rng.normal(size=(400, 5)).astype(np.float32)
    y = rng.normal(size=400).astype(np.float64)
    ds = lgb.Dataset(X, label=y)

    def nan_fobj(preds, train_set):
        grad = preds - np.asarray(train_set.get_label())
        grad[::3] = np.nan
        hess = np.ones_like(grad)
        return grad, hess

    params = {"objective": "regression", "num_leaves": 7, "verbose": -1,
              "obs_health_check_iters": 1}
    with pytest.raises(DivergenceError) as ei:
        lgb.train(params, ds, num_boost_round=4, fobj=nan_fobj)
    # check_iters=1: the very first round must trip the sentinel
    assert ei.value.iteration == 0
    assert ei.value.detail["grad"]["finite_frac"] < 1.0


# ---------------------------------------------------------------------------
# SLO burn rates
# ---------------------------------------------------------------------------

def test_slo_monitor_burn_rates_and_breach():
    t = [100.0]
    slo = SLOMonitor("m", p99_ms=10.0, error_rate=0.01,
                     windows=(60.0, 600.0), clock=lambda: t[0])
    for _ in range(99):
        slo.observe(latency_ms=5.0)
        t[0] += 0.1
    rep = slo.report()
    assert rep["model"] == "m" and not rep["breached"]
    w = rep["windows"]["60s"]
    assert w["requests"] == 99 and w["bad"] == 0
    assert w["p99_ms"] == 5.0
    assert w["error_burn"] == 0.0 and w["latency_burn"] == 0.5
    # two bad requests out of ~101 blows a 1% error budget
    slo.observe(bad=True)
    slo.observe(bad=True)
    rep = slo.report()
    w = rep["windows"]["60s"]
    assert w["bad"] == 2 and w["error_burn"] >= 1.0
    assert w["breached"] and rep["breached"]
    # ... and the old window ages out: far in the future nothing remains
    t[0] += 10_000.0
    w = slo.report()["windows"]["60s"]
    assert w["requests"] == 0 and not w["breached"]


def test_slo_latency_breach_without_errors():
    t = [0.0]
    slo = SLOMonitor("m", p99_ms=1.0, clock=lambda: t[0])
    for _ in range(10):
        slo.observe(latency_ms=3.0)
        t[0] += 1.0
    rep = slo.report()
    assert rep["breached"]
    assert rep["windows"]["300s"]["latency_burn"] == 3.0
    assert "error_burn" not in rep["windows"]["300s"]    # no error objective


def test_slo_batcher_integration():
    from lightgbm_tpu.serve.batcher import MicroBatcher
    slo = SLOMonitor("bm", p99_ms=500.0, error_rate=0.5)
    b = MicroBatcher(lambda X: X.sum(axis=1), max_batch_rows=64,
                     deadline_ms=0.0, queue_depth=8, name="bm",
                     num_features=3, slo=slo)
    try:
        X = np.ones((4, 3), np.float32)
        out = b.predict(X)
        assert out.shape == (4,)
        with pytest.raises(Exception):
            b.predict(np.ones((4, 7), np.float32))   # width mismatch -> bad
    finally:
        b.close()
    rep = slo.report()
    w = rep["windows"]["300s"]
    assert w["requests"] == 2 and w["bad"] == 1
    assert w["p99_ms"] is not None


# ---------------------------------------------------------------------------
# prometheus rendering + health server
# ---------------------------------------------------------------------------

def test_render_prometheus_exposition():
    obs_metrics.counter("serve.requests").inc(5)
    obs_metrics.gauge("stream.device_bytes").set(123.0)
    h = obs_metrics.histogram("serve.predict_ms")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    obs_health.register_slo(SLOMonitor("m", error_rate=0.1))
    text = obs_health.render_prometheus()
    assert "# TYPE lgbtpu_serve_requests counter" in text
    assert "lgbtpu_serve_requests 5" in text
    assert "lgbtpu_stream_device_bytes 123" in text
    assert 'lgbtpu_serve_predict_ms{quantile="0.99"}' in text
    assert "lgbtpu_serve_predict_ms_count 3" in text
    assert "lgbtpu_health_uptime_seconds" in text
    assert 'lgbtpu_slo_error_burn{model="m",window="300s"}' in text


def test_health_server_endpoints_and_idempotent_start():
    obs_health.set_status(run_id="rid1", stage="train", iteration=9)
    obs_metrics.counter("serve.requests").inc(2)
    srv = obs_health.start_health_server(0)     # ephemeral port
    assert srv is not None and srv.port > 0
    again = obs_health.maybe_start(srv.port)
    assert again is srv                          # one server per process
    code, body = _get(srv.url + "/healthz")
    assert code == 200
    data = json.loads(body)
    assert data["ok"] and data["run_id"] == "rid1"
    assert data["stage"] == "train" and data["iteration"] == 9
    code, body = _get(srv.url + "/metrics")
    assert code == 200 and "lgbtpu_serve_requests 2" in body
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(srv.url + "/nope")
    assert ei.value.code == 404


def test_health_server_busy_port_warns_not_raises():
    srv = obs_health.start_health_server(0)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        s.listen(1)
        busy = s.getsockname()[1]
        obs_health.stop_health_server()
        with pytest.warns(RuntimeWarning):
            assert obs_health.start_health_server(busy) is None
    assert obs_health.get_server() is None
    del srv


def test_live_training_answers_health_endpoints(tmp_path):
    """ISSUE 20 acceptance: a real training subprocess with
    ``obs_health_port`` set is probed over HTTP from THIS process."""
    port = _free_port()
    ready = tmp_path / "ready"
    script = tmp_path / "train_live.py"
    script.write_text(f"""
import os, sys, time
sys.path.insert(0, {REPO!r})
import numpy as np
import lightgbm_tpu as lgb
rng = np.random.default_rng(0)
X = rng.normal(size=(500, 6)).astype(np.float32)
y = (X[:, 0] > 0).astype(np.float32)
params = {{"objective": "binary", "num_leaves": 7, "verbose": -1,
          "obs_health_port": {port}, "obs_health_check_iters": 2}}
lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10)
open({str(ready)!r}, "w").write("ok")
time.sleep(20)
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen([sys.executable, str(script)], env=env,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True)
    try:
        deadline = time.monotonic() + 120
        while not ready.exists():
            assert p.poll() is None, p.communicate()[0]
            assert time.monotonic() < deadline, "training never finished"
            time.sleep(0.25)
        code, body = _get(f"http://127.0.0.1:{port}/healthz")
        data = json.loads(body)
        assert code == 200 and data["ok"]
        assert data["stage"] == "train" and data["iteration"] == 9
        assert data["status"]["numeric_ok"] is True
        code, body = _get(f"http://127.0.0.1:{port}/metrics")
        assert code == 200 and "lgbtpu_health_uptime_seconds" in body
    finally:
        p.kill()
        p.communicate()


# ---------------------------------------------------------------------------
# flight recorder: ring, dumps, crash paths
# ---------------------------------------------------------------------------

def test_flight_ring_bounded_and_dump_schema(tmp_path):
    rec = FlightRecorder(dir=str(tmp_path), run_id="ridX",
                         capacity=4, flush_every=100)
    for i in range(7):
        rec.note("tick", i=i)
    assert [r["i"] for r in rec.snapshot()] == [3, 4, 5, 6]
    assert rec.last_event()["i"] == 6
    path = rec.dump("manual")
    assert path == str(tmp_path / "flight_ridX.jsonl")
    evs = _assert_schema_lines(path)
    assert evs[0]["event"] == "flight_dump"
    assert evs[0]["reason"] == "manual" and evs[0]["events"] == 4
    assert [e["i"] for e in evs[1:]] == [3, 4, 5, 6]
    assert not list(tmp_path.glob("*.tmp.*"))   # atomic: no tmp residue


def test_flight_observer_taps_eventlog(tmp_path):
    rec = FlightRecorder(dir=str(tmp_path), capacity=8, flush_every=100)
    rec.install()
    try:
        log = EventLog(str(tmp_path / "ev.jsonl"))
        log.emit("stage_a", x=1)
        assert rec.last_event()["event"] == "stage_a"
    finally:
        rec.uninstall()
    log.emit("stage_b")
    assert rec.last_event()["event"] == "stage_a"   # tap removed


def test_flight_span_tail_in_dump(tmp_path):
    t = get_tracer()
    with t.span("outer"):
        with t.span("inner"):
            pass
    t.begin("still_open")
    try:
        rec = FlightRecorder(dir=str(tmp_path), flush_every=100)
        rec.note("tick")
        evs = _assert_schema_lines(rec.dump("manual"))
        spans = [e for e in evs if e["event"] == "flight_span"]
        names = {e["name"]: e["open"] for e in spans}
        assert names["inner"] is False and names["outer"] is False
        assert names["still_open"] is True
        open_rec = [e for e in spans if e["name"] == "still_open"][0]
        assert open_rec["age_s"] >= 0
    finally:
        t.end("still_open")


_CRASH_CHILD = """
import os, signal, sys
sys.path.insert(0, {repo!r})
from lightgbm_tpu import obs
rec = obs.flight.install(dir={dir!r}, run_id="victim", flush_every=1)
rec.note("about_to_die", mode={mode!r})
mode = {mode!r}
if mode == "sigkill":
    os.kill(os.getpid(), signal.SIGKILL)
elif mode == "sigterm":
    os.kill(os.getpid(), signal.SIGTERM)
elif mode == "exception":
    raise ValueError("boom from child")
"""


def _run_crash_child(tmp_path, mode):
    script = tmp_path / "child.py"
    script.write_text(_CRASH_CHILD.format(repo=REPO, dir=str(tmp_path),
                                          mode=mode))
    return subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, timeout=60)


def test_flight_periodic_flush_survives_sigkill(tmp_path):
    p = _run_crash_child(tmp_path, "sigkill")
    assert p.returncode == -signal.SIGKILL
    evs = _assert_schema_lines(tmp_path / "flight_victim.jsonl")
    # SIGKILL is uncatchable: the eager flush_every=1 dump IS the record
    assert evs[0]["reason"] == "periodic"
    assert any(e["event"] == "about_to_die" for e in evs)


def test_flight_dump_on_fatal_signal_preserves_exit_status(tmp_path):
    p = _run_crash_child(tmp_path, "sigterm")
    assert p.returncode == -signal.SIGTERM      # handler re-raised
    evs = _assert_schema_lines(tmp_path / "flight_victim.jsonl")
    assert evs[0]["reason"] == "signal_SIGTERM"
    assert any(e["event"] == "fatal_signal" and e["signal"] == "SIGTERM"
               for e in evs)


def test_flight_dump_on_unhandled_exception(tmp_path):
    p = _run_crash_child(tmp_path, "exception")
    assert p.returncode == 1
    assert "ValueError: boom from child" in p.stderr    # hook chains on
    evs = _assert_schema_lines(tmp_path / "flight_victim.jsonl")
    exc = [e for e in evs if e["event"] == "unhandled_exception"]
    assert exc and exc[0]["type"] == "ValueError"
    assert "boom" in exc[0]["message"]


# ---------------------------------------------------------------------------
# tracer overflow surfacing + report sections
# ---------------------------------------------------------------------------

def test_tracer_dropped_surfaces_in_summary(tmp_path, capsys):
    t = get_tracer()
    t.capacity = 0          # every completed span is a drop
    try:
        with t.span("doomed"):
            pass
        assert t.dropped == 1
        log = EventLog(str(tmp_path / "ev.jsonl"), echo=False)
        rec = log.summary(metric="x", unit="u", value=1.0)
        assert rec["tracer_dropped"] == 1
    finally:
        t.reset()
        t.capacity = 100_000


def test_obs_report_health_section(tmp_path):
    obs_health.set_status(run_id="repRID", stage="train", iteration=3)
    obs_health.register_slo(SLOMonitor("m", error_rate=0.1))
    out = tmp_path / "health.md"
    rc = obs_report.main(["--health", "--path",
                          str(tmp_path / "none.jsonl"), "--out", str(out)])
    assert rc == 0
    text = out.read_text()
    assert "## Runtime health" in text
    assert "repRID" in text and "| m |" in text


def test_obs_report_health_url_fetches_live_process(tmp_path):
    obs_health.set_status(run_id="liveRID", stage="serve")
    srv = obs_health.start_health_server(0)
    out = tmp_path / "health.md"
    rc = obs_report.main(["--health",
                          "--health-url", f"127.0.0.1:{srv.port}",
                          "--path", str(tmp_path / "none.jsonl"),
                          "--out", str(out)])
    assert rc == 0
    assert "liveRID" in out.read_text()


def test_config_health_knob_validation():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import LightGBMError
    for bad in ({"obs_health_port": -1}, {"obs_health_port": 70000},
                {"obs_health_check_iters": -2},
                {"serve_slo_p99_ms": -1.0},
                {"serve_slo_error_rate": 1.5}):
        with pytest.raises(LightGBMError):
            Config.from_params(dict(bad, objective="binary"))
    cfg = Config.from_params({"obs_health_port": 8123,
                              "obs_health_check_iters": 5,
                              "serve_slo_p99_ms": 20.0,
                              "serve_slo_error_rate": 0.01})
    assert cfg.obs_health_port == 8123
