"""Fault-injection tests for the unattended TPU-window watcher stack:
``lightgbm_tpu/utils/supervise.py`` primitives, the hardened
``bench.probe_backend``, and the ``scripts/tpu_window_watcher.py`` state
machine — all against scripted fakes (``WATCHER_FAKE_BACKEND`` seam), no
TPU and no real sleeps beyond stage-timeout kills (~1-2 s each).

The end-to-end cases mirror the failure modes that actually burned rounds
3-5: a probe that never comes back, a stage that hangs holding helper
grandchildren, and a window that re-wedges mid-pipeline.
"""
import json
import os
import random
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402

sup = bench._load_supervise()
WATCHER = os.path.join(REPO, "scripts", "tpu_window_watcher.py")

pytestmark = pytest.mark.watcher

# a child that forks a grandchild, records both pids, then hangs: the
# killpg path must reap BOTH (kill(pid) alone would orphan the grandchild
# — on real hardware that orphan keeps the TPU wedged)
HANG_TREE_CODE = """
import json, os, sys, time
child = os.fork()
if child == 0:
    time.sleep(60)
    os._exit(0)
with open(sys.argv[-1], "w") as f:
    json.dump({"child": os.getpid(), "grandchild": child}, f)
print("ndev=1", flush=True)
time.sleep(60)
"""


def _assert_tree_reaped(pidfile, deadline=5.0):
    with open(pidfile) as f:
        pids = json.load(f)
    t0 = time.monotonic()
    remaining = dict(pids)
    while remaining and time.monotonic() - t0 < deadline:
        for who, pid in list(remaining.items()):
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                del remaining[who]
        time.sleep(0.05)
    assert not remaining, f"processes survived the killpg: {remaining}"


# --------------------------------------------------------------------------
# supervise.run_stage
# --------------------------------------------------------------------------

def test_run_stage_ok_captures_output():
    res = sup.run_stage(
        "hello", [sys.executable, "-c", "print('out'); print(41+1)"],
        timeout=10)
    assert res.ok and res.status == "ok" and res.returncode == 0
    assert res.attempts == 1
    assert "out" in res.output_tail and "42" in res.output_tail


def test_run_stage_crash_is_isolated():
    res = sup.run_stage(
        "boom", [sys.executable, "-c", "import sys; sys.exit(3)"],
        timeout=10)
    assert not res.ok and res.status == "crash" and res.returncode == 3


def test_run_stage_timeout_reaps_grandchild_tree(tmp_path):
    pidfile = str(tmp_path / "pids.json")
    t0 = time.monotonic()
    res = sup.run_stage(
        "hang", [sys.executable, "-c", HANG_TREE_CODE, pidfile],
        timeout=1.0)
    wall = time.monotonic() - t0
    assert res.status == "timeout" and res.returncode is None
    assert wall < 8, f"timeout kill took {wall:.1f}s"
    _assert_tree_reaped(pidfile)


def test_run_stage_timeout_reaps_setsid_grandchild(tmp_path):
    """A grandchild that called setsid itself (the nested-run_stage shape:
    a supervised suite stage spawning its own supervised bench) left the
    child's process group — the /proc descendant sweep must still reap
    it."""
    code = """
import json, os, subprocess, sys, time
gc = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"],
                      start_new_session=True)
with open(sys.argv[-1], "w") as f:
    json.dump({"child": os.getpid(), "grandchild": gc.pid}, f)
time.sleep(60)
"""
    pidfile = str(tmp_path / "pids.json")
    res = sup.run_stage(
        "nested", [sys.executable, "-c", code, pidfile], timeout=1.0)
    assert res.status == "timeout"
    _assert_tree_reaped(pidfile)


def test_run_stage_retry_backoff_schedule():
    """Retries follow jittered exponential backoff: base*factor**i scaled
    by 1±jitter — verified without wall-clock cost via injected sleep."""
    slept = []
    events = []
    res = sup.run_stage(
        "flappy", [sys.executable, "-c", "import sys; sys.exit(1)"],
        timeout=10, retries=3, backoff=1.0, backoff_factor=2.0,
        jitter=0.25, sleep=slept.append, rng=random.Random(0),
        heartbeat=lambda event, **kv: events.append((event, kv)))
    assert res.status == "crash" and res.attempts == 4
    assert len(slept) == 3
    for i, d in enumerate(slept):
        lo, hi = (2.0 ** i) * 0.75, (2.0 ** i) * 1.25
        assert lo <= d <= hi, f"delay {i}: {d} outside [{lo}, {hi}]"
    kinds = [e for e, _ in events]
    assert kinds.count("stage_attempt") == 4
    assert kinds.count("stage_backoff") == 3


def test_backoff_schedule_caps():
    ds = sup.backoff_schedule(6, base=10.0, factor=2.0, cap=60.0,
                              jitter=0.0, rng=random.Random(1))
    assert ds == [10.0, 20.0, 40.0, 60.0, 60.0, 60.0]


# --------------------------------------------------------------------------
# heartbeat + lock + journal io
# --------------------------------------------------------------------------

def test_heartbeat_writes_structured_jsonl(tmp_path):
    hb = sup.Heartbeat(str(tmp_path / "hb.jsonl"), extra={"role": "test"})
    hb("start", x=1)
    hb.beat("stop")
    recs = [json.loads(l) for l in
            (tmp_path / "hb.jsonl").read_text().splitlines()]
    assert [r["event"] for r in recs] == ["start", "stop"]
    assert recs[0]["x"] == 1 and recs[0]["role"] == "test"
    assert recs[0]["seq"] == 0 and recs[1]["seq"] == 1
    assert all(r["pid"] == os.getpid() and r["ts"] > 0 for r in recs)


def test_lock_second_owner_refused(tmp_path):
    path = str(tmp_path / "w.lock")
    with sup.SingleOwnerLock(path):
        with pytest.raises(sup.LockHeldError) as ei:
            sup.SingleOwnerLock(path).acquire()
        assert str(os.getpid()) in str(ei.value)
    assert not os.path.exists(path)          # released on exit


def test_lock_stale_owner_reclaimed(tmp_path):
    path = str(tmp_path / "w.lock")
    # a dead pid: spawn-and-reap a child so the pid is known-free
    p = subprocess.run([sys.executable, "-c", "import os; print(os.getpid())"],
                       capture_output=True, text=True)
    dead = int(p.stdout.strip())
    with open(path, "w") as f:
        json.dump({"pid": dead, "host": __import__("socket").gethostname(),
                   "since": 0, "argv": ["ghost"]}, f)
    lock = sup.SingleOwnerLock(path).acquire()    # reclaims, no raise
    lock.release()


def test_json_atomic_roundtrip(tmp_path):
    path = str(tmp_path / "state.json")
    sup.write_json_atomic(path, {"a": [1, 2]})
    assert sup.read_json(path) == {"a": [1, 2]}
    assert sup.read_json(str(tmp_path / "missing.json"), default=7) == 7


# --------------------------------------------------------------------------
# bench.probe_backend (hardened probe)
# --------------------------------------------------------------------------

def test_probe_backend_parses_device_count():
    assert bench.probe_backend(10, count_devices=True,
                               code="print('ndev=3')") == 3
    assert bench.probe_backend(10, code="print('ndev=1')") is True
    assert bench.probe_backend(10, code="print('ndev=0')") is False


def test_probe_backend_dead_child_is_not_live():
    assert bench.probe_backend(
        10, code="import sys; print('ndev=1'); sys.exit(1)") is False


def test_probe_backend_hang_kills_whole_tree(tmp_path):
    """A hanging probe child that forked its own grandchild (a backend
    helper process) is killed within the timeout and leaves no
    orphans — the killpg path reaps the tree."""
    pidfile = str(tmp_path / "pids.json")
    t0 = time.monotonic()
    live = bench.probe_backend(1.0, argv=[sys.executable, "-c",
                                          HANG_TREE_CODE, pidfile])
    wall = time.monotonic() - t0
    assert live is False
    assert wall < 8, f"probe kill took {wall:.1f}s"
    _assert_tree_reaped(pidfile)


# --------------------------------------------------------------------------
# watcher end-to-end (subprocess, scripted fakes)
# --------------------------------------------------------------------------

def _run_watcher(tmp_path, env_extra=None, args=(), timeout=60):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               WATCHER_PERF_LOG=str(tmp_path / "perf.jsonl"),
               WATCHER_GRANDCHILD_PIDFILE=str(tmp_path / "gpids.json"),
               **(env_extra or {}))
    return subprocess.run(
        [sys.executable, WATCHER, "--state-dir", str(tmp_path),
         "--poll-interval", "0.01", "--poll-cap", "0.05",
         "--probe-timeout", "5", *args],
        capture_output=True, text=True, timeout=timeout, env=env)


def _journal(tmp_path):
    return json.loads((tmp_path / "watcher_state.json").read_text())


def _perf_records(tmp_path):
    p = tmp_path / "perf.jsonl"
    if not p.exists():
        return []
    return [json.loads(l) for l in p.read_text().splitlines()]


def _heartbeats(tmp_path):
    return [json.loads(l) for l in
            (tmp_path / "watcher_heartbeat.jsonl").read_text().splitlines()]


def test_watcher_captures_window_stages_in_order(tmp_path):
    p = _run_watcher(tmp_path, {"WATCHER_FAKE_BACKEND": "ok"},
                     args=("--stage-timeout", "10"))
    assert p.returncode == 0, p.stderr
    j = _journal(tmp_path)
    assert j["state"] == "done" and j["windows_captured"] == 1
    assert [s["status"] for s in j["stages"]] == ["ok"] * 6
    fake = [r["stage"] for r in _perf_records(tmp_path) if r.get("fake")]
    assert fake == ["parity", "perf_suite", "onehot_shootout", "headline",
                    "bench_serve", "bench_stream"]
    # the headline stage's JSON line is extracted into the watcher record
    head = [r for r in _perf_records(tmp_path)
            if r.get("stage") == "watcher_headline"]
    assert head and head[0]["result"]["unit"] == "Mrow_iters/sec"
    # the window summary lands, then the per-window obs-report artifact
    # (rendered AFTER the summary so the report covers it)
    tail = [r["stage"] for r in _perf_records(tmp_path)[-2:]]
    assert tail == ["watcher_window", "watcher_obs_report"]
    rep = _perf_records(tmp_path)[-1]
    assert "error" not in rep, rep
    assert os.path.exists(rep["path"])
    art = open(rep["path"]).read()
    assert "watcher_window" in art    # the digest covers the window record


def test_watcher_poll_backoff_on_repeated_failure(tmp_path):
    p = _run_watcher(tmp_path, {"WATCHER_FAKE_BACKEND": "fail"},
                     args=("--max-polls", "4"))
    assert p.returncode == 3
    assert _journal(tmp_path)["probe_failures"] == 4
    sleeps = [h["delay_sec"] for h in _heartbeats(tmp_path)
              if h["event"] == "sleep"]
    assert len(sleeps) == 3
    # base 0.01, doubling, ±25% jitter: the bands are disjoint, so the
    # schedule must be strictly increasing and the 3rd ≥ 3x the 1st
    assert sleeps[0] < sleeps[1] < sleeps[2]
    assert sleeps[2] >= 3 * sleeps[0]
    assert all(h["live"] is False for h in _heartbeats(tmp_path)
               if h["event"] == "probe")


def test_watcher_flaky_backend_eventually_captures(tmp_path):
    # flaky mode: probes fail, fail, ok — the window lands on poll 3
    p = _run_watcher(tmp_path, {"WATCHER_FAKE_BACKEND": "flaky"},
                     args=("--max-polls", "6", "--stage-timeout", "10"))
    assert p.returncode == 0, p.stderr
    assert _journal(tmp_path)["windows_captured"] == 1


def test_watcher_refuses_when_lock_held(tmp_path):
    with sup.SingleOwnerLock(str(tmp_path / "watcher.lock")):
        p = _run_watcher(tmp_path, {"WATCHER_FAKE_BACKEND": "ok"},
                         args=("--once",))
    assert p.returncode == 2
    assert "lock" in p.stderr and str(os.getpid()) in p.stderr
    assert not (tmp_path / "watcher_state.json").exists()


def test_watcher_stage_crash_degrades_to_remaining(tmp_path):
    plan = tmp_path / "stage_plan.json"
    plan.write_text(json.dumps({"perf_suite": ["crash"]}))
    p = _run_watcher(tmp_path, {"WATCHER_FAKE_BACKEND": "ok",
                                "WATCHER_FAKE_STAGE_PLAN": str(plan)},
                     args=("--stage-timeout", "10"))
    assert p.returncode == 0, p.stderr
    j = _journal(tmp_path)
    assert {s["name"]: s["status"] for s in j["stages"]} == {
        "parity": "ok", "perf_suite": "failed",
        "onehot_shootout": "ok", "headline": "ok", "bench_serve": "ok",
        "bench_stream": "ok"}
    fail = [r for r in _perf_records(tmp_path)
            if r.get("stage") == "watcher_perf_suite"]
    assert fail and fail[0]["status"] == "crash"
    # the window still completes: later stages ran after the failure
    fake = [r["stage"] for r in _perf_records(tmp_path) if r.get("fake")]
    assert fake == ["parity", "onehot_shootout", "headline", "bench_serve",
                    "bench_stream"]


def test_watcher_hung_stage_killed_at_timeout_group_reaped(tmp_path):
    plan = tmp_path / "stage_plan.json"
    plan.write_text(json.dumps({"onehot_shootout": ["hang"]}))
    t0 = time.monotonic()
    p = _run_watcher(tmp_path, {"WATCHER_FAKE_BACKEND": "ok",
                                "WATCHER_FAKE_STAGE_PLAN": str(plan)},
                     args=("--stage-timeout", "1"))
    wall = time.monotonic() - t0
    assert p.returncode == 0, p.stderr
    assert wall < 30
    j = _journal(tmp_path)
    assert {s["name"]: s["status"] for s in j["stages"]} == {
        "parity": "ok", "perf_suite": "ok",
        "onehot_shootout": "failed", "headline": "ok", "bench_serve": "ok",
        "bench_stream": "ok"}
    rec, = [r for r in _perf_records(tmp_path)
            if r.get("stage") == "watcher_onehot_shootout"]
    assert rec["status"] == "timeout"
    _assert_tree_reaped(str(tmp_path / "gpids.json"))


def test_watcher_rewedge_journals_and_resumes(tmp_path):
    """Mid-pipeline re-wedge: stage 2 dies AND the re-probe finds the
    backend dead → back to POLL with the journal holding the resume point;
    the next simulated window resumes from perf_suite WITHOUT re-running
    parity."""
    probe_plan = tmp_path / "probe_plan.txt"
    # poll 1: ok (window opens) · after perf_suite dies: fail (re-wedge)
    # · poll 2: ok (window reopens) · re-probes after that: default ok
    probe_plan.write_text("ok\nfail\nok\n")
    stage_plan = tmp_path / "stage_plan.json"
    stage_plan.write_text(json.dumps({"perf_suite": ["crash", "ok"]}))
    p = _run_watcher(tmp_path, {"WATCHER_FAKE_BACKEND": "ok",
                                "WATCHER_FAKE_PROBE_PLAN": str(probe_plan),
                                "WATCHER_FAKE_STAGE_PLAN": str(stage_plan)},
                     args=("--stage-timeout", "10", "--max-polls", "8"))
    assert p.returncode == 0, p.stderr
    j = _journal(tmp_path)
    assert j["windows_captured"] == 1
    stat = {s["name"]: s for s in j["stages"]}
    assert all(s["status"] == "ok" for s in j["stages"])
    assert stat["perf_suite"]["detail"].get("resumed") is True
    # parity ran ONCE: resume did not restart the pipeline
    fake = [r["stage"] for r in _perf_records(tmp_path) if r.get("fake")]
    assert fake == ["parity", "perf_suite", "onehot_shootout", "headline",
                    "bench_serve", "bench_stream"]
    # the re-wedge itself is journaled to the results log
    wedge, = [r for r in _perf_records(tmp_path)
              if r.get("stage") == "watcher_rewedge"]
    assert wedge["during"] == "perf_suite"
    # the resumed perf_suite stage asks the suite to skip landed phases
    assert any(h["event"] == "rewedge" for h in _heartbeats(tmp_path))


def test_watcher_once_poll_only(tmp_path):
    p = _run_watcher(tmp_path, {"WATCHER_FAKE_BACKEND": "fail"},
                     args=("--once",))
    assert p.returncode == 0
    j = _journal(tmp_path)
    assert j["state"] == "poll" and j["probe_failures"] == 1
    assert j["windows_captured"] == 0


def test_suite_resume_survives_second_rewedge(tmp_path):
    """Phases completed BEFORE an earlier resumed run stay skipped: the
    resume set seeds from suite_start's own skipped list, so a second
    mid-run re-wedge doesn't re-burn window time on phases captured two
    runs ago."""
    log = tmp_path / "perf.jsonl"
    log.write_text("".join(json.dumps(r) + "\n" for r in [
        {"stage": "suite_start", "rows": 5000, "skipped": [],
         "resumed_done": []},
        {"stage": "suite_phase_done", "phase": "sanity", "rows": 5000},
        # run 2 resumed (skipping sanity), landed parity, then re-wedged
        {"stage": "suite_start", "rows": 5000, "skipped": ["sanity"],
         "resumed_done": ["sanity"]},
        {"stage": "suite_phase_done", "phase": "parity", "rows": 5000},
    ]))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               WATCHER_PERF_LOG=str(log), TPU_SUITE_RESUME="1",
               TPU_SUITE_ONLY_PHASES="sanity,parity")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "tpu_perf_suite.py"),
         "5000"], capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    start = json.loads([l for l in p.stdout.splitlines()
                        if '"suite_start"' in l][-1])
    assert {"sanity", "parity"} <= set(start["skipped"])


def test_watcher_all_failed_window_not_captured(tmp_path):
    """A live backend with a persistently broken pipeline (every stage
    crashes) is NOT a captured window: the daemon keeps polling (with
    backoff) instead of reporting success, and post-parity failure records
    are tagged as suspect."""
    plan = tmp_path / "stage_plan.json"
    plan.write_text(json.dumps(
        {n: ["crash", "crash"] for n in
         ("parity", "perf_suite", "onehot_shootout", "headline",
          "bench_serve", "bench_stream")}))
    p = _run_watcher(tmp_path, {"WATCHER_FAKE_BACKEND": "ok",
                                "WATCHER_FAKE_STAGE_PLAN": str(plan)},
                     args=("--stage-timeout", "5", "--max-polls", "2"))
    assert p.returncode == 3, p.stderr
    j = _journal(tmp_path)
    assert j["windows_captured"] == 0 and j["state"] == "poll"
    wins = [r for r in _perf_records(tmp_path)
            if r.get("stage") == "watcher_window"]
    assert len(wins) == 2 and all(w["captured"] is False for w in wins)
    # numbers-bearing records after a parity failure carry the taint flag
    rec = [r for r in _perf_records(tmp_path)
           if r.get("stage") == "watcher_perf_suite"]
    assert rec and all(r.get("parity_failed") is True for r in rec)


def test_watcher_done_journal_rerun_runs_real_window(tmp_path):
    """Rerunning over a finished journal starts a FRESH window: the old
    all-ok stages must genuinely re-run, not skip straight to a phantom
    'captured' record."""
    for _ in range(2):
        p = _run_watcher(tmp_path, {"WATCHER_FAKE_BACKEND": "ok"},
                         args=("--stage-timeout", "10"))
        assert p.returncode == 0, p.stderr
    fake = [r["stage"] for r in _perf_records(tmp_path) if r.get("fake")]
    assert fake == ["parity", "perf_suite", "onehot_shootout", "headline",
                    "bench_serve", "bench_stream"] * 2
    wins = [r for r in _perf_records(tmp_path)
            if r.get("stage") == "watcher_window"]
    assert len(wins) == 2 and all(w["captured"] is True for w in wins)
