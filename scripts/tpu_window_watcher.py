"""Unattended TPU-window watcher: poll for a live backend, then spend the
window on the full perf story with zero human attention.

For a machine whose TPU comes and goes: this daemon converts "hope
someone is at the keyboard when the backend answers" into infrastructure.
(With a one-command chip tool there is nothing to poll for: see README
"Running".)  The watcher itself never imports jax, so each stage it starts
is the one process that holds the chip.  It is a state machine journaled
to ``watcher_state.json``:

  POLL      probe the backend (``bench.probe_backend``: subprocess +
            process group + killpg, ~10 min cadence) with jittered
            exponential backoff on repeated failure.
  PIPELINE  on the first live probe, run the staged capture — each stage
            its OWN subprocess under a wall-clock budget:
              parity           scripts/bench_dual.py
              perf_suite       scripts/tpu_perf_suite.py
              onehot_shootout  scripts/bench_onehot_variants.py
              headline         bench.py
            A stage crash or hang records a failure and DEGRADES to the
            remaining stages (window time is precious; one broken kernel
            must not cost the headline number).  After any stage failure
            the backend is re-probed: a dead probe means the window
            re-wedged mid-run — the watcher returns to POLL and, on the
            next window, RESUMES from the first incomplete stage instead
            of restarting (completed and deliberately-failed stages are
            never re-run within a window).
  DONE      after ``--max-windows`` captured windows.

Every stage result is appended to ``perf_results.jsonl`` as it lands; a
heartbeat jsonl (``watcher_heartbeat.jsonl``) records every poll, attempt,
backoff, and kill so a dead watcher leaves a legible trail.  A
single-owner pid-checked lock file (``watcher.lock``) guarantees only one
process ever touches the TPU: a second invocation refuses to start with a
clear message and exit code 2.

Fault-injection seam (CPU-testable, no TPU required): setting
``WATCHER_FAKE_BACKEND=ok|fail|hang|flaky`` swaps the probe and every
stage command for scripted fakes (re-invocations of this file with
``--fake-probe`` / ``--fake-stage``).  Finer scripting for tests:
``WATCHER_FAKE_PROBE_PLAN`` (file of ok/fail/hang lines, popped one per
probe) and ``WATCHER_FAKE_STAGE_PLAN`` (JSON file {stage: [behavior,...]},
popped one per invocation).  See docs/WATCHER.md.

Run unattended (the ONLY process touching the TPU):
    nohup python scripts/tpu_window_watcher.py >/dev/null 2>&1 &
Exit codes: 0 captured/stepped, 2 lock held, 3 --max-polls exhausted.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

STAGE_NAMES = ("parity", "perf_suite", "onehot_shootout", "headline",
               "bench_serve", "bench_stream")
JOURNAL_VERSION = 1


# --------------------------------------------------------------------------
# scripted fakes (run FIRST: the fake subprocesses must not import numpy/
# jax or take the argparse path)
# --------------------------------------------------------------------------

def _perf_log_path() -> str:
    return os.environ.get("WATCHER_PERF_LOG",
                          os.path.join(REPO, "perf_results.jsonl"))


def _append_perf(rec: dict) -> None:
    rec.setdefault("ts", round(time.time(), 3))
    with open(_perf_log_path(), "a") as f:
        f.write(json.dumps(rec) + "\n")


def _append_regress_verdict(stage: str, window_id) -> None:
    """Post-stage self-judgment: classify the numbers the stage just
    appended against the journal + BENCH_r* history via the jax-free
    regression sentinel (obs.regress), so a slower-than-last-window
    result flags WHILE the window is still open instead of after it
    closes.  Never fatal — a verdict bug must not cost a captured
    stage."""
    try:
        regress = bench.load_obs().regress
        res = regress.scan(journal_path=_perf_log_path())
        _append_perf({"stage": "watcher_regress", "after_stage": stage,
                      "window_id": window_id, "counts": res["counts"],
                      "regressed": res["regressed"],
                      "worst": [v for v in res["verdicts"]
                                if v["verdict"] == "regressed"][:5]})
    except Exception as e:
        _append_perf({"stage": "watcher_regress", "after_stage": stage,
                      "window_id": window_id,
                      "error": f"{type(e).__name__}: {e}"[:200]})


def _pop_plan_line(path: str) -> "str | None":
    """Pop the first nonempty line of a plan file (test scripting).  The
    watcher runs fakes strictly one at a time, so read-modify-write is
    race-free."""
    try:
        with open(path) as f:
            lines = [l.strip() for l in f.read().splitlines()]
    except OSError:
        return None
    lines = [l for l in lines if l]
    if not lines:
        return None
    with open(path, "w") as f:
        f.write("\n".join(lines[1:]) + ("\n" if len(lines) > 1 else ""))
    return lines[0]


def _hang_with_grandchild() -> None:
    """Fork a grandchild and hang both — the supervisor's killpg must reap
    the whole tree.  Pids go to WATCHER_GRANDCHILD_PIDFILE so tests can
    assert neither survives.  Sleeps are finite (a failed kill must not
    leak a truly immortal process into CI)."""
    child = os.fork()
    if child == 0:
        time.sleep(120)
        os._exit(0)
    pidfile = os.environ.get("WATCHER_GRANDCHILD_PIDFILE")
    if pidfile:
        with open(pidfile, "w") as f:
            json.dump({"child": os.getpid(), "grandchild": child}, f)
    print("hanging", flush=True)
    time.sleep(120)


def _fake_probe() -> int:
    plan = os.environ.get("WATCHER_FAKE_PROBE_PLAN")
    behavior = _pop_plan_line(plan) if plan else None
    if behavior is None:
        mode = os.environ.get("WATCHER_FAKE_BACKEND", "ok")
        if mode == "flaky":
            # fail twice, succeed on every third probe (counter on disk —
            # each probe is a fresh subprocess)
            cnt_path = os.path.join(
                os.environ.get("WATCHER_STATE_DIR", "."), "fake_probe_count")
            try:
                with open(cnt_path) as f:
                    n = int(f.read().strip() or 0)
            except (OSError, ValueError):
                n = 0
            with open(cnt_path, "w") as f:
                f.write(str(n + 1))
            behavior = "ok" if (n + 1) % 3 == 0 else "fail"
        else:
            behavior = mode
    if behavior == "hang":
        _hang_with_grandchild()
        return 1
    if behavior == "ok":
        print("ndev=1")
        return 0
    print("ndev=0")
    return 1


def _arm_fake_flight(name: str):
    """Arm a flight recorder inside a fake stage when the supervisor
    exported ``LGBM_FLIGHT_DIR`` (run_stage's flight_dir seam).  Loads the
    stdlib-only obs package standalone — fake subprocesses must not import
    bench/numpy/jax.  flush_every=1 so even a SIGKILLed hang leaves its
    eager flush on disk."""
    if not os.environ.get("LGBM_FLIGHT_DIR"):
        return None
    try:
        import importlib.util
        pkg_dir = os.path.join(REPO, "lightgbm_tpu", "obs")
        spec = importlib.util.spec_from_file_location(
            "_watcher_fake_obs", os.path.join(pkg_dir, "__init__.py"),
            submodule_search_locations=[pkg_dir])
        mod = importlib.util.module_from_spec(spec)
        sys.modules["_watcher_fake_obs"] = mod
        spec.loader.exec_module(mod)
        rec = mod.flight.install(flush_every=1)
        rec.note("fake_stage_start", stage=name, pid=os.getpid())
        return rec
    except Exception:
        return None      # forensics must never break the fake itself


def _fake_stage(name: str) -> int:
    flight_rec = _arm_fake_flight(name)
    behavior = None
    plan = os.environ.get("WATCHER_FAKE_STAGE_PLAN")
    if plan:
        table = {}
        try:
            with open(plan) as f:
                table = json.load(f)
        except (OSError, ValueError):
            pass
        seq = table.get(name) or []
        if seq:
            behavior = seq.pop(0)
            with open(plan, "w") as f:
                json.dump(table, f)
    if behavior is None:
        behavior = "ok"
    if flight_rec is not None:
        flight_rec.note("fake_stage_behavior", stage=name,
                        behavior=behavior)
    if behavior == "hang":
        _hang_with_grandchild()
        return 1
    if behavior in ("crash", "fail"):
        return 1
    _append_perf({"stage": name, "fake": True})
    if name == "headline":
        # mimic bench.py's one-JSON-line contract so the parent's
        # extraction path is exercised end to end
        print(json.dumps({"metric": "higgs_1m_train_throughput",
                          "value": 1.0, "unit": "Mrow_iters/sec",
                          "vs_baseline": 0.0248, "detail": {"fake": True}}))
    return 0


if "--fake-probe" in sys.argv[1:2]:
    sys.exit(_fake_probe())
if "--fake-stage" in sys.argv[1:2]:
    sys.exit(_fake_stage(sys.argv[2]))


# --------------------------------------------------------------------------
# watcher proper
# --------------------------------------------------------------------------

import bench                                                    # noqa: E402

sup = bench._load_supervise()


def stage_table(args) -> list:
    """(name, argv, timeout_sec) in pipeline order.  A
    backend that dies mid-stage is caught by the stage's wall-clock
    budget."""
    py = sys.executable
    fake = bool(os.environ.get("WATCHER_FAKE_BACKEND"))
    me = os.path.abspath(__file__)
    t = {"parity": args.stage_timeout or 1800,
         "perf_suite": args.stage_timeout or 7200,
         "onehot_shootout": args.stage_timeout or 3600,
         "headline": args.stage_timeout or 3600,
         "bench_serve": args.stage_timeout or 1800,
         "bench_stream": args.stage_timeout or 1800}
    if fake:
        return [(n, [py, me, "--fake-stage", n], t[n])
                for n in STAGE_NAMES]
    return [
        ("parity", [py, os.path.join(REPO, "scripts", "bench_dual.py")],
         t["parity"]),
        ("perf_suite", [py, os.path.join(REPO, "scripts",
                                         "tpu_perf_suite.py")],
         t["perf_suite"]),
        # the shootout sweeps every registry variant family at the bench
        # width AND max_bin=64 (exercising the lane-packing variant); the
        # flag mirrors the script default so the sweep is explicit in the
        # journal's argv without changing watcher_state.json semantics
        ("onehot_shootout", [py, os.path.join(REPO, "scripts",
                                              "bench_onehot_variants.py"),
                             "--max-bin", "255,64"],
         t["onehot_shootout"]),
        ("headline", [py, os.path.join(REPO, "bench.py")],
         t["headline"]),
        # serving p50/p99 + rows/s (docs/SERVING.md)
        ("bench_serve", [py, os.path.join(REPO, "scripts",
                                          "bench_serve.py")],
         t["bench_serve"]),
        # out-of-core streaming rows/s + H2D-overlap efficiency
        # (docs/STREAMING.md): on hardware the overlap numbers become the
        # real double-buffering measurement
        ("bench_stream", [py, os.path.join(REPO, "scripts",
                                           "bench_stream.py"), "--quick"],
         t["bench_stream"]),
    ]


def probe(args, hb) -> bool:
    argv = None
    if os.environ.get("WATCHER_FAKE_BACKEND"):
        argv = [sys.executable, os.path.abspath(__file__), "--fake-probe"]
    t0 = time.monotonic()
    live = bench.probe_backend(args.probe_timeout, argv=argv)
    hb("probe", live=bool(live), secs=round(time.monotonic() - t0, 3))
    return bool(live)


# ---- journal --------------------------------------------------------------

def fresh_stages() -> list:
    return [{"name": n, "status": "pending"} for n in STAGE_NAMES]


def fresh_journal() -> dict:
    return {"version": JOURNAL_VERSION, "state": "poll", "window_id": 1,
            "probe_failures": 0, "window_failures": 0, "polls": 0,
            "windows_captured": 0, "stages": fresh_stages()}


def load_journal(path: str) -> dict:
    j = sup.read_json(path, default=None)
    if not isinstance(j, dict) or j.get("version") != JOURNAL_VERSION:
        j = fresh_journal()
    # reconcile against the current stage table: renames/additions get a
    # pending entry, vanished stages are dropped, order is canonical
    by_name = {s.get("name"): s for s in j.get("stages", [])}
    j["stages"] = [by_name.get(n, {"name": n, "status": "pending"})
                   for n in STAGE_NAMES]
    # a stage left "running" means the WATCHER died mid-stage: incomplete
    for s in j["stages"]:
        if s.get("status") == "running":
            s["status"] = "interrupted"
    return j


def save_journal(path: str, j: dict) -> None:
    j["updated"] = round(time.time(), 3)
    sup.write_json_atomic(path, j)


def incomplete(j: dict) -> list:
    """Stages still owed to the CURRENT window (resume set): everything not
    terminally ok/failed."""
    return [s for s in j["stages"] if s["status"] not in ("ok", "failed")]


# ---- pipeline -------------------------------------------------------------

def run_pipeline(args, j: dict, hb) -> str:
    """Run every incomplete stage in order; returns "complete" (all stages
    terminal) or "wedged" (backend died mid-window; journal holds the
    resume point)."""
    table = stage_table(args)
    for name, argv, timeout in table:
        ent = next(s for s in j["stages"] if s["name"] == name)
        if ent["status"] in ("ok", "failed"):
            continue
        resumed = ent["status"] == "interrupted"
        ent["status"] = "running"
        save_journal(args.journal, j)
        env = dict(os.environ)
        env["WATCHER_PERF_LOG"] = _perf_log_path()
        if args.health_port:
            # stages run strictly one at a time, so a single port serves
            # whichever stage is live; each stage's loops call
            # obs.health.maybe_start off this env var
            env["LGBM_OBS_HEALTH_PORT"] = str(args.health_port)
        parity_ok = next(s for s in j["stages"]
                         if s["name"] == "parity")["status"] == "ok"
        if name == "perf_suite":
            if resumed:
                # a suite killed mid-phase left suite_phase_done markers
                # in perf_results.jsonl; let it skip what already landed
                env["TPU_SUITE_RESUME"] = "1"
            if parity_ok:
                # the watcher's parity stage IS bench_dual: don't burn
                # window time re-running the same checks in the suite's
                # parity phase.  But ONLY when our parity actually passed
                # — on a parity failure the suite must keep its own
                # "abort before recording numbers off a wrong kernel"
                # invariant.  (The suite's internal headline stays: it is
                # the grow_sweep-tuned measurement, distinct from the
                # watcher's default-knob headline stage.)
                env["TPU_SUITE_SKIP_PHASES"] = ",".join(filter(None, [
                    env.get("TPU_SUITE_SKIP_PHASES", ""), "parity"]))
        res = sup.run_stage(name, argv, timeout=timeout,
                            retries=args.stage_retries,
                            backoff=args.stage_backoff,
                            heartbeat=hb, env=env, cwd=REPO,
                            # crashed/hung stages leave their flight
                            # recorder dumps beside the journal
                            flight_dir=args.state_dir)
        ent["detail"] = {**res.to_record(), "window_id": j["window_id"],
                         **({"resumed": True} if resumed else {}),
                         # numbers recorded after a parity failure are
                         # suspect: say so ON the record, not just in the
                         # window summary
                         **({} if parity_ok or name == "parity"
                            else {"parity_failed": True})}
        if res.ok:
            ent["status"] = "ok"
            rec = {**ent["detail"], "stage": f"watcher_{name}"}
            if name == "headline":
                payload = sup.extract_json_line(res.output_tail)
                if payload:
                    rec["result"] = payload
            _append_perf(rec)
            _append_regress_verdict(name, j["window_id"])
            save_journal(args.journal, j)
            continue
        # crash or hang: distinguish "this stage is broken" from "the
        # whole window re-wedged" by re-probing the backend
        if probe(args, hb):
            ent["status"] = "failed"
            _append_perf({**ent["detail"], "stage": f"watcher_{name}",
                          "output_tail": res.output_tail[-500:]})
            hb("stage_degraded", stage=name, status=res.status)
            save_journal(args.journal, j)
            continue
        ent["status"] = "interrupted"
        _append_perf({"stage": "watcher_rewedge", "during": name,
                      "window_id": j["window_id"]})
        hb("rewedge", during=name)
        j["state"] = "poll"
        j["probe_failures"] = 1
        save_journal(args.journal, j)
        return "wedged"
    return "complete"


def finish_window(args, j: dict, hb) -> None:
    """Close out a window whose stages are all terminal.  A window where
    NOTHING succeeded is not a capture: a persistent stage defect on a
    live backend (e.g. an import error crashing every stage in seconds)
    must not let the daemon report success and stop polling — it retries
    from scratch on the poll cadence, with backoff, leaving a
    ``captured: false`` trail."""
    statuses = {s["name"]: s["status"] for s in j["stages"]}
    captured = any(v == "ok" for v in statuses.values())
    _append_perf({"stage": "watcher_window", "window_id": j["window_id"],
                  "stages": statuses, "captured": captured})
    # per-window observability artifact: render the perf journal through
    # obs.report (jax-free loader — this process must never touch the
    # backend) into a markdown digest beside the log.  Never fatal: a
    # render bug must not cost the captured window.
    try:
        report = bench.load_obs().report
        loaded = report.load_perf_log(_perf_log_path())
        md = report.render_markdown(report.summarize(loaded))
        art_path = os.path.join(
            os.path.dirname(os.path.abspath(_perf_log_path())),
            f"obs_report_window_{j['window_id']}.md")
        with open(art_path, "w") as f:
            f.write(md)
        _append_perf({"stage": "watcher_obs_report",
                      "window_id": j["window_id"], "path": art_path,
                      "events": loaded["total"], "bad": loaded["bad"]})
    except Exception as e:
        _append_perf({"stage": "watcher_obs_report",
                      "window_id": j["window_id"],
                      "error": f"{type(e).__name__}: {e}"[:300]})
    hb("window_complete", window_id=j["window_id"], stages=statuses,
       captured=captured)
    if captured:
        j["windows_captured"] += 1
        j["window_failures"] = 0
    else:
        # its own backoff counter (probe_failures is reset by every live
        # probe, so it cannot carry this): the backend is live but the
        # pipeline is broken — a hot retry loop would burn the window
        j["window_failures"] = j.get("window_failures", 0) + 1
    if not captured or j["windows_captured"] < args.max_windows:
        j["window_id"] += 1
        j["stages"] = fresh_stages()
        j["state"] = "poll"
    else:
        j["state"] = "done"
    save_journal(args.journal, j)
    return captured


def poll_delay(args, failures: int, rng: random.Random) -> float:
    """Backoff the POLL cadence on consecutive dead probes: base interval
    doubling per failure (after the first) up to ``--poll-cap``, jittered
    ±25% so restarted watchers don't synchronize against the backend."""
    d = min(args.poll_cap,
            args.poll_interval * (2.0 ** min(max(failures - 1, 0), 16)))
    return d * (1.0 + 0.25 * (2.0 * rng.random() - 1.0))


def watch(args, hb) -> int:
    rng = random.Random()
    j = load_journal(args.journal)
    if j["state"] == "done":
        # ANY finished journal restarts fresh (rerun later for another
        # window — including with a raised --max-windows: the old all-ok
        # stages must not skip straight to a phantom 'captured' window)
        j = fresh_journal()
    polls = 0          # consecutive polls WITHOUT a capture (exit-3 gauge)
    while True:
        live = probe(args, hb)
        polls += 1
        j["polls"] = j.get("polls", 0) + 1
        if live:
            j["probe_failures"] = 0
            j["state"] = "pipeline"
            save_journal(args.journal, j)
            hb("window_open", window_id=j["window_id"],
               resume=[s["name"] for s in incomplete(j)])
            if run_pipeline(args, j, hb) == "complete":
                if finish_window(args, j, hb):
                    polls = 0          # captured: the give-up clock restarts
                if j["state"] == "done":
                    return 0
        else:
            j["probe_failures"] = j.get("probe_failures", 0) + 1
            j["state"] = "poll"
            save_journal(args.journal, j)
        if args.once:
            return 0
        if args.max_polls and polls >= args.max_polls:
            hb("give_up", polls=polls)
            return 3
        # either trouble source backs the cadence off: dead probes, or
        # live-but-broken pipelines (window_failures)
        failures = j["probe_failures"] + j.get("window_failures", 0)
        d = poll_delay(args, failures, rng)
        hb("sleep", delay_sec=round(d, 3), probe_failures=j["probe_failures"],
           window_failures=j.get("window_failures", 0))
        time.sleep(d)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        description="Unattended TPU-window perf-capture watcher")
    ap.add_argument("--state-dir",
                    default=os.environ.get("WATCHER_STATE_DIR", REPO),
                    help="directory for journal/lock/heartbeat files")
    ap.add_argument("--poll-interval", type=float,
                    default=float(os.environ.get("WATCHER_POLL_INTERVAL",
                                                 600)),
                    help="seconds between backend probes (default 600)")
    ap.add_argument("--poll-cap", type=float,
                    default=float(os.environ.get("WATCHER_POLL_CAP", 3600)),
                    help="max backed-off poll interval (default 3600)")
    ap.add_argument("--probe-timeout", type=float,
                    default=float(os.environ.get("WATCHER_PROBE_TIMEOUT",
                                                 300)))
    ap.add_argument("--stage-timeout", type=float,
                    default=float(os.environ.get("WATCHER_STAGE_TIMEOUT", 0))
                    or None,
                    help="override EVERY stage's wall-clock budget (tests)")
    ap.add_argument("--stage-retries", type=int,
                    default=int(os.environ.get("WATCHER_STAGE_RETRIES", 0)))
    ap.add_argument("--stage-backoff", type=float,
                    default=float(os.environ.get("WATCHER_STAGE_BACKOFF", 5)))
    ap.add_argument("--max-windows", type=int, default=1,
                    help="exit 0 after this many captured windows")
    ap.add_argument("--max-polls", type=int, default=0,
                    help="exit 3 after this many polls without capture "
                         "(0 = poll forever)")
    ap.add_argument("--once", action="store_true",
                    help="one poll step (and pipeline, if live) then exit")
    ap.add_argument("--health-port", type=int,
                    default=int(os.environ.get("WATCHER_HEALTH_PORT", 0)),
                    help="export LGBM_OBS_HEALTH_PORT to stages so the "
                         "live stage serves /metrics //healthz here "
                         "(0 = off)")
    args = ap.parse_args(argv)
    os.makedirs(args.state_dir, exist_ok=True)
    args.journal = os.path.join(args.state_dir, "watcher_state.json")
    args.lock = os.path.join(args.state_dir, "watcher.lock")
    args.heartbeat = os.path.join(args.state_dir, "watcher_heartbeat.jsonl")
    os.environ["WATCHER_STATE_DIR"] = args.state_dir
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    hb = sup.Heartbeat(args.heartbeat)
    lock = sup.SingleOwnerLock(args.lock)
    try:
        lock.acquire()
    except sup.LockHeldError as e:
        print(f"tpu_window_watcher: {e}", file=sys.stderr)
        return 2
    hb("start", argv=sys.argv,
       fake=os.environ.get("WATCHER_FAKE_BACKEND", ""))
    try:
        return watch(args, hb)
    finally:
        hb("stop")
        lock.release()


if __name__ == "__main__":
    sys.exit(main())
