"""Of the ``lgbm/eval/metric`` spans that start inside the window, the percent
whose metric ran on the device (argument ``on_device`` 1) and not on a host
copy of the scores.  None against a program whose metric spans carry no such
argument, and in a run with no device plane."""
from benchmarks import phase_reduce
from benchmarks.trace_reduce import window_of


def read(run):
    obs = phase_reduce._program()
    if obs is None or run.get("trace") is None:
        return None
    spans = [s.as_dict() for s in obs.get_tracer().spans()]
    clock = phase_reduce.clock_offset(run["trace"], spans)
    if clock is None:
        return None
    lo, hi = (t - clock[0] for t in window_of(run["trace"]))
    flags = [(s["args"] or {}).get("on_device") for s in spans
             if s["name"] == "lgbm/eval/metric" and lo <= s["start"] < hi]
    if not flags or None in flags:
        return None
    return 100.0 * sum(flags) / len(flags)
