"""1 minus the union of device-busy intervals over the traced window."""
from benchmarks import trace_reduce


def read(run):
    if run["trace"] is None:
        return None
    trace = run["trace"]
    return 100.0 * (1.0 - trace_reduce.busy_seconds(trace) / trace_reduce.window_seconds(trace))
