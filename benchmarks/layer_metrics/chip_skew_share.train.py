"""What a straggler costs, over the traced window: the most time any chip
spends in ``lgbm/collective/...`` operations less the least.  Every chip
moves the same bytes there; a chip that comes first waits inside the
collective for the last, so the chips' difference is the wait.  (Busy time
cannot show it: a chip waiting inside a collective is busy.)  0 on one
chip; None against a program that names no such scope, and in a run with
no device plane."""
from benchmarks import chip_reduce, phase_reduce, trace_reduce


def read(run):
    obs = phase_reduce._program()
    if run.get("trace") is None or not chip_reduce.names_collectives(obs):
        return None
    trace = run["trace"]
    waits = [chip_reduce.collective_seconds(trace, chip, obs) for chip in trace["device"]]
    return 100.0 * (max(waits) - min(waits)) / trace_reduce.window_seconds(trace)
