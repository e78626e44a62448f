"""Share of the window the device spent in operations other than the histogram
kernels: partition, gather, segmented sums, split search, gradients, score
update."""
from benchmarks import trace_reduce


def read(run):
    if run["trace"] is None:
        return None
    hist = trace_reduce.kernel_seconds(run["trace"], "hist_kernel") or 0.0
    total = sum(trace_reduce.op_seconds(run["trace"]).values())
    return 100.0 * (total - hist) / run["window_s"]
