"""Least time over the histogram kernels' traced time.  The least time is what
any implementation needs (``benchmarks/work.py``): the root's rows plus each
split's smaller child, read off the grown trees' own counts, times (columns
bin bytes + 8 gradient bytes), over the chip's memory bandwidth.  The bound is
HBM bytes, not arithmetic."""
from benchmarks import trace_reduce, work


def read(run):
    sec = trace_reduce.kernel_seconds(run["trace"], "hist_kernel")
    if not sec:
        return None
    least = work.least_seconds(run["trees"], run["columns"], run["device_kind"])
    return 100.0 * least / sec
