"""Share of the window spent in the histogram kernels: self time of the Mosaic
custom calls (``benchmarks/kernels.json`` says how the trace names them) over
the window, averaged over chips."""
from benchmarks import trace_reduce


def read(run):
    sec = trace_reduce.kernel_seconds(run["trace"], "hist_kernel")
    return None if sec is None else 100.0 * sec / run["window_s"]
