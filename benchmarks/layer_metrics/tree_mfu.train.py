"""The whole step's share of the chip's peak: the same least time
(``benchmarks/work.py``, bounded by HBM bytes) over the whole window.  It still
bounds a gain once a PR has taken the kernel off the path."""
from benchmarks import work


def read(run):
    if run["trace"] is None:
        return None
    least = work.least_seconds(run["trees"], run["columns"], run["device_kind"])
    return 100.0 * least / run["window_s"]
