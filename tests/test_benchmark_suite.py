"""Tier-1 collects the benchmark's own tests (``benchmarks/tests``): the
plain reference, the trace reduction and how ``correct`` is decided are
guarded by the same run that guards the package.  No copy: one import."""
from benchmarks.tests.test_benchmark import *  # noqa: F401,F403
