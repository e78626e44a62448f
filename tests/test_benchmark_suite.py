"""Tier-1 collects the benchmark's own tests (``benchmarks/tests``): the
plain reference, the trace reduction and how ``correct`` is decided are
guarded by the same run that guards the package.  No copy: one import a
file.  The generator's population checks and the faulted runs, half of the
time, are collected by ``test_benchmark_controls.py``, so that two workers
share the benchmark's tests."""
from benchmarks.tests.test_benchmark import *  # noqa: F401,F403
from benchmarks.tests.test_epsilon2000 import *  # noqa: F401,F403

del test_two_seeds_are_two_samples_of_one_population, test_broken_timed_path_is_not_correct  # noqa: F821
