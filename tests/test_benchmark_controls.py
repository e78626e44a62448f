"""The half of the benchmark's own tests that ``test_benchmark_suite.py``
leaves out: every configuration's generator held to one population, and the
job under every configuration's limits with the timed path broken."""
from benchmarks.tests.test_benchmark import (  # noqa: F401
    test_broken_timed_path_is_not_correct,
    test_two_seeds_are_two_samples_of_one_population)
