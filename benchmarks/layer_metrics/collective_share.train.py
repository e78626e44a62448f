"""Share of the window in operations under ``lgbm/collective/...`` (the
histograms' exchange, the gather of the validation scores for the AUC) on
the chip that sets the pace (``chip_reduce.pacing_chip``): 0 on one chip.
A chip waiting for the others inside a collective counts here.  None
against a program that names no such scope, and in a run with no device
plane."""
from benchmarks import chip_reduce, phase_reduce


def read(run):
    obs = phase_reduce._program()
    if run.get("trace") is None or not chip_reduce.names_collectives(obs):
        return None
    trace = run["trace"]
    return 100.0 * chip_reduce.collective_seconds(
        trace, chip_reduce.pacing_chip(trace), obs) / run["window_s"]
