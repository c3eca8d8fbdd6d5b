"""Compiled train step: seconds of Python tracing and of lowering to
StableHLO, by JAX's own monitoring events that ended before the opening
tick (every program of the set-up, the step's the largest)."""

from benchmark.harness import setup_phases


def read(run):
    return setup_phases.counter_s(run, ("trace", "lower"))
