"""Compiled train step: ``dsod.setup.first_step``, the first call of
the step function, entry to return: Python trace, lowering, compile or
cache load (under the benchmark's tap also its weights from ``--seed``
and the fetch of the first loss)."""

from benchmark.harness import setup_phases


def read(run):
    return setup_phases.span_s(run, "first_step")
