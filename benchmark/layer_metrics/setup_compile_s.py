"""Compiled train step: seconds in backend compiles (a persistent-cache
hit's retrieval included), by JAX's own monitoring events that ended
before the opening tick."""

from benchmark.harness import setup_phases


def read(run):
    return setup_phases.counter_s(run, ("compile",))
