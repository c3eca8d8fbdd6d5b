"""Train loop on the host: ``dsod.setup.warmup``, the first call's
return to the run's opening tick (``run["ticks"][0]["t"]``): the steps
before the window, the tap's other two recorded calls among them."""

from benchmark.harness import setup_phases


def read(run):
    return setup_phases.span_s(run, "warmup")
