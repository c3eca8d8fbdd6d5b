"""Train loop on the host: ``dsod.setup.build``, ``fit()``'s entry to
the first call of the compiled step: mesh, dataset, loader start and
first batch, model, state init (its own compile), checkpoint manager,
telemetry, the step's builder, the first batch on the device."""

from benchmark.harness import setup_phases


def read(run):
    return setup_phases.span_s(run, "build")
