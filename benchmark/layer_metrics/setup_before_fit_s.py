"""Train loop on the host: ``dsod.setup.before_fit``, the package's
first line to ``fit()``'s entry: the caller's imports of JAX and of the
program, the backend's start, cache set-up, the config build."""

from benchmark.harness import setup_phases


def read(run):
    return setup_phases.span_s(run, "before_fit")
