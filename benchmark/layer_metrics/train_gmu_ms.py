"""Kernels and XLA fusions: device self time per step under
``dsod.gmu`` (the gated memory units: the gate's projection, its
product with the kept scan output, the output projection), from the
traced steps."""

from benchmark.harness import scopes_phi4flash


def read(run):
    return scopes_phi4flash.scope_ms_per_step(run, "gmu")
