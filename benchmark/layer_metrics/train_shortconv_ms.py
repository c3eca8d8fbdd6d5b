"""Kernels and XLA fusions: device self time per step under
``dsod.shortconv`` (the gated short convolutions: two projections, the
gates and the 3-tap causal depthwise conv), from the traced steps."""

from benchmark.harness import scopes_lm


def read(run):
    return scopes_lm.scope_ms_per_step(run, "shortconv")
