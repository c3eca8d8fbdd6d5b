"""Kernels and XLA fusions: device self time per step under
``dsod.moe.shared`` (the shared experts' SwiGLU over every token,
forward, recomputed and backward), from the traced steps."""

from benchmark.harness import scopes_lm


def read(run):
    return scopes_lm.scope_ms_per_step(run, "moe.shared")
