"""Kernels and XLA fusions: device self time per step under
``dsod.densemlp`` inside the looped stack (the SwiGLU feed-forward of
every visit, forward, recomputed and backward), from the traced steps."""

from benchmark.harness import scopes_loop


def read(run):
    return scopes_loop.scope_ms_per_step(run, "densemlp")
