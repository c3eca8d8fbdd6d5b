"""Kernels and XLA fusions: device self time per step under ``dsod.attn``
and below inside the looped stack (the four projections, the rotation,
the head-major copies and the attention core; every visit, forward,
recomputed and backward), from the traced steps."""

from benchmark.harness import scopes_loop


def read(run):
    return scopes_loop.scope_ms_per_step(run, "attn")
