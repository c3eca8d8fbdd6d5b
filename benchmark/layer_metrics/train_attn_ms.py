"""Kernels and XLA fusions: device self time per step under
``dsod.attn`` (projections, QK-norm, rotary embedding and the causal
flash kernels), from the traced steps."""

from benchmark.harness import scopes_lm


def read(run):
    return scopes_lm.scope_ms_per_step(run, "attn")
