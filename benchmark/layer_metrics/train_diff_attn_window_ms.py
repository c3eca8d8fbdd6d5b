"""Kernels and XLA fusions: device self time per step under
``dsod.attn.window`` (the windowed differential-attention mixers: the
biased projections, the pairing, the kernels under ``dsod.attn.flash``,
lambda, the sub-layer norm), from the traced steps."""

from benchmark.harness import scopes_phi4flash


def read(run):
    return scopes_phi4flash.scope_ms_per_step(run, "attn.window")
