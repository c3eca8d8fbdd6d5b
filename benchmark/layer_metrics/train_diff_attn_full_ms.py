"""Kernels and XLA fusions: device self time per step under
``dsod.attn.full`` (the full-causal differential-attention mixers, the
self-attention layer whose keys and values are kept and the
cross-attention layers that read them: projections, pairing, the
kernels under ``dsod.attn.flash``, lambda, the sub-layer norm), from
the traced steps."""

from benchmark.harness import scopes_phi4flash


def read(run):
    return scopes_phi4flash.scope_ms_per_step(run, "attn.full")
