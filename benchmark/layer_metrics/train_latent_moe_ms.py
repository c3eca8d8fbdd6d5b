"""Kernels and XLA fusions: device self time per step under ``dsod.moe.*``
in the latent expert layers (router, plan, gathers, the latent
projections, the routed products, the un-permute, the shared expert and
the bias update; forward, recomputed and backward), from the traced
steps."""

from benchmark.harness import scopes_hybrid


def read(run):
    return scopes_hybrid.scope_ms_per_step(run)
