"""Kernels and XLA fusions: device self time per step under
``dsod.moe.shared`` in the latent expert layers (the shared expert's
``relu(.)^2`` feed-forward over every token; forward, recomputed and
backward), from the traced steps."""

from benchmark.harness import scopes_hybrid


def read(run):
    return scopes_hybrid.scope_ms_per_step(run, "moe.shared")
