"""Kernels and XLA fusions: device self time per step of the latent
expert layers' routing — ``dsod.moe.route`` (scores over all experts,
top-k, the dispatch plan, the gather into expert order and its
backward), ``dsod.moe.combine`` (the un-permute and its backward) and
``dsod.moe.balance`` — from the traced steps."""

from benchmark.harness import scopes_hybrid


def read(run):
    return scopes_hybrid.scope_ms_per_step(run, *scopes_hybrid.ROUTING)
