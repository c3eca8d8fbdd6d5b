"""Kernels and XLA fusions: device self time per step of every op under
a ``dsod.moe.*`` scope (router, top-k, sort and gather into expert
order; the grouped products; the weighted combine — forward, backward
and rematerialised), from the traced steps."""

from benchmark.harness import scopes_lm


def read(run):
    return scopes_lm.scope_ms_per_step(run, "moe.")
