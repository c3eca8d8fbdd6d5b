"""Kernels and XLA fusions: what the expert layer spends AROUND its
products — ``dsod.moe.route`` (router, top-k, sort, gather into expert
order) plus ``dsod.moe.combine`` — per step, from the traced steps."""

from benchmark.harness import scopes_lm


def read(run):
    parts = [scopes_lm.scope_ms_per_step(run, p)
             for p in ("moe.route", "moe.combine")]
    return None if None in parts else sum(parts)
