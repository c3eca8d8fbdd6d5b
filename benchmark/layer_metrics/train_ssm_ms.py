"""Kernels and XLA fusions: device self time per step under ``dsod.ssm``
and below (the Mamba-2 mixer whole: projections, conv, scan, gated
norm; forward, recomputed and backward), from the traced steps."""

from benchmark.harness import scopes_ssm


def read(run):
    return scopes_ssm.scope_ms_per_step(run, "ssm")
