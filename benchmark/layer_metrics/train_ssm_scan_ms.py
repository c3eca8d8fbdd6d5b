"""Kernels and XLA fusions: device self time per step under
``dsod.ssm.scan`` (everything the state-space scan needs beyond its
projections: delta, the running sums, the kernels or the XLA form),
from the traced steps."""

from benchmark.harness import scopes_ssm


def read(run):
    return scopes_ssm.scope_ms_per_step(run, "ssm.scan")
