"""Kernels and XLA fusions: device self time per step under
``dsod.ssm.conv`` (the depthwise causal conv, its bias and SiLU),
from the traced steps."""

from benchmark.harness import scopes_ssm


def read(run):
    return scopes_ssm.scope_ms_per_step(run, "ssm.conv")
