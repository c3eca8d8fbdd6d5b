"""Compiled train step: device self time per step of the ops under the
``dsod.encoder`` named scope (forward, backward and rematerialised
copies), from the traced steps."""

from benchmark.harness import spans


def read(run):
    return spans.stage_ms_per_step(run, "encoder")
