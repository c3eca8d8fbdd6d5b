"""Compiled train step: share of the device's busy time in the traced
steps whose ops sit under no ``dsod.<stage>`` scope — the coverage
counter that shows the scopes rotting."""

from benchmark.harness import spans


def read(run):
    red = spans.of_run(run)
    if not red or not red["stage_s"]:
        return None
    return 100.0 * red["stage_s"].get(spans.UNSCOPED, 0.0) \
        / sum(red["stage_s"].values())
