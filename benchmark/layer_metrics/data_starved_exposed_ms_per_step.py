"""Input pipeline: device-idle time per step that fell inside the
loop's wait for a batch (the ``dsod.data.starved`` span, the region the
``data_starved_ms`` counter times) — the part of that wait the device
felt."""

from benchmark.harness import spans


def read(run):
    return spans.idle_ms_per_step(run, "dsod.data.starved")
