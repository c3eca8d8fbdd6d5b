"""Kernels and XLA fusions: device self time per step of the ops under
a ``dsod.resample`` named scope (``resize_to`` / ``resample_merge``:
forward, backward and rematerialised copies, whatever arm ran), summed
over the stages they sit in, from the traced steps."""

from benchmark.harness import spans


def read(run):
    red, n = spans.of_run(run), run.get("traced_steps")
    if not red or not red["stage_table_s"] or not n:
        return None
    return sum(s for k, s in red["stage_table_s"].items()
               if k.endswith("/resample")) * 1000.0 / n
