"""Train loop on the host: device-idle time per step that fell inside
any ``dsod.train.*`` span of fit()'s thread (dispatch, metric fetch,
logging and hooks, checkpoint, eval, the rest of the step's body)."""

from benchmark.harness import spans


def read(run):
    return spans.idle_ms_per_step(run, "dsod.train.")
