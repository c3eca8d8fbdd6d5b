"""Device: share of the device-idle time in the traced window that no
``dsod.*`` span of fit()'s thread covers."""

from benchmark.harness import spans


def read(run):
    red = spans.of_run(run)
    if not red or not red["idle_s"]:
        return None
    idle = sum(red["idle_s"].values())
    return 100.0 * red["idle_s"][spans.UNATTRIBUTED] / idle if idle else 0.0
