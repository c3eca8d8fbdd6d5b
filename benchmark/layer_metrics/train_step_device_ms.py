"""Compiled train step: device busy time (union of device-op
intervals, mean over chips) in the traced steps, per step."""


def read(run):
    tr, n = run.get("trace"), run.get("traced_steps")
    if not tr or not n:
        return None
    return tr["busy_s"] * 1000.0 / n
