"""Input pipeline: host wait on the prefetch queue per step, from the
trainer's own metric stream (``data_starved_ms`` is per logging
interval), over the measured ticks after the first."""


def read(run):
    ticks = run.get("ticks") or []
    if len(ticks) < 2:
        return None
    steps = ticks[-1]["step"] - ticks[0]["step"]
    return sum(t["data_starved_ms"] for t in ticks[1:]) / steps
