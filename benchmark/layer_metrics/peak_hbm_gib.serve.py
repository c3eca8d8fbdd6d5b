"""Device: the runtime's ``peak_bytes_in_use`` + ``peak_bytes_reserved``
on the fullest chip, read after the window (``harness/stats.py``): live
arrays plus what the runtime set aside for programs' scratch."""


def read(run):
    if not run.get("memory_peak_bytes"):
        return None
    return run["memory_peak_bytes"] / 2 ** 30
