"""Batcher: median ``queue`` of the answered requests' X-Timing."""
from benchmark.harness.stats import median


def read(run):
    rows = [r["timing"]["queue"] for r in run.get("requests", [])
            if r.get("timing")]
    return median(rows) if rows else None
