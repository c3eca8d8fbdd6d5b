"""Engine forward: median ``device`` of the answered requests'
X-Timing (dispatch to fetch complete, per batch)."""
from benchmark.harness.stats import median


def read(run):
    rows = [r["timing"]["device"] for r in run.get("requests", [])
            if r.get("timing")]
    return median(rows) if rows else None
