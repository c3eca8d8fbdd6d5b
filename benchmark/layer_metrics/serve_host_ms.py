"""HTTP front end and post-processing: median over answered requests
of the client's latency from the send, less ``queue`` and ``device``."""
from benchmark.harness.stats import median


def read(run):
    rows = [r["sent_ms"] - r["timing"]["queue"] - r["timing"]["device"]
            for r in run.get("requests", []) if r.get("timing")]
    return median(rows) if rows else None
