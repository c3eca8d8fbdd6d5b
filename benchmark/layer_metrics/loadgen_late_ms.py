"""The benchmark's own generator: p95 of (actual send - due)."""
from benchmark.harness.stats import percentile


def read(run):
    rows = [r["late_ms"] for r in run.get("requests", [])]
    return percentile(rows, 95) if rows else None
