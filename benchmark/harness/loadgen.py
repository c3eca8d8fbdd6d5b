"""Open-loop load generator (stdlib only).

A corrected copy of the idea in the program's ``serve/loadgen.py``:

- arrivals are a Poisson process; the schedule is a fixed multiset of
  exponential gaps and payload picks (drawn once from the cell's own
  constant), put in another order by ``--seed``, so every seed offers
  the same work over the same span;
- latency is timed from when a request was DUE, not from when it was
  sent: a stall of the generator or its pool counts against the system
  under test's tail, and how late each send ran is reported beside it;
- a request that fails, is refused or times out counts as the timeout.

One dispatcher thread sleeps to each due time and hands the request to
a pool of sender threads, each with its own keep-alive connection.
"""

from __future__ import annotations

import contextlib
import http.client
import queue
import random
import threading
import time
from typing import Callable, List, Optional

SCHEDULE_CONSTANT = 20240924  # the multiset's own seed, the same for every run


def make_schedule(rate: float, span_s: float, n_payloads: int, seed: int):
    """-> [(due_s, payload_index)] with due times in [0, span_s)."""
    base = random.Random(SCHEDULE_CONSTANT)
    n = int(round(rate * span_s))
    gaps = [base.expovariate(1.0) for _ in range(n)]
    scale = span_s / sum(gaps)  # the multiset spans exactly span_s
    picks = [i % n_payloads for i in range(n)]
    order = random.Random(seed)
    order.shuffle(gaps)
    order.shuffle(picks)
    due, t = [], 0.0
    for g, p in zip(gaps, picks):
        due.append((t, p))
        t += g * scale
    return due


def parse_timing(header: Optional[str]) -> Optional[dict]:
    """``X-Timing: trace=-;queue=1.2;device=3.4;...`` -> {stage: ms}."""
    if not header:
        return None
    out = {}
    for part in header.split(";"):
        k, sep, v = part.strip().partition("=")
        if sep and k != "trace":
            try:
                out[k] = float(v)
            except ValueError:
                pass
    return out or None


class LoadGen:
    def __init__(self, host: str, port: int, payloads: List[bytes],
                 schedule, *, timeout_s: float, senders: int = 64,
                 check: Optional[Callable[[bytes], bool]] = None,
                 keep: Optional[set] = None, annotate=None):
        self.host, self.port, self.payloads = host, port, payloads
        self.schedule, self.timeout_s = schedule, timeout_s
        self.check, self.keep = check, keep or set()
        self.annotate = annotate  # context-manager factory or None
        self.records = [None] * len(schedule)
        self.bodies = {}
        self._q: "queue.Queue" = queue.Queue()
        self._threads = [threading.Thread(target=self._sender, daemon=True,
                                          name=f"loadgen-{i}")
                         for i in range(senders)]
        self.t0 = None

    def _sender(self):
        conn = None
        while True:
            item = self._q.get()
            if item is None:
                break
            i, due, pick = item
            t_send = time.perf_counter()
            status, timing, ok, body = 0, None, False, None
            try:
                if conn is None:
                    conn = http.client.HTTPConnection(
                        self.host, self.port, timeout=self.timeout_s)
                with (self.annotate("benchmark.loadgen_send")
                      if self.annotate else contextlib.nullcontext()):
                    conn.request("POST", "/predict", body=self.payloads[pick],
                                 headers={"Content-Type":
                                          "application/octet-stream"})
                    resp = conn.getresponse()
                    body = resp.read()
                status = resp.status
                timing = parse_timing(resp.getheader("X-Timing"))
                ok = status == 200 and (self.check(body) if self.check
                                        else True)
            except (OSError, http.client.HTTPException):
                if conn is not None:
                    conn.close()
                conn = None
            t_done = time.perf_counter()
            if i in self.keep and ok:
                self.bodies[i] = body
            self.records[i] = {
                "i": i, "due": due, "pick": pick, "status": status, "ok": ok,
                "late_ms": (t_send - self.t0 - due) * 1000.0,
                "sent_ms": (t_done - t_send) * 1000.0,
                "latency_ms": (t_done - self.t0 - due) * 1000.0,
                "timing": timing if ok else None}
        if conn is not None:
            conn.close()

    def run(self) -> List[dict]:
        """Blocks until every request of the schedule was answered, has
        failed or has timed out.  ``self.t0`` is the schedule's zero."""
        for t in self._threads:
            t.start()
        self.t0 = time.perf_counter()
        for i, (due, pick) in enumerate(self.schedule):
            wait = self.t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self._q.put((i, due, pick))
        for _ in self._threads:
            self._q.put(None)
        for t in self._threads:
            t.join(timeout=self.timeout_s * 2 + 5)
        for i, (due, pick) in enumerate(self.schedule):
            if self.records[i] is None:  # a sender that never came back
                self.records[i] = {"i": i, "due": due, "pick": pick,
                                   "status": 0, "ok": False, "late_ms": 0.0,
                                   "sent_ms": self.timeout_s * 1000.0,
                                   "latency_ms": self.timeout_s * 1000.0,
                                   "timing": None}
        return self.records


def summarize(records: List[dict], timeout_s: float, limit_ms: float,
              span_s: float) -> dict:
    """The serving numbers over one set of requests (all due in one
    span): a request not answered 200 with a sound body counts as the
    timeout."""
    from .stats import median, percentile

    lat = [r["latency_ms"] if r["ok"] else timeout_s * 1000.0
           for r in records]
    ok_in_time = sum(1 for r in records
                     if r["ok"] and r["latency_ms"] <= limit_ms)
    return {"n": len(records),
            "answered_share": sum(1 for r in records if r["ok"])
            / max(len(records), 1),
            "p50_ms": median(lat), "p95_ms": percentile(lat, 95),
            "p99_ms": percentile(lat, 99),
            "ok_img_per_s": ok_in_time / span_s,
            "late_p95_ms": percentile([r["late_ms"] for r in records], 95)}
