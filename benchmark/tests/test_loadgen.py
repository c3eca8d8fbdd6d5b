"""The generator's schedule and its due-time arithmetic, against a
stub server that answers after a fixed delay."""

import http.server
import statistics
import threading
import time

from benchmark.harness import loadgen


def test_schedule_same_work_in_another_order():
    a = loadgen.make_schedule(200.0, 10.0, 256, seed=1)
    b = loadgen.make_schedule(200.0, 10.0, 256, seed=3000000019)
    assert a == loadgen.make_schedule(200.0, 10.0, 256, seed=1)
    assert len(a) == len(b) == 2000
    assert a != b
    gaps = lambda s: sorted(round(y[0] - x[0], 9) for x, y in zip(s, s[1:]))  # noqa: E731
    assert sorted(p for _, p in a) == sorted(p for _, p in b)
    # the same multiset of gaps, but for the one each order leaves last
    assert abs(sum(gaps(a)) - sum(gaps(b))) < 0.2
    assert all(0.0 <= d < 10.0 for d, _ in a)
    # Poisson: the gaps' spread is about their mean
    g = [y[0] - x[0] for x, y in zip(a, a[1:])]
    assert 0.8 < statistics.pstdev(g) / statistics.mean(g) < 1.2


class _Slow(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    delay = 0.05

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers["Content-Length"]))
        time.sleep(self.delay)
        body = b"ok"
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.send_header("X-Timing", "trace=-;queue=1.5;device=2.5;e2e=50.0")
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def test_latency_is_from_due_time_and_lateness_reported():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Slow)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    try:
        # 20 requests all due at once, ONE sender: the k-th waits for
        # k-1 others, and that wait counts from its due time.
        sched = [(0.0, 0)] * 20
        gen = loadgen.LoadGen("127.0.0.1", srv.server_address[1], [b"x"],
                              sched, timeout_s=5.0, senders=1)
        recs = gen.run()
    finally:
        srv.shutdown()
        srv.server_close()
    assert all(r["ok"] for r in recs)
    lat = [r["latency_ms"] for r in recs]
    assert lat[-1] > 19 * 50.0 and lat[0] < 200.0
    assert all(abs(r["latency_ms"] - r["late_ms"] - r["sent_ms"]) < 1e-6
               for r in recs)
    assert recs[-1]["late_ms"] > 18 * 50.0  # the generator ran late
    assert recs[0]["timing"] == {"queue": 1.5, "device": 2.5, "e2e": 50.0}
    s = loadgen.summarize(recs, 5.0, limit_ms=500.0, span_s=1.0)
    assert s["answered_share"] == 1.0 and s["n"] == 20
    assert s["ok_img_per_s"] == sum(1 for x in lat if x <= 500.0)


def test_failures_count_as_the_timeout():
    recs = [{"ok": True, "latency_ms": 10.0, "late_ms": 0.1}] * 9 \
        + [{"ok": False, "latency_ms": 3.0, "late_ms": 0.1}]
    s = loadgen.summarize(recs, timeout_s=2.0, limit_ms=50.0, span_s=2.0)
    assert s["p99_ms"] == 2000.0 and s["answered_share"] == 0.9
    assert s["ok_img_per_s"] == 4.5
