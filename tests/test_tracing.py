"""End-to-end tracing + unified telemetry tests (utils/tracing.py,
utils/telemetry.py, the span threading through serve/ and train/ —
docs/OBSERVABILITY.md).

Invariants proven here:

- sampling is deterministic in the trace id (router and replica agree
  without coordination) and bounded: the completed-trace ring never
  exceeds capacity and worst-N exemplars survive eviction;
- every request served over live HTTP yields ONE complete trace: a
  rooted, gap-free span tree (request → queue/coalesce/device[fetch]/
  resize_back) whose durations reconcile with the X-Timing header AND
  the latency histograms' observations;
- retried and hedged requests share one trace id — the router's
  attempt spans (replica + breaker state tagged) all hang off the one
  request root;
- with tracing OFF (trace_sample=0) the /metrics payload is
  byte-identical to rendering ServeStats directly (the PR-8 surface);
- parse_prom_text/merge_prom_families round-trip histogram bucket
  lines and escaped label values (the fleet relabel path);
- the trainer telemetry sidecar serves /metrics //healthz //debug/
  traces //debug/profile off a LIVE fit(), chunk traces land with the
  documented span schema, and the loadgen --slowest breakdown reports
  trace ids + stage splits;
- MetricWriter without clu degrades to a LOGGED no-op and reports
  backend="noop".
"""

import io
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import flax.linen as nn
import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from distributed_sod_project_tpu.configs import (DataConfig,
                                                 ExperimentConfig,
                                                 FleetConfig, MeshConfig,
                                                 ModelConfig, OptimConfig,
                                                 ServeConfig, get_config)
from distributed_sod_project_tpu.serve.engine import InferenceEngine
from distributed_sod_project_tpu.serve.fleet import EngineBackend, Fleet
from distributed_sod_project_tpu.serve.loadgen import run_loadgen
from distributed_sod_project_tpu.serve.router import make_fleet_server
from distributed_sod_project_tpu.serve.server import make_server
from distributed_sod_project_tpu.utils.observability import (
    PipelineStats, ServeStats, TelemetryRegistry, merge_prom_families,
    parse_prom_text, render_prom_families)
from distributed_sod_project_tpu.utils.tracing import (Tracer,
                                                       format_timing,
                                                       mint_trace_id,
                                                       parse_timing,
                                                       trace_sampled)


class TinySOD(nn.Module):
    """Minimal model with the zoo forward signature — keeps every
    tracing test's compile in the milliseconds."""

    @nn.compact
    def __call__(self, image, depth=None, train=False):
        x = nn.Conv(4, (3, 3), name="c1")(image)
        x = nn.relu(x)
        return (nn.Conv(1, (1, 1), name="head")(x),)


def _cfg(mname="minet", **serve_kw):
    serve_kw.setdefault("batch_buckets", (1, 2))
    serve_kw.setdefault("resolution_buckets", (16,))
    serve_kw.setdefault("max_wait_ms", 5.0)
    serve_kw.setdefault("watchdog_deadline_s", 30.0)
    serve_kw.setdefault("trace_sample", 1.0)
    return ExperimentConfig(data=DataConfig(image_size=(16, 16)),
                            model=ModelConfig(name=mname),
                            serve=ServeConfig(**serve_kw))


@pytest.fixture(scope="module")
def tiny():
    model = TinySOD()
    variables = model.init(jax.random.key(0),
                           np.zeros((1, 16, 16, 3), np.float32), None,
                           train=False)
    return model, variables


def _img(seed, h=16, w=16):
    return np.random.RandomState(seed).randint(0, 256, (h, w, 3), np.uint8)


def _post(url, img, rid=None, model=None, timeout=60.0):
    buf = io.BytesIO()
    np.save(buf, img)
    headers = {"Content-Type": "application/x-npy"}
    if rid:
        headers["X-Request-ID"] = rid
    if model:
        headers["X-Model"] = model
    req = urllib.request.Request(url + "/predict", data=buf.getvalue(),
                                 headers=headers, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, dict(r.headers), r.read()


def _get_json(url, path):
    with urllib.request.urlopen(url + path, timeout=10) as r:
        return json.loads(r.read().decode())


# --------------------------------------------------------- tracer unit


def test_sampling_deterministic_and_bounds():
    tid = mint_trace_id()
    assert trace_sampled(tid, 1.0) and not trace_sampled(tid, 0.0)
    # The same (id, rate) answers the same in any process.
    for rate in (0.1, 0.5, 0.9):
        assert trace_sampled(tid, rate) == trace_sampled(tid, rate)
    # At 0.5 a decent id population splits roughly in half.
    ids = [mint_trace_id() for _ in range(400)]
    frac = sum(trace_sampled(i, 0.5) for i in ids) / len(ids)
    assert 0.35 < frac < 0.65
    # Sampled at r implies sampled at any r' >= r (hash threshold).
    for i in ids:
        if trace_sampled(i, 0.2):
            assert trace_sampled(i, 0.6)
    with pytest.raises(ValueError, match="sample"):
        Tracer(sample=1.5)


def test_tracer_ring_bounded_and_worst_pinned():
    clk = [0.0]
    tr = Tracer(sample=1.0, capacity=8, worst_n=2, clock=lambda: clk[0])
    slow_ids = []
    for i in range(40):
        tid = mint_trace_id()
        dur = 5.0 if i in (3, 17) else 0.01  # two outliers
        if i in (3, 17):
            slow_ids.append(tid)
        root = tr.begin("request", tid, t0=clk[0], root=True)
        clk[0] += dur
        root.end(key=("m", 16))
    snap = tr.snapshot()
    assert snap["held"] <= 8
    assert snap["completed_total"] == 40
    assert snap["dropped_total"] >= 32
    # The two slow outliers survived 30+ evictions as exemplars.
    worst = snap["worst"]["m,16"]
    assert {t["trace_id"] for t in worst} == set(slow_ids)
    assert all(t["dur_ms"] == pytest.approx(5000.0) for t in worst)


def test_tracer_span_cap_and_nonpositive_n():
    # A reused (client-controlled) sampled id must not grow one ring
    # entry without bound: spans cap at MAX_SPANS_PER_TRACE, the root
    # still lands (the trace completes), and completion counts ONCE.
    from distributed_sod_project_tpu.utils.tracing import (
        MAX_SPANS_PER_TRACE)
    tr = Tracer(sample=1.0, capacity=4)
    tid = "feedc0de" * 2
    for _ in range(MAX_SPANS_PER_TRACE + 50):
        tr.record(tid, "queue", 0.0, 0.001)
    root = tr.begin("request", tid, root=True)
    root.end(key=("m", 16))
    again = tr.begin("request", tid, root=True)
    again.end(key=("m", 16))
    snap = tr.snapshot()
    held = tr.get_trace(tid)
    assert len(held["spans"]) == MAX_SPANS_PER_TRACE + 1  # cap + root
    assert snap["span_drops_total"] == 50 + 1  # overflow + second root
    assert snap["completed_total"] == 1
    # n<=0 means NONE, not done[-0:] == everything.
    assert snap["traces"]
    assert tr.snapshot(n=0)["traces"] == []
    assert tr.snapshot(n=-3)["traces"] == []
    assert tr.to_jsonl(n=0) == ""


def test_tracer_spans_and_jsonl_roundtrip():
    clk = [10.0]
    tr = Tracer(sample=1.0, clock=lambda: clk[0])
    tid = mint_trace_id()
    root = tr.begin("request", tid, t0=10.0, root=True,
                    attrs={"model": "m"})
    tr.record(tid, "queue", 10.0, 10.2, parent_id=root.span_id)
    child = tr.record(tid, "device", 10.2, 10.9,
                      parent_id=root.span_id)
    tr.record(tid, "fetch", 10.8, 10.9, parent_id=child)
    clk[0] = 11.0
    root.end(key=("m", 16), outcome="served")
    lines = tr.to_jsonl().strip().splitlines()
    assert len(lines) == 1
    t = json.loads(lines[0])
    assert t["trace_id"] == tid and t["done"]
    assert t["dur_ms"] == pytest.approx(1000.0)
    by_name = {s["name"]: s for s in t["spans"]}
    assert set(by_name) == {"request", "queue", "device", "fetch"}
    # Rooted: exactly one local root; every other span reachable.
    ids = {s["span"] for s in t["spans"]}
    roots = [s for s in t["spans"] if s["parent"] not in ids]
    assert [s["name"] for s in roots] == ["request"]
    assert by_name["fetch"]["parent"] == by_name["device"]["span"]
    # rel_ms offsets are trace-relative and ordered.
    assert by_name["request"]["rel_ms"] == 0.0
    assert by_name["device"]["rel_ms"] == pytest.approx(200.0)
    # Unsampled begin/record are None and record nothing.
    off = Tracer(sample=0.0)
    assert off.begin("x", mint_trace_id(), root=True) is None
    assert off.record(mint_trace_id(), "x", 0.0, 1.0) is None
    assert not off.enabled


def test_timing_header_roundtrip():
    h = format_timing("abc123", {"queue": 1.2345, "device": 5.0,
                                 "e2e": 6.5})
    tid, stages = parse_timing(h)
    assert tid == "abc123"
    assert stages == {"queue": pytest.approx(1.234, abs=1e-3),
                      "device": 5.0, "e2e": 6.5}
    # Unsampled marker and garbage tolerance.
    tid, stages = parse_timing(format_timing(None, {"e2e": 1.0}))
    assert tid is None and stages == {"e2e": 1.0}
    assert parse_timing(None) == (None, {})
    assert parse_timing("trace=x;bad;q=notanumber;e2e=2") == \
        ("x", {"e2e": 2.0})


# ------------------------------------------- prom text round-trips


def test_parse_prom_histogram_bucket_roundtrip():
    s = ServeStats()
    s.inc("submitted", 3)
    s.inc("served", 3)
    for ms in (1.5, 30.0, 7000.0):
        s.e2e_ms.observe(ms)
    text = s.render_prometheus()
    fams = parse_prom_text(text)
    # Round trip: parse → render is byte-identical (TYPE once, bucket
    # lines incl. le="+Inf" and _sum/_count preserved verbatim).
    assert render_prom_families(fams) == text
    by_name = {n: (t, lines) for n, t, lines in fams}
    typ, lines = by_name["dsod_serve_e2e_latency_ms"]
    assert typ == "histogram"
    assert 'dsod_serve_e2e_latency_ms_bucket{le="+Inf"} 3' in lines
    assert any(l.startswith("dsod_serve_e2e_latency_ms_sum") for l in lines)


def test_parse_prom_escaped_label_values_and_relabel():
    # Escaped quotes and spaces inside label values must survive the
    # relabel injection (the remote-replica scrape path).
    text = ('# TYPE weird gauge\n'
            'weird{msg="a\\"b c",unit="ms"} 1\n'
            'weird 2\n')
    fams = parse_prom_text(text, labels='model="m"')
    assert fams == [("weird", "gauge", [
        'weird{model="m",msg="a\\"b c",unit="ms"} 1',
        'weird{model="m"} 2'])]
    # Merging keeps ONE family entry and raises on a type conflict.
    merged = merge_prom_families([fams, parse_prom_text(
        '# TYPE weird gauge\nweird 3\n', labels='model="n"')])
    assert len(merged) == 1 and len(merged[0][2]) == 3
    with pytest.raises(ValueError, match="declared as both"):
        merge_prom_families([fams, [("weird", "counter", ["weird 9"])]])


# --------------------------------------------------- engine span trees


def _span_names(trace):
    return {s["name"] for s in trace["spans"]}


def _assert_rooted_gap_free(trace, extra_slack_ms=1.0):
    """One local root named request; every span parented inside the
    trace; every child inside the root's [0, dur] window."""
    ids = {s["span"] for s in trace["spans"]}
    roots = [s for s in trace["spans"] if s["parent"] not in ids]
    assert len(roots) == 1 and roots[0]["name"] == "request", trace
    root = roots[0]
    for s in trace["spans"]:
        assert s["rel_ms"] >= -extra_slack_ms
        assert s["rel_ms"] + s["dur_ms"] <= \
            root["rel_ms"] + root["dur_ms"] + extra_slack_ms, (s, root)
    return root


def test_engine_trace_complete_and_consistent(tiny):
    model, variables = tiny
    eng = InferenceEngine(_cfg(), model, variables).start()
    try:
        rid = mint_trace_id()
        fut = eng.submit(_img(0), trace_id=rid)
        pred, meta = fut.result(timeout=30)
        assert meta["trace_id"] == rid
        deadline = time.monotonic() + 5
        t = None
        while time.monotonic() < deadline:
            t = eng.tracer.get_trace(rid)
            if t is not None and t["done"]:
                break
            time.sleep(0.01)
        assert t is not None and t["done"]
        assert _span_names(t) == {"request", "queue", "coalesce",
                                  "device", "fetch", "resize_back"}
        root = _assert_rooted_gap_free(t)
        by = {s["name"]: s for s in t["spans"]}
        # fetch is the host-blocking tail of device.
        assert by["fetch"]["parent"] == by["device"]["span"]
        # Stage durations reconcile with the meta the histograms saw:
        # queue+coalesce tile arrival→dispatch, device matches, root
        # IS e2e.
        assert by["queue"]["dur_ms"] + by["coalesce"]["dur_ms"] == \
            pytest.approx(meta["queue_ms"], abs=0.05)
        assert by["device"]["dur_ms"] == pytest.approx(
            meta["device_ms"], abs=0.05)
        assert root["dur_ms"] == pytest.approx(meta["e2e_ms"], abs=0.05)
        # Exemplar bucket keyed (model, res_bucket).
        assert t["key"] == "minet,16"
    finally:
        eng.stop()


def test_engine_unsampled_records_nothing_and_flags_meta(tiny):
    model, variables = tiny
    eng = InferenceEngine(_cfg(trace_sample=0.0), model, variables).start()
    try:
        _pred, meta = eng.submit(_img(1), trace_id="r1").result(timeout=30)
        assert meta["trace_id"] is None  # not sampled
        assert eng.tracer.snapshot()["traces"] == []
    finally:
        eng.stop()


# ------------------------------------------------- live-HTTP single


def test_server_request_id_timing_and_debug_traces(tiny):
    model, variables = tiny
    eng = InferenceEngine(_cfg(), model, variables).start()
    srv = make_server(eng, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        rid = "my-client-id-42"
        status, headers, _body = _post(url, _img(2), rid=rid)
        assert status == 200
        assert headers["X-Request-ID"] == rid
        tid, stages = parse_timing(headers["X-Timing"])
        assert tid == rid  # sampled at 1.0 → the trace exists
        assert set(stages) == {"queue", "device", "resize", "e2e"}
        # The header's numbers ARE the response headers' numbers.
        assert stages["queue"] == pytest.approx(
            float(headers["X-Queue-MS"]), abs=1e-3)
        assert stages["device"] == pytest.approx(
            float(headers["X-Device-MS"]), abs=1e-3)
        assert stages["e2e"] == pytest.approx(
            float(headers["X-E2E-MS"]), abs=1e-3)
        assert stages["queue"] + stages["device"] + stages["resize"] \
            <= stages["e2e"] + 0.05
        # /debug/traces serves the sampled trace; its root == e2e.
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            snap = _get_json(url, "/debug/traces?n=10")
            hit = [t for t in snap["traces"] if t["trace_id"] == rid]
            if hit and hit[0]["done"]:
                break
            time.sleep(0.02)
        assert hit and hit[0]["dur_ms"] == pytest.approx(
            stages["e2e"], abs=0.05)
        _assert_rooted_gap_free(hit[0])
        # A minted id appears when the client sends none.
        status, headers2, _ = _post(url, _img(3))
        assert status == 200 and headers2["X-Request-ID"]
        assert headers2["X-Request-ID"] != rid
    finally:
        srv.shutdown()
        srv.server_close()
        eng.stop()


def test_metrics_byte_identical_with_tracing_off(tiny):
    """trace_sample=0: the live /metrics payload must be byte-for-byte
    what ServeStats renders directly — the PR-8 surface, no tracing
    families, no registry artifacts."""
    model, variables = tiny
    eng = InferenceEngine(_cfg(trace_sample=0.0), model, variables).start()
    srv = make_server(eng, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        for i in range(3):
            assert _post(url, _img(10 + i))[0] == 200
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            live = r.read().decode()
        assert live == eng.stats.render_prometheus()
        assert "trace" not in live
        # The trainer's half of the same promise: with no sampled chunk
        # (trace_sample=0 gives none) and no profiler session, the span
        # helper the loop calls reads no clock and touches no Tracer.
        from distributed_sod_project_tpu.utils import tracing

        reads = []
        real = tracing.time.monotonic
        tracing.time.monotonic = lambda: reads.append(1) or real()
        try:
            with tracing.span("dsod.train.dispatch", None):
                pass
        finally:
            tracing.time.monotonic = real
        assert reads == []
        # The registry render path is the identity for one provider.
        reg = TelemetryRegistry().register("serve",
                                           eng.stats.prom_families)
        assert reg.render() == eng.stats.render_prometheus()
    finally:
        srv.shutdown()
        srv.server_close()
        eng.stop()


# --------------------------------------------------- live-HTTP fleet


def test_fleet_every_request_one_complete_trace(tiny):
    """The acceptance e2e: N mixed requests through the router, every
    one yields one trace whose router half (request + attempt) and
    engine half (request + stage spans) share the trace id; the engine
    root is parented under the router's attempt span; durations
    reconcile with X-Timing."""
    model, variables = tiny
    ea = InferenceEngine(_cfg("tiny_a"), model, variables)
    eb = InferenceEngine(_cfg("tiny_b"), model, variables)
    fleet = Fleet([EngineBackend("a", ea), EngineBackend("b", eb)],
                  FleetConfig(trace_sample=1.0))
    fleet.start()
    srv = make_fleet_server(fleet, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    sent = []
    try:
        for i in range(8):
            mname = ("a", "b")[i % 2]
            rid = mint_trace_id()
            status, headers, _ = _post(url, _img(20 + i), rid=rid,
                                       model=mname)
            assert status == 200
            assert headers["X-Request-ID"] == rid
            sent.append((rid, mname, headers))
        # Every request: one merged trace with both halves.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            dbg = fleet.debug_traces(n=50)
            merged = {t["trace_id"]: t for t in dbg["merged"]}
            if all(rid in merged
                   and {"router", f"replica:{m}"}
                   <= set(merged[rid]["sources"])
                   for rid, m, _h in sent):
                break
            time.sleep(0.05)
        for rid, mname, headers in sent:
            t = merged[rid]
            by_name = {}
            for s in t["spans"]:
                by_name.setdefault(s["name"], []).append(s)
            # Router half: one request root + >=1 attempt; engine
            # half: its own request span + the stage spans.
            assert len(by_name["request"]) == 2  # router + engine
            assert len(by_name["attempt"]) >= 1
            for stage in ("queue", "coalesce", "device", "fetch",
                          "resize_back"):
                assert stage in by_name, (rid, sorted(by_name))
            ids = {s["span"] for s in t["spans"]}
            attempt = by_name["attempt"][0]
            assert attempt["attrs"]["replica"] == mname
            assert attempt["attrs"]["kind"] == "engine"
            assert attempt["attrs"]["breaker"] == "closed"
            # The engine's request span hangs off the router attempt —
            # the cross-tracer stitch that makes the merged tree rooted.
            engine_roots = [s for s in by_name["request"]
                            if s["parent"] in ids]
            assert len(engine_roots) == 1
            assert engine_roots[0]["parent"] == attempt["span"]
            router_roots = [s for s in by_name["request"]
                            if s["parent"] is None]
            assert len(router_roots) == 1
            assert attempt["parent"] == router_roots[0]["span"]
            # X-Timing reconciles with the engine half.
            _tid, stages = parse_timing(headers["X-Timing"])
            assert engine_roots[0]["dur_ms"] == pytest.approx(
                stages["e2e"], abs=0.05)
        # The router's worst-N exemplars key per model.
        snap = fleet.tracer.snapshot()
        assert set(snap["worst"]) <= {"a", "b"}
        assert set(snap["worst"]), "no exemplars recorded"
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()


class _FakeRemote:
    """Scriptable remote: behaviors consumed one per predict_raw; the
    last repeats.  'ok' | 'refuse' | 'http:<code>' | float (sleep→ok)."""

    kind = "remote"

    def __init__(self, name, behaviors=("ok",)):
        self.name = name
        self.behaviors = list(behaviors)
        self.calls = []
        self._i = 0
        self._lock = threading.Lock()

    def start(self):
        pass

    def stop(self):
        pass

    def queue_depth(self):
        return None

    @property
    def max_queue(self):
        return None

    def healthy(self):
        return True

    def health_reason(self):
        return ""

    def note_transport_failure(self, reason):
        pass

    def prom_families(self, labels):
        return []

    def stats_snapshot(self):
        return {}

    def debug_traces(self, n=50):
        return {}

    def describe(self):
        return {"kind": self.kind}

    def _next(self):
        with self._lock:
            i = min(self._i, len(self.behaviors) - 1)
            self._i += 1
            return self.behaviors[i]

    def predict_raw(self, body, headers, timeout_s=None):
        self.calls.append(dict(headers))
        b = self._next()
        if isinstance(b, float):
            time.sleep(b)
            b = "ok"
        if b == "refuse":
            raise ConnectionRefusedError("scripted refuse")
        if b.startswith("http:"):
            code = int(b.split(":", 1)[1])
            return code, [("Content-Type", "application/json")], \
                json.dumps({"error": "scripted"}).encode()
        buf = io.BytesIO()
        np.save(buf, np.zeros((4, 4), np.float32))
        return 200, [("Content-Type", "application/x-npy")], \
            buf.getvalue()


def _remote_fleet(replicas, **cfg_kw):
    cfg_kw.setdefault("retry_max_attempts", 3)
    cfg_kw.setdefault("retry_backoff_ms", 1.0)
    cfg_kw.setdefault("retry_backoff_max_ms", 5.0)
    cfg_kw.setdefault("trace_sample", 1.0)
    fleet = Fleet(replicas, FleetConfig(**cfg_kw))
    srv = make_fleet_server(fleet, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return fleet, srv, f"http://127.0.0.1:{srv.server_address[1]}"


def test_retries_share_one_trace_with_attempt_spans():
    r0 = _FakeRemote("m", behaviors=["http:500"])
    r1 = _FakeRemote("m", behaviors=["ok"])
    fleet, srv, url = _remote_fleet([r0, r1])
    try:
        rid = mint_trace_id()
        status, headers, _ = _post(url, _img(0, 8, 8), rid=rid)
        assert status == 200
        # Both replicas saw the SAME forwarded X-Request-ID.
        assert r0.calls[0]["X-Request-ID"] == rid
        assert r1.calls[0]["X-Request-ID"] == rid
        # The root span closes AFTER the response flushes (the handler
        # thread books its terminals then): an immediate read races it.
        deadline = time.monotonic() + 5.0
        t = fleet.tracer.get_trace(rid)
        while not (t and t["done"]) and time.monotonic() < deadline:
            time.sleep(0.01)
            t = fleet.tracer.get_trace(rid)
        assert t is not None and t["done"]
        attempts = sorted((s for s in t["spans"]
                           if s["name"] == "attempt"),
                          key=lambda s: s["attrs"]["n"])
        assert len(attempts) == 2
        assert attempts[0]["attrs"]["status"] == 500
        assert attempts[1]["attrs"]["status"] == 200
        assert {a["attrs"]["replica"] for a in attempts} == \
            {"m#0", "m#1"}
        ids = {s["span"] for s in t["spans"]}
        roots = [s for s in t["spans"] if s["parent"] not in ids]
        assert len(roots) == 1 and roots[0]["name"] == "request"
        assert roots[0]["attrs"]["outcome"] == "ok"
        assert all(a["parent"] == roots[0]["span"] for a in attempts)
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()


def test_hedge_shares_trace_and_tags_hedge_attempt():
    r0 = _FakeRemote("m", behaviors=[0.4])   # slow primary
    r1 = _FakeRemote("m", behaviors=["ok"])  # fast hedge target
    fleet, srv, url = _remote_fleet([r0, r1], hedge_ms=40.0)
    try:
        rid = mint_trace_id()
        status, _headers, _ = _post(url, _img(0, 8, 8), rid=rid)
        assert status == 200
        assert fleet.rstats.snapshot()["hedges_total"] == 1
        # The loser's span may land after the response: wait it out.
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            t = fleet.tracer.get_trace(rid)
            if t and sum(s["name"] == "attempt"
                         for s in t["spans"]) >= 2:
                break
            time.sleep(0.02)
        attempts = [s for s in t["spans"] if s["name"] == "attempt"]
        assert len(attempts) == 2
        hedged = [a for a in attempts if a["attrs"].get("hedge")]
        assert len(hedged) == 1  # exactly one marked as the hedge
        assert {a["attrs"]["replica"] for a in attempts} == \
            {"m#0", "m#1"}
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()


def test_transport_failure_attempt_span_and_trace_outcome():
    r0 = _FakeRemote("m", behaviors=["refuse"])
    fleet, srv, url = _remote_fleet([r0], retry_max_attempts=1)
    try:
        rid = mint_trace_id()
        buf = io.BytesIO()
        np.save(buf, _img(0, 8, 8))
        req = urllib.request.Request(
            url + "/predict", data=buf.getvalue(),
            headers={"Content-Type": "application/x-npy",
                     "X-Request-ID": rid}, method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        ei.value.read()
        assert ei.value.code == 502
        # The 502 is flushed from inside the dispatch loop; the root
        # span lands just after the response: wait it out.
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            t = fleet.tracer.get_trace(rid)
            if t is not None and t["done"]:
                break
            time.sleep(0.02)
        assert t is not None and t["done"]
        att = [s for s in t["spans"] if s["name"] == "attempt"]
        assert att and att[0]["attrs"]["result"] == "transport"
        roots = [s for s in t["spans"] if s["parent"] is None]
        assert roots[0]["attrs"]["outcome"] == "transport_error"
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()


def _real_remote_replicas(tiny, n, **serve_kw):
    """n REAL single-engine HTTP servers (the ServeHandler path, where
    DSOD_FAULTS serve-tier kinds apply) wrapped as RemoteBackends."""
    from distributed_sod_project_tpu.serve.fleet import RemoteBackend

    model, variables = tiny
    started = []
    remotes = []
    for _i in range(n):
        eng = InferenceEngine(_cfg(**serve_kw), model, variables).start()
        srv = make_server(eng, "127.0.0.1", 0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        started.append((eng, srv))
        remotes.append(RemoteBackend(
            "m", f"http://127.0.0.1:{srv.server_address[1]}",
            health_poll_s=0.2))
    def teardown():
        for eng, srv in started:
            srv.shutdown()
            srv.server_close()
            eng.stop()
    return remotes, started, teardown


def test_faulted_retry_and_hedge_share_trace_end_to_end(tiny):
    """The acceptance e2e under DSOD_FAULTS: a request whose first
    attempt eats an injected serve-tier 500 is retried, a request
    whose first replica drips is hedged — and each yields ONE trace
    (attempts share the id; the served attempt's engine half carries
    the full stage timeline reconciling with X-Timing)."""
    from distributed_sod_project_tpu.resilience import inject

    remotes, started, teardown = _real_remote_replicas(tiny, 2)
    os.environ[inject.ENV_VAR] = "serve_500@1,serve_drip@3:1.0"
    fleet = Fleet(remotes, FleetConfig(
        trace_sample=1.0, retry_max_attempts=3, retry_backoff_ms=1.0,
        retry_backoff_max_ms=5.0, hedge_ms=150.0, health_poll_s=0.2))
    fleet.start()
    srv = make_fleet_server(fleet, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        # Request 1: the first remote POST is the injected 500 → the
        # router retries (other replica or breaker fallback) → 200.
        rid_retry = mint_trace_id()
        status, headers, _ = _post(url, _img(0), rid=rid_retry,
                                   timeout=30)
        assert status == 200
        assert fleet.rstats.snapshot()["retries_total"] >= 1
        # The root span lands just after the response is flushed (the
        # hedge test below already waits this race out): poll briefly.
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            t = fleet.tracer.get_trace(rid_retry)
            if t is not None and t["done"]:
                break
            time.sleep(0.02)
        assert t is not None and t["done"]
        attempts = [s for s in t["spans"] if s["name"] == "attempt"]
        assert len(attempts) >= 2  # the faulted try + the winner
        roots = [s for s in t["spans"] if s["parent"] is None]
        assert roots[0]["attrs"]["outcome"] == "ok"
        assert all(a["parent"] == roots[0]["span"] for a in attempts)
        # Request 2 (serve ordinal 3 counting the retry): the primary
        # drips its body for 1 s → the 150 ms hedge fires and the
        # fast secondary wins; both attempts share the trace.
        rid_hedge = mint_trace_id()
        status, headers, _ = _post(url, _img(1), rid=rid_hedge,
                                   timeout=30)
        assert status == 200
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            t2 = fleet.tracer.get_trace(rid_hedge)
            n_att = sum(s["name"] == "attempt"
                        for s in (t2["spans"] if t2 else []))
            if t2 and t2["done"] and n_att >= 2:
                break
            time.sleep(0.05)
        assert fleet.rstats.snapshot()["hedges_total"] >= 1
        att2 = [s for s in t2["spans"] if s["name"] == "attempt"]
        assert len(att2) >= 2
        assert any(a["attrs"].get("hedge") for a in att2)
        # X-Timing from the WINNING replica reconciles through the
        # router relay; that replica's own engine trace (same process
        # here) holds the stage timeline under the same id.  The
        # dripping loser may ALSO have served the forward — X-Replica
        # names whose response the client actually got.
        tid, stages = parse_timing(headers["X-Timing"])
        assert tid == rid_hedge
        win_i = int(headers["X-Replica"].split("#")[1])
        eng_t = started[win_i][0].tracer.get_trace(rid_hedge)
        assert eng_t is not None, "the winner recorded no engine half"
        names = {s["name"] for s in eng_t["spans"]}
        assert {"request", "queue", "device", "resize_back"} <= names
        eng_root = [s for s in eng_t["spans"]
                    if s["name"] == "request"][0]
        assert eng_root["dur_ms"] == pytest.approx(stages["e2e"],
                                                   abs=0.05)
    finally:
        os.environ.pop(inject.ENV_VAR, None)
        inject.reset_plans()
        srv.shutdown()
        srv.server_close()
        fleet.stop()
        teardown()


# -------------------------------------------------- loadgen --slowest


def test_loadgen_slowest_reports_trace_and_stages(tiny):
    model, variables = tiny
    eng = InferenceEngine(_cfg(), model, variables).start()
    srv = make_server(eng, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        out = run_loadgen(url, mode="closed", concurrency=2, requests=6,
                          sizes=((16, 16),), timeout_s=30, slowest=3)
        assert out["ok"] == 6
        rows = out["slowest"]
        assert len(rows) == 3
        # Sorted slowest-first, each with an id and the server split.
        assert rows[0]["ms"] >= rows[-1]["ms"]
        for row in rows:
            assert row["request_id"]
            assert row["trace"] == row["request_id"]  # sampled at 1.0
            assert {"queue", "device", "resize", "e2e"} <= \
                set(row["stages"])
            assert row["stages"]["e2e"] <= row["ms"] + 1.0
            assert row["model"] == "minet"
    finally:
        srv.shutdown()
        srv.server_close()
        eng.stop()


# --------------------------------------- trainer sidecar + chunk spans


def test_trainer_sidecar_live_fit_endpoints_and_chunk_traces(tmp_path):
    """One tiny fit with the sidecar up: /metrics serves the trainer
    families mid-run, /healthz reads the watchdog's own heartbeat,
    /debug/traces shows chunk traces with the documented span schema,
    and /debug/profile arms jax.profiler on demand."""
    from distributed_sod_project_tpu.train.loop import fit

    cfg = get_config("minet_vgg16_ref").replace(
        data=DataConfig(dataset="synthetic", image_size=(32, 32),
                        synthetic_size=32, num_workers=0),
        model=ModelConfig(name="vit_sod", backbone="tiny", sync_bn=False,
                          compute_dtype="float32"),
        optim=OptimConfig(lr=0.01), mesh=MeshConfig(data=-1),
        global_batch_size=8, num_epochs=2, log_every_steps=2,
        checkpoint_every_steps=4, tensorboard=False,
        checkpoint_dir=str(tmp_path / "ck"),
        trace_sample=1.0, steps_per_dispatch=2,
        watchdog_deadline_s=120.0)
    pf = str(tmp_path / "telem.port")
    got = {}

    def on_metrics(step, host):
        # Scrape mid-run at the LAST log boundary (step 8 of 8), when
        # earlier chunks' traces have completed.
        if step < 8 or got:
            return
        with open(pf) as f:
            url = f"http://127.0.0.1:{int(f.read())}"
        for ep in ("/metrics", "/healthz", "/debug/traces?n=10",
                   "/debug/profile?seconds=0.2", "/nope"):
            try:
                with urllib.request.urlopen(url + ep, timeout=30) as r:
                    got[ep] = (r.status, r.read().decode())
            except urllib.error.HTTPError as e:
                got[ep] = (e.code, e.read().decode())

    out = fit(cfg, max_steps=8, hooks={"on_metrics": on_metrics},
              telemetry_port=0, telemetry_port_file=pf)
    assert out["final_step"] == 8
    assert got, "the on_metrics scrape never ran"
    code, metrics = got["/metrics"]
    assert code == 200
    for fam in ("dsod_train_step ", "dsod_train_step_time_ms",
                "dsod_train_chunks_total",
                "dsod_train_data_starved_ms_total",
                "dsod_train_device_bytes_in_use",
                'dsod_train_metric_writer_info{backend="'):
        assert fam in metrics, fam
    code, health = got["/healthz"]
    assert code == 200 and json.loads(health)["status"] == "ok"
    code, traces = got["/debug/traces?n=10"]
    snap = json.loads(traces)
    done = [t for t in snap["traces"] if t["done"]]
    assert done, snap
    t = done[-1]
    names = {s["name"] for s in t["spans"]}
    assert "chunk" in names and "dsod.train.dispatch" in names
    root = [s for s in t["spans"] if s["name"] == "chunk"][0]
    assert root["attrs"]["step_last"] - root["attrs"]["step_first"] == 1
    assert t["key"] == "train"
    # The data plane's spans are REAL intervals (PipelineStats.timed
    # regions of the pipeline threads, moved into the chunk that was
    # open when they ran) — none fabricated at the root's start.
    spans = [s for tr in done for s in tr["spans"]]
    assert {"dsod.data.h2d", "dsod.data.build", "dsod.data.starved",
            "dsod.train.flush"} <= {s["name"] for s in spans}
    assert not [s for s in spans if s.get("attrs", {}).get("synthetic")]
    h2d = [s for s in spans if s["name"] == "dsod.data.h2d"]
    assert any(s["rel_ms"] > 0 for s in h2d)  # placed where they ran
    assert all(s["parent"] == [r for r in tr["spans"]
                               if r["name"] == "chunk"][0]["span"]
               for tr in done for s in tr["spans"] if s["name"] != "chunk")
    code, prof = got["/debug/profile?seconds=0.2"]
    assert code == 200
    assert os.path.isdir(json.loads(prof)["logdir"])
    assert got["/nope"][0] == 404


def test_metric_writer_degrades_loudly_without_clu(tmp_path):
    import logging

    import distributed_sod_project_tpu.utils.observability as obs
    from distributed_sod_project_tpu.utils.logging import get_logger

    records = []

    class _Catch(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = _Catch(level=logging.WARNING)
    get_logger().addHandler(handler)
    real_clu = sys.modules.get("clu")
    saved_flag = obs.MetricWriter._warned_missing_clu
    obs.MetricWriter._warned_missing_clu = False
    sys.modules["clu"] = None  # forces ImportError on `from clu import`
    try:
        w1 = obs.MetricWriter(str(tmp_path / "tb"))
        w2 = obs.MetricWriter(str(tmp_path / "tb2"))
        assert w1.backend == "noop" and w2.backend == "noop"
        # Logged exactly once per process, not per construction.
        hits = [m for m in records if "TensorBoard metric writing" in m]
        assert len(hits) == 1
        # The no-op surface still accepts writes.
        w1.scalars(1, {"x": 1.0})
        w1.flush()
        w1.close()
    finally:
        get_logger().removeHandler(handler)
        if real_clu is not None:
            sys.modules["clu"] = real_clu
        else:
            sys.modules.pop("clu", None)
        obs.MetricWriter._warned_missing_clu = saved_flag


def test_metric_writer_reports_clu_backend_when_available(tmp_path):
    pytest.importorskip("clu")
    from distributed_sod_project_tpu.utils.observability import \
        MetricWriter

    w = MetricWriter(str(tmp_path / "tb"))
    assert w.backend == "clu"
    w.close()
    assert MetricWriter(None).backend == "noop"


# ------------------------------------------------------- metrics lint


def test_metrics_lint_seed_compare_and_drift(tmp_path):
    import metrics_lint

    baseline = str(tmp_path / "inv.json")
    assert metrics_lint.main(["--baseline", baseline,
                              "--update-baseline"]) == 0
    # Clean compare.
    assert metrics_lint.main(["--baseline", baseline]) == 0
    inv = json.load(open(baseline))
    assert "dsod_serve_e2e_latency_ms" in inv["fleet"]
    assert "dsod_train_step" in inv["trainer"]
    # A vanished family exits 2.
    inv["fleet"]["dsod_made_up_total"] = "counter"
    json.dump(inv, open(baseline, "w"))
    assert metrics_lint.main(["--baseline", baseline]) == 2
    # An undocumented family exits 2.
    del inv["fleet"]["dsod_made_up_total"]
    del inv["fleet"]["dsod_fleet_routed_total"]
    json.dump(inv, open(baseline, "w"))
    assert metrics_lint.main(["--baseline", baseline]) == 2


def test_checked_in_inventory_matches_current_surface():
    """The REAL baseline must match the rendered surface — the same
    check t1.sh runs, gating here so a family rename cannot land
    without --update-baseline."""
    import metrics_lint

    assert metrics_lint.main([]) == 0
