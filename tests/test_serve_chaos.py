"""Serve-tier fault injection tests (resilience/inject.py serve_*
kinds through the real HTTP surface — docs/SERVING.md "Failure
semantics", docs/RESILIENCE.md).

The training chaos suite (tests/test_resilience.py) proved the fit
loop survives injected faults; this module proves the SERVING tier
does: a deterministic ``DSOD_FAULTS`` plan makes a live replica answer
a 5xx burst, reset a connection mid-body, drip a response, or wedge
its dispatch — and the clients (loadgen, the fleet router) observe
exactly the failure class each fault models, with the router's
retry/failover machinery absorbing what it should absorb.  The
process-kill legs live in tools/fleet_chaos.py / tools/fleet_smoke.py
(real subprocesses; see the RESILIENCE.md note on fresh processes).
"""

import io
import json
import threading
import time
import urllib.error
import urllib.request

import flax.linen as nn
import jax
import numpy as np
import pytest

from distributed_sod_project_tpu.configs import (DataConfig,
                                                 ExperimentConfig,
                                                 FleetConfig, ModelConfig,
                                                 ServeConfig)
from distributed_sod_project_tpu.resilience import inject
from distributed_sod_project_tpu.serve.engine import InferenceEngine
from distributed_sod_project_tpu.serve.fleet import Fleet, RemoteBackend
from distributed_sod_project_tpu.serve.loadgen import _one
from distributed_sod_project_tpu.serve.router import make_fleet_server
from distributed_sod_project_tpu.serve.server import make_server


@pytest.fixture(autouse=True)
def _fresh_plans():
    inject.reset_plans()
    yield
    inject.reset_plans()


class TinySOD(nn.Module):
    @nn.compact
    def __call__(self, image, depth=None, train=False):
        return (nn.Conv(1, (1, 1), name="head")(image),)


def _mk_engine(**serve_kw):
    serve_kw.setdefault("batch_buckets", (1, 2))
    serve_kw.setdefault("resolution_buckets", (16,))
    serve_kw.setdefault("max_wait_ms", 5.0)
    serve_kw.setdefault("watchdog_deadline_s", 30.0)
    cfg = ExperimentConfig(data=DataConfig(image_size=(16, 16)),
                           model=ModelConfig(name="tiny"),
                           serve=ServeConfig(**serve_kw))
    model = TinySOD()
    probe = np.zeros((1, 16, 16, 3), np.float32)
    variables = model.init(jax.random.key(0), probe, None, train=False)
    return InferenceEngine(cfg, model, variables)


def _serve(engine):
    srv = make_server(engine, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _body():
    buf = io.BytesIO()
    np.save(buf, np.zeros((8, 8, 3), np.uint8))
    return buf.getvalue()


def _post(url, timeout=30.0):
    req = urllib.request.Request(
        url + "/predict", data=_body(),
        headers={"Content-Type": "application/x-npy"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, dict(r.headers), r.read()


# ------------------------------------------------------- plan parsing


def test_serve_fault_specs_parse():
    p = inject.FaultPlan(
        "serve_500@3x2, serve_reset@1, serve_drip@2:0.25, "
        "serve_stall@4:1.5")
    assert p.serve_500 == {3, 4}
    assert p.serve_reset == {1}
    assert p.serve_drip == {2: 0.25}
    assert p.serve_stall == {4: 1.5}


def test_serve_fault_bad_specs_raise():
    for bad in ("serve_500@", "serve_bogus@1", "serve_drip@x:1"):
        with pytest.raises(ValueError):
            inject.FaultPlan(bad)


def test_next_serve_request_sequences_and_latches():
    p = inject.FaultPlan("serve_500@2, serve_drip@3:0.5")
    assert p.next_serve_request() is None  # request 1: clean
    assert p.next_serve_request() == ("500", 0.0)  # request 2
    assert p.next_serve_request() == ("drip", 0.5)  # request 3
    assert p.next_serve_request() is None  # latched: once per ordinal
    assert p.fired == ["serve_500@2", "serve_drip@3:0.5"]


# ----------------------------------------------- live replica faults


def test_injected_500_burst_answers_before_the_engine(monkeypatch):
    monkeypatch.setenv(inject.ENV_VAR, "serve_500@1")
    eng = _mk_engine()
    eng.start()
    srv, url = _serve(eng)
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(url)
        assert exc.value.code == 500
        assert json.loads(exc.value.read().decode())["kind"] \
            == "injected_fault"
        # The engine never saw the faulted request...
        assert eng.stats.counter("submitted") == 0
        # ...and the next request is clean (the fault latched).
        status, _, _ = _post(url)
        assert status == 200
        assert eng.stats.counter("submitted") == 1
    finally:
        srv.shutdown()
        srv.server_close()
        eng.stop()


def test_injected_midbody_reset_reads_as_transport_failure(monkeypatch):
    monkeypatch.setenv(inject.ENV_VAR, "serve_reset@1")
    eng = _mk_engine()
    eng.start()
    srv, url = _serve(eng)
    try:
        out, _ms, _info = _one(url, _body(), None, 10.0)
        assert out == "transport"  # NOT an HTTP-status "error"
        out, _ms, _info = _one(url, _body(), None, 30.0)
        assert out == "ok"
    finally:
        srv.shutdown()
        srv.server_close()
        eng.stop()


def test_injected_drip_slows_but_completes(monkeypatch):
    monkeypatch.setenv(inject.ENV_VAR, "serve_drip@1:0.4")
    eng = _mk_engine()
    eng.start()
    srv, url = _serve(eng)
    try:
        t0 = time.monotonic()
        status, _, body = _post(url)
        dt = time.monotonic() - t0
        assert status == 200
        assert dt >= 0.3  # the drip held the reader
        np.load(io.BytesIO(body), allow_pickle=False)  # body intact
        assert inject.plan_from_env().fired == ["serve_drip@1:0.4"]
    finally:
        srv.shutdown()
        srv.server_close()
        eng.stop()


def test_injected_dispatch_stall_flips_watchdog_health(monkeypatch):
    monkeypatch.setenv(inject.ENV_VAR, "serve_stall@1:1.0")
    eng = _mk_engine(watchdog_deadline_s=0.2)
    eng.start()
    srv, url = _serve(eng)
    try:
        # The stalled dispatch holds ready work out of the device past
        # the watchdog deadline: health flips while the request is
        # still in flight — the probe-flagged signal the router's
        # health gate routes around.
        t = threading.Thread(target=lambda: _post(url, timeout=30.0),
                             daemon=True)
        t.start()
        deadline = time.monotonic() + 5.0
        while eng.stats.healthy and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not eng.stats.healthy, "watchdog never flagged the stall"
        t.join(timeout=10.0)
        assert "serve_stall@1:1" in inject.plan_from_env().fired[0]
    finally:
        srv.shutdown()
        srv.server_close()
        eng.stop()


# ------------------------------------------- router absorbs the chaos


def _settled_stats(fleet, timeout=5.0):
    """A terminal is booked after the response bytes flush, so a stats
    read right after the client's 200 can see the submission and not
    yet its ``served`` (tests/test_cache.py::_consistent_stats): wait
    the gap out; the last read goes back as it is, so a real hole still
    fails the caller."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = fleet.stats()
        if st["fleet"]["consistent"]:
            return st
        time.sleep(0.02)
    return fleet.stats()


def test_router_retry_absorbs_injected_5xx_burst(monkeypatch):
    """A replica answering an injected 5xx burst behind a live listener
    is exactly what the retry path exists for: the client sees 200, the
    burst shows up only in the retry counters and the replica book."""
    monkeypatch.setenv(inject.ENV_VAR, "serve_500@1")
    eng = _mk_engine()
    eng.start()
    rsrv, rurl = _serve(eng)
    fleet = Fleet([RemoteBackend("m", rurl, health_poll_s=30.0)],
                  FleetConfig(retry_max_attempts=2, retry_backoff_ms=1.0))
    srv = make_fleet_server(fleet, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        status, headers, _ = _post(url)
        assert status == 200
        assert headers["X-Model"] == "m"
        s = _settled_stats(fleet)
        assert s["router"]["retries_total"] == 1
        assert s["fleet"]["submitted"] == 1
        assert s["fleet"]["served"] == 1
        assert s["fleet"]["consistent"] is True
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()
        rsrv.shutdown()
        rsrv.server_close()
        eng.stop()


def test_router_retry_absorbs_injected_midbody_reset(monkeypatch):
    monkeypatch.setenv(inject.ENV_VAR, "serve_reset@1")
    eng = _mk_engine()
    eng.start()
    rsrv, rurl = _serve(eng)
    fleet = Fleet([RemoteBackend("m", rurl, health_poll_s=0.1)],
                  FleetConfig(retry_max_attempts=2, retry_backoff_ms=1.0,
                              breaker_failures=3))
    fleet.start()  # arms the background prober (re-admits after flip)
    srv = make_fleet_server(fleet, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        status, _, body = _post(url)
        assert status == 200
        np.load(io.BytesIO(body), allow_pickle=False)
        s = _settled_stats(fleet)
        assert s["router"]["retries_total"] == 1
        assert s["router"]["transport_errors_total"] == 0  # absorbed
        assert s["fleet"]["consistent"] is True
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()
        rsrv.shutdown()
        rsrv.server_close()
        eng.stop()
