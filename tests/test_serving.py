"""Serving subsystem tests (serve/ — docs/SERVING.md).

Invariants proven here:

- batcher coalescing never exceeds the largest static batch bucket and
  the max-wait deadline releases a batch even when the queue stalls;
- admission sheds at the queue bound, expires SLO-missed requests
  BEFORE a forward is wasted, and the degraded mode engages/disengages
  hysteretically;
- hot weight reload is atomic w.r.t. concurrent predicts (every
  response matches exactly one published weight set, never a mix);
- end-to-end over live HTTP: concurrent mixed-size requests return
  BITWISE-identical saliency maps to a direct ``make_forward`` call at
  the same buckets, while /metrics accounting stays consistent
  (served + shed + expired + errors == submitted) and an overload run
  sheds instead of growing the queue unboundedly;
- the run_inference satellites: bounded in-flight dispatches with no
  consumer, and immediate stop on host-worker errors.
"""

import io
import threading
import time
import urllib.request
from concurrent.futures import wait as futures_wait

import flax.linen as nn
import jax
import numpy as np
import pytest

from distributed_sod_project_tpu.configs import (DataConfig,
                                                 ExperimentConfig,
                                                 ServeConfig,
                                                 config_from_dict)
from distributed_sod_project_tpu.eval.inference import (_resize_pred,
                                                        make_forward,
                                                        pad_to_batch)
from distributed_sod_project_tpu.serve.admission import (AdmissionController,
                                                         DeadlineExpired,
                                                         QueueFull)
from distributed_sod_project_tpu.serve.batcher import DynamicBatcher, Request
from distributed_sod_project_tpu.serve.engine import (InferenceEngine,
                                                      preprocess_image)
from distributed_sod_project_tpu.serve.server import make_server
from distributed_sod_project_tpu.utils.observability import (LatencyHistogram,
                                                             ServeStats)


class TinySOD(nn.Module):
    """Minimal model with the zoo forward signature — keeps every
    serving test's compile in the milliseconds."""

    @nn.compact
    def __call__(self, image, depth=None, train=False):
        x = nn.Conv(4, (3, 3), name="c1")(image)
        x = nn.relu(x)
        return (nn.Conv(1, (1, 1), name="head")(x),)


def _cfg(**serve_kw):
    serve_kw.setdefault("batch_buckets", (1, 2, 4))
    serve_kw.setdefault("resolution_buckets", (16, 24))
    serve_kw.setdefault("max_wait_ms", 5.0)
    serve_kw.setdefault("watchdog_deadline_s", 30.0)
    return ExperimentConfig(data=DataConfig(image_size=(16, 16)),
                            serve=ServeConfig(**serve_kw))


@pytest.fixture(scope="module")
def tiny():
    model = TinySOD()
    variables = model.init(jax.random.key(0),
                           np.zeros((1, 16, 16, 3), np.float32), None,
                           train=False)
    return model, variables


def _engine(tiny, **serve_kw):
    model, variables = tiny
    return InferenceEngine(_cfg(**serve_kw), model, variables)


def _img(seed, h, w):
    return np.random.RandomState(seed).randint(0, 256, (h, w, 3), np.uint8)


# ---------------------------------------------------------------- stats


def test_latency_histogram_percentiles_and_prom():
    h = LatencyHistogram()
    for ms in (1.5, 3.0, 8.0, 40.0, 40.0, 400.0):
        h.observe(ms)
    assert h.count == 6
    assert 0.0 < h.percentile(0.5) <= 50.0
    assert h.percentile(0.99) <= 500.0
    lines = h.prom_lines("x_ms")
    assert lines[0] == "# TYPE x_ms histogram"
    assert f'x_ms_bucket{{le="+Inf"}} 6' in lines
    assert "x_ms_count 6" in lines


def test_serve_stats_accounting_and_render():
    s = ServeStats()
    s.inc("submitted", 5)
    s.inc("served", 3)
    s.inc("shed")
    s.inc("expired")
    s.observe_batch(3, 4)
    s.set_degraded(True)
    s.set_degraded(True)  # idempotent: one transition counted
    s.set_degraded(False)
    snap = s.snapshot()
    assert snap["served"] + snap["shed"] + snap["expired"] \
        + snap["errors"] == snap["submitted"]
    assert snap["degraded_entered"] == 1 and snap["degraded_exited"] == 1
    assert snap["batch_occupancy"] == 0.75
    prom = s.render_prometheus()
    assert "dsod_serve_submitted_total 5" in prom
    assert "dsod_serve_shed_total 1" in prom
    assert "dsod_serve_e2e_latency_ms_count" in prom


def test_serve_config_roundtrips_through_sidecar_dict():
    import dataclasses

    cfg = _cfg(max_queue=7, slo_ms=125.0)
    back = config_from_dict(dataclasses.asdict(cfg))
    assert back.serve == cfg.serve


# ------------------------------------------------------------- batcher


def test_batcher_coalescing_never_exceeds_largest_bucket():
    clk = [0.0]
    b = DynamicBatcher((1, 2, 4), max_wait_s=0.1, clock=lambda: clk[0])
    for i in range(10):
        b.put(Request(tensor=np.zeros((4, 4, 3), np.float32),
                      orig_hw=(4, 4), res_bucket=16, arrival=clk[0]))
    clk[0] = 1.0  # every head is past max-wait
    sizes = []
    while b.pending():
        (res, arm), group = b.get_batch(idle_timeout_s=0.0)
        assert (res, arm) == (16, "f32")
        sizes.append(len(group))
    assert all(n <= 4 for n in sizes)
    assert sizes == [4, 4, 2]
    assert b.pick_batch_bucket(1) == 1
    assert b.pick_batch_bucket(2) == 2
    assert b.pick_batch_bucket(3) == 4
    assert b.pick_batch_bucket(4) == 4


def test_batcher_max_wait_honored_under_stalled_queue():
    """One request, nothing else ever arrives: the batch must release
    at ~max_wait, not hang waiting for co-riders."""
    b = DynamicBatcher((1, 8), max_wait_s=0.05)
    t0 = time.monotonic()
    b.put(Request(tensor=np.zeros((4, 4, 3), np.float32), orig_hw=(4, 4),
                  res_bucket=16, arrival=t0))
    got = b.get_batch(idle_timeout_s=5.0)
    waited = time.monotonic() - t0
    assert got is not None and len(got[1]) == 1
    assert 0.03 <= waited < 1.0  # released by the deadline, not idle_timeout


def test_batcher_full_bucket_releases_before_max_wait():
    clk = [0.0]
    b = DynamicBatcher((1, 2, 4), max_wait_s=100.0, clock=lambda: clk[0])
    for _ in range(4):
        b.put(Request(tensor=np.zeros((4, 4, 3), np.float32),
                      orig_hw=(4, 4), res_bucket=24, arrival=clk[0]))
    (res, _arm), group = b.get_batch(idle_timeout_s=0.0)
    assert (res, len(group)) == (24, 4)  # full bucket: no wait at all


def test_batcher_groups_are_per_resolution_bucket():
    clk = [0.0]
    b = DynamicBatcher((1, 2, 4), max_wait_s=0.1, clock=lambda: clk[0])
    for i, res in enumerate([16, 24, 16, 24, 16]):
        b.put(Request(tensor=np.zeros((4, 4, 3), np.float32),
                      orig_hw=(4, 4), res_bucket=res, arrival=float(i)))
    clk[0] = 100.0
    groups = []
    while b.pending():
        groups.append(b.get_batch(idle_timeout_s=0.0))
    assert [(key, len(g)) for key, g in groups] \
        == [((16, "f32"), 3), ((24, "f32"), 2)]


def test_batcher_groups_are_per_precision_arm():
    """Same resolution, different precision arms: NEVER coalesced —
    a batch runs through exactly one compiled program."""
    clk = [0.0]
    b = DynamicBatcher((1, 2, 4), max_wait_s=0.1, clock=lambda: clk[0])
    for i, arm in enumerate(["f32", "bf16", "f32", "bf16", "bf16"]):
        b.put(Request(tensor=np.zeros((4, 4, 3), np.float32),
                      orig_hw=(4, 4), res_bucket=16, precision=arm,
                      arrival=float(i)))
    clk[0] = 100.0
    groups = []
    while b.pending():
        groups.append(b.get_batch(idle_timeout_s=0.0))
    assert [(key, len(g)) for key, g in groups] \
        == [((16, "f32"), 2), ((16, "bf16"), 3)]
    for key, g in groups:
        assert all(r.precision == key[1] for r in g)


# ----------------------------------------------------------- admission


def test_admission_queue_bound_sheds():
    a = AdmissionController(4)
    a.try_admit(3)
    with pytest.raises(QueueFull):
        a.try_admit(4)
    with pytest.raises(QueueFull):
        a.try_admit(9)


def test_admission_expiry_accounts_for_estimated_device_time():
    assert not AdmissionController.expired(None, 10.0, now=0.0)
    assert not AdmissionController.expired(1.0, 0.5, now=0.0)
    assert AdmissionController.expired(1.0, 1.5, now=0.0)  # can't make it
    assert AdmissionController.expired(1.0, 0.0, now=2.0)  # already past


def test_degraded_mode_engages_and_disengages_hysteretically():
    clk = [0.0]
    a = AdmissionController(10, high=0.8, low=0.2, engage_s=2.0,
                            disengage_s=5.0, clock=lambda: clk[0])
    # High depth must PERSIST for engage_s — a blip doesn't flip it.
    assert a.observe(9) is False
    clk[0] = 1.9
    assert a.observe(9) is False
    clk[0] = 2.1
    assert a.observe(9) is True
    # Dead-band depths hold the degraded state.
    clk[0] = 3.0
    assert a.observe(5) is True
    # Low depth must persist for disengage_s.
    clk[0] = 4.0
    assert a.observe(1) is True
    clk[0] = 8.9
    assert a.observe(1) is True
    clk[0] = 9.1
    assert a.observe(1) is False
    # A dip that doesn't last disengage_s resets the timer.
    clk[0] = 10.0
    assert a.observe(9) is False
    clk[0] = 12.1
    assert a.observe(9) is True
    clk[0] = 13.0
    assert a.observe(1) is True
    clk[0] = 14.0
    assert a.observe(5) is True  # dead band resets the below-timer
    clk[0] = 18.5
    assert a.observe(1) is True  # only 4.5s below since the reset
    clk[0] = 23.6
    assert a.observe(1) is False


# -------------------------------------------------------------- engine


def test_engine_warms_every_bucket_program_and_reuses_them(tiny):
    eng = _engine(tiny)
    eng.start()
    try:
        # res buckets x batch buckets x precision arms (default f32+bf16)
        assert len(eng.programs) == 2 * 3 * 2
        warmed = set(eng.programs)
        for seed, (h, w) in enumerate([(16, 16), (20, 28), (40, 40)]):
            eng.predict(_img(seed, h, w), timeout=30)
        assert set(eng.programs) == warmed  # serving compiled nothing new
    finally:
        eng.stop()


def test_request_compiles_counts_one_per_unwarmed_program(tiny):
    """``dsod_serve_request_compiles_total``: 0 while every dispatch
    finds its AOT-warmed program; a program missing from the cache is
    compiled by the jit fallback on its FIRST request only, and counted
    once — not once per dispatch."""
    eng = _engine(tiny, batch_buckets=(1,), resolution_buckets=(16,))
    eng.start()
    try:
        eng.predict(_img(0, 16, 16), timeout=30)
        assert eng.stats.snapshot()["request_compiles"] == 0
        eng.programs.clear()  # as a bucket added after start would be
        for seed in range(3):
            eng.predict(_img(seed, 16, 16), timeout=30)
        assert eng.stats.snapshot()["request_compiles"] == 1
    finally:
        eng.stop()


def test_engine_expired_requests_shed_before_forward(tiny):
    eng = _engine(tiny, max_wait_ms=60.0, batch_buckets=(4,))
    forwards = []
    orig = eng._forward

    def counting_forward(*a, **kw):
        forwards.append(1)
        return orig(*a, **kw)

    eng._forward = counting_forward
    eng.start()
    try:
        fut = eng.submit(_img(0, 16, 16), slo_ms=1.0)
        with pytest.raises(DeadlineExpired):
            fut.result(timeout=10)
        assert forwards == []  # no forward wasted on a dead request
        assert eng.stats.counter("expired") == 1
        assert eng.stats.counter("served") == 0
    finally:
        eng.stop()


def test_engine_degraded_uses_smallest_res_bucket_and_reports(tiny):
    eng = _engine(tiny)
    eng.start()
    try:
        # Force the FINAL ladder rung; hysteresis is tested above and
        # the precision-before-resolution ordering in test_precision.py.
        eng.admission._level = eng.admission.max_level
        pred, meta = eng.predict(_img(0, 40, 40), timeout=30)
        assert meta["degraded"] is True
        assert meta["res_bucket"] == min(eng.res_buckets)
        assert meta["precision"] == eng.precision_arms[-1]  # fully stepped
        assert pred.shape == (40, 40)
        eng.admission._level = 0
        _, meta2 = eng.predict(_img(0, 40, 40), timeout=30)
        assert meta2["degraded"] is False
        assert meta2["res_bucket"] == max(eng.res_buckets)
        assert meta2["precision"] == "f32"
    finally:
        eng.stop()


def test_hot_weight_reload_is_atomic_wrt_concurrent_predicts(tiny, tmp_path):
    """While checkpoints land mid-flight, every served prediction must
    equal the forward of exactly ONE published weight set — a torn
    half-old/half-new mix would produce a third value."""
    from distributed_sod_project_tpu.ckpt import CheckpointManager
    from distributed_sod_project_tpu.configs import OptimConfig
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    model, _ = tiny
    tx, _sched = build_optimizer(OptimConfig(), 1)
    probe = {"image": np.zeros((1, 16, 16, 3), np.float32)}
    state0 = create_train_state(jax.random.key(1), model, tx, probe)

    def bump(state, delta, step):
        return state.replace(
            step=state.step + 0,
            params=jax.tree_util.tree_map(lambda x: x + delta,
                                          state.params))

    states = [state0, bump(state0, 0.25, 1), bump(state0, -0.5, 2)]
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(0, states[0], force=True)
    mgr.wait()

    cfg = _cfg(reload_poll_s=0.02, resolution_buckets=(16,),
               batch_buckets=(1, 2))
    eng = InferenceEngine(cfg, model, states[0], ckpt_dir=str(tmp_path))
    eng.start()
    try:
        img = _img(3, 16, 16)
        fwd = make_forward(model)
        x = preprocess_image(img, 16, cfg.data.normalize_mean,
                             cfg.data.normalize_std)
        candidates = []
        for st in states:
            for bb in (1, 2):
                batch = pad_to_batch({"image": x[None]}, bb)
                candidates.append(np.asarray(
                    fwd(st.eval_variables(), batch))[0])

        results = []
        stop = threading.Event()

        def pounder():
            while not stop.is_set():
                try:
                    pred, _meta = eng.predict(img, timeout=30)
                    results.append(pred)
                except Exception:  # pragma: no cover — surfaces below
                    results.append(None)

        threads = [threading.Thread(target=pounder, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        for step in (1, 2):
            time.sleep(0.15)
            mgr.save(step, states[step], force=True)
            mgr.wait()
        deadline = time.monotonic() + 20
        while (eng.stats.counter("reloads") < 2
               and time.monotonic() < deadline):
            time.sleep(0.05)
        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join(timeout=30)

        assert eng.stats.counter("reloads") >= 2
        assert len(results) > 0 and all(r is not None for r in results)
        for pred in results:
            assert any(np.array_equal(pred, c) for c in candidates), \
                "a served prediction matched NO published weight set " \
                "(torn reload)"
        # The new weights actually took over: the last prediction after
        # both reloads must come from the final checkpoint.
        final = {2: [c for i, c in enumerate(candidates) if i >= 4]}
        assert any(np.array_equal(results[-1], c) for c in final[2])
    finally:
        eng.stop()
        mgr.close()


# ------------------------------------------------------- live-HTTP e2e


def _start_http(eng):
    srv = make_server(eng, "127.0.0.1", 0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _post_predict(url, img, slo_ms=None, timeout=60.0):
    buf = io.BytesIO()
    np.save(buf, img)
    headers = {"Content-Type": "application/x-npy"}
    if slo_ms:
        headers["X-SLO-MS"] = str(slo_ms)
    req = urllib.request.Request(url + "/predict", data=buf.getvalue(),
                                 headers=headers, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        pred = np.load(io.BytesIO(r.read()), allow_pickle=False)
        return pred, dict(r.headers)


def _get_json(url, path):
    import json

    with urllib.request.urlopen(url + path, timeout=10) as r:
        return json.loads(r.read().decode())


def test_e2e_concurrent_mixed_sizes_bitwise_and_metrics_consistent(tiny):
    """The acceptance run: N concurrent mixed-size requests through a
    LIVE server return bitwise-identical maps to a direct make_forward
    at the same (resolution, batch) buckets, and /metrics adds up."""
    model, variables = tiny
    eng = _engine(tiny, max_wait_ms=20.0)
    eng.start()
    srv, url = _start_http(eng)
    try:
        assert _get_json(url, "/healthz")["status"] == "ok"
        sizes = [(16, 16), (20, 28), (33, 17), (24, 24), (16, 24),
                 (40, 40)]
        n = 12
        out = [None] * n
        errs = []

        def one(i):
            try:
                out[i] = _post_predict(url, _img(i, *sizes[i % len(sizes)]))
            except Exception as e:  # pragma: no cover — surfaces below
                errs.append((i, e))

        threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errs, f"request failures: {errs}"

        fwd = make_forward(model)
        cfg = eng.cfg
        for i in range(n):
            pred, headers = out[i]
            img = _img(i, *sizes[i % len(sizes)])
            res = int(headers["X-Res-Bucket"])
            bb = int(headers["X-Batch-Bucket"])
            x = preprocess_image(img, res, cfg.data.normalize_mean,
                                 cfg.data.normalize_std)
            ref = np.asarray(fwd(variables,
                                 pad_to_batch({"image": x[None]}, bb)))[0]
            ref = _resize_pred(ref, img.shape[:2])
            assert pred.dtype == np.float32 and pred.shape == img.shape[:2]
            assert np.array_equal(pred, ref), \
                f"request {i}: served map is not bitwise-identical to " \
                f"the direct forward at buckets (res={res}, batch={bb})"

        stats = _get_json(url, "/stats")
        assert stats["submitted"] == n
        assert stats["served"] + stats["shed"] + stats["expired"] \
            + stats["errors"] == stats["submitted"]
        assert stats["errors"] == 0
        prom = urllib.request.urlopen(url + "/metrics", timeout=10
                                      ).read().decode()
        assert f"dsod_serve_submitted_total {n}" in prom
        assert "dsod_serve_e2e_latency_ms_bucket" in prom
    finally:
        srv.shutdown()
        srv.server_close()
        eng.stop()


def test_overload_sheds_instead_of_growing_queue_unboundedly(tiny):
    """Flood a deliberately slow engine: the bounded queue must shed
    (429-class), pending depth must never exceed max_queue, and the
    accounting identity must close once the dust settles."""
    eng = _engine(tiny, max_queue=4, max_wait_ms=1.0, batch_buckets=(1,),
                  resolution_buckets=(16,))
    orig = eng._forward

    def slow_forward(*a, **kw):
        time.sleep(0.05)
        return orig(*a, **kw)

    eng._forward = slow_forward
    eng.start()
    try:
        img = _img(0, 16, 16)
        futures, shed = [], [0]
        max_pending = [0]
        lock = threading.Lock()

        def flood(n):
            # CONCURRENT submitters: the bound must hold even when N
            # threads race the depth check (it lives under the
            # batcher's lock, not in a check-then-put from outside).
            for _ in range(n):
                try:
                    f = eng.submit(img)
                    with lock:
                        futures.append(f)
                except QueueFull:
                    with lock:
                        shed[0] += 1
                with lock:
                    max_pending[0] = max(max_pending[0],
                                         eng.batcher.pending())

        threads = [threading.Thread(target=flood, args=(10,))
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert shed[0] > 0, "overload never shed — queue grew unboundedly"
        assert max_pending[0] <= eng.cfg.serve.max_queue
        done, not_done = futures_wait(futures, timeout=60)
        assert not not_done
        s = eng.stats
        assert s.counter("submitted") == 40
        assert (s.counter("served") + s.counter("shed")
                + s.counter("expired") + s.counter("errors")) == 40
        assert s.counter("shed") == shed[0]
    finally:
        eng.stop()


def test_malformed_input_is_terminal_counted(tiny):
    """The engine owns every terminal counter: a request rejected at
    preprocess (400-class) must still close the accounting identity."""
    eng = _engine(tiny)
    eng.start()
    try:
        with pytest.raises(ValueError):
            eng.submit(np.zeros((16, 16), np.uint8))  # grayscale: no C=3
        s = eng.stats
        assert s.counter("submitted") == 1
        assert (s.counter("served") + s.counter("shed")
                + s.counter("expired") + s.counter("errors")) == 1
    finally:
        eng.stop()


def test_handler_timeout_does_not_double_count(tiny):
    """A /predict whose future outlives request_timeout_s gets a 504,
    but the request is still live — only the engine's eventual
    'served' may terminate it, or one request lands in two counters."""
    import urllib.error

    eng = _engine(tiny, request_timeout_s=0.05)
    orig = eng._forward

    def slow_forward(*a, **kw):
        time.sleep(0.4)
        return orig(*a, **kw)

    eng._forward = slow_forward
    eng.start()
    srv, url = _start_http(eng)
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post_predict(url, _img(0, 16, 16), timeout=30)
        assert exc.value.code == 504
        deadline = time.monotonic() + 10
        while (eng.stats.counter("served") < 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
        s = eng.stats
        assert s.counter("submitted") == 1
        assert s.counter("served") == 1  # the batch still completed
        assert s.counter("errors") == 0  # ...and nothing double-counted
    finally:
        srv.shutdown()
        srv.server_close()
        eng.stop()


# ----------------------------------------- run_inference satellite fixes


class _SweepDS:
    def __init__(self, n=40, hw=(8, 8)):
        self.n = n
        self.hw = hw

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        h, w = self.hw
        rng = np.random.RandomState(i)
        return {"image": rng.rand(h, w, 3).astype(np.float32),
                "mask": (rng.rand(h, w, 1) > 0.5).astype(np.float32)}


def test_run_inference_bounds_inflight_when_nothing_syncs(monkeypatch):
    """compute_metrics=False + no save_dir + device_metrics=False used
    to dispatch every batch with nothing ever syncing; now the sweep
    blocks periodically so in-flight work stays bounded."""
    from distributed_sod_project_tpu.eval import inference

    syncs = []
    real = jax.block_until_ready
    monkeypatch.setattr(inference.jax, "block_until_ready",
                        lambda x: (syncs.append(1), real(x))[1])

    import jax.numpy as jnp

    out = inference.run_inference(
        lambda batch: jnp.mean(jnp.asarray(batch["image"]), axis=-1),
        _SweepDS(40), batch_size=4, compute_metrics=False)
    assert out == {}
    # 10 batches → periodic syncs at every 4th dispatch + the final one.
    assert len(syncs) >= 3


class _SlowBuildDS(_SweepDS):
    """Per-sample decode delay: makes the batch build the loop's slow
    host section, the window worker errors used to slip through."""

    def __getitem__(self, i):
        time.sleep(0.025)
        return super().__getitem__(i)


def test_run_inference_stops_dispatching_on_worker_error(monkeypatch):
    """A worker failure landing during the NEXT batch's (slow) host
    build used to surface only after that batch was dispatched and
    enqueued for a dead worker; the pre-dispatch re-check must stop
    the loop with batch 1's forward the only one issued."""
    from distributed_sod_project_tpu.eval import inference

    def exploding_mask(dataset, index, sample=None):
        time.sleep(0.05)  # die mid-way through batch 2's build window
        raise RuntimeError("gt decode exploded")

    monkeypatch.setattr(inference, "_original_mask", exploding_mask)

    import jax.numpy as jnp

    calls = []

    def forward(batch):
        calls.append(1)
        return jnp.mean(jnp.asarray(batch["image"]), axis=-1)

    with pytest.raises(RuntimeError, match="gt decode exploded"):
        inference.run_inference(forward, _SlowBuildDS(48), batch_size=4,
                                compute_metrics=True,
                                compute_structure=False)
    # Batch 1 dispatches at ~100ms, the worker dies ~50ms later while
    # batch 2 is still building (100ms window); the pre-forward check
    # sees the error and never dispatches batch 2.
    assert len(calls) == 1
