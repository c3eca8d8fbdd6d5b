"""The serving engine: compiled-program cache + dynamic batching +
hot weight reload (docs/SERVING.md).

TF-Replicator's thesis (PAPERS.md) applied to serving: the user-facing
abstraction is thin — ``submit(image) -> Future`` — and everything
underneath maps onto the fixed-shape compiled programs the eval path
already owns.  Three device-facing invariants:

- **No request-time compilation.**  Every (resolution bucket, batch
  bucket, precision arm) program is AOT-compiled at startup via
  ``jax.jit(...).lower().compile()`` from the SAME ``make_forward`` the
  offline eval uses (quantized arms route through
  ``serve/precision.py``'s dequantizing forward), so a served
  prediction is bitwise what a direct call at the same bucket shapes
  and arm would produce.
- **Atomic weight swaps.**  The checkpoint watcher restores the newest
  VALID step (resilience integrity layer) off-thread, re-derives every
  precision arm's cast-on-load weight view, then swaps the whole
  arm→variables dict under a lock read once per dispatch — a
  concurrent /predict sees entirely-old or entirely-new weights, never
  a mix (across arms too).
- **Bounded device run-ahead.**  At most ``max_inflight`` dispatched-
  but-unfetched batches; the host completion pool (the
  ``run_inference`` overlap pattern, generalised to out-of-order
  completion) fetches, resizes back to each request's original
  resolution, and resolves futures.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from ..eval.inference import _resize_pred, flip_tta, pad_to_batch
from ..utils.logging import get_logger
from ..utils.observability import ServeStats, TelemetryRegistry
from ..utils.tracing import Tracer
from .admission import (AdmissionController, DeadlineExpired, EngineStopped,
                        QueueFull)
from .batcher import DynamicBatcher, Request
from .precision import (cast_variables, make_precision_forward, step_down,
                        validate_arms)


def preprocess_image(image: np.ndarray, res: int, mean, std, *,
                     depth: bool = False):
    """Request image → the compiled forward's input row: resize to the
    (res, res) bucket (PIL bilinear, the eval-path convention), scale to
    [0, 1], normalize.  uint8 in; float32 [0,1] arrays are accepted and
    quantized through uint8 so the server and any offline comparator
    see bit-identical inputs for the same source image.

    ``depth=True`` (RGB-D models, e.g. HDFNet): the request is an
    ``(H, W, 4)`` RGBD stack — the first three channels preprocess as
    above and the fourth splits off as the model's ``depth`` input
    (resized to the same bucket, scaled to [0, 1], NOT mean/std
    normalized — the depth-plane convention the data pipeline uses).
    Returns ``(tensor, depth_plane)`` with depth_plane float32
    ``(res, res, 1)``; the RGB path keeps its historical single-array
    return."""
    arr = np.asarray(image)
    want_c = 4 if depth else 3
    if arr.ndim != 3 or arr.shape[2] != want_c:
        kind = "(H, W, 4) RGBD" if depth else "(H, W, 3)"
        raise ValueError(
            f"expected an {kind} image, got shape {arr.shape}")
    if arr.dtype != np.uint8:
        arr = (np.clip(arr, 0.0, 1.0) * 255.0).round().astype(np.uint8)
    from PIL import Image

    dplane = None
    if depth:
        d = Image.fromarray(arr[:, :, 3])
        if d.size != (res, res):
            d = d.resize((res, res), Image.BILINEAR)
        dplane = (np.asarray(d, np.float32) / 255.0)[:, :, None]
        arr = arr[:, :, :3]
    im = Image.fromarray(arr)
    if im.size != (res, res):
        im = im.resize((res, res), Image.BILINEAR)
    x = np.asarray(im, np.float32) / 255.0
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    tensor = ((x - mean) / std).astype(np.float32)
    if depth:
        return tensor, dplane
    return tensor


class InferenceEngine:
    """Dynamic-batching inference engine over one model.

    ``state`` is a restored ``TrainState`` (its ``eval_variables()`` —
    EMA weights when tracked — are served) or a bare variables dict.
    ``ckpt_dir`` plus ``cfg.serve.reload_poll_s > 0`` arms the hot
    weight reload watcher (requires a TrainState for the restore
    template).  Request lifecycle and knobs: docs/SERVING.md.
    """

    def __init__(self, cfg, model, state, *, ckpt_dir: Optional[str] = None,
                 stats: Optional[ServeStats] = None, clock=time.monotonic):
        self.cfg = cfg
        # RGB-D zoo members (HDFNet under a use_depth config) demand a
        # depth plane on every request: /predict payloads are
        # (H, W, 4) RGBD, split at preprocess; warmup/probe batches
        # carry a zero depth plane.  The HTTP front ends read this to
        # 400 channel-mismatched payloads BEFORE submit.
        self.wants_depth = bool(cfg.data.use_depth)
        self.model = model
        self.ckpt_dir = ckpt_dir
        self.stats = stats or ServeStats()
        self._clock = clock
        self._log = get_logger()
        # Request tracing (utils/tracing.py; docs/OBSERVABILITY.md):
        # the per-request queue/coalesce/device/fetch/resize_back span
        # timeline, sampled deterministically by trace id.  At
        # trace_sample=0 every touch below is a None check — the
        # /metrics surface and request path are byte-for-byte the
        # pre-tracing behavior.
        self.tracer = Tracer(sample=cfg.serve.trace_sample,
                             capacity=cfg.serve.trace_capacity,
                             worst_n=cfg.serve.trace_worst_n, clock=clock)
        # /metrics renders through the shared registry (one code path
        # with the trainer sidecar); a single provider renders
        # byte-identical to ServeStats.render_prometheus().
        self.telemetry = TelemetryRegistry().register(
            "serve", self.stats.prom_families)

        sc = cfg.serve

        # Black-box flight recorder (utils/flightrecorder.py;
        # docs/OBSERVABILITY.md "Flight recorder & incidents"): samples
        # this registry into an on-disk segment ring and bundles
        # incidents on alert firings / watchdog trips / dispatch
        # crashes / SIGTERM.  None when off — no thread, no files,
        # /metrics byte-identical (the recorder registers no families
        # of its own).  Constructed BEFORE the alert engines so their
        # on_transition hooks can reference it; the bundle sections are
        # lambdas evaluated at bundle time, so attribute order is free.
        import dataclasses as _dc

        from ..utils.flightrecorder import recorder_from_knobs

        self.recorder = recorder_from_knobs(
            sc, families_fn=self.telemetry.prom_families,
            sections={
                "stats": lambda: self.stats_snapshot(),
                "traces": lambda: self.tracer.snapshot(n=16),
                "alerts": lambda: (self.alerts.snapshot()
                                   if self.alerts is not None else {}),
                "slo": lambda: (self.slo.snapshot()
                                if self.slo is not None else {}),
                "capacity": lambda: (self.capacity.snapshot()
                                     if self.capacity is not None
                                     else {}),
                "config": lambda: _dc.asdict(self.cfg),
            },
            meta={"source": "engine", "model": cfg.model.name},
            clock=clock)
        self._last_rec_level = 0  # degraded-ladder move detection
        self.res_buckets = tuple(sorted(
            sc.resolution_buckets or (max(cfg.data.image_size),)))
        self.batch_buckets = tuple(sorted(sc.batch_buckets))
        self._mean = np.asarray(cfg.data.normalize_mean, np.float32)
        self._std = np.asarray(cfg.data.normalize_std, np.float32)

        # Precision arms (serve/precision.py): every enabled arm gets a
        # cast-on-load weight view and its own AOT programs; requests
        # pick an arm (serve.precision default, X-Precision override),
        # possibly stepped down by the degraded ladder.
        self.precision_arms = validate_arms(sc.precision_arms, sc.precision)
        self.default_precision = sc.precision

        # Online quality/drift monitors + alert engine (serve/quality.py,
        # utils/alerts.py; docs/OBSERVABILITY.md "Model health").  Both
        # None unless serve.quality_monitor — every touch on the request
        # path guards on that, and with them off the telemetry registry
        # holds the one "serve" provider, so /metrics stays
        # byte-identical to the monitor-less rendering.
        self.quality = None
        self.alerts = None
        self._next_alert_eval = 0.0
        if not sc.quality_monitor:
            # Loudness: a monitor-scoped knob set while the monitor is
            # off would be silently ignored — the operator believes
            # online validation is running when nothing is.
            if sc.quality_shadow_sample > 0:
                raise ValueError(
                    "serve.quality_shadow_sample > 0 requires "
                    "serve.quality_monitor=true (shadow scoring is part "
                    "of the quality monitor)")
            if sc.alert_rules:
                raise ValueError(
                    "serve.alert_rules set but serve.quality_monitor is "
                    "false — the serving alert engine only runs with the "
                    "monitor on")
        if sc.quality_monitor:
            from ..utils.alerts import AlertEngine, parse_rules
            from .quality import (QualityMonitor, default_quality_rules,
                                  load_reference)

            if sc.quality_shadow_sample > 0 and \
                    "f32" not in self.precision_arms:
                raise ValueError(
                    "serve.quality_shadow_sample > 0 needs the f32 "
                    "reference arm among serve.precision_arms — shadow "
                    "scoring re-scores sampled requests on f32")
            self.quality = QualityMonitor(
                cfg.model.name,
                shadow_sample=sc.quality_shadow_sample,
                reference=load_reference(sc.quality_reference,
                                         cfg.model.name),
                psi_min_count=sc.quality_psi_min_count)
            self.alerts = AlertEngine(
                default_quality_rules(sc) + parse_rules(sc.alert_rules),
                clock=clock, on_transition=self._alert_transition)
            self.telemetry.register("quality", self.quality.prom_families)
            self.telemetry.register("alerts", self.alerts.prom_families)

        # Capacity ledger + SLO tracker (utils/capacity.py, utils/slo.py;
        # docs/OBSERVABILITY.md "Capacity & SLO").  Both None unless
        # their knobs are on — every touch guards, and with them off
        # the registry keeps its historical providers, so /metrics is
        # byte-identical to the ledger-less rendering.
        self.capacity = None
        self.slo = None
        self._next_slo_eval = 0.0
        if sc.capacity_ledger:
            from ..utils.capacity import CapacityLedger

            def _stage_shares():
                # Device-vs-queue-vs-host attribution from the stage
                # splits the histograms already hold (PR-9 seams):
                # deep queues + high device share → scale out; deep
                # queues + low device share → host-bound, scaling out
                # is futile (ROADMAP item 2's signal).
                e2e = self.stats.e2e_ms.sum_ms
                if e2e <= 0:
                    return {"device": 0.0, "queue": 0.0, "host": 0.0}
                dev = self.stats.device_ms.sum_ms / e2e
                q = self.stats.queue_ms.sum_ms / e2e
                return {"device": min(dev, 1.0), "queue": min(q, 1.0),
                        "host": max(1.0 - dev - q, 0.0)}

            self.capacity = CapacityLedger(share_fn=_stage_shares)
            self.telemetry.register("capacity",
                                    self.capacity.prom_families)
        if sc.slo_objectives:
            from ..utils.slo import build_tracker

            self.slo = build_tracker(
                sc.slo_objectives, burn_threshold=sc.slo_burn_threshold,
                alert_for_s=sc.slo_alert_for_s,
                alert_clear_s=sc.slo_alert_clear_s, clock=clock,
                on_transition=self._alert_transition)
            self.telemetry.register("slo", self.slo.prom_families)
            self.telemetry.register("slo_alerts",
                                    self.slo.alerts.prom_families)

        self._template = state if hasattr(state, "eval_variables") else None
        self._conv_impl = getattr(cfg.model, "conv_impl", "xla")
        variables = (state.eval_variables()
                     if self._template is not None else state)
        self._var_lock = threading.Lock()
        self._arm_vars = self._derive_arm_vars(variables)
        # Seed the reload watermark from the state's own step so the
        # watcher doesn't "reload" the checkpoint we just restored.
        self._loaded_step: Optional[int] = (
            int(jax.device_get(state.step))
            if self._template is not None else None)

        self._fwds = {arm: make_precision_forward(
            model, arm, conv_impl=self._conv_impl)
            for arm in self.precision_arms}
        # Compiled-program cache, AOT-warmed in start().  The key spells
        # out everything that selects a distinct executable: model,
        # static shapes, the decoder resample implementation, the
        # conv-block implementation, and the precision arm (each a
        # different compiled program).
        self.programs: Dict[Tuple[str, int, int, str, str, str],
                            object] = {}
        # Keys the jit fallback has compiled on the request path.
        self._request_compiled: set = set()

        self.batcher = DynamicBatcher(
            self.batch_buckets, sc.max_wait_ms / 1000.0,
            max_queue=sc.max_queue, clock=clock)
        # Ladder depth: one rung per precision downshift available from
        # the enabled arms, plus the final resolution rung (the
        # historical binary mode when only one arm is enabled).
        self._n_precision_rungs = len(self.precision_arms) - 1
        self.admission = AdmissionController(
            sc.max_queue, high=sc.degraded_high, low=sc.degraded_low,
            engage_s=sc.degraded_engage_s,
            disengage_s=sc.degraded_disengage_s,
            max_level=self._n_precision_rungs + 1, clock=clock)

        self._est_lock = threading.Lock()
        # (res bucket, arm) → EWMA device s: the arms are different
        # programs with different device costs, so the SLO-expiry
        # estimate must not blend them.
        self._est_s: Dict[Tuple[int, str], float] = {}

        self._stop = threading.Event()
        self._fault_plan = None  # armed from DSOD_FAULTS in start()
        self._running = False
        self._inflight_sem = threading.Semaphore(sc.max_inflight)
        self._inflight_lock = threading.Lock()
        self._inflight_n = 0
        self._dispatch_thread: Optional[threading.Thread] = None
        self._reload_thread: Optional[threading.Thread] = None
        self._watchdog = None
        self._fetch_pool = None
        self._post_pool = None
        # Shadow-scoring side lane: one worker, at most 2 queued+running
        # (try-acquire — a busy lane DROPS, counted, never queues live
        # traffic behind reference forwards).
        self._shadow_pool = None
        self._shadow_sem = threading.BoundedSemaphore(2)

    def _alert_transition(self, rule, old: str, new: str, state) -> None:
        """Alert/SLO state changes → flight-recorder events; a fresh
        firing also snapshots an incident bundle (debounced inside)."""
        if self.recorder is not None:
            self.recorder.alert_transition(rule, old, new, state)

    @property
    def loaded_step(self) -> Optional[int]:
        """The checkpoint step currently serving (``None`` for engines
        started from raw variables with no checkpoint identity).  The
        router cache (serve/cache.py) keys every entry on this, which
        is the whole invalidation story: hot reload, rollout
        promotion, and denylist rollback all move it, making old
        entries unreachable.  Reads are a single atomic attribute load
        — the reload path swaps it under ``_var_lock`` with the arm
        views, but a reader needs one consistent int, not the pair."""
        return self._loaded_step

    # -- precision arms ------------------------------------------------

    def _derive_arm_vars(self, variables) -> Dict[str, object]:
        """Every enabled arm's weight view of ``variables`` (the f32
        source of truth), device-resident.  Called at construction and
        on every hot reload — the views are RE-DERIVED from the freshly
        restored f32 state, then swapped in as one dict under the swap
        lock so no arm ever serves a different step than its siblings.

        At ``model.conv_impl=fused`` the quantized arms take the
        fused-kernel view (``precision.fused_conv_cast_variables``):
        conv kernels stay int8/fp8 leaves dequantized in-VMEM by the
        Pallas kernels, with the per-channel scales riding a parallel
        ``quant_scales`` collection."""
        from .precision import (QUANT_ARMS, fused_conv_cast_variables,
                                fused_conv_sites)

        out = {}
        sites = None  # site discovery is arm-independent: trace once
        for arm in self.precision_arms:
            if self._conv_impl == "fused" and arm in QUANT_ARMS:
                res = self.res_buckets[0]
                probe = {"image": np.zeros((1, res, res, 3), np.float32)}
                if self.wants_depth:
                    probe["depth"] = np.zeros((1, res, res, 1), np.float32)
                if sites is None:
                    sites = fused_conv_sites(self.model, variables, probe)
                view = fused_conv_cast_variables(self.model, variables,
                                                 arm, probe, sites=sites)
            else:
                view = cast_variables(variables, arm)
            out[arm] = jax.device_put(view)
        return out

    def _effective_arm(self, requested: str, level: int) -> str:
        """The arm a request actually serves at: the requested arm
        pushed down the enabled-arm ladder by the degraded level
        (resolution only degrades once every precision rung is spent —
        see :meth:`choose_res_bucket`)."""
        return step_down(requested, self.precision_arms,
                         min(level, self._n_precision_rungs))

    # -- lifecycle -----------------------------------------------------

    def start(self, own_dispatch: bool = True) -> "InferenceEngine":
        """Warm the programs and start serving.  ``own_dispatch=False``
        skips the engine's own dispatch thread — the fleet's interleaved
        dispatcher (serve/fleet.py) drives :meth:`_dispatch_once`
        instead, so N co-resident engines share one device through one
        loop that drains their batchers fairly."""
        if self._running:
            return self
        from concurrent.futures import ThreadPoolExecutor

        from ..resilience.inject import plan_from_env

        sc = self.cfg.serve
        self.warm()
        self._stop.clear()
        if self.recorder is not None:
            self.recorder.start()
        # Deterministic serve-tier chaos (resilience/inject.py): the
        # plan is cached once here so the dispatch hot path pays a
        # None check, not an environ read, per group.
        self._fault_plan = plan_from_env()
        self._fetch_pool = ThreadPoolExecutor(
            max_workers=max(sc.max_inflight, 1),
            thread_name_prefix="serve-fetch")
        self._post_pool = ThreadPoolExecutor(
            max_workers=max(sc.post_workers, 1),
            thread_name_prefix="serve-post")
        if self.quality is not None and sc.quality_shadow_sample > 0:
            self._shadow_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="serve-shadow")
        if sc.watchdog_deadline_s > 0:
            from ..resilience.watchdog import StepWatchdog

            def _on_stall(msg):
                # Health first (the router's gate must flip even if the
                # bundle write below is slow), then the incident — a
                # wedged dispatch is exactly the post-mortem case the
                # recorder exists for.
                self.stats.set_health(False, msg)
                if self.recorder is not None:
                    self.recorder.trigger("watchdog", msg)

            self._watchdog = StepWatchdog(
                deadline_s=sc.watchdog_deadline_s, on_stall=_on_stall)
            self._watchdog.start()
        if self.ckpt_dir and sc.reload_poll_s > 0:
            if self._template is None:
                raise ValueError(
                    "hot weight reload needs a TrainState restore "
                    "template — construct the engine from a TrainState "
                    "(from_checkpoint does)")
            self._reload_thread = threading.Thread(
                target=self._reload_loop, name="serve-reload", daemon=True)
            self._reload_thread.start()
        self._running = True
        if own_dispatch:
            self._dispatch_thread = threading.Thread(
                target=self._dispatch_loop, name="serve-dispatch",
                daemon=True)
            self._dispatch_thread.start()
        return self

    def warm(self) -> int:
        """AOT-compile every (resolution, batch, precision-arm) bucket
        program so no request ever pays a compile; returns the program
        count."""
        name = self.cfg.model.name
        impl = self.cfg.model.resample_impl
        with self._var_lock:
            arm_vars = self._arm_vars
        for arm in self.precision_arms:
            for res in self.res_buckets:
                for bb in self.batch_buckets:
                    key = (name, res, bb, impl, self._conv_impl, arm)
                    if key in self.programs:
                        continue
                    batch = {"image": np.zeros((bb, res, res, 3),
                                               np.float32)}
                    if self.wants_depth:
                        batch["depth"] = np.zeros((bb, res, res, 1),
                                                  np.float32)
                    t0 = time.perf_counter()
                    self.programs[key] = self._fwds[arm].lower(
                        arm_vars[arm], batch).compile()
                    self._log.info(
                        "serve: warmed program %s in %.1fs", key,
                        time.perf_counter() - t0)
                    if self.capacity is not None:
                        # The live half of tools/roofline.py: ask the
                        # executable itself what it costs, once, here
                        # at warmup (cost_analysis on the cached AOT
                        # program — no extra compile).
                        self.capacity.record(
                            self._capacity_key(res, bb, arm),
                            self.programs[key])
        return len(self.programs)

    def _capacity_key(self, res: int, bb: int, arm: str) -> str:
        """One compiled program's ledger key (the cache key, rendered
        label-safe)."""
        return (f"{self.cfg.model.name}/r{res}b{bb}/"
                f"{self.cfg.model.resample_impl}/{self._conv_impl}/{arm}")

    def stop(self) -> None:
        if not self._running:
            return
        self._running = False
        self._stop.set()
        for r in self.batcher.close():
            self.stats.inc("errors")
            self._trace_end(r, "stopped")
            self._fail(r, EngineStopped("engine stopped"))
        if self._dispatch_thread is not None:
            self._dispatch_thread.join(timeout=10.0)
            self._dispatch_thread = None
        if self._reload_thread is not None:
            self._reload_thread.join(timeout=10.0)
            self._reload_thread = None
        if self._fetch_pool is not None:
            self._fetch_pool.shutdown(wait=True)
            self._fetch_pool = None
        if self._post_pool is not None:
            self._post_pool.shutdown(wait=True)
            self._post_pool = None
        if self._shadow_pool is not None:
            self._shadow_pool.shutdown(wait=True)
            self._shadow_pool = None
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        if self.recorder is not None:
            self.recorder.stop()

    @classmethod
    def from_checkpoint(cls, ckpt_dir: str, config_name: Optional[str] = None,
                        overrides=(), step: Optional[int] = None,
                        **kw) -> "InferenceEngine":
        """Checkpoint directory → ready-to-start engine (config sidecar
        aware, via the shared ``restore_for_eval``)."""
        from ..eval.inference import restore_for_eval

        cfg, model, state = restore_for_eval(
            ckpt_dir, config_name=config_name, overrides=overrides,
            step=step)
        return cls(cfg, model, state, ckpt_dir=ckpt_dir, **kw)

    @classmethod
    def from_random_init(cls, cfg, **kw) -> "InferenceEngine":
        """Randomly-initialised engine for a config — the
        smoke/loadgen posture where the serving machinery, not a
        particular checkpoint, is under test (tools/serve.py
        --init-random, the fleet's replicas)."""
        from ..models import build_model
        from ..train import build_optimizer, create_train_state

        model = build_model(cfg.model)
        tx, _ = build_optimizer(cfg.optim, 1)
        h, w = cfg.data.image_size
        probe = {"image": np.zeros((1, h, w, 3), np.float32)}
        if cfg.data.use_depth:
            probe["depth"] = np.zeros((1, h, w, 1), np.float32)
        state = create_train_state(jax.random.key(cfg.seed), model, tx,
                                   probe, ema=cfg.optim.ema_decay > 0)
        return cls(cfg, model, state, **kw)

    # -- request plane -------------------------------------------------

    def choose_res_bucket(self, h: int, w: int, degraded: bool) -> int:
        if degraded:
            return self.res_buckets[0]
        side = max(h, w)
        for r in self.res_buckets:
            if side <= r:
                return r
        return self.res_buckets[-1]

    def submit(self, image: np.ndarray,
               slo_ms: Optional[float] = None,
               precision: Optional[str] = None,
               trace_id: Optional[str] = None,
               trace_parent: Optional[str] = None,
               stream: Optional[str] = None):
        """Enqueue one prediction; returns a ``concurrent.futures.Future``
        resolving to ``(pred, meta)`` — pred float32 (H, W) at the
        request's original resolution.  ``precision`` selects the arm
        (default ``serve.precision``; must be an enabled arm — the
        degraded ladder may still step it further down).  ``trace_id``
        joins the request to an end-to-end trace (the HTTP front ends
        pass the X-Request-ID; sampling decides whether spans are
        actually recorded); ``trace_parent`` is the caller's span id —
        the fleet router parents the engine's request span under its
        dispatch-attempt span.  Raises :class:`QueueFull` /
        :class:`EngineStopped` at the door (nothing enqueued)."""
        # Every submit() call is a submitted request — door rejects
        # included — so the accounting identity composes fleet-wide:
        # a router's forwarded count equals this engine's submitted
        # count exactly, whatever fate each request meets.
        self.stats.inc("submitted")
        if not self._running:
            self.stats.inc("errors")
            raise EngineStopped("engine not running")
        if not self.stats.healthy:
            self.stats.inc("errors")
            raise EngineStopped(
                f"engine unhealthy: {self.stats.health_reason}")
        try:
            self.admission.try_admit(self.batcher.pending())
        except QueueFull:
            self.stats.inc("shed")
            raise
        level = self.admission.level
        try:
            requested = (self.default_precision if precision is None
                         else str(precision))
            if requested not in self.precision_arms:
                raise ValueError(
                    f"unknown precision {requested!r}; enabled arms: "
                    f"{list(self.precision_arms)}")
            arm = self._effective_arm(requested, level)
            arr = np.asarray(image)
            # Resolution degrades only once every precision rung is
            # spent — precision steps down BEFORE resolution.
            res = self.choose_res_bucket(arr.shape[0], arr.shape[1],
                                         level > self._n_precision_rungs)
            # Per-stream affinity (serve/streams.py): a stream's next
            # frame coalesces into the SAME (res_bucket, precision)
            # compiled program its previous frame ran on, so warm
            # state stays on one program.  Only when the arm still
            # matches (the degraded ladder wins over affinity) and the
            # bucket is still configured.
            aff = self.batcher.affinity_bucket(stream)
            if aff is not None and aff[1] == arm \
                    and aff[0] in self.res_buckets:
                res = aff[0]
            dplane = None
            if self.wants_depth:
                tensor, dplane = preprocess_image(
                    arr, res, self._mean, self._std, depth=True)
            else:
                tensor = preprocess_image(arr, res, self._mean, self._std)
            if self.quality is not None:
                # Input drift histogram (serve/quality.py) — one mean()
                # over an image preprocess already walked.  Guarded
                # separately from the validation above: a monitor bug
                # (or a NaN-poisoned but servable input) may only cost
                # telemetry, never the request.
                try:
                    from .quality import input_mean01

                    self.quality.observe_input(input_mean01(arr))
                except Exception:  # noqa: BLE001
                    self._log.exception("serve: quality monitor failed")
        except Exception:
            # Malformed input / unknown arm: terminate the request in
            # the accounting (the engine owns ALL terminal counters, so
            # the served+shed+expired+errors == submitted invariant
            # holds for 400s too) and let the front end surface it.
            self.stats.inc("errors")
            raise
        now = self._clock()
        slo = self.cfg.serve.slo_ms if slo_ms is None else slo_ms
        # Root span for the request's in-engine life (None unless the
        # trace is sampled — every later touch guards on that).  The
        # root PARENT may live in another tracer (the router's attempt
        # span); within this tracer the request span is the root whose
        # end completes the trace.
        root = self.tracer.begin(
            "request", trace_id, parent_id=trace_parent, t0=now, root=True,
            attrs={"model": self.cfg.model.name, "res_bucket": res,
                   "arm": arm, "level": level})
        req = Request(
            tensor=tensor, orig_hw=(int(arr.shape[0]), int(arr.shape[1])),
            res_bucket=res, arrival=now, precision=arm,
            deadline=(now + slo / 1000.0) if slo and slo > 0 else None,
            degraded=level > 0, level=level, trace_id=trace_id, root=root,
            stream=stream, depth=dplane)
        try:
            # The batcher re-checks the bound under ITS lock (the
            # try_admit above is the cheap pre-preprocess gate; N
            # concurrent submitters could all have passed it).
            self.batcher.put(req)
        except QueueFull:
            self.stats.inc("shed")
            self._trace_end(req, "shed")
            raise
        except RuntimeError as e:  # closed: stop() raced this submit
            self.stats.inc("errors")
            self._trace_end(req, "stopped")
            raise EngineStopped(str(e)) from e
        self.stats.set_queue_depth(self.batcher.pending())
        return req.future

    def predict(self, image: np.ndarray, slo_ms: Optional[float] = None,
                timeout: Optional[float] = None,
                precision: Optional[str] = None):
        """Blocking convenience wrapper over :meth:`submit`."""
        return self.submit(image, slo_ms=slo_ms, precision=precision).result(
            timeout=timeout or self.cfg.serve.request_timeout_s)

    # -- dispatch loop -------------------------------------------------

    def _dispatch_loop(self) -> None:
        while not self._stop.is_set():
            self._dispatch_once(blocking=True)

    def _observe_depth(self) -> int:
        depth = self.batcher.pending()
        self.stats.set_queue_depth(depth)
        self.admission.observe(depth)
        level = self.admission.level
        self.stats.set_degraded(level)
        if self.recorder is not None and level != self._last_rec_level:
            # Degraded-ladder move: one typed event per rung change
            # (the observe point runs at ms cadence; the compare is
            # the only cost on the non-moving path).
            self.recorder.event("degraded_level", level=level,
                                prev=self._last_rec_level, depth=depth)
            self._last_rec_level = level
        if self.alerts is not None:
            # Throttled quality→alert evaluation rides the dispatch
            # loop's existing observe point (the fleet loop spins this
            # at ms cadence; the rules only need ~1 Hz).
            now = self._clock()
            if now >= self._next_alert_eval:
                self._next_alert_eval = now + 1.0
                sigs, details = self.quality.signals()
                self.alerts.evaluate(sigs, now=now, details=details)
        if self.slo is not None:
            # Same cadence for the SLO burn rules: window decay must
            # CLEAR a burn alert even when no new requests arrive to
            # trigger an ingest-side evaluation.
            now = self._clock()
            if now >= self._next_slo_eval:
                self._next_slo_eval = now + 1.0
                self.slo.evaluate(now)
        return depth

    def _dispatch_once(self, blocking: bool = True) -> bool:
        """One dispatch-loop iteration; returns True when a group came
        off the batcher.  ``blocking=True`` is the engine's own loop
        (waits on the coalescing deadline / idle timeout).
        ``blocking=False`` is the fleet's interleaved loop: it never
        waits — not on an empty queue, not on a group still coalescing,
        and not on this engine's inflight semaphore — so one
        back-pressured model reports False and its co-resident siblings
        keep dispatching.  The watchdog contract holds in both modes:
        the beat STOPS while ready work cannot enter the device (the
        wedged-device /healthz signal) and keeps ticking when idle."""
        if blocking:
            if self._watchdog is not None:
                self._watchdog.beat()
            got = self.batcher.get_batch(idle_timeout_s=0.1)
            self._observe_depth()
            if got is None:
                return False
            return self._dispatch_group(got, preacquired=False)
        self._observe_depth()
        if not self.batcher.ready():
            if self._watchdog is not None:
                self._watchdog.beat()
            return False
        if not self._inflight_sem.acquire(blocking=False):
            # Ready work but no device slot: NO beat, so a wedged
            # device still flips THIS model's health while the fleet
            # loop carries on serving its siblings.
            return False
        if self._watchdog is not None:
            self._watchdog.beat()
        got = self.batcher.poll_batch()
        if got is None:  # raced a close(); return the unused slot
            self._inflight_sem.release()
            return False
        return self._dispatch_group(got, preacquired=True)

    def _dispatch_group(self, got, preacquired: bool) -> bool:
        """Expiry-filter, pad, and dispatch one coalesced group.
        ``preacquired`` means the caller already holds one inflight
        semaphore slot (the non-blocking path acquires it BEFORE
        popping, so a group is never stranded outside the queue)."""
        t_pop = self._clock()  # the group just left the batcher
        if self._fault_plan is not None:
            # serve_stall@G:SEC — wedge THIS dispatch before its
            # forward; the watchdog's beat stops while the stall holds
            # ready work out of the device (the /healthz flip the
            # router's health gate reads).
            self._fault_plan.maybe_stall_serve_dispatch()
        (res, arm), reqs = got
        with self._est_lock:
            est = self._est_s.get((res, arm), 0.0)
        now = self._clock()
        live = []
        for r in reqs:
            if AdmissionController.expired(r.deadline, est, now):
                self.stats.inc("expired")
                self._trace_end(r, "expired", t_pop=t_pop)
                self._fail(r, DeadlineExpired(
                    f"deadline missed before dispatch (est device "
                    f"{est * 1000:.1f}ms)"))
            else:
                live.append(r)
        if not live:
            if preacquired:
                self._inflight_sem.release()
            return True
        bb = self.batcher.pick_batch_bucket(len(live))
        stacked = {"image": np.stack([r.tensor for r in live])}
        if self.wants_depth:
            # submit() guarantees every request for a depth model
            # carries its plane, so the stack is total.
            stacked["depth"] = np.stack([r.depth for r in live])
        batch = pad_to_batch(stacked, bb)
        with self._var_lock:
            variables = self._arm_vars[arm]
            step = self._loaded_step
        tta = self.cfg.serve.tta and not self.admission.degraded
        if not preacquired:
            # Bound run-ahead WITHOUT beating the watchdog while we
            # wait: a wedged device keeps this semaphore drained, the
            # beats stop, and /healthz flips — the intended signal.
            acquired = False
            while not self._stop.is_set():
                if self._inflight_sem.acquire(timeout=0.25):
                    acquired = True
                    break
            if not acquired:
                for r in live:
                    self.stats.inc("errors")
                    self._trace_end(r, "stopped", t_pop=t_pop)
                    self._fail(r, EngineStopped("engine stopped"))
                return True
        t0 = self._clock()
        for r in live:
            r.dispatch_t = t0
            self.stats.queue_ms.observe((t0 - r.arrival) * 1000.0)
            if r.root is not None:
                # queue: batcher wait (backlog + coalescing window);
                # coalesce: group assembly — expiry filter, padding,
                # the inflight-semaphore wait.  Together they tile
                # arrival → dispatch exactly (== the queue_ms
                # histogram's observation for this request).
                self.tracer.record(r.trace_id, "queue", r.arrival, t_pop,
                                   parent_id=r.root.span_id)
                self.tracer.record(r.trace_id, "coalesce", t_pop, t0,
                                   parent_id=r.root.span_id,
                                   attrs={"group": len(live), "bucket": bb})
        # Count the in-flight slot the moment the semaphore is held
        # so the error path's _release_inflight always undoes a
        # matching increment (the gauge must never go negative-ish
        # while OTHER batches are genuinely in flight).
        with self._inflight_lock:
            self._inflight_n += 1
            self.stats.set_inflight(self._inflight_n)
        try:
            probs = self._forward(res, bb, arm, variables, batch, tta)
        except Exception as e:  # noqa: BLE001 — per-request surface
            self._release_inflight()
            self._log.exception("serve: dispatch failed")
            for r in live:
                self.stats.inc("errors")
                self._trace_end(r, "error")
                self._fail(r, e)
            if self.recorder is not None:
                # A failed device dispatch is an incident: bundle the
                # telemetry around it (debounced — a poisoned program
                # failing every group cannot bundle-storm).
                self.recorder.event(
                    "dispatch_error", res=res, arm=arm,
                    requests=len(live),
                    error=f"{type(e).__name__}: {e}"[:200])
                # Background: this is the engine's ONE dispatch loop —
                # the capture must not stall sibling batches.
                self.recorder.trigger("dispatch_error",
                                      f"{type(e).__name__}",
                                      background=True)
            return True
        self.stats.observe_batch(len(live), bb, arm=arm)
        meta = {"res_bucket": res, "batch_bucket": bb, "tta": tta,
                "step": step, "precision": arm}
        self._fetch_pool.submit(self._complete, probs, live, meta, t0)
        return True

    def _forward(self, res: int, bb: int, arm: str, variables, batch,
                 tta: bool):
        key = (self.cfg.model.name, res, bb, self.cfg.model.resample_impl,
               self._conv_impl, arm)
        call = self.programs.get(key)
        if call is None:
            # Not AOT-warmed (a bucket added after start): the jit
            # fallback compiles ON THE REQUEST PATH the first time a
            # key is used, and only then.  Counted once per key, so "no
            # request ever pays a compile" is a number on /metrics and
            # not only a design intent.  (One dispatch thread: no lock.)
            if key not in self._request_compiled:
                self._request_compiled.add(key)
                self.stats.inc("request_compiles")
            call = self._fwds[arm]

        def fn(b):
            return call(variables, b)

        # Same wrapper the offline eval uses — serving TTA can never
        # drift from test.py's convention.
        return (flip_tta(fn) if tta else fn)(batch)

    # -- completion (host) ---------------------------------------------

    def _release_inflight(self) -> None:
        self._inflight_sem.release()
        with self._inflight_lock:
            self._inflight_n = max(self._inflight_n - 1, 0)
            self.stats.set_inflight(self._inflight_n)

    def _complete(self, probs, live, meta, t0: float) -> None:
        try:
            t_f0 = self._clock()
            arr = np.asarray(probs)[: len(live)]  # the blocking fetch
            t_f1 = self._clock()
            dev_ms = (t_f1 - t0) * 1000.0
            for r in live:
                if r.root is not None:
                    # device: dispatch → fetch complete (== the
                    # device_ms histogram's observation); fetch is the
                    # host-blocking tail of it, parented under device.
                    dev_sid = self.tracer.record(
                        r.trace_id, "device", t0, t_f1,
                        parent_id=r.root.span_id,
                        attrs={"batch_bucket": meta["batch_bucket"]})
                    self.tracer.record(r.trace_id, "fetch", t_f0, t_f1,
                                       parent_id=dev_sid)
            if self.capacity is not None and not meta.get("tta"):
                # Per-program measured time → live MFU.  TTA responses
                # are skipped: flip_tta runs the program twice, which
                # would halve the reported utilization of a program
                # that ran at full tilt.
                self.capacity.observe(
                    self._capacity_key(meta["res_bucket"],
                                       meta["batch_bucket"],
                                       meta["precision"]), dev_ms)
            est_key = (meta["res_bucket"], meta["precision"])
            with self._est_lock:
                old = self._est_s.get(est_key)
                now_s = dev_ms / 1000.0
                self._est_s[est_key] = (now_s if old is None
                                        else 0.8 * old + 0.2 * now_s)
            arm_stats = self.stats.arm(meta["precision"])
            for _ in live:
                self.stats.device_ms.observe(dev_ms)
                arm_stats.device_ms.observe(dev_ms)
            for j, r in enumerate(live):
                self._post_pool.submit(
                    self._finish, r, arr[j], dict(meta, device_ms=dev_ms))
        except Exception as e:  # noqa: BLE001 — per-request surface
            self._log.exception("serve: completion failed")
            for r in live:
                self.stats.inc("errors")
                self._trace_end(r, "error")
                self._fail(r, e)
        finally:
            self._release_inflight()

    def _finish(self, r: Request, row: np.ndarray, meta: dict) -> None:
        try:
            t_r0 = self._clock()
            pred = _resize_pred(row, r.orig_hw)
            t_done = self._clock()
            e2e = (t_done - r.arrival) * 1000.0
            meta.update(
                degraded=r.degraded, degraded_level=r.level,
                queue_ms=round((r.dispatch_t - r.arrival) * 1000.0, 3),
                resize_ms=round((t_done - t_r0) * 1000.0, 3),
                e2e_ms=round(e2e, 3),
                # trace_id only when the trace was SAMPLED (spans
                # exist in /debug/traces); X-Timing says "trace=-"
                # otherwise, request id still echoed separately.
                trace_id=r.trace_id if r.root is not None else None)
            if r.root is not None:
                self.tracer.record(r.trace_id, "resize_back", t_r0, t_done,
                                   parent_id=r.root.span_id)
                # Root ends with t1 = the same instant e2e_ms was
                # computed at, so the trace's dur_ms, the X-Timing
                # header, and the e2e histogram observation agree.
                r.root.end(t1=t_done,
                           key=(self.cfg.model.name, r.res_bucket),
                           outcome="served")
            self.stats.e2e_ms.observe(e2e)
            arm_stats = self.stats.arm(r.precision)
            arm_stats.e2e_ms.observe(e2e)
            arm_stats.inc_served()
            self.stats.inc("served")
            self._set_result(r, (pred, meta))
        except Exception as e:  # noqa: BLE001 — per-request surface
            self.stats.inc("errors")
            self._trace_end(r, "error")
            self._fail(r, e)
            return
        if self.quality is not None:
            # Quality monitors run AFTER the future resolved: the
            # response never waits on stats, and a monitor bug can
            # only cost telemetry, not a request.
            try:
                self.quality.observe_output(row)
                # Shadow only non-f32, non-TTA responses (a TTA row
                # vs a plain f32 forward would measure TTA, not the
                # arm) — the sampler sees every eligible response.
                if (r.precision != "f32" and not meta.get("tta")
                        and self.quality.should_shadow()):
                    self._submit_shadow(r.tensor, row, meta,
                                        depth=r.depth)
            except Exception:  # noqa: BLE001 — telemetry must not throw
                self._log.exception("serve: quality monitor failed")

    # -- shadow scoring (serve/quality.py) ------------------------------

    def _submit_shadow(self, tensor: np.ndarray, row: np.ndarray,
                       meta: dict,
                       depth: Optional[np.ndarray] = None) -> None:
        """Queue one arm-vs-f32 shadow score on the side lane, or DROP
        (counted) when the lane is full — reference forwards must never
        queue live traffic behind them."""
        if self._shadow_pool is None \
                or not self._shadow_sem.acquire(blocking=False):
            self.quality.record_shadow_dropped()
            return
        try:
            self._shadow_pool.submit(self._shadow_score, tensor, row,
                                     dict(meta), depth)
        except RuntimeError:  # pool shut down under us
            self._shadow_sem.release()
            self.quality.record_shadow_dropped()

    def _shadow_score(self, tensor: np.ndarray, row: np.ndarray,
                      meta: dict,
                      depth: Optional[np.ndarray] = None) -> None:
        """Re-run one served input through the f32 reference program
        and record the live disagreement (mean |Δ| + thresholded-mask
        flip rate) for the arm that served it.  A hot reload between
        the serve and the shadow invalidates the comparison (the arm
        row came from other weights) — dropped, counted."""
        try:
            with self._var_lock:
                variables = self._arm_vars["f32"]
                step = self._loaded_step
            if step != meta.get("step"):
                self.quality.record_shadow_dropped()
                return
            res = meta["res_bucket"]
            bb = self.batcher.pick_batch_bucket(1)
            stacked = {"image": tensor[None]}
            if depth is not None:
                stacked["depth"] = depth[None]
            batch = pad_to_batch(stacked, bb)
            probs = self._forward(res, bb, "f32", variables, batch,
                                  tta=False)
            ref = np.asarray(probs)[0].astype(np.float32)
            arm_row = np.asarray(row, np.float32)
            mae = float(np.mean(np.abs(arm_row - ref)))
            flip = float(np.mean((arm_row > 0.5) != (ref > 0.5)))
            self.quality.record_shadow(meta["precision"], mae, flip)
        except Exception:  # noqa: BLE001 — telemetry must not throw
            self._log.exception("serve: shadow score failed")
            self.quality.record_shadow_dropped()
        finally:
            self._shadow_sem.release()

    def stats_snapshot(self) -> Dict:
        """The /stats payload: ServeStats plus — when the monitors are
        on — the quality snapshot and the active alerts (the full rule
        states live at /alerts)."""
        out = self.stats.snapshot()
        if self._loaded_step is not None:
            # Which checkpoint is actually serving — the rollout control
            # plane (serve/rollout.py) reads this per replica to confirm
            # a canary/promote landed where it was sent.
            out["loaded_step"] = int(self._loaded_step)
        if self.quality is not None:
            out["quality"] = self.quality.snapshot()
        if self.alerts is not None:
            out["alerts"] = self.alerts.active()
        if self.capacity is not None:
            out["capacity"] = self.capacity.snapshot()
        if self.slo is not None:
            out["slo"] = self.slo.snapshot()
        if self.recorder is not None:
            out["recorder"] = self.recorder.snapshot()
        return out

    def _trace_end(self, r: Request, outcome: str,
                   t_pop: Optional[float] = None) -> None:
        """Close a failed/shed request's trace with its outcome (the
        happy path ends the root in :meth:`_finish`).  ``t_pop`` (the
        expiry path) records the queue span the request DID spend
        before being dropped."""
        if r.root is None:
            return
        if t_pop is not None:
            self.tracer.record(r.trace_id, "queue", r.arrival, t_pop,
                               parent_id=r.root.span_id)
        r.root.end(key=(self.cfg.model.name, r.res_bucket),
                   outcome=outcome)

    @staticmethod
    def _set_result(r: Request, value) -> None:
        try:
            r.future.set_result(value)
        except Exception:  # noqa: BLE001 — abandoned/cancelled future
            pass

    @staticmethod
    def _fail(r: Request, exc: Exception) -> None:
        try:
            r.future.set_exception(exc)
        except Exception:  # noqa: BLE001 — abandoned/cancelled future
            pass

    # -- hot weight reload ---------------------------------------------

    def _reload_loop(self) -> None:
        from ..ckpt import CheckpointManager

        mgr = CheckpointManager(self.ckpt_dir, async_save=False)
        try:
            while not self._stop.wait(self.cfg.serve.reload_poll_s):
                try:
                    self._maybe_reload(mgr)
                except Exception:  # noqa: BLE001 — keep serving old weights
                    self._log.exception(
                        "serve: weight reload failed; keeping current "
                        "weights")
        finally:
            mgr.close()

    def _maybe_reload(self, mgr) -> None:
        # Newest VALID (integrity-gated) step that the rollout denylist
        # (serve/rollout.py) has not pinned bad: a step that canaried
        # badly and was rolled back must never be re-picked by the
        # background poll, or the rollback would undo itself one poll
        # later.
        from .rollout import read_step_denylist

        mgr.reload()  # steps (and denylist verdicts) land between scans
        deny = read_step_denylist(self.ckpt_dir)
        steps = [s for s in mgr.valid_steps() if s not in deny]
        step = max(steps) if steps else None
        if step is None or step == self._loaded_step:
            return
        self._reload_step(mgr, step)

    def _reload_step(self, mgr, step: int) -> None:
        """Restore ``step`` and swap it in (the shared tail of the
        background poll and :meth:`reload_to`)."""
        state = mgr.restore(self._template, step)
        # Re-derive EVERY arm's weight view off-lock (cast + quantize
        # are the slow part), then swap the whole dict in one motion —
        # a concurrent dispatch sees either the old step's views or the
        # new step's views, never a mix across arms.
        arm_vars = self._derive_arm_vars(state.eval_variables())
        with self._var_lock:
            self._arm_vars = arm_vars
            self._loaded_step = step
        self.stats.inc("reloads")
        if self.recorder is not None:
            self.recorder.event("hot_reload", step=int(step))
        self._log.info("serve: hot-reloaded weights from step %d", step)

    def reload_to(self, step: int) -> int:
        """Synchronously load checkpoint ``step`` — the rollout control
        plane's targeted reload (serve/rollout.py drives ONE canary
        replica to the candidate step, everyone else on promote).
        Returns the loaded step; raises on a missing/invalid/denylisted
        step or an engine without a checkpoint source."""
        from ..ckpt import CheckpointManager

        from .rollout import read_step_denylist

        if not self.ckpt_dir or self._template is None:
            raise RuntimeError(
                "reload_to: engine has no checkpoint source (started "
                "from random init without ckpt_dir)")
        step = int(step)
        if step in read_step_denylist(self.ckpt_dir):
            raise ValueError(
                f"reload_to: step {step} is denylisted (it canaried "
                "badly and was rolled back)")
        mgr = CheckpointManager(self.ckpt_dir, async_save=False)
        try:
            if step not in mgr.valid_steps():
                raise ValueError(
                    f"reload_to: step {step} is not a VALID checkpoint "
                    f"in {self.ckpt_dir} (have {mgr.valid_steps()})")
            if step != self._loaded_step:
                self._reload_step(mgr, step)
        finally:
            mgr.close()
        return step
