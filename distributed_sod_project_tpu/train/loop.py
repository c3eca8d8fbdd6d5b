"""The training engine: config → trained checkpoints (SURVEY.md §2 C1, §3.1).

``fit(cfg)`` is the whole reference ``train.py::main`` (SURVEY.md §3.1)
minus process spawning: on TPU pods every host runs the same ``fit``
under ``jax.distributed`` and the mesh spans all chips; there is no
torchrun/fork step.  Per step the host only feeds its local shard of the
batch and reads back scalar metrics — everything else (forward, loss,
backward, cross-replica psum, optimizer) is one compiled XLA program
built by the unified rules engine (`parallel/engine.py`).
"""

from __future__ import annotations

import collections
import gc
import itertools
import os
import statistics
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ckpt import CheckpointManager
from ..configs.base import (ExperimentConfig, validate_parallel,
                            validate_steps_per_dispatch)
from ..data import chunk_batches, prefetch_to_device, resolve_dataset
from ..models import build_model
from ..parallel.mesh import make_mesh, replicated_sharding
from ..utils import tracing
from ..utils.logging import get_logger, is_primary_process
from ..utils.timing import StepTimer
from .optim import build_optimizer
from .state import create_train_state, param_count
from .step import make_eval_step


def _poll_stop(guard, step: int, sync_every: int) -> bool:
    """Graceful-stop polling cadence (one knob, unit-tested):
    single-process reads the host-local flag every step; multi-host
    agrees only at deterministic steps (all hosts must enter the
    allgather together — ``sync_every`` = the logging cadence), keeping
    the async run-ahead between agreement points."""
    if jax.process_count() == 1:
        return guard.should_stop
    if step % sync_every == 0:
        # Blocking allgather — throttled so the host keeps
        # its async run-ahead between agreement points.
        return guard.sync()
    return False


def fit(
    cfg: ExperimentConfig,
    workdir: Optional[str] = None,
    resume: bool = False,
    max_steps: Optional[int] = None,
    hooks: Optional[Dict[str, Callable]] = None,
    profile_dir: Optional[str] = None,
    telemetry_port: Optional[int] = None,
    telemetry_port_file: Optional[str] = None,
) -> Dict[str, float]:
    """Run the full training loop; returns final scalar metrics.

    ``max_steps`` truncates (smoke tests / benchmarks); ``hooks`` may
    contain ``on_metrics(step, dict)`` and — under step chunking —
    ``on_chunk_metrics(step, stacked_dict)`` for test instrumentation;
    ``profile_dir`` captures a jax.profiler trace of a short post-warmup
    step window (view in TensorBoard/Perfetto).

    ``telemetry_port`` (overrides ``cfg.telemetry_port``; >= 0 = on,
    0 = ephemeral) starts the opt-in telemetry sidecar
    (utils/telemetry.py — /metrics, /healthz off the step watchdog,
    /debug/traces, on-demand /debug/profile), publishing the bound
    port atomically to ``telemetry_port_file``.  ``cfg.trace_sample``
    additionally records per-chunk span timelines
    (docs/OBSERVABILITY.md).

    ``cfg.steps_per_dispatch=k > 1`` folds k steps into one
    ``lax.scan`` dispatch: the loop advances chunk-by-chunk (every
    cadence knob must divide by k — validate_steps_per_dispatch), k
    host batches stack into one H2D transfer, and the steady state
    does exactly ONE host↔device sync per chunk (the stacked-metrics
    readback).  See docs/PERFORMANCE.md "Device-side step chunking".

    Resilience (docs/RESILIENCE.md): restore lands on the newest VALID
    checkpoint; ``cfg.watchdog_deadline_s`` arms the wedged-step
    watchdog; ``cfg.data.skip_budget`` tolerates corrupt samples;
    ``DSOD_FAULTS`` injects deterministic faults (chaos tests).
    """
    from ..resilience import inject
    from ..utils.observability import (MetricWriter, PipelineStats,
                                       PreemptionGuard, profile_window)
    from ..utils.tracing import Tracer, mint_trace_id, span

    log = get_logger()
    # dsod.setup.before_fit ends and dsod.setup.build opens HERE, on
    # the host clock (docs/OBSERVABILITY.md "Set-up phases").
    watch = _HostWatch(log)
    hooks = hooks or {}
    workdir = workdir or cfg.checkpoint_dir
    plan = inject.plan_from_env()

    if not cfg.health_numerics:
        # Loudness: both knobs only act through the numerics monitor —
        # set without it they would be silent no-ops, and an operator
        # who opted into rollback protection must not run unprotected.
        if cfg.health_rollback_hint:
            raise ValueError(
                "health_rollback_hint=true requires health_numerics=true "
                "(the rollback hand-off consumes the numerics alerts)")
        if cfg.health_alert_rules:
            raise ValueError(
                "health_alert_rules set but health_numerics is false — "
                "the training alert engine only runs with the numerics "
                "telemetry on")

    # Device-side step chunking (docs/PERFORMANCE.md): k steps fold
    # into one lax.scan dispatch and the loop advances chunk-by-chunk.
    # Fault plans force k=1 — poison/stall/SIGTERM are PER-STEP
    # semantics the chaos suite asserts exactly, and a scanned chunk
    # has no host boundary between its steps to inject at.
    k = int(cfg.steps_per_dispatch)
    if plan is not None and k > 1:
        log.warning(
            "DSOD_FAULTS is set: forcing steps_per_dispatch=1 (was %d) "
            "so per-step poison/stall/SIGTERM semantics stay exact", k)
        k = 1

    mesh = make_mesh(cfg.mesh)
    n_dev = mesh.devices.size
    # The batch dim only shards over ``data`` (model/seq shard other
    # dims), so that is the divisibility requirement.
    data_size = mesh.shape.get("data", n_dev)
    if cfg.global_batch_size % data_size:
        raise ValueError(
            f"global_batch_size={cfg.global_batch_size} not divisible by "
            f"the data mesh axis ({data_size})")

    from ..data.tfdata import make_loader
    from ..parallel.mesh import host_batch_shard

    # Mesh-position-derived, NOT process_index: hosts that share a
    # data block (a seq/model axis spanning processes) must load
    # IDENTICAL batches — their devices hold different shards of the
    # same images.  Pure DP reduces to (process_index, process_count).
    shard_id, num_shards = host_batch_shard(mesh)
    dataset = resolve_dataset(cfg.data)
    # Name the input path that actually runs: a missing
    # native/build/libdsod_host.so (git-ignored, built by `make -C
    # native`) silently means PIL decode, and two machines would
    # otherwise feed the same step from different loaders unnoticed.
    from ..data import native as native_decode
    from ..data.synthetic import SyntheticSOD

    log.info("host loader: backend=%s dataset=%s decode=%s workers=%d",
             cfg.data.backend, type(dataset).__name__,
             "none (generated in numpy)"
             if isinstance(dataset, SyntheticSOD)
             else "native libdsod_host.so" if native_decode.available()
             else "PIL (native/build/libdsod_host.so not built)",
             cfg.data.num_workers)
    # Corrupt-sample degradation: bounded skip-budget with
    # deterministic substitution instead of an epoch-killing exception
    # (host/grain backends fetch through the wrapper; tfdata enforces
    # the same budget via its shortfall check — see dataguard.py).
    data_guard = None
    if cfg.data.skip_budget > 0 or (plan is not None
                                    and plan.corrupt_indices):
        from ..resilience.dataguard import GuardedDataset

        data_guard = GuardedDataset(dataset, cfg.data.skip_budget,
                                    fault_plan=plan)
        dataset = data_guard
    # Spans (utils/tracing.py::span; docs/OBSERVABILITY.md): what loop
    # and data plane do under ``dsod.train.*`` / ``dsod.data.*``, as
    # profiler annotations, as seconds per name on the host clock, and
    # for sampled chunks (cfg.trace_sample) in the /debug/traces ring.
    tracer = Tracer(sample=cfg.trace_sample, clock=tracing.now)
    # Host-data-plane telemetry: every blocking point in the loader /
    # prefetch stages reports here; the per-interval deltas ride the
    # metric stream (data_starved_ms: the loop's wait for a batch).
    data_stats = PipelineStats(keep_spans=tracer.enabled)
    loader = make_loader(
        dataset, cfg.data,
        global_batch_size=cfg.global_batch_size,
        shard_id=shard_id,
        num_shards=num_shards,
        shuffle=True,
        seed=cfg.seed,
        hflip=cfg.data.hflip,
        rotate_degrees=cfg.data.rotate_degrees,
        color_jitter=cfg.data.color_jitter,
        num_workers=cfg.data.num_workers,
        skip_budget=cfg.data.skip_budget,
        stats=data_stats,
    )
    steps_per_epoch = cfg.steps_per_epoch or loader.steps_per_epoch
    if steps_per_epoch <= 0:
        raise ValueError(
            f"dataset of {len(dataset)} samples yields zero steps at "
            f"global_batch_size={cfg.global_batch_size}")
    # Chunk-boundary contract: every cadence knob AND the loader's
    # actual epoch period must be multiples of k (loud ValueError
    # naming the offending pair — configs/base.py).
    validate_steps_per_dispatch(cfg.replace(steps_per_dispatch=k),
                                loader.steps_per_epoch)
    watch.lap("loader_start")
    total_steps = steps_per_epoch * cfg.num_epochs
    if max_steps is not None:
        total_steps = min(total_steps, max_steps)
        if k > 1 and total_steps % k:
            raise ValueError(
                f"max_steps={max_steps} truncates the run to "
                f"{total_steps} steps, not a multiple of "
                f"steps_per_dispatch={k} — the loop would overshoot "
                "mid-chunk; pass a max_steps that is a multiple of k")

    model = build_model(cfg.model)
    tx, schedule = build_optimizer(cfg.optim, total_steps)

    watch.lap("model")
    sample = next(iter(loader))
    watch.lap("loader_first_batch")
    from ..utils.checks import periodic_validate, validate_first_batch

    validate_first_batch(sample, cfg, model)
    state = create_train_state(jax.random.key(cfg.seed), model, tx, sample,
                               pretrained=cfg.model.pretrained,
                               ema=cfg.optim.ema_decay > 0)
    watch.lap("state_init")
    # Training numerics telemetry (utils/modelhealth.py;
    # docs/OBSERVABILITY.md "Model health"): the step emits per-group
    # grad norms / nonfinite provenance / update ratio, the monitor
    # aggregates them for the sidecar, and the alert engine watches the
    # derived signals.  All None when the knob is off — every touch
    # below guards on that, so the default path pays nothing.
    # Flight recorder (utils/flightrecorder.py): constructed AFTER the
    # telemetry registry below; the alert engines built first hook
    # their transitions through this cell so construction order stays
    # linear.  None-when-off discipline throughout.
    _recorder_cell = [None]

    def _rec_transition(rule, old, new, snap):
        if _recorder_cell[0] is not None:
            _recorder_cell[0].alert_transition(rule, old, new, snap)

    health_monitor = None
    health_alerts = None
    if cfg.health_numerics:
        from ..utils.alerts import AlertEngine, parse_rules
        from ..utils.modelhealth import (HealthMonitor,
                                         default_numerics_rules,
                                         param_group_names)

        health_monitor = HealthMonitor(param_group_names(state.params))
        health_alerts = AlertEngine(
            default_numerics_rules(clear_s=cfg.health_alert_clear_s)
            + parse_rules(cfg.health_alert_rules),
            on_transition=_rec_transition)

    # Capacity ledger + goodput SLO (utils/capacity.py, utils/slo.py;
    # docs/OBSERVABILITY.md "Capacity & SLO").  Both None when off —
    # every touch below guards, so the default loop pays nothing and
    # the sidecar surface is byte-identical.
    capacity = None
    slo_tracker = None
    t_run0 = time.monotonic()
    if cfg.capacity_ledger:
        from ..utils.capacity import CapacityLedger

        def _train_shares():
            # Host-vs-device attribution for the train loop: the
            # starved counter (the loop's wait for a batch) is an
            # upper bound on "device idle waiting on the host data
            # plane" — the futile-to-scale share.
            wall_ms = max((time.monotonic() - t_run0) * 1000.0, 1e-9)
            starved = data_stats.snapshot().get("data_starved_ms", 0.0)
            host = min(starved / wall_ms, 1.0)
            return {"device": max(1.0 - host, 0.0), "queue": 0.0,
                    "host": host}

        capacity = CapacityLedger(share_fn=_train_shares)
    if cfg.slo_objectives:
        from ..utils.slo import build_tracker

        slo_tracker = build_tracker(
            cfg.slo_objectives, burn_threshold=cfg.slo_burn_threshold,
            alert_for_s=cfg.slo_alert_for_s,
            alert_clear_s=cfg.slo_alert_clear_s,
            on_transition=_rec_transition)

    def _observe_health(metrics_host) -> None:
        """Feed one fetched metric dict to the health monitor + alert
        engine.  Under ``health_rollback_hint`` a FIRING rollback-
        hinted alert (numerics_nonfinite) raises the divergence
        RuntimeError the PR-1 supervisor's rollback-and-retry policy
        recognizes (resilience/supervisor.py::is_divergence)."""
        if health_monitor is None:
            return
        health_monitor.observe(metrics_host)
        sigs, details = health_monitor.signals()
        health_alerts.evaluate(sigs, details=details)
        if cfg.health_rollback_hint:
            fired = health_alerts.firing(hint="rollback")
            if fired:
                snap = health_monitor.snapshot()
                raise RuntimeError(
                    f"model-health alert {fired[0].name!r} "
                    f"(first non-finite group: "
                    f"{snap['last_nonfinite_group'] or '?'}): non-finite "
                    "gradient updates detected — rolling back to the "
                    "last checkpoint (health_rollback_hint)")

    log.info("model=%s params=%.2fM devices=%d global_batch=%d "
             "steps/epoch=%d total_steps=%d",
             cfg.model.name, param_count(state) / 1e6, n_dev,
             cfg.global_batch_size, steps_per_epoch, total_steps)

    if cfg.best_metric and not cfg.eval_every_steps:
        raise ValueError(
            "best_metric retention needs eval_every_steps > 0 — without "
            "eval metrics orbax never deletes checkpoints and keep_"
            "checkpoints is silently ignored")
    if cfg.best_mode not in ("max", "min"):
        raise ValueError(f"best_mode must be max|min, got {cfg.best_mode!r}")
    mgr = CheckpointManager(workdir, keep=cfg.keep_checkpoints,
                            best_metric=cfg.best_metric,
                            best_mode=cfg.best_mode)
    if is_primary_process():
        mgr.save_config(cfg)
    start_step = 0
    resumed_from = -1
    if resume:
        # Newest VALID checkpoint: tmp/truncated/corrupt step dirs are
        # quarantined and the next-newest is tried (ckpt/manager.py) —
        # a preemption mid-save costs checkpoint_every_steps of
        # recompute, never the run.
        state, ck_step = mgr.restore_latest_valid(state)
        if ck_step is not None:
            start_step = int(state.step)
            resumed_from = start_step
            log.info("resumed from checkpoint step %d", start_step)
            if k > 1 and start_step % k:
                # checkpoint_every_steps % k == 0 guarantees chunk-
                # aligned saves, so a misaligned resume means the
                # checkpoint came from a run with a different k (e.g. a
                # k=1 final force-save mid-cycle).
                raise ValueError(
                    f"resumed checkpoint step {start_step} is not a "
                    f"multiple of steps_per_dispatch={k} — the chunked "
                    "loop must re-enter on a chunk boundary.  Resume "
                    "with steps_per_dispatch=1 (or a k dividing "
                    f"{start_step}) until the next aligned checkpoint")

    watch.lap("checkpoint")
    # Step builder: every preset routes through the unified rule-driven
    # builder (parallel/engine.py — the only step builder since the
    # round-18 legacy deletion): shard_map DP for the CNN zoo
    # (named-axis SyncBN), GSPMD tp/fsdp when the model axis is
    # sharded, any ZeRO level is on, or parallel.preset=fsdp shards the
    # params themselves, and the sequence-parallel preset when ``seq``
    # is sharded (ring attention over token blocks, vit_sod only).
    validate_parallel(cfg)
    from ..parallel import engine as engine_mod

    zero_eff = engine_mod.effective_zero(cfg)
    preset = engine_mod.select_preset(cfg, mesh)
    use_sp = preset == "sp"
    use_gspmd = preset in ("tp", "fsdp")
    if use_sp:
        if (mesh.shape.get("model", 1) > 1 or cfg.optim.zero1
                or cfg.parallel.zero > 0):
            raise ValueError(
                "mesh.seq>1 cannot combine with mesh.model>1 / "
                "optim.zero1 (pick one non-data axis per run)")
        if cfg.model.sync_bn:
            raise ValueError(
                "sequence parallelism requires a BatchNorm-free model: "
                "set model.sync_bn=false (use model.name=vit_sod)")
        if not hasattr(model, "patch"):
            raise ValueError(
                f"model {cfg.model.name!r} does not support sequence "
                "parallelism — only halo-free token models (vit_sod) "
                "shard over mesh.seq")
        if cfg.data.multiscale:
            raise ValueError(
                "data.multiscale is not supported with mesh.seq>1")
        seq = mesh.shape["seq"]
        rows = cfg.data.image_size[0] // model.patch
        if cfg.data.image_size[0] % model.patch or rows % seq:
            raise ValueError(
                f"image height {cfg.data.image_size[0]} must be a "
                f"multiple of patch*seq = {model.patch}*{seq}")
        state = jax.device_put(state, replicated_sharding(mesh))

        def step_factory(scale_hw):
            return engine_mod.make_unified_train_step(
                model, cfg.loss, tx, mesh, preset="sp",
                schedule=schedule, ema_decay=cfg.optim.ema_decay,
                donate_batch=True,
                sp_strategy=cfg.mesh.sp_strategy,
                remat=cfg.model.remat,
                remat_policy=cfg.model.remat_policy,
                steps_per_dispatch=k,
                health=cfg.health_numerics)
    elif use_gspmd:
        from ..parallel.rules import (PRESET_PARAM_RULES,
                                      fsdp_fallback_rule,
                                      shard_state_by_rules)

        if cfg.model.sync_bn:
            raise ValueError(
                "mesh.model>1 / optim.zero1 / parallel.preset=fsdp "
                "route through the GSPMD step, which has no named mesh "
                "axis: set model.sync_bn=false (BN stats are "
                "global-batch there, strictly stronger)")
        n_model = mesh.shape.get("model", 1)
        # Head-alignment guard — models exposing a scalar ``heads``
        # (vit_sod) promise boundary-aligned column shards; fail loudly
        # when the promise can't hold (GSPMD would re-gather q/k/v
        # every block).  Swin's head-major qkv packing aligns whenever
        # ``model`` divides a stage's head count (3,6,12,24) — only
        # non-dividing stages fall back to GSPMD resharding (see
        # parallel/tp.py docstring; stage 1 with model=2 is the one
        # case for Swin-T).
        heads = getattr(model, "heads", None)
        if n_model > 1 and isinstance(heads, int) and heads % n_model:
            raise ValueError(
                f"mesh.model={n_model} does not divide the model's "
                f"{heads} attention heads — pick a model-axis degree "
                "that divides the head count")
        if preset == "fsdp":
            state, state_shardings = shard_state_by_rules(
                state, mesh, rules=PRESET_PARAM_RULES["fsdp"],
                zero=zero_eff, fallback=fsdp_fallback_rule(mesh))
        else:
            state, state_shardings = shard_state_by_rules(
                state, mesh, zero=zero_eff)

        def step_factory(scale_hw):
            return engine_mod.make_unified_train_step(
                model, cfg.loss, tx, mesh, preset=preset,
                schedule=schedule, ema_decay=cfg.optim.ema_decay,
                scale_hw=scale_hw, donate_batch=True,
                remat=cfg.model.remat,
                remat_policy=cfg.model.remat_policy,
                steps_per_dispatch=k,
                health=cfg.health_numerics,
                state_shardings=state_shardings, zero=zero_eff)
    else:
        # Replicate first, THEN seed the residual — seeding places the
        # residual P('data'), which a blanket replicate would undo.
        residual = getattr(state, "comm_residual", None)
        state = jax.device_put(state.replace(comm_residual=None),
                               replicated_sharding(mesh))
        if cfg.parallel.grad_compression == "int8_ef":
            state = engine_mod.seed_comm_residual(
                state.replace(comm_residual=residual), mesh)

        def step_factory(scale_hw):
            return engine_mod.make_unified_train_step(
                model, cfg.loss, tx, mesh, preset="dp",
                schedule=schedule, remat=cfg.model.remat,
                ema_decay=cfg.optim.ema_decay,
                scale_hw=scale_hw, donate_batch=True,
                remat_policy=cfg.model.remat_policy,
                steps_per_dispatch=k,
                health=cfg.health_numerics,
                comm_bucket_mb=cfg.parallel.comm_bucket_mb,
                grad_compression=cfg.parallel.grad_compression,
                data_hosts=cfg.mesh.data_hosts)

    # Multi-scale training: one compiled step per size in the cycle
    # (each is a distinct static-shape XLA program; the resize happens
    # on-device inside the step).  Single-scale is the 1-entry cycle at
    # the loader's native (possibly non-square) image_size.
    ms_cycle = (tuple((s, s) for s in cfg.data.multiscale)
                or (tuple(cfg.data.image_size),))
    step_for_size = {
        hw: step_factory(None if hw == tuple(cfg.data.image_size) else hw)
        for hw in dict.fromkeys(ms_cycle)
    }
    # Multi-scale cycles per CHUNK (all k steps of a dispatch share one
    # static-shape program; each size stays its own compiled program).
    # At k=1 this reduces exactly to the historical per-step cycling.
    train_step_at = lambda i: step_for_size[ms_cycle[(i // k) % len(ms_cycle)]]  # noqa: E731

    # Capacity/SLO feed points (both no-ops when the knobs are off).
    # The ledger key names the static program (size × chunk factor);
    # observations are gated past the StepTimer's warmup so compile
    # time never poisons the EWMA the MFU gauge divides by.
    _cap_recorded = set()
    _cap_t_last = [None]

    def _cap_key(at_step: int) -> str:
        hw = ms_cycle[(at_step // k) % len(ms_cycle)]
        return f"train/{hw[0]}x{hw[1]}/k{k}"

    def _maybe_record_capacity(at_step, train_step, state, batch) -> None:
        if capacity is None:
            return
        ck = _cap_key(at_step)
        if ck not in _cap_recorded:
            _cap_recorded.add(ck)
            # One extra AOT compile per static shape, paid only with
            # the ledger opted in — the cost_analysis()/
            # memory_analysis() of the REAL step program.
            capacity.record_jit(ck, train_step, state, batch)
            # Comm ledger (ROADMAP item 4): the engine's static
            # shape-priced plan — per-collective bytes and link level,
            # overlap estimate, ZeRO/FSDP HBM saving — under the same
            # program key.  Guarded like every telemetry touch.
            try:
                capacity.record_comm(ck, engine_mod.comm_plan(
                    state, mesh, preset=preset, zero=zero_eff,
                    comm_bucket_mb=cfg.parallel.comm_bucket_mb,
                    grad_compression=cfg.parallel.grad_compression,
                    data_hosts=cfg.mesh.data_hosts))
            except Exception:  # noqa: BLE001 — telemetry only
                log.exception("capacity: comm_plan failed for %s", ck)

    def _observe_capacity_slo(chunk_start_step: int) -> None:
        """Per completed chunk: fold the measured per-step time into
        the ledger EWMA and feed one goodput SLO event per step."""
        if capacity is None and slo_tracker is None:
            return
        now = time.monotonic()
        prev, _cap_t_last[0] = _cap_t_last[0], now
        if prev is None or timer.ticks <= timer.warmup:
            return  # compile-time interval: not a measured step
        per_step_ms = (now - prev) * 1000.0 / k
        if capacity is not None:
            capacity.observe(_cap_key(chunk_start_step), per_step_ms)
        if slo_tracker is not None:
            slo_tracker.observe(True, latency_ms=per_step_ms,
                                model=cfg.model.name, n=k)

    # SP shards image rows over ``seq`` in addition to batch over
    # ``data``; every other path uses the default batch-only sharding.
    # Chunked batches carry a new leading k axis, unsharded.
    batch_spec_override = None
    if use_sp or k > 1:
        from jax.sharding import PartitionSpec as P

        sp_dims = ("data", "seq") if use_sp else ("data",)
        batch_spec_override = P(*(((None,) + sp_dims) if k > 1 else sp_dims))

    watch.lap("step_build")
    writer = MetricWriter(os.path.join(workdir, "tb")
                          if cfg.tensorboard else None)
    eval_fn = (_make_inline_eval(cfg, model, mesh)
               if cfg.eval_every_steps else None)

    # Wedged-dispatch watchdog: heartbeat fed by timer.tick() (one beat
    # per completed CHUNK — a dispatch is k steps, so the deadline
    # scales by k); a chunk past the deadline → stack dump + exit code
    # 114 for the supervising layer to re-fire (watchdog.py).
    watchdog = None
    if cfg.watchdog_deadline_s > 0:
        from ..resilience.watchdog import StepWatchdog

        on_stall = None
        if cfg.flight_recorder:
            from ..resilience.watchdog import WATCHDOG_EXIT_CODE

            def on_stall(msg):
                # The watchdog's exit-114 contract is exactly why the
                # recorder exists: snapshot the incident (guarded —
                # capture failing must not change the exit), THEN die
                # with the documented code.  Only installed with the
                # recorder armed; the default stall path is untouched.
                rec = _recorder_cell[0]
                if rec is not None:
                    rec.trigger("watchdog", msg[:200])
                    rec.stop()
                os._exit(WATCHDOG_EXIT_CODE)

        watchdog = StepWatchdog(
            cfg.watchdog_deadline_s * k,
            first_deadline_s=max(cfg.watchdog_compile_grace_s,
                                 cfg.watchdog_deadline_s * k),
            dump_dir=workdir, on_stall=on_stall,
        ).start()
    timer = StepTimer(on_tick=watchdog.beat if watchdog else None)
    last_metrics: Dict[str, float] = {}
    eval_metrics: Dict[str, float] = {}
    step = start_step
    # Opt-in telemetry sidecar: READS the objects above (stats, timer,
    # watchdog heartbeat, tracer, the live ``step``) over stdlib HTTP;
    # the loop's own behavior is identical with it on or off.  The
    # flight recorder samples the SAME registry onto disk, so it works
    # with the sidecar port off — durable history needs no socket.
    from ..utils.telemetry import (build_trainer_registry,
                                   build_trainer_telemetry)

    registry = None
    recorder = None
    eff_tport = cfg.telemetry_port if telemetry_port is None \
        else telemetry_port
    if cfg.flight_recorder or (eff_tport is not None and eff_tport >= 0):
        registry = build_trainer_registry(
            cfg, data_stats=data_stats, timer=timer, writer=writer,
            step_fn=lambda: step, tracer=tracer, health=health_monitor,
            alerts=health_alerts, capacity=capacity, slo=slo_tracker)
    if cfg.flight_recorder:
        import dataclasses as _dc

        from ..utils.flightrecorder import recorder_from_knobs

        recorder = recorder_from_knobs(
            cfg, dir_default=os.path.join(workdir, "flightrec"),
            families_fn=registry.prom_families,
            sections={
                "traces": lambda: tracer.snapshot(16),
                "alerts": lambda: (health_alerts.snapshot()
                                   if health_alerts is not None else {}),
                "slo": lambda: (slo_tracker.snapshot()
                                if slo_tracker is not None else {}),
                "capacity": lambda: (capacity.snapshot()
                                     if capacity is not None else {}),
                "health": lambda: (health_monitor.snapshot()
                                   if health_monitor is not None else {}),
                "last_metrics": lambda: dict(last_metrics),
                "config": lambda: _dc.asdict(cfg),
            },
            meta={"source": "trainer", "model": cfg.model.name,
                  "workdir": workdir})
        _recorder_cell[0] = recorder
        recorder.start()
    telemetry = build_trainer_telemetry(
        cfg, data_stats=data_stats, timer=timer, writer=writer,
        watchdog=watchdog, tracer=tracer, workdir=workdir,
        step_fn=lambda: step, port=telemetry_port,
        port_file=telemetry_port_file,
        health=health_monitor, alerts=health_alerts,
        capacity=capacity, slo=slo_tracker, registry=registry,
        recorder=recorder)
    watch.lap("telemetry")
    # A restore means this step's checkpoint already exists on disk — a
    # zero-progress run must not force-save over it (orbax raises).
    last_saved = resumed_from
    last_eval_step = -1
    stop = False
    # Cross-host stop agreement only at deterministic steps (all hosts
    # must enter the collective together); local-only checks otherwise.
    sync_every = max(1, cfg.log_every_steps)
    profile_at = -1
    if profile_dir:
        profile_at = max(start_step, min(start_step + 10, total_steps - 1))
        # The loop only visits chunk-start steps; snap the profile
        # window onto one (exact historical value at k=1).
        profile_at -= (profile_at - start_step) % k
    # Resume position in LOADER coordinates: the loader always yields
    # loader.steps_per_epoch batches per epoch regardless of any
    # cfg.steps_per_epoch accounting override, so epoch/offset math must
    # use the loader's own period or the resumed stream diverges.
    loader_spe = max(loader.steps_per_epoch, 1)
    start_epoch = start_step // loader_spe
    if start_step % loader_spe and hasattr(loader, "skip_steps"):
        # Exact mid-epoch resume: the epoch order is a pure function of
        # (seed, epoch), so re-entry is an index skip — no replayed or
        # skipped samples vs the uninterrupted run.
        loader.skip_steps(start_step % loader_spe)
    # Epoch iteration is open-ended and bounded by total_steps (which
    # encodes cfg.num_epochs × steps_per_epoch): when cfg.steps_per_epoch
    # overrides the accounting, the loader may need more or fewer passes
    # than cfg.num_epochs.
    def _process_log(at_step, metrics_host, at_epoch):
        """The log-boundary block, shared by the k=1 inline path and the
        chunked flush.  Chunked metrics leaves are (k,)-stacked; the log
        line reports the chunk's LAST step — exactly the step a k=1 loop
        would log at this boundary."""
        nonlocal last_metrics
        host = {name: float(np.asarray(v).reshape(-1)[-1])
                for name, v in metrics_host.items()}
        if (cfg.optim.skip_nonfinite and
                host.get("notfinite_count", 0.0)
                >= cfg.optim.skip_nonfinite):
            raise RuntimeError(
                f"{int(host['notfinite_count'])} consecutive "
                "non-finite gradient updates (≥ optim."
                f"skip_nonfinite={cfg.optim.skip_nonfinite}) — "
                "training has diverged; no bad update was "
                "applied, restart from the last checkpoint "
                "with a lower lr / higher loss scale")
        host["imgs_per_sec"] = timer.fetched(at_step,
                                             cfg.global_batch_size)
        host["epoch"] = at_epoch
        # Data-plane health for this logging interval:
        # data_starved_ms > 0 means the loop waited on the
        # host pipeline (docs/PERFORMANCE.md).
        host.update(data_stats.delta())
        if cfg.data.skip_budget > 0:
            # Corrupt samples tolerated so far (dataguard
            # substitution + tfdata shortfall), surfaced as
            # a counter instead of an epoch-killing raise.
            host["data_skipped"] = float(
                (data_guard.skipped if data_guard is not None
                 else 0)
                + int(getattr(loader, "skipped", 0)))
        last_metrics = host
        writer.scalars(at_step, host)
        if is_primary_process():
            log.info(
                "step %d/%d  loss=%.4f  lr=%.2e  %.1f imgs/s",
                at_step, total_steps, host.get("total", float("nan")),
                host.get("lr", float("nan")),
                host["imgs_per_sec"])
        if "on_metrics" in hooks:
            hooks["on_metrics"](at_step, host)

    # One source for the "does this boundary read state?" predicates:
    # _run_state_events acts on them, _state_event_at (the chunked
    # loop's flush-ordering decision) ORs them — adding a state-reading
    # event means adding a predicate here, and both sides follow.
    def _eval_due(at_step) -> bool:
        return eval_fn is not None and at_step % cfg.eval_every_steps == 0

    def _ckpt_due(at_step) -> bool:
        return bool(cfg.checkpoint_every_steps
                    and at_step % cfg.checkpoint_every_steps == 0)

    def _run_state_events(at_step, root=None):
        """Eval/checkpoint at a boundary — these read the CURRENT state,
        so under chunking they may only run while ``state`` still is the
        state at ``at_step`` (before the next chunk's donated dispatch
        replaces it).  ``root`` (the boundary chunk's root span when it
        is sampled) gets an eval/ckpt span per event."""
        nonlocal eval_metrics, last_eval_step, last_saved
        if _eval_due(at_step):
            with span("dsod.train.eval", root, step=at_step):
                eval_metrics = eval_fn(state)
            last_eval_step = at_step
            if recorder is not None:
                recorder.event("eval", step=at_step,
                               **{k: round(float(v), 6)
                                  for k, v in eval_metrics.items()})
            writer.scalars(at_step, {f"eval/{k}": v
                                     for k, v in eval_metrics.items()})
            if is_primary_process():
                log.info("eval @ %d: %s", at_step,
                         {k: round(v, 4) for k, v in
                          eval_metrics.items()})
            if watchdog is not None:
                # Inline eval is legitimate beat-free progress;
                # don't let a val sweep longer than the step
                # deadline read as a wedged dispatch.
                watchdog.beat(at_step, eval_metrics)
        if _ckpt_due(at_step):
            if (cfg.best_metric and eval_fn is not None
                    and last_eval_step != at_step):
                # best-k ranking must reflect THIS state, not a
                # stale measurement from an earlier step.
                eval_metrics = eval_fn(state)
                last_eval_step = at_step
            # state passed as-is: orbax's async save does the D2H
            # copy behind the next train steps (no device_get stall).
            with span("dsod.train.ckpt", root, step=at_step):
                mgr.save(at_step, state, metrics=eval_metrics or None)
            if recorder is not None:
                recorder.event("checkpoint", step=at_step)
            last_saved = at_step
            if watchdog is not None:
                watchdog.beat(at_step)

    def _state_event_at(at_step) -> bool:
        return _eval_due(at_step) or _ckpt_due(at_step)

    # Chunked (k>1) bookkeeping: the dispatched-but-not-yet-observed
    # chunk.  Its metrics fetch — the chunk's ONE host↔device sync — is
    # LAGGED one iteration: chunk n is flushed after chunk n+1 has been
    # dispatched, so the device always has work queued (run-ahead
    # preserved; through high-latency transports the dispatch gap would
    # otherwise idle the device once per chunk).  Boundaries that need
    # the post-chunk STATE (eval/checkpoint) flush synchronously before
    # the next dispatch instead — donation replaces the state.
    pending = None  # (end_step, metrics_device, epoch, root span | None)

    def _finish_chunk_trace(root, at_step):
        """Close a chunk: the data plane's spans kept since the last
        chunk closed (``PipelineStats.timed`` regions of the pipeline
        THREADS, at the time they ran) join a sampled chunk's trace,
        then its root ends.  An unsampled chunk drops them."""
        kept = data_stats.drain_spans()
        if root is None:
            return
        for name, t0, t1, attrs in kept:
            tracer.record(root.trace_id, name, t0, t1,
                          parent_id=root.span_id, attrs=attrs)
        root.end(key=("train",), step=at_step)

    def _flush_chunk(with_state: bool):
        nonlocal pending, stop
        at_step, metrics_dev, at_epoch, root = pending
        pending = None
        # The fetch cannot return before chunk `at_step` completed, so
        # it doubles as the completed-work signal — the timer/watchdog
        # beat is fed by finished device work, not by dispatch
        # (utils/timing.py).
        with span("dsod.train.flush", root):
            metrics_host = jax.device_get(metrics_dev)
        timer.tick(steps=k)
        _observe_capacity_slo(at_step - k)
        # Health observes EVERY fetched chunk (a mid-interval NaN must
        # reach the provenance counters even off the logging cadence).
        _observe_health(metrics_host)
        if "on_chunk_metrics" in hooks:
            hooks["on_chunk_metrics"](at_step, metrics_host)
        stop = _poll_stop(guard, at_step, sync_every) or stop
        if at_step % cfg.log_every_steps == 0 or at_step == total_steps:
            with span("dsod.train.log", root, step=at_step) as logged:
                _process_log(at_step, metrics_host, at_epoch)
            watch.flushed(at_step, logged.t1)
        if with_state:
            _run_state_events(at_step, root)
        _finish_chunk_trace(root, at_step)

    # End-of-previous-chunk timestamp: a sampled chunk's root span
    # starts there, so it covers the wait for its batch.  Only
    # maintained while tracing is on — sample=0 reads no clocks.
    t_prev_end = None
    try:
      with PreemptionGuard() as guard:
        for epoch in itertools.count(start_epoch):
            if step >= total_steps or stop:
                break
            loader.set_epoch(epoch)
            # Host-side periodic re-validation rides BEFORE the H2D
            # prefetch (cheap numpy pass, no device sync); off unless
            # cfg.data.validate_every > 0.
            host_batches = periodic_validate(iter(loader),
                                             cfg.data.validate_every)
            if k > 1:
                # Chunk assembly: stack k host batches along a new
                # leading axis BEFORE the H2D stage, so one transfer
                # ships a whole dispatch's worth (ring-buffer-aware —
                # see data/pipeline.py::chunk_batches).
                host_batches = chunk_batches(host_batches, k,
                                             stats=data_stats)
            # mesh= (not sharding=): each host contributes its local
            # slice of the global batch — correct on multi-host pods.
            it = prefetch_to_device(
                host_batches, size=cfg.data.prefetch_batches, mesh=mesh,
                transfer_dtype=cfg.data.transfer_dtype,
                drop_keys=("index",),
                spec=batch_spec_override,
                stats=data_stats)
            for batch in it:
                if step >= total_steps or stop:
                    break
                with span("dsod.train.step", step_num=step + 1, steps=k,
                          epoch=epoch):
                    if pending is not None and _state_event_at(pending[0]):
                        # Chunk n's eval/checkpoint must observe the
                        # state AT its boundary — flush before chunk
                        # n+1's donated dispatch replaces it.
                        _flush_chunk(with_state=True)
                        if stop:
                            break
                    # Chunk trace: the root spans the wait for the batch
                    # + dispatch (+ flush/log/ckpt/eval and the data
                    # plane's spans, recorded where they happen); None
                    # unless this chunk is sampled.
                    root = None
                    if tracer.enabled:
                        t_now = tracing.now()
                        root = tracer.begin(
                            "chunk", mint_trace_id(),
                            t0=t_prev_end if t_prev_end is not None
                            else t_now, root=True,
                            attrs={"step_first": step + 1,
                                   "step_last": step + k, "epoch": epoch})
                    train_step = train_step_at(step)
                    _maybe_record_capacity(step, train_step, state, batch)
                    if plan is not None:
                        batch = plan.maybe_poison_batch(step + 1, batch)
                    # Host-side dispatch time (the device runs async;
                    # completed-work time shows up in the flush span).
                    watch.before_dispatch()
                    with span("dsod.train.dispatch", root):
                        if step == profile_at:
                            with profile_window(profile_dir):
                                state, metrics = train_step(state, batch)
                                jax.block_until_ready(metrics["total"])
                        else:
                            state, metrics = train_step(state, batch)
                    watch.after_dispatch()
                    step += k
                    if k > 1:
                        # Lagged flush: observe chunk n only after chunk
                        # n+1 is in flight, so the device never sits
                        # idle across the host's fetch + bookkeeping +
                        # dispatch gap (see _flush_chunk).
                        if pending is not None:
                            _flush_chunk(with_state=False)
                        pending = (step, metrics, epoch, root)
                        if tracer.enabled:
                            t_prev_end = tracing.now()
                        continue
                    # ---- k == 1: the historical per-step path.
                    if plan is not None:
                        # Stall BEFORE the heartbeat: to the watchdog
                        # this step is still in flight, like a wedged
                        # dispatch.
                        plan.maybe_stall(step)
                    timer.tick()
                    _observe_capacity_slo(step - 1)
                    if plan is not None:
                        plan.maybe_sigterm(step)
                    stop = _poll_stop(guard, step, sync_every)
                    if step % cfg.log_every_steps == 0 or step == total_steps:
                        # ONE batched device_get for the whole metric
                        # dict — not a blocking float(v) per scalar
                        # (each paid a full host↔device round trip on
                        # remote transports).
                        with span("dsod.train.flush", root):
                            metrics_host = jax.device_get(metrics)
                        _observe_health(metrics_host)
                        with span("dsod.train.log", root,
                                  step=step) as logged:
                            _process_log(step, metrics_host, epoch)
                        watch.flushed(step, logged.t1)
                    _run_state_events(step, root)
                    _finish_chunk_trace(root, step)
                    if tracer.enabled:
                        t_prev_end = tracing.now()
            if step >= total_steps or stop:
                break
        if pending is not None:
            # The run's last chunk: nothing was dispatched after it, so
            # ``state`` is still its boundary state — flush with state
            # events before wind-down.
            _flush_chunk(with_state=True)
        if stop and recorder is not None:
            # Preemption (SIGTERM/SIGINT via the guard): the graceful
            # cousin of the replica SIGKILL — bundle the final window
            # before the wind-down checkpoint.
            recorder.event("preemption_stop", step=step)
            recorder.trigger("sigterm", "preemption guard stop")
        if watchdog is not None:
            # Training is over: the final eval/force-save/close below is
            # legitimate wind-down, not a wedged step.
            watchdog.stop()
        if step != last_saved:
            if (cfg.best_metric and eval_fn is not None
                    and last_eval_step != step):
                # Rank the final checkpoint with fresh measurements too.
                eval_metrics = eval_fn(state)
                last_eval_step = step
            mgr.save(step, state, metrics=eval_metrics or None, force=True)
    finally:
        watch.close()
        if recorder is not None:
            import sys as _sys

            exc = _sys.exc_info()[1]
            if exc is not None:
                # A crashing fit (divergence RuntimeError, restore
                # failure, ...) bundles its last window on the way out
                # — the supervisor's rollback decision is then
                # post-mortemable from disk.
                recorder.trigger(
                    "train_crash",
                    f"{type(exc).__name__}: {exc}"[:200])
            recorder.stop()
        if telemetry is not None:
            telemetry.stop()
        if watchdog is not None:
            # Idempotent; also covers the exception paths, so the daemon
            # can never outlive fit() and 114 a healthy caller later.
            watchdog.stop()
        mgr.close()
        writer.close()
    last_metrics["final_step"] = step
    last_metrics.update({f"eval_{k}": v for k, v in eval_metrics.items()})
    return last_metrics


def _make_inline_eval(cfg: ExperimentConfig, model, mesh) -> Callable:
    """Build a lightweight in-training eval: max-Fβ/MAE over the
    held-out set (``data.val_root`` when set, else the train dataset —
    meaningful for overfit smoke tests, a real val set in production).
    Batches shard over the mesh's ``data`` axis, so eval reuses every
    chip the train step uses.  Feeds CheckpointManager's best-metric
    retention (cfg.best_metric)."""
    import dataclasses

    from ..eval import run_inference
    from ..eval.inference import make_forward
    from ..parallel.mesh import eval_batch_divisor, eval_batch_sharding

    data_cfg = cfg.data
    if cfg.data.val_root:
        data_cfg = dataclasses.replace(cfg.data, root=cfg.data.val_root)
    dataset = resolve_dataset(data_cfg)

    from ..parallel.sp import (make_sp_eval_forward, sp_eval_batch_size,
                               wants_sp_eval)

    # Which slice of the val set this process sweeps.  Host-disjoint
    # slices need batches that are NOT placed on the global mesh
    # (device_put onto non-addressable devices requires the same value
    # on every process), so the sharded sweep pairs with a HOST-LOCAL
    # eval mesh; the per-host metric states psum afterwards.
    shard = (0, 1)
    if wants_sp_eval(model, mesh):
        # Sequence-parallel forward (same helper as test.py's
        # evaluate()): image rows shard over ``seq`` with ring
        # attention, matching the train step's memory profile — a
        # full-attention eval would materialise the NxN scores the SP
        # run exists to avoid.  Batch shards over ``data`` only; the
        # seq axis may span hosts, so every host sweeps the full set
        # with identical batches (the global-placement contract).
        bs = sp_eval_batch_size(mesh, cfg.global_batch_size)
        make_eval_forward = make_sp_eval_forward(model, mesh,
                                                 cfg.mesh.sp_strategy)
    elif jax.process_count() > 1 and mesh.shape.get("model", 1) == 1:
        # Disjoint 1/num_hosts slice per host, on this host's own
        # chips only — total eval work is O(1) in host count and no
        # per-batch cross-host collectives.  Requires replicated
        # variables (model axis == 1): tensor-parallel params span
        # other hosts' devices and cannot be fetched host-locally, so
        # TP falls through to the global-mesh path below.
        import numpy as _np
        from jax.sharding import Mesh as _Mesh
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as _P

        from ..parallel.mesh import host_shard

        shard = host_shard()
        local = jax.local_devices()
        local_sharding = NamedSharding(
            _Mesh(_np.asarray(local), ("data",)), _P("data"))
        forward = make_forward(model)
        bs = max(1, cfg.global_batch_size // (len(local) *
                                              jax.process_count())
                 ) * len(local)

        def make_eval_forward(variables):
            # Off the global mesh first: arrays committed to a mesh
            # spanning other hosts' devices cannot join a host-local
            # computation (replicated arrays fetch locally for free).
            variables = jax.device_get(variables)
            return lambda b: forward(
                variables, jax.device_put(b, local_sharding))
    else:
        # jit once with the variables as an argument: re-invoking eval
        # does NOT retrace (same shapes), unlike a fresh closure per
        # call.  Batch dim over the flattened (data, seq) axes.
        forward = make_forward(model)
        div = eval_batch_divisor(mesh)
        bs = max(1, cfg.global_batch_size // div) * div

        def make_eval_forward(variables):
            return lambda b: forward(
                variables, jax.device_put(b, eval_batch_sharding(mesh)))

    def eval_fn(state) -> Dict[str, float]:
        from ..metrics.aggregator import results_from_state

        fwd = make_eval_forward(state.eval_variables())
        # Each host sweeps a DISJOINT 1/num_hosts slice of the val set
        # (not every host duplicating the full sweep), accumulating the
        # psum-able FBetaState inside jit at eval resolution; shard
        # states then sum across processes, so every host still
        # finalises identical metrics — best-k checkpoint ranking stays
        # consistent while total eval work is O(1) in host count.
        fstate = run_inference(
            fwd,
            dataset,
            batch_size=bs,
            use_depth=cfg.data.use_depth,
            compute_structure=False,
            device_metrics=True,
            shard=shard,
            return_state=True,
        )
        if shard[1] > 1:
            from jax.experimental import multihost_utils

            gathered = multihost_utils.process_allgather(fstate)
            fstate = jax.tree_util.tree_map(lambda x: x.sum(axis=0),
                                            gathered)
        return {k: v for k, v in results_from_state(fstate).items()
                if isinstance(v, float)}

    return eval_fn


class _HostWatch:
    """``fit()``'s two readers of the host-clock sink
    (``utils/tracing.py``), both log lines and nothing more.

    ``setup:`` — once, after the ``SETUP_TICKS``-th logging boundary or
    at the run's end: the sibling ``dsod.setup.*`` phases from the
    package's import to that boundary, and what JAX's compile events
    (``utils/platform.py::CompileStats``) say of them.  The watch opens
    ``dsod.setup.build`` when it is made (``fit()``'s entry), swaps it
    for ``dsod.setup.first_step`` around the first call of the step,
    and records one candidate ``dsod.setup.warmup`` per boundary: the
    caller (the benchmark's window) knows which one opened ITS clock.

    ``stall:`` — for a logging interval that took over ``STALL_RATIO``
    times the median of the intervals since set-up: what the loop and
    the data plane's threads spent under each span name in it, the
    CPU seconds of the process and of this thread (a descheduled or
    blocked process reads wall >> CPU), the garbage collector's work
    and the compile events that ended in it.  A steady run logs none.
    """

    SETUP_TICKS = 8
    STALL_RATIO = 1.5
    MIN_HISTORY = 2

    def __init__(self, log):
        from .. import T_IMPORT
        from ..utils.platform import CompileStats

        self._log = log
        self._compiles = CompileStats()
        self._mark = self._compiles.mark()
        tracing.reset_setup()
        tracing.record_setup("dsod.setup.before_fit", T_IMPORT,
                             tracing.now())
        self._open = tracing.span("dsod.setup.build").__enter__()
        self._lap = self._open.t0
        self._first_step_end = None
        self._ticks = 0
        self._setup_logged = False
        self._walls = collections.deque(maxlen=64)
        self._last = None  # the open interval's start: see _snapshot
        self._gc_n, self._gc_s, self._gc_t0 = 0, 0.0, None
        gc.callbacks.append(self._on_gc)

    # -- set-up ---------------------------------------------------------

    def lap(self, name: str) -> None:
        """A child of ``dsod.setup.build``, from the last lap (or
        ``fit()``'s entry) to now: the laps are siblings that touch, so
        they add up to ``build``."""
        t = tracing.now()
        tracing.record_setup("dsod.setup.build." + name, self._lap, t,
                                   "dsod.setup.build")
        self._lap = t

    def before_dispatch(self) -> None:
        """The first call of the step ends ``build`` and is
        ``first_step``: trace, lowering, compile or cache load."""
        if self._open is not None:  # only the first call finds one
            self.lap("first_device_batch")  # loop entry, prefetch, H2D
            self._open.__exit__(None, None, None)
            self._open = tracing.span(
                "dsod.setup.first_step").__enter__()

    def after_dispatch(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._first_step_end, self._open = self._open.t1, None

    def _log_setup(self) -> None:
        self._setup_logged = True
        spans = tracing.setup_spans()
        top = {}
        for name, t0, t1, parent in spans:
            if parent is None:
                top[name] = t1 - t0  # warmup: the newest candidate stays
        phases = []
        for phase in ("before_fit", "build", "first_step", "warmup"):
            if "dsod.setup." + phase not in top:
                continue
            text = f"{phase} {top['dsod.setup.' + phase]:.1f}"
            kids = [f"{n.rsplit('.', 1)[-1]} {t1 - t0:.1f}"
                    for n, t0, t1, parent in spans
                    if parent == "dsod.setup." + phase]
            if phase == "before_fit":
                text += " s"
            elif phase == "warmup":
                text += f" ({self._ticks} ticks)"
            if kids:
                text += f" ({', '.join(kids)})"
            phases.append(text)
        made = self._compiles.since(self._mark)
        self._log.info(
            "setup: %s | trace %.1f lower %.1f compile %.1f (hits %d "
            "misses %d)", " | ".join(phases), made["trace"], made["lower"],
            made["compile"], made["cache_hits"], made["cache_misses"])
        # The three largest of each kind, by function: seconds x events
        # (a step traced twice reads x2).
        self._log.info("setup: largest %s", " | ".join(
            kind + " " + ", ".join(f"{name} {s:.1f} x{n}" for name, s, n
                                   in made["largest"].get(kind, ()))
            for kind in ("trace", "lower", "compile")))

    # -- intervals ------------------------------------------------------

    def _on_gc(self, phase, _info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self._gc_n += 1
            self._gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def _snapshot(self, step, t):
        return (step, t, tracing.span_totals(), time.process_time(),
                time.thread_time(), self._gc_n, self._gc_s)

    def flushed(self, step: int, t: float) -> None:
        """A logging boundary: the ``on_metrics`` hook returned at
        ``t`` (the end of its ``dsod.train.log`` span)."""
        if self._ticks < self.SETUP_TICKS:
            self._ticks += 1
            if self._first_step_end is not None:
                tracing.record_setup(
                    "dsod.setup.warmup", self._first_step_end, t)
                self._compiles.checkpoint(t)
            if self._ticks == self.SETUP_TICKS:
                self._log_setup()
                self._last = self._snapshot(step, t)
            return
        step0, t0, totals0, cpu0, thread0, gc_n0, gc_s0 = self._last
        self._last = self._snapshot(step, t)
        _, _, totals, cpu, thread, gc_n, gc_s = self._last
        wall = t - t0
        if (len(self._walls) >= self.MIN_HISTORY and wall
                > self.STALL_RATIO * statistics.median(self._walls)):
            spent = {n: s - totals0.get(n, 0.0) for n, s in totals.items()}
            made = self._compiles.between(t0, t)
            self._log.warning(
                "stall: steps %d-%d wall %.3f s (median %.3f s of %d "
                "intervals) | %s | cpu process %.3f s loop thread %.3f s "
                "| gc %d collections %.3f s | compiles %s",
                step0 + 1, step, wall, statistics.median(self._walls),
                len(self._walls),
                " ".join(f"{n} {s:.3f}" for n, s in sorted(spent.items())
                         if s >= 0.0005) or "no span",
                cpu - cpu0, thread - thread0, gc_n - gc_n0, gc_s - gc_s0,
                ", ".join(f"{kind} {name} {s:.2f} s"
                          for kind, _t, s, name in made[:6]) or "none")
        self._walls.append(wall)

    def close(self) -> None:
        """``fit()``'s ``finally``: a run shorter than the set-up ticks
        still says where its set-up went; nothing stays registered."""
        if self._open is not None:  # raised before or inside the step
            self._open.__exit__(None, None, None)
            self._open = None
        if not self._setup_logged and self._first_step_end is not None:
            self._log_setup()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
