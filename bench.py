#!/usr/bin/env python
"""Throughput benchmark — prints ONE JSON line for the driver.

Measures the governing metric (BASELINE.json:2): images/sec/chip for the
flagship data-parallel train step (MINet-ResNet50, 320×320, bf16), the
TPU analogue of the reference's 8×V100 DDP throughput posture.

``vs_baseline`` is self-relative: the reference's V100 number was
unobtainable (BASELINE.md), so the first recorded run seeds
``bench_baseline.json`` and later runs report the ratio against it.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from functools import partial

# Per-config --batch-per-chip defaults.  128 is the flagship's measured
# v5e throughput optimum (batch sweep in BASELINE.md); the heavier zoo
# members (two-stream hdfnet, 89M-param basnet, 7-output u2net) were
# measured at 32 and risk HBM OOM at 128.  tools/bench_zoo.py reuses
# this table so sweeps and direct runs agree.
PER_CONFIG_BATCH = {"minet_r50_dp": 128}
DEFAULT_BATCH = 32

# Env vars that change the COMPILED PROGRAM (and therefore throughput):
# they must be part of the baseline key, or an A/B leg run with one of
# these set seeds the canonical key with the slow variant and every
# later run reports a bogus vs_baseline (the exact failure class the
# round-2 remat fix documented — see _report()).  Kept as an explicit
# literal on purpose: tools/dsodlint.py (env-coherence) cross-checks it
# against utils/envvars.py's program_affecting rows BOTH ways, so a new
# program-affecting knob that forgets either side fails lint.
_PROGRAM_ENV_VARS = (
    "DSOD_RESIZE_IMPL",
    "DSOD_RESIZE_INTERLEAVE",
    "DSOD_FLASH_BLOCK_Q",
    "DSOD_FLASH_BLOCK_KV",
    "DSOD_STEM_IMPL",
    "DSOD_DLF_VMEM_MB",
    "DSOD_RESAMPLE_VMEM_MB",
    "DSOD_CONV_VMEM_MB",
)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="minet_r50_dp")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--batch-per-chip", type=int, default=None,
                   help="per-chip batch (default: per-config — 128 for "
                        "the flagship, its measured v5e optimum; 32 for "
                        "the heavier zoo members, which risk HBM OOM at "
                        "b128 — see PER_CONFIG_BATCH)")
    p.add_argument("--image-size", type=int, default=320)
    p.add_argument("--device", default=None, choices=["tpu", "cpu", None])
    p.add_argument("--mode", default="train",
                   choices=["train", "eval", "data", "serve"],
                   help="train: full DP step (default); eval: forward-only "
                        "sigmoid inference (the test.py hot loop); data: "
                        "host input pipeline only — no device work, batch "
                        "is --batch-per-chip as-is (select the backend "
                        "with --set data.backend=host|tfdata|grain); "
                        "serve: end-to-end HTTP serving latency — an "
                        "in-process server (random-init weights) driven "
                        "by the closed-loop load generator, --steps "
                        "requests total; reports imgs/sec plus "
                        "p50/p95/p99 ms so serving latency joins the "
                        "recorded perf trajectory (docs/SERVING.md)")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="PATH=VALUE",
                   help="dotted config override, e.g. --set "
                        "loss.fused_kernel=true --set model.remat=true "
                        "(bench always times the shard_map DP step)")
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="device-side step chunking sweep arm (train "
                        "mode only): fold k train steps into one "
                        "lax.scan dispatch (train.steps_per_dispatch); "
                        "--steps then counts DISPATCHES, each k steps "
                        "on a k-stacked resident batch.  Folded into "
                        "the vs_baseline key as a --set override, so "
                        "A/B legs never contaminate the canonical "
                        "k=1 baselines")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of the timed window")
    p.add_argument("--baseline-file", default=None,
                   help="regression mode: JSON file of recorded "
                        "baselines keyed like bench_baseline.json.  "
                        "First run per key SEEDS the file; later runs "
                        "add a vs_recorded field (this run / recorded) "
                        "to the result line.  Unlike the implicit "
                        "bench_baseline.json side file, this one is "
                        "meant to be checked in (tools/bench_data.sh)")
    p.add_argument("--fail-below", type=float, default=0.0,
                   help="with --baseline-file: exit 3 when vs_recorded "
                        "falls below this ratio (0 = never gate — the "
                        "shared-CI posture; the number is still "
                        "printed and recorded)")
    args = p.parse_args(argv)
    if args.warmup < 0:
        p.error("--warmup must be >= 0")
    if args.steps < 1:
        p.error("--steps must be >= 1")
    if args.batch_per_chip is None:
        args.batch_per_chip = PER_CONFIG_BATCH.get(args.config,
                                                   DEFAULT_BATCH)
    if args.batch_per_chip < 1:
        p.error("--batch-per-chip must be >= 1")
    if args.steps_per_dispatch < 1:
        p.error("--steps-per-dispatch must be >= 1")
    if args.steps_per_dispatch > 1:
        if args.mode != "train":
            p.error("--steps-per-dispatch only applies to --mode train")
        # Route through the config override machinery so the compiled
        # program AND the vs_baseline key both carry the knob (the
        # same contamination-proofing --set and _PROGRAM_ENV_VARS get).
        args.overrides = list(args.overrides) + [
            f"steps_per_dispatch={args.steps_per_dispatch}"]
    try:
        return _run(args)
    except Exception as e:  # noqa: BLE001 — the CLI's outermost boundary
        # No chip under --device tpu (utils/platform.NoAcceleratorError),
        # OOM, a shape error, a bad flag: the traceback goes to stderr
        # for the human, the driver still gets one parseable line — an
        # error line with NO value: a failed run has no rate — and the
        # exit code is non-zero.
        import traceback

        traceback.print_exc()
        _report_error(args, f"{type(e).__name__}: {str(e)[:300]}")
        return 1


def _report_error(args, reason: str) -> None:
    print(json.dumps({
        "metric": f"{args.mode}_throughput[{args.config}@"
                  f"{args.image_size}px,{args.device or 'auto'}]",
        "error": reason,
    }), flush=True)


def _run(args):
    from distributed_sod_project_tpu.configs import apply_overrides, get_config

    hw = args.image_size

    if args.mode == "data":
        # Pure host path: never touch a jax backend (a chip belongs to
        # one process; a data bench must not take it from a trainer).
        batch = args.batch_per_chip
        cfg = get_config(args.config)
        cfg = apply_overrides(
            cfg, [f"global_batch_size={batch}",
                  f"data.image_size={hw},{hw}"] + list(args.overrides))
        _reject_non_train_chunking(args, cfg)
        dt = _bench_data(cfg, batch, args.steps, args.warmup,
                         overrides=args.overrides)
        return _report(args, batch * args.steps / dt,
                       {"platform": "host", "kind": "host", "count": 1},
                       mode=f"data[{cfg.data.backend}]")

    from distributed_sod_project_tpu.utils.platform import (
        describe_device, enable_compilation_cache, select_platform)

    select_platform(args.device)  # --device tpu with no chip raises here
    enable_compilation_cache()

    import jax
    import numpy as np

    from distributed_sod_project_tpu.parallel.mesh import (
        batch_sharding, make_mesh, replicated_sharding)
    from distributed_sod_project_tpu.train import random_init_setup

    device = describe_device()
    n_chips = device["count"]
    batch = args.batch_per_chip * n_chips

    cfg = get_config(args.config)
    cfg = apply_overrides(cfg, [f"global_batch_size={batch}"]
                          + list(args.overrides))
    _reject_non_train_chunking(args, cfg)

    if args.mode == "serve":
        return _bench_serve(args, cfg, device)

    mesh = make_mesh(cfg.mesh)
    model, tx, sched, host_batch, state = random_init_setup(cfg, batch, hw)
    if args.mode == "eval":
        # Forward-only: ship just the eval variables, not the optimizer
        # slots (3-4x the param bytes replicated onto every chip).
        from distributed_sod_project_tpu.train.state import TrainState

        state = TrainState(step=state.step, params=state.params,
                           batch_stats=state.batch_stats, opt_state=())
    state = jax.device_put(state, replicated_sharding(mesh))
    dev_batch = jax.device_put(host_batch, batch_sharding(mesh))

    # Each mode provides run_step() -> sync token, and sync() FETCHES a
    # scalar that depends on every device's shard (the train metrics
    # are pmean-replicated; eval sums the sharded output).  A host
    # fetch cannot return before the dependency chain has executed.  On
    # the v5e chip ``jax.block_until_ready`` waits just as long (three
    # basnet_ds steps: 0.7587 s against 0.7591 s to the fetch —
    # chip_smoke.py, PR 23), so either is sound there; the fetch stays
    # because it also proves the value is finite and reachable.
    if args.mode == "eval":
        from distributed_sod_project_tpu.metrics.streaming import (
            init_fbeta_state, update_fbeta_state)
        from distributed_sod_project_tpu.train.step import make_eval_step

        estep = make_eval_step(model, mesh)
        # The measured eval step is forward + DEVICE-SIDE metric
        # accumulation (the test.py --fast-metrics / inline-eval hot
        # loop), so the number includes what eval actually does.  The
        # metric state also chains every step: eval forwards are
        # independent, so without the carry the final fetch would only
        # prove the last dispatch drained.  ONE jit for forward+update:
        # one dispatch per step.
        @partial(jax.jit, donate_argnums=0)
        def eval_and_update(acc_state, s, b):
            return update_fbeta_state(acc_state, estep(s, b), b["mask"])

        acc = [init_fbeta_state()]

        def run_step():
            # Exactly ONE dispatch per step; the chained (donated) acc
            # state is the sync token.  The reductions that prove every
            # shard landed happen once, in sync(), after the loop.
            acc[0] = eval_and_update(acc[0], state, dev_batch)
            return acc[0]

        def sync(a):
            return float(a.mae_sum + a.f_curve_sum.sum())
    else:
        # From the RESOLVED config, not the flag: --set
        # steps_per_dispatch=k (or a config default) must count images
        # and skip the cost model exactly like --steps-per-dispatch k.
        k_spd = cfg.steps_per_dispatch
        # The unified rules engine (the only engine): same preset
        # routing as fit() (DP / FSDP / GSPMD+ZeRO / SP), so --set
        # parallel.preset=fsdp / parallel.zero=1 /
        # parallel.comm_bucket_mb=N / parallel.grad_compression=int8_ef
        # sweep arms bench the REAL program.  Re-places the state
        # (ZeRO/FSDP shard buffers over `data`); the comm plan is
        # priced offline by tools/roofline.py --comm, not here.
        from distributed_sod_project_tpu.parallel.engine import (
            prepare_train_step)

        state, step, _plan = prepare_train_step(
            cfg, model, tx, mesh, sched, state,
            steps_per_dispatch=k_spd)
        if k_spd > 1:
            # One resident k-stacked batch; each timed "step" below is
            # one dispatch = k train steps (the A/B isolates dispatch
            # overhead: device work per image is identical).  The spec
            # comes from the builders' single source of truth so the
            # bench can never place chunks differently than fit does.
            from jax.sharding import NamedSharding

            from distributed_sod_project_tpu.parallel.mesh import (
                batch_spec)
            from distributed_sod_project_tpu.train.step import (
                chunk_batch_spec)

            chunk_host = {key: np.stack([v] * k_spd)
                          for key, v in host_batch.items()}
            dev_batch = jax.device_put(
                chunk_host,
                NamedSharding(mesh, chunk_batch_spec(batch_spec())))
        carry = [state]

        def run_step():
            carry[0], metrics = step(carry[0], dev_batch)
            return metrics["total"]

        def sync(total):
            # Chunked: (k,) per-step losses — reduce so the fetch
            # depends on every step; scalar at k=1 as before.
            return float(np.asarray(jax.device_get(total)).sum())

    for _ in range(args.warmup):  # compile + stabilise
        token = run_step()
    if args.warmup:  # --warmup 0 is honored: compile lands in the timed
        sync(token)  # window, which is what a cold-start bench wants

    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    try:
        t0 = time.perf_counter()
        for _ in range(args.steps):
            token = run_step()
        sync(token)
        dt = time.perf_counter() - t0
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()

    if args.mode == "eval":
        # ADVICE r3: lower with the ACTUAL final acc object — a fresh
        # host-side init_fbeta_state() has different sharding/commit-
        # ment, which can miss the executable cache and trigger a
        # (post-timing, but slow on device backends) second compile.
        extra = _cost_fields(eval_and_update, dt / args.steps,
                             acc[0], state, dev_batch)
        k_spd = 1
    elif k_spd > 1:
        # XLA's cost model is ambiguous about while-loop trip counts —
        # a mislabeled per-step GFLOPs/MFU is worse than none.
        extra = {"steps_per_dispatch": k_spd}
    else:
        extra = _cost_fields(step, dt / args.steps, state, dev_batch)
    return _report(args, batch * args.steps * k_spd / dt, device, **extra)


def _cost_fields(jitted, dt_step: float, *call_args) -> dict:
    """FLOPs/step from XLA's cost model → ``gflops_per_step_chip``
    (cost_analysis is per-device under jit-of-shard_map, so the value
    is already the per-chip share) and, on a TPU, ``mfu`` against the
    chip's published bf16 peak (utils/chips.py — an unknown TPU kind
    is an error, a CPU run reports FLOPs only).

    ``lower().compile()`` hits the in-process executable cache (the
    step just ran), so this is bookkeeping, not a second compile.
    """
    from distributed_sod_project_tpu.utils.chips import local_chip_peaks

    cost = jitted.lower(*call_args).compile().cost_analysis()
    flops = float((cost or {}).get("flops", 0.0))
    if flops <= 0 or dt_step <= 0:
        return {}
    out = {"gflops_per_step_chip": round(flops / 1e9, 1)}
    peaks = local_chip_peaks()
    if peaks is not None:
        out["mfu"] = round(flops / dt_step / peaks.flops_bf16, 4)
    return out


def _reject_non_train_chunking(args, cfg) -> None:
    """Mirror of the --steps-per-dispatch flag guard for the --set
    spelling: a non-train mode never builds the chunked program, so a
    steps_per_dispatch override there would record an "A/B leg" under
    a distinct baseline key that measured the ordinary program —
    exactly the key contamination the tagging exists to prevent."""
    if args.mode != "train" and cfg.steps_per_dispatch > 1:
        raise SystemExit(
            f"--set steps_per_dispatch={cfg.steps_per_dispatch} only "
            f"applies to --mode train (mode {args.mode!r} runs the "
            "ordinary program; the override would tag a baseline key "
            "without changing what was measured)")


def _bench_serve(args, cfg, device) -> int:
    """--mode serve: stand up the real HTTP serving stack in-process
    (random-init weights — the bench measures the serving machinery,
    not a particular checkpoint) and drive it with the closed-loop
    generator.  The headline value is served imgs/sec; p50/p95/p99 ride
    along so --baseline-file regression-tracks the latency tail too.

    Single-device on purpose: the engine dispatches to the default
    device, so per-chip == total and the baseline key's platform tag
    still distinguishes cpu/tpu runs.
    """
    import threading

    from distributed_sod_project_tpu.configs import apply_overrides
    from distributed_sod_project_tpu.serve.engine import InferenceEngine
    from distributed_sod_project_tpu.serve.loadgen import run_loadgen
    from distributed_sod_project_tpu.serve.server import make_server

    hw = args.image_size
    cfg = apply_overrides(cfg, [f"data.image_size={hw},{hw}"])
    engine = InferenceEngine.from_random_init(cfg).start()
    srv = make_server(engine, "127.0.0.1", 0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    concurrency = max(cfg.serve.batch_buckets)
    try:
        if args.warmup:  # engine.start() AOT-warmed the programs; this
            run_loadgen(url, mode="closed", concurrency=1,  # warms HTTP
                        requests=args.warmup, sizes=((hw, hw),), seed=0)
        res = run_loadgen(url, mode="closed", concurrency=concurrency,
                          requests=args.steps, sizes=((hw, hw),), seed=1)
    finally:
        srv.shutdown()
        srv.server_close()
        engine.stop()
    if not res["ok"]:
        raise RuntimeError(f"serve bench completed 0/{args.steps} "
                           "requests")
    extra = {k: res[k] for k in ("p50_ms", "p95_ms", "p99_ms")}
    extra.update(shed=res["shed"], expired=res["expired"],
                 concurrency=concurrency,
                 precision=cfg.serve.precision)
    return _report(args, res["ok"] / res["elapsed_s"],
                   dict(device, count=1), mode="serve", **extra)


def _bench_data(cfg, batch: int, steps: int, warmup: int,
                overrides=()) -> float:
    """Time the host input pipeline alone: seconds to produce ``steps``
    batches (epochs cycled as needed) on the configured backend.

    Use enough --steps to overwhelm the backend's prefetch depth:
    deep-prefetch backends (grain) serve short runs from buffers filled
    during warmup — measured in-sandbox: grain "203 img/s" over 10
    steps collapsed to its true ~5 img/s sustained rate at 40 steps,
    while the host backend reported the same number at both lengths.
    """
    import itertools

    from distributed_sod_project_tpu.data import resolve_dataset
    from distributed_sod_project_tpu.data.tfdata import make_loader

    dataset = resolve_dataset(cfg.data)
    # The bench consumes each batch immediately, so UNLESS the user
    # said otherwise it runs the zero-copy posture the train loop uses
    # on hardware: recycled ring buffers.  An explicit --set
    # data.ring_buffers=<n> (including 0 = off, the A/B leg for the
    # allocating path) always wins.
    ring = cfg.data.ring_buffers
    user_set_ring = any(o.split("=", 1)[0].strip() == "data.ring_buffers"
                        for o in overrides)
    if not user_set_ring and ring == 0:
        ring = cfg.data.lookahead + 3
    loader = make_loader(
        dataset, cfg.data, global_batch_size=batch, shard_id=0,
        num_shards=1, shuffle=True, seed=cfg.seed, hflip=cfg.data.hflip,
        rotate_degrees=cfg.data.rotate_degrees,
        color_jitter=cfg.data.color_jitter,
        num_workers=cfg.data.num_workers,
        ring_buffers=ring)

    if loader.steps_per_epoch <= 0:
        raise SystemExit(
            f"global batch {batch} > dataset size {len(dataset)}: the "
            "loader yields zero batches per epoch (drop_last) — shrink "
            "--batch-per-chip or grow data.synthetic_size")

    def batches():
        for epoch in itertools.count():
            loader.set_epoch(epoch)
            yield from iter(loader)

    it = batches()
    for _ in range(warmup):
        next(it)
    t0 = time.perf_counter()
    for _ in range(steps):
        next(it)
    return time.perf_counter() - t0


def _report(args, imgs_per_sec: float, device: dict,
            mode: str | None = None, **extra) -> int:
    """One JSON line + self-relative baseline tracking (the first run
    per (config, size, platform, mode) seeds ``bench_baseline.json``).
    ``device`` is ``describe_device()``'s dict and rides the line: a
    rate is a number ABOUT a device, and ``--device cpu`` must never
    read as a chip's.  Returns the process exit code: 0, or 3 when
    --baseline-file + --fail-below flags a regression."""
    mode = mode or args.mode
    platform, n_chips = device["platform"], device["count"]
    per_chip = imgs_per_sec / n_chips
    from distributed_sod_project_tpu.utils import envvars

    base_path = (envvars.read("DSOD_BENCH_BASELINE")
                 or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "bench_baseline.json"))
    # Batch, --set overrides, AND program-affecting env vars are in the
    # key: throughput scales with batch (dispatch-latency amortisation)
    # and the others change the compiled program (remat, kernels,
    # resize impl, flash blocks), so baselines only compare like with
    # like.  (Round-2 lesson: a remat-on run seeded b64's key and every
    # remat-off run then reported a bogus vs_baseline; the same class
    # of contamination applied to DSOD_RESIZE_IMPL=xla A/B legs.)
    key = (f"{args.config}-{args.image_size}-b{args.batch_per_chip}"
           f"-{platform}")
    if args.overrides:
        key += "-" + ",".join(sorted(args.overrides))
    env_tags = []
    for k in _PROGRAM_ENV_VARS:
        v = envvars.read(k)
        if not v:
            continue
        if k == "DSOD_STEM_IMPL" and v == "s2d" and args.image_size % 2:
            # ADVICE r3: odd H/W forces the plain-stem fallback
            # (models/backbones/resnet.py) — tag the key with what
            # actually ran so an s2d A/B leg at an odd size never
            # records mislabeled numbers.
            v = "s2d[plain-stem-fallback]"
        env_tags.append(f"{k}={v}")
    if env_tags:
        key += "-env:" + ",".join(sorted(env_tags))
    if mode != "train":
        key += f"-{mode}"
    base = {}
    if os.path.exists(base_path):
        with open(base_path) as f:
            base = json.load(f)
    if key not in base:
        base[key] = per_chip
        with open(base_path, "w") as f:
            json.dump(base, f, indent=2)
    vs = per_chip / base[key] if base[key] else 1.0

    rc = 0
    if args.baseline_file:
        # Regression mode against a CHECKED-IN baseline: seed on first
        # contact, compare forever after (tools/bench_data.sh).
        recorded = {}
        if os.path.exists(args.baseline_file):
            with open(args.baseline_file) as f:
                recorded = json.load(f)
        if key in recorded and recorded[key]:
            extra["vs_recorded"] = round(per_chip / recorded[key], 3)
            if args.fail_below and extra["vs_recorded"] < args.fail_below:
                rc = 3
        else:
            recorded[key] = round(per_chip, 2)
            with open(args.baseline_file, "w") as f:
                json.dump(recorded, f, indent=2, sort_keys=True)
                f.write("\n")
            extra["recorded"] = True

    line = {
        "metric": f"{mode}_throughput[{args.config}@"
                  f"{args.image_size}px,{platform}x{n_chips}]",
        "value": round(per_chip, 2),
        "unit": ("images/sec/chip" if platform == "tpu"
                 else f"images/sec/{platform}"),
        "device": device,
        "vs_baseline": round(vs, 3),
        **extra,
    }
    print(json.dumps(line), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
