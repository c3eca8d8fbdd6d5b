#!/usr/bin/env python
"""Train entrypoint (SURVEY.md §2 C1, §3.1; [B:5] `train.py --device`).

    python train.py --config minet_r50_dp --device tpu
    python train.py --config u2net_ds --data-root /data/DUTS-TR --resume

Multi-host pods: launch the same command on every host (with
``--distributed`` to run ``jax.distributed.initialize``); the mesh spans
all chips — the TPU replacement for torchrun + init_process_group
(SURVEY.md §3.5).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default=None, help="registered config name")
    p.add_argument("--list-configs", action="store_true",
                   help="print registered configs and exit")
    p.add_argument("--device", default=None, choices=["tpu", "cpu", None],
                   help="force a JAX platform (default: auto)")
    p.add_argument("--workdir", default=None, help="checkpoint/log dir")
    p.add_argument("--resume", action="store_true",
                   help="resume from the newest checkpoint in workdir")
    p.add_argument("--data-root", default=None,
                   help="dataset root (overrides config; falls back to "
                        "synthetic data when absent)")
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--max-steps", type=int, default=None,
                   help="truncate training (smoke runs)")
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--distributed", action="store_true",
                   help="multi-host: run jax.distributed.initialize()")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="PATH=VALUE",
                   help="dotted config override, e.g. --set optim.lr=0.01 "
                        "--set data.image_size=256,256 (repeatable)")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of a post-warmup "
                        "step window into this directory")
    p.add_argument("--telemetry-port", type=int, default=None,
                   help="start the trainer telemetry sidecar on this "
                        "port (0 = ephemeral): /metrics, /healthz, "
                        "/debug/traces, /debug/profile?seconds=N "
                        "(docs/OBSERVABILITY.md; overrides "
                        "cfg.telemetry_port)")
    p.add_argument("--telemetry-port-file", default=None,
                   help="write the sidecar's bound port here once "
                        "listening (atomic, for scripts)")
    p.add_argument("--eval-every", type=int, default=None,
                   help="run held-out eval every N steps (overrides "
                        "config eval_every_steps)")
    p.add_argument("--debug-nans", action="store_true",
                   help="jax.config debug_nans: every compiled step "
                        "re-checks for NaN production and fails loudly "
                        "at the producing op (slow — debugging only; "
                        "for production guards use optim.skip_nonfinite)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)

    if args.list_configs:
        from distributed_sod_project_tpu.configs import get_config, list_configs

        for name in list_configs():
            cfg = get_config(name)
            print(f"{name:18s} model={cfg.model.name}/{cfg.model.backbone}"
                  f"  batch={cfg.global_batch_size}"
                  f"  data={cfg.data.dataset}")
        return 0
    if not args.config:
        raise SystemExit("--config is required (see --list-configs)")

    from distributed_sod_project_tpu.utils.platform import (
        CompileStats, describe_device, enable_compilation_cache,
        pin_platform, verify_platform)

    pin_platform(args.device)

    import jax

    if args.debug_nans:
        jax.config.update("jax_debug_nans", True)
    # Order matters: initialize() refuses to run once a backend is up,
    # and verify_platform() brings one up.
    if args.distributed:
        jax.distributed.initialize()
    verify_platform(args.device)
    compiles = CompileStats()
    cache_dir = enable_compilation_cache()
    # The imgs/s that fit() logs are rates on THIS device.
    print(json.dumps({"device": describe_device()}), flush=True)

    from distributed_sod_project_tpu.configs import apply_overrides, get_config
    from distributed_sod_project_tpu.train.loop import fit

    cfg = get_config(args.config)
    cfg = apply_overrides(cfg, args.overrides)
    if args.data_root is not None:
        cfg = cfg.replace(data=dataclasses.replace(cfg.data, root=args.data_root))
    if args.batch_size is not None:
        cfg = cfg.replace(global_batch_size=args.batch_size)
    if args.epochs is not None:
        cfg = cfg.replace(num_epochs=args.epochs)
    if args.lr is not None:
        cfg = cfg.replace(optim=dataclasses.replace(cfg.optim, lr=args.lr))
    if args.seed is not None:
        cfg = cfg.replace(seed=args.seed)
    if args.eval_every is not None:
        cfg = cfg.replace(eval_every_steps=args.eval_every)

    metrics = fit(cfg, workdir=args.workdir, resume=args.resume,
                  max_steps=args.max_steps, profile_dir=args.profile_dir,
                  telemetry_port=args.telemetry_port,
                  telemetry_port_file=args.telemetry_port_file)
    print(json.dumps({"compile": dict(compiles.as_dict(),
                                      cache_dir=cache_dir)}), flush=True)
    print({k: round(v, 4) if isinstance(v, float) else v
           for k, v in metrics.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
