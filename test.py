#!/usr/bin/env python
"""Test/eval entrypoint (SURVEY.md §2 C2, §3.2; [B:5] `test.py --device`).

    python test.py --config minet_r50_dp --ckpt-dir runs/minet --device tpu \
        --save-dir preds/ --data-root /data/DUTS-TE

Loads the newest checkpoint, sweeps every test set (resize → forward →
sigmoid → resize-back → PNG), and prints the metric dict (max-Fβ, MAE,
S/E-measure) as JSON.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default=None,
                   help="registered config name (default: read the "
                        "checkpoint's own config.json sidecar)")
    p.add_argument("--ckpt-dir", required=True,
                   help="directory of checkpoints written by train.py")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: newest)")
    p.add_argument("--device", default=None, choices=["tpu", "cpu", None])
    p.add_argument("--data-root", default=None,
                   help="test-set root; repeatable as name=path",
                   action="append")
    p.add_argument("--save-dir", default=None, help="write saliency PNGs here")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--no-structure", action="store_true",
                   help="skip S/E-measure (faster)")
    p.add_argument("--fast-metrics", action="store_true",
                   help="accumulate Fβ/Em/MAE on-device at the eval "
                        "resolution instead of the host-side "
                        "original-resolution convention — much faster, "
                        "slightly different numbers (PySODMetrics "
                        "scores at each image's native size)")
    p.add_argument("--tta", action="store_true",
                   help="average in the horizontally-flipped prediction "
                        "(2x forward cost)")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="PATH=VALUE",
                   help="dotted config override (repeatable)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)

    from distributed_sod_project_tpu.utils.platform import (
        enable_compilation_cache, select_platform)

    select_platform(args.device)

    import jax

    enable_compilation_cache()

    from distributed_sod_project_tpu.data import resolve_dataset
    from distributed_sod_project_tpu.eval import evaluate
    from distributed_sod_project_tpu.eval.inference import restore_for_eval

    cfg, model, state = restore_for_eval(
        args.ckpt_dir, config_name=args.config, overrides=args.overrides,
        step=args.step)

    # Named test sets: ["duts_te=/data/DUTS-TE", ...]; default config set.
    datasets = None
    if args.data_root:
        datasets = {}
        for spec in args.data_root:
            name, _, path = spec.rpartition("=")
            name = name or os.path.basename(path.rstrip("/")) or "test"
            datasets[name] = resolve_dataset(
                dataclasses.replace(cfg.data, root=path))

    from distributed_sod_project_tpu.parallel.mesh import make_mesh

    # All local chips share every eval batch (data-sharded forward).
    mesh = make_mesh(cfg.mesh) if jax.device_count() > 1 else None
    results = evaluate(cfg, state, model=model, mesh=mesh, datasets=datasets,
                       save_root=args.save_dir, batch_size=args.batch_size,
                       compute_structure=not args.no_structure,
                       tta=args.tta, device_metrics=args.fast_metrics)
    print(json.dumps(results, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
