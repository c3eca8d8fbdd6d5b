#!/usr/bin/env python
"""Precision-arm quality gate — CPU-runnable, per-PR (docs/SERVING.md
"Precision arms").

The serve engine can run every request through a bf16 / int8 / fp8
weight view of the f32 checkpoint (`serve/precision.py`).  Throughput
is a chip measurement (not measured on a chip), but QUALITY is
not: the arms' metric deltas vs f32 are a pure function of the weights
and the eval set, measurable on CPU at t1 time.  This tool scores each
arm against the f32 arm on a fixed eval set with the in-tree
max-Fβ / MAE metrics (`eval/inference.run_inference` → the same
aggregator `test.py` uses) and maintains a checked-in per-arm delta
ledger, `tools/precision_baseline.json` — the same discipline as
`tools/hlo_guard.py`:

- every run prints ONE JSON line with the per-arm deltas and the delta
  against the recorded ledger;
- `--fail-on-increase` exits 2 when an arm's quality delta exceeds its
  recorded budget by more than `--tolerance` (off in shared CI: the
  t1.sh posture is recorded, non-gating);
- `--update-baseline` re-seeds after an intentional change;
- a run whose own invariants failed (non-finite metrics, short eval
  set) NEVER seeds or updates the ledger — a corrupt seed would make
  every later comparison report delta 0 against garbage.

Deltas are signed so "worse" is positive for both metrics:
``delta_max_fbeta = f32 − arm`` (Fβ drop), ``delta_mae = arm − f32``
(MAE rise).

Usage:
    python tools/precision_gate.py                      # print deltas
    python tools/precision_gate.py --update-baseline    # re-seed
    python tools/precision_gate.py --fail-on-increase   # gate locally
    python tools/precision_gate.py --ckpt-dir runs/m    # gate a ckpt
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "precision_baseline.json")

# The two ledger metrics (ISSUE/ROADMAP contract: DUTS-TE-style
# max-Fβ + MAE).  Fβ is higher-better, MAE lower-better; _DELTA makes
# "worse" positive for both.
_DELTA = {
    "max_fbeta": lambda f32, arm: f32 - arm,
    "mae": lambda f32, arm: arm - f32,
}


def arm_metrics(model, variables, dataset, arm: str,
                batch_size: int = 4, conv_impl: str = "xla") -> dict:
    """One arm's eval metrics on ``dataset``: cast the f32 variables to
    the arm's weight view, run the arm's canonical serving forward
    through the standard metric sweep (max-Fβ/MAE; structure measures
    skipped — they are per-image host work the ledger doesn't use).
    At ``conv_impl='fused'`` the quantized arms take the fused-kernel
    view (int8/fp8 conv kernels dequantized in-VMEM) — the exact
    weights the serve engine would run, so the budget covers the
    kernel's dequant path, not just the dense one."""
    from distributed_sod_project_tpu.eval.inference import run_inference
    from distributed_sod_project_tpu.serve.precision import (
        QUANT_ARMS, cast_variables, fused_conv_cast_variables,
        make_precision_forward)

    fwd = make_precision_forward(model, arm, conv_impl=conv_impl)
    if conv_impl == "fused" and arm in QUANT_ARMS:
        import numpy as np

        sample = dataset[0]
        hw = np.asarray(sample["image"]).shape[:2]
        probe = {"image": np.zeros((1,) + tuple(hw) + (3,), np.float32)}
        if "depth" in sample:
            # RGB-D configs: the site-discovery trace needs the depth
            # operand.  (The metric sweep below still fails for them —
            # run_inference has never batched depth, a PRE-EXISTING
            # gate limitation independent of the conv arm.)
            probe["depth"] = np.zeros((1,) + tuple(hw) + (1,),
                                      np.float32)
        arm_vars = fused_conv_cast_variables(model, variables, arm, probe)
    else:
        arm_vars = cast_variables(variables, arm)

    def forward(batch):
        return fwd(arm_vars, batch)

    return run_inference(forward, dataset, batch_size=batch_size,
                         compute_metrics=True, compute_structure=False)


def build_report(metrics_by_arm: dict, expected_images: int) -> dict:
    """Per-arm deltas vs the f32 reference + the run's own invariants.

    ``invariant_failed`` (with reasons) means the measurements cannot
    be trusted — callers must not seed or update the ledger from it.
    """
    reasons = []
    f32 = metrics_by_arm.get("f32")
    if f32 is None:
        reasons.append("no f32 reference arm in the run")
    arms = {}
    for arm, m in metrics_by_arm.items():
        entry = {}
        for k in _DELTA:
            v = float(m.get(k, float("nan")))
            entry[k] = round(v, 6)
            if not math.isfinite(v):
                reasons.append(f"{arm}.{k} is not finite")
            if f32 is not None:
                entry[f"delta_{k}"] = round(
                    _DELTA[k](float(f32.get(k, float("nan"))), v), 6)
        n = int(m.get("num_images", 0))
        if expected_images and n != expected_images:
            reasons.append(
                f"{arm} scored {n}/{expected_images} images")
        arms[arm] = entry
    return {"arms": arms, "invariant_failed": bool(reasons),
            "reasons": reasons}


def apply_baseline(report: dict, baseline: dict, key: str, *,
                   update: bool = False, fail_on_increase: bool = False,
                   tolerance: float = 0.003, seed_if_missing: bool = True):
    """Ledger bookkeeping → ``(rc, baseline, summary)``.

    - invariant-failed runs never write (rc 1);
    - first contact (or ``update``) seeds ``baseline[key]`` with the
      full per-arm entry (rc 0, ``recorded`` flagged) — unless
      ``seed_if_missing=False`` (checkpoint runs: their keys are as
      transient as the checkpoint dir, and a checked-in ledger must not
      accrete them implicitly), in which case an unrecorded key just
      reports ``unrecorded``;
    - otherwise each arm's quality deltas compare against the recorded
      budget; ``fail_on_increase`` turns a breach (> recorded +
      ``tolerance`` on either delta) into rc 2.  Arms the record has
      never seen are reported ``unrecorded`` and never gate.
    """
    summary = {"metric": f"precision_gate[{key}]",
               "arms": report["arms"]}
    if report["invariant_failed"]:
        summary["invariant_failed"] = True
        summary["reasons"] = report["reasons"]
        return 1, baseline, summary
    recorded = baseline.get(key)
    if recorded is None and not (update or seed_if_missing):
        summary["unrecorded"] = True
        return 0, baseline, summary
    if update or recorded is None:
        baseline = dict(baseline)
        baseline[key] = report["arms"]
        summary["recorded"] = True
        return 0, baseline, summary
    rc = 0
    over = {}
    unrecorded = []
    for arm, entry in report["arms"].items():
        if arm == "f32":
            continue
        rec = recorded.get(arm)
        if rec is None:
            unrecorded.append(arm)
            continue
        for k in _DELTA:
            dk = f"delta_{k}"
            excess = entry.get(dk, 0.0) - rec.get(dk, 0.0)
            if excess > tolerance:
                over[f"{arm}.{dk}"] = round(excess, 6)
    if over:
        summary["over_budget"] = over
        if fail_on_increase:
            rc = 2
    if unrecorded:
        summary["unrecorded_arms"] = unrecorded
    summary["delta_vs_recorded"] = {
        arm: {f"delta_{k}": round(
            entry.get(f"delta_{k}", 0.0)
            - recorded.get(arm, {}).get(f"delta_{k}", 0.0), 6)
            for k in _DELTA}
        for arm, entry in report["arms"].items() if arm != "f32"
    }
    return rc, baseline, summary


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="minet_vgg16_ref",
                   help="registered config (ignored with --ckpt-dir "
                        "unless the sidecar is missing)")
    p.add_argument("--ckpt-dir", default=None,
                   help="gate a trained checkpoint instead of the "
                        "random-init posture (config sidecar aware)")
    p.add_argument("--image-size", type=int, default=64,
                   help="square eval resolution (small keeps the CPU "
                        "gate fast; the delta is a weight-rounding "
                        "effect, not a resolution effect)")
    p.add_argument("--num-images", type=int, default=12,
                   help="fixed synthetic eval set size (deterministic "
                        "per (seed, index) — every box scores the same "
                        "pixels)")
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--arms", default="bf16,int8",
                   help="comma-separated arms to score vs f32")
    p.add_argument("--seed", type=int, default=0,
                   help="random-init weight seed (part of the ledger "
                        "key: different weights = different deltas)")
    p.add_argument("--device", default="cpu", choices=["tpu", "cpu"],
                   help="cpu by default — the gate must run at t1 time "
                        "with no TPU window")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="PATH=VALUE", help="dotted config override")
    p.add_argument("--baseline", default=_BASELINE)
    p.add_argument("--update-baseline", action="store_true")
    p.add_argument("--fail-on-increase", action="store_true",
                   help="exit 2 when an arm exceeds its recorded "
                        "quality budget by more than --tolerance (off "
                        "in shared CI: recorded, not gating — the "
                        "t1.sh posture)")
    p.add_argument("--tolerance", type=float, default=0.003,
                   help="slack on the recorded delta before a breach "
                        "(metric units; covers CPU ulp noise)")
    args = p.parse_args(argv)

    from distributed_sod_project_tpu.utils.platform import select_platform

    select_platform(args.device)

    import jax
    import numpy as np

    from distributed_sod_project_tpu.configs import (apply_overrides,
                                                     get_config)
    from distributed_sod_project_tpu.data.folder import resolve_dataset
    from distributed_sod_project_tpu.serve.precision import validate_arms

    hw = args.image_size
    if args.ckpt_dir:
        from distributed_sod_project_tpu.eval.inference import \
            restore_for_eval

        cfg, model, state = restore_for_eval(
            args.ckpt_dir, config_name=None,  # sidecar: self-describing
            overrides=[f"data.image_size={hw},{hw}"] + list(args.overrides))
        variables = state.eval_variables()
    else:
        from distributed_sod_project_tpu.models import build_model
        from distributed_sod_project_tpu.train import (build_optimizer,
                                                       create_train_state)

        cfg = apply_overrides(
            get_config(args.config),
            [f"data.image_size={hw},{hw}", f"seed={args.seed}"]
            + list(args.overrides))
        model = build_model(cfg.model)
        tx, _ = build_optimizer(cfg.optim, 1)
        probe = {"image": np.zeros((1, hw, hw, 3), np.float32)}
        if cfg.data.use_depth:
            probe["depth"] = np.zeros((1, hw, hw, 1), np.float32)
        state = create_train_state(jax.random.key(cfg.seed), model, tx,
                                   probe, ema=cfg.optim.ema_decay > 0)
        variables = state.eval_variables()

    arms = [a.strip() for a in args.arms.split(",") if a.strip()]
    # Loudly reject unknown/unsupported arms up front (validate_arms
    # wants the set ordered + containing a default; f32 is ours).
    validate_arms(["f32"] + arms, "f32")

    import dataclasses

    data_cfg = dataclasses.replace(
        cfg.data, dataset="synthetic", root=None,
        synthetic_size=args.num_images, image_size=(hw, hw))
    dataset = resolve_dataset(data_cfg)

    metrics = {}
    for arm in ["f32"] + [a for a in arms if a != "f32"]:
        metrics[arm] = arm_metrics(model, variables, dataset, arm,
                                   batch_size=args.batch_size,
                                   conv_impl=cfg.model.conv_impl)
    report = build_report(metrics, expected_images=args.num_images)

    baseline = {}
    if os.path.exists(args.baseline):
        with open(args.baseline) as f:
            baseline = json.load(f)
    if args.ckpt_dir:
        # Key carries the checkpoint's identity (dir name + step), and
        # checkpoint runs never auto-seed the checked-in ledger — two
        # different checkpoints must not gate against each other's
        # budgets, and transient run dirs must not accrete keys.
        # --update-baseline still records one deliberately.
        ckpt_name = os.path.basename(os.path.normpath(args.ckpt_dir))
        step = int(jax.device_get(state.step))
        tag = f"ckpt-{ckpt_name}-step{step}"
    else:
        tag = f"s{args.seed}"
    if cfg.model.conv_impl != "xla":
        # Fused-arm rows are their own budgets: the kernel's in-VMEM
        # dequant path must never gate against (or silently reseed)
        # the dense arm's recorded deltas.
        tag += f"-conv_{cfg.model.conv_impl}"
    key = f"{cfg.name}@{hw}px-n{args.num_images}-{tag}"
    rc, new_baseline, summary = apply_baseline(
        report, baseline, key, update=args.update_baseline,
        fail_on_increase=args.fail_on_increase, tolerance=args.tolerance,
        seed_if_missing=not args.ckpt_dir)
    if rc == 1:
        print(f"precision_gate: invariant failed — NOT seeding/updating "
              f"baseline for {key}: {report['reasons']}", file=sys.stderr)
    elif new_baseline is not baseline:
        with open(args.baseline, "w") as f:
            json.dump(new_baseline, f, indent=2, sort_keys=True)
            f.write("\n")
    print(json.dumps(summary), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
