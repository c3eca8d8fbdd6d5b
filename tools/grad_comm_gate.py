#!/usr/bin/env python
"""Gradient wire-compression quality gate — CPU-runnable, per-PR.

The rules engine's bucketed allreduce can compress the gradient wire:
``parallel.grad_compression=bf16`` casts each flat bucket to bfloat16
before the ``psum`` (half the bytes); ``int8_ef`` quantizes to int8
against a global scale with a persistent error-feedback residual
(``state.comm_residual``) carrying each replica's rounding error into
the next step (quarter the achievable bytes).  The step-time win is a
chip measurement (not measured on a chip); the QUALITY cost
is not — wire rounding is a pure function of the
model/data/optimizer, measurable on CPU at t1 time.  This tool trains
the same model from the same init on the same deterministic synthetic
batches — f32 wire vs each compressed arm — and ledgers the
trajectory divergence in
``tools/grad_comm_baseline.json``, the same discipline as
``tools/precision_gate.py`` / ``tools/hlo_guard.py``:

- every run prints ONE JSON line with the arm deltas and the delta
  against the recorded ledger;
- ``--fail-on-increase`` exits 2 when a delta exceeds its recorded
  budget by more than ``--tolerance`` (off in shared CI: the t1.sh
  posture is recorded, non-gating);
- ``--update-baseline`` re-seeds after an intentional change;
- a run whose own invariants failed (non-finite loss, exploding drift)
  NEVER seeds or updates the ledger.

Each arm ledgers under its own key: the bf16 row keeps the original
``<config>@<px>-b<batch>-k<steps>-s<seed>`` key (baseline continuity),
the int8_ef row appends ``-int8_ef``.

Ledgered quantities ("worse" is positive):

- ``delta_final_loss`` — the compressed arm's last-step training loss
  minus the f32 arm's (positive = compression slowed the descent);
- ``param_rel_drift`` — relative L2 distance between the two final
  param trees, ‖p_arm − p_f32‖ / ‖p_f32‖ (how far the trajectories
  separated, magnitude-normalised).

Usage:
    python tools/grad_comm_gate.py                      # print deltas
    python tools/grad_comm_gate.py --update-baseline    # re-seed
    python tools/grad_comm_gate.py --fail-on-increase   # gate locally
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "grad_comm_baseline.json")


def run_arm(cfg, model, mesh, batches, *, steps: int,
            grad_compression: str):
    """Train ``steps`` steps through the rules-engine DP preset with the
    given wire precision; returns (final params, per-step losses)."""
    import jax

    from distributed_sod_project_tpu.parallel.engine import (
        make_unified_train_step, seed_comm_residual)
    from distributed_sod_project_tpu.parallel.mesh import (
        global_batch_array, replicated_sharding)
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    tx, sched = build_optimizer(cfg.optim, steps)
    state = jax.device_put(
        create_train_state(jax.random.key(cfg.seed), model, tx,
                           batches[0], ema=cfg.optim.ema_decay > 0),
        replicated_sharding(mesh))
    if grad_compression == "int8_ef":
        state = seed_comm_residual(state, mesh)
    step = make_unified_train_step(
        model, cfg.loss, tx, mesh, preset="dp", schedule=sched,
        donate=False, ema_decay=cfg.optim.ema_decay,
        comm_bucket_mb=cfg.parallel.comm_bucket_mb,
        grad_compression=grad_compression)
    losses = []
    for host in batches:
        state, metrics = step(state, global_batch_array(host, mesh))
        losses.append(float(jax.device_get(metrics["total"])))
    return jax.device_get(state.params), losses


def build_report(f32, comp, arm: str = "bf16") -> dict:
    """Arm deltas + the run's own invariants.  ``invariant_failed``
    means the measurements cannot be trusted — callers must not seed or
    update the ledger from it.  ``arm`` names the compressed side in
    the report (the gated delta keys stay arm-independent so every row
    shares one budget vocabulary)."""
    import jax
    import numpy as np

    p32, l32 = f32
    pbf, lbf = comp
    reasons = []
    for label, losses in (("f32", l32), (arm, lbf)):
        if not all(math.isfinite(v) for v in losses):
            reasons.append(f"{label} loss stream not finite: {losses}")
    num = math.sqrt(sum(
        float(np.sum((np.asarray(a, np.float64)
                      - np.asarray(b, np.float64)) ** 2))
        for a, b in zip(jax.tree_util.tree_leaves(pbf),
                        jax.tree_util.tree_leaves(p32))))
    den = math.sqrt(sum(
        float(np.sum(np.asarray(a, np.float64) ** 2))
        for a in jax.tree_util.tree_leaves(p32)))
    drift = num / den if den else float("nan")
    if not math.isfinite(drift):
        reasons.append("param_rel_drift is not finite")
    elif drift > 0.5:
        # A compressed WIRE should nudge the trajectory, not replace
        # it — half the weight norm means the arm is broken, and a
        # broken arm must not become the recorded budget.
        reasons.append(f"param_rel_drift {drift:.3f} > 0.5")
    arms = {
        "final_loss_f32": round(l32[-1], 6),
        f"final_loss_{arm}": round(lbf[-1], 6),
        "delta_final_loss": round(lbf[-1] - l32[-1], 6),
        "param_rel_drift": round(drift, 6) if math.isfinite(drift)
        else drift,
    }
    return {"arms": arms, "invariant_failed": bool(reasons),
            "reasons": reasons}


_GATED = ("delta_final_loss", "param_rel_drift")


def apply_baseline(report: dict, baseline: dict, key: str, *,
                   update: bool = False, fail_on_increase: bool = False,
                   tolerance: float = 0.005):
    """Ledger bookkeeping → ``(rc, baseline, summary)`` — invariant
    failures never write (rc 1), first contact or ``update`` seeds,
    otherwise each gated delta compares against the recorded budget and
    ``fail_on_increase`` turns a breach into rc 2."""
    summary = {"metric": f"grad_comm_gate[{key}]", "arms": report["arms"]}
    if report["invariant_failed"]:
        summary["invariant_failed"] = True
        summary["reasons"] = report["reasons"]
        return 1, baseline, summary
    recorded = baseline.get(key)
    if update or recorded is None:
        baseline = dict(baseline)
        baseline[key] = report["arms"]
        summary["recorded"] = True
        return 0, baseline, summary
    rc = 0
    over = {}
    for k in _GATED:
        excess = report["arms"][k] - recorded.get(k, 0.0)
        if excess > tolerance:
            over[k] = round(excess, 6)
    if over:
        summary["over_budget"] = over
        if fail_on_increase:
            rc = 2
    summary["delta_vs_recorded"] = {
        k: round(report["arms"][k] - recorded.get(k, 0.0), 6)
        for k in _GATED}
    return rc, baseline, summary


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="minet_vgg16_ref",
                   help="registered config whose model/optimizer/loss "
                        "the gate trains")
    p.add_argument("--image-size", type=int, default=32,
                   help="square train resolution (small keeps the CPU "
                        "gate fast; the delta is a gradient-rounding "
                        "effect, not a resolution effect)")
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--steps", type=int, default=4,
                   help="train steps per arm (enough for the rounding "
                        "error to compound visibly)")
    p.add_argument("--seed", type=int, default=0,
                   help="init + data seed (part of the ledger key)")
    p.add_argument("--device", default="cpu", choices=["tpu", "cpu"],
                   help="cpu by default — the gate must run at t1 time "
                        "with no TPU window")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="PATH=VALUE", help="dotted config override")
    p.add_argument("--arm", default="both",
                   choices=["bf16", "int8_ef", "both"],
                   help="which compressed arm(s) to gate; the f32 "
                        "reference trains once either way")
    p.add_argument("--baseline", default=_BASELINE)
    p.add_argument("--update-baseline", action="store_true")
    p.add_argument("--fail-on-increase", action="store_true",
                   help="exit 2 when a delta exceeds its recorded "
                        "budget by more than --tolerance (off in "
                        "shared CI: recorded, not gating — the t1.sh "
                        "posture)")
    p.add_argument("--tolerance", type=float, default=0.005,
                   help="slack on the recorded deltas before a breach "
                        "(loss / relative-drift units)")
    args = p.parse_args(argv)

    from distributed_sod_project_tpu.utils.platform import select_platform

    select_platform(args.device)

    import numpy as np

    from distributed_sod_project_tpu.configs import (apply_overrides,
                                                     get_config)
    from distributed_sod_project_tpu.configs.base import validate_parallel
    from distributed_sod_project_tpu.models import build_model
    from distributed_sod_project_tpu.parallel import make_mesh

    hw = args.image_size
    cfg = apply_overrides(
        get_config(args.config),
        [f"data.image_size={hw},{hw}", f"seed={args.seed}",
         "parallel.engine=rules", "optim.warmup_steps=0"]
        + list(args.overrides))
    validate_parallel(cfg)
    model = build_model(cfg.model)
    mesh = make_mesh(cfg.mesh)

    rng = np.random.default_rng(args.seed)
    batches = []
    for _ in range(args.steps):
        img = rng.normal(size=(args.batch_size, hw, hw, 3)
                         ).astype(np.float32)
        batch = {"image": img,
                 "mask": (img.mean(-1, keepdims=True) > 0
                          ).astype(np.float32)}
        if cfg.data.use_depth:
            batch["depth"] = img.mean(-1, keepdims=True)
        batches.append(batch)

    arms = ["bf16", "int8_ef"] if args.arm == "both" else [args.arm]
    f32 = run_arm(cfg, model, mesh, batches, steps=args.steps,
                  grad_compression="none")

    baseline = {}
    if os.path.exists(args.baseline):
        with open(args.baseline) as f:
            baseline = json.load(f)
    base_key = (f"{cfg.name}@{hw}px-b{args.batch_size}-k{args.steps}"
                f"-s{args.seed}")
    rc = 0
    for arm in arms:
        report = build_report(
            f32, run_arm(cfg, model, mesh, batches, steps=args.steps,
                         grad_compression=arm), arm=arm)
        # bf16 keeps the pre-int8 key verbatim (ledger continuity);
        # every other arm gets its own suffixed row.
        key = base_key if arm == "bf16" else f"{base_key}-{arm}"
        arm_rc, new_baseline, summary = apply_baseline(
            report, baseline, key, update=args.update_baseline,
            fail_on_increase=args.fail_on_increase,
            tolerance=args.tolerance)
        if arm_rc == 1:
            print(f"grad_comm_gate: invariant failed — NOT seeding/"
                  f"updating baseline for {key}: {report['reasons']}",
                  file=sys.stderr)
        elif new_baseline is not baseline:
            baseline = new_baseline
            with open(args.baseline, "w") as f:
                json.dump(baseline, f, indent=2, sort_keys=True)
                f.write("\n")
        print(json.dumps(summary), flush=True)
        rc = max(rc, arm_rc)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
