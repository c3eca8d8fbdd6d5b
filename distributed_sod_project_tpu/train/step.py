"""Shared step-building blocks + the compiled eval step.

The reference's inner loop (SURVEY.md §3.1: H2D copy → cuDNN forward →
loss → backward with DDP's bucketed NCCL allreduce → SGD step) compiles
to ONE XLA program per step — built by the rules engine's unified step
builder (parallel/engine.py, the only train-step builder since the
round-18 legacy deletion).  This module keeps the pieces every preset
shares — remat policy resolution, the optimizer/EMA tail
(``apply_update``), step chunking (``chunked_step_fn``), multi-scale
resize, health metrics — plus the forward-only eval step.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .state import TrainState


def resolve_remat_policy(name: str):
    """model.remat_policy → a jax.checkpoint policy.  "none" recomputes
    everything; "dots" saves matmul/conv outputs (recompute only
    elementwise — the usual FLOPs/HBM sweet spot on the MXU);
    "dots_no_batch" saves only batch-free contractions."""
    policies = {
        "none": None,  # jax.checkpoint default: nothing saveable
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    if name not in policies:
        raise ValueError(
            f"model.remat_policy must be one of {sorted(policies)}, "
            f"got {name!r}")
    return policies[name]


def maybe_remat(fn, remat: bool, remat_policy: str):
    """The one remat wrap shared by the DP/SP/TP step builders: resolve
    the policy EAGERLY (a typo'd policy name fails at build time, even
    with remat off) and checkpoint ``fn`` when remat is on."""
    policy = resolve_remat_policy(remat_policy)
    return jax.checkpoint(fn, policy=policy) if remat else fn


def _loss_kwargs(loss_cfg) -> Dict[str, Any]:
    return dict(
        bce_w=loss_cfg.bce,
        iou_w=loss_cfg.iou,
        ssim_w=loss_cfg.ssim,
        cel_w=loss_cfg.cel,
        ssim_window=loss_cfg.ssim_window,
        fused=loss_cfg.fused_kernel,
    )


def apply_update(state: TrainState, grads, new_stats, tx, *,
                 ema_decay: float = 0.0):
    """Shared optimizer/EMA tail of every train step (DP and TP).

    The EMA blends only on micro-steps where the parameters actually
    changed — derived by comparing trees, not by counting steps, so it
    stays correct under ``optax.MultiSteps`` accumulation AND
    ``apply_if_finite`` skips (a step counter desyncs the moment one
    micro-step is rejected).  Effective per-update decay is therefore
    exactly ``ema_decay``.
    """
    updates, new_opt = tx.update(grads, state.opt_state, state.params)
    new_params = optax.apply_updates(state.params, updates)
    new_ema = state.ema_params
    if ema_decay and new_ema is not None:
        d = jnp.float32(ema_decay)
        applied = jnp.any(jnp.stack([
            jnp.any(a != b) for a, b in zip(
                jax.tree_util.tree_leaves(state.params),
                jax.tree_util.tree_leaves(new_params))]))
        new_ema = jax.tree_util.tree_map(
            lambda e, p: jnp.where(
                applied, e * d + p.astype(e.dtype) * (1.0 - d), e),
            new_ema, new_params)
    # replace() (not a fresh TrainState) so fields this tail does not
    # touch — the int8_ef comm_residual — ride through unchanged.
    return state.replace(
        step=state.step + 1,
        params=new_params,
        batch_stats=new_stats,
        opt_state=new_opt,
        ema_params=new_ema,
    )


def notfinite_count(opt_state) -> Optional[jnp.ndarray]:
    """The ``apply_if_finite`` consecutive-failure counter, when the
    optimizer is wrapped with ``optim.skip_nonfinite`` (it is the
    OUTERMOST transform, so the counter sits at the state root);
    None otherwise."""
    if hasattr(opt_state, "notfinite_count"):
        return opt_state.notfinite_count
    return None


def chunked_step_fn(step_fn, steps_per_dispatch: int, *,
                    always_scan: bool = False):
    """Fold ``steps_per_dispatch`` train steps into ONE program body:
    a ``lax.scan`` of ``step_fn`` over batches stacked along a new
    leading axis, returning the final carry and the per-step metrics
    stacked along that same axis.

    Shared by all three step builders (DP shard_map, GSPMD TP, SP) so
    the chunking transform cannot diverge between them.  With k == 1
    the step function is returned UNTOUCHED (no scan wrapper) — the
    historical per-step program replays bit-identically — unless
    ``always_scan`` asks for the degenerate 1-step scan, which exists
    for the bitwise k-equivalence suite: scan(k) vs k dispatches of
    scan(1) is the comparison XLA:CPU keeps bitwise (the plain-vs-scan
    residual is a while-body conv-canonicalization layout artifact,
    quantified in tests/test_step_chunking.py).

    Because the per-step RNG folds on ``state.step`` INSIDE ``step_fn``
    and the carry threads the real TrainState, each scan iteration is
    the exact computation the sequential dispatch would run — per-step
    dropout draws, LR schedule reads, EMA gating and the
    ``apply_if_finite`` failure counter all advance identically.
    """
    k = int(steps_per_dispatch)
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
    if k == 1 and not always_scan:
        return step_fn

    def chunk_fn(state, batches):
        return lax.scan(step_fn, state, batches, length=k)

    return chunk_fn


def chunk_batch_spec(base_spec: P) -> P:
    """Batch PartitionSpec for a stacked chunk: the new leading k axis
    is unsharded (every device runs all k steps), the original batch
    dims keep their sharding shifted one dim right."""
    return P(None, *base_spec)


def rescale_batch(batch, scale_hw):
    """On-device multi-scale resize (image/mask/depth → ``scale_hw``);
    shared by the shard_map and GSPMD steps."""
    hw = batch["image"].shape[1:3]
    if scale_hw is None or tuple(scale_hw) == tuple(hw):
        return batch
    out = dict(batch)
    for k in ("image", "mask", "depth"):
        if k in out:
            b, _, _, c = out[k].shape
            out[k] = jax.image.resize(
                out[k], (b,) + tuple(scale_hw) + (c,), "bilinear")
    return out


def maybe_health_metrics(metrics, params, grads, new_params,
                         health: bool):
    """Append the model-health numerics scalars (per-group grad norms,
    nonfinite provenance, update/weight ratio — utils/modelhealth.py)
    when ``health`` is on.  ONE helper shared by the DP/TP/SP step
    builders so the health surface cannot diverge between them; with
    the knob off the metric dict is returned untouched and the step
    program stays byte-for-byte the historical one."""
    if not health:
        return metrics
    from ..utils.modelhealth import health_step_metrics

    metrics.update(health_step_metrics(params, grads, new_params))
    return metrics


def make_eval_step(model, mesh: Mesh) -> Callable:
    """Build ``(state, batch) -> probs``: forward-only, running BN stats,
    sigmoid on the primary logit.  Output stays batch-sharded — the eval
    loop gathers per-host slices for metric accumulation."""

    def eval_fn(state: TrainState, batch):
        outs = model.apply(
            state.eval_variables(),
            batch["image"],
            batch.get("depth"),
            train=False,
        )
        return jax.nn.sigmoid(outs[0][..., 0].astype(jnp.float32))

    sharded = jax.shard_map(
        eval_fn,
        mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=P("data"),
        check_vma=False,
    )
    return jax.jit(sharded)
