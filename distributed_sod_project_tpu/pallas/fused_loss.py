"""Pallas fused BCE+IoU+CEL loss reductions (SURVEY.md §2.2).

The hybrid SOD loss needs, per side output: the stable-BCE sum and the
per-image region sums Σσ(x)·t, Σσ(x), Σt.  Left to XLA these are four
reduction trees over the same [B,H,W] logits; the kernel here computes
all four in ONE pass over VMEM-resident tiles — logits and targets are
read from HBM exactly once per level (the loss is HBM-bound, SURVEY.md
§6's governing constraint).

The backward pass is elementwise given the forward's per-image scalars
(∂BCE/∂x = σ(x)−t; ∂IoU and ∂CEL are rational functions of the saved
sums), so the custom VJP recomputes it in plain XLA where it fuses into
the backbone's gradient epilogue for free — no second kernel needed.

Gated by ``LossConfig.fused_kernel``; numerically identical (tested) to
the reference-parity losses in ``losses/``.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

_LANES = 128  # TPU lane width: the per-image sums ride one lane row.


def _sums_kernel(x_ref, t_ref, out_ref):
    """One image per grid step: [1,N/128,128] logits/targets →
    [1,1,128] sums (lane 0: BCE sum, 1: Σpt, 2: Σp, 3: Σt; rest zero).

    The image rides VMEM as (sublanes, lanes) = (N/128, 128) — Mosaic
    requires the trailing block dims to match the array (or be 8/128
    multiples), so the caller reshapes pixels into full-lane rows
    rather than one giant row.
    """
    x = x_ref[:].astype(jnp.float32)
    t = t_ref[:].astype(jnp.float32)
    bce = jnp.sum(jnp.maximum(x, 0.0) - x * t + jnp.log1p(jnp.exp(-jnp.abs(x))))
    p = jax.nn.sigmoid(x)
    inter = jnp.sum(p * t)
    psum = jnp.sum(p)
    tsum = jnp.sum(t)
    lane = lax.broadcasted_iota(jnp.int32, (1, 1, _LANES), 2)
    out = (jnp.where(lane == 0, bce, 0.0) + jnp.where(lane == 1, inter, 0.0)
           + jnp.where(lane == 2, psum, 0.0) + jnp.where(lane == 3, tsum, 0.0))
    out_ref[:] = out


def fused_loss_available(shape) -> bool:
    """True when the fused kernel can run for this logit shape here:
    pixel count a lane multiple (padding would bias the Σσ(x) region
    statistics, so off-lane sizes are rejected, not padded) and a
    backend with a Pallas path (Mosaic on TPU, interpret on CPU).
    Callers fall back to the reference losses otherwise — configs with
    ``loss.fused_kernel=true`` must keep working at odd eval sizes and
    on GPU backends."""
    n = 1
    for d in shape[1:]:
        n *= int(d)
    return n % _LANES == 0 and jax.default_backend() in ("cpu", "tpu")


@jax.named_scope("dsod.kernel.fused_loss")
def pixel_region_sums(logits: jnp.ndarray, targets: jnp.ndarray,
                      interpret: bool | None = None,
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                 jnp.ndarray]:
    """Per-image (bce_sum, Σσ(x)t, Σσ(x), Σt), each [B], in one pass.

    Accepts [B,H,W,1]/[B,H,W]/[B,N]; pixel count must be a multiple of
    128 (true for every SOD config: 320²=800·128; padded inputs would
    bias Σσ(x) and are rejected).

    ``interpret`` defaults to auto (interpret on CPU, Mosaic on TPU);
    pass False to force the Mosaic lowering, e.g. when exporting for
    platform='tpu' from a CPU host (tests do this to validate the
    hardware path without a chip).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401

    b = logits.shape[0]
    n = int(jnp.size(logits)) // b
    if n % _LANES:
        raise ValueError(f"pixel count {n} not a multiple of {_LANES}")
    rows = n // _LANES
    x = logits.reshape(b, rows, _LANES)
    t = targets.reshape(b, rows, _LANES)

    out = pl.pallas_call(
        _sums_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, rows, _LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, rows, _LANES), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, _LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, _LANES), jnp.float32),
        interpret=(jax.default_backend() == "cpu"
                   if interpret is None else interpret),
    )(x, t)
    return out[:, 0, 0], out[:, 0, 1], out[:, 0, 2], out[:, 0, 3]


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def fused_bce_iou_cel(logits, targets, bce_w: float = 1.0,
                      iou_w: float = 1.0, cel_w: float = 0.0,
                      iou_eps: float = 1.0, cel_eps: float = 1e-6):
    """bce_w·mean(BCE) + iou_w·mean_i(IoU_i) + cel_w·mean_i(CEL_i) —
    exactly ``losses.bce_with_logits/iou_loss/cel_loss`` combined."""
    loss, _ = _fwd(logits, targets, bce_w, iou_w, cel_w, iou_eps, cel_eps)
    return loss


def _terms(bce, inter, psum, tsum, n_pix, bce_w, iou_w, cel_w,
           iou_eps, cel_eps):
    b = bce.shape[0]
    total = jnp.float32(0.0)
    if bce_w:
        total += bce_w * bce.sum() / (b * n_pix)
    if iou_w:
        union = psum + tsum - inter
        total += iou_w * jnp.mean(1.0 - (inter + iou_eps) / (union + iou_eps))
    if cel_w:
        tot = psum + tsum
        total += cel_w * jnp.mean((tot - 2.0 * inter) / (tot + cel_eps))
    return total


def _fwd(logits, targets, bce_w, iou_w, cel_w, iou_eps, cel_eps):
    bce, inter, psum, tsum = pixel_region_sums(logits, targets)
    n_pix = int(jnp.size(logits) // logits.shape[0])
    loss = _terms(bce, inter, psum, tsum, n_pix, bce_w, iou_w, cel_w,
                  iou_eps, cel_eps)
    return loss, (logits, targets, inter, psum, tsum)


def _bwd(bce_w, iou_w, cel_w, iou_eps, cel_eps, res, g):
    logits, targets, inter, psum, tsum = res
    b = logits.shape[0]
    n_pix = int(jnp.size(logits) // b)
    shape = logits.shape
    x = logits.reshape(b, -1).astype(jnp.float32)
    t = targets.reshape(b, -1).astype(jnp.float32)
    p = jax.nn.sigmoid(x)
    grad = jnp.zeros_like(x)
    if bce_w:
        grad += bce_w * (p - t) / (b * n_pix)
    # Region terms: scalar coefficients per image, broadcast over pixels;
    # dp/dx = p(1−p).
    if iou_w:
        union = (psum + tsum - inter)[:, None]
        i_e = (inter + iou_eps)[:, None]
        u_e = union + iou_eps
        d_dp = -(t * u_e - i_e * (1.0 - t)) / (u_e * u_e)
        grad += iou_w / b * d_dp * p * (1.0 - p)
    if cel_w:
        tot = (psum + tsum)[:, None]
        i2 = (2.0 * inter)[:, None]
        d_dp = ((1.0 - 2.0 * t) * (tot + cel_eps) - (tot - i2)) / (
            (tot + cel_eps) ** 2)
        grad += cel_w / b * d_dp * p * (1.0 - p)
    grad = (g * grad).reshape(shape).astype(logits.dtype)
    return grad, jnp.zeros_like(targets)


fused_bce_iou_cel.defvjp(_fwd, _bwd)
