"""Pallas fused SSIM (SURVEY.md §2.2; the 11×11-window loss of the
BASNet-style hybrid, losses/ssim.py).

The XLA path blurs a 5-moment channel stack with separable depthwise
convs — one HBM round trip for the stacked maps per level, times 7–8
deep-supervision levels.  This kernel computes the whole per-image SSIM
in VMEM: each grid step loads one image pair, builds the Gaussian blur
as BANDED MATRICES (blur-along-W = ``m @ K_w``, blur-along-H =
``K_h @ m`` — MXU contractions instead of VPU window sweeps; the taps
are symmetric so each band matrix is its own transpose), evaluates the
SSIM map pointwise, and writes back a single per-image sum.  HBM
traffic is exactly: read a, read b, write one scalar row.

Backward is a second kernel, not a recompute-in-XLA fallback: it
rebuilds the blurred moments, gets the pointwise partials via an
in-kernel ``jax.vjp`` (traces to elementwise ops — Mosaic-friendly),
and blurs them back through the same symmetric band matrices:

    dSum/da = G⊛∂S/∂μ_a + 2a ⊙ (G⊛∂S/∂E[a²]) + b ⊙ (G⊛∂S/∂E[ab])

Numerical parity with ``losses.ssim`` (forward AND gradients) is
asserted in tests/test_pallas_ssim.py; the real-TPU Mosaic lowering is
guarded by a ``jax.export(platforms=['tpu'])`` test (no chip needed).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_C1 = 0.01**2
_C2 = 0.03**2
_LANES = 128
_MAX_PIXELS = 448 * 448  # VMEM guard: beyond this, fall back to XLA


def _taps(window: int, sigma: float) -> np.ndarray:
    if window % 2 == 0:
        # The analytic backward relies on the band matrix being its own
        # transpose, which only holds for symmetric (odd-window) taps —
        # an even window would silently mirror the gradients.  The XLA
        # path (losses/ssim.py) handles even windows.
        raise ValueError(f"fused SSIM needs an odd window, got {window}")
    x = np.arange(window, dtype=np.float64) - window // 2
    g = np.exp(-(x**2) / (2.0 * sigma**2))
    return (g / g.sum()).astype(np.float32)


def _band(n: int, taps: np.ndarray):
    """(n, n) banded blur matrix K[i, j] = taps[j - i + r] — symmetric
    (symmetric taps), zero outside the band == 'SAME' zero padding."""
    r = len(taps) // 2
    i = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    diff = j - i
    k = jnp.zeros((n, n), jnp.float32)
    for t in range(len(taps)):
        k = k + jnp.where(diff == t - r, jnp.float32(taps[t]), 0.0)
    return k


def _blur_with(kh, kw, m):
    """K_h @ m @ K_w, both contractions in f32 on the MXU."""
    m = jnp.dot(kh, m, preferred_element_type=jnp.float32)
    return jnp.dot(m, kw, preferred_element_type=jnp.float32)


def _pointwise_ssim(mu_a, mu_b, e_aa, e_bb, e_ab):
    mu_aa, mu_bb, mu_ab = mu_a * mu_a, mu_b * mu_b, mu_a * mu_b
    var_a = e_aa - mu_aa
    var_b = e_bb - mu_bb
    cov = e_ab - mu_ab
    num = (2.0 * mu_ab + _C1) * (2.0 * cov + _C2)
    den = (mu_aa + mu_bb + _C1) * (var_a + var_b + _C2)
    return num / den


def _moments(a, b, kh, kw):
    return (_blur_with(kh, kw, a), _blur_with(kh, kw, b),
            _blur_with(kh, kw, a * a), _blur_with(kh, kw, b * b),
            _blur_with(kh, kw, a * b))


def _fwd_kernel(a_ref, b_ref, out_ref, *, taps):
    a = a_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    h, w = a.shape
    kh, kw = _band(h, taps), _band(w, taps)
    s = _pointwise_ssim(*_moments(a, b, kh, kw))
    lane = lax.broadcasted_iota(jnp.int32, (1, 1, _LANES), 2)
    out_ref[:] = jnp.where(lane == 0, jnp.sum(s), 0.0)


def _bwd_kernel(a_ref, b_ref, ga_ref, gb_ref, *, taps):
    a = a_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    h, w = a.shape
    kh, kw = _band(h, taps), _band(w, taps)

    def sum_from_moments(mu_a, mu_b, e_aa, e_bb, e_ab):
        return jnp.sum(_pointwise_ssim(mu_a, mu_b, e_aa, e_bb, e_ab))

    moms = _moments(a, b, kh, kw)
    _, vjp = jax.vjp(sum_from_moments, *moms)
    d_mu_a, d_mu_b, d_eaa, d_ebb, d_eab = vjp(jnp.float32(1.0))
    # Transpose of each blur is the same symmetric band matrix pair.
    g_eab = _blur_with(kh, kw, d_eab)
    ga = (_blur_with(kh, kw, d_mu_a) + 2.0 * a * _blur_with(kh, kw, d_eaa)
          + b * g_eab)
    gb = (_blur_with(kh, kw, d_mu_b) + 2.0 * b * _blur_with(kh, kw, d_ebb)
          + a * g_eab)
    ga_ref[:] = ga[None]
    gb_ref[:] = gb[None]


def _shape3(x) -> Tuple[int, int, int]:
    if x.ndim == 4:
        if x.shape[-1] != 1:
            raise ValueError(f"fused SSIM is single-channel, got {x.shape}")
        return x.shape[0], x.shape[1], x.shape[2]
    if x.ndim == 3:
        return x.shape
    raise ValueError(f"expected [B,H,W,1] or [B,H,W], got {x.shape}")


def fused_ssim_available(shape) -> bool:
    """The kernel holds one image pair + moments in VMEM; multi-channel
    or very large maps must use the XLA path."""
    shape = tuple(shape)
    if len(shape) == 4 and shape[-1] != 1:
        return False
    if len(shape) not in (3, 4):
        return False
    return shape[1] * shape[2] <= _MAX_PIXELS


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_ssim_mean(a, b, window: int = 11, sigma: float = 1.5):
    """mean SSIM(a, b) — identical to ``losses.ssim.ssim`` for
    single-channel maps, one Pallas pass per image."""
    val, _ = _ssim_fwd(a, b, window, sigma)
    return val


@jax.named_scope("dsod.kernel.fused_ssim")
def _run(kernel, a, b, out_shapes, taps, interpret=None):
    from jax.experimental import pallas as pl

    bsz, h, w = _shape3(a)
    a3 = a.reshape(bsz, h, w)
    b3 = b.reshape(bsz, h, w)
    if h * w > _MAX_PIXELS:
        raise ValueError(
            f"image {h}x{w} exceeds the fused-SSIM VMEM budget "
            f"({_MAX_PIXELS} px) — use losses.ssim (XLA) instead")
    return pl.pallas_call(
        partial(kernel, taps=taps),
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, h, w), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1,) + o, lambda i: (i,) + (0,) * len(o))
                   for o in out_shapes],
        out_shape=[jax.ShapeDtypeStruct((bsz,) + o, jnp.float32)
                   for o in out_shapes],
        interpret=(jax.default_backend() == "cpu"
                   if interpret is None else interpret),
    )(a3, b3)


def _ssim_fwd(a, b, window, sigma):
    bsz, h, w = _shape3(a)
    taps = _taps(window, sigma)
    (out,) = _run(_fwd_kernel, a, b, [(1, _LANES)], taps)
    val = out[:, 0, 0].sum() / (bsz * h * w)
    return val, (a, b)


def _ssim_bwd(window, sigma, res, g):
    a, b = res
    bsz, h, w = _shape3(a)
    taps = _taps(window, sigma)
    ga, gb = _run(_bwd_kernel, a, b, [(h, w), (h, w)], taps)
    scale = g / (bsz * h * w)
    ga = (scale * ga).reshape(a.shape).astype(a.dtype)
    gb = (scale * gb).reshape(b.shape).astype(b.dtype)
    return ga, gb


fused_ssim_mean.defvjp(_ssim_fwd, _ssim_bwd)


def fused_ssim_loss(logits, targets, *, window_size: int = 11,
                    sigma: float = 1.5):
    """1 − SSIM(sigmoid(logits), targets) — drop-in for
    ``losses.ssim.ssim_loss`` on single-channel maps."""
    p = jax.nn.sigmoid(logits.astype(jnp.float32))
    return 1.0 - fused_ssim_mean(p, targets.astype(jnp.float32),
                                 window_size, sigma)
