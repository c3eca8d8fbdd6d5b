"""Pallas dynamic local filtering — HDFNet's hot op (SURVEY.md §2 C5).

HDFNet applies per-position depthwise kernels predicted from the depth
stream (``models/hdfnet.py::dynamic_local_filter``).  The XLA path is
im2col (``conv_general_dilated_patches``) + einsum: it materialises a
``ksize²``-times-wider patch tensor in HBM per dilation branch — 9×C
channels where the op itself only ever needs C in flight.  This kernel
keeps everything in VMEM: each grid step loads one image's padded
feature tile and kernel maps, and the filtered output is just
``ksize²`` statically-shifted multiply-accumulates on the VPU.  HBM
traffic: read x (+pad) and k once, write out once.

Layouts (chosen for the TPU tiling, not torch parity):

- x / out: NHWC — C on the 128-lane axis.
- kernel maps: [B, ksize², H, W] (tap-major) — W on lanes, one clean
  (H, W) tile per tap instead of a 9-wide minor axis.

Backward is two more gather-form kernels (custom VJP, no scatters):

- ``dx[y'] = Σ_t (k_t ⊙ g)`` read at the MIRRORED shift ``2r − δ_t``
  — the transpose of a shifted gather is a gather at the opposite
  shift, so dx has the same structure as the forward.
- ``dk_t = Σ_c x_shifted ⊙ g`` — a channel reduction per tap.

Like fused_ssim, the grid is one image per step with a VMEM budget
guard: oversize inputs fall back to the XLA im2col path (same math,
asserted in tests).  Parity with that path (forward AND gradients) is
asserted in tests/test_pallas_dynfilter.py; Mosaic lowering is guarded
by ``jax.export(platforms=['tpu'])`` like the other kernels here.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Beyond this many f32 elements for the padded x tile, fall back to the
# XLA im2col path rather than risk VMEM pressure (≈8 MB at f32, and the
# kernel maps add T·H·W on top).
_MAX_TILE_ELEMS = 2 * 1024 * 1024
_LANES = 128

def _compiler_params() -> pltpu.CompilerParams:
    """Scoped-VMEM ceiling via the shared rule
    (pallas/vmem_budget.py); ``DSOD_DLF_VMEM_MB`` stays this kernel's
    escape hatch (0 = compiler default)."""
    from .vmem_budget import scoped_vmem_params

    return scoped_vmem_params("DSOD_DLF_VMEM_MB")


def _taps(ksize: int, dilation: int):
    """Static (dy, dx) offsets into the r-padded tile, tap-major."""
    offs = [dilation * i for i in range(ksize)]
    return [(dy, dx) for dy in offs for dx in offs]


def _fwd_kernel(x_ref, k_ref, o_ref, *, taps, h, w):
    # x_ref: (1, H+2r, W+2r, C); k_ref: (1, T, H, W); o_ref: (1, H, W, C)
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
    for t, (dy, dx) in enumerate(taps):
        xs = x_ref[0, dy:dy + h, dx:dx + w, :].astype(jnp.float32)
        acc = acc + xs * k_ref[0, t][:, :, None].astype(jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype)


def _dx_kernel(g_ref, k_ref, dx_ref, *, taps, h, w, r2):
    # g_ref: (1, H+2r, W+2r, C) padded cotangent; k_ref: (1, T, H+2r,
    # W+2r) padded kernel maps; dx_ref: (1, H, W, C).
    acc = jnp.zeros(dx_ref.shape[1:], jnp.float32)
    for t, (dy, dx) in enumerate(taps):
        sy, sx = r2 - dy, r2 - dx  # mirrored shift
        gs = g_ref[0, sy:sy + h, sx:sx + w, :].astype(jnp.float32)
        ks = k_ref[0, t, sy:sy + h, sx:sx + w].astype(jnp.float32)
        acc = acc + gs * ks[:, :, None]
    dx_ref[0] = acc.astype(dx_ref.dtype)


def _dk_kernel(x_ref, g_ref, dk_ref, *, taps, h, w):
    # x_ref: (1, H+2r, W+2r, C); g_ref: (1, H, W, C); dk_ref: (1, T, H, W)
    g = g_ref[0].astype(jnp.float32)
    for t, (dy, dx) in enumerate(taps):
        xs = x_ref[0, dy:dy + h, dx:dx + w, :].astype(jnp.float32)
        dk_ref[0, t] = jnp.sum(xs * g, axis=-1)


def _interpret(interpret):
    return jax.default_backend() == "cpu" if interpret is None else interpret


def _pad_hw(x, r):
    return jnp.pad(x, ((0, 0), (r, r), (r, r), (0, 0)))


def _img_spec(shape3):
    """BlockSpec for one image per grid step over leading dim."""
    n = len(shape3)
    return pl.BlockSpec((1,) + shape3,
                        lambda i, _n=n: (i,) + (0,) * _n)


@jax.named_scope("dsod.kernel.dynamic_filter")
def _call_filter(x, kt, ksize, dilation, interpret):
    b, h, w, c = x.shape
    r = dilation * (ksize // 2)
    taps = _taps(ksize, dilation)
    xp = _pad_hw(x, r)
    return pl.pallas_call(
        partial(_fwd_kernel, taps=taps, h=h, w=w),
        grid=(b,),
        in_specs=[_img_spec(xp.shape[1:]), _img_spec(kt.shape[1:])],
        out_specs=_img_spec((h, w, c)),
        out_shape=jax.ShapeDtypeStruct((b, h, w, c), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * w * c * len(taps), transcendentals=0,
            bytes_accessed=(2 * x.size + kt.size) * 4),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(xp, kt)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _dlf(x, kt, ksize, dilation, interpret):
    return _call_filter(x, kt, ksize, dilation, interpret)


def _dlf_fwd(x, kt, ksize, dilation, interpret):
    return _call_filter(x, kt, ksize, dilation, interpret), (x, kt)


@jax.named_scope("dsod.kernel.dynamic_filter")
def _dlf_bwd(ksize, dilation, interpret, res, g):
    x, kt = res
    b, h, w, c = x.shape
    t = ksize * ksize
    r = dilation * (ksize // 2)
    taps = _taps(ksize, dilation)

    gp = _pad_hw(g, r)
    ktp = jnp.pad(kt, ((0, 0), (0, 0), (r, r), (r, r)))
    dx = pl.pallas_call(
        partial(_dx_kernel, taps=taps, h=h, w=w, r2=2 * r),
        grid=(b,),
        in_specs=[_img_spec(gp.shape[1:]), _img_spec(ktp.shape[1:])],
        out_specs=_img_spec((h, w, c)),
        out_shape=jax.ShapeDtypeStruct((b, h, w, c), x.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(gp, ktp)

    xp = _pad_hw(x, r)
    dk = pl.pallas_call(
        partial(_dk_kernel, taps=taps, h=h, w=w),
        grid=(b,),
        in_specs=[_img_spec(xp.shape[1:]), _img_spec((h, w, c))],
        out_specs=_img_spec((t, h, w)),
        out_shape=jax.ShapeDtypeStruct((b, t, h, w), jnp.float32),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(xp, g)
    return dx, dk


_dlf.defvjp(_dlf_fwd, _dlf_bwd)


def fused_dynamic_filter_available(shape, ksize: int,
                                   dilation: int = 1) -> bool:
    """True when one grid step's tiles fit the kernel's VMEM budget.
    Counts BOTH the padded x/cotangent tile (C channels) and the
    tap-major kernel-map tile (ksize² planes) — the backward loads the
    padded kernel maps too, which dominate at low channel counts.
    The padded width must also fit ONE 128-lane row of the tap-major
    kernel maps: past it the v5e compiler refuses the backward's
    lane-offset slice (``Unsupported reshape`` at 160+2r; 120+2r=128
    compiles — tests/test_chip_compile.py)."""
    _, h, w, c = shape
    r = dilation * (ksize // 2)
    return (w + 2 * r <= _LANES
            and (h + 2 * r) * (w + 2 * r) * (c + ksize * ksize)
            <= _MAX_TILE_ELEMS)


def fused_dynamic_filter(x: jnp.ndarray, kernels: jnp.ndarray, ksize: int,
                         dilation: int = 1,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Drop-in for ``models.hdfnet.dynamic_local_filter`` (same
    signature/semantics: x [B,H,W,C], kernels [B,H,W,ksize²], SAME
    zero padding, channel-shared spatial kernels).  Differentiable via
    the Pallas backward kernels; ``interpret`` defaults to auto
    (interpret on CPU, Mosaic on TPU).  Oversize inputs fall back to
    the XLA im2col path."""
    b, h, w, c = x.shape
    if kernels.shape != (b, h, w, ksize * ksize):
        raise ValueError(
            f"kernels shape {kernels.shape} != {(b, h, w, ksize * ksize)}")
    if ksize % 2 == 0:
        raise ValueError(f"ksize must be odd, got {ksize}")
    if not fused_dynamic_filter_available(x.shape, ksize, dilation):
        from ..models.hdfnet import dynamic_local_filter

        return dynamic_local_filter(x, kernels, ksize, dilation,
                                    impl="xla")
    # Tap-major [B, T, H, W]: one clean (H, W) lane tile per tap.
    kt = jnp.moveaxis(kernels, -1, 1).astype(jnp.float32)
    return _dlf(x, kt, ksize, dilation, _interpret(interpret))
