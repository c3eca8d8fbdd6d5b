"""Pallas fused resample-merge — the fine-resolution decoder idiom.

The round-4 roofline reconciliation (docs/PERFORMANCE.md) put ~125 ms
of the 270 ms flagship step in the 160/80 buckets running 3.3x/2.1x off
streaming bandwidth, and named the decoder resample+merge chain as the
one place a kernel can repay.  The idiom — shared by MINet's AIM/SIM,
HDFNet's top-down decoder, GateNet's skip path and U²-Net's nested
U-merges — is::

    up   = 2x bilinear upsample(d)          # coarse -> fine
    out  = up + lateral        (add merge)  # or
    out  = concat(up, lateral) (concat merge)

On the XLA path each fine-resolution map crosses HBM several times: the
upsample writes ``up``, the merge reads ``up`` + ``lateral`` and writes
``out`` (plus the interleave's relayout copies the round-2 trace
surfaced).  This kernel runs the whole chain as ONE VMEM-resident pass
per image: read the coarse map (a quarter of the fine bytes) and the
lateral once, write the merged output once.

Numerics are identical to ``models/layers.py::resize_to``'s factor-2
fast path (itself ``jax.image.resize(method='bilinear')``-exact:
half-pixel centers, edge taps renormalised == index clamping)::

    out[2i]   = 0.25*x[i-1] + 0.75*x[i]     (x[-1] -> x[0])
    out[2i+1] = 0.75*x[i]   + 0.25*x[i+1]   (x[n]  -> x[n-1])

applied separably H then W.  The in-kernel interleave is four strided
phase stores into a VMEM staging ref (``_up2_into``), never an HBM
relayout.

Backward is a closed form, not a recompute: the op is linear in both
operands, so ``d_lateral`` is the cotangent (or its channel slab) and
``d_x`` is the transposed resample — per axis, with ``ge = g[2j]``,
``go = g[2j+1]``::

    dx[j] = 0.75*(ge[j] + go[j]) + 0.25*(go[j-1] + ge[j+1])

where the out-of-range taps fold the edge clamping in exactly:
``go[-1] -> ge[0]`` and ``ge[n] -> go[n-1]`` (the clamped forward taps
contribute 0.25*g[0] / 0.25*g[2n-1] to the edge gradients).  That runs
as a second gather-form kernel with the axes applied in reverse order.

Like the other kernels here: one image per grid step, a VMEM budget
guard with fallback handled by the caller (``layers.resample_merge``),
``interpret`` auto (interpret on CPU, Mosaic on TPU), parity guarded
in tests/test_pallas_resample.py and the v5e compiler's verdict at the
flagship's shapes in tests/test_chip_compile.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Element budget for ONE grid step's tiles AS VMEM HOLDS THEM (padded
# coarse input + f32 staging ref + lateral + merged output; channels
# round up to the 128 lanes, width to the 8 sublanes).  12M elems
# against the 100 MB scoped-VMEM ceiling — sized so every flagship
# fine-decoder site fits, including the largest, SIM-0's concat merge
# (80x80x32 up into 160x160x64 -> 96ch out = 10.8M padded elems; the
# v5e compiler accepts it in bf16 and f32 — tests/test_chip_compile.py).
# What gives way to the XLA path via ``fused_resample_available``:
# oversize maps (U²-Net's full-width 160->320 concat) and
# narrow-channel maps whose lane padding dwarfs the data (the 1-channel
# 160->320 saliency head: 128x waste, 13M elems for the staging ref
# alone, 182 MB of VMEM asked of a 128 MB core).
_MAX_TILE_ELEMS = 12 * 1024 * 1024


def _compiler_params() -> pltpu.CompilerParams:
    """Scoped-VMEM ceiling via the shared rule
    (pallas/vmem_budget.py); ``DSOD_RESAMPLE_VMEM_MB`` overrides
    either way (0 = compiler default)."""
    from .vmem_budget import scoped_vmem_params

    return scoped_vmem_params("DSOD_RESAMPLE_VMEM_MB")


def _interpret(interpret):
    return jax.default_backend() == "cpu" if interpret is None else interpret


def _img_spec(shape):
    """BlockSpec for one image per grid step over the leading dim."""
    n = len(shape)
    return pl.BlockSpec((1,) + tuple(shape),
                        lambda i, _n=n: (i,) + (0,) * _n)


def _clamp_pad(x):
    """Edge-replicate pad by 1 in both spatial dims — VALUE-level, so
    the padded map lives only in VMEM.  (An earlier draft jnp.pad'ed
    outside the pallas_call, which materialized the padded coarse copy
    in HBM and silently gave back ~2/3 of the per-site saving the
    kernel exists for.)"""
    x = jnp.concatenate([x[0:1], x, x[-1:]], axis=0)
    return jnp.concatenate([x[:, 0:1], x, x[:, -1:]], axis=1)


def _up2_into(x, up_ref):
    """(h, w, C) f32 tile -> the (2h, 2w, C) f32 VMEM ref ``up_ref``
    (clamped edges), as four phase writes ``up[2i+a, 2j+b]``.

    The interleave is a STRIDED STORE, not a value reshape: the v5e
    compiler refuses the lane-changing shape cast an in-register
    interleave needs (``(2h, w, 2C) -> (2h, 2w, C)``: "unsupported
    shape cast"), and refuses strided stores of sub-32-bit data — hence
    the f32 staging ref for bf16 outputs."""
    h, w = x.shape[0], x.shape[1]
    xp = _clamp_pad(x)                             # (h+2, w+2, C), VMEM
    rows = (0.25 * xp[0:h] + 0.75 * xp[1:h + 1],      # out rows 2i
            0.75 * xp[1:h + 1] + 0.25 * xp[2:h + 2])  # out rows 2i+1
    for a, y in enumerate(rows):
        up_ref[pl.ds(a, h, stride=2), pl.ds(0, w, stride=2), :] = (
            0.25 * y[:, 0:w] + 0.75 * y[:, 1:w + 1])
        up_ref[pl.ds(a, h, stride=2), pl.ds(1, w, stride=2), :] = (
            0.75 * y[:, 1:w + 1] + 0.25 * y[:, 2:w + 2])


def _up_kernel(x_ref, o_ref, up_ref):
    _up2_into(x_ref[0].astype(jnp.float32), up_ref)
    o_ref[0] = up_ref[...].astype(o_ref.dtype)


def _up_add_kernel(x_ref, lat_ref, o_ref, up_ref):
    _up2_into(x_ref[0].astype(jnp.float32), up_ref)
    o_ref[0] = (up_ref[...] + lat_ref[0].astype(jnp.float32)
                ).astype(o_ref.dtype)


def _up_cat_kernel(x_ref, lat_ref, o_ref, up_ref, *, cx, x_first):
    _up2_into(x_ref[0].astype(jnp.float32), up_ref)
    up = up_ref[...].astype(o_ref.dtype)
    lat = lat_ref[0].astype(o_ref.dtype)
    if x_first:
        o_ref[0, :, :, :cx] = up
        o_ref[0, :, :, cx:] = lat
    else:
        cl = lat.shape[-1]
        o_ref[0, :, :, :cl] = lat
        o_ref[0, :, :, cl:] = up


def _lerp_T(ge, go, axis):
    """One axis of the transposed upsample from its even/odd output
    phases: ``dx = 0.75*(ge+go) + 0.25*(go<<1 + ge>>1)`` with the
    edge-clamp corrections folded into the shifted operands
    (``go[-1] -> ge[0]``, ``ge[n] -> go[n-1]`` — derivation in the
    module docstring)."""
    n = ge.shape[axis]
    if n == 1:  # both shifts degenerate to the other phase's only row
        return ge + go
    go_shift = jnp.concatenate(  # go[j-1], with go[-1] := ge[0]
        [lax.slice_in_dim(ge, 0, 1, axis=axis),
         lax.slice_in_dim(go, 0, n - 1, axis=axis)], axis)
    ge_shift = jnp.concatenate(  # ge[j+1], with ge[n] := go[n-1]
        [lax.slice_in_dim(ge, 1, n, axis=axis),
         lax.slice_in_dim(go, n - 1, n, axis=axis)], axis)
    return 0.75 * (ge + go) + 0.25 * (go_shift + ge_shift)


def _upT_kernel(g_ref, dx_ref, g32_ref):
    # Phase split by strided LOADS from an f32 staging copy (same two
    # compiler limits as the forward's strided stores).
    g32_ref[...] = g_ref[0].astype(jnp.float32)
    h, w = dx_ref.shape[1], dx_ref.shape[2]

    def phase(a, b):
        return g32_ref[pl.ds(a, h, stride=2), pl.ds(b, w, stride=2), :]

    # W first, then H: the reverse of the forward's order.
    dx = _lerp_T(_lerp_T(phase(0, 0), phase(0, 1), axis=1),
                 _lerp_T(phase(1, 0), phase(1, 1), axis=1), axis=0)
    dx_ref[0] = dx.astype(dx_ref.dtype)


def _staging(h2, w2, c):
    """The f32 (2h, 2w, C) VMEM staging ref the strided phase
    stores/loads go through."""
    return [pltpu.VMEM((h2, w2, c), jnp.float32)]


@jax.named_scope("dsod.kernel.fused_resample")
def _call_up(x, interpret):
    b, h, w, c = x.shape
    return pl.pallas_call(
        _up_kernel,
        grid=(b,),
        in_specs=[_img_spec(x.shape[1:])],
        out_specs=_img_spec((2 * h, 2 * w, c)),
        out_shape=jax.ShapeDtypeStruct((b, 2 * h, 2 * w, c), x.dtype),
        scratch_shapes=_staging(2 * h, 2 * w, c),
        cost_estimate=pl.CostEstimate(
            flops=16 * b * h * w * c, transcendentals=0,
            bytes_accessed=(x.size + 4 * b * h * w * c) * 4),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(x)


@jax.named_scope("dsod.kernel.fused_resample")
def _call_merge(x, lat, mode, x_first, interpret):
    b, h, w, c = x.shape
    cl = lat.shape[-1]
    c_out = c + cl if mode == "concat" else c
    if mode == "add":
        kernel = _up_add_kernel
    else:
        kernel = partial(_up_cat_kernel, cx=c, x_first=x_first)
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[_img_spec(x.shape[1:]), _img_spec(lat.shape[1:])],
        out_specs=_img_spec((2 * h, 2 * w, c_out)),
        out_shape=jax.ShapeDtypeStruct((b, 2 * h, 2 * w, c_out), x.dtype),
        scratch_shapes=_staging(2 * h, 2 * w, c),
        cost_estimate=pl.CostEstimate(
            flops=(16 + 4) * b * h * w * c, transcendentals=0,
            bytes_accessed=(x.size + lat.size
                            + 4 * b * h * w * c_out) * 4),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(x, lat)


@jax.named_scope("dsod.kernel.fused_resample")
def _call_upT(g, interpret):
    b, hh, ww, c = g.shape
    return pl.pallas_call(
        _upT_kernel,
        grid=(b,),
        in_specs=[_img_spec(g.shape[1:])],
        out_specs=_img_spec((hh // 2, ww // 2, c)),
        out_shape=jax.ShapeDtypeStruct((b, hh // 2, ww // 2, c), g.dtype),
        scratch_shapes=_staging(hh, ww, c),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(g)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _up2(x, interpret):
    return _call_up(x, interpret)


def _up2_fwd(x, interpret):
    return _call_up(x, interpret), None


def _up2_bwd(interpret, _, g):
    return (_call_upT(g, interpret),)


_up2.defvjp(_up2_fwd, _up2_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _up2_add(x, lat, interpret):
    return _call_merge(x, lat, "add", True, interpret)


def _up2_add_fwd(x, lat, interpret):
    return _call_merge(x, lat, "add", True, interpret), None


def _up2_add_bwd(interpret, _, g):
    return _call_upT(g, interpret), g


_up2_add.defvjp(_up2_add_fwd, _up2_add_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _up2_cat(x, lat, cx, x_first, interpret):
    return _call_merge(x, lat, "concat", x_first, interpret)


def _up2_cat_fwd(x, lat, cx, x_first, interpret):
    return _call_merge(x, lat, "concat", x_first, interpret), None


def _up2_cat_bwd(cx, x_first, interpret, _, g):
    if x_first:
        gx, glat = g[..., :cx], g[..., cx:]
    else:
        gx, glat = g[..., g.shape[-1] - cx:], g[..., :g.shape[-1] - cx]
    return _call_upT(gx, interpret), glat


_up2_cat.defvjp(_up2_cat_fwd, _up2_cat_bwd)


def fused_resample_available(x_shape, out_hw, mode: str = "none",
                             lat_channels: int = 0) -> bool:
    """True when the fused kernel applies: the target is exactly a 2x
    upsample per axis AND one grid step's tiles (padded coarse input +
    f32 staging + lateral + merged output, at their VMEM footprint) fit
    the budget.  Callers fall back to the XLA path otherwise (same
    numerics, no fusion)."""
    b, h, w, c = x_shape
    if tuple(out_hw) != (2 * h, 2 * w):
        return False
    elems = _vmem_elems(h + 2, w + 2, c) + _vmem_elems(2 * h, 2 * w, c)
    if mode in ("add", "concat"):
        elems += _vmem_elems(2 * h, 2 * w, lat_channels)
    elems += _vmem_elems(
        2 * h, 2 * w, c + (lat_channels if mode == "concat" else 0))
    return elems <= _MAX_TILE_ELEMS


def _vmem_elems(h: int, w: int, c: int) -> int:
    """Elements an (h, w, c) tile occupies in VMEM: the minor dim pads
    to 128 lanes, the second-minor to 8 sublanes."""
    return h * (-(-w // 8) * 8) * (-(-c // 128) * 128)


def fused_upsample2(x: jnp.ndarray,
                    interpret: bool | None = None) -> jnp.ndarray:
    """2x bilinear upsample of an NHWC map as one Pallas pass —
    numerics-identical to ``resize_to(x, (2H, 2W))``'s fast path.
    Differentiable (closed-form transposed-resample kernel)."""
    if x.ndim != 4:
        raise ValueError(f"expected NHWC, got {x.shape}")
    return _up2(x, _interpret(interpret))


def fused_upsample2_merge(x: jnp.ndarray, lateral: jnp.ndarray,
                          mode: str = "add", x_first: bool = True,
                          interpret: bool | None = None) -> jnp.ndarray:
    """2x upsample ``x`` to ``lateral``'s spatial size and merge, in one
    VMEM-resident pass.  ``mode='add'`` needs matching channel counts;
    ``mode='concat'`` emits ``[up, lateral]`` channels (``x_first``)
    or ``[lateral, up]``.  Shape/budget gating is the CALLER's job
    (``fused_resample_available`` / ``layers.resample_merge``) — this
    raises on shape mismatch rather than silently falling back."""
    if x.ndim != 4 or lateral.ndim != 4:
        raise ValueError(f"expected NHWC, got {x.shape} / {lateral.shape}")
    b, h, w, c = x.shape
    if lateral.shape[0] != b or lateral.shape[1:3] != (2 * h, 2 * w):
        raise ValueError(
            f"lateral {lateral.shape} is not the 2x target of {x.shape}")
    if mode == "add":
        if lateral.shape[-1] != c:
            raise ValueError(
                f"add merge needs matching channels, got {c} vs "
                f"{lateral.shape[-1]}")
        return _up2_add(x, lateral, _interpret(interpret))
    if mode == "concat":
        return _up2_cat(x, lateral, c, x_first, _interpret(interpret))
    raise ValueError(f"mode must be 'add' or 'concat', got {mode!r}")
