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
``out``, and the W interleave of the slice/lerp path wants W on the
sublanes while XLA:TPU keeps conv activations as ``[row][column][image]
[channel]`` (batch on the sublanes, channels on the lanes), so it pays
a relayout copy in and out (the chip trace of PR 26, PERF.md).  This
kernel runs the whole chain as ONE pass over HBM IN THAT ORDER: read
the coarse map (a quarter of the fine bytes) and the lateral once,
write the merged output once, no relayout.

Numerics are identical to ``models/layers.py::resize_to``'s factor-2
fast path (itself ``jax.image.resize(method='bilinear')``-exact:
half-pixel centers, edge taps renormalised == index clamping)::

    out[2i]   = 0.25*x[i-1] + 0.75*x[i]     (x[-1] -> x[0])
    out[2i+1] = 0.75*x[i]   + 0.25*x[i+1]   (x[n]  -> x[n-1])

applied separably H then W, in float32, the result rounded once to the
input's dtype.  With rows AND columns as major dims a neighbour is an
index away and the four output phases are four whole-tile stores into
the output seen as ``(h, 2, w, 2, image, C)``: no strided store, no
lane- or sublane-changing shape cast (the two things the v5e compiler
refused of earlier forms of this kernel).

Backward: the op is linear in both operands, so ``d_lateral`` is the
cotangent (or its channel slab) and ``d_x`` is the transposed resample —
per axis, with ``ge = g[2j]``, ``go = g[2j+1]``::

    dx[j] = 0.75*(ge[j] + go[j]) + 0.25*(go[j-1] + ge[j+1])

with the edge clamping folded in (``go[-1] -> ge[0]``, ``ge[n] ->
go[n-1]``).  XLA computes it, as the transpose of ``jax.image.resize``
(``_upT``, which says why it is not a kernel).

The grid is ``(batch block, row band)``: one step upsamples ``r`` coarse
rows of 16 (or 8) images into ``2r`` output rows, reading one halo row
of the coarse map above and below the band (two extra one-row blocks of
the same operand whose index maps clamp at the map's edges — which IS
the edge clamping of the resample).  VMEM need therefore follows the
band, not the map or the batch: ``_band_rows`` takes the tallest band
whose tiles fit the element budget (``vmem_budget.rows_per_band``), so a
small map is one band and BASNet's 160->320 x 128 site runs a row or two
a step.  A map whose height the band does not divide ends in a shorter
band: rows read past the map are replaced by the clamped halo row, rows
written past it are dropped.

Like the other kernels here: a shape/VMEM rule with fallback handled by
the caller (``layers.resample_merge``), ``interpret`` auto (interpret
on CPU, Mosaic on TPU), parity guarded in tests/test_pallas_resample.py
and the v5e compiler's verdict at the flagship's and BASNet's shapes in
tests/test_chip_compile.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Element budget for ONE grid step's tiles AS VMEM HOLDS THEM (coarse
# band + halo rows, their f32 lerps, lateral, merged output, of one
# batch block; channels round up to the 128 lanes, images to the 8
# sublanes), each counted once: the pipeline's second buffers and the
# kernel's other f32 values make it about 4 bytes an element, 32 MB
# under the 100 MB scoped ceiling.  8M elements is one coarse row a
# step at BASNet's 160->320 x 128 (+64 lateral) concat (6.2M) and two
# for its bare upsample.
_MAX_TILE_ELEMS = 8 * 1024 * 1024
# Below 8 channels the 128-lane padding is 16x the data or more (the
# 1-channel 160->320 logit asked 182 MB of a 128 MB core as one tile):
# such maps are not this kernel's; ``layers.resize_to`` has a
# lane-dense form for them.  Below 8 images the sublanes pad the same
# way, and XLA itself no longer keeps the batch there.
_MIN_BATCH = 8
_MIN_CHANNELS = 8
_LANES = 128


def _compiler_params() -> pltpu.CompilerParams:
    """Scoped-VMEM ceiling via the shared rule
    (pallas/vmem_budget.py); ``DSOD_RESAMPLE_VMEM_MB`` overrides
    either way (0 = compiler default)."""
    from .vmem_budget import scoped_vmem_params

    return scoped_vmem_params("DSOD_RESAMPLE_VMEM_MB")


def _interpret(interpret):
    return jax.default_backend() == "cpu" if interpret is None else interpret


def _batch_block(b: int) -> int:
    """Images per grid step: one bf16 sublane tile (16) where the batch
    is a whole number of them, one f32 tile (8) likewise, else all."""
    return next((n for n in (16, 8) if b % n == 0), b)


def _band_spec(r, *tail):
    """BlockSpec of ``r`` rows and one batch block per (batch block,
    band) grid step of a ``(row, ..., image, channel)`` array."""
    return pl.BlockSpec((r,) + tail, lambda i, j: (
        (j,) + (0,) * (len(tail) - 2) + (i, 0)))


def _halo_specs(r, n_rows, w, bb, c):
    """The one-row blocks above and below a band of ``r`` rows of an
    ``n_rows``-row map.  A block of one row is indexed by its row, and
    clamping that index at the map's edges is the resample's edge
    clamping."""
    return (
        pl.BlockSpec((1, w, bb, c), lambda i, j: (
            jnp.maximum(j * r - 1, 0), 0, i, 0)),
        pl.BlockSpec((1, w, bb, c), lambda i, j: (
            jnp.minimum((j + 1) * r, n_rows - 1), 0, i, 0)))


def _ragged_fix(x, bot, n_rows):
    """In a last band shorter than the rest the block's rows past the
    map hold nothing defined; they read as the halo row below the band,
    which there is the map's (clamped) last row — what the edge asks of
    them.  Traced only where the band does not divide the map."""
    r = x.shape[0]
    if n_rows % r == 0:
        return x
    row = pl.program_id(1) * r + lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row < n_rows, x, bot)


def _shift(y, edge, axis, back):
    """``y`` moved one place along a MAJOR axis (a row or a column of
    (image, channel) tiles: renaming, no data movement), ``edge`` taking
    the vacated place: ``y[k-1]`` when ``back`` else ``y[k+1]``."""
    n = y.shape[axis]
    if n == 1:
        return edge
    keep = lax.slice_in_dim(y, 0, n - 1, axis=axis) if back \
        else lax.slice_in_dim(y, 1, n, axis=axis)
    return jnp.concatenate([edge, keep] if back else [keep, edge], axis)


def _up_kernel(top_ref, x_ref, bot_ref, *rest, h, mode, x_first):
    """One band of ``r`` coarse rows of one batch block, forward.  Every
    ref is ``(row, column, image, channel)``: rows and columns are major
    dims, so both lerps are whole-tile VPU ops on neighbours picked by
    index, and the four output phases go to ``o_ref[:, a, :, b]`` of the
    output seen as ``(h, 2, w, 2, image, C)`` — the interleave is an
    index, never a strided store or a relayout."""
    *lat_ref, o_ref = rest
    f32 = jnp.float32
    top, bot = top_ref[...].astype(f32), bot_ref[...].astype(f32)
    x = _ragged_fix(x_ref[...].astype(f32), bot, h)
    c = x.shape[-1]
    cl = lat_ref[0].shape[-1] if mode == "concat" else 0
    base = 0 if x_first else cl  # where up's channels start in o_ref
    for a, y in enumerate((0.25 * _shift(x, top, 0, True) + 0.75 * x,
                           0.75 * x + 0.25 * _shift(x, bot, 0, False))):
        first, last = y[:, :1], y[:, -1:]  # clamped W edges
        for b, up in enumerate((0.25 * _shift(y, first, 1, True) + 0.75 * y,
                                0.75 * y + 0.25 * _shift(y, last, 1, False))):
            if mode == "add":
                up = up + lat_ref[0][:, a, :, b].astype(f32)
            o_ref[:, a, :, b, :, base:base + c] = up.astype(o_ref.dtype)
    if mode == "concat":
        lo = c if x_first else 0
        o_ref[:, :, :, :, :, lo:lo + cl] = lat_ref[0][...].astype(o_ref.dtype)


def _rows_first(x):
    """(image, row, column, C) -> (row, column, image, C): the order
    XLA:TPU itself keeps conv activations in (batch on the sublanes,
    channels on the lanes), so next to a conv this is a relabelling."""
    return jnp.transpose(x, (1, 2, 0, 3))


@jax.named_scope("dsod.kernel.fused_resample")
def _call_up(x, lat, mode, x_first, r, interpret):
    """Forward: ``mode`` none (``lat`` is None) / add / concat over a
    grid of (batch block, band of ``r`` coarse rows)."""
    b, h, w, c = x.shape
    bb = _batch_block(b)
    cl = 0 if lat is None else lat.shape[-1]
    c_out = c + cl if mode == "concat" else c
    lat_args, lat_specs = (), []
    if lat is not None:  # fine rows and columns paired: a free view
        lat_args = (_rows_first(lat).reshape(h, 2, w, 2, b, cl),)
        lat_specs = [_band_spec(r, 2, w, 2, bb, cl)]
    top, bot = _halo_specs(r, h, w, bb, c)
    xt = _rows_first(x)
    out = pl.pallas_call(
        partial(_up_kernel, h=h, mode=mode, x_first=x_first),
        grid=(b // bb, pl.cdiv(h, r)),
        in_specs=[top, _band_spec(r, w, bb, c), bot] + lat_specs,
        out_specs=_band_spec(r, 2, w, 2, bb, c_out),
        out_shape=jax.ShapeDtypeStruct((h, 2, w, 2, b, c_out), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=(18 + 4 * (mode == "add")) * b * h * w * c,
            transcendentals=0,
            bytes_accessed=(x.size + 4 * b * h * w * (cl + c_out))
            * x.dtype.itemsize),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(xt, xt, xt, *lat_args)
    return jnp.transpose(out.reshape(2 * h, 2 * w, b, c_out), (2, 0, 1, 3))


def _upT(g):
    """The transposed 2x upsample of a cotangent, by XLA: the transpose
    of ``jax.image.resize`` (two ``dot_general``s an axis), the same
    linear map as the forward kernel's to round-off.  NOT a kernel, by
    measurement: a transposed kernel over this same grid — exact to one
    bf16 rounding on the chip at all nine BASNet shapes, and 2.7 ms a
    step cheaper than this — moved the benchmark's judged first-gradient
    leaves from 2 % to 61 % off the float32 reference on one seed of
    three, in every variant that held it and in none that did not
    (PERF.md section 6, PR 26; the cause is not known)."""
    b, hh, ww, c = g.shape
    up = lambda x: jax.image.resize(x, g.shape, "bilinear")
    return jax.linear_transpose(up, jax.ShapeDtypeStruct(
        (b, hh // 2, ww // 2, c), g.dtype))(g)[0]


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _up2(x, r, interpret):
    return _call_up(x, None, "none", True, r, interpret)


def _up2_fwd(x, r, interpret):
    return _call_up(x, None, "none", True, r, interpret), None


def _up2_bwd(r, interpret, _, g):
    return (_upT(g),)


_up2.defvjp(_up2_fwd, _up2_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _up2_add(x, lat, r, interpret):
    return _call_up(x, lat, "add", True, r, interpret)


def _up2_add_fwd(x, lat, r, interpret):
    return _call_up(x, lat, "add", True, r, interpret), None


def _up2_add_bwd(r, interpret, _, g):
    return _upT(g), g


_up2_add.defvjp(_up2_add_fwd, _up2_add_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _up2_cat(x, lat, cx, x_first, r, interpret):
    return _call_up(x, lat, "concat", x_first, r, interpret)


def _up2_cat_fwd(x, lat, cx, x_first, r, interpret):
    return _call_up(x, lat, "concat", x_first, r, interpret), None


def _up2_cat_bwd(cx, x_first, r, interpret, _, g):
    cl = g.shape[-1] - cx
    gx, glat = (g[..., :cx], g[..., cx:]) if x_first \
        else (g[..., cl:], g[..., :cl])
    return _upT(gx), glat


_up2_cat.defvjp(_up2_cat_fwd, _up2_cat_bwd)


def _vmem_elems(b: int, c: int) -> int:
    """Elements a (b, c) tile of images x channels occupies in VMEM: the
    minor dim pads to 128 lanes, the second-minor to 8 sublanes."""
    return (-(-b // 8) * 8) * (-(-c // _LANES) * _LANES)


def _band_rows(x_shape, mode: str = "none", lat_channels: int = 0) -> int:
    """Coarse rows per grid step at this site — the tallest band whose
    tiles (coarse rows + two halo rows, their f32 lerps, lateral, merged
    output, of one batch block, at their VMEM footprint) fit
    ``_MAX_TILE_ELEMS``, evened out over the bands; 0 when not even a
    one-row band fits."""
    from .vmem_budget import rows_per_band

    b, h, w, c = x_shape
    fine = [c, c + lat_channels if mode == "concat" else c]  # f32, output
    if mode in ("add", "concat"):
        fine.append(lat_channels)
    tile = partial(_vmem_elems, _batch_block(b))
    coarse_row = w * tile(c)
    return rows_per_band(
        h, per_row=coarse_row + 2 * 2 * w * sum(tile(n) for n in fine),
        fixed=2 * coarse_row, budget=_MAX_TILE_ELEMS)


def fused_resample_available(x_shape, out_hw, mode: str = "none",
                             lat_channels: int = 0) -> bool:
    """True when the fused kernel applies: the target is exactly a 2x
    upsample per axis, the map has channels enough to fill lanes
    (``_MIN_CHANNELS``) AND a band of at least one row fits the VMEM
    budget.  Callers fall back to the XLA path otherwise (same
    numerics, no fusion)."""
    b, h, w, c = x_shape
    if (tuple(out_hw) != (2 * h, 2 * w) or c < _MIN_CHANNELS
            or b < _MIN_BATCH):
        return False
    return _band_rows(x_shape, mode, lat_channels) > 0


def fused_upsample2(x: jnp.ndarray,
                    interpret: bool | None = None) -> jnp.ndarray:
    """2x bilinear upsample of an NHWC map as one Pallas pass —
    numerics-identical to ``resize_to(x, (2H, 2W))``'s fast path.
    Differentiable (closed-form transposed-resample kernel)."""
    if x.ndim != 4:
        raise ValueError(f"expected NHWC, got {x.shape}")
    return _up2(x, _band_rows(x.shape), _interpret(interpret))


def fused_upsample2_merge(x: jnp.ndarray, lateral: jnp.ndarray,
                          mode: str = "add", x_first: bool = True,
                          interpret: bool | None = None) -> jnp.ndarray:
    """2x upsample ``x`` to ``lateral``'s spatial size and merge, in one
    pass over HBM.  ``mode='add'`` needs matching channel counts;
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
    if mode not in ("add", "concat"):
        raise ValueError(f"mode must be 'add' or 'concat', got {mode!r}")
    r = _band_rows(x.shape, mode, lateral.shape[-1])
    if mode == "add":
        if lateral.shape[-1] != c:
            raise ValueError(
                f"add merge needs matching channels, got {c} vs "
                f"{lateral.shape[-1]}")
        return _up2_add(x, lateral, r, _interpret(interpret))
    return _up2_cat(x, lateral, c, x_first, r, _interpret(interpret))
