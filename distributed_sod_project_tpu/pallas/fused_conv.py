"""Pallas fused conv-stage kernels — conv + BN + ReLU in one VMEM pass.

The round-4 roofline reconciliation (docs/PERFORMANCE.md) put 72% of
the measured flagship step in convolution fusions, with the fine
160/80-px buckets running 3.3x/2.1x off streaming bandwidth, and the
round-5 resample work pre-committed the verdict: "if the A/B lands at
~2%, the buckets' overhead lives inside the conv fusions themselves and
the next lever is a conv-stage kernel".  This module is that kernel
(ROADMAP item 4): the dominant encoder/decoder block of the zoo —
``ConvBNAct`` = 3x3/1x1 stride-1 conv -> BatchNorm -> ReLU — and its
decoder-head sibling conv(concat(parts)) run as ONE VMEM-resident pass
per image: inputs are read from HBM once, the concat operand is never
materialized, and the BN normalize + ReLU epilogue rides the conv's
VMEM tile instead of a second HBM round trip.

In-kernel form (the CPU-bitwise contraction): zero-pad the image tile
in VMEM, then for each static row-chunk build the im2col block
``(rows*w, kh*kw*cin)`` by concatenating the kh*kw shifted tap slices
(parts interleaved per tap in concat order) and run ONE
``jnp.dot(..., preferred_element_type=f32)`` against the reshaped
``(kh*kw*cin, cout)`` weight matrix.  Per output element this is the
SAME flattened (u, v, cin) contraction XLA:CPU's conv performs, so the
interpret-mode forward matches ``lax.conv_general_dilated`` BITWISE in
f32 (asserted, not assumed: tests/test_pallas_conv.py; below 9 output
pixels per image XLA switches its small-GEMM kernel and parity is f32
round-off instead) — the tap-by-tap
accumulation an earlier draft used differs at ~1e-5 (k*k partial sums
re-associate the reduction) and was rejected for exactly that reason.
The row chunking only bounds VMEM (im2col is 9x the input bytes for a
3x3); rows are independent, so chunked == unchunked bitwise.

Epilogues, replicated op-for-op from the XLA arm so parity is bitwise
(f32) / MXU-native (bf16) rather than merely close:

- ``none``  — conv only (the train-mode arm: batch-statistics BN needs
  the whole batch, so ``ConvBNAct`` keeps flax's BatchNorm after the
  kernel when ``train=True``);
- ``bias``  — ``+ bias`` in compute dtype (``use_bn=False`` sites,
  nn.Conv's own order);
- ``bn``    — inference-mode BatchNorm folded: ``(c - mean) * mul +
  beta`` with ``mul = rsqrt(var + eps) * scale`` computed OUTSIDE the
  kernel in flax's exact op order (``_normalize``: subtract first,
  then the combined multiplier — NOT the algebraic ``c*s + o`` fold,
  which re-rounds differently);

each optionally followed by an in-kernel ``max(y, 0)`` (= jax.nn.relu's
value; its grad-at-0 convention is matched in the VJP via ``y > 0``).

Precision arms (PR 6 composition): the weight operand may be an int8 /
fp8 **quantized** leaf from ``serve/precision.py`` — the kernel casts
it to the compute dtype in-VMEM (|q| <= 127 and e4m3 values are exact
in bf16) and the per-output-channel dequant scale folds into the
epilogue as one row multiply, so quantized weights ship to the MXU at
1/4 the HBM bytes with NO dense dequantized copy in HBM.  Quantized
calls are serve-only and non-differentiable (loud error).

Backward is closed-form, not a recompute: ``dx`` is the SAME conv
kernel applied to the cotangent with the spatially-flipped,
io-transposed weights (stride-1 same-conv transpose identity), and
``dw`` is a second accumulate-over-grid kernel doing one
``(cin, h*w) x (h*w, cout)`` contraction per tap.  The cheap epilogue
adjoints (relu mask, BN vector grads) run as plain XLA elementwise +
reductions outside the kernels.  The inference-mode BN fold needs the
pre-epilogue conv output ``c`` for d(mul); the fwd-for-vjp variant
emits it as a second output — the plain forward (no grad requested)
never pays that write.

Like the other kernels here: one image per grid step, f32-element VMEM
budget checked by the CALLER (``layers.ConvBNAct``) via
:func:`fused_conv_available` with per-site fallback, scoped-VMEM
ceiling via the shared rule (pallas/vmem_budget.py,
``DSOD_CONV_VMEM_MB`` override), ``interpret`` auto (interpret on CPU,
Mosaic on TPU), exactness guarded in tests/test_pallas_conv.py and the
v5e compiler's verdict at the flagship's shapes in
tests/test_chip_compile.py.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# f32-element budget for ONE grid step's working set: raw input block +
# zero-padded VMEM copy + one im2col row-chunk + output tile + weights.
# 12M elems ~= 48 MB f32 against the 100 MB scoped-VMEM ceiling — sized
# so every flagship DECODER site (AIM/SIM 160px x 64ch ~= 5.7M) and the
# deepest fine backbone stage (VGG stage-2 @160px x 128ch ~= 9.8M) fit,
# while the 320px encoder stages (~16M+) fall back to the XLA arm by
# design (same posture as fused_resample's U²-Net full-width exclusion).
_MAX_TILE_ELEMS = 12 * 1024 * 1024

# Static rows per im2col chunk: 8 rows x 160 cols x 576 taps ~= 0.74M
# f32 elems at the flagship decoder shape — the im2col blowup (kh*kw x
# the input bytes) stays a bounded slice of the budget.
_CHUNK_ROWS = 8

# Fixed operand order for the epilogue vectors (pallas positional refs).
_VEC_ORDER = ("qscale", "mean", "mul", "bias")


def is_quantized_weight(w) -> bool:
    """True when ``w`` is a serve-precision quantized leaf (int8/fp8)
    the kernel dequantizes in-VMEM (scale folded into the epilogue).
    The dtype set is serve/precision.py's one definition."""
    from ..serve.precision import quant_dtypes

    return jnp.asarray(w).dtype in quant_dtypes()


def _compiler_params():
    """Scoped-VMEM ceiling via the shared rule
    (pallas/vmem_budget.py); ``DSOD_CONV_VMEM_MB`` overrides either
    way (0 = compiler default)."""
    from .vmem_budget import scoped_vmem_params

    return scoped_vmem_params("DSOD_CONV_VMEM_MB")


def _interpret(interpret):
    return jax.default_backend() == "cpu" if interpret is None else interpret


class _Spec(NamedTuple):
    """Static kernel configuration (hashable: custom_vjp nondiff arg)."""

    kh: int
    kw: int
    dilation: int
    splits: Tuple[int, ...]  # per-part channel widths, concat order
    mode: str                # none | bias | bn
    relu: bool
    vec_names: Tuple[str, ...]
    interpret: bool


def fused_conv_available(part_shapes: Sequence[Tuple[int, ...]],
                         kernel: Tuple[int, int], dilation: int,
                         features: int, dtype=jnp.float32) -> bool:
    """True when one grid step's tiles fit the f32-element VMEM budget
    and the v5e compiler takes the in-kernel im2col reshape.  Callers
    fall back to the XLA path otherwise (same numerics, no fusion).
    Static shape constraints (stride 1, odd kernel) are the caller's
    gate."""
    kh, kw = kernel
    _, h, w, _ = part_shapes[0]
    if jnp.dtype(dtype).itemsize < 4 and w % 2:
        # (rows, w, taps) -> (rows*w, taps) on a packed (sub-32-bit)
        # dtype with odd w: "unsupported shape cast" from the v5e
        # compiler (the 5x5 stage of the 320 px flagship in bf16).
        return False
    cin = sum(int(s[-1]) for s in part_shapes)
    ph, pw = dilation * (kh // 2), dilation * (kw // 2)
    taps = kh * kw * cin
    elems = h * w * cin                       # raw input block(s)
    elems += (h + 2 * ph) * (w + 2 * pw) * cin  # zero-padded VMEM copy
    elems += min(_CHUNK_ROWS, h) * w * taps   # im2col row chunk
    elems += h * w * features                 # output tile
    elems += taps * features                  # weight matrix
    return elems <= _MAX_TILE_ELEMS


def _zero_pad2(x, ph: int, pw: int):
    """Zero-pad a (h, w, c) tile spatially — concatenate form, so the
    padded copy lives only in VMEM (jnp.pad is avoided for the same
    reason fused_resample's _clamp_pad is value-level)."""
    if ph:
        zr = jnp.zeros((ph,) + x.shape[1:], x.dtype)
        x = jnp.concatenate([zr, x, zr], axis=0)
    if pw:
        zc = jnp.zeros((x.shape[0], pw, x.shape[2]), x.dtype)
        x = jnp.concatenate([zc, x, zc], axis=1)
    return x


def _epilogue(acc, spec: _Spec, vecs: Dict[str, Any], cd):
    """The f32 conv accumulator -> the block's output, replicating the
    XLA arm's op/dtype order exactly (module docstring)."""
    if "qscale" in vecs:
        acc = acc * vecs["qscale"]  # (rows, w, cout) * (1, cout), f32
    c = acc.astype(cd)              # nn.Conv's output dtype
    if spec.mode == "bias":
        y = c + vecs["bias"]        # bias pre-cast to cd (nn.Conv order)
    elif spec.mode == "bn":
        # flax _normalize: subtract, then the combined multiplier, then
        # beta — all promoting to f32 against the f32 stats — then the
        # cast back to the compute dtype.
        y = ((c - vecs["mean"]) * vecs["mul"] + vecs["bias"]).astype(cd)
    else:
        y = c
    if spec.relu:
        y = jnp.maximum(y, jnp.zeros((), y.dtype))
    return y, c


def _fwd_kernel(*refs, spec: _Spec, cd, save_preact: bool):
    n = len(spec.splits)
    part_refs = refs[:n]
    w_ref = refs[n]
    vec_refs = dict(zip(spec.vec_names, refs[n + 1:n + 1 + len(spec.vec_names)]))
    out_refs = refs[n + 1 + len(spec.vec_names):]
    o_ref = out_refs[0]
    c_ref = out_refs[1] if save_preact else None

    kh, kw, d = spec.kh, spec.kw, spec.dilation
    ph, pw = d * (kh // 2), d * (kw // 2)
    h, w = o_ref.shape[1], o_ref.shape[2]
    cout = o_ref.shape[3]
    cin = sum(spec.splits)
    taps = kh * kw * cin

    xps = [_zero_pad2(r[0].astype(cd), ph, pw) for r in part_refs]
    wm = w_ref[...].astype(cd).reshape(taps, cout)
    vecs = {k: v[...] for k, v in vec_refs.items()}

    chunk = min(_CHUNK_ROWS, h)
    for s in range(0, h, chunk):
        rows = min(chunk, h - s)
        # im2col over the chunk: per tap (u, v), the parts' shifted
        # slices in concat order — the flattened (u, v, cin) contraction
        # index matches w.reshape(kh*kw*cin, cout) row-major exactly.
        slabs = []
        for u in range(kh):
            for v in range(kw):
                for xp in xps:
                    slabs.append(xp[s + u * d:s + u * d + rows,
                                    v * d:v * d + w, :])
        cols = jnp.concatenate(slabs, axis=-1) if len(slabs) > 1 \
            else slabs[0]
        acc = jnp.dot(cols.reshape(rows * w, taps), wm,
                      preferred_element_type=jnp.float32)
        acc = acc.reshape(rows, w, cout)
        y, c = _epilogue(acc, spec, vecs, cd)
        o_ref[0, s:s + rows] = y.astype(o_ref.dtype)
        if c_ref is not None:
            c_ref[0, s:s + rows] = c.astype(c_ref.dtype)


def _dw_kernel(*refs, spec: _Spec, cd):
    n = len(spec.splits)
    part_refs = refs[:n]
    g_ref = refs[n]
    o_ref = refs[n + 1]

    @pl.when(pl.program_id(0) == 0)
    def _init():  # noqa: ANN202 — pallas pattern
        o_ref[...] = jnp.zeros_like(o_ref)

    kh, kw, d = spec.kh, spec.kw, spec.dilation
    ph, pw = d * (kh // 2), d * (kw // 2)
    h, w, cout = g_ref.shape[1], g_ref.shape[2], g_ref.shape[3]

    xps = [_zero_pad2(r[0].astype(cd), ph, pw) for r in part_refs]
    g2 = g_ref[0].astype(cd).reshape(h * w, cout)
    for u in range(kh):
        for v in range(kw):
            slabs = [xp[u * d:u * d + h, v * d:v * d + w, :] for xp in xps]
            lhs = jnp.concatenate(slabs, axis=-1) if len(slabs) > 1 \
                else slabs[0]
            lhs = lhs.reshape(h * w, lhs.shape[-1])
            acc = jax.lax.dot_general(
                lhs, g2, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[u, v] += acc.astype(o_ref.dtype)


def _img_spec(shape):
    n = len(shape)
    return pl.BlockSpec((1,) + tuple(shape),
                        lambda i, _n=n: (i,) + (0,) * _n)


def _full_spec(shape):
    n = len(shape)
    return pl.BlockSpec(tuple(shape), lambda i, _n=n: (0,) * _n)


def _vec2d(v):
    """Epilogue vector -> (1, C) so the VMEM ref is rank-2."""
    return jnp.asarray(v).reshape(1, -1)


@jax.named_scope("dsod.kernel.fused_conv")
def _call_fwd(parts, w, vecs: Dict[str, Any], spec: _Spec,
              save_preact: bool = False):
    b, h, wd, _ = parts[0].shape
    cd = parts[0].dtype
    cout = w.shape[-1]
    cin = sum(spec.splits)
    taps = spec.kh * spec.kw * cin
    vec_args = [_vec2d(vecs[k]) for k in spec.vec_names]
    out_shape = [jax.ShapeDtypeStruct((b, h, wd, cout), cd)]
    out_specs = [_img_spec((h, wd, cout))]
    if save_preact:
        out_shape.append(jax.ShapeDtypeStruct((b, h, wd, cout), cd))
        out_specs.append(_img_spec((h, wd, cout)))
    out = pl.pallas_call(
        partial(_fwd_kernel, spec=spec, cd=cd, save_preact=save_preact),
        grid=(b,),
        in_specs=[_img_spec(p.shape[1:]) for p in parts]
        + [_full_spec(w.shape)]
        + [_full_spec(v.shape) for v in vec_args],
        out_specs=out_specs if save_preact else out_specs[0],
        out_shape=out_shape if save_preact else out_shape[0],
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * wd * cout * taps, transcendentals=0,
            bytes_accessed=int(
                sum(p.size * p.dtype.itemsize for p in parts)
                + w.size * w.dtype.itemsize
                + (2 if save_preact else 1) * b * h * wd * cout
                * jnp.dtype(cd).itemsize)),
        interpret=spec.interpret,
        compiler_params=_compiler_params(),
    )(*parts, w, *vec_args)
    return out


@jax.named_scope("dsod.kernel.fused_conv")
def _call_dw(parts, g, spec: _Spec):
    b, h, wd, cout = g.shape
    cd = parts[0].dtype
    cin = sum(spec.splits)
    return pl.pallas_call(
        partial(_dw_kernel, spec=spec, cd=cd),
        grid=(b,),
        in_specs=[_img_spec(p.shape[1:]) for p in parts]
        + [_img_spec((h, wd, cout))],
        out_specs=_full_spec((spec.kh, spec.kw, cin, cout)),
        out_shape=jax.ShapeDtypeStruct(
            (spec.kh, spec.kw, cin, cout), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * wd * cout * spec.kh * spec.kw * cin,
            transcendentals=0,
            bytes_accessed=int(
                sum(p.size * p.dtype.itemsize for p in parts)
                + g.size * g.dtype.itemsize
                + 4 * spec.kh * spec.kw * cin * cout)),
        interpret=spec.interpret,
        compiler_params=_compiler_params(),
    )(*parts, g)


def _flip_transpose(w):
    """Stride-1 same-conv transpose weights: spatial flip + io swap."""
    return w[::-1, ::-1].transpose(0, 1, 3, 2)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_conv_diff(parts, w, vecs, spec: _Spec):
    return _call_fwd(parts, w, vecs, spec)


def _fused_conv_fwd(parts, w, vecs, spec: _Spec):
    if spec.mode == "bn":
        y, c = _call_fwd(parts, w, vecs, spec, save_preact=True)
    else:
        y, c = _call_fwd(parts, w, vecs, spec), None
    return y, (parts, w, vecs, y, c)


def _fused_conv_bwd(spec: _Spec, res, g):
    parts, w, vecs, y, c = res
    if "qscale" in vecs:
        raise NotImplementedError(
            "quantized fused-conv weights are a serve-only view; "
            "differentiate the dense arm instead")
    cd = parts[0].dtype
    dz = jnp.where(y > 0, g, jnp.zeros((), g.dtype)) if spec.relu else g
    dvecs = {}
    if spec.mode == "bn":
        dz32 = dz.astype(jnp.float32)
        axes = (0, 1, 2)
        # Cotangents must land on the PRIMAL dtypes: beta is a
        # param_dtype leaf (bf16 under bf16 params), mean/mul are f32
        # (BN stats / the f32-promoted fold product).
        dvecs["bias"] = jnp.sum(dz32, axes).astype(vecs["bias"].dtype)
        y0 = c.astype(jnp.float32) - vecs["mean"]
        dvecs["mul"] = jnp.sum(dz32 * y0, axes).astype(
            vecs["mul"].dtype)
        dy0 = dz32 * vecs["mul"]
        dvecs["mean"] = -jnp.sum(dy0, axes).astype(vecs["mean"].dtype)
        dc = dy0.astype(cd)
    elif spec.mode == "bias":
        dvecs["bias"] = jnp.sum(dz.astype(jnp.float32), (0, 1, 2)
                                ).astype(vecs["bias"].dtype)
        dc = dz
    else:
        dc = dz
    # dx: the transposed same-conv — the SAME forward kernel on the
    # cotangent with flipped/io-swapped weights, epilogue 'none'.
    bwd_spec = _Spec(spec.kh, spec.kw, spec.dilation,
                     (w.shape[-1],), "none", False, (), spec.interpret)
    dx = _call_fwd((dc.astype(cd),), _flip_transpose(w), {}, bwd_spec)
    dparts = []
    lo = 0
    for cw in spec.splits:
        dparts.append(dx[..., lo:lo + cw])
        lo += cw
    dw = _call_dw(parts, dc.astype(cd), spec).astype(w.dtype)
    return tuple(dparts), dw, dvecs


_fused_conv_diff.defvjp(_fused_conv_fwd, _fused_conv_bwd)


def fused_conv(parts, w, vecs: Optional[Dict[str, Any]] = None, *,
               kernel: Tuple[int, int], dilation: int = 1,
               mode: str = "none", relu: bool = False,
               interpret: Optional[bool] = None):
    """Fused conv(+concat)(+affine)(+ReLU) over NHWC ``parts``.

    ``parts`` is a sequence of same-spatial NHWC tensors convolved as
    their channel concatenation (one part = the plain conv; more = the
    decoder-head conv+concat, the concat never materialized in HBM).
    ``w`` is the ``(kh, kw, cin_total, cout)`` kernel in the compute
    dtype, or a serve-precision int8/fp8 quantized leaf (then
    ``vecs['qscale']`` must carry the per-output-channel dequant scale
    and the call is non-differentiable).  ``mode``/``relu`` select the
    epilogue (module docstring); ``vecs`` carries its f32 vectors
    (``mean``/``mul``/``bias``) or the cd-cast conv ``bias``.

    Shape/VMEM gating is the CALLER's job (``fused_conv_available`` /
    ``layers.ConvBNAct``) — this raises on malformed operands rather
    than silently falling back.
    """
    parts = tuple(jnp.asarray(p) for p in parts)
    if not parts or any(p.ndim != 4 for p in parts):
        raise ValueError(
            f"expected NHWC parts, got {[getattr(p, 'shape', p) for p in parts]}")
    sp = parts[0].shape[:3]
    if any(p.shape[:3] != sp for p in parts):
        raise ValueError(
            f"parts disagree on batch/spatial dims: "
            f"{[p.shape for p in parts]}")
    kh, kw = kernel
    if kh % 2 == 0 or kw % 2 == 0:
        raise ValueError(f"fused conv needs odd kernels, got {kernel}")
    cin = sum(p.shape[-1] for p in parts)
    if w.ndim != 4 or w.shape[:3] != (kh, kw, cin):
        raise ValueError(
            f"weight {w.shape} does not match kernel {kernel} x "
            f"cin {cin}")
    if mode not in ("none", "bias", "bn"):
        raise ValueError(f"mode must be none|bias|bn, got {mode!r}")
    vecs = dict(vecs or {})
    quant = is_quantized_weight(w)
    if quant and "qscale" not in vecs:
        raise ValueError("quantized weights need vecs['qscale']")
    names = tuple(k for k in _VEC_ORDER if k in vecs)
    if set(names) != set(vecs):
        raise ValueError(
            f"unknown epilogue vec(s) {sorted(set(vecs) - set(names))}")
    spec = _Spec(kh, kw, int(dilation),
                 tuple(int(p.shape[-1]) for p in parts), mode, bool(relu),
                 names, _interpret(interpret))
    if quant:
        # Serve-only fast path: no VJP (pallas has no autodiff rule, so
        # an accidental grad fails loudly rather than silently wrong).
        return _call_fwd(parts, w, vecs, spec)
    return _fused_conv_diff(parts, w, vecs, spec)
