"""Shared NHWC building blocks for the model zoo.

TPU-first conventions used throughout the zoo:

- NHWC layout (the XLA:TPU-native conv layout; channels land on the
  128-wide lane dimension of the MXU/VPU).
- ``dtype`` (compute) defaults to bfloat16 with float32 params — convs
  and matmuls run on the MXU in bf16, BatchNorm statistics and the loss
  are reduced in float32.
- Cross-replica BatchNorm via linen's ``axis_name``: inside a
  ``shard_map`` over the ``data`` mesh axis this psums batch statistics
  across replicas, which is the XLA-native form of the SyncBN the
  reference got from DDP (SURVEY.md §2.3, §7.3 hard part 3).
"""

from __future__ import annotations

import collections
import contextlib
import logging
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

Dtype = Any

CONV_IMPLS = ("xla", "fused")


def _resolve_conv_impl(impl: Optional[str]) -> str:
    """Resolve the conv-block execution strategy (``model.conv_impl``,
    threaded through the zoo as an explicit ``conv_impl``).  Unlike the
    resample knob there is no env alias — the config is the only
    selector; ``DSOD_CONV_VMEM_MB`` tunes the kernel, never selects it."""
    if impl is None:
        return "xla"
    if impl not in CONV_IMPLS:
        raise ValueError(
            f"conv impl must be one of {CONV_IMPLS}, got {impl!r}")
    return impl


class _FusedConvParams(nn.Module):
    """Parameter holder for the fused conv branch, named ``Conv_0`` so
    the param tree is byte-for-byte what ``nn.Conv`` declares on the
    XLA branch (same initializers, same RNG fold path) — a checkpoint
    trained at either ``conv_impl`` restores into the other.  Also the
    read point for the serve-precision quantized view: when the apply
    variables carry a ``quant_scales`` collection (built by
    ``serve/precision.fused_conv_cast_variables``), the kernel param
    itself is the int8/fp8 leaf and the per-channel dequant scale rides
    back alongside it."""

    features: int
    kernel: Tuple[int, int]
    in_features: int
    use_bias: bool
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self):
        k = self.param(
            "kernel", nn.initializers.lecun_normal(),
            tuple(self.kernel) + (self.in_features, self.features),
            self.param_dtype)
        b = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,),
            self.param_dtype) if self.use_bias else None
        s = None
        if self.has_variable("quant_scales", "kernel"):
            s = self.get_variable("quant_scales", "kernel")
        return k, b, s


class _FusedBNParams(nn.Module):
    """Inference-mode BatchNorm parameter holder, named
    ``BatchNorm_0`` with flax's exact names/shapes/dtypes (scale/bias
    in params at ``param_dtype``; mean/var in batch_stats at f32) so
    the fused fold and the real ``nn.BatchNorm`` share one state."""

    features: int
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (self.features,), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,), self.param_dtype)
        mean = self.variable(
            "batch_stats", "mean",
            lambda: jnp.zeros((self.features,), jnp.float32))
        var = self.variable(
            "batch_stats", "var",
            lambda: jnp.ones((self.features,), jnp.float32))
        return scale, bias, mean.value, var.value


class ConvBNAct(nn.Module):
    """Conv → (BatchNorm) → (activation), NHWC.

    THE conv-block seam of the zoo: every encoder/decoder block in the
    four decoder families (and the VGG/ResNet backbones) routes here,
    so ``model.conv_impl`` selects one execution strategy zoo-wide:

    - ``xla`` (default) — ``nn.Conv`` + ``nn.BatchNorm`` exactly as
      before the knob existed (the lowered program is byte-identical,
      asserted in tests/test_pallas_conv.py);
    - ``fused`` — the Pallas conv-stage kernel
      (``pallas/fused_conv.py``): conv + inference-mode-BN + ReLU as
      ONE VMEM pass per image, and — when ``x`` is a list/tuple of
      same-spatial maps — conv over their channel concat WITHOUT
      materializing the concat in HBM (the decoder-head idiom).
      Train-mode BatchNorm needs whole-batch statistics (plus the
      cross-replica ``axis_name`` psum), so those sites run the fused
      conv kernel followed by the real ``nn.BatchNorm``; sites outside
      the kernel's envelope (stride > 1, even kernels, VMEM budget —
      ``fused_conv_available``) fall back to the XLA math PER-SITE
      with a trace-time log line, mirroring ``resample_merge``.

    Either impl accepts a list/tuple input as "concat these along
    channels first" — on the XLA path that is a plain
    ``jnp.concatenate`` where the caller used to do it.
    """

    features: int
    kernel: Tuple[int, int] = (3, 3)
    strides: int = 1
    dilation: int = 1
    use_bn: bool = True
    act: Optional[Callable] = nn.relu
    axis_name: Optional[str] = None  # cross-replica BN axis (e.g. "data")
    bn_momentum: float = 0.9
    conv_impl: Optional[str] = None  # None/"xla" | "fused"
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        impl = _resolve_conv_impl(self.conv_impl)
        if impl == "fused":
            parts = list(x) if isinstance(x, (list, tuple)) else [x]
            return self._fused_branch(parts, train)
        if isinstance(x, (list, tuple)):
            x = x[0] if len(x) == 1 else jnp.concatenate(x, axis=-1)
        # Explicit symmetric padding (= torch's padding=k//2·dilation).
        # XLA's "SAME" pads (0,1) at stride 2 — one pixel off from the
        # torch alignment ImageNet weights were trained with, which
        # would silently degrade every ported backbone.  Identical to
        # SAME at stride 1 with odd kernels.
        if self.kernel[0] % 2 and self.kernel[1] % 2:
            pad = [(self.dilation * (k // 2),) * 2 for k in self.kernel]
        else:
            pad = "SAME"
        x = nn.Conv(
            self.features,
            self.kernel,
            strides=(self.strides, self.strides),
            kernel_dilation=(self.dilation, self.dilation),
            padding=pad,
            use_bias=not self.use_bn,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )(x)
        if self.use_bn:
            x = nn.BatchNorm(
                use_running_average=not train,
                momentum=self.bn_momentum,
                axis_name=self.axis_name if train else None,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
            )(x)
        if self.act is not None:
            x = self.act(x)
        return x

    def _fused_branch(self, parts, train: bool):
        """The ``conv_impl=fused`` arm: fused Pallas kernel where the
        site fits, the same XLA math on the same (self-held) params
        per-site otherwise."""
        import jax.lax as lax

        from ..pallas import fused_conv as fc

        # Marker for the serve-precision quantized-view builder
        # (``fused_conv_cast_variables``): a mutable 'dsod_fused_conv'
        # collection collects the scopes whose Conv_0/kernel this seam
        # consumes (and therefore may stay int8/fp8).  A no-op on every
        # normal apply (the collection is immutable/absent); guarded
        # out of init, where EVERY collection is mutable and the marker
        # would otherwise pollute the init tree.
        if not self.is_initializing():
            self.sow("dsod_fused_conv", "site", jnp.zeros((), jnp.int32))
        kh, kw = self.kernel
        cin = sum(p.shape[-1] for p in parts)
        kernel, bias, qscale = _FusedConvParams(
            features=self.features, kernel=self.kernel, in_features=cin,
            use_bias=not self.use_bn, param_dtype=self.param_dtype,
            name="Conv_0")()
        cd = self.dtype
        fits = (self.strides == 1 and kh % 2 == 1 and kw % 2 == 1
                and fc.fused_conv_available(
                    [tuple(p.shape) for p in parts], (kh, kw),
                    self.dilation, self.features, dtype=cd))
        if not fits:
            # Out of envelope: trace-time note so a fused A/B leg knows
            # which sites opted out (fires once per compile, not per
            # step) — the resample_merge fallback pattern.
            import logging

            logging.getLogger(__name__).debug(
                "fused conv out of envelope at %s (k=%s stride=%s "
                "dil=%s -> %dch): xla path",
                [tuple(p.shape) for p in parts], self.kernel,
                self.strides, self.dilation, self.features)
            return self._xla_conv_on_params(parts, kernel, bias, qscale,
                                            train)
        relu_in_kernel = self.act is nn.relu
        xs = tuple(p.astype(cd) for p in parts)
        vecs = {}
        if qscale is not None:
            vecs["qscale"] = jnp.asarray(qscale, jnp.float32).reshape(-1)
            wk = kernel  # int8/fp8 leaf: dequantized in-VMEM
        else:
            wk = kernel.astype(cd)  # nn.Conv's promote_dtype cast
        mode = "none"
        if self.use_bn and not train:
            scale, beta, mean, var = _FusedBNParams(
                features=self.features, param_dtype=self.param_dtype,
                name="BatchNorm_0")()
            # flax _normalize's exact op order (epsilon included), so
            # the fold is the SAME f32 values BatchNorm would compute.
            mul = lax.rsqrt(var + 1e-5)
            mul = mul * scale
            vecs.update(mean=mean, mul=mul, bias=beta)
            mode = "bn"
        elif not self.use_bn:
            vecs["bias"] = bias.astype(cd)
            mode = "bias"
        y = fc.fused_conv(
            xs, wk, vecs, kernel=self.kernel, dilation=self.dilation,
            mode=mode, relu=(mode != "none" and relu_in_kernel))
        if mode == "none":
            # Train-mode BN: batch statistics (and the cross-replica
            # psum) need the whole batch — the kernel fuses the conv,
            # flax's BatchNorm follows it unchanged.
            y = nn.BatchNorm(
                use_running_average=not train,
                momentum=self.bn_momentum,
                axis_name=self.axis_name if train else None,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="BatchNorm_0",
            )(y)
        if self.act is not None and not (mode != "none" and relu_in_kernel):
            y = self.act(y)
        return y

    def _xla_conv_on_params(self, parts, kernel, bias, qscale,
                            train: bool):
        """Per-site fallback inside the fused branch: ``nn.Conv``'s
        exact math (promote/pad/conv/bias order replicated) on the
        branch's own params — needed because a quantized view's int8
        kernel leaf must be dequantized densely here, which ``nn.Conv``
        cannot do."""
        import jax.lax as lax
        from flax.linen.dtypes import promote_dtype

        x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, -1)
        if qscale is not None:
            kernel = kernel.astype(jnp.float32) * qscale
        if self.kernel[0] % 2 and self.kernel[1] % 2:
            pad = [(self.dilation * (k // 2),) * 2 for k in self.kernel]
        else:
            pad = "SAME"
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        y = lax.conv_general_dilated(
            x, kernel, (self.strides, self.strides), pad,
            rhs_dilation=(self.dilation, self.dilation),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if bias is not None:
            y = y + bias.reshape((1,) * (y.ndim - 1) + (-1,))
        if self.use_bn:
            y = nn.BatchNorm(
                use_running_average=not train,
                momentum=self.bn_momentum,
                axis_name=self.axis_name if train else None,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="BatchNorm_0",
            )(y)
        if self.act is not None:
            y = self.act(y)
        return y


def max_pool(x, window: int = 2, stride: int = 2):
    return nn.max_pool(x, (window, window), strides=(stride, stride), padding="SAME")


class _S2DConv7x7(nn.Module):
    """7×7/stride-2 conv computed as space-to-depth + 4×4/stride-1.

    The MLPerf-ResNet TPU trick: a stride-2 conv on a 3-channel
    full-res image keeps the MXU's 128-lane input dimension 97% idle
    and streams the largest activation in the network from HBM.
    Re-expressing it over the 2×2-block space-to-depth input
    ([B,H/2,W/2,12]) quadruples the contraction depth and quarters the
    streamed rows, with IDENTICAL arithmetic: the stored parameter
    stays the standard ``kernel`` [7,7,C,F] (checkpoint- and
    weight-port-compatible), padded to 8×8 with a leading zero row/col
    and regrouped at trace time so tap (u,v) lands on the s2d channel
    of its parity.  Derivation: with torch padding 3, tap u = 2p+a−1
    reads x[2(i+p−2)+a] = s2d row i+p−2, parity a — hence the 4-tap
    kernel and explicit (2,1) padding.  Bit-equivalence vs the plain
    stem is asserted in tests/test_models.py.
    """

    features: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        import jax.lax as lax

        b, h, w, c = x.shape
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (7, 7, c, self.features), self.param_dtype)
        k = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
        k = (k.reshape(4, 2, 4, 2, c, self.features)
             .transpose(0, 2, 1, 3, 4, 5)
             .reshape(4, 4, 4 * c, self.features))
        x2 = (x.reshape(b, h // 2, 2, w // 2, 2, c)
              .transpose(0, 1, 3, 2, 4, 5)
              .reshape(b, h // 2, w // 2, 4 * c))
        return lax.conv_general_dilated(
            x2.astype(self.dtype), k.astype(self.dtype),
            window_strides=(1, 1), padding=((2, 1), (2, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


class SpaceToDepthStem(nn.Module):
    """Drop-in for ``ConvBNAct(F, (7,7), strides=2)`` with the conv
    computed via :class:`_S2DConv7x7`.  Instantiate with
    ``name="ConvBNAct_0"`` so the param tree is indistinguishable from
    the plain stem (children ``Conv_0`` / ``BatchNorm_0``) — a
    checkpoint trained either way restores into the other."""

    features: int
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    act: Optional[Callable] = nn.relu
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = _S2DConv7x7(self.features, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="Conv_0")(x)
        x = nn.BatchNorm(
            use_running_average=not train,
            momentum=self.bn_momentum,
            axis_name=self.axis_name if train else None,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="BatchNorm_0",
        )(x)
        if self.act is not None:
            x = self.act(x)
        return x


def _upsample_axis(x, axis: int, s: int):
    """Integer-factor bilinear upsample along one spatial axis.

    Numerically identical to ``jax.image.resize(method='bilinear')``
    (half-pixel centers; at the edges the out-of-range tap's weight is
    renormalised away, which for a 2-tap kernel equals index clamping):
    ``out[s*i + p] = (1-f_p)*x[i + d_p] + f_p*x[i + d_p + 1]`` with the
    phase constants baked in at trace time.  Pure slice/lerp/interleave
    — a single VPU pass, where the generic resize lowers to per-axis
    ``dot_general``s whose operand layouts cost two relayout copies per
    call (15% of the MINet-R50 train step on another tree, in July;
    on THIS tree's one measured cell the order is the reverse — the
    generic path 13.6 ms, this one 40.3 ms of BASNet's step, PERF.md
    section 6, PR 26 — which is why it is the fallback now and no
    longer what BASNet runs).

    The interleave is LAYOUT-STABLE (round 5): the phases concatenate
    along the NEXT axis and one reshape merges the pair — by row-major
    identity ``(…, n, s·m, …) == (…, n, s, m, …) == (…, s·n, m, …)``
    this produces exactly the elements of ``stack(axis+1) + reshape``,
    without inserting size-1 axes XLA:TPU answers with dim-shuffled
    relayout copies (~1.25 ms per call on ``bf16[64,160,64,160]`` in a
    July trace of another tree, ~10% of the flagship step in
    data-formatting total; not re-measured here).
    """
    import jax.lax as lax

    n = x.shape[axis]
    first = lax.slice_in_dim(x, 0, 1, axis=axis)
    last = lax.slice_in_dim(x, n - 1, n, axis=axis)
    left = jnp.concatenate(
        [first, lax.slice_in_dim(x, 0, n - 1, axis=axis)], axis)
    right = jnp.concatenate(
        [lax.slice_in_dim(x, 1, n, axis=axis), last], axis)
    phases = []
    for p in range(s):
        c = (p + 0.5) / s - 0.5
        if c < 0:  # taps x[i-1], x[i]
            a, b, f = left, x, c + 1.0
        else:  # taps x[i], x[i+1]
            a, b, f = x, right, c
        f = jnp.asarray(f, x.dtype)
        phases.append(a * (1 - f) + b * f)
    out_shape = x.shape[:axis] + (n * s,) + x.shape[axis + 1:]
    if axis + 1 >= x.ndim:  # the last axis has no next one to fold into
        y = jnp.stack(phases, axis=axis + 1)
    else:
        y = jnp.concatenate(phases, axis=axis + 1)  # layout-stable
    return y.reshape(out_shape)


def _downsample2_axis(x, axis: int):
    """Antialiased factor-2 bilinear downsample along one spatial axis.

    Matches ``jax.image.resize``'s default (antialias=True) triangle
    kernel [1,3,3,1]/8 at half-pixel phase, with the edge rows
    renormalised over their in-range taps exactly as the reference
    implementation does (verified by impulse response — the edge sum is
    7/8, hence the /0.875).
    """
    import jax.lax as lax

    n = x.shape[axis]
    xe = lax.slice_in_dim(x, 0, n, stride=2, axis=axis)  # x[2i]
    xo = lax.slice_in_dim(x, 1, n, stride=2, axis=axis)  # x[2i+1]
    m = n // 2
    if m == 1:  # both outer taps cut: renorm [_,3,3,_]/6 = plain mean
        return (xe + xo) * jnp.asarray(0.5, x.dtype)
    zero_first = jnp.zeros_like(lax.slice_in_dim(xo, 0, 1, axis=axis))
    xo_m1 = jnp.concatenate(  # x[2i-1]; cut tap at i=0
        [zero_first, lax.slice_in_dim(xo, 0, m - 1, axis=axis)], axis)
    xe_p1 = jnp.concatenate(  # x[2i+2]; cut tap at i=m-1
        [lax.slice_in_dim(xe, 1, m, axis=axis), zero_first], axis)
    w1, w3 = jnp.asarray(0.125, x.dtype), jnp.asarray(0.375, x.dtype)
    y = w1 * xo_m1 + w3 * xe + w3 * xo + w1 * xe_p1
    renorm = jnp.asarray(1.0 / 0.875, x.dtype)
    return jnp.concatenate([
        lax.slice_in_dim(y, 0, 1, axis=axis) * renorm,
        lax.slice_in_dim(y, 1, m - 1, axis=axis),
        lax.slice_in_dim(y, m - 1, m, axis=axis) * renorm,
    ], axis)


RESAMPLE_IMPLS = ("fast", "xla", "fused")
# What a resample site can come out as.  With no arm named (``impl``
# None) the route follows from the shape:
# an exact-2x map of 8+ channels goes through the row-banded Pallas
# kernel, a 1-channel map through two lane-dense matmuls, anything
# else through the slice/lerp path.
RESAMPLE_ROUTES = ("kernel", "lane_dense", "fallback")
_ROUTES: "collections.Counter[str]" = collections.Counter()
_log = logging.getLogger(__name__)


@contextlib.contextmanager
def resample_routes(log_as: Optional[str] = None):
    """Tally the route every resample site traced inside the block
    took (sites that resize nothing are no sites).  Yields a dict
    filled on exit; with ``log_as`` the tally is one line of the
    program's log — the train engine wraps the step's trace in it, so
    the line appears once per compile."""
    before = _ROUTES.copy()
    counts: dict = {}
    try:
        yield counts
    finally:
        counts.update({k: _ROUTES[k] - before[k] for k in RESAMPLE_ROUTES})
        if log_as:
            from ..utils.logging import get_logger

            get_logger().info("resample routes (%s): %s", log_as, " ".join(
                f"{k}={counts[k]}" for k in RESAMPLE_ROUTES))


def _count_route(route: str, x_shape, hw):
    _ROUTES[route] += 1
    if route == "fallback":
        # Trace-time note (once per compile, not per step): which
        # sites took neither the kernel nor the lane-dense form.
        _log.debug("resample outside the kernel's envelope at %s -> %s: "
                   "slice/lerp path", tuple(x_shape), tuple(hw))


def _check_resample_impl(impl: Optional[str]) -> None:
    """``None`` (the route follows from the shape) or a named arm."""
    if impl is not None and impl not in RESAMPLE_IMPLS:
        raise ValueError(
            f"resample impl must be None or one of {RESAMPLE_IMPLS}, "
            f"got {impl!r}")


def _interp_matrix(n: int, out_n: int) -> np.ndarray:
    """The (out_n, n) bilinear interpolation matrix of an upsample by a
    whole factor: half-pixel centres, the tap past an edge clamped onto
    it — row for row what ``jax.image.resize`` computes."""
    src = (np.arange(out_n) + 0.5) * n / out_n - 0.5
    lo = np.floor(src)
    f = (src - lo).astype(np.float32)
    a = np.zeros((out_n, n), np.float32)
    rows = np.arange(out_n)
    np.add.at(a, (rows, np.clip(lo, 0, n - 1).astype(int)), 1 - f)
    np.add.at(a, (rows, np.clip(lo + 1, 0, n - 1).astype(int)), f)
    return a


def _resize_lane_dense(x, hw: Tuple[int, int]):
    """Whole-factor bilinear upsample of a 1-CHANNEL map with W on the
    lanes: ``A_h @ x @ A_w^T`` per image, in float32 (the matrices are
    constants; autodiff gives their transposes).  As NHWC such a map
    uses one lane of 128 in every op that touches it."""
    import jax.lax as lax

    hi = lax.Precision.HIGHEST
    y = jnp.einsum("Hh,bhw->bHw", _interp_matrix(x.shape[1], hw[0]),
                   x[..., 0].astype(jnp.float32), precision=hi)
    y = jnp.einsum("bHw,Ww->bHW", y, _interp_matrix(x.shape[2], hw[1]),
                   precision=hi)
    return y.astype(x.dtype)[..., None]


def _fast_bilinear_axis(x, axis: int, out_n: int):
    """One axis of ``resize_to``'s fast path; None if unsupported."""
    n = x.shape[axis]
    if out_n == n:
        return x
    if out_n % n == 0:
        return _upsample_axis(x, axis, out_n // n)
    if n == 2 * out_n and n % 2 == 0:
        return _downsample2_axis(x, axis)
    return None


@jax.named_scope("dsod.resample")
def resize_to(x, hw: Tuple[int, int], method: str = "bilinear",
              impl: Optional[str] = None):
    """Static-shape spatial resize (the upsample path of every decoder).

    Bilinear integer-factor resizes — every resize the zoo performs —
    never reach ``jax.image.resize`` unless asked to (same numerics
    either way, asserted in tests/test_models.py).  With no ``impl``
    the route follows from the shape alone, one pass over HBM in a
    layout that fills the lanes:

    - exact 2x, 8+ channels, a row band that fits VMEM — the Pallas
      kernel (``pallas/fused_resample.py``);
    - 1 channel, a whole factor per axis — two constant interpolation
      matrices with W on the lanes (``_resize_lane_dense``);
    - anything else — the slice/lerp path.

    A named ``impl`` pins one arm:

    - ``fast``  — slice/lerp with the layout-stable interleave;
    - ``xla``   — the generic ``jax.image.resize`` everywhere (the arm
      the tests compare against);
    - ``fused`` — the Pallas kernel where its rule admits the site,
      the ``fast`` path otherwise.

    Every arm computes the same bilinear resample, to dtype round-off
    (the kernel and the lane-dense form lerp in f32, so under bf16
    compute they are the MORE precise arms, not bit-equal ones).
    """
    _check_resample_impl(impl)
    hw = tuple(hw)
    if method == "bilinear" and impl != "xla":
        if hw == x.shape[1:3]:
            return x  # resizes nothing: no site
        if impl in (None, "fused"):
            from ..pallas.fused_resample import (fused_resample_available,
                                                 fused_upsample2)

            if fused_resample_available(x.shape, hw):
                _count_route("kernel", x.shape, hw)
                return fused_upsample2(x)
        if (impl is None and x.shape[3] == 1
                and hw[0] % x.shape[1] == 0 and hw[1] % x.shape[2] == 0):
            _count_route("lane_dense", x.shape, hw)
            return _resize_lane_dense(x, hw)
        _count_route("fallback", x.shape, hw)
        h = _fast_bilinear_axis(x, 1, hw[0])
        if h is not None:
            w = _fast_bilinear_axis(h, 2, hw[1])
            if w is not None:
                return w
    elif hw != x.shape[1:3]:
        _count_route("fallback", x.shape, hw)
    out = jax.image.resize(x, (x.shape[0], hw[0], hw[1], x.shape[3]), method=method)
    return out.astype(x.dtype)


def upsample_like(x, ref, method: str = "bilinear",
                  impl: Optional[str] = None):
    """Resize ``x`` to the spatial size of ``ref``."""
    return resize_to(x, (ref.shape[1], ref.shape[2]), method=method,
                     impl=impl)


@jax.named_scope("dsod.resample")
def resample_merge(x, lateral, mode: str = "add", x_first: bool = True,
                   impl: Optional[str] = None):
    """The decoder-stage idiom: upsample ``x`` to ``lateral``'s spatial
    size and merge — ``mode='add'`` (``up + lateral``) or
    ``mode='concat'`` (``[up, lateral]`` channels when ``x_first``,
    ``[lateral, up]`` otherwise).

    All four decoder users (MINet AIM/SIM, HDFNet, GateNet via its
    bare-upsample form, U²-Net) route their merges here so the
    ``model.resample_impl`` knob selects one strategy zoo-wide; BASNet's
    decoder stages and refine module come here with no ``impl``.  With
    ``impl='fused'`` (or no arm named and an add merge) and an exact-2x
    resample within the kernel's rule the whole chain runs as ONE
    Pallas pass (the kernel writes the merge; ``up`` is never in HBM —
    roofline lever #1, docs/PERFORMANCE.md); any other impl, or an
    out-of-envelope shape, takes ``resize_to`` + the plain merge (with
    no arm named ``resize_to`` still picks the kernel for the upsample
    alone).  Every arm
    computes the same resample (≤1e-5 in f32, asserted in
    tests/test_pallas_resample.py); under bf16 compute the fused arm
    lerps in f32 in-kernel where the fast arm lerps in bf16, so the
    arms agree to bf16 round-off (~1e-3), not bitwise.
    """
    _check_resample_impl(impl)
    # With no arm named a CONCAT is left to XLA: it reads the two maps
    # straight into the conv that follows (the concat never exists in
    # HBM), where a kernel-written concat is a second copy of the
    # lateral that the backward keeps (+0.95 GiB on BASNet's step
    # compiled for a v5e — PERF.md, PR 26); the upsample alone still
    # takes the kernel, inside resize_to.
    if impl == "fused" or (impl is None and mode == "add"):
        from ..pallas.fused_resample import (fused_resample_available,
                                             fused_upsample2_merge)

        if (mode in ("add", "concat")
                and lateral.shape[0] == x.shape[0]
                and (mode != "add" or lateral.shape[-1] == x.shape[-1])
                and fused_resample_available(
                    x.shape, lateral.shape[1:3], mode, lateral.shape[-1])):
            _count_route("kernel", x.shape, lateral.shape[1:3])
            return fused_upsample2_merge(x, lateral, mode=mode,
                                         x_first=x_first)
        # Out of the merge kernel's envelope: resize_to picks and
        # counts the route itself (it notes a fallback at trace time).
    up = resize_to(x, (lateral.shape[1], lateral.shape[2]), impl=impl)
    if mode == "add":
        return up + lateral
    if mode == "concat":
        parts = [up, lateral] if x_first else [lateral, up]
        return jnp.concatenate(parts, axis=-1)
    raise ValueError(f"mode must be 'add' or 'concat', got {mode!r}")
