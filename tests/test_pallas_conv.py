"""Fused conv-stage kernels (pallas/fused_conv.py) + the
model.conv_impl execution-strategy knob (ISSUE 12 acceptance).

Coverage contract:

- interpret-mode exactness on CPU: fused conv+BN+ReLU and conv+concat
  forwards match the XLA arm BITWISE in f32 (both arms jitted — eager
  XLA elides the FMA contraction the compiler uses, so eager-vs-jit
  differs by a few ulp by construction) and to ≤1 bf16 ulp under bf16
  compute, at even AND odd spatial sizes, dilations, 1x1 and 3x3;
- the custom VJP (dx via the transposed-conv kernel, dw via the
  accumulate-over-grid kernel, closed-form epilogue adjoints) checked
  against the XLA arm's autodiff;
- train-mode BatchNorm sites run the fused conv + flax's BatchNorm:
  outputs AND updated batch statistics bitwise vs the XLA arm;
- int8/fp8 weight views dequantize IN-KERNEL (scale folded into the
  epilogue) and match the dense dequantized arm;
- per-site VMEM-budget fallback: an over-budget site takes the XLA
  math (bitwise) while in-envelope siblings stay fused, with the
  fused_resample-style loud log line; DSOD_CONV_VMEM_MB + the v2/v3
  small-VMEM denylist mirror the resample kernel's rule;
- conv_impl=xla leaves the lowered train-step program byte-identical
  to the pre-seam ConvBNAct (a verbatim seed copy lowered side by
  side), and init trees are identical across impls;
- the quantized-view builder (serve/precision.fused_conv_cast_variables)
  discovers exactly the fused seam's kernels and the engine AOT-warms
  fused programs keyed on conv_impl with no request-path compile;
- all four kernels Mosaic-export for platform='tpu' (no chip).
"""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax import lax

from distributed_sod_project_tpu.models.layers import (ConvBNAct,
                                                       _resolve_conv_impl)
from distributed_sod_project_tpu.pallas import fused_conv as fc


def _rand(*shape, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape).astype(np.float32))


_EPS32 = float(np.finfo(np.float32).eps)


def _same(got, ref, ulps: float = 8.0) -> bool:
    """The fused arm against the XLA arm in f32: equal to ``ulps`` units
    of f32 round-off at the array's own scale (max |ref|, at least 1).

    These assertions were BITWISE until PR 23.  The in-kernel im2col
    dot contracts the same flattened (u, v, cin) index in the same
    order as XLA:CPU's conv, and on a one-core host the two come out
    bit-equal; that is a property of the host's XLA:CPU kernels, not
    of the Pallas kernel.  On the 8-core host the suite runs on now,
    XLA:CPU blocks the conv and the dot differently and the sums
    re-associate: 17 of these cases differ at the parent commit too,
    by at most 4.9e-7 of the array's scale (7.3e-4 at |values| ~ 2057,
    9.5e-6 at ~ 26), and under ``--xla_cpu_multi_thread_eigen=false``
    3 still differ, one of which is bit-equal with threads.  8 ulps of
    scale (9.5e-7) is twice what was seen, 10x under what a wrong tap
    or a dropped term would give, and ~4000x under bf16 round-off."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if not ref.size:
        return got.shape == ref.shape
    scale = max(1.0, float(np.abs(ref).max()))
    return bool(np.abs(got - ref).max() <= ulps * _EPS32 * scale)


def _grads_same(got, ref) -> bool:
    """Gradients of the two arms (longer sums, a sin() on top).  The
    limit was 2e-5 ABSOLUTE until PR 23, which the same re-association
    breaks on this host at the parent commit (3.9e-5 at |values| ~ 12,
    1.7e-4 at ~ 170: at most 3.6e-6 of the array's scale).  Held to
    1e-5 of scale, never looser than the old 2e-5 below |values| = 2."""
    return _same(got, ref, ulps=1e-5 / _EPS32)


def _conv_ref(x, w, dilation=1):
    kh, kw = w.shape[0], w.shape[1]
    pad = [(dilation * (kh // 2),) * 2, (dilation * (kw // 2),) * 2]
    return lax.conv_general_dilated(
        x, w, (1, 1), pad, rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


# Even and odd spatial sizes; chunk-boundary crossing (h > 8) included.
# Bitwise holds for >= 9 output pixels per image; below that XLA:CPU
# switches to a different small-GEMM kernel with another reduction
# association (measured: <= 4e-6 at (1,2)/(2,2)/(2,4)) — covered by
# the degenerate-size test below at tolerance.
_SIZES = [(5, 7), (6, 6), (12, 9), (3, 3)]


@pytest.mark.parametrize("h,w", _SIZES)
@pytest.mark.parametrize("dilation,k", [(1, 3), (2, 3), (1, 1)])
def test_fused_conv_matches_xla_bitwise_f32(h, w, dilation, k):
    if k == 1 and dilation != 1:
        pytest.skip("1x1 dilation is degenerate")
    x = _rand(2, h, w, 8, seed=1)
    wk = _rand(k, k, 8, 16, seed=2)
    ref = jax.jit(lambda a, b: _conv_ref(a, b, dilation))(x, wk)
    got = jax.jit(lambda a, b: fc.fused_conv(
        (a,), b, kernel=(k, k), dilation=dilation))(x, wk)
    assert _same(got, ref), float(jnp.abs(got - ref).max())


@pytest.mark.parametrize("h,w", [(1, 2), (2, 2), (2, 4)])
def test_fused_conv_degenerate_sizes_to_roundoff(h, w):
    """Sub-9-pixel maps: XLA:CPU's small-GEMM path re-associates the
    reduction — parity to f32 round-off, not bitwise."""
    x = _rand(2, h, w, 8, seed=1)
    wk = _rand(3, 3, 8, 16, seed=2)
    ref = jax.jit(lambda a, b: _conv_ref(a, b))(x, wk)
    got = jax.jit(lambda a, b: fc.fused_conv(
        (a,), b, kernel=(3, 3)))(x, wk)
    assert float(jnp.abs(got - ref).max()) <= 1e-5


def test_fused_conv_concat_and_bn_relu_bitwise_f32():
    """conv+concat + folded-BN + ReLU vs the XLA composition, both
    jitted: bitwise — the im2col contraction reproduces XLA's conv
    reduction order and the epilogue replicates flax's op order."""
    x1, x2 = _rand(2, 6, 5, 8, seed=3), _rand(2, 6, 5, 12, seed=4)
    wk = _rand(3, 3, 20, 16, seed=5)
    mean = _rand(16, seed=6)
    var = jnp.abs(_rand(16, seed=7))
    scale, beta = _rand(16, seed=8), _rand(16, seed=9)

    @jax.jit
    def ref(a, b, w):
        mul = lax.rsqrt(var + 1e-5) * scale
        c = _conv_ref(jnp.concatenate([a, b], -1), w)
        return jnp.maximum((c - mean) * mul + beta, 0)

    @jax.jit
    def got(a, b, w):
        mul = lax.rsqrt(var + 1e-5) * scale
        return fc.fused_conv((a, b), w,
                             {"mean": mean, "mul": mul, "bias": beta},
                             kernel=(3, 3), mode="bn", relu=True)

    r, g = ref(x1, x2, wk), got(x1, x2, wk)
    assert _same(r, g), float(jnp.abs(r - g).max())


@pytest.mark.parametrize("mode", ["none", "bias", "bn"])
def test_fused_conv_vjp_matches_autodiff(mode):
    """Closed-form VJP vs the XLA arm's autodiff — every primal's
    cotangent (inputs, weights, epilogue vectors)."""
    x1, x2 = _rand(2, 5, 6, 8, seed=10), _rand(2, 5, 6, 4, seed=11)
    wk = _rand(3, 3, 12, 8, seed=12)
    mean, beta = _rand(8, seed=13), _rand(8, seed=14)
    mul = jnp.abs(_rand(8, seed=15)) + 0.5

    def xla_path(a, b, w, vec):
        c = _conv_ref(jnp.concatenate([a, b], -1), w)
        if mode == "bias":
            c = c + vec["bias"]
        elif mode == "bn":
            c = (c - vec["mean"]) * vec["mul"] + vec["bias"]
        return jnp.maximum(c, 0) if mode != "none" else c

    def fused_path(a, b, w, vec):
        return fc.fused_conv((a, b), w, vec, kernel=(3, 3), mode=mode,
                             relu=mode != "none")

    vec = {} if mode == "none" else (
        {"bias": beta} if mode == "bias"
        else {"mean": mean, "mul": mul, "bias": beta})
    args = (x1, x2, wk, vec)
    loss_r = jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.sin(xla_path(*a))), (0, 1, 2, 3)))
    loss_g = jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.sin(fused_path(*a))), (0, 1, 2, 3)))
    for r, g in zip(jax.tree_util.tree_leaves(loss_r(*args)),
                    jax.tree_util.tree_leaves(loss_g(*args))):
        assert _grads_same(g, r), float(jnp.abs(r - g).max())


def test_fused_conv_vjp_cotangent_dtypes_match_primals():
    """Non-f32 epilogue primals (bf16 beta under bf16 params) must get
    cotangents at THEIR dtype — custom_vjp rejects a dtype-mismatched
    return (caught in review; regression)."""
    x = _rand(1, 4, 4, 4, seed=40).astype(jnp.bfloat16)
    wk = _rand(3, 3, 4, 4, seed=41).astype(jnp.bfloat16)
    vec = {"mean": _rand(4, seed=42),
           "mul": jnp.abs(_rand(4, seed=43)) + 0.5,
           "bias": _rand(4, seed=44).astype(jnp.bfloat16)}
    g = jax.grad(lambda v: jnp.sum(fc.fused_conv(
        (x,), wk, v, kernel=(3, 3), mode="bn", relu=True
    ).astype(jnp.float32)))(vec)
    assert g["bias"].dtype == jnp.bfloat16
    assert g["mean"].dtype == jnp.float32
    assert g["mul"].dtype == jnp.float32


def test_fused_conv_int8_dequants_in_kernel():
    """int8 weights + per-channel scale: the kernel casts q exactly
    and folds the scale into the epilogue — matches the dense
    (q*s)-then-conv arm to f32 round-off, at 1/4 the weight bytes."""
    x = _rand(2, 6, 5, 8, seed=16)
    rng = np.random.RandomState(17)
    q = jnp.asarray(np.clip(np.round(rng.randn(3, 3, 8, 16) * 40),
                            -127, 127).astype(np.int8))
    s = jnp.asarray((rng.rand(16) * 0.02 + 0.01).astype(np.float32))
    ref = jax.jit(lambda a: _conv_ref(a, q.astype(jnp.float32) * s))(x)
    got = jax.jit(lambda a: fc.fused_conv(
        (a,), q, {"qscale": s}, kernel=(3, 3)))(x)
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(got - ref).max()) <= 1e-5 * max(scale, 1.0)
    with pytest.raises(ValueError, match="qscale"):
        fc.fused_conv((x,), q, kernel=(3, 3))


def test_fused_conv_validates_shapes():
    x = _rand(1, 4, 4, 8, seed=18)
    wk = _rand(3, 3, 8, 4, seed=19)
    with pytest.raises(ValueError, match="odd kernels"):
        fc.fused_conv((x,), _rand(2, 2, 8, 4, seed=20), kernel=(2, 2))
    with pytest.raises(ValueError, match="does not match"):
        fc.fused_conv((x, x), wk, kernel=(3, 3))
    with pytest.raises(ValueError, match="disagree"):
        fc.fused_conv((x, _rand(1, 5, 4, 8, seed=21)),
                      _rand(3, 3, 16, 4, seed=22), kernel=(3, 3))
    with pytest.raises(ValueError, match="mode"):
        fc.fused_conv((x,), wk, kernel=(3, 3), mode="scale")
    with pytest.raises(ValueError, match="unknown epilogue"):
        fc.fused_conv((x,), wk, {"gamma": x}, kernel=(3, 3))


# -- the ConvBNAct seam ------------------------------------------------


@pytest.mark.parametrize("use_bn,act,dilation,kernel,train", [
    (True, nn.relu, 1, (3, 3), False),   # the dominant block, folded BN
    (True, nn.relu, 2, (3, 3), False),   # dilated (U²-Net RSU4F/bridge)
    (True, nn.relu, 1, (3, 3), True),    # train: fused conv + flax BN
    (True, None, 1, (1, 1), False),      # bottleneck projection shape
    (False, nn.relu, 1, (3, 3), False),  # bias epilogue (plain VGG)
    (True, nn.relu, 1, (4, 4), False),   # even kernel -> per-site xla
])
def test_convbnact_fused_matches_xla_bitwise(use_bn, act, dilation,
                                             kernel, train):
    x = _rand(2, 6, 5, 8, seed=23)
    kw = dict(use_bn=use_bn, act=act, dilation=dilation)
    mx = ConvBNAct(16, kernel, conv_impl="xla", **kw)
    mf = ConvBNAct(16, kernel, conv_impl="fused", **kw)
    v = mx.init(jax.random.key(0), x, train=False)
    vf = mf.init(jax.random.key(0), x, train=False)
    # Init parity: same tree, same values, whichever impl initialised.
    assert jax.tree_util.tree_structure(v) \
        == jax.tree_util.tree_structure(vf)
    for a, b in zip(jax.tree_util.tree_leaves(v),
                    jax.tree_util.tree_leaves(vf)):
        assert jnp.array_equal(a, b)
    if use_bn:  # non-trivial running stats so the fold is exercised
        v["batch_stats"]["BatchNorm_0"]["mean"] = _rand(16, seed=24)
        v["batch_stats"]["BatchNorm_0"]["var"] = jnp.abs(
            _rand(16, seed=25))
    if train:
        yx, sx = jax.jit(lambda v, x: mx.apply(
            v, x, train=True, mutable=["batch_stats"]))(v, x)
        yf, sf = jax.jit(lambda v, x: mf.apply(
            v, x, train=True, mutable=["batch_stats"]))(v, x)
        for a, b in zip(jax.tree_util.tree_leaves(sx),
                        jax.tree_util.tree_leaves(sf)):
            assert _same(a, b)  # the same stat updates
    else:
        yx = jax.jit(lambda v, x: mx.apply(v, x, train=False))(v, x)
        yf = jax.jit(lambda v, x: mf.apply(v, x, train=False))(v, x)
    assert _same(yx, yf), float(jnp.abs(yx - yf).max())


def test_convbnact_list_input_is_concat_on_both_arms():
    """A list input means 'concat along channels': bitwise across
    impls AND vs the caller-side concat the models used to do."""
    a, b = _rand(2, 5, 7, 8, seed=26), _rand(2, 5, 7, 12, seed=27)
    mx = ConvBNAct(16, (3, 3), conv_impl="xla")
    mf = ConvBNAct(16, (3, 3), conv_impl="fused")
    v = mx.init(jax.random.key(1), [a, b], train=False)
    yx = jax.jit(lambda v: mx.apply(v, [a, b], train=False))(v)
    yf = jax.jit(lambda v: mf.apply(v, [a, b], train=False))(v)
    ycat = jax.jit(lambda v: mx.apply(
        v, jnp.concatenate([a, b], -1), train=False))(v)
    assert _same(yx, yf)
    assert jnp.array_equal(yx, ycat)


def test_convbnact_fused_bf16_within_one_ulp():
    """bf16 compute: the kernel accumulates in f32 on the MXU exactly
    as XLA's bf16 conv does — outputs agree to the last bf16 bit."""
    x = _rand(2, 6, 5, 8, seed=28).astype(jnp.bfloat16)
    mx = ConvBNAct(16, (3, 3), conv_impl="xla", dtype=jnp.bfloat16)
    mf = ConvBNAct(16, (3, 3), conv_impl="fused", dtype=jnp.bfloat16)
    v = mx.init(jax.random.key(2), x, train=False)
    yx = jax.jit(lambda v: mx.apply(v, x, train=False))(v)
    yf = jax.jit(lambda v: mf.apply(v, x, train=False))(v)
    # ≤1 ulp: nextafter in bf16 via the int16 view.
    bx = np.asarray(yx).view(np.int16).astype(np.int32)
    bf = np.asarray(yf).view(np.int16).astype(np.int32)
    assert int(np.abs(bx - bf).max()) <= 1


def test_convbnact_grads_match_xla_arm():
    x = _rand(2, 6, 5, 8, seed=29)
    mx = ConvBNAct(16, (3, 3), conv_impl="xla")
    mf = ConvBNAct(16, (3, 3), conv_impl="fused")
    v = mx.init(jax.random.key(3), x, train=False)
    v["batch_stats"]["BatchNorm_0"]["mean"] = _rand(16, seed=30)
    v["batch_stats"]["BatchNorm_0"]["var"] = jnp.abs(_rand(16, seed=31))
    gx = jax.jit(jax.grad(lambda v, x: jnp.sum(
        jnp.sin(mx.apply(v, x, train=False))), (0, 1)))(v, x)
    gf = jax.jit(jax.grad(lambda v, x: jnp.sum(
        jnp.sin(mf.apply(v, x, train=False))), (0, 1)))(v, x)
    for a, b in zip(jax.tree_util.tree_leaves(gx),
                    jax.tree_util.tree_leaves(gf)):
        assert _grads_same(b, a), float(jnp.abs(a - b).max())


class _TwoSite(nn.Module):
    """Two fused-seam sites with different working-set sizes — the
    per-site fallback carrier (narrow 8->8 site under budget, wide
    8->64 site over it; the working set is input+cols dominated, so
    both read 8 channels and only the output width differs)."""

    impl: str = "fused"

    @nn.compact
    def __call__(self, x, train: bool = False):
        y = ConvBNAct(8, (3, 3), conv_impl=self.impl,
                      name="narrow")(x, train)
        return ConvBNAct(64, (3, 3), conv_impl=self.impl,
                         name="wide")(y, train)


def test_vmem_budget_falls_back_per_site_not_globally(monkeypatch,
                                                      caplog):
    """A conv site exceeding the scoped budget must fall back to the
    XLA arm PER-SITE (in-envelope siblings stay fused), keep bitwise
    output, and emit the fused_resample-style loud log line."""
    x = _rand(2, 8, 8, 8, seed=32)
    mx, mf = _TwoSite(impl="xla"), _TwoSite(impl="fused")
    v = mx.init(jax.random.key(4), x, train=False)

    # Per fused_conv_available's pricing: in + xpad + cols + out + w.
    def need(cin, cout):
        return (64 * cin + 100 * cin + 64 * 9 * cin + 64 * cout
                + 9 * cin * cout)

    need_narrow, need_wide = need(8, 8), need(8, 64)
    assert need_narrow < need_wide  # the carrier's premise
    monkeypatch.setattr(fc, "_MAX_TILE_ELEMS",
                        (need_wide + need_narrow) // 2)
    assert fc.fused_conv_available([(2, 8, 8, 8)], (3, 3), 1, 8)
    assert not fc.fused_conv_available([(2, 8, 8, 8)], (3, 3), 1, 64)

    calls = []
    orig = fc.fused_conv

    def spy(parts, w, *a, **k):
        calls.append(w.shape)
        return orig(parts, w, *a, **k)

    monkeypatch.setattr(fc, "fused_conv", spy)
    with caplog.at_level(
            logging.DEBUG,
            logger="distributed_sod_project_tpu.models.layers"):
        yf = mf.apply(v, x, train=False)
    yx = mx.apply(v, x, train=False)
    assert _same(yx, yf)
    assert len(calls) == 1 and calls[0][-1] == 8  # only narrow fused
    assert any("fused conv out of envelope" in r.message
               for r in caplog.records)


def test_conv_compiler_params_follow_the_shared_vmem_rule(monkeypatch):
    """pallas/vmem_budget.py: the raised 100 MB scoped-VMEM ceiling on a
    chip the table knows to have the VMEM for it (v5e), the compiler
    default off-TPU (interpret mode never reads it), an ERROR for a TPU
    kind utils/chips.py has no row for, and DSOD_CONV_VMEM_MB as the
    escape hatch."""
    from distributed_sod_project_tpu.pallas import vmem_budget as vb
    from distributed_sod_project_tpu.utils.chips import UnknownChipError

    monkeypatch.delenv("DSOD_CONV_VMEM_MB", raising=False)
    for kind, want in {"TPU v5 lite": 100 << 20, None: None}.items():
        monkeypatch.setattr(vb, "_device_kind", lambda kind=kind: kind)
        got = getattr(fc._compiler_params(), "vmem_limit_bytes", None)
        assert got == want, (kind, got, want)
    monkeypatch.setattr(vb, "_device_kind", lambda: "TPU v9 ultra")
    with pytest.raises(UnknownChipError):
        fc._compiler_params()
    monkeypatch.setenv("DSOD_CONV_VMEM_MB", "8")
    assert fc._compiler_params().vmem_limit_bytes == 8 << 20
    monkeypatch.setenv("DSOD_CONV_VMEM_MB", "0")
    assert getattr(fc._compiler_params(), "vmem_limit_bytes", None) is None


def test_resolve_conv_impl_is_loud():
    assert _resolve_conv_impl(None) == "xla"
    assert _resolve_conv_impl("xla") == "xla"
    assert _resolve_conv_impl("fused") == "fused"
    with pytest.raises(ValueError, match="conv impl"):
        _resolve_conv_impl("banana")


def test_registry_conv_impl_is_loud_on_non_conv_models():
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.models.registry import build_model

    cfg = get_config("basnet_ds")
    bad = dataclasses.replace(cfg.model, conv_impl="fused")
    with pytest.raises(ValueError, match="only applies to"):
        build_model(bad)
    for name in ("minet_r50_dp", "hdfnet_rgbd", "gatenet_vgg16",
                 "u2net_ds"):
        mc = dataclasses.replace(get_config(name).model,
                                 conv_impl="fused")
        build_model(mc)  # constructs without raising


# -- byte-identity of the default program ------------------------------


class _SeedConvBNAct(nn.Module):
    """VERBATIM copy of ConvBNAct as of PR 11 (pre-seam HEAD) — the
    byte-identity reference: at conv_impl=xla the seam must lower to
    EXACTLY this program."""

    features: int
    kernel = (3, 3)
    strides: int = 1
    dilation: int = 1
    use_bn: bool = True
    act = staticmethod(nn.relu)
    axis_name = None
    bn_momentum: float = 0.9
    dtype = jnp.float32
    param_dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
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


class _Carrier(nn.Module):
    block: type = ConvBNAct

    @nn.compact
    def __call__(self, x, train: bool = False):
        kw = {} if self.block is _SeedConvBNAct else \
            {"conv_impl": "xla"}
        y = self.block(8, name="c0", **kw)(x, train)
        return self.block(4, name="c1", **kw)(y, train)


@pytest.mark.parametrize("train", [False, True])
def test_conv_impl_xla_program_byte_identical_to_seed(train):
    """conv_impl=xla lowers BYTE-IDENTICAL StableHLO to the pre-seam
    ConvBNAct — fwd and the grad program (what the train step lowers),
    so the default arm's compiled step cannot have drifted."""
    x = jnp.zeros((2, 8, 8, 3), jnp.float32)
    texts = []
    for blk in (_SeedConvBNAct, _Carrier.block):
        m = _Carrier(block=blk if blk is _SeedConvBNAct else ConvBNAct)
        v = m.init(jax.random.key(0), x, train=False)
        if train:
            def step(v, x, m=m):
                def loss(p):
                    y, _ = m.apply({**v, "params": p}, x, train=True,
                                   mutable=["batch_stats"])
                    return jnp.sum(y * y)
                return jax.grad(loss)(v["params"])
            lowered = jax.jit(step).lower(v, x)
        else:
            lowered = jax.jit(
                lambda v, x, m=m: m.apply(v, x, train=False)).lower(v, x)
        texts.append(lowered.as_text())
    assert texts[0] == texts[1]


# -- train-step metric invariance (the resample-test posture) ----------


class _MiniConvNet(nn.Module):
    """Smallest net exercising every seam idiom under the real train
    step: plain conv+BN+ReLU, conv+concat (list input), dilated,
    no-BN (bias epilogue), 1x1, and an even-kernel fallback site."""

    impl: str = "xla"
    axis_name: str = "data"

    @nn.compact
    def __call__(self, image, depth=None, *, train: bool = False):
        del depth
        kw = dict(axis_name=self.axis_name, conv_impl=self.impl)
        f1 = ConvBNAct(8, **kw)(image, train)
        f2 = ConvBNAct(8, dilation=2, **kw)(f1, train)
        f3 = ConvBNAct(8, use_bn=False, **kw)(f2, train)
        m = ConvBNAct(8, **kw)([f2, f3], train)          # conv+concat
        m = ConvBNAct(8, (1, 1), act=None, **kw)(m, train)
        m = ConvBNAct(8, (4, 4), **kw)(m, train)         # fallback site
        logit = nn.Conv(1, (3, 3), padding="SAME")(m)
        return [logit.astype(jnp.float32)]


def test_train_metrics_invariant_across_conv_impls():
    """One real shard_map train step per conv_impl arm: identical
    metrics (the execution-strategy-invariance posture of
    tests/test_pallas_resample.py — the knob changes the schedule,
    never the model)."""
    from distributed_sod_project_tpu.configs.base import (LossConfig,
                                                          MeshConfig,
                                                          OptimConfig)
    from distributed_sod_project_tpu.parallel import (
        make_mesh, make_unified_train_step)
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    rng = np.random.RandomState(0)
    batch = {"image": rng.randn(8, 16, 16, 3).astype(np.float32),
             "mask": (rng.rand(8, 16, 16, 1) > 0.5).astype(np.float32)}
    mesh = make_mesh(MeshConfig(data=-1), jax.devices()[:2])
    metrics = {}
    for impl in ("xla", "fused"):
        model = _MiniConvNet(impl=impl)
        tx, sched = build_optimizer(OptimConfig(lr=0.1, warmup_steps=0),
                                    10)
        state = create_train_state(jax.random.key(0), model, tx, batch)
        step = make_unified_train_step(
            model, LossConfig(ssim_window=5), tx, mesh, preset="dp",
            schedule=sched, donate=False)
        _, m = step(state, batch)
        metrics[impl] = {k: float(v) for k, v in m.items()}
    for k, ref in metrics["xla"].items():
        got = metrics["fused"][k]
        assert got == pytest.approx(ref, rel=2e-4, abs=2e-5), (k, got,
                                                               ref)


# -- precision-arm composition ----------------------------------------


def test_fused_conv_cast_variables_quant_view():
    """Site discovery + the quantized apply view: fused-seam conv
    kernels stay int8 with scales in quant_scales; everything else is
    densified; the view's forward tracks the dense int8 arm."""
    from distributed_sod_project_tpu.serve.precision import (
        cast_variables, fused_conv_cast_variables, fused_conv_sites,
        make_precision_forward)

    model = _MiniConvNet(impl="fused", axis_name=None)
    img = np.zeros((1, 16, 16, 3), np.float32)
    v = model.init(jax.random.key(0), jnp.asarray(img), train=False)
    probe = {"image": img}
    sites = fused_conv_sites(model, v, probe)
    # Every ConvBNAct in the carrier routes the seam (fallback sites
    # included — their dense dequant is explicit), the head nn.Conv
    # does not.
    assert len(sites) == 6
    view = fused_conv_cast_variables(model, v, "int8", probe)
    assert "quant_scales" in view
    flat = jax.tree_util.tree_flatten_with_path(view["params"])[0]
    int8_paths = {tuple(str(p.key) for p in path)
                  for path, leaf in flat
                  if jnp.asarray(leaf).dtype == jnp.int8}
    assert len(int8_paths) == 6
    assert all(p[-2:] == ("Conv_0", "kernel") for p in int8_paths)
    # The head conv quantizes in the bundle but is DENSE in this view.
    assert all(not p[0].startswith("Conv_") for p in int8_paths)

    def fwd_view(batch):
        return make_precision_forward(model, "int8", conv_impl="fused")(
            view, batch)

    plain = _MiniConvNet(impl="xla", axis_name=None)
    fwd_dense = make_precision_forward(plain, "int8")
    dense_vars = cast_variables(v, "int8")
    rng = np.random.RandomState(1)
    batch = {"image": rng.rand(2, 16, 16, 3).astype(np.float32)}
    a = np.asarray(fwd_view(batch))
    b = np.asarray(fwd_dense(dense_vars, batch))
    assert np.abs(a - b).max() <= 2e-3  # scale-fold vs dense rounding

    with pytest.raises(ValueError, match="no fused conv sites"):
        fused_conv_cast_variables(plain, v, "int8", probe)


def test_engine_warms_fused_programs_no_request_compile():
    """The serve program cache keys (model, res, batch, resample_impl,
    conv_impl, precision); fused+int8 programs AOT-warm (the int8 arm
    on the in-kernel-dequant weight view) and requests never touch
    .lower() again.  Carried by the cheap 6-site _MiniConvNet through
    the direct constructor — the same engine path from_random_init
    takes, minus a zoo member's compile bill."""
    from distributed_sod_project_tpu.configs import (apply_overrides,
                                                     get_config)
    from distributed_sod_project_tpu.serve.engine import InferenceEngine

    cfg = apply_overrides(get_config("minet_vgg16_ref"), [
        "data.image_size=16,16", "model.conv_impl=fused",
        "model.sync_bn=false", "serve.batch_buckets=1",
        "serve.precision_arms=f32,int8", "serve.precision=int8",
        "serve.max_wait_ms=0.1"])
    model = _MiniConvNet(impl="fused", axis_name=None)
    variables = model.init(
        jax.random.key(0), jnp.zeros((1, 16, 16, 3), jnp.float32),
        train=False)
    engine = InferenceEngine(cfg, model, variables)
    engine.start()
    try:
        keys = set(engine.programs)
        assert ("minet", 16, 1, "fast", "fused", "int8") in keys
        assert ("minet", 16, 1, "fast", "fused", "f32") in keys

        def boom(*a, **k):  # any request-path compile is a bug
            raise AssertionError("request-path lower() after warm")

        for arm in engine.precision_arms:
            engine._fwds[arm] = type("F", (), {"lower": boom})()
        img = (np.random.RandomState(2).rand(16, 16, 3) * 255
               ).astype(np.uint8)
        pred, meta = engine.predict(img, timeout=60)
        assert meta["precision"] == "int8"
        assert pred.shape == (16, 16)
    finally:
        engine.stop()


# -- zoo + lowering ----------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("cfg_name,model_name", [
    ("minet_vgg16_ref", "minet"), ("u2net_ds", "u2net"),
    ("gatenet_vgg16", "gatenet"), ("hdfnet_rgbd", "hdfnet")])
def test_zoo_forward_invariant_across_conv_impls(cfg_name, model_name):
    """Full-model forward invariance for every decoder family:
    block-level parity is bitwise (tests above); through a whole zoo
    member the two graph structures fuse/FMA differently around the
    kernels, so the contract is the resample-arm tolerance."""
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.models.registry import build_model

    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.randn(1, 32, 32, 3).astype(np.float32))
    dep = (jnp.asarray(rng.randn(1, 32, 32, 1).astype(np.float32))
           if model_name == "hdfnet" else None)
    cfg = get_config(cfg_name)
    outs = {}
    for impl in ("xla", "fused"):
        mc = dataclasses.replace(
            cfg.model, conv_impl=impl, sync_bn=False,
            compute_dtype="float32",
            backbone="small" if model_name == "u2net"
            else cfg.model.backbone)
        m = build_model(mc)
        v = m.init(jax.random.key(0), img, dep, train=False)
        outs[impl] = jax.jit(
            lambda v, i, d, m=m: m.apply(v, i, d, train=False)[0]
        )(v, img, dep)
    assert float(jnp.abs(outs["fused"] - outs["xla"]).max()) <= 1e-5


def test_fused_conv_lowers_for_real_tpu():
    """interpret=False + export for platform='tpu' runs the Mosaic
    pipeline end-to-end (no chip needed) — all four kernels: fused
    conv+BN+ReLU, fused conv+concat, the transposed-conv dx kernel,
    and the accumulate-over-grid dw kernel."""
    from jax import export

    x = jnp.zeros((1, 16, 16, 8), jnp.float32)
    x2 = jnp.zeros((1, 16, 16, 4), jnp.float32)
    g = jnp.zeros((1, 16, 16, 12), jnp.float32)
    wk = jnp.zeros((3, 3, 8, 12), jnp.float32)
    wc = jnp.zeros((3, 3, 12, 12), jnp.float32)
    vec = jnp.zeros((12,), jnp.float32)
    bn = {"mean": vec, "mul": vec, "bias": vec}
    spec1 = fc._Spec(3, 3, 1, (8,), "bn", True, ("mean", "mul", "bias"),
                     False)
    spec2 = fc._Spec(3, 3, 1, (8, 4), "none", False, (), False)
    dwspec = fc._Spec(3, 3, 1, (8,), "none", False, (), False)
    for fn, args in [
        (lambda a, w: fc._call_fwd((a,), w, bn, spec1), (x, wk)),
        (lambda a, b, w: fc._call_fwd((a, b), w, {}, spec2), (x, x2, wc)),
        (lambda c, w: fc._call_fwd(
            (c,), fc._flip_transpose(w), {},
            fc._Spec(3, 3, 1, (12,), "none", False, (), False)), (g, wk)),
        (lambda a, c: fc._call_dw((a,), c, dwspec), (x, g)),
    ]:
        exp = export.export(jax.jit(fn), platforms=["tpu"])(*args)
        assert "tpu_custom_call" in exp.mlir_module()
