"""Plain building blocks of the benchmark's references.

Straightforward ``jax.numpy`` / ``lax.conv_general_dilated`` in float32
at matmul precision HIGHEST: no kernels, no fusion tricks, nothing
imported from the program.  The weights are the benchmark's own
(``harness/weights.py``); they arrive as a nested dict under the
program's parameter names, which :class:`Scope` walks the way flax
numbers its children (``<Class>_<k>``, k counted per class in order of
construction).

``prec`` selects the arithmetic of every convolution:

- ``f32``  — the reference proper;
- ``bf16`` — operands (and their cotangents) rounded to bfloat16,
  float32 accumulation (what the configurations state; a sanity arm,
  must pass);
- ``fp8``  — the control, the nearest precision below bfloat16, as a
  float8 training step computes it: the forward's operands
  fake-quantised to float8_e4m3fn, the incoming cotangent of both
  backward convolutions to float8_e5m2, one scale per tensor, float32
  accumulation.  It has to come out NOT correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
BN_EPS = 1e-5  # flax.linen.BatchNorm default, which the program keeps


class Scope:
    """Cursor over (params, batch_stats) under flax's auto-names."""

    def __init__(self, params, stats=None):
        self.params, self.stats = params, stats or {}
        self._n = {}

    def sub(self, cls: str) -> "Scope":
        k = self._n.get(cls, 0)
        self._n[cls] = k + 1
        name = f"{cls}_{k}"
        return Scope(self.params[name], self.stats.get(name, {}))


def _fake_fp8(x, dtype=jnp.float8_e4m3fn):
    """Round to ``dtype`` and back, one scale per tensor."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = float(jnp.finfo(dtype).max) / amax
    return (x * s).astype(dtype).astype(jnp.float32) / s


def _conv(x, w, stride, dilation):
    kh, kw = w.shape[:2]
    pad = [(dilation * (kh // 2),) * 2, (dilation * (kw // 2),) * 2]
    return lax.conv_general_dilated(
        x, w, (stride, stride), pad, rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
        preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv_fp8(x, w, stride, dilation):
    return _conv(_fake_fp8(x), _fake_fp8(w), stride, dilation)


def _conv_fp8_fwd(x, w, stride, dilation):
    return jax.vjp(lambda a, b: _conv(a, b, stride, dilation),
                   _fake_fp8(x), _fake_fp8(w))


def _conv_fp8_bwd(stride, dilation, vjp, g):
    return vjp(_fake_fp8(g, jnp.float8_e5m2))


_conv_fp8.defvjp(_conv_fp8_fwd, _conv_fp8_bwd)


def conv(x, w, *, stride=1, dilation=1, prec="f32"):
    """NHWC x HWIO, symmetric padding dilation*(k//2) (= torch's)."""
    if prec == "fp8":
        return _conv_fp8(x, w, stride, dilation)
    if prec == "bf16":
        x, w = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (x, w))
    elif prec != "f32":
        raise ValueError(f"unknown reference precision {prec!r}")
    return _conv(x, w, stride, dilation)


def batch_norm(x, sc: Scope, train: bool):
    """Train: statistics of the whole (global) batch, biased variance.
    Eval: the running statistics."""
    if train:
        mean = x.mean((0, 1, 2))
        var = jnp.square(x - mean).mean((0, 1, 2))
    else:
        mean, var = sc.stats["mean"], sc.stats["var"]
    y = (x - mean) * lax.rsqrt(var + BN_EPS)
    return y * sc.params["scale"] + sc.params["bias"]


def conv_bn_act(x, sc: Scope, train, *, stride=1, dilation=1, act=True,
                prec="f32"):
    """One ``ConvBNAct`` scope: Conv_0 (no bias) + BatchNorm_0 + ReLU."""
    y = conv(x, sc.sub("Conv").params["kernel"], stride=stride,
             dilation=dilation, prec=prec)
    y = batch_norm(y, sc.sub("BatchNorm"), train)
    return jnp.maximum(y, 0.0) if act else y


def head_conv(x, sc: Scope, prec="f32"):
    """A bare 3x3 ``Conv`` scope with bias (the 1-channel heads)."""
    return conv(x, sc.params["kernel"], prec=prec) + sc.params["bias"]


def max_pool(x, window=2, stride=2, pad=0):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1),
        (1, stride, stride, 1),
        ((0, 0), (pad, pad), (pad, pad), (0, 0)))


def basic_block(x, sc: Scope, train, *, features, stride=1, prec="f32"):
    y = conv_bn_act(x, sc.sub("ConvBNAct"), train, stride=stride, prec=prec)
    y = conv_bn_act(y, sc.sub("ConvBNAct"), train, act=False, prec=prec)
    r = x
    if x.shape[-1] != features or stride != 1:
        r = conv_bn_act(x, sc.sub("ConvBNAct"), train, stride=stride,
                        act=False, prec=prec)
    return jnp.maximum(y + r, 0.0)


def bottleneck(x, sc: Scope, train, *, features, stride=1, prec="f32"):
    out = 4 * features
    y = conv_bn_act(x, sc.sub("ConvBNAct"), train, prec=prec)
    y = conv_bn_act(y, sc.sub("ConvBNAct"), train, stride=stride, prec=prec)
    y = conv_bn_act(y, sc.sub("ConvBNAct"), train, act=False, prec=prec)
    r = x
    if x.shape[-1] != out or stride != 1:
        r = conv_bn_act(x, sc.sub("ConvBNAct"), train, stride=stride,
                        act=False, prec=prec)
    return jnp.maximum(y + r, 0.0)


@functools.lru_cache(maxsize=None)
def _resize_matrix(n_in: int, n_out: int) -> np.ndarray:
    """Rows of triangle-filter weights, half-pixel centres; the filter
    widens by n_in/n_out when shrinking (antialias) and each row is
    renormalised over its in-range taps."""
    scale = n_in / n_out
    support = max(scale, 1.0)
    centre = (np.arange(n_out) + 0.5) * scale
    taps = np.arange(n_in) + 0.5
    w = np.clip(1.0 - np.abs(taps[None, :] - centre[:, None]) / support,
                0.0, None)
    return (w / w.sum(1, keepdims=True)).astype(np.float32)


def resize(x, hw):
    """Bilinear resize of NHWC ``x`` to ``hw`` as two matrix products."""
    h, w = x.shape[1:3]
    if (h, w) == tuple(hw):
        return x
    a = jnp.asarray(_resize_matrix(h, hw[0]))
    b = jnp.asarray(_resize_matrix(w, hw[1]))
    x = jnp.einsum("oh,bhwc->bowc", a, x, precision=HI)
    return jnp.einsum("pw,bowc->bopc", b, x, precision=HI)


# -- losses (per image where the papers say so, then averaged) ---------

def bce_loss(logit, target):
    x, t = logit, target
    return (jnp.maximum(x, 0.0) - x * t
            + jnp.log1p(jnp.exp(-jnp.abs(x)))).mean()


def iou_loss(logit, target):
    p = jax.nn.sigmoid(logit).reshape(logit.shape[0], -1)
    t = target.reshape(target.shape[0], -1)
    inter = (p * t).sum(-1)
    union = p.sum(-1) + t.sum(-1) - inter
    return (1.0 - (inter + 1.0) / (union + 1.0)).mean()


def cel_loss(logit, target):
    p = jax.nn.sigmoid(logit).reshape(logit.shape[0], -1)
    t = target.reshape(target.shape[0], -1)
    inter = (p * t).sum(-1)
    total = p.sum(-1) + t.sum(-1)
    return ((total - 2.0 * inter) / (total + 1e-6)).mean()


def _gauss_blur(x, size, sigma):
    g = np.exp(-((np.arange(size) - size // 2) ** 2) / (2.0 * sigma ** 2))
    g = jnp.asarray((g / g.sum()).astype(np.float32))
    c = x.shape[-1]
    p = size // 2
    for kern, pad in ((g.reshape(size, 1, 1, 1), [(p, p), (0, 0)]),
                      (g.reshape(1, size, 1, 1), [(0, 0), (p, p)])):
        x = lax.conv_general_dilated(
            x, jnp.tile(kern, (1, 1, 1, c)), (1, 1), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=c, precision=HI)
    return x


def ssim_loss(logit, target, size=11, sigma=1.5):
    a, b = jax.nn.sigmoid(logit), target
    blur = functools.partial(_gauss_blur, size=size, sigma=sigma)
    mu_a, mu_b = blur(a), blur(b)
    var_a = blur(a * a) - mu_a * mu_a
    var_b = blur(b * b) - mu_b * mu_b
    cov = blur(a * b) - mu_a * mu_b
    c1, c2 = 0.01 ** 2, 0.03 ** 2
    num = (2.0 * mu_a * mu_b + c1) * (2.0 * cov + c2)
    den = (mu_a * mu_a + mu_b * mu_b + c1) * (var_a + var_b + c2)
    return 1.0 - (num / den).mean()


def hybrid_loss(logits, target, w):
    """Sum over the supervised outputs of the weighted terms; ``w`` is
    the configuration file's ``loss`` object."""
    total = jnp.float32(0.0)
    for lg in logits:
        if w.get("bce"):
            total += w["bce"] * bce_loss(lg, target)
        if w.get("iou"):
            total += w["iou"] * iou_loss(lg, target)
        if w.get("cel"):
            total += w["cel"] * cel_loss(lg, target)
        if w.get("ssim"):
            total += w["ssim"] * ssim_loss(lg, target,
                                           size=w.get("ssim_window", 11))
    return total


# -- optimizers, as published; the schedule is poly decay --------------

def poly_lr(opt, step):
    t = jnp.minimum(step / opt["total_steps"], 1.0)
    return opt["lr"] * (1.0 - t) ** opt["poly_power"]


def opt_init(opt, params):
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    if opt["kind"] == "adamw":
        return {"m": zeros(), "v": zeros()}
    return {"m": zeros()}


def opt_update(opt, params, grads, state, step):
    """One update at 0-based ``step``.  Weight decay touches kernels
    only (rank >= 2), as the configurations state."""
    lr = poly_lr(opt, step)
    wd = opt.get("weight_decay", 0.0)
    tm = jax.tree_util.tree_map
    decayed = lambda p: wd if (wd and p.ndim >= 2) else 0.0  # noqa: E731
    if opt["kind"] == "sgd":
        mom = opt["momentum"]
        g = tm(lambda g, p: g + decayed(p) * p, grads, params)
        m = tm(lambda g, m: g + mom * m, g, state["m"])
        u = tm(lambda g, m: g + mom * m, g, m) if opt["nesterov"] else m
        return tm(lambda p, u: p - lr * u, params, u), {"m": m}
    if opt["kind"] == "adamw":
        b1, b2, eps = 0.9, 0.999, 1e-8
        m = tm(lambda g, m: b1 * m + (1 - b1) * g, grads, state["m"])
        v = tm(lambda g, v: b2 * v + (1 - b2) * g * g, grads, state["v"])
        c1, c2 = 1 - b1 ** (step + 1), 1 - b2 ** (step + 1)
        new = tm(lambda p, m, v: p - lr * (m / c1 / (jnp.sqrt(v / c2) + eps)
                                           + decayed(p) * p),
                 params, m, v)
        return new, {"m": m, "v": v}
    raise ValueError(f"unknown optimizer {opt['kind']!r}")
