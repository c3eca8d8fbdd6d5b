"""Plain reference of MINet-ResNet50 (Pang et al., CVPR 2020).

Backbone pyramid (stem /2 and the four bottleneck stages, /4../32) ->
AIM at every level (the level fused with its resampled neighbours) ->
top-down decoder with one SIM per level -> 32-wide head -> one logit at
the input size.  Written from the paper's description; departures, all
following the program's registered configuration so that one set of
weights serves both:

- every conv block is conv + BatchNorm + ReLU at 64 decoder channels,
  and the AIM/SIM branch widths are 64 / 32 as the configuration has
  them (the paper's SIM uses the same two-resolution exchange);
- resampling is bilinear with half-pixel centres (antialiased when
  shrinking), pooling is 2x2/2 max.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops
from .ops import Scope, conv_bn_act

STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))  # ResNet-50


def _unit(fn, remat):
    return jax.checkpoint(fn) if remat else fn


def _backbone(x, sc: Scope, train, prec, remat):
    feats = []
    x = conv_bn_act(x, sc.sub("ConvBNAct"), train, stride=2, prec=prec)
    feats.append(x)
    x = ops.max_pool(x, 3, 2, pad=1)
    for stage, (n, width) in enumerate(STAGES):
        for i in range(n):
            stride = 2 if (i == 0 and stage > 0) else 1
            blk = sc.sub("Bottleneck")
            x = _unit(lambda x, blk=blk, stride=stride, width=width:
                      ops.bottleneck(x, blk, train, features=width,
                                     stride=stride, prec=prec), remat)(x)
        feats.append(x)
    return feats


def _aim(below, cur, above, sc: Scope, train, prec):
    parts = [conv_bn_act(cur, sc.sub("ConvBNAct"), train, prec=prec)]
    hw = cur.shape[1:3]
    if below is not None:
        parts.append(ops.resize(
            conv_bn_act(below, sc.sub("ConvBNAct"), train, prec=prec), hw))
    if above is not None:
        parts.append(ops.resize(
            conv_bn_act(above, sc.sub("ConvBNAct"), train, prec=prec), hw))
    return conv_bn_act(jnp.concatenate(parts, -1), sc.sub("ConvBNAct"),
                       train, prec=prec)


def _sim(x, sc: Scope, train, prec):
    c = [sc.sub("ConvBNAct") for _ in range(7)]
    cba = lambda t, s: conv_bn_act(t, s, train, prec=prec)  # noqa: E731
    h = cba(x, c[0])
    l = ops.max_pool(cba(x, c[1]))
    # exchange: each branch receives the other, resampled
    h2 = cba(ops.resize(cba(l, c[3]), h.shape[1:3]) + h, c[2])
    l2 = cba(l + ops.max_pool(cba(h, c[5])), c[4])
    merged = jnp.concatenate([h2, ops.resize(l2, h2.shape[1:3])], -1)
    return cba(merged, c[6])


def forward(variables, image, *, train: bool, prec: str = "f32",
            remat: bool = False):
    """``variables`` = {"params", "batch_stats"} under the program's
    names; returns the list of logits (one), float32, input size."""
    sc = Scope(variables["params"], variables.get("batch_stats"))
    # Children are numbered per class in order of construction:
    # ResNet_0, AIM_0..4, SIM_0..4, ConvBNAct_0, Conv_0.
    feats = _backbone(image, sc.sub("ResNet"), train, prec, remat)
    agg = []
    for i, f in enumerate(feats):
        below = feats[i - 1] if i > 0 else None
        above = feats[i + 1] if i < len(feats) - 1 else None
        s = sc.sub("AIM")
        agg.append(_unit(lambda b, c, a, s=s: _aim(b, c, a, s, train, prec),
                         remat)(below, f, above))
    s = sc.sub("SIM")
    d = _unit(lambda d, s=s: _sim(d, s, train, prec), remat)(agg[-1])
    for i in range(len(agg) - 2, -1, -1):
        d = ops.resize(d, agg[i].shape[1:3]) + agg[i]
        s = sc.sub("SIM")
        d = _unit(lambda d, s=s: _sim(d, s, train, prec), remat)(d)
    h = conv_bn_act(d, sc.sub("ConvBNAct"), train, prec=prec)
    logit = ops.head_conv(h, sc.sub("Conv"), prec=prec)
    return [ops.resize(logit, image.shape[1:3])]
