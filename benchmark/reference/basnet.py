"""Plain reference of BASNet (Qin et al., CVPR 2019).

Predict module: a ResNet-34 encoder kept at the input size through its
first stage (3x3/1 stem, no pooling), two further 512-wide stages behind
2x2 pooling, a dilated bridge, a mirrored decoder of three conv blocks
per stage with a 1-channel side head at every depth.  Refine module: a
four-level 64-wide encoder-decoder whose output is a residual on the
finest side logit.  Eight supervised outputs, refined first, all at the
input size.  Written from the paper; departures, following the
program's registered configuration so that one set of weights serves
both: BatchNorm in every block incl. the refine module's, bilinear
resampling with half-pixel centres.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops
from .ops import Scope, conv_bn_act

ENCODER = ((3, 64, 1), (4, 128, 2), (6, 256, 2), (3, 512, 2))
DECODER = (512, 512, 512, 256, 128, 64)


def _unit(fn, remat):
    return jax.checkpoint(fn) if remat else fn


def _decoder_stage(d, skip, sc: Scope, train, prec):
    x = jnp.concatenate([ops.resize(d, skip.shape[1:3]), skip], -1)
    for _ in range(3):
        x = conv_bn_act(x, sc.sub("ConvBNAct"), train, prec=prec)
    return x


def _refine(logit, sc: Scope, train, prec, remat):
    def cba(t):
        s = sc.sub("ConvBNAct")  # ConvBNAct_0.. in order of use
        return _unit(lambda t: conv_bn_act(t, s, train, prec=prec), remat)(t)

    x = cba(logit)
    skips = []
    for _ in range(4):
        x = cba(x)
        skips.append(x)
        x = ops.max_pool(x)
    x = cba(x)
    for skip in reversed(skips):
        x = cba(jnp.concatenate([ops.resize(x, skip.shape[1:3]), skip], -1))
    return logit + ops.head_conv(x, sc.sub("Conv"), prec=prec)


def forward(variables, image, *, train: bool, prec: str = "f32",
            remat: bool = False):
    sc = Scope(variables["params"], variables.get("batch_stats"))
    x = conv_bn_act(image, sc.sub("ConvBNAct"), train, prec=prec)

    def block(x, features, stride=1):
        s = sc.sub("BasicBlock")
        return _unit(lambda x: ops.basic_block(
            x, s, train, features=features, stride=stride, prec=prec),
            remat)(x)

    feats = []
    for n, width, first in ENCODER:
        for i in range(n):
            x = block(x, width, first if i == 0 else 1)
        feats.append(x)
    for _ in range(2):
        x = ops.max_pool(x)
        for _ in range(3):
            x = block(x, 512)
        feats.append(x)
    b = x
    for _ in range(3):
        b = conv_bn_act(b, sc.sub("ConvBNAct"), train, dilation=2, prec=prec)
    d, stages = b, [b]
    for width, skip in zip(DECODER, reversed(feats)):
        s = sc.sub("_DecoderStage")
        d = _unit(lambda d, skip, s=s: _decoder_stage(d, skip, s, train,
                                                      prec), remat)(d, skip)
        stages.append(d)
    hw = image.shape[1:3]
    sides = [ops.resize(ops.head_conv(s, sc.sub("Conv"), prec=prec), hw)
             for s in reversed(stages)]
    refined = _refine(sides[0], sc.sub("RefineModule"), train, prec, remat)
    return [refined] + sides
