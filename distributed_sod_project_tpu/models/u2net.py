"""U²-Net — nested U-structure with RSU blocks, 7-level deep supervision.

TPU-native re-design of U²-Net (Qin et al., PR 2020; reference parity
target SURVEY.md §2 C5 and config ``u2net_ds7`` [B:10] — the reference
mount was unreadable, so the topology follows the paper):

- encoder: RSU7→RSU6→RSU5→RSU4→RSU4F→RSU4F with 2× max-pool between
- decoder: mirror RSU stack on concatenated skip connections
- heads: one 1-channel side logit per decoder stage + bottleneck, all
  upsampled to input resolution, plus a fused logit from their concat
  → returns **7 logits**, element 0 the fused (primary) prediction.

TPU notes: every RSU's inner U-loop is a static Python loop over a
fixed depth, so the whole net traces to one static XLA graph; convs are
NHWC/bf16 on the MXU; the dilated RSU4F variant trades pooling for
dilation so the deepest stages keep spatial extent without dynamic
shapes.
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from .layers import (ConvBNAct, max_pool, resample_merge, resize_to,
                     upsample_like)


class RSU(nn.Module):
    """Residual U-block: depth-``levels`` U-net with a residual skip."""

    levels: int  # e.g. 7 for RSU7
    mid: int
    out: int
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    resample_impl: str = "fast"
    conv_impl: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        kw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                  conv_impl=self.conv_impl,
                  dtype=self.dtype, param_dtype=self.param_dtype)
        xin = ConvBNAct(self.out, (3, 3), **kw)(x, train)

        # Contracting path: levels-1 encoder stages (pool between).
        enc = [ConvBNAct(self.mid, (3, 3), **kw)(xin, train)]
        for _ in range(self.levels - 2):
            enc.append(ConvBNAct(self.mid, (3, 3), **kw)(max_pool(enc[-1]), train))
        # Bottom: dilated conv at the coarsest resolution.
        d = ConvBNAct(self.mid, (3, 3), dilation=2, **kw)(enc[-1], train)
        # Expanding path: merge with skips, upsample back.
        for i in range(self.levels - 2, -1, -1):
            d = ConvBNAct(
                self.mid if i > 0 else self.out, (3, 3), **kw
            )([d, enc[i]], train)
            if i > 0:
                d = upsample_like(d, enc[i - 1], impl=self.resample_impl)
        return d + xin


class RSU4F(nn.Module):
    """Dilated RSU: fixed resolution, dilation 1/2/4/8 instead of pooling."""

    mid: int
    out: int
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    conv_impl: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        kw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                  conv_impl=self.conv_impl,
                  dtype=self.dtype, param_dtype=self.param_dtype)
        xin = ConvBNAct(self.out, (3, 3), **kw)(x, train)
        e1 = ConvBNAct(self.mid, (3, 3), dilation=1, **kw)(xin, train)
        e2 = ConvBNAct(self.mid, (3, 3), dilation=2, **kw)(e1, train)
        e3 = ConvBNAct(self.mid, (3, 3), dilation=4, **kw)(e2, train)
        b = ConvBNAct(self.mid, (3, 3), dilation=8, **kw)(e3, train)
        d3 = ConvBNAct(self.mid, (3, 3), dilation=4, **kw)(
            [b, e3], train)
        d2 = ConvBNAct(self.mid, (3, 3), dilation=2, **kw)(
            [d3, e2], train)
        d1 = ConvBNAct(self.out, (3, 3), dilation=1, **kw)(
            [d2, e1], train)
        return d1 + xin


class U2Net(nn.Module):
    """Full U²-Net.  ``small=True`` gives the U²-Net† (lite) widths."""

    small: bool = False
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    # Decoder resample strategy (model.resample_impl):
    # fast | xla | fused — see layers.resample_merge.
    resample_impl: str = "fast"
    # Conv-block strategy (model.conv_impl): xla | fused — see
    # layers.ConvBNAct; threaded to every RSU conv block.
    conv_impl: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, image, depth=None, *, train: bool = False) -> List[jnp.ndarray]:
        del depth  # RGB-only model; uniform zoo signature
        x = image.astype(self.dtype)
        kw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                  conv_impl=self.conv_impl,
                  dtype=self.dtype, param_dtype=self.param_dtype)
        # RSU blocks resample internally; RSU4F is resolution-fixed.
        rkw = dict(resample_impl=self.resample_impl, **kw)
        if self.small:
            # U²-Net†: every stage 16/64.
            enc_spec = [(7, 16, 64), (6, 16, 64), (5, 16, 64), (4, 16, 64)]
            f_mid, f_out = 16, 64
            dec_spec = [(4, 16, 64), (5, 16, 64), (6, 16, 64), (7, 16, 64)]
        else:
            enc_spec = [(7, 32, 64), (6, 32, 128), (5, 64, 256), (4, 128, 512)]
            f_mid, f_out = 256, 512
            dec_spec = [(4, 128, 256), (5, 64, 128), (6, 32, 64), (7, 16, 64)]

        # Encoder: 4 RSU stages + 2 dilated stages, pooling between all 6.
        with jax.named_scope("dsod.encoder"):
            feats = []
            h = x
            for lv, mid, out in enc_spec:
                h = RSU(lv, mid, out, **rkw)(h, train)
                feats.append(h)
                h = max_pool(h)
            h = RSU4F(f_mid, f_out, **kw)(h, train)
            feats.append(h)
            h = max_pool(h)
            h = RSU4F(f_mid, f_out, **kw)(h, train)  # En_6 (bottleneck)

        # Decoder: RSU4F then the mirrored RSU stack on concat skips.
        with jax.named_scope("dsod.decoder"):
            sides = [h]  # bottleneck side output source
            d = RSU4F(f_mid, f_out, **kw)(
                resample_merge(h, feats[4], mode="concat",
                               impl=self.resample_impl), train)
            sides.append(d)
            for (lv, mid, out), skip in zip(dec_spec, feats[3::-1]):
                d = RSU(lv, mid, out, **rkw)(
                    resample_merge(d, skip, mode="concat",
                                   impl=self.resample_impl), train)
                sides.append(d)

        # Side heads: 3x3 conv → 1ch logit, upsampled to input resolution.
        hw = image.shape[1:3]
        logits = []
        with jax.named_scope("dsod.heads"):
            for s in reversed(sides):  # finest (d1) first
                l = nn.Conv(1, (3, 3), padding="SAME", dtype=self.dtype,
                            param_dtype=self.param_dtype)(s)
                logits.append(resize_to(l, hw, impl=self.resample_impl)
                              .astype(jnp.float32))
            # Fused head over all 6 side logits.
            fused = nn.Conv(1, (1, 1), dtype=self.dtype,
                            param_dtype=self.param_dtype)(
                jnp.concatenate([l.astype(self.dtype) for l in logits],
                                axis=-1))
        return [fused.astype(jnp.float32)] + logits
