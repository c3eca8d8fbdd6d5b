"""Swin-SOD — transformer-encoder saliency model (stretch config [B:11]).

Swin-T pyramid (strides 4/8/16/32) + FPN-style top-down decoder:
lateral 1×1 projections, upsample-add, 3×3 smoothing per level, primary
head at stride 4, deep-supervision heads at strides 8/16.  Returns 3
logits at input resolution, element 0 primary (zoo convention).
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from .backbones.swin import SwinT
from .layers import ConvBNAct, resize_to, upsample_like


class SwinSOD(nn.Module):
    width: int = 128
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, image, depth=None, *, train: bool = False) -> List[jnp.ndarray]:
        del depth  # RGB-only model; uniform zoo signature
        x = image.astype(self.dtype)
        with jax.named_scope("dsod.encoder"):
            feats = SwinT(dtype=self.dtype, param_dtype=self.param_dtype)(
                x, train=train)

        kw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                  dtype=self.dtype, param_dtype=self.param_dtype)
        with jax.named_scope("dsod.decoder"):
            laterals = [ConvBNAct(self.width, (1, 1), **kw)(f, train)
                        for f in feats]

            d = laterals[-1]
            sides = [d]
            for lat in laterals[-2::-1]:
                d = upsample_like(d, lat) + lat
                d = ConvBNAct(self.width, (3, 3), **kw)(d, train)
                sides.append(d)

        hw = image.shape[1:3]
        logits = []
        # Primary = finest (stride 4); aux at strides 8 and 16.
        with jax.named_scope("dsod.heads"):
            for s in (sides[-1], sides[-2], sides[-3]):
                l = nn.Conv(1, (3, 3), padding="SAME", dtype=self.dtype,
                            param_dtype=self.param_dtype)(s)
                logits.append(resize_to(l, hw).astype(jnp.float32))
        return logits
