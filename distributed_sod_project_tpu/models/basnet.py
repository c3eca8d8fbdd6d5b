"""BASNet — boundary-aware SOD: predict module + residual refinement.

TPU-native re-design of BASNet (Qin et al., CVPR 2019; reference parity
target SURVEY.md §2 C5, deep-supervision config [B:10] — reference mount
unreadable, topology per the paper):

- predict module: ResNet34-style encoder kept at full input resolution
  through stage 1 (3×3/1 stem, no pooling), two extra 512-wide stages
  past the backbone, a dilated bridge, and a mirrored decoder with a
  side head at every depth
- refine module (RRM): a small full-resolution encoder–decoder whose
  output is a *residual* added to the coarse saliency logit

Returns **8 logits**: element 0 the refined prediction, element 1 the
coarse predict-module output, then the deeper side outputs — all at
input resolution so ``deep_supervision_loss`` consumes them uniformly.

TPU notes: the encoder is pure 3×3 convs (MXU-friendly); the refinement
residual is elementwise and fuses into the surrounding graph; every
upsample + skip concat goes through ``layers.resample_merge`` and every
side logit through ``layers.resize_to``, which pick a one-pass route
from the shape (Pallas kernel / lane-dense matmuls).
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from .backbones.resnet import BasicBlock
from .layers import ConvBNAct, max_pool, resample_merge, resize_to


class _DecoderStage(nn.Module):
    """Three ConvBNActs on the concat of the upsampled path and the skip."""

    width: int
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, d, skip, train: bool = False):
        kw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                  dtype=self.dtype, param_dtype=self.param_dtype)
        x = resample_merge(d, skip, mode="concat")
        for _ in range(3):
            x = ConvBNAct(self.width, (3, 3), **kw)(x, train)
        return x


class RefineModule(nn.Module):
    """RRM: 4-level encoder–decoder producing a residual logit."""

    width: int = 64
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, logit, train: bool = False):
        kw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                  dtype=self.dtype, param_dtype=self.param_dtype)
        x = ConvBNAct(self.width, (3, 3), **kw)(logit.astype(self.dtype), train)
        skips = []
        for _ in range(4):
            x = ConvBNAct(self.width, (3, 3), **kw)(x, train)
            skips.append(x)
            x = max_pool(x)
        x = ConvBNAct(self.width, (3, 3), **kw)(x, train)
        for skip in reversed(skips):
            x = ConvBNAct(self.width, (3, 3), **kw)(
                resample_merge(x, skip, mode="concat"), train)
        res = nn.Conv(1, (3, 3), padding="SAME", dtype=self.dtype,
                      param_dtype=self.param_dtype)(x)
        return logit + res.astype(jnp.float32)


class BASNet(nn.Module):
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, image, depth=None, *, train: bool = False) -> List[jnp.ndarray]:
        del depth  # RGB-only model; uniform zoo signature
        x = image.astype(self.dtype)
        kw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                  dtype=self.dtype, param_dtype=self.param_dtype)

        # --- predict-module encoder ---------------------------------
        # Stem at full resolution (3×3/1 — BASNet keeps stage 1 unpooled).
        with jax.named_scope("dsod.encoder"):
            x = ConvBNAct(64, (3, 3), **kw)(x, train)
            feats = []
            stage_blocks = [(3, 64, 1), (4, 128, 2), (6, 256, 2), (3, 512, 2)]
            for n, width, first_stride in stage_blocks:
                for i in range(n):
                    x = BasicBlock(width,
                                   strides=first_stride if i == 0 else 1,
                                   **kw)(x, train)
                feats.append(x)  # strides 1, 2, 4, 8
            for _ in range(2):  # extra stages → strides 16, 32
                x = max_pool(x)
                for _ in range(3):
                    x = BasicBlock(512, **kw)(x, train)
                feats.append(x)

        with jax.named_scope("dsod.decoder"):
            # Bridge: dilated 512 convs at the coarsest resolution.
            b = x
            for _ in range(3):
                b = ConvBNAct(512, (3, 3), dilation=2, **kw)(b, train)

            # --- decoder with side heads ----------------------------
            widths = [512, 512, 512, 256, 128, 64]
            d = b
            stages = [b]
            for width, skip in zip(widths, reversed(feats)):
                d = _DecoderStage(width, **kw)(d, skip, train)
                stages.append(d)

        hw = image.shape[1:3]
        side_logits = []
        with jax.named_scope("dsod.heads"):
            for s in reversed(stages):  # finest decoder stage first, bridge last
                l = nn.Conv(1, (3, 3), padding="SAME", dtype=self.dtype,
                            param_dtype=self.param_dtype)(s)
                side_logits.append(resize_to(l, hw).astype(jnp.float32))

            refined = RefineModule(axis_name=self.axis_name,
                                   bn_momentum=self.bn_momentum,
                                   dtype=self.dtype,
                                   param_dtype=self.param_dtype)(
                side_logits[0], train)
        return [refined] + side_logits
