"""GateNet — gated encoder→decoder information flow for SOD.

TPU-native re-design following the paper description of "Suppress and
Balance: A Simple Gated Network for Salient Object Detection" (ECCV
2020, Zhao et al. — lartpang is an author, which is why this member
belongs in a Distributed-SOD-Project parity zoo; SURVEY.md §2 C5 names
the reference zoo and this extends it).  The reference mount was
unreadable (SURVEY.md banner), so as with the rest of the zoo the
module follows the paper's architectural signature, implemented
TPU-first:

- backbone (VGG16 / ResNet50) → 5-level pyramid, per-level 3×3
  transfer convs to a fixed decoder width.
- **gate units**: at every skip connection a sigmoid gate computed
  from (encoder feature, upsampled decoder state) multiplicatively
  suppresses background activations before the skip enters the
  decoder — the paper's core idea (balance information flow between
  levels instead of passing raw skips).
- **dilated-pyramid bridge** on the deepest level standing in for the
  paper's Fold-ASPP: parallel 3×3 convs at dilations (1, 2, 4, 6)
  plus a global-context branch, concatenated and fused 1×1.  The
  paper's "fold" im2col step is a gather-heavy op that maps poorly to
  the MXU; dilated convs express the same receptive-field pyramid as
  native XLA convolutions (documented TPU-first substitution, same
  posture as HDFNet's im2col+einsum dynamic filters).
- **dual-branch heads with deep supervision**: every decoder stage
  emits a side logit (5 outputs); element 0 is the finest/primary —
  the zoo-uniform list-of-logits contract.

Conventions: NHWC, bf16 compute / f32 params, cross-replica BN via
``axis_name`` (SyncBN parity), all resizes static-shape.
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from .backbones import ResNet50, VGG16
from .layers import ConvBNAct, resize_to, upsample_like


class GateUnit(nn.Module):
    """Multiplicative skip gate: sigmoid over a fused (enc, dec) view
    suppresses encoder activations the decoder state marks as
    background."""

    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    conv_impl: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, enc, dec, train: bool = False):
        # (enc, dec) convolve as their channel concat — the ConvBNAct
        # seam fuses the concat away on the fused arm.
        gate = ConvBNAct(enc.shape[-1], (3, 3), act=None,
                         axis_name=self.axis_name,
                         bn_momentum=self.bn_momentum,
                         conv_impl=self.conv_impl, dtype=self.dtype,
                         param_dtype=self.param_dtype)([enc, dec],
                                                       train=train)
        return enc * nn.sigmoid(gate)


class DilatedPyramidBridge(nn.Module):
    """ASPP-style bridge: dilations (1, 2, 4, 6) + global context."""

    width: int
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
        branches = [
            ConvBNAct(self.width, (3, 3), dilation=d, **kw)(x, train=train)
            for d in (1, 2, 4, 6)
        ]
        # Global-context branch: pooled statistics broadcast back.
        g = jnp.mean(x, axis=(1, 2), keepdims=True)
        g = ConvBNAct(self.width, (1, 1), **kw)(g, train=train)
        branches.append(jnp.broadcast_to(
            g, x.shape[:3] + (self.width,)).astype(g.dtype))
        return ConvBNAct(self.width, (1, 1), **kw)(branches, train=train)


class GateNet(nn.Module):
    """Gated SOD network.  Returns five logits (finest first)."""

    backbone: str = "vgg16"
    backbone_bn: bool = True
    width: int = 64
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    # Decoder resample strategy (model.resample_impl): fast | xla |
    # fused.  GateNet's decoder reuses the upsampled state twice (gate
    # input AND skip concat), so the fused arm runs the BARE
    # single-pass upsample kernel (no merge epilogue) here.
    resample_impl: str = "fast"
    # Conv-block strategy (model.conv_impl): xla | fused — see
    # layers.ConvBNAct; threaded to every conv block, backbone included.
    conv_impl: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, image, depth=None, *,
                 train: bool = False) -> List[jnp.ndarray]:
        del depth  # RGB-only member; uniform zoo signature
        x = image.astype(self.dtype)
        bkw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                   conv_impl=self.conv_impl,
                   dtype=self.dtype, param_dtype=self.param_dtype)
        with jax.named_scope("dsod.encoder"):
            if self.backbone == "vgg16":
                feats = VGG16(use_bn=self.backbone_bn, **bkw)(x, train=train)
            elif self.backbone == "resnet50":
                feats = ResNet50(**bkw)(x, train=train)
            else:
                raise ValueError(
                    f"GateNet: unknown backbone {self.backbone!r}")

        kw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                  conv_impl=self.conv_impl,
                  dtype=self.dtype, param_dtype=self.param_dtype)
        logits: List[jnp.ndarray] = []

        def side_logit(feat):
            with jax.named_scope("dsod.heads"):
                l = nn.Conv(1, (3, 3), padding="SAME", dtype=self.dtype,
                            param_dtype=self.param_dtype)(feat)
                return resize_to(l, image.shape[1:3],
                                 impl=self.resample_impl).astype(jnp.float32)

        # The side heads interleave with the decoder stages (module
        # order fixes the parameter names), so the decoder scope opens
        # per stage and the two stay siblings.
        with jax.named_scope("dsod.decoder"):
            # Per-level transfer convs to the decoder width.
            trans = [ConvBNAct(self.width, (3, 3), **kw)(f, train=train)
                     for f in feats]
            d = DilatedPyramidBridge(self.width, **kw)(trans[-1],
                                                       train=train)
        logits.append(side_logit(d))  # coarsest
        for i in range(len(trans) - 2, -1, -1):
            with jax.named_scope("dsod.decoder"):
                up = upsample_like(d, trans[i], impl=self.resample_impl)
                gated = GateUnit(**kw)(trans[i], up, train=train)
                d = ConvBNAct(self.width, (3, 3), **kw)([gated, up],
                                                        train=train)
            logits.append(side_logit(d))

        # Zoo contract: element 0 is the primary (finest) prediction.
        return logits[::-1]
