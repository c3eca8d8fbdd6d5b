"""HDFNet — hierarchical dynamic filtering for RGB-D SOD.

TPU-native re-design of HDFNet (Pang et al., ECCV 2020; reference
parity target SURVEY.md §2 C5 and the RGB-D config [B:9] — reference
mount unreadable, topology per the paper):

- two encoder streams: RGB and depth (depth replicated to 3 channels),
  sharing the backbone architecture but not parameters
- hierarchical dynamic filtering at the three deepest levels: the depth
  stream *generates* spatially-variant kernels that filter the fused
  RGB+depth features (region-adaptive receptive fields)
- top-down decoder over the filtered pyramid; deep supervision with a
  side head per decoder level.

Returns **3 logits** at input resolution, element 0 primary.

TPU notes: dynamic filtering is the classic "local conv" op that is a
scatter/gather nightmare on GPUs; here it is expressed as
``conv_general_dilated_patches`` (an im2col XLA lowers to cheap
reshapes/slices) followed by an einsum over the patch axis — a large
batched contraction the MXU eats directly, with multi-dilation sharing
one patch extraction per dilation rate.
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from .backbones import ResNet50, VGG16
from .layers import ConvBNAct, resample_merge, resize_to


def dynamic_local_filter(x: jnp.ndarray, kernels: jnp.ndarray, ksize: int,
                         dilation: int = 1,
                         impl: str = "xla") -> jnp.ndarray:
    """Apply per-position ``ksize×ksize`` depthwise kernels to ``x``.

    x: (B,H,W,C); kernels: (B,H,W,ksize*ksize) — one kernel per spatial
    location, shared across channels (HDFNet's kernel-generation units
    emit channel-shared spatial kernels).

    ``impl='pallas'`` routes through the fused VMEM kernel
    (``pallas/dynamic_filter.py``) — same math, no ksize²-wide im2col
    materialisation in HBM.
    """
    if impl == "pallas":
        from ..pallas.dynamic_filter import fused_dynamic_filter

        return fused_dynamic_filter(x, kernels, ksize, dilation)
    if impl != "xla":
        raise ValueError(f"impl must be 'xla' or 'pallas', got {impl!r}")
    b, h, w, c = x.shape
    # im2col: (B,H,W, C*ksize*ksize) with channel-major ordering.
    patches = jax.lax.conv_general_dilated_patches(
        x, (ksize, ksize), window_strides=(1, 1), padding="SAME",
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    patches = patches.reshape(b, h, w, c, ksize * ksize)
    return jnp.einsum("bhwck,bhwk->bhwc", patches,
                      kernels.astype(patches.dtype))


class KernelGenUnit(nn.Module):
    """Generate normalized per-position kernels from guidance features."""

    ksize: int = 3
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    conv_impl: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, g, train: bool = False):
        kw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                  conv_impl=self.conv_impl,
                  dtype=self.dtype, param_dtype=self.param_dtype)
        k = ConvBNAct(64, (3, 3), **kw)(g, train)
        k = nn.Conv(self.ksize * self.ksize, (3, 3), padding="SAME",
                    dtype=self.dtype, param_dtype=self.param_dtype)(k)
        # Softmax over the patch axis → kernels are convex weights, which
        # keeps the filtered activations bounded (bf16-safe).
        return jax.nn.softmax(k.astype(jnp.float32), axis=-1)


class DDPM(nn.Module):
    """Dense dynamic pyramid module: multi-dilation dynamic filtering."""

    width: int
    dilations: tuple = (1, 2, 4)
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    dlf_impl: str = "xla"
    conv_impl: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, fused, guide, train: bool = False):
        kw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                  conv_impl=self.conv_impl,
                  dtype=self.dtype, param_dtype=self.param_dtype)
        x = ConvBNAct(self.width, (3, 3), **kw)(fused, train)
        outs = [x]
        for rate in self.dilations:
            kern = KernelGenUnit(axis_name=self.axis_name,
                                 bn_momentum=self.bn_momentum,
                                 conv_impl=self.conv_impl,
                                 dtype=self.dtype,
                                 param_dtype=self.param_dtype)(guide, train)
            outs.append(dynamic_local_filter(x, kern, ksize=3, dilation=rate,
                                             impl=self.dlf_impl))
        return ConvBNAct(self.width, (3, 3), **kw)(outs, train)


class HDFNet(nn.Module):
    backbone: str = "vgg16"
    backbone_bn: bool = True
    width: int = 64
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    dlf_impl: str = "xla"  # xla (im2col+einsum) | pallas (fused VMEM)
    # Decoder resample strategy (model.resample_impl):
    # fast | xla | fused — see layers.resample_merge.
    resample_impl: str = "fast"
    # Conv-block strategy (model.conv_impl): xla | fused — see
    # layers.ConvBNAct; threaded to every conv block, both backbones
    # included.
    conv_impl: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def _backbone(self, name_suffix: str):
        bkw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                   conv_impl=self.conv_impl,
                   dtype=self.dtype, param_dtype=self.param_dtype)
        if self.backbone == "vgg16":
            return VGG16(use_bn=self.backbone_bn, name=f"vgg_{name_suffix}", **bkw)
        if self.backbone == "resnet50":
            return ResNet50(name=f"resnet_{name_suffix}", **bkw)
        raise ValueError(f"HDFNet: unknown backbone {self.backbone!r}")

    @nn.compact
    def __call__(self, image, depth, *, train: bool = False) -> List[jnp.ndarray]:
        if depth is None:
            raise ValueError("HDFNet is an RGB-D model: `depth` is required "
                             "(data cfg use_depth=True, SURVEY.md §2 C7)")
        x = image.astype(self.dtype)
        d = depth.astype(self.dtype)
        if d.shape[-1] == 1:
            d = jnp.repeat(d, 3, axis=-1)

        with jax.named_scope("dsod.encoder"):
            rgb_feats = self._backbone("rgb")(x, train=train)
            dep_feats = self._backbone("depth")(d, train=train)

        kw = dict(axis_name=self.axis_name, bn_momentum=self.bn_momentum,
                  conv_impl=self.conv_impl,
                  dtype=self.dtype, param_dtype=self.param_dtype)

        with jax.named_scope("dsod.decoder"):
            # Fuse the three deepest levels with dynamic filtering; the
            # depth stream is the kernel-generating guide (hierarchical:
            # each level gets its own DDPM).
            filtered = []
            for lvl in (2, 3, 4):
                # The two streams convolve as their channel concat inside
                # DDPM's entry conv — the ConvBNAct seam fuses it away on
                # the fused arm.
                fused = [rgb_feats[lvl], dep_feats[lvl]]
                guide = ConvBNAct(self.width, (3, 3), **kw)(dep_feats[lvl],
                                                            train)
                filtered.append(DDPM(self.width, axis_name=self.axis_name,
                                     bn_momentum=self.bn_momentum,
                                     dlf_impl=self.dlf_impl,
                                     conv_impl=self.conv_impl,
                                     dtype=self.dtype,
                                     param_dtype=self.param_dtype)(
                    fused, guide, train))

            # Top-down decoder: deepest filtered level down to the finest
            # two RGB levels (compressed to `width`).
            dec = filtered[-1]
            sides = []  # supervised decoder states, coarse → fine
            for skip in (filtered[1], filtered[0]):
                dec = resample_merge(dec, skip, mode="add",
                                     impl=self.resample_impl)
                dec = ConvBNAct(self.width, (3, 3), **kw)(dec, train)
                sides.append(dec)
            for lvl in (1, 0):
                skip = ConvBNAct(self.width, (3, 3), **kw)(rgb_feats[lvl],
                                                           train)
                dec = resample_merge(dec, skip, mode="add",
                                     impl=self.resample_impl)
                dec = ConvBNAct(self.width, (3, 3), **kw)(dec, train)

        hw = image.shape[1:3]
        logits = []
        # Primary head on the finest decoder state + one deep-supervision
        # head per intermediate decoder level.
        with jax.named_scope("dsod.heads"):
            for s in (dec, sides[1], sides[0]):
                l = nn.Conv(1, (3, 3), padding="SAME", dtype=self.dtype,
                            param_dtype=self.param_dtype)(s)
                logits.append(resize_to(l, hw, impl=self.resample_impl)
                              .astype(jnp.float32))
        return logits
