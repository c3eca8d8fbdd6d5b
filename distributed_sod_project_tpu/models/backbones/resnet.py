"""ResNet-34/50 feature-pyramid backbones (SURVEY.md §2 C6).

Returns a 5-level pyramid: stem conv output (stride 2) plus the four
residual stages (strides 4/8/16/32).  For 320×320 input the spatial
sizes are 160/80/40/20/10; channels 64/256/512/1024/2048 for R50
(bottleneck ×4 expansion) and 64/64/128/256/512 for R34 (basic blocks).

Design notes (TPU):
- NHWC everywhere; the stem's 7×7/2 conv and all 3×3s tile cleanly onto
  the MXU in bf16.
- Identity shortcuts use strided 1×1 projections exactly where the
  channel/stride changes, matching the torchvision graph so ImageNet
  weights port 1:1 (``tools/port_torch_weights.py``).
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax.numpy as jnp
from flax import linen as nn

from ..layers import ConvBNAct

_S2D_FALLBACK_WARNED: set = set()


def _warn_s2d_fallback(shape: Tuple[int, ...]) -> None:
    """One warning per input shape per process (the module is traced
    under jit — a plain print would fire once per trace anyway, but
    dedup keeps multi-config sweeps readable)."""
    key = tuple(shape[1:3])
    if key in _S2D_FALLBACK_WARNED:
        return
    _S2D_FALLBACK_WARNED.add(key)
    from ...utils import get_logger

    get_logger().warning(
        "DSOD_STEM_IMPL=s2d requested but input H×W %s is odd — "
        "falling back to the plain 7x7 stem.  A run made under the "
        "variable at this size measured the PLAIN stem.", key)


class BasicBlock(nn.Module):
    features: int
    strides: int = 1
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    conv_impl: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        kw = dict(
            axis_name=self.axis_name,
            bn_momentum=self.bn_momentum,
            conv_impl=self.conv_impl,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        residual = x
        y = ConvBNAct(self.features, (3, 3), strides=self.strides, **kw)(x, train)
        y = ConvBNAct(self.features, (3, 3), act=None, **kw)(y, train)
        if residual.shape[-1] != self.features or self.strides != 1:
            residual = ConvBNAct(
                self.features, (1, 1), strides=self.strides, act=None, **kw
            )(x, train)
        return nn.relu(y + residual)


class Bottleneck(nn.Module):
    features: int  # bottleneck width; output is 4× this
    strides: int = 1
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    conv_impl: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        kw = dict(
            axis_name=self.axis_name,
            bn_momentum=self.bn_momentum,
            conv_impl=self.conv_impl,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        out_ch = self.features * 4
        residual = x
        y = ConvBNAct(self.features, (1, 1), **kw)(x, train)
        y = ConvBNAct(self.features, (3, 3), strides=self.strides, **kw)(y, train)
        y = ConvBNAct(out_ch, (1, 1), act=None, **kw)(y, train)
        if residual.shape[-1] != out_ch or self.strides != 1:
            residual = ConvBNAct(
                out_ch, (1, 1), strides=self.strides, act=None, **kw
            )(x, train)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block: type = Bottleneck
    axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    conv_impl: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False) -> List[jnp.ndarray]:
        kw = dict(
            axis_name=self.axis_name,
            bn_momentum=self.bn_momentum,
            conv_impl=self.conv_impl,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        feats: List[jnp.ndarray] = []
        # DSOD_STEM_IMPL=s2d: compute the stem as space-to-depth + 4×4
        # conv (layers.SpaceToDepthStem) — same arithmetic, same param
        # tree, TPU-friendlier tiling.  Never timed on this tree
        # (ROADMAP D3).
        from ...utils import envvars

        if envvars.read("DSOD_STEM_IMPL") == "s2d":
            if x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
                from ..layers import SpaceToDepthStem

                skw = {k: v for k, v in kw.items() if k != "conv_impl"}
                x = SpaceToDepthStem(64, name="ConvBNAct_0", **skw)(x, train)
            else:
                # Odd H or W forces the plain stem: say so in the log,
                # or a run made under the variable would be taken for
                # an s2d measurement it is not.
                _warn_s2d_fallback(x.shape)
                x = ConvBNAct(64, (7, 7), strides=2, **kw)(x, train)
        else:
            x = ConvBNAct(64, (7, 7), strides=2, **kw)(x, train)
        feats.append(x)  # stride 2
        # padding (1,1), not SAME: matches torch MaxPool2d(3,2,1) so
        # ported ImageNet weights see the alignment they trained with.
        x = nn.max_pool(x, (3, 3), strides=(2, 2),
                        padding=((1, 1), (1, 1)))
        widths = (64, 128, 256, 512)
        for stage, (n_blocks, width) in enumerate(zip(self.stage_sizes, widths)):
            for i in range(n_blocks):
                strides = 2 if (i == 0 and stage > 0) else 1
                x = self.block(width, strides=strides, **kw)(x, train)
            feats.append(x)  # strides 4, 8, 16, 32
        return feats


def ResNet50(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), block=Bottleneck, **kw)


def ResNet34(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), block=BasicBlock, **kw)
