"""Model zoo registry (SURVEY.md §2 C5).

``build_model(cfg.model)`` maps a ModelConfig onto a constructed linen
module.  Zoo-wide call convention::

    logits_list = model.apply(variables, image, depth, train=...,
                              mutable=["batch_stats"] if train else False)

where ``logits_list[0]`` is the primary full-resolution saliency logit.
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple

import jax.numpy as jnp
import numpy as np

_REGISTRY: Dict[str, Callable] = {}


class Kind(NamedTuple):
    """What the program asks of a model's kind, in ONE place: an image
    model (``image`` / ``mask`` / ``depth`` batches, the zoo-wide call
    convention above) or a token model (``tokens`` / ``targets``
    batches, the contract of ``models/lfm2.py``).  Two kinds and five
    callers, not an extension point."""
    name: str
    # The step builder takes the model under the dp preset alone and
    # without error-feedback compression (parallel/engine.py, which
    # also picks the kind's forward + loss by ``name``).
    dp_only: bool
    # (batch, cfg): ``fit()``'s check of its first batch, a ValueError.
    check_first_batch: Callable
    # sample_batch -> what ``model.init`` takes after its rng.
    init_inputs: Callable
    # (cfg, batch_size) -> a host batch of zeros at ``cfg.data``'s
    # sizes, for the tools and tests that lower a step and run nothing.
    zero_batch: Callable


def _check_image_batch(batch, cfg):
    from ..utils.checks import validate_batch

    validate_batch(batch, cfg.data.image_size, use_depth=cfg.data.use_depth)


def _image_init_inputs(sample_batch):
    depth = sample_batch.get("depth")
    return (jnp.asarray(sample_batch["image"]),
            None if depth is None else jnp.asarray(depth))


def _zero_image_batch(cfg, batch_size):
    h, w = cfg.data.image_size
    batch = {"image": np.zeros((batch_size, h, w, 3), np.float32),
             "mask": np.zeros((batch_size, h, w, 1), np.float32)}
    if cfg.data.use_depth:
        batch["depth"] = np.zeros((batch_size, h, w, 1), np.float32)
    return batch


def _check_token_batch(batch, cfg):
    from ..utils.checks import validate_token_batch

    validate_token_batch(batch, cfg.data.seq_len, cfg.model.lm.vocab)


def _token_init_inputs(sample_batch):
    # Parameter shapes do not depend on the sequence length: a short
    # stretch of one sequence keeps the init program small.
    return (jnp.asarray(sample_batch["tokens"])[:1, :128],)


def _zero_token_batch(cfg, batch_size):
    return {k: np.zeros((batch_size, cfg.data.seq_len), np.int32)
            for k in ("tokens", "targets")}


_KINDS = {k.name: k for k in (
    Kind("image", False, _check_image_batch, _image_init_inputs,
         _zero_image_batch),
    Kind("tokens", True, _check_token_batch, _token_init_inputs,
         _zero_token_batch))}


def kind_of(model) -> Kind:
    """The :class:`Kind` of a built model: its class says ``kind =
    "tokens"`` or, an image model, nothing."""
    return _KINDS[getattr(model, "kind", "image")]


def register_model(name: str):
    def deco(builder: Callable):
        if name in _REGISTRY:
            raise KeyError(f"model {name!r} already registered")
        _REGISTRY[name] = builder
        return builder

    return deco


def list_models():
    return sorted(_REGISTRY)


def build_model(model_cfg):
    """Construct the linen module described by a ModelConfig."""
    if model_cfg.name not in _REGISTRY:
        raise KeyError(
            f"unknown model {model_cfg.name!r}; known: {list_models()}"
        )
    if model_cfg.attn_impl != "xla" and model_cfg.name != "vit_sod":
        # Loud instead of a silent no-op (the CNN zoo has no attention
        # to swap; ADVICE.md round 1 flagged exactly this failure mode
        # for ignored knobs).
        raise ValueError(
            f"model.attn_impl={model_cfg.attn_impl!r} only applies to "
            f"vit_sod, not {model_cfg.name!r}")
    if model_cfg.dlf_impl != "xla" and model_cfg.name != "hdfnet":
        raise ValueError(
            f"model.dlf_impl={model_cfg.dlf_impl!r} only applies to "
            f"hdfnet, not {model_cfg.name!r}")
    resample_impl = getattr(model_cfg, "resample_impl", "fast")
    _RESAMPLE_USERS = ("minet", "hdfnet", "gatenet", "u2net")
    if resample_impl != "fast" and model_cfg.name not in _RESAMPLE_USERS:
        # Loud instead of a silent no-op (same posture as attn_impl /
        # dlf_impl above): only the four decoder users of the
        # upsample+merge idiom route the knob.
        raise ValueError(
            f"model.resample_impl={resample_impl!r} only applies to "
            f"{_RESAMPLE_USERS}, not {model_cfg.name!r}")
    conv_impl = getattr(model_cfg, "conv_impl", "xla")
    if conv_impl != "xla" and model_cfg.name not in _RESAMPLE_USERS:
        # Same loudness for the conv-block seam: the four decoder
        # families (and their backbones) thread ConvBNAct's conv_impl;
        # elsewhere the knob would silently do nothing.
        raise ValueError(
            f"model.conv_impl={conv_impl!r} only applies to "
            f"{_RESAMPLE_USERS}, not {model_cfg.name!r}")
    dtype = jnp.dtype(model_cfg.compute_dtype)
    param_dtype = jnp.dtype(model_cfg.param_dtype)
    axis_name = "data" if model_cfg.sync_bn else None
    return _REGISTRY[model_cfg.name](
        model_cfg, dtype=dtype, param_dtype=param_dtype, axis_name=axis_name
    )


@register_model("minet")
def _build_minet(cfg, *, dtype, param_dtype, axis_name):
    from .minet import MINet

    return MINet(
        resample_impl=cfg.resample_impl,
        conv_impl=cfg.conv_impl,
        backbone=cfg.backbone,
        backbone_bn=cfg.backbone_bn,
        axis_name=axis_name,
        bn_momentum=cfg.bn_momentum,
        dtype=dtype,
        param_dtype=param_dtype,
    )


@register_model("u2net")
def _build_u2net(cfg, *, dtype, param_dtype, axis_name):
    from .u2net import U2Net

    if cfg.backbone not in ("none", "small"):
        raise ValueError(
            f"u2net is self-contained: backbone must be 'none' (full) or "
            f"'small' (U²-Net†), got {cfg.backbone!r}")
    return U2Net(
        resample_impl=cfg.resample_impl,
        conv_impl=cfg.conv_impl,
        small=cfg.backbone == "small",
        axis_name=axis_name,
        bn_momentum=cfg.bn_momentum,
        dtype=dtype,
        param_dtype=param_dtype,
    )


@register_model("basnet")
def _build_basnet(cfg, *, dtype, param_dtype, axis_name):
    from .basnet import BASNet

    return BASNet(
        axis_name=axis_name,
        bn_momentum=cfg.bn_momentum,
        dtype=dtype,
        param_dtype=param_dtype,
    )


@register_model("swin_sod")
def _build_swin_sod(cfg, *, dtype, param_dtype, axis_name):
    from .swin_sod import SwinSOD

    return SwinSOD(
        axis_name=axis_name,
        bn_momentum=cfg.bn_momentum,
        dtype=dtype,
        param_dtype=param_dtype,
    )


@register_model("gatenet")
def _build_gatenet(cfg, *, dtype, param_dtype, axis_name):
    from .gatenet import GateNet

    return GateNet(
        resample_impl=cfg.resample_impl,
        conv_impl=cfg.conv_impl,
        backbone=cfg.backbone,
        backbone_bn=cfg.backbone_bn,
        axis_name=axis_name,
        bn_momentum=cfg.bn_momentum,
        dtype=dtype,
        param_dtype=param_dtype,
    )


@register_model("vit_sod")
def _build_vit_sod(cfg, *, dtype, param_dtype, axis_name):
    from .vit_sod import PRESETS, ViTSOD

    if axis_name is not None:
        raise ValueError("vit_sod has no BatchNorm: set model.sync_bn=false")
    if cfg.backbone not in PRESETS:
        raise ValueError(
            f"vit_sod backbone must be one of {sorted(PRESETS)} "
            f"(encoder preset), got {cfg.backbone!r}")
    dim, depth, heads = PRESETS[cfg.backbone]
    return ViTSOD(dim=dim, depth=depth, heads=heads,
                  deep_supervision=cfg.deep_supervision,
                  attn_impl=cfg.attn_impl,
                  dtype=dtype, param_dtype=param_dtype)


@register_model("hdfnet")
def _build_hdfnet(cfg, *, dtype, param_dtype, axis_name):
    from .hdfnet import HDFNet

    return HDFNet(
        resample_impl=cfg.resample_impl,
        conv_impl=cfg.conv_impl,
        backbone=cfg.backbone,
        backbone_bn=cfg.backbone_bn,
        axis_name=axis_name,
        bn_momentum=cfg.bn_momentum,
        dlf_impl=cfg.dlf_impl,
        dtype=dtype,
        param_dtype=param_dtype,
    )


@register_model("lfm2")
def _build_lfm2(cfg, *, dtype, param_dtype, axis_name):
    """The token model (``kind = "tokens"``): its shape is ``cfg.lm``,
    ``cfg.remat`` rematerialises each layer."""
    from .lfm2 import LFM2

    return LFM2(cfg=cfg.lm, remat=cfg.remat, dtype=dtype,
                param_dtype=param_dtype)


@register_model("kimi")
def _build_kimi(cfg, *, dtype, param_dtype, axis_name):
    """The second token model (latent attention, shared + balanced
    routed experts): its shape is ``cfg.lm``, as for ``lfm2``."""
    from .kimi import KimiDecoder

    return KimiDecoder(cfg=cfg.lm, remat=cfg.remat, dtype=dtype,
                       param_dtype=param_dtype)


@register_model("granite")
def _build_granite(cfg, *, dtype, param_dtype, axis_name):
    """The third token model (Mamba-2 state-space layers with a
    position-free attention layer to every nine): its shape is
    ``cfg.lm``, as for ``lfm2``."""
    from .granite import Granite

    return Granite(cfg=cfg.lm, remat=cfg.remat, dtype=dtype,
                   param_dtype=param_dtype)


@register_model("ouro")
def _build_ouro(cfg, *, dtype, param_dtype, axis_name):
    """The fourth token model (one stack of layers run several times on
    shared weights, an exit gate, the exit-weighted loss): its shape is
    ``cfg.lm``, as for ``lfm2``."""
    from .ouro import Ouro

    return Ouro(cfg=cfg.lm, remat=cfg.remat, dtype=dtype,
                param_dtype=param_dtype)


@register_model("nemotron_h")
def _build_nemotron_h(cfg, *, dtype, param_dtype, axis_name):
    """The fifth token model (ONE mixer a layer: Mamba-2, position-free
    attention or latent sparse experts; the chip's share of each
    mixer's heads): its shape is ``cfg.lm``, as for ``lfm2``."""
    from .nemotron_h import NemotronH

    return NemotronH(cfg=cfg.lm, remat=cfg.remat, dtype=dtype,
                     param_dtype=param_dtype)


@register_model("phi4flash")
def _build_phi4flash(cfg, *, dtype, param_dtype, axis_name):
    """The sixth token model (Mamba-1, windowed and full differential
    attention, a gated memory unit and cross-attention that read what
    earlier layers kept): its shape is ``cfg.lm``, as for ``lfm2``."""
    from .phi4flash import Phi4Flash

    return Phi4Flash(cfg=cfg.lm, remat=cfg.remat, dtype=dtype,
                     param_dtype=param_dtype)
