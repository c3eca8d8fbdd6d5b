"""ViT-SOD — the long-context zoo member (SURVEY.md §5 "long-context").

A plain-ViT encoder with GLOBAL attention over every patch token and a
per-token unpatchify head.  Unlike the CNN zoo (and Swin's windowed
attention), its attention cost grows quadratically with resolution —
this is the model whose training genuinely needs sequence parallelism,
and its architecture is chosen so SP is EXACT:

- ``patchify`` is a stride-``patch`` convolution with kernel ==
  stride: patches are disjoint tiles, so a block of patch ROWS of the
  image maps to a block of tokens with no cross-device halo.
- LayerNorm / MLP / the linear unpatchify head are per-token.
- Attention is the ONLY cross-token op; under sequence parallelism it
  is computed exactly by ``parallel.ring_attention`` (K/V blocks on a
  ``lax.ppermute`` ring), injected via the ``attn_fn`` call argument.
- No BatchNorm → no cross-replica stat plumbing in the SP step.

So the whole forward/backward decomposes over token blocks: each
``seq`` device runs this module on its slice of image rows with
``pos_row_offset`` pointing into the shared positional table
(``parallel/sp.py`` builds that step).  Run on the full image with the
default ``attn_fn`` (single-device softmax), the math is identical —
eval/test/predict need no special casing.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

class _Block(nn.Module):
    """Pre-LN transformer block; attention core injected per call."""

    dim: int
    heads: int
    mlp_ratio: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x, attn_fn: Callable, *, train: bool):
        b, n, d = x.shape
        h = self.heads
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)

        y = nn.LayerNorm(dtype=jnp.float32, param_dtype=self.param_dtype)(x)
        # Separate q/k/v projections (not one fused 3d Dense): the TP
        # rules column-shard each (d, d) kernel, and with heads % model
        # == 0 the shard boundary lands on a head boundary — a fused
        # kernel's packed 3d axis would split mid-k/v and force GSPMD
        # to re-gather qkv every block (parallel/tp.py VIT_TP_RULES).
        q = nn.Dense(d, name="q", **kw)(y)
        k = nn.Dense(d, name="k", **kw)(y)
        v = nn.Dense(d, name="v", **kw)(y)
        # [B, N, D] -> heads-major [B, H, N, D/H] (ring_attention layout).
        def split_heads(t):
            return t.reshape(b, n, h, d // h).transpose(0, 2, 1, 3)

        out = attn_fn(split_heads(q), split_heads(k), split_heads(v))
        out = out.transpose(0, 2, 1, 3).reshape(b, n, d)
        x = x + nn.Dense(d, name="proj", **kw)(out)

        y = nn.LayerNorm(dtype=jnp.float32, param_dtype=self.param_dtype)(x)
        y = nn.Dense(self.mlp_ratio * d, name="mlp_up", **kw)(y)
        # Exact (erf) GELU — what timm/DeiT checkpoints were trained
        # with; the tanh approximation costs ~1e-3 per activation,
        # which compounds over ported 12-block encoders.
        y = nn.gelu(y, approximate=False)
        x = x + nn.Dense(d, name="mlp_down", **kw)(y)
        return x


class ViTSOD(nn.Module):
    """Global-attention SOD.  Returns ``[logit]`` ([B,H,W,1], f32).

    ``full_grid``: the FULL image's (patch_rows, patch_cols).  Defaults
    to this call's image — pass it when the image argument is a row
    SLICE of a larger image (sequence parallelism), together with
    ``pos_row_offset`` (this slice's first patch row, may be traced)
    and an ``attn_fn`` that performs global attention across devices.
    """

    patch: int = 16
    dim: int = 384
    depth: int = 8
    heads: int = 6
    mlp_ratio: int = 4
    deep_supervision: bool = True  # aux unpatchify head at mid-depth
    # Default attention core when no attn_fn is injected: "xla" is the
    # materialized-scores softmax (full_attention), "flash" the Pallas
    # tiled kernel (pallas/flash_attention.py) — same math, O(N·D) HBM
    # instead of O(N²), which is what makes high-resolution single-chip
    # training/eval fit.  An explicit attn_fn (the SP ring) always wins.
    attn_impl: str = "xla"
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, image, depth=None, *, train: bool = False,
                 attn_fn: Optional[Callable] = None,
                 full_grid: Optional[tuple] = None,
                 pos_row_offset=0) -> List[jnp.ndarray]:
        del depth  # RGB-only member; uniform zoo signature
        if attn_fn is None:
            from ..parallel.ring_attention import resolve_attn_fn

            attn_fn = resolve_attn_fn(self.attn_impl)
        x = image.astype(self.dtype)
        b, hh, ww, _ = x.shape
        p = self.patch
        if hh % p or ww % p:
            raise ValueError(f"image {hh}x{ww} not divisible by patch {p}")
        rows, cols = hh // p, ww // p
        grid = tuple(full_grid) if full_grid is not None else (rows, cols)

        # Disjoint-tile patchify: kernel == stride == patch.  (No
        # ``dsod.decoder`` here: the heads read the encoder's tokens.)
        with jax.named_scope("dsod.encoder"):
            x = nn.Conv(self.dim, (p, p), strides=(p, p), dtype=self.dtype,
                        param_dtype=self.param_dtype, name="patch_embed")(x)
            x = x.reshape(b, rows * cols, self.dim)

            pos = self.param(
                "pos_embed",
                nn.initializers.truncated_normal(0.02),
                (grid[0] * grid[1], self.dim), self.param_dtype)
            # This call's token window of the full positional table: row
            # offset may be a traced per-device index (SP), so slice
            # dynamically; cols always span the full width.
            start = jnp.asarray(pos_row_offset, jnp.int32) * grid[1]
            from jax import lax

            pos_win = lax.dynamic_slice_in_dim(pos, start, rows * cols,
                                               axis=0)
            x = x + pos_win[None].astype(self.dtype)

        @jax.named_scope("dsod.heads")
        def unpatchify_head(tokens, name):
            """Per-token D -> p*p logits, tiled back to pixels — the
            only head shape that keeps the model halo-free for SP."""
            y = nn.LayerNorm(dtype=jnp.float32,
                             param_dtype=self.param_dtype,
                             name=f"{name}_norm")(tokens)
            l = nn.Dense(p * p, dtype=jnp.float32,
                         param_dtype=self.param_dtype, name=name)(y)
            l = l.reshape(b, rows, cols, p, p)
            return l.transpose(0, 1, 3, 2, 4).reshape(b, hh, ww, 1
                                                      ).astype(jnp.float32)

        aux = None
        for i in range(self.depth):
            with jax.named_scope("dsod.encoder"):
                x = _Block(dim=self.dim, heads=self.heads,
                           mlp_ratio=self.mlp_ratio, dtype=self.dtype,
                           param_dtype=self.param_dtype, name=f"block{i}")(
                               x, attn_fn, train=train)
            if self.deep_supervision and i == self.depth // 2 - 1:
                aux = unpatchify_head(x, "aux_head")

        logits = [unpatchify_head(x, "head")]
        if aux is not None:
            logits.append(aux)
        return logits

PRESETS = {
    # name: (dim, depth, heads).  "small"/"base" match the public
    # ViT-S/16 and ViT-B/16 shapes so timm/DeiT ImageNet checkpoints
    # port directly (tools/port_torch_weights.py --arch vit); "none"
    # stays a lighter from-scratch baseline that keeps the 320px
    # quadratic-attention model comfortably on one chip.
    "none": (384, 8, 6),
    "small": (384, 12, 6),
    "base": (768, 12, 12),
    # Debug/CI variant: compiles in seconds on one CPU — the model for
    # engine-plumbing smokes where the architecture is irrelevant.
    "tiny": (32, 2, 2),
}
