"""The decoder of Kimi-VL-A3B (moonshotai, ``text_config``): multi-head
latent attention over shared + bias-routed sparse experts with the
family's balancing rule — one chip's share of an expert-parallel stage.

The zoo's second token model (``kind = "tokens"``, the contract of
``models/lfm2.py``: ``apply(variables, tokens, train=...) -> (hidden
after the final norm, counters)``), built from the first one's parts:
``RMSNorm``, ``SwiGLU``, ``rope``, the per-layer remat with named saves
and :class:`~.lfm2.ExpertLayer` are imported, not copied.  What is new:

- *latent attention* (:class:`LatentAttention`): ``q = x W_q`` is 16
  heads of ``[q_nope (128) ; q_rope (64)]``; ``[c ; k_r] = x W_kva`` is
  a 512-wide latent and ONE 64-wide rotary key a token; ``[k_nope ; v]
  = RMSNorm(c) W_kvb`` is 16 heads of 128 + 128.  The rotary parts are
  rotated, and the causal softmax over ``(q_nope . k_nope + q_rope .
  k_rope) / sqrt(192)`` runs in the Pallas kernel
  (``pallas/flash_attention.py::flash_attention_mla``);
- *shared experts*: a SwiGLU of ``shared_experts * expert_width``
  columns over every token, added to the routed sum;
- *the balancing rule*: ``expert_bias`` is a buffer the step updates
  (``ExpertLayer.bias_update_rate``), so a train step applies this
  model with ``batch_stats`` mutable and keeps what it hands back;
- an output head of its own (``head/embedding``, [vocab, hidden]):
  :attr:`KimiDecoder.head` tells ``parallel/engine.py`` where; the
  input embedding is :class:`Embed` (``embed/kernel``, fan-in 1).

What a rematerialised layer KEEPS (:data:`REMAT_SAVES`): the kernel's
output and lse (without them the forward kernel runs twice) and the
routing plan.  NOT its operands: q is 3,072 columns a token and the
per-head keys and values 4,096, and at the published size the
compiler's books hold the step with neither (PERF.md section 6, PR 35:
16.03 GiB with q kept, 14.78 with the 576-column latent and rotary key
kept in place of k and v, 14.59 with neither, against a limit of
14.69) — so the backward makes q, the latent, ``k_nope`` and ``v``
again from the layer's input, four products of which the widest is
2,048 x 3,072 a token.

Device scopes (PERF.md section 3): ``dsod.encoder`` over the stack;
``dsod.attn``, ``dsod.densemlp``, ``dsod.moe.route`` / ``.experts`` /
``.combine`` as in ``lfm2.py``; ``dsod.moe.shared`` around the shared
experts, ``dsod.moe.balance`` around the bias update; the final norm is
``dsod.heads``.
"""

from __future__ import annotations

import collections
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..pallas.flash_attention import MLA_RESIDUAL_NAMES, flash_attention_mla
from .lfm2 import (ExpertLayer, RMSNorm, SwiGLU, _dense, _saves_counted,
                   log_flash_grid, log_saves, moe_counters, rope)

REMAT_SAVES = MLA_RESIDUAL_NAMES[1:] + ("plan",)  # out, lse; not q
_SAVE_NAMED = jax.checkpoint_policies.save_only_these_names(*REMAT_SAVES)


class LatentAttention(nn.Module):
    heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    rope_theta: float = 8e5
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, n, d = x.shape
        h, dn, dr, dv = self.heads, self.nope_dim, self.rope_dim, self.v_dim
        kw = (self.dtype, self.param_dtype)

        def rotated(t):  # [B, N, H', dr] -> rotated, in the compute dtype
            return rope(t.astype(jnp.float32), self.rope_theta).astype(
                self.dtype)

        q = _dense(h * (dn + dr), "q_proj", *kw)(x).reshape(b, n, h, dn + dr)
        kva = _dense(self.kv_rank + dr, "kv_a_proj", *kw)(x)
        c = RMSNorm(self.eps, self.dtype, name="kv_a_norm")(
            kva[..., :self.kv_rank])
        k_rope = rotated(kva[..., None, self.kv_rank:])[:, :, 0]
        kv = _dense(h * (dn + dv), "kv_b_proj", *kw)(c).reshape(
            b, n, h, dn + dv)
        heads_major = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        o = flash_attention_mla(
            heads_major(q[..., :dn]), heads_major(rotated(q[..., dn:])),
            heads_major(kv[..., :dn]), k_rope, heads_major(kv[..., dn:]))
        o = heads_major(o).reshape(b, n, h * dv)
        return _dense(d, "o_proj", *kw)(o)


class Block(nn.Module):
    ffn: str          # dense | moe
    cfg: Any          # configs.base.LMConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        y = RMSNorm(c.norm_eps, self.dtype, name="op_norm")(h)
        with jax.named_scope("dsod.attn"):
            h = h + LatentAttention(
                c.heads, c.head_dim - c.rope_dim, c.rope_dim, c.v_dim,
                c.kv_rank, c.rope_theta, c.norm_eps, name="attn", **kw)(y)
        y = RMSNorm(c.norm_eps, self.dtype, name="ffn_norm")(h)
        if self.ffn == "dense":
            with jax.named_scope("dsod.densemlp"):
                return h + SwiGLU(c.dense_width, name="mlp", **kw)(y), None
        out, counters = ExpertLayer(
            c.experts, c.experts_held, c.first_expert, c.top_k,
            c.expert_width, c.norm_topk_prob, c.routed_scaling_factor,
            c.topk_eps, c.bias_update_rate, name="moe", **kw)(y)
        with jax.named_scope("dsod.moe.shared"):
            out = out + SwiGLU(c.shared_experts * c.expert_width,
                               name="shared", **kw)(y)
        return h + out, counters


class Embed(nn.Module):
    """The input embedding as what it is, a linear map on a one-hot id:
    ``kernel`` is [vocab, 1, hidden], a stack of one-row maps of fan-in
    1.  Stored so, the benchmark's weights recipe (fan-in scaling on a
    ``kernel``, ``harness/weights_lm.py``) gives its rows unit variance
    like every other projection's output, and a token's identity leads
    the residual stream as it does in a trained model.  With rows of
    norm 1 (that recipe's ``embedding`` rule, made for a tied head) a
    random causal softmax over Zipf text adds a vector COMMON to all
    tokens, five times the embedding's norm, every router sees the same
    input for every token, and the share of pairs held here is the luck
    of the seed (PERF.md section 6, PR 35)."""
    vocab: int
    hidden: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        kernel = self.param("kernel", nn.initializers.normal(1.0),
                            (self.vocab, 1, self.hidden), self.param_dtype)
        return jnp.take(kernel[:, 0].astype(self.dtype), tokens, axis=0)


class Head(nn.Module):
    """Declares the output head's matrix; the product is the loss's
    (``losses/token_ce.py``, chunk by chunk)."""
    vocab: int
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        self.param("embedding", nn.initializers.variance_scaling(
            1.0, "fan_out", "normal"), (self.vocab, h.shape[-1]),
            self.param_dtype)
        return h


class KimiDecoder(nn.Module):
    """``cfg`` is the frozen ``configs.base.LMConfig`` (``model.lm``):
    the published widths, the layers kept and the chip's share."""
    cfg: Any
    remat: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    kind = "tokens"                  # what engine.py / loop.py route on
    head = ("head", "embedding")     # the loss's matrix, in ``params``

    @nn.compact
    def __call__(self, tokens, *, train: bool = False):
        del train  # no dropout; the bias moves where its buffer is mutable
        c = self.cfg
        saved = collections.Counter()
        block = (nn.remat(Block, policy=_saves_counted(saved, _SAVE_NAMED))
                 if self.remat else Block)
        per_layer = []
        with jax.named_scope("dsod.encoder"):
            h = Embed(c.vocab, c.hidden, self.dtype, self.param_dtype,
                      name="embed")(tokens)
            for i, ffn in enumerate(c.ffn_types):
                h, counters = block(ffn, c, self.dtype, self.param_dtype,
                                    name=f"layer_{i}")(h)
                if counters is not None:
                    per_layer.append(counters)
            # (inside the stage: the counters' few scalar ops are the
            # encoder's, not unscoped time)
            counters = moe_counters(per_layer, tokens.size * c.top_k)
        log_saves("kimi", len(c.ffn_types), saved, REMAT_SAVES)
        log_flash_grid(saved, tokens.shape[1])
        with jax.named_scope("dsod.heads"):
            h = RMSNorm(c.norm_eps, self.dtype, name="final_norm")(h)
            h = Head(c.vocab, self.param_dtype, name="head")(h)
        return h, counters
