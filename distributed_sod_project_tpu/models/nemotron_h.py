"""Nemotron-H (nvidia, ``nemotron_h``; the cut is of Nemotron 3 Super
120B-A12B): ONE mixer a layer — a Mamba-2 state-space mixer, a
position-free grouped-query attention layer or a latent sparse-expert
layer — by the letters of ``hybrid_override_pattern`` (``M`` | ``*`` |
``E``).  One chip's share of a deployment that divides every layer: the
mixers' heads 8 ways, the routed experts 64 ways.

The zoo's fifth token model (``kind = "tokens"``, the contract of
``models/lfm2.py``: ``apply(variables, tokens, train=...) -> (hidden
after the final norm, counters)``).  The Mamba-2 mixer and the
position-free attention layer are ``models/granite.py``'s classes with
other numbers (a mixer that holds ONE of the published 8 B/C groups and
its 16 heads norms over that group's 1,024 columns, which IS the
published group norm); ``RMSNorm``, the counted per-layer remat and
the walk through the expert-ordered buffer (the dispatch plan, the row
gathers, the grouped products, the un-permute kernel) are
``models/lfm2.py``'s; ``Embed`` and ``Head`` are ``models/kimi.py``'s.
Imported, not copied.  Width ``hidden`` throughout, no bias but the
conv's, ``eps`` = ``layer_norm_epsilon``:

- block ``l``: ``x <- x + mixer_l(RMSNorm(x))``; after the last one the
  final RMSNorm and an untied head (``head/embedding``);
- *mamba* (``granite.Mamba2Mixer``): ``[z | xBC | dt] = u W_in``; ``xBC
  = silu(conv(xBC) + bias)``; ``delta = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head ``H_t = exp(delta_t A) H_{t-1} + delta_t x_t
  B_t^T``, ``y_t = H_t C_t + D x_t`` with a float32 state; ``out =
  RMSNorm(y * silu(z)) W_out``, the norm over the group's columns;
- *attention* (``granite.Attention``): causal, grouped-query, no
  rotation, scores ``q k^T / sqrt(head_dim)``;
- *moe* (:class:`LatentExpertLayer` + the shared expert), for a token
  ``x``: ``s = sigmoid(W_r x)`` in float32 over ALL ``experts``; chosen
  = the ``top_k`` largest of ``s + b`` (``b`` the balancing bias, a
  buffer that only selects); ``w = routed_scaling_factor * s[chosen] /
  (sum s[chosen] + topk_eps)``; ``z = W_dn x`` (``latent_width``
  columns); ``r = sum over chosen AND HELD e of w_e W2_e relu(W1_e
  z)^2``; ``y = W_up r + V2 relu(V1 x)^2``.  The router and the shared
  expert read ``x``, the routed experts read ``z``; no norm and no
  activation on the latent.  The chip computes the part of ``r`` its
  held experts give; ``W_dn``, ``W_up``, the router and the shared
  expert are whole on every chip.  ``W_up`` is linear, so the shares'
  parts add up after it as before it.

What the deployment's other chips would add — the other 112 heads'
partial outputs of a mixer (an all-reduce over 8 chips), the other 504
experts' parts of ``r`` (an exchange over 64) — is left out: on one
chip each layer runs without its collective.

Compute is ``dtype`` (bf16) with float32 parameters; the router, ``dt``,
``delta A`` and its running sums, the carried state, every norm's
statistics and the softmax are float32.  When ``remat`` is on each
block's backward recomputes the block from its input except the values
:data:`REMAT_SAVES` names.

Device scopes (PERF.md section 3): ``dsod.encoder`` over the stack;
``dsod.ssm`` and below and ``dsod.attn`` as in ``granite.py``;
``dsod.moe.route`` (router, top-k, the plan, the gather into expert
order), ``dsod.moe.latent`` (the down- and the up-projection),
``dsod.moe.experts`` (the routed grouped products and ``relu^2``),
``dsod.moe.combine``, ``dsod.moe.shared``, ``dsod.moe.balance``; the
final norm is ``dsod.heads``.  Counters beside ``grad_norm``: the expert
layers' (``lfm2.moe_counters``), the mixers' (``granite.
ssm_counters``) and the hottest expert layer's share of the pairs and
of its usual buffer (``moe_pairs_here_share_max``,
``moe_buffer_fill_max``: over 1, that layer took the by-group path), and
``moe_weight_fetch_share``: the weight blocks the up-projection's grouped
product copies in over its grid steps, mean over the expert layers (1.0
= a block a step; ``pallas/grouped_matmul.py`` says which steps fetch).
"""

from __future__ import annotations

import collections
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..pallas.flash_attention import CAUSAL_RESIDUAL_NAMES
from .granite import Attention, Mamba2Mixer, ssm_counters
from .kimi import Embed, Head
from .lfm2 import (RMSNorm, _dense, _saves_counted,
                   held_experts_sum as _held_experts_sum, log_flash_grid,
                   log_saves, moe_counters)

# What a rematerialised layer KEEPS: the attention kernel's output and
# lse, and the expert layers' routing plan (chosen experts, scores,
# buffer layout, step list).  Not the scan's output and chunk states
# (``models/granite.py`` says why).
REMAT_SAVES = CAUSAL_RESIDUAL_NAMES[1:] + ("plan",)
_SAVE_NAMED = jax.checkpoint_policies.save_only_these_names(*REMAT_SAVES)

PATTERN = {"M": "mamba", "*": "attention", "E": "moe"}

# The usual buffer's rows, and the rows of it that are multiplied
# whatever the routing, both over the held experts' BALANCED share of
# the pairs.  With 8 of 512 experts held one layer's share leaves
# balance within 100 steps of seeded weights: of 24 seeds the hottest
# layer passed 6 x balanced in 4 and reached 8.2 x (PERF.md section 6,
# PR 43).  Up to ``WHOLE`` the empty tiles are multiplied too, so the
# step's time does not follow the routing; from there to ``CAPACITY``
# the grouped products follow the hottest layer's rows (0.09 ms a
# 256-row tile a layer) and everything else is sized by the buffer;
# past ``CAPACITY`` the layer takes the tokens a group at a time (+24 ms
# a layer a step at 74 tiles).
CAPACITY = 12.0
WHOLE = 6.0


def relu2(x):
    return jnp.square(nn.relu(x))


class ReLU2MLP(nn.Module):
    """The family's two-matrix feed-forward, ``W2 relu(W1 x)^2``."""
    width: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        u = _dense(self.width, "up", self.dtype, self.param_dtype)(x)
        return _dense(x.shape[-1], "down", self.dtype, self.param_dtype)(
            relu2(u))


def held_experts_sum(xt, idx, w, weights, ffn, *, experts: int,
                     first_expert: int):
    """``lfm2.held_experts_sum`` (the one way through the expert-ordered
    buffer) under this model's buffer rule: a usual buffer of
    ``CAPACITY`` x the balanced share of which ``WHOLE`` x is multiplied
    whatever it holds, and the default row tile."""
    return _held_experts_sum(xt, idx, w, weights, ffn, experts=experts,
                             first_expert=first_expert, capacity=CAPACITY,
                             whole=WHOLE)


class LatentExpertLayer(nn.Module):
    """Bias-routed sparse experts that work in a latent, the share of
    one chip: told which experts it holds (``first_expert``,
    ``experts_held`` of ``experts``), it routes over all of them from
    ``x``, projects ``x`` into ``latent`` columns, computes its own
    experts' part of ``sum_e w_e W2_e relu(W1_e z)^2`` and projects that
    back.  Returns ``(out, counters)`` as ``lfm2.ExpertLayer`` does,
    and balances its router by the same rule (``bias_update_rate``)."""
    experts: int
    experts_held: int
    first_expert: int
    top_k: int
    width: int
    latent: int
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    topk_eps: float = 1e-20
    bias_update_rate: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, n, d = x.shape
        e, f, lat = self.experts_held, self.width, self.latent
        xt = x.reshape(b * n, d)
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        w_up = self.param("up", init, (e, lat, f), self.param_dtype)
        w_down = self.param("down", init, (e, f, lat), self.param_dtype)
        bias_var = self.variable("batch_stats", "expert_bias", jnp.zeros,
                                 (self.experts,), jnp.float32)
        bias = bias_var.value
        with jax.named_scope("dsod.moe.route"):
            logits = nn.Dense(
                self.experts, use_bias=False, dtype=jnp.float32,
                param_dtype=self.param_dtype, name="router",
                precision=lax.Precision.HIGHEST)(xt.astype(jnp.float32))
            s = jax.nn.sigmoid(logits)
            _, idx = lax.top_k(s + lax.stop_gradient(bias), self.top_k)
            idx = checkpoint_name(idx.astype(jnp.int32), "plan")
            w = checkpoint_name(jnp.take_along_axis(s, idx, -1), "plan")
            if self.norm_topk_prob:
                w = w / (jnp.sum(w, -1, keepdims=True) + self.topk_eps)
            w = w * self.routed_scaling_factor
        with jax.named_scope("dsod.moe.latent"):
            z = _dense(lat, "latent_down", self.dtype, self.param_dtype)(xt)
        r, counts, dropped, (needed, usual), fetched = held_experts_sum(
            z, idx, w, (w_up, w_down),
            lambda gmm, xs, up, down: gmm(relu2(gmm(xs, up)), down),
            experts=self.experts, first_expert=self.first_expert)
        fill = needed / usual  # over 1 it took the by-group path
        with jax.named_scope("dsod.moe.latent"):
            out = _dense(d, "latent_up", self.dtype, self.param_dtype)(
                r.astype(self.dtype))
        pairs = jnp.sum(counts).astype(jnp.float32)
        counters = {
            "pairs_here": pairs,
            "load_max_over_mean": jnp.max(counts) * e / jnp.maximum(pairs, 1),
            "dropped": dropped.astype(jnp.float32),
            "buffer_fill": fill.astype(jnp.float32),
            "weight_fetch_share": fetched.astype(jnp.float32)}
        if (self.bias_update_rate and not self.is_initializing()
                and self.is_mutable_collection("batch_stats")):
            with jax.named_scope("dsod.moe.balance"):
                sent = jnp.sum(idx.reshape(-1)[None, :] == jnp.arange(
                    self.experts)[:, None], axis=1).astype(jnp.float32)
                bias_var.value = bias + self.bias_update_rate * jnp.sign(
                    idx.size / self.experts - sent)
                counters["bias_abs_max"] = jnp.max(jnp.abs(bias_var.value))
        return out.reshape(b, n, d), counters


class Block(nn.Module):
    op: str           # mamba | attention | moe
    cfg: Any          # configs.base.LMConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        y = RMSNorm(c.norm_eps, self.dtype, name="norm")(h)
        counters = None
        if self.op == "mamba":
            with jax.named_scope("dsod.ssm"):
                out, counters = Mamba2Mixer(
                    c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_conv,
                    c.ssm_chunk, c.norm_eps, name="mixer", **kw)(y)
        elif self.op == "attention":
            with jax.named_scope("dsod.attn"):
                out = Attention(c.heads, c.kv_heads, c.head_dim, name="attn",
                                **kw)(y)
        else:
            out, counters = LatentExpertLayer(
                c.experts, c.experts_held, c.first_expert, c.top_k,
                c.expert_width, c.latent_width, c.norm_topk_prob,
                c.routed_scaling_factor, c.topk_eps, c.bias_update_rate,
                name="moe", **kw)(y)
            with jax.named_scope("dsod.moe.shared"):
                out = out + ReLU2MLP(c.shared_width, name="shared", **kw)(y)
        return h + out, counters


class NemotronH(nn.Module):
    """``cfg`` is the frozen ``configs.base.LMConfig`` (``model.lm``):
    the published widths, the layers kept and the chip's share of heads,
    experts and vocabulary."""
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
        ssm, moe = [], []
        with jax.named_scope("dsod.encoder"):
            h = Embed(c.vocab, c.hidden, self.dtype, self.param_dtype,
                      name="embed")(tokens)
            for i, op in enumerate(c.layer_types):
                h, counters = block(op, c, self.dtype, self.param_dtype,
                                    name=f"layer_{i}")(h)
                if counters is not None:
                    (ssm if op == "mamba" else moe).append(counters)
            pairs_all = tokens.size * c.top_k
            counters = dict(ssm_counters(ssm),
                            **moe_counters(moe, pairs_all))
            if moe:  # the hottest layer: what the usual buffer is sized by
                counters["moe_pairs_here_share_max"] = jnp.max(jnp.stack(
                    [m["pairs_here"] for m in moe])) / pairs_all
                counters["moe_buffer_fill_max"] = jnp.max(jnp.stack(
                    [m["buffer_fill"] for m in moe]))
                counters["moe_weight_fetch_share"] = jnp.mean(jnp.stack(
                    [m["weight_fetch_share"] for m in moe]))
        log_saves("nemotron_h", len(c.layer_types), saved, REMAT_SAVES)
        log_flash_grid(saved, tokens.shape[1])
        with jax.named_scope("dsod.heads"):
            h = RMSNorm(c.norm_eps, self.dtype, name="final_norm")(h)
            h = Head(c.vocab, self.param_dtype, name="head")(h)
        return h, counters
