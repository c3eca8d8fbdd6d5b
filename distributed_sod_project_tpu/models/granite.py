"""Granite-4.0-H (ibm-granite, ``granitemoehybrid``, dense): Mamba-2
state-space layers with one position-free grouped-query attention layer
to every nine of them — one pipeline stage of the model, a whole period
of its layer pattern.

The zoo's third token model (``kind = "tokens"``, the contract of
``models/lfm2.py``: ``apply(variables, tokens, train=...) -> (hidden
after the final norm, counters)``).  ``RMSNorm``, ``SwiGLU``, the
per-layer remat with named saves and the causal flash kernel are the
first token model's, imported and not copied.  Width ``hidden``
throughout, no bias anywhere but the conv's; with ``u = RMSNorm(h)``:

- input ``h = embedding_multiplier * E[token]``; output
  ``RMSNorm(h) / logits_scaling``, multiplied by the tied ``E^T`` in the
  loss (``losses/token_ce.py``);
- block: ``h += residual_multiplier * Mix(u)``, then ``h +=
  residual_multiplier * SwiGLU(RMSNorm(h))``;
- *attention* (:class:`Attention`): grouped-query heads, NO rotation and
  no QK-norm, scores ``q k^T * attention_multiplier`` (the kernel's
  ``1 / sqrt(head_dim)`` times an exact factor on q), causal softmax in
  the Pallas flash kernel;
- *mamba* (:class:`Mamba2Mixer`): ``[z | xBC | dt] = u W_in``; ``xBC =
  silu(conv(xBC) + bias)``, depthwise and causal over ``ssm_conv`` taps;
  ``[x | B | C] = xBC`` with x as ``ssm_heads`` heads of
  ``ssm_head_dim``; ``delta = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head ``H_t = exp(delta_t A) H_{t-1} + delta_t x_t
  B_t^T``, ``y_t = H_t C_t + D x_t`` (``pallas/ssd_scan.py``: the state
  starts at zero in every sequence and is not reset at a document
  join); ``out = RMSNorm(y * silu(z)) W_out``, the norm over all the
  columns at once.

Compute is ``dtype`` (bf16) with float32 parameters; ``dt``, ``delta A``
and its running sums, the carried state, every norm's statistics and
the softmax are float32.  When ``remat`` is on each block's backward
recomputes the block from its input except the values
:data:`REMAT_SAVES` names.

Device scopes (PERF.md section 3): ``dsod.encoder`` over the stack;
``dsod.ssm`` around the mixer, inside it ``dsod.ssm.conv`` (the two
conv kernels), ``dsod.ssm.scan`` (delta, the running sums, the two
kernels) and ``dsod.ssm.gate`` (the D skip and the gated norm);
``dsod.attn`` and ``dsod.densemlp`` as in ``lfm2.py``; the final norm is
``dsod.heads``.  Counters beside ``grad_norm``: ``ssm_decay_min`` (the
smallest ``exp(delta A)`` of any head, token and layer of the step: how
fast the state forgets) and ``ssm_delta_max``.
"""

from __future__ import annotations

import collections
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..pallas.causal_conv import causal_conv_silu
from ..pallas.flash_attention import (CAUSAL_RESIDUAL_NAMES,
                                      flash_attention_causal)
from ..pallas.ssd_scan import ssd_scan
from .lfm2 import (RMSNorm, SwiGLU, _dense, _saves_counted, log_flash_grid,
                   log_saves)

# What a rematerialised layer KEEPS: the flash kernel's output and lse
# (without them its forward runs twice).  Not the scan's output and
# chunk states (``ssd_scan.SSD_RESIDUAL_NAMES``: 256 MiB a layer at
# 16,384 tokens, nine layers): the compiler's books do not hold them
# beside 11.5 GiB of state, so the backward runs the forward scan
# kernel again (PERF.md section 4).
REMAT_SAVES = CAUSAL_RESIDUAL_NAMES[1:]
_SAVE_NAMED = jax.checkpoint_policies.save_only_these_names(*REMAT_SAVES)


class Attention(nn.Module):
    """Causal grouped-query attention, position-free
    (``position_embedding_type: nope``) and without QK-norm."""
    heads: int
    kv_heads: int
    head_dim: int
    multiplier: float = 0.0   # on the scores; 0: 1 / sqrt(head_dim)
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, n, d = x.shape
        hq, hkv, hd = self.heads, self.kv_heads, self.head_dim

        def heads(name, h):
            return _dense(h * hd, name, self.dtype, self.param_dtype)(
                x).reshape(b, n, h, hd).transpose(0, 2, 1, 3)

        q, k, v = heads("q_proj", hq), heads("k_proj", hkv), \
            heads("v_proj", hkv)
        if self.multiplier:  # the kernel multiplies by 1 / sqrt(hd)
            q = q * jnp.asarray(self.multiplier * hd ** 0.5, q.dtype)
        o = flash_attention_causal(q, k, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, n, hq * hd)
        return _dense(d, "o_proj", self.dtype, self.param_dtype)(o)


class CausalConv(nn.Module):
    """``silu(conv(x) + bias)``: a depthwise causal convolution with a
    bias and its activation, float32 arithmetic on operands and a result
    of ``x.dtype`` (``pallas/causal_conv.py``).  ``kernel`` is [L, D];
    tap j multiplies the input L-1-j positions back."""
    taps: int = 4
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):                       # [B, N, D]
        d = x.shape[-1]
        k = self.param("kernel", nn.initializers.lecun_normal(),
                       (self.taps, d), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (d,),
                          self.param_dtype)
        return causal_conv_silu(x, k, bias)


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype):
    """The inverse softplus of a step size log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2Mixer(nn.Module):
    heads: int
    head_dim: int
    state: int
    taps: int = 4
    chunk: int = 256
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        b, n, d = u.shape
        h, p, s = self.heads, self.head_dim, self.state
        inner = h * p
        zxbcdt = _dense(2 * inner + 2 * s + h, "in_proj", self.dtype,
                        self.param_dtype)(u)
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * s], -1)
        with jax.named_scope("dsod.ssm.conv"):
            xbc = CausalConv(self.taps, self.param_dtype, name="conv")(xbc)
        x, bm, cm = jnp.split(xbc, [inner, inner + s], -1)
        x = x.reshape(b, n, h, p)
        vec = lambda name, init: self.param(  # noqa: E731
            name, init, (h,), self.param_dtype)
        with jax.named_scope("dsod.ssm.scan"):
            delta = jax.nn.softplus(dt.astype(jnp.float32)
                                    + vec("dt_bias", _dt_bias_init))
            a = -jnp.exp(vec("A_log", _a_log_init))
            # (a sequence shorter than the chunk is one chunk: the
            # 128-token trace that declares the parameters)
            y = ssd_scan(x, delta, a, bm, cm, chunk=min(self.chunk, n))
            counters = {"decay_min": jnp.exp(jnp.min(delta * a)),
                        "delta_max": jnp.max(delta)}
        with jax.named_scope("dsod.ssm.gate"):
            skip = vec("D", nn.initializers.ones)
            y = y.astype(jnp.float32) + skip[:, None] * x.astype(jnp.float32)
            gated = y.reshape(b, n, inner) * nn.silu(z.astype(jnp.float32))
            gated = RMSNorm(self.eps, self.dtype, name="norm")(gated)
        return _dense(d, "out_proj", self.dtype, self.param_dtype)(
            gated), counters


class Block(nn.Module):
    op: str           # mamba | attention
    cfg: Any          # configs.base.LMConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)

        def scaled(out):  # 0.22 is no bfloat16 number: scale in float32
            return (out.astype(jnp.float32)
                    * c.residual_multiplier).astype(self.dtype)

        y = RMSNorm(c.norm_eps, self.dtype, name="op_norm")(h)
        counters = None
        if self.op == "mamba":
            with jax.named_scope("dsod.ssm"):
                out, counters = Mamba2Mixer(
                    c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_conv,
                    c.ssm_chunk, c.norm_eps, name="mixer",
                    **kw)(y)
        else:
            with jax.named_scope("dsod.attn"):
                out = Attention(c.heads, c.kv_heads, c.head_dim,
                                c.attention_multiplier, name="attn",
                                **kw)(y)
        h = h + scaled(out)
        y = RMSNorm(c.norm_eps, self.dtype, name="ffn_norm")(h)
        with jax.named_scope("dsod.densemlp"):
            return h + scaled(SwiGLU(c.dense_width, name="mlp", **kw)(y)), \
                counters


class Granite(nn.Module):
    """``cfg`` is the frozen ``configs.base.LMConfig`` (``model.lm``):
    the published widths, the layers kept and the vocabulary's slice."""
    cfg: Any
    remat: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    kind = "tokens"  # what engine.py / loop.py route on

    @nn.compact
    def __call__(self, tokens, *, train: bool = False):
        del train  # no dropout, no buffers
        c = self.cfg
        saved = collections.Counter()
        block = (nn.remat(Block, policy=_saves_counted(saved, _SAVE_NAMED))
                 if self.remat else Block)
        per_layer = []
        with jax.named_scope("dsod.encoder"):
            h = nn.Embed(c.vocab, c.hidden, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="embed")(tokens)
            h = h * jnp.asarray(c.embedding_multiplier, self.dtype)
            for i, op in enumerate(c.layer_types):
                h, counters = block(op, c, self.dtype, self.param_dtype,
                                    name=f"layer_{i}")(h)
                if counters is not None:
                    per_layer.append(counters)
            counters = ssm_counters(per_layer)
        log_saves("granite", len(c.layer_types), saved, REMAT_SAVES)
        log_flash_grid(saved, tokens.shape[1])
        with jax.named_scope("dsod.heads"):
            h = RMSNorm(c.norm_eps, self.dtype, name="final_norm")(h)
            h = h * jnp.asarray(1.0 / c.logits_scaling, self.dtype)
        return h, counters


def ssm_counters(per_layer):
    """The trainer's counters from the mixers' own, over the layers."""
    if not per_layer:
        return {}
    return {
        "ssm_decay_min": jnp.min(jnp.stack(
            [c["decay_min"] for c in per_layer])),
        "ssm_delta_max": jnp.max(jnp.stack(
            [c["delta_max"] for c in per_layer]))}
