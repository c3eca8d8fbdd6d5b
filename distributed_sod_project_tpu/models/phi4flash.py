"""Phi-4-mini-flash-reasoning (microsoft, ``phi4flash``; the SambaY
decoder-hybrid-decoder of Ren et al., arXiv:2507.06607, with the
differential attention of Ye et al., arXiv:2410.05258) — one pipeline
stage of the model: the stretch on which the self-decoder hands over to
the cross-decoder, so that it holds every one of the five layer kinds.

The zoo's sixth token model (``kind = "tokens"``, the contract of
``models/lfm2.py``: ``apply(variables, tokens, train=...) -> (hidden
after the final norm, counters)``).  ``SwiGLU``, ``_dense``, the
per-layer remat with named saves and the causal flash kernel are the
first token model's, the conv kernel the third's, imported and not
copied.  Width ``hidden`` throughout; LayerNorm with scale and bias;
the model is position-free.  With ``u = LN_1(h)``, every layer is ``h +=
Mix(u)`` then ``h += SwiGLU(LN_2(h))``; ``layer_types`` names ``Mix``:

- *mamba* (:class:`Mamba1Mixer`): ``[x | z] = u W_in``; ``x =
  silu(conv(x) + bias)``, depthwise and causal over ``ssm_conv`` taps;
  ``[r | B | C] = x W_x`` (``ssm_dt_rank`` | ``ssm_state`` |
  ``ssm_state``); ``delta = softplus(r W_dt + dt_bias)``; ``A =
  -exp(A_log)``, one number a (channel, state) pair; ``y`` = the scan of
  ``pallas/selective_scan.py`` with its ``D`` skip; ``out = (y *
  silu(z)) W_out``.  The layer also hands on ``m = y``, before the gate;
- *window* / *full* (:class:`DiffAttention`): ``[q | k | v] = u W_qkv +
  b``; heads pair by parity — ``q1`` / ``k1`` the even heads, ``q2`` /
  ``k2`` the odd, a pair's value the two value heads side by side (twice
  ``head_dim`` wide); query pair i reads key/value pair ``i // (heads /
  kv_heads)``; ``o_i = (1 - lambda_init) RMSNorm(softmax(q1 k1^T) V -
  lambda softmax(q2 k2^T) V)`` with ``lambda = exp(lq1 . lk1) - exp(lq2 .
  lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` at the
  PUBLISHED layer index ``l = first_layer + i``; the pairs side by side
  through ``W_o + b``.  Causal; a *window* layer's query sees its last
  ``window`` keys alone, its own among them.  A *full* layer also hands
  on its ``k1 | k2`` and its values;
- *gmu*: ``out = (silu(u W_1) * m) W_2``, ``m`` the latest mamba
  layer's;
- *cross*: ``q = u W_q + b`` alone; differential attention as above,
  with lambdas, sub-norm and ``W_o`` of its own, against the latest full
  layer's keys and values, causal.

BOTH softmax maps of a layer run in ONE call of the causal flash kernel
(``pallas/flash_attention.py``): queries ``[q1 ; q2]`` over keys ``[k1 ;
k2]`` and the values twice, so that query head ``h`` reads key/value
head ``h // group`` as the kernel pairs them; the value is twice the
key's width, which the kernel takes as it is.

The kept tensors (``m``; the keys and values) are results of the
rematerialised block that makes them and arguments of those that read
them: their cotangents from every reader sum in the maker's backward.

Compute is ``dtype`` (bf16) with float32 parameters; ``delta``, ``delta
A``, the carried state, the softmax, lambda, every norm's statistics
and the loss are float32.  When ``remat`` is on each block's backward
recomputes the block from its inputs except the values
:data:`REMAT_SAVES` names.

Device scopes (PERF.md section 3): ``dsod.encoder`` over the stack;
``dsod.ssm`` around the mamba mixer, inside it ``dsod.ssm.conv``,
``dsod.ssm.scan`` (delta, ``delta A``'s extremes, the two kernels) and
``dsod.ssm.gate``; ``dsod.attn.window`` and ``dsod.attn.full`` (full
and cross layers) and, inside each, ``dsod.attn.flash`` around the
kernel call alone; ``dsod.gmu``; ``dsod.densemlp``; the final norm is
``dsod.heads``.  Counters beside ``grad_norm``: ``ssm_decay_min``,
``ssm_delta_max`` (``models/granite.py``'s), ``diff_lambda_min`` /
``diff_lambda_max`` over the attention layers, ``gmu_memory_abs_max``.
"""

from __future__ import annotations

import collections
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..pallas.flash_attention import (CAUSAL_RESIDUAL_NAMES,
                                      flash_attention_causal)
from ..pallas.selective_scan import SEL_RESIDUAL_NAMES, selective_scan
from .granite import CausalConv, _dt_bias_init, ssm_counters
from .lfm2 import (RMSNorm, SwiGLU, _dense, _saves_counted, log_flash_grid,
                   log_saves)

# What a rematerialised layer KEEPS: the flash kernel's output and lse,
# and the scan's output and the states its chunks started from — without
# them each kernel's forward runs twice (PERF.md section 4 has the bytes
# and the measurement).
REMAT_SAVES = CAUSAL_RESIDUAL_NAMES[1:] + SEL_RESIDUAL_NAMES
_SAVE_NAMED = jax.checkpoint_policies.save_only_these_names(*REMAT_SAVES)

KINDS = ("mamba", "window", "full", "gmu", "cross")
# What a layer of a kind hands on, and which kept value a kind reads.
KEEPS = {"mamba": "memory", "full": "keys_values"}
READS = {"gmu": "memory", "cross": "keys_values"}


def lambda_init(depth: int) -> float:
    """Differential attention's starting lambda at PUBLISHED layer
    ``depth``."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def out_gain(depth: int) -> float:
    """What the normed difference of the two maps is multiplied by."""
    return 1.0 - lambda_init(depth)


class LayerNorm(nn.Module):
    """Scale and bias, float32 statistics."""
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (d,), jnp.float32)
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, -1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), -1, keepdims=True)
        return ((x32 - mean) * lax.rsqrt(var + self.eps) * scale
                + bias).astype(self.dtype)


def _biased(features, name, dtype, param_dtype):
    return nn.Dense(features, use_bias=True, dtype=dtype,
                    param_dtype=param_dtype, name=name,
                    kernel_init=nn.initializers.lecun_normal())


def _a_log_init(key, shape, dtype):
    """Mamba-1's S4D-real start: ``A[c, n] = -(n + 1)``."""
    del key
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)),
                            shape)


class Mamba1Mixer(nn.Module):
    inner: int
    state: int
    dt_rank: int
    taps: int = 4
    chunk: int = 128
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        n, d = u.shape[1:]
        c, s, r = self.inner, self.state, self.dt_rank
        kw = (self.dtype, self.param_dtype)
        x, z = jnp.split(_dense(2 * c, "in_proj", *kw)(u), 2, -1)
        with jax.named_scope("dsod.ssm.conv"):
            x = CausalConv(self.taps, self.param_dtype, name="conv")(x)
        rbc = _dense(r + 2 * s, "x_proj", *kw)(x)
        step, bm, cm = jnp.split(rbc, [r, r + s], -1)
        with jax.named_scope("dsod.ssm.scan"):
            w_dt = self.param(
                "dt_proj", nn.initializers.variance_scaling(
                    1.0 / 3, "fan_in", "uniform"), (r, c), self.param_dtype)
            delta = jax.nn.softplus(
                jnp.einsum("bnr,rc->bnc", step, w_dt.astype(self.dtype),
                           preferred_element_type=jnp.float32)
                + self.param("dt_bias", _dt_bias_init, (c,),
                             self.param_dtype))
            a = -jnp.exp(self.param("A_log", _a_log_init, (c, s),
                                    self.param_dtype))
            skip = self.param("D", nn.initializers.ones, (c,),
                              self.param_dtype)
            # (a sequence shorter than the chunk is one chunk: the
            # 128-token trace that declares the parameters)
            y = selective_scan(x, delta, a, bm, cm, skip,
                               chunk=min(self.chunk, n))
            # delta > 0 > A: the fastest decay of a channel is its
            # largest step times its most negative A.
            counters = {
                "decay_min": jnp.exp(jnp.min(
                    jnp.max(delta, (0, 1)) * jnp.min(a, -1))),
                "delta_max": jnp.max(delta)}
        with jax.named_scope("dsod.ssm.gate"):
            gated = y * nn.silu(z)
        return _dense(d, "out_proj", *kw)(gated), y, counters


class DiffAttention(nn.Module):
    """Differential attention over head pairs; ``kept`` = (keys, values)
    of an earlier layer makes it cross-attention."""
    heads: int
    kv_heads: int
    head_dim: int
    depth: int               # the PUBLISHED layer index
    window: int = 0          # 0: every earlier key
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u, kept=()):
        b, n, d = u.shape
        hq, hkv, hd = self.heads, self.kv_heads, self.head_dim
        kw = (self.dtype, self.param_dtype)

        def by_parity(t, h):
            """[B, N, h * hd] -> [B, h, N, hd], even heads then odd."""
            t = t.reshape(b, n, h // 2, 2, hd)
            return t.transpose(0, 3, 2, 1, 4).reshape(b, h, n, hd)

        if kept:
            q = _biased(hq * hd, "q_proj", *kw)(u)
            k, v = kept
        else:
            q, k, v = jnp.split(_biased((hq + 2 * hkv) * hd, "qkv_proj",
                                        *kw)(u), [hq * hd, (hq + hkv) * hd],
                                -1)
            k = by_parity(k, hkv)
            # a pair's value: its two value heads side by side
            v = v.reshape(b, n, hkv // 2, 2 * hd).transpose(0, 2, 1, 3)
        with jax.named_scope("dsod.attn.flash"):
            both = flash_attention_causal(
                by_parity(q, hq), k, jnp.concatenate([v, v], 1),
                window=self.window or None)
        vec = lambda name: self.param(  # noqa: E731
            name, nn.initializers.normal(0.1), (hd,), jnp.float32)
        lam = jnp.exp(jnp.sum(vec("lambda_q1") * vec("lambda_k1"))) \
            - jnp.exp(jnp.sum(vec("lambda_q2") * vec("lambda_k2"))) \
            + lambda_init(self.depth)
        a1, a2 = jnp.split(both.astype(jnp.float32), 2, 1)
        o = RMSNorm(self.eps, jnp.float32, name="subln")(a1 - lam * a2)
        o = (out_gain(self.depth) * o).astype(self.dtype)
        o = o.transpose(0, 2, 1, 3).reshape(b, n, hq * hd)
        return _biased(d, "o_proj", *kw)(o), (k, v), lam


class GatedMemoryUnit(nn.Module):
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u, m):
        kw = (self.dtype, self.param_dtype)
        gate = nn.silu(_dense(m.shape[-1], "in_proj", *kw)(u))
        return _dense(u.shape[-1], "out_proj", *kw)(gate * m)


class Block(nn.Module):
    op: str           # one of KINDS
    depth: int        # the PUBLISHED layer index
    cfg: Any          # configs.base.LMConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, kept=()):
        """-> (h, what this layer hands on, its counters)."""
        c = self.cfg
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        u = LayerNorm(c.norm_eps, self.dtype, name="op_norm")(h)
        counters = {}
        if self.op == "mamba":
            with jax.named_scope("dsod.ssm"):
                out, y, counters = Mamba1Mixer(
                    c.ssm_heads * c.ssm_head_dim, c.ssm_state,
                    c.ssm_dt_rank, c.ssm_conv, c.ssm_chunk, name="mixer",
                    **kw)(u)
            kept = (y,)
        elif self.op == "gmu":
            with jax.named_scope("dsod.gmu"):
                out = GatedMemoryUnit(name="gmu", **kw)(u, *kept)
            counters = {"memory_abs_max": jnp.max(jnp.abs(kept[0]))}
            kept = ()
        elif self.op in ("window", "full", "cross"):
            windowed = self.op == "window"
            with jax.named_scope("dsod.attn.window" if windowed
                                 else "dsod.attn.full"):
                out, kv, lam = DiffAttention(
                    c.heads, c.kv_heads, c.head_dim, self.depth,
                    c.window if windowed else 0, c.norm_eps, name="attn",
                    **kw)(u, kept)
            counters = {"lambda": lam}
            kept = () if self.op == "cross" else kv
        else:
            raise ValueError(f"layer kind {self.op!r} is none of {KINDS}")
        h = h + out
        u = LayerNorm(c.norm_eps, self.dtype, name="ffn_norm")(h)
        with jax.named_scope("dsod.densemlp"):
            return h + SwiGLU(c.dense_width, name="mlp", **kw)(u), kept, \
                counters


class Phi4Flash(nn.Module):
    """``cfg`` is the frozen ``configs.base.LMConfig`` (``model.lm``):
    the published widths, the layers kept (``layer_types``, from
    published layer ``first_layer`` on) and the vocabulary's slice."""
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
        kept = {}   # by KEEPS' names: the latest such layer's
        with jax.named_scope("dsod.encoder"):
            h = nn.Embed(c.vocab, c.hidden, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="embed")(tokens)
            for i, op in enumerate(c.layer_types):
                reads = kept.get(READS.get(op), ())
                if op in READS and not reads:
                    raise ValueError(f"layer {i} ({op}) has no earlier "
                                     "layer to read from")
                h, hands_on, counters = block(
                    op, c.first_layer + i, c, self.dtype, self.param_dtype,
                    name=f"layer_{i}")(h, reads)
                if op in KEEPS:
                    kept[KEEPS[op]] = hands_on
                per_layer.append(counters)
            counters = stage_counters(per_layer)
        log_saves("phi4flash", len(c.layer_types), saved, REMAT_SAVES)
        n = tokens.shape[1]
        if "full" in c.layer_types or "cross" in c.layer_types:
            log_flash_grid(saved, n)
        if "window" in c.layer_types:
            log_flash_grid(saved, n, c.window)
        with jax.named_scope("dsod.heads"):
            h = LayerNorm(c.norm_eps, self.dtype, name="final_norm")(h)
        return h, counters


def stage_counters(per_layer):
    """The trainer's counters from the layers' own, over the layers."""
    out = ssm_counters([c for c in per_layer if "decay_min" in c])
    lam = [c["lambda"] for c in per_layer if "lambda" in c]
    if lam:
        out["diff_lambda_min"] = jnp.min(jnp.stack(lam))
        out["diff_lambda_max"] = jnp.max(jnp.stack(lam))
    mem = [c["memory_abs_max"] for c in per_layer if "memory_abs_max" in c]
    if mem:
        out["gmu_memory_abs_max"] = jnp.max(jnp.stack(mem)).astype(
            jnp.float32)
    return out
