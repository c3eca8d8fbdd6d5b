"""Ouro (ByteDance ``ouro``; Zhu et al., "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741): ONE stack of layers run
``ut_steps`` times on the SAME weights, a learned exit gate read after
every pass, the head applied after every pass — one pipeline stage of
the model.

The zoo's fourth token model (``kind = "tokens"``).  Where the other
three hand ``parallel/engine.py`` one hidden state and take the mean
cross-entropy, this one names its own loss (:meth:`Ouro.token_loss`):
``apply(variables, tokens, train=...) -> ((states [R, B, N, D], gate
logits [R, B, N]), counters)``.  ``RMSNorm``, ``SwiGLU``, ``rope``, the
per-layer remat with counted saves and the causal flash kernel are the
first token model's, the untied ``embed/kernel`` and ``head/embedding``
the second's: imported, not copied; the rotary kernel pair
(``pallas/rotary.py``) is this model's own.  Width ``hidden``
throughout, no bias but the gate's; with ``N*`` RMSNorms with a learned
scale:

- block (sandwich norm): ``a = x + N2(Attn(N1(x)))``, ``y = a +
  N4(SwiGLU(N3(a)))``; ``Attn``: q, k, v, o projections to ``heads`` x
  ``head_dim`` (plain multi-head: ``kv_heads == heads``), rotate-half
  rotary over the whole head on q and k, positions from 0 in every
  packed sequence, causal softmax at ``head_dim ** -0.5`` in the Pallas
  flash kernel; ``SwiGLU``: ``down(silu(gate(u)) * up(u))``;
- loop: ``h_0 = Embed(tokens)``; for t = 1..R: ``h_t = N_f(Stack(h_{t-1}))``
  with ``Stack`` the SAME ``len(layer_types)`` blocks and ``N_f`` the one
  final norm — the normed state is what the next pass reads, what the
  head reads and what the gate reads; ``g_t = w . h_t + b`` in float32;
- loss (``losses/token_ce.py::exit_weighted_cross_entropy``): the exit
  distribution ``p_t = sigmoid(g_t) prod_{j<t} (1 - sigmoid(g_j))``,
  ``p_R`` the rest; ``mean_i [sum_t p_ti CE(Head(h_ti), target_i) - beta
  H(p_.i)]``, the one untied head used R times.

**The loop over passes is traced pass by pass** (a Python loop over ONE
:class:`Pass` module, whose parameters every call shares): ``layers x
R`` visits, two ``pallas_call`` sites each.  The other form, one
``nn.scan`` over the passes with the parameters broadcast, was measured
once and removed (PERF.md section 6, PR 41): it traces the blocks once
(16 kernel sites for 64, ~40 s less of a first run's set-up) but carries
the shared weights' running gradient sums through the loop, ~1 GiB more
that the compiled step reserves, and at the cell's size that program no
longer loads beside the benchmark's first-call copy of the weights.

Compute is ``dtype`` (bf16) with float32 parameters; every norm's
statistics, the softmax, the gate and the loss are float32, and so is
the rotation: float32 angles, tables, products and sum on the
projection's ``dtype`` output, ONE rounding back to ``dtype`` — inside
the rotary kernel where a head fills the chip's 128 lanes (q and k in
one call, each read and written once; its backward the same kernel with
the sine negated, no residual but the tables), in XLA on a float32 copy
(``rope``) at any other width (:func:`rotated`).  When ``remat`` is on
each VISIT of a block (``layers x R`` a step) recomputes the block from
its input in the backward except the values :data:`REMAT_SAVES` names:
the rotation runs twice forward and once backward a visit.

Device scopes (PERF.md section 3): ``dsod.encoder`` over embedding and
loop; ``dsod.loop`` around the looped stack, inside it ``dsod.attn``
(the flash call alone under ``dsod.attn.core``; the rotation's
``dsod.kernel.rotary`` / ``dsod.kernel.rotary_bwd`` beside it, outside
the core), ``dsod.densemlp`` and
``dsod.loop.exit`` (final norm and gate; the distribution and entropy
carry the same name inside ``dsod.loss``); the R head products are
``dsod.heads``.  Counters beside ``grad_norm``: ``loop_exit_mass_t``,
``loop_exit_entropy``, ``loop_ce_t``.
"""

from __future__ import annotations

import collections
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..losses.token_ce import exit_weighted_cross_entropy
from ..pallas.flash_attention import (CAUSAL_RESIDUAL_NAMES,
                                      flash_attention_causal)
from ..pallas.rotary import rotate_half
from .kimi import Embed, Head
from .lfm2 import (RMSNorm, SwiGLU, _dense, _saves_counted, log_flash_grid,
                   log_saves, rope)

# What a rematerialised VISIT keeps: the flash kernel's output and lse
# (33 MiB a visit at 8,192 tokens, 32 visits; without them the forward
# kernel runs twice a visit).  Not q, k, v as the kernel takes them
# (96 MiB a visit: 3 GiB).
REMAT_SAVES = CAUSAL_RESIDUAL_NAMES[1:]
_SAVE_NAMED = jax.checkpoint_policies.save_only_these_names(*REMAT_SAVES)


def rotated(q, k, theta: float):
    """q, k: [B, N, H, d] in the compute dtype -> both rotated,
    head-major [B, H, N, d].  A head that fills the chip's 128 lanes goes
    through the rotary kernel (both tensors in one call, read and written
    once in their own dtype); any other width through ``rope`` on a
    float32 copy.  The same arithmetic either way: float32 tables,
    products and sum, one rounding."""
    if q.shape[-1] % 128 == 0:
        return rotate_half(
            (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)), theta)
    return tuple(rope(t.astype(jnp.float32), theta).astype(
        t.dtype).transpose(0, 2, 1, 3) for t in (q, k))


class Attention(nn.Module):
    """Causal multi-head attention, rotary on the whole head."""
    heads: int
    head_dim: int
    rope_theta: float = 1e6
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, n, d = x.shape
        h, hd = self.heads, self.head_dim

        def heads(name):
            return _dense(h * hd, name, self.dtype, self.param_dtype)(
                x).reshape(b, n, h, hd)

        q, k = rotated(heads("q_proj"), heads("k_proj"), self.rope_theta)
        v = heads("v_proj").transpose(0, 2, 1, 3)
        with jax.named_scope("dsod.attn.core"):
            o = flash_attention_causal(q, k, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, n, h * hd)
        return _dense(d, "o_proj", self.dtype, self.param_dtype)(o)


class Block(nn.Module):
    cfg: Any          # configs.base.LMConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(  # noqa: E731
            c.norm_eps, self.dtype, name=name)
        # Stage and loop are named HERE too: a jitted helper (silu) under
        # the visit's checkpoint lowers to a function of its own, whose
        # ops carry the path from the checkpoint down.
        with jax.named_scope("dsod.encoder"), jax.named_scope("dsod.loop"):
            with jax.named_scope("dsod.attn"):
                a = Attention(c.heads, c.head_dim, c.rope_theta,
                              name="attn", **kw)(norm("attn_norm")(x))
            a = x + norm("attn_out_norm")(a)
            with jax.named_scope("dsod.densemlp"):
                y = SwiGLU(c.dense_width, name="mlp", **kw)(
                    norm("ffn_norm")(a))
            return a + norm("ffn_out_norm")(y)


class Pass(nn.Module):
    """One pass: every block once, the final norm, the gate.  Called R
    times; every call reads the same parameters.  -> (what the next pass
    reads, what the head reads, the gate logits); the first two are ONE
    array, the normed state."""
    block: Any        # Block, or its rematerialised form
    cfg: Any
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        for i in range(len(c.layer_types)):
            h = self.block(c, self.dtype, self.param_dtype,
                           name=f"layer_{i}")(h)
        with jax.named_scope("dsod.loop.exit"):
            h = RMSNorm(c.norm_eps, self.dtype, name="final_norm")(h)
            gate = nn.Dense(
                1, dtype=jnp.float32, param_dtype=self.param_dtype,
                precision=lax.Precision.HIGHEST, name="exit_gate",
                kernel_init=nn.initializers.lecun_normal())(
                    h.astype(jnp.float32))[..., 0]
        return h, h, gate


class Ouro(nn.Module):
    """``cfg`` is the frozen ``configs.base.LMConfig`` (``model.lm``):
    the published widths, the layers this stage holds, the passes."""
    cfg: Any
    remat: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    kind = "tokens"                  # what engine.py / loop.py route on
    head = ("head", "embedding")     # the loss's matrix, in ``params``

    @nn.compact
    def __call__(self, tokens, *, train: bool = False):
        del train  # no dropout, no buffers
        c = self.cfg
        saved = collections.Counter()
        block = (nn.remat(Block, policy=_saves_counted(saved, _SAVE_NAMED))
                 if self.remat else Block)
        with jax.named_scope("dsod.encoder"):
            h = Embed(c.vocab, c.hidden, self.dtype, self.param_dtype,
                      name="embed")(tokens)
            one = Pass(block, c, self.dtype, self.param_dtype, name="loop")
            states, gates = [], []
            with jax.named_scope("dsod.loop"):
                for _ in range(c.ut_steps):
                    h, state, gate = one(h)
                    states.append(state)
                    gates.append(gate)
            states, gates = jnp.stack(states), jnp.stack(gates)
        log_saves("ouro", len(c.layer_types) * c.ut_steps, saved,
                  REMAT_SAVES, unit="visits")
        log_flash_grid(saved, tokens.shape[1])
        with jax.named_scope("dsod.heads"):
            Head(c.vocab, self.param_dtype, name="head")(states)
        return (states, gates), {}

    @nn.nowrap
    def token_loss(self, outputs, params, targets):
        """The model's own loss, as ``parallel/engine.py`` calls it:
        -> (total, counters)."""
        states, gates = outputs
        return exit_weighted_cross_entropy(
            states, gates, params["head"]["embedding"], targets,
            beta=self.cfg.exit_beta)
