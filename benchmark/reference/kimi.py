"""The plain reference of Kimi-VL-A3B-Instruct's decoder (moonshotai,
``config.json`` ``text_config``), one chip's share of an 8-way
expert-parallel stage.

Straightforward ``jax.numpy`` in float32 at matmul precision HIGHEST:
no kernels, no sort, no buffer, no chunked loss, nothing imported from
the program (the products, norm, SwiGLU and AdamW are those of
``reference/lfm2.py``).  The weights are the benchmark's own
(``harness/weights_lm.py``) under the program's parameter names.  One
sequence at a time, attention in query blocks, each layer
rematerialised.

The model, as the catalog row's ``config`` gives it (what it does not
give is listed under ``assumed`` in ``configs/kimi_vl_a3b_ep8.json``),
with ``x = RMSNorm(h)``, H heads, ``q_lora_rank`` null:

- *latent attention*: ``q = x W_q`` in R^(H x (dn + dr)), each head
  ``[q_nope ; q_rope]``; ``[c ; k_r] = x W_kva`` in R^(rank + dr);
  ``[k_nope ; v] = RMSNorm(c) W_kvb`` in R^(H x (dn + dv)); ``q_rope``
  and ``k_r`` are rotated (rotate-half form, positions from 0): ONE
  rotary key a token, used by every head; scores ``(q_nope . k_nope +
  q_rope . k_rope) / sqrt(dn + dr)``, causal softmax, ``o_h = p v_h``,
  ``out = concat(o_h) W_o``; no bias anywhere;
- layer 0: SwiGLU; the others: ``s = sigmoid(x W_r)``, chosen = top-k of
  ``s + expert_bias`` (the bias only selects; no group limit), weights
  ``routed_scaling_factor * s / (sum of the chosen s + 1e-20)``;
  ``out = sum_e w_e SwiGLU_e(x)`` over the experts HELD here, plus the
  shared experts' SwiGLU over every token;
- final RMSNorm, an output head of its own (``head/embedding``);
  next-token cross-entropy over the vocabulary slice held;
- balancing (``topk_method: noaux_tc``): after each step, per expert
  layer, ``expert_bias += gamma * sign(mean(c) - c)`` with ``c`` the
  pairs the step's tokens sent to each of ALL experts.

``prec`` selects the arithmetic of every projection and expert product
(``f32`` | ``bf16`` | ``fp8``); router, norms, softmax and loss stay
float32 in all three.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .lfm2 import (HI, Q_BLOCK, _leaf_norms, _mm, adamw_update, mm, rms_norm,
                   swiglu)

HEAD_GROUP = 4  # heads attended at a time (memory alone; any divisor of H)


def rope(x, theta):
    """x: [N, H, d]; rotate-half form, positions 0..N-1."""
    n, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention(x, p, m, prec="f32", remat=True):
    """Causal latent attention over one sequence [N, D].  ``Q_BLOCK``
    query rows at a time, each block against the keys up to its own last
    row, rematerialised in the backward when ``remat``."""
    n = x.shape[0]
    h, dn, dr, dv = m["heads"], m["nope_dim"], m["rope_dim"], m["v_dim"]
    rank = m["kv_rank"]
    q = mm(x, p["q_proj"]["kernel"], prec).reshape(n, h, dn + dr)
    kva = mm(x, p["kv_a_proj"]["kernel"], prec)
    c = rms_norm(kva[:, :rank], p["kv_a_norm"]["scale"], m["norm_eps"])
    k_rope = rope(kva[:, None, rank:], m["rope_theta"])[:, 0]  # one head
    kv = mm(c, p["kv_b_proj"]["kernel"], prec).reshape(n, h, dn + dv)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], m["rope_theta"])
    k_nope, v = kv[..., :dn], kv[..., dn:]

    def block(qn, qr, kn, kr, vi, *, row0):
        # The keys up to this block's last row are cut HERE, inside what
        # is rematerialised: cut outside, every block would keep its own
        # copy of them for the backward (4 GiB a layer at 16k tokens).
        kn, kr, vi = (t[:row0 + Q_BLOCK] for t in (kn, kr, vi))
        s = (jnp.einsum("qhd,khd->hqk", qn, kn, precision=HI)
             + jnp.einsum("qhd,kd->hqk", qr, kr, precision=HI)) \
            / np.sqrt(dn + dr)
        row = row0 + jnp.arange(qn.shape[0])
        s = jnp.where(jnp.arange(kn.shape[0])[None, :] <= row[:, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vi,
                          precision=HI)

    def at(r):
        f = functools.partial(block, row0=r)
        return jax.checkpoint(f) if remat else f

    def heads(group):
        """One group of heads [N, g, *] at a time (``lax.map``: one
        after another in the backward too), so that the score blocks
        and the blocks' key and value cotangents of all 16 heads are
        never held at once: beside 10 GB of float32 state they do not
        fit."""
        qn, qr, kn, vi = group
        return jnp.concatenate([
            at(r)(qn[r:r + Q_BLOCK], qr[r:r + Q_BLOCK], kn, k_rope, vi)
            for r in range(0, n, Q_BLOCK)])

    g = min(HEAD_GROUP, h)
    split = lambda t: jnp.moveaxis(  # noqa: E731  [N, H, d] -> [H/g, N, g, d]
        t.reshape(n, h // g, g, t.shape[-1]), 1, 0)
    o = lax.map(heads, tuple(split(t) for t in (q_nope, q_rope, k_nope, v)))
    return mm(jnp.moveaxis(o, 0, 1).reshape(n, h * dv),
              p["o_proj"]["kernel"], prec)


def route(x, p, bias, m):
    """-> (idx [N, k] over all E experts, weights [N, k])."""
    s = jax.nn.sigmoid(_mm(x, p["router"]["kernel"]))
    _, idx = lax.top_k(s + lax.stop_gradient(bias), m["top_k"])
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * m["routed_scaling_factor"]


def moe(x, p, shared, bias, m, prec="f32", remat=True):
    """The routed experts held (``first_expert + (0 .. E_held-1)``, every
    one over every token, weighted by what the router gave it) plus the
    shared experts -> (out, pairs sent to each of ALL experts).  Each
    expert is rematerialised on its own when ``remat``: eight experts'
    products over every token are 2 GiB to keep for a layer's backward."""
    idx, w = route(x, p, bias, m)
    out = swiglu(x, shared, prec)
    one = functools.partial(swiglu, prec=prec)
    if remat:
        one = jax.checkpoint(one)
    for e in range(p["gate"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == m.get("first_expert", 0) + e, w, 0.0),
                      -1)
        pe = {k: {"kernel": p[k][e]} for k in ("gate", "up", "down")}
        out = out + w_e[:, None] * one(x, pe)
    sent = jnp.sum(idx.reshape(-1, 1) == jnp.arange(bias.shape[0]), 0)
    return out, sent.astype(jnp.float32)


# -- the model -------------------------------------------------------------

def hidden(variables, tokens, m, *, prec="f32", remat=True):
    """tokens [N] int -> (the final-norm hidden states [N, D], {layer:
    pairs sent to each expert} for the expert layers)."""
    params, buffers = variables["params"], variables.get("batch_stats", {})
    h = params["embed"]["kernel"][tokens, 0]  # [V, 1, D]: fan-in 1

    def layer(h, p, b, ffn):
        y = rms_norm(h, p["op_norm"]["scale"], m["norm_eps"])
        h = h + attention(y, p["attn"], m, prec, remat)
        y = rms_norm(h, p["ffn_norm"]["scale"], m["norm_eps"])
        if ffn == "dense":
            return h + swiglu(y, p["mlp"], prec), None
        out, sent = moe(y, p["moe"], p["shared"], b["moe"]["expert_bias"], m,
                        prec, remat)
        return h + out, sent

    sent = {}
    for i, ffn in enumerate(m["ffn_types"]):
        f = functools.partial(layer, ffn=ffn)
        if remat:
            f = jax.checkpoint(f)
        name = f"layer_{i}"
        h, c = f(h, params[name], buffers.get(name, {}))
        if c is not None:
            sent[name] = c
    return rms_norm(h, params["final_norm"]["scale"], m["norm_eps"]), sent


def loss_and_sent(variables, tokens, targets, m, **kw):
    """Mean next-token cross-entropy of one sequence over the slice, and
    the expert layers' counts."""
    h, sent = hidden(variables, tokens, m, **kw)
    z = _mm(h, variables["params"]["head"]["embedding"].T)
    lse = jax.nn.logsumexp(z, -1)
    hit = jnp.take_along_axis(z, targets[:, None], -1)[:, 0]
    return jnp.mean(lse - hit), sent


def batch_loss(variables, tokens, targets, m, **kw):
    """Mean over a batch [B, N], one sequence at a time."""
    per = lax.map(lambda tt: loss_and_sent(variables, tt[0], tt[1], m,
                                           **kw)[0], (tokens, targets))
    return jnp.mean(per)


def balance(buffers, sent, gamma):
    """The family's rule: each expert's bias moves by ``gamma`` against
    the sign of its surplus over the mean load."""
    return {name: {"moe": {"expert_bias": b["moe"]["expert_bias"]
                           + gamma * jnp.sign(jnp.mean(sent[name])
                                              - sent[name])}}
            for name, b in buffers.items()}


def follow(make_variables, batches, ref: dict, *, prec="f32", remat=True):
    """Follow ``len(batches)`` train steps from ``make_variables()``
    (called again at the end for the starting point: the step donates
    its arguments).  The gradient of a batch is the mean of its
    sequences' gradients, accumulated one sequence at a time; the
    selection bias moves after each step by the batch's counts.
    Returns the losses, the per-leaf norms of the first gradient and of
    the parameters' change after the last step, and the final bias."""
    m, opt = ref["arch"], ref["optimizer"]

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def step(params, buffers, opt_state, tokens, targets, i):
        def one(carry, tt):
            (l, sent), g = jax.value_and_grad(
                lambda p: loss_and_sent(
                    {"params": p, "batch_stats": buffers}, tt[0], tt[1], m,
                    prec=prec, remat=remat), has_aux=True)(params)
            return jax.tree_util.tree_map(jnp.add, carry, (l, g, sent)), None

        zero = (jnp.float32(0.0),
                jax.tree_util.tree_map(jnp.zeros_like, params),
                {name: jnp.zeros_like(b["moe"]["expert_bias"])
                 for name, b in buffers.items()})
        (l, g, sent), _ = lax.scan(one, zero, (tokens, targets))
        n = tokens.shape[0]
        l, g = l / n, jax.tree_util.tree_map(lambda x: x / n, g)
        new, opt_state = adamw_update(opt, params, g, opt_state, i)
        return (new, balance(buffers, sent, m["bias_update_rate"]),
                opt_state, l, _leaf_norms(g))

    variables = make_variables()
    params, buffers = variables["params"], variables.get("batch_stats", {})
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    opt_state = {"m": zeros(), "v": zeros()}
    del variables
    losses, g1 = [], None
    for i, b in enumerate(batches):
        params, buffers, opt_state, l, gn = step(
            params, buffers, opt_state, jnp.asarray(b["tokens"], jnp.int32),
            jnp.asarray(b["targets"], jnp.int32), jnp.float32(i))
        losses.append(float(l))
        if i == 0:
            g1 = jax.device_get(gn)
    dp = jax.device_get(_leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, params, make_variables()["params"])))
    return {"loss": losses, "grad_norms": g1, "dparam_norms": dp,
            "expert_bias": jax.device_get(buffers)}
