"""The plain reference of Nemotron 3 Super 120B-A12B (nvidia,
``model_type: nemotron_h``): one chip's share of a deployment that
divides every layer (the mixers' heads 8 ways, the routed experts 64
ways), one period of the layer pattern.

Straightforward ``jax.numpy`` in float32 at matmul precision HIGHEST:
no kernel, no chunked scan, no sort, no buffer, nothing imported from
the program (the products, norm and AdamW are those of
``reference/lfm2.py``, the token-by-token recurrence and the
position-free attention those of ``reference/granite.py``, the router
and the balancing rule those of ``reference/kimi.py``).  The weights are
the benchmark's own (``harness/weights_hybrid.py``) under the program's
parameter names.

The model, as the catalog row's ``config`` gives it (what it does not
give is listed under ``assumed`` in
``configs/nemotron_3_super_tp8_ep64.json``).  Width 4,096 throughout,
RMSNorm eps 1e-5 with a learned scale, no bias but the conv's; ONE
mixer a layer:

- block ``l``: ``x <- x + mixer_l(RMSNorm(x))``; final RMSNorm; an
  untied head (``head/embedding``); next-token cross-entropy over the
  vocabulary slice held;
- *mamba*: ``[z | x | B | C | dt] = W_in u`` with ``x`` and ``z`` as
  ``ssm_heads`` heads of ``ssm_head_dim`` and ``B``, ``C`` as
  ``ssm_groups`` groups of ``ssm_state`` columns, a group shared by
  ``ssm_heads / ssm_groups`` consecutive heads; ``[x | B | C] =
  silu(conv([x | B | C]) + bias)``, depthwise, causal, tap j reaching
  ``taps - 1 - j`` back; ``delta = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``, per head; per head, with ``H`` [head_dim, state]
  starting at zero in every sequence: ``H_t = exp(delta_t A) H_{t-1} +
  delta_t x_t B_t^T``, ``y_t = H_t C_t + D x_t``; ``out = W_out
  RMSNorm_group(y * silu(z))``, the norm over each group's columns on
  its own.  The share held is ONE group and its heads
  (``ssm_groups`` 1); the uncut layer has 8;
- *attention*: grouped-query heads of ``head_dim``, NO rotation, scores
  ``q k^T / sqrt(head_dim)``, causal softmax, ``o_proj``;
- *moe*: ``s = sigmoid(W_r x)`` over all ``experts``; chosen = the
  ``top_k`` largest of ``s + expert_bias`` (the bias only selects);
  ``w = routed_scaling_factor * s[chosen] / (sum s[chosen] + 1e-20)``;
  ``z = W_dn x``; ``r = sum over chosen and HELD e of w_e W2_e relu(W1_e
  z)^2``; ``out = W_up r + V2 relu(V1 x)^2``: every held expert over
  every token, weighted by what the router gave it;
- balancing: after each step, per expert layer, ``expert_bias += gamma
  * sign(mean(c) - c)`` with ``c`` the pairs the step's tokens sent to
  each of ALL experts.

``prec`` selects the arithmetic of every projection and expert product
(``f32`` | ``bf16`` | ``fp8``); the router, the recurrence, the conv,
norms, softmax and loss stay float32 in all three.

Memory: 700.7 M parameters are 2.8 GB in float32.  The gradient is
taken with Adam's moments on the HOST and the update runs leaf by leaf
(``reference/granite.py``'s way); the recurrence runs a block of tokens
at a time under ``jax.checkpoint``, attention in query blocks, the loss
a block of tokens at a time, each layer and each expert rematerialised.
None of it is timed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import granite, kimi
from .lfm2 import _leaf_norms, _mm, adamw_update, mm, rms_norm

TOKEN_BLOCK = granite.TOKEN_BLOCK


def relu2_mlp(x, up, down, prec="f32"):
    """``W2 relu(W1 x)^2``, the family's two-matrix feed-forward."""
    return mm(jnp.square(jax.nn.relu(mm(x, up, prec))), down, prec)


def mamba(u, p, m, prec="f32", remat=True):
    """The Mamba-2 mixer over one sequence [N, D], ``ssm_groups`` B/C
    groups (1 where the chip holds one group and its heads)."""
    n = u.shape[0]
    h, hd, s = m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"]
    g = m.get("ssm_groups", 1)
    inner = h * hd
    z, xbc, dt = jnp.split(mm(u, p["in_proj"]["kernel"], prec),
                           [inner, 2 * inner + 2 * g * s], axis=-1)
    k = p["conv"]["kernel"]
    taps = k.shape[0]
    xp = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[j:j + n] * k[j] for j in range(taps))
                      + p["conv"]["bias"])
    x, b, c = jnp.split(xbc, [inner, inner + g * s], axis=-1)
    x = x.reshape(n, g, h // g, hd)
    delta = jax.nn.softplus(dt + p["dt_bias"]).reshape(n, g, h // g)
    a = -jnp.exp(p["A_log"]).reshape(g, h // g)
    y = jnp.stack([granite.recurrence(
        x[:, i], delta[:, i], a[i], b[:, i * s:(i + 1) * s],
        c[:, i * s:(i + 1) * s], remat) for i in range(g)], axis=1)
    y = y + p["D"].reshape(g, h // g)[:, :, None] * x
    gated = (y.reshape(n, inner) * jax.nn.silu(z)).reshape(n, g, inner // g)
    normed = rms_norm(gated, p["norm"]["scale"].reshape(g, inner // g),
                      m["norm_eps"]).reshape(n, inner)
    return mm(normed, p["out_proj"]["kernel"], prec)


def attention(x, p, m, prec="f32", remat=True):
    return granite.attention(
        x, p, dict(m, attention_multiplier=m["head_dim"] ** -0.5), prec,
        remat)


def moe(x, p, shared, bias, m, prec="f32", remat=True):
    """-> (out, pairs sent to each of ALL experts)."""
    idx, w = kimi.route(x, p, bias, m)
    z = mm(x, p["latent_down"]["kernel"], prec)
    one = functools.partial(relu2_mlp, prec=prec)
    if remat:
        one = jax.checkpoint(one)
    r = jnp.zeros_like(z)
    for e in range(p["up"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == m.get("first_expert", 0) + e, w, 0.0),
                      -1)
        r = r + w_e[:, None] * one(z, p["up"][e], p["down"][e])
    out = mm(r, p["latent_up"]["kernel"], prec) + relu2_mlp(
        x, shared["up"]["kernel"], shared["down"]["kernel"], prec)
    sent = jnp.sum(idx.reshape(-1, 1) == jnp.arange(bias.shape[0]), 0)
    return out, sent.astype(jnp.float32)


# -- the model -------------------------------------------------------------

def hidden(variables, tokens, m, *, prec="f32", remat=True):
    """tokens [N] int -> (the final-norm hidden states [N, D], {layer:
    pairs sent to each expert} for the expert layers)."""
    params, buffers = variables["params"], variables.get("batch_stats", {})
    h = params["embed"]["kernel"][tokens, 0]  # [V, 1, D]: fan-in 1

    def layer(h, p, b, op):
        y = rms_norm(h, p["norm"]["scale"], m["norm_eps"])
        if op == "mamba":
            return h + mamba(y, p["mixer"], m, prec, remat), None
        if op == "attention":
            return h + attention(y, p["attn"], m, prec, remat), None
        out, sent = moe(y, p["moe"], p["shared"], b["moe"]["expert_bias"], m,
                        prec, remat)
        return h + out, sent

    sent = {}
    for i, op in enumerate(m["layer_types"]):
        f = functools.partial(layer, op=op)
        if remat:
            f = jax.checkpoint(f)
        name = f"layer_{i}"
        h, c = f(h, params[name], buffers.get(name, {}))
        if c is not None:
            sent[name] = c
    return rms_norm(h, params["final_norm"]["scale"], m["norm_eps"]), sent


def loss_and_sent(variables, tokens, targets, m, **kw):
    """Mean next-token cross-entropy of one sequence over the slice (the
    logits a block of tokens at a time), and the expert layers' counts."""
    e = variables["params"]["head"]["embedding"]

    def block(ht):
        z = _mm(ht[0], e.T)
        return jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, ht[1][:, None], -1)[:, 0]

    h, sent = hidden(variables, tokens, m, **kw)
    c = granite._blocks(h.shape[0], TOKEN_BLOCK)
    if kw.get("remat", True):
        block = jax.checkpoint(block)
    per = lax.map(block, (h.reshape(-1, c, h.shape[1]),
                          targets.reshape(-1, c)))
    return jnp.mean(per), sent


def batch_loss(variables, tokens, targets, m, **kw):
    """Mean over a batch [B, N], one sequence at a time."""
    per = lax.map(lambda tt: loss_and_sent(variables, tt[0], tt[1], m,
                                           **kw)[0], (tokens, targets))
    return jnp.mean(per)


def follow(make_variables, batches, ref: dict, *, prec="f32", remat=True):
    """Follow ``len(batches)`` train steps from ``make_variables()``
    (called again at the end for the starting point).  The gradient of a
    batch is the mean of its sequences' gradients, one sequence at a
    time, taken while Adam's moments wait on the host; the AdamW update
    then runs leaf by leaf, and the selection bias moves by the batch's
    counts.  Returns the losses, the per-leaf norms of the first
    gradient and of the parameters' change after the last step, and the
    final bias."""
    m, opt = ref["arch"], ref["optimizer"]

    @jax.jit
    def grad_of_sequence(params, buffers, tokens, targets):
        (l, sent), g = jax.value_and_grad(
            lambda p: loss_and_sent(
                {"params": p, "batch_stats": buffers}, tokens, targets, m,
                prec=prec, remat=remat), has_aux=True)(params)
        return l, g, sent

    leaf_norms = jax.jit(_leaf_norms)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(acc, new):
        return jax.tree_util.tree_map(jnp.add, acc, new)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update_leaf(p, g, mu, nu, i):
        new, st = adamw_update(opt, {"x": p}, {"x": g},
                               {"m": {"x": mu}, "v": {"x": nu}}, i)
        return new["x"], st["m"]["x"], st["v"]["x"]

    variables = make_variables()
    buffers = variables.get("batch_stats", {})
    leaves, treedef = jax.tree_util.tree_flatten(variables["params"])
    del variables
    moments = [None] * len(leaves)          # (mu, nu) on the host
    losses, g1 = [], None
    for i, b in enumerate(batches):
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        acc = None
        for t, g in zip(jnp.asarray(b["tokens"], jnp.int32),
                        jnp.asarray(b["targets"], jnp.int32)):
            one = grad_of_sequence(params, buffers, t, g)
            acc = one if acc is None else add(acc, one)
        del params
        n = len(b["tokens"])
        l, grads, sent = acc
        if n > 1:
            l, grads = jax.tree_util.tree_map(lambda x: x / n, (l, grads))
        del acc
        losses.append(float(l))
        if i == 0:
            g1 = jax.device_get(leaf_norms(grads))
        grads = jax.tree_util.tree_leaves(grads)
        for j in range(len(leaves)):
            mu, nu = (jnp.zeros_like(leaves[j]), jnp.zeros_like(leaves[j])) \
                if moments[j] is None else map(jnp.asarray, moments[j])
            leaves[j], mu, nu = update_leaf(leaves[j], grads[j], mu, nu,
                                            jnp.float32(i))
            grads[j] = None
            moments[j] = (np.asarray(mu), np.asarray(nu))
        del grads
        buffers = kimi.balance(buffers, sent, m["bias_update_rate"])
    del moments
    dp = jax.device_get(_leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, jax.tree_util.tree_unflatten(treedef, leaves),
        make_variables()["params"])))
    return {"loss": losses, "grad_norms": g1, "dparam_norms": dp,
            "expert_bias": jax.device_get(buffers)}
