"""The plain reference of Ouro-2.6B (ByteDance, ``model_type: ouro``;
Zhu et al., "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741), one pipeline stage: ``layers`` blocks run
``ut_steps`` times on the same weights.

Straightforward ``jax.numpy`` in float32 at matmul precision HIGHEST:
no kernel, no scan over the passes, no chunked loss with weights inside,
nothing imported from the program (the products, norm, SwiGLU, rotation
and AdamW are those of ``reference/lfm2.py``).  The loop is a Python
loop over the passes and the blocks, so each shared weight's gradient is
the sum over its uses by autodiff of the plain loop.  The weights are
the benchmark's own (``harness/weights_loop.py``) under the program's
parameter names.

The model, as the catalog row's ``config`` gives it (what it does not
give is listed under ``assumed`` in ``configs/ouro_2_6b_pp6.json``).
Width 2,048 throughout, RMSNorm eps 1e-6 with a learned scale, no bias
but the gate's:

- block (sandwich norm): ``a = x + N2(Attn(N1(x)))``, ``y = a +
  N4(SwiGLU(N3(a)))``; ``Attn``: q, k, v, o projections to 16 heads of
  128, rotate-half rotary (theta 1e6) over the whole head on q and k,
  positions from 0, causal softmax at ``128 ** -0.5``; ``SwiGLU``:
  ``down(silu(gate(u)) * up(u))``, 5,632 wide;
- loop: ``h_0 = E[token]``; for t = 1..R: ``h_t = N_f(Stack(h_{t-1}))``,
  the normed state read by the next pass, the head and the gate;
  ``g_t = w . h_t + b``, ``lambda_t = sigmoid(g_t)``;
- exit distribution per token: ``p_1 = lambda_1``, ``p_t = lambda_t
  prod_{j<t} (1 - lambda_j)``, ``p_R = prod_{j<R} (1 - lambda_j)`` (the
  last gate logit is unused);
- loss: ``mean_i [sum_t p_ti CE(Head(h_ti), target_i) - beta H(p_.i)]``
  with ``Head`` the one untied [vocab, 2,048] matrix used R times.

``prec`` selects the arithmetic of every projection, the feed-forward
and the head (``f32`` | ``bf16`` | ``fp8``); norms, rotation, softmax,
gate and loss stay float32 in all three.

Memory: 612 M parameters are 2.45 GB in float32; the gradient is taken
with Adam's moments on the HOST and the update runs leaf by leaf, as
``reference/granite.py`` does; attention runs 512 query rows at a time,
the feed-forward, the head and the loss 4,096 tokens at a time, each
visit of a block rematerialised.  None of it is timed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .granite import TOKEN_BLOCK, _blocks, by_token_blocks
from .lfm2 import (HI, Q_BLOCK, _leaf_norms, adamw_update, mm, rms_norm,
                   rope, swiglu)


def attention(x, p, m, prec="f32", remat=True):
    """Causal multi-head attention over one sequence [N, D], rotary on
    the whole head.  ``Q_BLOCK`` query rows at a time, each block against
    the keys up to its last row."""
    n, _ = x.shape
    h, d = m["heads"], m["head_dim"]
    q = rope(mm(x, p["q_proj"]["kernel"], prec).reshape(n, h, 1, d),
             m["rope_theta"])[:, :, 0]
    k = rope(mm(x, p["k_proj"]["kernel"], prec).reshape(n, h, 1, d),
             m["rope_theta"])[:, :, 0]
    v = mm(x, p["v_proj"]["kernel"], prec).reshape(n, h, d)

    def block(qi, ki, vi, row0):
        s = jnp.einsum("qhd,khd->hqk", qi, ki, precision=HI) / np.sqrt(d)
        row = row0 + jnp.arange(qi.shape[0])
        s = jnp.where(jnp.arange(ki.shape[0])[None, :] <= row[:, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vi,
                          precision=HI)

    if remat:
        block = jax.checkpoint(block)
    o = jnp.concatenate([
        block(q[r:r + Q_BLOCK], k[:r + Q_BLOCK], v[:r + Q_BLOCK], r)
        for r in range(0, n, Q_BLOCK)])
    return mm(o.reshape(n, h * d), p["o_proj"]["kernel"], prec)


def block(x, p, m, prec="f32", remat=True):
    norm = lambda t, name: rms_norm(  # noqa: E731
        t, p[name]["scale"], m["norm_eps"])
    a = x + norm(attention(norm(x, "attn_norm"), p["attn"], m, prec, remat),
                 "attn_out_norm")
    y = by_token_blocks(lambda t: swiglu(t, p["mlp"], prec),
                        norm(a, "ffn_norm"), TOKEN_BLOCK, remat)
    return a + norm(y, "ffn_out_norm")


def states(variables, tokens, m, *, prec="f32", remat=True):
    """tokens [N] int -> (the R normed states [R, N, D], the R gate
    logits [R, N])."""
    params = variables["params"]
    loop = params["loop"]
    h = params["embed"]["kernel"][tokens, 0]
    one = functools.partial(block, m=m, prec=prec, remat=remat)
    if remat:
        one = jax.checkpoint(one)
    hs, gs = [], []
    for _ in range(m["ut_steps"]):
        for i in range(m["layers"]):
            h = one(h, loop[f"layer_{i}"])
        h = rms_norm(h, loop["final_norm"]["scale"], m["norm_eps"])
        hs.append(h)
        gs.append(jnp.matmul(h, loop["exit_gate"]["kernel"][:, 0],
                             precision=HI) + loop["exit_gate"]["bias"][0])
    return jnp.stack(hs), jnp.stack(gs)


def exit_distribution(gates):
    """gates [R, N] -> p [R, N]: the plain products."""
    lam = jax.nn.sigmoid(gates[:-1])
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], 0)
    return jnp.concatenate([lam * before, stay[-1:]], 0)


def cross_entropies(hs, head, targets, prec="f32", remat=True):
    """hs [R, N, D] -> each pass's per-token cross-entropy [R, N], the
    logits ``TOKEN_BLOCK`` tokens at a time."""
    def rows(ht):
        z = mm(ht[0], head.T, prec)
        return jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, ht[1][:, None], -1)[:, 0]

    if remat:
        rows = jax.checkpoint(rows)
    c = _blocks(targets.shape[0], TOKEN_BLOCK)
    return jnp.stack([
        lax.map(rows, (h.reshape(-1, c, h.shape[1]),
                       targets.reshape(-1, c))).reshape(-1) for h in hs])


def loss_and_exit(variables, tokens, targets, m, **kw):
    """One sequence -> (loss, (p_t's means [R], mean entropy, each
    pass's mean cross-entropy [R]))."""
    hs, gates = states(variables, tokens, m, **kw)
    ce = cross_entropies(hs, variables["params"]["head"]["embedding"],
                         targets, kw.get("prec", "f32"),
                         kw.get("remat", True))
    p = exit_distribution(gates)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    total = jnp.mean(jnp.sum(p * ce, axis=0) - m["exit_beta"] * entropy)
    return total, (jnp.mean(p, 1), jnp.mean(entropy), jnp.mean(ce, 1))


def loss(variables, tokens, targets, m, **kw):
    return loss_and_exit(variables, tokens, targets, m, **kw)[0]


def batch_loss(variables, tokens, targets, m, **kw):
    """Mean over a batch [B, N], one sequence at a time."""
    per = lax.map(lambda tt: loss(variables, tt[0], tt[1], m, **kw),
                  (tokens, targets))
    return jnp.mean(per)


def follow(make_variables, batches, ref: dict, *, prec="f32", remat=True):
    """Follow ``len(batches)`` train steps from ``make_variables()``
    (called again at the end for the starting point).  The gradient of a
    batch is the mean of its sequences' gradients, one sequence at a
    time, taken while Adam's moments wait on the host; the AdamW update
    then runs leaf by leaf.  Returns the losses, the per-leaf norms of
    the first gradient and of the parameters' change after the last
    step, and each step's mean exit distribution (``exit_mass``
    [steps][R])."""
    m, opt = ref["arch"], ref["optimizer"]

    @jax.jit
    def grad_of_sequence(params, tokens, targets):
        (l, (mass, _, _)), g = jax.value_and_grad(
            lambda p: loss_and_exit({"params": p}, tokens, targets, m,
                                    prec=prec, remat=remat),
            has_aux=True)(params)
        return (l, mass), g

    leaf_norms = jax.jit(_leaf_norms)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(acc, new):
        return jax.tree_util.tree_map(jnp.add, acc, new)

    def grads_of(params, tokens, targets):
        acc = None
        for t, g in zip(tokens, targets):
            one = grad_of_sequence(params, t, g)
            acc = one if acc is None else add(acc, one)
        n = tokens.shape[0]
        (l, mass), g = jax.tree_util.tree_map(lambda x: x / n, acc) \
            if n > 1 else acc
        return l, mass, g, leaf_norms(g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update_leaf(p, g, mu, nu, i):
        new, st = adamw_update(opt, {"x": p}, {"x": g},
                               {"m": {"x": mu}, "v": {"x": nu}}, i)
        return new["x"], st["m"]["x"], st["v"]["x"]

    params = make_variables()["params"]
    leaves, treedef = jax.tree_util.tree_flatten(params)
    del params
    moments = [None] * len(leaves)          # (mu, nu) on the host
    losses, masses, g1 = [], [], None
    for i, b in enumerate(batches):
        l, mass, grads, gn = grads_of(
            jax.tree_util.tree_unflatten(treedef, leaves),
            jnp.asarray(b["tokens"], jnp.int32),
            jnp.asarray(b["targets"], jnp.int32))
        losses.append(float(l))
        masses.append([float(x) for x in np.asarray(mass)])
        if i == 0:
            g1 = jax.device_get(gn)
        grads = jax.tree_util.tree_leaves(grads)
        for j in range(len(leaves)):
            mu, nu = (jnp.zeros_like(leaves[j]), jnp.zeros_like(leaves[j])) \
                if moments[j] is None else map(jnp.asarray, moments[j])
            leaves[j], mu, nu = update_leaf(leaves[j], grads[j], mu, nu,
                                            jnp.float32(i))
            grads[j] = None
            moments[j] = (np.asarray(mu), np.asarray(nu))
        del grads
    del moments
    dp = jax.device_get(_leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, jax.tree_util.tree_unflatten(treedef, leaves),
        make_variables()["params"])))
    return {"loss": losses, "grad_norms": g1, "dparam_norms": dp,
            "exit_mass": masses}
