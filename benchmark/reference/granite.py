"""The plain reference of granite-4.0-h-micro (ibm-granite,
``model_type: granitemoehybrid``, dense), one pipeline stage: a whole
period of its layer pattern.

Straightforward ``jax.numpy`` in float32 at matmul precision HIGHEST:
no kernel, no chunked form, nothing imported from the program (the
products, norm, SwiGLU and AdamW are those of ``reference/lfm2.py``).
The state-space layer is **the recurrence itself**, token by token.
The weights are the benchmark's own (``harness/weights_ssm.py``) under
the program's parameter names.

The model, as the catalog row's ``config`` gives it (what it does not
give is listed under ``assumed`` in
``configs/granite_4_0_h_micro_pp4.json``).  Width 2,048 throughout,
RMSNorm eps 1e-5 with a learned scale, no bias but the conv's:

- input ``h = embedding_multiplier * E[token]``; output ``logits =
  (RMSNorm(h) E^T) / logits_scaling`` (tied), next-token cross-entropy
  over the vocabulary slice held;
- block ``l``: ``h += residual_multiplier * Mix_l(RMSNorm(h))``; ``h +=
  residual_multiplier * W_out(silu(a) * b)``, ``[a | b] = W_in
  RMSNorm(h)`` (``gate`` | ``up``);
- ``Mix_l`` = *attention*: grouped-query heads, NO rotation, no QK-norm,
  scores ``q k^T * attention_multiplier``, causal softmax, ``o_proj``;
- ``Mix_l`` = *mamba*: ``[z | xBC | dt] = W_in u``; ``xBC = silu(conv(xBC)
  + bias)``, depthwise, causal, tap j reaching ``taps - 1 - j`` back;
  ``[x | B | C] = xBC``, x as heads of ``ssm_head_dim``; ``delta =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``, both per head; per head,
  with ``H`` [head_dim, state] starting at zero in every sequence:
  ``H_t = exp(delta_t A) H_{t-1} + delta_t x_t B_t^T``, ``y_t = H_t C_t +
  D x_t``; ``out = W_out RMSNorm(y * silu(z))``, the norm over all the
  columns at once.  No state reset at a document join.

``prec`` selects the arithmetic of every projection (``f32`` | ``bf16``
| ``fp8``); the recurrence, the conv, norms, softmax and loss stay
float32 in all three.

Memory: 772 M parameters are 3.1 GB in float32, so parameters,
gradients and Adam's two moments alone are 12.4 GB of the chip's 16.
The gradient is taken with the moments on the HOST, and the update runs
leaf by leaf; the feed-forward and the loss run a block of tokens at a
time, the recurrence a block of tokens at a time under
``jax.checkpoint`` (16,384 states of 2 MiB are never held), each layer
rematerialised.  None of it is timed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .lfm2 import (HI, Q_BLOCK, _leaf_norms, _mm, adamw_update, mm, rms_norm,
                   swiglu)

TOKEN_BLOCK = 4096   # feed-forward and loss: tokens at a time
SCAN_BLOCK = 128     # the recurrence: tokens per rematerialised block


def _blocks(n: int, block: int) -> int:
    """The largest divisor of ``n`` not above ``block``."""
    return next(c for c in range(min(block, n), 0, -1) if n % c == 0)


def by_token_blocks(f, x, block, remat):
    """``f`` over [N, ...] a block of tokens at a time (memory alone)."""
    n = x.shape[0]
    c = _blocks(n, block)
    if remat:
        f = jax.checkpoint(f)
    out = lax.map(f, x.reshape((n // c, c) + x.shape[1:]))
    return out.reshape((n,) + out.shape[2:])


def attention(x, p, m, prec="f32", remat=True):
    """Causal grouped-query attention, position-free.  ``Q_BLOCK`` query
    rows at a time, each block against the keys up to its last row."""
    n, _ = x.shape
    hq, hkv, d = m["heads"], m["kv_heads"], m["head_dim"]
    q = mm(x, p["q_proj"]["kernel"], prec).reshape(n, hkv, hq // hkv, d)
    k = mm(x, p["k_proj"]["kernel"], prec).reshape(n, hkv, d)
    v = mm(x, p["v_proj"]["kernel"], prec).reshape(n, hkv, d)

    def block(qi, ki, vi, row0):
        s = jnp.einsum("qhgd,khd->hgqk", qi, ki, precision=HI) \
            * m["attention_multiplier"]
        row = row0 + jnp.arange(qi.shape[0])
        s = jnp.where(jnp.arange(ki.shape[0])[None, :] <= row[:, None], s,
                      -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), vi,
                          precision=HI)

    if remat:
        block = jax.checkpoint(block)
    o = jnp.concatenate([
        block(q[r:r + Q_BLOCK], k[:r + Q_BLOCK], v[:r + Q_BLOCK], r)
        for r in range(0, n, Q_BLOCK)])
    return mm(o.reshape(n, hq * d), p["o_proj"]["kernel"], prec)


def recurrence(x, delta, a, b, c, remat=True):
    """``y_t = H_t C_t`` of ``H_t = exp(delta_t A) H_{t-1} + delta_t x_t
    B_t^T``, token by token.  x: [N, H, P]; delta: [N, H]; a: [H]; b, c:
    [N, S].  No product: multiply-adds on the state, in float32."""
    n, h, p = x.shape

    def token(state, t):
        xt, dt, bt, ct = t
        state = jnp.exp(dt * a)[:, None, None] * state \
            + (dt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return state, jnp.sum(state * ct[None, None, :], axis=-1)

    def block(state, ts):
        return lax.scan(token, state, ts)

    if remat:
        block = jax.checkpoint(block)
    blk = _blocks(n, SCAN_BLOCK)
    cut = lambda t: t.reshape((n // blk, blk) + t.shape[1:])  # noqa: E731
    _, y = lax.scan(block, jnp.zeros((h, p, b.shape[1]), jnp.float32),
                    (cut(x), cut(delta), cut(b), cut(c)))
    return y.reshape(n, h, p)


def mamba(u, p, m, prec="f32", remat=True):
    """The Mamba-2 mixer over one sequence [N, D]."""
    n = u.shape[0]
    h, hd, s = m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"]
    inner = h * hd
    z, xbc, dt = jnp.split(mm(u, p["in_proj"]["kernel"], prec),
                           [inner, 2 * inner + 2 * s], axis=-1)
    k = p["conv"]["kernel"]
    taps = k.shape[0]
    xp = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[j:j + n] * k[j] for j in range(taps))
                      + p["conv"]["bias"])
    x, b, c = jnp.split(xbc, [inner, inner + s], axis=-1)
    x = x.reshape(n, h, hd)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, delta, -jnp.exp(p["A_log"]), b, c, remat)
    y = (y + p["D"][:, None] * x).reshape(n, inner)
    g = rms_norm(y * jax.nn.silu(z), p["norm"]["scale"], m["norm_eps"])
    return mm(g, p["out_proj"]["kernel"], prec)


def hidden(variables, tokens, m, *, prec="f32", remat=True):
    """tokens [N] int -> the final-norm hidden states [N, D], already
    divided by ``logits_scaling``."""
    params = variables["params"]
    h = m["embedding_multiplier"] * params["embed"]["embedding"][tokens]
    res = m["residual_multiplier"]

    def layer(h, p, op):
        y = rms_norm(h, p["op_norm"]["scale"], m["norm_eps"])
        h = h + res * (mamba(y, p["mixer"], m, prec, remat) if op == "mamba"
                       else attention(y, p["attn"], m, prec, remat))
        y = rms_norm(h, p["ffn_norm"]["scale"], m["norm_eps"])
        return h + res * by_token_blocks(
            lambda t: swiglu(t, p["mlp"], prec), y, TOKEN_BLOCK, remat)

    for i, op in enumerate(m["layer_types"]):
        f = functools.partial(layer, op=op)
        if remat:
            f = jax.checkpoint(f)
        h = f(h, params[f"layer_{i}"])
    return rms_norm(h, params["final_norm"]["scale"], m["norm_eps"]) \
        / m["logits_scaling"]


def loss(variables, tokens, targets, m, **kw):
    """Mean next-token cross-entropy of one sequence over the slice, the
    logits a block of tokens at a time."""
    e = variables["params"]["embed"]["embedding"]

    def block(ht):
        z = _mm(ht[0], e.T)
        return jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, ht[1][:, None], -1)[:, 0]

    h = hidden(variables, tokens, m, **kw)
    c = _blocks(h.shape[0], TOKEN_BLOCK)
    if kw.get("remat", True):
        block = jax.checkpoint(block)
    per = lax.map(block, (h.reshape(-1, c, h.shape[1]),
                          targets.reshape(-1, c)))
    return jnp.mean(per)


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
    step."""
    m, opt = ref["arch"], ref["optimizer"]

    @jax.jit
    def grad_of_sequence(params, tokens, targets):
        return jax.value_and_grad(
            lambda p: loss({"params": p}, tokens, targets, m, prec=prec,
                           remat=remat))(params)

    leaf_norms = jax.jit(_leaf_norms)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(acc, new):
        return jax.tree_util.tree_map(jnp.add, acc, new)

    def grads_of(params, tokens, targets):
        """(mean loss, mean gradient, its per-leaf norms) of a batch, one
        sequence on the device at a time."""
        acc = None
        for t, g in zip(tokens, targets):
            one = grad_of_sequence(params, t, g)
            acc = one if acc is None else add(acc, one)
        n = tokens.shape[0]
        l, g = jax.tree_util.tree_map(lambda x: x / n, acc) if n > 1 else acc
        return l, g, leaf_norms(g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update_leaf(p, g, mu, nu, i):
        new, st = adamw_update(opt, {"x": p}, {"x": g},
                               {"m": {"x": mu}, "v": {"x": nu}}, i)
        return new["x"], st["m"]["x"], st["v"]["x"]

    params = make_variables()["params"]
    leaves, treedef = jax.tree_util.tree_flatten(params)
    del params
    moments = [None] * len(leaves)          # (mu, nu) on the host
    losses, g1 = [], None
    for i, b in enumerate(batches):
        l, grads, gn = grads_of(
            jax.tree_util.tree_unflatten(treedef, leaves),
            jnp.asarray(b["tokens"], jnp.int32),
            jnp.asarray(b["targets"], jnp.int32))
        losses.append(float(l))
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
    return {"loss": losses, "grad_norms": g1, "dparam_norms": dp}
