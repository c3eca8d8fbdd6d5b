"""The plain reference of Phi-4-mini-flash-reasoning (microsoft,
``model_type: phi4flash``; SambaY, arXiv:2507.06607, with the
differential attention of arXiv:2410.05258), one pipeline stage: the
stretch on which the self-decoder hands over to the cross-decoder.

Straightforward ``jax.numpy`` in float32 at matmul precision HIGHEST:
no kernel, no chunked form, nothing imported from the program (the
products, SwiGLU and AdamW are those of ``reference/lfm2.py``, the
token blocks those of ``reference/granite.py``).  The state-space layer
is **the recurrence itself**, token by token; the two softmax maps of a
differential pair are two softmaxes.  The weights are the benchmark's
own (``harness/weights_phi4flash.py``) under the program's parameter
names.

The model, as the catalog row's ``config`` gives it (what it does not
give is listed under ``assumed`` in
``configs/phi4_mini_flash_pp5.json``).  Width 2,560 throughout,
LayerNorm (scale and bias, eps 1e-5), no position anywhere:

- input ``h = E[token]``; output ``logits = LN_f(h) E^T`` (tied),
  next-token cross-entropy over the vocabulary slice held;
- layer ``l``: ``h += Mix_l(LN_1(h))``; ``h += W_down(silu(W_gate u) *
  (W_up u))``, ``u = LN_2(h)``;
- ``Mix_l`` = *mamba*: ``[x | z] = u W_in``; ``x = silu(conv(x) +
  bias)``, depthwise, causal, tap j reaching ``taps - 1 - j`` back; ``[r
  | B | C] = x W_x``; ``delta = softplus(r W_dt + dt_bias)``; ``A =
  -exp(A_log)`` [C, N]; per channel c and state n, with ``H`` starting
  at zero in every sequence: ``H_t[c, n] = exp(delta_t[c] A[c, n])
  H_{t-1}[c, n] + delta_t[c] x_t[c] B_t[n]``, ``y_t[c] = sum_n H_t[c, n]
  C_t[n] + D[c] x_t[c]``; ``out = (y * silu(z)) W_out``.  The layer
  hands on ``m = y``.  No state reset at a document join;
- ``Mix_l`` = *window* | *full*: ``[q | k | v] = u W_qkv + b``; heads
  pair by parity (``q1``, ``k1`` the even heads, ``q2``, ``k2`` the odd;
  a pair's value its two value heads side by side); query pair i reads
  key/value pair ``i // (heads / kv_heads)``; ``A1 = softmax(q1 k1^T /
  sqrt(head_dim) + mask) V``, ``A2`` likewise of ``q2 k2^T``; ``lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 -
  0.6 exp(-0.3 l)`` at the PUBLISHED index ``l``; ``o = (1 -
  lambda_init) RMSNorm(A1 - lambda A2)`` with a learned scale; the pairs
  side by side through ``W_o + b_o``.  Mask: causal; a *window* layer's
  query i also sees key j only if ``i - window < j``.  A *full* layer
  hands on ``k1``, ``k2`` and ``V``;
- ``Mix_l`` = *gmu*: ``(silu(u W_1) * m) W_2``;
- ``Mix_l`` = *cross*: ``q = u W_q + b`` alone, differential attention
  with lambdas, sub-norm and ``W_o`` of its own against the kept ``k1``,
  ``k2``, ``V``, causal.

Departures from the published code, none of which changes a result: the
published attention calls its kernel four times on 64-wide value halves
and concatenates; here a pair's 128-wide value is one operand.  The
published model applies dropout with probability 0.

``prec`` selects the arithmetic of every projection (``f32`` | ``bf16``
| ``fp8``); the recurrence, the conv, norms, softmax, lambda and loss
stay float32 in all three.

Attention runs one sequence at a time in ``Q_BLOCK``-row query blocks,
each against the keys its mask admits alone (up to the block's last row;
in a window layer from ``window - 1`` rows before its first), so that
the jaxpr's ``dot_general``s count what a step needs.

Memory: 697 M parameters are 2.8 GB in float32.  The gradient is taken
with Adam's moments on the HOST and the update runs leaf by leaf
(``reference/granite.py``'s way); the feed-forward and the loss run a
block of tokens at a time, the recurrence a block of tokens at a time
under ``jax.checkpoint``, each layer rematerialised.  None of it is
timed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .granite import SCAN_BLOCK, TOKEN_BLOCK, _blocks, by_token_blocks
from .lfm2 import (HI, Q_BLOCK, _leaf_norms, _mm, adamw_update, mm, rms_norm,
                   swiglu)


def layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def lambda_init(depth: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def recurrence(x, delta, a, b, c, remat=True):
    """``sum_n H_t[c, n] C_t[n]`` of ``H_t = exp(delta_t A) H_{t-1} +
    delta_t x_t B_t``, token by token.  x, delta: [N, C]; a: [C, S]; b,
    c: [N, S].  No product: multiply-adds on the state, in float32."""
    n, ch = x.shape

    def token(state, t):
        xt, dt, bt, ct = t
        state = jnp.exp(dt[:, None] * a) * state \
            + (dt * xt)[:, None] * bt[None, :]
        return state, jnp.sum(state * ct[None, :], axis=-1)

    def block(state, ts):
        return lax.scan(token, state, ts)

    if remat:
        block = jax.checkpoint(block)
    blk = _blocks(n, SCAN_BLOCK)
    cut = lambda t: t.reshape((n // blk, blk) + t.shape[1:])  # noqa: E731
    _, y = lax.scan(block, jnp.zeros((ch, a.shape[1]), jnp.float32),
                    (cut(x), cut(delta), cut(b), cut(c)))
    return y.reshape(n, ch)


def mamba(u, p, m, prec="f32", remat=True):
    """The Mamba-1 mixer over one sequence [N, D] -> (out, m = y)."""
    n = u.shape[0]
    r, s = m["ssm_dt_rank"], m["ssm_state"]
    x, z = jnp.split(mm(u, p["in_proj"]["kernel"], prec), 2, axis=-1)
    k = p["conv"]["kernel"]
    taps = k.shape[0]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(xp[j:j + n] * k[j] for j in range(taps))
                    + p["conv"]["bias"])
    step, b, c = jnp.split(mm(x, p["x_proj"]["kernel"], prec), [r, r + s],
                           axis=-1)
    delta = jax.nn.softplus(mm(step, p["dt_proj"], prec) + p["dt_bias"])
    y = recurrence(x, delta, -jnp.exp(p["A_log"]), b, c, remat) + p["D"] * x
    return mm(y * jax.nn.silu(z), p["out_proj"]["kernel"], prec), y


def softmax_values(q, k, v, window, remat=True):
    """``softmax(q k^T / sqrt(d) + mask) v`` for q [N, Hkv, G, d], k [N,
    Hkv, d], v [N, Hkv, dv], causal, a query seeing its last ``window``
    keys alone (0: all).  ``Q_BLOCK`` query rows at a time, each block
    against the keys its mask admits."""
    n, d = q.shape[0], q.shape[-1]

    def block(qi, ki, vi, row0, col0):
        s = jnp.einsum("qhgd,khd->hgqk", qi, ki, precision=HI) / np.sqrt(d)
        row = (row0 + jnp.arange(qi.shape[0]))[:, None]
        col = (col0 + jnp.arange(ki.shape[0]))[None, :]
        seen = col <= row
        if window:
            seen &= row - col < window
        return jnp.einsum("hgqk,khd->qhgd",
                          jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1),
                          vi, precision=HI)

    if remat:
        block = jax.checkpoint(block, static_argnums=(3, 4))
    out = []
    for r in range(0, n, Q_BLOCK):
        lo = max(0, r - (window - 1)) if window else 0
        out.append(block(q[r:r + Q_BLOCK], k[lo:r + Q_BLOCK],
                         v[lo:r + Q_BLOCK], r, lo))
    return jnp.concatenate(out)


def diff_attention(u, p, m, depth, window=0, kept=None, prec="f32",
                   remat=True):
    """Differential attention over one sequence [N, D] -> (out, (k1, k2,
    V)); ``kept`` = an earlier layer's (k1, k2, V) makes it
    cross-attention."""
    n = u.shape[0]
    hq, hkv, d = m["heads"], m["kv_heads"], m["head_dim"]
    if kept is None:
        w, bias = p["qkv_proj"]["kernel"], p["qkv_proj"]["bias"]
        q, k, v = jnp.split(mm(u, w, prec) + bias,
                            [hq * d, (hq + hkv) * d], axis=-1)
        k = k.reshape(n, hkv // 2, 2, d)
        kept = (k[:, :, 0], k[:, :, 1], v.reshape(n, hkv // 2, 2 * d))
    else:
        q = mm(u, p["q_proj"]["kernel"], prec) + p["q_proj"]["bias"]
    k1, k2, v = kept
    q = q.reshape(n, hkv // 2, hq // hkv, 2, d)   # pair i reads i // group
    a1 = softmax_values(q[:, :, :, 0], k1, v, window, remat)
    a2 = softmax_values(q[:, :, :, 1], k2, v, window, remat)
    lam0 = lambda_init(depth)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0
    o = (1.0 - lam0) * rms_norm(a1 - lam * a2, p["subln"]["scale"],
                                m["norm_eps"])
    return mm(o.reshape(n, hq * d), p["o_proj"]["kernel"], prec) \
        + p["o_proj"]["bias"], kept


def gmu(u, p, memory, prec="f32"):
    return mm(jax.nn.silu(mm(u, p["in_proj"]["kernel"], prec)) * memory,
              p["out_proj"]["kernel"], prec)


def hidden(variables, tokens, m, *, prec="f32", remat=True):
    """tokens [N] int -> the final-norm hidden states [N, D]."""
    params = variables["params"]
    h = params["embed"]["embedding"][tokens]

    def layer(h, p, reads, op, depth):
        u = layer_norm(h, p["op_norm"], m["norm_eps"])
        kept = None
        if op == "mamba":
            out, kept = mamba(u, p["mixer"], m, prec, remat)
        elif op == "gmu":
            out = gmu(u, p["gmu"], reads, prec)
        else:
            out, kv = diff_attention(
                u, p["attn"], m, depth, m["window"] if op == "window" else 0,
                reads if op == "cross" else None, prec, remat)
            kept = kv if op == "full" else None
        h = h + out
        u = layer_norm(h, p["ffn_norm"], m["norm_eps"])
        return h + by_token_blocks(lambda t: swiglu(t, p["mlp"], prec), u,
                                   TOKEN_BLOCK, remat), kept

    memory = keys_values = None
    for i, op in enumerate(m["layer_types"]):
        f = functools.partial(layer, op=op, depth=m["first_layer"] + i)
        if remat:
            f = jax.checkpoint(f)
        h, kept = f(h, params[f"layer_{i}"],
                    {"gmu": memory, "cross": keys_values}.get(op))
        if op == "mamba":
            memory = kept
        elif op == "full":
            keys_values = kept
    return layer_norm(h, params["final_norm"], m["norm_eps"])


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
    (called again at the end for the starting point), as
    ``reference/granite.py`` does: the gradient of a batch is the mean of
    its sequences' gradients, one sequence at a time, taken while Adam's
    moments wait on the host; the AdamW update then runs leaf by leaf.
    Returns the losses, the per-leaf norms of the first gradient and of
    the parameters' change after the last step."""
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
