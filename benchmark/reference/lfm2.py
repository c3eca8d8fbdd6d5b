"""The plain reference of LFM2-8B-A1B (LiquidAI, ``model_type:
lfm2_moe``), one chip's share of an expert-parallel deployment.

Straightforward ``jax.numpy`` in float32 at matmul precision HIGHEST:
no kernels, no sort, no chunked loss, nothing imported from the
program.  The weights are the benchmark's own
(``harness/weights_lm.py``) and arrive as a nested dict under the
program's parameter names.  One sequence at a time, attention in query
blocks, each layer rematerialised, so that the published widths fit
beside the float32 optimizer state.

The model, as ``config.json`` and the model card give it (departures
and what the config does not give are listed under ``assumed`` in
``configs/lfm2_8b_a1b_ep4.json``):

- pre-norm residual blocks ``h += op(RMSNorm(h))``, ``h += ffn(RMSNorm(h))``;
- *conv*: ``[B, C, x] = h W_in``; ``y = C * causal_depthwise_conv1d(B * x)``
  (kernel ``conv_L_cache``, no bias); ``out = y W_out``;
- *full_attention*: grouped-query heads, RMSNorm on each head's q and
  k, rotary embedding (rotate-half form), causal softmax, no biases;
- dense ffn: SwiGLU; expert ffn: ``s = sigmoid(h W_r)``, chosen =
  top-k of ``s + expert_bias`` (the bias only selects), weights = ``s``
  at the chosen over their sum + 1e-6, times ``routed_scaling_factor``;
  ``out = sum_e w_e SwiGLU_e(h)`` over the experts HELD here
  (``first_expert`` .. ``first_expert + E_held``); what the absent
  experts would add is left out;
- final RMSNorm, output head tied to the embedding; next-token
  cross-entropy over the vocabulary slice held.

``prec`` selects the arithmetic of every projection and expert product
(``f32`` | ``bf16`` | ``fp8``, as in ``reference/ops.py``); router,
norms, softmax and loss stay float32 in all three.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import ops

HI = lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows per block of the reference's attention


# -- products ------------------------------------------------------------

def _mm(x, w):
    return jnp.matmul(x, w, precision=HI, preferred_element_type=jnp.float32)


@jax.custom_vjp
def _mm_fp8(x, w):
    return _mm(ops._fake_fp8(x), ops._fake_fp8(w))


def _mm_fp8_fwd(x, w):
    return jax.vjp(_mm, ops._fake_fp8(x), ops._fake_fp8(w))


def _mm_fp8_bwd(vjp, g):
    return vjp(ops._fake_fp8(g, jnp.float8_e5m2))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(x, w, prec="f32"):
    """``x @ w`` in the arithmetic ``prec`` names."""
    if prec == "fp8":
        return _mm_fp8(x, w)
    if prec == "bf16":
        x, w = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (x, w))
    elif prec != "f32":
        raise ValueError(f"unknown reference precision {prec!r}")
    return _mm(x, w)


# -- layers, each over ONE sequence [N, D] ---------------------------------

def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def swiglu(x, p, prec="f32"):
    g = mm(x, p["gate"]["kernel"], prec)
    u = mm(x, p["up"]["kernel"], prec)
    return mm(jax.nn.silu(g) * u, p["down"]["kernel"], prec)


def short_conv(x, p, prec="f32"):
    """The gated short convolution.  ``kernel`` is [L, D]; tap j
    multiplies the input L-1-j positions back, so the last tap is the
    current position."""
    n, d = x.shape
    b, c, v = jnp.split(mm(x, p["in_proj"]["kernel"], prec), 3, axis=-1)
    u = b * v
    k = p["kernel"]
    taps = k.shape[0]
    up = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    y = sum(up[j:j + n] * k[j] for j in range(taps))
    return mm(c * y, p["out_proj"]["kernel"], prec)


def rope(x, theta):
    """x: [N, H, G, d]; rotate-half form, positions 0..N-1."""
    n, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[:, None, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention(x, p, m, prec="f32", remat=True):
    """Causal grouped-query attention with per-head QK-norm and RoPE.
    ``Q_BLOCK`` query rows at a time, each block against the keys up to
    its own last row (so nothing above the block diagonal is computed)
    and rematerialised in the backward when ``remat``."""
    n, _ = x.shape
    hq, hkv, d = m["heads"], m["kv_heads"], m["head_dim"]
    q = mm(x, p["q_proj"]["kernel"], prec).reshape(n, hkv, hq // hkv, d)
    k = mm(x, p["k_proj"]["kernel"], prec).reshape(n, hkv, 1, d)
    v = mm(x, p["v_proj"]["kernel"], prec).reshape(n, hkv, d)
    q = rope(rms_norm(q, p["q_norm"]["scale"], m["norm_eps"]), m["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"]["scale"], m["norm_eps"]),
             m["rope_theta"])[:, :, 0]

    def block(qi, ki, vi, row0):
        s = jnp.einsum("qhgd,khd->hgqk", qi, ki, precision=HI) / np.sqrt(d)
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


def route(x, p, bias, m):
    """-> (idx [N, k] over all E experts, weights [N, k])."""
    s = jax.nn.sigmoid(_mm(x, p["router"]["kernel"]))
    _, idx = lax.top_k(s + lax.stop_gradient(bias), m["top_k"])
    w = jnp.take_along_axis(s, idx, -1)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return idx, w * m["routed_scaling_factor"]


def moe(x, p, bias, m, prec="f32", first_expert=0):
    """The experts held, ``first_expert + (0 .. E_held-1)``: every one
    over every token, weighted by what the router gave it (zero where
    it was not chosen)."""
    idx, w = route(x, p, bias, m)
    out = jnp.zeros_like(x)
    for e in range(p["gate"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == first_expert + e, w, 0.0), -1)
        pe = {k: {"kernel": p[k][e]} for k in ("gate", "up", "down")}
        out = out + w_e[:, None] * swiglu(x, pe, prec)
    return out


# -- the model -------------------------------------------------------------

def hidden(variables, tokens, m, *, prec="f32", remat=True):
    """tokens [N] int -> the final-norm hidden states [N, D]."""
    params, buffers = variables["params"], variables.get("batch_stats", {})
    h = params["embed"]["embedding"][tokens]

    def layer(h, p, b, op, ffn):
        y = rms_norm(h, p["op_norm"]["scale"], m["norm_eps"])
        h = h + (short_conv(y, p["conv"], prec) if op == "conv"
                 else attention(y, p["attn"], m, prec, remat))
        y = rms_norm(h, p["ffn_norm"]["scale"], m["norm_eps"])
        return h + (swiglu(y, p["mlp"], prec) if ffn == "dense"
                    else moe(y, p["moe"], b["moe"]["expert_bias"], m, prec,
                             m.get("first_expert", 0)))

    for i, (op, ffn) in enumerate(zip(m["layer_types"], m["ffn_types"])):
        f = functools.partial(layer, op=op, ffn=ffn)
        if remat:
            f = jax.checkpoint(f)
        h = f(h, params[f"layer_{i}"], buffers.get(f"layer_{i}", {}))
    return rms_norm(h, params["final_norm"]["scale"], m["norm_eps"])


def logits(variables, tokens, m, **kw):
    return _mm(hidden(variables, tokens, m, **kw),
               variables["params"]["embed"]["embedding"].T)


def loss(variables, tokens, targets, m, **kw):
    """Mean next-token cross-entropy of one sequence over the slice."""
    z = logits(variables, tokens, m, **kw)
    lse = jax.nn.logsumexp(z, -1)
    return jnp.mean(lse - jnp.take_along_axis(z, targets[:, None], -1)[:, 0])


def batch_loss(variables, tokens, targets, m, **kw):
    """Mean over a batch [B, N], one sequence at a time."""
    per = lax.map(lambda tt: loss(variables, tt[0], tt[1], m, **kw),
                  (tokens, targets))
    return jnp.mean(per)


def lr_at(opt, step):
    """Linear warm-up from 0 over ``warmup_steps``, then poly decay over
    the steps that remain (the program's schedule)."""
    w = opt.get("warmup_steps", 0)
    t = jnp.clip((step - w) / max(opt["total_steps"] - w, 1), 0.0, 1.0)
    return jnp.where(step < w, opt["lr"] * step / max(w, 1),
                     opt["lr"] * (1.0 - t) ** opt["poly_power"])


def adamw_update(opt, params, grads, state, step):
    """One AdamW update at 0-based ``step``: betas 0.9 / 0.999, eps 1e-8,
    bias-corrected, decoupled weight decay on tensors of rank >= 2."""
    lr, wd = lr_at(opt, step), opt.get("weight_decay", 0.0)
    b1, b2, eps = 0.9, 0.999, 1e-8
    tm = jax.tree_util.tree_map
    m = tm(lambda g, m: b1 * m + (1 - b1) * g, grads, state["m"])
    v = tm(lambda g, v: b2 * v + (1 - b2) * g * g, grads, state["v"])
    c1, c2 = 1 - b1 ** (step + 1), 1 - b2 ** (step + 1)
    new = tm(lambda p, m, v: p - lr * (
        m / c1 / (jnp.sqrt(v / c2) + eps) + (wd if p.ndim >= 2 else 0.0) * p),
        params, m, v)
    return new, {"m": m, "v": v}


def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def follow(make_variables, batches, ref: dict, *, prec="f32", remat=True):
    """Follow ``len(batches)`` train steps from ``make_variables()``
    (called again at the end for the starting point: the step donates
    its arguments).  The gradient of a batch is the mean of its
    sequences' gradients, accumulated one sequence at a time.  Returns
    the losses, the per-leaf norms of the first gradient and of the
    parameters' change after the last step."""
    m, opt = ref["arch"], ref["optimizer"]

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def step(params, buffers, opt_state, tokens, targets, i):
        def one(carry, tt):
            l, g = jax.value_and_grad(
                lambda p: loss({"params": p, "batch_stats": buffers},
                               tt[0], tt[1], m, prec=prec, remat=remat))(
                                   params)
            return jax.tree_util.tree_map(jnp.add, carry, (l, g)), None

        zero = (jnp.float32(0.0),
                jax.tree_util.tree_map(jnp.zeros_like, params))
        (l, g), _ = lax.scan(one, zero, (tokens, targets))
        n = tokens.shape[0]
        l, g = l / n, jax.tree_util.tree_map(lambda x: x / n, g)
        new, opt_state = adamw_update(opt, params, g, opt_state, i)
        return new, opt_state, l, _leaf_norms(g)

    variables = make_variables()
    params, buffers = variables["params"], variables.get("batch_stats", {})
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    opt_state = {"m": zeros(), "v": zeros()}
    del variables
    losses, g1 = [], None
    for i, b in enumerate(batches):
        params, opt_state, l, gn = step(
            params, buffers, opt_state, jnp.asarray(b["tokens"], jnp.int32),
            jnp.asarray(b["targets"], jnp.int32), jnp.float32(i))
        losses.append(float(l))
        if i == 0:
            g1 = jax.device_get(gn)
    dp = jax.device_get(_leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, params, make_variables()["params"])))
    return {"loss": losses, "grad_norms": g1, "dparam_norms": dp}
