"""LFM2-MoE token model (LiquidAI ``lfm2_moe``): gated short-convolution
and causal grouped-query attention layers over bias-routed sparse
experts — one chip's share of an expert-parallel deployment.

The zoo's first token model.  ``kind = "tokens"`` is what
``parallel/engine.py`` and ``train/loop.py`` route on: the batch is
``tokens`` / ``targets``, the call is ``model.apply(variables, tokens,
train=...) -> (hidden [B, N, D] after the final norm, counters)``, and
the loss is ``losses/token_ce.py`` over the tied embedding.

Pre-norm residual blocks, ``h += op(RMSNorm(h))``, ``h += ffn(RMSNorm(h))``:

- *conv*: ``[B, C, x] = h W_in``; ``y = C * causal_depthwise_conv1d(B * x)``
  (kernel ``conv_kernel``, no bias); ``out = y W_out``;
- *attention*: grouped-query heads, RMSNorm on each head's q and k,
  rotary embedding, causal softmax through the Pallas flash kernel
  (``pallas/flash_attention.py::flash_attention_causal``);
- dense ffn: SwiGLU; expert ffn (:class:`ExpertLayer`): the router
  scores ALL ``experts``, picks ``top_k`` by ``sigmoid + expert_bias``
  (the bias only selects and is a buffer, not a parameter), and this
  chip computes the part of the sum that ITS experts give —
  ``first_expert .. first_expert + experts_held``.  What the absent
  experts would add is left out; on one chip the layer runs without its
  exchange.

Compute is ``dtype`` (bf16) with float32 parameters; the router, every
norm's statistics, the rotary angles and the softmax are float32.
When ``remat`` is on each block's backward recomputes the block from its
input, EXCEPT the values named in :data:`REMAT_SAVES`, which are kept
from the forward: the flash kernel's residuals (q, k, v, output, lse)
and the routing plan (PERF.md section 6, PR 31: cheap to hold, costly
to make twice).

Device scopes (PERF.md section 3): the whole stack is ``dsod.encoder``;
inside it ``dsod.shortconv``, ``dsod.attn``, ``dsod.densemlp``,
``dsod.moe.route`` (router, top-k, sort, gather into expert order),
``dsod.moe.experts`` (the grouped products), ``dsod.moe.combine``; the
final norm is ``dsod.heads``.
"""

from __future__ import annotations

import collections
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..pallas.flash_attention import (CAUSAL_RESIDUAL_NAMES, band_back,
                                      causal_blocks, causal_pairs,
                                      flash_attention_causal)
from ..pallas.grouped_matmul import (TILE_M, grid_order, grouped_matmul,
                                     weight_block_fetches)
from ..pallas.moe_unpermute import moe_unpermute, unpermute_steps


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + self.eps)
        return (y * scale).astype(self.dtype)


def _dense(features, name, dtype, param_dtype):
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=param_dtype, name=name,
                    kernel_init=nn.initializers.lecun_normal())


class SwiGLU(nn.Module):
    width: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        g = _dense(self.width, "gate", self.dtype, self.param_dtype)(x)
        u = _dense(self.width, "up", self.dtype, self.param_dtype)(x)
        return _dense(x.shape[-1], "down", self.dtype, self.param_dtype)(
            nn.silu(g) * u)


class ShortConv(nn.Module):
    """The gated short convolution.  ``conv/kernel`` is [L, D]; tap j
    multiplies the input L-1-j positions back."""
    kernel: int = 3
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        bcx = _dense(3 * d, "in_proj", self.dtype, self.param_dtype)(x)
        b, c, v = (t.astype(jnp.float32) for t in jnp.split(bcx, 3, -1))
        k = self.param("kernel", nn.initializers.lecun_normal(),
                       (self.kernel, d), self.param_dtype)
        u = b * v
        n = u.shape[1]
        up = jnp.pad(u, ((0, 0), (self.kernel - 1, 0), (0, 0)))
        y = sum(up[:, j:j + n] * k[j] for j in range(self.kernel))
        return _dense(d, "out_proj", self.dtype, self.param_dtype)(
            (c * y).astype(self.dtype))


def rope(x, theta: float):
    """x: [B, N, H, d] float32; rotate-half form, positions 0..N-1."""
    n, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


class Attention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float = 1e6
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, n, d = x.shape
        hq, hkv, hd = self.heads, self.kv_heads, self.head_dim

        def heads(name, h):
            return _dense(h * hd, name, self.dtype, self.param_dtype)(
                x).reshape(b, n, h, hd)

        def normed(t, name):  # per-head RMSNorm, then the rotation
            t = RMSNorm(self.eps, jnp.float32, name=name)(t)
            return rope(t, self.rope_theta).astype(
                self.dtype).transpose(0, 2, 1, 3)

        q = normed(heads("q_proj", hq), "q_norm")
        k = normed(heads("k_proj", hkv), "k_norm")
        v = heads("v_proj", hkv).transpose(0, 2, 1, 3)
        o = flash_attention_causal(q, k, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, n, hq * hd)
        return _dense(d, "o_proj", self.dtype, self.param_dtype)(o)


# -- the expert layer --------------------------------------------------------

def worst_case_tiles(pairs: int, experts_held: int, tile_m: int) -> int:
    """Row tiles that hold every pair whatever the imbalance: all
    ``pairs`` may be held, and each expert's group is padded to whole
    tiles, at least one."""
    return -(-pairs // tile_m) + experts_held


def held_key(idx, first_expert: int, experts_held: int):
    """Per (token, choice) pair, flat: the chosen expert's index among
    the held ones, ``experts_held`` where it is not held."""
    local = idx.reshape(-1) - first_expert
    return jnp.where((local >= 0) & (local < experts_held), local,
                     experts_held).astype(jnp.int32)


def tiles_needed(idx, first_expert: int, experts_held: int, tile_m: int):
    """Row tiles the held experts' groups take, each at least one."""
    counts = jnp.sum(held_key(idx, first_expert, experts_held)[None, :]
                     == jnp.arange(experts_held)[:, None], axis=1)
    return jnp.sum(jnp.maximum(-(-counts // tile_m), 1))


def plan_dispatch(idx, first_expert: int, experts_held: int, tile_m: int,
                  n_tiles: int):
    """Where each (token, choice) pair routed to a held expert goes in
    the expert-ordered buffer, from a SORT of the pairs by expert.

    idx: [T, K] int32, the chosen experts over all of them.  The buffer
    has ``n_tiles * tile_m`` rows, each expert's group padded to whole
    row tiles, at least one: :func:`worst_case_tiles` holds every held
    pair whatever the imbalance; a caller that passes fewer checks
    :func:`tiles_needed` first.  Returns

    - ``row_of_pair`` [T, K]: the pair's row, ``rows`` where the expert
      is not held (reads back as zero);
    - ``pair_of_row`` [rows]: the flat pair index, -1 on padding rows;
    - ``tile_expert`` [n_tiles], ``n_used`` [1]: the grouped product's
      per-tile expert map and tile count;
    - ``counts`` [experts_held]: pairs per held expert;
    - ``dropped``: held pairs that found no row (0 by construction;
      counted, not assumed).
    """
    t, k = idx.shape
    rows = n_tiles * tile_m
    key = held_key(idx, first_expert, experts_held)
    held = key < experts_held
    held_ids = jnp.arange(experts_held, dtype=jnp.int32)

    def lookup(table, e):  # table[e] over a handful of experts, no gather
        return jnp.sum(jnp.where(e[..., None] == held_ids, table, 0), -1)

    # The sort: pairs by expert, ties in pair order.  ``order[i]`` is the
    # pair in sorted slot i; a pair's slot inside its expert's group is
    # its rank, a running count of the same key.
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    same = (key[None, :] == held_ids[:, None]).astype(jnp.int32)
    running = jnp.cumsum(same, axis=1)                # [E_held, T*K]
    counts = running[:, -1]
    rank = jnp.sum(running * same, axis=0) - 1
    tiles = jnp.maximum(-(-counts // tile_m), 1)
    tile_end = jnp.cumsum(tiles)
    row0 = (tile_end - tiles) * tile_m                # first row per expert
    slot0 = jnp.cumsum(counts) - counts               # first sorted slot
    row_of_pair = jnp.where(held, lookup(row0, key) + rank, rows)
    # Row r of expert e holds sorted slot slot0[e] + (r - row0[e]), if
    # the group is that long: a gather of ``order``, not a scatter.
    tile_expert = jnp.minimum(
        jnp.sum(jnp.arange(n_tiles)[:, None] >= tile_end[None, :], axis=1),
        experts_held - 1).astype(jnp.int32)
    e_row = jnp.repeat(tile_expert, tile_m)
    within = jnp.arange(rows, dtype=jnp.int32) - lookup(row0, e_row)
    filled = within < lookup(counts, e_row)
    slot = jnp.where(filled, lookup(slot0, e_row) + within, 0)
    pair_of_row = jnp.where(filled, order[slot], -1)
    dropped = jnp.sum(held & (row_of_pair >= rows))
    return (row_of_pair.reshape(t, k), pair_of_row, tile_expert,
            tile_end[-1:].astype(jnp.int32), counts, dropped)


def _rows(x, index):
    """x[index] with zeros where ``index`` is out of range."""
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


def _token_of_row(pair_of_row, top_k: int, tokens: int):
    """The token each buffer row holds, ``tokens`` (out of range: reads
    back as zero) on padding rows."""
    return jnp.where(pair_of_row >= 0, pair_of_row // top_k, tokens)


# Which pass is which.  INTO the buffer a row has one source, so both
# ``dispatch`` forward and ``combine`` backward are XLA row gathers of
# the buffer's size (``_rows(., token of row)``).  OUT of the buffer a
# token sums its K rows, of which this chip holds about a quarter:
# ``combine`` forward and ``dispatch`` backward are the Pallas kernel
# (``pallas/moe_unpermute.py``), which reads the held rows alone.
# Nothing in either direction is sized [T, K, D].

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def dispatch(x, row_of_pair, pair_of_row, steps, tile_m):
    """[T, D] -> the expert-ordered buffer [rows, D] (padding rows
    zero): an XLA gather of ``rows`` rows.  The backward sums a token's
    held rows in the un-permute kernel (a buffer row has one pair, so
    nothing is scattered)."""
    return _rows(x, _token_of_row(pair_of_row, row_of_pair.shape[1],
                                  x.shape[0]))


def _dispatch_fwd(x, row_of_pair, pair_of_row, steps, tile_m):
    return (dispatch(x, row_of_pair, pair_of_row, steps, tile_m),
            (row_of_pair, steps))


def _dispatch_bwd(tile_m, res, g):
    row_of_pair, steps = res
    dx = moe_unpermute(g, jnp.ones(row_of_pair.shape, jnp.float32),
                       row_of_pair, steps, tile_m=tile_m)
    return dx.astype(g.dtype), None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def combine(y, w, row_of_pair, pair_of_row, steps, tile_m):
    """out[t] = sum_k w[t, k] * y[row_of_pair[t, k]] in float32, by the
    un-permute kernel (a pair whose expert is not held adds nothing and
    is never read).  The backward is sized by the buffer: one XLA gather
    of ``g``'s rows gives ``dy``, and ``dw`` is a per-ROW dot product
    ``<g[token of row], y[row]>`` read back through a gather of scalars.
    """
    return moe_unpermute(y, w, row_of_pair, steps, tile_m=tile_m)


def _combine_fwd(y, w, row_of_pair, pair_of_row, steps, tile_m):
    return (combine(y, w, row_of_pair, pair_of_row, steps, tile_m),
            (y, w, row_of_pair, pair_of_row))


def _combine_bwd(tile_m, res, g):
    y, w, row_of_pair, pair_of_row = res
    gt = _rows(g, _token_of_row(pair_of_row, w.shape[1], g.shape[0]))
    w_row = _rows(w.reshape(-1), pair_of_row)      # -1 -> fill 0
    dy = (gt * w_row[:, None]).astype(y.dtype)
    dw_row = jnp.sum(gt * y.astype(jnp.float32), axis=1)
    return dy, _rows(dw_row, row_of_pair), None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


def _tile_m(pairs: int, experts: int) -> int:
    """Row-tile height: ``TILE_M``, or the largest power of two (>= 8)
    under the share of ``pairs`` that one of ``experts`` gets."""
    share = max(pairs // experts, 8)
    return min(TILE_M, 1 << (share.bit_length() - 1))


def held_experts_sum(xt, idx, w, weights, ffn, *, experts: int,
                     first_expert: int, capacity: float, whole,
                     tile_m: int = None, weigh=None):
    """``out[t] = sum over k with idx[t, k] held of w[t, k] *
    ffn_e(xt[t])`` in float32, no pair dropped: the ONE way through the
    expert-ordered buffer (plan, row gather, grouped products,
    un-permute kernel) for any expert: ``ffn(gmm, xs, *weights)`` with
    ``gmm(a, w_stacked)`` the grouped product over the buffer's rows and
    ``weights`` the held experts' stacked matrices.

    The usual buffer is ``capacity`` x the held experts' BALANCED share
    of the pairs, and everything around the grouped products (gathers,
    activations, cotangents) costs by its rows, not by the rows used.
    Of it ``whole`` x the balanced share is multiplied whatever it
    holds, empty tiles (zero rows) too, the price of static shapes: the
    step's time does not follow the routing of whatever weights it is
    given (+-0.3 % across seeds at random weights, for ~6 % of the
    step: PERF.md section 6, PR 28); ``None`` multiplies all of it.  A
    routing the usual buffer cannot hold is taken a GROUP of tokens at
    a time (the other branch of a cond), each group planned on its own
    into a buffer that holds ITS worst case: no buffer, here or in the
    backward, larger than the usual one.  Both are the caller's shape
    rule, as is ``tile_m``, the row-tile height (default: :func:`_tile_m`
    of an expert's balanced share).

    xt: [T, A]; idx: [T, K] int32 over ALL ``experts``; w: [T, K]
    float32, or the chosen scores that ``weigh`` turns into it once the
    plan is made (under ``dsod.moe.route``: where the first expert
    layer's program has always made its weights).  -> (out [T, B]
    float32, pairs per held expert, dropped, (row tiles this routing
    needs, row tiles of the usual buffer: past it the by-group path
    ran), the share of the grid steps of the usual buffer's product
    with ``weights[0]`` that fetch a weight block — ``None`` where all
    of the buffer is multiplied, a constant of the shapes).
    """
    tokens, top_k = idx.shape
    e = weights[0].shape[0]
    pairs_all = tokens * top_k
    if tile_m is None:  # 352 pairs an expert -> 256 rows
        tile_m = _tile_m(pairs_all, experts)
    # A token's choices differ, so it sends a held expert one pair at most.
    held_max = tokens * min(top_k, e)
    worst = worst_case_tiles(held_max, e, tile_m)

    def tiles(factor):  # row tiles of ``factor`` x the balanced share
        return int(-(-factor * pairs_all * e // (experts * tile_m))) + e

    usual = min(worst, tiles(capacity))
    # Row tiles multiplied whatever they hold: of the usual buffer
    # ``floor``, of any other ``none``.  (Under a ``whole`` the count of
    # used tiles meets a maximum with 0 there: a no-op the latent
    # layers' step has held since PR 43, kept with it to the byte.)
    none = None if whole is None else 0
    floor = (none if usual == worst  # a tiny size: it holds any routing
             else usual if whole is None else min(usual, tiles(whole)))

    def plan_for(idx, n_tiles):
        """-> (plan, counts, dropped): where the pairs of ``idx`` go in
        a buffer of ``n_tiles`` row tiles, and the un-permute kernel's
        step list for it."""
        (row_of_pair, pair_of_row, tile_expert, n_used, counts,
         dropped) = plan_dispatch(idx, first_expert, e, tile_m, n_tiles)
        steps = unpermute_steps(pair_of_row, top_k, idx.shape[0], tile_m, e)
        return ((row_of_pair, pair_of_row, tile_expert, n_used, steps),
                counts, dropped)

    with jax.named_scope("dsod.moe.route"):
        # The plan for the buffer that usually holds the pairs is made
        # HERE, once, above the cond, and kept for the backward with the
        # chosen experts and their scores (``REMAT_SAVES``): under 2 MiB
        # a layer at the published size, against a sort, running counts
        # and two gathers of scalars (XLA's run at ~10 ns an element)
        # made twice.
        plan, counts, dropped = jax.tree_util.tree_map(
            lambda t: checkpoint_name(t, "plan"), plan_for(idx, usual))
        if weigh is not None:
            w = weigh(w)
    fetched = None
    if whole is not None:
        nj, row_inner = grid_order(usual, tile_m, e, weights[0].shape[2])
        fetched = weight_block_fetches(
            plan[2], jnp.maximum(plan[3], floor), nj,
            row_inner) / (usual * nj)

    def through(plan, multiplied, xt, w, *weights):
        """This chip's part of the sum for the tokens of ``xt`` through
        the buffer ``plan`` lays out, at least ``multiplied`` of its
        tiles multiplied -> out [T, B] f32."""
        row_of_pair, pair_of_row, tile_expert, n_used, steps = plan
        with jax.named_scope("dsod.moe.route"):
            xs = dispatch(xt, row_of_pair, pair_of_row, steps, tile_m)
        if multiplied is not None:
            n_used = (jnp.full((1,), tile_expert.shape[0], jnp.int32)
                      if multiplied == tile_expert.shape[0]
                      else jnp.maximum(n_used, multiplied))
        with jax.named_scope("dsod.moe.experts"):
            ys = ffn(lambda a, wt: grouped_matmul(
                a, wt, tile_expert, n_used, tile_m=tile_m), xs, *weights)
        with jax.named_scope("dsod.moe.combine"):
            return combine(ys, w, row_of_pair, pair_of_row, steps, tile_m)

    def in_the_usual_buffer(plan, dropped, xt, w, idx, *weights):
        return through(plan, floor, xt, w, *weights), dropped

    def by_group(plan, dropped, xt, w, idx, *weights):
        """The fewest groups whose worst case the usual buffer holds: 4
        at the first model's published size, 72 row tiles a group
        against the usual 104."""
        del plan, dropped  # those are of the usual buffer, overflowed
        groups = next(g for g in range(1, tokens + 1) if tokens % g == 0
                      and worst_case_tiles(held_max // g, e, tile_m)
                      <= usual)
        n_tiles = worst_case_tiles(held_max // groups, e, tile_m)

        def one(group):
            xt, w, idx = group
            # A scan's body lowers to a function of its own, whose ops
            # carry the scopes from HERE down: the stage again.
            with jax.named_scope("dsod.encoder"):
                with jax.named_scope("dsod.moe.route"):
                    plan, _, dropped = plan_for(idx, n_tiles)
                return through(plan, none, xt, w, *weights), dropped

        out, dropped = lax.map(jax.checkpoint(one), tuple(
            t.reshape(groups, -1, t.shape[-1]) for t in (xt, w, idx)))
        return out.reshape(tokens, -1), jnp.sum(dropped)

    args = (plan, dropped, xt, w, idx) + tuple(weights)
    needed = tiles_needed(idx, first_expert, e, tile_m)
    if usual == worst:
        out, dropped = in_the_usual_buffer(*args)
    else:
        # Each branch keeps its INPUTS alone for the backward (the plan
        # among them) and recomputes inside it: a cond under autodiff
        # otherwise holds BOTH branches' residuals (zeros for the one
        # not taken).  So nothing inside a branch is saved by name
        # either: a name kept from one is kept from both.  And a step's
        # memory is its LARGER branch's, run or not: ONE worst-case
        # buffer for all the tokens (264 row tiles) made the whole step
        # 2.3 GiB larger in the compiler's books than the branch that
        # runs, and the compiler paid for that by recomputing (PERF.md
        # section 6, PR 31).
        out, dropped = lax.cond(
            needed <= usual, jax.checkpoint(in_the_usual_buffer),
            jax.checkpoint(by_group), *args)
    return out, counts, dropped, (needed, usual), fetched


class ExpertLayer(nn.Module):
    """Bias-routed sparse experts, the share of one chip.

    Told which experts it holds (``first_expert``, ``experts_held`` of
    ``experts``); routes over all of them; computes its own experts'
    part of ``sum_e w_e SwiGLU_e(h)`` with no pair dropped.  Returns
    ``(out, counters)``: ``pairs_here`` (pairs routed to held experts),
    ``load_max_over_mean`` (over the held experts) and ``dropped``.

    ``bias_update_rate`` > 0 turns on the family's balancing rule
    (``noaux_tc``): where the ``batch_stats`` collection is mutable (a
    train step), the layer hands back ``expert_bias + rate *
    sign(mean(c) - c)`` with ``c`` the pairs this call's tokens sent to
    each of ALL ``experts`` (every router output is computed here, so
    every count is known here), and counts ``bias_abs_max``.  At 0 the
    bias stays the buffer it was given.
    """
    experts: int
    experts_held: int
    first_expert: int
    top_k: int
    width: int
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    topk_eps: float = 1e-6   # in the chosen scores' normaliser
    bias_update_rate: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, n, d = x.shape
        e, f = self.experts_held, self.width
        xt = x.reshape(b * n, d)
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        w_gate = self.param("gate", init, (e, d, f), self.param_dtype)
        w_up = self.param("up", init, (e, d, f), self.param_dtype)
        w_down = self.param("down", init, (e, f, d), self.param_dtype)
        bias_var = self.variable("batch_stats", "expert_bias", jnp.zeros,
                                 (self.experts,), jnp.float32)
        bias = bias_var.value
        pairs_all = b * n * self.top_k
        with jax.named_scope("dsod.moe.route"):
            logits = nn.Dense(
                self.experts, use_bias=False, dtype=jnp.float32,
                param_dtype=self.param_dtype, name="router",
                precision=lax.Precision.HIGHEST)(xt.astype(jnp.float32))
            s = jax.nn.sigmoid(logits)
            _, idx = lax.top_k(s + lax.stop_gradient(bias), self.top_k)
            idx = checkpoint_name(idx.astype(jnp.int32), "plan")
            chosen = jnp.take_along_axis(s, idx, -1)

        def weigh(w):
            # Named (an op of the program, for a float) and normalised
            # once the plan is made: where this layer's step has had
            # both since PR 31, held to the byte.
            w = checkpoint_name(w, "plan")
            if self.norm_topk_prob:
                w = w / (jnp.sum(w, -1, keepdims=True) + self.topk_eps)
            return w * self.routed_scaling_factor

        # This layer's buffer rule: 1.5 x the balanced share of the
        # pairs (the worst case is four times the share), all of it
        # multiplied.
        out, counts, dropped, _, _ = held_experts_sum(
            xt, idx, chosen, (w_gate, w_up, w_down),
            lambda gmm, xs, gate, up, down: gmm(
                nn.silu(gmm(xs, gate)) * gmm(xs, up), down),
            experts=self.experts, first_expert=self.first_expert,
            capacity=1.5, whole=None, tile_m=_tile_m(pairs_all, e),
            weigh=weigh)
        pairs = jnp.sum(counts).astype(jnp.float32)
        counters = {
            "pairs_here": pairs,
            "load_max_over_mean": jnp.max(counts) * e / jnp.maximum(pairs, 1),
            "dropped": dropped.astype(jnp.float32)}
        if (self.bias_update_rate and not self.is_initializing()
                and self.is_mutable_collection("batch_stats")):
            with jax.named_scope("dsod.moe.balance"):
                sent = jnp.sum(idx.reshape(-1)[None, :] == jnp.arange(
                    self.experts)[:, None], axis=1).astype(jnp.float32)
                bias_var.value = bias + self.bias_update_rate * jnp.sign(
                    pairs_all / self.experts - sent)
                counters["bias_abs_max"] = jnp.max(jnp.abs(bias_var.value))
        return out.astype(self.dtype).reshape(b, n, d), counters


# What a rematerialised block KEEPS from its forward, by
# ``checkpoint_name``; everything else its backward recomputes.  The
# flash kernel's residuals (q, k, v as it takes them 192 MiB, out + lse
# 132 MiB at the published size; without them the kernel, the rotation,
# the casts and the transposes run twice) and the routing plan (the
# chosen experts, their scores, the usual buffer's layout and step
# list: under 2 MiB a layer; without them top-k, the sort, the running
# counts and two gathers of scalars run twice).  What else a layer
# recomputes is buffer-sized (PERF.md section 6, PR 31).
REMAT_SAVES = CAUSAL_RESIDUAL_NAMES + ("plan",)
_SAVE_NAMED = jax.checkpoint_policies.save_only_these_names(*REMAT_SAVES)


def _saves_counted(saved, save_named=_SAVE_NAMED):
    """The remat policy, counting into ``saved`` what it lets through:
    values per name and their ``bytes``.  Autodiff asks a policy once
    per value while it splits the block, so the count is the step's own
    and is empty in a trace that is not differentiated."""
    def policy(prim, *avals, **params):
        save = save_named(prim, *avals, **params)
        if save:
            saved[params["name"]] += 1
            saved["bytes"] += sum(a.size * a.dtype.itemsize for a in avals)
        return save

    return policy


class Block(nn.Module):
    op: str           # conv | attention
    ffn: str          # dense | moe
    cfg: Any          # configs.base.LMConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        y = RMSNorm(c.norm_eps, self.dtype, name="op_norm")(h)
        if self.op == "conv":
            with jax.named_scope("dsod.shortconv"):
                h = h + ShortConv(c.conv_kernel, name="conv", **kw)(y)
        else:
            with jax.named_scope("dsod.attn"):
                h = h + Attention(c.heads, c.kv_heads, c.head_dim,
                                  c.rope_theta, c.norm_eps, name="attn",
                                  **kw)(y)
        y = RMSNorm(c.norm_eps, self.dtype, name="ffn_norm")(h)
        if self.ffn == "dense":
            with jax.named_scope("dsod.densemlp"):
                return h + SwiGLU(c.dense_width, name="mlp", **kw)(y), None
        out, counters = ExpertLayer(
            c.experts, c.experts_held, c.first_expert, c.top_k,
            c.expert_width, c.norm_topk_prob, c.routed_scaling_factor,
            name="moe", **kw)(y)
        return h + out, counters


class LFM2(nn.Module):
    """``cfg`` is the frozen ``configs.base.LMConfig`` (``model.lm``):
    the published widths, the layers kept and the chip's share."""
    cfg: Any
    remat: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    kind = "tokens"  # what engine.py / loop.py route on

    @nn.compact
    def __call__(self, tokens, *, train: bool = False):
        del train  # no dropout, no batch statistics
        c = self.cfg
        saved = collections.Counter()
        block = (nn.remat(Block, policy=_saves_counted(saved))
                 if self.remat else Block)
        per_layer = []
        with jax.named_scope("dsod.encoder"):
            h = nn.Embed(c.vocab, c.hidden, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="embed")(tokens)
            for i, (op, ffn) in enumerate(zip(c.layer_types, c.ffn_types)):
                h, counters = block(op, ffn, c, self.dtype, self.param_dtype,
                                    name=f"layer_{i}")(h)
                if counters is not None:
                    per_layer.append(counters)
        log_saves("lfm2", len(c.layer_types), saved, REMAT_SAVES)
        log_flash_grid(saved, tokens.shape[1])
        with jax.named_scope("dsod.heads"):
            h = RMSNorm(c.norm_eps, self.dtype, name="final_norm")(h)
        return h, moe_counters(per_layer, tokens.size * c.top_k)


def log_saves(model: str, layers: int, saved, names,
              unit: str = "layers") -> None:
    """One line per DIFFERENTIATED trace saying what the remat policy
    kept (a trace in which the policy was never asked logs nothing).
    ``unit``: what ``layers`` counts (a looped model's are visits)."""
    if saved:
        from ..utils.logging import get_logger

        get_logger().info(
            "remat saves (%s, %d %s): %s MiB=%.1f", model, layers, unit,
            " ".join(f"{k}={saved[k]}" for k in names),
            saved["bytes"] / 2 ** 20)


def log_flash_grid(saved, seq_len: int, window: int | None = None) -> None:
    """Beside ``log_saves``, under its rule: how many grid steps a head
    the causal flash kernels take over ``seq_len`` tokens (the tile pairs
    on or under the diagonal, from the function that builds the kernels'
    tables) of the rectangle's; with ``window``, the pairs of a layer
    whose queries see their last ``window`` keys alone."""
    if saved:
        from ..utils.logging import get_logger

        blk, nb = causal_blocks(seq_len)
        if window is None or window >= seq_len:
            get_logger().info("flash grid: steps=%d of %d a head",
                              causal_pairs(nb)[0][0].size, nb * nb)
        else:
            get_logger().info(
                "flash grid (window %d): steps=%d of %d a head", window,
                causal_pairs(nb, 1, band_back(window, blk))[0][0].size,
                nb * nb)


def moe_counters(per_layer, pairs_total: int):
    """The trainer's counters from the expert layers' own:
    ``moe_pairs_here_share`` (mean over layers of held pairs / all
    pairs), ``moe_load_max_over_mean`` (worst layer),
    ``moe_dropped_pairs`` (sum) and, where the layers balance their
    router, ``moe_bias_abs_max`` (largest |expert_bias| of any layer)."""
    if not per_layer:
        return {}
    stack = {k: jnp.stack([c[k] for c in per_layer]) for k in per_layer[0]}
    out = {
        "moe_pairs_here_share": jnp.mean(stack["pairs_here"]) / pairs_total,
        "moe_load_max_over_mean": jnp.max(stack["load_max_over_mean"]),
        "moe_dropped_pairs": jnp.sum(stack["dropped"])}
    if "bias_abs_max" in stack:
        out["moe_bias_abs_max"] = jnp.max(stack["bias_abs_max"])
    return out
