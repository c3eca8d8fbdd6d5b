"""Pallas grouped matrix product — the expert layer's hot op.

The rows of ``x`` are the (token, expert) pairs routed to the experts
held here, sorted by expert and laid out so that every expert's group
starts on a row-tile boundary (``models/lfm2.py::plan_dispatch`` pads
each group to a multiple of ``tile_m`` with zero rows and gives every
expert at least one tile).  A row tile therefore belongs to exactly one
expert, named by the scalar-prefetched ``tile_expert`` map, and the
kernel is a plain tiled matmul whose weight block is chosen per tile:
no per-row masks, no expert loop, no token dropped — the buffer is
sized for the worst imbalance, and the tiles past ``n_used`` (most of
them, when routing is balanced) are not multiplied.

What a grid step moves.  Pallas copies a block in only when its block
index differs from the step before, so the index maps decide the
traffic (:func:`weight_block_fetches` counts it):

- a tile past ``n_used`` names the ``x`` block AND the weight block the
  last used step left resident (:func:`_weight_block`), so it fetches
  nothing; it costs its grid step and the zero rows it writes (what
  follows reads the whole buffer);
- with the column blocks inner (grid ``(row tile, column block)``)
  every used tile fetches its expert's whole matrix again wherever
  there is more than one column block, and ``x`` once;
- with the row tiles inner (grid ``(column block, row tile)``) a weight
  block stays put across an expert's consecutive row tiles and across
  the unused tail: a pass fetches ``expert runs x column blocks`` weight
  blocks, and ``x`` once a column block.

:func:`_row_inner` picks the order from the call's static shapes by the
bytes each moves; every output block is the same ``dot_general`` of the
same operands either way, so the results are the same to the last bit.
``_tgmm`` freezes both its input blocks past ``n_used`` and keeps its
output block while the expert stays.

Three calls, one custom VJP:

- ``y  = x  @ w[e]``          forward            (``_gmm``)
- ``dx = dy @ w[e].T``        the same kernel, weight block transposed
- ``dw[e] = sum_tiles x.T @ dy``  the tiles of one expert accumulate into
  the resident float32 output block (``_tgmm``); an expert's (possibly
  all-zero) first tile initialises it, so every block is written.

The MXU sees bf16 operands with float32 accumulation; ``w`` may stay
float32 outside (it is cast once per call) and ``dw`` comes back in
float32 straight from the accumulators.

What surrounds these calls (``models/lfm2.py``): rows go INTO the
buffer by XLA row gathers of the buffer's size (``dispatch`` forward,
``combine`` backward) and come OUT of it, summed over a token's K
choices, through ``pallas/moe_unpermute.py`` (``combine`` forward,
``dispatch`` backward).  Neither is fused into the kernels here: the
benchmark prices each of these calls as the plain product of its
shapes (``benchmark/harness/flops_lm.py`` counts the FLOPs and bytes,
not this file), and a gather or a combine inside them would change
what that price means.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_M = 512
_VMEM_LIMIT = 64 * 1024 * 1024


def _tile_n(n: int, cap: int = 1024) -> int:
    """The widest multiple of 128 up to ``cap`` that DIVIDES ``n`` — a
    grid of ``n // tile`` blocks must cover every column — or ``n``
    itself when it is no multiple of 128 (a tiny test width)."""
    if n % 128:
        return n
    return next(t for t in range(cap, 0, -128) if n % t == 0)


def _row_inner(r: int, tile_m: int, e: int, b: int, nj: int) -> bool:
    """Whether ``r`` row tiles of ``e`` experts against ``nj`` column
    blocks of a ``b``-wide output move fewer bytes with the row tiles
    inner: ``x`` is then read ``nj`` times and each expert's matrix
    once, where the other order reads ``x`` once and a matrix per row
    tile (both sides in units of the contracted width)."""
    return (nj - 1) * tile_m * r + e * b < r * b


def grid_order(r: int, tile_m: int, e: int, b: int):
    """(column blocks, whether the row tiles are the inner grid axis) of
    the ``_gmm`` call over ``r`` row tiles whose output is ``b`` wide."""
    nj = b // _tile_n(b)
    return nj, _row_inner(r, tile_m, e, b, nj)


def _weight_block(i, j, te, nu, nj: int, row_inner: bool):
    """The (expert, column block) that the grid step of row tile ``i``
    and column block ``j`` has resident: a tile past ``nu`` names what
    the last used step left there."""
    expert = te[jnp.minimum(i, nu - 1)]
    return expert, j if row_inner else jnp.where(i < nu, j, nj - 1)


def weight_block_fetches(tile_expert, n_used, nj: int, row_inner: bool):
    """The weight blocks one ``_gmm`` call copies in: the grid steps, in
    the order the grid walks them, whose :func:`_weight_block` differs
    from the step before (the first step fetches)."""
    te, nu = jnp.asarray(tile_expert), jnp.asarray(n_used).reshape(-1)[0]
    i, j = jnp.arange(te.shape[0]), jnp.arange(nj)
    i, j = ((jnp.tile(i, nj), jnp.repeat(j, i.size)) if row_inner
            else (jnp.repeat(i, nj), jnp.tile(j, i.size)))
    expert, col = _weight_block(i, j, te, nu, nj, row_inner)
    return 1 + jnp.sum((expert[1:] != expert[:-1]) | (col[1:] != col[:-1]))


def _gmm_kernel(te_ref, nu_ref, x_ref, w_ref, o_ref, *, transpose_w,
                row_axis):
    i = pl.program_id(row_axis)

    @pl.when(i < nu_ref[0])
    def _():
        dims = (((1,), (1,)), ((), ())) if transpose_w \
            else (((1,), (0,)), ((), ()))
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[0], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(i >= nu_ref[0])
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@jax.named_scope("dsod.kernel.grouped_matmul")
def _gmm(x, w, tile_expert, n_used, *, tile_m, transpose_w, interpret):
    """x [M, A] @ w[e] ([E, A, B], or [E, B, A] with ``transpose_w``)
    -> [M, B], the expert per row tile from ``tile_expert``."""
    m, a = x.shape
    b = w.shape[1] if transpose_w else w.shape[2]
    r = m // tile_m
    nj, row_inner = grid_order(r, tile_m, w.shape[0], b)
    tn = b // nj

    def at(f):  # an index map over (row tile, column block), either order
        if row_inner:
            return lambda j, i, te, nu: f(i, j, te, nu)
        return f

    def w_map(i, j, te, nu):
        expert, col = _weight_block(i, j, te, nu[0], nj, row_inner)
        return (expert, col, 0) if transpose_w else (expert, 0, col)

    return pl.pallas_call(
        partial(_gmm_kernel, transpose_w=transpose_w,
                row_axis=int(row_inner)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nj, r) if row_inner else (r, nj),
            in_specs=[
                pl.BlockSpec((tile_m, a), at(lambda i, j, te, nu: (
                    jnp.minimum(i, nu[0] - 1), 0))),
                pl.BlockSpec((1, tn, a) if transpose_w else (1, a, tn),
                             at(w_map))],
            out_specs=pl.BlockSpec((tile_m, tn),
                                   at(lambda i, j, te, nu: (i, j)))),
        out_shape=jax.ShapeDtypeStruct((m, b), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert, n_used, x, w)


def _tgmm_kernel(te_ref, nu_ref, x_ref, dy_ref, o_ref):
    t = pl.program_id(2)
    prev = te_ref[jnp.maximum(t, 1) - 1]
    used = t < nu_ref[0]

    @pl.when(used & ((t == 0) | (te_ref[t] != prev)))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(used)
    def _():
        o_ref[0] += lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


@jax.named_scope("dsod.kernel.grouped_matmul_dw")
def _tgmm(x, dy, tile_expert, n_used, n_experts, *, tile_m, interpret):
    """dw[e] = sum over e's row tiles of x_tile.T @ dy_tile, float32."""
    m, a = x.shape
    b = dy.shape[1]
    ta, tb = _tile_n(a, 512), _tile_n(b)
    last = lambda t, nu: jnp.minimum(t, nu[0] - 1)  # noqa: E731
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(a // ta, b // tb, m // tile_m),
            in_specs=[pl.BlockSpec((tile_m, ta),
                                   lambda i, j, t, te, nu: (last(t, nu), i)),
                      pl.BlockSpec((tile_m, tb),
                                   lambda i, j, t, te, nu: (last(t, nu), j))],
            out_specs=pl.BlockSpec((1, ta, tb),
                                   lambda i, j, t, te, nu: (te[t], i, j))),
        out_shape=jax.ShapeDtypeStruct((n_experts, a, b), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert, n_used, x, dy)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped(x, w, tile_expert, n_used, tile_m, interpret):
    return _gmm(x, w.astype(x.dtype), tile_expert, n_used, tile_m=tile_m,
                transpose_w=False, interpret=interpret)


def _grouped_fwd(x, w, tile_expert, n_used, tile_m, interpret):
    return (_grouped(x, w, tile_expert, n_used, tile_m, interpret),
            (x, w, tile_expert, n_used))


def _grouped_bwd(tile_m, interpret, res, dy):
    x, w, tile_expert, n_used = res
    dx = _gmm(dy, w.astype(x.dtype), tile_expert, n_used, tile_m=tile_m,
              transpose_w=True, interpret=interpret)
    dw = _tgmm(x, dy, tile_expert, n_used, w.shape[0], tile_m=tile_m,
               interpret=interpret)
    return dx, dw.astype(w.dtype), None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, tile_expert, n_used, *, tile_m: int = TILE_M,
                   interpret: bool | None = None):
    """``y[r] = x[r] @ w[tile_expert[r // tile_m]]`` for the rows of the
    first ``n_used[0]`` tiles, zeros after.

    x: [M, A] (M a multiple of ``tile_m``), w: [E, A, B] in any float
    dtype (cast to ``x.dtype`` for the MXU), tile_expert: [M / tile_m]
    int32 — non-decreasing, every expert present, the unused tail
    repeating the last used value —, n_used: [1] int32.  Differentiable
    in x and w.
    """
    if x.shape[0] % tile_m:
        raise ValueError(f"{x.shape[0]} rows are no multiple of {tile_m}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _grouped(x, w, tile_expert, n_used, tile_m, interpret)
