"""Pallas grouped matrix product — the expert layer's hot op.

The rows of ``x`` are the (token, expert) pairs routed to the experts
held here, sorted by expert and laid out so that every expert's group
starts on a row-tile boundary (``models/lfm2.py::plan_dispatch`` pads
each group to a multiple of ``tile_m`` with zero rows and gives every
expert at least one tile).  A row tile therefore belongs to exactly one
expert, named by the scalar-prefetched ``tile_expert`` map, and the
kernel is a plain tiled matmul whose weight block is chosen per tile:
no per-row masks, no expert loop, no token dropped — the buffer is
sized for the worst imbalance and the tiles past ``n_used`` (most of
them, when routing is balanced) are neither loaded nor multiplied, only
written as zeros.

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


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _gmm_kernel(te_ref, nu_ref, x_ref, w_ref, o_ref, *, transpose_w):
    i = pl.program_id(0)

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
    tn = _tile_n(b)
    last = lambda i, nu: jnp.minimum(i, nu[0] - 1)  # noqa: E731
    w_spec = (pl.BlockSpec((1, tn, a), lambda i, j, te, nu: (te[i], j, 0))
              if transpose_w else
              pl.BlockSpec((1, a, tn), lambda i, j, te, nu: (te[i], 0, j)))
    return pl.pallas_call(
        partial(_gmm_kernel, transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // tile_m, b // tn),
            in_specs=[pl.BlockSpec((tile_m, a),
                                   lambda i, j, te, nu: (last(i, nu), 0)),
                      w_spec],
            out_specs=pl.BlockSpec((tile_m, tn),
                                   lambda i, j, te, nu: (i, j))),
        out_shape=jax.ShapeDtypeStruct((m, b), x.dtype),
        compiler_params=_params(),
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
