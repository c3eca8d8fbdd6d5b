"""Pallas "un-permute and sum over K" — the expert layer's way back from
the expert-ordered buffer to token order without a [T, K, D] array.

``out[t] = sum_k w[t, k] * y[row_of_pair[t, k]]`` in float32, a pair
whose expert is not held (``row_of_pair == rows``) adding nothing.  Two
users in ``models/lfm2.py``: ``combine`` forward, and ``dispatch``
backward with ``w = 1``.

The buffer's rows are the held (token, choice) pairs sorted by expert,
each expert's group padded to whole ``tile_m`` row tiles
(``plan_dispatch``).  The sort is stable, so inside a group the rows
ascend by token: a tile of ``tt`` tokens owns ONE contiguous run of
rows per held expert, and the ``chunk``-row pieces of the buffer a
token tile has to read are few — about ``groups * (run / chunk + 1)``.
:func:`unpermute_steps` lists them, token tile by token tile; the
kernel's grid IS that list.  A step loads one chunk ``[chunk, D]`` of
``y`` through the ordinary block pipeline, builds the 0/1 selection
matrix ``sel[t, j] = (row_of_pair[t, k] == chunk's row j for some k)``
and adds ``wc * (sel @ chunk)`` into the token tile's resident float32
output block, ``wc[t]`` the weight of the token's pair in this chunk.
The product is exact (0/1 times a value, float32 accumulation): the MXU
only moves rows.  ``chunk`` divides ``tile_m``, so a chunk lies inside
one expert's group and a token — whose K experts differ — has at most
one row in it; that is what lets ONE weight per (token, chunk) stand
for the pair's.

Rows not held are never fetched, zero-filled, written or summed; the
time follows the held rows (plus one chunk's rounding per run), not the
buffer: a buffer sized for a worst case costs what a full one does.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import _VMEM_LIMIT, _tile_n

_TOKEN_TILE = 512
_CHUNK = 128  # the MXU's contraction depth: a shorter chunk fills it less


def _token_tile(tokens: int) -> int:
    """The largest power of two up to ``_TOKEN_TILE`` that divides
    ``tokens``."""
    return min(_TOKEN_TILE, tokens & -tokens)


def _chunk(tile_m: int) -> int:
    """Rows a step loads: ``_CHUNK``, or the whole row tile at a tiny
    test size — a chunk never spans two experts' groups."""
    return min(_CHUNK, tile_m)


def unpermute_steps(pair_of_row, top_k: int, tokens: int, tile_m: int,
                    groups: int):
    """The kernel's work list from ``plan_dispatch``'s ``pair_of_row``
    [rows] (the flat pair index of each row, -1 on padding).

    Returns ``(step_tile, step_chunk, n_steps)``: step ``s`` adds chunk
    ``step_chunk[s]`` of the buffer into token tile ``step_tile[s]``.
    Token tiles ascend, every (tile, chunk) appears at most once, every
    tile at least once (a tile with no held pair still has to write its
    zeros) and the steps past ``n_steps[0]`` repeat the last one, which
    the kernel skips.  A chunk is listed for the token tiles between
    its first and its last row's.

    The list is ``n_chunks + (groups + 1) * n_token_tiles`` long, which
    it cannot outgrow while the rows of each of the ``groups`` experts'
    groups ascend by token: along one group the chunks' tile ranges
    overlap only at their ends, at most once per token-tile edge.
    """
    rows = pair_of_row.shape[0]
    chunk, tt = _chunk(tile_m), _token_tile(tokens)
    n_chunks, n_tt = rows // chunk, tokens // tt
    size = n_chunks + (groups + 1) * n_tt
    tok = (pair_of_row // top_k).reshape(n_chunks, chunk)  # padding: -1
    first = jnp.min(jnp.where(tok >= 0, tok, tokens), axis=1) // tt
    last = jnp.max(tok, axis=1) // tt          # -1: a chunk of padding
    tile = jnp.arange(n_tt, dtype=jnp.int32)[:, None]
    hit = (first[None, :] <= tile) & (tile <= last[None, :])
    hit = hit.at[:, 0].set(hit[:, 0] | ~jnp.any(hit, axis=1))
    # Step s is the j-th listed chunk of its token tile: both by
    # compare-and-count against running sums (no scatter, no sort), the
    # tile over [size, n_tt] and the chunk over [size, n_chunks].
    count = jnp.sum(hit, axis=1, dtype=jnp.int32)
    end = jnp.cumsum(count)
    n_steps = end[-1:]
    s = jnp.minimum(jnp.arange(size, dtype=jnp.int32), n_steps - 1)[:, None]
    before = end[None, :] <= s                            # [size, n_tt]
    step_tile = jnp.sum(before, axis=1, dtype=jnp.int32)
    j = s - jnp.sum(jnp.where(before, count[None, :], 0), axis=1,
                    keepdims=True)
    listed = jnp.cumsum(hit.astype(jnp.int32), axis=1)    # [n_tt, n_chunks]
    step_chunk = jnp.sum(jnp.take(listed, step_tile, axis=0) <= j, axis=1,
                         dtype=jnp.int32)
    return step_tile, step_chunk, n_steps


def _kernel(tile_ref, chunk_ref, n_ref, rop_ref, w_ref, y_ref, o_ref, *,
            tn, precision):
    s = pl.program_id(0)
    used = s < n_ref[0]
    tt, top_k = rop_ref.shape
    chunk, d = y_ref.shape

    @pl.when(used & ((s == 0)
                     | (tile_ref[s] != tile_ref[jnp.maximum(s, 1) - 1])))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(used)
    def _():
        local = rop_ref[...] - chunk_ref[s] * chunk       # [tt, K]
        inside = (local >= 0) & (local < chunk)
        wc = jnp.sum(jnp.where(inside, w_ref[...], 0.0), axis=1,
                     keepdims=True)                        # [tt, 1]
        col = lax.broadcasted_iota(jnp.int32, (tt, chunk), 1)
        hit = local[:, 0:1] == col
        for k in range(1, top_k):
            hit = hit | (local[:, k:k + 1] == col)
        sel = jnp.where(hit, 1.0, 0.0).astype(y_ref.dtype)
        for j in range(0, d, tn):
            moved = jnp.dot(sel, y_ref[:, j:j + tn], precision=precision,
                            preferred_element_type=jnp.float32)
            o_ref[:, j:j + tn] += wc * moved


@jax.named_scope("dsod.kernel.moe_unpermute")
def moe_unpermute(y, w, row_of_pair, steps, *, tile_m: int,
                  interpret: bool | None = None):
    """``out[t] = sum_k w[t, k] * y[row_of_pair[t, k]]`` -> [T, D] f32.

    y: [rows, D], rows a multiple of ``tile_m``; w: [T, K] float32;
    row_of_pair: [T, K] int32, ``rows`` (or more) where the pair is not
    held, a token's held rows in K different experts' groups; steps:
    what :func:`unpermute_steps` gave for the same plan and ``tile_m``.
    """
    rows, d = y.shape
    t, top_k = row_of_pair.shape
    chunk, tt = _chunk(tile_m), _token_tile(t)
    if rows % chunk:
        raise ValueError(f"{rows} rows are no multiple of {chunk}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    step_tile, step_chunk, n_steps = steps
    by_tile = lambda s, tile, chunk, n: (tile[s], 0)  # noqa: E731
    return pl.pallas_call(
        partial(_kernel, tn=_tile_n(d, 512),
                # float32 rows (a test, never the chip's cell) must not
                # be rounded to bf16 on their way through the MXU
                precision=(lax.Precision.HIGHEST
                           if y.dtype == jnp.float32 else None)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(step_tile.shape[0],),
            in_specs=[pl.BlockSpec((tt, top_k), by_tile),
                      pl.BlockSpec((tt, top_k), by_tile),
                      pl.BlockSpec((chunk, d),
                                   lambda s, tile, chunk, n: (chunk[s], 0))],
            out_specs=pl.BlockSpec((tt, d), by_tile)),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(step_tile, step_chunk, n_steps, row_of_pair, w.astype(jnp.float32), y)
