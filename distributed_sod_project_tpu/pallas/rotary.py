"""Rotate-half rotary embedding of head-major tensors, forward and
backward, one kernel each: one read and one write of every tensor.

With ``x`` [B*H, N, D] in the compute dtype and float32 tables ``cos``,
``sin`` [N, D] (:func:`rotary_tables`: the angle of column ``i`` and of
column ``i + D/2`` is ``position * theta ** (-2 i / D)``; the sine table
carries the sign of the rotation, ``[-sin, sin]``)::

    y = dtype(f32(x) * cos + roll(f32(x), D/2) * sin)

which is ``models/lfm2.py::rope`` on the float32 copy, rounded once —
``roll`` by half the head swaps its halves, and the table's sign makes
the swapped halves ``[-x2, x1]``.  The map is linear and orthogonal, so
its backward is the same kernel with the sine negated::

    dx = dtype(f32(dy) * cos - roll(f32(dy), D/2) * sin)

and nothing but the tables is a residual.

XLA makes three float32 fusions a tensor of this (the projection's
product written in float32, the negated and plain halves written as
arrays of their own, a third fusion that reads all of them: ~350 MiB
moved for a 32 MiB bf16 tensor; PERF.md section 6, PR 42).  Here every
tensor of one call (q and k go through ONE) is read once and written
once in its own dtype; a tile is converted to float32 in VMEM piece by
piece (``_ROWS`` rows: a piece's chain of operations stays near the
vector registers), the roll is a lane rotation.

The grid is (token tile, folded head), the heads innermost: a table
tile's block index does not change while the heads stream past it, so it
is fetched once a token tile.  A tile is ``_TILE`` rows (fewer for a
short sequence) by the whole head; a head that is no multiple of the 128
lanes raises (a half-lane roll is another kernel: such heads rotate in
XLA, ``models/ouro.py``), a length that is no multiple of the tile is
zero-padded by :func:`rotate_half` as the flash entry pads it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _pad_n
from .vmem_budget import fitted_vmem_params

_LANES = 128
_TILE = 1024       # rows of a tile
_ROWS = 256        # rows of the piece of a tile one chain of operations takes
_F32 = jnp.float32


def rotary_tables(n: int, d: int, theta: float):
    """-> (cos, signed sin), float32 [n, d], positions 0..n-1: the
    angles of ``models/lfm2.py::rope``, the sine negated over the first
    half of the head."""
    inv = theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d)
    ang = jnp.arange(n, dtype=_F32)[:, None] * inv[None]
    sin = jnp.sin(ang)
    return (jnp.concatenate([jnp.cos(ang)] * 2, -1),
            jnp.concatenate([-sin, sin], -1))


def _kernel(*refs, backward: bool):
    """refs: the tensors' blocks [1, tn, D], cos and sin [tn, D], then as
    many output blocks."""
    m = (len(refs) - 2) // 2
    cos_ref, sin_ref = refs[m:m + 2]
    tn, d = cos_ref.shape
    rows = math.gcd(_ROWS, tn)
    for r0 in range(0, tn, rows):
        at = pl.ds(r0, rows)
        cos, sin = cos_ref[at, :], sin_ref[at, :]
        for x_ref, y_ref in zip(refs[:m], refs[m + 2:]):
            x = x_ref[0, at, :].astype(_F32)
            turned = pltpu.roll(x, d // 2, 1) * sin
            y_ref[0, at, :] = (x * cos - turned if backward
                               else x * cos + turned).astype(y_ref.dtype)


def _call(xs, cos, sin, interpret, backward):
    bh, n, d = xs[0].shape
    tn = min(_TILE, n)
    item = xs[0].dtype.itemsize
    x_spec = pl.BlockSpec((1, tn, d), lambda t, h: (h, t, 0))
    table = pl.BlockSpec((tn, d), lambda t, h: (t, 0))
    m = len(xs)
    return pl.pallas_call(
        partial(_kernel, backward=backward),
        grid=(n // tn, bh),
        in_specs=[x_spec] * m + [table] * 2,
        out_specs=[x_spec] * m,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs],
        cost_estimate=pl.CostEstimate(
            flops=3 * m * bh * n * d, transcendentals=0,
            bytes_accessed=2 * m * bh * n * d * item + 2 * n * d * 4),
        # every block double-buffered, and 4 MiB for the compiler's own
        compiler_params=fitted_vmem_params(
            2 * tn * d * (2 * m * item + 2 * 4) + 4 * 2 ** 20, "rotary"),
        interpret=interpret,
    )(*xs, cos, sin)


# Jitted INLINE, as the conv kernels are (causal_conv.py): 32 visits of a
# looped stack splice ONE traced kernel body into the step's jaxpr, under
# each caller's name stack.
@partial(jax.jit, static_argnums=3, inline=True)
@jax.named_scope("dsod.kernel.rotary")
def _fwd_call(xs, cos, sin, interpret):
    return _call(xs, cos, sin, interpret, False)


@partial(jax.jit, static_argnums=3, inline=True)
@jax.named_scope("dsod.kernel.rotary_bwd")
def _bwd_call(gs, cos, sin, interpret):
    return _call(gs, cos, sin, interpret, True)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate(xs, cos, sin, interpret):
    return _fwd_call(xs, cos, sin, interpret)


def _rotate_fwd(xs, cos, sin, interpret):
    return _fwd_call(xs, cos, sin, interpret), (cos, sin)


def _rotate_bwd(interpret, tables, gs):
    cos, sin = tables
    return (_bwd_call(tuple(gs), cos, sin, interpret),
            jnp.zeros_like(cos), jnp.zeros_like(sin))


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def rotate_half(xs, theta: float, *, interpret: bool | None = None):
    """The rotation of the module docstring on every tensor of ``xs`` (a
    tuple of equal shapes [..., N, D], head-major, positions 0..N-1 along
    the last axis but one) in ONE kernel call -> a tuple of the same
    shapes and dtype.  Differentiable in the tensors.  The kernels run in
    the interpreter on the CPU (``interpret`` None)."""
    xs = tuple(xs)
    shape, dtype = xs[0].shape, xs[0].dtype
    if len(shape) < 2 or any(x.shape != shape or x.dtype != dtype
                             for x in xs):
        raise ValueError("rotate_half wants tensors of one shape "
                         f"[..., N, D] and dtype, got {[x.shape for x in xs]}")
    n, d = shape[-2:]
    if d % _LANES:
        raise ValueError(f"a head of {d} columns does not fill the chip's "
                         f"{_LANES} lanes")
    interpret = (jax.default_backend() == "cpu" if interpret is None
                 else interpret)
    tn = min(_TILE, -(-n // _LANES) * _LANES)
    np_ = -(-n // tn) * tn
    cos, sin = rotary_tables(np_, d, theta)
    out = _rotate(tuple(_pad_n(x.reshape(-1, n, d), np_) for x in xs),
                  cos, sin, interpret)
    return tuple(y[:, :n].reshape(shape) for y in out)
