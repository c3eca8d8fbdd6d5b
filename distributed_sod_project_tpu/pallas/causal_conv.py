"""Depthwise causal convolution + bias + SiLU (the Mamba-2 mixer's
``silu(conv(xBC) + bias)``), forward and backward, one kernel each.

With ``x`` [B, N, D] in the compute dtype, float32 taps ``k`` [L, D]
(tap j multiplies the input L-1-j positions back) and ``bias`` [D]::

    pre[t] = sum_j f32(x)[t-(L-1-j)] k[j] + bias     (taps j = 0..L-1 in
    y[t]   = dtype(pre[t] * sigmoid(pre[t]))          that order, then bias)

zero to the left of every sequence's first token, no reset at a document
join, each batch row on its own.  The backward makes ``pre`` again from
``x`` (nothing but ``x`` is a residual)::

    dpre  = f32(dy) silu'(pre)
    dx[t] = dtype(sum_j dpre[t+(L-1-j)] k[j])
    dk[j] = sum_t dpre[t] f32(x)[t-(L-1-j)]          dbias = sum_t dpre[t]

Both kernels read and write HBM in ``x.dtype`` and do the arithmetic in
float32 in VMEM: a tile is converted ONCE into a float32 scratch whose
first ``_HALO`` rows hold the rows before the tile, and the L taps read
L statically offset windows of it.  A tile is ONE vreg (128 lanes) wide:
the scratch is then row after row in memory and a window that starts 1
to L-1 rows early is an address, not a shuffle of sublanes (at 256 lanes
the same kernels took 1.17 x / 1.25 x as long on the chip; XLA's form
shifted a float32 copy of the whole array through HBM: PERF.md sections
5 and 6).  A tile is walked in pieces of ``_ROWS`` rows so that a
piece's chain of operations stays near the vector registers (one piece
of 4,096 rows took 1.3 x / 1.5 x as long; pieces of 64 rows were no
faster than 256 and made the step's trace 17 s longer on the chip's
host, which is why the two calls are also jitted inline).

The grid is (batch row, column tile, token tile), the token tiles
innermost and sequential.  Forward: the last rows of a tile stay in the
scratch for the next one.  Backward: the token tiles, and the pieces of
each, run from the last to the first so that the first rows of ``dpre``
AFTER a piece are in VMEM for its ``dx``; the rows of ``x`` BEFORE the
tile come through a second block spec; ``dk`` and ``dbias`` accumulate
in float32 across the token tiles, eight sublanes apart, and are folded
at the sequence's first tile.

Tiles come from the shape alone (:func:`_tiles`): 128 lanes (all of D
where 128 does not divide it: the interpreter's tiny widths) by the most
rows that fit ``_BUDGET`` bytes of VMEM and divide N.  A length no tile
divides raises: a grid of ``n // tile`` steps covers ``n`` only if it
does, and interpret mode cannot tell (PERF.md section 6, PR 28).

:func:`causal_conv_silu_xla` is the same mathematics in plain
``jax.numpy`` — the tests' oracle; no option selects it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .vmem_budget import fitted_vmem_params

_HALO = 8          # float32 sublanes of one vreg: rows kept before a tile
_LANES = 128       # columns of a tile: one vreg
_ROWS = 256        # rows of the piece of a tile one chain of operations takes
_BUDGET = 12 * 2 ** 20   # VMEM the backward's blocks and scratch may fill
_F32 = jnp.float32


def _sublanes(dtype) -> int:
    """Rows of one packed vreg: 8 at 4 bytes, 16 at 2."""
    return 32 // jnp.dtype(dtype).itemsize


def _vmem_bytes(tn: int, tc: int, itemsize: int) -> int:
    """What the backward holds at a (tn, tc) tile: the x, dy and dx blocks
    double-buffered and two float32 scratch tiles."""
    return tn * tc * (3 * 2 * itemsize + 2 * 4)


def _tiles(n: int, d: int, dtype) -> tuple[int, int]:
    """(token tile, column tile) for [*, n, d] operands of ``dtype``."""
    sub, tc = _sublanes(dtype), d if d % _LANES else _LANES
    for m in range(1, n // sub + 1):
        tn = n // m
        if n % m == 0 and tn % sub == 0 and _vmem_bytes(
                tn, tc, jnp.dtype(dtype).itemsize) <= _BUDGET:
            return tn, tc
    raise ValueError(f"no token tile of a multiple of {sub} rows divides "
                     f"the sequence of {n} tokens")


def _pieces(tn: int):
    """(rows, first rows) of the pieces a tile of ``tn`` rows is walked
    in (a static loop: PERF.md section 3, a loop inside a kernel loses
    the scope path in interpret mode)."""
    rows = math.gcd(_ROWS, tn)
    return rows, range(0, tn, rows)


def _pre(xs, k, bias, r0, rows):
    """Rows r0.. of the tile in ``xs`` (its first ``_HALO`` rows are the
    rows before the tile): the L row-shifted float32 windows (window j is
    the input L-1-j positions back) and the pre-activation."""
    taps = len(k)
    windows = [xs[pl.ds(_HALO - (taps - 1 - j) + r0, rows), :]
               for j in range(taps)]
    acc = windows[0] * k[0]
    for j in range(1, taps):
        acc = acc + windows[j] * k[j]
    return windows, acc + bias


def _over(ref, rows):
    """Each row of ``ref`` [R, tc] over ``rows`` sublanes."""
    return [jnp.broadcast_to(ref[j:j + 1, :], (rows, ref.shape[1]))
            for j in range(ref.shape[0])]


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _cc_fwd_kernel(x_ref, k_ref, b_ref, y_ref, xs, *, taps: int):
    tn, tc = x_ref.shape[1:]
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        xs[0:_HALO] = jnp.zeros((_HALO, tc), _F32)

    @pl.when(t > 0)
    def _():       # the previous tile's last rows are still in the scratch
        xs[0:_HALO] = xs[tn:tn + _HALO]

    xs[_HALO:] = x_ref[0].astype(_F32)
    rows, starts = _pieces(tn)
    k, (bias,) = _over(k_ref, rows), _over(b_ref, rows)
    for r0 in starts:
        _, pre = _pre(xs, k, bias, r0, rows)
        y_ref[0, r0:r0 + rows] = (pre * jax.nn.sigmoid(pre)).astype(
            y_ref.dtype)


def _fold(v):
    """[rows, tc] -> [8, tc]: rows added eight sublanes apart (VPU adds;
    the last 8 -> 1 fold waits for the sequence's end)."""
    return jnp.sum(v.reshape(v.shape[0] // _HALO, _HALO, v.shape[1]), axis=0)


def _cc_bwd_kernel(x_ref, xh_ref, dy_ref, k_ref, b_ref, dx_ref, dk_ref,
                   db_ref, xs, ds, acc, *, taps: int):
    tn, tc = x_ref.shape[1:]
    j, nt = pl.program_id(2), pl.num_programs(2)   # j = 0: the LAST tile

    @pl.when(j == 0)
    def _():
        ds[tn:] = jnp.zeros((_HALO, tc), _F32)
        acc[...] = jnp.zeros(acc.shape, _F32)

    @pl.when(j > 0)
    def _():       # the first rows of dpre of the tile after this one
        ds[tn:] = ds[0:_HALO]

    @pl.when(j == nt - 1)
    def _():
        xs[0:_HALO] = jnp.zeros((_HALO, tc), _F32)

    @pl.when(j < nt - 1)
    def _():
        xs[0:_HALO] = xh_ref[0].astype(_F32)[xh_ref.shape[1] - _HALO:]

    xs[_HALO:] = x_ref[0].astype(_F32)
    rows, starts = _pieces(tn)
    k, (bias,) = _over(k_ref, rows), _over(b_ref, rows)
    sums = [jnp.zeros((_HALO, tc), _F32)] * (taps + 1)
    for r0 in reversed(starts):
        windows, pre = _pre(xs, k, bias, r0, rows)
        sig = jax.nn.sigmoid(pre)
        dpre = dy_ref[0, r0:r0 + rows].astype(_F32) * (
            sig * (1.0 + pre * (1.0 - sig)))
        ds[r0:r0 + rows] = dpre
        dx = dpre * k[taps - 1]
        for i in range(taps - 1):
            dx = dx + ds[pl.ds(r0 + taps - 1 - i, rows), :] * k[i]
        dx_ref[0, r0:r0 + rows] = dx.astype(dx_ref.dtype)
        sums = [s + _fold(dpre * w) for s, w in zip(sums, windows)] + [
            sums[taps] + _fold(dpre)]
    for i in range(taps + 1):
        acc[i] += sums[i]

    @pl.when(j == nt - 1)
    def _():
        for i in range(taps):
            dk_ref[0, i:i + 1, :] = jnp.sum(acc[i], axis=0, keepdims=True)
        db_ref[0] = jnp.sum(acc[taps], axis=0, keepdims=True)


def _params(tn, tc, dtype):
    """The scoped-VMEM limit a call asks for: what its shapes say it
    holds and 4 MiB for the compiler's own temporaries."""
    return fitted_vmem_params(
        _vmem_bytes(tn, tc, jnp.dtype(dtype).itemsize) + 4 * 2 ** 20,
        "causal_conv")


# Each call is jitted INLINE: a layer's call with shapes another layer has
# already shown splices the kernel's jaxpr, traced once, into the
# caller's (under the caller's name stack, so the scope path stands)
# instead of tracing its pieces again: 27 calls a step, traced twice by
# ``fit()``, were 20 s of host time a trace on the chip's host.
@partial(jax.jit, static_argnums=3, inline=True)
@jax.named_scope("dsod.kernel.causal_conv")
def _fwd_call(x, k, bias, interpret):
    bs, n, d = x.shape
    taps = k.shape[0]
    tn, tc = _tiles(n, d, x.dtype)
    tok = pl.BlockSpec((1, tn, tc), lambda b, c, t: (b, t, c))
    return pl.pallas_call(
        partial(_cc_fwd_kernel, taps=taps),
        grid=(bs, d // tc, n // tn),
        in_specs=[tok, pl.BlockSpec((taps, tc), lambda b, c, t: (0, c)),
                  pl.BlockSpec((1, tc), lambda b, c, t: (0, c))],
        out_specs=tok,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + tn, tc), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=(2 * taps + 4) * x.size, transcendentals=x.size,
            bytes_accessed=2 * x.size * x.dtype.itemsize),
        compiler_params=_params(tn, tc, x.dtype),
        interpret=interpret,
    )(x, k, bias.reshape(1, d))


@partial(jax.jit, static_argnums=4, inline=True)
@jax.named_scope("dsod.kernel.causal_conv_bwd")
def _bwd_call(x, k, bias, dy, interpret):
    bs, n, d = x.shape
    taps = k.shape[0]
    tn, tc = _tiles(n, d, x.dtype)
    nt, sub = n // tn, _sublanes(x.dtype)
    tok = pl.BlockSpec((1, tn, tc), lambda b, c, t: (b, nt - 1 - t, c))
    # the ``sub`` rows before the tile (the sequence's first tile reads
    # its own first rows and does not use them)
    before = pl.BlockSpec(
        (1, sub, tc),
        lambda b, c, t: (b, jnp.maximum((nt - 1 - t) * (tn // sub) - 1, 0),
                         c))
    vec = lambda rows: pl.BlockSpec(  # noqa: E731
        (1, rows, tc), lambda b, c, t: (b, 0, c))
    dx, dk, db = pl.pallas_call(
        partial(_cc_bwd_kernel, taps=taps),
        grid=(bs, d // tc, nt),
        in_specs=[tok, before, tok,
                  pl.BlockSpec((taps, tc), lambda b, c, t: (0, c)),
                  pl.BlockSpec((1, tc), lambda b, c, t: (0, c))],
        out_specs=[tok, vec(taps), vec(1)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bs, taps, d), _F32),
                   jax.ShapeDtypeStruct((bs, 1, d), _F32)],
        scratch_shapes=[pltpu.VMEM((_HALO + tn, tc), _F32),
                        pltpu.VMEM((tn + _HALO, tc), _F32),
                        pltpu.VMEM((taps + 1, _HALO, tc), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=(6 * taps + 12) * x.size, transcendentals=x.size,
            bytes_accessed=3 * x.size * x.dtype.itemsize),
        compiler_params=_params(tn, tc, x.dtype),
        interpret=interpret,
    )(x, x, dy, k, bias.reshape(1, d))
    return dx, jnp.sum(dk, axis=0), jnp.sum(db, axis=(0, 1))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv(x, k, bias, interpret):
    return _fwd_call(x, k, bias, interpret)


def _conv_fwd(x, k, bias, interpret):
    return _fwd_call(x, k, bias, interpret), (x, k, bias)


def _conv_bwd(interpret, res, dy):
    return _bwd_call(*res, dy, interpret)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_silu(x, k, bias, *, interpret: bool | None = None):
    """``silu(conv(x) + bias)`` of the module docstring, in ``x.dtype``.

    x: [B, N, D]; k: [L, D] and bias: [D], float32.  Differentiable in
    all three.  The kernels run in the interpreter on the CPU
    (``interpret`` None)."""
    if x.ndim != 3 or k.ndim != 2 or k.shape[1] != x.shape[2] \
            or bias.shape != x.shape[2:]:
        raise ValueError(f"bad conv shapes x {x.shape} k {k.shape} "
                         f"bias {bias.shape}")
    if not 1 <= k.shape[0] <= _HALO + 1:
        raise ValueError(f"{k.shape[0]} taps: the kernel keeps {_HALO} "
                         "rows before a tile")
    interpret = (jax.default_backend() == "cpu" if interpret is None
                 else interpret)
    if not interpret and x.shape[2] % 128:
        raise ValueError(f"{x.shape[2]} columns do not fill the chip's "
                         "128 lanes")
    return _conv(x, k.astype(_F32), bias.astype(_F32), interpret)


def causal_conv_silu_xla(x, k, bias):
    """:func:`causal_conv_silu` without a kernel: a float32 copy, a pad,
    L shifted slices, bias, SiLU, a cast back."""
    n, taps = x.shape[1], k.shape[0]
    xp = jnp.pad(x.astype(_F32), ((0, 0), (taps - 1, 0), (0, 0)))
    pre = sum(xp[:, j:j + n] * k[j] for j in range(taps)) + bias
    return (pre * jax.nn.sigmoid(pre)).astype(x.dtype)
