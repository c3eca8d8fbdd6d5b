"""The scan of a Mamba-1 selective state-space layer, forward and
backward: a decay of its own for every (channel, state) pair.

Per channel ``c`` and state ``n``, with a float32 state ``H`` [C, N]
that starts at zero in every sequence, a per-token per-channel step
size ``delta`` > 0, ``A`` [C, N] < 0, ``B`` and ``C`` of N columns
shared by all channels, and a skip ``D`` [C]::

    H_t[c, n] = exp(delta_t[c] A[c, n]) H_{t-1}[c, n]
                + delta_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n H_t[c, n] C_t[n] + D[c] x_t[c]

``C x N`` independent scalar recurrences a token: no chunk of it is a
matrix product (``pallas/ssd_scan.py`` needs ONE decay a head for
that), and the associative-scan form holds ``tokens x C x N`` float32.
The kernels walk the sequence instead, with the state in VMEM.

Layout: the N states lie on sublanes and the channels on lanes, so a
token's update of 128 channels is N / 8 vector registers wide, a
``[1, 128]`` row of ``delta`` or ``x`` broadcasts over sublanes, and
the sum over the states is a sublane reduction.  ``B_t`` and ``C_t``
would be ``[N, 1]`` columns broadcast over LANES, a token at a time;
the wrapper hands them over already lane-replicated (``[B, L, N, 128]``
in the operands' type: XLA's broadcast, 64 MiB each at 16,384 tokens of
bfloat16) and the kernel reads a token's tile by its index.

The grid is (batch, chunks of ``chunk`` tokens, channel tiles), the
tile axis innermost and everything sequential: the state of ALL
channels ([C / tile, N, tile] float32) is carried in VMEM from chunk to
chunk.  The forward also writes the state each chunk STARTED from
([chunks, N, C] float32 a sequence).  The ONE backward kernel runs the
chunks in reverse carrying ``dH``: per chunk and tile it first makes the
chunk's states again from that edge state (kept in VMEM, ``chunk + 1``
of them), then walks the tokens backwards for all six cotangents.  The
cotangents of ``B`` and ``C`` sum over the channels: the kernel
accumulates them lane-dense over the tiles and the wrapper reduces the
128 lanes.

:func:`selective_scan_xla` is the same chunked form in plain
``jax.numpy`` (a ``lax.scan`` over chunks, each rematerialised): the
kernels' test oracle beside the token-by-token recurrence; no option
selects it.  A sequence the chunk does not divide raises.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .vmem_budget import fitted_vmem_params

_LANES = 128
_UNROLL = 8          # tokens a loop iteration takes
_TILE_FWD = 1024     # channels a grid step takes, forward
_TILE_BWD = 512      # ... and backward (twice the live accumulators)
# The carried state's type, in VMEM and in the edge states written out:
# what the configuration states.
STATE_DTYPE = jnp.float32

# What a caller's ``jax.checkpoint`` policy may keep so that its
# backward does not run the forward kernel again: the scan's output and
# the state each chunk started from.
SEL_RESIDUAL_NAMES = ("sel_scan_y", "sel_scan_edges")


def _check(x, delta, a, b, c, d, chunk):
    if x.ndim != 3 or delta.shape != x.shape or a.ndim != 2 \
            or a.shape[0] != x.shape[2] or b.shape != c.shape \
            or b.shape != x.shape[:2] + a.shape[1:] \
            or d.shape != x.shape[2:]:
        raise ValueError(f"bad scan shapes x {x.shape} delta {delta.shape} "
                         f"A {a.shape} B {b.shape} C {c.shape} D {d.shape}")
    if x.shape[1] % chunk:
        raise ValueError(f"a chunk of {chunk} does not divide the "
                         f"sequence of {x.shape[1]} tokens")


def _tile(channels: int, most: int) -> int:
    """Channels a grid step takes: the widest multiple of 128 lanes not
    above ``most`` that divides them (all of them below 128: interpret
    mode at test widths)."""
    if channels < _LANES:
        return channels
    return next((t for t in range(most, 0, -_LANES) if channels % t == 0),
                channels)


def _groups(tile: int):
    """The tile as static lane slices of one register width each."""
    lw = min(_LANES, tile)
    return [slice(q, q + lw) for q in range(0, tile, lw)]


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
#
# A token's row of ``delta`` / ``x`` / ``dy`` is a static slice of an
# aligned tile of ``_UNROLL`` rows (Mosaic loads no single row at an index
# it cannot prove aligned), and a token's row of a result is put into
# such a tile by a sublane select before the tile is stored.

def _stack_rows(rows):
    """``_UNROLL`` rows [1, W] -> one tile [_UNROLL, W]."""
    at = lax.broadcasted_iota(jnp.int32, (len(rows), rows[0].shape[1]), 0)
    tile = jnp.broadcast_to(rows[0], at.shape)
    for r, row in enumerate(rows[1:], 1):
        tile = jnp.where(at == r, row, tile)
    return tile


def _fwd_kernel(x_ref, dl_ref, a_ref, b_ref, c_ref, d_ref, y_ref, edge_ref,
                h_s, x_s, y_s):
    k, j = pl.program_id(1), pl.program_id(2)
    chunk, tile = x_s.shape
    lanes = _groups(tile)

    @pl.when(k == 0)
    def _():
        h_s[j] = jnp.zeros(h_s.shape[1:], h_s.dtype)

    edge_ref[0, 0] = h_s[j]
    x_s[...] = x_ref[0].astype(jnp.float32)
    a = [a_ref[:, sl] for sl in lanes]
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def tokens(i, hs):
        hs = list(hs)
        base = pl.multiple_of(i * _UNROLL, _UNROLL)
        for q, sl in enumerate(lanes):
            dl8, x8 = dl_ref[0, pl.ds(base, _UNROLL), sl], \
                x_s[pl.ds(base, _UNROLL), sl]
            rows = []
            for r in range(_UNROLL):
                dl = dl8[r:r + 1]
                hs[q] = jnp.exp(dl * a[q]) * hs[q] \
                    + (dl * x8[r:r + 1]) * f32(b_ref[0, base + r])
                rows.append(jnp.sum(hs[q] * f32(c_ref[0, base + r]), axis=0,
                                    keepdims=True))
            y_s[pl.ds(base, _UNROLL), sl] = _stack_rows(rows)
        return tuple(hs)

    hs = lax.fori_loop(0, chunk // _UNROLL, tokens,
                       tuple(h_s[j, :, sl] for sl in lanes))
    for q, sl in enumerate(lanes):
        h_s[j, :, sl] = hs[q]
    y_ref[0] = (y_s[...] + d_ref[...] * x_s[...]).astype(y_ref.dtype)


def _bwd_kernel(x_ref, dl_ref, a_ref, b_ref, c_ref, d_ref, dy_ref, edge_ref,
                dx_ref, ddl_ref, da_ref, db_ref, dc_ref, dd_ref,
                g_s, da_s, dd_s, h_s, x_s, dy_s, dx_s):
    k, j = pl.program_id(1), pl.program_id(2)
    chunk, tile = x_s.shape
    lanes = _groups(tile)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    @pl.when(k == 0)   # the LAST chunk of the sequence: nothing behind it
    def _():
        g_s[j] = jnp.zeros(g_s.shape[1:], jnp.float32)
        da_s[j] = jnp.zeros(da_s.shape[1:], jnp.float32)
        dd_s[j] = jnp.zeros(dd_s.shape[1:], jnp.float32)

    @pl.when(j == 0)   # summed over the channel tiles
    def _():
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, jnp.float32)

    x_s[...] = f32(x_ref[0])
    dy_s[...] = f32(dy_ref[0])
    a = [a_ref[:, sl] for sl in lanes]

    # The chunk's states again, from the one it started from: h_s[t + 1]
    # is the state after token t.
    h_s[0] = edge_ref[0, 0]

    def again(i, hs):
        hs = list(hs)
        base = pl.multiple_of(i * _UNROLL, _UNROLL)
        for q, sl in enumerate(lanes):
            dl8, x8 = dl_ref[0, pl.ds(base, _UNROLL), sl], \
                x_s[pl.ds(base, _UNROLL), sl]
            for r in range(_UNROLL):
                dl = dl8[r:r + 1]
                hs[q] = jnp.exp(dl * a[q]) * hs[q] \
                    + (dl * x8[r:r + 1]) * f32(b_ref[0, base + r])
                h_s[base + r + 1, :, sl] = hs[q]
        return tuple(hs)

    lax.fori_loop(0, chunk // _UNROLL, again,
                  tuple(h_s[0, :, sl] for sl in lanes))

    def back(i, carry):
        gs, das = list(carry[0]), list(carry[1])
        base = pl.multiple_of(chunk - (i + 1) * _UNROLL, _UNROLL)
        db, dc = [0.0] * _UNROLL, [0.0] * _UNROLL
        for q, sl in enumerate(lanes):
            dl8, x8, dy8 = (ref[pl.ds(base, _UNROLL), sl] for ref in (
                dl_ref.at[0], x_s, dy_s))
            ddl, dx = [None] * _UNROLL, [None] * _UNROLL
            for r in reversed(range(_UNROLL)):
                t = base + r
                dl, xt, dy = dl8[r:r + 1], x8[r:r + 1], dy8[r:r + 1]
                g = dy * f32(c_ref[0, t]) + gs[q]    # dL / dH_t
                decay = jnp.exp(dl * a[q])
                dda = g * h_s[t, :, sl] * decay      # dL / d(delta_t A)
                du = jnp.sum(g * f32(b_ref[0, t]), axis=0, keepdims=True)
                ddl[r] = jnp.sum(dda * a[q], axis=0, keepdims=True) + du * xt
                dx[r] = du * dl
                das[q] = das[q] + dda * dl
                db[r] = db[r] + g * (dl * xt)
                dc[r] = dc[r] + dy * h_s[t + 1, :, sl]
                gs[q] = decay * g
            ddl_ref[0, pl.ds(base, _UNROLL), sl] = _stack_rows(ddl)
            dx_s[pl.ds(base, _UNROLL), sl] = _stack_rows(dx)
        for r in range(_UNROLL):
            db_ref[0, base + r] += db[r]
            dc_ref[0, base + r] += dc[r]
        return tuple(gs), tuple(das)

    gs, das = lax.fori_loop(
        0, chunk // _UNROLL, back,
        (tuple(g_s[j, :, sl] for sl in lanes),
         tuple(da_s[j, :, sl] for sl in lanes)))
    for q, sl in enumerate(lanes):
        g_s[j, :, sl] = gs[q]
        da_s[j, :, sl] = das[q]
    dd_s[j] += jnp.sum(dy_s[...] * x_s[...], axis=0, keepdims=True)
    dx_ref[0] = (dx_s[...] + d_ref[...] * dy_s[...]).astype(dx_ref.dtype)
    # The sums so far; whole at the sequence's first chunk, the last visit.
    da_ref[0] = da_s[j]
    dd_ref[0] = dd_s[j]


def _specs(n, ns, lw, chunk, tile, *, rev: bool):
    """Grid (batch, chunk, tile); the backward walks the chunks from the
    last one down."""
    nc = n // chunk
    at = (lambda k: nc - 1 - k) if rev else (lambda k: k)
    return {
        "x": pl.BlockSpec((1, chunk, tile), lambda b, k, j: (b, at(k), j)),
        "a": pl.BlockSpec((ns, tile), lambda b, k, j: (0, j)),
        "bc": pl.BlockSpec((1, chunk, ns, lw),
                           lambda b, k, j: (b, at(k), 0, 0)),
        "d": pl.BlockSpec((1, tile), lambda b, k, j: (0, j)),
        "edge": pl.BlockSpec((1, 1, ns, tile),
                             lambda b, k, j: (b, at(k), 0, j)),
        "da": pl.BlockSpec((1, ns, tile), lambda b, k, j: (b, 0, j)),
        "dd": pl.BlockSpec((1, 1, tile), lambda b, k, j: (b, 0, j)),
    }


def _vmem_params(need_bytes: int, what: str):
    """Never under the compiler's own default of 16 MiB."""
    return fitted_vmem_params(max(need_bytes, 16 * 2 ** 20), what)


@jax.named_scope("dsod.kernel.selective_scan")
def _fwd_call(x, dl, at, bb, cb, d2, cfg):
    chunk, tile, _, interpret = cfg
    bs, n, ch = x.shape
    ns, lw = at.shape[0], bb.shape[-1]
    sp = _specs(n, ns, lw, chunk, tile, rev=False)
    blocks = chunk * tile * (2 * x.dtype.itemsize + 4) \
        + 2 * chunk * ns * lw * bb.dtype.itemsize + 2 * ns * tile * 4
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bs, n // chunk, ch // tile),
        in_specs=[sp["x"], sp["x"], sp["a"], sp["bc"], sp["bc"], sp["d"]],
        out_specs=[sp["x"], sp["edge"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bs, n // chunk, ns, ch),
                                        STATE_DTYPE)],
        scratch_shapes=[pltpu.VMEM((ch // tile, ns, tile), STATE_DTYPE),
                        pltpu.VMEM((chunk, tile), jnp.float32),
                        pltpu.VMEM((chunk, tile), jnp.float32)],
        compiler_params=_vmem_params(
            2 * blocks + 4 * ns * ch + 8 * chunk * tile + 4 * 2 ** 20,
            f"selective_scan over {ch} channels of {ns} states"),
        cost_estimate=pl.CostEstimate(
            flops=6 * bs * n * ch * ns, transcendentals=bs * n * ch * ns,
            bytes_accessed=x.size * (2 * x.dtype.itemsize + 4)),
        interpret=interpret,
    )(x, dl, at, bb, cb, d2)


@jax.named_scope("dsod.kernel.selective_scan_bwd")
def _bwd_call(x, dl, at, bb, cb, d2, dy, edges, cfg):
    chunk, _, tile, interpret = cfg
    bs, n, ch = x.shape
    ns, lw = at.shape[0], bb.shape[-1]
    sp = _specs(n, ns, lw, chunk, tile, rev=True)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    acc = lambda *shape: pltpu.VMEM(shape, jnp.float32)  # noqa: E731
    blocks = chunk * tile * (3 * x.dtype.itemsize + 8) \
        + 2 * chunk * ns * lw * (bb.dtype.itemsize + 4) + 5 * ns * tile * 4
    return pl.pallas_call(
        _bwd_kernel,
        grid=(bs, n // chunk, ch // tile),
        in_specs=[sp["x"], sp["x"], sp["a"], sp["bc"], sp["bc"], sp["d"],
                  sp["x"], sp["edge"]],
        out_specs=[sp["x"], sp["x"], sp["da"], sp["bc"], sp["bc"], sp["dd"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), f32(*x.shape),
                   f32(bs, ns, ch), f32(bs, n, ns, lw), f32(bs, n, ns, lw),
                   f32(bs, 1, ch)],
        scratch_shapes=[acc(ch // tile, ns, tile), acc(ch // tile, ns, tile),
                        acc(ch // tile, 1, tile), acc(chunk + 1, ns, tile),
                        acc(chunk, tile), acc(chunk, tile),
                        acc(chunk, tile)],
        compiler_params=_vmem_params(
            2 * blocks + 8 * ns * ch + 4 * (chunk + 1) * ns * tile
            + 12 * chunk * tile + 4 * 2 ** 20,
            f"selective_scan's backward over {ch} channels of {ns} states"),
        cost_estimate=pl.CostEstimate(
            flops=20 * bs * n * ch * ns,
            transcendentals=2 * bs * n * ch * ns,
            bytes_accessed=x.size * (3 * x.dtype.itemsize + 8)
            + edges.size * 4),
        interpret=interpret,
    )(x, dl, at, bb, cb, d2, dy, edges)


def _lane_copies(t, lw):
    """[B, L, N] -> [B, L, N, lw], every lane the same."""
    return jnp.broadcast_to(t[..., None], t.shape + (lw,))


def _lane_width(x):
    return min(_LANES, x.shape[2])


def _forward(x, dl, a, b, c, d, cfg):
    lw = _lane_width(x)
    return _fwd_call(x, dl, a.T, _lane_copies(b, lw), _lane_copies(c, lw),
                     d[None], cfg)


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dl, a, b, c, d, cfg):
    return _forward(x, dl, a, b, c, d, cfg)[0]


def _scan_fwd(x, dl, a, b, c, d, cfg):
    y, edges = _forward(x, dl, a, b, c, d, cfg)
    n_y, n_edges = SEL_RESIDUAL_NAMES
    return checkpoint_name(y, n_y), (x, dl, a, b, c, d,
                                     checkpoint_name(edges, n_edges))


def _scan_bwd(cfg, res, dy):
    x, dl, a, b, c, d, edges = res
    lw = _lane_width(x)
    dx, ddl, da, db, dc, dd = _bwd_call(
        x, dl, a.T, _lane_copies(b, lw), _lane_copies(c, lw), d[None], dy,
        edges, cfg)
    return (dx, ddl, jnp.sum(da, 0).T, jnp.sum(db, -1).astype(b.dtype),
            jnp.sum(dc, -1).astype(c.dtype), jnp.sum(dd, (0, 1)))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, delta, a, b, c, d, *, chunk: int = 128,
                   interpret: bool | None = None):
    """``y`` of the recurrence in the module docstring, the ``D`` skip
    included.

    x: [B, L, C]; delta: [B, L, C] (already positive); a: [C, N]
    (negative); b, c: [B, L, N]; d: [C].  ``y`` has x's type.
    Differentiable in all six.  The kernels run in the interpreter on
    the CPU (``interpret`` None)."""
    _check(x, delta, a, b, c, d, chunk)
    ch, ns = a.shape
    interpret = (jax.default_backend() == "cpu" if interpret is None
                 else interpret)
    if not interpret and (ch % _LANES or ns % 8 or chunk % 8):
        raise ValueError(f"{ch} channels of {ns} states in chunks of "
                         f"{chunk} do not fill the chip's 128 lanes and 8 "
                         "sublanes")
    if chunk % _UNROLL:
        raise ValueError(f"a chunk of {chunk} is no multiple of {_UNROLL}")
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    return _scan(x, f32(delta), f32(a), b, c, f32(d),
                 (chunk, _tile(ch, _TILE_FWD), _tile(ch, _TILE_BWD),
                  interpret))


# ---------------------------------------------------------------------------
# the same chunked form in plain XLA
# ---------------------------------------------------------------------------

def selective_scan_xla(x, delta, a, b, c, d, *, chunk: int = 128):
    """:func:`selective_scan` without a kernel: a ``lax.scan`` over the
    chunks, each rematerialised and walked token by token, so that the
    backward keeps the states at the chunks' edges alone."""
    _check(x, delta, a, b, c, d, chunk)
    bs, n, ch = x.shape
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    a, d = f32(a), f32(d)
    chunks = lambda t: jnp.moveaxis(  # noqa: E731  -> [chunks, Q, B, ...]
        f32(t).reshape((bs, n // chunk, chunk) + t.shape[2:]), 0, 2)

    def token(h, ts):                    # h [B, C, N] float32
        xt, dl, bt, ct = ts
        h = jnp.exp(dl[..., None] * a) * h \
            + (dl * xt)[..., None] * bt[:, None, :]
        return h, jnp.sum(h * ct[:, None, :], -1) + d * xt

    @jax.checkpoint
    def one(h, ts):
        return lax.scan(token, h, ts)

    _, y = lax.scan(one, jnp.zeros((bs, ch, a.shape[1]), jnp.float32),
                    (chunks(x), chunks(delta), chunks(b), chunks(c)))
    return jnp.moveaxis(y.reshape(n, bs, ch), 0, 1).astype(x.dtype)
