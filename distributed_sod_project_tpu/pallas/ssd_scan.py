"""The scan of a selective state-space layer (Mamba-2's SSD), forward
and backward, in its chunked dual form.

Per head, with a float32 state ``H`` of shape [P, N] that starts at
zero in every sequence, a per-token per-head step size ``delta`` > 0, a
per-head ``A`` < 0, and ``B``, ``C`` of N columns shared by all heads::

    H_t = exp(delta_t A) H_{t-1} + delta_t x_t B_t^T        y_t = H_t C_t

Within a chunk of Q tokens, with ``cum`` the running sum of ``delta A``
inside the chunk::

    y = ((C B^T) * L) (delta x) + exp(cum) C H_prev
    L[i, j] = exp(cum_i - cum_j) for j <= i, else 0
    H_next = exp(cum_Q) H_prev + ((exp(cum_Q - cum) delta) x)^T B

``C B^T`` is ONE [Q, Q] product a chunk for all heads; each head then
costs a [Q, Q] mask on the VPU and [Q, Q] x [Q, P] on the MXU.  A head
is P = 64 columns, so the kernels work on units of ``128 // P`` heads
(128 lanes): each head's [Q, Q] matrix multiplies the WHOLE unit — the
MXU is 128 columns wide whether 64 are used or not — and a lane select
keeps the head's own columns.  Nothing is sliced at 64 lanes.

The grid is (batch, chunks, slabs of 8 heads), sequential: the state of
ALL heads ([N, H P] float32, 2 MiB at 64 x 64 x 128) is carried in VMEM
from chunk to chunk; the backward runs the chunks in reverse carrying
``dH``, and reads the state each chunk STARTED from, which the forward
writes out ([chunks, N, H P] float32 a sequence).  Matrix products take
operands in ``x.dtype`` (bfloat16 on the chip) and accumulate in
float32; ``cum``, ``L``, the state and every reduction are float32.

What is XLA's around the two ``pallas_call``s (:func:`ssd_scan`):
``delta A``, its running sum inside each chunk, and the head-major
copies of ``cum`` / ``delta`` / ``B`` / ``C`` the kernels read as rows
(a [1, Q] row broadcasts over sublanes for free; a [Q, 1] column is
picked out of the token-major ``cum`` by a one-hot lane reduction).
``cum`` enters the kernel twice, token-major and head-major, as two
operands with a cotangent each; autodiff adds them up outside.

:func:`ssd_scan_xla` is the same chunked form in plain ``jax.numpy``
(a ``lax.scan`` over chunks, each rematerialised): the kernels' test
oracle beside the token-by-token recurrence; no option selects it.

A sequence the chunk does not divide raises: a grid of ``n // chunk``
steps covers ``n`` only if it does (interpret mode at a tiny size
cannot tell; the chip reads what was never written).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_OFF = -1e30  # exp() of it is 0: the mask above the diagonal
# The carried state's type, in VMEM and in the chunk states written out.
# float32 is what the configuration states; the benchmark's rehearsal of
# a lower precision (PERF.md section 4) sets bfloat16 here from outside.
STATE_DTYPE = jnp.float32

# What a caller's ``jax.checkpoint`` policy may keep so that its
# backward does not run the forward kernel again: the scan's output and
# the state each chunk started from.
SSD_RESIDUAL_NAMES = ("ssd_y", "ssd_states")


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


_NN = ((1,), (0,))   # a @ b
_NT = ((1,), (1,))   # a @ b.T
_TN = ((0,), (0,))   # a.T @ b


def _heads_per_unit(heads: int, head_dim: int) -> int:
    """Heads that fill the 128 lanes one product works on."""
    g = min(heads, max(1, _LANES // head_dim))
    if heads % g:
        raise ValueError(f"{heads} heads do not split into units of {g}")
    return g


def _check(x, dt, a, b, c, chunk):
    if x.ndim != 4 or dt.shape != x.shape[:3] or a.shape != x.shape[2:3] \
            or b.shape != c.shape or b.shape[:2] != x.shape[:2]:
        raise ValueError(f"bad scan shapes x {x.shape} dt {dt.shape} "
                         f"A {a.shape} B {b.shape} C {c.shape}")
    if x.shape[1] % chunk:
        raise ValueError(f"a chunk of {chunk} does not divide the "
                         f"sequence of {x.shape[1]} tokens")


def _chunk_cumsum(a, chunk):
    """Running sum of ``a`` [B, L, H] inside each chunk of ``chunk``."""
    b, n, h = a.shape
    return jnp.cumsum(a.reshape(b, n // chunk, chunk, h), axis=2).reshape(
        b, n, h)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
#
# Grid (batch, chunks, slabs), the slab axis innermost: one step is one
# chunk of ``hps`` heads (``_SLAB`` lanes of x), walked 128 lanes (g
# heads) at a time by a static loop.  What all heads of a chunk share
# (``C B^T`` forward; its cotangent, ``dB``, ``dC`` backward) is made at
# the chunk's first slab, kept in VMEM, and finished at its last.

_SLAB = 512  # lanes of x a grid step takes (8 heads of 64)


def _head_terms(cum_ref, cumt_ref, dtt_ref, head, k, q):
    """One head of the chunk: its ``cum`` as a column [Q, 1] (``head``:
    its index among all heads) and as a row [1, Q] (``k``: its index in
    the slab), ``delta`` as a row, and ``cum`` at the chunk's last token
    [1, 1]."""
    cum = cum_ref[0]                                   # [Q, H]
    lane = lax.broadcasted_iota(jnp.int32, cum.shape, 1)
    col = jnp.sum(jnp.where(lane == head, cum, 0.0), axis=1, keepdims=True)
    row = cumt_ref[0, 0, k:k + 1, :]
    return col, row, dtt_ref[0, 0, k:k + 1, :], row[:, q - 1:q]


def _tri(q):
    return (lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _fwd_kernel(x_ref, cum_ref, cumt_ref, dtt_ref, b_ref, bt_ref, c_ref,
                y_ref, st_ref, s_scr, cb_scr, *, g: int, p: int):
    q, w = x_ref.shape[1], g * p
    dtype = x_ref.dtype
    s = pl.program_id(2)
    hps = x_ref.shape[2] // p

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[s] = jnp.zeros(s_scr.shape[1:], s_scr.dtype)

    @pl.when(s == 0)
    def _():
        cb_scr[...] = _dot(c_ref[0], b_ref[0], _NT)    # [Q, Q], all heads

    st_ref[0, 0] = s_scr[s]            # the state this chunk starts from
    cb, tri = cb_scr[...], _tri(q)
    head_of_lane = lax.broadcasted_iota(jnp.int32, (1, w), 1) // p
    bt = bt_ref[0].astype(jnp.float32)                 # [N, Q]
    for unit in range(x_ref.shape[2] // w):
        lanes = slice(unit * w, (unit + 1) * w)
        xs = x_ref[0, :, lanes]                        # [Q, W]
        state = s_scr[s, :, lanes].astype(jnp.float32)  # [N, W]
        y = jnp.zeros((q, w), jnp.float32)
        e = jnp.zeros((q, w), jnp.float32)
        u = jnp.zeros(state.shape, jnp.float32)
        dec = jnp.zeros((1, w), jnp.float32)
        for k in range(g):
            mine = head_of_lane == k
            col, row, drow, last = _head_terms(
                cum_ref, cumt_ref, dtt_ref, s * hps + unit * g + k,
                unit * g + k, q)
            m = cb * jnp.exp(jnp.where(tri, col - row, _OFF)) * drow
            y = jnp.where(mine, _dot(m.astype(dtype), xs, _NN), y)
            e = jnp.where(mine, jnp.exp(col), e)
            btw = bt * (jnp.exp(last - row) * drow)
            u = jnp.where(mine, _dot(btw.astype(dtype), xs, _NN), u)
            dec = jnp.where(mine, jnp.exp(last), dec)
        y = y + e * _dot(c_ref[0], state.astype(dtype), _NN)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        s_scr[s, :, lanes] = (state * dec + u).astype(s_scr.dtype)


def _bwd_kernel(x_ref, cum_ref, cumt_ref, dtt_ref, b_ref, bt_ref, c_ref,
                ct_ref, st_ref, dy_ref,
                dx_ref, dcum_ref, dcumt_ref, ddtt_ref, db_ref, dbt_ref,
                dc_ref, ds_scr, cb_scr, dcb_scr, *, g: int, p: int):
    q, w = x_ref.shape[1], g * p
    dtype = x_ref.dtype
    s = pl.program_id(2)
    hps = x_ref.shape[2] // p

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[s] = jnp.zeros(ds_scr.shape[1:], jnp.float32)

    @pl.when(s == 0)
    def _():
        cb_scr[...] = _dot(c_ref[0], b_ref[0], _NT)
        dcb_scr[...] = jnp.zeros(dcb_scr.shape, jnp.float32)
        dcum_ref[0] = jnp.zeros(dcum_ref.shape[1:], jnp.float32)
        dc_ref[0] = jnp.zeros(dc_ref.shape[1:], jnp.float32)
        dbt_ref[0] = jnp.zeros(dbt_ref.shape[1:], jnp.float32)

    cb, tri = cb_scr[...], _tri(q)
    is_last = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    head_of_lane = lax.broadcasted_iota(jnp.int32, (1, w), 1) // p
    head_lane = lax.broadcasted_iota(jnp.int32, (1, cum_ref.shape[2]), 1)
    bt = bt_ref[0].astype(jnp.float32)
    for unit in range(x_ref.shape[2] // w):
        lanes = slice(unit * w, (unit + 1) * w)
        xs = x_ref[0, :, lanes]
        dys = dy_ref[0, :, lanes]
        dys32 = dys.astype(jnp.float32)
        state = st_ref[0, 0, :, lanes].astype(jnp.float32)  # at the start
        ds_next = ds_scr[s, :, lanes]                  # d(state after)
        ds_next_lo = ds_next.astype(dtype)
        z = _dot(c_ref[0], state.astype(dtype), _NN)   # C H_prev
        dx = jnp.zeros((q, w), jnp.float32)
        e = jnp.zeros((q, w), jnp.float32)
        dec = jnp.zeros((1, w), jnp.float32)
        for k in range(g):
            mine = head_of_lane == k
            head = s * hps + unit * g + k
            col, row, drow, last = _head_terms(cum_ref, cumt_ref, dtt_ref,
                                               head, unit * g + k, q)
            lm = jnp.exp(jnp.where(tri, col - row, _OFF))
            cbl = cb * lm
            m = (cbl * drow).astype(dtype)
            dm = _dot(jnp.where(mine, dys, jnp.zeros_like(dys)), xs, _NT)
            und = dm * cbl                     # dM M / delta_j
            t = und * drow
            dcol = jnp.sum(t, axis=1, keepdims=True)
            drow_cum = -jnp.sum(t, axis=0, keepdims=True)
            ddt = jnp.sum(und, axis=0, keepdims=True)
            dcb_scr[...] += dm * lm * drow
            # y's second term, exp(cum) C H_prev
            e_col = jnp.exp(col)
            dcol = dcol + e_col * jnp.sum(
                jnp.where(mine, dys32 * z, 0.0), axis=1, keepdims=True)
            e = jnp.where(mine, e_col, e)
            # the state's update, exp(cum_Q) H_prev + (w x)^T B
            decay = jnp.exp(last - row)
            wrow = decay * drow
            v = _dot(jnp.where(mine, ds_next_lo, jnp.zeros_like(ds_next_lo)),
                     xs, _NT)                                      # [N, Q]
            dbt_ref[0] += v * wrow
            dw = jnp.sum(v * bt, axis=0, keepdims=True)
            ddt = ddt + dw * decay
            dww = dw * wrow
            dec_k = jnp.exp(last)
            dlast = jnp.sum(dww, axis=1, keepdims=True) + dec_k * jnp.sum(
                jnp.where(mine, ds_next * state, 0.0), keepdims=True)
            drow_cum = drow_cum - dww + jnp.where(is_last, dlast, 0.0)
            dx = jnp.where(
                mine, _dot(m, dys, _TN)
                + _dot((bt * wrow).astype(dtype), ds_next_lo, _TN), dx)
            dec = jnp.where(mine, dec_k, dec)
            dcumt_ref[0, 0, unit * g + k:unit * g + k + 1, :] = drow_cum
            ddtt_ref[0, 0, unit * g + k:unit * g + k + 1, :] = ddt
            dcum_ref[0] += jnp.where(head_lane == head, dcol, 0.0)
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        dye = (dys32 * e).astype(dtype)
        dc_ref[0] += _dot(dye, state.astype(dtype), _NT)
        ds_scr[s, :, lanes] = ds_next * dec + _dot(ct_ref[0], dye, _NN)

    @pl.when(s == pl.num_programs(2) - 1)
    def _():
        dcb = dcb_scr[...].astype(dtype)
        dc_ref[0] += _dot(dcb, b_ref[0], _NN)
        db_ref[0] = _dot(dcb, c_ref[0], _TN)


def _specs(x, cum, cumt, b, chunk, slab, rev):
    """Block specs of one chunk (and slab) of each operand kind; ``rev``:
    the grid walks the chunks last to first."""
    nc = x.shape[1] // chunk
    at = (lambda j: nc - 1 - j) if rev else (lambda j: j)
    tok = lambda cols: pl.BlockSpec(  # noqa: E731  [B, L, cols], all slabs
        (1, chunk, cols), lambda i, j, s: (i, at(j), 0))
    return dict(
        x=pl.BlockSpec((1, chunk, slab), lambda i, j, s: (i, at(j), s)),
        cum=tok(cum.shape[2]), b=tok(b.shape[2]),
        bt=pl.BlockSpec((1, b.shape[2], chunk),
                        lambda i, j, s: (i, 0, at(j))),
        headrow=pl.BlockSpec((1, 1, cumt.shape[2], chunk),
                             lambda i, j, s: (i, s, 0, at(j))),
        state=pl.BlockSpec((1, 1, b.shape[2], slab),
                           lambda i, j, s: (i, at(j), 0, s)))


def _slab(hp: int, w: int) -> int:
    """Lanes of x a grid step takes: ``_SLAB``, or all of them."""
    return _SLAB if hp % _SLAB == 0 and _SLAB % w == 0 else hp


@jax.named_scope("dsod.kernel.ssd_scan")
def _fwd_call(x, cum, cumt, dtt, b, bt, c, cfg):
    chunk, g, p, interpret = cfg
    bs, n, hp = x.shape
    nc, ns, slab = n // chunk, b.shape[2], _slab(hp, g * p)
    sp = _specs(x, cum, cumt, b, chunk, slab, rev=False)
    return pl.pallas_call(
        partial(_fwd_kernel, g=g, p=p),
        grid=(bs, nc, hp // slab),
        in_specs=[sp["x"], sp["cum"], sp["headrow"], sp["headrow"], sp["b"],
                  sp["bt"], sp["b"]],
        out_specs=[sp["x"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bs, nc, ns, hp), STATE_DTYPE)],
        scratch_shapes=[pltpu.VMEM((hp // slab, ns, slab), STATE_DTYPE),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * bs * n * (hp // p) * (2 * chunk * g * p
                                            + 3 * ns * g * p),
            transcendentals=bs * n * (hp // p) * chunk,
            bytes_accessed=2 * x.size * x.dtype.itemsize
            + bs * nc * ns * hp * 4),
        interpret=interpret,
    )(x, cum, cumt, dtt, b, bt, c)


@jax.named_scope("dsod.kernel.ssd_scan_bwd")
def _bwd_call(x, cum, cumt, dtt, b, bt, c, ct, states, dy, cfg):
    chunk, g, p, interpret = cfg
    bs, n, hp = x.shape
    ns, slab = b.shape[2], _slab(hp, g * p)
    sp = _specs(x, cum, cumt, b, chunk, slab, rev=True)
    f32 = lambda t: jax.ShapeDtypeStruct(t.shape, jnp.float32)  # noqa: E731
    return pl.pallas_call(
        partial(_bwd_kernel, g=g, p=p),
        grid=(bs, n // chunk, hp // slab),
        in_specs=[sp["x"], sp["cum"], sp["headrow"], sp["headrow"], sp["b"],
                  sp["bt"], sp["b"], sp["bt"], sp["state"], sp["x"]],
        out_specs=[sp["x"], sp["cum"], sp["headrow"], sp["headrow"],
                   sp["b"], sp["bt"], sp["b"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), f32(cum),
                   f32(cumt), f32(dtt), f32(b), f32(bt), f32(c)],
        scratch_shapes=[pltpu.VMEM((hp // slab, ns, slab), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * bs * n * (hp // p) * (4 * chunk * g * p
                                            + 7 * ns * g * p),
            transcendentals=2 * bs * n * (hp // p) * chunk,
            bytes_accessed=3 * x.size * x.dtype.itemsize + states.size * 4),
        interpret=interpret,
    )(x, cum, cumt, dtt, b, bt, c, ct, states, dy)


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def _ssd(x, cum, cumt, dtt, b, bt, c, cfg):
    return _fwd_call(x, cum, cumt, dtt, b, bt, c, cfg)[0]


def _ssd_fwd(x, cum, cumt, dtt, b, bt, c, cfg):
    y, states = _fwd_call(x, cum, cumt, dtt, b, bt, c, cfg)
    n_y, n_states = SSD_RESIDUAL_NAMES
    y = checkpoint_name(y, n_y)
    return y, (x, cum, cumt, dtt, b, bt, c,
               checkpoint_name(states, n_states))


def _ssd_bwd(cfg, res, dy):
    x, cum, cumt, dtt, b, bt, c, states = res
    dx, dcum, dcumt, ddtt, db, dbt, dc = _bwd_call(
        x, cum, cumt, dtt, b, bt, c, jnp.swapaxes(c, 1, 2), states, dy, cfg)
    return (dx, dcum, dcumt, ddtt, db.astype(b.dtype), dbt.astype(bt.dtype),
            dc.astype(c.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, a, b, c, *, chunk: int = 256,
             interpret: bool | None = None):
    """``y_t = H_t C_t`` of the recurrence in the module docstring.

    x: [B, L, H, P]; dt: [B, L, H] (``delta``, already positive); a: [H]
    (negative); b, c: [B, L, N].  Differentiable in all five.  The
    kernels run in the interpreter on the CPU (``interpret`` None)."""
    _check(x, dt, a, b, c, chunk)
    bs, n, h, p = x.shape
    g = _heads_per_unit(h, p)
    interpret = (jax.default_backend() == "cpu" if interpret is None
                 else interpret)
    if not interpret and ((g * p) % _LANES or chunk % _LANES):
        raise ValueError(f"units of {g} heads x {p} columns and a chunk of "
                         f"{chunk} do not fill the chip's 128 lanes")
    dt = dt.astype(jnp.float32)
    cum = _chunk_cumsum(dt * a.astype(jnp.float32), chunk)
    hps = _slab(h * p, g * p) // p          # heads a grid step takes
    head_rows = lambda t: jnp.swapaxes(t, 1, 2).reshape(  # noqa: E731
        bs, h // hps, hps, n)
    y = _ssd(x.reshape(bs, n, h * p), cum, head_rows(cum), head_rows(dt),
             b, jnp.swapaxes(b, 1, 2), c, (chunk, g, p, interpret))
    return y.reshape(x.shape)


# ---------------------------------------------------------------------------
# the same chunked form in plain XLA
# ---------------------------------------------------------------------------

def ssd_scan_xla(x, dt, a, b, c, *, chunk: int = 256):
    """:func:`ssd_scan` without a kernel: a ``lax.scan`` over the chunks,
    each rematerialised, so that one chunk's [H, Q, Q] float32 masks are
    live at a time and the backward keeps the carried states alone."""
    _check(x, dt, a, b, c, chunk)
    bs, n, h, p = x.shape
    dtype = x.dtype
    dt = dt.astype(jnp.float32)
    chunks = lambda t: jnp.moveaxis(  # noqa: E731  -> [chunks, B, Q, ...]
        t.reshape((bs, n // chunk, chunk) + t.shape[2:]), 1, 0)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    ein = partial(jnp.einsum, preferred_element_type=jnp.float32)

    @jax.checkpoint
    def one(state, xs):                  # state [B, H, P, N] float32
        x_c, dt_c, b_c, c_c = xs
        cum = jnp.cumsum(dt_c * a.astype(jnp.float32), axis=1)  # [B, Q, H]
        cum_h = jnp.swapaxes(cum, 1, 2)                         # [B, H, Q]
        lm = jnp.exp(jnp.where(
            tri, cum_h[:, :, :, None] - cum_h[:, :, None, :], _OFF))
        cb = ein("bin,bjn->bij", c_c, b_c)
        m = cb[:, None] * lm * jnp.swapaxes(dt_c, 1, 2)[:, :, None, :]
        y = ein("bhij,bjhp->bihp", m.astype(dtype), x_c)
        y = y + jnp.exp(cum)[..., None] * ein(
            "bin,bhpn->bihp", c_c, state.astype(dtype))
        last = cum[:, -1:, :]
        w = jnp.exp(last - cum) * dt_c                          # [B, Q, H]
        bw = (b_c.astype(jnp.float32)[:, :, None, :]
              * w[..., None]).astype(dtype)                     # [B,Q,H,N]
        new = state * jnp.exp(last[:, 0])[..., None, None] + ein(
            "bjhp,bjhn->bhpn", x_c, bw)
        return new, y.astype(dtype)

    _, y = lax.scan(one, jnp.zeros((bs, h, p, b.shape[2]), jnp.float32),
                    (chunks(x), chunks(dt), chunks(b), chunks(c)))
    return jnp.moveaxis(y, 0, 1).reshape(x.shape)
