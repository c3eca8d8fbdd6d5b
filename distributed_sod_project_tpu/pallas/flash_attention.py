"""Pallas flash attention — the ViT-SOD hot op (SURVEY.md §2.2, §5).

``models/vit_sod.py`` is the long-context zoo member: global attention
over every patch token, quadratic in resolution.  The XLA path
(``parallel/ring_attention.full_attention``) materialises the [N, N]
score matrix in HBM — at 1024px/patch16 that is 4096² floats *per head*
per block, which is exactly the memory wall flash attention exists to
remove.  This kernel computes attention tile-by-tile in VMEM with an
online softmax: HBM traffic is O(N·D) (read q/k/v, write out + one
lse row) instead of O(N²).

Design (mirrors the layout conventions of the other kernels here):

- Heads-major [B, H, N, D] public layout (``ring_attention``'s), folded
  to [B·H, N, D] for the grid.  N is zero-padded to a multiple of the
  128-lane tile; padded KEY columns are masked with a large negative
  bias (never ``-inf`` — a fully-finite path keeps ``exp`` NaN-free),
  padded QUERY rows compute garbage that the wrapper slices off, and
  their zero upstream gradients keep the backward exact.
- Running (m, l) softmax statistics live in VMEM scratch as
  (block_q, 128) lane-replicated tiles (the Mosaic-native layout),
  carried across the innermost KV grid dimension; the accumulator is
  rescaled once per visiting block and divided once at the end.
- The MXU sees three dots per tile pair — q·kᵀ, p·v, and (backward)
  ds·k / dsᵀ·q / pᵀ·do — all with ``preferred_element_type=float32``;
  ``p`` is cast to the value dtype so bf16 inputs ride the MXU at full
  rate.
- Backward is two more kernels (custom VJP, no O(N²) residual): dq
  accumulates over KV blocks; dk/dv swap the grid so the KV block is
  resident while Q blocks stream past.  Both rebuild ``p`` from the
  saved lse row, flash-attention style.  (The two causal variants
  further down fuse them into one kernel each and reduce
  ``delta = Σ do·out`` in-kernel from the streamed do/out tiles.)

Exactness: forward AND gradients match the XLA oracle to float32
round-off (tests/test_pallas_flash.py); the real-TPU Mosaic lowering is
guarded by ``jax.export(platforms=['tpu'])`` tests, same as
fused_ssim/fused_loss.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Large-but-finite mask bias (the official TPU kernels' choice): keeps
# every intermediate finite so exp/max never see -inf - -inf = NaN.
_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def _widen(x, n: int):
    """Lane-replicated (rows, 128) tile -> (rows, n): slice for n < 128,
    tile for multiples of 128 (the Mosaic-proven broadcast pattern)."""
    if n < _LANES:
        return x[:, :n]
    reps, rem = divmod(n, _LANES)
    if rem:
        raise ValueError(f"width {n} not a multiple of {_LANES}")
    return jnp.tile(x, (1, reps)) if reps > 1 else x


def _key_mask_bias(j, bkv: int, bq: int, n: int):
    """(bq, bkv) additive bias masking key columns >= n (padding)."""
    col = lax.broadcasted_iota(jnp.int32, (bq, bkv), 1) + j * bkv
    return jnp.where(col < n, 0.0, _MASK_VALUE).astype(jnp.float32)


def _scores(q_ref, k_ref, blk, *, scale, n, padded):
    """(bq, bkv) masked, scaled logits for one tile pair; ``blk`` is the
    kv-block index the key columns belong to."""
    s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if padded:
        s = s + _key_mask_bias(blk, k_ref.shape[1], q_ref.shape[1], n)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, scale: float, n: int, padded: bool):
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    d = acc_s.shape[1]

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, _MASK_VALUE, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    s = _scores(q_ref, k_ref, j, scale=scale, n=n, padded=padded)

    m_prev = m_s[...]                                   # (bq, 128)
    m_curr = jnp.max(s, axis=1)[:, None]                # (bq, 1)
    m_next = jnp.maximum(m_prev, m_curr)                # (bq, 128)
    p = jnp.exp(s - _widen(m_next, k_ref.shape[1]))     # (bq, bkv)
    corr = jnp.exp(m_prev - m_next)                     # (bq, 128)
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1)[:, None]
    m_s[...] = m_next
    pv = lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                         (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    acc_s[...] = acc_s[...] * _widen(corr, d) + pv

    @pl.when(j == nj - 1)
    def _():
        l_safe = jnp.where(l_s[...] == 0.0, 1.0, l_s[...])
        o_ref[0] = (acc_s[...] / _widen(l_safe, d)).astype(o_ref.dtype)
        lse_ref[0] = m_s[...] + jnp.log(l_safe)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_s, *, scale: float, n: int, padded: bool):
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    s = _scores(q_ref, k_ref, j, scale=scale, n=n, padded=padded)
    p = jnp.exp(s - _widen(lse_ref[0], k_ref.shape[1]))
    do = do_ref[0].astype(jnp.float32)
    dp = lax.dot_general(do, v_ref[0].astype(jnp.float32),
                         (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0][:, :1]) * scale
    dq_s[...] += lax.dot_general(ds.astype(k_ref.dtype), k_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_s, dv_s,
                *, scale: float, n: int, padded: bool):
    i = pl.program_id(1)      # kv block (resident)
    j = pl.program_id(2)      # q block (streams past)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    s = _scores(q_ref, k_ref, i, scale=scale, n=n, padded=padded)
    p = jnp.exp(s - _widen(lse_ref[0], k_ref.shape[1]))
    do = do_ref[0].astype(jnp.float32)
    # dv += pᵀ · do   (contract over the q rows)
    dv_s[...] += lax.dot_general(p.astype(do_ref.dtype), do_ref[0],
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    dp = lax.dot_general(do, v_ref[0].astype(jnp.float32),
                         (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0][:, :1]) * scale
    dk_s[...] += lax.dot_general(ds.astype(q_ref.dtype), q_ref[0],
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# wrappers
# ---------------------------------------------------------------------------


def _pad_n(x, np_):
    pad = np_ - x.shape[1]
    return x if pad == 0 else jnp.pad(x, ((0, 0), (0, pad), (0, 0)))


def _specs(bq, bkv, d, *, kv_resident: bool):
    """BlockSpecs for the two grid orders.  ``kv_resident=False``: grid
    (bh, qi, kj) — q-like blocks follow dim 1, kv-like dim 2.
    ``kv_resident=True``: grid (bh, ki, qj) — swapped."""
    if kv_resident:
        q_ix = lambda b, i, j: (b, j, 0)
        kv_ix = lambda b, i, j: (b, i, 0)
    else:
        q_ix = lambda b, i, j: (b, i, 0)
        kv_ix = lambda b, i, j: (b, j, 0)
    qs = pl.BlockSpec((1, bq, d), q_ix)
    kv = pl.BlockSpec((1, bkv, d), kv_ix)
    row = pl.BlockSpec((1, bq, _LANES), q_ix)
    return qs, kv, row


@jax.named_scope("dsod.kernel.flash_attention")
def _fwd_call(q, k, v, cfg):
    bq, bkv, interpret, n = cfg
    bh, np_, d = q.shape
    qs, kvs, row = _specs(bq, bkv, d, kv_resident=False)
    return pl.pallas_call(
        partial(_fwd_kernel, scale=1.0 / d**0.5, n=n, padded=np_ != n),
        grid=(bh, np_ // bq, np_ // bkv),
        in_specs=[qs, kvs, kvs],
        out_specs=[qs, row],
        out_shape=[jax.ShapeDtypeStruct((bh, np_, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, np_, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * np_ * np_ * d,
            transcendentals=bh * np_ * np_,
            bytes_accessed=4 * q.size * q.dtype.itemsize),
        interpret=interpret,
    )(q, k, v)


@jax.named_scope("dsod.kernel.flash_attention")
def _bwd_call(q, k, v, out, lse_row, do, cfg, dlse=None):
    bq, bkv, interpret, n = cfg
    bh, np_, d = q.shape
    scale = 1.0 / d**0.5
    # delta_i = Σ_d out·do — loop-invariant per query row, so computed
    # ONCE here (one fused XLA pass) and streamed to both kernels as a
    # lane-replicated row tile.  A cotangent on lse folds in exactly
    # here: ∂lse_i/∂s_ij = p_ij, so
    # s̄_ij = p_ij·(dp_ij − delta_i + dlse_i) — i.e. dlse just shifts
    # delta, and the kernels need no second code path.
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None], (bh, np_, _LANES))
    # lse is saved as one lane per row ((bh, np), 1/128th the tile the
    # kernels stream) and re-broadcast here, same as delta.
    lse = jnp.broadcast_to(lse_row[..., None], (bh, np_, _LANES))

    qs, kvs, row = _specs(bq, bkv, d, kv_resident=False)
    dq = pl.pallas_call(
        partial(_dq_kernel, scale=scale, n=n, padded=np_ != n),
        grid=(bh, np_ // bq, np_ // bkv),
        in_specs=[qs, kvs, kvs, qs, row, row],
        out_specs=qs,
        out_shape=jax.ShapeDtypeStruct((bh, np_, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=6 * bh * np_ * np_ * d,
            transcendentals=bh * np_ * np_,
            bytes_accessed=6 * q.size * q.dtype.itemsize),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    qs, kvs, row = _specs(bq, bkv, d, kv_resident=True)
    dk, dv = pl.pallas_call(
        partial(_dkv_kernel, scale=scale, n=n, padded=np_ != n),
        grid=(bh, np_ // bkv, np_ // bq),
        in_specs=[kvs, kvs, qs, qs, row, row],
        out_specs=[kvs, kvs],
        out_shape=[jax.ShapeDtypeStruct((bh, np_, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, np_, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bkv, d), jnp.float32),
                        pltpu.VMEM((bkv, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=10 * bh * np_ * np_ * d,
            transcendentals=bh * np_ * np_,
            bytes_accessed=6 * q.size * q.dtype.itemsize),
        interpret=interpret,
    )(k, v, q, do, lse, delta)
    return dq, dk, dv


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_lse(q, k, v, cfg):
    """The forward+lse primitive ([bh, np] f32 lse — the merge
    statistic ring attention needs; the plain wrapper drops it)."""
    out, lse = _fwd_call(q, k, v, cfg)
    return out, lse[:, :, 0]


def _flash_lse_fwd(q, k, v, cfg):
    out, lse = _fwd_call(q, k, v, cfg)
    # Residuals keep ONE lane of the lane-replicated lse tile — the
    # backward re-broadcasts; holding all 128 copies across the
    # fwd→bwd gap would rival the q/k/v residuals themselves.
    return (out, lse[:, :, 0]), (q, k, v, out, lse[:, :, 0])


def _flash_lse_bwd(cfg, res, gs):
    q, k, v, out, lse_row = res
    g_out, g_lse = gs
    return _bwd_call(q, k, v, out, lse_row, g_out, cfg, dlse=g_lse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _prepare(q, k, v, block_q, block_kv, interpret):
    """Validate, fold heads into batch, pad N; returns folded q/k/v,
    the static kernel cfg, and the original (b, h, n, d)."""
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(f"q/k/v shapes differ: {q.shape} {k.shape} {v.shape}")
    if q.ndim != 4:
        raise ValueError(f"expected [B, H, N, D], got {q.shape}")
    if block_q is None:
        block_q = _env_block("DSOD_FLASH_BLOCK_Q", 128)
    if block_kv is None:
        block_kv = _env_block("DSOD_FLASH_BLOCK_KV", 128)
    b, h, n, d = q.shape
    if d > _LANES and d % _LANES:
        raise ValueError(
            f"head dim {d} unsupported (need <= {_LANES} or a multiple); "
            "use parallel.ring_attention.full_attention")
    if block_q % _LANES or block_kv % _LANES:
        raise ValueError("block sizes must be multiples of 128")
    # Pad to a COMMON multiple of both blocks — rounding to only the
    # larger would leave valid rows uncovered by the floor-divided grid
    # whenever the blocks don't divide each other.
    step = math.lcm(block_q, block_kv)
    np_ = -(-n // step) * step
    interpret = jax.default_backend() == "cpu" if interpret is None else interpret
    cfg = (min(block_q, np_), min(block_kv, np_), interpret, n)
    fold = lambda t: _pad_n(t.reshape(b * h, n, d), np_)
    return fold(q), fold(k), fold(v), cfg, (b, h, n, d)


def _env_block(name: str, default: int) -> int:
    """Block-shape override for on-hardware tuning
    (``DSOD_FLASH_BLOCK_Q`` / ``DSOD_FLASH_BLOCK_KV`` — the knob
    ``tools/bench_flash.py`` sweeps; round-2 v5e measurement showed the
    128/128 default leaves >2x on the table at short N)."""
    from ..utils import envvars

    return envvars.read_int(name, default)


def flash_attention(q, k, v, *, block_q: int | None = None,
                    block_kv: int | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Drop-in for ``ring_attention.full_attention`` (non-causal).

    q/k/v: [B, H, N, D] (any N; zero-padded internally to the 128-lane
    tile), D ≤ 128 or a multiple of 128.  Differentiable via the Pallas
    backward kernels.  ``interpret`` defaults to auto (interpret on
    CPU, Mosaic on TPU).
    """
    qf, kf, vf, cfg, (b, h, n, d) = _prepare(q, k, v, block_q, block_kv,
                                             interpret)
    # Single custom-VJP definition shared with the lse variant: the
    # dropped lse output arrives in the backward as a zero cotangent,
    # which reduces the dlse delta-shift to a no-op subtract.
    out, _ = _flash_lse(qf, kf, vf, cfg)
    return out[:, :n].reshape(b, h, n, d)


def flash_attention_with_lse(q, k, v, *, block_q: int | None = None,
                             block_kv: int | None = None,
                             interpret: bool | None = None):
    """``flash_attention`` that also returns lse ([B, H, N] f32, the
    per-row logsumexp of the scaled scores) — the statistic that makes
    per-block results mergeable, which is how the SP ring composes
    flash blocks (parallel/ring_attention.py).  Both outputs are
    differentiable: an lse cotangent folds into the same backward
    kernels as a shift of delta."""
    qf, kf, vf, cfg, (b, h, n, d) = _prepare(q, k, v, block_q, block_kv,
                                             interpret)
    out, lse = _flash_lse(qf, kf, vf, cfg)
    return (out[:, :n].reshape(b, h, n, d),
            lse[:, :n].reshape(b, h, n))


# ---------------------------------------------------------------------------
# causal, grouped KV heads (the token model's attention, models/lfm2.py)
# ---------------------------------------------------------------------------
#
# Same online-softmax tiling as above with three differences.  (1) One
# square block size, so a tile pair is either wholly below the diagonal
# (no mask), on it (a local row >= col mask), or above it: those are NOT
# IN THE GRID.  The last grid axis runs over the pairs on or under the
# diagonal alone, in the order the accumulators rely on, and every index
# map reads the pair's block indices from scalar-prefetched tables
# (``causal_pairs``: numpy constants made at trace time from the block
# count and the group size).  (2) Hq query heads share Hkv key/value
# heads: the forward's kv index map divides the folded head index by the
# group size G.  (3) delta = sum(out * do) is reduced in-kernel from the
# streamed out/do tiles instead of being broadcast to a lane-replicated
# HBM array; the matmul operands stay in the input dtype (bf16 on the
# chip) with float32 accumulation.  Zero-padded rows past N need no key
# mask of their own: a valid query row never sees a padded (later) key,
# and padded query rows carry zero cotangents.
#
# The forward walks q block i outer and kv block j from 0 up to i: a
# row's statistics start at j == 0 and its output is written at the
# diagonal, its last pair.
#
# The backward is ONE kernel that makes a tile pair's p and ds once and
# takes dq, dk and dv from them (the latent kernel's design below,
# carried to grouped kv heads).  A kv block i is resident while the q
# blocks j = i .. nb - 1 of all G heads of its group stream past, head
# after head (dk, dv: a block-sized float32 accumulator each); dq
# accumulates in float32 VMEM scratch that holds the WHOLE row range of
# the group's G heads.  kv blocks run in ascending order and only blocks
# i <= j touch q block j, so a head's dq block i is complete at the
# diagonal pair (i, i), the first pair of that head kv block i visits: it
# is written there, to an output block the pipeline flushes when the head
# or i moves on, and never goes to HBM as partial sums.  The scoped-VMEM
# limit follows from the shapes (``_causal_bwd_vmem_bytes``); a sequence
# too long for the chip's VMEM raises.

_CAUSAL_BLOCK = 512


def causal_blocks(n: int, block: int | None = None) -> tuple[int, int]:
    """(block size, blocks a side) the causal kernels tile ``n`` rows
    with: one square block of at most 512 rows, ``n`` padded up to it."""
    if block is None:
        block = min(_CAUSAL_BLOCK, -(-n // _LANES) * _LANES)
    if block % _LANES:
        raise ValueError("block must be a multiple of 128")
    return block, -(-n // block)


def causal_pairs(nb: int, group: int = 1, back: int | None = None):
    """The tile pairs on or under the diagonal of ``nb`` blocks a side,
    in the two orders the causal kernels' last grid axis walks them
    (int32 numpy tables, read through scalar prefetch).

    -> (forward, backward).  ``forward = (q block, kv block)``, one entry
    a pair: q block i outer, kv block j = 0 .. i, so a row starts at
    j == 0 and ends on its diagonal.  ``backward = (kv block, head in
    group, q block)``, ``group`` entries a pair: kv block i outer, under
    it each of the group's heads in turn, its q blocks j = i .. nb - 1,
    so every head starts a kv block on the diagonal.

    ``back`` (a sliding window, :func:`band_back`): only the pairs at
    most ``back`` blocks under the diagonal, in the same two orders — a
    row then starts at ``j == max(i - back, 0)`` and a kv block ends at
    q block ``min(i + back, nb - 1)``."""
    rows = np.arange(nb, dtype=np.int32)
    back = nb - 1 if back is None else min(back, nb - 1)
    low = np.maximum(rows - back, 0)         # a q block's first kv block
    ahead = np.minimum(nb - rows, back + 1)  # pairs kv block i visits a head
    fwd = (np.repeat(rows, rows - low + 1),
           np.concatenate([rows[low[i]:i + 1] for i in rows]))
    bwd = (np.repeat(rows, group * ahead),
           np.concatenate([np.repeat(np.arange(group, dtype=np.int32), a)
                           for a in ahead]),
           np.concatenate([np.tile(rows[i:i + ahead[i]], group)
                           for i in rows]))
    return fwd, bwd


def band_back(window: int, block: int) -> int:
    """Blocks under the diagonal a window of ``window`` keys (the query's
    own among them) reaches: tile pair (i, j) holds a visible entry iff
    ``i - back <= j <= i``."""
    if window < 1:
        raise ValueError(f"a window of {window} keys sees nothing")
    return (window + block - 2) // block


def _causal_p(q_ref, k_ref, lse_or_none, *, scale, masked, band=None):
    """One tile pair: the masked, scaled scores (forward: no lse yet),
    or ``p = exp(scores - lse)`` once the row's lse is known.  ``band``
    = (blocks the pair lies under the diagonal, window): the mask of a
    windowed pair, both of its edges (``0 <= row - col < window`` in
    whole-sequence positions)."""
    s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if masked:
        row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if band is None:
            s = jnp.where(col <= row, s, _MASK_VALUE)
        else:
            under, window = band
            gap = row - col + under * s.shape[0]
            s = jnp.where((gap >= 0) & (gap < window), s, _MASK_VALUE)
    if lse_or_none is None:
        return s
    return jnp.exp(s - _widen(lse_or_none, s.shape[1]))


def _band_edges(i, j, window, blk):
    """A windowed pair (q block i, kv block j): (the mask's ``band``,
    whether the pair needs it: the diagonal, and the pairs so far under
    it that the window's far edge cuts them)."""
    return (i - j, window), (j == i) | (i - j >= window // blk)


def _c_fwd_kernel(qb_ref, kb_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                  m_s, l_s, acc_s, *, scale: float, window=None):
    pair = pl.program_id(1)
    i, j = qb_ref[pair], kb_ref[pair]           # q block; kv block <= i
    d = acc_s.shape[1]
    blk = q_ref.shape[1]
    first = 0 if window is None else jnp.maximum(
        i - band_back(window, blk), 0)

    @pl.when(j == first)
    def _():
        m_s[...] = jnp.full(m_s.shape, _MASK_VALUE, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def visit(masked, band=None):
        s = _causal_p(q_ref, k_ref, None, scale=scale, masked=masked,
                      band=band)
        m_prev = m_s[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _widen(m_next, s.shape[1]))
        corr = jnp.exp(m_prev - m_next)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1)[:, None]
        m_s[...] = m_next
        pv = lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_s[...] = acc_s[...] * _widen(corr, d) + pv

    if window is None:
        pl.when(j < i)(lambda: visit(False))
    else:
        # A row the far edge hides all of a pair from leaves m at the
        # mask's value there; the diagonal, which every row sees, then
        # scales that pair's share to exp(mask - m) = 0.
        band, cut = _band_edges(i, j, window, blk)
        pl.when(~cut)(lambda: visit(False))
        pl.when(cut & (j < i))(lambda: visit(True, band))

    @pl.when(j == i)   # the diagonal: the row's last pair
    def _():
        visit(True, None if window is None else (0, window))
        o_ref[0] = (acc_s[...] / _widen(l_s[...], d)).astype(o_ref.dtype)
        lse_ref[0] = m_s[...] + jnp.log(l_s[...])


def _causal_ds(p, q_side, v_ref, *, scale):
    """p * (dp - delta) * scale from the streamed do/out tiles."""
    do_ref, out_ref = q_side
    dp = lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    delta = jnp.sum(out_ref[0].astype(jnp.float32)
                    * do_ref[0].astype(jnp.float32), axis=1, keepdims=True)
    return p * (dp - delta) * scale


def _c_bwd_kernel(kb_ref, head_ref, qb_ref, k_ref, v_ref, q_ref, do_ref,
                  out_ref, lse_ref, dq_ref, dk_ref, dv_ref, dq_s, dk_s, dv_s,
                  *, scale: float, nb: int, group: int, window=None):
    pair = pl.program_id(1)
    # kv block; head in group; q block >= i
    i, g, j = kb_ref[pair], head_ref[pair], qb_ref[pair]
    t = g * nb + j

    @pl.when(pair == 0)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    @pl.when((g == 0) & (j == i))   # the kv block's first pair
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    def visit(masked, band=None):
        p = _causal_p(q_ref, k_ref, lse_ref[0], scale=scale, masked=masked,
                      band=band)
        over_q = (((0,), (0,)), ((), ()))   # contract the pair's q rows
        dv_s[...] += lax.dot_general(p.astype(do_ref.dtype), do_ref[0],
                                     over_q,
                                     preferred_element_type=jnp.float32)
        ds = _causal_ds(p, (do_ref, out_ref), v_ref, scale=scale).astype(
            q_ref.dtype)
        dk_s[...] += lax.dot_general(ds, q_ref[0], over_q,
                                     preferred_element_type=jnp.float32)
        dq_s[t] += lax.dot_general(ds, k_ref[0], (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    last = nb - 1
    if window is None:
        pl.when(j > i)(lambda: visit(False))
    else:
        blk = q_ref.shape[1]
        band, cut = _band_edges(j, i, window, blk)
        pl.when(~cut)(lambda: visit(False))
        pl.when(cut & (j > i))(lambda: visit(True, band))
        last = jnp.minimum(i + band_back(window, blk), last)

    @pl.when(j == i)
    def _():
        visit(True, None if window is None else (0, window))
        # Every kv block <= i has added to this head's q block i by now.
        dq_ref[0] = dq_s[t].astype(dq_ref.dtype)

    @pl.when((g == group - 1) & (j == last))   # ... and its last
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _causal_cfg(cfg, np_):
    """(block, group, interpret, window or None, blocks under the
    diagonal the tables hold or None)."""
    blk, group, interpret, *rest = cfg
    window = rest[0] if rest else None
    return (blk, group, interpret, window,
            None if window is None else band_back(window, blk))


def _band_share(pairs, nb):
    """Share of the triangle's tile pairs a forward table holds."""
    return pairs[0].size / (nb * (nb + 1) // 2)


@jax.named_scope("dsod.kernel.flash_attention_causal")
def _c_fwd_call(q, k, v, cfg):
    bh, np_, d = q.shape
    dv = v.shape[2]                              # a value's own width
    blk, group, interpret, window, back = _causal_cfg(cfg, np_)
    pairs, _ = causal_pairs(np_ // blk, 1, back)
    share = _band_share(pairs, np_ // blk)
    q_ix = lambda b, p, qb, kb: (b, qb[p], 0)  # noqa: E731
    kv_ix = lambda b, p, qb, kb: (b // group, kb[p], 0)  # noqa: E731
    qs = pl.BlockSpec((1, blk, d), q_ix)
    row = pl.BlockSpec((1, blk, _LANES), q_ix)
    return pl.pallas_call(
        partial(_c_fwd_kernel, scale=1.0 / d**0.5, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, pairs[0].size),
            in_specs=[qs, pl.BlockSpec((1, blk, d), kv_ix),
                      pl.BlockSpec((1, blk, dv), kv_ix)],
            out_specs=[pl.BlockSpec((1, blk, dv), q_ix), row],
            scratch_shapes=[pltpu.VMEM((blk, _LANES), jnp.float32),
                            pltpu.VMEM((blk, _LANES), jnp.float32),
                            pltpu.VMEM((blk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bh, np_, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, np_, _LANES), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=int(share * bh * np_ * np_ * (d + dv)),
            transcendentals=int(share * (bh * np_ * np_ // 2)),
            bytes_accessed=(q.size + bh * np_ * dv + k.size + v.size)
            * q.dtype.itemsize),
        interpret=interpret,
    )(*pairs, q, k, v)


def _causal_bwd_vmem_bytes(group, np_, blk, d, itemsize, dv=None):
    """What the fused backward holds in VMEM: the float32 dq accumulator
    of the group's G heads over the whole row range; each operand and
    result tile twice (the pipeline's two buffers: k, q, dq, dk of the
    key's width, v, do, out, dv of the value's); the kv block's two
    accumulators; and room for the float32 score-sized temporaries of one
    tile pair (s, p, dp, ds, their casts and transposes)."""
    d = -(-d // _LANES) * _LANES
    dv = d if dv is None else -(-dv // _LANES) * _LANES
    tiles = 2 * blk * (itemsize * 4 * (d + dv) + 4 * _LANES)
    return (4 * group * np_ * d + tiles + 4 * blk * (d + dv)
            + 12 * 4 * blk * blk)


@jax.named_scope("dsod.kernel.flash_attention_causal_bwd")
def _c_bwd_kernel_call(q, k, v, out, lse, do, cfg):
    """One visit of each tile pair gives dq, dk and dv (the comment that
    heads this section): kv block resident; under it the q blocks from
    the diagonal on of the group's G heads stream past, head after head
    (``causal_pairs``' backward order)."""
    from .vmem_budget import fitted_vmem_params

    bh, np_, d = q.shape
    dv = v.shape[2]
    blk, group, interpret, window, back = _causal_cfg(cfg, np_)
    nb = np_ // blk
    fwd_pairs, pairs = causal_pairs(nb, group, back)
    share = _band_share(fwd_pairs, nb)
    q_ix = lambda b, p, kb, head, qb: (  # noqa: E731
        b * group + head[p], qb[p], 0)
    kv_ix = lambda b, p, kb, head, qb: (b, kb[p], 0)  # noqa: E731
    qs, outs = pl.BlockSpec((1, blk, d), q_ix), pl.BlockSpec((1, blk, dv),
                                                             q_ix)
    row = pl.BlockSpec((1, blk, _LANES), q_ix)
    ks, vs = pl.BlockSpec((1, blk, d), kv_ix), pl.BlockSpec((1, blk, dv),
                                                            kv_ix)
    # dq's finished block (head, block i: written at the diagonal, the
    # head's first pair under kv block i) stays put while that head's q
    # blocks pass and is flushed after them.
    dqs = pl.BlockSpec((1, blk, d), lambda b, p, kb, head, qb: (
        b * group + head[p], kb[p], 0))
    acc = lambda *shape: pltpu.VMEM(shape, jnp.float32)  # noqa: E731
    return pl.pallas_call(
        partial(_c_bwd_kernel, scale=1.0 / d**0.5, nb=nb, group=group,
                window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(k.shape[0], pairs[0].size),
            in_specs=[ks, vs, qs, outs, outs, row],
            out_specs=[dqs, ks, vs],
            scratch_shapes=[acc(group * nb, blk, d), acc(blk, d),
                            acc(blk, dv)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=fitted_vmem_params(
            _causal_bwd_vmem_bytes(group, np_, blk, d, q.dtype.itemsize, dv),
            f"flash_attention_causal's backward over {np_} rows of "
            f"{group} heads a kv head"),
        cost_estimate=pl.CostEstimate(
            flops=int(share * bh * np_ * np_ * (3 * d + 2 * dv)),
            transcendentals=int(share * (bh * np_ * np_ // 2)),
            bytes_accessed=(2 * q.size + 2 * do.size + 2 * k.size
                            + 2 * v.size) * q.dtype.itemsize + 4 * lse.size),
        interpret=interpret,
    )(*pairs, k, v, q, do, out, lse)


def _c_bwd_call(q, k, v, out, lse_row, do, cfg):
    # One scope per pallas_call and nothing else under it: the trace
    # reader counts a kernel's calls by its scope.
    lse = jnp.broadcast_to(lse_row[..., None], q.shape[:2] + (_LANES,))
    return tuple(_c_bwd_kernel_call(q, k, v, out, lse, do, cfg))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_causal(q, k, v, cfg):
    return _c_fwd_call(q, k, v, cfg)[0]


# What a caller's ``jax.checkpoint`` policy may keep so that its backward
# runs neither the forward kernel nor what made q, k, v a second time
# (``save_only_these_names(*CAUSAL_RESIDUAL_NAMES)``; inert otherwise).
CAUSAL_RESIDUAL_NAMES = ("flash_qkv", "flash_out", "flash_lse")


def _flash_causal_fwd(q, k, v, cfg):
    out, lse = _c_fwd_call(q, k, v, cfg)
    # Named HERE, on the residuals the backward kernels read: naming the
    # primal output outside the custom_vjp would leave lse to a second
    # run of the kernel.  q, k, v are named on ALIASES the forward does
    # not use: ``jax.checkpoint`` rounds a saved value the forward also
    # uses through ``reduce_precision`` (against excess precision, which
    # a kernel's operands cannot have), a pass over each array.  ``out``
    # has to be the primal too (the caller's backward reads it) and
    # takes that pass.
    n_qkv, n_out, n_lse = CAUSAL_RESIDUAL_NAMES
    out = checkpoint_name(out, n_out)
    return out, (*(checkpoint_name(t, n_qkv) for t in (q, k, v)), out,
                 checkpoint_name(lse[:, :, 0], n_lse))


def _flash_causal_bwd(cfg, res, g):
    return _c_bwd_call(*res, g, cfg)


_flash_causal.defvjp(_flash_causal_fwd, _flash_causal_bwd)


def flash_attention_causal(q, k, v, *, window: int | None = None,
                           block: int | None = None,
                           interpret: bool | None = None) -> jnp.ndarray:
    """Causal attention with grouped KV heads.

    q: [B, Hq, N, D]; k: [B, Hkv, N, D]; v: [B, Hkv, N, Dv] with Hkv
    dividing Hq (query head h reads kv head h // (Hq / Hkv)); any N
    (zero-padded to the block), D and Dv each <= 128 or a multiple of
    128; the result is Dv wide.  The grid holds only the tile pairs on
    or under the diagonal (``causal_pairs``): one above it is no grid
    step.  ``window``: query i sees keys ``i - window < j <= i`` alone
    (its own among them); the grid then holds only the pairs that band
    touches, and the pairs an edge of it cuts are masked.
    Differentiable: the backward is one Pallas kernel that
    visits each tile pair once for dq, dk and dv, its float32 dq
    accumulators held in VMEM for the whole sequence of a kv head's
    group — a sequence too long for the chip's VMEM raises.
    """
    if k.shape[:3] != v.shape[:3] or q.ndim != 4 or k.ndim != 4 \
            or v.ndim != 4:
        raise ValueError(f"bad q/k/v shapes {q.shape} {k.shape} {v.shape}")
    b, hq, n, d = q.shape
    hkv, dv = k.shape[1], v.shape[3]
    if (b, n, d) != (k.shape[0], k.shape[2], k.shape[3]) or hq % hkv:
        raise ValueError(f"q {q.shape} and k/v {k.shape} do not pair into "
                         "groups of query heads over shared kv heads")
    if any(w > _LANES and w % _LANES for w in (d, dv)):
        raise ValueError(f"head dim {d} / {dv} unsupported")
    block, nb = causal_blocks(n, block)
    np_ = nb * block
    interpret = (jax.default_backend() == "cpu" if interpret is None
                 else interpret)
    fold = lambda t: _pad_n(t.reshape(-1, n, t.shape[3]), np_)  # noqa: E731
    cfg = (block, hq // hkv, interpret)
    if window is not None and window < n:   # (a longer one hides nothing)
        cfg += (int(window),)
    out = _flash_causal(fold(q), fold(k), fold(v), cfg)
    return out[:, :n].reshape(b, hq, n, dv)


# ---------------------------------------------------------------------------
# causal latent attention (the second token model's, models/kimi.py)
# ---------------------------------------------------------------------------
#
# The causal tiling above with the three things multi-head latent
# attention changes.  (1) A key is wider than a value: each head's key
# is ``[k_nope (dn) ; k_rope (dr)]`` against a value of ``dv`` columns,
# and dn + dr (192) is no multiple of the 128 lanes — so the two parts
# arrive as arrays of their own and a tile pair's scores are the SUM of
# two products, one contracting dn columns and one dr.  (2) The rotary
# part of the key is ONE head shared by every query head: its index map
# divides the folded head index by the head count, and the backward
# writes each head's float32 share of ``dk_rope``, which the wrapper
# sums over the heads.  (3) No kv grouping: k_nope and v are per head.
#
# The forward's grid, as above, holds only the pairs on or under the
# diagonal (``causal_pairs``), read from scalar-prefetched tables.
#
# The backward is ONE kernel that makes a tile pair's p and ds once and
# takes dq, dk and dv from them.  A kv block is resident while its
# head's q blocks stream past (dk, dv: a block-sized float32 accumulator
# each, as above); dq accumulates in float32 VMEM scratch that holds the
# WHOLE row range of one (batch, head).  kv blocks run in ascending
# order and only blocks i <= j touch q block j, so its dq is complete at
# the diagonal pair (i, i), the first one kv block i visits: it is
# written there, to an output block the pipeline flushes when i moves
# on, and never goes to HBM as partial sums.  The scoped-VMEM limit
# follows from the shapes (``_mla_bwd_vmem_bytes``).
#
# The backward ALONE keeps the rectangular grid (bh, kv block, q block):
# a pair above the diagonal is a grid step with its compute under a false
# ``pl.when`` and its q-side index maps clamped to the diagonal's block,
# so nothing is copied.  Measured on the chip at the cell's shape
# (PERF.md section 6, PR 45): such a step costs ~0.2 us, but a step whose
# thirteen block specs read their indices from the tables costs ~0.3 us
# MORE than one that computes them from the grid indices (the same
# rectangle walked through tables: 58.09 -> 68.23 ms a call), so the
# pair grid ran this kernel SLOWER (60.04 ms) where the three kernels
# with five to nine specs gained.

MLA_RESIDUAL_NAMES = ("mla_q", "mla_out", "mla_lse")


def _mla_p(qn_ref, qr_ref, kn_ref, kr_ref, lse_or_none, *, scale, masked):
    """One tile pair's masked, scaled scores over both key parts
    (forward: no lse yet), or ``p = exp(scores - lse)``."""
    dims = (((1,), (1,)), ((), ()))
    s = (lax.dot_general(qn_ref[0], kn_ref[0], dims,
                         preferred_element_type=jnp.float32)
         + lax.dot_general(qr_ref[0], kr_ref[0], dims,
                           preferred_element_type=jnp.float32)) * scale
    if masked:
        row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col <= row, s, _MASK_VALUE)
    if lse_or_none is None:
        return s
    return jnp.exp(s - _widen(lse_or_none, s.shape[1]))


def _m_fwd_kernel(qb_ref, kb_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                  o_ref, lse_ref, m_s, l_s, acc_s, *, scale: float):
    pair = pl.program_id(1)
    i, j = qb_ref[pair], kb_ref[pair]           # q block; kv block <= i
    d = acc_s.shape[1]

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, _MASK_VALUE, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def visit(masked):
        s = _mla_p(qn_ref, qr_ref, kn_ref, kr_ref, None, scale=scale,
                   masked=masked)
        m_prev = m_s[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _widen(m_next, s.shape[1]))
        corr = jnp.exp(m_prev - m_next)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1)[:, None]
        m_s[...] = m_next
        pv = lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_s[...] = acc_s[...] * _widen(corr, d) + pv

    pl.when(j < i)(lambda: visit(False))

    @pl.when(j == i)   # the diagonal: the row's last pair
    def _():
        visit(True)
        o_ref[0] = (acc_s[...] / _widen(l_s[...], d)).astype(o_ref.dtype)
        lse_ref[0] = m_s[...] + jnp.log(l_s[...])


def _m_bwd_kernel(kn_ref, kr_ref, v_ref, qn_ref, qr_ref, do_ref, out_ref,
                  lse_ref, dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                  dqn_s, dqr_s, dkn_s, dkr_s, dv_s, *, scale: float):
    i, j = pl.program_id(1), pl.program_id(2)   # kv block; q block

    @pl.when((i == 0) & (j == 0))
    def _():
        dqn_s[...] = jnp.zeros(dqn_s.shape, jnp.float32)
        dqr_s[...] = jnp.zeros(dqr_s.shape, jnp.float32)

    @pl.when(j == 0)
    def _():
        dkn_s[...] = jnp.zeros(dkn_s.shape, jnp.float32)
        dkr_s[...] = jnp.zeros(dkr_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    def visit(masked):
        p = _mla_p(qn_ref, qr_ref, kn_ref, kr_ref, lse_ref[0], scale=scale,
                   masked=masked)
        over_q = (((0,), (0,)), ((), ()))   # contract the pair's q rows
        over_k = (((1,), (0,)), ((), ()))   # ... its kv rows
        dv_s[...] += lax.dot_general(p.astype(do_ref.dtype), do_ref[0],
                                     over_q,
                                     preferred_element_type=jnp.float32)
        ds = _causal_ds(p, (do_ref, out_ref), v_ref, scale=scale).astype(
            qn_ref.dtype)
        dkn_s[...] += lax.dot_general(ds, qn_ref[0], over_q,
                                      preferred_element_type=jnp.float32)
        dkr_s[...] += lax.dot_general(ds, qr_ref[0], over_q,
                                      preferred_element_type=jnp.float32)
        dqn_s[j] += lax.dot_general(ds, kn_ref[0], over_k,
                                    preferred_element_type=jnp.float32)
        dqr_s[j] += lax.dot_general(ds, kr_ref[0], over_k,
                                    preferred_element_type=jnp.float32)

    pl.when(j > i)(lambda: visit(False))

    @pl.when(j == i)
    def _():
        visit(True)
        # Every kv block <= i has added to q block i by now.
        dqn_ref[0] = dqn_s[i].astype(dqn_ref.dtype)
        dqr_ref[0] = dqr_s[i].astype(dqr_ref.dtype)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dkn_ref[0] = dkn_s[...].astype(dkn_ref.dtype)
        dkr_ref[0] = dkr_s[...]
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _mla_grid(qn, qr, v, cfg, *, kv_resident: bool):
    """-> (grid, tables, scale, specs).  The forward's grid is (bh,
    pairs) with the two tables of ``causal_pairs``' forward order for
    scalar prefetch; the backward's, ``kv_resident``, is (bh, kv block, q
    block) with no table, a q block above the diagonal clamped to the
    diagonal's so that it costs no DMA.  The BlockSpecs: a q-side tile of
    each width, a kv-side one, the shared rotary key's and the lse
    row's."""
    blk, heads, _ = cfg
    bh, np_, dn = qn.shape
    dr, dv = qr.shape[2], v.shape[2]
    if np_ % blk:  # a floor-divided grid would leave rows unwritten
        raise ValueError(f"block {blk} does not divide the padded length "
                         f"{np_}")
    nb = np_ // blk
    if kv_resident:
        grid, tables = (bh, nb, nb), ()
        q_of = lambda i, j: jnp.maximum(j, i)  # noqa: E731
        k_of = lambda i, j: i  # noqa: E731
    else:
        tables, _ = causal_pairs(nb)
        grid = (bh, tables[0].size)
        q_of = lambda p, qb, kb: qb[p]  # noqa: E731
        k_of = lambda p, qb, kb: kb[p]  # noqa: E731
    q_ix = lambda b, *at: (b, q_of(*at), 0)  # noqa: E731
    k_ix = lambda b, *at: (b, k_of(*at), 0)  # noqa: E731
    kr_ix = lambda b, *at: (b // heads, k_of(*at), 0)  # noqa: E731
    spec = lambda d, ix: pl.BlockSpec((1, blk, d), ix)  # noqa: E731
    return grid, tables, 1.0 / (dn + dr) ** 0.5, dict(
        qn=spec(dn, q_ix), qr=spec(dr, q_ix), qv=spec(dv, q_ix),
        row=spec(_LANES, q_ix), kn=spec(dn, k_ix), kr=spec(dr, kr_ix),
        kv=spec(dv, k_ix), kr_own=spec(dr, k_ix))


def _mla_pairs(qn, qr, v):
    """(score elements on or under the diagonal, key width, value
    width), for the kernels' cost estimates."""
    bh, np_, dn = qn.shape
    return bh * np_ * np_ // 2, dn + qr.shape[2], v.shape[2]


@jax.named_scope("dsod.kernel.flash_attention_mla")
def _m_fwd_call(qn, qr, kn, kr, v, cfg):
    grid, tabs, scale, s = _mla_grid(qn, qr, v, cfg, kv_resident=False)
    bh, np_, _ = qn.shape
    blk, dv = cfg[0], v.shape[2]
    pairs, dk, _ = _mla_pairs(qn, qr, v)
    return pl.pallas_call(
        partial(_m_fwd_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tabs),
            grid=grid,
            in_specs=[s["qn"], s["qr"], s["kn"], s["kr"], s["kv"]],
            out_specs=[s["qv"], s["row"]],
            scratch_shapes=[pltpu.VMEM((blk, _LANES), jnp.float32),
                            pltpu.VMEM((blk, _LANES), jnp.float32),
                            pltpu.VMEM((blk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bh, np_, dv), qn.dtype),
                   jax.ShapeDtypeStruct((bh, np_, _LANES), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * pairs * (dk + dv), transcendentals=pairs,
            bytes_accessed=(2 * qn.size + 2 * v.size) * qn.dtype.itemsize),
        interpret=cfg[2],
    )(*tabs, qn, qr, kn, kr, v)


def _mla_bwd_vmem_bytes(np_, blk, widths, itemsize):
    """What the fused backward holds in VMEM: the whole row range's two
    float32 dq accumulators; each operand and result tile twice (the
    pipeline's two buffers); the kv block's three accumulators; and room
    for the float32 score-sized temporaries of one tile pair (s, p, dp,
    ds, their casts and transposes)."""
    dn, dr, dv = (-(-d // _LANES) * _LANES for d in widths)
    acc = 4 * np_ * (dn + dr)
    tiles = 2 * blk * (itemsize * (4 * dn + 3 * dr + 4 * dv)
                       + 4 * (_LANES + dr))
    return acc + tiles + 4 * blk * (dn + dr + dv) + 12 * 4 * blk * blk


@jax.named_scope("dsod.kernel.flash_attention_mla_bwd")
def _m_bwd_kernel_call(qn, qr, kn, kr, v, out, lse, do, cfg):
    """One visit of each tile pair gives dq, dk and dv (the comment that
    heads this section).  The shared rotary key's cotangent comes out
    per head, float32 (summed over the heads by the caller)."""
    from .vmem_budget import fitted_vmem_params

    grid, _, scale, s = _mla_grid(qn, qr, v, cfg, kv_resident=True)
    blk, nb = cfg[0], grid[1]
    np_, dn = qn.shape[1:]
    dr, dv = qr.shape[2], v.shape[2]
    pairs = _mla_pairs(qn, qr, v)[0]
    acc = lambda *shape: pltpu.VMEM(shape, jnp.float32)  # noqa: E731
    return pl.pallas_call(
        partial(_m_bwd_kernel, scale=scale),
        grid=grid,
        in_specs=[s["kn"], s["kr"], s["kv"], s["qn"], s["qr"], s["qv"],
                  s["qv"], s["row"]],
        # dq's finished block (i, written at the diagonal) rides the kv
        # block's index map: the kv-side specs of its two widths.
        out_specs=[s["kn"], s["kr_own"], s["kn"], s["kr_own"], s["kv"]],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct(qr.shape, qr.dtype),
                   jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                   jax.ShapeDtypeStruct(qr.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[acc(nb, blk, dn), acc(nb, blk, dr), acc(blk, dn),
                        acc(blk, dr), acc(blk, dv)],
        compiler_params=fitted_vmem_params(
            _mla_bwd_vmem_bytes(np_, blk, (dn, dr, dv), qn.dtype.itemsize),
            f"flash_attention_mla's backward over {np_} rows"),
        cost_estimate=pl.CostEstimate(
            flops=2 * pairs * (3 * (dn + dr) + 2 * dv),
            transcendentals=pairs,
            bytes_accessed=4 * (qn.size + qr.size + v.size)
            * qn.dtype.itemsize + 4 * lse.size),
        interpret=cfg[2],
    )(kn, kr, v, qn, qr, do, out, lse)


def _m_bwd_call(qn, qr, kn, kr, v, out, lse_row, do, cfg):
    # One scope per pallas_call and nothing else under it (the trace
    # reader counts a kernel's calls by its scope).
    lse = jnp.broadcast_to(lse_row[..., None], qn.shape[:2] + (_LANES,))
    dqn, dqr, dkn, dkr_heads, dv = _m_bwd_kernel_call(
        qn, qr, kn, kr, v, out, lse, do, cfg)
    dkr = jnp.sum(dkr_heads.reshape((kr.shape[0], -1) + kr.shape[1:]),
                  axis=1).astype(kr.dtype)
    return dqn, dqr, dkn, dkr, dv


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash_mla(qn, qr, kn, kr, v, cfg):
    return _m_fwd_call(qn, qr, kn, kr, v, cfg)[0]


def _flash_mla_fwd(qn, qr, kn, kr, v, cfg):
    out, lse = _m_fwd_call(qn, qr, kn, kr, v, cfg)
    # As for the causal kernel: out and lse are named on the residuals,
    # q on aliases the forward does not use.  The keys and values are
    # NOT named: they are an up-projection of a latent 8 x narrower,
    # which a caller keeps or makes again (models/kimi.py).
    n_q, n_out, n_lse = MLA_RESIDUAL_NAMES
    out = checkpoint_name(out, n_out)
    return out, (checkpoint_name(qn, n_q), checkpoint_name(qr, n_q), kn, kr,
                 v, out, checkpoint_name(lse[:, :, 0], n_lse))


def _flash_mla_bwd(cfg, res, g):
    return _m_bwd_call(*res, g, cfg)


_flash_mla.defvjp(_flash_mla_fwd, _flash_mla_bwd)


def flash_attention_mla(q_nope, q_rope, k_nope, k_rope, v, *,
                        block: int | None = None,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Causal attention whose keys are ``[k_nope ; k_rope]`` with ONE
    rotary key head for all query heads, and whose values have a width
    of their own.

    q_nope, k_nope: [B, H, N, dn]; q_rope: [B, H, N, dr]; k_rope:
    [B, N, dr]; v: [B, H, N, dv] -> [B, H, N, dv].  Scores are
    ``(q_nope . k_nope + q_rope . k_rope) / sqrt(dn + dr)``.  Any N
    (zero-padded to the block); each width <= 128 or a multiple of 128.
    The forward's grid holds only the tile pairs on or under the diagonal
    (``causal_pairs``).  Differentiable: the backward is one Pallas
    kernel that visits each tile pair once for dq, dk and dv (its grid is
    the rectangle, the pairs above the diagonal skipped), its float32 dq
    accumulators held in VMEM for the whole sequence of a head — a
    sequence too long for the chip's VMEM raises.
    """
    b, h, n, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], v.shape[-1]
    if (q_rope.shape != (b, h, n, dr) or k_nope.shape != q_nope.shape
            or k_rope.shape != (b, n, dr) or v.shape != (b, h, n, dv)):
        raise ValueError(
            f"bad latent-attention shapes: q {q_nope.shape} {q_rope.shape} "
            f"k {k_nope.shape} {k_rope.shape} v {v.shape}")
    for d in (dn, dr, dv):
        if d > _LANES and d % _LANES:
            raise ValueError(f"head width {d} unsupported")
    block, nb = causal_blocks(n, block)
    np_ = nb * block
    interpret = (jax.default_backend() == "cpu" if interpret is None
                 else interpret)
    fold = lambda t: _pad_n(t.reshape(-1, n, t.shape[-1]), np_)  # noqa: E731
    out = _flash_mla(fold(q_nope), fold(q_rope), fold(k_nope), fold(k_rope),
                     fold(v), (block, h, interpret))
    return out[:, :n].reshape(b, h, n, dv)
