"""Ring attention — sequence-parallel exact attention over the ``seq`` axis.

Long-context support (SURVEY.md §5 notes the 320×320 CNN zoo never needs
a sequence axis; this module exists so the transformer path — Swin-SOD
at high resolution, or any future ViT-style member — scales past
single-chip memory the TPU-native way, per PAPERS.md's blockwise /
ring-attention lineage).

Design (TPU-first):
- Each of the ``seq`` devices holds one contiguous block of queries,
  keys and values.  K/V blocks rotate around the ring with
  ``lax.ppermute`` (a pure ICI neighbour exchange — no all-gather, so
  per-chip memory stays O(N/n)) while every device accumulates its
  queries' attention over each visiting block.
- Numerically stable online softmax (running max / numerator /
  denominator, flash-attention style) in float32, inputs bf16-friendly.
- The loop is ``lax.fori_loop`` with a statically-known permutation, so
  XLA overlaps each block's einsum with the next ppermute (compute
  hides the communication, the standard ring-attention win).

Exactness: for any block partition, the result equals full softmax
attention — verified in tests against a single-device oracle.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def resolve_attn_fn(attn_impl: str, causal: bool = False):
    """The single attn_impl → dense-attention-callable dispatch, shared
    by ``ulysses_attention``, ``ViTSOD``'s default core, and (for
    validation) the ring: 'xla' materializes scores, 'flash' is the
    Pallas kernel (non-causal only).  Raises the one canonical error
    for anything else."""
    if attn_impl == "flash":
        if causal:
            raise ValueError(
                "attn_impl='flash' has no causal mask; use the xla core")
        from ..pallas.flash_attention import flash_attention

        return flash_attention
    if attn_impl == "xla":
        return partial(full_attention, causal=causal) if causal \
            else full_attention
    raise ValueError(
        f"attn_impl must be 'xla' or 'flash', got {attn_impl!r}")


def _block_attend(q, k, v, *, scale, mask=None):
    """One block pair: returns (numerator, denominator, block_max).

    q: [B,H,Nq,D]; k/v: [B,H,Nk,D] → num [B,H,Nq,D], den/max [B,H,Nq].
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    # All-masked rows: keep the running stats neutral (exp(-inf)=0).
    m_safe = jnp.where(jnp.isfinite(m), m, -jnp.inf)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
    num = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    den = jnp.sum(p, axis=-1)
    return num, den, m_safe


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "seq",
    causal: bool = False,
    attn_impl: str = "xla",
) -> jnp.ndarray:
    """Exact attention with K/V ring-rotated over ``axis_name``.

    Call inside ``shard_map`` with the sequence dim sharded over
    ``axis_name``.  q/k/v: [B, H, N_local, D] (heads-major NHD layout);
    returns [B, H, N_local, D] in q's dtype.

    ``causal`` masks by *global* position: block offsets are derived
    from ``lax.axis_index``, so tokens attend only to global positions
    ≤ their own.

    ``attn_impl='flash'`` computes each visiting block pair with the
    Pallas flash kernel (O(N_local·D) HBM per step instead of a
    materialized N_local² score tile) and merges per-block
    (out, lse) results — composition of the two memory levers: shard
    the sequence over chips, then tile it through VMEM within each.
    Non-causal only (the kernel has no causal mask).
    """
    resolve_attn_fn(attn_impl, causal=causal)  # one shared validation
    if attn_impl == "flash":
        return _ring_flash(q, k, v, axis_name)
    n_blocks = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    qf = q.astype(jnp.float32)
    n_local = q.shape[2]

    perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]

    def causal_mask(src_idx):
        # [Nq, Nk] of "query global pos >= key global pos".
        q_pos = my_idx * n_local + jnp.arange(n_local)[:, None]
        k_pos = src_idx * n_local + jnp.arange(n_local)[None, :]
        return (q_pos >= k_pos)[None, None]  # broadcast over B,H

    def fold(i, k_blk, v_blk, num, den, m):
        # Block i arrived from device (my_idx - i) around the ring.
        src = (my_idx - i) % n_blocks
        mask = causal_mask(src) if causal else None
        b_num, b_den, b_max = _block_attend(qf, k_blk, v_blk,
                                            scale=scale, mask=mask)
        new_m = jnp.maximum(m, b_max)
        corr_old = jnp.exp(m - new_m)
        corr_new = jnp.exp(b_max - new_m)
        num = num * corr_old[..., None] + b_num * corr_new[..., None]
        den = den * corr_old + b_den * corr_new
        return num, den, new_m

    def body(i, carry):
        k_blk, v_blk, num, den, m = carry
        num, den, m = fold(i, k_blk, v_blk, num, den, m)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, num, den, m

    b, h, _, d = q.shape
    init = (
        k, v,
        jnp.zeros((b, h, n_local, d), jnp.float32),
        jnp.zeros((b, h, n_local), jnp.float32),
        jnp.full((b, h, n_local), -jnp.inf, jnp.float32),
    )
    # Rotate only n_blocks-1 times: the last visiting block is folded
    # in outside the loop — its ppermute result would be discarded, and
    # a collective can't be DCE'd, so it would be pure wasted ICI.
    k_l, v_l, num, den, m = lax.fori_loop(0, n_blocks - 1, body, init)
    num, den, m = fold(n_blocks - 1, k_l, v_l, num, den, m)
    out = num / jnp.maximum(den, 1e-30)[..., None]
    # Rows that attended to nothing (fully masked) return zeros.
    out = jnp.where(jnp.isfinite(m)[..., None], out, 0.0)
    return out.astype(q.dtype)


def _ring_flash(q, k, v, axis_name: str) -> jnp.ndarray:
    """Flash-kernel ring body: each visiting K/V block is attended with
    ``pallas.flash_attention_with_lse`` and folded into the running
    result by lse-weighted merge — algebraically the same online
    softmax as the xla body, just with the per-block inner loop pushed
    into VMEM.  Exact vs ``full_attention`` (tests)."""
    from ..pallas.flash_attention import flash_attention_with_lse

    n_blocks = lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]
    b, h, n_local, d = q.shape

    def fold(k_blk, v_blk, out, lse):
        o_b, lse_b = flash_attention_with_lse(q, k_blk, v_blk)
        m = jnp.maximum(lse, lse_b)
        w_prev = jnp.exp(lse - m)          # 0 on the first visit
        w_blk = jnp.exp(lse_b - m)
        den = w_prev + w_blk
        out = (out * w_prev[..., None]
               + o_b.astype(jnp.float32) * w_blk[..., None]) / den[..., None]
        return out, m + jnp.log(den)

    def body(i, carry):
        k_blk, v_blk, out, lse = carry
        out, lse = fold(k_blk, v_blk, out, lse)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, out, lse

    init = (k, v,
            jnp.zeros((b, h, n_local, d), jnp.float32),
            jnp.full((b, h, n_local), -jnp.inf, jnp.float32))
    # Same n_blocks-1 rotation structure as the xla body: the final
    # visiting block folds in without a dead trailing ppermute.
    k_l, v_l, out, lse = lax.fori_loop(0, n_blocks - 1, body, init)
    out, _ = fold(k_l, v_l, out, lse)
    return out.astype(q.dtype)


def full_attention(q, k, v, causal: bool = False) -> jnp.ndarray:
    """Single-device oracle with the same [B,H,N,D] layout."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        n, kn = q.shape[2], k.shape[2]
        mask = jnp.arange(n)[:, None] >= jnp.arange(kn)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def make_ring_attention_fn(mesh, causal: bool = False,
                           attn_impl: str = "xla"):
    """jit(jax.shard_map(...)) wrapper: global [B,H,N,D] arrays sharded on
    N over the mesh's ``seq`` axis; drop-in replacement for
    ``full_attention`` at pod scale."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, "seq", None)

    def fn(q, k, v):
        return ring_attention(q, k, v, axis_name="seq", causal=causal,
                              attn_impl=attn_impl)

    sharded = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)
    return jax.jit(sharded)
