"""Ulysses sequence parallelism — all-to-all head redistribution.

The second SP strategy next to ``ring_attention`` (SURVEY.md §2.3/§5
long-context).  Where the ring rotates K/V blocks around the ``seq``
axis (n_blocks-1 neighbour ppermutes, score tiles never leave the
chip), Ulysses re-shards ONCE: an all-to-all converts
sequence-sharding into head-sharding, every device then attends the
FULL sequence for its subset of heads, and a second all-to-all
converts back.  Trade-offs, honestly:

- ring: any head count, O(blocks) exchanges that overlap with compute,
  per-step traffic 2·(N/s)·D·(s−1)/s per head — the right shape when
  ICI latency hides under per-block compute.
- ulysses: exactly two all-to-alls (lower latency at moderate ``seq``),
  but needs ``heads % seq == 0``, and each device holds the full
  sequence for H/s heads — activation memory O(N·H/s·D), same total as
  the ring.  The full-length sequence per head is also the best shape
  for the Pallas flash kernel (long q/kv tiles instead of ring-block
  slivers), so ``attn_impl='flash'`` composes here too.

Both are exact: outputs equal single-device full attention to fp
round-off (tests/test_ulysses.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .ring_attention import resolve_attn_fn


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "seq",
    causal: bool = False,
    attn_impl: str = "xla",
) -> jnp.ndarray:
    """All-to-all sequence-parallel exact attention.

    Call inside ``shard_map`` with the sequence dim sharded over
    ``axis_name``.  q/k/v: [B, H, N_local, D] (heads-major, the
    ``ring_attention`` layout); returns the same shape/dtype.
    Requires ``H % axis_size == 0``.
    """
    s = lax.axis_size(axis_name)
    h = q.shape[1]
    if h % s:
        raise ValueError(
            f"ulysses needs heads % seq == 0, got heads={h} seq={s} "
            "(use the ring strategy for non-dividing head counts)")

    def to_heads(t):
        # [B, H, N/s, D] -> [B, H/s, N, D]; all_to_all concatenates in
        # source-device order, so global token order is preserved.
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_seq(t):
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qg, kg, vg = to_heads(q), to_heads(k), to_heads(v)
    og = resolve_attn_fn(attn_impl, causal=causal)(qg, kg, vg)
    return to_seq(og)


def make_ulysses_attention_fn(mesh, causal: bool = False,
                              attn_impl: str = "xla"):
    """jit(jax.shard_map(...)) wrapper mirroring
    ``ring_attention.make_ring_attention_fn``."""
    import jax
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, "seq", None)

    def fn(q, k, v):
        return ulysses_attention(q, k, v, axis_name="seq", causal=causal,
                                 attn_impl=attn_impl)

    sharded = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)
    return jax.jit(sharded)
