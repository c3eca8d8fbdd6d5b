"""Ring attention vs single-device oracle (SURVEY.md §4 distributed tier)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sod_project_tpu.configs.base import MeshConfig
from distributed_sod_project_tpu.parallel.mesh import make_mesh
from distributed_sod_project_tpu.parallel.ring_attention import (
    full_attention, make_ring_attention_fn)


def _qkv(rng, b=2, h=4, n=32, d=16, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, (b, h, n, d), dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full_attention(eight_devices, causal):
    mesh = make_mesh(MeshConfig(data=1, model=1, seq=8), eight_devices)
    q, k, v = _qkv(jax.random.key(0))
    ring = make_ring_attention_fn(mesh, causal=causal)
    out = ring(q, k, v)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_flash_matches_full_attention(eight_devices):
    """attn_impl='flash': per-block Pallas kernel + lse merge across
    the ring is exact vs the single-device oracle — fwd AND grads."""
    mesh = make_mesh(MeshConfig(data=1, model=1, seq=4), eight_devices[:4])
    q, k, v = _qkv(jax.random.key(0), n=64)
    ring = make_ring_attention_fn(mesh, attn_impl="flash")
    np.testing.assert_allclose(np.asarray(ring(q, k, v)),
                               np.asarray(full_attention(q, k, v)),
                               atol=2e-6)

    cot = jax.random.normal(jax.random.key(7), q.shape)
    g_fl = jax.grad(lambda *a: jnp.sum(ring(*a) * cot),
                    argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(full_attention(*a) * cot),
                     argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6, err_msg=f"d{name}")


def test_ring_flash_bf16(eight_devices):
    """The production default is compute_dtype=bfloat16: per-block
    kernel outputs round to bf16 before the f32 lse merge — cover that
    numeric path against the f32 oracle at bf16 tolerance."""
    mesh = make_mesh(MeshConfig(data=1, model=1, seq=4), eight_devices[:4])
    q, k, v = _qkv(jax.random.key(2), n=64, dtype=jnp.bfloat16)
    ring = make_ring_attention_fn(mesh, attn_impl="flash")
    out = ring(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=3e-2)


def test_ring_flash_rejects_causal(eight_devices):
    mesh = make_mesh(MeshConfig(data=1, model=1, seq=4), eight_devices[:4])
    ring = make_ring_attention_fn(mesh, causal=True, attn_impl="flash")
    q, k, v = _qkv(jax.random.key(0), n=64)
    with pytest.raises(ValueError, match="causal"):
        ring(q, k, v)


def test_ring_attention_seq4_uneven_heads(eight_devices):
    # seq=4 ring on the first 4 devices, non-power-of-two head count.
    mesh = make_mesh(MeshConfig(data=1, model=1, seq=4), eight_devices[:4])
    q, k, v = _qkv(jax.random.key(1), b=1, h=3, n=16, d=8)
    out = make_ring_attention_fn(mesh)(q, k, v)
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_bf16_inputs(eight_devices):
    mesh = make_mesh(MeshConfig(data=1, model=1, seq=8), eight_devices)
    q, k, v = _qkv(jax.random.key(2), dtype=jnp.bfloat16)
    out = make_ring_attention_fn(mesh)(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = full_attention(q, k, v)
    # bf16 tolerance: accumulation is f32, rounding only on store.
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)


def test_ring_attention_grads_finite(eight_devices):
    from distributed_sod_project_tpu.parallel.ring_attention import (
        ring_attention)
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(MeshConfig(data=1, model=1, seq=8), eight_devices)
    q, k, v = _qkv(jax.random.key(3), b=1, h=2, n=16, d=8)
    spec = P(None, None, "seq", None)

    def loss(q, k, v):
        out = ring_attention(q, k, v, axis_name="seq")
        return jnp.sum(out ** 2)

    # Grad through shard_map: psum of local losses.
    def global_loss(q, k, v):
        f = jax.shard_map(
            lambda a, b, c: jax.lax.psum(loss(a, b, c), "seq"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=P(),
            check_vma=False)
        return f(q, k, v)

    grads = jax.jit(jax.grad(global_loss, argnums=(0, 1, 2)))(q, k, v)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))
        assert float(jnp.abs(g).max()) > 0
