"""The Mamba-2 mixer's depthwise causal conv + bias + SiLU kernel pair
(pallas/causal_conv.py, interpret mode on the CPU) against the XLA form
``silu(conv(x.astype(f32)) + bias).astype(dtype)`` and ``jax.vjp`` of it:

- forward and all three cotangents (dx, dk, dbias) at float32 operands
  (tight) and bfloat16 operands (forward within one bfloat16 ulp), 3 and
  4 taps, over several token tiles AND several column tiles AND several
  pieces a tile;
- the same with the halo dropped at every tile's edge is far off (the
  planted fault);
- zeros left of token 0; batch rows on their own; one tile only (the
  128-token trace that declares a token model's parameters);
- a length no tile divides raises; lanes the chip cannot fill raise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sod_project_tpu.pallas import causal_conv as cc

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 32 tokens x 128 columns in pieces of 16 rows: [2, 128,
    384] is 2 x 3 x 4 grid steps of two pieces each."""
    monkeypatch.setattr(cc, "_LANES", 128)
    monkeypatch.setattr(cc, "_ROWS", 16)
    monkeypatch.setattr(cc, "_BUDGET", cc._vmem_bytes(32, 128, 4))


def _args(dtype, taps, b=2, n=128, d=384, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (b, n, d)).astype(dtype)
    k = jax.random.normal(ks[1], (taps, d)) * 0.5
    bias = jax.random.normal(ks[2], (d,)) * 0.3
    return (x, k, bias), jax.random.normal(ks[3], (b, n, d)).astype(dtype)


def _rel(u, v):
    u, v = u.astype(F32), v.astype(F32)
    return float(jnp.max(jnp.abs(u - v)) / jnp.max(jnp.abs(v)))


def _ulps(u, v):
    """Largest distance in units of v's last bfloat16 place."""
    u, v = u.astype(F32), v.astype(F32)
    ulp = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(jnp.abs(v), 1e-30))) - 7)
    return float(jnp.max(jnp.abs(u - v) / ulp))


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_forward_and_all_three_cotangents_match_the_xla_form(
        small_tiles, dtype, taps):
    args, g = _args(dtype, taps)
    assert cc._tiles(128, 384, dtype) == (32, 128)
    want, vjp = jax.vjp(cc.causal_conv_silu_xla, *args)
    got, vjp_got = jax.vjp(cc.causal_conv_silu, *args)
    assert got.dtype == dtype
    if dtype == F32:
        assert _rel(got, want) < 1e-6
    else:
        assert _ulps(got, want) <= 1.0
    tol = {"x": 1e-6 if dtype == F32 else 2.0 ** -7, "k": 2e-6, "bias": 2e-6}
    for (name, t), u, v in zip(tol.items(), vjp_got(g), vjp(g)):
        assert u.dtype == v.dtype and u.shape == v.shape, name
        assert _rel(u, v) < t, name
    # the planted fault: each tile convolved as a sequence of its own (the
    # halo dropped at every tile's edge) is far off
    x, k, bias = args
    alone = cc.causal_conv_silu(x.reshape(-1, 32, 384), k, bias).reshape(
        x.shape)
    assert _rel(alone, want) > 0.1
    rows = jnp.arange(128) % 32 >= taps - 1     # ... at the edges alone
    assert _rel(alone[:, rows], want[:, rows]) < 1e-2


def test_zeros_left_of_the_first_token(small_tiles):
    """Ones through taps of one: token t sums min(t + 1, L) of them, in
    every batch row and column tile (the grid walks them one after
    another through the same scratch)."""
    x = jnp.ones((2, 64, 256), F32)
    y = cc.causal_conv_silu(x, jnp.ones((4, 256), F32), jnp.zeros(256, F32))
    pre = jnp.minimum(jnp.arange(64) + 1.0, 4.0)
    np.testing.assert_allclose(
        np.asarray(y), np.broadcast_to(np.asarray(pre * jax.nn.sigmoid(pre))[
            None, :, None], y.shape), rtol=1e-6)


def test_nothing_leaks_from_one_batch_row_into_the_next(small_tiles):
    (x, k, bias), g = _args(F32, 4)
    both, vjp = jax.vjp(cc.causal_conv_silu, x, k, bias)
    dx, dk, db = vjp(g)
    dks, dbs = [], []
    for i in range(2):
        one, vjp_one = jax.vjp(cc.causal_conv_silu, x[i:i + 1], k, bias)
        dx1, dk1, db1 = vjp_one(g[i:i + 1])
        assert jnp.array_equal(one[0], both[i])
        assert jnp.array_equal(dx1[0], dx[i])
        dks.append(dk1), dbs.append(db1)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(sum(dks)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(db), np.asarray(sum(dbs)),
                               rtol=1e-5, atol=1e-5)
    # another second row leaves the first row's output as it was
    other = cc.causal_conv_silu(x.at[1].set(x[1] * 3.0 + 1.0), k, bias)
    assert jnp.array_equal(other[0], both[0])


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_one_tile_only(dtype):
    """The 128-token trace with which ``fit()`` declares a token model's
    parameters, at the tiny mixer's 160 columns: one grid step."""
    args, g = _args(dtype, 4, b=1, n=128, d=160)
    assert cc._tiles(128, 160, dtype) == (128, 160)
    want, vjp = jax.vjp(cc.causal_conv_silu_xla, *args)
    got, vjp_got = jax.vjp(cc.causal_conv_silu, *args)
    assert _ulps(got, want) <= 1.0
    for u, v in zip(vjp_got(g), vjp(g)):
        assert _rel(u, v) < (2e-6 if dtype == F32 else 2.0 ** -7)


def test_the_cells_shape_takes_the_tiles_it_was_measured_at():
    assert cc._tiles(16384, 4352, BF16) == (4096, 128)


@pytest.mark.parametrize("dtype,n", [(F32, 100), (BF16, 24)],
                         ids=["f32", "bf16"])
def test_a_length_no_tile_divides_raises(dtype, n):
    args, _ = _args(dtype, 4, n=n, d=128)
    with pytest.raises(ValueError, match="divides"):
        cc.causal_conv_silu(*args)


def test_the_kernel_refuses_lanes_it_cannot_fill_on_the_chip():
    args, _ = _args(BF16, 4, d=160)
    with pytest.raises(ValueError, match="128 lanes"):
        cc.causal_conv_silu(*args, interpret=False)


def test_more_taps_than_the_halo_holds_raise():
    args, _ = _args(F32, 10, d=128)
    with pytest.raises(ValueError, match="taps"):
        cc.causal_conv_silu(*args)
