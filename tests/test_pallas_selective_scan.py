"""The Mamba-1 selective scan (pallas/selective_scan.py) on the CPU: the
Pallas kernels (interpret mode) and the XLA chunked form against the
token-by-token recurrence, forward and all six cotangents (x, delta, A,
B, C, D), at two channel counts over several chunks, with a decay so
slow that a dropped cross-chunk term fails; a chunk that does not divide
the sequence raises; on the chip the kernel refuses lanes it cannot
fill; the state is carried in float32 under bfloat16 operands."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sod_project_tpu.pallas import selective_scan as ss

B = 2


def _scan_args(ch, ns, n, seed=0):
    """A decay near 1 (delta A between -0.02 and -0.3 a token): after a
    chunk of 16 or 32 tokens most of the state is still there, so a
    dropped cross-chunk term is a gross error."""
    ks = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(ks[0], (B, n, ch))
    delta = jnp.exp(jax.random.uniform(ks[1], (B, n, ch),
                                       minval=np.log(0.02),
                                       maxval=np.log(0.1)))
    a = -jax.random.uniform(ks[2], (ch, ns), minval=1.0, maxval=3.0)
    b = jax.random.normal(ks[3], (B, n, ns))
    c = jax.random.normal(ks[4], (B, n, ns))
    d = jax.random.normal(ks[5], (ch,))
    return (x, delta, a, b, c, d), jax.random.normal(ks[6], (B, n, ch))


def _recurrence(x, delta, a, b, c, d):
    """One token at a time, one sequence at a time."""
    def one(x, delta, b, c):
        def token(h, t):
            xt, dt, bt, ct = t
            h = jnp.exp(dt[:, None] * a) * h + (dt * xt)[:, None] * bt[None]
            return h, jnp.sum(h * ct[None], -1) + d * xt

        return jax.lax.scan(token, jnp.zeros(a.shape), (x, delta, b, c))[1]

    return jax.vmap(one)(x, delta, b, c)


def _rel(u, v):
    return float(jnp.max(jnp.abs(u - v)) / jnp.max(jnp.abs(v)))


SCANS = {"xla": ss.selective_scan_xla, "kernel": ss.selective_scan}


@pytest.mark.parametrize("impl", list(SCANS))
@pytest.mark.parametrize("shape", [(32, 16, 64, 16), (256, 8, 96, 32)])
def test_scan_matches_the_recurrence_forward_and_all_six_cotangents(
        impl, shape):
    ch, ns, n, chunk = shape
    scan = SCANS[impl]
    args, g = _scan_args(ch, ns, n)
    want, vjp = jax.vjp(_recurrence, *args)
    got, vjp_got = jax.vjp(lambda *a: scan(*a, chunk=chunk), *args)
    assert _rel(got, want) < 1e-5
    for name, u, v in zip("x delta A B C D".split(), vjp_got(g), vjp(g)):
        assert _rel(u, v) < 1e-5, name
    # the same with the state dropped at every chunk's edge is far off
    cut = lambda t: t.reshape((-1, chunk) + t.shape[2:])  # noqa: E731
    x, delta, a, b, c, d = args
    alone = scan(cut(x), cut(delta), a, cut(b), cut(c), d,
                 chunk=chunk).reshape(x.shape)
    assert _rel(alone, want) > 0.1


@pytest.mark.parametrize("impl", list(SCANS))
def test_a_chunk_that_does_not_divide_the_sequence_raises(impl):
    args, _ = _scan_args(32, 16, 80)
    with pytest.raises(ValueError, match="does not divide"):
        SCANS[impl](*args, chunk=32)


def test_the_kernel_refuses_lanes_it_cannot_fill_on_the_chip():
    args, _ = _scan_args(32, 16, 64)
    with pytest.raises(ValueError, match="128 lanes"):
        ss.selective_scan(*args, chunk=32, interpret=False)


def test_the_scan_carries_its_state_in_float32_under_bfloat16_operands():
    """The configuration states a float32 carried state: the forward
    kernel writes y in the operands' type and the state each chunk
    started from in float32, and the backward's cotangents of delta, A
    and D are float32."""
    from test_lfm2 import _eqns

    assert ss.STATE_DTYPE == jnp.float32
    (x, delta, a, b, c, d), _ = _scan_args(256, 16, 64)
    x, b, c = (t.astype(jnp.bfloat16) for t in (x, b, c))
    grad = jax.grad(lambda *t: jnp.sum(ss.selective_scan(
        *t, chunk=32).astype(jnp.float32)), argnums=(0, 1, 2, 3, 4, 5))
    jaxpr = jax.make_jaxpr(grad)(x, delta, a, b, c, d)
    calls = {eqn.params["jaxpr"].debug_info.func_name: eqn
             for eqn in _eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"}
    assert set(calls) == {"_fwd_kernel", "_bwd_kernel"}  # ONE backward
    y, edges = (v.aval for v in calls["_fwd_kernel"].outvars)
    assert y.dtype == jnp.bfloat16
    assert edges.dtype == jnp.float32 and edges.shape == (B, 2, 16, 256)
    got = jax.eval_shape(grad, x, delta, a, b, c, d)
    assert [t.dtype for t in got] == [jnp.bfloat16, jnp.float32,
                                      jnp.float32, jnp.bfloat16,
                                      jnp.bfloat16, jnp.float32]
