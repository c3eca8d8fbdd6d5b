"""The rotary kernel pair (pallas/rotary.py) in interpret mode against
``models/lfm2.py::rope`` on the float32 copy, rounded once to bf16 — the
oracle, as the looped model ran it before PR 42 — and the looped model
(models/ouro.py) run THROUGH the kernel against its plain reference at
heads of 128, where tests/test_ouro.py's heads of 16 take the jnp form.

"To the last bit" on the CPU: both arms are jitted and XLA:CPU contracts
ONE of the two products of ``x cos + turned sin`` into a fused
multiply-add, not the same one in both programs, so the two float32 sums
can differ by one float32 rounding of a product.  Where that straddles a
bf16 rounding boundary — or where the sum cancels — the bf16 results
differ.  The comparison therefore holds the kernel to the bf16 values the
oracle's float32 sum can round to when moved by that one rounding, and
counts the elements that differ at all (under 1e-4 of them).  The chip's
vector unit has no fused multiply-add: there the count is 0 (PERF.md
section 6, PR 42).
"""

import dataclasses
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_lfm2 import _eqns
from test_ouro import (TINY, _arch, _close, _loss_of, _plain, _variables)

from benchmark.reference import ouro as ref
from distributed_sod_project_tpu.configs import apply_overrides, get_config
from distributed_sod_project_tpu.losses import token_ce
from distributed_sod_project_tpu.models import build_model, ouro
from distributed_sod_project_tpu.models.lfm2 import rope
from distributed_sod_project_tpu.pallas import rotary

THETA = 1e6
BF = jnp.bfloat16


def _rope32(x32):
    """``rope`` on a head-major float32 [B, H, N, D]."""
    return rope(x32.transpose(0, 2, 1, 3), THETA).transpose(0, 2, 1, 3)


def _oracle32(x):
    return _rope32(x.astype(jnp.float32))


def _oracle(x):
    return _oracle32(x).astype(x.dtype)


def _pair(shape, dtype=BF, seed=0):
    ks = jax.random.split(jax.random.key(sum(shape) + seed), 2)
    return tuple(jax.random.normal(k, shape, jnp.float32).astype(dtype)
                 for k in ks)


def _bits(a):
    return np.asarray(a).view(np.uint16)


def _assert_rounds_as_the_oracle(got, x, want32):
    """``got`` (bf16) is a bf16 rounding of ``want32`` moved by at most
    the float32 roundings of the two products of ``x`` (|cos|, |sin| <= 1)
    and of the sum, and differs from ``bf16(want32)`` in under 1e-4 of
    its elements."""
    x = np.abs(np.asarray(x.astype(jnp.float32)))
    slack = 2.0 ** -22 * (x + np.roll(x, x.shape[-1] // 2, -1))
    want32 = np.asarray(want32)
    lo, hi = (np.asarray(jnp.asarray(v).astype(BF).astype(jnp.float32))
              for v in (want32 - slack, want32 + slack))
    got32 = np.asarray(got.astype(jnp.float32))
    assert np.all((lo <= got32) & (got32 <= hi))
    differ = np.mean(_bits(got) != _bits(jnp.asarray(want32).astype(BF)))
    assert differ < 1e-4, differ


# [2, 3, 384, 128]: one tile; 200 tokens: padded to 256; 1,100: two tiles
# of 1,024, the second mostly padding; a head of 256: a roll by a vreg
@pytest.mark.parametrize("shape", [(2, 3, 384, 128), (2, 3, 200, 128),
                                   (1, 2, 1100, 128), (1, 2, 256, 256)])
def test_the_kernel_equals_rope_on_the_float32_copy_rounded_once(shape):
    q, k = _pair(shape)
    yq, yk = jax.jit(lambda q, k: rotary.rotate_half((q, k), THETA))(q, k)
    assert yq.shape == shape and yq.dtype == BF
    want = jax.jit(_oracle32)
    _assert_rounds_as_the_oracle(yq, q, want(q))
    _assert_rounds_as_the_oracle(yk, k, want(k))


def test_float32_tensors_stay_float32():
    q, _ = _pair((1, 2, 256, 128), jnp.float32)
    (y,) = jax.jit(lambda q: rotary.rotate_half((q,), THETA))(q)
    assert y.dtype == jnp.float32
    _close(y, jax.jit(_oracle32)(q), 1e-6)


def test_q_and_k_in_one_call_equal_two_calls():
    q, k = _pair((2, 3, 200, 128))
    one = jax.jit(lambda q, k: rotary.rotate_half((q, k), THETA))(q, k)
    two = jax.jit(lambda q, k: rotary.rotate_half((q,), THETA)
                  + rotary.rotate_half((k,), THETA))(q, k)
    for a, b in zip(one, two):
        np.testing.assert_array_equal(_bits(a), _bits(b))


def test_the_vjp_equals_the_oracles_to_bf16_rounding():
    shape = (2, 3, 200, 128)
    q, k = _pair(shape)
    gq, gk = _pair(shape, seed=1)

    def through(f):
        return jax.jit(lambda q, k, gq, gk: jax.vjp(f, q, k)[1]((gq, gk)))(
            q, k, gq, gk)

    got = through(lambda q, k: rotary.rotate_half((q, k), THETA))
    want = through(lambda q, k: (_oracle(q), _oracle(k)))
    # ... and against the float32 cotangent before its rounding: rope's
    # transpose is rope at the negated angle, the same two products
    want32 = [jax.jit(lambda g: jax.vjp(_rope32, q.astype(jnp.float32))[1](
        g.astype(jnp.float32))[0])(g) for g in (gq, gk)]
    for a, b, g, b32 in zip(got, want, (gq, gk), want32):
        assert a.dtype == BF and a.shape == shape
        _close(a.astype(jnp.float32), b.astype(jnp.float32), 2.0 ** -8)
        _assert_rounds_as_the_oracle(a, g, b32)


def test_the_vjp_is_the_forward_kernel_with_the_sine_negated():
    """Bit-equal, padded length and all."""
    q, k = _pair((3, 256, 128))
    cos, sin = rotary.rotary_tables(256, 128, THETA)
    back = jax.jit(lambda gs: jax.vjp(
        lambda *xs: rotary._rotate(xs, cos, sin, True), q, k)[1](gs))((q, k))
    fwd = jax.jit(lambda gs: rotary._fwd_call(gs, cos, -sin, True))((q, k))
    for a, b in zip(back, fwd):
        np.testing.assert_array_equal(_bits(a), _bits(b))


def test_the_vjp_keeps_no_residual_but_the_tables():
    """What ``jax.vjp`` hands its backward (the leaves of the function it
    returns): cos and the signed sine at the padded length, nothing of
    q's or k's size."""
    q, k = _pair((2, 3, 200, 128))
    _, back = jax.vjp(lambda q, k: rotary.rotate_half((q, k), THETA), q, k)
    kept = [(r.shape, str(r.dtype)) for r in jax.tree_util.tree_leaves(back)
            if r.ndim]
    assert kept == [((256, 128), "float32")] * 2, kept


def test_bad_operands_raise():
    q, k = _pair((1, 2, 128, 128))
    with pytest.raises(ValueError, match="128 lanes"):
        rotary.rotate_half((q[..., :64],), THETA)
    with pytest.raises(ValueError, match="one shape"):
        rotary.rotate_half((q, k[:, :1]), THETA)
    with pytest.raises(ValueError, match="one shape"):
        rotary.rotate_half((q, k.astype(jnp.float32)), THETA)


def _kernel_names(f, *args):
    """The innermost scope of every ``pallas_call``, less ``dsod.kernel.``
    (the two rotary calls share one kernel body)."""
    return [str(eqn.source_info.name_stack).rsplit("dsod.kernel.", 1)[-1]
            for eqn in _eqns(jax.make_jaxpr(f)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("head_dim, kernels",
                         [(16, 0), (64, 0), (128, 1), (256, 1)])
def test_the_model_chooses_by_the_heads_width(head_dim, kernels):
    """Heads that fill the lanes take the kernel, q and k in ONE call;
    heads of 16 and 64 take ``rope`` and lower to no kernel; the same
    values either way."""
    q, k = _pair((1, 96, 2, head_dim))
    f = lambda q, k: ouro.rotated(q, k, THETA)  # noqa: E731
    assert _kernel_names(f, q, k) == ["rotary"] * kernels
    assert ("dsod.kernel.rotary" in jax.jit(f).lower(q, k).as_text(
        debug_info=True)) == bool(kernels)
    for y, x in zip(jax.jit(f)(q, k), (q, k)):
        assert y.shape == (1, 2, 96, head_dim) and y.dtype == BF
        x = x.transpose(0, 2, 1, 3)
        _assert_rounds_as_the_oracle(y, x, jax.jit(_oracle32)(x))


# -- the looped model THROUGH the kernel: heads of 128 ----------------------

B, N, R, LAYERS = 2, 256, 4, 2
WIDE = ["model.lm.heads=2", "model.lm.kv_heads=2", "model.lm.head_dim=128",
        "data.seq_len=256"]


@pytest.fixture(scope="module")
def setup():
    cfg = apply_overrides(get_config("ouro_2_6b_pp6"), TINY + WIDE)
    model = build_model(cfg.model)
    tokens = jax.random.randint(jax.random.key(1), (B, N), 0, 512)
    return cfg, model, _variables(model, tokens), tokens, _arch(cfg.model.lm)


def test_states_gates_exit_distribution_and_loss_match_reference(setup):
    """tests/test_ouro.py's comparison, inside its limits."""
    _, model, v, tokens, m = setup
    (states, gates), _ = model.apply(v, tokens)
    assert states.shape == (R, B, N, 64) and gates.shape == (R, B, N)
    for b in range(B):
        hs, gs = ref.states(v, tokens[b], m)
        _close(states[:, b], hs, 1e-4)
        _close(gates[:, b], gs, 1e-4)
        p, _ = token_ce.exit_distribution(gates[:, b])
        _close(p, ref.exit_distribution(gs), 1e-4)
    total, _ = _loss_of(model, tokens)(v["params"])
    want = _plain(tokens, m)(v["params"])
    assert abs(float(total) - float(want)) < 1e-5 * float(want)


def test_every_gradient_matches_reference(setup):
    _, model, v, tokens, m = setup
    gp, _ = jax.jit(jax.grad(_loss_of(model, tokens), has_aux=True))(
        v["params"])
    g_ref = jax.jit(jax.grad(_plain(tokens, m)))(v["params"])
    flat = jax.tree_util.tree_flatten_with_path(gp)[0]
    assert len(flat) == 5 + 11 * LAYERS
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(g_ref)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-4 * float(jnp.max(jnp.abs(b))), jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)


def test_named_saves_give_the_gradient_of_no_remat(setup):
    cfg, model, v, tokens, _ = setup
    plain = build_model(dataclasses.replace(cfg.model, remat=False))
    ga, _ = jax.jit(jax.grad(_loss_of(model, tokens), has_aux=True))(
        v["params"])
    gb, _ = jax.jit(jax.grad(_loss_of(plain, tokens), has_aux=True))(
        v["params"])
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ga)[0],
                            jax.tree_util.tree_leaves(gb)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 1e-5 * float(jnp.max(jnp.abs(b))), jax.tree_util.keystr(path)


def test_the_gradient_runs_one_forward_kernel_a_visit(setup, caplog):
    """A visit (layers x passes) runs the flash forward once and its
    fused backward once; the rotation twice forward (the visit keeps
    nothing of it: recomputed) and once backward, q and k in one call;
    and the visit's checkpoint still keeps 2 names."""
    _, model, v, tokens, _ = setup
    logger = logging.getLogger("dsod")
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="dsod"):
            names = _kernel_names(
                jax.grad(_loss_of(model, tokens), has_aux=True), v["params"])
            lines = [r.getMessage() for r in caplog.records
                     if "remat saves (ouro" in r.getMessage()]
    finally:
        logger.removeHandler(caplog.handler)
    visits = LAYERS * R
    assert names.count("flash_attention_causal") == visits
    assert names.count("flash_attention_causal_bwd") == visits
    assert names.count("rotary") == 2 * visits
    assert names.count("rotary_bwd") == visits
    assert len(names) == 5 * visits
    assert len(lines) == 1 and re.search(
        rf"\(ouro, {visits} visits\): flash_out={visits} "
        rf"flash_lse={visits} MiB=", lines[0]), lines


@pytest.mark.parametrize("scope", ["dsod.kernel.rotary",
                                   "dsod.kernel.rotary_bwd"])
def test_the_kernels_sit_under_attn_and_outside_its_core(setup, scope):
    _, model, v, tokens, _ = setup
    text = jax.jit(jax.grad(_loss_of(model, tokens), has_aux=True)).lower(
        v["params"]).as_text(debug_info=True)
    paths = re.findall(r'^#loc\d+ = loc\("([^"]*)"', text, re.M)
    under = [p for p in paths
             if re.search(re.escape(scope) + r"(?![\w.])", p)]
    assert under
    for p in under:
        assert "dsod.encoder" in p and "dsod.loop" in p and "dsod.attn" in p
        assert "dsod.attn.core" not in p
    # ... and the flash kernels stay inside the core
    assert all("dsod.attn.core" in p for p in paths
               if "dsod.kernel.flash_attention_causal" in p)
