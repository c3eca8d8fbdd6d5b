"""Pallas flash attention vs the XLA oracle (parallel/ring_attention
.full_attention) — forward, all three gradients, padding, bf16, the
ViT-SOD attn_impl wiring, and the real-TPU Mosaic lowering."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sod_project_tpu.pallas.flash_attention import (
    _bwd_call, _fwd_call, flash_attention, flash_attention_with_lse)
from distributed_sod_project_tpu.parallel.ring_attention import full_attention


def _qkv(b, h, n, d, dtype=jnp.float32, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda k: jax.random.normal(k, (b, h, n, d)).astype(dtype)
    return mk(kq), mk(kk), mk(kv)


@pytest.mark.parametrize(
    "b,h,n,d",
    [
        (1, 2, 257, 64),   # padded N (one ragged key block) — the
        #                    quick-gate representative; the other
        #                    cases cost ~10 s cold compile each and
        #                    exercise the same kernel (full suite)
        pytest.param(2, 3, 128, 32, marks=pytest.mark.slow),
        pytest.param(1, 1, 200, 128, marks=pytest.mark.slow),
    ],
)
def test_forward_and_grads_match_oracle(b, h, n, d):
    q, k, v = _qkv(b, h, n, d)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v)),
        np.asarray(full_attention(q, k, v)), atol=2e-6)

    cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    g_fl = jax.grad(lambda *a: jnp.sum(flash_attention(*a) * cot),
                    argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(lambda *a: jnp.sum(full_attention(*a) * cot),
                     argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_fl, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-6, err_msg=f"d{name}")


def test_multi_lane_kv_blocks():
    """block_kv=256 exercises the lane-tile (reps>1) broadcast path."""
    q, k, v = _qkv(1, 2, 300, 32)
    out = flash_attention(q, k, v, block_q=256, block_kv=256)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(full_attention(q, k, v)), atol=2e-6)


def test_non_dividing_block_pair():
    """Regression: blocks that don't divide each other must still cover
    every valid row (padding rounds to their lcm, not the max)."""
    q, k, v = _qkv(1, 1, 600, 32)
    out = flash_attention(q, k, v, block_q=256, block_kv=640)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(full_attention(q, k, v)), atol=2e-6)


@pytest.mark.slow
def test_with_lse_values_and_cotangent():
    """The lse output equals logsumexp of the scaled scores, and a
    NONZERO lse cotangent backpropagates correctly (it folds into the
    kernels as a delta shift) — the contract the SP ring merge needs."""
    q, k, v = _qkv(1, 2, 200, 32)

    def oracle(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
        return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v),
                jax.scipy.special.logsumexp(s, axis=-1))

    out, lse = flash_attention_with_lse(q, k, v)
    ref_out, ref_lse = oracle(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-6)

    co = jax.random.normal(jax.random.PRNGKey(3), out.shape)
    cl = jax.random.normal(jax.random.PRNGKey(4), lse.shape)

    def loss(fn):
        def f(*a):
            o, l = fn(*a)
            return jnp.sum(o * co) + jnp.sum(l * cl)
        return f

    g_fl = jax.grad(loss(flash_attention_with_lse), argnums=(0, 1, 2))(q, k, v)
    g_or = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_fl, g_or):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6, err_msg=f"d{name}")


def test_bfloat16_inputs():
    q, k, v = _qkv(1, 2, 256, 64, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=3e-2)


def test_shape_validation():
    q, k, v = _qkv(1, 1, 128, 32)
    with pytest.raises(ValueError, match="shapes differ"):
        flash_attention(q, k[:, :, :64], v)
    with pytest.raises(ValueError, match="head dim"):
        bad = jnp.zeros((1, 1, 128, 192))
        flash_attention(bad, bad, bad)
    with pytest.raises(ValueError, match="multiples of 128"):
        flash_attention(q, k, v, block_q=64)


@pytest.mark.slow
def test_vit_sod_flash_wiring_matches_xla():
    """attn_impl='flash' is numerically the same model as 'xla'."""
    from distributed_sod_project_tpu.models.vit_sod import ViTSOD

    img = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64, 3))
    kw = dict(patch=16, dim=32, depth=2, heads=2, deep_supervision=False)
    m_x = ViTSOD(attn_impl="xla", **kw)
    m_f = ViTSOD(attn_impl="flash", **kw)
    params = m_x.init(jax.random.PRNGKey(1), img)

    out_x = m_x.apply(params, img)[0]
    out_f = m_f.apply(params, img)[0]
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_x),
                               atol=1e-4)

    def loss(mod):
        def f(p):
            return jnp.mean(jax.nn.sigmoid(mod.apply(p, img)[0]) ** 2)
        return f

    g_x = jax.grad(loss(m_x))(params)
    g_f = jax.grad(loss(m_f))(params)
    flat_x = jax.tree.leaves(g_x)
    flat_f = jax.tree.leaves(g_f)
    for a, b in zip(flat_f, flat_x):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_registry_rejects_attn_impl_on_cnn_zoo():
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.models.registry import build_model

    cfg = get_config("minet_vgg16_ref")
    bad = cfg.model.__class__(**{**cfg.model.__dict__, "attn_impl": "flash"})
    with pytest.raises(ValueError, match="only applies to vit_sod"):
        build_model(bad)


def test_unknown_attn_impl_raises():
    from distributed_sod_project_tpu.models.vit_sod import ViTSOD

    img = jnp.zeros((1, 32, 32, 3))
    m = ViTSOD(patch=16, dim=32, depth=1, heads=2, attn_impl="nope")
    with pytest.raises(ValueError, match="attn_impl"):
        m.init(jax.random.PRNGKey(0), img)


def test_flash_lowers_for_real_tpu():
    """interpret=False + export for platform='tpu' runs the Mosaic
    pipeline end-to-end (no chip needed) — fwd, dq, and dkv kernels,
    both the aligned and the padded/masked variants."""
    from jax import export

    bh, npad, d = 2, 256, 64
    q = jnp.zeros((bh, npad, d), jnp.float32)
    lse = jnp.zeros((bh, npad), jnp.float32)  # one-lane residual row

    for n in (256, 200):  # aligned; padded (mask-bias iota path)
        cfg = (128, 128, False, n)
        exp = export.export(jax.jit(
            lambda q_, k_, v_: _fwd_call(q_, k_, v_, cfg)),
            platforms=["tpu"])(q, q, q)
        assert "tpu_custom_call" in exp.mlir_module()

        exp = export.export(jax.jit(
            lambda q_, k_, v_, o_, l_, g_: _bwd_call(q_, k_, v_, o_, l_,
                                                     g_, cfg)),
            platforms=["tpu"])(q, q, q, q, lse, q)
        assert "tpu_custom_call" in exp.mlir_module()


# -- causal latent attention (models/kimi.py) --------------------------------

def _mla_inputs(n, b=2, h=3, dn=32, dr=16, dv=24, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + n), 6)
    shapes = ((b, h, n, dn), (b, h, n, dr), (b, h, n, dn), (b, n, dr),
              (b, h, n, dv), (b, h, n, dv))
    return [jax.random.normal(k, s) for k, s in zip(ks, shapes)]


def _plain_mla(qn, qr, kn, kr, v):
    """[k_nope ; k_rope] keys, ONE rotary key head for all query heads,
    values of their own width, causal softmax: plain jnp."""
    n = qn.shape[2]
    s = (jnp.einsum("bhqd,bhkd->bhqk", qn, kn)
         + jnp.einsum("bhqd,bkd->bhqk", qr, kr)) \
        / np.sqrt(qn.shape[-1] + qr.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


# lengths that are and are not multiples of the tile; one tile and many
@pytest.mark.parametrize("n,block", [(256, 128), (160, 128), (72, None),
                                     (384, 128)])
@pytest.mark.parametrize("what", ["forward", "dq", "dkv"])
def test_flash_mla_matches_plain_attention(n, block, what):
    from distributed_sod_project_tpu.pallas.flash_attention import \
        flash_attention_mla

    *args, cot = _mla_inputs(n)
    kernel = lambda *a: flash_attention_mla(*a, block=block)  # noqa: E731
    if what == "forward":
        got, want = kernel(*args), _plain_mla(*args)
        assert got.shape == cot.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)
        return
    argnums = (0, 1) if what == "dq" else (2, 3, 4)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * cot), argnums)(*args)
    want = jax.grad(lambda *a: jnp.sum(_plain_mla(*a) * cot), argnums)(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b),
            atol=3e-6 * float(jnp.max(jnp.abs(b))))


# at least 3 blocks a side: pairs on the diagonal, below it (2-3 q blocks
# add to one dq accumulator; kv block 0 sees every q block) and skipped
@pytest.mark.parametrize("n,block,h", [(384, 128, 3), (512, 128, 2),
                                       (768, 256, 2)])
def test_flash_mla_fused_backward_matches_plain_gradient(n, block, h):
    """The ONE backward kernel, called as the custom VJP calls it,
    against the float32 gradient of plain latent attention: dq (whole-
    sequence accumulators that must start from zero at every (batch,
    head) row: the rows hold different data), per-head dk_nope and dv,
    and the shared rotary key's cotangent summed over the heads."""
    from distributed_sod_project_tpu.pallas.flash_attention import (
        _m_bwd_call, _m_fwd_call)

    *args, cot = _mla_inputs(n, b=2, h=h)
    fold = lambda t: t.reshape((-1,) + t.shape[-2:])  # noqa: E731
    folded = [fold(t) for t in args]
    cfg = (block, h, True)
    out, lse = _m_fwd_call(*folded, cfg)
    got = _m_bwd_call(*folded, out, lse[:, :, 0], fold(cot), cfg)
    want = jax.grad(lambda *a: jnp.sum(_plain_mla(*a) * cot),
                    (0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(("dq_nope", "dq_rope", "dk_nope", "dk_rope",
                           "dv"), got, want):
        assert a.shape == fold(b).shape, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(fold(b)), err_msg=name,
            atol=3e-6 * float(jnp.max(jnp.abs(b))))


def test_flash_mla_backward_refuses_a_sequence_its_vmem_cannot_hold(
        monkeypatch):
    """The dq accumulators hold a head's whole sequence in VMEM: on a
    chip whose VMEM they pass, the call raises and names the limit (a
    compile error from Mosaic says far less); the scoped limit it asks
    for otherwise is what the shapes derive.  Shapes only: nothing
    runs."""
    from distributed_sod_project_tpu.pallas import vmem_budget as vb
    from distributed_sod_project_tpu.pallas.flash_attention import (
        _m_bwd_call, _mla_bwd_vmem_bytes)

    monkeypatch.setattr(vb, "_device_kind", lambda: "TPU v5 lite")

    def call(n):
        bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
        return jax.eval_shape(
            lambda *a: _m_bwd_call(*a, (512, 16, False)),
            bf(16, n, 128), bf(16, n, 64), bf(16, n, 128), bf(1, n, 64),
            bf(16, n, 128), bf(16, n, 128),
            jax.ShapeDtypeStruct((16, n), jnp.float32), bf(16, n, 128))

    assert call(16384)[1].shape == (16, 16384, 64)
    need = _mla_bwd_vmem_bytes(16384, 512, (128, 64, 128), 2)
    assert 16 * 2**20 < need - 16384 * 1024 < 32 * 2**20  # beside 16 MiB
    assert vb.fitted_vmem_params(need, "x").vmem_limit_bytes == need
    with pytest.raises(ValueError, match=r"131072 rows needs 144\.5 MiB of "
                                         r"VMEM; a TPU v5 lite has 128 MiB"):
        call(131072)
    monkeypatch.setattr(vb, "_device_kind", lambda: None)  # interpret mode
    assert vb.fitted_vmem_params(2**40, "x").vmem_limit_bytes is None


def test_flash_mla_raises_where_a_tile_does_not_divide():
    """A floor-divided grid would leave the last rows unwritten, which
    interpret mode at one block cannot show (PERF.md, PR 28): the calls
    refuse a block that does not divide the padded length, and the
    wrapper a block or a width the lanes cannot hold."""
    from distributed_sod_project_tpu.pallas.flash_attention import (
        _m_bwd_call, _m_fwd_call, flash_attention_mla)

    qn, qr, kn, kr, v, cot = (t.reshape((-1,) + t.shape[-2:])
                              for t in _mla_inputs(192))
    bad = (128, 3, True)  # 192 rows, blocks of 128
    with pytest.raises(ValueError, match="does not divide"):
        _m_fwd_call(qn, qr, kn, kr, v, bad)
    with pytest.raises(ValueError, match="does not divide"):
        _m_bwd_call(qn, qr, kn, kr, v, cot, jnp.zeros(qn.shape[:2]), cot,
                    bad)
    args = _mla_inputs(192)[:5]
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention_mla(*args, block=96)
    with pytest.raises(ValueError, match="shapes"):
        flash_attention_mla(args[0], args[1], args[2], args[3][:, :100],
                            args[4])
    wide = _mla_inputs(128, dn=192)[:5]
    with pytest.raises(ValueError, match="width 192"):
        flash_attention_mla(*wide)


# -- the causal kernels' grid: the pairs on or under the diagonal ------------

@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("nb", [1, 2, 5, 16, 32])
def test_causal_pairs_hold_the_triangle_once_in_the_accumulators_order(
        nb, group):
    """Every pair on or under the diagonal exactly once (a head), none
    above it, in the two orders the kernels' accumulators rely on: the
    forward's row runs kv block 0 .. i and ENDS on its diagonal (where
    out and lse are written); the backward's kv blocks ascend, and under
    each every head of the group in turn STARTS on the diagonal (where
    its dq block is complete and written) and runs to the last q
    block (where dk and dv are)."""
    from distributed_sod_project_tpu.pallas.flash_attention import \
        causal_pairs

    (q_of, k_of), (kv_of, head_of, qb_of) = causal_pairs(nb, group)
    triangle = [(i, j) for i in range(nb) for j in range(i + 1)]
    for table in (q_of, k_of, kv_of, head_of, qb_of):
        assert table.dtype == np.int32 and table.ndim == 1
    # forward: q block outer, kv ascending to the diagonal
    assert list(zip(q_of.tolist(), k_of.tolist())) == triangle
    # backward: kv block outer, then the head, then q from the diagonal on
    want = [(i, g, j) for i in range(nb) for g in range(group)
            for j in range(i, nb)]
    got = list(zip(kv_of.tolist(), head_of.tolist(), qb_of.tolist()))
    assert got == want
    assert len(got) == group * len(triangle) == group * nb * (nb + 1) // 2
    assert sorted((j, i) for i, _, j in got) == sorted(triangle * group)


def _mla_grad_jaxpr(n, h=2):
    from distributed_sod_project_tpu.pallas.flash_attention import \
        flash_attention_mla

    *args, cot = _mla_inputs(n, b=2, h=h)
    return jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(flash_attention_mla(*a, block=128) * cot),
        (0, 1, 2, 3, 4)))(*args)


@pytest.mark.parametrize("n", [128, 640, 600])
def test_flash_mla_forward_grid_holds_the_pairs_under_the_diagonal_alone(n):
    """The latent forward's ``pallas_call`` equation carries the grid
    (batch x heads, pairs) with ``causal_pairs``' count and its two
    tables among the traced program's constants; the backward keeps the
    rectangle (bh, kv block, q block) and no table: on the chip it was
    slower on the pair grid (PERF.md section 6, PR 45)."""
    from distributed_sod_project_tpu.pallas.flash_attention import \
        causal_pairs

    from test_lfm2 import _eqns

    jaxpr = _mla_grad_jaxpr(n)
    fwd, bwd = (eqn.params["grid_mapping"] for eqn in _eqns(jaxpr.jaxpr)
                if eqn.primitive.name == "pallas_call")
    nb = -(-n // 128)
    tables, _ = causal_pairs(nb)
    assert fwd.grid == (4, nb * (nb + 1) // 2) == (4, tables[0].size)
    assert fwd.num_index_operands == 2
    for table in tables:
        assert any(np.array_equal(c, table) for c in jaxpr.consts)
    assert bwd.grid == (4, nb, nb) and bwd.num_index_operands == 0


# nb = 5 (no power of two), with and without padding rows, both float
# types; the rotary key is one head for h = 1 and for h = 4 query heads
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h", [(640, 1), (640, 4), (600, 1), (600, 4)])
def test_flash_mla_pair_grid_matches_plain_attention(n, h, dtype):
    """Forward and every gradient over a grid of 15 pairs a head (of 25)."""
    from distributed_sod_project_tpu.pallas.flash_attention import \
        flash_attention_mla

    *args, cot = (t.astype(dtype) for t in _mla_inputs(n, b=2, h=h))
    f32 = lambda ts: [t.astype(jnp.float32) for t in ts]  # noqa: E731
    tol = 3e-6 if dtype == jnp.float32 else 2e-2
    kernel = lambda *a: flash_attention_mla(*a, block=128)  # noqa: E731
    out = kernel(*args)
    assert out.dtype == dtype and out.shape == cot.shape
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(_plain_mla(*f32(args))),
        atol=tol)
    every = (0, 1, 2, 3, 4)
    got = jax.grad(lambda *a: jnp.sum((kernel(*a) * cot).astype(
        jnp.float32)), every)(*args)
    want = jax.grad(lambda *a: jnp.sum(_plain_mla(*a) * f32([cot])[0]),
                    every)(*f32(args))
    for name, a, b in zip(("dq_nope", "dq_rope", "dk_nope", "dk_rope",
                           "dv"), got, want):
        assert a.dtype == dtype, name
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), err_msg=name,
            atol=5 * tol * float(jnp.max(jnp.abs(b))))


# -- the grouped causal kernel: a sliding window, a value wider than a key ---

def _dense_causal(q, k, v, window=None):
    """Masked softmax in plain XLA: grouped kv heads, causal, a query
    seeing its last ``window`` keys alone (its own among them)."""
    b, hq, n, d = q.shape
    g = hq // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, g, 1)) / d ** 0.5
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    seen = j <= i if window is None else (j <= i) & (i - j < window)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1),
                      jnp.repeat(v, g, 1))


def _causal_inputs(n, d=32, dv=32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (2, 4, n, d)),
            jax.random.normal(ks[1], (2, 2, n, d)),
            jax.random.normal(ks[2], (2, 2, n, dv)),
            jax.random.normal(ks[3], (2, 4, n, dv)))


# blocks of 128 over 640 (5 blocks) or 600 rows (padding): a window
# smaller than a block (the diagonal pair cut twice), equal to one (the
# published 512 over blocks of 512, in small), between one and two,
# larger than two, of one key; and a value twice the key's width
@pytest.mark.parametrize("n,window,dv", [
    (640, 40, 32), (640, 128, 32), (600, 200, 32), (640, 300, 32),
    (640, 1, 32), (640, None, 64), (600, 128, 64)])
def test_causal_window_and_wide_value_match_dense_masked_softmax(
        n, window, dv):
    from distributed_sod_project_tpu.pallas.flash_attention import \
        flash_attention_causal

    q, k, v, cot = _causal_inputs(n, dv=dv)
    kernel = lambda *a: flash_attention_causal(  # noqa: E731
        *a, window=window, block=128)
    out = kernel(q, k, v)
    assert out.shape == cot.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(
        _dense_causal(q, k, v, window)), atol=3e-6)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * cot), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense_causal(*a, window) * cot),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), err_msg=f"d{name}",
            atol=5e-6 * max(float(jnp.max(jnp.abs(b))), 1.0))


def test_a_window_longer_than_the_sequence_is_plain_causal():
    """The same tables, the same kernels: the same program."""
    from distributed_sod_project_tpu.pallas.flash_attention import \
        flash_attention_causal

    q, k, v, _ = _causal_inputs(256)
    plain = jax.make_jaxpr(lambda *a: flash_attention_causal(
        *a, block=128))(q, k, v)
    for window in (256, 1000):
        assert str(jax.make_jaxpr(lambda *a: flash_attention_causal(
            *a, window=window, block=128))(q, k, v)) == str(plain)
    assert str(jax.make_jaxpr(lambda *a: flash_attention_causal(
        *a, window=255, block=128))(q, k, v)) != str(plain)


@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("nb", [1, 2, 5, 16, 32])
def test_without_a_window_the_tables_are_the_triangles(nb, group):
    """``window=None`` builds what ``causal_pairs`` built before it took
    a band (the comprehension is PR 45's function, kept here), and a band
    that reaches every block is the triangle."""
    from distributed_sod_project_tpu.pallas.flash_attention import \
        causal_pairs

    rows = np.arange(nb, dtype=np.int32)
    ahead = nb - rows
    fwd = (np.repeat(rows, rows + 1),
           np.concatenate([rows[:i + 1] for i in rows]))
    bwd = (np.repeat(rows, group * ahead),
           np.concatenate([np.repeat(np.arange(group, dtype=np.int32), a)
                           for a in ahead]),
           np.concatenate([np.tile(rows[i:], group) for i in rows]))
    for back in (None, nb - 1, nb + 3):
        got = causal_pairs(nb, group, back)
        for a, b in zip(got[0] + got[1], fwd + bwd):
            assert a.dtype == b.dtype == np.int32
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("window,block,back", [
    (1, 128, 0), (2, 128, 1), (128, 128, 1), (129, 128, 1), (130, 128, 2),
    (512, 512, 1), (513, 512, 1), (514, 512, 2), (1024, 512, 2)])
def test_the_band_holds_the_pairs_a_window_touches_and_no_other(
        window, block, back):
    from distributed_sod_project_tpu.pallas.flash_attention import (
        band_back, causal_pairs)

    assert band_back(window, block) == back
    nb, group = 6, 2
    (q_of, k_of), (kv_of, head_of, qb_of) = causal_pairs(nb, group, back)
    # pair (i, j) holds an entry with 0 <= row - col < window
    touched = [(i, j) for i in range(nb) for j in range(i + 1)
               if (i - j - 1) * block + 1 < window]
    assert list(zip(q_of.tolist(), k_of.tolist())) == touched
    assert list(zip(kv_of.tolist(), head_of.tolist(), qb_of.tolist())) == [
        (j, g, i) for j in range(nb) for g in range(group)
        for i in range(j, nb) if (i, j) in touched]
