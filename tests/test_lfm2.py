"""The token model (models/lfm2.py, config ``lfm2_8b_a1b_ep4``) against
its plain reference (benchmark/reference/lfm2.py) on the CPU at tiny
widths, float32, seeded weights (benchmark/harness/weights_lm.py):

- each layer kind, the whole model's loss and every gradient leaf;
- the four shares' expert outputs add up to the uncut reference layer;
- no pair dropped when every token picks held experts only, zero
  contribution when none does; the grouped product against a loop;
- the un-permute kernel (``combine`` / ``dispatch`` and their VJPs)
  against ``jnp.take`` + ``einsum``, and no [T, K, D] array anywhere in
  the expert layer's forward or backward;
- the causal grouped-KV flash kernel (interpret mode), also at a length
  that is no multiple of the block;
- what a rematerialised block keeps by name (``REMAT_SAVES``): the same
  gradient bits as no remat, each kernel and sort once in the gradient,
  the names among one block's saved residuals and no buffer, the step's
  ``remat saves`` log line;
- the chunked loss equals the unchunked one;
- every ``dsod.moe.*`` / ``dsod.attn`` / ``dsod.shortconv`` scope in the
  lowered step and none of them in BASNet's;
- the packed-token dataset, and three steps of ``fit()``.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.weights_lm import variables_builder
from benchmark.reference import lfm2 as ref
from distributed_sod_project_tpu.configs import apply_overrides, get_config
from distributed_sod_project_tpu.losses.token_ce import tied_cross_entropy
from distributed_sod_project_tpu.models import build_model
from distributed_sod_project_tpu.models import lfm2 as lm
from distributed_sod_project_tpu.pallas import grouped_matmul as gm
from distributed_sod_project_tpu.pallas.flash_attention import (
    causal_pairs, flash_attention_causal)
from distributed_sod_project_tpu.pallas.grouped_matmul import grouped_matmul
from distributed_sod_project_tpu.pallas.moe_unpermute import unpermute_steps

TINY = ["model.lm.vocab=512", "model.lm.hidden=64", "model.lm.heads=4",
        "model.lm.kv_heads=2", "model.lm.head_dim=16",
        "model.lm.dense_width=96", "model.lm.expert_width=48",
        "model.lm.experts=8", "model.lm.experts_held=2",
        "model.lm.top_k=2", "data.seq_len=160", "data.vocab=512",
        "data.synthetic_size=32", "global_batch_size=2",
        "model.compute_dtype=float32"]
B, N = 2, 160  # 160: one whole 128-row block and a part of one


def _cfg(*more):
    return apply_overrides(get_config("lfm2_8b_a1b_ep4"),
                           TINY + list(more))


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    model = build_model(cfg.model)
    tokens = jax.random.randint(jax.random.key(0), (B, N), 0, 512)
    shapes = jax.eval_shape(lambda r, t: model.init(r, t),
                            jax.random.key(1), tokens)
    variables = variables_builder(shapes, {})(7)
    return cfg, model, variables, tokens, dataclasses.asdict(cfg.model.lm)


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol * float(np.max(np.abs(b)) + 1e-12))


# -- layer by layer ----------------------------------------------------------

def _x(seed=3):
    return jax.random.normal(jax.random.key(seed), (B, N, 64))


def _per_seq(fn, x):
    return jnp.stack([fn(x[i]) for i in range(x.shape[0])])


@pytest.mark.parametrize("kind", ["shortconv", "attention", "densemlp",
                                  "experts"])
def test_layer_matches_reference(setup, kind):
    cfg, _, v, _, m = setup
    c, x = cfg.model.lm, _x()
    f32 = dict(dtype=jnp.float32)
    if kind == "shortconv":
        p = v["params"]["layer_0"]["conv"]
        got = lm.ShortConv(c.conv_kernel, **f32).apply({"params": p}, x)
        want = _per_seq(lambda s: ref.short_conv(s, p), x)
    elif kind == "attention":
        p = v["params"]["layer_1"]["attn"]
        got = lm.Attention(c.heads, c.kv_heads, c.head_dim, c.rope_theta,
                           c.norm_eps, **f32).apply({"params": p}, x)
        want = _per_seq(lambda s: ref.attention(s, p, m), x)
    elif kind == "densemlp":
        p = v["params"]["layer_0"]["mlp"]
        got = lm.SwiGLU(c.dense_width, **f32).apply({"params": p}, x)
        want = _per_seq(lambda s: ref.swiglu(s, p), x)
    else:
        p = v["params"]["layer_1"]["moe"]
        b = v["batch_stats"]["layer_1"]["moe"]
        got, counters = _experts(c).apply(
            {"params": p, "batch_stats": b}, x)
        want = _per_seq(lambda s: ref.moe(s, p, b["expert_bias"], m), x)
        assert float(counters["dropped"]) == 0.0
        assert 0 < float(counters["pairs_here"]) < B * N * c.top_k
    _close(got, want)


def _experts(c, **kw):
    args = dict(experts=c.experts, experts_held=c.experts_held,
                first_expert=c.first_expert, top_k=c.top_k,
                width=c.expert_width, dtype=jnp.float32)
    return lm.ExpertLayer(**dict(args, **kw))


def test_model_loss_and_every_gradient_match_reference(setup):
    _, model, v, tokens, m = setup

    def plain(p):
        return ref.batch_loss({"params": p, "batch_stats": v["batch_stats"]},
                              tokens, jnp.roll(tokens, -1, 1), m)

    lp, gp = jax.jit(jax.value_and_grad(_loss_of(model, v, tokens)))(
        v["params"])
    lr, gr = jax.jit(jax.value_and_grad(plain))(v["params"])
    assert abs(float(lp) - float(lr)) < 1e-5 * float(lr)
    flat = jax.tree_util.tree_flatten_with_path(gp)[0]
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(gr)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-4 * float(jnp.max(jnp.abs(b))), jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)


# -- the chip's share --------------------------------------------------------

def _whole_layer(seed=11, experts=8, d=64, f=48):
    """An uncut expert layer: all ``experts`` held."""
    ks = jax.random.split(jax.random.key(seed), 5)
    p = {"router": {"kernel": jax.random.normal(ks[0], (d, experts)) / 8},
         "gate": jax.random.normal(ks[1], (experts, d, f)) / 8,
         "up": jax.random.normal(ks[2], (experts, d, f)) / 8,
         "down": jax.random.normal(ks[3], (experts, f, d)) / 7}
    return p, jax.random.normal(ks[4], (experts,)) * 0.01


def test_four_shares_add_up_to_the_uncut_reference_layer(setup):
    cfg, _, _, _, m = setup
    c, x = cfg.model.lm, _x(5)
    p, bias = _whole_layer()
    whole = _per_seq(lambda s: ref.moe(s, p, bias, m), x)
    total, pairs = jnp.zeros_like(x), 0.0
    for share in range(4):
        lo = share * 2
        mine = dict(p, **{k: p[k][lo:lo + 2] for k in ("gate", "up", "down")})
        out, counters = _experts(c, first_expert=lo).apply(
            {"params": mine, "batch_stats": {"expert_bias": bias}}, x)
        assert float(counters["dropped"]) == 0.0
        # the reference, given the same share, gives the same part
        _close(out, _per_seq(lambda s: ref.moe(s, mine, bias, m,
                                               first_expert=lo), x))
        total, pairs = total + out, pairs + float(counters["pairs_here"])
    _close(total, whole)
    assert pairs == B * N * c.top_k  # every pair computed on some chip


@pytest.mark.parametrize("held", ["every_pair", "no_pair"])
def test_no_pair_dropped_whatever_the_imbalance(setup, held):
    """A bias that sends every token to the two held experts fills the
    buffer's worst case with no pair dropped; one that sends every
    token elsewhere gives exactly zero."""
    cfg, _, _, _, m = setup
    c, x = cfg.model.lm, _x(6)
    p, _ = _whole_layer()
    mine = dict(p, **{k: p[k][:2] for k in ("gate", "up", "down")})
    bias = jnp.where(jnp.arange(8) < 2, 10.0, 0.0) * (
        1.0 if held == "every_pair" else -1.0)
    out, counters = _experts(c).apply(
        {"params": mine, "batch_stats": {"expert_bias": bias}}, x)
    assert float(counters["dropped"]) == 0.0
    if held == "every_pair":
        assert float(counters["pairs_here"]) == B * N * c.top_k
        _close(out, _per_seq(lambda s: ref.moe(s, mine, bias, m), x))
    else:
        assert float(counters["pairs_here"]) == 0.0
        assert float(jnp.max(jnp.abs(out))) == 0.0


def test_overflowing_routing_has_the_reference_gradient(setup):
    """The by-group path (every token picks the two held experts: more
    than the usual buffer holds) under autodiff: the gradient of every
    parameter and of the input against the plain reference's."""
    cfg, _, _, _, m = setup
    c, x = cfg.model.lm, _x(8)
    p, _ = _whole_layer()
    mine = dict(p, **{k: p[k][:2] for k in ("gate", "up", "down")})
    bias = jnp.where(jnp.arange(8) < 2, 10.0, 0.0)
    g = jax.random.normal(jax.random.key(9), x.shape)

    def layer(params, x):
        out, counters = _experts(c).apply(
            {"params": params, "batch_stats": {"expert_bias": bias}}, x)
        return jnp.sum(out * g), counters

    def plain(params, x):
        return jnp.sum(_per_seq(lambda s: ref.moe(s, params, bias, m), x) * g)

    (_, counters), got = jax.value_and_grad(layer, (0, 1), has_aux=True)(
        mine, x)
    assert float(counters["pairs_here"]) == B * N * c.top_k  # it overflowed
    want = jax.grad(plain, (0, 1))(mine, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b, 1e-4)


def _buffer(counts, a, tile, unused, rng):
    """An expert-ordered buffer of ragged groups with ``unused`` tiles
    at its end: x, each row's expert (-1 on padding), the tile map."""
    tiles = [max(-(-n // tile), 1) for n in counts]
    rows = (sum(tiles) + unused) * tile
    x, expert_of_row = np.zeros((rows, a), np.float32), np.full(rows, -1)
    r0 = 0
    for i, (n, t) in enumerate(zip(counts, tiles)):
        x[r0:r0 + n] = rng.randn(n, a)
        expert_of_row[r0:r0 + n] = i
        r0 += t * tile
    te = np.repeat(np.arange(len(counts)), tiles).tolist()
    return (jnp.asarray(x), expert_of_row,
            jnp.asarray(te + [te[-1]] * unused, jnp.int32),
            jnp.asarray([sum(tiles)], jnp.int32))


@pytest.mark.parametrize("counts,a,b", [
    ((40, 0, 100, 7), 24, 40), ((0, 0, 0, 64), 24, 40),
    ((16, 16, 16, 16), 24, 40),
    # widths whose widest tile does not divide them (640 = 5 x 128, as
    # 1792 = 14 x 128 at the published width): every block of dw written
    ((20, 3), 640, 128), ((5, 30), 128, 640)])
def test_grouped_matmul_matches_a_loop_over_experts(counts, a, b):
    """Forward, dx and dw, with empty experts and ragged groups."""
    tile = 16
    e = len(counts)
    rng = np.random.RandomState(0)
    # two unused tiles at the end
    x, expert_of_row, te, nu = _buffer(counts, a, tile, 2, rng)
    w = jnp.asarray(rng.randn(e, a, b), jnp.float32)
    g = jnp.asarray(rng.randn(x.shape[0], b), jnp.float32)

    def loop(x, w):
        onehot = (expert_of_row[:, None] == np.arange(e)[None]).astype(
            np.float32)
        return jnp.einsum("re,ra,eab->rb", onehot, x, w)

    def kernel(x, w):
        return grouped_matmul(x, w, te, nu, tile_m=tile)

    _close(kernel(x, w), loop(x, w))
    for i in (0, 1):
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) * g), i)(x, w)
        want = jax.grad(lambda *a: jnp.sum(loop(*a) * g), i)(x, w)
        valid = (expert_of_row >= 0)[:, None] if i == 0 else 1.0
        _close(got * valid, want)


@pytest.mark.parametrize("counts,a,b", [
    # more than one column block (256 = 2 x 128, 384 = 3 x 128 under the
    # caps this test gives ``_tile_n``) and three skipped tiles at the end
    ((40, 0, 100, 7), 128, 256), ((5, 30), 256, 384),
    ((0, 0, 0, 64), 128, 384)])
@pytest.mark.parametrize("what", ["forward", "dx", "dw"])
def test_both_grid_orders_match_the_loop_and_each_other_bit_for_bit(
        monkeypatch, counts, a, b, what):
    tile, e = 16, len(counts)
    rng = np.random.RandomState(1)
    x, expert_of_row, te, nu = _buffer(counts, a, tile, 3, rng)
    w = jnp.asarray(rng.randn(e, a, b), jnp.float32)
    g = jnp.asarray(rng.randn(x.shape[0], b), jnp.float32)
    onehot = jnp.asarray(expert_of_row[:, None] == np.arange(e)[None],
                         jnp.float32)
    tile_n = gm._tile_n
    monkeypatch.setattr(gm, "_tile_n", lambda n, cap=128: tile_n(n, 128))

    def of(product):
        if what == "forward":
            return product(x, w)
        return jax.grad(lambda *t: jnp.sum(product(*t) * g),
                        ("dx", "dw").index(what))(x, w)

    got = {}
    for row_inner in (False, True):
        monkeypatch.setattr(gm, "_row_inner", lambda *_: row_inner)
        assert gm.grid_order(x.shape[0] // tile, tile, e, b) == (
            b // 128, row_inner)
        got[row_inner] = of(lambda x, w: grouped_matmul(x, w, te, nu,
                                                        tile_m=tile))
    assert np.array_equal(np.asarray(got[False]), np.asarray(got[True]))
    want = of(lambda x, w: jnp.einsum("re,ra,eab->rb", onehot, x, w))
    valid = (expert_of_row >= 0)[:, None] if what == "dx" else 1.0
    _close(got[True] * valid, want)
    if what != "dw":  # the skipped tiles' rows are written, as zeros
        assert not np.asarray(got[True])[-3 * tile:].any()


@pytest.mark.parametrize("te,nu,nj,col_inner,row_inner", [
    # one column block: a fetch per run of an expert, in either order
    ([0, 0, 1, 2, 2, 2], 6, 1, 3, 3), ([0, 0, 1, 2, 2, 2], 4, 1, 3, 3),
    # three column blocks: a fetch per step of a used tile / per (run,
    # column block); the skipped tail (te repeats the last used expert)
    # adds none in either order
    ([0, 0, 1, 2], 4, 3, 12, 9), ([0, 0, 1, 2, 2, 2, 2], 4, 3, 12, 9),
    ([0, 1, 1, 1, 1, 1], 3, 2, 6, 4), ([0] * 8, 1, 4, 4, 4),
    # the hybrid cell's usual buffer: 8 experts in 74 multiplied tiles
    # of 140, three column blocks: 222 of 420 steps fetch, or 24
    (sorted(list(range(8)) * 9 + [7, 7]) + [7] * 66, 74, 3, 222, 24)])
def test_weight_block_fetches_counts_the_steps_whose_block_changes(
        te, nu, nj, col_inner, row_inner):
    te = np.asarray(te, np.int32)
    assert int(gm.weight_block_fetches(te, [nu], nj, False)) == col_inner
    assert int(jax.jit(gm.weight_block_fetches, static_argnums=(2, 3))(
        te, jnp.asarray([nu]), nj, True)) == row_inner
    runs = 1 + int(np.sum(te[1:nu] != te[:nu - 1]))
    assert row_inner == runs * nj


@pytest.mark.parametrize("b,tile_m,tiles,nj,row_inner", [
    # every ``_gmm`` shape the three configurations run, forward and dx,
    # by its output's width over the cell's usual buffer
    pytest.param(2688, 256, 140, 3, True, id="nemotron-1024-to-2688"),
    pytest.param(1024, 256, 140, 1, True, id="nemotron-2688-to-1024"),
    pytest.param(1792, 512, 104, 2, True, id="lfm2-2048-to-1792"),
    pytest.param(2048, 512, 104, 2, True, id="lfm2-1792-to-2048"),
    pytest.param(2048, 512, 80, 2, True, id="kimi-1408-to-2048"),
    # 1,408 = 11 x 128 and 11 is prime: eleven column blocks, and x
    # read eleven times would cost more than the matrices do
    pytest.param(1408, 512, 80, 11, False, id="kimi-2048-to-1408")])
def test_the_grid_order_follows_the_bytes_at_the_cells_shapes(
        b, tile_m, tiles, nj, row_inner):
    assert gm.grid_order(tiles, tile_m, 8, b) == (nj, row_inner)


def _routing(case, t, k, experts, held, rng):
    """[t, k] distinct experts per token, held experts 0..held-1."""
    idx = np.stack([rng.permutation(experts)[:k] for _ in range(t)])
    if case == "no_pair_and_every_pair":
        idx[0] = np.arange(held, held + k)       # token 0: none held
        idx[1] = np.arange(k)                    # token 1: all K held
        idx[t // 2:t // 2 + 70] = np.arange(held, held + k)  # a whole tile
    elif case == "empty_expert":
        idx[idx == 1] = experts - 1              # expert 1: padding only
    elif case == "long_group":                   # most pairs to expert 0
        idx[:, 0], idx[:, 1:] = 0, 1 + np.stack(
            [rng.permutation(experts - 1)[:k - 1] for _ in range(t)])
    return jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("case,t,k,held,tile_m,buffer,d", [
    # a token with no held pair, one with all K held, a token tile with
    # no held pair at all (it still writes its zeros)
    ("no_pair_and_every_pair", 320, 2, 2, 16, "usual", 64),
    # a group that is padding only, and padding rows in every other
    ("empty_expert", 192, 2, 3, 32, "usual", 64),
    # one expert's group over several row tiles and several chunks;
    # three token tiles of 64 cut its run twice
    ("long_group", 192, 2, 2, 16, "worst", 64),
    # 128-row chunks, two to a row tile; five token tiles
    ("random", 320, 4, 4, 256, "worst", 128),
    # the worst-case buffer: more rows than pairs
    ("random", 192, 2, 2, 8, "worst", 64),
    # a width whose widest column tile does not divide it
    ("random", 64, 2, 2, 16, "usual", 640)])
def test_unpermute_kernel_matches_take_and_einsum(case, t, k, held, tile_m,
                                                  buffer, d):
    """``combine`` (value, dy, dw) and ``dispatch``'s backward (the
    ``w = 1`` use) against ``jnp.take(mode="fill")`` + ``einsum``."""
    experts = 8
    rng = np.random.RandomState(t + d)
    idx = _routing(case, t, k, experts, held, rng)
    worst = lm.worst_case_tiles(t * k, held, tile_m)
    needed = int(lm.tiles_needed(idx, 0, held, tile_m))
    n_tiles = worst if buffer == "worst" else needed + 1
    row_of_pair, pair_of_row, _, _, _, dropped = lm.plan_dispatch(
        idx, 0, held, tile_m, n_tiles)
    rows = n_tiles * tile_m
    steps = unpermute_steps(pair_of_row, k, t, tile_m, held)
    assert int(dropped) == 0
    assert int(steps[2][0]) <= steps[0].shape[0]  # the list held them all
    assert int(steps[0][-1]) + 1 >= 3 or d == 640  # several token tiles
    take = lambda a, i: jnp.take(a, i, axis=0, mode="fill",  # noqa: E731
                                 fill_value=0)
    tok = jnp.where(pair_of_row >= 0, pair_of_row // k, t)
    y = jnp.asarray(rng.randn(rows, d), jnp.float32) * (
        pair_of_row >= 0)[:, None]
    w = jnp.asarray(rng.rand(t, k), jnp.float32)
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    g_out = jnp.asarray(rng.randn(t, d), jnp.float32)
    g_buf = jnp.asarray(rng.randn(rows, d), jnp.float32)

    def combine(y, w):
        return lm.combine(y, w, row_of_pair, pair_of_row, steps, tile_m)

    def plain_combine(y, w):
        return jnp.einsum("tk,tkd->td", w, take(y, row_of_pair))

    def dispatch(x):
        return lm.dispatch(x, row_of_pair, pair_of_row, steps, tile_m)

    out = combine(y, w)
    _close(out, plain_combine(y, w))
    if case == "no_pair_and_every_pair":
        assert float(jnp.max(jnp.abs(out[0]))) == 0.0
        assert float(jnp.max(jnp.abs(out[t // 2:t // 2 + 64]))) == 0.0
    got = jax.grad(lambda *a: jnp.sum(combine(*a) * g_out), (0, 1))(y, w)
    want = jax.grad(lambda *a: jnp.sum(plain_combine(*a) * g_out),
                    (0, 1))(y, w)
    _close(got[0], want[0])
    _close(got[1], want[1], 1e-4)
    _close(dispatch(x), take(x, tok))
    _close(jax.grad(lambda x: jnp.sum(dispatch(x) * g_buf))(x),
           jax.grad(lambda x: jnp.sum(take(x, tok) * g_buf))(x))


def _sub_jaxprs(params):
    for v in params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            item = getattr(item, "jaxpr", item)
            if hasattr(item, "eqns"):
                yield item


def _intermediates(jaxpr):
    """Every value a jaxpr computes, inner jaxprs included — but not a
    kernel's own body, whose values are blocks in VMEM."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        if eqn.primitive.name != "pallas_call":
            for sub in _sub_jaxprs(eqn.params):
                yield from _intermediates(sub)


def test_expert_layer_builds_nothing_sized_pairs_by_hidden(setup):
    """The guard that the pairs-sized gather does not come back: in the
    gradient of an expert layer (both branches of its cond) only the
    expert-ordered buffers reach T*K*D elements, and nothing has a
    (token, choice) pair per row of a [.., D]- or [.., F]-wide array."""
    cfg, _, v, _, _ = setup
    c, x = cfg.model.lm, _x()
    t, k, d = B * N, c.top_k, x.shape[-1]
    variables = {"params": v["params"]["layer_1"]["moe"],
                 "batch_stats": v["batch_stats"]["layer_1"]["moe"]}
    layer = _experts(c)

    def loss(params, x):
        out, _ = layer.apply(dict(variables, params=params), x)
        return jnp.sum(out * out)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(variables["params"], x)
    tile_m = lm._tile_m(t * k, c.experts_held)
    assert (t * k) % tile_m  # so whole row tiles tell a buffer from pairs
    avals = [a for a in _intermediates(jaxpr.jaxpr) if hasattr(a, "shape")]
    buffers = {a.shape[0] for a in avals
               if a.shape[1:] == (d,) and a.shape[0] % tile_m == 0}
    # the cond's usual buffer; a group's, when the routing overflows it,
    # is no larger (here: as large), and no worst-case buffer of all the
    # tokens at once (5 row tiles) exists
    assert buffers == {3 * tile_m}
    big = [a.shape for a in avals
           if a.size >= t * k * d and a.shape[0] not in buffers]
    per_pair = [a.shape for a in avals if a.ndim >= 2 and a.shape[-1] >= 16
                and a.size // a.shape[-1] == t * k]
    assert not big and not per_pair, (big, per_pair)


# -- what the per-layer remat keeps ------------------------------------------

def _loss_of(model, v, tokens):
    targets = jnp.roll(tokens, -1, 1)

    def prog(p):
        h, _ = model.apply({"params": p, "batch_stats": v["batch_stats"]},
                           tokens, train=True)
        return tied_cross_entropy(h, p["embed"]["embedding"], targets,
                                  chunk=64)

    return prog


def _eqns(jaxpr):
    """Every equation of a jaxpr, inner jaxprs included (not a kernel's
    own body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in _sub_jaxprs(eqn.params):
                yield from _eqns(sub)


def test_named_saves_give_the_gradient_of_no_remat_to_the_last_bit(setup):
    """A saved value IS the value its recompute would give, so keeping
    it changes no bit of any leaf (both arms jitted)."""
    cfg, model, v, tokens, _ = setup
    assert model.remat
    plain = build_model(dataclasses.replace(cfg.model, remat=False))
    grads = [jax.jit(jax.grad(_loss_of(m, v, tokens)))(v["params"])
             for m in (model, plain)]
    flat = jax.tree_util.tree_flatten_with_path(grads[0])[0]
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(grads[1])):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)


@pytest.mark.parametrize("what,per", [
    ("_c_fwd_kernel", "attention"), ("_c_bwd_kernel", "attention"),
    ("sort", "moe"), ("top_k", "moe")])
def test_gradient_runs_each_kernel_and_each_sort_once_a_layer(setup, what,
                                                              per):
    """In the jaxpr of the gradient the causal forward kernel appears
    once per attention layer (twice when the remat saved nothing: the
    forward and its recompute) and the sort and the top-k once per
    expert layer (the sort stood in both branches of the cond, forward
    and recompute: four)."""
    cfg, model, v, tokens, _ = setup
    c = cfg.model.lm
    layers = sum(t == per for t in c.layer_types + c.ffn_types)
    assert layers >= 1
    jaxpr = jax.make_jaxpr(jax.grad(_loss_of(model, v, tokens)))(v["params"])
    found = [eqn for eqn in _eqns(jaxpr.jaxpr) if what == (
        eqn.params["jaxpr"].debug_info.func_name
        if eqn.primitive.name == "pallas_call" else eqn.primitive.name)]
    if what == "sort":
        # of ALL the pairs; the path for routing that overflows the usual
        # buffer sorts a group of tokens at a time, inside its cond branch
        pairs = tokens.size * c.top_k
        found = [q for q in found if q.invars[0].aval.shape == (pairs,)]
    assert len(found) == layers


@pytest.mark.parametrize("op", ["attention", "conv"])
def test_a_rematerialised_block_keeps_the_names_and_no_buffer(setup, op,
                                                              capsys):
    """``print_saved_residuals`` of one block under the model's policy:
    beside its arguments and its output the block keeps the named values
    alone — the kernel's q, k, v and lse, its output (JAX rounds a
    residual that the forward also uses through ``reduce_precision``,
    and prints that), the chosen experts and their scores, the usual
    buffer's plan — and no float with a buffer's rows."""
    import flax.linen as nn
    from jax.ad_checkpoint import print_saved_residuals

    cfg, _, v, _, _ = setup
    c, x = cfg.model.lm, _x()
    layer = "layer_1" if op == "attention" else "layer_2"
    assert (c.layer_types[int(layer[-1])], c.ffn_types[int(layer[-1])]) \
        == (op, "moe")
    variables = {k: v[k][layer] for k in ("params", "batch_stats")}
    block = nn.remat(lm.Block, policy=jax.checkpoint_policies
                     .save_only_these_names(*lm.REMAT_SAVES))(
                         op, "moe", c, jnp.float32)

    def loss(params, x):
        out, _ = block.apply(dict(variables, params=params), x)
        return jnp.sum(out * out)

    print_saved_residuals(loss, variables["params"], x)
    kept = [ln.split(" ", 1) for ln in capsys.readouterr().out.splitlines()
            if " from the argument " not in ln and "from a constant" not in ln]
    t, k = B * N, c.top_k
    named = sorted((shape, why.split("'")[1]) for shape, why in kept
                   if why.startswith("named "))
    tile_m = lm._tile_m(t * k, c.experts_held)
    tiles = -(-(t * k * 3 * c.experts_held)
              // (2 * c.experts * tile_m)) + c.experts_held   # the usual
    steps = tiles * tile_m // min(128, tile_m) + (c.experts_held + 1) * (
        t // min(512, t & -t))
    plan = sorted(
        [(f"i32[{t},{k}]", "plan")] * 2                # idx, row_of_pair
        + [(f"i32[{tiles * tile_m}]", "plan"),         # pair_of_row
           (f"i32[{tiles}]", "plan")]                  # the tiles' experts
        + [(f"i32[{steps}]", "plan")] * 2 + [("i32[1]", "plan")])  # steps
    hq, hkv, hd = c.heads, c.kv_heads, c.head_dim
    floats = sorted(shape for shape, why in kept
                    if shape.startswith("f32") and "named" not in why)
    if op == "attention":
        np_ = -(-N // 128) * 128  # the kernel's padded length
        assert named == sorted(plan + [
            (f"f32[{B * hq},{np_},{hd}]", "flash_qkv"),
            (f"f32[{B * hkv},{np_},{hd}]", "flash_qkv"),
            (f"f32[{B * hkv},{np_},{hd}]", "flash_qkv"),
            (f"f32[{B * hq},{np_}]", "flash_lse")])
        assert floats == sorted([
            f"f32[{t},{k}]",                                  # the scores
            f"f32[{B},{N},{c.hidden}]",                       # the output
            f"f32[{B * hq},{np_},{hd}]"])                     # flash out
    else:
        assert named == plan
        assert floats == sorted([f"f32[{t},{k}]",
                                 f"f32[{B},{N},{c.hidden}]"])
    # and no float with a buffer's rows
    rows = [re.match(r"[fb]\w+\[(\d+)", shape) for shape, _ in kept]
    assert not [m.group(0) for m in rows
                if m and int(m.group(1)) % tile_m == 0]


def test_the_step_says_what_its_remat_saves(setup, caplog):
    """One log line per differentiated trace, counted by the policy
    itself as autodiff asks it; none from a trace that keeps nothing."""
    from distributed_sod_project_tpu.utils.logging import get_logger

    cfg, model, v, tokens, _ = setup
    c = cfg.model.lm
    get_logger().addHandler(caplog.handler)  # "dsod" does not propagate
    try:
        jax.eval_shape(_loss_of(model, v, tokens), v["params"])
        assert not [r for r in caplog.records
                    if "remat saves" in r.getMessage()]
        jax.eval_shape(jax.grad(_loss_of(model, v, tokens)), v["params"])
    finally:
        get_logger().removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records
             if "remat saves" in r.getMessage()]
    assert len(lines) == 1 and lines[0].startswith(
        "remat saves (lfm2, 5 layers): flash_qkv=3 flash_out=1 flash_lse=1 "
        "plan=44 MiB=0.")  # 11 values of the plan in each of 4 layers



def flash_grid_lines(caplog, differentiated, plain):
    """The ``flash grid`` log lines of a differentiated trace and of a
    forward-only one (``log_flash_grid`` speaks where ``log_saves``
    does): two thunks that trace the model."""
    import logging

    logger = logging.getLogger("dsod")  # does not propagate
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="dsod"):
            out = []
            for trace in (differentiated, plain):
                caplog.clear()
                trace()
                out.append([r.getMessage() for r in caplog.records
                            if r.getMessage().startswith("flash grid:")])
    finally:
        logger.removeHandler(caplog.handler)
    return out


def test_the_step_says_its_flash_grid(setup, caplog):
    """160 tokens are one block of 256: one pair a head, of one."""
    _, model, v, tokens, _ = setup
    loss = _loss_of(model, v, tokens)
    said, quiet = flash_grid_lines(
        caplog, lambda: jax.eval_shape(jax.grad(loss), v["params"]),
        lambda: jax.eval_shape(loss, v["params"]))
    assert said == ["flash grid: steps=1 of 1 a head"] and not quiet


# the looped and the LFM2 cell's 8k, the latent-attention and the
# state-space cell's 16k, lengths of 3 and 5 blocks, one short block
@pytest.mark.parametrize("seq_len,line", [
    (8192, "steps=136 of 256"), (16384, "steps=528 of 1024"),
    (1300, "steps=6 of 9"), (2560, "steps=15 of 25"), (100, "steps=1 of 1")])
def test_flash_grid_line_counts_the_kernels_own_pairs(seq_len, line,
                                                      caplog):
    from distributed_sod_project_tpu.pallas.flash_attention import \
        causal_blocks

    said, quiet = flash_grid_lines(
        caplog, lambda: lm.log_flash_grid({"flash_out": 1}, seq_len),
        lambda: lm.log_flash_grid({}, seq_len))
    assert said == [f"flash grid: {line} a head"] and not quiet
    nb = causal_blocks(seq_len)[1]
    assert line == (f"steps={causal_pairs(nb)[0][0].size} of {nb * nb}")


# -- the kernels and the loss ------------------------------------------------

def _plain_causal(q, k, v):
    g = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, g, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    n = q.shape[2]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


# n, block, query heads, kv heads: group sizes 1, 2 and 4; lengths the
# block does not divide (padding rows); 1, 2, 3, 4 and 5 blocks, so that
# the fused backward finishes a q block's dq at the FIRST kv block that
# visits it (block 0, or one block in all) and at a LATER one.
# The last two: the fourth token model's heads (models/ouro.py), 16 / 16
# of 128 columns, no grouping, full lanes.
@pytest.mark.parametrize("n,block,hq,hkv,d", [
    (256, 128, 4, 2, 16), (160, 128, 4, 2, 16), (72, None, 4, 2, 16),
    (384, 128, 4, 2, 16), (72, None, 2, 2, 16), (256, 128, 2, 2, 16),
    (512, 128, 2, 2, 16), (520, 128, 2, 2, 16), (72, None, 4, 1, 16),
    (256, 128, 4, 1, 16), (512, 128, 4, 1, 16), (520, 128, 4, 1, 16),
    (384, 128, 16, 16, 128), (200, None, 16, 16, 128)])
def test_flash_causal_grouped_kv_matches_plain_attention(n, block, hq, hkv,
                                                         d):
    ks = jax.random.split(jax.random.key(n), 4)
    q = jax.random.normal(ks[0], (2, hq, n, d))
    k = jax.random.normal(ks[1], (2, hkv, n, d))
    v = jax.random.normal(ks[2], (2, hkv, n, d))
    g = jax.random.normal(ks[3], (2, hq, n, d))
    flash = lambda *a: flash_attention_causal(*a, block=block)  # noqa: E731
    _close(flash(q, k, v), _plain_causal(q, k, v))
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_plain_causal(*a) * g),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        _close(a, b, 1e-4)


def _causal_grad_jaxpr(hq=4, n=512):
    """The gradient of the causal kernel over q, k and v: 2 sequences of
    ``hq`` bfloat16 query heads on one kv head, blocks of 128."""
    q = jnp.zeros((2, hq, n, 16), jnp.bfloat16)
    kv = jnp.zeros((2, 1, n, 16), jnp.bfloat16)
    return jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(flash_attention_causal(*a, block=128).astype(
            jnp.float32)), (0, 1, 2)))(q, kv, kv)


def _causal_grad_kernels(hq=4, n=512):
    """Its ``pallas_call`` equations: forward, backward."""
    return [eqn for eqn in _eqns(_causal_grad_jaxpr(hq, n).jaxpr)
            if eqn.primitive.name == "pallas_call"]


def _block_index(mapping, tables):
    """(grid indices) -> block index, from a ``pallas_call`` block
    mapping whose index map reads scalar-prefetched ``tables``."""
    from jax._src.state.discharge import discharge_state

    closed = mapping.index_map_jaxpr
    pure, consts = discharge_state(closed.jaxpr, closed.consts)
    n_out = len(closed.jaxpr.outvars)
    at = jax.jit(lambda *step: jax.core.eval_jaxpr(
        pure, consts, *step, *tables)[:n_out])
    return lambda *step: tuple(
        int(x) for x in at(*(jnp.int32(x) for x in step)))


def test_flash_causal_gradient_is_two_kernels_and_no_partial_sums():
    """Forward and ONE backward kernel; the backward's three results are
    dq, dk and dv themselves, in the operands' type and shape — no
    float32 partial sums of dq leave the kernel to be added up in HBM."""
    fwd, bwd = _causal_grad_kernels()
    names = [e.params["jaxpr"].debug_info.func_name for e in (fwd, bwd)]
    assert names == ["_c_fwd_kernel", "_c_bwd_kernel"]
    assert [(v.aval.shape, v.aval.dtype) for v in bwd.outvars] == [
        ((8, 512, 16), jnp.bfloat16)] + [((2, 512, 16), jnp.bfloat16)] * 2


@pytest.mark.parametrize("hq,n", [(4, 512), (2, 128), (4, 640)])
def test_flash_causal_backward_writes_each_dq_block_once(hq, n):
    """Walk the backward's grid in the order the chip does and follow
    dq's output block: the pipeline flushes a block when its index moves
    on, so every (head, q block) has to come up in ONE unbroken run of
    steps (a second run would overwrite what the first flushed) and that
    run has to hold the diagonal pair, the only step that writes it."""
    jaxpr = _causal_grad_jaxpr(hq=hq, n=n)
    bwd = [eqn for eqn in _eqns(jaxpr.jaxpr)
           if eqn.primitive.name == "pallas_call"][1]
    gm = bwd.params["grid_mapping"]
    nb = n // 128
    tables = causal_pairs(nb, hq)[1]
    # the tables the traced program carries are the helper's
    for table in tables:
        assert any(np.array_equal(c, table) for c in jaxpr.consts)
    assert gm.grid == (2, hq * nb * (nb + 1) // 2)
    dq_at = _block_index(gm.block_mappings[gm.num_inputs], tables)
    runs, wrote = [], []
    for b, pair in np.ndindex(*gm.grid):
        i, g, j = (int(t[pair]) for t in tables)
        at = dq_at(b, pair)
        if not runs or runs[-1] != at:
            runs.append(at)
            wrote.append(0)
            assert j == i   # a run opens on the diagonal ...
        if j == i:   # ... the kernel's ``pl.when(j == i)``
            wrote[-1] += 1
            assert at == (b * hq + g, i, 0)
    assert len(set(runs)) == len(runs) == 2 * hq * nb
    assert wrote == [1] * len(runs)


@pytest.mark.parametrize("hq,n", [(4, 512), (1, 640), (2, 128)])
def test_flash_causal_grids_hold_the_pairs_under_the_diagonal_alone(hq, n):
    """The grid each ``pallas_call`` equation carries is (heads, pairs):
    ``causal_pairs``' count, the one ``log_flash_grid`` prints, and not
    the rectangle's."""
    fwd, bwd = _causal_grad_kernels(hq=hq, n=n)
    nb = n // 128
    (q_of, _), (kv_of, _, _) = causal_pairs(nb, hq)
    assert q_of.size == nb * (nb + 1) // 2 and kv_of.size == hq * q_of.size
    assert fwd.params["grid_mapping"].grid == (2 * hq, q_of.size)
    assert bwd.params["grid_mapping"].grid == (2, kv_of.size)
    for eqn, tables in ((fwd, 2), (bwd, 3)):
        assert eqn.params["grid_mapping"].num_index_operands == tables


# nb = 5 (no power of two) and a length the block does not divide (nb =
# 5 with padding rows), one head a kv head and four, both float types
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,hq,hkv", [(640, 2, 2), (640, 4, 1),
                                      (600, 2, 2), (600, 4, 1)])
def test_flash_causal_pair_grid_matches_plain_attention(n, hq, hkv, dtype):
    """Forward, dq, dk and dv over a grid of 15 pairs a head (of 25)."""
    ks = jax.random.split(jax.random.key(n + hq), 4)
    q, k, v, g = (jax.random.normal(key, (2, h, n, 32)).astype(dtype)
                  for key, h in zip(ks, (hq, hkv, hkv, hq)))
    f32 = lambda *a: [t.astype(jnp.float32) for t in a]  # noqa: E731
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    flash = lambda *a: flash_attention_causal(*a, block=128)  # noqa: E731
    out = flash(q, k, v)
    assert out.dtype == dtype
    _close(out.astype(jnp.float32), _plain_causal(*f32(q, k, v)), tol)
    got = jax.grad(lambda *a: jnp.sum((flash(*a) * g).astype(jnp.float32)),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_plain_causal(*a) * f32(g)[0]),
                    (0, 1, 2))(*f32(q, k, v))
    for a, b in zip(got, want):
        assert a.dtype == dtype
        _close(a.astype(jnp.float32), b, 5 * tol)


def test_flash_causal_backward_refuses_a_group_its_vmem_cannot_hold(
        monkeypatch):
    """The dq accumulators hold the whole sequence of a kv head's G query
    heads in VMEM, a 128-lane row each: the scoped limit the call asks
    for is what the shapes derive (both cells' sizes), and on a chip
    whose VMEM they pass it raises and names the need.  Shapes only:
    nothing runs."""
    from distributed_sod_project_tpu.pallas import vmem_budget as vb
    from distributed_sod_project_tpu.pallas.flash_attention import (
        _c_bwd_call, _causal_bwd_vmem_bytes)

    monkeypatch.setattr(vb, "_device_kind", lambda: "TPU v5 lite")

    def call(n, hq=32, hkv=8):
        bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
        return jax.eval_shape(
            lambda *a: _c_bwd_call(*a, (512, hq // hkv, False)),
            bf(hq, n, 64), bf(hkv, n, 64), bf(hkv, n, 64), bf(hq, n, 64),
            jax.ShapeDtypeStruct((hq, n), jnp.float32), bf(hq, n, 64))

    assert [t.shape for t in call(16384)] == [
        (32, 16384, 64), (8, 16384, 64), (8, 16384, 64)]
    for n, acc_mib in ((8192, 16), (16384, 32)):
        need = _causal_bwd_vmem_bytes(4, n, 512, 64, 2)
        assert 14 * 2**20 < need - acc_mib * 2**20 < 16 * 2**20
    with pytest.raises(ValueError, match=r"65536 rows of 4 heads a kv head "
                                         r"needs 143\.0 MiB of VMEM; a TPU "
                                         r"v5 lite has 128 MiB"):
        call(65536)
    assert call(65536, hq=8)[0].shape == (8, 65536, 64)  # groups of one


@pytest.mark.parametrize("chunk", [64, 100, 4096])
def test_chunked_loss_equals_the_unchunked_one(chunk):
    ks = jax.random.split(jax.random.key(2), 3)
    h = jax.random.normal(ks[0], (2, 160, 32))
    e = jax.random.normal(ks[1], (96, 32))
    t = jax.random.randint(ks[2], (2, 160), 0, 96)

    def plain(h, e):
        z = h.reshape(-1, 32) @ e.T
        return jnp.mean(jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, t.reshape(-1, 1), -1)[:, 0])

    chunked = lambda h, e: tied_cross_entropy(h, e, t, chunk=chunk)  # noqa: E731
    lp, gp = jax.value_and_grad(plain, (0, 1))(h, e)
    lc, gc = jax.value_and_grad(chunked, (0, 1))(h, e)
    assert abs(float(lp) - float(lc)) < 1e-5
    for a, b in zip(gc, gp):
        _close(a, b, 1e-4)


# -- scopes ------------------------------------------------------------------

SCOPES = ("dsod.moe.route", "dsod.moe.experts", "dsod.moe.combine",
          "dsod.attn", "dsod.shortconv", "dsod.densemlp",
          "dsod.kernel.grouped_matmul", "dsod.kernel.grouped_matmul_dw",
          "dsod.kernel.moe_unpermute",
          "dsod.kernel.flash_attention_causal",
          "dsod.kernel.flash_attention_causal_bwd")
_STAGE = re.compile(r"dsod\.(encoder|decoder|heads|loss|update)\b")


def _scope_paths(name):
    from test_profiler_names import _lowered_step_text

    return re.findall(r'^#loc\d+ = loc\("([^"]*)"',
                      _lowered_step_text(name), re.M)


@pytest.fixture(scope="module")
def lowered_paths():
    return _scope_paths("lfm2_8b_a1b_ep4")


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_names_the_token_models_scopes(lowered_paths, scope):
    under = [p for p in lowered_paths if re.search(re.escape(scope) + r"\b", p)]
    assert under, scope
    # inside the encoder stage and no other (a jitted helper called
    # under jax.checkpoint lowers to a function of its own, whose ops
    # carry the path from the checkpoint down: no stage, never another)
    stages = [set(_STAGE.findall(p)) for p in under]
    assert {"encoder"} in stages and all(s <= {"encoder"} for s in stages)


def test_image_models_step_names_none_of_them():
    paths = _scope_paths("basnet_ds")
    assert not [p for p in paths if re.search(
        r"dsod\.(moe|attn|shortconv|densemlp)\b"
        r"|dsod\.kernel\.(grouped_matmul|flash_attention_causal"
        r"|moe_unpermute)", p)]


# -- data and the loop -------------------------------------------------------

def test_packed_tokens_are_deterministic_zipf_documents():
    from distributed_sod_project_tpu.data.tokens import EOD, PackedTokens
    from distributed_sod_project_tpu.utils.checks import validate_first_batch

    def check(batch, vocab=1024):  # fit()'s, through the model's kind
        cfg = _cfg("data.seq_len=4096", f"model.lm.vocab={vocab}")
        validate_first_batch(batch, cfg, build_model(cfg.model))

    ds = PackedTokens(size=8, seq_len=4096, vocab=1024)
    a, b = ds[5], ds[5]
    assert np.array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].dtype == np.int32 and a["tokens"].shape == (4096,)
    assert np.array_equal(a["tokens"][1:], a["targets"][:-1])
    assert not np.array_equal(a["tokens"], ds[6]["tokens"])
    ids = np.concatenate([ds[i]["tokens"] for i in range(8)])
    assert 0 <= ids.min() and ids.max() < 1024
    ends = int((ids == EOD).sum())  # mean length 512 * exp(0.72) ~ 1050
    assert 8 <= ends <= 120
    counts = np.bincount(ids, minlength=1024)[1:]
    assert counts[:8].sum() > counts[512:].sum()  # Zipf: few ids, most mass
    batch = {k: np.stack([ds[i][k] for i in range(2)]) for k in a}
    check(batch)
    with pytest.raises(ValueError, match="outside"):
        check(batch, vocab=512)
    with pytest.raises(ValueError, match="shifted"):
        check(dict(batch, targets=batch["tokens"]))
    with pytest.raises(ValueError, match="missing 'tokens'"):
        check({"image": batch["tokens"]})


def test_three_steps_of_fit_at_tiny_size(tmp_path):
    from distributed_sod_project_tpu.train.loop import fit

    cfg = _cfg("log_every_steps=1", "data.num_workers=2", "tensorboard=false",
               "checkpoint_every_steps=100").replace(
                   checkpoint_dir=str(tmp_path / "ck"))
    seen = []
    out = fit(cfg, max_steps=3,
              hooks={"on_metrics": lambda step, host: seen.append(host)})
    assert out["final_step"] == 3 and len(seen) == 3
    for host in seen:
        assert np.isfinite(host["total"]) and host["grad_norm"] > 0
        assert host["moe_dropped_pairs"] == 0.0
        assert 0.1 < host["moe_pairs_here_share"] < 0.45
        assert host["moe_load_max_over_mean"] >= 1.0
        assert "data_starved_ms" in host
    assert abs(seen[0]["total"] - np.log(512)) < 1.5
