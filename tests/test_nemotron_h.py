"""The fifth token model (models/nemotron_h.py, config
``nemotron_3_super_tp8_ep64``) against its plain reference
(benchmark/reference/nemotron_h.py) on the CPU at tiny widths, float32,
seeded weights (benchmark/harness/weights_hybrid.py):

- the whole 11-layer pattern: hidden states, counters, loss, every
  gradient leaf and one AdamW update of it; three optimizer steps of the
  compiled train step against the reference's ``follow``, the balancing
  bias among them, on a pattern of three layers (one of each kind: the
  step's compile is most of this file's time);
- **the shares add up**: the 8 head shares of one Mamba-2 mixer (the
  uncut reference has 8 B/C groups), the 8 head shares of the attention
  layer (key-value heads 0 and 1) and the expert shares of one latent
  expert layer (the latent projections, the router and the shared
  expert counted once) each sum to the uncut reference layer; a routing
  that overflows the usual buffer is taken by group with no pair dropped;
- the scan's carried state is float32 at 16 heads and chunk 128;
- each planted fault of ``benchmark/tests/hybrid_faults.py`` fails the
  small comparison;
- what a rematerialised layer keeps by name, and the step's log line;
- every new ``dsod.*`` scope in the lowered step, inside the encoder
  stage;
- three steps of ``fit()`` with both families of counters on the stream.
"""

import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.weights_hybrid import variables_builder
from benchmark.reference import nemotron_h as ref
from benchmark.tests.hybrid_faults import FAULTS, plant
from distributed_sod_project_tpu.configs import apply_overrides, get_config
from distributed_sod_project_tpu.losses.token_ce import tied_cross_entropy
from distributed_sod_project_tpu.models import build_model
from distributed_sod_project_tpu.models import granite as gr
from distributed_sod_project_tpu.models import nemotron_h as nh
from distributed_sod_project_tpu.pallas import ssd_scan as ssd

TINY = ["model.lm.vocab=512", "model.lm.hidden=64", "model.lm.heads=4",
        "model.lm.kv_heads=1", "model.lm.head_dim=16",
        "model.lm.expert_width=48", "model.lm.latent_width=32",
        "model.lm.shared_width=96", "model.lm.experts=16",
        "model.lm.experts_held=4", "model.lm.top_k=3",
        "model.lm.ssm_heads=8", "model.lm.ssm_head_dim=16",
        "model.lm.ssm_state=16", "model.lm.ssm_chunk=32", "data.seq_len=128",
        "data.vocab=512", "data.synthetic_size=32", "global_batch_size=2",
        "model.compute_dtype=float32"]
SHORT = ["model.lm.layer_types=mamba,moe,attention"]  # the fault tests'
RECIPE = {"expert_bias_std": 0.05, "time_step_min": 0.001,
          "time_step_max": 0.1, "time_step_floor": 1e-4}
B, N = 2, 128  # four chunks of 32 tokens
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


def _cfg(*more):
    return apply_overrides(get_config("nemotron_3_super_tp8_ep64"),
                           TINY + list(more))


def _arch(c):
    """The reference's ``arch`` (configs/nemotron_3_super_tp8_ep64.json)
    at the program's tiny shape."""
    return dict(layer_types=c.layer_types, heads=c.heads,
                kv_heads=c.kv_heads, head_dim=c.head_dim,
                ssm_heads=c.ssm_heads, ssm_head_dim=c.ssm_head_dim,
                ssm_state=c.ssm_state, ssm_groups=1, norm_eps=c.norm_eps,
                top_k=c.top_k, first_expert=c.first_expert,
                routed_scaling_factor=c.routed_scaling_factor,
                bias_update_rate=c.bias_update_rate)


def _variables(model, tokens, seed=7):
    shapes = jax.eval_shape(lambda r, t: model.init(r, t),
                            jax.random.key(1), tokens)
    return variables_builder({"params": shapes["params"],
                              "batch_stats": shapes["batch_stats"]},
                             RECIPE)(seed)


def _setup(*more):
    cfg = _cfg(*more)
    model = build_model(cfg.model)
    tokens = jax.random.randint(jax.random.key(0), (B, N), 0, 512)
    return cfg, model, _variables(model, tokens), tokens, _arch(cfg.model.lm)


@pytest.fixture(scope="module")
def setup():
    return _setup()


@pytest.fixture(scope="module")
def short():
    return _setup(*SHORT)


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol * float(np.max(np.abs(b)) + 1e-12))


def _x(seed=3):
    return jax.random.normal(jax.random.key(seed), (B, N, 64))


def _per_seq(fn, x):
    return jnp.stack([fn(x[i]) for i in range(x.shape[0])])


# -- the whole model ---------------------------------------------------------

def _loss_of(model, v, tokens):
    def prog(p):
        h, _ = model.apply({"params": p, "batch_stats": v["batch_stats"]},
                           tokens, train=True)
        return tied_cross_entropy(h, p["head"]["embedding"],
                                  jnp.roll(tokens, -1, 1))

    return prog


def _plain(v, tokens, m):
    return lambda p: ref.batch_loss(
        {"params": p, "batch_stats": v["batch_stats"]}, tokens,
        jnp.roll(tokens, -1, 1), m)


def test_the_pattern_is_one_period_in_the_published_ratio():
    lm = get_config("nemotron_3_super_tp8_ep64").model.lm
    assert lm.layer_types == tuple(nh.PATTERN[c] for c in "MEMEMEM*EME")
    assert [lm.layer_types.count(k) for k in ("mamba", "moe", "attention")] \
        == [5, 5, 1]
    # every width as published, the chip's share of heads and experts
    assert (lm.hidden, lm.head_dim, lm.ssm_head_dim, lm.ssm_state,
            lm.ssm_conv, lm.ssm_chunk) == (4096, 128, 64, 128, 4, 128)
    assert (lm.expert_width, lm.latent_width, lm.shared_width, lm.experts,
            lm.top_k, lm.routed_scaling_factor) == (2688, 1024, 5376, 512,
                                                    22, 5.0)
    assert (lm.ssm_heads, lm.heads, lm.kv_heads, lm.experts_held,
            lm.vocab) == (16, 4, 1, 8, 16384)


def test_hidden_states_counters_loss_and_every_gradient_match_reference(
        setup):
    cfg, model, v, tokens, m = setup
    h, counters = model.apply(v, tokens)
    sent = []
    for b in range(B):
        hb, sb = ref.hidden(v, tokens[b], m)
        _close(h[b], hb, 1e-4)
        sent.append(sb)
    assert set(counters) == {"ssm_decay_min", "ssm_delta_max",
                             "moe_pairs_here_share", "moe_load_max_over_mean",
                             "moe_dropped_pairs", "moe_pairs_here_share_max",
                             "moe_buffer_fill_max", "moe_weight_fetch_share"}
    assert float(counters["moe_dropped_pairs"]) == 0.0
    # the hottest layer's share of the pairs, and of its usual buffer
    per_layer = [sum(float(jnp.sum(s[name][:4])) for s in sent)
                 for name in sent[0]]
    assert float(counters["moe_pairs_here_share_max"]) == pytest.approx(
        max(per_layer) / (B * N * 3), rel=1e-6)
    assert float(counters["moe_pairs_here_share_max"]) >= float(
        counters["moe_pairs_here_share"])
    assert 0 < float(counters["moe_buffer_fill_max"]) <= 1
    # the up-projection copies a weight block in on fewer steps than it has
    assert 0 < float(counters["moe_weight_fetch_share"]) < 1
    held = np.mean([sum(float(jnp.sum(s[name][:4])) for s in sent)
                    for name in sent[0]])
    assert float(counters["moe_pairs_here_share"]) == pytest.approx(
        held / (B * N * 3), rel=1e-6)
    prog, plain = _loss_of(model, v, tokens), _plain(v, tokens, m)
    (lp, gp), (lr, gr_) = (jax.jit(jax.value_and_grad(f))(v["params"])
                           for f in (prog, plain))
    assert abs(float(lp) - float(lr)) < 1e-5 * float(lr)
    flat = jax.tree_util.tree_flatten_with_path(gr_)[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(gp)) == 93
    for (path, want), got in zip(flat, jax.tree_util.tree_leaves(gp)):
        try:
            _close(got, want, 2e-4)
        except AssertionError as e:
            raise AssertionError(jax.tree_util.keystr(path)) from e
    # one AdamW update of that gradient: the program's optimizer chain
    # against the reference's plain rule, leaf by leaf
    import optax

    from distributed_sod_project_tpu.train import build_optimizer

    cfg = apply_overrides(cfg, ["optim.warmup_steps=2"])
    tx, _ = build_optimizer(cfg.optim, 50)
    # (two calls: the warm-up starts from a rate of 0)
    stepped, state = v["params"], tx.init(v["params"])
    opt = dict(kind="adamw", lr=cfg.optim.lr, weight_decay=0.1,
               warmup_steps=2, poly_power=0.9, total_steps=50)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, v["params"])
    want, moments = v["params"], {"m": zeros, "v": zeros}
    for i in range(2):
        updates, state = tx.update(gp, state, stepped)
        stepped = optax.apply_updates(stepped, updates)
        want, moments = ref.adamw_update(opt, want, gr_, moments,
                                         jnp.float32(i))
    moved = jax.tree_util.tree_map(jnp.subtract, stepped, v["params"])
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(moved)[0],
            jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                jnp.subtract, want, v["params"]))):
        # (Adam's first ratios m / sqrt(v) are signs: where a gradient
        # element is all but zero its sign is rounding, so whole leaves)
        assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(a - b)) <= 2e-3 * float(
            jnp.linalg.norm(b)), jax.tree_util.keystr(path)


def _three_steps(cfg, model, v, batches, warmup=2):
    from distributed_sod_project_tpu.parallel import make_mesh
    from distributed_sod_project_tpu.parallel.engine import \
        make_unified_train_step
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    cfg = apply_overrides(cfg, [f"optim.warmup_steps={warmup}"])
    tx, sched = build_optimizer(cfg.optim, 50)
    state = create_train_state(jax.random.key(0), model, tx, batches[0])
    state = state.replace(params=v["params"], batch_stats=v["batch_stats"])
    step = make_unified_train_step(
        model, cfg.loss, tx, make_mesh(cfg.mesh, jax.devices()[:1]),
        preset="dp", schedule=sched, donate=False)
    losses = []
    for b in batches:
        state, metrics = step(state, b)
        losses.append(float(metrics["total"]))
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.sqrt(jnp.sum(jnp.square(a - b)))),
        state.params, v["params"])
    return losses, moved, state, metrics


def _batches():
    return [{"tokens": np.asarray(t), "targets": np.roll(t, -1, 1)}
            for t in np.asarray(jax.random.randint(
                jax.random.key(5), (3, B, N), 0, 512))]


def _follow(cfg, v, m):
    opt = dict(kind="adamw", lr=cfg.optim.lr, weight_decay=0.1,
               warmup_steps=2, poly_power=0.9, total_steps=50)
    return ref.follow(lambda: jax.tree_util.tree_map(jnp.array, v),
                      _batches(), {"arch": m, "optimizer": opt})


@pytest.fixture(scope="module")
def short_followed(short):
    cfg, _, v, _, m = short
    return _follow(cfg, v, m)


def test_three_steps_follow_the_reference(short, short_followed):
    """The compiled train step itself (``make_unified_train_step``, dp
    preset) from the benchmark's weights on three batches: losses, every
    leaf's change, the balancing bias."""
    cfg, model, v, _, _ = short
    followed = short_followed
    losses, moved, state, metrics = _three_steps(cfg, model, v, _batches())
    np.testing.assert_allclose(losses, followed["loss"], rtol=2e-5)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(moved)[0],
            jax.tree_util.tree_leaves(followed["dparam_norms"])):
        assert a == pytest.approx(float(b), rel=2e-3), \
            jax.tree_util.keystr(path)
        assert a > 0, jax.tree_util.keystr(path)
    for got, want in zip(jax.tree_util.tree_leaves(state.batch_stats),
                         jax.tree_util.tree_leaves(followed["expert_bias"])):
        np.testing.assert_allclose(got, want, atol=1e-7)
    assert set(metrics) >= {"total", "grad_norm", "moe_bias_abs_max",
                            "moe_pairs_here_share", "ssm_decay_min"}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(short, short_followed, fault,
                                              monkeypatch):
    """Each fault on the program, the reference as published: the first
    batch's loss (forward only; a sound step reads 2e-5 there, the test
    above)."""
    cfg, _, v, _, _ = short
    faulty = build_model(apply_overrides(
        cfg, plant(fault, monkeypatch.setattr)).model)
    batch = _batches()[0]
    loss = jax.jit(lambda p: tied_cross_entropy(
        faulty.apply({"params": p, "batch_stats": v["batch_stats"]},
                     batch["tokens"], train=True)[0],
        p["head"]["embedding"], batch["targets"]))(v["params"])
    want = short_followed["loss"][0]
    assert abs(float(loss) - want) / want > 1e-4, (fault, float(loss), want)


# -- the shares add up -------------------------------------------------------

def _normal(seed, *shape):
    return jax.random.normal(jax.random.key(seed), shape) * shape[0] ** -0.5


def test_the_eight_head_shares_of_a_mamba_mixer_add_up():
    """An uncut mixer of 16 heads in 8 B/C groups (the reference's
    ``ssm_groups``) against the sum of 8 of the program's mixers, each
    given one group, its 2 heads and the rows of ``out_proj`` they
    feed: the group norm of the uncut layer IS the share's whole norm."""
    g, hg, p, s, d = 8, 2, 16, 16, 64
    h, inner = g * hg, g * hg * p
    uncut = {
        "in_proj": {"kernel": _normal(1, d, 2 * inner + 2 * g * s + h)},
        "conv": {"kernel": _normal(2, 4, inner + 2 * g * s),
                 "bias": 0.1 * _normal(3, inner + 2 * g * s)},
        "A_log": jnp.log(jnp.linspace(1.0, 4.0, h)),
        "dt_bias": jnp.linspace(-3.0, -1.0, h),
        "D": jnp.linspace(0.5, 1.5, h),
        "norm": {"scale": jnp.linspace(0.5, 1.5, inner)},
        "out_proj": {"kernel": _normal(4, inner, d)}}
    m = dict(ssm_heads=h, ssm_head_dim=p, ssm_state=s, ssm_groups=g,
             norm_eps=1e-5)
    x = _x()
    want = _per_seq(lambda u: ref.mamba(u, uncut, m), x)

    def share(i):
        cols = lambda lo, w: slice(lo + i * w, lo + (i + 1) * w)  # noqa: E731
        z, xs = cols(0, hg * p), cols(inner, hg * p)
        bs, cs = cols(2 * inner, s), cols(2 * inner + g * s, s)
        dts = cols(2 * inner + 2 * g * s, hg)
        heads = slice(i * hg, (i + 1) * hg)
        w_in, conv = uncut["in_proj"]["kernel"], uncut["conv"]
        xbc = lambda t: jnp.concatenate(  # noqa: E731  this share's x | B | C
            [t[..., xs.start - inner:xs.stop - inner],
             t[..., bs.start - inner:bs.stop - inner],
             t[..., cs.start - inner:cs.stop - inner]], -1)
        return {
            "in_proj": {"kernel": jnp.concatenate(
                [w_in[:, z], w_in[:, xs], w_in[:, bs], w_in[:, cs],
                 w_in[:, dts]], 1)},
            "conv": {"kernel": xbc(conv["kernel"]), "bias": xbc(conv["bias"])},
            "A_log": uncut["A_log"][heads], "dt_bias": uncut["dt_bias"][heads],
            "D": uncut["D"][heads],
            "norm": {"scale": uncut["norm"]["scale"][z]},
            "out_proj": {"kernel": uncut["out_proj"]["kernel"][z]}}

    mixer = gr.Mamba2Mixer(hg, p, s, 4, 32, 1e-5, **F32)
    got = sum(mixer.apply({"params": share(i)}, x)[0] for i in range(g))
    _close(got, want, 1e-4)
    # ... and one share alone is the reference given the same share
    _close(mixer.apply({"params": share(3)}, x)[0], _per_seq(
        lambda u: ref.mamba(u, share(3), dict(m, ssm_heads=hg,
                                              ssm_groups=1)), x), 1e-4)


def test_the_eight_head_shares_of_the_attention_layer_add_up():
    """16 query heads on 2 key-value heads, uncut, against the sum of 8
    of the program's layers of 2 query heads on ONE key-value head:
    shares 0-3 read key-value head 0, shares 4-7 head 1."""
    hq, hkv, hd, d, n_shares = 16, 2, 16, 64, 8
    per = hq // n_shares
    uncut = {"q_proj": {"kernel": _normal(1, d, hq * hd)},
             "k_proj": {"kernel": _normal(2, d, hkv * hd)},
             "v_proj": {"kernel": _normal(3, d, hkv * hd)},
             "o_proj": {"kernel": _normal(4, hq * hd, d)}}
    m = dict(heads=hq, kv_heads=hkv, head_dim=hd)
    x = _x()
    want = _per_seq(lambda u: ref.attention(u, uncut, m), x)

    def share(i):
        q = slice(i * per * hd, (i + 1) * per * hd)
        kv = i * per // (hq // hkv)
        kvs = slice(kv * hd, (kv + 1) * hd)
        return {"q_proj": {"kernel": uncut["q_proj"]["kernel"][:, q]},
                "k_proj": {"kernel": uncut["k_proj"]["kernel"][:, kvs]},
                "v_proj": {"kernel": uncut["v_proj"]["kernel"][:, kvs]},
                "o_proj": {"kernel": uncut["o_proj"]["kernel"][q]}}

    layer = gr.Attention(per, 1, hd, **F32)
    got = sum(layer.apply({"params": share(i)}, x) for i in range(n_shares))
    _close(got, want, 1e-4)


@pytest.mark.parametrize("capacity,whole", [
    (nh.CAPACITY, nh.WHOLE),  # the model's own: 22 tiles, 13 multiplied
    (8.0, 6.0),   # 16 tiles of the worst case's 28, 13 of them multiplied
    (8.0, 1.0)])  # ... 6 multiplied: the routing needs 8, and gets them
def test_the_expert_shares_of_a_latent_expert_layer_add_up(
        monkeypatch, capacity, whole):
    """16 routed experts, uncut, against the sum of 4 of the program's
    layers of 4 held experts each (experts 0-3, 4-7, 8-11, 12-15), all
    given the same router, bias and latent projections — ``latent_up``
    is linear, so the shares' parts add up after it — plus the shared
    expert counted once.  Whatever part of the usual buffer is
    multiplied empty."""
    monkeypatch.setattr(nh, "CAPACITY", capacity)
    monkeypatch.setattr(nh, "WHOLE", whole)
    e, held, k, d, lat, f = 16, 4, 3, 64, 32, 48
    uncut = {"router": {"kernel": _normal(1, d, e)},
             "latent_down": {"kernel": _normal(2, d, lat)},
             "latent_up": {"kernel": _normal(3, lat, d)},
             "up": jax.vmap(lambda i: _normal(i, lat, f))(jnp.arange(e)),
             "down": jax.vmap(lambda i: _normal(i + 99, f, lat))(
                 jnp.arange(e))}
    shared = {"up": {"kernel": _normal(5, d, 96)},
              "down": {"kernel": _normal(6, 96, d)}}
    bias = 0.05 * jax.random.normal(jax.random.key(7), (e,))
    m = dict(top_k=k, first_expert=0, routed_scaling_factor=5.0)
    x = _x()
    want = _per_seq(lambda u: ref.moe(u, uncut, shared, bias, m)[0], x)

    def routed(first):
        layer = nh.LatentExpertLayer(e, held, first, k, f, lat, True, 5.0,
                                     1e-20, **F32)
        own = slice(first, first + held)
        out, counters = layer.apply(
            {"params": dict(uncut, up=uncut["up"][own],
                            down=uncut["down"][own]),
             "batch_stats": {"expert_bias": bias}}, x)
        assert float(counters["dropped"]) == 0.0
        return out, float(counters["pairs_here"])

    parts = [routed(first) for first in range(0, e, held)]
    assert sum(p for _, p in parts) == B * N * k  # every pair held once
    got = sum(o for o, _ in parts) + nh.ReLU2MLP(96, **F32).apply(
        {"params": shared}, x)
    _close(got, want, 1e-4)


def test_a_routing_that_overflows_the_usual_buffer_drops_no_pair(
        monkeypatch):
    """A selection bias that sends EVERY token's three choices to the
    four held experts: 768 pairs where a usual buffer of 1.5 x the
    balanced share holds 13 tiles of 32 rows (at the model's own factor
    a quarter of the experts held leaves nothing to overflow), so the
    layer takes the tokens a group at a time (the other branch of its
    cond).  Output and the gradient of the input against the reference,
    which has no buffer to overflow."""
    monkeypatch.setattr(nh, "CAPACITY", 1.5)
    e, held, k, d, lat, f = 16, 4, 3, 64, 32, 48
    params = {"router": {"kernel": _normal(1, d, e)},
              "latent_down": {"kernel": _normal(2, d, lat)},
              "latent_up": {"kernel": _normal(3, lat, d)},
              "up": jax.vmap(lambda i: _normal(i, lat, f))(jnp.arange(held)),
              "down": jax.vmap(lambda i: _normal(i + 9, f, lat))(
                  jnp.arange(held))}
    bias = jnp.where(jnp.arange(e) < held, 10.0, 0.0)
    m = dict(top_k=k, first_expert=0, routed_scaling_factor=5.0)
    none = {"up": {"kernel": jnp.zeros((d, 8))},
            "down": {"kernel": jnp.zeros((8, d))}}
    layer = nh.LatentExpertLayer(e, held, 0, k, f, lat, True, 5.0, 1e-20,
                                 **F32)
    v = {"params": params, "batch_stats": {"expert_bias": bias}}
    x = _x()
    out, counters = layer.apply(v, x)
    assert float(counters["pairs_here"]) == B * N * k
    assert float(counters["dropped"]) == 0.0
    plain = lambda x: _per_seq(  # noqa: E731
        lambda u: ref.moe(u, params, none, bias, m)[0], x)
    _close(out, plain(x), 1e-4)
    got, want = (jax.grad(lambda x: jnp.sum(jnp.sin(fn(x))))(x) for fn in (
        lambda x: layer.apply(v, x)[0], plain))
    _close(got, want, 1e-4)


# -- the scan at the share's shape -------------------------------------------

def test_the_scan_carries_its_state_in_float32_at_16_heads_and_chunk_128():
    """The share's scan: 16 heads of 64, state 128, chunk 128 (two slabs
    of 8 heads where the state-space cell runs eight).  The forward
    kernel writes y in the operands' type and the state each chunk
    started from in float32, and matches the recurrence across the
    chunk's edge."""
    from test_lfm2 import _eqns

    assert ssd.STATE_DTYPE == jnp.float32
    ks = jax.random.split(jax.random.key(0), 5)
    n, h, p, s = 256, 16, 64, 128
    x = jax.random.normal(ks[0], (1, n, h, p))
    dt = jnp.exp(jax.random.uniform(ks[1], (1, n, h), minval=np.log(0.02),
                                    maxval=np.log(0.1)))
    a = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=3.0)
    b, c = (jax.random.normal(k, (1, n, s)) for k in ks[3:])
    want = ref.granite.recurrence(x[0], dt[0], a, b[0], c[0], remat=False)
    got = ssd.ssd_scan(x, dt, a, b, c, chunk=128)[0]
    assert float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))) < 1e-5
    xb, bb, cb = (t.astype(jnp.bfloat16) for t in (x, b, c))
    jaxpr = jax.make_jaxpr(jax.grad(lambda *t: jnp.sum(ssd.ssd_scan(
        *t, chunk=128).astype(jnp.float32))))(xb, dt, a, bb, cb)
    (fwd,) = [eqn for eqn in _eqns(jaxpr.jaxpr)
              if eqn.primitive.name == "pallas_call"
              and eqn.params["jaxpr"].debug_info.func_name == "_fwd_kernel"]
    y, states = (v.aval for v in fwd.outvars)
    assert y.dtype == jnp.bfloat16
    assert states.dtype == jnp.float32 and states.shape == (1, 2, s, h * p)


# -- what the per-layer remat keeps ------------------------------------------

def test_named_saves_give_the_gradient_of_no_remat(short):
    cfg, model, v, tokens, _ = short
    plain = build_model(apply_overrides(cfg, ["model.remat=false"]).model)
    ga, gb = (jax.jit(jax.grad(_loss_of(mdl, v, tokens)))(v["params"])
              for mdl in (model, plain))
    for a, b in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        _close(a, b, 1e-5)


def test_the_step_says_what_its_remat_saves(short, caplog):
    _, model, v, tokens, _ = short
    logger = logging.getLogger("dsod")
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="dsod"):
            jax.make_jaxpr(jax.grad(_loss_of(model, v, tokens)))(v["params"])
            lines = [r.getMessage() for r in caplog.records
                     if "remat saves (nemotron_h" in r.getMessage()]
    finally:
        logger.removeHandler(caplog.handler)
    # the attention layer's output and lse; the expert layer's chosen
    # experts, their scores, the plan's 8 values, counts and dropped
    assert len(lines) == 1 and re.search(
        r"3 layers\): flash_out=1 flash_lse=1 plan=11 ", lines[0]), lines
    assert nh.REMAT_SAVES == ("flash_out", "flash_lse", "plan")
    assert not set(ssd.SSD_RESIDUAL_NAMES) & set(nh.REMAT_SAVES)



def test_the_step_says_its_flash_grid(short, caplog):
    from test_lfm2 import flash_grid_lines

    _, model, v, tokens, _ = short
    loss = _loss_of(model, v, tokens)
    said, quiet = flash_grid_lines(
        caplog, lambda: jax.eval_shape(jax.grad(loss), v["params"]),
        lambda: jax.eval_shape(loss, v["params"]))
    assert said == ["flash grid: steps=1 of 1 a head"] and not quiet


# -- scopes -------------------------------------------------------------------

SCOPES = ("dsod.ssm", "dsod.ssm.conv", "dsod.ssm.scan", "dsod.ssm.gate",
          "dsod.attn", "dsod.moe.route", "dsod.moe.latent",
          "dsod.moe.experts", "dsod.moe.combine", "dsod.moe.shared",
          "dsod.moe.balance", "dsod.kernel.ssd_scan",
          "dsod.kernel.causal_conv", "dsod.kernel.flash_attention_causal",
          "dsod.kernel.grouped_matmul", "dsod.kernel.grouped_matmul_dw",
          "dsod.kernel.moe_unpermute")
_STAGE = re.compile(r"dsod\.(encoder|decoder|heads|loss|update)\b")


@pytest.fixture(scope="module")
def lowered_text():
    from test_profiler_names import _lowered_step_text

    return _lowered_step_text("nemotron_3_super_tp8_ep64")


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_names_the_new_scopes(lowered_text, scope):
    paths = re.findall(r'^#loc\d+ = loc\("([^"]*)"', lowered_text, re.M)
    under = [p for p in paths
             if re.search(re.escape(scope) + r"(?![\w.])", p)]
    assert under, scope
    stages = [set(_STAGE.findall(p)) for p in under]
    assert {"encoder"} in stages and all(s <= {"encoder"} for s in stages)
    if scope == "dsod.kernel.grouped_matmul":
        assert all("dsod.moe.experts" in p for p in under)


def test_no_product_of_the_step_is_outside_a_stage(lowered_text):
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', lowered_text,
                           re.M))
    dots = [ln for ln in lowered_text.splitlines()
            if "stablehlo.dot_general" in ln]
    assert len(dots) > 30
    assert [ln[-160:] for ln in dots if not _STAGE.search(locs.get(
        re.search(r"loc\((#loc\d+)\)\s*$", ln).group(1), ""))] == []


# -- the loop -----------------------------------------------------------------

def test_three_steps_of_fit_at_tiny_size(tmp_path):
    from distributed_sod_project_tpu.train.loop import fit

    cfg = _cfg(*SHORT, "log_every_steps=1", "data.num_workers=2",
               "tensorboard=false", "checkpoint_every_steps=100").replace(
                   checkpoint_dir=str(tmp_path / "ck"))
    seen = []
    out = fit(cfg, max_steps=3,
              hooks={"on_metrics": lambda step, host: seen.append(host)})
    assert out["final_step"] == 3 and len(seen) == 3
    assert all(np.isfinite(h["total"]) for h in seen)
    for h in seen:
        assert h["moe_dropped_pairs"] == 0
        assert 0 < h["moe_pairs_here_share"] < 1
        assert h["moe_bias_abs_max"] > 0
        assert 0 < h["moe_weight_fetch_share"] < 1
        assert 0 < h["ssm_decay_min"] < 1 and h["ssm_delta_max"] > 0
