"""The second token model (models/kimi.py, config ``kimi_vl_a3b_ep8``)
against its plain reference (benchmark/reference/kimi.py) on the CPU at
tiny widths, float32, seeded weights (benchmark/harness/weights_lm.py):

- latent attention, the expert layer with its shared experts, the whole
  model's hidden states, loss and every gradient leaf;
- three optimizer steps of the compiled train step, the bias update
  among them, against the reference's ``follow``;
- the eight shares' routed parts plus the shared experts counted once
  add up to the uncut reference layer;
- the bias moves by exactly ``gamma`` against the sign of each expert's
  surplus, only where its buffer is mutable, and takes no gradient;
- what a rematerialised layer keeps by name, and the step's log line;
- one forward and one backward attention kernel a layer in the gradient;
- every new ``dsod.*`` scope in the lowered step, inside the encoder
  stage, and no matrix product outside a stage;
- three steps of ``fit()`` with the fourth counter on the stream.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.weights_lm import variables_builder
from benchmark.reference import kimi as ref
from distributed_sod_project_tpu.configs import apply_overrides, get_config
from distributed_sod_project_tpu.losses.token_ce import tied_cross_entropy
from distributed_sod_project_tpu.models import build_model
from distributed_sod_project_tpu.models import kimi as km
from distributed_sod_project_tpu.models import lfm2 as lm

TINY = ["model.lm.vocab=512", "model.lm.hidden=64", "model.lm.heads=4",
        "model.lm.head_dim=24", "model.lm.rope_dim=8",
        "model.lm.v_dim=16", "model.lm.kv_rank=32",
        "model.lm.dense_width=96", "model.lm.expert_width=48",
        "model.lm.experts=8", "model.lm.experts_held=2",
        "model.lm.top_k=2", "data.seq_len=160", "data.vocab=512",
        "data.synthetic_size=32", "global_batch_size=2",
        "model.compute_dtype=float32"]
B, N = 2, 160  # 160: one whole 128-row block and a part of one
GAMMA = 1e-3


def _cfg(*more):
    return apply_overrides(get_config("kimi_vl_a3b_ep8"), TINY + list(more))


def _arch(c):
    """The reference's ``arch`` (configs/kimi_vl_a3b_ep8.json) at the
    program's tiny shape."""
    return dict(ffn_types=c.ffn_types, heads=c.heads,
                nope_dim=c.head_dim - c.rope_dim, rope_dim=c.rope_dim,
                v_dim=c.v_dim, kv_rank=c.kv_rank, top_k=c.top_k,
                first_expert=c.first_expert, norm_eps=c.norm_eps,
                rope_theta=c.rope_theta,
                routed_scaling_factor=c.routed_scaling_factor,
                bias_update_rate=c.bias_update_rate)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    model = build_model(cfg.model)
    tokens = jax.random.randint(jax.random.key(0), (B, N), 0, 512)
    shapes = jax.eval_shape(lambda r, t: model.init(r, t),
                            jax.random.key(1), tokens)
    # a bias that is not zero, so that a selection that ignores it shows
    variables = variables_builder(shapes, {"expert_bias_std": 0.01})(7)
    return cfg, model, variables, tokens, _arch(cfg.model.lm)


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol * float(np.max(np.abs(b)) + 1e-12))


def _x(seed=3):
    return jax.random.normal(jax.random.key(seed), (B, N, 64))


def _per_seq(fn, x):
    return jnp.stack([fn(x[i]) for i in range(x.shape[0])])


def _experts(c, **kw):
    args = dict(experts=c.experts, experts_held=c.experts_held,
                first_expert=c.first_expert, top_k=c.top_k,
                width=c.expert_width,
                routed_scaling_factor=c.routed_scaling_factor,
                topk_eps=c.topk_eps, bias_update_rate=c.bias_update_rate,
                dtype=jnp.float32)
    return lm.ExpertLayer(**dict(args, **kw))


# -- layer by layer, the model, the step -------------------------------------

@pytest.mark.parametrize("head_group", [4, 2])  # one group of heads; two
def test_latent_attention_matches_reference(setup, monkeypatch, head_group):
    monkeypatch.setattr(ref, "HEAD_GROUP", head_group)
    cfg, _, v, _, m = setup
    c, x = cfg.model.lm, _x()
    p = v["params"]["layer_1"]["attn"]
    got = km.LatentAttention(
        c.heads, c.head_dim - c.rope_dim, c.rope_dim, c.v_dim, c.kv_rank,
        c.rope_theta, c.norm_eps, dtype=jnp.float32).apply({"params": p}, x)
    _close(got, _per_seq(lambda s: ref.attention(s, p, m), x))


def test_expert_layer_with_shared_experts_matches_reference(setup):
    cfg, _, v, _, m = setup
    c, x = cfg.model.lm, _x(4)
    p, shared = (v["params"]["layer_2"][k] for k in ("moe", "shared"))
    b = v["batch_stats"]["layer_2"]["moe"]
    routed, counters = _experts(c).apply({"params": p, "batch_stats": b}, x)
    got = routed + lm.SwiGLU(2 * c.expert_width, dtype=jnp.float32).apply(
        {"params": shared}, x)
    want = _per_seq(lambda s: ref.moe(s, p, shared, b["expert_bias"], m)[0],
                    x)
    _close(got, want)
    assert float(counters["dropped"]) == 0.0
    assert "bias_abs_max" not in counters  # the buffer was not mutable


def _loss_of(model, v, tokens):
    targets = jnp.roll(tokens, -1, 1)

    def prog(p):
        h, _ = model.apply({"params": p, "batch_stats": v["batch_stats"]},
                           tokens, train=True)
        return tied_cross_entropy(h, p["head"]["embedding"], targets,
                                  chunk=64)

    return prog


def test_hidden_states_loss_and_every_gradient_match_reference(setup):
    _, model, v, tokens, m = setup
    h, _ = model.apply(v, tokens)
    _close(h, _per_seq(lambda t: ref.hidden(v, t, m)[0], tokens), 1e-4)

    def plain(p):
        return ref.batch_loss({"params": p, "batch_stats": v["batch_stats"]},
                              tokens, jnp.roll(tokens, -1, 1), m)

    lp, gp = jax.jit(jax.value_and_grad(_loss_of(model, v, tokens)))(
        v["params"])
    lr, gr = jax.jit(jax.value_and_grad(plain))(v["params"])
    assert abs(float(lp) - float(lr)) < 1e-5 * float(lr)
    flat = jax.tree_util.tree_flatten_with_path(gp)[0]
    assert len(flat) == 3 + 10 + 5 * 14  # every leaf, each reached (below)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(gr)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-4 * float(jnp.max(jnp.abs(b))), jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)


def test_three_steps_with_the_bias_update_follow_the_reference(setup):
    """The compiled train step itself (``make_unified_train_step``, dp
    preset) from the benchmark's weights on three batches: losses, the
    parameters' change and the balanced bias against ``ref.follow``."""
    from distributed_sod_project_tpu.parallel import make_mesh
    from distributed_sod_project_tpu.parallel.engine import \
        make_unified_train_step
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    cfg, model, v, _, m = setup
    zero_bias = jax.tree_util.tree_map(jnp.zeros_like, v["batch_stats"])
    start = {"params": v["params"], "batch_stats": zero_bias}
    opt = dict(kind="adamw", lr=cfg.optim.lr, weight_decay=0.1,
               warmup_steps=2, poly_power=0.9, total_steps=50)
    cfg = apply_overrides(cfg, ["optim.warmup_steps=2"])
    tx, sched = build_optimizer(cfg.optim, 50)
    batches = [{"tokens": np.asarray(t), "targets": np.roll(t, -1, 1)}
               for t in np.asarray(jax.random.randint(
                   jax.random.key(5), (3, B, N), 0, 512))]
    state = create_train_state(jax.random.key(0), model, tx, batches[0])
    state = state.replace(params=start["params"], batch_stats=zero_bias)
    step = make_unified_train_step(
        model, cfg.loss, tx, make_mesh(cfg.mesh, jax.devices()[:1]),
        preset="dp", schedule=sched, donate=False)
    losses = []
    for b in batches:
        state, metrics = step(state, b)
        losses.append(float(metrics["total"]))
    # (the reference's step donates what it is given: a copy each call)
    want = ref.follow(lambda: jax.tree_util.tree_map(jnp.array, start),
                      batches, {"arch": m, "optimizer": opt})
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.sqrt(jnp.sum(jnp.square(a - b)))),
        state.params, start["params"])
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(moved)[0],
                            jax.tree_util.tree_leaves(want["dparam_norms"])):
        assert a == pytest.approx(float(b), rel=2e-3), \
            jax.tree_util.keystr(path)
    for a, b in zip(jax.tree_util.tree_leaves(state.batch_stats),
                    jax.tree_util.tree_leaves(want["expert_bias"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # three moves of gamma each (none where a count sits on the mean)
        assert set(np.round(np.abs(np.asarray(a)) / GAMMA)) <= {0, 1, 2, 3}
    assert float(metrics["moe_bias_abs_max"]) == pytest.approx(max(
        float(np.max(np.abs(b)))
        for b in jax.tree_util.tree_leaves(want["expert_bias"])))


# -- the chip's share ---------------------------------------------------------

def test_eight_shares_and_the_shared_experts_once_add_up_to_the_whole(setup):
    """64 experts over 8 chips, top-6, the scaling factor and the 1e-20
    of the published rule: each chip's routed part, plus the shared
    experts' counted once, is the uncut reference layer."""
    cfg, _, _, _, m = setup
    x = _x(5)
    c = dataclasses.replace(cfg.model.lm, experts=64, experts_held=8,
                            top_k=6)
    m = dict(m, top_k=6)
    ks = jax.random.split(jax.random.key(11), 6)
    p = {"router": {"kernel": jax.random.normal(ks[0], (64, 64)) / 8},
         "gate": jax.random.normal(ks[1], (64, 64, 48)) / 8,
         "up": jax.random.normal(ks[2], (64, 64, 48)) / 8,
         "down": jax.random.normal(ks[3], (64, 48, 64)) / 7}
    shapes = jax.eval_shape(lambda: lm.SwiGLU(96, dtype=jnp.float32).init(
        jax.random.key(0), x))
    shared = variables_builder(shapes, {})(3)["params"]
    bias = jax.random.normal(ks[4], (64,)) * 0.01
    whole = _per_seq(lambda s: ref.moe(s, p, shared, bias, m)[0], x)
    total = lm.SwiGLU(96, dtype=jnp.float32).apply({"params": shared}, x)
    pairs = 0.0
    for share in range(8):
        lo = share * 8
        mine = dict(p, **{k: p[k][lo:lo + 8] for k in ("gate", "up", "down")})
        out, counters = _experts(c, first_expert=lo).apply(
            {"params": mine, "batch_stats": {"expert_bias": bias}}, x)
        assert float(counters["dropped"]) == 0.0
        total, pairs = total + out, pairs + float(counters["pairs_here"])
    _close(total, whole, 5e-5)
    assert pairs == B * N * 6  # every pair computed on some chip


# -- the balancing rule -------------------------------------------------------

def test_the_bias_moves_by_gamma_against_each_experts_surplus(setup):
    cfg, _, v, _, _ = setup
    c, x = cfg.model.lm, _x(6)
    p = v["params"]["layer_3"]["moe"]
    bias = jnp.linspace(-0.02, 0.02, c.experts)
    variables = {"params": p, "batch_stats": {"expert_bias": bias}}
    layer = _experts(c)
    (_, counters), mut = layer.apply(variables, x, mutable=["batch_stats"])
    # what the router chose, from the reference's own routing
    idx = jnp.concatenate([ref.route(x[i], p, bias, dict(
        top_k=c.top_k, routed_scaling_factor=1.0))[0] for i in range(B)])
    sent = np.bincount(np.asarray(idx).reshape(-1), minlength=c.experts)
    want = np.asarray(bias) + np.float32(GAMMA) * np.sign(
        sent.mean() - sent).astype(np.float32)
    assert len(set(sent)) > 1
    np.testing.assert_array_equal(
        np.asarray(mut["batch_stats"]["expert_bias"]), want)
    assert float(counters["bias_abs_max"]) == np.max(np.abs(want))
    # not mutable (evaluation, a rate of 0): the buffer stays, no counter
    still, _ = _experts(c, bias_update_rate=0.0).apply(
        variables, x, mutable=["batch_stats"])
    assert "bias_abs_max" not in still[1]


def test_the_bias_takes_no_gradient(setup):
    cfg, _, v, _, _ = setup
    c, x = cfg.model.lm, _x(7)
    p = v["params"]["layer_3"]["moe"]

    def out_sum(bias):
        (out, _), _ = _experts(c).apply(
            {"params": p, "batch_stats": {"expert_bias": bias}}, x,
            mutable=["batch_stats"])
        return jnp.sum(jnp.square(out))

    g = jax.grad(out_sum)(jnp.full((c.experts,), 0.01))
    assert float(jnp.max(jnp.abs(g))) == 0.0


# -- what the per-layer remat keeps -------------------------------------------

def test_named_saves_give_the_gradient_of_no_remat(setup):
    cfg, model, v, tokens, _ = setup
    plain = build_model(dataclasses.replace(cfg.model, remat=False))
    ga = jax.jit(jax.grad(_loss_of(model, v, tokens)))(v["params"])
    gb = jax.jit(jax.grad(_loss_of(plain, v, tokens)))(v["params"])
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ga)[0],
                            jax.tree_util.tree_leaves(gb)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 1e-5 * float(jnp.max(jnp.abs(b))), jax.tree_util.keystr(path)


def test_the_step_says_what_its_remat_saves(setup, caplog):
    _, model, v, tokens, _ = setup
    import logging

    logger = logging.getLogger("dsod")
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="dsod"):
            jax.make_jaxpr(jax.grad(_loss_of(model, v, tokens)))(v["params"])
            lines = [r.getMessage() for r in caplog.records
                     if "remat saves (kimi" in r.getMessage()]
            caplog.clear()
            jax.make_jaxpr(_loss_of(model, v, tokens))(v["params"])
            quiet = [r for r in caplog.records
                     if "remat saves" in r.getMessage()]
    finally:
        logger.removeHandler(caplog.handler)
    assert len(lines) == 1 and not quiet
    # the kernel's output and lse once a layer; its q is named, not kept
    assert re.search(r"\(kimi, 6 layers\): mla_out=6 mla_lse=6 plan=\d+ ",
                     lines[0]), lines[0]
    assert km.REMAT_SAVES == ("mla_out", "mla_lse", "plan")



def test_the_step_says_its_flash_grid(setup, caplog):
    from test_lfm2 import flash_grid_lines

    _, model, v, tokens, _ = setup
    loss = _loss_of(model, v, tokens)
    said, quiet = flash_grid_lines(
        caplog, lambda: jax.eval_shape(jax.grad(loss), v["params"]),
        lambda: jax.eval_shape(loss, v["params"]))
    assert said == ["flash grid: steps=1 of 1 a head"] and not quiet


@pytest.mark.parametrize("kernel", ["_m_fwd_kernel", "_m_bwd_kernel"])
def test_gradient_runs_one_forward_and_one_backward_kernel_a_layer(setup,
                                                                   kernel):
    """In the jaxpr of the config's gradient (all 6 layers, tiny widths)
    latent attention is 6 forward calls (out and lse are kept, so no
    recompute) and 6 backward calls: ONE kernel gives dq, dk and dv."""
    from test_lfm2 import _eqns

    cfg, model, v, tokens, _ = setup
    layers = len(cfg.model.lm.ffn_types)
    assert layers == len(get_config("kimi_vl_a3b_ep8").model.lm.ffn_types) \
        == 6
    jaxpr = jax.make_jaxpr(jax.grad(_loss_of(model, v, tokens)))(v["params"])
    names = [eqn.params["jaxpr"].debug_info.func_name
             for eqn in _eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert names.count(kernel) == layers
    assert sum(n.startswith("_m_") for n in names) == 2 * layers


# -- scopes -------------------------------------------------------------------

SCOPES = ("dsod.attn", "dsod.densemlp", "dsod.moe.route", "dsod.moe.experts",
          "dsod.moe.combine", "dsod.moe.shared", "dsod.moe.balance",
          "dsod.kernel.grouped_matmul", "dsod.kernel.grouped_matmul_dw",
          "dsod.kernel.moe_unpermute", "dsod.kernel.flash_attention_mla",
          "dsod.kernel.flash_attention_mla_bwd")
_STAGE = re.compile(r"dsod\.(encoder|decoder|heads|loss|update)\b")


@pytest.fixture(scope="module")
def lowered_text():
    from test_profiler_names import _lowered_step_text

    return _lowered_step_text("kimi_vl_a3b_ep8")


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_names_the_new_scopes(lowered_text, scope):
    paths = re.findall(r'^#loc\d+ = loc\("([^"]*)"', lowered_text, re.M)
    under = [p for p in paths if re.search(re.escape(scope) + r"\b", p)]
    assert under, scope
    stages = [set(_STAGE.findall(p)) for p in under]
    assert {"encoder"} in stages and all(s <= {"encoder"} for s in stages)


def test_no_product_of_the_step_is_outside_a_stage(lowered_text):
    """The unscoped share of the step's matrix products is 0: every
    ``dot_general`` of the lowered step, forward, recomputed and
    backward, the new block's among them, names a stage."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', lowered_text,
                           re.M))
    dots = [ln for ln in lowered_text.splitlines()
            if "stablehlo.dot_general" in ln]
    assert len(dots) > 100
    assert [ln[-160:] for ln in dots if not _STAGE.search(locs.get(
        re.search(r"loc\((#loc\d+)\)\s*$", ln).group(1), ""))] == []


# -- the loop -----------------------------------------------------------------

def test_three_steps_of_fit_at_tiny_size(tmp_path):
    from distributed_sod_project_tpu.train.loop import fit

    cfg = _cfg("log_every_steps=1", "data.num_workers=2", "tensorboard=false",
               "checkpoint_every_steps=100").replace(
                   checkpoint_dir=str(tmp_path / "ck"))
    seen = []
    out = fit(cfg, max_steps=3,
              hooks={"on_metrics": lambda step, host: seen.append(host)})
    assert out["final_step"] == 3 and len(seen) == 3
    for i, host in enumerate(seen):
        assert np.isfinite(host["total"]) and host["grad_norm"] > 0
        assert host["moe_dropped_pairs"] == 0.0
        assert 0.1 < host["moe_pairs_here_share"] < 0.45
        assert host["moe_bias_abs_max"] == pytest.approx((i + 1) * GAMMA)
    assert abs(seen[0]["total"] - np.log(512)) < 1.5
