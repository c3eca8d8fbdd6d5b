"""The fourth token model (models/ouro.py, config ``ouro_2_6b_pp6``)
against its plain reference (benchmark/reference/ouro.py) on the CPU at
tiny widths, float32, seeded weights (benchmark/harness/weights_loop.py):

- the four normed states, the gate logits, the exit distribution and the
  loss; every leaf's gradient, and that a shared weight's gradient is the
  sum over its four uses (against four untied copies of the stack);
- three optimizer steps of the compiled train step against ``follow``,
  the exit masses among what is followed;
- each planted fault (benchmark/tests/loop_faults.py) fails the
  comparison;
- the per-token, per-pass loss against a dense [N, V] computation,
  gradients in the states, the head AND the gate; ``p`` sums to 1 and
  the last gate logit gets no gradient;
- what a rematerialised visit keeps, counted per visit, and the log line;
- every new ``dsod.*`` scope in the lowered step inside its stage, no
  product outside a stage, the counters on the stream;
- three steps of ``fit()``.
"""

import dataclasses
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.weights_loop import variables_builder
from benchmark.reference import ouro as ref
from benchmark.runners.train_loop import exit_mass_gap
from benchmark.tests.loop_faults import FAULTS, plant
from distributed_sod_project_tpu.configs import apply_overrides, get_config
from distributed_sod_project_tpu.losses import token_ce
from distributed_sod_project_tpu.models import build_model

TINY = ["model.lm.vocab=512", "model.lm.hidden=64", "model.lm.heads=4",
        "model.lm.kv_heads=4", "model.lm.head_dim=16",
        "model.lm.dense_width=96",
        "model.lm.layer_types=attention,attention",
        "model.lm.ffn_types=dense,dense", "data.seq_len=128",
        "data.vocab=512", "data.synthetic_size=32", "global_batch_size=2",
        "model.compute_dtype=float32"]
B, N, R, LAYERS = 2, 128, 4, 2


def _cfg(*more):
    return apply_overrides(get_config("ouro_2_6b_pp6"), TINY + list(more))


def _arch(c):
    """The reference's ``arch`` (configs/ouro_2_6b_pp6.json) at the
    program's tiny shape."""
    return dict(layers=len(c.layer_types), ut_steps=c.ut_steps,
                heads=c.heads, head_dim=c.head_dim, rope_theta=c.rope_theta,
                norm_eps=c.norm_eps, exit_beta=c.exit_beta)


def _variables(model, tokens, seed=7):
    shapes = jax.eval_shape(lambda r, t: model.init(r, t),
                            jax.random.key(0), tokens)
    shapes = {"params": shapes["params"], "batch_stats": {}}
    v = variables_builder(shapes, {})(seed)
    # the recipe's zero bias hides a gate that drops it: move it
    v["params"]["loop"]["exit_gate"]["bias"] = jnp.full((1,), 0.3)
    return v


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    model = build_model(cfg.model)
    tokens = jax.random.randint(jax.random.key(1), (B, N), 0, 512)
    return cfg, model, _variables(model, tokens), tokens, _arch(cfg.model.lm)


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-30)


def _loss_of(model, tokens):
    targets = jnp.roll(tokens, -1, 1)

    def prog(p):
        out, _ = model.apply({"params": p}, tokens, train=True)
        return model.token_loss(out, p, targets)

    return prog


def _plain(tokens, m):
    return lambda p: ref.batch_loss({"params": p}, tokens,
                                    jnp.roll(tokens, -1, 1), m)


def test_states_gates_exit_distribution_and_loss_match_reference(setup):
    _, model, v, tokens, m = setup
    (states, gates), counters = model.apply(v, tokens)
    assert states.shape == (R, B, N, 64) and gates.shape == (R, B, N)
    assert counters == {}
    for b in range(B):
        hs, gs = ref.states(v, tokens[b], m)
        _close(states[:, b], hs, 1e-4)
        _close(gates[:, b], gs, 1e-4)
        p, logp = token_ce.exit_distribution(gates[:, b])
        _close(p, ref.exit_distribution(gs), 1e-4)
        _close(jnp.exp(logp), p, 1e-6)
    (total, cnt) = _loss_of(model, tokens)(v["params"])
    want = _plain(tokens, m)(v["params"])
    assert abs(float(total) - float(want)) < 1e-5 * float(want)
    _, (mass, entropy, ce) = jax.vmap(
        lambda t, g: ref.loss_and_exit(v, t, g, m))(
            tokens, jnp.roll(tokens, -1, 1))
    for t in range(R):
        assert float(cnt[f"loop_exit_mass_{t + 1}"]) == pytest.approx(
            float(jnp.mean(mass[:, t])), rel=1e-4)
        assert float(cnt[f"loop_ce_{t + 1}"]) == pytest.approx(
            float(jnp.mean(ce[:, t])), rel=1e-5)
    assert float(cnt["loop_exit_entropy"]) == pytest.approx(
        float(jnp.mean(entropy)), rel=1e-4)
    assert 0 < float(cnt["loop_exit_entropy"]) <= np.log(R)


def test_every_gradient_matches_reference(setup):
    _, model, v, tokens, m = setup
    gp, _ = jax.jit(jax.grad(_loss_of(model, tokens), has_aux=True))(
        v["params"])
    g_ref = jax.jit(jax.grad(_plain(tokens, m)))(v["params"])
    flat = jax.tree_util.tree_flatten_with_path(gp)[0]
    # embed, head, gate kernel + bias, final norm, 11 leaves a block
    assert len(flat) == 5 + 11 * LAYERS
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(g_ref)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-4 * float(jnp.max(jnp.abs(b))), jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)


def test_a_shared_weights_gradient_is_the_sum_over_its_four_uses(setup):
    """The reference's loop with a COPY of the stack's weights a pass:
    the looped model's gradient is the sum of the four copies'."""
    _, model, v, tokens, m = setup
    targets = jnp.roll(tokens, -1, 1)

    def untied(copies):
        def one(t, g):
            h = v["params"]["embed"]["kernel"][t, 0]
            hs, gs = [], []
            for loop in copies:
                for i in range(m["layers"]):
                    h = ref.block(h, loop[f"layer_{i}"], m)
                h = ref.rms_norm(h, loop["final_norm"]["scale"],
                                 m["norm_eps"])
                hs.append(h)
                gs.append(h @ loop["exit_gate"]["kernel"][:, 0]
                          + loop["exit_gate"]["bias"][0])
            ce = ref.cross_entropies(jnp.stack(hs),
                                     v["params"]["head"]["embedding"], g)
            p = ref.exit_distribution(jnp.stack(gs))
            return jnp.mean(jnp.sum(p * ce, 0) + m["exit_beta"]
                            * jnp.sum(p * jnp.log(p), 0))

        return jnp.mean(jax.vmap(one)(tokens, targets))

    per_use = jax.jit(jax.grad(untied))([v["params"]["loop"]] * R)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_use)
    gp, _ = jax.jit(jax.grad(_loss_of(model, tokens), has_aux=True))(
        v["params"])
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(gp["loop"])[0],
            jax.tree_util.tree_leaves(summed)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-4 * float(jnp.max(jnp.abs(b))), jax.tree_util.keystr(path)
    # and no single use is the whole of it
    k = lambda g: g["layer_0"]["mlp"]["up"]["kernel"]  # noqa: E731
    assert all(float(jnp.max(jnp.abs(k(u) - k(gp["loop"]))))
               > 1e-2 * float(jnp.max(jnp.abs(k(gp["loop"]))))
               for u in per_use)
    # the last pass's gate logit is not read: the gate's gradient is the
    # first three uses' alone
    gate = lambda g: g["exit_gate"]["kernel"]  # noqa: E731
    assert float(jnp.max(jnp.abs(gate(per_use[-1])))) == 0.0


# -- the loss ----------------------------------------------------------------

def _dense_loss(states, gates, head, targets, beta):
    """[R, T, D] states: every logit at once, the plain products."""
    z = jnp.einsum("rtd,vd->rtv", states, head)
    ce = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
        z, jnp.broadcast_to(targets, z.shape[:2])[..., None], -1)[..., 0]
    p = ref.exit_distribution(gates)
    return jnp.mean(jnp.sum(p * ce, 0)
                    + beta * jnp.sum(p * jnp.log(p), 0)), ce


@pytest.mark.parametrize("chunk", [32, 96, 4096])
def test_exit_weighted_loss_matches_a_dense_computation(chunk):
    """Loss, per-pass cross-entropies and the gradients in the states,
    the head and the gate logits; a chunk that divides one pass's rows,
    one that does not (the largest divisor under it is taken) and one
    above them."""
    r, t, d, vocab = 4, 192, 32, 160
    ks = jax.random.split(jax.random.key(3), 4)
    states = jax.random.normal(ks[0], (r, 2, t // 2, d))
    gates = jax.random.normal(ks[1], (r, 2, t // 2))
    head = jax.random.normal(ks[2], (vocab, d)) * d ** -0.5
    targets = jax.random.randint(ks[3], (2, t // 2), 0, vocab)

    def prog(s, g, e):
        return token_ce.exit_weighted_cross_entropy(
            s, g, e, targets, beta=0.1, chunk=chunk)

    def plain(s, g, e):
        return _dense_loss(s.reshape(r, t, d), g.reshape(r, t), e,
                           targets.reshape(t), 0.1)

    (lp, cnt), gp = jax.jit(jax.value_and_grad(
        prog, argnums=(0, 1, 2), has_aux=True))(states, gates, head)
    (lr, ce), gr = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1, 2), has_aux=True))(states, gates, head)
    assert float(lp) == pytest.approx(float(lr), rel=1e-6)
    for i in range(r):
        assert float(cnt[f"loop_ce_{i + 1}"]) == pytest.approx(
            float(jnp.mean(ce[i])), rel=1e-6)
    for a, b in zip(gp, gr):
        _close(a, b, 1e-5)
    assert float(jnp.max(jnp.abs(gp[1][-1]))) == 0.0  # the last logit
    assert float(jnp.min(jnp.abs(gp[1][:-1]))) > 0.0


def test_exit_distribution_sums_to_one_and_survives_large_logits():
    g = jnp.asarray([[-40.0, 0.3, 40.0, 90.0], [2.0, -1.0, -90.0, 0.0],
                     [0.5, 0.5, 0.0, -3.0], [7.0, 7.0, 7.0, 7.0]]).T
    p, logp = token_ce.exit_distribution(g.T)
    np.testing.assert_allclose(np.sum(np.asarray(p), 0), 1.0, atol=1e-6)
    assert np.all(np.isfinite(np.asarray(logp)))
    # R = 1: the one pass takes everything
    p1, _ = token_ce.exit_distribution(jnp.zeros((1, 5)))
    np.testing.assert_array_equal(np.asarray(p1), 1.0)


# -- the step, against the reference's follow --------------------------------

def _three_steps(cfg, model, v, batches, warmup=2):
    from distributed_sod_project_tpu.parallel import make_mesh
    from distributed_sod_project_tpu.parallel.engine import \
        make_unified_train_step
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    cfg = apply_overrides(cfg, [f"optim.warmup_steps={warmup}"])
    tx, sched = build_optimizer(cfg.optim, 50)
    state = create_train_state(jax.random.key(0), model, tx, batches[0])
    state = state.replace(params=v["params"])
    step = make_unified_train_step(
        model, cfg.loss, tx, make_mesh(cfg.mesh, jax.devices()[:1]),
        preset="dp", schedule=sched, donate=False)
    losses, masses = [], []
    for b in batches:
        state, metrics = step(state, b)
        losses.append(float(metrics["total"]))
        masses.append([float(metrics[f"loop_exit_mass_{t + 1}"])
                       for t in range(model.cfg.ut_steps)])
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.sqrt(jnp.sum(jnp.square(a - b)))),
        state.params, v["params"])
    return losses, masses, moved, metrics


def _batches():
    return [{"tokens": np.asarray(t), "targets": np.roll(t, -1, 1)}
            for t in np.asarray(jax.random.randint(
                jax.random.key(5), (3, B, N), 0, 512))]


@pytest.fixture(scope="module")
def followed(setup):
    cfg, _, v, _, m = setup
    opt = dict(kind="adamw", lr=cfg.optim.lr, weight_decay=0.1,
               warmup_steps=2, poly_power=0.9, total_steps=50)
    return ref.follow(lambda: jax.tree_util.tree_map(jnp.array, v),
                      _batches(), {"arch": m, "optimizer": opt})


def test_three_steps_follow_the_reference(setup, followed):
    """The compiled train step itself (``make_unified_train_step``, dp
    preset, the loss through the engine's seam) from the benchmark's
    weights on three batches."""
    cfg, model, v, _, _ = setup
    losses, masses, moved, metrics = _three_steps(cfg, model, v, _batches())
    np.testing.assert_allclose(losses, followed["loss"], rtol=2e-5)
    assert exit_mass_gap(masses, followed["exit_mass"]) < 1e-5
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(moved)[0],
            jax.tree_util.tree_leaves(followed["dparam_norms"])):
        assert a == pytest.approx(float(b), rel=2e-3), \
            jax.tree_util.keystr(path)
        assert a > 0, jax.tree_util.keystr(path)
    assert set(metrics) >= {"total", "grad_norm", "loop_exit_entropy"} | {
        f"loop_{k}_{t + 1}" for k in ("ce", "exit_mass") for t in range(R)}
    assert sum(float(metrics[f"loop_exit_mass_{t + 1}"])
               for t in range(R)) == pytest.approx(1.0, abs=1e-5)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(setup, followed, fault,
                                              monkeypatch):
    """Each fault on the program, the reference as published: sound runs
    read 2e-5 on the losses and 1e-5 on the masses (the test above)."""
    cfg, _, v, _, _ = setup
    faulty = build_model(apply_overrides(
        cfg, plant(fault, monkeypatch.setattr)).model)
    losses, masses, moved, _ = _three_steps(cfg, faulty, v, _batches())
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(losses, followed["loss"]))
    mass_gap = exit_mass_gap(masses, followed["exit_mass"])
    gate = moved["loop"]["exit_gate"]
    if fault in ("uniform_exit_weights", "last_pass_loss_alone"):
        # the gate gets no gradient: it never moves
        # (its kernel by the weight decay alone)
        assert gate["bias"] == 0.0
        assert mass_gap > 1e-2
    elif fault == "three_passes":
        assert mass_gap > 1e-2   # the fourth pass's mass is nowhere
    else:
        assert loss_gap > 1e-3, (fault, loss_gap, mass_gap)


# -- what the per-visit remat keeps ------------------------------------------

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


def test_the_step_says_what_its_remat_saves_per_visit(setup, caplog):
    _, model, v, tokens, _ = setup
    logger = logging.getLogger("dsod")
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="dsod"):
            jax.make_jaxpr(jax.grad(_loss_of(model, tokens), has_aux=True))(
                v["params"])
            lines = [r.getMessage() for r in caplog.records
                     if "remat saves (ouro" in r.getMessage()]
            caplog.clear()
            jax.make_jaxpr(_loss_of(model, tokens))(v["params"])
            quiet = [r for r in caplog.records
                     if "remat saves" in r.getMessage()]
    finally:
        logger.removeHandler(caplog.handler)
    assert len(lines) == 1 and not quiet
    # 2 layers x 4 passes = 8 visits, each keeps the kernel's out and lse:
    # B x heads x N x (16 float32 + 1 float32) bytes a visit
    got = re.search(r"\(ouro, 8 visits\): flash_out=8 flash_lse=8 "
                    r"MiB=([\d.]+)", lines[0])
    assert got, lines[0]
    assert float(got.group(1)) == pytest.approx(
        8 * B * 4 * N * (16 + 1) * 4 / 2 ** 20, abs=0.06)



def test_the_step_says_its_flash_grid_once(setup, caplog):
    """One line a trace, not one a visit."""
    from test_lfm2 import flash_grid_lines

    _, model, v, tokens, _ = setup
    loss = _loss_of(model, tokens)
    said, quiet = flash_grid_lines(
        caplog, lambda: jax.eval_shape(jax.grad(loss, has_aux=True),
                                       v["params"]),
        lambda: jax.eval_shape(loss, v["params"]))
    assert said == ["flash grid: steps=1 of 1 a head"] and not quiet


def test_the_gradient_runs_one_forward_kernel_a_visit(setup):
    """One forward and one fused backward kernel a VISIT (layers x
    passes): the forward's out and lse are kept, not made again."""
    from test_lfm2 import _eqns

    _, model, v, tokens, _ = setup
    jaxpr = jax.make_jaxpr(jax.grad(_loss_of(model, tokens), has_aux=True))(
        v["params"])
    names = [eqn.params["jaxpr"].debug_info.func_name
             for eqn in _eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert names.count("_c_fwd_kernel") == LAYERS * R
    assert names.count("_c_bwd_kernel") == LAYERS * R


# -- scopes and counters -----------------------------------------------------

SCOPES = ("dsod.loop", "dsod.attn", "dsod.attn.core", "dsod.densemlp",
          "dsod.loop.exit", "dsod.kernel.flash_attention_causal",
          "dsod.kernel.flash_attention_causal_bwd")
_STAGE = re.compile(r"dsod\.(encoder|decoder|heads|loss|update)\b")


@pytest.fixture(scope="module")
def lowered_text():
    from test_profiler_names import _lowered_step_text

    return _lowered_step_text("ouro_2_6b_pp6")


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_names_the_new_scopes(lowered_text, scope):
    paths = re.findall(r'^#loc\d+ = loc\("([^"]*)"', lowered_text, re.M)
    under = [p for p in paths
             if re.search(re.escape(scope) + r"(?![\w.])", p)]
    assert under, scope
    stages = [set(_STAGE.findall(p)) for p in under]
    if scope == "dsod.loop.exit":   # norm + gate; distribution + entropy
        assert {"encoder"} in stages and {"loss"} in stages
    else:
        assert {"encoder"} in stages and all(s <= {"encoder"} for s in stages)
    if scope.startswith("dsod.kernel."):
        assert all("dsod.attn.core" in p for p in under)
    assert all("dsod.loop" in p for p in under)


def test_no_product_of_the_step_is_outside_a_stage(lowered_text):
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', lowered_text,
                           re.M))
    dots = [ln for ln in lowered_text.splitlines()
            if "stablehlo.dot_general" in ln]
    assert len(dots) > 30
    assert [ln[-160:] for ln in dots if not _STAGE.search(locs.get(
        re.search(r"loc\((#loc\d+)\)\s*$", ln).group(1), ""))] == []
    heads = [ln for ln in dots if "dsod.heads" in locs.get(
        re.search(r"loc\((#loc\d+)\)\s*$", ln).group(1), "")]
    assert heads  # the head products, forward and backward


# -- the loop ----------------------------------------------------------------

def test_three_steps_of_fit_at_tiny_size(tmp_path):
    from distributed_sod_project_tpu.train.loop import fit

    cfg = _cfg("log_every_steps=1", "data.num_workers=2", "tensorboard=false",
               "checkpoint_every_steps=100").replace(
                   checkpoint_dir=str(tmp_path / "ck"))
    seen = []
    out = fit(cfg, max_steps=3,
              hooks={"on_metrics": lambda step, host: seen.append(host)})
    assert out["final_step"] == 3 and len(seen) == 3
    assert all(np.isfinite(h["total"]) for h in seen)
    for h in seen:
        assert 0 < h["loop_exit_entropy"] <= np.log(R) + 1e-6
        assert sum(h[f"loop_exit_mass_{t + 1}"] for t in range(R)) \
            == pytest.approx(1.0, abs=1e-5)
        assert all(h[f"loop_ce_{t + 1}"] > 0 for t in range(R))
