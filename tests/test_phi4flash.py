"""The sixth token model (models/phi4flash.py, config
``phi4_mini_flash_pp5``) against its plain reference
(benchmark/reference/phi4flash.py) on the CPU at tiny widths, float32,
seeded weights (benchmark/harness/weights_phi4flash.py):

- each mixer (Mamba-1 with what it hands on; windowed, full and cross
  differential attention; the gated memory unit), the whole model's
  hidden states, loss and every gradient leaf;
- a stage with TWO memory units and TWO cross layers (published layers
  14-21's kinds), so that cotangents from two readers sum into ``m`` and
  into the kept keys and values;
- three optimizer steps of the compiled train step against the
  reference's ``follow``;
- each planted fault of benchmark/tests/phi4flash_faults.py fails that
  comparison;
- what a rematerialised layer keeps by name, the step's log lines (its
  saves, its two flash grids), and the kernels a gradient runs;
- every new ``dsod.*`` scope in the lowered step, inside the encoder
  stage, and no product outside a stage;
- three steps of ``fit()`` with the five counters on the stream.
"""

import dataclasses
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.weights_phi4flash import variables_builder
from benchmark.reference import phi4flash as ref
from benchmark.tests.phi4flash_faults import FAULTS, plant
from distributed_sod_project_tpu.configs import apply_overrides, get_config
from distributed_sod_project_tpu.losses.token_ce import tied_cross_entropy
from distributed_sod_project_tpu.models import build_model
from distributed_sod_project_tpu.models import phi4flash as pf

WINDOW = 24
TINY = ["model.lm.vocab=512", "model.lm.hidden=64", "model.lm.heads=4",
        "model.lm.kv_heads=2", "model.lm.head_dim=16",
        "model.lm.dense_width=96", "model.lm.ssm_heads=128",
        "model.lm.ssm_state=16", "model.lm.ssm_chunk=32",
        "model.lm.ssm_dt_rank=4", f"model.lm.window={WINDOW}",
        "data.seq_len=128", "data.vocab=512", "data.synthetic_size=32",
        "global_batch_size=2", "model.compute_dtype=float32"]
B, N = 2, 128  # four chunks of 32 tokens; the window a fifth of them


def _cfg(*more):
    return apply_overrides(get_config("phi4_mini_flash_pp5"),
                           TINY + list(more))


def _arch(c):
    """The reference's ``arch`` (configs/phi4_mini_flash_pp5.json) at the
    program's tiny shape."""
    return dict(layer_types=c.layer_types, heads=c.heads,
                kv_heads=c.kv_heads, head_dim=c.head_dim,
                ssm_state=c.ssm_state, ssm_dt_rank=c.ssm_dt_rank,
                window=c.window, first_layer=c.first_layer,
                norm_eps=c.norm_eps)


def _variables(model, tokens, seed=7):
    shapes = jax.eval_shape(lambda r, t: model.init(r, t),
                            jax.random.key(1), tokens)
    return variables_builder({"params": shapes["params"],
                              "batch_stats": {}}, {})(seed)


def _setup(*more):
    cfg = _cfg(*more)
    model = build_model(cfg.model)
    tokens = jax.random.randint(jax.random.key(0), (B, N), 0, 512)
    return cfg, model, _variables(model, tokens), tokens, _arch(cfg.model.lm)


@pytest.fixture(scope="module")
def setup():
    return _setup()


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol * float(np.max(np.abs(b)) + 1e-12))


def _x(seed=3):
    return jax.random.normal(jax.random.key(seed), (B, N, 64))


def _per_seq(fn, *xs):
    outs = [fn(*(x[i] for x in xs)) for i in range(B)]
    return jax.tree_util.tree_map(lambda *t: jnp.stack(t), *outs)


def _rel(u, v):
    return float(jnp.max(jnp.abs(u - v)) / jnp.max(jnp.abs(v)))


# -- layer by layer ----------------------------------------------------------

def test_the_published_stage_is_what_the_config_registers():
    c = get_config("phi4_mini_flash_pp5").model.lm
    assert c.layer_types == ("mamba", "window", "mamba", "full", "gmu",
                             "cross") and c.first_layer == 14
    assert (c.hidden, c.heads, c.kv_heads, c.head_dim, c.dense_width) == (
        2560, 40, 20, 64, 10240)
    assert (c.ssm_heads * c.ssm_head_dim, c.ssm_state, c.ssm_dt_rank,
            c.ssm_conv, c.window, c.vocab) == (5120, 16, 160, 4, 512, 25088)
    assert [round(pf.lambda_init(15 + 2 * i), 4) for i in range(3)] == [
        0.7933, 0.7963, 0.798]


def test_mamba_mixer_and_what_it_hands_on_match_reference(setup):
    cfg, _, v, _, m = setup
    c, x = cfg.model.lm, _x()
    p = v["params"]["layer_0"]["mixer"]
    out, y, counters = pf.Mamba1Mixer(
        c.ssm_heads, c.ssm_state, c.ssm_dt_rank, c.ssm_conv, c.ssm_chunk,
        dtype=jnp.float32).apply({"params": p}, x)
    want_out, want_y = _per_seq(lambda s: ref.mamba(s, p, m), x)
    _close(out, want_out)
    _close(y, want_y)     # before the gate, with the D skip
    inner = jax.nn.silu(sum(
        jnp.pad((x @ p["in_proj"]["kernel"])[..., :c.ssm_heads],
                ((0, 0), (3, 0), (0, 0)))[:, j:j + N] * p["conv"]["kernel"][j]
        for j in range(4)) + p["conv"]["bias"])
    delta = jax.nn.softplus(
        (inner @ p["x_proj"]["kernel"])[..., :c.ssm_dt_rank] @ p["dt_proj"]
        + p["dt_bias"])
    assert float(counters["delta_max"]) == pytest.approx(
        float(jnp.max(delta)), rel=1e-5)
    assert float(counters["decay_min"]) == pytest.approx(float(jnp.min(
        jnp.exp(-delta[..., None] * jnp.exp(p["A_log"])))), rel=1e-4)


@pytest.mark.parametrize("layer,window", [(1, WINDOW), (3, 0)])
def test_differential_attention_matches_reference(setup, layer, window):
    cfg, _, v, _, m = setup
    c, x = cfg.model.lm, _x(4)
    p = v["params"][f"layer_{layer}"]["attn"]
    depth = c.first_layer + layer
    out, (k, val), lam = pf.DiffAttention(
        c.heads, c.kv_heads, c.head_dim, depth, window,
        dtype=jnp.float32).apply({"params": p}, x)
    want, (k1, k2, want_val) = _per_seq(
        lambda s: ref.diff_attention(s, p, m, depth, window), x)
    _close(out, want)
    # what a full layer hands on: k1 then k2, heads-major; a pair's value
    _close(k, jnp.concatenate([k1, k2], 2).transpose(0, 2, 1, 3))
    _close(val, want_val.transpose(0, 2, 1, 3))
    assert float(lam) == pytest.approx(float(
        jnp.exp(p["lambda_q1"] @ p["lambda_k1"])
        - jnp.exp(p["lambda_q2"] @ p["lambda_k2"])) + ref.lambda_init(depth))
    # the window hides keys: the two maps differ
    if window:
        other, _, _ = pf.DiffAttention(
            c.heads, c.kv_heads, c.head_dim, depth, 0,
            dtype=jnp.float32).apply({"params": p}, x)
        assert _rel(other, want) > 1e-2


def test_cross_attention_and_memory_unit_match_reference(setup):
    cfg, _, v, _, m = setup
    c, x, src = cfg.model.lm, _x(5), _x(6)
    full, cross = (v["params"][f"layer_{i}"]["attn"] for i in (3, 5))
    _, kept, _ = pf.DiffAttention(c.heads, c.kv_heads, c.head_dim, 17,
                                  dtype=jnp.float32).apply(
                                      {"params": full}, src)
    out, _, _ = pf.DiffAttention(c.heads, c.kv_heads, c.head_dim, 19,
                                 dtype=jnp.float32).apply(
                                     {"params": cross}, x, kept)
    assert "qkv_proj" not in cross and set(cross) == {
        "q_proj", "o_proj", "subln", "lambda_q1", "lambda_k1", "lambda_q2",
        "lambda_k2"}
    _close(out, _per_seq(lambda s, t: ref.diff_attention(
        s, cross, m, 19, 0, ref.diff_attention(t, full, m, 17)[1])[0],
        x, src))
    p = v["params"]["layer_4"]["gmu"]
    memory = jax.random.normal(jax.random.key(8), (B, N, c.ssm_heads))
    _close(pf.GatedMemoryUnit(dtype=jnp.float32).apply(
        {"params": p}, x, memory),
        _per_seq(lambda s, t: ref.gmu(s, p, t), x, memory))


# -- the model, the step -----------------------------------------------------

def _loss_of(model, tokens):
    targets = jnp.roll(tokens, -1, 1)

    def prog(p):
        h, _ = model.apply({"params": p}, tokens, train=True)
        return tied_cross_entropy(h, p["embed"]["embedding"], targets,
                                  chunk=64)

    return prog


def _plain(tokens, m):
    return lambda p: ref.batch_loss({"params": p}, tokens,
                                    jnp.roll(tokens, -1, 1), m)


def _check_loss_and_every_gradient(model, v, tokens, m, n_leaves):
    lp, gp = jax.jit(jax.value_and_grad(_loss_of(model, tokens)))(
        v["params"])
    lr, g_ref = jax.jit(jax.value_and_grad(_plain(tokens, m)))(v["params"])
    assert abs(float(lp) - float(lr)) < 1e-5 * float(lr)
    flat = jax.tree_util.tree_flatten_with_path(gp)[0]
    assert len(flat) == n_leaves  # every leaf, each reached (below)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(g_ref)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-4 * float(jnp.max(jnp.abs(b))), jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)
    return g_ref


# leaves: every layer 4 norm + 3 feed-forward; mamba 9, attention 9,
# memory unit 2, cross 9; the embedding and the final norm's two
_LEAVES = {"mamba": 16, "window": 16, "full": 16, "gmu": 9, "cross": 16}


def test_hidden_states_loss_and_every_gradient_match_reference(setup):
    _, model, v, tokens, m = setup
    h, counters = model.apply(v, tokens)
    _close(h, _per_seq(lambda t: ref.hidden(v, t, m), tokens), 1e-4)
    assert set(counters) == {"ssm_decay_min", "ssm_delta_max",
                             "diff_lambda_min", "diff_lambda_max",
                             "gmu_memory_abs_max"}
    _check_loss_and_every_gradient(
        model, v, tokens, m, 3 + sum(_LEAVES[k] for k in m["layer_types"]))


def test_two_readers_sum_their_cotangents_into_what_is_kept():
    """Published layers 14-21's kinds: two memory units read layer 16's
    ``m`` and two cross layers layer 17's keys and values.  Every
    gradient matches the reference, and the makers' leaves move when a
    SECOND reader is added (its cotangent reaches them)."""
    kinds = "mamba,window,mamba,full,gmu,cross,gmu,cross"
    _, model, v, tokens, m = _setup("model.lm.layer_types=" + kinds)
    assert m["layer_types"] == tuple(kinds.split(","))
    g8 = _check_loss_and_every_gradient(
        model, v, tokens, m, 3 + sum(_LEAVES[k] for k in m["layer_types"]))
    # the same weights with the second pair of readers cut off
    six = dict(m, layer_types=m["layer_types"][:6])
    g6 = jax.jit(jax.grad(_plain(tokens, six)))(v["params"])
    for leaf in (("layer_2", "mixer", "A_log"),
                 ("layer_3", "attn", "qkv_proj", "kernel")):
        a, b = g8, g6
        for k in leaf:
            a, b = a[k], b[k]
        assert _rel(a, b) > 1e-3, leaf


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(setup, fault, monkeypatch):
    """The program with one fault planted against the reference as
    published: the loss shows it (sound: 1e-7)."""
    _, _, v, tokens, m = setup
    overrides = plant(fault, monkeypatch.setattr, window=WINDOW)
    faulty = build_model(_cfg(*overrides).model)
    lp = jax.jit(_loss_of(faulty, tokens))(v["params"])
    lr = jax.jit(_plain(tokens, m))(v["params"])
    assert abs(float(lp) - float(lr)) > 1e-4 * float(lr), fault


def test_three_steps_follow_the_reference(setup):
    """The compiled train step itself (``make_unified_train_step``, dp
    preset) from the benchmark's weights on three batches: losses, the
    first gradient and the parameters' change against ``ref.follow``
    (which keeps Adam's moments on the host and updates leaf by leaf)."""
    from distributed_sod_project_tpu.parallel import make_mesh
    from distributed_sod_project_tpu.parallel.engine import \
        make_unified_train_step
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    cfg, model, v, _, m = setup
    opt = dict(kind="adamw", lr=cfg.optim.lr, weight_decay=0.1,
               warmup_steps=2, poly_power=0.9, total_steps=50)
    cfg = apply_overrides(cfg, ["optim.warmup_steps=2"])
    tx, sched = build_optimizer(cfg.optim, 50)
    batches = [{"tokens": np.asarray(t), "targets": np.roll(t, -1, 1)}
               for t in np.asarray(jax.random.randint(
                   jax.random.key(5), (3, B, N), 0, 512))]
    state = create_train_state(jax.random.key(0), model, tx, batches[0])
    state = state.replace(params=v["params"])
    step = make_unified_train_step(
        model, cfg.loss, tx, make_mesh(cfg.mesh, jax.devices()[:1]),
        preset="dp", schedule=sched, donate=False)
    losses = []
    for b in batches:
        state, metrics = step(state, b)
        losses.append(float(metrics["total"]))
    want = ref.follow(lambda: jax.tree_util.tree_map(jnp.array, v),
                      batches, {"arch": m, "optimizer": opt})
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.sqrt(jnp.sum(jnp.square(a - b)))),
        state.params, v["params"])
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(moved)[0],
                            jax.tree_util.tree_leaves(want["dparam_norms"])):
        # A bias on the keys moves no softmax: that third of W_qkv's bias
        # has rounding for a gradient, which Adam scales up to a step.
        name = jax.tree_util.keystr(path)
        tol = 2e-2 if name.endswith("['qkv_proj']['bias']") else 2e-3
        assert a == pytest.approx(float(b), rel=tol), name
        assert a > 0, name
    assert 0 < float(metrics["ssm_decay_min"]) < 1
    assert float(metrics["ssm_delta_max"]) > 0
    assert 0.5 < float(metrics["diff_lambda_min"]) \
        <= float(metrics["diff_lambda_max"]) < 1.1
    assert float(metrics["gmu_memory_abs_max"]) > 0


# -- what the per-layer remat keeps -------------------------------------------

def test_named_saves_give_the_gradient_of_no_remat(setup):
    cfg, model, v, tokens, _ = setup
    plain = build_model(dataclasses.replace(cfg.model, remat=False))
    ga = jax.jit(jax.grad(_loss_of(model, tokens)))(v["params"])
    gb = jax.jit(jax.grad(_loss_of(plain, tokens)))(v["params"])
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ga)[0],
                            jax.tree_util.tree_leaves(gb)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 1e-5 * float(jnp.max(jnp.abs(b))), jax.tree_util.keystr(path)


def _log_lines(caplog, trace, starts):
    logger = logging.getLogger("dsod")  # does not propagate
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="dsod"):
            caplog.clear()
            trace()
            return [r.getMessage() for r in caplog.records
                    if r.getMessage().startswith(starts)]
    finally:
        logger.removeHandler(caplog.handler)


def test_the_step_says_its_saves_and_its_flash_grids(caplog):
    """At 1,536 tokens (three blocks of 512 a side) and a window of 24:
    the full layers' triangle and the window layer's band (the diagonal
    and the block under it); a trace that is not differentiated is
    quiet."""
    _, model, v, _, _ = _setup("data.seq_len=1536", "model.lm.ssm_chunk=128")
    tokens = jax.random.randint(jax.random.key(0), (1, 1536), 0, 512)
    loss = _loss_of(model, tokens)
    said = _log_lines(
        caplog, lambda: jax.eval_shape(jax.grad(loss), v["params"]),
        ("remat saves", "flash grid"))
    assert len(said) == 3
    # three attention layers' output and lse, two scans' output and edges
    assert re.match(r"remat saves \(phi4flash, 6 layers\): flash_out=3 "
                    r"flash_lse=3 sel_scan_y=2 sel_scan_edges=2 ", said[0])
    assert said[1:] == ["flash grid: steps=6 of 9 a head",
                        "flash grid (window 24): steps=5 of 9 a head"]
    assert pf.REMAT_SAVES == ("flash_out", "flash_lse", "sel_scan_y",
                              "sel_scan_edges")
    assert not _log_lines(caplog,
                          lambda: jax.eval_shape(loss, v["params"]),
                          ("remat saves", "flash grid"))


def test_gradient_runs_the_kernels_it_should(setup):
    """In the jaxpr of the stage's gradient: each Mamba-1 layer runs the
    scan's forward kernel ONCE (its output and edge states are kept) and
    its one backward kernel once, the conv's forward twice (no named
    save) and its backward once; each of the three attention layers ONE
    forward and one backward flash call — both softmax maps in one."""
    from test_lfm2 import _eqns

    _, model, v, tokens, _ = setup
    jaxpr = jax.make_jaxpr(jax.grad(_loss_of(model, tokens)))(v["params"])
    names = [re.search(r"dsod\.kernel\.(\w+)$",
                       str(eqn.source_info.name_stack)).group(1)
             for eqn in _eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert sorted(names) == sorted(
        ["selective_scan", "selective_scan_bwd", "causal_conv",
         "causal_conv", "causal_conv_bwd"] * 2
        + ["flash_attention_causal", "flash_attention_causal_bwd"] * 3)


# -- scopes -------------------------------------------------------------------

SCOPES = ("dsod.ssm", "dsod.ssm.conv", "dsod.ssm.scan", "dsod.ssm.gate",
          "dsod.attn.window", "dsod.attn.full", "dsod.attn.flash",
          "dsod.gmu", "dsod.densemlp", "dsod.kernel.selective_scan",
          "dsod.kernel.selective_scan_bwd", "dsod.kernel.causal_conv",
          "dsod.kernel.causal_conv_bwd", "dsod.kernel.flash_attention_causal",
          "dsod.kernel.flash_attention_causal_bwd")
_STAGE = re.compile(r"dsod\.(encoder|decoder|heads|loss|update)\b")


@pytest.fixture(scope="module")
def lowered_text():
    from test_profiler_names import _lowered_step_text

    return _lowered_step_text("phi4_mini_flash_pp5")


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_names_the_new_scopes(lowered_text, scope):
    paths = re.findall(r'^#loc\d+ = loc\("([^"]*)"', lowered_text, re.M)
    under = [p for p in paths if re.search(re.escape(scope) + r"\b", p)]
    assert under, scope
    stages = [set(_STAGE.findall(p)) for p in under]
    assert {"encoder"} in stages and all(s <= {"encoder"} for s in stages)
    if scope.startswith("dsod.kernel.selective_scan"):
        assert all("dsod.ssm.scan" in p for p in under)
    if scope.startswith("dsod.kernel.causal_conv"):
        assert all("dsod.ssm.conv" in p for p in under)
    if scope.startswith("dsod.kernel.flash"):
        assert all("dsod.attn.flash" in p for p in under)
    if scope == "dsod.attn.flash":  # inside a window or a full layer
        assert all(re.search(r"dsod\.attn\.(window|full)\b", p)
                   for p in under)
        assert {"window", "full"} == {
            re.search(r"dsod\.attn\.(window|full)\b", p).group(1)
            for p in under}


def test_no_product_of_the_step_is_outside_a_stage(lowered_text):
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
    assert all(np.isfinite(h["total"]) for h in seen)
    assert all(0 < h["ssm_decay_min"] < 1 and h["ssm_delta_max"] > 0
               and h["diff_lambda_min"] > 0 and h["gmu_memory_abs_max"] > 0
               for h in seen)
