"""The third token model (models/granite.py, config
``granite_4_0_h_micro_pp4``) against its plain reference
(benchmark/reference/granite.py) on the CPU at tiny widths, float32,
seeded weights (benchmark/harness/weights_ssm.py):

- the scan: the XLA chunked form and the Pallas kernels (interpret mode)
  against the token-by-token recurrence, forward and all five
  cotangents, at two chunk sizes, over several chunks, with a decay so
  slow that a dropped cross-chunk term fails; a chunk that does not
  divide the sequence raises;
- the mixer, the position-free attention layer, the whole model's
  hidden states, loss and every gradient leaf;
- three optimizer steps of the compiled train step against the
  reference's ``follow``;
- each of the four multipliers and the missing rotation, left out one
  at a time, fails that comparison;
- what a rematerialised layer keeps by name, and the step's log line;
- the scan's carried state is float32 under bfloat16 operands;
- one forward scan kernel a Mamba-2 layer in the forward, two and one
  backward in the gradient; the same count of the conv's kernel pair,
  layer by layer;
- every new ``dsod.*`` scope in the lowered step, inside the encoder
  stage;
- three steps of ``fit()`` with the two counters on the stream.
"""

import collections
import dataclasses
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.weights_ssm import variables_builder
from benchmark.reference import granite as ref
from distributed_sod_project_tpu.configs import apply_overrides, get_config
from distributed_sod_project_tpu.losses.token_ce import tied_cross_entropy
from distributed_sod_project_tpu.models import build_model
from distributed_sod_project_tpu.models import granite as gr
from distributed_sod_project_tpu.pallas import ssd_scan as ssd

TINY = ["model.lm.vocab=512", "model.lm.hidden=64", "model.lm.heads=4",
        "model.lm.kv_heads=2", "model.lm.head_dim=16",
        "model.lm.dense_width=96", "model.lm.ssm_heads=8",
        "model.lm.ssm_head_dim=16", "model.lm.ssm_state=16",
        "model.lm.ssm_chunk=32", "data.seq_len=128", "data.vocab=512",
        "data.synthetic_size=32", "global_batch_size=2",
        "model.compute_dtype=float32"]
B, N = 2, 128  # four chunks of 32 tokens


def _cfg(*more):
    return apply_overrides(get_config("granite_4_0_h_micro_pp4"),
                           TINY + list(more))


def _arch(c):
    """The reference's ``arch`` (configs/granite_4_0_h_micro_pp4.json) at
    the program's tiny shape."""
    return dict(layer_types=c.layer_types, heads=c.heads,
                kv_heads=c.kv_heads, head_dim=c.head_dim,
                ssm_heads=c.ssm_heads, ssm_head_dim=c.ssm_head_dim,
                ssm_state=c.ssm_state, norm_eps=c.norm_eps,
                embedding_multiplier=c.embedding_multiplier,
                residual_multiplier=c.residual_multiplier,
                attention_multiplier=c.attention_multiplier,
                logits_scaling=c.logits_scaling)


def _variables(model, tokens, seed=7):
    shapes = jax.eval_shape(lambda r, t: model.init(r, t),
                            jax.random.key(1), tokens)
    return variables_builder({"params": shapes["params"],
                              "batch_stats": {}}, {})(seed)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    model = build_model(cfg.model)
    tokens = jax.random.randint(jax.random.key(0), (B, N), 0, 512)
    return cfg, model, _variables(model, tokens), tokens, _arch(cfg.model.lm)


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol * float(np.max(np.abs(b)) + 1e-12))


def _x(seed=3):
    return jax.random.normal(jax.random.key(seed), (B, N, 64))


def _per_seq(fn, x):
    return jnp.stack([fn(x[i]) for i in range(x.shape[0])])


# -- the scan ----------------------------------------------------------------

def _scan_args(h, p, s, n, seed=0):
    """A decay near 1 (delta A between -0.02 and -0.3 a token): after a
    chunk of 16 or 32 tokens most of the state is still there, so a
    dropped cross-chunk term is a gross error."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (B, n, h, p))
    dt = jnp.exp(jax.random.uniform(ks[1], (B, n, h), minval=np.log(0.02),
                                    maxval=np.log(0.1)))
    a = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=3.0)
    b = jax.random.normal(ks[3], (B, n, s))
    c = jax.random.normal(ks[4], (B, n, s))
    return (x, dt, a, b, c), jax.random.normal(ks[5], (B, n, h, p))


def _recurrence(x, dt, a, b, c):
    return jax.vmap(lambda x, dt, b, c: ref.recurrence(
        x, dt, a, b, c, remat=False))(x, dt, b, c)


def _rel(u, v):
    return float(jnp.max(jnp.abs(u - v)) / jnp.max(jnp.abs(v)))


SCANS = {"xla": ssd.ssd_scan_xla, "kernel": ssd.ssd_scan}


@pytest.mark.parametrize("impl", list(SCANS))
@pytest.mark.parametrize("shape", [(4, 64, 32, 64, 16), (4, 64, 32, 64, 32),
                                   (8, 16, 16, 96, 32)])
def test_scan_matches_the_recurrence_forward_and_all_five_cotangents(
        impl, shape):
    h, p, s, n, chunk = shape
    scan = SCANS[impl]
    args, g = _scan_args(h, p, s, n)
    want, vjp = jax.vjp(_recurrence, *args)
    got, vjp_got = jax.vjp(lambda *a: scan(*a, chunk=chunk), *args)
    assert _rel(got, want) < 1e-5
    for name, u, v in zip("x dt A B C".split(), vjp_got(g), vjp(g)):
        assert _rel(u, v) < 1e-5, name
    # the same with the state dropped at every chunk's edge is far off
    cut = lambda t: t.reshape((-1, chunk) + t.shape[2:])  # noqa: E731
    x, dt, a, b, c = args
    alone = scan(cut(x), cut(dt), a, cut(b), cut(c),
                 chunk=chunk).reshape(x.shape)
    assert _rel(alone, want) > 0.1


@pytest.mark.parametrize("impl", list(SCANS))
def test_a_chunk_that_does_not_divide_the_sequence_raises(impl):
    args, _ = _scan_args(4, 64, 32, 80)
    with pytest.raises(ValueError, match="does not divide"):
        SCANS[impl](*args, chunk=32)


def test_the_kernel_refuses_lanes_it_cannot_fill_on_the_chip():
    args, _ = _scan_args(8, 16, 16, 64)
    with pytest.raises(ValueError, match="128 lanes"):
        ssd.ssd_scan(*args, chunk=32, interpret=False)


# -- layer by layer, the model, the step -------------------------------------

@pytest.mark.parametrize("impl", list(SCANS))
def test_mixer_matches_reference(setup, impl, monkeypatch):
    cfg, _, v, _, m = setup
    c, x = cfg.model.lm, _x()
    p = v["params"]["layer_0"]["mixer"]
    monkeypatch.setattr(gr, "ssd_scan", SCANS[impl])
    got, counters = gr.Mamba2Mixer(
        c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_conv, c.ssm_chunk,
        c.norm_eps, dtype=jnp.float32).apply({"params": p}, x)
    _close(got, _per_seq(lambda s: ref.mamba(s, p, m), x))
    delta = jax.nn.softplus(
        (x @ p["in_proj"]["kernel"])[..., -c.ssm_heads:] + p["dt_bias"])
    assert float(counters["delta_max"]) == pytest.approx(
        float(jnp.max(delta)), rel=1e-5)
    assert float(counters["decay_min"]) == pytest.approx(float(jnp.min(
        jnp.exp(-delta * jnp.exp(p["A_log"])))), rel=1e-4)


def test_attention_matches_reference(setup):
    cfg, _, v, _, m = setup
    c, x = cfg.model.lm, _x(4)
    p = v["params"]["layer_5"]["attn"]
    got = gr.Attention(c.heads, c.kv_heads, c.head_dim,
                       c.attention_multiplier,
                       dtype=jnp.float32).apply({"params": p}, x)
    _close(got, _per_seq(lambda s: ref.attention(s, p, m), x))


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


def test_hidden_states_loss_and_every_gradient_match_reference(setup):
    _, model, v, tokens, m = setup
    h, _ = model.apply(v, tokens)
    _close(h, _per_seq(lambda t: ref.hidden(v, t, m), tokens), 1e-4)
    lp, gp = jax.jit(jax.value_and_grad(_loss_of(model, tokens)))(
        v["params"])
    lr, g_ref = jax.jit(jax.value_and_grad(_plain(tokens, m)))(v["params"])
    assert abs(float(lp) - float(lr)) < 1e-5 * float(lr)
    flat = jax.tree_util.tree_flatten_with_path(gp)[0]
    assert len(flat) == 2 + 9 * 13 + 9  # every leaf, each reached (below)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(g_ref)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 2e-4 * float(jnp.max(jnp.abs(b))), jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)


def _rotated(monkeypatch):
    """Plants lfm2's rotation on q and k in front of the flash kernel."""
    from distributed_sod_project_tpu.models.lfm2 import rope

    real = gr.flash_attention_causal
    turn = lambda t: rope(t.transpose(0, 2, 1, 3), 1e4).transpose(  # noqa: E731
        0, 2, 1, 3).astype(t.dtype)
    monkeypatch.setattr(gr, "flash_attention_causal",
                        lambda q, k, v: real(turn(q), turn(k), v))


@pytest.mark.parametrize("override", [
    "model.lm.embedding_multiplier=1.0", "model.lm.residual_multiplier=1.0",
    "model.lm.attention_multiplier=0.0", "model.lm.logits_scaling=1.0",
    "rotary"])
def test_a_multiplier_left_out_or_a_rotation_added_fails_the_comparison(
        setup, override, monkeypatch):
    """One planted fault each: the program without 12, 0.22, 1/64 or 1/8,
    or with a rotation, against the reference as published.  The loss
    shows the three that touch every layer; the attention layer's own
    leaves show the two that touch it alone (at this width a random
    causal softmax is all but uniform whatever scales its scores)."""
    _, _, v, tokens, m = setup
    if override == "rotary":
        _rotated(monkeypatch)
        faulty = build_model(_cfg().model)
    else:
        faulty = build_model(_cfg(override).model)
    lp, gp = jax.jit(jax.value_and_grad(_loss_of(faulty, tokens)))(
        v["params"])
    lr, g_ref = jax.jit(jax.value_and_grad(_plain(tokens, m)))(v["params"])
    if "attention" in override or "rotary" in override:
        a, b = (g["layer_5"]["attn"]["q_proj"]["kernel"]
                for g in (gp, g_ref))
        assert _rel(a, b) > 1e-2
    else:
        assert abs(float(lp) - float(lr)) > 1e-4 * float(lr)  # sound: 1e-7


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
        assert a == pytest.approx(float(b), rel=2e-3), \
            jax.tree_util.keystr(path)
        assert a > 0, jax.tree_util.keystr(path)
    assert 0 < float(metrics["ssm_decay_min"]) < 1
    assert float(metrics["ssm_delta_max"]) > 0


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


def test_the_step_says_what_its_remat_saves(setup, caplog):
    _, model, v, tokens, _ = setup
    logger = logging.getLogger("dsod")
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="dsod"):
            jax.make_jaxpr(jax.grad(_loss_of(model, tokens)))(v["params"])
            lines = [r.getMessage() for r in caplog.records
                     if "remat saves (granite" in r.getMessage()]
            caplog.clear()
            jax.make_jaxpr(_loss_of(model, tokens))(v["params"])
            quiet = [r for r in caplog.records
                     if "remat saves" in r.getMessage()]
    finally:
        logger.removeHandler(caplog.handler)
    assert len(lines) == 1 and not quiet
    # the one attention layer's output and lse; the scan's output and
    # chunk states are named, not kept
    assert re.search(r"\(granite, 10 layers\): flash_out=1 flash_lse=1 ",
                     lines[0]), lines[0]
    assert gr.REMAT_SAVES == ("flash_out", "flash_lse")
    assert not set(ssd.SSD_RESIDUAL_NAMES) & set(gr.REMAT_SAVES)



def test_the_step_says_its_flash_grid(setup, caplog):
    from test_lfm2 import flash_grid_lines

    _, model, v, tokens, _ = setup
    loss = _loss_of(model, tokens)
    said, quiet = flash_grid_lines(
        caplog, lambda: jax.eval_shape(jax.grad(loss), v["params"]),
        lambda: jax.eval_shape(loss, v["params"]))
    assert said == ["flash grid: steps=1 of 1 a head"] and not quiet


def test_gradient_runs_the_scan_kernels_it_should(setup):
    """In the jaxpr of the config's gradient (all 10 layers, tiny
    widths): 9 backward scan kernels, and 18 forward ones (the remat
    keeps neither the scan's output nor its chunk states, so each
    layer's backward runs the forward kernel again); the attention
    layer's forward kernel once (its output and lse are kept)."""
    from test_lfm2 import _eqns

    cfg, model, v, tokens, _ = setup
    assert cfg.model.lm.layer_types == get_config(
        "granite_4_0_h_micro_pp4").model.lm.layer_types
    assert cfg.model.lm.layer_types.count("mamba") == 9
    jaxpr = jax.make_jaxpr(jax.grad(_loss_of(model, tokens)))(v["params"])
    names = [eqn.params["jaxpr"].debug_info.func_name
             for eqn in _eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert names.count("_bwd_kernel") == 9
    assert names.count("_fwd_kernel") == 18
    assert names.count("_c_fwd_kernel") == 1
    assert names.count("_c_bwd_kernel") == 1


def test_gradient_runs_the_conv_kernels_it_should(setup):
    """Layer by layer in the same jaxpr: each Mamba-2 layer runs the conv's
    forward kernel twice (``x`` is no named save, so the layer's backward
    makes it and the conv's output again) and its ONE backward kernel
    once; the attention layer runs neither."""
    from test_lfm2 import _eqns

    _, model, v, tokens, _ = setup
    jaxpr = jax.make_jaxpr(jax.grad(_loss_of(model, tokens)))(v["params"])
    calls = collections.Counter()
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            stack = str(eqn.source_info.name_stack)
            kernel = re.search(r"dsod\.kernel\.(\w+)$", stack).group(1)
            if kernel.startswith("causal_conv"):
                assert "/dsod.ssm.conv/" in stack, stack
                calls[re.search(r"layer_(\d+)", stack).group(1), kernel] += 1
    mamba = [str(i) for i, op in enumerate(model.cfg.layer_types)
             if op == "mamba"]
    assert len(mamba) == 9
    assert calls == {(i, k): n for i in mamba for k, n in (
        ("causal_conv", 2), ("causal_conv_bwd", 1))}


def test_the_scan_carries_its_state_in_float32_under_bfloat16_operands():
    """The configuration states a float32 carried state.  On the chip a
    bfloat16 state stays inside the cell's limits (PERF.md section 7),
    so the yardstick cannot hold a later PR to it: this does.  The
    forward kernel writes y in the operands' type and the state each
    chunk started from in float32."""
    from test_lfm2 import _eqns

    assert ssd.STATE_DTYPE == jnp.float32
    (x, dt, a, b, c), _ = _scan_args(4, 64, 32, 64)
    x, b, c = (t.astype(jnp.bfloat16) for t in (x, b, c))
    jaxpr = jax.make_jaxpr(jax.grad(lambda *t: jnp.sum(ssd.ssd_scan(
        *t, chunk=32).astype(jnp.float32))))(x, dt, a, b, c)
    (fwd,) = [eqn for eqn in _eqns(jaxpr.jaxpr)
              if eqn.primitive.name == "pallas_call"
              and eqn.params["jaxpr"].debug_info.func_name == "_fwd_kernel"]
    y, states = (v.aval for v in fwd.outvars)
    assert y.dtype == jnp.bfloat16
    assert states.dtype == jnp.float32 and states.shape == (B, 2, 32, 256)


# -- scopes -------------------------------------------------------------------

SCOPES = ("dsod.ssm", "dsod.ssm.conv", "dsod.ssm.scan", "dsod.ssm.gate",
          "dsod.attn", "dsod.densemlp", "dsod.kernel.ssd_scan",
          "dsod.kernel.ssd_scan_bwd", "dsod.kernel.causal_conv",
          "dsod.kernel.causal_conv_bwd", "dsod.kernel.flash_attention_causal",
          "dsod.kernel.flash_attention_causal_bwd")
_STAGE = re.compile(r"dsod\.(encoder|decoder|heads|loss|update)\b")


@pytest.fixture(scope="module")
def lowered_text():
    from test_profiler_names import _lowered_step_text

    return _lowered_step_text("granite_4_0_h_micro_pp4")


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_names_the_new_scopes(lowered_text, scope):
    paths = re.findall(r'^#loc\d+ = loc\("([^"]*)"', lowered_text, re.M)
    under = [p for p in paths if re.search(re.escape(scope) + r"\b", p)]
    assert under, scope
    stages = [set(_STAGE.findall(p)) for p in under]
    assert {"encoder"} in stages and all(s <= {"encoder"} for s in stages)
    if scope.startswith("dsod.kernel.ssd_scan"):
        assert all("dsod.ssm.scan" in p for p in under)
    if scope.startswith("dsod.kernel.causal_conv"):
        assert all("dsod.ssm.conv" in p for p in under)


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
               for h in seen)
