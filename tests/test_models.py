"""Model zoo tests: forward shapes + finite loss/grad smoke (SURVEY.md §4)."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sod_project_tpu.configs import (apply_overrides, get_config,
                                                 list_configs)
from distributed_sod_project_tpu.models import build_model
from distributed_sod_project_tpu.models.backbones import ResNet34, ResNet50, VGG16


@pytest.mark.parametrize("size", [(64, 64), (96, 64)])
def test_vgg16_pyramid_shapes(size):
    h, w = size
    m = VGG16()
    x = jnp.zeros((2, h, w, 3))
    vars_ = m.init(jax.random.key(0), x)
    feats = m.apply(vars_, x)
    assert len(feats) == 5
    widths = (64, 128, 256, 512, 512)
    for i, (f, c) in enumerate(zip(feats, widths)):
        s = 2**i
        assert f.shape == (2, h // s, w // s, c), f"level {i}: {f.shape}"


@pytest.mark.slow
def test_resnet50_pyramid_shapes():
    m = ResNet50()
    x = jnp.zeros((1, 64, 64, 3))
    feats = m.apply(m.init(jax.random.key(0), x), x)
    shapes = [f.shape for f in feats]
    assert shapes == [
        (1, 32, 32, 64),
        (1, 16, 16, 256),
        (1, 8, 8, 512),
        (1, 4, 4, 1024),
        (1, 2, 2, 2048),
    ]


def test_resnet_s2d_stem_matches_plain_stem(monkeypatch):
    """DSOD_STEM_IMPL=s2d (layers.SpaceToDepthStem) is an
    arithmetic-identical re-tiling of the 7×7/2 stem: same param tree
    (init AND restore interchange), same outputs to conv-reassociation
    tolerance.  Guards the kernel-regroup/padding derivation."""
    m = ResNet50()
    x = jnp.asarray(np.random.RandomState(0).randn(2, 48, 48, 3),
                    jnp.float32)

    monkeypatch.delenv("DSOD_STEM_IMPL", raising=False)
    v_plain = m.init(jax.random.key(0), x)
    feats_plain = m.apply(v_plain, x)

    monkeypatch.setenv("DSOD_STEM_IMPL", "s2d")
    v_s2d = m.init(jax.random.key(0), x)
    # Identical param trees — same paths, shapes, AND init values (the
    # RNG folds over the same "ConvBNAct_0/Conv_0/kernel" path).
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        v_plain, v_s2d)
    feats_s2d = m.apply(v_plain, x)  # plain-trained params, s2d compute
    for fp, fs in zip(feats_plain, feats_s2d):
        np.testing.assert_allclose(np.asarray(fp), np.asarray(fs),
                                   rtol=1e-4, atol=1e-4)

    # Odd spatial size: falls back to the plain stem (no s2d possible)
    # — and WARNS: a run made under the variable would otherwise be
    # taken for an s2d measurement it is not (ADVICE r3).
    # Fully-convolutional → reuse the same params, no third init.
    from distributed_sod_project_tpu.models.backbones import resnet

    resnet._S2D_FALLBACK_WARNED.clear()
    x_odd = jnp.asarray(np.random.RandomState(1).randn(1, 47, 47, 3),
                        jnp.float32)
    assert m.apply(v_plain, x_odd)[0].shape == (1, 24, 24, 64)
    assert (47, 47) in resnet._S2D_FALLBACK_WARNED


def test_resnet34_pyramid_shapes():
    m = ResNet34()
    x = jnp.zeros((1, 64, 64, 3))
    feats = m.apply(m.init(jax.random.key(0), x), x)
    assert [f.shape[-1] for f in feats] == [64, 64, 128, 256, 512]


@pytest.mark.parametrize("config_name", ["minet_vgg16_ref", "gatenet_vgg16"])
def test_model_forward_from_config(config_name):
    cfg = get_config(config_name)
    model = build_model(cfg.model.__class__(
        name=cfg.model.name, backbone=cfg.model.backbone, sync_bn=False,
        compute_dtype="float32"))
    x = jnp.zeros((1, 64, 64, 3))
    vars_ = model.init(jax.random.key(0), x, train=False)
    outs = model.apply(vars_, x, train=False)
    assert isinstance(outs, list) and len(outs) >= 1
    assert outs[0].shape == (1, 64, 64, 1)
    assert outs[0].dtype == jnp.float32


@pytest.mark.slow
def test_minet_train_mode_updates_batch_stats_and_grads_finite():
    cfg = get_config("minet_vgg16_ref")
    model = build_model(cfg.model.__class__(
        name="minet", backbone="vgg16", sync_bn=False, compute_dtype="float32"))
    rng = jax.random.key(1)
    x = jax.random.normal(rng, (2, 64, 64, 3))
    y = (jax.random.uniform(rng, (2, 64, 64, 1)) > 0.5).astype(jnp.float32)
    vars_ = model.init(rng, x, train=True)

    def loss_fn(params):
        outs, new_state = model.apply(
            {"params": params, "batch_stats": vars_["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        logit = outs[0]
        loss = jnp.mean(
            jnp.maximum(logit, 0) - logit * y + jnp.log1p(jnp.exp(-jnp.abs(logit)))
        )
        return loss, new_state

    (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        vars_["params"]
    )
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(g)) for g in flat)
    assert any(float(jnp.abs(g).max()) > 0 for g in flat)
    # batch_stats actually changed
    old = jax.tree_util.tree_leaves(vars_["batch_stats"])
    new = jax.tree_util.tree_leaves(new_state["batch_stats"])
    assert any(not np.allclose(a, b) for a, b in zip(old, new))


@pytest.mark.slow
def test_minet_bf16_compute_keeps_f32_output():
    cfg = get_config("minet_vgg16_ref")
    model = build_model(cfg.model.__class__(
        name="minet", backbone="vgg16", sync_bn=False, compute_dtype="bfloat16"))
    x = jnp.zeros((1, 32, 32, 3))
    vars_ = model.init(jax.random.key(0), x, train=False)
    outs = model.apply(vars_, x, train=False)
    assert outs[0].dtype == jnp.float32
    # params stay f32
    p = jax.tree_util.tree_leaves(vars_["params"])
    assert all(a.dtype == jnp.float32 for a in p)


def _finite_grad_check(model, x, y, depth=None, n_outputs=None):
    rng = jax.random.key(0)
    vars_ = model.init(rng, x, depth, train=True)

    def loss_fn(params):
        outs, new_state = model.apply(
            {"params": params, "batch_stats": vars_["batch_stats"]},
            x, depth, train=True, mutable=["batch_stats"],
        )
        loss = sum(
            jnp.mean(jnp.maximum(l, 0) - l * y + jnp.log1p(jnp.exp(-jnp.abs(l))))
            for l in outs
        )
        return loss, outs

    (loss, outs), grads = jax.value_and_grad(loss_fn, has_aux=True)(vars_["params"])
    if n_outputs is not None:
        assert len(outs) == n_outputs
    for l in outs:
        assert l.shape == (x.shape[0], x.shape[1], x.shape[2], 1)
        assert l.dtype == jnp.float32
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(g)) for g in flat)
    assert any(float(jnp.abs(g).max()) > 0 for g in flat)


@pytest.mark.slow
def test_u2net_seven_outputs_and_finite_grads():
    from distributed_sod_project_tpu.models.u2net import U2Net

    model = U2Net(small=True)
    x = jax.random.normal(jax.random.key(1), (1, 64, 64, 3))
    y = (jax.random.uniform(jax.random.key(2), (1, 64, 64, 1)) > 0.5).astype(
        jnp.float32)
    _finite_grad_check(model, x, y, n_outputs=7)


@pytest.mark.slow
def test_basnet_eight_outputs_and_finite_grads():
    from distributed_sod_project_tpu.models.basnet import BASNet

    model = BASNet()
    x = jax.random.normal(jax.random.key(1), (1, 64, 64, 3))
    y = (jax.random.uniform(jax.random.key(2), (1, 64, 64, 1)) > 0.5).astype(
        jnp.float32)
    _finite_grad_check(model, x, y, n_outputs=8)


@pytest.mark.slow
def test_hdfnet_rgbd_outputs_and_finite_grads():
    from distributed_sod_project_tpu.models.hdfnet import HDFNet

    model = HDFNet(backbone="vgg16")
    x = jax.random.normal(jax.random.key(1), (1, 64, 64, 3))
    d = jax.random.normal(jax.random.key(3), (1, 64, 64, 1))
    y = (jax.random.uniform(jax.random.key(2), (1, 64, 64, 1)) > 0.5).astype(
        jnp.float32)
    _finite_grad_check(model, x, y, depth=d, n_outputs=3)


def test_hdfnet_requires_depth():
    from distributed_sod_project_tpu.models.hdfnet import HDFNet

    model = HDFNet()
    x = jnp.zeros((1, 32, 32, 3))
    with pytest.raises(ValueError, match="RGB-D"):
        model.init(jax.random.key(0), x, None, train=False)


def test_dynamic_local_filter_identity_kernel():
    """A one-hot-center kernel must reproduce the input exactly."""
    from distributed_sod_project_tpu.models.hdfnet import dynamic_local_filter

    x = jax.random.normal(jax.random.key(0), (2, 8, 8, 4))
    k = jnp.zeros((2, 8, 8, 9)).at[..., 4].set(1.0)  # center tap of 3x3
    out = dynamic_local_filter(x, k, ksize=3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-6)


def test_dynamic_local_filter_mean_kernel_matches_avgpool():
    """Uniform kernels = 3×3 box filter (zero-padded), cross-checked."""
    from distributed_sod_project_tpu.models.hdfnet import dynamic_local_filter

    x = jax.random.normal(jax.random.key(0), (1, 6, 6, 2))
    k = jnp.full((1, 6, 6, 9), 1.0 / 9.0)
    out = dynamic_local_filter(x, k, ksize=3)
    ref = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 3, 3, 1), (1, 1, 1, 1), "SAME") / 9.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.slow
def test_gatenet_five_outputs_and_finite_grads():
    from distributed_sod_project_tpu.models.gatenet import GateNet

    model = GateNet(backbone="vgg16")
    x = jax.random.normal(jax.random.key(1), (1, 64, 64, 3))
    y = (jax.random.uniform(jax.random.key(2), (1, 64, 64, 1)) > 0.5).astype(
        jnp.float32)
    _finite_grad_check(model, x, y, n_outputs=5)


def test_gatenet_gate_actually_gates():
    """A zeroed gate conv (bias -inf-ish) must suppress the skip: the
    GateUnit output scales with sigmoid of the gate logit."""
    from distributed_sod_project_tpu.models.gatenet import GateUnit

    gu = GateUnit()
    enc = jnp.ones((1, 8, 8, 4))
    dec = jnp.zeros((1, 8, 8, 4))
    vars_ = gu.init(jax.random.key(0), enc, dec)
    out = gu.apply(vars_, enc, dec)
    assert out.shape == enc.shape
    # Force a hugely negative gate logit (conv kernel ≪ 0, BN at its
    # identity init): sigmoid → 0, so the skip is fully suppressed.
    neg = jax.tree.map(lambda a: jnp.full_like(a, -50.0)
                       if a.ndim == 4 else a, vars_)
    out0 = gu.apply(neg, enc, dec)
    assert float(jnp.abs(out0).max()) < 1e-6


def test_registry_builds_all_zoo_models():
    from distributed_sod_project_tpu.models import list_models

    assert {"minet", "u2net", "basnet", "hdfnet",
            "gatenet"} <= set(list_models())


@pytest.mark.parametrize("config_name", list_configs())
def test_every_registered_config_resolves_to_a_kind(config_name):
    """The seam the step builder, the state, the first-batch check and
    the lowering tools ask (``models/registry.py::kind_of``): every
    registered configuration's model is an image or a token model, and
    the kind's zero batch is one its own check and its init accept."""
    from distributed_sod_project_tpu.models import kind_of

    cfg = apply_overrides(get_config(config_name), [
        "data.image_size=32,32", "data.seq_len=192"])
    kind = kind_of(build_model(cfg.model))
    tokens = cfg.model.name in ("lfm2", "kimi", "granite", "ouro",
                                "nemotron_h", "phi4flash")
    assert kind.name == ("tokens" if tokens else "image")
    assert kind.dp_only == tokens
    batch = kind.zero_batch(cfg, 2)
    with warnings.catch_warnings():  # zeros: "every mask pixel is 0"
        warnings.simplefilter("ignore", UserWarning)
        kind.check_first_batch(batch, cfg)
    shapes = [None if a is None else a.shape
              for a in kind.init_inputs(batch)]
    if tokens:
        assert sorted(batch) == ["targets", "tokens"]
        assert shapes == [(1, 128)]  # a stretch of ONE sequence
        with pytest.raises(ValueError, match="not integers"):
            kind.check_first_batch(kind.zero_batch(apply_overrides(
                cfg, ["data.seq_len=64"]), 2), cfg)
    else:
        depth = (2, 32, 32, 1) if cfg.data.use_depth else None
        assert ("depth" in batch) == cfg.data.use_depth
        assert shapes == [(2, 32, 32, 3), depth]
        with pytest.raises(ValueError, match="image shape"):
            kind.check_first_batch(kind.zero_batch(apply_overrides(
                cfg, ["data.image_size=16,16"]), 2), cfg)


@pytest.mark.slow
def test_swin_backbone_pyramid_shapes():
    from distributed_sod_project_tpu.models.backbones.swin import SwinT

    m = SwinT()
    x = jnp.zeros((1, 64, 64, 3))
    feats = m.apply(m.init(jax.random.key(0), x), x)
    assert [f.shape for f in feats] == [
        (1, 16, 16, 96), (1, 8, 8, 192), (1, 4, 4, 384), (1, 2, 2, 768)]


def test_swin_window_partition_roundtrip():
    from distributed_sod_project_tpu.models.backbones.swin import (
        window_partition, window_reverse)

    x = jax.random.normal(jax.random.key(0), (2, 8, 12, 5))
    w = 4
    parts = window_partition(x, w)
    assert parts.shape == (2 * 2 * 3, 16, 5)
    back = window_reverse(parts, w, 8, 12)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x))


@pytest.mark.slow
def test_swin_sod_outputs_and_finite_grads():
    from distributed_sod_project_tpu.models.swin_sod import SwinSOD

    model = SwinSOD(width=32)
    x = jax.random.normal(jax.random.key(1), (1, 64, 64, 3))
    y = (jax.random.uniform(jax.random.key(2), (1, 64, 64, 1)) > 0.5).astype(
        jnp.float32)
    _finite_grad_check(model, x, y, n_outputs=3)


@pytest.mark.slow
def test_swin_nondivisible_input_padding():
    # 56 = 8*7: stride-4 map is 14 (divisible by 7), stride-8 is 7,
    # stride-16 is 3 (needs pad→window clamp), stride-32 is 1.
    from distributed_sod_project_tpu.models.backbones.swin import SwinT

    m = SwinT()
    x = jnp.zeros((1, 56, 56, 3))
    feats = m.apply(m.init(jax.random.key(0), x), x)
    assert [f.shape[1] for f in feats] == [14, 7, 3, 1]


@pytest.mark.parametrize("shape,hw", [
    ((2, 10, 10, 3), (20, 20)),   # 2x up (every decoder stage)
    # One representative case stays in the quick gate; each extra case
    # costs ~10 s of cold XLA compile (resize oracle + fast path) and
    # they guard the same slice/lerp math — full suite runs them all.
    pytest.param((1, 5, 5, 2), (40, 40),      # 8x up (deep-sup heads)
                 marks=pytest.mark.slow),
    pytest.param((2, 16, 16, 3), (8, 8),      # 2x antialiased down
                 marks=pytest.mark.slow),
    pytest.param((2, 12, 8, 3), (6, 16),      # mixed down2-H / up2-W
                 marks=pytest.mark.slow),
    ((1, 9, 9, 1), (3, 3)),       # non-integer factor -> fallback
])
def test_resize_fast_path_matches_jax_image(shape, hw):
    # The slice/lerp fast paths (layers._upsample_axis/_downsample2_axis)
    # must be numerically identical to jax.image.resize's bilinear
    # (half-pixel centers, antialias on downscale, edge renorm) — the
    # torch-port parity suite and every zoo logit depend on it.
    from distributed_sod_project_tpu.models.layers import resize_to

    x = jax.random.normal(jax.random.key(0), shape)
    ref = jax.image.resize(x, (shape[0],) + tuple(hw) + (shape[3],),
                           method="bilinear")
    got = resize_to(x, hw)
    assert jnp.abs(ref - got).max() < 2e-6

    def loss(fn, x):
        return jnp.sum(jnp.sin(fn(x)))

    g_ref = jax.grad(lambda x: loss(
        lambda v: jax.image.resize(
            v, (shape[0],) + tuple(hw) + (shape[3],), "bilinear"), x))(x)
    g_got = jax.grad(lambda x: loss(lambda v: resize_to(v, hw), x))(x)
    # Relative: an 8x up-resize cotangent sums 64 contributions, so the
    # f32 round-off scales with |g|.
    assert jnp.allclose(g_ref, g_got, rtol=1e-5, atol=1e-5)
