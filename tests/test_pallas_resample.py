"""Fused resample-merge kernel (pallas/fused_resample.py) + the
model.resample_impl execution-strategy knob.

Coverage contract (ISSUE 3 acceptance):

- interpret-mode forward exactness vs the XLA path at even AND odd
  spatial sizes, for every decoder-user idiom — MINet SIM/AIM (add +
  lateral-first concat), HDFNet (add), U²-Net (up-first concat),
  GateNet (bare upsample);
- custom-VJP gradients checked against the XLA path's autodiff;
- execution-strategy invariance of train METRICS across
  resample_impl={xla,convt,fused} (mirrors the backend-invariance
  posture of tests/test_data_plane.py: the strategy knob must never
  change the training stream);
- out-of-envelope shapes fall back to the plain path bit-compatibly;
- the knob is loud on non-decoder models and subsumes
  DSOD_RESIZE_IMPL;
- the Mosaic TPU lowering runs end-to-end via jax.export (no chip).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from distributed_sod_project_tpu.models.layers import (resample_merge,
                                                       resize_to)
from distributed_sod_project_tpu.pallas import fused_resample as fr


def _rand(*shape, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape).astype(np.float32))


# The four decoder users' resample idioms (mode, up-operand channels,
# lateral channels, x_first), exercised at even and odd coarse sizes.
_IDIOMS = [
    ("minet_sim_add", "add", 16, 16, True),       # SIM exchange: up(l)+h
    ("minet_sim_cat", "concat", 8, 16, False),    # SIM merge: [h2, up(l2)]
    ("hdfnet_dec_add", "add", 16, 16, True),      # top-down: up(dec)+skip
    ("u2net_dec_cat", "concat", 16, 24, True),    # RSU skip: [up(d), skip]
]
_SIZES = [(4, 6), (5, 7), (3, 3), (1, 2)]


@pytest.mark.parametrize("h,w", _SIZES)
@pytest.mark.parametrize("label,mode,cx,cl,x_first", _IDIOMS)
def test_fused_merge_matches_xla_fwd_and_grad(label, mode, cx, cl,
                                              x_first, h, w):
    x = _rand(2, h, w, cx, seed=1)
    lat = _rand(2, 2 * h, 2 * w, cl, seed=2)

    def xla_path(a, b):
        up = resize_to(a, (2 * h, 2 * w), impl="fast")
        if mode == "add":
            return up + b
        parts = [up, b] if x_first else [b, up]
        return jnp.concatenate(parts, axis=-1)

    ref = xla_path(x, lat)
    got = fr.fused_upsample2_merge(x, lat, mode=mode, x_first=x_first)
    assert got.shape == ref.shape
    assert float(jnp.abs(got - ref).max()) <= 1e-5

    # VJP: nonlinear readout so every cotangent position is distinct.
    loss_ref = lambda a, b: jnp.sum(jnp.sin(xla_path(a, b)))
    loss_got = lambda a, b: jnp.sum(jnp.sin(
        fr.fused_upsample2_merge(a, b, mode=mode, x_first=x_first)))
    gr = jax.grad(loss_ref, (0, 1))(x, lat)
    gg = jax.grad(loss_got, (0, 1))(x, lat)
    for r, g in zip(gr, gg):
        assert float(jnp.abs(r - g).max()) <= 1e-5


@pytest.mark.parametrize("h,w", _SIZES)
def test_fused_bare_upsample_matches_gatenet_path(h, w):
    """GateNet reuses the upsampled state (gate input AND concat), so
    its fused arm is the bare single-pass kernel."""
    x = _rand(2, h, w, 16, seed=3)
    ref = resize_to(x, (2 * h, 2 * w), impl="fast")
    ref2 = jax.image.resize(x, (2, 2 * h, 2 * w, 16), "bilinear")
    got = fr.fused_upsample2(x)
    assert float(jnp.abs(got - ref).max()) <= 1e-5
    assert float(jnp.abs(got - ref2).max()) <= 1e-5
    g_ref = jax.grad(lambda v: jnp.sum(
        jnp.sin(resize_to(v, (2 * h, 2 * w), impl="fast"))))(x)
    g_got = jax.grad(lambda v: jnp.sum(jnp.sin(fr.fused_upsample2(v))))(x)
    assert float(jnp.abs(g_ref - g_got).max()) <= 1e-5


def test_resample_merge_falls_back_out_of_envelope(monkeypatch):
    """Oversize tiles and non-2x targets must take the plain path —
    same numerics, no kernel."""
    x = _rand(1, 4, 4, 8, seed=4)
    lat = _rand(1, 8, 8, 8, seed=5)
    ref = resample_merge(x, lat, mode="add", impl="fast")
    # Budget of zero elements: nothing fits, everything falls back.
    monkeypatch.setattr(fr, "_MAX_TILE_ELEMS", 0)
    got = resample_merge(x, lat, mode="add", impl="fused")
    assert float(jnp.abs(got - ref).max()) == 0.0
    # Non-2x target (4x upsample): available() is False regardless.
    assert not fr.fused_resample_available((1, 4, 4, 8), (16, 16),
                                           "add", 8)
    big = resize_to(x, (16, 16), impl="fused")
    assert float(jnp.abs(big - resize_to(x, (16, 16), impl="fast")
                         ).max()) == 0.0


def test_vmem_budget_covers_flagship_fine_sites():
    """The budget must admit EVERY flagship fine-decoder site — the
    roofline lever-#1 targets — including the largest one, SIM-0's
    concat merge (80x80x32 -> into 160x160x64, 96ch out: 10.8M
    elements as VMEM holds them, lanes padded to 128; the v5e compiler
    accepts it — tests/test_chip_compile.py).  What stays out, by
    design: U²-Net's full-width 160->320 concat, and the 1-channel
    160->320 saliency head, whose lane padding (128x) asks 182 MB of a
    128 MB core."""
    assert fr.fused_resample_available((64, 80, 80, 32), (160, 160),
                                       "concat", 64)
    assert fr.fused_resample_available((64, 80, 80, 64), (160, 160),
                                       "add", 64)
    assert not fr.fused_resample_available((64, 160, 160, 1), (320, 320))
    assert not fr.fused_resample_available((16, 160, 160, 64),
                                           (320, 320), "concat", 64)


def test_fused_merge_validates_shapes():
    x = _rand(1, 4, 4, 8, seed=6)
    with pytest.raises(ValueError, match="not the 2x target"):
        fr.fused_upsample2_merge(x, _rand(1, 12, 12, 8, seed=7))
    with pytest.raises(ValueError, match="matching channels"):
        fr.fused_upsample2_merge(x, _rand(1, 8, 8, 4, seed=8), "add")
    with pytest.raises(ValueError, match="mode must be"):
        fr.fused_upsample2_merge(x, _rand(1, 8, 8, 8, seed=9), "mul")


def test_interleave_stack_arm_bit_identical(monkeypatch):
    """The layout-stable concat interleave and the historical
    stack+reshape arm (DSOD_RESIZE_INTERLEAVE=stack) are the same
    permutation of the same lerp values — bit-identical, which is why
    flipping the default needed no numerics A/B (tools/hlo_guard.py
    diffs their op counts instead)."""
    x = _rand(2, 5, 6, 8, seed=10)
    monkeypatch.delenv("DSOD_RESIZE_INTERLEAVE", raising=False)
    concat_arm = resize_to(x, (15, 18))  # non-2x: generic interleave
    up2 = resize_to(x, (10, 12))
    monkeypatch.setenv("DSOD_RESIZE_INTERLEAVE", "stack")
    stack_arm = resize_to(x, (15, 18))
    up2_stack = resize_to(x, (10, 12))
    assert jnp.array_equal(concat_arm, stack_arm)
    assert jnp.array_equal(up2, up2_stack)


def test_resample_impl_subsumes_env(monkeypatch):
    """model.resample_impl subsumes DSOD_RESIZE_IMPL: env selects the
    arm at the default, an explicit non-default impl wins over env."""
    from distributed_sod_project_tpu.models.layers import \
        _resolve_resample_impl

    monkeypatch.delenv("DSOD_RESIZE_IMPL", raising=False)
    assert _resolve_resample_impl(None) == "fast"
    assert _resolve_resample_impl("fast") == "fast"
    assert _resolve_resample_impl("convt") == "convt"
    monkeypatch.setenv("DSOD_RESIZE_IMPL", "xla")
    assert _resolve_resample_impl(None) == "xla"    # env wins at default
    assert _resolve_resample_impl("fast") == "xla"
    assert _resolve_resample_impl("fused") == "fused"  # explicit wins
    with pytest.raises(ValueError, match="resample impl"):
        _resolve_resample_impl("banana")


def test_registry_resample_impl_is_loud_on_non_decoder_models():
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.models.registry import build_model

    cfg = get_config("basnet_ds")
    bad = dataclasses.replace(cfg.model, resample_impl="fused")
    with pytest.raises(ValueError, match="only applies to"):
        build_model(bad)
    # The four decoder users accept it.
    for name in ("minet_r50_dp", "hdfnet_rgbd", "gatenet_vgg16",
                 "u2net_ds"):
        mc = dataclasses.replace(get_config(name).model,
                                 resample_impl="fused")
        build_model(mc)  # constructs without raising


class _MiniDecoder(nn.Module):
    """Smallest net exercising every resample_merge idiom the four
    decoder users route (add, both concat orders, bare upsample) under
    the real train step — the cheap carrier for the train-metrics
    invariance check (full zoo members run in the slow suite)."""

    impl: str = "fast"
    axis_name: str = "data"

    @nn.compact
    def __call__(self, image, depth=None, *, train: bool = False):
        from distributed_sod_project_tpu.models.layers import (ConvBNAct,
                                                               max_pool)

        del depth
        kw = dict(axis_name=self.axis_name)
        f1 = ConvBNAct(8, **kw)(image, train)            # full res
        f2 = ConvBNAct(8, **kw)(max_pool(f1), train)     # /2
        f3 = ConvBNAct(8, **kw)(max_pool(f2), train)     # /4
        d = resample_merge(f3, f2, mode="add", impl=self.impl)
        d = resample_merge(d, f1, mode="concat", x_first=True,
                           impl=self.impl)
        d = ConvBNAct(8, **kw)(d, train)
        d = resample_merge(max_pool(d), d, mode="concat", x_first=False,
                           impl=self.impl)
        up = resize_to(d, image.shape[1:3], impl=self.impl)  # bare
        logit = nn.Conv(1, (3, 3), padding="SAME")(up)
        return [logit.astype(jnp.float32)]


def test_train_metrics_invariant_across_resample_impls():
    """Execution-strategy invariance (the tests/test_data_plane.py
    posture, device-side edition): one real shard_map train step on
    each resample_impl arm must produce the same metrics to f32
    round-off — the knob changes the schedule, never the model."""
    from distributed_sod_project_tpu.configs.base import (LossConfig,
                                                          MeshConfig,
                                                          OptimConfig)
    from distributed_sod_project_tpu.parallel import (
        make_mesh, make_unified_train_step)
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    rng = np.random.RandomState(0)
    batch = {"image": rng.randn(8, 16, 16, 3).astype(np.float32),
             "mask": (rng.rand(8, 16, 16, 1) > 0.5).astype(np.float32)}
    mesh = make_mesh(MeshConfig(data=-1), jax.devices()[:2])
    metrics = {}
    for impl in ("fast", "xla", "convt", "fused"):
        model = _MiniDecoder(impl=impl)
        tx, sched = build_optimizer(OptimConfig(lr=0.1, warmup_steps=0), 10)
        state = create_train_state(jax.random.key(0), model, tx, batch)
        step = make_unified_train_step(
            model, LossConfig(ssim_window=5), tx, mesh, preset="dp",
            schedule=sched, donate=False)
        _, m = step(state, batch)
        metrics[impl] = {k: float(v) for k, v in m.items()}
    for impl in ("xla", "convt", "fused"):
        for k, ref in metrics["fast"].items():
            got = metrics[impl][k]
            assert got == pytest.approx(ref, rel=2e-4, abs=2e-5), (
                impl, k, got, ref)


@pytest.mark.slow
@pytest.mark.parametrize("cfg_name,model_name", [
    ("minet_vgg16_ref", "minet"), ("u2net_ds", "u2net"),
    ("gatenet_vgg16", "gatenet"), ("hdfnet_rgbd", "hdfnet")])
def test_zoo_forward_invariant_across_resample_impls(cfg_name, model_name):
    """Full-model forward invariance for every decoder user × every
    impl arm (the 32px smoke the tier-1 MiniDecoder test compresses)."""
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.models.registry import build_model

    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.randn(1, 32, 32, 3).astype(np.float32))
    dep = (jnp.asarray(rng.randn(1, 32, 32, 1).astype(np.float32))
           if model_name == "hdfnet" else None)
    cfg = get_config(cfg_name)
    outs = {}
    for impl in ("fast", "xla", "convt", "fused"):
        mc = dataclasses.replace(
            cfg.model, resample_impl=impl, sync_bn=False,
            compute_dtype="float32",
            backbone="small" if model_name == "u2net" else cfg.model.backbone)
        m = build_model(mc)
        v = m.init(jax.random.key(0), img, dep, train=False)
        outs[impl] = m.apply(v, img, dep, train=False)[0]
    for impl in ("xla", "convt", "fused"):
        assert float(jnp.abs(outs[impl] - outs["fast"]).max()) <= 1e-5


def test_fused_resample_lowers_for_real_tpu():
    """interpret=False + export for platform='tpu' runs the Mosaic
    pipeline end-to-end (no chip needed) — all three forward kernels
    and the transposed-resample backward."""
    from jax import export

    x = jnp.zeros((1, 16, 16, 8), jnp.float32)
    lat = jnp.zeros((1, 32, 32, 8), jnp.float32)
    g = jnp.zeros((1, 32, 32, 8), jnp.float32)
    for fn, args in [
        (lambda a: fr._call_up(a, False), (x,)),
        (lambda a, b: fr._call_merge(a, b, "add", True, False), (x, lat)),
        (lambda a, b: fr._call_merge(a, b, "concat", False, False),
         (x, lat)),
        (lambda c: fr._call_upT(c, False), (g,)),
    ]:
        exp = export.export(jax.jit(fn), platforms=["tpu"])(*args)
        assert "tpu_custom_call" in exp.mlir_module()


def test_resample_compiler_params_follow_the_shared_vmem_rule(monkeypatch):
    """pallas/vmem_budget.py: the raised 100 MB scoped-VMEM ceiling on a
    chip the table knows to have the VMEM for it (v5e), the compiler
    default off-TPU (interpret mode never reads it), an ERROR for a TPU
    kind utils/chips.py has no row for, and DSOD_RESAMPLE_VMEM_MB as the
    escape hatch."""
    from distributed_sod_project_tpu.pallas import vmem_budget as vb
    from distributed_sod_project_tpu.utils.chips import UnknownChipError

    monkeypatch.delenv("DSOD_RESAMPLE_VMEM_MB", raising=False)
    for kind, want in {"TPU v5 lite": 100 << 20, None: None}.items():
        monkeypatch.setattr(vb, "_device_kind", lambda kind=kind: kind)
        got = getattr(fr._compiler_params(), "vmem_limit_bytes", None)
        assert got == want, (kind, got, want)
    monkeypatch.setattr(vb, "_device_kind", lambda: "TPU v9 ultra")
    with pytest.raises(UnknownChipError):
        fr._compiler_params()
    monkeypatch.setenv("DSOD_RESAMPLE_VMEM_MB", "8")
    assert fr._compiler_params().vmem_limit_bytes == 8 << 20
    monkeypatch.setenv("DSOD_RESAMPLE_VMEM_MB", "0")
    assert getattr(fr._compiler_params(), "vmem_limit_bytes", None) is None