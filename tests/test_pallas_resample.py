"""Fused resample-merge kernel (pallas/fused_resample.py) + the
model.resample_impl execution-strategy knob.

Coverage contract (ISSUE 3 acceptance):

- interpret-mode forward exactness vs the XLA path at even AND odd
  spatial sizes, for every decoder-user idiom — MINet SIM/AIM (add +
  lateral-first concat), HDFNet (add), U²-Net (up-first concat),
  GateNet (bare upsample);
- custom-VJP gradients checked against the XLA path's autodiff;
- execution-strategy invariance of train METRICS across
  resample_impl={fast,xla,fused} (mirrors the backend-invariance
  posture of tests/test_data_plane.py: the strategy knob must never
  change the training stream);
- out-of-envelope shapes fall back to the plain path bit-compatibly;
- the knob is loud on non-decoder models, an unknown arm raises, and
  with no arm named the route follows from the shape alone;
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
    x = _rand(8, 4, 4, 8, seed=4)
    lat = _rand(8, 8, 8, 8, seed=5)
    ref = resample_merge(x, lat, mode="add", impl="fast")
    assert fr.fused_resample_available(x.shape, (8, 8), "add", 8)
    # Budget of zero elements: nothing fits, everything falls back.
    monkeypatch.setattr(fr, "_MAX_TILE_ELEMS", 0)
    got = resample_merge(x, lat, mode="add", impl="fused")
    assert float(jnp.abs(got - ref).max()) == 0.0
    # Non-2x target (4x upsample): available() is False regardless.
    assert not fr.fused_resample_available((8, 4, 4, 8), (16, 16),
                                           "add", 8)
    big = resize_to(x, (16, 16), impl="fused")
    assert float(jnp.abs(big - resize_to(x, (16, 16), impl="fast")
                         ).max()) == 0.0


def test_vmem_budget_covers_flagship_fine_sites():
    """The rule must admit EVERY flagship fine-decoder site — the
    roofline lever-#1 targets — and, now that VMEM need follows the
    row band of one batch block and not the map or the batch, BASNet's
    160->320 sites (a coarse row or two a step).  What stays out, by
    design: the 1-channel 160->320 saliency head, whose lane padding is
    128x the data (``layers.resize_to`` gives it the lane-dense form
    instead), and batches under 8, which would pad the sublanes the
    same way."""
    assert fr.fused_resample_available((64, 80, 80, 32), (160, 160),
                                       "concat", 64)
    assert fr.fused_resample_available((64, 80, 80, 64), (160, 160),
                                       "add", 64)
    assert not fr.fused_resample_available((64, 160, 160, 1), (320, 320))
    assert not fr.fused_resample_available((16, 160, 160, 4), (320, 320))
    assert not fr.fused_resample_available((4, 80, 80, 64), (160, 160))
    assert fr.fused_resample_available((16, 160, 160, 64),
                                       (320, 320), "concat", 64)
    assert fr._band_rows((16, 160, 160, 128)) == 2
    assert fr._band_rows((16, 160, 160, 128), "concat", 64) == 1
    assert fr._band_rows((64, 160, 160, 128)) == 2  # 16 images a step
    assert fr._band_rows((16, 10, 10, 512)) == 10


def test_fused_merge_validates_shapes():
    x = _rand(1, 4, 4, 8, seed=6)
    with pytest.raises(ValueError, match="not the 2x target"):
        fr.fused_upsample2_merge(x, _rand(1, 12, 12, 8, seed=7))
    with pytest.raises(ValueError, match="matching channels"):
        fr.fused_upsample2_merge(x, _rand(1, 8, 8, 4, seed=8), "add")
    with pytest.raises(ValueError, match="mode must be"):
        fr.fused_upsample2_merge(x, _rand(1, 8, 8, 8, seed=9), "mul")


def test_unknown_arm_raises_and_a_named_arm_is_kept():
    """``impl`` is None (the route follows from the shape) or one of
    RESAMPLE_IMPLS, which then keeps its path whatever the shape would
    have chosen; anything else raises at both entry points."""
    from distributed_sod_project_tpu.models import layers

    assert layers.RESAMPLE_IMPLS == ("fast", "xla", "fused")
    x, lat = _rand(8, 4, 4, 16, seed=10), _rand(8, 8, 8, 16, seed=11)
    for bad in ("banana", "auto", ""):
        with pytest.raises(ValueError, match="resample impl"):
            resize_to(x, (8, 8), impl=bad)
        with pytest.raises(ValueError, match="resample impl"):
            resample_merge(x, lat, mode="add", impl=bad)
    ref = jax.image.resize(x, (8, 8, 8, 16), "bilinear")
    for impl, route in (("fast", "fallback"), ("xla", "fallback"),
                        ("fused", "kernel")):
        with layers.resample_routes() as routes:
            up = resize_to(x, (8, 8), impl=impl)
            merged = resample_merge(x, lat, mode="add", impl=impl)
        assert routes[route] == 2 and sum(routes.values()) == 2, (impl, routes)
        assert float(jnp.abs(up - ref).max()) <= 1e-5
        assert float(jnp.abs(merged - (ref + lat)).max()) <= 1e-5


# One site per route: what the shape shows decides, nothing else.
_ROUTE_SITES = {
    "kernel": ((8, 4, 4, 16), (8, 8)),       # exact 2x, 8+ channels
    "lane_dense": ((8, 4, 4, 1), (16, 8)),   # 1 channel, whole factors
    "fallback": ((8, 4, 4, 4), (12, 8)),     # 3x by 2x, 4 channels
}


@pytest.mark.parametrize("route", sorted(_ROUTE_SITES))
def test_route_is_a_function_of_the_shape_alone(route, monkeypatch):
    """The variables that used to pick a resample arm from the shell
    are dead: set to the values that once changed the program, they
    leave a site's route, its traced program and its values as they
    are.  (The one place that still names them.)"""
    from distributed_sod_project_tpu.models import layers

    shape, hw = _ROUTE_SITES[route]
    x = _rand(*shape, seed=12)

    def site():
        with layers.resample_routes() as routes:
            jaxpr = str(jax.make_jaxpr(lambda v: resize_to(v, hw))(x))
        return routes, jaxpr, resize_to(x, hw)

    for name in ("DSOD_RESIZE_IMPL", "DSOD_RESIZE_INTERLEAVE"):
        monkeypatch.delenv(name, raising=False)
    routes, jaxpr, out = site()
    assert routes == {r: int(r == route) for r in layers.RESAMPLE_ROUTES}
    monkeypatch.setenv("DSOD_RESIZE_IMPL", "xla")
    monkeypatch.setenv("DSOD_RESIZE_INTERLEAVE", "stack")
    routes_set, jaxpr_set, out_set = site()
    assert routes_set == routes
    assert jaxpr_set == jaxpr
    assert jnp.array_equal(out_set, out)


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
    # 8 images a device: the kernel keeps the batch on the sublanes and
    # gives way below 8.
    batch = {"image": rng.randn(16, 16, 16, 3).astype(np.float32),
             "mask": (rng.rand(16, 16, 16, 1) > 0.5).astype(np.float32)}
    mesh = make_mesh(MeshConfig(data=-1), jax.devices()[:2])
    metrics = {}
    for impl in ("fast", "xla", "fused"):
        model = _MiniDecoder(impl=impl)
        tx, sched = build_optimizer(OptimConfig(lr=0.1, warmup_steps=0), 10)
        state = create_train_state(jax.random.key(0), model, tx, batch)
        step = make_unified_train_step(
            model, LossConfig(ssim_window=5), tx, mesh, preset="dp",
            schedule=sched, donate=False)
        _, m = step(state, batch)
        metrics[impl] = {k: float(v) for k, v in m.items()}
    for impl in ("xla", "fused"):
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
    for impl in ("fast", "xla", "fused"):
        mc = dataclasses.replace(
            cfg.model, resample_impl=impl, sync_bn=False,
            compute_dtype="float32",
            backbone="small" if model_name == "u2net" else cfg.model.backbone)
        m = build_model(mc)
        v = m.init(jax.random.key(0), img, dep, train=False)
        outs[impl] = m.apply(v, img, dep, train=False)[0]
    for impl in ("xla", "fused"):
        assert float(jnp.abs(outs[impl] - outs["fast"]).max()) <= 1e-5


def test_fused_resample_lowers_for_real_tpu():
    """interpret=False + export for platform='tpu' runs the Mosaic
    pipeline end-to-end (no chip needed) — all three forward kernels
    (the backward is XLA's: ``fr._upT``)."""
    from jax import export

    x = jnp.zeros((1, 16, 16, 8), jnp.float32)
    lat = jnp.zeros((1, 32, 32, 8), jnp.float32)
    for fn, args in [  # two bands of 8 coarse rows each
        (lambda a: fr._call_up(a, None, "none", True, 8, False), (x,)),
        (lambda a, b: fr._call_up(a, b, "add", True, 8, False), (x, lat)),
        (lambda a, b: fr._call_up(a, b, "concat", False, 8, False),
         (x, lat)),
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

# -- the row-banded grid -------------------------------------------------

def _bf16_close(got, ref32, at_scale=False):
    """``got`` (bf16 or f32) against a float32 reference computed from
    the same (upcast) inputs: float32 round-off for f32, ONE bf16
    rounding of the f32 result for bf16 (half an ulp is 2**-9
    relative; 2**-8 leaves room for a tie that f32 association flips).
    ``at_scale``: a few bf16 roundings at the ARRAY's scale (2**-7 of
    its largest value) — what XLA's own bf16 resize transpose, the
    backward of every arm, is held to: it rounds between its two axes,
    so an output that cancels to near zero keeps its addends' error."""
    got32 = np.asarray(got, np.float32)
    ref32 = np.asarray(ref32, np.float32)
    if got.dtype == jnp.float32:
        tol = 1e-5 + 1e-6 * np.abs(ref32)
    elif at_scale:
        tol = 2.0 ** -7 * np.abs(ref32).max()
    else:
        tol = 2.0 ** -8 * np.abs(ref32) + 1e-6
    return bool(np.all(np.abs(got32 - ref32) <= tol))


# (coarse height, coarse rows per band): one band, two bands, a last
# band shorter than the rest — at every h of ISSUE 26's list.
_BANDS = [(1, 1), (2, 2), (2, 1), (5, 5), (5, 2), (10, 10), (10, 5),
          (10, 4), (10, 3)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mode,x_first", [
    ("concat", True), ("concat", False), ("add", True), ("none", True)],
    ids=["cat_up_first", "cat_lat_first", "add", "bare"])
@pytest.mark.parametrize("h,r", _BANDS, ids=[f"h{h}r{r}" for h, r in _BANDS])
def test_banded_kernel_matches_xla_arm_fwd_and_vjp(monkeypatch, h, r, mode,
                                                   x_first, dtype):
    """The kernel over a grid of (batch block, band of ``r`` coarse rows
    + one halo row each side) against the ``xla`` arm
    (``jax.image.resize`` + the plain merge) in float32 on the same
    inputs, forward and VJP (the op is linear: one random cotangent;
    the backward is XLA's transposed resize in the cotangent's
    dtype)."""
    w, cx = 6, 16
    cl = {"concat": 24, "add": cx, "none": 0}[mode]
    monkeypatch.setattr(fr, "_band_rows", lambda *a, **k: r)
    x = _rand(2, h, w, cx, seed=11).astype(dtype)
    lat = _rand(2, 2 * h, 2 * w, max(cl, 1), seed=12).astype(dtype)

    def xla_arm(a, b):
        up = resize_to(a, (2 * h, 2 * w), impl="xla")
        if mode == "none":
            return up
        if mode == "add":
            return up + b
        return jnp.concatenate([up, b] if x_first else [b, up], axis=-1)

    def kernel(a, b):
        if mode == "none":
            return fr.fused_upsample2(a)
        return fr.fused_upsample2_merge(a, b, mode=mode, x_first=x_first)

    ref, ref_vjp = jax.vjp(xla_arm, x.astype(jnp.float32),
                           lat.astype(jnp.float32))
    got, got_vjp = jax.vjp(kernel, x, lat)
    assert got.shape == ref.shape and got.dtype == dtype
    assert _bf16_close(got, ref)
    g = _rand(*ref.shape, seed=13).astype(dtype)
    for got_d, ref_d in zip(got_vjp(g), ref_vjp(g.astype(jnp.float32))):
        assert got_d.dtype == dtype
        assert _bf16_close(got_d, ref_d, at_scale=True)


def test_band_rule_follows_the_budget(monkeypatch):
    """Band height is the shape's and the budget's, evened out over
    the bands; with no room for one row the site is refused."""
    from distributed_sod_project_tpu.pallas.vmem_budget import rows_per_band

    assert rows_per_band(160, per_row=10, fixed=5, budget=705) == 54
    assert rows_per_band(10, per_row=10, fixed=0, budget=1000) == 10
    assert rows_per_band(10, per_row=10, fixed=5, budget=14) == 0
    shape = (2, 10, 6, 16)
    assert fr._band_rows(shape) == 10
    tile = fr._vmem_elems(2, 16)  # one (images, channels) tile: 8 x 128
    assert tile == 8 * 128
    halo, row = 2 * 6 * tile, 6 * tile + 2 * 12 * 2 * tile  # x; f32 + out
    monkeypatch.setattr(fr, "_MAX_TILE_ELEMS", halo + 4 * row)
    assert fr._band_rows(shape) == 4  # 4 fit -> 3 bands -> 4, 4, 2
    x = _rand(*shape, seed=14)
    assert float(jnp.abs(fr.fused_upsample2(x) - resize_to(
        x, (20, 12), impl="xla")).max()) <= 1e-5
    monkeypatch.setattr(fr, "_MAX_TILE_ELEMS", halo + row - 1)
    assert fr._band_rows(shape) == 0
    assert not fr.fused_resample_available((8,) + shape[1:], (20, 12))
    assert fr._batch_block(64) == 16 and fr._batch_block(24) == 8
    assert fr._batch_block(6) == 6


# -- the lane-dense form of 1-channel maps -------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("factor", [2, 4, 8, 16, 32])
def test_lane_dense_logit_resize_matches_jax_image_resize(factor, dtype):
    """``resize_to`` of a (B, h, w, 1) map with no arm named: two
    constant interpolation matrices, W on the lanes — against
    ``jax.image.resize`` in float32 on the same inputs, forward and
    VJP.  The two axes need not share a factor."""
    from distributed_sod_project_tpu.models import layers

    h, w = 64 // factor, 96 // factor
    x = _rand(2, h, w, 1, seed=factor).astype(dtype)
    with layers.resample_routes() as routes:
        got, got_vjp = jax.vjp(lambda a: resize_to(a, (64, 96)), x)
        mixed = resize_to(x, (h, 96))  # one axis stays
    assert routes == {"kernel": 0, "lane_dense": 2, "fallback": 0}
    ref, ref_vjp = jax.vjp(
        lambda a: jax.image.resize(a, (2, 64, 96, 1), "bilinear"),
        x.astype(jnp.float32))
    assert got.shape == (2, 64, 96, 1) and got.dtype == dtype
    assert _bf16_close(got, ref)
    g = _rand(2, 64, 96, 1, seed=99).astype(dtype)
    (got_d,), (ref_d,) = got_vjp(g), ref_vjp(g.astype(jnp.float32))
    assert got_d.dtype == dtype and _bf16_close(got_d, ref_d)
    assert _bf16_close(mixed, jax.image.resize(
        x.astype(jnp.float32), (2, h, 96, 1), "bilinear"))


def test_route_follows_the_shape_and_named_arms_pin():
    """No arm named: kernel / lane-dense / slice-lerp by what the shape
    shows; a named arm keeps its path, and a site that resizes nothing
    is no site."""
    from distributed_sod_project_tpu.models import layers

    wide, one, few = (_rand(8, 4, 4, c, seed=15) for c in (16, 1, 4))
    with layers.resample_routes() as routes:
        resize_to(wide, (8, 8))            # exact 2x, 16 ch: kernel
        resize_to(wide, (16, 16))          # 4x: slice/lerp
        resize_to(few, (8, 8))             # 4 ch < 8: slice/lerp
        resize_to(wide[:4], (8, 8))        # 4 images < 8: slice/lerp
        resize_to(one, (16, 8))            # 1 ch: lane-dense
        resize_to(one, (2, 2))             # a downsample: slice/lerp
        resize_to(wide, (4, 4))            # nothing to do
        # a concat is XLA's (it reads it into the next conv); the
        # upsample under it still takes the kernel; an add is fused.
        resample_merge(wide, _rand(8, 8, 8, 8), mode="concat")
        resample_merge(wide, _rand(8, 8, 8, 16), mode="add")
        resample_merge(wide, _rand(8, 4, 4, 8), mode="concat")  # no site
    assert routes == {"kernel": 3, "lane_dense": 1, "fallback": 4}
    with layers.resample_routes() as routes:
        resize_to(wide, (8, 8), impl="fast")
        resize_to(one, (8, 8), impl="fast")
        resize_to(wide, (8, 8), impl="xla")
        resize_to(one, (8, 8), impl="xla")
    assert routes == {"kernel": 0, "lane_dense": 0, "fallback": 4}


# -- BASNet through the seam ----------------------------------------------

@pytest.fixture(scope="module")
def basnet64():
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.models.registry import build_model

    mc = dataclasses.replace(get_config("basnet_ds").model, sync_bn=False,
                             compute_dtype="float32")
    model = build_model(mc)
    img = _rand(8, 64, 64, 3, seed=16)  # 8: the kernel's least batch
    init = jax.jit(lambda k, i: model.init(k, i, train=False))
    return model, img, init(jax.random.key(0), img[:1])


def test_basnet_route_counter_reads_9_6_0(basnet64, caplog):
    """BASNet's nine 2x upsample+concat sites (5 decoder, 4 refine) take
    the kernel, six of its seven side logits the lane-dense form (the
    seventh is already full size), nothing falls back — at 64 px as at
    320 — and the tally is one log line."""
    from distributed_sod_project_tpu.models import layers

    model, img, variables = basnet64
    from distributed_sod_project_tpu.utils.logging import get_logger

    get_logger().addHandler(caplog.handler)  # "dsod" does not propagate
    try:
        with layers.resample_routes(log_as="test") as routes:
            jax.eval_shape(lambda v, i: model.apply(v, i, train=False),
                           variables, img)
            assert routes == {}  # filled on exit
    finally:
        get_logger().removeHandler(caplog.handler)
    assert routes == {"kernel": 9, "lane_dense": 6, "fallback": 0}
    lines = [r.getMessage() for r in caplog.records
             if "resample routes" in r.getMessage()]
    assert lines == [
        "resample routes (test): kernel=9 lane_dense=6 fallback=0"]
    big = jax.ShapeDtypeStruct((16, 320, 320, 3), jnp.float32)
    with layers.resample_routes() as routes:
        jax.eval_shape(lambda v, i: model.apply(v, i, train=False),
                       variables, big)
    assert routes == {"kernel": 9, "lane_dense": 6, "fallback": 0}


def test_basnet_default_route_matches_xla_arm(basnet64, monkeypatch):
    """BASNet at 64 px, float32 compute: the 8 outputs and the
    parameter gradient of a scalar readout with the default route
    (kernel + lane-dense) against the ``xla`` arm pinned at every site
    (BASNet threads no ``impl``; the test hands its module the seam's
    two functions with the arm bound).  Both arms
    are the same bilinear resample in float32, so they differ by
    float32 round-off carried through ~60 conv layers: outputs within
    2e-4 of the largest output, every leaf's gradient within 1e-3 of
    its norm.  (Under bf16 compute the arms differ by one bf16
    rounding per resample, ~4e-3 relative: the kernel lerps in f32
    where the slice/lerp path lerps in bf16 — asserted per site in
    ``test_banded_kernel_matches_xla_arm_fwd_and_vjp``.)"""
    import functools

    from distributed_sod_project_tpu.models import basnet

    model, img, variables = basnet64

    def run():
        def readout(params):
            outs = model.apply({**variables, "params": params}, img,
                               train=False)
            return sum(jnp.mean(jnp.tanh(o)) for o in outs), outs

        (_, outs), grads = jax.jit(
            jax.value_and_grad(readout, has_aux=True))(variables["params"])
        return outs, grads

    outs, grads = run()
    for seam in (resize_to, resample_merge):
        monkeypatch.setattr(basnet, seam.__name__,
                            functools.partial(seam, impl="xla"))
    ref_outs, ref_grads = run()
    scale = max(float(jnp.abs(o).max()) for o in ref_outs)
    for o, ref in zip(outs, ref_outs):
        assert float(jnp.abs(o - ref).max()) <= 2e-4 * scale
    gaps = jax.tree_util.tree_map(
        lambda g, ref: float(jnp.linalg.norm(g - ref)
                             / (jnp.linalg.norm(ref) + 1e-30)),
        grads, ref_grads)
    worst = max(jax.tree_util.tree_leaves(gaps))
    assert worst <= 1e-3, worst
