"""Real-compiler guard: every Pallas kernel entry point, compiled for a
DESCRIBED (not attached) TPU v5e at the shapes its configs give at
320x320 (flash: vit_sod_hires' 1024 px / N=4096).

Interpret mode and ``jax.export`` lowering (the per-kernel
``*_lowers_for_real_tpu`` tests) stop before Mosaic's layout inference
and the VMEM allocator; this file goes through them, which is where
the chip's compiler refused the pre-PR-23 resample kernel ("unsupported
shape cast"), bf16 odd-width conv tiles and over-wide dynamic-filter
maps.  Nothing runs — a pass here is a compile, not a chip run.

All of these tests live in ONE file on purpose: only one process may
hold the TPU library, pytest-xdist (``--dist loadfile``) gives a file
to one worker, and the topology is described inside a fixture — never
at import — so every worker collects the same tests.  The no-chip CLI
tests at the bottom are here for the same reason: ``--device tpu``
makes each child try the TPU library, and in another file (another
worker) such a child could hold the library's lock at the moment this
file's fixture asks for it, turning every compile test into a skip.
"""

import base64
import collections
import glob
import importlib
import importlib.util
import json
import os
import re
import subprocess
import sys
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))
import step_cache_key  # noqa: E402 — imports no JAX until it is called

_P = "distributed_sod_project_tpu.pallas."
fc, fr, dfm, fl, fs, fa, vb, gm, mu, ssd, cc, rot, sel = (
    importlib.import_module(_P + m)
    for m in ("fused_conv", "fused_resample", "dynamic_filter",
              "fused_loss", "fused_ssim", "flash_attention",
              "vmem_budget", "grouped_matmul", "moe_unpermute", "ssd_scan",
              "causal_conv", "rotary", "selective_scan"))

_S = collections.namedtuple("_S", "shape dtype")  # an argument's spec
B = 2  # the kernels grid over images; the tile is what the compiler prices
RB = 16  # ... but the resample kernel keeps the batch on the sublanes
BF, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """Compile-for-v5e context: a one-chip sharding for the argument
    shapes, the scoped-VMEM rule steered to the described chip's kind
    (``jax.devices()`` still says cpu here), conftest's float32
    matmul-precision default lifted (the chip runs the kernels' bf16
    dots at the default precision; forced fp32 on bf16 operands is a
    "Bad lhs type" the program never asks for), and the persistent
    compile cache off — an entry compiled for a described chip cannot
    be read back without one and every later compile would warn."""
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setattr(vb, "_device_kind", lambda: topo.devices[0].device_kind)
    was = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_default_matmul_precision")}
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    mp.undo()


def _conv(parts, w, mode="none", relu=False, grad=False):
    cout = w.shape[-1]
    vecs = ({k: _S((cout,), F32) for k in ("mean", "mul", "bias")}
            if mode == "bn" else {})

    def f(parts, w, vecs):
        y = fc.fused_conv(parts, w, vecs, kernel=w.shape[:2], mode=mode,
                          relu=relu, interpret=False)
        return y.astype(F32).sum()

    return (jax.grad(f, argnums=(0, 1)) if grad else f), (parts, w, vecs)


def _resample_vjp(f, out, *args):
    # The op is linear: its grad never reads the primals, so a
    # ``jax.grad`` of it prunes every argument and the lowering loses
    # the described device.  Feed the cotangent as an input instead.
    return (lambda g, *a: jax.vjp(f, *a)[1](g)), (out,) + args


def _basnet_site(h, cx, cl):
    """One of BASNet's 2x upsample + skip concat sites, bf16: the
    row-banded forward kernel, and its VJP (XLA's transposed resize of
    the upsampled operand's slab of the cotangent: no custom call)."""
    x, lat = _S((RB, h, h, cx), BF), _S((RB, 2 * h, 2 * h, cl), BF)
    name = f"fused_resample.concat@{h}x{cx}+{cl}"
    return {name: (_cat_up, (x, lat), 1),
            name + ",transposed": _resample_vjp(
                _cat_up, _S((RB, 2 * h, 2 * h, cx + cl), BF), x, lat) + (0,)}


def _dlf(h, dilation, grad):
    def f(x, k):
        return dfm.fused_dynamic_filter(x, k, 3, dilation,
                                        interpret=False)

    args = (_S((B, h, h, 64), BF), _S((B, h, h, 9), BF))
    if grad:
        return jax.grad(lambda x, k: f(x, k).astype(F32).sum(),
                        argnums=(0, 1)), args
    return f, args


_up = partial(fr.fused_upsample2, interpret=False)
_add = partial(fr.fused_upsample2_merge, mode="add", interpret=False)
_cat = partial(fr.fused_upsample2_merge, mode="concat", x_first=False,
               interpret=False)
_cat_up = partial(fr.fused_upsample2_merge, mode="concat", x_first=True,
                  interpret=False)
_IMG = _S((B, 320, 320, 1), F32)
_QKV = _S((1, 6, 4096, 64), BF)
_flash = partial(fa.flash_attention, interpret=False)
_causal = partial(fa.flash_attention_causal, interpret=False)


def _causal_qkv(n):
    return (_S((1, 32, n, 64), BF),) + (_S((1, 8, n, 64), BF),) * 2


_causal_grad = jax.grad(lambda q, k, v: _causal(q, k, v).astype(F32).sum(),
                        argnums=(0, 1, 2))
_gmm = partial(gm.grouped_matmul, interpret=False)
_mla = partial(fa.flash_attention_mla, interpret=False)
# kimi_vl_a3b_ep8: one 16,384-token sequence of 16 heads (the cell runs
# two): q_nope, q_rope, k_nope, the ONE shared rotary key, v.
def _mla_args(n):
    return (_S((1, 16, n, 128), BF), _S((1, 16, n, 64), BF),
            _S((1, 16, n, 128), BF), _S((1, n, 64), BF),
            _S((1, 16, n, 128), BF))


_mla_grad = jax.grad(lambda *a: _mla(*a).astype(F32).sum(),
                     argnums=(0, 1, 2, 3, 4))


# granite_4_0_h_micro_pp4: one 16,384-token sequence of 64 heads of 64
# with a state of 128: x, delta, A, B, C; 64 chunks of 256.
_ssd = partial(ssd.ssd_scan, chunk=256, interpret=False)
_SSD_ARGS = (_S((1, 16384, 64, 64), BF), _S((1, 16384, 64), F32),
             _S((64,), F32), _S((1, 16384, 128), BF),
             _S((1, 16384, 128), BF))


# phi4_mini_flash_pp5: one 16,384-token sequence of 5,120 Mamba-1
# channels of 16 states (x, delta, A, B, C, D), and one differential
# layer's two softmax maps in one call: 40 query heads of 64 over 20 key
# heads of 64 and values of 128.
_sel = partial(sel.selective_scan, interpret=False)
_SEL_ARGS = (_S((1, 16384, 5120), BF), _S((1, 16384, 5120), F32),
             _S((5120, 16), F32), _S((1, 16384, 16), BF),
             _S((1, 16384, 16), BF), _S((5120,), F32))
_DIFF_QKV = (_S((1, 40, 16384, 64), BF), _S((1, 20, 16384, 64), BF),
             _S((1, 20, 16384, 128), BF))
_window = partial(_causal, window=512)


# ... and its mixer's conv + bias + SiLU over x | B | C: 4,352 columns of
# one 16,384-token sequence, 4 float32 taps and a bias.
_cconv = partial(cc.causal_conv_silu, interpret=False)
_CCONV_ARGS = (_S((1, 16384, 4352), BF), _S((4, 4352), F32),
               _S((4352,), F32))


# ouro_2_6b_pp6's rotation of q and k in ONE call: 16 heads of 128 over
# one 8,192-token sequence, head-major.
_rotary = lambda q, k: rot.rotate_half(  # noqa: E731
    (q, k), 1e6, interpret=False)
_ROTARY_ARGS = (_S((16, 8192, 128), BF),) * 2


def _gmm_args(a, b, tiles=24, experts=8, tile_m=512):
    return (_S((tiles * tile_m, a), BF), _S((experts, a, b), F32),
            _S((tiles,), jnp.int32), _S((1,), jnp.int32))


def _unpermute(rows, tokens=32768, top_k=4, d=2048):
    """The expert layer's way back to token order at the cell's shapes:
    the step list from ``pair_of_row`` and the kernel over it."""
    def f(y, w, row_of_pair, pair_of_row):
        steps = mu.unpermute_steps(pair_of_row, top_k, tokens, 512, 8)
        return mu.moe_unpermute(y, w, row_of_pair, steps, tile_m=512,
                                interpret=False)

    return f, (_S((rows, d), BF), _S((tokens, top_k), F32),
               _S((tokens, top_k), jnp.int32), _S((rows,), jnp.int32)), 1


# name -> (fn, pytree of argument specs, custom calls expected)
CASES = {
    # u2net_ds / basnet_ds / gatenet_vgg16: loss.fused_kernel=True
    "fused_loss.sums@320": (
        partial(fl.pixel_region_sums, interpret=False), (_IMG, _IMG), 1),
    "fused_ssim.fwd@320": (
        lambda a, b: fs._run(fs._fwd_kernel, a, b, [(1, 128)],
                             fs._taps(11, 1.5), interpret=False),
        (_IMG, _IMG), 1),
    "fused_ssim.bwd@320": (
        lambda a, b: fs._run(fs._bwd_kernel, a, b, [(320, 320)] * 2,
                             fs._taps(11, 1.5), interpret=False),
        (_IMG, _IMG), 1),
    # hdfnet_rgbd, model.dlf_impl=pallas: 80/40/20 px maps x 64ch,
    # dilations 1/2/4.
    "dynamic_filter.fwd@80d1": _dlf(80, 1, False) + (1,),
    "dynamic_filter.dx+dk@80d4": _dlf(80, 4, True) + (2,),
    # minet_r50_dp, model.resample_impl=fused: AIM/SIM merges.
    "fused_resample.up@80x64": (_up, (_S((RB, 80, 80, 64), BF),), 1),
    "fused_resample.add@80x64": (
        _add, (_S((RB, 80, 80, 64), BF), _S((RB, 160, 160, 64), BF)), 1),
    "fused_resample.concat@80x32+64": (
        _cat, (_S((RB, 80, 80, 32), BF), _S((RB, 160, 160, 64), BF)), 1),
    "fused_resample.concat@80x32+64,f32": (
        _cat, (_S((RB, 80, 80, 32), F32), _S((RB, 160, 160, 64), F32)), 1),
    "fused_resample.transposed@160x64": _resample_vjp(
        _up, _S((RB, 160, 160, 64), BF), _S((RB, 80, 80, 64), BF)) + (0,),
    "fused_resample.transposed@10x64": _resample_vjp(
        _up, _S((RB, 10, 10, 64), BF), _S((RB, 5, 5, 64), BF)) + (0,),
    # basnet_ds, no arm named: the 320 px decoder stage (bands of 32
    # output rows), the refine module's last level, a wide coarse
    # stage, the coarsest (one band, width no multiple of 8 sublanes).
    **_basnet_site(160, 128, 64), **_basnet_site(160, 64, 64),
    **_basnet_site(80, 256, 128), **_basnet_site(10, 512, 512),
    # minet_r50_dp, model.conv_impl=fused: decoder ConvBNAct sites.
    "fused_conv.fwd_bn_relu@80x64": _conv(
        (_S((B, 80, 80, 64), BF),), _S((3, 3, 64, 64), BF), "bn", True)
    + (1,),
    "fused_conv.dx+dw@80x64": _conv(
        (_S((B, 80, 80, 64), BF),), _S((3, 3, 64, 64), BF), grad=True)
    + (2,),
    "fused_conv.dx+dw@80x(64,64,64)": _conv(
        (_S((B, 80, 80, 64), BF),) * 3, _S((3, 3, 192, 64), BF), grad=True)
    + (2,),
    "fused_conv.dx+dw@40x512,1x1": _conv(
        (_S((B, 40, 40, 512), BF),), _S((1, 1, 512, 128), BF), grad=True)
    + (2,),
    "fused_conv.dx+dw@160x128": _conv(  # the budget's upper edge
        (_S((B, 160, 160, 128), BF),), _S((3, 3, 128, 128), BF), grad=True)
    + (2,),
    # vit_sod_hires, model.attn_impl=flash: 1024 px -> N=4096.
    "flash_attention.fwd@4096": (_flash, (_QKV,) * 3, 1),
    "flash_attention.bwd@4096": (
        jax.grad(lambda q, k, v: _flash(q, k, v).astype(F32).sum(),
                 argnums=(0, 1, 2)), (_QKV,) * 3, 3),
    # lfm2_8b_a1b_ep4: one 8,192-token sequence of 32 query / 8 KV
    # heads of 64 (the cell runs four), causal, forward and backward.
    # The backward is ONE kernel whose float32 dq accumulators hold the
    # whole sequence of a kv head's 4 query heads in VMEM, lane-padded:
    # 16 MiB of the 31 MiB scoped limit the shapes derive here, 32 of 47
    # at granite_4_0_h_micro_pp4's one 16,384-token sequence.
    "flash_attention_causal.fwd@8192": (_causal, _causal_qkv(8192), 1),
    "flash_attention_causal.bwd@8192": (_causal_grad, _causal_qkv(8192), 2),
    "flash_attention_causal.bwd@16384": (_causal_grad, _causal_qkv(16384),
                                         2),
    # ouro_2_6b_pp6: 16 / 16 heads of 128 (no grouping, full lanes): a
    # 4 MiB dq accumulator a head at the cell's 8,192 rows.
    "flash_attention_causal.bwd@8192x16x128": (
        _causal_grad, (_S((1, 16, 8192, 128), BF),) * 3, 2),
    # kimi_vl_a3b_ep8: keys of 128 + 64 columns against values of 128.
    "flash_attention_mla.fwd@16384": (_mla, _mla_args(16384), 1),
    # The backward is ONE kernel whose float32 dq accumulators hold the
    # head's whole sequence in VMEM: 16 MiB of the 32.5 MiB scoped limit
    # the shapes derive at the cell's 16,384 rows, 64 of 80.5 at 65,536
    # (interpret mode cannot show a VMEM overflow; the compiler does).
    "flash_attention_mla.bwd@16384": (_mla_grad, _mla_args(16384), 2),
    "flash_attention_mla.bwd@65536": (_mla_grad, _mla_args(65536), 2),
    # ... and its grouped expert products at 2048 -> 1408 -> 2048 over
    # the usual buffer's 80 row tiles: 2048 -> 1408 is eleven column
    # blocks of 128 and keeps the column blocks inner (and so does the
    # other entry's dx), the rest take the row tiles inner.
    "grouped_matmul.dx+dw@2048x1408": (
        jax.grad(lambda x, w, te, nu: _gmm(x, w, te, nu).astype(F32).sum(),
                 argnums=(0, 1)), _gmm_args(2048, 1408, tiles=80), 2),
    "grouped_matmul.dx+dw@1408x2048": (
        jax.grad(lambda x, w, te, nu: _gmm(x, w, te, nu).astype(F32).sum(),
                 argnums=(0, 1)), _gmm_args(1408, 2048, tiles=80), 2),
    "moe_unpermute@40960x6": _unpermute(40960, top_k=6),
    # lfm2_8b_a1b_ep4's grouped expert products at the published widths:
    # 8 experts of 2048 -> 1792 -> 2048 over 16 + 8 row tiles of 512.
    "grouped_matmul.fwd@2048x1792": (_gmm, _gmm_args(2048, 1792), 1),
    "grouped_matmul.dx+dw@2048x1792": (
        jax.grad(lambda x, w, te, nu: _gmm(x, w, te, nu).astype(F32).sum(),
                 argnums=(0, 1)), _gmm_args(2048, 1792), 2),
    "grouped_matmul.dx+dw@1792x2048": (
        jax.grad(lambda x, w, te, nu: _gmm(x, w, te, nu).astype(F32).sum(),
                 argnums=(0, 1)), _gmm_args(1792, 2048), 2),
    # nemotron_3_super_tp8_ep64's up-projection, 8 experts of 1024 ->
    # 2688 over the usual buffer's 140 row tiles of 256: three column
    # blocks of 896 with the ROW TILES INNER (forward and dx; dw in 384s).
    "grouped_matmul.fwd@1024x2688": (
        partial(_gmm, tile_m=256),
        _gmm_args(1024, 2688, tiles=140, tile_m=256), 1),
    "grouped_matmul.dx+dw@1024x2688": (
        jax.grad(lambda x, w, te, nu: _gmm(x, w, te, nu, tile_m=256).astype(
            F32).sum(), argnums=(0, 1)),
        _gmm_args(1024, 2688, tiles=140, tile_m=256), 2),
    # ... and the un-permute-and-sum out of the usual buffer (1.5 x the
    # balanced share: 104 row tiles) and the worst-case one (264).
    # granite_4_0_h_micro_pp4's chunked scan: forward (y and the chunk
    # states), and the gradient (the forward again + ONE backward kernel
    # that gives all five cotangents).
    "ssd_scan.fwd@16384": (_ssd, _SSD_ARGS, 1),
    "ssd_scan.bwd@16384": (
        jax.grad(lambda *a: _ssd(*a).astype(F32).sum(),
                 argnums=(0, 1, 2, 3, 4)), _SSD_ARGS, 2),
    # ... and the conv kernel pair in front of it: the forward, and the
    # gradient (the forward's output is not needed: ONE backward kernel
    # that makes the pre-activation again and gives dx, dk and dbias).
    "causal_conv.fwd@16384x4352": (_cconv, _CCONV_ARGS, 1),
    "causal_conv.bwd@16384x4352": (
        jax.grad(lambda *a: _cconv(*a).astype(F32).sum(),
                 argnums=(0, 1, 2)), _CCONV_ARGS, 1),
    # ... ouro_2_6b_pp6's rotary pair: the forward, and the gradient (the
    # same kernel with the sine negated: nothing of the forward runs).
    "rotary.fwd@16x8192x128": (_rotary, _ROTARY_ARGS, 1),
    "rotary.bwd@16x8192x128": _resample_vjp(
        _rotary, _ROTARY_ARGS, *_ROTARY_ARGS) + (1,),
    "moe_unpermute@53248": _unpermute(53248),
    "moe_unpermute@135168": _unpermute(135168),
    # phi4_mini_flash_pp5's selective scan: forward (y and the states the
    # chunks started from), and the gradient (the forward again + ONE
    # backward kernel that gives all six cotangents).
    "selective_scan.fwd@16384x5120": (_sel, _SEL_ARGS, 1),
    "selective_scan.bwd@16384x5120": (
        jax.grad(lambda *a: _sel(*a).astype(F32).sum(),
                 argnums=(0, 1, 2, 3, 4, 5)), _SEL_ARGS, 2),
    # ... and the grouped causal pair at a value twice the key's width,
    # over the triangle and over the 512-key band.
    "flash_attention_causal.bwd@16384x40x64v128": (
        _causal_grad, _DIFF_QKV, 2),
    "flash_attention_causal.bwd@16384x40x64v128,window512": (
        jax.grad(lambda q, k, v: _window(q, k, v).astype(F32).sum(),
                 argnums=(0, 1, 2)), _DIFF_QKV, 2),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    fn, shapes, n_calls = CASES[name]
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        shapes, is_leaf=lambda s: isinstance(s, _S))
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= n_calls


def _lowered_step(name, topo, monkeypatch):
    """The registered config's whole train step at the cell's own size,
    lowered for the described chip on abstract state, as
    ``tools/step_cache_key.py`` lowers it."""
    from distributed_sod_project_tpu.configs import get_config

    # jax.default_backend() still says cpu here: steer the flash kernels
    # (and whatever else asks) to Mosaic for the length of this test.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return step_cache_key.lower_step(get_config(name), topo.devices[0])


_KEYED = """\
from distributed_sod_project_tpu.pallas import fused_loss


def program(a, b):
    return fused_loss.pixel_region_sums(a, b, interpret=False)
"""


def test_the_cache_key_does_not_know_where_the_code_stands(chip, tmp_path):
    """The canonical IR (what JAX's persistent cache hashes) of one
    kernel-bearing program, lowered from two source files at two paths
    that differ by an inserted line: the same bytes, because the package
    keeps Python frames out of the kernels' serialized bodies
    (``pallas/__init__.py``) — and other bytes with JAX's default of ten
    frames put back, which is what every tree before PR 46 compiled
    under."""
    for where, pad in (("here", ""), ("elsewhere", "# a line more\n")):
        (tmp_path / where).mkdir()
        (tmp_path / where / "keyed.py").write_text(pad + _KEYED)
    args = [jax.ShapeDtypeStruct(_IMG.shape, _IMG.dtype, sharding=chip)] * 2

    def key(where):  # a fresh module a call: nothing traced is reused
        spec = importlib.util.spec_from_file_location(
            "keyed", tmp_path / where / "keyed.py")
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        lowered = jax.jit(module.program).lower(*args)
        assert "tpu_custom_call" in lowered.as_text()
        return step_cache_key.canonical_ir(lowered)

    assert jax.config.jax_traceback_in_locations_limit == 0
    assert key("here") == key("elsewhere")
    jax.config.update("jax_traceback_in_locations_limit", 10)
    try:
        assert key("here") != key("elsewhere")
    finally:
        jax.config.update("jax_traceback_in_locations_limit", 0)


@pytest.mark.parametrize("name", ["basnet_ds", "lfm2_8b_a1b_ep4"])
def test_no_kernel_body_of_a_step_names_the_checkout(chip, monkeypatch,
                                                     capsys, name):
    """``tools/step_cache_key.py`` from where it stands, on an image and
    on a token configuration at tiny widths (the fused loss and SSIM
    kernels under BASNet's eight outputs; the first token model's causal
    flash, grouped-product and un-permute kernels): one line with the
    hash and the kernel count, and no serialized ``tpu_custom_call`` body of the
    whole lowered step carries the checkout's path (nor any Python
    file's) — the compile cache's key is the same from any checkout.
    (In this process because only one may hold the TPU library; the
    names the tool steers are put back after it.)"""
    monkeypatch.setattr(jax, "default_backend", jax.default_backend)
    monkeypatch.setattr(vb, "_device_kind", vb._device_kind)
    seen = []
    real = step_cache_key.canonical_ir
    monkeypatch.setattr(step_cache_key, "canonical_ir",
                        lambda lowered: seen.append(lowered) or real(lowered))
    from test_profiler_names import _TOKEN_MODELS  # the tiny widths

    sets = [a for o in _TOKEN_MODELS.get(name, []) for a in ("--set", o)]
    assert step_cache_key.main(
        ["--config", name, "--batch", "2", "--image-size", "64",
         "--seq-len", "256"] + sets) == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    found = re.fullmatch(name + r" canonical step IR sha256 [0-9a-f]{64} "
                         r"\(\d+ bytes, (\d+) kernels\)", line)
    assert found and int(found.group(1)) >= 8, line
    (lowered,) = seen
    bodies = [base64.b64decode(b) for b in re.findall(
        r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered.as_text())]
    assert len(bodies) == int(found.group(1))
    assert all(b.startswith(b"ML\xefR") for b in bodies)  # MLIR bytecode
    assert not [b for b in bodies if _REPO.encode() in b or b".py" in b]


def _state_gib_and_fits(compiled):
    mem = compiled.memory_analysis()
    state_gib = mem.argument_size_in_bytes / 2 ** 30
    assert state_gib + mem.temp_size_in_bytes / 2 ** 30 < 15.75
    return state_gib


def test_the_state_space_step_compiles_for_v5e_and_fits(chip, topo,
                                                        monkeypatch):
    """``granite_4_0_h_micro_pp4``'s whole train step at the cell's size
    (published widths, 10 layers, 16,384 tokens) compiled for a described
    v5e: 56 kernels (18 forward scans and 9 backward, 18 forward convs
    and 9 backward, the two causal flash kernels), and state +
    temporaries inside the chip's 15.75 GiB.  (The compiler's own books,
    which decide whether it rematerialises, are read from its log:
    .claude/skills/verify.)"""
    lowered = _lowered_step("granite_4_0_h_micro_pp4", topo,
                                  monkeypatch)
    assert lowered.as_text().count("tpu_custom_call") == 56
    # 772 M parameters x 12 bytes
    assert 8.5 < _state_gib_and_fits(lowered.compile()) < 8.8


def test_the_looped_step_compiles_for_v5e_and_fits(chip, topo, monkeypatch):
    """``ouro_2_6b_pp6``'s whole train step at the cell's size (published
    widths, 8 layers run 4 times, 8,192 tokens, the whole vocabulary)
    compiled for a described v5e: 160 kernels (32 visits: a forward and a
    fused backward causal flash kernel each, and the rotation of q and k
    in one call forward, recomputed and backward), no op rematerialised
    by the compiler, state + temporaries inside the chip's 15.75 GiB, and
    no float32 copy of q or k under ``dsod.attn`` (before PR 42 XLA wrote
    192 of them a step, 64 MiB each, around the rotation).
    (The compiler's own books read 12.51 of 14.54 GiB: PERF.md section 4;
    they are read from its log, .claude/skills/verify.)"""
    lowered = _lowered_step("ouro_2_6b_pp6", topo, monkeypatch)
    assert lowered.as_text().count("tpu_custom_call") == 160
    compiled = lowered.compile()
    assert ".remat" not in compiled.as_text()
    assert [ln[:200] for ln in compiled.as_text().splitlines()
            if " = f32[1,8192,16,128]" in ln and "dsod.attn" in ln] == []
    # 612.4 M parameters x 12 bytes
    assert 6.8 < _state_gib_and_fits(compiled) < 6.9


def test_the_hybrid_step_compiles_for_v5e_and_fits(chip, topo, monkeypatch):
    """``nemotron_3_super_tp8_ep64``'s whole train step at the cell's size
    (published widths, 11 layers, 8,192 tokens) compiled for a described
    v5e — the shapes the shared kernels had not met: grouped products of
    [rows, 1024] x [1024, 2688] in 140 row tiles of 256 (2,688 = 21 x 128
    takes column tiles of 896 and 384), the un-permute at 22 choices,
    the scan at 16 heads and chunk 128, the conv over 1,280 columns, the
    causal flash pair at 4 query heads on 1 key-value head of 128 — 162
    kernels (five Mamba-2 layers: 10 scans + 5 backward, 10 convs + 5
    backward; the attention layer's pair; five expert layers: 40 grouped
    products, 10 ``dw`` and 15 un-permutes in the branch that runs, as
    many again in the one that takes a routing the usual buffer cannot
    hold), no op
    rematerialised by the compiler, state + temporaries inside the chip's
    15.75 GiB.  (The compiler's own books read 11.37 of 14.65 GiB: PERF.md
    section 4.)"""
    lowered = _lowered_step("nemotron_3_super_tp8_ep64", topo,
                                  monkeypatch)
    assert lowered.as_text().count("tpu_custom_call") == 162
    compiled = lowered.compile()
    assert ".remat" not in compiled.as_text()
    # 700.9 M parameters x 12 bytes
    assert 7.8 < _state_gib_and_fits(compiled) < 7.9


def test_the_decoder_hybrid_decoder_step_compiles_for_v5e_and_fits(
        chip, topo, monkeypatch):
    """``phi4_mini_flash_pp5``'s whole train step at the cell's size
    (published widths, 6 layers, 16,384 tokens) compiled for a described
    v5e: 16 kernels (two Mamba-1 layers: the scan forward ONCE and
    backward, the conv forward twice and backward; three attention
    layers: one forward and one backward causal flash call each, both
    softmax maps in one), no op rematerialised by the compiler, state +
    temporaries inside the chip's 15.75 GiB."""
    lowered = _lowered_step("phi4_mini_flash_pp5", topo, monkeypatch)
    assert lowered.as_text().count("tpu_custom_call") == 16
    compiled = lowered.compile()
    assert ".remat" not in compiled.as_text()
    # 697.2 M parameters x 12 bytes
    assert 7.7 < _state_gib_and_fits(compiled) < 7.9


def test_availability_rules_match_the_compiler():
    """What the v5e compiler refused at real widths gives way to the
    XLA path through the ``*_available`` rules (each probed once by
    hand, PR 23 — CHANGES.md): lane-padded 1-channel resample maps
    (and, the batch sitting on the sublanes, batches under 8),
    bf16 conv tiles of odd width, dynamic-filter maps whose padded
    width passes one 128-lane row."""
    assert not fr.fused_resample_available((RB, 160, 160, 1), (320, 320))
    assert not fr.fused_resample_available((B, 80, 80, 32), (160, 160),
                                           "concat", 64)  # 2 images
    assert fr.fused_resample_available((RB, 80, 80, 32), (160, 160),
                                       "concat", 64)
    shape = [(B, 5, 5, 32)]
    assert not fc.fused_conv_available(shape, (3, 3), 1, 32, dtype=BF)
    assert fc.fused_conv_available(shape, (3, 3), 1, 32, dtype=F32)
    assert fc.fused_conv_available([(B, 10, 10, 64)], (3, 3), 1, 64,
                                   dtype=BF)
    assert dfm.fused_dynamic_filter_available((B, 120, 120, 64), 3, 4)
    assert not dfm.fused_dynamic_filter_available((B, 160, 160, 64), 3, 4)


_NO_CHIP_CLIS = {
    "train.py": ["train.py", "--config", "minet_r50_dp", "--device", "tpu",
                 "--max-steps", "1"],
    "test.py": ["test.py", "--ckpt-dir", "/nonexistent", "--device", "tpu"],
    "tools/serve.py": ["tools/serve.py", "--config", "minet_r50_dp",
                       "--init-random", "--device", "tpu", "--port", "0"],
    "chip_smoke.py": ["chip_smoke.py"],
}


def _tpu_device_nodes():
    """Device nodes a TPU host exposes (v4/v5e: /dev/accel*, newer:
    numbered /dev/vfio groups) — looked for without touching JAX."""
    return glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*")


@pytest.mark.parametrize("cli", sorted(_NO_CHIP_CLIS))
def test_device_tpu_without_a_chip_exits_nonzero(cli):
    """No chip here: every entry point asked for the TPU exits non-zero
    with a message naming the backend found, and none prints a rate or
    an ok result.  (A child of the worker that holds the TPU library
    fails on its lock instead of on "no device found" — the same
    NoAcceleratorError either way.)  On a host that HAS a chip these
    children would take it and run for real — against the
    one-process-per-chip rule, and for minutes — so the case is
    skipped there; ``chip_smoke.py`` is that host's check."""
    nodes = _tpu_device_nodes()
    if nodes:
        pytest.skip(f"this host has a TPU ({nodes[0]}): the no-chip "
                    "exits cannot be shown here")
    p = subprocess.run([sys.executable] + _NO_CHIP_CLIS[cli], cwd=_REPO,
                       capture_output=True, text=True, timeout=300)
    out = p.stdout + p.stderr
    assert p.returncode != 0, out[-2000:]
    assert "--device tpu: JAX" in out and "cpu" in out, out[-2000:]
    assert "imgs/s" not in out and '"value"' not in out
    assert '"ok": true' not in out
    if cli == "chip_smoke.py":
        last = json.loads(p.stdout.strip().splitlines()[-1])
        assert last["ok"] is False and last["device"] is None
