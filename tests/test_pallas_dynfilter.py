"""Pallas fused dynamic local filter vs the XLA im2col path
(models/hdfnet.py) — forward, both gradients, dilations, the HDFNet
dlf_impl wiring, the VMEM fallback, and the real-TPU Mosaic lowering."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sod_project_tpu.models.hdfnet import dynamic_local_filter
from distributed_sod_project_tpu.pallas import dynamic_filter as df
from distributed_sod_project_tpu.pallas.dynamic_filter import (
    fused_dynamic_filter, fused_dynamic_filter_available)


def _xk(b=2, h=12, w=16, c=8, ksize=3, seed=0):
    kx, kk = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (b, h, w, c))
    k = jax.nn.softmax(jax.random.normal(kk, (b, h, w, ksize * ksize)), -1)
    return x, k


@pytest.mark.parametrize("ksize,dilation", [
    (3, 1),
    # HDFNet's other dilation branches exercise the same shifted-FMA
    # kernel; each costs ~10 s cold compile — full suite only.
    pytest.param(3, 2, marks=pytest.mark.slow),
    pytest.param(3, 4, marks=pytest.mark.slow),
    pytest.param(5, 1, marks=pytest.mark.slow),
])
def test_forward_and_grads_match_im2col(ksize, dilation):
    x, k = _xk(ksize=ksize)
    out = fused_dynamic_filter(x, k, ksize, dilation)
    ref = dynamic_local_filter(x, k, ksize, dilation, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)

    cot = jax.random.normal(jax.random.PRNGKey(9), out.shape)
    g_p = jax.grad(lambda x_, k_: jnp.sum(
        fused_dynamic_filter(x_, k_, ksize, dilation) * cot),
        argnums=(0, 1))(x, k)
    g_x = jax.grad(lambda x_, k_: jnp.sum(
        dynamic_local_filter(x_, k_, ksize, dilation, impl="xla") * cot),
        argnums=(0, 1))(x, k)
    np.testing.assert_allclose(np.asarray(g_p[0]), np.asarray(g_x[0]),
                               atol=5e-6, err_msg="dx")
    np.testing.assert_allclose(np.asarray(g_p[1]), np.asarray(g_x[1]),
                               atol=5e-6, err_msg="dkernels")


def test_bfloat16_inputs():
    x, k = _xk(c=16)
    out = fused_dynamic_filter(x.astype(jnp.bfloat16), k, 3)
    assert out.dtype == jnp.bfloat16
    ref = dynamic_local_filter(x, k, 3, impl="xla")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=3e-2)


def test_identity_kernel():
    """One-hot-center kernels must reproduce the input exactly (same
    invariant test_models.py checks for the im2col path)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 4))
    k = jnp.zeros((2, 8, 8, 9)).at[..., 4].set(1.0)
    out = fused_dynamic_filter(x, k, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-6)


def test_validation_and_fallback():
    x, k = _xk()
    with pytest.raises(ValueError, match="kernels shape"):
        fused_dynamic_filter(x, k[..., :4], 3)
    with pytest.raises(ValueError, match="odd"):
        fused_dynamic_filter(x, jnp.zeros(x.shape[:3] + (16,)), 4)
    # Oversize tiles silently take the XLA path — same numbers.
    assert not fused_dynamic_filter_available((1, 2048, 2048, 64), 3)
    assert fused_dynamic_filter_available(x.shape, 3)


def test_vmem_fallback_actually_runs(monkeypatch):
    """Shrink the budget so the fallback branch EXECUTES (not just the
    predicate): results must equal the im2col path and grads flow."""
    from distributed_sod_project_tpu.pallas import dynamic_filter as df

    monkeypatch.setattr(df, "_MAX_TILE_ELEMS", 1)
    x, k = _xk()
    assert not df.fused_dynamic_filter_available(x.shape, 3)
    out = df.fused_dynamic_filter(x, k, 3)
    ref = dynamic_local_filter(x, k, 3, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    g = jax.grad(lambda x_: jnp.sum(df.fused_dynamic_filter(x_, k, 3)))(x)
    assert np.all(np.isfinite(np.asarray(g)))


@pytest.mark.slow
def test_hdfnet_dlf_impl_parity():
    """HDFNet(dlf_impl='pallas') is numerically the same model."""
    from distributed_sod_project_tpu.models.hdfnet import HDFNet

    img = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 32, 3))
    dep = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 1))
    m_x = HDFNet(axis_name=None)
    m_p = HDFNet(axis_name=None, dlf_impl="pallas")
    params = m_x.init(jax.random.PRNGKey(2), img, dep, train=False)
    out_x = m_x.apply(params, img, dep, train=False)
    out_p = m_p.apply(params, img, dep, train=False)
    for a, b in zip(out_p, out_x):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_registry_rejects_dlf_impl_on_other_models():
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.models.registry import build_model

    cfg = get_config("minet_vgg16_ref")
    bad = cfg.model.__class__(**{**cfg.model.__dict__, "dlf_impl": "pallas"})
    with pytest.raises(ValueError, match="only applies to hdfnet"):
        build_model(bad)


def test_dynfilter_lowers_for_real_tpu():
    """interpret=False + export for platform='tpu' runs the Mosaic
    pipeline end-to-end (no chip needed) — fwd and both bwd kernels."""
    from jax import export

    from distributed_sod_project_tpu.pallas import dynamic_filter as df

    b, h, w, c = 1, 16, 16, 8
    x = jnp.zeros((b, h, w, c), jnp.float32)
    kt = jnp.zeros((b, 9, h, w), jnp.float32)

    exp = export.export(jax.jit(
        lambda x_, k_: df._call_filter(x_, k_, 3, 1, False)),
        platforms=["tpu"])(x, kt)
    assert "tpu_custom_call" in exp.mlir_module()

    g = jnp.zeros((b, h, w, c), jnp.float32)
    exp = export.export(jax.jit(
        lambda x_, k_, g_: df._dlf_bwd(3, 1, False, (x_, k_), g_)),
        platforms=["tpu"])(x, kt, g)
    assert "tpu_custom_call" in exp.mlir_module()


def test_dlf_compiler_params_follow_the_shared_vmem_rule(monkeypatch):
    """pallas/vmem_budget.py: the raised 100 MB scoped-VMEM ceiling on a
    chip the table knows to have the VMEM for it (v5e), the compiler
    default off-TPU (interpret mode never reads it), an ERROR for a TPU
    kind utils/chips.py has no row for, and DSOD_DLF_VMEM_MB as the
    escape hatch."""
    from distributed_sod_project_tpu.pallas import vmem_budget as vb
    from distributed_sod_project_tpu.utils.chips import UnknownChipError

    monkeypatch.delenv("DSOD_DLF_VMEM_MB", raising=False)
    for kind, want in {"TPU v5 lite": 100 << 20, None: None}.items():
        monkeypatch.setattr(vb, "_device_kind", lambda kind=kind: kind)
        got = getattr(df._compiler_params(), "vmem_limit_bytes", None)
        assert got == want, (kind, got, want)
    monkeypatch.setattr(vb, "_device_kind", lambda: "TPU v9 ultra")
    with pytest.raises(UnknownChipError):
        df._compiler_params()
    monkeypatch.setenv("DSOD_DLF_VMEM_MB", "8")
    assert df._compiler_params().vmem_limit_bytes == 8 << 20
    monkeypatch.setenv("DSOD_DLF_VMEM_MB", "0")
    assert getattr(df._compiler_params(), "vmem_limit_bytes", None) is None