"""CPU rehearsals of the latent-attention cell at tiny widths: the whole
of a run through ``run_cell`` but the look for a chip, then the same run
with a fault planted under the timed path, held to the cell's OWN limits
(those of the workload file).

The balancing rule's published rate (0.001) moves a selection too
rarely at this size for three steps to show its sign: every rehearsal
here runs program and reference at 0.05, and says so.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_mla.py
"""

import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from benchmark import run as harness

CELL = "kimi_vl_a3b_ep8.train_s16k_b2"
GAMMA = 0.05
# Three layers of the six (the dense one and two expert layers): every
# kind of layer, half the compile.
TINY = ["model.lm.ffn_types=dense,moe,moe", "model.lm.vocab=512",
        "model.lm.hidden=64", "model.lm.heads=4", "model.lm.head_dim=24",
        "model.lm.rope_dim=8",
        "model.lm.v_dim=16", "model.lm.kv_rank=32",
        "model.lm.dense_width=96", "model.lm.expert_width=48",
        "model.lm.experts=8", "model.lm.experts_held=2", "model.lm.top_k=2",
        f"model.lm.bias_update_rate={GAMMA}",
        "data.seq_len=160", "data.vocab=512", "data.synthetic_size=64",
        "global_batch_size=2", "model.compute_dtype=float32",
        "log_every_steps=1", "data.num_workers=2"]
TINY_ARCH = dict(ffn_types=["dense", "moe", "moe"], heads=4, nope_dim=16,
                 rope_dim=8, v_dim=16, kv_rank=32, top_k=2,
                 bias_update_rate=GAMMA)
# At float32 compute the program and the reference differ by rounding
# order only; the sound rehearsal is held to these.
TIGHT = {"loss_rel_gap.step1": 1e-5, "loss_rel_gap.step2": 1e-5,
         "loss_rel_gap.step3": 1e-5, "grad_norm_median_leaf_gap": 1e-4,
         "grad_norm_worst_leaf_gap": 1e-3,
         "dparam_norm_median_leaf_gap": 1e-3, "dparam_zero_leaf_share": 0.0}


def _run(monkeypatch, limits=None, overrides=(), seed=3000000019):
    d = jax.devices()[0]
    if d.platform != "cpu":
        pytest.skip("a rehearsal is for the CPU")
    real = harness.resolve

    def resolve(*a, **kw):
        entry, cell, config = real(*a, **kw)
        ref = dict(config["reference"])
        ref["arch"] = dict(ref["arch"], **TINY_ARCH)
        # ticks come every step here: open the window past the three
        # steps that are followed
        cell = dict(cell, warmup_ticks=4)
        if limits is not None:
            cell["limits"] = limits
        return entry, cell, dict(config, reference=ref)

    monkeypatch.setattr(harness, "resolve", resolve)
    return harness.run_cell(
        CELL, seed, 4.0, False, t_start=time.perf_counter(),
        device={"platform": "cpu", "kind": d.device_kind, "count": 1},
        extra_overrides=TINY + list(overrides))


def _failed(line):
    assert line["correct"] is False, line["compared"]
    return {n for n, _, _, ok in line["compared"] if not ok}


def test_rehearsal_is_correct_and_reports_no_device_metric(monkeypatch):
    line = _run(monkeypatch, limits=TIGHT)
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    assert set(line["rehearsal"]) == {"train_img_per_s_chip", "setup_s"}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    rows = {n: v for n, v, _, _ in line["compared"]}
    assert rows["moe_dropped_pairs"] == 0.0
    # 320 tokens over 2 of 8 experts: the share is noisy at this size;
    # 1.0 is the reading of a routing that left the held experts
    assert rows["moe_pairs_here_share_drift"] < 1.0


def test_the_shared_experts_dropped(monkeypatch):
    from distributed_sod_project_tpu.models import kimi, lfm2

    monkeypatch.setattr(
        kimi, "SwiGLU", lambda width, name, **kw: lambda y: lfm2.SwiGLU(
            width, name=name, **kw)(y) * (0.0 if name == "shared" else 1.0))
    assert "loss_rel_gap.step1" in _failed(_run(monkeypatch))


def test_the_rotary_key_rotated_per_head_instead_of_shared(monkeypatch):
    """The one rotary key a token cut into a key of its own per query
    head (each a quarter as wide, with the frequencies of that width)."""
    from distributed_sod_project_tpu.models import kimi, lfm2

    def rope(t, theta):
        if t.shape[2] != 1:
            return lfm2.rope(t, theta)
        b, n, _, d = t.shape
        return lfm2.rope(t.reshape(b, n, 4, d // 4), theta).reshape(t.shape)

    monkeypatch.setattr(kimi, "rope", rope)
    assert "loss_rel_gap.step1" in _failed(_run(monkeypatch))


def test_the_scaling_factor_left_out(monkeypatch):
    # the program's routed weights sum to 1, the reference's to 2.446
    assert "loss_rel_gap.step1" in _failed(_run(
        monkeypatch, overrides=["model.lm.routed_scaling_factor=1.0"]))


def test_the_latent_norm_left_out(monkeypatch):
    from distributed_sod_project_tpu.models import kimi, lfm2

    class ScaleOnly(nn.Module):  # keeps the parameter, skips the norm
        dtype: jnp.dtype

        @nn.compact
        def __call__(self, x):
            scale = self.param("scale", nn.initializers.ones,
                               (x.shape[-1],), jnp.float32)
            return (x.astype(jnp.float32) * scale).astype(self.dtype)

    monkeypatch.setattr(
        kimi, "RMSNorm", lambda eps, dtype, name: ScaleOnly(dtype, name=name)
        if name == "kv_a_norm" else lfm2.RMSNorm(eps, dtype, name=name))
    assert "loss_rel_gap.step1" in _failed(_run(monkeypatch))


def test_a_bias_update_with_the_wrong_sign(monkeypatch):
    """Step 1 routes by the seeded (zero) bias on both sides; the
    program then pushes the loaded experts' bias UP, and the losses of
    the steps after part."""
    bad = _failed(_run(monkeypatch, overrides=[
        f"model.lm.bias_update_rate={-GAMMA}"]))
    assert "loss_rel_gap.step1" not in bad
    assert bad & {"loss_rel_gap.step2", "loss_rel_gap.step3"}


def test_top_k_one_short(monkeypatch):
    # The program routes to one expert fewer than the reference.  At
    # this width the embedding carries the first loss (gap 2e-5); the
    # first gradient and every later step show the missing expert.
    bad = _failed(_run(monkeypatch, overrides=["model.lm.top_k=1"]))
    assert {"grad_norm_median_leaf_gap", "loss_rel_gap.step2"} <= bad
