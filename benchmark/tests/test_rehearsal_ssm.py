"""CPU rehearsals of the state-space cell at tiny widths: the whole of a
run through ``run_cell`` but the look for a chip, then the same run with
a fault planted under the timed path: each of the family's four multipliers
left out, a rotation the published model does not have, the state
dropped at every chunk's edge, the D skip dropped, and the carried state
kept in bfloat16.  At float32 compute the program and the reference
differ by rounding order alone, so sound and faulty runs alike are held
to limits a hundred times under the cell's own (``TIGHT``): at this
width a random causal softmax is all but uniform whatever scales or
rotates its scores, and a fault in the one attention layer shows in its
leaves' gradients long before it shows in the loss.

The cell's 2,000-step warm-up moves a ``dt_bias`` of magnitude 4-8 by
less than half a float32 ulp in the three steps followed, and with 8
heads a layer a whole leaf can stand still (``dparam_zero_leaf_share``
1/37 at the first seed tried): every rehearsal here warms up over 20
steps, program and reference alike, and says so.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_ssm.py
"""

import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as harness

CELL = "granite_4_0_h_micro_pp4.train_s16k_b1"
# Three layers of the ten (mamba, attention, mamba): every kind of layer,
# a third of the compile; four chunks of 32 tokens a sequence.
TINY = ["model.lm.layer_types=mamba,attention,mamba", "model.lm.vocab=512",
        "model.lm.hidden=64", "model.lm.heads=4", "model.lm.kv_heads=2",
        "model.lm.head_dim=16", "model.lm.dense_width=96",
        "model.lm.ssm_heads=8", "model.lm.ssm_head_dim=16",
        "model.lm.ssm_state=16", "model.lm.ssm_chunk=32",
        "data.seq_len=128", "data.vocab=512", "data.synthetic_size=64",
        "global_batch_size=2", "model.compute_dtype=float32",
        "log_every_steps=1", "data.num_workers=2", "optim.warmup_steps=20"]
TINY_ARCH = dict(layer_types=["mamba", "attention", "mamba"], heads=4,
                 kv_heads=2, head_dim=16, ssm_heads=8, ssm_head_dim=16,
                 ssm_state=16)
# At float32 compute the program and the reference differ by rounding
# order only; the sound rehearsal is held to these.
TIGHT = {"loss_rel_gap.step1": 1e-6, "loss_rel_gap.step2": 1e-6,
         "loss_rel_gap.step3": 1e-6, "grad_norm_median_leaf_gap": 1e-6,
         "grad_norm_worst_leaf_gap": 1e-5,
         "dparam_norm_median_leaf_gap": 1e-4, "dparam_zero_leaf_share": 0.0}


def _run(monkeypatch, limits=TIGHT, overrides=(), seed=3000000019):
    d = jax.devices()[0]
    if d.platform != "cpu":
        pytest.skip("a rehearsal is for the CPU")
    real = harness.resolve

    def resolve(*a, **kw):
        entry, cell, config = real(*a, **kw)
        ref = dict(config["reference"])
        ref["arch"] = dict(ref["arch"], **TINY_ARCH)
        ref["optimizer"] = dict(ref["optimizer"], warmup_steps=20)
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
    line = _run(monkeypatch)
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    assert set(line["rehearsal"]) == {"train_img_per_s_chip", "setup_s"}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # the seven judged numbers and no routing row
    assert [n for n, _, lim, _ in line["compared"] if lim is not None] == [
        "loss_rel_gap.step1", "loss_rel_gap.step2", "loss_rel_gap.step3",
        "grad_norm_median_leaf_gap", "grad_norm_worst_leaf_gap",
        "dparam_norm_median_leaf_gap", "dparam_zero_leaf_share"]
    assert not [n for n, _, _, _ in line["compared"] if n.startswith("moe_")]


@pytest.mark.parametrize("override", [
    "model.lm.embedding_multiplier=1.0",   # 12 left out
    "model.lm.residual_multiplier=1.0",    # 0.22 left out
    "model.lm.logits_scaling=1.0",         # 1/8 left out
])
def test_a_multiplier_left_out(monkeypatch, override):
    assert "loss_rel_gap.step1" in _failed(_run(monkeypatch,
                                                overrides=[override]))


def test_the_scores_scaled(monkeypatch):
    """1/sqrt(head_dim) where the published model has 1/64."""
    assert "grad_norm_worst_leaf_gap" in _failed(_run(
        monkeypatch, overrides=["model.lm.attention_multiplier=0.0"]))


def test_the_scores_rotated(monkeypatch):
    """A rotation the published model does not have, planted on q and k
    in front of the flash kernel."""
    from distributed_sod_project_tpu.models import granite
    from distributed_sod_project_tpu.models.lfm2 import rope

    real = granite.flash_attention_causal
    turn = lambda t: rope(t.transpose(0, 2, 1, 3), 1e4).transpose(  # noqa: E731
        0, 2, 1, 3).astype(t.dtype)
    monkeypatch.setattr(granite, "flash_attention_causal",
                        lambda q, k, v: real(turn(q), turn(k), v))
    assert "grad_norm_worst_leaf_gap" in _failed(_run(monkeypatch))


def test_the_state_dropped_at_every_chunks_edge(monkeypatch):
    """Each chunk scanned as a sequence of its own: the intra-chunk term
    alone, what a dropped cross-chunk term computes."""
    from distributed_sod_project_tpu.models import granite
    from distributed_sod_project_tpu.pallas import ssd_scan as ssd

    def per_chunk(x, dt, a, b, c, *, chunk):
        cut = lambda t: t.reshape((-1, chunk) + t.shape[2:])  # noqa: E731
        return ssd.ssd_scan(cut(x), cut(dt), a, cut(b), cut(c),
                            chunk=chunk).reshape(x.shape)

    monkeypatch.setattr(granite, "ssd_scan", per_chunk)
    assert "loss_rel_gap.step1" in _failed(_run(monkeypatch))


def test_the_d_skip_dropped(monkeypatch):
    from distributed_sod_project_tpu.models import granite

    real = granite.Mamba2Mixer.param

    def param(self, name, init, *a):
        v = real(self, name, init, *a)
        return v * 0.0 if name == "D" else v

    monkeypatch.setattr(granite.Mamba2Mixer, "param", param)
    assert "loss_rel_gap.step1" in _failed(_run(monkeypatch))


def test_the_carried_state_in_bfloat16(monkeypatch):
    """The precision below the one the configuration states: everything
    else is exact here, so the tight limits show it."""
    from distributed_sod_project_tpu.pallas import ssd_scan as ssd

    monkeypatch.setattr(ssd, "STATE_DTYPE", jnp.bfloat16)
    assert "grad_norm_worst_leaf_gap" in _failed(_run(monkeypatch))
