"""CPU rehearsals of the token model's cell at tiny widths: the whole of
a run through ``run_cell`` but the look for a chip, then the same run
with a fault planted under the timed path, held to the cell's OWN
limits (those of the workload file).  Minutes each.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_lm.py
"""

import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as harness

CELL = "lfm2_8b_a1b_ep4.train_s8k_b4"
TINY = ["model.lm.vocab=512", "model.lm.hidden=64", "model.lm.heads=4",
        "model.lm.kv_heads=2", "model.lm.head_dim=16",
        "model.lm.dense_width=96", "model.lm.expert_width=48",
        "model.lm.experts=8", "model.lm.experts_held=2", "model.lm.top_k=2",
        "data.seq_len=160", "data.vocab=512", "data.synthetic_size=64",
        "global_batch_size=2", "model.compute_dtype=float32",
        "log_every_steps=1", "data.num_workers=2"]
TINY_ARCH = dict(heads=4, kv_heads=2, head_dim=16, top_k=2)
# At float32 compute the program and the reference differ by rounding
# order only; the sound rehearsal is held to these.
TIGHT = {"loss_rel_gap.step1": 1e-5, "loss_rel_gap.step3": 1e-5,
         "grad_norm_median_leaf_gap": 1e-4, "grad_norm_worst_leaf_gap": 1e-3,
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
    assert rows["moe_pairs_here_share_drift"] < 0.5


def test_a_dropped_expert(monkeypatch):
    from distributed_sod_project_tpu.models import lfm2

    real = lfm2.combine
    # the first held expert's rows never reach the sum
    monkeypatch.setattr(
        lfm2, "grouped_matmul",
        lambda a, w, te, nu, **kw: lfm2_gmm(a, w, te, nu, **kw)
        * (jnp.repeat(te, kw["tile_m"]) != 0)[:, None])
    from distributed_sod_project_tpu.pallas.grouped_matmul import \
        grouped_matmul as lfm2_gmm

    assert real is lfm2.combine
    assert "loss_rel_gap.step1" in _failed(_run(monkeypatch))


def test_top_k_one_short(monkeypatch):
    # the program routes to one expert fewer than the reference
    assert "loss_rel_gap.step1" in _failed(
        _run(monkeypatch, overrides=["model.lm.top_k=1"]))


def test_the_bias_added_to_the_weights(monkeypatch):
    """The bias must only select.  Its published scale (0.01) moves the
    weights by a percent: the rehearsal plants it at 0.2 and says so."""
    from distributed_sod_project_tpu.models import lfm2

    real = jnp.take_along_axis

    class Leaky:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def take_along_axis(s, idx, axis):
            if s.ndim == 2 and s.shape[-1] == 8 and idx.shape[-1] == 2:
                s = s + Leaky.bias  # the selection scores, not sigmoid
            return real(s, idx, axis)

    Leaky.bias = 0.2 * jax.random.normal(jax.random.key(0), (8,))
    monkeypatch.setattr(lfm2, "jnp", Leaky())
    assert _failed(_run(monkeypatch)) & {
        "loss_rel_gap.step1", "grad_norm_median_leaf_gap"}


def test_a_non_causal_mask(monkeypatch):
    from distributed_sod_project_tpu.models import lfm2

    def full(q, k, v):
        g = q.shape[1] // k.shape[1]
        k, v = (jnp.repeat(t, g, axis=1) for t in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    monkeypatch.setattr(lfm2, "flash_attention_causal", full)
    assert "loss_rel_gap.step1" in _failed(_run(monkeypatch))


def test_a_gradient_twice_too_large(monkeypatch):
    """AdamW hides a scaled gradient from the parameters' change and the
    loss of step 1 is untouched: the first-gradient rows have to see it."""
    from distributed_sod_project_tpu.losses import token_ce

    @jax.custom_vjp
    def twice(x):
        return x

    twice.defvjp(lambda x: (x, None), lambda _, g: (2.0 * g,))
    real = token_ce.tied_cross_entropy
    monkeypatch.setattr(token_ce, "tied_cross_entropy",
                        lambda *a, **kw: twice(real(*a, **kw)))
    bad = _failed(_run(monkeypatch))
    assert "grad_norm_median_leaf_gap" in bad
    assert "loss_rel_gap.step1" not in bad
