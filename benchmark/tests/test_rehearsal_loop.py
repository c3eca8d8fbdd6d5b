"""CPU rehearsals of the looped cell at tiny widths: the whole of a run
through ``run_cell`` but the look for a chip, then the same run with
each fault of ``loop_faults.py`` planted under the timed path: three
passes for four, the final norm left out between passes, uniform exit
weights, the entropy term dropped, the last pass's loss alone.  At
float32 compute the program and the reference differ by rounding order
alone, so sound and faulty runs alike are held to limits far under the
cell's own (``TIGHT``).

The cell's 2,000-step warm-up moves the gate's bias (it starts at 0) by
less than the rehearsal can tell from standing still in three steps:
every rehearsal here warms up over 20 steps, program and reference
alike, and says so.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_loop.py
"""

import time

import jax
import pytest

from benchmark import run as harness
from benchmark.tests.loop_faults import FAULTS, plant

CELL = "ouro_2_6b_pp6.train_s8k_b1"
# Two layers of the eight, four passes, 128 tokens.
TINY = ["model.lm.layer_types=attention,attention",
        "model.lm.ffn_types=dense,dense", "model.lm.vocab=512",
        "model.lm.hidden=64", "model.lm.heads=4", "model.lm.kv_heads=4",
        "model.lm.head_dim=16", "model.lm.dense_width=96",
        "data.seq_len=128", "data.vocab=512", "data.synthetic_size=64",
        "global_batch_size=2", "model.compute_dtype=float32",
        "log_every_steps=1", "data.num_workers=2", "optim.warmup_steps=20"]
TINY_ARCH = dict(layers=2, heads=4, head_dim=16)
TIGHT = {"loss_rel_gap.step1": 1e-5, "loss_rel_gap.step2": 1e-5,
         "loss_rel_gap.step3": 1e-5, "grad_norm_median_leaf_gap": 1e-5,
         "grad_norm_worst_leaf_gap": 1e-4,
         "dparam_norm_median_leaf_gap": 1e-3, "dparam_zero_leaf_share": 0.0,
         "exit_mass_gap": 1e-4}
JUDGED = ["loss_rel_gap.step1", "loss_rel_gap.step2", "loss_rel_gap.step3",
          "grad_norm_median_leaf_gap", "grad_norm_worst_leaf_gap",
          "dparam_norm_median_leaf_gap", "dparam_zero_leaf_share",
          "exit_mass_gap"]


def _run(monkeypatch, limits=TIGHT, overrides=(), seed=3100000019):
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


def test_rehearsal_is_correct_and_reports_no_device_metric(monkeypatch):
    line = _run(monkeypatch)
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    assert set(line["rehearsal"]) == {"train_img_per_s_chip", "setup_s"}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # the eight judged numbers and no routing row
    assert [n for n, _, lim, _ in line["compared"] if lim is not None] \
        == JUDGED
    assert not [n for n, _, _, _ in line["compared"] if n.startswith("moe_")]


# The row each fault is caught by at this size (others may fail too).
CAUGHT_BY = {"three_passes": "exit_mass_gap",
             "no_norm_between_passes": "loss_rel_gap.step1",
             "uniform_exit_weights": "exit_mass_gap",
             "no_entropy_term": "loss_rel_gap.step1",
             "last_pass_loss_alone": "exit_mass_gap"}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    line = _run(monkeypatch,
                overrides=plant(fault, monkeypatch.setattr))
    assert line["correct"] is False, line["compared"]
    failed = {n for n, _, _, ok in line["compared"] if not ok}
    assert CAUGHT_BY[fault] in failed, (fault, line["compared"])
    if fault in ("uniform_exit_weights", "last_pass_loss_alone"):
        # the gate gets no gradient: its bias never moves
        assert "dparam_zero_leaf_share" in failed
