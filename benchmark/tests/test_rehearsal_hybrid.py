"""CPU rehearsals of the hybrid cell at tiny widths: the whole of a run
through ``run_cell`` but the look for a chip, then the same run with
each fault of ``hybrid_faults.py`` planted under the timed path: the
routed scaling factor left out, one expert fewer kept, ``relu`` for
``relu^2``, the shared expert dropped, one held expert's part never
projected up, the scan's state dropped at every chunk's edge.  At
float32 compute the program and the reference differ by rounding order
alone, so sound and faulty runs alike are held to limits far under the
cell's own (``TIGHT``).

Every rehearsal here warms up over 20 steps, program and reference
alike (the cell's 2,000 steps move a leaf by less than three steps can
tell from standing still), and says so.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_hybrid.py
"""

import time

import jax
import pytest

from benchmark import run as harness
from benchmark.tests.hybrid_faults import FAULTS, plant

CELL = "nemotron_3_super_tp8_ep64.train_s8k_b1"
# One layer of each kind and a second expert layer, 128 tokens in four
# chunks, 4 of 16 experts held, 3 kept.
TYPES = ["mamba", "moe", "attention", "moe"]
TINY = ["model.lm.layer_types=" + ",".join(TYPES), "model.lm.vocab=512",
        "model.lm.hidden=64", "model.lm.heads=4", "model.lm.kv_heads=1",
        "model.lm.head_dim=16", "model.lm.expert_width=48",
        "model.lm.latent_width=32", "model.lm.shared_width=96",
        "model.lm.experts=16", "model.lm.experts_held=4", "model.lm.top_k=3",
        "model.lm.ssm_heads=8", "model.lm.ssm_head_dim=16",
        "model.lm.ssm_state=16", "model.lm.ssm_chunk=32",
        "data.seq_len=128", "data.vocab=512", "data.synthetic_size=64",
        "global_batch_size=2", "model.compute_dtype=float32",
        "log_every_steps=1", "data.num_workers=2", "optim.warmup_steps=20"]
TINY_ARCH = dict(layer_types=TYPES, heads=4, kv_heads=1, head_dim=16,
                 ssm_heads=8, ssm_head_dim=16, ssm_state=16, top_k=3)
TIGHT = {"loss_rel_gap.step1": 2e-5, "loss_rel_gap.step2": 2e-5,
         "loss_rel_gap.step3": 2e-5, "grad_norm_median_leaf_gap": 2e-5,
         "grad_norm_worst_leaf_gap": 5e-4,
         "dparam_norm_median_leaf_gap": 1e-3, "dparam_zero_leaf_share": 0.0}
JUDGED = ["loss_rel_gap.step1", "loss_rel_gap.step2", "loss_rel_gap.step3",
          "grad_norm_median_leaf_gap", "grad_norm_worst_leaf_gap",
          "dparam_norm_median_leaf_gap", "dparam_zero_leaf_share",
          "moe_dropped_pairs"]  # the share's drift is printed, not judged


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
    # the seven judged numbers and the dropped pairs
    assert [n for n, _, lim, _ in line["compared"] if lim is not None] \
        == JUDGED
    # the runner's own swap of train_lm's names is undone
    from benchmark.runners import train_lm
    from benchmark.harness import weights_lm

    assert train_lm.variables_builder is weights_lm.variables_builder
    assert train_lm.MOE_KEYS == ("moe_pairs_here_share",
                                 "moe_load_max_over_mean",
                                 "moe_dropped_pairs")


# The row each fault is caught by at this size (others may fail too).
CAUGHT_BY = {"no_scaling_factor": "loss_rel_gap.step1",
             "top_k_one_short": "loss_rel_gap.step1",
             "relu_for_relu2": "loss_rel_gap.step1",
             "no_shared_expert": "loss_rel_gap.step1",
             "one_held_expert_never_projected_up": "loss_rel_gap.step1",
             "state_dropped_at_chunk_edge": "loss_rel_gap.step1"}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    line = _run(monkeypatch,
                overrides=plant(fault, monkeypatch.setattr))
    assert line["correct"] is False, line["compared"]
    failed = {n for n, _, _, ok in line["compared"] if not ok}
    assert CAUGHT_BY[fault] in failed, (fault, line["compared"])
