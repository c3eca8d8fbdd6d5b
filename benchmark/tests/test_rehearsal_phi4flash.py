"""CPU rehearsals of the decoder-hybrid-decoder cell at tiny widths: the
whole of a run through ``run_cell`` but the look for a chip, then the
same run with each fault of ``phi4flash_faults.py`` planted under the
timed path: a window twice as long, ``(1 - lambda_init)`` left out,
``lambda_init`` read from the index on this stage, ``m`` taken after the
gate, the cross layer given the windowed layer's keys and values, the
scan's state dropped at every chunk's edge, ``A`` made one number a
channel.  At float32 compute the program and the reference differ by
rounding order alone, so sound and faulty runs alike are held to limits
far under the cell's own (``TIGHT``).

Every rehearsal here warms up over 20 steps, program and reference
alike (the cell's 2,000 steps move a leaf by less than three steps can
tell from standing still), and says so.

Then the five new readers on a rehearsal trace: a CPU profile has no
device plane, so the trace is made of the tiny step's OWN op paths (its
lowering's name stacks), one event of one millisecond an op, reduced by
the readers' own reducers — what a reader finds by scope in the program
as it is, it finds there.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_phi4flash.py
"""

import json
import math
import os
import re
import sys
import time

import jax
import pytest

from benchmark import run as harness
from benchmark.tests.phi4flash_faults import FAULTS, plant

CELL = "phi4_mini_flash_pp5.train_s16k_b1"
# The stage's six layers, 128 tokens in four chunks, a window of 24.
TINY = ["model.lm.vocab=512", "model.lm.hidden=64", "model.lm.heads=4",
        "model.lm.kv_heads=2", "model.lm.head_dim=16",
        "model.lm.dense_width=96", "model.lm.ssm_heads=128",
        "model.lm.ssm_state=16", "model.lm.ssm_chunk=32",
        "model.lm.ssm_dt_rank=4", "model.lm.window=24",
        "data.seq_len=128", "data.vocab=512", "data.synthetic_size=64",
        "global_batch_size=2", "model.compute_dtype=float32",
        "log_every_steps=1", "data.num_workers=2", "optim.warmup_steps=20"]
TINY_ARCH = dict(heads=4, kv_heads=2, head_dim=16, ssm_state=16,
                 ssm_dt_rank=4, window=24)
TIGHT = {"loss_rel_gap.step1": 2e-5, "loss_rel_gap.step2": 2e-5,
         "loss_rel_gap.step3": 2e-5, "grad_norm_median_leaf_gap": 2e-5,
         "grad_norm_worst_leaf_gap": 5e-4,
         "dparam_norm_median_leaf_gap": 1e-3, "dparam_zero_leaf_share": 0.0}
JUDGED = ["loss_rel_gap.step1", "loss_rel_gap.step2", "loss_rel_gap.step3",
          "grad_norm_median_leaf_gap", "grad_norm_worst_leaf_gap",
          "dparam_norm_median_leaf_gap", "dparam_zero_leaf_share"]
NEW = ["train_diff_attn_window_ms", "train_diff_attn_full_ms",
       "train_gmu_ms", "selective_scan_roofline", "diff_attention_roofline"]


def _run(monkeypatch, limits=TIGHT, overrides=(), seed=3200000029):
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
    # the seven judged numbers and no routing row
    assert [n for n, _, lim, _ in line["compared"] if lim is not None] \
        == JUDGED
    # the runner's own swap of train_ssm's names is undone
    from benchmark.harness import weights_ssm
    from benchmark.runners import train_ssm

    assert train_ssm.variables_builder is weights_ssm.variables_builder
    assert train_ssm.SSM_KEYS == ("ssm_decay_min", "ssm_delta_max")


# The row each fault is caught by at this size (others may fail too).
CAUGHT_BY = {f: "loss_rel_gap.step1" for f in FAULTS}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    line = _run(monkeypatch,
                overrides=plant(fault, monkeypatch.setattr, window=24))
    assert line["correct"] is False, line["compared"]
    failed = {n for n, _, _, ok in line["compared"] if not ok}
    assert CAUGHT_BY[fault] in failed, (fault, line["compared"])


# -- the five new readers on a rehearsal trace ------------------------------

@pytest.fixture(scope="module")
def rehearsal_paths():
    """The op paths of the tiny step's lowering (``tools/dump_hlo.py``'s
    shrink of the registered config)."""
    sys.path.insert(0, os.path.join(harness.ROOT, "tests"))
    from test_profiler_names import _lowered_step_text

    text = _lowered_step_text("phi4_mini_flash_pp5")
    return re.findall(r'^#loc\d+ = loc\("([^"]*)"', text, re.M)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_returns_a_finite_number_from_the_rehearsal_trace(
        rehearsal_paths, monkeypatch, name):
    from benchmark.harness import scopes_phi4flash, scopes_ssm, spans

    # one event of 1 ms an op, end to end on one device plane
    events = [("op", i * 1e-3, 1e-3, p)
              for i, p in enumerate(rehearsal_paths)]
    tr = {"host": [], "devices": {"/device:TPU:0": events}}
    monkeypatch.setattr(spans, "window_of", lambda host: None)
    monkeypatch.setattr(spans, "_clip", lambda ev, window: ev)
    phi, ssm = scopes_phi4flash.reduce(tr), scopes_ssm.reduce(tr)
    assert set(phi["layer"]) == {"attn.window", "attn.full", "gmu"}
    assert set(phi["flash"]) == {"attn.flash"}
    assert {"ssm", "ssm.conv", "ssm.scan", "ssm.gate"} <= set(ssm)
    monkeypatch.setattr(scopes_phi4flash, "_of_dir", lambda d: phi)
    monkeypatch.setattr(scopes_ssm, "_of_dir", lambda d: ssm)
    _, _, config = harness.resolve(harness.load_manifest(), CELL)
    with open(os.path.join(harness.HERE, "harness", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    got = harness.load_reader(name)(
        {"config": config, "seq_len": 16384, "tokens_per_step": 16384,
         "trace_dir": "rehearsal", "traced_steps": 4,
         "device": {"peaks": peaks}})
    assert got is not None and math.isfinite(got) and got > 0, name
