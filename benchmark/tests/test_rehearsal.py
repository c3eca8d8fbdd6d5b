"""CPU rehearsals of both runners at a tiny size: the whole of a run
but the look for a chip.  A rehearsal reports device ``cpu`` and NO
metric (its numbers go under ``rehearsal``).  Three of them break the
timed path underneath and see ``correct`` come out false.  Minutes
each: the CPU compiles the whole step.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal.py
"""

import json
import os
import shutil
import time

import jax
import pytest

from benchmark import run as harness

TINY_TRAIN = ["data.image_size=64,64", "global_batch_size=8",
              "data.synthetic_size=64", "log_every_steps=1",
              "data.num_workers=2", "model.compute_dtype=float32"]
TINY_SERVE = ["data.image_size=64,64", "serve.batch_buckets=1,2",
              "serve.precision=f32", "serve.precision_arms=f32"]
# At float32 compute the program and the reference differ by rounding
# order only (three steps of batch 8 amplify that in the worst leaf).
# The sound rehearsal is held to these; the rehearsals with a planted
# fault are held to the cell's OWN limits, those of the workload file.
TIGHT = {"loss_rel_gap.step1": 1e-4, "grad_norm_worst_leaf_gap": 2e-2,
         "dparam_norm_worst_leaf_gap": 0.25, "dparam_norm_median_leaf_gap": 0.1,
         "dparam_zero_leaf_share": 0.0}
TRAIN = "basnet_ds.train_b16"


def _cpu():
    d = jax.devices()[0]
    if d.platform != "cpu":
        pytest.skip("a rehearsal is for the CPU")
    return {"platform": "cpu", "kind": d.device_kind,
            "count": len(jax.devices())}


def _train(monkeypatch, seed=3000000019, limits=None, overrides=()):
    real_resolve = harness.resolve

    def resolve(*a, **kw):
        # ticks come every step here: open the window past the three
        # steps that are followed, as the cell's own cadence does
        entry, cell, config = real_resolve(*a, **kw)
        cell = dict(cell, warmup_ticks=4)
        return entry, dict(cell, limits=limits) if limits else cell, config

    monkeypatch.setattr(harness, "resolve", resolve)
    return harness.run_cell(
        TRAIN, seed, 8.0, False, device=_cpu(),
        t_start=time.perf_counter(),
        extra_overrides=TINY_TRAIN + list(overrides))


def test_train_rehearsal(monkeypatch):
    line = _train(monkeypatch, limits=TIGHT)
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    assert set(line["rehearsal"]) == {"train_img_per_s_chip", "setup_s"}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    judged = {n for n, _, lim, _ in line["compared"] if lim is not None}
    assert "grad_norm_judged_median_leaf_gap" not in judged  # TIGHT has none
    assert any(n == "grad_norm_judged_median_leaf_gap"
               for n, _, _, _ in line["compared"])


def _break_step(monkeypatch, wrap):
    """Plant a fault under the timed path: ``wrap(step)`` takes the
    compiled step ``fit()`` builds and returns what is driven instead."""
    from distributed_sod_project_tpu.parallel import engine

    build = engine.make_unified_train_step
    monkeypatch.setattr(engine, "make_unified_train_step",
                        lambda *a, **kw: wrap(build(*a, **kw)))


def _failed(line):
    assert line["correct"] is False, line["compared"]
    return {n for n, _, _, ok in line["compared"] if not ok}


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    def wrap(step):
        def broken(state, batch):
            copy = jax.tree_util.tree_map(lambda x: x.copy(), state)
            _, metrics = step(copy, batch)
            return state, metrics
        return broken

    _break_step(monkeypatch, wrap)
    bad = _failed(_train(monkeypatch))
    assert {"dparam_zero_leaf_share", "dparam_norm_median_leaf_gap"} <= bad


def test_train_step_whose_gradient_is_twice_too_large(monkeypatch):
    # AdamW hides a scaled gradient from the parameters' change
    assert _failed(_train(monkeypatch, overrides=[
        "loss.bce=2.0", "loss.iou=2.0", "loss.ssim=2.0"])) == {
            "grad_norm_judged_median_leaf_gap"}


def test_train_step_at_twice_the_learning_rate(monkeypatch):
    assert "dparam_norm_median_leaf_gap" in _failed(
        _train(monkeypatch, overrides=["optim.lr=0.002"]))


SERVE = ["minet_r50_dp.serve_steady"]
# The serving cell's own file, as the PR that proves it on the chip
# would add it; rate and latency limit come from that PR's sweep.
SERVE_CELL = {
    "runner": "serve", "config": "minet_r50_dp", "chips": 1,
    "why": "rehearsal",
    "overrides": ["serve.precision=bf16", "serve.precision_arms=bf16",
                  "serve.trace_sample=0.0"],
    "rate_per_s": 8.0, "limit_ms": 1000.0, "timeout_s": 5.0,
    "warmup_s": 2.0, "trace_s": 3.0, "senders": 64,
    "catalog": 256, "catalog_seed": 7,
    "sizes_hw": [[300, 400], [400, 300], [320, 320], [400, 400]],
    "compare": 16,
    "limits": {"mask_mean_abs_gap": 2.5 / 255, "mask_max_abs_gap": 1.0},
}
SERVE_ENTRIES = {
    "workloads": [{"name": SERVE[0], "config": "minet_r50_dp",
                   "traffic": "serve_steady", "chips": 1, "why": "rehearsal"}],
    "end_to_end": [
        {"name": "serve_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.05, "source": "host_clock", "workloads": SERVE},
        {"name": "serve_ok_img_per_s", "unit": "img/s", "better": "higher",
         "bound": 0.05, "source": "host_clock", "workloads": SERVE}],
    "per_layer": [
        {"name": n, "unit": "ms", "better": "lower", "source": "program_span",
         "layer": "serve", "moves": "serve_p95_ms", "workloads": SERVE}
        for n in ("serve_queue_ms", "serve_device_ms", "serve_host_ms",
                  "loadgen_late_ms")],
}


def _root_with_serve_cell(tmp_path):
    """The serving cell is not in BENCHMARK.json yet (PERF.md, Open
    questions): add it the way a later PR would, a workload file and
    entries, in a temporary root that shares this benchmark's files."""
    m = harness.load_manifest()
    m["configs"] = m["configs"] + [{
        "name": "minet_r50_dp", "source": "rehearsal", "reduced": [],
        "file": "benchmark/configs/minet_r50_dp.json", "why": "rehearsal"}]
    for kind, entries in SERVE_ENTRIES.items():
        m[kind] = m[kind] + entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    (tmp_path / "benchmark").mkdir()
    for d in ("configs", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(harness.HERE, d),
                        tmp_path / "benchmark" / d)
    (tmp_path / "benchmark" / "workloads" / (SERVE[0] + ".json")).write_text(
        json.dumps(SERVE_CELL))
    return str(tmp_path)


def _serve(tmp_path, seed=3000000019, trace=False):
    return harness.run_cell(
        SERVE[0], seed, 3.0, trace, root=_root_with_serve_cell(tmp_path),
        device=_cpu(), t_start=time.perf_counter(),
        extra_overrides=TINY_SERVE)


def test_serve_rehearsal(tmp_path):
    line = _serve(tmp_path)
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    assert set(line["rehearsal"]) == {"serve_p95_ms", "serve_ok_img_per_s",
                                      "setup_s"}
    assert line["attempted"] > 10 and line["failed"] == 0
    assert line["correct"] is True, line["compared"]  # uint8 steps of the answer


def test_serve_answer_altered_where_it_is_produced(monkeypatch, tmp_path):
    from distributed_sod_project_tpu.serve import engine

    real = engine._resize_pred
    monkeypatch.setattr(engine, "_resize_pred",
                        lambda row, hw: real(1.0 - row, hw))
    monkeypatch.setattr(
        harness, "resolve", lambda *a, _r=harness.resolve, **kw: (
            lambda e, c, f: (e, dict(c, limits={"mask_mean_abs_gap": 0.01,
                                                "mask_max_abs_gap": 1.0}), f)
        )(*_r(*a, **kw)))
    line = _serve(tmp_path)
    assert line["correct"] is False
