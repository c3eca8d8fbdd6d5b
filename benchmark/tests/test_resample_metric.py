"""``train_resample_ms`` (``layer_metrics/train_resample_ms.py``): the
entry, the reader on hand-made events and on the cut of the chip trace
of ``basnet_ds.train_b16`` kept beside this file (PR 25's tree: every
resample there is slice/lerp ops under ``dsod.resample``)."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import spans

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "train_resample_ms"

DEC_R = "jit(step_fn)/jvp(BASNet)/dsod.decoder/_DecoderStage_5/dsod.resample/"
HEAD_RT = "jit(step_fn)/transpose(jvp(BASNet))/dsod.heads/dsod.resample/dot"
ENC = "jit(step_fn)/jvp(BASNet)/dsod.encoder/ConvBNAct_0/conv_general_dilated"


def test_the_entry():
    e = {e["name"]: e for e in bench_run.load_manifest()["per_layer"]}[NAME]
    assert e == {"name": NAME, "unit": "ms", "better": "lower",
                 "source": "device_trace",
                 "layer": "kernels and XLA fusions",
                 "moves": "train_img_per_s_chip",
                 "workloads": ["basnet_ds.train_b16"]}


def test_reads_the_resample_rows_of_every_stage(monkeypatch):
    read = bench_run.load_reader(NAME)
    # No trace, or a trace of a program that names nothing: nothing.
    assert read({"trace_dir": None, "traced_steps": 15}) is None
    dev = [("fusion.1", 0.0, 1.0, ENC),
           ("dsod.kernel.fused_resample.3", 1.0, 0.5,
            DEC_R + "dsod.kernel.fused_resample/pallas_call"),
           ("copy.9", 1.5, 0.25,  # a layout copy that took its user's path
            spans.INHERITED + DEC_R + "dsod.kernel.fused_resample/pallas_call"),
           ("fusion.4", 2.0, 0.25, HEAD_RT)]
    tr = {"devices": {"/device:TPU:0": dev}, "host": []}
    monkeypatch.setattr(spans, "of_run", lambda run: spans.reduce(tr))
    assert read({"trace_dir": "x", "traced_steps": 2}) == pytest.approx(500.0)
    assert read({"trace_dir": "x", "traced_steps": 0}) is None
    unnamed = {"devices": {"/device:TPU:0": [("fusion.1", 0.0, 1.0, "")]},
               "host": []}
    monkeypatch.setattr(spans, "of_run", lambda run: spans.reduce(unnamed))
    assert read({"trace_dir": "x", "traced_steps": 2}) is None


def test_on_the_recorded_chip_trace(monkeypatch):
    path = os.path.join(HERE, "data", "spans_head.json")
    if not os.path.isfile(path):
        pytest.skip("no recorded trace kept")
    with open(path) as f:
        tr = json.load(f)
    tr = {"devices": {k: [tuple(e) for e in v]
                      for k, v in tr["devices"].items()},
          "host": [tuple(h) for h in tr["host"]]}
    red = spans.reduce(tr)
    monkeypatch.setattr(spans, "of_run", lambda run: red)
    got = bench_run.load_reader(NAME)({"trace_dir": "x", "traced_steps": 1})
    rows = {k: s for k, s in red["stage_table_s"].items()
            if k.endswith("/resample")}
    assert got == pytest.approx(1000.0 * sum(rows.values()))
    # The cut is the head of a step: whatever resample ops it holds sit
    # in the decoder or the heads, never in the encoder.
    assert all(k.split("/")[0] in ("decoder", "heads") for k in rows)
    assert 0.0 <= got <= 1000.0 * sum(red["stage_s"].values())
