"""The trace reduction, on hand-made events, on the small recorded
chip trace kept beside this file, and (the loader) on a trace recorded
here on the CPU."""

import json
import os

import pytest

from benchmark.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def test_reduce_busy_idle_selftime_and_gaps():
    dev = [("fusion.1", 0.0, 1.0), ("while", 2.0, 4.0),
           ("conv.2", 2.5, 1.0), ("all-reduce.3", 4.0, 1.0),
           ("fusion.4", 8.0, 1.0)]
    host = [("benchmark.window", 0.0, 10.0),
            ("benchmark.fit_hook_tick", 6.2, 1.5)]
    r = trace.reduce_events({"devices": {"/device:TPU:0": dev},
                             "host": host})
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(1.0 + 4.0 + 1.0)
    ops = dict(r["device_ops"])
    assert ops["while"] == pytest.approx(4.0 - 1.0 - 1.0)  # less children
    assert ops["conv.2"] == pytest.approx(1.0)
    assert r["collective_s"] == pytest.approx(1.0)
    assert r["collective_exposed_s"] == pytest.approx(0.0)  # under while
    assert r["idle_gaps"][0] == ["benchmark.fit_hook_tick",
                                 pytest.approx(2.0)]
    assert r["idle_gaps"][1][0] == "unattributed"


def test_no_device_op_is_none():
    assert trace.reduce_events({"devices": {}, "host": []}) is None


def test_recorded_chip_trace():
    path = os.path.join(HERE, "data", "trace_head.json")
    if not os.path.isfile(path):
        pytest.skip("no recorded trace kept")
    with open(path) as f:
        tr = json.load(f)
    r = trace.reduce_events(tr)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert len(r["device_ops"]) == 10
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] * 1.0001


def test_load_finds_the_benchmarks_annotations(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_MARK):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path)))
    assert any(n == trace.WINDOW_MARK for n, _, _ in tr["host"])
    assert trace.reduce_events(tr) is None  # a CPU: no device plane
