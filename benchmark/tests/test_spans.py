"""The readers of the program's own names (``harness/spans.py``): the
pure reductions on hand-made events and on the small cut of a chip
trace kept beside this file, the wire-format walk on a hand-made
XSpace, the loader on a trace recorded here on the CPU, and the nine
per-layer entries loading through ``run.py``."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ["train_stage_ms.encoder", "train_stage_ms.decoder",
       "train_stage_ms.heads", "train_stage_ms.loss",
       "train_stage_ms.update", "train_stage_unscoped_share",
       "data_starved_exposed_ms_per_step", "host_loop_exposed_ms_per_step",
       "device_idle_unattributed_share.train"]

ENC = "jit(step_fn)/jvp(BASNet)/dsod.encoder/ConvBNAct_0/conv_general_dilated"
ENC_T = "jit(step_fn)/transpose(jvp(BASNet))/dsod.encoder/ConvBNAct_0/mul"
LOSS = "jit(step_fn)/jvp(dsod.loss)/dsod.kernel.fused_loss/pallas_call"
HEAD_R = "jit(step_fn)/jvp(BASNet)/dsod.heads/dsod.resample/mul"
UPD = "jit(step_fn)/dsod.update/add"
FIT, H2D = "3:python", "5:python"


def hand_made():
    dev = [("fusion.1", 0.0, 1.0, ENC),
           ("while.2", 2.0, 4.0, UPD),            # wraps the next two
           ("fusion.3", 2.5, 1.0, ENC_T),
           ("custom-call.4", 4.0, 1.0, LOSS),
           ("fusion.5", 6.5, 0.5, spans.INHERITED + HEAD_R),
           ("copy.6", 8.0, 1.0, "")]
    host = [(trace.WINDOW_MARK, FIT, 0.0, 10.0, {}),
            ("dsod.data.starved", FIT, 1.0, 0.5, {}),
            ("dsod.train.step", FIT, 1.5, 6.5, {"step_num": 1}),
            ("dsod.train.dispatch", FIT, 1.6, 0.3, {}),
            ("dsod.train.flush", FIT, 6.0, 1.5, {}),
            ("dsod.train.step", FIT, 9.0, 0.8, {"step_num": 2}),
            ("dsod.data.h2d", H2D, 0.0, 9.9, {})]   # another thread
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_stage_self_times_by_outermost_stage():
    tr = hand_made()
    ev = tr["devices"]["/device:TPU:0"]
    st = spans.stage_self_times(ev, (0.0, 10.0))
    assert st == pytest.approx({"encoder": 2.0, "update": 2.0, "loss": 1.0,
                                "heads": 0.5, "unscoped": 1.0})
    busy = trace.reduce_events({"devices": {"d": [e[:3] for e in ev]},
                                "host": [(trace.WINDOW_MARK, 0.0, 10.0)]})
    assert sum(st.values()) == pytest.approx(busy["busy_s"])
    # One level below the stage, and an op by its own path alone.
    sub = spans.stage_self_times(ev, (0.0, 10.0), sub=True)
    assert sub["loss/kernel.fused_loss"] == pytest.approx(1.0)
    assert sub["heads/resample"] == pytest.approx(0.5)
    assert sub["encoder/-"] == pytest.approx(2.0)
    own = spans.stage_self_times(ev, (0.0, 10.0), inherited=False)
    assert own["unscoped"] == pytest.approx(1.5) and "heads" not in own
    # The window clips.
    assert spans.stage_self_times(ev, (0.5, 2.25)) == pytest.approx(
        {"encoder": 0.5, "update": 0.25})


def test_idle_goes_to_the_deepest_span_on_fits_thread():
    tr = hand_made()
    idle = spans.idle_by_span(tr)
    # idle: 1.0-2.0, 6.0-6.5, 7.0-8.0, 9.0-10.0
    assert idle == pytest.approx({
        "dsod.data.starved": 0.5,            # 1.0-1.5
        "dsod.train.step": 0.1 + 0.1 + 0.5 + 0.8,  # 1.5-1.6, 1.9-2.0,
        "dsod.train.dispatch": 0.3,          # 7.5-8.0, 9.0-9.8
        "dsod.train.flush": 0.5 + 0.5,       # 6.0-6.5, 7.0-7.5
        "unattributed": 0.2})                # 9.8-10.0; never the H2D thread
    busy = trace.reduce_events({
        "devices": {"d": [e[:3] for e in tr["devices"]["/device:TPU:0"]]},
        "host": [(trace.WINDOW_MARK, 0.0, 10.0)]})
    assert sum(idle.values()) == pytest.approx(
        busy["window_s"] - busy["busy_s"])
    assert spans.fit_line(tr["host"]) == FIT


def test_reduce_reports_nothing_where_the_program_names_nothing():
    tr = hand_made()
    bare = {"devices": {k: [e[:3] + ("jit(step_fn)/jvp(BASNet)/mul",)
                            for e in v] for k, v in tr["devices"].items()},
            "host": [h for h in tr["host"] if h[0] == trace.WINDOW_MARK]}
    red = spans.reduce(bare)
    assert red["stage_s"] is None and red["idle_s"] is None
    assert spans.reduce({"devices": {}, "host": []})["stage_s"] is None
    red = spans.reduce(tr)
    assert red["unscoped_own_s"] == pytest.approx(1.5)
    assert red["spans"][f"{H2D} dsod.data.h2d"] == [1, pytest.approx(9.9)]


def _msg(*fields):
    out = b""
    for no, v in fields:
        if isinstance(v, int):
            out += bytes([no << 3]) if no < 16 else bytes(
                [((no << 3) & 0x7F) | 0x80, no >> 4])
            while v > 0x7F:
                out += bytes([(v & 0x7F) | 0x80])
                v >>= 7
            out += bytes([v])
        else:
            key = (no << 3) | 2
            out += bytes([key]) if key < 0x80 else bytes(
                [(key & 0x7F) | 0x80, key >> 7])
            n, size = len(v), b""
            while n > 0x7F:
                size += bytes([(n & 0x7F) | 0x80])
                n >>= 7
            out += size + bytes([n]) + v
    return out


def test_op_names_from_a_hand_made_xspace():
    def instr(i, name, path, operands=()):
        f = [(1, name.encode()), (35, i)]
        if path:
            f.append((7, _msg((2, path.encode()))))
        if operands:
            f.append((36, b"".join(_msg((1, o))[1:] for o in operands)))
        return (2, _msg(*f))

    comp = _msg((1, b"main"),
                instr(1, "fusion.1", ENC),
                instr(2, "copy-start.2", "", (1,)),   # -> copy-done -> user
                instr(3, "copy-done.2", "", (2,)),
                instr(4, "fusion.4", UPD, (3,)),
                instr(5, "constant.5", ""))
    proto = _msg((1, _msg((1, b"jit_step_fn"), (3, comp))))
    meta = _msg((1, 7), (2, b"jit_step_fn(123)"),
                (5, _msg((1, 1), (6, proto))))
    plane = _msg((2, b"/host:metadata"), (4, _msg((1, 7), (2, meta))))
    other = _msg((2, b"/device:TPU:0"), (4, _msg((1, 1), (2, _msg((1, 1))))))
    names = spans.hlo_op_names(_msg((1, other), (1, plane)))
    assert names == {"jit_step_fn(123)": {
        "fusion.1": ENC, "fusion.4": UPD, "constant.5": "",
        "copy-done.2": spans.INHERITED + UPD,
        "copy-start.2": spans.INHERITED + UPD}}
    assert spans.stage_of(names["jit_step_fn(123)"]["copy-start.2"]) == "update"
    assert spans.stage_of("jit(f)/jvp(dsod.loss)/dsod.kernel.x/mul") == "loss"
    assert spans.stage_of("jit(f)/mul") == spans.UNSCOPED


def test_recorded_chip_trace():
    """The cut of the chip trace of ``basnet_ds.train_b16`` (PERF.md
    section 5): stages cover the busy time, idle pieces the idle time."""
    path = os.path.join(HERE, "data", "spans_head.json")
    if not os.path.isfile(path):
        pytest.skip("no recorded trace kept")
    with open(path) as f:
        tr = json.load(f)
    tr = {"devices": {k: [tuple(e) for e in v]
                      for k, v in tr["devices"].items()},
          "host": [tuple(h) for h in tr["host"]]}
    red = spans.reduce(tr)
    old = trace.reduce_events({
        "devices": {k: [e[:3] for e in v] for k, v in tr["devices"].items()},
        "host": [(h[0], h[2], h[3]) for h in tr["host"]]})
    assert sum(red["stage_s"].values()) == pytest.approx(old["busy_s"])
    assert sum(red["idle_s"].values()) == pytest.approx(
        old["window_s"] - old["busy_s"])
    assert set(red["stage_s"]) - {spans.UNSCOPED} <= set(spans.STAGES)
    assert red["stage_s"].get(spans.UNSCOPED, 0.0) < 0.05 * old["busy_s"]


def test_load_on_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_MARK):
        with jax.profiler.StepTraceAnnotation("dsod.train.step", step_num=4):
            jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    tr = spans.load(trace.find_xplane(str(tmp_path)))
    step = [h for h in tr["host"] if h[0] == "dsod.train.step"]
    assert len(step) == 1 and step[0][4]["step_num"] == 4
    assert spans.fit_line(tr["host"]) == step[0][1]
    red = spans.reduce(tr)  # a CPU: no device plane, nothing to report
    assert red["stage_s"] is None and red["idle_s"] is None


def test_the_nine_entries_load_and_read(tmp_path, monkeypatch):
    m = bench_run.load_manifest()
    entries = {e["name"]: e for e in m["per_layer"]}
    assert [n for n in NEW if n not in entries] == []
    for n in NEW:
        assert entries[n]["workloads"] == ["basnet_ds.train_b16"]
        assert entries[n]["moves"] == "train_img_per_s_chip"
        assert entries[n]["source"] == "device_trace"
    readers = {n: bench_run.load_reader(n) for n in NEW}
    # No trace (the untraced run, a parent without the names): nothing.
    assert [r({"trace_dir": None, "traced_steps": 15})
            for r in readers.values()] == [None] * 9
    monkeypatch.setattr(spans, "of_run", lambda run: spans.reduce(hand_made()))
    got = {n: r({"trace_dir": "x", "traced_steps": 2})
           for n, r in readers.items()}
    assert got == pytest.approx({
        "train_stage_ms.encoder": 1000.0, "train_stage_ms.decoder": 0.0,
        "train_stage_ms.heads": 250.0, "train_stage_ms.loss": 500.0,
        "train_stage_ms.update": 1000.0,
        "train_stage_unscoped_share": 100.0 * 1.0 / 6.5,
        "data_starved_exposed_ms_per_step": 250.0,
        "host_loop_exposed_ms_per_step": 1400.0,
        "device_idle_unattributed_share.train": 100.0 * 0.2 / 3.5})
