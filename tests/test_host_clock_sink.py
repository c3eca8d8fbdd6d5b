"""The host-clock sink of ``utils/tracing.py::span`` and its readers
(docs/OBSERVABILITY.md "Host-clock sink", PERF.md section 3):

- a span reads ONE pair of clock values, profiler session or not, adds
  them to its thread's per-name total and hands the same pair to a
  sampled chunk's ring; ``dsod.setup.*`` intervals land in a bounded
  list;
- a tiny ``fit()`` leaves the four sibling set-up spans in order,
  touching, and logs one ``setup:`` line; a planted slow interval logs
  exactly one ``stall:`` line that names where the time went, a steady
  run none;
- ``CompileStats`` is one listener a process;
- the seven ``setup_*`` readers of the benchmark add up on a synthetic
  run and say nothing of a run without ticks, whatever an earlier
  ``fit()`` left in the sink.
"""

import logging
import threading

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as harness
from distributed_sod_project_tpu.configs import (DataConfig, MeshConfig,
                                                 ModelConfig, OptimConfig,
                                                 get_config)
from distributed_sod_project_tpu.train import loop
from distributed_sod_project_tpu.utils import platform as plat
from distributed_sod_project_tpu.utils import tracing

SETUP_METRICS = ("setup_before_fit_s", "setup_build_s", "setup_first_step_s",
                 "setup_warmup_s", "setup_unattributed_s",
                 "setup_trace_lower_s", "setup_compile_s")
SIBLINGS = ["dsod.setup.before_fit", "dsod.setup.build",
            "dsod.setup.first_step", "dsod.setup.warmup"]


class FakeClock:
    """Advances by ``tick`` at every read; ``sleep`` adds more."""

    def __init__(self, tick=1.0):
        self.t, self.tick, self.reads = 0.0, tick, 0

    def __call__(self):
        self.reads += 1
        self.t += self.tick
        return self.t

    def sleep(self, s):
        self.t += s


@pytest.fixture
def clock(monkeypatch):
    fake = FakeClock()
    monkeypatch.setattr(tracing, "_clock", fake)
    return fake


def _delta(before):
    return {n: s - before.get(n, 0.0)
            for n, s in tracing.span_totals().items()
            if s != before.get(n, 0.0)}


def _tiny_cfg(tmp_path, **kw):
    return get_config("minet_vgg16_ref").replace(
        data=DataConfig(dataset="synthetic", image_size=(32, 32),
                        synthetic_size=32, num_workers=0),
        model=ModelConfig(name="vit_sod", backbone="tiny", sync_bn=False,
                          compute_dtype="float32"),
        optim=OptimConfig(lr=0.01), mesh=MeshConfig(data=-1),
        global_batch_size=8, log_every_steps=1, tensorboard=False,
        checkpoint_dir=str(tmp_path / "ck"), **kw)


@pytest.fixture
def dsod_log(caplog):
    """The program's logger does not propagate: hand it caplog's handler."""
    logger = logging.getLogger("dsod")
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="dsod"):
            yield caplog
    finally:
        logger.removeHandler(caplog.handler)


def _lines(caplog, prefix):
    return [r.getMessage() for r in caplog.records
            if r.getMessage().startswith(prefix)]


# -- the sink ------------------------------------------------------------


def test_totals_are_per_name_and_a_nested_span_counts_in_both(clock):
    before = tracing.span_totals()
    with tracing.span("dsod.test.outer"):          # reads 1 .. 6
        with tracing.span("dsod.test.inner"):      # reads 2, 3
            pass
        with tracing.span("dsod.test.inner", step=3):  # reads 4, 5
            pass
    assert _delta(before) == {"dsod.test.outer": 5.0, "dsod.test.inner": 2.0}
    assert clock.reads == 6  # two a span, no profiler session running


def test_totals_merge_threads_and_outlive_them(clock):
    before = tracing.span_totals()

    def work():
        with tracing.span("dsod.test.thread"):
            pass

    threads = [threading.Thread(target=work) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with tracing.span("dsod.test.thread"):
        pass
    assert _delta(before) == {"dsod.test.thread": 4.0}
    # the ended threads were folded away, and nothing was counted twice
    assert all(t.is_alive() for t, _ in tracing._threads)
    assert _delta(before) == {"dsod.test.thread": 4.0}


def test_no_update_is_lost_while_a_reader_merges_and_folds(monkeypatch):
    """More threads than cores come and go, each adding spans of
    exactly one second on a clock of its own, while this thread keeps
    merging (and folding away the ended ones): the total is exact."""
    import itertools
    import sys

    local = threading.local()

    def per_thread_clock():
        if not hasattr(local, "count"):
            local.count = itertools.count()
        return float(next(local.count))

    monkeypatch.setattr(tracing, "_clock", per_thread_clock)
    before = tracing.span_totals()
    n_threads, n_spans = 32, 400

    def work():
        for _ in range(n_spans):
            with tracing.span("dsod.test.stress"):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads[:16]:
            t.start()
        seen = 0.0
        for t in threads[16:]:
            t.start()
            got = _delta(before).get("dsod.test.stress", 0.0)
            assert got >= seen  # never backwards: nothing counted twice
            seen = got
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert _delta(before) == {"dsod.test.stress": float(n_threads * n_spans)}


def test_a_sampled_chunk_gets_the_same_two_timestamps(clock):
    """One pair of reads serves the sink, the span's ``t0`` / ``t1``
    and the ring (``fit()`` builds its Tracer on the sink's clock)."""
    tr = tracing.Tracer(sample=1.0, clock=tracing.now)
    root = tr.begin("chunk", tracing.mint_trace_id(), root=True)
    before, reads = tracing.span_totals(), clock.reads
    with tracing.span("dsod.train.log", root, step=4) as s:
        pass
    assert clock.reads == reads + 2
    assert (s.t0, s.t1) == (clock.t - 1.0, clock.t)
    assert _delta(before) == {"dsod.train.log": 1.0}
    root.end()
    (trace,) = tr.snapshot()["traces"]
    (log,) = [x for x in trace["spans"] if x["name"] == "dsod.train.log"]
    assert log["dur_ms"] == 1000.0 and log["attrs"] == {"step": 4}
    assert log["t0_unix"] == pytest.approx(s.t0 + tr._wall0)


def test_the_setup_list_is_bounded_and_keeps_the_first(clock):
    tracing.reset_setup()
    with tracing.span("dsod.setup.build"):
        pass
    tracing.record_setup("dsod.setup.build.model", 1.0, 2.0,
                         "dsod.setup.build")
    with tracing.span("dsod.train.step"):  # not a set-up name
        pass
    for i in range(2 * tracing.MAX_SETUP_SPANS):
        tracing.record_setup("dsod.setup.warmup", 2.0, 3.0 + i)
    kept = tracing.setup_spans()
    assert len(kept) == tracing.MAX_SETUP_SPANS
    assert kept[:3] == [("dsod.setup.build", 1.0, 2.0, None),
                        ("dsod.setup.build.model", 1.0, 2.0,
                         "dsod.setup.build"),
                        ("dsod.setup.warmup", 2.0, 3.0, None)]
    tracing.reset_setup()
    assert tracing.setup_spans() == []


# -- CompileStats --------------------------------------------------------


def test_compile_stats_is_one_listener_however_many_ask():
    a = plat.CompileStats()
    mark = a.mark()
    t0 = tracing.now()

    def once_for_the_listener_test(x):
        return x * 5.0 - 2.0

    jax.jit(once_for_the_listener_test)(jnp.ones((3, 11)))
    b = plat.CompileStats()
    assert b is a and plat.CompileStats() is a
    mine = {k: v for k, v in a.by_name.items()
            if "once_for_the_listener_test" in k[1]}
    assert sorted(k[0] for k in mine) == ["compile", "lower", "trace"]
    assert {n for n, _ in mine.values()} == {1}  # three asked, one listens
    made = a.since(mark)
    assert made["trace"] > 0 and made["lower"] > 0 and made["compile"] > 0
    assert any("once_for_the_listener_test" in name and n == 1
               for name, _s, n in made["largest"]["trace"])
    t1 = tracing.now()
    kept = [e for e in a.between(t0, t1)
            if "once_for_the_listener_test" in e[3]]
    assert sorted(e[0] for e in kept) == ["compile", "lower", "trace"]
    assert a.between(t1, t1 + 1.0) == []


def test_compile_stats_counts_a_nested_trace_once():
    """A jitted function traced inside another's trace reports its own
    event: by name each keeps all its seconds, by kind they add up to
    wall time."""
    import time

    stats = plat.CompileStats()

    @jax.jit
    def nested_for_the_listener_test(x):
        time.sleep(0.1)  # runs while tracing
        return x + 1.0

    def nesting_for_the_listener_test(x):
        time.sleep(0.2)
        return nested_for_the_listener_test(x) * 2.0

    mark, t0 = stats.mark(), time.perf_counter()
    jax.jit(nesting_for_the_listener_test)(jnp.ones((5, 3)))
    wall = time.perf_counter() - t0
    made = stats.since(mark)
    by_name = {name: s for name, s, _n in made["largest"]["trace"]}
    assert by_name["nested_for_the_listener_test"] >= 0.1
    assert by_name["nesting_for_the_listener_test"] >= 0.3  # all in
    assert 0.3 <= made["trace"] < 0.39  # the inner 0.1 s once, not twice
    assert made["trace"] + made["lower"] + made["compile"] <= wall


def test_compile_stats_answers_from_the_first_checkpoint_at_or_after(
        monkeypatch):
    import collections

    stats = plat.CompileStats()
    monkeypatch.setattr(stats, "checkpoints", collections.deque(maxlen=64))
    monkeypatch.setattr(stats, "seconds",
                        {"trace": 1.0, "lower": 0.5, "compile": 0.0})
    assert stats.before(0.0) is None  # none kept: nothing to say
    stats.checkpoint(10.0)
    stats.seconds["trace"] = 3.0
    stats.checkpoint(12.0)
    stats.seconds["compile"] = 9.0  # after the last instant kept
    assert stats.before(9.5) == {"trace": 1.0, "lower": 0.5, "compile": 0.0}
    assert stats.before(10.5) == {"trace": 3.0, "lower": 0.5, "compile": 0.0}
    assert stats.before(12.5) is None


# -- fit() ---------------------------------------------------------------


def test_fit_leaves_four_sibling_setup_spans_that_touch(tmp_path, dsod_log):
    returned = []

    def on_metrics(step, host):
        returned.append(tracing.now())

    t_entry = tracing.now()
    out = loop.fit(_tiny_cfg(tmp_path), max_steps=4,
                   hooks={"on_metrics": on_metrics})
    assert out["final_step"] == 4 and len(returned) == 4
    spans = tracing.setup_spans()
    top = [s for s in spans if s[3] is None]
    assert [s[0] for s in top] == SIBLINGS[:3] + [SIBLINGS[3]] * 4
    before_fit, build, first, warm1, warm2 = top[:5]
    assert before_fit[2] >= t_entry and build[1] - before_fit[2] < 0.01
    for a, b in zip(top[:3], top[1:4]):  # in order, not overlapping
        assert a[1] <= a[2] <= b[1] + 1e-9
    # every candidate warm-up starts where the first step ended; the
    # second ends just after the hook's second call read its clock
    assert {s[1] for s in top[3:]} == {first[2]}
    assert 0 <= warm2[2] - returned[1] < 0.05
    covered = (build[2] - build[1]) + (first[2] - first[1]) \
        + (returned[1] - first[2])
    assert covered >= 0.98 * (returned[1] - build[1])
    # build's laps are siblings that touch: they add up to it
    laps = [s for s in spans if s[3] == "dsod.setup.build"]
    assert {"dsod.setup.build.loader_first_batch",
            "dsod.setup.build.state_init"} <= {s[0] for s in laps}
    assert sum(s[2] - s[1] for s in laps) == pytest.approx(
        build[2] - build[1], abs=0.01)
    # the run ended before the eighth boundary: the line comes at its end
    (line,) = _lines(dsod_log, "setup: before_fit")
    assert "| build " in line and "state_init" in line
    assert "| first_step " in line and "| warmup " in line \
        and "(4 ticks)" in line
    assert "| trace " in line and " lower " in line and " compile " in line
    (largest,) = _lines(dsod_log, "setup: largest")
    assert "step_fn" in largest and " x1" in largest  # traced once
    # nothing stays registered with the garbage collector
    assert not [c for c in loop.gc.callbacks
                if isinstance(getattr(c, "__self__", None), loop._HostWatch)]


@pytest.mark.parametrize("planted", [True, False])
def test_a_planted_slow_interval_logs_one_stall_line_a_steady_run_none(
        tmp_path, dsod_log, clock, planted):
    """On a fake clock (a millisecond a read) every interval is the
    hook's own 0.2 s; the planted one is 2 s longer."""
    clock.tick = 0.001

    def on_metrics(step, host):
        clock.sleep(2.2 if planted and step == 13 else 0.2)

    loop.fit(_tiny_cfg(tmp_path), max_steps=16,
             hooks={"on_metrics": on_metrics})
    stalls = _lines(dsod_log, "stall:")
    assert len(_lines(dsod_log, "setup: before_fit")) == 1
    if not planted:
        assert stalls == []
        return
    (line,) = stalls
    assert line.startswith("stall: steps 13-13 wall 2.2")
    assert "median 0.2" in line and "of 4 intervals" in line
    spent = dict(zip(*[iter(line.split(" | ")[1].split())] * 2))
    assert float(spent["dsod.train.log"]) == pytest.approx(2.2, abs=0.05)
    assert "cpu process" in line and "gc " in line and "compiles none" in line


# -- the benchmark's readers ----------------------------------------------


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_setup_reader_says_nothing_of_a_run_without_ticks(tmp_path, name):
    """... whatever an earlier fit() of this process left in the sink
    (the yardstick's own test reads every reader on such a run)."""
    if not tracing.setup_spans():
        loop.fit(_tiny_cfg(tmp_path), max_steps=2)
    assert len(tracing.setup_spans()) >= 4
    read = harness.load_reader(name)
    assert read({"ticks": [], "trace_dir": None, "trace": None}) is None
    # ticks of another run (before this sink's first step): nothing
    assert read({"ticks": [{"t": 0.0}]}) is None


def test_the_seven_setup_readers_add_up_on_a_synthetic_run(monkeypatch):
    tracing.reset_setup()
    tracing.record_setup("dsod.setup.before_fit", 100.0, 108.0)
    tracing.record_setup("dsod.setup.build", 108.0, 119.0)
    tracing.record_setup("dsod.setup.build.state_init", 110.0, 116.0,
                         "dsod.setup.build")
    tracing.record_setup("dsod.setup.first_step", 119.25, 167.25)
    for t in (168.0, 169.5, 171.0):  # the hook's first three returns
        tracing.record_setup("dsod.setup.warmup", 167.25, t)
    # the listener's counters as they stood when each hook returned
    import collections

    stats = plat.CompileStats()
    monkeypatch.setattr(stats, "checkpoints", collections.deque([
        (168.0, {"trace": 20.5, "lower": 21.0, "compile": 8.0}),
        (169.5, {"trace": 20.5, "lower": 21.0, "compile": 8.5}),
        (171.0, {"trace": 20.5, "lower": 21.0, "compile": 8.5})]))
    # the window opened at the hook's second call, which read its clock
    # 0.1 s before it returned; T_START was 0.1 s before the import
    run = {"ticks": [{"t": 169.4}, {"t": 170.9}], "trace": None}
    got = {n: harness.load_reader(n)(run) for n in SETUP_METRICS}
    assert got == pytest.approx({
        "setup_before_fit_s": 8.0, "setup_build_s": 11.0,
        "setup_first_step_s": 48.0, "setup_warmup_s": 2.15,
        "setup_unattributed_s": 0.25, "setup_trace_lower_s": 41.5,
        "setup_compile_s": 8.5})
    assert sum(got[n] for n in SETUP_METRICS[:5]) == pytest.approx(
        169.4 - 100.0)
    assert got["setup_trace_lower_s"] + got["setup_compile_s"] <= (
        got["setup_build_s"] + got["setup_first_step_s"]
        + got["setup_warmup_s"] + 0.5)
    # a window that opens past the boundaries the program keeps: nothing
    late = {"ticks": [{"t": 172.0}]}
    assert all(harness.load_reader(n)(late) is None for n in SETUP_METRICS)
    tracing.reset_setup()


def test_the_manifest_lists_the_seven_for_the_two_cells_only():
    manifest = harness.load_manifest()
    rows = {m["name"]: m for m in manifest["per_layer"]}
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] not in SETUP_METRICS}
    # (relative order and membership: a later PR appends its own metrics
    # after them and its own cells to their lists, as PR 41 did)
    assert [m["name"] for m in manifest["per_layer"]
            if m["name"] in SETUP_METRICS] == list(SETUP_METRICS)
    for name in SETUP_METRICS:
        m = rows[name]
        assert m["workloads"][:2] == [
            "basnet_ds.train_b16", "granite_4_0_h_micro_pp4.train_s16k_b1"]
        assert not {"lfm2_8b_a1b_ep4.train_s8k_b4",
                    "kimi_vl_a3b_ep8.train_s16k_b2"} & set(m["workloads"])
        assert (m["unit"], m["better"], m["moves"]) == ("s", "lower",
                                                        "setup_s")
        assert m["layer"] in layers  # no new layer string
        assert m["source"] == ("program_counter" if name in (
            "setup_trace_lower_s", "setup_compile_s") else "program_span")
