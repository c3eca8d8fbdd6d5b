"""The ``dsod.*`` vocabulary (docs/OBSERVABILITY.md, PERF.md section 3):
named scopes on the device ops of the train step, profiler annotations
on the host loop and the data plane.

- a tiny ``fit()`` under ``jax.profiler`` writes every ``dsod.train.*``
  and ``dsod.data.*`` span into the one ``.xplane.pb``, the step span
  carries ``step_num``, there is one ``dsod.train.log`` per log
  boundary, and the ``dsod.data.starved`` spans add up to the
  ``data_starved_ms`` counter (one timed region feeds both);
- every registered config's lowered step carries the stage scopes, no
  op sits in two stages, and no convolution is outside a stage;
- sampled chunk or not, the span helper reads the host clock twice
  (tests/test_host_clock_sink.py has the sink's own tests).
"""

import collections
import glob
import os
import re
import threading

import jax
import pytest

from distributed_sod_project_tpu.configs import (DataConfig, MeshConfig,
                                                 ModelConfig, OptimConfig,
                                                 apply_overrides,
                                                 get_config)
from distributed_sod_project_tpu.configs.base import list_configs
from distributed_sod_project_tpu.utils import observability, tracing

TRAIN_SPANS = {"dsod.train.step", "dsod.train.dispatch", "dsod.train.flush",
               "dsod.train.log", "dsod.train.ckpt", "dsod.train.eval"}
DATA_SPANS = {"dsod.data.starved", "dsod.data.h2d", "dsod.data.prefetch_full",
              "dsod.data.build", "dsod.data.build_wait",
              "dsod.data.ring_wait", "dsod.data.chunk_assemble"}
STAGES = ("encoder", "decoder", "heads", "loss", "update")
_STAGE = re.compile(r"dsod\.(%s)\b" % "|".join(STAGES))


def _host_spans(trace_dir):
    """[(line index, name, start_ns, duration_ns, stats)] of the
    ``dsod.*`` events in the profile under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("dsod."):
                    out.append((i, ev.name, ev.start_ns, ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_fit_under_profiler_names_loop_and_data_plane(tmp_path, monkeypatch):
    from distributed_sod_project_tpu.data.pipeline import BatchRing
    from distributed_sod_project_tpu.train.loop import fit

    made = []

    class Stats(observability.PipelineStats):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    monkeypatch.setattr(observability, "PipelineStats", Stats)
    cfg = get_config("minet_vgg16_ref").replace(
        data=DataConfig(dataset="synthetic", image_size=(32, 32),
                        synthetic_size=32, num_workers=2),
        model=ModelConfig(name="vit_sod", backbone="tiny", sync_bn=False,
                          compute_dtype="float32"),
        optim=OptimConfig(lr=0.01), mesh=MeshConfig(data=-1),
        global_batch_size=8, num_epochs=3, log_every_steps=2,
        checkpoint_every_steps=4, eval_every_steps=4, tensorboard=False,
        checkpoint_dir=str(tmp_path / "ck"), steps_per_dispatch=2)
    logged = []
    tdir = str(tmp_path / "trace")
    jax.profiler.start_trace(tdir)
    try:
        out = fit(cfg, max_steps=12,
                  hooks={"on_metrics": lambda step, host: logged.append(step)})
        # The one wait the tiny fit may never hit: a builder blocked on
        # a ring with no free slot, until another thread releases one.
        ring = BatchRing(1, {"x": ((1,), "float32")}, stats=made[0])
        slot = ring.acquire()
        threading.Timer(0.05, ring.release, (slot,)).start()
        ring.acquire()
    finally:
        jax.profiler.stop_trace()
    assert out["final_step"] == 12 and logged == [2, 4, 6, 8, 10, 12]

    spans = _host_spans(tdir)
    names = {s[1] for s in spans}
    assert TRAIN_SPANS | DATA_SPANS <= names, (TRAIN_SPANS | DATA_SPANS) - names
    steps = [s for s in spans if s[1] == "dsod.train.step"]
    assert sorted(s[4]["step_num"] for s in steps) == [1, 3, 5, 7, 9, 11]
    assert all(s[4]["steps"] == 2 for s in steps)
    assert len({s[0] for s in steps}) == 1  # one thread: fit()'s
    fit_line = steps[0][0]
    by_name = collections.Counter(s[1] for s in spans)
    assert by_name["dsod.train.log"] == len(logged)
    assert by_name["dsod.train.ckpt"] == 3 and by_name["dsod.train.eval"] == 3
    assert by_name["dsod.train.dispatch"] == 6
    logs = [s for s in spans if s[1] == "dsod.train.log"]
    assert sorted(s[4]["step"] for s in logs) == logged
    # The loop's own spans and its wait for a batch share fit()'s
    # thread; the H2D stage and the builders run on others.
    on_fit = {s[1] for s in spans if s[0] == fit_line}
    assert TRAIN_SPANS | {"dsod.data.starved"} <= on_fit
    assert not on_fit & {"dsod.data.h2d", "dsod.data.build"}
    # One timed region feeds the counter and the span.
    starved_ms = sum(s[3] for s in spans
                     if s[1] == "dsod.data.starved") * 1e-6
    counted = made[0].snapshot()["data_starved_ms"]
    assert starved_ms == pytest.approx(counted, rel=0.05, abs=1.0)
    assert by_name["dsod.data.build"] >= 12
    assert {s[4]["batch"] for s in spans if s[1] == "dsod.data.build"} \
        >= set(range(4))


# A token batch, no convolution: config -> the widths it is traced at.
_TINY_LM = ["model.lm.vocab=512", "model.lm.hidden=64", "model.lm.heads=4",
            "model.lm.dense_width=96", "model.lm.expert_width=48"]
_TOKEN_MODELS = {
    "lfm2_8b_a1b_ep4": _TINY_LM + ["model.lm.kv_heads=2",
                                   "model.lm.head_dim=16"],
    "kimi_vl_a3b_ep8": _TINY_LM + [
        "model.lm.head_dim=24", "model.lm.rope_dim=8", "model.lm.v_dim=16",
        "model.lm.kv_rank=32"],
    "granite_4_0_h_micro_pp4": _TINY_LM + [
        "model.lm.kv_heads=2", "model.lm.head_dim=16", "model.lm.ssm_heads=8",
        "model.lm.ssm_head_dim=16", "model.lm.ssm_state=16",
        "model.lm.ssm_chunk=64"],
    "ouro_2_6b_pp6": _TINY_LM + ["model.lm.kv_heads=4",
                                 "model.lm.head_dim=16"],
    "nemotron_3_super_tp8_ep64": _TINY_LM + [
        "model.lm.kv_heads=1", "model.lm.head_dim=16",
        "model.lm.latent_width=32", "model.lm.shared_width=96",
        "model.lm.experts=16", "model.lm.experts_held=4", "model.lm.top_k=3",
        "model.lm.ssm_heads=8", "model.lm.ssm_head_dim=16",
        "model.lm.ssm_state=16", "model.lm.ssm_chunk=64"],
    "phi4_mini_flash_pp5": _TINY_LM + [
        "model.lm.kv_heads=2", "model.lm.head_dim=16",
        "model.lm.ssm_heads=128", "model.lm.ssm_state=16",
        "model.lm.ssm_chunk=64", "model.lm.ssm_dt_rank=4",
        "model.lm.window=24"]}


def _lowered_step_text(name: str, size: int = 64) -> str:
    """``lower().as_text(debug_info=True)`` of the config's train step
    on abstract state at a tiny size: traced, never compiled."""
    from distributed_sod_project_tpu.models import build_model, kind_of
    from distributed_sod_project_tpu.parallel import make_mesh
    from distributed_sod_project_tpu.parallel.engine import \
        make_unified_train_step
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    cfg = apply_overrides(get_config(name), [
        "global_batch_size=2", f"data.image_size={size},{size}",
        "data.seq_len=256", "mesh.data=1", "mesh.model=1", "mesh.seq=1"]
        + _TOKEN_MODELS.get(name, []))
    mesh = make_mesh(cfg.mesh, jax.devices()[:1])
    model = build_model(cfg.model)
    tx, sched = build_optimizer(cfg.optim, 100)
    batch = kind_of(model).zero_batch(cfg, 2)
    state = jax.eval_shape(
        lambda: create_train_state(jax.random.key(0), model, tx, batch))
    step = make_unified_train_step(model, cfg.loss, tx, mesh, preset="dp",
                                   schedule=sched, donate=False)
    return step.lower(state, batch).as_text(debug_info=True)


@pytest.mark.parametrize("name", list_configs())
def test_lowered_step_carries_stage_scopes(name):
    text = _lowered_step_text(name)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    stages_of = {k: set(_STAGE.findall(v)) for k, v in locs.items()}
    seen = set().union(*stages_of.values())
    token_model = (get_config(name).model.name == "vit_sod"
                   or name in _TOKEN_MODELS)
    want = set(STAGES) - ({"decoder"} if token_model else set())
    assert seen == want
    # Sibling scopes: no op path names two different stages.
    assert [locs[k] for k, v in stages_of.items() if len(v) > 1] == []
    if name in _TOKEN_MODELS:
        # No convolution to place; every matrix product belongs to a stage.
        dots = [ln for ln in text.splitlines()
                if "stablehlo.dot_general" in ln]
        assert dots and not [ln[-160:] for ln in dots if not stages_of.get(
            re.search(r"loc\((#loc\d+)\)\s*$", ln).group(1))]
        return
    # Every convolution, forward and backward, belongs to a stage.
    convs = [ln for ln in text.splitlines() if "stablehlo.convolution" in ln]
    assert convs
    bare = [ln[-160:] for ln in convs
            if not stages_of.get(re.search(r"loc\((#loc\d+)\)\s*$",
                                           ln).group(1))]
    assert bare == []
    if not token_model:
        assert any("dsod.resample" in v for v in locs.values())


def test_fused_kernels_sit_under_kernel_scopes():
    """The Pallas call sites name themselves one level below the stage."""
    text = _lowered_step_text("basnet_ds")  # fused hybrid loss by default
    locs = re.findall(r'^#loc\d+ = loc\("([^"]*)"', text, re.M)
    for kernel in ("fused_loss", "fused_ssim"):
        under = [v for v in locs if f"dsod.kernel.{kernel}" in v]
        assert under, kernel
        assert all(set(_STAGE.findall(v)) == {"loss"} for v in under), kernel


def test_span_reads_the_clock_twice_sampled_or_not(monkeypatch):
    """trace_sample=0 and no profiler session: the helper still reads
    the host clock, ONCE on entry and ONCE on exit (the host-clock sink
    is always on), and makes no Tracer call; a sampled chunk's ring gets
    the same two values, not two more reads."""
    reads = []
    tr = tracing.Tracer(sample=0.0, clock=lambda: reads.append(1) or 0.0)
    reads.clear()  # the constructor anchors its clock to wall time once
    assert tr.begin("chunk", tracing.mint_trace_id(), root=True) is None
    monkeypatch.setattr(tracing, "_clock",
                        lambda: reads.append(1) or float(len(reads)))
    with tracing.span("dsod.train.dispatch", None):
        with tracing.span("dsod.train.step", None, step_num=3, steps=1):
            pass
    assert len(reads) == 4
    assert tr.snapshot()["held"] == 0
    # Sampled: the same call records the interval under the same name,
    # parented to the chunk's root, from the same pair of reads.
    tr = tracing.Tracer(sample=1.0, clock=tracing.now)
    root = tr.begin("chunk", tracing.mint_trace_id(), root=True)
    reads.clear()
    with tracing.span("dsod.train.log", root, step=4):
        pass
    assert len(reads) == 2
    root.end()
    (trace,) = tr.snapshot()["traces"]
    log = [s for s in trace["spans"] if s["name"] == "dsod.train.log"]
    assert log and log[0]["attrs"] == {"step": 4}
    assert log[0]["dur_ms"] == 1000.0


def test_pipeline_stats_timed_is_counter_and_span_in_one():
    stats = observability.PipelineStats(keep_spans=True)
    with stats.timed("data_h2d_ms"):
        pass
    with stats.timed("data_build_ms", batch=7):
        pass
    snap = stats.snapshot()
    assert set(snap) == {"data_h2d_ms", "data_build_ms"}
    kept = stats.drain_spans()
    assert [(n, a) for n, _, _, a in kept] == [
        ("dsod.data.h2d", {}), ("dsod.data.build", {"batch": 7})]
    for (_, t0, t1, _), key in zip(kept, ("data_h2d_ms", "data_build_ms")):
        assert (t1 - t0) * 1000.0 == pytest.approx(snap[key])
    assert stats.drain_spans() == []
    assert observability.PipelineStats().drain_spans() == []  # keeps none
