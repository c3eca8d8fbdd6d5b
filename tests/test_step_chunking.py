"""Device-side step chunking (``train.steps_per_dispatch=k``): k train
steps folded into one ``lax.scan`` dispatch (ISSUE 4).

The k-equivalence contract, asserted in two layers:

- **Bitwise**: ``scan(k)`` equals k sequential dispatches of
  ``scan(1)`` — final state AND per-step metric streams, f32, for all
  three step builders (DP shard_map, GSPMD TP, SP), including
  ``optim.accum_steps>1``, the ``skip_nonfinite`` failure-counter
  carry across a NaN mid-chunk batch, and the EMA blend.  This proves
  the chunking transform itself (batch stacking/slicing, carry
  threading, per-step RNG fold on ``state.step``) adds exactly
  nothing.
- **Tolerance + exact counters** vs the plain (no-scan) k=1 program:
  XLA:CPU canonicalizes convolution kernel-gradients differently
  inside while-loop bodies than at entry (measured: the scan body
  keeps ``dim_labels=f01b_i01o->01bf`` where the entry program is
  rewritten to transposed ``b01f`` form — a different reduction loop
  order, hence last-ulp f32 accumulation drift; the same program
  re-dispatched is run-to-run deterministic).  So plain-vs-scan is
  gated at tight f32 tolerance, with the semantic streams — lr
  schedule reads, ``notfinite_count``, ``state.step`` — exact.

Loop-level: fit(k) equivalence, cadence/divisibility validation,
chunk-boundary resume, DSOD_FAULTS forcing k=1, and the
one-``device_get``-per-chunk steady-state sync contract.
"""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_sod_project_tpu.configs.base import (
    DataConfig, LossConfig, MeshConfig, ModelConfig, OptimConfig,
    validate_steps_per_dispatch)
from distributed_sod_project_tpu.configs import get_config
from distributed_sod_project_tpu.models.layers import ConvBNAct
from distributed_sod_project_tpu.parallel import make_mesh
from distributed_sod_project_tpu.parallel.mesh import (
    batch_sharding, global_batch_array, replicated_sharding)
from distributed_sod_project_tpu.parallel.engine import (
    make_unified_train_step)
from distributed_sod_project_tpu.train import (
    build_optimizer, create_train_state)


class TinyNet(nn.Module):
    """Conv+SyncBN micro-model with the zoo call convention (the same
    harness as test_train.py) — small enough that every (k, variant)
    program compiles in seconds."""

    axis_name: str = "data"

    @nn.compact
    def __call__(self, image, depth=None, *, train: bool = False):
        del depth
        x = ConvBNAct(8, axis_name=self.axis_name)(image, train)
        logit = nn.Conv(1, (3, 3), padding="SAME")(x)
        return [logit.astype(jnp.float32)]


def _batch(n=8, hw=16, seed=0):
    rng = np.random.default_rng(seed)
    img = rng.normal(size=(n, hw, hw, 3)).astype(np.float32)
    mask = (img.mean(-1, keepdims=True) > 0).astype(np.float32)
    return {"image": img, "mask": mask}


def _stack(batches):
    return {k: np.stack([b[k] for b in batches]) for k in batches[0]}


def _leaves(tree):
    return [(jax.tree_util.keystr(p), np.asarray(v)) for p, v in
            jax.tree_util.tree_leaves_with_path(jax.device_get(tree))]


def assert_trees_bitwise(a, b, context=""):
    for (pa, xa), (pb, xb) in zip(_leaves(a), _leaves(b)):
        if np.issubdtype(xa.dtype, np.floating):
            ok = np.array_equal(xa, xb, equal_nan=True)
        else:
            ok = np.array_equal(xa, xb)
        assert ok, f"{context}: leaf {pa} not bitwise equal"


def assert_trees_close(a, b, atol, context=""):
    for (pa, xa), (pb, xb) in zip(_leaves(a), _leaves(b)):
        if np.issubdtype(xa.dtype, np.floating):
            np.testing.assert_allclose(
                xa, xb, atol=atol, rtol=atol, equal_nan=True,
                err_msg=f"{context}: leaf {pa}")
        else:
            assert np.array_equal(xa, xb), f"{context}: leaf {pa}"


def _metric_stream_bitwise(ms, mstack, context=""):
    """Per-step metrics from sequential dispatches vs the stacked
    (k,)-leaved chunk metrics."""
    mstack = jax.device_get(mstack)
    for i, m in enumerate(ms):
        for key in m:
            a, b = np.asarray(m[key]), np.asarray(mstack[key])[i]
            assert np.array_equal(a, b, equal_nan=True), (
                f"{context}: metric {key!r} at step {i}: {a} != {b}")


# ------------------------------------------------------------------ DP


def _dp_setup(rich_optim=True):
    mesh = make_mesh(MeshConfig(), jax.devices()[:8])
    model = TinyNet()
    kw = dict(lr=0.1, warmup_steps=0)
    if rich_optim:
        # The carries the chunk must thread exactly: MultiSteps
        # accumulation, the apply_if_finite failure counter, EMA.
        kw.update(ema_decay=0.5, accum_steps=2, skip_nonfinite=3)
    ocfg = OptimConfig(**kw)
    tx, sched = build_optimizer(ocfg, 10)
    state = create_train_state(jax.random.key(0), model, tx, _batch(2),
                               ema=rich_optim)
    lcfg = LossConfig(ssim_window=5)
    ema = 0.5 if rich_optim else 0.0
    build = lambda **bkw: make_unified_train_step(  # noqa: E731
        model, lcfg, tx, mesh, preset="dp", schedule=sched, donate=False,
        ema_decay=ema, **bkw)
    return mesh, state, build


def test_dp_scan_chunk_bitwise_smoke(eight_devices):
    """t1.sh pre-run smoke: scan(2) == 2 x scan(1), DP, bitwise."""
    mesh, state, build = _dp_setup(rich_optim=False)
    ref = build(steps_per_dispatch=1, _always_scan=True)
    chunk = build(steps_per_dispatch=2)
    batches = [_batch(8, seed=i) for i in range(2)]
    s_seq, ms = state, []
    for b in batches:
        one = {k: v[None] for k, v in b.items()}
        s_seq, m = ref(s_seq, global_batch_array(one, mesh,
                                                 spec=P(None, "data")))
        ms.append(jax.device_get(
            jax.tree_util.tree_map(lambda x: x[0], m)))
    s_c, mstack = chunk(state, global_batch_array(
        _stack(batches), mesh, spec=P(None, "data")))
    assert_trees_bitwise(s_seq, s_c, "DP k=2 state")
    _metric_stream_bitwise(ms, mstack, "DP k=2")


@pytest.mark.parametrize("k", [1, 2, 4])
def test_dp_scan_chunk_bitwise_and_plain_tolerance(k, eight_devices):
    """scan(k) vs k sequential dispatches: BITWISE against scan(1)
    dispatches; tight-tolerance + exact counter streams against the
    plain k=1 program.  Includes accum_steps=2, a NaN batch mid-chunk
    (skip_nonfinite carry), and the EMA blend."""
    mesh, state, build = _dp_setup()
    plain = build(steps_per_dispatch=1)
    chunk = build(steps_per_dispatch=k) if k > 1 else plain
    ref = build(steps_per_dispatch=1, _always_scan=True)

    batches = [_batch(8, seed=i) for i in range(k)]
    if k > 1:
        batches[1]["image"][0, 0, 0, 0] = np.nan  # mid-chunk nonfinite

    # Reference A: k dispatches of the degenerate 1-step scan.
    s_ref, ms = state, []
    for b in batches:
        one = {key: v[None] for key, v in b.items()}
        s_ref, m = ref(s_ref, global_batch_array(one, mesh,
                                                 spec=P(None, "data")))
        ms.append(jax.device_get(
            jax.tree_util.tree_map(lambda x: x[0], m)))
    # Reference B: k dispatches of the historical plain program.
    s_plain, ms_plain = state, []
    for b in batches:
        s_plain, m = plain(s_plain, global_batch_array(b, mesh))
        ms_plain.append(jax.device_get(m))

    if k == 1:
        # k=1 must BE the plain path: same callable, scalar metrics.
        assert chunk is plain
        assert np.asarray(ms_plain[0]["total"]).ndim == 0
        s_c, mstack = s_ref, jax.tree_util.tree_map(
            lambda x: np.asarray(x)[None], ms[0])
    else:
        s_c, mstack = chunk(state, global_batch_array(
            _stack(batches), mesh, spec=P(None, "data")))
        assert np.asarray(jax.device_get(mstack)["total"]).shape == (k,)

    # (a) the chunking transform is bitwise-neutral.
    assert_trees_bitwise(s_ref, s_c, f"DP k={k} state")
    if k > 1:
        _metric_stream_bitwise(ms, mstack, f"DP k={k}")
    # (b) vs the plain program: semantic streams exact, floats at f32
    # accumulation tolerance (XLA:CPU while-body conv canonicalization
    # — see module docstring).
    assert int(jax.device_get(s_c.step)) == int(jax.device_get(
        s_plain.step)) == k
    for i in range(k):
        for key in ("lr", "notfinite_count"):
            if key in ms_plain[i]:
                np.testing.assert_array_equal(
                    np.asarray(ms_plain[i][key]),
                    np.asarray(jax.device_get(mstack)[key])[i],
                    err_msg=f"{key} stream at step {i}")
    assert_trees_close(s_plain, s_c, atol=5e-6, context=f"DP k={k} plain")


def test_dp_chunk_ema_blend_matches_plain(eight_devices):
    """The EMA gate (blend only when params changed) carries through
    the scan: after a 2-step chunk with accum_steps=2, the EMA equals
    d*p0 + (1-d)*p2 — one blend, at the accumulation boundary."""
    mesh, state, build = _dp_setup()
    chunk = build(steps_per_dispatch=2)
    batches = [_batch(8, seed=i) for i in range(2)]
    s_c, _ = chunk(state, global_batch_array(
        _stack(batches), mesh, spec=P(None, "data")))
    p0 = jax.tree_util.tree_leaves(jax.device_get(state.params))
    p2 = jax.tree_util.tree_leaves(jax.device_get(s_c.params))
    ema = jax.tree_util.tree_leaves(jax.device_get(s_c.ema_params))
    for a, b, e in zip(p0, p2, ema):
        np.testing.assert_allclose(e, 0.5 * a + 0.5 * b, rtol=1e-5,
                                   atol=1e-6)


# ------------------------------------------------------------- TP / SP


def _vit_tiny():
    from distributed_sod_project_tpu.models.vit_sod import ViTSOD

    return ViTSOD(patch=8, dim=32, depth=2, heads=2, mlp_ratio=2)


@pytest.mark.parametrize("k", [2, 4])
def test_tp_scan_chunk_bitwise(k, eight_devices):
    """GSPMD TP builder: scan(k) == k x scan(1) bitwise on a
    (data=2, model=2) mesh."""
    from distributed_sod_project_tpu.parallel.tp import shard_state

    model = _vit_tiny()
    mesh = make_mesh(MeshConfig(data=2, model=2), eight_devices[:4])
    tx, sched = build_optimizer(OptimConfig(lr=0.05, warmup_steps=0), 10)
    state0 = jax.device_get(
        create_train_state(jax.random.key(0), model, tx, _batch(4, hw=32)))
    state, shardings = shard_state(state0, mesh)
    lcfg = LossConfig(ssim=0.0, ssim_window=5)
    build = lambda **bkw: make_unified_train_step(  # noqa: E731
        model, lcfg, tx, mesh, preset="tp", schedule=sched, donate=False,
        state_shardings=shardings, **bkw)
    ref = build(steps_per_dispatch=1, _always_scan=True)
    chunk = build(steps_per_dispatch=k)
    chunk_shard = NamedSharding(mesh, P(None, "data"))

    batches = [_batch(4, hw=32, seed=i) for i in range(k)]
    s_ref, ms = state, []
    for b in batches:
        one = {key: v[None] for key, v in b.items()}
        s_ref, m = ref(s_ref, jax.device_put(one, chunk_shard))
        ms.append(jax.device_get(
            jax.tree_util.tree_map(lambda x: x[0], m)))
    s_c, mstack = chunk(state, jax.device_put(_stack(batches),
                                              chunk_shard))
    assert_trees_bitwise(s_ref, s_c, f"TP k={k} state")
    _metric_stream_bitwise(ms, mstack, f"TP k={k}")
    # and vs the plain TP program: tight tolerance, exact step counter.
    plain = build()
    s_p = state
    for b in batches:
        s_p, _ = plain(s_p, jax.device_put(b, batch_sharding(mesh)))
    assert int(jax.device_get(s_c.step)) == int(jax.device_get(s_p.step))
    assert_trees_close(s_p, s_c, atol=5e-6, context=f"TP k={k} plain")


@pytest.mark.parametrize("k", [2, 4])
def test_sp_scan_chunk_bitwise(k, eight_devices):
    """Sequence-parallel builder: scan(k) == k x scan(1) bitwise on a
    (data=2, seq=4) mesh (ring attention, psum'd loss statistics)."""
    from distributed_sod_project_tpu.parallel.sp import sp_batch_sharding

    model = _vit_tiny()
    mesh = make_mesh(MeshConfig(data=2, seq=4), eight_devices)
    tx, sched = build_optimizer(OptimConfig(lr=0.05, warmup_steps=0), 10)
    state = create_train_state(jax.random.key(0), model, tx,
                               _batch(4, hw=32))
    state = jax.device_put(state, replicated_sharding(mesh))
    lcfg = LossConfig(bce=1.0, iou=1.0, ssim=0.0)
    build = lambda **bkw: make_unified_train_step(  # noqa: E731
        model, lcfg, tx, mesh, preset="sp", schedule=sched, donate=False,
        **bkw)
    ref = build(steps_per_dispatch=1, _always_scan=True)
    chunk = build(steps_per_dispatch=k)
    chunk_shard = NamedSharding(mesh, P(None, "data", "seq"))

    batches = [_batch(4, hw=32, seed=i) for i in range(k)]
    s_ref, ms = state, []
    for b in batches:
        one = {key: v[None] for key, v in b.items()}
        s_ref, m = ref(s_ref, jax.device_put(one, chunk_shard))
        ms.append(jax.device_get(
            jax.tree_util.tree_map(lambda x: x[0], m)))
    s_c, mstack = chunk(state, jax.device_put(_stack(batches),
                                              chunk_shard))
    assert_trees_bitwise(s_ref, s_c, f"SP k={k} state")
    _metric_stream_bitwise(ms, mstack, f"SP k={k}")
    # and vs the plain SP program: tight tolerance, exact step counter.
    plain = build()
    s_p = state
    for b in batches:
        s_p, _ = plain(s_p, jax.device_put(b, sp_batch_sharding(mesh)))
    assert int(jax.device_get(s_c.step)) == int(jax.device_get(s_p.step))
    assert_trees_close(s_p, s_c, atol=5e-6, context=f"SP k={k} plain")


# -------------------------------------------------- chunk assembly


def test_chunk_batches_stacks_in_order():
    from distributed_sod_project_tpu.data import chunk_batches

    batches = [{"image": np.full((2, 3), i, np.float32),
                "index": np.arange(2) + 10 * i} for i in range(6)]
    chunks = list(chunk_batches(iter(batches), 3))
    assert len(chunks) == 2
    np.testing.assert_array_equal(chunks[0]["image"][:, 0, 0], [0, 1, 2])
    np.testing.assert_array_equal(chunks[1]["image"][:, 0, 0], [3, 4, 5])
    assert chunks[0]["index"].shape == (3, 2)


def test_chunk_batches_copies_out_of_ring_buffers():
    """The assembler must copy each batch the moment it is yielded —
    a loader recycling ONE buffer (harsher than the real ring's
    2-yield window) must still produce correct chunks."""
    from distributed_sod_project_tpu.data import chunk_batches

    buf = {"image": np.zeros((2, 2), np.float32)}

    def recycling_loader():
        for i in range(4):
            buf["image"][:] = i  # overwrite in place, same array
            yield buf

    chunks = list(chunk_batches(recycling_loader(), 2))
    np.testing.assert_array_equal(chunks[0]["image"][:, 0, 0], [0, 1])
    np.testing.assert_array_equal(chunks[1]["image"][:, 0, 0], [2, 3])


def test_chunk_batches_buffer_rotation_contract():
    """Yielded chunk i stays valid while chunk i+1 is assembled (the
    pair rotation); buffer reuse begins at chunk i+2 — mirroring the
    prefetch cast-buffer contract its consumer relies on."""
    from distributed_sod_project_tpu.data import chunk_batches

    batches = ({"x": np.full((1,), i, np.float32)} for i in range(8))
    it = chunk_batches(batches, 2)
    c0 = next(it)
    c0_snapshot = c0["x"].copy()
    c1 = next(it)
    np.testing.assert_array_equal(c0["x"], c0_snapshot)  # still valid
    c2 = next(it)
    assert c2["x"] is c0["x"]  # pair rotation reuses chunk 0's buffer
    np.testing.assert_array_equal(c1["x"][:, 0], [2, 3])
    np.testing.assert_array_equal(c2["x"][:, 0], [4, 5])


def test_chunk_batches_k1_passthrough_and_partial_drop():
    from distributed_sod_project_tpu.data import chunk_batches
    from distributed_sod_project_tpu.utils.observability import (
        PipelineStats)

    batches = [{"x": np.full((1,), i, np.float32)} for i in range(3)]
    out = list(chunk_batches(iter(batches), 1))
    assert all(a["x"] is b["x"] for a, b in zip(out, batches))

    stats = PipelineStats()
    chunks = list(chunk_batches(iter(batches), 2, stats=stats))
    assert len(chunks) == 1  # trailing partial dropped, loudly counted
    snap = stats.snapshot()
    assert snap["data_partial_chunks_dropped"] == 1.0
    assert snap["data_chunks"] == 1.0
    assert snap["data_chunk_assemble_ms"] >= 0.0


# ------------------------------------------------- config validation


def test_validate_steps_per_dispatch_names_offending_pair():
    cfg = get_config("minet_vgg16_ref").replace(
        steps_per_dispatch=4, log_every_steps=20,
        checkpoint_every_steps=500, eval_every_steps=0)
    validate_steps_per_dispatch(cfg)  # 4 | 20, 4 | 500: fine
    bad = cfg.replace(log_every_steps=10)
    with pytest.raises(ValueError, match="log_every_steps=10"):
        validate_steps_per_dispatch(bad)
    bad = cfg.replace(checkpoint_every_steps=6)
    with pytest.raises(ValueError, match="checkpoint_every_steps=6"):
        validate_steps_per_dispatch(bad)
    bad = cfg.replace(eval_every_steps=2)
    with pytest.raises(ValueError, match="eval_every_steps=2"):
        validate_steps_per_dispatch(bad)
    bad = cfg.replace(steps_per_epoch=10)
    with pytest.raises(ValueError, match="steps_per_epoch=10"):
        validate_steps_per_dispatch(bad)
    with pytest.raises(ValueError, match="loader steps_per_epoch=6"):
        validate_steps_per_dispatch(cfg, loader_steps_per_epoch=6)
    with pytest.raises(ValueError, match=">= 1"):
        validate_steps_per_dispatch(cfg.replace(steps_per_dispatch=0))
    # k=1 never raises, whatever the cadences.
    validate_steps_per_dispatch(
        cfg.replace(steps_per_dispatch=1, log_every_steps=7), 13)


# ------------------------------------------------------- loop level


def _loop_cfg(tmp_path, **kw):
    """The tiny-ViT engine preset (test_engine.py) with chunk-friendly
    cadences; 32 synthetic samples / batch 8 = 4 steps per epoch."""
    cfg = get_config("minet_vgg16_ref")
    base = dict(
        data=DataConfig(dataset="synthetic", image_size=(32, 32),
                        synthetic_size=32, num_workers=0),
        model=ModelConfig(name="vit_sod", backbone="tiny", sync_bn=False,
                          compute_dtype="float32"),
        optim=OptimConfig(lr=0.01),
        mesh=MeshConfig(data=-1),
        global_batch_size=8,
        num_epochs=2,
        log_every_steps=2,
        checkpoint_every_steps=2,
        tensorboard=False,
        checkpoint_dir=str(tmp_path / "ck"),
    )
    base.update(kw)
    return cfg.replace(**base)


def test_fit_chunked_matches_per_step_fit(tmp_path, eight_devices):
    """fit(k=2) and fit(k=1) from the same seed produce the same
    training trajectory: same logged-step metric values (tight f32
    tolerance — the plain-vs-scan XLA:CPU context rounding bounds the
    gap) and matching step-4 checkpoints."""
    from distributed_sod_project_tpu.ckpt import CheckpointManager
    from distributed_sod_project_tpu.train.loop import fit

    streams = {}
    outs = {}
    for k in (1, 2):
        cfg = _loop_cfg(tmp_path / f"k{k}", steps_per_dispatch=k)
        seen = []
        outs[k] = fit(cfg, max_steps=4,
                      hooks={"on_metrics":
                             lambda s, m: seen.append((s, dict(m)))})
        streams[k] = seen
    assert outs[1]["final_step"] == outs[2]["final_step"] == 4
    steps1 = [s for s, _ in streams[1]]
    steps2 = [s for s, _ in streams[2]]
    assert steps1 == steps2 == [2, 4]  # same log boundaries
    for (s1, m1), (s2, m2) in zip(streams[1], streams[2]):
        for key in ("total", "lr", "grad_norm"):
            np.testing.assert_allclose(
                m1[key], m2[key], atol=5e-5, rtol=5e-5,
                err_msg=f"metric {key} at step {s1}")
    # The step-4 checkpoints hold the same weights.
    params = {}
    for k in (1, 2):
        cfg = _loop_cfg(tmp_path / f"k{k}", steps_per_dispatch=k)
        from distributed_sod_project_tpu.models import build_model
        from distributed_sod_project_tpu.data import resolve_dataset

        model = build_model(cfg.model)
        tx, _ = build_optimizer(cfg.optim, 4)
        ds = resolve_dataset(cfg.data)
        template = create_train_state(
            jax.random.key(cfg.seed), model, tx,
            {"image": np.asarray(ds[0]["image"])[None]})
        mgr = CheckpointManager(cfg.checkpoint_dir)
        restored, ck_step = mgr.restore_latest_valid(template)
        mgr.close()
        assert int(restored.step) == 4, ck_step
        params[k] = restored.params
    assert_trees_close(params[1], params[2], atol=5e-5,
                       context="fit k=1 vs k=2 checkpoint")


def test_fit_chunked_per_chunk_metrics_stream(tmp_path, eight_devices):
    """on_chunk_metrics receives the stacked per-step stream once per
    chunk, and the steady-state loop does exactly ONE jax.device_get
    per chunk between log boundaries (the zero-per-step-sync
    contract)."""
    from distributed_sod_project_tpu.train.loop import fit

    counts = {"n": 0}
    real_device_get = jax.device_get

    def counting_device_get(x):
        counts["n"] += 1
        return real_device_get(x)

    chunk_calls = []
    window = {}

    def on_chunk(step, stacked):
        chunk_calls.append((step, stacked))

    def on_metrics(step, m):
        if step == 2:
            window["start"] = counts["n"]
        if step == 8:
            window["end"] = counts["n"]

    cfg = _loop_cfg(tmp_path, steps_per_dispatch=2,
                    checkpoint_every_steps=0)
    old = jax.device_get
    jax.device_get = counting_device_get
    try:
        out = fit(cfg, max_steps=8,
                  hooks={"on_chunk_metrics": on_chunk,
                         "on_metrics": on_metrics})
    finally:
        jax.device_get = old
    assert out["final_step"] == 8
    # one stacked stream per chunk, chunk-end steps 2,4,6,8
    assert [s for s, _ in chunk_calls] == [2, 4, 6, 8]
    for _, stacked in chunk_calls:
        assert np.asarray(stacked["total"]).shape == (2,)
    # steps (2, 8] span chunks ending at 4, 6, 8 → exactly 3 syncs.
    assert window["end"] - window["start"] == 3


def test_fit_chunked_counts_dispatches_not_steps(tmp_path,
                                                 eight_devices,
                                                 monkeypatch):
    """8 steps at k=2 = 4 dispatches of the compiled chunk."""
    from distributed_sod_project_tpu.train import loop as loop_mod

    from distributed_sod_project_tpu.parallel import engine as engine_mod

    calls = {"n": 0}
    real = engine_mod.make_unified_train_step

    def wrapped_builder(*a, **kw):
        step = real(*a, **kw)

        def counting_step(state, batch):
            calls["n"] += 1
            return step(state, batch)

        return counting_step

    monkeypatch.setattr(engine_mod, "make_unified_train_step",
                        wrapped_builder)
    cfg = _loop_cfg(tmp_path, steps_per_dispatch=2,
                    checkpoint_every_steps=0)
    out = loop_mod.fit(cfg, max_steps=8)
    assert out["final_step"] == 8
    assert calls["n"] == 4


def test_fit_rejects_misaligned_cadences(tmp_path, eight_devices):
    from distributed_sod_project_tpu.train.loop import fit

    cfg = _loop_cfg(tmp_path, steps_per_dispatch=2, log_every_steps=3)
    with pytest.raises(ValueError, match="log_every_steps=3"):
        fit(cfg, max_steps=4)
    cfg = _loop_cfg(tmp_path, steps_per_dispatch=2,
                    checkpoint_every_steps=5)
    with pytest.raises(ValueError, match="checkpoint_every_steps=5"):
        fit(cfg, max_steps=4)
    # 3 divides the cadences below but not the loader's 4-step epoch.
    cfg = _loop_cfg(tmp_path, steps_per_dispatch=3, log_every_steps=3,
                    checkpoint_every_steps=3)
    with pytest.raises(ValueError, match="steps_per_epoch=4"):
        fit(cfg, max_steps=6)
    cfg = _loop_cfg(tmp_path, steps_per_dispatch=2)
    with pytest.raises(ValueError, match="max_steps=3"):
        fit(cfg, max_steps=3)


def test_async_save_not_torn_by_donated_next_step(tmp_path,
                                                  eight_devices):
    """Regression (found by the chunk-boundary resume work): on the CPU
    backend ``device_get`` aliases host memory, so orbax's async write
    raced the next donated train step's in-place update — a step-2
    checkpoint dir holding step-3 state.  The manager must snapshot
    before queueing the write: a mid-run checkpoint's stored step must
    equal its directory's step."""
    from distributed_sod_project_tpu.ckpt import CheckpointManager
    from distributed_sod_project_tpu.train.loop import fit

    cfg = _loop_cfg(tmp_path, steps_per_dispatch=1)
    out = fit(cfg, max_steps=3)  # saves at 2, trains on, force-saves 3
    assert out["final_step"] == 3
    mgr = CheckpointManager(cfg.checkpoint_dir)
    raw = mgr.restore_raw(2)
    mgr.close()
    assert int(np.asarray(raw["step"])) == 2


def test_fit_chunked_resume_requires_chunk_boundary(tmp_path,
                                                    eight_devices):
    """A k=1 run's final force-save can land mid-chunk; resuming that
    checkpoint with k=2 must fail loudly, and resuming an aligned one
    must work.  Runs in a FRESH cache-less interpreter, chaos-style:
    interrupted-fit + in-process-resume sequences trip a known
    jaxlib-0.4.36 heap-corruption bug once the persistent XLA cache
    has engaged (docs/RESILIENCE.md "Known sharp edges") — and a
    process-fresh resume is also the faithful preemption semantics."""
    import json
    import subprocess
    import sys

    script = tmp_path / "resume_child.py"
    script.write_text(f"""
import json, os, shutil, sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
from test_step_chunking import _loop_cfg
from pathlib import Path
from distributed_sod_project_tpu.train.loop import fit

tmp = Path({str(tmp_path)!r})
out1 = fit(_loop_cfg(tmp, steps_per_dispatch=1), max_steps=3)
cfg2 = _loop_cfg(tmp, steps_per_dispatch=2)
# Aligned chunked resume: wipe the mid-chunk step-3 force-save so the
# chunk-aligned step 2 is newest-valid, then resume to 6 (mid-epoch
# re-entry at a chunk boundary: 2 %% loader_spe != 0 but 2 %% k == 0).
shutil.rmtree(os.path.join(cfg2.checkpoint_dir, "3"))
out2 = fit(cfg2, resume=True, max_steps=6)
# Manufacture a mid-chunk checkpoint (k=1 step to 7), then the
# misaligned chunked resume must raise the actionable error.
out3 = fit(_loop_cfg(tmp, steps_per_dispatch=1), resume=True,
           max_steps=7)
try:
    fit(cfg2, resume=True, max_steps=8)
    err = "NO RAISE"
except ValueError as e:
    err = str(e)
print("RESULT:" + json.dumps({{
    "first": out1["final_step"], "aligned": out2["final_step"],
    "mid": out3["final_step"], "err": err}}))
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("DSOD_FAULTS", None)
    if "xla_force_host_platform_device_count" not in env.get(
            "XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
    p = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, timeout=300)
    out = p.stdout.decode()
    assert p.returncode == 0, (
        f"resume child rc={p.returncode}\nstdout={out[-3000:]}\n"
        f"stderr={p.stderr.decode()[-3000:]}")
    lines = [l for l in out.splitlines() if l.startswith("RESULT:")]
    assert lines, f"no RESULT line: {out[-2000:]}"
    res = json.loads(lines[-1][len("RESULT:"):])
    assert res["first"] == 3
    assert res["aligned"] == 6
    assert res["mid"] == 7
    assert "chunk boundary" in res["err"]


def test_fit_faults_force_per_step_dispatch(tmp_path, eight_devices,
                                            monkeypatch):
    """DSOD_FAULTS + steps_per_dispatch>1: k falls back to 1 with a
    logged warning, per-step fault semantics stay exact (the stall
    fires between steps), and cadence validation runs at the FORCED
    k — log_every_steps=1 would be illegal at k=2."""
    import logging

    from distributed_sod_project_tpu.resilience import inject
    from distributed_sod_project_tpu.train.loop import fit
    from distributed_sod_project_tpu.utils.logging import get_logger

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Capture()
    get_logger().addHandler(handler)  # dsod logger has propagate=False
    monkeypatch.setenv("DSOD_FAULTS", "stall@1:0.01")
    inject.reset_plans()
    try:
        cfg = _loop_cfg(tmp_path, steps_per_dispatch=2,
                        log_every_steps=1, checkpoint_every_steps=0)
        out = fit(cfg, max_steps=2)
        assert out["final_step"] == 2
        assert any("forcing steps_per_dispatch=1" in m for m in records)
        plan = inject.plan_from_env()
        assert "stall@1:0.01" in plan.fired
    finally:
        get_logger().removeHandler(handler)
        inject.reset_plans()


@pytest.mark.slow
def test_fit_chunked_multiscale_cycles_per_chunk(tmp_path,
                                                 eight_devices):
    """Multi-scale + chunking: one static program per size, the cycle
    advancing per CHUNK; the run trains to completion."""
    from distributed_sod_project_tpu.train.loop import fit

    # Multi-scale needs size-agnostic params — a CNN zoo member, not
    # the tiny ViT (its pos_embed is grid-shaped).
    cfg = _loop_cfg(tmp_path, steps_per_dispatch=2,
                    checkpoint_every_steps=0)
    cfg = cfg.replace(
        data=dataclasses.replace(cfg.data, image_size=(64, 64),
                                 multiscale=(64, 32)),
        model=ModelConfig(name="minet", backbone="vgg16",
                          compute_dtype="float32"))
    out = fit(cfg, max_steps=8)
    assert out["final_step"] == 8
    assert np.isfinite(out["total"])


# ------------------------------------------------------------ timing


def test_step_timer_credits_chunk_steps(monkeypatch):
    from distributed_sod_project_tpu.utils import timing

    clock = {"t": 100.0}
    monkeypatch.setattr(timing.time, "perf_counter",
                        lambda: clock["t"])
    beats = []
    t = timing.StepTimer(window=8, warmup=0,
                         on_tick=lambda: beats.append(clock["t"]))
    t.tick(steps=4)
    clock["t"] += 0.4  # one 0.4s chunk of 4 steps → 0.1s/step
    t.tick(steps=4)
    assert t.mean_step_time == pytest.approx(0.1)
    # images_per_sec takes the per-STEP batch: 8 imgs / 0.1 s = 80.
    assert t.images_per_sec(8) == pytest.approx(80.0)
    # one watchdog beat per tick (per chunk), not per step.
    assert len(beats) == 2
    # a k=1 tick of the same interval reads 4x slower per step.
    clock["t"] += 0.4
    t.tick(steps=1)
    assert t.mean_step_time == pytest.approx((0.1 + 0.4) / 2)


def test_logged_rate_is_completed_steps_between_metric_fetches(monkeypatch):
    """The logged imgs_per_sec: steps between two host fetches of the
    metrics over the time between them (completed work on both ends),
    whatever the k=1 loop's per-dispatch ticks say in between."""
    from distributed_sod_project_tpu.utils import timing

    clock = {"t": 50.0}
    monkeypatch.setattr(timing.time, "perf_counter",
                        lambda: clock["t"])
    beats = []
    t = timing.StepTimer(window=8, warmup=0, on_tick=lambda: beats.append(1))
    # k=1 with run-ahead: five dispatches tick within 10 ms, then the
    # fetch at the log boundary blocks until the device has done them.
    for _ in range(5):
        clock["t"] += 0.002
        t.tick()
    clock["t"] += 1.24
    first = t.fetched(5, 16)  # no interval yet: the windowed mean
    assert first == pytest.approx(t.images_per_sec(16))
    for _ in range(5):
        clock["t"] += 0.002
        t.tick()
    assert t.images_per_sec(16) > 1.5 * 64  # the window is led by dispatches
    clock["t"] += 1.24
    assert t.fetched(10, 16) == pytest.approx(5 * 16 / 1.25)
    assert len(beats) == 10  # the watchdog's cadence is the ticks', untouched
    # A fetch that reports no progress has no rate of its own.
    clock["t"] += 1.0
    assert t.fetched(10, 16) == pytest.approx(t.images_per_sec(16))
