"""Host data-plane overhaul tests (vectorized augment, ring buffers,
multi-stage prefetch, starvation telemetry).

The load-bearing contract: batch content is a pure function of
(seed, epoch, idx) and IDENTICAL for every execution strategy —
scalar reference vs vectorized batch path (bitwise for hflip/jitter,
atol 1e-5 vs the scipy rotation), any num_workers / lookahead /
ring_buffers / decode_procs / cache_decoded setting.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

from distributed_sod_project_tpu.data import augment as A
from distributed_sod_project_tpu.data.pipeline import (
    BatchRing, HostDataLoader, prefetch_to_device)
from distributed_sod_project_tpu.data.synthetic import SyntheticSOD
from distributed_sod_project_tpu.utils.observability import PipelineStats

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _ref_batch(ds, idxs, aug_seed, **aug):
    """Scalar-reference augmentation, stacked."""
    outs = [A.augment_sample(dict(ds[i]), int(i), aug_seed,
                             norm_mean=ds.mean, norm_std=ds.std, **aug)
            for i in idxs]
    return {k: np.stack([o[k] for o in outs]) for k in outs[0]}


def _raw_batch(ds, idxs):
    samples = [ds[int(i)] for i in idxs]
    return {k: np.stack([s[k] for s in samples]) for k in samples[0]}


@pytest.mark.parametrize("use_depth", [False, True])
def test_augment_batch_matches_scalar_reference(use_depth):
    """hflip+jitter bitwise; rotation ≤1e-5 (bilinear) and exact for
    the nearest-interpolated mask."""
    ds = SyntheticSOD(size=12, image_size=(33, 41), use_depth=use_depth,
                      seed=3)
    idxs = [5, 2, 9, 11, 0, 7]
    aug_seed = 991

    # Geometric off, photometric on → must be BITWISE.
    ref = _ref_batch(ds, idxs, aug_seed, hflip=True, rotate_degrees=0.0,
                     color_jitter=0.4)
    got = A.augment_batch(_raw_batch(ds, idxs), idxs, aug_seed,
                          hflip=True, rotate_degrees=0.0,
                          color_jitter=0.4, norm_mean=ds.mean,
                          norm_std=ds.std)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)

    # Full stack with rotation → 1e-5 vs the scipy reference.
    ref = _ref_batch(ds, idxs, aug_seed, hflip=True, rotate_degrees=10.0,
                     color_jitter=0.4)
    got = A.augment_batch(_raw_batch(ds, idxs), idxs, aug_seed,
                          hflip=True, rotate_degrees=10.0,
                          color_jitter=0.4, norm_mean=ds.mean,
                          norm_std=ds.std)
    np.testing.assert_allclose(ref["image"], got["image"], atol=1e-5)
    np.testing.assert_array_equal(ref["mask"], got["mask"])
    if use_depth:
        np.testing.assert_allclose(ref["depth"], got["depth"], atol=1e-5)


def test_rotate_batch_matches_scipy_semantics():
    """The gather implements scipy.ndimage's exact conventions:
    rotation direction, (n-1)/2 center, constant-0 OUTSIDE [0, n-1]
    (no edge/cval interpolation), floor(x+0.5) nearest."""
    rng = np.random.RandomState(0)
    img = rng.rand(5, 30, 26, 3).astype(np.float32)
    mask = (rng.rand(5, 30, 26, 1) > 0.5).astype(np.float32)
    angles = np.asarray([17.0, -120.0, 0.0, 90.0, 63.1])

    got = A.rotate_batch({"image": img.copy(), "mask": mask.copy()},
                         angles)
    for j in range(5):
        ref_i = A.apply_rotate({"image": img[j], "mask": mask[j]},
                               float(angles[j]))
        np.testing.assert_allclose(got["image"][j], ref_i["image"],
                                   atol=1e-5)
        np.testing.assert_array_equal(got["mask"][j], ref_i["mask"])


def test_rotate_batch_inplace_out_matches_fresh():
    """out= aliasing the input (ring reuse) gives identical results."""
    rng = np.random.RandomState(1)
    img = rng.rand(3, 16, 16, 3).astype(np.float32)
    angles = np.asarray([5.0, -8.0, 3.0])
    fresh = A.rotate_batch({"image": img.copy()}, angles)
    buf = {"image": img.copy()}
    inplace = A.rotate_batch(buf, angles, out={"image": buf["image"]})
    np.testing.assert_array_equal(fresh["image"], inplace["image"])
    assert inplace["image"] is buf["image"]  # really wrote the slot


def _collect(ld, epoch=1, copy=True):
    ld.set_epoch(epoch)
    out = []
    for b in ld:
        out.append({k: v.copy() if copy else v for k, v in b.items()})
    return out


@pytest.mark.parametrize("kw", [
    dict(num_workers=2),
    dict(num_workers=2, ring_buffers=4),
    dict(num_workers=3, lookahead=4, ring_buffers=6),
    dict(num_workers=0, ring_buffers=4),
    dict(num_workers=0, cache_decoded=0),
    dict(num_workers=0, cache_decoded=5),
])
def test_loader_execution_strategy_never_changes_batches(kw):
    """Every pipelining/buffering knob yields bitwise-identical
    batches to the plain serial loader."""
    mk = lambda **k: HostDataLoader(  # noqa: E731
        SyntheticSOD(size=24, image_size=(24, 24), seed=2),
        global_batch_size=4, shuffle=True, seed=9, hflip=True,
        rotate_degrees=8.0, color_jitter=0.3, **k)
    ref = _collect(mk(num_workers=0))
    got = _collect(mk(**kw))
    assert len(ref) == len(got) == 6
    for a, b in zip(ref, got):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_loader_decode_procs_identical_batches():
    """Process-pool decode (shared-memory transport) is behavior-
    invisible: same batches, bit for bit."""
    mk = lambda **k: HostDataLoader(  # noqa: E731
        SyntheticSOD(size=16, image_size=(16, 16), seed=4),
        global_batch_size=4, shuffle=True, seed=1, hflip=True,
        rotate_degrees=5.0, **k)
    ref = _collect(mk(num_workers=0))
    procs = mk(num_workers=2, decode_procs=2)
    try:
        got = _collect(procs)
    finally:
        procs.close()
    for a, b in zip(ref, got):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_ring_buffers_are_recycled_and_contract_respected():
    """Zero-copy assembly: with a ring the loader reuses the SAME
    arrays (no per-step allocation), and a yielded batch stays intact
    for the contract window (2 further yields)."""
    ds = SyntheticSOD(size=32, image_size=(8, 8), seed=0)
    ld = HostDataLoader(ds, global_batch_size=4, shuffle=False, seed=0,
                        num_workers=0, ring_buffers=4)
    ld.set_epoch(0)
    seen_ids = []
    first_copy = None
    first_ref = None
    for step, b in enumerate(iter(ld)):
        if step == 0:
            first_ref = b["image"]
            first_copy = b["image"].copy()
        if step == 2:
            # Window: after 2 further yields the first batch is still
            # untouched...
            np.testing.assert_array_equal(first_ref, first_copy)
        seen_ids.append(id(b["image"]))
    # ...and the ring really recycled buffers: 8 steps, ≤ ring slots
    # distinct arrays.
    assert len(set(seen_ids)) <= ld.ring_buffers
    assert len(seen_ids) == 8


def test_ring_survives_early_consumer_exit():
    """Breaking out mid-epoch (the train loop's total_steps exit) must
    release slots — further epochs keep producing."""
    ds = SyntheticSOD(size=32, image_size=(8, 8), seed=0)
    ld = HostDataLoader(ds, global_batch_size=4, shuffle=True, seed=3,
                        num_workers=2, ring_buffers=4)
    for epoch in range(4):
        ld.set_epoch(epoch)
        n = 0
        for _ in iter(ld):
            n += 1
            if n == 3:
                break  # early exit with builds in flight
    ld.set_epoch(9)
    assert len(list(iter(ld))) == 8  # nothing leaked, full epoch works


def test_batch_ring_acquire_release_telemetry():
    stats = PipelineStats()
    ring = BatchRing(2, {"x": ((2, 3), np.float32)}, stats=stats)
    a = ring.acquire()
    b = ring.acquire()
    assert a is not b and a["x"].shape == (2, 3)
    ring.release(a)
    c = ring.acquire()
    assert c is a  # FIFO recycle
    ring.release(b)
    ring.release(c)
    assert stats.snapshot().get("data_ring_wait_ms", 0.0) >= 0.0


def test_prefetch_starvation_and_backpressure_counters():
    """A slow producer shows up as data_starved_ms; a slow consumer as
    data_prefetch_full_ms — 'input-bound' is a number, not a guess."""

    def slow_producer():
        for i in range(4):
            time.sleep(0.05)
            yield {"image": np.zeros((2, 4, 4, 3), np.float32)}

    stats = PipelineStats()
    for _ in prefetch_to_device(slow_producer(), size=1, stats=stats):
        pass
    starved = stats.snapshot()
    assert starved["data_starved_ms"] > 50.0
    assert starved["data_batches"] if "data_batches" in starved else True

    def fast_producer():
        for i in range(4):
            yield {"image": np.zeros((2, 4, 4, 3), np.float32)}

    stats2 = PipelineStats()
    for _ in prefetch_to_device(fast_producer(), size=1, stats=stats2):
        time.sleep(0.05)  # consumer is the bottleneck
    snap = stats2.snapshot()
    assert snap["data_prefetch_full_ms"] > 50.0
    assert snap["data_h2d_ms"] >= 0.0


def test_pipeline_stats_delta_resets_between_intervals():
    s = PipelineStats()
    s.add("data_starved_ms", 5.0)
    s.observe_depth(1, 2)
    d1 = s.delta()
    assert d1["data_starved_ms"] == 5.0
    assert d1["data_queue_depth_avg"] == 1.0
    s.add("data_starved_ms", 2.0)
    d2 = s.delta()
    assert d2["data_starved_ms"] == 2.0  # interval, not cumulative
    assert s.snapshot()["data_starved_ms"] == 7.0  # totals keep running


def test_loader_cache_decoded_budget_and_bound():
    """cache_decoded=N caches at most N samples; auto (-1) disables
    itself when the dataset exceeds cache_budget_mb."""
    ds = SyntheticSOD(size=16, image_size=(16, 16), seed=0)
    ld = HostDataLoader(ds, global_batch_size=4, shuffle=False,
                        num_workers=0, cache_decoded=6)
    _collect(ld, epoch=0)
    assert ld._cache is not None and len(ld._cache) == 6

    tiny_budget = HostDataLoader(ds, global_batch_size=4, shuffle=False,
                                 num_workers=0, cache_decoded=-1,
                                 cache_budget_mb=0)
    _collect(tiny_budget, epoch=0)
    assert tiny_budget._cache is None  # auto mode bowed out

    auto = HostDataLoader(ds, global_batch_size=4, shuffle=False,
                          num_workers=0)  # 16x16 trivially fits 1 GB
    _collect(auto, epoch=0)
    assert auto._cache is not None and len(auto._cache) == 16


def test_train_loop_emits_data_plane_metrics(tmp_path):
    """End to end: the train loop surfaces the pipeline telemetry in
    its metric stream (data_starved_ms & co. reach on_metrics)."""
    from distributed_sod_project_tpu.configs import apply_overrides, get_config
    from distributed_sod_project_tpu.train.loop import fit

    cfg = get_config("minet_vgg16_ref")
    cfg = apply_overrides(cfg, [
        "global_batch_size=2", "data.image_size=32,32",
        "data.synthetic_size=8", "num_epochs=1", "log_every_steps=2",
        "model.compute_dtype=float32", "checkpoint_every_steps=0",
        "tensorboard=false", "data.num_workers=2",
        "data.ring_buffers=4",
    ])
    seen = {}

    def on_metrics(step, m):
        seen.update(m)

    fit(cfg, workdir=str(tmp_path), max_steps=4,
        hooks={"on_metrics": on_metrics})
    assert "data_batches" in seen
    assert "data_starved_ms" in seen


def test_decode_procs_refused_under_skip_budget_guard():
    """Worker processes would privatize the GuardedDataset counters,
    breaking the bounded-corruption invariant — the loader must refuse
    procs and decode in-thread (code-review finding)."""
    from distributed_sod_project_tpu.resilience.dataguard import (
        GuardedDataset)

    ds = GuardedDataset(SyntheticSOD(size=8, image_size=(8, 8)),
                        skip_budget=2)
    ld = HostDataLoader(ds, global_batch_size=4, shuffle=False,
                        num_workers=0, decode_procs=2)
    batches = _collect(ld, epoch=0)
    assert len(batches) == 2
    assert ld.decode_procs == 0  # gate tripped
    assert ld._proc_pool is None
