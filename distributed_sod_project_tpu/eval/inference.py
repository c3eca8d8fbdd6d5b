"""Inference + metric sweep — the reference's ``test.py`` path
(SURVEY.md §2 C2, §3.2).

Reference behavior reproduced: resize → forward → sigmoid →
resize-back-to-original → save PNG → stream (pred, gt) into the metric
aggregator.  TPU-shaped differences (SURVEY.md §7.3 hard part 5):

- the compiled forward only ever sees the static ``cfg.data.image_size``
  shape; per-image original-size handling (resize-back, PNG write,
  metric update) is host-side numpy,
- images run in fixed-size batches (last batch zero-padded and the pad
  masked out) so there is exactly ONE compiled program, not one per
  image size,
- prediction batches come back as one device array per batch; the host
  thread overlaps PNG/metric work with the next device batch.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..metrics import SODMetrics
from ..utils.logging import get_logger


def _original_mask(dataset, index: int, sample=None) -> np.ndarray:
    """GT at original resolution when the dataset is file-backed;
    falls back to the already-fetched (resized) sample mask otherwise."""
    if hasattr(dataset, "mask_paths") and hasattr(dataset, "stems"):
        from PIL import Image

        with Image.open(dataset.mask_paths[dataset.stems[index]]) as im:
            return (np.asarray(im.convert("L"), np.float32) / 255.0 > 0.5
                    ).astype(np.float32)
    if sample is None:
        sample = dataset[index]
    return np.asarray(sample["mask"]).squeeze()


def _stem(dataset, index: int) -> str:
    if hasattr(dataset, "stems"):
        return dataset.stems[index]
    return f"{index:06d}"


def _resize_pred(pred: np.ndarray, hw) -> np.ndarray:
    from PIL import Image

    if pred.shape == tuple(hw):
        return pred
    im = Image.fromarray((np.clip(pred, 0, 1) * 255).astype(np.uint8))
    im = im.resize((hw[1], hw[0]), Image.BILINEAR)
    return np.asarray(im, np.float32) / 255.0


def _save_pngs(items) -> None:
    """One eval batch of saliency maps → PNGs: C++ threaded writer when
    the native lib is built (GIL-free, SURVEY.md §3.2's dump hot loop),
    else PIL."""
    from ..data import native

    if native.png_writer_available():
        native.write_png_batch(items)
        return
    from PIL import Image

    for path, arr in items:
        Image.fromarray(arr).save(path)


def make_forward(model):
    """The canonical eval forward: ``(variables, batch) -> probs``
    (sigmoid on the primary logit, f32, [B,H,W]).  jitted once with the
    variables as an ARGUMENT so repeated calls never retrace.  Single
    definition shared by evaluate(), the in-training eval, and
    tools/predict.py — the mesh-sharded variant lives in
    train/step.py::make_eval_step."""

    @jax.jit
    def forward(variables, batch):
        outs = model.apply(variables, batch["image"], batch.get("depth"),
                           train=False)
        return jax.nn.sigmoid(outs[0][..., 0].astype(jnp.float32))

    return forward


def pad_to_batch(batch: Dict[str, np.ndarray], batch_size: int
                 ) -> Dict[str, np.ndarray]:
    """Zero-pad every leaf's leading dim to ``batch_size`` so the
    compiled forward only ever sees ONE static shape; callers slice the
    pad back off the output."""
    short = batch_size - next(iter(batch.values())).shape[0]
    if short <= 0:
        return batch
    return {k: np.concatenate(
        [v, np.zeros((short,) + v.shape[1:], v.dtype)])
        for k, v in batch.items()}


def restore_for_eval(ckpt_dir: str, config_name: Optional[str] = None,
                     overrides=(), step: Optional[int] = None):
    """Checkpoint directory → ``(cfg, model, state)``, shared by the
    eval-side CLIs (test.py, tools/predict.py).

    Config comes from the registry when ``config_name`` is given, else
    from the checkpoint's own ``config.json`` sidecar (checkpoints are
    self-describing).  The restore template is built from a zeros batch
    of the config's static eval shape — only shapes matter to orbax,
    and it must mirror training-time state (EMA slots included).
    """
    import json as _json

    from ..ckpt import CheckpointManager
    from ..configs import apply_overrides, config_from_dict, get_config
    from ..models import build_model
    from ..train import build_optimizer, create_train_state

    if config_name:
        cfg = get_config(config_name)
    else:
        sidecar = os.path.join(ckpt_dir, "config.json")
        if not os.path.exists(sidecar):
            raise SystemExit(
                f"no --config given and {sidecar} missing — pass the "
                "config name explicitly")
        with open(sidecar) as f:
            cfg = config_from_dict(_json.load(f))
    cfg = apply_overrides(cfg, list(overrides))

    model = build_model(cfg.model)
    tx, _ = build_optimizer(cfg.optim, 1)
    h, w = cfg.data.image_size
    probe = {"image": np.zeros((1, h, w, 3), np.float32)}
    if cfg.data.use_depth:
        probe["depth"] = np.zeros((1, h, w, 1), np.float32)
    template = create_train_state(jax.random.key(0), model, tx, probe,
                                  ema=cfg.optim.ema_decay > 0)
    mgr = CheckpointManager(ckpt_dir, async_save=False)
    state = mgr.restore(template, step=step)
    mgr.close()
    return cfg, model, state


def run_inference(
    forward,
    dataset,
    batch_size: int = 8,
    use_depth: bool = False,
    save_dir: Optional[str] = None,
    compute_metrics: bool = True,
    compute_structure: bool = True,
    device_metrics: bool = False,
    shard: Optional[tuple] = None,
    return_state: bool = False,
) -> Dict[str, float]:
    """Sweep ``dataset`` through a compiled ``forward(batch)->probs``.

    ``forward`` maps a dict with 'image' (and optionally 'depth') of the
    static eval shape to per-pixel probabilities [B,H,W].  Returns the
    SOD metric dict (empty when ``compute_metrics=False``).

    ``device_metrics=True`` accumulates the threshold-curve metrics
    (max/mean-Fβ, Em, MAE) INSIDE jit at the eval resolution — the
    prediction never reaches the host unless PNGs or the per-image
    structure measures need it, and the device pipelines batch k+1's
    forward under batch k's update.  The host convention (PySODMetrics)
    scores at each image's ORIGINAL resolution, so numbers differ
    slightly from the default path; use it where throughput matters and
    the ranking is what counts (inline train eval, benchmarking).

    Host post-processing (original-size resize, S/E-measure, PNG
    encode) runs on a worker thread so it overlaps the next batch's
    device work instead of serialising after it.

    ``shard=(shard_id, num_shards)`` sweeps only every num_shards-th
    image (the multi-host split: each host scores a disjoint slice
    instead of all hosts duplicating the full set).
    ``return_state=True`` (requires ``device_metrics``) returns the raw
    ``FBetaState`` instead of the result dict so the caller can psum
    shard states across hosts before finalising.
    """
    if return_state and not (compute_metrics and device_metrics
                             and not compute_structure):
        raise ValueError(
            "return_state needs device_metrics=True and "
            "compute_structure=False (host structure measures have "
            "nowhere to go when only the device state is returned)")
    log = get_logger()
    if save_dir:
        os.makedirs(save_dir, exist_ok=True)

    host_fbeta = compute_metrics and not device_metrics
    host_structure = compute_metrics and compute_structure
    agg = (SODMetrics(compute_structure=host_structure,
                      compute_fbeta=host_fbeta)
           if (host_fbeta or host_structure) else None)
    need_host = agg is not None or bool(save_dir)

    dev_state = dev_update = None
    if compute_metrics and device_metrics:
        from ..metrics.streaming import init_fbeta_state, update_fbeta_state

        dev_state = init_fbeta_state()
        dev_update = jax.jit(update_fbeta_state, donate_argnums=0)

    # Host worker: drains (device probs, indices, samples) and does the
    # original-resolution work.  maxsize bounds in-flight device
    # outputs; np.asarray inside the worker is the blocking fetch.
    import queue
    import threading

    errors: list = []
    work_q: queue.Queue = queue.Queue(maxsize=2)

    def _host_batch(probs_np, idxs, samples):
        pending = []
        for j, i in enumerate(idxs):
            gt = _original_mask(dataset, i, samples[j])
            pred = _resize_pred(probs_np[j], gt.shape[:2])
            if agg is not None:
                agg.add(pred, gt)
            if save_dir:
                pending.append((
                    os.path.join(save_dir, f"{_stem(dataset, i)}.png"),
                    (np.clip(pred, 0, 1) * 255).astype(np.uint8)))
        if pending:
            _save_pngs(pending)

    def _worker():
        while True:
            item = work_q.get()
            try:
                if item is None:
                    return
                probs_dev, idxs, samples = item
                _host_batch(np.asarray(probs_dev)[: len(idxs)], idxs,
                            samples)
            except Exception as e:  # noqa: BLE001 — re-raised on main
                errors.append(e)
            finally:
                work_q.task_done()

    worker = None
    if need_host:
        worker = threading.Thread(target=_worker, daemon=True)
        worker.start()

    all_idxs = (list(range(len(dataset))) if shard is None
                else list(range(shard[0], len(dataset), shard[1])))
    n = len(all_idxs)
    # With no host consumer AND no device metric carry, NOTHING in this
    # loop ever syncs: every forward is an async dispatch and a sweep
    # would queue the entire dataset onto the device (a warmup/
    # throughput pass with compute_metrics=False did exactly that).
    # Bound in-flight dispatches by blocking on a batch every few steps.
    free_running = not need_host and dev_update is None
    sync_every = 4
    probs = None
    try:
        for bi, lo in enumerate(range(0, n, batch_size)):
            if errors:
                break
            idxs = all_idxs[lo:lo + batch_size]
            pad = batch_size - len(idxs)
            samples = [dataset[i] for i in idxs]
            batch = {"image": np.stack([s["image"] for s in samples])}
            if use_depth:
                batch["depth"] = np.stack([s["depth"] for s in samples])
            if pad:
                batch = pad_to_batch(batch, batch_size)
            # The batch build above is the loop's slow host section
            # (dataset decode); a worker error that landed during it
            # used to surface only at the NEXT loop top — after this
            # batch was already dispatched and enqueued for a worker
            # that will never drain it.  Re-check at both seams: before
            # the dispatch, and right after the (possibly blocking)
            # enqueue below.
            if errors:
                break
            probs = forward(batch)  # async dispatch — no host sync here
            if dev_update is not None:
                gts = np.stack([s["mask"] for s in samples])
                if pad:
                    gts = np.concatenate(
                        [gts, np.zeros((pad,) + gts.shape[1:], gts.dtype)])
                valid = np.concatenate(
                    [np.ones((len(idxs),), np.float32),
                     np.zeros((pad,), np.float32)])
                dev_state = dev_update(dev_state, probs, gts, valid=valid)
            if need_host:
                work_q.put((probs, idxs, samples))
                if errors:  # the put may have blocked across a failure
                    break
            elif free_running and bi % sync_every == sync_every - 1:
                jax.block_until_ready(probs)
        if free_running and probs is not None:
            jax.block_until_ready(probs)
    finally:
        if worker is not None:
            work_q.put(None)
            worker.join()
    if errors:
        raise errors[0]

    if return_state:
        return jax.device_get(dev_state)

    out: Dict[str, float] = {}
    if dev_state is not None:
        from ..metrics.aggregator import results_from_state

        out.update(results_from_state(jax.device_get(dev_state)))
    if agg is not None:
        out.update(agg.results())
    if out:
        log.info("eval: %s", {k: round(v, 4) if isinstance(v, float) else v
                              for k, v in out.items()})
    return out


def flip_tta(forward):
    """Wrap an eval ``forward(batch)->probs`` with horizontal-flip
    test-time augmentation: average the prediction with the unflipped
    prediction of the mirrored input (the classic SOD eval trick;
    masks are flip-equivariant).  Costs 2x forward."""

    def wrapped(batch):
        probs = forward(batch)
        flipped = {k: (v[:, :, ::-1] if k in ("image", "depth") else v)
                   for k, v in batch.items()}
        return 0.5 * (probs + forward(flipped)[:, :, ::-1])

    return wrapped


def evaluate(
    cfg,
    state,
    model=None,
    mesh=None,
    datasets: Optional[Dict[str, object]] = None,
    save_root: Optional[str] = None,
    batch_size: Optional[int] = None,
    compute_structure: bool = True,
    tta: bool = False,
    device_metrics: bool = False,
) -> Dict[str, Dict[str, float]]:
    """Test-entrypoint engine: run every test set through the model.

    ``datasets`` maps name → dataset; defaults to the config's dataset.
    Pass ``mesh`` to shard the forward over its ``data`` axis (all local
    chips work on every batch — the pod/donut eval path); without it the
    jit runs on the default device.  ``tta`` averages in the
    horizontally-flipped prediction (2x forward cost).
    ``device_metrics`` accumulates Fβ/Em/MAE in the compiled step at
    eval resolution (see run_inference).
    """
    from ..data import resolve_dataset
    from ..models import build_model

    model = model or build_model(cfg.model)
    if datasets is None:
        # hflip is a train-loader op, not a dataset property — resolve as-is.
        datasets = {cfg.data.dataset: resolve_dataset(cfg.data)}
    # Cap 32, not the old 8: eval is forward-only (no grad/optimizer
    # memory), and measured v5e eval throughput rises steeply with
    # batch (248 -> 365 img/s from b32 to b64, BASELINE.md) — while
    # tiny validation sets still pad at most one batch.
    bs = batch_size or min(cfg.global_batch_size, 32)
    # Only the eval variables (params + BN stats) go to the devices —
    # NOT the optimizer/EMA buffers a restored TrainState carries
    # (3-4x the param bytes, replicated onto every chip for nothing).
    variables = (state.eval_variables() if hasattr(state, "eval_variables")
                 else state.variables())
    from ..parallel.sp import (make_sp_eval_forward, sp_eval_batch_size,
                               wants_sp_eval)

    if wants_sp_eval(model, mesh):
        # Row-sharded ring-attention forward (same helper as the inline
        # eval in train/loop.py): a full-attention eval would
        # materialise the NxN score matrix per chip — the memory
        # profile an SP-trained model exists to avoid at long-context
        # resolutions.
        bs = sp_eval_batch_size(mesh, bs)
        forward = make_sp_eval_forward(model, mesh,
                                       cfg.mesh.sp_strategy)(variables)
    else:
        if mesh is not None:
            from ..parallel.mesh import (eval_batch_divisor,
                                         eval_batch_sharding,
                                         replicated_sharding)

            div = eval_batch_divisor(mesh)  # batch over flat (data, seq)
            bs = max(1, bs // div) * div
            variables = jax.device_put(variables,
                                       replicated_sharding(mesh))

        _apply = make_forward(model)

        def forward(batch):
            if mesh is not None:
                batch = jax.device_put(batch, eval_batch_sharding(mesh))
            return _apply(variables, batch)

    if tta:
        forward = flip_tta(forward)

    results = {}
    for name, ds in datasets.items():
        results[name] = run_inference(
            forward, ds,
            batch_size=bs,
            use_depth=cfg.data.use_depth,
            save_dir=os.path.join(save_root, name) if save_root else None,
            compute_structure=compute_structure,
            device_metrics=device_metrics,
        )
    return results
