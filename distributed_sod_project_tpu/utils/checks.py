"""Input sanitation — host-side failure detection (SURVEY.md §5).

The functional training step cannot race, but it CAN be fed garbage:
wrong dataset layout, masks that aren't binary, NaNs from a corrupt
decode, images that skipped normalization.  ``validate_batch`` runs
once on the first batch of a training run (cheap, host-side) and fails
loudly with the actual problem instead of letting a silent bad input
become an unexplained divergence thousands of steps later.

``periodic_validate`` extends the net past the first batch: a
non-finite-only re-check every ``cfg.data.validate_every`` batches on
the host side of the prefetch queue (before the H2D copy, so it costs
no device sync).  Default off — the once-only behavior stands.
"""

from __future__ import annotations

from typing import Dict, Iterable, Iterator

import numpy as np


def validate_batch(batch: Dict, image_size, use_depth: bool = False) -> None:
    """Raise ValueError describing the first problem found."""
    def arr(k):
        v = batch.get(k)
        if v is None:
            raise ValueError(f"batch is missing {k!r}")
        return np.asarray(v)

    img = arr("image")
    mask = arr("mask")
    h, w = int(image_size[0]), int(image_size[1])
    if img.ndim != 4 or img.shape[1:] != (h, w, 3):
        raise ValueError(
            f"image shape {img.shape} != [B,{h},{w},3] — dataset layout "
            "or image_size mismatch")
    if mask.shape != img.shape[:3] + (1,):
        raise ValueError(f"mask shape {mask.shape} does not pair with "
                         f"image {img.shape}")
    if not np.all(np.isfinite(img)):
        raise ValueError("non-finite pixels in image batch (corrupt "
                         "decode or broken normalization)")
    mmin, mmax = float(mask.min()), float(mask.max())
    if mmin < 0.0 or mmax > 1.0:
        raise ValueError(f"mask range [{mmin}, {mmax}] outside [0,1] — "
                         "masks must be binarized probabilities")
    uniq = np.unique(mask)
    if np.any((uniq > 0.0) & (uniq < 1.0)):
        # Bilinear-resized masks must have been re-binarized upstream.
        raise ValueError("mask is not binary (found values strictly "
                         "between 0 and 1) — check the mask transform")
    if float(mask.mean()) in (0.0, 1.0):
        import warnings

        warnings.warn("every mask pixel in the first batch is "
                      f"{int(mask.mean())} — wrong mask directory?",
                      stacklevel=2)
    if use_depth:
        depth = arr("depth")
        if depth.shape != img.shape[:3] + (1,):
            raise ValueError(f"depth shape {depth.shape} does not pair "
                             f"with image {img.shape}")
        if not np.all(np.isfinite(depth)):
            raise ValueError("non-finite values in depth batch")


def check_finite_batch(batch: Dict, batch_index: int = -1) -> None:
    """The cheap subset of ``validate_batch``: raise on non-finite
    values in the float arrays (corrupt decode / poisoned cache).
    Shape/range invariants can't drift mid-run; finiteness can."""
    for k in ("image", "mask", "depth"):
        v = batch.get(k)
        if v is not None and not np.all(np.isfinite(np.asarray(v))):
            raise ValueError(
                f"non-finite values in {k!r} at batch {batch_index} — "
                "mid-run data corruption (decoder bug, bitrot, or a "
                "poisoned cache); see docs/RESILIENCE.md")


def periodic_validate(batches: Iterable[Dict], every: int,
                      start_index: int = 0) -> Iterator[Dict]:
    """Yield ``batches``, re-running :func:`check_finite_batch` on every
    ``every``-th one (host-side, pre-transfer).  ``every<=0`` passes
    the iterator through untouched."""
    if every <= 0:
        yield from batches
        return
    for i, batch in enumerate(batches, start=start_index):
        if i % every == 0:
            check_finite_batch(batch, batch_index=i)
        yield batch


def validate_first_batch(batch: Dict, cfg, model) -> None:
    """``fit()``'s one check of its first batch: a token model's batch
    against ``data.seq_len`` and the vocabulary rows the model holds, an
    image model's against ``data.image_size`` (and depth)."""
    from ..models import kind_of

    kind_of(model).check_first_batch(batch, cfg)


def validate_token_batch(batch: Dict, seq_len: int, vocab: int) -> None:
    """The token model's first-batch check: ``tokens`` / ``targets`` are
    [B, seq_len] integers inside the vocabulary slice, and the targets
    are the tokens shifted by one."""
    for k in ("tokens", "targets"):
        v = batch.get(k)
        if v is None:
            raise ValueError(f"batch is missing {k!r}")
        v = np.asarray(v)
        if v.ndim != 2 or v.shape[1] != int(seq_len) \
                or not np.issubdtype(v.dtype, np.integer):
            raise ValueError(f"{k} is {v.dtype}{v.shape}, not integers "
                             f"[B, {seq_len}]")
        if v.min() < 0 or v.max() >= vocab:
            raise ValueError(f"{k} ids span [{v.min()}, {v.max()}], outside "
                             f"the {vocab} rows of the vocabulary held")
    if not np.array_equal(np.asarray(batch["tokens"])[:, 1:],
                          np.asarray(batch["targets"])[:, :-1]):
        raise ValueError("targets are not the tokens shifted by one")
