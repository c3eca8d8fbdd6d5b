"""Directory-format SOD dataset loaders (SURVEY.md §2 C7).

Layouts supported (the idiomatic public-release layouts for these
datasets; the reference mount was unreadable, see SURVEY.md banner):

- DUTS:   ``<root>/DUTS-TR-Image/*.jpg`` + ``<root>/DUTS-TR-Mask/*.png``
          (or generically ``<root>/{Image,Mask}/``)
- RGB-D (NJU2K/NLPR): ``<root>/{RGB,depth,GT}/`` with matching stems.

Decoding + geometric transforms run host-side (XLA graphs stay static at
the configured size, SURVEY.md §7.3 hard part 5).  The heavy per-image
work (resize, normalize) is dispatched to the C++ runtime in
``native/`` when built, else falls back to PIL/numpy.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .synthetic import SyntheticSOD

_IMG_EXTS = (".jpg", ".jpeg", ".png", ".bmp")


def _index_dir(d: str) -> Dict[str, str]:
    out = {}
    for fn in sorted(os.listdir(d)):
        stem, ext = os.path.splitext(fn)
        if ext.lower() in _IMG_EXTS:
            out[stem] = os.path.join(d, fn)
    return out


def _find_subdir(root: str, candidates: Sequence[str]) -> Optional[str]:
    for c in candidates:
        p = os.path.join(root, c)
        if os.path.isdir(p):
            return p
    # Fuzzy: any subdir whose name ends with the candidate suffix.
    try:
        subdirs = sorted(
            d for d in os.listdir(root) if os.path.isdir(os.path.join(root, d))
        )
    except FileNotFoundError:
        return None
    for c in candidates:
        for d in subdirs:
            if d.lower().endswith(c.lower()):
                return os.path.join(root, d)
    return None


class FolderSOD:
    """Image/mask(/depth) triplets from a directory tree."""

    def __init__(
        self,
        root: str,
        image_size: Tuple[int, int] = (320, 320),
        use_depth: bool = False,
        normalize_mean: Tuple[float, float, float] = (0.485, 0.456, 0.406),
        normalize_std: Tuple[float, float, float] = (0.229, 0.224, 0.225),
        keep_original_size: bool = False,
    ):
        self.root = root
        self.image_size = image_size
        self.use_depth = use_depth
        self.mean = np.asarray(normalize_mean, np.float32)
        self.std = np.asarray(normalize_std, np.float32)
        self.keep_original_size = keep_original_size

        img_dir = _find_subdir(root, ["Image", "RGB", "Img", "images", "DUTS-TR-Image", "DUTS-TE-Image"])
        mask_dir = _find_subdir(root, ["Mask", "GT", "gt", "masks", "DUTS-TR-Mask", "DUTS-TE-Mask"])
        if img_dir is None or mask_dir is None:
            raise FileNotFoundError(
                f"could not locate Image/ and Mask/ (or RGB/ and GT/) under {root!r}"
            )
        imgs, masks = _index_dir(img_dir), _index_dir(mask_dir)
        stems = sorted(set(imgs) & set(masks))

        self.depth_paths: Optional[Dict[str, str]] = None
        if use_depth:
            depth_dir = _find_subdir(root, ["depth", "Depth", "depths"])
            if depth_dir is None:
                raise FileNotFoundError(f"use_depth=True but no depth/ under {root!r}")
            self.depth_paths = _index_dir(depth_dir)
            stems = sorted(set(stems) & set(self.depth_paths))

        if not stems:
            raise FileNotFoundError(f"no paired samples under {root!r}")
        self.stems: List[str] = stems
        self.img_paths = imgs
        self.mask_paths = masks

    def __len__(self) -> int:
        return len(self.stems)

    def _load(self, path: str, gray: bool) -> np.ndarray:
        from PIL import Image

        with Image.open(path) as im:
            im = im.convert("L" if gray else "RGB")
            if not self.keep_original_size:
                h, w = self.image_size
                im = im.resize((w, h), Image.BILINEAR)
            arr = np.asarray(im, dtype=np.float32) / 255.0
        if gray:
            arr = arr[..., None]
        return arr

    def __getitem__(self, index: int) -> Dict[str, np.ndarray]:
        stem = self.stems[index]
        img = self._load(self.img_paths[stem], gray=False)
        img = (img - self.mean) / self.std
        mask = self._load(self.mask_paths[stem], gray=True)
        mask = (mask > 0.5).astype(np.float32)
        out = {"image": img, "mask": mask, "index": np.int32(index)}
        if self.depth_paths is not None:
            out["depth"] = self._load(self.depth_paths[stem], gray=True)
        return out

    def load_batch(self, indices, hflip=None) -> Optional[Dict[str, np.ndarray]]:
        """Native C++ batch decode (data/native.py); None when the
        library is unbuilt or original sizes are kept (eval path)."""
        from . import native

        if self.keep_original_size or not native.available():
            return None
        stems = [self.stems[int(i)] for i in indices]
        kw = dict(size_hw=self.image_size, hflip=hflip)
        try:
            out = {
                "image": native.decode_batch(
                    [self.img_paths[s] for s in stems], gray=False,
                    mean=self.mean, std=self.std, **kw),
                "mask": (native.decode_batch(
                    [self.mask_paths[s] for s in stems], gray=True, **kw)
                    > 0.5).astype(np.float32),
                "index": np.asarray(indices, np.int32),
            }
            if self.depth_paths is not None:
                out["depth"] = native.decode_batch(
                    [self.depth_paths[s] for s in stems], gray=True, **kw)
        except RuntimeError:
            # Format the native decoder doesn't cover (BMP, CMYK JPEG…):
            # this batch — and, via the caller's latch, the rest of the
            # run — goes down the PIL path, which handles them all.
            return None
        return out


def resolve_dataset(cfg) -> object:
    """Build a dataset from a DataConfig; falls back to synthetic when the
    configured real-dataset root is absent (no network in this env).

    An existing ``root`` always wins — a user passing ``--data-root``
    to a config whose default dataset is synthetic means the files,
    not the fallback."""
    if cfg.dataset == "packed_tokens":
        from .tokens import PackedTokens

        return PackedTokens(size=cfg.synthetic_size, seq_len=cfg.seq_len,
                            vocab=cfg.vocab)
    if cfg.root is None or not os.path.isdir(cfg.root):
        if cfg.dataset != "synthetic":
            from ..utils.logging import get_logger

            get_logger().warning(
                "dataset %r root %r not found — falling back to SYNTHETIC data; "
                "results will be meaningless for real benchmarks",
                cfg.dataset,
                cfg.root,
            )
        return SyntheticSOD(
            size=cfg.synthetic_size,
            image_size=cfg.image_size,
            use_depth=cfg.use_depth,
            normalize_mean=cfg.normalize_mean,
            normalize_std=cfg.normalize_std,
        )
    return FolderSOD(
        root=cfg.root,
        image_size=cfg.image_size,
        use_depth=cfg.use_depth,
        normalize_mean=cfg.normalize_mean,
        normalize_std=cfg.normalize_std,
    )
