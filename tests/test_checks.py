"""Input sanitation (utils/checks.py)."""

import numpy as np
import pytest

from distributed_sod_project_tpu.configs import apply_overrides, get_config
from distributed_sod_project_tpu.models import build_model
from distributed_sod_project_tpu.utils.checks import (validate_batch,
                                                      validate_first_batch)


def _good(b=2, hw=16, depth=False):
    out = {
        "image": np.random.default_rng(0).normal(size=(b, hw, hw, 3)
                                                 ).astype(np.float32),
        "mask": (np.random.default_rng(1).random((b, hw, hw, 1)) > 0.5
                 ).astype(np.float32),
    }
    if depth:
        out["depth"] = np.zeros((b, hw, hw, 1), np.float32)
    return out


def _first(batch, config):
    """``fit()``'s check of its first batch, through the model's kind."""
    cfg = apply_overrides(get_config(config), ["data.image_size=16,16"])
    validate_first_batch(batch, cfg, build_model(cfg.model))


def test_good_batch_passes():
    _first(_good(), "minet_vgg16_ref")
    _first(_good(depth=True), "hdfnet_rgbd")


@pytest.mark.parametrize("breaker,match", [
    (lambda b: b.pop("mask"), "missing 'mask'"),
    (lambda b: b.__setitem__("image", b["image"][:, :8]), "image shape"),
    (lambda b: b["image"].__setitem__((0, 0, 0, 0), np.nan), "non-finite"),
    (lambda b: b.__setitem__("mask", b["mask"] * 255.0), "range"),
    (lambda b: b.__setitem__("mask", b["mask"] * 0.5 + 0.25), "not binary"),
])
def test_bad_batches_fail_loudly(breaker, match):
    b = _good()
    breaker(b)
    with pytest.raises(ValueError, match=match):
        validate_batch(b, (16, 16))


def test_all_zero_mask_warns():
    b = _good()
    b["mask"] = np.zeros_like(b["mask"])
    with pytest.warns(UserWarning, match="wrong mask directory"):
        validate_batch(b, (16, 16))


def test_missing_depth_fails():
    with pytest.raises(ValueError, match="missing 'depth'"):
        _first(_good(), "hdfnet_rgbd")
    with pytest.raises(ValueError, match="missing 'image'"):
        _first({"tokens": np.zeros((2, 16), np.int32)}, "minet_vgg16_ref")
