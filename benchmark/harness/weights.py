"""The benchmark's own weights: made on the device in one jitted call
from ``--seed``, for whatever parameter tree the program declares.

The recipe is data (a configuration file's ``weights`` object):

- conv kernels: normal, std ``sqrt(2 / fan_in)`` (He), so that the
  second moment survives conv + ReLU in eval mode as in train mode;
  the 1-channel head kernels times ``head_gain``, so that served
  logits stay O(1) and the sigmoid is not saturated;
- BatchNorm scale 1, except where a path matches one of ``small_gamma``
  (the last BatchNorm of every residual branch), which gets ``gamma``:
  the deep stacks then stay well conditioned and the stated precision
  can be told from the next one down (PERF.md, Findings);
- BatchNorm bias normal(0, 0.1); conv biases 0;
- running mean normal(0, 0.1), running variance uniform(0.8, 1.25).

Program and reference are handed the same arrays; neither makes any.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in p)


def variables_builder(shapes, recipe: dict, sharding=None):
    """``shapes``: {"params", "batch_stats"} of ShapeDtypeStructs (the
    program's tree, e.g. from ``jax.eval_shape(model.init, ...)``).
    Returns ``make(seed)`` -> the same tree filled, float32, placed by
    ``sharding``; one compiled program however often it is called."""
    small = [re.compile(r) for r in recipe.get("small_gamma", [])]
    gamma = float(recipe.get("gamma", 1.0))
    head_gain = float(recipe.get("head_gain", 1.0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path(p) for p, _ in leaves]

    def build(key):
        out = []
        for i, (name, (_, leaf)) in enumerate(zip(names, leaves)):
            k = jax.random.fold_in(key, i)
            shape, last = leaf.shape, name.rsplit("/", 1)[-1]
            if last == "kernel":
                fan_in = 1
                for d in shape[:-1]:
                    fan_in *= d
                std = (2.0 / fan_in) ** 0.5
                if shape[-1] == 1:  # a 1-channel head: keep logits O(1)
                    std *= head_gain
                v = jax.random.normal(k, shape) * std
            elif last == "scale":
                g = gamma if any(r.search(name) for r in small) else 1.0
                v = jnp.full(shape, g)
            elif last == "bias":
                v = (jax.random.normal(k, shape) * 0.1
                     if "BatchNorm" in name else jnp.zeros(shape))
            elif last == "mean":
                v = jax.random.normal(k, shape) * 0.1
            elif last == "var":
                v = jax.random.uniform(k, shape, minval=0.8, maxval=1.25)
            else:
                raise ValueError(f"weights recipe has no rule for {name!r}")
            out.append(v.astype(jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    jitted = jax.jit(build, out_shardings=sharding)
    return lambda seed: jitted(jax.random.key(seed % (2 ** 31 - 1)))
