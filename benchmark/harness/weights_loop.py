"""The benchmark's own weights for the looped token model: made on the
device in one jitted call from ``--seed``, for whatever tree the program
declares.  ``harness/weights_lm.py``'s rules for the leaves they cover:

- projection kernels ``[in, out]`` (``kernel``): normal, std ``in **
  -0.5``; the exit gate's ``[hidden, 1]`` falls under it, so a gate
  logit over a normed state starts near N(0, 1);
- the input embedding ``embed/kernel`` ``[V, 1, D]`` by the same rule
  (``in`` = 1): std 1, a row of the size the sandwich-normed branches
  add to it (PERF.md section 6, PR 35: why not norm 1);
- the untied head ``head/embedding`` ``[V, D]``: std ``D ** -0.5``, so
  that the first logits are O(1) and each pass's first loss is near
  ``ln V``;
- every norm scale 1;

and the one leaf that recipe raises on: the exit gate's ``bias``: 0.

Program and reference are handed the same arrays; neither makes any.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .weights import _path


def variables_builder(shapes, recipe: dict, sharding=None):
    """``shapes``: {"params", "batch_stats"} of ShapeDtypeStructs.
    Returns ``make(seed)`` -> the same tree filled, float32, placed by
    ``sharding``; one compiled program however often it is called."""
    del recipe  # no free number
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path(p) for p, _ in leaves]

    def build(key):
        out = []
        for i, (name, (_, leaf)) in enumerate(zip(names, leaves)):
            k = jax.random.fold_in(key, i)
            shape, last = leaf.shape, name.rsplit("/", 1)[-1]
            if last == "scale":
                v = jnp.ones(shape)
            elif last == "bias":
                v = jnp.zeros(shape)
            elif last == "embedding":
                v = jax.random.normal(k, shape) * shape[-1] ** -0.5
            elif last == "kernel" and len(shape) in (2, 3):
                v = jax.random.normal(k, shape) * shape[-2] ** -0.5
            else:
                raise ValueError(f"weights recipe has no rule for {name!r}")
            out.append(v.astype(jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    jitted = jax.jit(build, out_shardings=sharding)
    return lambda seed: jitted(jax.random.key(seed % (2 ** 31 - 1)))
