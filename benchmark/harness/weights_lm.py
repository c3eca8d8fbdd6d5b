"""The benchmark's own weights for the token model: made on the device
in one jitted call from ``--seed``, for whatever tree the program
declares (``harness/weights.py`` is the image models' recipe).

- projection kernels ``[in, out]``: normal, std ``in ** -0.5``;
- stacked expert weights ``[E, in, out]``: the same std PER EXPERT
  (``in ** -0.5``), not over the stacked axis;
- the short convolution's taps ``[L, D]``: std ``L ** -0.5``;
- the embedding ``[V, D]`` (also the tied head): std ``D ** -0.5``, so
  that the first logits are O(1) and the first loss is near ``ln V``;
- every norm scale 1;
- ``expert_bias`` (a buffer, not a parameter): normal, std
  ``recipe["expert_bias_std"]`` — the published model learns it by a
  rule of its own; here it is a seeded constant that only selects.

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
    bias_std = float(recipe.get("expert_bias_std", 0.01))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path(p) for p, _ in leaves]

    def build(key):
        out = []
        for i, (name, (_, leaf)) in enumerate(zip(names, leaves)):
            k = jax.random.fold_in(key, i)
            shape, last = leaf.shape, name.rsplit("/", 1)[-1]
            if last == "scale":
                v = jnp.ones(shape)
            elif last == "expert_bias":
                v = jax.random.normal(k, shape) * bias_std
            elif last == "embedding":
                v = jax.random.normal(k, shape) * shape[-1] ** -0.5
            elif last in ("kernel", "gate", "up", "down") \
                    and len(shape) in (2, 3):
                v = jax.random.normal(k, shape) * shape[-2] ** -0.5
            else:
                raise ValueError(f"weights recipe has no rule for {name!r}")
            out.append(v.astype(jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    jitted = jax.jit(build, out_shardings=sharding)
    return lambda seed: jitted(jax.random.key(seed % (2 ** 31 - 1)))
