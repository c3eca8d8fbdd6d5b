"""The benchmark's own weights for the hybrid token model (Mamba-2,
attention and latent sparse-expert layers): made on the device in one
jitted call from ``--seed``, for whatever tree the program declares.
The rules of ``harness/weights_lm.py`` and ``harness/weights_ssm.py``
for the leaves each covers, in one recipe because each of those raises
on the other's leaves:

- projection kernels ``[in, out]`` (``kernel``; the depthwise conv's
  taps ``[L, D]`` and the input embedding ``embed/kernel`` ``[V, 1, D]``
  fall under it): normal, std ``in ** -0.5``;
- stacked expert weights ``up`` / ``down`` ``[E, in, out]``: the same
  std PER EXPERT;
- the untied head ``head/embedding`` ``[V, D]``: std ``D ** -0.5``;
- every norm scale and ``D`` 1; the conv's ``bias`` 0;
- ``A_log`` [H]: ``log A``, ``A`` uniform in [1, 16]; ``dt_bias`` [H]:
  the inverse softplus of a step size log-uniform in
  [``time_step_min``, ``time_step_max``] and not under
  ``time_step_floor`` (the configuration's, as published);
- ``expert_bias`` (a buffer the step updates): normal, std
  ``recipe["expert_bias_std"]``.

Program and reference are handed the same arrays; neither makes any.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import _path
from .weights_ssm import A_RANGE


def variables_builder(shapes, recipe: dict, sharding=None):
    """``shapes``: {"params", "batch_stats"} of ShapeDtypeStructs.
    Returns ``make(seed)`` -> the same tree filled, float32, placed by
    ``sharding``; one compiled program however often it is called."""
    bias_std = float(recipe.get("expert_bias_std", 0.01))
    dt_lo, dt_hi = float(recipe["time_step_min"]), float(
        recipe["time_step_max"])
    dt_floor = float(recipe["time_step_floor"])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path(p) for p, _ in leaves]

    def build(key):
        out = []
        for i, (name, (_, leaf)) in enumerate(zip(names, leaves)):
            k = jax.random.fold_in(key, i)
            shape, last = leaf.shape, name.rsplit("/", 1)[-1]
            if last in ("scale", "D"):
                v = jnp.ones(shape)
            elif last == "bias":
                v = jnp.zeros(shape)
            elif last == "A_log":
                v = jnp.log(jax.random.uniform(k, shape, minval=A_RANGE[0],
                                               maxval=A_RANGE[1]))
            elif last == "dt_bias":
                dt = jnp.maximum(jnp.exp(jax.random.uniform(
                    k, shape, minval=math.log(dt_lo),
                    maxval=math.log(dt_hi))), dt_floor)
                v = dt + jnp.log(-jnp.expm1(-dt))
            elif last == "expert_bias":
                v = jax.random.normal(k, shape) * bias_std
            elif last == "embedding":
                v = jax.random.normal(k, shape) * shape[-1] ** -0.5
            elif (last == "kernel" and len(shape) in (2, 3)) \
                    or (last in ("up", "down") and len(shape) == 3):
                v = jax.random.normal(k, shape) * shape[-2] ** -0.5
            else:
                raise ValueError(f"weights recipe has no rule for {name!r}")
            out.append(v.astype(jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    jitted = jax.jit(build, out_shardings=sharding)
    return lambda seed: jitted(jax.random.key(seed % (2 ** 31 - 1)))
