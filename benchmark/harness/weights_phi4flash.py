"""The benchmark's own weights for the decoder-hybrid-decoder token
model (Mamba-1, differential attention, gated memory unit): made on the
device in one jitted call from ``--seed``, for whatever tree the program
declares.  ``harness/weights_ssm.py``'s rules for the leaves they cover:

- projection kernels ``[in, out]`` (``kernel``): normal, std ``in **
  -0.5``; the depthwise conv's taps ``[L, D]`` fall under it: std ``L **
  -0.5``;
- the embedding ``[V, D]`` (also the tied head): std ``D ** -0.5``;
- every norm ``scale`` 1 and ``D`` 1; every ``bias`` 0 (the conv's, the
  LayerNorms', the projections');
- ``dt_bias`` [C]: the inverse softplus of a step size log-uniform in
  [0.001, 0.1];

and the Mamba-1 and differential-attention starts for the leaves that
recipe raises on:

- ``A_log`` [C, N]: ``log(n + 1)`` for state n, in every channel
  (S4D-real);
- ``dt_proj`` [R, C]: uniform in ``+- R ** -0.5``;
- ``lambda_q1`` / ``lambda_k1`` / ``lambda_q2`` / ``lambda_k2``: normal,
  std 0.1.

Program and reference are handed the same arrays; neither makes any.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import _path
from .weights_ssm import DT_RANGE


def variables_builder(shapes, recipe: dict, sharding=None):
    """``shapes``: {"params", "batch_stats"} of ShapeDtypeStructs.
    Returns ``make(seed)`` -> the same tree filled, float32, placed by
    ``sharding``; one compiled program however often it is called."""
    del recipe  # no free number: the ranges above are the families'
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
                v = jnp.broadcast_to(
                    jnp.log(jnp.arange(1.0, shape[-1] + 1.0)), shape)
            elif last == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, minval=math.log(DT_RANGE[0]),
                    maxval=math.log(DT_RANGE[1])))
                v = dt + jnp.log(-jnp.expm1(-dt))
            elif last == "dt_proj":
                lim = shape[0] ** -0.5
                v = jax.random.uniform(k, shape, minval=-lim, maxval=lim)
            elif last.startswith("lambda_"):
                v = jax.random.normal(k, shape) * 0.1
            elif last == "embedding":
                v = jax.random.normal(k, shape) * shape[-1] ** -0.5
            elif last == "kernel" and len(shape) == 2:
                v = jax.random.normal(k, shape) * shape[-2] ** -0.5
            else:
                raise ValueError(f"weights recipe has no rule for {name!r}")
            out.append(v.astype(jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    jitted = jax.jit(build, out_shardings=sharding)
    return lambda seed: jitted(jax.random.key(seed % (2 ** 31 - 1)))
