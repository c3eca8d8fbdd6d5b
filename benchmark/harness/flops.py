"""Counts the FLOPs one training step NEEDS: forward + backward of the
plain reference (no remat, so nothing is counted twice) at a cell's
global batch, from the shapes in its jaxpr — 2 per multiply-add of every
convolution.  Elementwise work is not counted, nor are the reference's
resize matrices (dense products standing in for a 2-tap lerp).  The
number is stored in the workload file as ``flops_per_step``:

    python -m benchmark.harness.flops --workload basnet_ds.train_b16
"""

from __future__ import annotations

import argparse
import json
import math
import os

import jax
import jax.numpy as jnp


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "conv_general_dilated":
        rhs = eqn.invars[1].aval
        out = eqn.outvars[0].aval
        dn = eqn.params["dimension_numbers"]
        k_spatial = math.prod(rhs.shape[i] for i in dn.rhs_spec[2:])
        cin = rhs.shape[dn.rhs_spec[1]]  # already per group
        macs = math.prod(out.shape) * k_spatial * cin
        # a dilated lhs (the backward of a strided conv) is mostly zeros
        macs /= math.prod(eqn.params["lhs_dilation"] or (1,))
        return 2.0 * macs
    return 0.0


def jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        total += _eqn_flops(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += jaxpr_flops(inner)
    return total


def train_step_flops(forward, shapes, loss_w, batch: int, hw) -> float:
    from ..reference import ops

    def loss_fn(params, stats, image, mask):
        return ops.hybrid_loss(
            forward({"params": params, "batch_stats": stats}, image,
                    train=True), mask, loss_w)

    sds = jax.ShapeDtypeStruct
    args = (shapes["params"], shapes["batch_stats"],
            sds((batch,) + tuple(hw) + (3,), jnp.float32),
            sds((batch,) + tuple(hw) + (1,), jnp.float32))
    return jaxpr_flops(jax.make_jaxpr(jax.grad(loss_fn))(*args).jaxpr)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    a = p.parse_args()
    from distributed_sod_project_tpu.models import build_model

    from .. import run as harness
    from ..runners.train import build_cfg
    from .correct import load_reference

    _, cell, config = harness.resolve(harness.load_manifest(), a.workload)
    cfg = build_cfg({"cell": cell, "config": config, "seed": 0})
    model = build_model(cfg.model)
    hw = tuple(cfg.data.image_size)
    shapes = jax.eval_shape(
        lambda r, i: model.init(r, i, None, train=False), jax.random.key(0),
        jnp.zeros((1,) + hw + (3,)))
    f = train_step_flops(load_reference(config["reference"]["model"]),
                         shapes, config["reference"]["loss"],
                         int(cfg.global_batch_size), hw)
    print(json.dumps({"workload": a.workload, "flops_per_step": f,
                      "batch": int(cfg.global_batch_size)}))


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
