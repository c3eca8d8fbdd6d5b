"""FLOPs and bytes of the hybrid token model (Mamba-2, attention and
latent sparse-expert layers), counted from shapes.

``flops_per_step``: the ``dot_general`` FLOPs of forward + backward of
the plain reference (``reference/nemotron_h.py``, no remat) at the
cell's batch, with the held experts a token meets under balanced
routing (22 x 8 / 512 = 0.34375), PLUS the recurrence's own
multiply-adds, which the reference runs token by token with no product
to count (``flops_ssm.recurrence_flops``).  The reference runs every
held expert over every token, so it is traced with ONE held expert and
with TWO and a step needs ``F(1) - (1 - share) * (F(2) - F(1))``.  (Not
``flops_mla``'s ``F(0) + share * (F(1) - F(0))``: with no expert held
the latent down-projection and the router get no gradient, and ``F(0)``
leaves their backward products out.)

    python -m benchmark.harness.flops_hybrid \
        --workload nemotron_3_super_tp8_ep64.train_s8k_b1

``latent_experts_cost`` gives what ONE expert layer's routed products
need in a step — forward and backward of ``W2 relu(W1 z)^2`` over
``rows`` routed pairs: two products forward, four backward, every
operand and cotangent read once, every result written once, each held
expert's two matrices read once a pass and their float32 gradients
written once.  It is the WORK, whatever implements it: padding rows, a
buffer multiplied whole and a forward run twice are not work.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os

import jax
import jax.numpy as jnp

from . import flops_lm, flops_mla, flops_ssm


def latent_experts_cost(kind: str, rows: float, latent: int, width: int,
                        n_experts: int, itemsize: int = 2):
    """``fwd``: z read, the hidden rows written and read, r written.
    ``bwd``: z, the hidden rows and dr read, the hidden cotangent written
    and read, dz written; dW1 and dW2 written in float32."""
    one = 2.0 * rows * latent * width             # one product
    acts = rows * (latent + width) * itemsize     # a latent + a hidden array
    weights = 2 * n_experts * latent * width
    return {"fwd": (2 * one, 2 * acts + weights * itemsize),
            "bwd": (4 * one, 4 * acts + weights * (itemsize + 4))}[kind]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    a = p.parse_args()
    from distributed_sod_project_tpu.models import build_model

    from .. import run as harness
    from ..runners.train import build_cfg

    _, cell, config = harness.resolve(harness.load_manifest(), a.workload)
    cfg = build_cfg({"cell": cell, "config": config, "seed": 0})
    lm = cfg.model.lm
    share = lm.top_k * lm.experts_held / lm.experts
    model = build_model(cfg.model)
    shapes = jax.eval_shape(lambda r, t: model.init(r, t),
                            jax.random.key(0), jnp.zeros((1, 128), jnp.int32))
    ref = importlib.import_module(
        f"benchmark.reference.{config['reference']['model']}")
    batch, n = int(cfg.global_batch_size), int(cfg.data.seq_len)
    f1, f2 = (flops_lm.train_step_flops(
        ref, flops_mla._with_experts(shapes, held),
        config["reference"]["arch"], batch, n) for held in (1, 2))
    dots = f1 - (1.0 - share) * (f2 - f1)
    rec = flops_ssm.recurrence_flops(
        batch * n, lm.layer_types.count("mamba"), lm.ssm_heads,
        lm.ssm_head_dim, lm.ssm_state)
    print(json.dumps({"workload": a.workload, "flops_per_step": dots + rec,
                      "dot_general": dots, "recurrence": rec, "batch": batch,
                      "experts_counted": share}))


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
