"""FLOPs and bytes of the state-space token model, counted from shapes
(``harness/flops_lm.py`` is the first token model's).

``flops_per_step``: the ``dot_general`` FLOPs of forward + backward of
the plain reference (``reference/granite.py``, no remat) at the cell's
batch, by ``flops_lm.jaxpr_dot_flops``, PLUS the recurrence's own
multiply-adds: the reference runs it token by token on the VPU and has
no product to count there.  Per token, layer and head the state's
update and its read-out are 2 multiply-adds on each of ``head_dim x
state`` elements: ``4 x heads x head_dim x state`` FLOPs forward, three
times that for a step.

    python -m benchmark.harness.flops_ssm --workload granite_4_0_h_micro_pp4.train_s16k_b1

``ssd_scan_cost`` gives what one forward or one backward of ONE layer's
scan needs: the recurrence's FLOPs, and bytes with every operand and
cotangent read once and every result written once.  Both are the WORK,
whatever implements it: a smaller chunk, a fused backward or a saved
state changes neither.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os

import jax
import jax.numpy as jnp

from . import flops_lm


def recurrence_flops(tokens: int, layers: int, heads: int, head_dim: int,
                     state: int) -> float:
    """Forward + backward of the recurrence in ``layers`` layers."""
    return 3.0 * 4.0 * tokens * layers * heads * head_dim * state


def ssd_scan_cost(kind: str, batch: int, n: int, heads: int, head_dim: int,
                  state: int, itemsize: int = 2):
    """``fwd``: x, B, C, delta, A read, y written.  ``bwd``: those read
    again with dy; dx, dB, dC, d delta, dA written."""
    tokens = batch * n
    wide = tokens * heads * head_dim * itemsize     # x, y, dy, dx
    narrow = tokens * state * itemsize              # B, C, dB, dC
    step = tokens * heads * 4 + heads * 4           # delta + A, float32
    fwd = 4.0 * tokens * heads * head_dim * state
    return {"fwd": (fwd, 2 * wide + 2 * narrow + step),
            "bwd": (2 * fwd, 3 * wide + 4 * narrow + 2 * step)}[kind]


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
    model = build_model(cfg.model)
    shapes = jax.eval_shape(lambda r, t: model.init(r, t),
                            jax.random.key(0), jnp.zeros((1, 256), jnp.int32))
    shapes = {"params": shapes["params"],
              "batch_stats": shapes.get("batch_stats", {})}
    ref = importlib.import_module(
        f"benchmark.reference.{config['reference']['model']}")
    batch, n = int(cfg.global_batch_size), int(cfg.data.seq_len)
    dots = flops_lm.train_step_flops(ref, shapes, config["reference"]["arch"],
                                     batch, n)
    rec = recurrence_flops(batch * n, lm.layer_types.count("mamba"),
                           lm.ssm_heads, lm.ssm_head_dim, lm.ssm_state)
    print(json.dumps({"workload": a.workload, "flops_per_step": dots + rec,
                      "dot_general": dots, "recurrence": rec,
                      "batch": batch}))


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
