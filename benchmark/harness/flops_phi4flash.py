"""FLOPs and bytes of the decoder-hybrid-decoder token model, counted
from shapes (``harness/flops_lm.py`` is the first token model's,
``harness/flops_ssm.py`` the Mamba-2 one's).

``flops_per_step``: the ``dot_general`` FLOPs of forward + backward of
the plain reference (``reference/phi4flash.py``, no remat) at the cell's
batch, by ``flops_lm.jaxpr_dot_flops`` — its attention multiplies each
block of query rows against the keys the mask admits alone, so a window
layer counts its band and a full layer its triangle — PLUS the
recurrence's own multiply-adds: the reference runs it token by token on
the VPU and has no product to count there.  Per token, layer, channel
and state the update and the read-out are 2 multiply-adds: ``4 x
channels x state`` FLOPs forward, three times that for a step.

    python -m benchmark.harness.flops_phi4flash --workload phi4_mini_flash_pp5.train_s16k_b1

``selective_scan_cost`` and ``diff_attention_cost`` give what one
forward or one backward of ONE layer's scan or attention needs:
operations, and bytes with every operand and cotangent read once and
every result written once.  Both are the WORK, whatever implements it:
a smaller chunk, a fused or split backward, a saved state, one kernel
call a layer or four change neither.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os

import jax
import jax.numpy as jnp

from . import flops_lm


def recurrence_flops(tokens: int, layers: int, channels: int,
                     state: int) -> float:
    """Forward + backward of the recurrence in ``layers`` layers."""
    return 3.0 * 4.0 * tokens * layers * channels * state


def selective_scan_cost(kind: str, batch: int, n: int, channels: int,
                        state: int, itemsize: int = 2):
    """``fwd``: x, delta, B, C, A, D read, y written.  ``bwd``: those
    read again with dy; dx, d delta, dB, dC, dA, dD written.  x, y and
    their cotangents in the operands' type, delta, A and D float32."""
    tokens = batch * n
    wide = tokens * channels * itemsize           # x, y, dy, dx
    step = tokens * channels * 4                  # delta, d delta
    narrow = tokens * state * itemsize            # B, C, dB, dC
    small = channels * (state + 1) * 4            # A and D, dA and dD
    fwd = 4.0 * tokens * channels * state
    return {"fwd": (fwd, 2 * wide + step + 2 * narrow + small),
            "bwd": (2 * fwd, 3 * wide + 2 * step + 4 * narrow + 2 * small)
            }[kind]


def seen_entries(n: int, window: int = 0) -> float:
    """Score entries a causal mask admits over ``n`` tokens; a query
    sees its last ``window`` keys alone (0: all of them)."""
    if not window or window >= n:
        return n * (n + 1) / 2.0
    return window * (window + 1) / 2.0 + (n - window) * float(window)


def diff_attention_cost(kind: str, batch: int, heads: int, kv_heads: int,
                        n: int, d: int, window: int = 0, itemsize: int = 2):
    """Both softmax maps of every pair of ONE differential layer:
    ``heads`` query heads (``heads`` maps), ``kv_heads`` key heads of
    ``d`` columns, ``kv_heads / 2`` values of ``2 d``.  ``fwd``: q k^T
    and p v over the entries the mask admits.  ``bwd``: q k^T again, do
    v^T, p^T do, ds^T q, ds k.  2 per multiply-add."""
    dv = 2 * d
    entries = batch * heads * seen_entries(n, window)
    q_like = batch * heads * n * d * itemsize       # q, dq
    k_like = batch * kv_heads * n * d * itemsize    # k, dk
    v_like = batch * (kv_heads // 2) * n * dv * itemsize
    o_like = batch * heads * n * dv * itemsize      # o, do
    lse = batch * heads * n * 4
    return {"fwd": (2.0 * entries * (d + dv),
                    q_like + k_like + v_like + o_like + lse),
            "bwd": (2.0 * entries * (3 * d + 2 * dv),
                    2 * (q_like + k_like + v_like + o_like) + lse)}[kind]


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
                           lm.ssm_heads * lm.ssm_head_dim, lm.ssm_state)
    print(json.dumps({"workload": a.workload, "flops_per_step": dots + rec,
                      "dot_general": dots, "recurrence": rec,
                      "batch": batch}))


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
