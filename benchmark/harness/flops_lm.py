"""FLOPs and bytes of the token model, counted from shapes.

``flops_per_step`` (stored in the workload file, read by
``train_step_mfu``): the ``dot_general`` FLOPs of forward + backward of
the plain reference (``reference/lfm2.py``, no remat, so nothing is
counted twice) at the cell's batch — 2 per multiply-add, a ``scan``'s
body times its length.  Two things make the reference's jaxpr count
what a step NEEDS:

- its attention multiplies each block of query rows against the keys up
  to that block's last row only, so nothing above the block diagonal is
  counted (N(N + 512)/2 score columns a head, against N(N + 1)/2);
- it is traced with ONE expert held: the reference runs every held
  expert over every token, and under balanced routing a token meets
  ``top_k * experts_held / experts`` = 1 held expert, so one dense
  expert is what the sparse layer needs.  (Measured routing is within a
  few percent of balanced; ``moe_pairs_here_share`` says how far.)

Elementwise work, the router's top-k and the sort are not counted.

    python -m benchmark.harness.flops_lm --workload lfm2_8b_a1b_ep4.train_s8k_b4

The functions below it give what ONE CALL of each new kernel needs
(operations, and bytes with every operand read once and every result
written once), for the ``*_roofline`` metrics.
"""

from __future__ import annotations

import argparse
import json
import math
import os

import jax
import jax.numpy as jnp


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else (v,)):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def jaxpr_dot_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2.0 * math.prod(eqn.outvars[0].aval.shape) \
                * math.prod(lhs[i] for i in lc)
        inner = [jaxpr_dot_flops(j) for j in _sub_jaxprs(eqn)]
        if eqn.primitive.name == "cond":
            total += max(inner, default=0.0)
        else:
            total += sum(inner) * eqn.params.get("length", 1) \
                if eqn.primitive.name == "scan" else sum(inner)
    return total


def train_step_flops(ref, shapes, model: dict, batch: int, seq_len: int
                     ) -> float:
    """``shapes``: {"params", "batch_stats"} of ShapeDtypeStructs, the
    expert axis already cut to the balanced share."""
    def loss_fn(params, buffers, tokens, targets):
        return ref.batch_loss({"params": params, "batch_stats": buffers},
                              tokens, targets, model, remat=False)

    tok = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
    return jaxpr_dot_flops(jax.make_jaxpr(jax.grad(loss_fn))(
        shapes["params"], shapes["batch_stats"], tok, tok).jaxpr)


# -- one call of each new kernel -------------------------------------------

def grouped_matmul_cost(rows: float, a: int, b: int, n_experts: int,
                        itemsize: int = 2):
    """``y[rows, b] = x[rows, a] @ w[e]`` over ``n_experts`` weight
    matrices, ``rows`` routed pairs (padding rows are not work): the
    forward product and the ``dx`` product have these counts alike."""
    return (2.0 * rows * a * b,
            (rows * (a + b) + n_experts * a * b) * itemsize)


def grouped_matmul_dw_cost(rows: float, a: int, b: int, n_experts: int,
                           itemsize: int = 2):
    """``dw[e] = x_e.T @ dy_e``: the float32 result written once."""
    return (2.0 * rows * a * b,
            rows * (a + b) * itemsize + n_experts * a * b * 4)


def _causal_pairs(n: int) -> float:
    return n * (n + 1) / 2.0


def flash_causal_cost(kind: str, batch: int, heads: int, kv_heads: int,
                      n: int, d: int, itemsize: int = 2):
    """One call over [batch, heads, n, d] queries and ``kv_heads`` shared
    key/value heads.  ``fwd``: q k^T and p v.  ``dq``: q k^T again, do
    v^T, ds k.  ``dkv``: q k^T again, p^T do, do v^T, ds^T q.  2 per
    multiply-add over the n(n+1)/2 pairs on or under the diagonal."""
    dots = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    flops = 2.0 * dots * batch * heads * _causal_pairs(n) * d
    q_like = batch * heads * n * d * itemsize
    kv_like = batch * kv_heads * n * d * itemsize
    lse = batch * heads * n * 4
    nbytes = {"fwd": 2 * q_like + 2 * kv_like + lse,        # q k v -> o lse
              "dq": 4 * q_like + 2 * kv_like + lse,         # q do o k v -> dq
              "dkv": 3 * q_like + 4 * kv_like + lse}[kind]  # ... -> dk dv
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take for one call."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    a = p.parse_args()
    import importlib

    from distributed_sod_project_tpu.configs import apply_overrides
    from distributed_sod_project_tpu.models import build_model

    from .. import run as harness
    from ..runners.train import build_cfg

    _, cell, config = harness.resolve(harness.load_manifest(), a.workload)
    cfg = build_cfg({"cell": cell, "config": config, "seed": 0})
    lm = cfg.model.lm
    share, rem = divmod(lm.top_k * lm.experts_held, lm.experts)
    if rem or share < 1:
        raise SystemExit("the balanced share of held experts a token meets "
                         "is no whole number")
    cfg = apply_overrides(cfg, [f"model.lm.experts_held={share}"])
    model = build_model(cfg.model)
    shapes = jax.eval_shape(lambda r, t: model.init(r, t),
                            jax.random.key(0), jnp.zeros((1, 128), jnp.int32))
    ref = importlib.import_module(
        f"benchmark.reference.{config['reference']['model']}")
    f = train_step_flops(ref, shapes, config["reference"]["arch"],
                         int(cfg.global_batch_size), int(cfg.data.seq_len))
    print(json.dumps({"workload": a.workload, "flops_per_step": f,
                      "batch": int(cfg.global_batch_size),
                      "experts_counted": share}))


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
