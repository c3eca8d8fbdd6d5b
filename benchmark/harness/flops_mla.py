"""FLOPs and bytes of the latent-attention token model, counted from
shapes (``harness/flops_lm.py`` is the first token model's).

``flops_per_step``: the ``dot_general`` FLOPs of forward + backward of
the plain reference (``reference/kimi.py``, no remat) at the cell's
batch, by ``flops_lm.jaxpr_dot_flops``.  The reference runs every held
expert over every token; under balanced routing a token meets ``top_k *
experts_held / experts`` held experts, which here is no whole number
(6 x 8 / 64 = 0.75).  So the reference is traced with NO held expert
and with ONE, and a step needs ``F(0) + share * (F(1) - F(0))``.

    python -m benchmark.harness.flops_mla --workload kimi_vl_a3b_ep8.train_s16k_b2

``flash_mla_cost`` gives what ONE CALL of each latent-attention kernel
needs: operations over the n(n+1)/2 pairs on or under the diagonal, and
bytes with every operand read once and every result written once — the
rotary key once per token, not per head.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os

import jax
import jax.numpy as jnp

from . import flops_lm


def _with_experts(shapes, held: int):
    """The tree with every stacked expert weight cut to ``held``."""
    def cut(path, leaf):
        name = jax.tree_util.keystr(path)
        if leaf.ndim == 3 and "'moe'" in name:
            return jax.ShapeDtypeStruct((held,) + leaf.shape[1:], leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(cut, shapes)


def train_step_flops(ref, shapes, model: dict, batch: int, seq_len: int,
                     share: float) -> float:
    """``shapes``: {"params", "batch_stats"} of ShapeDtypeStructs with at
    least one held expert; ``share``: held experts a token meets."""
    f0, f1 = (flops_lm.train_step_flops(ref, _with_experts(shapes, held),
                                        model, batch, seq_len)
              for held in (0, 1))
    return f0 + share * (f1 - f0)


def flash_mla_cost(kind: str, batch: int, heads: int, n: int, dn: int,
                   dr: int, dv: int, itemsize: int = 2):
    """One call over [batch, heads, n] queries of dn + dr columns, keys
    ``[k_nope (dn) per head ; k_rope (dr) ONE head]``, values of dv.
    ``fwd``: q k^T over dn + dr and p v over dv.  ``dq``: q k^T again,
    do v^T, ds k.  ``dkv``: q k^T again, p^T do, do v^T, ds^T q."""
    dk = dn + dr
    cols = {"fwd": dk + dv, "dq": 2 * dk + dv, "dkv": 2 * dk + 2 * dv}[kind]
    flops = 2.0 * batch * heads * flops_lm._causal_pairs(n) * cols
    per_head = batch * heads * n * itemsize
    q, k_nope, v_like = per_head * dk, per_head * dn, per_head * dv
    k_rope = batch * n * dr * itemsize
    lse = batch * heads * n * 4
    reads = q + k_nope + k_rope + v_like          # q, k, v
    nbytes = {"fwd": reads + v_like + lse,                    # -> o, lse
              "dq": reads + 2 * v_like + lse + q,             # do, o -> dq
              "dkv": reads + 2 * v_like + lse                 # -> dk, dv
              + k_nope + k_rope + v_like}[kind]
    return flops, nbytes


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    a = p.parse_args()
    from distributed_sod_project_tpu.configs import apply_overrides
    from distributed_sod_project_tpu.models import build_model

    from .. import run as harness
    from ..runners.train import build_cfg

    _, cell, config = harness.resolve(harness.load_manifest(), a.workload)
    cfg = build_cfg({"cell": cell, "config": config, "seed": 0})
    lm = cfg.model.lm
    share = lm.top_k * lm.experts_held / lm.experts
    model = build_model(apply_overrides(
        cfg, ["model.lm.experts_held=1"]).model)
    shapes = jax.eval_shape(lambda r, t: model.init(r, t),
                            jax.random.key(0), jnp.zeros((1, 128), jnp.int32))
    ref = importlib.import_module(
        f"benchmark.reference.{config['reference']['model']}")
    f = train_step_flops(ref, shapes, config["reference"]["arch"],
                         int(cfg.global_batch_size), int(cfg.data.seq_len),
                         share)
    print(json.dumps({"workload": a.workload, "flops_per_step": f,
                      "batch": int(cfg.global_batch_size),
                      "experts_counted": share}))


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
