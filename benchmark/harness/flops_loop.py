"""FLOPs and bytes of the looped token model, counted from shapes
(``harness/flops_lm.py`` is the first token model's).

``flops_per_step``: the products a step NEEDS, 2 per multiply-add,
forward + backward (3 x the forward), nothing recomputed: every dense
product of a block times the block's VISITS (``layers x ut_steps``),
causal attention over the n(n+1)/2 pairs on or under the diagonal (q k^T
and p v), the gate, and the head once a PASS.

    python -m benchmark.harness.flops_loop \
        --workload ouro_2_6b_pp6.train_s8k_b1

``flash_causal_cost`` gives what ONE call of the causal attention core
needs: the forward (q k^T, p v: 2 products a visited pair) or the FUSED
backward (q k^T again, do v^T, p^T do, ds^T q, ds k: 5), and bytes with
every operand read once and every result written once.  It is the WORK,
whatever implements it: a kernel split in two or run twice changes
neither.
"""

from __future__ import annotations

import argparse
import json
import os


def _causal_pairs(n: int) -> float:
    return n * (n + 1) / 2.0


def block_params(hidden: int, heads: int, head_dim: int, width: int) -> int:
    """The matrix entries of one block: q, k, v, o and the SwiGLU."""
    return 4 * hidden * heads * head_dim + 3 * hidden * width


def step_flops(*, batch: int, n: int, hidden: int, heads: int, head_dim: int,
               width: int, vocab: int, layers: int, passes: int) -> dict:
    """-> {"dense", "attention", "heads", "gate", "flops_per_step"}, each
    forward + backward."""
    tokens, visits = batch * n, layers * passes
    dense = 2.0 * tokens * block_params(hidden, heads, head_dim, width) \
        * visits
    attention = 2.0 * 2 * batch * heads * _causal_pairs(n) * head_dim * visits
    head = 2.0 * tokens * vocab * hidden * passes
    gate = 2.0 * tokens * hidden * passes
    out = {"dense": 3 * dense, "attention": 3 * attention,
           "heads": 3 * head, "gate": 3 * gate}
    out["flops_per_step"] = sum(out.values())
    return out


def flash_causal_cost(kind: str, batch: int, heads: int, n: int, d: int,
                      itemsize: int = 2):
    """One call over [batch, heads, n, d] queries, keys and values (no
    grouping).  ``fwd``: q, k, v read, out and lse written.  ``bwd``: q,
    k, v, do, out, lse read, dq, dk, dv written."""
    products = {"fwd": 2, "bwd": 5}[kind]
    flops = 2.0 * products * batch * heads * _causal_pairs(n) * d
    wide = batch * heads * n * d * itemsize
    lse = batch * heads * n * 4
    return flops, {"fwd": 4 * wide + lse, "bwd": 8 * wide + lse}[kind]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    a = p.parse_args()
    from .. import run as harness
    from ..runners.train import build_cfg

    _, cell, config = harness.resolve(harness.load_manifest(), a.workload)
    cfg = build_cfg({"cell": cell, "config": config, "seed": 0})
    lm = cfg.model.lm
    out = step_flops(batch=int(cfg.global_batch_size),
                     n=int(cfg.data.seq_len), hidden=lm.hidden,
                     heads=lm.heads, head_dim=lm.head_dim,
                     width=lm.dense_width, vocab=lm.vocab,
                     layers=len(lm.layer_types), passes=lm.ut_steps)
    print(json.dumps(dict(out, workload=a.workload,
                          batch=int(cfg.global_batch_size))))


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
