#!/usr/bin/env python
"""Micro-benchmark the Pallas flash-attention kernel vs the XLA core.

First real-v5e capture (round 2) showed the 128/128-block default
2.2x SLOWER than XLA's materialised attention on vit_sod shapes
(N=1024, D=64) — at short N the online-softmax VPU work dominates the
tiny per-tile dots.  This sweeps block shapes on the hardware so the
defaults can be set from measurement, not folklore:

    python tools/bench_flash.py --shape 12,1024,64
    python tools/bench_flash.py --shape 12,4096,64 --no-xla   # long N
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def time_fn(f, *args, iters=20):
    out = f(*args)  # compile + warm
    jax.block_until_ready(out)
    # Host fetch of a value depending on the result: returns only
    # when the device has finished it.
    def sync(o):
        leaf = jax.tree_util.tree_leaves(o)[0]
        return float(leaf.sum())
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    sync(out)
    return (time.perf_counter() - t0) / iters


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--shape", default="12,1024,64",
                   help="bh,n,d (batch*heads, seq, head_dim)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--blocks", default="128/128,128/512,256/512,256/1024,"
                                       "512/512,512/1024",
                   help="comma list of block_q/block_kv pairs")
    p.add_argument("--no-xla", action="store_true",
                   help="skip the XLA oracle (OOMs at long N)")
    p.add_argument("--fwd-only", action="store_true")
    args = p.parse_args(argv)

    from distributed_sod_project_tpu.pallas.flash_attention import (
        flash_attention)
    from distributed_sod_project_tpu.parallel.ring_attention import (
        resolve_attn_fn)

    bh, n, d = (int(x) for x in args.shape.split(","))
    rng = np.random.RandomState(0)
    # Both cores take [B, H, N, D]; batch*heads folded into H is
    # equivalent for attention (no cross-head mixing).
    q, k, v = (jnp.asarray(rng.randn(1, bh, n, d), jnp.bfloat16)
               for _ in range(3))

    def run(fn):
        if args.fwd_only:
            return jax.jit(fn)
        return jax.jit(jax.grad(
            lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))

    rows = []
    if not args.no_xla:
        xla = resolve_attn_fn("xla")
        dt = time_fn(run(xla), q, k, v, iters=args.iters)
        rows.append(("xla", dt))
    for pair in args.blocks.split(","):
        bq, bkv = (int(x) for x in pair.split("/"))
        if bq > n or bkv > n:
            continue
        fn = lambda q, k, v, bq=bq, bkv=bkv: flash_attention(
            q, k, v, block_q=bq, block_kv=bkv)
        try:
            dt = time_fn(run(fn), q, k, v, iters=args.iters)
        except Exception as e:  # noqa: BLE001 — sweep must survive OOMs
            print(f"flash {pair}: FAILED {type(e).__name__}: "
                  f"{str(e)[:120]}")
            continue
        rows.append((f"flash {pair}", dt))

    mode = "fwd" if args.fwd_only else "fwd+bwd"
    print(f"\nshape bh={bh} n={n} d={d}  ({mode}, {args.iters} iters)")
    base = rows[0][1] if rows else 1.0
    for name, dt in rows:
        print(f"  {name:16s} {dt * 1e3:8.3f} ms   x{base / dt:.2f}")


if __name__ == "__main__":
    raise SystemExit(main())
