#!/usr/bin/env python
"""Benchmark the whole model zoo in one command → markdown table.

    python tools/bench_zoo.py --device tpu --out BENCH_ZOO.md
    python tools/bench_zoo.py --device cpu --steps 2 --warmup 1 \
        --batch-per-chip 1 --image-size 64        # CI smoke

Runs ``bench.py`` once per (config, mode) in a SUBPROCESS each — a
crashed config (OOM) must not take down the sweep — and renders one
markdown table of images/sec/chip.  Rows that fail record the error
instead of a number.

One process per chip: the cells run one after another, and THIS parent
must stay off JAX — a parent that has touched a backend holds the chip
and every cell would then fail to get it.  ``import bench`` is safe
(bench.py imports jax only inside ``_run``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from bench import DEFAULT_BATCH as _DEFAULT_BATCH  # noqa: E402
from bench import PER_CONFIG_BATCH as ZOO_BATCH  # noqa: E402

ZOO = [
    "minet_vgg16_ref",
    "minet_r50_dp",
    "hdfnet_rgbd",
    "u2net_ds",
    "basnet_ds",
    "gatenet_vgg16",
    "swin_sod",
    "vit_sod_sp",
]

# Per-config batch/chip lives in bench.py (PER_CONFIG_BATCH) so direct
# bench runs and zoo sweeps default identically.


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--device", default=None, choices=["tpu", "cpu", None])
    p.add_argument("--modes", default="train,eval",
                   help="comma list of bench modes (train,eval,data)")
    p.add_argument("--configs", default=None,
                   help="comma list (default: the whole zoo)")
    p.add_argument("--exclude", default=None,
                   help="comma list of configs to drop from the sweep "
                        "(e.g. swin_sod, whose eval kills the TPU "
                        "worker) — applied after --configs, so sweeps "
                        "can run 'the zoo minus X' without restating "
                        "the zoo membership")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--batch-per-chip", type=int, default=None,
                   help="override the per-config default")
    p.add_argument("--image-size", type=int, default=320)
    p.add_argument("--timeout", type=int, default=1800,
                   help="seconds per (config, mode) subprocess")
    p.add_argument("--out", default=None, help="write the table here too")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="PATH=VALUE", help="forwarded to every run")
    return p.parse_args(argv)


def run_one(cfg_name, mode, args):
    cmd = [sys.executable, os.path.join(_REPO, "bench.py"),
           "--config", cfg_name, "--mode", mode,
           "--steps", str(args.steps), "--warmup", str(args.warmup),
           "--image-size", str(args.image_size)]
    if args.device:
        cmd += ["--device", args.device]
    batch = (args.batch_per_chip if args.batch_per_chip is not None
             else ZOO_BATCH.get(cfg_name, _DEFAULT_BATCH))
    cmd += ["--batch-per-chip", str(batch)]
    for ov in args.overrides:
        cmd += ["--set", ov]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=args.timeout, cwd=_REPO)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {args.timeout}s"}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if not isinstance(parsed, dict):
            continue
        if proc.returncode == 0 and "value" in parsed:
            return parsed
        if "error" in parsed:  # bench.py's failure line (rc != 0)
            return {"error": parsed["error"][:200]}
    tail = (proc.stderr or proc.stdout).strip().splitlines()
    return {"error": tail[-1][:200] if tail else f"rc={proc.returncode}"}


def main(argv=None):
    args = parse_args(argv)
    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    zoo = list(ZOO)
    if args.configs:
        # Keep the zoo's order for known names; append unknown names so
        # a typo surfaces as a visible ERR row, never a silent drop.
        wanted = [c.strip() for c in args.configs.split(",") if c.strip()]
        zoo = ([c for c in ZOO if c in wanted]
               + [c for c in wanted if c not in ZOO])
    if args.exclude:
        dropped = {c.strip() for c in args.exclude.split(",") if c.strip()}
        unknown = dropped - set(zoo)
        if unknown:
            # Loud, like a typo'd --configs: a silently ignored
            # exclusion would run the very config the caller meant to
            # keep off the hardware (swin_sod's eval kills the worker).
            print(f"--exclude names not in the sweep: {sorted(unknown)} "
                  f"(sweep: {zoo})", file=sys.stderr)
            return 1
        zoo = [c for c in zoo if c not in dropped]

    def render(results):
        lines = [f"| config | {' | '.join(modes)} |",
                 f"|---|{'---|' * len(modes)}"]
        for cfg_name in zoo:
            cells = []
            for mode in modes:
                r = results.get((cfg_name, mode))
                if r is None:
                    cells.append("…")
                else:
                    cells.append(f"{r['value']:g}" if "value" in r
                                 else f"ERR: {r['error']}")
            lines.append(f"| {cfg_name} | {' | '.join(cells)} |")
        unit = next((r["unit"] for r in results.values() if "unit" in r),
                    "images/sec/chip")
        return "\n".join(lines) + f"\n\n(all numbers {unit}; " \
            f"{args.image_size}px, steps={args.steps})\n"

    results = {}
    for cfg_name in zoo:
        for mode in modes:
            print(f"… {cfg_name} [{mode}]", file=sys.stderr, flush=True)
            r = run_one(cfg_name, mode, args)
            results[(cfg_name, mode)] = r
            # Emit each row the moment it lands (stderr, like the
            # progress dots) AND flush the partial table to --out: a
            # sweep killed by an outer timeout must not take its
            # finished measurements with it — round 2 lost the first
            # real-TPU zoo table exactly this way and the numbers had
            # to be dug out of bench_baseline.json seeds.
            print(f"  {cfg_name} [{mode}] -> {json.dumps(r)}",
                  file=sys.stderr, flush=True)
            if args.out:
                with open(args.out, "w") as f:
                    f.write(render(results))

    table = render(results)
    print(table)
    if args.out:
        with open(args.out, "w") as f:
            f.write(table)
    return 0 if all("value" in r for r in results.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
