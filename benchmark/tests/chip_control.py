#!/usr/bin/env python3
"""On the chip, at a cell's own size: the numbers ``correct`` compares,
for sound runs and for the control, over several seeds in ONE process
(set-up is long).  Not run by the benchmark's own runs.

    python benchmark/tests/chip_control.py --workload <cell> --seeds 11,12,13 \
        --seconds 5 --precs f32,fp8 [--overrides serve.precision=int8 ...]

Training cells: the control is the plain reference at the next
precision down (``fp8``), judged against the float32 reference as if it
were the program.  Serving cells: the control is the program's own
next arm down (``--overrides serve.precision=int8
serve.precision_arms=bf16,int8``), compared with the same reference.
Every row goes to ``chiprun_out/control_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--precs", default="f32,fp8")
    p.add_argument("--overrides", nargs="*", default=[])
    p.add_argument("--tag", default="")
    a = p.parse_args()
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    from benchmark import run as harness

    manifest = harness.load_manifest()
    entry, _, _ = harness.resolve(manifest, a.workload)
    device = harness.find_chips(entry["chips"])
    harness.setup_jax_cache()
    os.makedirs("chiprun_out", exist_ok=True)
    out = os.path.join("chiprun_out", f"control_{a.workload}{a.tag}.jsonl")
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        line = harness.run_cell(
            a.workload, seed, a.seconds, False, device=device, t_start=t0,
            ref_precs=tuple(a.precs.split(",")),
            extra_overrides=list(a.overrides))
        rows = {n: v for n, v, _, _ in line["compared"]}
        steps = os.path.join("benchmark", "out", a.workload,
                             "first_steps.json")
        if os.path.isfile(steps):  # every leaf, for whoever sets a limit
            shutil.copy(steps, os.path.join(
                "chiprun_out", f"first_steps_{a.workload}_{seed}.json"))
        rec = {"workload": a.workload, "seed": seed, "overrides": a.overrides,
               "rows": rows, "line": line,
               "seconds": time.perf_counter() - t0}
        print("CONTROL", json.dumps(rec), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
