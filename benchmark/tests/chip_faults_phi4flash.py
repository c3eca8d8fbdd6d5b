#!/usr/bin/env python3
"""On the chip, at the decoder-hybrid-decoder cell's own size: each fault
of ``phi4flash_faults.py`` planted on the program, one run each in ONE
process, and the numbers ``correct`` compares.  Not run by the
benchmark's runs.

    python benchmark/tests/chip_faults_phi4flash.py --seed 4700000301 \
        --seconds 4 [--faults window_twice_as_long,one_decay_a_channel]

Every row goes to ``chiprun_out/faults_<cell>.jsonl``; a fault that
passes every limit is a line for PERF.md, not an error here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "phi4_mini_flash_pp5.train_s16k_b1"


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=4.0)
    p.add_argument("--faults", default="")
    a = p.parse_args()
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    from benchmark import run as harness
    from benchmark.tests.phi4flash_faults import FAULTS, plant

    entry, _, _ = harness.resolve(harness.load_manifest(), CELL)
    device = harness.find_chips(entry["chips"])
    harness.setup_jax_cache()
    os.makedirs("chiprun_out", exist_ok=True)
    out = os.path.join("chiprun_out", f"faults_{CELL}.jsonl")
    for i, fault in enumerate(a.faults.split(",") if a.faults else FAULTS):
        undo = []

        def patch(obj, name, value):
            undo.append((obj, name, getattr(obj, name)))
            setattr(obj, name, value)

        t0 = time.perf_counter()
        try:
            line = harness.run_cell(
                CELL, a.seed + i, a.seconds, False, device=device,
                t_start=t0, extra_overrides=plant(fault, patch))
        finally:
            for obj, name, value in reversed(undo):
                setattr(obj, name, value)
        rec = {"fault": fault, "seed": a.seed + i,
               "correct": line["correct"],
               "failed_rows": [n for n, _, _, ok in line["compared"]
                               if not ok],
               "rows": {n: v for n, v, _, _ in line["compared"]},
               "seconds": time.perf_counter() - t0}
        print("FAULT", json.dumps(rec), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
