#!/usr/bin/env python3
"""The ONE sweep on the chip that fixes a serving cell's rate and
latency limit: the system is brought up once and offered each rate in
turn, open loop, for ``--seconds``.  Rows go to
``chiprun_out/sweep_<cell>.jsonl`` and into PERF.md.

    python benchmark/tests/chip_sweep.py --workload minet_r50_dp.serve_steady \
        --rates 10,20,40,60,80,100,120,160,200 --seconds 15
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--seed", type=int, default=2600000001)
    a = p.parse_args()
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    from benchmark import run as harness
    from benchmark.harness import loadgen
    from benchmark.runners import serve as runner

    entry, cell, config = harness.resolve(harness.load_manifest(), a.workload)
    harness.find_chips(entry["chips"])
    harness.setup_jax_cache()
    cfg = runner.build_cfg({"cell": cell, "config": config, "seed": a.seed})
    sut = runner.Served(cfg, a.seed, config, cell)
    os.makedirs("chiprun_out", exist_ok=True)
    out = os.path.join("chiprun_out", f"sweep_{a.workload}.jsonl")
    warm = float(cell["warmup_s"])
    for k, rate in enumerate(float(r) for r in a.rates.split(",")):
        window, gen, _ = sut.drive(rate, a.seconds, a.seed + k)
        summ = loadgen.summarize(window, float(cell["timeout_s"]),
                                 float(cell["limit_ms"]), a.seconds)
        summ["rate"] = rate
        summ["unfinished_at_close"] = sum(
            1 for r in window
            if r["due"] + r["latency_ms"] / 1000.0 > warm + a.seconds)
        ok = [r for r in window if r["timing"]]
        if ok:
            med = lambda k: sorted(r["timing"][k] for r in ok)[len(ok) // 2]  # noqa: E731
            summ.update(queue_p50_ms=med("queue"), device_p50_ms=med("device"))
        print("SWEEP", json.dumps(summ), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(summ) + "\n")
        time.sleep(2.0)  # let the queue drain before the next rate
    print("engine:", sut.close(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
