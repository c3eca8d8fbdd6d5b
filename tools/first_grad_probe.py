#!/usr/bin/env python
"""First-gradient leaf norms of a benchmark train cell in ~4 chip-minutes.

The benchmark's own run compiles a float32 reference (250-320 s) to
judge a step; when the question is "does THIS variant of the step move
the leaves" (ROADMAP D12; PERF.md section 6, PR 26) the reference of an
earlier run of the same seed serves, because the weights and the rows
fed follow from the seed alone.  This drives the cell's ``fit()`` for 5
steps through the benchmark's ``StepTap`` (its weights, its first three
steps recorded), writes every leaf's first-gradient norm, and — given
the ``first_steps.json`` a full run of the SAME seed left in
``benchmark/out/<cell>/`` — prints the gaps the benchmark judges.

    chiprun -- python tools/first_grad_probe.py --seed 2600000103 \\
        --against first_steps.json --set loss.fused_kernel=false

The variant is whatever ``--set`` (and a program-affecting ``DSOD_*``
variable, ``utils/envvars.py``) makes of the program; one process per
variant (three ``fit()`` calls in one process exceed the host's
memory).  Needs the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", default="basnet_ds.train_b16")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--set", action="append", default=[], dest="overrides")
    p.add_argument("--against", help="first_steps.json of a full run, same seed")
    p.add_argument("--out", default="chiprun_out/first_grad.json")
    args = p.parse_args(argv)
    os.chdir(ROOT)

    import jax
    import numpy as np

    from benchmark import run as brun
    from benchmark.runners import train as runner
    from distributed_sod_project_tpu.parallel import engine
    from distributed_sod_project_tpu.train.loop import fit

    manifest = brun.load_manifest()
    entry, cell, config = brun.resolve(manifest, args.workload)
    brun.find_chips(entry["chips"])
    brun.setup_jax_cache()
    cfg = runner.build_cfg(dict(cell=cell, config=config, seed=args.seed,
                                extra_overrides=args.overrides))
    taps, build = [], engine.make_unified_train_step

    def tapped(*a, **kw):
        taps.append(runner.StepTap(build(*a, **kw), args.seed, config))
        return taps[-1]

    workdir = os.path.join(ROOT, "benchmark", "out", "first_grad_probe")
    shutil.rmtree(workdir, ignore_errors=True)
    engine.make_unified_train_step = tapped
    try:
        fit(cfg, workdir=workdir, max_steps=5, hooks={})
    finally:
        engine.make_unified_train_step = build
    shutil.rmtree(workdir, ignore_errors=True)
    (tap,) = taps
    flat = jax.tree_util.tree_flatten_with_path(tap.grad_norms)[0]
    out = {"seed": args.seed, "overrides": args.overrides,
           "leaf": [runner._path(k) for k, _ in flat],
           "grad_norms": [float(v) for _, v in flat], "loss": tap.loss}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(f"first-steps loss {tap.loss}; wrote {args.out}")
    if args.against:
        with open(args.against) as f:
            ref = json.load(f)
        if ref["seed"] != args.seed or ref["leaf"] != out["leaf"]:
            print("--against is of another seed or model: no comparison")
            return 2
        want = np.asarray(ref["grad_norms"][1])
        gap = np.abs(np.asarray(out["grad_norms"]) - want) / np.maximum(
            want, 1e-30)
        judged = [i for i, n in enumerate(out["leaf"])
                  if re.search(cell.get("grad_leaves", "$^"), n)]
        print(f"reference losses {ref['loss'][1]}")
        print(f"median leaf gap: judged ({len(judged)} leaves) "
              f"{np.median(gap[judged]):.4f}, worst {gap[judged].max():.4f}; "
              f"all {np.median(gap):.1f}; leaves > 100x too large "
              f"{int((gap > 99).sum())} of {len(gap)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
