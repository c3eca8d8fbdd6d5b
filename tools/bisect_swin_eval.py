#!/usr/bin/env python
"""Bisect the swin_sod EVAL TPU-worker crash (round-2 session 3).

A timed eval loop of ``swin_sod`` crashed the v5e worker
twice ("kernel fault", the 2026-07-31 zoo sweep); the train step is fine,
and eval of every other zoo member is fine.  The train/eval program
differences are small and enumerable, so each stage below isolates one
of them, IN A SUBPROCESS, smallest program first:

  metrics_only    the 256-bin scatter-add metric update, no model
  backbone        SwinT forward alone (ignores train — shared by the
                  working train step)
  fwd_b1          full model, train=False, batch 1
  fwd             full model, train=False, eval batch
  fwd_trainflag   full model, train=True + mutable BN (the working
                  train step's forward, minus grad) — isolates the
                  running-average-BN vs batch-BN program difference
  eval_step       make_eval_step (shard_map + sigmoid)
  eval_metrics_nofuse  the crasher's program with XLA fusion passes
                  disabled — implicates/exonerates a fused kernel
                  (the scatter-metrics fusion suspect) in one stage
  eval_metrics    eval_step + metric update, the reproduced crasher —
                  LAST: it is the one expected to kill the worker

After any CRASHED/WEDGED stage the tool re-probes the backend
out-of-process; if the chip no longer answers it STOPS and reports,
rather than burning 900 s per remaining stage.  One process per chip:
this parent stays off JAX, and stages and probes run one at a time.

    python tools/bisect_swin_eval.py            # all stages
    python tools/bisect_swin_eval.py --stage fwd_b1
    python tools/bisect_swin_eval.py --export-check   # no hardware

``--export-check`` (VERDICT r3 item 7) serializes every stage's
jitted program for platforms=['tpu'] via jax.export ON CPU at the
real crash shapes.  What it can exclude: StableHLO lowering /
cross-platform legalization failures.  What it cannot: Mosaic/XLA:TPU
*backend* compilation and runtime faults (the export path stops at
serialized StableHLO — no TPU codegen happens off-device).  Result of
the round-4 run: ALL stages export clean at b32@320 (see
docs/PERFORMANCE.md swin note), so the crash is a backend
compile/runtime fault, not a lowering bug — consistent with the
worker dying only on real hardware.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PLATFORM = """
import jax
{platform_select}
import os as _os


def finish(label, jitted, fargs, run):
    '''Shared stage footer: execute the stage (default), or — with
    DSOD_BISECT_EXPORT=1 — serialize the same jitted program for the
    TPU platform via jax.export WITHOUT running it.  The export path
    works on the CPU backend, so it checks cross-platform (StableHLO)
    lowering of the exact crash-shaped program with no hardware.'''
    if _os.environ.get("DSOD_BISECT_EXPORT") == "1":
        from jax import export as _jexport

        exp = _jexport.export(jitted, platforms=["tpu"])(*fargs)
        print(label, "EXPORT-TPU ok:",
              len(exp.mlir_module_serialized), "bytes")
    else:
        print(label, "ok", run())
"""

_PRELUDE = _PLATFORM + """
import jax.numpy as jnp, numpy as np
from distributed_sod_project_tpu.configs import get_config, apply_overrides
from distributed_sod_project_tpu.models import build_model
from distributed_sod_project_tpu.parallel.mesh import (
    batch_sharding, make_mesh, replicated_sharding)
from distributed_sod_project_tpu.train import (
    build_optimizer, create_train_state)
from distributed_sod_project_tpu.train.state import TrainState

B = max({batch}, jax.device_count())  # batch must shard over the mesh
cfg = get_config("swin_sod")
cfg = apply_overrides(cfg, [f"global_batch_size={{B}}",
                            "data.image_size={hw},{hw}"])
mesh = make_mesh(cfg.mesh)
model = build_model(cfg.model)
rng = np.random.RandomState(0)
batch = {{
    "image": rng.randn(B, {hw}, {hw}, 3).astype(np.float32),
    "mask": (rng.rand(B, {hw}, {hw}, 1) > 0.5).astype(np.float32),
}}
tx, _ = build_optimizer(cfg.optim, 100)
state = create_train_state(jax.random.key(0), model, tx, batch)
state = TrainState(step=state.step, params=state.params,
                   batch_stats=state.batch_stats, opt_state=())
state = jax.device_put(state, replicated_sharding(mesh))
dev = jax.device_put(batch, batch_sharding(mesh))
"""

# No model at all: just the scatter-add metric kernel on random probs.
_METRICS_ONLY = _PLATFORM + """
import jax.numpy as jnp, numpy as np
from distributed_sod_project_tpu.metrics.streaming import (
    init_fbeta_state, update_fbeta_state)
B = {batch}
rng = np.random.RandomState(0)
probs = jnp.asarray(rng.rand(B, {hw}, {hw}).astype(np.float32))
gt = jnp.asarray((rng.rand(B, {hw}, {hw}, 1) > 0.5).astype(np.float32))
upd = jax.jit(update_fbeta_state, donate_argnums=0)


def _run():
    acc = init_fbeta_state()
    for _ in range(3):
        acc = upd(acc, probs, gt)
    return float(acc.mae_sum)


finish("metrics", upd, (init_fbeta_state(), probs, gt), _run)
"""

_BACKBONE = _PLATFORM + """
import jax.numpy as jnp, numpy as np
from distributed_sod_project_tpu.models.backbones.swin import SwinT
B = {batch}
rng = np.random.RandomState(0)
img = jnp.asarray(rng.randn(B, {hw}, {hw}, 3).astype(np.float32))
bb = SwinT(dtype=jnp.bfloat16)
vars_ = bb.init(jax.random.key(0), img)
fn = jax.jit(lambda v, x: [f.astype(jnp.float32).sum()
                           for f in bb.apply(v, x)])
finish("backbone", fn, (vars_, img),
       lambda: [float(s) for s in fn(vars_, img)])
"""

_FWD = _PRELUDE + """
fn = jax.jit(lambda s, b: model.apply(
    {{"params": s.params, "batch_stats": s.batch_stats}},
    b["image"], None, train=False)[0])
finish("fwd", fn, (state, dev),
       lambda: float(fn(state, dev).astype(jnp.float32).sum()))
"""

# The working train step's forward (train=True + mutable BN), no grad:
# if this passes where fwd crashes, the BN running-average program
# difference is implicated.
_FWD_TRAINFLAG = _PRELUDE + """
def f(s, b):
    outs, _ = model.apply(
        {{"params": s.params, "batch_stats": s.batch_stats}},
        b["image"], None, train=True, mutable=["batch_stats"],
        rngs={{"dropout": jax.random.key(0)}})
    return outs[0]
fn = jax.jit(f)
finish("fwd_trainflag", fn, (state, dev),
       lambda: float(fn(state, dev).astype(jnp.float32).sum()))
"""

_EVAL_STEP = _PRELUDE + """
from distributed_sod_project_tpu.train.step import make_eval_step
estep = make_eval_step(model, mesh)
finish("eval_step", estep, (state, dev),
       lambda: float(estep(state, dev).astype(jnp.float32).sum()))
"""

# Eval step + device-side metric accumulation (what bench --mode eval
# timed in round 2, and what crashed).
_EVAL_METRICS = _PRELUDE + """
from distributed_sod_project_tpu.train.step import make_eval_step
from distributed_sod_project_tpu.metrics.streaming import (
    init_fbeta_state, update_fbeta_state)
estep = make_eval_step(model, mesh)
upd = jax.jit(update_fbeta_state, donate_argnums=0)


def _run():
    acc = init_fbeta_state()
    for _ in range(3):
        probs = estep(state, dev)
        acc = upd(acc, probs, dev["mask"])
    return float(acc.mae_sum)


def _combined(acc, s, b):
    return upd(acc, estep(s, b), b["mask"])


finish("eval+metrics", jax.jit(_combined), (init_fbeta_state(), state, dev),
       _run)
"""

# (name, source, extra_env, batch_override) — order = smallest program
# first; the known crasher stays LAST.  eval_metrics_nofuse (VERDICT
# r3 item 7) runs the crasher's program with XLA's fusion passes
# disabled: if IT survives where eval_metrics kills the worker, the
# fault lives in a fused kernel (the scatter-metrics fusion suspect),
# not in any single op — and vice versa.  Unknown pass names in
# --xla_disable_hlo_passes are ignored, so the stage degrades to a
# duplicate-of-crasher rather than an error on backends that name the
# passes differently.
_NOFUSE_FLAGS = ("--xla_disable_hlo_passes="
                 "fusion,priority-fusion,multi-output-fusion")
_STAGES = [
    ("metrics_only", _METRICS_ONLY, {}, None),
    ("backbone", _BACKBONE, {}, None),
    ("fwd_b1", _FWD, {}, 1),
    ("fwd", _FWD, {}, None),
    ("fwd_trainflag", _FWD_TRAINFLAG, {}, None),
    ("eval_step", _EVAL_STEP, {}, None),
    ("eval_metrics_nofuse", _EVAL_METRICS, {"XLA_FLAGS": _NOFUSE_FLAGS},
     None),
    ("eval_metrics", _EVAL_METRICS, {}, None),
]


def _probe_backend(timeout: float = 90.0) -> bool:
    """Out-of-process dial: is the TPU still answering?"""
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].platform)"],
            capture_output=True, text=True, timeout=timeout, cwd=_REPO)
    except subprocess.TimeoutExpired:
        return False
    return r.returncode == 0 and "tpu" in r.stdout.lower()


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--stage", default=None,
                   choices=[n for n, *_ in _STAGES])
    p.add_argument("--batch", type=int, default=32,
                   help="eval batch (round-2 crash was at the zoo's 32)")
    p.add_argument("--image-size", type=int, default=320)
    p.add_argument("--device", default="tpu", choices=["tpu", "cpu"],
                   help="cpu = smoke-test THIS TOOL's machinery on tiny "
                        "shapes; the bisect itself is tpu")
    p.add_argument("--export-check", action="store_true",
                   help="no hardware: on the CPU backend, jax.export "
                        "each stage's jitted program for platforms="
                        "['tpu'] at the CRASH shapes instead of running "
                        "it — rules lowering-level causes in or out "
                        "(VERDICT r3 item 7); combine with the default "
                        "--batch/--image-size for the real shapes")
    p.add_argument("--timeout", type=int, default=900)
    p.add_argument("--json-out", default=None,
                   help="write a {stage: verdict} summary here")
    args = p.parse_args(argv)

    if args.export_check:
        args.device = "cpu"
    platform_select = (
        'jax.config.update("jax_platforms", "cpu")'
        if args.device == "cpu" else "")
    stages = [(n, s, e, b) for n, s, e, b in _STAGES
              if args.stage in (None, n)]
    verdicts = {}
    for name, src, extra_env, b_over in stages:
        b = b_over if b_over is not None else args.batch
        src = src.format(batch=b, hw=args.image_size,
                         platform_select=platform_select)
        env = dict(os.environ, **extra_env)
        if args.export_check:
            env["DSOD_BISECT_EXPORT"] = "1"
        print(f"== {name} (b={b}{', ' if extra_env else ''}"
              f"{' '.join(f'{k}={v}' for k, v in extra_env.items())})",
              flush=True)
        try:
            r = subprocess.run([sys.executable, "-c", src],
                               capture_output=True, text=True, env=env,
                               timeout=args.timeout, cwd=_REPO)
        except subprocess.TimeoutExpired:
            verdicts[name] = "WEDGED"
            print("   WEDGED (timeout)", flush=True)
        else:
            if r.returncode == 0:
                verdicts[name] = "OK"
                print("   OK:", (r.stdout or "").strip().splitlines()[-1:],
                      flush=True)
            else:
                verdicts[name] = f"CRASHED rc={r.returncode}"
                print(f"   CRASHED rc={r.returncode}", flush=True)
                for line in (r.stderr or "").strip().splitlines()[-8:]:
                    print("   |", line[:200], flush=True)
        if (verdicts[name] != "OK" and len(stages) > 1
                and args.device == "tpu"):
            # A worker kill can take the chip with it; do not spend
            # 900 s per remaining stage on a dead backend.
            if not _probe_backend():
                print("!! backend no longer answering — stopping bisect "
                      "(remaining stages would only measure the wedge)",
                      flush=True)
                verdicts["_aborted"] = "backend dead after failure"
                break
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(verdicts, f, indent=2)
    print(json.dumps(verdicts), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
