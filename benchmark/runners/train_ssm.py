"""Runner of the state-space token model's training cell:
``runners/train_lm.py``'s method — the program's own ``fit()`` in this
process, the compiled step tapped for its first three calls, the window
on the ``on_metrics`` ticks, the plain reference following the same
rows — with what a dense state-space model changes: the weights come
from ``harness/weights_ssm.py`` (that recipe knows ``A_log``,
``dt_bias``, ``D`` and the conv's bias), the ticks keep the mixers'
counters (``ssm_decay_min``, ``ssm_delta_max``) where ``train_lm``
keeps the expert layers', and no routing row is judged: the model has
no router.

``correct`` = every judged number inside its limit (the three losses,
the first gradient's median and worst leaf, the parameters' change,
no leaf unmoved), every tick's loss finite, no compilation inside the
window.
"""

from __future__ import annotations

import importlib
import os
import shutil
import time

import jax
import numpy as np

from ..harness import correct
from ..harness.compiles import CompileLog
from ..harness.stats import device_memory_peak
from ..harness.weights_ssm import variables_builder
from .train import Window, _dump_first_steps, build_cfg
from .train_lm import TokenStepTap

SSM_KEYS = ("ssm_decay_min", "ssm_delta_max")


class SsmStepTap(TokenStepTap):
    """``TokenStepTap`` with this model's weights recipe."""

    def variables0(self):
        if self._make is None:
            self._make = variables_builder(
                self.shapes, self.config["weights"], self.sharding)
        return self._make(self.seed)


class SsmWindow(Window):
    """The tick table also keeps the mixers' counters."""

    def __call__(self, step, host):
        ssm = {k: float(host[k]) for k in SSM_KEYS if k in host}
        n = len(self.ticks)
        super().__call__(step, host)
        if len(self.ticks) > n:
            self.ticks[n].update(ssm)


def run(ctx) -> dict:
    cell, config = ctx["cell"], ctx["config"]
    cfg = build_cfg(ctx)
    workdir = os.path.join(ctx["out_dir"], "workdir")
    shutil.rmtree(workdir, ignore_errors=True)
    trace_dir = os.path.join(ctx["out_dir"], "trace") if ctx["trace"] else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)

    from distributed_sod_project_tpu.parallel import engine
    from distributed_sod_project_tpu.train.loop import fit

    compiles = CompileLog()
    taps = []
    build = engine.make_unified_train_step

    def tapped(*a, **kw):
        taps.append(SsmStepTap(build(*a, **kw), ctx["seed"], config))
        return taps[-1]

    win = SsmWindow(ctx["seconds"], int(cell["warmup_ticks"]), trace_dir,
                    int(cell.get("trace_ticks", 3)))
    engine.make_unified_train_step = tapped
    try:
        fit(cfg, workdir=workdir, max_steps=int(cell["max_steps"]),
            hooks={"on_metrics": win})
    finally:
        engine.make_unified_train_step = build
    shutil.rmtree(workdir, ignore_errors=True)
    (tap,) = taps
    mem_peak = device_memory_peak(jax.local_devices())
    print(f"memory: peak in use + peak reserved {mem_peak} bytes; "
          f"stats {jax.local_devices()[0].memory_stats()}", flush=True)

    ticks = win.measured()
    chips, batch = int(cell["chips"]), int(cfg.global_batch_size)
    steps = ticks[-1]["step"] - ticks[0]["step"] if len(ticks) > 1 else 0
    span = ticks[-1]["t"] - ticks[0]["t"] if len(ticks) > 1 else 0.0
    for t in win.ticks:
        print(f"tick: step {t['step']} t {t['t'] - ctx['t_start']:.3f}s "
              f"loss {t['loss']:.5f} grad_norm {t['grad_norm']:.4g} "
              f"data_starved_ms {t['data_starved_ms']:.2f} " + " ".join(
                  f"{k} {t[k]:.4g}" for k in SSM_KEYS if k in t),
              flush=True)
    in_window = compiles.inside(ticks[0]["t"], ticks[-1]["t"]) if ticks else 0
    print(f"compile: {compiles.summary()} inside_window {in_window}",
          flush=True)
    print(f"window: {len(ticks)} ticks, {steps} steps, {span:.3f} s; "
          f"first-steps loss {tap.loss}", flush=True)

    # The plain reference follows the same rows, once the program's
    # state is gone from the device.
    t_ref = time.perf_counter()
    ref = importlib.import_module(
        f"benchmark.reference.{config['reference']['model']}")
    rows_ok, rows = True, []
    for prec in ctx.get("ref_precs", ("f32",)):
        try:
            ref_out = ref.follow(tap.variables0, tap.batches,
                                 config["reference"], prec=prec)
        except Exception as e:  # a control that does not fit says so
            if prec == "f32":
                raise
            print(f"control[{prec}]: gave no number: {e!r}"[:2000], flush=True)
            continue
        if prec == "f32":
            rows_ok, rows = correct.compare_training(
                {"loss": tap.loss, "grad_norms": tap.grad_norms,
                 "dparam_norms": tap.dparam_norms}, ref_out, cell["limits"])
            correct.print_rows(rows)
            f32_out = ref_out
            _dump_first_steps(os.path.join(ctx["out_dir"],
                                           "first_steps.json"),
                              ctx["seed"], tap, ref_out)
        else:  # a control: the reference at a lower precision, judged
            _, crow = correct.compare_training(  # as if it were the program
                ref_out, f32_out, cell["limits"])
            print(f"control[{prec}]:", flush=True)
            correct.print_rows(crow)
            rows += [(f"control.{prec}.{n}", v, lim, ok)
                     for n, v, lim, ok in crow]
    print(f"reference: {time.perf_counter() - t_ref:.1f} s", flush=True)

    bad = sum(1 for t in ticks if not np.isfinite(t["loss"]))
    ok = rows_ok and len(ticks) > 1 and bad == 0 and in_window == 0
    return {
        "correct": bool(ok),
        "attempted": int(steps), "failed": int(bad * cfg.log_every_steps),
        "end_to_end": {
            "train_img_per_s_chip": (steps * batch / span / chips
                                     if span > 0 else float("nan")),
            "setup_s": (ticks[0]["t"] - ctx["t_start"]) if ticks
            else float("nan")},
        "memory_peak_bytes": int(mem_peak),
        "sources": {"ticks": ticks, "chips": chips,
                    "traced_steps": (win.traced[1] - win.traced[0]
                                     if win.traced and win.traced[1] else 0),
                    "trace_dir": trace_dir, "cell": cell, "config": config,
                    "seq_len": int(cfg.data.seq_len),
                    "tokens_per_step": batch * int(cfg.data.seq_len),
                    "compare_rows": rows},
    }
