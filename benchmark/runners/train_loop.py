"""Runner of the looped token model's training cell:
``runners/train_lm.py``'s method — the program's own ``fit()`` in this
process, the compiled step tapped for its first three calls, the window
on the ``on_metrics`` ticks, the plain reference following the same
rows — with what a looped model changes: the weights come from
``harness/weights_loop.py`` (that recipe knows the gate's bias), the
tap also records the step's mean exit distribution
(``loop_exit_mass_t``), the ticks keep the exit counters where
``train_lm`` keeps the expert layers', and ``exit_mass_gap`` is judged
beside the seven numbers of the other token cells: the largest
``|program - reference|`` of any pass's mean ``p_t`` over the three
steps followed.  No routing row: the model has no router.

``correct`` = every judged number inside its limit, every tick's loss
finite, no compilation inside the window.
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
from ..harness.weights_loop import variables_builder
from .train import Window, _dump_first_steps, build_cfg
from .train_lm import N_FOLLOW, TokenStepTap

def _passes(host, family):
    """The counters ``<family>1..R`` of a metrics dict, in order."""
    out = []
    while f"{family}{len(out) + 1}" in host:
        out.append(float(host[f"{family}{len(out) + 1}"]))
    return out


class LoopStepTap(TokenStepTap):
    """``TokenStepTap`` with this model's weights recipe; the first
    ``N_FOLLOW`` calls also keep the step's mean exit distribution."""

    def __init__(self, inner, seed: int, config: dict):
        super().__init__(inner, seed, config)
        self.exit_mass = []

    def variables0(self):
        if self._make is None:
            self._make = variables_builder(
                self.shapes, self.config["weights"], self.sharding)
        return self._make(self.seed)

    def __call__(self, state, batch):
        followed = self.calls < N_FOLLOW
        state, metrics = super().__call__(state, batch)
        if followed:
            self.exit_mass.append(_passes(jax.device_get(metrics),
                                          "loop_exit_mass_"))
        return state, metrics


class LoopWindow(Window):
    """The tick table also keeps the exit counters (``loop_*``)."""

    def __call__(self, step, host):
        more = {k: float(v) for k, v in host.items()
                if k.startswith("loop_")}
        n = len(self.ticks)
        super().__call__(step, host)
        if len(self.ticks) > n:
            self.ticks[n].update(more)


def exit_mass_gap(prog, ref) -> float:
    """Largest |program - reference| of any pass's mean ``p_t`` over the
    steps followed; a pass one side lacks counts as mass 0 there."""
    gap = 0.0
    for a, b in zip(prog, ref):
        r = max(len(a), len(b))
        a, b = (list(x) + [0.0] * (r - len(x)) for x in (a, b))
        gap = max([gap] + [abs(x - y) for x, y in zip(a, b)])
    return gap


def compare(tap_out: dict, ref_out: dict, limits: dict):
    """``correct.compare_training``'s rows and ``exit_mass_gap``."""
    ok, rows = correct.compare_training(tap_out, ref_out, limits)
    gap = exit_mass_gap(tap_out["exit_mass"], ref_out["exit_mass"])
    lim = limits.get("exit_mass_gap")
    passed = bool(lim is None or (np.isfinite(gap) and gap <= lim))
    rows.append(("exit_mass_gap", float(gap),
                 None if lim is None else float(lim), passed))
    return ok and passed, rows


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
        taps.append(LoopStepTap(build(*a, **kw), ctx["seed"], config))
        return taps[-1]

    win = LoopWindow(ctx["seconds"], int(cell["warmup_ticks"]), trace_dir,
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
                  f"{k} {v:.4g}" for k, v in t.items()
                  if k.startswith("loop_")), flush=True)
    in_window = compiles.inside(ticks[0]["t"], ticks[-1]["t"]) if ticks else 0
    print(f"compile: {compiles.summary()} inside_window {in_window}",
          flush=True)
    print(f"window: {len(ticks)} ticks, {steps} steps, {span:.3f} s; "
          f"first-steps loss {tap.loss} exit mass {tap.exit_mass}",
          flush=True)

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
            rows_ok, rows = compare(
                {"loss": tap.loss, "grad_norms": tap.grad_norms,
                 "dparam_norms": tap.dparam_norms,
                 "exit_mass": tap.exit_mass}, ref_out, cell["limits"])
            correct.print_rows(rows)
            f32_out = ref_out
            _dump_first_steps(os.path.join(ctx["out_dir"],
                                           "first_steps.json"),
                              ctx["seed"], tap, ref_out)
        else:  # a control: the reference at a lower precision, judged
            _, crow = compare(ref_out, f32_out, cell["limits"])  # as if it
            print(f"control[{prec}]:", flush=True)           # were the program
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
