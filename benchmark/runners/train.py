"""Runner of the training cells: the program's own ``fit()`` in this
process, its input pipeline, augmentation, H2D copies and logging all
running, stopped by a SIGTERM to itself (the program's
``PreemptionGuard``) once the window has closed.

The clock is the ``on_metrics`` hook: ``fit()`` calls it after a host
fetch of the step's metrics, every ``log_every_steps`` steps, so each
tick says "this many steps are DONE".

One seam is tapped, ``parallel.engine.make_unified_train_step``: the
compiled step that ``fit()`` builds is wrapped so that (a) its first
call receives the benchmark's own weights in place of the program's
initialisation and (b) the first three calls are recorded — the rows
fed, the loss, the optimizer's state after one step, the parameters
after three — for the comparison with the plain reference.  It is the
same object the window then drives.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import correct, trace
from ..harness.compiles import CompileLog
from ..harness.stats import device_memory_peak
from ..harness.weights import _path, variables_builder

N_FOLLOW = 3


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), tree)


def _first_grad(opt_state, params0, opt: dict):
    """The first gradient as the optimizer got it, from its state after
    one step: SGD's trace is g + wd*p0 on kernels, Adam's mu is
    (1 - b1) * g."""
    for part in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "trace")
            or hasattr(x, "mu")):
        if hasattr(part, "trace"):
            wd = opt.get("weight_decay", 0.0)
            return jax.tree_util.tree_map(
                lambda t, p: t - (wd * p if p.ndim >= 2 else 0.0),
                part.trace, params0)
        if hasattr(part, "mu"):
            return jax.tree_util.tree_map(lambda m: m / (1.0 - 0.9), part.mu)
    raise ValueError("no momentum trace or Adam mu in the optimizer state")


class StepTap:
    """Wraps the compiled step; see the module docstring."""

    def __init__(self, inner, seed: int, config: dict):
        self.inner, self.seed, self.config = inner, seed, config
        self.calls = 0
        self.batches, self.loss = [], []
        self.grad_norms = self.dparam_norms = None
        self.shapes = self.sharding = self._make = None

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def variables0(self):
        """The benchmark's weights for this seed, made anew (the step
        donates what it is given)."""
        if self._make is None:
            self._make = variables_builder(
                self.shapes, self.config["weights"], self.sharding)
        return self._make(self.seed)

    def batch_sharding(self):
        """The batch split over the program's own mesh (its first axis
        is the data axis), so that the reference sees the global batch."""
        sh = self.sharding
        if not hasattr(sh, "mesh") or sh.mesh.size == 1:
            return None
        return jax.sharding.NamedSharding(
            sh.mesh, jax.sharding.PartitionSpec(sh.mesh.axis_names[0]))

    def __call__(self, state, batch):
        i = self.calls
        self.calls += 1
        if i >= N_FOLLOW:
            return self.inner(state, batch)
        if i == 0:
            self.shapes = {"params": _shapes(state.params),
                           "batch_stats": _shapes(state.batch_stats)}
            self.sharding = jax.tree_util.tree_leaves(
                state.params)[0].sharding
            v = self.variables0()
            state = state.replace(params=v["params"],
                                  batch_stats=v["batch_stats"])
        self.batches.append({k: np.asarray(batch[k])
                             for k in ("image", "mask")})
        state, metrics = self.inner(state, batch)
        self.loss.append(float(jax.device_get(metrics["total"])))
        if i == 0:
            opt = self.config["reference"]["optimizer"]
            self.grad_norms = jax.device_get(jax.jit(
                lambda o, p: correct._leaf_norms(_first_grad(o, p, opt)))(
                    state.opt_state, self.variables0()["params"]))
        if i == N_FOLLOW - 1:
            self.dparam_norms = jax.device_get(jax.jit(
                lambda a, b: correct._leaf_norms(jax.tree_util.tree_map(
                    jnp.subtract, a, b)))(state.params,
                                          self.variables0()["params"]))
        return state, metrics


def _dump_first_steps(path, seed, tap, ref_out) -> None:
    """Every leaf's norms, program then reference, for whoever has to
    find which module a gap sits in."""
    def floats(tree):
        return [float(x) for x in jax.tree_util.tree_leaves(tree)]

    names = [_path(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tap.grad_norms)[0]]
    with open(path, "w") as f:
        json.dump({"seed": seed, "leaf": names,
                   "loss": [tap.loss, ref_out["loss"]],
                   "grad_norms": [floats(tap.grad_norms),
                                  floats(ref_out["grad_norms"])],
                   "dparam_norms": [floats(tap.dparam_norms),
                                    floats(ref_out["dparam_norms"])]}, f)


class Window:
    """The ``on_metrics`` hook: keeps the tick table, opens the window
    after the warm-up ticks, traces part of it, and stops ``fit()``."""

    def __init__(self, seconds, warmup_ticks, trace_dir, trace_ticks):
        self.seconds, self.warmup_ticks = seconds, warmup_ticks
        self.trace_dir, self.trace_ticks = trace_dir, trace_ticks
        self.ticks = []
        self.t_open = None
        self.traced = None  # (step_first, step_last)
        self._mark = None
        self.stopped = False

    def __call__(self, step, host):
        with jax.profiler.TraceAnnotation("benchmark.fit_hook_tick"):
            now = time.perf_counter()
            self.ticks.append({
                "step": int(step), "t": now, "loss": float(host["total"]),
                "data_starved_ms": float(host.get("data_starved_ms", 0.0)),
                "grad_norm": float(host.get("grad_norm", float("nan")))})
            n = len(self.ticks)
            if n == self.warmup_ticks:
                self.t_open = now
            if self.trace_dir and self.t_open is not None:
                if n == self.warmup_ticks + 1:
                    jax.profiler.start_trace(self.trace_dir)
                    self._mark = jax.profiler.TraceAnnotation(
                        trace.WINDOW_MARK)
                    self._mark.__enter__()
                    self.traced = [int(step), None]
                elif n == self.warmup_ticks + 1 + self.trace_ticks:
                    self._mark.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    self.traced[1] = int(step)
            if (self.t_open is not None and not self.stopped
                    and now - self.t_open >= self.seconds
                    and (not self.trace_dir or (self.traced or [0, 0])[1])):
                self.stopped = True
                os.kill(os.getpid(), signal.SIGTERM)

    def measured(self):
        """Ticks from the window's opening tick to the last one inside
        ``seconds`` of it."""
        if self.t_open is None:
            return []
        return [t for t in self.ticks
                if self.t_open <= t["t"] <= self.t_open + self.seconds]


def build_cfg(ctx):
    from distributed_sod_project_tpu.configs import (apply_overrides,
                                                     get_config)

    cell, config = ctx["cell"], ctx["config"]
    cfg = get_config(config["registered"])
    cfg = apply_overrides(cfg, list(config.get("overrides", []))
                          + list(cell.get("overrides", []))
                          + list(ctx.get("extra_overrides", [])))
    return cfg.replace(seed=ctx["seed"] % (2 ** 31 - 1))


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
        taps.append(StepTap(build(*a, **kw), ctx["seed"], config))
        return taps[-1]

    win = Window(ctx["seconds"], int(cell["warmup_ticks"]), trace_dir,
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
              f"data_starved_ms {t['data_starved_ms']:.2f}", flush=True)
    in_window = compiles.inside(ticks[0]["t"], ticks[-1]["t"]) if ticks else 0
    print(f"compile: {compiles.summary()} inside_window {in_window}",
          flush=True)
    print(f"window: {len(ticks)} ticks, {steps} steps, {span:.3f} s; "
          f"first-steps loss {tap.loss}", flush=True)

    # The plain reference follows the same rows, once the program's
    # state is gone from the device.
    t_ref = time.perf_counter()
    fwd = correct.load_reference(config["reference"]["model"])
    rows_ok, rows = True, []
    for prec in ctx.get("ref_precs", ("f32",)):
        try:
            ref_out = correct.reference_follow(
                fwd, tap.variables0, tap.batches, config["reference"],
                prec=prec, sharding=tap.batch_sharding())
        except Exception as e:  # a control that does not fit says so
            if prec == "f32":
                raise
            print(f"control[{prec}]: gave no number: {e!r}"[:2000], flush=True)
            continue
        if prec == "f32":
            rows_ok, rows = correct.compare_training(
                {"loss": tap.loss, "grad_norms": tap.grad_norms,
                 "dparam_norms": tap.dparam_norms}, ref_out, cell["limits"],
                cell.get("grad_leaves"))
            correct.print_rows(rows)
            f32_out = ref_out
            _dump_first_steps(os.path.join(ctx["out_dir"],
                                           "first_steps.json"),
                              ctx["seed"], tap, ref_out)
        else:  # a control: the reference at a lower precision, judged
            _, crow = correct.compare_training(  # as if it were the program
                ref_out, f32_out, cell["limits"], cell.get("grad_leaves"))
            print(f"control[{prec}]:", flush=True)
            correct.print_rows(crow)
            rows += [(f"control.{prec}.{n}", v, None, True)
                     for n, v, _, _ in crow]
    print(f"reference: {time.perf_counter() - t_ref:.1f} s", flush=True)

    losses = [t["loss"] for t in ticks]
    bad = sum(1 for x in losses if not np.isfinite(x))
    ok = (rows_ok and len(ticks) > 1 and bad == 0 and in_window == 0)
    out = {
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
                    "trace_dir": trace_dir, "cell": cell,
                    "compare_rows": rows},
    }
    return out
