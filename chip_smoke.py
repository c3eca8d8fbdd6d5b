#!/usr/bin/env python
"""Chip smoke: the trainer and the server on ONE TPU chip, through the
entry points a user calls, at the flagship's full width.

    python chip_smoke.py                 # one chip; what the driver runs
    python chip_smoke.py --four-chips    # data-parallel parity on a 2x2 host
    python chip_smoke.py --rehearse-cpu  # tiny sizes on the CPU; never "ok"

Phases (one chip), each a child process run to its end before the next
starts — a chip belongs to one process, so THIS parent never imports
JAX and takes the device description from what the trainer prints:

1. trainer  ``train.py --config minet_r50_dp --device tpu`` at 320 px /
   batch 32 on the synthetic dataset (asked for by name), host loader
   running, checkpoints written, an inline eval, finite loss per step;
   then the newest checkpoint is dropped and the same command runs with
   ``--resume`` — the restore path, and a warm compile of the same
   programs out of the persistent cache.
2. server   ``tools/serve.py --config minet_r50_dp --init-random
   --device tpu`` at its default resolution and batch buckets: AOT
   warm, a few ``POST /predict`` answered 200 with a finite mask of the
   request's shape, ``/metrics`` showing zero request-time compiles,
   SIGTERM drained cleanly (exit 0).
3. kernels  (``--child kernels``) a few ``basnet_ds`` train steps at
   320 px / batch 16 with its default ``loss.fused_kernel=true``: the
   compiled step holds a ``tpu_custom_call`` and the first-step loss
   agrees with ``loss.fused_kernel=false``; then one ``minet_r50_dp``
   forward with ``model.conv_impl=fused model.resample_impl=fused``
   that prints how many sites took the Pallas kernel and how many gave
   way to XLA, checked against the XLA arms' output.

The LAST line of stdout is one JSON object, ``{"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": 1}}``; anything else (loss
per step, img/s — informational, not a benchmark — compile seconds,
cache hits, loader path, request latencies) is on earlier lines.  Any
failed phase, or a device that is not a TPU, ends the run at once with
``"ok": false`` and a non-zero exit code.  ``--rehearse-cpu`` is the
only way this script runs on a CPU, and it never reports ``ok``.

``--four-chips`` ends ``"ok": false`` on the chip as of PR 23: the
losses of the two meshes agree, every array spans four devices, the
step holds its all-reduces — and the step-1 gradient norms differ by
orders of magnitude (the comment above ``DP_LR``; ROADMAP D12).
"""

from __future__ import annotations

import argparse
import io
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150  # the driver allows 1200 s, compilation included

# Stated tolerances (relative).  The fused loss kernels compute the
# same f32 formulas as the XLA losses; what differs on the chip is the
# reduction order and the MXU's default-precision passes in the SSIM
# band-matrix blur.
KERNEL_LOSS_RTOL = 1e-2
FUSED_FORWARD_ATOL = 2e-2  # sigmoid maps in [0, 1], bf16 compute
# Four chips against one device, same global batch/seed/steps, bf16
# compute and a different reduction order across the batch.
#
# Step 1 is taken at IDENTICAL parameters on the same batch, before any
# update: its loss and gradient norm do not depend on the learning rate
# (tests/test_train.py holds that bitwise), so they are the flagship's
# own-lr values, and both are held to a tolerance.  The gradient norm
# is the check on the data=4 BACKWARD (the sync-BN psums and the
# gradient pmean): a missing or doubled 1/n shows as 4x or 0.25x, and
# on 4 virtual CPU devices the two meshes agree to 1% in bf16 and to
# every printed digit in f64.  ON THE CHIP THIS CHECK FAILS TODAY (PR
# 23, CHANGES.md): through fit() the flagship's bf16 gradient norm
# jumps between ~8 and ~1e5 from step to step and from mesh to mesh
# (17.6 against 357,388 at step 1 in one run, 92,842 against 175,768
# in another) while the losses agree.  The cause is not located; the
# check stays, last, so that --four-chips says so instead of "ok".
#
# The later steps run with the learning rate turned down 1000x on BOTH
# meshes: at 0.005 from a random init on synthetic data the two
# trajectories part within a few steps whatever the mesh (two ONE-chip
# runs of one seed were 25% apart at step 3), so only a quiet optimizer
# lets every step's loss be compared.
DP_LR = 5e-6
DP_FIRST_STEP_RTOL = 5e-3
DP_FIRST_STEP_GRAD_RTOL = 0.25
DP_ALL_STEPS_RTOL = 5e-2

REAL = dict(device="tpu", image=320, train_batch=32, train_steps=6,
            synthetic=256, requests=4, kernel_batch=16, kernel_steps=3,
            forward_batch=8, dp_batch=128, dp_steps=4, sets=[],
            serve_sets=[])
# Same code paths at sizes one CPU core finishes in minutes.
REHEARSAL = dict(device="cpu", image=64, train_batch=4, train_steps=6,
                 synthetic=16, requests=3, kernel_batch=2, kernel_steps=2,
                 forward_batch=1, dp_batch=8, dp_steps=3,
                 sets=["data.image_size=64,64"],
                 serve_sets=["serve.batch_buckets=1,2",
                             "serve.precision_arms=f32"])


class PhaseFailed(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)
    say(f"ok: {what}")


# ------------------------------------------------------------ children


class Child:
    """One child process, its own process group, output echoed line by
    line as it arrives (merged stderr) and kept for parsing."""

    def __init__(self, cmd):
        say("$ " + " ".join(cmd))
        self.t0 = time.monotonic()
        self.lines = []  # (seconds since start, text)
        self.proc = subprocess.Popen(
            cmd, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, start_new_session=True)
        LIVE.append(self)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            self.lines.append((time.monotonic() - self.t0, line))
            print("    | " + line, flush=True)

    def wait(self, timeout: float) -> int:
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise PhaseFailed(
                f"child still running after {timeout:.0f}s: "
                f"{self.proc.args[:3]}") from None
        self._reader.join(timeout=10)
        return rc

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()

    def json_lines(self, key: str):
        out = []
        for _t, line in self.lines:
            if line.startswith("{") and f'"{key}"' in line:
                try:
                    out.append(json.loads(line)[key])
                except (ValueError, KeyError):
                    pass
        return out


LIVE: list = []  # every child ever started; all are dead at exit


def remaining(t_start: float) -> float:
    left = DEADLINE_S - (time.monotonic() - t_start)
    if left <= 0:
        raise PhaseFailed(f"out of time ({DEADLINE_S}s budget)")
    return left


# ------------------------------------------------------ phase: trainer

_STEP_RE = re.compile(
    r"step (\d+)/\d+\s+loss=(\S+)\s+lr=\S+\s+(\S+) imgs/s")


def phase_trainer(sz, tmp: str, t_start: float) -> dict:
    workdir = os.path.join(tmp, "run")
    n = sz["train_steps"]
    base = [sys.executable, "train.py", "--config", "minet_r50_dp",
            "--device", sz["device"], "--workdir", workdir,
            "--batch-size", str(sz["train_batch"]),
            "--set", "data.dataset=synthetic",
            "--set", f"data.synthetic_size={sz['synthetic']}",
            "--set", "log_every_steps=1",
            "--set", f"checkpoint_every_steps={n // 2}",
            "--eval-every", str(n)]
    for s in sz["sets"]:
        base += ["--set", s]

    def run(extra, label):
        child = Child(base + extra)
        rc = child.wait(remaining(t_start))
        check(rc == 0, f"trainer ({label}) exited 0 (got {rc})")
        steps = [(t, int(m.group(1)), float(m.group(2)), float(m.group(3)))
                 for t, line in child.lines
                 for m in [_STEP_RE.search(line)] if m]
        return child, steps

    cold, steps = run(["--max-steps", str(n)], "cold")
    device = (cold.json_lines("device") or [None])[0]
    check(device is not None, f"trainer named its device: {device}")
    check([s for _t, s, _l, _r in steps] == list(range(1, n + 1)),
          f"trainer logged steps 1..{n}")
    losses = [l for _t, _s, l, _r in steps]
    check(all(l == l and abs(l) != float("inf") for l in losses),
          f"finite loss at every step: {losses}")
    say(f"imgs/s as the trainer logged them (informational, not a "
        f"benchmark; the first steps include compilation): "
        f"{[r for _t, _s, _l, r in steps]}")
    check(any("eval @" in line for _t, line in cold.lines),
          "inline eval ran")
    ckpts = sorted(d for d in os.listdir(workdir) if d.isdigit())
    check(ckpts == [str(n // 2), str(n)], f"checkpoints written: {ckpts}")
    loader = [line for _t, line in cold.lines if "host loader:" in line]
    check(len(loader) >= 1, f"loader path named: {loader[:1]}")

    # As if the run had been preempted after its first checkpoint: drop
    # the newer one and resume.  Same --max-steps, so the SAME programs
    # (the LR schedule bakes the step count in): a warm compile.
    shutil.rmtree(os.path.join(workdir, ckpts[-1]))
    warm, wsteps = run(["--max-steps", str(n), "--resume"], "resume")
    check(any(f"resumed from checkpoint step {ckpts[0]}" in line
              for _t, line in warm.lines),
          f"resume restored checkpoint {ckpts[0]}")
    check([s for _t, s, _l, _r in wsteps] == list(range(n // 2 + 1, n + 1))
          and all(l == l and abs(l) != float("inf")
                  for _t, _s, l, _r in wsteps),
          f"resumed run took steps {n // 2 + 1}..{n} with finite loss")
    c_cold = (cold.json_lines("compile") or [{}])[0]
    c_warm = (warm.json_lines("compile") or [{}])[0]
    say(f"trainer compile, cold: {c_cold}")
    say(f"trainer compile, warm: {c_warm}")
    say(f"seconds to the first logged step: cold {steps[0][0]:.1f}, "
        f"warm {wsteps[0][0]:.1f}")
    if c_cold.get("cache_dir"):
        check(c_warm.get("cache_hits", 0) > 0
              and c_warm["seconds"] < c_cold["seconds"],
              "second trainer run hit the compile cache and compiled "
              "for less time")
    else:
        say("compile cache is off on this backend (CPU): cold/warm "
            "compare skipped")
    return device


# ------------------------------------------------------- phase: server


def _get(url: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def phase_server(sz, tmp: str, t_start: float) -> None:
    import numpy as np  # the payload format; not JAX

    port_file = os.path.join(tmp, "serve.port")
    cmd = [sys.executable, "tools/serve.py", "--config", "minet_r50_dp",
           "--init-random", "--device", sz["device"], "--port", "0",
           "--port-file", port_file]
    for s in sz["sets"] + sz["serve_sets"]:
        cmd += ["--set", s]
    srv = Child(cmd)
    try:
        while not os.path.exists(port_file):
            if srv.proc.poll() is not None:
                raise PhaseFailed(f"server exited {srv.proc.returncode} "
                                  "before listening")
            remaining(t_start)
            time.sleep(0.5)
        with open(port_file) as f:
            url = f"http://127.0.0.1:{int(f.read().strip())}"
        warmed = [line for _t, line in srv.lines if "warmed program" in line]
        say(f"server listening at {url} after "
            f"{time.monotonic() - srv.t0:.1f}s, {len(warmed)} programs "
            "AOT-warmed")
        check(len(warmed) >= 1, "AOT warm ran before the port opened")
        rng = np.random.RandomState(0)
        hw = sz["image"]
        lat = []
        for i in range(sz["requests"]):
            img = rng.randint(0, 256, (hw, hw, 3)).astype(np.uint8)
            buf = io.BytesIO()
            np.save(buf, img)
            req = urllib.request.Request(
                url + "/predict", data=buf.getvalue(), method="POST",
                headers={"Content-Type": "application/x-npy"})
            t0 = time.monotonic()
            with urllib.request.urlopen(req, timeout=120) as r:
                body, status = r.read(), r.status
            lat.append(round((time.monotonic() - t0) * 1e3, 1))
            mask = np.load(io.BytesIO(body))
            check(status == 200 and mask.shape == (hw, hw)
                  and bool(np.isfinite(mask).all())
                  and 0.0 <= float(mask.min()) and float(mask.max()) <= 1.0,
                  f"request {i + 1}: 200, mask {mask.shape} "
                  f"{mask.dtype} finite in [0, 1]")
        say(f"request latencies ms (informational): {lat}")
        def counter(name):
            m = re.search(rf"^{name}(?:{{[^}}]*}})? (\S+)$", metrics, re.M)
            return None if m is None else float(m.group(1))

        for _ in range(20):  # the book closes after the response flushes
            metrics = _get(url + "/metrics")
            if counter("dsod_serve_served_total") == sz["requests"]:
                break
            time.sleep(0.25)
        check(counter("dsod_serve_served_total") == sz["requests"],
              f"/metrics: served == {sz['requests']}")
        check(counter("dsod_serve_request_compiles_total") == 0,
              "/metrics: zero request-time compiles")
        os.kill(srv.proc.pid, signal.SIGTERM)
        rc = srv.wait(min(120, remaining(t_start)))
        check(rc == 0 and any("shut down cleanly" in line
                              for _t, line in srv.lines),
              f"SIGTERM drained cleanly (exit {rc})")
    finally:
        srv.kill()


# ------------------------------------------------ child: kernel phases


def _train_setup(cfg, batch_size: int, hw: int, mesh, total_steps=1000):
    """The real step builder on a resident batch."""
    import jax

    from distributed_sod_project_tpu.parallel.engine import \
        prepare_train_step
    from distributed_sod_project_tpu.parallel.mesh import batch_sharding
    from distributed_sod_project_tpu.train import random_init_setup

    model, tx, sched, host, state = random_init_setup(
        cfg, batch_size, hw, total_steps)
    state, step, _plan = prepare_train_step(cfg, model, tx, mesh, sched,
                                            state)
    return state, step, jax.device_put(host, batch_sharding(mesh))


def child_kernels(sz) -> int:
    from distributed_sod_project_tpu.utils.platform import (
        CompileStats, describe_device, enable_compilation_cache,
        select_platform)

    select_platform(sz["device"])
    compiles = CompileStats()
    enable_compilation_cache()
    import jax
    import numpy as np

    from distributed_sod_project_tpu.configs import (apply_overrides,
                                                     get_config)
    from distributed_sod_project_tpu.parallel.mesh import make_mesh

    on_tpu = describe_device()["platform"] == "tpu"
    hw, b = sz["image"], sz["kernel_batch"]
    mesh = make_mesh(devices=jax.devices()[:1])

    # -- basnet_ds: the fused loss kernels a shipped config runs by
    # default, against the same step with the XLA losses.
    first = {}
    for fused in (True, False):
        cfg = apply_overrides(get_config("basnet_ds"), sz["sets"] + [
            f"global_batch_size={b}",
            f"loss.fused_kernel={'true' if fused else 'false'}"])
        check(cfg.loss.fused_kernel is fused,
              f"basnet_ds resolves loss.fused_kernel={fused}")
        state, step, batch = _train_setup(cfg, b, hw, mesh)
        step = step.lower(state, batch).compile()
        n_calls = step.as_text().count("tpu_custom_call")
        say(f"basnet_ds fused_kernel={fused}: compiled step holds "
            f"{n_calls} tpu_custom_call(s)")
        if on_tpu:
            check((n_calls > 0) is fused,
                  "Pallas kernels are in the compiled step iff "
                  "loss.fused_kernel")
        losses = []
        for _ in range(sz["kernel_steps"]):
            state, metrics = step(state, batch)
            losses.append(float(jax.device_get(metrics["total"])))
        check(all(np.isfinite(losses)), f"finite losses: {losses}")
        first[fused] = losses[0]
        if fused:
            # Is block_until_ready a sound sync on this device?  Time
            # the same steps against a host fetch of the loss.
            t0 = time.perf_counter()
            for _ in range(3):
                state, metrics = step(state, batch)
            jax.block_until_ready(metrics["total"])
            t_block = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(3):
                state, metrics = step(state, batch)
            float(jax.device_get(metrics["total"]))
            t_fetch = time.perf_counter() - t0
            say(f"3 steps timed to block_until_ready: {t_block:.4f}s; "
                f"to a host fetch of the loss: {t_fetch:.4f}s "
                "(informational)")
        del state, step, batch
    rel = abs(first[True] - first[False]) / abs(first[False])
    check(rel <= KERNEL_LOSS_RTOL,
          f"first-step loss fused {first[True]:.6f} vs XLA "
          f"{first[False]:.6f}: rel diff {rel:.2e} <= {KERNEL_LOSS_RTOL}")

    # -- minet_r50_dp forward with both fused arms: which sites took
    # the kernel.  Sites are counted where the per-site rule is asked
    # (at trace time), kernels from the compiled program.
    from distributed_sod_project_tpu.eval.inference import make_forward
    from distributed_sod_project_tpu.models import build_model
    from distributed_sod_project_tpu.pallas import fused_conv as fc
    from distributed_sod_project_tpu.pallas import fused_resample as fr

    asked = {"conv": [0, 0], "resample": [0, 0]}  # [sites, admitted]

    def counting(name, fn):
        def wrapped(*a, **k):
            ok = fn(*a, **k)
            asked[name][0] += 1
            asked[name][1] += bool(ok)
            return ok
        return wrapped

    fc.fused_conv_available = counting("conv", fc.fused_conv_available)
    fr.fused_resample_available = counting("resample",
                                           fr.fused_resample_available)
    fb = sz["forward_batch"]
    img = {"image": np.random.RandomState(1).randn(fb, hw, hw, 3)
           .astype(np.float32)}
    outs = {}
    for arm, sets in (("fused", ["model.conv_impl=fused",
                                 "model.resample_impl=fused"]),
                      ("xla", [])):
        cfg = apply_overrides(get_config("minet_r50_dp"),
                              sz["sets"] + sets)
        model = build_model(cfg.model)
        variables = jax.jit(lambda r, i: model.init(r, i, None, train=False)
                            )(jax.random.key(0), img["image"])
        for v in asked.values():  # model.init asked the rules too
            v[0] = v[1] = 0
        fwd = make_forward(model).lower(variables, img).compile()
        if arm == "fused":
            n_calls = fwd.as_text().count("tpu_custom_call")
            sites = asked["conv"][0] + asked["resample"][0]
            admitted = asked["conv"][1] + asked["resample"][1]
            say(f"minet_r50_dp fused forward @{hw}px: conv sites "
                f"{asked['conv'][0]} ({asked['conv'][1]} within the "
                f"rule), resample sites {asked['resample'][0]} "
                f"({asked['resample'][1]} within the rule)")
            if on_tpu:
                say(f"the compiled program holds {n_calls} Pallas "
                    f"kernels -> {n_calls} of {sites} sites took the "
                    f"kernel, {sites - n_calls} gave way to XLA")
                check(0 < n_calls == admitted,
                      "every site the rule admitted is a kernel in the "
                      "compiled program, and 'fused' is not 'XLA "
                      "everywhere'")
            else:
                say("(interpret mode on this backend: admitted sites "
                    "are not custom calls in the compiled program)")
        outs[arm] = np.asarray(jax.device_get(fwd(variables, img)),
                               np.float32)
    diff = float(np.abs(outs["fused"] - outs["xla"]).max())
    check(bool(np.isfinite(outs["fused"]).all())
          and diff <= FUSED_FORWARD_ATOL,
          f"fused forward finite and within {FUSED_FORWARD_ATOL} of the "
          f"XLA arms (max abs diff {diff:.2e})")
    print(json.dumps({"compile": compiles.as_dict()}), flush=True)
    print(json.dumps({"device": describe_device()}), flush=True)
    return 0


# ------------------------------------------------- child: four chips


def child_fourchips(sz) -> int:
    """minet_r50_dp with sync-BN through fit(): a data=4 mesh against a
    one-device mesh, same global batch, seed and steps."""
    from distributed_sod_project_tpu.utils.platform import (
        describe_device, enable_compilation_cache, select_platform)

    if sz["device"] == "cpu":  # the rehearsal: 4 virtual devices
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    select_platform(sz["device"])
    enable_compilation_cache()
    import jax
    import numpy as np

    from distributed_sod_project_tpu.configs import (apply_overrides,
                                                     get_config)
    from distributed_sod_project_tpu.train.loop import fit

    device = describe_device()
    check(device["count"] == 4, f"four devices: {device}")
    hw, b, n = sz["image"], sz["dp_batch"], sz["dp_steps"]
    tmp = tempfile.mkdtemp(prefix="chip_smoke_dp_")
    runs = {}
    try:
        for n_dev in (1, 4):  # b128 on ONE chip is the tight fit: fail early
            cfg = apply_overrides(get_config("minet_r50_dp"), sz["sets"] + [
                f"global_batch_size={b}", "data.dataset=synthetic",
                f"data.synthetic_size={2 * b}", "log_every_steps=1",
                f"optim.lr={DP_LR}", f"mesh.data={n_dev}"])
            check(cfg.model.sync_bn, "sync-BN is on")
            losses, gnorms, placement = [], [], {}

            def on_metrics(step, host, _l=losses, _g=gnorms,
                           _p=placement):
                _l.append(float(host["total"]))
                _g.append(float(host["grad_norm"]))
                if step == 1:  # what lives on the devices mid-run
                    for a in jax.live_arrays():
                        k = len(a.sharding.device_set)
                        big = a.size >= 1024
                        _p[(k, big)] = _p.get((k, big), 0) + 1

            fit(cfg, workdir=os.path.join(tmp, f"dp{n_dev}"),
                max_steps=n, hooks={"on_metrics": on_metrics})
            say(f"data={n_dev}: losses {losses}; grad norms {gnorms}; "
                f"live arrays by (devices spanned, >=1024 elems): "
                f"{placement}")
            runs[n_dev] = (losses, gnorms, placement)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    l4, g4, p4 = runs[4]
    l1, g1, _p1 = runs[1]
    check(len(l4) == len(l1) == n
          and bool(np.isfinite(l4 + l1 + g4 + g1).all()),
          f"{n} finite steps on both meshes")
    strays = sum(c for (k, big), c in p4.items() if big and k != 4)
    check(strays == 0 and p4.get((4, True), 0) > 0,
          "every parameter, optimizer and batch array of the data=4 run "
          "spans four distinct devices")

    rels = [abs(a - c) / abs(c) for a, c in zip(l4, l1)]
    grel = abs(g4[0] - g1[0]) / abs(g1[0])
    say(f"grad norms, one device {g1} four {g4}")
    check(rels[0] <= DP_FIRST_STEP_RTOL,
          f"step-1 loss (identical parameters) data=4 {l4[0]:.6f} vs one "
          f"device {l1[0]:.6f}: rel diff {rels[0]:.2e} <= "
          f"{DP_FIRST_STEP_RTOL}")
    check(max(rels) <= DP_ALL_STEPS_RTOL,
          f"per-step loss data=4 vs one device at lr {DP_LR}: rel diffs "
          f"{[f'{r:.2e}' for r in rels]} (all <= {DP_ALL_STEPS_RTOL})")

    # The collective, from the compiled program of the same builder
    # (donate_batch as fit() builds it: the same program, so a compile-
    # cache hit where the cache is on).
    from distributed_sod_project_tpu.parallel.engine import \
        prepare_train_step
    from distributed_sod_project_tpu.parallel.mesh import (batch_sharding,
                                                           make_mesh)
    from distributed_sod_project_tpu.train import random_init_setup

    t0 = time.monotonic()
    cfg = apply_overrides(get_config("minet_r50_dp"), sz["sets"] + [
        f"global_batch_size={b}", f"optim.lr={DP_LR}", "mesh.data=4"])
    mesh = make_mesh(cfg.mesh)
    model, tx, sched, host, state = random_init_setup(cfg, b, hw,
                                                      total_steps=n)
    state, step, _plan = prepare_train_step(cfg, model, tx, mesh, sched,
                                            state, donate_batch=True)
    batch = jax.device_put(host, batch_sharding(mesh))
    text = step.lower(state, batch).compile().as_text()
    n_ar = len(re.findall(r"\ball-reduce(?:-start)?\(", text))
    check(n_ar > 0, f"the compiled data=4 step holds {n_ar} all-reduce "
                    f"op(s) ({time.monotonic() - t0:.0f}s to build, "
                    "compile and read it)")
    for leaf in jax.tree_util.tree_leaves((state.params, batch)):
        if len(leaf.sharding.device_set) != 4:
            raise PhaseFailed(f"array {leaf.shape} spans "
                              f"{len(leaf.sharding.device_set)} devices")
    say("ok: every parameter and batch array handed to that step spans "
        "four distinct devices (sharding.device_set)")
    print(json.dumps({"device": device}), flush=True)
    # Last, because it is the one that fails on the chip today: every
    # check above has then been shown to hold or not on its own.
    check(grel <= DP_FIRST_STEP_GRAD_RTOL,
          f"step-1 gradient norm (identical parameters) data=4 "
          f"{g4[0]:.4f} vs one device {g1[0]:.4f}: rel diff {grel:.2e} "
          f"<= {DP_FIRST_STEP_GRAD_RTOL}")
    return 0


# --------------------------------------------------------------- main


def run_self_child(name: str, args, t_start: float) -> dict:
    cmd = [sys.executable, "chip_smoke.py", "--child", name]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    child = Child(cmd)
    rc = child.wait(remaining(t_start))
    check(rc == 0, f"{name} phase exited 0 (got {rc})")
    device = (child.json_lines("device") or [None])[-1]
    check(device is not None, f"{name} phase named its device: {device}")
    return device


def finish(ok: bool, device, **extra) -> int:
    for c in LIVE:
        c.kill()
    print(json.dumps({"ok": ok, "device": device, **extra}), flush=True)
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run ONLY the data-parallel parity phase: "
                        "minet_r50_dp on a data=4 mesh against a "
                        "one-device mesh (needs a four-chip host)")
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="the same phases at tiny sizes on the CPU "
                        "backend, to find wrong paths and arguments "
                        "without a chip; always ends with ok=false")
    p.add_argument("--child", choices=["kernels", "fourchips"],
                   help=argparse.SUPPRESS)  # internal: one phase's process
    args = p.parse_args(argv)
    sz = REHEARSAL if args.rehearse_cpu else REAL
    if args.child:
        try:
            return {"kernels": child_kernels,
                    "fourchips": child_fourchips}[args.child](sz)
        except PhaseFailed as e:
            say(f"FAILED: {e}")
            return 1

    t_start = time.monotonic()
    device = None
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        for need in ("train.py", "tools/serve.py",
                     "distributed_sod_project_tpu"):
            if not os.path.exists(os.path.join(REPO, need)):
                raise PhaseFailed(f"{need} is not next to chip_smoke.py: "
                                  "this script drives the repo's own "
                                  "entry points")
        if args.four_chips:
            device = run_self_child("fourchips", args, t_start)
        else:
            device = phase_trainer(sz, tmp, t_start)
            phase_server(sz, tmp, t_start)
            kdev = run_self_child("kernels", args, t_start)
            check(kdev == device, "every phase ran on the same device")
        want = 4 if args.four_chips else 1
        if args.rehearse_cpu:
            say("rehearsal: every phase passed on the CPU at tiny "
                "sizes; this is not a chip run and never reports ok")
            return finish(False, device, rehearsal=True, phases_ok=True)
        check(device["platform"] == "tpu" and device["count"] == want,
              f"device is {want} TPU chip(s): {device}")
        say(f"all phases passed in {time.monotonic() - t_start:.0f}s")
        return finish(True, device)
    except PhaseFailed as e:
        say(f"FAILED: {e}")
        return finish(False, device, error=str(e)[:500])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
