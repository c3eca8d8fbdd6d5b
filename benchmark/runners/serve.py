"""Runner of the serving cells: the program's real server in this
process (``InferenceEngine(...).start()`` + ``make_server``), driven
over loopback HTTP by the benchmark's open-loop generator at the rate
fixed in the workload file.

The engine is built through its public constructor from a state that
carries the benchmark's own weights.  ``correct`` compares, once the
window has closed, a seeded sample of the masks served in it (the
largest payload among them) with the plain reference's forward on the
same payloads, through the reference's own resize.
"""

from __future__ import annotations

import io
import os
import random
import shutil
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import correct, loadgen, trace
from ..harness.compiles import CompileLog
from ..harness.stats import device_memory_peak
from ..harness.weights import variables_builder
from ..reference import ops
from .train import build_cfg


def make_catalog(n: int, sizes, seed: int):
    """``n`` structured uint8 images (a textured ground plus 1-3 bright
    ellipses), sizes cycling through ``sizes`` (h, w); the same for
    every --seed, which only orders the traffic."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        h, w = sizes[i % len(sizes)]
        yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
        coarse = rng.normal(0.35, 0.12, (h // 16 + 1, w // 16 + 1, 3))
        img = coarse.repeat(16, 0).repeat(16, 1)[:h, :w].astype(np.float32)
        for _ in range(int(rng.integers(1, 4))):
            cy, cx = rng.uniform(0.2, 0.8) * h, rng.uniform(0.2, 0.8) * w
            ry, rx = rng.uniform(0.08, 0.25) * h, rng.uniform(0.08, 0.25) * w
            inside = ((xx - cx) / rx) ** 2 + ((yy - cy) / ry) ** 2 <= 1.0
            img[inside] = 0.25 * img[inside] + 0.75 * rng.uniform(0.6, 1.0, 3)
        img = np.clip(img + rng.normal(0, 0.02, img.shape), 0.0, 1.0)
        out.append((img * 255.0).round().astype(np.uint8))
    return out


def npy_bytes(arr: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, arr)
    return buf.getvalue()


def _resize_u8(arr_u8: np.ndarray, hw) -> np.ndarray:
    """The reference's own resize of a uint8 image or map: triangle
    filter (``ops._resize_matrix``), rounded half up to uint8."""
    a = ops._resize_matrix(arr_u8.shape[0], hw[0])
    b = ops._resize_matrix(arr_u8.shape[1], hw[1])
    x = arr_u8.astype(np.float32)
    x = np.tensordot(a, x, axes=(1, 0))
    x = np.moveaxis(np.tensordot(b, x, axes=(1, 1)), 0, 1)
    return np.clip(np.floor(x + 0.5), 0, 255).astype(np.uint8)


def reference_masks(fwd, variables, images, res, mean, std, prec="f32",
                    block=4):
    """The plain path from payload to mask, as the server documents it:
    resize to (res, res), /255, normalise, forward in eval mode,
    sigmoid, quantise to uint8, resize back, /255."""
    mean, std = np.asarray(mean, np.float32), np.asarray(std, np.float32)
    rows = np.stack([(_resize_u8(im, (res, res)).astype(np.float32) / 255.0
                      - mean) / std for im in images])
    f = jax.jit(lambda v, x: jax.nn.sigmoid(
        fwd(v, x, train=False, prec=prec)[0][..., 0]))
    probs = []
    for i in range(0, len(rows), block):
        chunk = rows[i:i + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.zeros((pad,) + chunk.shape[1:],
                                                    np.float32)])
        probs.append(np.asarray(f(variables, chunk))[:block - pad])
    probs = np.concatenate(probs)
    out = []
    for p, im in zip(probs, images):
        q = (np.clip(p, 0, 1) * 255).astype(np.uint8)
        out.append(_resize_u8(q, im.shape[:2]).astype(np.float32) / 255.0)
    return out


def build_engine(cfg, seed: int, config: dict):
    """The program's engine on the benchmark's weights."""
    from distributed_sod_project_tpu.models import build_model
    from distributed_sod_project_tpu.serve.engine import InferenceEngine
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    model = build_model(cfg.model)
    tx, _ = build_optimizer(cfg.optim, 1)
    h, w = cfg.data.image_size
    state = create_train_state(jax.random.key(0), model, tx,
                               {"image": np.zeros((1, h, w, 3), np.float32)})
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
        {"params": state.params, "batch_stats": state.batch_stats})
    build = variables_builder(shapes, config["weights"])
    make = lambda: build(seed)  # noqa: E731
    v = make()
    state = state.replace(params=v["params"], batch_stats=v["batch_stats"])
    return InferenceEngine(cfg, model, state), make


class Served:
    """The system under test, up: engine, HTTP server, payload catalog."""

    def __init__(self, cfg, seed: int, config: dict, cell: dict):
        from distributed_sod_project_tpu.serve.server import make_server

        self.cfg, self.cell = cfg, cell
        self.engine, self.make_vars = build_engine(cfg, seed, config)
        self.engine.start()
        self.srv = make_server(self.engine, "127.0.0.1", 0)
        self.port = self.srv.server_address[1]
        self.thread = threading.Thread(target=self.srv.serve_forever,
                                       daemon=True, name="bench-http")
        self.thread.start()
        sizes = [tuple(s) for s in cell["sizes_hw"]]
        self.catalog = make_catalog(int(cell["catalog"]), sizes,
                                    int(cell.get("catalog_seed", 7)))
        self.payloads = [npy_bytes(a) for a in self.catalog]

    def drive(self, rate: float, seconds: float, seed: int, *, keep_n=0,
              trace_dir=None):
        """Offer ``rate`` req/s for warm-up + ``seconds``; -> (window's
        records, the generator, its schedule)."""
        cell = self.cell
        warm_s = float(cell["warmup_s"])
        schedule = loadgen.make_schedule(rate, warm_s + seconds,
                                         len(self.payloads), seed)
        in_window = [i for i, (due, _) in enumerate(schedule)
                     if due >= warm_s]
        keep = set(random.Random(seed).sample(
            in_window, min(keep_n, len(in_window))))
        if keep_n and in_window:  # the largest payload is always compared
            keep.add(max(in_window, key=lambda i: (
                self.catalog[schedule[i][1]].size, -i)))

        def check(body: bytes) -> bool:
            arr = np.load(io.BytesIO(body), allow_pickle=False)
            return arr.ndim == 2 and bool(np.isfinite(arr).all())

        gen = loadgen.LoadGen(
            "127.0.0.1", self.port, self.payloads, schedule,
            timeout_s=float(cell["timeout_s"]),
            senders=int(cell.get("senders", 64)), check=check, keep=keep,
            annotate=jax.profiler.TraceAnnotation if trace_dir else None)
        tracer = None
        if trace_dir:
            def _trace():
                time.sleep(warm_s + 1.0)
                jax.profiler.start_trace(trace_dir)
                with jax.profiler.TraceAnnotation(trace.WINDOW_MARK):
                    time.sleep(float(cell.get("trace_s", 3.0)))
                jax.profiler.stop_trace()

            tracer = threading.Thread(target=_trace, name="bench-trace")
            tracer.start()
        records = gen.run()
        if tracer:
            tracer.join()
        return [r for r in records if r["due"] >= warm_s], gen, schedule

    def close(self) -> dict:
        self.srv.shutdown()
        self.srv.server_close()
        self.thread.join(timeout=10)
        stats = self.engine.stats_snapshot()
        self.engine.stop()
        self.engine = None
        return stats


def run(ctx) -> dict:
    cell, config, seed = ctx["cell"], ctx["config"], ctx["seed"]
    cfg = build_cfg(ctx)
    trace_dir = os.path.join(ctx["out_dir"], "trace") if ctx["trace"] else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiles = CompileLog()
    sut = Served(cfg, seed, config, cell)
    catalog = sut.catalog
    rate = float(ctx.get("rate") or cell["rate_per_s"])
    timeout_s, limit_ms = float(cell["timeout_s"]), float(cell["limit_ms"])
    warm_s, seconds = float(cell["warmup_s"]), ctx["seconds"]
    window, gen, schedule = sut.drive(rate, seconds, seed,
                                      keep_n=int(cell["compare"]),
                                      trace_dir=trace_dir)
    t_open = gen.t0 + warm_s
    mem_peak = device_memory_peak(jax.local_devices())
    print(f"memory: peak in use + peak reserved {mem_peak} bytes", flush=True)
    make_vars = sut.make_vars
    stats = sut.close()

    summ = loadgen.summarize(window, timeout_s, limit_ms, seconds)
    n_compiles = compiles.inside(t_open, t_open + seconds)
    backlog = sum(1 for r in window
                  if r["due"] + r["latency_ms"] / 1000.0 > warm_s + seconds)
    print(f"serve: rate {rate} req/s, window {seconds} s: {summ} "
          f"unfinished_at_close {backlog}", flush=True)
    print(f"serve: engine stats {stats}", flush=True)
    print(f"compile: {compiles.summary()} inside_window {n_compiles}",
          flush=True)

    t_ref = time.perf_counter()
    fwd = correct.load_reference(config["reference"]["model"])
    idx = sorted(gen.bodies)
    rows, ok_cmp = [], bool(idx)
    if idx:
        images = [catalog[schedule[i][1]] for i in idx]
        served = [np.load(io.BytesIO(gen.bodies[i])) for i in idx]
        res = int(cfg.data.image_size[0])
        variables = make_vars()
        refs = {}
        for prec in ctx.get("ref_precs", ("f32",)):
            refs[prec] = reference_masks(
                fwd, variables, images, res, cfg.data.normalize_mean,
                cfg.data.normalize_std, prec=prec)
        diffs = [np.abs(s - r) for s, r in zip(served, refs["f32"])]
        rows = [("mask_mean_abs_gap",
                 float(np.mean([d.mean() for d in diffs])),
                 cell["limits"]["mask_mean_abs_gap"]),
                ("mask_max_abs_gap", float(max(d.max() for d in diffs)),
                 cell["limits"]["mask_max_abs_gap"])]
        rows = [(n, v, float(lim), bool(np.isfinite(v) and v <= lim))
                for n, v, lim in rows]
        correct.print_rows(rows)
        ok_cmp = all(r[3] for r in rows)
        for prec, masks in refs.items():
            if prec == "f32":
                continue
            d = [np.abs(a - b) for a, b in zip(masks, refs["f32"])]
            print(f"control[{prec}]: mask_mean_abs_gap "
                  f"{np.mean([x.mean() for x in d]):.6g} mask_max_abs_gap "
                  f"{max(x.max() for x in d):.6g}", flush=True)
    print(f"reference: {len(idx)} masks compared in "
          f"{time.perf_counter() - t_ref:.1f} s", flush=True)

    failed = sum(1 for r in window if not r["ok"])
    ok = ok_cmp and n_compiles == 0 and len(window) > 0 \
        and int(stats.get("request_compiles", 0)) == 0
    return {
        "correct": bool(ok), "attempted": len(window), "failed": failed,
        "end_to_end": {"serve_p95_ms": summ["p95_ms"],
                       "serve_ok_img_per_s": summ["ok_img_per_s"],
                       "setup_s": t_open - ctx["t_start"]},
        "memory_peak_bytes": int(mem_peak),
        "sources": {"requests": window, "summary": summ, "chips": 1,
                    "trace_dir": trace_dir, "cell": cell, "rate": rate,
                    "backlog": backlog, "compare_rows": rows},
    }
