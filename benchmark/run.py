#!/usr/bin/env python3
"""The benchmark's entry point.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell.  Everything that belongs to one configuration,
one traffic mix, one runner or one per-layer metric is a file of its
own, found by the name ``BENCHMARK.json`` gives (``benchmark/README.md``).
The last line of standard output is the result object; everything else
(tick or request tables, each number compared beside its limit, compile
counts) is printed before it or written under ``benchmark/out/``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    pass


def load_manifest(root=ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(manifest: dict, workload: str, root=ROOT):
    """A cell's files, found by name: -> (entry, cell file, config file)."""
    entry = next((w for w in manifest["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    with open(os.path.join(root, "benchmark", "workloads",
                           f"{workload}.json")) as f:
        cell = json.load(f)
    conf = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    if cell["config"] != entry["config"] or int(cell["chips"]) != entry["chips"]:
        raise SystemExit(f"{workload}: workload file and BENCHMARK.json "
                         "disagree on config or chips")
    return entry, cell, config


def cell_metrics(manifest: dict, workload: str, kind: str):
    """The metrics of ``kind`` (end_to_end | per_layer) this cell reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(name: str, root=ROOT):
    """``benchmark/layer_metrics/<metric>.py`` -> its ``read``."""
    path = os.path.join(root, "benchmark", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def setup_jax_cache():
    """JAX's persistent compilation cache at ONE fixed path inside the
    checkout, ``.jax_cache/``, with no size cap and every program kept:
    a cell's programs are large (the reference's float32 step alone is
    ~150 MB), and a capped cache evicts them between runs."""
    import jax

    path = os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_chips(chips: int) -> dict:
    """The device as JAX reports it; NoChip unless it is a TPU of a kind
    the table of peaks knows, with at least ``chips`` chips."""
    import jax

    with open(os.path.join(HERE, "harness", "peaks.json")) as f:
        peaks = json.load(f)
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX found platform {d.platform!r}, not a TPU")
    if d.device_kind not in peaks:
        raise NoChip(f"device kind {d.device_kind!r} is not in "
                     "benchmark/harness/peaks.json")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "peaks": peaks[d.device_kind]}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root=ROOT, device=None, t_start=None, **extra) -> dict:
    """Drive one cell and build the result object.  ``device`` is what
    :func:`find_chips` returned; tests pass their own (a CPU rehearsal
    then reports NO metric: its numbers go under ``rehearsal``)."""
    manifest = load_manifest(root)
    _, cell, config = resolve(manifest, workload, root)
    out_dir = os.path.join(root, "benchmark", "out", workload)
    os.makedirs(out_dir, exist_ok=True)
    runner = importlib.import_module(f"benchmark.runners.{cell['runner']}")
    ctx = dict(cell=cell, config=config, seed=int(seed),
               seconds=float(seconds), trace=bool(trace), out_dir=out_dir,
               t_start=T_START if t_start is None else t_start,
               device=device, **extra)
    res = runner.run(ctx)

    values = {}
    if trace:
        run = dict(res["sources"], memory_peak_bytes=res["memory_peak_bytes"],
                   device=device, trace=None)
        tdir = res["sources"].get("trace_dir")
        if tdir:
            from benchmark.harness import trace as trace_mod

            path = trace_mod.find_xplane(tdir)
            if path:
                raw = trace_mod.load(path)
                print("trace: planes", json.dumps(
                    {k: sorted(set(v)) for k, v in raw["lines"].items()
                     if k.startswith("/device:")}), flush=True)
                run["trace"] = trace_mod.reduce_events(raw)
                with open(os.path.join(out_dir, "trace_head.json"), "w") as f:
                    json.dump(trace_mod.head(raw), f)
        for m in cell_metrics(manifest, workload, "per_layer"):
            v = load_reader(m["name"], root)(run)
            if v is not None and math.isfinite(v):
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell_metrics(manifest, workload, "end_to_end"):
            v = res["end_to_end"].get(m["name"])
            if v is not None and math.isfinite(v):
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    on_chip = device["platform"] == "tpu"
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": values if on_chip else {},
            "device": dev}
    if not on_chip:
        line["rehearsal"] = values  # never a device metric
    line["compared"] = [list(r) for r in res["sources"].get("compare_rows", [])]
    if trace and run["trace"]:
        dev["busy_s"] = run["trace"]["busy_s"]
        dev["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                             "idle_gaps": run["trace"]["idle_gaps"]}
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    manifest = load_manifest()
    entry, _, _ = resolve(manifest, args.workload)
    try:
        import distributed_sod_project_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"benchmark: the program is not in this directory: {e}",
              file=sys.stderr)
        return 4
    try:
        device = find_chips(entry["chips"])
    except (NoChip, RuntimeError) as e:
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        return 3
    setup_jax_cache()
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    device=device)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
