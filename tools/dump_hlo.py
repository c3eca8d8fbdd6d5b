#!/usr/bin/env python
"""Dump the compiled train step's HLO for a config — regression diffing.

SURVEY.md §5 (tracing/profiling): the TPU-native analogue of "did my
change alter the compiled program?" is an HLO diff.  This tool lowers
the full sharded train step for a registered config on a virtual
n-device CPU mesh and writes:

    <out>/<config>.stablehlo.txt   — pre-optimization StableHLO (stable
                                     across machines; the diffing target)
    <out>/<config>.cost.json       — XLA's per-program cost analysis
                                     (flops, bytes accessed) when
                                     available

Usage:
    python tools/dump_hlo.py --config minet_r50_dp --out hlo/
    diff hlo_before/minet_r50_dp.stablehlo.txt hlo_after/...
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_TINY = ["model.lm.vocab=512", "model.lm.hidden=64", "model.lm.heads=4",
         "model.lm.dense_width=96", "model.lm.expert_width=48",
         "data.vocab=512"]
_TINY_LM = {  # model.name -> the token model's shrink
    "lfm2": _TINY + ["model.lm.kv_heads=2", "model.lm.head_dim=16"],
    "kimi": _TINY + ["model.lm.head_dim=24", "model.lm.rope_dim=8",
                     "model.lm.v_dim=16", "model.lm.kv_rank=32"],
    "granite": _TINY + ["model.lm.kv_heads=2", "model.lm.head_dim=16",
                        "model.lm.ssm_heads=8", "model.lm.ssm_head_dim=16",
                        "model.lm.ssm_state=16", "model.lm.ssm_chunk=32"],
    "ouro": _TINY + ["model.lm.kv_heads=4", "model.lm.head_dim=16"],
    "nemotron_h": _TINY + ["model.lm.kv_heads=1", "model.lm.head_dim=16",
                           "model.lm.latent_width=32",
                           "model.lm.shared_width=96", "model.lm.experts=16",
                           "model.lm.experts_held=4", "model.lm.top_k=3",
                           "model.lm.ssm_heads=8", "model.lm.ssm_head_dim=16",
                           "model.lm.ssm_state=16", "model.lm.ssm_chunk=32"],
    "phi4flash": _TINY + ["model.lm.kv_heads=2", "model.lm.head_dim=16",
                          "model.lm.ssm_heads=128", "model.lm.ssm_state=16",
                          "model.lm.ssm_chunk=32", "model.lm.ssm_dt_rank=4",
                          "model.lm.window=24"]}


def dump(config_name: str, out_dir: str, n_devices: int = 8,
         batch_per_device: int = 1, image_size: int = 64,
         compile_cost: bool = True, overrides=(),
         post_opt: bool = False) -> dict:
    """Lower the config's train step; returns {'stablehlo': path, ...}.

    ``compile_cost=False`` skips the (slow) compile that only feeds the
    cost-analysis sidecar — tools/hlo_guard.py lowers the step several
    times per run and needs just the StableHLO text.  ``overrides`` are
    extra ``section.field=value`` config overrides applied on top of
    the standard virtual-mesh shrink — e.g. pin an execution-strategy
    arm (``model.conv_impl=fused``) to dump/diff arm-specific programs.

    ``post_opt=True`` also compiles and writes the POST-optimization
    HLO (``<config>.hlo_post.txt``).  GSPMD presets (fsdp/tp) need it:
    their pre-opt StableHLO carries only sharding annotations — the
    SPMD partitioner inserts the collectives during compilation, so
    the JIT all-gathers/reduce-scatters are countable only post-opt.
    Post-opt text is backend-dependent (do NOT diff it across
    machines); hlo_guard only counts collective op names in it.
    """
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={n_devices}")
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 — backend already initialized
        pass
    from distributed_sod_project_tpu.configs import (apply_overrides,
                                                     get_config)
    from distributed_sod_project_tpu.models import build_model, kind_of
    from distributed_sod_project_tpu.parallel.mesh import (
        batch_sharding, make_mesh)
    from distributed_sod_project_tpu.train import (
        build_optimizer, create_train_state)

    cfg = get_config(config_name)
    shrink = [f"data.image_size={image_size},{image_size}"]
    if cfg.model.name in _TINY_LM:
        # A token model's shrink: tiny widths, --image-size tokens a
        # sequence (the structure of the program is what is diffed).
        shrink = [f"data.seq_len={image_size}"] + _TINY_LM[cfg.model.name]
    cfg = apply_overrides(cfg, [
        f"global_batch_size={batch_per_device * n_devices}",
        "mesh.data=-1", "mesh.model=1", "mesh.seq=1",
    ] + shrink + list(overrides))
    mesh = make_mesh(cfg.mesh, jax.devices()[:n_devices])
    model = build_model(cfg.model)
    tx, sched = build_optimizer(cfg.optim, 100)

    # Zeros: nothing runs, and neither the init nor the step program
    # reads the values (a token model's --image-size is its sequence
    # length, set above).
    batch = kind_of(model).zero_batch(cfg, cfg.global_batch_size)
    state = create_train_state(jax.random.key(0), model, tx, batch)
    dbatch = jax.device_put(batch, batch_sharding(mesh))

    # The unified rules engine (parallel/engine.py, the only engine):
    # same preset routing as fit(), so hlo_guard's comm arms can pin
    # parallel.* overrides (preset=fsdp, data_hosts, grad_compression)
    # and count the lowered collectives.
    from distributed_sod_project_tpu.parallel.engine import (
        prepare_train_step)

    state, step, _plan = prepare_train_step(
        cfg, model, tx, mesh, sched, state, donate=False)
    lowered = step.lower(state, dbatch)

    os.makedirs(out_dir, exist_ok=True)
    paths = {}
    shlo = os.path.join(out_dir, f"{config_name}.stablehlo.txt")
    with open(shlo, "w") as f:
        f.write(lowered.as_text())
    paths["stablehlo"] = shlo

    if post_opt:
        compiled = lowered.compile()
        ppath = os.path.join(out_dir, f"{config_name}.hlo_post.txt")
        with open(ppath, "w") as f:
            f.write(compiled.as_text())
        paths["hlo_post"] = ppath

    if not compile_cost:
        return paths
    try:
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        cost = {k: float(v) for k, v in cost.items()
                if isinstance(v, (int, float))}
        cpath = os.path.join(out_dir, f"{config_name}.cost.json")
        with open(cpath, "w") as f:
            json.dump(cost, f, indent=2, sort_keys=True)
        paths["cost"] = cpath
    except Exception as e:  # noqa: BLE001 — cost analysis is best-effort
        print(f"[warn] cost analysis unavailable: {e}", file=sys.stderr)
    return paths


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", required=True)
    p.add_argument("--out", default="hlo")
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--batch-per-device", type=int, default=1)
    p.add_argument("--image-size", type=int, default=64)
    args = p.parse_args(argv)
    paths = dump(args.config, args.out, args.devices,
                 args.batch_per_device, args.image_size)
    for k, v in paths.items():
        print(f"{k}: {v}  ({os.path.getsize(v)} bytes)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
