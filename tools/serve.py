#!/usr/bin/env python
"""Run the online serving engine — or a multi-model FLEET — over HTTP
(docs/SERVING.md).

    # Serve a trained checkpoint (config sidecar aware), hot-reloading
    # whenever training writes a newer VALID checkpoint:
    python tools/serve.py --ckpt-dir runs/minet --port 8080 \
        --set serve.reload_poll_s=5

    # Smoke/e2e posture: serve a randomly-initialised model (no
    # checkpoint needed; what tools/t1.sh and the agenda legs use):
    python tools/serve.py --config minet_vgg16_ref --init-random \
        --port 0 --port-file /tmp/serve.port

    # Single model behind the FLEET router (adds X-Model routing,
    # tenancy, and the aggregated fleet /metrics):
    python tools/serve.py --config minet_vgg16_ref --init-random \
        --model minet --port 8080

    # Multi-model fleet from a JSON config (docs/SERVING.md "Fleet"):
    python tools/serve.py --fleet-config fleet.json \
        --port 0 --port-file /tmp/fleet.port

``--port 0`` binds an ephemeral port; ``--port-file`` writes the bound
port atomically for scripts.  SIGTERM/SIGINT drain cleanly (exit 0).
Knobs live under the ``serve.*`` config section
(``--set serve.max_wait_ms=10``; with a fleet, ``--set`` applies to
every in-process member after its own overrides).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ckpt-dir", default=None,
                   help="checkpoint directory written by train.py")
    p.add_argument("--config", default=None,
                   help="registered config name (default: the "
                        "checkpoint's config.json sidecar)")
    p.add_argument("--init-random", action="store_true",
                   help="serve a randomly-initialised model instead of "
                        "a checkpoint (requires --config; smoke/bench "
                        "posture)")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: newest VALID)")
    p.add_argument("--model", default=None,
                   help="routing key: front the single engine with the "
                        "fleet router under this model name (X-Model "
                        "routing, tenancy, aggregated /metrics)")
    p.add_argument("--fleet-config", default=None,
                   help="JSON fleet config (models/tenants — "
                        "docs/SERVING.md \"Fleet\"): serve a "
                        "multi-model fleet behind the router instead "
                        "of one engine")
    p.add_argument("--host", default=None,
                   help="bind host (default: serve.host)")
    p.add_argument("--port", type=int, default=None,
                   help="bind port, 0 = ephemeral (default: serve.port)")
    p.add_argument("--port-file", default=None,
                   help="write the bound port here once listening")
    p.add_argument("--device", default=None, choices=["tpu", "cpu", None])
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="PATH=VALUE", help="dotted config override")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.fleet_config:
        if (args.ckpt_dir or args.config or args.model
                or args.init_random or args.step is not None):
            raise SystemExit(
                "--fleet-config is exclusive of --ckpt-dir/--config/"
                "--model/--init-random/--step (members and their "
                "sources are named in the JSON; a silently ignored "
                "flag would serve the wrong weights)")
    elif not args.ckpt_dir and not (args.init_random and args.config):
        raise SystemExit(
            "need --fleet-config, --ckpt-dir, or --init-random with "
            "--config")

    from distributed_sod_project_tpu.utils.platform import (
        describe_device, enable_compilation_cache, select_platform)

    select_platform(args.device)

    def device_ready() -> None:
        """Before the first in-process engine: place the compile cache
        (a restart then reuses the AOT-warmed programs) and name the
        device that serves.  A router fronting only remote replicas
        never calls this and never touches a backend — a chip belongs
        to one process, and it is the replica's."""
        cache_dir = enable_compilation_cache()
        print(json.dumps({"device": describe_device(),
                          "compile_cache_dir": cache_dir}), flush=True)

    if args.fleet_config:
        from distributed_sod_project_tpu.configs import \
            fleet_config_from_dict
        from distributed_sod_project_tpu.serve.fleet import Fleet
        from distributed_sod_project_tpu.serve.router import \
            serve_fleet_forever

        with open(args.fleet_config) as f:
            fc = fleet_config_from_dict(json.load(f))
        if any(not (m.url or m.urls) for m in fc.models):
            device_ready()
        fleet = Fleet.from_config(fc, extra_overrides=args.overrides)
        host = args.host if args.host is not None else fc.host
        port = args.port if args.port is not None else fc.port
        return serve_fleet_forever(fleet, host, port,
                                   port_file=args.port_file)

    from distributed_sod_project_tpu.serve.engine import InferenceEngine
    from distributed_sod_project_tpu.serve.server import serve_forever

    device_ready()
    if args.ckpt_dir:
        engine = InferenceEngine.from_checkpoint(
            args.ckpt_dir, config_name=args.config,
            overrides=args.overrides, step=args.step)
    else:
        from distributed_sod_project_tpu.configs import (apply_overrides,
                                                         get_config)

        cfg = apply_overrides(get_config(args.config), args.overrides)
        engine = InferenceEngine.from_random_init(cfg)

    host = args.host if args.host is not None else engine.cfg.serve.host
    port = args.port if args.port is not None else engine.cfg.serve.port
    if args.model:
        # One engine behind the router: same process, fleet front door
        # (X-Model routing + tenancy + fleet metrics for one model).
        from distributed_sod_project_tpu.serve.fleet import (EngineBackend,
                                                             Fleet)
        from distributed_sod_project_tpu.serve.router import \
            serve_fleet_forever

        fleet = Fleet([EngineBackend(args.model, engine)])
        return serve_fleet_forever(fleet, host, port,
                                   port_file=args.port_file)
    return serve_forever(engine, host, port, port_file=args.port_file)


if __name__ == "__main__":
    raise SystemExit(main())
