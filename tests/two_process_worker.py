"""Subprocess body for tests/test_multiprocess.py — NOT a test module.

Runs ``fit()`` as one rank of a 2-process ``jax.distributed`` job on
fake CPU devices (4 per process → 8 global), the in-sandbox stand-in
for a 2-host TPU pod (SURVEY.md §4 "distributed without a cluster").

The platform is pinned to the CPU via ``jax.config.update`` BEFORE any
backend touch.
"""

import json
import os
import sys

# Overwrite (not setdefault): pytest's conftest exports 8 fake devices,
# which this process would inherit — each rank must contribute exactly 4
# so the 2-process cluster matches the 8-device single-process oracle.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def main():
    addr, pid, cfg_path, workdir = (
        sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4])
    max_steps = 4
    if "--max-steps" in sys.argv:
        max_steps = int(sys.argv[sys.argv.index("--max-steps") + 1])
    jax.distributed.initialize(coordinator_address=addr, num_processes=2,
                               process_id=pid)
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()

    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    from distributed_sod_project_tpu.configs import config_from_dict
    from distributed_sod_project_tpu.train.loop import fit

    with open(cfg_path) as f:
        cfg = config_from_dict(json.load(f))

    out = fit(cfg, workdir=workdir, max_steps=max_steps)
    # One parseable line per rank; the parent asserts cross-rank
    # agreement of train/eval metrics (every host sweeps the full val
    # set, so ranking inputs must be identical).
    print("WORKER_RESULT " + json.dumps(
        {"pid": pid, **{k: float(v) for k, v in out.items()}}), flush=True)


if __name__ == "__main__":
    main()
