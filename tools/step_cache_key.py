#!/usr/bin/env python
"""The part of the compile-cache key a code change can move: sha256 of
a config's train step, lowered for a DESCRIBED TPU v5e (no chip) and
canonicalised as JAX's persistent cache does it (debug info stripped
from the outer module).

``tools/dump_hlo.py`` diffs the StableHLO of a CPU lowering, where the
Pallas kernels run in interpret mode.  On the chip each kernel is a
``tpu_custom_call`` whose serialized Mosaic body keeps the FILE PATHS
and LINE NUMBERS of the frames above it, and ``strip-debuginfo`` does
not reach inside.  So a line inserted above ``_forward_loss`` in
``parallel/engine.py`` — or a checkout at another path — changes the
key of every image config's step while ``dump_hlo.py`` still says
"identical" (PR 28 met both).  To compare two trees, run ONE copy of
this file (it is a frame above the kernels too) on each tree THROUGH
THE SAME PATH, a symlink switched between them:

    cp tools/step_cache_key.py /tmp/k.py
    ln -sfn <tree> /tmp/w && python /tmp/k.py --root /tmp/w --config basnet_ds
"""

from __future__ import annotations

import argparse
import hashlib
import importlib
import os
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    p.add_argument("--config", default="basnet_ds")
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--image-size", type=int, default=320)
    a = p.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, a.root)
    import jax
    import numpy as np
    from jax._src import cache_key
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_sod_project_tpu.configs import (apply_overrides,
                                                     get_config)
    from distributed_sod_project_tpu.models import build_model
    from distributed_sod_project_tpu.parallel.engine import \
        make_unified_train_step
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    jax.config.update("jax_enable_compilation_cache", False)
    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    # jax.devices() still says cpu here: steer the kernels to Mosaic.
    importlib.import_module(
        "distributed_sod_project_tpu.pallas.vmem_budget"
    )._device_kind = lambda: dev.device_kind
    jax.default_backend = lambda: "tpu"
    s = a.image_size
    cfg = apply_overrides(get_config(a.config), [
        f"global_batch_size={a.batch}", "mesh.data=1",
        f"data.image_size={s},{s}"])
    mesh = Mesh(np.array([dev]).reshape(1, 1, 1), ("data", "model", "seq"))
    model = build_model(cfg.model)
    tx, sched = build_optimizer(cfg.optim, 20000)
    batch = {"image": np.zeros((a.batch, s, s, 3), np.float32),
             "mask": np.zeros((a.batch, s, s, 1), np.float32)}
    if cfg.data.use_depth:
        batch["depth"] = np.zeros((a.batch, s, s, 1), np.float32)
    state = jax.eval_shape(lambda: create_train_state(
        jax.random.key(0), model, tx, batch))
    on = lambda spec: lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=NamedSharding(mesh, spec))
    step = make_unified_train_step(
        model, cfg.loss, tx, mesh, preset="dp", schedule=sched,
        donate_batch=True, remat=cfg.model.remat,
        remat_policy=cfg.model.remat_policy)
    lowered = step.lower(jax.tree_util.tree_map(on(P()), state),
                         jax.tree_util.tree_map(on(P("data")), batch))
    ir = cache_key._canonicalize_ir(lowered.compiler_ir(),
                                    cache_key.IgnoreCallbacks.NO)
    print(f"{a.config} canonical step IR sha256 "
          f"{hashlib.sha256(ir).hexdigest()} ({len(ir)} bytes, "
          f"{lowered.as_text().count('tpu_custom_call')} kernels)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
