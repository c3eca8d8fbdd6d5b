#!/usr/bin/env python
"""The part of the compile-cache key a code change can move: sha256 of
a registered config's train step, lowered for a DESCRIBED TPU v5e (no
chip) and canonicalised as JAX's persistent cache does it (debug info
stripped from the outer module).

``tools/dump_hlo.py`` diffs the StableHLO of a CPU lowering, where the
Pallas kernels run in interpret mode.  On the chip each kernel is a
``tpu_custom_call`` whose serialized Mosaic body is part of the key.
The package keeps Python frames out of it
(``distributed_sod_project_tpu/pallas/__init__.py``), so the hash
follows the program alone: the same from any checkout path and whatever
line moves in any file, this one among them.  Two trees whose hashes
differ compile different programs:

    python tools/step_cache_key.py --config basnet_ds
    python tools/step_cache_key.py --config lfm2_8b_a1b_ep4 \\
        --seq-len 8192 --batch 4 [--root <another tree>]

``--set section.field=value`` shrinks a config
(tests/test_chip_compile.py runs one at tiny widths and lowers its
whole-step cases through :func:`lower_step`).
"""

from __future__ import annotations

import argparse
import hashlib
import importlib
import os
import sys


def lower_step(cfg, device):
    """``cfg``'s train step at ``cfg.global_batch_size``, lowered for
    ``device`` (a described chip: nothing is placed) on abstract state.
    The caller has steered ``jax.default_backend`` and the scoped-VMEM
    rule to the chip: ``jax.devices()`` still says cpu here."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_sod_project_tpu.models import build_model, kind_of
    from distributed_sod_project_tpu.parallel.engine import \
        make_unified_train_step
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    mesh = Mesh(np.array([device]).reshape(1, 1, 1),
                ("data", "model", "seq"))
    model = build_model(cfg.model)
    tx, sched = build_optimizer(cfg.optim, 20000)
    batch = kind_of(model).zero_batch(cfg, cfg.global_batch_size)
    state = jax.eval_shape(lambda: create_train_state(
        jax.random.key(0), model, tx, batch))
    on = lambda spec: lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=NamedSharding(mesh, spec))
    step = make_unified_train_step(
        model, cfg.loss, tx, mesh, preset="dp", schedule=sched,
        donate_batch=True, remat=cfg.model.remat,
        remat_policy=cfg.model.remat_policy)
    return step.lower(jax.tree_util.tree_map(on(P()), state),
                      jax.tree_util.tree_map(on(P("data")), batch))


def canonical_ir(lowered) -> bytes:
    """The bytes JAX's persistent cache hashes for a lowered program."""
    from jax._src import cache_key

    return cache_key._canonicalize_ir(lowered.compiler_ir(),
                                      cache_key.IgnoreCallbacks.NO)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    p.add_argument("--config", default="basnet_ds")
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--image-size", type=int, default=320)
    p.add_argument("--seq-len", type=int, default=8192)
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="SECTION.FIELD=VALUE")
    a = p.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, a.root)
    import jax
    from jax.experimental import topologies

    from distributed_sod_project_tpu.configs import (apply_overrides,
                                                     get_config)

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
        f"data.image_size={s},{s}", f"data.seq_len={a.seq_len}"]
        + a.overrides)
    lowered = lower_step(cfg, dev)
    ir = canonical_ir(lowered)
    print(f"{a.config} canonical step IR sha256 "
          f"{hashlib.sha256(ir).hexdigest()} ({len(ir)} bytes, "
          f"{lowered.as_text().count('tpu_custom_call')} kernels)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
