"""Train state — the single pytree the compiled step transforms.

Replaces the reference's mutable trio (model.state_dict(), optimizer
state, epoch counter; SURVEY.md §2 C11, §3.4) with one immutable pytree:
``train_step(state, batch) -> state`` with the input buffers donated, so
XLA updates parameters in place in HBM.

Static callables (``apply_fn``, the optax transform) live in closures,
NOT in the state, so the state is a pure array pytree — directly
serializable by orbax and shardable by pjit without pytree surgery.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import struct


@struct.dataclass
class TrainState:
    step: jnp.ndarray            # i32 scalar
    params: Any                  # f32 param pytree
    batch_stats: Any             # BatchNorm running stats (f32)
    opt_state: Any               # optax state
    ema_params: Any = None       # EMA of params (None = EMA disabled)
    # int8_ef error-feedback residual (parallel.grad_compression):
    # (n_data, n_grad_elems) f32, row r = replica r's accumulated
    # quantization error, sharded P('data') — per-replica state that
    # checkpoints with the rest of the pytree.  None when compression
    # is off (the overwhelmingly common case; pytree shape unchanged).
    comm_residual: Any = None

    def variables(self) -> Dict[str, Any]:
        return {"params": self.params, "batch_stats": self.batch_stats}

    def eval_variables(self) -> Dict[str, Any]:
        """Variables for evaluation: the EMA weights when tracked (the
        averaged model generalises better; reference-era repos get the
        same effect from picking the best epoch), else the raw params."""
        params = self.ema_params if self.ema_params is not None else self.params
        return {"params": params, "batch_stats": self.batch_stats}


def create_train_state(rng, model, tx, sample_batch,
                       pretrained: str = None,
                       ema: bool = False) -> TrainState:
    """Initialise params/batch_stats from one (host-side) sample batch
    and wrap them with the optimizer's initial state.  ``pretrained``
    merges a ported ImageNet backbone (.npz) over the fresh init.
    ``ema=True`` seeds the EMA tree as a copy of the initial params."""
    from ..models import kind_of

    # ONE compiled program, not an eager init: un-jitted, flax's init
    # dispatches (and on the chip compiles) every primitive on its own —
    # 165 s before the trainer's first log line for minet_r50_dp on a
    # v5e, ~110 s of a server's start (chip_smoke.py, PR 23).
    variables = jax.jit(
        lambda r, *inputs: model.init(r, *inputs, train=False))(
            rng, *kind_of(model).init_inputs(sample_batch))
    if pretrained:
        from ..models.pretrained import load_pretrained

        variables = load_pretrained(variables, pretrained)
    return _wrap_state(variables, tx, ema)


def _wrap_state(variables, tx, ema: bool) -> TrainState:
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    opt_state, ema_params = jax.jit(lambda p: (
        tx.init(p),
        jax.tree_util.tree_map(jnp.copy, p) if ema else None))(params)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=opt_state,
        ema_params=ema_params,
    )


def random_init_setup(cfg, batch_size: int, hw: int,
                      total_steps: int = 1000):
    """chip_smoke.py's measurement recipe, kept beside the state: the
    config's model and optimizer, one host batch of seeded noise at
    ``batch_size`` x ``hw`` x ``hw`` (depth where the config uses it)
    and the TrainState initialised from it.  Returns ``(model, tx,
    schedule, host_batch, state)``; the caller picks the mesh and the
    step."""
    import numpy as np

    from ..models import build_model
    from .optim import build_optimizer

    model = build_model(cfg.model)
    tx, sched = build_optimizer(cfg.optim, total_steps)
    rng = np.random.RandomState(0)
    host_batch = {
        "image": rng.randn(batch_size, hw, hw, 3).astype(np.float32),
        "mask": (rng.rand(batch_size, hw, hw, 1) > 0.5
                 ).astype(np.float32),
    }
    if cfg.data.use_depth:
        host_batch["depth"] = rng.randn(batch_size, hw, hw, 1
                                        ).astype(np.float32)
    state = create_train_state(jax.random.key(0), model, tx, host_batch)
    return model, tx, sched, host_batch, state


def param_count(state: TrainState) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(state.params))
