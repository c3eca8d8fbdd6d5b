"""The faults the looped cell's comparison is held against, planted on
the PROGRAM (the reference stays as published), one name each.  Used by
``tests/test_ouro.py``, ``test_rehearsal_loop.py`` (CPU, tiny widths) and
``chip_faults_loop.py`` (the chip, the cell's own size).

``plant(name, setattr)`` patches the program through ``setattr(obj,
attribute, value)`` (``monkeypatch.setattr`` in a test) and returns the
``--set`` overrides the run needs besides.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

FAULTS = ("three_passes", "no_norm_between_passes", "uniform_exit_weights",
          "no_entropy_term", "last_pass_loss_alone")


def plant(name: str, setattr_) -> list:
    from distributed_sod_project_tpu.losses import token_ce
    from distributed_sod_project_tpu.models import ouro

    if name == "three_passes":           # 3 passes for 4
        return ["model.lm.ut_steps=3"]
    if name == "no_entropy_term":        # beta H(p) dropped
        return ["model.lm.exit_beta=0.0"]
    if name == "no_norm_between_passes":
        # ``Pass.__call__`` as models/ouro.py has it, but the next pass
        # reads the stack's output itself; head and gate still read the
        # normed state
        class Pass(ouro.Pass):
            @nn.compact
            def __call__(self, h):
                c = self.cfg
                for i in range(len(c.layer_types)):
                    h = self.block(c, self.dtype, self.param_dtype,
                                   name=f"layer_{i}")(h)
                state = ouro.RMSNorm(c.norm_eps, self.dtype,
                                     name="final_norm")(h)
                gate = nn.Dense(
                    1, dtype=jnp.float32, param_dtype=self.param_dtype,
                    precision=lax.Precision.HIGHEST, name="exit_gate")(
                        state.astype(jnp.float32))[..., 0]
                return h, state, gate

        setattr_(ouro, "Pass", Pass)
        return []
    if name == "uniform_exit_weights":   # p_t = 1 / R whatever the gate
        def uniform(gate_logits):
            p = jnp.full(gate_logits.shape, 1.0 / gate_logits.shape[0],
                         jnp.float32)
            return p, jnp.log(p)

        setattr_(token_ce, "exit_distribution", uniform)
        return []
    if name == "last_pass_loss_alone":   # mean CE of the last pass
        def last(gate_logits):
            p = jnp.zeros(gate_logits.shape, jnp.float32).at[-1].set(1.0)
            return p, jnp.zeros_like(p)

        setattr_(token_ce, "exit_distribution", last)
        return []
    raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")

