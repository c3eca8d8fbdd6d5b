"""The faults the hybrid cell's comparison is held against, planted on
the PROGRAM (the reference stays as published), one name each.  Used by
``tests/test_nemotron_h.py``, ``test_rehearsal_hybrid.py`` (CPU, tiny
widths) and ``chip_faults_hybrid.py`` (the chip, the cell's own size).

``plant(name, setattr)`` patches the program through ``setattr(obj,
attribute, value)`` (``monkeypatch.setattr`` in a test) and returns the
``--set`` overrides the run needs besides.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

FAULTS = ("no_scaling_factor", "top_k_one_short", "relu_for_relu2",
          "no_shared_expert", "one_held_expert_never_projected_up",
          "state_dropped_at_chunk_edge")
SKIPPED = 3  # the held expert whose part never reaches the up-projection


def plant(name: str, setattr_) -> list:
    from distributed_sod_project_tpu.models import granite, nemotron_h

    if name == "no_scaling_factor":      # routed_scaling_factor 5 left out
        return ["model.lm.routed_scaling_factor=1.0"]
    if name == "top_k_one_short":        # 21 experts kept for 22
        real_layer = nemotron_h.LatentExpertLayer

        def one_short(experts, held, first, top_k, *a, **kw):
            return real_layer(experts, held, first, top_k - 1, *a, **kw)

        setattr_(nemotron_h, "LatentExpertLayer", one_short)
        return []
    if name == "relu_for_relu2":         # routed and shared experts alike
        setattr_(nemotron_h, "relu2", nn.relu)
        return []
    if name == "no_shared_expert":
        # the same two leaves (weights recipe and reference name them),
        # their product left out of the layer's sum
        class Dropped(nn.Module):
            width: int
            dtype: jnp.dtype = jnp.bfloat16
            param_dtype: jnp.dtype = jnp.float32

            @nn.compact
            def __call__(self, x):
                kw = (self.dtype, self.param_dtype)
                u = nemotron_h._dense(self.width, "up", *kw)(x)
                return jnp.zeros_like(nemotron_h._dense(
                    x.shape[-1], "down", *kw)(nemotron_h.relu2(u)))

        setattr_(nemotron_h, "ReLU2MLP", Dropped)
        return []
    if name == "one_held_expert_never_projected_up":
        # held expert ``SKIPPED``'s weighted output is left out of the
        # latent sum, so ``latent_up`` never sees it
        real = nemotron_h.held_experts_sum

        def skipping(xt, idx, w, weights, ffn, *, experts, first_expert):
            w = jnp.where(idx == first_expert + SKIPPED, 0.0, w)
            return real(xt, idx, w, weights, ffn, experts=experts,
                        first_expert=first_expert)

        setattr_(nemotron_h, "held_experts_sum", skipping)
        return []
    if name == "state_dropped_at_chunk_edge":
        # every chunk of the scan starts from a zero state: the chunks
        # as sequences of their own
        real = granite.ssd_scan

        def dropped(x, dt, a, b, c, *, chunk=256, **kw):
            bs, n = x.shape[:2]
            cut = lambda t: t.reshape(  # noqa: E731
                (bs * n // chunk, chunk) + t.shape[2:])
            return real(cut(x), cut(dt), a, cut(b), cut(c), chunk=chunk,
                        **kw).reshape(x.shape)

        setattr_(granite, "ssd_scan", dropped)
        return []
    raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
