"""The faults the decoder-hybrid-decoder cell's comparison is held
against, planted on the PROGRAM (the reference stays as published), one
name each.  Used by ``tests/test_phi4flash.py``,
``test_rehearsal_phi4flash.py`` (CPU, tiny widths) and
``chip_faults_phi4flash.py`` (the chip, the cell's own size).

``plant(name, setattr, window=...)`` patches the program through
``setattr(obj, attribute, value)`` (``monkeypatch.setattr`` in a test)
and returns the ``--set`` overrides the run needs besides; ``window`` is
the sound run's (the published 512; a test's tiny one).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

FAULTS = ("window_twice_as_long", "no_one_minus_lambda_init",
          "lambda_init_of_the_local_index", "memory_after_the_gate",
          "cross_reads_the_window_layers_keys", "state_dropped_at_chunk_edge",
          "one_decay_a_channel")


def plant(name: str, setattr_, window: int = 512) -> list:
    from distributed_sod_project_tpu.models import phi4flash

    if name == "window_twice_as_long":       # 1,024 keys for 512
        return [f"model.lm.window={2 * window}"]
    if name == "no_one_minus_lambda_init":   # the normed difference as it is
        setattr_(phi4flash, "out_gain", lambda depth: 1.0)
        return []
    if name == "lambda_init_of_the_local_index":
        # 1, 3, 5 on this stage for the published 15, 17, 19
        real, first = phi4flash.lambda_init, 14
        setattr_(phi4flash, "lambda_init", lambda depth: real(depth - first))
        return []
    if name == "memory_after_the_gate":
        # m = y * silu(z) where the published model hands on y
        sound = phi4flash.Mamba1Mixer

        class Gated(sound):
            @nn.compact
            def __call__(self, u):
                out, y, counters = sound.__call__(self, u)
                z = (u @ self.variables["params"]["in_proj"]["kernel"]
                     .astype(u.dtype))[..., self.inner:]
                return out, y * nn.silu(z), counters

        setattr_(phi4flash, "Mamba1Mixer", Gated)
        return []
    if name == "cross_reads_the_window_layers_keys":
        # the keys and values kept are the windowed layer's, not the
        # full layer's
        setattr_(phi4flash, "KEEPS", {"mamba": "memory",
                                      "window": "keys_values"})
        return []
    real = phi4flash.selective_scan
    if name == "state_dropped_at_chunk_edge":
        # every chunk of the scan starts from a zero state: the chunks
        # as sequences of their own
        def dropped(x, delta, a, b, c, d, *, chunk=128, **kw):
            bs, n = x.shape[:2]
            cut = lambda t: t.reshape(  # noqa: E731
                (bs * n // chunk, chunk) + t.shape[2:])
            return real(cut(x), cut(delta), a, cut(b), cut(c), d,
                        chunk=chunk, **kw).reshape(x.shape)

        setattr_(phi4flash, "selective_scan", dropped)
        return []
    if name == "one_decay_a_channel":
        # A[c, n] -> its mean over the states: Mamba-2's scalar decay
        def scalar(x, delta, a, *rest, **kw):
            return real(x, delta, jnp.broadcast_to(
                jnp.mean(a, -1, keepdims=True), a.shape), *rest, **kw)

        setattr_(phi4flash, "selective_scan", scalar)
        return []
    raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
