"""Runner of the hybrid token model's training cell (Mamba-2, attention
and latent sparse-expert layers): ``runners/train_lm.py``'s ``run``
itself — the program's own ``fit()`` in this process, the compiled step
tapped for its first three calls, the window on the ``on_metrics``
ticks, the plain reference following the same rows, the routing rows —
with the two names a model of both kinds changes swapped while it runs:
the weights come from ``harness/weights_hybrid.py`` (``weights_lm``'s
recipe raises on ``A_log``, ``dt_bias``, ``D`` and the conv's bias,
``weights_ssm``'s on the stacked experts and ``expert_bias``), and the
tick table keeps the mixers' counters, ``moe_bias_abs_max`` and the
hottest expert layer's ``moe_pairs_here_share_max`` and
``moe_buffer_fill_max`` beside the expert layers'.

``correct`` is ``train_lm``'s: every judged number inside its limit,
every tick's loss finite, no compilation inside the window,
and ``moe_dropped_pairs`` 0 on every tick.  The cell gives NO limit to
``moe_pairs_here_share_drift`` (the row is printed, not judged): the
share of one chip's 8 of 512 experts reads 0.2-1.7 in sound runs and 1.0
where the routing has left them altogether, so no limit can fail it
(PERF.md section 7, item 28).
"""

from __future__ import annotations

from ..harness.weights_hybrid import variables_builder
from . import train_lm
from .train_ssm import SSM_KEYS

KEYS = train_lm.MOE_KEYS + ("moe_bias_abs_max", "moe_pairs_here_share_max",
                            "moe_buffer_fill_max") + SSM_KEYS


def run(ctx) -> dict:
    swapped = {"variables_builder": variables_builder, "MOE_KEYS": KEYS}
    kept = {k: getattr(train_lm, k) for k in swapped}
    for k, v in swapped.items():
        setattr(train_lm, k, v)
    try:
        return train_lm.run(ctx)
    finally:
        for k, v in kept.items():
            setattr(train_lm, k, v)
