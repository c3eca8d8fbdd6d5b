"""Runner of the decoder-hybrid-decoder token model's training cell
(Mamba-1, windowed and full differential attention, a gated memory unit
and cross-attention): ``runners/train_ssm.py``'s ``run`` itself — the
program's own ``fit()`` in this process, the compiled step tapped for
its first three calls, the window on the ``on_metrics`` ticks, the plain
reference following the same rows — with the two names this model
changes swapped while it runs (``runners/train_hybrid.py``'s pattern):
the weights come from ``harness/weights_phi4flash.py`` (``weights_ssm``'s
recipe raises on the two-dimensional ``A_log``'s start, ``dt_proj`` and
the lambda vectors), and the tick table keeps ``diff_lambda_min`` /
``diff_lambda_max`` and ``gmu_memory_abs_max`` beside the mixers'
counters.

``correct`` is ``train_ssm``'s: every judged number inside its limit,
every tick's loss finite, no compilation inside the window.
"""

from __future__ import annotations

from ..harness.weights_phi4flash import variables_builder
from . import train_ssm

KEYS = train_ssm.SSM_KEYS + ("diff_lambda_min", "diff_lambda_max",
                             "gmu_memory_abs_max")


def run(ctx) -> dict:
    swapped = {"variables_builder": variables_builder, "SSM_KEYS": KEYS}
    kept = {k: getattr(train_ssm, k) for k in swapped}
    for k, v in swapped.items():
        setattr(train_ssm, k, v)
    try:
        return train_ssm.run(ctx)
    finally:
        for k, v in kept.items():
            setattr(train_ssm, k, v)
