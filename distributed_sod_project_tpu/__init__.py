"""distributed_sod_project_tpu — a TPU-native salient-object-detection framework.

A ground-up JAX/XLA/Pallas re-design of the capability surface of
``lartpang/Distributed-SOD-Project`` (see ``SURVEY.md`` — the reference
mount was unreadable, so parity targets come from SURVEY.md §2's
component inventory and ``BASELINE.json``):

- Model zoo: MINet (VGG16/ResNet50), HDFNet (RGB-D two-stream), U²-Net,
  BASNet, Swin-T SOD  (``models/``)
- Losses: BCE + soft-IoU + SSIM + CEL with multi-level deep supervision
  (``losses/``, fused Pallas reductions in ``pallas/``)
- Data: DUTS / NJU2K / NLPR loaders with per-host sharding and a
  synthetic fallback — three batch-identical backends (C++/PIL host,
  tf.data, Grain) (``data/``), C++ decode/encode runtime (``native/``)
- Parallelism: SPMD data-parallel training over a ``jax.sharding.Mesh``
  via ``shard_map`` (cross-replica BatchNorm + gradient psum riding
  ICI), GSPMD tensor parallelism + ZeRO-1 weight-update sharding, and
  ring-attention sequence parallelism for the transformer path
  (``parallel/``)
- Train/eval engines, poly-LR schedules, orbax checkpointing, SOD
  metrics (MAE, max-Fβ, S-measure, E-measure)  (``train/``, ``eval/``,
  ``metrics/``)

The package directory uses underscores (``distributed_sod_project_tpu``)
because the upstream-style name ``distributed-sod-project_tpu`` is not a
valid Python identifier.
"""

import time as _time

# The package's first line, on the clock of the host-clock sink
# (utils/tracing.py): ``fit()`` records ``dsod.setup.before_fit`` from
# here to its entry — the caller's imports of JAX and of the program,
# the backend's start, its own set-up.  Nothing heavy is imported here.
T_IMPORT = _time.perf_counter()

__version__ = "0.1.0"
