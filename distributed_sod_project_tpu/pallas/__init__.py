"""The Pallas kernels.

Importing this package (every kernel's user does) takes the Python
frames out of the MLIR locations JAX writes, for the whole process.  On
the chip a kernel is a ``tpu_custom_call`` whose serialized Mosaic body
keeps the file path, line and column of up to
``jax_traceback_in_locations_limit`` (JAX's default: 10) frames above
it; those bytes are part of the persistent compile cache's key and
``strip-debuginfo`` does not reach inside them, so a comment line added
to ANY file a trace passes through, or a checkout at another path, made
every kernel-bearing step a cache miss (PERF.md section 6, PR 46).  At
0 a step's key follows its program alone.  The price: a Mosaic compile
error, a runtime error's MLIR location or a profile's source column no
longer names a Python line.  The ``dsod.*`` scopes and the kernels'
names still do: they come from the name stack, not from tracebacks.
"""

import jax

jax.config.update("jax_traceback_in_locations_limit", 0)

from .dynamic_filter import fused_dynamic_filter
from .flash_attention import flash_attention
from .fused_loss import fused_bce_iou_cel, pixel_region_sums
from .fused_resample import (
    fused_resample_available,
    fused_upsample2,
    fused_upsample2_merge,
)
from .fused_ssim import (
    fused_ssim_available,
    fused_ssim_loss,
    fused_ssim_mean,
)

__all__ = [
    "flash_attention",
    "fused_dynamic_filter",
    "fused_bce_iou_cel",
    "fused_resample_available",
    "fused_ssim_available",
    "fused_ssim_loss",
    "fused_ssim_mean",
    "fused_upsample2",
    "fused_upsample2_merge",
    "pixel_region_sums",
]
