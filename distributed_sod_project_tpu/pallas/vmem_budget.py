"""Shared scoped-VMEM compiler-params rule for the Pallas kernels.

XLA's memory-space assignment can park a custom call's full output in
VMEM and die against the default 16 MB scoped limit even when the
per-grid-step windows are tiny, and the whole-image blocks of the
conv/resample kernels need tens of MB by design.  The kernels
therefore raise the scoped ceiling to 100 MB where the chip has the
VMEM for it (v5e: 128 MiB/core; the v5e compiler accepts the raise —
tests/test_chip_compile.py).  Each kernel keeps its own env-var
escape hatch (0 = compiler default).
"""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

_RAISED_LIMIT = 100 * 1024 * 1024


def _device_kind() -> str | None:
    """``device_kind`` of the TPU this process compiles for; ``None``
    off-TPU, where the kernels run in interpret mode and the compiler
    params are never read."""
    dev = jax.devices()[0]
    return dev.device_kind if dev.platform == "tpu" else None


def scoped_vmem_params(env_var: str) -> pltpu.CompilerParams:
    """The per-kernel scoped-VMEM ceiling, overridable via ``env_var``
    (MB; 0 or negative = compiler default; must be a registered
    program-affecting knob — utils/envvars.py).  An unknown TPU kind is
    an error (utils/chips.py): a limit past physical VMEM fails the
    compile with a far less helpful message."""
    from ..utils import envvars
    from ..utils.chips import chip_peaks

    env = envvars.read(env_var)
    if env is not None:
        mb = int(env)
        return (pltpu.CompilerParams() if mb <= 0
                else pltpu.CompilerParams(vmem_limit_bytes=mb * 1024 * 1024))
    kind = _device_kind()
    if kind is None or chip_peaks(kind).vmem_bytes <= _RAISED_LIMIT:
        return pltpu.CompilerParams()
    return pltpu.CompilerParams(vmem_limit_bytes=_RAISED_LIMIT)


def rows_per_band(n_rows: int, *, per_row: int, fixed: int,
                  budget: int) -> int:
    """Rows one grid step of a row-banded kernel takes: the most that
    fit ``budget`` (``per_row`` a row plus ``fixed`` a band, in
    whatever unit the caller counts), evened out so the bands differ by
    at most one row's worth of rounding; 0 when not one row fits."""
    fit = (budget - fixed) // per_row
    if fit < 1:
        return 0
    n_bands = -(-n_rows // fit)
    return -(-n_rows // n_bands)


def fitted_vmem_params(need_bytes: int, what: str) -> pltpu.CompilerParams:
    """A scoped-VMEM ceiling of ``need_bytes`` — what a kernel's shapes
    say it holds — for the chip this process compiles for; a
    ``ValueError`` where that passes the chip's VMEM (utils/chips.py).
    Off-TPU the kernels run in interpret mode: no limit, no params."""
    from ..utils.chips import chip_peaks

    kind = _device_kind()
    if kind is None:
        return pltpu.CompilerParams()
    limit = chip_peaks(kind).vmem_bytes
    if need_bytes > limit:
        raise ValueError(
            f"{what} needs {need_bytes / 2**20:.1f} MiB of VMEM; a "
            f"{kind} has {limit / 2**20:.0f} MiB")
    return pltpu.CompilerParams(vmem_limit_bytes=need_bytes)
