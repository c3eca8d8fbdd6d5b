"""Central registry of every ``DSOD_*`` environment knob.

Env knobs used to be read wherever they were born.  This module is
the single source of truth:

- every knob is declared ONCE here (name, default, whether it selects
  a different *compiled program*, one-line doc, where it is read);
- every read goes through :func:`read` — the only place in the
  codebase allowed to touch ``os.environ`` for a ``DSOD_`` name
  (``tools/dsodlint.py`` check ``env-coherence``: a read that bypasses
  it, or names no row here, fails lint);
- the generated table in docs/PERFORMANCE.md ("Environment knobs") is
  rendered from this registry (:func:`markdown_table`), so the docs
  cannot drift from the code.

``program_affecting=True`` is documentation for that table: two runs
with different values of the variable compile DIFFERENT XLA programs,
so a measurement has to say which value it ran under (ROADMAP D3: each
such row is to become a constant, a shape rule or go).  Host-side
knobs (paths, process-pool method, fault injection) are False.
"""

from __future__ import annotations

import os
from typing import Dict, NamedTuple, Optional


class EnvVar(NamedTuple):
    name: str
    default: Optional[str]    # value when unset (None = genuinely unset)
    program_affecting: bool   # selects a different compiled program
    doc: str                  # one line, rendered into PERFORMANCE.md
    read_at: str              # where the value is consumed


_ENTRIES = (
    EnvVar("DSOD_STEM_IMPL", None, True,
           "'s2d' computes the ResNet stem as space-to-depth + 4x4 "
           "conv (same arithmetic, TPU-friendlier tiling).",
           "models/backbones/resnet.py"),
    EnvVar("DSOD_FLASH_BLOCK_Q", None, True,
           "Flash-attention Q block rows (on-hardware tuning; "
           "tools/bench_flash.py sweeps it).",
           "pallas/flash_attention.py"),
    EnvVar("DSOD_FLASH_BLOCK_KV", None, True,
           "Flash-attention KV block rows (paired with "
           "DSOD_FLASH_BLOCK_Q).",
           "pallas/flash_attention.py"),
    EnvVar("DSOD_DLF_VMEM_MB", None, True,
           "Scoped-VMEM ceiling override for the dynamic-filter "
           "kernel (MB; <=0 = compiler default).",
           "pallas/dynamic_filter.py"),
    EnvVar("DSOD_RESAMPLE_VMEM_MB", None, True,
           "Scoped-VMEM ceiling override for the fused-resample "
           "kernel (MB; <=0 = compiler default).",
           "pallas/fused_resample.py"),
    EnvVar("DSOD_CONV_VMEM_MB", None, True,
           "Scoped-VMEM ceiling override for the fused conv-stage "
           "kernels (MB; <=0 = compiler default).",
           "pallas/fused_conv.py"),
    EnvVar("DSOD_FAULTS", "", False,
           "Deterministic fault-injection plan for the chaos suites "
           "(resilience/inject.py spec syntax); empty = no faults.",
           "resilience/inject.py"),
    EnvVar("DSOD_NATIVE_LIB", None, False,
           "Path override for the native host-decode shared library "
           "(default: native/build/libdsod_host.so).",
           "data/native.py"),
    EnvVar("DSOD_DECODE_MP", "spawn", False,
           "multiprocessing start method for the decode process pool "
           "(spawn default: fork inherits held locks from a "
           "jax-initialized process).",
           "data/pipeline.py"),
    EnvVar("DSOD_NO_COMPILE_CACHE", None, False,
           "Any non-empty value disables the persistent XLA "
           "compilation cache setup.",
           "utils/platform.py"),
    EnvVar("DSOD_BISECT_EXPORT", None, False,
           "'1' makes tools/bisect_swin_eval.py stage scripts "
           "jax.export for TPU instead of executing (read inside the "
           "generated stage script).",
           "tools/bisect_swin_eval.py (generated stage)"),
    EnvVar("DSOD_T1_FAST", None, False,
           "Any non-empty value makes tools/t1.sh skip the non-gating "
           "smokes (read by the shell script, not Python).",
           "tools/t1.sh"),
)

REGISTRY: Dict[str, EnvVar] = {e.name: e for e in _ENTRIES}


def spec(name: str) -> EnvVar:
    """The registry row for ``name``; loud KeyError for unregistered
    names — an unregistered knob is a bug, not a feature request."""
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"{name!r} is not a registered DSOD env var — add it to "
            "utils/envvars.py") from None


def read(name: str, env: Optional[dict] = None) -> Optional[str]:
    """THE one sanctioned ``os.environ`` read for ``DSOD_*`` knobs
    (every other read site fails ``tools/dsodlint.py`` env-coherence).
    Returns the raw string, or the registry default when unset.
    ``env`` overrides the source mapping (injectable for tests)."""
    e = spec(name)
    v = (os.environ if env is None else env).get(name)
    return e.default if v is None else v


def read_int(name: str, fallback: int, env: Optional[dict] = None) -> int:
    """Integer knob: ``fallback`` when unset or empty."""
    v = read(name, env=env)
    return int(v) if v else fallback


def markdown_table() -> str:
    """The docs/PERFORMANCE.md "Environment knobs" table body —
    regenerate with ``python -m distributed_sod_project_tpu.utils.envvars``."""
    lines = ["| Knob | Default | Program-affecting | Read at | What it does |",
             "|---|---|---|---|---|"]
    for e in _ENTRIES:
        default = "*(unset)*" if e.default is None else f"`{e.default!r}`"
        lines.append(
            f"| `{e.name}` | {default} | "
            f"{'yes' if e.program_affecting else 'no'} | "
            f"`{e.read_at}` | {e.doc} |")
    return "\n".join(lines)


if __name__ == "__main__":
    print(markdown_table())
