"""JAX platform selection, device naming and compile-cache placement
shared by every entrypoint ([B:5] --device)."""

from __future__ import annotations

import os
from typing import Dict, Optional

# The one in-checkout cache location (git-ignored).  The path is part
# of a cache entry's key, so it must not move between runs.
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


class NoAcceleratorError(RuntimeError):
    """``--device tpu`` was asked for and this process has no TPU."""


def pin_platform(device: Optional[str]) -> None:
    """Pin ``jax_platforms`` to a ``--device {tpu,cpu}`` choice WITHOUT
    touching a backend.  ``None`` (flag unset) leaves JAX's own
    discovery alone — for library use; a CLI that reports a rate names
    the device it ran on (:func:`describe_device`)."""
    if device is None:
        return
    if device not in ("cpu", "tpu"):
        raise ValueError(f"unknown --device {device!r}")
    import jax

    jax.config.update("jax_platforms", device)


def verify_platform(device: Optional[str]) -> None:
    """Resolve the backend and raise :class:`NoAcceleratorError` when
    ``tpu`` was asked for and it is not one: a run that was asked for
    the chip never carries on on the CPU.  This initialises the
    backends, so a multi-host entry point calls it AFTER
    ``jax.distributed.initialize()`` (which refuses to run once they
    are)."""
    if device != "tpu":
        return
    import jax

    try:
        found = jax.default_backend()
    except RuntimeError as e:
        raise NoAcceleratorError(
            "--device tpu: JAX found no TPU backend in this process "
            f"({e}); the only backend here is the cpu, and a tpu "
            "run does not fall back to it") from e
    if found != "tpu":
        raise NoAcceleratorError(
            f"--device tpu: JAX resolved to the {found!r} backend, "
            "not a tpu")


def select_platform(device: Optional[str]) -> None:
    """Apply a ``--device {tpu,cpu}`` choice: :func:`pin_platform`,
    then :func:`verify_platform` on the spot.  Call before the first
    backend touch."""
    pin_platform(device)
    verify_platform(device)


def describe_device() -> Dict[str, object]:
    """The device this process runs on, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compilation_cache() -> Optional[str]:
    """Persistent XLA compilation cache: the zoo's 320×320 programs
    take minutes to compile for TPU, and every CLI invocation is a
    fresh process.  Call once per entry point, after the backend is
    resolved and before the first compile.  Returns the directory in
    effect (``None`` = cache off).

    Placement: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and no directory is set here; otherwise the cache lives at
    ONE fixed git-ignored path inside the checkout (``.jax_cache``).
    Off on the CPU backend (XLA:CPU entries pin the host's machine
    features and abort when replayed on different silicon) and with
    ``DSOD_NO_COMPILE_CACHE=1``."""
    from . import envvars

    if envvars.read("DSOD_NO_COMPILE_CACHE"):
        return None
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    os.makedirs(_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR


class CompileStats:
    """Seconds spent in backend compiles and persistent-cache
    hits/misses since construction, from JAX's own monitoring events
    (a cache hit still passes through the compile event: its seconds
    are the retrieval)."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def as_dict(self) -> Dict[str, object]:
        return {"seconds": round(self.seconds, 3),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def pin_cpu() -> None:
    """Pin jax to the CPU backend.  Shared by the offline tools
    (eval_preds, inspect_ckpt, export_model), which never need a
    chip and must not take one from a run that does."""
    select_platform("cpu")
