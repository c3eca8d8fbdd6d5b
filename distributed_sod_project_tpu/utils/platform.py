"""JAX platform selection, device naming and compile-cache placement
shared by every entrypoint ([B:5] --device)."""

from __future__ import annotations

import os
import threading
import time
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


_COMPILE_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


class CompileStats:
    """What JAX's own monitoring events say this process spent making
    programs: seconds of Python tracing (``trace``), of lowering to
    StableHLO (``lower``) and in backend compiles (``compile``; a
    persistent-cache hit still passes through that event: its seconds
    are the retrieval), and the cache's hits and misses.

    ONE listener a process — ``jax.monitoring`` listeners cannot be
    unregistered, so every ``CompileStats()`` is the same object
    (``train.py``, ``chip_smoke.py`` and ``fit()`` share it) and a
    caller that wants "since I started" keeps :meth:`mark` and asks
    :meth:`since`.

    Events NEST: a jitted function traced inside another's trace, an
    eager op compiled while a function is traced, each reports its own
    seconds and is inside its parent's too.  ``by_name`` holds
    ``[events, seconds]`` per ``(kind, function name)`` with the
    seconds as JAX reports them, children included (a step traced
    twice reads 2 events; "the step's trace took 14 s, all in").
    ``seconds`` per kind, and the newest ``MAX_EVENTS`` events kept one
    by one as ``(kind, end time on time.perf_counter, seconds, function
    name)`` for :meth:`between`, hold each event's OWN seconds — its
    duration less its direct children's, found as the events of the
    same thread that began after it did — so a nested event counts
    once.  :meth:`checkpoint` keeps the counters
    at an instant a later reader will ask about (:meth:`before`)."""

    _shared = None
    MAX_EVENTS = 4096

    def __new__(cls):
        if cls._shared is None:
            import collections

            from jax import monitoring

            self = super().__new__(cls)
            self.seconds = {"trace": 0.0, "lower": 0.0, "compile": 0.0}
            self.cache_hits = 0
            self.cache_misses = 0
            self.by_name = {}
            self.events = collections.deque(maxlen=cls.MAX_EVENTS)
            self.checkpoints = collections.deque(maxlen=64)
            self._ended = threading.local()  # per thread: [(start, s)]
            monitoring.register_event_listener(self._on_event)
            monitoring.register_event_duration_secs_listener(
                self._on_duration)
            cls._shared = self
        return cls._shared

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        kind = _COMPILE_KINDS.get(event)
        if kind is None:
            return
        name = str(kw.get("fun_name", "?"))
        n_s = self.by_name.setdefault((kind, name), [0, 0.0])
        n_s[0] += 1
        n_s[1] += seconds
        # Own seconds: events end innermost first, so the ones this
        # thread saw end since this one began are its children.
        t_end = time.perf_counter()
        start = t_end - seconds
        try:
            ended = self._ended.events
        except AttributeError:
            ended = self._ended.events = []
        own = float(seconds)
        while ended and ended[-1][0] >= start - 1e-4:
            own -= ended.pop()[1]
        ended.append((start, float(seconds)))
        del ended[:-256]  # top-level events have no parent to pop them
        own = max(own, 0.0)
        self.seconds[kind] += own
        self.events.append((kind, t_end, own, name))

    def mark(self) -> Dict[str, object]:
        """The counters now, to subtract later (:meth:`since`)."""
        return dict(self.seconds, cache_hits=self.cache_hits,
                    cache_misses=self.cache_misses,
                    by_name={k: tuple(v) for k, v in
                             dict(self.by_name).items()})

    def since(self, mark: Dict[str, object]) -> Dict[str, object]:
        """Seconds per kind, hits and misses since ``mark``, and under
        ``largest`` the three functions of each kind that took the most
        seconds since, as ``(name, seconds, events)``."""
        now = self.mark()
        was = mark["by_name"]
        out = {k: v - mark[k] for k, v in now.items() if k != "by_name"}
        rows = {}
        for (kind, name), (n, s) in now["by_name"].items():
            n0, s0 = was.get((kind, name), (0, 0.0))
            if n > n0:
                rows.setdefault(kind, []).append((name, s - s0, n - n0))
        out["largest"] = {kind: sorted(r, key=lambda x: -x[1])[:3]
                          for kind, r in rows.items()}
        return out

    def between(self, t0: float, t1: float):
        """The kept events whose end time lies in ``(t0, t1]``."""
        return [e for e in list(self.events) if t0 < e[1] <= t1]

    def checkpoint(self, t: float) -> None:
        """Keep the seconds per kind as they stand, under the time
        ``t`` (``time.perf_counter``) the caller gives the instant."""
        self.checkpoints.append((t, dict(self.seconds)))

    def before(self, t: float) -> Optional[Dict[str, float]]:
        """Seconds per kind at the first kept checkpoint at or after
        ``t`` (``fit()`` takes one at each of its first logging
        boundaries), or ``None``."""
        return next((dict(s) for t_kept, s in sorted(
            self.checkpoints, key=lambda c: c[0]) if t_kept >= t), None)

    def as_dict(self) -> Dict[str, object]:
        return {"seconds": round(self.seconds["compile"], 3),
                "trace_seconds": round(self.seconds["trace"], 3),
                "lower_seconds": round(self.seconds["lower"], 3),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def pin_cpu() -> None:
    """Pin jax to the CPU backend.  Shared by the offline tools
    (eval_preds, inspect_ckpt, export_model), which never need a
    chip and must not take one from a run that does."""
    select_platform("cpu")
