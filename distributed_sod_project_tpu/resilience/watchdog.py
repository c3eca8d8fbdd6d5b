"""In-process step watchdog — the wedged-dispatch detector.

The 2026-08-02 TPU window showed the failure mode this targets: the
runtime keeps answering ``jax.devices()`` while every dispatched
program blocks forever, so the train loop sits inside
``train_step(...)`` indefinitely and nothing ever raises.  No
in-process recovery is possible (the thread is stuck in C++), so the
contract is: detect the stall from a side thread, dump live stack
traces + the last known metrics for post-mortem, and exit the process
with a DISTINCT code (:data:`WATCHDOG_EXIT_CODE`) so the supervising
layer (a k8s restart policy, or
resilience/supervisor.py run under a process manager) can tell "step
deadline exceeded" from a crash and re-fire cleanly — the next run
``--resume``'s from the last valid checkpoint.

The heartbeat is fed by the train loop's ``StepTimer.tick()`` (one
beat per completed step), so the deadline bounds a SINGLE step, not
the whole run.  The first beat gets a separate, larger grace period:
step 1 includes XLA compilation, which legitimately takes minutes.
"""

from __future__ import annotations

import faulthandler
import os
import sys
import threading
import time
import traceback
from typing import Callable, Dict, Optional

from ..utils.logging import get_logger

# Distinct from Python's 1, SIGKILL's 137, timeout(1)'s 124: a
# supervising shell can case on it.  Documented in docs/RESILIENCE.md.
WATCHDOG_EXIT_CODE = 114


def dump_all_stacks(out=None) -> str:
    """Write every thread's Python stack to ``out`` (default stderr);
    returns the formatted dump.  Uses both the pure-Python formatter
    (readable, thread names) and faulthandler (works even when a
    thread wedges holding odd state)."""
    out = out or sys.stderr
    names = {t.ident: t.name for t in threading.enumerate()}
    parts = []
    for ident, frame in sys._current_frames().items():
        parts.append(f"--- thread {names.get(ident, '?')} ({ident}) ---\n"
                     + "".join(traceback.format_stack(frame)))
    text = "\n".join(parts)
    try:
        out.write(text + "\n")
        faulthandler.dump_traceback(file=out, all_threads=True)
        out.flush()
    except (OSError, ValueError):
        pass  # stderr may be gone during interpreter shutdown
    return text


class StepWatchdog:
    """Heartbeat-deadline monitor running in a daemon thread.

    >>> with StepWatchdog(deadline_s=300) as wd:
    ...     for batch in loader:
    ...         state, m = train_step(state, batch)
    ...         wd.beat(step)          # fed via StepTimer.tick()

    On ``deadline_s`` without a beat the watchdog dumps diagnostics and
    calls ``on_stall`` — by default :func:`os._exit` with
    :data:`WATCHDOG_EXIT_CODE` (``atexit``/orbax finalizers are wedged
    too; a clean shutdown is not on offer).  Tests pass a callable to
    observe the firing in-process.
    """

    def __init__(
        self,
        deadline_s: float,
        *,
        first_deadline_s: Optional[float] = None,
        exit_code: int = WATCHDOG_EXIT_CODE,
        on_stall: Optional[Callable[[str], None]] = None,
        dump_dir: Optional[str] = None,
        poll_s: Optional[float] = None,
    ):
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.deadline_s = float(deadline_s)
        # First beat covers jit compile + data warmup: give it the
        # larger of 3 deadlines or the explicit grace.
        self.first_deadline_s = float(first_deadline_s
                                      if first_deadline_s is not None
                                      else 3.0 * deadline_s)
        self.exit_code = int(exit_code)
        self._on_stall = on_stall
        self.dump_dir = dump_dir
        self._poll_s = float(poll_s) if poll_s else min(
            1.0, self.deadline_s / 4.0)
        self._lock = threading.Lock()
        self._last_beat = None  # None until start()
        self._beats = 0
        self.last_step: Optional[int] = None
        self.last_metrics: Dict[str, float] = {}
        self.fired = False
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ----------------------------------------------------

    def start(self) -> "StepWatchdog":
        if self._thread is not None:
            return self
        self._last_beat = time.monotonic()
        self._thread = threading.Thread(
            target=self._run, name="step-watchdog", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self) -> "StepWatchdog":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    # -- heartbeat ----------------------------------------------------

    def beat(self, step: Optional[int] = None,
             metrics: Optional[Dict[str, float]] = None) -> None:
        """One step finished.  Called from the train loop / StepTimer;
        both args are optional diagnostics context."""
        with self._lock:
            self._last_beat = time.monotonic()
            self._beats += 1
            if step is not None:
                self.last_step = int(step)
            if metrics:
                self.last_metrics = dict(metrics)

    def seconds_since_beat(self) -> Optional[float]:
        """Age of the last heartbeat (None before start()) — the
        trainer telemetry sidecar's /healthz reads this so liveness is
        the watchdog's OWN signal, not a second, subtly different
        clock."""
        with self._lock:
            if self._last_beat is None:
                return None
            return time.monotonic() - self._last_beat

    # -- monitor ------------------------------------------------------

    def _run(self) -> None:
        while not self._stop_evt.wait(self._poll_s):
            with self._lock:
                elapsed = time.monotonic() - self._last_beat
                limit = (self.deadline_s if self._beats
                         else self.first_deadline_s)
            if elapsed > limit:
                self._fire(elapsed, limit)
                return

    def _fire(self, elapsed: float, limit: float) -> None:
        self.fired = True
        log = get_logger()
        phase = "step" if self._beats else "first step (incl. compile)"
        msg = (f"WATCHDOG: {phase} exceeded deadline — {elapsed:.1f}s "
               f"since last heartbeat (limit {limit:.1f}s), last step="
               f"{self.last_step}, last metrics={self.last_metrics} — "
               "dumping stacks and exiting with code "
               f"{self.exit_code} (wedged-dispatch mode; resume from "
               "the last valid checkpoint)")
        try:
            log.error(msg)
            sys.stderr.write(msg + "\n")
        except (OSError, ValueError):
            pass
        text = dump_all_stacks()
        if self.dump_dir:
            try:
                os.makedirs(self.dump_dir, exist_ok=True)
                path = os.path.join(
                    self.dump_dir, f"watchdog_stall_{os.getpid()}.txt")
                with open(path, "w") as f:
                    f.write(msg + "\n\n" + text)
                log.error("watchdog stall dump written to %s", path)
            except OSError:
                pass
        if self._on_stall is not None:
            self._on_stall(msg)
            return
        os._exit(self.exit_code)
