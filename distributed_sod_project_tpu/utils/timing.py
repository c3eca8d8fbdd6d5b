"""Throughput accounting — the imgs/sec counter the governing metric
(BASELINE.json:2, images/sec/chip) is computed from."""

from __future__ import annotations

import time
from collections import deque


class StepTimer:
    """Sliding-window step timer; excludes the first ``warmup`` ticks so
    XLA compilation time never pollutes throughput numbers.

    **What a tick means (honesty contract).**  ``tick(steps=n)`` marks
    an observation that ``n`` more train steps COMPLETED on device, and
    the window stores per-step time = interval / n.  Under device-side
    step chunking (``train.steps_per_dispatch=k``) the loop calls
    ``tick(steps=k)`` immediately after the per-chunk metric readback —
    a ``jax.device_get`` that cannot return before the chunk's
    dependency chain executed — so the clock advances with completed
    device work, never with host dispatches, and ``imgs_per_sec`` stays
    honest under async run-ahead.  The historical k=1 path keeps its
    per-dispatch tick: there the log-cadence metric fetch bounds host
    run-ahead, so the window mean still converges to the completion
    rate (documented dispatch-rate semantics, preserved so recorded
    baselines replay identically).  The LOGGED ``imgs_per_sec`` does
    not read that window: :meth:`fetched` rates the steps between two
    host fetches of the metrics over the time between them, which is
    completed work on either path.

    ``on_tick`` (optional) is invoked once per ``tick()`` — the train
    loop feeds the step watchdog's heartbeat through it
    (resilience/watchdog.py), so "a chunk completed" and "the
    throughput clock advanced" are, by construction, the same event.
    """

    def __init__(self, window: int = 50, warmup: int = 2, on_tick=None):
        self.window = window
        self.warmup = warmup
        self.on_tick = on_tick
        self._times: deque = deque(maxlen=window)
        self._last = None
        self._count = 0
        self._fetch = None  # (time, step) of the last metric fetch

    def fetched(self, step: int, batch_size: int) -> float:
        """Images per second over the interval since the previous call:
        the loop calls this right after a host fetch of step ``step``'s
        metrics, which cannot return before that step completed, so
        both ends of the interval are completed work (the clock the
        benchmark's ``on_metrics`` ticks read).  The first call has no
        interval and falls back to the windowed mean."""
        now = time.perf_counter()
        prev, self._fetch = self._fetch, (now, step)
        if prev is None or step <= prev[1] or now <= prev[0]:
            return self.images_per_sec(batch_size)
        return (step - prev[1]) * batch_size / (now - prev[0])

    def tick(self, steps: int = 1) -> None:
        """Record that ``steps`` more train steps completed since the
        previous tick (1 = the per-step path; k = one scanned chunk)."""
        now = time.perf_counter()
        self._count += 1
        if self._last is not None and self._count > self.warmup:
            self._times.append((now - self._last) / max(int(steps), 1))
        self._last = now
        if self.on_tick is not None:
            self.on_tick()

    @property
    def mean_step_time(self) -> float:
        """Mean PER-STEP time over the window (chunk intervals are
        divided by their step count before entering the window)."""
        if not self._times:
            return float("nan")
        return sum(self._times) / len(self._times)

    def images_per_sec(self, batch_size: int) -> float:
        """Throughput from the windowed per-step mean; ``batch_size``
        is the per-STEP global batch (not the chunk total)."""
        st = self.mean_step_time
        return batch_size / st if st == st and st > 0 else float("nan")

    @property
    def ticks(self) -> int:
        """Completed-work observations so far (chunks, not steps)."""
        return self._count

    def snapshot(self) -> dict:
        """Telemetry-sidecar view: windowed per-step time and tick
        count (NaN-free — 0.0 before the window fills, so Prometheus
        samples stay parseable)."""
        st = self.mean_step_time
        return {"ticks": self._count,
                "mean_step_ms": round(st * 1000.0, 3) if st == st else 0.0}
