"""Observability: metric writers, profiler traces, preemption handling.

SURVEY.md §5 rows "tracing/profiling", "metrics/logging" and "failure
detection": the reference had a rank-0 file/console logger + TensorBoard
and nothing for preemption beyond --resume restarts.  TPU-native forms:

- ``MetricWriter``: clu.metric_writers (TensorBoard event files) on the
  primary process, no-op elsewhere — scalars stream from the train loop.
- ``profile_window``: ``jax.profiler`` trace of a step range; the dump
  opens in TensorBoard/Perfetto and shows per-HLO timing on device.
- ``PreemptionGuard``: SIGTERM/SIGINT → finish the current step, write
  a final checkpoint, exit 0.  TPU pods are preemptible by design; a
  final-checkpoint-on-SIGTERM is the idiomatic elasticity story (the
  next run --resume's from it).
"""

from __future__ import annotations

import collections
import contextlib
import signal
import threading
from typing import Dict, Optional

from .logging import get_logger, is_primary_process
from .tracing import span


class PipelineStats:
    """Thread-safe counters/gauges for the host data plane.

    Every blocking point in the input pipeline (data/pipeline.py)
    reports here, so "the step is input-bound" is a measured number
    instead of a guess.  Counters (cumulative):

    - ``data_starved_ms``   — the CONSUMER (the train loop's thread)
      blocked on an empty prefetch queue.  A host wait, not device
      idle: the device may still hold queued steps.  How much of it
      the device felt is read where both share a clock — the
      ``dsod.data.starved`` span of a profiler trace against the
      device ops (``data_starved_exposed_ms_per_step``, PERF.md).
    - ``data_h2d_ms``       — time inside device_put / global array
      assembly on the H2D thread.
    - ``data_prefetch_full_ms`` — H2D thread blocked on a full queue
      (healthy: the step, not the input, is the bottleneck).
    - ``data_build_ms``     — build workers decoding + augmenting one
      batch each (summed over workers, so it can exceed wall time).
    - ``data_build_wait_ms`` — loader blocked waiting for a batch
      build worker (decode+augment stage is the bottleneck).
    - ``data_ring_wait_ms`` — builders blocked waiting for a free
      batch buffer (consumer holding the ring; raise ring_buffers).
    - ``data_batches``      — batches produced.

    Each ``data_*_ms`` counter is fed by :meth:`timed` and by nothing
    else: one timed region adds the counter AND emits the span
    ``dsod.data.<key without data_/_ms>`` on the profiler's clock, so
    count and span are the same measurement.

    Queue depth is tracked as a running (sum, count) pair and reported
    as ``data_queue_depth_avg`` / ``data_queue_size``.

    ``delta()`` returns metrics accumulated since the previous
    ``delta()`` call — the train loop calls it once per logging
    interval and hands the result to :class:`MetricWriter`, so the
    TensorBoard curves are per-interval, not monotone totals.

    ``keep_spans``: also keep each timed region as ``(name, t0, t1,
    attrs)`` — the span's own two clock reads (``utils/tracing.py``:
    the clock ``fit()`` gives the :class:`Tracer` ring) — until
    :meth:`drain_spans`: the train loop moves them into the sampled
    chunk's trace.  Bounded; off by default.  (The host-clock sink
    keeps seconds per NAME, not intervals: it cannot stand in.)
    """

    def __init__(self, keep_spans: bool = False):
        self._lock = threading.Lock()
        self._counts: Dict[str, float] = {}
        self._last: Dict[str, float] = {}
        self._depth_sum = 0.0
        self._depth_n = 0
        self._depth_size = 0
        self._spans = collections.deque(maxlen=1024) if keep_spans else None

    @contextlib.contextmanager
    def timed(self, key: str, **attrs):
        """THE timing seam of the data plane: the span
        ``dsod.data.<what>`` with ``attrs`` times the body once, and
        its seconds are added to counter ``key`` (``data_<what>_ms``)."""
        name = "dsod.data." + key[len("data_"):-len("_ms")]
        with span(name, **attrs) as region:
            yield
        self.add(key, (region.t1 - region.t0) * 1000.0)
        if self._spans is not None:
            self._spans.append((name, region.t0, region.t1, attrs))

    def drain_spans(self) -> list:
        """The timed regions kept since the last drain (``keep_spans``)."""
        out = []
        while self._spans:
            out.append(self._spans.popleft())
        return out

    def add(self, key: str, value: float) -> None:
        with self._lock:
            self._counts[key] = self._counts.get(key, 0.0) + float(value)

    def observe_depth(self, depth: int, size: int) -> None:
        with self._lock:
            self._depth_sum += depth
            self._depth_n += 1
            self._depth_size = size

    def snapshot(self) -> Dict[str, float]:
        """Cumulative totals (plus average queue depth over the run)."""
        with self._lock:
            out = dict(self._counts)
            if self._depth_n:
                out["data_queue_depth_avg"] = self._depth_sum / self._depth_n
                out["data_queue_size"] = float(self._depth_size)
            return out

    def delta(self) -> Dict[str, float]:
        """Counters accumulated since the last ``delta()`` call."""
        with self._lock:
            out = {}
            for k, v in self._counts.items():
                out[k] = v - self._last.get(k, 0.0)
            self._last = dict(self._counts)
            if self._depth_n:
                out["data_queue_depth_avg"] = self._depth_sum / self._depth_n
                self._depth_sum = 0.0
                self._depth_n = 0
            return out

    # The documented counter set (every blocking point above plus the
    # chunk-assembly stage) rendered UNCONDITIONALLY, so the /metrics
    # family inventory is stable across runs and platforms — a family
    # that happens to be zero this run must not read as "vanished" to
    # tools/metrics_lint.py.
    CANONICAL = ("data_starved_ms", "data_h2d_ms", "data_prefetch_full_ms",
                 "data_build_ms", "data_build_wait_ms",
                 "data_ring_wait_ms", "data_batches",
                 "data_chunk_assemble_ms", "data_chunks",
                 "data_partial_chunks_dropped")

    def prom_families(self, labels: str = "", prefix: str = "dsod_train_"):
        """The host-data-plane telemetry as Prometheus families (the
        trainer sidecar's half of the rendering the serve stack already
        does through ``ServeStats.prom_families``)."""
        with self._lock:
            counts = dict(self._counts)
            depth = (self._depth_sum / self._depth_n
                     if self._depth_n else 0.0)
            size = self._depth_size
        sb = f"{{{labels}}}" if labels else ""
        fams = []
        for key in self.CANONICAL:
            name = f"{prefix}{key}_total"
            fams.append((name, "counter",
                         [f"{name}{sb} {counts.pop(key, 0.0):g}"]))
        for key in sorted(counts):  # anything non-canonical still shows
            name = f"{prefix}{key}_total"
            fams.append((name, "counter",
                         [f"{name}{sb} {counts[key]:g}"]))
        for name, v in ((f"{prefix}data_queue_depth_avg", depth),
                        (f"{prefix}data_queue_size", float(size))):
            fams.append((name, "gauge", [f"{name}{sb} {v:g}"]))
        return fams


def _merge_labels(*parts: str) -> str:
    """Merge pre-rendered label fragments (``'model="m"'``,
    ``'arm="bf16"'``) into one label set, skipping empties."""
    return ",".join(p for p in parts if p)


def render_prom_families(families) -> str:
    """Family list → Prometheus text: ``# TYPE`` once per family, then
    every sample line (the text-format rule promtool/OpenMetrics
    parsers enforce — a family's samples must be one contiguous group
    under a single TYPE line)."""
    lines = []
    for name, typ, samples in families:
        lines.append(f"# TYPE {name} {typ}")
        lines.extend(samples)
    return "\n".join(lines) + "\n"


def merge_prom_families(groups):
    """Concatenate several family lists (e.g. one per fleet replica,
    each already carrying its ``model=`` label) into one list with each
    family appearing ONCE — the aggregation a fleet /metrics endpoint
    must do so that per-replica series share metric families instead of
    re-declaring them.  Raises on a type conflict for the same family
    name."""
    order, merged = [], {}
    for fams in groups:
        for name, typ, samples in fams:
            if name not in merged:
                merged[name] = (typ, [])
                order.append(name)
            elif merged[name][0] != typ:
                raise ValueError(
                    f"metric family {name!r} declared as both "
                    f"{merged[name][0]!r} and {typ!r}")
            merged[name][1].extend(samples)
    return [(n,) + tuple(merged[n]) for n in order]


def _inject_labels(sample: str, labels: str) -> str:
    """Merge ``labels`` into one exposition sample line."""
    head, _, _ = sample.partition(" ")
    if "{" in head:
        return sample.replace("{", "{" + labels + ",", 1)
    name, _, rest = sample.partition(" ")
    return f"{name}{{{labels}}} {rest}"


def parse_prom_text(text: str, labels: str = ""):
    """Prometheus exposition text → family list
    ``[(name, type, [sample, ...]), ...]`` with ``labels`` injected
    into every sample — how a fleet router relabels a REMOTE replica's
    scraped /metrics under its ``model=`` key before merging.  Samples
    appearing before any ``# TYPE`` line get an ``untyped`` family per
    metric name."""
    fams = []
    cur = None
    untyped = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) < 4:
                continue
            cur = (parts[2], parts[3], [])
            fams.append(cur)
            continue
        if line.startswith("#"):
            continue
        if labels:
            line = _inject_labels(line, labels)
        if cur is not None:
            cur[2].append(line)
        else:
            name = line.partition("{")[0].partition(" ")[0]
            fam = untyped.get(name)
            if fam is None:
                fam = untyped[name] = (name, "untyped", [])
                fams.append(fam)
            fam[2].append(line)
    return fams


class LatencyHistogram:
    """Fixed-bucket latency histogram (milliseconds) with Prometheus
    rendering and bucket-interpolated percentiles.

    Prometheus-shaped on purpose: cumulative ``le`` buckets plus
    ``_sum``/``_count``, so ``render_prometheus`` is a straight dump and
    any scrape-side histogram_quantile() agrees with the in-process
    ``percentile()`` (both interpolate linearly inside a bucket).
    """

    BOUNDS_MS = (1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
                 1000.0, 2000.0, 5000.0, 10000.0)

    def __init__(self, bounds=BOUNDS_MS):
        self._bounds = tuple(float(b) for b in bounds)
        self._counts = [0] * (len(self._bounds) + 1)  # +1: overflow
        self._sum = 0.0
        self._n = 0
        self._lock = threading.Lock()

    def observe(self, ms: float) -> None:
        ms = float(ms)
        with self._lock:
            self._sum += ms
            self._n += 1
            for i, b in enumerate(self._bounds):
                if ms <= b:
                    self._counts[i] += 1
                    return
            self._counts[-1] += 1

    @property
    def count(self) -> int:
        with self._lock:
            return self._n

    @property
    def sum_ms(self) -> float:
        """Total observed ms — the ``_sum`` sample, exposed for
        stage-share attribution (utils/capacity.py divides the device
        histogram's sum by the e2e histogram's sum)."""
        with self._lock:
            return self._sum

    def percentile(self, p: float) -> float:
        """p in [0, 1] → estimated latency ms (linear interpolation
        inside the bucket; the overflow bucket reports its lower
        bound — an honest floor, not an invented tail)."""
        with self._lock:
            if not self._n:
                return 0.0
            target = p * self._n
            cum = 0
            lo = 0.0
            for i, b in enumerate(self._bounds):
                c = self._counts[i]
                if cum + c >= target and c:
                    frac = (target - cum) / c
                    return lo + (b - lo) * min(max(frac, 0.0), 1.0)
                cum += c
                lo = b
            return self._bounds[-1]

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            n, s = self._n, self._sum
        return {
            "count": float(n),
            "sum_ms": round(s, 3),
            "p50_ms": round(self.percentile(0.50), 3),
            "p95_ms": round(self.percentile(0.95), 3),
            "p99_ms": round(self.percentile(0.99), 3),
        }

    def prom_lines(self, name: str, labels: str = "",
                   include_type: bool = True) -> list:
        """Prometheus exposition lines; ``labels`` is a pre-rendered
        label set (e.g. ``arm="bf16"``) merged into every sample so
        per-arm histograms share one metric family (pass
        ``include_type=False`` for every family member after the first
        — TYPE may appear only once per family)."""
        with self._lock:
            counts = list(self._counts)
            s, n = self._sum, self._n
        pre = f"{labels}," if labels else ""
        suf = f"{{{labels}}}" if labels else ""
        lines = [f"# TYPE {name} histogram"] if include_type else []
        cum = 0
        for b, c in zip(self._bounds, counts):
            cum += c
            lines.append(f'{name}_bucket{{{pre}le="{b:g}"}} {cum}')
        lines.append(f'{name}_bucket{{{pre}le="+Inf"}} {n}')
        lines.append(f"{name}_sum{suf} {s:g}")
        lines.append(f"{name}_count{suf} {n}")
        return lines


class TailEstimator:
    """Windowed latency-tail estimate over the last ``window``
    observations (exact order statistic, not a histogram bound).

    The fleet router keeps one per model to pick the tail-latency
    HEDGE trigger (serve/failover.py ``pick_hedge_delay``): hedging at
    an EWMA would hedge half of all traffic, hedging at a fixed guess
    would miss regime changes — the observed p95 over a sliding window
    tracks the actual tail cheaply (the window is a few hundred floats
    and percentile() sorts only on demand, off the hot path)."""

    def __init__(self, window: int = 256):
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self._window = int(window)
        self._buf = []
        self._i = 0
        self._lock = threading.Lock()

    def observe(self, ms: float) -> None:
        with self._lock:
            if len(self._buf) < self._window:
                self._buf.append(float(ms))
            else:  # ring overwrite: O(1), no deque rotation
                self._buf[self._i] = float(ms)
                self._i = (self._i + 1) % self._window

    @property
    def count(self) -> int:
        with self._lock:
            return len(self._buf)

    def percentile(self, p: float) -> Optional[float]:
        """p in [0, 1] → the windowed order statistic, or None before
        the first observation (callers must not invent a tail)."""
        with self._lock:
            if not self._buf:
                return None
            s = sorted(self._buf)
        i = min(int(p * len(s)), len(s) - 1)
        return s[i]


class ArmStats:
    """Per-precision-arm serving telemetry (one instance per arm,
    created lazily by :meth:`ServeStats.arm`): the latency tail and the
    padding tax are only actionable split per compiled-program family,
    because the arms are different programs with different device
    costs."""

    def __init__(self):
        self._lock = threading.Lock()
        self.device_ms = LatencyHistogram()
        self.e2e_ms = LatencyHistogram()
        self._served = 0
        self._occ_sum = 0
        self._occ_slots = 0

    def inc_served(self, n: int = 1) -> None:
        with self._lock:
            self._served += n

    def observe_batch(self, occupancy: int, bucket: int) -> None:
        with self._lock:
            self._occ_sum += int(occupancy)
            self._occ_slots += int(bucket)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = {"served": float(self._served)}
            if self._occ_slots:
                out["batch_occupancy"] = round(
                    self._occ_sum / self._occ_slots, 4)
        for name, h in (("device", self.device_ms), ("e2e", self.e2e_ms)):
            for k, v in h.snapshot().items():
                out[f"{name}_{k}"] = v
        return out


class ServeStats:
    """Thread-safe serving telemetry (serve/ subsystem; docs/SERVING.md).

    Request accounting invariant — checked by tests/test_serving.py and
    worth checking on any live deployment's /metrics:

        served + shed + expired + errors == submitted   (eventually)

    every submitted request terminates in exactly one of the four.
    Latency histograms split the end-to-end path at its two seams:
    ``queue_ms`` (arrival → dispatch: coalescing wait + backlog),
    ``device_ms`` (dispatch → device fetch complete), ``e2e_ms``
    (arrival → response ready).  Batch occupancy records how full the
    static batch buckets run (occupancy_sum / occupancy_batches — the
    padding tax is 1 minus that ratio over the bucket sizes).  Each
    precision arm additionally owns an :class:`ArmStats` (device/e2e
    histograms, served count, occupancy) exposed under ``arm=`` labels
    in /metrics, so loadgen curves and dashboards split per arm.
    ``degraded`` is the ladder level (0 = full quality); the
    entered/exited counters tick on the 0 ↔ >0 boundary.
    """

    COUNTERS = ("submitted", "served", "shed", "expired", "errors",
                "batches", "reloads", "degraded_entered", "degraded_exited",
                "request_compiles")

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = {k: 0 for k in self.COUNTERS}
        self.queue_ms = LatencyHistogram()
        self.device_ms = LatencyHistogram()
        self.e2e_ms = LatencyHistogram()
        self._arms: Dict[str, ArmStats] = {}
        self._occ_sum = 0
        self._occ_slots = 0
        self._queue_depth = 0
        self._inflight = 0
        self._degraded_level = 0
        self._healthy = True
        self._health_reason = ""

    def inc(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._counts[key] += n

    def arm(self, name: str) -> ArmStats:
        """The named arm's stats, created on first touch (lazy so the
        metric surface only shows arms that actually served)."""
        with self._lock:
            st = self._arms.get(name)
            if st is None:
                st = self._arms[name] = ArmStats()
            return st

    def observe_batch(self, occupancy: int, bucket: int,
                      arm: Optional[str] = None) -> None:
        with self._lock:
            self._counts["batches"] += 1
            self._occ_sum += int(occupancy)
            self._occ_slots += int(bucket)
        if arm is not None:
            self.arm(arm).observe_batch(occupancy, bucket)

    def set_queue_depth(self, depth: int) -> None:
        with self._lock:
            self._queue_depth = int(depth)

    def set_inflight(self, n: int) -> None:
        with self._lock:
            self._inflight = int(n)

    def set_degraded(self, level) -> None:
        """Feed the current ladder level (bool accepted for the binary
        callers: True == 1)."""
        level = int(level)
        with self._lock:
            if level > 0 and self._degraded_level == 0:
                self._counts["degraded_entered"] += 1
            elif level == 0 and self._degraded_level > 0:
                self._counts["degraded_exited"] += 1
            self._degraded_level = level

    def set_health(self, healthy: bool, reason: str = "") -> None:
        with self._lock:
            self._healthy = bool(healthy)
            self._health_reason = reason

    @property
    def healthy(self) -> bool:
        with self._lock:
            return self._healthy

    @property
    def health_reason(self) -> str:
        with self._lock:
            return self._health_reason

    @property
    def degraded(self) -> bool:
        with self._lock:
            return self._degraded_level > 0

    @property
    def degraded_level(self) -> int:
        with self._lock:
            return self._degraded_level

    def counter(self, key: str) -> int:
        with self._lock:
            return self._counts[key]

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = {k: float(v) for k, v in self._counts.items()}
            out["queue_depth"] = float(self._queue_depth)
            out["inflight"] = float(self._inflight)
            out["degraded"] = float(self._degraded_level > 0)
            out["degraded_level"] = float(self._degraded_level)
            out["healthy"] = float(self._healthy)
            if self._occ_slots:
                out["batch_occupancy"] = round(
                    self._occ_sum / self._occ_slots, 4)
            arms = dict(self._arms)
        for name, h in (("queue", self.queue_ms),
                        ("device", self.device_ms),
                        ("e2e", self.e2e_ms)):
            for k, v in h.snapshot().items():
                out[f"{name}_{k}"] = v
        if arms:
            out["arms"] = {a: st.snapshot() for a, st in sorted(arms.items())}
        return out

    def prom_families(self, labels: str = ""):
        """Every metric family as ``(name, type, [sample, ...])`` with
        ``labels`` (e.g. ``'model="minet"'``) merged into every sample
        — the unit a fleet aggregator merges across replicas so each
        family keeps ONE ``# TYPE`` line no matter how many labeled
        series export it (``merge_prom_families``).  Per-arm families
        carry ``labels`` + their ``arm=`` label."""
        with self._lock:
            counts = dict(self._counts)
            gauges = {
                "dsod_serve_queue_depth": self._queue_depth,
                "dsod_serve_inflight": self._inflight,
                "dsod_serve_degraded": int(self._degraded_level > 0),
                "dsod_serve_degraded_level": self._degraded_level,
                "dsod_serve_healthy": int(self._healthy),
            }
            occ = (self._occ_sum, self._occ_slots)
            arms = sorted(self._arms.items())
        sb = f"{{{labels}}}" if labels else ""
        fams = []
        for k, v in sorted(counts.items()):
            name = f"dsod_serve_{k}_total"
            fams.append((name, "counter", [f"{name}{sb} {v}"]))
        for name, v in sorted(gauges.items()):
            fams.append((name, "gauge", [f"{name}{sb} {v}"]))
        fams.append(("dsod_serve_batch_occupancy_sum", "counter",
                     [f"dsod_serve_batch_occupancy_sum{sb} {occ[0]}"]))
        fams.append(("dsod_serve_batch_slots_sum", "counter",
                     [f"dsod_serve_batch_slots_sum{sb} {occ[1]}"]))
        for name, h in (("dsod_serve_queue_latency_ms", self.queue_ms),
                        ("dsod_serve_device_latency_ms", self.device_ms),
                        ("dsod_serve_e2e_latency_ms", self.e2e_ms)):
            fams.append((name, "histogram",
                         h.prom_lines(name, labels=labels,
                                      include_type=False)))
        # Per-arm families: every arm's sample in ONE family group.
        counters = []
        for a, st in arms:
            with st._lock:
                counters.append((a, st._served, st._occ_sum, st._occ_slots))
        def arm_labels(a):
            return _merge_labels(labels, 'arm="' + a + '"')

        if counters:
            fams.append(("dsod_serve_arm_served_total", "counter", [
                'dsod_serve_arm_served_total{%s} %s'
                % (arm_labels(a), served)
                for a, served, _o, _s in counters]))
            fams.append(("dsod_serve_arm_batch_occupancy_sum", "counter", [
                'dsod_serve_arm_batch_occupancy_sum{%s} %s'
                % (arm_labels(a), occ_sum)
                for a, _served, occ_sum, _s in counters]))
            fams.append(("dsod_serve_arm_batch_slots_sum", "counter", [
                'dsod_serve_arm_batch_slots_sum{%s} %s'
                % (arm_labels(a), occ_slots)
                for a, _served, _o, occ_slots in counters]))
        for fam_name, attr in (("dsod_serve_arm_device_latency_ms",
                                "device_ms"),
                               ("dsod_serve_arm_e2e_latency_ms", "e2e_ms")):
            samples = []
            for a, st in arms:
                samples += getattr(st, attr).prom_lines(
                    fam_name, labels=arm_labels(a), include_type=False)
            if samples:
                fams.append((fam_name, "histogram", samples))
        return fams

    def render_prometheus(self, labels: str = "") -> str:
        """The /metrics payload (Prometheus text exposition format);
        ``labels`` rides every sample (fleet replicas pass their
        ``model=`` key)."""
        return render_prom_families(self.prom_families(labels))


class TelemetryRegistry:
    """Named providers of Prometheus families behind ONE render path.

    Both telemetry surfaces — the serve /metrics endpoints and the
    trainer sidecar — register ``provider(labels) -> families``
    callables here and render through the same
    ``merge_prom_families`` + ``render_prom_families`` machinery, so
    the TYPE-once-per-family discipline (and any future exposition
    change) cannot drift between the two stacks.  With a single
    provider the output is byte-identical to rendering that provider
    directly (merge of one group is the identity).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._providers = []  # (name, provider)

    def register(self, name: str, provider) -> "TelemetryRegistry":
        """``provider(labels: str) -> [(family, type, samples), ...]``.
        Registration order is render order."""
        with self._lock:
            if any(n == name for n, _p in self._providers):
                raise ValueError(f"telemetry provider {name!r} already "
                                 "registered")
            self._providers.append((name, provider))
        return self

    def prom_families(self, labels: str = ""):
        with self._lock:
            providers = list(self._providers)
        return merge_prom_families([p(labels) for _n, p in providers])

    def render(self, labels: str = "") -> str:
        """The /metrics payload (Prometheus text exposition format)."""
        return render_prom_families(self.prom_families(labels))


class MetricWriter:
    """Rank-0-gated scalar writer over clu.metric_writers.

    ``backend`` names what is actually writing (``clu`` | ``noop``):
    when clu is not importable the writer degrades to a LOGGED no-op
    (once per process, not per construction) instead of a silent one —
    a run that thinks it is writing TensorBoard curves but isn't is a
    debugging trap — and the trainer telemetry sidecar surfaces the
    active backend in /metrics
    (``dsod_train_metric_writer_info{backend=...}``).
    """

    _warned_missing_clu = False  # process-wide: log the fallback ONCE

    def __init__(self, logdir: Optional[str]):
        self._writer = None
        self.backend = "noop"
        if logdir and is_primary_process():
            try:
                from clu import metric_writers
            except ImportError:
                if not MetricWriter._warned_missing_clu:
                    MetricWriter._warned_missing_clu = True
                    get_logger().warning(
                        "clu is not installed — TensorBoard metric "
                        "writing is DISABLED (scalars still stream to "
                        "the log and the telemetry sidecar); pip "
                        "install clu to restore event files")
                return
            self._writer = metric_writers.create_default_writer(
                logdir, asynchronous=True)
            self.backend = "clu"

    def scalars(self, step: int, values: Dict[str, float]) -> None:
        if self._writer is not None:
            self._writer.write_scalars(
                int(step),
                {k: float(v) for k, v in values.items()
                 if isinstance(v, (int, float))})

    def flush(self) -> None:
        if self._writer is not None:
            self._writer.flush()

    def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None


@contextlib.contextmanager
def profile_window(logdir: Optional[str]):
    """Trace everything inside the with-block to ``logdir`` (no-op when
    logdir is falsy)."""
    if not logdir:
        yield
        return
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        get_logger().info("profiler trace written to %s", logdir)


class PreemptionGuard:
    """Install SIGTERM/SIGINT handlers that request a graceful stop.

    The train loop polls ``should_stop`` once per step; on True it saves
    a final checkpoint and returns instead of dying mid-epoch.
    """

    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT)):
        self._stop = False
        self._prev = {}
        self._signals = signals

    def __enter__(self):
        for s in self._signals:
            try:
                self._prev[s] = signal.signal(s, self._handler)
            except ValueError:  # non-main thread (tests)
                pass
        return self

    def __exit__(self, *exc):
        for s, prev in self._prev.items():
            signal.signal(s, prev)
        return False

    def _handler(self, signum, frame):
        get_logger().warning(
            "signal %s: finishing step, checkpointing, exiting", signum)
        self._stop = True

    @property
    def should_stop(self) -> bool:
        """Host-local flag; on multi-host pods use :meth:`sync` so every
        worker leaves the collective train loop on the same step."""
        return self._stop

    def sync(self) -> bool:
        """Cross-host agreement: True iff ANY process saw a signal.

        Preemption typically SIGTERMs a single worker; if only that
        worker broke out of the loop, the rest would still be inside the
        train step's collectives and the final (collective) checkpoint
        save would deadlock.  Cheap (one tiny allgather) relative to a
        train step; skipped entirely in the single-process case.
        """
        import jax

        if jax.process_count() == 1:
            return self._stop
        import numpy as np
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([self._stop], np.int32))
        return bool(np.asarray(flags).any())
