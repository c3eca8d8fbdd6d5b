"""End-to-end tracing: the span layer under docs/OBSERVABILITY.md.

Dependency-free on purpose (stdlib only, no jax/numpy): the load
generator imports this next to a TPU-bound server, and the trainer
sidecar renders it while a fit() is mid-dispatch.  One schema serves
BOTH stacks — a serving request's queue/coalesce/device/fetch/
resize-back stages and a training chunk's data-wait/dispatch/flush/
ckpt/eval stages are the same shape:

    span = {trace, span, parent, name, t0, dur_ms, attrs}

- **Trace ids propagate, span ids don't.**  A trace id is minted once
  at the outermost door (the fleet router's ``X-Request-ID``, a chunk
  boundary in the train loop) and rides headers across processes;
  every attempt, retry, and hedge of one request shares it.  Span ids
  are local and only exist to parent children.
- **Sampling is deterministic in the trace id** (:func:`trace_sampled`)
  so a router and its remote replicas agree on which requests to trace
  without coordination, and a retried request is traced either
  everywhere or nowhere.
- **Bounded by construction.**  Completed traces live in a ring of
  ``capacity`` entries; the worst-``worst_n`` traces per exemplar key
  (e.g. ``(model, res_bucket)``) are pinned so a latency outlier
  survives the ring even under full-rate traffic.  An abandoned trace
  (root span never ended) is evicted like any other entry.
- **Export is JSON/JSONL.**  ``snapshot()`` backs the ``/debug/traces``
  endpoints; ``to_jsonl()`` writes one trace per line for offline
  timeline tooling.

The ``X-Timing`` response header (:func:`format_timing` /
:func:`parse_timing`) is the zero-overhead sibling: a per-request
stage summary computed from numbers the engine already tracks, echoed
on EVERY 200 regardless of sampling, so a client (tools/loadgen.py
``--slowest``) can always break its tail down by stage and quote the
trace id when the request was sampled.
"""

from __future__ import annotations

import json
import os
import threading
import time
import zlib
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

__all__ = [
    "Span", "Tracer", "format_timing", "mint_trace_id", "now",
    "parse_timing", "record_setup", "reset_setup", "setup_spans", "span",
    "span_totals", "trace_sampled",
]

_SAMPLE_MOD = 1 << 24
# Per-trace span bound: the ring caps the number of TRACES, this caps
# each trace's span list — a client free to reuse one sampled
# X-Request-ID forever must not be free to grow one ring entry forever.
MAX_SPANS_PER_TRACE = 256


def mint_trace_id() -> str:
    """A fresh 16-hex-char trace id (also the ``X-Request-ID`` value)."""
    return os.urandom(8).hex()


def trace_sampled(trace_id: str, sample: float) -> bool:
    """Deterministic per-trace sampling verdict.

    Hash-based, not random: the same (trace_id, rate) pair answers the
    same everywhere, so a router at 1% and its replicas at 1% trace the
    SAME 1% of requests end-to-end, and all attempts of one request
    (retries, hedges) are all-or-nothing.
    """
    if sample >= 1.0:
        return True
    if sample <= 0.0:
        return False
    h = zlib.crc32(trace_id.encode("utf-8", "replace")) & (_SAMPLE_MOD - 1)
    return h < int(sample * _SAMPLE_MOD)


class Span:
    """A live span handle.  ``end()`` records it into the tracer; a
    span that is never ended simply never appears (its trace can still
    complete — gaps are the caller's bug, visible in the export)."""

    __slots__ = ("_tracer", "trace_id", "span_id", "parent_id", "name",
                 "t0", "_root", "attrs", "_done")

    def __init__(self, tracer: "Tracer", trace_id: str, span_id: str,
                 parent_id: Optional[str], name: str, t0: float,
                 root: bool, attrs: Optional[Dict]):
        self._tracer = tracer
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.t0 = t0
        self._root = root
        self.attrs = dict(attrs) if attrs else {}
        self._done = False

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def end(self, t1: Optional[float] = None, key=None, **attrs) -> None:
        """Record the span.  ``key`` (root spans only) names the
        worst-N exemplar bucket this trace competes in, e.g.
        ``(model, res_bucket)``.  Idempotent: a double end is a no-op
        (failure paths may race the happy path's end)."""
        if self._done:
            return
        self._done = True
        if attrs:
            self.attrs.update(attrs)
        self._tracer._record(self.trace_id, self.span_id, self.parent_id,
                             self.name, self.t0,
                             t1 if t1 is not None else self._tracer._clock(),
                             self.attrs, root=self._root, key=key)


class Tracer:
    """Thread-safe span store: sampling gate, bounded ring of completed
    traces, pinned worst-N exemplars per key.

    ``begin()`` returns None when the trace is not sampled — callers
    guard every further touch on that None, so an unsampled request
    costs exactly one crc32 and one compare.
    """

    def __init__(self, sample: float = 0.0, capacity: int = 256,
                 worst_n: int = 4, clock=time.monotonic):
        if not 0.0 <= float(sample) <= 1.0:
            raise ValueError(f"sample must be in [0, 1], got {sample}")
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if worst_n < 0:
            raise ValueError(f"worst_n must be >= 0, got {worst_n}")
        self.sample = float(sample)
        self.capacity = int(capacity)
        self.worst_n = int(worst_n)
        self._clock = clock
        # monotonic → wall anchor, taken once: exported t0s are epoch
        # seconds so cross-process timelines line up approximately.
        self._wall0 = time.time() - clock()
        self._lock = threading.Lock()
        # trace_id → {"spans": [...], "done", "dur_ms", "key", "pinned"}
        self._traces: "OrderedDict[str, Dict]" = OrderedDict()
        # exemplar key → [(dur_ms, trace_id)] sorted ascending, len<=N
        self._worst: Dict[str, List[Tuple[float, str]]] = {}
        self._completed = 0
        self._dropped = 0
        self._span_drops = 0

    @property
    def enabled(self) -> bool:
        return self.sample > 0.0

    def sampled(self, trace_id: str) -> bool:
        return trace_sampled(trace_id, self.sample)

    # -- recording -----------------------------------------------------

    def begin(self, name: str, trace_id: Optional[str], *,
              parent_id: Optional[str] = None, t0: Optional[float] = None,
              root: bool = False, attrs: Optional[Dict] = None
              ) -> Optional[Span]:
        """Open a span in ``trace_id``, or None when the trace is not
        sampled (or ``trace_id`` is None).  ``root=True`` marks the
        span whose ``end()`` completes the trace IN THIS PROCESS — the
        engine's request span is a root even when it carries a
        cross-process parent (the router's attempt span id)."""
        if trace_id is None or not self.sampled(trace_id):
            return None
        return Span(self, trace_id, os.urandom(4).hex(), parent_id, name,
                    t0 if t0 is not None else self._clock(), root, attrs)

    def record(self, trace_id: Optional[str], name: str, t0: float,
               t1: float, *, parent_id: Optional[str] = None,
               attrs: Optional[Dict] = None) -> Optional[str]:
        """Record a retroactive (already-finished) span from two
        timestamps; returns its span id.  Sampling-gated like
        :meth:`begin`."""
        if trace_id is None or not self.sampled(trace_id):
            return None
        sid = os.urandom(4).hex()
        self._record(trace_id, sid, parent_id, name, t0, t1,
                     dict(attrs) if attrs else {}, root=False, key=None)
        return sid

    def _record(self, trace_id, span_id, parent_id, name, t0, t1, attrs,
                *, root: bool, key) -> None:
        span = {
            "span": span_id,
            "parent": parent_id,
            "name": name,
            "t0": t0,
            "dur_ms": round(max(t1 - t0, 0.0) * 1000.0, 3),
        }
        if attrs:
            span["attrs"] = attrs
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is None:
                tr = self._traces[trace_id] = {
                    "spans": [], "done": False, "dur_ms": None,
                    "key": None, "pinned": False}
            if (len(tr["spans"]) >= MAX_SPANS_PER_TRACE
                    and (not root or tr["done"])):
                # Past the cap only a COMPLETING root still lands (so
                # the trace closes); everything else — including repeat
                # roots on a done trace — is dropped, not stored.
                self._span_drops += 1
                return
            tr["spans"].append(span)
            if root and not tr["done"]:
                tr["done"] = True
                tr["dur_ms"] = span["dur_ms"]
                self._completed += 1
                if key is not None and self.worst_n > 0:
                    tr["key"] = self._key_str(key)
                    self._consider_worst(tr["key"], span["dur_ms"],
                                         trace_id)
            self._evict_locked()

    @staticmethod
    def _key_str(key) -> str:
        if isinstance(key, (tuple, list)):
            return ",".join(str(k) for k in key)
        return str(key)

    def _consider_worst(self, key: str, dur_ms: float, trace_id: str
                        ) -> None:
        lst = self._worst.setdefault(key, [])
        lst.append((dur_ms, trace_id))
        lst.sort(key=lambda e: e[0])
        tr = self._traces.get(trace_id)
        if tr is not None:
            tr["pinned"] = True
        while len(lst) > self.worst_n:
            _d, evicted = lst.pop(0)
            ev = self._traces.get(evicted)
            if ev is not None and not any(
                    tid == evicted for ws in self._worst.values()
                    for _dd, tid in ws):
                ev["pinned"] = False

    def _evict_locked(self) -> None:
        while len(self._traces) > self.capacity:
            victim = None
            for tid, tr in self._traces.items():
                if not tr["pinned"]:
                    victim = tid
                    break
            if victim is None:  # everything pinned: drop the oldest
                victim = next(iter(self._traces))
                for ws in self._worst.values():
                    ws[:] = [e for e in ws if e[1] != victim]
            self._traces.pop(victim, None)
            self._dropped += 1

    # -- export --------------------------------------------------------

    def _trace_dict(self, tid: str, tr: Dict) -> Dict:
        spans = sorted(tr["spans"], key=lambda s: s["t0"])
        tmin = spans[0]["t0"] if spans else 0.0
        out_spans = []
        for s in spans:
            d = {k: v for k, v in s.items() if k != "t0"}
            d["rel_ms"] = round((s["t0"] - tmin) * 1000.0, 3)
            d["t0_unix"] = round(s["t0"] + self._wall0, 6)
            out_spans.append(d)
        return {"trace_id": tid, "done": tr["done"], "dur_ms": tr["dur_ms"],
                "key": tr["key"], "spans": out_spans}

    def snapshot(self, n: int = 50) -> Dict:
        """The ``/debug/traces`` payload: the newest ``n`` completed
        traces plus the pinned worst-N exemplars per key."""
        with self._lock:
            done = [(tid, tr) for tid, tr in self._traces.items()
                    if tr["done"]]
            # done[-n:] at n<=0 would be the WHOLE list — a client
            # n=0 must mean none, not everything.
            recent = [self._trace_dict(tid, tr)
                      for tid, tr in (done[-n:] if n > 0 else [])]
            worst = {key: [self._trace_dict(tid, self._traces[tid])
                           for _d, tid in reversed(lst)
                           if tid in self._traces]
                     for key, lst in sorted(self._worst.items())}
            stats = {"sample": self.sample, "capacity": self.capacity,
                     "completed_total": self._completed,
                     "dropped_total": self._dropped,
                     "span_drops_total": self._span_drops,
                     "held": len(self._traces)}
        return {**stats, "traces": recent, "worst": worst}

    def to_jsonl(self, n: Optional[int] = None) -> str:
        """Completed traces as JSONL, one trace per line (offline
        timeline tooling; newest last)."""
        with self._lock:
            done = [(tid, tr) for tid, tr in self._traces.items()
                    if tr["done"]]
            if n is not None:
                done = done[-n:] if n > 0 else []
            lines = [json.dumps(self._trace_dict(tid, tr))
                     for tid, tr in done]
        return "\n".join(lines) + ("\n" if lines else "")

    def get_trace(self, trace_id: str) -> Optional[Dict]:
        with self._lock:
            tr = self._traces.get(trace_id)
            return self._trace_dict(trace_id, tr) if tr else None

    @property
    def completed_total(self) -> int:
        with self._lock:
            return self._completed


# -- dsod.* spans: the profiler's clock and the host's -------------------

_profiler = None  # jax.profiler, imported on first use: this module
# stays importable (and the load generator stays light) without JAX.

# The host-clock sink (always on; docs/OBSERVABILITY.md "Host-clock
# sink").  Every span reads ``_clock`` — the clock of
# ``benchmark/run.py::T_START`` and of the runners' ticks — on entry
# and on exit, profiler session or not, and adds its seconds to a
# per-name total of ITS thread (no lock on the hot path; the readers
# merge).  Names under ``dsod.setup.`` also land as intervals in one
# bounded list: the set-up phases happen once, the loop's spans
# thousands of times a minute.
_clock = time.perf_counter
SETUP = "dsod.setup."
MAX_SETUP_SPANS = 64
_sink_lock = threading.Lock()
_local = threading.local()
_threads: List[Tuple[threading.Thread, Dict[str, float]]] = []
_retired: Dict[str, float] = {}  # totals of threads that have ended
_setup_spans: List[Tuple[str, float, float, Optional[str]]] = []


def now() -> float:
    """The sink's clock, for intervals a caller records itself."""
    return _clock()


def _thread_totals() -> Dict[str, float]:
    """This thread's seconds per span name, made on first use."""
    try:
        return _local.totals
    except AttributeError:
        totals = _local.totals = {}
        with _sink_lock:
            _threads.append((threading.current_thread(), totals))
        return totals


def span_totals() -> Dict[str, float]:
    """Seconds inside each span name since the process started, summed
    over threads; a nested span counts in full under its own name AND
    inside its parent's.  A reader keeps the last answer and subtracts
    (``fit()``'s ``stall:`` line does, per logging interval)."""
    with _sink_lock:
        out, live = dict(_retired), []
        for thread, totals in _threads:
            for name, s in dict(totals).items():
                out[name] = out.get(name, 0.0) + s
            if thread.is_alive():
                live.append((thread, totals))
            else:  # a loader thread of a finished epoch: fold it away
                for name, s in totals.items():
                    _retired[name] = _retired.get(name, 0.0) + s
        _threads[:] = live
    return out


def record_setup(name: str, t0: float, t1: float,
                 parent: Optional[str] = None) -> None:
    """One finished ``dsod.setup.*`` interval on ``_clock`` into the
    bounded list, under the name of the phase it is part of, if any —
    :class:`span` calls it on exit; ``fit()`` calls it for the
    intervals it only knows afterwards (``before_fit``, the laps of
    ``build``, each candidate end of ``warmup``).  The first
    ``MAX_SETUP_SPANS`` stay."""
    with _sink_lock:
        if len(_setup_spans) < MAX_SETUP_SPANS:
            _setup_spans.append((name, t0, t1, parent))


def setup_spans() -> List[Tuple[str, float, float, Optional[str]]]:
    """``(name, t0, t1, parent name)`` of the newest ``fit()``'s set-up."""
    with _sink_lock:
        return list(_setup_spans)


def reset_setup() -> None:
    """``fit()``'s entry: forget an earlier run's set-up (tier-1
    workers run many)."""
    with _sink_lock:
        del _setup_spans[:]


class span:
    """One host interval under one ``dsod.*`` name, for every reader.

    Opens a ``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation``
    when ``step_num`` is given), so the interval lands in whatever
    ``.xplane.pb`` a profiler session is writing, on the clock of the
    device ops.  The same interval, from ONE pair of ``_clock`` reads
    (``t0``, ``t1``), goes to the host-clock sink above and, with
    ``root`` — the root :class:`Span` of a SAMPLED chunk, whose
    ``Tracer`` ``fit()`` builds on the same clock —, into that chunk's
    trace in the ring (``/debug/traces``) under the same name.
    """

    __slots__ = ("_ann", "_root", "_attrs", "name", "t0", "t1")

    def __init__(self, name: str, root: Optional[Span] = None, *,
                 step_num: Optional[int] = None, **attrs):
        global _profiler
        if _profiler is None:
            import jax.profiler as _profiler
        self._ann = (_profiler.TraceAnnotation(name, **attrs)
                     if step_num is None else
                     _profiler.StepTraceAnnotation(name, step_num=step_num,
                                                   **attrs))
        self._root, self.name, self._attrs = root, name, attrs

    def __enter__(self) -> "span":
        self.t0 = _clock()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._ann.__exit__(*exc)
        name, t0 = self.name, self.t0
        t1 = self.t1 = _clock()
        totals = _thread_totals()
        totals[name] = totals.get(name, 0.0) + (t1 - t0)
        if name.startswith(SETUP):
            record_setup(name, t0, t1)
        root = self._root
        if root is not None:
            root._tracer.record(
                root.trace_id, name, t0, t1,
                parent_id=root.span_id, attrs=self._attrs)
        return False


# -- X-Timing header ---------------------------------------------------
#
# Format: ``trace=<id>;queue=1.234;device=5.678;e2e=7.001`` — the
# stage values are milliseconds with 3 decimals, the exact numbers the
# engine's latency histograms observed for this request, so a client
# can reconcile its own e2e against the server's split without a
# /debug/traces round trip.  ``trace=-`` means the request was not
# sampled (stages still ride).

def format_timing(trace_id: Optional[str], stages: Dict[str, float]) -> str:
    parts = [f"trace={trace_id if trace_id else '-'}"]
    parts += [f"{k}={float(v):.3f}" for k, v in stages.items()]
    return ";".join(parts)


def parse_timing(header: Optional[str]
                 ) -> Tuple[Optional[str], Dict[str, float]]:
    """``X-Timing`` value → ``(trace_id | None, {stage: ms})``.
    Tolerant: unparseable fragments are skipped, never raised on."""
    if not header:
        return None, {}
    trace_id = None
    stages: Dict[str, float] = {}
    for part in header.split(";"):
        k, sep, v = part.strip().partition("=")
        if not sep:
            continue
        if k == "trace":
            trace_id = v if v and v != "-" else None
            continue
        try:
            stages[k] = float(v)
        except ValueError:
            continue
    return trace_id, stages
