"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the
numbers the per-layer metrics read: device busy and idle time, the
table of device operations, collective time, and the longest idle gaps
with what the host was doing in them.

Two stages, so that the arithmetic can be checked on a small recorded
trace without the chip: :func:`load` turns the file into plain event
lists (``jax.profiler.ProfileData.from_file``, nothing but JAX), and
:func:`reduce_events` does every sum.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "benchmark."
WINDOW_MARK = "benchmark.window"  # the runner's span over what it traces
Event = Tuple[str, float, float]  # name, start_s, duration_s


def op_name(text: str) -> str:
    """The trace names a device op by its whole HLO instruction,
    ``%fusion.12 = (...) fusion(...), kind=kLoop, ...``: keep the
    instruction's name (a Pallas kernel's is its ``name``)."""
    return text.split(" = ", 1)[0].lstrip("%")[:120]


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str) -> dict:
    """-> {"devices": {plane: [Event]}, "host": [Event], "lines": {..}}.
    Device events are those of each TPU plane's "XLA Ops" line; host
    events are the benchmark's own TraceAnnotations, from any host
    thread.  Times are seconds on the profile's one clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    lines: Dict[str, List[str]] = {}
    for plane in data.planes:
        lines[plane.name] = [ln.name for ln in plane.lines]
        if plane.name.startswith(DEVICE_PLANE):
            for ln in plane.lines:
                if ln.name != OPS_LINE:
                    continue
                devices[plane.name] = [
                    (op_name(ev.name), ev.start_ns * 1e-9,
                     ev.duration_ns * 1e-9) for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9))
    return {"devices": devices, "host": host, "lines": lines}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(events: List[Event]) -> Dict[str, float]:
    """Seconds by op name, a parent's time less its children's (a
    ``while`` or a fusion wrapping named ops is not counted twice)."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, self]

    def pop():
        name, _, self_s = stack.pop()
        out[name] = out.get(name, 0.0) + max(self_s, 0.0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1] - 1e-12:
            pop()
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    while stack:
        pop()
    return out


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(n.startswith(p) for p in (
        "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
        "collective-permute"))


def reduce_events(tr: dict, window: Optional[Tuple[float, float]] = None,
                  top: int = 10, gaps: int = 5) -> Optional[dict]:
    """Every number the readers take from a trace, or None where no
    operation ran on a device.  Busy, idle and collective times are
    averaged over the devices traced; the op table and the gaps are
    those of the first device."""
    devs = {k: v for k, v in sorted(tr["devices"].items()) if v}
    if not devs:
        return None
    marks = [(s, s + d) for n, s, d in tr["host"] if n == WINDOW_MARK]
    if window is None and marks:
        window = max(marks, key=lambda m: m[1] - m[0])
    if window is None:
        t0 = min(s for ev in devs.values() for _, s, _ in ev)
        t1 = max(s + d for ev in devs.values() for _, s, d in ev)
    else:
        t0, t1 = window
    busy, coll, coll_exposed = [], [], []
    for events in devs.values():
        ev = [(n, max(s, t0), min(s + d, t1)) for n, s, d in events
              if s + d > t0 and s < t1]
        iv = _union([(s, e) for _, s, e in ev])
        busy.append(sum(e - s for s, e in iv))
        c_iv = _union([(s, e) for n, s, e in ev if is_collective(n)])
        k_iv = _union([(s, e) for n, s, e in ev if not is_collective(n)])
        c = sum(e - s for s, e in c_iv)
        both = sum(e - s for s, e in _union(c_iv + k_iv)) \
            - sum(e - s for s, e in k_iv)
        coll.append(c)
        coll_exposed.append(both)  # collective time with no compute under it
    first = next(iter(devs.values()))
    in_win = [(n, s, d) for n, s, d in first if s + d > t0 and s < t1]
    table = sorted(_self_times(in_win).items(), key=lambda kv: -kv[1])[:top]
    iv = _union([(s, s + d) for _, s, d in in_win])
    holes = [(a[1], b[0]) for a, b in zip(iv, iv[1:])]
    if iv:
        holes = [(t0, iv[0][0])] + holes + [(iv[-1][1], t1)]
    holes = sorted((h for h in holes if h[1] > h[0]),
                   key=lambda h: h[0] - h[1])[:gaps]
    gap_rows = []
    for a, b in holes:
        best, cover = "unattributed", 0.0
        for n, s, d in tr["host"]:
            if n == WINDOW_MARK:
                continue
            ov = min(b, s + d) - max(a, s)
            if ov > cover:
                best, cover = n, ov
        gap_rows.append([best, b - a])
    n = len(devs)
    return {"window_s": t1 - t0, "busy_s": sum(busy) / n,
            "collective_s": sum(coll) / n,
            "collective_exposed_s": sum(coll_exposed) / n,
            "device_ops": [[k, v] for k, v in table],
            "idle_gaps": gap_rows, "devices": n}


def head(tr: dict, n: int = 400) -> dict:
    """A small cut of a loaded trace (the first ``n`` device events of
    each device and the host spans beside them), for a test fixture."""
    devs = {k: sorted(v, key=lambda e: e[1])[:n]
            for k, v in tr["devices"].items()}
    ends = [e[1] + e[2] for v in devs.values() for e in v]
    t1 = max(ends) if ends else 0.0
    host = [e for e in tr["host"] if e[1] <= t1 and e[0] != WINDOW_MARK]
    return {"devices": devs, "host": host[:n], "lines": {}}
