"""What the program names in a ``jax.profiler`` trace, read back:
``dsod.<stage>`` named scopes on the device ops and ``dsod.*``
annotations on the host threads, both in the one ``.xplane.pb`` and so
on one clock.

Where the scope path of a device op comes from (established on the
chip, PERF.md section 3): NOT from the event.  An event on the "XLA
Ops" line carries its HLO instruction as text without metadata, and
the stats ``ProfileData`` exposes are timing only.  The optimised
``HloProto`` of every module that ran sits in the ``/host:metadata``
plane (one event-metadata entry per module, named like the events of
the "XLA Modules" line, one bytes stat).  ``ProfileData`` does not
expose event metadata, so :func:`hlo_op_names` walks the protobuf wire
format down to ``HloInstructionProto.metadata.op_name`` — field
numbers only, no generated code — and an op event finds its path by
the module that encloses it and its instruction name.

Two stages like ``harness/trace.py``: :func:`load` turns the file into
plain lists, the pure functions do every sum, and a small cut of a
chip trace (:func:`head`) checks them without the chip.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Iterator, List, Optional, Tuple

from . import trace

STAGES = ("encoder", "decoder", "heads", "loss", "update")
UNSCOPED = "unscoped"
UNATTRIBUTED = "unattributed"
INHERITED = "~"  # leads a path an op took from its user or operand
PREFIX = "dsod."
STEP_SPAN = "dsod.train.step"
MODULES_LINE = "XLA Modules"
_STAGE = re.compile(r"dsod\.(%s)\b" % "|".join(STAGES))
_SUB = re.compile(r"dsod\.(resample\b|kernel\.\w+)")

OpEvent = Tuple[str, float, float, str]  # name, start_s, duration_s, path
HostSpan = Tuple[str, str, float, float, dict]  # name, line, start, dur, stats


# -- the protobuf wire format, as far as the HloProto's op names ---------

def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one serialized message: ints for
    varints, memoryviews for length-delimited and fixed fields."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        v = shift = 0
        while True:
            b = buf[i]
            i += 1
            v |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return v

    while i < n:
        key = varint()
        wire = key & 7
        if wire == 0:
            yield key >> 3, varint()
            continue
        size = varint() if wire == 2 else {1: 8, 5: 4}[wire]
        yield key >> 3, buf[i:i + size]
        i += size


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _instructions(hlo_proto) -> Iterator[Tuple[str, str]]:
    """(instruction name, metadata.op_name) over every computation of
    an ``HloProto``: hlo_module=1 / computations=3 / instructions=2 /
    name=1, metadata=7 / op_name=2, id=35, operand_ids=36 (packed).

    An instruction the compiler made (a layout ``copy``, the
    ``copy-start``/``copy-done`` of a prefetch, a ``pad`` split off a
    convolution) has no ``op_name``.  It exists for its user, so it
    takes the path of its first user that is under a stage (through up
    to four hops: ``copy-start`` -> ``copy-done`` -> the user), else
    of an operand, marked with a leading ``~`` (:data:`INHERITED`)."""
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f, comp in _fields(module):
            if f != 3:
                continue
            rows = []  # [id, name, path, operand ids]
            for f, instr in _fields(comp):
                if f != 2:
                    continue
                row = [0, "", "", ()]
                for f, v in _fields(instr):
                    if f == 1:
                        row[1] = _text(v)
                    elif f == 7:
                        row[2] = next((_text(x) for g, x in _fields(v)
                                       if g == 2), "")
                    elif f == 35:
                        row[0] = v
                    elif f == 36:
                        row[3] = (v,) if isinstance(v, int) else _packed(v)
                rows.append(row)
            yield from _inherit(rows)


def _packed(buf) -> Tuple[int, ...]:
    out, v, shift = [], 0, 0
    for b in bytes(buf):
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            out.append(v)
            v = shift = 0
    return tuple(out)


def _inherit(rows) -> Iterator[Tuple[str, str]]:
    path = {r[0]: r[2] for r in rows}
    users: Dict[int, List[int]] = {}
    for r in rows:
        for o in r[3]:
            users.setdefault(o, []).append(r[0])
    for hop in range(8):  # four hops along users, then operands too
        found = {}
        for r in rows:
            if _STAGE.search(path[r[0]]):
                continue
            near = users.get(r[0], []) + (list(r[3]) if hop >= 4 else [])
            donor = next((path[i] for i in near
                          if _STAGE.search(path.get(i, ""))), None)
            if donor:
                found[r[0]] = INHERITED + donor.lstrip(INHERITED)
        path.update(found)
    for r in rows:
        yield r[1], path[r[0]]


def hlo_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{module as "XLA Modules" names it: {instruction: op_name path}}
    from the ``/host:metadata`` plane of a serialized XSpace: planes=1 /
    name=2, event_metadata=4 (map: value=2) / name=2, stats=5 /
    bytes_value=6."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        entries = list(_fields(plane))
        if not any(f == 2 and _text(v) == "/host:metadata"
                   for f, v in entries):
            continue
        for f, entry in entries:
            if f != 4:
                continue
            for f, meta in _fields(entry):
                if f != 2:
                    continue
                module, protos = "", []
                for f, v in _fields(meta):
                    if f == 2:
                        module = _text(v)
                    elif f == 5:
                        protos += [x for g, x in _fields(v) if g == 6]
                for proto in protos:
                    out.setdefault(module, {}).update(_instructions(proto))
    return out


# -- the file -> plain lists ---------------------------------------------

def load(path: str) -> dict:
    """-> {"devices": {plane: [OpEvent]}, "host": [HostSpan]}.  Device
    events are those of each TPU plane's "XLA Ops" line, each with the
    ``op_name`` path of its instruction ("" where the module or the
    instruction is not in the metadata plane); host spans are the
    ``dsod.*`` annotations and the benchmark's window mark, with the
    line (thread) they ran on.  Seconds on the profile's one clock."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    names = hlo_op_names(raw)
    devices: Dict[str, List[OpEvent]] = {}
    host: List[HostSpan] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith(trace.DEVICE_PLANE):
            lines = {ln.name: ln for ln in plane.lines}
            if trace.OPS_LINE not in lines:
                continue
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           names.get(ev.name, {}))
                          for ev in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            ops, k = [], 0
            for ev in sorted(lines[trace.OPS_LINE].events,
                             key=lambda e: e.start_ns):
                while k < len(mods) and mods[k][1] <= ev.start_ns:
                    k += 1
                inside = k < len(mods) and mods[k][0] <= ev.start_ns
                name = trace.op_name(ev.name)
                ops.append((name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                            mods[k][2].get(name, "") if inside else ""))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                for ev in ln.events:
                    if ev.name.startswith(PREFIX) \
                            or ev.name == trace.WINDOW_MARK:
                        host.append((ev.name, f"{i}:{ln.name}",
                                     ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9, dict(ev.stats)))
    return {"devices": devices, "host": host}


# -- pure reductions -----------------------------------------------------

def stage_of(path: str) -> str:
    """The outermost ``dsod.<stage>`` component of an op's path."""
    m = _STAGE.search(path)
    return m.group(1) if m else UNSCOPED


def window_of(host: List[HostSpan]) -> Optional[Tuple[float, float]]:
    marks = [(s, s + d) for n, _, s, d, _ in host if n == trace.WINDOW_MARK]
    return max(marks, key=lambda m: m[1] - m[0]) if marks else None


def _clip(events, window):
    if window is None:
        return list(events)
    t0, t1 = window
    return [(e[0], max(e[1], t0), min(e[1] + e[2], t1) - max(e[1], t0))
            + tuple(e[3:]) for e in events if e[1] + e[2] > t0 and e[1] < t1]


def stage_self_times(events: List[OpEvent], window=None, *,
                     sub: bool = False, inherited: bool = True
                     ) -> Dict[str, float]:
    """Seconds of device self time by stage (a parent's time less its
    children's, the rule of ``trace._self_times``), ``unscoped`` for ops
    under no stage; they add up to the union of the op intervals.  With
    ``sub``, keys are ``stage/resample``, ``stage/kernel.<name>`` or
    ``stage/-``: what nests one level below.  ``inherited=False``
    counts an op by its own path alone."""
    keyed = []
    for _, s, d, path in _clip(events, window):
        if not inherited and path.startswith(INHERITED):
            path = ""
        key = stage_of(path)
        if sub:
            m = _SUB.search(path)
            key += "/" + (m.group(1) if m else "-")
        keyed.append((key, s, d))
    return trace._self_times(keyed)


def fit_line(host: List[HostSpan]) -> Optional[str]:
    """The thread ``fit()`` runs on: the one with the step spans."""
    return next((ln for n, ln, _, _, _ in host if n == STEP_SPAN), None)


def _deepest(spans: List[Tuple[str, float, float]]):
    """Properly nested (name, start, end) spans of one thread -> flat,
    disjoint (start, end, name) pieces, each named by the deepest span
    open there."""
    out, stack = [], []  # stack of [name, end, cursor]

    def close():
        name, end, cur = stack.pop()
        if end > cur:
            out.append((cur, end, name))
        if stack:
            stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][1]:
            close()
        if stack:
            top = stack[-1]
            if s > top[2]:
                out.append((top[2], s, top[0]))
            top[2] = s
            e = min(e, top[1])
        stack.append([name, e, s])
    while stack:
        close()
    return sorted(out)


def idle_by_span(tr: dict, window=None) -> Optional[Dict[str, float]]:
    """Seconds of device-idle time inside the window (the benchmark's
    mark unless given), by the deepest ``dsod.*`` span open on fit()'s
    thread at each instant, ``unattributed`` where none is.  Mean over
    the devices traced; None where no device ran an op."""
    devs = [v for _, v in sorted(tr["devices"].items()) if v]
    window = window or window_of(tr["host"])
    if not devs:
        return None
    if window is None:
        window = (min(e[1] for v in devs for e in v),
                  max(e[1] + e[2] for v in devs for e in v))
    line = fit_line(tr["host"])
    pieces = _deepest([(n, s, s + d) for n, ln, s, d, _ in tr["host"]
                       if ln == line and n.startswith(PREFIX)])
    out: Dict[str, float] = {UNATTRIBUTED: 0.0}
    for events in devs:
        busy = trace._union([(s, s + d) for _, s, d, *_ in
                             _clip(events, window)])
        edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
        k = 0
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            left = b - a
            while k < len(pieces) and pieces[k][1] <= a:
                k += 1
            j = k
            while j < len(pieces) and pieces[j][0] < b:
                ov = min(b, pieces[j][1]) - max(a, pieces[j][0])
                if ov > 0:
                    out[pieces[j][2]] = out.get(pieces[j][2], 0.0) + ov
                    left -= ov
                j += 1
            out[UNATTRIBUTED] += max(left, 0.0)
    return {k: v / len(devs) for k, v in out.items()}


def span_totals(host: List[HostSpan], window=None) -> Dict[str, list]:
    """{"<line> <name>": [count, seconds]} of the host spans that start
    inside the window: every thread, for context."""
    out: Dict[str, list] = {}
    for n, ln, s, d, _ in host:
        if n == trace.WINDOW_MARK or (
                window and not window[0] <= s < window[1]):
            continue
        row = out.setdefault(f"{ln} {n}", [0, 0.0])
        row[0] += 1
        row[1] += d
    return out


def reduce(tr: dict) -> dict:
    """Everything the readers take.  ``stage_s`` / ``idle_s`` are None
    where the program names nothing (no op under a stage; no step span):
    the readers then report nothing."""
    window = window_of(tr["host"])
    devs = [v for _, v in sorted(tr["devices"].items()) if v]
    stage = table = None
    own_unscoped = 0.0
    if any(stage_of(e[3]) != UNSCOPED for v in devs for e in v):
        n = len(devs)
        stage, table = {}, {}
        for v in devs:
            for k, s in stage_self_times(v, window, sub=True).items():
                table[k] = table.get(k, 0.0) + s / n
                k = k.split("/")[0]
                stage[k] = stage.get(k, 0.0) + s / n
            own_unscoped += stage_self_times(
                v, window, inherited=False).get(UNSCOPED, 0.0) / n
    idle = idle_by_span(tr, window) if fit_line(tr["host"]) else None
    return {"stage_s": stage, "stage_table_s": table, "idle_s": idle,
            "unscoped_own_s": own_unscoped,
            "spans": span_totals(tr["host"], window), "window": window}


def head(tr: dict, n: int = 400) -> dict:
    """A small cut of a loaded trace for a test fixture: the first
    ``n`` device events inside the window and the host spans beside
    them, the window mark cut to the same stretch."""
    window = window_of(tr["host"])
    devs = {k: sorted(_clip(v, window), key=lambda e: e[1])[:n]
            for k, v in tr["devices"].items()}
    t1 = max(e[1] + e[2] for v in devs.values() for e in v)
    t0 = window[0] if window else min(e[1] for v in devs.values() for e in v)
    host = [[nm, ln, s, min(d, t1 - s), st] for nm, ln, s, d, st in tr["host"]
            if s < t1 and s + d > t0 and nm != trace.WINDOW_MARK]
    host.append([trace.WINDOW_MARK, fit_line(tr["host"]) or "", t0, t1 - t0,
                 {}])
    return {"devices": devs, "host": host}


# -- for the readers in layer_metrics/ -----------------------------------

@functools.lru_cache(maxsize=2)
def _of_dir(trace_dir: str) -> Optional[dict]:
    path = trace.find_xplane(trace_dir)
    if not path:
        return None
    red = reduce(load(path))
    print_tables(red)
    return red


def of_run(run: dict) -> Optional[dict]:
    """The reduction of the run's trace, made once for all readers (and
    printed once, for PERF.md section 5); None without a trace."""
    tdir = run.get("trace_dir")
    return _of_dir(tdir) if tdir else None


def stage_ms_per_step(run: dict, stage: str) -> Optional[float]:
    red, n = of_run(run), run.get("traced_steps")
    if not red or not red["stage_s"] or not n:
        return None
    return red["stage_s"].get(stage, 0.0) * 1000.0 / n


def idle_ms_per_step(run: dict, prefix: str) -> Optional[float]:
    """Device-idle ms per step under the fit()-thread spans whose name
    starts with ``prefix``."""
    red, n = of_run(run), run.get("traced_steps")
    if not red or not red["idle_s"] or not n:
        return None
    return sum(v for k, v in red["idle_s"].items()
               if k.startswith(prefix)) * 1000.0 / n


def print_tables(red: dict) -> None:
    if red["stage_table_s"]:
        print("spans: device self time by stage / resample or kernel, s:")
        for k, v in sorted(red["stage_table_s"].items(), key=lambda kv: -kv[1]):
            print(f"spans:   {k:40s} {v:.6f}")
        print(f"spans:   under no stage by the op's own path (before "
              f"it takes its user's): {red['unscoped_own_s']:.6f}")
    if red["idle_s"]:
        print("spans: device idle by fit()-thread span, s:", {
            k: round(v, 6) for k, v in sorted(red["idle_s"].items())})
    for k, (c, s) in sorted(red["spans"].items()):
        print(f"spans: host {k:48s} n {c:5d} total {s:.6f} s")
