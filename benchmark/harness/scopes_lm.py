"""Device self time of the token model's sub-scopes and kernels, from
the same trace and by the same rule as the stage table
(``harness/spans.py``: an op's path from the optimised ``HloProto``, a
parent's time less its children's).

Sub-scopes (``models/lfm2.py``): ``dsod.moe.route`` / ``.experts`` /
``.combine``, ``dsod.attn``, ``dsod.shortconv``, ``dsod.densemlp`` —
the outermost one in an op's path, whatever stage it sits in.  Kernels:
every ``dsod.kernel.<name>`` scope wraps exactly one ``pallas_call``,
so the events whose OWN path (not one taken from a neighbour) names the
kernel are its calls: their count and their summed time.

A program that names none of this (the parent commit, an image model)
reduces to empty tables and the readers report nothing.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional

from . import spans, trace

_SCOPE = re.compile(r"dsod\.(moe\.\w+|attn|shortconv|densemlp)\b")
_KERNEL = re.compile(r"dsod\.kernel\.(\w+)")
OTHER = "-"


def reduce(tr: dict) -> dict:
    """``tr``: what :func:`spans.load` returns.  -> ``scope_s`` {scope:
    seconds}, ``kernel_s`` {kernel: seconds}, ``kernel_calls`` {kernel:
    events}, each averaged over the device planes, inside the window."""
    window = spans.window_of(tr["host"])
    devs = [v for _, v in sorted(tr["devices"].items()) if v]
    scope_s: Dict[str, float] = {}
    kernel_s: Dict[str, float] = {}
    calls: Dict[str, float] = {}
    for events in devs:
        by_scope, by_kernel = [], []
        for name, s, d, path in spans._clip(events, window):
            m = _SCOPE.search(path)
            by_scope.append((m.group(1) if m else OTHER, s, d))
            k = None if path.startswith(spans.INHERITED) \
                else _KERNEL.search(path)
            by_kernel.append((k.group(1) if k else OTHER, s, d))
            if k:
                calls[k.group(1)] = calls.get(k.group(1), 0) + 1 / len(devs)
        for out, keyed in ((scope_s, by_scope), (kernel_s, by_kernel)):
            for key, sec in trace._self_times(keyed).items():
                if key != OTHER:
                    out[key] = out.get(key, 0.0) + sec / len(devs)
    return {"scope_s": scope_s, "kernel_s": kernel_s, "kernel_calls": calls,
            "top_ops": _top_ops(devs[0], window) if devs else {}}


def _top_ops(events, window, n: int = 6) -> dict:
    """{scope: the ``n`` ops with most self time under it, [(op, s)]} on
    the first device: what PERF.md section 5 names inside a scope."""
    keyed = []
    for name, s, d, path in spans._clip(events, window):
        m = _SCOPE.search(path)
        keyed.append(((m.group(1) if m else OTHER) + " " + name, s, d))
    per: Dict[str, list] = {}
    for key, sec in trace._self_times(keyed).items():
        scope, op = key.split(" ", 1)
        per.setdefault(scope, []).append((op, sec))
    return {k: sorted(v, key=lambda r: -r[1])[:n] for k, v in per.items()}


@functools.lru_cache(maxsize=2)
def _of_dir(trace_dir: str) -> Optional[dict]:
    path = trace.find_xplane(trace_dir)
    if not path:
        return None
    red = reduce(spans.load(path))
    for title, table in (("sub-scope", red["scope_s"]),
                         ("kernel", red["kernel_s"])):
        for k, v in sorted(table.items(), key=lambda kv: -kv[1]):
            print(f"scopes: {title} {k:36s} {v:.6f} s"
                  + (f"  calls {red['kernel_calls'][k]:.0f}"
                     if title == "kernel" else ""), flush=True)
    for scope, ops in sorted(red["top_ops"].items()):
        print(f"scopes: top ops under {scope}: " + ", ".join(
            f"{op} {sec:.4f}" for op, sec in ops), flush=True)
    return red


def of_run(run: dict) -> Optional[dict]:
    tdir = run.get("trace_dir")
    return _of_dir(tdir) if tdir else None


def scope_ms_per_step(run: dict, prefix: str) -> Optional[float]:
    """ms per traced step under the sub-scopes starting with ``prefix``;
    None where the program names none of them."""
    red, n = of_run(run), run.get("traced_steps")
    if not red or not n:
        return None
    hit = [v for k, v in red["scope_s"].items() if k.startswith(prefix)]
    return sum(hit) * 1000.0 / n if hit else None


def kernel_roofline_pct(run: dict, calls: dict) -> Optional[float]:
    """``calls``: {kernel scope: (flops, bytes) of ONE call}.  The least
    time the chip could take for the calls the trace shows, over the
    time they took, in percent; None unless every kernel was seen."""
    from .flops_lm import roofline_s

    red, peaks = of_run(run), (run.get("device") or {}).get("peaks")
    if not red or not peaks:
        return None
    least = took = 0.0
    for name, (flops, nbytes) in calls.items():
        n, s = red["kernel_calls"].get(name), red["kernel_s"].get(name)
        if not n or not s:
            return None
        least += n * roofline_s(flops, nbytes, peaks)
        took += s
    return 100.0 * least / took
