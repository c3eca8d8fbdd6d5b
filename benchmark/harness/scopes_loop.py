"""Device self time of the looped model's sub-scopes, from the same
trace and by the same rule as the stage table and ``harness/
scopes_ssm.py`` (an op's path from the optimised ``HloProto``, a
parent's time less its children's).

Sub-scopes (``models/ouro.py``): ``dsod.loop`` around the looped stack
and, inside it, ``dsod.attn`` (with the attention core alone under
``dsod.attn.core``), ``dsod.densemlp`` and ``dsod.loop.exit`` — the
DEEPEST one in an op's path, so that the projections and the rotation
are ``attn`` and whatever computes the causal softmax ``attn.core``.  No
kernel is named here.

A program that names no ``dsod.loop`` (the parent commit, another
model) reduces to an empty table and the readers report nothing.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional

from . import spans, trace

_SCOPE = re.compile(r"dsod\.(loop\.exit|loop|attn\.core|attn|densemlp)\b")
OTHER = "-"


def reduce(tr: dict) -> Dict[str, float]:
    """``tr``: what :func:`spans.load` returns.  -> {scope: seconds},
    averaged over the device planes, inside the window; empty unless
    some op sits under ``dsod.loop``."""
    window = spans.window_of(tr["host"])
    devs = [v for _, v in sorted(tr["devices"].items()) if v]
    scope_s: Dict[str, float] = {}
    for events in devs:
        keyed = []
        for _, s, d, path in spans._clip(events, window):
            found = _SCOPE.findall(path)
            keyed.append((found[-1] if "loop" in found else OTHER, s, d))
        for key, sec in trace._self_times(keyed).items():
            if key != OTHER:
                scope_s[key] = scope_s.get(key, 0.0) + sec / len(devs)
    return scope_s


@functools.lru_cache(maxsize=2)
def _of_dir(trace_dir: str) -> Optional[Dict[str, float]]:
    path = trace.find_xplane(trace_dir)
    if not path:
        return None
    red = reduce(spans.load(path))
    for k, v in sorted(red.items(), key=lambda kv: -kv[1]):
        print(f"scopes: loop sub-scope {k:24s} {v:.6f} s", flush=True)
    return red


def scope_seconds(run: dict, prefix: str) -> Optional[float]:
    """Seconds in the traced steps under the sub-scopes starting with
    ``prefix``; None where the program names none of them."""
    tdir = run.get("trace_dir")
    red = _of_dir(tdir) if tdir else None
    if not red or not run.get("traced_steps"):
        return None
    hit = [v for k, v in red.items() if k.startswith(prefix)]
    return sum(hit) if hit else None


def scope_ms_per_step(run: dict, prefix: str) -> Optional[float]:
    s = scope_seconds(run, prefix)
    return None if s is None else s * 1000.0 / run["traced_steps"]
