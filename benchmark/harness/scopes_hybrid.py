"""Device self time of the latent expert layer's sub-scopes, from the
same trace and by the same rule as the stage table and
``harness/scopes_ssm.py`` (an op's path from the optimised ``HloProto``,
a parent's time less its children's).

Sub-scopes (``models/nemotron_h.py``): ``dsod.moe.route`` (router,
top-k, the plan, the gather into expert order), ``dsod.moe.latent`` (the
down- and the up-projection), ``dsod.moe.experts`` (the routed grouped
products), ``dsod.moe.combine``, ``dsod.moe.shared``,
``dsod.moe.balance`` — siblings; the DEEPEST in an op's path.  A table
is made only where some op sits under ``dsod.moe.latent``: another
model's expert layer (no latent) is not read.  No kernel is named here:
the routed products' time is whatever runs under their scope.

A program that names none of this (the parent commit, another model)
reduces to an empty table and the readers report nothing.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional

from . import spans, trace

_SCOPE = re.compile(r"dsod\.(moe\.\w+)\b")
OTHER = "-"
ROUTING = ("moe.route", "moe.combine", "moe.balance")


def reduce(tr: dict) -> Dict[str, float]:
    """``tr``: what :func:`spans.load` returns.  -> {scope: seconds},
    averaged over the device planes, inside the window; empty unless
    some op sits under ``dsod.moe.latent``."""
    window = spans.window_of(tr["host"])
    devs = [v for _, v in sorted(tr["devices"].items()) if v]
    scope_s: Dict[str, float] = {}
    for events in devs:
        keyed = []
        for _, s, d, path in spans._clip(events, window):
            found = _SCOPE.findall(path)
            keyed.append((found[-1] if found else OTHER, s, d))
        for key, sec in trace._self_times(keyed).items():
            if key != OTHER:
                scope_s[key] = scope_s.get(key, 0.0) + sec / len(devs)
    return scope_s if "moe.latent" in scope_s else {}


@functools.lru_cache(maxsize=2)
def _of_dir(trace_dir: str) -> Optional[Dict[str, float]]:
    path = trace.find_xplane(trace_dir)
    if not path:
        return None
    red = reduce(spans.load(path))
    for k, v in sorted(red.items(), key=lambda kv: -kv[1]):
        print(f"scopes: latent-moe sub-scope {k:24s} {v:.6f} s", flush=True)
    return red


def scope_seconds(run: dict, *scopes: str) -> Optional[float]:
    """Seconds in the traced steps under ``scopes`` (all of ``moe.*``
    where none is given); None where the program names none of them."""
    tdir = run.get("trace_dir")
    red = _of_dir(tdir) if tdir else None
    if not red or not run.get("traced_steps"):
        return None
    hit = [v for k, v in red.items() if not scopes or k in scopes]
    return sum(hit) if hit else None


def scope_ms_per_step(run: dict, *scopes: str) -> Optional[float]:
    s = scope_seconds(run, *scopes)
    return None if s is None else s * 1000.0 / run["traced_steps"]
