"""Device self time of the decoder-hybrid-decoder model's sub-scopes,
from the same trace and by the same rule as the stage table,
``harness/scopes_lm.py`` and ``harness/scopes_ssm.py`` (an op's path
from the optimised ``HloProto``, a parent's time less its children's).
``scopes_ssm.py`` reads this model's ``dsod.ssm*`` scopes as it is; its
pattern knows no others.

Sub-scopes (``models/phi4flash.py``): ``dsod.attn.window`` around a
windowed differential-attention mixer, ``dsod.attn.full`` around a full
one (self- or cross-attention), ``dsod.gmu`` around a gated memory
unit — the OUTERMOST of the three in an op's path is its ``layer`` — and,
inside either attention scope, ``dsod.attn.flash`` around the kernel
calls alone: an op is ``flash`` if that scope is anywhere in its path.
No kernel is named here: the attention's time is whatever runs under
its scope.

A program that names none of this (the parent commit, another model)
reduces to empty tables and the readers report nothing.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Optional

from . import spans, trace

_LAYER = re.compile(r"dsod\.(attn\.window|attn\.full|gmu)\b")
_FLASH = re.compile(r"dsod\.attn\.flash\b")
OTHER = "-"


def reduce(tr: dict) -> Dict[str, Dict[str, float]]:
    """``tr``: what :func:`spans.load` returns.  -> {"layer": {scope:
    seconds}, "flash": {"attn.flash": seconds}}, averaged over the
    device planes, inside the window."""
    window = spans.window_of(tr["host"])
    devs = [v for _, v in sorted(tr["devices"].items()) if v]
    out: Dict[str, Dict[str, float]] = {"layer": {}, "flash": {}}
    for events in devs:
        by_layer, by_flash = [], []
        for _, s, d, path in spans._clip(events, window):
            m = _LAYER.search(path)
            by_layer.append((m.group(1) if m else OTHER, s, d))
            by_flash.append(("attn.flash" if _FLASH.search(path) else OTHER,
                             s, d))
        for table, keyed in (("layer", by_layer), ("flash", by_flash)):
            for key, sec in trace._self_times(keyed).items():
                if key != OTHER:
                    out[table][key] = out[table].get(key, 0.0) \
                        + sec / len(devs)
    return out


@functools.lru_cache(maxsize=2)
def _of_dir(trace_dir: str) -> Optional[Dict[str, Dict[str, float]]]:
    path = trace.find_xplane(trace_dir)
    if not path:
        return None
    red = reduce(spans.load(path))
    for table in red.values():
        for k, v in sorted(table.items(), key=lambda kv: -kv[1]):
            print(f"scopes: phi4flash sub-scope {k:16s} {v:.6f} s",
                  flush=True)
    return red


def scope_seconds(run: dict, table: str, scope: str) -> Optional[float]:
    """Seconds in the traced steps under ``scope`` of ``table`` (layer |
    flash); None where the program does not name it."""
    tdir = run.get("trace_dir")
    red = _of_dir(tdir) if tdir else None
    if not red or not run.get("traced_steps"):
        return None
    return red[table].get(scope)


def scope_ms_per_step(run: dict, scope: str) -> Optional[float]:
    s = scope_seconds(run, "layer", scope)
    return None if s is None else s * 1000.0 / run["traced_steps"]
