#!/usr/bin/env python
"""Summarise a ``jax.profiler`` trace: stage table, op table, idle attribution.

    python tools/analyze_trace.py <profile-dir> [--top N]

``<profile-dir>`` is what ``train.py --profile-dir``, the sidecar's
``/debug/profile?seconds=N`` (its ``logdir``) or the benchmark's
``--trace 1`` (``benchmark/out/<cell>/trace``) wrote.  One reduction for
the operator and the benchmark: this is a thin CLI over
``benchmark/harness/trace.py`` (busy / idle / op table / longest gaps)
and ``benchmark/harness/spans.py`` (device self time by ``dsod.<stage>``
scope, device-idle time by the ``dsod.*`` span open on fit()'s thread,
host span totals by thread), the functions the per-layer metrics read.
They need nothing but JAX.  A trace taken on the CPU backend has no
device plane: the host spans still print.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

from benchmark.harness import spans, trace  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace_dir", help="the profile directory")
    p.add_argument("--top", type=int, default=15,
                   help="rows of the op table")
    args = p.parse_args(argv)

    path = trace.find_xplane(args.trace_dir)
    if not path:
        print(f"no plugins/profile/*/*.xplane.pb under {args.trace_dir}",
              file=sys.stderr)
        return 1
    print(f"xplane: {path}")
    red = trace.reduce_events(trace.load(path), top=args.top)
    if red is None:
        print("device: no operation on a TPU plane in this trace")
    else:
        idle = red["window_s"] - red["busy_s"]
        print(f"device: window {red['window_s']:.6f} s, busy "
              f"{red['busy_s']:.6f} s, idle {idle:.6f} s "
              f"({100.0 * idle / red['window_s']:.2f} %), collectives "
              f"{red['collective_s']:.6f} s ({red['collective_exposed_s']:.6f}"
              f" s exposed), {red['devices']} device(s)")
        print(f"top {args.top} device ops by self time, s:")
        for name, s in red["device_ops"]:
            print(f"  {name:56s} {s:.6f}")
        print("longest device-idle gaps, s:", red["idle_gaps"])
    spans.print_tables(spans.reduce(spans.load(path)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
