#!/usr/bin/env python
"""Summarise a ``jax.profiler`` trace into the tables the MFU push needs.

``bench.py --profile-dir DIR`` writes an XSpace (``*.xplane.pb``) under
``DIR/plugins/profile/<run>/``.  TensorBoard can render it, but the
sandbox has no browser — this tool extracts the numbers that matter
straight from xprof's converters (installed with jax's profiler deps):

    python tools/analyze_trace.py <profile-dir>
    python tools/analyze_trace.py <profile-dir> --tool hlo_stats --top 25
    python tools/analyze_trace.py <profile-dir> --list-tools
    python tools/analyze_trace.py <profile-dir> --dump-json out/

Default output: the overview page's step-time / FLOPS utilisation
summary plus the top-N HLO ops by self time (the "attack list" for
VERDICT round-1 weakness #1: profile-driven optimisation, not guesses).

The xprof tool JSON shapes are not a stable API; every extractor here
degrades to dumping the raw JSON (``--dump-json``) rather than failing,
so a converter change can never lose a captured trace's information.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys


def find_xspaces(trace_dir: str) -> list[str]:
    """All xplane.pb files under a profile dir (any nesting)."""
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def convert(xspace_paths: list[str], tool: str):
    """Run one xprof converter; returns (data, mime) or raises."""
    from xprof.convert import raw_to_tool_data

    # xprof's converter names tools with the tab suffix ("^") trimmed;
    # params dict is tool-specific, empty works for the summary tools.
    data, _mime = raw_to_tool_data.xspace_to_tool_data(
        xspace_paths, tool, params={})
    if isinstance(data, bytes):
        data = data.decode("utf-8", errors="replace")
    return data


def _gviz_rows(table: dict) -> tuple[list[str], list[list]]:
    """Flatten a gviz DataTable dict -> (column labels, rows)."""
    cols = [c.get("label") or c.get("id") or f"c{i}"
            for i, c in enumerate(table.get("cols", []))]
    rows = []
    for r in table.get("rows", []):
        rows.append([c.get("v") if isinstance(c, dict) else c
                     for c in r.get("c", [])])
    return cols, rows


def _fmt_table(cols: list[str], rows: list[list]) -> str:
    if not rows:
        return "(no rows)"
    widths = [min(max(len(str(c)), *(len(str(r[i])) if i < len(r) else 0
                                     for r in rows)), 48)
              for i, c in enumerate(cols)]
    def fmt_row(vals):
        cells = []
        for i, v in enumerate(vals):
            s = str(v)
            if len(s) > widths[i]:
                s = s[: widths[i] - 1] + "…"
            cells.append(s.ljust(widths[i]))
        return "  ".join(cells)
    out = [fmt_row(cols), fmt_row(["-" * w for w in widths])]
    out.extend(fmt_row(r) for r in rows)
    return "\n".join(out)


def show_overview(xspaces: list[str]) -> None:
    """Step time + utilisation headline from the overview_page tool."""
    try:
        raw = convert(xspaces, "overview_page")
        page = json.loads(raw)
    except Exception as e:  # noqa: BLE001 — degrade, never lose the trace
        print(f"[overview_page unavailable: {type(e).__name__}: {e}]")
        return
    # overview_page ships a list of gviz-ish tables; the properties
    # blocks ("p" keys) carry the scalar headline stats.
    props: dict = {}
    stack = [page]
    while stack:
        node = stack.pop()
        if isinstance(node, dict):
            p = node.get("p")
            if isinstance(p, dict):
                props.update(p)
            stack.extend(node.values())
        elif isinstance(node, list):
            stack.extend(node)
    wanted = [
        ("average_step_time_ms", "avg step time (ms)"),
        ("steptime_ms_average", "avg step time (ms)"),
        ("flop_rate_utilization_relative_to_roofline", "FLOPS vs roofline"),
        ("mxu_utilization_percent", "MXU utilisation"),
        ("device_duty_cycle_percent", "device duty cycle"),
        ("memory_bw_utilization_relative_to_hw_limit", "HBM BW vs limit"),
        ("host_idle_time_percent", "host idle"),
        ("device_idle_time_percent", "device idle"),
    ]
    shown = False
    for key, label in wanted:
        if key in props:
            print(f"  {label:28s} {props[key]}")
            shown = True
    if not shown:
        print("  [overview_page parsed but no recognised scalar keys; "
              "use --dump-json to inspect]")


def show_hlo_stats(xspaces: list[str], top: int, sort_hint: str) -> None:
    """Top-N HLO ops by self time — the optimisation attack list."""
    try:
        raw = convert(xspaces, "hlo_stats")
        table = json.loads(raw)
    except Exception as e:  # noqa: BLE001
        print(f"[hlo_stats unavailable: {type(e).__name__}: {e}]")
        return
    if isinstance(table, list):  # some versions wrap in a list
        table = table[0] if table else {}
    cols, rows = _gviz_rows(table)
    if not rows:
        print("  (hlo_stats empty — use --dump-json)")
        return
    # Keep the informative columns; sort by self-time if identifiable.
    lowered = [c.lower() for c in cols]
    def col_idx(*cands):
        for cand in cands:
            for i, c in enumerate(lowered):
                if cand in c:
                    return i
        return None
    i_sort = col_idx(sort_hint, "total self time (us)", "self time")
    if i_sort is not None:
        def keyf(r):
            try:
                return -float(r[i_sort])
            except (TypeError, ValueError, IndexError):
                return 0.0
        rows = sorted(rows, key=keyf)
    keep = [i for i in (
        col_idx("hlo op name", "hlo_op_name", "op name"),
        col_idx("category"),
        col_idx("occurrences", "#"),
        i_sort,
        col_idx("self time (%", "self_time_percent", "%"),
        col_idx("flop rate", "gflops"),
        col_idx("bandwidth", "gibytes"),
    ) if i is not None]
    if not keep:
        keep = list(range(min(len(cols), 7)))
    sel_cols = [cols[i] for i in keep]
    sel_rows = [[r[i] if i < len(r) else "" for i in keep]
                for r in rows[:top]]
    print(_fmt_table(sel_cols, sel_rows))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace_dir", help="dir passed to bench.py --profile-dir")
    p.add_argument("--top", type=int, default=15)
    p.add_argument("--tool", default=None,
                   help="run ONE named xprof tool and print its raw JSON "
                        "(see --list-tools)")
    p.add_argument("--sort", default="total self time",
                   help="hlo_stats column substring to sort descending by")
    p.add_argument("--list-tools", action="store_true")
    p.add_argument("--dump-json", default=None, metavar="DIR",
                   help="write every available tool's raw JSON to DIR")
    args = p.parse_args(argv)

    xspaces = find_xspaces(args.trace_dir)
    if not xspaces:
        print(f"no *.xplane.pb under {args.trace_dir} — was the bench run "
              "with --profile-dir?", file=sys.stderr)
        return 1
    print(f"xspace files: {[os.path.basename(x) for x in xspaces]}")

    # Degrade, don't traceback (the module docstring's promise): xprof
    # ships with the jax profiler deps and its layout has moved between
    # releases — a missing/changed package must not crash --list-tools.
    # Tool enumeration failing is fatal only for the flags that need
    # it; the default overview path still runs (its extractors degrade
    # one by one).
    try:
        from xprof.convert import raw_to_tool_data

        names = [n.rstrip("^@")
                 for n in raw_to_tool_data.xspace_to_tool_names(xspaces)]
    except Exception as e:  # noqa: BLE001 — import/layout drift
        print(f"[xprof tool conversion unavailable "
              f"({type(e).__name__}: {e}); install the jax profiler "
              f"deps (xprof / tensorboard-plugin-profile)]",
              file=sys.stderr)
        if args.list_tools or args.tool or args.dump_json:
            return 1
        names = []
    if args.list_tools:
        print("\n".join(names))
        return 0

    if args.tool:
        print(convert(xspaces, args.tool))
        return 0

    if args.dump_json:
        os.makedirs(args.dump_json, exist_ok=True)
        for name in names:
            try:
                data = convert(xspaces, name)
            except Exception as e:  # noqa: BLE001 — tool-by-tool isolation
                print(f"  {name}: FAILED {type(e).__name__}: {e}")
                continue
            path = os.path.join(args.dump_json, f"{name}.json")
            with open(path, "w") as f:
                f.write(data if isinstance(data, str) else str(data))
            print(f"  {name}: {path}")
        return 0

    print("\n== overview ==")
    show_overview(xspaces)
    print(f"\n== top {args.top} HLO ops by self time ==")
    show_hlo_stats(xspaces, args.top, args.sort)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
