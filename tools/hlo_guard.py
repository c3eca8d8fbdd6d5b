#!/usr/bin/env python
"""HLO structure guard — catch lowered-program regressions at t1 time.

A TPU window is needed to SEE a relayout copy or a collective in a
trace; what causes them is already countable on the CPU.  This tool
lowers a train step (reusing tools/dump_hlo.py, lowering only — no
compile) and counts, per arm:

- the data-formatting ops in the pre-optimization StableHLO —
  ``reshape``, ``transpose`` and ``broadcast_in_dim`` — for the
  conv-block arms, on a small carrier (the fused arm lowers every
  Pallas kernel in interpret mode — minutes of tracing at flagship
  size):

  - ``conv_xla``    — model.conv_impl=xla (the default; its counts
                      drifting is a byte-identity regression canary);
  - ``conv_fused``  — model.conv_impl=fused (the Pallas conv-stage
                      kernels; counts pin the fused seam's lowered
                      structure);

- the gradient collectives of the rules engine's arms on the flagship
  (``COMM_ARMS`` below, with the invariants the tool itself asserts,
  exit 1).

Pre-optimization StableHLO is stable across machines (the same reason
dump_hlo.py diffs it), so the counts are checked into
``tools/hlo_copy_baseline.json`` and every run prints a ONE-LINE JSON
delta against that baseline per group — recorded, non-gating in
tools/t1.sh (pass ``--fail-on-increase`` to gate locally).  The counts
are a proxy from before there was a trace: what a resample or a conv
costs on the chip is read from the benchmark's ``dsod.*`` stages.

Usage:
    python tools/hlo_guard.py                      # print delta lines
    python tools/hlo_guard.py --update-baseline    # re-seed the file
    python tools/hlo_guard.py --fail-on-increase   # gate (local use)
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "hlo_copy_baseline.json")

# What counts as a data-formatting op in pre-opt StableHLO.  reshape +
# transpose are the relayout-copy feeders; broadcast_in_dim is counted
# too because a size-1-axis insertion may lower either way.
_FORMATTING = ("reshape", "transpose", "broadcast_in_dim")

# Conv-block arms (round 14): formatting-op counts per
# model.conv_impl arm, lowered on a smaller carrier than the flagship —
# the fused arm lowers the Pallas kernels in interpret mode on CPU
# (grid loops and im2col slicing all visible as countable ops), which
# on the flagship costs ~2 min of pure tracing; the carrier keeps the
# guard inside the t1 smoke budget while covering every seam idiom
# (plain/concat/dilated/no-BN conv blocks).  conv_xla is lowered too —
# its counts must track the seam's default arm, and a drift here is a
# byte-identity regression before tests/test_pallas_conv.py says so.
CONV_ARMS = {
    "conv_xla": (),
    "conv_fused": ("model.conv_impl=fused",),
}
# Gradient-collective arms (round 18, ISSUE 18 acceptance): the rules
# engine's bucketed allreduce fuses each backward-ordered bucket into
# ONE flat 1-D psum (parallel/rules.py::bucketed_pmean), so the
# ``stablehlo.all_reduce`` count is the countable structure signal —
# on the FLAGSHIP config:
#
# - ``comm_mono``     — comm_bucket_mb=0: the monolithic ``lax.pmean``
#                       spelling, one all_reduce PER GRADIENT LEAF in
#                       pre-opt StableHLO;
# - ``comm_flat``     — one giant bucket: every grad fused into a
#                       single flat all_reduce (the bucket-count floor);
# - ``comm_bucketed`` — the default parallel.comm_bucket_mb: B buckets.
#
# Invariants asserted (exit 1): bucketed − flat == B − 1 ≥ 1 (the
# "≥2 psum buckets at default bucket size" acceptance check — the only
# all_reduce delta between the two arms IS the extra buckets), and
# mono > bucketed (bucket fusion actually collapsed the per-leaf
# reduces).  Counts from a run whose own invariant failed are never
# written to the baseline: a corrupt seed would make every later
# comparison report delta 0 against garbage.
#
# Round 18 adds the pod-scale arms:
#
# - ``comm_hier``  — mesh.data_hosts=2 on a 4-device virtual mesh:
#   each bucket's flat psum becomes intra-host reduce-scatter →
#   inter-host all-reduce → intra-host all-gather
#   (parallel/rules.py::_hier_psum), so per bucket the pre-opt
#   StableHLO gains exactly one reduce_scatter and one all_gather
#   while the all_reduce count stays EQUAL to the bucketed arm's
#   (the bucket psum is replaced 1:1 by the inter-host psum).
#   Invariants: rs_hier − rs_bucketed == n_buckets, ag_hier −
#   ag_bucketed == n_buckets, ar_hier == ar_bucketed.
# - ``comm_fsdp``  — parallel.preset=fsdp (model.sync_bn=false: GSPMD
#   has no named BN axis): counted in POST-opt HLO because the SPMD
#   partitioner inserts the collectives during compilation — the
#   pre-opt StableHLO of a GSPMD step contains ZERO collectives.
#   Invariants: ≥1 all-gather (the JIT param gathering that IS FSDP)
#   and ≥1 reduce-scatter-or-all-reduce (the grad reduction; XLA:CPU
#   lowers reduce-scatter to all-reduce+slice, so the rs count alone
#   cannot gate on this backend).
COMM_ARMS = {
    "comm_mono": ("parallel.comm_bucket_mb=0",),
    "comm_flat": ("parallel.comm_bucket_mb=100000",),
    "comm_bucketed": (),
}
# All three collective kinds are counted per arm (flat arms lower with
# zero rs/ag today; the hier invariants difference against them).
_COLLECTIVES = ("all_reduce", "reduce_scatter", "all_gather")
COMM_HIER_ARMS = {
    "comm_hier": ("mesh.data_hosts=2",),
}
# data_hosts=2 needs ≥2 chips per host on the virtual mesh.
_HIER_DEVICES = 4
COMM_FSDP_ARMS = {
    "comm_fsdp": ("parallel.preset=fsdp", "model.sync_bn=false"),
}
# Post-opt HLO spells collectives with dashes (all-gather, ...).
_POST_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather")


def count_formatting_ops(stablehlo_text: str) -> dict:
    """Count stablehlo data-formatting ops by kind (+ 'total')."""
    counts = {}
    for kind in _FORMATTING:
        counts[kind] = len(
            re.findall(rf"stablehlo\.{kind}\b", stablehlo_text))
    counts["total"] = sum(counts.values())
    return counts


def dump_conv_arm_counts(config: str, out_dir: str, n_devices: int,
                         image_size: int) -> dict:
    """Lower the conv-arm carrier once per model.conv_impl arm (config
    overrides); return {arm: counts}."""
    from dump_hlo import dump  # tools/ sibling (path set above)

    results = {}
    for arm, overrides in CONV_ARMS.items():
        paths = dump(config, os.path.join(out_dir, arm),
                     n_devices=n_devices, image_size=image_size,
                     compile_cost=False, overrides=overrides)
        with open(paths["stablehlo"]) as f:
            results[arm] = count_formatting_ops(f.read())
    return results


def _count_collectives(stablehlo_text: str) -> dict:
    """Per-kind collective counts in pre-opt StableHLO; 'total' stays
    the all_reduce count for baseline continuity with the round-17
    rows (the bucketing invariants are all_reduce deltas)."""
    counts = {kind: len(re.findall(rf"stablehlo\.{kind}\b",
                                   stablehlo_text))
              for kind in _COLLECTIVES}
    counts["total"] = counts["all_reduce"]
    return counts


def dump_comm_arm_counts(config: str, out_dir: str, n_devices: int,
                         image_size: int) -> dict:
    """Lower the flagship step once per gradient-collective arm (config
    overrides on the rules engine); return {arm: {'all_reduce': n,
    ..., 'total': n}}.  The hierarchical
    arm lowers on a 4-device virtual mesh (data_hosts=2 needs ≥2 chips
    per host — main() sizes the device pool up front so this works
    in-process); op COUNTS in the traced program are device-count
    independent, so its deltas difference cleanly against the 2-device
    bucketed arm."""
    from dump_hlo import dump  # tools/ sibling (path set above)

    results = {}
    for arm, overrides in COMM_ARMS.items():
        paths = dump(config, os.path.join(out_dir, arm),
                     n_devices=n_devices, image_size=image_size,
                     compile_cost=False, overrides=overrides)
        with open(paths["stablehlo"]) as f:
            results[arm] = _count_collectives(f.read())
    for arm, overrides in COMM_HIER_ARMS.items():
        paths = dump(config, os.path.join(out_dir, arm),
                     n_devices=max(n_devices, _HIER_DEVICES),
                     image_size=image_size,
                     compile_cost=False, overrides=overrides)
        with open(paths["stablehlo"]) as f:
            results[arm] = _count_collectives(f.read())
    for arm, overrides in COMM_FSDP_ARMS.items():
        paths = dump(config, os.path.join(out_dir, arm),
                     n_devices=n_devices, image_size=image_size,
                     compile_cost=False, overrides=overrides,
                     post_opt=True)
        with open(paths["hlo_post"]) as f:
            txt = f.read()
        counts = {kind.replace("-", "_"): txt.count(f"{kind}(")
                  for kind in _POST_COLLECTIVES}
        counts["total"] = counts["all_gather"]
        results[arm] = counts
    return results


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="minet_r50_dp",
                   help="carrier of the gradient-collective arms: the "
                        "flagship by default")
    p.add_argument("--image-size", type=int, default=64,
                   help="small-but-even lowering size: every decoder "
                        "resample stays an exact factor-2")
    p.add_argument("--devices", type=int, default=2,
                   help="virtual CPU mesh size (lowering only; 2 keeps "
                        "the guard fast while exercising the sharded "
                        "step)")
    p.add_argument("--out", default=None,
                   help="dump dir (default: a temp dir)")
    p.add_argument("--conv-config", default="minet_vgg16_ref",
                   help="carrier for the model.conv_impl arms — "
                        "smaller than the flagship because the fused "
                        "arm lowers every Pallas kernel in interpret "
                        "mode (~2 min of tracing at flagship size)")
    p.add_argument("--conv-image-size", type=int, default=32,
                   help="conv-arm lowering size (even, so decoder "
                        "shapes stay exact factor-2)")
    p.add_argument("--no-comm-arms", action="store_true",
                   help="skip the gradient-collective arm dumps "
                        "(round 18: rules-engine bucketed allreduce)")
    p.add_argument("--baseline", default=_BASELINE)
    p.add_argument("--update-baseline", action="store_true")
    p.add_argument("--fail-on-increase", action="store_true",
                   help="exit 2 when any arm's total exceeds the "
                        "baseline (off in shared CI: recorded, not "
                        "gating — the t1.sh posture)")
    args = p.parse_args(argv)

    # The virtual device pool must be sized BEFORE the first dump
    # initializes jax (dump()'s own setdefault cannot grow an already-
    # initialized backend): the comm_hier arm needs _HIER_DEVICES even
    # when every other arm lowers on --devices.  Each dump still
    # slices jax.devices()[:n], so the smaller-mesh traces are
    # unchanged by the larger pool.
    os.environ.setdefault(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count="
        f"{max(args.devices, _HIER_DEVICES)}")

    rc = 0
    baseline = {}
    if os.path.exists(args.baseline):
        with open(args.baseline) as f:
            baseline = json.load(f)

    # -- conv_impl arms (round 14): recorded on first contact, delta-
    #    compared after; conv_xla drifting is a byte-identity
    #    regression canary, conv_fused drifting means the fused seam's
    #    lowered structure changed.
    tmp2 = None
    out_dir2 = args.out
    if out_dir2 is None:
        import tempfile

        # Cleaned up on exit: a StableHLO dump is multi-MB and t1.sh
        # runs this on every pass.
        tmp2 = tempfile.TemporaryDirectory(prefix="hlo_guard_conv_")
        out_dir2 = tmp2.name
    try:
        conv_counts = dump_conv_arm_counts(
            args.conv_config, out_dir2, args.devices,
            args.conv_image_size)
    finally:
        if tmp2 is not None:
            tmp2.cleanup()
    ckey = f"{args.conv_config}@{args.conv_image_size}px-conv"
    if args.update_baseline or ckey not in baseline:
        baseline[ckey] = conv_counts
        with open(args.baseline, "w") as f:
            json.dump(baseline, f, indent=2, sort_keys=True)
            f.write("\n")
        crecorded = True
        cdelta = {arm: 0 for arm in conv_counts}
    else:
        crecorded = False
        cdelta = {arm: conv_counts[arm]["total"]
                  - baseline[ckey].get(arm, {}).get("total", 0)
                  for arm in conv_counts}
        if args.fail_on_increase and any(d > 0 for d in cdelta.values()):
            rc = rc or 2
    print(json.dumps({
        "metric": f"hlo_formatting_ops[{ckey}]",
        "arms": {arm: c["total"] for arm, c in conv_counts.items()},
        "detail": conv_counts,
        "delta_vs_baseline": cdelta,
        **({"recorded": True} if crecorded else {}),
    }), flush=True)

    if args.no_comm_arms:
        return rc

    # -- gradient-collective arms (round 18): all_reduce counts per
    #    bucketing arm of the rules engine on the FLAGSHIP config.
    tmp3 = None
    out_dir3 = args.out
    if out_dir3 is None:
        import tempfile

        tmp3 = tempfile.TemporaryDirectory(prefix="hlo_guard_comm_")
        out_dir3 = tmp3.name
    try:
        comm_counts = dump_comm_arm_counts(
            args.config, out_dir3, args.devices, args.image_size)
    finally:
        if tmp3 is not None:
            tmp3.cleanup()
    mkey = f"{args.config}@{args.image_size}px-comm"
    n_buckets = (comm_counts["comm_bucketed"]["total"]
                 - comm_counts["comm_flat"]["total"] + 1)
    comm_invariant_failed = False
    if n_buckets < 2:
        print(f"hlo_guard: bucketed arm emits {n_buckets} psum "
              "bucket(s) — the default bucket size must split the "
              "flagship gradient into >= 2 (ISSUE 18 acceptance)",
              file=sys.stderr)
        comm_invariant_failed = True
    if comm_counts["comm_mono"]["total"] <= \
            comm_counts["comm_bucketed"]["total"]:
        print("hlo_guard: bucket fusion did NOT reduce the all_reduce "
              f"count ({comm_counts['comm_mono']['total']} mono vs "
              f"{comm_counts['comm_bucketed']['total']} bucketed)",
              file=sys.stderr)
        comm_invariant_failed = True
    # Hierarchical arm (round 18): per bucket, one intra-host
    # reduce_scatter and all_gather appear and the flat bucket psum is
    # replaced 1:1 by the inter-host psum — per-level counts asserted.
    hier = comm_counts["comm_hier"]
    bktd = comm_counts["comm_bucketed"]
    for kind, expect in (("reduce_scatter", n_buckets),
                         ("all_gather", n_buckets)):
        got = hier.get(kind, 0) - bktd.get(kind, 0)
        if got != expect:
            print(f"hlo_guard: hierarchical arm {kind} delta vs "
                  f"bucketed is {got}, expected n_buckets={expect}",
                  file=sys.stderr)
            comm_invariant_failed = True
    if hier.get("all_reduce", 0) != bktd.get("all_reduce", 0):
        print("hlo_guard: hierarchical arm all_reduce count "
              f"({hier.get('all_reduce', 0)}) != bucketed arm's "
              f"({bktd.get('all_reduce', 0)}) — the inter-host psum "
              "must replace the flat bucket psum 1:1",
              file=sys.stderr)
        comm_invariant_failed = True
    # FSDP arm (round 18, post-opt counts): the JIT param all-gather
    # is FSDP's signature; grads must reduce (rs, or XLA:CPU's
    # all-reduce lowering of it).
    fsdp = comm_counts["comm_fsdp"]
    if fsdp.get("all_gather", 0) < 1:
        print("hlo_guard: fsdp arm lowered ZERO all-gathers — params "
              "are not being gathered just-in-time", file=sys.stderr)
        comm_invariant_failed = True
    if fsdp.get("reduce_scatter", 0) + fsdp.get("all_reduce", 0) < 1:
        print("hlo_guard: fsdp arm lowered no gradient reduction "
              "(reduce-scatter or all-reduce)", file=sys.stderr)
        comm_invariant_failed = True
    if comm_invariant_failed:
        rc = rc or 1
        print(f"hlo_guard: invariant failed — NOT seeding/updating "
              f"baseline for {mkey}", file=sys.stderr)
        print(json.dumps({
            "metric": f"hlo_grad_collectives[{mkey}]",
            "arms": {arm: c["total"] for arm, c in comm_counts.items()},
            "n_buckets": n_buckets,
            "invariant_failed": True,
        }), flush=True)
        return rc
    if args.update_baseline or mkey not in baseline:
        baseline[mkey] = comm_counts
        with open(args.baseline, "w") as f:
            json.dump(baseline, f, indent=2, sort_keys=True)
            f.write("\n")
        mrecorded = True
        mdelta = {arm: 0 for arm in comm_counts}
    else:
        mrecorded = False
        mdelta = {arm: comm_counts[arm]["total"]
                  - baseline[mkey].get(arm, {}).get("total", 0)
                  for arm in comm_counts}
        if args.fail_on_increase and any(d > 0 for d in mdelta.values()):
            rc = rc or 2
    print(json.dumps({
        "metric": f"hlo_grad_collectives[{mkey}]",
        "arms": {arm: c["total"] for arm, c in comm_counts.items()},
        "n_buckets": n_buckets,
        "delta_vs_baseline": mdelta,
        **({"recorded": True} if mrecorded else {}),
    }), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
