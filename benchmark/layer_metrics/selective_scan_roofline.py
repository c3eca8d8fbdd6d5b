"""Kernels: share of its roofline the Mamba-1 selective scan reaches —
the least time the chip could take for ONE forward and ONE backward of
the recurrence per Mamba-1 layer per traced step
(``harness/flops_phi4flash.py``: the larger of FLOPs over peak and bytes
over HBM bandwidth, which here is the byte side) over ALL device time
under ``dsod.ssm.scan`` in those steps.  It names no kernel: fusing,
splitting or renaming the kernels cannot silence it, and a forward that
is run twice lowers it."""

from benchmark.harness import flops_lm, flops_phi4flash, scopes_ssm


def read(run):
    conf = run.get("config") or {}
    peaks = (run.get("device") or {}).get("peaks")
    if "mamba_dt_rank" not in conf or "seq_len" not in run or not peaks:
        return None
    took = scopes_ssm.scope_seconds(run, "ssm.scan")
    if not took:
        return None
    n = run["seq_len"]
    shape = (run["tokens_per_step"] // n, n, conf["mamba_d_inner"],
             conf["mamba_d_state"])
    least = sum(flops_lm.roofline_s(
        *flops_phi4flash.selective_scan_cost(k, *shape), peaks)
        for k in ("fwd", "bwd"))
    layers = conf["layer_kinds"].count("mamba")
    return 100.0 * least * layers * run["traced_steps"] / took
