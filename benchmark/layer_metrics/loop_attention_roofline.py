"""Kernels: share of its roofline the looped stack's causal attention
core reaches — the least time the chip could take for ONE forward and
ONE fused backward a VISIT (``layers x passes`` of each a traced step;
``harness/flops_loop.py``: the larger of FLOPs over peak and bytes over
HBM bandwidth, here the FLOP side) over ALL device time under
``dsod.attn.core`` in those steps.  It names no kernel: fusing,
splitting or renaming the kernels cannot silence it, and a forward that
is run twice lowers it."""

from benchmark.harness import flops_lm, flops_loop, scopes_loop


def read(run):
    conf = run.get("config") or {}
    peaks = (run.get("device") or {}).get("peaks")
    if "total_ut_steps" not in conf or "seq_len" not in run or not peaks:
        return None
    took = scopes_loop.scope_seconds(run, "attn.core")
    if not took:
        return None
    n = run["seq_len"]
    shape = (run["tokens_per_step"] // n, conf["num_attention_heads"], n,
             conf["head_dim"])
    least = sum(flops_lm.roofline_s(*flops_loop.flash_causal_cost(k, *shape),
                                    peaks) for k in ("fwd", "bwd"))
    visits = conf["num_hidden_layers"] * conf["total_ut_steps"]
    return 100.0 * least * visits * run["traced_steps"] / took
