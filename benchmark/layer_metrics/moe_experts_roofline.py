"""Kernels: share of its roofline the grouped (per-expert) matrix
product reaches — the least time the chip could take for the calls the
trace shows (``dsod.kernel.grouped_matmul``: y = x w and dx = dy w^T;
``dsod.kernel.grouped_matmul_dw``) over the time they took.  One call's
operations and bytes come from ``harness/flops_lm.py`` at the expert
widths of the configuration and the routed pairs the trainer counted
(``moe_pairs_here_share`` x top-k x tokens); padding rows are not work."""

from benchmark.harness import flops_lm, scopes_lm


def read(run):
    conf, ticks = run.get("config") or {}, run.get("ticks") or []
    share = [t["moe_pairs_here_share"] for t in ticks
             if "moe_pairs_here_share" in t]
    if not share or "moe_intermediate_size" not in conf:
        return None
    rows = (sum(share) / len(share) * conf["num_experts_per_tok"]
            * run["tokens_per_step"])
    dims = (conf["hidden_size"], conf["moe_intermediate_size"],
            conf["num_experts"])
    return scopes_lm.kernel_roofline_pct(run, {
        "grouped_matmul": flops_lm.grouped_matmul_cost(rows, *dims),
        "grouped_matmul_dw": flops_lm.grouped_matmul_dw_cost(rows, *dims)})
