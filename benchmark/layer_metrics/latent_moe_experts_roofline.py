"""Kernels: share of its roofline the latent expert layers' routed
products reach — the least time the chip could take for ONE forward and
ONE backward of ``W2 relu(W1 z)^2`` over the pairs routed here, per
expert layer per traced step (``harness/flops_hybrid.py``: the larger of
FLOPs over peak and bytes over HBM bandwidth; the pairs from the
trainer's ``moe_pairs_here_share``) over ALL device time under
``dsod.moe.experts`` in those steps.  It names no kernel
(``ssm_scan_roofline``'s pattern): padding rows, a buffer multiplied
whole and a forward run twice lower it."""

from benchmark.harness import flops_hybrid, flops_lm, scopes_hybrid


def read(run):
    conf, ticks = run.get("config") or {}, run.get("ticks") or []
    peaks = (run.get("device") or {}).get("peaks")
    share = [t["moe_pairs_here_share"] for t in ticks
             if "moe_pairs_here_share" in t]
    if not share or "moe_latent_size" not in conf or not peaks:
        return None
    took = scopes_hybrid.scope_seconds(run, "moe.experts")
    if not took:
        return None
    rows = (sum(share) / len(share) * conf["num_experts_per_tok"]
            * run["tokens_per_step"])
    dims = (conf["moe_latent_size"], conf["moe_intermediate_size"],
            conf["n_routed_experts"])
    least = sum(flops_lm.roofline_s(
        *flops_hybrid.latent_experts_cost(k, rows, *dims), peaks)
        for k in ("fwd", "bwd"))
    layers = conf["hybrid_override_pattern"].count("E")
    return 100.0 * least * layers * run["traced_steps"] / took
