"""Kernels: share of its roofline the causal grouped-KV flash attention
reaches — the least time the chip could take for the forward, dq and
dk/dv calls the trace shows (``dsod.kernel.flash_attention_causal``,
``..._dq``, ``..._dkv``) over the time they took; one call's operations
(the n(n+1)/2 pairs on or under the diagonal) and bytes from
``harness/flops_lm.py``."""

from benchmark.harness import flops_lm, scopes_lm


def read(run):
    conf = run.get("config") or {}
    if "num_key_value_heads" not in conf or "seq_len" not in run:
        return None
    n = run["seq_len"]
    shape = (run["tokens_per_step"] // n, conf["num_attention_heads"],
             conf["num_key_value_heads"], n, conf["head_dim"])
    return scopes_lm.kernel_roofline_pct(run, {
        "flash_attention_causal": flops_lm.flash_causal_cost("fwd", *shape),
        "flash_attention_causal_dq": flops_lm.flash_causal_cost("dq", *shape),
        "flash_attention_causal_dkv": flops_lm.flash_causal_cost(
            "dkv", *shape)})
