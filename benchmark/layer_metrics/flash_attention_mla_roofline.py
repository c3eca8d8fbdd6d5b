"""Kernels: share of its roofline the causal latent-attention flash
kernel reaches — the least time the chip could take for the forward, dq
and dk/dv calls the trace shows (``dsod.kernel.flash_attention_mla``,
``..._dq``, ``..._dkv``) over the time they took; one call's operations
and bytes from ``harness/flops_mla.py``."""

from benchmark.harness import flops_mla, scopes_lm


def read(run):
    conf = run.get("config") or {}
    if "kv_lora_rank" not in conf or "seq_len" not in run:
        return None
    n = run["seq_len"]
    shape = (run["tokens_per_step"] // n, conf["num_attention_heads"], n,
             conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
             conf["v_head_dim"])
    return scopes_lm.kernel_roofline_pct(run, {
        "flash_attention_mla": flops_mla.flash_mla_cost("fwd", *shape),
        "flash_attention_mla_dq": flops_mla.flash_mla_cost("dq", *shape),
        "flash_attention_mla_dkv": flops_mla.flash_mla_cost("dkv", *shape)})
