"""Kernels and XLA fusions: device self time per step under
``dsod.attn`` LESS the three latent-attention kernels — the
projections, the latent's norm, the rotation and whatever lays out the
per-head keys and values: what latent attention adds around its kernel.
Nothing where the trace shows no such kernel."""

from benchmark.harness import scopes_lm

KERNELS = ("flash_attention_mla", "flash_attention_mla_dq",
           "flash_attention_mla_dkv")


def read(run):
    red, n = scopes_lm.of_run(run), run.get("traced_steps")
    if not red or not n or "attn" not in red["scope_s"]:
        return None
    inside = [red["kernel_s"].get(k) for k in KERNELS]
    if None in inside:
        return None
    return (red["scope_s"]["attn"] - sum(inside)) * 1000.0 / n
