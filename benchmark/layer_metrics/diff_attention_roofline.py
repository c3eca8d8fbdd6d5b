"""Kernels: share of its roofline differential attention reaches — the
least time the chip could take for ONE forward and ONE backward of both
softmax maps of every pair, over the score entries the mask admits (the
512-key band in a window layer, the triangle in a full or a cross
layer), per attention layer per traced step
(``harness/flops_phi4flash.py``: the larger of FLOPs over peak and bytes
over HBM bandwidth) over ALL device time under ``dsod.attn.flash`` in
those steps.  It names no kernel: one call a layer or four, a fused or a
split backward cannot silence it, and a score map computed twice lowers
it."""

from benchmark.harness import flops_lm, flops_phi4flash, scopes_phi4flash


def read(run):
    conf = run.get("config") or {}
    peaks = (run.get("device") or {}).get("peaks")
    if "layer_kinds" not in conf or "seq_len" not in run or not peaks:
        return None
    took = scopes_phi4flash.scope_seconds(run, "flash", "attn.flash")
    if not took:
        return None
    n = run["seq_len"]
    shape = (run["tokens_per_step"] // n, conf["num_attention_heads"],
             conf["num_key_value_heads"], n, conf["head_dim"])
    least = 0.0
    for kind in conf["layer_kinds"]:
        if kind in ("window", "full", "cross"):
            window = conf["sliding_window"] if kind == "window" else 0
            least += sum(flops_lm.roofline_s(
                *flops_phi4flash.diff_attention_cost(k, *shape, window),
                peaks) for k in ("fwd", "bwd"))
    return 100.0 * least * run["traced_steps"] / took
