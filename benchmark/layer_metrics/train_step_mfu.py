"""Kernels and fusions inside the step: the FLOPs one step NEEDS
(forward + backward of the plain reference at the cell's global batch,
``flops_per_step`` in the workload file, counted by harness/flops.py)
over the device-busy time per step, the chip's bf16 peak and the chips."""


def read(run):
    tr, n = run.get("trace"), run.get("traced_steps")
    flops = (run.get("cell") or {}).get("flops_per_step")
    dev = run.get("device") or {}
    if not tr or not n or not flops or "peaks" not in dev:
        return None
    per_step_s = tr["busy_s"] / n
    peak = dev["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * flops / per_step_s / peak
