#!/usr/bin/env python
"""Analytic roofline for the flagship (MINet-R50 @320px) train step.

VERDICT r3 item 3: make the MFU push falsifiable BEFORE hardware.
This derives, from closed forms (no device needed):

  - per-op forward/backward FLOPs and ideal-fusion HBM bytes for every
    conv/BN/pool/resize/loss/optimizer op in the MINet-R50 train step,
  - a per-resolution-bucket roofline time  t >= max(F/peak, B/bw)
    on v5e (197 TFLOP/s dense bf16, 819 GB/s HBM),
  - predicted step time / throughput / MFU at b32/b64/b128,
    remat on/off, plain vs s2d stem, fast vs xla resize.

The measured side is ``tools/analyze_trace.py <profile-dir>``: the step
names its stages (``dsod.encoder`` ... ``dsod.update``), so a trace is
read by stage, not by the spatial size in an op's result shape.

Cross-checks:
  - ``--xla-check`` jits the REAL train step on CPU at b4 and compares
    XLA's cost-model FLOPs against this ledger (catches hand-math rot;
    agreement within ~10% expected — XLA counts a handful of fusions
    this ledger rolls into "elementwise").

Usage:
    python tools/roofline.py                       # predictions
    python tools/roofline.py --xla-check

Modeling assumptions (documented so disagreement is informative):
  - bf16 activations (2 B), f32 params/BN stats (4 B).
  - Ideal fusion: each ConvBNAct costs one read of its input and one
    write of its output; BN statistics reduce in the conv's epilogue
    (the trace's ``convert_reduce_fusion`` ops are exactly this) and
    the normalize+relu rides the consumer's read.  Real fusion is
    never better, often worse — predictions are LOWER bounds.
  - Backward per conv: dx-conv + dw-conv, each the fwd FLOP count;
    bytes: read upstream grad + saved input + weights, write grad-in
    + weight-grad.
  - ``--remat`` (the ``model.remat=true, policy=none`` config): the
    backward additionally re-runs the forward (its FLOPs and bytes are
    added to bwd) — remat trades HBM *capacity* for bandwidth+FLOPs,
    which is why b128 no-remat beat b64+remat on v5e.
  - SGD+momentum update: read param/momentum/grad, write param/
    momentum (f32) — 20 B/param, ~3 FLOPs/param.

Reference capability being modeled: the SURVEY §2.2 "Pallas where
profitable" contract — this table ranks which stages can repay a
custom kernel (HBM-bound, far from roofline) before any is written.
"""

from __future__ import annotations

import argparse
import math
import os
import sys
from dataclasses import dataclass, field

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_sod_project_tpu.utils.chips import (  # noqa: E402
    DCN_BW, chip_peaks)

# This is an OFFLINE model of one named chip, whatever machine prints
# it: the v5e the flagship was sized for.  Its peaks are the table's
# (utils/chips.py) — the same rows the live ledger divides by when it
# runs on that chip.  DCN is 16x slower than ICI, which is
# WHY the hierarchical reduction moves only 1/chips of the bytes
# across it.
MODELED_CHIP = "TPU v5 lite"
_PEAKS = chip_peaks(MODELED_CHIP)
PEAK_FLOPS = _PEAKS.flops_bf16
HBM_BW = _PEAKS.hbm_bw
ICI_BW = _PEAKS.ici_bw

A = 2  # activation bytes (bf16)
P = 4  # param / stat / f32 bytes


@dataclass
class Op:
    name: str
    res: int          # output spatial bucket (H of the square output)
    flops: float      # forward FLOPs
    bytes: float      # forward ideal-fusion HBM bytes
    bwd_flops: float = 0.0
    bwd_bytes: float = 0.0
    params: int = 0

    def scaled(self, k: float) -> "Op":
        return Op(self.name, self.res, self.flops * k, self.bytes * k,
                  self.bwd_flops * k, self.bwd_bytes * k, self.params)


def conv(name, b, h_in, cin, cout, k=3, stride=1, res_out=None,
         bn=True) -> Op:
    """ConvBNAct closed form (NHWC, square spatial)."""
    h_out = res_out if res_out is not None else h_in // stride
    f = 2.0 * b * h_out * h_out * cout * cin * k * k
    n_in = b * h_in * h_in * cin
    n_out = b * h_out * h_out * cout
    params = cin * cout * k * k + (4 * cout if bn else cout)
    fwd_bytes = A * (n_in + n_out) + P * params
    # dx + dw convs; read g_out twice (dx, dw) + saved input, write
    # g_in + dw; BN bwd rides the same fusions (stat grads are f32
    # scalars per channel — negligible traffic).
    bwd_f = 2.0 * f
    bwd_b = A * (2 * n_out + n_in + n_in) + P * 2 * params
    return Op(name, h_out, f, fwd_bytes, bwd_f, bwd_b, params)


def eltwise(name, b, h, c, reads=1, writes=1, res=None) -> Op:
    """Pure-VPU op: residual add, pool, fast resize, activation copy."""
    n = b * h * h * c
    return Op(name, res or h, 0.0, A * n * (reads + writes),
              0.0, A * n * (reads + writes))


# Decoder upsample/merge sites of the flagship (the fused-resample
# kernel's targets).  Populated by minet_r50_ledger as a side list so
# the per-arm ledger (fmt_fused_ledger) and the predictions price the
# SAME sites.  Each fused site replaces "read the fine map, write the
# fine map" with "read the COARSE map (a quarter of the bytes), write
# the fine map" — the merge operand reads are unchanged — so every
# site saves 0.75 * n_fine * A bytes of HBM traffic, fwd and bwd (the
# transposed-resample backward reads fine / writes coarse the same
# way).


def _up_site(ops, sites, name, b, res, c, reads=1, fused=False):
    """An upsample(+merge) decoder site: ``reads`` counts the fine-res
    operand reads on the XLA path (1 = bare upsample, 2 = upsample +
    add/concat merge).  ``fused=True`` prices the Pallas fused arm."""
    n = b * res * res * c
    plain = eltwise(name, b, res, c, reads=reads)
    if not fused:
        op = plain
    else:
        bytes_ = plain.bytes - 0.75 * A * n  # coarse read, fine write
        op = Op(name, res, 0.0, bytes_, 0.0, bytes_)
    ops.append(op)
    sites.append((name, res, plain.bytes - op.bytes))
    return op


def _conv_site(ops, sites, op: Op, b: int, cout: int,
               fused: bool = False, cat_elems: float = 0.0) -> Op:
    """A DECODER ConvBNAct site (the fused-conv kernel's targets).

    ``fused=True`` prices the ``model.conv_impl=fused`` arm: the
    BN-normalize+ReLU epilogue runs on the conv's VMEM tile instead of
    a second HBM round trip over the output map (the r4 reconciliation
    shows the fine buckets do NOT get this fusion for free — 160/80 at
    3.3x/2.1x off the ideal-fusion prediction), and a conv over a
    materialized channel concat (``cat_elems`` = elements of that
    concat) reads its parts directly, saving the concat's write+read.
    FLOPs are untouched by construction (asserted by
    ``fmt_fused_conv_ledger``); the saving is counted fwd and bwd (the
    backward's mask+scale epilogue fuses into the dx kernel's read the
    same way).  Conservative: backbone convs are NOT repriced even
    though the seam routes them too — only the decoder sites the
    roofline names are claimed.
    """
    n_out = float(b) * op.res * op.res * cout
    saved = (2.0 * A * n_out + 2.0 * A * cat_elems) if fused else 0.0
    if fused:
        op = Op(op.name, op.res, op.flops, op.bytes - saved,
                op.bwd_flops, op.bwd_bytes - saved, op.params)
    ops.append(op)
    sites.append((op.name, op.res, saved))
    return op


def minet_r50_ledger(b: int, hw: int = 320, s2d: bool = False,
                     resize: str = "fast",
                     fused_sites: list | None = None,
                     conv_arm: str = "xla",
                     conv_sites: list | None = None) -> list:
    """Every op in one MINet-R50 train step (fwd reference: the module
    graph in models/minet.py + models/backbones/resnet.py).

    ``resize``: 'fast'/'xla' as before; 'fused' prices the decoder
    upsample+merge sites as the Pallas fused-resample kernel
    (model.resample_impl=fused) — ``fused_sites`` (when passed a list)
    collects (site, res, bytes saved/step) for the per-arm ledger.
    ``conv_arm``: 'xla'/'fused' — 'fused' prices the decoder ConvBNAct
    sites as the Pallas fused conv-stage kernel
    (model.conv_impl=fused; see ``_conv_site``), ``conv_sites``
    collecting (site, res, bytes saved per direction).
    """
    ops: list[Op] = []
    sites = fused_sites if fused_sites is not None else []
    csites = conv_sites if conv_sites is not None else []
    fused = resize == "fused"
    cfused = conv_arm == "fused"
    r = hw // 2  # 160 for 320

    # ---- backbone stem ----------------------------------------------
    if s2d:
        # Same bytes (reads the same image, writes the same map); the
        # contraction runs 4x4x12=192 taps vs 7x7x3=147 — nominally
        # +31% FLOPs, but the MXU packs Cin=12 4x denser than Cin=3,
        # so wall-clock compute drops ~4x where the op is MXU-limited.
        st = conv("stem_s2d", b, hw // 2, 12, 64, k=4, stride=1)
        st.bytes = A * (b * hw * hw * 3 + b * r * r * 64) + P * st.params
        ops.append(st)
    else:
        ops.append(conv("stem7x7", b, hw, 3, 64, k=7, stride=2))
    ops.append(eltwise("maxpool", b, r, 64))  # 160 -> 80

    # ---- residual stages (torchvision bottleneck counts) ------------
    # (stage, blocks, width, out, res): R50 = 3/4/6/3.
    stages = [("res2", 3, 64, 256, hw // 4), ("res3", 4, 128, 512, hw // 8),
              ("res4", 6, 256, 1024, hw // 16), ("res5", 3, 512, 2048, hw // 32)]
    cin = 64
    for name, blocks, w, cout, res_ in stages:
        for i in range(blocks):
            stride = 2 if (i == 0 and name != "res2") else 1
            h_in = res_ * stride if stride == 2 else res_
            ops.append(conv(f"{name}.b{i}.c1", b, h_in, cin if i == 0 else cout,
                            w, k=1, res_out=h_in))
            ops.append(conv(f"{name}.b{i}.c2", b, h_in, w, w, k=3,
                            stride=stride))
            ops.append(conv(f"{name}.b{i}.c3", b, res_, w, cout, k=1))
            if i == 0:
                ops.append(conv(f"{name}.proj", b, h_in, cin, cout, k=1,
                                stride=stride, bn=True))
            ops.append(eltwise(f"{name}.b{i}.add", b, res_, cout, reads=2))
        cin = cout

    # ---- AIM (one per level; width 64) ------------------------------
    feats = [(hw // 2, 64), (hw // 4, 256), (hw // 8, 512),
             (hw // 16, 1024), (hw // 32, 2048)]
    for i, (res_, c) in enumerate(feats):
        n_parts = 1 + (i > 0) + (i < 4)
        _conv_site(ops, csites, conv(f"aim{i}.cur", b, res_, c, 64),
                   b, 64, fused=cfused)
        if i > 0:
            rb, cb = feats[i - 1]
            _conv_site(ops, csites, conv(f"aim{i}.below", b, rb, cb, 64),
                       b, 64, fused=cfused)
            ops.append(eltwise(f"aim{i}.down", b, rb, 64, res=res_))
        if i < 4:
            ra, ca = feats[i + 1]
            _conv_site(ops, csites, conv(f"aim{i}.above", b, ra, ca, 64),
                       b, 64, fused=cfused)
            _up_site(ops, sites, f"aim{i}.up", b, res_, 64, fused=fused)
        # The merge conv's input IS a materialized concat on the XLA
        # arm — the fused conv+concat kernel reads the parts directly.
        _conv_site(ops, csites,
                   conv(f"aim{i}.merge", b, res_, 64 * n_parts, 64),
                   b, 64, fused=cfused,
                   cat_elems=float(b) * res_ * res_ * 64 * n_parts)

    # ---- SIM decoder (one per level, coarsest first) ----------------
    for i, (res_, _) in enumerate(reversed(feats)):
        p = f"sim{4 - i}"
        _conv_site(ops, csites, conv(f"{p}.h", b, res_, 64, 64),
                   b, 64, fused=cfused)
        _conv_site(ops, csites, conv(f"{p}.l0", b, res_, 64, 32),
                   b, 32, fused=cfused)
        ops.append(eltwise(f"{p}.lpool", b, res_ // 2, 32))
        _conv_site(ops, csites, conv(f"{p}.l2h", b, res_ // 2, 32, 64),
                   b, 64, fused=cfused)
        _up_site(ops, sites, f"{p}.hup", b, res_, 64, fused=fused)
        _conv_site(ops, csites, conv(f"{p}.h2", b, res_, 64, 64),
                   b, 64, fused=cfused)
        _conv_site(ops, csites, conv(f"{p}.h2l", b, res_, 64, 32),
                   b, 32, fused=cfused)
        _conv_site(ops, csites, conv(f"{p}.l2", b, res_ // 2, 32, 32),
                   b, 32, fused=cfused)
        # SIM's merge input concat is the fused-RESAMPLE kernel's site
        # (resample_merge mode='concat') — claimed there, NOT here.
        _conv_site(ops, csites, conv(f"{p}.merge", b, res_, 96, 64),
                   b, 64, fused=cfused)
        if i < 4:  # decoder hop up to the next (finer) level
            _up_site(ops, sites, f"{p}.declift", b, res_ * 2, 64,
                     reads=2, fused=fused)

    # ---- head + full-res logit --------------------------------------
    _conv_site(ops, csites, conv("head.c1", b, hw // 2, 64, 32),
               b, 32, fused=cfused)
    ops.append(conv("head.logit", b, hw // 2, 32, 1, bn=False))
    if fused:  # the head's 2x logit upsample rides the kernel too
        _up_site(ops, sites, "head.resize", b, hw, 1, fused=True)
    else:
        k_resize = 3.0 if resize == "xla" else 1.0  # dot_general + 2 relayouts
        ops.append(eltwise("head.resize", b, hw, 1,
                           reads=k_resize, writes=k_resize))

    # ---- loss @ full res (BCE+IoU+SSIM+CEL, f32) --------------------
    n = b * hw * hw
    ops.append(Op("loss", hw, 40.0 * n, P * 8 * n, 40.0 * n, P * 8 * n))

    # ---- optimizer (SGD+momentum, f32) ------------------------------
    n_params = sum(o.params for o in ops)
    ops.append(Op("sgd", 0, 0.0, 0.0, 3.0 * n_params, 20.0 * n_params))
    return ops


def act_capacity_gb(b, hw=320, policy: str = "none") -> float:
    """Rough live-activation footprint for the backward pass (upper
    bound — XLA frees what it can reorder around).  ``policy``:
    'none' = no remat, every op output resident; 'dots' = the
    ``remat_policy=dots`` checkpoint policy, only conv/matmul outputs
    resident (elementwise recomputed).  Against v5e's 16 GB HBM this
    predicts where the batch curve hits the capacity wall."""
    ops = minet_r50_ledger(b, hw=hw)
    n_out = 0.0
    for o in ops:
        if policy == "dots" and not o.params:
            continue
        # bytes = A*(n_in+n_out)+P*params for convs; A*n*(r+w) for
        # eltwise — recover n_out as the write half.
        writes = (o.bytes - P * o.params) / 2 if o.params else o.bytes / 2
        n_out += max(writes, 0.0)
    return n_out / 1e9


def predict(b, remat=False, s2d=False, resize="fast", hw=320,
            remat_policy="none", conv="xla"):
    ops = minet_r50_ledger(b, hw=hw, s2d=s2d, resize=resize,
                           conv_arm=conv)
    rows = {}
    tot_f = tot_b = tot_t = 0.0
    for o in ops:
        f = o.flops + o.bwd_flops
        by = o.bytes + o.bwd_bytes
        if remat:
            if remat_policy == "dots":
                # conv outputs saved; only elementwise recomputed
                if not o.params:
                    f += o.flops
                    by += o.bytes
            else:  # policy=none: bwd re-runs the whole forward
                f += o.flops
                by += o.bytes
        t = max(f / PEAK_FLOPS, by / HBM_BW)
        r = rows.setdefault(o.res, [0.0, 0.0, 0.0])
        r[0] += f
        r[1] += by
        r[2] += t
        tot_f += f
        tot_b += by
        tot_t += t
    return rows, tot_f, tot_b, tot_t


def fmt_pred(b, remat=False, s2d=False, resize="fast",
             remat_policy="none", conv="xla"):
    rows, tf, tb, tt = predict(b, remat=remat, s2d=s2d, resize=resize,
                               remat_policy=remat_policy, conv=conv)
    tag = f"on[{remat_policy}]" if remat else "off"
    out = [f"## predicted  b{b}  remat={tag}  "
           f"stem={'s2d' if s2d else 'plain'}  resize={resize}  "
           f"conv={conv}",
           "| res | GFLOPs | HBM GB | roofline ms | bound |",
           "|---|---|---|---|---|"]
    for res in sorted(rows, reverse=True):
        f, by, t = rows[res]
        bound = "HBM" if by / HBM_BW > f / PEAK_FLOPS else "MXU"
        out.append(f"| {res} | {f / 1e9:.1f} | {by / 1e9:.2f} | "
                   f"{t * 1e3:.2f} | {bound} |")
    out.append(f"| **total** | **{tf / 1e9:.1f}** | **{tb / 1e9:.2f}** | "
               f"**{tt * 1e3:.2f}** | |")
    ideal = b / tt
    mfu = tf / tt / PEAK_FLOPS
    out.append(f"roofline-ideal: {ideal:.1f} img/s/chip, MFU {mfu:.0%} "
               f"(intensity {tf / tb:.0f} FLOPs/B vs ridge "
               f"{PEAK_FLOPS / HBM_BW:.0f})")
    policy = remat_policy if remat else "none"
    if not remat or remat_policy == "dots":
        cap = act_capacity_gb(b, policy=policy if remat else "none")
        label = "dots-saved" if remat else "no-remat live"
        out.append(f"{label} activations (upper bound): "
                   f"~{cap:.1f} GB vs 16 GB v5e HBM")
    return "\n".join(out)


def fmt_fused_ledger(b: int, hw: int = 320) -> str:
    """Per-site HBM ledger for the fused-resample arm
    (``model.resample_impl=fused``): what each decoder upsample/merge
    stage saves per step vs the fast XLA path, and the falsifiable
    total a chip A/B has to meet (not measured on a chip).

    Conservative by construction: only sites the base ledger already
    prices are counted (SIM's concat-merge upsample is idealized away
    there and so claims no savings here), and the relayout copies the
    layout-stable interleave removes (tools/hlo_guard.py) are NOT
    priced — both make the prediction a lower bound.
    """
    sites: list = []
    minet_r50_ledger(b, hw=hw, resize="fused", fused_sites=sites)
    out = [f"## fused-resample ledger  b{b}@{hw}px  "
           f"(model.resample_impl=fused vs fast)",
           "| site | res | HBM bytes saved/step | ms saved (fwd+bwd) |",
           "|---|---|---|---|"]
    tot = 0.0
    for name, res, saved in sites:
        tot += saved
        out.append(f"| {name} | {res} | {saved / 1e6:.2f} MB | "
                   f"{2 * saved / HBM_BW * 1e3:.3f} |")
    out.append(f"| **total** | | **{tot / 1e6:.2f} MB** | "
               f"**{2 * tot / HBM_BW * 1e3:.3f}** |")
    _, _, _, t_fast = predict(b, hw=hw, resize="fast")
    _, _, _, t_fused = predict(b, hw=hw, resize="fused")
    out.append(f"prediction: step roofline {t_fast * 1e3:.2f} -> "
               f"{t_fused * 1e3:.2f} ms "
               f"({(1 - t_fused / t_fast):.1%} of the ideal step) — "
               f"the A/B leg must beat noise on THIS number to flip "
               f"any default")
    return "\n".join(out)


def fmt_fused_conv_ledger(b: int, hw: int = 320) -> str:
    """Per-site HBM ledger for the fused conv-stage arm
    (``model.conv_impl=fused``): what each decoder ConvBNAct saves per
    step vs the XLA arm, and the falsifiable total a chip A/B has to
    meet (not measured on a chip).

    Assumptions on record (the ledger's honesty contract): the XLA arm
    is charged one extra read+write of each decoder conv's OUTPUT map
    (the BN-normalize+ReLU epilogue the r4 trace reconciliation shows
    is NOT riding the conv fusion at the fine buckets), and one extra
    write+read of each materialized pre-conv CONCAT (AIM merges; SIM's
    merge concat belongs to the fused-resample ledger and is NOT
    double-counted).  Backbone convs route the same seam but claim
    nothing here — decoder sites only, so the total is a floor the
    prof_conv trace leg can only raise.  FLOPs invariance between the
    arms is asserted, not assumed.
    """
    csites: list = []
    ops_f = minet_r50_ledger(b, hw=hw, conv_arm="fused",
                             conv_sites=csites)
    ops_x = minet_r50_ledger(b, hw=hw)
    fx = sum(o.flops + o.bwd_flops for o in ops_x)
    ff = sum(o.flops + o.bwd_flops for o in ops_f)
    if fx != ff:
        raise AssertionError(
            f"fused-conv arm changed ledger FLOPs: {fx} != {ff} — the "
            "kernel computes the SAME convolution; a bytes-only arm "
            "must not touch the FLOP column")
    out = [f"## fused-conv ledger  b{b}@{hw}px  "
           f"(model.conv_impl=fused vs xla)",
           f"FLOPs invariant across arms: {fx / 1e9:.1f} GFLOPs both",
           "| site | res | HBM bytes saved/step | ms saved (fwd+bwd) |",
           "|---|---|---|---|"]
    tot = 0.0
    for name, res, saved in csites:
        if saved <= 0:
            continue
        tot += saved
        out.append(f"| {name} | {res} | {2 * saved / 1e6:.2f} MB | "
                   f"{2 * saved / HBM_BW * 1e3:.3f} |")
    out.append(f"| **total** | | **{2 * tot / 1e6:.2f} MB** | "
               f"**{2 * tot / HBM_BW * 1e3:.3f}** |")
    _, _, _, t_x = predict(b, hw=hw)
    _, _, _, t_f = predict(b, hw=hw, conv="fused")
    out.append(f"prediction: step roofline {t_x * 1e3:.2f} -> "
               f"{t_f * 1e3:.2f} ms "
               f"({(1 - t_f / t_x):.1%} of the ideal step) — the "
               f"ledger floor; the real target is the fine buckets' "
               f"3.3x/2.1x conv-fusion overhead, which only the "
               f"prof_conv trace leg can price.  The r14 A/B must "
               f"beat noise on THIS number to flip any default")
    return "\n".join(out)


def fmt_comm_ledger(b: int, n_dp: int = 8, bucket_mb: float = 25.0,
                    compression: str = "none", hosts: int = 1) -> str:
    """Per-step gradient-communication ledger for the flagship
    (ROADMAP item 4, round 18): the REAL param tree's leaves (abstract
    init — no arrays allocated) partitioned into the rules engine's
    backward-ordered buckets (parallel/rules.py::grad_buckets), each
    priced as a ring allreduce over ``n_dp`` replicas — wire bytes
    ``2(n-1)/n × payload`` at ``ICI_BW`` — plus the structural overlap
    estimate (every bucket except the last overlaps remaining backward
    compute) and the ZeRO per-device HBM saving.

    ``hosts > 1`` prices the hierarchical two-level schedule
    (parallel/rules.py::_hier_psum) instead: per bucket, intra-host
    reduce-scatter ((c−1)/c × payload at ICI, c = chips/host) →
    inter-host all-reduce (2(h−1)/h × payload/c at DCN — each chip
    owns 1/c of the bucket, so only that slice crosses the slow leg)
    → intra-host all-gather ((c−1)/c × payload at ICI).

    ``compression`` scales the wire bytes: bf16 halves them; int8_ef
    prices the ACHIEVABLE 1 B/elem (wire_scale 0.25) even though the
    current XLA transport psums int32 — the ledger documents the wire
    format's information content, the transport honesty note below
    keeps the gap visible.  The live twin of this table is the
    ``dsod_capacity_comm_*`` surface (utils/capacity.py::record_comm);
    the numbers are predictions, not measured on a chip.
    """
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.models import build_model
    from distributed_sod_project_tpu.parallel.rules import grad_buckets

    if hosts > 1 and n_dp % hosts:
        raise SystemExit(f"--hosts {hosts} must divide --n-dp {n_dp}")
    cfg = get_config("minet_r50_dp")
    model = build_model(cfg.model)
    # Param shapes are input-size independent for the conv zoo; a 64px
    # abstract init keeps this instant and allocation-free.
    variables = jax.eval_shape(
        lambda k, img: model.init(k, img, None, train=False),
        jax.random.key(0), jnp.zeros((1, 64, 64, 3), jnp.float32))
    leaves = jax.tree_util.tree_leaves(variables["params"])
    shapes = [(x.shape, x.dtype) for x in leaves]
    sizes = [int(math.prod(s or (1,))) * 4 for s, _ in shapes]  # f32
    wire_scale = {"none": 1.0, "bf16": 0.5, "int8_ef": 0.25}[compression]
    buckets = grad_buckets(shapes, int(bucket_mb * 2 ** 20))
    chips = n_dp // hosts if hosts > 1 else n_dp
    out = [f"## comm ledger  b{b}  n_dp={n_dp}  hosts={hosts}  "
           f"bucket={bucket_mb}MB  compression={compression}",
           f"param leaves: {len(leaves)}  grad bytes/replica: "
           f"{sum(sizes) / 1e6:.1f} MB f32"]
    tot_ici = tot_dcn = 0.0
    if hosts > 1:
        out += ["| bucket | leaves | payload MB | ICI wire MB "
                "(rs+ag) | ICI ms | DCN wire MB (ar) | DCN ms |",
                "|---|---|---|---|---|---|---|"]
        ici_frac = (chips - 1) / chips           # rs and ag, each
        dcn_ring = 2.0 * (hosts - 1) / hosts
        for i, bucket in enumerate(buckets):
            payload = sum(sizes[j] for j in bucket) * wire_scale
            ici = 2.0 * ici_frac * payload       # rs + ag
            dcn = dcn_ring * payload / chips     # 1/chips of the bytes
            tot_ici += ici
            tot_dcn += dcn
            out.append(
                f"| {i} | {len(bucket)} | {payload / 1e6:.2f} | "
                f"{ici / 1e6:.2f} | {ici / ICI_BW * 1e3:.3f} | "
                f"{dcn / 1e6:.2f} | {dcn / DCN_BW * 1e3:.3f} |")
        out.append(
            f"| **total** | **{len(leaves)}** | "
            f"**{sum(sizes) * wire_scale / 1e6:.2f}** | "
            f"**{tot_ici / 1e6:.2f}** | "
            f"**{tot_ici / ICI_BW * 1e3:.3f}** | "
            f"**{tot_dcn / 1e6:.2f}** | "
            f"**{tot_dcn / DCN_BW * 1e3:.3f}** |")
        flat_dcn = 2.0 * (n_dp - 1) / n_dp * sum(sizes) * wire_scale
        out.append(
            f"flat ring at DCN for comparison: "
            f"{flat_dcn / 1e6:.2f} MB ~{flat_dcn / DCN_BW * 1e3:.3f} "
            f"ms — the hierarchy moves {1.0 / chips:.0%} of the bytes "
            f"over the slow leg")
    else:
        out += ["| bucket | leaves | payload MB | wire MB (ring) | "
                "ICI ms |",
                "|---|---|---|---|---|"]
        ring = 2.0 * (n_dp - 1) / n_dp
        for i, bucket in enumerate(buckets):
            payload = sum(sizes[j] for j in bucket) * wire_scale
            wire = ring * payload
            tot_ici += wire
            out.append(f"| {i} | {len(bucket)} | {payload / 1e6:.2f} | "
                       f"{wire / 1e6:.2f} | "
                       f"{wire / ICI_BW * 1e3:.3f} |")
        out.append(f"| **total** | **{len(leaves)}** | "
                   f"**{sum(sizes) * wire_scale / 1e6:.2f}** | "
                   f"**{tot_ici / 1e6:.2f}** | "
                   f"**{tot_ici / ICI_BW * 1e3:.3f}** |")
    last = sum(sizes[j] for j in buckets[-1]) if buckets else 0
    overlap = (1.0 - last / max(sum(sizes), 1)
               if len(buckets) > 1 else 0.0)
    _, _, _, t_step = predict(b)
    wire_time = tot_ici / ICI_BW + tot_dcn / DCN_BW
    exposed = wire_time * (1.0 - overlap)
    out.append(
        f"overlap estimate (structural): {overlap:.0%} of wire time "
        f"hides under backward compute; exposed comm "
        f"~{exposed * 1e3:.3f} ms vs roofline step "
        f"{t_step * 1e3:.2f} ms")
    if compression == "int8_ef":
        out.append(
            "int8_ef transport honesty: XLA's collective carries the "
            "quantized values as int32 today (4 B/elem on the wire); "
            "the 0.25 wire scale above prices the 1 B/elem the int8 "
            "payload CONTAINS — the gap is transport packing, not "
            "information, and closes with a packed-collective lowering")
    # ZeRO: moments (momentum = 1x params f32) + EMA when on shard
    # over n_dp — each replica keeps 1/n of the buffer bytes.
    opt_bytes = sum(sizes)  # SGD momentum: one f32 slot per param
    saved = opt_bytes * (1.0 - 1.0 / n_dp)
    out.append(
        f"ZeRO-1 (parallel.zero=1): optimizer moments "
        f"{opt_bytes / 1e6:.1f} MB/replica -> "
        f"{opt_bytes / n_dp / 1e6:.1f} MB sharded; "
        f"{saved / 1e6:.1f} MB HBM freed per device "
        f"(+ the same again per EMA tree when ema_decay>0)")
    return "\n".join(out)


def xla_check(b: int = 4, hw: int = 64):
    """Compare the ledger against XLA's cost model on the REAL step —
    and cross-check the LIVE capacity ledger (utils/capacity.py) on the
    SAME compiled executable: the dsod_capacity_* surface must report
    exactly what cost_analysis reports here (within 1%), or live MFU
    and this offline roofline have diverged."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from distributed_sod_project_tpu.configs import (apply_overrides,
                                                     get_config)
    from distributed_sod_project_tpu.models import build_model
    from distributed_sod_project_tpu.parallel.engine import (
        prepare_train_step)
    from distributed_sod_project_tpu.parallel.mesh import (
        batch_sharding, make_mesh)
    from distributed_sod_project_tpu.train import (build_optimizer,
                                                   create_train_state)

    cfg = get_config("minet_r50_dp")
    cfg = apply_overrides(cfg, [f"data.image_size={hw},{hw}",
                                "model.compute_dtype=float32",
                                f"global_batch_size={b}"])
    mesh = make_mesh(cfg.mesh)
    model = build_model(cfg.model)
    tx, sched = build_optimizer(cfg.optim, 100)
    rng = np.random.RandomState(0)
    batch = {"image": rng.randn(b, hw, hw, 3).astype(np.float32),
             "mask": (rng.rand(b, hw, hw, 1) > 0.5).astype(np.float32)}
    state = create_train_state(jax.random.key(0), model, tx, batch)
    state, step, _plan = prepare_train_step(
        cfg, model, tx, mesh, sched, state)
    dev_batch = jax.device_put(batch, batch_sharding(mesh))
    compiled = step.lower(state, dev_batch).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    xla_flops = float(cost.get("flops", 0.0))
    ops = minet_r50_ledger(b, hw=hw)
    ours = sum(o.flops + o.bwd_flops for o in ops)
    print(f"XLA cost model (b{b}@{hw}px, full train step): "
          f"{xla_flops / 1e9:.2f} GFLOPs")
    print(f"ledger                                      : "
          f"{ours / 1e9:.2f} GFLOPs  "
          f"(ratio {ours / xla_flops:.3f})")
    # Live-ledger cross-check on the SAME executable: what the
    # capacity_ledger knob would export for this program.
    from distributed_sod_project_tpu.utils.capacity import CapacityLedger

    cap = CapacityLedger(device_memory=False)
    rec = cap.record(f"train/{hw}x{hw}/k1", compiled)
    live_ratio = rec["flops"] / xla_flops if xla_flops else 0.0
    print(f"capacity ledger (live dsod_capacity_* source): "
          f"{rec['flops'] / 1e9:.2f} GFLOPs  "
          f"(ratio {live_ratio:.4f} — must be within 1%)")
    if not 0.99 <= live_ratio <= 1.01:
        print("capacity ledger DISAGREES with cost_analysis on the "
              "same executable")
        return 0.0  # outside every acceptance band below
    return ours / xla_flops


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batch", type=int, default=None,
                   help="single batch size (default: the b32/64/128 sweep)")
    p.add_argument("--remat", action="store_true")
    p.add_argument("--remat-policy", choices=["none", "dots"],
                   default="none",
                   help="with --remat: the model.remat_policy knob — "
                        "'none' re-runs the whole forward in bwd, "
                        "'dots' keeps conv outputs (capacity cost) "
                        "and recomputes only elementwise")
    p.add_argument("--s2d", action="store_true")
    p.add_argument("--resize", choices=["fast", "xla", "fused"],
                   default="fast",
                   help="price the resample arm: fast (slice/lerp), "
                        "xla (generic jax.image.resize), fused (the "
                        "Pallas resample-merge kernel; also prints the "
                        "per-site bytes-saved ledger)")
    p.add_argument("--conv", choices=["xla", "fused"], default="xla",
                   help="price the conv-block arm: xla (nn.Conv + "
                        "BatchNorm), fused (the Pallas conv-stage "
                        "kernel, model.conv_impl=fused; also prints "
                        "the per-decoder-site bytes-saved ledger and "
                        "asserts FLOPs invariance vs the xla arm)")
    p.add_argument("--xla-check", action="store_true")
    p.add_argument("--comm", action="store_true",
                   help="print the gradient-communication ledger "
                        "(round 18): real param-tree buckets priced as "
                        "ring allreduces at ICI bandwidth, overlap "
                        "estimate, ZeRO HBM saving")
    p.add_argument("--n-dp", type=int, default=8,
                   help="with --comm: data-parallel degree the ring "
                        "is priced for")
    p.add_argument("--bucket-mb", type=float, default=25.0,
                   help="with --comm: parallel.comm_bucket_mb arm")
    p.add_argument("--compression",
                   choices=["none", "bf16", "int8_ef"],
                   default="none",
                   help="with --comm: parallel.grad_compression arm "
                        "(int8_ef prices the achievable 1 B/elem wire)")
    p.add_argument("--hosts", type=int, default=1,
                   help="with --comm: mesh.data_hosts — price the "
                        "hierarchical intra-host rs / inter-host ar / "
                        "intra-host ag schedule with the ICI and DCN "
                        "legs separated")
    args = p.parse_args(argv)

    if args.xla_check:
        ratio = xla_check()
        return 0 if 0.8 < ratio < 1.25 else 1

    batches = [args.batch] if args.batch else [32, 64, 128]
    if args.comm:
        for b in batches:
            print(fmt_comm_ledger(b, n_dp=args.n_dp,
                                  bucket_mb=args.bucket_mb,
                                  compression=args.compression,
                                  hosts=args.hosts))
            print()
        return 0
    for b in batches:
        print(fmt_pred(b, remat=args.remat, s2d=args.s2d,
                       resize=args.resize,
                       remat_policy=args.remat_policy, conv=args.conv))
        print()
        if args.resize == "fused":
            print(fmt_fused_ledger(b))
            print()
        if args.conv == "fused":
            print(fmt_fused_conv_ledger(b))
            print()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
