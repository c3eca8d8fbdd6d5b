"""Experiment configuration system.

Capability parity: SURVEY.md §2 C13 (per-experiment config dicts +
dataset path registry in the reference's ``config/``).  Re-designed as
typed, frozen dataclasses so a config can be hashed into a jit cache key
and serialized into a checkpoint for exact-resume.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Input pipeline configuration (SURVEY.md §2 C7)."""

    dataset: str = "synthetic"  # synthetic | duts | nju2k | nlpr
    backend: str = "host"  # host (C++/PIL loader) | tfdata | grain
    root: Optional[str] = None  # directory with <name>-Image/ and <name>-Mask/
    val_root: Optional[str] = None  # held-out set for in-training eval
    image_size: Tuple[int, int] = (320, 320)  # H, W — static for XLA
    use_depth: bool = False  # RGB-D datasets carry a depth channel
    hflip: bool = True
    # ColorJitter-style photometric aug: brightness/saturation/contrast
    # factors each drawn in [1-s, 1+s] per sample (0 disables; image
    # only, identical across backends via data/augment.py draws).
    color_jitter: float = 0.0
    rotate_degrees: float = 0.0  # ±deg random rotation (MINet-style
    #   aug); identical per-index draws on every backend
    normalize_mean: Tuple[float, float, float] = (0.485, 0.456, 0.406)
    normalize_std: Tuple[float, float, float] = (0.229, 0.224, 0.225)
    num_workers: int = 4  # host backend: parallel batch-BUILD threads
    #   (each assembles+augments a whole batch; decode may additionally
    #   go to processes, see decode_procs)
    prefetch_batches: int = 2
    # Host-backend data-plane knobs (docs/PERFORMANCE.md "Host data
    # plane").  lookahead: batches built ahead of the consumer (in
    # flight across the build workers).
    lookahead: int = 2
    # >0: recycle this many preallocated batch buffers instead of
    # allocating per step (zero-copy assembly).  CONTRACT: a yielded
    # batch's arrays are overwritten after 2 further batches have been
    # yielded — consumers that hold batches longer must copy.  The
    # train/bench paths consume immediately; keep 0 (fresh arrays)
    # when iterating by hand.
    ring_buffers: int = 0
    # >0: decode samples in this many worker PROCESSES writing into
    # shared-memory ring slots — sidesteps the GIL for the PIL decode
    # path when native/ is unbuilt (implies a ring).  0 = in-thread.
    decode_procs: int = 0
    # Raw-decoded-sample cache (the tf.data cache() analogue): -1 =
    # auto (cache every sample when the whole dataset fits
    # cache_budget_mb of host RAM), 0 = off, N = cache at most N
    # samples.  Epochs after the first cost a row copy per sample
    # instead of a decode; augmentation still runs per epoch, so the
    # (seed, epoch, idx) draw contract is untouched.
    cache_decoded: int = -1
    cache_budget_mb: int = 1024
    transfer_dtype: str = "float32"  # bfloat16 halves H2D image bytes
    synthetic_size: int = 256  # virtual dataset length when dataset=synthetic
    # Multi-scale training (MINet-style): the cycle of square train
    # sizes, e.g. (256, 320, 384).  Empty = single-scale at image_size.
    # Each size is one statically-shaped compiled step (XLA-friendly);
    # the resize rides the device, not the input pipeline.  Use
    # multiples of 32 (backbone strides + fused-loss lane alignment).
    multiscale: Tuple[int, ...] = ()
    # >0: re-run the cheap non-finite batch check every N batches (the
    # first batch is always fully validated); 0 keeps the once-only
    # behavior.  Catches mid-run data corruption before it becomes an
    # unexplained divergence (utils/checks.py).
    validate_every: int = 0
    # >0: tolerate this many corrupt samples per run — each is skipped
    # (deterministic next-index substitution) and counted into the
    # `data_skipped` metric instead of killing the epoch; budget
    # exhaustion raises.  0 = fail on the first corrupt sample.
    # See resilience/dataguard.py and docs/RESILIENCE.md.
    skip_budget: int = 0
    # dataset="packed_tokens" (data/tokens.py; the token model's
    # source): sequences of seq_len ids cut from packed documents of
    # log-normal length joined by an end-of-document id, the ids
    # Zipf-distributed over the first `vocab` of the vocabulary;
    # synthetic_size is the number of sequences.
    seq_len: int = 8192
    vocab: int = 16384


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """Shape of a token model (model.name=lfm2 | kimi | granite | ouro |
    nemotron_h | phi4flash, models/lfm2.py, models/kimi.py,
    models/granite.py, models/ouro.py, models/nemotron_h.py,
    models/phi4flash.py).
    The defaults
    are LFM2-8B-A1B's published widths (LiquidAI, config.json) and the
    share one chip holds in `lfm2_8b_a1b_ep4`: the layers kept,
    `experts_held` of `experts` from `first_expert` on, `vocab` rows of
    the 65,536.  `kimi_vl_a3b_ep8`, `granite_4_0_h_micro_pp4` and
    `ouro_2_6b_pp6` set every field they read (configs/experiments.py),
    and so does `nemotron_3_super_tp8_ep64`, whose `heads`, `kv_heads`
    and `ssm_heads` are the chip's share of each mixer's heads and whose
    `layer_types` are mamba | attention | moe, ONE mixer a layer.
    The state-space sizes and the four multipliers are read by `granite`
    alone, whose attention layer is position-free (it reads no
    `rope_theta`); the passes and the exit term by `ouro` alone, which
    reads `layer_types` for its length only (every layer is attention).
    `phi4_mini_flash_pp5` (`phi4flash`) reads `layer_types` as mamba |
    window | full | gmu | cross, `ssm_heads` as the Mamba-1 channels (a
    decay of its own each: `ssm_head_dim` 1), and the three fields at
    the end."""

    vocab: int = 16384
    hidden: int = 2048
    layer_types: Tuple[str, ...] = (  # lfm2: conv | attention, per layer kept
        "conv", "attention", "conv", "conv", "conv")
    ffn_types: Tuple[str, ...] = (  # dense | moe, per layer kept
        "dense", "moe", "moe", "moe", "moe")
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    dense_width: int = 7168
    expert_width: int = 1792
    experts: int = 32  # the router's width
    experts_held: int = 8
    first_expert: int = 0
    top_k: int = 4
    conv_kernel: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    topk_eps: float = 1e-6  # in the chosen scores' normaliser
    # The balancing rule of the bias-routed family: after each step
    # expert_bias += rate * sign(mean pairs - pairs sent), per expert
    # layer.  0: the bias stays the buffer it was given (lfm2).
    bias_update_rate: float = 0.0
    # Latent attention (kimi, every layer; it reads neither layer_types
    # nor kv_heads): head_dim is the key's width, of which
    # rope_dim columns are the shared rotary key's; values are v_dim
    # wide; keys and values come from a latent of kv_rank columns.
    rope_dim: int = 0
    v_dim: int = 0
    kv_rank: int = 0
    shared_experts: int = 0  # each expert_width wide, over every token
    # The selective state-space layer (granite; layer_types: mamba |
    # attention): ssm_heads heads of ssm_head_dim columns, each carrying
    # a float32 state of ssm_head_dim x ssm_state; B and C are ssm_state
    # wide and shared by all heads (one group); the depthwise causal
    # conv over x | B | C has ssm_conv taps and a bias; ssm_chunk is the
    # chunk of the scan (pallas/ssd_scan.py), which has to divide the
    # sequence.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # The family's four multipliers (granite): on the embedding, on each
    # residual branch, on the attention scores (0: 1/sqrt(head_dim)) and
    # the divisor of the logits.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # The looped stack (ouro): the layers kept are run ut_steps times on
    # the same weights; exit_beta weighs the entropy of the exit
    # distribution in the loss (losses/token_ce.py).
    ut_steps: int = 1
    exit_beta: float = 0.0
    # The latent expert layer (nemotron_h): the routed experts are
    # two-matrix relu(.)^2 feed-forwards of expert_width columns that
    # read and write a latent of latent_width columns, between a down-
    # and an up-projection; ONE shared expert of shared_width columns
    # reads the hidden state itself.
    latent_width: int = 0
    shared_width: int = 0
    # The decoder-hybrid-decoder stage (phi4flash): a window layer's
    # query sees its last `window` keys alone, its own among them;
    # ssm_dt_rank is the width of the Mamba-1 step size's low-rank
    # projection; first_layer is the PUBLISHED index of the first layer
    # kept (differential attention's starting lambda reads it).
    window: int = 0
    ssm_dt_rank: int = 0
    first_layer: int = 0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Model zoo selection (SURVEY.md §2 C5/C6)."""

    name: str = "minet"  # minet | hdfnet | u2net | basnet | swin_sod
    backbone: str = "vgg16"  # vgg16 | resnet50 | swin_t | none (u2net is self-contained)
    backbone_bn: bool = True  # False → classic torchvision VGG16 layout
    #   (the tree ImageNet weight porting targets; see backbones/vgg.py)
    out_stride: int = 1  # saliency logits at input resolution
    sync_bn: bool = True  # cross-replica BatchNorm stats over the data axis
    bn_momentum: float = 0.9
    compute_dtype: str = "bfloat16"  # MXU-native; params stay float32
    param_dtype: str = "float32"
    remat: bool = False  # jax.checkpoint the forward (train step)
    # What remat SAVES (only read when remat=true): "none" recomputes
    # everything (min memory, +~1/3 FLOPs); "dots" keeps matmul/conv
    # outputs and recomputes elementwise (the usual best-MFU
    # compromise); "dots_no_batch" keeps only batch-free dots (weights'
    # contractions).
    remat_policy: str = "none"  # none | dots | dots_no_batch
    # Attention core for the transformer zoo member (vit_sod only):
    # "xla" materializes the score matrix, "flash" runs the Pallas
    # tiled-softmax kernel (pallas/flash_attention.py) — required for
    # high-resolution single-chip work where N² scores exceed HBM.
    attn_impl: str = "xla"  # xla | flash
    # Dynamic-local-filter core (hdfnet only): "xla" = im2col+einsum,
    # "pallas" = fused VMEM shifted-FMA kernel
    # (pallas/dynamic_filter.py) — no ksize²-wide patch tensor in HBM.
    dlf_impl: str = "xla"  # xla | pallas
    # Decoder resample strategy (minet / hdfnet / gatenet / u2net —
    # the four decoder users of the upsample+merge idiom):
    #   fast  — slice/lerp fast paths, layout-stable interleave
    #           (default; all-XLA, jax.image.resize-exact)
    #   xla   — the generic jax.image.resize (the arm tests compare against)
    #   fused — Pallas fused resample-merge (pallas/fused_resample.py):
    #           upsample + add/concat as ONE VMEM pass per image.
    #           Knob-gated pending a hardware A/B win (the pre-committed
    #           non-XLA-default rule; not measured on a chip).
    resample_impl: str = "fast"  # fast | xla | fused
    # Conv-block execution strategy (minet / hdfnet / gatenet / u2net —
    # every ConvBNAct in the four decoder families AND their VGG/ResNet
    # backbones routes through the one models/layers.py seam):
    #   xla   — nn.Conv + nn.BatchNorm (default; the lowered program is
    #           byte-identical to the pre-knob tree)
    #   fused — Pallas fused conv-stage kernel (pallas/fused_conv.py):
    #           conv + inference-mode BN + ReLU as ONE VMEM pass per
    #           image; list inputs convolve as their channel concat
    #           without materializing it (decoder heads); train-mode
    #           BN sites keep flax's BatchNorm after the fused conv;
    #           out-of-envelope sites (stride>1, even kernels, VMEM
    #           budget) fall back per-site.  Composes with the serve
    #           precision arms (int8/fp8 weights dequantize in-kernel).
    #           Knob-gated pending a hardware A/B win (the pre-committed
    #           non-XLA-default rule; not measured on a chip).
    conv_impl: str = "xla"  # xla | fused
    pretrained: Optional[str] = None  # .npz from tools/port_torch_weights.py
    # The token model's shape (model.name=lfm2 only; its remat is per layer
    # and keeps the values models/lfm2.py::REMAT_SAVES names, no policy field).
    lm: LMConfig = dataclasses.field(default_factory=LMConfig)
    # Structural deep supervision for models where aux heads are
    # optional add-ons (vit_sod's mid-depth head).  U²-Net/BASNet side
    # outputs are integral to their architectures and ignore this.
    # LossConfig.deep_supervision separately gates which returned
    # outputs the loss consumes.
    deep_supervision: bool = True


@dataclasses.dataclass(frozen=True)
class LossConfig:
    """Loss weighting (SURVEY.md §2 C8)."""

    bce: float = 1.0
    iou: float = 1.0
    ssim: float = 1.0
    cel: float = 0.0  # MINet's consistency-enhanced loss
    ssim_window: int = 11
    deep_supervision: bool = True  # sum loss over every side output
    fused_kernel: bool = False  # route through the Pallas fused loss


@dataclasses.dataclass(frozen=True)
class OptimConfig:
    """Optimizer + schedule (SURVEY.md §2 C9)."""

    optimizer: str = "sgd"  # sgd | adamw | lars (large-batch)
    lr: float = 0.005
    momentum: float = 0.9
    weight_decay: float = 5e-4
    nesterov: bool = True
    schedule: str = "poly"  # poly | cosine | constant
    poly_power: float = 0.9
    warmup_steps: int = 0
    grad_clip_norm: float = 0.0  # 0 disables
    # Layer-wise LR decay for transformer fine-tuning (BEiT-style):
    # heads at full LR, encoder block i at decay^(n_blocks+1-(i+1)),
    # the patch/pos embedding deepest.  1.0 disables (from-scratch).
    layer_decay: float = 1.0
    accum_steps: int = 1  # >1: optax.MultiSteps gradient accumulation
    ema_decay: float = 0.0  # >0: track an EMA of params; eval uses it
    # >0: skip updates whose gradients are non-finite (bad batch / bf16
    # overflow) instead of poisoning the params; the train loop raises
    # once this many CONSECUTIVE skips accumulate (a persistent
    # divergence, not a glitch), checked at the logging cadence.  A bad
    # update is NEVER applied.
    skip_nonfinite: int = 0
    # ZeRO-1-style cross-replica weight-update sharding (PAPERS.md:
    # arXiv 2004.13336): optimizer/EMA buffers shard over the data axis,
    # grads reduce-scatter into a 1/N-sized update, params all-gather.
    # Routes training through the GSPMD step (needs model.sync_bn=False;
    # BN stats are global-batch there by construction).
    zero1: bool = False


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device mesh layout (SURVEY.md §2.3).

    The load-bearing axis is ``data`` (DP parity with the reference's
    DDP/NCCL).  ``model`` shards attention heads / wide dense layers for
    the Swin path; ``seq`` is the ring-attention sequence-parallel axis.
    Axis size ``-1`` means "all remaining devices".
    """

    data: int = -1
    model: int = 1
    seq: int = 1
    # Sequence-parallel strategy over the ``seq`` axis: 'ring' rotates
    # K/V blocks (ppermute; any head count), 'ulysses' redistributes
    # heads with two all-to-alls (needs model heads % seq == 0; lower
    # collective latency, full-sequence tiles for the flash kernel).
    sp_strategy: str = "ring"  # ring | ulysses
    # Two-level data-axis hierarchy for pod-scale meshes: the ``data``
    # axis factors as (data_hosts, chips_per_host) with consecutive
    # device ids on the same host (the make_mesh layout guarantees
    # this).  >1 routes each gradient bucket's psum through intra-host
    # reduce-scatter -> inter-host all-reduce on 1/chips_per_host of
    # the bytes -> intra-host all-gather, so the slow DCN hop carries
    # only a 1/chips_per_host segment (docs/MULTIHOST.md "Hierarchical
    # collectives").  Must divide the data axis size.
    data_hosts: int = 1


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """The unified partition-rule sharding engine (parallel/rules.py +
    parallel/engine.py; docs/MULTIHOST.md "Rule presets").

    ``engine='rules'`` routes training through ONE rule-driven step
    builder: DP, TP, SP, and FSDP are partition-rule presets on the
    same traced root.  The rules engine shipped bitwise-proven against
    the legacy builders in round 17 and the default flipped in round 18
    per the bit-identical flip rule; the legacy builders are deleted —
    'rules' is the only engine.
    """

    engine: str = "rules"  # rules (the legacy builders were removed)
    # Preset selection: 'auto' derives the preset from the mesh (seq>1
    # -> sp, model>1 or zero -> tp/gspmd, else dp).  'fsdp' is the only
    # value that cannot be derived: params themselves shard over
    # ``data`` (fsdp_fallback_rule picks each leaf's largest divisible
    # dim), the partitioner all-gathers them just-in-time per layer in
    # forward/backward and reduce-scatters grads — full ZeRO-3-style
    # sharding as pure config.  Requires model.sync_bn=false (GSPMD
    # path, no named axis) and mesh.model == mesh.seq == 1.
    preset: str = "auto"  # auto | dp | tp | sp | fsdp
    # ZeRO-style cross-replica weight-update sharding (PAPERS.md: arXiv
    # 2004.13336), the rules-engine generalization of optim.zero1:
    #   0 — off (replicated optimizer state)
    #   1 — optimizer moments + EMA shard over ``data``; grads reduce-
    #       scatter into 1/N-sized updates, params all-gather
    #   2 — additionally pins the gradient tree to the sharded layout
    #       (with_sharding_constraint), so the full replicated gradient
    #       tree is never materialized between reduce and update
    # Routes through the GSPMD preset (needs model.sync_bn=false, same
    # contract as optim.zero1).  Per-device HBM saving is reported via
    # the capacity ledger (dsod_capacity_comm_zero_hbm_saved_bytes).
    zero: int = 0
    # Bucketed, backward-ordered gradient allreduce (DP preset only):
    # grads partition into size-targeted buckets — latest-layer grads
    # (first available in the backward pass) reduce first — and each
    # bucket is its own ``lax.psum``, so early buckets' communication
    # can overlap remaining backward compute.  0 = one monolithic
    # reduce (the legacy program).  Per-element arithmetic is identical
    # (psum/n exactly as lax.pmean computes it) — bitwise-asserted vs
    # monolithic in tests/test_sharding_rules.py.  No-op on the GSPMD
    # preset (the partitioner schedules its own collectives).
    comm_bucket_mb: float = 25.0
    # Gradient compression arm for the bucketed allreduce: 'bf16' casts
    # each bucket to bfloat16 for the wire and back to f32 after —
    # halves gradient comm bytes, NOT bitwise.  'int8_ef' symmetrically
    # quantizes each bucket to int8 against a shared global scale
    # (lax.pmax of per-replica amax, so the integer psum is exact) and
    # carries the quantization error in a persistent error-feedback
    # residual in the train state (sharded by the ZeRO specs), added
    # back into the next step's buffer — 1 B/elem achievable wire,
    # quality-gated exactly like bf16.  Both gated the precision_gate
    # way: tools/grad_comm_gate.py keeps a checked-in delta baseline
    # (tools/grad_comm_baseline.json).
    grad_compression: str = "none"  # none | bf16 | int8_ef
    # Raise on params the rule table does not match (instead of the
    # replicate-by-default fallback) — debugging aid when authoring
    # rules for a new backbone.
    rules_strict: bool = False


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Online serving (serve/ subsystem — docs/SERVING.md).

    The engine coalesces arbitrary-time, arbitrary-size requests into
    the fixed-shape compiled programs evaluation already uses: one AOT-
    compiled forward per (resolution bucket, batch bucket), requests
    grouped per resolution bucket and padded up to the smallest batch
    bucket that fits.  All knobs here are request-plane policy; nothing
    below changes a compiled program's math.
    """

    host: str = "127.0.0.1"
    port: int = 8080  # tools/serve.py --port 0 binds an ephemeral port
    # Static batch shapes compiled at startup (ascending).  A dispatch
    # takes the smallest bucket >= the coalesced group, zero-padding
    # the remainder (eval/inference.py::pad_to_batch).
    batch_buckets: Tuple[int, ...] = (1, 4, 8)
    # Static square resolutions compiled at startup.  Empty = one
    # bucket at max(data.image_size).  A request resizes to the
    # smallest bucket >= its longest side (largest bucket otherwise);
    # degraded mode forces the smallest.
    resolution_buckets: Tuple[int, ...] = ()
    # Precision arms (serve/precision.py; docs/SERVING.md "Precision
    # arms").  Every arm in precision_arms gets its own cast-on-load
    # weight view and its own AOT-compiled program per (res, batch)
    # bucket at startup; `precision` picks the arm requests serve at by
    # default (X-Precision overrides per request, within the enabled
    # set).  Arms: f32 (identity — bitwise the offline eval path),
    # bf16 (weights cast to bfloat16: half the weight HBM), int8 / fp8
    # (8-bit weight-only per-channel quantization, dequantized inside
    # the compiled program; fp8 only where jaxlib has float8_e4m3fn).
    # The degraded ladder steps DOWN through the enabled arms before it
    # touches resolution; quality deltas per arm are measured and
    # budgeted by tools/precision_gate.py.
    precision: str = "f32"
    precision_arms: Tuple[str, ...] = ("f32", "bf16")
    # How long the oldest queued request may wait for co-riders before
    # its batch dispatches anyway (the latency/occupancy trade).
    max_wait_ms: float = 5.0
    max_queue: int = 64  # admission bound; beyond it requests shed (429)
    max_inflight: int = 2  # device batches dispatched but not fetched
    post_workers: int = 2  # host pool for original-resolution resize-back
    # Default per-request deadline (0 = none; X-SLO-MS overrides).  A
    # request that can no longer meet its deadline — now + the res
    # bucket's EWMA device time exceeds it — is shed BEFORE the forward.
    slo_ms: float = 0.0
    request_timeout_s: float = 30.0  # HTTP handler wait on the future
    tta: bool = False  # horizontal-flip TTA (2x forward; off when degraded)
    # >0: watch the checkpoint directory and hot-swap weights between
    # dispatches when a newer VALID step appears (restore-latest-VALID
    # via the integrity layer; swaps are atomic w.r.t. /predict).
    reload_poll_s: float = 0.0
    # Dispatch-loop heartbeat deadline feeding /healthz (resilience/
    # watchdog.py).  A wedged device dispatch stops the beat; /healthz
    # flips 503 so the fronting LB drains this replica.  0 = off.
    watchdog_deadline_s: float = 60.0
    # Degraded-mode hysteresis LADDER: each rung engages after queue
    # depth has stayed >= degraded_high * max_queue for
    # degraded_engage_s, and unwinds (one rung at a time, reverse
    # order) after it has stayed <= degraded_low * max_queue for
    # degraded_disengage_s.  Rungs step PRECISION down through the
    # enabled precision_arms first (TTA off from rung 1), and only the
    # final rung forces the smallest resolution bucket; responses
    # self-report the rung (X-Degraded: <level>).
    degraded_high: float = 0.75
    degraded_low: float = 0.25
    degraded_engage_s: float = 2.0
    degraded_disengage_s: float = 5.0
    # End-to-end tracing (utils/tracing.py; docs/OBSERVABILITY.md).
    # trace_sample is the fraction of requests whose span timelines are
    # recorded (deterministic in the request id, so a router and its
    # replicas trace the SAME requests); 0 disables tracing entirely —
    # /metrics output is then byte-identical to the pre-tracing
    # rendering.  The X-Timing response header rides every 200
    # regardless (it is computed from numbers the engine already
    # tracks).  trace_capacity bounds the in-memory ring of completed
    # traces; trace_worst_n pins the slowest N traces per
    # (model, res bucket) as exemplars that survive the ring.
    trace_sample: float = 0.01
    trace_capacity: int = 256
    trace_worst_n: int = 4
    # -- model-health quality/drift monitors (serve/quality.py;
    #    docs/OBSERVABILITY.md "Model health").  All OFF by default:
    #    with quality_monitor=false the request hot path pays nothing
    #    and /metrics is byte-identical to the monitor-less rendering.
    # Master switch: per-request output statistics (foreground
    # fraction, mean confidence, boundary entropy) + input/output
    # drift histograms with PSI vs a checked-in reference
    # (tools/quality_reference.json), under model=/arm= labels.
    quality_monitor: bool = False
    # Fraction of non-f32 responses re-scored on the f32 reference arm
    # (shadow scoring): live arm-vs-f32 disagreement gauges turn the
    # offline tools/precision_gate.py budget into a continuous online
    # check.  Deterministic counter sampling; requires "f32" among
    # precision_arms; shadow forwards run on a bounded side lane and
    # DROP (counted) rather than queue behind live traffic.
    quality_shadow_sample: float = 0.0
    # Reference-histogram file for PSI drift ("" = the checked-in
    # tools/quality_reference.json when it has an entry for this
    # model; no reference = drift gauges idle, stats still collected).
    quality_reference: str = ""
    # Default alert budgets (utils/alerts.py; wired when the monitor
    # is on): shadow mean-abs-disagreement budget, PSI drift bound,
    # and the hysteresis dwells of the built-in quality rules.
    quality_shadow_budget: float = 0.02
    quality_psi_threshold: float = 0.25
    # Minimum online-histogram observations before a PSI verdict is
    # rendered at all: one request is not drift evidence, and an
    # unwarmed histogram scored against a reference reads as a huge
    # (false) shift.  Below the floor the drift gauges stay absent
    # and quality_psi_max reports 0 (no verdict).
    quality_psi_min_count: int = 64
    quality_alert_for_s: float = 5.0
    quality_alert_clear_s: float = 10.0
    # Extra alert rules, colon DSL ("name:signal:kind:value[:for[:clear]]"
    # — comma-free so --set tuple coercion passes them through); they
    # join the built-in quality rules when the monitor is on.
    alert_rules: Tuple[str, ...] = ()
    # -- capacity & SLO observability (utils/capacity.py, utils/slo.py;
    #    docs/OBSERVABILITY.md "Capacity & SLO").  Both OFF by default:
    #    /metrics stays byte-identical to the ledger-less rendering.
    # Live per-compiled-program cost ledger: at AOT warmup every cached
    # executable's cost_analysis()/memory_analysis() is recorded, and
    # the per-(res,batch,arm) EWMA device time turns it into live
    # MFU / roofline-utilization / HBM gauges (dsod_capacity_*), plus a
    # device-vs-queue-vs-host stage-share attribution gauge derived
    # from the PR-9 stage splits — the scale-out-vs-futile signal.
    capacity_ledger: bool = False
    # Declarative SLO objectives, colon DSL (comma-free):
    #   name:scope:kind:goal:window_s[:latency_ms]
    #   scope = all | model=NAME | tenant=NAME
    #   kind  = availability (good = served ok)
    #         | latency      (good = served ok within latency_ms)
    # e.g. "avail:all:availability:0.999:3600"
    #      "fast:all:latency:0.95:3600:250"
    # Empty = off.  Non-empty arms sliding-window error-budget
    # accounting + multi-window burn rates (dsod_slo_* families, the
    # /slo endpoint) fed by the server's own terminal outcomes;
    # burn-rate/budget rules ride the alert engine and degrade
    # /healthz on budget exhaustion.
    slo_objectives: Tuple[str, ...] = ()
    # Burn-rate alert threshold: the rule fires when BOTH the fast
    # (window/12) and slow (full-window) burn rates exceed it (the
    # multi-window AND — min of the two windows is the signal).
    slo_burn_threshold: float = 10.0
    # Hysteresis dwells of the built-in SLO rules (alert-engine
    # semantics: breach for_s before firing, clear clear_s to resolve).
    slo_alert_for_s: float = 5.0
    slo_alert_clear_s: float = 60.0
    # -- black-box flight recorder (utils/flightrecorder.py;
    #    docs/OBSERVABILITY.md "Flight recorder & incidents").  OFF by
    #    default: no thread, no files, /metrics byte-identical.  On,
    #    a background thread samples this engine's telemetry registry
    #    every recorder_sample_s into a bounded on-disk ring of
    #    append-only JSONL segments (recorder_dir REQUIRED — loud
    #    ValueError otherwise), records typed events (hot reloads,
    #    degraded-ladder moves, alert transitions, dispatch errors),
    #    and on a trigger (alert firing, watchdog trip, SIGTERM,
    #    dispatch crash) snapshots the last recorder_bundle_window_s of
    #    the ring + live sections (/debug/traces, /alerts, /slo,
    #    capacity, resolved config) into one gzip incident bundle under
    #    <recorder_dir>/incidents/ — debounced by recorder_debounce_s
    #    so a flapping alert cannot bundle-storm.  The ring survives
    #    SIGKILL (torn-tail-tolerant reader; tools/fleet_chaos.py
    #    proves the replay) and tools/incident.py post-mortems it.
    flight_recorder: bool = False
    recorder_dir: str = ""
    recorder_sample_s: float = 1.0
    recorder_segment_kb: int = 256
    recorder_keep_segments: int = 16
    recorder_bundle_window_s: float = 300.0
    recorder_debounce_s: float = 30.0


@dataclasses.dataclass(frozen=True)
class FleetTenantConfig:
    """One tenant class for multi-tenant fleet admission (serve/router.py).

    ``priority`` orders tenants into shed classes under backlog: when a
    target replica's queue is past a class's backlog fraction, that
    class sheds at the ROUTER (429) while higher classes still admit —
    the fraction for a class is ``(rank+1) / n_classes`` over the
    distinct priorities in the fleet (the highest class never priority-
    sheds before the engine's own queue bound).  ``rate_rps``/``burst``
    arm a token-bucket budget (requests/s sustained, ``burst`` capacity
    — defaults to ``rate_rps`` when 0); ``rate_rps=0`` means unlimited.
    Budgets are enforced at the router door, BEFORE a request ever
    reaches an engine queue.
    """

    name: str = "default"
    priority: int = 0
    rate_rps: float = 0.0
    burst: float = 0.0


@dataclasses.dataclass(frozen=True)
class FleetModelConfig:
    """One fleet member: a routing key plus exactly one backend source.

    - ``config`` (registered experiment name) → in-process engine with
      randomly-initialised weights (smoke/bench posture);
    - ``ckpt_dir`` → in-process engine serving that checkpoint
      (``config`` optionally overrides the sidecar config name);
    - ``url`` → remote serve process proxied as-is (its own engine owns
      admission and accounting; the router adds tenancy + aggregation).

    ``overrides`` are dotted ``section.field=value`` strings applied to
    the member's ExperimentConfig (in-process members only).
    """

    name: str = ""
    config: Optional[str] = None
    ckpt_dir: Optional[str] = None
    url: Optional[str] = None
    # N remote replicas under ONE routing key (scale-out + failover):
    # each URL becomes a RemoteBackend replica "name#i"; the router
    # spreads requests round-robin and fails over between them
    # (serve/failover.py).  Exclusive of url/config/ckpt_dir.
    urls: Tuple[str, ...] = ()
    overrides: Tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Multi-model, multi-tenant serving fleet (serve/fleet.py +
    serve/router.py; docs/SERVING.md "Fleet").

    A router tier fronting N engine replicas: requests name a model
    (``X-Model`` header / ``model=`` query field) and a tenant
    (``X-Tenant``); the router resolves the replica (404 on unknown),
    enforces the tenant's token-bucket budget and priority class, then
    forwards.  Co-resident in-process engines share one device through
    a single interleaved dispatch loop (round-robin over per-model
    batchers, so a hot model cannot starve a cold one).
    """

    models: Tuple[FleetModelConfig, ...] = ()
    tenants: Tuple[FleetTenantConfig, ...] = ()
    # Tenant class used when a request carries no X-Tenant header (or
    # an unknown one, unless strict_tenants).  Auto-registered with
    # unlimited budget + the lowest configured priority when absent
    # from ``tenants``.
    default_tenant: str = "default"
    # True: an unknown X-Tenant is rejected 403 at the door (never
    # counted — the request does not enter the fleet accounting).
    # False (default): unknown tenants ride the default tenant's class.
    strict_tenants: bool = False
    host: str = "127.0.0.1"
    port: int = 8080
    # Router-side wait on an in-process engine future / remote response.
    request_timeout_s: float = 30.0
    # Seconds between remote-replica /healthz probes.  Probing runs on
    # a BACKGROUND thread per remote (serve/fleet.py HealthProber) —
    # the request path and the /healthz//metrics handlers only ever
    # read the cached verdict, never pay a connect timeout inline.
    health_poll_s: float = 2.0

    # -- fault tolerance (serve/failover.py; docs/SERVING.md
    #    "Failure semantics") ------------------------------------------
    # Total dispatch attempts per request (1 = no retry).  Retries fire
    # on transport failures (connect refused/reset, timeout) and remote
    # 5xx, preferring a DIFFERENT healthy replica (failover) before
    # re-trying the same one.  Every retry is charged against the
    # request's residual X-SLO-MS budget — the router forwards the
    # residual, not the original, on every attempt.
    retry_max_attempts: int = 2
    # Capped exponential backoff between attempts (base, cap; ms).
    retry_backoff_ms: float = 10.0
    retry_backoff_max_ms: float = 250.0
    # Tail-latency hedge: after this many ms without a first answer,
    # fire the SAME request at a second healthy replica; first response
    # wins, the loser is abandoned and counted.  0 = off; -1 = auto
    # (hedge at the router's observed per-model p95).  Remote replicas
    # only — an in-process engine shares the device with its siblings,
    # so a hedge there would just queue behind itself.
    hedge_ms: float = 0.0
    # Circuit breaker per replica: this many CONSECUTIVE failures open
    # it (dispatches route around the replica without paying its
    # timeout); after breaker_reset_s one half-open probe request is
    # let through and its outcome decides re-admission vs re-open.
    breaker_failures: int = 3
    breaker_reset_s: float = 5.0
    # Router-tier tracing (utils/tracing.py; docs/OBSERVABILITY.md):
    # the router mints X-Request-ID, records a child span per dispatch
    # attempt (replica + breaker state; retries and hedges share the
    # request's one trace id), and serves sampled + worst-N exemplar
    # traces at /debug/traces.  Sampling is deterministic in the
    # request id, so in-process engines (serve.trace_sample) and
    # remote replicas at the same rate trace the same requests.
    trace_sample: float = 0.01
    trace_capacity: int = 256
    trace_worst_n: int = 4

    # -- capacity & SLO observability (utils/slo.py, serve/prober.py;
    #    docs/OBSERVABILITY.md "Capacity & SLO") -----------------------
    # Router-tier SLO objectives (same colon DSL as
    # serve.slo_objectives; scope model=/tenant= keys match the fleet's
    # routing keys and tenant classes).  Fed by the ROUTER'S OWN exact
    # terminal book — every counted submission feeds its matching
    # objectives with its one terminal outcome, so /slo reconciles
    # against /stats' fleet identity.  Empty = off (byte-identical
    # /metrics).
    slo_objectives: Tuple[str, ...] = ()
    slo_burn_threshold: float = 10.0
    slo_alert_for_s: float = 5.0
    slo_alert_clear_s: float = 60.0
    # Synthetic canary prober (serve/prober.py): > 0 starts a
    # background thread pushing one known-ground-truth synthetic probe
    # through the FULL router→engine path every this-many seconds,
    # round-robin over the fleet's models, under the reserved
    # prober_tenant (auto-registered at the LOWEST priority so probes
    # shed first under overload — and the prober itself DROPS, counted,
    # rather than queue when its previous probe is still in flight).
    # Probe latency/quality/availability export as dsod_probe_*;
    # because probes ride the real door they also feed the router book
    # and any model-scoped SLO — outages fire burn-rate alerts even at
    # zero live traffic.  0 = off.
    prober_interval_s: float = 0.0
    prober_tenant: str = "_probe"
    # Square pixel size of the synthetic probe images (resized into the
    # target model's resolution buckets like any request).
    prober_px: int = 64
    # Per-probe HTTP timeout.
    prober_timeout_s: float = 10.0
    # Router-tier flight recorder (utils/flightrecorder.py; same knob
    # block as serve.flight_recorder).  Samples the ROUTER'S OWN book
    # (tenant/outcome counters, replica up + breaker gauges) — never a
    # per-second scrape of every replica — and triggers an incident
    # bundle on replica transport failures, SLO burn firings, and
    # SIGTERM.  The router /incidents endpoint aggregates its own
    # bundles with every replica's (in-process read direct, remotes
    # scraped bounded).
    flight_recorder: bool = False
    recorder_dir: str = ""
    recorder_sample_s: float = 1.0
    recorder_segment_kb: int = 256
    recorder_keep_segments: int = 16
    recorder_bundle_window_s: float = 300.0
    recorder_debounce_s: float = 30.0

    # -- closed-loop fleet controller (serve/controller.py;
    #    docs/SERVING.md "Fleet control plane") -----------------------
    # False (default): no controller thread, no dsod_ctrl_* families —
    # /metrics stays byte-identical.  True: a sensor-driven control
    # loop heals dead replicas, scales the fleet out on queue-bound SLO
    # burn (and REFUSES, recording why, when the stage-share
    # attribution says the bottleneck is host- or device-side — more
    # replicas on the same device would not help), and scales in with
    # drain-then-retire, never killing in-flight work.
    controller: bool = False
    # Seconds between controller policy evaluations (one tick).
    ctrl_interval_s: float = 5.0
    # Healing/scaling floor per replica set; 0 = the group's configured
    # member count (heal back to what the config promised).
    ctrl_target_replicas: int = 0
    # Scale-out ceiling per replica set (supervised members included).
    ctrl_max_replicas: int = 4
    # Scale-out trigger: SLO burn at or past this rate...
    ctrl_scale_out_burn: float = 2.0
    # ...AND the replicas' queue stage share at or past this fraction
    # (queue-bound — the one bottleneck another replica absorbs).
    ctrl_queue_share: float = 0.5
    # Scale-in trigger: burn at or below this rate while the set holds
    # more members than the target.
    ctrl_scale_in_burn: float = 0.1
    # Hysteresis: a trigger must hold this long before the controller
    # acts (fake-clock-provable, the degraded-ladder dwell idiom)...
    ctrl_dwell_s: float = 10.0
    # ...and after any scale action the policy holds off this long.
    ctrl_cooldown_s: float = 30.0
    # Drain-then-retire grace: a draining replica leaves routing
    # immediately; its process is retired (SIGTERM first — the
    # replica's own clean drain) only after this many seconds.
    ctrl_drain_grace_s: float = 5.0
    # Replica spawn argv template for scale-out/heal, with ``{port}``
    # and ``{port_file}`` placeholders (e.g. the tools/serve.py
    # single-engine command line).  Empty = the controller can
    # drain/retire and refuse, but never spawn.
    ctrl_spawn_cmd: Tuple[str, ...] = ()
    # Seconds a spawned replica gets to bind its port and turn healthy
    # before the supervisor books the attempt as a crash-loop failure.
    ctrl_spawn_deadline_s: float = 150.0
    # Crash-loop backoff between supervised spawn attempts (base,
    # doubled per consecutive failure, capped).
    ctrl_backoff_s: float = 2.0
    ctrl_backoff_max_s: float = 60.0
    # True: arm a PreemptionGuard (utils/observability.py) inside the
    # controller — a SIGTERM-style preemption notice drains supervised
    # replicas instead of letting them die with work in flight, and
    # scale-out is refused while the notice stands.
    ctrl_spot_guard: bool = False

    # -- progressive checkpoint delivery (serve/rollout.py;
    #    docs/SERVING.md "Fleet control plane") -----------------------
    # Non-empty: watch this checkpoint directory and deliver new steps
    # progressively — canary ONE replica, score it, then promote
    # fleet-wide or auto-roll-back and denylist the step — instead of
    # every replica hot-reloading at once.  Empty (default): off,
    # byte-identical /metrics.
    rollout_ckpt_dir: str = ""
    # Replica set the rollout drives (default: the fleet's single
    # model; required when the fleet serves several).
    rollout_model: str = ""
    # Seconds between checkpoint-directory polls / state-machine ticks.
    rollout_poll_s: float = 5.0
    # Seconds the canary bakes on live + probe traffic before the
    # verdict is taken.
    rollout_bake_s: float = 10.0
    # Ground-truth canary probes per verdict (serve/prober.py probe
    # set), sent DIRECTLY to the canary replica and to a stable
    # baseline replica for the relative comparison.
    rollout_probes: int = 6
    rollout_probe_px: int = 64
    # Verdict fails when canary probe MAE exceeds the baseline
    # replica's by more than this...
    rollout_mae_degrade: float = 0.1
    # ...or exceeds this absolute ceiling (0 = no absolute ceiling)...
    rollout_mae_max: float = 0.0
    # ...or the canary's drift PSI (serve/quality.py, when the quality
    # monitors are armed) exceeds this (0 = PSI not consulted)...
    rollout_psi_max: float = 0.0
    # ...or fewer than this fraction of canary probes answered.
    rollout_min_avail: float = 1.0

    # -- router-door response cache (serve/cache.py; docs/SERVING.md
    #    "Router cache") ----------------------------------------------
    # Byte budget for the content-addressed response LRU (entries are
    # keyed on payload hash × model × precision arm × loaded
    # checkpoint step).  0 (default): cache fully off — no object, no
    # threads, byte-identical /metrics.
    cache_bytes: int = 0
    # Fold concurrent identical payloads into ONE engine submit with N
    # responses (each booked cache_hit).  Only meaningful with
    # cache_bytes > 0.
    cache_coalesce: bool = True
    # Arm the perceptual-hash near-dup arm: resize-normalized hits for
    # perceptually identical payloads.  Quality-gated offline by
    # tools/cache_gate.py; arm the online shadow gate via
    # cache_shadow_sample.
    cache_near_dup: bool = False
    # Near-dup match budget in Hamming bits over the 256-bit phash
    # (0 = exact-phash matches only; ~16 tolerates typical re-encode/
    # resize perturbations — see tools/cache_baseline.json).
    cache_near_dup_hamming: int = 0
    # Shadow-score every Nth near-dup hit against a fresh engine
    # forward, off the request path (0 = no shadow scoring).
    cache_shadow_sample: int = 0

    # -- streaming-video sessions (serve/streams.py; docs/SERVING.md
    #    "Streaming") ----------------------------------------------------
    # Maximum concurrent per-client stream sessions (the X-Stream-ID
    # header opens one).  0 (default): streaming fully off — no session
    # table, no dsod_stream_* families, byte-identical /metrics, and
    # the batcher never sees a stream key.  A NEW stream past the cap
    # sheds loudly at the door (429 kind=stream_budget) — existing
    # sessions are never silently evicted to make room.
    stream_sessions: int = 0
    # Idle TTL: a session untouched this long is evicted (LRU order)
    # and counted into dsod_stream_expired_total.
    stream_ttl_s: float = 30.0
    # Temporal-coherence fast path: when a frame's 256-bit phash is
    # within this many Hamming bits of the stream's previous frame,
    # serve the previous mask WITHOUT a forward (terminal class
    # `stream_reuse`).  0 = fast path off (sessions still track state
    # and pin replicas).  Quality-gated offline by tools/stream_gate.py
    # (checked-in tools/stream_baseline.json) and online by the cache
    # shadow monitors.
    stream_reuse_hamming: int = 0
    # EMA mask blend for flicker damping: on a FULL forward for a
    # stream that has a previous mask of the same shape, the response
    # becomes blend*prev + (1-blend)*new.  0 (default) = off — full
    # forwards are bitwise the engine's own answer.
    stream_ema_blend: float = 0.0


def fleet_config_from_dict(d: Dict) -> FleetConfig:
    """Build + validate a FleetConfig from its JSON dict (the
    ``tools/serve.py --fleet-config`` file format).  Loud ValueError on
    an unknown key, a duplicate model/tenant name, or a member without
    exactly one backend source."""
    d = dict(d)
    models = []
    for md in d.pop("models", []):
        md = dict(md)
        unknown = set(md) - {f.name for f in
                             dataclasses.fields(FleetModelConfig)}
        if unknown:
            raise ValueError(
                f"unknown fleet model key(s) {sorted(unknown)} in {md!r}")
        if "overrides" in md:
            md["overrides"] = tuple(md["overrides"])
        if "urls" in md:
            md["urls"] = tuple(md["urls"])
        models.append(FleetModelConfig(**md))
    tenants = []
    for td in d.pop("tenants", []):
        td = dict(td)
        unknown = set(td) - {f.name for f in
                             dataclasses.fields(FleetTenantConfig)}
        if unknown:
            raise ValueError(
                f"unknown fleet tenant key(s) {sorted(unknown)} in {td!r}")
        tenants.append(FleetTenantConfig(**td))
    known = {f.name for f in dataclasses.fields(FleetConfig)} \
        - {"models", "tenants"}
    unknown = set(d) - known
    if unknown:
        raise ValueError(f"unknown fleet config key(s) {sorted(unknown)}")
    if "ctrl_spawn_cmd" in d:
        d["ctrl_spawn_cmd"] = tuple(d["ctrl_spawn_cmd"])
    fc = FleetConfig(models=tuple(models), tenants=tuple(tenants), **d)
    return validate_fleet_config(fc)


def validate_fleet_config(fc: FleetConfig) -> FleetConfig:
    """Invariants a fleet must satisfy before a single engine warms:
    at least one model, unique routing keys, exactly one backend source
    per member, unique tenant names.  Returns ``fc`` (with the default
    tenant auto-registered when missing)."""
    if not fc.models:
        raise ValueError("fleet config needs at least one model")
    seen = set()
    for m in fc.models:
        if not m.name:
            raise ValueError(f"fleet model {m!r} needs a name (routing key)")
        if m.name in seen:
            raise ValueError(f"duplicate fleet model name {m.name!r}")
        seen.add(m.name)
        if m.url and (m.config or m.ckpt_dir or m.overrides):
            raise ValueError(
                f"fleet model {m.name!r}: url is exclusive of "
                "config/ckpt_dir/overrides (the remote process owns its "
                "own config)")
        if m.urls and (m.url or m.config or m.ckpt_dir or m.overrides):
            raise ValueError(
                f"fleet model {m.name!r}: urls (replica set) is "
                "exclusive of url/config/ckpt_dir/overrides (each "
                "remote replica owns its own config)")
        if m.urls and len(set(m.urls)) != len(m.urls):
            raise ValueError(
                f"fleet model {m.name!r}: duplicate replica url in "
                f"{m.urls}")
        if not m.url and not m.urls and not m.ckpt_dir and not m.config:
            raise ValueError(
                f"fleet model {m.name!r} needs one of config / ckpt_dir "
                "/ url / urls")
    tseen = set()
    for t in fc.tenants:
        if not t.name:
            raise ValueError(f"fleet tenant {t!r} needs a name")
        if t.name in tseen:
            raise ValueError(f"duplicate fleet tenant name {t.name!r}")
        tseen.add(t.name)
        if t.rate_rps < 0 or t.burst < 0:
            raise ValueError(
                f"fleet tenant {t.name!r}: rate_rps/burst must be >= 0")
    if fc.retry_max_attempts < 1:
        raise ValueError(
            f"fleet retry_max_attempts must be >= 1 (1 = no retry), "
            f"got {fc.retry_max_attempts}")
    if fc.retry_backoff_ms < 0 or fc.retry_backoff_max_ms < 0:
        raise ValueError(
            "fleet retry_backoff_ms/retry_backoff_max_ms must be >= 0")
    if fc.hedge_ms < 0 and fc.hedge_ms != -1:
        raise ValueError(
            f"fleet hedge_ms must be >= 0 (0 = off) or exactly -1 "
            f"(auto: hedge at observed p95), got {fc.hedge_ms}")
    if fc.breaker_failures < 1:
        raise ValueError(
            f"fleet breaker_failures must be >= 1, got "
            f"{fc.breaker_failures}")
    if fc.breaker_reset_s <= 0:
        raise ValueError(
            f"fleet breaker_reset_s must be > 0, got {fc.breaker_reset_s}")
    if not 0.0 <= fc.trace_sample <= 1.0:
        raise ValueError(
            f"fleet trace_sample must be in [0, 1], got {fc.trace_sample}")
    if fc.trace_capacity < 1 or fc.trace_worst_n < 0:
        raise ValueError(
            "fleet trace_capacity must be >= 1 and trace_worst_n >= 0, "
            f"got {fc.trace_capacity}/{fc.trace_worst_n}")
    if fc.slo_objectives:
        # Loud parse at config time, not first scrape (utils/slo.py).
        from ..utils.slo import parse_slos

        parse_slos(fc.slo_objectives)
    if fc.slo_burn_threshold <= 0:
        raise ValueError(
            f"fleet slo_burn_threshold must be > 0, got "
            f"{fc.slo_burn_threshold}")
    if fc.slo_alert_for_s < 0 or fc.slo_alert_clear_s < 0:
        raise ValueError(
            "fleet slo_alert_for_s/slo_alert_clear_s must be >= 0")
    if fc.prober_interval_s < 0:
        raise ValueError(
            f"fleet prober_interval_s must be >= 0 (0 = off), got "
            f"{fc.prober_interval_s}")
    if fc.prober_interval_s > 0:
        if not fc.prober_tenant:
            raise ValueError(
                "fleet prober_tenant must be non-empty when the prober "
                "is on")
        if fc.prober_px < 8:
            raise ValueError(
                f"fleet prober_px must be >= 8, got {fc.prober_px}")
        if fc.prober_timeout_s <= 0:
            raise ValueError(
                f"fleet prober_timeout_s must be > 0, got "
                f"{fc.prober_timeout_s}")
    if fc.flight_recorder:
        # Loud at config time, not first sample (the recorder knobs
        # are re-validated by FlightRecorder itself; the dir check is
        # the one only the config layer can make early).
        if not fc.recorder_dir:
            raise ValueError(
                "fleet flight_recorder=true needs recorder_dir (the "
                "on-disk segment-ring location)")
        if fc.recorder_sample_s <= 0:
            raise ValueError(
                f"fleet recorder_sample_s must be > 0, got "
                f"{fc.recorder_sample_s}")
    if fc.controller:
        if fc.ctrl_interval_s <= 0:
            raise ValueError(
                f"fleet ctrl_interval_s must be > 0, got "
                f"{fc.ctrl_interval_s}")
        if fc.ctrl_target_replicas < 0:
            raise ValueError(
                f"fleet ctrl_target_replicas must be >= 0 (0 = the "
                f"group's configured size), got {fc.ctrl_target_replicas}")
        if fc.ctrl_max_replicas < 1:
            raise ValueError(
                f"fleet ctrl_max_replicas must be >= 1, got "
                f"{fc.ctrl_max_replicas}")
        if fc.ctrl_scale_out_burn <= 0 or fc.ctrl_scale_in_burn < 0:
            raise ValueError(
                "fleet ctrl_scale_out_burn must be > 0 and "
                "ctrl_scale_in_burn >= 0, got "
                f"{fc.ctrl_scale_out_burn}/{fc.ctrl_scale_in_burn}")
        if not 0.0 <= fc.ctrl_queue_share <= 1.0:
            raise ValueError(
                f"fleet ctrl_queue_share must be in [0, 1], got "
                f"{fc.ctrl_queue_share}")
        if fc.ctrl_dwell_s < 0 or fc.ctrl_cooldown_s < 0 \
                or fc.ctrl_drain_grace_s < 0:
            raise ValueError(
                "fleet ctrl_dwell_s/ctrl_cooldown_s/ctrl_drain_grace_s "
                "must be >= 0")
        if fc.ctrl_spawn_cmd:
            joined = " ".join(fc.ctrl_spawn_cmd)
            if "{port}" not in joined or "{port_file}" not in joined:
                raise ValueError(
                    "fleet ctrl_spawn_cmd must contain both {port} and "
                    "{port_file} placeholders (the supervisor needs to "
                    "assign the port and learn when the replica bound "
                    "it) — got " + repr(fc.ctrl_spawn_cmd))
        if fc.ctrl_spawn_deadline_s <= 0 or fc.ctrl_backoff_s <= 0 \
                or fc.ctrl_backoff_max_s < fc.ctrl_backoff_s:
            raise ValueError(
                "fleet ctrl_spawn_deadline_s/ctrl_backoff_s must be > 0 "
                "and ctrl_backoff_max_s >= ctrl_backoff_s, got "
                f"{fc.ctrl_spawn_deadline_s}/{fc.ctrl_backoff_s}/"
                f"{fc.ctrl_backoff_max_s}")
    if fc.rollout_ckpt_dir:
        if fc.rollout_model:
            if fc.rollout_model not in seen:
                raise ValueError(
                    f"fleet rollout_model {fc.rollout_model!r} is not a "
                    f"configured model (have {sorted(seen)})")
        elif len(fc.models) != 1:
            raise ValueError(
                "fleet rollout_model is required when the fleet serves "
                "more than one model (the rollout drives ONE replica "
                "set)")
        if fc.rollout_poll_s <= 0 or fc.rollout_bake_s < 0:
            raise ValueError(
                "fleet rollout_poll_s must be > 0 and rollout_bake_s "
                f">= 0, got {fc.rollout_poll_s}/{fc.rollout_bake_s}")
        if fc.rollout_probes < 1 or fc.rollout_probe_px < 8:
            raise ValueError(
                "fleet rollout_probes must be >= 1 and rollout_probe_px "
                f">= 8, got {fc.rollout_probes}/{fc.rollout_probe_px}")
        if fc.rollout_mae_degrade < 0 or fc.rollout_mae_max < 0 \
                or fc.rollout_psi_max < 0:
            raise ValueError(
                "fleet rollout_mae_degrade/rollout_mae_max/"
                "rollout_psi_max must be >= 0")
        if not 0.0 <= fc.rollout_min_avail <= 1.0:
            raise ValueError(
                f"fleet rollout_min_avail must be in [0, 1], got "
                f"{fc.rollout_min_avail}")
    if fc.cache_bytes < 0:
        raise ValueError(
            f"fleet cache_bytes must be >= 0 (0 = off), got "
            f"{fc.cache_bytes}")
    if fc.cache_near_dup and fc.cache_bytes <= 0:
        raise ValueError(
            "fleet cache_near_dup requires cache_bytes > 0 — the "
            "near-dup arm serves out of the exact arm's LRU")
    if fc.cache_near_dup_hamming < 0 \
            or fc.cache_near_dup_hamming > 256:
        raise ValueError(
            "fleet cache_near_dup_hamming must be in [0, 256] (bits "
            f"over the 256-bit phash), got {fc.cache_near_dup_hamming}")
    if fc.cache_near_dup_hamming > 0 and not fc.cache_near_dup:
        raise ValueError(
            "fleet cache_near_dup_hamming is set but cache_near_dup is "
            "off — a Hamming budget without the near-dup arm does "
            "nothing (loud beats silent)")
    if fc.cache_shadow_sample < 0:
        raise ValueError(
            f"fleet cache_shadow_sample must be >= 0 (every Nth "
            f"near-dup hit; 0 = off), got {fc.cache_shadow_sample}")
    if fc.cache_shadow_sample > 0 and not fc.cache_near_dup:
        raise ValueError(
            "fleet cache_shadow_sample is set but cache_near_dup is "
            "off — only near-dup hits are shadow-scored (exact hits "
            "are bitwise the engine's own answer)")
    if fc.stream_sessions < 0:
        raise ValueError(
            f"fleet stream_sessions must be >= 0 (0 = streaming off), "
            f"got {fc.stream_sessions}")
    if fc.stream_sessions > 0 and fc.stream_ttl_s <= 0:
        raise ValueError(
            f"fleet stream_ttl_s must be > 0 when streaming is on, got "
            f"{fc.stream_ttl_s}")
    if fc.stream_reuse_hamming < 0 or fc.stream_reuse_hamming > 256:
        raise ValueError(
            "fleet stream_reuse_hamming must be in [0, 256] (bits over "
            f"the 256-bit phash), got {fc.stream_reuse_hamming}")
    if fc.stream_reuse_hamming > 0 and fc.stream_sessions <= 0:
        raise ValueError(
            "fleet stream_reuse_hamming is set but stream_sessions is "
            "0 — the temporal-coherence fast path serves out of a "
            "stream session (loud beats silent)")
    if not 0.0 <= fc.stream_ema_blend < 1.0:
        raise ValueError(
            f"fleet stream_ema_blend must be in [0, 1), got "
            f"{fc.stream_ema_blend}")
    if fc.stream_ema_blend > 0 and fc.stream_sessions <= 0:
        raise ValueError(
            "fleet stream_ema_blend is set but stream_sessions is 0 — "
            "the blend reads a stream session's previous mask (loud "
            "beats silent)")
    if fc.default_tenant not in tseen:
        low = min((t.priority for t in fc.tenants), default=0)
        fc = dataclasses.replace(
            fc, tenants=fc.tenants + (FleetTenantConfig(
                name=fc.default_tenant, priority=low),))
        tseen.add(fc.default_tenant)
    if fc.prober_interval_s > 0 and fc.prober_tenant not in tseen:
        # Reserved probe tenant, registered AFTER the default tenant so
        # it lands STRICTLY below every class (default included): under
        # overload probes are the FIRST thing the router sheds —
        # synthetic traffic must never displace a real request.
        low = min(t.priority for t in fc.tenants) - 1
        fc = dataclasses.replace(
            fc, tenants=fc.tenants + (FleetTenantConfig(
                name=fc.prober_tenant, priority=low),))
    return fc


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    name: str = "default"
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    loss: LossConfig = dataclasses.field(default_factory=LossConfig)
    optim: OptimConfig = dataclasses.field(default_factory=OptimConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    parallel: ParallelConfig = dataclasses.field(
        default_factory=ParallelConfig)
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
    global_batch_size: int = 8
    num_epochs: int = 50
    steps_per_epoch: Optional[int] = None  # None → derived from dataset size
    seed: int = 0
    # Device-side step chunking (docs/PERFORMANCE.md): fold this many
    # train steps into ONE compiled dispatch (a lax.scan over stacked
    # batches inside the step program).  Amortises the per-step host
    # tax — Python loop, dispatch latency, fault-plan checks, metric
    # readback — over k steps; the loop then observes the run only at
    # chunk boundaries, so every cadence knob (log/eval/checkpoint/
    # stop-polling) must be divisible by k (validate_steps_per_dispatch
    # raises otherwise).  1 = the historical per-step path, unchanged.
    # DSOD_FAULTS forces 1 (per-step poison/stall/SIGTERM semantics).
    steps_per_dispatch: int = 1
    log_every_steps: int = 20
    checkpoint_every_steps: int = 500
    checkpoint_dir: str = "checkpoints"
    keep_checkpoints: int = 3
    eval_every_steps: int = 0  # 0 = no in-training eval
    best_metric: Optional[str] = None  # e.g. "max_fbeta": keep best ckpts
    best_mode: str = "max"  # "min" for lower-is-better metrics (mae)
    tensorboard: bool = True  # event files under <workdir>/tb
    # >0: arm the step watchdog (resilience/watchdog.py): a train step
    # exceeding this many seconds means the wedged-dispatch failure
    # mode (device answers enumeration, programs never complete) —
    # dump stacks + last metrics and exit with code 114 so the
    # supervising layer re-fires and resumes.  Must exceed the slowest
    # legitimate step.  0 = off.
    watchdog_deadline_s: float = 0.0
    # Grace for the FIRST step, which includes XLA compilation
    # (minutes, legitimately).  Only read when the watchdog is armed.
    watchdog_compile_grace_s: float = 600.0
    # Opt-in trainer telemetry sidecar (utils/telemetry.py;
    # docs/OBSERVABILITY.md): >= 0 binds a stdlib HTTP server on that
    # port (0 = ephemeral; publish via train.py --telemetry-port-file)
    # exposing /metrics (PipelineStats + StepTimer + device memory),
    # /healthz (step-watchdog heartbeat), /debug/traces, and
    # /debug/profile?seconds=N (on-demand jax.profiler window).
    # -1 (default) = off: zero threads, zero sockets.
    telemetry_port: int = -1
    # Fraction of train chunks whose span timelines are recorded
    # (data-wait/dispatch/flush + ckpt/eval spans correlated to step
    # numbers — utils/tracing.py).  0 = off (no per-chunk clock reads).
    trace_sample: float = 0.0
    # -- training numerics telemetry (utils/modelhealth.py;
    #    docs/OBSERVABILITY.md "Model health").  OFF by default: the
    #    compiled step and the metric stream are byte-for-byte the
    #    historical ones.  On, every step additionally emits per-
    #    parameter-group gradient norms, non-finite PROVENANCE (which
    #    group first went NaN — skip_nonfinite counts but cannot
    #    attribute), and the update/weight ratio; the host aggregates
    #    them into dsod_health_* sidecar families and feeds the alert
    #    engine (utils/alerts.py, /alerts on the sidecar).
    health_numerics: bool = False
    # Extra alert rules (colon DSL, see serve.alert_rules) joining the
    # built-in numerics set (nonfinite / grad-norm-z / loss-z).
    health_alert_rules: Tuple[str, ...] = ()
    # Clear dwell of the built-in numerics rules: how long the signal
    # must stay healthy before an alert resolves (hysteresis).
    health_alert_clear_s: float = 30.0
    # Opt-in hand-off to the PR-1 resilience supervisor: when a
    # rollback-hinted alert (numerics_nonfinite) FIRES, fit() raises
    # the divergence RuntimeError the supervisor's rollback-and-retry
    # policy recognizes — the alert engine becomes a rollback hint,
    # not just a dashboard.  Off: alerts only report.
    health_rollback_hint: bool = False
    # -- capacity & SLO observability, trainer side (utils/capacity.py,
    #    utils/slo.py; docs/OBSERVABILITY.md "Capacity & SLO").  Both
    #    OFF by default: the step program, the metric stream, and the
    #    sidecar /metrics are byte-for-byte the historical ones.
    # Live train-step cost ledger: each step program is additionally
    # AOT-compiled ONCE for its cost_analysis()/memory_analysis()
    # (one extra compile per static shape, paid only when opted in)
    # and the StepTimer's measured step time turns it into live
    # MFU/roofline gauges on the telemetry sidecar.
    capacity_ledger: bool = False
    # Goodput SLO on train steps (same colon DSL as
    # serve.slo_objectives; kind=latency over per-step wall time is
    # the meaningful form — every completed step feeds one event):
    # e.g. "goodput:all:latency:0.99:600:2000" = 99% of steps under
    # 2 s over any 10-minute window.  Surfaces as dsod_slo_* + /slo on
    # the sidecar; burn/budget alerts degrade the sidecar /healthz.
    slo_objectives: Tuple[str, ...] = ()
    slo_burn_threshold: float = 10.0
    slo_alert_for_s: float = 5.0
    slo_alert_clear_s: float = 60.0
    # -- black-box flight recorder, trainer side
    #    (utils/flightrecorder.py; docs/OBSERVABILITY.md "Flight
    #    recorder & incidents").  OFF by default: no thread, no files,
    #    the loop and sidecar surface byte-identical.  On, the trainer
    #    telemetry registry (built even when the sidecar port is off)
    #    is sampled into an on-disk segment ring under recorder_dir
    #    (default <workdir>/flightrec), checkpoint/eval/preemption/
    #    rollback events are recorded, and watchdog trips / health-
    #    alert firings / train crashes snapshot incident bundles —
    #    evidence that survives the exit-114 the watchdog's stall
    #    policy mandates.  resilience/supervisor.py notes each
    #    rollback into the same ring between attempts.
    flight_recorder: bool = False
    recorder_dir: str = ""
    recorder_sample_s: float = 1.0
    recorder_segment_kb: int = 256
    recorder_keep_segments: int = 16
    recorder_bundle_window_s: float = 300.0
    recorder_debounce_s: float = 30.0

    def replace(self, **kw) -> "ExperimentConfig":
        return dataclasses.replace(self, **kw)


def validate_steps_per_dispatch(cfg: ExperimentConfig,
                                loader_steps_per_epoch: Optional[int] = None,
                                ) -> None:
    """Chunk-boundary divisibility contract for ``steps_per_dispatch``.

    With k steps folded into one dispatch the train loop only observes
    the run at chunk boundaries, so every step-cadence knob must be a
    multiple of k or its events would fall mid-chunk and silently never
    fire.  Raises ``ValueError`` naming the offending (knob, value)
    pair.  ``loader_steps_per_epoch`` lets ``fit()`` also check the
    loader's actual epoch period (a partial trailing chunk per epoch
    would drop steps and skew the epoch accounting).
    """
    k = cfg.steps_per_dispatch
    if k < 1:
        raise ValueError(
            f"steps_per_dispatch must be >= 1, got {k}")
    if k == 1:
        return
    pairs = [
        ("log_every_steps", cfg.log_every_steps),
        ("eval_every_steps", cfg.eval_every_steps),
        ("checkpoint_every_steps", cfg.checkpoint_every_steps),
        ("steps_per_epoch", cfg.steps_per_epoch or 0),
        ("loader steps_per_epoch", loader_steps_per_epoch or 0),
    ]
    for name, value in pairs:
        if value and value % k:
            raise ValueError(
                f"steps_per_dispatch={k} does not divide {name}={value}"
                " — the chunked loop only observes chunk boundaries, so"
                f" a {name} event would fall mid-chunk and never fire."
                f"  Pick k dividing every cadence knob or change {name}"
                " to a multiple of k (docs/PERFORMANCE.md"
                " \"Device-side step chunking\")")


def validate_parallel(cfg: ExperimentConfig) -> None:
    """Loud validation of the sharding-engine knobs (ParallelConfig)."""
    par = cfg.parallel
    if par.engine == "legacy":
        raise ValueError(
            "parallel.engine=legacy: the legacy step builders were "
            "removed in round 18 after the rules engine shipped "
            "bitwise-proven — parallel.engine=rules is the only engine")
    if par.engine != "rules":
        raise ValueError(
            f"parallel.engine must be rules, got {par.engine!r}")
    if par.preset not in ("auto", "dp", "tp", "sp", "fsdp"):
        raise ValueError(
            "parallel.preset must be auto|dp|tp|sp|fsdp, got "
            f"{par.preset!r}")
    if par.zero not in (0, 1, 2):
        raise ValueError(f"parallel.zero must be 0|1|2, got {par.zero!r}")
    if par.grad_compression not in ("none", "bf16", "int8_ef"):
        raise ValueError(
            "parallel.grad_compression must be none|bf16|int8_ef, got "
            f"{par.grad_compression!r}")
    if par.comm_bucket_mb < 0:
        raise ValueError(
            f"parallel.comm_bucket_mb must be >= 0, got "
            f"{par.comm_bucket_mb}")
    if cfg.mesh.data_hosts < 1:
        raise ValueError(
            f"mesh.data_hosts must be >= 1, got {cfg.mesh.data_hosts}"
            " (divisibility vs the resolved data axis is checked at "
            "mesh build time — the axis may be -1 here)")
    if par.zero and cfg.optim.zero1:
        raise ValueError(
            "optim.zero1 and parallel.zero are both set — pick ONE "
            "spelling (parallel.zero on the rules engine)")
    if par.zero and cfg.model.sync_bn:
        raise ValueError(
            "parallel.zero routes through the GSPMD preset, which has "
            "no named mesh axis: set model.sync_bn=false (BN stats are "
            "global-batch there, strictly stronger)")
    if par.preset == "fsdp":
        if cfg.model.sync_bn:
            raise ValueError(
                "parallel.preset=fsdp routes through the GSPMD path, "
                "which has no named mesh axis: set model.sync_bn=false "
                "(BN stats are global-batch there, strictly stronger)")
        if cfg.mesh.model != 1 or cfg.mesh.seq != 1:
            raise ValueError(
                "parallel.preset=fsdp shards params over the data axis "
                "only — set mesh.model=1 and mesh.seq=1 (got model="
                f"{cfg.mesh.model}, seq={cfg.mesh.seq})")


_REGISTRY: Dict[str, Callable[[], ExperimentConfig]] = {}


def register_config(name: str):
    """Decorator: register a zero-arg factory under ``name``."""

    def deco(fn: Callable[[], ExperimentConfig]):
        if name in _REGISTRY:
            raise KeyError(f"config {name!r} already registered")
        _REGISTRY[name] = fn
        return fn

    return deco


def get_config(name: str, **overrides) -> ExperimentConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown config {name!r}; known: {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def list_configs():
    return sorted(_REGISTRY)


def _coerce(value: str, ftype):
    """Parse a CLI string into a dataclass field's annotated type.

    Typed by the annotation, not the current value, so fields defaulting
    to ``None`` (``Optional[int] steps_per_epoch``) still coerce.
    """
    import typing

    origin = typing.get_origin(ftype)
    if origin is typing.Union:  # Optional[X] and friends
        args = [a for a in typing.get_args(ftype) if a is not type(None)]
        if value.lower() in ("none", "null"):
            return None
        return _coerce(value, args[0])
    if origin is tuple:
        parts = [p for p in value.replace("(", "").replace(")", "").split(",") if p]
        args = typing.get_args(ftype)
        elem = args[0] if args else str
        return tuple(_coerce(p, elem) for p in parts)
    if ftype is bool:
        if value.lower() in ("1", "true", "yes"):
            return True
        if value.lower() in ("0", "false", "no"):
            return False
        raise ValueError(f"expected bool, got {value!r}")
    if ftype is int:
        return int(value)
    if ftype is float:
        return float(value)
    if ftype is str:
        return value
    raise ValueError(f"cannot coerce {value!r} onto {ftype!r}")


def config_from_dict(d: Dict) -> ExperimentConfig:
    """Rebuild an ExperimentConfig from its JSON dict (the checkpoint
    config sidecar, ckpt/manager.py) — checkpoints are self-describing,
    so ``test.py`` can run without naming the config again."""
    import typing

    def build(cls, dd):
        hints = typing.get_type_hints(cls)
        kwargs = {}
        for f in dataclasses.fields(cls):
            if f.name not in dd:
                continue
            v = dd[f.name]
            ft = hints[f.name]
            if dataclasses.is_dataclass(ft) and isinstance(v, dict):
                kwargs[f.name] = build(ft, v)
            elif typing.get_origin(ft) is tuple and isinstance(v, list):
                kwargs[f.name] = tuple(v)
            else:
                kwargs[f.name] = v
        return cls(**kwargs)

    return build(ExperimentConfig, d)


def apply_overrides(cfg: ExperimentConfig, overrides) -> ExperimentConfig:
    """Apply ``section.field=value`` CLI overrides (SURVEY.md §2 C13).

    Dotted paths address nested config dataclasses:
    ``data.image_size=64,64 optim.lr=0.01 model.name=u2net``.
    Top-level fields work without a dot (``global_batch_size=16``).
    """
    for ov in overrides or []:
        if "=" not in ov:
            raise ValueError(f"override {ov!r} is not key=value")
        path, value = ov.split("=", 1)
        keys = path.strip().split(".")
        # Walk down, collecting the chain of dataclass instances.
        objs = [cfg]
        for k in keys[:-1]:
            if not hasattr(objs[-1], k) or not dataclasses.is_dataclass(
                    getattr(objs[-1], k)):
                raise KeyError(f"no config field {'.'.join(keys)!r}")
            objs.append(getattr(objs[-1], k))
        leaf = keys[-1]
        fields = {f.name: f for f in dataclasses.fields(type(objs[-1]))}
        if leaf not in fields:
            raise KeyError(f"no config field {'.'.join(keys)!r}")
        ftype = fields[leaf].type
        if isinstance(ftype, str):  # `from __future__ import annotations`
            import typing

            ftype = typing.get_type_hints(type(objs[-1]))[leaf]
        new = _coerce(value.strip(), ftype)
        # Rebuild the frozen chain bottom-up.
        for obj, key in zip(reversed(objs), reversed(keys)):
            new = dataclasses.replace(obj, **{key: new})
        cfg = new
    return cfg
