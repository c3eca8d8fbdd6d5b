"""The five driver configs from BASELINE.json:7-11 (see SURVEY.md §2).

1. MINet-VGG16, DUTS-TR 320×320, batch=1 single-image forward (CPU ref)
2. MINet-ResNet50, DUTS-TR full data-parallel train
3. HDFNet RGB-D (NJU2K / NLPR) — two-stream depth-fusion encoder
4. U²-Net / BASNet — nested U-decoder + 7-level deep supervision
5. Swin-T backbone SOD (stretch — transformer encoder on TPU)
"""

from .base import (
    DataConfig,
    ExperimentConfig,
    LMConfig,
    LossConfig,
    MeshConfig,
    ModelConfig,
    OptimConfig,
    register_config,
)


@register_config("minet_vgg16_ref")
def minet_vgg16_ref() -> ExperimentConfig:
    """Config 1: MINet-VGG16 single-image forward reference."""
    return ExperimentConfig(
        name="minet_vgg16_ref",
        data=DataConfig(dataset="synthetic", image_size=(320, 320)),
        model=ModelConfig(name="minet", backbone="vgg16", sync_bn=False),
        loss=LossConfig(cel=1.0),
        optim=OptimConfig(lr=0.001),
        global_batch_size=1,
        mesh=MeshConfig(data=1),
    )


@register_config("minet_r50_dp")
def minet_r50_dp() -> ExperimentConfig:
    """Config 2: MINet-ResNet50 full data-parallel training (flagship)."""
    return ExperimentConfig(
        name="minet_r50_dp",
        # rotate_degrees=10: the MINet-era joint-transform recipe
        # (hflip + small random rotation) on the host data plane.
        data=DataConfig(dataset="duts", image_size=(320, 320),
                        rotate_degrees=10.0),
        model=ModelConfig(name="minet", backbone="resnet50", sync_bn=True),
        loss=LossConfig(cel=1.0),
        optim=OptimConfig(lr=0.005, schedule="poly"),
        global_batch_size=32,
        num_epochs=50,
    )


@register_config("hdfnet_rgbd")
def hdfnet_rgbd() -> ExperimentConfig:
    """Config 3: HDFNet two-stream RGB-D on NJU2K/NLPR."""
    return ExperimentConfig(
        name="hdfnet_rgbd",
        data=DataConfig(dataset="nju2k", image_size=(320, 320), use_depth=True),
        model=ModelConfig(name="hdfnet", backbone="vgg16", sync_bn=True),
        loss=LossConfig(),
        optim=OptimConfig(lr=0.005),
        global_batch_size=16,
        num_epochs=40,
    )


@register_config("u2net_ds")
def u2net_ds() -> ExperimentConfig:
    """Config 4a: U²-Net — nested U decoder, 7-level deep supervision."""
    return ExperimentConfig(
        name="u2net_ds",
        data=DataConfig(dataset="duts", image_size=(320, 320)),
        model=ModelConfig(name="u2net", backbone="none", sync_bn=True),
        # fused_kernel: the same deep-supervision shape as basnet_ds,
        # below; no cell measures this config.
        loss=LossConfig(bce=1.0, iou=0.0, ssim=0.0, deep_supervision=True,
                        fused_kernel=True),
        optim=OptimConfig(optimizer="adamw", lr=1e-3, weight_decay=0.0),
        global_batch_size=16,
        num_epochs=100,
    )


@register_config("basnet_ds")
def basnet_ds() -> ExperimentConfig:
    """Config 4b: BASNet — predict+refine, BCE+SSIM+IoU hybrid loss."""
    return ExperimentConfig(
        name="basnet_ds",
        data=DataConfig(dataset="duts", image_size=(320, 320)),
        model=ModelConfig(name="basnet", backbone="resnet34", sync_bn=True),
        # fused_kernel: on in the cell basnet_ds.train_b16, where the
        # kernels read fused_ssim 1.95 + fused_loss 0.29 ms of a 214 ms
        # step (ROADMAP D3); the off side is unmeasured on this tree.
        # Exactness vs the unfused path: tests/test_pallas_loss.py.
        loss=LossConfig(bce=1.0, iou=1.0, ssim=1.0, deep_supervision=True,
                        fused_kernel=True),
        optim=OptimConfig(optimizer="adamw", lr=1e-3, weight_decay=0.0),
        global_batch_size=16,
        num_epochs=100,
    )


@register_config("swin_sod")
def swin_sod() -> ExperimentConfig:
    """Config 5 (stretch): Swin-T transformer encoder SOD."""
    return ExperimentConfig(
        name="swin_sod",
        data=DataConfig(dataset="duts", image_size=(320, 320)),
        model=ModelConfig(name="swin_sod", backbone="swin_t", sync_bn=False),
        loss=LossConfig(),
        optim=OptimConfig(optimizer="adamw", lr=3e-4, weight_decay=0.01,
                          warmup_steps=500),
        global_batch_size=16,
        mesh=MeshConfig(data=-1, model=1, seq=1),
    )


@register_config("vit_sod_hires")
def vit_sod_hires() -> ExperimentConfig:
    """Long-context flagship recipe: ViT-SOD at 1024px (4096 global
    tokens).  Image rows shard over ``mesh.seq`` (ring attention;
    ``--set mesh.sp_strategy=ulysses`` for the all-to-all variant when
    heads divide).  Attention defaults to ``attn_impl="xla"``: at every
    operating point measured on v5e (round 2, N=1024) the Pallas flash
    kernel was 2.2x SLOWER than XLA's materialized attention whenever
    the N² scores fit in HBM, and the pre-committed decision rule says
    flash must measurably win to be a default (docs/PERFORMANCE.md).
    ``--set model.attn_impl=flash`` remains the documented memory
    lever — at b16/N=4096 it runs where XLA OOMs; a block-shape sweep
    (tools/bench_flash.py; not measured on a chip) re-flips this
    default if any block shape beats XLA at this config's operating
    point."""
    return ExperimentConfig(
        name="vit_sod_hires",
        data=DataConfig(dataset="duts", image_size=(1024, 1024)),
        model=ModelConfig(name="vit_sod", backbone="small", sync_bn=False,
                          attn_impl="xla", remat=True),
        loss=LossConfig(bce=1.0, iou=1.0, ssim=1.0),
        optim=OptimConfig(optimizer="adamw", lr=3e-4, weight_decay=0.01,
                          warmup_steps=500),
        global_batch_size=8,
        mesh=MeshConfig(data=1, model=1, seq=-1),
    )


@register_config("gatenet_vgg16")
def gatenet_vgg16() -> ExperimentConfig:
    """Zoo extension beyond the 5 driver configs: GateNet (ECCV 2020,
    lartpang et al.) — gated skip connections + dilated-pyramid
    bridge, 5-level deep supervision."""
    return ExperimentConfig(
        name="gatenet_vgg16",
        data=DataConfig(dataset="duts", image_size=(320, 320)),
        model=ModelConfig(name="gatenet", backbone="vgg16"),
        loss=LossConfig(bce=1.0, iou=1.0, ssim=1.0, deep_supervision=True,
                        fused_kernel=True),
        optim=OptimConfig(optimizer="sgd", lr=0.01, momentum=0.9,
                          weight_decay=5e-4, schedule="poly",
                          warmup_steps=200),
        global_batch_size=32,
        mesh=MeshConfig(data=-1, model=1, seq=1),
    )


@register_config("vit_sod_sp")
def vit_sod_sp() -> ExperimentConfig:
    """Long-context member: global-attention ViT-SOD, trainable with
    the sequence-parallel step (--set mesh.seq=N shards image rows /
    token blocks over N devices; ring attention crosses them).  SSIM
    defaults off here for parity with the historical recipe, but the
    full hybrid loss IS supported under SP since the row-halo exchange
    (parallel/sp.py::_sp_ssim_loss) — enable with --set loss.ssim=1."""
    return ExperimentConfig(
        name="vit_sod_sp",
        data=DataConfig(dataset="duts", image_size=(320, 320)),
        model=ModelConfig(name="vit_sod", backbone="small", sync_bn=False),
        loss=LossConfig(bce=1.0, iou=1.0, ssim=0.0),
        optim=OptimConfig(optimizer="adamw", lr=3e-4, weight_decay=0.01,
                          warmup_steps=500),
        global_batch_size=16,
        mesh=MeshConfig(data=-1, model=1, seq=1),
    )


@register_config("lfm2_8b_a1b_ep4")
def lfm2_8b_a1b_ep4() -> ExperimentConfig:
    """The zoo's token model: LFM2-8B-A1B (LiquidAI, ``lfm2_moe``) at
    its published widths, ONE chip's share of a 4-way expert-parallel
    deployment — experts 0-7 of 32 in every expert layer (the router
    stays 32 wide, top-4), vocabulary rows 0-16,383 of 65,536, and of
    the 24 layers the leading dense one plus one period of the pattern
    that follows (attention, conv, conv, conv); the layers left out lie
    on further hosts as pipeline stages.  Trains on packed synthetic
    documents, 4 sequences of 8,192 tokens a step (each held expert then
    sees the 4,096 tokens a step it sees in the deployment), AdamW,
    per-layer remat.  ``model.lm.*`` / ``data.seq_len`` shrink it for a
    CPU drive (tests/test_lfm2.py)."""
    return ExperimentConfig(
        name="lfm2_8b_a1b_ep4",
        data=DataConfig(dataset="packed_tokens", hflip=False,
                        synthetic_size=4096, seq_len=8192, vocab=16384),
        model=ModelConfig(name="lfm2", backbone="none", sync_bn=False,
                          remat=True, lm=LMConfig()),
        loss=LossConfig(),
        # The warm-up matters beyond habit: this chip's partial sum lets
        # the loss fall by routing AWAY from the held experts (random at
        # first, so noise), and at the full rate Adam does that within
        # ten steps (PERF.md, Findings PR 28).
        optim=OptimConfig(optimizer="adamw", lr=3e-4, weight_decay=0.1,
                          schedule="poly", warmup_steps=2000),
        global_batch_size=4,
        num_epochs=100,
        mesh=MeshConfig(data=1, model=1, seq=1),
    )


@register_config("kimi_vl_a3b_ep8")
def kimi_vl_a3b_ep8() -> ExperimentConfig:
    """The second token model: the decoder of Kimi-VL-A3B-Instruct
    (moonshotai, ``text_config``) at its published widths, ONE chip's
    share of an 8-way expert-parallel stage — routed experts 0-7 of 64
    in every expert layer (the router stays 64 wide, top-6), both shared
    experts, all 16 latent-attention heads, rows 0-20,479 of the
    163,840-row embedding and of the untied head; of the 27 layers the
    dense layer 0 and expert layers 1-5, the rest on further chips as
    pipeline stages (the image tower on the stage before: this stage's
    traffic is text).  The router is balanced by the family's rule
    (``bias_update_rate``).  Trains on packed synthetic documents, 2
    sequences of 16,384 tokens a step, AdamW, per-layer remat.
    ``model.lm.*`` / ``data.seq_len`` shrink it for a CPU drive
    (tests/test_kimi.py)."""
    return ExperimentConfig(
        name="kimi_vl_a3b_ep8",
        data=DataConfig(dataset="packed_tokens", hflip=False,
                        synthetic_size=4096, seq_len=16384, vocab=20480),
        model=ModelConfig(
            name="kimi", backbone="none", sync_bn=False, remat=True,
            lm=LMConfig(
                vocab=20480, hidden=2048,
                ffn_types=("dense",) + ("moe",) * 5, heads=16, head_dim=192, rope_dim=64, v_dim=128, kv_rank=512,
                dense_width=11264, expert_width=1408, shared_experts=2,
                experts=64, experts_held=8, first_expert=0, top_k=6,
                norm_eps=1e-5, rope_theta=8e5, norm_topk_prob=True,
                routed_scaling_factor=2.446, topk_eps=1e-20,
                bias_update_rate=1e-3)),
        loss=LossConfig(),
        # The warm-up for the reason lfm2_8b_a1b_ep4 gives; the balancing
        # rule holds the router's load level beside it.
        optim=OptimConfig(optimizer="adamw", lr=3e-4, weight_decay=0.1,
                          schedule="poly", warmup_steps=2000),
        global_batch_size=2,
        num_epochs=100,
        mesh=MeshConfig(data=1, model=1, seq=1),
    )


@register_config("granite_4_0_h_micro_pp4")
def granite_4_0_h_micro_pp4() -> ExperimentConfig:
    """The third token model: granite-4.0-h-micro (ibm-granite,
    ``granitemoehybrid``, dense) at its published widths, the FIRST of 4
    pipeline stages — layers 0-9 of the 40, one whole period of the
    layer pattern (five Mamba-2 layers, one position-free grouped-query
    attention layer, four Mamba-2 layers), no layer divided — with rows
    0-12,543 of the 100,352-row tied embedding (the rows are divided
    over 8 chips; the tied head and the loss stay on this chip so that
    a step is a whole step).  Trains on packed synthetic documents, 1
    sequence of 16,384 tokens a step, AdamW, per-layer remat.
    ``model.lm.*`` / ``data.seq_len`` shrink it for a CPU drive
    (tests/test_granite.py)."""
    return ExperimentConfig(
        name="granite_4_0_h_micro_pp4",
        data=DataConfig(dataset="packed_tokens", hflip=False,
                        synthetic_size=4096, seq_len=16384, vocab=12544),
        model=ModelConfig(
            name="granite", backbone="none", sync_bn=False, remat=True,
            lm=LMConfig(
                vocab=12544, hidden=2048,
                layer_types=("mamba",) * 5 + ("attention",)
                + ("mamba",) * 4,
                ffn_types=("dense",) * 10, heads=32, kv_heads=8,
                head_dim=64, dense_width=8192, norm_eps=1e-5,
                ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_conv=4,
                ssm_chunk=256, embedding_multiplier=12.0,
                residual_multiplier=0.22, attention_multiplier=0.015625,
                logits_scaling=8.0)),
        loss=LossConfig(),
        # AdamW and the warm-up of the two other token configs.
        optim=OptimConfig(optimizer="adamw", lr=3e-4, weight_decay=0.1,
                          schedule="poly", warmup_steps=2000),
        global_batch_size=1,
        num_epochs=100,
        mesh=MeshConfig(data=1, model=1, seq=1),
    )


@register_config("ouro_2_6b_pp6")
def ouro_2_6b_pp6() -> ExperimentConfig:
    """The fourth token model: Ouro-2.6B (ByteDance, ``ouro``) at its
    published widths, the FIRST of 6 pipeline stages — layers 0-7 of the
    48, no layer divided, every head — with the WHOLE 49,152-row
    embedding and the whole untied head (head, gate and loss stay on
    this chip so that a step is a whole step).  The stage's 8 layers are
    run 4 times on the same weights (``total_ut_steps``); the loss is
    the exit-weighted sum of the four passes' cross-entropies less 0.1 x
    the exit distribution's entropy.  Trains on packed synthetic
    documents, 1 sequence of 8,192 tokens a step, AdamW, per-visit
    remat.  ``model.lm.*`` / ``data.seq_len`` shrink it for a CPU drive
    (tests/test_ouro.py)."""
    return ExperimentConfig(
        name="ouro_2_6b_pp6",
        data=DataConfig(dataset="packed_tokens", hflip=False,
                        synthetic_size=4096, seq_len=8192, vocab=49152),
        model=ModelConfig(
            name="ouro", backbone="none", sync_bn=False, remat=True,
            lm=LMConfig(
                vocab=49152, hidden=2048,
                layer_types=("attention",) * 8, ffn_types=("dense",) * 8,
                heads=16, kv_heads=16, head_dim=128, dense_width=5632,
                norm_eps=1e-6, rope_theta=1e6, ut_steps=4,
                exit_beta=0.1)),
        loss=LossConfig(),
        # AdamW and the warm-up of the three other token configs.
        optim=OptimConfig(optimizer="adamw", lr=3e-4, weight_decay=0.1,
                          schedule="poly", warmup_steps=2000),
        global_batch_size=1,
        num_epochs=100,
        mesh=MeshConfig(data=1, model=1, seq=1),
    )


@register_config("nemotron_3_super_tp8_ep64")
def nemotron_3_super_tp8_ep64() -> ExperimentConfig:
    """The fifth token model: Nemotron 3 Super 120B-A12B (nvidia,
    ``nemotron_h``) at its published widths, ONE chip's share of a
    deployment that divides every layer over 64 chips — the routed
    experts 64 ways (experts 0-7 of 512; the router stays 512 wide,
    top-22) and each mixer's heads 8 ways (Mamba-2 heads 0-15 of 128
    with B/C group 0 of 8; query heads 0-3 of 32 on key-value head 0 of
    2) — with rows 0-16,383 of the 131,072-row embedding and of the
    untied head, and of the 88 layers the first 11, one period of the
    pattern in its published 5 : 5 : 1 (``MEMEMEM*EME``); the other 77
    lie on 7 further pipeline stages, the multi-token-prediction module
    with the last.  The router is balanced by the family's rule.  Trains
    on packed synthetic documents, 1 sequence of 8,192 tokens a step,
    AdamW, per-layer remat.  ``model.lm.*`` / ``data.seq_len`` shrink it
    for a CPU drive (tests/test_nemotron_h.py)."""
    from ..models.nemotron_h import PATTERN

    return ExperimentConfig(
        name="nemotron_3_super_tp8_ep64",
        data=DataConfig(dataset="packed_tokens", hflip=False,
                        synthetic_size=4096, seq_len=8192, vocab=16384),
        model=ModelConfig(
            name="nemotron_h", backbone="none", sync_bn=False, remat=True,
            lm=LMConfig(
                vocab=16384, hidden=4096,
                layer_types=tuple(PATTERN[c] for c in "MEMEMEM*EME"),
                ffn_types=(), heads=4, kv_heads=1, head_dim=128,
                expert_width=2688, latent_width=1024, shared_width=5376,
                experts=512, experts_held=8, first_expert=0, top_k=22,
                norm_eps=1e-5, norm_topk_prob=True,
                routed_scaling_factor=5.0, topk_eps=1e-20,
                bias_update_rate=1e-3,
                ssm_heads=16, ssm_head_dim=64, ssm_state=128, ssm_conv=4,
                ssm_chunk=128)),
        loss=LossConfig(),
        # AdamW and the warm-up of the four other token configs.
        optim=OptimConfig(optimizer="adamw", lr=3e-4, weight_decay=0.1,
                          schedule="poly", warmup_steps=2000),
        global_batch_size=1,
        num_epochs=100,
        mesh=MeshConfig(data=1, model=1, seq=1),
    )


@register_config("phi4_mini_flash_pp5")
def phi4_mini_flash_pp5() -> ExperimentConfig:
    """The sixth token model: Phi-4-mini-flash-reasoning (microsoft,
    ``phi4flash``) at its published widths, the THIRD of 5 pipeline
    stages — published layers 14-19 of the 32, the stretch on which the
    self-decoder hands over to the cross-decoder, so that it holds each
    of the five layer kinds: Mamba-1, 512-key sliding-window
    differential attention, Mamba-1 (its scan's output kept), full
    differential attention (its keys and values kept), a gated memory
    unit that reads the kept output, cross-attention that reads the kept
    keys and values.  No layer divided — with rows 0-25,087 of the
    200,064-row tied embedding (the rows are divided over 8 chips; the
    tied head and the loss stay on this chip so that a step is a whole
    step).  Trains on packed synthetic documents, 1 sequence of 16,384
    tokens a step, AdamW, per-layer remat.  ``model.lm.*`` /
    ``data.seq_len`` shrink it for a CPU drive
    (tests/test_phi4flash.py)."""
    return ExperimentConfig(
        name="phi4_mini_flash_pp5",
        data=DataConfig(dataset="packed_tokens", hflip=False,
                        synthetic_size=4096, seq_len=16384, vocab=25088),
        model=ModelConfig(
            name="phi4flash", backbone="none", sync_bn=False, remat=True,
            lm=LMConfig(
                vocab=25088, hidden=2560,
                layer_types=("mamba", "window", "mamba", "full", "gmu",
                             "cross"),
                ffn_types=("dense",) * 6, heads=40, kv_heads=20,
                head_dim=64, dense_width=10240, norm_eps=1e-5,
                ssm_heads=5120, ssm_head_dim=1, ssm_state=16, ssm_conv=4,
                ssm_chunk=128, ssm_dt_rank=160, window=512,
                first_layer=14)),
        loss=LossConfig(),
        # AdamW and the warm-up of the five other token configs.
        optim=OptimConfig(optimizer="adamw", lr=3e-4, weight_decay=0.1,
                          schedule="poly", warmup_steps=2000),
        global_batch_size=1,
        num_epochs=100,
        mesh=MeshConfig(data=1, model=1, seq=1),
    )
