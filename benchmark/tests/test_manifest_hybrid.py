"""What PR 43 added to the manifest, checked without the chip: the cell
resolves, every metric it is listed under has a reader that loads and
says nothing where there is nothing to read, the four latent-expert
metrics list the cell, the configuration's file keeps every number of
the catalog row and lists each cut, file, reference and registered
config tell one story, the step's FLOPs and the routed products' cost
are a hand count, and each new reader reads a synthetic run and says
nothing on another cell's.  Membership and relative order only: nothing
here pins where a list ends, how long it is, or what else it holds."""

import json
import os

import pytest

from benchmark import run as harness
from benchmark.harness import flops_hybrid, flops_lm, scopes_hybrid

CELL = "nemotron_3_super_tp8_ep64.train_s8k_b1"
CONFIG = "nemotron_3_super_tp8_ep64"
NEW = ["train_latent_moe_ms", "train_latent_moe_route_ms",
       "train_latent_moe_shared_ms", "latent_moe_experts_roofline"]
SSM = ["train_ssm_ms", "train_ssm_scan_ms", "train_ssm_conv_ms",
       "ssm_scan_roofline"]
# pinned to the LFM2 cell by benchmark/tests/test_manifest_lm.py, or
# another model's: their readers are not asked here
NOT_LISTED = ["train_moe_ms", "train_moe_dispatch_ms", "moe_experts_roofline",
              "moe_load_max_over_mean", "train_attn_ms",
              "train_moe_shared_ms", "flash_attention_causal_roofline",
              "flash_attention_mla_roofline", "train_stage_ms.decoder",
              "train_loop_attn_ms", "loop_exit_entropy"]
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
PUBLISHED = {  # the catalog row's `config`,
    # nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 2688,
    "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEM*EME",
           "n_routed_experts": 8, "mamba_num_heads": 16, "n_groups": 1,
           "num_attention_heads": 4, "num_key_value_heads": 1,
           "vocab_size": 16384, "num_nextn_predict_layers": 0}
V5E = json.load(open(os.path.join(os.path.dirname(harness.__file__),
                                  "harness", "peaks.json")))["TPU v5 lite"]


@pytest.fixture(scope="module")
def files():
    manifest = harness.load_manifest()
    entry, cell, config = harness.resolve(manifest, CELL)
    return manifest, entry, cell, config


def test_cell_resolves_and_reports_what_the_issue_lists(files):
    manifest, entry, cell, config = files
    assert entry["chips"] == 1 and cell["runner"] == "train_hybrid"
    assert cell["overrides"] == ["global_batch_size=1", "data.seq_len=8192",
                                 "mesh.data=1", "log_every_steps=2"]
    assert (cell["warmup_ticks"], cell["trace_ticks"]) == (2, 4)
    names = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                     "per_layer")}
    # everything the state-space cell reports (the whole step's share of
    # the peak, the seven set-up phases and its own four among it) and
    # the four latent-expert metrics
    older = "granite_4_0_h_micro_pp4.train_s16k_b1"
    shared = {m["name"] for m in manifest["per_layer"]
              if older in m["workloads"]}
    assert {"train_step_mfu", "setup_compile_s", *SSM} <= shared
    assert shared | set(NEW) <= names
    assert not names & set(NOT_LISTED)
    assert {"train_img_per_s_chip", "setup_s"} <= {
        m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                "end_to_end")}
    assert len(entry["why"]) <= 200 and entry["why"] == cell["why"]
    # the seven judged numbers of the token cells.  NO limit on the
    # routing row: with 8 of 512 held the share reads 0.2-1.7 in sound
    # runs and 1.0 where no pair is routed to the held experts, so no
    # limit can fail it; the runner prints the row unjudged
    assert set(cell["limits"]) == {
        "loss_rel_gap.step1", "loss_rel_gap.step2", "loss_rel_gap.step3",
        "grad_norm_median_leaf_gap", "grad_norm_worst_leaf_gap",
        "dparam_norm_median_leaf_gap", "dparam_zero_leaf_share"}
    assert cell["limits"]["dparam_zero_leaf_share"] == 0.0
    # every float row is 3 x the largest sound reading of the cell's own
    # seeds on the chip (PERF.md section 4 lists each beside the control
    # and the planted faults); the three losses share the pooled limit
    floats = {k: v for k, v in cell["limits"].items()
              if k != "dparam_zero_leaf_share"}
    assert all(isinstance(v, float) and v > 0 for v in floats.values())
    assert len({floats[f"loss_rel_gap.step{i}"] for i in (1, 2, 3)}) == 1
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 0
    assert len({c["name"] for c in manifest["configs"]}) >= 6
    assert len({w["name"] for w in manifest["workloads"]}) >= 6


def test_every_reader_of_the_cell_loads_and_finds_nothing_in_an_empty_run(
        files):
    manifest = files[0]
    for m in harness.cell_metrics(manifest, CELL, "per_layer"):
        read = harness.load_reader(m["name"])
        # a run with no trace and no counters (the parent, a CPU run)
        assert read({"ticks": [], "trace_dir": None, "trace": None}) is None


@pytest.mark.parametrize("name", NEW)
def test_latent_expert_metric_lists_the_cell(files, name):
    manifest, _, _, config = files
    (m,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert m["workloads"] == [CELL] and m["moves"] == "train_img_per_s_chip"
    assert (m["source"], m["layer"]) == ("device_trace",
                                         "kernels and XLA fusions")
    if name.endswith("_roofline"):
        assert m["unit"] == "%" and m["better"] == "higher"
    else:
        assert m["unit"] == "ms" and m["better"] == "lower"
    # a traced run of this configuration that names no such scope and
    # carries no such counter (the parent's program under this PR's
    # benchmark files) reads nothing
    assert harness.load_reader(name)(
        {"config": config, "seq_len": 8192, "tokens_per_step": 8192,
         "trace_dir": None, "traced_steps": 8, "ticks": [{"loss": 1.0}],
         "device": {"peaks": V5E}}) is None


def test_the_entries_stand_after_the_older_ones(files):
    """Appended, not inserted: the cell, its configuration and the four
    metrics come after what the benchmark had, wherever a list ends."""
    manifest = files[0]

    def at(entries, name):
        return [e["name"] for e in entries].index(name)

    older = "ouro_2_6b_pp6.train_s8k_b1"
    assert at(manifest["workloads"], CELL) > at(manifest["workloads"], older)
    assert at(manifest["configs"], CONFIG) > at(manifest["configs"],
                                                "ouro_2_6b_pp6")
    # (the last metric the benchmark had before them)
    last = at(manifest["per_layer"], "loop_exit_entropy")
    assert all(at(manifest["per_layer"], n) > last for n in NEW)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        w = m.get("workloads", [])
        for before in (older, "granite_4_0_h_micro_pp4.train_s16k_b1"):
            if CELL in w and before in w:
                assert w.index(CELL) > w.index(before)


def test_config_file_keeps_published_numbers_and_lists_each_cut(files):
    manifest, _, _, config = files
    (conf,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert set(PUBLISHED) <= set(config)  # every key of the row
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    assert set(conf["reduced"]) == set(config["reduced"]) == set(REDUCED)
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    # no width among the cuts
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank")) or any(
        w in k for w in ("hidden_size", "intermediate", "latent",
                         "state_size", "expand", "per_tok"))]
    assert config["source"] == conf["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        "/blob/main/config.json")
    assert len(conf["why"]) <= 200
    # one period in the published ratio
    assert PATTERN.startswith(config["hybrid_override_pattern"])
    assert [PATTERN.count(c) // 8 for c in "ME*"] == [
        config["hybrid_override_pattern"].count(c) for c in "ME*"] == [5, 5, 1]
    assert config["num_hidden_layers"] * 8 == PUBLISHED["num_hidden_layers"]
    # the share: a B/C group and its heads; a key-value head's queries
    assert config["mamba_num_heads"] * 8 == PUBLISHED["mamba_num_heads"]
    assert config["num_attention_heads"] * 8 == PUBLISHED[
        "num_attention_heads"]
    assert config["n_routed_experts"] * 64 == PUBLISHED["n_routed_experts"]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for said in ("shared by 64 chips", "experts 0-7 of 512",
                 "heads 0-15 of 128", "B/C group 0 of 8",
                 "query heads 0-3 of 32", "key-value head 0 of 2",
                 "rows 0-16383", "first of 8 pipeline stages",
                 "without its collective"):
        assert said in config["deployment"], said
    assumed = " ".join(config["assumed"])
    for said in ("sigmoid", "only selects", "gamma = 0.001",
                 "no norm and no activation on the latent",
                 "without rotation", "rope_theta", "time_step_floor",
                 "AdamW", "Zipf", "harness/weights_hybrid.py"):
        assert said in assumed, said
    assert "bfloat16" in config["precision"]
    # the names the accepted scan reader looks up repeat the row's
    assert (config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"]) == (
        config["mamba_num_heads"], config["mamba_head_dim"],
        config["ssm_state_size"])
    assert config["layer_types"].count("mamba") == 5


def test_file_reference_and_registered_config_agree(files):
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.models.nemotron_h import PATTERN as KIND

    _, _, cell, config = files
    cfg = get_config(config["registered"])
    lm, ref = cfg.model.lm, config["reference"]["arch"]
    assert cfg.model.name == config["model_type"] == "nemotron_h"
    assert (lm.hidden, lm.vocab, lm.norm_eps) == (
        config["hidden_size"], config["vocab_size"],
        config["layer_norm_epsilon"])
    types = [KIND[c] for c in config["hybrid_override_pattern"]]
    assert list(lm.layer_types) == types == config["layer_types"] \
        == ref["layer_types"]
    assert (lm.heads, lm.kv_heads, lm.head_dim) == (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"]) == (ref["heads"], ref["kv_heads"],
                                ref["head_dim"])
    assert (lm.ssm_heads, lm.ssm_head_dim, lm.ssm_state, lm.ssm_conv,
            lm.ssm_chunk) == (
        config["mamba_num_heads"], config["mamba_head_dim"],
        config["ssm_state_size"], config["conv_kernel"],
        config["chunk_size"])
    assert lm.ssm_heads * lm.ssm_head_dim * 8 == config["expand"] * lm.hidden
    assert (ref["ssm_heads"], ref["ssm_head_dim"], ref["ssm_state"],
            ref["ssm_groups"]) == (lm.ssm_heads, lm.ssm_head_dim,
                                   lm.ssm_state, config["n_groups"])
    assert (lm.experts, lm.experts_held, lm.top_k, lm.expert_width,
            lm.latent_width, lm.shared_width) == (
        PUBLISHED["n_routed_experts"], config["n_routed_experts"],
        config["num_experts_per_tok"], config["moe_intermediate_size"],
        config["moe_latent_size"],
        config["moe_shared_expert_intermediate_size"])
    assert (lm.routed_scaling_factor, lm.norm_topk_prob, lm.top_k,
            lm.first_expert, lm.bias_update_rate) == (
        config["routed_scaling_factor"], config["norm_topk_prob"],
        ref["top_k"], ref["first_expert"], ref["bias_update_rate"])
    assert ref["routed_scaling_factor"] == 5.0 and lm.topk_eps == 1e-20
    assert config["tie_word_embeddings"] is False
    assert config["weights"] == {
        "expert_bias_std": 0.0, "time_step_min": config["time_step_min"],
        "time_step_max": config["time_step_max"],
        "time_step_floor": config["time_step_floor"]}
    opt, ropt = cfg.optim, config["reference"]["optimizer"]
    assert (opt.optimizer, opt.lr, opt.weight_decay, opt.warmup_steps,
            opt.poly_power) == (ropt["kind"], ropt["lr"],
                                ropt["weight_decay"], ropt["warmup_steps"],
                                ropt["poly_power"])
    assert ropt["total_steps"] == cell["max_steps"]
    assert cfg.data.vocab == lm.vocab and cfg.global_batch_size == 1
    assert cfg.data.seq_len == 8192


def test_flops_per_step_is_a_hand_count(files):
    """The stored number, against the same count written out: a token's
    matrix products forward, the reference's block-causal attention
    (each 512-row block against the keys up to its last row), three
    times that for a step, and the recurrence's own multiply-adds."""
    _, _, cell, c = files
    n, d = 8192, c["hidden_size"]
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    mamba = d * (2 * inner + 2 * c["ssm_state_size"] + c["mamba_num_heads"]) \
        + inner * d
    assert mamba == 13_697_024
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    attn = d * hd * (2 * hq + 2 * hkv)
    assert attn == 5_242_880
    lat, f = c["moe_latent_size"], c["moe_intermediate_size"]
    share = c["num_experts_per_tok"] * c["n_routed_experts"] / 512
    assert share == 0.34375
    outside = d * 512 + 2 * d * lat \
        + 2 * d * c["moe_shared_expert_intermediate_size"]
    assert outside == 54_525_952
    head = d * c["vocab_size"]
    per_token = 5 * mamba + attn + 5 * (outside + share * 2 * lat * f) + head
    assert 2 * per_token == pytest.approx(845.86e6, rel=1e-4)
    forward = 2 * n * per_token + 2 * 2 * hq * hd * (n * (n + 512) // 2)
    recurrence = 3 * 4 * n * 5 * inner * c["ssm_state_size"]
    assert cell["flops_per_step"] == pytest.approx(3 * forward + recurrence,
                                                   rel=1e-12)
    assert 21.0e12 < cell["flops_per_step"] < 21.1e12


def test_the_routed_products_cost_is_the_works_own():
    """One expert layer's routed products over the balanced 2,816 pairs:
    two products forward, four backward; the 8 held experts' two
    matrices read once a pass, their float32 gradients written once.
    BYTE-bound on the chip's peaks: 44 M weights for 2,816 rows."""
    rows, lat, f, e = 2816.0, 1024, 2688, 8
    flops, nbytes = flops_hybrid.latent_experts_cost("fwd", rows, lat, f, e)
    assert flops == 2 * 2 * rows * lat * f
    assert nbytes == 2 * rows * (lat + f) * 2 + 2 * e * lat * f * 2
    fb, bb = flops_hybrid.latent_experts_cost("bwd", rows, lat, f, e)
    assert fb == 2 * flops
    assert bb == 4 * rows * (lat + f) * 2 + 2 * e * lat * f * (2 + 4)
    for fl, nb in ((flops, nbytes), (fb, bb)):
        assert flops_lm.roofline_s(fl, nb, V5E) \
            == nb / V5E["hbm_bytes_per_s"] > fl / V5E["bf16_flops_per_s"]


# -- the new readers on a synthetic run --------------------------------------

def _synthetic(config, monkeypatch, scopes):
    """A traced run of the cell whose trace reduced to ``scopes``
    (seconds over 4 traced steps)."""
    monkeypatch.setattr(scopes_hybrid, "_of_dir", lambda d: dict(scopes))
    return {"config": config, "seq_len": 8192, "tokens_per_step": 8192,
            "trace_dir": "somewhere", "traced_steps": 4,
            "device": {"peaks": V5E},
            "ticks": [{"moe_pairs_here_share": 0.014},
                      {"moe_pairs_here_share": 0.018}]}


def test_the_new_readers_read_a_synthetic_run(files, monkeypatch):
    config = files[3]
    run = _synthetic(config, monkeypatch, {
        "moe.route": 0.2, "moe.latent": 0.1, "moe.experts": 0.04,
        "moe.combine": 0.02, "moe.shared": 0.4, "moe.balance": 0.004})
    read = harness.load_reader
    assert read("train_latent_moe_ms")(run) == pytest.approx(191.0)
    assert read("train_latent_moe_route_ms")(run) == pytest.approx(56.0)
    assert read("train_latent_moe_shared_ms")(run) == pytest.approx(100.0)
    # 5 layers x 4 steps x (fwd + bwd) least over 0.04 s taken, at the
    # pairs the ticks counted: 0.016 x 22 x 8,192
    rows = 0.016 * 22 * 8192
    least = sum(flops_lm.roofline_s(
        *flops_hybrid.latent_experts_cost(k, rows, 1024, 2688, 8), V5E)
        for k in ("fwd", "bwd"))
    assert read("latent_moe_experts_roofline")(run) == pytest.approx(
        100 * least * 5 * 4 / 0.04)
    assert 0 < read("latent_moe_experts_roofline")(run) < 100


def test_the_new_readers_say_nothing_on_another_cells_run(monkeypatch):
    """The latent-attention cell's traced run: ``dsod.moe.*`` scopes and
    routing counters, but no latent: the reducer makes no table."""
    _, _, config = harness.resolve(harness.load_manifest(),
                                   "kimi_vl_a3b_ep8.train_s16k_b2")
    run = _synthetic(config, monkeypatch, {})
    for name in NEW:
        assert harness.load_reader(name)(run) is None, name


def test_the_reducer_takes_the_deepest_scope_and_needs_the_latent(
        monkeypatch):
    keyed = lambda path: (scopes_hybrid._SCOPE.findall(path)  # noqa: E731
                          or [scopes_hybrid.OTHER])[-1]
    inside = "jit(s)/dsod.encoder/layer_1/"
    assert keyed(inside + "moe/dsod.moe.route/router/dot") == "moe.route"
    assert keyed(inside + "moe/dsod.moe.latent/latent_down/dot") \
        == "moe.latent"
    assert keyed(inside + "moe/dsod.moe.experts/dsod.kernel.grouped_matmul/"
                 "pallas_call") == "moe.experts"
    assert keyed(inside + "dsod.moe.shared/shared/up/dot") == "moe.shared"
    assert keyed(inside + "dsod.ssm/mixer/dsod.ssm.scan/x") \
        == scopes_hybrid.OTHER
    # another model's expert layer (no latent) makes no table
    window = {"host": [], "devices": {}}
    monkeypatch.setattr(scopes_hybrid.spans, "window_of", lambda h: None)
    monkeypatch.setattr(
        scopes_hybrid.spans, "_clip", lambda events, w: events)
    ev = lambda path, at: ("op", at, 1.0, path)  # noqa: E731
    window["devices"] = {"/device:TPU:0": [
        ev(inside + "moe/dsod.moe.route/x", 0.0),
        ev(inside + "dsod.moe.shared/y", 1.0)]}
    assert scopes_hybrid.reduce(window) == {}
    window["devices"]["/device:TPU:0"].append(
        ev(inside + "moe/dsod.moe.latent/z", 2.0))
    assert set(scopes_hybrid.reduce(window)) == {"moe.route", "moe.shared",
                                                 "moe.latent"}
