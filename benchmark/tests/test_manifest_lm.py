"""What PR 28 added to the manifest, checked without the chip: every new
per-layer metric has a reader and a cell, a reader that finds nothing
says so, the new configuration's file keeps every published number and
lists each cut, and file, reference and registered config tell one story."""

import json
import os

import pytest

from benchmark import run as harness
from benchmark.harness import flops_lm

CELL = "lfm2_8b_a1b_ep4.train_s8k_b4"
NEW = ["train_moe_ms", "train_moe_dispatch_ms", "train_attn_ms",
       "train_shortconv_ms", "moe_experts_roofline",
       "flash_attention_causal_roofline", "moe_load_max_over_mean"]
PUBLISHED = {  # the catalog row's `config`, LiquidAI/LFM2-8B-A1B
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


@pytest.fixture(scope="module")
def files():
    manifest = harness.load_manifest()
    entry, cell, config = harness.resolve(manifest, CELL)
    return manifest, entry, cell, config


@pytest.mark.parametrize("name", NEW)
def test_new_metric_has_a_reader_and_the_cell(files, name):
    manifest = files[0]
    (m,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert m["workloads"] == [CELL] and m["moves"] == "train_img_per_s_chip"
    read = harness.load_reader(name)
    # a run with no trace and no counters (the parent, an image cell)
    assert read({"ticks": [], "trace_dir": None, "trace": None}) is None
    if name.endswith("_roofline"):
        assert m["unit"] == "%"


def test_cell_reports_what_the_issue_lists(files):
    manifest, entry, cell, _ = files
    assert entry["chips"] == 1 and cell["runner"] == "train_lm"
    names = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                     "per_layer")}
    assert set(NEW) <= names
    assert {"train_step_mfu", "peak_hbm_gib", "train_stage_ms.encoder",
            "train_stage_ms.update", "device_idle_share.train"} <= names
    assert not {"train_stage_ms.decoder", "train_resample_ms"} & names
    assert {m["name"] for m in harness.cell_metrics(
        manifest, CELL, "end_to_end")} == {"train_img_per_s_chip", "setup_s"}


def test_config_file_keeps_published_numbers_and_lists_each_cut(files):
    manifest, _, _, config = files
    (conf,) = [c for c in manifest["configs"]
               if c["name"] == "lfm2_8b_a1b_ep4"]
    differs = {k for k, v in PUBLISHED.items() if config.get(k) != v}
    assert differs | {"layer_types"} == set(conf["reduced"]) \
        == set(config["reduced"])
    for k in differs:  # the published value is stated beside the cut
        assert config["published"][k] == PUBLISHED[k]
    assert config["source"] == conf["source"]
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    # floors of a model_config PR: a period + the dense layer, 8 experts,
    # an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 5 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_file_reference_and_registered_config_agree(files):
    from distributed_sod_project_tpu.configs import get_config

    _, _, cell, config = files
    lm = get_config(config["registered"]).model.lm
    ref = config["reference"]["arch"]
    assert (lm.hidden, lm.dense_width, lm.expert_width) == (
        config["hidden_size"], config["intermediate_size"],
        config["moe_intermediate_size"])
    assert (lm.heads, lm.kv_heads, lm.head_dim) == (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"]) == (ref["heads"], ref["kv_heads"],
                                ref["head_dim"])
    assert (lm.experts, lm.experts_held, lm.top_k, lm.first_expert) == (
        config["router_width"], config["num_experts"],
        config["num_experts_per_tok"], config["first_expert"])
    assert ref["top_k"] == lm.top_k and lm.vocab == config["vocab_size"]
    assert list(lm.layer_types) == ref["layer_types"] == [
        t.replace("full_", "") for t in config["layer_types"]]
    assert list(lm.ffn_types) == ref["ffn_types"]
    assert lm.ffn_types.count("dense") == config["num_dense_layers"]
    assert (lm.conv_kernel, lm.norm_eps, lm.rope_theta) == (
        config["conv_L_cache"], config["norm_eps"], config["rope_theta"])
    opt = get_config(config["registered"]).optim
    ropt = config["reference"]["optimizer"]
    assert (opt.optimizer, opt.lr, opt.weight_decay, opt.warmup_steps,
            opt.poly_power) == (ropt["kind"], ropt["lr"],
                                ropt["weight_decay"], ropt["warmup_steps"],
                                ropt["poly_power"])
    assert ropt["total_steps"] == cell["max_steps"]


def test_flops_per_step_is_what_the_counter_gives(files):
    """The stored number, against the closed form of the same count."""
    _, _, cell, c = files
    t, d, v = 4 * 8192, c["hidden_size"], c["vocab_size"]
    hd = c["head_dim"]
    conv = 4 * d * d  # in_proj d -> 3d, out_proj d -> d
    attn = (2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"]) \
        * hd * d
    experts = 3 * d * c["moe_intermediate_size"]  # one expert a token
    n_conv = c["layer_types"].count("conv")
    n_moe = c["num_hidden_layers"] - c["num_dense_layers"]
    params = (n_conv * conv + attn + 3 * d * c["intermediate_size"]
              + n_moe * (experts + d * c["router_width"]) + v * d)
    n, blk, seqs = 8192, 512, 4
    # q k^T and p v, 2 per multiply-add, each block of query rows against
    # the keys up to its last row; forward + two backward products each
    scores = seqs * 3 * 4 * c["num_attention_heads"] * hd * sum(
        blk * (r + blk) for r in range(0, n, blk))
    # every product has a forward, a dx and a dw (the embedding lookup is
    # no product; the tied head is)
    want = 6.0 * t * params + scores
    assert cell["flops_per_step"] == pytest.approx(want, rel=0.01)


def test_kernel_costs_are_the_algorithms_own():
    f, b = flops_lm.grouped_matmul_cost(1000, 2048, 1792, 8)
    assert f == 2 * 1000 * 2048 * 1792
    assert b == (1000 * (2048 + 1792) + 8 * 2048 * 1792) * 2
    f, b = flops_lm.flash_causal_cost("fwd", 4, 32, 8, 8192, 64)
    assert f == 2 * 2 * 4 * 32 * (8192 * 8193 / 2) * 64
    peaks = json.load(open(os.path.join(
        os.path.dirname(harness.__file__), "harness", "peaks.json")))
    assert flops_lm.roofline_s(f, b, peaks["TPU v5 lite"]) == f / 197e12
