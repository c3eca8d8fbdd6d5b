"""What PR 35 added to the manifest, checked without the chip: the new
cell resolves, every metric it is listed under has a reader that loads
and says nothing where there is nothing to read, the three new metrics
are listed for the cell, the configuration's file keeps every number of
the catalog row and lists each cut, and file, reference and registered
config tell one story."""

import json
import os

import pytest

from benchmark import run as harness
from benchmark.harness import flops_lm, flops_mla

CELL = "kimi_vl_a3b_ep8.train_s16k_b2"
NEW = ["flash_attention_mla_roofline", "train_moe_shared_ms",
       "train_attn_outside_kernel_ms"]
PUBLISHED = {  # the catalog row's `config`, moonshotai/Kimi-VL-A3B-Instruct
    "vocab_size": 163840, "max_position_embeddings": 131072,
    "hidden_size": 2048, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "num_hidden_layers": 27,
    "num_attention_heads": 16, "n_shared_experts": 2,
    "n_routed_experts": 64, "ep_size": 1, "routed_scaling_factor": 2.446,
    "kv_lora_rank": 512, "q_lora_rank": None, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "qk_nope_head_dim": 128, "topk_method": "noaux_tc",
    "n_group": 1, "topk_group": 1, "num_experts_per_tok": 6,
    "moe_layer_freq": 1, "first_k_dense_replace": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "seq_aux": True, "num_key_value_heads": 16,
    "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 800000,
    "rope_scaling": None, "attention_bias": False,
    "tie_word_embeddings": False}


@pytest.fixture(scope="module")
def files():
    manifest = harness.load_manifest()
    entry, cell, config = harness.resolve(manifest, CELL)
    return manifest, entry, cell, config


def test_cell_resolves_and_reports_what_the_issue_lists(files):
    manifest, entry, cell, config = files
    assert entry["chips"] == 1 and cell["runner"] == "train_lm"
    assert cell["overrides"] == ["global_batch_size=2", "data.seq_len=16384",
                                 "mesh.data=1", "log_every_steps=2"]
    assert (cell["warmup_ticks"], cell["trace_ticks"]) == (2, 4)
    names = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                     "per_layer")}
    assert set(NEW) <= names
    # the 13 that both older cells report, and none of the first token
    # model's seven (the yardstick pins those to its cell)
    both = {m["name"] for m in manifest["per_layer"] if {
        "basnet_ds.train_b16", "lfm2_8b_a1b_ep4.train_s8k_b4"}
        <= set(m["workloads"])}
    assert len(both) == 13 and both <= names and len(names) == 16
    assert {m["name"] for m in harness.cell_metrics(
        manifest, CELL, "end_to_end")} == {"train_img_per_s_chip", "setup_s"}
    assert len(entry["why"]) <= 200 and entry["why"] == cell["why"]


def test_every_reader_of_the_cell_loads_and_finds_nothing_in_an_empty_run(
        files):
    manifest = files[0]
    for m in harness.cell_metrics(manifest, CELL, "per_layer"):
        read = harness.load_reader(m["name"])
        # a run with no trace and no counters (the parent, a CPU run)
        assert read({"ticks": [], "trace_dir": None, "trace": None}) is None


@pytest.mark.parametrize("name", NEW)
def test_new_metric_lists_the_cell(files, name):
    manifest, _, _, config = files
    (m,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert CELL in m["workloads"] and m["moves"] == "train_img_per_s_chip"
    assert m["layer"] == "kernels and XLA fusions"
    assert m["source"] == "device_trace"
    if name.endswith("_roofline"):
        assert m["unit"] == "%" and m["better"] == "higher"
    # the other token model's configuration gives these readers nothing
    other = harness.resolve(manifest, "lfm2_8b_a1b_ep4.train_s8k_b4")[2]
    assert harness.load_reader(name)(
        {"config": other, "seq_len": 8192, "tokens_per_step": 32768,
         "trace_dir": None, "traced_steps": 3}) is None


def test_config_file_keeps_published_numbers_and_lists_each_cut(files):
    manifest, _, _, config = files
    (conf,) = [c for c in manifest["configs"]
               if c["name"] == "kimi_vl_a3b_ep8"]
    assert set(PUBLISHED) <= set(config)  # every key of the row
    differs = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == set(conf["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    for k in differs:  # the published value is stated beside the cut
        assert config["published"][k] == PUBLISHED[k]
    assert config["source"] == conf["source"]
    assert config["source"].endswith("config.json (text_config)")
    assert "8 chips that share each layer" in config["deployment"]
    # floors of a model_config PR: the dense layer + 4 expert layers, 8
    # experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 5
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # what the first token model's readers look up, for the benchmark PR
    # that points them at this cell
    assert config["num_experts"] == config["n_routed_experts"]
    assert config["assumed"] and config["weights"] == {"expert_bias_std": 0.0}


def test_file_reference_and_registered_config_agree(files):
    from distributed_sod_project_tpu.configs import get_config

    _, _, cell, config = files
    cfg = get_config(config["registered"])
    lm, ref = cfg.model.lm, config["reference"]["arch"]
    assert (lm.hidden, lm.dense_width, lm.expert_width, lm.vocab) == (
        config["hidden_size"], config["intermediate_size"],
        config["moe_intermediate_size"], config["vocab_size"])
    assert (lm.heads, lm.head_dim - lm.rope_dim, lm.rope_dim, lm.v_dim,
            lm.kv_rank) == (
        config["num_attention_heads"], config["qk_nope_head_dim"],
        config["qk_rope_head_dim"], config["v_head_dim"],
        config["kv_lora_rank"]) == (
        ref["heads"], ref["nope_dim"], ref["rope_dim"], ref["v_dim"],
        ref["kv_rank"])
    assert (lm.experts, lm.experts_held, lm.first_expert, lm.top_k,
            lm.shared_experts) == (
        config["router_width"], config["n_routed_experts"],
        config["first_expert"], config["num_experts_per_tok"],
        config["n_shared_experts"])
    assert ref["top_k"] == lm.top_k and ref["first_expert"] == lm.first_expert
    assert list(lm.ffn_types) == ref["ffn_types"] \
        and len(lm.ffn_types) == config["num_hidden_layers"] \
        and lm.ffn_types.count("dense") == config["first_k_dense_replace"]
    assert (lm.norm_eps, lm.rope_theta, lm.routed_scaling_factor,
            lm.bias_update_rate, lm.topk_eps) == (
        config["rms_norm_eps"], config["rope_theta"],
        config["routed_scaling_factor"], config["bias_update_rate"], 1e-20)
    assert (ref["norm_eps"], ref["rope_theta"], ref["routed_scaling_factor"],
            ref["bias_update_rate"]) == (
        lm.norm_eps, lm.rope_theta, lm.routed_scaling_factor,
        lm.bias_update_rate)
    opt, ropt = cfg.optim, config["reference"]["optimizer"]
    assert (opt.optimizer, opt.lr, opt.weight_decay, opt.warmup_steps) == (
        ropt["kind"], ropt["lr"], ropt["weight_decay"], ropt["warmup_steps"])
    assert ropt["total_steps"] == cell["max_steps"]
    assert cfg.data.vocab == lm.vocab


def test_flops_per_step_is_what_the_counter_gives(files):
    """The stored number, against the closed form of the same count."""
    _, _, cell, c = files
    seqs, n, blk = 2, 16384, 512
    t, d, v, h = seqs * n, c["hidden_size"], c["vocab_size"], \
        c["num_attention_heads"]
    dk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    dv, rank = c["v_head_dim"], c["kv_lora_rank"]
    attn = (d * h * dk + d * (rank + c["qk_rope_head_dim"])
            + rank * h * (c["qk_nope_head_dim"] + dv) + h * dv * d)
    expert = 3 * d * c["moe_intermediate_size"]
    share = c["num_experts_per_tok"] * c["n_routed_experts"] \
        / c["router_width"]
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    params = (c["num_hidden_layers"] * attn + 3 * d * c["intermediate_size"]
              + n_moe * ((c["n_shared_experts"] + share) * expert
                         + d * c["router_width"]) + v * d)
    # q k^T over 192 columns and p v over 128, 2 per multiply-add, each
    # block of query rows against the keys up to its last row; forward +
    # two backward products each
    scores = c["num_hidden_layers"] * seqs * 3 * 2 * h * (dk + dv) * sum(
        blk * (r + blk) for r in range(0, n, blk))
    want = 6.0 * t * params + scores
    assert share == 0.75
    assert cell["flops_per_step"] == pytest.approx(want, rel=0.01)


def test_kernel_costs_are_the_algorithms_own():
    b, h, n = 2, 16, 16384
    pairs = b * h * n * (n + 1) / 2
    f, nbytes = flops_mla.flash_mla_cost("fwd", b, h, n, 128, 64, 128)
    assert f == 2 * pairs * (192 + 128)
    per_head = b * h * n * 2
    # q (192), k_nope, v, o per head; the rotary key once per token
    assert nbytes == per_head * (192 + 128 + 128 + 128) + b * n * 64 * 2 \
        + b * h * n * 4
    assert flops_mla.flash_mla_cost("dq", b, h, n, 128, 64, 128)[0] \
        == 2 * pairs * (192 + 128 + 192)
    assert flops_mla.flash_mla_cost("dkv", b, h, n, 128, 64, 128)[0] \
        == 2 * pairs * (192 + 128 + 128 + 192)
    peaks = json.load(open(os.path.join(
        os.path.dirname(harness.__file__), "harness", "peaks.json")))
    assert flops_lm.roofline_s(f, nbytes, peaks["TPU v5 lite"]) == f / 197e12
