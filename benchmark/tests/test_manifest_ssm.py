"""What PR 37 added to the manifest, checked without the chip: the
cell resolves, every metric it is listed under has a reader that loads
and says nothing where there is nothing to read, the four state-space
metrics list the cell, the configuration's file keeps every
number of the catalog row and lists each cut, file, reference and
registered config tell one story, and the work the roofline share is
measured against is the recurrence's own.  Nothing here pins where in
a list an entry stands or what else a list holds: a later PR appends
its own cells, configurations and metrics, and lists its cells under
these metrics, without an edit to this file."""

import json
import os

import pytest

from benchmark import run as harness
from benchmark.harness import flops_lm, flops_ssm

CELL = "granite_4_0_h_micro_pp4.train_s16k_b1"
NEW = ["train_ssm_ms", "train_ssm_scan_ms", "train_ssm_conv_ms",
       "ssm_scan_roofline"]
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
PUBLISHED = {  # the catalog row's `config`, ibm-granite/granite-4.0-h-micro
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (PERIOD + ["mamba"] * 5 + ["attention"] + ["mamba"] * 9
                    + ["attention"] + ["mamba"] * 9 + ["attention"]
                    + ["mamba"] * 4),
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


@pytest.fixture(scope="module")
def files():
    manifest = harness.load_manifest()
    entry, cell, config = harness.resolve(manifest, CELL)
    return manifest, entry, cell, config


def test_cell_resolves_and_reports_what_the_issue_lists(files):
    manifest, entry, cell, config = files
    assert entry["chips"] == 1 and cell["runner"] == "train_ssm"
    assert cell["overrides"] == ["global_batch_size=1", "data.seq_len=16384",
                                 "mesh.data=1", "log_every_steps=2"]
    assert (cell["warmup_ticks"], cell["trace_ticks"]) == (2, 4)
    names = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                     "per_layer")}
    # what the three older cells all report (the whole step's share of
    # the peak among it) and the four state-space metrics
    shared = {m["name"] for m in manifest["per_layer"] if {
        "basnet_ds.train_b16", "lfm2_8b_a1b_ep4.train_s8k_b4",
        "kimi_vl_a3b_ep8.train_s16k_b2"} <= set(m["workloads"])}
    assert "train_step_mfu" in shared and shared | set(NEW) <= names
    assert {"train_img_per_s_chip", "setup_s"} <= {
        m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                "end_to_end")}
    assert len(entry["why"]) <= 200 and entry["why"] == cell["why"]
    # the seven judged numbers and no routing row
    assert set(cell["limits"]) == {
        "loss_rel_gap.step1", "loss_rel_gap.step2", "loss_rel_gap.step3",
        "grad_norm_median_leaf_gap", "grad_norm_worst_leaf_gap",
        "dparam_norm_median_leaf_gap", "dparam_zero_leaf_share"}
    assert cell["limits"]["dparam_zero_leaf_share"] == 0.0


def test_every_reader_of_the_cell_loads_and_finds_nothing_in_an_empty_run(
        files):
    manifest = files[0]
    for m in harness.cell_metrics(manifest, CELL, "per_layer"):
        read = harness.load_reader(m["name"])
        # a run with no trace and no counters (the parent, a CPU run)
        assert read({"ticks": [], "trace_dir": None, "trace": None}) is None


@pytest.mark.parametrize("name", NEW)
def test_state_space_metric_lists_the_cell(files, name):
    manifest, _, _, config = files
    (m,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert CELL in m["workloads"] and m["moves"] == "train_img_per_s_chip"
    assert m["layer"] == "kernels and XLA fusions"
    assert m["source"] == "device_trace"
    if name.endswith("_roofline"):
        assert m["unit"] == "%" and m["better"] == "higher"
    # a traced run of this configuration that names no such scope (the
    # parent's program under this PR's benchmark files) reads nothing
    peaks = json.load(open(os.path.join(
        os.path.dirname(harness.__file__), "harness", "peaks.json")))
    assert harness.load_reader(name)(
        {"config": config, "seq_len": 16384, "tokens_per_step": 16384,
         "trace_dir": None, "traced_steps": 8,
         "device": {"peaks": peaks["TPU v5 lite"]}}) is None


def test_the_entries_stand_after_the_older_ones(files):
    """Appended, not inserted: the cell, its configuration and the four
    metrics come after what the benchmark had, wherever a list ends."""
    manifest = files[0]

    def at(entries, name):
        return [e["name"] for e in entries].index(name)

    assert at(manifest["workloads"], CELL) > at(
        manifest["workloads"], "kimi_vl_a3b_ep8.train_s16k_b2")
    assert at(manifest["configs"], "granite_4_0_h_micro_pp4") > at(
        manifest["configs"], "kimi_vl_a3b_ep8")
    # (the last metric the benchmark had before them)
    older = at(manifest["per_layer"], "train_attn_outside_kernel_ms")
    assert all(at(manifest["per_layer"], n) > older for n in NEW)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        w = m.get("workloads", [])
        if CELL in w and "kimi_vl_a3b_ep8.train_s16k_b2" in w:
            assert w.index(CELL) > w.index("kimi_vl_a3b_ep8.train_s16k_b2")


def test_config_file_keeps_published_numbers_and_lists_each_cut(files):
    manifest, _, _, config = files
    (conf,) = [c for c in manifest["configs"]
               if c["name"] == "granite_4_0_h_micro_pp4"]
    assert set(PUBLISHED) <= set(config)  # every key of the row
    differs = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == set(conf["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert config["published"] == {"num_hidden_layers": 40,
                                   "vocab_size": 100352}
    assert config["source"] == conf["source"]
    assert config["source"].endswith("granite-4.0-h-micro/blob/main/"
                                     "config.json")
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    # one whole period, layers 0-9, in the published ratio
    assert config["layer_types"] == PUBLISHED["layer_types"][:10] == PERIOD
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 10
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for said in ("4 stages", "No layer is divided", "8 chips",
                 "first eighth"):
        assert said in config["deployment"], said
    assert config["assumed"] and config["head_dim"] == 64


def test_file_reference_and_registered_config_agree(files):
    from distributed_sod_project_tpu.configs import get_config

    _, _, cell, config = files
    cfg = get_config(config["registered"])
    lm, ref = cfg.model.lm, config["reference"]["arch"]
    assert (lm.hidden, lm.dense_width, lm.vocab) == (
        config["hidden_size"], config["shared_intermediate_size"],
        config["vocab_size"])
    assert (lm.heads, lm.kv_heads, lm.head_dim) == (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"]) == (ref["heads"], ref["kv_heads"],
                                ref["head_dim"])
    assert (lm.ssm_heads, lm.ssm_head_dim, lm.ssm_state, lm.ssm_conv,
            lm.ssm_chunk) == (
        config["mamba_n_heads"], config["mamba_d_head"],
        config["mamba_d_state"], config["mamba_d_conv"],
        config["mamba_chunk_size"])
    assert (ref["ssm_heads"], ref["ssm_head_dim"], ref["ssm_state"]) == (
        lm.ssm_heads, lm.ssm_head_dim, lm.ssm_state)
    assert lm.ssm_heads * lm.ssm_head_dim \
        == config["mamba_expand"] * config["hidden_size"]
    assert (lm.embedding_multiplier, lm.residual_multiplier,
            lm.attention_multiplier, lm.logits_scaling) == (
        config["embedding_multiplier"], config["residual_multiplier"],
        config["attention_multiplier"], config["logits_scaling"]) == (
        ref["embedding_multiplier"], ref["residual_multiplier"],
        ref["attention_multiplier"], ref["logits_scaling"])
    assert config["position_embedding_type"] == "nope"
    assert list(lm.layer_types) == ref["layer_types"] \
        == config["layer_types"]
    assert lm.norm_eps == config["rms_norm_eps"] == ref["norm_eps"]
    opt, ropt = cfg.optim, config["reference"]["optimizer"]
    assert (opt.optimizer, opt.lr, opt.weight_decay, opt.warmup_steps,
            opt.poly_power) == (ropt["kind"], ropt["lr"],
                                ropt["weight_decay"], ropt["warmup_steps"],
                                ropt["poly_power"])
    assert ropt["total_steps"] == cell["max_steps"]
    assert cfg.data.vocab == lm.vocab and cfg.global_batch_size == 1


def test_flops_per_step_is_what_the_counter_gives(files):
    """The stored number, against the closed form of the same count."""
    _, _, cell, c = files
    n, blk, d, v = 16384, 512, c["hidden_size"], c["vocab_size"]
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    mixer = d * (2 * inner + 2 * c["mamba_d_state"] + c["mamba_n_heads"]) \
        + inner * d
    hd = c["head_dim"]
    attn = (2 * c["num_attention_heads"]
            + 2 * c["num_key_value_heads"]) * hd * d
    ffn = 3 * d * c["shared_intermediate_size"]
    n_mamba = c["layer_types"].count("mamba")
    params = n_mamba * mixer + attn + 10 * ffn + v * d
    # q k^T and p v, 2 per multiply-add, each block of query rows against
    # the keys up to its last row; forward + two backward products each
    scores = 3 * 4 * c["num_attention_heads"] * hd * sum(
        blk * (r + blk) for r in range(0, n, blk))
    recurrence = flops_ssm.recurrence_flops(
        n, n_mamba, c["mamba_n_heads"], c["mamba_d_head"],
        c["mamba_d_state"])
    assert recurrence == 3 * 4 * n * 9 * 64 * 64 * 128
    want = 6.0 * n * params + scores + recurrence
    assert cell["flops_per_step"] == pytest.approx(want, rel=0.01)


def test_the_scans_cost_is_the_recurrences_own():
    """FLOPs and bytes of ONE layer's scan over 16,384 tokens: the
    recurrence's multiply-adds, every operand read once and every result
    written once; byte-bound on the chip's peaks."""
    f, nbytes = flops_ssm.ssd_scan_cost("fwd", 1, 16384, 64, 64, 128)
    assert f == 4 * 16384 * 64 * 64 * 128
    # x and y 4,096 bf16 columns, B and C 128, delta 64 float32 (+ A)
    assert nbytes == 16384 * (2 * 8192 + 2 * 256 + 256) + 256
    fb, bb = flops_ssm.ssd_scan_cost("bwd", 1, 16384, 64, 64, 128)
    assert fb == 2 * f
    assert bb == 16384 * (3 * 8192 + 4 * 256 + 2 * 256) + 512
    peaks = json.load(open(os.path.join(
        os.path.dirname(harness.__file__), "harness", "peaks.json")))
    v5e = peaks["TPU v5 lite"]
    assert flops_lm.roofline_s(f, nbytes, v5e) \
        == nbytes / v5e["hbm_bytes_per_s"] > f / v5e["bf16_flops_per_s"]
