"""What PR 47 added to the manifest, checked without the chip: the cell
resolves, every metric it is listed under has a reader that loads and
says nothing where there is nothing to read, the five new metrics list
the cell, the configuration's file keeps every number of the catalog
row and lists each cut and each assumption, file, reference and
registered config tell one story, the step's FLOPs and the two kernels'
costs are a hand count, and each new reader reads a synthetic run and
says nothing on another cell's.  Membership and relative order only:
nothing here pins where a list ends, how long it is, or what else it
holds."""

import json
import os

import pytest

from benchmark import run as harness
from benchmark.harness import (flops_lm, flops_phi4flash, scopes_phi4flash,
                               scopes_ssm)

CELL = "phi4_mini_flash_pp5.train_s16k_b1"
CONFIG = "phi4_mini_flash_pp5"
NEW = ["train_diff_attn_window_ms", "train_diff_attn_full_ms",
       "train_gmu_ms", "selective_scan_roofline", "diff_attention_roofline"]
SSM = ["train_ssm_ms", "train_ssm_scan_ms", "train_ssm_conv_ms"]
KINDS = ["mamba", "window", "mamba", "full", "gmu", "cross"]
PUBLISHED = {  # the catalog row's `config`,
    # microsoft/Phi-4-mini-flash-reasoning
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
V5E = json.load(open(os.path.join(os.path.dirname(harness.__file__),
                                  "harness", "peaks.json")))["TPU v5 lite"]


@pytest.fixture(scope="module")
def files():
    manifest = harness.load_manifest()
    entry, cell, config = harness.resolve(manifest, CELL)
    return manifest, entry, cell, config


def test_cell_resolves_and_reports_what_the_issue_lists(files):
    manifest, entry, cell, config = files
    assert entry["chips"] == 1 and cell["runner"] == "train_phi4flash"
    assert cell["overrides"] == ["global_batch_size=1", "data.seq_len=16384",
                                 "mesh.data=1", "log_every_steps=2"]
    assert (cell["warmup_ticks"], cell["trace_ticks"]) == (2, 4)
    names = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                     "per_layer")}
    # what every older training cell reports (the whole step's share of
    # the peak among it), the three state-space times, the seven set-up
    # phases and the five new metrics
    shared = {m["name"] for m in manifest["per_layer"] if {
        "basnet_ds.train_b16", "lfm2_8b_a1b_ep4.train_s8k_b4",
        "granite_4_0_h_micro_pp4.train_s16k_b1"} <= set(m["workloads"])}
    setup = {m["name"] for m in manifest["per_layer"]
             if m["name"].startswith("setup_")}
    assert "train_step_mfu" in shared and len(setup) == 7
    assert shared | setup | set(SSM) | set(NEW) <= names
    assert "ssm_scan_roofline" not in names   # Mamba-2's work, not this
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
def test_new_metric_lists_the_cell(files, name):
    manifest, _, _, config = files
    (m,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert m["workloads"] == [CELL] and m["moves"] == "train_img_per_s_chip"
    assert m["layer"] == "kernels and XLA fusions"
    assert m["source"] == "device_trace"
    if name.endswith("_roofline"):
        assert m["unit"] == "%" and m["better"] == "higher"
    else:
        assert m["unit"] == "ms" and m["better"] == "lower"
    # a traced run of this configuration that names no such scope (the
    # parent's program under this PR's benchmark files) reads nothing
    assert harness.load_reader(name)(
        {"config": config, "seq_len": 16384, "tokens_per_step": 16384,
         "trace_dir": None, "traced_steps": 8,
         "device": {"peaks": V5E}}) is None


def test_the_entries_stand_after_the_older_ones(files):
    """Appended, not inserted: the cell, its configuration and the five
    metrics come after what the benchmark had, wherever a list ends."""
    manifest = files[0]
    last = "nemotron_3_super_tp8_ep64.train_s8k_b1"

    def at(entries, name):
        return [e["name"] for e in entries].index(name)

    assert at(manifest["workloads"], CELL) > at(manifest["workloads"], last)
    assert at(manifest["configs"], CONFIG) > at(
        manifest["configs"], "nemotron_3_super_tp8_ep64")
    older = at(manifest["per_layer"], "latent_moe_experts_roofline")
    assert all(at(manifest["per_layer"], n) > older for n in NEW)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        w = m.get("workloads", [])
        if CELL in w and last in w:
            assert w.index(CELL) > w.index(last)


def test_config_file_keeps_published_numbers_and_lists_each_cut(files):
    manifest, _, _, config = files
    (conf,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert set(PUBLISHED) <= set(config)  # every key of the row
    differs = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == set(conf["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "vocab_size"}
    assert config["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 200064}
    assert config["source"] == conf["source"]
    assert config["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    # published layers 14-19: each of the five kinds, in the published
    # placement (even: Mamba family; odd: attention family)
    assert config["layer_kinds"] == KINDS and config["first_layer"] == 14
    assert config["num_hidden_layers"] == len(KINDS) == 6 >= 4
    assert all((kind in ("mamba", "gmu")) == ((14 + i) % 2 == 0)
               for i, kind in enumerate(KINDS))
    assert config["vocab_size"] == 196 * 128 >= PUBLISHED["vocab_size"] / 8
    assert config["vocab_size"] - 128 < PUBLISHED["vocab_size"] / 8
    for said in ("5 stages", "14-19", "No layer is divided", "8 chips",
                 "rows 0-25087"):
        assert said in config["deployment"], said
    for said in ("ONE reader", "seven", "2 of 6", "8 of 32"):
        assert said in config["why"], said
    for said in ("16 is the Mamba-1 layer whose scan output is kept",
                 "17 the one full attention layer", "mb_per_layer 2"):
        assert said in config["placement"], said
    # no width is cut or assumed away: the Mamba-1 sizes follow the row's
    assert (config["mamba_d_inner"], config["mamba_dt_rank"],
            config["mamba_d_state"], config["mamba_d_conv"]) == (
        config["mamba_expand"] * config["hidden_size"],
        -(-config["hidden_size"] // 16), 16, 4)
    assert config["head_dim"] * config["num_attention_heads"] \
        == config["hidden_size"]
    for piece in ("float32", "bfloat16", "delta", "softmax", "lambda"):
        assert piece in config["precision"], piece


@pytest.mark.parametrize("said", [
    "state 16", "dt_rank = ceil(2560 / 16) = 160", "the mixer's form",
    "placement", "arXiv:2410.05258", "heads pair by parity",
    "PUBLISHED layer index", "biases on W_qkv, W_q and W_o",
    "512 keys, its own among them", "BEFORE the gate", "NOT shared",
    "head size 64", "A_log[c, n] = log(n + 1)", "NOT reset at a document join",
    "AdamW", "Zipf(1.0)"])
def test_the_file_lists_what_it_assumed(files, said):
    assert sum(said in line for line in files[3]["assumed"]) == 1, said


def test_file_reference_and_registered_config_agree(files):
    from distributed_sod_project_tpu.configs import get_config

    _, _, cell, config = files
    cfg = get_config(config["registered"])
    lm, ref = cfg.model.lm, config["reference"]["arch"]
    assert cfg.model.name == config["model_type"] == "phi4flash"
    assert config["reference"]["model"] == "phi4flash"
    assert (lm.hidden, lm.vocab, lm.norm_eps, lm.dense_width) == (
        config["hidden_size"], config["vocab_size"],
        config["layer_norm_eps"], config["intermediate_size"])
    assert list(lm.layer_types) == config["layer_kinds"] \
        == ref["layer_types"]
    assert (lm.heads, lm.kv_heads, lm.head_dim, lm.window,
            lm.first_layer) == (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], config["sliding_window"],
        config["first_layer"]) == (
        ref["heads"], ref["kv_heads"], ref["head_dim"], ref["window"],
        ref["first_layer"])
    assert (lm.ssm_heads * lm.ssm_head_dim, lm.ssm_state, lm.ssm_conv,
            lm.ssm_dt_rank) == (
        config["mamba_d_inner"], config["mamba_d_state"],
        config["mamba_d_conv"], config["mamba_dt_rank"])
    assert (ref["ssm_state"], ref["ssm_dt_rank"], ref["norm_eps"]) == (
        lm.ssm_state, lm.ssm_dt_rank, lm.norm_eps)
    assert config["tie_word_embeddings"] is True and config["weights"] == {}
    opt, ropt = cfg.optim, config["reference"]["optimizer"]
    assert (opt.optimizer, opt.lr, opt.weight_decay, opt.warmup_steps,
            opt.poly_power) == (ropt["kind"], ropt["lr"],
                                ropt["weight_decay"], ropt["warmup_steps"],
                                ropt["poly_power"])
    assert ropt["total_steps"] == cell["max_steps"]
    assert cfg.data.vocab == lm.vocab and cfg.global_batch_size == 1
    assert cfg.data.seq_len == 16384


def test_the_parameters_are_the_issues_sums(files):
    """697.2 M parameters: 7.79 GiB of float32 weights and Adam moments."""
    import jax
    import jax.numpy as jnp

    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.models import build_model

    model = build_model(get_config(files[3]["registered"]).model)
    shapes = jax.eval_shape(lambda r, t: model.init(r, t),
                            jax.random.key(0), jnp.zeros((1, 128), jnp.int32))
    per_layer = {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
                 for k, v in shapes["params"].items()}
    # mixer + feed-forward (3 x 2,560 x 10,240) + the two LayerNorms
    ffn = 3 * 2560 * 10240 + 4 * 2560
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 + 5120 * 16 \
        + 5 * 5120 + 5120 + 5120 * 2560
    attn = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 128 + 4 * 64
    cross = 2 * (2560 * 2560 + 2560) + 128 + 4 * 64
    assert [per_layer[f"layer_{i}"] - ffn for i in range(6)] == [
        mamba, attn, mamba, attn, 2 * 2560 * 5120, cross]
    assert per_layer["embed"] == 25088 * 2560
    total = sum(per_layer.values())
    assert 697.0e6 < total < 697.5e6
    assert 7.78 < total * 12 / 2 ** 30 < 7.81


def test_flops_per_step_is_a_hand_count(files):
    """The stored number, against the same count written out: a token's
    matrix products forward, the reference's block attention (each
    512-row block against the keys its mask admits: up to its last row,
    and in the window layer from 511 rows before its first), both
    softmax maps of all 20 query pairs at 64-wide keys and 128-wide
    values, three times that for a step, and the recurrence's own
    multiply-adds."""
    _, _, cell, c = files
    n, d, f = 16384, c["hidden_size"], c["intermediate_size"]
    inner, r, s = c["mamba_d_inner"], c["mamba_dt_rank"], c["mamba_d_state"]
    mamba = d * 2 * inner + inner * (r + 2 * s) + r * inner + inner * d
    assert mamba == 41_123_840
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    attn = d * (hq + 2 * hkv) * hd + hq * hd * d
    cross, gmu = 2 * d * hq * hd, 2 * d * inner
    per_token = 2 * mamba + 2 * attn + gmu + cross + 6 * 3 * d * f \
        + d * c["vocab_size"]
    assert per_token == 696_975_360
    full = n * (n + 512) // 2
    window = 512 * 512 + (n // 512 - 1) * 512 * (511 + 512)
    maps = 2 * hq * (hd + 2 * hd)   # q k^T and p v, 2 per multiply-add
    forward = 2 * n * per_token + maps * (2 * full + window)
    recurrence = 3 * 4 * n * 2 * inner * s
    assert cell["flops_per_step"] == pytest.approx(3 * forward + recurrence,
                                                   rel=1e-12)
    assert 82.0e12 < cell["flops_per_step"] < 82.1e12
    # the shares the cell's `why` names
    assert 3 * maps * 2 * full / cell["flops_per_step"] \
        == pytest.approx(0.155, abs=0.001)
    assert 3 * 2 * n * 6 * 3 * d * f / cell["flops_per_step"] \
        == pytest.approx(0.565, abs=0.001)


def test_the_two_kernels_costs_are_the_works_own():
    """One Mamba-1 layer's recurrence over 16,384 tokens: BYTE-bound on
    the chip's peaks (5.4 GFLOP against 0.67 GB).  One differential
    layer's attention: FLOP-bound, the band of a window layer 1/16.5 of
    a full layer's triangle."""
    n, ch, s = 16384, 5120, 16
    flops, nbytes = flops_phi4flash.selective_scan_cost("fwd", 1, n, ch, s)
    assert flops == 4.0 * n * ch * s
    assert nbytes == n * ch * (2 + 4 + 2) + 2 * n * s * 2 + ch * (s + 1) * 4
    fb, bb = flops_phi4flash.selective_scan_cost("bwd", 1, n, ch, s)
    assert fb == 2 * flops
    assert bb == n * ch * (3 * 2 + 2 * 4) + 4 * n * s * 2 \
        + 2 * ch * (s + 1) * 4
    for fl, nb in ((flops, nbytes), (fb, bb)):
        assert flops_lm.roofline_s(fl, nb, V5E) \
            == nb / V5E["hbm_bytes_per_s"] > fl / V5E["bf16_flops_per_s"]
    assert flops_phi4flash.seen_entries(n) == n * (n + 1) / 2
    assert flops_phi4flash.seen_entries(n, 512) == sum(
        min(i + 1, 512) for i in range(n))
    assert flops_phi4flash.seen_entries(300, 512) == 300 * 301 / 2
    full = flops_phi4flash.diff_attention_cost("fwd", 1, 40, 20, n, 64)
    band = flops_phi4flash.diff_attention_cost("fwd", 1, 40, 20, n, 64, 512)
    assert full[0] == 2.0 * 40 * (n * (n + 1) / 2) * (64 + 128)
    assert full[1] == band[1] == n * 2 * (40 * 64 + 20 * 64 + 10 * 128
                                          + 40 * 128) + 40 * n * 4
    assert full[0] / band[0] == pytest.approx(16.26, abs=0.01)
    fb, bb = flops_phi4flash.diff_attention_cost("bwd", 1, 40, 20, n, 64)
    assert fb == full[0] * (3 * 64 + 2 * 128) / (64 + 128)
    assert bb == 2 * (full[1] - 40 * n * 4) + 40 * n * 4
    for fl, nb in (full, (fb, bb)):
        assert flops_lm.roofline_s(fl, nb, V5E) \
            == fl / V5E["bf16_flops_per_s"] > nb / V5E["hbm_bytes_per_s"]


# -- the new readers on a synthetic run --------------------------------------

def _synthetic(config, monkeypatch, layer, flash, ssm):
    """A traced run of the cell whose trace reduced to these tables
    (seconds over 4 traced steps)."""
    monkeypatch.setattr(scopes_phi4flash, "_of_dir", lambda d: {
        "layer": dict(layer), "flash": dict(flash)})
    monkeypatch.setattr(scopes_ssm, "_of_dir", lambda d: dict(ssm))
    return {"config": config, "seq_len": 16384, "tokens_per_step": 16384,
            "trace_dir": "somewhere", "traced_steps": 4,
            "device": {"peaks": V5E}, "ticks": []}


def test_the_new_readers_read_a_synthetic_run(files, monkeypatch):
    config = files[3]
    run = _synthetic(
        config, monkeypatch,
        {"attn.window": 0.04, "attn.full": 0.6, "gmu": 0.02},
        {"attn.flash": 0.5}, {"ssm": 0.1, "ssm.scan": 0.2, "ssm.conv": 0.01})
    read = harness.load_reader
    assert read("train_diff_attn_window_ms")(run) == pytest.approx(10.0)
    assert read("train_diff_attn_full_ms")(run) == pytest.approx(150.0)
    assert read("train_gmu_ms")(run) == pytest.approx(5.0)
    assert read("train_ssm_scan_ms")(run) == pytest.approx(50.0)
    # 2 Mamba-1 layers x 4 steps x (fwd + bwd) least over 0.2 s taken
    least = sum(flops_lm.roofline_s(*flops_phi4flash.selective_scan_cost(
        k, 1, 16384, 5120, 16), V5E) for k in ("fwd", "bwd"))
    assert read("selective_scan_roofline")(run) == pytest.approx(
        100 * least * 2 * 4 / 0.2)
    # a window layer's band and two full layers' triangles x 4 steps
    least = sum(flops_lm.roofline_s(*flops_phi4flash.diff_attention_cost(
        k, 1, 40, 20, 16384, 64, w), V5E)
        for k in ("fwd", "bwd") for w in (512, 0, 0))
    assert read("diff_attention_roofline")(run) == pytest.approx(
        100 * least * 4 / 0.5)
    for name in ("selective_scan_roofline", "diff_attention_roofline"):
        assert 0 < read(name)(run) < 100


def test_the_new_readers_say_nothing_on_another_cells_run(monkeypatch):
    """The Mamba-2 cell's traced run: ``dsod.ssm*`` and ``dsod.attn``
    scopes, none of this model's; its configuration has no Mamba-1
    sizes and no layer kinds."""
    _, _, config = harness.resolve(harness.load_manifest(),
                                   "granite_4_0_h_micro_pp4.train_s16k_b1")
    run = _synthetic(config, monkeypatch, {}, {},
                     {"ssm": 0.1, "ssm.scan": 0.2})
    for name in NEW:
        assert harness.load_reader(name)(run) is None, name


def test_the_reducer_keys_a_layer_by_its_outermost_scope_and_flash_anywhere(
        monkeypatch):
    inside = "jit(s)/dsod.encoder/layer_3/"
    path = inside + "dsod.attn.full/attn/dsod.attn.flash/" \
        "dsod.kernel.flash_attention_causal/pallas_call"
    assert scopes_phi4flash._LAYER.search(path).group(1) == "attn.full"
    assert scopes_phi4flash._FLASH.search(path)
    assert not scopes_phi4flash._FLASH.search(
        inside + "dsod.attn.full/attn/qkv_proj/dot_general")
    # another model's attention scope makes no table
    assert not scopes_phi4flash._LAYER.search(
        "jit(s)/dsod.encoder/layer_5/dsod.attn/attn/q_proj/dot_general")
    monkeypatch.setattr(scopes_phi4flash.spans, "window_of", lambda h: None)
    monkeypatch.setattr(scopes_phi4flash.spans, "_clip",
                        lambda events, w: events)
    ev = lambda p, at: ("op", at, 1.0, p)  # noqa: E731
    tr = {"host": [], "devices": {"/device:TPU:0": [
        ev(path, 0.0), ev(inside + "dsod.attn.full/attn/o_proj/dot", 1.0),
        ev("jit(s)/dsod.encoder/layer_1/dsod.attn.window/attn/"
           "dsod.attn.flash/x", 2.0),
        ev("jit(s)/dsod.encoder/layer_4/dsod.gmu/gmu/in_proj/dot", 3.0),
        ev("jit(s)/dsod.encoder/layer_0/dsod.ssm/mixer/dsod.ssm.scan/x",
           4.0)]}}
    assert scopes_phi4flash.reduce(tr) == {
        "layer": {"attn.full": 2.0, "attn.window": 1.0, "gmu": 1.0},
        "flash": {"attn.flash": 2.0}}
