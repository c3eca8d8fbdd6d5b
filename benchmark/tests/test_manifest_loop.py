"""What PR 41 added to the manifest, checked without the chip: the cell
resolves, every metric it is listed under has a reader that loads and
says nothing where there is nothing to read, the four loop metrics list
the cell, the configuration's file keeps every number of the catalog
row and lists each cut, file, reference and registered config tell one
story, the step's FLOPs and the attention core's cost are a hand count,
and each new reader reads a synthetic run and says nothing on another
cell's.  Membership and relative order only: nothing here pins where a
list ends, how long it is, or what else it holds."""

import json
import os

import pytest

from benchmark import run as harness
from benchmark.harness import flops_lm, flops_loop, scopes_loop

CELL = "ouro_2_6b_pp6.train_s8k_b1"
NEW = ["train_loop_attn_ms", "train_loop_ffn_ms", "loop_attention_roofline",
       "loop_exit_entropy"]
# pinned to one older cell each; their readers read nothing here
NOT_LISTED = ["train_attn_ms", "flash_attention_causal_roofline",
              "flash_attention_mla_roofline", "train_attn_outside_kernel_ms",
              "train_stage_ms.decoder", "train_ssm_ms", "ssm_scan_roofline",
              "train_moe_ms", "moe_load_max_over_mean"]
PUBLISHED = {  # the catalog row's `config`, ByteDance/Ouro-2.6B
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
V5E = json.load(open(os.path.join(os.path.dirname(harness.__file__),
                                  "harness", "peaks.json")))["TPU v5 lite"]


@pytest.fixture(scope="module")
def files():
    manifest = harness.load_manifest()
    entry, cell, config = harness.resolve(manifest, CELL)
    return manifest, entry, cell, config


def test_cell_resolves_and_reports_what_the_issue_lists(files):
    manifest, entry, cell, config = files
    assert entry["chips"] == 1 and cell["runner"] == "train_loop"
    assert cell["overrides"] == ["global_batch_size=1", "data.seq_len=8192",
                                 "mesh.data=1", "log_every_steps=2"]
    assert (cell["warmup_ticks"], cell["trace_ticks"]) == (2, 4)
    names = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                     "per_layer")}
    # what the state-space cell reports, less its own four and the
    # decoder stage, and the four loop metrics
    older = "granite_4_0_h_micro_pp4.train_s16k_b1"
    shared = {m["name"] for m in manifest["per_layer"]
              if older in m["workloads"] and not m["name"].startswith(
                  ("train_ssm", "ssm_"))}
    assert "train_step_mfu" in shared and "setup_compile_s" in shared
    assert shared | set(NEW) <= names
    assert not names & set(NOT_LISTED)
    assert {"train_img_per_s_chip", "setup_s"} <= {
        m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                "end_to_end")}
    assert len(entry["why"]) <= 200 and entry["why"] == cell["why"]
    # the eight judged numbers and no routing row
    assert set(cell["limits"]) == {
        "loss_rel_gap.step1", "loss_rel_gap.step2", "loss_rel_gap.step3",
        "grad_norm_median_leaf_gap", "grad_norm_worst_leaf_gap",
        "dparam_norm_median_leaf_gap", "dparam_zero_leaf_share",
        "exit_mass_gap"}
    assert cell["limits"]["dparam_zero_leaf_share"] == 0.0
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 0


def test_every_reader_of_the_cell_loads_and_finds_nothing_in_an_empty_run(
        files):
    manifest = files[0]
    for m in harness.cell_metrics(manifest, CELL, "per_layer"):
        read = harness.load_reader(m["name"])
        # a run with no trace and no counters (the parent, a CPU run)
        assert read({"ticks": [], "trace_dir": None, "trace": None}) is None


@pytest.mark.parametrize("name", NEW)
def test_loop_metric_lists_the_cell(files, name):
    manifest, _, _, config = files
    (m,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert CELL in m["workloads"] and m["moves"] == "train_img_per_s_chip"
    if name == "loop_exit_entropy":
        assert (m["source"], m["layer"], m["unit"]) == (
            "program_counter", "compiled train step", "nats")
    else:
        assert (m["source"], m["layer"]) == ("device_trace",
                                             "kernels and XLA fusions")
    if name.endswith("_roofline"):
        assert m["unit"] == "%" and m["better"] == "higher"
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

    older = "granite_4_0_h_micro_pp4.train_s16k_b1"
    assert at(manifest["workloads"], CELL) > at(manifest["workloads"], older)
    assert at(manifest["configs"], "ouro_2_6b_pp6") > at(
        manifest["configs"], "granite_4_0_h_micro_pp4")
    # (the last metric the benchmark had before them)
    last = at(manifest["per_layer"], "setup_compile_s")
    assert all(at(manifest["per_layer"], n) > last for n in NEW)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        w = m.get("workloads", [])
        if CELL in w and older in w:
            assert w.index(CELL) > w.index(older)


def test_config_file_keeps_published_numbers_and_lists_each_cut(files):
    manifest, _, _, config = files
    (conf,) = [c for c in manifest["configs"]
               if c["name"] == "ouro_2_6b_pp6"]
    assert set(PUBLISHED) <= set(config)  # every key of the row
    differs = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == set(conf["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types"}
    assert config["published"]["num_hidden_layers"] == 48
    assert config["source"] == conf["source"]
    assert config["source"].endswith("ByteDance/Ouro-2.6B/blob/main/"
                                     "config.json")
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    assert config["layer_types"] == PUBLISHED["layer_types"][:8]
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 8
    assert config["num_hidden_layers"] * 6 == PUBLISHED["num_hidden_layers"]
    for said in ("6 stages of 8 layers", "first stage",
                 "No layer is divided", "WHOLE 49152-row embedding",
                 "whole untied head", "four times"):
        assert said in config["deployment"], said
    assumed = " ".join(config["assumed"])
    for said in ("sandwich norm", "NORMED state", "with a bias",
                 "beta = 0.1", "rotate-half", "AdamW", "Zipf",
                 "weights (harness/weights_loop.py)"):
        assert said in assumed, said
    assert "bfloat16" in config["precision"]


def test_file_reference_and_registered_config_agree(files):
    from distributed_sod_project_tpu.configs import get_config

    _, _, cell, config = files
    cfg = get_config(config["registered"])
    lm, ref = cfg.model.lm, config["reference"]["arch"]
    assert (lm.hidden, lm.dense_width, lm.vocab) == (
        config["hidden_size"], config["intermediate_size"],
        config["vocab_size"])
    assert (lm.heads, lm.kv_heads, lm.head_dim) == (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"])
    assert (ref["heads"], ref["head_dim"]) == (lm.heads, lm.head_dim)
    assert len(lm.layer_types) == config["num_hidden_layers"] \
        == ref["layers"]
    assert lm.ut_steps == config["total_ut_steps"] == ref["ut_steps"]
    assert lm.exit_beta == ref["exit_beta"] == 0.1
    assert lm.rope_theta == config["rope_theta"] == ref["rope_theta"]
    assert lm.norm_eps == config["rms_norm_eps"] == ref["norm_eps"]
    assert config["tie_word_embeddings"] is False
    opt, ropt = cfg.optim, config["reference"]["optimizer"]
    assert (opt.optimizer, opt.lr, opt.weight_decay, opt.warmup_steps,
            opt.poly_power) == (ropt["kind"], ropt["lr"],
                                ropt["weight_decay"], ropt["warmup_steps"],
                                ropt["poly_power"])
    assert ropt["total_steps"] == cell["max_steps"]
    assert cfg.data.vocab == lm.vocab and cfg.global_batch_size == 1
    assert cfg.data.seq_len == 8192


def test_flops_per_step_is_a_hand_count(files):
    """The stored number, against the same count written out."""
    _, _, cell, c = files
    n, d, v = 8192, c["hidden_size"], c["vocab_size"]
    h, hd, r = c["num_attention_heads"], c["head_dim"], c["total_ut_steps"]
    layer = 4 * d * h * hd + 3 * d * c["intermediate_size"]
    assert layer == 51_380_224
    visits = c["num_hidden_layers"] * r
    assert visits == 32
    forward = (2 * n * layer * visits                      # dense products
               + 2 * 2 * h * hd * (n * (n + 1) // 2) * visits  # q k^T, p v
               + 2 * n * v * d * r                         # four head calls
               + 2 * n * d * r)                            # the gate
    assert cell["flops_per_step"] == pytest.approx(3 * forward, rel=1e-12)
    assert 126.9e12 < cell["flops_per_step"] < 127.1e12
    got = flops_loop.step_flops(
        batch=1, n=n, hidden=d, heads=h, head_dim=hd,
        width=c["intermediate_size"], vocab=v,
        layers=c["num_hidden_layers"], passes=r)
    assert got["flops_per_step"] == cell["flops_per_step"]
    assert got["heads"] == 3 * 2 * n * v * d * r


def test_the_attention_cores_cost_is_the_works_own():
    """One call at [1, 16, 8192, 128]: 2 products a visited pair forward
    and 5 in the fused backward; every operand read once, every result
    written once; FLOP-bound on the chip's peaks."""
    pairs = 8192 * 8193 // 2
    f, nb = flops_loop.flash_causal_cost("fwd", 1, 16, 8192, 128)
    assert f == 2 * 2 * 16 * pairs * 128
    wide = 16 * 8192 * 128 * 2
    assert nb == 4 * wide + 16 * 8192 * 4       # q k v -> out, lse
    fb, bb = flops_loop.flash_causal_cost("bwd", 1, 16, 8192, 128)
    assert fb == 2 * 5 * 16 * pairs * 128
    assert bb == 8 * wide + 16 * 8192 * 4       # q k v do out lse -> dq dk dv
    for flops, nbytes in ((f, nb), (fb, bb)):
        assert flops_lm.roofline_s(flops, nbytes, V5E) \
            == flops / V5E["bf16_flops_per_s"] \
            > nbytes / V5E["hbm_bytes_per_s"]


# -- the new readers on a synthetic run --------------------------------------

def _synthetic(config, monkeypatch, scopes):
    """A traced run of the cell whose trace reduced to ``scopes``
    (seconds over 4 traced steps)."""
    monkeypatch.setattr(scopes_loop, "_of_dir", lambda d: dict(scopes))
    return {"config": config, "seq_len": 8192, "tokens_per_step": 8192,
            "trace_dir": "somewhere", "traced_steps": 4,
            "device": {"peaks": V5E},
            "ticks": [{"loop_exit_entropy": 1.0},
                      {"loop_exit_entropy": 1.2}]}


def test_the_new_readers_read_a_synthetic_run(files, monkeypatch):
    config = files[3]
    run = _synthetic(config, monkeypatch, {
        "loop": 0.2, "attn": 0.4, "attn.core": 1.2, "densemlp": 2.0,
        "loop.exit": 0.04})
    read = harness.load_reader
    assert read("train_loop_attn_ms")(run) == pytest.approx(400.0)
    assert read("train_loop_ffn_ms")(run) == pytest.approx(500.0)
    assert read("loop_exit_entropy")(run) == pytest.approx(1.1)
    # 32 visits x 4 steps x (1.3955 + 3.4887 ms) least over 1.2 s taken
    least = sum(flops_lm.roofline_s(
        *flops_loop.flash_causal_cost(k, 1, 16, 8192, 128), V5E)
        for k in ("fwd", "bwd"))
    assert read("loop_attention_roofline")(run) == pytest.approx(
        100 * least * 32 * 4 / 1.2)
    assert 0 < read("loop_attention_roofline")(run) < 100


def test_the_new_readers_say_nothing_on_another_cells_run(monkeypatch):
    """The state-space cell's traced run: its configuration has no
    passes, its trace no ``dsod.loop``, its ticks no exit counter."""
    _, _, config = harness.resolve(harness.load_manifest(),
                                   "granite_4_0_h_micro_pp4.train_s16k_b1")
    run = _synthetic(config, monkeypatch, {})
    run["ticks"] = [{"ssm_decay_min": 1e-9}]
    for name in NEW:
        assert harness.load_reader(name)(run) is None, name


def test_the_reducer_takes_the_deepest_scope_and_needs_the_loop():
    keyed = lambda path: (  # noqa: E731
        scopes_loop._SCOPE.findall(path)[-1]
        if "loop" in scopes_loop._SCOPE.findall(path) else scopes_loop.OTHER)
    inside = "jit(s)/dsod.encoder/loop/dsod.encoder/dsod.loop/layer_0/"
    assert keyed(inside + "dsod.attn/attn/dsod.attn.core/x") == "attn.core"
    assert keyed(inside + "dsod.attn/attn/q_proj/dot") == "attn"
    assert keyed(inside + "dsod.densemlp/mlp/dot") == "densemlp"
    assert keyed(inside + "dsod.loop.exit/final_norm/mul") == "loop.exit"
    assert keyed(inside + "add") == "loop"
    # another model's attention layer is not the loop's
    assert keyed("jit(s)/dsod.encoder/layer_5/dsod.attn/attn/dot") \
        == scopes_loop.OTHER
