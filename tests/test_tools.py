"""Tooling tests: HLO dump (tools/dump_hlo.py), the HLO guard, the
roofline ledgers and friends."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

@pytest.mark.slow
def test_dump_hlo_writes_stablehlo(tmp_path):
    import dump_hlo

    paths = dump_hlo.dump("minet_vgg16_ref", str(tmp_path), n_devices=2,
                          batch_per_device=1, image_size=32)
    assert os.path.exists(paths["stablehlo"])
    text = open(paths["stablehlo"]).read()
    assert "module" in text and len(text) > 10_000
    # The sharded step must actually carry the mesh axes.
    assert "shard_map" in text or "mhlo.sharding" in text or "sdy" in text
    if "cost" in paths:
        import json

        cost = json.load(open(paths["cost"]))
        assert cost.get("flops", 1) > 0

    # `overrides` pins an execution-strategy arm through the config:
    # the xla arm's generic resize is a different program than the
    # default fast arm's slice/lerp.
    p2 = dump_hlo.dump("minet_vgg16_ref", str(tmp_path / "xla"),
                       n_devices=2, batch_per_device=1, image_size=32,
                       compile_cost=False,
                       overrides=["model.resample_impl=xla"])
    assert open(p2["stablehlo"]).read() != text


def test_hlo_guard_counts_and_invariant(tmp_path, capsys):
    """tools/hlo_guard.py: the counter sees through the op spellings,
    and one real lowering of the conv arms on the light carrier seeds
    the baseline and renders the one-line JSON delta — the fused arm's
    interpret-mode kernels count MORE formatting ops than the plain
    arm (the same counting path the t1 smoke runs).  Compare / gate
    bookkeeping: ``test_hlo_guard_conv_arms_record_and_gate``."""
    import json

    import hlo_guard

    text = ('%0 = stablehlo.reshape %a : x\n'
            '%1 = stablehlo.transpose %b : y\n'
            '%2 = stablehlo.broadcast_in_dim %c : z\n'
            '%3 = stablehlo.reshape %d : w\n'
            '%4 = stablehlo.add %e, %f : v\n')
    counts = hlo_guard.count_formatting_ops(text)
    assert counts == {"reshape": 2, "transpose": 1,
                      "broadcast_in_dim": 1, "total": 4}

    baseline = tmp_path / "baseline.json"
    rc = hlo_guard.main(["--conv-config", "minet_vgg16_ref",
                         "--conv-image-size", "32", "--devices", "2",
                         "--out", str(tmp_path / "hlo"),
                         "--baseline", str(baseline),
                         "--no-comm-arms"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["recorded"] is True
    assert 0 < out["arms"]["conv_xla"] < out["arms"]["conv_fused"]
    recorded = json.load(open(baseline))
    key = "minet_vgg16_ref@32px-conv"
    assert recorded[key]["conv_xla"]["total"] == out["arms"]["conv_xla"]
    # The default arm's checked-in counts are this lowering's: a drift
    # there is a changed program (the byte-identity canary).
    checked_in = json.load(open(hlo_guard._BASELINE))
    assert recorded[key]["conv_xla"] == checked_in[key]["conv_xla"]


def test_hlo_guard_never_seeds_on_failed_invariant(tmp_path, capsys,
                                                   monkeypatch):
    """A run whose own invariant fails (here: bucket fusion that did
    not reduce the all_reduce count) must NOT write that group's
    counts — a corrupt seed would make every later --fail-on-increase
    comparison report delta 0 against garbage."""
    import json

    import hlo_guard

    conv = {"reshape": 5, "transpose": 0, "broadcast_in_dim": 0,
            "total": 5}
    comm = {"comm_mono": {"all_reduce": 8, "total": 8},
            "comm_flat": {"all_reduce": 4, "total": 4},
            "comm_bucketed": {"all_reduce": 8, "total": 8},
            "comm_hier": {"all_reduce": 8, "reduce_scatter": 5,
                          "all_gather": 5, "total": 8},
            "comm_fsdp": {"all_gather": 12, "all_reduce": 6,
                          "reduce_scatter": 0, "total": 12}}
    monkeypatch.setattr(
        hlo_guard, "dump_conv_arm_counts",
        lambda *a, **k: {"conv_xla": dict(conv), "conv_fused": dict(conv)})
    monkeypatch.setattr(
        hlo_guard, "dump_comm_arm_counts",
        lambda *a, **k: {a_: dict(c) for a_, c in comm.items()})
    baseline = tmp_path / "baseline.json"
    rc = hlo_guard.main(["--config", "whatever", "--out",
                         str(tmp_path / "hlo"),
                         "--baseline", str(baseline)])
    assert rc == 1
    assert "whatever@64px-comm" not in json.load(open(baseline))
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["invariant_failed"] is True


def test_checked_in_hlo_baseline_matches_guard_arms():
    """The checked-in tools/hlo_copy_baseline.json carries the
    round-14 conv_impl arm rows on the conv carrier key and the
    gradient-collective arms on the flagship key — the groups the t1
    smoke records against, and no other."""
    import json

    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "hlo_copy_baseline.json")
    base = json.load(open(path))
    assert sorted(base) == ["minet_r50_dp@64px-comm",
                            "minet_vgg16_ref@32px-conv"]
    ckey = "minet_vgg16_ref@32px-conv"
    assert ckey in base
    assert base[ckey]["conv_xla"]["total"] > 0
    assert base[ckey]["conv_fused"]["total"] > 0
    # Round-18 gradient-collective arms on the flagship key: bucket
    # fusion collapses the per-leaf reduces, and the default bucket
    # size splits the flagship gradient into >= 2 buckets.
    mkey = "minet_r50_dp@64px-comm"
    assert mkey in base
    assert (base[mkey]["comm_mono"]["total"]
            > base[mkey]["comm_bucketed"]["total"])
    assert (base[mkey]["comm_bucketed"]["total"]
            - base[mkey]["comm_flat"]["total"] + 1) >= 2


def test_hlo_guard_conv_arms_record_and_gate(tmp_path, capsys,
                                             monkeypatch):
    """The round-14 conv_impl arms + round-18 comm arms: recorded on
    first contact under their own -conv/-comm keys, delta-compared
    after, --fail-on-increase trips on a regression.  dump paths are
    stubbed — the real lowerings run in the t1 smoke; this covers the
    bookkeeping."""
    import json

    import hlo_guard

    conv = {"conv_xla": {"reshape": 3, "transpose": 0,
                         "broadcast_in_dim": 0, "total": 3},
            "conv_fused": {"reshape": 9, "transpose": 1,
                           "broadcast_in_dim": 0, "total": 10}}
    comm = {"comm_mono": {"all_reduce": 40, "total": 40},
            "comm_flat": {"all_reduce": 4, "total": 4},
            "comm_bucketed": {"all_reduce": 8, "total": 8},
            # n_buckets = 8 - 4 + 1 = 5: hier adds one rs + one ag per
            # bucket and replaces the bucket psum 1:1 (ar equal).
            "comm_hier": {"all_reduce": 8, "reduce_scatter": 5,
                          "all_gather": 5, "total": 8},
            # post-opt fsdp counts: >=1 all_gather (JIT params),
            # >=1 reduction; total tracks the all_gather signature.
            "comm_fsdp": {"all_gather": 12, "all_reduce": 6,
                          "reduce_scatter": 0, "total": 12}}
    monkeypatch.setattr(
        hlo_guard, "dump_conv_arm_counts",
        lambda *a, **k: {a_: dict(c) for a_, c in conv.items()})
    monkeypatch.setattr(
        hlo_guard, "dump_comm_arm_counts",
        lambda *a, **k: {a_: dict(c) for a_, c in comm.items()})
    baseline = tmp_path / "baseline.json"
    args = ["--config", "cfg", "--out", str(tmp_path / "hlo"),
            "--baseline", str(baseline)]
    assert hlo_guard.main(args) == 0
    lines = [json.loads(l) for l
             in capsys.readouterr().out.strip().splitlines()]
    ckey = "minet_vgg16_ref@32px-conv"
    mkey = "cfg@64px-comm"
    assert lines[-2]["metric"] == f"hlo_formatting_ops[{ckey}]"
    assert lines[-2]["recorded"] is True
    assert lines[-1]["metric"] == f"hlo_grad_collectives[{mkey}]"
    assert lines[-1]["recorded"] is True
    assert lines[-1]["n_buckets"] == 5  # bucketed - flat + 1
    recorded = json.load(open(baseline))
    assert recorded[ckey] == conv
    assert recorded[mkey] == comm
    # Regression in the fused arm trips the gate.
    conv["conv_fused"]["total"] = 11
    conv["conv_fused"]["reshape"] = 10
    assert hlo_guard.main(args + ["--fail-on-increase"]) == 2
    out = json.loads(
        capsys.readouterr().out.strip().splitlines()[-2])
    assert out["delta_vs_baseline"]["conv_fused"] == 1
    conv["conv_fused"]["total"] = 10
    conv["conv_fused"]["reshape"] = 9
    # A bucketing change that grows the all_reduce count trips too.
    # (The hier arm moves with it — per-level invariants are checked
    # BEFORE the gate, and an inconsistent stub would rc=1 instead.)
    comm["comm_bucketed"]["total"] = 9
    comm["comm_bucketed"]["all_reduce"] = 9
    comm["comm_hier"].update(all_reduce=9, total=9,
                             reduce_scatter=6, all_gather=6)
    assert hlo_guard.main(args + ["--fail-on-increase"]) == 2
    out = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])
    assert out["delta_vs_baseline"]["comm_bucketed"] == 1


def test_roofline_fused_resample_ledger(capsys):
    """The per-arm fused-resample ledger (ISSUE 3 satellite): every
    decoder upsample site claims a positive per-step HBM saving, the
    fused arm's total bytes are strictly below the fast arm's, and the
    CLI renders the falsifiable table (not measured on a chip)."""
    import roofline

    sites: list = []
    roofline.minet_r50_ledger(64, resize="fused", fused_sites=sites)
    assert len(sites) >= 14  # 4 AIM ups + 5 hup + 4 declift + head
    assert all(saved > 0 for _, _, saved in sites)
    # Savings scale with the fine-map size: the 160 sites dominate.
    by_res = {}
    for _, res, saved in sites:
        by_res[res] = by_res.get(res, 0.0) + saved
    assert by_res[160] > by_res[80] > by_res[40]

    _, _, b_fast, t_fast = roofline.predict(64, resize="fast")
    _, _, b_fused, t_fused = roofline.predict(64, resize="fused")
    assert b_fused < b_fast and t_fused < t_fast
    # FLOPs unchanged: the kernel moves bytes, not arithmetic.
    f_fast = roofline.predict(64, resize="fast")[1]
    f_fused = roofline.predict(64, resize="fused")[1]
    assert abs(f_fast - f_fused) / f_fast < 1e-6

    assert roofline.main(["--batch", "64", "--resize", "fused"]) == 0
    out = capsys.readouterr().out
    assert "fused-resample ledger" in out and "sim1.declift" in out
    assert "HBM bytes saved/step" in out


def test_roofline_fused_conv_ledger(capsys):
    """The per-arm fused-conv ledger (ISSUE 12 satellite): every
    decoder ConvBNAct site claims a positive per-step saving on the
    fused arm, the AIM merge convs additionally claim their concat
    materialization, FLOPs are INVARIANT across arms (asserted inside
    the tool), and the CLI renders the r14 falsifiable table."""
    import roofline

    csites: list = []
    roofline.minet_r50_ledger(64, conv_arm="fused", conv_sites=csites)
    # 5 AIM cur + 4 below + 4 above + 5 merge + 5x5 SIM convs + head.
    assert len(csites) >= 30
    assert all(saved > 0 for _, _, saved in csites)
    by_name = {name: saved for name, _, saved in csites}
    # Concat-merge convs save strictly more than their same-res plain
    # siblings (the concat write+read rides on top of the epilogue).
    assert by_name["aim0.merge"] > by_name["aim0.cur"]
    # Fine sites dominate (the 160-bucket lever).
    by_res = {}
    for _, res, saved in csites:
        by_res[res] = by_res.get(res, 0.0) + saved
    assert by_res[160] > by_res[80] > by_res[40]

    _, f_x, b_x, t_x = roofline.predict(64)
    _, f_f, b_f, t_f = roofline.predict(64, conv="fused")
    assert b_f < b_x and t_f < t_x
    assert f_x == f_f  # FLOPs-invariance, exactly

    assert roofline.main(["--batch", "64", "--conv", "fused"]) == 0
    out = capsys.readouterr().out
    assert "fused-conv ledger" in out and "aim0.merge" in out
    assert "FLOPs invariant across arms" in out


def test_plot_curves_writes_figures(tmp_path):
    import json

    import numpy as np

    import plot_curves

    t = np.linspace(0, 1, 256)
    curves = {}
    for i, name in enumerate(["m1", "m2"]):
        curves[name] = {
            "precision": (0.9 - 0.1 * i - 0.3 * t).clip(0, 1).tolist(),
            "recall": t.tolist(),
            "fbeta_macro": (0.8 - 0.1 * i - 0.4 * (t - 0.4) ** 2).tolist(),
            "emeasure_macro": (0.85 - 0.1 * i - 0.3 * (t - 0.5) ** 2
                               ).tolist(),
        }
    cj = tmp_path / "curves.json"
    cj.write_text(json.dumps(curves))
    rc = plot_curves.main([str(cj), "--out", str(tmp_path / "figs")])
    assert rc == 0
    for f in ("pr_curve.png", "fbeta_curve.png", "emeasure_curve.png"):
        p = tmp_path / "figs" / f
        assert p.exists() and p.stat().st_size > 5_000


def test_plot_curves_partial_entries(tmp_path):
    """A series with only an Em curve plots without crashing and sizes
    its threshold axis from that curve."""
    import json

    import plot_curves

    curves = {"only_em": {"emeasure_macro": [0.5] * 128}}
    cj = tmp_path / "c.json"
    cj.write_text(json.dumps(curves))
    rc = plot_curves.main([str(cj), "--out", str(tmp_path / "f")])
    assert rc == 0
    assert (tmp_path / "f" / "emeasure_curve.png").exists()
    assert not (tmp_path / "f" / "pr_curve.png").exists()


@pytest.mark.slow
def test_predict_cli_writes_original_size_maps(tmp_path, eight_devices):
    """tools/predict.py: checkpoint (config sidecar) → saliency PNGs at
    each input's ORIGINAL resolution, batch padding included (3 images,
    batch 2)."""
    import numpy as np
    from PIL import Image

    import predict
    from distributed_sod_project_tpu.configs.base import (
        DataConfig, MeshConfig, ModelConfig, OptimConfig)
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.train.loop import fit

    cfg = get_config("minet_vgg16_ref").replace(
        data=DataConfig(dataset="synthetic", image_size=(32, 32),
                        synthetic_size=8, num_workers=0),
        model=ModelConfig(name="minet", backbone="vgg16", sync_bn=True,
                          compute_dtype="float32"),
        optim=OptimConfig(lr=0.01),
        mesh=MeshConfig(data=-1),
        global_batch_size=8,
        checkpoint_every_steps=1,
        checkpoint_dir=str(tmp_path / "ck"),
    )
    fit(cfg, max_steps=1)

    imgs = tmp_path / "imgs"
    imgs.mkdir()
    sizes = [(40, 30), (64, 48), (32, 32)]  # (W, H) PIL order
    rng = np.random.RandomState(0)
    for i, wh in enumerate(sizes):
        Image.fromarray(rng.randint(0, 255, (wh[1], wh[0], 3), np.uint8)
                        ).save(imgs / f"im{i}.jpg")

    out = tmp_path / "preds"
    rc = predict.main(["--ckpt-dir", str(tmp_path / "ck"),
                       "--input", str(imgs), "--output", str(out),
                       "--batch-size", "2"])
    assert rc == 0
    for i, wh in enumerate(sizes):
        with Image.open(out / f"im{i}.png") as im:
            assert im.size == wh and im.mode == "L"
            arr = np.asarray(im)
        assert arr.min() >= 0 and arr.max() <= 255


@pytest.mark.slow
def test_check_determinism_tool(tmp_path, capsys, monkeypatch):
    """tools/check_determinism.py: two identical runs → bitwise-equal
    params, exit 0 (the §5 'race detection' audit)."""
    import check_determinism

    rc = check_determinism.main([
        "--config", "minet_vgg16_ref", "--device", "cpu", "--steps", "2",
        "--image-size", "32", "--batch-size", "8",
        "--set", "data.synthetic_size=16",
        "--set", "model.compute_dtype=float32",
        "--set", "data.num_workers=0",
    ])
    assert rc == 0
    assert "deterministic" in capsys.readouterr().out


@pytest.mark.slow
def test_inspect_ckpt_census_and_diff(tmp_path, capsys, eight_devices):
    """tools/inspect_ckpt.py: steps/config/param census from the
    sidecar, and the cross-checkpoint diff (identical dirs → 0)."""
    import inspect_ckpt
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.configs.base import (
        DataConfig, MeshConfig, ModelConfig, OptimConfig)
    from distributed_sod_project_tpu.train.loop import fit

    cfg = get_config("minet_vgg16_ref").replace(
        data=DataConfig(dataset="synthetic", image_size=(32, 32),
                        synthetic_size=8, num_workers=0),
        model=ModelConfig(name="minet", backbone="vgg16", sync_bn=True,
                          compute_dtype="float32"),
        optim=OptimConfig(lr=0.01),
        mesh=MeshConfig(data=-1),
        global_batch_size=8,
        checkpoint_every_steps=1,
        checkpoint_dir=str(tmp_path / "ck"),
    )
    fit(cfg, max_steps=1)

    rc = inspect_ckpt.main([str(tmp_path / "ck")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "available steps: [1]" in out
    assert "minet" in out and "params:" in out
    assert "VGG16_0" in out  # per-module census row

    rc = inspect_ckpt.main([str(tmp_path / "ck"),
                            "--diff", str(tmp_path / "ck")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "0.000e+00" in out  # identical checkpoints diff to zero


@pytest.mark.slow
def test_export_model_roundtrip_and_tpu_lowering(tmp_path, eight_devices):
    """tools/export_model.py: the serialized artifact, deserialized
    cold, reproduces the framework's own eval forward exactly — and the
    same checkpoint exports for platform='tpu' (full-model Mosaic/XLA
    TPU lowering, no chip needed)."""
    import numpy as np
    from jax import export as jexport

    import export_model
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.configs.base import (
        DataConfig, MeshConfig, ModelConfig, OptimConfig)
    from distributed_sod_project_tpu.eval.inference import (
        make_forward, restore_for_eval)
    from distributed_sod_project_tpu.train.loop import fit

    cfg = get_config("vit_sod_sp").replace(
        data=DataConfig(dataset="synthetic", image_size=(32, 32),
                        synthetic_size=8, num_workers=0),
        model=ModelConfig(name="vit_sod", backbone="tiny", sync_bn=False,
                          compute_dtype="float32"),
        optim=OptimConfig(optimizer="adamw", lr=1e-3),
        mesh=MeshConfig(data=-1),
        global_batch_size=8,
        checkpoint_every_steps=1,
        checkpoint_dir=str(tmp_path / "ck"),
    )
    fit(cfg, max_steps=1)

    out = str(tmp_path / "m.bin")
    info = export_model.export_checkpoint(str(tmp_path / "ck"), out,
                                          platform="cpu", batch_size=2)
    assert info["bytes"] > 0

    x = np.random.RandomState(0).randn(2, 32, 32, 3).astype(np.float32)
    fn = jexport.deserialize(open(out, "rb").read())
    got = np.asarray(fn.call(x))

    _, model, state = restore_for_eval(str(tmp_path / "ck"))
    want = np.asarray(make_forward(model)(state.eval_variables()
                                          if hasattr(state,
                                                     "eval_variables")
                                          else state.variables(),
                                          {"image": x}))
    np.testing.assert_allclose(got, want, atol=1e-6)

    # TPU lowering of the same artifact (serialize only; no chip).
    info = export_model.export_checkpoint(
        str(tmp_path / "ck"), str(tmp_path / "m_tpu.bin"), platform="tpu",
        batch_size=2)
    assert info["platform"] == "tpu" and info["bytes"] > 0


def test_analyze_trace_summarises_profile(tmp_path, capsys):
    # End-to-end: capture a tiny real profiler trace with the program's
    # own names in it, then read it back through the one reduction the
    # benchmark's per-layer metrics use (benchmark/harness/trace.py +
    # spans.py; tools/analyze_trace.py is their CLI).
    import jax
    import jax.numpy as jnp

    import analyze_trace
    from distributed_sod_project_tpu.utils.tracing import span

    @jax.jit
    def f(x):
        with jax.named_scope("dsod.loss"):
            return jnp.tanh(x @ x.T).sum()

    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    for i in range(4):
        with span("dsod.train.step", step_num=i + 1, steps=1, epoch=0):
            with span("dsod.train.dispatch"):
                y = f(x)
            with span("dsod.train.flush"):
                y.block_until_ready()
    jax.profiler.stop_trace()

    assert analyze_trace.main([trace_dir, "--top", "3"]) == 0
    out = capsys.readouterr().out
    # XLA:CPU traces carry no TPU device plane (the op and stage tables
    # populate only for a trace from the chip — PERF.md section 5), so
    # this asserts the plumbing: the file is found, the device section
    # says what it lacks, and the host spans are totalled by thread.
    assert "xplane: " in out and ".xplane.pb" in out
    assert "no operation on a TPU plane" in out
    rows = {ln.split()[3]: int(ln.split()[5])
            for ln in out.splitlines() if ln.startswith("spans: host ")}
    assert rows == {"dsod.train.step": 4, "dsod.train.dispatch": 4,
                    "dsod.train.flush": 4}
    # It imports no xprof converter: the reduction needs only JAX.
    with open(analyze_trace.__file__) as fh:
        assert "xprof" not in fh.read()
    # Missing dir is a clean rc=1, not a traceback.
    assert analyze_trace.main([str(tmp_path / "nope")]) == 1


@pytest.mark.slow
def test_bench_flash_sweep_runs_on_cpu(capsys):
    # CPU smoke of the block-shape sweep harness (interpret-mode
    # kernel): tiny shape, one block pair, fwd-only.  Validates the
    # timing/sync plumbing so the on-hardware sweep can't die on a
    # harness bug.
    import bench_flash

    assert bench_flash.main(["--shape", "2,256,64", "--iters", "2",
                             "--blocks", "128/128", "--fwd-only"]) is None
    out = capsys.readouterr().out
    assert "xla" in out and "flash 128/128" in out and "ms" in out


def test_roofline_ledger_and_buckets(capsys):
    """tools/roofline.py (VERDICT r3 item 3): the analytic ledger's
    invariants that need no hardware — FLOP linearity in batch,
    HBM-bound totals at the flagship's intensity, remat adding
    forward recompute, capacity estimates that retro-predict the
    round-2 b256 death.  (The measured side reads a trace by the
    step's ``dsod.<stage>`` scopes: tools/analyze_trace.py.)"""
    import roofline

    rows32, f32_, b32_, t32 = roofline.predict(32)
    rows64, f64_, b64_, t64 = roofline.predict(64)
    assert abs(f64_ / f32_ - 2.0) < 0.02  # FLOPs linear in batch
    assert f64_ / b64_ < roofline.PEAK_FLOPS / roofline.HBM_BW  # HBM-bound

    _, fr, br, tr = roofline.predict(64, remat=True)
    assert fr > f64_ * 1.2 and tr > t64  # remat re-runs the forward

    # s2d keeps the stem's HBM bytes (same image in, same map out).
    plain = {o.name: o for o in roofline.minet_r50_ledger(64)}
    s2d = {o.name: o for o in roofline.minet_r50_ledger(64, s2d=True)}
    assert abs(s2d["stem_s2d"].bytes - plain["stem7x7"].bytes) < 1e6

    # Capacity: monotone in batch; b256 no-remat must exceed v5e HBM.
    caps = [roofline.act_capacity_gb(b) for b in (64, 128, 256)]
    assert caps[0] < caps[1] < caps[2] and caps[2] > 16.0

    # CLI prints the prediction tables.
    assert roofline.main(["--batch", "64", "--remat"]) == 0
    out = capsys.readouterr().out
    assert "roofline-ideal" in out and "| 160 |" in out


def test_make_tiny_dataset_heldout_split(tmp_path):
    """--eval-n (round 4): the held-out split must be genuinely
    disjoint from the train split — distinct stems (no PNG can shadow
    a train file through the prediction-matching path) and distinct
    image content (the rng stream continues past the train draws, so
    an accidental reseed that replayed the same ellipses would turn
    the 'generalization' band into a memorization test)."""
    import numpy as np
    from PIL import Image

    from make_tiny_dataset import main as make_ds

    out = str(tmp_path / "t")
    make_ds(["--out", out, "--n", "4", "--size", "32", "--seed", "7",
             "--eval-n", "3"])
    tr = sorted(os.listdir(os.path.join(out, "DUTS-TR-Image")))
    ev_root = out + "_eval"
    ev = sorted(os.listdir(os.path.join(ev_root, "DUTS-TR-Image")))
    assert len(tr) == 4 and len(ev) == 3
    assert not (set(tr) & set(ev))
    assert all(s.startswith("tinyeval_") for s in ev)

    def imgs(root, names):
        return [np.asarray(Image.open(os.path.join(root,
                "DUTS-TR-Image", n))) for n in names]

    for e in imgs(ev_root, ev):
        assert all(not np.array_equal(e, t) for t in imgs(out, tr))

    # Determinism: the same seed reproduces both splits bit-for-bit.
    out2 = str(tmp_path / "t2")
    make_ds(["--out", out2, "--n", "4", "--size", "32", "--seed", "7",
             "--eval-n", "3", "--eval-out", out2 + "_ev"])
    a = imgs(ev_root, ev)
    b = imgs(out2 + "_ev", sorted(os.listdir(
        os.path.join(out2 + "_ev", "DUTS-TR-Image"))))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
