"""bench.py CLI: the data-mode path (device modes are exercised against
real hardware; data mode is pure host and cheap enough for CI)."""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def test_bench_data_mode_prints_one_json_line(tmp_path, capsys, monkeypatch):
    import bench

    # DSOD_BENCH_BASELINE keeps the baseline side file out of the repo.
    monkeypatch.setenv("DSOD_BENCH_BASELINE", str(tmp_path / "base.json"))

    rc = bench.main([
        "--device", "cpu", "--mode", "data", "--steps", "4", "--warmup",
        "1", "--batch-per-chip", "4", "--image-size", "32",
        "--set", "data.synthetic_size=16", "--set", "data.num_workers=0",
    ])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["unit"] == "images/sec/host"  # no device in a data run
    assert out["value"] > 0
    assert "data[host]_throughput" in out["metric"]
    assert (tmp_path / "base.json").exists()


def test_bench_zoo_renders_table(tmp_path, capsys, monkeypatch):
    """tools/bench_zoo.py: one subprocess per (config, mode) → markdown
    table; data-mode only (no model compile) keeps this CI-cheap.  The
    env var propagates into the subprocess, isolating the baseline."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import bench_zoo

    monkeypatch.setenv("DSOD_BENCH_BASELINE", str(tmp_path / "base.json"))
    out = tmp_path / "zoo.md"
    rc = bench_zoo.main([
        "--device", "cpu", "--configs", "minet_vgg16_ref", "--modes",
        "data", "--steps", "2", "--warmup", "1", "--batch-per-chip", "2",
        "--image-size", "32", "--set", "data.synthetic_size=8",
        "--set", "data.num_workers=0", "--out", str(out),
    ])
    assert rc == 0
    text = out.read_text()
    assert "| minet_vgg16_ref |" in text and "ERR" not in text
    assert "| minet_vgg16_ref |" in capsys.readouterr().out
    assert (tmp_path / "base.json").exists()


def test_bench_zoo_unknown_config_is_visible_error(tmp_path, monkeypatch):
    """A typo'd --configs name must surface as an ERR row + exit 1,
    never a silently dropped row."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import bench_zoo

    monkeypatch.setenv("DSOD_BENCH_BASELINE", str(tmp_path / "base.json"))
    out = tmp_path / "zoo.md"
    rc = bench_zoo.main([
        "--device", "cpu", "--configs", "mynet_typo", "--modes", "data",
        "--steps", "1", "--warmup", "0", "--batch-per-chip", "2",
        "--image-size", "32", "--out", str(out),
    ])
    assert rc == 1
    assert "ERR" in out.read_text()


def test_zoo_sweep_covers_every_registered_config():
    """Every registered experiment config must be in bench_zoo.ZOO or
    in the explicit exclusion list below — GateNet sat registered but
    silently absent from the hardware sweep for a whole round, and a
    missing row reads as 'covered' in the zoo table."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import bench_zoo

    from distributed_sod_project_tpu.configs import list_configs

    excluded = {
        # Variant of vit_sod_sp at 512px whose distinguishing knobs
        # (flash attention, hires memory posture) are A/B'd by
        # tools/bench_flash.py.
        "vit_sod_hires",
        # The token model: bench.py feeds image batches; its step is
        # timed through fit() by the benchmark's own cell
        # (lfm2_8b_a1b_ep4.train_s8k_b4).
        "lfm2_8b_a1b_ep4",
    }
    missing = set(list_configs()) - set(bench_zoo.ZOO) - excluded
    assert not missing, (
        f"configs registered but absent from bench_zoo.ZOO and not "
        f"explicitly excluded: {sorted(missing)}")


def test_bench_batch_defaults_are_per_config(monkeypatch):
    """ADVICE r2: a bare ``bench.py --config basnet_ds`` must not
    default into the flagship's b128 regime (HBM OOM risk on the heavy
    zoo members) — the default is per-config via PER_CONFIG_BATCH."""
    import bench

    seen = []

    def record(args):
        seen.append(args.batch_per_chip)
        return 0

    monkeypatch.setattr(bench, "_run", record)
    bench.main(["--device", "cpu"])  # flagship
    bench.main(["--device", "cpu",
                "--config", "basnet_ds"])
    bench.main(["--device", "cpu",
                "--config", "basnet_ds", "--batch-per-chip", "7"])
    assert seen == [bench.PER_CONFIG_BATCH["minet_r50_dp"],
                    bench.DEFAULT_BATCH, 7]


def test_bench_baseline_key_includes_program_env_vars(
        tmp_path, capsys, monkeypatch):
    """ADVICE r2 (medium): DSOD_RESIZE_IMPL / DSOD_FLASH_BLOCK_* change
    the compiled program; an A/B leg run with one of them set must not
    seed the canonical baseline key (bogus vs_baseline later)."""
    import bench

    monkeypatch.setenv("DSOD_BENCH_BASELINE", str(tmp_path / "base.json"))
    monkeypatch.setenv("DSOD_RESIZE_IMPL", "xla")
    rc = bench.main([
        "--device", "cpu", "--mode", "data", "--steps", "2", "--warmup",
        "0", "--batch-per-chip", "4", "--image-size", "32",
        "--set", "data.synthetic_size=16", "--set", "data.num_workers=0",
    ])
    assert rc == 0
    capsys.readouterr()
    keys = list(json.loads((tmp_path / "base.json").read_text()))
    assert len(keys) == 1 and "env:DSOD_RESIZE_IMPL=xla" in keys[0]


def test_bench_error_is_one_attempt_exit_1_and_no_rate(tmp_path, monkeypatch,
                                                       capsys):
    """A failed run (a shape error in the step, an OOM, no chip under
    --device tpu) surfaces immediately — exactly one attempt, rc=1, a
    parseable JSON error line for the driver, and NO value in it: a
    run that failed has no rate."""
    import json

    import bench

    monkeypatch.setenv("DSOD_BENCH_BASELINE", str(tmp_path / "base.json"))
    calls = []

    def boom(args):
        calls.append(1)
        raise ValueError("shapes do not match")

    monkeypatch.setattr(bench, "_run", boom)
    rc = bench.main(["--device", "cpu"])
    assert rc == 1
    assert len(calls) == 1
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert "shapes do not match" in line["error"]
    assert "value" not in line and "unit" not in line


def test_bench_device_tpu_without_a_chip_exits_nonzero(capsys):
    """--device tpu on a CPU-only process: non-zero exit, an error
    line naming the backend found, no rate (the pre-PR-23 bench
    answered a missing chip with value 0.0 and exit code 0)."""
    import bench

    rc = bench.main(["--device", "tpu", "--steps", "1"])
    assert rc != 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "NoAcceleratorError" in line["error"]
    assert "cpu" in line["error"]
    assert "value" not in line


def test_bench_steps_per_dispatch_folds_into_override_key(monkeypatch):
    """--steps-per-dispatch rides the --set override machinery, so the
    compiled program gets cfg.steps_per_dispatch AND the vs_baseline
    key is tagged apart from the canonical k=1 baselines."""
    import bench

    captured = {}

    def fake_run(args):
        captured["overrides"] = list(args.overrides)
        return 0

    monkeypatch.setattr(bench, "_run", fake_run)
    rc = bench.main(["--device", "cpu", "--mode", "train",
                     "--steps-per-dispatch", "4"])
    assert rc == 0
    assert "steps_per_dispatch=4" in captured["overrides"]


def test_bench_steps_per_dispatch_rejects_non_train_modes():
    import pytest

    import bench

    with pytest.raises(SystemExit):
        bench.main(["--mode", "data", "--steps-per-dispatch", "2"])
    with pytest.raises(SystemExit):
        bench.main(["--mode", "train", "--steps-per-dispatch", "0"])


def test_bench_set_override_chunking_rejected_off_train(tmp_path,
                                                        monkeypatch):
    """The --set spelling gets the same non-train guard as the flag —
    otherwise the override tags a baseline key without changing the
    measured program."""
    import pytest

    import bench

    monkeypatch.setenv("DSOD_BENCH_BASELINE", str(tmp_path / "base.json"))
    with pytest.raises(SystemExit, match="only "):
        bench.main([
            "--device", "cpu", "--mode", "data", "--steps", "2",
            "--warmup", "0", "--batch-per-chip", "4",
            "--image-size", "32", "--set", "data.synthetic_size=16",
            "--set", "steps_per_dispatch=2",
        ])


def test_bench_serve_mode_rejects_step_chunking():
    """serve never builds the chunked train program; the generic
    non-train guard must cover the new mode too."""
    import pytest

    import bench

    with pytest.raises(SystemExit):
        bench.main(["--mode", "serve", "--steps-per-dispatch", "2"])


def test_bench_serve_mode_reports_latency_fields(tmp_path, capsys,
                                                 monkeypatch):
    """--mode serve routes the loadgen summary through _report: one
    JSON line with imgs/sec plus the latency-tail extras, keyed -serve
    so serving baselines never contaminate train/eval keys."""
    import bench

    monkeypatch.setenv("DSOD_BENCH_BASELINE", str(tmp_path / "base.json"))

    def fake_bench_serve(args, cfg, device):
        assert cfg.serve.max_queue == 5  # --set reached the serve section
        return bench._report(args, 12.0, dict(device, count=1),
                             mode="serve",
                             p50_ms=1.0, p95_ms=2.0, p99_ms=3.0)

    monkeypatch.setattr(bench, "_bench_serve", fake_bench_serve)
    rc = bench.main([
        "--device", "cpu", "--mode", "serve", "--steps", "4",
        
        "--set", "serve.max_queue=5",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # A CPU run's rate is labelled as such, never as a chip's.
    assert out["unit"] == "images/sec/cpu"
    assert out["device"]["platform"] == "cpu"
    assert out["value"] == 12.0
    assert out["p99_ms"] == 3.0
    assert "serve_throughput" in out["metric"]
    key = json.loads((tmp_path / "base.json").read_text())
    assert all(k.endswith("-serve") for k in key)
