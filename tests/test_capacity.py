"""Live capacity ledger (utils/capacity.py — docs/OBSERVABILITY.md
"Capacity & SLO").

Invariants proven here:

- the ledger's numbers ARE the executable's own cost_analysis() (the
  same-source contract the acceptance criterion states: live MFU on
  CPU agrees with the offline cost_analysis for the same program
  within 1%);
- the MFU / roofline-utilization arithmetic against an injected
  measured time;
- the engine integration: warmup records every cached program, a
  served request feeds the EWMA, the dsod_capacity_* families render
  with stage-share attribution in [0, 1];
- the trainer integration: a tiny fit with the knob on records the
  step program and serves live train MFU + /slo on the sidecar;
- the roofline cross-check (slow): tools/roofline.py --xla-check on
  the full real step agrees with the ledger on the same executable.
"""

import json
import os
import sys
import urllib.request

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sod_project_tpu.configs import (DataConfig,
                                                 ExperimentConfig,
                                                 ModelConfig, ServeConfig)
from distributed_sod_project_tpu.serve.engine import InferenceEngine
from distributed_sod_project_tpu.utils.capacity import (CapacityLedger,
                                                        device_hbm_gauges,
                                                        program_cost)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


# ------------------------------------------------ cost extraction


def _compiled_matmul(n=64):
    @jax.jit
    def f(a, b):
        return a @ b

    x = jnp.ones((n, n), jnp.float32)
    return f.lower(x, x).compile()


def test_program_cost_matches_cost_analysis_same_executable():
    """The ledger reports exactly what the executable's own
    cost_analysis reports — the live/offline agreement the acceptance
    criterion demands, on the same CPU executable."""
    compiled = _compiled_matmul()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    xla_flops = float(cost.get("flops", 0.0))
    assert xla_flops > 0  # a 64³ matmul is not free
    rec = CapacityLedger().record("mm", compiled)
    assert rec["flops"] == pytest.approx(xla_flops, rel=0.01)


def test_program_cost_tolerates_missing_apis():
    class Broken:
        def cost_analysis(self):
            raise RuntimeError("no analysis on this backend")

        def memory_analysis(self):
            raise RuntimeError("nope")

    c = program_cost(Broken())
    assert c == {"flops": 0.0, "bytes": 0.0, "peak_hbm_bytes": 0.0}


def test_record_jit_requires_lower():
    cap = CapacityLedger()
    assert cap.record_jit("k", lambda x: x, 1) is False
    assert cap.snapshot()["programs"] == {}

    @jax.jit
    def f(x):
        return x * 2.0

    assert cap.record_jit("k", f, jnp.ones((8, 8))) is True
    assert "k" in cap.snapshot()["programs"]


# ---------------------------------------------------- utilization


def _peaks(flops=1e9, hbm_bw=1e9):
    from distributed_sod_project_tpu.utils.chips import ChipPeaks

    return ChipPeaks(flops_bf16=flops, hbm_bw=hbm_bw, hbm_bytes=1e9,
                     ici_bw=1e9, vmem_bytes=1 << 20, source="test")


def test_no_mfu_on_cpu_and_unknown_chip_is_an_error():
    """The ledger divides by the peaks of the chip it RUNS ON
    (utils/chips.py): on the CPU there is none and MFU reads 0 — never
    a share of a v5e's peak — and a device kind without a row in the
    table is an error wherever a share of peak would be printed."""
    from distributed_sod_project_tpu.utils import chips

    cap = CapacityLedger()  # this process: the CPU
    assert cap.peaks is None

    class Stub:
        def cost_analysis(self):
            return {"flops": 5e8, "bytes accessed": 1e9}

        def memory_analysis(self):
            return None

    cap.record("p", Stub())
    cap.observe("p", 1000.0)
    snap = cap.snapshot()
    assert snap["programs"]["p"]["mfu"] == 0.0
    assert snap["programs"]["p"]["flops"] == 5e8  # static cost still there
    assert snap["peak_flops"] is None
    assert chips.chip_peaks("TPU v5 lite").flops_bf16 == 197e12
    with pytest.raises(chips.UnknownChipError, match="TPU v9 ultra"):
        chips.chip_peaks("TPU v9 ultra")


def test_mfu_and_roofline_math():
    cap = CapacityLedger(peaks=_peaks())

    class Stub:
        def cost_analysis(self):
            return {"flops": 5e8, "bytes accessed": 1e9}

        def memory_analysis(self):
            return None

    cap.record("p", Stub())
    assert cap.mfu("p") == 0.0  # no measurement yet: never invent one
    cap.observe("p", 1000.0)    # exactly one second
    assert cap.mfu("p") == pytest.approx(0.5)
    snap = cap.snapshot()["programs"]["p"]
    # Bandwidth-bound: roofline util is the max of the two.
    assert snap["roofline_util"] == pytest.approx(1.0)
    assert snap["mfu"] == pytest.approx(0.5)
    # EWMA folds at 0.8/0.2.
    cap.observe("p", 500.0)
    assert cap.snapshot()["programs"]["p"]["device_ms_ewma"] == \
        pytest.approx(900.0)
    # Unknown key: a silent no-op (telemetry must not throw).
    cap.observe("nope", 1.0)


def test_device_hbm_gauges_platform_stable():
    rows = device_hbm_gauges()
    assert rows  # CPU renders zero rows, never an empty family
    for _dev, in_use, headroom in rows:
        assert in_use >= 0 and headroom >= 0


def test_prom_families_shape():
    cap = CapacityLedger(
        share_fn=lambda: {"device": 0.6, "queue": 0.3, "host": 0.1})

    class Stub:
        def cost_analysis(self):
            return {"flops": 1e6, "bytes accessed": 2e6}

        def memory_analysis(self):
            return None

    cap.record("m/r64b1/fast/f32", Stub())
    cap.observe("m/r64b1/fast/f32", 10.0)
    fams = dict((n, (t, s)) for n, t, s in
                cap.prom_families('model="m"'))
    assert fams["dsod_capacity_program_flops"][1] == [
        'dsod_capacity_program_flops{model="m",'
        'program="m/r64b1/fast/f32"} 1e+06']
    share = {s.split('stage="')[1].split('"')[0]:
             float(s.rsplit(" ", 1)[1])
             for s in fams["dsod_capacity_stage_share"][1]}
    assert share == {"device": 0.6, "queue": 0.3, "host": 0.1}
    assert "dsod_capacity_hbm_headroom_bytes" in fams


# ------------------------------------------------ engine integration


class TinySOD(nn.Module):
    @nn.compact
    def __call__(self, image, depth=None, train=False):
        x = nn.Conv(4, (3, 3), name="c1")(image)
        x = nn.relu(x)
        return (nn.Conv(1, (1, 1), name="head")(x),)


def test_engine_capacity_ledger_end_to_end():
    cfg = ExperimentConfig(
        data=DataConfig(image_size=(16, 16)),
        model=ModelConfig(name="minet"),
        serve=ServeConfig(batch_buckets=(1, 2), resolution_buckets=(16,),
                          precision_arms=("f32", "bf16"),
                          capacity_ledger=True,
                          watchdog_deadline_s=30.0))
    model = TinySOD()
    probe = np.zeros((1, 16, 16, 3), np.float32)
    variables = model.init(jax.random.key(0), probe, None, train=False)
    eng = InferenceEngine(cfg, model, variables).start()
    try:
        # Warmup recorded every (res, batch, arm) program.
        programs = eng.capacity.snapshot()["programs"]
        assert set(programs) == {
            f"minet/r16b{b}/fast/xla/{a}"
            for b in (1, 2) for a in ("f32", "bf16")}
        assert all(p["flops"] > 0 for p in programs.values())
        # A served request feeds the EWMA of ITS program only.
        pred, meta = eng.predict(np.zeros((16, 16, 3), np.uint8))
        key = f"minet/r16b{meta['batch_bucket']}/fast/xla/f32"
        snap = eng.capacity.snapshot()
        assert snap["programs"][key]["device_ms_ewma"] > 0
        assert snap["programs"][key]["mfu"] >= 0
        untouched = [k for k in programs if k != key]
        assert all(snap["programs"][k]["device_ms_ewma"] is None
                   for k in untouched)
        # Stage shares are fractions that cover the e2e.
        shares = snap["stage_share"]
        assert set(shares) == {"device", "queue", "host"}
        assert all(0.0 <= v <= 1.0 for v in shares.values())
        # snapshot() rounds each share to 6 decimals, so the three
        # rounding errors can stack to 1.5e-6 — the bound must cover
        # that, or the assertion flakes on unlucky measured timings.
        assert sum(shares.values()) == pytest.approx(1.0, abs=2e-6)
        # The families ride the engine registry.
        text = eng.telemetry.render()
        for fam in ("dsod_capacity_mfu", "dsod_capacity_stage_share",
                    "dsod_capacity_program_peak_hbm_bytes",
                    "dsod_capacity_hbm_headroom_bytes"):
            assert fam in text, fam
        # /stats carries the capacity block.
        assert "capacity" in eng.stats_snapshot()
    finally:
        eng.stop()


# ------------------------------------------------ trainer integration


def test_fit_capacity_and_goodput_slo_on_sidecar(tmp_path):
    """A tiny fit with capacity_ledger + a goodput SLO: the step
    program's cost lands in dsod_capacity_*, every completed step
    feeds the SLO, and /slo answers on the sidecar."""
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.train.loop import fit

    cfg = get_config("minet_vgg16_ref").replace(
        data=DataConfig(dataset="synthetic", image_size=(32, 32),
                        synthetic_size=32, num_workers=0),
        model=ModelConfig(name="vit_sod", backbone="tiny", sync_bn=False,
                          compute_dtype="float32"),
        global_batch_size=8, num_epochs=2, log_every_steps=2,
        checkpoint_every_steps=8, tensorboard=False,
        checkpoint_dir=str(tmp_path / "ck"),
        capacity_ledger=True,
        slo_objectives=("goodput:all:latency:0.5:600:600000",))
    pf = str(tmp_path / "telem.port")
    got = {}

    def on_metrics(step, host):
        if step < 8 or got:
            return
        with open(pf) as f:
            url = f"http://127.0.0.1:{int(f.read())}"
        for ep in ("/metrics", "/slo", "/healthz"):
            with urllib.request.urlopen(url + ep, timeout=30) as r:
                got[ep] = r.read().decode()

    out = fit(cfg, max_steps=8, hooks={"on_metrics": on_metrics},
              telemetry_port=0, telemetry_port_file=pf)
    assert out["final_step"] == 8
    assert got, "the on_metrics scrape never ran"
    metrics = got["/metrics"]
    assert "dsod_capacity_program_flops" in metrics
    assert 'program="train/32x32/k1"' in metrics
    assert "dsod_slo_burn_rate" in metrics
    slo = json.loads(got["/slo"])
    obj = slo["objectives"][0]
    assert obj["name"] == "goodput" and obj["kind"] == "latency"
    # Warmup-gated: the first (compile) intervals are skipped, the
    # rest all completed well under the absurd 600 s threshold.
    assert obj["good"] >= 4 and obj["bad"] == 0
    assert json.loads(got["/healthz"])["status"] == "ok"


# ------------------------------------------------ roofline cross-check


@pytest.mark.slow
def test_roofline_xla_check_cross_checks_capacity_ledger():
    """tools/roofline.py --xla-check on the REAL train step now also
    records the same compiled executable into a CapacityLedger and
    fails the band when the live surface disagrees with cost_analysis
    by more than 1% — run it end to end, as a SUBPROCESS: conftest's
    8-virtual-device mesh would shard the step, and cost_analysis on a
    shard_map program reports PER-SHARD flops (the tool's hand-ledger
    band is calibrated for the 1-device t1.sh posture)."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # drop the forced 8-device platform
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "tools",
                      "roofline.py"), "--xla-check"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "capacity ledger" in proc.stdout
    assert "must be within 1%" in proc.stdout
