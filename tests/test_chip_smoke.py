"""chip_smoke.py's contract off the chip: the rehearsal option ends in
a well-formed last line that is never ``ok``, and a directory holding
the script alone fails without a result.  (The no-chip run of the real
phases is in tests/test_chip_compile.py — it loads the TPU library.)"""

import json
import os
import shutil
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402  (imports no JAX)


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_rehearsal_ends_well_formed_and_never_ok(monkeypatch, capsys):
    """--rehearse-cpu with every phase passing (stubbed: the phases
    themselves are rehearsed by hand, minutes of CPU compile) still
    reports ok=false, names the CPU device, and exits non-zero."""
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    seen = []
    monkeypatch.setattr(chip_smoke, "phase_trainer",
                        lambda sz, tmp, t0: seen.append(sz) or cpu)
    monkeypatch.setattr(chip_smoke, "phase_server",
                        lambda sz, tmp, t0: seen.append(sz))
    monkeypatch.setattr(chip_smoke, "run_self_child",
                        lambda name, args, t0: cpu)
    rc = chip_smoke.main(["--rehearse-cpu"])
    last = _last_json(capsys.readouterr().out)
    assert rc != 0
    assert last == {"ok": False, "device": cpu, "rehearsal": True,
                    "phases_ok": True}
    assert seen == [chip_smoke.REHEARSAL] * 2  # tiny sizes, cpu device
    assert chip_smoke.REHEARSAL["device"] == "cpu"
    assert chip_smoke.REAL["device"] == "tpu"


def test_cpu_device_without_the_option_is_a_failure(monkeypatch, capsys):
    """Every phase 'passing' on a CPU device is still not ok: only a
    TPU of the expected count ends in ok=true."""
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    monkeypatch.setattr(chip_smoke, "phase_trainer", lambda *a: cpu)
    monkeypatch.setattr(chip_smoke, "phase_server", lambda *a: None)
    monkeypatch.setattr(chip_smoke, "run_self_child", lambda *a: cpu)
    assert chip_smoke.main([]) != 0
    last = _last_json(capsys.readouterr().out)
    assert last["ok"] is False and "TPU chip" in last["error"]
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "phase_trainer", lambda *a: tpu)
    monkeypatch.setattr(chip_smoke, "run_self_child", lambda *a: tpu)
    assert chip_smoke.main([]) == 0
    assert _last_json(capsys.readouterr().out) == {"ok": True,
                                                   "device": tpu}
    # Four chips asked for, one found: not ok either.
    assert chip_smoke.main(["--four-chips"]) != 0
    assert _last_json(capsys.readouterr().out)["ok"] is False


def test_script_alone_fails_without_a_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo: non-zero exit, no ok=true, no child ever started."""
    shutil.copy(chip_smoke.__file__, tmp_path / "chip_smoke.py")
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode != 0
    last = _last_json(p.stdout)
    assert last["ok"] is False and last["device"] is None
    assert "train.py is not next to chip_smoke.py" in last["error"]
