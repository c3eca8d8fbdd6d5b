"""A configuration, a cell, a runner and a per-layer metric are each
added by files of their own and entries in BENCHMARK.json, with no
edit to a file that is there: proved on a temporary copy."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

RUNNER = '''
def run(ctx):
    return {"correct": True, "attempted": 3, "failed": 0,
            "end_to_end": {"dummy_rate": 7.0, "setup_s": 0.5},
            "memory_peak_bytes": 1,
            "sources": {"dummy_rows": [1.0, 2.0, 6.0],
                        "size": ctx["config"]["size"] * ctx["cell"]["scale"]}}
'''
READER = '''
def read(run):
    return sum(run["dummy_rows"]) * run["size"]
'''


def test_add_config_cell_runner_metric_by_files_only(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    b = tmp_path / "benchmark"
    (b / "configs" / "dummy.json").write_text(json.dumps({"size": 2}))
    (b / "workloads" / "dummy.mix.json").write_text(json.dumps(
        {"runner": "dummy", "config": "dummy", "chips": 1, "scale": 5}))
    (b / "runners" / "dummy.py").write_text(RUNNER)
    (b / "layer_metrics" / "dummy_sum.py").write_text(READER)
    m["configs"].append({"name": "dummy", "source": "none", "reduced": [],
                         "file": "benchmark/configs/dummy.json", "why": "x"})
    m["workloads"].append({"name": "dummy.mix", "config": "dummy",
                           "traffic": "mix", "chips": 1, "why": "x"})
    m["end_to_end"].append({"name": "dummy_rate", "unit": "x/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["dummy.mix"]})
    m["per_layer"].append({"name": "dummy_sum", "unit": "x",
                           "better": "lower", "source": "program_counter",
                           "layer": "dummy", "moves": "dummy_rate",
                           "workloads": ["dummy.mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    code = (
        "import sys, json; sys.path.insert(0, %r)\n"
        "from benchmark import run\n"
        "dev = {'platform': 'tpu', 'kind': 'rehearsal', 'count': 1}\n"
        "for tr in (False, True):\n"
        "    print(json.dumps(run.run_cell('dummy.mix', 1, 1.0, tr, "
        "root=%r, device=dev)))\n" % (str(tmp_path), str(tmp_path)))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, cwd=str(tmp_path))
    e2e, layer = (json.loads(x) for x in out.stdout.strip().splitlines()[-2:])
    assert e2e["metrics"] == {"dummy_rate": {"value": 7.0, "unit": "x/s"},
                              "setup_s": {"value": 0.5, "unit": "s"}}
    assert layer["metrics"] == {"dummy_sum": {"value": 90.0, "unit": "x"}}
