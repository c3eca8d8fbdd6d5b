"""The same-program rule, one table: the StableHLO of each benchmark
cell's train step at tiny widths, as ``tools/dump_hlo.py`` lowers it on
the CPU (Pallas kernels in interpret mode), to the byte.

A PR that does not mean to change a step leaves its row alone; a PR
that does changes the row's hash with the step and says so in PERF.md
section 6.  What the rows have meant so far: the five token steps as
the commit before each later model left them, with PR 40's one-kernel
causal backward, PR 44's grouped product (a weight block moves only
where the expert or the column block does) and PR 45's causal flash
grids (only the tile pairs on or under the diagonal); the image step as
PR 46 found it; the sixth token step as PR 47 brought it (the windowed
band and the value's own width moved none of the five before it).  On the chip the kernels are Mosaic calls this lowering
cannot see: ``tools/step_cache_key.py`` hashes those.
"""

import hashlib
import os
import subprocess
import sys

import pytest

PINS = {
    "basnet_ds":
        "dccdc59380e801bbb06d76155660de2d23dfa59df2b0dd132a5b2b8ff6206fa2",
    "lfm2_8b_a1b_ep4":
        "64c67471bb7894da77c5f2c61f88423e2433ea9fd0840880f6f7353582353757",
    "kimi_vl_a3b_ep8":
        "f2f7822374b39c5b885103c7cd5c9afc83db4f2979aa2101c980ed2360d5c0a5",
    "granite_4_0_h_micro_pp4":
        "086f1f0c1c391aaf596551052ca1ffa7836d0226ef53bc26aa73a5cc73d01793",
    "ouro_2_6b_pp6":
        "ec4cabce12c073955b2077f9e80d15c2fff424f93c8b5ca5e2a34ee1cfe85181",
    "nemotron_3_super_tp8_ep64":
        "929d5936b27b67aaffd2b1385ff8685402719f7bd0a5a358b39256fc2e4b91f9",
    "phi4_mini_flash_pp5":
        "cfa5951d4fe55935753c7aa8717ac211328842aa997aef10c7bbe7b7135b6abc",
}


@pytest.fixture(scope="module")
def dumped(tmp_path_factory):
    """Every row's step dumped by ONE child process, as the tool's
    command line runs it (a process of its own: this suite's conftest
    sets a matmul precision, which is part of a program)."""
    out = tmp_path_factory.mktemp("hlo")
    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                         "tools")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    subprocess.run(
        [sys.executable, "-c",
         "import sys, dump_hlo\n"
         "for config in sys.argv[2:]:\n"
         "    dump_hlo.dump(config, sys.argv[1], compile_cost=False)",
         str(out), *PINS],
        check=True, env=dict(env, PYTHONPATH=tools, JAX_PLATFORMS="cpu"),
        capture_output=True, timeout=1200)
    return out


@pytest.mark.parametrize("config", sorted(PINS))
def test_the_step_is_the_program_the_table_pins(dumped, config):
    with open(dumped / f"{config}.stablehlo.txt", "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == PINS[config]
