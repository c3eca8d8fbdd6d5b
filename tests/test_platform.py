"""utils/platform.py: --device handling, device naming and the compile
cache's placement rule.  In-process and off the TPU library on purpose
(the backend is faked): only tests/test_chip_compile.py may load it."""

import os

import jax
import pytest

from distributed_sod_project_tpu.utils import platform as plat


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_select_platform_tpu_fails_on_a_cpu_only_process(
        monkeypatch, config_updates):
    """--device tpu pins the platform and fails loudly, naming the
    backend found — it used to be a no-op that carried on on the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    with pytest.raises(plat.NoAcceleratorError, match="'cpu' backend"):
        plat.select_platform("tpu")
    assert ("jax_platforms", "tpu") in config_updates

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu': no device")

    monkeypatch.setattr(jax, "default_backend", no_backend)
    with pytest.raises(plat.NoAcceleratorError,
                       match="no TPU backend.*Unable to initialize"):
        plat.select_platform("tpu")


def test_select_platform_cpu_none_and_unknown(monkeypatch, config_updates):
    plat.select_platform(None)  # library use: JAX's own discovery
    assert config_updates == []
    plat.select_platform("cpu")
    assert config_updates == [("jax_platforms", "cpu")]
    with pytest.raises(ValueError, match="unknown --device"):
        plat.select_platform("gpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plat.select_platform("tpu")  # a TPU process passes


def test_pin_platform_never_touches_a_backend(monkeypatch, config_updates):
    def touched():
        raise AssertionError("pin_platform resolved the backend")

    monkeypatch.setattr(jax, "default_backend", touched)
    plat.pin_platform("tpu")
    assert config_updates == [("jax_platforms", "tpu")]


def test_train_cli_initializes_distributed_before_resolving_the_backend(
        monkeypatch, config_updates):
    """``train.py --device tpu --distributed``: on this JAX,
    ``jax.distributed.initialize()`` raises once the backends are up,
    so the platform is pinned first, the cluster joined second, and
    only then is the backend resolved and held to ``tpu``."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import train

    order = []
    backends_up = []

    def default_backend():
        order.append("resolve")
        backends_up.append(True)
        return "cpu"

    def initialize(*_a, **_k):
        if backends_up:
            raise RuntimeError("jax.distributed.initialize() must be "
                               "called before any JAX computations")
        order.append("initialize")

    monkeypatch.setattr(jax, "default_backend", default_backend)
    monkeypatch.setattr(jax.distributed, "initialize", initialize)
    with pytest.raises(plat.NoAcceleratorError, match="'cpu' backend"):
        train.main(["--config", "minet_r50_dp", "--device", "tpu",
                    "--distributed"])
    assert config_updates[0] == ("jax_platforms", "tpu")
    assert order == ["initialize", "resolve"]


def test_describe_device_names_what_jax_reports():
    d = plat.describe_device()
    assert d == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                 "count": len(jax.devices())}


@pytest.mark.parametrize("env_dir", [None, "/some/where/outside"])
def test_compile_cache_placement_rule(monkeypatch, config_updates, env_dir):
    """JAX_COMPILATION_CACHE_DIR set -> no directory is set in code (JAX
    reads the variable itself); unset -> ONE fixed git-ignored path
    inside the checkout, never the home directory."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("DSOD_NO_COMPILE_CACHE", raising=False)
    made = []
    monkeypatch.setattr(os, "makedirs", lambda p, **k: made.append(p))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    got = plat.enable_compilation_cache()
    set_dirs = [v for k, v in config_updates
                if k == "jax_compilation_cache_dir"]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if env_dir is None:
        assert got == os.path.join(repo, ".jax_cache")
        assert set_dirs == [got] and made == [got]
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    else:
        assert got == env_dir and set_dirs == [] and made == []


def test_compile_cache_off_on_cpu_and_by_env(monkeypatch, config_updates):
    assert plat.enable_compilation_cache() is None  # this process: cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DSOD_NO_COMPILE_CACHE", "1")
    assert plat.enable_compilation_cache() is None
    assert config_updates == []


def test_compile_stats_counts_backend_compiles():
    import jax.numpy as jnp

    stats = plat.CompileStats()
    jax.jit(lambda x: x * 3.0 + 1.0).lower(jnp.ones((7, 5))).compile()
    d = stats.as_dict()
    assert d["seconds"] > 0
    assert set(d) == {"seconds", "trace_seconds", "lower_seconds",
                      "cache_hits", "cache_misses"}
    assert d["trace_seconds"] > 0 and d["lower_seconds"] > 0
