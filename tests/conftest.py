"""Test harness: the CPU backend with 8 virtual devices.

Tests run on the CPU (``JAX_PLATFORMS=cpu``), where a mesh of 8 virtual
devices stands in for a pod slice (SURVEY.md §4) and the Pallas kernels
run in interpret mode.  The chip is reached through ``chip_smoke.py``,
never through this suite; the one file that loads the TPU's compiler
(for a described, not attached, chip) is tests/test_chip_compile.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "float32")
# Persistent compilation cache for the suite's own CPU programs: the
# model-zoo compiles dominate suite time.  Keyed by this host's CPU
# identity and the jaxlib version, because XLA:CPU entries pin machine
# features and abort the process when replayed on different silicon.
import hashlib  # noqa: E402


def _cpu_key() -> str:
    """CPU identity (family/model/stepping/model name and feature
    flags: llvm picks host-tuning pseudo-features from the
    micro-architecture, invisible in the flags alone) plus the jaxlib
    version, so an image bump never replays old entries."""
    try:
        with open("/proc/cpuinfo") as f:
            # x86 spells it "flags", ARM "Features"; include the model
            # identity lines (sorted-unique: one socket's worth).
            keep = ("flags", "Features", "model", "cpu family",
                    "stepping", "vendor_id",
                    # ARM spells CPU identity differently:
                    "CPU implementer", "CPU part", "CPU variant",
                    "CPU architecture", "CPU revision")
            ident = "".join(sorted({line for line in f
                                    if line.startswith(keep)}))
        if not ident:
            raise OSError("no cpuinfo lines")
    except OSError:
        import platform

        ident = (platform.processor() or platform.machine() or "unknown")
    import jaxlib

    ident += f"|jaxlib={getattr(jaxlib, '__version__', '?')}"
    return hashlib.sha1(ident.encode()).hexdigest()[:10]


jax.config.update("jax_compilation_cache_dir",
                  f"/tmp/jax_pytest_cache_{_cpu_key()}")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
jax.config.update("jax_persistent_cache_enable_xla_caches",
                  "xla_gpu_per_fusion_autotune_cache_dir")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


@pytest.fixture(autouse=True)
def _chaos_deadline(request):
    """Per-test deadline for the chaos suite (pytest.ini `chaos`
    marker).  Fault-injection tests stall/kill/corrupt things on
    purpose; a recovery-path bug must surface as a bounded-time test
    failure, not wedge the whole tier-1 run until its outer `timeout`
    kills everything.  SIGALRM-based because the image ships no
    pytest-timeout; default 120 s, override via
    ``@pytest.mark.chaos(timeout=N)``."""
    import signal

    marker = request.node.get_closest_marker("chaos")
    if marker is None or not hasattr(signal, "SIGALRM"):
        yield
        return
    limit = int(marker.kwargs.get("timeout", 120))

    def _expire(signum, frame):
        raise TimeoutError(
            f"chaos test exceeded its {limit}s deadline — a recovery "
            "path is wedged (see docs/RESILIENCE.md)")

    prev = signal.signal(signal.SIGALRM, _expire)
    signal.alarm(limit)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)
