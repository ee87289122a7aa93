"""Test harness for deepspeed_tpu.

The reference simulates multi-node as multi-process on one host
(tests/unit/common.py:DistributedExec). The TPU-native analogue is simpler:
JAX can expose N virtual CPU devices in one process
(``--xla_force_host_platform_device_count``), so every multi-chip sharding
test runs single-process over an 8-device mesh. Env vars must be set before
jax is imported, hence this module-level block.
"""

import os

# Unit tests run on the CPU backend, never on an attached accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
# The CPU thunk runtime's concurrency-optimized schedule can execute
# independent collectives in different orders on different virtual
# devices; each collective BLOCKS its worker thread until all devices
# arrive, so on a small host (CI boxes can have ONE core) two reordered
# collectives deadlock the rendezvous (observed: ZeRO-1 grad allreduce
# vs a gather, rendezvous.cc "Termination timeout ... exceeded").
# Force program order, and raise the 20s/40s rendezvous timeouts that
# otherwise fire spuriously under heavy time-sharing.
if "xla_cpu_enable_concurrency_optimized_scheduler" not in _flags:
    _flags += " --xla_cpu_enable_concurrency_optimized_scheduler=false"
if "xla_cpu_collective" not in _flags:
    _flags += (" --xla_cpu_collective_call_warn_stuck_timeout_seconds=300"
               " --xla_cpu_collective_call_terminate_timeout_seconds=1200"
               " --xla_cpu_collective_timeout_seconds=1200")
os.environ["XLA_FLAGS"] = _flags

import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8(devices):
    """A flat 8-way data mesh."""
    from deepspeed_tpu.parallel.mesh import build_mesh
    return build_mesh(data=8)


# Persistent compilation cache: the suite's wall clock is dominated by XLA
# CPU compiles of near-identical tiny programs; caching them across runs
# (and across tests in one run) cuts a cold ~50 min suite to the warm
# execution time. Safe to share: keys include jaxlib version + flags.
# Placement follows utils/compile_cache: JAX_COMPILATION_CACHE_DIR when the
# environment sets it, else one fixed path (DSTPU_TEST_CACHE, default
# <repo>/.jax_test_cache).
from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache(
    default_dir=os.environ.get(
        "DSTPU_TEST_CACHE",
        os.path.join(os.path.dirname(__file__), "..", ".jax_test_cache")),
    min_compile_secs=0.1)
jax.config.update("jax_persistent_cache_enable_xla_caches", "all")


def pytest_collection_modifyitems(config, items):
    """Dynamic 'smoke' marker (VERDICT r3 #10): `pytest -m smoke` runs a
    <5 min cross-subsystem slice listed in tests/smoke.txt — one fast test
    per area — without scattering marks over 40 files."""
    smoke_file = os.path.join(os.path.dirname(__file__), "smoke.txt")
    if not os.path.exists(smoke_file):
        return
    with open(smoke_file) as fh:
        wanted = {ln.strip() for ln in fh
                  if ln.strip() and not ln.startswith("#")}
    for item in items:
        base = item.nodeid.split("[")[0]
        if base in wanted or item.nodeid in wanted:
            item.add_marker(pytest.mark.smoke)
