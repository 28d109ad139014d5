"""Test config: simulate an 8-device CPU mesh (SURVEY §4: better than the
reference's subprocess-only story — XLA can fake N devices on one host)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax

# deterministic fp32 matmuls for numerics comparisons against numpy
jax.config.update("jax_default_matmul_precision", "highest")
# Persistent compilation cache: OFF.  jaxlib's CPU backend crashes
# (SIGSEGV/SIGABRT) deserializing cache entries written by an earlier
# process — observed at several different tests depending on which keys
# hit; even intact cross-run entries abort, so reuse is disabled rather
# than hardened.  The entry points place the cache on the chip
# (paddle_tpu/utils/compile_cache.py).
jax.config.update("jax_enable_compilation_cache", False)
assert jax.default_backend() == "cpu", jax.default_backend()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "chaos: fault-injection tests (subprocess kills, "
        "corrupted shards, partitioned stores); deterministic under "
        "FLAGS_ft_inject_seed — run the full matrix with scripts/chaos_sweep.sh")
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 "
        "selection (-m 'not slow')")


import pytest


@pytest.fixture(autouse=True, scope="module")
def _global_mesh_stays_in_its_file():
    """A file that sets the process-global mesh (``fleet.init``,
    ``set_global_mesh``) keeps it to itself: the next file of the same
    xdist worker builds its models without it (a Llama built under a stray
    mesh carries sharding constraints, which the ONNX exporter refuses)."""
    from paddle_tpu.distributed import mesh

    before = mesh.get_mesh()
    yield
    mesh.set_global_mesh(before)
