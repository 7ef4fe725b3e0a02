"""Test harness: simulated 8-device TPU-shaped mesh on CPU.

The reference has no test suite (SURVEY §4); its answer to "multi-node
without a cluster" was unsolved. Ours: force the CPU backend with 8
virtual devices (`--xla_force_host_platform_device_count=8`) so every
sharding/collective path runs under pytest on any machine. The platform
is pinned via jax.config as well as by the caller's JAX_PLATFORMS=cpu,
so a bare `pytest` on a machine with a chip does not take the chip.
"""

import os
import tempfile

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
# Persistent XLA compile cache, shared with the subprocess CLI tests
# (supervisor/serve spawn `python -m hyperion_tpu.cli.main ...`, which
# inherits this env): the trainer re-jits an identical step function
# per call, and without the cache each integration test pays the same
# ~35s XLA compile again. Content-keyed, so correctness is unaffected;
# compile-count assertions count traces, not XLA wall time.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "hyperion_tpu_xla_cache"),
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    ds = jax.devices()
    assert len(ds) == 8, f"expected 8 simulated devices, got {len(ds)}"
    return ds


@pytest.fixture(scope="session")
def mesh8():
    from hyperion_tpu.runtime.mesh import MeshSpec, make_mesh

    return make_mesh(MeshSpec(data=2, fsdp=4))


@pytest.fixture(scope="session")
def mesh_dp():
    from hyperion_tpu.runtime.mesh import MeshSpec, make_mesh

    return make_mesh(MeshSpec(data=-1))
