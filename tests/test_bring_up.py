"""No fallback that hides the device, one process per chip: the small
rules PR 21 put in place of the code that guessed."""

from __future__ import annotations

import os
import sys
import types

import jax
import numpy as np
import pytest

import hyperion_tpu.ops.pallas.flash_attention  # noqa: F401 — see below
import hyperion_tpu.ops.pallas.fused_ce  # noqa: F401
import hyperion_tpu.ops.pallas.fused_norm  # noqa: F401
import hyperion_tpu.ops.pallas.paged_attention  # noqa: F401

KERNELS = ("flash_attention", "fused_ce", "fused_norm", "paged_attention")


def kernel_module(name):
    # the package re-exports flash_attention the function under the
    # module's own name; sys.modules has the module
    return sys.modules[f"hyperion_tpu.ops.pallas.{name}"]


class TestInterpretDecision:
    @pytest.mark.parametrize("name", KERNELS)
    def test_unknown_backend_is_refused(self, monkeypatch, name):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            kernel_module(name)._interpret()

    @pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
    def test_cpu_interprets_tpu_compiles(self, monkeypatch, backend, want):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        for name in KERNELS:
            assert kernel_module(name)._interpret() is want


def fake_device(platform, kind, stats):
    return types.SimpleNamespace(platform=platform, device_kind=kind,
                                 memory_stats=lambda: stats)


class TestNoSilentNone:
    def test_unknown_tpu_kind_raises(self):
        from hyperion_tpu.utils.chips import nominal_peak_tflops

        with pytest.raises(ValueError, match="TPU v9"):
            nominal_peak_tflops("bfloat16", fake_device("tpu", "TPU v9", {}))

    def test_known_kinds_and_cpu(self):
        from hyperion_tpu.utils.chips import nominal_peak_tflops

        v5e = fake_device("tpu", "TPU v5 lite", {})
        assert nominal_peak_tflops("bfloat16", v5e) == 197.0
        assert nominal_peak_tflops("int8", v5e) == 394.0
        assert nominal_peak_tflops("bfloat16", jax.devices()[0]) is None

    def test_tpu_without_memory_stats_raises(self):
        from hyperion_tpu.utils.memory import device_memory_stats

        with pytest.raises(RuntimeError, match="memory_stats"):
            device_memory_stats(fake_device("tpu", "TPU v5 lite", None))

    def test_cpu_without_memory_stats_reads_zero(self):
        from hyperion_tpu.utils.memory import (
            device_memory_stats,
            peak_bytes_in_use,
        )

        cpu = fake_device("cpu", "cpu", None)
        assert device_memory_stats(cpu) == {}
        assert peak_bytes_in_use(cpu) == 0


class TestScalingParentStaysOffJax:
    """bench/scaling.py starts one trainer per device count; a parent
    that had asked JAX for the devices would hold the chips they need."""

    @pytest.fixture()
    def sweep(self, monkeypatch, tmp_path):
        from hyperion_tpu.bench import scaling

        def no_jax(*a, **k):
            raise AssertionError("the scaling parent asked JAX")

        monkeypatch.setattr(jax, "devices", no_jax)
        monkeypatch.setattr(jax, "default_backend", no_jax)
        monkeypatch.setattr(jax, "device_count", no_jax)
        monkeypatch.setattr(scaling.time, "sleep", lambda s: None)
        calls = []
        monkeypatch.setattr(
            scaling.subprocess, "run",
            lambda cmd, check, env: calls.append((cmd, env)))

        def run(**kw):
            scaling.run_scaling_experiment(
                models="language_ddp", base_dir=str(tmp_path), **kw)
            return calls

        return run

    def test_module_does_not_import_jax_itself(self):
        from hyperion_tpu.bench import scaling

        assert not hasattr(scaling, "jax")

    def test_real_devices_take_counts_from_the_caller(self, sweep):
        calls = sweep(device_counts=[1, 4])
        assert [c[c.index("--devices") + 1] for c, _ in calls] == ["1", "4"]
        for _, env in calls:   # the children see the caller's backend
            assert env.get("JAX_PLATFORMS") == os.environ.get("JAX_PLATFORMS")

    def test_real_devices_without_counts_is_an_error(self, sweep):
        with pytest.raises(ValueError, match="--scaling_devices"):
            sweep(device_counts=None)

    def test_simulation_is_chosen_by_flag(self, sweep):
        calls = sweep(device_counts=None, simulate_on_cpu=True)
        assert [c[c.index("--devices") + 1] for c, _ in calls] == \
            ["1", "2", "4", "8"]
        for _, env in calls:
            assert env["JAX_PLATFORMS"] == "cpu"
            assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]


class TestExportRoundTrip:
    def test_bfloat16_leaves_come_back_as_bfloat16(self, tmp_path):
        """npy has no name for bfloat16; a bf16 export used to load as
        2-byte void and could not be served."""
        import jax.numpy as jnp

        from hyperion_tpu.checkpoint.io import export_gathered, load_gathered

        tree = {"a": {"kernel": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4)},
                "norm": {"weight": jnp.ones((4,), jnp.float32)}}
        export_gathered(tmp_path / "t.npz", tree)
        back = load_gathered(tmp_path / "t.npz")
        assert back["a"]["kernel"].dtype == jnp.bfloat16
        assert back["norm"]["weight"].dtype == np.float32
        np.testing.assert_array_equal(
            np.asarray(back["a"]["kernel"], np.float32),
            np.arange(12, dtype=np.float32).reshape(3, 4))


class TestEnginePlacesWeightsOnce:
    def test_host_tree_is_put_on_the_device_at_construction(self):
        """The server hands the engine the export as numpy; passed to a
        jit as it is, the whole tree would be uploaded on every call."""
        from hyperion_tpu.models.llama import Llama, llama_tiny_config
        from hyperion_tpu.serve.engine import Engine, EngineConfig

        model = Llama(llama_tiny_config())
        params = jax.tree_util.tree_map(
            np.asarray, model.init_params(jax.random.key(0), seq=8))
        eng = Engine(model, {"params": params},
                     EngineConfig(slots=2, max_len=32))
        leaves = jax.tree_util.tree_leaves(eng.variables)
        assert leaves and all(isinstance(x, jax.Array) for x in leaves)


class TestSuperviseParentsLeaveTheChipAlone:
    """`--supervise` parents may import JAX; they must not initialise a
    backend, or the child they watch cannot have the chip."""

    CODE = """
import sys
sys.path.insert(0, {repo!r})
import hyperion_tpu.supervisor as sup
import hyperion_tpu.train.supervisor as tsup
started = []
def fake_loop(child, **kw):
    started.append(child)
    return 0
sup.supervise_loop = fake_loop
tsup.supervise_loop = fake_loop
from hyperion_tpu.cli.main import main
rc = main({argv!r})
assert rc == 0 and len(started) == 1, (rc, started)
assert "--supervise" not in started[0]
import jax
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), "parent initialised a backend"
print("OK")
"""

    @pytest.mark.parametrize("argv", [
        ["--model", "language_ddp", "--supervise", "--epochs", "1"],
        ["serve", "--ckpt", "x.npz", "--supervise"],
    ], ids=["trainer", "serve"])
    def test_parent_reaches_the_spawn_without_a_backend(self, argv, tmp_path):
        import subprocess
        from pathlib import Path

        repo = str(Path(__file__).resolve().parents[1])
        if argv[0] == "--model":
            argv = [*argv, "--base_dir", str(tmp_path)]
        p = subprocess.run(
            [sys.executable, "-c", self.CODE.format(repo=repo, argv=argv)],
            capture_output=True, text=True, timeout=120, cwd=tmp_path,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert p.returncode == 0 and "OK" in p.stdout, p.stderr[-2000:]
