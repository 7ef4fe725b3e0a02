import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from hyperion_tpu.runtime import dist
from hyperion_tpu.runtime.comm_check import comm_check
from hyperion_tpu.runtime.mesh import (
    AxisName,
    MeshSpec,
    batch_sharding,
    global_batch_size,
    make_mesh,
    replicated_sharding,
)


class TestMeshSpec:
    def test_infer_axis(self):
        assert MeshSpec(data=-1, fsdp=2).resolve(8).shape == (4, 2, 1, 1, 1, 1)

    def test_explicit(self):
        assert MeshSpec(data=2, fsdp=2, model=2).resolve(8).shape == (2, 2, 2, 1, 1, 1)

    def test_mismatch_raises(self):
        with pytest.raises(ValueError):
            MeshSpec(data=3).resolve(8)
        with pytest.raises(ValueError):
            MeshSpec(data=-1, fsdp=3).resolve(8)
        with pytest.raises(ValueError):
            MeshSpec(data=-1, fsdp=-1).resolve(8)


class TestMesh:
    def test_default_all_data(self, devices):
        mesh = make_mesh()
        assert mesh.shape[AxisName.DATA] == 8
        assert mesh.shape[AxisName.FSDP] == 1

    def test_axes_complete(self, mesh8):
        assert set(mesh8.axis_names) == set(AxisName.ALL)
        assert mesh8.shape[AxisName.DATA] == 2
        assert mesh8.shape[AxisName.FSDP] == 4

    def test_batch_sharding_spans_data_and_fsdp(self, mesh8):
        s = batch_sharding(mesh8)
        x = jax.device_put(np.zeros((16, 4), np.float32), s)
        # batch split over data(2) x fsdp(4) = 8 shards of 2 rows
        assert x.addressable_shards[0].data.shape == (2, 4)
        assert global_batch_size(2, mesh8) == 16

    def test_replicated(self, mesh8):
        s = replicated_sharding(mesh8)
        x = jax.device_put(np.ones((3,)), s)
        assert x.addressable_shards[0].data.shape == (3,)
        assert len(x.addressable_shards) == 8


class TestDist:
    def test_single_process_noop(self):
        dist.setup()  # must be a no-op without multi-process env
        assert dist.is_primary()
        assert dist.process_count() == 1
        dist.barrier()
        dist.cleanup()


class TestCommCheck:
    def test_all_collectives_pass(self, devices):
        assert comm_check(verbose=False)

    def test_subset_ring(self, devices):
        assert comm_check(devices=devices[:4], verbose=False)

    def test_cli_exit_code(self, capsys):
        from hyperion_tpu.runtime.comm_check import main

        assert main([]) == 0
        assert "ALL COLLECTIVES PASSED" in capsys.readouterr().out


class TestHostCoordIntegration:
    """VERDICT r2 item 6: the C++ HostCoordinator must be reachable
    THROUGH dist (setup/barrier/cleanup), not only via native_coord.
    Two real OS processes run the handshake + named barriers."""

    WORKER = r"""
import os, sys
sys.path.insert(0, os.environ["HYP_REPO"])
from hyperion_tpu.runtime import dist

dist.setup()
assert dist.is_primary() == (os.environ["RANK"] == "0")
for i in range(3):
    dist.barrier(f"step_{i}")
alive = dist.peers_alive()
dist.cleanup()
print(f"WORKER_OK rank={os.environ['RANK']} alive={alive}")
"""

    def _spawn(self, rank: int, world: int, port: int, extra_env=None):
        import subprocess, sys, os, pathlib

        env = dict(os.environ)
        env.update({
            "RANK": str(rank), "WORLD_SIZE": str(world),
            "MASTER_ADDR": "127.0.0.1",
            "HYPERION_COORD_PORT": str(port),
            "HYPERION_SKIP_JAX_INIT": "1",
            "HYP_REPO": str(pathlib.Path(__file__).resolve().parents[1]),
            "JAX_PLATFORMS": "cpu",
        })
        env.update(extra_env or {})
        return subprocess.Popen(
            [sys.executable, "-c", self.WORKER],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )

    @staticmethod
    def _free_port() -> int:
        # a fixed port collides with a lingering socket of an earlier
        # run (or of a neighbouring suite on the same host)
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def test_two_process_setup_and_barriers(self):
        port = self._free_port()
        p0 = self._spawn(0, 2, port)
        p1 = self._spawn(1, 2, port)
        # two interpreter start-ups beside five busy xdist workers: the
        # handshake itself waits 300 s, so the test must not give up first
        out0, _ = p0.communicate(timeout=300)
        out1, _ = p1.communicate(timeout=300)
        assert p0.returncode == 0, out0
        assert p1.returncode == 0, out1
        assert "WORKER_OK rank=0 alive=2" in out0
        assert "WORKER_OK rank=1" in out1

    def test_peer_death_fails_fast(self):
        """A worker that dies must turn the primary's barrier into an
        error, not a hang (the reference's watchdog-off failure mode)."""
        import subprocess, sys, os, pathlib

        port = self._free_port()
        dead_worker = r"""
import os, sys
sys.path.insert(0, os.environ["HYP_REPO"])
from hyperion_tpu.runtime import dist
dist.setup()
os._exit(1)  # die without cleanup, mid-job
"""
        survivor = r"""
import os, sys
sys.path.insert(0, os.environ["HYP_REPO"])
from hyperion_tpu.runtime import dist
from hyperion_tpu.runtime.native_coord import CoordError
dist.setup()
import time; time.sleep(1.0)
try:
    dist.barrier("after_death")
    print("BARRIER_PASSED")
except CoordError as e:
    print(f"FAST_FAIL {e}")
"""
        env_base = {
            "WORLD_SIZE": "2", "MASTER_ADDR": "127.0.0.1",
            "HYPERION_COORD_PORT": str(port),
            "HYPERION_SKIP_JAX_INIT": "1",
            "HYP_REPO": str(pathlib.Path(__file__).resolve().parents[1]),
            "JAX_PLATFORMS": "cpu",
        }

        def spawn(code, rank):
            env = dict(os.environ); env.update(env_base); env["RANK"] = str(rank)
            return subprocess.Popen(
                [sys.executable, "-c", code], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

        p0 = spawn(survivor, 0)
        p1 = spawn(dead_worker, 1)
        p1.communicate(timeout=60)
        out0, _ = p0.communicate(timeout=120)
        assert "FAST_FAIL" in out0, out0

    @pytest.mark.slow
    def test_two_process_real_jax_distributed(self):
        """The one branch the skip-jax tests never reach (dist.py:
        jax.distributed.initialize): two real OS processes rendezvous
        through the JAX coordination service on CPU, agree on
        process_index/count and the global device view, and pass a real
        `barrier()` (sync_global_devices), then cleanup()."""
        import subprocess, sys, os, pathlib

        worker = r"""
import os, sys
sys.path.insert(0, os.environ["HYP_REPO"])
import jax
from hyperion_tpu.runtime import dist

dist.setup()
rank = int(os.environ["RANK"])
assert jax.process_count() == 2, jax.process_count()
assert dist.process_count() == 2
assert dist.process_index() == rank == jax.process_index()
assert dist.is_primary() == (rank == 0)
n_global = jax.device_count()
n_local = len(jax.local_devices())
assert n_global == 2 * n_local, (n_global, n_local)
dist.barrier("real_jax_barrier")
dist.cleanup()
print(f"JAX_DIST_OK rank={rank} global_devices={n_global}")
"""
        from tests.test_native import free_port

        jax_port, coord_port = free_port(), free_port()
        procs = []
        for rank in range(2):
            env = dict(os.environ)
            env.update({
                "RANK": str(rank), "WORLD_SIZE": "2",
                # fresh ports per run: jax's coordinator AND the C++ host
                # layer must not collide with parallel test invocations
                "MASTER_ADDR": f"127.0.0.1:{jax_port}",
                "HYPERION_COORD_PORT": str(coord_port),
                "HYP_REPO": str(pathlib.Path(__file__).resolve().parents[1]),
                "JAX_PLATFORMS": "cpu",
                # one CPU device per process keeps the global view simple
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            })
            env.pop("HYPERION_SKIP_JAX_INIT", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", worker], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            ))
        outs = [p.communicate(timeout=180)[0] for p in procs]
        for rank, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {rank}:\n{out}"
            assert f"JAX_DIST_OK rank={rank} global_devices=2" in out, out

    def test_comm_check_host_only_cli(self):
        import subprocess, sys, os, pathlib

        port = 29521
        repo = str(pathlib.Path(__file__).resolve().parents[1])
        procs = []
        for rank in range(2):
            env = dict(os.environ)
            env.update({
                "RANK": str(rank), "WORLD_SIZE": "2",
                "MASTER_ADDR": "127.0.0.1",
                "HYPERION_COORD_PORT": str(port),
                "JAX_PLATFORMS": "cpu",
            })
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "hyperion_tpu.runtime.comm_check",
                 "--host-only"],
                env=env, cwd=repo,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
        outs = [p.communicate(timeout=120)[0] for p in procs]
        for p, out in zip(procs, outs):
            assert p.returncode == 0, out
            assert "HOST LAYER OK" in out
