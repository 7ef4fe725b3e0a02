"""train/supervisor.py — unit tests for the restart policy, plus the
chaos-driven subprocess integration tests (tier-1, CPU): a supervised
run killed mid-epoch twice resumes to the same final state as an
uninterrupted run, and a corrupted latest checkpoint falls back to the
prior verified step."""

import csv
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from hyperion_tpu.train import supervisor
from hyperion_tpu.train.supervisor import (
    ATTEMPT_ENV,
    EXIT_GAVE_UP,
    EXIT_HEALTH_ABORT,
    EXIT_PREEMPTED,
    supervise,
)

# ------------------------------------------------------------ unit half


class FakeChild:
    def __init__(self, rcs):
        self.rcs = list(rcs)
        self.attempts = []

    def __call__(self, argv, env):
        self.attempts.append(env[ATTEMPT_ENV])
        return self.rcs.pop(0)


class TestRestartPolicy:
    def test_restarts_until_success_with_backoff(self, tmp_path):
        child = FakeChild([1, 1, 0])
        sleeps = []
        rc = supervise(["job"], base_dir=tmp_path, max_restarts=3,
                       backoff_s=1.0, run_child=child, sleep=sleeps.append)
        assert rc == 0
        assert child.attempts == ["0", "1", "2"]  # lineage stamped
        assert len(sleeps) == 2 and sleeps[1] > sleeps[0]  # exponential

    def test_gives_up_after_max_restarts(self, tmp_path):
        child = FakeChild([1, 1, 1, 1])
        rc = supervise(["job"], base_dir=tmp_path, max_restarts=2,
                       run_child=child, sleep=lambda s: None)
        assert rc == EXIT_GAVE_UP
        assert child.attempts == ["0", "1", "2"]  # initial + 2 restarts

    def test_usage_errors_never_restart(self, tmp_path):
        child = FakeChild([2])
        assert supervise(["job"], base_dir=tmp_path, max_restarts=5,
                         run_child=child, sleep=lambda s: None) == 2
        assert child.attempts == ["0"]

    def test_preemption_restarts_without_backoff(self, tmp_path):
        child = FakeChild([EXIT_PREEMPTED, 0])
        sleeps = []
        rc = supervise(["job"], base_dir=tmp_path, max_restarts=2,
                       run_child=child, sleep=sleeps.append)
        assert rc == 0 and sleeps == []  # the capacity event is over

    def test_progressing_preemptions_dont_burn_budget(self, tmp_path,
                                                      monkeypatch):
        """N capacity events over a long preemptible run are normal
        life: a preemption whose doctor evidence shows forward progress
        must not count against --max-restarts."""
        steps = iter([10, 20, 30])
        monkeypatch.setattr(
            supervisor, "_consult_doctor",
            lambda b, prefer_diverged=False: {
                "verdict": "healthy", "last_step": next(steps),
                "run": "job_1gpus_1", "reason": "preempted"})
        child = FakeChild([EXIT_PREEMPTED] * 3 + [0])
        rc = supervise(["job"], base_dir=tmp_path, max_restarts=0,
                       run_child=child, sleep=lambda s: None)
        # max_restarts=0: only progress-free preemption restarts could
        # carry the run through all three capacity events
        assert rc == 0 and child.attempts == ["0", "1", "2", "3"]

    def test_diverged_quarantines_newest_checkpoint(self, tmp_path):
        newest = tmp_path / "checkpoints" / "llama_8dev" / "step_00000008"
        older = tmp_path / "checkpoints" / "llama_8dev" / "step_00000004"
        for d in (older, newest):
            d.mkdir(parents=True)
            (d / "data.bin").write_bytes(b"x")
        child = FakeChild([EXIT_HEALTH_ABORT, 0])
        rc = supervise(["job"], base_dir=tmp_path, max_restarts=1,
                       run_child=child, sleep=lambda s: None)
        assert rc == 0
        assert (newest.parent / "step_00000008.corrupt").is_dir()
        assert not newest.exists() and older.exists()


# ----------------------------------------------------- integration half

TRAIN_ARGS = [
    "--model", "llama", "--llama_size", "tiny", "--steps-per-epoch", "4",
    "--batch_size", "8", "--seq_len", "16", "--no-validate", "--seed", "0",
]


def run_cli(*args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONFAULTHANDLER="1")
    # hermetic children: a persistent compile cache shared across test
    # subprocesses is both unrealistic for these scenarios and broken on
    # this CPU backend (reloading a cached executable aborts)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "-m", "hyperion_tpu.cli.main", *args],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=str(Path(__file__).resolve().parents[1]),
    )


def epoch_losses(base_dir) -> dict[int, float]:
    """epoch -> loss across every attempt's CSV (a killed attempt never
    logs a partial row, so epochs appear exactly once per lineage)."""
    out: dict[int, float] = {}
    for p in sorted(Path(base_dir).glob("distributed/*_metrics.csv")):
        with p.open() as f:
            for row in csv.DictReader(f):
                out[int(row["epoch"])] = float(row["loss"])
    return out


@pytest.fixture(scope="module")
def uninterrupted(tmp_path_factory):
    """The control arm: 3 epochs x 4 steps, no faults."""
    base = tmp_path_factory.mktemp("plain")
    r = run_cli(*TRAIN_ARGS, "--epochs", "3", "--base_dir", str(base))
    assert r.returncode == 0, r.stderr[-2000:]
    return base


class TestChaosIntegration:
    def test_supervised_run_survives_two_kills(self, uninterrupted,
                                               tmp_path):
        """Acceptance: SIGKILL mid-epoch at global steps 6 and 10;
        --supervise resumes through both to the same final step count
        and losses as the uninterrupted run — no batch trained twice or
        skipped (the resumed epochs replay the same seeded permutation
        from the restored step)."""
        from hyperion_tpu import checkpoint as ckpt
        from hyperion_tpu.obs.doctor import diagnose

        base = tmp_path / "chaos"
        r = run_cli(*TRAIN_ARGS, "--epochs", "3", "--base_dir", str(base),
                    "--supervise", "--max-restarts", "3",
                    "--chaos", "kill@step=6,kill@step=10")
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        assert r.stdout.count("[chaos] firing kill") == 2
        assert "resumed from step 4" in r.stdout
        assert "resumed from step 8" in r.stdout

        plain_dir = str(uninterrupted / "checkpoints" / "llama_8dev")
        chaos_dir = str(base / "checkpoints" / "llama_8dev")
        assert ckpt.latest_step(chaos_dir) == ckpt.latest_step(plain_dir) == 12
        # per-epoch losses identical: every batch trained exactly once,
        # in order, on both arms
        plain, chaotic = epoch_losses(uninterrupted), epoch_losses(base)
        assert set(chaotic) == {1, 2, 3}
        for ep in (1, 2, 3):
            assert chaotic[ep] == pytest.approx(plain[ep], rel=1e-5), ep
        # the final exports are bit-comparable
        a = np.load(uninterrupted / "checkpoints" / "llama_fsdp_bf16_final.npz")
        b = np.load(base / "checkpoints" / "llama_fsdp_bf16_final.npz")
        for k in a.files:
            np.testing.assert_allclose(a[k], b[k], rtol=1e-6, atol=1e-7)
        # doctor reports the restart lineage across the stream
        d = diagnose(base)
        assert d["attempts"] == [0, 1, 2] and d["verdict"] == "healthy"

    def test_corrupt_latest_falls_back_to_prior_verified(self, tmp_path):
        """Acceptance: with checkpoints at steps 4 and 8, corrupt the
        latest; the next run quarantines it as step_X.corrupt (reason
        file included) and resumes from the prior verified step 4."""
        base = tmp_path / "corrupt"
        r1 = run_cli(*TRAIN_ARGS, "--epochs", "2", "--base_dir", str(base))
        assert r1.returncode == 0, r1.stderr[-2000:]
        job_dir = base / "checkpoints" / "llama_8dev"
        assert sorted(p.name for p in job_dir.iterdir()) == [
            "step_00000004", "step_00000008"]

        r2 = run_cli(*TRAIN_ARGS, "--epochs", "3", "--base_dir", str(base),
                     "--chaos", "corrupt_ckpt@latest")
        assert r2.returncode == 0, r2.stdout[-2000:] + r2.stderr[-2000:]
        assert "quarantined step_00000008" in r2.stdout
        assert "resumed from step 4" in r2.stdout
        corrupt = job_dir / "step_00000008.corrupt"
        assert corrupt.is_dir()
        assert "size mismatch" in (corrupt / "QUARANTINE_REASON.txt").read_text()
        from hyperion_tpu import checkpoint as ckpt

        assert ckpt.latest_step(job_dir) == 12  # retrained through the end

    def test_supervised_divergence_quarantines_then_resumes(self, tmp_path):
        """The doctor-guided arm: a NaN loss under --health-policy abort
        exits 4; the supervisor confirms 'diverged' with obs doctor,
        quarantines the newest checkpoint, and the restart resumes from
        the PRIOR verified step to a clean finish."""
        base = tmp_path / "nan"
        r = run_cli(*TRAIN_ARGS, "--epochs", "3", "--base_dir", str(base),
                    "--health-policy", "abort",
                    "--supervise", "--max-restarts", "2",
                    "--chaos", "nan_loss@step=10")
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        assert "doctor verdict: diverged" in r.stdout
        assert "quarantined step_00000008" in r.stdout
        assert "resumed from step 4" in r.stdout
        job_dir = base / "checkpoints" / "llama_8dev"
        assert (job_dir / "step_00000008.corrupt").is_dir()
        from hyperion_tpu import checkpoint as ckpt

        assert ckpt.latest_step(job_dir) == 12


class TestSuperviseFlagStripping:
    def test_child_argv_never_supervises(self):
        from hyperion_tpu.cli.main import _strip_supervise_flags

        argv = ["--model", "llama", "--supervise", "--max-restarts", "3",
                "--epochs", "2"]
        assert _strip_supervise_flags(argv) == [
            "--model", "llama", "--epochs", "2"]
        assert _strip_supervise_flags(["--max-restarts=3", "--supervise"]) == []

class TestCompileCache:
    """One rule (utils/compile_cache.py): an outside
    JAX_COMPILATION_CACHE_DIR is left alone — JAX reads it itself, and
    supervised children inherit it — else the cache is <repo>/.jax_cache."""

    @pytest.fixture()
    def cache_dir_restored(self):
        import jax

        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_outside_variable_wins_and_nothing_is_set_in_code(
            self, tmp_path, monkeypatch, cache_dir_restored):
        import jax

        from hyperion_tpu.utils.compile_cache import place_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "/sentinel")
        before = dict(os.environ)
        assert place_compile_cache() == str(tmp_path)
        # no jax.config.update happened, and the environment is as it was
        assert jax.config.jax_compilation_cache_dir == "/sentinel"
        assert dict(os.environ) == before

    def test_without_the_variable_the_cache_is_under_the_repo(
            self, monkeypatch, cache_dir_restored):
        import jax

        from hyperion_tpu.utils.compile_cache import (
            DEFAULT_DIR,
            place_compile_cache,
        )

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = dict(os.environ)
        assert place_compile_cache() == str(DEFAULT_DIR)
        assert DEFAULT_DIR == Path(__file__).resolve().parents[1] / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        assert dict(os.environ) == before   # in-process only

    def test_a_kernel_program_does_not_name_its_checkout(self, tmp_path):
        """A program that holds a Pallas kernel carries the kernel's
        module, source locations and all, inside its custom call, where
        the cache's key hashes it. After `place_compile_cache()` the
        text lowered for a TPU from two checkout directories is the
        same text; without it the directory is in there (PR 21's
        finding, which PR 27's tick would have paid in `setup_s`)."""
        import shutil
        import subprocess
        import sys

        repo = Path(__file__).resolve().parents[1]
        moved = tmp_path / "elsewhere"
        shutil.copytree(
            repo / "hyperion_tpu", moved / "hyperion_tpu",
            ignore=shutil.ignore_patterns("__pycache__", "_build", "*.so"))
        script = (
            "import hashlib, sys\n"
            "import jax, jax.numpy as jnp\n"
            "from hyperion_tpu.utils.compile_cache import "
            "place_compile_cache\n"
            "if sys.argv[1] == 'place': place_compile_cache()\n"
            "import hyperion_tpu.ops.pallas.paged_attention as pm\n"
            "pm._interpret = lambda: False\n"
            "S = jax.ShapeDtypeStruct\n"
            "pool = S((64, 8, 16, 128), jnp.bfloat16)\n"
            "low = jax.jit(pm.paged_attention).trace(\n"
            "    S((4, 1, 32, 128), jnp.bfloat16), pool, pool,\n"
            "    S((4, 8), jnp.int32), S((4,), jnp.int32)\n"
            ").lower(lowering_platforms=('tpu',))\n"
            "print(hashlib.sha256(low.as_text().encode()).hexdigest())\n")
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env.pop("JAX_COMPILATION_CACHE_DIR", None)

        def lowered(root, how):
            env["PYTHONPATH"] = str(root)
            return subprocess.run(
                [sys.executable, "-c", script, how], cwd=root, env=env,
                check=True, capture_output=True, text=True,
                timeout=120).stdout.split()[-1]

        assert lowered(repo, "place") == lowered(moved, "place")
        assert lowered(repo, "bare") != lowered(moved, "bare")

    @pytest.mark.parametrize("name", [
        "--compile-cache", "HYPERION_COMPILE_CACHE"])
    def test_the_old_names_are_gone(self, name):
        from hyperion_tpu.cli.main import build_parser
        from hyperion_tpu.config import OptimizationConfig

        assert not hasattr(OptimizationConfig(), "compile_cache")
        if name.startswith("--"):
            with pytest.raises(SystemExit):
                build_parser().parse_args([name, "/tmp/cc"])
        else:
            src = (Path(__file__).resolve().parents[1]
                   / "hyperion_tpu" / "cli" / "main.py").read_text()
            assert name not in src

    def test_cli_threads_overlap_knobs(self):
        from hyperion_tpu.cli.main import build_parser, make_config

        args = build_parser().parse_args([
            "--model", "llama", "--prefetch-depth", "4",
            "--no-async-checkpoint"])
        cfg = make_config(args, "llama")
        assert cfg.train.prefetch_depth == 4
        assert cfg.train.async_checkpoint is False
        # defaults: prefetch on at depth 2, async saves on
        dflt = make_config(build_parser().parse_args([]), "language_ddp")
        assert dflt.train.prefetch_depth == 2
        assert dflt.train.async_checkpoint is True


def test_exit_code_contract():
    """Watching scripts branch on these — they are API."""
    assert supervisor.EXIT_OK == 0
    assert supervisor.EXIT_USAGE == 2
    assert EXIT_GAVE_UP == 3
    assert EXIT_HEALTH_ABORT == 4
    assert EXIT_PREEMPTED == 75
