"""bench.py parent logic tests (no subprocesses, no backend).

The parent runs the measurement children in sequence and has exactly
two outcomes: one JSON result line and exit 0, or a reason on stderr,
no result line and a non-zero exit. These tests replace the child
runner, so each scenario runs in microseconds.
"""

from __future__ import annotations

import ast
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture()
def bench():
    """Fresh bench module (repo-root bench.py is not a package member)."""
    spec = importlib.util.spec_from_file_location("bench_mod", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


GOOD_MEASUREMENT = {
    "tflops": 150.0, "per_iter_ms": 7.0, "amortized_ms": 7.0,
    "dispatch_overhead_ms": 0.5, "chain_lengths": [16, 48],
    "peak_tflops": 197.0, "mfu": 0.76, "scaling_ratio_vs_half_n": 7.9,
    "plausible": True, "checks": {}, "platform": "tpu",
    "device_kind": "TPU v5 lite",
}


def install_runner(bench, monkeypatch, overrides=None):
    """Every child answers {"row": <mode>} unless `overrides` (mode ->
    dict to return, or an exception to raise) says otherwise."""
    calls = []
    overrides = overrides or {}

    def _run(mode, timeout_s, env=None):
        calls.append((mode, timeout_s, env))
        got = overrides.get(mode, {"row": mode})
        if mode == "--child-matmul" and mode not in overrides:
            got = GOOD_MEASUREMENT
        if isinstance(got, Exception):
            raise got
        return got

    monkeypatch.setattr(bench, "_run_child", _run)
    return calls


class TestBenchParent:
    def test_all_children_ok_prints_one_line_and_exits_zero(
            self, bench, monkeypatch, capsys):
        calls = install_runner(bench, monkeypatch)
        assert bench.main() == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["value"] == 150.0 and out["platform"] == "tpu"
        assert out["vs_baseline"] == round(150.0 / 121.07, 3)
        assert out["measurement"] == GOOD_MEASUREMENT
        # strictly in sequence, chip children first, every row present
        assert [c[0] for c in calls] == [m for _, m, _, _ in bench.CHILDREN]
        for row, mode, _, _ in bench.CHILDREN[1:]:
            assert out[row] == {"row": mode}

    def test_chip_children_inherit_env_host_children_get_cpu(
            self, bench, monkeypatch):
        calls = install_runner(bench, monkeypatch)
        bench.main()
        want = {m: on_chip for _, m, _, on_chip in bench.CHILDREN}
        for mode, _, env in calls:
            assert env == (None if want[mode] else {"JAX_PLATFORMS": "cpu"})

    @pytest.mark.parametrize("mode", [
        "--child-matmul", "--child-lm-step", "--child-serving-scale"])
    def test_failed_child_exits_nonzero_without_a_line(
            self, bench, monkeypatch, capsys, mode):
        calls = install_runner(bench, monkeypatch, {
            mode: bench.ChildFailed(f"{mode} timed out after 5s")})
        assert bench.main() == 1
        cap = capsys.readouterr()
        assert cap.out.strip() == ""          # no result line at all
        assert "timed out" in cap.err
        assert calls[-1][0] == mode           # nothing runs after a failure

    def test_device_that_is_not_a_tpu_is_a_failure(
            self, bench, monkeypatch, capsys):
        calls = install_runner(bench, monkeypatch, {
            "--child-matmul": {**GOOD_MEASUREMENT, "platform": "cpu",
                               "device_kind": "cpu"}})
        assert bench.main() == 1
        cap = capsys.readouterr()
        assert cap.out.strip() == "" and "not a TPU" in cap.err
        assert len(calls) == 1                # no substitute measurement

    def test_implausible_reading_is_a_failure(
            self, bench, monkeypatch, capsys):
        install_runner(bench, monkeypatch, {
            "--child-matmul": {**GOOD_MEASUREMENT, "tflops": 41998.0,
                               "plausible": False,
                               "checks": {"under_peak": False}}})
        assert bench.main() == 1
        cap = capsys.readouterr()
        assert cap.out.strip() == "" and "41998" in cap.err

    def test_failure_reaches_the_event_stream(
            self, bench, monkeypatch, tmp_path):
        tele = tmp_path / "t.jsonl"
        monkeypatch.setenv("HYPERION_TELEMETRY", str(tele))
        install_runner(bench, monkeypatch, {
            "--child-lm-step": bench.ChildFailed("boom")})
        assert bench.main() == 1
        events = [json.loads(x) for x in tele.read_text().splitlines()]
        publish = [e for e in events if e.get("name") == "publish"]
        assert publish and publish[-1]["failed"] is True
        assert publish[-1]["error"] == "boom"


class TestRunChild:
    def test_env_is_inherited_not_overridden(self, bench, monkeypatch):
        """An outside JAX_COMPILATION_CACHE_DIR reaches the child as it
        is; the parent adds nothing but what the caller asked for."""
        seen = {}

        def fake_run(cmd, **kw):
            seen.update(kw["env"])
            return subprocess.CompletedProcess(cmd, 0, '{"a": 1}\n', "")

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/outside/cache")
        monkeypatch.setattr(bench.subprocess, "run", fake_run)
        assert bench._run_child("--child-matmul", 5) == {"a": 1}
        assert seen["JAX_COMPILATION_CACHE_DIR"] == "/outside/cache"
        bench._run_child("--child-serving", 5, env={"JAX_PLATFORMS": "cpu"})
        assert seen["JAX_PLATFORMS"] == "cpu"
        assert seen["JAX_COMPILATION_CACHE_DIR"] == "/outside/cache"

    @pytest.mark.parametrize("proc,why", [
        (subprocess.CompletedProcess([], 3, "", "Traceback\nboom"), "rc=3"),
        (subprocess.CompletedProcess([], 0, "no json here\n", ""), "no JSON"),
    ])
    def test_bad_child_raises(self, bench, monkeypatch, proc, why):
        monkeypatch.setattr(bench.subprocess, "run", lambda *a, **k: proc)
        with pytest.raises(bench.ChildFailed, match=why):
            bench._run_child("--child-matmul", 5)

    def test_timeout_raises(self, bench, monkeypatch):
        def fake_run(cmd, **kw):
            raise subprocess.TimeoutExpired(cmd, kw["timeout"])

        monkeypatch.setattr(bench.subprocess, "run", fake_run)
        with pytest.raises(bench.ChildFailed, match="timed out after 5s"):
            bench._run_child("--child-matmul", 5)


class TestParentStaysOffJax:
    def test_top_level_imports_are_jax_free(self):
        tree = ast.parse((REPO / "bench.py").read_text())
        top = set()
        for node in tree.body:
            if isinstance(node, ast.Import):
                top |= {a.name.split(".")[0] for a in node.names}
            elif isinstance(node, ast.ImportFrom):
                top.add((node.module or "").split(".")[0])
        assert top <= {"__future__", "json", "os", "subprocess", "sys"}

    def test_parent_run_never_imports_jax(self, tmp_path):
        """The whole parent path in a real interpreter: children stubbed
        at the subprocess boundary, then `jax` must not be in
        sys.modules — a parent that had touched it would hold the chip
        its children need."""
        code = f"""
import importlib.util, json, subprocess, sys
spec = importlib.util.spec_from_file_location("b", {str(REPO / 'bench.py')!r})
b = importlib.util.module_from_spec(spec); spec.loader.exec_module(b)
good = {GOOD_MEASUREMENT!r}
b.subprocess.run = lambda cmd, **kw: subprocess.CompletedProcess(
    cmd, 0, json.dumps(good) + "\\n", "")
rc = b.main()
assert rc == 0, rc
assert "jax" not in sys.modules, "the bench parent imported jax"
"""
        p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                           capture_output=True, text=True, timeout=120,
                           env={"PATH": "/usr/bin:/bin",
                                "PYTHONPATH": str(REPO)})
        assert p.returncode == 0, p.stderr
