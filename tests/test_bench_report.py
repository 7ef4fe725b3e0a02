"""Scaling-report + bench-suite plumbing tests (pure/fast paths).

The reference's report pipeline was only ever validated by running it on
a 4-GPU box (SURVEY §4); here the parsing, warmup-discard, and
speedup/efficiency math get golden tests on synthetic CSVs.
"""

import csv
from pathlib import Path

import pytest

from hyperion_tpu.bench.compile_bench import summarize
from hyperion_tpu.metrics.csv_logger import run_id
from hyperion_tpu.metrics.scaling_report import (
    create_scaling_report,
    parse_run_name,
)


def write_metrics(dir: Path, job: str, n: int, durations, ts="20260729_120000"):
    dir.mkdir(parents=True, exist_ok=True)
    path = dir / f"{job}_{n}gpus_{ts}_metrics.csv"
    with path.open("w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["epoch", "loss", "duration_s", "gpus"])
        for i, d in enumerate(durations):
            w.writerow([i + 1, 5.0, d, n])
    return path


class TestParseRunName:
    def test_roundtrip_with_logger_format(self):
        rid = run_id("language_ddp", 4)
        assert parse_run_name(f"{rid}_metrics.csv") == ("language_ddp", 4)

    def test_job_names_with_underscores(self):
        assert parse_run_name("cifar_ddp_8gpus_20260101_000000_metrics.csv") == \
            ("cifar_ddp", 8)

    def test_rejects_foreign_files(self):
        assert parse_run_name("scaling_analysis.csv") is None


class TestScalingReport:
    def test_speedup_and_efficiency(self, tmp_path):
        # 3 epochs; first third (1 epoch) discarded as warmup
        write_metrics(tmp_path, "language_ddp", 1, [100.0, 12.0, 12.0])
        write_metrics(tmp_path, "language_ddp", 4, [50.0, 4.0, 4.0])
        rows = create_scaling_report(tmp_path)
        by_n = {r["gpus"]: r for r in rows}
        assert by_n[1]["epoch_time_s"] == 12.0  # warmup epoch dropped
        assert by_n[4]["speedup"] == 3.0
        assert by_n[4]["efficiency_pct"] == 75.0
        assert (tmp_path / "scaling_analysis.csv").exists()

    def test_multiple_runs_same_count_average(self, tmp_path):
        write_metrics(tmp_path, "cifar_ddp", 1, [10.0, 10.0],
                      ts="20260729_110000")
        write_metrics(tmp_path, "cifar_ddp", 1, [20.0, 20.0],
                      ts="20260729_120000")
        rows = create_scaling_report(tmp_path)
        assert rows[0]["epoch_time_s"] == 15.0

    def test_no_baseline_reports_absolute_only(self, tmp_path):
        write_metrics(tmp_path, "llama", 4, [30.0, 30.0])
        rows = create_scaling_report(tmp_path)
        assert rows[0]["speedup"] == ""

    def test_empty_dir_is_empty_not_fabricated(self, tmp_path):
        # the reference fabricates sample data here; we must not
        assert create_scaling_report(tmp_path) == []
        content = (tmp_path / "scaling_analysis.csv").read_text()
        assert content.strip().splitlines()[1:] == []


class TestCompileBenchSummary:
    def test_speedups_vs_jit(self):
        rows = [
            {"model": "m", "variant": "op_by_op", "median_ms": 100.0, "note": ""},
            {"model": "m", "variant": "jit", "median_ms": 10.0, "note": ""},
            {"model": "m", "variant": "jit_pallas", "median_ms": 5.0, "note": ""},
        ]
        text = summarize(rows)
        assert "0.10x" in text
        assert "2.00x" in text

    def test_failed_variant(self):
        rows = [
            {"model": "m", "variant": "jit", "median_ms": 10.0, "note": ""},
            {"model": "m", "variant": "jit_pallas", "median_ms": float("nan"),
             "note": "failed: x"},
            {"model": "m", "variant": "op_by_op", "median_ms": 20.0, "note": ""},
        ]
        assert "failed" in summarize(rows)


class TestCliParser:
    def test_defaults_per_job(self):
        from hyperion_tpu.cli.main import build_parser, make_config

        args = build_parser().parse_args(["--model", "cifar"])
        cfg = make_config(args, "cifar")
        assert cfg.train.batch_size == 64
        assert cfg.train.learning_rate == 1e-3

    def test_fsdp_jobs_get_fsdp_mesh_and_clip(self):
        from hyperion_tpu.cli.main import build_parser, make_config

        args = build_parser().parse_args(["--model", "language_fsdp"])
        cfg = make_config(args, "language_fsdp")
        assert cfg.distributed.fsdp == -1
        assert cfg.optimization.grad_clip_norm == 1.0

    def test_mesh_override(self):
        from hyperion_tpu.cli.main import build_parser, make_config

        args = build_parser().parse_args(
            ["--model", "language_ddp", "--mesh", "2,2,2,1"])
        cfg = make_config(args, "language_ddp")
        assert (cfg.distributed.data, cfg.distributed.fsdp,
                cfg.distributed.model, cfg.distributed.seq) == (2, 2, 2, 1)


class TestDecodeBench:
    @pytest.mark.slow
    def test_tiny_decode_row(self, tmp_path):
        from hyperion_tpu.bench.decode_bench import benchmark_decode

        row = benchmark_decode("tiny", batch=2, prompt_len=16, decode_len=8)
        assert row["decode_tokens_per_s"] > 0
        assert row["prefill_ms"] > 0
        assert row["params_m"] > 0


class TestCompareToReference:
    """The round-end comparison tool (scripts/compare_to_reference.py)
    must render whatever subset of capture artifacts exists."""

    def _run(self, tmp_path, capsys):
        import importlib.util
        import sys

        spec = importlib.util.spec_from_file_location(
            "compare_to_reference",
            Path(__file__).parent.parent / "scripts" / "compare_to_reference.py",
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        argv = sys.argv
        sys.argv = ["x", "--root", str(tmp_path / "benchmarks"),
                    "--runs", str(tmp_path / "runs")]
        try:
            mod.main()
        finally:
            sys.argv = argv
        return capsys.readouterr().out

    def test_empty_capture_renders_placeholders(self, tmp_path, capsys):
        out = self._run(tmp_path, capsys)
        assert "not captured yet" in out
        assert "## Model baselines" in out

    def test_populated_tables(self, tmp_path, capsys):
        bdir = tmp_path / "benchmarks" / "baseline"
        bdir.mkdir(parents=True)
        with (bdir / "model_benchmarks.csv").open("w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=[
                "model", "batch_size", "dtype", "total_ms", "samples_per_s"])
            w.writeheader()
            w.writerow({"model": "resnet50", "batch_size": 32,
                        "dtype": "bfloat16", "total_ms": 28.0,
                        "samples_per_s": 1142.9})
        (tmp_path / "benchmarks" / "bench_live.json").write_text(
            '{"value": 175.75, "unit": "TFLOPS", "vs_baseline": 1.452}\n')
        out = self._run(tmp_path, capsys)
        assert "175.75" in out
        assert "resnet50" in out and "2.01x" in out  # 1142.9/568.22


class TestAttentionBench:
    """Long-seq attention scaling bench (`bench.attention_bench`):
    row shape, CSV union-fieldnames, and error rows must not kill the
    sweep (an OOM row is the finding, not a crash)."""

    def test_ok_row_and_csv(self, tmp_path, capsys):
        from hyperion_tpu.bench import attention_bench

        attention_bench.main([
            "--seqs", "128", "--impls", "xla", "--modes", "fwd",
            "--geometries", "gpt2",
            "--dtype", "float32", "--out", str(tmp_path)])
        rows = list(csv.DictReader(
            (tmp_path / "attention_scaling.csv").open()))
        assert len(rows) == 1 and rows[0]["status"] == "ok"
        assert rows[0]["geometry"] == "gpt2"
        assert float(rows[0]["per_iter_ms"]) > 0
        assert float(rows[0]["achieved_tflops"]) > 0

    def test_error_row_records_note(self, tmp_path):
        from hyperion_tpu.bench.attention_bench import benchmark_attention
        from hyperion_tpu.bench.util import write_csv as _write_csv

        ok = benchmark_attention(128, "xla", "fwd", "float32")
        bad = benchmark_attention(128, "definitely-not-an-impl", "fwd")
        assert bad["status"] == "error" and "impl" in bad["note"]
        # union fieldnames: ok row lacks "note", error row adds it
        _write_csv(tmp_path / "mixed.csv", [ok, bad])
        rows = list(csv.DictReader((tmp_path / "mixed.csv").open()))
        assert rows[0]["note"] == "" and rows[1]["status"] == "error"

    def test_attention_table_renders(self, tmp_path, capsys):
        # uses the report-runner helper from TestCompareToReference (the
        # table lives in the same compare_to_reference.py report)
        adir = tmp_path / "benchmarks" / "attention"
        adir.mkdir(parents=True)
        with (adir / "attention_scaling.csv").open("w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=[
                "seq", "impl", "mode", "status", "per_iter_ms",
                "temp_memory_gb"])
            w.writeheader()
            w.writerow({"seq": 8192, "impl": "xla", "mode": "train",
                        "status": "oom", "per_iter_ms": "nan",
                        "temp_memory_gb": "nan"})
            w.writerow({"seq": 8192, "impl": "pallas", "mode": "train",
                        "status": "ok", "per_iter_ms": 12.5,
                        "temp_memory_gb": 0.21})
        out = TestCompareToReference()._run(tmp_path, capsys)
        assert "Long-seq attention" in out
        assert "oom" in out and "12.5" in out  # xla OOM row renders as such
        assert "nanx" not in out  # no speedup computed from a nan row


class TestTier1DurationGuard:
    """scripts/check_tier1_duration.py — the tier-1 wall-time budget
    (a suite one slow test away from the 900s timeout is already a
    regression; the guard fails it at 880s with headroom to spare)."""

    def _guard(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "check_tier1_duration",
            Path(__file__).parent.parent / "scripts"
            / "check_tier1_duration.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_under_budget_passes(self, tmp_path):
        mod = self._guard()
        log = tmp_path / "t1.log"
        log.write_text("...\n== 1014 passed, 3 skipped in 782.41s "
                       "(0:13:02) ==\n")
        assert mod.main([str(log)]) == 0

    def test_over_budget_fails(self, tmp_path):
        mod = self._guard()
        log = tmp_path / "t1.log"
        log.write_text("== 1014 passed in 891.02s (0:14:51) ==\n")
        assert mod.main([str(log)]) == 1
        # and a custom budget is respected
        assert mod.main([str(log), "920"]) == 0

    def test_missing_summary_is_a_failure(self, tmp_path):
        # a log with no summary line means pytest never finished —
        # exactly the timeout scenario the guard exists to preempt
        mod = self._guard()
        log = tmp_path / "t1.log"
        log.write_text("tests/test_serve.py ......\n")
        assert mod.main([str(log)]) == 1
        assert mod.main([str(tmp_path / "missing.log")]) == 1

    def test_elapsed_fallback_when_quiet_log_has_no_summary(self, tmp_path):
        # the real tier-1 command runs at -qq (pyproject -q + command
        # -q), which suppresses the summary line entirely: the guard
        # must then judge the shell-measured elapsed time instead
        mod = self._guard()
        log = tmp_path / "t1.log"
        log.write_text(".......... [100%]\n")
        assert mod.main([str(log), "--elapsed", "790"]) == 0
        assert mod.main([str(log), "--elapsed", "893"]) == 1
        # a parsed summary line wins over the measurement (the shell
        # clock includes collection + teardown slop)
        log.write_text("== 1014 passed in 700.00s (0:11:40) ==\n")
        assert mod.main([str(log), "--elapsed", "9999"]) == 0

    def test_top_durations_sums_phases_per_test(self, tmp_path, capsys):
        # the --durations table charges setup/call/teardown separately;
        # the guard's share line must charge a slow fixture to the test
        # that paid for it, then rank
        mod = self._guard()
        table = (
            "============ slowest 15 durations ============\n"
            "30.00s call     tests/test_router.py::test_drill\n"
            "12.00s setup    tests/test_router.py::test_drill\n"
            "25.00s call     tests/test_serve.py::test_smoke\n"
            "20.00s call     tests/test_scaling.py::test_scale\n"
            "1.50s teardown  tests/test_serve.py::test_smoke\n"
            "9.00s call     tests/test_obs.py::test_minor\n"
        )
        top = mod.top_durations(table)
        assert top == [
            (42.0, "tests/test_router.py::test_drill"),
            (26.5, "tests/test_serve.py::test_smoke"),
            (20.0, "tests/test_scaling.py::test_scale"),
        ]
        # and main() narrates the share on every run, not just failures
        log = tmp_path / "t1.log"
        log.write_text(table + "== 100 passed in 200.00s ==\n")
        assert mod.main([str(log)]) == 0
        out = capsys.readouterr().out
        assert "top-3 tests carry 44% of the suite" in out
        assert "test_drill 42s" in out
