#!/usr/bin/env bash
# The exploration command lines on your chip: hardware sweep, model
# baselines, compile tiers, decode throughput. The benchmark itself is
# BENCHMARK.json + `python3 benchmarks/run.py --workload <cell> --seed
# <n> --seconds 30 --trace <0|1>` (benchmarks/README.md).
#
#   examples/benchmark_chip.sh [outdir]
#
# Every suite uses chained data-dependent iterations fenced by a host
# fetch (utils/timing.py) — a lazy backend yields a rejected
# measurement, never a fake number. Compare against the MI250X
# reference rows with scripts/compare_to_reference.py.
set -euo pipefail
cd "$(dirname "$0")/.."
OUT="${1:-results/benchmarks_local}"

python -m hyperion_tpu.bench.hw_explore --out "$OUT/hardware"
python -m hyperion_tpu.bench.baseline --scaling \
  --precisions float32 bfloat16 --out "$OUT/baseline"
python -m hyperion_tpu.bench.compile_bench --train-step --out "$OUT/compilation"
python -m hyperion_tpu.bench.decode_bench --out "$OUT/decode"

python scripts/compare_to_reference.py --root "$OUT"
