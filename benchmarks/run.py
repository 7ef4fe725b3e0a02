#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one run of one cell of BENCHMARK.json. Fails (traceback,
non-zero exit, no result line) where JAX finds no TPU or fewer chips
than the cell asks for. The last line of its standard output is the
result; earlier lines are `{"info": ...}` for the reader of a log.
`--rehearse` is for the tests: the tiny sizes of the data files'
`rehearse` blocks, any backend, the platform reported truthfully."""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) \
            if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    from benchmarks import spec

    cell = spec.cell(args.workload)
    if args.rehearse:
        cell = _merge(cell, cell.pop("rehearse"))
        cell["model"] = _merge(cell["model"], cell["model"].pop("rehearse"))

    import jax

    from hyperion_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    # every program is kept, however quickly it compiled: a run after
    # the first finds all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse:
        if dev.platform != "tpu":
            raise SystemExit(f"no TPU: JAX found {dev.platform!r}")
        if len(devices) < cell["chips"]:
            raise SystemExit(f"{args.workload} asks for {cell['chips']} "
                             f"chips, JAX found {len(devices)}")
        peaks = spec.peaks(dev.device_kind)
    else:
        print("REHEARSAL: tiny sizes, not a measurement", flush=True)
        peaks = None

    def say(**info) -> None:
        print(json.dumps({"info": info}, default=float), flush=True)

    say(workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, compile_cache=cache_dir,
        import_s=time.monotonic() - T_START)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        out = spec.plugin("adapters", cell["adapter"]).run(
            cell, args.seed, args.seconds, bool(args.trace), trace_dir,
            T_START, say)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out["peak_bytes"]}
    # `extra`: what an adapter sets beside the contract's keys, such as
    # the live share of the memory the peak counts
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], **out.get("extra", {})}
    if args.trace:
        ctx = {**out["ctx"], "peaks": peaks}
        values = {}
        for metric in cell["per_layer"]:
            read = spec.plugin("readers", metric["reader"]).read
            values[metric["name"]] = (
                read(ctx, **metric.get("args", {})), metric["unit"])
        red = ctx.get("trace")
        if red:
            device["busy_s"], device["window_s"] = \
                red["busy_s"], red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    else:
        values = {m["name"]: (out["measured"][m["name"]], m["unit"])
                  for m in cell["end_to_end"]}
    # a reader that found nothing to read returns nothing, and the
    # metric is left out of the line
    result["metrics"] = {k: {"value": v, "unit": u}
                         for k, (v, u) in values.items() if v is not None}
    result["device"] = device
    say(total_s=time.monotonic() - T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
