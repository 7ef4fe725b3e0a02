"""Baseline model benchmarks: fwd/bwd/opt decomposition + batch scaling — C17/C15.

Reference: `baseline_performance.ipynb cell 0:70-340` times forward,
forward+backward, and full train step separately (bwd = total − fwd,
opt = total − fwd − bwd), records peak memory and samples/s per model
(ResNet-50, ViT-B/16, CustomTransformer), and sweeps batch sizes until
OOM. `Phase 1/benchmarking.py` packages the same timers as a library.
MI250X numbers in BASELINE.md (ResNet-50 bs32: 56.32 ms, 568 samples/s).

JAX-native decomposition: three separately-jitted programs —
  fwd            logits only
  fwd+bwd        loss + grads
  fwd+bwd+opt    full optimizer step
Each timed as a chain of data-dependent iterations inside one jit with
per-iteration time from the slope of two chain lengths
(`utils.timing.time_chained`) — honest under the lazy-fence backend
round 2 exposed, with fixed dispatch overhead excluded. XLA fuses each
program globally, so "bwd time" = t(fwd+bwd) − t(fwd) measures the
*marginal* cost exactly as the reference's subtraction did.

CLI: `python -m hyperion_tpu.bench.baseline [--models ...] [--batch-sizes ...]`.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from hyperion_tpu.bench.util import write_csv as _write_csv
from hyperion_tpu.models.encoder import TransformerEncoder, custom_transformer_config
from hyperion_tpu.models.resnet import resnet50
from hyperion_tpu.models.vit import ViT, vit_b16_config
from hyperion_tpu.utils.memory import peak_bytes_in_use
from hyperion_tpu.utils.timing import time_chained


def _resnet50_spec(batch: int, dtype: str):
    model = resnet50(num_classes=1000, dtype=dtype)
    variables = model.init_variables(jax.random.key(0), image_size=224)
    x = jnp.zeros((batch, 224, 224, 3), jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)

    def apply(params, batch_stats, x):
        return model.apply(
            {"params": params, "batch_stats": batch_stats}, x,
            train=True, mutable=["batch_stats"],
        )[0]

    return variables, apply, (x, y)


def _vit_spec(batch: int, dtype: str):
    model = ViT(vit_b16_config(dtype=dtype))
    variables = {"params": model.init_params(jax.random.key(0))}
    x = jnp.zeros((batch, 224, 224, 3), jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)

    def apply(params, batch_stats, x):
        return model.apply({"params": params}, x, deterministic=True)

    return variables, apply, (x, y)


class _RefFallbackCNN(nn.Module):
    """The reference's ACTUAL "ViT" benchmark subject.

    `baseline_performance.ipynb cell 0:35-54`: on the reference's
    torchvision build, `create_vit_model` falls back to a ~100K-param
    Sequential CNN (conv7x7/2 -> maxpool -> conv3x3 -> maxpool -> GAP
    -> linear 128->1000), and the committed `model_benchmarks.csv` row
    2 (5.44 ms / 515 MB / 5883 samples/s at bs 32) is consistent with
    that CNN, not with an 86M-param ViT-B/16 (which could not train
    ~10x faster than the same GPU's ResNet-50). Benchmarked here
    verbatim so the comparison table has an apples-to-apples row; the
    real ViT-B/16 row stands on its own with no true reference
    counterpart.
    """

    dtype: str = "bfloat16"

    @nn.compact
    def __call__(self, x):
        dt = jnp.dtype(self.dtype)
        x = x.astype(dt)
        x = nn.relu(nn.Conv(64, (7, 7), strides=2, padding=3, dtype=dt)(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        x = nn.relu(nn.Conv(128, (3, 3), padding=1, dtype=dt)(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        x = jnp.mean(x, axis=(1, 2))  # AdaptiveAvgPool2d((1,1)) + Flatten
        return nn.Dense(1000, dtype=dt)(x).astype(jnp.float32)


def _vit_fallback_cnn_spec(batch: int, dtype: str):
    model = _RefFallbackCNN(dtype=dtype)
    x = jnp.zeros((batch, 224, 224, 3), jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)
    variables = {"params": model.init({"params": jax.random.key(0)}, x)["params"]}

    def apply(params, batch_stats, x):
        return model.apply({"params": params}, x)

    return variables, apply, (x, y)


def _custom_transformer_spec(batch: int, dtype: str, seq: int = 16):
    model = TransformerEncoder(custom_transformer_config(dropout=0.0, dtype=dtype))
    variables = {"params": model.init_params(jax.random.key(0), seq=seq)}
    x = jnp.zeros((batch, seq, 512), jnp.float32)
    y = jnp.zeros((batch, seq, 512), jnp.float32)  # MSE target, as in the reference

    def apply(params, batch_stats, x):
        return model.apply({"params": params}, x)

    return variables, apply, (x, y)


MODEL_SPECS: dict[str, Callable] = {
    "resnet50": _resnet50_spec,
    "vit_b16": _vit_spec,
    "vit_fallback_cnn": _vit_fallback_cnn_spec,
    "custom_transformer": _custom_transformer_spec,
}


def benchmark_model(
    name: str, batch: int, dtype: str = "bfloat16",
    iters: int = 20, warmup: int = 5, static_memory: bool = True,
) -> dict:
    """One row of the reference's `model_benchmarks.csv`."""
    variables, apply, (x, y) = MODEL_SPECS[name](batch, dtype)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    def loss_fn(params, batch_stats, x, y):
        out = apply(params, batch_stats, x)
        if out.ndim == 2 and y.ndim == 1:  # classification
            return optax.softmax_cross_entropy_with_integer_labels(
                out.astype(jnp.float32), y).mean()
        return jnp.mean((out - y) ** 2)  # reference uses MSE for the encoder

    def fwd(p, bs, x, y):
        return loss_fn(p, bs, x, y)  # scalar output -> probe is free

    def fwd_bwd(p, bs, x, y):
        # thread params through an epsilon-update so each iteration's
        # backward depends on the previous one WITHOUT a per-iteration
        # probe reduction (which would skew the bwd-minus-fwd
        # subtraction); 1e-30*g is numerically a no-op but the compiler
        # cannot elide it
        g = jax.grad(loss_fn)(p, bs, x, y)
        return jax.tree_util.tree_map(
            lambda a, b: a - jnp.asarray(1e-30, a.dtype) * b.astype(a.dtype),
            p, g,
        )

    def full_step(p, opt_state, bs, x, y):
        grads = jax.grad(loss_fn)(p, bs, x, y)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state

    del warmup  # chains warm themselves; kept for CLI compat
    k2 = max(6, min(iters, 16))
    k1 = max(2, k2 // 3)
    # every chain threads real state -> no probe rides in any timed
    # region, so the subtraction decomposition stays comparable
    t_fwd = time_chained(fwd, params, batch_stats, x, y, k1=k1, k2=k2)
    t_bwd = time_chained(fwd_bwd, params, batch_stats, x, y,
                         k1=k1, k2=k2, n_thread=1)
    t_full = time_chained(full_step, params, opt_state, batch_stats, x, y,
                          k1=k1, k2=k2, n_thread=2)

    # decomposition by subtraction, clamped at 0 (fusion can make a
    # superset program faster than the sum of its parts)
    fwd_ms = t_fwd.per_iter_ms
    bwd_ms = max(t_bwd.per_iter_ms - fwd_ms, 0.0)
    opt_ms = max(t_full.per_iter_ms - t_bwd.per_iter_ms, 0.0)

    peak = peak_bytes_in_use()
    mem_source = "allocator_peak"
    if peak == 0 and not static_memory:
        mem_source = "unavailable"
    elif peak == 0:
        # the CPU test backend has no allocator counters (a TPU
        # without them raises in utils/memory.py): fall back to XLA's
        # static analysis of the full-step program —
        # live bytes = arguments (params/opt state/batch) + temps +
        # un-aliased outputs, the same quantity the reference's
        # max_memory_allocated approximates per step
        try:
            ma = (
                jax.jit(full_step)
                .lower(params, opt_state, batch_stats, x, y)
                .compile()
                .memory_analysis()
            )
            peak = int(
                ma.argument_size_in_bytes
                + ma.temp_size_in_bytes
                + ma.output_size_in_bytes
            )
            mem_source = "xla_static"
        except Exception:  # noqa: BLE001 — analysis unavailable
            mem_source = "unavailable"
    return {
        "model": name,
        "batch_size": batch,
        "dtype": dtype,
        "forward_ms": round(fwd_ms, 3),
        "backward_ms": round(bwd_ms, 3),
        "optimizer_ms": round(opt_ms, 3),
        "total_ms": round(t_full.per_iter_ms, 3),
        "peak_memory_mb": round(peak / 1e6, 2),
        "memory_source": mem_source,
        "samples_per_s": round(t_full.throughput(batch), 2),
        "dispatch_overhead_ms": round(t_full.overhead_ms, 2),
    }


def batch_size_scaling(
    name: str, batch_sizes=(1, 2, 4, 8, 16, 32, 64), dtype: str = "bfloat16",
    iters: int = 10, sink=None,
) -> list[dict]:
    """Reference `test_batch_size_scaling`: sweep until OOM, break
    gracefully (baseline_performance.ipynb cell 0:295-340)."""
    rows = []
    for bs in batch_sizes:
        try:
            # static_memory=False: the fallback memory analysis costs a
            # fresh full-step compile per row, and the scaling
            # comparison only consumes samples/s
            rows.append(benchmark_model(name, bs, dtype, iters=iters, warmup=3,
                                        static_memory=False))
        except Exception as e:  # noqa: BLE001 — XLA OOM ends the sweep
            msg = str(e).splitlines()[0][:120]
            print(f"[baseline] {name} bs={bs}: stopping sweep ({msg})")
            break
        if sink is not None:
            sink(rows)
    return rows


def precision_comparison(
    name: str, batch: int = 32, dtypes=("float32", "bfloat16"), iters: int = 10
) -> list[dict]:
    """C15's `compare_precision_formats`."""
    return [benchmark_model(name, batch, dt, iters=iters) for dt in dtypes]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--models", nargs="*", default=list(MODEL_SPECS))
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--scaling", action="store_true",
                   help="also run the batch-size scaling sweep")
    p.add_argument("--precisions", nargs="*", default=None,
                   help="also sweep these dtypes per model (C15's "
                        "compare_precision_formats), e.g. float32 bfloat16")
    p.add_argument("--batch-sizes", type=int, nargs="*",
                   default=[1, 2, 4, 8, 16, 32, 64])
    p.add_argument("--out", default="results/benchmarks/baseline")
    return p


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)

    from hyperion_tpu.metrics.plots import (
        plot_baseline_models, plot_batch_scaling, try_plot,
    )

    out = Path(args.out)
    rows = []
    for name in args.models:
        r = benchmark_model(name, args.batch_size, args.dtype, iters=args.iters)
        rows.append(r)
        # flush per model: measured rows must already be on disk if
        # the run is cut at its time limit
        _write_csv(out / "model_benchmarks.csv", rows)
        print(f"[baseline] {json.dumps(r)}")
    try_plot(plot_baseline_models, rows, out / "model_benchmarks.png")

    if args.precisions:
        by_model = {r["model"]: r for r in rows}
        prec_rows = []
        for name in args.models:
            for dt in args.precisions:
                if dt == args.dtype and name in by_model:
                    prec_rows.append(by_model[name])  # already measured
                else:
                    try:
                        prec_rows.append(
                            benchmark_model(name, args.batch_size, dt,
                                            iters=args.iters)
                        )
                    except Exception as e:  # noqa: BLE001 — one OOM must
                        # not kill the rest of the capture (fp32 doubles
                        # memory)
                        print(f"[baseline] precision {name}/{dt} failed: "
                              f"{str(e).splitlines()[0][:120]}")
                        continue
                # flush after EVERY append (reuse rows included): the
                # next measurement may be the one SIGTERM lands on
                _write_csv(out / "precision_comparison.csv", prec_rows)
        for r in prec_rows:
            print(f"[baseline] precision {json.dumps(r)}")

    if args.scaling:
        sweeps = {}
        for name in args.models:
            sweep = batch_size_scaling(
                name, args.batch_sizes, args.dtype,
                sink=lambda rows, p=out / f"{name}_batch_scaling.csv":
                    _write_csv(p, rows),
            )
            sweeps[name] = sweep
            for r in sweep:
                print(f"[baseline] scaling {json.dumps(r)}")
        try_plot(plot_batch_scaling,
                 {k: v for k, v in sweeps.items() if v},
                 out / "batch_scaling.png")
    print(f"[baseline] results in {out}/")


if __name__ == "__main__":
    main()
