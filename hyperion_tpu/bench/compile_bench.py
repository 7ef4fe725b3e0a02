"""Compilation-tier benchmark: op-by-op vs jit vs jit+pallas — C14.

Reference: `02_development/compilation_optimization.py` benchmarks eager
vs `torch.compile` (default) vs max-autotune on a GPT-2-shaped LM and a
channels_last ResNet-18, eval mode, with per-variant failure tolerance
and CSV/JSON/txt artifacts (MI250X: ResNet-18 1.68x, LM 1.07x —
BASELINE.md).

TPU-native tier mapping (SURVEY §2.3):
  op-by-op    un-jitted apply — each op dispatched separately (the eager
              analogue; on TPU this is *pathological*, which is itself
              the point the reference's eager column makes)
  jit         one fused XLA program — the `torch.compile` default analogue
  jit+pallas  jit with the in-tree Pallas kernels: flash attention plus
              fused LayerNorm (transformer_lm) / fused RMSNorm (llama) —
              the max-autotune analogue (resnet has no attention; its
              pallas tier reports the jit number, flagged `same_as_jit`)

Beyond the reference's eval-mode table, `--train-step` times a full
fwd+bwd+optimizer step of the GPT-2-shaped LM at seq 1024, jit vs
jit+pallas — the regime where flash attention's memory behavior matters.

CLI: `python -m hyperion_tpu.bench.compile_bench [--dtype bf16] [--repeat N]
      [--train-step] [--train-seq 1024]`.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from hyperion_tpu.models.resnet import resnet18
from hyperion_tpu.models.transformer_lm import TransformerLM, gpt2_lm_config
from hyperion_tpu.bench.util import write_csv
from hyperion_tpu.utils.timing import time_chained, time_fn


def _compiled_temp_gb(jitted, *args) -> float:
    """Per-program temp memory from XLA's own analysis — unlike the
    allocator's lifetime peak counter, this resets per variant, so a
    memory-lighter variant can actually show a smaller number."""
    try:
        ma = jitted.lower(*args).compile().memory_analysis()
        return round(int(ma.temp_size_in_bytes) / 1e9, 4)
    except Exception:  # noqa: BLE001 — backends without the analysis
        return float("nan")


def _lm_spec(dtype: str, pallas: bool = False):
    impl = "pallas" if pallas else "xla"
    model = TransformerLM(gpt2_lm_config(
        dropout=0.0, dtype=dtype, attention_impl=impl, norm_impl=impl))
    params = model.init_params(jax.random.key(0), batch=2)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 50257, (32, 128)), jnp.int32
    )
    return lambda p, x: model.apply({"params": p}, x), params, ids


def _llama_spec(dtype: str, pallas: bool = False):
    """GPT-2-sized Llama stack — the fused-RMSNorm swap data point."""
    from hyperion_tpu.models.llama import Llama, LlamaConfig

    impl = "pallas" if pallas else "xla"
    model = Llama(LlamaConfig(
        vocab_size=32000, d_model=768, n_layers=4, n_heads=12,
        n_kv_heads=12, ff_dim=3072, max_len=512, remat=False, dtype=dtype,
        attention_impl=impl, norm_impl=impl,
    ))
    params = model.init_params(jax.random.key(0), batch=1, seq=512)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 32000, (8, 512)), jnp.int32
    )
    return lambda p, x: model.apply({"params": p}, x), params, ids


def _resnet_spec(dtype: str, pallas: bool = False):
    model = resnet18(num_classes=1000, cifar_stem=False, dtype=dtype)
    variables = model.init_variables(jax.random.key(0), image_size=224)
    x = jnp.zeros((32, 224, 224, 3), jnp.float32)

    def apply(v, x):
        return model.apply(v, x, train=False)

    return apply, variables, x


MODEL_SPECS = {
    "transformer_lm": _lm_spec,
    "llama": _llama_spec,
    "resnet18": _resnet_spec,
}
VARIANTS = ("op_by_op", "jit", "jit_pallas")


def bench_variant(
    name: str, variant: str, dtype: str, iters: int
) -> dict:
    apply, params, x = MODEL_SPECS[name](dtype, variant == "jit_pallas")
    if name == "resnet18" and variant == "jit_pallas":
        # no attention to swap; the tier exists for table parity
        variant_note = "same_as_jit"
    else:
        variant_note = ""

    if variant == "op_by_op":
        # per-call dispatch overhead IS the thing this tier measures
        # (the eager analogue), so per-call host-fenced timing is right
        it = max(3, iters // 4)
        t = time_fn(apply, params, x, warmup=2, iters=it)
        mean_ms = median_ms = t.median_ms
        temp_gb = float("nan")  # no single compiled program to analyse
    else:
        # jit tiers: chained data-dependent iterations, slope-based —
        # kernel time with fixed dispatch overhead excluded. The chain's
        # fencing reduction rides identically in every variant, so the
        # tier comparison stays like-for-like (absolute ms includes the
        # reduction; XLA may fuse it into the output matmul).
        it = max(6, min(iters, 16))
        jitted = jax.jit(apply)
        t = time_chained(jitted, params, x, k1=max(2, it // 3), k2=it)
        mean_ms = median_ms = t.per_iter_ms
        temp_gb = _compiled_temp_gb(jitted, params, x)
    return {
        "model": name,
        "variant": variant,
        "dtype": dtype,
        "mean_ms": round(mean_ms, 3),
        "median_ms": round(median_ms, 3),
        "temp_memory_gb": temp_gb,
        "iters": it,
        "note": variant_note,
    }


def run(models, dtype: str, iters: int, sink=None) -> list[dict]:
    rows = []
    for name in models:
        for variant in VARIANTS:
            try:
                r = bench_variant(name, variant, dtype, iters)
            except Exception as e:  # noqa: BLE001 — per-variant tolerance (C14)
                r = {
                    "model": name, "variant": variant, "dtype": dtype,
                    "mean_ms": float("nan"), "median_ms": float("nan"),
                    "temp_memory_gb": float("nan"), "iters": 0,
                    "note": f"failed: {str(e).splitlines()[0][:80]}",
                }
            rows.append(r)
            if sink is not None:
                sink(r)
            print(f"[compile_bench] {json.dumps(r)}")
    return rows


def train_step_rows(dtype: str, seq: int = 1024, batch: int = 4,
                    sink=None) -> list[dict]:
    """Full train step (fwd+bwd+opt) at long sequence, jit vs
    jit+pallas — where flash attention's O(T) memory vs the XLA path's
    [B, H, T, T] logits shows up in both time and peak memory."""
    import optax

    from hyperion_tpu.train.losses import next_token_loss
    from hyperion_tpu.train.state import make_optimizer

    rows = []
    for variant in ("jit", "jit_pallas"):
        impl = "pallas" if variant == "jit_pallas" else "xla"
        model = TransformerLM(gpt2_lm_config(
            dropout=0.0, dtype=dtype, max_len=seq,
            attention_impl=impl, norm_impl=impl,
        ))
        params = model.init_params(jax.random.key(0), batch=1)
        tx = make_optimizer(2e-4, grad_clip_norm=1.0)
        opt_state = tx.init(params)
        ids = jnp.asarray(
            np.random.default_rng(0).integers(0, 50257, (batch, seq)),
            jnp.int32,
        )

        def step(params, opt_state, ids):
            def loss_fn(p):
                logits = model.apply({"params": p}, ids)
                return next_token_loss(logits, ids, impl=impl)

            grads = jax.grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        try:
            t = time_chained(step, params, opt_state, ids,
                             k1=2, k2=6, n_thread=2)
            rows.append({
                "model": f"transformer_lm_seq{seq}_train",
                "variant": variant,
                "dtype": dtype,
                "mean_ms": round(t.per_iter_ms, 3),
                "median_ms": round(t.per_iter_ms, 3),
                "temp_memory_gb": _compiled_temp_gb(
                    jax.jit(step), params, opt_state, ids),
                "iters": t.k2,
                "note": "",
            })
        except Exception as e:  # noqa: BLE001 — per-variant tolerance (C14)
            rows.append({
                "model": f"transformer_lm_seq{seq}_train",
                "variant": variant, "dtype": dtype,
                "mean_ms": float("nan"), "median_ms": float("nan"),
                "temp_memory_gb": float("nan"), "iters": 0,
                "note": f"failed: {str(e).splitlines()[0][:80]}",
            })
        if sink is not None:
            sink(rows[-1])
        print(f"[compile_bench] {json.dumps(rows[-1])}")
    return rows


def summarize(rows: list[dict]) -> str:
    lines = ["compilation tier analysis", "=" * 40]
    for model in {r["model"] for r in rows}:
        sub = {r["variant"]: r for r in rows if r["model"] == model}
        base = sub.get("jit", {}).get("median_ms")
        lines.append(f"\n{model}:")
        for variant in VARIANTS:
            r = sub.get(variant)
            if r is None:
                continue  # tier not attempted (e.g. train-step rows)
            if r["median_ms"] != r["median_ms"]:
                lines.append(f"  {variant:>10}: failed")
                continue
            speed = (base / r["median_ms"]) if base else float("nan")
            mem = r.get("temp_memory_gb")
            mem_s = (
                f"  temp {mem:.3f} GB"
                if isinstance(mem, (int, float)) and mem == mem else ""
            )
            lines.append(
                f"  {variant:>10}: {r['median_ms']:9.3f} ms"
                f"  ({speed:.2f}x vs jit){mem_s} {r['note']}"
            )
    return "\n".join(lines)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--models", nargs="*", default=list(MODEL_SPECS))
    p.add_argument("--dtype", choices=["fp32", "bf16"], default="bf16")
    p.add_argument("--repeat", type=int, default=20)
    p.add_argument("--train-step", action="store_true",
                   help="add the long-seq train-step jit-vs-pallas rows")
    p.add_argument("--train-seq", type=int, default=1024)
    p.add_argument("--train-batch", type=int, default=4)
    p.add_argument("--out", default="results/benchmarks/compilation")
    args = p.parse_args(argv)

    dtype = {"fp32": "float32", "bf16": "bfloat16"}[args.dtype]
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    # incremental flush: every row already measured must be on disk,
    # not in this process's memory, if the run is cut at its time limit
    flushed: list[dict] = []

    def sink(row: dict) -> None:
        flushed.append(row)
        write_csv(out / "compilation_benchmark.csv", flushed)
        (out / "compilation_benchmark.json").write_text(
            json.dumps(flushed, indent=2))

    rows = run(args.models, dtype, args.repeat, sink=sink)
    if args.train_step:
        rows += train_step_rows(dtype, args.train_seq, args.train_batch,
                                sink=sink)
    from hyperion_tpu.metrics.plots import plot_compile_tiers, try_plot

    try_plot(plot_compile_tiers, rows, out / "compilation_benchmark.png")
    text = summarize(rows)
    (out / "compilation_analysis.txt").write_text(text)
    print(text)


if __name__ == "__main__":
    main()
