"""Long-sequence attention scaling: XLA einsum vs the Pallas flash kernel.

The reference never runs attention past seq 128 (its encoder benchmark
uses seq 16, its LM seq 128 — SURVEY §5.7 calls long-context "absent");
this framework claims long-context as first-class, and this benchmark is
the single-chip evidence: per-sequence-length fwd and train-step time
plus per-program temp memory for

  impl="xla"     materializes the [T, T] score matrix (HBM O(T^2) —
                 at seq 16k that is 6+ GB for one GPT-2-shaped head
                 block, and the fwd+bwd program keeps it for the
                 backward pass)
  impl="pallas"  in-tree flash attention (streaming K/V tiles, online
                 softmax, O(T) residuals; hand-written dq/dk/dv)

A row whose program cannot fit records status="oom" instead of killing
the sweep — "flash extends the reachable context" is exactly the claim,
so the failure row IS the evidence. Memory per row comes from XLA's
static `memory_analysis()` (per-program, no cross-row contamination —
the allocator's lifetime peak would smear the xla rows' O(T^2) spike
over every later flash row).

Timing: `utils.timing.time_chained` with (q, k, v) threaded through
epsilon-updates, so every chained iteration is data-dependent on the
last and the lazy-fence backend cannot elide or overlap anything. The
bwd chain folds dq/dk/dv into all three carries, so both impls pay
their full backward (a q-only chain would let XLA dead-code the dk/dv
kernels of whichever impl splits them).

Multi-device sequence parallelism (ring / Ulysses over the seq axis) is
deliberately not here: one chip has no seq axis to shard; those paths
are validated on the simulated mesh (tests/test_ring_attention.py,
tests/test_ulysses.py) and dry-run by `__graft_entry__.dryrun_multichip`.

CLI: `python -m hyperion_tpu.bench.attention_bench [--seqs ...]
[--impls xla pallas] [--out results/benchmarks/attention]`.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp

from hyperion_tpu.bench.util import write_csv
from hyperion_tpu.ops.attention import dot_product_attention
from hyperion_tpu.utils.timing import time_chained

# (batch, heads, head_dim) per geometry: gpt2 is the toy-LM family's
# hot shape (D=64 half-fills the MXU contraction); llama is the
# 7B-family shape (D=128, the MXU's native lane width).
GEOMETRIES = {
    "gpt2": (1, 12, 64),
    "llama": (1, 32, 128),
}


def _qkv(seq: int, dtype: str, geometry: str):
    batch, heads, head_dim = GEOMETRIES[geometry]
    ks = jax.random.split(jax.random.key(0), 3)
    shape = (batch, seq, heads, head_dim)
    dt = jnp.dtype(dtype)
    scale = 1.0 / head_dim**0.25  # unit-variance logits at any seq
    return tuple(jax.random.normal(k, shape, dt) * scale for k in ks)


def _attn_flops(seq: int, backward: bool, geometry: str) -> float:
    """Causal-aware FLOP count: QK^T and PV are each 2*B*H*T^2*D MACs,
    halved by causality; backward re-does both plus dq/dk/dv (5 matmuls
    vs 2 — the standard 2.5x accounting)."""
    batch, heads, head_dim = GEOMETRIES[geometry]
    fwd = 2 * 2 * batch * heads * seq * seq * head_dim * 0.5
    return fwd * 3.5 if backward else fwd


def _fwd_step(impl: str):
    def step(q, k, v):
        o = dot_product_attention(q, k, v, causal=True, impl=impl)
        # thread the output back into q (same shape): each iteration
        # consumes every element the previous one produced
        return o, k, v

    return step


def _train_step(impl: str):
    def loss(q, k, v):
        o = dot_product_attention(q, k, v, causal=True, impl=impl)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def step(q, k, v):
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        eps = jnp.asarray(1e-30, q.dtype)
        return q - eps * dq.astype(q.dtype), \
            k - eps * dk.astype(k.dtype), \
            v - eps * dv.astype(v.dtype)

    return step


def _temp_gb(fn, *args) -> float:
    """Per-program temp memory from XLA's static analysis."""
    try:
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
        return round(int(ma.temp_size_in_bytes) / 1e9, 4)
    except Exception:  # noqa: BLE001 — backends without the analysis
        return float("nan")


def benchmark_attention(
    seq: int, impl: str, mode: str = "train", dtype: str = "bfloat16",
    k1: int = 4, k2: int = 12, geometry: str = "gpt2",
) -> dict:
    """One row: `mode` is "fwd" (inference shape) or "train" (fwd+bwd)."""
    batch, heads, head_dim = GEOMETRIES[geometry]
    q, k, v = _qkv(seq, dtype, geometry)
    step = (_fwd_step if mode == "fwd" else _train_step)(impl)
    from hyperion_tpu.ops.pallas.flash_attention import KERNEL_REV

    row = {
        "seq": seq, "impl": impl, "mode": mode, "dtype": dtype,
        "geometry": geometry,
        "batch": batch, "heads": heads, "head_dim": head_dim,
        # stamp the kernel revision so offline comparisons can detect a
        # capture that predates a kernel retune (compare_to_reference.py
        # suppresses its auto-pick MISMATCH flag on stale captures)
        "kernel_rev": KERNEL_REV,
    }
    try:
        res = time_chained(step, q, k, v, k1=k1, k2=k2, n_thread=3)
        tflops = (_attn_flops(seq, mode == "train", geometry)
                  / (res.per_iter_ms / 1e3) / 1e12)
        row.update(
            status="ok",
            per_iter_ms=round(res.per_iter_ms, 3),
            achieved_tflops=round(tflops, 4),  # 4dp: tiny smoke shapes are sub-0.01
            temp_memory_gb=_temp_gb(step, q, k, v),
            dispatch_overhead_ms=round(res.overhead_ms, 2),
        )
    except Exception as e:  # noqa: BLE001 — an OOM row is the finding
        msg = (str(e).splitlines()[0] if str(e) else repr(e))[:160]
        oom = "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()
        row.update(
            status="oom" if oom else "error",
            per_iter_ms=float("nan"), achieved_tflops=float("nan"),
            temp_memory_gb=float("nan"), dispatch_overhead_ms=float("nan"),
            note=msg,
        )
    return row


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seqs", type=int, nargs="*",
                   default=[1024, 2048, 4096, 8192, 16384])
    p.add_argument("--impls", nargs="*", default=["xla", "pallas"])
    p.add_argument("--modes", nargs="*", default=["fwd", "train"])
    p.add_argument("--geometries", nargs="*", default=["gpt2", "llama"],
                   choices=sorted(GEOMETRIES))
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--out", default="results/benchmarks/attention")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    out = Path(args.out)
    rows: list[dict] = []
    # seq-major order: both impls at seq T land (and flush) before the
    # bigger T compiles — a capture window that dies mid-sweep still
    # committed a complete like-for-like comparison at every finished T
    for seq in args.seqs:
        for geometry in args.geometries:
            for mode in args.modes:
                for impl in args.impls:
                    row = benchmark_attention(
                        seq, impl, mode, args.dtype, geometry=geometry
                    )
                    rows.append(row)
                    write_csv(out / "attention_scaling.csv", rows)
                    print(f"[attention] {json.dumps(row)}")
    print(f"[attention] results in {out}/")
    # status="oom" is the expected long-seq finding; status="error" means
    # the measurement itself broke — exit nonzero so a broken sweep is
    # never taken for evidence
    return 1 if any(r["status"] == "error" for r in rows) else 0


if __name__ == "__main__":
    raise SystemExit(main())
