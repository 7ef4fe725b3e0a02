"""What the kernels share: the TPU's fp32 tile, and which way a
kernel runs on the process's default backend."""

from __future__ import annotations

import math

import jax
from jax.experimental import pallas as pl

# One fp32 VMEM tile is (SUBLANES, LANES). Per-row operands (softmax
# statistics, CE targets, padding masks) are carried replicated across
# one of the two so that their blocks have a tiling XLA and Mosaic agree on.
LANES = 128
SUBLANES = 8


def interpret_on_backend() -> bool:
    """True on the CPU backend (the tests run the kernels through the
    Pallas interpreter), False on a TPU (compiled by Mosaic). Any other
    backend is refused: a kernel that silently ran the interpreter
    there would be measured as if it were the kernel."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' and interpreted on 'cpu'; "
        f"the default backend is {backend!r}"
    )


def cost(flops: int, transcendentals: int, *moved,
         extra_bytes: int = 0) -> pl.CostEstimate:
    """A kernel's cost for the compiler and for a trace's reader
    (obs/xprof.py sets a kernel's time against it): the FLOPs and
    transcendentals its algorithm needs, and as bytes every array in
    `moved` (operands and results, arrays or `ShapeDtypeStruct`s) once
    plus `extra_bytes`: what the kernel must read and write, not what
    a tiling happens to read again."""
    moved_bytes = sum(
        math.prod(a.shape) * jax.numpy.dtype(a.dtype).itemsize
        for a in moved)
    return pl.CostEstimate(
        flops=int(flops), transcendentals=int(transcendentals),
        bytes_accessed=int(moved_bytes + extra_bytes))
