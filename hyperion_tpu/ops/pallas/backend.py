"""What the four kernels share: the TPU's fp32 tile, and which way a
kernel runs on the process's default backend."""

from __future__ import annotations

import jax

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
