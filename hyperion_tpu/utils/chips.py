"""Known TPU chip peak throughputs — plausibility guard data.

The reference publishes its hardware peaks implicitly (MI250X ~383
TFLOPS fp16 marketing peak vs ~121-128 achieved, BASELINE.md); our bench
harness goes further and *refuses to publish* a measurement above the
chip's nominal peak: a timing that is not fenced measures the enqueue
and yields physically impossible numbers.

Peaks are public nominal dense-matmul numbers per chip. `fp32` on the
MXU routes through bf16-based passes, so the bf16 peak is a safe upper
bound for every float dtype; int8 runs at 2x.
"""

from __future__ import annotations

import jax

# device_kind substring (lowercased) -> nominal dense bf16 TFLOPS per chip
_BF16_PEAKS: tuple[tuple[str, float], ...] = (
    ("v6 lite", 918.0),   # Trillium / v6e
    ("v6", 918.0),
    ("v5 lite", 197.0),   # v5e
    ("v5p", 459.0),
    ("v5", 459.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def device_kind(device: jax.Device | None = None) -> str:
    d = device or jax.devices()[0]
    return str(getattr(d, "device_kind", "unknown"))


def nominal_peak_tflops(
    dtype: str = "bfloat16", device: jax.Device | None = None
) -> float | None:
    """Nominal matmul peak for this chip; None on the CPU (tests). A TPU
    whose `device_kind` is not in the table is an error, not a default:
    a utilization against a guessed peak is not a measurement.

    Any float dtype is bounded by the bf16 peak; int8/int4 get 2x/4x.
    """
    d = device or jax.devices()[0]
    if d.platform != "tpu":
        return None
    kind = device_kind(d).lower()
    bf16 = next((peak for sub, peak in _BF16_PEAKS if sub in kind), None)
    if bf16 is None:
        raise ValueError(
            f"no nominal peak known for TPU device_kind {device_kind(d)!r}; "
            "add it to utils/chips.py with its source")
    if dtype in ("int8", "uint8"):
        return 2 * bf16
    if dtype in ("int4", "uint4"):
        return 4 * bf16
    return bf16


def mfu(tflops: float, dtype: str = "bfloat16",
        device: jax.Device | None = None) -> float | None:
    """Model-FLOPs-utilisation fraction vs the chip's nominal peak."""
    peak = nominal_peak_tflops(dtype, device)
    if not peak:
        return None
    return tflops / peak
