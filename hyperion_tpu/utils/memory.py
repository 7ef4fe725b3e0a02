"""Device-memory accounting — the torch.cuda memory-counter analogue.

Reference reads `memory_allocated` / `max_memory_allocated` /
`reset_peak_memory_stats` throughout its benchmarks
(`baseline_performance.ipynb cell 0:158-162`,
`01_hardware_exploration.ipynb cell 1:25-32`). The TPU equivalents come
from the PJRT allocator via `device.memory_stats()`. The CPU (test)
backend reports none, and there every reader gives 0; a TPU that
reports none is an error, not a 0.
"""

from __future__ import annotations

import jax


def device_memory_stats(device: jax.Device | None = None) -> dict:
    device = device or jax.devices()[0]
    stats = device.memory_stats()
    if not stats and device.platform == "tpu":
        raise RuntimeError(f"{device} reports no memory_stats()")
    return dict(stats or {})


def live_bytes_in_use(device: jax.Device | None = None) -> int:
    return int(device_memory_stats(device).get("bytes_in_use", 0))


def peak_bytes_in_use(device: jax.Device | None = None) -> int:
    s = device_memory_stats(device)
    return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))


def compiled_peak_bytes(jitted, *args, **kwargs) -> int:
    """Peak device bytes of ONE compiled program from XLA's own
    `memory_analysis()`.

    Program peak = live arguments + outputs + XLA temp (activations,
    collective buffers), minus donated/aliased buffers counted on both
    sides. This is a compile-time static bound for the one executable,
    not a process lifetime peak — for a train step it is exactly the
    number the '7B fits in 16 GB' story needs. With the persistent
    compilation cache the lower/compile here is a cache hit, not a
    second real compile. Returns 0 when the backend lacks the analysis."""
    try:
        ma = jitted.lower(*args, **kwargs).compile().memory_analysis()
        return int(
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes
        )
    except Exception:  # noqa: BLE001 — backends without the analysis
        return 0
