"""Decode tick against the HBM roofline: the least time the chip could
take to read what a tick must read (`costs.tick_bytes`: the weights once
and the keys and values of the live slots' tokens, averaged over the
traced ticks) over the device time of one run of the tick program (the
median of its `XLA Modules` events in the trace). The tick is bound by
bytes, not operations: 48 tokens x 7.5 GFLOP is 2 ms at the bf16 peak."""

import numpy as np

TICK_MODULE = "_tick_impl"      # the engine's decode tick, as the trace names it


def read(ctx):
    t, need = ctx.get("trace"), ctx.get("tick_bytes")
    if not t or not need:
        return None
    runs = [d for name, ds in t["modules"].items()
            if TICK_MODULE in name for d in ds]
    if not runs:
        return None
    least = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / float(np.median(runs))
