"""Faults for the comparison that decides `correct` in the cell
`solar2.chat-saturated`, on both of its sides.

`system(name)`: the SERVED side, a stand-in for the system with one
fault planted: `models/solar_open2.py` with one of its equations wrong
(no decay, beta without its factor 2, the conv dropped, rotary positions
on the GQA layer) or the engine's recurrent state advanced where it
must not be (a prefill bucket's pad positions entering the state, the
decode tick advancing a lane that is still prefilling in chunks). The
model looks the replaced function up when a program is traced, so the
stand-in is a model of another identity (`twin`): the engine's jits are
shared process-wide and keyed by the model.

`fp8(params)`: the served side in the nearest precision below the
configuration's bf16: the weights the engine is given, every matrix
rounded to float8 e4m3, while the reference keeps the bf16 ones.

`reference(name)`: the same equations wrong on the REFERENCE's side
(`benchmarks/reference/solar_open2.py`): what the chip can read at the
cell's size from one run's served tokens, without serving them again."""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from benchmarks.reference import solar_open2 as ref_module
from benchmarks.reference.decoder import rope
from hyperion_tpu.models import solar_open2 as model_module


def _rotary(q, k, base):
    """Rotary positions (theta 10000) at each row's own positions."""
    from hyperion_tpu.models.afmoe import device_rope_table
    from hyperion_tpu.models.llama import apply_rope

    table = device_rope_table(q.shape[-1], 8192, 10000.0)
    return apply_rope(q, table, base), apply_rope(k, table, base)


def _advancing(where: str):
    right = model_module.advancing

    def wrong(lens, T):
        if (T > 1) == (where == "prompt"):
            return jnp.ones((lens.shape[0], T), bool)
        return right(lens, T)

    return wrong


# fault -> (the model's function it replaces, the wrong one)
SYSTEM = {
    "pads_advance_state": ("advancing", lambda: _advancing("prompt")),
    "no_decay": ("log_decay", lambda: lambda f, a, dt: jnp.zeros_like(f)),
    "beta_without_2": ("update_strength", lambda: jax.nn.sigmoid),
    "conv_dropped": ("short_conv", lambda: lambda xs, w: xs[
        :, w.shape[0] - 1:].astype(jnp.float32)),
    "ticks_advance_a_chunking_lane": ("advancing", lambda: _advancing("tick")),
    "rotary_on_gqa": ("positions", lambda: _rotary),
}


def twin(name: str):
    """A model class of its own for the stand-in with fault `name`."""
    return type(f"SolarOpen2_{name}", (model_module.SolarOpen2,), {})


@contextlib.contextmanager
def system(name: str):
    """`models/solar_open2.py` is wrong in the named way inside the
    block, for programs traced there."""
    attr, wrong = SYSTEM[name]
    right = getattr(model_module, attr)
    setattr(model_module, attr, wrong())
    try:
        yield
    finally:
        setattr(model_module, attr, right)


def fp8(params):
    """`params` with every matrix (the conv's taps among them) rounded
    to float8 e4m3 and held in its own type again; vectors as they are."""
    return jax.tree.map(lambda w: w.astype(jnp.float8_e4m3fn).astype(
        w.dtype) if w.ndim > 1 else w, params)


# fault -> (the reference's function it replaces, right one -> wrong one)
REFERENCE = {
    "no_decay": ("log_decay",
                 lambda right: lambda f, a, dt: jnp.zeros_like(f)),
    "beta_without_2": ("update_strength", lambda right: jax.nn.sigmoid),
    "conv_dropped": ("short_conv", lambda right: lambda x, w: x),
    "rotary_on_gqa": ("positions", lambda right: lambda q, k, theta: (
        rope(q, theta), rope(k, theta))),
}


@contextlib.contextmanager
def reference(name: str):
    """`reference.logits` is wrong in the named way inside the block."""
    attr, wrong = REFERENCE[name]
    right = getattr(ref_module, attr)

    def put(fn):
        setattr(ref_module, attr, fn)
        for jitted in (ref_module.layer, ref_module.head):
            jitted.clear_cache()     # traced with the function that was there

    put(wrong(right))
    try:
        yield
    finally:
        put(right)
