"""The plain reference `benchmarks/reference/afmoe.py` put wrong in one
way, for the tests and the chip runs that show the comparison deciding
`correct` says so: the served tokens (or the model's logits) are held to
a reference that has no window mask, rotary positions on the full layers
too, no output gate, no shared expert, no `route_scale`, no QK-norm, or
(the nearest precision below the configuration's bf16) every matrix
rounded to float8 where the reference upcasts it, so that no second copy
of the weights is ever held. The reference itself knows none of this:
each fault replaces one of its functions while the block lasts."""

from __future__ import annotations

import contextlib

import jax.numpy as jnp

from benchmarks.reference import afmoe as reference

_JITTED = (reference.layer, reference.head)


def _with(**over):
    return lambda right: lambda *a, **kw: right(*a, **{**kw, **over})


# fault -> (the reference's function it replaces, right one -> wrong one)
FAULTS = {
    "no_window_mask": ("logits", _with(window=0)),
    "rotary_on_full_layers": ("layer", _with(rotary=True)),
    "no_gate": ("output_gate", lambda right: lambda a, u, kernel: a),
    "no_shared_expert": ("shared_expert", lambda right: lambda flat, p: 0.0),
    "no_route_scale": ("logits", _with(route_scale=1.0)),
    "no_qk_norm": ("qk_norm", lambda right: lambda q, k, p, eps: (q, k)),
    "fp8_weights": ("upcast", lambda right: lambda w: right(
        w.astype(jnp.float8_e4m3fn) if w.ndim > 1 else w)),
}
EQUATIONS = tuple(f for f in FAULTS if f != "fp8_weights")


@contextlib.contextmanager
def fault(name: str):
    """`reference.logits` is wrong in the named way inside the block."""
    attr, wrong = FAULTS[name]
    right = getattr(reference, attr)

    def put(fn):
        setattr(reference, attr, fn)
        for jitted in _JITTED:      # traced with the function that was there
            jitted.clear_cache()

    put(wrong(right))
    try:
        yield
    finally:
        put(right)
