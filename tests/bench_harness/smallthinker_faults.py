"""The plain reference `benchmarks/reference/smallthinker.py` put wrong
in one way, for the tests and the chip runs that show the comparison
deciding `correct` says so: the served tokens (or the model's logits)
are held to a reference whose router reads the post-attention normed
state (what `afmoe` routes on) or the input-normed state, whose gate is
SiLU, whose weights are the picked sigmoids normalised, that has no
window mask, rotary positions on the full layers too or on none, or
(the nearest precision below the configuration's bf16) every matrix
rounded to float8 where the reference upcasts it, so that no second copy
of the weights is ever held. The reference itself knows none of this:
each fault replaces one of its functions while the block lasts. These
controls stand on the REFERENCE's side of the comparison: the served
tokens are the sound program's, the logits (and the rows' margins) a
wrong reference's. `another_slots_token` stands on the other side: one
served token is not the one the program chose."""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import smallthinker as reference

_JITTED = (reference.layer, reference.head)


def _with(**over):
    return lambda right: lambda *a, **kw: right(*a, **{**kw, **over})


def _sigmoid_weights(r, top_k):
    s = jax.nn.sigmoid(r)
    top, picked = jax.lax.top_k(s, top_k)
    return jnp.zeros_like(r).at[
        jnp.arange(r.shape[0])[:, None], picked].add(
            top / top.sum(-1, keepdims=True))


# fault -> (the reference's function it replaces, right one -> wrong one)
FAULTS = {
    "router_reads_post_attention_state": (
        "router_input", lambda right: lambda h, u, x: x),
    "router_reads_normed_input": (
        "router_input", lambda right: lambda h, u, x: u),
    "silu_gate": ("gate_act", lambda right: jax.nn.silu),
    "sigmoid_weights": ("route_weights", lambda right: _sigmoid_weights),
    "no_window_mask": ("logits", _with(window=0)),
    "rotary_on_full_layers": ("layer", _with(rotary=True)),
    "no_rotary_on_sliding_layers": ("layer", _with(rotary=False)),
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


@contextlib.contextmanager
def another_slots_token():
    """One served token is another slot's inside the block: where the
    shortest checked request's middle token was, the check scores the
    token the last checked request was served at the same step (the
    nearest step where the two differ), as a host that hands a fetched
    token to the wrong client would leave it. The engine went on from
    its own token, so no other row moves."""
    from benchmarks.adapters import serve_smallthinker as adapter

    right = adapter.served

    def wrong(picked):
        toks = right(picked)
        mine, other = toks[0], toks[-1][:len(toks[0])]
        differ = np.flatnonzero(mine[:len(other)] != other)
        at = differ[np.abs(differ - len(mine) // 2).argmin()]
        toks[0] = mine.copy()
        toks[0][at] = other[at]
        return toks

    adapter.served = wrong
    try:
        yield
    finally:
        adapter.served = right
