"""The plain reference `benchmarks/reference/ouro.py` put wrong in one
way, for the tests and the chip runs that show the comparison deciding
`correct` says so: the served tokens are held to a reference that runs
one step fewer, whose steps all read the keys and values the LAST step
computed (the cache shared between steps that the model's own paper
proposes to save memory: a different result, not a faster one), that
norms the stream only after the last step (a plain decoder's place for
the final norm), that leaves the sandwich's post-norms out, or (the
nearest precision below the configuration's bf16) that rounds every
matrix to float8 where it upcasts it. The reference itself knows none
of this: each fault replaces functions of it while the block lasts.
These controls stand on the REFERENCE's side of the comparison: the
served tokens are the sound program's, the logits (and the rows'
margins) a wrong reference's. `another_slots_token` stands on the other
side: one served token is not the one the program chose."""

from __future__ import annotations

import contextlib

import jax.numpy as jnp
import numpy as np

from benchmarks.reference import ouro as reference

_JITTED = (reference.layer, reference.step_end, reference.head)


def _one_step_fewer(right):
    return lambda *a, **kw: right(*a, **{**kw, "steps": kw["steps"] - 1})


@contextlib.contextmanager
def _replaced(**fns):
    """The reference's functions `fns` replaced while the block lasts."""
    rights = {attr: getattr(reference, attr) for attr in fns}

    def put(which):
        for attr, fn in which.items():
            setattr(reference, attr, fn)
        for jitted in _JITTED:      # traced with the functions that were there
            jitted.clear_cache()

    put(fns)
    try:
        yield rights
    finally:
        put(rights)


def _shared_pass(right):
    """A first, sound pass keeps the last step's keys and values; the
    pass that is scored hands them to every layer of every step."""
    def forward(params, ids, *, rows=None, **kw):
        *_, last = right(params, ids, **kw)
        with _replaced(keys_values=lambda k, v, shared: shared):
            return right(params, ids, rows=rows, shared=last, **kw)
    return forward


# fault -> (the reference's function it replaces, right one -> wrong one)
FAULTS = {
    "fp8_weights": ("upcast", lambda right: lambda w: right(
        w.astype(jnp.float8_e4m3fn) if w.ndim > 1 else w)),
    "three_steps_of_four": ("forward", _one_step_fewer),
    "cache_shared_between_steps": ("forward", _shared_pass),
    "no_norm_between_steps": ("between_steps", lambda right: (
        lambda h, w, eps, last: right(h, w, eps, last) if last else h)),
    "no_post_norms": ("post_norm", lambda right: lambda y, w, eps: y),
}
EQUATIONS = tuple(f for f in FAULTS if f != "fp8_weights")


@contextlib.contextmanager
def fault(name: str):
    """`reference.logits` is wrong in the named way inside the block."""
    attr, wrong = FAULTS[name]
    with _replaced(**{attr: wrong(getattr(reference, attr))}):
        yield


@contextlib.contextmanager
def another_slots_token():
    """One served token is another slot's inside the block: where the
    shortest checked request's middle token was, the check scores the
    token the last checked request was served at the same step (the
    nearest step where the two differ), as a host that hands a fetched
    token to the wrong client would leave it. The engine went on from
    its own token, so no other row moves."""
    from benchmarks.adapters import serve_ouro as adapter

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
