"""Mixture-of-Experts FFN with expert parallelism over the `expert` axis.

Reference status: **absent** — SURVEY §2.2's EP row records no MoE code
in the MI250X project; this is beyond-parity TPU headroom, written in
the GShard/Switch einsum formulation the hardware wants:

  * Routing is top-k over a fp32 router; every shape is static. Tokens
    route within fixed-size GROUPS (GShard's G dimension, default one
    group per batch row): assignment becomes two one-hot tensors per
    group — `dispatch` [G, g, E, C] (token n of group g occupies slot c
    of expert e) and `combine` (same shape, gate-weighted) — so
    dispatch and return are plain einsums that XLA tiles onto the MXU,
    with C = ceil(k·g/E)·capacity_factor PER GROUP (memory linear in
    total tokens). No gathers, no dynamic shapes.
  * Expert weights are stacked [E, ...] and shard `P('expert')`
    (`parallel.partition` claims the leading dim, like the pipeline's
    stage leaves). The dispatched-token tensor [E, C, d] carries a
    `with_sharding_constraint` on the same axis, so GSPMD inserts the
    token all-to-all over ICI on its own — expert parallelism as a
    layout decision, consistent with how this framework does DP/FSDP/TP.
  * Capacity is `ceil(k * N / E) * capacity_factor` per expert; tokens
    routed past capacity are dropped (their combine weights are zero, so
    with the usual residual connection they pass through unchanged) —
    standard Switch semantics.
  * The load-balancing auxiliary loss is GShard's
    `E * Σ_e f_e · P_e` (f_e = fraction of tokens whose top-1 choice is
    e, P_e = mean router probability for e); ≈ 1.0 under uniform
    routing, grows as routing collapses.

Two layers live here and serve different users. `moe_ffn` (above) is
TRAINING's: `MoELM`'s capacity routing, which drops tokens past an
expert's capacity and is exact only against itself. What follows it is
SERVING's: token-choice routing over ALL of a model's experts, no
capacity and no drop, in two steps a model puts together. A ROUTER
says which experts each token picked and with what weight, `(picked
[N, k] int32, w [N, k] float32)`: `sigmoid_topk_route` (sigmoid scores,
a selection bias, normalised and scaled: `models/afmoe.py`) or
`softmax_topk_route` (a softmax over the picked logits:
`models/smallthinker.py`, which routes on the layer's input, before
attention). ONE expert step, `grouped_experts`, takes the tokens the
experts read, the picks, the weights, which contiguous run of experts
this chip holds (`held`) and the gate's activation, and computes the
part of the result those experts give, over grouped matrix products
(tokens sorted by expert); what the absent experts would have added is
left out. The three products take one of two forms, chosen per call
from its static shape and the backend (`select_grouped_impl`, resolved
at trace time): the Pallas kernel that streams each touched expert's
matrix once (`ops/pallas/grouped_matmul.py`) at the shapes it was
measured to win at on a TPU, `jax.lax.ragged_dot` everywhere else and
on every other backend. `dropless_moe` is the two composed on one
input, as `afmoe` calls them. That is what an inference reference can
be matched against; `moe_ffn` is not.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from hyperion_tpu.runtime.mesh import AxisName, active_mesh


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 4
    top_k: int = 2
    capacity_factor: float = 1.25
    d_model: int = 256
    ff_dim: int = 1024
    activation: str = "gelu"
    # routing group size in tokens (GShard's G dimension): dispatch
    # memory is O(group * E * capacity) PER GROUP, linear in total
    # tokens — without grouping it would grow quadratically. 0 = one
    # group per batch row (group = seq_len).
    group_size: int = 0

    def __post_init__(self):
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(
                f"need 1 <= top_k <= n_experts, got top_k={self.top_k} "
                f"n_experts={self.n_experts}"
            )

    def capacity(self, n_tokens: int) -> int:
        per = -(-self.top_k * n_tokens // self.n_experts)  # ceil
        return max(1, int(per * self.capacity_factor))


def init_moe_params(rng: jax.Array, cfg: MoEConfig) -> dict:
    """Stacked expert FFN + router. `experts/` leaves are [E, ...] so the
    partition layer can claim the leading dim for the expert axis."""
    r_router, r_wi, r_wo = jax.random.split(rng, 3)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.ff_dim
    xavier = jax.nn.initializers.xavier_uniform()
    return {
        "router": {"kernel": xavier(r_router, (d, E), jnp.float32)},
        "experts": {
            "wi": jax.vmap(lambda r: xavier(r, (d, f), jnp.float32))(
                jax.random.split(r_wi, E)
            ),
            "bi": jnp.zeros((E, f), jnp.float32),
            "wo": jax.vmap(lambda r: xavier(r, (f, d), jnp.float32))(
                jax.random.split(r_wo, E)
            ),
            "bo": jnp.zeros((E, d), jnp.float32),
        },
    }


def top_k_routing(probs: jax.Array, cfg: MoEConfig, capacity: int,
                  valid: jax.Array | None = None):
    """probs [N, E] → (dispatch [N, E, C] bool-ish, combine [N, E, C]).

    Slot positions come from a cumulative count over the token dim, with
    all k=0 picks prioritized before k=1 picks (Switch's top-1-first
    ordering). Gates are normalized over ALL top-k picks before capacity
    is applied, so a token whose pick overflows capacity simply loses
    that share of its output (it passes through the residual instead) —
    dropped mass is not re-routed to the surviving pick.

    `valid` ([N], 1 = real token): padding tokens are excluded from
    dispatch entirely — they consume no capacity slots and get zero
    combine weight (their block output is 0; the residual carries them).
    """
    N, E = probs.shape
    masks, gates = [], []
    p = probs
    for _ in range(cfg.top_k):
        idx = jnp.argmax(p, axis=-1)
        mask = jax.nn.one_hot(idx, E, dtype=probs.dtype)  # [N, E]
        gates.append(jnp.sum(probs * mask, axis=-1))      # original prob
        masks.append(mask if valid is None else mask * valid[:, None])
        p = p * (1.0 - mask)

    dispatch = jnp.zeros((N, E, capacity), probs.dtype)
    combine = jnp.zeros((N, E, capacity), probs.dtype)
    gate_total = sum(gates) + 1e-9
    used = jnp.zeros((E,), probs.dtype)
    for mask, gate in zip(masks, gates):
        pos = jnp.cumsum(mask, axis=0) - mask + used[None, :]  # [N, E]
        used = used + jnp.sum(mask, axis=0)
        slot = jnp.sum(pos * mask, axis=-1).astype(jnp.int32)  # [N]
        keep = (jnp.sum(pos * mask, axis=-1) < capacity).astype(probs.dtype)
        hot = jax.nn.one_hot(slot, capacity, dtype=probs.dtype)  # [N, C]
        sel = mask * keep[:, None]                               # [N, E]
        dispatch = dispatch + sel[:, :, None] * hot[:, None, :]
        combine = combine + (gate / gate_total)[:, None, None] * (
            sel[:, :, None] * hot[:, None, :]
        )
    return dispatch, combine


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig,
            padding_mask: jax.Array | None = None):
    """x [B, T, d] → (y [B, T, d], aux_loss scalar).

    Tokens route within fixed-size GROUPS (GShard's G dimension, default
    one group per batch row) so dispatch/combine are [G, g, E, C] with
    C ∝ g/E — memory linear in total tokens, not quadratic. The expert
    einsums run with the [G, E, C, d] token blocks and [E, ...] weights
    sharded over the mesh's `expert` axis when one is active — GSPMD
    turns the dispatch/return einsums into the token all-to-all.

    `padding_mask` ([B, T], 1 = real): pads neither consume expert
    capacity nor count in the load-balancing loss; their output is 0
    (the residual carries them).
    """
    B, T, d = x.shape
    N = B * T
    E = cfg.n_experts
    act = {"relu": jax.nn.relu, "gelu": jax.nn.gelu}[cfg.activation]
    g = cfg.group_size or T
    if N % g:
        raise ValueError(f"{N} tokens not divisible by group_size {g}")
    G = N // g
    capacity = cfg.capacity(g)  # per group

    xg = x.reshape(G, g, d)
    logits = jnp.einsum(
        "gnd,de->gne", xg.astype(jnp.float32), params["router"]["kernel"]
    )
    probs = jax.nn.softmax(logits, axis=-1)  # [G, g, E] fp32

    if padding_mask is None:
        route = jax.vmap(lambda p: top_k_routing(p, cfg, capacity))
        dispatch, combine = route(probs)
        valid = None
    else:
        valid = padding_mask.reshape(G, g).astype(jnp.float32)
        route = jax.vmap(lambda p, v: top_k_routing(p, cfg, capacity, v))
        dispatch, combine = route(probs, valid)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    # token blocks to experts: [G, g, E, C] x [G, g, d] → [G, E, C, d]
    xe = jnp.einsum("gnec,gnd->gecd", dispatch, xg)
    mesh = active_mesh()
    ep = mesh is not None and mesh.shape[AxisName.EXPERT] > 1
    if ep:
        # G stays sharded over the batch axes (the groups came from the
        # sharded batch); only E moves onto the expert axis — declaring
        # G replicated would all-gather every token group onto every
        # data coordinate and duplicate the expert FFN data-ways
        xe = lax.with_sharding_constraint(
            xe, NamedSharding(mesh, P(AxisName.BATCH, AxisName.EXPERT))
        )
    w = params["experts"]
    h = act(jnp.einsum("gecd,edf->gecf", xe, w["wi"].astype(x.dtype))
            + w["bi"].astype(x.dtype)[None, :, None, :])
    ye = jnp.einsum("gecf,efd->gecd", h, w["wo"].astype(x.dtype))
    ye = ye + w["bo"].astype(x.dtype)[None, :, None, :]
    if ep:
        ye = lax.with_sharding_constraint(
            ye, NamedSharding(mesh, P(AxisName.BATCH, AxisName.EXPERT))
        )
    y = jnp.einsum("gnec,gecd->gnd", combine, ye)

    # GShard load-balance loss over REAL tokens only:
    # E * Σ_e (top-1 token fraction)·(mean prob)
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), E, dtype=jnp.float32)
    if valid is None:
        f_e = top1.mean(axis=(0, 1))
        p_e = probs.mean(axis=(0, 1))
    else:
        wt = valid[..., None]
        denom = jnp.maximum(valid.sum(), 1.0)
        f_e = (top1 * wt).sum(axis=(0, 1)) / denom
        p_e = (probs * wt).sum(axis=(0, 1)) / denom
    aux = E * jnp.sum(f_e * p_e)
    return y.reshape(B, T, d), aux


# --- serving: dropless token-choice routing over a held share ---------


def _router_logits(x: jax.Array, router: jax.Array) -> jax.Array:
    """x [N, d] @ router [d, E] in float32 (a bf16 product flips
    near-ties among many experts)."""
    return jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST)


def sigmoid_topk_route(x: jax.Array, router: jax.Array, bias: jax.Array, *,
                       top_k: int, route_norm: bool, route_scale: float):
    """x [N, d] → (picked [N, k] int32 over ALL experts, weights [N, k]
    float32). Scores are `sigmoid(x W_r)` in float32 (a bf16 product
    flips near-ties among 256 experts); `bias` moves which experts are
    PICKED and never the weight a pick gets; the weights are normalised
    over all k picks, held here or not, then scaled."""
    scores = jax.nn.sigmoid(_router_logits(x, router))
    _, picked = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, picked, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return picked.astype(jnp.int32), w * route_scale


def softmax_topk_route(x: jax.Array, router: jax.Array, *, top_k: int):
    """x [N, d] → (picked [N, k] int32 over ALL experts, weights [N, k]
    float32): the `top_k` largest logits `x W_r` (float32) are picked
    and the weights are a softmax over the picked logits alone, which
    is the softmax over all experts renormalised over the picks. No
    bias, no scale. `x` is whatever the model routes on, not
    necessarily what the experts read."""
    top, picked = lax.top_k(_router_logits(x, router), top_k)
    return picked.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


# What `select_grouped_impl` sends to the kernel on a TPU: the shapes
# probed on a v5e (PERF.md section 6, PR 35), where the kernel read the
# touched matrices at 82-91 % of the HBM roofline and `ragged_dot` at
# 31-81 %. Matrices of 3.75 MiB (SmallThinker's) and 18 MiB (Trinity's)
# were measured, nothing smaller: below a few megabytes a group's copy
# no longer hides a grid step, so smaller ones stay where they are.
# Rows from 32 to 3072, up to 64 a group by the static shape (a
# 512-token chunk: 48 a group over SmallThinker's 64 experts, all held;
# 64 over Trinity's 32, an eighth of them held); well past that a
# visit's product outgrows its copy.
GROUPED_KERNEL_MIN_MATRIX_BYTES = 3 << 20
GROUPED_KERNEL_MAX_ROWS_A_GROUP = 64
GROUPED_KERNEL_ROW_GRAIN = 16      # the kernel's smallest row tile


def select_grouped_impl(rows: int, groups: int, k: int, n: int,
                        backend: str, itemsize: int = 2) -> str:
    """"kernel" or "ragged" for one expert step's grouped products,
    from the call's static shape (`rows` sorted (token, pick) rows
    over `groups` held experts, matrices `[k, n]` of `itemsize` bytes
    an element) and the backend. Resolved at trace time, as
    `models.llama.select_paged_attn_impl` is: jit sees one branch. Off
    a TPU the kernel would run through the interpreter, which is an
    oracle and not a path, and every program keeps the text it had.
    On a TPU the kernel takes the calls whose work is streaming the
    matrices: each at least `GROUPED_KERNEL_MIN_MATRIX_BYTES`, at most
    `GROUPED_KERNEL_MAX_ROWS_A_GROUP` rows a group on average, the
    rows in whole row tiles."""
    if backend != "tpu":
        return "ragged"
    streams = k * n * itemsize >= GROUPED_KERNEL_MIN_MATRIX_BYTES
    few_rows = rows <= GROUPED_KERNEL_MAX_ROWS_A_GROUP * groups
    whole_tiles = rows % GROUPED_KERNEL_ROW_GRAIN == 0
    return "kernel" if streams and few_rows and whole_tiles else "ragged"


def _grouped_product(impl: str, sizes: jax.Array, rows: int):
    """`(lhs [rows, K], rhs [G, K, N]) -> [rows, N]` over the groups of
    `sizes`, in the form `impl` names. The kernel's walk of the groups
    is listed here, once for the products that share `sizes`."""
    if impl == "ragged":
        return lambda lhs, rhs: lax.ragged_dot(lhs, rhs, sizes)
    from hyperion_tpu.ops.pallas.grouped_matmul import (
        group_visits,
        grouped_matmul,
        row_tile,
    )
    visits = group_visits(sizes, rows, row_tile(rows))
    return lambda lhs, rhs: grouped_matmul(lhs, rhs, sizes, visits=visits)


def grouped_experts(x: jax.Array, picked: jax.Array, w: jax.Array,
                    params: dict, *, held: tuple[int, int],
                    act=jax.nn.silu):
    """The expert step every router shares: x [N, d], a router's
    `picked` and `w` [N, k] → (y [N, d], load [N, count] int32), the
    part of `sum_k w_k expert_{picked_k}(x)` that the experts `held =
    (first, count)` give, `expert(x) = (act(x G) * (x U)) D`.

    `params`: the held experts' weights stacked, `gate`, `up`
    [count, d, f], `down` [count, f, d], read as stored. Every shape is
    static: the N*k (token, pick) pairs are sorted by expert, pairs
    whose expert lives on another chip sort last, and the three
    products run grouped over the held experts with the per-expert
    counts, in the form `select_grouped_impl` names for the call (the
    grouped-matmul kernel, whose walk of the groups is listed once and
    shared by the three, or `ragged_dot`; operands as stored, float32
    accumulation, the operands' dtype between gate/up and down either
    way): no capacity, no token dropped however uneven the routing,
    and no dense pass over every held expert for every token.
    `load[n, e]` is 1 where token n picked held expert e: the tick's
    counters are sums of it.

    Device scopes (under the caller's): `dispatch`, `experts`,
    `combine`."""
    first, count = held
    N, top_k = picked.shape
    with jax.named_scope("dispatch"):
        local = picked - first
        here = (local >= 0) & (local < count)
        flat = jnp.where(here, local, count).reshape(-1)       # [N*k]
        order = jnp.argsort(flat, stable=True)
        load = jnp.sum(jax.nn.one_hot(
            jnp.where(here, local, -1), count, dtype=jnp.int32), axis=1)
        sizes = jnp.sum(load, axis=0)                           # [count]
    with jax.named_scope("experts"):
        # the rows in expert order, then the three grouped products:
        # the kernel (a Pallas call carries this scope) or the
        # compiler's own `ragged_dot` custom calls, which a trace names
        # after what they read (obs/xprof.py)
        xs = x[order // top_k]                                  # [N*k, d]
        product = _grouped_product(
            select_grouped_impl(N * top_k, count, *params["gate"].shape[1:],
                                jax.default_backend(),
                                params["gate"].dtype.itemsize),
            sizes, N * top_k)
        gate = product(xs, params["gate"])
        up = product(xs, params["up"])
        ys = product(act(gate) * up, params["down"])
    with jax.named_scope("combine"):
        # back to (token, pick) order; rows past the held groups carry
        # whatever the grouped product left there and are zeroed
        back = jnp.argsort(order)
        ys = ys[back].reshape(N, top_k, -1).astype(jnp.float32)
        y = jnp.sum(jnp.where(here[..., None], ys * w[..., None], 0.0), axis=1)
    return y.astype(x.dtype), load


def dropless_moe(x: jax.Array, params: dict, *, held: tuple[int, int],
                 top_k: int, route_norm: bool = True,
                 route_scale: float = 1.0):
    """The routed part of `afmoe`'s expert layer: the sigmoid router on
    x [N, d], then `grouped_experts` (SwiGLU) on the same x, for the
    experts `held` → (y [N, d], load [N, count] int32). `params`:
    `router` [d, E], `expert_bias` [E] and the stacked `gate`, `up`,
    `down`. Device scopes: `router`, then the expert step's."""
    with jax.named_scope("router"):
        picked, w = sigmoid_topk_route(
            x, params["router"], params["expert_bias"], top_k=top_k,
            route_norm=route_norm, route_scale=route_scale)
    return grouped_experts(x, picked, w, params, held=held)
