"""Upstage `solar_open2` (Solar Open 2 250B-A15B): a hybrid decoder of
linear-attention layers with a recurrent state beside gated softmax
layers, sparse experts in every layer.

Source: `huggingface.co/upstage/Solar-Open2-250B` `config.json`
(`model_type: solar_open2`). Every layer outside `gqa_layers` is Kimi
Delta Attention (KDA: Kimi Linear, arXiv:2510.26692), the row's
`linear_attn_config` and `kda_*` keys; what the row does not carry is
the configuration file's `assumed`. With `x` the residual stream:

  * Model: `h = embed(ids)`; the layers; `logits = lm_head(RMSNorm(h))`,
    untied. Layer: `h = h + mix(input_norm(h))`;
    `h = h + moe(pre_mlp_norm(h))`.
  * KDA layer (`mix` on a `recurrent` layer), u the normed input, H
    heads of K = V channels: `q, k, v = SiLU(conv4(W u))` per channel
    (a causal depthwise conv over the last four positions, no bias);
    q and k L2-normed per head, q times 1/sqrt(K);
    `g = -exp(A_log[h]) * softplus(W_f2 W_f1 u + dt_bias)` per head and
    key channel, `alpha = exp(g)`; `beta = 2 sigmoid(W_b u)` per head;
    `S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T`,
    `S_-1 = 0`, `o_t = S_t^T q_t`; out = `W_o(RMSNorm_head(o) *
    sigmoid(W_g2 W_g1 u))`.
  * GQA layer (`mix` on a `full` layer): causal softmax with NO
    positions (NoPE), no QK-norm; `out = o_proj(a * sigmoid(gate_proj(u)))`.
  * Experts: `models/afmoe.py`'s expert layer (`AfmoeMoE`: a shared
    SwiGLU expert plus `ops/moe.py` `dropless_moe` over the held share,
    sigmoid scores with a selection bias, normalised over all picks),
    at `route_scale` 1.

**Two forms of the recurrence, the same numbers.** A window of several
positions (a full forward, a prefill bucket, a chunk) runs the CHUNKED
form (`chunked_delta_rule`): within a chunk of `SCAN_CHUNK` positions
the decays apply as differences of cumulative log-decays, every factor
at most 1, so nothing overflows in float32 however strong the decay;
the state passes from chunk to chunk in float32. A one-position window
(the decode tick) runs the STEP form (`delta_rule_step`): read S, decay
it, update it, read the output off it. On a TPU the tick's step is the
kernel `ops/pallas/state_update.py` over the rows it advances alone,
in place (`select_state_update_impl`).

**Serving.** The call surface is the engine's: `apply(variables, ids,
cache=, cache_index=, block_tables=, state_len=)`. A `full` layer's
cache is a paged pool as `models/afmoe.py`'s full layer has it; a
`recurrent` layer's is ONE ROW A SLOT of the arrays `cfg.recurrent_state`
declares (S in float32, the conv's last `conv_size - 1` inputs in the
compute dtype; `llama.init_paged_cache`). `block_tables["recurrent"]`
[B] names the state row of each window row (a prefill or a chunk: the
slot's); without it window row b is state row b (the tick).
`state_len` [B] says how many leading positions of each row advance the
state (a prompt's true length in its bucket, 0 for a lane the tick does
not advance; None: all of them). A position at or past it leaves S and
the conv tail as they were, and a prefill or a chunk that starts at
position 0 (`cache_index`) starts from zero state (the tick starts no
row: a row is live only after its prefill). Without a cache the call
is one full forward from zero state (what the tests hold against the
reference).

Device scopes: `layer_*/full/attn/{qkv_proj, kv_write, kv_read,
attention, gate, o_proj}`, `layer_*/recurrent/{qkv_proj, conv, gates,
scan | step, out}`, `layer_*/moe/{router, dispatch, experts, combine,
shared}`, `lm_head`. A decode tick that asks for them
(`mutable=["tick_stats"]`) gets each expert layer's `expert_load` and
each recurrent layer's `state_rows`: the rows whose state it advanced.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from hyperion_tpu.models.afmoe import AfmoeMoE
from hyperion_tpu.models.llama import (
    RMSNorm,
    _grouped_cache_attention,
    paged_kv_write,
    paged_read,
    select_paged_attn_impl,
)

FULL, RECURRENT = "full", "recurrent"
# positions a chunk of the chunked form: its pairwise decays are
# [B, H, C, C, K] float32 (33.5 MB a chunk at 64 heads of 128)
SCAN_CHUNK = 32
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    d_model: int = 4096
    n_heads: int = 64                 # the GQA layers' query heads
    n_kv_heads: int = 8
    head_dim: int = 128
    kda_heads: int = 64               # linear_attn_config num_heads
    kda_head_dim: int = 128           # its head_dim (keys and values)
    conv_size: int = 4                # short_conv_kernel_size
    gate_rank: int = 128              # the f and g projections' inner width
    moe_ff_dim: int = 1280            # every expert's, and the shared one's
    layer_types: tuple[str, ...] = (FULL, RECURRENT, RECURRENT, RECURRENT)
    n_experts: int = 320              # the router's width
    experts_held: tuple[int, int] = (0, 320)   # (first, count) held here
    top_k: int = 8
    n_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 1.0
    max_len: int = 8192
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        bad = set(self.layer_types) - {FULL, RECURRENT}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(
                f"experts_held {self.experts_held} is not a run of the "
                f"router's {self.n_experts} experts")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def layer_kinds(self) -> tuple[tuple[str, int], ...]:
        """`(kind, window)` per layer, what the serving cache is built
        from: a GQA layer keeps every position in the paged pool, a KDA
        layer a state row a slot (`recurrent_state`)."""
        return tuple((t, 0) for t in self.layer_types)

    @property
    def recurrent_state(self) -> dict[str, tuple[tuple[int, ...], str]]:
        """What one slot holds of a recurrent layer, by name: (shape,
        dtype). The engine allocates `[slots, *shape]` of each and
        reads no width of its own."""
        H, K = self.kda_heads, self.kda_head_dim
        return {"S": ((H, K, K), "float32"),
                "conv": ((self.conv_size - 1, 3 * H * K), self.dtype)}

    def tick_state_update(self) -> str:
        """The form ("kernel" or "dense") of the decode tick's state
        update, resolved from the tick's shape and the process's
        backend: what each KDA layer asks at trace time, and the engine
        asks for the tick record's `state_rows_visited`."""
        K = self.kda_head_dim
        return select_state_update_impl(1, True, K, K,
                                        jax.default_backend())

    @property
    def expert_step(self) -> dict:
        """The static shape of an expert layer's `grouped_experts` call
        but for its rows: what `ops.moe.select_grouped_impl` is asked
        with, here and by the engine's counters."""
        return {"layers": self.n_layers,
                "groups": self.experts_held[1], "top_k": self.top_k,
                "k": self.d_model, "n": self.moe_ff_dim,
                "itemsize": self.compute_dtype.itemsize}


def solar_open2_tiny_config(**kw) -> SolarOpen2Config:
    """Test-sized, with every mechanism present: one whole period (one
    GQA layer, three KDA layers), a held share of the experts."""
    base = dict(
        vocab_size=96, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        kda_heads=2, kda_head_dim=8, gate_rank=8, moe_ff_dim=16,
        n_experts=8, experts_held=(0, 8), top_k=2, max_len=128,
        dtype="float32",
    )
    base.update(kw)
    return SolarOpen2Config(**base)


def _dense(c: SolarOpen2Config, features, name, axis=-1):
    return nn.DenseGeneral(
        features=features, axis=axis, use_bias=False, dtype=c.compute_dtype,
        kernel_init=nn.initializers.normal(0.02), name=name)


def _l2_norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


# The KDA layer's and the GQA layer's equations that a test of the
# benchmark's check plants a fault in (tests/bench_harness/
# solar_open2_faults.py): looked up when a program is traced.


def short_conv(xs, w):
    """xs [B, taps + T, C] (the conv's last inputs, then the window's),
    w [taps + 1, C] → [B, T, C] float32: the causal depthwise conv, tap
    j on position t - taps + j."""
    T = xs.shape[1] - w.shape[0] + 1
    return sum(xs[:, j:j + T].astype(jnp.float32) * w[j].astype(jnp.float32)
               for j in range(w.shape[0]))


def log_decay(f, a_log, dt_bias):
    """f [B, T, H, K] float32 → g = -exp(A_log[h]) softplus(f + dt_bias)."""
    return -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + dt_bias)


def update_strength(b):
    """b [B, T, H] float32 → beta in (0, 2): negative eigenvalues."""
    return 2.0 * jax.nn.sigmoid(b)


def advancing(lens, T: int):
    """[B, T]: the positions of each row that advance its state, the
    first `lens[b]`."""
    return jnp.arange(T)[None, :] < lens[:, None]


def positions(q, k, base):
    """What the GQA layer does with positions: nothing (NoPE)."""
    return q, k


# ------------------------------------------------------------ the recurrence

# The state-update kernel's block is `[heads, K, V]` float32: K on the
# sublanes and V on the lanes, both in whole lane tiles
STATE_KERNEL_WIDTH_GRAIN = 128


def select_state_update_impl(window: int, slot_rows: bool, k: int, v: int,
                             backend: str) -> str:
    """"kernel" or "dense" for one call's state update, from its static
    shape (`window` positions a row; `slot_rows`: the window's rows are
    the cache's state rows in order, a cache and no `rows`; keys of `k`
    and values of `v` channels) and the backend. Resolved at trace
    time, as `ops.moe.select_grouped_impl` is: jit sees one branch. The
    kernel (`ops/pallas/state_update.py`) takes the decode tick's form
    on a TPU at widths in whole lane tiles: it updates the rows the
    tick advances in place and never touches the others. Everything
    else keeps `delta_rule_step` and its select, or the chunked scan:
    off a TPU the kernel would run through the interpreter, which is
    an oracle and not a path."""
    if backend != "tpu" or window != 1 or not slot_rows:
        return "dense"
    whole = k % STATE_KERNEL_WIDTH_GRAIN == 0 \
        and v % STATE_KERNEL_WIDTH_GRAIN == 0
    return "kernel" if whole else "dense"


def delta_rule_step(q, k, v, g, beta, S):
    """One position of the gated delta rule. q, k [B, H, K] (q scaled),
    v [B, H, V], g [B, H, K] log-decays, beta [B, H], S [B, H, K, V]
    float32 → (o [B, H, V], S'). Elementwise products and sums, no
    matrix product: float32 throughout. `o = S'^T q` is read as
    `(aS)^T q + u (k.q)`, so both sums are over the decayed state."""
    Sd = jnp.exp(g)[..., None] * S
    u = beta[..., None] * (v - jnp.sum(k[..., None] * Sd, -2))
    o = jnp.sum(q[..., None] * Sd, -2) + u * jnp.sum(q * k, -1)[..., None]
    return o, Sd + k[..., None] * u[..., None, :]


def chunked_delta_rule(q, k, v, g, beta, S, chunk: int = SCAN_CHUNK):
    """The gated delta rule over T positions a chunk at a time. q, k
    [B, T, H, K], v [B, T, H, V], g [B, T, H, K], beta [B, T, H], S
    [B, H, K, V] float32 → (o [B, T, H, V], S after the last position).
    A position with g = 0 and beta = 0 leaves S as it was (the caller's
    padding).

    Within a chunk, with cumulative log-decays `G_t` and the update
    written `S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T`, the `u` solve
    `(I + A) U = beta (V - (e^G k) S_0)` with `A_ts = beta_t sum_c
    k_t[c] k_s[c] e^(G_t[c] - G_s[c])` (s < t); then `o = (e^G q) S_0 +
    P U`, `P_ts = sum_c q_t[c] k_s[c] e^(G_t[c] - G_s[c])` (s <= t), and
    `S_C = e^(G_C) S_0 + (e^(G_C - G) k)^T U`. Every exponent is a
    difference in which the later position comes first, so every factor
    lies in [0, 1]."""
    B, T, H, K = k.shape
    C = min(chunk, T)
    n = -(-T // C)
    pad = n * C - T

    def chunks(x):
        x = x.astype(jnp.float32)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        # [n, B, H, C, ...]
        x = x.reshape(B, n, C, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    qs, ks, vs, gs, bs = map(chunks, (q, k, v, g, beta))
    later = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]     # s <= t
    hi = lax.Precision.HIGHEST

    def one(S0, xs):
        qc, kc, vc, gc, bc = xs
        G = jnp.cumsum(gc, axis=-2)                            # [B, H, C, K]
        diff = G[..., :, None, :] - G[..., None, :, :]         # [.., t, s, K]
        decay = jnp.exp(jnp.where(later[..., None], diff, -jnp.inf))
        kk = jnp.einsum("bhtk,bhsk,bhtsk->bhts", kc, kc, decay,
                        precision=hi)
        A = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                      bc[..., None] * kk, 0.0)
        P = jnp.einsum("bhtk,bhsk,bhtsk->bhts", qc, kc, decay, precision=hi)
        eG = jnp.exp(G)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bhtk,bhkv->bhtv", eG * kc, S0, precision=hi))
        U = lax.linalg.triangular_solve(
            jnp.eye(C, dtype=A.dtype) + A, rhs, left_side=True, lower=True,
            unit_diagonal=True)
        o = jnp.einsum("bhtk,bhkv->bhtv", eG * qc, S0, precision=hi) \
            + jnp.einsum("bhts,bhsv->bhtv", P, U, precision=hi)
        last = G[..., -1:, :]
        S1 = jnp.exp(last[..., 0, :])[..., None] * S0 + jnp.einsum(
            "bhsk,bhsv->bhkv", jnp.exp(last - G) * kc, U, precision=hi)
        return S1, o

    S, o = lax.scan(one, S.astype(jnp.float32), (qs, ks, vs, gs, bs))
    # [n, B, H, C, V] -> [B, T, H, V]
    o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(B, n * C, H, -1)
    return o[:, :T], S


# ------------------------------------------------------------------ layers


class KimiDeltaAttention(nn.Module):
    """The KDA layer over its state rows (module docstring)."""
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, u, cache=None, cache_index=None, rows=None,
                 state_len=None):
        c = self.cfg
        H, K = c.kda_heads, c.kda_head_dim
        B, T = u.shape[0], u.shape[1]
        taps = c.conv_size - 1
        init = nn.initializers.normal(0.02)
        with jax.named_scope("qkv_proj"):
            x = jnp.concatenate([
                _dense(c, H * K, name)(u)
                for name in ("q_proj", "k_proj", "v_proj")], -1)
        lens = jnp.full((B,), T, jnp.int32) if state_len is None \
            else jnp.asarray(state_len, jnp.int32)
        valid = advancing(lens, T)                               # [B, T]
        # the state's reads and writes are the scan's (a prompt's window)
        # or the step's (the tick)
        state_scope = "step" if T == 1 else "scan"
        impl = select_state_update_impl(
            T, cache is not None and rows is None, K, K,
            jax.default_backend())
        with jax.named_scope(state_scope):
            if cache is None:
                S0 = jnp.zeros((B, H, K, K), jnp.float32)
                tail0 = jnp.zeros((B, taps, 3 * H * K), x.dtype)
                S_in, tail_in = S0, tail0
            elif rows is None:
                # the tick: every slot's rows in order. It never starts a
                # row (a row is live only after its prefill), so it reads
                # them as they are
                S0, tail0 = cache["S"], cache["conv"]
                S_in, tail_in = S0, tail0.astype(x.dtype)
            else:
                # a prompt's window on its slot's rows, from zero state
                # where it starts at position 0
                S0, tail0 = cache["S"][rows], cache["conv"][rows]
                fresh = jnp.broadcast_to(
                    jnp.asarray(cache_index, jnp.int32) == 0, (B,))
                S_in = jnp.where(fresh[:, None, None, None], 0.0, S0)
                tail_in = jnp.where(fresh[:, None, None], 0, tail0) \
                    .astype(x.dtype)
        with jax.named_scope("conv"):
            w = self.param("conv", init, (c.conv_size, 3 * H * K),
                           c.compute_dtype)
            xs = jnp.concatenate([tail_in, x], 1)       # [B, taps + T, .]
            y = jax.nn.silu(short_conv(xs, w))
            # the last `taps` inputs at or before each row's last position
            tail = jax.vmap(lambda r, n: lax.dynamic_slice_in_dim(
                r, n, taps, axis=0))(xs, lens)
            q, k, v = (part.reshape(B, T, H, K)
                       for part in jnp.split(y, 3, axis=-1))
        with jax.named_scope("gates"):
            q = _l2_norm(q) * (1.0 / np.sqrt(K))
            k = _l2_norm(k)
            f = _dense(c, (H, K), "f_b_proj")(
                _dense(c, c.gate_rank, "f_a_proj")(u)).astype(jnp.float32)
            a_log = self.param("A_log", nn.initializers.zeros, (H,),
                               jnp.float32)
            dt_bias = self.param("dt_bias", nn.initializers.zeros,
                                 (H * K,), jnp.float32)
            g = log_decay(f, a_log, dt_bias.reshape(H, K))
            beta = update_strength(
                _dense(c, H, "b_proj")(u).astype(jnp.float32))
            # positions past a row's length neither decay nor update
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        new_cache = None
        with jax.named_scope(state_scope):
            if impl == "kernel":
                # the rows the tick advances, each read and written once
                # in place; the others' state is never touched, and
                # their output holds no number: 0 is selected there
                from hyperion_tpu.ops.pallas.state_update import (
                    delta_rule_step_rows,
                )

                adv = valid.any(axis=1)
                o, S1 = delta_rule_step_rows(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], S_in,
                    adv)
                o = jnp.where(adv[:, None, None], o, 0.0)[:, None]
            elif T == 1:
                o, S1 = delta_rule_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                        beta[:, 0], S_in)
                o = o[:, None]
            else:
                o, S1 = chunked_delta_rule(q, k, v, g, beta, S_in)
            if cache is not None:
                if impl == "dense":
                    # a row that advanced nothing keeps its state bit
                    # for bit
                    adv = valid.any(axis=1)
                    S1 = jnp.where(adv[:, None, None, None], S1, S0)
                tail = jnp.where(adv[:, None, None],
                                 tail.astype(tail0.dtype), tail0)
                if rows is None:
                    new_cache = {"S": S1, "conv": tail}
                else:
                    new_cache = {"S": cache["S"].at[rows].set(S1),
                                 "conv": cache["conv"].at[rows].set(tail)}
                self.sow("tick_stats", "state_rows",
                         jnp.sum(adv.astype(jnp.int32)))
        with jax.named_scope("out"):
            o = RMSNorm(c.norm_eps, jnp.float32, name="o_norm")(o)
            gate = _dense(c, (H, K), "g_b_proj")(
                _dense(c, c.gate_rank, "g_a_proj")(u))
            o = (o * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(
                c.compute_dtype)
            out = _dense(c, c.d_model, "o_proj", axis=(-2, -1))(o)
        return out, new_cache


class GatedAttention(nn.Module):
    """The GQA layer: causal, no positions, no QK-norm, gated output."""
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, u, cache=None, cache_index=None, block_tables=None):
        c = self.cfg
        H, Hkv, D = c.n_heads, c.n_kv_heads, c.head_dim
        B, T = u.shape[0], u.shape[1]
        with jax.named_scope("qkv_proj"):
            q = _dense(c, (H, D), "q_proj")(u)
            k = _dense(c, (Hkv, D), "k_proj")(u)
            v = _dense(c, (Hkv, D), "v_proj")(u)
        idx = jnp.asarray(0 if cache is None else cache_index, jnp.int32)
        base = idx if idx.ndim == 1 else jnp.full((B,), idx, jnp.int32)
        q, k = positions(q, k, base)
        if cache is None:
            with jax.named_scope("attention"):
                pos = jnp.arange(T)
                mask = pos[None, :] <= pos[:, None]
            a, new_cache = _grouped_cache_attention(
                q, k, v, mask, H // Hkv), None
        else:
            table = block_tables[FULL]
            ck, cv = paged_kv_write(cache, k, v, table, base)
            impl = select_paged_attn_impl(
                T, H // Hkv, jax.default_backend())
            a = paged_read(impl, q, ck, cv, table, base)
            new_cache = {"k": ck, "v": cv}
        with jax.named_scope("gate"):
            a = a * jax.nn.sigmoid(_dense(c, (H, D), "gate_proj")(u))
        with jax.named_scope("o_proj"):
            out = _dense(c, c.d_model, "o_proj", axis=(-2, -1))(a)
        return out, new_cache


class SolarBlock(nn.Module):
    cfg: SolarOpen2Config
    index: int

    @nn.compact
    def __call__(self, x, cache=None, cache_index=None, block_tables=None,
                 state_len=None):
        c = self.cfg

        def norm(name):
            return RMSNorm(c.norm_eps, c.compute_dtype, name=name)

        h = norm("input_norm")(x)
        if c.layer_types[self.index] == FULL:
            # the parent scope says the layer's kind (obs/xprof.py)
            with jax.named_scope(FULL):
                a, cache = GatedAttention(c, name="attn")(
                    h, cache, cache_index, block_tables)
        else:
            rows = None if block_tables is None \
                else block_tables.get(RECURRENT)
            a, cache = KimiDeltaAttention(c, name=RECURRENT)(
                h, cache, cache_index, rows, state_len)
        x = x + a
        return x + AfmoeMoE(c, name="moe")(norm("pre_mlp_norm")(x)), cache


class SolarOpen2(nn.Module):
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, input_ids, cache=None, cache_index=None,
                 block_tables=None, state_len=None):
        """input_ids int32 [B, T] → logits fp32 [B, T, vocab], or with
        `cache` (paged pools and state rows, by layer) → (logits,
        updated cache)."""
        c = self.cfg
        x = nn.Embed(
            c.vocab_size, c.d_model, dtype=c.compute_dtype,
            embedding_init=nn.initializers.normal(0.02), name="embed_tokens",
        )(input_ids)
        new_cache = []
        for i in range(c.n_layers):
            x, layer_cache = SolarBlock(c, i, name=f"layer_{i}")(
                x, None if cache is None else cache[i], cache_index,
                block_tables, state_len)
            new_cache.append(layer_cache)
        x = RMSNorm(c.norm_eps, c.compute_dtype, name="final_norm")(x)
        with jax.named_scope("lm_head"):
            logits = _dense(c, c.vocab_size, "lm_head")(x)
            logits = logits.astype(jnp.float32)
        return logits if cache is None else (logits, new_cache)

    def init_params(self, rng: jax.Array, batch: int = 1,
                    seq: int | None = None):
        ids = jnp.zeros((batch, seq or 8), jnp.int32)
        return self.init(rng, ids)["params"]
