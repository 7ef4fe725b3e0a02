"""ByteDance Ouro (2.6B) — a looped decoder: the same stack of layers
applied `total_ut_steps` times a token over SHARED weights, in-tree.

Source: `huggingface.co/ByteDance/Ouro-2.6B` `config.json`
(`model_type` `ouro`) and its public modeling file; what the config's
keys do not carry is listed under `assumed` in the benchmark's
configuration file. It shares `RMSNorm`, `apply_rope`, the paged write,
the two paged reads and the choice between them with `llama`, and the
rotary table computed in the program with `afmoe`. With `h` the stream
`[T, d]`:

  * Model: `h = embed(ids)`; `for t in 0..steps-1: { for l in layers:
    h = layer_l(h); h = norm(h); g_t = sigmoid(h w_gate + b_gate) }`;
    `logits = lm_head(h)`, untied. The same layers' weights serve every
    step; the ONE final RMSNorm is applied at the end of every step and
    its output feeds the next.
  * Layer, four RMSNorms (sandwich): `h1 = h + norm_2(attn(norm_1(h)))`;
    `h2 = h1 + norm_4(mlp(norm_3(h1)))`; `mlp(x) = (silu(x G) * (x U)) D`.
  * Attention: q, k, v, o without bias, plain multi-head in the
    published model (`rep` 1); rotary positions (half-split pairing) on
    q and k at the token's position, the same in every step; causal
    softmax over every earlier position.
  * Exit: `p_t = g_t * prod_{s<t}(1 - g_s)`, the last step taking the
    rest; a token leaves at the first step whose cumulative `p` reaches
    `early_exit_threshold`. At the published 1.0 that is the last step
    for every token: the number of steps is static. Below 1.0 the depth
    differs by token, which nothing here runs: the serving path raises.

**The loops are loops in the program.** The steps are one `lax.scan`
that carries the stream and, when serving, the pools; inside it the
layers are `lax.scan`s over STACKED weights, `pool_layers` layers a
scan (`n_layers / pool_layers` scans, one after the other). A program
so holds `n_layers / pool_layers` copies of the layer's body whatever
`total_ut_steps` and `n_layers` say: unrolled, the 48 layers took the
TPU compiler 109 s a prefill bucket (PERF.md section 6, PR 34). The
weights of a scan are leaves `[pool_layers, ...]` under
`loop/layers_<g>`; a kernel is a matrix (q, k, v `[heads * D, d]`, the
rest `[in, out]`), the activations are regrouped by head.

**Serving.** The keys and values of layer `l` in step `t` come from
step `t`'s stream, so exact incremental decoding keeps `steps x layers`
caches. The config states `cache_steps`, `pool_layers` and their
product `cache_segments`; `llama.init_paged_cache` then builds one pair
of pools a scan, `[cache_segments * NB, Hkv, bs, D]`, segment `i *
cache_steps + t` holding the keys and values of the scan's layer `i` in
step `t`, all behind ONE block table: a block id names `bs` positions
in every step and layer. (One array for all the layers would do but for
its size: 4.5 GiB and 2.4e9 values at the published widths and 385
blocks, past what 32 signed bits index. `pool_layers` is chosen so that
an array stays under 2 GiB.) Inside the loops `llama.segment_shift`
turns (layer, step) into what `paged_kv_write` and `paged_read` add to
the table's entries. The call surface is the engine's:
`apply(variables, ids, cache=, cache_index=, block_tables=)`. Without a
cache the call is one full forward that also returns the exit
distribution `p` `[B, T, steps]`.

Device scopes: `Ouro/embed_tokens`, `Ouro/rope_table`,
`Ouro/loop/layer/attn/{qkv_proj, rope, kv_write, kv_read, attention,
o_proj, post_norm}`, `Ouro/loop/layer/mlp/{gate_up, down, post_norm}`,
`Ouro/loop/{step_norm, exit_gate}`, `Ouro/lm_head` (a scope is summed
over the loops' iterations by `obs profile --summarize`).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp

from hyperion_tpu.models.afmoe import device_rope_table
from hyperion_tpu.models.llama import (
    _grouped_cache_attention,
    apply_rope,
    paged_kv_write,
    paged_read,
    rms_norm,
    segment_shift,
    select_paged_attn_impl,
)


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    ff_dim: int = 5632
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    max_len: int = 65536
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    # layers a `lax.scan` runs over stacked weights, and whose caches
    # share one pair of pool arrays (0 = all of them: `__post_init__`
    # writes the number in). Part of the weights' tree: a scan's leaves
    # are `[pool_layers, ...]`
    pool_layers: int = 0
    # "auto" | "gather" | "pallas": `LlamaConfig.paged_attn_impl`
    paged_attn_impl: str = "auto"
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not self.pool_layers:
            object.__setattr__(self, "pool_layers", self.n_layers)
        if self.n_layers % self.pool_layers:
            raise ValueError(f"pool_layers {self.pool_layers} does not "
                             f"divide n_layers {self.n_layers}")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def layer_kinds(self) -> tuple[tuple[str, int], ...]:
        """Per WEIGHT layer, what it keeps in the serving cache: every
        layer keeps every position, in each of `cache_steps` segments."""
        return (("full", 0),) * self.n_layers

    @property
    def cache_steps(self) -> int:
        """How many caches a weight layer keeps behind the one block
        table: one a step of the loop. Asked by whatever serves the
        model (the engine at construction, the model's cached call),
        so this is where a per-token depth is refused."""
        if self.early_exit_threshold < 1.0:
            raise ValueError(
                f"early_exit_threshold {self.early_exit_threshold} < 1.0: "
                "tokens would leave the loop at different steps, and "
                "per-token depth is not run here (the decode tick runs "
                "every live row through the same program). Serve with "
                "the published 1.0: all total_ut_steps steps, statically.")
        return self.total_ut_steps

    @property
    def cache_segments(self) -> int:
        """Caches one pool array holds: a step's of each of its layers."""
        return self.cache_steps * self.pool_layers

    def paged_attn_for(self, window: int) -> str:
        if self.paged_attn_impl != "auto":
            return self.paged_attn_impl
        return select_paged_attn_impl(
            window, self.n_heads // self.n_kv_heads, jax.default_backend())


def ouro_tiny_config(**kw) -> OuroConfig:
    """Test-sized: four layers in two scans, four steps, plain
    multi-head."""
    base = dict(
        vocab_size=96, d_model=32, n_layers=4, n_heads=4, n_kv_heads=4,
        head_dim=8, ff_dim=48, total_ut_steps=4, pool_layers=2, max_len=64,
        dtype="float32",
    )
    base.update(kw)
    return OuroConfig(**base)


class _Leaves(nn.Module):
    """Declares leaves and hands them back: the weights of what runs
    inside the loops, where no module can be called."""
    leaves: tuple       # ((leaf, shape, initialiser), ...)

    @nn.compact
    def __call__(self):
        return {leaf: self.param(leaf, init, shape, jnp.float32)
                for leaf, shape, init in self.leaves}


def _leaf(name, leaf, shape, init=nn.initializers.normal(0.02)):
    return _Leaves(((leaf, shape, init),), name=name)()


class OuroLayers(nn.Module):
    """The weights of one scan: `pool_layers` layers stacked
    (`<module>/kernel` `[n, in, out]`, but q, k, v `[n, out, in]`;
    `<norm>/weight` `[n, d]`)."""
    cfg: OuroConfig

    @nn.compact
    def __call__(self):
        c = self.cfg
        d, f, n = c.d_model, c.ff_dim, c.pool_layers
        q, kv = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
        w = {name: _leaf(name, "kernel", (n, *shape)) for name, shape
             in (("q_proj", (q, d)), ("k_proj", (kv, d)),
                 ("v_proj", (kv, d)), ("o_proj", (q, d)),
                 ("gate_proj", (d, f)), ("up_proj", (d, f)),
                 ("down_proj", (f, d)))}
        w.update({name: _leaf(name, "weight", (n, d), nn.initializers.ones)
                  for name in ("input_norm", "attn_post_norm",
                               "pre_mlp_norm", "mlp_post_norm")})
        return w


class OuroLoop(nn.Module):
    """The weights of the loop: `layers_<g>` a scan, the one final norm
    (`step_norm`) and the exit gate."""
    cfg: OuroConfig

    @nn.compact
    def __call__(self):
        c = self.cfg
        return {
            "layers": [OuroLayers(c, name=f"layers_{g}")()
                       for g in range(c.n_layers // c.pool_layers)],
            "step_norm": _leaf("step_norm", "weight", (c.d_model,),
                               nn.initializers.ones),
            "exit_gate": _Leaves(
                (("kernel", (c.d_model, 1), nn.initializers.normal(0.02)),
                 ("bias", (1,), nn.initializers.zeros)),
                name="exit_gate")(),
        }


def _attention(c: OuroConfig, w, u, rope_table, pool, cache_index,
               block_tables, segment):
    """`u` [B, T, d], the layer's normed input, through one layer's
    attention; `pool` the scan's pools (or None: one full forward) and
    `segment` this (layer, step)'s place in them."""
    H, Hkv, D = c.n_heads, c.n_kv_heads, c.head_dim
    B, T = u.shape[0], u.shape[1]
    dt = c.compute_dtype

    def proj(x, name):
        # q, k, v kernels lie `[out, in]`, as the source's checkpoints
        # have every matrix: the layout the TPU compiler wants for these
        # three products. Handed `[in, out]` it transposes them, and
        # inside a loop it hoists the transposed copies out of the loop:
        # 24 MiB a layer, 1.13 GiB in all, held for the whole program
        return jnp.einsum("btd,od->bto", x, w[name]["kernel"].astype(dt))

    # the kernels are matrices and the activations are regrouped by head
    with jax.named_scope("qkv_proj"):
        q = proj(u, "q_proj").reshape(B, T, H, D)
        k = proj(u, "k_proj").reshape(B, T, Hkv, D)
        v = proj(u, "v_proj").reshape(B, T, Hkv, D)
    offset = 0 if pool is None else cache_index
    with jax.named_scope("rope"):
        q = apply_rope(q, rope_table, offset)
        k = apply_rope(k, rope_table, offset)
    if pool is None:
        with jax.named_scope("attention"):
            pos = jnp.arange(T)
            mask = pos[None, :] <= pos[:, None]
        a = _grouped_cache_attention(q, k, v, mask, H // Hkv)
    else:
        idx = jnp.asarray(cache_index, jnp.int32)
        base = idx if idx.ndim == 1 else jnp.full((B,), idx, jnp.int32)
        shift = segment_shift(pool["k"], segment, c.cache_segments)
        ck, cv = paged_kv_write(pool, k, v, block_tables, base, shift)
        a = paged_read(c.paged_attn_for(T), q, ck, cv, block_tables,
                       base, shift=shift)
        pool = {"k": ck, "v": cv}
    with jax.named_scope("o_proj"):
        out = jnp.dot(a.reshape(B, T, H * D),
                      w["o_proj"]["kernel"].astype(dt))
    with jax.named_scope("post_norm"):
        out = rms_norm(out, w["attn_post_norm"]["weight"], c.norm_eps, dt)
    return out, pool


def _mlp(c: OuroConfig, w, x):
    dt = c.compute_dtype
    with jax.named_scope("gate_up"):
        gate = jnp.dot(x, w["gate_proj"]["kernel"].astype(dt))
        up = jnp.dot(x, w["up_proj"]["kernel"].astype(dt))
    with jax.named_scope("down"):
        y = jnp.dot(nn.silu(gate) * up, w["down_proj"]["kernel"].astype(dt))
    with jax.named_scope("post_norm"):
        return rms_norm(y, w["mlp_post_norm"]["weight"], c.norm_eps, dt)


def _block(c: OuroConfig, w, h, rope_table, pool, cache_index, block_tables,
           segment):
    """The sandwich block: a norm before and a norm after each of
    attention and the MLP, the two after (`post_norm`) on what is added
    to the stream."""
    dt = c.compute_dtype
    with jax.named_scope("attn"):
        a, pool = _attention(
            c, w, rms_norm(h, w["input_norm"]["weight"], c.norm_eps, dt),
            rope_table, pool, cache_index, block_tables, segment)
    h = h + a
    with jax.named_scope("mlp"):
        h = h + _mlp(
            c, w, rms_norm(h, w["pre_mlp_norm"]["weight"], c.norm_eps, dt))
    return h, pool


def exit_distribution(gates):
    """`g` [steps, ...] -> `p` [..., steps]: `p_t = g_t prod_{s<t} (1 -
    g_s)`, the last step taking what is left, so that `p` sums to 1."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    p = jnp.concatenate([(gates * before)[:-1], before[-1:]])
    return jnp.moveaxis(p, 0, -1)


class Ouro(nn.Module):
    cfg: OuroConfig

    @nn.compact
    def __call__(self, input_ids, cache=None, cache_index=None,
                 block_tables=None):
        """input_ids int32 [B, T] -> (logits fp32 [B, T, vocab], exit
        distribution [B, T, steps]), or with `cache` (paged: a pair of
        pools a scan, `cache_segments` segments each) -> (logits,
        updated cache)."""
        c = self.cfg
        dt = c.compute_dtype
        steps = c.total_ut_steps if cache is None else c.cache_steps
        if cache is not None and block_tables is None:
            raise ValueError("models/ouro.py serves through the paged "
                             "cache only: pass block_tables")
        if isinstance(block_tables, dict):
            # the engine hands the tables by layer kind; every layer
            # here is `full`
            block_tables = block_tables["full"]
        x = nn.Embed(
            c.vocab_size, c.d_model, dtype=dt,
            embedding_init=nn.initializers.normal(0.02), name="embed_tokens",
        )(input_ids)
        with jax.named_scope("rope_table"):
            rope = device_rope_table(c.head_dim, c.max_len, c.rope_theta)
        w = OuroLoop(c, name="loop")()

        def layer(t, carry, xs):
            h, pool = carry
            wl, i = xs
            with jax.named_scope("layer"):
                return _block(c, wl, h, rope, pool, cache_index,
                              block_tables, i * steps + t), None

        def step(carry, t):
            h, pools = carry
            with jax.named_scope("loop"):
                after = []
                for g, stacked in enumerate(w["layers"]):
                    (h, pool), _ = jax.lax.scan(
                        partial(layer, t),
                        (h, None if pools is None else pools[g]),
                        (stacked, jnp.arange(c.pool_layers, dtype=jnp.int32)))
                    after.append(pool)
                with jax.named_scope("step_norm"):
                    h = rms_norm(h, w["step_norm"]["weight"], c.norm_eps, dt)
                with jax.named_scope("exit_gate"):
                    gate = jnp.dot(h, w["exit_gate"]["kernel"].astype(dt)) \
                        + w["exit_gate"]["bias"].astype(dt)
                    gate = jax.nn.sigmoid(gate[..., 0].astype(jnp.float32))
            return (h, None if pools is None else after), gate

        (x, new_cache), gates = jax.lax.scan(
            step, (x, cache), jnp.arange(steps, dtype=jnp.int32))
        with jax.named_scope("lm_head"):
            logits = nn.DenseGeneral(
                features=c.vocab_size, use_bias=False, dtype=dt,
                kernel_init=nn.initializers.normal(0.02), name="lm_head")(x)
            logits = logits.astype(jnp.float32)
        if cache is None:
            return logits, exit_distribution(gates)
        return logits, new_cache

    def init_params(self, rng: jax.Array, batch: int = 1,
                    seq: int | None = None):
        ids = jnp.zeros((batch, seq or 8), jnp.int32)
        return self.init(rng, ids)["params"]
