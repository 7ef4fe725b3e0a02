"""Tiered KV cache — the host-RAM spill tier behind the radix cache.

The radix prefix cache (`serve/blocks.py`) turns a shared prompt into
shared HBM blocks, but its eviction is terminal: under pool pressure a
cold chain is dropped and a later same-prefix request pays the full
re-prefill. On real chips HBM is the scarcest resource in the serving
system while host RAM is ~10x larger and one DMA away — so eviction
should DEMOTE, not delete. This module is the host half of that tier:

  * `HostBlockStore` — evicted full-block prefix chains as host numpy
    buffers under an LRU `--host-cache-mb` budget. Each entry is keyed
    by the chain's full token prefix (root..block inclusive), so a
    later lookup extends a device match by walking consecutive keys:
    device-hit for the first k blocks, host-hit for the next m, miss
    for the rest. Restoring a hit costs one H2D copy per block through
    the engine's eager block-scatter — bit-identical K/V (same dtype
    down and up), zero new executables.
  * `save`/`load` — the store serializes to `<base_dir>/hostcache/`
    on drain (index.json + one raw chains.bin, written atomically), so
    a spilled chain outlives the process and rides the journal's
    recovery path: restart between evict and rehit still restores.
  * `prefix_root_digest` + `HotRootTracker` — the fleet half's
    vocabulary. Replicas advertise their top-k hot prefix roots
    (sha1 token digests, same construction the router's `p:` affinity
    key uses) on heartbeats; the router's cache-aware scoring steers a
    matching request to the replica whose KV already holds the prefix.

Deliberately jax-free (numpy + stdlib only): the router imports the
digest helpers without paying a backend init, and the property tests
drive spill/restore/persistence without a device.
"""

from __future__ import annotations

import hashlib
import json
import os
from collections import OrderedDict

import numpy as np

# Tokens hashed into a prefix-root digest. Matches the router's
# affinity `prefix_tokens` default so an engine-advertised root and the
# router's request-side digest agree without configuration handshakes.
PREFIX_ROOT_TOKENS = 32

# Roots a replica advertises per heartbeat: enough to cover every hot
# system prompt a ~handful-tenant replica serves, small enough that the
# heartbeat record stays a single atomic write.
TOP_ROOTS = 8

INDEX_NAME = "index.json"
CHAINS_NAME = "chains.bin"


def prefix_root_digest(token_ids, n: int = PREFIX_ROOT_TOKENS) -> str | None:
    """Stable digest of a prompt's first `n` token ids — the unit of
    cache-aware routing. Same construction as the router's `p:`
    affinity key (comma-joined ints, sha1, 16 hex chars) so the two
    vocabularies can never drift; None for an empty prompt."""
    ids = [int(t) for t in list(token_ids)[:n]]
    if not ids:
        return None
    return hashlib.sha1(
        ",".join(str(t) for t in ids).encode()).hexdigest()[:16]


class HotRootTracker:
    """Recency-ordered set of prefix-root digests this engine served —
    what the replica advertises on its heartbeat. Bounded (`cap`) so a
    long-lived engine's tracker never grows with traffic; `top()`
    returns most-recent-first, which is exactly the k the router should
    trust most."""

    def __init__(self, cap: int = 64):
        self.cap = cap
        self._roots: OrderedDict[str, None] = OrderedDict()

    def note(self, digest: str | None) -> None:
        if not digest:
            return
        self._roots.pop(digest, None)
        self._roots[digest] = None
        while len(self._roots) > self.cap:
            self._roots.popitem(last=False)

    def top(self, k: int = TOP_ROOTS) -> list[str]:
        return list(self._roots)[-k:][::-1]

    def __len__(self) -> int:
        return len(self._roots)


class HostBlockStore:
    """Evicted prefix chains in host RAM under an LRU byte budget.

    Keys are the chain's FULL token prefix (a tuple covering every
    position from the root through this block), so consecutive chain
    links are independent entries: `match` extends a device hit of k
    full blocks by probing `tokens[:k*bs+bs]`, `tokens[:k*bs+2*bs]`,
    ... and a mid-chain LRU eviction simply shortens what a given
    device base can restore. Payloads are `[n_layers, 2(k/v),
    block_size, n_kv_heads, head_dim]` host arrays in the pool's own
    dtype — the D2H/H2D round trip is bit-exact, which is what keeps a
    restored stream identical to the never-evicted run.

    Content under a key is immutable by the radix invariant (full
    blocks are never written again), so a re-spill of a key the store
    already holds is a no-op refresh, never an overwrite hazard."""

    def __init__(self, budget_mb: int, block_size: int):
        if budget_mb <= 0:
            raise ValueError(f"host cache budget must be > 0 MB, "
                             f"got {budget_mb}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.budget_bytes = int(budget_mb) * 2 ** 20
        self.block_size = block_size
        self._chains: OrderedDict[tuple[int, ...], np.ndarray] = \
            OrderedDict()
        self.bytes_used = 0
        # lifetime tallies — the store's own evidence for doctor/tests
        self.spills = 0          # chains accepted by put()
        self.restores = 0        # blocks handed back by match()
        self.evictions = 0       # chains LRU-dropped for budget
        self.rejected = 0        # puts refused (payload alone > budget)

    # ------------------------------------------------------------ reads

    def __len__(self) -> int:
        return len(self._chains)

    @property
    def occupancy_mb(self) -> float:
        return self.bytes_used / 2 ** 20

    def match(self, tokens, start: int, limit: int) -> list[np.ndarray]:
        """Consecutive spilled blocks extending a device match: `start`
        is the device full-block coverage in tokens (a multiple of
        block_size), `limit` caps matched positions (callers pass
        len-1, the radix rule: one token must remain to prefill).
        Returns the payloads in chain order; every hit refreshes LRU
        recency. Empty list = the host tier has nothing contiguous."""
        bs = self.block_size
        toks = [int(t) for t in list(tokens)[:limit]]
        out: list[np.ndarray] = []
        pos = start
        while pos + bs <= limit:
            key = tuple(toks[:pos + bs])
            payload = self._chains.get(key)
            if payload is None:
                break
            self._chains.move_to_end(key)
            out.append(payload)
            pos += bs
        self.restores += len(out)
        return out

    def stats(self) -> dict:
        return {
            "chains": len(self._chains),
            "bytes": self.bytes_used,
            "mb": round(self.occupancy_mb, 3),
            "spills": self.spills,
            "restores": self.restores,
            "evictions": self.evictions,
            "rejected": self.rejected,
        }

    # ----------------------------------------------------------- writes

    def put(self, chain_tokens, payload: np.ndarray) -> bool:
        """Accept one evicted block: `chain_tokens` is the FULL prefix
        (length a multiple of block_size, the last block_size ids being
        this block's own), `payload` its host K/V. Returns False when
        the payload alone exceeds the whole budget (counted, never
        raised — spilling is opportunistic)."""
        key = tuple(int(t) for t in list(chain_tokens))
        if not key or len(key) % self.block_size != 0:
            raise ValueError(
                f"chain key length {len(key)} is not a multiple of "
                f"block_size {self.block_size}")
        if key in self._chains:
            # immutable content: refresh recency, keep the incumbent
            self._chains.move_to_end(key)
            return True
        payload = np.asarray(payload)
        if payload.nbytes > self.budget_bytes:
            self.rejected += 1
            return False
        self._chains[key] = payload
        self.bytes_used += payload.nbytes
        self.spills += 1
        while self.bytes_used > self.budget_bytes:
            _, old = self._chains.popitem(last=False)
            self.bytes_used -= old.nbytes
            self.evictions += 1
        return True

    def clear(self) -> None:
        self._chains.clear()
        self.bytes_used = 0

    # ------------------------------------------------------ persistence

    def save(self, dirpath: str) -> dict:
        """Serialize the store to `dirpath` (index.json + chains.bin,
        both written to temp names then renamed — a crash mid-save
        leaves the previous snapshot intact). Chains are written
        oldest-first so `load` rebuilds the exact LRU order. Returns
        the stats dict of what was written."""
        os.makedirs(dirpath, exist_ok=True)
        index: list[dict] = []
        offset = 0
        bin_tmp = os.path.join(dirpath, CHAINS_NAME + ".tmp")
        with open(bin_tmp, "wb") as f:
            for key, payload in self._chains.items():
                raw = payload.tobytes()
                f.write(raw)
                index.append({
                    "tokens": list(key),
                    "shape": list(payload.shape),
                    "dtype": payload.dtype.name,
                    "offset": offset,
                    "nbytes": len(raw),
                })
                offset += len(raw)
        idx_tmp = os.path.join(dirpath, INDEX_NAME + ".tmp")
        with open(idx_tmp, "w") as f:
            json.dump({"v": 1, "block_size": self.block_size,
                       "chains": index}, f)
        os.replace(bin_tmp, os.path.join(dirpath, CHAINS_NAME))
        os.replace(idx_tmp, os.path.join(dirpath, INDEX_NAME))
        return self.stats()

    def load(self, dirpath: str) -> int:
        """Rebuild from a prior `save` (missing/corrupt files load
        nothing — persistence is an optimization, never a crash).
        Entries load oldest-first, re-running the LRU budget, so a
        shrunk `--host-cache-mb` keeps the most recent chains. Returns
        chains loaded."""
        idx_path = os.path.join(dirpath, INDEX_NAME)
        bin_path = os.path.join(dirpath, CHAINS_NAME)
        try:
            with open(idx_path) as f:
                index = json.load(f)
            raw = open(bin_path, "rb").read()
        except (OSError, ValueError):
            return 0
        if index.get("block_size") != self.block_size:
            return 0  # a different pool geometry: the chains are alien
        loaded = 0
        for ent in index.get("chains", []):
            try:
                dtype = np.dtype(ent["dtype"])
            except TypeError:
                # a dtype numpy can't name without its extension module
                # (e.g. bfloat16 via ml_dtypes) — resolve it lazily
                try:
                    import ml_dtypes

                    dtype = np.dtype(getattr(ml_dtypes, ent["dtype"]))
                except (ImportError, AttributeError, TypeError):
                    continue
            off, nb = int(ent["offset"]), int(ent["nbytes"])
            if off + nb > len(raw):
                continue
            payload = np.frombuffer(
                raw[off:off + nb], dtype=dtype).reshape(ent["shape"])
            if self.put(ent["tokens"], payload.copy()):
                loaded += 1
        return loaded
