"""Paged KV-cache bookkeeping — block manager + radix prefix cache.

The slot engine's original cache was a `[S, L]` slab: every request
owned `L` cache rows from admission to finish, so HBM burn was
proportional to the *longest possible* request, and two requests with
the same system prompt each prefilled it from scratch. PagedAttention
(Kwon et al., SOSP '23) is the standard fix: carve the cache into
fixed-size **blocks**, give each sequence a **block table** (logical
position -> physical block), and let the host hand blocks out
on demand. Memory then tracks *actual* tokens, and a block whose
contents two sequences agree on can simply appear in both tables.

This module is the host half — pure bookkeeping, no jax:

  * `BlockManager` — the physical pool: free list, per-block reference
    counts, all-or-nothing allocation, admission *reservations* (the
    scheduler's worst-case earmark), and fail-loud double-free checks.
    Physical block 0 is the **null block**: never allocated, it is the
    write target the device code routes masked/inactive lanes to, so
    garbage always has somewhere harmless to land.
  * `RadixPrefixCache` — a trie over *full* blocks of token ids. A
    finished-prefilling request registers its prompt's full blocks;
    a later request whose prompt starts with the same tokens walks the
    trie and shares those blocks instead of re-prefilling them
    (refcount++, zero device work). The trie holds one reference of
    its own per block, so cached prefixes survive their original
    request — until pool pressure evicts them, LRU-leaf first.
  * `fork_alloc` — copy-on-write fork of a sequence's allocation:
    full blocks are shared (immutable by construction — writers only
    ever append into their exclusive tail), the partially-filled tail
    block is copied into a fresh block the fork owns. The caller is
    responsible for the device-side block copy; this returns the
    (src, dst) pairs to apply.

Why sharing is safe: K/V at position p depend only on the token ids at
positions 0..p (RoPE is absolute, attention is causal), so any two
sequences with identical prefixes have bit-identical K/V for the
shared span. Only *full* blocks enter the trie, and full blocks are
never written again (writes always append at the sequence frontier,
which lives in the exclusive tail block) — shared memory is immutable
memory, and the only copy the design ever needs is the partial-tail
copy at fork/extension time.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Iterable

import numpy as np

NULL_BLOCK = 0  # reserved physical block: masked/inactive lanes write here


def blocks_for(tokens: int, block_size: int) -> int:
    """Blocks needed to cover `tokens` positions."""
    return -(-tokens // block_size)


class BlockError(RuntimeError):
    """Bookkeeping violation (double free / unref of an unallocated
    block). Raised loudly: a silent refcount bug corrupts user-visible
    K/V, so the property test treats this as the tripwire."""


class BlockManager:
    """Fixed-size block pool: free list + refcounts + reservations.

    `num_blocks` counts physical blocks INCLUDING the reserved null
    block 0, matching the device pool's leading dimension; `capacity`
    (= num_blocks - 1) is what is actually allocatable. Allocation is
    all-or-nothing and deterministic (ascending ids), so a seeded test
    run maps to one exact block layout.

    Refcount protocol: `alloc` returns blocks at refcount 1 owned by
    the caller; every additional holder (a sharing sequence, the radix
    trie) `incref`s; `decref` at refcount 1 frees the block back to the
    pool. `reserve`/`release` track admission-time worst-case earmarks
    so the scheduler can promise growth room without allocating it yet.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (one is the reserved "
                             f"null block), got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # pop() yields ascending ids: 1, 2, 3, ... (deterministic runs)
        self._free = list(range(num_blocks - 1, NULL_BLOCK, -1))
        self._ref: dict[int, int] = {}
        self.reserved = 0  # worst-case blocks promised but not yet allocated

    # ------------------------------------------------------------ state

    @property
    def capacity(self) -> int:
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.capacity - self.num_free

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    # ------------------------------------------------------- allocation

    def alloc(self, n: int) -> list[int] | None:
        """n fresh blocks at refcount 1, or None (all-or-nothing: a
        partial grant would have to be unwound by every caller)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def incref(self, blocks: Iterable[int]) -> None:
        for b in blocks:
            if b not in self._ref:
                raise BlockError(f"incref of unallocated block {b}")
            self._ref[b] += 1

    def decref(self, blocks: Iterable[int]) -> None:
        for b in blocks:
            ref = self._ref.get(b)
            if ref is None:
                raise BlockError(f"free of unallocated block {b} "
                                 "(double free?)")
            if ref == 1:
                del self._ref[b]
                self._free.append(b)
            else:
                self._ref[b] = ref - 1

    # ----------------------------------------------------- reservations

    def reserve(self, n: int) -> None:
        self.reserved += n

    def release(self, n: int) -> None:
        self.reserved = max(0, self.reserved - n)

    def check(self) -> None:
        """Invariant audit (tests): every tracked block is allocated
        exactly once, free+used == capacity, refcounts positive."""
        if len(self._free) != len(set(self._free)):
            raise BlockError("free list holds duplicates")
        overlap = set(self._free) & set(self._ref)
        if overlap:
            raise BlockError(f"blocks both free and referenced: {overlap}")
        if NULL_BLOCK in self._ref or NULL_BLOCK in self._free:
            raise BlockError("null block entered circulation")
        if len(self._free) + len(self._ref) != self.capacity:
            raise BlockError(
                f"leak: {len(self._free)} free + {len(self._ref)} used "
                f"!= capacity {self.capacity}")
        if any(r < 1 for r in self._ref.values()):
            raise BlockError("non-positive refcount")


@dataclasses.dataclass
class SeqAlloc:
    """One sequence's view of the pool: its block chain in logical
    order, how much of its admission-time reservation is still
    unclaimed, and its admission order (preemption picks the
    youngest). The engine keeps one per layer kind for a slot: the
    `full` kind's carries `order` and `n_filled` for all of them."""

    blocks: list[int]
    n_shared: int = 0        # leading blocks also held by the radix trie
    reserved: int = 0        # worst-case blocks promised, not yet claimed
    order: int = 0           # admission sequence number
    n_filled: int = 0        # tokens written so far (the write frontier)
    # a windowed layer kind's chain slides: `blocks[0]` is logical block
    # `first` (the blocks before it fell behind every query's window and
    # went back to the pool), and `limit` is the logical blocks the
    # sequence can ever reach, which sizes its reservation as it slides
    first: int = 0
    limit: int = 0


def fork_alloc(
    mgr: BlockManager, seq: SeqAlloc, n_filled: int,
) -> tuple[SeqAlloc | None, list[tuple[int, int]]]:
    """Copy-on-write fork of `seq` at `n_filled` tokens — the generic
    sequence-level fork primitive (beam search / parallel sampling /
    the property suite's fork model). The engine's admission-time COW
    is the trie-mediated special case of the same protocol
    (`RadixPrefixCache.lookup().cow_src` + the engine's copy jit).

    Full blocks are shared (incref — immutable, nobody writes them
    again); the partially-filled tail block, which `seq` WILL keep
    writing, is copied into a fresh block the fork owns exclusively.
    Returns (fork, copies) where `copies` is the [(src, dst)] list the
    caller must apply on device, or (None, []) when the pool cannot
    supply the tail copy."""
    bs = mgr.block_size
    n_full = n_filled // bs
    tail = n_filled - n_full * bs
    shared = seq.blocks[:n_full]
    copies: list[tuple[int, int]] = []
    new_blocks = list(shared)
    if tail:
        dst = mgr.alloc(1)
        if dst is None:
            return None, []
        copies.append((seq.blocks[n_full], dst[0]))
        new_blocks.append(dst[0])
    mgr.incref(shared)
    return SeqAlloc(blocks=new_blocks, n_shared=len(shared)), copies


# ---------------------------------------------------------------- radix


@dataclasses.dataclass(eq=False)
class _Node:
    tokens: tuple[int, ...]          # the block_size token ids this block holds
    block: int
    parent: "_Node | None"
    children: dict[tuple[int, ...], "_Node"] = dataclasses.field(
        default_factory=dict)
    last_used: int = 0


@dataclasses.dataclass
class PrefixMatch:
    """Result of a prefix-cache walk.

    `blocks` are full shared blocks (the caller increfs them when it
    commits); `tokens` counts cached positions including the COW
    extension; `cow_src` (when set) is a trie block whose first
    `tokens - len(blocks)*block_size` ids extend the match mid-block —
    the caller copies it and owns the copy."""

    blocks: list[int]
    tokens: int
    cow_src: int | None = None


class RadixPrefixCache:
    """Trie over full token blocks -> retained physical blocks.

    Nodes hold one manager reference each, so a cached chain outlives
    the request that built it; `evict` walks it back LRU-leaf-first
    under pool pressure. The children of a node are keyed by their full
    `block_size`-token chunk; longest-common-prefix against a child is
    the copy-on-write *extension*: a new prompt that diverges mid-block
    still reuses the agreeing positions via one block copy."""

    def __init__(self, mgr: BlockManager, spill=None):
        self.mgr = mgr
        self.root = _Node(tokens=(), block=NULL_BLOCK, parent=None)
        self._nodes: list[_Node] = []
        self._clock = itertools.count(1)
        # the host-tier seam (serve/hostcache.py): when set, `evict`
        # hands each dying chain's full token key + physical block to
        # the callback BEFORE the decref frees it — demotion instead of
        # deletion. `clear` never spills (shutdown/tests drop holds,
        # they don't demote), and a block some sequence still shares
        # (refcount > 1) isn't dying, so it never spills either.
        self.spill = spill

    # ------------------------------------------------------------ reads

    def __len__(self) -> int:
        return len(self._nodes)

    def evictable(self) -> int:
        """Blocks only the trie still holds (refcount 1) — what `evict`
        could free right now. A node at refcount 1 cannot have a child
        at refcount > 1 (sharers hold the whole chain), so this count
        is cascade-accurate, not just leaf-accurate."""
        return sum(1 for n in self._nodes
                   if self.mgr.refcount(n.block) == 1)

    def lookup(self, tokens: np.ndarray, limit: int) -> PrefixMatch:
        """Longest cached prefix of `tokens`, capped at `limit` matched
        positions (callers pass len-1: at least one token must remain
        to prefill, because the first sampled token needs the last
        prompt position's logits)."""
        bs = self.mgr.block_size
        node = self.root
        blocks: list[int] = []
        pos = 0
        toks = [int(t) for t in tokens]
        while pos + bs <= limit:
            child = node.children.get(tuple(toks[pos:pos + bs]))
            if child is None:
                break
            blocks.append(child.block)
            child.last_used = next(self._clock)
            node = child
            pos += bs
        # copy-on-write extension: the longest mid-block agreement with
        # any child buys `m` more cached positions for one block copy
        cap = min(limit - pos, bs)
        best_m, best_src = 0, None
        if cap > 0:
            want = toks[pos:pos + cap]
            for child in node.children.values():
                m = 0
                for a, b in zip(child.tokens, want):
                    if a != b:
                        break
                    m += 1
                if m > best_m:
                    best_m, best_src = m, child.block
                    if m == cap:
                        break
        if best_m > 0:
            child_touch = best_src  # touched via its block below
            for child in node.children.values():
                if child.block == child_touch:
                    child.last_used = next(self._clock)
                    break
            return PrefixMatch(blocks=blocks, tokens=pos + best_m,
                               cow_src=best_src)
        return PrefixMatch(blocks=blocks, tokens=pos)

    # ----------------------------------------------------------- writes

    def insert(self, tokens: np.ndarray, blocks: list[int]) -> int:
        """Register a prompt's full-block chain. `blocks` is the
        sequence's chain in logical order; only chunks whose every
        position is a prompt token are inserted (tail positions get
        generated tokens appended later — those blocks stay private).
        Chunks already present keep the incumbent node (first writer
        wins; the duplicate block stays private to its sequence).
        Returns the number of new nodes created."""
        bs = self.mgr.block_size
        n_full = min(len(tokens) // bs, len(blocks))
        node = self.root
        created = 0
        toks = [int(t) for t in tokens]
        for c in range(n_full):
            chunk = tuple(toks[c * bs:(c + 1) * bs])
            child = node.children.get(chunk)
            if child is None:
                child = _Node(tokens=chunk, block=blocks[c], parent=node)
                node.children[chunk] = child
                self._nodes.append(child)
                self.mgr.incref([blocks[c]])  # the trie's own hold
                created += 1
            child.last_used = next(self._clock)
            node = child
        return created

    def evict(self, n: int) -> int:
        """Free up to `n` blocks by dropping least-recently-used leaves
        nobody else references; an evicted leaf may expose its parent
        for the next pass. Returns blocks actually freed."""
        freed = 0
        while freed < n:
            victim: _Node | None = None
            for node in self._nodes:
                if node.children or self.mgr.refcount(node.block) != 1:
                    continue
                if victim is None or node.last_used < victim.last_used:
                    victim = node
            if victim is None:
                break
            self._drop(victim, spill=True)
            freed += 1
        return freed

    def clear(self) -> int:
        """Drop every trie hold (tests / shutdown). Returns blocks whose
        last reference was the trie's."""
        freed = 0
        # leaves-first: repeatedly drop nodes without children
        while self._nodes:
            progress = False
            for node in list(self._nodes):
                if node.children:
                    continue
                if self.mgr.refcount(node.block) == 1:
                    freed += 1
                self._drop(node)
                progress = True
            if not progress:  # pragma: no cover — cycle-free by construction
                break
        return freed

    def chain_tokens(self, node: _Node) -> tuple[int, ...]:
        """The full token prefix a node's block completes — root..node
        inclusive, reconstructed by walking parents. This is the host
        tier's chain key: `tokens[-block_size:]` are the node's own."""
        parts: list[tuple[int, ...]] = []
        n: _Node | None = node
        while n is not None and n.tokens:
            parts.append(n.tokens)
            n = n.parent
        return tuple(t for chunk in reversed(parts) for t in chunk)

    def _drop(self, node: _Node, spill: bool = False) -> None:
        if spill and self.spill is not None \
                and self.mgr.refcount(node.block) == 1:
            # the block's K/V still sit in the device pool until the
            # decref below recycles it — spill reads them out NOW
            self.spill(self.chain_tokens(node), node.block)
        parent = node.parent
        if parent is not None:
            parent.children.pop(node.tokens, None)
        self._nodes.remove(node)
        self.mgr.decref([node.block])
