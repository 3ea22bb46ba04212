"""Radix prefix cache: block-granular KV reuse for the serving engine.

Production traffic is dominated by shared prefixes — system prompts,
few-shot templates, and multi-turn sessions that re-send the whole
conversation.  The engine (engine.py) recomputed the full prefill for
every admission anyway.  This module is the RadixAttention/SGLang idea
(Zheng et al. 2023) rebuilt for the fixed-shape TPU engine:

* **block pool** — one preallocated arena of ``num_blocks`` KV blocks
  per K/V, shape ``(L, num_blocks + 1, block_size, H_kv·D)`` (the +1
  is a trash block scatter padding writes into).  A cached prefix is a
  chain of blocks; all device copies between the pool and a slot's
  cache row are ONE fixed-shape gather/scatter executable each,
  whatever the chain length, so the engine's no-runtime-recompiles
  contract survives intact;
* **radix tree** — host-side trie at block granularity: each node is
  one ``block_size``-token block keyed by its token tuple, children
  hashed under the parent.  Longest-prefix match is a dict walk per
  block.  Nodes are REF-COUNTED (in-flight requests and pinned
  sessions hold references); eviction is LRU over unreferenced
  leaves only, so a referenced block can never be freed and interior
  nodes never orphan their children;
* **canonical KV only** — the cache stores exclusively K/V produced by
  the prefill/chunked-prefill executables.  On this backend those are
  BITWISE identical to each other and invariant to the tokens beyond
  the prefix (masked causal attention contributes exact zeros), so a
  warm admission's token stream is byte-identical to cold prefill.
  Decode-step K/V is NOT canonical (measured ~1e-6 drift vs prefill
  on CPU f32), so a pinned session's generated region is
  re-canonicalized through ``gpt2_decode.prefill_chunk`` at retire
  time — one chunk pass off the TTFT path buys every later turn a
  near-full prefix hit without sacrificing parity;
* **graceful pressure** — a full pool with nothing evictable degrades
  to cold prefill (misses, skipped donations), never an error; a
  rebuilt engine (EngineSupervisor restart) starts from an empty tree
  and stays correct, just cold.

Metrics flow into the process-wide observe registry (and therefore
the health report and Prometheus export) as
``serve.prefix.{hits,misses,evictions,cached_blocks,hit_tokens,
lookup_tokens}`` with the owning engine's label.  The
``serve.prefix_copy`` fault site (singa_tpu.resilience) covers the
pool<->row copy paths: an injected copy failure fails the engine
TYPED and the supervisor rebuild path recovers with an empty cache
(bench_chaos.py asserts zero wedged/lost requests under it).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..observe import requests as _reqs
from ..observe.registry import registry as _default_registry
from ..ops.paged_attention import pool_zeros
from ..resilience import faults as _faults
from ..utils.logging import get_channel
from .paged import _pool_to_row, _row_to_pool

__all__ = ["PrefixCacheConfig", "PrefixCache", "SessionHandle",
           "FleetPrefixIndex"]


@dataclass(frozen=True)
class PrefixCacheConfig:
    """Knobs for the engine's prefix cache (hand to
    ``model.serve(prefix_cache=...)``; the supervisor forwards it
    verbatim to every rebuilt engine, which is what makes restart
    recovery rebuild-from-empty by construction).

    ``block_size``: tokens per cached block — the reuse granularity.
    Smaller blocks match more of a ragged prefix but cost more tree
    nodes per token; the engine requires ``max_len % block_size == 0``
    so chunked prefill windows never cross the arena edge.
    ``num_blocks``: pool capacity in blocks (device memory:
    ``2 * L * num_blocks * H_kv * block_size * D`` elements)."""

    block_size: int = 64
    num_blocks: int = 256

    def __post_init__(self):
        if self.block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {self.block_size}")
        if self.num_blocks < 1:
            raise ValueError(
                f"num_blocks must be >= 1, got {self.num_blocks}")


# -- fixed-shape device copies ----------------------------------------------
# The cache-owned pool is stored and copied as the paged arena's is
# (serve/paged.py ``_pool_to_row`` / ``_row_to_pool``: one executable
# per (pool, row) geometry whatever the chain length, dense or int8).

@jax.jit
def _read_slot(kc_arena, vc_arena, slot):
    """One slot's cache rows (L, 1, H, W, ...) out of the engine
    arena (per leaf — int8 arenas are (values, scales) tuples whose
    scales leaf lacks the trailing D axis)."""

    def rd(arena):
        sizes = (arena.shape[0], 1) + arena.shape[2:]
        start = (0, slot) + (0,) * (arena.ndim - 2)
        return jax.lax.dynamic_slice(arena, start, sizes)

    return jax.tree.map(rd, kc_arena), jax.tree.map(rd, vc_arena)


class _Node:
    """One cached block: ``key`` is the tuple of its block_size tokens,
    ``block`` its pool slot.  ``refs`` counts in-flight admissions and
    pinned sessions holding it; ``last_used`` is a logical LRU clock
    tick (deterministic — no wall time)."""

    __slots__ = ("key", "parent", "children", "block", "refs",
                 "last_used")

    def __init__(self, key, parent, block, tick):
        self.key = key
        self.parent = parent
        self.children = {}
        self.block = block
        self.refs = 0
        self.last_used = tick


class SessionHandle:
    """A finished request's sequence, pinned for multi-turn
    continuation.  ``tokens`` is the full prompt + generation;
    :meth:`request` builds the next turn's ``GenerationRequest`` with
    the conversation re-sent as its prompt — against a warm cache the
    whole pinned history is a block-prefix hit, so the next turn
    prefills only the new user tokens.  Works (cold) against a
    restarted engine's empty cache too: the handle owns host tokens,
    not device state.  :meth:`release` unpins the cached path; a
    released or restart-orphaned handle keeps building valid requests.
    """

    def __init__(self, tokens, cache=None, nodes=()):
        self.tokens = np.asarray(tokens, np.int32).reshape(-1)
        self._cache = cache
        self._nodes = list(nodes)

    @property
    def pinned_blocks(self) -> int:
        return len(self._nodes)

    def request(self, extra_tokens, **kw):
        """The next turn: a GenerationRequest whose prompt is this
        session's full sequence + ``extra_tokens`` (the new user
        input).  Keyword args pass through to GenerationRequest
        (``max_new_tokens``, ``temperature``, ``pin_session`` for the
        turn after this one, ...).  The request carries
        ``session_of=self`` so a fleet router can keep the continuation
        on the replica whose cache holds the pinned blocks."""
        from .request import GenerationRequest
        extra = np.asarray(extra_tokens, np.int32).reshape(-1)
        kw.setdefault("session_of", self)
        return GenerationRequest(
            np.concatenate([self.tokens, extra]), **kw)

    def release(self):
        """Unpin the session's cached path (idempotent).  The blocks
        stay cached until LRU pressure evicts them."""
        if self._cache is not None and self._nodes:
            self._cache.release(self._nodes)
        self._nodes = []


class _IndexNode:
    """One fleet-index block: children keyed by token tuple, the set
    of replica indices whose trees were seen holding this block, and
    a logical recency tick (the capacity bound's eviction order)."""

    __slots__ = ("children", "replicas", "tick")

    def __init__(self, tick=0):
        self.children = {}
        self.replicas = set()
        self.tick = tick


class FleetPrefixIndex:
    """FLEET-level residency index over the replicas' radix trees (the
    disaggregation round): one host-side trie at block granularity
    mapping token-block paths to the set of replica indices whose
    prefix caches hold them — the structure that makes the prefix
    cache a fleet resource instead of N private copies.

    The index is a HINT, not ground truth: per-replica LRU eviction
    never notifies the fleet, so every consumer verifies a candidate
    against the source replica's LIVE tree (``PrefixCache.lookup``)
    before acting on it — a stale entry degrades to a cold prefill or
    a fresh ship, never to an error.  Registration happens at the
    fleet's observation points (a prefill specialist's donation, a
    ship landing on a decode replica); ``drop_replica`` clears a
    failed-over or revived replica wholesale (its rebuilt tree starts
    empty), ``unregister`` prunes a hint a failed verify just proved
    stale, and ``max_blocks`` bounds the trie (least-recently-touched
    root subtree evicted first — the host-memory discipline every
    bounded store in the codebase keeps)."""

    def __init__(self, block_size, max_blocks=4096):
        if block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {block_size}")
        if max_blocks < 1:
            raise ValueError(
                f"max_blocks must be >= 1, got {max_blocks}")
        self.block_size = int(block_size)
        self.max_blocks = int(max_blocks)
        self._count = 0
        self._ticks = itertools.count(1)
        self._root = _IndexNode()

    def _keys(self, tokens, n_blocks):
        B = self.block_size
        toks = np.asarray(tokens, np.int32).reshape(-1)
        n = min(int(n_blocks), len(toks) // B)
        return [tuple(int(t) for t in toks[j * B:(j + 1) * B])
                for j in range(n)]

    def register(self, tokens, n_blocks, replica):
        """Record that ``replica`` holds the first ``n_blocks`` blocks
        of ``tokens`` (refreshes recency; may evict the stalest root
        subtree to stay within ``max_blocks``)."""
        tick = next(self._ticks)
        node = self._root
        for key in self._keys(tokens, n_blocks):
            child = node.children.get(key)
            if child is None:
                child = _IndexNode(tick)
                node.children[key] = child
                self._count += 1
            child.replicas.add(int(replica))
            child.tick = tick
            node = child
        self._prune()

    def _subtree_size(self, node):
        return 1 + sum(self._subtree_size(c)
                       for c in node.children.values())

    def _prune(self):
        """Hold the trie at ``max_blocks`` nodes: evict whole root
        subtrees, least-recently-touched first (the just-registered
        path carries the max tick, so it is never its own victim).
        Unbounded growth is the alternative — hints are only ever
        removed by failover otherwise, and a long-running fleet
        serving unique prompts would leak host memory forever."""
        while self._count > self.max_blocks and self._root.children:
            key = min(self._root.children,
                      key=lambda k: self._root.children[k].tick)
            victim = self._root.children.pop(key)
            self._count -= self._subtree_size(victim)

    def unregister(self, tokens, n_blocks, replica):
        """Drop ``replica`` from the first ``n_blocks`` blocks'
        residency sets (a verify against its live tree just failed —
        the hint is stale) and prune nodes nobody holds."""
        replica = int(replica)
        path = []
        node = self._root
        for key in self._keys(tokens, n_blocks):
            child = node.children.get(key)
            if child is None:
                break
            child.replicas.discard(replica)
            path.append((node, key, child))
            node = child
        for parent, key, child in reversed(path):
            if not child.replicas and not child.children:
                del parent.children[key]
                self._count -= 1

    def holders(self, tokens, n_blocks) -> list:
        """Replica indices whose registered residency covers ALL of
        the first ``n_blocks`` blocks, ascending (deterministic) —
        the targeted-ship source / local-warm routing candidates.
        Empty when nothing covers the whole span."""
        keys = self._keys(tokens, n_blocks)
        if len(keys) < n_blocks or not keys:
            return []
        node, held = self._root, None
        for key in keys:
            node = node.children.get(key)
            if node is None:
                return []
            held = (set(node.replicas) if held is None
                    else held & node.replicas)
            if not held:
                return []
        return sorted(held)

    def drop_replica(self, replica):
        """Forget every residency record for ``replica`` (failover or
        revive: the rebuilt tree is empty) and prune nodes no replica
        holds."""
        replica = int(replica)

        def sub(node):
            node.replicas.discard(replica)
            dead = [k for k, c in node.children.items()
                    if not sub(c)]
            for k in dead:
                del node.children[k]
            return bool(node.replicas or node.children)

        sub(self._root)
        self._count = self._subtree_size(self._root) - 1

    def snapshot(self) -> dict:
        return {"block_size": self.block_size,
                "max_blocks": self.max_blocks,
                "indexed_blocks": self._count}


def _kv_zeros(lead, head_dim, dtype, quant, sharding=None):
    """A zeroed KV pytree with leading dims ``lead`` — a dense
    ``lead + (head_dim,)`` array, or the int8 ``(values, scales)``
    pair — allocated DIRECTLY at ``sharding`` (committed; no transient
    copy on the default device).  ``None`` leaves it on the default
    device for a sharded executor's ``place_cache`` to lay out."""
    if quant:
        return (jnp.zeros(lead + (head_dim,), jnp.int8, device=sharding),
                jnp.zeros(lead, jnp.float32, device=sharding))
    return jnp.zeros(lead + (head_dim,), dtype, device=sharding)


class PrefixCache:
    """Block-granular radix tree over a pooled KV arena (module
    docstring).  Owned by one engine; the engine drives every device
    copy through the fixed-shape helpers above and this class keeps
    the host-side tree, refcounts, LRU state, and metrics."""

    def __init__(self, config, n_layer, n_kv_head, head_dim, dtype,
                 engine_label="0", reg=None, quant=False, arena=None,
                 tp=None, sharding=None):
        self.config = config
        B, N = config.block_size, config.num_blocks
        self.block_size = B
        self.num_blocks = N
        # ARENA mode (paged engines): the tree indexes blocks of the
        # engine's shared PagedKVArena instead of owning a pool —
        # capacity is the arena's, device copies route through it, and
        # donation is zero-copy adoption (adopt_blocks)
        self._arena = arena
        # tensor-parallel executor (serve/tp.py): cache rows and the
        # cache-owned pool become SHARDED pytrees over the tp mesh's
        # H_kv axis, and the pool<->row copies dispatch through the
        # executor's sharded twins.  The host-side radix tree, ref
        # counts, and LRU state are untouched — a cached block is the
        # same logical block on every shard
        self._tp = tp
        self._head_dim = int(head_dim)
        if arena is not None:
            self.num_blocks = arena.num_blocks
            self._pool_k = self._pool_v = None
        else:
            # +1: trash block scatter padding lands in (never read);
            # int8 pools are the engine arena's (values, scales) layout.
            # An unsharded engine hands its weights' ``sharding`` so
            # the pool is born beside them; a tp executor lays it out
            self._pool_k, self._pool_v = (
                pool_zeros(n_layer, N + 1, B, n_kv_head, head_dim,
                           dtype, quant, sharding) for _ in "kv")
            if tp is not None:
                self._pool_k = tp.place_pool(self._pool_k)
                self._pool_v = tp.place_pool(self._pool_v)
        self._root = _Node((), None, -1, 0)
        self._free = [] if arena is not None else list(range(N))
        self._nodes_by_block = {}       # pool slot -> node
        self._tick = itertools.count(1)
        self._log = get_channel("serve")
        reg = reg if reg is not None else _default_registry()
        lbl = dict(engine=engine_label)
        self._c_hits = reg.counter(
            "serve.prefix.hits",
            help="admissions that reused >=1 cached block", **lbl)
        self._c_misses = reg.counter(
            "serve.prefix.misses",
            help="admissions with no usable cached prefix", **lbl)
        self._c_evictions = reg.counter(
            "serve.prefix.evictions",
            help="LRU evictions of unreferenced leaf blocks", **lbl)
        self._c_hit_tokens = reg.counter(
            "serve.prefix.hit_tokens",
            help="prompt tokens served from cached blocks", **lbl)
        self._c_lookup_tokens = reg.counter(
            "serve.prefix.lookup_tokens",
            help="prompt tokens seen by admission lookups", **lbl)
        self._c_donate_skipped = reg.counter(
            "serve.prefix.donate_skipped",
            help="blocks not cached because the pool was full of "
                 "referenced blocks", **lbl)
        self._g_cached = reg.gauge(
            "serve.prefix.cached_blocks",
            help="blocks currently held by the radix tree", **lbl)
        self._registry = reg
        self._registered = [
            self._c_hits, self._c_misses, self._c_evictions,
            self._c_hit_tokens, self._c_lookup_tokens,
            self._c_donate_skipped, self._g_cached]

    # -- tree ------------------------------------------------------------
    @property
    def cached_blocks(self) -> int:
        return len(self._nodes_by_block)

    def cached_block_ids(self):
        """Pool slots the radix tree currently owns — the cache's side
        of the engine's block-accounting invariant (every used arena
        block is either cached here or owned by a live/prefilling
        row)."""
        return list(self._nodes_by_block)

    def _block_keys(self, tokens):
        B = self.block_size
        toks = np.asarray(tokens, np.int32).reshape(-1)
        n = len(toks) // B
        return [tuple(int(t) for t in toks[j * B:(j + 1) * B])
                for j in range(n)]

    def lookup(self, tokens):
        """Longest cached block-prefix of ``tokens``: the matched node
        path, root-first.  Pure — no counters, no refcounts (the
        scheduler's admission-cost probe uses it too).  Block keys are
        built lazily so an early miss (block 0 of a long prompt) does
        no O(prompt_len) tuple work."""
        toks = np.asarray(tokens, np.int32).reshape(-1)
        B = self.block_size
        path = []
        node = self._root
        for j in range(len(toks) // B):
            key = tuple(int(t) for t in toks[j * B:(j + 1) * B])
            node = node.children.get(key)
            if node is None:
                break
            path.append(node)
        return path

    def touch(self, nodes):
        """Refresh LRU recency for an already-cached path (the
        donation short-circuit: nothing to copy, but the path was
        just used)."""
        tick = next(self._tick)
        for n in nodes:
            n.last_used = tick

    def acquire(self, nodes):
        """Pin a matched path for the lifetime of an in-flight request
        (or a session): referenced nodes are never evicted, so a hot
        prefix cannot be churned out from under its users."""
        tick = next(self._tick)
        for n in nodes:
            n.refs += 1
            n.last_used = tick

    def release(self, nodes):
        for n in nodes:
            n.refs -= 1
            if n.refs < 0:
                # a real exception, not an assert (-O strips asserts):
                # underflow would let a still-pinned block read as
                # unreferenced and be evicted under a live session
                n.refs = 0
                raise RuntimeError(
                    "prefix-cache refcount underflow (double release "
                    f"of block {n.block})")

    def on_donate_skipped(self, n):
        """Account ``n`` blocks that could not be cached under pool
        pressure (the engine's ship-export path under a failed
        allocation — :meth:`donate_from_row` counts its own)."""
        self._c_donate_skipped.inc(int(n))

    def on_admit(self, hit_blocks, prompt_len, request_id=None):
        """Metrics for one admission: ``hit_blocks`` usable cached
        blocks against a ``prompt_len``-token prompt.  With the
        request ledger on, also annotates the request's timeline with
        the authoritative cold/warm verdict and hit-token count (the
        cache owns hit accounting; the engine only owns timing)."""
        self._c_lookup_tokens.inc(int(prompt_len))
        if hit_blocks > 0:
            self._c_hits.inc()
            self._c_hit_tokens.inc(int(hit_blocks) * self.block_size)
        else:
            self._c_misses.inc()
        if _reqs._active and request_id is not None:
            _reqs._ledger.on_prefix(
                request_id,
                hit_tokens=int(hit_blocks) * self.block_size)

    # -- allocation / eviction -------------------------------------------
    def _evict_one(self):
        """Drop the least-recently-used UNREFERENCED LEAF.  Interior
        nodes and referenced nodes are untouchable: evicting an
        interior node would orphan its children's match path, and a
        referenced one is in use.  Returns the freed pool slot or
        None."""
        victim = None
        for node in self._nodes_by_block.values():
            if node.refs > 0 or node.children:
                continue
            if victim is None or node.last_used < victim.last_used:
                victim = node
        if victim is None:
            return None
        del victim.parent.children[victim.key]
        del self._nodes_by_block[victim.block]
        self._c_evictions.inc()
        self._g_cached.set(self.cached_blocks)
        return victim.block

    def evictable_blocks(self) -> int:
        """How many blocks LRU eviction could EVER reclaim: a node is
        reclaimable only after its whole subtree is (evicting an
        interior node would orphan children), so a referenced node
        shields every ancestor.  The paged engine's allocation
        feasibility check uses this to avoid preempting live work for
        an allocation that could never fit anyway (pinned sessions
        holding the pool)."""

        def sub(node):
            # (evictable count, whole subtree reclaimable)
            total, fully = 0, True
            for c in node.children.values():
                ev, f = sub(c)
                total += ev
                fully = fully and f
            if fully and node.refs == 0:
                return total + 1, True
            return total, False

        return sum(sub(c)[0] for c in self._root.children.values())

    def _alloc(self):
        if self._free:
            return self._free.pop()
        return self._evict_one()

    # -- device copies (engine-driven) -----------------------------------
    def _pad_idx(self, blocks, trash):
        """Fixed-width block-index vector: real entries then ``trash``
        padding, so one executable serves every chain length."""
        nb = len(blocks)
        idx = np.full(self._row_blocks, trash, np.int32)
        idx[:nb] = blocks
        return jnp.asarray(idx)

    def attach_row_geometry(self, max_len):
        """Called once by the owning engine: the number of blocks a
        full cache row spans (the fixed width of every copy's index
        vector)."""
        assert max_len % self.block_size == 0
        self._row_blocks = max_len // self.block_size

    def copy_into_row(self, nodes):
        """Build a cache row holding ``nodes``' blocks at positions
        [0, len(nodes)*B); the rest zeros.  One gather dispatch — out
        of the shared paged arena in arena mode (its
        ``serve.paged_copy`` fault site covers that path), out of the
        cache-owned pool otherwise."""
        if self._arena is not None:
            return self._arena.gather_row([n.block for n in nodes],
                                          n_used=len(nodes))
        if _faults._armed:
            _faults.check("serve.prefix_copy")
        idx = self._pad_idx([n.block for n in nodes], trash=0)
        if self._tp is not None:
            return self._tp.pool_to_row(self._pool_k, self._pool_v,
                                        idx, jnp.int32(len(nodes)))
        return _pool_to_row(self._pool_k, self._pool_v, idx,
                            jnp.int32(len(nodes)),
                            head_dim=self._head_dim)

    def adopt_blocks(self, tokens, blocks, n_goal):
        """ZERO-COPY donation (arena mode): insert tree nodes that
        take OWNERSHIP of a retiring slot's private pool blocks —
        ``blocks[j]`` holds the canonical K/V for token block ``j`` of
        ``tokens``, already sitting in the shared paged arena, so
        donation moves a pointer, not bytes.  A lane that ALREADY has
        a node (the slot's shared admission prefix, or a sibling's
        earlier donation of the same content) keeps the tree's block
        and the caller frees any duplicate (it is absent from the
        returned path's block set).  Never skips, never allocates:
        adoption cannot fail under pool pressure.  Returns the tree
        path covering ``n_goal`` blocks."""
        keys = self._block_keys(tokens)[:n_goal]
        tick = next(self._tick)
        path = []
        node = self._root
        for j, key in enumerate(keys):
            child = node.children.get(key)
            if child is None:
                child = _Node(key, node, blocks[j], tick)
                node.children[key] = child
                self._nodes_by_block[blocks[j]] = child
            child.last_used = tick
            path.append(child)
            node = child
        self._g_cached.set(self.cached_blocks)
        return path

    def donate_from_row(self, tokens, kc_row, vc_row, n_blocks):
        """Insert ``tokens``' first ``n_blocks`` full blocks into the
        tree, copying the missing ones out of the (canonical) cache
        row in ONE scatter dispatch.  Under pool pressure the
        donation stops at the first unallocatable block (the stored
        path must stay a contiguous prefix) — counted, never raised.
        Returns the tree path covering what is now cached.  Arena-mode
        caches never call this — the paged engine donates by
        :meth:`adopt_blocks` (zero copy)."""
        if self._arena is not None:
            raise RuntimeError(
                "donate_from_row on an arena-backed prefix cache: "
                "paged engines donate by adoption (adopt_blocks)")
        if _faults._armed:
            _faults.check("serve.prefix_copy")
        keys = self._block_keys(tokens)[:n_blocks]
        tick = next(self._tick)
        path, new_nodes = [], []
        node = self._root
        try:
            for j, key in enumerate(keys):
                child = node.children.get(key)
                if child is None:
                    slot = self._alloc()
                    if slot is None:
                        self._c_donate_skipped.inc(len(keys) - j)
                        break
                    child = _Node(key, node, slot, tick)
                    node.children[key] = child
                    self._nodes_by_block[slot] = child
                    new_nodes.append((j, child))
                # transient ref: the in-progress path must not be LRU
                # fodder for its OWN later allocations (an evicted
                # ancestor would orphan the blocks donated under it)
                child.refs += 1
                child.last_used = tick
                path.append(child)
                node = child
            if new_nodes:
                idx = np.full(self._row_blocks, self.num_blocks,
                              np.int32)
                for j, child in new_nodes:
                    idx[j] = child.block
                if self._tp is not None:
                    self._pool_k, self._pool_v = self._tp.row_to_pool(
                        self._pool_k, self._pool_v, kc_row, vc_row,
                        jnp.asarray(idx))
                else:
                    self._pool_k, self._pool_v = _row_to_pool(
                        self._pool_k, self._pool_v, kc_row, vc_row,
                        jnp.asarray(idx))
                self._g_cached.set(self.cached_blocks)
        finally:
            for n in path:
                n.refs -= 1
        return path

    # -- lifecycle / reporting -------------------------------------------
    def unregister(self):
        """Release registry entries and the device pool (engine
        close(); in arena mode the shared pool is the arena's to
        release)."""
        self._registry.remove(*self._registered)
        self._pool_k = self._pool_v = None

    def snapshot(self) -> dict:
        lookup = self._c_lookup_tokens.value
        return {
            "block_size": self.block_size,
            "capacity_blocks": self.num_blocks,
            "cached_blocks": self.cached_blocks,
            "hits": self._c_hits.value,
            "misses": self._c_misses.value,
            "evictions": self._c_evictions.value,
            "hit_tokens": self._c_hit_tokens.value,
            "lookup_tokens": lookup,
            "donate_skipped": self._c_donate_skipped.value,
            "hit_rate_tokens": (self._c_hit_tokens.value / lookup
                                if lookup else 0.0),
        }
