"""Block-paged KV arena: ONE memory system for live decode and the
radix prefix cache (the paged-KV round; vLLM/PagedAttention, cited in
ISSUE.md/ROADMAP item 2).

The engine's original memory model reserved worst-case bytes per
request: a ``(L, max_slots, H_kv, max_len, D)`` slot arena where every
slot owns ``max_len`` positions whether its request uses 20 of them or
all of them, plus a SECOND pool for the prefix cache's blocks
(serve/prefix.py).  Short requests therefore wasted most of the arena
and ``max_slots`` capped concurrency far below what the bytes could
carry.  This module collapses both into one pool:

* **block pool** — one preallocated arena of ``num_blocks`` KV blocks
  per K/V, stored a token a row: ``(L, num_blocks + 1, block_size,
  H_kv·D)`` (the +1 is the trash block scatter padding lands in,
  prefix.py's idiom) — a block is ``block_size`` rows, a row one
  position's keys (or values) of all K/V heads side by side, so rows
  fill whole 128-lane tiles (1280 lanes for gpt2-large, 512 for
  Falcon-H1) and the array holds exactly its data, no padding.
  Leaves are PYTREE-GENERIC: a dense pool is one array per K/V, an
  int8 pool is a ``(values, scales (L, num_blocks + 1, block_size,
  H_kv))`` tuple — every copy helper below tree-maps over both, which
  is what lifts the old ``int8 + prefix-cache`` refusal.  The layout
  and the one way programs reach into it (the slab ``pool[layer,
  blk]`` read in the block loop; whole blocks read, changed and
  scattered back at ``pool.at[layer, dst]``, a layer at a time) live in
  ops/paged_attention.py: together they let the compiler update a
  donated pool where it lies — no decode step, admission scatter or
  block copy re-lays or copies it (tests/test_tpu_compile.py compiles
  them for a v5e at gpt2-large's sizes).  A family whose cache is one
  row a position (``ServedFamily.value_leaf`` false: a latent that is
  key and value at once) gets ONE pool: ``pool_v`` is ``None``, an
  empty pytree that every copy helper below carries along untouched;
* **block tables** — a live request's KV is a per-slot block LIST
  grown block-by-block as decode advances.  Capacity is "blocks free",
  not "slots free": a 20-token request holds one block, not a
  ``max_len`` row, so far more requests fit the same bytes;
* **paged pool step** — ONE implementation, block-native: the
  decode program (``_paged_decode_kernel``, through the family's
  ``decode_step``; GPT-2's is ``gpt2_decode.decode_step_paged``) and
  the speculative program (``_paged_spec_kernel``, through
  ``gpt2_decode.chunk_step_paged``) run flash-style online-softmax
  attention directly over the pool with the block table as the index
  structure — a ``fori_loop`` over each slot's live blocks (bound =
  the longest LIVE slot's block count, one traced scalar),
  running-max + rescaled-partial-sum accumulation, trash and
  beyond-``pos`` lanes masked, int8 dequantized per block inside the
  accumulator; the workspace is O(block_size) and the write-back is a
  read-modify-write, layer by layer, of the one or two blocks the
  step touched, so pool bytes round-trip exactly.  Token streams are
  pinned identical to the slot engine's, and logits allclose to the
  materialized-row math (``gpt2_decode.decode_step``): online softmax
  reorders the float reduction, so bitwise logit equality is
  impossible by construction (tests/test_paged.py).  The PERSISTENT
  KV allocation (what the capacity model counts) is the pool alone;
* **preemption / swap** — a request's blocks can be evicted to HOST
  memory mid-decode (``swap_out``: one fixed-shape gather + device
  sync) and restored later (``swap_in``: one scatter).  The copy is
  byte-exact, so a preempted-and-resumed request's remaining tokens
  are the ones the uninterrupted run would have produced — recompute
  through ``prefill_chunk`` could NOT promise that (decode-step KV
  drifts ~1e-6 from chunked prefill; see serve/prefix.py's
  canonical-KV analysis), which is why resume restores bytes and the
  chunked path is reserved for admissions;
* **unified prefix cache** — with ``prefix_cache=`` on a paged engine
  the radix tree allocates from THIS pool (``PrefixCache(arena=...)``):
  warm admission shares the matched blocks by reference (zero copy),
  retire donation ADOPTS the slot's private prompt blocks into the
  tree (zero copy — ``PrefixCache.adopt_blocks``), and cached-but-
  unreferenced blocks double as soft free space (``alloc`` evicts LRU
  leaves under pressure before failing).

Copy paths (gather/scatter/swap) check the ``serve.paged_copy`` fault
site (singa_tpu.resilience): an injected copy failure fails the engine
TYPED and the supervisor rebuild recovers (bench_chaos.py
``chaos_paged`` gates zero wedged/lost requests under a fault
mid-swap).

Metrics ride the process-wide observe registry as
``serve.paged.{blocks_free,blocks_used,preemptions,swap_in,swap_out}``
with the owning engine's label, and surface in
``health_report()["serve"]["paged"]``.

Compile capture: the paged pool steps dispatch through a small AOT
cache (:func:`_aot_call`) that lowers + compiles each new signature
once, records the XLA cost-analysis table and the program's
``temp_bytes`` / ``alias_bytes`` (``memory_analysis()``; also the gauge
``serve.paged.program_temp_bytes{program=}``) on a ``serve/compile``
trace span, and registers the tables with ``observe.monitor`` — so paged
executables show up in Chrome traces and crash bundles exactly like
``_GraphRunner`` train steps do (the VERDICT weak-#6 gap: serve-side
``jax.jit`` dispatches used to compile invisibly).
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..model import _cost_args
from ..models import gpt2_decode as _gpt2
from ..observe import monitor as _monitor
from ..observe import trace as _trace
from ..observe.registry import registry as _default_registry
from ..resilience import faults as _faults
from ..utils.logging import get_channel
from ..ops.paged_attention import (blocks_to_row, leaf_dims, pool_zeros,
                                   row_to_blocks, take_blocks)
from ..ops.sampling import select_sample as _select_sample

__all__ = ["PagedConfig", "PagedKVArena"]


@dataclass(frozen=True)
class PagedConfig:
    """Knobs for the paged KV arena (hand to
    ``model.serve(paged=...)``; the supervisor/fleet forward it
    verbatim, so every rebuilt replica allocates its own fresh pool).

    ``block_size``: tokens per KV block — the allocation granularity
    AND (when a prefix cache rides the same pool) the reuse
    granularity.  The engine requires ``max_len % block_size == 0``.
    ``num_blocks``: pool capacity in blocks; device memory is exactly
    ``2 * L * (num_blocks + 1) * block_size * H_kv * D`` elements (the
    +1 is the trash block; rows of ``H_kv * D`` lanes are not padded)
    — compare against the slot arena's ``2 * L * max_slots * max_len *
    H_kv * D`` to hold the byte budget fixed (docs/SERVING.md "Paged
    KV").
    ``prefill_token_budget``: the Sarathi-style chunked-prefill
    budget: at most this many prefill TOKENS per engine step, and a
    single admission whose prompt exceeds the budget is SPLIT across
    consecutive steps in block-multiple chunks (the engine's
    ``_chunk_row`` / ``gpt2_decode.prefill_chunk`` executables,
    chunk rows pinned bitwise against full prefill), so one 32k
    document admission can never stall the live decode lanes for
    more than one chunk's latency per step.  Must be a multiple of
    ``block_size``; None = off (whole-prompt admissions).
    docs/SERVING.md "Long-context serving" has the budget's
    semantics table."""

    block_size: int = 32
    num_blocks: int = 128
    prefill_token_budget: int | None = None

    def __post_init__(self):
        if self.block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {self.block_size}")
        if self.num_blocks < 1:
            raise ValueError(
                f"num_blocks must be >= 1, got {self.num_blocks}")
        if self.prefill_token_budget is not None:
            if self.prefill_token_budget < self.block_size \
                    or self.prefill_token_budget % self.block_size:
                raise ValueError(
                    f"prefill_token_budget "
                    f"({self.prefill_token_budget}) must be a "
                    f"positive multiple of block_size "
                    f"({self.block_size}): chunked prefill advances "
                    f"in block-width windows")


# -- pytree-generic fixed-shape copies ---------------------------------------
# Pool blocks <-> prefill rows.  How a pool is stored -- a token a row,
# ``(L, N+1, B, H_kv·D)``, scales ``(L, N+1, B, H_kv)`` -- is
# ops/paged_attention.py's business; the copies below gather or scatter
# whole blocks along axis 1 and turn them to and from the row's
# ``(L, 1, H_kv, W[, D])`` in the same program (``blocks_to_row`` /
# ``row_to_blocks``), tree-mapped over a dense leaf or the int8
# ``(values, scales)`` pair.  Shapes are keyed on (pool, row) geometry
# only, so each compiles once per engine geometry and serves any chain
# length (the index vector is always the full row's worth of lanes,
# unused lanes masked / pointed at the trash block).

def _leaf_to_row(pool, idx, n_used, head_dim):
    """One leaf's gather: pool blocks ``idx`` (nb,) -> (L, 1, H, W, ...)
    row, lanes >= ``n_used`` blocks zeroed (junk the chunked prefill and
    the decode position mask never read live).  ``head_dim`` 0 for a
    scales leaf (``leaf_dims``)."""
    row = blocks_to_row(take_blocks(pool, idx), head_dim)
    live = jnp.arange(row.shape[2]) < n_used * pool.shape[2]
    live = live.reshape((1, 1, -1) + (1,) * (row.ndim - 3))
    return jnp.where(live, row, 0)[:, None]      # (L, 1, H, W, ...)


def _leaf_to_pool(pool, rows, idx):
    """One leaf's scatter: the blocks of ``rows`` (L, R, H, W, ...) ->
    pool blocks ``idx`` (R * W//B,), row by row (lanes that should not
    store anything point at the trash block)."""
    return pool.at[:, idx].set(row_to_blocks(rows, pool.shape[2]))


def _tree_to_row(pool, idx, n_used, head_dim):
    return jax.tree.map(
        lambda p, d: _leaf_to_row(p, idx, n_used, d), pool,
        leaf_dims(pool, head_dim))


@partial(jax.jit, static_argnames=("head_dim",))
def _pool_to_row(pool_k, pool_v, idx, n_used, head_dim):
    """Gather ``idx`` (nb,) pool blocks into fresh (L, 1, H, W, ...)
    cache rows, tree-mapped over dense or (values, scales) pools."""
    return (_tree_to_row(pool_k, idx, n_used, head_dim),
            _tree_to_row(pool_v, idx, n_used, head_dim))


@partial(jax.jit, donate_argnums=(0, 1))
def _row_to_pool(pool_k, pool_v, kc_row, vc_row, idx):
    """Scatter cache-row lanes into the pool at ``idx``; pools DONATED
    (the caller rebinds) so a donation/swap is a scatter in place, not
    an O(pool) copy."""
    scatter = partial(_leaf_to_pool, idx=idx)
    return (jax.tree.map(scatter, pool_k, kc_row),
            jax.tree.map(scatter, pool_v, vc_row))


@partial(jax.jit, donate_argnums=(0, 1))
def _copy_pool_block(pool_k, pool_v, src, dst):
    """Copy ONE block's bytes ``src`` -> ``dst`` inside the pool (both
    traced ints — one executable per engine geometry).  The
    copy-on-first-write path of KV forking: a forked branch about to
    write into a block a sibling still references gets its own byte
    copy first, so siblings never observe each other's writes."""
    cp = lambda p: p.at[:, dst].set(p[:, src])
    return jax.tree.map(cp, pool_k), jax.tree.map(cp, pool_v)


@partial(jax.jit, donate_argnums=(0, 1))
def _rows_to_pool(pool_k, pool_v, kc_rows, vc_rows, sel, idx):
    """Batched admission scatter (the gather-tax round): rows
    (L, R, H, W, ...) from ONE batched pass prefill, ``sel`` (R',)
    the successfully-admitted row indices, ``idx`` (R' * W//B,) the
    flattened per-row block targets (trash for unmapped lanes) — ONE
    donated scatter writes every admission of a scheduling pass, so
    K admissions stop costing the live decode lanes K dispatches."""
    def scatter(pool, rows):
        return _leaf_to_pool(pool, jnp.take(rows, sel, axis=1), idx)

    return (jax.tree.map(scatter, pool_k, kc_rows),
            jax.tree.map(scatter, pool_v, vc_rows))


# -- paged pool steps --------------------------------------------------------
# Attention runs directly over the pool (module docstring).  The block
# loop's bound is one traced scalar, so one executable serves every
# step; the lanes go through each layer's matmuls together and only
# the attention is per lane; each layer writes its new rows into the
# pool it carries (``write_rows``), so the donated pool is updated
# where it lies.

def _block_bounds(pos, live, block, window):
    """(clamped positions, the block loop's upper bound, its lower
    bound or None): the longest live lane's block count, and under a
    sliding window the lowest in-window block of any live lane."""
    p_all = jnp.where(live, pos, 0)
    n_blk = jnp.max((p_all + block - 1) // block)
    if window is None:
        return p_all, n_blk, None
    lo = jnp.maximum(0, (p_all - window + 1) // block)
    return p_all, n_blk, jnp.min(jnp.where(live, lo, n_blk))


@partial(jax.jit,
         static_argnames=("block", "n_head", "eps", "moe_top_k",
                          "top_k", "use_top_p", "window", "tp_axis",
                          "tp_world", "ep", "with_lp", "fam"),
         donate_argnums=(1, 2, 11))
def _paged_decode_kernel(params, pool_k, pool_v, tables, toks, pos,
                         live, keys, temps, top_p, masks=None,
                         state=None, slots=None,
                         block=None, n_head=None, eps=None,
                         moe_top_k=None, top_k=None, use_top_p=None,
                         window=None, tp_axis=None, tp_world=1,
                         ep=None, with_lp=False, *, fam):
    """Advance EVERY slot one token against the block pool, through
    the family's ``decode_step`` (models/served.py): per slot,
    online-softmax attention over its live blocks (beyond-``pos`` and
    trash lanes masked) plus the step's own K/V as the current lane,
    the new K/V written back into the block containing ``pos`` (dead
    slots write the trash block) — and,
    for a family with per-slot state, row ``slots[w]`` of each
    ``state`` arena read, advanced and written back (dead lanes: the
    trash row).  Then each lane samples from its logits (``masks``:
    None, or a (S, V) bool vocab-mask batch for constrained decoding —
    an all-True row is a bitwise no-op).  Returns (next_toks, pool_k,
    pool_v, new_keys[, logprobs (S,) when ``with_lp``][, state][, the
    family's step counts]).

    ``window`` (static): sliding-window decode (the long-context
    round) — each slot's query additionally masks pool lanes at
    positions <= pos - window, and the block loop STARTS at the
    lowest in-window block across live slots, so a windowed long
    chat's attention work is O(window) blocks regardless of how far
    ``pos`` has advanced (the engine drops fully-out-of-window
    blocks back to the free list host-side; their table entries are
    trash by then, so the bound is a work optimization, never a
    correctness input)."""
    trash = jax.tree.leaves(pool_k)[0].shape[1] - 1
    _, n_blk, blk_lo = _block_bounds(pos, live, block, window)
    logits, pool_k, pool_v, state, *counts = fam.decode_step(
        params, pool_k, pool_v, state, slots, tables, toks, pos, live,
        n_blk, block=block, trash=trash, n_head=n_head, eps=eps,
        moe_top_k=moe_top_k, window=window, blk_lo=blk_lo,
        tp_axis=tp_axis, tp_world=tp_world, ep=ep)

    def choose(logit, key, temp, mask_r):
        ks = jax.random.split(key)
        nxt = _select_sample(logit, ks[0], temp, top_k, top_p,
                             use_top_p, mask=mask_r)
        # chosen-token logprob under the RAW model distribution (the
        # fork round's ranking signal): an output, never an input
        lp = (jax.nn.log_softmax(logit.astype(jnp.float32))[nxt]
              if with_lp else jnp.float32(0.0))
        return nxt, ks[1], lp

    with jax.named_scope("sample"):
        nxt, keys2, lps = jax.vmap(
            choose, in_axes=(0, 0, 0, None if masks is None else 0))(
                logits, keys, temps, masks)
    out = (nxt, pool_k, pool_v, keys2)
    if with_lp:
        out += (lps,)
    if state is not None:
        out += (state,)
    # a family's counts about the step (``ServedFamily.step_counts``)
    return out + tuple(counts)


@partial(jax.jit,
         static_argnames=("block", "spec_k", "tn", "te", "tm", "dn",
                          "de", "dm", "top_k", "use_top_p", "window",
                          "tp_axis", "tp_world", "ep"),
         donate_argnums=(2, 3, 4, 5))
def _paged_spec_kernel(t_params, d_params, pool_k, pool_v, dkc, dvc,
                       tables, toks, pos, live, keys, temps, top_p,
                       block, spec_k, tn, te, tm, dn, de, dm, top_k,
                       use_top_p, window=None, tp_axis=None,
                       tp_world=1, ep=None):
    """Speculative chunk against the block pool, block-natively: the
    draft scan and verify are the slot spec step's, lane by lane
    (``gpt2_decode._draft_propose`` / ``spec_verify`` — the accept
    logic cannot drift); between them the TARGET advances every lane's
    chunk together through ``gpt2_decode.chunk_step_paged``:
    chunk-query online-softmax attention over the pool, each layer
    writing the chunk's rows into the one or two blocks they span
    (``spec_k <= block_size`` is validated at engine construction).
    The DRAFT arena stays slot-shaped (donated, advanced in lockstep —
    it is small by construction and carries no prefix cache).  Returns
    (out, a_draft, pool_k, pool_v, dkc, dvc, new_keys)."""
    trash = jax.tree.leaves(pool_k)[0].shape[1] - 1
    # the LOWEST query of a verify chunk is position pos itself, so the
    # decode kernel's lower bound covers every query
    p_c, n_blk, blk_lo = _block_bounds(pos, live, block, window)
    t_c = jnp.where(live, toks, 0)

    def draft(dkc_r, dvc_r, tok, pos_r, key, temp):
        k_draft, k_verify, k_next = jax.random.split(key, 3)
        props, d_probs, dkc_b, dvc_b = _gpt2._draft_propose(
            d_params, dkc_r, dvc_r, tok, pos_r, k_draft, temp, top_p,
            spec_k, dn, de, dm, top_k, use_top_p)
        return (props, d_probs, _gpt2._unbatch1(dkc_b),
                _gpt2._unbatch1(dvc_b), k_verify, k_next)

    props, d_probs, dkc, dvc, k_verify, keys2 = jax.vmap(
        draft, in_axes=(1, 1, 0, 0, 0, 0),
        out_axes=(0, 0, 1, 1, 0, 0))(dkc, dvc, t_c, p_c, keys, temps)
    chunk_toks = jnp.concatenate([t_c[:, None], props], axis=1)
    xs = (jnp.take(t_params["wte"], chunk_toks, axis=0)
          + jnp.take(t_params["wpe"],
                     p_c[:, None] + jnp.arange(spec_k), axis=0))
    lg, pool_k, pool_v = _gpt2.chunk_step_paged(
        t_params, xs, pool_k, pool_v, tables, p_c, live, n_blk, tn, te,
        block=block, trash=trash, moe_top_k=tm, window=window,
        blk_lo=blk_lo, tp_axis=tp_axis, tp_world=tp_world, ep=ep)
    out, a_draft = jax.vmap(
        lambda l, q, pr, k, t: _gpt2.spec_verify(
            l, q, pr, k, t, top_p, top_k, use_top_p))(
        lg, d_probs, props, k_verify, temps)
    return out, a_draft, pool_k, pool_v, dkc, dvc, keys2


# -- AOT compile capture -----------------------------------------------------
# Serve-side executables used to compile invisibly: no span, no cost
# table, nothing in crash bundles.  The paged steps dispatch through
# this cache instead — each new (function, shapes, statics) signature
# is lowered + compiled ONCE under a serve/compile span carrying the
# XLA cost-analysis scalars, and the tables feed monitor crash bundles
# through the registered cost source below.

_aot_cache = {}          # (name, leaf shapes/dtypes, statics) -> Compiled
_aot_costs = []          # [{"key": ..., "cost": {...}}] for crash bundles


def _paged_cost_tables():
    return list(_aot_costs)


_aot_scopes = {}         # program name -> {"<instruction> <result>": scope}
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = \(?(\w+\[[\d,]*\])[^\n]*'
    r'op_name="([^"]*)"', re.M)


def _keep_scopes(name, scopes, hlo_text):
    """Which instruction of a compiled program lies under which of the
    family's ``jax.named_scope`` names (``ServedFamily.scopes``), keyed
    by the instruction's name and result as a device trace prints them:
    a trace names operations, not scopes, so this is what lets a reader
    split a program's device time by mixer."""
    found = _aot_scopes.setdefault(name, {})
    for inst, result, op_name in _HLO_LINE.findall(hlo_text):
        parts = op_name.split("/")
        # the innermost of the family's scopes on the path
        scope = next((p for p in reversed(parts) if p in scopes), None)
        if scope is not None:
            found[f"{inst} {result}"] = scope


def program_scopes():
    """{program name: {"<instruction> <result>": scope}} of the paged
    programs compiled so far for families that name scopes."""
    return {k: dict(v) for k, v in _aot_scopes.items()}


_monitor.register_cost_source(_paged_cost_tables)


def _aot_call(name, fn, *args, _memo=None, _token=None, _run=True,
              **statics):
    """Dispatch ``fn(*args, **statics)`` through the AOT cache.  The
    compiled executable takes only the traced args (statics were
    consumed at lowering); the cache key mirrors jit's (placement +
    leaf shapes + dtypes + statics), so warm/timed engines, supervisor
    rebuilds, and same-device fleet replicas with identical geometry
    all share one compile —
    the same restart-is-a-cache-hit contract the jitted paths keep.
    ``_memo``/``_token``: optional caller-owned signature memo — an
    engine's dispatch shapes are FIXED per (step, batch width), so
    the executor caches the expensive leaf-shape key under a cheap
    token instead of re-walking ~80 param leaves every decode step
    (a measurable host tax on the per-step path).
    ``_run=False`` compiles and does not dispatch: ``args`` may then be
    ``jax.ShapeDtypeStruct`` objects (with the sharding a committed argument
    will have), and the program waits in the cache under the key its
    first real call computes."""
    key = _memo.get(_token) if _memo is not None else None
    if key is None:
        leaves = jax.tree.leaves(args)
        # the weights' placement is part of the key: a compiled
        # executable is bound to its devices, so a replica on another
        # chip compiles its own instead of being handed this one
        key = (name, leaves[0].sharding,
               tuple((tuple(a.shape), str(a.dtype)) for a in leaves),
               tuple(sorted(statics.items())))
        if _memo is not None:
            _memo[_token] = key
    entry = _aot_cache.get(key)
    if entry is None:
        with _trace.span("serve/compile", cat="serve", fn=name) as sp:
            # a lowering/compile failure surfaces here as itself
            entry = fn.lower(*args, **statics).compile()
            scalars = _cost_args(entry.cost_analysis())
            _aot_costs.append(
                {"key": f"serve.paged/{name}", "cost": scalars})
            # what the program keeps beside its arguments, and how much
            # of them it updates where they lie: a step that stopped
            # being in place (a pool re-laid, a pool copied) says so
            # here, at set-up, on any backend
            mem = entry.memory_analysis()
            if mem is not None:
                scalars = dict(scalars,
                               temp_bytes=mem.temp_size_in_bytes,
                               alias_bytes=mem.alias_size_in_bytes)
                _default_registry().gauge(
                    "serve.paged.program_temp_bytes",
                    help="temporaries of the newest compile of a paged "
                         "program (memory_analysis): device bytes it "
                         "needs beside its arguments",
                    program=name).set(mem.temp_size_in_bytes)
            sp.set(**scalars)
            scopes = getattr(statics.get("fam"), "scopes", ())
            if scopes:
                _keep_scopes(name, scopes, entry.as_text())
        _aot_cache[key] = entry
    return entry(*args) if _run else None


def _compile_cache_size():
    """Entries in the paged AOT cache — counted alongside the jitted
    functions' ``_cache_size()`` by ``bench_serve._serve_jit_cache_size``
    so the no-runtime-recompiles pin covers the paged dispatch path
    too."""
    return len(_aot_cache)


# -- the arena ---------------------------------------------------------------

class PagedKVArena:
    """Host-side owner of the block pool: free list, block accounting,
    the copy entry points the engine drives, swap buffers, and
    metrics.  Allocation is block-granular, so there is no external
    fragmentation by construction — any ``n`` free blocks satisfy any
    ``n``-block request (tests/test_paged.py churn-checks the
    accounting invariant ``free + used == num_blocks`` with cached
    blocks counted in ``used``)."""

    def __init__(self, config, n_layer, n_kv_head, head_dim, dtype,
                 row_width, quant=False, engine_label="0", reg=None,
                 tp=None, sharding=None, value_leaf=True):
        self.config = config
        B, N = config.block_size, config.num_blocks
        self.block_size = B
        self.num_blocks = N
        self.trash = N
        if row_width % B != 0:
            raise ValueError(
                f"row width ({row_width}) must be a multiple of "
                f"block_size ({B})")
        self.row_blocks = row_width // B
        self.quant = bool(quant)
        self.head_dim = int(head_dim)
        # tensor-parallel executor (serve/tp.py): the pool leaves are
        # placed SHARDED over the tp mesh on their last axis (each
        # shard owns the rows of its H_kv/tp contiguous heads,
        # (L, N+1, B, H_kv/tp·D), + its scales slice) and the
        # gather/scatter/swap copies dispatch through the executor's
        # sharded twins.  Host-side block accounting is untouched —
        # block ids are the same on every shard
        self._tp = tp

        def pool():
            # an unsharded engine hands its weights' ``sharding`` so
            # the pool is born beside them; a tp executor lays it out
            z = pool_zeros(n_layer, N + 1, B, n_kv_head, head_dim,
                           dtype, quant, sharding)
            return z if tp is None else tp.place_pool(z)

        self.pool_k = pool()
        # a one-leaf cache (``ServedFamily.value_leaf`` false) has no
        # value pool: None, an empty pytree, rides every copy below
        self.pool_v = pool() if value_leaf else None
        self._free = list(range(N))
        # LIVE-slot reference counts (the fork round): a block a forked
        # branch shares with its siblings carries an entry here (count
        # >= 2; allocated-but-unshared blocks have an implicit count of
        # 1 and no entry).  ``free`` decrements and only returns a
        # block to the free list at count 1 — existing callers see the
        # historical free() exactly when nothing is forked.  Disjoint
        # from the prefix tree's node refs by construction: tree-owned
        # (cached) blocks are never arena-shared, live tails are never
        # tree-owned until retire adoption (which is capped below the
        # first shared block by the engine).
        self._refs = {}
        # soft free space: the engine wires this to the prefix cache's
        # LRU leaf eviction so cached-but-unreferenced blocks are
        # reclaimed before an allocation fails
        self.evict_cb = None
        self._log = get_channel("serve")
        reg = reg if reg is not None else _default_registry()
        lbl = dict(engine=engine_label)
        self._g_free = reg.gauge(
            "serve.paged.blocks_free",
            help="pool blocks on the free list", **lbl)
        self._g_used = reg.gauge(
            "serve.paged.blocks_used",
            help="pool blocks held by live slots or the prefix cache "
                 "(a swapped-out request holds NONE — its blocks were "
                 "freed at preemption and resume re-allocates its "
                 "full need)", **lbl)
        self._c_preempt = reg.counter(
            "serve.paged.preemptions",
            help="live requests preempted (blocks evicted to host)",
            **lbl)
        self._c_swap_out = reg.counter(
            "serve.paged.swap_out",
            help="request KV rows copied device -> host", **lbl)
        self._c_swap_in = reg.counter(
            "serve.paged.swap_in",
            help="request KV rows restored host -> device", **lbl)
        self._c_window_drop = reg.counter(
            "serve.paged.window_drops",
            help="out-of-window blocks a sliding-window slot dropped "
                 "back to the free list as its position advanced "
                 "(the O(window) memory model's reclaim path)", **lbl)
        self.window_drops = 0
        self._registered = [self._g_free, self._g_used, self._c_preempt,
                            self._c_swap_out, self._c_swap_in,
                            self._c_window_drop]
        self._registry = reg
        self._update_gauges()

    # -- accounting ------------------------------------------------------
    @property
    def blocks_free(self) -> int:
        return len(self._free)

    @property
    def blocks_used(self) -> int:
        return self.num_blocks - len(self._free)

    def _update_gauges(self):
        self._g_free.set(self.blocks_free)
        self._g_used.set(self.blocks_used)

    def alloc(self, n) -> list | None:
        """``n`` pool blocks, or None — all or nothing, so a partial
        grab can never strand a request mid-allocation.  Under
        pressure the prefix cache's LRU leaves are evicted first
        (``evict_cb``); evicted blocks stay freed even when the
        request ultimately does not fit."""
        while len(self._free) < n and self.evict_cb is not None:
            blk = self.evict_cb()
            if blk is None:
                break
            self._free.append(blk)
        if len(self._free) < n:
            self._update_gauges()
            return None
        out = [self._free.pop() for _ in range(n)]
        self._update_gauges()
        return out

    def free(self, blocks):
        """Release ``blocks``: a block no live reference still shares
        returns to the free list; a SHARED block (a forked sibling
        still holds it) just sheds one reference — bytes stay put
        until the last holder frees it.  With no forks in flight this
        is exactly the historical extend-the-free-list."""
        if not self._refs:
            self._free.extend(blocks)
            self._update_gauges()
            return
        for b in blocks:
            c = self._refs.get(b)
            if c is None:
                self._free.append(b)
            elif c <= 2:
                del self._refs[b]
            else:
                self._refs[b] = c - 1
        self._update_gauges()

    # -- live-slot sharing (the fork round) ------------------------------
    @property
    def shared_blocks(self) -> int:
        """Blocks currently referenced by MORE than one live slot."""
        return len(self._refs)

    def share(self, blocks):
        """Add one live reference to each of ``blocks`` (a fork's
        block-table copy: the child's table points at the parent's
        blocks; nothing moves on device)."""
        for b in blocks:
            self._refs[b] = self._refs.get(b, 1) + 1

    def is_shared(self, block) -> bool:
        return block in self._refs

    def ref_count(self, block) -> int:
        return self._refs.get(block, 1)

    def copy_block(self, src, dst):
        """Copy ``src``'s bytes into ``dst`` — the copy-on-first-write
        of a forked branch about to write into a block a sibling still
        references.  Checks the ``serve.fork_copy`` fault site (the
        chaos_fork scenario's injection point: a raising copy rejects
        ONLY the writing branch; siblings keep their intact bytes)."""
        if _faults._armed:
            _faults.check("serve.fork_copy")
        if self._tp is not None:
            # fork is typed-rejected on sharded executors at submit;
            # reaching here means a caller bypassed validation
            raise RuntimeError(
                "copy_block on a tensor-parallel pool: KV forking "
                "requires the default executor")
        self.pool_k, self.pool_v = _copy_pool_block(
            self.pool_k, self.pool_v, jnp.int32(src), jnp.int32(dst))

    # -- device copies ---------------------------------------------------
    def _pad_idx(self, blocks):
        idx = np.full(self.row_blocks, self.trash, np.int32)
        idx[:len(blocks)] = blocks
        return jnp.asarray(idx)

    def gather_row(self, blocks, n_used=None):
        """Fixed-shape row holding ``blocks``' contents at lanes
        [0, len(blocks)); lanes >= ``n_used`` (default: all of them)
        zeroed.  One executable for every chain length."""
        if _faults._armed:
            _faults.check("serve.paged_copy")
        n = len(blocks) if n_used is None else n_used
        if self._tp is not None:
            return self._tp.pool_to_row(self.pool_k, self.pool_v,
                                        self._pad_idx(blocks),
                                        jnp.int32(n))
        return _pool_to_row(self.pool_k, self.pool_v,
                            self._pad_idx(blocks), jnp.int32(n),
                            head_dim=self.head_dim)

    def scatter_row(self, kc_row, vc_row, lanes):
        """Write row lanes into pool blocks: ``lanes`` maps lane index
        -> block id; unmapped lanes point at the trash block.  One
        donated scatter — the pool updates in place.  The lane count
        comes off the ROW's own width, so NARROW rows (the paged
        cold-admission fast path prefills at the smallest
        block-multiple width covering the prompt, not max_len) scatter
        through the same entry point."""
        if _faults._armed:
            _faults.check("serve.paged_copy")
        row_w = jax.tree.leaves(kc_row)[0].shape[3]
        idx = np.full(row_w // self.block_size, self.trash, np.int32)
        for lane, blk in lanes.items():
            idx[lane] = blk
        if self._tp is not None:
            self.pool_k, self.pool_v = self._tp.row_to_pool(
                self.pool_k, self.pool_v, kc_row, vc_row,
                jnp.asarray(idx))
            return
        self.pool_k, self.pool_v = _row_to_pool(
            self.pool_k, self.pool_v, kc_row, vc_row, jnp.asarray(idx))

    def scatter_rows(self, kc_rows, vc_rows, sel, lanes_list):
        """Batched admission scatter: ``kc_rows``/``vc_rows`` the
        (L, R, H, W, ...) stacked rows of one pass prefill, ``sel``
        the admitted row indices, ``lanes_list`` one lane->block dict
        per selected row.  ONE device dispatch for the whole pass
        (``_rows_to_pool``); one ``serve.paged_copy`` policy tick —
        one logical admission write."""
        if _faults._armed:
            _faults.check("serve.paged_copy")
        row_w = jax.tree.leaves(kc_rows)[0].shape[3]
        nb = row_w // self.block_size
        idx = np.full(len(sel) * nb, self.trash, np.int32)
        for r, lanes in enumerate(lanes_list):
            for lane, blk in lanes.items():
                idx[r * nb + lane] = blk
        if self._tp is not None:
            self.pool_k, self.pool_v = self._tp.rows_to_pool(
                self.pool_k, self.pool_v, kc_rows, vc_rows,
                jnp.asarray(np.asarray(sel, np.int32)),
                jnp.asarray(idx))
            return
        self.pool_k, self.pool_v = _rows_to_pool(
            self.pool_k, self.pool_v, kc_rows, vc_rows,
            jnp.asarray(np.asarray(sel, np.int32)), jnp.asarray(idx))

    # -- swap / ship images ----------------------------------------------
    # Both host-image paths — preemption swap AND fleet KV shipping —
    # produce/consume the SAME versioned serve/kvimage.py format, so
    # the two cannot drift and a truncated or geometry-mismatched
    # image fails typed before any scatter touches the pool.

    def swap_out(self, blocks, n_data) -> "KVImage":
        """Copy ``blocks``' first ``n_data`` lanes to HOST memory (one
        gather + device sync) — the preemption path.  Returns a
        full-row-width :class:`~singa_tpu.serve.kvimage.KVImage` (one
        gather executable per engine geometry, the historical swap
        shape)."""
        from .kvimage import pack_image

        kc_row, vc_row = self.gather_row(blocks, n_used=n_data)
        self._c_swap_out.inc()
        return pack_image(jax.tree.map(np.asarray, kc_row),
                          jax.tree.map(np.asarray, vc_row),
                          block_size=self.block_size, n_data=n_data,
                          quant=self.quant)

    def swap_in(self, image, blocks):
        """Restore a swapped-out image's lanes into freshly allocated
        ``blocks`` (one scatter — ``scatter_row`` carries the
        ``serve.paged_copy`` fault check, so one logical restore is
        one policy tick).  The image validates against THIS pool's
        geometry first (:class:`~singa_tpu.serve.kvimage.KVImageError`
        on any mismatch — never scatters garbage).  Byte-exact: the
        resumed request's cache state is exactly what swap_out
        saved."""
        image.validate(self.block_size, self.quant,
                       pool_k=self.pool_k, head_dim=self.head_dim)
        self._c_swap_in.inc()
        self.scatter_row(jax.tree.map(jnp.asarray, image.kc),
                         jax.tree.map(jnp.asarray, image.vc),
                         {j: b for j, b in enumerate(blocks)})

    def export_image(self, blocks, n_data) -> "KVImage":
        """Gather ``blocks``' first ``n_data`` lanes into a NARROW
        host image (``n_data * block_size`` lanes — ship bytes track
        the shipped prefix, not ``max_len``): the KV-shipping source
        path.  Packs directly (NOT via :meth:`swap_out` — the
        ``serve.paged.swap_out`` counter means preemption pressure
        and must not absorb ship traffic).  Checks the
        ``serve.kv_ship`` fault site — an injected mid-ship failure
        raises typed and the fleet requeues the request
        cold-but-correct."""
        from .kvimage import pack_image

        if _faults._armed:
            _faults.check("serve.kv_ship")
        kc_row, vc_row = self.gather_row(blocks, n_used=n_data)
        img = pack_image(jax.tree.map(np.asarray, kc_row),
                         jax.tree.map(np.asarray, vc_row),
                         block_size=self.block_size, n_data=n_data,
                         quant=self.quant)
        return img.narrowed()

    def export_row_image(self, kc_row, vc_row, n_data) -> "KVImage":
        """Build a narrow ship image straight from a device cache ROW
        (the prefill-specialist path when pool pressure skipped the
        donation: the chunked row is the only copy).  Same fault site
        and format as :meth:`export_image`."""
        from .kvimage import pack_image

        if _faults._armed:
            _faults.check("serve.kv_ship")
        img = pack_image(jax.tree.map(np.asarray, kc_row),
                         jax.tree.map(np.asarray, vc_row),
                         block_size=self.block_size, n_data=n_data,
                         quant=self.quant)
        return img.narrowed()

    def import_image(self, image, lanes):
        """Scatter a validated ship image's lanes into pool blocks:
        ``lanes`` maps lane index -> block id (lanes below a local
        prefix hit are simply absent — their bytes never move).  The
        ``serve.kv_ship`` fault site covers the destination half of a
        ship; validation runs BEFORE the fault check so a malformed
        image is always the typed :class:`KVImageError`, never a
        chaos artifact."""
        image.validate(self.block_size, self.quant,
                       pool_k=self.pool_k, head_dim=self.head_dim)
        if _faults._armed:
            _faults.check("serve.kv_ship")
        self.scatter_row(jax.tree.map(jnp.asarray, image.kc),
                         jax.tree.map(jnp.asarray, image.vc),
                         dict(lanes))

    def on_preempt(self):
        self._c_preempt.inc()

    def on_window_drop(self, n):
        """Account ``n`` out-of-window blocks freed by a windowed
        slot's advance (the engine already returned them via
        :meth:`free`)."""
        self.window_drops += n
        self._c_window_drop.inc(n)

    # -- lifecycle / reporting -------------------------------------------
    def unregister(self):
        """Release registry entries and the device pool (engine
        close())."""
        self._registry.remove(*self._registered)
        self.pool_k = self.pool_v = None

    def snapshot(self) -> dict:
        return {
            "block_size": self.block_size,
            "num_blocks": self.num_blocks,
            "blocks_free": self.blocks_free,
            "blocks_used": self.blocks_used,
            "preemptions": self._c_preempt.value,
            "swap_out": self._c_swap_out.value,
            "swap_in": self._c_swap_in.value,
            "quant": self.quant,
            "shared_blocks": self.shared_blocks,
        }
