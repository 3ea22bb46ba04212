"""Continuous-batching inference engine over the KV-cached GPT-2
decoder (the Orca/vLLM iteration-level scheduler, shape-stable for
TPU; round 6).

The offline path (models/gpt2_decode.generate) assembles one static
batch and runs prefill + a compiled scan to the LAST row's length:
every caller blocks until the slowest row finishes, and a new prompt
cannot enter until the whole batch drains.  This engine inverts that
control flow:

* **slot pool** — a fixed pool of ``max_slots`` rows backed by ONE
  preallocated KV-cache arena of shape ``(L, max_slots, H_kv,
  max_len, D)`` per K/V.  Every jitted function below is keyed only on
  ``(max_slots, max_len)`` and the model statics, so the engine NEVER
  recompiles at runtime — admission, decode, and retirement all happen
  inside the same three executables;
* **iteration-level step loop** — each ``step()`` advances every live
  slot by one token (one batched call over the whole pool), retires
  rows that hit their token budget IMMEDIATELY, and backfills the
  freed slots from the scheduler queue in the SAME step (prefill one
  row, write it into the arena at the free slot index);
* **exactness** — a slot runs the same per-row math as single-prompt
  ``generate``: prefill over a (1, max_len) padded row, then
  gpt2_decode.decode_step per token, with the request's private
  sampling-key chain split exactly as the offline path splits it.
  tests/test_serve.py asserts token-for-token identity against
  ``generate`` for greedy AND seeded-sampling requests.

Why it wins: the static batch pays ``Σ_batches max(new_tokens)``
pool-wide steps while the engine pays ~``Σ new_tokens / max_slots`` —
the gap is the per-batch straggler tail plus the slots that sat idle
behind it (bench_serve.py measures it on a ragged workload).

Fast decode (perf round): the offline path's measured decode wins now
run inside the engine too —

* **int8 KV arenas** (``cache_dtype="int8"``): the pool arena stores
  (int8 values, f32 per-(token, head) scales) tuples, halving cache
  bytes on a cache-read-bound loop; every executable is shape-agnostic
  between dense and quantized arenas (pytree-mapped), and engine
  streams are byte-identical to offline ``generate(...,
  cache_dtype="int8")``;
* **speculative decoding** (``draft_model=``, ``spec_k=``): each
  ``step()`` runs spec_k sequential DRAFT decode steps and ONE target
  chunk verify (``_advance_chunk`` — a single cache read serves spec_k
  positions), emitting up to spec_k tokens per step.  Greedy requests
  accept by argmax match (byte-identical streams to non-speculative
  serve, same near-tie caveat as ``generate_speculative``); sampled
  requests go through rejection sampling (``gpt2_decode.spec_verify``:
  accept with min(1, p/q), resample the residual) so every emitted
  token is distributed exactly as direct target sampling.  Multi-token
  steps change the downstream accounting: retire fires per TOKEN
  (budget/stop mid-chunk), ``on_token`` streams per accepted token,
  and TPOT becomes tokens-per-step aware (stats.py).

Paged KV (memory-model round): ``paged=PagedConfig(...)`` swaps the
worst-case slot arena for ONE block-paged pool (serve/paged.py)
shared with the prefix cache — a request's KV is a block list grown
as decode advances, admission is bounded by blocks free rather than
slots free, and pool pressure PREEMPTS (swap a request's blocks to
host byte-exactly, resume later) instead of stalling.  The paged pool
steps attend block-natively over the pool (online softmax), so the two
memory models produce token-identical streams.

Long-context serving (the long-context round; docs/SERVING.md
"Long-context serving"):

* **chunked-prefill token budget**
  (``PagedConfig(prefill_token_budget=)``): a Sarathi-style per-step
  prefill TOKEN budget — an admission whose prompt exceeds it splits
  across consecutive steps, ONE ``_chunk_row`` launch a request a
  step, as wide as the step's budget allows (any whole number of
  blocks, and two requests' pieces of one pass in one launch where
  the budget is four blocks or more: a launch reads the layers'
  weights once whatever it covers; every shape is compiled when the
  engine is built), so one 32k document admission never stalls the live decode
  lanes for more than one step's budget (the request ledger's stall
  phase is the proof metric);
* **windowed paged decode**: sliding-window models
  (``GPT2Config(attn_window=W)``) serve on the PAGED engine — block
  tables drop fully-out-of-window blocks back to the free list as
  ``pos`` advances, so a long chat holds O(window) blocks whatever
  its length, and the block-native kernel masks + loop-bounds the
  attention to the window;
* **ring-attention prefill** (``TPConfig(ring_prefill=True)``): cold
  long-prompt admissions on a TP engine prefill SEQUENCE-sharded
  over the mesh (parallel/ring_attention.py), for prompts beyond one
  shard's flash tile.

Scope: dense/GQA/MoE models (everything _advance_one supports with a
position-indexed dense cache).  Sliding-window models serve in paged
mode only (windowed without ``paged=`` and windowed + prefix cache
stay rejected typed);
repetition_penalty/min_p are offline-only knobs.  int8 arenas compose
with the prefix cache since the paged round (pytree-generic block
pools; cache-enabled int8 engines route every admission through the
chunked canonical form — see _admit).
"""

from __future__ import annotations

import inspect
import itertools
import math
import time
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# the model's math comes through ``model.served_family()``
# (models/served.py) on the paged path; ``_gpt2`` is named only by the
# paths no other family has yet: the slot arena, whole-prompt prefill,
# speculation, and the sharded executors' per-row twins
from ..models import gpt2_decode as _gpt2
from ..models.served import FEATURES, Segment as _Segment
from ..ops.paged_attention import decode_attn_impl as _decode_attn_impl
from ..ops.sampling import select_sample as _select_sample
from ..observe import monitor as _monitor
from ..observe import requests as _reqs
from ..observe import stepprof as _stepprof
from ..observe import trace as _trace
from ..resilience import faults as _faults
from ..utils.logging import get_channel
from .fork import BranchHandle, ForkHandle
from .paged import (PagedConfig, PagedKVArena, _aot_call,
                    _paged_decode_kernel, _paged_spec_kernel)
from .prefix import (PrefixCache, PrefixCacheConfig, SessionHandle,
                     _kv_zeros, _read_slot)
from .request import (DeadlineExceededError, EngineFailedError,
                      GenerationRequest, GenerationResult, LoadShedError,
                      RequestHandle)
from .scheduler import FIFOScheduler, PriorityScheduler
from .stats import EngineStats


def _decode_row(params, kc_r, vc_r, tok, pos_r, live_r, key, temp,
                top_p, n_head, eps, moe_top_k, top_k, use_top_p,
                tp_axis=None, tp_world=1, ep=None):
    """ONE slot's decode-step math — kc_r/vc_r: (L, H_kv, max_len, D)
    cache rows (int8 arenas are (values, scales) pytrees, so the
    batch-axis insert/strip is tree-mapped rather than indexed).
    ``tp_axis``/``tp_world`` thread the tensor-parallel mesh axis
    through (serve/tp.py's sharded twins; defaults leave the serial
    math bit-identical)."""
    p_c = jnp.where(live_r, pos_r, 0)
    t_c = jnp.where(live_r, tok, 0)
    x = (params["wte"][t_c] + params["wpe"][p_c])[None, None, :]
    logits, kc2, vc2 = _gpt2.decode_step(
        params, x, jax.tree.map(lambda a: a[:, None], kc_r),
        jax.tree.map(lambda a: a[:, None], vc_r), p_c, n_head, eps,
        moe_top_k=moe_top_k, tp_axis=tp_axis, tp_world=tp_world,
        ep=ep)
    ks = jax.random.split(key)
    nxt = _select_sample(logits[0], ks[0], temp, top_k, top_p,
                         use_top_p)
    return (nxt, jax.tree.map(lambda a: a[:, 0], kc2),
            jax.tree.map(lambda a: a[:, 0], vc2), ks[1])


@partial(jax.jit,
         static_argnames=("n_head", "eps", "moe_top_k", "top_k",
                          "use_top_p", "tp_axis", "tp_world"),
         donate_argnums=(1, 2))
def _pool_decode_step(params, kc, vc, toks, pos, live, keys, temps,
                      top_p, n_head, eps, moe_top_k, top_k, use_top_p,
                      tp_axis=None, tp_world=1):
    """Advance EVERY slot one token: toks/pos/live/temps (S,), keys
    (S, 2), arenas (L, S, H_kv, max_len, D) — donated, so the arena
    updates in place across steps.  Dead slots run the same math on
    clamped inputs (fixed shapes; their cache rows are garbage that
    the next admission's full-row prefill write overwrites) and their
    outputs are ignored host-side.  Returns (next_toks, kc, vc,
    new_keys)."""

    def row(kc_r, vc_r, tok, pos_r, live_r, key, temp):
        return _decode_row(params, kc_r, vc_r, tok, pos_r, live_r,
                           key, temp, top_p, n_head, eps, moe_top_k,
                           top_k, use_top_p, tp_axis=tp_axis,
                           tp_world=tp_world)

    return jax.vmap(row, in_axes=(1, 1, 0, 0, 0, 0, 0),
                    out_axes=(0, 1, 1, 0))(kc, vc, toks, pos, live,
                                           keys, temps)


@partial(jax.jit,
         static_argnames=("n_head", "eps", "moe_top_k", "top_k",
                          "use_top_p", "quant", "window", "tp_axis",
                          "tp_world"))
def _prefill_one(params, ids, prompt_len, key, temp, top_p, n_head,
                 eps, moe_top_k, top_k, use_top_p, quant=False,
                 window=None, tp_axis=None, tp_world=1, ep=None,
                 mask=None):
    """Admission prefill for ONE request: ids (1, max_len)
    right-padded.  Returns (first token, carried key, kc_row, vc_row)
    with cache rows (L, 1, H_kv, max_len, D) ready to write into the
    arena ((values, scales) tuples when ``quant`` — the int8 arena
    mode).  ``prompt_len`` is traced, so every admission reuses one
    executable regardless of prompt length.  ``window``: banded
    (sliding-window) prefill with a LINEAR cache layout
    (``rolling=False`` — the paged engine's block tables address
    positions directly; the offline rolling layout would scramble
    them)."""
    hidden, kc, vc = _gpt2.prefill(params, ids, n_head, eps,
                             moe_top_k=moe_top_k, quant_cache=quant,
                             window=window, rolling=False,
                             tp_axis=tp_axis, tp_world=tp_world,
                             ep=ep)
    last_h = jax.lax.dynamic_index_in_dim(
        hidden, prompt_len - 1, axis=1, keepdims=False)      # (1, E)
    logit0 = _gpt2._logits(last_h[:, None, :], params)[0, 0]  # (V,)
    ks = jax.random.split(key)
    tok0 = _select_sample(logit0, ks[0], temp, top_k, top_p, use_top_p,
                          mask=mask)
    return tok0, ks[1], kc, vc


@partial(jax.jit,
         static_argnames=("n_head", "eps", "moe_top_k", "top_k",
                          "use_top_p", "quant", "window", "tp_axis",
                          "tp_world"))
def _prefill_batch(params, ids, plens, seeds, temps, top_p, n_head,
                   eps, moe_top_k, top_k, use_top_p, quant=False,
                   window=None, tp_axis=None, tp_world=1, ep=None):
    """BATCHED cold admission (the gather-tax round): R requests'
    prefills in ONE dispatch — ids (R, W) right-padded at the pass's
    shared narrow width, plens/seeds/temps (R,).  vmaps the exact
    :func:`_prefill_one` row body (key chain included: PRNGKey(seed)
    -> split -> sample/carry, moved inside the executable), so every
    row's (tok0, carried key, cache rows) is BITWISE the per-request
    call's — pinned by tests/test_paged.py::test_prefill_batch
    _bitwise_equals_single.  One scheduling pass that admits K
    requests pays one dispatch + one host sync instead of K, which
    is what keeps an arrival burst from stalling live decode lanes
    (the paged bench's TPOT tax).  Returns (tok0 (R,), keys (R, 2),
    kc rows (L, R, H, W, D), vc rows) — the caller scatters each
    row's lanes into its freshly-allocated blocks."""
    def row(ids_r, plen, seed, temp):
        key0 = jax.random.split(jax.random.PRNGKey(seed), 1)[0]
        return _prefill_one.__wrapped__(
            params, ids_r[None], plen, key0, temp, top_p, n_head,
            eps, moe_top_k, top_k, use_top_p, quant=quant,
            window=window, tp_axis=tp_axis, tp_world=tp_world, ep=ep)

    tok0, keys, kc, vc = jax.vmap(row, in_axes=(0, 0, 0, 0),
                                  out_axes=(0, 0, 1, 1))(
        ids, plens, seeds, temps)
    sq = lambda a: a[:, :, 0]   # drop the vmapped rows' B=1 axis
    return tok0, keys, jax.tree.map(sq, kc), jax.tree.map(sq, vc)


@partial(jax.jit,
         static_argnames=("n_head", "eps", "moe_top_k", "quant"))
def _prefill_rows(params, ids, n_head, eps, moe_top_k, quant=False):
    """DRAFT-side admission prefill: cache rows only, no sampling (the
    draft first proposes from the next spec step's state; the
    admission token is always the TARGET's, sampled by ``_prefill_one``
    / the warm path — which is what keeps spec admission tokens
    byte-identical to non-speculative admission)."""
    _, kc, vc = _gpt2.prefill(params, ids, n_head, eps,
                              moe_top_k=moe_top_k, quant_cache=quant)
    return kc, vc


def _launch_of(off, block):
    """``(first position, width)`` of the prefill window ``off`` names:
    a scalar is ONE block at that position; a vector of ``n`` block
    offsets is the ``n`` consecutive blocks from ``off[0]`` on.  The
    width rides in as the SHAPE of an argument, so every executor's
    program cache (jit's, the sharded twins', the AOT cache) keys on it
    by itself, and the one-block call is the call it always was."""
    if off.ndim:
        return off[0], block * off.shape[0]
    return off, block


@partial(jax.jit,
         static_argnames=("n_head", "eps", "moe_top_k", "chunk",
                          "window", "tp_axis", "tp_world", "fam"),
         donate_argnums=(2, 3))
def _chunk_row(params, ids, kc_row, vc_row, off, state=None,
               n_valid=None, *, n_head, eps, moe_top_k, chunk,
               window=None, tp_axis=None, tp_world=1, ep=None, fam):
    """Offset prefill of ONE launch through the family's ``chunk_row``
    (models/served.py): tokens at positions [off, off+width) of the
    padded ``ids`` row, advanced against a cache row that already holds
    canonical K/V below ``off`` and the per-slot ``state`` the window
    before left (None for a family that keeps none; with it
    ``n_valid``, how many of the window's tokens are the prompt's and
    not padding).  ``chunk`` (static) is the BLOCK; the window is one
    block at a scalar ``off`` and ``n`` blocks where ``off`` holds their
    ``n`` offsets (:func:`_launch_of`): the budgeted path launches as
    many blocks as the step's budget allows, every other caller one.
    ``off``'s value is traced, so every admission's every window of one
    width rides one executable.  Returns ((1, chunk, E) final-norm
    hidden of the window's LAST block, kc_row, vc_row[, state]) — rows
    donated, the admission loop rebinds.

    A launch of SEVERAL segments -- pieces of different requests, each
    against its own row and state -- has a tuple, an entry a segment,
    for every argument between ``params`` and the statics (``state``
    may stay None) and returns a tuple for each result.  A segment's
    ``off`` is then the offsets of its SLOT's blocks and its ``n_valid``
    says how many of the slot's tokens are real: the hidden block
    returned for it is the last one that holds a real token."""
    statics = dict(block=chunk, n_head=n_head, eps=eps,
                   moe_top_k=moe_top_k, window=window, tp_axis=tp_axis,
                   tp_world=tp_world, ep=ep)
    if isinstance(off, tuple):
        segs = [_Segment(i, k, v, s, *_launch_of(o, chunk), n)
                for i, k, v, s, o, n in zip(
                    ids, kc_row, vc_row, state or (None,) * len(off),
                    off, n_valid)]
        outs = fam.chunk_rows(params, segs, **statics)
        hidden, kc_row, vc_row, new = zip(*outs)
        hidden = tuple(
            jax.lax.dynamic_slice_in_dim(
                h, (sg.n_valid - 1) // chunk * chunk, chunk, axis=1)
            for h, sg in zip(hidden, segs))
        if state is None:
            return hidden, kc_row, vc_row
        return hidden, kc_row, vc_row, new
    off, width = _launch_of(off, chunk)
    hidden, kc_row, vc_row, state = fam.chunk_row(
        params, ids, kc_row, vc_row, state, off, n_valid, chunk=width,
        **statics)
    if width > chunk:
        # a prompt's last window ends with its last block, and only
        # that block's rows are ever sampled from (_first_from_hidden)
        hidden = hidden[:, width - chunk:]
    if state is None:
        return hidden, kc_row, vc_row
    return hidden, kc_row, vc_row, state


@partial(jax.jit, static_argnames=("top_k", "use_top_p", "fam"))
def _first_from_hidden(params, hidden, row, key, temp, top_p, top_k,
                       use_top_p, mask=None, *, fam):
    """Sample the admission token from a chunk's hidden block: row
    ``row`` of ``hidden`` (1, chunk, E) is position prompt_len-1.
    Mirrors the tail of ``_prefill_one`` exactly — same (1, 1, E)
    logits projection, same key split, same ``_select_sample`` — so a
    warm admission's first token matches the cold path's bit for bit
    given a bitwise-equal hidden row."""
    last_h = jax.lax.dynamic_index_in_dim(hidden, row, axis=1,
                                          keepdims=False)     # (1, E)
    logit0 = fam.logits(params, last_h[:, None, :])[0, 0]     # (V,)
    ks = jax.random.split(key)
    tok0 = _select_sample(logit0, ks[0], temp, top_k, top_p, use_top_p,
                          mask=mask)
    return tok0, ks[1]


def _spec_row(t_params, d_params, kc_r, vc_r, dkc_r, dvc_r, tok, pos_r,
              live_r, key, temp, top_p, spec_k, tn, te, tm, dn, de, dm,
              top_k, use_top_p, tp_axis=None, tp_world=1, ep=None):
    """ONE slot's speculative-chunk math: the shared draft proposal
    scan (``gpt2_decode._draft_propose``), then ONE target chunk advance
    (``_advance_chunk`` — a single cache read serves all ``spec_k``
    positions), then :func:`~singa_tpu.models.gpt2_decode.spec_verify`
    decides the accept count: greedy match for ``temp <= 0`` rows,
    rejection sampling with residual resample for sampled rows — both
    in the SAME executable (temp is traced, like ``_select_sample``).
    The paged spec program (``paged._paged_spec_kernel``) runs the same
    draft scan and verify per lane around a lane-batched block-native
    target chunk."""
    p_c = jnp.where(live_r, pos_r, 0)
    t_c = jnp.where(live_r, tok, 0)
    k_draft, k_verify, k_next = jax.random.split(key, 3)
    props, d_probs, dkc_b, dvc_b = _gpt2._draft_propose(
        d_params, dkc_r, dvc_r, t_c, p_c, k_draft, temp, top_p,
        spec_k, dn, de, dm, top_k, use_top_p)

    chunk_toks = jnp.concatenate([t_c[None], props])
    xs = (jnp.take(t_params["wte"], chunk_toks, axis=0)
          + jnp.take(t_params["wpe"],
                     p_c + jnp.arange(spec_k), axis=0))[None]
    # only the TARGET side shards under TP (serve/tp.py): the draft
    # scan above runs replicated on every shard (same inputs → same
    # proposals bitwise), which is what keeps any draft geometry legal
    # whatever the tp width
    lg, kc2, vc2 = _gpt2._advance_chunk(
        t_params, xs, _gpt2._batch1(kc_r), _gpt2._batch1(vc_r), p_c, tn,
        te, moe_top_k=tm, tp_axis=tp_axis, tp_world=tp_world, ep=ep)
    out, a_draft = _gpt2.spec_verify(lg[0], d_probs, props, k_verify,
                               temp, top_p, top_k, use_top_p)
    return (out, a_draft, _gpt2._unbatch1(kc2), _gpt2._unbatch1(vc2),
            _gpt2._unbatch1(dkc_b), _gpt2._unbatch1(dvc_b), k_next)


@partial(jax.jit,
         static_argnames=("spec_k", "tn", "te", "tm", "dn", "de", "dm",
                          "top_k", "use_top_p", "tp_axis", "tp_world"),
         donate_argnums=(2, 3, 4, 5))
def _pool_spec_step(t_params, d_params, kc, vc, dkc, dvc, toks, pos,
                    live, keys, temps, top_p, spec_k, tn, te, tm,
                    dn, de, dm, top_k, use_top_p, tp_axis=None,
                    tp_world=1):
    """Advance EVERY slot one speculative chunk (the per-slot math is
    :func:`_spec_row`).  Arenas (target AND draft) are donated and
    update in place; dead slots run the same math on clamped inputs,
    their rows are garbage the next admission's full-row write
    overwrites, and rows a REJECTED proposal wrote past the accept
    point are overwritten by the next chunk's contiguous write before
    the position mask can ever read them live (the free-rollback
    argument from gpt2_decode._spec_row).  Returns ``(out (S, spec_k)
    candidate tokens, a_draft (S,) accepted-proposal counts, kc, vc,
    dkc, dvc, new_keys)`` — the host emits ``a_draft + 1`` tokens per
    live slot (capped by the request's remaining budget)."""

    def row(kc_r, vc_r, dkc_r, dvc_r, tok, pos_r, live_r, key, temp):
        return _spec_row(t_params, d_params, kc_r, vc_r, dkc_r, dvc_r,
                         tok, pos_r, live_r, key, temp, top_p, spec_k,
                         tn, te, tm, dn, de, dm, top_k, use_top_p,
                         tp_axis=tp_axis, tp_world=tp_world)

    return jax.vmap(row, in_axes=(1, 1, 1, 1, 0, 0, 0, 0, 0),
                    out_axes=(0, 0, 1, 1, 1, 1, 0))(
        kc, vc, dkc, dvc, toks, pos, live, keys, temps)


@jax.jit
def _take_rows(a, idx):
    """Jitted row gather — the compacted paged dispatch's key-table
    select.  One jitted call instead of an eager op: eager jnp
    dispatches carry ~2-3x the per-call overhead, which is real money
    on the per-step path."""
    return jnp.take(a, idx, axis=0)


@jax.jit
def _set_rows(a, idx, vals):
    """Jitted row scatter (key-table write-back) — same eager-op
    avoidance as :func:`_take_rows`."""
    return a.at[idx].set(vals)


@jax.jit
def _merge_keys(keys_tbl, keys_b, idxs, rs):
    """One-dispatch key flush for a batched admission pass: rows
    ``rs`` of the pass's carried keys land at slots ``idxs``."""
    return keys_tbl.at[idxs].set(jnp.take(keys_b, rs, axis=0))


@partial(jax.jit, donate_argnums=(0, 1))
def _write_slot(kc_arena, vc_arena, kc_row, vc_row, slot):
    """Install an admitted request's prefilled cache rows at ``slot``
    (traced index — one executable for every slot).  Arenas/rows are
    pytrees: dense arrays, or (values, scales) tuples for int8 arenas
    — the scales leaf lacks the trailing D axis, so the start index is
    sized per leaf."""
    def wr(arena, row):
        start = (0, slot) + (0,) * (arena.ndim - 2)
        return jax.lax.dynamic_update_slice(arena, row, start)

    return (jax.tree.map(wr, kc_arena, kc_row),
            jax.tree.map(wr, vc_arena, vc_row))


def _on(knob):
    """Whether a constructor knob asks for its feature (None and False
    are off)."""
    return knob is not None and knob is not False


def _paged_knob(paged, name, default):
    """One field of ``paged=`` as the constructor was handed it (a
    PagedConfig, a kwargs dict, True; anything else is refused later,
    where the knob is parsed)."""
    if isinstance(paged, dict):
        return paged.get(name, default)
    return getattr(paged, name, default)


def _require(fam, **asked):
    """The one capability check: refuse, by name, each asked-for
    feature that the model's family does not implement
    (``ServedFamily.features``)."""
    assert set(asked) <= FEATURES, set(asked) - FEATURES
    for feature, on in asked.items():
        if on and feature not in fam.features:
            raise NotImplementedError(
                f"the {fam.name} family does not support {feature} on "
                f"the serve engine yet: its served-model contract "
                f"(models/served.py) implements "
                f"{sorted(fam.features) or 'the budgeted paged path'}"
                f" only")


@partial(jax.jit, donate_argnums=(0,))
def _write_state(arena, rows, slot):
    """Lay one slot's per-layer state ``rows`` {kind: (L, ...)} into
    row ``slot`` of each arena {kind: (L, S+1, ...)} (traced index —
    one executable for every slot)."""
    return {k: a.at[:, slot].set(rows[k].astype(a.dtype))
            for k, a in arena.items()}


@jax.jit
def _read_state(arena, slot):
    """Row ``slot`` of each state arena: {kind: (L, ...)}."""
    return {k: a[:, slot] for k, a in arena.items()}


def _weights_sharding(params):
    """The sharding an unsharded engine's state is allocated at: the
    one device its weights are committed to, or — for a plan-sharded
    model, whose weights span a mesh — replicated over that mesh
    (what GSPMD assumes for an unplaced operand anyway)."""
    leaves = jax.tree.leaves(params)
    devs = set().union(*(a.devices() for a in leaves))
    if len(devs) == 1:
        return jax.sharding.SingleDeviceSharding(devs.pop())
    for a in leaves:
        if isinstance(a.sharding, jax.sharding.NamedSharding):
            return jax.sharding.NamedSharding(
                a.sharding.mesh, jax.sharding.PartitionSpec())
    raise ValueError(
        f"model weights are spread over {len(devs)} devices without a "
        f"mesh sharding ({sorted(d.id for d in devs)}): build the "
        f"model on one device, or serve it with tp=/ep=/pp=")


class _LocalExec:
    """The engine's default (single-device) executor: every dispatch
    the engine makes goes through this surface, so the TP backend
    (serve/tp.py ``TPExecutor``) can plug sharded twins in its place
    without the host-side step loop knowing.  Methods bind the
    engine's statics onto the module-level jitted executables — the
    paged pool steps keep their AOT cost-capture dispatch."""

    def __init__(self, eng):
        self._e = eng
        self._aot_memo = {}   # (name, width) -> full AOT cache key

    def pool_decode_step(self, params, kc, vc, toks, pos, live, keys,
                         temps, top_p):
        return _pool_decode_step(params, kc, vc, toks, pos, live,
                                 keys, temps, top_p,
                                 **self._e._statics)

    def pool_spec_step(self, t_params, d_params, kc, vc, dkc, dvc,
                       toks, pos, live, keys, temps, top_p):
        e = self._e
        st = e._statics
        return _pool_spec_step(t_params, d_params, kc, vc, dkc, dvc,
                               toks, pos, live, keys, temps, top_p,
                               spec_k=e.spec_k, tn=st["n_head"],
                               te=st["eps"], tm=st["moe_top_k"],
                               dn=e._d_statics[0], de=e._d_statics[1],
                               dm=e._d_statics[2], top_k=st["top_k"],
                               use_top_p=st["use_top_p"])

    def paged_decode_step(self, params, pool_k, pool_v, tables, toks,
                          pos, live, keys, temps, top_p, block,
                          masks=None, with_lp=False, state=None,
                          slots=None):
        e = self._e
        return _aot_call("paged_decode_kernel", _paged_decode_kernel,
                         params, pool_k, pool_v, tables, toks, pos,
                         live, keys, temps, top_p, masks, state, slots,
                         block=block, _memo=self._aot_memo,
                         _token=("paged_decode_kernel", toks.shape[0],
                                 masks is not None, with_lp),
                         with_lp=with_lp, window=e._window, fam=e._fam,
                         **e._statics)

    def paged_spec_step(self, t_params, d_params, pool_k, pool_v, dkc,
                        dvc, tables, toks, pos, live, keys, temps,
                        top_p, block):
        e = self._e
        st = e._statics
        return _aot_call("paged_spec_kernel", _paged_spec_kernel,
                         t_params, d_params, pool_k, pool_v, dkc, dvc,
                         tables, toks, pos, live, keys, temps, top_p,
                         _memo=self._aot_memo,
                         _token=("paged_spec_kernel", toks.shape[0]),
                         window=e._window, block=block, spec_k=e.spec_k,
                         tn=st["n_head"], te=st["eps"],
                         tm=st["moe_top_k"], dn=e._d_statics[0],
                         de=e._d_statics[1], dm=e._d_statics[2],
                         top_k=st["top_k"],
                         use_top_p=st["use_top_p"])

    def prefill_one(self, params, ids, prompt_len, key, temp, top_p,
                    mask=None):
        e = self._e
        return _prefill_one(params, ids, prompt_len, key, temp, top_p,
                            **e._statics, quant=e._quant,
                            window=e._window, mask=mask)

    def prefill_batch(self, params, ids, plens, seeds, temps, top_p):
        e = self._e
        return _prefill_batch(params, ids, plens, seeds, temps,
                              top_p, **e._statics, quant=e._quant,
                              window=e._window)

    def chunk_row(self, params, ids, kc_row, vc_row, off, state=None,
                  n_valid=None, run=True):
        """``run=False`` compiles the program of ``off``'s width from
        abstract arguments and launches nothing (the engine does so
        for every width when it is built)."""
        # through the AOT cache whatever the family: it keeps a
        # family's scopes, and a program compiled ahead waits there
        e = self._e
        return _aot_call("chunk_row", _chunk_row, params, ids, kc_row,
                         vc_row, off, state, n_valid, fam=e._fam,
                         _memo=self._aot_memo,
                         _token=("chunk_row",
                                 tuple(o.shape for o in off)
                                 if isinstance(off, tuple) else off.shape),
                         _run=run,
                         **e._chunk_statics)

    def write_slot(self, kc, vc, kc_row, vc_row, slot):
        return _write_slot(kc, vc, kc_row, vc_row, slot)

    def read_slot(self, kc, vc, slot):
        return _read_slot(kc, vc, slot)


def _seam(method):
    """One executor-seam method of :class:`_ProfExec`."""
    name = "serve.dispatch." + method

    def call(self, *a, **kw):
        with _trace.phase(name, cat="serve"):
            out = getattr(self._inner, method)(*a, **kw)
        if _stepprof._active:
            _stepprof.fence_device(out)
        return out

    call.__name__ = method
    return call


class _ProfExec:
    """The instrumentation at the executor seam: every dispatch the
    engine makes routes through ``self._x``, so wrapping HERE covers
    every parallelism mode — ``_LocalExec`` and the tp/ep/pp sharded
    executors alike — without the step loop knowing.  Each dispatch is
    one ``serve.dispatch.<method>`` phase: the HOST's side of it
    (building inputs + launching), which a profiler trace shows above
    the device operations it launched — the trace, not this seam, says
    how long the device ran.  While ``stepprof.enable()`` is on, the
    seam also blocks on the outputs (``stepprof.fence_device``: a
    host-fence estimate, outputs the engine was about to sync anyway,
    so nothing enters jitted code and the recompile pin holds); off,
    that is one module-flag read per dispatch."""

    __slots__ = ("_inner",)

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        # non-dispatch surface (executor-specific attrs) falls through
        return getattr(self._inner, name)

    pool_decode_step = _seam("pool_decode_step")
    pool_spec_step = _seam("pool_spec_step")
    paged_decode_step = _seam("paged_decode_step")
    paged_spec_step = _seam("paged_spec_step")
    prefill_one = _seam("prefill_one")
    prefill_batch = _seam("prefill_batch")
    chunk_row = _seam("chunk_row")
    write_slot = _seam("write_slot")
    read_slot = _seam("read_slot")


class _Slot:
    """Host-side bookkeeping for one pool row (the decode position
    lives in the engine's per-slot arrays — the jitted step's
    inputs — not here).  On a paged engine ``blocks`` is the slot's
    block table (pool block ids, grown block-by-block as decode
    advances) and ``n_shared`` the count of leading blocks REFERENCED
    from the prefix cache (never written, never freed by this slot —
    only released).

    Fork-round fields: ``group`` ties sibling branches of one fork
    family together (None for plain requests — it also gates the
    per-step logprob output that feeds ``score``, the best-of-n
    ranking signal), ``branch`` is this slot's index in the family,
    and ``cow`` marks a slot whose tail blocks MAY still be shared
    with a sibling (the growth pass copy-on-first-writes them).
    ``automaton``/``astate`` carry a structured request's grammar
    state between steps (serve/structured.py)."""

    __slots__ = ("handle", "emitted", "remaining",
                 "first_token_time", "admit_time", "admitted_step",
                 "prefix_nodes", "blocks", "n_shared",
                 "group", "branch", "score", "cow",
                 "automaton", "astate")

    def __init__(self, handle, max_new, now, step):
        self.handle = handle
        self.emitted = []
        self.remaining = max_new
        self.first_token_time = None
        self.admit_time = now
        self.admitted_step = step
        self.prefix_nodes = []   # cached-prefix refs held while live
        self.blocks = []         # paged mode: the slot's block table
        self.n_shared = 0        # leading blocks shared with the cache
        self.group = None        # fork family id (None = plain)
        self.branch = 0          # branch index within the family
        self.score = 0.0         # cumulative chosen-token logprob
        self.cow = False         # tail blocks may be sibling-shared
        self.automaton = None    # structured-decoding grammar
        self.astate = None       # its current state


class _Prefilling:
    """Host-side state of one IN-FLIGHT chunked-prefill admission
    (the ``PagedConfig(prefill_token_budget=)`` path): the request
    holds a reserved slot index and its pool blocks, but its cache
    rows live in a private device row (``kc_row``/``vc_row``) that
    ``_chunk_row`` launches advance across STEPS, from ``off`` to
    ``last_off`` (the prompt's last block), each as many blocks wide
    as the step's budget allows — only when the last block lands does
    the first token sample (from ``hidden``, the newest launch's last
    block; it waits on the device in ``first`` until the pass that
    sampled it promotes the slot), the row scatter into the blocks,
    and the slot go live.  Nothing has
    streamed, so an engine failure mid-prefill rejects these
    requeue-safe (``started=False``) and returns their blocks to the
    free list."""

    __slots__ = ("handle", "request", "ids_j", "kc_row", "vc_row",
                 "state", "hidden", "off", "last_off", "blocks",
                 "n_shared", "nodes", "key0", "temp", "t_admit",
                 "admitted_step", "seq", "first")


class _PrefixJob:
    """Host-side state of one fleet-driven PREFILL-FOR-SHIP build (the
    disaggregation round): the shippable canonical-KV prefix of a
    prompt — its ``(plen - 1) // block_size`` full blocks, exactly
    what a warm admission can consume — advanced across steps in
    block-width ``_chunk_row`` windows against a private device row.
    No slot is reserved, no token is sampled, and nothing streams:
    the build is pure cache work, so a failed or abandoned build is
    always replayable from scratch with byte-identical results.
    ``engine`` pins the generation — a supervisor rebuild invalidates
    the job (its row belongs to the dead engine's params) and the
    fleet restarts the build."""

    __slots__ = ("tokens", "plen", "n_goal", "ids_j", "kc_row",
                 "vc_row", "off", "last_off", "nodes", "engine",
                 "hit")


class _Swapped:
    """A preempted request's complete host-side state: byte copies of
    its target cache lanes (and draft rows on a speculative engine),
    the sampling-key chain, and every scrap of slot bookkeeping — so a
    resume continues the EXACT token stream the uninterrupted run
    would have produced.  Swapped requests are STARTED (the admission
    token always streamed), so they are never requeue-safe: an engine
    failure rejects them typed with ``started=True``."""

    __slots__ = ("handle", "request", "emitted", "remaining",
                 "first_token_time", "admit_time", "admitted_step",
                 "pos", "tok", "temp", "key", "image", "state",
                 "dkc_h", "dvc_h", "n_data", "seq", "t_preempt", "j_lo",
                 "group", "branch", "score", "automaton", "astate")

    @property
    def priority(self):
        return getattr(self.request, "priority", 0)


class InferenceEngine:
    """In-process continuous-batching engine for a ``GPT2LMHead``.

    >>> eng = model.serve(max_slots=8)
    >>> h = eng.submit(GenerationRequest(prompt, max_new_tokens=32))
    >>> eng.run_until_complete()
    >>> h.result().tokens      # == model.generate(prompt, ...) exactly

    ``max_len`` defaults to ``cfg.n_positions`` — the same padded width
    single-prompt ``generate`` uses, which is what makes engine logits
    (and therefore tokens) identical to the offline path.  ``top_k``/
    ``top_p`` are ENGINE-level statics (one executable for the pool);
    per-request knobs are temperature/seed/max_new_tokens/deadline.
    ``clock`` is injectable for deterministic scheduling tests.
    ``slo``: optional :class:`~singa_tpu.observe.health.SLO` — retires
    and scheduling passes are checked against it (see
    ``EngineStats``/docs/SERVING.md).

    Fast-decode knobs (docs/SERVING.md "Fast decode"):
    ``cache_dtype="int8"`` quantizes the KV arena (~2× less cache
    traffic, streams byte-identical to offline int8 generate);
    ``draft_model=`` + ``spec_k=`` turn on speculative decoding — up
    to ``spec_k`` tokens per step, greedy streams byte-identical to
    the non-speculative engine, sampled traffic served through
    rejection sampling.  Incompatible combinations (vocab/position
    mismatch, sliding-window draft, spec_k wider than a paged block)
    are rejected with typed errors at construction, never inside a
    jitted dispatch.

    Paged KV (``paged=`` a :class:`~singa_tpu.serve.paged.PagedConfig`;
    docs/SERVING.md "Paged KV and preemption"): the worst-case
    ``(max_slots, max_len)`` slot arena is replaced by ONE block pool
    shared with the prefix cache — admission is bounded by blocks
    free rather than slots free, a request's KV grows block-by-block,
    retire donation is zero-copy adoption, and when the pool runs out
    the engine PREEMPTS (swap a lower-priority request's blocks to
    host, resume byte-identically later) instead of stalling.  Pair
    with ``scheduler="priority"`` so urgent arrivals overtake and
    preempt background work.  Decode runs the BLOCK-NATIVE
    online-softmax kernel, admissions prefill at narrow widths and
    batch per scheduling pass, and the pool step dispatches at a
    compacted width covering only the live slots — token streams stay
    identical to the slot engine's, with an allclose logits pin
    against the row math (docs/SERVING.md "Paged KV and preemption"
    has the pin taxonomy)."""

    def __init__(self, model, max_slots=8, max_len=None, dtype=None,
                 scheduler=None, top_k=0, top_p=None,
                 clock=time.monotonic, slo=None, prefix_cache=None,
                 draft_model=None, spec_k=None, cache_dtype=None,
                 paged=None, tp=None, ep=None, pp=None):
        cfg = model.cfg
        # the model's math, and which of the engine's optional features
        # it implements (models/served.py); what it does not is refused
        # here, by name, before any state exists
        fam = self._fam = model.served_family()
        _require(fam, **{
            "tp=": _on(tp), "ep=": _on(ep), "pp=": _on(pp),
            "draft_model=": draft_model is not None,
            "cache_dtype='int8'": cache_dtype is not None,
            "prefix_cache=": _on(prefix_cache),
            "the slot arena (serving without paged=)": not _on(paged),
            "whole-prompt admission (paged= without "
            "prefill_token_budget)": _on(paged) and _paged_knob(
                paged, "prefill_token_budget", None) is None})
        # sliding-window models serve in PAGED mode only (the
        # long-context round): block tables are position-indexed, so
        # a windowed slot drops fully-out-of-window blocks back to
        # the free list as ``pos`` advances — long chats hold
        # O(window) blocks instead of O(length).  The slot arena's
        # worst-case rows still cannot roll, so windowed WITHOUT
        # paged= stays refused.
        self._window = fam.window(cfg)
        if self._window is not None and (paged is None
                                         or paged is False):
            raise NotImplementedError(
                "serve engine supports sliding-window models only in "
                f"paged mode (attn_window={cfg.attn_window}): pass "
                "paged=PagedConfig(...) for windowed decode in "
                "O(window) blocks (docs/SERVING.md 'Long-context "
                "serving'); without paged= the slot arena's "
                "position-indexed rows cannot roll — offline "
                "windowed GPT2LMHead.generate covers the no-engine "
                "case")
        if self._window is not None and _on(prefix_cache):
            # the remaining windowed composition limit, checked BEFORE
            # any registry/arena state exists so a refused construction
            # leaks nothing
            raise NotImplementedError(
                "prefix_cache on a sliding-window model: windowed "
                "slots drop out-of-window blocks, so a retiring "
                "request's prompt chain is no longer a contiguous "
                "block prefix the radix tree could adopt; serve "
                "windowed models without a prefix cache "
                "(docs/SERVING.md 'Long-context serving' "
                "composition matrix)")
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        self.model = model
        self.cfg = cfg
        self.max_slots = int(max_slots)
        self.max_len = int(max_len or cfg.n_positions)
        if self.max_len > cfg.n_positions:
            raise ValueError(
                f"max_len ({self.max_len}) exceeds n_positions "
                f"({cfg.n_positions})")
        if top_k and top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        self._top_k = min(int(top_k or 0), cfg.vocab_size)
        self._top_p = jnp.float32(1.0 if top_p is None else top_p)
        self._use_top_p = top_p is not None
        # -- fast-decode config (speculative + int8 KV, perf round) --
        # every incompatible combination is rejected HERE with a typed
        # error naming the conflict, never deep inside a jitted
        # dispatch where the failure surfaces as a shape/dtype trace
        self._quant = fam.quant_flag(cache_dtype)  # bool; rejects typos
        self.cache_dtype = cache_dtype
        if spec_k is not None and draft_model is None:
            raise ValueError(
                f"spec_k={spec_k} without draft_model: speculative "
                "decoding needs a draft to propose; pass draft_model= "
                "(or drop spec_k)")
        self.draft = draft_model
        self.spec_k = 4 if spec_k is None else int(spec_k)
        if draft_model is not None:
            dcfg = draft_model.cfg
            if self.spec_k < 2:
                raise ValueError(
                    f"spec_k must be >= 2, got {self.spec_k} (one "
                    "proposal + the bonus token is the smallest chunk)")
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft/target vocab mismatch: draft "
                    f"{dcfg.vocab_size} vs target {cfg.vocab_size} — "
                    "the draft must propose from the target's token "
                    "space")
            if dcfg.n_positions < self.max_len:
                raise ValueError(
                    f"draft n_positions ({dcfg.n_positions}) < engine "
                    f"max_len ({self.max_len}): the draft cache must "
                    "cover every arena position the target can reach")
            if draft_model.served_family().window(dcfg) \
                    is not None:
                raise NotImplementedError(
                    "speculative serve does not support sliding-window "
                    f"drafts (attn_window={dcfg.attn_window}); same "
                    "rolling-cache restriction as the target")
        # ring-prefill composition limits (TPConfig(ring_prefill=)),
        # checked BEFORE any registry/executor/arena state exists so
        # a refused construction leaks nothing; the tp branch below
        # re-coerces idempotently
        if tp is not None and tp is not False:
            from .tp import as_tp_config
            tp = as_tp_config(tp)
            if tp.tp > 1 and tp.ring_prefill:
                if paged is None or paged is False:
                    raise ValueError(
                        "ring_prefill requires paged= (the ring twin "
                        "scatters narrow block-multiple rows; the "
                        "slot arena's full-width write path is not "
                        "wired)")
                if prefix_cache is not None \
                        and prefix_cache is not False:
                    raise ValueError(
                        "ring_prefill with a prefix_cache: ring "
                        "attention reorders the float reduction, so "
                        "its K/V is not byte-canonical with chunked "
                        "prefill — donated blocks would poison the "
                        "cache's warm==cold byte-identity contract")
                if self._window is not None:
                    raise NotImplementedError(
                        "ring_prefill on a sliding-window model is "
                        "not implemented (the ring's causal skip has "
                        "no banded variant here); windowed long "
                        "prompts admit through the chunked-prefill "
                        "budget instead")
                if self._quant:
                    raise ValueError(
                        "ring_prefill with cache_dtype='int8': the "
                        "engine's int8 parity pin is byte equality "
                        "with the offline oracle, which ring "
                        "reduction reordering cannot keep through "
                        "quantization bins; serve int8 without ring")
        # -- expert-parallel / pipeline-parallel backends (serve/ep.py
        # and serve/pp.py): the FULL refusal matrix runs HERE, before
        # EngineStats (or any executor) registers a single metric — a
        # refused construction must leak nothing (the PR-12 leaked-
        # gauge hazard, audited for every ep/pp combination)
        self._ep_cfg = self._pp_cfg = None
        if ep is not None and ep is not False:
            from .ep import as_ep_config
            ep = as_ep_config(ep)
            if ep.ep * ep.tp > 1:
                self._ep_cfg = ep
        if pp is not None and pp is not False:
            from .pp import as_pp_config
            pp = as_pp_config(pp)
            if pp.stages > 1:
                self._pp_cfg = pp
        # conflicts test ACTIVE backends, not knobs-passed: explicit
        # "off" values (tp=1, pp=1, ep=1) next to an active backend
        # are legal no-ops, matching each knob's own "1 = off"
        # contract (tp was coerced to a TPConfig up top when set)
        _tp_on = (tp is not None and tp is not False and tp.tp > 1)
        if self._ep_cfg is not None:
            if _tp_on:
                raise ValueError(
                    "ep= together with tp=: EPConfig carries the "
                    "dense layers' tensor-parallel width itself — "
                    "pass ep=EPConfig(ep=, tp=) and drop the bare "
                    "tp= knob")
            if self._pp_cfg is not None:
                raise ValueError(
                    "ep= together with pp=: one sharded executor "
                    "per engine — serve expert-parallel (ep=) or "
                    "pipeline-parallel (pp=), not both")
            from .ep import check_ep
            check_ep(self._ep_cfg, cfg,
                     model_plan=getattr(model, "plan", None),
                     prefix_cache=prefix_cache)
        if self._pp_cfg is not None:
            if _tp_on:
                raise ValueError(
                    "pp= together with tp=: one sharded executor "
                    "per engine — interleaving tensor parallelism "
                    "inside a stage is the documented next "
                    "extension, not a supported composition")
            from .pp import check_pp
            check_pp(self._pp_cfg, cfg,
                     model_plan=getattr(model, "plan", None),
                     paged=paged, draft_model=draft_model,
                     window=self._window)
        self._clock = clock
        # string schedulers construct PER ENGINE — an object instance
        # forwarded through supervisor/fleet engine_kw would be SHARED
        # across replicas, which is never what "priority scheduling on
        # a fleet" means
        if scheduler == "priority":
            scheduler = PriorityScheduler()
        elif scheduler == "fifo":
            scheduler = FIFOScheduler()
        elif isinstance(scheduler, str):
            raise ValueError(
                f"unknown scheduler {scheduler!r}: pass 'fifo', "
                f"'priority', or a scheduler instance")
        # the default queue holds at least one request a slot: a caller
        # that fills every lane at once (128 of them) is not refused at
        # the 64th (PR 34)
        self.scheduler = scheduler or FIFOScheduler(
            max_queue_depth=max(64, int(max_slots)))
        self.stats = EngineStats(self.max_slots, clock, slo=slo,
                                 spec=draft_model is not None)
        # per-ENGINE watchdog source: with a shared "serve" source a
        # wedged engine would be masked as long as any sibling engine
        # kept beating (per-tenant engines are a supported pattern)
        self._hb_source = "serve.e" + self.stats.engine_label
        self._log = get_channel("serve")
        # the always-on step log (observe/stepprof.py): host clock
        # stamps at the phase() sites below, from the first engine on
        _stepprof.install()

        model.eval()
        self._params = fam.extract_params(model, dtype=dtype)
        self._statics = dict(
            n_head=cfg.n_head, eps=float(cfg.layer_norm_eps),
            moe_top_k=int(getattr(cfg, "moe_top_k", 2) or 2),
            top_k=self._top_k, use_top_p=self._use_top_p)
        # -- tensor-parallel backend (serve/tp.py): shard the decode
        # math + every KV arena over a `tp` mesh axis.  The executor
        # re-places the extracted weights Megatron-style and supplies
        # sharded twins for every dispatch below; the host-side step
        # loop, paging, prefix cache, and ledger see a single logical
        # engine either way (self._x is the pluggable dispatch seam)
        self.tp_exec = None
        self._tp_cfg = None
        host_params = None
        if tp is not None and tp is not False:
            from .tp import TPExecutor, as_tp_config
            tp = as_tp_config(tp)
            self._tp_cfg = tp
            if tp.tp > 1:
                self.tp_exec = TPExecutor(
                    tp, cfg, statics=self._statics, quant=self._quant,
                    model_plan=getattr(model, "plan", None),
                    engine_label=self.stats.engine_label,
                    reg=self.stats.registry)
                self.tp_exec.set_window(self._window)
                # ring prefill keeps a REPLICATED full-weight copy
                # (context parallelism over the same mesh: sequence
                # sharded, weights whole) — grab the host tree before
                # the Megatron placement below consumes it; the ring
                # composition checks run once paged/prefix parse
                host_params = (self._params
                               if getattr(tp, "ring_prefill", False)
                               else None)
                self._params = self.tp_exec.place_params(self._params)
                self.stats.tp_source = self.tp_exec.snapshot
        # -- expert-parallel / pipeline-parallel executors: same seam,
        # different mesh.  Validation already ran up top (before any
        # registration); the executors re-check defensively before
        # registering their own metrics.
        self.ep_exec = self.pp_exec = None
        if self._ep_cfg is not None:
            from .ep import EPExecutor
            self.ep_exec = EPExecutor(
                self._ep_cfg, cfg, statics=self._statics,
                quant=self._quant,
                model_plan=getattr(model, "plan", None),
                engine_label=self.stats.engine_label,
                reg=self.stats.registry, prefix_cache=prefix_cache)
            self.ep_exec.set_window(self._window)
            self._params = self.ep_exec.place_params(self._params)
            self.stats.ep_source = self.ep_exec.snapshot
        if self._pp_cfg is not None:
            from .pp import PPExecutor
            self.pp_exec = PPExecutor(
                self._pp_cfg, cfg, statics=self._statics,
                quant=self._quant,
                model_plan=getattr(model, "plan", None),
                engine_label=self.stats.engine_label,
                reg=self.stats.registry)
            self._params = self.pp_exec.place_params(self._params)
            self.stats.pp_source = self.pp_exec.snapshot
        #: the ONE sharded executor (tp | ep | pp | None) — placement
        #: and late-statics calls below go through this seam so the
        #: host-side step loop never knows which mesh it runs over
        self._shard = (self.tp_exec or self.ep_exec or self.pp_exec)
        #: where an UNSHARDED engine's device state lives: with the
        #: weights.  Arenas, pools and the key table are allocated at
        #: this sharding (never on the process default device), so a
        #: model built on chip 2 serves from chip 2 and a model left on
        #: the host CPU serves — visibly — from the host CPU.  Sharded
        #: executors own placement themselves (place_cache/_replicated)
        self._state_sh = (None if self._shard is not None
                          else _weights_sharding(self._params))
        # the step-anatomy shim wraps the seam permanently: one
        # module-flag read per dispatch when the profiler is off
        # (observe/stepprof.py), dispatch/ready timestamps when on
        self._x = _ProfExec(self._shard if self._shard is not None
                            else _LocalExec(self))
        # fixed-shape KV arena keyed on (max_slots, max_len): L layers,
        # H_kv heads (GQA keeps the narrow cache), compute dtype —
        # or (int8 values, f32 scales) tuples for cache_dtype="int8"
        # (half the bytes per element on a cache-read-bound loop; the
        # same (values, scales) layout gpt2_decode._quantize_kv makes)
        S, W = self.max_slots, self.max_len
        L, H_kv, D = fam.kv_geometry(cfg)
        cdt = self._params["wte"].dtype

        def _arena(L_, H_, D_, shard=True):
            z = _kv_zeros((L_, S, H_, W), D_, cdt, self._quant,
                          self._state_sh)
            if self._shard is None:
                return z
            # target arenas shard on the H_kv axis; the DRAFT arena
            # (shard=False) replicates — every shard runs the full
            # draft, which is what keeps any draft geometry legal
            return (self._shard.place_cache(z) if shard
                    else self._shard.place_replicated(z))

        # -- paged KV mode (serve/paged.py): ONE block pool replaces
        # the per-slot worst-case arena; capacity becomes "blocks
        # free", requests grow block-by-block, and preemption/swap +
        # the unified prefix cache ride the same pool.  max_slots
        # still bounds the decode vmap width, but a slot costs only
        # the blocks its request actually holds
        self.paged_arena = None
        self._decode_attn = None    # "kernel" | "loop" (paged engines)
        self._spec_pad = 0 if draft_model is None else self.spec_k - 1
        if paged is not None and paged is not False:
            if paged is True:
                paged = PagedConfig()
            elif isinstance(paged, dict):
                paged = PagedConfig(**paged)
            if not isinstance(paged, PagedConfig):
                raise ValueError(
                    f"paged must be a PagedConfig, a kwargs dict, or "
                    f"True, got {type(paged)}")
            if self.max_len % paged.block_size != 0:
                raise ValueError(
                    f"max_len ({self.max_len}) must be a multiple of "
                    f"the paged block_size ({paged.block_size}) so "
                    f"block tables tile the row exactly")
            if draft_model is not None \
                    and self.spec_k > paged.block_size:
                raise ValueError(
                    f"spec_k ({self.spec_k}) > paged block_size "
                    f"({paged.block_size}): a verify chunk would span "
                    f"more than two pool blocks; raise block_size or "
                    f"lower spec_k")
            self.paged_arena = PagedKVArena(
                paged, L, H_kv, D, cdt, row_width=W,
                quant=self._quant,
                engine_label=self.stats.engine_label,
                reg=self.stats.registry, tp=self._shard,
                sharding=self._state_sh, value_leaf=fam.value_leaf)
            self.stats.paged_source = self.paged_arena.snapshot
            self._kc = self._vc = None
            # which attention this engine's decode dispatches run: the
            # rule the programs themselves go by, on the same operands
            # (a token a lane, or a verify chunk of spec_k; H_kv heads
            # of D against the pool; sharded executors keep the loop)
            self._decode_attn = _decode_attn_impl(
                jax.ShapeDtypeStruct(
                    (1, H_kv, 1, 1 + self._spec_pad, D), cdt),
                self.paged_arena.pool_k, window=self._window,
                tp_axis=None if self._shard is None else "mesh")
        else:
            self._kc = _arena(L, H_kv, D)
            self._vc = _arena(L, H_kv, D)
        # -- per-slot state that is not K/V (models/served.py): one
        # arena a kind, (L, S + 1, *shape) beside the pool — no
        # position axis, so nothing to page.  Row S is the trash row
        # dead lanes write.  A slot's row is written when its prefill
        # finishes (from the zeroed state the chunk rows carried),
        # advanced in place by every decode step (donated, like the
        # pool), and saved and restored with the slot's blocks
        self._state_spec = fam.state_spec(cfg)
        self._state = None
        self._decode_state = None   # "kernel" | "loop" (a stepped state)
        if self._state_spec:
            self._state = {
                k: jnp.zeros((L, S + 1) + tuple(shape), dt,
                             device=self._state_sh)
                for k, (shape, dt) in self._state_spec.items()}
            # what this engine's decode dispatches advance it with: the
            # family's rule, on the arenas themselves
            self._decode_state = fam.state_step_impl(self._state)
        # ... as the serve.decode / serve.step spans say them
        self._decode_impls = {
            k: v for k, v in (("attn", self._decode_attn),
                              ("state", self._decode_state)) if v}
        # draft-side state (speculative decoding): its own params and
        # its own (cheap) KV arena, advanced in lockstep by the spec
        # pool step
        self._d_params = self._d_statics = None
        self._dkc = self._dvc = None
        if self.draft is not None:
            self.draft.eval()
            self._d_params = self.draft.served_family().extract_params(
                self.draft, dtype=dtype)
            dcfg = self.draft.cfg
            self._d_statics = (dcfg.n_head, float(dcfg.layer_norm_eps),
                               int(getattr(dcfg, "moe_top_k", 2) or 2))
            self._dkc = _arena(dcfg.n_layer, dcfg.n_kv_head,
                               dcfg.n_embd // dcfg.n_head, shard=False)
            self._dvc = _arena(dcfg.n_layer, dcfg.n_kv_head,
                               dcfg.n_embd // dcfg.n_head, shard=False)
            if self._shard is not None:
                self._d_params = self._shard.place_replicated(
                    self._d_params)
                self._shard.set_spec(self.spec_k, self._d_statics)
        # per-slot host state + device sampling keys
        self._slots = [None] * S            # _Slot or None
        self._toks = np.zeros(S, np.int32)  # last emitted token
        self._pos = np.zeros(S, np.int32)
        self._temps = np.zeros(S, np.float32)
        self._keys = jnp.zeros((S, 2), jnp.uint32,
                               device=self._state_sh)
        if self._shard is not None:
            # committed replicated so the sharded twins never pay a
            # per-dispatch broadcast for the key table
            self._keys = self._shard.place_replicated(self._keys)
        self._handles = {}
        self._swapped = []                  # paged mode: _Swapped list
        # batched-admission deferral (the gather-tax round): one
        # scheduling pass's prefilled rows (_prefill_admissions) plus
        # the per-request scatter/key writes deferred onto them —
        # flushed as ONE pool scatter + ONE key write per pass
        self._admit_batch = None            # (keys, kc, vc) device
        self._pending_scatter = []          # [(batch row, lanes dict)]
        self._pending_keys = []             # [(slot idx, batch row)]
        self._batch_cache = None            # last pass's pure batch
        self._swap_seq = itertools.count()
        self._closed = False
        self._failed = False
        self.step_count = 0
        # radix prefix cache (serve/prefix.py): block-granular KV
        # reuse for shared prompts and pinned sessions.  The cache is
        # engine-owned and starts empty — a supervisor rebuild gets a
        # fresh one (cold but correct) from the forwarded config.
        self.prefix_cache = None
        self._sched_cost = None
        self._chunk_statics = None
        # identity check, not truthiness: prefix_cache={} means
        # "enable with defaults", and silently disabling on a falsy
        # dict would only surface as stats["prefix"] == None much later
        if prefix_cache is not None and prefix_cache is not False:
            if prefix_cache is True:
                prefix_cache = PrefixCacheConfig()
            elif isinstance(prefix_cache, dict):
                prefix_cache = PrefixCacheConfig(**prefix_cache)
            if not isinstance(prefix_cache, PrefixCacheConfig):
                raise ValueError(
                    f"prefix_cache must be a PrefixCacheConfig, a "
                    f"kwargs dict, or True, got {type(prefix_cache)}")
            # int8 + prefix cache is SUPPORTED since the paged round:
            # the block pool is pytree-leaf-generic ((values, scales)
            # blocks), and quantized engines with a cache route EVERY
            # admission through the chunked prefill path so warm and
            # cold streams stay byte-identical to each other (see
            # _admit; docs/SERVING.md "int8 and the prefix cache")
            if self.paged_arena is not None:
                # one pool, one granularity: the radix tree shares the
                # paged arena's blocks by reference, so its block size
                # IS the arena's
                if prefix_cache.block_size != \
                        self.paged_arena.block_size:
                    raise ValueError(
                        f"prefix_cache.block_size "
                        f"({prefix_cache.block_size}) != paged "
                        f"block_size ({self.paged_arena.block_size}): "
                        f"a paged engine keeps ONE block pool, so the "
                        f"cache must share its granularity (its "
                        f"num_blocks is ignored — capacity is the "
                        f"arena's)")
            elif self.max_len % prefix_cache.block_size != 0:
                raise ValueError(
                    f"max_len ({self.max_len}) must be a multiple of "
                    f"prefix_cache.block_size "
                    f"({prefix_cache.block_size}) so chunked prefill "
                    f"windows never cross the arena edge")
            self.prefix_cache = PrefixCache(
                prefix_cache, L, H_kv, D, cdt,
                engine_label=self.stats.engine_label,
                reg=self.stats.registry, quant=self._quant,
                arena=self.paged_arena, tp=self._shard,
                sharding=self._state_sh)
            self.prefix_cache.attach_row_geometry(W)
            if self.paged_arena is not None:
                # cached-but-unreferenced blocks are soft free space:
                # allocation evicts LRU leaves before failing
                self.paged_arena.evict_cb = \
                    self.prefix_cache._evict_one
            self._chunk_statics = dict(
                n_head=cfg.n_head, eps=float(cfg.layer_norm_eps),
                moe_top_k=self._statics["moe_top_k"],
                chunk=prefix_cache.block_size, window=self._window)
            if self._shard is not None:
                self._shard.set_chunk(self._chunk_statics)
            self.stats.prefix_source = self.prefix_cache.snapshot
            # prefill-interleave pricing: warm admissions that
            # recompute at most one chunk don't consume the cold
            # budget (scheduler.schedule's ``cost``; custom schedulers
            # without the parameter keep the flat 1-per-admit price)
            try:
                params_ = inspect.signature(
                    self.scheduler.schedule).parameters
                if "cost" in params_:
                    self._sched_cost = self._prefill_cost
            except (TypeError, ValueError):
                pass
        # -- chunked-prefill token budget (the long-context round):
        # PagedConfig(prefill_token_budget=) splits admissions across
        # steps in _chunk_row launches of whole blocks — host state for the
        # in-flight chunked prefills lives in self._prefilling (slot
        # index -> _Prefilling; the slot is RESERVED but not live, so
        # the decode dispatch never sees it until the first token
        # samples)
        self._budget = (self.paged_arena.config.prefill_token_budget
                        if self.paged_arena is not None else None)
        self._prefilling = {}
        self._prefill_seq = itertools.count()
        # prompt blocks prefilled, and the chunk-row launches that did
        # it (serve.schedule's args)
        self._chunks_run = 0
        self._launches_run = 0
        self._segments_run = 0
        self._own_metrics = []
        # what the newest decode step's program counted about itself
        # (``ServedFamily.step_counts``), for the serve.step span
        self._step_counts = {}
        self._count_metrics = {}
        if self.paged_arena is not None:
            g_row = self.stats.registry.gauge(
                "serve.kv.row_bytes",
                help="pool bytes one cached position takes in one "
                     "layer, every leaf (K and V, or the one latent "
                     "row)", engine=self.stats.engine_label)
            g_row.set(sum(a.shape[-1] * a.dtype.itemsize
                          for a in jax.tree.leaves(
                              (self.paged_arena.pool_k,
                               self.paged_arena.pool_v))))
            self._own_metrics.append(g_row)
        if self._state is not None:
            reg, lbl = self.stats.registry, self.stats.engine_label
            self._c_state_resets = reg.counter(
                "serve.state.resets",
                help="per-slot states zeroed for an admission",
                engine=lbl)
            self._c_state_snapshots = reg.counter(
                "serve.state.snapshots",
                help="per-slot states copied to the host with a "
                     "preempted slot", engine=lbl)
            self._c_state_restores = reg.counter(
                "serve.state.restores",
                help="per-slot states copied back for a resumed slot",
                engine=lbl)
            g_bytes = reg.gauge(
                "serve.state.bytes",
                help="device bytes of the per-slot state arenas",
                engine=lbl)
            g_bytes.set(sum(a.nbytes for a in self._state.values()))
            self._own_metrics.extend([
                self._c_state_resets, self._c_state_snapshots,
                self._c_state_restores, g_bytes])
        if self._budget is not None:
            if self._chunk_statics is None:
                self._chunk_statics = dict(
                    n_head=cfg.n_head, eps=float(cfg.layer_norm_eps),
                    moe_top_k=self._statics["moe_top_k"],
                    chunk=self.paged_arena.block_size,
                    window=self._window)
                if self._shard is not None:
                    self._shard.set_chunk(self._chunk_statics)
            self._c_budget_chunks = self.stats.registry.counter(
                "serve.prefill.budget_chunks",
                help="prompt blocks the chunked-prefill token budget "
                     "prefilled (a launch of n blocks counts n)",
                engine=self.stats.engine_label)
            self._c_launches = self.stats.registry.counter(
                "serve.prefill.launches",
                help="chunk-row launches of the chunked-prefill token "
                     "budget (budget_chunks / launches = blocks a "
                     "launch)",
                engine=self.stats.engine_label)
            self._c_early_launches = self.stats.registry.counter(
                "serve.prefill.early_launches",
                help="those of serve.prefill.launches that went out "
                     "behind the decode program, before the host "
                     "waited for its tokens (early / all = the share "
                     "of launches the wait no longer delays)",
                engine=self.stats.engine_label)
            self._c_merged_launches = self.stats.registry.counter(
                "serve.prefill.merged_launches",
                help="those of serve.prefill.launches that carried "
                     "pieces of two requests (merged / all = the share "
                     "of launches that served two prompts with one "
                     "read of the weights)",
                engine=self.stats.engine_label)
            self._own_metrics.extend([self._c_budget_chunks,
                                      self._c_launches,
                                      self._c_early_launches,
                                      self._c_merged_launches])
            # a launch is a list of segments.  One segment is any whole
            # number of one request's blocks that the step's budget
            # allows (widest first); the sharded executors, which
            # compile a width when they first meet it, keep the block
            # times a power of two
            B = self.paged_arena.block_size
            n_max = min(self._budget, W) // B
            self._pair_blocks = 0
            if self._shard is None:
                self._launch_widths = tuple(
                    B * n for n in range(n_max, 0, -1))
                # two segments of two requests ride ONE further program:
                # two slots of half the budget each, where that is two
                # blocks or more (a slot of one block serves only two
                # whole one-block prompts met in one pass)
                if n_max >= 4:
                    self._pair_blocks = n_max // 2
                self._compile_launch_widths()
            else:
                self._launch_widths = tuple(
                    B << j for j in reversed(range(n_max.bit_length())))
        # -- CoW KV forking (serve/fork.py): fork-family id sequence
        # and the fork-round metrics (paged engines only — forking
        # rides on the arena's block refcounts)
        self._fork_seq = itertools.count(1)
        self._c_fork_branches = self._c_fork_cow = None
        self._c_fork_pruned = self._g_fork_shared = None
        if self.paged_arena is not None:
            self._c_fork_branches = self.stats.registry.counter(
                "serve.fork.branches",
                help="decoding branches forked off live slots "
                     "(n>1 admissions and explicit fork() calls)",
                engine=self.stats.engine_label)
            self._c_fork_cow = self.stats.registry.counter(
                "serve.fork.cow_copies",
                help="copy-on-write block copies: a branch reached a "
                     "block a sibling still references and got a "
                     "private copy",
                engine=self.stats.engine_label)
            self._c_fork_pruned = self.stats.registry.counter(
                "serve.fork.pruned",
                help="branches cut by prune() (private blocks freed, "
                     "result sealed finish_reason=pruned)",
                engine=self.stats.engine_label)
            self._g_fork_shared = self.stats.registry.gauge(
                "serve.fork.shared_blocks",
                help="arena blocks currently referenced by more than "
                     "one live slot (each saves a full block of KV "
                     "per extra reference)",
                engine=self.stats.engine_label)
            self._own_metrics.extend([
                self._c_fork_branches, self._c_fork_cow,
                self._c_fork_pruned, self._g_fork_shared])
        # -- ring-attention prefill (TPConfig(ring_prefill=True)):
        # cold long-prompt admissions prefill SEQUENCE-sharded over
        # the tp mesh (parallel/ring_attention.py) — composition was
        # validated up top, before any registration
        self._ring = bool(self.tp_exec is not None and self._tp_cfg
                          and getattr(self._tp_cfg, "ring_prefill",
                                      False))
        if self._ring:
            self.tp_exec.enable_ring(host_params)
        self._log.info(
            "engine up: slots=%d max_len=%d cache_dtype=%s "
            "prefix_cache=%s spec=%s paged=%s tp=%s",
            S, W, cache_dtype or str(cdt),
            "off" if self.prefix_cache is None else
            f"{self.prefix_cache.num_blocks}x"
            f"{self.prefix_cache.block_size}",
            "off" if self.draft is None else f"k={self.spec_k}",
            "off" if self.paged_arena is None else
            f"{self.paged_arena.num_blocks}x"
            f"{self.paged_arena.block_size}",
            "off" if self.tp_exec is None
            else f"{self.tp_exec.tp} shards",
        )
        if self.ep_exec is not None:
            self._log.info(
                "engine ep backend: %d expert shards x %d tp "
                "(capacity_factor=%s)", self.ep_exec.ep,
                self.ep_exec.tp, self.ep_exec.config.capacity_factor)
        if self.pp_exec is not None:
            self._log.info(
                "engine pp backend: %d stages x %d microbatches",
                self.pp_exec.stages, self.pp_exec.microbatches)

    # -- submission ------------------------------------------------------
    def submit(self, request) -> RequestHandle:
        """Queue a request; returns immediately with a handle.  Raises
        QueueFullError under back-pressure and ValueError for requests
        that could never fit the arena."""
        if self._closed:
            raise RuntimeError(
                "engine is closed; build a new one with model.serve()")
        if self._failed:
            raise EngineFailedError(
                "engine has failed; rebuild it (EngineSupervisor does "
                "this automatically)", engine_step=self.step_count)
        if not isinstance(request, GenerationRequest):
            request = GenerationRequest(np.asarray(request))
        self.validate_request(request)
        if request.request_id in self._handles:
            # an in-flight duplicate would orphan the earlier handle
            # (the id is the engine's completion-routing key); finished
            # requests are evicted at retire/reject, so an id may be
            # REUSED once its predecessor resolved
            raise ValueError(
                f"request_id {request.request_id!r} is already "
                f"in flight")
        handle = RequestHandle(request)
        t_sub = self._clock()
        if _reqs._active:
            # request-ledger hook: one flag read when tracing is off.
            # Starts (or, on a supervisor/fleet requeue, CONTINUES)
            # this request's timeline with a hop on this engine
            _reqs._ledger.on_submit(
                request.request_id, engine=self.stats.engine_label,
                t=t_sub, prompt_len=len(request.prompt_ids),
                max_new_tokens=request.max_new_tokens)
        self.stats.on_submit()
        try:
            self.scheduler.enqueue(request)
        except Exception:
            self.stats.on_queue_full(request.request_id)
            _trace.event("serve/request_rejected", cat="serve",
                         request=request.request_id,
                         reason="queue_full")
            if _reqs._active:
                _reqs._ledger.on_reject(
                    request.request_id, t=self._clock(),
                    reason="queue_full",
                    engine=self.stats.engine_label, started=False)
            raise
        handle._submit_time = t_sub
        self._handles[request.request_id] = handle
        if request.n > 1:
            # best-of-n: the scheduler will fork n-1 siblings off this
            # slot the moment the prompt admits (serve/fork.py);
            # surface the n-branch view instead of the bare handle
            handle._fork_children = []
            return ForkHandle(self, handle)
        return handle

    def validate_request(self, request):
        """Submit-time feasibility: raises ValueError for a request
        that could NEVER fit this engine's arena (position space, or
        paged worst-case blocks).  Shared by :meth:`submit` and the
        fleet's disaggregated admission path, so a ship-parked
        request fails the caller synchronously with the same typed
        error a direct submit would."""
        need = len(request.prompt_ids) + request.max_new_tokens
        spec_pad = 0 if self.draft is None else self.spec_k - 1
        if need + spec_pad > self.max_len:
            # speculative engines reserve spec_k - 1 positions of
            # verify-chunk headroom past the last emitted token (the
            # same rule as generate_speculative) — checked HERE so the
            # failure is a submit-time ValueError, not a clipped
            # dynamic_update_slice corrupting a neighbor's rows.
            # max_len is a POSITION-EMBEDDING bound (<= n_positions),
            # not a memory one: within it, the long-context serve
            # path handles long traffic first-class — a chunked-
            # prefill token budget (PagedConfig(prefill_token_budget=)
            # splits a long admission across steps so decode lanes
            # never stall) and, for sliding-window models, windowed
            # paged decode in O(window) blocks.  Only generations
            # whose POSITIONS exceed n_positions remain offline-only
            # (the windowed GPT2LMHead.generate fallback); see
            # docs/SERVING.md "Long-context serving" for what still
            # refuses (windowed without paged=, windowed + prefix
            # cache).
            raise ValueError(
                f"prompt ({len(request.prompt_ids)}) + max_new_tokens "
                f"({request.max_new_tokens})"
                + (f" + spec_k-1 ({spec_pad})" if spec_pad else "")
                + f" exceeds the engine arena max_len ({self.max_len})"
                f" — the model's position space, not a memory limit "
                f"(long admissions within it serve via the chunked-"
                f"prefill budget / windowed paged decode; docs/"
                f"SERVING.md 'Long-context serving'); only beyond-"
                f"n_positions generations need the offline windowed "
                f"GPT2LMHead.generate")
        if self.paged_arena is not None:
            B = self.paged_arena.block_size
            worst = ((len(request.prompt_ids) + request.max_new_tokens
                      - 1 + spec_pad) // B) + 1
            if self._window is not None:
                # a windowed slot never holds more than the blocks
                # covering one window span plus the block being
                # written — out-of-window blocks return to the free
                # list as pos advances, so worst-case footprint is
                # O(window), not O(prompt + generation)
                worst = min(worst,
                            (self._window - 1 + spec_pad) // B + 2)
            if worst > self.paged_arena.num_blocks:
                # a request that could never fit the pool ALONE would
                # deadlock the growth loop; fail it at submit, typed
                raise ValueError(
                    f"request needs up to {worst} KV blocks but the "
                    f"paged pool holds {self.paged_arena.num_blocks}; "
                    f"raise PagedConfig.num_blocks or lower "
                    f"max_new_tokens")
        _require(self._fam, fork=request.n > 1)
        if request.n > 1 or request.structured is not None:
            what = (f"n={request.n}" if request.n > 1
                    else "structured decoding")
            if self.paged_arena is None:
                raise ValueError(
                    f"{what} needs a paged engine (model.serve("
                    f"paged=PagedConfig(...))) — forking rides on the "
                    f"arena's per-block refcounts and structured masks "
                    f"on its per-row dispatch")
            if self.draft is not None:
                raise ValueError(
                    f"{what} is incompatible with speculative decoding "
                    f"(the verify chunk samples several tokens per "
                    f"dispatch; per-token masks and branch logprobs "
                    f"need the one-token step)")
            if self._shard is not None:
                raise ValueError(
                    f"{what} is not supported on the tensor-parallel "
                    f"backend yet (the tp twins predate the mask/"
                    f"logprob dispatch signature)")
        if request.n > 1:
            if self._window is not None:
                raise ValueError(
                    f"n={request.n} on a sliding-window engine: "
                    f"windowed slots DROP out-of-window blocks, which "
                    f"a sibling may still share — fork needs the full "
                    f"block table")
            if self._budget is not None or self._ring:
                raise ValueError(
                    f"n={request.n} with chunked/ring prefill: "
                    f"branches fork off the admission pass, which "
                    f"these paths split across steps; use a plain "
                    f"paged admission for forked requests")
            B = self.paged_arena.block_size
            plen = len(request.prompt_ids)
            shared = plen // B
            tail = (plen + request.max_new_tokens - 1) // B + 1 - shared
            if shared + request.n * tail > self.paged_arena.num_blocks:
                raise ValueError(
                    f"n={request.n} needs up to {shared} shared + "
                    f"{request.n}x{tail} per-branch KV blocks but the "
                    f"paged pool holds {self.paged_arena.num_blocks}; "
                    f"raise PagedConfig.num_blocks, lower n, or lower "
                    f"max_new_tokens")
        if request.structured is not None:
            a = request.structured
            vs = getattr(a, "vocab_size", None)
            if vs is not None and int(vs) != int(self.cfg.vocab_size):
                raise ValueError(
                    f"structured automaton covers vocab_size={vs} but "
                    f"the model's vocab is {self.cfg.vocab_size} — the "
                    f"mask would mis-index logits")
            m0 = np.asarray(a.mask(a.initial()), bool)
            if m0.shape != (int(self.cfg.vocab_size),):
                raise ValueError(
                    f"structured mask shape {m0.shape} != "
                    f"({self.cfg.vocab_size},) — masks must be one "
                    f"bool per vocab token")
            if not m0.any():
                raise ValueError(
                    "structured automaton's initial state accepts NO "
                    "token — the grammar is unsatisfiable under this "
                    "vocab (every legal first emission simulates to a "
                    "dead end)")

    @property
    def pending(self) -> bool:
        """True while any request is queued, occupying a slot,
        mid-chunked-prefill, or swapped out awaiting resume."""
        return (self.scheduler.queue_depth > 0
                or any(s is not None for s in self._slots)
                or bool(self._prefilling)
                or bool(self._swapped))

    def check_block_accounting(self):
        """Leak invariant for the paged arena: every used pool block
        is owned by exactly one of (a) the prefix cache's radix tree,
        (b) a live slot's block table, (c) an in-flight chunked
        prefill.  Anything else is a leaked block — raised as an
        AssertionError naming the counts, so benches and tests can
        assert ``arena.used == cached + live_referenced`` after a
        drain with one call.  Returns the used-block count.  Fork-
        shared blocks are counted ONCE here (ownership is the block
        id, not the refcount) — the arena's refcounts only govern
        when ``free`` actually recycles."""
        arena = self.paged_arena
        if arena is None:
            return 0
        owned = set()
        if self.prefix_cache is not None:
            owned.update(self.prefix_cache.cached_block_ids())
        n_cached = len(owned)
        for s in self._slots:
            if s is not None:
                owned.update(b for b in s.blocks if b != arena.trash)
        for pf in self._prefilling.values():
            owned.update(b for b in pf.blocks if b != arena.trash)
        used = arena.blocks_used
        if used != len(owned):
            raise AssertionError(
                f"paged-arena block leak: arena reports {used} used "
                f"blocks but owners account for {len(owned)} "
                f"({n_cached} cached + {len(owned) - n_cached} "
                f"live/prefilling) — "
                f"{used - len(owned)} block(s) leaked")
        return used

    # -- lifecycle -------------------------------------------------------
    def close(self, force=False):
        """Retire the engine: unregister its ``serve.*{engine=n}``
        metrics from the process-wide observe registry (they would
        otherwise be pinned — TTFT/TPOT value lists included — for
        process lifetime) and drop the KV arena references.  Idempotent;
        the engine must be drained (``not pending``) first unless
        ``force=True`` (the fleet's failover path: an abandoned
        replica's handles are already rejected typed, its device state
        is garbage to be released, not drained).  Also the
        context-manager exit: ``with model.serve(...) as eng: ...``."""
        if self.pending and not force:
            raise RuntimeError(
                f"close() with work in flight (queue="
                f"{self.scheduler.queue_depth}, live={self.live_slots});"
                f" drain with run_until_complete() first")
        if (not force and not self._failed
                and self.paged_arena is not None):
            # leak invariant: a drained engine's arena holds exactly
            # the cache-owned blocks — any extra used block is a leak
            # (a forked branch that freed a shared block, a preempt
            # path that dropped a refcount on the floor)
            self.check_block_accounting()
        self._release_everything()

    def _release_everything(self):
        self.stats.unregister()
        _monitor.forget(self._hb_source)
        _stepprof.forget_engine(self.stats.engine_label)
        if self.prefix_cache is not None:
            self.prefix_cache.unregister()
        if self.paged_arena is not None:
            self.paged_arena.unregister()
        if self.tp_exec is not None:
            self.tp_exec.unregister()
        if self.ep_exec is not None:
            self.ep_exec.unregister()
        if self.pp_exec is not None:
            self.pp_exec.unregister()
        self.stats.registry.remove(*self._own_metrics)
        self._own_metrics = []
        self._kc = self._vc = None
        self._dkc = self._dvc = None
        self._state = None
        self._params = self._d_params = None
        self._swapped = []
        self._prefilling = {}
        self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, exc_type, *a):
        if exc_type is None:
            self.close()
        else:
            # don't let the drained-first check mask the in-flight
            # exception; still release the registry entries AND the
            # arena/params (the pinning close() exists to prevent)
            self._release_everything()
        return False

    @property
    def live_slots(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def live_request_ids(self):
        """Request ids currently occupying slots OR swapped out —
        i.e. STARTED: tokens already streamed through ``on_token`` (a
        swapped request streamed at least its admission token), so
        these are never safely re-runnable elsewhere (the fleet's
        failover path uses exactly this distinction)."""
        ids = {s.handle.request.request_id
               for s in self._slots if s is not None}
        ids.update(sw.request.request_id for sw in self._swapped)
        return ids

    # -- the iteration-level step loop -----------------------------------
    def step(self) -> bool:
        """One engine iteration: decode every live slot by one token,
        retire finished rows, then backfill freed slots from the queue
        (so backfill lands on the very step a row retires).  Returns
        ``pending``.

        Under ``prefill_token_budget`` the step's two programs go out
        back to back: the decode program is launched, then the launches
        of the requests that were already prefilling when the step
        began (they read their private rows, nothing of this step), and
        only then does the host wait for the decode tokens, emit them
        and run the schedule pass with the budget that is left — the
        prefills whose last block has landed are promoted there, after
        the emit, and new work is admitted (``_decode_once``,
        ``_schedule_budgeted``).

        A raising decode/prefill does NOT wedge the engine: every
        in-flight and queued request is rejected with a typed
        :class:`EngineFailedError` (``started`` says which were
        occupying slots), the engine marks itself failed, and the
        error re-raises for the caller/supervisor — no handle is ever
        left dangling behind a dead pool."""
        if self._closed:
            raise RuntimeError(
                "engine is closed; build a new one with model.serve()")
        if self._failed:
            raise EngineFailedError(
                "engine has failed; rebuild it (EngineSupervisor does "
                "this automatically)", engine_step=self.step_count)
        if _monitor.active():
            # arm BEFORE the dispatches below: if the first prefill or
            # decode after an idle period wedges, this beat is what
            # lets the watchdog see an armed, then-silent source — a
            # re-arm only after the dispatch returns would never come
            _monitor.heartbeat(self._hb_source)
        with _trace.phase("serve.step", cat="serve",
                          engine=self.stats.engine_label,
                          step=self.step_count) as ph:
            width = 0
            try:
                if self.paged_arena is not None:
                    # paged growth: every live slot must own the
                    # block(s) the coming decode/spec chunk will write
                    # BEFORE the dispatch; a slot that cannot grow
                    # (pool exhausted, no strictly-lower-priority
                    # victim) swaps ITSELF out
                    with _trace.phase("serve.grow", cat="serve"):
                        self._grow_live_slots()
                # (serve.schedule's totals are the whole step's: the
                # launches that go out ahead of the decode tokens too)
                n_pf, n_ch = self.stats.prefills, self._chunks_run
                n_la, n_sg = self._launches_run, self._segments_run
                left = self._budget
                if any(s is not None for s in self._slots):
                    width, left = self._decode_once()
                with _trace.phase("serve.schedule", cat="serve") as sp:
                    self._schedule(self._clock(), left)
                    sp.set(admitted=self.stats.prefills - n_pf,
                           chunks=self._chunks_run - n_ch,
                           launches=self._launches_run - n_la,
                           segments=self._segments_run - n_sg)
            except Exception as e:
                # (a raising step has no meaningful anatomy: the
                # phase's exit drops stepprof's open record)
                raise self._fail(e) from e
            qd = self.scheduler.queue_depth
            self.stats.on_schedule(qd)
            self.step_count += 1
            arena = self.paged_arena
            # (pending: the step log counts the caller's time until the
            # next step only while work was left waiting)
            pending = self.pending
            ph.set(live=self.live_slots, width=width, queue_depth=qd,
                   pending=pending,
                   blocks_used=(arena.blocks_used if arena is not None
                                else 0),
                   prefill_tokens=self.stats.prefill_tokens,
                   state_slots=(self.live_slots + len(self._prefilling)
                                if self._state_spec else 0),
                   **(self._step_counts if width else {}),
                   **(self._decode_impls if width else {}))
        if not pending and _monitor.active():
            # drained: refresh liveness but DISARM hang detection —
            # an idle engine between traffic bursts is not a wedged
            # one; the next step's top-of-loop beat re-arms
            _monitor.heartbeat(self._hb_source, busy=False)
        return pending

    def _fail(self, cause) -> EngineFailedError:
        """Fail the engine: reject every in-flight (started=True) and
        queued (started=False) request typed, disarm the watchdog
        source, and return the error for ``step()`` to raise.  The KV
        arena and params stay allocated until ``close()`` — the
        supervisor reads nothing from them, but a debugger might."""
        self._failed = True
        # drop any deferred admission writes FIRST: the teardown loop
        # below frees blocks (whose _free_slot_blocks guard would
        # otherwise re-run the very flush that may have just raised —
        # a second raise mid-loop would abandon the remaining handles,
        # breaking the no-dangling-handle contract), and a failing
        # engine's pool state is garbage to be released, not written
        self._pending_scatter = []
        self._pending_keys = []
        self._admit_batch = None
        self._batch_cache = None
        step = self.step_count
        msg = f"engine failed at step {step}: {cause!r}"
        self._log.error("%s — rejecting %d in-flight and %d queued "
                        "requests typed", msg, self.live_slots,
                        self.scheduler.queue_depth)
        _trace.event("serve/engine_failed", cat="serve", step=step,
                     error=repr(cause), live=self.live_slots,
                     queued=self.scheduler.queue_depth)
        self.stats.registry.counter(
            "resilience.engine_failures",
            help="serve engines failed by a raising decode/prefill").inc()
        t_fail = self._clock()
        lbl = self.stats.engine_label
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            self._release_prefix(slot)
            self._free_slot_blocks(slot)
            rid = slot.handle.request.request_id
            # typed rejections must be VISIBLE, not just raised: the
            # instant puts the rejected request in the trace/flight
            # recorder and the ledger hook keeps its timeline from
            # vanishing from the request log
            _trace.event("serve/request_rejected", cat="serve",
                         request=rid, reason="engine_failed",
                         started=True)
            if _reqs._active:
                _reqs._ledger.on_reject(rid, t=t_fail,
                                        reason="engine_failed",
                                        engine=lbl, started=True)
            slot.handle._reject(EngineFailedError(
                f"{msg} ({rid} was in flight, "
                f"{len(slot.emitted)} tokens emitted)", request_id=rid,
                started=True, engine_step=step))
            self._slots[i] = None
            self._handles.pop(rid, None)
        # mid-chunked-prefill requests (the token-budget path) have
        # streamed NOTHING — their first token samples only when the
        # last chunk lands — so they reject requeue-safe
        # (started=False), and their partially-filled blocks return
        # to the free list HERE: a supervisor restart must find zero
        # leaked blocks behind a fault that fired between chunks
        # (docs/RESILIENCE.md; chaos_longctx gates it)
        for idx, pf in list(self._prefilling.items()):
            rid = pf.request.request_id
            if self.prefix_cache is not None and pf.nodes:
                self.prefix_cache.release(pf.nodes)
            if self.paged_arena is not None and pf.blocks:
                self.paged_arena.free(
                    [b for b in pf.blocks[pf.n_shared:]
                     if b != self.paged_arena.trash])
            _trace.event("serve/request_rejected", cat="serve",
                         request=rid, reason="engine_failed",
                         started=False)
            if _reqs._active:
                _reqs._ledger.on_reject(rid, t=t_fail,
                                        reason="engine_failed",
                                        engine=lbl, started=False)
            pf.handle._reject(EngineFailedError(
                f"{msg} ({rid} was mid-chunked-prefill at offset "
                f"{pf.off}, nothing streamed)", request_id=rid,
                started=False, engine_step=step))
            self._handles.pop(rid, None)
        self._prefilling = {}
        # swapped-out requests are STARTED (tokens streamed before the
        # preemption): typed started=True, never requeued — without
        # this pass the generic not-done sweep below would misread
        # them as requeue-safe and a restart would re-stream duplicates
        for sw in self._swapped:
            rid = sw.request.request_id
            _trace.event("serve/request_rejected", cat="serve",
                         request=rid, reason="engine_failed",
                         started=True)
            if _reqs._active:
                _reqs._ledger.on_reject(rid, t=t_fail,
                                        reason="engine_failed",
                                        engine=lbl, started=True)
            sw.handle._reject(EngineFailedError(
                f"{msg} ({rid} was swapped out mid-decode, "
                f"{len(sw.emitted)} tokens emitted)", request_id=rid,
                started=True, engine_step=step))
            self._handles.pop(rid, None)
        self._swapped = []
        for req in self.scheduler.drain():
            h = self._handles.pop(req.request_id, None)
            if h is not None:
                _trace.event("serve/request_rejected", cat="serve",
                             request=req.request_id,
                             reason="engine_failed", started=False)
                if _reqs._active:
                    _reqs._ledger.on_reject(req.request_id, t=t_fail,
                                            reason="engine_failed",
                                            engine=lbl, started=False)
                h._reject(EngineFailedError(
                    f"{msg} ({req.request_id} was queued, not started)",
                    request_id=req.request_id, started=False,
                    engine_step=step))
        # a request can also fail MID-ADMISSION: popped from the queue
        # by schedule() but not yet occupying a slot (e.g. a raising
        # prefill or prefix-cache copy).  It has streamed nothing, so
        # it is requeue-safe (started=False) — without this pass its
        # handle would be cleared unresolved and the caller wedged
        for rid, h in list(self._handles.items()):
            if not h.done():
                _trace.event("serve/request_rejected", cat="serve",
                             request=rid, reason="engine_failed",
                             started=False)
                if _reqs._active:
                    _reqs._ledger.on_reject(rid, t=t_fail,
                                            reason="engine_failed",
                                            engine=lbl, started=False)
                h._reject(EngineFailedError(
                    f"{msg} ({rid} was admitting, not started)",
                    request_id=rid, started=False, engine_step=step))
        self._handles.clear()
        if _monitor.active():
            # dead, not hung: liveness beat with hang detection off so
            # the watchdog doesn't page for an engine that failed FAST
            _monitor.heartbeat(self._hb_source, busy=False)
        return EngineFailedError(msg, engine_step=step)

    def shed(self, reason="slo_pressure", below_priority=None):
        """Shed the lowest-priority queued request (see
        ``FIFOScheduler.shed_lowest``), rejecting its handle with a
        typed :class:`LoadShedError`.  Returns the shed request or
        None.  The supervisor's SLO-pressure admission mode calls this
        before latency collapses; direct engine users can too."""
        victim = self.scheduler.shed_lowest(reason,
                                            below_priority=below_priority)
        if victim is None:
            return None
        h = self._handles.pop(victim.request_id, None)
        if h is not None:
            h._reject(LoadShedError(
                f"{victim.request_id} shed ({reason}): priority "
                f"{victim.priority} was the lowest queued under SLO "
                f"pressure"))
        _trace.event("serve/shed", cat="serve", reason=reason,
                     request=victim.request_id,
                     priority=victim.priority)
        _trace.event("serve/request_rejected", cat="serve",
                     request=victim.request_id,
                     reason=f"shed:{reason}")
        if _reqs._active:
            _reqs._ledger.on_reject(victim.request_id, t=self._clock(),
                                    reason=f"shed:{reason}",
                                    engine=self.stats.engine_label,
                                    started=False)
        self._log.warning("shed %s (%s, priority=%d)",
                          victim.request_id, reason, victim.priority)
        return victim

    def run_until_complete(self, max_steps=None):
        """Drive ``step()`` until every submitted request resolves.
        ``max_steps`` guards tests against scheduling bugs."""
        steps = 0
        while self.pending:
            self.step()
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError(
                    f"engine did not drain within {max_steps} steps "
                    f"(queue={self.scheduler.queue_depth}, "
                    f"live={self.live_slots})")

    # -- internals -------------------------------------------------------
    def _decode_once(self):
        """Decode every live slot by one token (or one speculative
        chunk) and emit: launch the pool step, launch what the budget
        allows of the prefills already in flight behind it, and only
        then wait for the tokens.  Returns the width the pool step ran
        at (0 when a structured dead end emptied the pool before the
        dispatch) and the prefill budget the step has left."""
        if _faults._armed:
            # chaos hook: a fault here is exactly a raising pool decode
            # (speculative mode included — the draft scan, the chunk
            # verify, and the rejection sample all sit behind this one
            # dispatch) — step() fails the engine typed and the
            # supervisor rebuilds; disarmed this is one module-flag
            # read per step
            _faults.check("serve.decode_step")
        live = np.asarray([s is not None for s in self._slots])
        left = self._budget
        # serve.decode: from building the pool step's inputs to its
        # tokens on the host — input building and launch first, then
        # serve.sync, the host blocked on the device
        with _trace.phase("serve.decode", cat="serve",
                          paged=self.paged_arena is not None) as ph:
            launch = (self._launch_spec if self.draft is not None
                      else self._launch_decode)
            flight = launch(live, int(live.sum()))
            if flight is None:
                ph.set(live=0, width=0)
                return 0, left
            if self._prefilling:
                # the early pass: a request that was prefilling when
                # the step began needs nothing of this decode step, so
                # its launches queue behind the decode program now and
                # start the moment it ends — not after the host has
                # noticed, emitted and scheduled.  Dispatch only: a
                # prefill that lands here is promoted after the emit
                # (_schedule_budgeted), never into this step's tokens
                with _trace.phase("serve.launch", cat="serve") as lp:
                    n_ch, n_la = self._chunks_run, self._launches_run
                    left = self._launch_inflight(left)
                    self._c_early_launches.inc(self._launches_run - n_la)
                    lp.set(launches=self._launches_run - n_la,
                           chunks=self._chunks_run - n_ch)
            n_live, width, toks, a_draft, lps = \
                self._collect_step(*flight)
            ph.set(live=n_live, width=width)
            ph.set(**self._decode_impls)
        if _monitor.active():
            # watchdog heartbeat after the pool step, fed from the step
            # log's own stamps and no clock of its own: the step's start
            # to the end of serve.sync, so the np.asarray sync is in it
            # and the fed step time is real device time
            _monitor.heartbeat(
                self._hb_source, step_time=ph.step_elapsed(),
                fresh_compile=self.stats.decode_steps == 0)
        self.stats.on_decode_step(
            n_live, attn_kernel=self._decode_attn == "kernel",
            state_kernel=self._decode_state == "kernel")
        # serve.emit: the emit loop — clients' on_token callbacks,
        # retires and ledger hooks included
        with _trace.phase("serve.emit", cat="serve") as ph:
            t0 = self.stats.tokens_out
            self._emit_step(toks, a_draft, lps)
            ph.set(tokens=self.stats.tokens_out - t0)
        return width, left

    def _launch_spec(self, live, n_live):
        """Launch the speculative pool step; nothing here reads a
        device value.  Returns what :meth:`_collect_step` takes:
        ``(n_live, width, accepted chunk tokens (S, spec_k), accepted
        draft counts (S,), None, None, None)``, still on the device."""
        arena = self.paged_arena
        # (speculative paged steps run at full width: the DRAFT arena
        # is slot-indexed — compacting would have to gather/scatter
        # draft cache rows per step, which is exactly the copy tax
        # the block tables exist to avoid on the target side)
        if arena is not None:
            (out, a_draft, arena.pool_k, arena.pool_v,
             self._dkc, self._dvc,
             self._keys) = self._x.paged_spec_step(
                self._params, self._d_params, arena.pool_k,
                arena.pool_v, self._dkc, self._dvc,
                self._block_tables(), jnp.asarray(self._toks),
                jnp.asarray(self._pos), jnp.asarray(live),
                self._keys, jnp.asarray(self._temps),
                self._top_p, arena.block_size)
        else:
            (out, a_draft, self._kc, self._vc, self._dkc,
             self._dvc, self._keys) = self._x.pool_spec_step(
                self._params, self._d_params, self._kc,
                self._vc, self._dkc, self._dvc,
                jnp.asarray(self._toks),
                jnp.asarray(self._pos), jnp.asarray(live),
                self._keys, jnp.asarray(self._temps),
                self._top_p)
        return n_live, self.max_slots, out, a_draft, None, None, None

    def _launch_decode(self, live, n_live):
        """Launch the plain pool step; nothing after the host-side
        grammar pass reads a device value.  Returns what
        :meth:`_collect_step` takes — ``(n_live, width, next tokens,
        None, chosen-token logprobs or None, the family's step counts
        or None, the lanes of a compacted step or None)``, still on the
        device — or None when a structured dead end emptied the pool
        before the dispatch."""
        arena = self.paged_arena
        # fork/structured pre-dispatch pass (paged, non-spec):
        # per-slot grammar masks computed on the HOST between
        # steps, stacked into one fixed-shape (S, V) bool input
        # (plain slots get all-True rows — a bitwise no-op in the
        # shared _sample), and the chosen-token logprob output
        # turned on whenever any live slot belongs to a fork
        # family.  Both are signature STATICS only in their
        # presence (masks-or-not, lp-or-not), so the warmed jit
        # cache covers every grammar and every fork pattern.
        masks_np = None
        need_lp = False
        if arena is not None:
            t_rej = None
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                if s.group is not None:
                    need_lp = True
                if s.automaton is None:
                    continue
                m = np.asarray(s.automaton.mask(s.astate), bool)
                if not m.any():
                    # no vocab token continues the grammar from
                    # here (incomplete output, nothing legal to
                    # emit): that request is dead, typed — the
                    # engine keeps serving everyone else
                    t_rej = self._clock()
                    rid = s.handle.request.request_id
                    self._log.warning(
                        "structured automaton for %s reached a "
                        "dead end (no legal token); rejecting "
                        "that request", rid)
                    self._reject_live(
                        i, s,
                        ValueError(
                            f"{rid}: structured automaton state "
                            f"{s.astate!r} admits no vocab token "
                            f"— the grammar cannot complete from "
                            f"here"),
                        "structured_dead_end", t_rej)
                    continue
                if masks_np is None:
                    masks_np = np.ones(
                        (self.max_slots, self.cfg.vocab_size),
                        bool)
                masks_np[i] = m
            if t_rej is not None:
                live = np.asarray(
                    [s is not None for s in self._slots])
                n_live = int(live.sum())
                if n_live == 0:
                    return None
        lanes = lps = counts = None
        width = self.max_slots
        if arena is not None:
            # COMPACTED dispatch (the gather-tax round): run
            # the pool step at the smallest width bucket
            # covering the live slots instead of always at
            # max_slots.  Legal precisely because the pool is
            # paged — block tables address the KV, so a lane
            # permutation is pure host bookkeeping (per-slot
            # math is lane-independent; pad lanes are dead:
            # clamped inputs, trash-table writes, keys never
            # written back).  An over-provisioned engine
            # (many slots, few live) stops paying dead-lane
            # MLP/vocab/sampling work per step.
            lanes = np.flatnonzero(live)
            width = self._paged_width(len(lanes))
            # masks/with_lp only when active: the sharded
            # executors (tp/ep/pp) predate the fork
            # signature and validation refuses fork on
            # them, so the plain call must stay kwarg-free
            fkw = {}
            if need_lp:
                fkw["with_lp"] = True
            if width < self.max_slots:
                sel = np.full(width, -1, np.intp)
                sel[:len(lanes)] = lanes
                live_w = np.zeros(width, bool)
                live_w[:len(lanes)] = True
                sel_in = np.where(sel < 0, 0, sel)
                keys_w = _take_rows(self._keys,
                                    jnp.asarray(sel_in))
                if masks_np is not None:
                    fkw["masks"] = jnp.asarray(masks_np[sel_in])
                if self._state is not None:
                    # each lane's row of the state arenas; a pad lane
                    # reads and writes the trash row
                    fkw.update(state=self._state, slots=jnp.asarray(
                        np.where(sel < 0, self.max_slots, sel)
                        .astype(np.int32)))
                res = self._x.paged_decode_step(
                    self._params, arena.pool_k, arena.pool_v,
                    self._block_tables(list(sel)),
                    jnp.asarray(self._toks[sel_in]),
                    jnp.asarray(self._pos[sel_in]),
                    jnp.asarray(live_w), keys_w,
                    jnp.asarray(self._temps[sel_in]),
                    self._top_p, arena.block_size, **fkw)
                next_toks, arena.pool_k, arena.pool_v, keys2 = \
                    res[:4]
                self._keys = _set_rows(
                    self._keys, jnp.asarray(lanes),
                    keys2[:len(lanes)])
            else:
                lanes = None
                if masks_np is not None:
                    fkw["masks"] = jnp.asarray(masks_np)
                if self._state is not None:
                    fkw.update(state=self._state, slots=jnp.asarray(
                        np.where(live, np.arange(self.max_slots),
                                 self.max_slots).astype(np.int32)))
                res = self._x.paged_decode_step(
                    self._params, arena.pool_k, arena.pool_v,
                    self._block_tables(),
                    jnp.asarray(self._toks),
                    jnp.asarray(self._pos), jnp.asarray(live),
                    self._keys, jnp.asarray(self._temps),
                    self._top_p, arena.block_size, **fkw)
                (next_toks, arena.pool_k, arena.pool_v,
                 self._keys) = res[:4]
            if self._fam.step_counts:
                res, counts = res[:-1], res[-1]
            if need_lp:
                lps = res[4]
            if self._state is not None:
                self._state = res[-1]
        else:
            next_toks, self._kc, self._vc, self._keys = \
                self._x.pool_decode_step(
                    self._params, self._kc, self._vc,
                    jnp.asarray(self._toks),
                    jnp.asarray(self._pos),
                    jnp.asarray(live), self._keys,
                    jnp.asarray(self._temps), self._top_p)
        return n_live, width, next_toks, None, lps, counts, lanes

    def _collect_step(self, n_live, width, toks, a_draft, lps, counts,
                      lanes):
        """Wait for the pool step :meth:`_launch_decode` or
        :meth:`_launch_spec` launched (``serve.sync``: the host blocked
        on the decode program alone — launches queued behind it run
        on) and bring its outputs to the host, a compacted step's lanes
        back at their slots.  Returns ``(n_live, width, tokens (S,) or
        (S, spec_k), accepted draft counts (S,) or None, logprobs (S,)
        or None)``."""
        with _trace.phase("serve.sync", cat="serve"):
            toks = np.asarray(toks)
            if a_draft is not None:
                a_draft = np.asarray(a_draft)
            if lps is not None:
                lps = np.asarray(lps)
            if counts is not None:
                counts = np.asarray(counts)
        if counts is not None:
            self._on_step_counts(counts)
        if lanes is not None:
            # a compacted step's lanes back at their slots
            wide = np.zeros(self.max_slots, np.int32)
            wide[lanes] = toks[:len(lanes)]
            toks = wide
            if lps is not None:
                wide = np.zeros(self.max_slots)
                wide[lanes] = lps[:len(lanes)]
                lps = wide
        return n_live, width, toks, a_draft, lps

    def _on_step_counts(self, counts):
        """One decode step's counts, as the family reads them: span
        arguments for this step's ``serve.step``, increments of the
        engine's counters and, where the family gives a third, values
        of its gauges (each metric made on first use, removed at
        close)."""
        self._step_counts, incs, *gauges = self._fam.on_step_counts(
            counts, self.cfg)
        reg = self.stats.registry
        for make, update, values in (
                (reg.counter, "inc", incs),
                (reg.gauge, "set", gauges[0] if gauges else {})):
            for key, n in values.items():
                m = self._count_metrics.get(key)
                if m is None:
                    m = self._count_metrics[key] = make(
                        key[0], engine=self.stats.engine_label,
                        **dict(key[1]))
                    self._own_metrics.append(m)
                getattr(m, update)(n)

    def _emit_step(self, next_toks, a_draft, lps):
        """Emit one decode step's tokens slot by slot
        (``next_toks``: (S,) plain, (S, spec_k) speculative)."""
        t_emit = self._clock()
        led = _reqs._ledger if _reqs._active else None
        lbl = self.stats.engine_label
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            rid = slot.handle.request.request_id
            if a_draft is None:
                if lps is not None and slot.group is not None:
                    # best-of-n ranking signal: cumulative chosen-
                    # token logprob under the raw distribution,
                    # accumulated BEFORE _emit (which may retire the
                    # slot and seal the score into the result)
                    slot.score += float(lps[i])
                self._emit(i, slot, int(next_toks[i]), t_emit)
                if led is not None:
                    led.on_step(rid, engine=lbl, t=t_emit, tokens=1)
                self._toks[i] = next_toks[i]
                self._pos[i] += 1
                continue
            # speculative: up to a_draft[i] + 1 accepted tokens this
            # step.  Emission stops mid-chunk the moment the request
            # retires (budget hit, stop token) or rejects (raising
            # on_token) — tokens past that point are discarded, and
            # their cache rows are dead weight the next admission's
            # full-row write replaces
            a = int(a_draft[i]) + 1
            self.stats.on_spec(int(a_draft[i]), self.spec_k - 1)
            emitted = 0
            for j in range(a):
                self._emit(i, slot, int(next_toks[i, j]), t_emit)
                emitted += 1
                if self._slots[i] is not slot:
                    break
            if led is not None:
                # per-step ledger record with the chunk's acceptance:
                # emitted tokens (may stop mid-chunk), accepted
                # proposals, proposals offered (lands on the sealed
                # entry when the last token retired the request)
                led.on_step(rid, engine=lbl, t=t_emit, tokens=emitted,
                            accepted=int(a_draft[i]),
                            drafted=self.spec_k - 1)
            if self._slots[i] is slot:
                self._toks[i] = int(next_toks[i, emitted - 1])
                self._pos[i] += emitted

    def _emit(self, idx, slot, token, now):
        slot.emitted.append(token)
        slot.remaining -= 1
        req = slot.handle.request
        self.stats.on_token()
        if slot.first_token_time is None:
            slot.first_token_time = now
        if req.on_token is not None:
            try:
                req.on_token(req, token)
            except Exception as e:
                # a raising CLIENT callback is that request's failure,
                # not an engine death: reject it typed-as-raised, free
                # the slot, and keep serving the other tenants (a
                # blanket engine _fail here would let one bad streaming
                # client burn everyone — and the supervisor's restart
                # budget with it)
                self._log.warning(
                    "on_token callback for %s raised (%r); rejecting "
                    "that request, slot %d freed", req.request_id, e,
                    idx)
                self._reject_live(idx, slot, e, "on_token_callback",
                                  now)
                return
        if slot.automaton is not None:
            # structured decoding: advance the grammar with the token
            # the mask admitted.  A mismatch here means the mask and
            # the automaton disagree — an automaton bug, charged to
            # THIS request (typed reject), never an engine death.
            try:
                slot.astate = slot.automaton.advance(slot.astate,
                                                     token)
            except Exception as e:
                self._log.warning(
                    "structured automaton for %s rejected its own "
                    "masked token (%r); rejecting that request",
                    req.request_id, e)
                self._reject_live(idx, slot, e, "structured_advance",
                                  now)
                return
            if slot.automaton.done(slot.astate):
                self._retire(idx, slot, now, finish_reason="stop")
                return
        stop = (req.stop_token is not None and token == req.stop_token)
        if stop or slot.remaining <= 0:
            # budget/EOS retire is per TOKEN, not per step: a
            # multi-token speculative chunk retires mid-chunk the
            # moment the budget or the stop token lands, and the
            # chunk's remaining tokens are never emitted
            self._retire(idx, slot, now,
                         finish_reason="stop" if stop else "length")

    def _retire(self, idx, slot, now, finish_reason="length"):
        req = slot.handle.request
        n = len(slot.emitted)
        _trace.event("serve/retire", cat="serve",
                     request=req.request_id, slot=idx, tokens=n,
                     step=self.step_count)
        if _reqs._active:
            _reqs._ledger.on_retire(req.request_id,
                                    engine=self.stats.engine_label,
                                    t=now, finish_reason=finish_reason,
                                    tokens=n)
        submit_t = getattr(slot.handle, "_submit_time", slot.admit_time)
        ttft = slot.first_token_time - submit_t
        tpot = ((now - slot.first_token_time) / (n - 1)
                if n > 1 else None)
        result = GenerationResult(
            request_id=req.request_id,
            tokens=np.concatenate(
                [req.prompt_ids,
                 np.asarray(slot.emitted, np.int32)]),
            finish_reason=finish_reason,
            ttft=ttft, tpot=tpot,
            queue_time=slot.admit_time - submit_t,
            admitted_step=slot.admitted_step,
            finished_step=self.step_count,
            branch=slot.branch,
            score=(slot.score if slot.group is not None else None))
        if self.paged_arena is not None:
            self._paged_retire(idx, slot, req, result)
        elif self.prefix_cache is not None:
            self._prefix_retire(idx, slot, req, result)
        elif req.pin_session:
            # no cache: the session handle still works, continuation
            # just runs through cold prefill
            result.session = SessionHandle(result.tokens)
        slot.handle._finish(result)
        self.stats.on_complete(result)
        self._slots[idx] = None
        # the caller's handle owns the result now; dropping the routing
        # entry keeps a long-lived engine's memory flat under sustained
        # traffic
        self._handles.pop(req.request_id, None)
        if self.paged_arena is not None:
            self._fork_gauge()

    def _reject_live(self, idx, slot, error, reason, now):
        """Reject a LIVE slot's request typed (client callback raised,
        structured dead end, CoW copy faulted): release its prefix
        refs, free/deref its blocks, drop the slot, and seal the
        handle with ``error``.  Started=True — tokens streamed, never
        requeue-safe.  The engine keeps serving everyone else."""
        req = slot.handle.request
        self._release_prefix(slot)
        self._free_slot_blocks(slot)
        self._slots[idx] = None
        self._handles.pop(req.request_id, None)
        _trace.event("serve/request_rejected", cat="serve",
                     request=req.request_id, reason=reason)
        if _reqs._active:
            _reqs._ledger.on_reject(
                req.request_id, t=now, reason=reason,
                engine=self.stats.engine_label, started=True)
        slot.handle._reject(error)
        if self.paged_arena is not None:
            self._fork_gauge()

    def _fork_gauge(self):
        if self._g_fork_shared is not None:
            self._g_fork_shared.set(self.paged_arena.shared_blocks)

    def _release_prefix(self, slot):
        if self.prefix_cache is not None and slot.prefix_nodes:
            self.prefix_cache.release(slot.prefix_nodes)
            slot.prefix_nodes = []

    # -- paged-arena internals -------------------------------------------
    def _free_slot_blocks(self, slot):
        """Teardown for a paged slot that will not retire normally:
        free its private blocks (shared prefix blocks are only
        ref-released, by ``_release_prefix``).  Deferred admission
        writes flush FIRST: a block freed here could be re-allocated
        by a later same-pass admission, and a pending scatter landing
        after that would clobber the new owner."""
        if self._pending_scatter or self._pending_keys:
            self._flush_admission_writes()
        if self.paged_arena is not None and slot.blocks:
            # windowed slots hold trash sentinels at already-dropped
            # leading lanes — those were freed when they left the
            # window, so only real ids return to the free list
            self.paged_arena.free(
                [b for b in slot.blocks[slot.n_shared:]
                 if b != self.paged_arena.trash])
            slot.blocks = []

    def _block_tables(self, idxs=None):
        """The (S, W//B) int32 block-table input of the paged pool
        steps: each live slot's block list, trash-padded (dead slots
        are all-trash, so their writes land in the trash block).
        ``idxs``: optional slot-id row order for a COMPACTED step
        (entries < 0 are pad lanes — all-trash rows)."""
        arena = self.paged_arena
        rows = (range(self.max_slots) if idxs is None else idxs)
        tables = np.full((len(rows), arena.row_blocks),
                         arena.trash, np.int32)
        for r, i in enumerate(rows):
            slot = self._slots[i] if i >= 0 else None
            if slot is not None:
                tables[r, :len(slot.blocks)] = slot.blocks
        return jnp.asarray(tables)

    def _paged_width(self, n_live):
        """Decode-dispatch width for ``n_live`` live slots: the
        smallest HALVING bucket of ``max_slots`` still covering them
        ({S, S/2, S/4, ...} — one compiled signature per bucket,
        ~log2(S) of them, all covered by a warmup pass over the same
        workload, since the live trajectory is deterministic).
        The paged pool makes this free: KV is addressed by BLOCK
        TABLES, not by slot index, so a step over any subset of slots
        is just a shorter table/token batch — no cache rows move.
        The slot arena cannot compact (its KV is indexed by slot),
        which is why over-provisioned paged engines stop paying the
        dead-lane tax the moment occupancy sits below the peak — the
        per-step decode cost is COMPUTE-bound in the lane count
        (MLP + vocab per lane), so width tracks occupancy nearly 1:1
        in step time.  Halving (not a finer ladder) is deliberate:
        each sub-width step pays two small key-compaction dispatches,
        so buckets must buy a real width drop to be worth switching
        (measured: a 3/4 ladder was net SLOWER at the bench
        geometry)."""
        w = self.max_slots
        while w >= 2 and w >= 2 * n_live:
            w //= 2
        return max(w, n_live)

    def _grow_live_slots(self):
        """Block-by-block growth: before the pool step dispatches,
        every live slot must own the block(s) covering the position(s)
        this step writes (``pos`` .. ``pos + spec_k - 1`` on a
        speculative engine).  A slot that cannot grow — pool exhausted
        and no strictly-lower-priority victim to preempt — swaps
        ITSELF out: its blocks free the pool for the others and it
        resumes (byte-identical) once capacity returns, so the pool
        never livelocks with every slot too big to advance."""
        arena = self.paged_arena
        B = arena.block_size
        W = self._window
        for i in range(self.max_slots):
            slot = self._slots[i]
            if slot is None:
                continue
            pos = int(self._pos[i])
            if W is not None:
                # DROP out-of-window blocks first (so this slot's own
                # freed block can satisfy its growth below): block j
                # is fully dead once its last position (j+1)*B - 1
                # falls below the lowest key the next query attends
                # (pos - W + 1) — the long-chat O(window) memory
                # model.  The table lane keeps a trash sentinel so
                # block indices stay positional.
                dead = max(0, (pos - W + 1) // B)
                drop = [b for b in slot.blocks[:dead]
                        if b != arena.trash]
                if drop:
                    if self._pending_scatter or self._pending_keys:
                        # a deferred admission write could target a
                        # block about to be freed-and-reallocated
                        self._flush_admission_writes()
                    arena.free(drop)
                    arena.on_window_drop(len(drop))
                    for j in range(min(dead, len(slot.blocks))):
                        slot.blocks[j] = arena.trash
            if slot.cow:
                # copy-on-first-write (serve/fork.py): this step
                # writes position pos into block pos // B — if a
                # sibling still references that block, give this slot
                # a private byte copy BEFORE the dispatch so the
                # sibling's KV is never clobbered.  Fork geometry
                # keeps wb >= n_shared always (branches share at the
                # write frontier, past the cache-owned prefix), so
                # cache-owned blocks are never copied here.
                wb = pos // B
                if wb < len(slot.blocks) \
                        and arena.is_shared(slot.blocks[wb]):
                    if not self._cow_copy(i, slot, wb):
                        continue
            need = (pos + self._spec_pad) // B + 1
            short = need - len(slot.blocks)
            if short <= 0:
                continue
            prio = getattr(slot.handle.request, "priority", 0)
            got = self._alloc_blocks(short, prio, exclude_idx=i)
            if got is None:
                self._preempt_slot(i, reason="pool_exhausted")
                continue
            slot.blocks.extend(got)

    def _cow_copy(self, idx, slot, wb):
        """Give ``slot`` a private copy of its sibling-shared block
        ``wb`` before this step writes into it.  Returns False when
        the slot did not survive (pool exhausted → self-preempt, or
        the copy dispatch faulted → typed reject) — the caller skips
        the slot this pass."""
        arena = self.paged_arena
        prio = getattr(slot.handle.request, "priority", 0)
        got = self._alloc_blocks(1, prio, exclude_idx=idx)
        if got is None:
            self._preempt_slot(idx, reason="pool_exhausted")
            return False
        old = slot.blocks[wb]
        try:
            arena.copy_block(old, got[0])
        except Exception as e:
            # the CoW copy is this BRANCH's work, not the engine's:
            # a fault here (resilience site serve.fork_copy) rejects
            # the one branch typed and frees its claim — siblings and
            # unrelated tenants keep streaming
            arena.free(got)
            self._log.warning(
                "CoW block copy for %s faulted (%r); rejecting that "
                "branch, slot %d freed",
                slot.handle.request.request_id, e, idx)
            self._reject_live(idx, slot, e, "fork_copy", self._clock())
            return False
        slot.blocks[wb] = got[0]
        arena.free([old])  # drop this slot's reference; sibling keeps it
        self._c_fork_cow.inc()
        self._fork_gauge()
        return True

    def _alloc_blocks(self, n, priority, exclude_idx=None):
        """``n`` pool blocks for a request at ``priority``, evicting
        unreferenced cached blocks first (arena.alloc) and then
        PREEMPTING strictly-lower-priority live slots (lowest
        priority, then latest admitted) until the allocation fits or
        no victim remains.  Strictly-lower only: equal-priority slots
        never preempt each other, which is what makes every preemption
        chain terminate.

        Feasibility is checked BEFORE any side effect: when free +
        evictable + every eligible victim's private blocks still
        cannot cover ``n`` (e.g. pinned sessions hold unevictable
        references), the claimant simply waits — preempting victims
        that cannot make the allocation fit would be pure swap churn,
        and with a permanently infeasible head request it would
        livelock the engine (preempt → fail → resume → preempt)."""
        arena = self.paged_arena
        avail = arena.blocks_free
        if self.prefix_cache is not None:
            avail += self.prefix_cache.evictable_blocks()
        trash = arena.trash
        # a victim's sibling-shared blocks do NOT come back to the
        # free list (free only drops a reference), so they cannot
        # count toward feasibility
        avail += sum(
            sum(1 for b in s.blocks[s.n_shared:]
                if b != trash and not arena.is_shared(b))
            for i, s in enumerate(self._slots)
            if s is not None and i != exclude_idx
            and getattr(s.handle.request, "priority", 0) < priority)
        if n > avail:
            return None
        while True:
            got = arena.alloc(n)
            if got is not None:
                return got
            victim = self._pick_victim(priority, exclude=exclude_idx)
            if victim is None:
                return None
            self._preempt_slot(victim, reason="preempted")

    def _pick_victim(self, below_priority, exclude=None):
        """The live slot to preempt for a ``below_priority`` claimant:
        strictly lower priority only; lowest priority first, ties to
        the latest-admitted (least sunk progress).  None when nothing
        qualifies."""
        best = None
        for i, s in enumerate(self._slots):
            if s is None or i == exclude:
                continue
            p = getattr(s.handle.request, "priority", 0)
            if p >= below_priority:
                continue
            k = (p, -s.admitted_step)
            if best is None or k < best[0]:
                best = (k, i)
        return None if best is None else best[1]

    def _preempt_slot(self, idx, reason):
        """Swap one live request's state to HOST memory and free its
        blocks: one fixed-shape gather + device sync for the target
        lanes (plus the draft row on a speculative engine), every
        scrap of host bookkeeping saved, shared prefix refs released.
        The byte copy is what keeps a resumed request's remaining
        token stream identical to the uninterrupted run's — see
        serve/paged.py's module docstring for why recompute-on-resume
        could not promise that."""
        arena = self.paged_arena
        # a same-pass admission's deferred writes must land before
        # this gather reads the pool (and before self._keys[idx] is
        # snapshotted below) — the victim could be a slot admitted
        # earlier in the very pass that is now preempting
        if self._pending_scatter or self._pending_keys:
            self._flush_admission_writes()
        slot = self._slots[idx]
        req = slot.handle.request
        rid = req.request_id
        pos = int(self._pos[idx])
        sw = _Swapped()
        sw.handle = slot.handle
        sw.request = req
        sw.emitted = slot.emitted
        sw.remaining = slot.remaining
        sw.first_token_time = slot.first_token_time
        sw.admit_time = slot.admit_time
        sw.admitted_step = slot.admitted_step
        sw.pos = pos
        sw.tok = int(self._toks[idx])
        sw.temp = float(self._temps[idx])
        sw.key = np.asarray(self._keys[idx])
        # windowed slots: leading lanes already dropped to trash hold
        # no bytes — swap only the live tail, and remember the lane
        # offset so resume rebuilds the same positional table (the
        # swap image stays O(window) like the device footprint)
        sw.j_lo = 0
        if self._window is not None:
            while sw.j_lo < len(slot.blocks) \
                    and slot.blocks[sw.j_lo] == arena.trash:
                sw.j_lo += 1
        sw.n_data = max(0, (pos - 1) // arena.block_size + 1
                        - sw.j_lo)
        sw.seq = next(self._swap_seq)
        sw.t_preempt = self._clock()
        # fork/structured state rides the swap image too: the resumed
        # slot scores and masks exactly as the uninterrupted one would
        sw.group = slot.group
        sw.branch = slot.branch
        sw.score = slot.score
        sw.automaton = slot.automaton
        sw.astate = slot.astate
        # the swap image rides the shared versioned host format
        # (serve/kvimage.py) — the same one KV shipping uses, so the
        # two host-image paths cannot drift
        sw.image = arena.swap_out(slot.blocks[sw.j_lo:], sw.n_data)
        sw.state = None
        if self._state is not None:
            # the slot's row of every state arena travels with its
            # blocks, byte for byte
            with _trace.phase("serve.state.snapshot", cat="serve"):
                sw.state = jax.tree.map(
                    np.asarray,
                    _read_state(self._state, jnp.int32(idx)))
            self._c_state_snapshots.inc()
        sw.dkc_h = sw.dvc_h = None
        if self.draft is not None:
            dkc_row, dvc_row = _read_slot(self._dkc, self._dvc,
                                          jnp.int32(idx))
            sw.dkc_h = jax.tree.map(np.asarray, dkc_row)
            sw.dvc_h = jax.tree.map(np.asarray, dvc_row)
        n_freed = sum(1 for b in slot.blocks[slot.n_shared:]
                      if b != arena.trash)
        self._free_slot_blocks(slot)
        self._release_prefix(slot)
        self._slots[idx] = None
        self._swapped.append(sw)
        arena.on_preempt()
        _trace.event("serve/preempt", cat="serve", request=rid,
                     slot=idx, reason=reason, pos=pos,
                     blocks_freed=n_freed, tokens=len(sw.emitted))
        if _reqs._active:
            _reqs._ledger.on_preempt(rid,
                                     engine=self.stats.engine_label,
                                     t=sw.t_preempt)
        self._log.info("preempted %s (%s): %d blocks freed at pos %d",
                       rid, reason, n_freed, pos)

    def _try_resume(self, now):
        """Resume swapped-out requests, highest priority first (FIFO
        within a class): allocate the full block need (preempting
        strictly-lower live slots if necessary), scatter the host copy
        back, restore the slot state and sampling key.  Head-of-line
        semantics: if the best swapped request does not fit, nothing
        behind it jumps the line."""
        if not self._swapped:
            return
        arena = self.paged_arena
        B = arena.block_size
        while self._swapped:
            # re-sort every iteration: a resume's own preemption (of a
            # strictly-lower live slot) APPENDS to the swap list, and
            # the next head must still be the highest-priority oldest
            self._swapped.sort(key=lambda s: (-s.priority, s.seq))
            # a slot reserved by an in-flight chunked prefill is NOT
            # free: a resume landing there would be clobbered when
            # _finish_prefilling promotes the reservation
            free = self._free_slots()
            if not free:
                return
            sw = self._swapped[0]
            j_lo = getattr(sw, "j_lo", 0)
            need = (sw.pos + self._spec_pad) // B + 1 - j_lo
            blocks = self._alloc_blocks(need, sw.priority)
            if blocks is None:
                return
            idx = free[0]
            arena.swap_in(sw.image, blocks[:sw.n_data])
            if sw.state is not None:
                with _trace.phase("serve.state.restore", cat="serve"):
                    self._state = _write_state(
                        self._state,
                        jax.tree.map(jnp.asarray, sw.state),
                        jnp.int32(idx))
                self._c_state_restores.inc()
            if self.draft is not None and sw.dkc_h is not None:
                self._dkc, self._dvc = _write_slot(
                    self._dkc, self._dvc,
                    jax.tree.map(jnp.asarray, sw.dkc_h),
                    jax.tree.map(jnp.asarray, sw.dvc_h),
                    jnp.int32(idx))
            slot = _Slot(sw.handle, sw.remaining, sw.admit_time,
                         sw.admitted_step)
            slot.emitted = sw.emitted
            slot.first_token_time = sw.first_token_time
            # windowed: rebuild the positional table with the dropped
            # leading lanes as trash sentinels (same shape the
            # uninterrupted slot would hold at this pos)
            slot.blocks = [arena.trash] * j_lo + blocks
            slot.n_shared = 0
            # the swap-in scattered a private byte copy of every
            # block, so the resumed slot shares nothing: cow stays
            # False, but its fork identity/score and grammar state
            # continue where they left off
            slot.group = getattr(sw, "group", None)
            slot.branch = getattr(sw, "branch", 0)
            slot.score = getattr(sw, "score", 0.0)
            slot.automaton = getattr(sw, "automaton", None)
            slot.astate = getattr(sw, "astate", None)
            self._slots[idx] = slot
            self._toks[idx] = sw.tok
            self._pos[idx] = sw.pos
            self._temps[idx] = sw.temp
            self._keys = self._keys.at[idx].set(jnp.asarray(sw.key))
            self._swapped.pop(0)
            rid = sw.request.request_id
            _trace.event("serve/resume", cat="serve", request=rid,
                         slot=idx, pos=sw.pos,
                         swapped_s=now - sw.t_preempt)
            if _reqs._active:
                _reqs._ledger.on_resume(
                    rid, engine=self.stats.engine_label, t=now)
            self._log.info("resumed %s after %.3fs swapped", rid,
                           now - sw.t_preempt)

    def _paged_retire(self, idx, slot, req, result):
        """Retire teardown for the paged arena.  Donation is
        ZERO-COPY: the slot's prompt blocks already live in the shared
        pool, so the radix tree ADOPTS them (``adopt_blocks``) instead
        of scattering a copy — only a pinned session's generated
        windows pay a re-canonicalization chunk pass (decode-step KV
        is not canonical; same analysis as ``_prefix_retire``)."""
        arena = self.paged_arena
        cache = self.prefix_cache
        B = arena.block_size
        try:
            if cache is None:
                if req.pin_session:
                    result.session = SessionHandle(result.tokens)
                return
            plen = len(req.prompt_ids)
            total = len(result.tokens)
            want_session = bool(req.pin_session)
            n_goal = (total // B) if want_session else (plen // B)
            # the FINAL emitted token's KV position is never written
            # (nothing decodes after it), so at block_size=1 its block
            # was never allocated — a session pins one block less (the
            # next turn's admission recomputes the tail block anyway)
            n_goal = min(n_goal, len(slot.blocks))
            # fork: never adopt a block a LIVE sibling still shares —
            # the tree would own a block the sibling may CoW-free, and
            # double-ownership breaks the accounting invariant.  The
            # LAST retiring sibling sees refcount 1 everywhere and
            # adopts the full prefix, so the cache still wins it.
            for j in range(slot.n_shared, n_goal):
                if arena.is_shared(slot.blocks[j]):
                    n_goal = j
                    break
            path = []
            if n_goal > 0:
                if want_session and n_goal > plen // B:
                    kc_row, vc_row = arena.gather_row(slot.blocks)
                    ids = np.zeros((1, self.max_len), np.int32)
                    ids[0, :total] = result.tokens
                    ids_j = jnp.asarray(ids)
                    for j in range(plen // B, n_goal):
                        _, kc_row, vc_row = self._x.chunk_row(
                            self._params, ids_j, kc_row, vc_row,
                            jnp.int32(j * B))
                    arena.scatter_row(
                        kc_row, vc_row,
                        {j: slot.blocks[j]
                         for j in range(plen // B, n_goal)})
                path = cache.adopt_blocks(result.tokens, slot.blocks,
                                          n_goal)
            if want_session:
                cache.acquire(path)
                result.session = SessionHandle(result.tokens, cache,
                                               path)
            # free the private blocks the tree did not adopt (the
            # decode-region blocks, the growth block, and any lane a
            # sibling's earlier donation made a duplicate of)
            adopted = {n.block for n in path}
            arena.free([b for b in slot.blocks[slot.n_shared:]
                        if b not in adopted])
            slot.blocks = []
        finally:
            self._release_prefix(slot)
            # exception path: nothing was adopted, every private
            # block is still slot-owned — free them so a raising
            # donation cannot leak pool capacity
            self._free_slot_blocks(slot)

    def _prefix_retire(self, idx, slot, req, result):
        """Donate the retired request's prefix back to the radix tree
        (its prompt's full blocks are canonical prefill K/V sitting in
        the slot row — decode never touched positions < prompt_len),
        and pin the FULL sequence for ``pin_session`` requests.

        Session pinning re-canonicalizes the generated region first:
        decode-step K/V is not bitwise prefill K/V (~1e-6 drift), so
        the windows containing generated tokens are recomputed through
        the same ``_chunk_row`` executable warm admission uses — one
        chunk pass at retire (off the TTFT path) keeps every future
        warm turn byte-identical to cold prefill."""
        cache = self.prefix_cache
        B = cache.block_size
        try:
            plen = len(req.prompt_ids)
            total = len(result.tokens)
            want_session = bool(req.pin_session)
            n_goal = (total // B) if want_session else (plen // B)
            path = []
            if n_goal > 0:
                existing = cache.lookup(result.tokens)[:n_goal]
                if len(existing) == n_goal:
                    # everything already cached (steady-state hit
                    # regime): no row gather, no chunks, no scatter —
                    # just refresh recency
                    cache.touch(existing)
                    path = existing
                else:
                    kc_row, vc_row = self._x.read_slot(
                        self._kc, self._vc, jnp.int32(idx))
                    if want_session and total // B > plen // B:
                        ids = np.zeros((1, self.max_len), np.int32)
                        ids[0, :total] = result.tokens
                        ids_j = jnp.asarray(ids)
                        for j in range(plen // B, total // B):
                            _, kc_row, vc_row = self._x.chunk_row(
                                self._params, ids_j, kc_row, vc_row,
                                jnp.int32(j * B))
                    path = cache.donate_from_row(result.tokens, kc_row,
                                                 vc_row, n_goal)
            if want_session:
                cache.acquire(path)
                result.session = SessionHandle(result.tokens, cache,
                                               path)
        finally:
            self._release_prefix(slot)

    def _schedule(self, now, left):
        """The step's scheduling pass; ``left`` is the prefill budget
        the step has not spent yet (None without
        ``prefill_token_budget``)."""
        if self.paged_arena is not None:
            # swapped requests re-enter BEFORE new admissions: they
            # already made progress (and streamed tokens), so leaving
            # them swapped behind fresh arrivals would invert both the
            # priority order and the latency story
            self._try_resume(now)
        if self._budget is not None:
            # chunked-prefill token budget (the long-context round):
            # a dedicated pass that first advances in-flight chunked
            # prefills and then admits new work against the step's
            # remaining token budget — one admission can span many
            # steps, so the whole-prompt flow below does not apply
            self._schedule_budgeted(now, left)
            return
        free = self._free_slots()
        if not free and self.scheduler.queue_depth == 0:
            return
        admit = self._sched_admissions(len(free), now)
        blocked_p = self._blocked_priority()
        # BATCHED pass prefill (the gather-tax round): a multi-request
        # pass on a cold paged engine (no prefix cache to consult, no
        # draft rows to build) prefills every admission in ONE
        # dispatch + one host sync up front, so an arrival burst costs
        # the live decode lanes one prefill's latency instead of K —
        # the computation is pure (block allocation happens per
        # request below), so a request that ultimately requeues only
        # wasted its row, never pool state
        # only the prefix that will actually be admitted is worth
        # prefilling: admission order blocks at the first request a
        # swapped higher-priority request outranks, so batching past
        # it would pay a whole discarded dispatch + sync EVERY pass
        # for as long as the blockage lasts
        batchable = admit
        if blocked_p is not None:
            batchable = []
            for r in admit:
                if getattr(r, "priority", 0) <= blocked_p:
                    break
                batchable.append(r)
        # forked (n>1) and structured admissions keep the per-request
        # path: the batch prefill samples tok0 unmasked and its rows
        # predate the fork bookkeeping
        if not all(getattr(r, "n", 1) == 1
                   and getattr(r, "structured", None) is None
                   for r in batchable):
            batchable = []
        prefilled = {}
        if (self.paged_arena is not None and self.draft is None
                and self.prefix_cache is None and not self._ring
                and len(batchable) > 1
                # int32 seed lanes: an exotic >= 2^31 seed keeps the
                # per-request path (identical streams either way — the
                # batch must never silently rekey a request)
                and all(0 <= int(r.seed) < 2 ** 31 for r in batchable)):
            # prefilled rows are PURE functions of (prompt, seed,
            # temp): when a capacity-blocked pass requeues the same
            # requests, reuse the batch instead of re-dispatching it
            # every step for as long as the blockage lasts.  Keyed on
            # request OBJECT identity (the cache holds the refs, so
            # an id cannot be recycled under it); any change in the
            # pass's membership recomputes
            cached = self._batch_cache
            if (cached is not None
                    and len(cached[0]) == len(batchable)
                    and all(a is b
                            for a, b in zip(cached[0], batchable))):
                prefilled, self._admit_batch = cached[1], cached[2]
            else:
                prefilled = self._prefill_admissions(batchable)
                self._batch_cache = (tuple(batchable), prefilled,
                                     self._admit_batch)
        for k, req in enumerate(admit):
            n_br = getattr(req, "n", 1)
            ok = False
            if (blocked_p is None
                    or getattr(req, "priority", 0) > blocked_p) \
                    and len(free) >= n_br:
                # n>1 admits only when the WHOLE family fits this
                # pass (one slot per branch): a partially-forked
                # family would leave branch count dependent on
                # scheduling noise
                ph = self._handles[req.request_id]
                with _trace.phase("serve.admit", cat="serve"):
                    ok = self._admit(free.pop(0), req, now,
                                     prefilled=prefilled.get(
                                         req.request_id))
                if ok and n_br > 1:
                    self._fork_group_admit(req, ph, free, now)
            if not ok:
                # capacity block: the head request's blocks do not fit
                # even after eviction + priority preemption (or a
                # swapped request outranks it).  Push it AND
                # everything scheduled behind it back to the queue
                # front in original order — admission order blocks,
                # it never skips
                for r in reversed(admit[k:]):
                    self.scheduler.requeue_front(r)
                break
        else:
            # every scheduled request admitted: the cached pass batch
            # can never recur, so release its device rows — without
            # this, one large burst's stacked prefill KV would stay
            # pinned for the engine's lifetime
            self._batch_cache = None
        if self._admit_batch is not None:
            self._flush_admission_writes(drop_batch=True)

    def _free_slots(self):
        """Slot indices genuinely available for admission or resume:
        unoccupied AND not reserved by an in-flight chunked prefill."""
        return [i for i, s in enumerate(self._slots)
                if s is None and i not in self._prefilling]

    # -- CoW KV forking (serve/fork.py) ----------------------------------
    def _fork_group_admit(self, req, handle, free, now):
        """Spawn branches 1..n-1 of an ``n > 1`` admission off the
        freshly admitted parent slot, inside the same scheduling pass
        (the admit loop reserved one free slot per branch up front).
        If tok0 already resolved the parent — its stop token landed on
        the first sample, or its on_token callback rejected it — every
        sibling would have produced the same single token, so they
        seal immediately with the parent's outcome instead of
        forking."""
        rid = req.request_id
        pidx = next((i for i, s in enumerate(self._slots)
                     if s is not None and s.handle is handle), None)
        if pidx is None:
            for k in range(1, req.n):
                ch = RequestHandle(req)
                if handle._result is not None:
                    ch._finish(replace(handle._result,
                                       request_id=f"{rid}#{k}",
                                       branch=k))
                else:
                    ch._reject(handle._error)
                handle._fork_children.append(ch)
            return
        parent = self._slots[pidx]
        if parent.group is None:
            parent.group = next(self._fork_seq)
        for k in range(1, req.n):
            self._spawn_branch(pidx, free.pop(0), k, now)

    def _spawn_branch(self, parent_idx, child_idx, branch, now,
                      seed=None, max_new=None):
        """Clone the live slot at ``parent_idx`` into ``child_idx`` as
        fork branch ``branch``: the child's block table is a COPY of
        the parent's with every non-cache-owned block's arena refcount
        bumped (zero KV bytes move), prefix-cache refs re-acquired,
        and host decode state (token, position, temperature, emitted
        list, grammar state) duplicated.  Both slots turn ``cow`` on:
        the next write into a still-shared block copies it first
        (:meth:`_cow_copy`).  The child re-keys via
        ``fold_in(parent_key, branch)`` (or a fresh chain from
        ``seed``) so siblings sample independently from the shared
        distribution."""
        arena = self.paged_arena
        cache = self.prefix_cache
        # deferred same-pass admission writes must land before the
        # parent's key/pool state is read below
        if self._pending_scatter or self._pending_keys:
            self._flush_admission_writes()
        parent = self._slots[parent_idx]
        preq = parent.handle.request
        rid = preq.request_id
        child_rid = f"{rid}#{branch}"
        child_req = replace(
            preq, request_id=child_rid, n=1,
            max_new_tokens=(preq.max_new_tokens if max_new is None
                            else int(max_new)))
        child_handle = RequestHandle(child_req)
        child_handle._submit_time = now
        child = _Slot(child_handle,
                      parent.remaining if max_new is None
                      else int(max_new),
                      now, self.step_count)
        child.emitted = list(parent.emitted)
        # the branch point IS its first token: a branch pruned before
        # its first own decode still seals with real latency numbers
        child.first_token_time = now
        shared = [b for b in parent.blocks[parent.n_shared:]
                  if b != arena.trash]
        arena.share(shared)
        child.blocks = list(parent.blocks)
        if cache is not None and parent.prefix_nodes:
            cache.acquire(parent.prefix_nodes)
            child.prefix_nodes = list(parent.prefix_nodes)
        child.n_shared = parent.n_shared
        child.group = parent.group
        child.branch = branch
        child.score = parent.score
        child.automaton = parent.automaton
        child.astate = parent.astate
        parent.cow = child.cow = True
        if seed is None:
            ck = jax.random.fold_in(self._keys[parent_idx],
                                    int(branch))
        else:
            ck = jax.random.split(
                jax.random.PRNGKey(int(seed)), 1)[0]
        self._keys = self._keys.at[child_idx].set(ck)
        self._toks[child_idx] = self._toks[parent_idx]
        self._pos[child_idx] = self._pos[parent_idx]
        self._temps[child_idx] = self._temps[parent_idx]
        self._slots[child_idx] = child
        self._handles[child_rid] = child_handle
        kids = getattr(parent.handle, "_fork_children", None)
        if kids is None:
            kids = parent.handle._fork_children = []
        kids.append(child_handle)
        # a branch is a submission that skipped the queue and the
        # prefill (its KV is the parent's, by reference): submitted
        # counts balance completions, but no admission latency sample
        # is recorded — zero queue/prefill would drag the TTFT
        # distribution with samples no client experienced
        self.stats.on_submit()
        if _reqs._active:
            lbl = self.stats.engine_label
            _reqs._ledger.on_submit(
                child_rid, engine=lbl, t=now,
                prompt_len=len(preq.prompt_ids),
                max_new_tokens=child_req.max_new_tokens)
            _reqs._ledger.on_admit(child_rid, engine=lbl, t=now,
                                   slot=child_idx,
                                   step=self.step_count,
                                   branch=branch)
            _reqs._ledger.on_first_token(child_rid, engine=lbl, t=now)
        _trace.event("serve/fork", cat="serve", request=child_rid,
                     parent=rid, slot=child_idx, branch=branch,
                     shared_blocks=len(shared),
                     pos=int(self._pos[parent_idx]))
        self._c_fork_branches.inc()
        self._fork_gauge()
        return child_handle

    def fork(self, request_id, *, seed=None, max_new_tokens=None):
        """Split the LIVE request ``request_id`` into two branches
        sharing every block decoded so far copy-on-write (tree-shaped
        search: fork the promising branch, ``prune`` the losers).
        Returns a :class:`~singa_tpu.serve.fork.BranchHandle` for the
        new branch; the original keeps streaming unchanged.  ``seed``
        re-keys the new branch from a fresh chain (default:
        ``fold_in`` of the parent's current key by the branch index);
        ``max_new_tokens`` caps the new branch's REMAINING budget
        (default: inherit the parent's)."""
        _require(self._fam, fork=True)
        if self._closed:
            raise RuntimeError(
                "engine is closed; build a new one with model.serve()")
        if self._failed:
            raise EngineFailedError(
                "engine has failed; rebuild it (EngineSupervisor does "
                "this automatically)", engine_step=self.step_count)
        if (self.paged_arena is None or self.draft is not None
                or self._shard is not None or self._window is not None
                or self._ring):
            raise ValueError(
                "fork() needs a plain paged engine (no draft, no "
                "tensor-parallel backend, no sliding window, no ring "
                "prefill) — same support matrix as "
                "GenerationRequest(n>1)")
        if max_new_tokens is not None and int(max_new_tokens) < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        pidx = next(
            (i for i, s in enumerate(self._slots)
             if s is not None
             and s.handle.request.request_id == request_id), None)
        if pidx is None:
            if self._handles.get(request_id) is None:
                raise ValueError(
                    f"{request_id}: unknown or already finished — "
                    f"fork() splits a LIVE branch")
            if any(sw.request.request_id == request_id
                   for sw in self._swapped):
                state = "swapped out (preempted)"
            elif any(pf.request.request_id == request_id
                     for pf in self._prefilling.values()):
                state = "mid chunked prefill"
            else:
                state = "still queued"
            raise ValueError(
                f"{request_id} is {state}: fork() needs a live "
                f"decoding slot (step the engine until it is "
                f"decoding, then fork)")
        parent = self._slots[pidx]
        if parent.handle.request.pin_session:
            raise ValueError(
                f"{request_id} pins a session: a session continues "
                f"ONE stream — fork before pinning, or continue the "
                f"session and fork the continuation")
        free = self._free_slots()
        if not free:
            raise RuntimeError(
                f"no free slot to fork {request_id} into "
                f"(max_slots={self.max_slots}, all occupied) — retire "
                f"or prune a branch first")
        if parent.group is None:
            parent.group = next(self._fork_seq)
        kids = getattr(parent.handle, "_fork_children", None)
        branch = len(kids) + 1 if kids else 1
        now = self._clock()
        ch = self._spawn_branch(pidx, free[0], branch, now,
                                seed=seed, max_new=max_new_tokens)
        return BranchHandle(self, ch, branch)

    def prune(self, request_id):
        """Cut a fork branch (or any live/swapped request): free its
        private blocks, drop its references on shared ones, and seal a
        complete ``finish_reason="pruned"`` result carrying everything
        emitted so far — the handle resolves, never wedges.  Typed
        ValueError for a request that is not live or swapped (queued
        requests cancel by deadline; finished ones are already
        sealed)."""
        if self._closed:
            raise RuntimeError(
                "engine is closed; build a new one with model.serve()")
        now = self._clock()
        for i, s in enumerate(self._slots):
            if s is not None \
                    and s.handle.request.request_id == request_id:
                if self._c_fork_pruned is not None:
                    self._c_fork_pruned.inc()
                _trace.event("serve/prune", cat="serve",
                             request=request_id, slot=i,
                             tokens=len(s.emitted))
                self._retire(i, s, now, finish_reason="pruned")
                return
        for j, sw in enumerate(self._swapped):
            if sw.request.request_id != request_id:
                continue
            # a swapped branch holds no pool blocks (freed at
            # preempt) — sealing it is pure host bookkeeping
            n = len(sw.emitted)
            submit_t = getattr(sw.handle, "_submit_time",
                               sw.admit_time)
            result = GenerationResult(
                request_id=request_id,
                tokens=np.concatenate(
                    [sw.request.prompt_ids,
                     np.asarray(sw.emitted, np.int32)]),
                finish_reason="pruned",
                ttft=sw.first_token_time - submit_t,
                tpot=((now - sw.first_token_time) / (n - 1)
                      if n > 1 else None),
                queue_time=sw.admit_time - submit_t,
                admitted_step=sw.admitted_step,
                finished_step=self.step_count,
                branch=getattr(sw, "branch", 0),
                score=(sw.score
                       if getattr(sw, "group", None) is not None
                       else None))
            if _reqs._active:
                _reqs._ledger.on_retire(
                    request_id, engine=self.stats.engine_label,
                    t=now, finish_reason="pruned", tokens=n)
            if self._c_fork_pruned is not None:
                self._c_fork_pruned.inc()
            _trace.event("serve/prune", cat="serve",
                         request=request_id, slot=None, tokens=n)
            sw.handle._finish(result)
            self.stats.on_complete(result)
            del self._swapped[j]
            self._handles.pop(request_id, None)
            return
        raise ValueError(
            f"{request_id}: not a live or swapped request — prune() "
            f"cuts a decoding branch (queued requests expire by "
            f"deadline; finished ones are already sealed)")

    def _sched_admissions(self, navail, now):
        """One scheduler consultation, shared by the whole-prompt and
        budgeted passes so the two cannot drift: pass the warm-prefix
        cost pricer when the scheduler takes one, and reject
        deadline-expired requests.  Returns the admit list."""
        if self._sched_cost is not None:
            admit, expired = self.scheduler.schedule(
                navail, now, cost=self._sched_cost)
        else:
            admit, expired = self.scheduler.schedule(navail, now)
        self._reject_expired(expired, now)
        return admit

    def _blocked_priority(self):
        """The capacity-block fairness bound: a swapped request still
        waiting after the resume pass outranks fresh arrivals at or
        below its priority (it already streamed tokens — letting new
        work overtake would grow its latency without bound)."""
        return (max(sw.priority for sw in self._swapped)
                if self._swapped else None)

    def _reject_expired(self, expired, now):
        for req in expired:
            self.stats.on_deadline_expired(req.request_id)
            _trace.event("serve/request_rejected", cat="serve",
                         request=req.request_id, reason="deadline")
            if _reqs._active:
                _reqs._ledger.on_reject(req.request_id, t=now,
                                        reason="deadline",
                                        engine=self.stats.engine_label,
                                        started=False)
            self._handles.pop(req.request_id)._reject(
                DeadlineExceededError(
                    f"{req.request_id}: deadline {req.deadline} passed "
                    f"at {now} before a slot was available"))

    # -- chunked-prefill token budget (the long-context round) -----------
    def _schedule_budgeted(self, now, left):
        """One scheduling pass under ``prefill_token_budget``: spend
        at most that many prefill TOKENS a step — first on in-flight
        chunked prefills (admission order: the FIFO contract holds
        across steps, an expensive head request BLOCKS the budget, it
        is never skipped), then on new admissions.  A new admission
        whose prompt exceeds the remaining budget simply carries over:
        its chunks continue next step, which is the whole point —
        decode lanes never wait for more than one step's budget of
        prefill work.

        ``left`` is what the step has not spent yet.  In a step with a
        live lane the in-flight prefills' launches went out BEFORE this
        pass, behind the decode program and ahead of the host's wait
        for its tokens (``_decode_once``), so the first loop finds
        nothing to launch; with no lane live it launches here.  Either
        way a prefill whose last block has been launched is promoted
        here, after the decode step's emit: a slot promoted earlier
        would be handed a token of a decode step it was not in.

        The pass dispatches before it waits: a landed prefill's
        completion programs go out (``_finish_landed``), then the
        admissions and their first launches, and only then does the
        host fetch the first tokens (``_promote_landed``) — the chip is
        still on the landed prompt's last launch meanwhile, so the
        admission's host work and the leftover budget's launch cost
        the step no idle time.

        Each of the two passes (the early one over the requests in
        flight, this one's admissions) first COLLECTS its pieces — (a
        request, the blocks of it the budget left allows), FIFO, the
        budget spent once — and dispatches when it has them all
        (``_launch_chunks``): a piece is one launch, and two pieces
        that stand next to each other and fit the pair program's slots
        are ONE.  A pass never waits for the other: what is in flight
        goes out behind the decode program, whatever may be admitted
        after it."""
        left = self._launch_inflight(left)
        self._finish_landed()
        self._admit_budgeted(now, left)
        self._promote_landed()

    def _admit_budgeted(self, now, left):
        """The admissions of one budgeted scheduling pass: start new
        chunked prefills in the free slots, FIFO, while ``left`` budget
        tokens allow a first launch; their first pieces go out together
        when the last of them is admitted."""
        B = self.paged_arena.block_size
        free = self._free_slots()
        if not free and self.scheduler.queue_depth == 0:
            return
        admit = self._sched_admissions(len(free), now)
        blocked_p = self._blocked_priority()
        pieces = []
        for k, req in enumerate(admit):
            ok = False
            admissible = (left >= B
                          and (blocked_p is None
                               or getattr(req, "priority", 0)
                               > blocked_p))
            if admissible and self._ring_eligible(
                    len(req.prompt_ids)):
                # ring prefill is ONE mesh-sharded dispatch for the
                # whole prompt — admit whole and charge the budget,
                # so no further prefill stacks onto this step
                with _trace.phase("serve.admit", cat="serve"):
                    ok = self._admit(free[0], req, now)
                if ok:
                    free.pop(0)
                    left = max(0, left - len(req.prompt_ids))
            elif admissible:
                with _trace.phase("serve.admit", cat="serve"):
                    idx = self._start_prefilling(free[0], req, now)
                if idx is not None:
                    free.pop(0)
                    ok = True
                    left = self._take_piece(self._prefilling[idx], left,
                                            pieces)
            if not ok:
                # budget exhausted or capacity-blocked: everything
                # scheduled from here returns to the queue FRONT in
                # original order — admission order blocks, it never
                # skips
                for r in reversed(admit[k:]):
                    self.scheduler.requeue_front(r)
                break
        if pieces:
            self._launch_chunks(pieces)
            self._finish_landed()

    def _start_prefilling(self, idx, req, now):
        """Begin a chunked-prefill admission at slot ``idx``: acquire
        any cached prefix, allocate the request's prompt blocks (all
        of them up front — a mid-prefill capacity dance would
        deadlock against other prefills), and park the request in
        ``self._prefilling`` with a fresh full-width cache row.
        Returns the slot index, or None when the blocks do not fit
        (caller requeues at the queue front)."""
        arena = self.paged_arena
        B = arena.block_size
        plen = len(req.prompt_ids)
        cache = self.prefix_cache
        nodes = []
        if cache is not None:
            with _trace.phase("serve.prefix_lookup", cat="serve"):
                nodes = cache.lookup(req.prompt_ids)[:(plen - 1) // B]
            if nodes:
                cache.acquire(nodes)
        j_lo0 = 0
        if self._window is not None:
            # a windowed admission only ever stores the lanes a
            # future query can attend: blocks below the first
            # in-window lane are never allocated at all
            j_lo0 = max(0, (plen - self._window + 1) // B)
        n_new = plen // B + 1 - j_lo0 - len(nodes)
        new_blocks = self._alloc_blocks(n_new,
                                        getattr(req, "priority", 0))
        if new_blocks is None:
            if cache is not None and nodes:
                cache.release(nodes)
            return None
        if _reqs._active:
            _reqs._ledger.on_admit(req.request_id,
                                   engine=self.stats.engine_label,
                                   t=now, slot=idx,
                                   step=self.step_count)
        if cache is not None:
            cache.on_admit(len(nodes), plen,
                           request_id=req.request_id)
        try:
            if nodes:
                kc_row, vc_row = cache.copy_into_row(nodes)
            else:
                # a fresh zero row of the full width — the same
                # chunk-from-scratch canonical form the int8+cache
                # cold path runs (chunked == full prefill bitwise on
                # dense rows, pinned by tests/test_prefix.py)
                kc_row, vc_row = arena.gather_row([], n_used=0)
        except Exception:
            # the copies above check fault sites (serve.prefix_copy /
            # serve.paged_copy): a raise here is BEFORE the blocks are
            # registered in self._prefilling, so _fail's sweep would
            # never see them — return them ourselves or they leak
            arena.free(new_blocks)
            if cache is not None and nodes:
                cache.release(nodes)
            raise
        ids = np.zeros((1, self.max_len), np.int32)
        ids[0, :plen] = req.prompt_ids
        pf = _Prefilling()
        pf.handle = self._handles[req.request_id]
        pf.request = req
        pf.ids_j = jnp.asarray(ids)
        pf.kc_row, pf.vc_row = kc_row, vc_row
        # per-slot state starts from zero at admission, whoever held
        # the slot before; each chunk row carries it on
        pf.state = None
        if self._state_spec:
            pf.state = self._zero_state()
            self._c_state_resets.inc()
        pf.hidden = None
        pf.first = None
        pf.off = len(nodes) * B
        pf.last_off = ((plen - 1) // B) * B
        if self._window is not None:
            pf.blocks = [arena.trash] * j_lo0 + new_blocks
        else:
            pf.blocks = [n.block for n in nodes] + new_blocks
        pf.n_shared = len(nodes)
        pf.nodes = nodes
        pf.key0 = jax.random.split(
            jax.random.PRNGKey(int(req.seed)), 1)[0]
        pf.temp = np.float32(req.temperature)
        pf.t_admit = now
        pf.admitted_step = self.step_count
        pf.seq = next(self._prefill_seq)
        self._prefilling[idx] = pf
        _trace.event("serve/prefill_budgeted", cat="serve",
                     request=req.request_id, slot=idx,
                     prompt_len=plen, step=self.step_count,
                     chunks=(pf.last_off - pf.off) // B + 1)
        return idx

    def _compile_launch_widths(self):
        """Compile every chunk-row program the planner can choose now
        -- each launch width, and the pair program where the engine has
        one -- from abstract arguments shaped like a prefilling
        request's: a warm-up of short prompts reaches the one-block
        program only, and a shape first met under traffic would compile
        there."""
        def placed(make):
            # what ``make`` allocates at the engine's placement, as
            # shapes
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=self._state_sh),
                jax.eval_shape(make))

        kc_row, vc_row = placed(
            lambda: self.paged_arena.gather_row([], n_used=0))
        n_valid = jax.ShapeDtypeStruct((), jnp.int32)
        off_of = lambda w: jax.eval_shape(lambda: self._launch_off(0, w))
        kw = {}
        if self._state_spec:
            kw["state"] = placed(self._zero_state)
        if self._state_spec or self._fam.pad_aware:
            kw["n_valid"] = n_valid
        ids = jax.ShapeDtypeStruct((1, self.max_len), jnp.int32)
        for w in self._launch_widths:
            self._x.chunk_row(self._params, ids, kc_row, vc_row,
                              off_of(w), run=False, **kw)
        if self._pair_blocks:
            two = lambda a: (a, a)
            off = off_of(self._pair_blocks * self.paged_arena.block_size)
            self._x.chunk_row(
                self._params, two(ids), two(kc_row), two(vc_row),
                two(off), run=False,
                **{k: two(v) for k, v in dict(kw, n_valid=n_valid).items()})

    def _zero_state(self):
        """A prefilling request's per-slot state before its first
        token: {kind: zeros (L, *shape)}."""
        return {k: jnp.zeros((self._state[k].shape[0],) + tuple(shape),
                             dt, device=self._state_sh)
                for k, (shape, dt) in self._state_spec.items()}

    def _launch_off(self, off, w):
        """The ``off`` argument of a launch of ``w`` tokens at ``off``
        (:func:`_launch_of`): the position itself for one block, the
        offsets of its blocks for more."""
        B = self.paged_arena.block_size
        if w == B:
            return jnp.int32(off)
        return jnp.asarray(np.arange(off, off + w, B, dtype=np.int32))

    def _launch_inflight(self, left):
        """Spend up to ``left`` budget tokens on the chunked prefills
        in flight, oldest admission first; a head request that takes
        the whole budget blocks the ones behind it.  Dispatch only —
        nothing here reads a device value or promotes a request, so it
        may run while the decode program is still in flight.  Returns
        the remaining budget."""
        pieces = []
        for idx in sorted(self._prefilling,
                          key=lambda i: self._prefilling[i].seq):
            if left < self.paged_arena.block_size:
                break
            left = self._take_piece(self._prefilling[idx], left, pieces)
        self._launch_chunks(pieces)
        return left

    def _take_piece(self, pf, left, pieces):
        """Add to ``pieces`` what ``left`` budget tokens allow of one
        chunked prefill this pass: ``(pf, blocks)`` — never past the
        prompt's last block, nor, then, past the row's end (the row is
        whole blocks), where the program's slices would clamp silently.
        Returns the remaining budget."""
        B = self.paged_arena.block_size
        n = min(left, pf.last_off - pf.off + B) // B
        if n > 0:
            pieces.append((pf, n))
        return left - n * B

    def _launch_chunks(self, pieces):
        """Dispatch one pass's ``pieces`` (:meth:`_take_piece`), in
        their order, in the fewest launches the engine's programs
        allow.  A launch reads the layers' weights once, whatever it
        covers.  Two neighbours that each fit a slot of the pair
        program (``_pair_blocks`` blocks, none of them past its row's
        end) are ONE launch of two segments; every other piece is one
        launch of its own where the engine holds a program of its
        width, and the widest launches that add up to it otherwise (the
        sharded executors' ladder).  A one-block launch is the exact
        executable warm admission rides, and a wider or shared one
        computes the same rows (every position attends what lies below
        it in its own row), so a budgeted stream is the unbudgeted
        one.  A prompt's last block is launched when ``pf.off >
        pf.last_off``; :meth:`_finish_prefilling` then completes the
        admission."""
        B = self.paged_arena.block_size
        slot = self._pair_blocks
        fits = lambda pf, n: (n <= slot
                              and pf.off + slot * B <= self.max_len)
        i = 0
        while i < len(pieces):
            if _faults._armed:
                # chaos hook: a fault BETWEEN launches models a raising
                # mid-prefill dispatch — step() fails the engine
                # typed, the rejection is started=False (nothing
                # streamed), and _fail returns the partial blocks to
                # the free list (RESILIENCE.md; chaos_longctx)
                _faults.check("serve.prefill_chunk")
            if i + 1 < len(pieces) and fits(*pieces[i]) \
                    and fits(*pieces[i + 1]):
                self._launch(pieces[i:i + 2], slot * B)
                i += 2
                continue
            pf, n = pieces[i]
            w = next(w for w in self._launch_widths if w <= n * B)
            self._launch([(pf, w // B)], w)
            if w == n * B:
                i += 1
            else:
                pieces[i] = (pf, n - w // B)

    def _launch(self, segs, w):
        """ONE chunk-row launch of ``segs`` — ``(pf, real blocks)``, one
        request's or two requests' — each in a window of ``w`` tokens
        from its ``pf.off``, and the bookkeeping of what it covered."""
        B = self.paged_arena.block_size
        # the prompt positions each segment really covers: cut at the
        # blocks the budget gave it and at the prompt's end, not padded
        n_valid = [min(n * B, len(pf.request.prompt_ids) - pf.off)
                   for pf, n in segs]
        pfs = [pf for pf, _ in segs]
        # one segment's arguments ride as themselves, several segments'
        # as tuples with an entry a segment
        pack = tuple if len(pfs) > 1 else (lambda per_seg: per_seg[0])
        kw = {}
        if pfs[0].state is not None:
            kw["state"] = pack([pf.state for pf in pfs])
        if len(pfs) > 1 or pfs[0].state is not None \
                or self._fam.pad_aware:
            # (a slot, a family with state and a pad-aware one are told
            # where the real tokens end)
            kw["n_valid"] = pack([jnp.int32(n) for n in n_valid])
        out = self._x.chunk_row(
            self._params, pack([pf.ids_j for pf in pfs]),
            pack([pf.kc_row for pf in pfs]),
            pack([pf.vc_row for pf in pfs]),
            pack([self._launch_off(pf.off, w) for pf in pfs]), **kw)
        out = list(zip(*out)) if len(pfs) > 1 else [out]
        # the rows were donated: rebind every segment's before anything
        # else can raise
        for (pf, _), o in zip(segs, out):
            pf.hidden, pf.kc_row, pf.vc_row = o[:3]
            if len(o) > 3:
                pf.state = o[3]
        self._c_launches.inc()
        self._launches_run += 1
        if len(segs) > 1:
            self._c_merged_launches.inc()
        for (pf, n), n_tok in zip(segs, n_valid):
            # blocks, not launches: what the benchmark's token count
            # multiplies by the block
            self._c_budget_chunks.inc(n)
            self._chunks_run += n
            self._segments_run += 1
            self.stats.on_prefill_tokens(n_tok)
            if _reqs._active:
                _reqs._ledger.on_prefill_chunk(
                    pf.request.request_id,
                    engine=self.stats.engine_label, t=self._clock(),
                    offset=pf.off)
            pf.off += n * B

    def _finish_landed(self):
        """Dispatch the completion of every chunked prefill whose last
        block has been launched, oldest first (``_promote_landed``
        makes them live)."""
        for idx, pf in sorted(self._prefilling.items(),
                              key=lambda kv: kv[1].seq):
            if pf.off > pf.last_off and pf.first is None:
                self._finish_prefilling(idx, pf)

    def _promote_landed(self):
        """Promote every chunked prefill that ``_finish_landed``
        completed, oldest first."""
        for idx, pf in sorted(self._prefilling.items(),
                              key=lambda kv: kv[1].seq):
            if pf.first is not None:
                self._promote(idx, pf)

    def _finish_prefilling(self, idx, pf):
        """The last chunk landed: sample the admission token from the
        final chunk's hidden block (mirrors ``_prefill_one``'s tail
        via ``_first_from_hidden`` — bitwise the unbudgeted token) and
        scatter the row's lanes into the request's pool blocks.
        Dispatch only: the token stays on the device in ``pf.first``
        until :meth:`_promote` fetches it, so the pass's admissions —
        host work and a first launch — go out while the chip is still
        on this prompt's last launch, not after the host has waited
        for it."""
        arena = self.paged_arena
        req = pf.request
        plen = len(req.prompt_ids)
        ast0 = mask0 = None
        if req.structured is not None:
            # budgeted admission of a structured request: the first
            # token samples here, so the initial mask applies here
            ast0 = req.structured.initial()
            mask0 = jnp.asarray(
                np.asarray(req.structured.mask(ast0), bool))
        tok0, carry_key = _first_from_hidden(
            self._params, pf.hidden,
            jnp.int32(plen - 1 - pf.last_off), pf.key0, pf.temp,
            self._top_p, top_k=self._statics["top_k"],
            use_top_p=self._statics["use_top_p"], mask=mask0,
            fam=self._fam)
        if pf.state is not None:
            self._state = _write_state(self._state, pf.state,
                                       jnp.int32(idx))
        lanes = {j: pf.blocks[j]
                 for j in range(pf.n_shared, plen // arena.block_size
                                + 1)
                 if pf.blocks[j] != arena.trash}
        arena.scatter_row(pf.kc_row, pf.vc_row, lanes)
        if self.draft is not None:
            # the draft prefills whole at completion — it is cheap by
            # construction (the whole point of a draft), so it never
            # needed the budget's protection
            dkc_row, dvc_row = _prefill_rows(
                self._d_params, pf.ids_j, *self._d_statics,
                quant=self._quant)
            self._dkc, self._dvc = _write_slot(
                self._dkc, self._dvc, dkc_row, dvc_row,
                jnp.int32(idx))
        pf.first = (tok0, carry_key, ast0)

    def _promote(self, idx, pf):
        """Promote a completed chunked prefill's reservation to a LIVE
        slot: fetch its first token (the device sync), stamp TTFT,
        emit."""
        req = pf.request
        plen = len(req.prompt_ids)
        tok0, carry_key, ast0 = pf.first
        self.stats.on_prefill()
        slot = _Slot(pf.handle, req.max_new_tokens, pf.t_admit,
                     pf.admitted_step)
        slot.prefix_nodes = pf.nodes
        slot.blocks = pf.blocks
        slot.n_shared = pf.n_shared
        slot.automaton = req.structured
        slot.astate = ast0
        del self._prefilling[idx]
        self._slots[idx] = slot
        tok0 = int(np.asarray(tok0))   # device sync: prefill done
        t_first = self._clock()
        submit_t = getattr(pf.handle, "_submit_time", pf.t_admit)
        self.stats.on_admission(pf.t_admit - submit_t,
                                t_first - pf.t_admit,
                                warm=bool(pf.nodes))
        if _reqs._active:
            _reqs._ledger.on_first_token(
                req.request_id, engine=self.stats.engine_label,
                t=t_first)
        self._toks[idx] = tok0
        self._pos[idx] = plen
        self._temps[idx] = pf.temp
        self._keys = self._keys.at[idx].set(carry_key)
        self._emit(idx, slot, tok0, t_first)

    # -- ring-attention prefill (the long-context round, part 3) ---------
    def _ring_width(self, plen):
        """The padded prompt width a ring prefill runs at: the
        smallest width that is both a block multiple (the scatter's
        lane granularity) and divisible by the mesh width (equal
        per-shard sequence chunks), or None when that exceeds
        ``max_len`` (the caller falls back to the serial prefill)."""
        B = self.paged_arena.block_size
        tpw = self.tp_exec.tp
        # the admission scatters plen//B + 1 lanes (the last one is
        # the block the first decode write lands in — same as the
        # serial narrow path), so the row must be at least that wide
        wn0 = (plen // B + 1) * B
        step = B * tpw // math.gcd(B, tpw)
        wn = -(-wn0 // step) * step
        return wn if wn <= self.max_len else None

    def _ring_eligible(self, plen):
        """Ring prefill fires for cold admissions at or above
        ``TPConfig.ring_min_tokens`` when a legal padded width
        exists."""
        if not self._ring:
            return False
        mt = getattr(self._tp_cfg, "ring_min_tokens", 0) or 0
        return plen >= mt and self._ring_width(plen) is not None

    def _prefill_cost(self, req):
        """Scheduler interleave price of admitting ``req`` now: 0 for
        a warm prefix hit that recomputes at most one block-width
        chunk, 1 for anything colder (the O(ctx²) work the interleave
        cap exists to bound)."""
        cache = self.prefix_cache
        plen = len(req.prompt_ids)
        with _trace.phase("serve.prefix_lookup", cat="serve"):
            usable = min(len(cache.lookup(req.prompt_ids)),
                         (plen - 1) // cache.block_size)
        if usable > 0 and plen - usable * cache.block_size \
                <= cache.block_size:
            return 0
        return 1

    def _prefill_admissions(self, reqs):
        """One batched prefill dispatch for a scheduling pass's cold
        paged admissions (:func:`_prefill_batch`): all R requests ride
        one (R, W) executable at the pass's shared narrow width (the
        largest per-request block-multiple width — rows are bitwise
        invariant to extra pad width, so sharing the widest is free)
        and ONE host sync fetches every first token.  Returns
        ``{request_id: (tok0, batch row index)}``; the stacked rows
        and carried keys stay on the device in ``self._admit_batch``
        for the deferred per-request writes to flush against."""
        B = self.paged_arena.block_size
        wn = min(self.max_len,
                 max((len(r.prompt_ids) // B + 1) * B for r in reqs))
        R = len(reqs)
        ids = np.zeros((R, wn), np.int32)
        plens = np.zeros(R, np.int32)
        seeds = np.zeros(R, np.int32)
        temps = np.zeros(R, np.float32)
        for r, req in enumerate(reqs):
            plen = len(req.prompt_ids)
            ids[r, :plen] = req.prompt_ids
            plens[r] = plen
            seeds[r] = int(req.seed)
            temps[r] = req.temperature
        tok0, keys, kc, vc = self._x.prefill_batch(
            self._params, jnp.asarray(ids), jnp.asarray(plens),
            jnp.asarray(seeds), jnp.asarray(temps), self._top_p)
        tok0 = np.asarray(tok0)      # ONE sync for the whole pass
        # rows stay STACKED on the device: per-request scatters and
        # key writes are deferred against this batch and flushed as
        # one dispatch each at the end of the pass
        # (_flush_admission_writes) — per-admission device work
        # inside the pass drops to zero
        self._admit_batch = (keys, kc, vc)
        return {req.request_id: (int(tok0[r]), r)
                for r, req in enumerate(reqs)}

    def _flush_admission_writes(self, drop_batch=False):
        """Write one scheduling pass's deferred admission state: ONE
        batched pool scatter (``arena.scatter_rows``) for every
        admitted request's prefilled lanes and ONE key-table write
        for their carried sampling keys.  Called at the end of
        ``_schedule`` (``drop_batch=True`` — the pass is over) and
        defensively before any same-pass path that reads pool or key
        state a deferred write still owns (preemption's swap gather,
        block frees on instant retire/reject — a freed block could be
        re-allocated and the late flush would then clobber the new
        owner)."""
        if self._pending_scatter:
            _, kc_b, vc_b = self._admit_batch
            self.paged_arena.scatter_rows(
                kc_b, vc_b,
                [r for r, _ in self._pending_scatter],
                [l for _, l in self._pending_scatter])
            self._pending_scatter = []
        if self._pending_keys:
            keys_b = self._admit_batch[0]
            idxs = jnp.asarray(np.asarray(
                [i for i, _ in self._pending_keys], np.int32))
            rs = jnp.asarray(np.asarray(
                [r for _, r in self._pending_keys], np.int32))
            self._keys = _merge_keys(self._keys, keys_b, idxs, rs)
            self._pending_keys = []
        if drop_batch:
            self._admit_batch = None

    def _admit(self, idx, req, now, prefilled=None):
        """Prefill one request into slot ``idx`` and emit its first
        token.  Mirrors the offline key chain exactly: generate() makes
        per-row keys with split(PRNGKey(seed), B)[row]; a single-prompt
        call is B=1, row 0.  ``prefilled``: this request's
        ``(tok0, batch row index)`` from a BATCHED pass prefill
        (:meth:`_prefill_admissions`) — the cache rows and carried
        key stay STACKED in ``self._admit_batch`` and the writes
        defer onto that batch (cold paged admissions only, so the
        warm/draft branches below never see it).

        With a prefix cache, the longest cached block-prefix is copied
        into the slot and only the suffix past the divergence boundary
        is prefilled (block-width chunks through ``_chunk_row``).
        Cached K/V is canonical prefill output and the first-token
        sampling mirrors ``_prefill_one``'s tail, so warm token
        streams are byte-identical to the cold path's.  The match is
        capped at ``(plen - 1) // block_size`` blocks: the hidden
        state at prompt_len-1 must be recomputed to sample from — a
        fully-cached prompt still recomputes its last block."""
        handle = self._handles[req.request_id]
        plen = len(req.prompt_ids)
        cache = self.prefix_cache
        nodes = []
        if cache is not None:
            with _trace.phase("serve.prefix_lookup", cat="serve"):
                nodes = cache.lookup(req.prompt_ids)[
                    :(plen - 1) // cache.block_size]
        arena = self.paged_arena
        new_blocks = []
        if arena is not None:
            # admission by BLOCKS FREE: the request needs lanes
            # [len(nodes), plen//B] now (matched prefix blocks are
            # shared by reference — zero copy).  The matched path is
            # ACQUIRED before allocating: _alloc_blocks' eviction only
            # spares referenced nodes, so without the pin the
            # allocation could evict the request's OWN match and hand
            # the same pool block back as one of new_blocks — the
            # block table would alias one block in two lanes and the
            # admission scatter would corrupt the shared prefix KV.
            # Eviction and strictly-lower priority preemption run
            # inside _alloc_blocks; a miss blocks admission (caller
            # requeues at the queue front) rather than dropping the
            # request
            if cache is not None and nodes:
                cache.acquire(nodes)
            j_lo0 = 0
            if self._window is not None:
                # windowed admission: lanes below the first in-window
                # position are never attended by any future query, so
                # their blocks are never allocated — a long prompt on
                # a windowed model admits in O(window) blocks
                j_lo0 = max(0,
                            (plen - self._window + 1)
                            // arena.block_size)
            n0 = plen // arena.block_size + 1
            new_blocks = self._alloc_blocks(
                n0 - j_lo0 - len(nodes), getattr(req, "priority", 0))
            if new_blocks is None:
                if cache is not None and nodes:
                    cache.release(nodes)
                return False
        if _reqs._active:
            # admission started: the queue-wait phase of this hop ends
            # HERE (cold/warm classification is annotated by the
            # prefix cache's own hook below)
            _reqs._ledger.on_admit(req.request_id,
                                   engine=self.stats.engine_label,
                                   t=now, slot=idx,
                                   step=self.step_count)
        ast0 = mask0 = None
        if req.structured is not None:
            # structured decoding: the FIRST token samples inside the
            # prefill executable, so the initial state's vocab mask
            # threads into it (fixed (vocab,) shape — no new
            # signature per grammar)
            ast0 = req.structured.initial()
            mask0 = jnp.asarray(
                np.asarray(req.structured.mask(ast0), bool))
        cached = len(nodes) * cache.block_size if nodes else 0
        with _trace.phase("serve.prefill", cat="serve",
                          request=req.request_id, slot=idx,
                          prompt_len=plen, step=self.step_count,
                          cached_tokens=cached):
            ids_j = None
            if prefilled is None:
                ids = np.zeros((1, self.max_len), np.int32)
                ids[0, :plen] = req.prompt_ids
                ids_j = jnp.asarray(ids)
                key0 = jax.random.split(
                    jax.random.PRNGKey(int(req.seed)), 1)[0]
            temp = np.float32(req.temperature)
            # int8 + prefix cache: EVERY admission (cold included)
            # runs the chunked path, because a quantized engine's
            # full-prefill hidden attends FLOAT keys while a chunked
            # recompute over the quantized cache attends DEQUANTIZED
            # ones — the streams can only be byte-identical if cold
            # and warm admissions share one canonical form, and
            # chunked-quantized is the one donation can store (docs/
            # SERVING.md "int8 and the prefix cache")
            deferred_row = None
            if prefilled is not None:
                # batched-pass fast path (_prefill_admissions): this
                # request's prefill — key chain included — already ran
                # in ONE dispatch for the whole scheduling pass, and
                # its row stays in the stacked device batch: the
                # scatter and key write below DEFER onto it (one
                # flushed dispatch each per pass), so admitting K
                # requests costs the live decode lanes one write, not K
                tok0, deferred_row = prefilled
                carry_key = kc_row = vc_row = None
            elif nodes or (cache is not None and self._quant):
                tok0, carry_key, kc_row, vc_row = self._admit_warm(
                    ids, plen, nodes, key0, temp,
                    rid=req.request_id, mask=mask0)
            elif arena is not None and self._ring_eligible(plen) \
                    and req.structured is None:
                # ring-attention prefill (the long-context round):
                # the prompt's sequence axis shards over the tp mesh
                # and K/V blocks rotate the ICI ring
                # (parallel/ring_attention.py via the executor seam)
                # — ONE dispatch whose attention workspace per shard
                # is O((S/tp)^2) instead of O(S^2), for prompts
                # beyond one shard's flash tile.  Token-identical to
                # the serial prefill (logsumexp merge reorders the
                # float reduction — same caveat as the TP psum),
                # pinned by tests/test_serve_longctx.py.
                wn = self._ring_width(plen)
                tok0, carry_key, kc_row, vc_row = \
                    self.tp_exec.ring_prefill_one(
                        self._params, ids_j[:, :wn], plen, key0,
                        temp, self._top_p)
            else:
                pf_ids = ids_j
                if arena is not None:
                    # narrow-width admission (the gather-tax round):
                    # prefill at the smallest block-multiple width
                    # whose lanes cover the blocks this admission
                    # scatters, not max_len — prefill cost tracks the
                    # PROMPT's length, so a burst of short admissions
                    # stops stalling the decode lanes behind
                    # O(max_len) pad work (the paged bench's TPOT
                    # tax).  Pad lanes cannot reach live rows (every
                    # op is row-independent over positions), so the
                    # first token is the same at any width and the
                    # rows agree to f32 reduction order — XLA may tile
                    # a wider GEMM differently (pinned by tests/
                    # test_paged.py::test_prefill_width_invariance).
                    # One executable per distinct width, bounded by
                    # max_len // block_size — the warmup pass covers
                    # the workload's widths, keeping the recompile
                    # pin intact
                    wn = min(self.max_len,
                             (plen // arena.block_size + 1)
                             * arena.block_size)
                    pf_ids = ids_j[:, :wn]
                tok0, carry_key, kc_row, vc_row = self._x.prefill_one(
                    self._params, pf_ids, plen, key0, temp,
                    self._top_p,
                    **({"mask": mask0} if mask0 is not None else {}))
            if arena is not None:
                # the prefilled lanes past the shared prefix scatter
                # into the request's freshly-allocated pool blocks;
                # matched lanes never move (shared by reference).
                # Windowed admissions start at the first in-window
                # lane instead (below it nothing was allocated)
                m = len(nodes) + j_lo0
                lanes = {m + j: b for j, b in enumerate(new_blocks)}
                if deferred_row is not None:
                    self._pending_scatter.append((deferred_row, lanes))
                else:
                    arena.scatter_row(kc_row, vc_row, lanes)
            else:
                self._kc, self._vc = self._x.write_slot(
                    self._kc, self._vc, kc_row, vc_row,
                    jnp.int32(idx))
            if self.draft is not None:
                # the draft sees the SAME prompt cold (its prefill is
                # cheap by construction; the prefix cache stores only
                # target K/V) — rows land in the draft arena at the
                # same slot so the spec step advances both in lockstep
                dkc_row, dvc_row = _prefill_rows(
                    self._d_params, ids_j, *self._d_statics,
                    quant=self._quant)
                self._dkc, self._dvc = _write_slot(
                    self._dkc, self._dvc, dkc_row, dvc_row,
                    jnp.int32(idx))
        if cache is not None:
            if arena is None:
                # paged admissions acquired the path BEFORE the block
                # allocation above; acquiring again would double-pin
                cache.acquire(nodes)
            cache.on_admit(len(nodes), plen,
                           request_id=req.request_id)
        self.stats.on_prefill()
        self.stats.on_prefill_tokens(plen - cached)
        slot = _Slot(handle, req.max_new_tokens, now, self.step_count)
        slot.prefix_nodes = nodes
        slot.automaton = req.structured
        slot.astate = ast0
        if arena is not None:
            slot.blocks = ([n.block for n in nodes]
                           + [arena.trash] * j_lo0 + new_blocks)
            slot.n_shared = len(nodes)
        self._slots[idx] = slot
        tok0 = int(np.asarray(tok0))  # device sync: prefill is done
        t_first = self._clock()
        self.stats.on_admission(
            now - getattr(handle, "_submit_time", now),
            t_first - now, warm=bool(nodes))
        if _reqs._active:
            _reqs._ledger.on_first_token(req.request_id,
                                         engine=self.stats.engine_label,
                                         t=t_first)
        self._toks[idx] = tok0
        self._pos[idx] = plen
        self._temps[idx] = temp
        if deferred_row is not None:
            self._pending_keys.append((idx, deferred_row))
        else:
            self._keys = self._keys.at[idx].set(carry_key)
        self._emit(idx, slot, tok0, t_first)
        return True

    def _admit_warm(self, ids, plen, nodes, key0, temp, rid=None,
                    mask=None):
        """Warm admission: one gather copies the matched blocks into a
        fresh cache row, then block-width ``_chunk_row`` calls prefill
        [divergence, last-block-end) — fixed shapes throughout, so the
        jit cache stays warm whatever the hit length."""
        cache = self.prefix_cache
        B = cache.block_size
        kc_row, vc_row = cache.copy_into_row(nodes)
        ids_j = jnp.asarray(ids)
        last_off = ((plen - 1) // B) * B
        off = len(nodes) * B
        hidden = None
        while off <= last_off:
            hidden, kc_row, vc_row = self._x.chunk_row(
                self._params, ids_j, kc_row, vc_row, jnp.int32(off))
            self._chunks_run += 1
            self._launches_run += 1
            if _reqs._active and rid is not None:
                _reqs._ledger.on_prefill_chunk(
                    rid, engine=self.stats.engine_label,
                    t=self._clock(), offset=off)
            off += B
        tok0, carry_key = _first_from_hidden(
            self._params, hidden, jnp.int32(plen - 1 - last_off),
            key0, temp, self._top_p, top_k=self._statics["top_k"],
            use_top_p=self._statics["use_top_p"], mask=mask,
            fam=self._fam)
        return tok0, carry_key, kc_row, vc_row

    # -- disaggregated prefill / KV shipping (the disagg round) ----------
    # The fleet drives these from OUTSIDE the step loop: a prefill
    # specialist builds the shippable canonical-KV prefix of a prompt
    # (chunked — the PR-12 budget machinery's executable, so the
    # shipped bytes ARE the canonical form warm admission consumes,
    # dense and int8 alike), exports it as a versioned host image
    # (serve/kvimage.py — the swap format), and a decode replica
    # adopts the image's blocks into its OWN radix tree so the
    # subsequent engine.submit lands as a local warm hit.  Parity is
    # inherited, not re-proven: warm == cold is already pinned per
    # engine, and the image is a byte copy of canonical chunk KV.

    def _require_ship_support(self):
        _require(self._fam, **{"KV image ship": True})
        if self._closed:
            raise RuntimeError(
                "engine is closed; build a new one with model.serve()")
        if self._failed:
            raise EngineFailedError(
                "engine has failed; rebuild it (EngineSupervisor does "
                "this automatically)", engine_step=self.step_count)
        if self.paged_arena is None or self.prefix_cache is None:
            raise RuntimeError(
                "KV shipping needs paged= AND prefix_cache= on every "
                "replica: the ship format is the paged host image and "
                "residency lives in the radix tree (docs/SERVING.md "
                "'Disaggregated serving')")

    def start_prefix_build(self, prompt_ids):
        """Begin building the shippable prefix of ``prompt_ids``: its
        ``(plen - 1) // block_size`` full blocks (the cap warm
        admission applies — the final partial block is always
        recomputed by the admitting engine to sample from).  Returns a
        :class:`_PrefixJob`, or None when nothing is shippable (short
        prompt).  A prefix already resident in THIS engine's tree
        starts complete (``hit`` set — no recompute, the fleet's
        shared-prefix-hit path); the matched path is ACQUIRED until
        the job is exported or abandoned."""
        self._require_ship_support()
        arena, cache = self.paged_arena, self.prefix_cache
        B = arena.block_size
        toks = np.asarray(prompt_ids, np.int32).reshape(-1)
        plen = len(toks)
        n_goal = (plen - 1) // B
        if n_goal < 1:
            return None
        job = _PrefixJob()
        job.tokens = toks
        job.plen = plen
        job.n_goal = n_goal
        job.engine = self
        nodes = cache.lookup(toks)[:n_goal]
        cache.acquire(nodes)
        job.nodes = nodes
        job.hit = len(nodes) == n_goal
        job.off = len(nodes) * B
        job.last_off = (n_goal - 1) * B
        job.ids_j = None
        job.kc_row = job.vc_row = None
        if job.hit:
            return job
        try:
            ids = np.zeros((1, self.max_len), np.int32)
            ids[0, :plen] = toks
            job.ids_j = jnp.asarray(ids)
            if nodes:
                job.kc_row, job.vc_row = cache.copy_into_row(nodes)
            else:
                # the fresh-zero chunk-from-scratch canonical form —
                # the same row every cold chunked admission starts
                # from
                job.kc_row, job.vc_row = arena.gather_row([],
                                                          n_used=0)
        except Exception:
            # the copies check fault sites (serve.prefix_copy /
            # serve.paged_copy): a raise here happens before the job
            # reaches the caller, so nothing would ever release the
            # acquired path — release it ourselves or the refs pin
            # those blocks unevictable forever (the same guard the
            # warm-admission path keeps)
            self.abandon_prefix_build(job)
            raise
        return job

    def advance_prefix_build(self, job, max_tokens=None, rid=None):
        """Spend up to ``max_tokens`` prefill tokens on the build's
        chunk windows (None = finish it; the fleet passes the
        specialist's ``prefill_token_budget`` so one giant document
        never monopolizes a specialist's step).  Returns True when
        the build is complete.  A raising chunk FAILS THE ENGINE
        typed — the same contract as a raising admission prefill
        inside ``step()`` — which is what makes 'kill a prefill
        specialist mid-ship' a first-class chaos scenario."""
        self._require_ship_support()
        if job.engine is not self:
            # a supervisor rebuild happened under the job: its rows /
            # nodes belong to the dead engine's arena — advancing
            # would adopt the wrong blocks.  The fleet restarts the
            # build (nothing streamed; the replay is identical)
            raise RuntimeError(
                "stale prefix build: the engine was rebuilt under it;"
                " restart the build")
        B = self.paged_arena.block_size
        left = (job.last_off - job.off + B if max_tokens is None
                else int(max_tokens))
        # a prefill specialist never runs the decode step loop, so its
        # anatomy comes from here: each budgeted advance is one phase
        # of its own, which the step-anatomy profiler books as a step
        # quantum (unless a step is already open — a build driven
        # from inside step() stays attributed to that step)
        with _trace.phase("serve.prefix_build", cat="serve",
                          engine=self.stats.engine_label,
                          step=self.step_count):
            try:
                while left >= B and job.off <= job.last_off:
                    if _faults._armed:
                        _faults.check("serve.prefill_chunk")
                    off = job.off
                    _, job.kc_row, job.vc_row = self._x.chunk_row(
                        self._params, job.ids_j, job.kc_row,
                        job.vc_row, jnp.int32(off))
                    self.stats.on_prefill_tokens(B)
                    job.off += B
                    left -= B
                    if _reqs._active and rid is not None:
                        _reqs._ledger.on_prefill_chunk(
                            rid, engine=self.stats.engine_label,
                            t=self._clock(), offset=off)
            except Exception as e:
                self.abandon_prefix_build(job)
                raise self._fail(e) from e
        return job.off > job.last_off

    def abandon_prefix_build(self, job):
        """Release a build's acquired prefix refs (ship fallback,
        failover, a raising chunk).  Idempotent; a job whose engine
        was rebuilt is a no-op (the old tree died with it)."""
        if job.nodes and self.prefix_cache is not None \
                and job.engine is self:
            try:
                self.prefix_cache.release(job.nodes)
            except RuntimeError:
                pass
        job.nodes = []

    def export_prefix_image(self, job):
        """Finish the source half of a ship: DONATE the finished
        chunk row's blocks into this engine's radix tree (residency —
        the next request for this prefix exports without recompute,
        fleet-wide) and pack the narrow versioned host image
        (``serve.kv_ship`` fault site).  Under pool pressure the
        donation is skipped (counted by the cache) and the image
        ships straight from the row — shipping never fails on SOURCE
        capacity.  Returns ``(image, resident)``: ``resident`` says
        whether this engine's tree now holds the prefix (the fleet
        records residency only when it is true — a skipped donation
        must not plant a stale index entry).  Releases the job's
        refs in all cases."""
        self._require_ship_support()
        if job.engine is not self:
            raise RuntimeError(
                "stale prefix build: the engine was rebuilt under it;"
                " restart the build")
        arena, cache = self.paged_arena, self.prefix_cache
        n = job.n_goal
        try:
            if job.hit:
                # resident: export straight from the tree's blocks
                return arena.export_image(
                    [nd.block for nd in job.nodes], n), True
            k = len(job.nodes)
            new = arena.alloc(n - k)
            if new is None:
                cache.on_donate_skipped(n - k)
                return arena.export_row_image(job.kc_row, job.vc_row,
                                              n), False
            try:
                arena.scatter_row(job.kc_row, job.vc_row,
                                  {k + j: b for j, b in enumerate(new)})
                blockmap = [nd.block for nd in job.nodes] + new
                path = cache.adopt_blocks(job.tokens, blockmap, n)
            except Exception:
                arena.free(new)
                raise
            adopted = {nd.block for nd in path}
            arena.free([b for b in new if b not in adopted])
            return arena.export_image(
                [nd.block for nd in path], n), True
        finally:
            self.abandon_prefix_build(job)

    def admit_prefix_image(self, tokens, image):
        """Destination half of a ship: validate the image TYPED
        (:class:`~singa_tpu.serve.kvimage.KVImageError` — a truncated
        or geometry-mismatched image never scatters), land its lanes
        in this pool, and ADOPT them into the radix tree so the next
        admission of ``tokens`` is a local warm hit.  Returns the
        ACQUIRED node path (the caller releases it once the shipped
        request resolves — the blocks must survive until admission),
        or None when the pool has no capacity for the missing blocks
        (cold fallback, counted by the fleet, never an error)."""
        self._require_ship_support()
        arena, cache = self.paged_arena, self.prefix_cache
        toks = np.asarray(tokens, np.int32).reshape(-1)
        n = int(image.n_data)
        existing = cache.lookup(toks)[:n]
        k = len(existing)
        if k == n:
            # already resident (an earlier ship, or a sibling's
            # donation): nothing will scatter, so run the typed
            # validation HERE (the scatter path's lives inside
            # arena.import_image — exactly one validate either way)
            image.validate(arena.block_size, arena.quant,
                           pool_k=arena.pool_k, head_dim=arena.head_dim)
            cache.touch(existing)
            cache.acquire(existing)
            return existing
        # pin the partial hit across the allocation: alloc's LRU
        # eviction must not reclaim the very prefix we are extending
        cache.acquire(existing)
        new = arena.alloc(n - k)
        if new is None:
            cache.release(existing)
            return None
        try:
            arena.import_image(image,
                               {k + j: b for j, b in enumerate(new)})
            blockmap = [nd.block for nd in existing] + new
            path = cache.adopt_blocks(toks, blockmap, n)
        except Exception:
            arena.free(new)
            cache.release(existing)
            raise
        adopted = {nd.block for nd in path}
        arena.free([b for b in new if b not in adopted])
        cache.release(existing)
        cache.acquire(path)
        return path
