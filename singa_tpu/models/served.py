"""What a model family gives the serve engine on the paged path.

``InferenceEngine`` (serve/engine.py) schedules, pages K/V, budgets
prefill and samples; it knows no layer.  A model hands it one
:class:`ServedFamily` through ``model.served_family()``, and the engine's
paged programs (``_chunk_row``, ``_first_from_hidden``,
``paged._paged_decode_kernel``) call the model's math through it alone.
The object is a *static* argument of those programs: it must hash and
compare by what it computes, so that two engines over equal models share
one compiled program.

Two kinds of per-sequence state exist side by side:

* **paged K/V** -- ``kv_geometry`` gives ``(layers, K/V heads, head
  size)``; the engine owns the block pool ``(L, N+1, B, H_kv·D)``, the
  block tables and a prefilling request's private row ``(L, 1, H_kv, W,
  D)``.  Attention over the pool and the write of new rows are shared
  code (``ops/paged_attention.py``).  A family whose cache is one row
  a position -- a latent that every head reads as key and as value --
  sets ``value_leaf = False``: ``kv_geometry`` then gives ``(layers, 1,
  row width)``, the engine allocates ONE pool and one private row, and
  ``pool_v`` / ``vc_row`` are ``None`` wherever the contract passes
  them (no second leaf is allocated to stand in).
* **per-slot state** -- ``state_spec`` names each further kind with its
  shape *per layer per slot* and dtype (no position axis, so it is not
  paged): the engine keeps one arena ``(L, max_slots + 1, *shape)`` a
  kind beside the pool (the last row is the trash row that dead lanes
  write), hands a prefilling request a zeroed ``(L, *shape)`` that each
  chunk row carries on, writes it to the slot's row when the slot goes
  live, and saves and restores the row with the slot's blocks on
  preemption.  A family with no such state returns ``{}`` and sees
  ``None`` wherever state is passed.

``features`` lists the optional engine features the family's math
implements (:data:`FEATURES`); the engine refuses the others by name
(``engine._require``).
"""

from collections import namedtuple

import jax
import jax.numpy as jnp

#: every optional feature a family may implement; the engine's one
#: capability check maps what was asked for onto these names
FEATURES = frozenset({
    "tp=", "ep=", "pp=", "draft_model=", "cache_dtype='int8'",
    "prefix_cache=", "fork",
    "the slot arena (serving without paged=)",
    "whole-prompt admission (paged= without prefill_token_budget)",
    "KV image ship"})


#: one piece of one prefilling request in a chunk-row launch
#: (:meth:`ServedFamily.chunk_rows`): ``chunk`` tokens at ``[off, off +
#: chunk)`` of ``ids``, the first ``n_valid`` of them real
Segment = namedtuple(
    "Segment", "ids kc_row vc_row state off chunk n_valid")


def seg_cat(parts, axis=0):
    """The segments' tokens laid end to end along ``axis``; one
    segment's as they are (no operation)."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis)


def seg_split(x, segs, axis=0):
    """``x``, whose ``axis`` is the segments' tokens end to end, a
    segment at a time; one segment's as it is."""
    if len(segs) == 1:
        return [x]
    at, out = 0, []
    for s in segs:
        out.append(jax.lax.slice_in_dim(x, at, at + s.chunk, axis=axis))
        at += s.chunk
    return out


def seg_tokens(segs):
    """``(token ids, positions)`` of a launch's tokens, the segments'
    end to end: each segment's ``chunk`` ids of its own padded row from
    its own ``off`` on."""
    toks = seg_cat([jax.lax.dynamic_slice(
        s.ids, (0, s.off), (1, s.chunk))[0] for s in segs])
    return toks, seg_cat([s.off + jnp.arange(s.chunk) for s in segs])


def seg_valid(segs):
    """Which of a launch's tokens are real: each segment's first
    ``n_valid``."""
    return seg_cat([jnp.arange(s.chunk) < s.n_valid for s in segs])


class ServedFamily:
    """The contract; a family overrides what its model needs."""

    name = "?"
    features = frozenset()
    #: ``jax.named_scope`` names inside the family's programs that a
    #: device trace may be split by (serve/paged.py keeps, per compiled
    #: program, which instruction lies under which of them)
    scopes = ()
    #: False: the paged cache is one leaf (key and value are one row)
    value_leaf = True
    #: True: ``chunk_row`` is told ``n_valid`` though the family keeps no
    #: state -- its padding would otherwise do work of its own (tokens
    #: that choose experts)
    pad_aware = False
    #: True: ``decode_step`` returns a fifth value, a small int32 array
    #: of counts about the step, which the engine reads with the step's
    #: tokens and hands to ``on_step_counts``
    step_counts = False

    # -- what the engine allocates ---------------------------------------
    def extract_params(self, model, dtype=None):
        """The weights as a pytree of raw arrays, floats cast to
        ``dtype``; ``params["wte"]`` is the token embedding (its dtype
        is the K/V pool's)."""
        raise NotImplementedError

    def kv_geometry(self, cfg):
        """(layers, K/V heads, head size) of the paged cache."""
        raise NotImplementedError

    def state_spec(self, cfg):
        """{kind: (shape per layer per slot, dtype)}; {} = K/V only."""
        return {}

    def state_step_impl(self, state):
        """``"kernel"`` or ``"loop"``: what :meth:`decode_step` advances
        the arenas ``state`` with, where it has two ways and a rule that
        chooses by the operands (``ops/mamba2.step_impl``); None where
        it has not.  The engine asks once, of its own arenas, and says
        the answer on its decode spans."""
        return None

    def window(self, cfg):
        """Sliding-window width of the attention, or None."""
        return None

    def quant_flag(self, cache_dtype):
        """``cache_dtype`` -> whether the pool is (int8, scales)."""
        return False

    # -- the math ----------------------------------------------------------
    def chunk_row(self, params, ids, kc_row, vc_row, state, off, n_valid,
                  *, chunk, block=None, **statics):
        """Prefill ``chunk`` prompt tokens at positions ``[off, off +
        chunk)`` of the padded ``ids`` (1, W) against a private cache
        row that holds K/V below ``off`` and the ``state`` carried from
        the row before; the first ``n_valid`` of them are the prompt's,
        the rest padding that must leave the state alone (both None for
        a family without state, unless it is ``pad_aware``).  ``chunk``
        is the launch's width and ``block`` the pool's block: ``chunk``
        is a whole number of blocks (as many as the step's prefill
        budget allows: any number, not a power of two) and ``off`` a
        multiple of ``block``, not of ``chunk``; what a family lays out
        or walks by the block (the row as pool blocks, a scan's chunks)
        goes by ``block``, and the same rows must come of one wide
        launch as of its blocks one by one.  Returns ``(final-norm
        hidden (1, chunk, E), kc_row, vc_row, state)``.

        This is :meth:`chunk_rows` of one segment; a family overrides
        that.  (``block`` left out: the launch is one block.)"""
        return self.chunk_rows(
            params, [Segment(ids, kc_row, vc_row, state, off, chunk,
                             n_valid)], block=block or chunk, **statics)[0]

    def chunk_rows(self, params, segs, *, block, **statics):
        """One launch over a list of :class:`Segment`: each a piece of
        ONE request as :meth:`chunk_row` describes it -- its own ``ids``,
        private row, carried state, ``off``, width and ``n_valid`` -- and
        no two of one request.  What treats a token alone (norms, the
        projections either side of the mixing, dense and expert
        feed-forward, the router) runs ONCE over the segments' tokens
        laid end to end (:func:`seg_cat`), so a launch reads the weights
        once however many requests it serves; what mixes along a
        sequence (attention over the private row and the segment's own
        causal part, a convolution over the carried tail, a scan from
        the carried state) runs a segment at a time (:func:`seg_split`),
        each against its own request's row and state.  No row is copied
        or stacked: each is updated where it lies.

        A segment of a launch of several is a SLOT: its width is the
        slot's, a whole number of blocks, and ``n_valid`` (always given
        then, whatever the family) may leave whole blocks of it unused.
        What lies past ``n_valid`` must carry no state on and choose no
        expert (as the padding of a prompt's last block); its K/V may
        land in the row, above ``off + n_valid`` only, where the launch
        that really covers those positions writes over it, and nothing
        below ``off`` may change.  One segment must lower to the program
        it always did: laying one piece end to end is no operation.

        Returns a list, an entry a segment, of ``(final-norm hidden (1,
        width, E), kc_row, vc_row, state)``."""
        raise NotImplementedError

    def decode_step(self, params, pool_k, pool_v, state, slots, tables,
                    toks, pos, live, n_blk, *, block, trash, **statics):
        """Advance every lane one token: ``tables`` (W, row blocks),
        ``toks``/``pos``/``live`` (W,), ``slots`` (W,) the state-arena
        row of each lane (the trash row for a dead one), ``n_blk`` the
        block-loop bound.  Writes each live lane's new K/V row into the
        pool (dead lanes: the trash block) and its new state into the
        arena.  Returns ``(logits (W, V), pool_k, pool_v, state)`` --
        and the step's counts after them where ``step_counts``."""
        raise NotImplementedError

    def on_step_counts(self, counts, cfg):
        """The host's reading of one decode step's ``counts`` (numpy):
        ``{name: number}`` for the arguments of the ``singa/serve.step``
        span, and ``{(counter name, labels as a sorted tuple): n}`` to
        add to the engine's counters -- and, where the family has any,
        a third ``{(gauge name, labels): value}`` to set."""
        return {}, {}

    def logits(self, params, hidden):
        """(..., E) final-norm hidden -> (..., V) logits."""
        raise NotImplementedError
