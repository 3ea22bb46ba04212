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

#: every optional feature a family may implement; the engine's one
#: capability check maps what was asked for onto these names
FEATURES = frozenset({
    "tp=", "ep=", "pp=", "draft_model=", "cache_dtype='int8'",
    "prefix_cache=", "fork",
    "the slot arena (serving without paged=)",
    "whole-prompt admission (paged= without prefill_token_budget)",
    "KV image ship"})


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

    def window(self, cfg):
        """Sliding-window width of the attention, or None."""
        return None

    def quant_flag(self, cache_dtype):
        """``cache_dtype`` -> whether the pool is (int8, scales)."""
        return False

    # -- the math ----------------------------------------------------------
    def chunk_row(self, params, ids, kc_row, vc_row, state, off, n_valid,
                  *, chunk, block, **statics):
        """Prefill ``chunk`` prompt tokens at positions ``[off, off +
        chunk)`` of the padded ``ids`` (1, W) against a private cache
        row that holds K/V below ``off`` and the ``state`` carried from
        the row before; the first ``n_valid`` of them are the prompt's,
        the rest padding that must leave the state alone (both None for
        a family without state, unless it is ``pad_aware``).  ``chunk``
        is the launch's width and ``block`` the pool's block: ``chunk``
        is ``block`` times a power of two (whatever the step's prefill
        budget allows) and ``off`` a multiple of ``block``, not of
        ``chunk``; what a family lays out or walks by the block (the
        row as pool blocks, a scan's chunks) goes by ``block``, and the
        same rows must come of one wide launch as of its blocks one by
        one.  Returns ``(final-norm hidden (1, chunk, E), kc_row,
        vc_row, state)``."""
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
