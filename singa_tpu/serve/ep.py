"""Expert-parallel MoE serving: one engine's EXPERTS sharded across an
``ep`` mesh axis, dense layers keeping the Megatron TP layout on an
orthogonal ``tp`` axis (the EP-serve round; GShard-style
capacity-bounded expert dispatch composed with the Megatron-LM decode
layout serve/tp.py already runs — ROADMAP item 4's first half).

The serve stack could shard a DENSE model (serve/tp.py) but refused
MoE outright — the expert axis is not the tensor-parallel axis, so a
trained GPT-MoE had no serve story.  This module is the second
executor behind the pluggable ``engine._x`` seam:

* **mesh** — a 2-D ``(ep, tp)`` mesh
  (``parallel.sharding.create_ep_mesh``): the stacked ``moe_*`` expert
  weights shard their leading expert axis over ``ep``
  (``tensor_parallel.decode_param_specs(ep_axis=)``), the dense
  attention/embedding weights ride the Megatron column/row layout over
  ``tp`` exactly as serve/tp.py lays them (replicated over ``ep``),
  and every KV arena keeps the TP head-axis sharding (replicated over
  ``ep`` — experts hold no KV);
* **routing** — per-token top-k gating with CAPACITY-BOUNDED dispatch
  inside the jitted pool-step twins (decode, spec chunk, prefill, warm
  chunk): ``gpt2_decode._moe_ffn_ep`` reuses ``parallel/moe.py``'s
  ``_top1_dispatch``/``_top2_dispatch`` one-hots (the training layer's
  routing math, verbatim), each rank computes only its RESIDENT
  experts' contributions, and ONE ``lax.psum`` over ``ep`` per MoE
  layer sums each token's top-k expert outputs — the degenerate
  all-to-all for replicated decode activations (every rank already
  holds every token, so only the combine half communicates);
* **capacity / drops** — ``EPConfig(capacity_factor=None)`` (default)
  sets capacity to the dispatch's token count: nothing drops, routing
  is per-token independent, and EP streams are pinned token-identical
  to the single-device MoE engine (greedy + seeded, GQA, int8, paged
  preempt-resume — tests/test_ep_serve.py; the ep psum is the one
  arithmetic difference, the same near-tie caveat as the TP psum).  A
  FINITE factor is the GShard capacity mode: expert buffers are
  (E/ep, C, D)-bounded and over-capacity assignments DROP — the
  combine weight goes to zero and the transformer block's RESIDUAL
  path carries the token (renormalized to the surviving expert when
  one of a top-2 pair drops; never a silently zeroed hidden state) —
  deterministic per workload, counted, and refused next to the prefix
  cache (capacity couples tokens within a dispatch group, so chunked
  prefill would stop being canonical with full prefill — the
  warm==cold byte-identity contract cannot survive it);
* **observability** — the dispatch twins RETURN their routing load:
  every EP twin carries two extra replicated outputs (tokens routed
  per expert, assignments dropped — ``parallel.moe.dispatch_load``,
  collected at trace time by ``gpt2_decode._ep_collecting``), and the
  executor feeds ``serve.ep.expert_tokens{engine=,expert=}`` +
  ``serve.ep.dropped_tokens{engine=}`` counters, the
  ``EngineStats.snapshot()["ep"]`` section (with a max/mean
  ``load_imbalance`` — an imbalanced router is the MoE why_slow), and
  ``health_report()["serve"]["ep"]``.

Twins are cached MODULE-WIDE keyed like TP's — supervisor rebuild or
an identical fleet replica is a compile-cache hit (``recompiles: 0``,
counted by ``bench_serve._serve_jit_cache_size``).  Every sharded
dispatch checks the ``serve.ep_dispatch`` fault site: an injected
fault is a raising sharded step — the engine fails TYPED and the
supervisor rebuilds (bench_chaos.py ``chaos_ep`` gates zero
wedged/lost/leaked).

Scope: MoE models (``cfg.moe_every``); ``ep`` must divide
``moe_experts`` and the orthogonal ``tp`` must divide
``n_head``/``n_kv_head``/``n_inner``.  Dense models take ``tp=``
(serve/tp.py); a model carrying a training ``ShardingPlan`` owns its
layout already — both rejected typed at construction, BEFORE any
registry registration.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..observe import trace as _trace
from ..observe.registry import registry as _default_registry
from ..parallel.sharding import EP as EP_AXIS
from ..parallel.sharding import TP as TP_AXIS
from ..parallel.sharding import create_ep_mesh
from ..parallel.tensor_parallel import (decode_cache_spec,
                                        decode_param_specs,
                                        decode_pool_spec)
from ..resilience import faults as _faults
from ..utils.logging import get_channel

__all__ = ["EPConfig", "EPExecutor", "fleet_ep_configs"]

import jax.numpy as jnp

#: replicated spec over the 2-D (ep, tp) mesh
_R = P()
#: KV leaves: head axis (axis 2) over tp, replicated over ep —
#: experts hold no KV, so the cache layout is exactly serve/tp.py's
_CS = decode_cache_spec(TP_AXIS)
_PS = decode_pool_spec(TP_AXIS)

# module-wide twin cache, keyed like tp.py's: (base, extra statics,
# executor key) -> jitted sharded executable
_TWINS = {}


def _twin_cache_size():
    """Compiled-signature count across every cached EP twin — counted
    by ``bench_serve._serve_jit_cache_size`` next to the jit caches so
    the sharded dispatch path cannot recompile unnoticed."""
    total = 0
    for f in _TWINS.values():
        try:
            total += f._cache_size()
        except Exception:
            return None
    return total


@dataclass(frozen=True)
class EPConfig:
    """Knobs for the expert-parallel serve backend (hand to
    ``model.serve(ep=...)`` — a bare int is shorthand for
    ``EPConfig(ep=k)``; the supervisor/fleet forward it verbatim so a
    rebuilt replica lands on the SAME device group and reuses the same
    compiled twins).

    ``ep``: expert-shard count (must divide ``cfg.moe_experts``).
    ``tp``: orthogonal tensor-parallel width for the DENSE layers
    (Megatron column/row, one psum per attention out-proj and MLP fc2
    — serve/tp.py's layout; must divide n_head/n_kv_head/n_inner; 1 =
    dense layers replicated).  The mesh is ``ep x tp`` devices.
    ``devices``: explicit device tuple (default: the first ``ep*tp``
    of ``jax.devices()``) — the fleet hands each EP replica a disjoint
    slice (:func:`fleet_ep_configs`).
    ``capacity_factor``: GShard expert capacity per dispatch group —
    ``C = ceil(top_k * tokens / E * capacity_factor)``.  ``None``
    (default) means capacity == tokens: drop-free, per-token
    independent routing, exact single-device-oracle parity — the serve
    default, because serving wants parity and capacity is a
    buffer-size knob.  A finite factor bounds the (E/ep, C, D) expert
    buffers and DROPS over-capacity assignments through the residual
    path (deterministic, counted in ``serve.ep.dropped_tokens``);
    it is refused next to a prefix cache (chunk canonicality —
    docs/SERVING.md 'Expert-parallel and pipeline serving')."""

    ep: int = 2
    tp: int = 1
    devices: tuple | None = None
    capacity_factor: float | None = None

    def __post_init__(self):
        if self.ep < 1:
            raise ValueError(f"ep must be >= 1, got {self.ep}")
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.devices is not None \
                and len(self.devices) < self.ep * self.tp:
            raise ValueError(
                f"EPConfig(ep={self.ep}, tp={self.tp}) with only "
                f"{len(self.devices)} explicit devices")
        if self.capacity_factor is not None \
                and self.capacity_factor <= 0:
            raise ValueError(
                f"capacity_factor must be > 0 (or None for drop-free "
                f"full capacity), got {self.capacity_factor}")


def as_ep_config(ep):
    """Normalize the ``ep=`` knob (bare int expert-shard count, kwargs
    dict, or an EPConfig) — the ONE coercion the engine and the fleet
    both apply."""
    if isinstance(ep, EPConfig):
        return ep
    if isinstance(ep, int) and not isinstance(ep, bool):
        return EPConfig(ep=ep)
    if isinstance(ep, dict):
        return EPConfig(**ep)
    raise ValueError(
        f"ep must be an int expert-shard count, an EPConfig, or a "
        f"kwargs dict, got {type(ep)}")


def check_ep(config, cfg, model_plan=None, prefix_cache=None):
    """The full EP composition/validity matrix, TYPED — callable
    BEFORE any registry/executor/arena state exists (the engine runs
    it first so a refused construction leaks no metrics; the executor
    re-runs it defensively before registering anything)."""
    if model_plan is not None:
        raise ValueError(
            "ep= on a plan-sharded model: the training ShardingPlan "
            "already owns the weight layout; build the serve model "
            "without a plan and let the EP backend place the decode "
            "weights")
    if getattr(cfg, "moe_every", None) is None:
        raise ValueError(
            f"ep={config.ep} on a dense model (no MoE blocks): there "
            f"is no expert axis to shard — serve dense/GQA models "
            f"with tp= (serve/tp.py)")
    n_exp = int(cfg.moe_experts)
    if n_exp % config.ep != 0:
        raise ValueError(
            f"ep={config.ep} does not divide moe_experts ({n_exp}): "
            f"every shard must own a whole number of experts")
    for what, n in (("n_head", cfg.n_head),
                    ("n_kv_head (H_kv)", cfg.n_kv_head),
                    ("n_inner", cfg.n_inner)):
        if n % config.tp != 0:
            raise ValueError(
                f"EPConfig(tp={config.tp}) does not divide {what} "
                f"({n}): the dense layers' Megatron layout needs a "
                f"whole head/column count per tp shard")
    if config.capacity_factor is not None and prefix_cache is not None \
            and prefix_cache is not False:
        raise ValueError(
            "ep with a finite capacity_factor AND a prefix_cache: "
            "capacity-bounded routing couples tokens within a "
            "dispatch group, so chunked prefill K/V is no longer "
            "canonical with full prefill and the cache's warm==cold "
            "byte-identity contract cannot hold; serve with "
            "capacity_factor=None (drop-free) or drop the cache "
            "(docs/SERVING.md 'Expert-parallel and pipeline serving')")


def fleet_ep_configs(ep, replicas, devices=None):
    """Disjoint per-replica :class:`EPConfig`\\ s: replica ``i`` owns
    the ``ep*tp``-wide device group ``[i*g, (i+1)*g)`` — expert (and
    dense-tensor) parallelism inside each replica, data parallelism
    across them.  Raises when the groups exceed the mesh."""
    ep = as_ep_config(ep)
    if ep.ep * ep.tp == 1:
        return [ep] * replicas
    devs = (list(ep.devices) if ep.devices is not None
            else list(jax.devices()))
    g = ep.ep * ep.tp
    need = g * replicas
    if need > len(devs):
        raise ValueError(
            f"(ep x tp) x replicas ({ep.ep} x {ep.tp} x {replicas} = "
            f"{need}) exceeds the {len(devs)}-device mesh; shrink the "
            f"fleet or the group, or provision a larger virtual mesh "
            f"via XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need}")
    return [EPConfig(ep=ep.ep, tp=ep.tp,
                     capacity_factor=ep.capacity_factor,
                     devices=tuple(devs[i * g:(i + 1) * g]))
            for i in range(replicas)]


def _fold_ep_stats(rec, n_expert, live_r=None):
    """Sum one row/body trace's collected (counts, dropped) pairs —
    one pair per MoE layer application.  ``live_r``: the row's live
    flag — dead decode lanes run clamped garbage through the router
    and must not pollute the load counters."""
    if rec:
        cnt = sum(c for c, _ in rec)
        drp = sum(d for _, d in rec)
    else:
        cnt = jnp.zeros((n_expert,), jnp.int32)
        drp = jnp.int32(0)
    if live_r is not None:
        cnt = jnp.where(live_r, cnt, 0)
        drp = jnp.where(live_r, drp, 0)
    return cnt, drp


class EPExecutor:
    """The engine's expert-parallel executor: owns the ``(ep, tp)``
    mesh, the expert + Megatron weight placement, the stats-carrying
    sharded-twin dispatch, and the ``serve.ep.*`` metrics.  Built by
    ``InferenceEngine`` when ``ep=`` is set; the engine routes every
    target-side dispatch through the same surface ``_LocalExec`` /
    ``TPExecutor`` expose."""

    def __init__(self, config, cfg, statics, quant, model_plan=None,
                 engine_label="0", reg=None, prefix_cache=None):
        # the FULL validity matrix runs before anything registers —
        # a refused construction must leak no metrics (the PR-12
        # leaked-gauge hazard)
        check_ep(config, cfg, model_plan=model_plan,
                 prefix_cache=prefix_cache)
        self.mesh = create_ep_mesh(config.ep, config.tp,
                                   devices=config.devices)
        self.config = config
        self.ep = int(config.ep)
        self.tp = int(config.tp)
        self.n_expert = int(cfg.moe_experts)
        self.n_layer = int(cfg.n_layer)
        self._cap = (None if config.capacity_factor is None
                     else float(config.capacity_factor))
        #: the static triple gpt2_decode._mlp routes the MoE FFN on
        self._ep3 = (EP_AXIS, self.ep, self._cap)
        self._statics = dict(statics)
        self._quant = bool(quant)
        self._spec = None
        self._chunk = None
        self._window = None
        self._pspec = None
        self._cache_sh = NamedSharding(self.mesh, _CS)
        self._pool_sh = NamedSharding(self.mesh, _PS)
        self._head_dim = int(cfg.n_embd) // int(cfg.n_head)
        self._repl_sh = NamedSharding(self.mesh, _R)
        self._kv_bytes = 0
        self._log = get_channel("serve")
        self._key = (self.ep, self.tp, self._cap,
                     tuple(int(d.id) for d in self.mesh.devices.flat),
                     tuple(sorted(self._statics.items())),
                     self._quant)
        reg = reg if reg is not None else _default_registry()
        lbl = dict(engine=engine_label)
        self._g_shards = reg.gauge(
            "serve.ep.shards",
            help="expert-parallel shard count (experts per shard = "
                 "moe_experts / ep)", **lbl)
        self._g_tp = reg.gauge(
            "serve.ep.dense_tp",
            help="orthogonal tensor-parallel width of the dense "
                 "layers inside the (ep, tp) mesh", **lbl)
        self._g_kv = reg.gauge(
            "serve.ep.kv_bytes_per_shard",
            help="persistent KV-cache bytes each tp shard holds "
                 "(experts hold no KV — the arena shards over tp "
                 "only, replicated over ep)", **lbl)
        self._c_dispatch = reg.counter(
            "serve.ep.sharded_dispatches",
            help="sharded-twin executions under the (ep, tp) mesh",
            **lbl)
        self._c_dropped = reg.counter(
            "serve.ep.dropped_tokens",
            help="top-k expert assignments capacity bounded away "
                 "(the token rides the residual path; only a finite "
                 "EPConfig.capacity_factor can drop)", **lbl)
        self._c_expert = [
            reg.counter(
                "serve.ep.expert_tokens",
                help="tokens routed to (and kept by) each expert — "
                     "the router load-balance signal; an imbalanced "
                     "router is the MoE why_slow",
                expert=str(e), **lbl)
            for e in range(self.n_expert)]
        self._g_shards.set(self.ep)
        self._g_tp.set(self.tp)
        self._g_kv.set(0)
        self._registered = [self._g_shards, self._g_tp, self._g_kv,
                            self._c_dispatch, self._c_dropped,
                            *self._c_expert]
        self._registry = reg
        self.expert_tokens = np.zeros(self.n_expert, np.int64)
        self.dropped_tokens = 0
        self._pending_stats = []   # lazy chunk-path (cnt, drp) queue
        self._log.info(
            "ep executor up: %d expert shards x %d tp over %s "
            "(capacity_factor=%s)", self.ep, self.tp,
            [str(d) for d in self.mesh.devices.flat], self._cap)

    # -- placement --------------------------------------------------------
    def place_params(self, params):
        """Lay the decode weights out over the 2-D mesh: stacked
        ``moe_*`` expert weights on their leading axis over ``ep``,
        dense attention/MLP Megatron-style over ``tp``, everything
        else replicated (``decode_param_specs(ep_axis=)``)."""
        self._pspec = decode_param_specs(params, axis=TP_AXIS,
                                         ep_axis=EP_AXIS)
        self._key = self._key + (jax.tree.structure(params),)
        return jax.tree.map(
            lambda a, s: jax.device_put(
                a, NamedSharding(self.mesh, s)), params, self._pspec)

    def place_cache(self, tree, sharding=None):
        sh = sharding or self._cache_sh
        placed = jax.tree.map(lambda a: jax.device_put(a, sh), tree)
        self._kv_bytes += sum(a.nbytes
                              for a in jax.tree.leaves(tree)) // self.tp
        self._g_kv.set(self._kv_bytes)
        return placed

    def place_pool(self, tree):
        """A block pool: sharded over ``tp`` on its last axis (a pool
        row is the K/V heads side by side)."""
        return self.place_cache(tree, self._pool_sh)

    def place_replicated(self, tree):
        return jax.tree.map(
            lambda a: jax.device_put(a, self._repl_sh), tree)

    # -- late statics -----------------------------------------------------
    def set_spec(self, spec_k, d_statics):
        self._spec = (int(spec_k), tuple(d_statics))

    def set_chunk(self, chunk_statics):
        self._chunk = dict(chunk_statics)

    def set_window(self, window):
        self._window = None if window is None else int(window)

    # -- twin dispatch ----------------------------------------------------
    def _twin(self, base, extra, make, donate=()):
        key = (base, extra, self._key)
        fn = _TWINS.get(key)
        if fn is None:
            fn = jax.jit(
                jax.shard_map(make(), mesh=self.mesh,
                              in_specs=self._in_specs(base),
                              out_specs=self._out_specs(base),
                              check_vma=False),
                donate_argnums=donate)
            _TWINS[key] = fn
        return fn

    def _dispatch(self, fn, *args):
        """Run a twin: ``serve.ep_dispatch`` fault site, dispatch
        counter, compile-visibility instant — and for the compute
        twins, strip the two trailing stats outputs into the
        expert-load counters (one tiny host fetch per dispatch; the
        engine syncs the same dispatch's tokens right after, so this
        adds no extra wait)."""
        if _faults._armed:
            _faults.check("serve.ep_dispatch")
        try:
            before = fn._cache_size()
        except Exception:
            before = None
        out = fn(*args)
        if before is not None and fn._cache_size() != before:
            _trace.event("serve/compile", cat="serve", fn="serve.ep",
                         shards=self.ep)
        self._c_dispatch.inc()
        return out

    def _dispatch_stats(self, fn, *args):
        out = self._dispatch(fn, *args)
        *std, cnt, drp = out
        self._flush_stats()
        self._fold_stats(cnt, drp)
        return tuple(std)

    def _dispatch_stats_lazy(self, fn, *args):
        """Like :meth:`_dispatch_stats` but WITHOUT the host fetch:
        the chunk-row path issues many dispatches back to back (warm
        prefill, the chunked-prefill budget) and deliberately stays
        async — a per-chunk stats sync would serialize exactly the
        TTFT pipeline chunking exists for.  The device arrays queue
        and fold at the next synchronous dispatch (every decode step)
        or at :meth:`snapshot` — bounded by the chunks of one
        admission, never unbounded."""
        out = self._dispatch(fn, *args)
        *std, cnt, drp = out
        self._pending_stats.append((cnt, drp))
        return tuple(std)

    def _fold_stats(self, cnt, drp):
        cnt = np.asarray(cnt)
        drp = int(np.asarray(drp))
        self.expert_tokens += cnt
        for e, c in enumerate(cnt):
            if c:
                self._c_expert[e].inc(int(c))
        if drp:
            self.dropped_tokens += drp
            self._c_dropped.inc(drp)

    def _flush_stats(self):
        if self._pending_stats:
            pend, self._pending_stats = self._pending_stats, []
            for cnt, drp in pend:
                self._fold_stats(cnt, drp)

    def _in_specs(self, base):
        ps = self._pspec
        return {
            "pool_decode": (ps, _CS, _CS, _R, _R, _R, _R, _R, _R),
            "pool_spec": (ps, _R, _CS, _CS, _R, _R, _R, _R, _R, _R,
                          _R, _R),
            "prefill_one": (ps, _R, _R, _R, _R, _R),
            "prefill_batch": (ps, _R, _R, _R, _R, _R),
            "chunk_row": (ps, _R, _CS, _CS, _R),
            "paged_decode": (ps, _PS, _PS, _R, _R, _R, _R, _R, _R,
                             _R),
            "paged_spec": (ps, _R, _PS, _PS, _R, _R, _R, _R, _R, _R,
                           _R, _R, _R),
            "write_slot": (_CS, _CS, _CS, _CS, _R),
            "read_slot": (_CS, _CS, _R),
            "pool_to_row": (_PS, _PS, _R, _R),
            "row_to_pool": (_PS, _PS, _CS, _CS, _R),
            "rows_to_pool": (_PS, _PS, _CS, _CS, _R, _R),
        }[base]

    def _out_specs(self, base):
        # compute twins append two REPLICATED stats outputs (routing
        # is computed from replicated activations, identically on
        # every rank)
        return {
            "pool_decode": (_R, _CS, _CS, _R, _R, _R),
            "pool_spec": (_R, _R, _CS, _CS, _R, _R, _R, _R, _R),
            "prefill_one": (_R, _R, _CS, _CS, _R, _R),
            "prefill_batch": (_R, _R, _CS, _CS, _R, _R),
            "chunk_row": (_R, _CS, _CS, _R, _R),
            "paged_decode": (_R, _PS, _PS, _R, _R, _R),
            "paged_spec": (_R, _R, _PS, _PS, _R, _R, _R, _R, _R),
            "write_slot": (_CS, _CS),
            "read_slot": (_CS, _CS),
            "pool_to_row": (_CS, _CS),
            "row_to_pool": (_PS, _PS),
            "rows_to_pool": (_PS, _PS),
        }[base]

    # -- twin bodies ------------------------------------------------------
    # The engine's slot-arena steps vmap a per-row function; the EP
    # stats collector must be consumed INSIDE the vmapped row (its
    # tracers belong to the row's trace), so the small vmap wrappers
    # are restated here with the shared row math untouched — the
    # per-slot ops are engine._decode_row/_spec_row with the ep triple
    # threaded, one definition, no drift.  The paged steps take the
    # triple themselves (:meth:`_mk_paged`).

    def _mk_pool_decode(self):
        from .engine import _decode_row

        st, ep3, tpw = self._statics, self._ep3, self.tp
        from ..models import gpt2_decode as G
        ne = self.n_expert

        def body(params, kc, vc, toks, pos, live, keys, temps, top_p):
            def row(kc_r, vc_r, tok, pos_r, live_r, key, temp):
                with G._ep_collecting() as rec:
                    nxt, kc2, vc2, k2 = _decode_row(
                        params, kc_r, vc_r, tok, pos_r, live_r, key,
                        temp, top_p, **st, tp_axis=TP_AXIS,
                        tp_world=tpw, ep=ep3)
                cnt, drp = _fold_ep_stats(rec, ne, live_r)
                return nxt, kc2, vc2, k2, cnt, drp

            nxt, kc, vc, keys2, cnt, drp = jax.vmap(
                row, in_axes=(1, 1, 0, 0, 0, 0, 0),
                out_axes=(0, 1, 1, 0, 0, 0))(kc, vc, toks, pos, live,
                                             keys, temps)
            return nxt, kc, vc, keys2, cnt.sum(0), drp.sum()

        return body

    def _mk_pool_spec(self):
        from .engine import _spec_row

        from ..models import gpt2_decode as G

        st, ep3, tpw = self._statics, self._ep3, self.tp
        ne = self.n_expert
        spec_k, (dn, de, dm) = self._spec

        def body(t_params, d_params, kc, vc, dkc, dvc, toks, pos,
                 live, keys, temps, top_p):
            def row(kc_r, vc_r, dkc_r, dvc_r, tok, pos_r, live_r, key,
                    temp):
                with G._ep_collecting() as rec:
                    out, a_draft, kc2, vc2, dkc2, dvc2, k2 = _spec_row(
                        t_params, d_params, kc_r, vc_r, dkc_r, dvc_r,
                        tok, pos_r, live_r, key, temp, top_p, spec_k,
                        st["n_head"], st["eps"], st["moe_top_k"], dn,
                        de, dm, st["top_k"], st["use_top_p"],
                        tp_axis=TP_AXIS, tp_world=tpw, ep=ep3)
                cnt, drp = _fold_ep_stats(rec, ne, live_r)
                return (out, a_draft, kc2, vc2, dkc2, dvc2, k2, cnt,
                        drp)

            (out, a_draft, kc, vc, dkc, dvc, keys2, cnt,
             drp) = jax.vmap(
                row, in_axes=(1, 1, 1, 1, 0, 0, 0, 0, 0),
                out_axes=(0, 0, 1, 1, 1, 1, 0, 0, 0))(
                kc, vc, dkc, dvc, toks, pos, live, keys, temps)
            return (out, a_draft, kc, vc, dkc, dvc, keys2,
                    cnt.sum(0), drp.sum())

        return body

    def _mk_paged(self, base, **statics):
        """A paged pool step of serve/paged.py (``base``) with the ep
        triple threaded: the lanes fold their own routing stats
        (``gpt2_decode._ep_lane_stats``), the collector around the
        program hands the sums out."""
        from ..models import gpt2_decode as G

        ne = self.n_expert
        statics.update(tp_axis=TP_AXIS, tp_world=self.tp, ep=self._ep3)

        def body(*args):
            with G._ep_collecting() as rec:
                out = base.__wrapped__(*args, **statics)
            return (*out, *_fold_ep_stats(rec, ne))

        return body

    def _mk_paged_decode(self, block):
        from ..models import gpt2_decode as G
        from .paged import _paged_decode_kernel

        return self._mk_paged(_paged_decode_kernel, block=block,
                              window=self._window, fam=G.FAMILY,
                              **self._statics)

    def _mk_paged_spec(self, block):
        from .paged import _paged_spec_kernel

        st = self._statics
        spec_k, (dn, de, dm) = self._spec
        return self._mk_paged(
            _paged_spec_kernel, block=block, window=self._window,
            spec_k=spec_k, tn=st["n_head"], te=st["eps"],
            tm=st["moe_top_k"], dn=dn, de=de, dm=dm, top_k=st["top_k"],
            use_top_p=st["use_top_p"])

    def _mk_prefill_one(self):
        from .engine import _prefill_one

        from ..models import gpt2_decode as G

        st, ep3, tpw = self._statics, self._ep3, self.tp
        ne = self.n_expert
        quant, window = self._quant, self._window

        def body(params, ids, prompt_len, key, temp, top_p):
            with G._ep_collecting() as rec:
                out = _prefill_one.__wrapped__(
                    params, ids, prompt_len, key, temp, top_p, **st,
                    quant=quant, window=window, tp_axis=TP_AXIS,
                    tp_world=tpw, ep=ep3)
            cnt, drp = _fold_ep_stats(rec, ne)
            return (*out, cnt, drp)

        return body

    def _mk_prefill_batch(self):
        from .engine import _prefill_one

        from ..models import gpt2_decode as G

        st, ep3, tpw = self._statics, self._ep3, self.tp
        ne = self.n_expert
        quant, window = self._quant, self._window

        def body(params, ids, plens, seeds, temps, top_p):
            def row(ids_r, plen, seed, temp):
                key0 = jax.random.split(jax.random.PRNGKey(seed), 1)[0]
                with G._ep_collecting() as rec:
                    out = _prefill_one.__wrapped__(
                        params, ids_r[None], plen, key0, temp, top_p,
                        **st, quant=quant, window=window,
                        tp_axis=TP_AXIS, tp_world=tpw, ep=ep3)
                cnt, drp = _fold_ep_stats(rec, ne)
                return (*out, cnt, drp)

            tok0, keys, kc, vc, cnt, drp = jax.vmap(
                row, in_axes=(0, 0, 0, 0),
                out_axes=(0, 0, 1, 1, 0, 0))(ids, plens, seeds, temps)
            sq = lambda a: a[:, :, 0]
            return (tok0, keys, jax.tree.map(sq, kc),
                    jax.tree.map(sq, vc), cnt.sum(0), drp.sum())

        return body

    def _mk_chunk_row(self):
        from .engine import _chunk_row

        from ..models import gpt2_decode as G

        ck = dict(self._chunk)
        ep3, tpw = self._ep3, self.tp
        ne = self.n_expert

        def body(params, ids, kc_row, vc_row, off):
            with G._ep_collecting() as rec:
                out = _chunk_row.__wrapped__(
                    params, ids, kc_row, vc_row, off, **ck,
                    fam=G.FAMILY, tp_axis=TP_AXIS, tp_world=tpw,
                    ep=ep3)
            cnt, drp = _fold_ep_stats(rec, ne)
            return (*out, cnt, drp)

        return body

    # -- the executor surface (mirrors engine._LocalExec) -----------------
    def pool_decode_step(self, params, kc, vc, toks, pos, live, keys,
                         temps, top_p):
        fn = self._twin("pool_decode", (), self._mk_pool_decode,
                        donate=(1, 2))
        return self._dispatch_stats(fn, params, kc, vc, toks, pos,
                                    live, keys, temps, top_p)

    def pool_spec_step(self, t_params, d_params, kc, vc, dkc, dvc,
                       toks, pos, live, keys, temps, top_p):
        spec_k, d_st = self._spec
        fn = self._twin("pool_spec", (spec_k, d_st),
                        self._mk_pool_spec, donate=(2, 3, 4, 5))
        return self._dispatch_stats(fn, t_params, d_params, kc, vc,
                                    dkc, dvc, toks, pos, live, keys,
                                    temps, top_p)

    def paged_decode_step(self, params, pool_k, pool_v, tables, toks,
                          pos, live, keys, temps, top_p, block):
        fn = self._twin("paged_decode", (block, self._window),
                        lambda: self._mk_paged_decode(block),
                        donate=(1, 2))
        return self._dispatch_stats(fn, params, pool_k, pool_v,
                                    tables, toks, pos, live, keys,
                                    temps, top_p)

    def paged_spec_step(self, t_params, d_params, pool_k, pool_v, dkc,
                        dvc, tables, toks, pos, live, keys, temps,
                        top_p, block):
        spec_k, d_st = self._spec
        fn = self._twin(
            "paged_spec", (block, spec_k, d_st, self._window),
            lambda: self._mk_paged_spec(block),
            donate=(2, 3, 4, 5))
        return self._dispatch_stats(fn, t_params, d_params, pool_k,
                                    pool_v, dkc, dvc, tables, toks,
                                    pos, live, keys, temps, top_p)

    def prefill_one(self, params, ids, prompt_len, key, temp, top_p):
        fn = self._twin("prefill_one", (self._window,),
                        self._mk_prefill_one)
        return self._dispatch_stats(fn, params, ids, prompt_len, key,
                                    temp, top_p)

    def prefill_batch(self, params, ids, plens, seeds, temps, top_p):
        fn = self._twin("prefill_batch", (self._window,),
                        self._mk_prefill_batch)
        return self._dispatch_stats(fn, params, ids, plens, seeds,
                                    temps, top_p)

    def chunk_row(self, params, ids, kc_row, vc_row, off):
        fn = self._twin("chunk_row",
                        tuple(sorted(self._chunk.items())),
                        self._mk_chunk_row, donate=(2, 3))
        return self._dispatch_stats_lazy(fn, params, ids, kc_row,
                                         vc_row, off)

    # -- cache copies (no MoE math — tp.py's bodies, EP's mesh) ----------
    def write_slot(self, kc, vc, kc_row, vc_row, slot):
        from .engine import _write_slot

        fn = self._twin("write_slot", (),
                        lambda: _write_slot.__wrapped__,
                        donate=(0, 1))
        return self._dispatch(fn, kc, vc, kc_row, vc_row, slot)

    def read_slot(self, kc, vc, slot):
        from .prefix import _read_slot

        fn = self._twin("read_slot", (),
                        lambda: _read_slot.__wrapped__)
        return self._dispatch(fn, kc, vc, slot)

    def pool_to_row(self, pool_k, pool_v, idx, n_used):
        from functools import partial

        from .paged import _pool_to_row

        fn = self._twin("pool_to_row", (),
                        lambda: partial(_pool_to_row.__wrapped__,
                                        head_dim=self._head_dim))
        return self._dispatch(fn, pool_k, pool_v, idx, n_used)

    def row_to_pool(self, pool_k, pool_v, kc_row, vc_row, idx):
        from .paged import _row_to_pool

        fn = self._twin("row_to_pool", (),
                        lambda: _row_to_pool.__wrapped__, donate=(0, 1))
        return self._dispatch(fn, pool_k, pool_v, kc_row, vc_row, idx)

    def rows_to_pool(self, pool_k, pool_v, kc_rows, vc_rows, sel, idx):
        from .paged import _rows_to_pool

        fn = self._twin("rows_to_pool", (),
                        lambda: _rows_to_pool.__wrapped__, donate=(0, 1))
        return self._dispatch(fn, pool_k, pool_v, kc_rows, vc_rows,
                              sel, idx)

    # -- lifecycle / reporting -------------------------------------------
    def unregister(self):
        """Release the registry entries (engine close()); the twin
        cache stays module-wide by design."""
        self._registry.remove(*self._registered)

    def snapshot(self) -> dict:
        self._flush_stats()
        toks = self.expert_tokens
        total = int(toks.sum())
        imb = (float(toks.max() / (toks.mean() or 1.0))
               if total else None)
        return {
            "shards": self.ep,
            "dense_tp": self.tp,
            "experts": self.n_expert,
            "experts_per_shard": self.n_expert // self.ep,
            "capacity_factor": self._cap,
            "devices": [str(d) for d in self.mesh.devices.flat],
            "kv_bytes_per_shard": self._kv_bytes,
            "sharded_dispatches": self._c_dispatch.value,
            "expert_tokens": [int(t) for t in toks],
            "dropped_tokens": self.dropped_tokens,
            # max/mean routed load — 1.0 is a perfectly balanced
            # router, E/top_k is total collapse onto one expert
            "load_imbalance": imb,
        }
