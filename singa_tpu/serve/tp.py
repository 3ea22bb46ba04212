"""Tensor-parallel sharded serving: one engine's weights and KV
memory partitioned across a ``tp`` device mesh (the TP-serve round;
Megatron-LM intra-layer partitioning applied to the paged serve
engine — ROADMAP item 1's second half, after PR 6's data-parallel
fleet).

A fleet of replicas scales REQUESTS, but every replica still holds a
full weight copy and a full KV arena, so the largest servable model is
whatever fits one device.  This module shards ONE engine instead:

* **execution model** — every engine executable (pool decode, spec
  chunk, admission prefill, warm chunk prefill, slot/row copies, the
  paged pool steps, swap in/out) gains a SHARDED TWIN: the same jitted
  function body run under ``jax.shard_map`` over a 1-D ``tp`` mesh
  (``parallel.sharding.create_tp_mesh``), with the Megatron layout
  from ``parallel.tensor_parallel.decode_param_specs`` — attention
  heads and MLP columns column-partitioned (local, no communication),
  attention out-proj and MLP fc2 row-partitioned closing with ONE
  ``lax.psum`` each (``gpt2_decode._tp_psum`` — 2 collectives per
  layer per step, recorded with axis name + mesh size so Chrome traces
  can attribute them);
* **sharded KV** — each shard owns its H_kv/tp contiguous heads of
  every KV leaf: of slot arenas and cache rows the head axis
  (``decode_cache_spec``: ALWAYS axis 2, whatever the leaf rank), of
  the paged block pool and the prefix-cache pool the last axis — a
  ``(L, num_blocks+1, block_size, H_kv/tp·D)`` slice, and the same
  slice of the int8 scales leaf (``decode_pool_spec``; a pool row is
  the heads side by side).  Block ids are global — a pool block is the
  same logical block on every shard — so the host-side free list,
  block tables, radix tree, preemption/swap bookkeeping, scheduler,
  and request ledger are untouched and see a single logical engine;
* **replicated everything else** — embeddings, LayerNorms, the LM
  head, sampling, and the whole DRAFT model (speculative decoding)
  run replicated: every shard computes identical tokens from identical
  post-psum activations, so the twin's outputs need no gather and any
  draft geometry is legal at any tp width;
* **parity** — TP streams are pinned token-identical to the
  single-device engine (tests/test_tp_serve.py: cold/warm/int8/GQA/
  speculative/preempt-resume, greedy and seeded sampling).  The psum
  is the one arithmetic difference (the row-parallel contraction is
  summed per shard, then reduced), so per-position logits agree to
  float addition-order, not bitwise — on token streams that is
  identity away from exact argmax/CDF ties, the same near-tie caveat
  ``generate_speculative`` documents;
* **swap parity across shards** — ``swap_out`` gathers the sharded row
  to ONE host copy with the full head axis (``np.asarray`` assembles
  the global array), so a preempted TP request's host image is
  byte-compatible with the single-device engine's and resume restores
  it shard-exactly.

Twins are cached MODULE-WIDE keyed on (twin, mesh devices, statics) —
a supervisor rebuild or an identical fleet replica reuses the same
compiled executables, keeping the restart-is-a-cache-hit contract;
``bench_serve.py``'s recompile pin counts this cache too.  Every
sharded dispatch checks the ``serve.tp_collective`` fault site
(singa_tpu.resilience): an injected fault is a raising sharded step —
the engine fails TYPED and the supervisor rebuilds the sharded engine
(bench_chaos.py ``chaos_tp`` gates zero wedged/lost requests).

Metrics ride the observe registry as ``serve.tp.{shards,
collectives_per_step,kv_bytes_per_shard,sharded_dispatches}{engine=}``
and surface in ``health_report()["serve"]["tp"]``.

Scope: dense/GQA models (``n_head``, ``n_kv_head``, and ``n_inner``
must divide by ``tp``).  MoE blocks shard over the EXPERT axis, not
tp, and models carrying a training ``ShardingPlan`` own their layout
already — both rejected typed at construction.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import gpt2_decode as G
from ..observe import trace as _trace
from ..observe.registry import registry as _default_registry
from ..parallel.sharding import TP as TP_AXIS
from ..parallel.sharding import create_tp_mesh
from ..parallel.tensor_parallel import (decode_cache_spec,
                                        decode_param_specs,
                                        decode_pool_spec)
from ..resilience import faults as _faults
from ..utils.logging import get_channel

__all__ = ["TPConfig", "TPExecutor", "fleet_tp_configs"]

#: replicated spec (host scalars, token/pos/live vectors, draft state,
#: sampling keys — everything the twins do not shard)
_R = P()
#: every arena / cache-row leaf: head axis (axis 2) over the tp mesh
_CS = decode_cache_spec(TP_AXIS)
#: every block-pool leaf: the row axis (the last; heads side by side)
_PS = decode_pool_spec(TP_AXIS)

# module-wide twin cache: (base, extra statics, executor key) -> jitted
# sharded executable.  Engines, supervisor rebuilds, and same-device
# fleet replicas with identical geometry share one entry, so a restart
# is a jit-cache hit exactly like the single-device engine's contract.
_TWINS = {}


def _twin_cache_size():
    """Compiled-signature count across every cached TP twin —
    ``bench_serve._serve_jit_cache_size`` adds this to the recompile
    pin so the sharded dispatch path cannot recompile unnoticed."""
    total = 0
    for f in _TWINS.values():
        try:
            total += f._cache_size()
        except Exception:
            return None
    return total


@dataclass(frozen=True)
class TPConfig:
    """Knobs for the tensor-parallel serve backend (hand to
    ``model.serve(tp=...)`` — a bare int is shorthand for
    ``TPConfig(tp=k)``; the supervisor/fleet forward it verbatim so a
    rebuilt replica lands on the SAME device group and reuses the same
    compiled twins).

    ``tp``: shard count (the mesh width; 1 = tensor parallelism off).
    ``devices``: explicit device tuple (default: the first ``tp`` of
    ``jax.devices()``) — the fleet hands each TP replica a disjoint
    slice (:func:`fleet_tp_configs`).
    ``ring_prefill``: RING-ATTENTION prefill for cold long-prompt
    admissions (the long-context round): the prompt's sequence axis
    shards over the SAME tp mesh and K/V blocks rotate the ICI ring
    (``parallel/ring_attention.ring_self_attention``, causal), so
    prefill attention workspace per shard is O((S/tp)^2) — prompts
    beyond one shard's flash tile stop being the admission
    bottleneck.  The ring path keeps a REPLICATED full-weight copy
    (context parallelism: sequence sharded, weights whole — the
    attention heads cannot stay Megatron-column-sharded when the
    visiting K/V block carries a different rank's sequence chunk),
    so it trades one extra weight copy for the sequence-memory win;
    composition limits (no prefix cache, no sliding window, no int8)
    are typed at engine construction — docs/SERVING.md "Long-context
    serving".
    ``ring_min_tokens``: only prompts at least this long take the
    ring path (shorter ones stay on the serial narrow-width
    prefill, which is cheaper than paying ppermute latency)."""

    tp: int = 2
    devices: tuple | None = None
    ring_prefill: bool = False
    ring_min_tokens: int = 256

    def __post_init__(self):
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.devices is not None \
                and len(self.devices) < self.tp:
            raise ValueError(
                f"TPConfig(tp={self.tp}) with only "
                f"{len(self.devices)} explicit devices")
        if self.ring_min_tokens < 0:
            raise ValueError(
                f"ring_min_tokens must be >= 0, got "
                f"{self.ring_min_tokens}")


def as_tp_config(tp):
    """Normalize the ``tp=`` knob (bare int shard count, kwargs dict,
    or a TPConfig) to a TPConfig — the ONE coercion the engine and
    the fleet both apply, so what they accept cannot diverge."""
    if isinstance(tp, TPConfig):
        return tp
    if isinstance(tp, int) and not isinstance(tp, bool):
        return TPConfig(tp=tp)
    if isinstance(tp, dict):
        return TPConfig(**tp)
    raise ValueError(
        f"tp must be an int shard count, a TPConfig, or a kwargs "
        f"dict, got {type(tp)}")


def fleet_tp_configs(tp, replicas, devices=None):
    """Disjoint per-replica :class:`TPConfig`\\ s for a fleet of TP
    engines: replica ``i`` owns devices ``[i*tp, (i+1)*tp)`` — tensor
    parallelism inside each replica, data parallelism across them.
    Raises when ``tp x replicas`` exceeds the mesh: TP shards must not
    time-share a device with another replica's shards (on the CPU
    virtual mesh that would silently serialize the fleet)."""
    tp = as_tp_config(tp)
    if tp.tp == 1:
        return [tp] * replicas
    devs = (list(tp.devices) if tp.devices is not None
            else list(jax.devices()))
    need = tp.tp * replicas
    if need > len(devs):
        raise ValueError(
            f"tp x replicas ({tp.tp} x {replicas} = {need}) exceeds "
            f"the {len(devs)}-device mesh; shrink the fleet or the tp "
            f"width, or provision a larger virtual mesh via XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}")
    return [TPConfig(tp=tp.tp,
                     devices=tuple(devs[i * tp.tp:(i + 1) * tp.tp]))
            for i in range(replicas)]


class TPExecutor:
    """The engine's pluggable sharded executor: owns the ``tp`` mesh,
    the Megatron weight placement, the sharded-twin dispatch, and the
    ``serve.tp.*`` metrics.  Built by ``InferenceEngine`` when
    ``tp=`` is set; the engine routes every target-side dispatch
    through the methods below (the default ``_LocalExec`` routes them
    to the single-device executables instead — engine.py)."""

    def __init__(self, config, cfg, statics, quant, model_plan=None,
                 engine_label="0", reg=None):
        if model_plan is not None:
            raise ValueError(
                "tp= on a plan-sharded model: the training "
                "ShardingPlan already owns the weight layout; build "
                "the serve model without a plan and let the TP "
                "backend place the decode weights")
        if getattr(cfg, "moe_every", None) is not None:
            raise NotImplementedError(
                "tp= on an MoE model: expert weights shard over the "
                "expert axis, not the tensor-parallel axis — serve "
                "this model with model.serve(ep=EPConfig(ep=, tp=)) "
                "(singa_tpu/serve/ep.py: expert-parallel decode with "
                "the dense layers on an orthogonal tp axis); bare "
                "tp= covers dense/GQA models")
        tp = int(config.tp)
        # mesh first: "tp wider than the machine" is the clearer error
        # when both it and a divisibility check would fire
        self.mesh = create_tp_mesh(tp, devices=config.devices)
        for what, n in (("n_head", cfg.n_head),
                        ("n_kv_head (H_kv)", cfg.n_kv_head),
                        ("n_inner", cfg.n_inner)):
            if n % tp != 0:
                raise ValueError(
                    f"tp={tp} does not divide {what} ({n}): every "
                    f"shard must own a whole number of heads/columns "
                    f"(and the KV arena slice is (..., H_kv/tp, ...))")
        self.config = config
        self.tp = tp
        self.n_layer = int(cfg.n_layer)
        self._statics = dict(statics)
        self._quant = bool(quant)
        self._spec = None      # (spec_k, (dn, de, dm)) once set_spec
        self._chunk = None     # chunk statics dict once set_chunk
        self._window = None    # sliding window once set_window
        self._ring_params = None   # replicated copy once enable_ring
        self._top = None
        self._pspec = None     # set by place_params
        self._cache_sh = NamedSharding(self.mesh, _CS)
        self._pool_sh = NamedSharding(self.mesh, _PS)
        self._head_dim = int(cfg.n_embd) // int(cfg.n_head)
        self._repl_sh = NamedSharding(self.mesh, _R)
        self._kv_bytes = 0
        self._log = get_channel("serve")
        # twin identity: device group + the engine statics every twin
        # bakes in (per-twin extras — block size, spec/chunk statics —
        # ride the twin key's `extra` slot).  place_params appends the
        # param pytree's treedef: the in_specs closures bake _pspec in,
        # so two models with identical statics on the same devices but
        # different tree STRUCTURE (layer count, head tying) must not
        # share a twin — the cached spec tree would be a mismatched
        # prefix for the second model's params.
        self._key = (tp,
                     tuple(int(d.id) for d in self.mesh.devices.flat),
                     tuple(sorted(self._statics.items())),
                     self._quant)
        reg = reg if reg is not None else _default_registry()
        lbl = dict(engine=engine_label)
        self._lbl = lbl
        self._g_shards = reg.gauge(
            "serve.tp.shards",
            help="tensor-parallel shard count of this engine's mesh",
            **lbl)
        self._g_coll = reg.gauge(
            "serve.tp.collectives_per_step",
            help="psums one decode dispatch issues (2 per layer: "
                 "attention out-proj + MLP fc2)", **lbl)
        self._g_kv = reg.gauge(
            "serve.tp.kv_bytes_per_shard",
            help="persistent KV-cache bytes each shard holds (its "
                 "H_kv/tp slice of every arena/pool this engine "
                 "placed)", **lbl)
        self._c_dispatch = reg.counter(
            "serve.tp.sharded_dispatches",
            help="sharded-twin executions (decode/spec/prefill/copy/"
                 "swap dispatches that ran under shard_map)", **lbl)
        self._g_shards.set(tp)
        self._g_coll.set(2 * self.n_layer)
        self._g_kv.set(0)
        self._registered = [self._g_shards, self._g_coll, self._g_kv,
                            self._c_dispatch]
        self._registry = reg
        self._log.info("tp executor up: %d shards over %s", tp,
                       [str(d) for d in self.mesh.devices.flat])

    # -- placement --------------------------------------------------------
    def place_params(self, params):
        """Lay the extracted decode weights out Megatron-style over
        the mesh (column q/k/v/fc1, row out-proj/fc2, everything else
        replicated — ``decode_param_specs``).  Also derives the
        in-spec pytree every twin uses for its params argument."""
        self._pspec = decode_param_specs(params, axis=TP_AXIS)
        self._key = self._key + (jax.tree.structure(params),)
        # None leaves (the tied-weights head) are empty subtrees in
        # BOTH pytrees, so tree.map skips them and the placed dict
        # keeps its None where the original had one
        return jax.tree.map(
            lambda a, s: jax.device_put(
                a, NamedSharding(self.mesh, s)), params, self._pspec)

    def place_cache(self, tree, sharding=None):
        """Place a KV pytree (arena/row; dense or (values, scales))
        sharded on its head axis, and account its per-shard bytes in
        ``serve.tp.kv_bytes_per_shard``."""
        sh = sharding or self._cache_sh
        placed = jax.tree.map(lambda a: jax.device_put(a, sh), tree)
        self._kv_bytes += sum(a.nbytes
                              for a in jax.tree.leaves(tree)) // self.tp
        self._g_kv.set(self._kv_bytes)
        return placed

    def place_pool(self, tree):
        """:meth:`place_cache` for a block pool: sharded on its last
        axis, each shard its contiguous heads' part of every row."""
        return self.place_cache(tree, self._pool_sh)

    def place_replicated(self, tree):
        """Commit a pytree replicated across the mesh (draft params
        and arenas, sampling keys): every shard reads its own copy and
        the twins' ``P()`` in-specs never re-broadcast per dispatch."""
        return jax.tree.map(
            lambda a: jax.device_put(a, self._repl_sh), tree)

    # -- late statics -----------------------------------------------------
    def set_spec(self, spec_k, d_statics):
        self._spec = (int(spec_k), tuple(d_statics))

    def set_chunk(self, chunk_statics):
        self._chunk = dict(chunk_statics)

    def set_window(self, window):
        """Sliding-window width (or None) — a STATIC every prefill
        and paged-step twin bakes in, so it rides each twin's
        ``extra`` key slot (two engines for the same weights with
        different windows must not share a twin)."""
        self._window = None if window is None else int(window)

    def enable_ring(self, host_params):
        """Arm ring-attention prefill: commit a REPLICATED full-weight
        copy for the sequence-sharded twin (the Megatron column shards
        cannot serve it — a visiting K/V block carries another rank's
        sequence chunk for ALL heads) and register the dispatch
        counter.  The engine runs the composition checks before
        calling this (no prefix cache / window / int8)."""
        self._ring_params = self.place_replicated(host_params)
        self._c_ring = self._registry.counter(
            "serve.tp.ring_prefills",
            help="cold admissions prefilled via ring attention "
                 "(sequence sharded over the tp mesh)", **self._lbl)
        self._registered.append(self._c_ring)
        self.ring_prefills = 0

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
        """Run a twin: the ``serve.tp_collective`` fault site (an
        injected fault is a raising sharded step — the engine fails
        typed, the supervisor rebuilds), the dispatch counter, and a
        ``serve/compile`` trace instant whenever this call compiled a
        new signature (jit-cache-size delta: serve-side compiles must
        not be invisible)."""
        if _faults._armed:
            _faults.check("serve.tp_collective")
        try:
            before = fn._cache_size()
        except Exception:
            before = None
        out = fn(*args)
        if before is not None and fn._cache_size() != before:
            _trace.event("serve/compile", cat="serve", fn="serve.tp",
                         shards=self.tp)
        self._c_dispatch.inc()
        return out

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
            # ring prefill: replicated weights, SEQUENCE-sharded ids
            "ring_prefill": (_R, P(None, TP_AXIS)),
        }[base]

    def _out_specs(self, base):
        return {
            "pool_decode": (_R, _CS, _CS, _R),
            "pool_spec": (_R, _R, _CS, _CS, _R, _R, _R),
            "prefill_one": (_R, _R, _CS, _CS),
            "prefill_batch": (_R, _R, _CS, _CS),
            "chunk_row": (_R, _CS, _CS),
            "paged_decode": (_R, _PS, _PS, _R),
            "paged_spec": (_R, _R, _PS, _PS, _R, _R, _R),
            "write_slot": (_CS, _CS),
            "read_slot": (_CS, _CS),
            "pool_to_row": (_CS, _CS),
            "row_to_pool": (_PS, _PS),
            "rows_to_pool": (_PS, _PS),
            # (hidden, kc_row, vc_row) — everything sharded on the
            # SEQUENCE axis; ring_prefill_one re-places afterwards
            "ring_prefill": (P(None, TP_AXIS, None),
                             P(None, None, None, TP_AXIS, None),
                             P(None, None, None, TP_AXIS, None)),
        }[base]

    # -- the executor surface (mirrors engine._LocalExec) -----------------
    def pool_decode_step(self, params, kc, vc, toks, pos, live, keys,
                         temps, top_p):
        from functools import partial

        from .engine import _pool_decode_step

        fn = self._twin(
            "pool_decode", (),
            lambda: partial(_pool_decode_step.__wrapped__,
                            **self._statics, tp_axis=TP_AXIS,
                            tp_world=self.tp),
            donate=(1, 2))
        return self._dispatch(fn, params, kc, vc, toks, pos, live,
                              keys, temps, top_p)

    def pool_spec_step(self, t_params, d_params, kc, vc, dkc, dvc,
                       toks, pos, live, keys, temps, top_p):
        from functools import partial

        from .engine import _pool_spec_step

        st = self._statics
        spec_k, (dn, de, dm) = self._spec
        fn = self._twin(
            "pool_spec", (spec_k, dn, de, dm),
            lambda: partial(_pool_spec_step.__wrapped__, spec_k=spec_k,
                            tn=st["n_head"], te=st["eps"],
                            tm=st["moe_top_k"], dn=dn, de=de, dm=dm,
                            top_k=st["top_k"],
                            use_top_p=st["use_top_p"],
                            tp_axis=TP_AXIS, tp_world=self.tp),
            donate=(2, 3, 4, 5))
        return self._dispatch(fn, t_params, d_params, kc, vc, dkc,
                              dvc, toks, pos, live, keys, temps,
                              top_p)

    def paged_decode_step(self, params, pool_k, pool_v, tables, toks,
                          pos, live, keys, temps, top_p, block):
        from functools import partial

        from .paged import _paged_decode_kernel

        fn = self._twin(
            "paged_decode", (block, self._window),
            lambda: partial(_paged_decode_kernel.__wrapped__,
                            block=block, **self._statics,
                            window=self._window, fam=G.FAMILY,
                            tp_axis=TP_AXIS, tp_world=self.tp),
            donate=(1, 2))
        return self._dispatch(fn, params, pool_k, pool_v, tables,
                              toks, pos, live, keys, temps, top_p)

    def paged_spec_step(self, t_params, d_params, pool_k, pool_v, dkc,
                        dvc, tables, toks, pos, live, keys, temps,
                        top_p, block):
        from functools import partial

        from .paged import _paged_spec_kernel

        st = self._statics
        spec_k, (dn, de, dm) = self._spec
        fn = self._twin(
            "paged_spec", (block, spec_k, dn, de, dm, self._window),
            lambda: partial(_paged_spec_kernel.__wrapped__, block=block,
                            spec_k=spec_k, tn=st["n_head"],
                            te=st["eps"], tm=st["moe_top_k"], dn=dn,
                            de=de, dm=dm, top_k=st["top_k"],
                            use_top_p=st["use_top_p"],
                            window=self._window,
                            tp_axis=TP_AXIS, tp_world=self.tp),
            donate=(2, 3, 4, 5))
        return self._dispatch(fn, t_params, d_params, pool_k, pool_v,
                              dkc, dvc, tables, toks, pos, live,
                              keys, temps, top_p)

    def prefill_one(self, params, ids, prompt_len, key, temp, top_p):
        from functools import partial

        from .engine import _prefill_one

        fn = self._twin(
            "prefill_one", (self._window,),
            lambda: partial(_prefill_one.__wrapped__, **self._statics,
                            quant=self._quant, window=self._window,
                            tp_axis=TP_AXIS, tp_world=self.tp))
        return self._dispatch(fn, params, ids, prompt_len, key, temp,
                              top_p)

    def prefill_batch(self, params, ids, plens, seeds, temps, top_p):
        from functools import partial

        from .engine import _prefill_batch

        fn = self._twin(
            "prefill_batch", (self._window,),
            lambda: partial(_prefill_batch.__wrapped__,
                            **self._statics, quant=self._quant,
                            window=self._window,
                            tp_axis=TP_AXIS, tp_world=self.tp))
        return self._dispatch(fn, params, ids, plens, seeds, temps,
                              top_p)

    def chunk_row(self, params, ids, kc_row, vc_row, off):
        from functools import partial

        from .engine import _chunk_row

        ck = self._chunk
        fn = self._twin(
            "chunk_row", tuple(sorted(ck.items())),
            lambda: partial(_chunk_row.__wrapped__, **ck, fam=G.FAMILY,
                            tp_axis=TP_AXIS, tp_world=self.tp),
            donate=(2, 3))
        return self._dispatch(fn, params, ids, kc_row, vc_row, off)

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

    # The pool<->row copies are paged.py's own bodies: a shard's part
    # of a pool row is its heads side by side, of a cache row its head
    # slice, so the same blocks<->row turn serves each shard (and, with
    # the block width read off the pool, the paged arena AND the prefix
    # cache's private pool whatever their block sizes).

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

    def _make_ring_body(self):
        """The ring-prefill twin body: per rank, embed the LOCAL
        sequence chunk, and per layer run causal
        ``ring_self_attention`` over the tp axis (K/V blocks rotate
        the ICI ring; logsumexp-exact partial merges) with the
        REPLICATED weights, dense Megatron-free MLP, and collect the
        chunk's K/V in the GQA-narrow head count.  Returns
        (final-LN hidden, kc, vc) — all sequence-sharded; the
        dispatch wrapper re-places them."""
        import jax.numpy as jnp
        from jax import lax

        from ..parallel.communicator import _record_collective
        from ..parallel.ring_attention import ring_self_attention

        st = self._statics
        n_head, eps = st["n_head"], st["eps"]
        moe_top_k = st["moe_top_k"]
        tp = self.tp

        def body(params, ids):
            rank = lax.axis_index(TP_AXIS)
            s_loc = ids.shape[1]
            pos = rank * s_loc + jnp.arange(s_loc)
            x = (jnp.take(params["wte"], ids[0], axis=0)[None]
                 + jnp.take(params["wpe"], pos, axis=0)[None])
            ks, vs = [], []
            for p in G._layers(params):
                h = G._ln(x, p["ln1_s"], p["ln1_b"], eps)
                q = h @ p["wq"] + p["bq"]
                k = h @ p["wk"] + p["bk"]
                v = h @ p["wv"] + p["bv"]
                b, s, e = x.shape
                d = e // n_head
                n_kv = k.shape[-1] // d
                qh = q.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)
                kh = k.reshape(b, s, n_kv, d).transpose(0, 2, 1, 3)
                vh = v.reshape(b, s, n_kv, d).transpose(0, 2, 1, 3)
                krep, vrep = kh, vh
                if n_kv != n_head:
                    # the ring rotates FULL query-head-width K/V (its
                    # per-step kernel has no grouped layout); the
                    # cache keeps the narrow GQA heads below
                    krep = jnp.repeat(kh, n_head // n_kv, axis=1)
                    vrep = jnp.repeat(vh, n_head // n_kv, axis=1)
                # trace-time observe hook: one ring pass issues
                # axis_size ppermutes of this K/V block pair —
                # attributable in Chrome traces like every other
                # collective (axis + world recorded)
                _record_collective("ring_ppermute", [krep, vrep],
                                   axis=TP_AXIS, world=tp)
                a = ring_self_attention(qh, krep, vrep, TP_AXIS,
                                        causal=True, remat=False)
                a = a.transpose(0, 2, 1, 3).reshape(b, s, e)
                x = x + (a @ p["wo"] + p["bo"])
                h2 = G._ln(x, p["ln2_s"], p["ln2_b"], eps)
                x = x + G._mlp(h2, p, moe_top_k)
                ks.append(kh)
                vs.append(vh)
            x = G._ln(x, params["lnf_s"], params["lnf_b"], eps)
            return x, G._cache_stack(ks), G._cache_stack(vs)

        return body

    def ring_prefill_one(self, params, ids, plen, key, temp, top_p):
        """Ring-attention cold admission prefill (the long-context
        round): ``ids`` (1, wn) right-padded at a width divisible by
        both the block size and the mesh width.  One sequence-sharded
        dispatch computes hidden + K/V for the whole prompt — per
        shard the attention tile is O((wn/tp)^2) — then the outputs
        re-place (hidden replicated, rows onto the head-axis cache
        sharding every copy twin expects; one explicit transfer per
        long admission, off the decode hot path) and the admission
        token samples through the same ``_first_from_hidden`` tail
        the chunked path uses.  Token-identical to the serial
        prefill: the logsumexp partial merge reorders the float
        reduction, the same caveat as the decode psum.  Returns the
        ``prefill_one`` contract (tok0, carried key, kc_row,
        vc_row)."""
        import jax.numpy as jnp

        from .engine import _first_from_hidden

        st = self._statics
        fn = self._twin("ring_prefill", (), self._make_ring_body)
        hidden, kc_row, vc_row = self._dispatch(
            fn, self._ring_params, ids)
        hidden = jax.device_put(hidden, self._repl_sh)
        kc_row = jax.tree.map(
            lambda a: jax.device_put(a, self._cache_sh), kc_row)
        vc_row = jax.tree.map(
            lambda a: jax.device_put(a, self._cache_sh), vc_row)
        self._c_ring.inc()
        self.ring_prefills += 1
        tok0, carry_key = _first_from_hidden(
            params, hidden, jnp.int32(plen - 1), key, temp, top_p,
            top_k=st["top_k"], use_top_p=st["use_top_p"],
            fam=G.FAMILY)
        return tok0, carry_key, kc_row, vc_row

    # -- lifecycle / reporting -------------------------------------------
    def unregister(self):
        """Release the registry entries (engine close()).  The twin
        cache is module-wide by design — a successor engine with the
        same geometry rides the same compiled executables."""
        self._registry.remove(*self._registered)

    def snapshot(self) -> dict:
        return {
            "shards": self.tp,
            "devices": [str(d) for d in self.mesh.devices.flat],
            "collectives_per_step": 2 * self.n_layer,
            "kv_bytes_per_shard": self._kv_bytes,
            "sharded_dispatches": self._c_dispatch.value,
        }
