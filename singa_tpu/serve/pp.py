"""Pipeline-parallel serving: one engine's LAYERS partitioned into
stages across a ``pp`` mesh axis, each stage owning its layer slice of
the paged KV pool (the EP/PP-serve round; GPipe's microbatch schedule
applied to continuous-batching decode — ROADMAP item 4's second half,
"models bigger than any single mesh group").

serve/tp.py shards a model WIDE (every layer split across shards);
this module shards it DEEP: a model whose layer stack exceeds one
device's memory serves with stage ``s`` holding layers
``[s*L/P, (s+1)*L/P)`` — the stage split ``parallel/pipeline.py`` uses
for training, restated against the decode pytree.  Third executor
behind the pluggable ``engine._x`` seam:

* **placement** — the per-layer block dicts STACK into (L, ...) arrays
  sharded ``P(pp)`` on the layer axis (each rank materializes only its
  L/P resident layers — the memory win), embeddings/norms/LM-head
  replicated; the paged block pool shards the SAME way:
  ``(L/P, num_blocks+1, B, H_kv·D)`` per stage with GLOBAL block ids,
  so the host-side free list, block tables, radix tree, scheduler,
  preemption/swap bookkeeping, and request ledger run unchanged;
* **microbatched decode** — the jitted pool step runs the GPipe
  schedule over the live continuous batch: the dispatch's slot lanes
  split into M microbatches (``PPConfig(microbatches=)``, clamped by
  gcd to the compacted dispatch width), and each of the ``M + P - 1``
  ticks advances every stage on a different microbatch with
  activations hopping one ``lax.ppermute`` forward — bubbles amortize
  across the batch (fraction ``(P-1)/(M+P-1)``), each rank
  reads/writes only ITS pool slice for the microbatch it is serving,
  and the last stage samples (the same ``_select_sample`` chain) and
  masked-psums tokens + carried keys back to every rank;
* **prefill / warm chunks** — cold admissions and block-width chunk
  windows flow stage-to-stage as one wave (a single row has no
  microbatch parallelism to mine — prefill through a pipeline is
  latency-sequential by construction); every rank runs its resident
  layers per wave and keeps its own K/V via a rank mask, so the cache
  rows come back layer-sharded exactly like the pool.  SPMD honesty:
  each rank traces every wave (its stage on the rotating buffer), so
  a P-stage prefill pays ~P× the FLOPs of the serial one in garbage
  waves — static shapes over compute waste, the standard shard_map
  trade, documented in docs/SERVING.md;
* **parity** — PP streams are pinned token-identical to the
  single-device paged engine (cold/warm/int8/preempt-resume, greedy +
  seeded — tests/test_pp_serve.py): no arithmetic is reordered (layers
  run in the same order with the same per-layer kernels; ppermute
  moves bytes, not sums), so the pin is strictly tighter than TP's
  psum caveat;
* **swap / preemption** — the pool<->row copy twins run with
  ``P(pp)`` layer-axis specs; ``swap_out``'s ``np.asarray`` assembles
  the full layer axis, so a preempted PP request's host image is
  byte-compatible with the single-device engine's (the same cross-
  geometry guarantee TP gives on the head axis).

Twins are cached MODULE-WIDE keyed like TP's (supervisor rebuild or
an identical fleet replica = compile-cache hit; counted by
``bench_serve._serve_jit_cache_size``).  Every sharded dispatch checks
the ``serve.pp_boundary`` fault site: an injected fault is a raising
stage-boundary hop — the engine fails TYPED and the supervisor
rebuilds (bench_chaos.py ``chaos_pp`` gates zero wedged/lost/leaked).

Scope (every refusal typed at construction, BEFORE any registry
registration): requires ``paged=`` (the tentpole memory model —
per-stage block pools); ``stages`` must divide ``n_layer``;
dense/GQA models only (MoE stacks heterogeneous block
dicts — serve MoE with ``ep=``); no speculative draft (the draft's
sequential proposal scan would serialize the pipeline, and a draft of
mismatched depth cannot even take the stage split); no sliding
window; no plan-sharded models; ``pp`` composes with paged + prefix
cache + int8 + chunked-prefill budget.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..observe import trace as _trace
from ..observe.registry import registry as _default_registry
from ..ops.sampling import select_sample as _select_sample
from ..parallel.sharding import PP as PP_AXIS
from ..parallel.sharding import create_pp_mesh
from ..resilience import faults as _faults
from ..utils.logging import get_channel

__all__ = ["PPConfig", "PPExecutor", "fleet_pp_configs"]

#: replicated spec over the 1-D pp mesh
_R = P()
#: every KV leaf (pool, cache row, scales): LAYER axis (axis 0) over pp
_LS = P(PP_AXIS)

# module-wide twin cache, keyed like tp.py's
_TWINS = {}


def _twin_cache_size():
    """Compiled-signature count across every cached PP twin — counted
    by ``bench_serve._serve_jit_cache_size``."""
    total = 0
    for f in _TWINS.values():
        try:
            total += f._cache_size()
        except Exception:
            return None
    return total


@dataclass(frozen=True)
class PPConfig:
    """Knobs for the pipeline-parallel serve backend (hand to
    ``model.serve(pp=...)`` — a bare int is shorthand for
    ``PPConfig(stages=k)``; the supervisor/fleet forward it verbatim
    so a rebuilt replica lands on the SAME device group).

    ``stages``: pipeline depth (must divide ``n_layer``; 1 = off).
    ``microbatches``: decode microbatch count — the GPipe bubble
    knob: a pool step splits its slot lanes into this many
    microbatches so stages overlap on different lanes (bubble
    fraction (stages-1)/(microbatches+stages-1)).  Clamped per
    dispatch to gcd(microbatches, dispatch width) so the compacted
    width buckets stay legal.  Default: ``stages``.
    ``devices``: explicit device tuple (default: the first ``stages``
    of ``jax.devices()``) — the fleet hands each PP replica a
    disjoint stage-wide group (:func:`fleet_pp_configs`)."""

    stages: int = 2
    microbatches: int | None = None
    devices: tuple | None = None

    def __post_init__(self):
        if self.stages < 1:
            raise ValueError(f"stages must be >= 1, got {self.stages}")
        if self.microbatches is not None and self.microbatches < 1:
            raise ValueError(
                f"microbatches must be >= 1 (or None for one per "
                f"stage), got {self.microbatches}")
        if self.devices is not None \
                and len(self.devices) < self.stages:
            raise ValueError(
                f"PPConfig(stages={self.stages}) with only "
                f"{len(self.devices)} explicit devices")

    @property
    def mb(self):
        return (self.stages if self.microbatches is None
                else int(self.microbatches))


def as_pp_config(pp):
    """Normalize the ``pp=`` knob (bare int stage count, kwargs dict,
    or a PPConfig) — the ONE coercion the engine and the fleet both
    apply."""
    if isinstance(pp, PPConfig):
        return pp
    if isinstance(pp, int) and not isinstance(pp, bool):
        return PPConfig(stages=pp)
    if isinstance(pp, dict):
        return PPConfig(**pp)
    raise ValueError(
        f"pp must be an int stage count, a PPConfig, or a kwargs "
        f"dict, got {type(pp)}")


def check_pp(config, cfg, model_plan=None, paged=None,
             draft_model=None, window=None):
    """The full PP composition/validity matrix, TYPED — callable
    BEFORE any registry/executor/arena state exists (the engine runs
    it first so a refused construction leaks no metrics)."""
    if model_plan is not None:
        raise ValueError(
            "pp= on a plan-sharded model: the training ShardingPlan "
            "already owns the weight layout; build the serve model "
            "without a plan and let the PP backend place the decode "
            "weights")
    if getattr(cfg, "moe_every", None) is not None:
        raise ValueError(
            f"pp={config.stages} on an MoE model: MoE and dense "
            f"blocks carry different weight sets, so the layer stack "
            f"cannot stack into the stage-sharded (L, ...) arrays — "
            f"serve MoE models with ep=EPConfig(ep=, tp=) "
            f"(singa_tpu/serve/ep.py)")
    # mesh first: "stages wider than the machine" is the clearer
    # error when both it and the divisibility check would fire (the
    # same ordering serve/tp.py keeps)
    devs = (config.devices if config.devices is not None
            else jax.devices())
    if len(devs) < config.stages:
        raise ValueError(
            f"stages={config.stages} needs {config.stages} devices, "
            f"have {len(devs)} — provision a virtual CPU mesh via "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{config.stages} or lower stages")
    if cfg.n_layer % config.stages != 0:
        raise ValueError(
            f"stages={config.stages} does not divide n_layer "
            f"({cfg.n_layer}): every stage must own a whole number "
            f"of layers (and the paged pool's layer axis slices the "
            f"same way)")
    if paged is None or paged is False:
        raise ValueError(
            "pp= requires paged=: the pipeline's memory model IS the "
            "per-stage slice of the paged block pool "
            "(docs/SERVING.md 'Expert-parallel and pipeline "
            "serving'); the slot arena has no stage split")
    if draft_model is not None:
        raise ValueError(
            f"pp= with a speculative draft: the draft's spec_k "
            f"sequential proposal scan would serialize every "
            f"pipeline tick, and a draft of mismatched depth "
            f"({getattr(draft_model.cfg, 'n_layer', '?')} layers vs "
            f"{config.stages} stages) cannot take the stage split at "
            f"all; serve speculative traffic on tp=/ep= engines")
    if window is not None:
        raise NotImplementedError(
            "pp= on a sliding-window model is not implemented (the "
            "windowed block-drop bookkeeping is untested against "
            "stage-sliced pools); serve windowed models with tp= or "
            "single-device paged engines")


def fleet_pp_configs(pp, replicas, devices=None):
    """Disjoint per-replica :class:`PPConfig`\\ s: replica ``i`` owns
    the stage-wide device group ``[i*stages, (i+1)*stages)`` —
    pipeline parallelism inside each replica, data parallelism across
    them."""
    pp = as_pp_config(pp)
    if pp.stages == 1:
        return [pp] * replicas
    devs = (list(pp.devices) if pp.devices is not None
            else list(jax.devices()))
    need = pp.stages * replicas
    if need > len(devs):
        raise ValueError(
            f"stages x replicas ({pp.stages} x {replicas} = {need}) "
            f"exceeds the {len(devs)}-device mesh; shrink the fleet "
            f"or the stage count, or provision a larger virtual mesh "
            f"via XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need}")
    return [PPConfig(stages=pp.stages, microbatches=pp.microbatches,
                     devices=tuple(devs[i * pp.stages:
                                        (i + 1) * pp.stages]))
            for i in range(replicas)]


class PPExecutor:
    """The engine's pipeline-parallel executor: owns the ``pp`` mesh,
    the stage-stacked weight placement, the GPipe-scheduled sharded
    twins, and the ``serve.pp.*`` metrics.  Built by
    ``InferenceEngine`` when ``pp=`` is set; exposes the same surface
    ``_LocalExec``/``TPExecutor``/``EPExecutor`` do."""

    def __init__(self, config, cfg, statics, quant, model_plan=None,
                 engine_label="0", reg=None):
        # defensive re-validation (the engine already ran the full
        # matrix BEFORE any registration; direct users get the same
        # typed errors here, still before this executor registers)
        if model_plan is not None or \
                getattr(cfg, "moe_every", None) is not None or \
                cfg.n_layer % config.stages != 0:
            check_pp(config, cfg, model_plan=model_plan, paged=True)
        self.mesh = create_pp_mesh(config.stages,
                                   devices=config.devices)
        self.config = config
        self.stages = int(config.stages)
        self.microbatches = int(config.mb)
        self.n_layer = int(cfg.n_layer)
        self._statics = dict(statics)
        self._quant = bool(quant)
        self._chunk = None
        self._window = None
        self._pspec = None
        self._layer_sh = NamedSharding(self.mesh, _LS)
        self._head_dim = int(cfg.n_embd) // int(cfg.n_head)
        self._repl_sh = NamedSharding(self.mesh, _R)
        self._kv_bytes = 0
        self._log = get_channel("serve")
        self._key = (self.stages, self.microbatches,
                     tuple(int(d.id) for d in self.mesh.devices.flat),
                     tuple(sorted(self._statics.items())),
                     self._quant)
        reg = reg if reg is not None else _default_registry()
        lbl = dict(engine=engine_label)
        self._g_stages = reg.gauge(
            "serve.pp.stages",
            help="pipeline stage count (layers per stage = n_layer / "
                 "stages)", **lbl)
        self._g_mb = reg.gauge(
            "serve.pp.microbatches",
            help="decode microbatch count the GPipe schedule splits "
                 "each pool step's slot lanes into", **lbl)
        self._g_kv = reg.gauge(
            "serve.pp.kv_bytes_per_stage",
            help="persistent KV-cache bytes each stage holds (its "
                 "L/stages layer slice of every pool this engine "
                 "placed)", **lbl)
        self._c_dispatch = reg.counter(
            "serve.pp.sharded_dispatches",
            help="sharded-twin executions under the pp mesh", **lbl)
        self._c_hops = reg.counter(
            "serve.pp.boundary_hops",
            help="stage-boundary activation hops (one ppermute per "
                 "pipeline tick) the decode twins issued", **lbl)
        self._g_stages.set(self.stages)
        self._g_mb.set(self.microbatches)
        self._g_kv.set(0)
        self._registered = [self._g_stages, self._g_mb, self._g_kv,
                            self._c_dispatch, self._c_hops]
        self._registry = reg
        self._log.info(
            "pp executor up: %d stages (%d layers each) x %d "
            "microbatches over %s", self.stages,
            self.n_layer // self.stages, self.microbatches,
            [str(d) for d in self.mesh.devices.flat])

    # -- placement --------------------------------------------------------
    def place_params(self, params):
        """Stack the per-layer block dicts into (L, ...) arrays
        sharded ``P(pp)`` on the layer axis (each stage materializes
        only its resident layers); embeddings, final norm, and the
        head replicate.  The engine's dispatches carry the stacked
        structure from here on — the host-side step loop never reads
        inside ``params``."""
        out = {k: v for k, v in params.items() if k != "blocks"}
        # one dict of (L, ...) arrays — the stage-shardable layout, and
        # what ``extract_params`` hands over for a dense model (typed
        # refusal on heterogeneous stacks is check_pp's job: MoE)
        out["blocks"] = params["blocks"]
        spec = {k: (None if v is None else _R)
                for k, v in out.items() if k != "blocks"}
        spec["blocks"] = {k: _LS for k in out["blocks"]}
        self._pspec = spec
        self._key = self._key + (jax.tree.structure(out),)
        return jax.tree.map(
            lambda a, s: jax.device_put(
                a, NamedSharding(self.mesh, s)), out, spec)

    def place_cache(self, tree):
        placed = jax.tree.map(
            lambda a: jax.device_put(a, self._layer_sh), tree)
        self._kv_bytes += sum(
            a.nbytes for a in jax.tree.leaves(tree)) // self.stages
        self._g_kv.set(self._kv_bytes)
        return placed

    #: a pool is layer-sliced like every other KV leaf here
    place_pool = place_cache

    def place_replicated(self, tree):
        return jax.tree.map(
            lambda a: jax.device_put(a, self._repl_sh), tree)

    # -- late statics -----------------------------------------------------
    def set_spec(self, spec_k, d_statics):
        raise RuntimeError(
            "speculative decoding on a pipeline engine — check_pp "
            "refuses this at construction")

    def set_chunk(self, chunk_statics):
        self._chunk = dict(chunk_statics)

    def set_window(self, window):
        if window is not None:
            raise RuntimeError(
                "sliding window on a pipeline engine — check_pp "
                "refuses this at construction")
        self._window = None

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

    def _dispatch(self, fn, *args, hops=0):
        """Run a twin: the ``serve.pp_boundary`` fault site (an
        injected fault is a raising stage-boundary hop — the engine
        fails typed, the supervisor rebuilds), the dispatch/hop
        counters, and a compile-visibility instant."""
        if _faults._armed:
            _faults.check("serve.pp_boundary")
        try:
            before = fn._cache_size()
        except Exception:
            before = None
        out = fn(*args)
        if before is not None and fn._cache_size() != before:
            _trace.event("serve/compile", cat="serve", fn="serve.pp",
                         stages=self.stages)
        self._c_dispatch.inc()
        if hops:
            self._c_hops.inc(hops)
        return out

    def _in_specs(self, base):
        ps = self._pspec
        return {
            "paged_decode": (ps, _LS, _LS, _R, _R, _R, _R, _R, _R,
                             _R),
            "prefill_one": (ps, _R, _R, _R, _R, _R),
            "prefill_batch": (ps, _R, _R, _R, _R, _R),
            "chunk_row": (ps, _R, _LS, _LS, _R),
            "pool_to_row": (_LS, _LS, _R, _R),
            "row_to_pool": (_LS, _LS, _LS, _LS, _R),
            "rows_to_pool": (_LS, _LS, _LS, _LS, _R, _R),
        }[base]

    def _out_specs(self, base):
        return {
            "paged_decode": (_R, _LS, _LS, _R),
            "prefill_one": (_R, _R, _LS, _LS),
            "prefill_batch": (_R, _R, _LS, _LS),
            "chunk_row": (_R, _LS, _LS),
            "pool_to_row": (_LS, _LS),
            "row_to_pool": (_LS, _LS),
            "rows_to_pool": (_LS, _LS),
        }[base]

    # -- stage helpers (trace-time) --------------------------------------
    def _local_layers(self):
        return self.n_layer // self.stages

    def _fwd_perm(self):
        return [(i, i + 1) for i in range(self.stages - 1)]

    def _stage_wave(self, x, layer_fn):
        """One full pipeline pass of a SINGLE wave (prefill/chunk):
        every rank applies its resident layers to the rotating buffer
        each iteration; rank ``s``'s iteration-``s`` output is the
        true activation, and its per-layer side outputs are kept via
        a rank mask.  Returns (final hidden — masked-psum replicated,
        kept side-output pytree — layer-sharded)."""
        rank = lax.axis_index(PP_AXIS)
        stages = self.stages
        kept = None
        buf = x
        y = x
        for s in range(stages):
            y, side = layer_fn(buf)
            mine = rank == s
            if kept is None:
                kept = jax.tree.map(
                    lambda a: jnp.where(mine, a, jnp.zeros_like(a)),
                    side)
            else:
                kept = jax.tree.map(
                    lambda old, new: jnp.where(mine, new, old),
                    kept, side)
            if stages > 1 and s < stages - 1:
                # no trailing permute: the last wave's output leaves
                # through the masked psum below, so a final hop would
                # be a dead cross-stage transfer (and would break the
                # boundary_hops counter's one-permute-per-issued-hop
                # exactness)
                buf = lax.ppermute(y, PP_AXIS, self._fwd_perm())
        h = jnp.where(rank == stages - 1, y, jnp.zeros_like(y))
        return lax.psum(h, PP_AXIS), kept

    # -- twin bodies ------------------------------------------------------
    def _mk_paged_decode(self, block):
        from ..models import gpt2_decode as G

        st = self._statics
        n_head, eps = st["n_head"], st["eps"]
        moe_top_k = st["moe_top_k"]
        top_k, use_top_p = st["top_k"], st["use_top_p"]
        stages = self.stages
        mb_req = self.microbatches
        L_loc = self._local_layers()
        fwd = self._fwd_perm()

        def body(params, pool_k, pool_v, tables, toks, pos, live,
                 keys, temps, top_p):
            rank = lax.axis_index(PP_AXIS)
            S = toks.shape[0]
            M = math.gcd(mb_req, S)
            mbw = S // M
            blocks = params["blocks"]
            trash = jax.tree.leaves(pool_k)[0].shape[1] - 1
            p_all = jnp.where(live, pos, 0)
            n_blk = jnp.max((p_all + block - 1) // block)
            emb_dt = params["wte"].dtype
            E = params["wte"].shape[1]
            buf = jnp.zeros((mbw, E), emb_dt)
            toks_out = jnp.zeros((S,), jnp.int32)
            keys_out = keys

            def samp(lg_r, key, temp):
                ks = jax.random.split(key)
                nxt = _select_sample(lg_r, ks[0], temp, top_k, top_p,
                                     use_top_p)
                return nxt, ks[1]

            for t in range(M + stages - 1):
                m = t - rank
                valid = (m >= 0) & (m < M)
                mc = jnp.clip(m, 0, M - 1)
                i0 = mc * mbw
                tb = lax.dynamic_slice_in_dim(tables, i0, mbw, axis=0)
                tk = lax.dynamic_slice_in_dim(toks, i0, mbw)
                ps_ = lax.dynamic_slice_in_dim(pos, i0, mbw)
                lv = lax.dynamic_slice_in_dim(live, i0, mbw) & valid
                tp_ = lax.dynamic_slice_in_dim(temps, i0, mbw)
                ky = lax.dynamic_slice_in_dim(keys, i0, mbw, axis=0)
                p_c = jnp.where(lv, ps_, 0)
                t_c = jnp.where(lv, tk, 0)
                # pipeline entry (rank 0): embed this tick's
                # microbatch; later stages consume the hop buffer
                x0 = params["wte"][t_c] + params["wpe"][p_c]
                # each rank runs the microbatch through ITS layers and
                # writes their new rows into its layer slice of the
                # pool; invalid/dead lanes land in trash
                h_out = jnp.where(rank == 0, x0, buf)[:, None, :]
                for i in range(L_loc):
                    lp = {k: v[i] for k, v in blocks.items()}
                    h_out, pool_k, pool_v = G._block_paged(
                        h_out, pool_k, pool_v, lp, i, tb, p_c, lv,
                        n_blk, n_head, eps, block, trash,
                        moe_top_k=moe_top_k)
                h_out = h_out[:, 0]
                # pipeline exit (rank P-1): final LN + head + sample
                # for the microbatch that just left the last stage.
                # Every rank traces this (SPMD), only the last one's
                # values survive the masked writes below.
                xf = G._ln(h_out[:, None, :], params["lnf_s"],
                           params["lnf_b"], eps)
                lg = G._logits(xf, params)[:, 0]
                nxt, k2 = jax.vmap(samp)(lg, ky, tp_)
                emit = (rank == stages - 1) & valid
                cur_t = lax.dynamic_slice_in_dim(toks_out, i0, mbw)
                toks_out = lax.dynamic_update_slice_in_dim(
                    toks_out, jnp.where(emit, nxt, cur_t), i0, axis=0)
                cur_k = lax.dynamic_slice_in_dim(keys_out, i0, mbw,
                                                 axis=0)
                keys_out = lax.dynamic_update_slice_in_dim(
                    keys_out, jnp.where(emit, k2, cur_k), i0, axis=0)
                if stages > 1 and t < M + stages - 2:
                    # the final tick's output leaves through the
                    # masked psums below — same dead-hop guard as
                    # _stage_wave, keeping issued permutes ==
                    # M + stages - 2 == the boundary_hops count
                    buf = lax.ppermute(h_out, PP_AXIS, fwd)
            last = rank == stages - 1
            toks_out = lax.psum(
                jnp.where(last, toks_out, jnp.zeros_like(toks_out)),
                PP_AXIS)
            keys_out = lax.psum(
                jnp.where(last, keys_out, jnp.zeros_like(keys_out)),
                PP_AXIS)
            return toks_out, pool_k, pool_v, keys_out

        return body

    def _prefill_wave(self, params, x):
        """Shared stage-flow prefill core: run the batch ``x``
        (B, W, E) through every stage, each rank keeping its resident
        layers' head-shaped (and optionally quantized) K/V.  Returns
        (final-LN hidden (B, W, E) replicated, kc, vc layer-sharded
        (L_loc, B, H, W, D))."""
        from ..models import gpt2_decode as G

        st = self._statics
        n_head, eps = st["n_head"], st["eps"]
        moe_top_k = st["moe_top_k"]
        quant = self._quant
        L_loc = self._local_layers()
        blocks = params["blocks"]
        b, sp, e = x.shape
        d = e // n_head

        def layer_fn(h):
            y = h
            ks, vs = [], []
            for i in range(L_loc):
                lp = {k: v[i] for k, v in blocks.items()}
                y, k_, v_ = G._block_prefill(y, lp, n_head, eps,
                                             moe_top_k=moe_top_k)
                n_kv = k_.shape[-1] // d
                kh = k_.reshape(b, sp, n_kv, d).transpose(0, 2, 1, 3)
                vh = v_.reshape(b, sp, n_kv, d).transpose(0, 2, 1, 3)
                if quant:
                    kh = G._quantize_kv(kh)
                    vh = G._quantize_kv(vh)
                ks.append(kh)
                vs.append(vh)
            return y, (G._cache_stack(ks), G._cache_stack(vs))

        h, (kc, vc) = self._stage_wave(x, layer_fn)
        h = G._ln(h, params["lnf_s"], params["lnf_b"], eps)
        return h, kc, vc

    def _mk_prefill_one(self):
        from ..models import gpt2_decode as G

        st = self._statics
        top_k, use_top_p = st["top_k"], st["use_top_p"]
        wave = self._prefill_wave

        def body(params, ids, prompt_len, key, temp, top_p):
            pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :]
            x = jnp.take(params["wte"], ids, axis=0) + \
                jnp.take(params["wpe"], pos, axis=0)
            hidden, kc, vc = wave(params, x)
            last_h = jax.lax.dynamic_index_in_dim(
                hidden, prompt_len - 1, axis=1, keepdims=False)
            logit0 = G._logits(last_h[:, None, :], params)[0, 0]
            ks = jax.random.split(key)
            tok0 = _select_sample(logit0, ks[0], temp, top_k, top_p,
                                  use_top_p)
            return tok0, ks[1], kc, vc

        return body

    def _mk_prefill_batch(self):
        from ..models import gpt2_decode as G

        st = self._statics
        top_k, use_top_p = st["top_k"], st["use_top_p"]
        wave = self._prefill_wave

        def body(params, ids, plens, seeds, temps, top_p):
            pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :]
            x = jnp.take(params["wte"], ids, axis=0) + \
                jnp.take(params["wpe"], pos, axis=0)
            hidden, kc, vc = wave(params, x)

            def tail(h_r, plen, seed, temp):
                key0 = jax.random.split(jax.random.PRNGKey(seed),
                                        1)[0]
                last_h = jax.lax.dynamic_index_in_dim(
                    h_r, plen - 1, axis=0, keepdims=False)
                logit0 = G._logits(last_h[None, None, :],
                                   params)[0, 0]
                ks = jax.random.split(key0)
                tok0 = _select_sample(logit0, ks[0], temp, top_k,
                                      top_p, use_top_p)
                return tok0, ks[1]

            tok0, keys = jax.vmap(tail)(hidden, plens, seeds, temps)
            return tok0, keys, kc, vc

        return body

    def _mk_chunk_row(self):
        from ..models import gpt2_decode as G

        ck = dict(self._chunk)
        n_head, eps = ck["n_head"], ck["eps"]
        moe_top_k, block = ck["moe_top_k"], ck["chunk"]
        L_loc = self._local_layers()
        stage_wave = self._stage_wave

        def body(params, ids, kc_row, vc_row, off):
            from .engine import _launch_of

            blocks = params["blocks"]
            off, chunk = _launch_of(off, block)
            toks = jax.lax.dynamic_slice(ids, (0, off), (1, chunk))
            pos = off + jnp.arange(chunk)
            x = jnp.take(params["wte"], toks[0], axis=0)[None] + \
                jnp.take(params["wpe"], pos, axis=0)[None]

            # the SAME wave schedule prefill rides (_stage_wave: one
            # schedule definition, no drift): each rank advances the
            # chunk through its resident layers against its ORIGINAL
            # row slice — rank r's true wave is wave r, and at that
            # point no earlier where-fold has touched rank r's local
            # rows, so reading the closure rows is exact — and keeps
            # its own updated (kc, vc) stacks via the rank mask
            def layer_fn(h):
                y = h
                new_k, new_v = [], []
                for i in range(L_loc):
                    lp = {k: v[i] for k, v in blocks.items()}
                    y, kl, vl = G._block_chunk(
                        y, lp, G._cache_layer(kc_row, i),
                        G._cache_layer(vc_row, i), off, n_head, eps,
                        moe_top_k=moe_top_k)
                    new_k.append(kl)
                    new_v.append(vl)
                return y, (G._cache_stack(new_k),
                           G._cache_stack(new_v))

            h, (kc2, vc2) = stage_wave(x, layer_fn)
            h = G._ln(h, params["lnf_s"], params["lnf_b"], eps)
            # (the window's last block, as engine._chunk_row returns)
            return h[:, chunk - block:] if chunk > block else h, kc2, vc2

        return body

    # -- the executor surface (paged subset — check_pp guarantees it) -----
    def paged_decode_step(self, params, pool_k, pool_v, tables, toks,
                          pos, live, keys, temps, top_p, block):
        fn = self._twin("paged_decode", (block,),
                        lambda: self._mk_paged_decode(block),
                        donate=(1, 2))
        S = int(toks.shape[0])
        hops = math.gcd(self.microbatches, S) + self.stages - 2
        return self._dispatch(fn, params, pool_k, pool_v, tables,
                              toks, pos, live, keys, temps, top_p,
                              hops=max(hops, 0))

    def paged_spec_step(self, *a, **k):
        raise RuntimeError(
            "speculative decoding on a pipeline engine — check_pp "
            "refuses this at construction")

    def pool_decode_step(self, *a, **k):
        raise RuntimeError(
            "slot-arena decode on a pipeline engine — pp requires "
            "paged= (check_pp refuses this at construction)")

    pool_spec_step = paged_spec_step

    def prefill_one(self, params, ids, prompt_len, key, temp, top_p):
        fn = self._twin("prefill_one", (), self._mk_prefill_one)
        return self._dispatch(fn, params, ids, prompt_len, key, temp,
                              top_p, hops=self.stages - 1)

    def prefill_batch(self, params, ids, plens, seeds, temps, top_p):
        fn = self._twin("prefill_batch", (), self._mk_prefill_batch)
        return self._dispatch(fn, params, ids, plens, seeds, temps,
                              top_p, hops=self.stages - 1)

    def chunk_row(self, params, ids, kc_row, vc_row, off):
        fn = self._twin("chunk_row",
                        tuple(sorted(self._chunk.items())),
                        self._mk_chunk_row, donate=(2, 3))
        return self._dispatch(fn, params, ids, kc_row, vc_row, off,
                              hops=self.stages - 1)

    def write_slot(self, *a, **k):
        raise RuntimeError(
            "slot-arena write on a pipeline engine — pp requires "
            "paged= (check_pp refuses this at construction)")

    read_slot = write_slot

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
        return {
            "stages": self.stages,
            "layers_per_stage": self.n_layer // self.stages,
            "microbatches": self.microbatches,
            "devices": [str(d) for d in self.mesh.devices.flat],
            "kv_bytes_per_stage": self._kv_bytes,
            "sharded_dispatches": self._c_dispatch.value,
            "boundary_hops": self._c_hops.value,
        }
