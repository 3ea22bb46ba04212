"""KV-cached incremental decoding for GPT-2 (TPU-native inference path).

The reference has no inference machinery at all (its ONNX examples run
full forwards — SURVEY.md §2.4); the round-2 ``generate`` here did the
fixed-window equivalent: one FULL-context forward per emitted token,
O(S²·T) total attention work.  This module is the idiomatic TPU design:

* **prefill** — one causal forward over the (padded) prompt that also
  returns every layer's K/V, written into a preallocated
  ``(L, B, H, ctx, D)`` cache;
* **decode** — a single ``lax.scan`` over new tokens, each step
  attending its one-query block against the cache (masked to the live
  positions) and writing its K/V at the current position with
  ``lax.dynamic_update_slice`` — O(S·D) per token, static shapes, ONE
  compiled executable for the whole generation.  The scan body is
  UNROLLED 4× by default (round 5): XLA schedules 4 sequential token
  steps per loop iteration, which amortizes loop overhead and
  pipelines the weight reads — measured 2633 → 4483 tok/s (+70%) at
  the bench config on the v5e (unroll=8 adds only +3.6% more for 2×
  the compile time).

The math mirrors the layer stack exactly (same fp32-stat LayerNorm,
same tanh-approx gelu, same scale placement), and
``tests/test_gpt2.py`` asserts the cached step's logits equal the full
forward's to tolerance at every position.  Batched (possibly ragged)
prompts decode lockstep in one executable (`jax.vmap` over the row
core — per-row cache writes lower to scatters), with greedy,
temperature, top-k, and top-p (nucleus) sampling.  Plan-sharded models
decode here too (round 4): extract_params lays the weights out per the
Megatron plan and the jitted generation runs SPMD.  MoE models decode
here as well (round 5): per-token top-k expert routing with no capacity
limit — see extract_params.  GQA models (``GPT2Config(n_kv_head=K)``,
round 5) keep their cache at K heads — the head counts are derived
from the weight widths, and the decode step contracts each K/V head
against its query group without materializing a repeat
(``_block_decode``).  Sliding-window models
(``GPT2Config(attn_window=W)``, round 5) decode from an O(W) ROLLING
cache — position p lives in slot p % W — and the int8 cache
(``cache_dtype="int8"``) stores (values, scales) tuples with the
scales folded into the score/prob contractions; all of these compose.

Every walk over the layers here is :func:`_over_layers`: a dense model's
blocks arrive stacked on a layer axis (``extract_params``) and the walk
is one ``lax.scan`` of the layer body; a mixed dense/MoE stack arrives
as a list and unrolls.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged_attention import (NEG_INF,
                                   paged_decode_attn as _paged_decode_attn,
                                   write_rows as _write_rows)
from ..ops.sampling import (filter_logits as _filter_logits,
                            sample as _sample)
from .served import FEATURES, ServedFamily, seg_cat, seg_split


def extract_params(m, dtype=None):
    """Pull the GPT2LMHead weight pytree (raw jax arrays).
    ``dtype`` (e.g. jnp.bfloat16) casts the float weights for inference
    — decode is weight-read-bound, so bf16 weights ≈ double the
    steady-state tokens/sec (measured 803 → 1604 on the v5e at the
    bench config); LayerNorm statistics stay fp32 inside _ln either
    way.

    Plan-sharded models work too (round 4): each weight is device_put
    with its layer's partition spec (Megatron column/row layout), and
    since the decode math is pure jnp, the jitted generation runs SPMD
    — GSPMD inserts the same collectives the training forward uses.

    MoE blocks (round 5): the expert weights come out as stacked
    (E, ...) arrays under ``moe_*`` keys and decode routes each token
    to its top-k experts with NO capacity limit (capacity is a
    static-shape training-efficiency device; at inference every token
    gets its chosen experts).  Token-parity with the windowed sampler
    therefore holds exactly when the windowed forward drops nothing —
    the regime its capacity_factor is tuned for.

    LAYERS: where every block holds the same kinds of weights at the
    same shapes (any dense model; an MoE model whose every block routes)
    and no plan lays them out, ``params["blocks"]`` is ONE dict of
    stacked ``(L, ...)`` arrays, and every function here that walks the
    layers scans one layer body over it (:func:`_over_layers`): a
    36-layer program compiles as one layer does, and on the device it is
    one loop, not 36 layers' worth of separate operations.  The stack is
    a copy of the blocks' weights (the cast to ``dtype`` is one anyway;
    uncast float32 serving holds the model's tensors and the stack).
    Mixed dense/MoE stacks and plan-sharded models keep the list of
    per-layer dicts, and the same functions unroll over it.

    SESSION CACHE (round 5): the extracted (cast, plan-laid-out)
    pytree is cached on the model, keyed by ``dtype``/plan and the
    identity of every state buffer — repeated ``generate``/
    ``generate_beam`` calls on an unchanged model skip the per-call
    re-cast/re-shard (a full weight upload per request under a plan).
    Any state mutation (a training step, ``set_states``,
    ``load_states``) replaces the underlying ``jax.Array`` buffers, so
    the identity signature misses and the cache rebuilds; since round 6
    ``Model.set_states`` additionally DROPS the entry eagerly, so the
    superseded weight copy the entry's strong refs pinned is released
    at swap time, not at the next generate call."""
    bufs = [t_.data for _, t_ in sorted(m.get_states().items())]
    sig = (str(dtype), id(m.plan), tuple(id(b) for b in bufs))
    cache = getattr(m, "_decode_param_cache", None)
    if cache is not None and cache[0] == sig:
        return cache[2]
    t = m.transformer
    blocks = []
    for blk in t.blocks:
        mlp = blk.mlp
        if mlp is None:
            raise RuntimeError("model not initialized: call compile() or "
                               "run one forward first")
        common = dict(
            ln1_s=blk.ln1.scale.data, ln1_b=blk.ln1.bias.data,
            wq=blk.attn.q_proj.W.data, bq=blk.attn.q_proj.b.data,
            wk=blk.attn.k_proj.W.data, bk=blk.attn.k_proj.b.data,
            wv=blk.attn.v_proj.W.data, bv=blk.attn.v_proj.b.data,
            wo=blk.attn.out_proj.W.data, bo=blk.attn.out_proj.b.data,
            ln2_s=blk.ln2.scale.data, ln2_b=blk.ln2.bias.data,
        )
        if hasattr(mlp, "fc1"):
            common.update(w1=mlp.fc1.W.data, b1=mlp.fc1.b.data,
                          w2=mlp.fc2.W.data, b2=mlp.fc2.b.data)
        elif hasattr(mlp, "Wg"):  # MoEFFN expert-routed block
            common.update(
                moe_wg=mlp.Wg.data,
                moe_w1=mlp.W1.data, moe_b1=mlp.b1.data,
                moe_w2=mlp.W2.data, moe_b2=mlp.b2.data)
        else:
            raise ValueError(
                f"KV-cache decode does not recognize MLP type "
                f"{type(mlp).__name__}")
        blocks.append(common)
    head = None if m.cfg.tie_weights else m.lm_head.W.data
    def cast(tree):
        if dtype is None:
            return tree
        return jax.tree.map(
            lambda a: a.astype(dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    params = cast(dict(wte=t.wte.W.data, wpe=t.wpe.W.data,
                       lnf_s=t.ln_f.scale.data, lnf_b=t.ln_f.bias.data,
                       head=head))
    if m.plan is not None:
        params = _shard_params(m, dict(params, blocks=cast(blocks)))
    else:
        params["blocks"] = _stack_blocks(blocks, cast)
    # the strong refs to the keyed buffers make the id() signature
    # sound: while this entry lives, no new array can recycle their ids
    m._decode_param_cache = (sig, bufs, params)
    return params


def _stack_blocks(blocks, cast):
    """The per-layer dicts, ``cast``, as one dict of stacked (L, ...)
    arrays where the layers are alike (same keys, shapes and dtypes);
    else (a mixed dense/MoE stack) the list of them.  A kind of weight
    at a time, so that what is alive beside the stack is one kind's
    casts, not a second copy of all the weights."""
    kinds = {tuple(sorted((k, v.shape, str(v.dtype)) for k, v in b.items()))
             for b in blocks}
    if len(kinds) != 1:
        return cast(blocks)
    return {k: jnp.stack(cast([b[k] for b in blocks])) for k in blocks[0]}


def _n_layers(params):
    blocks = params["blocks"]
    if isinstance(blocks, dict):
        return len(next(iter(blocks.values())))
    return len(blocks)


def _layers(params):
    """The per-layer weight dicts, for code that unrolls the layers
    whatever form ``params["blocks"]`` has."""
    blocks = params["blocks"]
    if isinstance(blocks, dict):
        return [{k: v[i] for k, v in blocks.items()}
                for i in range(_n_layers(params))]
    return blocks


def _over_layers(params, body, carry, xs=None):
    """``carry, y = body(carry, li, p, x)`` for every layer in turn:
    ``p`` the layer's weights, ``x`` the layer's slice of ``xs`` (a
    pytree of (L, ...) arrays, or None), ``li`` its index.  Returns
    ``(carry, ys)``, the ``y`` stacked on a leading layer axis (None
    where ``body`` returns none).  Stacked blocks (``extract_params``)
    run as ONE ``lax.scan`` of the layer body (``li`` traced);
    a list of per-layer dicts unrolls (``li`` a Python int)."""
    blocks = params["blocks"]
    if isinstance(blocks, dict):
        def step(c, lx):
            # what an expert-parallel MoE layer records of its routing
            # belongs to this trace: out with the ys, summed after
            with _ep_collecting() as rec:
                c, y = body(c, *lx)
            return c, (y, _ep_lane_stats(rec, True))

        carry, (ys, stats) = jax.lax.scan(
            step, carry, (jnp.arange(_n_layers(params)), blocks, xs))
        _ep_record_lanes(stats)
        return carry, ys
    ys = []
    for li, p in enumerate(blocks):
        carry, y = body(carry, li, p,
                        jax.tree.map(lambda a: a[li], xs))
        ys.append(y)
    if ys[0] is None:
        return carry, None
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)


def _shard_params(m, params):
    """Lay the extracted weights out per the model's sharding plan so
    the jitted decode runs SPMD over the mesh (weights loaded via
    set_states may sit unsharded on one device otherwise).  Spec
    resolution delegates to ShardingPlan.spec_for_state — the full
    three-tier rule (partition_spec attr, then the plan's regex rules
    by state name, then replicated), not just the attr."""
    plan = m.plan
    t = m.transformer
    names = {id(v): k for k, v in m.get_states().items()}

    def put(arr, owner):
        spec = plan.spec_for_state(names.get(id(owner), ""), owner)
        return jax.device_put(arr, plan.sharding(spec))

    out = dict(params)
    out["wte"] = put(params["wte"], t.wte.W)
    out["wpe"] = put(params["wpe"], t.wpe.W)
    out["lnf_s"] = put(params["lnf_s"], t.ln_f.scale)
    out["lnf_b"] = put(params["lnf_b"], t.ln_f.bias)
    if params["head"] is not None:
        out["head"] = put(params["head"], m.lm_head.W)
    new_blocks = []
    for blk, p in zip(t.blocks, params["blocks"]):
        owners = dict(
            ln1_s=blk.ln1.scale, ln1_b=blk.ln1.bias,
            wq=blk.attn.q_proj.W, bq=blk.attn.q_proj.b,
            wk=blk.attn.k_proj.W, bk=blk.attn.k_proj.b,
            wv=blk.attn.v_proj.W, bv=blk.attn.v_proj.b,
            wo=blk.attn.out_proj.W, bo=blk.attn.out_proj.b,
            ln2_s=blk.ln2.scale, ln2_b=blk.ln2.bias)
        if hasattr(blk.mlp, "fc1"):
            owners.update(w1=blk.mlp.fc1.W, b1=blk.mlp.fc1.b,
                          w2=blk.mlp.fc2.W, b2=blk.mlp.fc2.b)
        else:  # MoEFFN: expert weights carry P(EXPERT, ...) specs
            owners.update(moe_wg=blk.mlp.Wg,
                          moe_w1=blk.mlp.W1, moe_b1=blk.mlp.b1,
                          moe_w2=blk.mlp.W2, moe_b2=blk.mlp.b2)
        new_blocks.append({k: put(v, owners[k]) for k, v in p.items()})
    out["blocks"] = new_blocks
    return out


def _ln(x, s, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * s + b).astype(x.dtype)


# -- tensor-parallel threading (serve/tp.py) ---------------------------------
# Every block function below takes ``tp_axis``/``tp_world`` kwargs
# (default None/1).  Unset, each expression is LITERALLY the pre-TP
# one — ``(a @ wo) + bo`` with no reduction reordered — so the
# single-device paths stay bit-identical.  Set (inside a shard_map
# over a ``tp`` mesh axis), the attention/MLP weights arrive COLUMN/
# ROW-sharded Megatron-style (parallel/tensor_parallel.py's layout,
# specs from ``decode_param_specs``): q/k/v/fc1 are column-local (the
# per-shard head/column slice needs no communication), and the two
# row-parallel products — attention out-proj and MLP fc2 — each close
# with ONE psum here, bias added AFTER the reduction (added per shard
# it would be multiplied by the world size).

def _tp_psum(y, axis, world):
    """All-reduce a row-parallel partial product over the ``axis``
    mesh axis; ``axis=None`` returns ``y`` untouched (the serial
    path).  The collective is recorded through the communicator's
    observe hook at trace time — op, payload bytes, axis name, and
    mesh size — so TP-serve psums are attributable in Chrome traces
    next to the training collectives."""
    if axis is None:
        return y
    from ..parallel.communicator import _record_collective

    _record_collective("psum", [y], axis=axis, world=world)
    return jax.lax.psum(y, axis)


# -- int8 KV cache (round 5) ------------------------------------------------
# The GQA measurement (PERF.md §8) showed decode tokens/sec scales
# near-linearly with cache BYTES — so halving bytes/element is the same
# lever: the cache stores (int8 values, one f32 scale per (token, head)
# row over D), cutting cache traffic ~2× vs bf16.  XLA fuses the
# dequantize into the score/value einsums, so HBM sees int8 + scales
# only.  A quantized cache is a (values, scales) tuple everywhere a
# dense cache is an array; the helpers below keep every decode path
# shape-agnostic between the two.

def _quantize_kv(x):
    """(…, D) float -> ((…, D) int8, (…) f32 scale), symmetric per-row."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.round(xf / scale[..., None]).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _cache_layer(c, li):
    """Layer li of a stacked cache (dense array or (values, scales))."""
    return (c[0][li], c[1][li]) if isinstance(c, tuple) else c[li]


def _cache_stack(layers):
    if isinstance(layers[0], tuple):
        return (jnp.stack([l[0] for l in layers]),
                jnp.stack([l[1] for l in layers]))
    return jnp.stack(layers)


def _attn_full(q, k, v, n_head, start=None, window=None, tp_world=1):
    """Causal attention over the full (B, S, E) prefill block.
    ``start``: optional (B,) first-live window position per row
    (left-padded batch) — keys before it are masked out.  GQA models
    arrive with k/v narrower than q (n_kv_head·D wide — the head count
    is derived from the widths, never threaded); each K/V head is
    broadcast over its query-head group, matching the training stack's
    RepeatKV (parallel/tensor_parallel.py ParallelMHA).  ``window``:
    sliding-window band (query i sees keys [i-window+1, i]), matching
    the training stack's banded _sdpa.  ``tp_world`` > 1: q/k/v carry
    only this shard's heads (1/tp_world of the widths) — attention is
    head-local, so the per-shard math below is exactly the serial
    math on the head slice."""
    b, s, e = q.shape
    d = (e * tp_world) // n_head
    n_local = n_head // tp_world
    n_kv = k.shape[-1] // d

    def heads(t, nh):
        return t.reshape(b, s, nh, d).transpose(0, 2, 1, 3)

    qh, kh, vh = heads(q, n_local), heads(k, n_kv), heads(v, n_kv)
    if n_kv != n_local:
        kh = jnp.repeat(kh, n_local // n_kv, axis=1)
        vh = jnp.repeat(vh, n_local // n_kv, axis=1)
    sc = jnp.einsum("bhsd,bhtd->bhst", qh, kh) / math.sqrt(d)
    cm = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        cm = cm & (i - j < window)
    cm = cm[None, None]
    if start is not None:
        live = jnp.arange(s)[None, :] >= start[:, None]  # (B, S) keys
        cm = cm & live[:, None, None, :]
        # fully-masked pad-query rows degrade to uniform attention over
        # NEG_INF scores (finite garbage, never read) — NEG_INF is -1e30,
        # not -inf, so no NaNs propagate
    sc = jnp.where(cm, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhst,bhtd->bhsd", p, vh)
    return o.transpose(0, 2, 1, 3).reshape(b, s, e)


def _block_prefill(x, p, n_head, eps, start=None, moe_top_k=2,
                   window=None, tp_axis=None, tp_world=1, ep=None):
    h = _ln(x, p["ln1_s"], p["ln1_b"], eps)
    q = h @ p["wq"] + p["bq"]
    k = h @ p["wk"] + p["bk"]
    v = h @ p["wv"] + p["bv"]
    a = _attn_full(q, k, v, n_head, start=start, window=window,
                   tp_world=tp_world)
    x = x + (_tp_psum(a @ p["wo"], tp_axis, tp_world) + p["bo"])
    h = _ln(x, p["ln2_s"], p["ln2_b"], eps)
    x = x + _mlp(h, p, moe_top_k, tp_axis=tp_axis, tp_world=tp_world,
                 ep=ep)
    return x, k, v


def _block_decode(x, p, k_cache, v_cache, pos, n_head, eps, start=None,
                  moe_top_k=2, window=None, tp_axis=None, tp_world=1,
                  ep=None):
    """x: (B, 1, E); k/v_cache: (B, H_kv, ctx, D) with this step's K/V
    already written at ``pos``.  Attends to positions <= pos (and
    >= ``start`` per row for left-padded batches).

    GQA (H_kv < n_head): the cache stays at H_kv heads — THE point of
    GQA at decode, n_head/H_kv× less cache traffic per token on a
    cache-read-bound loop — and the query block reshapes to
    (B, H_kv, G, D) so each K/V head serves its G-query group in one
    grouped einsum (no repeat materialized).  H_kv == n_head makes
    G=1 and this is exactly the ungrouped math.

    int8 caches arrive as (values, scales) tuples: reads dequantize
    into the einsums (XLA fuses — HBM traffic stays int8), writes
    quantize this step's K/V row.

    ``window`` (static): ROLLING cache of exactly ``window`` slots —
    position pos lives in slot pos % window, so each write overwrites
    the slot that just fell out of the band, and the live mask
    reconstructs each slot's position from (pos, slot index) with no
    extra state.  O(window) cache reads per token regardless of how
    long the generation runs."""
    quant = isinstance(k_cache, tuple)
    kq = k_cache[0] if quant else k_cache
    b, _, e = x.shape
    d = e // n_head
    n_kv = kq.shape[1]          # LOCAL kv heads (H_kv / tp_world)
    g = n_head // (n_kv * tp_world)
    ctx = kq.shape[2]
    if window is not None:
        assert ctx == window, (
            f"rolling cache dim {ctx} != window {window}")
        slot = pos % window
    else:
        slot = pos
    h = _ln(x, p["ln1_s"], p["ln1_b"], eps)
    q = (h @ p["wq"] + p["bq"]).reshape(b, n_kv, g, d)
    k_new = (h @ p["wk"] + p["bk"]).reshape(b, n_kv, 1, d)
    v_new = (h @ p["wv"] + p["bv"]).reshape(b, n_kv, 1, d)
    if quant:
        # scale-FOLDED quantized attention: contract against the raw
        # int8 arrays (the convert rides the einsum operand; no
        # dequantized cache is materialized) and apply the per-token
        # scales outside the contractions —
        #   scores[t] = (q · k8[t]) · kscale[t];
        #   out = Σ_t (p[t]·vscale[t]) · v8[t]
        (kqv, ksc), (vqv, vsc) = k_cache, v_cache
        k8, k8s = _quantize_kv(k_new)
        v8, v8s = _quantize_kv(v_new)
        kqv = jax.lax.dynamic_update_slice(kqv, k8, (0, 0, slot, 0))
        ksc = jax.lax.dynamic_update_slice(ksc, k8s, (0, 0, slot))
        vqv = jax.lax.dynamic_update_slice(vqv, v8, (0, 0, slot, 0))
        vsc = jax.lax.dynamic_update_slice(vsc, v8s, (0, 0, slot))
        k_cache, v_cache = (kqv, ksc), (vqv, vsc)
        sc = jnp.einsum("bkgd,bktd->bkgt", q, kqv.astype(x.dtype))
        sc = sc * ksc[:, :, None, :].astype(sc.dtype) / math.sqrt(d)
    else:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k_new,
                                               (0, 0, slot, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v_new,
                                               (0, 0, slot, 0))
        sc = jnp.einsum("bkgd,bktd->bkgt", q, k_cache) / math.sqrt(d)
    if window is not None:
        # slot s currently holds position pos - ((pos - s) mod window)
        # (<= pos, within the band by construction; negative = never
        # written)
        p_slot = pos - ((pos - jnp.arange(ctx)) % window)
        live = (p_slot >= 0)[None, None, None, :]
        if start is not None:
            live = live & (p_slot[None, None, None, :]
                           >= start[:, None, None, None])
    else:
        live = jnp.arange(ctx)[None, None, None, :] <= pos
        if start is not None:
            live = live & (jnp.arange(ctx)[None, None, None, :]
                           >= start[:, None, None, None])
    sc = jnp.where(live, sc, NEG_INF)
    p_attn = jax.nn.softmax(sc, axis=-1)
    if quant:
        pv = p_attn * vsc[:, :, None, :].astype(p_attn.dtype)
        a = jnp.einsum("bkgt,bktd->bkgd", pv, vqv.astype(x.dtype))
    else:
        a = jnp.einsum("bkgt,bktd->bkgd", p_attn, v_cache)
    # (B, H_kv, G, D) in head-major order == (B, 1, E) concat of heads
    # (this shard's slice of it when tp_world > 1)
    a = a.reshape(b, 1, e // tp_world)
    x = x + (_tp_psum(a @ p["wo"], tp_axis, tp_world) + p["bo"])
    h = _ln(x, p["ln2_s"], p["ln2_b"], eps)
    x = x + _mlp(h, p, moe_top_k, tp_axis=tp_axis, tp_world=tp_world,
                 ep=ep)
    return x, k_cache, v_cache


def _moe_weights(probs, top_k):
    """Per-token combine weights (…, E) from router softmax ``probs``
    (f32), zeros except the top-k experts.  Mirrors parallel/moe.py's
    gating exactly in the no-drop regime: top-1 keeps the RAW chosen
    prob (Switch); top-2 renormalizes the two gates to sum 1
    (GShard)."""
    if top_k not in (1, 2):
        raise ValueError("moe_top_k must be 1 (Switch) or 2 (GShard), "
                         f"got {top_k}")
    e = probs.shape[-1]
    m1 = jax.nn.one_hot(jnp.argmax(probs, axis=-1), e,
                        dtype=probs.dtype)
    g1 = jnp.sum(probs * m1, axis=-1)
    if top_k == 1:
        return m1 * g1[..., None]
    p2 = probs * (1.0 - m1)
    m2 = jax.nn.one_hot(jnp.argmax(p2, axis=-1), e, dtype=probs.dtype)
    g2 = jnp.sum(p2 * m2, axis=-1)
    den = g1 + g2
    den = jnp.where(den <= 0.0, 1.0, den)
    return (m1 * (g1 / den)[..., None] + m2 * (g2 / den)[..., None])


def _moe_ffn(h, p, top_k):
    """Capacity-free MoE FFN for decode: route each of the (B, S, D)
    post-LN tokens to its top-k experts and mask-and-sum over a python
    loop of per-expert GEMMs (E dense MLPs — each big enough for the
    MXU; memory stays O(B·S·F), not O(B·S·E·F)).  No capacity limit:
    see extract_params."""
    probs = jax.nn.softmax(
        (h @ p["moe_wg"].astype(h.dtype)).astype(jnp.float32), axis=-1)
    w = _moe_weights(probs, top_k).astype(h.dtype)          # (B, S, E)
    y = jnp.zeros_like(h)
    for e in range(p["moe_w1"].shape[0]):
        he = jax.nn.gelu(h @ p["moe_w1"][e] + p["moe_b1"][e])
        y = y + w[..., e:e + 1] * (he @ p["moe_w2"][e] + p["moe_b2"][e])
    return y


# -- expert-parallel MoE FFN (serve/ep.py) -----------------------------------
# The serve EP backend runs every dispatch under a shard_map over a
# 2-D (ep, tp) mesh with the stacked expert weights sharded on their
# leading axis.  The FFN below is the GShard formulation restated for
# replicated decode activations: routing + capacity run identically on
# every rank (probs are replicated), each rank computes only its
# RESIDENT experts' contributions through the capacity-shaped
# dispatch/combine one-hots (parallel/moe.py's — the training layer's
# routing math, reused verbatim), and ONE psum over the ep axis sums
# each token's top-k expert outputs — the degenerate all-to-all for
# replicated tokens (the dispatch half is free because every rank
# already holds every token; only the combine reduces).
#
# Exactness: with ``cap_factor=None`` the capacity is the token count —
# nothing ever drops, and per-token outputs equal `_moe_ffn`'s exactly
# up to float summation order (the ep psum — the same near-tie caveat
# as the TP psum).  A FINITE cap_factor is the GShard capacity mode:
# per-dispatch token groups bound each expert's buffer, over-capacity
# assignments are DROPPED — their combine weight is zero, so the
# block's residual path carries the token (never a zeroed hidden
# state) — and the drop pattern couples tokens within a dispatch
# (which is why the engine refuses a finite cap_factor next to the
# prefix cache: chunked and full prefill route different groups, so
# chunk KV would stop being canonical).  Pad lanes of a prefill
# dispatch route like real tokens and consume capacity — deterministic
# but part of the group, documented in docs/SERVING.md.
#
# Observability rides a TRACE-TIME collector: while an ep.py twin body
# is being traced, every `_moe_ffn_ep` application appends its
# (tokens-per-expert, dropped) arrays, and the twin wrapper folds them
# into two extra replicated outputs (`serve.ep.expert_tokens{expert=}`
# / the dropped-token counter).  One thread-local stack — the wrapper
# consumes the tracers inside the same trace that made them.

_EP_COLLECT = __import__("threading").local()


class _ep_collecting:
    """Context manager arming the EP-stats collector for one row/body
    trace; yields the list `_moe_ffn_ep` appends (counts, dropped)
    tracer pairs to."""

    def __enter__(self):
        stack = getattr(_EP_COLLECT, "stack", None)
        if stack is None:
            stack = _EP_COLLECT.stack = []
        self._rec = []
        stack.append(self._rec)
        return self._rec

    def __exit__(self, *exc):
        _EP_COLLECT.stack.pop()
        return False


def _ep_record(counts, dropped):
    stack = getattr(_EP_COLLECT, "stack", None)
    if stack:
        stack[-1].append((counts, dropped))


def _ep_lane_stats(rec, live):
    """What one lane's trace collected under :class:`_ep_collecting`,
    summed over its MoE layers and zeroed for a dead lane (it runs
    clamped garbage through the router and must not pollute the load
    counters); ``()`` where nothing routed under ``ep``.  A lane is
    traced under ``jax.vmap``: its tracers must leave as outputs of the
    lane, and :func:`_ep_record_lanes` hands their sum on."""
    if not rec:
        return ()
    return (jnp.where(live, sum(c for c, _ in rec), 0),
            jnp.where(live, sum(d for _, d in rec), 0))


def _ep_record_lanes(stats):
    """Record the lanes' :func:`_ep_lane_stats` (stacked by the vmap)
    with the collector around the whole program."""
    if stats:
        _ep_record(stats[0].sum(0), stats[1].sum())


def _moe_ffn_ep(h, p, top_k, ep):
    """Expert-parallel MoE FFN: ``ep = (axis, world, cap_factor)`` —
    the mesh axis the stacked expert weights shard over, its size, and
    the GShard capacity factor (None = capacity == tokens, drop-free).
    ``p['moe_w1']``&co arrive as this rank's (E/world, ...) slices
    under shard_map; ``moe_wg`` is replicated."""
    from ..parallel import moe as _moe

    axis, world, cap_factor = ep
    b, s, dm = h.shape
    n = b * s
    e = p["moe_wg"].shape[-1]
    probs = jax.nn.softmax(
        (h @ p["moe_wg"].astype(h.dtype)).astype(jnp.float32),
        axis=-1).reshape(n, e)
    cap = (n if cap_factor is None
           else max(1, int(math.ceil(top_k * n / e * cap_factor))))
    if top_k == 2:
        dispatch, combine, _ = _moe._top2_dispatch(probs, cap)
    elif top_k == 1:
        dispatch, combine, _ = _moe._top1_dispatch(probs, cap)
    else:
        raise ValueError("moe_top_k must be 1 (Switch) or 2 (GShard), "
                         f"got {top_k}")
    _ep_record(*_moe.dispatch_load(dispatch, top_k))
    rank = jax.lax.axis_index(axis)
    e_loc = e // world
    d_l = jax.lax.dynamic_slice_in_dim(
        dispatch, rank * e_loc, e_loc, axis=1).astype(h.dtype)
    c_l = jax.lax.dynamic_slice_in_dim(
        combine, rank * e_loc, e_loc, axis=1).astype(h.dtype)
    ht = h.reshape(n, dm)
    # dispatch: tokens -> this rank's (E_loc, C, D) expert buffers
    xin = jnp.einsum("nec,nd->ecd", d_l, ht)
    hh = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin, p["moe_w1"])
                     + p["moe_b1"][:, None, :])
    out = jnp.einsum("ecf,efd->ecd", hh, p["moe_w2"]) \
        + p["moe_b2"][:, None, :]
    # combine locally (non-resident experts weight zero on this rank),
    # then ONE psum over ep sums each token's top-k contributions —
    # recorded through the communicator hook like the TP psums
    y = jnp.einsum("nec,ecd->nd", c_l, out)
    return _tp_psum(y, axis, world).reshape(b, s, dm)


def _mlp(h, p, moe_top_k, tp_axis=None, tp_world=1, ep=None):
    """The block's feed-forward: dense two-layer gelu MLP, or the
    expert-routed MoE when the block carries ``moe_*`` weights.  Under
    ``tp_axis`` the dense path is column-fc1 / row-fc2 with ONE psum
    (Megatron); MoE blocks shard over the EXPERT axis instead —
    ``ep = (axis, world, cap_factor)`` threads the serve EP backend's
    mesh through (singa_tpu/serve/ep.py), and an MoE block under
    ``tp_axis`` WITHOUT an ep axis is rejected with a pointer at the
    ``serve(ep=)`` path."""
    if "moe_wg" in p:
        if ep is not None:
            return _moe_ffn_ep(h, p, moe_top_k, ep)
        if tp_axis is not None:
            raise NotImplementedError(
                "MoE blocks are not tensor-parallel: expert weights "
                "shard over the expert axis — serve this model with "
                "model.serve(ep=EPConfig(ep=, tp=)) "
                "(singa_tpu/serve/ep.py)")
        return _moe_ffn(h, p, moe_top_k)
    return _tp_psum(jax.nn.gelu(h @ p["w1"] + p["b1"]) @ p["w2"],
                    tp_axis, tp_world) + p["b2"]


def _logits(x, params):
    head = params["head"]
    if head is None:
        return x @ params["wte"].T
    return x @ head


def prefill(params, ids, n_head, eps, start=None, moe_top_k=2,
            quant_cache=False, window=None, prompt_end=None,
            rolling=True, tp_axis=None, tp_world=1, ep=None):
    """ids: (B, Sp) int32 (padded prompt).  Returns (hidden, k_caches,
    v_caches): hidden is the final-LN (B, Sp, E) — the caller picks the
    rows it needs BEFORE the vocab matmul (materializing (Sp, V) logits
    for all pad positions would double prefill cost) — and caches are
    (L, B, H, Sp, D); pad positions hold garbage K/V that decode never
    attends to (mask is position-indexed).

    ``start`` (B,): LEFT-padded batch — row i's prompt occupies window
    positions [start_i, Sp_shared).  Row-relative position embeddings
    (window pos − start_i, clipped for pads) and a per-row key mask make
    the math identical to a right-padded row shifted by start_i, which
    is what puts RAGGED batches on the shared-position fast path."""
    b, sp = ids.shape
    if start is None:
        # (1, Sp) gather broadcasts in the add — one wpe read, not B
        pos = jnp.arange(sp, dtype=jnp.int32)[None, :]
    else:
        pos = jnp.clip(jnp.arange(sp, dtype=jnp.int32)[None, :]
                       - start[:, None], 0, None)
    x = jnp.take(params["wte"], ids, axis=0) + \
        jnp.take(params["wpe"], pos, axis=0)
    roll = None
    if window is not None and window < sp and rolling:
        # ROLLING cache (sliding window): slot w <- the last prompt
        # position p < prompt_end with p ≡ w (mod window); decode
        # writes position pos into slot pos % window, so the slot
        # mapping must be position-mod from the start.  Gathering by
        # prompt_end (not the padded width sp) keeps right-pad
        # garbage from overwriting real prompt K/V in its slot.
        # ``rolling=False`` keeps the banded attention mask but a
        # LINEAR position-indexed cache — the paged serve engine's
        # windowed mode (block tables address positions directly and
        # drop out-of-window blocks; the roll would scramble its
        # block arithmetic).  The K/V VALUES are identical either
        # way: the roll is a pure reorder after they are computed.
        pe_ = (sp if prompt_end is None else prompt_end) - 1
        w = jnp.arange(window)
        roll = jnp.clip(pe_ - ((pe_ - w) % window), 0, sp - 1)
    def layer(x, li, p, _):
        x, k, v = _block_prefill(x, p, n_head, eps, start=start,
                                 moe_top_k=moe_top_k, window=window,
                                 tp_axis=tp_axis, tp_world=tp_world,
                                 ep=ep)
        e = x.shape[-1]
        d = e // n_head
        n_kv = k.shape[-1] // d  # GQA caches hold n_kv_head heads
        kh = k.reshape(b, sp, n_kv, d).transpose(0, 2, 1, 3)
        vh = v.reshape(b, sp, n_kv, d).transpose(0, 2, 1, 3)
        if roll is not None:
            kh = jnp.take(kh, roll, axis=2)
            vh = jnp.take(vh, roll, axis=2)
        if quant_cache:
            kh, vh = _quantize_kv(kh), _quantize_kv(vh)
        return x, (kh, vh)

    x, (kc, vc) = _over_layers(params, layer, x)
    x = _ln(x, params["lnf_s"], params["lnf_b"], eps)
    return x, kc, vc


def _advance_one(params, x, kc, vc, pos, n_head, eps, start=None,
                 moe_top_k=2, window=None, tp_axis=None, tp_world=1,
                 ep=None):
    """Advance one decode step through every block: x (B, 1, E) at
    position ``pos`` against caches (L, B, H, ctx, D).  Returns
    ((B, V) logits, new kc, new vc).  Shared by sampling
    (_generate_row), the left-padded ragged path, and beam search so
    the paths cannot drift."""
    def layer(x, li, p, caches):
        x, kl, vl = _block_decode(x, p, *caches, pos, n_head, eps,
                                  start=start, moe_top_k=moe_top_k,
                                  window=window, tp_axis=tp_axis,
                                  tp_world=tp_world, ep=ep)
        return x, (kl, vl)

    x, (kc, vc) = _over_layers(params, layer, x, (kc, vc))
    x = _ln(x, params["lnf_s"], params["lnf_b"], eps)
    return _logits(x, params)[:, 0], kc, vc


def decode_step(params, x, kc, vc, pos, n_head, eps, *, start=None,
                moe_top_k=2, window=None, tp_axis=None, tp_world=1,
                ep=None):
    """PUBLIC single-step decode core with an EXTERNALIZED cache carry
    (the serve engine's contract; round 6).  The generation loops in
    this module own their KV cache inside a ``lax.scan`` carry; an
    iteration-level scheduler (singa_tpu/serve) instead owns the cache
    arena across steps and calls this once per engine iteration.

    ``x``: (B, 1, E) embedded inputs at position ``pos`` (traced
    scalar, or per-row under vmap); ``kc``/``vc``: (L, B, H_kv, ctx, D)
    caches — this step's K/V rows are written at ``pos`` and the new
    caches RETURNED (functional carry; the caller rebinds).  Returns
    ``((B, V) logits, new kc, new vc)``.  Exactly the math every
    sampling/beam/speculative path here uses (_advance_one), so an
    external cache owner cannot drift from ``generate``.

    ``tp_axis``/``tp_world`` (serve/tp.py): inside a shard_map over a
    ``tp`` mesh axis with Megatron-sharded params and head-sharded
    caches, the step runs one psum per attention output and per MLP
    fc2 and returns replicated logits.  Defaults leave the serial
    math bit-identical."""
    return _advance_one(params, x, kc, vc, pos, n_head, eps,
                        start=start, moe_top_k=moe_top_k, window=window,
                        tp_axis=tp_axis, tp_world=tp_world, ep=ep)


def _chunk_attend(q, k_new, v_new, k_cache, v_cache, pos, window, dtype):
    """ONE sequence's part of :func:`_block_chunk`: the K new K/V rows
    (B, kv, K, d) written into its cache at ``pos`` and its K queries
    (B, kv, g, K, d) attended against the cache.  Returns ((B, kv, g, K,
    d), k_cache, v_cache)."""
    quant = isinstance(k_cache, tuple)
    klen, d = q.shape[3:]
    if quant:
        (kqv, ksc), (vqv, vsc) = k_cache, v_cache
        k8, k8s = _quantize_kv(k_new)
        v8, v8s = _quantize_kv(v_new)
        kqv = jax.lax.dynamic_update_slice(kqv, k8, (0, 0, pos, 0))
        ksc = jax.lax.dynamic_update_slice(ksc, k8s, (0, 0, pos))
        vqv = jax.lax.dynamic_update_slice(vqv, v8, (0, 0, pos, 0))
        vsc = jax.lax.dynamic_update_slice(vsc, v8s, (0, 0, pos))
        k_cache, v_cache = (kqv, ksc), (vqv, vsc)
        sc = jnp.einsum("bkgqd,bktd->bkgqt", q, kqv.astype(dtype))
        sc = sc * ksc[:, :, None, None, :].astype(sc.dtype) \
            / math.sqrt(d)
    else:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k_new,
                                               (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v_new,
                                               (0, 0, pos, 0))
        sc = jnp.einsum("bkgqd,bktd->bkgqt", q, k_cache) \
            / math.sqrt(d)
    ctx = sc.shape[-1]
    live = (jnp.arange(ctx)[None, :]
            <= (pos + jnp.arange(klen))[:, None])       # (K, ctx)
    if window is not None:
        live = live & (jnp.arange(ctx)[None, :]
                       > (pos + jnp.arange(klen))[:, None] - window)
    sc = jnp.where(live[None, None, None], sc, NEG_INF)
    p_attn = jax.nn.softmax(sc, axis=-1)
    if quant:
        pv = p_attn * vsc[:, :, None, None, :].astype(p_attn.dtype)
        a = jnp.einsum("bkgqt,bktd->bkgqd", pv, vqv.astype(dtype))
    else:
        a = jnp.einsum("bkgqt,bktd->bkgqd", p_attn, v_cache)
    return a, k_cache, v_cache


def _block_chunk(x, p, k_cache, v_cache, pos, n_head, eps,
                 moe_top_k=2, window=None, tp_axis=None, tp_world=1,
                 ep=None, segs=None):
    """Chunked cache advance: x (B, K, E) are K consecutive tokens at
    positions pos..pos+K-1.  Writes all K K/V rows in one contiguous
    dynamic_update_slice and attends the K queries against the cache
    with a per-query position mask (query i sees positions
    <= pos + i).  The speculative verify step: ONE cache read serves
    K token positions, which is where the speedup over K sequential
    decode steps comes from on a cache-read-bound loop.  Dense or
    int8 caches; GQA via the same grouped layout as _block_decode.
    ``window``: sliding-window band — query i additionally masks
    positions <= pos + i - window (LINEAR cache, the paged serve
    engine's windowed chunk prefill; the rolling-cache decode path
    is _block_decode's).

    ``segs`` (models/served.py ``Segment``s): the K tokens are the
    segments' laid end to end, ``k_cache`` / ``v_cache`` / ``pos`` a
    list with an entry a segment; the projections and the feed-forward
    take them together and each attends its own cache."""
    if segs is None:
        caches = [(k_cache, v_cache, pos)]
        split = lambda t, axis: [t]
    else:
        caches = list(zip(k_cache, v_cache, pos))
        split = lambda t, axis: seg_split(t, segs, axis)
    kq0 = caches[0][0]
    kq0 = kq0[0] if isinstance(kq0, tuple) else kq0
    b, klen, e = x.shape
    d = e // n_head
    n_kv = kq0.shape[1]         # LOCAL kv heads (H_kv / tp_world)
    g = n_head // (n_kv * tp_world)
    h = _ln(x, p["ln1_s"], p["ln1_b"], eps)
    q = (h @ p["wq"] + p["bq"]).reshape(b, klen, n_kv, g, d) \
        .transpose(0, 2, 3, 1, 4)                       # (B,kv,g,K,d)
    k_new = (h @ p["wk"] + p["bk"]).reshape(b, klen, n_kv, d) \
        .transpose(0, 2, 1, 3)                          # (B,kv,K,d)
    v_new = (h @ p["wv"] + p["bv"]).reshape(b, klen, n_kv, d) \
        .transpose(0, 2, 1, 3)
    a, k_cache, v_cache = zip(*(
        _chunk_attend(q_s, k_s, v_s, kc, vc, at, window, x.dtype)
        for q_s, k_s, v_s, (kc, vc, at) in zip(
            split(q, 3), split(k_new, 2), split(v_new, 2), caches)))
    a = seg_cat(a, 3)
    a = a.transpose(0, 3, 1, 2, 4).reshape(b, klen, e // tp_world)
    x = x + (_tp_psum(a @ p["wo"], tp_axis, tp_world) + p["bo"])
    h = _ln(x, p["ln2_s"], p["ln2_b"], eps)
    x = x + _mlp(h, p, moe_top_k, tp_axis=tp_axis, tp_world=tp_world,
                 ep=ep)
    if segs is None:
        return x, k_cache[0], v_cache[0]
    return x, list(k_cache), list(v_cache)


def prefill_chunk(params, x, kc, vc, pos, n_head, eps, *, moe_top_k=2,
                  window=None, tp_axis=None, tp_world=1, ep=None,
                  segs=None):
    """PUBLIC offset-prefill entry (the prefix cache's contract;
    serve.prefix round).  Advance every layer by a K-token chunk —
    ``x``: (B, K, E) embedded inputs at positions ``pos..pos+K-1``
    (``pos`` traced) against caches (L, B, H_kv, ctx, D) that already
    hold K/V for positions < ``pos``.  Writes the chunk's K/V rows at
    ``pos`` and returns ``((B, K, E) final-LN hidden, new kc, new vc)``
    — hidden, NOT logits, so a caller prefilling from a cached-prefix
    divergence boundary projects only the row it samples from instead
    of paying a (K, V) vocab matmul per chunk.

    Exactness: on this backend a chunked advance over [pos, pos+K) on
    top of full-prefill K/V produces K/V and hidden rows BITWISE equal
    to the full ``prefill`` of the same row (every op is row-independent
    over the position axis with identical per-row reduction structure;
    pinned by tests/test_prefix.py) — which is what lets the serve
    engine's warm-prefix admissions emit byte-identical token streams
    to cold prefill.  The paged serve arena (serve/paged.py) leans on
    the same guarantee for its zero-copy donation path: a retiring
    slot's prompt blocks hold prefill/chunk output, so the radix tree
    adopts them in place.  NOTE the guarantee is about DENSE rows:
    with a quantized (int8) cache this function is self-consistent —
    the same chunk over the same quantized cache reproduces itself
    bitwise — but the hidden states attend DEQUANTIZED keys where the
    full ``prefill``'s attend float ones, which is why int8 engines
    with a prefix cache route every admission (cold included) through
    the chunked path (engine._admit)."""
    def layer(x, li, p, caches):
        x, kl, vl = _block_chunk(x, p, *caches, pos, n_head, eps,
                                 moe_top_k=moe_top_k, window=window,
                                 tp_axis=tp_axis, tp_world=tp_world,
                                 ep=ep, segs=segs)
        return x, (kl, vl)

    x, (kc, vc) = _over_layers(params, layer, x, (kc, vc))
    x = _ln(x, params["lnf_s"], params["lnf_b"], eps)
    return x, kc, vc


def _advance_chunk(params, x, kc, vc, pos, n_head, eps, moe_top_k=2,
                   tp_axis=None, tp_world=1, ep=None):
    """Advance every block by a K-token chunk (x: (B, K, E) embedded
    inputs at positions pos..pos+K-1).  Returns ((B, K, V) logits,
    new kc, new vc).  The speculative verify step — routed through
    :func:`prefill_chunk` so the chunked cache math exists once."""
    x, kc, vc = prefill_chunk(params, x, kc, vc, pos, n_head, eps,
                              moe_top_k=moe_top_k, tp_axis=tp_axis,
                              tp_world=tp_world, ep=ep)
    return _logits(x, params), kc, vc


# -- block-native paged decode attention -------------------------------------
# The serve engine's paged pool steps (serve/paged.py) never lay a
# slot's blocks out as a (max_len)-wide row: the kernel below computes
# flash-style attention DIRECTLY over the block pool with the
# block table as the index structure: a ``lax.fori_loop`` over the
# slot's live blocks with online-softmax accumulation (running max,
# rescaled partial sums — the FlashAttention recurrence), trash-block
# and beyond-``pos`` lanes masked, the current step's K/V attended as
# one extra lane (it is not in the pool yet).  The workspace drops to
# O(block_size) and the loop runs ``ceil(pos / block)`` iterations, so
# long-context slots stop paying for their own padding.
#
# The lanes of a step go through each layer together — (W, Q, E)
# through the projections and the MLP — and only the attention runs per
# lane (``jax.vmap`` over ``paged_attn``); each layer then writes the
# lanes' new rows into the whole pool it carries (``write_rows``: whole
# blocks read, changed and scattered back at ``pool.at[layer, dst]``).
# No layer is sliced out of the pool and no block leaves the layer that
# wrote it, which is what lets the compiler update the donated pool
# where it lies (ops/paged_attention.py; tests/test_tpu_compile.py).
#
# Parity pins (docs/SERVING.md "Paged KV and preemption"): online
# softmax REORDERS the float reduction, so bitwise equality to the
# row-softmax math (``decode_step`` on a materialized row) is impossible
# by construction — the contract is (a) token streams identical to the
# slot engine / offline oracles away from exact argmax/CDF ties, the
# same caveat TP serving documents for its psum, and (b) per-step
# logits allclose to the row math (tests/test_paged.py pins both,
# plus byte equality of the untouched lanes of every written block —
# the read-modify-write keeps pool bytes round-tripping).  int8
# pools dequantize PER BLOCK inside the accumulator (the same folded
# scale placement as _block_decode: scores scale by kscale outside the
# int8 contraction, probabilities by vscale before the value einsum).

def _mlp_lanes(h, p, moe_top_k, live, tp_axis, tp_world, ep):
    """The feed-forward of lane-batched activations ``h`` (W, Q, E).
    Dense and capacity-free MoE blocks treat every token alone, so the
    batch goes through as one.  Under expert parallelism a dispatch's
    tokens share the experts' capacity, and a lane's chunk is the
    routing group it has always been: each lane routes on its own, and
    a dead lane's garbage stays out of the load counters."""
    if ep is None or "moe_wg" not in p:
        return _mlp(h, p, moe_top_k, tp_axis=tp_axis, tp_world=tp_world,
                    ep=ep)

    def lane(h_r, live_r):
        with _ep_collecting() as rec:
            y = _moe_ffn_ep(h_r[None], p, moe_top_k, ep)[0]
        return y, _ep_lane_stats(rec, live_r)

    y, stats = jax.vmap(lane)(h, live)
    _ep_record_lanes(stats)
    return y


def _block_paged(x, pool_k, pool_v, p, li, tables, pos, live, n_blk,
                 n_head, eps, block, trash, moe_top_k=2, window=None,
                 blk_lo=None, tp_axis=None, tp_world=1, ep=None):
    """Layer ``li``'s block-native step for every lane: x (W, Q, E) at
    positions ``pos[w]..pos[w]+Q-1`` (Q = 1: a decode step; Q = K: a
    speculative verify chunk), the whole pools ((L, N+1, B, H_kv·D)
    dense or (values, scales)), ``tables`` (W, W//B) the lanes'
    trash-padded block tables.  Pool lanes < ``pos`` are visible to
    every query; the chunk's own keys are causal within the chunk — the
    same mask structure _block_chunk applies to its materialized row.
    The attention never materializes a row: O(block_size) workspace,
    ``n_blk`` loop iterations (trash / beyond-``pos`` lanes masked).
    Returns (x, pool_k, pool_v) with the lanes' new K/V rows written
    (dead lanes: into the trash block; every other row of a touched
    block a byte copy)."""
    quant = isinstance(pool_k, tuple)
    w, nq, e = x.shape
    d = e // n_head        # full head dim: x is replicated under TP
    h = _ln(x, p["ln1_s"], p["ln1_b"], eps)
    q = h @ p["wq"] + p["bq"]
    k_cur = h @ p["wk"] + p["bk"]          # (W, Q, n_kv·d): pool rows
    v_cur = h @ p["wv"] + p["bv"]
    # n_kv is the LOCAL kv-head count, read off the weight widths
    # (shard-local widths carry the layout; no tp_world needed)
    n_kv = k_cur.shape[-1] // d
    g = q.shape[-1] // (n_kv * d)
    q = q.reshape(w, nq, n_kv, g, d).transpose(0, 2, 3, 1, 4)
    if quant:
        def quantize(rows):
            q8, sc = _quantize_kv(rows.reshape(w, nq, n_kv, d))
            return q8.reshape(w, nq, n_kv * d), sc

        k_cur, v_cur = quantize(k_cur), quantize(v_cur)
    cur_mask = jnp.tril(jnp.ones((nq, nq), bool))
    if window is not None:
        # within-chunk banding: query i attends chunk key j at
        # position pos+j only when (pos+i) - (pos+j) < window
        i = jnp.arange(nq)
        cur_mask = cur_mask & (i[:, None] - i[None, :] < window)
    # one call for every lane: the decode kernel where the operands
    # allow it, else the block loop a lane (ops/paged_attention.py)
    a = _paged_decode_attn(
        q, pool_k, pool_v, li, tables, pos, block, trash, k_cur, v_cur,
        1.0 / math.sqrt(d), cur_mask=cur_mask, window=window,
        blk_lo=blk_lo, n_blk=n_blk, tp_axis=tp_axis)
    # the new rows depend on nothing the block loop computes, so the
    # compiler is free to put a layer's scatter before its reads of the
    # pool, and then keeps a second pool to read from: tie the rows to
    # the attention's result
    a, k_cur, v_cur = jax.lax.optimization_barrier((a, k_cur, v_cur))
    a = a.astype(x.dtype).transpose(0, 3, 1, 2, 4).reshape(
        w, nq, e // tp_world)
    x = x + (_tp_psum(a @ p["wo"], tp_axis, tp_world) + p["bo"])
    h = _ln(x, p["ln2_s"], p["ln2_b"], eps)
    x = x + _mlp_lanes(h, p, moe_top_k, live, tp_axis, tp_world, ep)
    def lay(pool, new):
        return _write_rows(pool, li, new, tables, pos, live, block, trash)

    return (x, jax.tree.map(lay, pool_k, k_cur),
            jax.tree.map(lay, pool_v, v_cur))


def chunk_step_paged(params, x, pool_k, pool_v, tables, pos, live,
                     n_blk, n_head, eps, *, block, trash, moe_top_k=2,
                     window=None, blk_lo=None, tp_axis=None,
                     tp_world=1, ep=None):
    """PUBLIC block-native chunk advance of every lane (speculative
    verify against the pool; serve/paged.py ``_paged_spec_kernel``).
    ``x``: (W, K, E) embedded chunks at ``pos[w]..pos[w]+K-1`` (dead
    lanes clamped to position 0 by the caller); ``pool_k/v``: the full
    (L, N+1, B, H_kv·D) pools (int8 pools are (values, scales));
    ``tables``: (W, W//B) trash-padded block tables; ``n_blk``: loop
    bound — any traced value >= ceil(max live pos / block).  Returns
    ((W, K, V) logits, pool_k, pool_v), each layer's new rows written
    at ``tables[w, pos // B]`` (and the block after it where the chunk
    runs on; dead lanes: the trash block)."""
    def layer(carry, li, p, _):
        return _block_paged(
            *carry, p, li, tables, pos, live, n_blk, n_head, eps, block,
            trash, moe_top_k=moe_top_k, window=window, blk_lo=blk_lo,
            tp_axis=tp_axis, tp_world=tp_world, ep=ep), None

    (x, pool_k, pool_v), _ = _over_layers(params, layer,
                                          (x, pool_k, pool_v))
    x = _ln(x, params["lnf_s"], params["lnf_b"], eps)
    return _logits(x, params), pool_k, pool_v


def decode_step_paged(params, x, pool_k, pool_v, tables, pos, live,
                      n_blk, n_head, eps, **kw):
    """PUBLIC block-native single-step decode of every lane (the paged
    serve engine's hot path; serve/paged.py ``_paged_decode_kernel``):
    :func:`chunk_step_paged` at one token a lane.  ``x``: (W, E)
    embedded inputs at ``pos``; returns ((W, V) logits, pool_k,
    pool_v)."""
    logits, pool_k, pool_v = chunk_step_paged(
        params, x[:, None], pool_k, pool_v, tables, pos, live, n_blk,
        n_head, eps, **kw)
    return logits[:, 0], pool_k, pool_v


def spec_verify(t_logits, d_probs, props, key, temp, top_p, top_k,
                use_top_p):
    """Rejection-sampling chunk verify — the sampled half of
    speculative decoding (VERDICT missing #4), batched over the chunk's
    positions (vmap over slots batches it over rows; the serve engine's
    ``_pool_spec_step`` does exactly that).

    ``t_logits``: (spec_k, V) target logits at positions
    pos..pos+spec_k-1; ``d_probs``: (spec_k-1, V) post-filter draft
    distributions the proposals were drawn from; ``props``:
    (spec_k-1,) proposed tokens; ``temp`` is TRACED (a serve pool mixes
    greedy and sampled requests in one executable).  Returns
    ``(out (spec_k,) int32, a_draft int32)``: ``out[:a_draft]`` echo
    the accepted proposals, ``out[a_draft]`` is the correction token
    (residual resample, or the bonus draw on a full accept), entries
    past that are garbage the caller must not emit.  Tokens emitted =
    ``a_draft + 1``.

    Greedy (``temp <= 0``): accept while ``props[i] ==
    argmax(t_logits[i])``, emit the target's argmax at the stop
    position — the deterministic limit of the scheme and byte-identical
    to sequential target-greedy decode (up to chunk-vs-sequential
    einsum-order near-ties, same caveat as ``generate_speculative``).

    Sampled: position i's proposal is accepted with probability
    ``min(1, p_i(x) / q_i(x))`` where p/q are the POST-FILTER
    (temperature → top-k → top-p, via the shared ``_filter_logits``)
    target/draft distributions; the first rejection resamples from the
    normalized residual ``max(0, p_i − q_i)`` and stops; all spec_k−1
    accepted samples the bonus token from the last position's target
    distribution (expressed below as the residual against a virtual
    all-zero q row).  Marginally each emitted token is distributed
    EXACTLY as direct target sampling — the standard speculative
    sampling guarantee (Leviathan et al. / Chen et al. 2023) —
    pinned distributionally by tests/test_spec_serve.py's χ² gate.
    ``p == q`` makes the residual mass exactly 0; that degenerate case
    falls back to sampling from p (acceptance was certain anyway, any
    correction distribution is unreachable in exact arithmetic and p
    is the safe float-noise answer)."""
    spec_k, V = t_logits.shape
    t_logits = t_logits.astype(jnp.float32)
    # greedy branch: match-against-argmax, emit the target candidates
    cands = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
    match_g = props == cands[:-1]
    a_greedy = jnp.argmin(jnp.concatenate(
        [match_g, jnp.zeros((1,), bool)]))
    # sampled branch: post-filter target distributions per position
    ts = jnp.maximum(temp, 1e-6)
    p = jax.nn.softmax(jax.vmap(
        lambda lg: _filter_logits(lg, ts, top_p, top_k, use_top_p))(
            t_logits), axis=-1)                          # (spec_k, V)
    # virtual zero-q last row: its residual max(p-0, 0) IS the last
    # position's target distribution, so one gather serves both the
    # mid-chunk rejection resample and the full-accept bonus draw
    q = jnp.concatenate(
        [d_probs.astype(jnp.float32), jnp.zeros((1, V), jnp.float32)])
    k_acc, k_fix = jax.random.split(key)
    u = jax.random.uniform(k_acc, (spec_k - 1,))
    p_prop = jnp.take_along_axis(p[:-1], props[:, None], axis=-1)[:, 0]
    q_prop = jnp.take_along_axis(d_probs.astype(jnp.float32),
                                 props[:, None], axis=-1)[:, 0]
    # u < p/q without the division: q == 0 accepts iff p > 0 (the
    # ratio's limit), and p >= q accepts always (u < 1 <= p/q)
    accept = u * q_prop < p_prop
    a_sampled = jnp.argmin(jnp.concatenate(
        [accept, jnp.zeros((1,), bool)]))
    res = jnp.maximum(p[a_sampled] - q[a_sampled], 0.0)
    mass = jnp.sum(res)
    res = jnp.where(mass > 0.0, res / jnp.maximum(mass, 1e-38),
                    p[a_sampled])
    fix = jax.random.categorical(
        k_fix, jnp.log(jnp.maximum(res, 1e-38))).astype(jnp.int32)
    out_s = jnp.concatenate([props, jnp.zeros((1,), jnp.int32)])
    out_s = out_s.at[a_sampled].set(fix)
    greedy = temp <= 0.0
    out = jnp.where(greedy, cands, out_s)
    a_draft = jnp.where(greedy, a_greedy, a_sampled)
    return out, a_draft.astype(jnp.int32)


def _batch1(c):
    """Insert the width-1 batch axis on a cache pytree (dense arrays
    or (values, scales) tuples)."""
    return jax.tree.map(lambda a: a[:, None], c)


def _unbatch1(c):
    return jax.tree.map(lambda a: a[:, 0], c)


def _draft_propose(d_params, dkc_r, dvc_r, t_c, p_c, k_draft, temp,
                   top_p, spec_k, dn, de, dm, top_k, use_top_p):
    """The DRAFT half of one slot's speculative chunk: ``spec_k``
    sequential draft decode steps propose ``spec_k - 1`` tokens (the
    extra step processes the last proposal as an input so a
    full-accept chunk leaves the draft cache a valid row ahead — the
    same trick as the offline ``_spec_row``).  Shared by the
    slot-arena spec row and the paged-kernel spec row, so the
    proposal chain (and therefore the verify outcome) cannot drift
    between memory models.  Returns (props (spec_k-1,), d_probs
    (spec_k-1, V), dkc_b, dvc_b) with the draft rows batched."""
    ts = jnp.maximum(temp, 1e-6)

    def dstep(c, k):
        dkc_b, dvc_b, tok_, dpos = c
        x = (d_params["wte"][tok_] + d_params["wpe"][dpos])[None, None]
        lg, dkc_b, dvc_b = _advance_one(d_params, x, dkc_b, dvc_b,
                                        dpos, dn, de, moe_top_k=dm)
        # post-filter draft distribution (the q of the accept
        # ratio) AND the proposal drawn from it — the identical
        # filter chain _sample uses, via the shared helper
        fl = _filter_logits(lg[0], ts, top_p, top_k, use_top_p)
        nxt_s = jax.random.categorical(k, fl).astype(jnp.int32)
        nxt_g = jnp.argmax(lg[0]).astype(jnp.int32)
        nxt = jnp.where(temp <= 0.0, nxt_g, nxt_s)
        return ((dkc_b, dvc_b, nxt, dpos + 1),
                (nxt, jax.nn.softmax(fl)))

    dkeys = jax.random.split(k_draft, spec_k)
    (dkc_b, dvc_b, _, _), (props_all, q_all) = jax.lax.scan(
        dstep, (_batch1(dkc_r), _batch1(dvc_r), t_c, p_c), dkeys)
    return props_all[:-1], q_all[:-1], dkc_b, dvc_b


def _rep_mask_init(ids, live, vocab):
    """(ctx,) ids + (ctx,) live mask -> (V,) bool presence mask."""
    return jnp.zeros((vocab,), bool).at[ids].max(live)


def _generate_row(params, ids, prompt_len, key, temperature, top_p, *,
                  n_head, eps, n_new, greedy, top_k, use_top_p,
                  moe_top_k=2, unroll=4, quant_cache=False,
                  min_p=1.0, use_min_p=False, rep_penalty=1.0,
                  use_rep=False, window=None):
    """Single-prompt core: ids (ctx,) right-padded, returns (n_new,).
    Batched decoding vmaps this over (ids, prompt_len, key) — the
    per-row cache writes at differing positions lower to scatters.
    With ``use_rep`` a (V,) presence mask (prompt tokens + everything
    emitted) rides the scan carry for the repetition penalty."""
    hidden, kc, vc = prefill(params, ids[None, :], n_head, eps,
                             moe_top_k=moe_top_k, quant_cache=quant_cache,
                             window=window, prompt_end=prompt_len)
    # dense caches span ctx (prefill processed the full padded row);
    # windowed models return an O(window) ROLLING cache instead.
    # Vocab-project ONLY the last live row — (1, V), not (ctx, V)
    last_h = jax.lax.dynamic_index_in_dim(
        hidden, prompt_len - 1, axis=1, keepdims=False)    # (1, E)
    first_logit = _logits(last_h[:, None, :], params)[0, 0]  # (V,)

    def sample(logit, k, rep):
        return _sample(logit, k, temperature, top_p, greedy, top_k,
                       use_top_p, min_p=min_p, use_min_p=use_min_p,
                       rep_mask=rep, rep_penalty=rep_penalty)

    rep = None
    if use_rep:
        vocab = params["wte"].shape[0]
        rep = _rep_mask_init(ids, jnp.arange(ids.shape[0]) < prompt_len,
                             vocab)
    k0, key = jax.random.split(key)
    tok0 = sample(first_logit, k0, rep)
    if rep is not None:
        rep = rep.at[tok0].set(True)

    # ``rep`` rides the carry as None (an empty pytree leaf) when the
    # penalty is off — one scan body serves both modes
    def step(carry, _):
        tok, pos, kc, vc, key, rep = carry
        x = params["wte"][tok][None, None, :] + \
            params["wpe"][pos][None, None, :]
        logits, kc, vc = _advance_one(params, x, kc, vc, pos, n_head,
                                      eps, moe_top_k=moe_top_k,
                                      window=window)
        k, key = jax.random.split(key)
        nxt = sample(logits[0], k, rep)
        new_rep = None if rep is None else rep.at[nxt].set(True)
        return (nxt, pos + 1, kc, vc, key, new_rep), tok

    (last, *_), toks = jax.lax.scan(
        step, (tok0, prompt_len, kc, vc, key, rep), None,
        length=n_new - 1, unroll=min(unroll, max(1, n_new - 1)))
    return jnp.concatenate([toks, last[None]])


@partial(jax.jit, static_argnames=("n_head", "eps", "n_new", "ctx",
                                   "greedy", "top_k", "use_top_p",
                                   "moe_top_k", "unroll", "quant_cache",
                                   "use_min_p", "use_rep", "window"))
def generate_cached(params, ids, prompt_lens, n_head, eps, n_new, ctx,
                    greedy, temperature, keys, top_k=0, top_p=1.0,
                    use_top_p=False, moe_top_k=2, unroll=4,
                    quant_cache=False, min_p=1.0, use_min_p=False,
                    rep_penalty=1.0, use_rep=False, window=None):
    """One compiled prefill + lax.scan decode for a BATCH of prompts.
    ids: (B, ctx) right-padded; prompt_lens: (B,) int32; keys: (B, 2)
    PRNG keys.  Returns (B, n_new) sampled token ids.  ``top_k=0``
    disables top-k; ``use_top_p`` gates nucleus sampling (static so the
    sort compiles away when off).

    This is the per-row SCATTER path (vmapped row core, per-row
    positions, cache writes lower to scatters).  Since round 5 it is
    the EQUALITY ORACLE only: ``generate`` routes every batch — ragged
    included, via left-padding — through
    :func:`generate_cached_uniform`, whose shared position means one
    batched cache write and full-batch GEMMs per step (measured +66%
    tokens/sec at the bench config).  Kept because its math is
    transparently per-row right-padded, which is what the left-padded
    fast path must match token-for-token in f32
    (tests/test_gpt2.py)."""
    row = partial(_generate_row, n_head=n_head, eps=eps, n_new=n_new,
                  greedy=greedy, top_k=top_k, use_top_p=use_top_p,
                  moe_top_k=moe_top_k, unroll=unroll,
                  quant_cache=quant_cache, min_p=min_p,
                  use_min_p=use_min_p, rep_penalty=rep_penalty,
                  use_rep=use_rep, window=window)
    return jax.vmap(
        lambda i, n, k: row(params, i, n, k, temperature, top_p))(
            ids, prompt_lens, keys)


@partial(jax.jit, static_argnames=("n_head", "eps", "n_new", "ctx",
                                   "greedy", "top_k", "use_top_p",
                                   "moe_top_k", "unroll", "quant_cache",
                                   "use_min_p", "use_rep", "window"))
def generate_cached_uniform(params, ids, prompt_len, n_head, eps, n_new,
                            ctx, greedy, temperature, keys, top_k=0,
                            top_p=1.0, use_top_p=False, start=None,
                            moe_top_k=2, unroll=4, quant_cache=False,
                            min_p=1.0, use_min_p=False, rep_penalty=1.0,
                            use_rep=False, window=None):
    """Shared-position fast path: ids (B, ctx), ONE traced scalar
    ``prompt_len`` (the shared first free window position) — the
    per-step cache update is a single batched dynamic_update_slice and
    the projections run as full-batch GEMMs (the vmapped ragged path
    pays per-row scatters and B=1 matmuls for the same work).

    Equal-length batches: right-padded ids, ``start=None``.  RAGGED
    batches (round 5): LEFT-pad so every prompt ENDS at ``prompt_len``
    and pass ``start`` (B,) = the per-row first live position; the only
    per-row work is a wpe gather and the mask's lower bound — cache
    writes and GEMMs stay batched.  Token-exact vs the per-row scatter
    path in f32 (the oracle test); bf16 may flip argmax near-ties."""
    hidden, kc, vc = prefill(params, ids, n_head, eps, start=start,
                             moe_top_k=moe_top_k, quant_cache=quant_cache,
                             window=window, prompt_end=prompt_len)
    last_h = jax.lax.dynamic_index_in_dim(
        hidden, prompt_len - 1, axis=1, keepdims=False)     # (B, E)
    logits0 = _logits(last_h[:, None, :], params)[:, 0]     # (B, V)

    def sample(logits, keys_, rep):
        return jax.vmap(
            lambda lg, k, r: _sample(lg, k, temperature, top_p, greedy,
                                     top_k, use_top_p, min_p=min_p,
                                     use_min_p=use_min_p, rep_mask=r,
                                     rep_penalty=rep_penalty),
            in_axes=(0, 0, None if rep is None else 0))(
                logits, keys_, rep)

    rep = None
    if use_rep:
        vocab = params["wte"].shape[0]
        bsz = ids.shape[0]
        span = jnp.arange(ctx)[None, :]
        live = span < prompt_len
        if start is not None:  # left-padded: pads sit BEFORE start_i
            live = live & (span >= start[:, None])
        else:
            live = jnp.broadcast_to(live, (bsz, ctx))
        rep = jax.vmap(_rep_mask_init, in_axes=(0, 0, None))(
            ids, live, vocab)
    keys0 = jax.vmap(lambda k: jax.random.split(k))(keys)
    tok0 = sample(logits0, keys0[:, 0], rep)
    keys_cur = keys0[:, 1]
    if rep is not None:
        rep = rep.at[jnp.arange(ids.shape[0]), tok0].set(True)

    # ``rep`` rides the carry as None (an empty pytree leaf) when the
    # penalty is off — one scan body serves both modes
    def step(carry, t):
        toks, kc, vc, keys_cur, rep = carry
        pos = prompt_len + t
        if start is None:
            pe = params["wpe"][pos][None, None, :]
        else:
            # row-relative position: window pos − start_i
            pe = jnp.take(params["wpe"], pos - start, axis=0)[:, None, :]
        x = jnp.take(params["wte"], toks, axis=0)[:, None, :] + pe
        logits, kc, vc = _advance_one(params, x, kc, vc, pos, n_head,
                                      eps, start=start,
                                      moe_top_k=moe_top_k,
                                      window=window)
        ks = jax.vmap(lambda k: jax.random.split(k))(keys_cur)
        nxt = sample(logits, ks[:, 0], rep)
        new_rep = (None if rep is None
                   else rep.at[jnp.arange(nxt.shape[0]), nxt].set(True))
        return (nxt, kc, vc, ks[:, 1], new_rep), toks

    (last, *_), toks = jax.lax.scan(
        step, (tok0, kc, vc, keys_cur, rep), jnp.arange(n_new - 1),
        unroll=min(unroll, max(1, n_new - 1)))
    return jnp.concatenate([toks.T, last[:, None]], axis=1)


@partial(jax.jit, static_argnames=("n_head", "eps", "n_new", "ctx",
                                   "num_beams", "moe_top_k", "unroll",
                                   "quant_cache", "window"))
def _beam_search_cached(params, ids, prompt_len, n_head, eps, n_new,
                        ctx, num_beams, moe_top_k=2, start=None,
                        unroll=4, quant_cache=False, window=None):
    """Fixed-length beam search, ONE compiled prefill + scan, for a
    BATCH of prompts (round 5).  ids: (B, ctx) sharing one end
    position ``prompt_len`` (right-padded when equal-length; ragged
    batches come in LEFT-padded with ``start`` (B,) as in
    generate_cached_uniform).  Returns ((B, num_beams, n_new) token
    ids, (B, num_beams) total log-probs), best beam first per prompt.
    The beams are the batch — (B·K) rows advance lockstep, and each
    step reorders every prompt's K cache rows by parent with one
    BLOCK-DIAGONAL gather (global row index b·K + parent).  Exact when
    num_beams covers the frontier (tests compare against exhaustive
    search on tiny models, and batched-vs-looped equality)."""
    bsz = ids.shape[0]
    K = num_beams
    hidden, kc, vc = prefill(params, ids, n_head, eps, start=start,
                             moe_top_k=moe_top_k, quant_cache=quant_cache,
                             window=window, prompt_end=prompt_len)
    last_h = jax.lax.dynamic_index_in_dim(
        hidden, prompt_len - 1, axis=1, keepdims=False)      # (B, E)
    logp0 = jax.nn.log_softmax(
        _logits(last_h[:, None, :], params)[:, 0].astype(jnp.float32))
    V = logp0.shape[-1]
    k0 = min(K, V)
    top0, tok0 = jax.lax.top_k(logp0, k0)                    # (B, k0)
    # pad the beam set if num_beams > V (dead beams at -inf)
    pad = K - k0
    scores = jnp.concatenate(
        [top0, jnp.full((bsz, pad), NEG_INF, jnp.float32)], axis=1)
    toks = jnp.concatenate(
        [tok0, jnp.zeros((bsz, pad), jnp.int32)], axis=1)    # (B, K)
    # replicate the prompt caches across beams: (L, B, ...) ->
    # (L, B*K, ...) in (b, k) row-major order (tree-mapped: int8
    # caches are (values, scales) tuples)
    kc = jax.tree.map(lambda a: jnp.repeat(a, K, axis=1), kc)
    vc = jax.tree.map(lambda a: jnp.repeat(a, K, axis=1), vc)
    start_rows = None if start is None else jnp.repeat(start, K)
    seqs = jnp.zeros((bsz, K, n_new), jnp.int32)
    seqs = seqs.at[:, :, 0].set(toks)

    def step(carry, t):
        seqs, scores, toks, kc, vc = carry
        pos = prompt_len + t
        if start_rows is None:
            pe = params["wpe"][pos][None, None, :]
        else:
            pe = jnp.take(params["wpe"], pos - start_rows,
                          axis=0)[:, None, :]
        x = jnp.take(params["wte"], toks.reshape(-1),
                     axis=0)[:, None, :] + pe
        logits, kc, vc = _advance_one(params, x, kc, vc, pos, n_head,
                                      eps, start=start_rows,
                                      moe_top_k=moe_top_k,
                                      window=window)
        logp = jax.nn.log_softmax(
            logits.astype(jnp.float32)).reshape(bsz, K, V)
        cand = scores[:, :, None] + logp                 # (B, K, V)
        flat_scores, flat_idx = jax.lax.top_k(
            cand.reshape(bsz, K * V), K)                 # (B, K)
        parents = flat_idx // V                          # (B, K) in [0,K)
        toks = (flat_idx % V).astype(jnp.int32)
        seqs = jnp.take_along_axis(seqs, parents[:, :, None], axis=1)
        seqs = seqs.at[:, :, t + 1].set(toks)
        # block-diagonal cache reorder: beam rows only ever gather from
        # their own prompt's block
        glob = (jnp.arange(bsz)[:, None] * K + parents).reshape(-1)
        kc = jax.tree.map(lambda a: a[:, glob], kc)
        vc = jax.tree.map(lambda a: a[:, glob], vc)
        return (seqs, flat_scores, toks, kc, vc), None

    if n_new > 1:
        (seqs, scores, *_), _ = jax.lax.scan(
            step, (seqs, scores, toks, kc, vc),
            jnp.arange(n_new - 1), unroll=min(unroll, n_new - 1))
    # already best-first: top_k (and the padded init) sort descending
    return seqs, scores


def _is_batch(prompt_ids):
    """Shared batch-vs-single classification (a list of rows or a 2-D
    array is a batch; ragged batches defeat np.ndim on the whole
    object, so classify by the first element)."""
    if isinstance(prompt_ids, np.ndarray):
        return prompt_ids.ndim > 1
    seq = list(prompt_ids)
    return bool(seq) and np.ndim(seq[0]) > 0


def _normalize_prompts(prompt_ids, max_new_tokens, cfg,
                       over_length_hint=""):
    """Shared prompt handling for generate/generate_beam: classify
    single-vs-batch, coerce rows, length-check, and build the
    LEFT-padded shared-end window.  Returns (single, rows, lens,
    max_len, window, start) — ``start`` is None for equal-length
    batches (every row already ends at max_len = its length)."""
    single = not _is_batch(prompt_ids)
    seq = [prompt_ids] if single else list(prompt_ids)
    rows = [np.asarray(r, np.int32).reshape(-1) for r in seq]
    for r in rows:
        if len(r) + max_new_tokens > cfg.n_positions:
            raise ValueError(
                f"prompt ({len(r)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds n_positions ({cfg.n_positions})"
                + over_length_hint)
    lens = np.asarray([len(r) for r in rows], np.int32)
    max_len = int(lens.max()) if len(rows) else 0
    padded = np.zeros((len(rows), cfg.n_positions), np.int32)
    for i, r in enumerate(rows):
        padded[i, max_len - len(r):max_len] = r
    uniform = len(set(lens.tolist())) <= 1
    start = None if uniform else jnp.asarray(max_len - lens)
    return single, rows, lens, max_len, padded, start


def generate_beam(m, prompt_ids, max_new_tokens=20, num_beams=4,
                  dtype=None, unroll=4, cache_dtype=None):
    """Fixed-length beam search for a (optionally plan-sharded, possibly
    MoE) GPT2LMHead: returns the highest-total-log-prob continuation of
    ``max_new_tokens`` tokens.  Takes one 1-D prompt (returns one
    array) or a list/2-D batch, possibly ragged (returns a list) —
    all (B·num_beams) rows advance in ONE compiled executable, each
    prompt's beams reordering through a block-diagonal parent gather
    (round 5); ragged batches ride the left-padding machinery.
    ``num_beams=1`` equals greedy decoding.  No EOS handling — this
    framework's models are tokenizer-free, so sequences are
    fixed-length and the length penalty cancels."""
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    cfg = m.cfg
    single, rows, lens, max_len, padded, start = _normalize_prompts(
        prompt_ids, max_new_tokens, cfg)
    if max_new_tokens <= 0:
        out = [r.copy() for r in rows]
        return out[0] if single else out
    params = extract_params(m, dtype=dtype)
    seqs, _scores = _beam_search_cached(
        params, jnp.asarray(padded), max_len, cfg.n_head,
        float(cfg.layer_norm_eps), int(max_new_tokens),
        cfg.n_positions, int(num_beams),
        moe_top_k=int(getattr(cfg, "moe_top_k", 2) or 2), start=start,
        unroll=int(unroll), quant_cache=_quant_flag(cache_dtype),
        window=_norm_window(cfg))
    seqs = np.asarray(seqs)
    out = [np.concatenate([r, seqs[i, 0]]).astype(np.int32)
           for i, r in enumerate(rows)]
    return out[0] if single else out


def _norm_window(cfg):
    """The decode-effective sliding window: None when the model has no
    window or the window covers the whole position space (a rolling
    cache would then be the dense cache with extra index math)."""
    w = getattr(cfg, "attn_window", None)
    if w is None or w >= cfg.n_positions:
        return None
    return int(w)


def _quant_flag(cache_dtype):
    """Map the user-facing ``cache_dtype`` to the static jit flag.
    Only None (cache in the compute dtype) and "int8" exist — dtype
    strings that would not change behavior are rejected rather than
    silently accepted."""
    if cache_dtype is None:
        return False
    if cache_dtype == "int8":
        return True
    raise ValueError(f"cache_dtype must be None or 'int8', "
                     f"got {cache_dtype!r}")


def _seed(temperature, rng):
    # rng=None must stay non-deterministic across calls like the
    # windowed sampler's np.random fallback; accept both RandomState
    # (.randint) and Generator (.integers); greedy decoding draws
    # nothing (the key is unused, and consuming the caller's rng would
    # perturb downstream reproducibility)
    if temperature <= 0:
        return 0
    if rng is None:
        return int(np.random.randint(0, 2 ** 31 - 1))
    if hasattr(rng, "integers"):
        return int(rng.integers(0, 2 ** 31 - 1))
    return int(rng.randint(0, 2 ** 31 - 1))


def generate(m, prompt_ids, max_new_tokens=20, temperature=1.0, rng=None,
             top_k=0, top_p=None, min_p=None, repetition_penalty=None,
             dtype=None, unroll=4, cache_dtype=None,
             _ragged_impl="left"):
    """KV-cached sampling for a GPT2LMHead (dense or MoE,
    optionally plan-sharded).  Requires
    prompt_len + max_new_tokens <= cfg.n_positions (the windowed
    fallback in models/gpt2.py handles longer generations).

    ``prompt_ids``: one 1-D prompt (returns a 1-D array) or a list/2-D
    batch of prompts, possibly ragged (returns a list of 1-D arrays —
    each its prompt + continuation; all rows decode lockstep in ONE
    compiled executable).  Ragged batches are LEFT-padded onto the
    shared-position fast path (round 5); ``_ragged_impl="scatter"``
    selects the per-row vmap oracle instead (tests).  ``top_k``
    (int > 0) / ``top_p`` (0 < p ≤ 1) filter the temperature-scaled
    distribution before sampling.  ``dtype=jnp.bfloat16`` runs
    inference in bf16 (≈2× steady-state throughput; see
    extract_params).  ``cache_dtype="int8"`` quantizes the KV cache
    (symmetric per-(token, head) scales over D) — ~2× less cache
    traffic on a cache-read-bound loop, at the cost of quantization
    noise in the attention scores (argmax near-ties can flip; sampled
    distributions shift by the score error).  ``unroll`` (default 4):
    decode-loop unroll factor — the measured throughput/compile-time
    knee; see the module docstring."""
    cfg = m.cfg
    single, rows, lens, max_len, padded, start = _normalize_prompts(
        prompt_ids, max_new_tokens, cfg,
        over_length_hint="; use the windowed GPT2LMHead.generate")
    if max_new_tokens <= 0:
        out = [r.copy() for r in rows]
        return out[0] if single else out
    if top_k and top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    # HF behavior: top_k larger than the vocab means "no filter" — an
    # unclamped value would die at trace time inside lax.top_k with an
    # obscure shape error (advisor r04)
    top_k = min(int(top_k or 0), cfg.vocab_size)
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if min_p is not None and not 0.0 < min_p <= 1.0:
        raise ValueError(f"min_p must be in (0, 1], got {min_p}")
    if repetition_penalty is not None and repetition_penalty <= 0.0:
        raise ValueError(f"repetition_penalty must be > 0, "
                         f"got {repetition_penalty}")
    use_rep = (repetition_penalty is not None
               and repetition_penalty != 1.0)
    params = extract_params(m, dtype=dtype)
    ctx = cfg.n_positions
    bsz = len(rows)
    uniform = start is None
    if not uniform and _ragged_impl == "scatter":
        # the oracle path wants RIGHT-padded rows
        padded = np.zeros((bsz, ctx), np.int32)
        for i, r in enumerate(rows):
            padded[i, :len(r)] = r
    keys = jax.random.split(
        jax.random.PRNGKey(_seed(temperature, rng)), bsz)
    common = dict(
        top_k=int(top_k or 0),
        top_p=jnp.float32(1.0 if top_p is None else top_p),
        use_top_p=top_p is not None,
        min_p=jnp.float32(1.0 if min_p is None else min_p),
        use_min_p=min_p is not None,
        rep_penalty=jnp.float32(1.0 if repetition_penalty is None
                                else repetition_penalty),
        use_rep=use_rep,
        moe_top_k=int(getattr(cfg, "moe_top_k", 2) or 2),
        unroll=int(unroll), quant_cache=_quant_flag(cache_dtype),
        window=_norm_window(cfg))
    sample_args = (cfg.n_head, float(cfg.layer_norm_eps),
                   int(max_new_tokens), ctx, temperature <= 0,
                   jnp.float32(max(temperature, 1e-6)), keys)
    if uniform:
        new = generate_cached_uniform(
            params, jnp.asarray(padded), max_len, *sample_args,
            **common)
    elif _ragged_impl == "left":
        new = generate_cached_uniform(
            params, jnp.asarray(padded), max_len, *sample_args,
            start=start, **common)
    elif _ragged_impl == "scatter":
        # per-row vmap oracle (see generate_cached docstring)
        new = generate_cached(
            params, jnp.asarray(padded), jnp.asarray(lens),
            *sample_args, **common)
    else:
        raise ValueError(f"unknown _ragged_impl {_ragged_impl!r}; "
                         "expected 'left' or 'scatter'")
    new = np.asarray(new)
    out = [np.concatenate([r, new[i]]).astype(np.int32)
           for i, r in enumerate(rows)]
    return out[0] if single else out


# ---------------------------------------------------------------------------
# speculative decoding (greedy draft-and-verify, round 5)
# ---------------------------------------------------------------------------

def _spec_row(t_params, d_params, ids, prompt_len, spec_k,
              n_new, t_static, d_static, quant_cache=False):
    """Greedy speculative decoding ROW CORE (ids: (1, ctx)).

    Per chunk: the draft decodes ``spec_k - 1`` tokens sequentially
    (cheap model, cheap cache), then the target verifies the whole
    chunk with ONE chunked cache advance (_advance_chunk — one big
    cache read serves spec_k positions).  The emitted tokens are
    always the TARGET's greedy choices, so the output is exactly
    target-greedy whatever the draft proposes; the draft only decides
    how many positions each target read amortizes over.

    Cache rollback is FREE by design: both caches gate reads on
    position (live = slot <= pos), and every chunk's contiguous write
    at the new position overwrites any rows a rejected proposal left
    behind before they can ever become live again.

    ``t_static``/``d_static``: (n_head, eps, moe_top_k) per model.
    Returns (out tokens (n_new + spec_k,), n_chunks, n_accepted_draft)
    — acceptance rate = n_accepted_draft / (n_chunks * (spec_k - 1)).
    """
    tn, te, tm = t_static
    dn, de, dm = d_static
    t_hidden, t_kc, t_vc = prefill(t_params, ids, tn, te,
                                   moe_top_k=tm,
                                   quant_cache=quant_cache)
    _, d_kc, d_vc = prefill(d_params, ids, dn, de, moe_top_k=dm,
                            quant_cache=quant_cache)
    last_h = jax.lax.dynamic_index_in_dim(
        t_hidden, prompt_len - 1, axis=1, keepdims=False)
    first = jnp.argmax(
        _logits(last_h[:, None, :], t_params)[0, 0]).astype(jnp.int32)
    out = jnp.zeros((n_new + spec_k,), jnp.int32)
    out = out.at[0].set(first)

    def cond(c):
        return c[1] < n_new

    def body(c):
        out, n_emit, pos, last, t_kc, t_vc, d_kc, d_vc, chunks, acc = c

        def dstep(dc, _):
            d_kc, d_vc, tok, dpos = dc
            x = (d_params["wte"][tok] + d_params["wpe"][dpos])[None, None]
            lg, d_kc, d_vc = _advance_one(d_params, x, d_kc, d_vc,
                                          dpos, dn, de, moe_top_k=dm)
            nxt = jnp.argmax(lg[0]).astype(jnp.int32)
            return (d_kc, d_vc, nxt, dpos + 1), nxt

        # spec_k steps, spec_k - 1 proposals: the extra step processes
        # the LAST proposal as an input so the draft cache always has
        # a row for position pos + spec_k - 1 — without it, a
        # full-accept chunk (whose bonus advances past every draft
        # write) leaves the next chunk's draft reading a stale prefill
        # row (caught by the self-draft acceptance test: acceptance
        # was 0.83, not 1.0, on a trained model)
        (d_kc, d_vc, _, _), props = jax.lax.scan(
            dstep, (d_kc, d_vc, last, pos), None, length=spec_k)
        props = props[:-1]

        chunk_toks = jnp.concatenate([last[None], props])   # (spec_k,)
        xs = (jnp.take(t_params["wte"], chunk_toks, axis=0)
              + jnp.take(t_params["wpe"],
                         pos + jnp.arange(spec_k), axis=0))[None]
        lg, t_kc, t_vc = _advance_chunk(t_params, xs, t_kc, t_vc, pos,
                                        tn, te, moe_top_k=tm)
        cands = jnp.argmax(lg[0], axis=-1).astype(jnp.int32)  # c_1..c_k
        match = props == cands[:-1]
        # first mismatch index = number of ACCEPTED draft tokens; all
        # matched -> spec_k - 1 accepted + the bonus candidate
        a_draft = jnp.argmin(jnp.concatenate(
            [match, jnp.zeros((1,), bool)]))
        a = a_draft + 1                     # tokens emitted this chunk
        # write the whole candidate block at n_emit; entries beyond
        # ``a`` are overwritten by the next chunk before they can
        # count (same argument as the cache rows)
        out = jax.lax.dynamic_update_slice(out, cands, (n_emit,))
        last = cands[a_draft]
        return (out, n_emit + a, pos + a, last, t_kc, t_vc, d_kc,
                d_vc, chunks + 1, acc + a_draft)

    out, n_emit, pos, last, *_, chunks, acc = jax.lax.while_loop(
        cond, body,
        (out, jnp.int32(1), jnp.asarray(prompt_len, jnp.int32), first,
         t_kc, t_vc, d_kc, d_vc, jnp.int32(0), jnp.int32(0)))
    return out, chunks, acc


@partial(jax.jit, static_argnames=("spec_k", "n_new", "t_static",
                                   "d_static", "quant_cache"))
def _speculative_loop(t_params, d_params, ids, prompt_lens, spec_k,
                      n_new, t_static, d_static, quant_cache=False):
    """Batched speculative decoding: vmap of the row core over (B, ctx)
    right-padded prompts with per-row lengths.  Rows accept at
    different rates, so each runs its own chunk loop — JAX's
    while_loop batching executes until every row has emitted n_new
    tokens, freezing finished rows' carries (their discarded body
    re-executions index past their window; jax gathers clip, and the
    headroom check in generate_speculative keeps live rows in
    bounds).  Per-row caches mean per-row scatters, like the ragged
    scatter oracle — speculation is a latency device for SMALL
    batches, which is exactly where that cost is irrelevant.
    Returns ((B, n_new + spec_k) tokens, (B,) chunks, (B,)
    accepted).

    B == 1 (the primary latency case) dispatches the UNBATCHED row
    core: the batched while_loop rule rewrites every chunk as
    carry = select(done, carry, body(carry)) over the full K/V cache
    carries, an elementwise cache copy per chunk that a single prompt
    need not pay."""
    if ids.shape[0] == 1:
        out, chunks, acc = _spec_row(
            t_params, d_params, ids, prompt_lens[0], spec_k, n_new,
            t_static, d_static, quant_cache=quant_cache)
        return (out[None], jnp.asarray(chunks)[None],
                jnp.asarray(acc)[None])
    return jax.vmap(
        lambda row, n: _spec_row(t_params, d_params, row[None, :], n,
                                 spec_k, n_new, t_static, d_static,
                                 quant_cache=quant_cache))(
                                     ids, prompt_lens)


def generate_speculative(target, draft, prompt_ids, max_new_tokens=20,
                         spec_k=4, dtype=None, cache_dtype=None):
    """Greedy speculative decoding: ``draft`` (a smaller GPT2LMHead)
    proposes ``spec_k - 1`` tokens per chunk, ``target`` verifies the
    chunk in one cache read, and every emitted token is the TARGET's
    greedy choice — the draft only changes the speed.  Matches
    ``target.generate(prompt, temperature=0)`` token for token up to
    argmax near-ties: the chunked verify computes the same logits as
    sequential decode to ~1e-7 (einsum order), so only a model whose
    top-2 logits tie within that can flip (tested exact on trained
    models; with ``cache_dtype="int8"`` the comparison point is int8
    sequential decode).  Returns ``(ids, stats)`` where ids is
    prompt + continuation and stats carries ``acceptance_rate`` (the
    fraction of draft proposals the target kept; None when nothing
    was verified), ``chunks``, and ``tokens_per_chunk``.

    Speedup condition: decode is cache/weight-read-bound, so one
    verify read amortized over ``a`` accepted positions beats ``a``
    sequential target steps whenever the draft is cheap and agrees
    often (acceptance is a property of the MODEL PAIR and data, not
    of this mechanism).

    Speculation-vs-unroll crossover (when each pays): the sequential
    path already amortizes loop overhead with ``unroll=4`` (+76%
    measured, PERF.md §8), so speculation must beat the UNROLLED
    baseline, not the naive one.  Per emitted token the speculative
    loop costs ``spec_k · c_draft + c_verify(spec_k)`` per ``a``
    emitted tokens (``a = 1 + acceptance·(spec_k−1)`` expected), vs
    one unrolled target step; with a draft ``r×`` cheaper than the
    target and the chunk verify ≈ one target step on a
    cache-read-bound loop, speculation wins when
    ``(spec_k/r + 1) / a < 1`` — e.g. at ``spec_k=4``, ``r≈8``
    (the 1-vs-2-layer demo pair is ~2×; production drafts are
    8–20×), break-even sits near acceptance ≈ 0.17 and the measured
    3.92 tokens/chunk at acceptance ≈ 0.97 is a ~2.6× bound.  Low
    acceptance (< ~0.3 at spec_k=4) or an expensive draft (r < 2)
    means the unrolled sequential loop is the faster choice; raising
    spec_k helps only while acceptance stays high (expected emitted
    tokens saturate at ``1/(1−acceptance)``).  Measured points for
    this model: ``bench_serve.py --spec-sweep`` runs spec_k ∈
    {2, 4, 8} on a trained pair and commits tokens/s vs measured
    acceptance per k to BENCH_SERVE.json (the ``spec_sweep``
    section, ``chip_pending`` — CPU prices the k sequential draft
    steps differently from a chip, so the peak-k is ratified on
    hardware).  The serve engine
    exposes the same trade via ``model.serve(draft_model=,
    spec_k=)``, where per-engine ``serve.spec.{accepted,drafted}``
    metrics measure the realized acceptance on live traffic; sampled
    (temperature/top-p) speculation lives there too, via
    :func:`spec_verify` — this offline entry is greedy-only.

    Takes one 1-D prompt (returns one array) or
    a list/2-D batch, possibly ragged (returns a list): rows accept
    at different rates, so each runs its own vmapped chunk loop
    until every row finishes — per-row cache scatters like the
    ragged oracle path, which is irrelevant at the small batches
    speculation targets.  Greedy only; sliding-window models are not
    supported (the rolling cache's slot arithmetic does not admit
    the chunked overwrite-rollback trick)."""
    cfg_t, cfg_d = target.cfg, draft.cfg
    if cfg_t.vocab_size != cfg_d.vocab_size:
        raise ValueError(
            f"target/draft vocab mismatch: {cfg_t.vocab_size} vs "
            f"{cfg_d.vocab_size}")
    for name, cfg in (("target", cfg_t), ("draft", cfg_d)):
        if getattr(cfg, "attn_window", None) is not None:
            raise NotImplementedError(
                f"speculative decoding does not support sliding-window "
                f"models ({name} has attn_window={cfg.attn_window})")
    if spec_k < 2:
        raise ValueError(f"spec_k must be >= 2, got {spec_k}")
    single = not _is_batch(prompt_ids)
    rows = ([prompt_ids] if single else list(prompt_ids))
    rows = [np.asarray(r, np.int32).reshape(-1) for r in rows]
    ctx = min(cfg_t.n_positions, cfg_d.n_positions)
    # the verify chunk may run up to spec_k - 1 positions past the
    # last emitted token, so reserve that headroom in the window
    for r in rows:
        if len(r) + max_new_tokens + spec_k - 1 > ctx:
            raise ValueError(
                f"prompt ({len(r)}) + max_new_tokens "
                f"({max_new_tokens}) + spec_k-1 ({spec_k - 1}) exceeds "
                f"n_positions ({ctx})")
    if max_new_tokens <= 0:
        outs = [r.copy() for r in rows]
        stats = {"acceptance_rate": None, "chunks": 0,
                 "tokens_per_chunk": None,
                 "per_row_chunks": [0] * len(rows)}
        return (outs[0] if single else outs), stats
    t_params = extract_params(target, dtype=dtype)
    d_params = extract_params(draft, dtype=dtype)
    bsz = len(rows)
    ids = np.zeros((bsz, ctx), np.int32)
    for i, r in enumerate(rows):
        ids[i, :len(r)] = r
    lens = jnp.asarray([len(r) for r in rows], jnp.int32)
    out, chunks, acc = _speculative_loop(
        t_params, d_params, jnp.asarray(ids), lens,
        int(spec_k), int(max_new_tokens),
        (cfg_t.n_head, float(cfg_t.layer_norm_eps),
         int(getattr(cfg_t, "moe_top_k", 2) or 2)),
        (cfg_d.n_head, float(cfg_d.layer_norm_eps),
         int(getattr(cfg_d, "moe_top_k", 2) or 2)),
        quant_cache=_quant_flag(cache_dtype))
    out = np.asarray(out)
    chunks = np.asarray(chunks)
    acc = np.asarray(acc)
    total_chunks = int(chunks.sum())
    # chunks == 0 (max_new_tokens == 1: the prefill token was enough)
    # verified zero proposals — report None, not an arbitrary rate
    stats = {
        "acceptance_rate": (float(acc.sum())
                            / (total_chunks * (spec_k - 1))
                            if total_chunks else None),
        "chunks": total_chunks,
        "tokens_per_chunk": (bsz * (max_new_tokens - 1) / total_chunks
                             if total_chunks else None),
        "per_row_chunks": chunks.tolist(),
    }
    outs = [np.concatenate([r, out[i, :max_new_tokens]]).astype(np.int32)
            for i, r in enumerate(rows)]
    return (outs[0] if single else outs), stats


# -- the family the serve engine is handed (models/served.py) --------------

class _GPT2Family(ServedFamily):
    """GPT-2 behind the served-model contract: learned positions,
    K/V as the only per-sequence state, every engine feature."""

    name = "gpt2"
    features = FEATURES

    def extract_params(self, model, dtype=None):
        return extract_params(model, dtype=dtype)

    def kv_geometry(self, cfg):
        return cfg.n_layer, cfg.n_kv_head, cfg.n_embd // cfg.n_head

    def window(self, cfg):
        return _norm_window(cfg)

    def quant_flag(self, cache_dtype):
        return _quant_flag(cache_dtype)

    def chunk_rows(self, params, segs, *, n_head, eps, block=None,
                   moe_top_k=2, window=None, tp_axis=None, tp_world=1,
                   ep=None):
        # each row is attended whole, whatever its blocks: ``block``
        # changes nothing here, and ``n_valid`` neither (what follows it
        # is K/V above the positions any query of the launch reads)
        toks = seg_cat([jax.lax.dynamic_slice(
            s.ids, (0, s.off), (1, s.chunk)) for s in segs], 1)
        pos = seg_cat([s.off + jnp.arange(s.chunk) for s in segs])
        x = jnp.take(params["wte"], toks[0], axis=0)[None] + \
            jnp.take(params["wpe"], pos, axis=0)[None]
        hidden, kc_rows, vc_rows = prefill_chunk(
            params, x, [s.kc_row for s in segs], [s.vc_row for s in segs],
            [s.off for s in segs], n_head, eps, moe_top_k=moe_top_k,
            window=window, tp_axis=tp_axis, tp_world=tp_world, ep=ep,
            segs=segs)
        return [(h, kc, vc, None) for h, kc, vc in zip(
            seg_split(hidden, segs, 1), kc_rows, vc_rows)]

    def decode_step(self, params, pool_k, pool_v, state, slots, tables,
                    toks, pos, live, n_blk, *, block, trash, n_head,
                    eps, moe_top_k=2, window=None, blk_lo=None,
                    tp_axis=None, tp_world=1, ep=None):
        """The lanes through each layer together; per lane only the
        online-softmax attention over its live blocks plus the step's
        own K/V; each layer's new rows written straight into the pool
        (dead lanes write the trash block)."""
        p_c = jnp.where(live, pos, 0)
        t_c = jnp.where(live, toks, 0)
        x = params["wte"][t_c] + params["wpe"][p_c]          # (W, E)
        logits, pool_k, pool_v = decode_step_paged(
            params, x, pool_k, pool_v, tables, p_c, live, n_blk,
            n_head, eps, block=block, trash=trash,
            moe_top_k=moe_top_k, window=window, blk_lo=blk_lo,
            tp_axis=tp_axis, tp_world=tp_world, ep=ep)
        return logits, pool_k, pool_v, None

    def logits(self, params, hidden):
        return _logits(hidden, params)


FAMILY = _GPT2Family()
