"""Tensor parallelism — Megatron-style sharded transformer layers.

The reference has NO tensor parallelism (SURVEY.md §2.3: data-parallel
DistOpt is its only modern strategy); this is the TPU-native extension
the survey marks as the ``('data','model')`` mesh-axis design point.

Execution model (see parallel/sharding.py): parameters carry
``PartitionSpec``s over the ``model`` axis; the jitted step runs under
GSPMD, which turns the annotated einsums into local matmuls + the
canonical Megatron collectives —

  * ``ColumnParallelLinear``  W:(in, out/model) — activations leave
    sharded on the feature dim, no communication;
  * ``RowParallelLinear``     W:(in/model, out) — consumes feature-
    sharded activations, XLA inserts the all-reduce (psum over
    ``model``) that closes the pair;
  * attention: heads sharded over ``model`` (column q/k/v + row output
    projection ⇒ exactly one all-reduce per attention block);
  * MLP: column fc1 + row fc2 ⇒ one all-reduce per MLP block;
  * ``VocabParallelEmbedding``: table rows sharded over ``model``; the
    sharded gather lowers to a one-hot matmul + psum on TPU.

Everything also runs UNSHARDED (plan=None or eager mode): the layers
degrade to their serial equivalents, so one model definition serves
single-chip and multi-chip.
"""

from __future__ import annotations

import logging
import math

import jax.numpy as jnp

from .. import amp, autograd, initializer
from ..layer import Layer
from ..tensor import Tensor
from . import sharding
from .sharding import DATA, MODEL, SEQ, P, ShardingPlan, constrain

__all__ = [
    "ColumnParallelLinear", "RowParallelLinear", "VocabParallelEmbedding",
    "ParallelMLP", "ParallelMHA", "ParallelTransformerBlock",
    "decode_param_specs", "decode_cache_spec", "decode_pool_spec",
]


# ---------------------------------------------------------------------------
# decode-shaped partition plans (the serve TP backend's layout;
# singa_tpu/serve/tp.py).  The layer classes above shard TRAINING
# tensors via ``partition_spec`` attributes; inference runs on the raw
# pytree ``models/gpt2_decode.extract_params`` extracts, so the same
# Megatron column/row decisions are restated here against that pytree's
# key names.
# ---------------------------------------------------------------------------

#: per-block key -> how its weight shards over the TP axis.  Column
#: weights (q/k/v projections, MLP fc1) split their OUTPUT dim — the
#: per-shard head/column slice needs no communication; row weights
#: (attention out-proj, MLP fc2) split their INPUT dim and close with
#: the block's one psum; everything else (LayerNorms, row biases,
#: embeddings, the LM head) is replicated.
_DECODE_COL_W = ("wq", "wk", "wv", "w1")
_DECODE_COL_B = ("bq", "bk", "bv", "b1")
_DECODE_ROW_W = ("wo", "w2")


#: MoE expert-weight keys: stacked (E, ...) arrays whose LEADING
#: expert axis shards over the serve ``ep`` axis (serve/ep.py); the
#: router ``moe_wg`` stays replicated (tiny, and every rank routes).
_DECODE_EXPERT_W = ("moe_w1", "moe_b1", "moe_w2", "moe_b2")


def decode_param_specs(params, axis=MODEL, ep_axis=None):
    """PartitionSpec pytree (same structure as ``params``) laying an
    ``extract_params`` decode pytree out Megatron-style over ``axis``:
    attention heads + MLP columns partitioned, out-proj/fc2 row-
    partitioned, embeddings/norms/head replicated.  MoE blocks shard
    their stacked expert weights over ``ep_axis`` (the serve
    expert-parallel backend, singa_tpu/serve/ep.py) — without one they
    are rejected here so the failure is a typed construction error
    naming the ``serve(ep=)`` path, not a shape mismatch deep inside a
    shard_map trace."""
    stacked = isinstance(params["blocks"], dict)
    blocks = []
    for li, blk in enumerate([params["blocks"]] if stacked
                             else params["blocks"]):
        if "moe_wg" in blk and ep_axis is None:
            raise NotImplementedError(
                f"block {li} is an MoE block: expert weights shard "
                f"over the expert axis, not the tensor-parallel axis "
                f"— serve this model with model.serve(ep=EPConfig("
                f"ep=, tp=)) (singa_tpu/serve/ep.py: expert-parallel "
                f"decode; tp= covers dense/GQA models only)")
        spec = {}
        for k in blk:
            if k in _DECODE_COL_W:
                spec[k] = P(None, axis)
            elif k in _DECODE_COL_B:
                spec[k] = P(axis)
            elif k in _DECODE_ROW_W:
                spec[k] = P(axis, None)
            elif k in _DECODE_EXPERT_W:
                spec[k] = P(ep_axis)
            else:
                spec[k] = P()
            if stacked:          # a leading layer axis, never sharded
                spec[k] = P(None, *spec[k])
        blocks.append(spec)
    out = {k: (None if v is None else P())
           for k, v in params.items() if k != "blocks"}
    out["blocks"] = blocks[0] if stacked else blocks
    return out


def decode_cache_spec(axis=MODEL):
    """PartitionSpec for every slot-arena and cache-row leaf the serve
    engine owns — slot arenas ``(L, S, H_kv, W, D)``, cache rows
    ``(L, 1, H_kv, W, D)`` and their trailing-axis-free int8 scales
    leaves: the KV-HEAD axis (always axis 2) shards over ``axis``,
    everything else stays local.  One spec serves every leaf rank
    because PartitionSpec trailing dims default to unsharded."""
    return P(None, None, axis)


def decode_pool_spec(axis=MODEL):
    """PartitionSpec for every leaf of a paged block pool — values
    ``(L, num_blocks+1, B, H_kv·D)``, int8 scales
    ``(L, num_blocks+1, B, H_kv)``: a row is the K/V heads side by
    side, so the LAST axis shards over ``axis`` (contiguous heads a
    shard, the same heads as :func:`decode_cache_spec` gives it of a
    cache row)."""
    return P(None, None, None, axis)


class ColumnParallelLinear(Layer):
    """y = x W + b with W's OUTPUT dim sharded over ``model``.

    ``gather_output=False`` (default) leaves y sharded on its last dim —
    feed it to a RowParallelLinear or another column-sharded consumer."""

    def __init__(self, out_features, plan: ShardingPlan | None = None,
                 bias=True, gather_output=False):
        super().__init__()
        self.out_features = int(out_features)
        self.plan = plan
        self.bias = bool(bias)
        self.gather_output = bool(gather_output)

    def initialize(self, x):
        in_features = x.shape[-1]
        dt = amp.param_dtype(x.data.dtype)
        self.W = Tensor((in_features, self.out_features), device=x.device,
                        dtype=dt, requires_grad=True, stores_grad=True)
        initializer.xavier(self.W)
        self.W.partition_spec = P(None, MODEL)
        if self.bias:
            self.b = Tensor((self.out_features,), device=x.device, dtype=dt,
                            requires_grad=True, stores_grad=True)
            self.b.set_value(0.0)
            self.b.partition_spec = P(MODEL)

    def forward(self, x):
        y = autograd.matmul(x, self.W)
        if self.bias:
            y = autograd.add_bias(y, self.b, axis=0)
        if self.plan is not None:
            spec = self.plan.act_spec(len(y.shape),
                                      model_last=not self.gather_output)
            y = constrain(y, self.plan, spec)
        return y


class RowParallelLinear(Layer):
    """y = x W + b with W's INPUT dim sharded over ``model``; closes a
    column-parallel pair — XLA emits the single psum here."""

    def __init__(self, out_features, plan: ShardingPlan | None = None,
                 bias=True):
        super().__init__()
        self.out_features = int(out_features)
        self.plan = plan
        self.bias = bool(bias)

    def initialize(self, x):
        in_features = x.shape[-1]
        dt = amp.param_dtype(x.data.dtype)
        self.W = Tensor((in_features, self.out_features), device=x.device,
                        dtype=dt, requires_grad=True, stores_grad=True)
        initializer.xavier(self.W)
        self.W.partition_spec = P(MODEL, None)
        if self.bias:
            # bias is applied AFTER the reduction — replicated
            self.b = Tensor((self.out_features,), device=x.device, dtype=dt,
                            requires_grad=True, stores_grad=True)
            self.b.set_value(0.0)

    def forward(self, x):
        y = autograd.matmul(x, self.W)
        if self.bias:
            y = autograd.add_bias(y, self.b, axis=0)
        if self.plan is not None:
            y = constrain(y, self.plan,
                          self.plan.act_spec(len(y.shape), model_last=False))
        return y


class VocabParallelEmbedding(Layer):
    """Embedding table with vocab rows sharded over ``model``."""

    def __init__(self, vocab_size, embed_dim,
                 plan: ShardingPlan | None = None, std=0.02):
        super().__init__()
        self.vocab_size = int(vocab_size)
        self.embed_dim = int(embed_dim)
        self.plan = plan
        self.std = float(std)

    def initialize(self, ids):
        self.W = Tensor((self.vocab_size, self.embed_dim), device=ids.device,
                        requires_grad=True, stores_grad=True)
        self.W.gaussian(0.0, self.std)
        self.W.partition_spec = P(MODEL, None)

    def forward(self, ids):
        e = autograd.embedding(ids, self.W)
        if self.plan is not None:
            e = constrain(e, self.plan, self.plan.act_spec(len(e.shape)))
        return e


class ParallelMLP(Layer):
    """Transformer FFN: column fc1 → activation → row fc2 (one psum)."""

    def __init__(self, hidden, intermediate, plan: ShardingPlan | None = None,
                 activation="gelu"):
        super().__init__()
        self.fc1 = ColumnParallelLinear(intermediate, plan)
        self.fc2 = RowParallelLinear(hidden, plan)
        self.activation = activation

    def forward(self, x):
        h = self.fc1(x)
        h = getattr(autograd, self.activation)(h)
        return self.fc2(h)


class ParallelMHA(Layer):
    """Multi-head attention with heads sharded over ``model``.

    q/k/v projections are column-parallel (head dim ⊂ feature dim, so the
    per-head split is a local reshape of the sharded feature axis); the
    output projection is row-parallel.  With a real ``seq`` mesh axis and
    ``seq_parallel=True``, the score/value contraction runs as ring
    attention (parallel/ring_attention.py) over the ICI ring — activations
    stay sharded (B@data, H@model, S@seq, D) end to end, so max sequence
    length scales with the seq-axis size (the long-context design the
    reference lacks, SURVEY.md §5.7).

    ``num_kv_heads`` < ``num_heads`` gives grouped-query attention
    (GQA): k/v project to ``num_kv_heads`` heads which each serve a
    contiguous group of ``num_heads // num_kv_heads`` query heads.  In
    training the K/V heads are broadcast up to the full head count
    before the score contraction (the RepeatKV op — GQA's training
    FLOPs match MHA; the win is the num_heads/num_kv_heads× smaller
    K/V cache at inference, where decode is cache-read-bound — see
    models/gpt2_decode.py)."""

    def __init__(self, num_heads, plan: ShardingPlan | None = None,
                 dropout=0.0, seq_parallel=None, causal=False,
                 remat=False, use_flash=False, num_kv_heads=None,
                 window=None):
        super().__init__()
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads or num_heads)
        if self.num_heads % self.num_kv_heads != 0:
            raise ValueError(
                f"num_heads {self.num_heads} not divisible by "
                f"num_kv_heads {self.num_kv_heads}")
        if window is not None and (not causal or int(window) < 1):
            raise ValueError("window requires causal attention and "
                             f"window >= 1, got {window} "
                             f"(causal={causal})")
        self.window = None if window is None else int(window)
        self.plan = plan
        self.dropout = float(dropout)
        self.causal = bool(causal)
        self.remat = bool(remat)
        self.use_flash = bool(use_flash)
        if seq_parallel is None:
            seq_parallel = plan is not None and plan.axis_size(SEQ) > 1
        self.seq_parallel = bool(seq_parallel)
        self.q_proj = ColumnParallelLinear(0, plan)
        self.k_proj = ColumnParallelLinear(0, plan)
        self.v_proj = ColumnParallelLinear(0, plan)
        self.out_proj = RowParallelLinear(0, plan)
        if plan is not None:
            for what, n in (("num_heads", self.num_heads),
                            ("num_kv_heads", self.num_kv_heads)):
                if n % plan.axis_size(MODEL) != 0:
                    raise ValueError(
                        f"{what} {n} not divisible by model-axis "
                        f"size {plan.axis_size(MODEL)}")

    def initialize(self, x, mask=None):
        e = x.shape[-1]
        if e % self.num_heads != 0:
            raise ValueError(
                f"embed dim {e} not divisible by num_heads {self.num_heads}")
        e_kv = (e // self.num_heads) * self.num_kv_heads
        for proj in (self.q_proj, self.out_proj):
            proj.out_features = e
        for proj in (self.k_proj, self.v_proj):
            proj.out_features = e_kv

    def _heads_spec(self):
        # (B, H, S, D): batch@data, heads@model, seq@seq when ring
        return P(DATA, MODEL, SEQ if self.seq_parallel else None, None)

    def forward(self, x, mask=None):
        b, s, e = x.shape
        h = self.num_heads
        h_kv = self.num_kv_heads
        d = e // h
        plan = self.plan

        def split_heads(t, nh):
            t = autograd.reshape(t, (b, s, nh, d))
            t = autograd.transpose(t, (0, 2, 1, 3))
            if nh != h:  # GQA: broadcast each K/V head over its Q group
                t = autograd.repeat_kv(t, h // nh)
            if plan is not None:
                t = constrain(t, plan, self._heads_spec())
            return t

        q = split_heads(self.q_proj(x), h)
        k = split_heads(self.k_proj(x), h_kv)
        v = split_heads(self.v_proj(x), h_kv)

        if self.seq_parallel and plan is not None \
                and sharding.plan_active():
            if self.window is not None:
                raise NotImplementedError(
                    "sliding-window attention is not implemented on "
                    "the ring sequence-parallel path (a band never "
                    "needs most of the ring's hops — use a plan "
                    "without a seq axis for windowed models, or drop "
                    "window for ring attention)")
            # use_flash composes here: inside shard_map the Pallas
            # kernel runs per device (manual mode), so each ring step's
            # local-Q x visiting-K/V attention is the flash kernel
            ctx = _ring_attention_op(q, k, v, mask, plan, self.causal,
                                     use_flash=self.use_flash)
        else:
            # pallas_call has no GSPMD partitioning rule: under an active
            # sharded plan WITHOUT a seq axis the fused einsum path
            # (auto-partitioned head-locally) is the correct kernel —
            # warn and fall back so an auto-selected attn_impl keeps
            # training (with a seq axis, the branch above runs the
            # flash kernel per ring step inside shard_map)
            use_flash = self.use_flash and not (
                plan is not None and sharding.plan_active())
            if self.use_flash and not use_flash \
                    and not getattr(self, "_warned_flash", False):
                self._warned_flash = True
                logging.getLogger("singa_tpu").warning(
                    "ParallelMHA: use_flash ignored under an active "
                    "ShardingPlan without a seq axis (no GSPMD rule "
                    "for pallas_call outside shard_map); using the "
                    "fused head-sharded path — shard the seq axis to "
                    "get ring attention with per-shard flash kernels")
            ctx = _sdpa(q, k, v, mask, self.causal, remat=self.remat,
                        use_flash=use_flash, window=self.window)
        ctx = autograd.transpose(ctx, (0, 2, 1, 3))
        ctx = autograd.reshape(ctx, (b, s, e))
        if plan is not None:
            ctx = constrain(ctx, plan,
                            plan.act_spec(3, model_last=True))
        if self.dropout > 0:
            ctx = autograd.dropout(ctx, self.dropout)
        return self.out_proj(ctx)


class ParallelTransformerBlock(Layer):
    """Pre-LN transformer block from the parallel pieces: exactly two
    psums over ``model`` per block (attention out-proj + MLP fc2)."""

    def __init__(self, num_heads, intermediate, plan=None, dropout=0.0,
                 causal=False, eps=1e-5, moe_experts=None, moe_top_k=2,
                 moe_capacity_factor=1.25, moe_groups=None, remat=False,
                 use_flash=False, num_kv_heads=None, window=None):
        super().__init__()
        from ..layer import LayerNorm

        self.ln1 = LayerNorm(eps)
        self.attn = ParallelMHA(num_heads, plan, dropout=dropout,
                                causal=causal, remat=remat,
                                use_flash=use_flash,
                                num_kv_heads=num_kv_heads,
                                window=window)
        self.ln2 = LayerNorm(eps)
        self.mlp = None  # needs hidden size; built at initialize
        self._intermediate = int(intermediate)
        self._plan = plan
        self._dropout = float(dropout)
        self._moe = (None if moe_experts is None
                     else (int(moe_experts), int(moe_top_k),
                           float(moe_capacity_factor), moe_groups))
        self._remat = bool(remat)

    def initialize(self, x, mask=None):
        hidden = x.shape[-1]
        if self._moe is not None:
            from .moe import MoEFFN

            e, k, cf, g = self._moe
            self.mlp = MoEFFN(e, self._intermediate, self._plan,
                              top_k=k, capacity_factor=cf, groups=g,
                              remat=self._remat)
        else:
            self.mlp = ParallelMLP(hidden, self._intermediate, self._plan)

    @property
    def aux_loss(self):
        """Taped MoE load-balance loss from the last forward (None for a
        dense block)."""
        return getattr(self.mlp, "last_aux_loss", None)

    def forward(self, x, mask=None):
        a = self.attn(self.ln1(x), mask)
        if self._dropout > 0:
            a = autograd.dropout(a, self._dropout)
        x = autograd.add(x, a)
        m = self.mlp(self.ln2(x))
        if self._dropout > 0:
            m = autograd.dropout(m, self._dropout)
        return autograd.add(x, m)


# ---------------------------------------------------------------------------
# attention kernels (taped)
# ---------------------------------------------------------------------------

def _sdpa(q, k, v, mask, causal, remat=False, use_flash=False,
          window=None):
    """Plain scaled-dot-product attention (B,H,S,D); heads may be sharded
    — the einsums are head-local so GSPMD keeps them collective-free.
    scale/causal/window ride op.params for sonnx's decomposed export;
    remat recomputes the S x S tensors in backward (jax.checkpoint);
    use_flash routes to the Pallas online-softmax kernel, whose HBM
    footprint is O(S·D) instead of O(S²) (the long-context lever —
    see LONGCTX.json for the measured crossover).

    ``window`` (causal only): sliding-window attention — query i sees
    keys in [i-window+1, i] (Mistral-style band).  The band is built
    in-kernel (XLA fuses it into the softmax chain; nothing extra in
    HBM).  The matching decode side keeps an O(window) rolling KV
    cache (models/gpt2_decode.py)."""
    if use_flash:
        from ..ops.pallas.flash_attention import flash_attention_op

        return flash_attention_op(q, k, v, mask, causal=causal,
                                  remat=remat, window=window)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def f(qv, kv, vv, *rest, scale, causal, window):
        sc = jnp.einsum("bhsd,bhtd->bhst", qv, kv) * scale
        if rest:
            sc = sc + rest[0]
        if causal:
            s_, t_ = sc.shape[-2:]
            cm = jnp.tril(jnp.ones((s_, t_), bool))
            if window is not None:
                i = jnp.arange(s_)[:, None]
                j = jnp.arange(t_)[None, :]
                cm = cm & (i - j < window)
            sc = jnp.where(cm[None, None], sc, -1e30)
        p = jnp.exp(sc - sc.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return jnp.einsum("bhst,bhtd->bhsd", p, vv)

    xs = (q, k, v) if mask is None else (q, k, v, mask)
    apply = autograd.checkpoint_op if remat else autograd._op
    return apply(f, *xs, _name="TPAttention", scale=scale,
                 causal=causal, window=window)


def _ring_attention_op(q, k, v, mask, plan, causal, use_flash=False):
    """Ring attention as a taped op: shard_map over the FULL mesh with
    (B@data, H@model, S@seq, D) blocks; the K/V ring rotates over the
    ``seq`` axis only (lax.ppermute — the one collective XLA cannot
    infer).  Differentiable end-to-end (scan+ppermute have exact VJPs).

    ``mask`` (optional): a (B, 1, 1, S) additive key-padding mask; its
    key dim is sequence-sharded and rotates around the ring with K/V.
    Masks with a query dim (full (B,H,S,S) biases) are not expressible
    blockwise here — use seq_parallel=False for those."""
    import jax

    from .ring_attention import (ring_self_attention,
                                 zigzag_repartition,
                                 zigzag_ring_self_attention)

    spec = P(DATA, MODEL, SEQ, None)
    seq_world = plan.axis_size(SEQ)
    s_local = q.shape[2] // seq_world
    if causal and mask is None and seq_world > 1 and s_local % 2 == 0:
        # round 5: causal rings run the load-BALANCED zigzag layout —
        # repartition the contiguous-sharded blocks in (one hop of
        # wire each way), attend balanced, repartition back.  The
        # contiguous causal ring below is kept for odd local lengths
        # and masked/non-causal cases.
        def zz(q_, k_, v_):
            q_ = zigzag_repartition(q_, SEQ)
            k_ = zigzag_repartition(k_, SEQ)
            v_ = zigzag_repartition(v_, SEQ)
            # per-hop checkpointing stays ON (the zigzag callee's
            # default): it is the ring path's O(S_local·D) backward-
            # memory guarantee, deliberately NOT governed by
            # ParallelMHA.remat (which checkpoints the non-seq _sdpa
            # internals) — same contract as the contiguous ring below
            o = zigzag_ring_self_attention(q_, k_, v_, SEQ,
                                           use_flash=use_flash)
            return zigzag_repartition(o, SEQ, inverse=True)

        f = jax.shard_map(zz, mesh=plan.mesh,
                          in_specs=(spec, spec, spec), out_specs=spec,
                          check_vma=False)
        return autograd._op(f, q, k, v, _name="ZigzagRingAttention")
    if mask is not None:
        if mask.shape[-2] != 1:
            raise NotImplementedError(
                "ring attention supports key-padding masks (B,1,1,S); "
                "per-query masks need seq_parallel=False")
        mspec = P(DATA, None, None, SEQ)
        f = jax.shard_map(
            lambda q_, k_, v_, m_: ring_self_attention(
                q_, k_, v_, SEQ, causal=causal, kv_mask=m_,
                use_flash=use_flash),
            mesh=plan.mesh, in_specs=(spec, spec, spec, mspec),
            out_specs=spec, check_vma=False)
        return autograd._op(f, q, k, v, mask, _name="RingAttention")
    f = jax.shard_map(
        lambda q_, k_, v_: ring_self_attention(q_, k_, v_, SEQ,
                                               causal=causal,
                                               use_flash=use_flash),
        mesh=plan.mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return autograd._op(f, q, k, v, _name="RingAttention")
