"""A latent-attention, sparse-expert decoder (the DeepSeek-V3 block, as
``model_type`` ``dots_vlm``'s language model publishes it:
huggingface.co/rednote-hilab/dots.vlm1.inst; the equations are written
out key by key in ``benchmark/references/mla_moe.py``, the plain
reference the tests hold this file to).

Every layer is multi-head latent attention (MLA) and a feed-forward:
SwiGLU in the ``first_k_dense_replace`` leading layers, a routed expert
layer beside one shared expert in the others.  What is here is the
SERVING side: ``MlaMoeLMHead(cfg).serve(paged=...)`` returns the one
:class:`~singa_tpu.serve.InferenceEngine`, which calls the math below
through :class:`MlaMoeFamily` (models/served.py).

**The cache is one row a position a layer** (``value_leaf = False``):
the RMS-normalised K/V latent ``c_kv`` (``kv_lora_rank`` wide) beside
the one rotated key ``k_r`` (``qk_rope_head_dim`` wide) that all heads
share -- 576 values where 128 heads' keys and values would be 49,152.
The engine's pool holds that row and no second leaf.  Attention against
it takes the ABSORBED form: a head's query is carried into the latent
(``q_lat = q_nope W_kb``), all heads are the rows of one matmul against
a block of latent rows, the value is the first ``kv_lora_rank`` values
of the same row (``ops/paged_attention.paged_attn`` with ``v_dim``),
and the result is carried back out through the value half of ``kv_b``.
A decode step and a prefill chunk row both run it (a chunk of 128
queries against a block of 128 rows costs 4.5 GFLOP a layer absorbed,
5.6 with keys and values expanded per head first).  ``forward_full``,
the cacheless whole-sequence pass behind ``Model.forward``, is the
EXPANDED form -- per-head keys and values from ``c_kv`` -- and the tests
hold the two to each other and both to the reference.

**The expert layer is told which experts it holds**
(``cfg.experts_held``, ``ops/expert_layer.py``): the router keeps all
``n_routed_experts`` outputs and its ``num_experts_per_tok`` choices;
this chip computes its own experts' terms.

Identical layers are kept as STACKED weights and every program scans
them: the dense layers as one stack, the expert layers as another, so a
program compiles two layer bodies whatever the depth.  Matrices are
built in ``cfg.dtype``; the per-channel vectors, the router and its bias
stay float32.  Training is not here (ROADMAP Reach A).
"""

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd, model
from ..ops.expert_layer import held_terms, route, swiglu
from ..ops.paged_attention import (paged_attn, paged_decode_attn, rotary,
                                   write_rows, yarn_frequencies)
from ..tensor import Tensor
from .served import ServedFamily, seg_cat, seg_split, seg_tokens, seg_valid

#: a layer's float32 tensors (whatever ``cfg.dtype``), by kind of layer
_ATTN_VECTORS = ("ln1", "q_norm", "kv_norm", "ln2")
_ATTN_MATRICES = ("w_qa", "w_qn", "w_qr", "w_kva", "w_kb", "w_vb", "wo")
_KINDS = {
    "dense": (_ATTN_VECTORS, _ATTN_MATRICES + ("w_gu", "w_down")),
    "moe": (_ATTN_VECTORS + ("router", "bias"),
            _ATTN_MATRICES + ("e_gu", "e_down", "s_gu", "s_down")),
}


@dataclass(frozen=True)
class MlaMoeConfig:
    """The published ``config.json`` keys that shape the language model,
    plus ``experts_held`` -- the ownership range ``(first, end)`` of the
    router's outputs whose experts this chip holds -- ``max_len``, the
    served context, and ``dtype``, what the matrices are built in."""

    vocab_size: int = 129280
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: tuple = (("beta_fast", 32), ("beta_slow", 1),
                           ("factor", 40), ("mscale", 1),
                           ("mscale_all_dim", 1),
                           ("original_max_position_embeddings", 4096),
                           ("type", "yarn"))
    max_position_embeddings: int = 163840
    experts_held: tuple = (0, 256)
    max_len: int = 2048
    dtype: str = "float32"

    def __post_init__(self):
        rs = self.rope_scaling
        object.__setattr__(self, "rope_scaling", tuple(sorted(
            (rs.items() if isinstance(rs, dict) else rs))))
        object.__setattr__(self, "experts_held",
                           tuple(int(v) for v in self.experts_held))
        for k in ("rope_theta", "rms_norm_eps", "routed_scaling_factor"):
            object.__setattr__(self, k, float(getattr(self, k)))
        if dict(self.rope_scaling).get("type") != "yarn":
            raise ValueError("rope_scaling.type must be 'yarn'")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the router's "
                             f"{self.n_routed_experts} outputs")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")
        if not 0 < self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError("needs leading dense layers, then expert "
                             "layers")
        if self.max_len > self.max_position_embeddings:
            raise ValueError("max_len exceeds max_position_embeddings")

    # the engine's names for what it reads off any model's cfg
    n_layer = property(lambda s: s.num_hidden_layers)
    n_head = property(lambda s: s.num_attention_heads)
    n_embd = property(lambda s: s.hidden_size)
    n_positions = property(lambda s: s.max_len)
    layer_norm_eps = property(lambda s: s.rms_norm_eps)
    n_dense = property(lambda s: s.first_k_dense_replace)
    n_moe = property(lambda s: s.num_hidden_layers
                     - s.first_k_dense_replace)
    n_held = property(lambda s: s.experts_held[1] - s.experts_held[0])
    row_width = property(lambda s: s.kv_lora_rank + s.qk_rope_head_dim)
    # what a pool row is stored as: whole 128-lane tiles (see the
    # module docstring)
    pool_row_width = property(lambda s: -(-s.row_width // 128) * 128)

    @property
    def softmax_scale(self):
        """``(nope + rope)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim *
        ln(factor) + 1``."""
        rs = dict(self.rope_scaling)
        m = 1.0
        if rs["factor"] > 1 and rs["mscale_all_dim"]:
            m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m

    def rope_frequencies(self):
        rs = dict(self.rope_scaling)
        return yarn_frequencies(
            self.qk_rope_head_dim, self.rope_theta, rs["factor"],
            rs["beta_fast"], rs["beta_slow"],
            rs["original_max_position_embeddings"])

    def shapes(self, kind):
        """{tensor: shape} of one layer of ``kind`` ("dense", "moe"), or
        of the three tensors outside the layers ("model")."""
        c, e, h = self, self.hidden_size, self.num_attention_heads
        if kind == "model":
            return dict(wte=(c.vocab_size, e), head=(e, c.vocab_size),
                        lnf=(e,))
        nope, rope, r = (c.qk_nope_head_dim, c.qk_rope_head_dim,
                         c.kv_lora_rank)
        out = dict(
            ln1=(e,), ln2=(e,), q_norm=(c.q_lora_rank,), kv_norm=(r,),
            w_qa=(e, c.q_lora_rank),
            w_qn=(h * nope, c.q_lora_rank), w_qr=(h * rope, c.q_lora_rank),
            w_kva=(e, r + rope), w_kb=(h, nope, r),
            w_vb=(h, r, c.v_head_dim), wo=(h * c.v_head_dim, e))
        if kind == "dense":
            out.update(w_gu=(e, 2 * c.intermediate_size),
                       w_down=(c.intermediate_size, e))
        else:
            im = c.moe_intermediate_size
            sh = im * c.n_shared_experts
            out.update(router=(e, c.n_routed_experts),
                       bias=(c.n_routed_experts,),
                       e_gu=(c.n_held, e, 2 * im),
                       e_down=(c.n_held, im, e),
                       s_gu=(e, 2 * sh), s_down=(sh, e))
        return out


# --------------------------------------------------------------------- math


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _queries_and_row(h, p, c, pos):
    """h (T, E) normalised, at positions ``pos`` (T,) -> the heads'
    queries before absorption ``q_nope`` (T, H, nope), their rotated
    part ``q_r`` (H, T, rope), and the row to cache (T, pool_row_width):
    ``c_kv`` after its norm beside ``k_r`` after rotation, zeros to the
    tile.  The rope columns of ``w_qr`` and ``w_kva`` are stored in the
    half-split order (the published code permutes the activations from
    interleaved pairs to that order before it turns them; here the
    permutation is the loader's, made once on the weights' columns).
    ``w_qn`` and ``w_qr`` are stored (out, in): the compiler otherwise
    transposes each out of the layer stack, every step."""
    t, r = h.shape[0], c.kv_lora_rank
    freq = c.rope_frequencies()
    cq = _rms(h @ p["w_qa"], p["q_norm"], c.rms_norm_eps)
    q_nope = jnp.einsum("tq,nq->tn", cq, p["w_qn"]).reshape(
        t, c.n_head, -1)
    q_r = rotary(jnp.einsum("tq,nq->tn", cq, p["w_qr"]).reshape(
        t, c.n_head, -1).transpose(1, 0, 2), pos, freq)
    kv = h @ p["w_kva"]
    k_r = rotary(kv[None, :, r:], pos, freq)[0]
    row = jnp.concatenate(
        [_rms(kv[:, :r], p["kv_norm"], c.rms_norm_eps), k_r,
         jnp.zeros((t, c.pool_row_width - c.row_width), k_r.dtype)],
        axis=-1)
    return q_nope, q_r, row


def _absorb(q_nope, q_r, p, c):
    """Queries carried into the latent: (T, H, nope), (H, T, rope) ->
    (H, T, pool_row_width), to meet cached rows as they are stored."""
    q_lat = jnp.einsum("thd,hdc->htc", q_nope, p["w_kb"])
    pad = jnp.zeros(q_lat.shape[:2] + (c.pool_row_width - c.row_width,),
                    q_lat.dtype)
    return jnp.concatenate([q_lat, q_r.astype(q_lat.dtype), pad], axis=-1)


def _attn_out(o_lat, p, x_dtype):
    """Attention's result in the latent (T, H, kv_lora_rank) -> carried
    back out through the value half of kv_b, heads concatenated, through
    W_o: (T, E)."""
    o = jnp.einsum("thc,hcd->thd", o_lat.astype(x_dtype), p["w_vb"])
    return o.reshape(o.shape[0], -1) @ p["wo"]


def _ffn(x, p, c, kind, li, valid):
    """The layer's feed-forward on ``x`` (T, E) after ln2: ``(y (T, E),
    counts)``; counts None for a dense layer.  ``li`` indexes the expert
    stack (the matrices of ``p`` are this layer's, the experts' are the
    whole stack's: ops/expert_layer.held_terms slices an expert out)."""
    g = _rms(x, p["ln2"], c.rms_norm_eps)
    if kind == "dense":
        with jax.named_scope("dense_mlp"):
            return swiglu(g, p["w_gu"], p["w_down"]).astype(x.dtype), None
    with jax.named_scope("moe_route"):
        idx, w = route(g, p["router"], p["bias"], n_group=c.n_group,
                       topk_group=c.topk_group,
                       top_k=c.num_experts_per_tok,
                       scale=c.routed_scaling_factor)
    with jax.named_scope("moe_experts"):
        y, counts = held_terms(g, idx, w, p["e_gu"], p["e_down"],
                               c.experts_held[0], valid, layer=li)
    with jax.named_scope("moe_shared"):
        y = y + swiglu(g, p["s_gu"], p["s_down"])
    return y.astype(x.dtype), counts


def _scan_layers(body, carry, params, c):
    """Run ``body(carry, kind, pool layer, stack layer, p) -> (carry,
    counts)`` over the dense stack and then the expert stack.  The
    experts' matrices are not scanned over: the body reaches into their
    whole stack (so no layer's 16 experts are sliced out as a copy).
    Returns (carry, counts (n_moe, n_held + 1))."""
    counts = None
    for kind, first, n in (("dense", 0, c.n_dense),
                           ("moe", c.n_dense, c.n_moe)):
        stack = params[kind]
        whole = {k: stack[k] for k in ("e_gu", "e_down") if k in stack}
        xs = {k: v for k, v in stack.items() if k not in whole}

        def step(carry, lp, kind=kind, first=first, whole=whole):
            i, p = lp
            return body(carry, kind, first + i, i, dict(p, **whole))

        carry, out = jax.lax.scan(step, carry, (jnp.arange(n), xs))
        if kind == "moe":
            counts = out
    return carry, counts


def forward_full(params, ids, c):
    """ids (S,) -> logits (S, V): the whole sequence at once, no cache,
    attention in its EXPANDED form -- every head's keys and values made
    from ``c_kv``.  What ``Model.forward`` runs; serving goes through
    the family."""
    s = ids.shape[0]
    pos = jnp.arange(s)
    x = params["wte"][ids]
    causal = jnp.tril(jnp.ones((s, s), bool))
    r = c.kv_lora_rank

    def layer(x, kind, _li, i, p):
        h = _rms(x, p["ln1"], c.rms_norm_eps)
        q_nope, q_r, row = _queries_and_row(h, p, c, pos)
        c_kv, k_r = row[:, :r], row[:, r:c.row_width]
        f32 = jnp.float32
        k_nope = jnp.einsum("tc,hdc->htd", c_kv, p["w_kb"]).astype(f32)
        v = jnp.einsum("tc,hcd->htd", c_kv, p["w_vb"]).astype(f32)
        sc = jnp.einsum("shd,htd->hst", q_nope.astype(f32), k_nope) \
            + jnp.einsum("hsd,td->hst", q_r.astype(f32), k_r.astype(f32))
        pr = jax.nn.softmax(jnp.where(causal, sc * c.softmax_scale,
                                      -jnp.inf), axis=-1)
        o = jnp.einsum("hst,htd->shd", pr, v).reshape(s, -1)
        x = x + o.astype(x.dtype) @ p["wo"]
        y, counts = _ffn(x, p, c, kind, i, None)
        return x + y, counts

    x, _ = _scan_layers(layer, x, params, c)
    return _logits(params, _rms(x, params["lnf"], c.rms_norm_eps))


def _logits(params, hidden):
    """(..., E) -> (..., V), accumulated and returned in float32."""
    return jnp.dot(hidden, params["head"],
                   preferred_element_type=jnp.float32)


# ----------------------------------------------------- the served contract


@dataclass(frozen=True)
class MlaMoeFamily(ServedFamily):
    """The family for the serve engine (models/served.py): the budgeted
    paged path over a one-leaf latent pool.  Hashes by its
    configuration, so equal models share compiled programs."""

    cfg: MlaMoeConfig

    name = "mla_moe"
    features = frozenset()
    value_leaf = False
    pad_aware = True
    step_counts = True
    # mla_attn: the absorbed attention against cached rows and the write
    # of the new ones; mla_proj: the projections either side of it
    scopes = ("mla_attn", "mla_proj", "moe_route", "moe_experts",
              "moe_shared", "dense_mlp", "head")

    def extract_params(self, m, dtype=None):
        st = {k.rsplit(".", 1)[-1]: t.data
              for k, t in m.get_states().items()}
        if not st:
            raise RuntimeError("model not initialized: call compile() "
                               "or run one forward first")
        cast = (lambda a: a) if dtype is None else \
            (lambda a: a.astype(dtype))
        out = dict(wte=cast(st["wte"]), head=cast(st["head"]),
                   lnf=st["lnf"])
        for kind, (vectors, matrices) in _KINDS.items():
            out[kind] = {k: (st[f"{kind}_{k}"] if k in vectors
                             else cast(st[f"{kind}_{k}"]))
                         for k in vectors + matrices}
        return out

    def kv_geometry(self, cfg):
        """One "head" whose "size" is the whole cached row."""
        return cfg.n_layer, 1, cfg.pool_row_width

    def logits(self, params, hidden):
        with jax.named_scope("head"):
            return _logits(params, hidden)

    def on_step_counts(self, counts, cfg):
        held = counts[:, :-1]
        first = cfg.experts_held[0]
        incs = {("serve.moe.expert_tokens",
                 (("expert", str(first + e)),)): int(n)
                for e, n in enumerate(held.sum(0))}
        incs["serve.moe.assignments_elsewhere", ()] = int(
            counts[:, -1].sum())
        return dict(experts_hit=int(np.count_nonzero(held)),
                    expert_tokens_max=int(held.max()),
                    expert_tokens_mean=float(held.mean())), incs

    def chunk_rows(self, params, segs, *, block, **_):
        """One launch: each segment a whole number of blocks of one
        request: its queries, absorbed, over its private latent row
        below its ``off`` (block by block, the shared loop) and over its
        own rows; the new rows written into that row.  ``vc_row`` is
        None: the row is key and value at once.  What follows a
        segment's ``n_valid`` chooses no expert: its rows are never
        read, and a run of like tokens that all chose one held expert
        cost that expert further tiles.  Projections and the
        feed-forward take the segments' tokens together."""
        c = self.cfg
        valid = None if segs[0].n_valid is None else seg_valid(segs)
        toks, pos = seg_tokens(segs)
        x = jnp.take(params["wte"], toks, axis=0)
        n_l, _, _, width, d = segs[0].kc_row.shape
        # what lies below off, as the blocks of a pool (one head: a
        # reshape)
        below = [s.kc_row.reshape(n_l, width // block, block, d)
                 for s in segs]
        tbl = jnp.arange(width // block)
        cur = [jnp.tril(jnp.ones((s.chunk, s.chunk), bool)) for s in segs]

        def layer(carry, kind, li, i, p):
            x, kc_rows = carry
            kc_rows = list(kc_rows)
            h = _rms(x, p["ln1"], c.rms_norm_eps)
            with jax.named_scope("mla_proj"):
                q_nope, q_r, row = _queries_and_row(h, p, c, pos)
                q = _absorb(q_nope, q_r, p, c)
            with jax.named_scope("mla_attn"):
                o_lat = []
                for n, (s, q_s, row_s) in enumerate(zip(
                        segs, seg_split(q, segs, 1), seg_split(row, segs))):
                    o_lat.append(paged_attn(
                        q_s[None], below[n], None, li, tbl, s.off,
                        s.off // block, block, -1, row_s, None, cur[n],
                        c.softmax_scale, v_dim=c.kv_lora_rank)[0])
                    kc_rows[n] = jax.lax.dynamic_update_slice(
                        kc_rows[n],
                        row_s[None, None, None].astype(kc_rows[n].dtype),
                        (li, 0, 0, s.off, 0))
                o_lat = seg_cat(o_lat, 1)
            with jax.named_scope("mla_proj"):
                x = x + _attn_out(o_lat.transpose(1, 0, 2), p, x.dtype)
            y, counts = _ffn(x, p, c, kind, i, valid)
            return (x + y, tuple(kc_rows)), counts

        (x, kc_rows), _ = _scan_layers(
            layer, (x, tuple(s.kc_row for s in segs)), params, c)
        hidden = _rms(x, params["lnf"], c.rms_norm_eps)
        return [(h[None], kc, None, None)
                for h, kc in zip(seg_split(hidden, segs), kc_rows)]

    def decode_step(self, params, pool_k, pool_v, state, slots, tables,
                    toks, pos, live, n_blk, *, block, trash, **_):
        """Every lane one token: per layer, each lane's 128 absorbed
        queries over its live blocks of the latent pool plus its own new
        row (the shared loop), the new row written straight into the
        pool, which is carried through the layer scans and updated in
        place.  ``pool_v`` is None.  Returns the experts' counts of the
        step after the contract's four."""
        c = self.cfg
        p_c = jnp.where(live, pos, 0)
        t_c = jnp.where(live, toks, 0)
        x = params["wte"][t_c]                               # (W, E)

        def layer(carry, kind, li, i, p):
            x, pool_k = carry
            h = _rms(x, p["ln1"], c.rms_norm_eps)
            with jax.named_scope("mla_proj"):
                q_nope, q_r, row = _queries_and_row(h, p, c, p_c)
                q = _absorb(q_nope, q_r, p, c)                  # (H, W, d)
            with jax.named_scope("mla_attn"):
                o_lat = paged_decode_attn(
                    q.transpose(1, 0, 2)[:, None], pool_k, None, li,
                    tables, p_c, block, trash, row, None, c.softmax_scale,
                    v_dim=c.kv_lora_rank, n_blk=n_blk)[:, 0]  # (W, H, r)
                pool_k = write_rows(pool_k, li, row[:, None], tables,
                                    p_c, live, block, trash)
            with jax.named_scope("mla_proj"):
                x = x + _attn_out(o_lat, p, x.dtype)
            y, counts = _ffn(x, p, c, kind, i, live)
            return (x + y, pool_k), counts

        (x, pool_k), counts = _scan_layers(layer, (x, pool_k), params, c)
        logits = self.logits(params, _rms(x, params["lnf"],
                                          c.rms_norm_eps))
        return logits, pool_k, None, None, counts


# ---------------------------------------------------------------- the model


@partial(jax.jit, static_argnames=("c",))
def _init_params(key, c):
    # the device's own bit generator: billions of draws at memory speed
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    big, out = jnp.dtype(c.dtype), {}

    def matrix(k, full, fan):
        # a slab of the leading axis at a time, so that the draw's
        # temporaries are a slab's and the peak of a build stays the
        # weights themselves
        # (slabs of whole 8-row tiles: joined from slabs of 1,010 rows the
        # 16,160-row embedding took 17 s to compile, from 1,616 rows 1 s)
        n = max(d for d in range(1, 17)
                if full[0] % d == 0 and (d == 1 or full[0] // d % 8 == 0
                                         or len(full) > 2))
        slab = (full[0] // n,) + full[1:]
        # uniform with the variance 1 / fan: a normal draw's inverse error
        # function takes 3 s a tensor to compile for the chip, 22 tensors
        a = math.sqrt(3.0 / fan)
        return jax.lax.map(
            lambda kk: jax.random.uniform(kk, slab, big, -a, a),
            jax.random.split(k, n)).reshape(full)

    def tensor(i, name, full, shape, vector):
        k = jax.random.fold_in(key, i)
        if name == "bias":
            return jnp.zeros(full, jnp.float32)
        if name == "router":
            a = math.sqrt(3.0 / shape[0])
            return jax.random.uniform(k, full, jnp.float32, -a, a)
        if vector:
            return jnp.ones(full, jnp.float32)
        fan = shape[1] if name == "wte" else shape[-2]
        return matrix(k, full, fan)

    i = 0
    for name, shape in c.shapes("model").items():
        out[name] = tensor(i, name, shape, shape, name == "lnf")
        i += 1
    for kind, n in (("dense", c.n_dense), ("moe", c.n_moe)):
        vectors = _KINDS[kind][0]
        for name, shape in c.shapes(kind).items():
            out[f"{kind}_{name}"] = tensor(i, name, (n,) + shape, shape,
                                           name in vectors)
            i += 1
    return out


class MlaMoeLMHead(model.Model):
    """The causal LM as a ``Model``: stacked weights, an inference
    forward, and ``serve()``."""

    def __init__(self, cfg=None):
        super().__init__()
        self.cfg = cfg or MlaMoeConfig()

    def initialize(self, ids):
        """Creates the parameters in ``cfg.dtype`` (vectors and the
        router float32), drawn in that dtype by one program: matrices
        uniform with variance 1/fan-in, norms at 1, the router's bias at
        0."""
        dev = ids.device
        for name, a in _init_params(dev.rng_key(), self.cfg).items():
            setattr(self, name, Tensor(
                data=jax.device_put(a, dev.jax_device), device=dev,
                requires_grad=True, stores_grad=True))

    def served_family(self):
        return MlaMoeFamily(self.cfg)

    def forward(self, input_ids):
        """(B, S) ids -> (B, S, V) float32 logits; inference only."""
        fam, c = self.served_family(), self.cfg

        @jax.jit                # one program, not an op at a time
        def run(ids, *leaves):
            params = jax.tree.unflatten(tree, leaves)
            # a row at a time: the expert loop's trip count is the
            # row's own
            return jax.lax.map(lambda r: forward_full(params, r, c), ids)

        if not hasattr(self, "wte"):
            self.initialize(input_ids)
            self._name_params()
        leaves, tree = jax.tree.flatten(fam.extract_params(self))
        dev = input_ids.device
        return autograd._op(
            run, input_ids,
            *[Tensor(data=a, device=dev, requires_grad=False)
              for a in leaves], _name="MlaMoeForward")

    def serve(self, **kw):
        """The continuous-batching engine over this model
        (:class:`singa_tpu.serve.InferenceEngine`): pass
        ``paged=PagedConfig(..., prefill_token_budget=)``, ``dtype=``,
        ``max_slots=``.  What this family does not implement the engine
        refuses by name (docs/SERVING.md "The served-model contract")."""
        from ..serve import InferenceEngine

        return InferenceEngine(self, **kw)
