"""A decoder whose layers are of two kinds -- a gated short convolution,
or rotary grouped-query attention -- each before a feed-forward that is
dense in the leading layers and sparse experts in the others
(``model_type`` ``lfm2_moe``, as LiquidAI's LFM2-24B-A2B publishes it:
huggingface.co/LiquidAI/LFM2-24B-A2B; the equations are written out key
by key in ``benchmark/references/conv_moe.py``, the plain reference the
tests hold this file to).

Every layer is an operator and a feed-forward, each behind one RMS norm
and each added to the residual stream.  ``layer_types`` says which
operator a layer has.  A ``conv`` layer projects its input to three
streams ``B, C, x``; ``B * x`` goes through a depthwise causal
convolution of ``conv_L_cache`` taps, the result is gated by ``C`` and
projected back.  A ``full_attention`` layer is grouped-query attention
over everything before it, queries and keys RMS-normalised per head and
turned by rotary positions.  The feed-forward is SwiGLU in the
``num_dense_layers`` leading layers and a routed expert layer with no
shared expert in the others (``ops/expert_layer.py``: this chip is told
which experts it holds; by default all of them).  The embedding is the
output head.

What is here is the SERVING side: ``ConvMoeLMHead(cfg).serve(paged=...)``
returns the one :class:`~singa_tpu.serve.InferenceEngine`, which calls
the math below through :class:`ConvMoeFamily` (models/served.py).

**Only the attention layers have K/V.**  Their keys and values are
paged: the engine's block pool, block tables and private prefill row,
described by ``kv_geometry`` as a model of the attention layers alone.
A ``conv`` layer keeps, a sequence, the last ``conv_L_cache - 1`` inputs
of its convolution -- its TAIL, two rows of ``hidden_size`` -- whatever
the sequence's length.  The tails are declared through ``state_spec``:
the engine keeps them in its state arenas, zeroes them at admission,
carries them from chunk row to chunk row, writes them when the slot goes
live and saves and restores them with the slot.  The arenas' leading
axis is the paged cache's layers, so an attention layer's row holds the
tails of ``tail_rows`` conv layers (conv layer ``i`` at row ``i //
tail_rows``, place ``i % tail_rows``; where the conv layers do not divide
by the attention layers the last places stay zero).

Identical layers are kept as STACKED weights, a stack for each pair of
feed-forward and operator that occurs (``dc``: dense + conv, ``ec``:
experts + conv, ``ef``: experts + attention, ``df``), and every program
walks the layers in their order as RUNS of like layers under
``lax.scan``; a stretch of runs that repeats (attention, three conv
layers, attention, three conv layers, ...) is one scan over its periods.
Matrices are built in ``cfg.dtype``; the per-channel vectors, the
convolution's taps, the router and its bias stay float32.  Training is
not here (ROADMAP Reach A).
"""

import math
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd, model
from ..ops.expert_layer import held_terms, route, swiglu
from ..ops.paged_attention import (paged_attn, paged_decode_attn, rotary,
                                   row_to_blocks, write_rows)
from ..tensor import Tensor
from .served import ServedFamily, seg_cat, seg_split, seg_tokens, seg_valid

#: rows of an expert's tile (ops/expert_layer.held_terms): an expert's
#: matrices are read once a tile whatever the tile's rows, and at a
#: deployment's load (hundreds of lanes over 64 experts) a tile of 16
#: sends one expert in four round a second time (PERF.md section 6, PR 42)
TILE = 32

#: a stack's tensors by (feed-forward, operator): float32 vectors, then
#: matrices in ``cfg.dtype``
_OPERATOR = {"conv": (("conv_w",), ("w_in", "w_out")),
             "full": (("q_norm", "k_norm"), ("wq", "wk", "wv", "wo"))}
_FFN = {"dense": ((), ("w_gu", "w_down")),
        "moe": (("router", "bias"), ("e_gu", "e_down"))}
#: the stacks: name -> (feed-forward, operator)
STACKS = {"dc": ("dense", "conv"), "df": ("dense", "full"),
          "ec": ("moe", "conv"), "ef": ("moe", "full")}
_KIND = {"conv": "conv", "full_attention": "full"}


def _tensors(stack):
    ffn, op = STACKS[stack]
    return (("ln_op", "ln_ffn") + _OPERATOR[op][0] + _FFN[ffn][0],
            _OPERATOR[op][1] + _FFN[ffn][1])


def _stack_of(layer, kind, n_dense):
    return ("d" if layer < n_dense else "e") + _KIND[kind][0]


@lru_cache(maxsize=None)
def _plan(kinds, n_dense):
    """The layers in their order as runs of like layers, ``(stack, first
    index in the stack, first index among the layers of its operator,
    layers)``, split in three: ``(head runs, (unit runs, repeats), tail
    runs)`` where the unit is the stretch of runs that repeats most
    (``repeats`` 0 and no unit if nothing does)."""
    runs, seen = [], {}
    for layer, kind in enumerate(kinds):
        stack = _stack_of(layer, kind, n_dense)
        op = STACKS[stack][1]
        if runs and runs[-1][0] == stack:
            runs[-1][3] += 1
        else:
            runs.append([stack, seen.get(stack, 0), seen.get(op, 0), 1])
        seen[stack] = seen.get(stack, 0) + 1
        seen[op] = seen.get(op, 0) + 1
    runs = [tuple(r) for r in runs]
    like = lambda a, b: [(r[0], r[3]) for r in a] == [(r[0], r[3])
                                                      for r in b]
    best = (0, 0, 0, 0)                   # layers covered, start, unit, n
    for start in range(len(runs)):
        for u in range(1, (len(runs) - start) // 2 + 1):
            n = 1
            while like(runs[start:start + u],
                       runs[start + n * u:start + (n + 1) * u]):
                n += 1
            if n > 1 and n * u > best[0]:
                best = (n * u, start, u, n)
    _, start, u, n = best
    return (tuple(runs[:start]), (tuple(runs[start:start + u]), n),
            tuple(runs[start + n * u:]))


@dataclass(frozen=True)
class ConvMoeConfig:
    """The published ``config.json`` keys that shape the model, plus
    ``experts_held`` -- the ownership range ``(first, end)`` of the
    router's outputs whose experts this chip holds, all of them unless
    told -- ``max_len``, the served context, and ``dtype``, what the
    matrices are built in."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    layer_types: tuple = ()
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    experts_held: tuple = None
    max_len: int = 2048
    dtype: str = "float32"

    def __post_init__(self):
        n = self.num_hidden_layers
        # as published: two conv layers, then attention in every fourth
        given = tuple(self.layer_types) or tuple(
            "full_attention" if i >= 2 and (i - 2) % 4 == 0 else "conv"
            for i in range(n))
        object.__setattr__(self, "layer_types", given)
        held = self.experts_held or (0, self.num_experts)
        object.__setattr__(self, "experts_held",
                           tuple(int(v) for v in held))
        for k in ("rope_theta", "norm_eps", "routed_scaling_factor"):
            object.__setattr__(self, k, float(getattr(self, k)))
        if len(given) != n or set(given) - set(_KIND):
            raise ValueError(
                f"layer_types must name num_hidden_layers ({n}) layers, "
                f"each 'conv' or 'full_attention'")
        if "full_attention" not in given or "conv" not in given:
            raise ValueError(
                "layer_types must hold a conv layer and a full_attention "
                "layer: the paged cache is the attention layers', the "
                "state arenas' rows go by them")
        if not 0 <= self.num_dense_layers < n:
            raise ValueError("num_dense_layers must leave an expert layer")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide hidden_size, K/V heads "
                             "the heads")
        if self.conv_bias or not self.norm_topk_prob \
                or not self.use_expert_bias:
            raise ValueError("conv_bias true, norm_topk_prob false and "
                             "use_expert_bias false are not implemented")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache must be 2 at least")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the router's {self.num_experts} "
                             f"outputs")
        if self.max_len > self.max_position_embeddings:
            raise ValueError("max_len exceeds max_position_embeddings")

    # the engine's names for what it reads off any model's cfg
    n_layer = property(lambda s: s.num_hidden_layers)
    n_head = property(lambda s: s.num_attention_heads)
    n_kv_head = property(lambda s: s.num_key_value_heads)
    n_embd = property(lambda s: s.hidden_size)
    n_positions = property(lambda s: s.max_len)
    layer_norm_eps = property(lambda s: s.norm_eps)
    head_dim = property(lambda s: s.hidden_size // s.num_attention_heads)
    n_held = property(lambda s: s.experts_held[1] - s.experts_held[0])
    n_full = property(lambda s: s.layer_types.count("full_attention"))
    n_conv = property(lambda s: s.layer_types.count("conv"))
    n_moe = property(lambda s: s.num_hidden_layers - s.num_dense_layers)
    kv_width = property(lambda s: s.num_key_value_heads * s.head_dim)
    #: rows of a conv layer's tail: the inputs its next output still needs
    tail = property(lambda s: s.conv_L_cache - 1)
    #: conv layers' tails in one attention layer's row of the arena
    tail_rows = property(lambda s: -(-s.n_conv // s.n_full))

    def plan(self):
        return _plan(self.layer_types, self.num_dense_layers)

    def stack_sizes(self):
        """{stack: layers in it}, the stacks that occur."""
        out = {}
        for layer, kind in enumerate(self.layer_types):
            k = _stack_of(layer, kind, self.num_dense_layers)
            out[k] = out.get(k, 0) + 1
        return out

    def place(self, layer):
        """Layer ``layer`` of the model -> (stack, index in the stack)."""
        stack = _stack_of(layer, self.layer_types[layer],
                          self.num_dense_layers)
        return stack, sum(
            _stack_of(i, k, self.num_dense_layers) == stack
            for i, k in enumerate(self.layer_types[:layer]))

    def tail_bytes(self):
        """What a slot's tails take in the arenas (float32)."""
        return (self.n_full * self.tail_rows * self.tail
                * self.hidden_size * 4)

    def shapes(self, stack):
        """{tensor: shape} of one layer of ``stack``, or of the two
        tensors outside the layers ("model")."""
        c, e = self, self.hidden_size
        if stack == "model":
            return dict(wte=(c.vocab_size, e), lnf=(e,))
        ffn, op = STACKS[stack]
        out = dict(ln_op=(e,), ln_ffn=(e,))
        if op == "conv":
            out.update(conv_w=(c.conv_L_cache, e), w_in=(e, 3 * e),
                       w_out=(e, e))
        else:
            d = c.head_dim
            # W_q and W_k are stored (out, in): their results are
            # normalised head by head (models/swa_moe.py says why)
            out.update(q_norm=(d,), k_norm=(d,), wq=(e, e),
                       wk=(c.kv_width, e), wv=(e, c.kv_width), wo=(e, e))
        if ffn == "dense":
            out.update(w_gu=(e, 2 * c.intermediate_size),
                       w_down=(c.intermediate_size, e))
        else:
            im = c.moe_intermediate_size
            out.update(router=(e, c.num_experts), bias=(c.num_experts,),
                       e_gu=(c.n_held, e, 2 * im),
                       e_down=(c.n_held, im, e))
        return out


# --------------------------------------------------------------------- math


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _gates(a, p):
    """a (T, E) normalised -> the convolution's input ``B * x`` and the
    gate ``C``, both (T, E) float32."""
    bcx = jnp.dot(a, p["w_in"], preferred_element_type=jnp.float32)
    b, c_gate, x = jnp.split(bcx, 3, axis=-1)
    return b * x, c_gate


def _conv_out(conv, c_gate, p, dtype):
    return (c_gate * conv).astype(dtype) @ p["w_out"]


def _conv_taps(u, p, tail, n_valid):
    """The convolution over its inputs ``u`` (T, E) that follow ``tail``
    (K - 1, E), the inputs at the K - 1 positions before them (zeros at a
    sequence's start).  Returns (conv (T, E), the tail after the first
    ``n_valid`` rows: what lies past them is padding and leaves it
    alone)."""
    t = u.shape[0]
    ext = jnp.concatenate([tail, u], axis=0)               # (K - 1 + T, E)
    conv = sum(p["conv_w"][j] * ext[j:j + t]
               for j in range(p["conv_w"].shape[0]))
    tail = jax.lax.dynamic_slice_in_dim(ext, n_valid, tail.shape[0], axis=0)
    return conv, tail


def _conv_chunk(a, p, tail, n_valid):
    """The operator over T rows ``a`` of ONE sequence that follow
    ``tail`` (:func:`_conv_taps`).  Returns (o (T, E), the tail)."""
    u, c_gate = _gates(a, p)
    conv, tail = _conv_taps(u, p, tail, n_valid)
    return _conv_out(conv, c_gate, p, a.dtype), tail


def _conv_step(a, p, arena, ci, rows, slots):
    """One token a lane: ``arena`` (L, S + 1, rows, K - 1, E) holds every
    slot's tails; conv layer ``ci``'s are read at ``slots`` (W,),
    advanced and written back.  Returns (o (W, E), arena)."""
    at = (ci // rows, slots, ci % rows)
    tail = arena[at]                                       # (W, K - 1, E)
    u, c_gate = _gates(a, p)
    ext = jnp.concatenate([tail, u[:, None]], axis=1)      # (W, K, E)
    conv = jnp.sum(p["conv_w"] * ext, axis=1)
    return (_conv_out(conv, c_gate, p, a.dtype),
            arena.at[at].set(ext[:, 1:]))


def _qkv(a, p, c, pos):
    """a (T, E) normalised, at positions ``pos`` (T,) -> q (T, H, D) and
    k (T, KV, D), each RMS-normalised per head and turned; v (T, KV,
    D)."""
    t = a.shape[0]
    proj = lambda w: jnp.einsum("te,ne->tn", a, w)      # stored (out, in)
    turn = lambda x: rotary(x.transpose(1, 0, 2), pos,
                            c.rope_theta).transpose(1, 0, 2)
    q = turn(_rms(proj(p["wq"]).reshape(t, c.n_head, c.head_dim),
                  p["q_norm"], c.norm_eps))
    k = turn(_rms(proj(p["wk"]).reshape(t, c.n_kv_head, c.head_dim),
                  p["k_norm"], c.norm_eps))
    return q, k, (a @ p["wv"]).reshape(t, c.n_kv_head, c.head_dim)


def _by_group(q, c):
    """q (T, H, D) -> (KV, g, T, D): the query heads of each K/V head."""
    t = q.shape[0]
    return q.reshape(t, c.n_kv_head, -1, c.head_dim).transpose(1, 2, 0, 3)


def _ffn(x, p, c, ffn, li, valid):
    """The layer's feed-forward on ``x`` (T, E), its norm included: ``(y
    (T, E) float32, counts)``; counts None for a dense layer.  ``li``
    indexes the expert stack (``p``'s other tensors are this layer's,
    the experts' the whole stack's: ops/expert_layer.held_terms slices an
    expert out)."""
    m = _rms(x, p["ln_ffn"], c.norm_eps)
    if ffn == "dense":
        with jax.named_scope("dense_mlp"):
            return swiglu(m, p["w_gu"], p["w_down"]), None
    with jax.named_scope("moe_route"):
        idx, w = route(m, p["router"], p["bias"], n_group=1, topk_group=1,
                       top_k=c.num_experts_per_tok,
                       scale=c.routed_scaling_factor)
    with jax.named_scope("moe_experts"):
        return held_terms(m, idx, w, p["e_gu"], p["e_down"],
                          c.experts_held[0], valid, layer=li, tile=TILE)


def _scan_layers(body, carry, params, c):
    """Run ``body(carry, stack, index in the stack, index among the
    layers of its operator, p) -> (carry, counts or None)`` over the
    model's layers in their order (:func:`_plan`): each run of like
    layers a scan, the repeating stretch a scan over its periods.  A
    layer's tensors are sliced out of their stack where the body runs;
    the experts' matrices are not: the body reaches into their whole
    stack.  Returns (carry, counts (expert layers, n_held + 1))."""
    head, (unit, repeats), tail = c.plan()

    def at(stack, i):
        return {k: (v if k in ("e_gu", "e_down") else v[i])
                for k, v in params[stack].items()}

    def run(carry, stack, i0, m0, n):
        if n == 1:
            carry, cnt = body(carry, stack, i0, m0, at(stack, i0))
            return carry, None if cnt is None else cnt[None]
        return jax.lax.scan(
            lambda carry, j: body(carry, stack, i0 + j, m0 + j,
                                  at(stack, i0 + j)),
            carry, jnp.arange(n))

    def runs(carry, some, t=0):
        # period ``t`` of the unit lies ``t`` units' layers further into
        # each stack and among each operator's layers
        per = {}
        for stack, _, _, n in some:
            for k in (stack, STACKS[stack][1]):
                per[k] = per.get(k, 0) + n
        out = []
        for stack, i0, m0, n in some:
            carry, cnt = run(carry, stack, i0 + t * per[stack],
                             m0 + t * per[STACKS[stack][1]], n)
            if cnt is not None:
                out.append(cnt)
        return carry, (jnp.concatenate(out) if out else None)

    counts = []
    carry, cnt = runs(carry, head)
    counts.append(cnt)
    if repeats:
        carry, cnt = jax.lax.scan(lambda carry, t: runs(carry, unit, t),
                                  carry, jnp.arange(repeats))
        counts.append(None if cnt is None
                      else cnt.reshape(-1, cnt.shape[-1]))
    carry, cnt = runs(carry, tail)
    counts.append(cnt)
    return carry, jnp.concatenate([k for k in counts if k is not None])


def forward_full(params, ids, c):
    """ids (S,) -> logits (S, V): the whole sequence at once, no cache.
    What ``Model.forward`` runs; serving goes through the family."""
    s = ids.shape[0]
    pos = jnp.arange(s)
    x = params["wte"][ids]
    causal = pos[None, :] <= pos[:, None]
    tail0 = jnp.zeros((c.tail, c.hidden_size), jnp.float32)

    def layer(x, stack, i, _m, p):
        ffn, op = STACKS[stack]
        a = _rms(x, p["ln_op"], c.norm_eps)
        if op == "conv":
            o, _ = _conv_chunk(a, p, tail0, 0)
        else:
            q, k, v = _qkv(a, p, c, pos)
            f32 = jnp.float32
            sc = jnp.einsum("kgsd,tkd->kgst", _by_group(q, c).astype(f32),
                            k.astype(f32)) / math.sqrt(c.head_dim)
            pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            o = jnp.einsum("kgst,tkd->skgd", pr, v.astype(f32))
            o = o.reshape(s, -1).astype(x.dtype) @ p["wo"]
        x = x + o
        y, counts = _ffn(x, p, c, ffn, i, None)
        return x + y.astype(x.dtype), counts

    x, _ = _scan_layers(layer, x, params, c)
    return _logits(params, _rms(x, params["lnf"], c.norm_eps))


def _logits(params, hidden):
    """(..., E) -> (..., V) through the embedding (the head is tied to
    it), accumulated and returned in float32."""
    return jnp.einsum("...e,ve->...v", hidden, params["wte"],
                      preferred_element_type=jnp.float32)


# ----------------------------------------------------- the served contract


@dataclass(frozen=True)
class ConvMoeFamily(ServedFamily):
    """The family for the serve engine (models/served.py): the budgeted
    paged path, the attention layers' K/V in the pool and the conv
    layers' tails in the engine's state arenas.  Hashes by its
    configuration, so equal models share compiled programs."""

    cfg: ConvMoeConfig

    name = "conv_moe"
    features = frozenset()
    pad_aware = True
    step_counts = True
    # short_conv: the whole operator of a conv layer, projections and
    # tails included; attn_full: the attention itself against the pool
    # and the write of the new rows; attn_proj: the projections and
    # norms either side of it
    scopes = ("short_conv", "attn_full", "attn_proj", "moe_route",
              "moe_experts", "dense_mlp", "head")

    def extract_params(self, m, dtype=None):
        st = {k.rsplit(".", 1)[-1]: t.data
              for k, t in m.get_states().items()}
        if not st:
            raise RuntimeError("model not initialized: call compile() "
                               "or run one forward first")
        cast = (lambda a: a) if dtype is None else \
            (lambda a: a.astype(dtype))
        out = dict(wte=cast(st["wte"]), lnf=st["lnf"])
        for stack in self.cfg.stack_sizes():
            vectors, matrices = _tensors(stack)
            out[stack] = {k: (st[f"{stack}_{k}"] if k in vectors
                              else cast(st[f"{stack}_{k}"]))
                          for k in vectors + matrices}
        return out

    def kv_geometry(self, cfg):
        """The paged cache is the ATTENTION layers'."""
        return cfg.n_full, cfg.n_kv_head, cfg.head_dim

    def state_spec(self, cfg):
        """The conv layers' tails, ``tail_rows`` of them under each of
        the arena's leading rows (the attention layers'), each ``conv_L_cache
        - 1`` inputs of the convolution; float32."""
        return {"conv": ((cfg.tail_rows, cfg.tail, cfg.hidden_size),
                         jnp.dtype("float32"))}

    def logits(self, params, hidden):
        with jax.named_scope("head"):
            return _logits(params, hidden)

    def on_step_counts(self, counts, cfg):
        """``counts``: a row an expert layer, the assignments each held
        expert received and last those held elsewhere.  The tiles the
        expert loop ran follow from them: an expert takes a tile for
        every ``TILE`` assignments, and one if it has none."""
        held = counts[:, :-1]
        first = cfg.experts_held[0]
        tiles = int(np.maximum(-(-held // TILE), 1).sum())
        incs = {("serve.moe.expert_tokens",
                 (("expert", str(first + e)),)): int(n)
                for e, n in enumerate(held.sum(0))}
        incs["serve.moe.assignments_elsewhere", ()] = int(
            counts[:, -1].sum())
        incs["serve.moe.tiles", ()] = tiles
        gauges = {("serve.state.conv_tail_bytes", ()): cfg.tail_bytes()}
        return dict(experts_hit=int(np.count_nonzero(held)),
                    expert_tiles=tiles,
                    expert_tokens_max=int(held.max()),
                    expert_tokens_mean=float(held.mean())), incs, gauges

    def chunk_rows(self, params, segs, *, block, **_):
        """One launch: each segment a whole number of blocks of one
        request.  An attention layer: a segment's queries over its
        private row below its ``off`` (the shared loop) and its own
        keys, the new rows written into that row.  A conv layer: the
        convolution runs on from the tail the segment's row before
        left, and the tail moves on by the ``n_valid`` rows that are the
        prompt's (what follows them leaves it alone and chooses no
        expert).  Projections, gates and the feed-forward take the
        segments' tokens together."""
        c = self.cfg
        n_tok = sum(s.chunk for s in segs)
        valid = seg_valid(segs)
        toks, pos = seg_tokens(segs)
        x = jnp.take(params["wte"], toks, axis=0)
        width, d = segs[0].kc_row.shape[3:]
        # what lies below ``off`` is walked in strides of eight blocks
        # (models/swa_moe.py, PR 37)
        stride = min(8 * block, width)
        below = [(row_to_blocks(s.kc_row, stride),
                  row_to_blocks(s.vc_row, stride)) for s in segs]
        tbl = jnp.arange(width // stride)
        cur = [jnp.tril(jnp.ones((s.chunk, s.chunk), bool)) for s in segs]
        scale = 1.0 / math.sqrt(d)
        rows_of = lambda t: t.transpose(1, 0, 2).reshape(t.shape[1], -1)

        def layer(carry, stack, i, mi, p):
            x, kc_rows, vc_rows, tails = carry
            ffn, op = STACKS[stack]
            if op == "conv":
                with jax.named_scope("short_conv"):
                    a = _rms(x, p["ln_op"], c.norm_eps)
                    before = [tl[mi] for tl in tails]
                    u, c_gate = _gates(a, p)
                    conv, after = zip(*(
                        _conv_taps(u_s, p, tl, s.n_valid)
                        for s, u_s, tl in zip(segs, seg_split(u, segs),
                                              before)))
                    o = _conv_out(seg_cat(conv), c_gate, p, a.dtype)
                    tails = tuple(tl.at[mi].set(t)
                                  for tl, t in zip(tails, after))
            else:
                with jax.named_scope("attn_proj"):
                    a = _rms(x, p["ln_op"], c.norm_eps)
                    q, k, v = _qkv(a, p, c, pos)
                    q = _by_group(q, c)
                    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
                with jax.named_scope("attn_full"):
                    o, kc_rows, vc_rows = [], list(kc_rows), list(vc_rows)
                    for j, (s, q_s, k_s, v_s) in enumerate(zip(
                            segs, seg_split(q, segs, 2),
                            seg_split(k, segs, 1), seg_split(v, segs, 1))):
                        o.append(paged_attn(
                            q_s, *below[j], mi, tbl, s.off,
                            -(-s.off // stride), stride, -1, rows_of(k_s),
                            rows_of(v_s), cur[j], scale))
                        kc_rows[j] = jax.lax.dynamic_update_slice(
                            kc_rows[j],
                            k_s[None, None].astype(kc_rows[j].dtype),
                            (mi, 0, 0, s.off, 0))
                        vc_rows[j] = jax.lax.dynamic_update_slice(
                            vc_rows[j],
                            v_s[None, None].astype(vc_rows[j].dtype),
                            (mi, 0, 0, s.off, 0))
                    o, kc_rows, vc_rows = (seg_cat(o, 2), tuple(kc_rows),
                                           tuple(vc_rows))
                with jax.named_scope("attn_proj"):
                    o = o.transpose(2, 0, 1, 3).reshape(n_tok, -1)
                    o = o.astype(x.dtype) @ p["wo"]
            x = x + o
            y, counts = _ffn(x, p, c, ffn, i, valid)
            return (x + y.astype(x.dtype), kc_rows, vc_rows, tails), counts

        shape = segs[0].state["conv"].shape
        (x, kc_rows, vc_rows, tails), _ = _scan_layers(
            layer, (x, tuple(s.kc_row for s in segs),
                    tuple(s.vc_row for s in segs),
                    tuple(s.state["conv"].reshape((-1,) + shape[2:])
                          for s in segs)), params, c)
        hidden = _rms(x, params["lnf"], c.norm_eps)
        return [(h[None], kc, vc, {"conv": tl.reshape(shape)})
                for h, kc, vc, tl in zip(seg_split(hidden, segs), kc_rows,
                                         vc_rows, tails)]

    def decode_step(self, params, pool_k, pool_v, state, slots, tables,
                    toks, pos, live, n_blk, *, block, trash, **_):
        """Every lane one token.  An attention layer: each lane's query
        over its live blocks of the pool plus its own new key, the new
        K/V row written straight into the pool.  A conv layer: each
        lane's tail read from the arena at its slot, the convolution's
        one new output, the tail moved on a row and written back (dead
        lanes: the trash row).  Pool and arena are carried through the
        layer scans and updated in place.  Returns the expert layers'
        counts of the step after the contract's four."""
        c = self.cfg
        p_c = jnp.where(live, pos, 0)
        t_c = jnp.where(live, toks, 0)
        x = params["wte"][t_c]                                   # (W, E)
        n_kv, d = c.n_kv_head, c.head_dim
        n_w = x.shape[0]
        scale = 1.0 / math.sqrt(d)

        def layer(carry, stack, i, mi, p):
            x, pool_k, pool_v, tails = carry
            ffn, op = STACKS[stack]
            if op == "conv":
                with jax.named_scope("short_conv"):
                    a = _rms(x, p["ln_op"], c.norm_eps)
                    o, tails = _conv_step(a, p, tails, mi, c.tail_rows,
                                          slots)
            else:
                with jax.named_scope("attn_proj"):
                    a = _rms(x, p["ln_op"], c.norm_eps)
                    q, k, v = _qkv(a, p, c, p_c)
                    q = q.reshape(n_w, n_kv, -1, d)
                    k, v = k.reshape(n_w, -1), v.reshape(n_w, -1)
                with jax.named_scope("attn_full"):
                    o = paged_decode_attn(
                        q, pool_k, pool_v, mi, tables, p_c, block, trash,
                        k, v, scale, n_blk=n_blk)
                    pool_k = write_rows(pool_k, mi, k[:, None], tables,
                                        p_c, live, block, trash)
                    pool_v = write_rows(pool_v, mi, v[:, None], tables,
                                        p_c, live, block, trash)
                with jax.named_scope("attn_proj"):
                    o = o.reshape(n_w, -1).astype(x.dtype) @ p["wo"]
            x = x + o
            y, counts = _ffn(x, p, c, ffn, i, live)
            return (x + y.astype(x.dtype), pool_k, pool_v, tails), counts

        (x, pool_k, pool_v, tails), counts = _scan_layers(
            layer, (x, pool_k, pool_v, state["conv"]), params, c)
        logits = self.logits(params, _rms(x, params["lnf"], c.norm_eps))
        return logits, pool_k, pool_v, {"conv": tails}, counts


# ---------------------------------------------------------------- the model


@partial(jax.jit, static_argnames=("c",))
def _init_params(key, c):
    # the device's own bit generator: billions of draws at memory speed
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    big, out = jnp.dtype(c.dtype), {}

    def matrix(k, full):
        # a slab of the leading axis at a time, so that the draw's
        # temporaries are a slab's; uniform with the N(0, 0.02)'s
        # variance (models/swa_moe.py says why not a normal draw)
        n = max(d for d in range(1, 17)
                if full[0] % d == 0 and (d == 1 or full[0] // d % 8 == 0
                                         or len(full) > 2))
        slab = (full[0] // n,) + full[1:]
        a = 0.02 * math.sqrt(3.0)
        return jax.lax.map(
            lambda kk: jax.random.uniform(kk, slab, big, -a, a),
            jax.random.split(k, n)).reshape(full)

    def tensor(i, name, full, vector):
        k = jax.random.fold_in(key, i)
        if name == "bias":
            return jnp.zeros(full, jnp.float32)
        if name == "router":
            a = 0.02 * math.sqrt(3.0)
            return jax.random.uniform(k, full, jnp.float32, -a, a)
        if name == "conv_w":
            # taps of order 1 / sqrt(taps): the convolution keeps its
            # input's scale
            return jax.random.uniform(k, full, jnp.float32, -1.0, 1.0)
        if vector:
            return jnp.ones(full, jnp.float32)
        return matrix(k, full)

    i = 0
    for name, shape in c.shapes("model").items():
        out[name] = tensor(i, name, shape, name == "lnf")
        i += 1
    for stack, n in c.stack_sizes().items():
        vectors = _tensors(stack)[0]
        for name, shape in c.shapes(stack).items():
            out[f"{stack}_{name}"] = tensor(i, name, (n,) + shape,
                                            name in vectors)
            i += 1
    return out


class ConvMoeLMHead(model.Model):
    """The causal LM as a ``Model``: stacked weights, an inference
    forward, and ``serve()``."""

    def __init__(self, cfg=None):
        super().__init__()
        self.cfg = cfg or ConvMoeConfig()

    def initialize(self, ids):
        """Creates the parameters in ``cfg.dtype`` (vectors, taps and
        the router float32), drawn in that dtype by one program:
        matrices with the variance of N(0, 0.02), norms at 1, taps of
        order 1, the router's bias at 0."""
        dev = ids.device
        for name, a in _init_params(dev.rng_key(), self.cfg).items():
            setattr(self, name, Tensor(
                data=jax.device_put(a, dev.jax_device), device=dev,
                requires_grad=True, stores_grad=True))

    def served_family(self):
        return ConvMoeFamily(self.cfg)

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
              for a in leaves], _name="ConvMoeForward")

    def serve(self, **kw):
        """The continuous-batching engine over this model
        (:class:`singa_tpu.serve.InferenceEngine`): pass
        ``paged=PagedConfig(..., prefill_token_budget=)``, ``dtype=``,
        ``max_slots=``.  What this family does not implement the engine
        refuses by name (docs/SERVING.md "The served-model contract")."""
        from ..serve import InferenceEngine

        return InferenceEngine(self, **kw)
