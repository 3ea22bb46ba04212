"""A decoder that mixes window and full attention by layer, with gated
attention and sparse experts (``model_type`` ``afmoe``, as arcee-ai's
Trinity-Mini publishes it: huggingface.co/arcee-ai/Trinity-Mini; the
equations are written out key by key in
``benchmark/references/swa_moe.py``, the plain reference the tests hold
this file to).

Every layer is grouped-query attention and a feed-forward between FOUR
RMS norms (one before and one after each half: the halves' results are
normalised before they join the residual stream).  Queries and keys are
RMS-normalised per head; the attention's result is gated, element by
element, by a sigmoid of a further projection of the layer's input.  The
layers come in periods of ``global_attn_every_n_layers``: all but the
last of a period attend the last ``sliding_window`` positions only, with
rotary positions; the last attends everything and has no positions at
all.  The feed-forward is SwiGLU in the ``num_dense_layers`` leading
layers and a routed expert layer beside one shared expert in the others
(``ops/expert_layer.py``: this chip is told which experts it holds).

What is here is the SERVING side: ``SwaMoeLMHead(cfg).serve(paged=...)``
returns the one :class:`~singa_tpu.serve.InferenceEngine`, which calls
the math below through :class:`SwaMoeFamily` (models/served.py).

**The cache is of two kinds**, each held for as long as its kind of
layer needs it.  The FULL layers' keys and values are paged: the
engine's block pool, block tables and private prefill row, described by
``kv_geometry`` as a model of one layer a period.  The WINDOW layers'
keys and values live in per-slot RINGS of ``sliding_window`` rows
(position ``p`` at row ``p % ring``), declared through ``state_spec``:
the engine keeps them in its state arenas, zeroes them at admission,
carries them through the chunk rows, writes them when the slot goes
live and saves and restores them with the slot.  A sequence of any
length holds one ring a window layer, and a decode step walks each
lane's ring and nothing else (``ops/paged_attention.ring_decode_attn``):
neither the memory nor the work of a window layer follows the longest
lane.  The engine-wide window (``ServedFamily.window``) is None here: it
would drop the full layers' blocks too.

Identical layers are kept as STACKED weights and every program scans
them: the dense window layers (``dw``), the expert window layers of the
first period (``ew0``) and of the others (``ew``, a period a scan step),
the expert full layers (``ef``).  Matrices are built in ``cfg.dtype``;
the per-channel vectors, the router and its bias stay float32.
Training is not here (ROADMAP Reach A).
"""

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd, model
from ..ops.expert_layer import held_terms, route, swiglu
from ..ops.paged_attention import (paged_attn, paged_decode_attn,
                                   ring_chunk_attn, ring_decode_attn,
                                   ring_write_chunk, rotary, row_to_blocks,
                                   write_rows)
from ..tensor import Tensor
from .served import ServedFamily, seg_cat, seg_split, seg_tokens, seg_valid

#: a layer's float32 tensors (whatever ``cfg.dtype``) and its matrices
_VECTORS = ("ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp",
            "q_norm", "k_norm")
_MATRICES = ("wq", "wk", "wv", "wg", "wo")
_FFN = {"dense": ((), ("w_gu", "w_down")),
        "moe": (("router", "bias"),
                ("e_gu", "e_down", "s_gu", "s_down"))}
#: the stacks of layers, in the order a period meets them: name ->
#: (feed-forward, attention)
STACKS = {"dw": ("dense", "window"), "ew0": ("moe", "window"),
          "ew": ("moe", "window"), "ef": ("moe", "full")}


def _tensors(stack):
    vec, mat = _FFN[STACKS[stack][0]]
    return _VECTORS + vec, _MATRICES + mat


@dataclass(frozen=True)
class SwaMoeConfig:
    """The published ``config.json`` keys that shape the model, plus
    ``experts_held`` -- the ownership range ``(first, end)`` of the
    router's outputs whose experts this chip holds -- ``max_len``, the
    served context, and ``dtype``, what the matrices are built in."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_dense_layers: int = 2
    num_experts: int = 128
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    layer_types: tuple = ()
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 131072
    experts_held: tuple = (0, 128)
    max_len: int = 2048
    dtype: str = "float32"

    def __post_init__(self):
        g, n = self.global_attn_every_n_layers, self.num_hidden_layers
        pattern = tuple("full_attention" if (i + 1) % g == 0
                        else "sliding_attention" for i in range(n))
        given = tuple(self.layer_types) or pattern
        object.__setattr__(self, "layer_types", given)
        object.__setattr__(self, "experts_held",
                           tuple(int(v) for v in self.experts_held))
        for k in ("rope_theta", "rms_norm_eps", "route_scale"):
            object.__setattr__(self, k, float(getattr(self, k)))
        if given != pattern or n % g or n < 2 * g:
            raise ValueError(
                "layer_types must be whole periods (two at least) of "
                f"{g - 1} sliding_attention layers and one "
                "full_attention layer")
        if not 0 < self.num_dense_layers < g - 1:
            raise ValueError(
                "the leading dense layers must be window layers of the "
                "first period, with an expert window layer after them")
        if not self.route_norm:
            raise ValueError("route_norm false is not implemented")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the router's {self.num_experts} "
                             f"outputs")
        if self.num_experts % self.n_group:
            raise ValueError("num_experts must divide into n_group")
        if self.max_len > self.max_position_embeddings:
            raise ValueError("max_len exceeds max_position_embeddings")

    # the engine's names for what it reads off any model's cfg
    n_layer = property(lambda s: s.num_hidden_layers)
    n_head = property(lambda s: s.num_attention_heads)
    n_kv_head = property(lambda s: s.num_key_value_heads)
    n_embd = property(lambda s: s.hidden_size)
    n_positions = property(lambda s: s.max_len)
    layer_norm_eps = property(lambda s: s.rms_norm_eps)
    n_held = property(lambda s: s.experts_held[1] - s.experts_held[0])
    n_periods = property(lambda s: s.num_hidden_layers
                         // s.global_attn_every_n_layers)
    #: window layers a period
    n_win = property(lambda s: s.global_attn_every_n_layers - 1)
    #: rows of a slot's ring: the window itself (a decode step writes a
    #: position's row where the row ``window`` positions back lay, which
    #: that position no longer sees)
    ring = property(lambda s: s.sliding_window)
    kv_width = property(lambda s: s.num_key_value_heads * s.head_dim)
    embedding_multiplier = property(
        lambda s: math.sqrt(s.hidden_size) if s.mup_enabled else 1.0)

    def stack_sizes(self):
        """{stack: layers in it}."""
        return {"dw": self.num_dense_layers,
                "ew0": self.n_win - self.num_dense_layers,
                "ew": (self.n_periods - 1) * self.n_win,
                "ef": self.n_periods}

    def place(self, layer):
        """Layer ``layer`` of the model -> (stack, index in the stack)."""
        p, j = divmod(layer, self.global_attn_every_n_layers)
        if j == self.n_win:
            return "ef", p
        if p:
            return "ew", (p - 1) * self.n_win + j
        nd = self.num_dense_layers
        return ("dw", j) if j < nd else ("ew0", j - nd)

    def row_bytes(self, itemsize):
        """{kind: bytes one cached position takes in all the layers of
        that kind}."""
        one = 2 * self.kv_width * itemsize
        return {"full": self.n_periods * one,
                "window": self.n_periods * self.n_win * one}

    def shapes(self, stack):
        """{tensor: shape} of one layer of ``stack``, or of the three
        tensors outside the layers ("model")."""
        c, e = self, self.hidden_size
        if stack == "model":
            return dict(wte=(c.vocab_size, e), head=(e, c.vocab_size),
                        lnf=(e,))
        qd, d = c.n_head * c.head_dim, c.head_dim
        # W_q and W_k are stored (out, in): their results are normalised
        # head by head, and the compiler otherwise transposes both
        # matrices out of their stack, every layer of every step, to
        # have a head's values along the sublanes
        out = dict(ln_in=(e,), ln_post_attn=(e,), ln_pre_mlp=(e,),
                   ln_post_mlp=(e,), q_norm=(d,), k_norm=(d,),
                   wq=(qd, e), wk=(c.kv_width, e), wv=(e, c.kv_width),
                   wg=(e, qd), wo=(qd, e))
        if STACKS[stack][0] == "dense":
            out.update(w_gu=(e, 2 * c.intermediate_size),
                       w_down=(c.intermediate_size, e))
        else:
            im = c.moe_intermediate_size
            sh = im * c.num_shared_experts
            out.update(router=(e, c.num_experts), bias=(c.num_experts,),
                       e_gu=(c.n_held, e, 2 * im),
                       e_down=(c.n_held, im, e),
                       s_gu=(e, 2 * sh), s_down=(sh, e))
        return out


# --------------------------------------------------------------------- math


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _qkvg(a, p, c, pos, kind):
    """a (T, E) normalised, at positions ``pos`` (T,) -> q (T, H, D) and
    k (T, KV, D), each RMS-normalised per head and, in a window layer,
    rotated; v (T, KV, D); the gate's pre-activation (T, H·D)."""
    t = a.shape[0]
    # W_q and W_k are stored (out, in): SwaMoeConfig.shapes
    proj = lambda w: jnp.einsum("te,ne->tn", a, w)
    q = _rms(proj(p["wq"]).reshape(t, c.n_head, c.head_dim), p["q_norm"],
             c.rms_norm_eps)
    k = _rms(proj(p["wk"]).reshape(t, c.n_kv_head, c.head_dim),
             p["k_norm"], c.rms_norm_eps)
    if kind == "window":
        turn = lambda x: rotary(x.transpose(1, 0, 2), pos,
                                c.rope_theta).transpose(1, 0, 2)
        q, k = turn(q), turn(k)
    v = (a @ p["wv"]).reshape(t, c.n_kv_head, c.head_dim)
    return q, k, v, a @ p["wg"]


def _by_group(q, c):
    """q (T, H, D) -> (KV, g, T, D): the query heads of each K/V head."""
    t = q.shape[0]
    return q.reshape(t, c.n_kv_head, -1, c.head_dim).transpose(1, 2, 0, 3)


def _attn_out(o, gate, p, x):
    """Attention's result ``o`` (T, H·D) float32, gated element by
    element by the sigmoid of ``gate``, through W_o: (T, E) in the
    stream's dtype."""
    o = o * jax.nn.sigmoid(gate.astype(jnp.float32))
    return o.astype(x.dtype) @ p["wo"]


def _ffn(x, p, c, ffn, li, valid):
    """The layer's feed-forward on ``x`` (T, E), from its pre-norm to its
    post-norm: ``(y (T, E), counts)``; counts None for a dense layer.
    ``li`` indexes the expert stack (``p``'s other tensors are this
    layer's, the experts' the whole stack's: ops/expert_layer.held_terms
    slices an expert out)."""
    m = _rms(x, p["ln_pre_mlp"], c.rms_norm_eps)
    counts = None
    if ffn == "dense":
        with jax.named_scope("dense_mlp"):
            y = swiglu(m, p["w_gu"], p["w_down"])
    else:
        with jax.named_scope("moe_route"):
            idx, w = route(m, p["router"], p["bias"], n_group=c.n_group,
                           topk_group=c.topk_group,
                           top_k=c.num_experts_per_tok,
                           scale=c.route_scale)
        with jax.named_scope("moe_experts"):
            y, counts = held_terms(m, idx, w, p["e_gu"], p["e_down"],
                                   c.experts_held[0], valid, layer=li)
        with jax.named_scope("moe_shared"):
            y = y + swiglu(m, p["s_gu"], p["s_down"])
    return _rms(y.astype(x.dtype), p["ln_post_mlp"], c.rms_norm_eps), counts


def _scan_layers(body, carry, params, c):
    """Run ``body(carry, stack, period, window layer of the period or
    None, index in the stack, p) -> (carry, counts)`` over the model's
    layers in their order: the dense window layers, the first period's
    expert window layers and its full layer, then a period a scan step
    (its window layers an inner scan, then its full layer).  A layer's
    tensors are sliced out of their stack where the body runs; the
    experts' matrices are not: the body reaches into their whole stack.
    Returns (carry, counts (expert layers, n_held + 1), in the order
    ew0, ef[0], then by period)."""
    nd, nw = c.num_dense_layers, c.n_win

    def at(stack, i):
        return {k: (v if k in ("e_gu", "e_down") else v[i])
                for k, v in params[stack].items()}

    def window_layers(carry, stack, period, first_j, first_i, n):
        def step(carry, i):
            return body(carry, stack, period, first_j + i, first_i + i,
                        at(stack, first_i + i))

        return jax.lax.scan(step, carry, jnp.arange(n))

    def period(carry, t):
        carry, c_w = window_layers(carry, "ew", t, 0, (t - 1) * nw, nw)
        carry, c_f = body(carry, "ef", t, None, t, at("ef", t))
        return carry, jnp.concatenate([c_w, c_f[None]])

    carry, _ = window_layers(carry, "dw", 0, 0, 0, nd)
    carry, c_ew0 = window_layers(carry, "ew0", 0, nd, 0, nw - nd)
    carry, c_ef0 = body(carry, "ef", 0, None, 0, at("ef", 0))
    carry, c_rest = jax.lax.scan(period, carry,
                                 jnp.arange(1, c.n_periods))
    return carry, jnp.concatenate(
        [c_ew0, c_ef0[None], c_rest.reshape(-1, c_rest.shape[-1])])


def forward_full(params, ids, c):
    """ids (S,) -> logits (S, V): the whole sequence at once, no cache;
    the window layers under a band mask.  What ``Model.forward`` runs;
    serving goes through the family."""
    s = ids.shape[0]
    pos = jnp.arange(s)
    x = (c.embedding_multiplier * params["wte"][ids]).astype(
        params["wte"].dtype)
    causal = pos[None, :] <= pos[:, None]
    band = causal & (pos[:, None] - pos[None, :] < c.sliding_window)

    def layer(x, stack, _period, _j, i, p):
        ffn, kind = STACKS[stack]
        a = _rms(x, p["ln_in"], c.rms_norm_eps)
        q, k, v, gate = _qkvg(a, p, c, pos, kind)
        f32 = jnp.float32
        sc = jnp.einsum("kgsd,tkd->kgst", _by_group(q, c).astype(f32),
                        k.astype(f32)) / math.sqrt(c.head_dim)
        pr = jax.nn.softmax(jnp.where(band if kind == "window" else causal,
                                      sc, -jnp.inf), axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", pr, v.astype(f32)).reshape(s, -1)
        x = x + _rms(_attn_out(o, gate, p, x), p["ln_post_attn"],
                     c.rms_norm_eps)
        y, counts = _ffn(x, p, c, ffn, i, None)
        return x + y, counts

    x, _ = _scan_layers(layer, x, params, c)
    return _logits(params, _rms(x, params["lnf"], c.rms_norm_eps))


def _logits(params, hidden):
    """(..., E) -> (..., V), accumulated and returned in float32."""
    return jnp.dot(hidden, params["head"],
                   preferred_element_type=jnp.float32)


# ----------------------------------------------------- the served contract


@dataclass(frozen=True)
class SwaMoeFamily(ServedFamily):
    """The family for the serve engine (models/served.py): the budgeted
    paged path, the full layers' K/V in the pool and the window layers'
    in per-slot rings (the engine's state arenas).  Hashes by its
    configuration, so equal models share compiled programs."""

    cfg: SwaMoeConfig

    name = "swa_moe"
    features = frozenset()
    pad_aware = True
    step_counts = True
    # attn_window / attn_full: the attention itself against ring or pool
    # and the write of the new rows; attn_proj: the projections, norms
    # and gate either side of it
    scopes = ("attn_window", "attn_full", "attn_proj", "moe_route",
              "moe_experts", "moe_shared", "dense_mlp", "head")

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
        for stack in STACKS:
            vectors, matrices = _tensors(stack)
            out[stack] = {k: (st[f"{stack}_{k}"] if k in vectors
                              else cast(st[f"{stack}_{k}"]))
                          for k in vectors + matrices}
        return out

    def kv_geometry(self, cfg):
        """The paged cache is the FULL layers': one a period."""
        return cfg.n_periods, cfg.n_kv_head, cfg.head_dim

    def state_spec(self, cfg):
        """The window layers' rings: under the arena's leading period,
        the period's window layers, each ``ring`` rows as the pool
        stores rows; in the pool's dtype."""
        shape = (cfg.n_win, cfg.ring, cfg.kv_width)
        dt = jnp.dtype(cfg.dtype)
        return {"win_k": (shape, dt), "win_v": (shape, dt)}

    def logits(self, params, hidden):
        with jax.named_scope("head"):
            return _logits(params, hidden)

    def on_step_counts(self, counts, cfg):
        """``counts``: the expert layers' rows (assignments a held
        expert, and last those held elsewhere), then five rows whose
        first entry counts the step's lanes (:meth:`decode_step`)."""
        held = counts[:-5, :-1]
        lanes, full_rows, win_rows, full_cap, wraps = (
            int(v) for v in counts[-5:, 0])
        first = cfg.experts_held[0]
        incs = {("serve.moe.expert_tokens",
                 (("expert", str(first + e)),)): int(n)
                for e, n in enumerate(held.sum(0))}
        incs["serve.moe.assignments_elsewhere", ()] = int(
            counts[:-5, -1].sum())
        incs["serve.kv.ring_wraps", ()] = wraps
        rb = cfg.row_bytes(jnp.dtype(cfg.dtype).itemsize)
        ring_bytes = cfg.ring * rb["window"]
        gauges = {("serve.kv.window_ring_bytes", ()): ring_bytes,
                  ("serve.kv.row_bytes", (("kind", "full"),)): rb["full"],
                  ("serve.kv.row_bytes", (("kind", "window"),)):
                  rb["window"]}
        return dict(
            experts_hit=int(np.count_nonzero(held)),
            expert_tokens_max=int(held.max()),
            expert_tokens_mean=float(held.mean()),
            # what the live sequences' K/V takes here -- their blocks of
            # the full layers' pool and a ring each -- and what a cache
            # that kept every position of every layer would take for
            # the same blocks
            kv_bytes_held=full_cap * rb["full"] + lanes * ring_bytes,
            kv_bytes_uniform=full_cap * (rb["full"] + rb["window"]),
            window_ring_bytes=ring_bytes,
            # the rows a step must read: every cached position of the
            # full layers, the positions inside the window of the others
            full_rows=full_rows, window_rows=win_rows), incs, gauges

    def chunk_rows(self, params, segs, *, block, **_):
        """One launch: each segment a whole number of blocks of one
        request.  A full layer: a segment's queries over its private
        row below its ``off`` (block by block, the shared loop) and its
        own keys, the new rows written into that row.  A window layer:
        its queries over the request's ring -- the rows of the band
        below ``off`` -- and its own keys, THEN its rows laid into the
        ring (those of the prompt: what follows ``n_valid`` writes
        nothing and chooses no expert).  Projections, norms, gates and
        the feed-forward take the segments' tokens together."""
        c = self.cfg
        n_tok = sum(s.chunk for s in segs)
        valid = seg_valid(segs)
        toks, pos = seg_tokens(segs)
        x = (c.embedding_multiplier * jnp.take(params["wte"], toks, axis=0)
             ).astype(params["wte"].dtype)
        n_l, _, n_kv, width, d = segs[0].kc_row.shape
        # what lies below ``off`` is walked in STRIDES of eight blocks (a
        # stride's rows beyond ``off`` are masked): walked a block at a
        # time, a launch 12,000 positions in took twice a first one, and
        # the gap's p95 sat on that slope (PERF.md section 6, PR 37)
        stride = min(8 * block, width)
        rstride = min(8 * block, c.ring)
        below = [(row_to_blocks(s.kc_row, stride),
                  row_to_blocks(s.vc_row, stride)) for s in segs]
        tbl = jnp.arange(width // stride)
        cur = [jnp.tril(jnp.ones((s.chunk, s.chunk), bool)) for s in segs]
        scale = 1.0 / math.sqrt(d)
        # the rings a window layer a row: (P · J, ring, X)
        flat = lambda r: r.reshape((-1,) + r.shape[2:])
        rows_of = lambda t: t.transpose(1, 0, 2).reshape(t.shape[1], -1)

        def layer(carry, stack, period, j, i, p):
            x, *rows = carry
            kc_rows, vc_rows, win_k, win_v = map(list, rows)
            ffn, kind = STACKS[stack]
            with jax.named_scope("attn_proj"):
                a = _rms(x, p["ln_in"], c.rms_norm_eps)
                q, k, v, gate = _qkvg(a, p, c, pos, kind)
                q = _by_group(q, c)
                k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
            o = []
            for n, (s, q_s, k_s, v_s) in enumerate(zip(
                    segs, seg_split(q, segs, 2), seg_split(k, segs, 1),
                    seg_split(v, segs, 1))):
                if kind == "window":
                    with jax.named_scope("attn_window"):
                        li = period * c.n_win + j
                        o.append(ring_chunk_attn(
                            q_s, win_k[n], win_v[n], li, s.off, rstride,
                            -(-width // rstride), rows_of(k_s),
                            rows_of(v_s), scale, c.sliding_window))
                        win_k[n] = ring_write_chunk(
                            win_k[n], li, rows_of(k_s), s.off, s.n_valid,
                            block)
                        win_v[n] = ring_write_chunk(
                            win_v[n], li, rows_of(v_s), s.off, s.n_valid,
                            block)
                else:
                    with jax.named_scope("attn_full"):
                        o.append(paged_attn(
                            q_s, *below[n], period, tbl, s.off,
                            -(-s.off // stride), stride, -1, rows_of(k_s),
                            rows_of(v_s), cur[n], scale))
                        kc_rows[n] = jax.lax.dynamic_update_slice(
                            kc_rows[n],
                            k_s[None, None].astype(kc_rows[n].dtype),
                            (period, 0, 0, s.off, 0))
                        vc_rows[n] = jax.lax.dynamic_update_slice(
                            vc_rows[n],
                            v_s[None, None].astype(vc_rows[n].dtype),
                            (period, 0, 0, s.off, 0))
            with jax.named_scope("attn_proj"):
                o = seg_cat(o, 2).transpose(2, 0, 1, 3).reshape(n_tok, -1)
                x = x + _rms(_attn_out(o, gate, p, x), p["ln_post_attn"],
                             c.rms_norm_eps)
            y, counts = _ffn(x, p, c, ffn, i, valid)
            return (x + y, tuple(kc_rows), tuple(vc_rows), tuple(win_k),
                    tuple(win_v)), counts

        (x, kc_rows, vc_rows, win_k, win_v), _ = _scan_layers(
            layer, (x, tuple(s.kc_row for s in segs),
                    tuple(s.vc_row for s in segs),
                    tuple(flat(s.state["win_k"]) for s in segs),
                    tuple(flat(s.state["win_v"]) for s in segs)),
            params, c)
        hidden = _rms(x, params["lnf"], c.rms_norm_eps)
        back = lambda r: r.reshape(segs[0].state["win_k"].shape)
        return [(h[None], kc, vc, {"win_k": back(wk), "win_v": back(wv)})
                for h, kc, vc, wk, wv in zip(
                    seg_split(hidden, segs), kc_rows, vc_rows, win_k,
                    win_v)]

    def decode_step(self, params, pool_k, pool_v, state, slots, tables,
                    toks, pos, live, n_blk, *, block, trash, **_):
        """Every lane one token.  A full layer: each lane's query over
        its live blocks of the pool plus its own new key (the shared
        loop, to the longest lane's bound), the new K/V row written
        straight into the pool.  A window layer: each lane's query over
        ITS ring and its own new key, the new row written into the ring
        (a lane at a time: ``ring_decode_attn``).  Pool and arenas are
        carried through the layer scans and updated in place.  Returns
        the experts' counts of the step, and five counts of its lanes,
        after the contract's four."""
        c = self.cfg
        p_c = jnp.where(live, pos, 0)
        t_c = jnp.where(live, toks, 0)
        x = (c.embedding_multiplier * params["wte"][t_c]).astype(
            params["wte"].dtype)                             # (W, E)
        n_kv, d = c.n_kv_head, c.head_dim
        g = c.n_head // n_kv
        n_w = x.shape[0]
        scale = 1.0 / math.sqrt(d)

        def layer(carry, stack, period, j, i, p):
            x, pool_k, pool_v, win_k, win_v = carry
            ffn, kind = STACKS[stack]
            with jax.named_scope("attn_proj"):
                a = _rms(x, p["ln_in"], c.rms_norm_eps)
                q, k, v, gate = _qkvg(a, p, c, p_c, kind)
                q = q.reshape(n_w, n_kv, g, d)
                k, v = k.reshape(n_w, -1), v.reshape(n_w, -1)
            if kind == "window":
                with jax.named_scope("attn_window"):
                    o, win_k, win_v = ring_decode_attn(
                        q, k, v, win_k, win_v, (period, j), slots, p_c,
                        scale, c.sliding_window)
            else:
                with jax.named_scope("attn_full"):
                    o = paged_decode_attn(
                        q, pool_k, pool_v, period, tables, p_c, block,
                        trash, k, v, scale, n_blk=n_blk)
                    pool_k = write_rows(pool_k, period, k[:, None],
                                        tables, p_c, live, block, trash)
                    pool_v = write_rows(pool_v, period, v[:, None],
                                        tables, p_c, live, block, trash)
            with jax.named_scope("attn_proj"):
                x = x + _rms(_attn_out(o.reshape(n_w, -1), gate, p, x),
                             p["ln_post_attn"], c.rms_norm_eps)
            y, counts = _ffn(x, p, c, ffn, i, live)
            return (x + y, pool_k, pool_v, win_k, win_v), counts

        (x, pool_k, pool_v, win_k, win_v), counts = _scan_layers(
            layer, (x, pool_k, pool_v, state["win_k"], state["win_v"]),
            params, c)
        logits = self.logits(params, _rms(x, params["lnf"],
                                          c.rms_norm_eps))
        # the step's lanes: how many, the rows they hold after it (every
        # position; those inside the window), the positions their blocks
        # of the pool have room for, and the rings that came round
        held = jnp.where(live, p_c + 1, 0)
        lanes = jnp.stack([
            jnp.sum(live), jnp.sum(held),
            jnp.sum(jnp.minimum(held, c.sliding_window)),
            block * jnp.sum((tables != trash) & live[:, None]),
            jnp.sum(live & (p_c > 0) & (p_c % c.ring == 0))]).astype(
                jnp.int32)
        counts = jnp.concatenate([
            counts, jnp.zeros((5, counts.shape[1]), jnp.int32)
            .at[:, 0].set(lanes)])
        return (logits, pool_k, pool_v, {"win_k": win_k, "win_v": win_v},
                counts)


# ---------------------------------------------------------------- the model


@partial(jax.jit, static_argnames=("c",))
def _init_params(key, c):
    # the device's own bit generator: billions of draws at memory speed
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    big, out = jnp.dtype(c.dtype), {}

    def matrix(k, full):
        # a slab of the leading axis at a time (whole 8-row tiles), so
        # that the draw's temporaries are a slab's; uniform with the
        # published N(0, 0.02)'s variance (a normal draw's inverse error
        # function takes seconds a tensor to compile for the chip)
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


class SwaMoeLMHead(model.Model):
    """The causal LM as a ``Model``: stacked weights, an inference
    forward, and ``serve()``."""

    def __init__(self, cfg=None):
        super().__init__()
        self.cfg = cfg or SwaMoeConfig()

    def initialize(self, ids):
        """Creates the parameters in ``cfg.dtype`` (vectors and the
        router float32), drawn in that dtype by one program: matrices
        with the published initialiser's variance (0.02²), norms at 1,
        the router's bias at 0."""
        dev = ids.device
        for name, a in _init_params(dev.rng_key(), self.cfg).items():
            setattr(self, name, Tensor(
                data=jax.device_put(a, dev.jax_device), device=dev,
                requires_grad=True, stores_grad=True))

    def served_family(self):
        return SwaMoeFamily(self.cfg)

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
              for a in leaves], _name="SwaMoeForward")

    def serve(self, **kw):
        """The continuous-batching engine over this model
        (:class:`singa_tpu.serve.InferenceEngine`): pass
        ``paged=PagedConfig(..., prefill_token_budget=)``, ``dtype=``,
        ``max_slots=``.  What this family does not implement the engine
        refuses by name (docs/SERVING.md "The served-model contract")."""
        from ..serve import InferenceEngine

        return InferenceEngine(self, **kw)
