"""A decoder whose every layer is ONE mixer -- a Mamba-2 state-space
mixer, grouped-query attention without positions, or a mixture of experts
that works in a latent -- behind one RMS norm, added to the residual
stream (``model_type`` ``nemotron_h``, as NVIDIA publishes
Nemotron-3-Super-120B-A12B:
huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16; the
equations are written out key by key in
``benchmark/references/ssm_moe.py``, the plain reference the tests hold
this file to).

``hybrid_override_pattern`` says which mixer a layer is, a letter a
layer: ``M`` a Mamba-2 mixer (in-projection to ``z | x B C | dt``, a
depthwise causal convolution over ``x B C``, the recurrence of
``ops/mamba2.py``, a gate, a grouped RMS norm, an out-projection), ``*``
causal attention with no rotary and no learned position (the recurrent
layers carry the order), ``E`` a LatentMoE: a sigmoid router over all
``n_routed_experts`` chooses ``num_experts_per_tok`` of them a token; the
routed experts -- two matrices and ``relu^2`` each -- work in a
``moe_latent_size``-wide latent behind ONE shared down- and up-projection,
and one shared expert on the full hidden size is added
(``ops/expert_layer.py``: this chip is told which experts it holds; by
default all of them).  There is no feed-forward beside a mixer: the
expert layer is a layer of its own.  The head is not tied to the
embedding.

What is here is the SERVING side: ``SsmMoeLMHead(cfg).serve(paged=...)``
returns the one :class:`~singa_tpu.serve.InferenceEngine`, which calls
the math below through :class:`SsmMoeFamily` (models/served.py).

**One mixer a layer: K/V for the attention layers alone, state for the
Mamba layers alone.**  Keys and values are paged: the engine's block
pool, block tables and private prefill row, described by ``kv_geometry``
as a model of the ``*`` layers alone.  An ``M`` layer keeps, a sequence,
its state ``(heads, head size, state size)`` in float32 and the last
inputs of its convolution -- ``conv_kernel`` rows of which the newest
``conv_kernel - 1`` are read (``ops/mamba2.step`` says why a row more is
kept) -- whatever the sequence's length.  Both are declared through ``state_spec``: the engine keeps them
in its state arenas, zeroes them at admission, carries them from chunk
row to chunk row, writes them when the slot goes live and saves and
restores them with the slot.  The arenas' leading axis is the paged
cache's layers, so an attention layer's row holds the state of
``state_rows`` Mamba layers (Mamba layer ``i`` at row ``i //
state_rows``, place ``i % state_rows``; where the Mamba layers do not
divide by the attention layers the last places stay zero).  An ``E``
layer keeps nothing.

Identical layers are kept as STACKED weights, a stack a kind (``m``,
``a``, ``e``), and every program walks the layers as the pattern says:
the stretch of letters that repeats most is one ``lax.scan`` over its
periods, what lies before and after it runs layer by layer.  Matrices are
built in ``cfg.dtype``; the per-channel vectors, the convolution's taps,
the router and its bias stay float32, and so does the recurrent state.
Training is not here (ROADMAP Reach A).
"""

import math
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd, model
from ..ops import mamba2
from ..ops.expert_layer import held_terms, relu2, route
from ..ops.paged_attention import (paged_attn, paged_decode_attn,
                                   row_to_blocks, write_rows)
from ..tensor import Tensor
from .served import ServedFamily, seg_cat, seg_split, seg_tokens, seg_valid

#: rows of an expert's tile (ops/expert_layer.held_terms): at 22 choices
#: a token over hundreds of small experts a held expert sees a handful of
#: tokens a decode step, and a tile of 16 covers them
TILE = 16

#: the pattern's letters -> the stack of that kind of layer
KINDS = {"M": "m", "*": "a", "E": "e"}
#: a stack's tensors: float32 vectors (the router among them), then
#: matrices in ``cfg.dtype``
VECTORS = {"m": ("ln", "conv_w", "conv_b", "dt_bias", "a_log", "d", "norm"),
           "a": ("ln",),
           "e": ("ln", "router", "bias")}
MATRICES = {"m": ("w_in", "w_out"),
            "a": ("wq", "wk", "wv", "wo"),
            "e": ("w_fc1", "w_fc2", "w_su", "w_sd", "e_up", "e_down")}
_EXPERTS = ("e_up", "e_down")

PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")


@lru_cache(maxsize=None)
def _plan(pattern):
    """The pattern split in three, ``(head, (unit, repeats), tail)``:
    ``unit`` the stretch of letters whose back-to-back repeats cover the
    most layers (``repeats`` 0 and no unit if nothing repeats)."""
    n, best = len(pattern), (0, 0, 0, 0)  # layers covered, start, unit, n
    for start in range(n):
        for u in range(1, (n - start) // 2 + 1):
            unit, r = pattern[start:start + u], 1
            while pattern[start + r * u:start + (r + 1) * u] == unit:
                r += 1
            if r > 1 and r * u > best[0]:
                best = (r * u, start, u, r)
    _, start, u, r = best
    return (pattern[:start], (pattern[start:start + u], r),
            pattern[start + r * u:])


@dataclass(frozen=True)
class SsmMoeConfig:
    """The published ``config.json`` keys that shape the model, plus
    ``experts_held`` -- the ownership range ``(first, end)`` of the
    router's outputs whose experts this chip holds, all of them unless
    told -- ``max_len``, the served context, and ``dtype``, what the
    matrices are built in."""

    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    experts_held: tuple = None
    max_len: int = 2048
    dtype: str = "float32"

    def __post_init__(self):
        held = self.experts_held or (0, self.n_routed_experts)
        object.__setattr__(self, "experts_held",
                           tuple(int(v) for v in held))
        for k in ("routed_scaling_factor", "layer_norm_epsilon"):
            object.__setattr__(self, k, float(getattr(self, k)))
        pat = self.hybrid_override_pattern
        if len(pat) != self.num_hidden_layers or set(pat) - set(KINDS):
            raise ValueError(
                f"hybrid_override_pattern must name num_hidden_layers "
                f"({self.num_hidden_layers}) layers, each one of "
                f"{''.join(KINDS)}")
        if set(KINDS) - set(pat):
            raise ValueError(
                "hybrid_override_pattern must hold a layer of each kind "
                "(M, *, E): the paged cache is the attention layers', the "
                "state arenas' rows go by them")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.mamba_num_heads % self.n_groups:
            raise ValueError("K/V heads must divide the heads, n_groups "
                             "the Mamba heads")
        if not self.use_conv_bias or not self.norm_topk_prob \
                or self.n_shared_experts != 1:
            raise ValueError("use_conv_bias false, norm_topk_prob false "
                             "and n_shared_experts other than 1 are not "
                             "implemented")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the router's "
                             f"{self.n_routed_experts} outputs")
        if self.max_len > self.max_position_embeddings:
            raise ValueError("max_len exceeds max_position_embeddings")

    # the engine's names for what it reads off any model's cfg
    n_layer = property(lambda s: s.num_hidden_layers)
    n_head = property(lambda s: s.num_attention_heads)
    n_kv_head = property(lambda s: s.num_key_value_heads)
    n_embd = property(lambda s: s.hidden_size)
    n_positions = property(lambda s: s.max_len)
    layer_norm_eps = property(lambda s: s.layer_norm_epsilon)
    n_held = property(lambda s: s.experts_held[1] - s.experts_held[0])
    n_m = property(lambda s: s.hybrid_override_pattern.count("M"))
    n_a = property(lambda s: s.hybrid_override_pattern.count("*"))
    n_e = property(lambda s: s.hybrid_override_pattern.count("E"))
    kv_width = property(lambda s: s.num_key_value_heads * s.head_dim)
    d_ssm = property(lambda s: s.mamba_num_heads * s.mamba_head_dim)
    #: channels of the convolution: x, B and C side by side
    conv_dim = property(lambda s: s.d_ssm
                        + 2 * s.n_groups * s.ssm_state_size)
    #: Mamba layers' states in one attention layer's row of the arenas
    state_rows = property(lambda s: -(-s.n_m // s.n_a))

    def plan(self):
        return _plan(self.hybrid_override_pattern)

    def stack_sizes(self):
        """{stack: layers in it}."""
        return {"m": self.n_m, "a": self.n_a, "e": self.n_e}

    def place(self, layer):
        """Layer ``layer`` of the model -> (stack, index in the stack)."""
        pat = self.hybrid_override_pattern
        return KINDS[pat[layer]], pat[:layer].count(pat[layer])

    def state_bytes(self):
        """What one sequence's recurrent state takes (float32): every
        Mamba layer's state and its convolution's tail."""
        return 4 * self.n_m * (
            self.mamba_num_heads * self.mamba_head_dim * self.ssm_state_size
            + self.conv_kernel * self.conv_dim)

    def shapes(self, stack):
        """{tensor: shape} of one layer of ``stack``, or of the three
        tensors outside the layers ("model")."""
        c, e = self, self.hidden_size
        if stack == "model":
            return dict(wte=(c.vocab_size, e), head=(e, c.vocab_size),
                        lnf=(e,))
        if stack == "m":
            h = c.mamba_num_heads
            return dict(
                ln=(e,), conv_w=(c.conv_kernel, c.conv_dim),
                conv_b=(c.conv_dim,), dt_bias=(h,), a_log=(h,), d=(h,),
                norm=(c.d_ssm,), w_in=(e, c.d_ssm + c.conv_dim + h),
                w_out=(c.d_ssm, e))
        if stack == "a":
            qd = c.n_head * c.head_dim
            return dict(ln=(e,), wq=(e, qd), wk=(e, c.kv_width),
                        wv=(e, c.kv_width), wo=(qd, e))
        lat, im = c.moe_latent_size, c.moe_intermediate_size
        sh = c.moe_shared_expert_intermediate_size
        return dict(ln=(e,), router=(e, c.n_routed_experts),
                    bias=(c.n_routed_experts,), w_fc1=(e, lat),
                    w_fc2=(lat, e), w_su=(e, sh), w_sd=(sh, e),
                    e_up=(c.n_held, lat, im), e_down=(c.n_held, im, lat))


# --------------------------------------------------------------------- math


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _mixer_inputs(a, p, c):
    """a (T, E) normalised -> z (T, d_ssm), xBC (T, conv_dim) before the
    conv, dt (T, heads) before its bias; float32."""
    zxbcdt = jnp.dot(a, p["w_in"], preferred_element_type=jnp.float32)
    ds, cd = c.d_ssm, c.conv_dim
    return zxbcdt[:, :ds], zxbcdt[:, ds:ds + cd], zxbcdt[:, ds + cd:]


def _mixer_out(y, z, p, c):
    """The gate, then the grouped RMS norm, then the out-projection: y, z
    (T, d_ssm) float32."""
    t = y.shape[0]
    y = (y * jax.nn.silu(z)).reshape(t, c.n_groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + c.layer_norm_epsilon)
    y = y.reshape(t, -1) * p["norm"]
    return y.astype(p["w_out"].dtype) @ p["w_out"]


def _qkv(a, p, c):
    """a (T, E) normalised -> q (T, H, D), k and v (T, KV, D): no bias,
    no norm and no rotation."""
    t = a.shape[0]
    return ((a @ p["wq"]).reshape(t, c.n_head, c.head_dim),
            (a @ p["wk"]).reshape(t, c.n_kv_head, c.head_dim),
            (a @ p["wv"]).reshape(t, c.n_kv_head, c.head_dim))


def _by_group(q, c):
    """q (T, H, D) -> (KV, g, T, D): the query heads of each K/V head."""
    t = q.shape[0]
    return q.reshape(t, c.n_kv_head, -1, c.head_dim).transpose(1, 2, 0, 3)


def _to_latent(a, p):
    """The ONE down-projection before the routed experts: (T, E) -> (T,
    latent)."""
    return a @ p["w_fc1"]


def _shared_expert(a, p):
    """The shared expert, on the full hidden size; float32."""
    return relu2(a, p["w_su"], p["w_sd"])


def _moe(a, p, c, li, valid):
    """The expert layer on ``a`` (T, E) normalised: ``(y (T, E) float32,
    counts)``.  ``li`` indexes the expert stacks (``p``'s other tensors
    are this layer's, the experts' the whole stack's:
    ops/expert_layer.held_terms slices an expert out)."""
    with jax.named_scope("moe_route"):
        idx, w = route(a, p["router"], p["bias"], n_group=c.n_group,
                       topk_group=c.topk_group, top_k=c.num_experts_per_tok,
                       scale=c.routed_scaling_factor)
    with jax.named_scope("moe_latent"):
        u = _to_latent(a, p)
    with jax.named_scope("moe_experts"):
        r, counts = held_terms(u, idx, w, p["e_up"], p["e_down"],
                               c.experts_held[0], valid, layer=li,
                               tile=TILE, body=relu2)
    with jax.named_scope("moe_latent"):
        y = jnp.dot(r.astype(u.dtype), p["w_fc2"],
                    preferred_element_type=jnp.float32) \
            + _shared_expert(a, p)
    return y, counts


def _walk(body, carry, params, c):
    """Run ``body(carry, stack, index in the stack, p) -> (carry, counts
    or None)`` over the model's layers as the pattern says
    (:func:`_plan`): the repeating stretch a scan over its periods, the
    rest layer by layer.  A layer's tensors are sliced out of their stack
    where the body runs; the experts' matrices are not: the body reaches
    into their whole stack.  Returns (carry, counts (expert layers,
    n_held + 1))."""
    head, (unit, repeats), tail = c.plan()

    def at(stack, i):
        return {k: (v if k in _EXPERTS else v[i])
                for k, v in params[stack].items()}

    def run(carry, letters, base, t=0):
        # period ``t`` of the unit lies ``t`` units' layers further into
        # each stack
        seen, out = dict(base), []
        for letter in letters:
            stack = KINDS[letter]
            i = seen[stack] + t * letters.count(letter)
            carry, cnt = body(carry, stack, i, at(stack, i))
            seen[stack] += 1
            if cnt is not None:
                out.append(cnt[None])
        return carry, (jnp.concatenate(out) if out else None)

    def after(base, letters, n=1):
        return {k: v + n * sum(KINDS[x] == k for x in letters)
                for k, v in base.items()}

    base = dict.fromkeys(KINDS.values(), 0)
    counts = []
    carry, cnt = run(carry, head, base)
    counts.append(cnt)
    base = after(base, head)
    if repeats:
        carry, cnt = jax.lax.scan(
            lambda carry, t, base=base: run(carry, unit, base, t), carry,
            jnp.arange(repeats))
        counts.append(None if cnt is None
                      else cnt.reshape(-1, cnt.shape[-1]))
        base = after(base, unit, repeats)
    carry, cnt = run(carry, tail, base)
    counts.append(cnt)
    return carry, jnp.concatenate([k for k in counts if k is not None])


def _zero_state(c):
    return (jnp.zeros((c.mamba_num_heads, c.mamba_head_dim,
                       c.ssm_state_size), jnp.float32),
            jnp.zeros((c.conv_kernel - 1, c.conv_dim), jnp.float32))


def forward_full(params, ids, c):
    """ids (S,) -> logits (S, V): the whole sequence at once, no cache
    (full causal attention; the scan chunk by chunk from a zero state).
    What ``Model.forward`` runs; serving goes through the family."""
    s, t = ids.shape[0], c.chunk_size
    n_chunks = -(-s // t)
    x = params["wte"][ids]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, stack, i, p):
        a = _rms(x, p["ln"], c.layer_norm_epsilon)
        if stack == "e":
            y, counts = _moe(a, p, c, i, None)
            return x + y.astype(x.dtype), counts
        if stack == "a":
            q, k, v = _qkv(a, p, c)
            f32 = jnp.float32
            sc = jnp.einsum("kgsd,tkd->kgst", _by_group(q, c).astype(f32),
                            k.astype(f32)) / math.sqrt(c.head_dim)
            pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            o = jnp.einsum("kgst,tkd->skgd", pr, v.astype(f32))
            return x + o.reshape(s, -1).astype(x.dtype) @ p["wo"], None
        z, xbc, dt = _mixer_inputs(a, p, c)
        pad = ((0, n_chunks * t - s), (0, 0))
        xbc, dt = jnp.pad(xbc, pad), jnp.pad(dt, pad)

        def chunk(state, j):
            y, ssm, conv = mamba2.mix(
                jax.lax.dynamic_slice_in_dim(xbc, j * t, t),
                jax.lax.dynamic_slice_in_dim(dt, j * t, t), p, *state,
                jnp.minimum(t, s - j * t))
            return (ssm, conv), y

        _, y = jax.lax.scan(chunk, _zero_state(c), jnp.arange(n_chunks))
        y = y.reshape(n_chunks * t, -1)[:s]
        return x + _mixer_out(y, z, p, c).astype(x.dtype), None

    x, _ = _walk(layer, x, params, c)
    return _logits(params, _rms(x, params["lnf"], c.layer_norm_epsilon))


def _logits(params, hidden):
    """(..., E) -> (..., V) through the untied head, accumulated and
    returned in float32."""
    return jnp.dot(hidden, params["head"],
                   preferred_element_type=jnp.float32)


# ----------------------------------------------------- the served contract


@dataclass(frozen=True)
class SsmMoeFamily(ServedFamily):
    """The family for the serve engine (models/served.py): the budgeted
    paged path, the attention layers' K/V in the pool and the Mamba
    layers' state in the engine's state arenas.  Hashes by its
    configuration, so equal models share compiled programs."""

    cfg: SsmMoeConfig

    name = "ssm_moe"
    features = frozenset()
    pad_aware = True
    step_counts = True
    # ssm_scan / ssm_step: the recurrence itself, inside the mixer's
    # projections, conv, gate and norm (ssm_proj); attn_full: the
    # attention against the pool and the write of the new rows;
    # attn_proj: the projections either side of it; moe_latent: the two
    # latent projections and the shared expert, either side of
    # moe_experts
    scopes = ("ssm_proj", "ssm_scan", "ssm_step", "attn_full", "attn_proj",
              "moe_route", "moe_latent", "moe_experts", "head")

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
        for stack in KINDS.values():
            out[stack] = {k: (st[f"{stack}_{k}"] if k in VECTORS[stack]
                              else cast(st[f"{stack}_{k}"]))
                          for k in VECTORS[stack] + MATRICES[stack]}
        return out

    def kv_geometry(self, cfg):
        """The paged cache is the ATTENTION layers'."""
        return cfg.n_a, cfg.n_kv_head, cfg.head_dim

    def state_spec(self, cfg):
        """The Mamba layers' state, ``state_rows`` of them under each of
        the arenas' leading rows (the attention layers'): the SSM state
        and the last ``conv_kernel`` inputs of the convolution (one more
        than it reads: ops/mamba2.step); float32."""
        f32 = jnp.dtype("float32")
        return {"ssm": ((cfg.state_rows, cfg.mamba_num_heads,
                         cfg.mamba_head_dim, cfg.ssm_state_size), f32),
                "conv": ((cfg.state_rows, cfg.conv_kernel, cfg.conv_dim),
                         f32)}

    def state_step_impl(self, state):
        return mamba2.step_impl(state["ssm"])

    def logits(self, params, hidden):
        with jax.named_scope("head"):
            return _logits(params, hidden)

    def on_step_counts(self, counts, cfg):
        """``counts``: a row an expert layer, the assignments each held
        expert received and last those held elsewhere.  The tiles the
        expert loop ran follow from them (an expert takes a tile for
        every ``TILE`` assignments, and one if it has none), and so do
        the live lanes: each made ``num_experts_per_tok`` choices a
        layer."""
        held = counts[:, :-1]
        tiles = int(np.maximum(-(-held // TILE), 1).sum())
        choices = int(counts.sum())
        lanes = choices // (cfg.n_e * cfg.num_experts_per_tok)
        incs = {("serve.moe.assignments_elsewhere", ()):
                int(counts[:, -1].sum()),
                ("serve.moe.tiles", ()): tiles}
        gauges = {("serve.state.ssm_bytes", ()): lanes * cfg.state_bytes()}
        return dict(experts_hit=int(np.count_nonzero(held)),
                    expert_tiles=tiles,
                    expert_tokens_max=int(held.max()),
                    expert_tokens_mean=float(held.mean()),
                    choices_elsewhere=100.0 * float(counts[:, -1].sum())
                    / max(choices, 1)), incs, gauges

    def chunk_rows(self, params, segs, *, block, **_):
        """One launch: each segment a whole number of blocks of one
        request (a block = one scan chunk).  An attention layer: a
        segment's queries over its private row below its ``off`` (the
        shared loop) and its own keys, the new rows written into that
        row.  A Mamba layer: the conv and the chunked scan, a block at a
        time, from the state the segment's row before left; what lies
        past ``n_valid`` leaves the state alone (and chooses no expert).
        Projections, gate, norm and the expert layers take the segments'
        tokens together."""
        c = self.cfg
        n_tok = sum(s.chunk for s in segs)
        valid = seg_valid(segs)
        toks, _ = seg_tokens(segs)
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

        def layer(carry, stack, i, p):
            x, kc_rows, vc_rows, ssm, conv = carry
            a = _rms(x, p["ln"], c.layer_norm_epsilon)
            if stack == "e":
                y, counts = _moe(a, p, c, i, valid)
                return (x + y.astype(x.dtype), kc_rows, vc_rows, ssm,
                        conv), counts
            if stack == "m":
                with jax.named_scope("ssm_proj"):
                    z, xbc, dt = _mixer_inputs(a, p, c)
                    y, new_ssm, new_conv = zip(*(
                        mamba2.mix(xbc_s, dt_s, p, ssm_s[i], conv_s[i],
                                   s.n_valid, sub=block)
                        for s, xbc_s, dt_s, ssm_s, conv_s in zip(
                            segs, seg_split(xbc, segs),
                            seg_split(dt, segs), ssm, conv)))
                    o = _mixer_out(seg_cat(y), z, p, c)
                    ssm = tuple(a_.at[i].set(n)
                                for a_, n in zip(ssm, new_ssm))
                    conv = tuple(a_.at[i].set(n)
                                 for a_, n in zip(conv, new_conv))
                return (x + o.astype(x.dtype), kc_rows, vc_rows, ssm,
                        conv), None
            with jax.named_scope("attn_proj"):
                q, k, v = _qkv(a, p, c)
                q = _by_group(q, c)
                k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
            with jax.named_scope("attn_full"):
                o, kc_rows, vc_rows = [], list(kc_rows), list(vc_rows)
                for j, (s, q_s, k_s, v_s) in enumerate(zip(
                        segs, seg_split(q, segs, 2),
                        seg_split(k, segs, 1), seg_split(v, segs, 1))):
                    o.append(paged_attn(
                        q_s, *below[j], i, tbl, s.off,
                        -(-s.off // stride), stride, -1, rows_of(k_s),
                        rows_of(v_s), cur[j], scale))
                    kc_rows[j] = jax.lax.dynamic_update_slice(
                        kc_rows[j],
                        k_s[None, None].astype(kc_rows[j].dtype),
                        (i, 0, 0, s.off, 0))
                    vc_rows[j] = jax.lax.dynamic_update_slice(
                        vc_rows[j],
                        v_s[None, None].astype(vc_rows[j].dtype),
                        (i, 0, 0, s.off, 0))
                o, kc_rows, vc_rows = (seg_cat(o, 2), tuple(kc_rows),
                                       tuple(vc_rows))
            with jax.named_scope("attn_proj"):
                o = o.transpose(2, 0, 1, 3).reshape(n_tok, -1)
                o = o.astype(x.dtype) @ p["wo"]
            return (x + o, kc_rows, vc_rows, ssm, conv), None

        # a segment's states as one row a Mamba layer
        flat = lambda a_: a_.reshape((-1,) + a_.shape[2:])
        (x, kc_rows, vc_rows, ssm, conv), _ = _walk(
            layer, (x, tuple(s.kc_row for s in segs),
                    tuple(s.vc_row for s in segs),
                    tuple(flat(s.state["ssm"]) for s in segs),
                    tuple(flat(s.state["conv"]) for s in segs)),
            params, c)
        hidden = _rms(x, params["lnf"], c.layer_norm_epsilon)
        return [(h[None], kc, vc,
                 {"ssm": sm.reshape(s.state["ssm"].shape),
                  "conv": cv.reshape(s.state["conv"].shape)})
                for s, h, kc, vc, sm, cv in zip(
                    segs, seg_split(hidden, segs), kc_rows, vc_rows, ssm,
                    conv)]

    def decode_step(self, params, pool_k, pool_v, state, slots, tables,
                    toks, pos, live, n_blk, *, block, trash, **_):
        """Every lane one token.  An attention layer: each lane's query
        over its live blocks of the pool plus its own new key, the new
        K/V row written straight into the pool.  A Mamba layer: each
        lane's state read from the arenas at its slot, advanced one step
        and written back (dead lanes: the trash row; ``ops/mamba2.step``:
        all lanes in one Pallas call on a TPU, a lane at a time anywhere
        else).  Pool and arenas
        are carried through the walk and updated in place.  Returns the
        expert layers' counts of the step after the contract's four."""
        c = self.cfg
        p_c = jnp.where(live, pos, 0)
        t_c = jnp.where(live, toks, 0)
        x = params["wte"][t_c]                                   # (W, E)
        n_kv, d, rows = c.n_kv_head, c.head_dim, c.state_rows
        n_w = x.shape[0]
        scale = 1.0 / math.sqrt(d)

        def layer(carry, stack, i, p):
            x, pool_k, pool_v, ssm, conv = carry
            a = _rms(x, p["ln"], c.layer_norm_epsilon)
            if stack == "e":
                y, counts = _moe(a, p, c, i, live)
                return (x + y.astype(x.dtype), pool_k, pool_v, ssm,
                        conv), counts
            if stack == "m":
                with jax.named_scope("ssm_proj"):
                    z, xbc, dt = _mixer_inputs(a, p, c)
                    y, ssm, conv = mamba2.step(
                        xbc, dt, p, ssm, conv,
                        lambda slot: (i // rows, slot, i % rows), slots)
                    o = _mixer_out(y, z, p, c)
                return (x + o.astype(x.dtype), pool_k, pool_v, ssm,
                        conv), None
            with jax.named_scope("attn_proj"):
                q, k, v = _qkv(a, p, c)
                q = q.reshape(n_w, n_kv, -1, d)
                k, v = k.reshape(n_w, -1), v.reshape(n_w, -1)
            with jax.named_scope("attn_full"):
                o = paged_decode_attn(
                    q, pool_k, pool_v, i, tables, p_c, block, trash, k, v,
                    scale, n_blk=n_blk)
                pool_k = write_rows(pool_k, i, k[:, None], tables, p_c,
                                    live, block, trash)
                pool_v = write_rows(pool_v, i, v[:, None], tables, p_c,
                                    live, block, trash)
            with jax.named_scope("attn_proj"):
                o = o.reshape(n_w, -1).astype(x.dtype) @ p["wo"]
            return (x + o, pool_k, pool_v, ssm, conv), None

        (x, pool_k, pool_v, ssm, conv), counts = _walk(
            layer, (x, pool_k, pool_v, state["ssm"], state["conv"]),
            params, c)
        logits = self.logits(params, _rms(x, params["lnf"],
                                          c.layer_norm_epsilon))
        return logits, pool_k, pool_v, {"ssm": ssm, "conv": conv}, counts


# ---------------------------------------------------------------- the model


@partial(jax.jit, static_argnames=("c",))
def _init_params(key, c):
    # the device's own bit generator: billions of draws at memory speed
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    big, out = jnp.dtype(c.dtype), {}

    def matrix(k, full, fan):
        # a slab of the leading axis at a time, so that the draw's
        # temporaries are a slab's; uniform with N(0, 1 / fan)'s variance
        n = max(d for d in range(1, 17)
                if full[0] % d == 0 and (d == 1 or full[0] // d % 8 == 0
                                         or len(full) > 2))
        slab = (full[0] // n,) + full[1:]
        a = math.sqrt(3.0 / fan)
        return jax.lax.map(
            lambda kk: jax.random.uniform(kk, slab, big, -a, a),
            jax.random.split(k, n)).reshape(full)

    def tensor(i, name, full, vector):
        k = jax.random.fold_in(key, i)
        if name == "a_log":
            return jnp.log(jax.random.uniform(k, full, jnp.float32, 1.0,
                                              16.0))
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, full, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))         # softplus^-1
        if name == "conv_w":
            return jax.random.uniform(k, full, jnp.float32, -0.8, 0.8)
        if name == "router":
            a = math.sqrt(3.0 / full[-2])
            return jax.random.uniform(k, full, jnp.float32, -a, a)
        if name in ("bias", "conv_b"):
            return jnp.zeros(full, jnp.float32)
        if vector:                               # norm weights and D
            return jnp.ones(full, jnp.float32)
        return matrix(k, full, full[0] if name == "wte" else full[-2])

    i = 0
    for name, shape in c.shapes("model").items():
        out[name] = tensor(i, name, shape, name == "lnf")
        i += 1
    for stack, n in c.stack_sizes().items():
        for name, shape in c.shapes(stack).items():
            out[f"{stack}_{name}"] = tensor(i, name, (n,) + shape,
                                            name in VECTORS[stack])
            i += 1
    return out


class SsmMoeLMHead(model.Model):
    """The causal LM as a ``Model``: stacked weights, an inference
    forward, and ``serve()``."""

    def __init__(self, cfg=None):
        super().__init__()
        self.cfg = cfg or SsmMoeConfig()

    def initialize(self, ids):
        """Creates the parameters in ``cfg.dtype`` (vectors, taps and
        the router float32), drawn in that dtype by one program:
        matrices with the variance of N(0, 1 / fan-in), norms and ``D``
        at 1, Mamba-2's usual ``A_log`` and ``dt_bias``, the router's
        bias at 0."""
        dev = ids.device
        for name, a in _init_params(dev.rng_key(), self.cfg).items():
            setattr(self, name, Tensor(
                data=jax.device_put(a, dev.jax_device), device=dev,
                requires_grad=True, stores_grad=True))

    def served_family(self):
        return SsmMoeFamily(self.cfg)

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
              for a in leaves], _name="SsmMoeForward")

    def serve(self, **kw):
        """The continuous-batching engine over this model
        (:class:`singa_tpu.serve.InferenceEngine`): pass
        ``paged=PagedConfig(..., prefill_token_budget=)``, ``dtype=``,
        ``max_slots=``.  What this family does not implement the engine
        refuses by name (docs/SERVING.md "The served-model contract")."""
        from ..serve import InferenceEngine

        return InferenceEngine(self, **kw)
