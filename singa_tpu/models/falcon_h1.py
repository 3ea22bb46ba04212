"""Falcon-H1 — a hybrid decoder: in every layer a Mamba-2 mixer and rotary
grouped-query attention run side by side on one RMS-normalised input,
then a SwiGLU feed-forward; untied head; µP multipliers throughout
(huggingface.co/tiiuae/Falcon-H1-34B-Instruct, ``model_type``
``falcon_h1``; the equations are written out key by key in
``benchmark/references/falcon_h1.py``, the plain reference the tests
hold this file to).

What is here is the SERVING side: ``FalconH1LMHead(cfg).serve(paged=...)``
returns the one :class:`~singa_tpu.serve.InferenceEngine`, which calls
the math below through :class:`FalconH1Family` (models/served.py).  Each
sequence holds two kinds of state: K/V in the engine's block pool, and
per layer a recurrent state with no position axis — the SSM state
``(heads, head size, d_state)`` in float32 and the conv's tail of
``d_conv - 1`` inputs — in the engine's per-slot state arenas.  The
Mamba-2 mixer comes in both forms: a chunked scan (one prefill chunk row
= one scan chunk) and a one-step recurrence (decode).  The attention
half is the shared code of ``ops/paged_attention.py``.

Identical layers are kept as STACKED weights ``(L, ...)`` and every
program scans them (``lax.scan``), so a program compiles one layer body
whatever the depth.  The model is built in ``cfg.dtype``: at bfloat16
no parameter ever exists in float32 (only the few per-channel vectors —
norm weights, conv taps, ``A_log``, ``D``, ``dt_bias`` — are float32
always).  Training is not here yet (ROADMAP Reach A2).
"""

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd, model
from ..ops import mamba2
from ..ops.mamba2 import ssd_chunk  # noqa: F401  (the tests import it here)
from ..ops.paged_attention import (paged_attn, rotary, row_to_blocks,
                                   write_rows)
from ..tensor import Tensor
from .served import ServedFamily, seg_cat, seg_split, seg_tokens

#: a layer's per-channel vectors (float32 whatever ``cfg.dtype``) and
#: its matrices (``cfg.dtype``)
_VECTORS = ("ln1", "ln2", "conv_w", "conv_b", "dt_bias", "a_log", "d",
            "norm")
_MATRICES = ("wq", "wk", "wv", "wo", "w_in", "w_out", "w_gate", "w_up",
             "w_down")


@dataclass(frozen=True)
class FalconH1Config:
    """The published ``config.json`` keys (those that shape the model),
    plus ``max_len`` — the served context, a deployment's setting — and
    ``dtype``, what the weights are built in."""

    vocab_size: int = 261120
    hidden_size: int = 5120
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    mamba_d_ssm: int = 4096
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: tuple = (0.3535533905932738, 0.25,
                              0.1767766952966369, 0.5,
                              0.3535533905932738)
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    max_position_embeddings: int = 262144
    max_len: int = 8192
    dtype: str = "float32"

    def __post_init__(self):
        for k in ("ssm_multipliers", "mlp_multipliers"):
            object.__setattr__(self, k, tuple(float(v)
                                              for v in getattr(self, k)))
        # the published file writes whole numbers (rope_theta 1e11 as
        # an integer too wide for an int32)
        for k in ("rope_theta", "rms_norm_eps", "embedding_multiplier",
                  "lm_head_multiplier", "attention_in_multiplier",
                  "attention_out_multiplier", "key_multiplier",
                  "ssm_in_multiplier", "ssm_out_multiplier"):
            object.__setattr__(self, k, float(getattr(self, k)))
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba_n_heads * mamba_d_head must equal "
                             "mamba_d_ssm")
        if self.max_len > self.max_position_embeddings:
            raise ValueError("max_len exceeds max_position_embeddings")

    # the engine's names for what it reads off any model's cfg
    n_layer = property(lambda s: s.num_hidden_layers)
    n_head = property(lambda s: s.num_attention_heads)
    n_kv_head = property(lambda s: s.num_key_value_heads)
    n_embd = property(lambda s: s.hidden_size)
    n_positions = property(lambda s: s.max_len)
    layer_norm_eps = property(lambda s: s.rms_norm_eps)

    @property
    def segments(self):
        """Widths of the in-projection's segments: z, x, B, C, dt."""
        gn = self.mamba_n_groups * self.mamba_d_state
        return (self.mamba_d_ssm, self.mamba_d_ssm, gn, gn,
                self.mamba_n_heads)

    @property
    def conv_dim(self):
        return self.mamba_d_ssm + 2 * self.mamba_n_groups \
            * self.mamba_d_state

    def shapes(self):
        """{tensor: shape}: the layers' tensors without their leading
        ``L``, and the three outside the layers."""
        c, E, I = self, self.hidden_size, self.intermediate_size
        qd, kd = c.n_head * c.head_dim, c.n_kv_head * c.head_dim
        return dict(
            wte=(c.vocab_size, E), head=(E, c.vocab_size), lnf=(E,),
            ln1=(E,), ln2=(E,), wq=(E, qd), wk=(E, kd), wv=(E, kd),
            wo=(qd, E), w_in=(E, sum(c.segments)),
            conv_w=(c.mamba_d_conv, c.conv_dim), conv_b=(c.conv_dim,),
            dt_bias=(c.mamba_n_heads,), a_log=(c.mamba_n_heads,),
            d=(c.mamba_n_heads,), norm=(c.mamba_d_ssm,),
            w_out=(c.mamba_d_ssm, E), w_gate=(E, I), w_up=(E, I),
            w_down=(I, E))


# --------------------------------------------------------------------- math


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _mup(c):
    return np.concatenate([np.full(w, m, np.float32)
                           for w, m in zip(c.segments, c.ssm_multipliers)])


def _mlp(x, p, c):
    g = _rms(x, p["ln2"], c.rms_norm_eps)
    gate = jax.nn.silu(c.mlp_multipliers[0] * (g @ p["w_gate"]))
    return c.mlp_multipliers[1] * (((g @ p["w_up"]) * gate) @ p["w_down"])


def _qkv(h, p, c):
    """h (T, E) -> q (T, H, D), k and v (T, KV, D), before rotation."""
    t = h.shape[0]
    u = c.attention_in_multiplier * h
    q = (u @ p["wq"]).reshape(t, c.n_head, c.head_dim)
    k = (c.key_multiplier * (u @ p["wk"])).reshape(t, c.n_kv_head,
                                                   c.head_dim)
    v = (u @ p["wv"]).reshape(t, c.n_kv_head, c.head_dim)
    return q, k, v


def _rows(x):
    """Keys or values by head (KV, T, D) as the pool stores them: a
    token a row, (T, KV·D)."""
    return x.transpose(1, 0, 2).reshape(x.shape[1], -1)


def _mixer_inputs(h, p, c):
    """h (T, E) -> z (T, d_ssm), xBC (T, conv_dim) before the conv,
    dt (T, heads) before its bias; float32."""
    u = c.ssm_in_multiplier * h
    zxbcdt = (u @ p["w_in"]).astype(jnp.float32) * _mup(c)
    ds, cd = c.mamba_d_ssm, c.conv_dim
    return zxbcdt[:, :ds], zxbcdt[:, ds:ds + cd], zxbcdt[:, ds + cd:]


def _mixer_out(y, z, p, c):
    """Gate, grouped RMSNorm and the out-projection: y, z (T, d_ssm)."""
    t, g = y.shape[0], c.mamba_n_groups
    y = (y * jax.nn.silu(z)).reshape(t, g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + c.rms_norm_eps)
    y = y.reshape(t, -1) * p["norm"]
    return c.ssm_out_multiplier * (y.astype(p["w_out"].dtype)
                                   @ p["w_out"])


def _mamba_chunk(h, p, c, ssm, conv, n_valid, sub=None):
    """The mixer over a chunk row of ONE sequence: h (T, E) normalised
    input; the projections and the norm either side of
    :func:`_mamba_mix`.  Returns (m (T, E), ssm, conv)."""
    z, xbc, dt = _mixer_inputs(h, p, c)
    y, ssm, conv = mamba2.mix(xbc, dt, p, ssm, conv, n_valid, sub)
    return _mixer_out(y, z, p, c), ssm, conv


def _mamba_step(h, p, c, ssm_all, conv_all, li, slots):
    """The mixer one token a lane: h (W, E); lane w's state is row
    ``slots[w]`` of layer ``li`` of the arenas ``ssm_all`` (L, S+1, h,
    p, n) and ``conv_all`` (L, S+1, d_conv - 1, conv_dim), read,
    advanced one step and written back (``ops/mamba2.step``: the SSM
    state of all lanes in one Pallas call on a TPU, ``li`` -- the layer
    scan's counter -- among its prefetched indices; a lane at a time
    anywhere else).  Returns (m (W, E), ssm_all, conv_all)."""
    z, xbc, dt = _mixer_inputs(h, p, c)
    y, ssm_all, conv_all = mamba2.step(xbc, dt, p, ssm_all, conv_all,
                                       lambda slot: (li, slot), slots)
    return _mixer_out(y, z, p, c), ssm_all, conv_all


def forward_full(params, ids, c):
    """ids (S,) -> logits (S, V): the whole sequence at once, no cache
    (full causal attention; the scan chunk by chunk from a zero state).
    What ``Model.forward`` runs; serving goes through the family."""
    s = ids.shape[0]
    t = c.mamba_chunk_size
    n_chunks = -(-s // t)
    pos = jnp.arange(s)
    x = (c.embedding_multiplier * params["wte"][ids]).astype(
        params["wte"].dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))
    g = c.n_head // c.n_kv_head

    def layer(x, p):
        h = _rms(x, p["ln1"], c.rms_norm_eps)
        q, k, v = _qkv(h, p, c)
        q = rotary(q.transpose(1, 0, 2), pos, c.rope_theta)   # (H, S, D)
        k = rotary(k.transpose(1, 0, 2), pos, c.rope_theta)
        q = q.reshape(c.n_kv_head, g, s, c.head_dim).astype(jnp.float32)
        sc = jnp.einsum("kgsd,ktd->kgst", q, k.astype(jnp.float32)) \
            / math.sqrt(c.head_dim)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        a = jnp.einsum("kgst,ktd->skgd", pr,
                       v.transpose(1, 0, 2).astype(jnp.float32))
        a = a.reshape(s, -1).astype(x.dtype)
        att = c.attention_out_multiplier * (a @ p["wo"])
        hp = jnp.pad(h, ((0, n_chunks * t - s), (0, 0)))

        def chunk(carry, i):
            ssm, conv = carry
            m, ssm, conv = _mamba_chunk(
                jax.lax.dynamic_slice_in_dim(hp, i * t, t), p, c, ssm,
                conv, jnp.minimum(t, s - i * t))
            return (ssm, conv), m

        zero = (jnp.zeros((c.mamba_n_heads, c.mamba_d_head,
                           c.mamba_d_state), jnp.float32),
                jnp.zeros((c.mamba_d_conv - 1, c.conv_dim), jnp.float32))
        _, m = jax.lax.scan(chunk, zero, jnp.arange(n_chunks))
        x = x + att + m.reshape(n_chunks * t, -1)[:s].astype(x.dtype)
        return x + _mlp(x, p, c).astype(x.dtype), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _logits(params, _rms(x, params["lnf"], c.rms_norm_eps), c)


def _logits(params, hidden, c):
    """(..., E) -> (..., V), accumulated and returned in float32."""
    return c.lm_head_multiplier * jnp.dot(
        hidden, params["head"], preferred_element_type=jnp.float32)


# ----------------------------------------------------- the served contract


@dataclass(frozen=True)
class FalconH1Family(ServedFamily):
    """Falcon-H1 for the serve engine (models/served.py): the budgeted
    paged path, with the recurrent state in the per-slot arenas.  Hashes
    by its configuration, so equal models share compiled programs."""

    cfg: FalconH1Config

    name = "falcon_h1"
    features = frozenset()
    # the recurrence itself (``ssm_scan`` in a chunk row, ``ssm_step`` in
    # a decode step) lies inside the mixer's projections, conv and norm
    # (``ssm_proj``)
    scopes = ("attn", "ssm_proj", "ssm_scan", "ssm_step", "mlp", "head")

    def extract_params(self, m, dtype=None):
        st = {k.rsplit(".", 1)[-1]: t.data
              for k, t in m.get_states().items()}
        if not st:
            raise RuntimeError("model not initialized: call compile() "
                               "or run one forward first")
        cast = (lambda a: a) if dtype is None else \
            (lambda a: a.astype(dtype))
        lay = {k: (st[k] if k in _VECTORS else cast(st[k]))
               for k in _VECTORS + _MATRICES}
        return dict(wte=cast(st["wte"]), head=cast(st["head"]),
                    lnf=st["lnf"], layers=lay)

    def kv_geometry(self, cfg):
        return cfg.n_layer, cfg.n_kv_head, cfg.head_dim

    def state_spec(self, cfg):
        return {"ssm": ((cfg.mamba_n_heads, cfg.mamba_d_head,
                         cfg.mamba_d_state), jnp.float32),
                "conv": ((cfg.mamba_d_conv - 1, cfg.conv_dim),
                         jnp.float32)}

    def state_step_impl(self, state):
        return mamba2.step_impl(state["ssm"])

    def logits(self, params, hidden):
        with jax.named_scope("head"):
            return _logits(params, hidden, self.cfg)

    def chunk_rows(self, params, segs, *, block, **_):
        """One launch: each segment a whole number of blocks of one
        request (a block = one scan chunk): attention of a segment's
        queries over its private cache row below its ``off`` (block by
        block, the shared loop) and their own keys; the mixer's conv and
        chunked scan, a block at a time, from the state the segment's
        row before left.  Projections, norms and the feed-forward take
        the segments' tokens together."""
        c = self.cfg
        n_tok = sum(s.chunk for s in segs)
        toks, pos = seg_tokens(segs)
        x = (c.embedding_multiplier * jnp.take(params["wte"], toks, axis=0)
             ).astype(params["wte"].dtype)
        n_l, _, n_kv, width, d = segs[0].kc_row.shape
        g = c.n_head // n_kv
        # what lies below off, as the blocks of a pool
        below = [(row_to_blocks(s.kc_row, block),
                  row_to_blocks(s.vc_row, block)) for s in segs]
        tbl = jnp.arange(width // block)
        cur = [jnp.tril(jnp.ones((s.chunk, s.chunk), bool)) for s in segs]

        def layer(carry, lp):
            x, kc_rows, vc_rows = carry
            kc_rows, vc_rows = list(kc_rows), list(vc_rows)
            li, p, ssm, conv = lp
            h = _rms(x, p["ln1"], c.rms_norm_eps)
            with jax.named_scope("attn"):
                q, k, v = _qkv(h, p, c)
                q = rotary(q.transpose(1, 0, 2), pos, c.rope_theta)
                k = rotary(k.transpose(1, 0, 2), pos, c.rope_theta)
                v = v.transpose(1, 0, 2)                    # (KV, T, D)
                a = [paged_attn(
                    q_s.reshape(n_kv, g, s.chunk, d), *below[n], li, tbl,
                    s.off, s.off // block, block, -1, _rows(k_s),
                    _rows(v_s), cur[n], 1.0 / math.sqrt(d))
                    for n, (s, q_s, k_s, v_s) in enumerate(zip(
                        segs, seg_split(q, segs, 1), seg_split(k, segs, 1),
                        seg_split(v, segs, 1)))]
                a = seg_cat(a, 2).transpose(2, 0, 1, 3).reshape(n_tok, -1)
                att = c.attention_out_multiplier * (
                    a.astype(x.dtype) @ p["wo"])
                for n, (s, k_s, v_s) in enumerate(zip(
                        segs, seg_split(k, segs, 1), seg_split(v, segs, 1))):
                    kc_rows[n] = jax.lax.dynamic_update_slice(
                        kc_rows[n],
                        k_s[None, None].astype(kc_rows[n].dtype),
                        (li, 0, 0, s.off, 0))
                    vc_rows[n] = jax.lax.dynamic_update_slice(
                        vc_rows[n],
                        v_s[None, None].astype(vc_rows[n].dtype),
                        (li, 0, 0, s.off, 0))
            with jax.named_scope("ssm_proj"):
                z, xbc, dt = _mixer_inputs(h, p, c)
                y, ssm, conv = zip(*(
                    mamba2.mix(xbc_s, dt_s, p, ssm_s, conv_s, s.n_valid,
                               sub=block)
                    for s, xbc_s, dt_s, ssm_s, conv_s in zip(
                        segs, seg_split(xbc, segs), seg_split(dt, segs),
                        ssm, conv)))
                m = _mixer_out(seg_cat(y), z, p, c)
            x = x + att + m.astype(x.dtype)
            with jax.named_scope("mlp"):
                x = x + _mlp(x, p, c).astype(x.dtype)
            return (x, tuple(kc_rows), tuple(vc_rows)), (ssm, conv)

        (x, kc_rows, vc_rows), (ssm, conv) = jax.lax.scan(
            layer, (x, tuple(s.kc_row for s in segs),
                    tuple(s.vc_row for s in segs)),
            (jnp.arange(n_l), params["layers"],
             tuple(s.state["ssm"] for s in segs),
             tuple(s.state["conv"] for s in segs)))
        hidden = _rms(x, params["lnf"], c.rms_norm_eps)
        return [(h[None], kc, vc, {"ssm": sm, "conv": cv})
                for h, kc, vc, sm, cv in zip(
                    seg_split(hidden, segs), kc_rows, vc_rows, ssm, conv)]

    def decode_step(self, params, pool_k, pool_v, state, slots, tables,
                    toks, pos, live, n_blk, *, block, trash, **_):
        """Every lane one token: per layer, each lane's query over its
        live blocks of the pool plus its own new key (the shared loop),
        the new K/V row written straight into the pool; each lane's
        row of the state arenas read, advanced one step and written
        back.  Pool and arenas are carried through the layer scan and
        updated in place."""
        c = self.cfg
        p_c = jnp.where(live, pos, 0)
        t_c = jnp.where(live, toks, 0)
        x = (c.embedding_multiplier * params["wte"][t_c]).astype(
            params["wte"].dtype)                             # (W, E)
        n_kv, d = c.n_kv_head, c.head_dim
        g = c.n_head // n_kv
        one = jnp.ones((1, 1), bool)

        def layer(carry, lp):
            x, pool_k, pool_v, ssm, conv = carry
            li, p = lp
            h = _rms(x, p["ln1"], c.rms_norm_eps)
            with jax.named_scope("attn"):
                q, k, v = _qkv(h, p, c)          # (W, H, D), (W, KV, D)

                def lane(q_r, k_r, v_r, tbl, pos_r):
                    at = pos_r[None]
                    q_r = rotary(q_r[:, None], at, c.rope_theta)
                    k_r = rotary(k_r[:, None], at, c.rope_theta)
                    k_r = _rows(k_r)
                    a = paged_attn(
                        q_r.reshape(n_kv, g, 1, d), pool_k, pool_v, li,
                        tbl, pos_r, n_blk, block, trash, k_r,
                        v_r.reshape(1, -1), one, 1.0 / math.sqrt(d))
                    return a.reshape(-1), k_r

                a, k = jax.vmap(lane)(q, k, v, tables, p_c)
                att = c.attention_out_multiplier * (
                    a.astype(x.dtype) @ p["wo"])
                pool_k = write_rows(pool_k, li, k, tables, p_c, live,
                                    block, trash)
                pool_v = write_rows(pool_v, li, v.reshape(len(v), 1, -1),
                                    tables, p_c, live, block, trash)
            with jax.named_scope("ssm_proj"):
                m, ssm, conv = _mamba_step(h, p, c, ssm, conv, li, slots)
            x = x + att + m.astype(x.dtype)
            with jax.named_scope("mlp"):
                x = x + _mlp(x, p, c).astype(x.dtype)
            return (x, pool_k, pool_v, ssm, conv), None

        (x, pool_k, pool_v, ssm, conv), _ = jax.lax.scan(
            layer, (x, pool_k, pool_v, state["ssm"], state["conv"]),
            (jnp.arange(c.n_layer), params["layers"]))
        logits = self.logits(params, _rms(x, params["lnf"],
                                          c.rms_norm_eps))
        return logits, pool_k, pool_v, {"ssm": ssm, "conv": conv}


# ---------------------------------------------------------------- the model


@partial(jax.jit, static_argnames=("c",))
def _init_params(key, c):
    # the device's own bit generator: billions of draws at memory speed
    # (the counter-based default takes tens of seconds at this size)
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    big, n_l, out = jnp.dtype(c.dtype), c.n_layer, {}

    def matrix(k, full, std):
        # a slab of the leading axis at a time (a layer; a sixteenth of
        # the vocabulary), so that the draw's temporaries are a slab's
        # and the peak of a build stays the weights themselves
        n = max(d for d in range(1, 17) if full[0] % d == 0)
        slab = (full[0] // n,) + full[1:]
        return jax.lax.map(
            lambda kk: jax.random.normal(kk, slab, big)
            * jnp.asarray(std, big), jax.random.split(k, n)).reshape(full)

    for i, (name, shape) in enumerate(c.shapes().items()):
        k = jax.random.fold_in(key, i)
        full = shape if name in ("wte", "head", "lnf") else (n_l,) + shape
        if name in _MATRICES + ("wte", "head"):
            fan = shape[1] if name == "wte" else shape[0]
            a = matrix(k, full, 1.0 / math.sqrt(fan))
        elif name == "a_log":
            a = jnp.log(jax.random.uniform(k, full, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, full, jnp.float32, math.log(1e-3), math.log(1e-1)))
            a = dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1
        elif name == "conv_w":
            a = 0.5 * jax.random.normal(k, full, jnp.float32)
        elif name == "conv_b":
            a = jnp.zeros(full, jnp.float32)
        else:                                    # norm weights and D
            a = jnp.ones(full, jnp.float32)
        out[name] = a
    return out


class FalconH1LMHead(model.Model):
    """The causal LM as a ``Model``: stacked weights, an inference
    forward, and ``serve()``."""

    def __init__(self, cfg=None):
        super().__init__()
        self.cfg = cfg or FalconH1Config()

    def initialize(self, ids):
        """Creates the parameters in ``cfg.dtype`` (vectors float32),
        drawn in that dtype by one program: N(0, 1/sqrt(fan-in))
        matrices, norms at 1, Mamba-2's usual ``A_log``, ``D`` and
        ``dt_bias``."""
        dev = ids.device
        for name, a in _init_params(dev.rng_key(), self.cfg).items():
            setattr(self, name, Tensor(
                data=jax.device_put(a, dev.jax_device), device=dev,
                requires_grad=True, stores_grad=True))

    def served_family(self):
        return FalconH1Family(self.cfg)

    def forward(self, input_ids):
        """(B, S) ids -> (B, S, V) float32 logits; inference only."""
        fam, c = self.served_family(), self.cfg

        @jax.jit                # one program, not an op at a time
        def run(ids, *leaves):
            params = jax.tree.unflatten(tree, leaves)
            return jax.vmap(lambda r: forward_full(params, r, c))(ids)

        if not hasattr(self, "wte"):
            self.initialize(input_ids)
            self._name_params()
        leaves, tree = jax.tree.flatten(fam.extract_params(self))
        dev = input_ids.device
        return autograd._op(
            run, input_ids,
            *[Tensor(data=a, device=dev, requires_grad=False)
              for a in leaves], _name="FalconH1Forward")

    def serve(self, **kw):
        """The continuous-batching engine over this model
        (:class:`singa_tpu.serve.InferenceEngine`, the one GPT-2 is
        served by): pass ``paged=PagedConfig(..., prefill_token_budget=)``,
        ``dtype=``, ``max_slots=``.  What this family does not implement
        yet the engine refuses by name (docs/SERVING.md "The served-model
        contract")."""
        from ..serve import InferenceEngine

        return InferenceEngine(self, **kw)
