"""Trinity-Mini as published, in plain float32 ``jax.numpy`` -- the
yardstick.

Source: huggingface.co/arcee-ai/Trinity-Mini, ``config.json``
(``model_type`` ``afmoe``, 26B-A3B).  Key by key, with ``h`` the residual
stream (``hidden_size``) and RMSNorm at ``rms_norm_eps``; items marked +
are not fixed by the config's keys: they are the family's public
implementation (``modeling_afmoe.py`` in ``transformers``) as recalled,
and are repeated in the configuration file's ``assumed``:

    h = E[ids] * sqrt(hidden_size)                      (+ mup_enabled)
    per layer, its kind from layer_types (sliding_attention x
    (global_attn_every_n_layers - 1), then full_attention, repeated):
      a  = RMSNorm_in(h)
      q  = a W_q -> num_attention_heads heads of head_dim
      k  = a W_k, v = a W_v -> num_key_value_heads heads of head_dim
      g  = a W_g -> num_attention_heads * head_dim      (+ the gate)
      q  = RMSNorm_q(q), k = RMSNorm_k(k), per head over head_dim    (+)
      sliding_attention: rotary at rope_theta over the whole head (the
        half-split turn) on q and k; full_attention: no positions    (+)
      s_ij = q_i . k_j / sqrt(head_dim), causal; sliding_attention: j
        visible iff i - sliding_window < j <= i
      o  = (softmax(s) v * sigmoid(g)) W_o                           (+)
      h  = h + RMSNorm_post_attn(o)                       (+ sandwich)
      m  = RMSNorm_pre_mlp(h)
      f  = W_down(silu(W_gate m) * W_up m), intermediate_size wide, in
           the num_dense_layers leading layers; in the others
           E_shared(m) + sum_{e in top} w_e E_e(m), every E a SwiGLU of
           moe_intermediate_size, with
             sc  = sigmoid(W_router m) in float32       num_experts wide
             the num_experts_per_tok largest sc + bias chosen (+ a bias
             that takes part in the choice only; n_group = topk_group =
             1: no groups)
             w_e = sc_e / (sum of the chosen sc + 1e-20)  (route_norm)
                   * route_scale
      h  = h + RMSNorm_post_mlp(f)                        (+ sandwich)
    logits = RMSNorm_final(h) W_head        (tie_word_embeddings false)

DEPARTURES from the published model, each the configuration's to state:

* **the chip's share**: ``held = (first, end)`` is the range of the
  router's outputs whose experts exist here.  The router keeps all its
  outputs and all its choices, and ``w_e`` is normalised over ALL the
  chosen experts; the terms of chosen experts outside ``held`` are left
  out of the sum ``f``, and that partial result goes through the
  post-norm and on to the next layer.  With ``held = (0, num_experts)``
  this is the published layer.  The vocabulary may be a slice: ids are
  drawn from it, and the embedding and head have its rows only.
* the served context is the engine's ``max_len``, not
  ``max_position_embeddings``.

There is no cache and no batching; experts are applied to every token
and weighted (zero where not chosen); no kernels.  Nothing here imports
the program and nothing here takes an array the program made.  Every
entry point runs under matmul precision ``highest``.

**Computed in blocks**, so that a 12,000-token sequence fits beside the
program's weights: a sequence is ``ROWS`` rows at a time through every
matmul, attention is a block of queries against a block of keys with a
running softmax (blocks wholly outside the band or above the diagonal
are skipped: they add nothing), the head a slice of the vocabulary at a
time.  Weights are a *function* of (seed, tensor, layer, block):
``init_weights`` returns a handle (:class:`Weights`) and one layer's
pieces exist at a time.  The adapter lays the same pieces into the
program.

ASSUMED (no key of the config pins them; repeated in the configuration
file's ``assumed``): the + items above; weights N(0, 0.02) as the
family initialises them (the sandwich norms keep every half's result at
unit scale whatever the matrices' scale, so the stream grows like the
square root of the depth and the logits land near 1); norm weights 1 +
0.1 N(0, 1), so that a norm left out shows; the router's bias 0.01 N(0,
1): small against the spacing of the chosen scores and not zero (PR 34:
a large random bias makes experts that nobody chooses, and which ones
is the seed's).

``precision`` selects the arithmetic of the matmul operands: ``"f32"``
the reference; ``"fp8"`` the control, a step below bf16 (operands rounded
to float8 e4m3, weights per output channel, activations per row, float32
accumulation), which the cell's limits must catch.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

VB = 6256             # ids a vocabulary block (25,024 = 4 blocks)
IB = 2048             # columns a block of the dense feed-forward (6,144 =
                      # 3 blocks)
ROWS = 1024           # rows of a sequence that exist at once in a matmul,
                      # and the queries and the keys of one attention block
SERVED = 512          # served positions are projected this many at once
                      # (the cell's longest reply: one shape)

_ATTN = ("ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp", "q_norm",
         "k_norm", "wq", "wk", "wv", "wg", "wo")
_DENSE = ("w_gate", "w_up", "w_down")
_MOE = ("router", "bias", "e_gate", "e_up", "e_down", "s_gate", "s_up",
        "s_down")
_TENSORS = ("embed", "head", "lnf") + _ATTN + _DENSE + _MOE
STD = 0.02


def sizes_of(config):
    """The sizes this file needs, from a configuration file's keys (the
    names of the published ``config.json``; ``P``, the served context,
    from the engine settings; ``R``, the router's outputs, and ``held``
    from the ``share`` the file states).  Every value hashes."""
    c, sh = config, config.get("share", {})
    r = int(sh.get("num_experts_published", c["num_experts"]))
    held = tuple(int(v) for v in sh.get("experts_held", (0, r)))
    if held[1] - held[0] != int(c["num_experts"]):
        raise ValueError("num_experts counts the experts held here: "
                         f"{c['num_experts']} against the range {held}")
    per = int(c["global_attn_every_n_layers"])
    kinds = tuple(c["layer_types"])
    if kinds != tuple("full_attention" if (i + 1) % per == 0
                      else "sliding_attention"
                      for i in range(int(c["num_hidden_layers"]))):
        raise ValueError("layer_types is not global_attn_every_n_layers' "
                         "pattern")
    return dict(
        V=int(c["vocab_size"]), P=int(c["engine"]["max_len"]),
        E=int(c["hidden_size"]), L=int(c["num_hidden_layers"]),
        KD=int(c["num_dense_layers"]), H=int(c["num_attention_heads"]),
        KV=int(c["num_key_value_heads"]), D=int(c["head_dim"]),
        I=int(c["intermediate_size"]), IM=int(c["moe_intermediate_size"]),
        R=r, held=held, NS=int(c["num_shared_experts"]),
        K=int(c["num_experts_per_tok"]), scale=float(c["route_scale"]),
        eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
        W=int(c["sliding_window"]), PER=per,
        mup=bool(c["mup_enabled"]))


def vocab_blocks(s):
    """[(first id, ids)] of the embedding's and the head's blocks."""
    return [(a, min(VB, s["V"] - a)) for a in range(0, s["V"], VB)]


def dense_blocks(s):
    """[(first column, columns)] of the dense feed-forward's blocks."""
    return [(a, min(IB, s["I"] - a)) for a in range(0, s["I"], IB)]


def layer_keys(s, layer):
    return _ATTN + (_DENSE if layer < s["KD"] else _MOE)


def is_window(s, layer):
    return (layer + 1) % s["PER"] != 0


# ------------------------------------------------------------------ weights


def _spec(s, name, rows):
    """(shape, kind, standard deviation) of one piece."""
    e, qd, kd = s["E"], s["H"] * s["D"], s["KV"] * s["D"]
    sh = s["IM"] * s["NS"]
    norm = lambda n: ((n,), "norm", 0.1)
    mat = lambda *shape: (shape, "normal", STD)
    table = {
        "embed": mat(rows, e), "head": mat(e, rows), "lnf": norm(e),
        "ln_in": norm(e), "ln_post_attn": norm(e), "ln_pre_mlp": norm(e),
        "ln_post_mlp": norm(e), "q_norm": norm(s["D"]),
        "k_norm": norm(s["D"]),
        "wq": mat(e, qd), "wk": mat(e, kd), "wv": mat(e, kd),
        "wg": mat(e, qd), "wo": mat(qd, e),
        "w_gate": mat(e, rows), "w_up": mat(e, rows),
        "w_down": mat(rows, e),
        "router": mat(e, s["R"]), "bias": ((s["R"],), "normal", 0.01),
        "e_gate": mat(e, s["IM"]), "e_up": mat(e, s["IM"]),
        "e_down": mat(s["IM"], e),
        "s_gate": mat(e, sh), "s_up": mat(e, sh), "s_down": mat(sh, e),
    }
    return table[name]


def _draw(seed, index, layer, block, shape, kind, std):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), index), layer), block)
    z = jax.random.normal(key, shape, jnp.float32)
    return 1.0 + std * z if kind == "norm" else std * z


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std"))
def _tensor(seed, index, layer, block, *, shape, kind, std):
    """One piece; a program a shape, not a tensor's name (two dozen names
    share a dozen shapes, and every program is compiled on the clock of
    a run's check)."""
    return _draw(seed, index, layer, block, shape, kind, std)


class Weights:
    """The seed's weights as a function of (tensor, layer, block): every
    call makes the float32 piece anew, on the default device.  ``block``
    is a block of the vocabulary (embed, head), of the dense
    feed-forward's columns (w_gate, w_up; rows of w_down), or the
    expert's number among the router's outputs (e_gate, e_up, e_down)."""

    def __init__(self, sizes, seed):
        self.sizes = dict(sizes)
        # any whole number up to a little over 2**31 is a valid --seed
        self.seed = np.uint32(int(seed) % (2 ** 32))
        self._sz = tuple(sorted(self.sizes.items()))

    def _spec(self, name, block):
        rows = None
        if name in ("embed", "head"):
            rows = vocab_blocks(self.sizes)[block][1]
        elif name in _DENSE:
            rows = dense_blocks(self.sizes)[block][1]
        shape, kind, std = _spec(self.sizes, name, rows)
        return dict(shape=shape, kind=kind, std=std)

    def tensor(self, name, layer=0, block=0):
        return _tensor(self.seed, _TENSORS.index(name), layer, block,
                       **self._spec(name, block))


def init_weights(sizes, seed):
    """A handle, not arrays (see the module docstring)."""
    return Weights(sizes, seed)


# --------------------------------------------------------------------- math


def _round(x, axis):
    """Round to float8 e4m3 with the row's or column's largest value
    scaled to the format's largest."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


def _mm(x, w, precision):
    if precision == "fp8":
        x, w = _round(x, -1), _round(w, 0)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return x @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rot(x, first, theta):
    """x (n, heads, D) at positions ``first + [0, n)``: ``x cos +
    rotate_half(x) sin`` with the D / 2 frequencies ``theta^(-2i/D)``
    repeated over both halves."""
    n, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (first + jnp.arange(n)).astype(jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("precision",))
def _matmul(x, w, *, precision):
    """One matmul, a program a shape: the layers' projections share a
    handful of shapes, and a float32 matmul at precision ``highest`` takes
    seconds to compile for the chip -- on the clock of a run's check."""
    with jax.default_matmul_precision("highest"):
        return _mm(x, w, precision)


@functools.partial(jax.jit, static_argnames=("sz", "window"))
def _heads(q, k, v, q_norm, k_norm, first, *, sz, window):
    """The projections' results as heads: q (n, H, D), k and v (n, KV, D);
    q and k normalised per head and, in a window layer, turned."""
    s = dict(sz)
    n = q.shape[0]
    q = _rms(q.reshape(n, s["H"], s["D"]), q_norm, s["eps"])
    k = _rms(k.reshape(n, s["KV"], s["D"]), k_norm, s["eps"])
    if window:
        q, k = _rot(q, first, s["theta"]), _rot(k, first, s["theta"])
    return q, k, v.reshape(n, s["KV"], s["D"])


def _project(x, p, first, *, sz, window, precision):
    """A block of rows at positions ``first + [0, n)`` -> q (n, H, D), k
    and v (n, KV, D), the gate (n, H D)."""
    a = _norm(x, p["ln_in"], dict(sz)["eps"])
    q, k, v, gate = (_matmul(a, p[w], precision=precision)
                     for w in ("wq", "wk", "wv", "wg"))
    return _heads(q, k, v, p["q_norm"], p["k_norm"], first, sz=sz,
                  window=window) + (gate,)


@functools.partial(jax.jit, static_argnames=("sz",))
def _attend(acc, q, k, v, q_first, k_first, band, *, sz):
    """A block of queries against a block of keys: the running softmax
    ``acc = (largest score, sum, weighted values)`` a query a head,
    carried on.  ``band``: a key is visible to the queries less than
    ``band`` positions after it (the window, or more than any length)."""
    s = dict(sz)
    m, l, o = acc
    g = s["H"] // s["KV"]
    qi = q_first + jnp.arange(q.shape[0])
    kj = k_first + jnp.arange(k.shape[0])
    see = (kj[None, :] <= qi[:, None]) & (kj[None, :] > qi[:, None] - band)
    with jax.default_matmul_precision("highest"):
        qg = q.reshape(q.shape[0], s["KV"], g, s["D"])
        sc = jnp.einsum("skgd,tkd->kgst", qg, k) / math.sqrt(s["D"])
        sc = jnp.where(see, sc, -jnp.inf)
        m2 = jnp.maximum(m, jnp.max(sc, axis=-1))
        # a query that has seen no key yet keeps a finite maximum
        safe = jnp.where(jnp.isfinite(m2), m2, 0.0)
        pr = jnp.exp(sc - safe[..., None])
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
        return (m2, l * alpha + jnp.sum(pr, axis=-1),
                o * alpha[..., None] + jnp.einsum("kgst,tkd->kgsd", pr, v))


@jax.jit
def _gated(acc, gate):
    """softmax(s) v of a block of rows, heads side by side, times
    sigmoid(g)."""
    _, l, o = acc
    o = (o / l[..., None]).transpose(2, 0, 1, 3).reshape(gate.shape[0], -1)
    return o * jax.nn.sigmoid(gate)


@functools.partial(jax.jit, static_argnames=("eps",))
def _join(x, y, ln, eps):
    """h + RMSNorm(y): how either half of a layer joins the stream."""
    return x + _rms(y, ln, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return _rms(x, w, eps)


def _swiglu_(m, w_gate, w_up, w_down, precision):
    return _mm(jax.nn.silu(_mm(m, w_gate, precision))
               * _mm(m, w_up, precision), w_down, precision)


@jax.jit
def _silu_mul(a, b):
    return jax.nn.silu(a) * b


def _swiglu(m, w_gate, w_up, w_down, *, precision):
    mm = functools.partial(_matmul, precision=precision)
    return mm(_silu_mul(mm(m, w_gate), mm(m, w_up)), w_down)


def _route(m, router, bias, s):
    n = m.shape[0]
    sc = jax.nn.sigmoid(m.astype(jnp.float32)
                        @ router.astype(jnp.float32))
    idx = jax.lax.top_k(sc + bias, s["K"])[1]
    w = jnp.take_along_axis(sc, idx, axis=1)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) * s["scale"]
    return jnp.zeros((n, s["R"])).at[jnp.arange(n)[:, None], idx].set(w)


@functools.partial(jax.jit, static_argnames=("sz",))
def route(m, router, bias, *, sz):
    """m (n, E) -> the layer's weights as a dense (n, R) float32 matrix:
    ``w_e`` at each chosen expert's column, zero elsewhere."""
    with jax.default_matmul_precision("highest"):
        return _route(m, router, bias, dict(sz))


@functools.partial(jax.jit, static_argnames=("sz", "precision"))
def _moe_terms(m, first, router, bias, experts, shared, *, sz, precision):
    """The shared expert and the terms of the experts ``first + [0, n)``
    (``experts``: their gate, up and down matrices stacked), every one
    applied to every row and weighted, an expert at a time."""
    with jax.default_matmul_precision("highest"):
        wts = _route(m, router, bias, dict(sz))

        def one(f, e_w):
            e, w_e = e_w
            wt = jax.lax.dynamic_slice_in_dim(wts, first + e, 1, axis=1)
            return f + wt * _swiglu_(m, *w_e, precision), None

        f, _ = jax.lax.scan(one, _swiglu_(m, *shared, precision),
                            (jnp.arange(experts[0].shape[0]), experts))
        return f


def ffn_terms(w, ms, layer, precision, held=None):
    """``f`` of one layer for the blocks ``ms`` of pre-normed rows, before
    the post-norm: the dense SwiGLU, or the shared expert and the terms
    of the experts in ``held`` (default: the sizes' ownership range).
    Each piece of the weights is made once and meets every block."""
    s = w.sizes
    if layer < s["KD"]:
        fs = [0.0] * len(ms)
        for b in range(len(dense_blocks(s))):
            piece = [w.tensor(k, layer, b) for k in _DENSE]
            fs = [f + _swiglu(m, *piece, precision=precision)
                  for f, m in zip(fs, ms)]
        return fs
    lo, hi = s["held"] if held is None else held
    pieces = dict(
        router=w.tensor("router", layer), bias=w.tensor("bias", layer),
        experts=tuple(jnp.stack([w.tensor(k, layer, e)
                                 for e in range(lo, hi)])
                      for k in ("e_gate", "e_up", "e_down")),
        shared=tuple(w.tensor(k, layer)
                     for k in ("s_gate", "s_up", "s_down")))
    return [_moe_terms(m, lo, sz=w._sz, precision=precision, **pieces)
            for m in ms]


def _layer(w, xs, layer, precision):
    """One layer over a sequence held as blocks of ``rows`` rows."""
    s = w.sizes
    window = is_window(s, layer)
    rows = xs[0].shape[0]
    p = {k: w.tensor(k, layer) for k in _ATTN}
    band = s["W"] if window else 2 ** 30
    proj = [_project(x, p, b * rows, sz=w._sz, window=window,
                     precision=precision) for b, x in enumerate(xs)]
    shape = (s["KV"], s["H"] // s["KV"], rows)
    for i, (q, _, _, gate) in enumerate(proj):
        acc = (jnp.full(shape, -jnp.inf), jnp.zeros(shape),
               jnp.zeros(shape + (s["D"],)))
        for j in range(i + 1):
            # the block's newest key lies outside the oldest query's band
            if (j + 1) * rows - 1 <= i * rows - band:
                continue
            acc = _attend(acc, q, proj[j][1], proj[j][2], i * rows,
                          j * rows, band, sz=w._sz)
        y = _matmul(_gated(acc, gate), p["wo"], precision=precision)
        xs[i] = _join(xs[i], y, p["ln_post_attn"], s["eps"])
    ms = [_norm(x, p["ln_pre_mlp"], s["eps"]) for x in xs]
    fs = ffn_terms(w, ms, layer, precision)
    return [_join(x, f, p["ln_post_mlp"], s["eps"])
            for x, f in zip(xs, fs)]


@jax.jit
def _embed_rows(table, ids, first):
    """Rows of one block of the table for the ids that lie in it."""
    local = ids - first
    hit = (local >= 0) & (local < table.shape[0])
    rows = table[jnp.clip(local, 0, table.shape[0] - 1)]
    return jnp.where(hit[:, None], rows, 0.0)


def embed(w, ids):
    s = w.sizes
    x = 0.0
    for b, (first, _) in enumerate(vocab_blocks(s)):
        x = x + _embed_rows(w.tensor("embed", block=b), ids, first)
    return x * (math.sqrt(s["E"]) if s["mup"] else 1.0)


def hidden_states(w, ids, precision="f32"):
    """ids (S,) int32 -> final-RMSNorm hidden states (S', E), S' = S
    rounded up to whole blocks of rows (right padding is invisible: the
    attention is causal); one layer's pieces of the weights alive at a
    time."""
    s = w.sizes
    ids = np.asarray(ids, np.int32)
    rows = min(ROWS, s["P"])
    pad = np.zeros(-(-len(ids) // rows) * rows, np.int32)
    pad[:len(ids)] = ids
    xs = [embed(w, jnp.asarray(pad[a:a + rows]))
          for a in range(0, len(pad), rows)]
    for layer in range(s["L"]):
        xs = _layer(w, xs, layer, precision)
    lnf = w.tensor("lnf")
    return jnp.concatenate([_norm(x, lnf, s["eps"]) for x in xs])


@functools.partial(jax.jit, static_argnames=("precision",))
def _head_block(h, wb, *, precision):
    with jax.default_matmul_precision("highest"):
        return _mm(h, wb, precision)


def logits(w, h, precision="f32"):
    """Final hidden states (R, E) -> logits (R, V), a vocabulary block at
    a time (small R only: the tests, and the served rows below)."""
    return jnp.concatenate([
        _head_block(h, w.tensor("head", block=b), precision=precision)
        for b in range(len(vocab_blocks(w.sizes)))], axis=-1)


# ------------------------------------------------------------------ serving


def _served_rows(h, first, n_served):
    """The hidden rows of the served positions, padded to ``SERVED``."""
    r = -(-n_served // SERVED) * SERVED
    idx = np.clip(first + np.arange(r), 0, h.shape[0] - 1)
    return h[jnp.asarray(idx)]


def served_token_gap(w, sizes, tokens, prompt_len, precision="f32"):
    """The gap by which a served token's reference logit lies below the
    reference's best, over the served positions of one finished request
    (``tokens`` = prompt + served tokens; greedy traffic only): the
    widest, and the sum (for a mean over many requests).

    With ``precision="f32"`` the served tokens are the ones in
    ``tokens``.  With a lower precision this is the control: at each
    served position the token that the lower precision puts first takes
    the served token's place.  Returns (widest gap, sum of gaps, scale
    of the logits)."""
    s = w.sizes
    tokens = np.asarray(tokens)
    first, n_served = prompt_len - 1, len(tokens) - prompt_len
    rows = _served_rows(hidden_states(w, tokens, "f32"), first, n_served)
    if precision == "f32":
        chosen = np.zeros(rows.shape[0], np.int64)
        chosen[:n_served] = tokens[prompt_len:]
    else:
        low = _served_rows(hidden_states(w, tokens, precision), first,
                           n_served)
        chosen = np.asarray(jnp.argmax(logits(w, low, precision), -1))
    best = np.full(rows.shape[0], -np.inf)
    got = np.zeros(rows.shape[0])
    scale = 0.0
    for b, (a, n) in enumerate(vocab_blocks(s)):
        lg = np.asarray(_head_block(rows, w.tensor("head", block=b),
                                    precision="f32"))
        best = np.maximum(best, lg.max(-1))
        scale = max(scale, float(np.abs(lg[:n_served]).max()))
        hit = (chosen >= a) & (chosen < a + n)
        got = np.where(hit, lg[np.arange(len(chosen)),
                               np.clip(chosen - a, 0, n - 1)], got)
    gap = (best - got)[:n_served]
    return float(gap.max()), float(gap.sum()), scale
