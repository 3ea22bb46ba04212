"""LFM2-24B-A2B as published, in plain float32 ``jax.numpy`` -- the
yardstick.

Source: huggingface.co/LiquidAI/LFM2-24B-A2B, ``config.json``
(``model_type`` ``lfm2_moe``, 24B-A2B).  Key by key, with ``h`` the
residual stream (``hidden_size``) and RMSNorm at ``norm_eps``; items
marked + are not fixed by the config's keys: they are the family's
public implementation (``modeling_lfm2_moe.py`` in ``transformers``) as
recalled, and are repeated in the configuration file's ``assumed``:

    h = E[ids]
    per layer, its kind from layer_types:
      a = RMSNorm_op(h)                           (+ operator_norm, pre)
      conv:
        (B, C, x) = split3(a W_in)       W_in: hidden -> 3 hidden   (+)
        u_t = B_t * x_t
        c_t = sum_{j < conv_L_cache} w_j * u_{t - (conv_L_cache-1) + j}
              depthwise, causal, u_t = 0 before the sequence; no bias
              (conv_bias false)                                     (+)
        o_t = (C_t * c_t) W_out
      full_attention:
        q = a W_q -> num_attention_heads heads of hidden / heads
        k = a W_k, v = a W_v -> num_key_value_heads heads
        q = RMSNorm_q(q), k = RMSNorm_k(k), per head               (+)
        rotary at rope_parameters.rope_theta over the whole head (the
          half-split turn) on q and k
        s_ij = q_i . k_j / sqrt(head size), causal
        o = softmax(s) v W_o
      h = h + o
      m = RMSNorm_ffn(h)                                  (+ ffn_norm)
      f = W_2(silu(W_1 m) * W_3 m), intermediate_size wide, in the
          num_dense_layers leading layers; in the others
          sum_{e in top} w_e E_e(m), every E a SwiGLU of
          moe_intermediate_size, no shared expert, with
            sc  = sigmoid(W_router m) in float32     num_experts wide
            the num_experts_per_tok largest sc + bias chosen
              (use_expert_bias: a bias that takes part in the choice
              only)                                                (+)
            w_e = sc_e / (sum of the chosen sc + 1e-6)
                  (norm_topk_prob) * routed_scaling_factor         (+)
      h = h + f
    logits = RMSNorm_final(h) E^T     (+ embedding_norm after the last
                                       layer; + the head tied to E)

DEPARTURES from the published model, each the configuration's to state:

* depth: the configuration's ``layer_types`` are the layers kept (one
  pipeline stage's, with the embedding and the head); ``held = (first,
  end)`` is the range of the router's outputs whose experts exist here
  -- all of them in the benchmark's configuration, so the layer is the
  published layer; with a narrower range the terms of chosen experts
  outside it are left out of ``f`` (the tests' share of eight).
* the served context is the engine's ``max_len``, not
  ``max_position_embeddings``.
* the program's router divides by the chosen scores' sum + 1e-20
  (``ops/expert_layer.route``, shared with two other families); this
  file follows the family's 1e-6: a relative 5e-7 at a sum near 2,
  below float32's rounding.

There is no cache and no batching; experts are applied to every token
and weighted (zero where not chosen); no kernels.  Nothing here imports
the program and nothing here takes an array the program made.  Every
entry point runs under matmul precision ``highest``.

**Computed in blocks**, so that a 4,600-token sequence fits beside the
program's 10.4 GB of weights: a sequence is ``ROWS`` rows at a time
through every matmul, attention is a block of queries against a block
of keys with a running softmax, the experts ``EG`` at a time, the head
a slice of the vocabulary at a time.  Weights are a *function* of (seed,
tensor, layer, block): ``init_weights`` returns a handle
(:class:`Weights`) and one layer's pieces exist at a time.  The adapter
lays the same pieces into the program.

ASSUMED (no key of the config pins them; repeated in the configuration
file's ``assumed``): the + items above; matrices N(0, 0.02); norm
weights 1 + 0.1 N(0, 1), so that a norm left out shows; the router's
bias 0.01 N(0, 1): small against the spacing of the chosen scores and
not zero (PR 34: a seed must not choose the work); the convolution's
taps N(0, 1/3), of order 1 / sqrt(3): the operator's result then has the
scale of the attention's and of the feed-forward's, and a tail that is
never carried, or a gate left out, moves the logits (at 0.02 a conv
layer would add a thousandth of the stream and either would pass).

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

VB = 8192             # ids a vocabulary block (65,536 = 8 blocks)
IB = 2944             # columns a block of the dense feed-forward (11,776 =
                      # 4 blocks)
EG = 16               # experts whose matrices exist at once (64 = 4 groups
                      # of 0.6 GB)
ROWS = 512            # rows of a sequence that exist at once in a matmul,
                      # and the queries and the keys of one attention block
SERVED = 512          # served positions are projected this many at once
                      # (the cell's longest reply: one shape)

_NORMS = ("ln_op", "ln_ffn")
_CONV = ("conv_w", "w_in", "w_out")
_FULL = ("q_norm", "k_norm", "wq", "wk", "wv", "wo")
_DENSE = ("w_gate", "w_up", "w_down")
_MOE = ("router", "bias", "e_gate", "e_up", "e_down")
_TENSORS = ("embed", "lnf") + _NORMS + _CONV + _FULL + _DENSE + _MOE
STD = 0.02            # of every matrix, unless the configuration's
                      # ``initializer_range`` says otherwise (the tests'
                      # narrow presets: std x sqrt(hidden) stays near 0.9,
                      # so that a layer adds to the stream what it adds
                      # at the published width, and the embedding -- which
                      # is the head -- does not outweigh the layers)


def sizes_of(config):
    """The sizes this file needs, from a configuration file's keys (the
    names of the published ``config.json``; ``P``, the served context,
    from the engine settings; ``R``, the router's outputs, and ``held``
    from the ``share`` a file may state: all of them otherwise; ``std``,
    the matrices' standard deviation, from ``initializer_range``).  Every
    value hashes."""
    c, sh = config, config.get("share", {})
    r = int(sh.get("num_experts_published", c["num_experts"]))
    held = tuple(int(v) for v in sh.get("experts_held", (0, r)))
    if held[1] - held[0] != int(c["num_experts"]):
        raise ValueError("num_experts counts the experts held here: "
                         f"{c['num_experts']} against the range {held}")
    kinds = tuple(c["layer_types"])
    if len(kinds) != int(c["num_hidden_layers"]) \
            or set(kinds) - {"conv", "full_attention"}:
        raise ValueError("layer_types must name num_hidden_layers layers, "
                         "each conv or full_attention")
    if c["conv_bias"] or not c["norm_topk_prob"] \
            or not c["use_expert_bias"]:
        raise ValueError("conv_bias true, norm_topk_prob false and "
                         "use_expert_bias false are not written down here")
    h = int(c["num_attention_heads"])
    return dict(
        V=int(c["vocab_size"]), P=int(c["engine"]["max_len"]),
        E=int(c["hidden_size"]), L=int(c["num_hidden_layers"]),
        KD=int(c["num_dense_layers"]), H=h,
        KV=int(c["num_key_value_heads"]), D=int(c["hidden_size"]) // h,
        I=int(c["intermediate_size"]), IM=int(c["moe_intermediate_size"]),
        R=r, held=held, K=int(c["num_experts_per_tok"]),
        scale=float(c["routed_scaling_factor"]),
        eps=float(c["norm_eps"]),
        theta=float(c["rope_parameters"]["rope_theta"]),
        KC=int(c["conv_L_cache"]), kinds=kinds,
        std=float(c.get("initializer_range", STD)))


def vocab_blocks(s):
    """[(first id, ids)] of the embedding's blocks."""
    return [(a, min(VB, s["V"] - a)) for a in range(0, s["V"], VB)]


def dense_blocks(s):
    """[(first column, columns)] of the dense feed-forward's blocks."""
    return [(a, min(IB, s["I"] - a)) for a in range(0, s["I"], IB)]


def is_conv(s, layer):
    return s["kinds"][layer] == "conv"


def layer_keys(s, layer):
    return (_NORMS + (_CONV if is_conv(s, layer) else _FULL)
            + (_DENSE if layer < s["KD"] else _MOE))


# ------------------------------------------------------------------ weights


def _spec(s, name, rows):
    """(shape, kind, standard deviation) of one piece."""
    e, kd = s["E"], s["KV"] * s["D"]
    norm = lambda n: ((n,), "norm", 0.1)
    mat = lambda *shape: (shape, "normal", s["std"])
    table = {
        "embed": mat(rows, e), "lnf": norm(e), "ln_op": norm(e),
        "ln_ffn": norm(e),
        "conv_w": ((s["KC"], e), "normal", 1.0 / math.sqrt(3.0)),
        "w_in": mat(e, 3 * e), "w_out": mat(e, e),
        "q_norm": norm(s["D"]), "k_norm": norm(s["D"]),
        "wq": mat(e, e), "wk": mat(e, kd), "wv": mat(e, kd),
        "wo": mat(e, e),
        "w_gate": mat(e, rows), "w_up": mat(e, rows),
        "w_down": mat(rows, e),
        "router": mat(e, s["R"]), "bias": ((s["R"],), "normal", 0.01),
        "e_gate": mat(e, s["IM"]), "e_up": mat(e, s["IM"]),
        "e_down": mat(s["IM"], e),
    }
    return table[name]


def _draw(seed, index, layer, block, shape, kind, std):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), index), layer), block)
    z = jax.random.normal(key, shape, jnp.float32)
    return 1.0 + std * z if kind == "norm" else std * z


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std"))
def _tensor(seed, index, layer, block, *, shape, kind, std):
    """One piece; a program a shape, not a tensor's name."""
    return _draw(seed, index, layer, block, shape, kind, std)


class Weights:
    """The seed's weights as a function of (tensor, layer, block): every
    call makes the float32 piece anew, on the default device.  ``block``
    is a block of the vocabulary (embed), of the dense feed-forward's
    columns (w_gate, w_up; rows of w_down), or the expert's number among
    the router's outputs (e_gate, e_up, e_down)."""

    def __init__(self, sizes, seed):
        self.sizes = dict(sizes)
        # any whole number up to a little over 2**31 is a valid --seed
        self.seed = np.uint32(int(seed) % (2 ** 32))
        self._sz = tuple(sorted(self.sizes.items()))

    def _spec(self, name, block):
        rows = None
        if name == "embed":
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
    """One matmul, a program a shape (a float32 matmul at precision
    ``highest`` takes seconds to compile for the chip -- on the clock of
    a run's check)."""
    with jax.default_matmul_precision("highest"):
        return _mm(x, w, precision)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return _rms(x, w, eps)


@jax.jit
def _add(x, y):
    return x + y


# -- the conv operator


@jax.jit
def _conv_input(bcx):
    """The in-projection's result (n, 3 E) -> u = B * x, and C."""
    b, c_gate, x = jnp.split(bcx, 3, axis=-1)
    return b * x, c_gate


@jax.jit
def _conv(u, taps):
    """The depthwise causal convolution over a WHOLE sequence ``u`` (S,
    E): ``c_t = sum_j taps[j] * u_{t - (K - 1) + j}``, zeros before the
    sequence."""
    k = taps.shape[0]
    ext = jnp.pad(u, ((k - 1, 0), (0, 0)))
    return sum(taps[j] * ext[j:j + u.shape[0]] for j in range(k))


@jax.jit
def _gate(c_gate, conv):
    return c_gate * conv


def conv_operator(w, xs, layer, precision):
    """``o`` of a conv layer for a sequence held as blocks of rows
    ``xs``."""
    s = w.sizes
    p = {k: w.tensor(k, layer) for k in ("ln_op",) + _CONV}
    rows = xs[0].shape[0]
    ins = [_conv_input(_matmul(_norm(x, p["ln_op"], s["eps"]), p["w_in"],
                               precision=precision)) for x in xs]
    conv = _conv(jnp.concatenate([u for u, _ in ins]), p["conv_w"])
    return [_matmul(_gate(c_gate, conv[b * rows:(b + 1) * rows]),
                    p["w_out"], precision=precision)
            for b, (_, c_gate) in enumerate(ins)]


# -- attention


@functools.partial(jax.jit, static_argnames=("sz",))
def _heads(q, k, v, q_norm, k_norm, first, *, sz):
    """The projections' results as heads: q (n, H, D), k and v (n, KV, D);
    q and k normalised per head, then turned."""
    s = dict(sz)
    n = q.shape[0]
    q = _rms(q.reshape(n, s["H"], s["D"]), q_norm, s["eps"])
    k = _rms(k.reshape(n, s["KV"], s["D"]), k_norm, s["eps"])
    return (_rot(q, first, s["theta"]), _rot(k, first, s["theta"]),
            v.reshape(n, s["KV"], s["D"]))


@functools.partial(jax.jit, static_argnames=("sz",))
def _attend(acc, q, k, v, q_first, k_first, *, sz):
    """A block of queries against a block of keys: the running softmax
    ``acc = (largest score, sum, weighted values)`` a query a head,
    carried on."""
    s = dict(sz)
    m, l, o = acc
    g = s["H"] // s["KV"]
    qi = q_first + jnp.arange(q.shape[0])
    kj = k_first + jnp.arange(k.shape[0])
    see = kj[None, :] <= qi[:, None]
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
def _attn_rows(acc):
    """softmax(s) v of a block of rows, heads side by side."""
    _, l, o = acc
    o = o / l[..., None]
    return o.transpose(2, 0, 1, 3).reshape(o.shape[2], -1)


def attention(w, xs, layer, precision):
    """``o`` of a full_attention layer for a sequence held as blocks."""
    s = w.sizes
    p = {k: w.tensor(k, layer) for k in ("ln_op",) + _FULL}
    rows = xs[0].shape[0]
    mm = functools.partial(_matmul, precision=precision)
    proj = []
    for b, x in enumerate(xs):
        a = _norm(x, p["ln_op"], s["eps"])
        proj.append(_heads(mm(a, p["wq"]), mm(a, p["wk"]), mm(a, p["wv"]),
                           p["q_norm"], p["k_norm"], b * rows, sz=w._sz))
    shape = (s["KV"], s["H"] // s["KV"], rows)
    out = []
    for i, (q, _, _) in enumerate(proj):
        acc = (jnp.full(shape, -jnp.inf), jnp.zeros(shape),
               jnp.zeros(shape + (s["D"],)))
        for j in range(i + 1):
            acc = _attend(acc, q, proj[j][1], proj[j][2], i * rows,
                          j * rows, sz=w._sz)
        out.append(mm(_attn_rows(acc), p["wo"]))
    return out


# -- the feed-forward


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
    w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-6) * s["scale"]
    return jnp.zeros((n, s["R"])).at[jnp.arange(n)[:, None], idx].set(w)


@functools.partial(jax.jit, static_argnames=("sz",))
def route(m, router, bias, *, sz):
    """m (n, E) -> the layer's weights as a dense (n, R) float32 matrix:
    ``w_e`` at each chosen expert's column, zero elsewhere."""
    with jax.default_matmul_precision("highest"):
        return _route(m, router, bias, dict(sz))


@functools.partial(jax.jit, static_argnames=("precision",))
def _expert_terms(f, m, wts, experts, *, precision):
    """``f`` plus the terms of the experts whose matrices are stacked in
    ``experts`` (gate, up, down), every one applied to every row and
    weighted by its column of ``wts`` (n, experts)."""
    with jax.default_matmul_precision("highest"):
        def one(f, e_w):
            wt, w_e = e_w
            return f + wt[:, None] * _swiglu_(m, *w_e, precision), None

        return jax.lax.scan(one, f, (wts.T, experts))[0]


def ffn_terms(w, ms, layer, precision, held=None):
    """``f`` of one layer for the blocks ``ms`` of pre-normed rows: the
    dense SwiGLU, or the terms of the experts in ``held`` (default: the
    sizes' ownership range).  Each piece of the weights is made once and
    meets every block."""
    s = w.sizes
    if layer < s["KD"]:
        fs = [0.0] * len(ms)
        for b in range(len(dense_blocks(s))):
            piece = [w.tensor(k, layer, b) for k in _DENSE]
            fs = [f + _swiglu(m, *piece, precision=precision)
                  for f, m in zip(fs, ms)]
        return fs
    lo, hi = s["held"] if held is None else held
    router, bias = w.tensor("router", layer), w.tensor("bias", layer)
    wts = [route(m, router, bias, sz=w._sz) for m in ms]
    fs = [jnp.zeros_like(m) for m in ms]
    for first in range(lo, hi, EG):
        group = range(first, min(first + EG, hi))
        experts = tuple(jnp.stack([w.tensor(k, layer, e) for e in group])
                        for k in ("e_gate", "e_up", "e_down"))
        fs = [_expert_terms(f, m, wt[:, group.start:group.stop], experts,
                            precision=precision)
              for f, m, wt in zip(fs, ms, wts)]
    return fs


def _layer(w, xs, layer, precision):
    """One layer over a sequence held as blocks of rows."""
    s = w.sizes
    op = conv_operator if is_conv(s, layer) else attention
    xs = [_add(x, o) for x, o in zip(xs, op(w, xs, layer, precision))]
    ln = w.tensor("ln_ffn", layer)
    fs = ffn_terms(w, [_norm(x, ln, s["eps"]) for x in xs], layer,
                   precision)
    return [_add(x, f) for x, f in zip(xs, fs)]


@jax.jit
def _embed_rows(table, ids, first):
    """Rows of one block of the table for the ids that lie in it."""
    local = ids - first
    hit = (local >= 0) & (local < table.shape[0])
    rows = table[jnp.clip(local, 0, table.shape[0] - 1)]
    return jnp.where(hit[:, None], rows, 0.0)


def embed(w, ids):
    x = 0.0
    for b, (first, _) in enumerate(vocab_blocks(w.sizes)):
        x = x + _embed_rows(w.tensor("embed", block=b), ids, first)
    return x


def hidden_states(w, ids, precision="f32"):
    """ids (S,) int32 -> final-RMSNorm hidden states (S', E), S' = S
    rounded up to whole blocks of rows (right padding is invisible:
    convolution and attention are causal); one layer's pieces of the
    weights alive at a time."""
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
    """Logits over one block of the vocabulary: the head is the
    embedding's rows."""
    with jax.default_matmul_precision("highest"):
        return _mm(h, wb.T, precision)


def logits(w, h, precision="f32"):
    """Final hidden states (R, E) -> logits (R, V), a vocabulary block at
    a time (small R only: the tests, and the served rows below)."""
    return jnp.concatenate([
        _head_block(h, w.tensor("embed", block=b), precision=precision)
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
        lg = np.asarray(_head_block(rows, w.tensor("embed", block=b),
                                    precision="f32"))
        best = np.maximum(best, lg.max(-1))
        scale = max(scale, float(np.abs(lg[:n_served]).max()))
        hit = (chosen >= a) & (chosen < a + n)
        got = np.where(hit, lg[np.arange(len(chosen)),
                               np.clip(chosen - a, 0, n - 1)], got)
    gap = (best - got)[:n_served]
    return float(gap.max()), float(gap.sum()), scale
