"""Falcon-H1 as published, in plain float32 ``jax.numpy`` -- the yardstick.

Source: huggingface.co/tiiuae/Falcon-H1-34B-Instruct, ``config.json``
(``model_type`` ``falcon_h1``).  Each layer runs a Mamba-2 mixer and
rotary grouped-query attention side by side on one normalised input,
then a SwiGLU feed-forward.  Key by key, with ``x`` the residual stream:

    h = RMSNorm(x)                                  rms_norm_eps
    a = attention_out_multiplier * Attn(attention_in_multiplier * h)
        q = Wq u, k = key_multiplier * Wk u, v = Wv u   (no biases:
        attention_bias false); num_attention_heads query heads and
        num_key_value_heads K/V heads of head_dim; rotary over all of
        head_dim at rope_theta (rope_scaling null); causal softmax
        scaled 1/sqrt(head_dim); output Wo
    m = ssm_out_multiplier * Mamba2(ssm_in_multiplier * h)
        zxBCdt = (W_in u) * mu, mu repeating ssm_multipliers[0..4] over
        z (mamba_d_ssm), x (mamba_d_ssm), B and C (mamba_n_groups *
        mamba_d_state each), dt (mamba_n_heads)  (mamba_proj_bias false)
        xBC <- silu(causal depthwise conv of width mamba_d_conv, with
        bias (mamba_conv_bias), over the channels of x|B|C)
        D_t = softplus(dt_t + dt_bias), A = -exp(A_log), per head
        S_t = exp(D_t A) S_(t-1) + D_t x_t (x) B_t   (S: mamba_d_head x
        mamba_d_state a head; head h reads group h // (heads / groups))
        y_t = S_t C_t + D x_t
        y <- RMSNorm_grouped(y * silu(z)) (mamba_rms_norm true,
        mamba_norm_before_gate false; groups of d_ssm / n_groups)
        output W_out y
    x <- x + a + m
    g = RMSNorm(x)
    x <- x + mlp_multipliers[1] * W_down(W_up g * silu(
             mlp_multipliers[0] * W_gate g))         (mlp_bias false)

Model: ``embedding_multiplier * Embed(ids)`` in, a final RMSNorm,
``lm_head_multiplier * W_head`` out, untied (tie_word_embeddings false).

The state-space mixer is the plain recurrence over time (``lax.scan``,
one token a step, no chunking); attention is full and causal; there is
no cache and no batching.  Nothing here imports the program and nothing
here takes an array the program made.  Every entry point runs under
matmul precision ``highest``.

**Computed in blocks.**  Six layers and the whole vocabulary are 21 GB
in float32, and the program's weights stay on the chip during the check.
So weights are a *function* of (seed, tensor, layer, block):
``init_weights`` returns a handle (:class:`Weights`), not arrays; one
layer's weights exist at a time, the embedding is read by blocks of rows
and the head by blocks of the vocabulary (``VB`` ids each).  The adapter
lays the same blocks into the program.

ASSUMED (no key of the config pins them; repeated in the configuration
file's ``assumed``):

* rotary pairs dims ``i`` and ``i + head_dim / 2`` (the half-split
  layout of the Hugging Face implementation);
* the conv's tap ``mamba_d_conv - 1`` multiplies the current token;
* weights: seeded normal, each tensor's standard deviation chosen so
  that, under the published multipliers, what it produces from a
  unit-variance input has standard deviation ``c``: 1 for q, k, v, the
  in-projection's five segments, the gate and up projections; 0.5 for
  the three projections that write to the residual stream (so it stays
  O(1): variance 1 + 0.75 a layer); the embedding lands at 1 and the
  logits at 2.  Norm weights 1 + 0.1 N(0, 1); conv taps N(0, 0.5), conv
  bias N(0, 0.1);
* Mamba-2's usual ``A_log`` = log U(1, 16), ``D`` = 1, ``dt_bias`` =
  softplus^-1 of exp U(log 0.001, log 0.1) (``time_step`` 0.001-0.1).

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

VB = 16320            # ids a vocabulary block (261,120 = 16 blocks)
PAD = 1536            # sequences are padded to a multiple of this
ROWS = 512            # served positions are projected this many at once

_TENSORS = ("embed", "head", "lnf", "ln1", "ln2", "wq", "wk", "wv", "wo",
            "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d", "norm",
            "w_out", "w_gate", "w_up", "w_down")
LAYER_KEYS = _TENSORS[3:]


def sizes_of(config):
    """The sizes this file needs, from a configuration file's keys (the
    names of the published ``config.json``; ``P``, the served context,
    from the engine settings).  Every value hashes."""
    c = config
    return dict(
        V=int(c["vocab_size"]), P=int(c["engine"]["max_len"]),
        E=int(c["hidden_size"]), L=int(c["num_hidden_layers"]),
        H=int(c["num_attention_heads"]), KV=int(c["num_key_value_heads"]),
        D=int(c["head_dim"]), I=int(c["intermediate_size"]),
        DS=int(c["mamba_d_ssm"]), N=int(c["mamba_d_state"]),
        G=int(c["mamba_n_groups"]), MH=int(c["mamba_n_heads"]),
        MP=int(c["mamba_d_head"]), K=int(c["mamba_d_conv"]),
        eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
        m_embed=float(c["embedding_multiplier"]),
        m_head=float(c["lm_head_multiplier"]),
        m_attn_in=float(c["attention_in_multiplier"]),
        m_attn_out=float(c["attention_out_multiplier"]),
        m_key=float(c["key_multiplier"]),
        m_ssm_in=float(c["ssm_in_multiplier"]),
        m_ssm_out=float(c["ssm_out_multiplier"]),
        m_ssm=tuple(float(v) for v in c["ssm_multipliers"]),
        m_mlp=tuple(float(v) for v in c["mlp_multipliers"]))


def segments(s):
    """Widths of the in-projection's five segments: z, x, B, C, dt."""
    gn = s["G"] * s["N"]
    return (s["DS"], s["DS"], gn, gn, s["MH"])


def mup_vector(s):
    """``ssm_multipliers`` repeated over the five segments."""
    return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                            for w, m in zip(segments(s), s["m_ssm"])])


def vocab_blocks(s):
    """[(first id, ids)] of the embedding's and the head's blocks."""
    return [(a, min(VB, s["V"] - a)) for a in range(0, s["V"], VB)]


# ------------------------------------------------------------------ weights


def _spec(s, name):
    """(shape, kind, standard deviation or per-column deviations)."""
    E, I, DS = s["E"], s["I"], s["DS"]
    conv = DS + 2 * s["G"] * s["N"]
    fan = lambda n, c=1.0, m=1.0: c / (m * math.sqrt(n))
    if name == "w_in":
        col = jnp.concatenate([
            jnp.full((w,), fan(E, 1.0, s["m_ssm_in"] * m), jnp.float32)
            for w, m in zip(segments(s), s["m_ssm"])])
        return (E, sum(segments(s))), "normal", col
    # the gate is silu(unit) * unit: standard deviation about 0.4
    table = {
        "lnf": ((E,), "norm", 0.1), "ln1": ((E,), "norm", 0.1),
        "ln2": ((E,), "norm", 0.1), "norm": ((DS,), "norm", 0.1),
        "wq": ((E, s["H"] * s["D"]), "normal", fan(E, 1, s["m_attn_in"])),
        "wk": ((E, s["KV"] * s["D"]), "normal",
               fan(E, 1, s["m_attn_in"] * s["m_key"])),
        "wv": ((E, s["KV"] * s["D"]), "normal", fan(E, 1, s["m_attn_in"])),
        "wo": ((s["H"] * s["D"], E), "normal",
               fan(s["H"] * s["D"], 0.5, s["m_attn_out"])),
        "conv_w": ((s["K"], conv), "normal", 0.5),
        "conv_b": ((conv,), "normal", 0.1),
        "dt_bias": ((s["MH"],), "dt_bias", None),
        "a_log": ((s["MH"],), "a_log", None),
        "d": ((s["MH"],), "ones", None),
        "w_out": ((DS, E), "normal", fan(DS, 0.5, s["m_ssm_out"])),
        "w_gate": ((E, I), "normal", fan(E, 1, s["m_mlp"][0])),
        "w_up": ((E, I), "normal", fan(E)),
        "w_down": ((I, E), "normal", fan(I, 0.5, 0.4 * s["m_mlp"][1])),
    }
    return table[name]


@functools.partial(jax.jit, static_argnames=("name", "sz", "rows"))
def _tensor(seed, layer, block, *, name, sz, rows=None):
    s = dict(sz)
    key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), _TENSORS.index(name)), layer), block)
    if name == "embed":      # `rows` ids of the table, unit after m_embed
        return jax.random.normal(key, (rows, s["E"]), jnp.float32) \
            / s["m_embed"]
    if name == "head":       # `rows` ids of the head: logits of scale 2
        return jax.random.normal(key, (s["E"], rows), jnp.float32) \
            * (2.0 / (s["m_head"] * math.sqrt(s["E"])))
    shape, kind, std = _spec(s, name)
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * std
    if kind == "norm":
        return 1.0 + std * jax.random.normal(key, shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(1.0 + 15.0 * u)
    dt = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)


class Weights:
    """The seed's weights as a function of (tensor, layer, block): every
    call makes the float32 tensor anew, on the default device."""

    def __init__(self, sizes, seed):
        self.sizes = dict(sizes)
        # any whole number up to a little over 2**31 is a valid --seed
        self.seed = np.uint32(int(seed) % (2 ** 32))
        self._sz = tuple(sorted(self.sizes.items()))

    def tensor(self, name, layer=0, block=0):
        rows = None
        if name in ("embed", "head"):
            rows = vocab_blocks(self.sizes)[block][1]
        return _tensor(self.seed, layer, block, name=name, sz=self._sz,
                       rows=rows)

    def layer(self, layer):
        return {k: self.tensor(k, layer) for k in LAYER_KEYS}


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


def _rotary(x, theta):
    """x (S, heads, D) at positions 0..S-1, half-split pairs."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(u, p, s, precision):
    n, h, kv, d = u.shape[0], s["H"], s["KV"], s["D"]
    q = _mm(u, p["wq"], precision).reshape(n, h, d)
    k = (s["m_key"] * _mm(u, p["wk"], precision)).reshape(n, kv, d)
    v = _mm(u, p["wv"], precision).reshape(n, kv, d)
    q, k = _rotary(q, s["theta"]), _rotary(k, s["theta"])
    causal = jnp.tril(jnp.ones((n, n), bool))

    def group(args):            # one K/V head and its query heads
        qg, kg, vg = args       # (n, h/kv, d), (n, d), (n, d)
        sc = jnp.einsum("sgd,td->gst", qg, kg) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("gst,td->sgd", pr, vg)

    qg = q.reshape(n, kv, h // kv, d).transpose(1, 0, 2, 3)
    a = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    a = a.transpose(1, 0, 2, 3).reshape(n, h * d)
    return _mm(a, p["wo"], precision)


def _mamba(u, p, s, precision):
    n, mh, mp, ns, g = u.shape[0], s["MH"], s["MP"], s["N"], s["G"]
    zxbcdt = _mm(u, p["w_in"], precision) * mup_vector(s)
    ds, _, gn, _, _ = segments(s)
    z, xbc, dt = (zxbcdt[:, :ds], zxbcdt[:, ds:2 * ds + 2 * gn],
                  zxbcdt[:, 2 * ds + 2 * gn:])
    k = s["K"]
    pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(sum(p["conv_w"][j] * pad[j:j + n] for j in range(k))
                      + p["conv_b"])
    x = xbc[:, :ds].reshape(n, mh, mp)
    b = xbc[:, ds:ds + gn].reshape(n, g, ns)
    c = xbc[:, ds + gn:].reshape(n, g, ns)
    b = jnp.repeat(b, mh // g, axis=1)            # head h: group h // (mh/g)
    c = jnp.repeat(c, mh // g, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])       # (n, mh)
    a = -jnp.exp(p["a_log"])

    def step(state, t):
        x_t, b_t, c_t, dt_t = t
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((mh, mp, ns)), (x, b, c, dt))
    y = (y + p["d"][:, None] * x).reshape(n, ds) * jax.nn.silu(z)
    y = _rms(y.reshape(n, g, ds // g), p["norm"].reshape(g, ds // g),
             s["eps"]).reshape(n, ds)
    return _mm(y, p["w_out"], precision)


@functools.partial(jax.jit, static_argnames=("sz", "precision"))
def _layer(x, p, *, sz, precision):
    s = dict(sz)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, p["ln1"], s["eps"])
        a = s["m_attn_out"] * _attention(s["m_attn_in"] * h, p, s,
                                         precision)
        m = s["m_ssm_out"] * _mamba(s["m_ssm_in"] * h, p, s, precision)
        x = x + a + m
        g = _rms(x, p["ln2"], s["eps"])
        up = _mm(g, p["w_up"], precision)
        gate = jax.nn.silu(s["m_mlp"][0] * _mm(g, p["w_gate"], precision))
        return x + s["m_mlp"][1] * _mm(up * gate, p["w_down"], precision)


@jax.jit
def _embed_rows(table, ids, first):
    """Rows of one block of the table for the ids that lie in it."""
    local = ids - first
    hit = (local >= 0) & (local < table.shape[0])
    rows = table[jnp.clip(local, 0, table.shape[0] - 1)]
    return jnp.where(hit[:, None], rows, 0.0)


def hidden_states(w, ids, precision="f32"):
    """ids (S,) int32 -> final-RMSNorm hidden states (S, E); one layer's
    weights alive at a time."""
    s = w.sizes
    x = 0.0
    for b, (first, _) in enumerate(vocab_blocks(s)):
        x = x + _embed_rows(w.tensor("embed", block=b), ids, first)
    x = s["m_embed"] * x
    for layer in range(s["L"]):
        x = _layer(x, w.layer(layer), sz=w._sz, precision=precision)
    return _final_norm(x, w.tensor("lnf"), s["eps"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, lnf, eps):
    return _rms(x, lnf, eps)


@functools.partial(jax.jit, static_argnames=("m_head", "precision"))
def _head_block(h, wb, *, m_head, precision):
    with jax.default_matmul_precision("highest"):
        return m_head * _mm(h, wb, precision)


def logits(w, h, precision="f32"):
    """Final hidden states (R, E) -> logits (R, V), a vocabulary block at
    a time (small R only: the tests, and the served rows below)."""
    s = w.sizes
    return jnp.concatenate([
        _head_block(h, w.tensor("head", block=b), m_head=s["m_head"],
                    precision=precision)
        for b in range(len(vocab_blocks(s)))], axis=-1)


# ------------------------------------------------------------------ serving


def _padded(tokens, s):
    n = min(s["P"], -(-len(tokens) // PAD) * PAD)
    ids = np.zeros(max(n, len(tokens)), np.int32)   # right padding is
    ids[:len(tokens)] = tokens                      # invisible (causal)
    return jnp.asarray(ids)


def _served_rows(h, first, n_served):
    """The hidden rows of the served positions, padded to ``ROWS``."""
    r = -(-n_served // ROWS) * ROWS
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
    ids = _padded(tokens, s)
    rows = _served_rows(hidden_states(w, ids, "f32"), first, n_served)
    if precision == "f32":
        chosen = np.zeros(rows.shape[0], np.int64)
        chosen[:n_served] = tokens[prompt_len:]
    else:
        low = _served_rows(hidden_states(w, ids, precision), first,
                           n_served)
        chosen = np.asarray(jnp.argmax(logits(w, low, precision), -1))
    best = np.full(rows.shape[0], -np.inf)
    got = np.zeros(rows.shape[0])
    scale = 0.0
    for b, (a, n) in enumerate(vocab_blocks(s)):
        lg = np.asarray(_head_block(rows, w.tensor("head", block=b),
                                    m_head=s["m_head"], precision="f32"))
        best = np.maximum(best, lg.max(-1))
        scale = max(scale, float(np.abs(lg[:n_served]).max()))
        hit = (chosen >= a) & (chosen < a + n)
        got = np.where(hit, lg[np.arange(len(chosen)),
                               np.clip(chosen - a, 0, n - 1)], got)
    gap = (best - got)[:n_served]
    return float(gap.max()), float(gap.sum()), scale
