"""Nemotron-3-Super-120B-A12B as published, in plain float32
``jax.numpy`` -- the yardstick.

Source: huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
``config.json`` (``model_type`` ``nemotron_h``).  Every layer is ONE
mixer behind one RMS norm, added to the residual stream; which mixer, a
letter of ``hybrid_override_pattern`` says.  Key by key, with ``h`` the
residual stream (``hidden_size``) and RMSNorm at ``layer_norm_epsilon``;
items marked + are not fixed by the config's keys: they are the family's
public implementation (``modeling_nemotron_h.py``) as recalled, and are
repeated in the configuration file's ``assumed``:

    h = E[ids]                      (no multiplier, no position)
    per layer:  h = h + mixer(RMSNorm(h)),  a = RMSNorm(h)
      M  (Mamba-2; mamba_num_heads heads of mamba_head_dim, n_groups
          groups of ssm_state_size, conv_kernel taps):
        [z | xBC | dt] = a W_in      d_ssm | d_ssm + 2 groups state |
                                     heads; no bias (mamba_proj_bias
                                     false)                          (+ order)
        xBC = silu(conv1d(xBC))      depthwise, causal, zeros before the
                                     sequence, with bias (use_conv_bias);
                                     the last tap multiplies the current
                                     token                           (+)
        x, B, C = split(xBC)         head i reads group i // (heads /
                                     groups)                         (+)
        dt = softplus(dt + dt_bias)  not clamped above               (+)
        S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t,   A = -exp(A_log)
        y_t = S_t C_t + D x_t
        y = RMSNorm_grouped(y * silu(z))   gate first, then the norm,
                                     over groups of d_ssm / n_groups
                                     channels                        (+)
        out = y W_out
      *  (attention; num_attention_heads query heads and
          num_key_value_heads K/V heads of head_dim):
        q = a W_q, k = a W_k, v = a W_v     no bias (attention_bias
                                     false); NO rotary and no learned
                                     position: rope_theta and
                                     partial_rotary_factor are published
                                     and the block does not use them  (+)
        out = softmax(q k^T / sqrt(head_dim), causal) v W_o
      E  (LatentMoE; n_routed_experts experts, num_experts_per_tok a
          token, moe_latent_size wide):
        s = sigmoid(a W_r) in float32
        chosen = the num_experts_per_tok largest of s + b   (b the
                 router's correction bias, in the choice only; n_group =
                 topk_group = 1: no group limit)
        w_k = s_k / (sum of the chosen s + 1e-20) * routed_scaling_factor
              (norm_topk_prob)
        u = a W_fc1                         hidden -> latent
        r = sum_k w_k W_down,k relu(W_up,k u)^2     latent ->
              moe_intermediate_size -> latent (mlp_hidden_act relu2,
              mlp_bias false)
        out = r W_fc2 + W_sd relu(W_su a)^2     the shared expert,
              moe_shared_expert_intermediate_size wide, on the full
              hidden size                                            (+)
    logits = RMSNorm_f(h) W_head    (tie_word_embeddings false)

DEPARTURES from the published model, each the configuration's to state:

* depth: ``hybrid_override_pattern`` is the layers kept (one pipeline
  stage's: a whole period of the published pattern).
* the chip's share: ``held = (first, end)`` is the range of the router's
  outputs whose experts exist here.  The router scores all ``R`` and
  chooses among all of them; the terms of chosen experts outside the
  range are left out of ``r``, nothing stands in for them, and the
  partial result goes on to the next layer.  Router, latent projections
  and shared expert are whole on every chip.  The vocabulary is a slice:
  a smaller vocabulary.
* no multi-token prediction head (``num_nextn_predict_layers`` 0).
* the served context is the engine's ``max_len``.

The state-space mixer is the plain recurrence over time (``lax.scan``,
one token a step, no chunking); attention is full and causal; there is
no cache and no batching; the held experts are applied to every token
and weighted (zero where not chosen); no kernels.  Nothing here imports
the program and nothing here takes an array the program made.  Every
entry point runs under matmul precision ``highest``.

**Computed in blocks**, so that a 9,000-token sequence fits beside the
program's 9.3 GB of weights: a sequence is ``ROWS`` rows at a time
through every matmul and through the recurrence (each block from the
state the block before left), attention is a block of queries
against a block of keys with a running softmax, the experts ``EG`` at a
time, embedding and head a slice of the vocabulary at a time.  Weights
are a *function* of (seed, tensor, layer, block): ``init_weights``
returns a handle (:class:`Weights`) and one layer's pieces exist at a
time.  The adapter lays the same pieces into the program.

ASSUMED (no key of the config pins them; repeated in the configuration
file's ``assumed``): the + items above; seeded normal weights, each
tensor's standard deviation chosen so that what it produces from a
unit-variance input has a standard deviation of 1 (q, k, v, the
in-projection, the router's logits, the latent, the experts' and the
shared expert's first matrices; ``relu(unit)^2`` has a second moment of
1.5, which the second matrices divide out) or 0.5 (the projections that
write to the residual stream: ``W_out``, ``W_o``, the shared expert's
``W_sd``; the routed experts' sum through ``W_fc2`` lands at 0.5 for
this chip's quarter of the chosen), so that the stream stays O(1) over
eleven additions (variance about 1 + 0.25 a layer and 0.5 an expert
layer); the embedding unit, the logits of scale 2; norm weights 1 + 0.1
N(0, 1), so that a norm left out shows; conv taps N(0, 0.5), conv bias
N(0, 0.1); Mamba-2's usual ``A_log`` = log U(1, 16), ``D`` = 1,
``dt_bias`` = softplus^-1 of exp U(log time_step_min, log
time_step_max) floored at time_step_floor; the router's bias 0.01 N(0,
1): zero-mean, a few times the spacing of the scores near the cut, so
that it takes part in the choice and a seed does not choose the work.

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

VB = 8192             # ids a vocabulary block (32,768 = 4 blocks)
EG = 16               # experts whose matrices exist at once (128 = 8
                      # groups of 0.35 GB)
ROWS = 512            # rows of a sequence that exist at once in a matmul,
                      # and the queries and the keys of one attention block
SERVED = 768          # served positions are projected this many at once
                      # (the cell's longest reply: one shape)

_M = ("ln", "conv_w", "conv_b", "dt_bias", "a_log", "d", "norm", "w_in",
      "w_out")
_A = ("ln", "wq", "wk", "wv", "wo")
_E = ("ln", "router", "bias", "w_fc1", "w_fc2", "w_su", "w_sd", "e_up",
      "e_down")
_KEYS = {"M": _M, "*": _A, "E": _E}
_TENSORS = ("embed", "head", "lnf") + _M + _A[1:] + _E[1:]


def sizes_of(config):
    """The sizes this file needs, from a configuration file's keys (the
    names of the published ``config.json``; ``P``, the served context,
    from the engine settings; ``R``, the router's outputs, and ``held``
    from the ``share`` a file may state: all of them otherwise; ``KD``
    the layers that are not expert layers, so that ``L - KD`` counts the
    expert layers).  Every value hashes."""
    c, sh = config, config.get("share", {})
    r = int(sh.get("num_experts_published", c["n_routed_experts"]))
    held = tuple(int(v) for v in sh.get("experts_held", (0, r)))
    if held[1] - held[0] != int(c["n_routed_experts"]):
        raise ValueError("n_routed_experts counts the experts held here: "
                         f"{c['n_routed_experts']} against the range {held}")
    pattern = str(c["hybrid_override_pattern"])
    if len(pattern) != int(c["num_hidden_layers"]) \
            or set(pattern) - set(_KEYS):
        raise ValueError("hybrid_override_pattern must name "
                         "num_hidden_layers layers, each M, * or E")
    if not c["use_conv_bias"] or not c["norm_topk_prob"] \
            or c["n_group"] != 1 or c["topk_group"] != 1 \
            or c["n_shared_experts"] != 1 \
            or c["mlp_hidden_act"] != "relu2" or c["tie_word_embeddings"] \
            or c.get("num_nextn_predict_layers", 0):
        raise ValueError(
            "use_conv_bias false, norm_topk_prob false, groups of experts, "
            "n_shared_experts other than 1, another mlp_hidden_act than "
            "relu2, a tied head and a multi-token head are not written "
            "down here")
    if int(c["mamba_num_heads"]) * int(c["mamba_head_dim"]) \
            != int(c["expand"]) * int(c["hidden_size"]):
        raise ValueError("mamba_num_heads * mamba_head_dim must equal "
                         "expand * hidden_size")
    lo, hi, floor = (float(c[k]) for k in (
        "time_step_min", "time_step_max", "time_step_floor"))
    return dict(
        V=int(c["vocab_size"]), P=int(c["engine"]["max_len"]),
        E=int(c["hidden_size"]), L=int(c["num_hidden_layers"]),
        KD=len(pattern) - pattern.count("E"), pattern=pattern,
        H=int(c["num_attention_heads"]), KV=int(c["num_key_value_heads"]),
        D=int(c["head_dim"]), MH=int(c["mamba_num_heads"]),
        MP=int(c["mamba_head_dim"]), N=int(c["ssm_state_size"]),
        G=int(c["n_groups"]), KC=int(c["conv_kernel"]),
        R=r, held=held, K=int(c["num_experts_per_tok"]),
        scale=float(c["routed_scaling_factor"]),
        IM=int(c["moe_intermediate_size"]), LAT=int(c["moe_latent_size"]),
        IS=int(c["moe_shared_expert_intermediate_size"]),
        eps=float(c["layer_norm_epsilon"]), dt=(lo, hi, floor))


def vocab_blocks(s):
    """[(first id, ids)] of the embedding's and the head's blocks."""
    return [(a, min(VB, s["V"] - a)) for a in range(0, s["V"], VB)]


def kind(s, layer):
    """``M``, ``*`` or ``E``."""
    return s["pattern"][layer]


def layer_keys(s, layer):
    return _KEYS[kind(s, layer)]


def d_ssm(s):
    return s["MH"] * s["MP"]


def conv_dim(s):
    return d_ssm(s) + 2 * s["G"] * s["N"]


# ------------------------------------------------------------------ weights


def _spec(s, name, rows):
    """(shape, kind, standard deviation) of one piece."""
    e, ds, cd = s["E"], d_ssm(s), conv_dim(s)
    qd, kd = s["H"] * s["D"], s["KV"] * s["D"]
    norm = lambda n: ((n,), "norm", 0.1)
    mat = lambda a, b, c=1.0: ((a, b), "normal", c / math.sqrt(a))
    # relu(unit)^2 has a second moment of 1.5
    act = lambda a, b, c=1.0: ((a, b), "normal", c / math.sqrt(1.5 * a))
    table = {
        "embed": ((rows, e), "normal", 1.0),
        "head": ((e, rows), "normal", 2.0 / math.sqrt(e)),
        "lnf": norm(e), "ln": norm(e), "norm": norm(ds),
        "conv_w": ((s["KC"], cd), "normal", 0.5),
        "conv_b": ((cd,), "normal", 0.1),
        "dt_bias": ((s["MH"],), "dt_bias", None),
        "a_log": ((s["MH"],), "a_log", None),
        "d": ((s["MH"],), "ones", None),
        "w_in": mat(e, ds + cd + s["MH"]), "w_out": mat(ds, e, 0.5),
        "wq": mat(e, qd), "wk": mat(e, kd), "wv": mat(e, kd),
        "wo": mat(qd, e, 0.5),
        "router": mat(e, s["R"]), "bias": ((s["R"],), "normal", 0.01),
        "w_fc1": mat(e, s["LAT"]), "w_fc2": mat(s["LAT"], e),
        "w_su": mat(e, s["IS"]), "w_sd": act(s["IS"], e, 0.5),
        "e_up": mat(s["LAT"], s["IM"]), "e_down": act(s["IM"], s["LAT"]),
    }
    return table[name]


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std", "dt"))
def _tensor(seed, index, layer, block, *, shape, kind, std, dt):
    """One piece; a program a shape, not a tensor's name."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), index), layer), block)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind in ("normal", "norm"):
        z = jax.random.normal(key, shape, jnp.float32)
        return 1.0 + std * z if kind == "norm" else std * z
    u = jax.random.uniform(key, shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(1.0 + 15.0 * u)
    lo, hi, floor = dt
    step = jnp.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    step = jnp.maximum(step, floor)
    return step + jnp.log(-jnp.expm1(-step))          # softplus^-1


class Weights:
    """The seed's weights as a function of (tensor, layer, block): every
    call makes the float32 piece anew, on the default device.  ``block``
    is a block of the vocabulary (embed, head) or the expert's number
    among the router's outputs (e_up, e_down)."""

    def __init__(self, sizes, seed):
        self.sizes = dict(sizes)
        # any whole number up to a little over 2**31 is a valid --seed
        self.seed = np.uint32(int(seed) % (2 ** 32))
        self._sz = tuple(sorted(self.sizes.items()))

    def tensor(self, name, layer=0, block=0):
        rows = None
        if name in ("embed", "head"):
            rows = vocab_blocks(self.sizes)[block][1]
        shape, kind_, std = _spec(self.sizes, name, rows)
        return _tensor(self.seed, _TENSORS.index(name), layer, block,
                       shape=shape, kind=kind_, std=std,
                       dt=self.sizes["dt"])


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


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


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


# -- the Mamba-2 mixer


@functools.partial(jax.jit, static_argnames=("sz",))
def _recurrence(zxbcdt, p, state, *, sz):
    """The in-projection's result for a block of rows (n, d_ssm +
    conv_dim + heads) that follow ``state`` -- the SSM state (heads, head
    size, state size) and the convolution's last ``conv_kernel - 1``
    inputs, zeros at a sequence's start -- -> (y (n, d_ssm) after the
    gate and the grouped norm, the state after the block): the conv, then
    the recurrence one token a step."""
    s = dict(sz)
    n, mh, mp, ns, g = zxbcdt.shape[0], s["MH"], s["MP"], s["N"], s["G"]
    ds, cd, k = d_ssm(s), conv_dim(s), s["KC"]
    z, xbc, dt = (zxbcdt[:, :ds], zxbcdt[:, ds:ds + cd],
                  zxbcdt[:, ds + cd:])
    ssm, tail = state
    pad = jnp.concatenate([tail, xbc])
    xbc = jax.nn.silu(sum(p["conv_w"][j] * pad[j:j + n] for j in range(k))
                      + p["conv_b"])
    x = xbc[:, :ds].reshape(n, mh, mp)
    b = xbc[:, ds:ds + g * ns].reshape(n, g, ns)
    c = xbc[:, ds + g * ns:].reshape(n, g, ns)
    dt = jax.nn.softplus(dt + p["dt_bias"])       # (n, mh)
    a = -jnp.exp(p["a_log"])

    def step(ssm, t):
        x_t, b_t, c_t, dt_t = t
        b_t = jnp.repeat(b_t, mh // g, axis=0)    # head h: group h // (mh/g)
        c_t = jnp.repeat(c_t, mh // g, axis=0)
        ssm = (jnp.exp(dt_t * a)[:, None, None] * ssm
               + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return ssm, jnp.sum(ssm * c_t[:, None, :], axis=-1)

    ssm, y = jax.lax.scan(step, ssm, (x, b, c, dt))
    y = (y + p["d"][:, None] * x).reshape(n, ds) * jax.nn.silu(z)
    y = _rms(y.reshape(n, g, ds // g), p["norm"].reshape(g, ds // g),
             s["eps"]).reshape(n, ds)
    return y, (ssm, pad[n:])


def mamba_mixer(w, xs, layer, precision):
    """``out`` of an ``M`` layer for a sequence held as blocks of rows
    ``xs``: a block at a time, each from the state the block before
    left."""
    s = w.sizes
    p = {k: w.tensor(k, layer) for k in _M}
    vec = {k: p[k] for k in _M if k not in ("ln", "w_in", "w_out")}
    state = (jnp.zeros((s["MH"], s["MP"], s["N"])),
             jnp.zeros((s["KC"] - 1, conv_dim(s))))
    out = []
    for x in xs:
        proj = _matmul(_norm(x, p["ln"], s["eps"]), p["w_in"],
                       precision=precision)
        y, state = _recurrence(proj, vec, state, sz=w._sz)
        out.append(_matmul(y, p["w_out"], precision=precision))
    return out


# -- attention


@functools.partial(jax.jit, static_argnames=("sz",))
def _attend(acc, q, k, v, q_first, k_first, *, sz):
    """A block of queries (n, H D) against a block of keys and values (m,
    KV D): the running softmax ``acc = (largest score, sum, weighted
    values)`` a query a head, carried on."""
    s = dict(sz)
    m, l, o = acc
    g = s["H"] // s["KV"]
    qi = q_first + jnp.arange(q.shape[0])
    kj = k_first + jnp.arange(k.shape[0])
    see = kj[None, :] <= qi[:, None]
    with jax.default_matmul_precision("highest"):
        qg = q.reshape(q.shape[0], s["KV"], g, s["D"])
        k = k.reshape(k.shape[0], s["KV"], s["D"])
        v = v.reshape(v.shape[0], s["KV"], s["D"])
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
    """``out`` of a ``*`` layer for a sequence held as blocks: no
    position enters."""
    s = w.sizes
    p = {k: w.tensor(k, layer) for k in _A}
    rows = xs[0].shape[0]
    mm = functools.partial(_matmul, precision=precision)
    proj = []
    for x in xs:
        a = _norm(x, p["ln"], s["eps"])
        proj.append((mm(a, p["wq"]), mm(a, p["wk"]), mm(a, p["wv"])))
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


# -- the expert layer


@functools.partial(jax.jit, static_argnames=("sz",))
def route(m, router, bias, *, sz):
    """m (n, E) -> the layer's weights as a dense (n, R) float32 matrix:
    ``w_k`` at each chosen expert's column, zero elsewhere."""
    s = dict(sz)
    with jax.default_matmul_precision("highest"):
        n = m.shape[0]
        sc = jax.nn.sigmoid(m.astype(jnp.float32)
                            @ router.astype(jnp.float32))
        idx = jax.lax.top_k(sc + bias, s["K"])[1]
        wt = jnp.take_along_axis(sc, idx, axis=1)
        wt = wt / (jnp.sum(wt, axis=1, keepdims=True) + 1e-20) * s["scale"]
        return jnp.zeros((n, s["R"])).at[jnp.arange(n)[:, None],
                                         idx].set(wt)


@functools.partial(jax.jit, static_argnames=("precision",))
def _expert_terms(r, u, wts, experts, *, precision):
    """``r`` plus the terms of the experts whose matrices are stacked in
    ``experts`` (up, down), every one applied to every row of the latent
    ``u`` and weighted by its column of ``wts`` (n, experts)."""
    with jax.default_matmul_precision("highest"):
        def one(r, e_w):
            wt, (up, down) = e_w
            return r + wt[:, None] * _mm(_relu2(_mm(u, up, precision)),
                                         down, precision), None

        return jax.lax.scan(one, r, (wts.T, experts))[0]


@functools.partial(jax.jit, static_argnames=("precision",))
def _shared(m, w_su, w_sd, *, precision):
    with jax.default_matmul_precision("highest"):
        return _mm(_relu2(_mm(m, w_su, precision)), w_sd, precision)


def routed_terms(w, ms, layer, precision, held=None):
    """``r`` (in the latent) of one ``E`` layer for the blocks ``ms`` of
    normed rows: the terms of the chosen experts that lie in ``held``
    (default: the sizes' ownership range).  Each expert's matrices are
    made once and meet every block."""
    s = w.sizes
    lo, hi = s["held"] if held is None else held
    router, bias = w.tensor("router", layer), w.tensor("bias", layer)
    w_fc1 = w.tensor("w_fc1", layer)
    wts = [route(m, router, bias, sz=w._sz) for m in ms]
    us = [_matmul(m, w_fc1, precision=precision) for m in ms]
    rs = [jnp.zeros_like(u) for u in us]
    for first in range(lo, hi, EG):
        group = range(first, min(first + EG, hi))
        experts = tuple(jnp.stack([w.tensor(k, layer, e) for e in group])
                        for k in ("e_up", "e_down"))
        rs = [_expert_terms(r, u, wt[:, group.start:group.stop], experts,
                            precision=precision)
              for r, u, wt in zip(rs, us, wts)]
    return rs


def expert_layer(w, ms, layer, precision, held=None, shared=True):
    """``out`` of one ``E`` layer for the blocks ``ms`` of normed rows:
    the held experts' sum through the latent up-projection, and (unless
    ``shared`` is false: the shares' test counts it once) the shared
    expert."""
    w_fc2 = w.tensor("w_fc2", layer)
    outs = [_matmul(r, w_fc2, precision=precision)
            for r in routed_terms(w, ms, layer, precision, held)]
    if not shared:
        return outs
    w_su, w_sd = w.tensor("w_su", layer), w.tensor("w_sd", layer)
    return [_add(o, _shared(m, w_su, w_sd, precision=precision))
            for o, m in zip(outs, ms)]


def _expert_mixer(w, xs, layer, precision):
    ln = w.tensor("ln", layer)
    return expert_layer(w, [_norm(x, ln, w.sizes["eps"]) for x in xs],
                        layer, precision)


_MIXERS = {"M": mamba_mixer, "*": attention, "E": _expert_mixer}


def _layer(w, xs, layer, precision):
    """One layer over a sequence held as blocks of rows."""
    out = _MIXERS[kind(w.sizes, layer)](w, xs, layer, precision)
    return [_add(x, o) for x, o in zip(xs, out)]


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
    convolution, recurrence and attention are causal); one layer's pieces
    of the weights alive at a time."""
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
    """Logits over one block of the vocabulary."""
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
