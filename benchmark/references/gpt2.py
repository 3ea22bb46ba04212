"""GPT-2 as published, in plain float32 ``jax.numpy`` -- the yardstick.

Radford et al. 2019: learned positions, pre-LayerNorm blocks, causal
softmax attention scaled by 1/sqrt(head size), tanh-approximated GELU
MLP, final LayerNorm, output head tied to the token embedding.  No
kernel, no cache, no batching tricks.  Nothing here imports the program
and nothing here takes an array the program made: the weights come from
``init_weights`` (one jitted call from the seed), which the benchmark
hands to the program *and* to this file.

Every entry point runs under matmul precision ``highest``: on a TPU a
float32 matmul otherwise runs in bf16 passes.

``precision`` selects the arithmetic of the matmul operands:

* ``"f32"``  -- the reference.
* ``"fp8"``  -- the control: a step below bf16.  Matmul operands are
  rounded to float8 e4m3, weights per output channel and activations per
  row, the product accumulates in float32; the backward pass sees the
  rounding as identity (straight-through).  The benchmark's limits are
  set so that this comes out as not correct.
* ``"int8"`` -- the same with int8 operands (absmax/127).  Read beside
  fp8 and found to lie within bf16's own error on the numbers compared
  (PERF.md section 2): it sets no limit.

Limits (each with the readings it was set from) are in the cell files,
``benchmark/cells/*.json`` under ``limits``; PERF.md section 2 has the table.
"""

import functools
import math

import jax
import jax.numpy as jnp

LAYER_KEYS = ("ln1_s", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "bo", "ln2_s", "ln2_b", "w1", "b1", "w2", "b2")


def sizes_of(config):
    """The sizes this file needs, from a configuration file's keys (the
    names of the published ``config.json``)."""
    e = int(config["n_embd"])
    return dict(V=int(config["vocab_size"]), P=int(config["n_positions"]),
                E=e, L=int(config["n_layer"]), H=int(config["n_head"]),
                I=int(config.get("n_inner") or 4 * e),
                eps=float(config["layer_norm_epsilon"]))


@functools.partial(jax.jit, static_argnames=("V", "P", "E", "L", "I"))
def _init(seed, *, V, P, E, L, I):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda k, shape, std: std * jax.random.normal(k, shape, jnp.float32)
    # the published initialisation: N(0, 0.02), residual projections
    # scaled by 1/sqrt(2 L); biases zero, LayerNorm at identity
    res = 0.02 / math.sqrt(2 * L)
    z = lambda *shape: jnp.zeros(shape, jnp.float32)
    o = lambda *shape: jnp.ones(shape, jnp.float32)
    return dict(
        wte=n(ks[0], (V, E), 0.02), wpe=n(ks[1], (P, E), 0.01),
        ln1_s=o(L, E), ln1_b=z(L, E), ln2_s=o(L, E), ln2_b=z(L, E),
        wq=n(ks[2], (L, E, E), 0.02), bq=z(L, E),
        wk=n(ks[3], (L, E, E), 0.02), bk=z(L, E),
        wv=n(ks[4], (L, E, E), 0.02), bv=z(L, E),
        wo=n(ks[5], (L, E, E), res), bo=z(L, E),
        w1=n(ks[6], (L, E, I), 0.02), b1=z(L, I),
        w2=n(ks[7], (L, I, E), res), b2=z(L, E),
        lnf_s=o(E), lnf_b=z(E))


def init_weights(sizes, seed):
    """Seeded float32 weights on the default device, in one jitted call.
    Per-layer tensors are stacked on a leading ``L`` axis."""
    s = sizes
    # any whole number up to a little over 2**31 is a valid --seed
    return _init(jnp.uint32(int(seed) % (2 ** 32)), V=s["V"], P=s["P"],
                 E=s["E"], L=s["L"], I=s["I"])


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _round(x, axis, precision):
    """Round to int8, or to float8 e4m3 (three mantissa bits), with the
    row's or column's largest value scaled to the format's largest; the
    gradient passes straight through."""
    top = {"int8": 127.0, "fp8": 448.0}[precision]
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if precision == "int8":
        q = jnp.clip(jnp.round(x / s), -127, 127) * s
    else:
        q = (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, precision):
    if precision in ("int8", "fp8"):
        x, w = _round(x, -1, precision), _round(w, 0, precision)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return x @ w


def hidden_states(w, ids, n_head, eps, precision="f32"):
    """ids (S,) int32 -> final-LayerNorm hidden states (S, E)."""
    s = ids.shape[0]
    x = w["wte"][ids] + w["wpe"][:s]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, p):
        h = _ln(x, p["ln1_s"], p["ln1_b"], eps)
        q, k, v = (_mm(h, p["w" + n], precision) + p["b" + n]
                   for n in "qkv")
        d = q.shape[-1] // n_head
        q, k, v = (a.reshape(s, n_head, d) for a in (q, k, v))
        sc = jnp.einsum("shd,thd->hst", q, k) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        a = jnp.einsum("hst,thd->shd", pr, v).reshape(s, -1)
        x = x + _mm(a, p["wo"], precision) + p["bo"]
        h = _ln(x, p["ln2_s"], p["ln2_b"], eps)
        h = jax.nn.gelu(_mm(h, p["w1"], precision) + p["b1"],
                        approximate=True)
        return x + _mm(h, p["w2"], precision) + p["b2"], None

    x, _ = jax.lax.scan(block, x, {k: w[k] for k in LAYER_KEYS})
    return _ln(x, w["lnf_s"], w["lnf_b"], eps)


def logits(w, ids, n_head, eps, precision="f32"):
    """ids (S,) -> logits (S, V) through the tied head."""
    h = hidden_states(w, ids, n_head, eps, precision)
    return _mm(h, w["wte"].T, precision)


# ------------------------------------------------------------------ serving


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def _served_gaps(w, ids, first, n_served, *, n_head, eps, precision):
    with jax.default_matmul_precision("highest"):
        ref = logits(w, ids, n_head, eps, "f32")
        best = ref.max(-1)
        if precision == "f32":
            chosen = jnp.roll(ids, -1)          # the token served next
        else:
            chosen = logits(w, ids, n_head, eps, precision).argmax(-1)
        gap = best - jnp.take_along_axis(ref, chosen[:, None], 1)[:, 0]
    pos = jnp.arange(ids.shape[0])
    served = (pos >= first) & (pos < first + n_served)
    gap = jnp.where(served, gap, 0.0)
    return gap.max(), gap.sum(), jnp.abs(ref).max()


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
    import numpy as np

    ids = np.zeros(sizes["P"], np.int32)        # right padding is
    ids[:len(tokens)] = tokens                  # invisible (causal)
    worst, total, scale = _served_gaps(
        w, jnp.asarray(ids), prompt_len - 1, len(tokens) - prompt_len,
        n_head=sizes["H"], eps=sizes["eps"], precision=precision)
    return float(worst), float(total), float(scale)


# ----------------------------------------------------------------- training


def _row_loss_sum(w, ids, labels, n_head, eps, precision):
    lg = logits(w, ids, n_head, eps, precision)
    lse = jax.nn.logsumexp(lg, -1)
    return jnp.sum(lse - jnp.take_along_axis(lg, labels[:, None], 1)[:, 0])


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def _batch_loss_and_grad(w, ids, labels, *, n_head, eps, precision):
    """Mean next-token cross entropy of a (B, S) batch and its gradient,
    one row at a time so that it fits beside nothing."""
    f = jax.value_and_grad(_row_loss_sum)

    def row(carry, xy):
        with jax.default_matmul_precision("highest"):
            l, g = f(w, xy[0], xy[1], n_head, eps, precision)
        return (carry[0] + l, jax.tree.map(jnp.add, carry[1], g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, w))
    (l, g), _ = jax.lax.scan(row, zero, (ids, labels))
    n = ids.shape[0] * ids.shape[1]
    return l / n, jax.tree.map(lambda a: a / n, g)


def _leaf_norms(tree):
    """{name: (L,) or () norms}: one norm per published tensor (stacked
    layers give one per layer)."""
    out = {}
    for k, a in tree.items():
        axes = tuple(range(1, a.ndim)) if k in LAYER_KEYS else None
        out[k] = jnp.sqrt(jnp.sum(a.astype(jnp.float32) ** 2, axis=axes))
    return out


def sgd_momentum_steps(w0, sizes, batches, lr, momentum, precision="f32"):
    """Follow the first ``len(batches)`` optimizer steps of SGD with
    momentum (buf = m * buf + g; p -= lr * buf) from ``w0``.

    Returns (losses per step, per-tensor norms of the first gradient,
    per-tensor norms of the parameters' change after the last step)."""
    w, buf = w0, None
    losses, g1 = [], None
    for ids, labels in batches:
        l, g = _batch_loss_and_grad(
            w, ids, labels, n_head=sizes["H"], eps=sizes["eps"],
            precision=precision)
        if g1 is None:
            g1 = _leaf_norms(g)
        buf = g if buf is None else jax.tree.map(
            lambda b, gg: momentum * b + gg, buf, g)
        w = jax.tree.map(lambda p, b: p - lr * b, w, buf)
        losses.append(float(l))
    delta = _leaf_norms(jax.tree.map(jnp.subtract, w, w0))
    return losses, g1, delta
