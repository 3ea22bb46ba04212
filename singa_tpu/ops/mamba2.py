"""The Mamba-2 mixer's recurrence, by shape: what of the mixer runs along
a sequence -- the depthwise causal convolution over ``x | B | C`` and

    S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t;    y_t = S_t C_t + D x_t

-- in its two forms: a chunked scan in the matrix ("state-space dual")
form for a prefill launch (:func:`mix`, over :func:`ssd_chunk`), and one
step a lane for a decode step (:func:`step`), each lane's state read,
advanced and written back where it lies in the engine's state arena:
by ONE Pallas call over all the lanes' rows (ops/pallas/mamba2_step.py)
where :func:`step_impl` -- a pure function of the arena -- says so, a
float32 arena of whole tiles in an unsharded program on a TPU; by a loop
a lane at a time (:func:`_step_loop`) everywhere else, every CPU run
among them, which is also what the kernel is held to
(tests/test_mamba2_step_kernel.py).
Every served family with such a mixer calls these (``models/falcon_h1.py``:
a mixer beside attention in every layer; ``models/ssm_moe.py``: layers
that are a mixer and nothing else); the projections, multipliers, gate
and norm either side are the family's own.

Nothing here reads a configuration: heads ``h``, head size ``p``, state
size ``n`` are the state's shape ``(h, p, n)``, the groups of ``B`` and
``C`` follow from the convolution's width ``h p + 2 g n`` (head ``i``
reads group ``i // (h / g)``), the taps from ``conv_w`` (K, width).  ``p``
below is a layer's per-channel vectors, float32: ``conv_w``, ``conv_b``,
``dt_bias`` (h,), ``a_log`` (h,), ``d`` (h,).  The state path is float32
throughout.
"""

import jax
import jax.numpy as jnp

from .paged_attention import _varies
from .pallas import mamba2_step as _pallas

HI = jax.lax.Precision.HIGHEST      # the state path: float32 throughout


def split_xbc(xbc, shape):
    """Conv output (T, h p + 2 g n) -> x (T, h, p), B and C (T, g, n),
    for a state of ``shape`` (h, p, n)."""
    t = xbc.shape[0]
    h, p, n = shape
    ds = h * p
    gn = (xbc.shape[1] - ds) // 2
    x = xbc[:, :ds].reshape(t, h, p)
    b = xbc[:, ds:ds + gn].reshape(t, gn // n, n)
    cc = xbc[:, ds + gn:].reshape(t, gn // n, n)
    return x, b, cc


def ssd_chunk(x, b, cc, dt, a, s_in):
    """One chunk of the Mamba-2 scan in its matrix ("state-space dual")
    form: x (T, h, p), b and cc (T, g, n), dt (T, h) after softplus (0
    where a token must leave the state as it is), a (h,) negative, s_in
    (h, p, n) the state before the chunk.  Returns (y (T, h, p) without
    the D term, the state after the chunk).  Equal to T steps of
    ``S <- exp(dt a) S + dt x (x) B;  y = S C``."""
    t, h, p = x.shape
    g = b.shape[1]
    k = h // g                                   # heads a group
    la = jnp.cumsum(dt * a, axis=0)              # (T, h), decreasing
    # within the chunk: y_t += sum_{s<=t} (C_t.B_s) e^{la_t-la_s} dt_s x_s
    cb = jnp.einsum("tgn,sgn->gts", cc, b, precision=HI)
    dec = jnp.exp(jnp.where(jnp.tril(jnp.ones((t, t), bool))[None],
                            la.T[:, :, None] - la.T[:, None, :],
                            -jnp.inf))           # (h, T, T)
    m = cb[:, None].repeat(k, 1).reshape(h, t, t) * dec * dt.T[:, None, :]
    y = jnp.einsum("hts,shp->thp", m, x, precision=HI)
    # from the state the chunk started with
    sg = s_in.reshape(g, k, p, -1)
    y_in = jnp.einsum("tgn,gkpn->tgkp", cc, sg, precision=HI)
    y = y + y_in.reshape(t, h, p) * jnp.exp(la)[:, :, None]
    # the state after the chunk
    w = (dt * jnp.exp(la[-1][None] - la))[:, :, None] * x     # (T, h, p)
    s_new = jnp.einsum("sgkp,sgn->gkpn", w.reshape(t, g, k, p), b,
                       precision=HI).reshape(h, p, -1)
    return y, jnp.exp(la[-1])[:, None, None] * s_in + s_new


def mix(xbc, dt, p, ssm, conv, n_valid, sub=None):
    """The recurrence along ONE sequence: ``xbc`` (T, width) before the
    conv and ``dt`` (T, h) before its bias, float32; ``ssm`` (h, p, n) and
    ``conv`` (R, width) the state the row before left -- the conv's last
    ``R >= K - 1`` inputs, of which the newest ``K - 1`` are read (a
    family may keep a row more than the conv needs: :func:`step`) --
    ``n_valid`` how many of the T tokens are real (the prompt's last row
    is padded; padding leaves the state alone).  The conv takes the T rows
    together; the scan walks them ``sub`` at a time in order (default:
    all T as one chunk), each sub-chunk from the state the one before
    left, so a row of several scan chunks computes what as many rows of
    one did.  Returns (y (T, h p) before gate and norm, ssm, conv)."""
    t, kk, r = xbc.shape[0], p["conv_w"].shape[0], conv.shape[0]
    lo = r - (kk - 1)                         # rows older than the conv reads
    ext = jnp.concatenate([conv, xbc], axis=0)            # (T+R, C)
    xbc = jax.nn.silu(sum(p["conv_w"][j] * ext[lo + j:lo + j + t]
                          for j in range(kk)) + p["conv_b"])
    conv = jax.lax.dynamic_slice_in_dim(ext, n_valid, r, axis=0)
    x, b, cc = split_xbc(xbc, ssm.shape)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    dt = jnp.where(jnp.arange(t)[:, None] < n_valid, dt, 0.0)
    with jax.named_scope("ssm_scan"):
        if sub is None or sub == t:
            y, ssm = ssd_chunk(x, b, cc, dt, -jnp.exp(p["a_log"]), ssm)
        else:
            # unrolled (a ``lax.scan`` of two iterations cost more than
            # it ran: PERF.md §6, PR 36)
            ys = []
            for j in range(0, t, sub):
                y_j, ssm = ssd_chunk(
                    x[j:j + sub], b[j:j + sub], cc[j:j + sub],
                    dt[j:j + sub], -jnp.exp(p["a_log"]), ssm)
                ys.append(y_j)
            y = jnp.concatenate(ys)
    return (y + p["d"][:, None] * x).reshape(t, -1), ssm, conv


def step_impl(ssm_all, backend=None):
    """``"kernel"`` or ``"loop"``: which of the two :func:`step` runs for
    this state arena -- an array, a tracer or a ``jax.ShapeDtypeStruct``,
    only its shape, dtype and placement are read.

    The kernel (ops/pallas/mamba2_step.py: every lane's state advanced
    in one call, the arena aliased) takes an unsharded float32 arena
    whose rows fill whole tiles -- ``n`` a multiple of 128, ``p`` of 8 --
    on a TPU.  The loop keeps any other backend (every CPU run), an
    arena that varies over a mesh axis, any other shape or dtype.
    ``backend`` defaults to ``jax.default_backend()``."""
    if backend is None:
        backend = jax.default_backend()
    p, n = ssm_all.shape[-2:]
    tiles = n % 128 == 0 and p % 8 == 0
    return ("kernel" if backend == "tpu" and tiles
            and ssm_all.dtype == jnp.float32 and not _varies(ssm_all)
            else "loop")


def _step_loop(ssm_all, at_lane, da, dx, b, cc):
    """:func:`step`'s recurrence a lane at a time: lane i's state sliced
    out of the arena at ``at_lane(i)``, advanced and written back --
    serial, each iteration paying its own read and its own write.  What
    every backend but a TPU runs, and what the kernel is held to."""
    w, shape = da.shape[0], ssm_all.shape[-3:]
    k = shape[0] // b.shape[1]
    bh = jnp.repeat(b, k, axis=1)                         # (W, h, n)
    ch = jnp.repeat(cc, k, axis=1)
    n_lead = ssm_all.ndim - 3
    row = (1,) * n_lead + shape

    def lane(i, carry):
        # one lane's state read, advanced and written back where it
        # lies: 4 MB in, 4 MB out.  (A gather of the lanes' rows makes
        # the compiler slice the WHOLE arena first, every layer.)
        arena, y = carry
        at = tuple(at_lane(i)) + (0, 0, 0)
        s = jax.lax.dynamic_slice(arena, at, row)[(0,) * n_lead]
        s = da[i][:, None, None] * s + dx[i][..., None] * bh[i][:, None, :]
        y_i = jnp.einsum("hpn,hn->hp", s, ch[i], precision=HI)
        return (jax.lax.dynamic_update_slice(arena, s[(None,) * n_lead],
                                             at),
                jax.lax.dynamic_update_slice(y, y_i[None], (i, 0, 0)))

    return jax.lax.fori_loop(0, w, lane, (ssm_all, jnp.zeros_like(dx)))


def step(xbc, dt, p, ssm_all, conv_all, lead, slots):
    """The recurrence one token a lane: ``xbc`` (W, width) and ``dt`` (W,
    h) as in :func:`mix`; lane w's state is the ``(h, p, n)`` and ``(R,
    width)`` at ``lead(slots[w])`` -- the leading indices of this
    layer's place in the arenas ``ssm_all`` and ``conv_all``, whatever
    their leading axes -- read, advanced one step and written back.
    ``R`` is ``K - 1``, the inputs the conv's next output still needs,
    or more: the newest are used, and a family whose ``K - 1`` is 3 keeps
    4 (a second-minor axis of 3 is tiled by ones at a program's boundary
    and by fours inside it, and the compiler then re-lays the WHOLE
    arena on the way in and on the way out of every step:
    tests/test_tpu_compile.py).

    The SSM state's step runs as :func:`step_impl` says, by the arena
    alone and never by a flag: ONE Pallas call over all W lanes
    (ops/pallas/mamba2_step.py: the lanes' rows of the arena streamed
    through the chip's fast memory, a lane's fetched while the one
    before is advanced and the one before that written back), or the
    loop a lane at a time (:func:`_step_loop`).  The two agree up to
    float32 reordering (tests/test_mamba2_step_kernel.py).
    Returns (y (W, h p) before gate and norm, ssm_all, conv_all)."""
    w = xbc.shape[0]
    shape = ssm_all.shape[-3:]
    conv = conv_all[lead(slots)]
    ext = jnp.concatenate([conv, xbc[:, None]], axis=1)   # (W, R+1, C)
    lo = ext.shape[1] - p["conv_w"].shape[0]
    xbc = jax.nn.silu(jnp.einsum("kc,wkc->wc", p["conv_w"],
                                 ext[:, lo:] if lo else ext,
                                 precision=HI) + p["conv_b"])
    x, b, cc = split_xbc(xbc, shape)        # (W, h, p), (W, g, n) x 2
    dt = jax.nn.softplus(dt + p["dt_bias"])               # (W, h)
    da = jnp.exp(dt * -jnp.exp(p["a_log"]))
    dx = dt[:, :, None] * x                               # (W, h, p)
    with jax.named_scope("ssm_step"):
        if step_impl(ssm_all) == "kernel":
            idx = jnp.stack([jnp.broadcast_to(a, slots.shape)
                             for a in lead(slots)])
            ssm_all, y = _pallas.mamba2_step(ssm_all, idx, da, dx, b, cc)
        else:
            ssm_all, y = _step_loop(ssm_all, lambda i: lead(slots[i]), da,
                                    dx, b, cc)
    conv_all = conv_all.at[lead(slots)].set(ext[:, 1:])
    return (y + p["d"][:, None] * x).reshape(w, -1), ssm_all, conv_all
