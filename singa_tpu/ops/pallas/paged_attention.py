"""Decode attention over the block-paged K/V pool -- a Pallas TPU kernel.

One query token a lane (a decode step) against the pool of
``ops/paged_attention.py``: ``(L, N+1, B, H_kv·D)``, a position a row,
every K/V head side by side.  ``ops/paged_attention.paged_decode_attn``
decides when it runs; ``paged_attn``, the block loop, is the reference
it is held to (tests/test_paged_decode_kernel.py).

* The pool stays in HBM where it lies (``memory_space`` ANY) and is only
  read.  Block tables, positions and the layer index are scalar
  prefetch, so ``layer`` may be traced inside a layer scan.
* Grid ``(W,)``, a lane a grid step.  A lane walks ITS OWN
  ``ceil(pos / B)`` blocks, in chunks of ``_CHUNK_BYTES`` of rows: every
  block of a chunk comes to VMEM by its own asynchronous copy, and the
  next chunk's copies -- the next lane's first chunk after a lane's
  last -- are in flight while this one is computed (two buffers).
* Rows are contracted AS STORED, ``(rows, H_kv·D)`` bf16: the queries
  arrive laid in their own K/V head's columns with zeros elsewhere
  (``ring_decode_attn`` does the same off the kernel), so one matmul
  scores every head and one weighs the values; a head's own columns of
  the result are picked by the caller.  The zeros cost FLOPs that an
  attention bound by its reads does not miss.
* Nothing is rounded below float32 but the rows, which are bf16 as
  stored: a float32 operand (the probabilities; the queries where they
  are not bf16 already) goes to the MXU as THREE bf16 terms whose sum
  it is (split in the kernel), stacked as rows of one matmul against
  the exact bf16 rows, and the three results are added in float32.  The
  extra rows ride on the same load of the K/V tiles.
* Running max, sum and accumulator in float32 in VMEM; the lane's new
  row (not yet in the pool) is folded in last, in the kernel.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_CHUNK_BYTES = 640 * 1024   # of K (and of V) a loop iteration contracts
_LANES = 128         # row-stat scratch lane width (min f32 tile is (8, 128))
_SUBLANES = 16       # bf16 tile height: the stacked terms stay aligned


def _n_terms(dtype):
    """bf16 terms that carry a value of ``dtype`` whole."""
    return 1 if dtype == jnp.bfloat16 else 3


def _bf16_terms(x):
    """``x`` (R, C) as bf16 terms stacked as rows: itself where it is
    bf16, else three, (3·R, C), with hi + mid + lo == x to float32's
    last bit."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    if x.dtype == bf16:
        return x
    hi = x.astype(bf16)
    r = x - hi.astype(f32)
    mid = r.astype(bf16)
    lo = (r - mid.astype(f32)).astype(bf16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _sum_terms(y, rows):
    """The float32 result rows of a matmul whose left operand was
    :func:`_bf16_terms`' stack."""
    return sum(y[i:i + rows] for i in range(0, y.shape[0], rows))


def _kernel(layer_ref, tables_ref, pos_ref, q_ref, *rest, block, trash,
            scale, n_tbl, blocks, two_leaf, own):
    if two_leaf:
        (kcur_ref, vcur_ref, pk_hbm, pv_hbm, o_ref,
         kbuf, vbuf, sems, q3_ref, acc_ref, m_ref, l_ref, slot_ref) = rest
    else:
        (kcur_ref, pk_hbm, o_ref,
         kbuf, sems, q3_ref, acc_ref, m_ref, l_ref, slot_ref) = rest
        vcur_ref = pv_hbm = vbuf = None
    w, n_w = pl.program_id(0), pl.num_programs(0)
    rows = blocks * block                    # rows a chunk holds
    hp, xv = acc_ref.shape               # heads (padded), value columns
    layer = layer_ref[0]
    p = pos_ref[w]
    # a lane with nothing in the pool still takes one (empty) turn, so
    # "the chunk after this one" never has to search for a lane
    n_chunks = jnp.maximum(1, (p + rows - 1) // rows)

    def entry(w_, c_, i):
        """Block ``i`` of lane ``w_``'s chunk ``c_``: its pool index,
        and whether the lane attends it."""
        j = c_ * blocks + i
        blk = tables_ref[w_ * n_tbl + jnp.minimum(j, n_tbl - 1)]
        return blk, (j * block < pos_ref[w_]) & (blk != trash)

    def copies(w_, c_, slot, act):
        def one(i, carry):
            blk, live = entry(w_, c_, i)
            at = pl.ds(pl.multiple_of(i * block, block), block)

            @pl.when(live)
            def _():
                act(pltpu.make_async_copy(
                    pk_hbm.at[layer, blk], kbuf.at[slot, at],
                    sems.at[0, slot]))
                if two_leaf:
                    act(pltpu.make_async_copy(
                        pv_hbm.at[layer, blk], vbuf.at[slot, at],
                        sems.at[1, slot]))

            return carry

        # (a loop, not sixteen copies of its body: the kernel is
        # compiled once a decode bucket, inside every run's set-up)
        jax.lax.fori_loop(0, blocks, one, 0)

    start = functools.partial(copies, act=lambda cp: cp.start())
    wait = functools.partial(copies, act=lambda cp: cp.wait())

    @pl.when(w == 0)
    def _first():
        # a chunk's absent blocks keep what the buffer held: zeros or
        # older rows of the pool, never uninitialised memory (0 · NaN)
        kbuf[...] = jnp.zeros_like(kbuf)
        if two_leaf:
            vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        start(0, 0, 0)

    q3_ref[...] = _bf16_terms(q_ref[...])
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def chunk(c, slot):
        nxt = 1 - slot

        @pl.when(c + 1 < n_chunks)
        def _():
            start(w, c + 1, nxt)

        @pl.when((c + 1 >= n_chunks) & (w + 1 < n_w))
        def _():
            start(w + 1, 0, nxt)

        wait(w, c, slot)

        @pl.when(c * rows < p)
        def _():
            col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
            blk_of = jax.lax.fori_loop(
                0, blocks, lambda i, at: jnp.where(
                    col // block == i, entry(w, c, i)[0], at),
                jnp.zeros((1, rows), jnp.int32))
            live = (c * rows + col < p) & (blk_of != trash)
            k = kbuf[slot]
            s = _sum_terms(jax.lax.dot_general(
                q3_ref[...], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32), hp) * scale
            s = jnp.where(live, s, NEG_INF)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # explicit zero: a fully masked chunk leaves m_new at NEG_INF
            pr = jnp.where(live, jnp.exp(s - m_new), 0.0)
            l_new = l_ref[:, :1] * alpha + jnp.sum(pr, axis=-1,
                                                   keepdims=True)
            v = vbuf[slot] if two_leaf else k[:, :xv]
            acc_ref[...] = acc_ref[...] * alpha + _sum_terms(
                jnp.dot(_bf16_terms(pr), v,
                        preferred_element_type=jnp.float32), hp)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        return nxt

    slot_ref[0] = jax.lax.fori_loop(0, n_chunks, chunk, slot_ref[0])

    # the lane's own new row, not yet in the pool
    kc = kcur_ref[...]                                   # (1, X) f32
    s_cur = jnp.sum(q_ref[...].astype(jnp.float32) * kc, axis=-1,
                    keepdims=True) * scale
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, s_cur)
    alpha = jnp.exp(m_prev - m_new)
    p_cur = jnp.exp(s_cur - m_new)
    vc = vcur_ref[...] if two_leaf else kc[:, :xv]
    out = (acc_ref[...] * alpha + p_cur * vc) \
        / (l_ref[:, :1] * alpha + p_cur)
    if own is None:
        o_ref[...] = out
        return
    # ... of which a head keeps its own K/V head's columns: whole tiles
    g, d = own
    acc_ref[...] = out
    for k in range(xv // d):
        o_ref[k * g:(k + 1) * g, :] = \
            acc_ref[k * g:(k + 1) * g, k * d:(k + 1) * d]


def paged_decode_attn(q, pool_k, pool_v, layer, tables, pos, block, trash,
                      k_cur, v_cur, scale, v_dim=None, _interpret=False):
    """One token a lane against its blocks of the pool and its own new
    row: ``q`` (W, n_kv, g, d); ``pool_k``/``pool_v`` (L, N+1, B, X) bf16
    with X = n_kv·d (``pool_v`` None: the value is the first ``v_dim``
    columns of the key row); ``layer`` a traced or static scalar;
    ``tables`` (W, T); ``pos`` (W,); ``k_cur``/``v_cur`` (W, X) the
    lanes' new rows.  Attends positions ``< pos[w]`` of lane ``w``'s
    blocks (trash entries masked) and the new row.  Returns
    (W, n_kv, g, d | v_dim) float32."""
    n_w, n_kv, g, d = q.shape
    n_h, x = n_kv * g, n_kv * d
    two_leaf = pool_v is not None
    # the value's columns of a one-leaf row, in whole 128-lane tiles
    xv = x if two_leaf else -(-v_dim // _LANES) * _LANES
    hp = -(-n_h // _SUBLANES) * _SUBLANES
    # whole blocks a loop iteration: 640 KB of bf16 rows (256 rows of
    # 1280 columns, 512 of 640, 640 of 512) is as fast as more, and the
    # kernel -- unrolled over a chunk's tiles -- compiles in a fraction
    # of the time, which every decode bucket pays in every run's set-up
    blocks = max(1, _CHUNK_BYTES // (2 * x * block))
    # a head's own columns of the result are whole tiles where its size
    # is: the kernel picks them; else the caller does, below
    tiled = two_leaf and n_kv > 1 and d % _LANES == 0
    # a head's query in its own K/V head's columns of a row
    own = jnp.eye(n_kv, dtype=bool)[None, :, None, :, None]
    qx = jnp.where(own, q[:, :, :, None, :], 0)
    qx = jnp.pad(qx.reshape(n_w, n_h, x), ((0, 0), (0, hp - n_h), (0, 0)))
    f32row = lambda r: r.astype(jnp.float32)[:, None, :]
    lane3 = lambda *tail: pl.BlockSpec((None,) + tail,
                                       lambda w, *_: (w, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    cur = [f32row(k_cur)] + ([f32row(v_cur)] if two_leaf else [])
    pools = [pool_k] + ([pool_v] if two_leaf else [])
    buf = pltpu.VMEM((2, blocks * block, x), pool_k.dtype)
    out = pl.pallas_call(
        functools.partial(
            _kernel, block=block, trash=trash, scale=scale,
            n_tbl=tables.shape[1], blocks=blocks, two_leaf=two_leaf,
            own=(g, d) if tiled else None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_w,),
            in_specs=[lane3(hp, x)]
            + [lane3(1, x)] * len(cur) + [hbm] * len(pools),
            out_specs=lane3(hp, d if tiled else xv),
            scratch_shapes=[buf] * len(pools) + [
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((_n_terms(q.dtype) * hp, x), jnp.bfloat16),
                pltpu.VMEM((hp, xv), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((n_w, hp, d if tiled else xv),
                                       jnp.float32),
        # the buffer slot and the copies in flight pass from a lane to
        # the next: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode_attn",
        interpret=_interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      qx, *cur, *pools)
    if not two_leaf:
        return out[:, :n_h, :v_dim].reshape(n_w, n_kv, g, v_dim)
    out = out[:, :n_h]
    if tiled or n_kv == 1:
        return out.reshape(n_w, n_kv, g, d)
    # ... of which a head keeps its own K/V head's columns
    return jnp.sum(jnp.where(own, out.reshape(n_w, n_kv, g, n_kv, d), 0.0),
                   axis=3)
