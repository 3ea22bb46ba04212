"""Attention of one sequence against a block-paged K/V pool, and the
write of its new keys and values: the half of a decode or chunk block
that every served family shares (serve/paged.py's pool, the engine's
block tables).  What differs between families happens before the call:
the projections, a rotation of q and k (:func:`rotary`), how many query
heads share a K/V head (read off ``q``'s shape).

This module alone knows how a pool is stored (:func:`pool_zeros`): a
token a row, ``(L, N+1, B, H_kv·D)``.  Every program reaches into it the
same way -- the block loop reads the slab ``pool[layer, blk]``, writers
read whole blocks, change rows and scatter the blocks back at
``pool.at[layer, dst]`` -- and a prefill row ``(L, 1, H_kv, W, D)``
becomes blocks, and blocks a row, in :func:`row_to_blocks` /
:func:`blocks_to_row`.  With rows that fill whole 128-lane tiles and
that access pattern the compiler updates a donated pool where it lies;
with a head's 64 values as the row it laid the whole pool out again in
every program that scattered into it (tests/test_tpu_compile.py).
"""

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def pool_zeros(n_layer, n_blocks, block, n_kv, head_dim, dtype, quant,
               sharding=None):
    """A zeroed pool of ``n_blocks`` blocks (the trash block counted):
    ``(L, n_blocks, B, H_kv·D)`` -- a block is ``B`` rows, a row one
    position's keys (or values) of all K/V heads side by side, so the
    last axis fills whole 128-lane tiles where ``D`` alone would not --
    or the int8 pair ``(values (L, n_blocks, B, H_kv·D), scales
    (L, n_blocks, B, H_kv))``.  Allocated directly at ``sharding``."""
    lead = (n_layer, n_blocks, block)
    if quant:
        return (jnp.zeros(lead + (n_kv * head_dim,), jnp.int8,
                          device=sharding),
                jnp.zeros(lead + (n_kv,), jnp.float32, device=sharding))
    return jnp.zeros(lead + (n_kv * head_dim,), dtype, device=sharding)


def leaf_dims(pool, head_dim):
    """``head_dim`` shaped like ``pool``'s pytree, for ``jax.tree.map``
    beside it: a scales leaf has no head-size axis (0)."""
    return (head_dim, 0) if isinstance(pool, tuple) else head_dim


def take_blocks(pool, idx):
    """Blocks ``idx`` (nb,) of every layer of one pool leaf,
    (L, nb, B, X) -- a layer at a time, as every program reads the pool
    (asked for as one gather along the block axis of the whole leaf,
    the TPU compiler first slices the leaf into a temporary of its own
    size)."""
    return jax.lax.map(lambda li: pool[li, idx],
                       jnp.arange(pool.shape[0]))


def row_to_blocks(row, block):
    """Cache rows ``(L, R, H_kv, W[, D])`` as pool blocks
    ``(L, R·W/B, B, H_kv[·D])``: row r's block j is entry
    ``r·W/B + j``."""
    n_l, r, h, w = row.shape[:4]
    b = row.reshape(n_l, r, h, w // block, block, -1)
    b = b.transpose(0, 1, 3, 4, 2, 5)            # (L, R, nb, B, H, D|1)
    return b.reshape(n_l, r * (w // block), block, -1)


def blocks_to_row(blocks, head_dim):
    """Pool blocks ``(L, nb, B, H_kv[·D])`` laid end to end as one cache
    row ``(L, H_kv, nb·B[, D])``; ``head_dim`` 0 for a scales leaf."""
    n_l, nb, b, x = blocks.shape
    d = head_dim or 1
    r = blocks.reshape(n_l, nb, b, x // d, d).transpose(0, 3, 1, 2, 4)
    r = r.reshape(n_l, x // d, nb * b, d)
    return r if head_dim else r[..., 0]


def paged_attn(q, pool_k, pool_v, layer, tbl, p_limit, n_blk, block,
               trash, k_cur, v_cur, cur_mask, scale, window=None,
               blk_lo=None):
    """Online-softmax attention of ``q`` (n_kv, g, Q, d) against one
    slot's paged KV: layer ``layer`` (static or traced) of the whole
    pools, lanes at positions < ``p_limit`` (blocks ``tbl[0:n_blk]``;
    trash lanes masked), plus the current chunk's keys
    ``k_cur``/``v_cur`` -- rows ``(Q_k, n_kv·d)`` as the pool stores
    them, ``(values, scales (Q_k, n_kv))`` on int8 pools -- under
    ``cur_mask`` (Q, Q_k), the chunk's own causal mask.  The loop reads
    the slab ``pool[layer, blk]`` and views it as ``(B, n_kv, d)``; no
    layer is sliced out of the pool.  Accumulates in f32; returns
    (n_kv, g, Q, d).

    ``window`` (static): sliding-window band — query i (at position
    ``p_limit + i``) additionally masks pool lanes at positions
    <= p_limit + i - window, matching the banded prefill/_block_decode
    semantics on a LINEAR layout.  ``blk_lo`` (traced, default 0):
    loop start — any value <= the first block holding an in-window
    lane (the pool-step wrapper passes the min over live slots, so a
    windowed long chat pays O(window / block) loop iterations instead
    of O(pos / block); out-of-window blocks the engine already
    dropped to the free list sit below it as trash-table entries, so
    correctness never depends on the bound — only work does)."""
    quant = isinstance(pool_k, tuple)
    qf = q.astype(jnp.float32)
    n_kv, g, nq, d = qf.shape
    m0 = jnp.full((n_kv, g, nq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_kv, g, nq), jnp.float32)
    a0 = jnp.zeros((n_kv, g, nq, d), jnp.float32)

    def split(rows):
        """Rows ``(n, n_kv·d)`` [with scales ``(n, n_kv)``] ->
        (f32 ``(n, n_kv, d)``, scales ``(n_kv, 1, 1, n)`` or None)."""
        vals, sc = rows if quant else (rows, None)
        vals = vals.reshape(vals.shape[0], n_kv, d).astype(jnp.float32)
        return vals, None if sc is None else sc.T[:, None, None, :]

    def update(carry, k_rows, v_rows, live):
        m, l, acc = carry
        kb, ksc = split(k_rows)
        vb, vsc = split(v_rows)
        sc = jnp.einsum("kgqd,bkd->kgqb", qf, kb)
        sc = sc * scale if ksc is None else sc * ksc * scale
        sc = jnp.where(live, sc, NEG_INF)
        m2 = jnp.maximum(m, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m - m2)
        pr = jnp.exp(sc - m2[..., None])
        # explicit zero, not just NEG_INF scores: a fully-masked block
        # leaves m2 at NEG_INF and exp(NEG_INF - NEG_INF) would be 1
        pr = jnp.where(live, pr, 0.0)
        l2 = l * alpha + jnp.sum(pr, axis=-1)
        if vsc is not None:
            pr = pr * vsc
        upd = jnp.einsum("kgqb,bkd->kgqd", pr, vb)
        return m2, l2, acc * alpha[..., None] + upd

    def body(j, carry):
        blk = tbl[j]
        at = jax.tree.map(lambda p: p[layer, blk], (pool_k, pool_v))
        lane = j * block + jnp.arange(block)
        live = (lane < p_limit) & (blk != trash)         # (B,)
        if window is not None:
            qpos = p_limit + jnp.arange(nq)              # (Q,)
            live = (live[None, :]
                    & (lane[None, :] > qpos[:, None] - window))
            live = live[None, None]                      # (1,1,Q,B)
        else:
            live = live[None, None, None, :]
        return update(carry, *at, live)

    lo = jnp.int32(0) if blk_lo is None else blk_lo
    carry = jax.lax.fori_loop(lo, n_blk, body, (m0, l0, a0))
    # the chunk's own keys — computed this step, not yet in the pool
    m, l, acc = update(carry, k_cur, v_cur, cur_mask[None, None])
    return acc / l[..., None]


def write_rows(pool, layer, new, tables, pos, live, block, trash):
    """Lay each lane's new rows into one leaf of a whole pool, in place
    where the pool is donated or carried through a loop: ``new``
    (W, Q, X) goes to rows ``pos[w] % block`` onward of block
    ``tables[w, pos[w] // block]`` of layer ``layer``, running on into
    the next block of the table where they pass its end (``Q <=
    block``: two blocks at most); a dead lane writes the trash block.
    Whole blocks are read, changed and scattered back (not single rows:
    a scatter whose window is a row makes the compiler re-lay the whole
    pool twice a step), so every other row of a block stays a byte
    copy."""
    lanes = jnp.arange(pos.shape[0])
    b0, off = pos // block, pos % block
    dst0 = jnp.where(live, tables[lanes, b0], trash)
    lay = jax.vmap(lambda b, rows, o: jax.lax.dynamic_update_slice(
        b, rows.astype(b.dtype), (o, 0)))
    if new.shape[1] == 1:
        return pool.at[layer, dst0].set(lay(pool[layer, dst0], new, off))
    b1 = (pos + new.shape[1] - 1) // block
    # a chunk inside one block routes the second write to trash, so the
    # two scatters never meet on a real block
    dst1 = jnp.where(live & (b1 > b0), tables[lanes, b1], trash)
    dbl = lay(jnp.concatenate([pool[layer, dst0], pool[layer, dst1]],
                              axis=1), new, off)
    pool = pool.at[layer, dst0].set(dbl[:, :block])
    return pool.at[layer, dst1].set(dbl[:, block:])


def rotary(x, pos, theta):
    """Rotary position embedding over the whole last axis of ``x``
    (..., Q, d) at positions ``pos`` (Q,), in the half-split layout:
    dims ``i`` and ``i + d/2`` turn together by ``pos * theta**(-2i/d)``.
    Computed in float32, returned in ``x``'s dtype."""
    d = x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # (Q, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)
