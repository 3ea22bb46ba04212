"""Attention of one sequence against a block-paged K/V pool, and the
write of its new keys and values: the half of a decode or chunk block
that every served family shares (serve/paged.py's pool, the engine's
block tables).  What differs between families happens before the call:
the projections, a rotation of q and k (:func:`rotary`), how many query
heads share a K/V head (read off ``q``'s shape).
"""

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_attn(q, pool_k_l, pool_v_l, tbl, p_limit, n_blk, block,
               trash, k_cur, v_cur, cur_mask, scale, window=None,
               blk_lo=None, layer=None):
    """Online-softmax attention of ``q`` (n_kv, g, Q, d) against one
    slot's paged KV: pool lanes at positions < ``p_limit`` (blocks
    ``tbl[0:n_blk]``; trash lanes masked) plus the current chunk's
    keys ``k_cur``/``v_cur`` (n_kv, Q_k, d, quantized tuples on int8
    pools) under ``cur_mask`` (Q, Q_k) — the chunk's own causal mask.
    Accumulates in f32; returns (n_kv, g, Q, d).

    ``window`` (static): sliding-window band — query i (at position
    ``p_limit + i``) additionally masks pool lanes at positions
    <= p_limit + i - window, matching the banded prefill/_block_decode
    semantics on a LINEAR layout.  ``blk_lo`` (traced, default 0):
    loop start — any value <= the first block holding an in-window
    lane (the pool-step wrapper passes the min over live slots, so a
    windowed long chat pays O(window / block) loop iterations instead
    of O(pos / block); out-of-window blocks the engine already
    dropped to the free list sit below it as trash-table entries, so
    correctness never depends on the bound — only work does).

    ``layer`` (traced, dense pools only): the pools are the whole
    ``(L, N+1, H_kv, B, D)`` arrays and the loop reads block
    ``[layer, blk]`` — for a caller that scans its layers and must not
    slice a layer out of the pool (a copy of it) each time round."""
    quant = isinstance(pool_k_l, tuple)
    qf = q.astype(jnp.float32)
    n_kv, g, nq, d = qf.shape
    m0 = jnp.full((n_kv, g, nq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_kv, g, nq), jnp.float32)
    a0 = jnp.zeros((n_kv, g, nq, d), jnp.float32)

    def update(carry, sc, live, vb, vsc):
        m, l, acc = carry
        sc = jnp.where(live, sc, NEG_INF)
        m2 = jnp.maximum(m, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m - m2)
        pr = jnp.exp(sc - m2[..., None])
        # explicit zero, not just NEG_INF scores: a fully-masked block
        # leaves m2 at NEG_INF and exp(NEG_INF - NEG_INF) would be 1
        pr = jnp.where(live, pr, 0.0)
        l2 = l * alpha + jnp.sum(pr, axis=-1)
        if vsc is not None:
            pr = pr * vsc[:, None, None, :]
        upd = jnp.einsum("kgqb,kbd->kgqd", pr, vb.astype(jnp.float32))
        return m2, l2, acc * alpha[..., None] + upd

    def body(j, carry):
        blk = tbl[j]
        if quant:
            kb, ksc = pool_k_l[0][blk], pool_k_l[1][blk]
            vb, vsc = pool_v_l[0][blk], pool_v_l[1][blk]
            sc = jnp.einsum("kgqd,kbd->kgqb", qf,
                            kb.astype(jnp.float32))
            sc = sc * ksc[:, None, None, :] * scale
        else:
            at = blk if layer is None else (layer, blk)
            kb, vb, vsc = pool_k_l[at], pool_v_l[at], None
            sc = jnp.einsum("kgqd,kbd->kgqb", qf,
                            kb.astype(jnp.float32)) * scale
        lane = j * block + jnp.arange(block)
        live = (lane < p_limit) & (blk != trash)         # (B,)
        if window is not None:
            qpos = p_limit + jnp.arange(nq)              # (Q,)
            live = (live[None, :]
                    & (lane[None, :] > qpos[:, None] - window))
            live = live[None, None]                      # (1,1,Q,B)
        else:
            live = live[None, None, None, :]
        return update(carry, sc, live, vb, vsc)

    lo = jnp.int32(0) if blk_lo is None else blk_lo
    carry = jax.lax.fori_loop(lo, n_blk, body, (m0, l0, a0))
    # the chunk's own keys — computed this step, not yet in the pool
    if quant:
        (kc, kcs), (vc, vcs) = k_cur, v_cur
        sc = jnp.einsum("kgqd,kbd->kgqb", qf, kc.astype(jnp.float32))
        sc = sc * kcs[:, None, None, :] * scale
    else:
        kc, vc, vcs = k_cur, v_cur, None
        sc = jnp.einsum("kgqd,kbd->kgqb", qf,
                        kc.astype(jnp.float32)) * scale
    m, l, acc = update(carry, sc, cur_mask[None, None], vc, vcs)
    return acc / l[..., None]




def write_block(pool_l, new, tbl, pos, block):
    """The block of one layer's pool leaf that holds position ``pos``,
    with the rows ``new`` (H_kv, Q, ...) laid in from ``pos % block``
    on and every other lane a byte copy: what a per-slot step hands
    back for the caller to scatter at ``tbl[pos // block]``."""
    b = pool_l[tbl[pos // block]]
    start = (0, pos % block) + (0,) * (b.ndim - 2)
    return jax.lax.dynamic_update_slice(b, new, start)


def write_rows(pool, layer, new, tables, pos, live, block, trash):
    """Lay one new row a lane into a whole dense pool, in place where
    the pool is donated or carried through a loop: ``new`` (W, H_kv, D)
    goes to ``pool[layer, tables[w, pos[w] // block], :, pos[w] %
    block]``; a dead lane writes the trash block.  Whole blocks are
    read, changed and scattered back (not single rows: a scatter whose
    window is a row makes the compiler re-lay the whole pool twice a
    step)."""
    lanes = jnp.arange(pos.shape[0])
    dst = jnp.where(live, tables[lanes, pos // block], trash)
    blocks = jax.vmap(
        lambda b, row, off: jax.lax.dynamic_update_slice(
            b, row[:, None].astype(b.dtype), (0, off, 0)))(
                pool[layer, dst], new, pos % block)
    return pool.at[layer, dst].set(blocks)


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
