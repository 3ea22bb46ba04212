"""Attention of one sequence against a block-paged K/V pool, and the
write of its new keys and values: the half of a decode or chunk block
that every served family shares (serve/paged.py's pool, the engine's
block tables).  What differs between families happens before the call:
the projections, a rotation of q and k (:func:`rotary`), how many query
heads share a K/V head (read off ``q``'s shape).

This module alone knows how a pool is stored (:func:`pool_zeros`): a
token a row, ``(L, N+1, B, H_kv·D)``.  A family whose cache is ONE row a
position (a latent that is key and value at once: ``ServedFamily.
value_leaf`` false) has no second pool: ``pool_v``, ``vc_row`` and
``v_cur`` are ``None`` wherever they are passed -- an empty pytree, so
every tree-mapped copy carries it along -- and :func:`paged_attn` takes
the value from the first ``v_dim`` values of the key row.  Every program reaches into it the
same way -- the block loop reads the slab ``pool[layer, blk]``, writers
read whole blocks, change rows and scatter the blocks back at
``pool.at[layer, dst]`` -- and a prefill row ``(L, 1, H_kv, W, D)``
becomes blocks, and blocks a row, in :func:`row_to_blocks` /
:func:`blocks_to_row`.  With rows that fill whole 128-lane tiles and
that access pattern the compiler updates a donated pool where it lies;
with a head's 64 values as the row it laid the whole pool out again in
every program that scattered into it (tests/test_tpu_compile.py).

A decode step's attention -- one query token a lane -- goes through
:func:`paged_decode_attn`, one call for all lanes (GPT-2, ``mla_moe``,
the full layers of ``swa_moe``; ``falcon_h1`` still calls
:func:`paged_attn` a lane itself), and
:func:`decode_attn_impl`, a pure function of the operands' shapes, says
what that call runs.  The Pallas kernel (ops/pallas/paged_attention.py:
the pool read where it lies, a lane walking its own blocks, rows
contracted as stored) takes bf16 pools of whole tiles in an unsharded
program on a TPU.  :func:`paged_attn` under ``jax.vmap`` -- every lane to
the longest lane's bound -- keeps everything else: Q > 1 (chunk rows, a
speculative verify chunk, :func:`ring_chunk_attn`), int8 pools,
``window=`` / ``blk_lo``, a sharded program (``tp_axis``), any backend
that is not a TPU.  The parity contract is one: the kernel is held to
``vmap(paged_attn)`` up to float reordering
(tests/test_paged_decode_kernel.py), and ``paged_attn`` to the row math
(token streams identical, logits allclose: tests/test_paged.py).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from .pallas import paged_attention as _pallas

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
    beside it: a scales leaf has no head-size axis (0); no leaf at all
    (the absent value pool of a one-leaf cache) has none."""
    if pool is None:
        return None
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
               blk_lo=None, v_dim=None):
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

    ``pool_v`` and ``v_cur`` None (a one-leaf cache): a position's value
    is the first ``v_dim`` values of its key row, so each block is read
    once and serves both contractions; returns (n_kv, g, Q, v_dim).

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
    a0 = jnp.zeros((n_kv, g, nq, d if pool_v is not None else v_dim),
                   jnp.float32)

    def split(rows):
        """Rows ``(n, n_kv·d)`` [with scales ``(n, n_kv)``] ->
        (f32 ``(n, n_kv, d)``, scales ``(n_kv, 1, 1, n)`` or None)."""
        vals, sc = rows if quant else (rows, None)
        vals = vals.reshape(vals.shape[0], n_kv, d).astype(jnp.float32)
        return vals, None if sc is None else sc.T[:, None, None, :]

    def update(carry, k_rows, v_rows, live):
        m, l, acc = carry
        kb, ksc = split(k_rows)
        vb, vsc = (kb[..., :v_dim], None) if v_rows is None \
            else split(v_rows)
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


def _varies(a):
    """Does ``a`` (array, tracer or ShapeDtypeStruct) differ between the
    shards of a ``shard_map`` it is traced in?"""
    return bool(getattr(a, "vma", None)
                or getattr(getattr(a, "aval", None), "vma", None))


def decode_attn_impl(q, pool_k, *, window=None, blk_lo=None, tp_axis=None,
                     backend=None):
    """``"kernel"`` or ``"loop"``: which of the two
    :func:`paged_decode_attn` runs for these operands -- arrays or
    ``jax.ShapeDtypeStruct``s, only shapes and dtypes are read.  ``q`` is
    lane-batched: (W, n_kv, g, d) a token a lane, or (W, n_kv, g, Q, d).

    The kernel (ops/pallas/paged_attention.py) takes one query token a
    lane against a bf16 pool whose rows and blocks fill whole tiles, in
    an unsharded program on a TPU.  The loop keeps: Q > 1 (chunk rows, a
    speculative verify chunk), int8 pools (``pool_k`` a tuple),
    ``window=`` / ``blk_lo`` (banded serving), ``tp_axis`` or operands
    that vary over a mesh axis (a pool sharded by head or by layer), any
    other backend.  ``backend`` defaults to ``jax.default_backend()``."""
    if backend is None:
        backend = jax.default_backend()
    nq = 1 if len(q.shape) == 4 else q.shape[3]
    if (backend != "tpu" or nq != 1 or isinstance(pool_k, tuple)
            or window is not None or blk_lo is not None
            or tp_axis is not None or _varies(q) or _varies(pool_k)):
        return "loop"
    block, x = pool_k.shape[2:]
    tiles = (x == q.shape[1] * q.shape[-1] and x % 128 == 0
             and block % 16 == 0)
    return "kernel" if tiles and pool_k.dtype == jnp.bfloat16 else "loop"


def paged_decode_attn(q, pool_k, pool_v, layer, tables, pos, block, trash,
                      k_cur, v_cur, scale, *, v_dim=None, cur_mask=None,
                      window=None, blk_lo=None, n_blk=None, tp_axis=None):
    """Every lane's attention of a decode step in one call: ``q``
    (W, n_kv, g, d), one token a lane at position ``pos[w]`` (W,), over
    the lane's blocks ``tables[w]`` (W, T) of layer ``layer`` (static, or
    traced inside a layer scan) of the whole pools, plus the lanes' new
    rows ``k_cur``/``v_cur`` (W, H_kv·D), which are not in the pool yet.
    ``pool_v`` and ``v_cur`` None with ``v_dim``: the one-leaf latent
    cache.  Returns (W, n_kv, g, d | v_dim) float32; a dead lane
    (position 0, a trash table) attends its own new row and nothing
    else, and no lane yields a NaN.

    :func:`decode_attn_impl` chooses by the operands alone, never by a
    flag: the Pallas kernel, in which a lane walks ITS OWN blocks and K/V
    rows are read once as stored; or ``jax.vmap`` over
    :func:`paged_attn`, the loop every lane walks to ``n_blk`` (default:
    the longest lane's blocks).  The two agree up to float reordering
    (tests/test_paged_decode_kernel.py).  What only the loop takes goes
    through as well: ``q`` (W, n_kv, g, Q, d) with rows (W, Q, H_kv·D)
    and the chunk's ``cur_mask`` (Q, Q); int8 pools and rows as
    ``(values, scales)``; ``window=`` / ``blk_lo``; then the result has
    ``q``'s rank."""
    one = q.ndim == 4                      # no Q axis: give it one
    if one:
        q = q[:, :, :, None]
        k_cur, v_cur = jax.tree.map(lambda r: r[:, None], (k_cur, v_cur))
    if decode_attn_impl(q, pool_k, window=window, blk_lo=blk_lo,
                        tp_axis=tp_axis) == "kernel":
        k_cur, v_cur = jax.tree.map(lambda r: r[:, 0], (k_cur, v_cur))
        out = _pallas.paged_decode_attn(
            q[:, :, :, 0], pool_k, pool_v, layer, tables, pos, block,
            trash, k_cur, v_cur, scale, v_dim=v_dim)[:, :, :, None]
    else:
        if cur_mask is None:
            cur_mask = jnp.tril(jnp.ones((q.shape[3],) * 2, bool))
        if n_blk is None:
            n_blk = jnp.max((pos + block - 1) // block)
        out = jax.vmap(
            lambda q_r, k_r, v_r, tbl, pos_r: paged_attn(
                q_r, pool_k, pool_v, layer, tbl, pos_r, n_blk, block,
                trash, k_r, v_r, cur_mask, scale, window=window,
                blk_lo=blk_lo, v_dim=v_dim))(q, k_cur, v_cur, tables, pos)
    return out[:, :, :, 0] if one else out


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


def yarn_frequencies(dim, theta, factor, beta_fast, beta_slow,
                     original_max):
    """The ``dim / 2`` rotary frequencies under YaRN scaling: frequency
    ``i`` is ``f_i = theta**(-2i/dim)`` where a dim turns more than
    ``beta_fast`` times in ``original_max`` positions, ``f_i / factor``
    where it turns fewer than ``beta_slow`` times, and the linear blend
    of the two between those dims.  A numpy vector (a constant of the
    program), for :func:`rotary`'s ``theta``."""
    def turns_at(n_rot):                  # the dim that turns n_rot times
        return dim * math.log(original_max / (n_rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    f = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def rotary(x, pos, theta):
    """Rotary position embedding over the whole last axis of ``x``
    (..., Q, d) at positions ``pos`` (Q,), in the half-split layout:
    dims ``i`` and ``i + d/2`` turn together by ``pos * theta**(-2i/d)``
    -- or, with ``theta`` a vector of ``d/2`` frequencies
    (:func:`yarn_frequencies`), by ``pos * theta[i]``.  Computed in
    float32, returned in ``x``'s dtype."""
    d = x.shape[-1]
    if jnp.ndim(theta):
        inv = jnp.asarray(theta, jnp.float32)
    else:
        inv = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # (Q, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)



# -- window layers: a ring a slot ---------------------------------------------
# A layer that attends the last ``window`` positions only keeps them in a
# RING of ``ring >= window`` rows a slot (position ``p`` at row ``p %
# ring``), not in the pool: its memory and its decode work are O(window)
# whatever the sequence's length, and whatever the other lanes' lengths
# (models/swa_moe.py; the rings live in the engine's per-slot state
# arenas).  Rows are stored as the pool stores them: ``(ring, H_kv·D)``.


def ring_chunk_attn(q, ring_k, ring_v, layer, off, block, row_blocks,
                    k_cur, v_cur, scale, window):
    """Banded attention of a chunk's queries ``q`` (n_kv, g, C, d) at
    positions ``off + [0, C)``: over one slot's ring of layer ``layer``
    -- ``ring_k``/``ring_v`` (J, ring, H_kv·D), holding positions
    ``[off - ring, off)`` -- and over the chunk's own rows
    ``k_cur``/``v_cur`` (C, H_kv·D), which are NOT in the ring yet
    (:func:`ring_write_chunk` lays them in afterwards).  The ring is
    walked as blocks of ``block`` rows by the shared loop
    (:func:`paged_attn` with its band mask): block ``j`` of the
    sequence lies in ring block ``j % (ring / block)`` -- the table, of
    ``row_blocks`` entries (the sequence's most) -- and the walk starts
    at the first block that holds an in-window row: O(window / block)
    iterations wherever ``off`` is.  ``block`` divides the ring and need
    not divide ``off``: of the walk's last block the rows from ``off`` on
    are masked as not yet written, and of its first those that a later
    turn of the ring has overwritten are masked as out of the band."""
    n_j, ring, x = ring_k.shape
    c = q.shape[2]
    if ring % block or ring < window or c > ring:
        raise ValueError(
            f"a ring of {ring} rows needs whole blocks of {block}, at "
            f"least the window ({window}) and the launch ({c} tokens)")
    rb = ring // block
    i = jnp.arange(c)
    cur = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < window)
    return paged_attn(
        q, ring_k.reshape(n_j, rb, block, x),
        ring_v.reshape(n_j, rb, block, x), layer,
        jnp.arange(row_blocks) % rb, off, -(-off // block), block, -1,
        k_cur, v_cur, cur, scale, window=window,
        blk_lo=jnp.maximum(0, (off - window + 1) // block))


def ring_write_chunk(ring, layer, rows, off, n_valid, block):
    """Lay a chunk's rows ``rows`` (C, X) at positions ``off + [0, C)``
    into layer ``layer`` of one slot's ring (J, ring, X), whole blocks
    at a time (``off`` a multiple of ``block``; a chunk that passes the
    ring's end runs on at its start).  Rows from ``n_valid`` on are
    padding after the prompt's end and leave the ring as it was: the
    positions they would overwrite are still inside the window."""
    n_j, n_ring, x = ring.shape
    c, rb = rows.shape[0], n_ring // block
    blocks = ring.reshape(n_j, rb, block, x)
    idx = (off // block + jnp.arange(c // block)) % rb
    new = jnp.where((jnp.arange(c) < n_valid).reshape(-1, block, 1),
                    rows.reshape(-1, block, x).astype(ring.dtype),
                    blocks[layer, idx])
    return blocks.at[layer, idx].set(new).reshape(ring.shape)


def ring_decode_attn(q, k_new, v_new, arena_k, arena_v, at, slots, pos,
                     scale, window):
    """One token a lane against its ring, and the write of its new row:
    ``q`` (W, n_kv, g, d) at positions ``pos`` (W,); ``k_new``/``v_new``
    (W, H_kv·D) the lanes' new rows; the arenas ``(P, S + 1, J, ring,
    H_kv·D)`` hold slot ``slots[w]``'s ring of this layer at ``[at[0],
    slots[w], at[1]]`` (a dead lane: the trash row, position 0).

    A lane at a time, in a loop whose bound is the number of lanes: the
    lane's ring is sliced out where it lies (a gather of the lanes' rings
    makes the compiler slice the whole arena first), its rows inside the
    band ``(pos - window, pos)`` and the new row are attended, and the
    new row is written at ``pos % ring``.  The work of a lane is its
    ring's, whatever its own position and whatever the others'.
    Returns ``(out (W, n_kv, g, d) float32, arena_k, arena_v)``."""
    n_w, n_kv, g, d = q.shape
    ring, x = arena_k.shape[-2:]
    r = jnp.arange(ring)
    f32 = jnp.float32
    own = jnp.eye(n_kv, dtype=q.dtype)       # a query head's K/V head

    def lane(i, carry):
        a_k, a_v, out = carry
        p, here = pos[i], (at[0], slots[i], at[1])
        one = lambda a: jax.lax.dynamic_slice(
            a, here + (0, 0), (1, 1, 1, ring, x)).reshape(ring, x)
        kb, vb = one(a_k), one(a_v)
        # row r holds the newest position below p that is r modulo ring
        held = p - 1 - (p - 1 - r) % ring
        vis = (held >= 0) & (held > p - window)                 # (ring,)
        # every query head against the rows AS STORED, all K/V heads side
        # by side: a head's query sits in its own K/V head's columns and
        # zeros in the others', so one matmul scores all heads and one
        # weighs the values (viewed as (ring, n_kv, d) instead, the
        # compiler re-laid the whole arena, transposed, every step; the
        # zeros cost a few times the operations of an attention that is
        # bound by reading the ring)
        qx = jnp.einsum("kgd,kj->kgjd", q[i], own).reshape(n_kv * g, x)
        sc = jnp.einsum("hx,rx->hr", qx, kb,
                        preferred_element_type=f32) * scale
        sc = jnp.where(vis, sc, NEG_INF)
        s_cur = jnp.einsum("hx,x->h", qx, k_new[i],
                           preferred_element_type=f32) * scale
        m = jnp.maximum(jnp.max(sc, axis=-1), s_cur)
        pr = jnp.where(vis, jnp.exp(sc - m[:, None]), 0.0)
        p_cur = jnp.exp(s_cur - m)
        o = jnp.einsum("hr,rx->hx", pr.astype(vb.dtype), vb,
                       preferred_element_type=f32) \
            + p_cur[:, None] * v_new[i].astype(f32)[None, :]
        o = o / (jnp.sum(pr, axis=-1) + p_cur)[:, None]
        # ... of which a head keeps its own K/V head's columns
        o = jnp.einsum("kgjd,kj->kgd", o.reshape(n_kv, g, n_kv, d),
                       own.astype(f32))
        put = lambda a, row: jax.lax.dynamic_update_slice(
            a, row.reshape(1, 1, 1, 1, x).astype(a.dtype),
            here + (p % ring, 0))
        return (put(a_k, k_new[i]), put(a_v, v_new[i]),
                jax.lax.dynamic_update_slice(out, o[None], (i, 0, 0, 0)))

    a_k, a_v, out = jax.lax.fori_loop(
        0, n_w, lane, (arena_k, arena_v,
                       jnp.zeros((n_w, n_kv, g, d), f32)))
    return out, a_k, a_v
