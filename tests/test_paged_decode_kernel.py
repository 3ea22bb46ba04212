"""The decode-attention kernel (ops/pallas/paged_attention.py) against
the block loop it stands in for, ``jax.vmap`` over ``paged_attn``, at
small sizes of the four served geometries; and the cases that stay on
the loop.  On the CPU the kernel body runs under ``interpret=True``
(the kernel function's own private argument); the same file run on a
TPU compiles it with Mosaic: that run is the on-chip parity."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.ops import paged_attention as pa
from singa_tpu.ops.pallas import paged_attention as kernel

ON_TPU = jax.default_backend() == "tpu"

# name -> (n_kv, g, d, block, v_dim): GPT-2 (d = 64, a query head a K/V
# head); the hybrid family and the window/full family (d = 128, grouped
# queries); the latent cache (ONE leaf, one wide head: a row is 96
# values and 32 zeros, the value its first 64 columns)
GEOMETRIES = {
    "kv4_g1_d64": (4, 1, 64, 32, None),
    "kv2_g5_d128": (2, 5, 128, 16, None),
    "kv2_g8_d128": (2, 8, 128, 128, None),
    "latent_g16_d128_v64": (1, 16, 128, 32, 64),
}
# how the lanes of one call lie in the pool
LANES = ("a dead lane on the trash table", "position 0", "whole blocks",
         "one row into a new block", "a table in scattered pool order",
         "a few rows", "a full table")
SCAN = "layer traced inside a scan"
LAYERS, BLOCKS, TABLE = 3, 14, 5


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """At these sizes a loop iteration of the kernel takes 32 KB of rows,
    not its half megabyte: a table of five blocks is then one to three
    iterations (1, 2 or 4 blocks each), so the copies in flight pass from
    an iteration to the next, and from a lane to the next, here too."""
    monkeypatch.setattr(kernel, "_CHUNK_BYTES", 32 * 1024)


def _operands(geometry, q_dtype, seed=0):
    n_kv, g, d, block, v_dim = GEOMETRIES[geometry]
    rng = np.random.default_rng(seed)
    x, trash = n_kv * d, BLOCKS
    rows = lambda *lead: jnp.asarray(
        rng.normal(size=lead + (x,)), jnp.bfloat16)
    if v_dim:       # the stored row: values, a rotated key, zeros
        pad = np.ones(x, np.float32)
        pad[x - 32:] = 0.0
        pool_k = rows(LAYERS, BLOCKS + 1, block) * jnp.asarray(
            pad, jnp.bfloat16)
        pool_v = None
    else:
        pool_k = rows(LAYERS, BLOCKS + 1, block)
        pool_v = rows(LAYERS, BLOCKS + 1, block)
    pos = np.array([0, 0, 2 * block, 2 * block + 1, 3 * block + 7, 5,
                    TABLE * block - 1], np.int32)
    tables = np.full((len(pos), TABLE), trash, np.int32)
    for w, p in enumerate(pos):
        held = -(-int(p) // block)
        # pool order scattered, and blocks shared between lanes: the
        # attention only reads
        tables[w, :held] = rng.choice(BLOCKS, size=held, replace=False)
    q = jnp.asarray(rng.normal(size=(len(pos), n_kv, g, d)), q_dtype)
    k_cur = rows(len(pos))
    v_cur = None if v_dim else rows(len(pos))
    return dict(q=q, pool_k=pool_k, pool_v=pool_v,
                tables=jnp.asarray(tables), pos=jnp.asarray(pos),
                block=block, trash=trash, k_cur=k_cur, v_cur=v_cur,
                scale=1.0 / math.sqrt(d), v_dim=v_dim)


def _loop(o, layer, **kw):
    """``vmap(paged_attn)`` at one token a lane: the reference."""
    n_blk = jnp.max((o["pos"] + o["block"] - 1) // o["block"])
    one = jnp.ones((1, 1), bool)

    def lane(q_r, k_r, v_r, tbl, pos_r):
        return pa.paged_attn(
            q_r[:, :, None], o["pool_k"], o["pool_v"], layer, tbl, pos_r,
            n_blk, o["block"], o["trash"], k_r[None],
            None if v_r is None else v_r[None], one, o["scale"],
            v_dim=o["v_dim"], **kw)[:, :, 0]

    return jax.vmap(lane)(o["q"], o["k_cur"], o["v_cur"], o["tables"],
                          o["pos"])


def _kernel(o, layer):
    return kernel.paged_decode_attn(
        o["q"], o["pool_k"], o["pool_v"], layer, o["tables"], o["pos"],
        o["block"], o["trash"], o["k_cur"], o["v_cur"], o["scale"],
        v_dim=o["v_dim"], _interpret=not ON_TPU)


@functools.lru_cache(maxsize=None)
def _both(geometry, q_dtype, scanned):
    """(kernel, loop) results of one call with every lane of
    :data:`LANES`: layer 1, or every layer as a scan's counter (as in
    each family's decode step: it reaches the kernel as a prefetched
    scalar)."""
    o = _operands(geometry, jnp.dtype(q_dtype), seed=int(scanned))

    @jax.jit
    def run():
        if not scanned:
            return _kernel(o, 1), _loop(o, 1)
        return jax.lax.scan(
            lambda _, li: (None, (_kernel(o, li), _loop(o, li))), None,
            jnp.arange(LAYERS))[1]

    return o, *run()


@pytest.mark.parametrize("case", LANES + (SCAN,))
@pytest.mark.parametrize("q_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_kernel_is_the_loop_up_to_float_order(geometry, q_dtype, case):
    """The kernel's result for a lane is ``vmap(paged_attn)``'s, no NaN,
    and a lane with nothing in the pool attends its own new row alone."""
    o, got, want = _both(geometry, q_dtype, case == SCAN)
    assert got.shape == want.shape and got.dtype == jnp.float32
    # the loop's own matmuls round their float32 operands on a TPU
    tol = 2e-2 if ON_TPU else 2e-6
    if case == SCAN:
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
        assert not np.allclose(got[0], got[1])      # the layers differ
        return
    w = LANES.index(case)
    assert bool(jnp.all(jnp.isfinite(got[w])))
    np.testing.assert_allclose(got[w], want[w], rtol=tol, atol=tol)
    if w < 2:
        v_dim = o["v_dim"]
        own = o["k_cur"][w, :v_dim] if v_dim else o["v_cur"][w]
        own = own.astype(jnp.float32).reshape(got.shape[1], 1, -1)
        np.testing.assert_allclose(
            got[w], jnp.broadcast_to(own, got[w].shape), rtol=1e-6)


def _entry(o, q, k_cur, v_cur, pool_k=None, pool_v=None, **kw):
    return pa.paged_decode_attn(
        q, pool_k if pool_k is not None else o["pool_k"],
        pool_v if pool_v is not None else o["pool_v"], 1, o["tables"],
        o["pos"], o["block"], o["trash"], k_cur, v_cur, o["scale"], **kw)


@pytest.mark.parametrize("reason", ["Q = 2", "an int8 pool", "window="])
def test_what_stays_on_the_loop(reason):
    """Each reason to keep the loop, judged for a TPU: the rule says
    ``"loop"`` and the entry's result is ``vmap(paged_attn)``'s to the
    bit."""
    o = _operands("kv4_g1_d64", jnp.bfloat16, seed=2)
    n_kv, _, d, block, _ = GEOMETRIES["kv4_g1_d64"]
    n_blk = jnp.max((o["pos"] + block - 1) // block)
    q, k_cur, v_cur = o["q"], o["k_cur"], o["v_cur"]
    pools, kw, cur = (o["pool_k"], o["pool_v"]), {}, jnp.ones((1, 1), bool)
    if reason == "Q = 2":
        q = jnp.stack([q, q[::-1]], axis=3)           # (W, n_kv, g, 2, d)
        k_cur = jnp.stack([k_cur, k_cur[::-1]], axis=1)
        v_cur = jnp.stack([v_cur, v_cur[::-1]], axis=1)
        cur = jnp.tril(jnp.ones((2, 2), bool))
    elif reason == "an int8 pool":
        def quantize(rows):
            by_head = rows.astype(jnp.float32).reshape(
                rows.shape[:-1] + (n_kv, d))
            sc = jnp.max(jnp.abs(by_head), axis=-1) / 127.0
            q8 = jnp.round(by_head / sc[..., None]).astype(jnp.int8)
            return q8.reshape(rows.shape), sc

        pools = tuple(quantize(p) for p in pools)
        k_cur, v_cur = quantize(k_cur), quantize(v_cur)
    else:
        kw = dict(window=40, blk_lo=jnp.int32(0))
    assert pa.decode_attn_impl(q, pools[0], backend="tpu", **kw) == "loop"
    got = jax.jit(lambda: _entry(o, q, k_cur, v_cur, *pools, **kw))()

    def lane(q_r, k_r, v_r, tbl, pos_r):
        if q.ndim == 4:
            q_r = q_r[:, :, None]
            k_r, v_r = jax.tree.map(lambda r: r[None], (k_r, v_r))
        a = pa.paged_attn(q_r, *pools, 1, tbl, pos_r, n_blk, block,
                          o["trash"], k_r, v_r, cur, o["scale"], **kw)
        return a if q.ndim == 5 else a[:, :, 0]

    want = jax.jit(lambda: jax.vmap(lane)(q, k_cur, v_cur, o["tables"],
                                          o["pos"]))()
    np.testing.assert_array_equal(got, want)


def test_the_entry_runs_what_the_rule_says():
    """One token a lane through the entry: the kernel on a TPU, the loop
    (to the bit) anywhere else -- the rule decides, not the caller."""
    o = _operands("kv2_g5_d128", jnp.bfloat16, seed=3)
    impl = pa.decode_attn_impl(o["q"], o["pool_k"])
    assert impl == ("kernel" if ON_TPU else "loop")
    got = jax.jit(lambda: _entry(o, o["q"], o["k_cur"], o["v_cur"]))()
    want = jax.jit(lambda: (_kernel if ON_TPU else _loop)(o, 1))()
    np.testing.assert_array_equal(got, want)


# the decode steps' attention operands in the benchmark's serve
# configurations whose decode step goes through the entry (the hybrid
# family's does not yet: PERF.md section 7): queries (lanes, n_kv, g, d),
# pool (L, N+1, B, X)
BENCHMARK_SHAPES = {
    "gpt2-large": ((12, 20, 1, 64), (36, 562, 32, 1280)),
    "dots-vlm1-inst": ((128, 1, 128, 640), (6, 1921, 128, 640)),
    "trinity-mini": ((24, 4, 8, 128), (8, 801, 128, 512)),
}


@pytest.mark.parametrize("config", sorted(BENCHMARK_SHAPES))
def test_the_benchmarks_decode_steps_take_the_kernel(config):
    """The rule on shapes alone (no device): on a TPU each served
    configuration's decode step runs the kernel, and none does here."""
    q, pool = BENCHMARK_SHAPES[config]
    q = jax.ShapeDtypeStruct(q, jnp.bfloat16)
    says = lambda dtype=jnp.bfloat16, **kw: pa.decode_attn_impl(
        q, jax.ShapeDtypeStruct(pool, dtype), **kw)
    assert says(backend="tpu") == "kernel"
    assert says(backend="cpu") == "loop"
    assert says(backend="tpu", tp_axis="tp") == "loop"
    assert says(jnp.float32, backend="tpu") == "loop"
