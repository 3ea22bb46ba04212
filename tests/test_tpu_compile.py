"""The serve programs compiled at their real widths for a described TPU
v5e -- no chip attached, nothing runs: what the chip's compiler refuses,
and what it would copy or keep beside the arguments, shows here at no
chip time (on-chip-measurement guide, section 2).  The hybrid family at
two layers for six (every program scans one layer body); ``gpt2-large``
at all 36 (its programs unroll them) with the benchmark's pool of 561 +
1 blocks of 32: every program that writes the K/V pool has to update it
where it lies -- no operation copies or re-lays a pool, the aliased
bytes are the two pools' data and nothing more (no padded rows), and
the temporaries stay small.  The topology is described inside a
fixture, never at import.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

L, SLOTS, BLOCKS, WIDTH, BLOCK = 2, 32, 200, 8192, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shapes(one_chip):
    from jax.experimental.compilation_cache import compilation_cache
    from singa_tpu.models.falcon_h1 import (_MATRICES, _VECTORS,
                                            FalconH1Config, FalconH1Family)

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    cfg = FalconH1Config(num_hidden_layers=L, max_len=WIDTH,
                         dtype="bfloat16")

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=one_chip)

    sh = cfg.shapes()
    params = dict(
        wte=sds(sh["wte"]), head=sds(sh["head"]),
        lnf=sds(sh["lnf"], jnp.float32),
        layers={k: sds((L,) + sh[k], jnp.float32 if k in _VECTORS
                       else jnp.bfloat16) for k in _VECTORS + _MATRICES})
    yield cfg, FalconH1Family(cfg), params, sds
    jax.config.update("jax_enable_compilation_cache", was)


def _big_copies(text, floor=50e6):
    out = []
    for dims in re.findall(r"= \w+\[([\d,]+)\][^\n]* copy\(", text):
        n = 1
        for d in dims.split(","):
            n *= int(d)
        if n > floor:
            out.append(dims)
    return out


def test_the_decode_program_compiles_in_place(shapes):
    from singa_tpu.serve import paged

    cfg, fam, params, sds = shapes
    n = SLOTS
    pool = sds((L, BLOCKS + 1, BLOCK, 4 * 128))
    state = {"ssm": sds((L, n + 1, 32, 128, 256), jnp.float32),
             "conv": sds((L, n + 1, 3, cfg.conv_dim), jnp.float32)}
    i32 = lambda *s: sds(s, jnp.int32)
    comp = paged._paged_decode_kernel.lower(
        params, pool, pool, i32(n, WIDTH // BLOCK), i32(n), i32(n),
        sds((n,), jnp.bool_), sds((n, 2), jnp.uint32),
        sds((n,), jnp.float32), sds((), jnp.float32), None, state, i32(n),
        block=BLOCK, n_head=20, eps=1e-5, moe_top_k=2, top_k=0,
        use_top_p=False, window=None, fam=fam).compile()
    ma = comp.memory_analysis()
    # the pool and the state arenas are updated where they lie
    assert ma.alias_size_in_bytes >= 2 * 2 * L * (BLOCKS + 1) * 4 * BLOCK \
        * 128 + 4 * L * (n + 1) * 32 * 128 * 256
    # neither is copied or re-laid whole (a row-wide scatter into the
    # pool once made the compiler re-lay all of it twice a step)
    assert _big_copies(comp.as_text()) == []
    assert ma.temp_size_in_bytes < 1.0e9
    scopes = {}
    paged._keep_scopes("decode", fam.scopes, comp.as_text())
    for s in paged.program_scopes()["decode"].values():
        scopes[s] = scopes.get(s, 0) + 1
    assert {"attn", "ssm_step", "ssm_proj", "mlp", "head"} <= set(scopes)


def test_the_chunk_row_program_compiles(shapes):
    from singa_tpu.serve import engine, paged

    cfg, fam, params, sds = shapes
    row = sds((L, 1, 4, WIDTH, 128))
    state = {"ssm": sds((L, 32, 128, 256), jnp.float32),
             "conv": sds((L, 3, cfg.conv_dim), jnp.float32)}
    comp = engine._chunk_row.lower(
        params, sds((1, WIDTH), jnp.int32), row, row, sds((), jnp.int32),
        state, sds((), jnp.int32), n_head=20, eps=1e-5, moe_top_k=2,
        chunk=BLOCK, window=None, fam=fam).compile()
    assert comp.memory_analysis().temp_size_in_bytes < 0.5e9
    paged._keep_scopes("chunk", fam.scopes, comp.as_text())
    assert "ssm_scan" in set(paged.program_scopes()["chunk"].values())


# ---- gpt2-large: 36 layers, 1280 wide, 20 heads of 64, bf16 ------------

GL, GE, GH, GV, GW, GBLOCKS, GB = 36, 1280, 20, 50257, 1024, 561, 32
POOLS = 2 * GL * (GBLOCKS + 1) * GB * GE * 2     # both pools' data bytes


def _gpt2_params(sds, n_layer, e, vocab=GV):
    """``gpt2_decode.extract_params`` of a dense model: the blocks
    stacked on a layer axis."""
    layer = lambda *shape: sds((n_layer,) + shape)
    blk = dict(ln1_s=layer(e), ln1_b=layer(e), ln2_s=layer(e),
               ln2_b=layer(e), w1=layer(e, 4 * e), b1=layer(4 * e),
               w2=layer(4 * e, e), b2=layer(e))
    for m in "qkvo":
        blk["w" + m], blk["b" + m] = layer(e, e), layer(e)
    return dict(wte=sds((vocab, e)), wpe=sds((GW, e)), blocks=blk,
                lnf_s=sds((e,)), lnf_b=sds((e,)), head=None)


@pytest.fixture(scope="module")
def gpt2l(shapes):
    sds = shapes[3]
    return (sds, _gpt2_params(sds, GL, GE), sds((GL, GBLOCKS + 1, GB, GE)),
            lambda *s: sds(s, jnp.int32))


def _lanes(sds, n):
    """tables, toks, pos, live, keys, temps, top_p of ``n`` lanes."""
    i32 = lambda *s: sds(s, jnp.int32)
    return (i32(n, GW // GB), i32(n), i32(n), sds((n,), jnp.bool_),
            sds((n, 2), jnp.uint32), sds((n,), jnp.float32),
            sds((), jnp.float32))


def _in_place(comp):
    """The program updates both pools where they lie: see the module
    docstring."""
    ma = comp.memory_analysis()
    assert _big_copies(comp.as_text()) == []
    assert POOLS <= ma.alias_size_in_bytes < 1.01 * POOLS
    assert ma.temp_size_in_bytes < 0.5e9
    return ma


@pytest.mark.parametrize("lanes", [24, 48])
def test_gpt2_large_decode_step_is_in_place(gpt2l, lanes):
    from singa_tpu.models import gpt2_decode
    from singa_tpu.serve import paged

    sds, params, pool, _ = gpt2l
    _in_place(paged._paged_decode_kernel.lower(
        params, pool, pool, *_lanes(sds, lanes), block=GB, n_head=GH,
        eps=1e-5, moe_top_k=2, top_k=0, use_top_p=False,
        window=None, fam=gpt2_decode.FAMILY).compile())


@pytest.mark.parametrize("rows,width", [(4, 128), (1, GW)])
def test_gpt2_large_admission_scatter_is_in_place(gpt2l, rows, width):
    """``_rows_to_pool`` (every admission of a pass in one scatter) at a
    pass of short prompts and at one full-width row; ``_row_to_pool``
    (swap-in, image import) at the full row."""
    from singa_tpu.serve import paged

    sds, _, pool, i32 = gpt2l
    row = sds((GL, rows, GH, width, GE // GH))
    _in_place(paged._rows_to_pool.lower(
        pool, pool, row, row, i32(rows), i32(rows * width // GB)).compile())
    if rows == 1:
        _in_place(paged._row_to_pool.lower(
            pool, pool, row, row, i32(width // GB)).compile())


def test_gpt2_large_block_copy_is_in_place(gpt2l):
    from singa_tpu.serve import paged

    _, _, pool, i32 = gpt2l
    ma = _in_place(paged._copy_pool_block.lower(
        pool, pool, i32(), i32()).compile())
    assert ma.temp_size_in_bytes < 1e6


def test_gpt2_large_speculative_step_is_in_place(gpt2l):
    """The verify chunk (two blocks a lane) of ``_paged_spec_kernel``
    with a small draft beside it: 2 layers, 256 wide, its slot arena
    donated with the pools."""
    from singa_tpu.serve import paged

    sds, params, pool, _ = gpt2l
    n, dl, de, dh = 24, 2, 256, 4
    arena = sds((dl, n, dh, GW, de // dh))
    comp = paged._paged_spec_kernel.lower(
        params, _gpt2_params(sds, dl, de), pool, pool, arena, arena,
        *_lanes(sds, n), block=GB, spec_k=4, tn=GH, te=1e-5, tm=2, dn=dh,
        de=1e-5, dm=2, top_k=0, use_top_p=False, window=None).compile()
    ma = comp.memory_analysis()
    arenas = 2 * dl * n * GW * de * 2
    assert _big_copies(comp.as_text()) == []
    assert POOLS + arenas <= ma.alias_size_in_bytes \
        < 1.01 * (POOLS + arenas)
    assert ma.temp_size_in_bytes < 0.5e9
