"""The serve programs compiled at their real widths for a described TPU
v5e -- no chip attached, nothing runs: what the chip's compiler refuses,
and what it would copy or keep beside the arguments, shows here at no
chip time (on-chip-measurement guide, section 2).  The hybrid family at
two layers for six (every program scans one layer body); ``gpt2-large``
at all 36 (its programs unroll them) with the benchmark's pool of 561 +
1 blocks of 32: every program that writes the K/V pool has to update it
where it lies -- no operation copies or re-lays a pool, the aliased
bytes are the two pools' data and nothing more (no padded rows), and
the temporaries stay small.  The latent-attention, routed-expert family
(``mla_moe``) at a dense layer and two expert layers for one and five,
at the benchmark's 128 lanes and its pool of 1,920 + 1 blocks of 128
rows: ONE pool leaf, updated where it lies.  The decode programs choose
their attention by the backend (``ops/paged_attention.decode_attn_impl``)
and the backend here is the CPU, so they compile with the block loop --
what int8 pools, windows, ``tp=`` and every CPU run keep -- unless a test
says "tpu" for its duration (``on_a_tpu``): then they compile WITH the
Pallas decode kernel, and are held to the same.  The topology is
described inside a fixture, never at import.
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


def _big_copies(text, floor=50e6, hbm_only=False):
    """Dims of the copies of more than ``floor`` elements in a compiled
    program's text; ``hbm_only``: without the compiler's own moves of an
    operand into its fast memory (the result's layout says ``S(1)``)."""
    out = []
    for dims, layout in re.findall(
            r"= \w+\[([\d,]+)\](\{[^}]*\})?[^\n]* copy\(", text):
        n = 1
        for d in dims.split(","):
            n *= int(d)
        if n > floor and not (hbm_only and "S(" in layout):
            out.append(dims)
    return out


def _step_kernel_calls(text, found):
    """The state-step kernel's custom calls in a compiled program's
    text, each of which ``found`` (``program_scopes()`` of that text)
    has to put under ``ssm_step`` -- the scope whose device time
    ``ssm_step_share`` and ``ssm_step_roofline`` read: a roofline that
    lost its kernel to another scope would read over 100%."""
    calls = re.findall(r"%(mamba2_step[\w.\-]*) = ", text)
    assert calls, "the decode program holds no state-step kernel"
    assert {v for k, v in found.items()
            if k.split(" ")[0] in calls} == {"ssm_step"}
    return calls


def test_the_decode_program_compiles_in_place(shapes, on_a_tpu):
    """As the chip runs it: the Mamba step is the Pallas kernel
    (ops/pallas/mamba2_step.py), ONE custom call in the scanned layer
    body, the state arena -- a carry of the layer scan, the layer index
    traced -- its aliased operand."""
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
    print(f"hybrid decode: temporaries {ma.temp_size_in_bytes}, aliased "
          f"{ma.alias_size_in_bytes}")
    # the pool and the state arenas are updated where they lie
    assert ma.alias_size_in_bytes >= 2 * 2 * L * (BLOCKS + 1) * 4 * BLOCK \
        * 128 + 4 * L * (n + 1) * 32 * 128 * 256
    # neither is copied or re-laid whole (a row-wide scatter into the
    # pool once made the compiler re-lay all of it twice a step)
    assert _big_copies(comp.as_text()) == []
    assert ma.temp_size_in_bytes < 1.0e9
    paged._keep_scopes("decode", fam.scopes, comp.as_text())
    found = paged.program_scopes()["decode"]
    assert {"attn", "ssm_step", "ssm_proj", "mlp", "head"} <= set(
        found.values())
    assert len(_step_kernel_calls(comp.as_text(), found)) == 1


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


def _row_copies(text, row):
    """Copies in a compiled program whose result has ``row``'s dims (a
    prefilling request's whole private row)."""
    dims = ",".join(str(d) for d in row.shape)
    return len(re.findall(r"= \w+\[" + dims + r"\][^\n]* copy\(", text))


def test_a_launch_of_two_blocks_compiles(shapes):
    """The hybrid family's 256-wide launch (docqa's budget over its
    block): an ``off`` of two block offsets; the scan walks the two
    blocks in order inside the program.  Temporaries stay under the
    one-block program's bound and no copy of the whole private row
    comes with the width."""
    from singa_tpu.serve import engine, paged

    cfg, fam, params, sds = shapes
    row = sds((L, 1, 4, WIDTH, 128))
    state = {"ssm": sds((L, 32, 128, 256), jnp.float32),
             "conv": sds((L, 3, cfg.conv_dim), jnp.float32)}
    texts = {}
    for n in (1, 2):
        comp = engine._chunk_row.lower(
            params, sds((1, WIDTH), jnp.int32), row, row,
            sds((n,) if n > 1 else (), jnp.int32), state,
            sds((), jnp.int32), n_head=20, eps=1e-5, moe_top_k=2,
            chunk=BLOCK, window=None, fam=fam).compile()
        assert comp.memory_analysis().temp_size_in_bytes < 0.5e9
        texts[n] = comp.as_text()
    assert _row_copies(texts[2], row) <= _row_copies(texts[1], row)
    paged._keep_scopes("chunk2", fam.scopes, texts[2])
    assert "ssm_scan" in set(paged.program_scopes()["chunk2"].values())


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


def _lanes(sds, n, n_tbl=GW // GB):
    """tables (``n_tbl`` blocks a lane), toks, pos, live, keys, temps,
    top_p of ``n`` lanes."""
    i32 = lambda *s: sds(s, jnp.int32)
    return (i32(n, n_tbl), i32(n), i32(n), sds((n,), jnp.bool_),
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


def test_gpt2_large_launch_of_the_whole_budget_compiles(gpt2l):
    """longdoc's budget in one launch: 4 blocks of 32 = 128 positions
    through all 36 layers, beside the one-block program.  The private
    rows are donated and written where they lie, temporaries stay
    under 0.5 GB, and the width brings no copy of a whole row."""
    from singa_tpu.models import gpt2_decode
    from singa_tpu.serve import engine

    sds, params, _, i32 = gpt2l
    row = sds((GL, 1, GH, GW, GE // GH))
    texts = {}
    for n in (1, 4):
        comp = engine._chunk_row.lower(
            params, i32(1, GW), row, row, i32(n) if n > 1 else i32(),
            n_head=GH, eps=1e-5, moe_top_k=2, chunk=GB, window=None,
            fam=gpt2_decode.FAMILY).compile()
        ma = comp.memory_analysis()
        assert ma.alias_size_in_bytes >= 2 * 2 * GL * GW * GE
        assert ma.temp_size_in_bytes < 0.5e9
        texts[n] = comp.as_text()
    assert _row_copies(texts[4], row) <= _row_copies(texts[1], row)


def test_gpt2_large_launch_of_three_blocks_and_of_two_requests_compile(
        gpt2l):
    """What a launch became when it turned into a list of segments: a
    remainder of three blocks in ONE launch (96 positions), and two
    requests' pieces in one -- two slots of half longdoc's budget, each
    against its own private rows.  Every row is donated and written
    where it lies (the pair aliases BOTH requests' rows), and neither
    program copies a request's whole row more often than the one-block
    program does."""
    from singa_tpu.models import gpt2_decode
    from singa_tpu.serve import engine

    sds, params, _, i32 = gpt2l
    row = sds((GL, 1, GH, GW, GE // GH))
    statics = dict(n_head=GH, eps=1e-5, moe_top_k=2, chunk=GB,
                   window=None, fam=gpt2_decode.FAMILY)
    rows = 2 * 2 * GL * GW * GE          # K and V of one request, bf16
    one = engine._chunk_row.lower(params, i32(1, GW), row, row, i32(),
                                  **statics).compile().as_text()
    three = engine._chunk_row.lower(params, i32(1, GW), row, row, i32(3),
                                    **statics).compile()
    two = lambda a: (a, a)
    pair = engine._chunk_row.lower(
        params, two(i32(1, GW)), two(row), two(row), two(i32(2)),
        n_valid=two(i32()), **statics).compile()
    for comp, n_req in ((three, 1), (pair, 2)):
        ma = comp.memory_analysis()
        assert ma.alias_size_in_bytes >= n_req * rows
        assert ma.temp_size_in_bytes < 0.5e9
        assert _row_copies(comp.as_text(), row) <= \
            n_req * _row_copies(one, row)


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


# ---- mla_moe: 7168 wide, 128 heads, a 576-value latent row, 16 of 256
# ---- experts of 2048, bf16 -------------------------------------------

ML, MLANES, MBLOCKS, MW, MB = 3, 128, 1920, 2048, 128


@pytest.fixture(scope="module")
def mla(shapes):
    from singa_tpu.models.mla_moe import (_KINDS, MlaMoeConfig,
                                          MlaMoeFamily)

    sds = shapes[3]
    cfg = MlaMoeConfig(vocab_size=16160, num_hidden_layers=ML,
                       first_k_dense_replace=1, experts_held=(0, 16),
                       max_len=MW, dtype="bfloat16")
    sh = cfg.shapes("model")
    params = dict(wte=sds(sh["wte"]), head=sds(sh["head"]),
                  lnf=sds(sh["lnf"], jnp.float32))
    for kind, n in (("dense", cfg.n_dense), ("moe", cfg.n_moe)):
        params[kind] = {
            k: sds((n,) + s, jnp.float32 if k in _KINDS[kind][0]
                   else jnp.bfloat16)
            for k, s in cfg.shapes(kind).items()}
    return cfg, MlaMoeFamily(cfg), params, sds


def test_the_latent_pool_is_one_leaf_updated_in_place(mla):
    """The decode program at the cell's 128 lanes: the pool is ONE leaf
    of 640-wide rows -- the 576 values of a position (latent 512 +
    rotated key 64) and 64 zeros, so that a row fills whole 128-lane
    tiles.  Stored 576 wide the compiler gave the pool another layout
    for the scatter of new rows than for the block loop and copied ALL
    of it between the two in every layer (two whole-pool copies in the
    program, 1.9 GB of temporaries); at 640 no operation is shaped like
    the pool, the aliased bytes are the pool's data and nothing more,
    and the temporaries stay under 0.2 GB (compiled: 33 MB)."""
    from singa_tpu.serve import paged

    cfg, fam, params, sds = mla
    assert cfg.row_width == 576 and cfg.pool_row_width == 640
    assert not fam.value_leaf
    pool = sds((ML, MBLOCKS + 1, MB, cfg.pool_row_width))
    comp = paged._paged_decode_kernel.lower(
        params, pool, None, *_lanes(sds, MLANES, MW // MB), None, None,
        None, block=MB, n_head=128, eps=1e-6, moe_top_k=2, top_k=0,
        use_top_p=False, window=None, fam=fam).compile()
    ma = comp.memory_analysis()
    data = 2 * ML * (MBLOCKS + 1) * MB * 640
    assert data <= ma.alias_size_in_bytes < 1.01 * data
    assert ma.temp_size_in_bytes < 0.2e9
    text = comp.as_text()
    # nothing as large as a tenth of the pool is copied, and no expert's
    # matrices (7168 x 4096, 2048 x 7168) are copied out of their stack
    assert _big_copies(text, data / 20) == []
    copied = set(re.findall(r"= \w+\[([\d,]+)\][^\n]* copy\(", text))
    assert not {d for d in copied if d.endswith(("7168,4096",
                                                 "2048,7168"))}, copied
    paged._keep_scopes("mla_decode", fam.scopes, text)
    assert {"mla_attn", "mla_proj", "moe_route", "moe_experts",
            "moe_shared", "dense_mlp", "head"} <= set(
                paged.program_scopes()["mla_decode"].values())


def test_the_latent_chunk_row_program_compiles(mla):
    from singa_tpu.serve import engine, paged

    cfg, fam, params, sds = mla
    row = sds((ML, 1, 1, MW, cfg.pool_row_width))
    comp = engine._chunk_row.lower(
        params, sds((1, MW), jnp.int32), row, None, sds((), jnp.int32),
        None, sds((), jnp.int32), n_head=128, eps=1e-6, moe_top_k=2, chunk=MB, window=None,
        fam=fam).compile()
    ma = comp.memory_analysis()
    # the private row is donated and written in place
    assert ma.alias_size_in_bytes >= 2 * ML * MW * 640
    assert ma.temp_size_in_bytes < 0.2e9
    paged._keep_scopes("mla_chunk", fam.scopes, comp.as_text())
    assert {"mla_attn", "moe_experts"} <= set(
        paged.program_scopes()["mla_chunk"].values())


def test_one_leaf_copies_between_row_and_pool_are_in_place(mla):
    """Admission scatter, swap-in and the gather of a fresh row, with
    no value leaf (``None`` rides the tree-mapped copies)."""
    from singa_tpu.serve import paged

    cfg, _, _, sds = mla
    pool = sds((ML, MBLOCKS + 1, MB, cfg.pool_row_width))
    row = sds((ML, 1, 1, MW, cfg.pool_row_width))
    i32 = lambda *s: sds(s, jnp.int32)
    data = 2 * ML * (MBLOCKS + 1) * MB * 640
    comp = paged._row_to_pool.lower(pool, None, row, None,
                                    i32(MW // MB)).compile()
    ma = comp.memory_analysis()
    assert data <= ma.alias_size_in_bytes < 1.01 * data
    assert _big_copies(comp.as_text(), data / 20) == []
    comp = paged._pool_to_row.lower(pool, None, i32(MW // MB), i32(),
                                    head_dim=cfg.pool_row_width).compile()
    assert comp.memory_analysis().temp_size_in_bytes < 0.1e9


# ---- what the other families' programs compile to is what they compiled
# ---- to before the pool could be one leaf ------------------------------

# compiled at the parent commit (PR 30's tree) for the same described
# chip: temporaries, aliased bytes, and the program's text without its
# source locations, lines sorted.  A PR that means to change one of
# these programs compiles the parent's anew and replaces the values.
PARENT = {
    "gpt2l_decode24_temp": 1_161_728,
    "gpt2l_decode24_text": "4fd1f4ddd3c84f27d4ca26fd2c189ef079fb2882",
    "hybrid_decode_temp": 7_988_736,
    "hybrid_decode_alias": 386_260_992,
    "hybrid_decode_text": "4172c986717a4d1034bf4b0f078c10ebfcf1851c",
}


def _text_hash(comp):
    """The compiled program's text with source locations taken out and
    its lines sorted, hashed."""
    import hashlib

    t = re.sub(r", metadata=\{[^}]*\}", "", comp.as_text())
    t = re.sub(r"(?ms)^(FileNames|FunctionNames|FileLocations|StackFrames)"
               r"\n.*?\n\n", "", t)
    return hashlib.sha1("\n".join(sorted(t.splitlines())).encode()) \
        .hexdigest()


def test_gpt2_large_and_the_hybrid_family_compile_as_at_the_parent(
        gpt2l, shapes):
    """The two-leaf programs are untouched by the one-leaf pool: gpt2-
    large's decode step at 24 lanes and the hybrid family's decode
    program compile to the parent's programs, instruction for
    instruction."""
    from singa_tpu.models import gpt2_decode
    from singa_tpu.serve import paged

    sds, params, pool, _ = gpt2l
    comp = paged._paged_decode_kernel.lower(
        params, pool, pool, *_lanes(sds, 24), block=GB, n_head=GH,
        eps=1e-5, moe_top_k=2, top_k=0, use_top_p=False,
        window=None, fam=gpt2_decode.FAMILY).compile()
    ma = comp.memory_analysis()
    assert POOLS <= ma.alias_size_in_bytes < 1.01 * POOLS
    assert ma.temp_size_in_bytes == PARENT["gpt2l_decode24_temp"]
    assert _text_hash(comp) == PARENT["gpt2l_decode24_text"]
    cfg, fam, hparams, _ = shapes
    n = SLOTS
    hpool = sds((L, BLOCKS + 1, BLOCK, 4 * 128))
    state = {"ssm": sds((L, n + 1, 32, 128, 256), jnp.float32),
             "conv": sds((L, n + 1, 3, cfg.conv_dim), jnp.float32)}
    comp = paged._paged_decode_kernel.lower(
        hparams, hpool, hpool, *_lanes(sds, n, WIDTH // BLOCK), None,
        state, sds((n,), jnp.int32), block=BLOCK, n_head=20, eps=1e-5,
        moe_top_k=2, top_k=0, use_top_p=False, window=None,
        fam=fam).compile()
    ma = comp.memory_analysis()
    assert ma.temp_size_in_bytes == PARENT["hybrid_decode_temp"]
    assert ma.alias_size_in_bytes == PARENT["hybrid_decode_alias"]
    assert _text_hash(comp) == PARENT["hybrid_decode_text"]


# ---- swa_moe (Trinity-Mini): two periods for eight, real widths ----------

SW_P, SW_SLOTS, SW_BLOCKS, SW_WIDTH = 2, 24, 200, 16384


@pytest.fixture(scope="module")
def swa(shapes):
    """(cfg, family, abstract params, sds) of the window-and-full family
    at two periods (8 layers: every stack the programs scan) with the
    benchmark's share: 16 experts held, an eighth of the vocabulary."""
    from singa_tpu.models.swa_moe import (SwaMoeConfig, SwaMoeFamily,
                                          _tensors)

    sds = shapes[3]
    cfg = SwaMoeConfig(num_hidden_layers=4 * SW_P, vocab_size=25024,
                       experts_held=(0, 16), max_len=SW_WIDTH,
                       dtype="bfloat16")
    sh = cfg.shapes("model")
    params = dict(wte=sds(sh["wte"]), head=sds(sh["head"]),
                  lnf=sds(sh["lnf"], jnp.float32))
    for stack, n in cfg.stack_sizes().items():
        vec, mat = _tensors(stack)
        params[stack] = {
            k: sds((n,) + cfg.shapes(stack)[k],
                   jnp.float32 if k in vec else jnp.bfloat16)
            for k in vec + mat}
    return cfg, SwaMoeFamily(cfg), params, sds


def test_the_two_kind_cache_is_updated_in_place(swa):
    """The decode program at the benchmark's 24 lanes: the full layers'
    pool and the window layers' ring arenas (50 MB a slot at two
    periods) are aliased and neither is copied or re-laid -- viewed as
    (ring, heads, head size) the compiler re-laid both arenas,
    transposed, every step (3.1 GB of temporaries at size); nor is any
    layer's matrix (W_q and W_k, stored (in, out), were transposed out
    of their stacks every step)."""
    from singa_tpu.serve import paged

    cfg, fam, params, sds = swa
    n = SW_SLOTS
    pool = sds((SW_P, SW_BLOCKS + 1, BLOCK, 512))
    arena = sds((SW_P, n + 1, 3, 2048, 512))
    state = {"win_k": arena, "win_v": arena}
    comp = paged._paged_decode_kernel.lower(
        params, pool, pool, *_lanes(sds, n, SW_WIDTH // BLOCK), None,
        state, sds((n,), jnp.int32), block=BLOCK, n_head=32, eps=1e-5,
        moe_top_k=2, top_k=0, use_top_p=False, window=None,
        fam=fam).compile()
    ma = comp.memory_analysis()
    cache = 2 * 2 * (SW_P * (SW_BLOCKS + 1) * BLOCK * 512
                     + SW_P * (n + 1) * 3 * 2048 * 512)
    assert cache <= ma.alias_size_in_bytes < 1.01 * cache
    # the smallest of: a pool (26 M elements), an arena (157 M), a
    # layer's W_q (8.4 M)
    assert _big_copies(comp.as_text(), floor=8e6) == []
    assert ma.temp_size_in_bytes < 0.1e9
    paged._keep_scopes("swa_decode", fam.scopes, comp.as_text())
    assert {"attn_window", "attn_full", "moe_experts", "moe_shared",
            "dense_mlp", "head"} <= set(
                paged.program_scopes()["swa_decode"].values())


@pytest.mark.parametrize("blocks", [1, 4])
def test_the_two_kind_chunk_row_programs_compile(swa, blocks):
    """A launch of one block and of the budget's four: the request's own
    rings (2 x 6.3 M elements at two periods) and its private row are
    what it may copy; nothing the size of a layer's stack of matrices."""
    from singa_tpu.serve import engine, paged

    cfg, fam, params, sds = swa
    row = sds((SW_P, 1, 4, SW_WIDTH, 128))
    ring = sds((SW_P, 3, 2048, 512))
    comp = engine._chunk_row.lower(
        params, sds((1, SW_WIDTH), jnp.int32), row, row,
        sds((blocks,) if blocks > 1 else (), jnp.int32),
        {"win_k": ring, "win_v": ring}, sds((), jnp.int32), n_head=32,
        eps=1e-5, moe_top_k=2, chunk=BLOCK, window=None,
        fam=fam).compile()
    assert comp.memory_analysis().temp_size_in_bytes < 0.3e9
    assert _big_copies(comp.as_text(), floor=17e6) == []
    paged._keep_scopes(f"swa_chunk{blocks}", fam.scopes, comp.as_text())
    assert {"attn_window", "attn_full", "moe_experts"} <= set(
        paged.program_scopes()[f"swa_chunk{blocks}"].values())


# ---- the decode programs WITH the Pallas kernel ---------------------------


@pytest.fixture
def on_a_tpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` sees the CPU here and
    takes its CPU branch (on-chip-measurement guide, section 2); the
    decode programs choose their attention by it, so a test that
    compiles them as the chip runs them says "tpu" itself -- and lets
    no trace made under the other answer stand in for its own."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.clear_caches()


def _decode_program(name, request):
    """(the decode program's arguments, its statics, bytes of its
    cache's data, its scope of the attention, the most it may keep
    beside its arguments -- what the loop's program keeps today, as
    compiled here from PR 38's tree: 1.16, 32.9 and 43.3 MB -- and
    the elements from which a copy counts as one of a pool or of a
    stack of matrices) of one family at its benchmark width."""
    if name == "gpt2-large":
        from singa_tpu.models import gpt2_decode
        sds, params, pool, _ = request.getfixturevalue("gpt2l")
        args = (params, pool, pool, *_lanes(sds, 24))
        kw = dict(block=GB, n_head=GH, fam=gpt2_decode.FAMILY)
        return args, kw, POOLS, None, PARENT["gpt2l_decode24_temp"], 50e6
    if name == "mla_moe":
        cfg, fam, params, sds = request.getfixturevalue("mla")
        pool = sds((ML, MBLOCKS + 1, MB, cfg.pool_row_width))
        args = (params, pool, None, *_lanes(sds, MLANES, MW // MB), None,
                None, None)
        kw = dict(block=MB, n_head=128, fam=fam)
        data = 2 * ML * (MBLOCKS + 1) * MB * 640
        return args, kw, data, "mla_attn", 32_880_128, data / 20
    cfg, fam, params, sds = request.getfixturevalue("swa")
    n = SW_SLOTS
    pool = sds((SW_P, SW_BLOCKS + 1, BLOCK, 512))
    arena = sds((SW_P, n + 1, 3, 2048, 512))
    args = (params, pool, pool, *_lanes(sds, n, SW_WIDTH // BLOCK), None,
            {"win_k": arena, "win_v": arena}, sds((n,), jnp.int32))
    kw = dict(block=BLOCK, n_head=32, fam=fam)
    cache = 2 * 2 * (SW_P * (SW_BLOCKS + 1) * BLOCK * 512
                     + SW_P * (n + 1) * 3 * 2048 * 512)
    return args, kw, cache, "attn_full", 43_291_648, 8e6


@pytest.mark.parametrize("family", ["gpt2-large", "mla_moe", "swa_moe"])
def test_the_decode_program_compiles_with_the_kernel(family, request,
                                                     on_a_tpu):
    """As the chip runs it: the attention over the pool is the Pallas
    kernel (one custom call a layer body, the pools its operands where
    they lie), the program still updates the donated cache in place --
    aliased bytes are the cache's data and nothing more, no operation
    copies or re-lays a pool -- it keeps no more beside its arguments
    than the loop's program does, and the kernel's instruction lies
    under the family's scope of the attention, named as a device trace
    names it (``benchmark/readers/scopes.py`` splits the decode
    program's time by these)."""
    from singa_tpu.serve import paged

    args, kw, cache, scope, temp, floor = _decode_program(family, request)
    comp = paged._paged_decode_kernel.lower(
        *args, **kw, eps=1e-5, moe_top_k=2, top_k=0, use_top_p=False,
        window=None).compile()
    text = comp.as_text()
    calls = re.findall(r"%(paged_decode_attn[\w.\-]*) = ", text)
    assert calls, "the decode program holds no kernel"
    ma = comp.memory_analysis()
    print(f"{family}: {len(calls)} kernel call(s), temporaries "
          f"{ma.temp_size_in_bytes}, aliased {ma.alias_size_in_bytes}")
    assert cache <= ma.alias_size_in_bytes < 1.01 * cache
    assert _big_copies(text, floor) == []
    assert ma.temp_size_in_bytes <= temp
    if scope is not None:
        paged._keep_scopes(f"kernel/{family}", kw["fam"].scopes, text)
        found = paged.program_scopes()[f"kernel/{family}"]
        under = {v for k, v in found.items()
                 if k.split(" ")[0] in calls}
        assert under == {scope}, (calls, under)


# ---- conv_moe (LFM2-24B-A2B): the benchmark's nine layers, real widths ----

CM_SLOTS, CM_BLOCKS, CM_WIDTH = 256, 3000, 8192


@pytest.fixture(scope="module")
def conv(shapes):
    """(cfg, family, abstract params, sds) of the conv-and-attention
    family as the benchmark cuts it: a dense conv layer and two periods
    of one attention layer and three conv layers, all 64 experts of the
    eight expert layers (10.4 GB of arguments), the whole vocabulary."""
    from singa_tpu.models.conv_moe import (ConvMoeConfig, ConvMoeFamily,
                                           _tensors)

    sds = shapes[3]
    cfg = ConvMoeConfig(
        num_hidden_layers=9, num_dense_layers=1,
        layer_types=("conv",) + ("full_attention",) + ("conv",) * 3
        + ("full_attention",) + ("conv",) * 3,
        max_len=CM_WIDTH, dtype="bfloat16")
    sh = cfg.shapes("model")
    params = dict(wte=sds(sh["wte"]), lnf=sds(sh["lnf"], jnp.float32))
    for stack, n in cfg.stack_sizes().items():
        vec, mat = _tensors(stack)
        params[stack] = {
            k: sds((n,) + cfg.shapes(stack)[k],
                   jnp.float32 if k in vec else jnp.bfloat16)
            for k in vec + mat}
    return cfg, ConvMoeFamily(cfg), params, sds


def test_the_256_lane_decode_program_updates_pool_and_tails_in_place(
        conv, on_a_tpu):
    """The decode program as the chip runs it, at the benchmark's 256
    lanes and its pool of 3,000 + 1 blocks: the attention layers' pool
    (2 x 787 MB) and the conv layers' tail arena (34 MB) are aliased and
    neither is copied or re-laid, no expert stack (3.2 G elements) or
    layer's matrix is copied, the attention is the Pallas kernel under
    the family's scope, and what the program keeps beside its 12 GB of
    arguments stays under 0.2 GB (the chip has 16)."""
    from singa_tpu.serve import paged

    cfg, fam, params, sds = conv
    n = CM_SLOTS
    pool = sds((2, CM_BLOCKS + 1, BLOCK, 512))
    state = {"conv": sds((2, n + 1, 4, 2, 2048), jnp.float32)}
    comp = paged._paged_decode_kernel.lower(
        params, pool, pool, *_lanes(sds, n, CM_WIDTH // BLOCK), None,
        state, sds((n,), jnp.int32), block=BLOCK, n_head=32, eps=1e-5,
        moe_top_k=2, top_k=0, use_top_p=False, window=None,
        fam=fam).compile()
    ma, text = comp.memory_analysis(), comp.as_text()
    cache = 2 * 2 * 2 * (CM_BLOCKS + 1) * BLOCK * 512 \
        + 4 * 2 * (n + 1) * 4 * 2 * 2048
    assert cache <= ma.alias_size_in_bytes < 1.01 * cache
    # the smallest of: a layer's W_out (4.2 M elements), the tail arena
    # (8.4 M), a pool (393 M), an expert stack (3.2 G); the largest copy
    # there is, the lanes' 32 blocks of table rows, is 4.19 M
    assert _big_copies(text, floor=4.2e6) == []
    assert ma.temp_size_in_bytes < 0.2e9
    assert ma.argument_size_in_bytes > 11.9e9
    calls = re.findall(r"%(paged_decode_attn[\w.\-]*) = ", text)
    assert calls, "the decode program holds no kernel"
    paged._keep_scopes("conv_decode", fam.scopes, text)
    found = paged.program_scopes()["conv_decode"]
    assert {v for k, v in found.items()
            if k.split(" ")[0] in calls} == {"attn_full"}
    assert {"short_conv", "attn_full", "moe_experts", "dense_mlp",
            "head"} <= set(found.values())


@pytest.mark.parametrize("blocks", [1, 3, 4, "pair"])
def test_the_conv_family_chunk_row_programs_compile(conv, blocks):
    """A launch of one block, of three, of the budget's four, and of two
    requests in slots of two blocks each: a request's private row (2 x
    8.4 M elements over the two attention layers) is what it may copy;
    nothing the size of an expert stack or of a layer's in-projection
    (12.6 M)."""
    from singa_tpu.serve import engine, paged

    cfg, fam, params, sds = conv
    row = sds((2, 1, 8, CM_WIDTH, 64))
    args = (sds((1, CM_WIDTH), jnp.int32), row, row,
            sds((2 if blocks == "pair" else blocks,)
                if blocks != 1 else (), jnp.int32),
            {"conv": sds((2, 4, 2, 2048), jnp.float32)},
            sds((), jnp.int32))
    if blocks == "pair":
        args = tuple((a, a) for a in args)
    comp = engine._chunk_row.lower(
        params, *args, n_head=32, eps=1e-5, moe_top_k=2, chunk=BLOCK,
        window=None, fam=fam).compile()
    assert comp.memory_analysis().temp_size_in_bytes < 0.2e9
    assert _big_copies(comp.as_text(), floor=8.5e6) == []
    paged._keep_scopes(f"conv_chunk{blocks}", fam.scopes, comp.as_text())
    assert {"short_conv", "attn_full", "moe_experts"} <= set(
        paged.program_scopes()[f"conv_chunk{blocks}"].values())


# ---- ssm_moe (Nemotron-3-Super-120B-A12B): the benchmark's eleven layers,
# ---- real widths

SM_SLOTS, SM_BLOCKS, SM_WIDTH = 128, 2048, 9216


@pytest.fixture(scope="module")
def ssm(shapes):
    """(cfg, family, abstract params, sds) of the one-mixer-a-layer
    family as the benchmark cuts it: one period ``MEMEMEMEM*E``, experts
    [0, 128) of the router's 512 in each of the five expert layers, a
    quarter of the vocabulary (9.3 GB of arguments)."""
    from singa_tpu.models.ssm_moe import (MATRICES, VECTORS, SsmMoeConfig,
                                          SsmMoeFamily)

    sds = shapes[3]
    cfg = SsmMoeConfig(
        num_hidden_layers=11, hybrid_override_pattern="MEMEMEMEM*E",
        vocab_size=32768, experts_held=(0, 128), max_len=SM_WIDTH,
        dtype="bfloat16")
    sh = cfg.shapes("model")
    params = dict(wte=sds(sh["wte"]), head=sds(sh["head"]),
                  lnf=sds(sh["lnf"], jnp.float32))
    for stack, n in cfg.stack_sizes().items():
        params[stack] = {
            k: sds((n,) + cfg.shapes(stack)[k],
                   jnp.float32 if k in VECTORS[stack] else jnp.bfloat16)
            for k in VECTORS[stack] + MATRICES[stack]}
    return cfg, SsmMoeFamily(cfg), params, sds


def _ssm_state(sds, lead):
    """The two state arenas (or a request's carried state) under the
    leading axes ``lead``: five Mamba layers' state (128 x 64 x 128) and
    conv tails (4 x 10,240: a row more than the conv reads, see below),
    float32."""
    return {"ssm": sds(lead + (5, 128, 64, 128), jnp.float32),
            "conv": sds(lead + (5, 4, 10240), jnp.float32)}


def test_the_128_lane_decode_program_updates_pool_and_state_in_place(
        ssm, on_a_tpu):
    """The decode program as the chip runs it, at the benchmark's 128
    lanes: the ONE attention layer's pool (2 x 134 MB) and the five Mamba
    layers' state arenas (2.7 GB + 106 MB) are aliased and neither is
    copied or re-laid -- two arenas of 2.8 GB copied once would not fit
    beside 9.3 GB of weights -- no expert stack (1.8 G elements) or
    mixer's matrix is copied, the attention is the Pallas kernel under
    the family's scope, and what the program keeps beside its 12.4 GB of
    arguments stays under 0.5 GB (the chip has 16).  (With the conv's
    tail kept as the 3 rows the conv reads, the arena's second-minor axis
    was tiled by ones at the program's boundary and by fours inside it,
    and the compiler re-laid all of it on the way in and on the way out
    of every step: ops/mamba2.step.)"""
    from singa_tpu.serve import paged

    cfg, fam, params, sds = ssm
    n = SM_SLOTS
    pool = sds((1, SM_BLOCKS + 1, BLOCK, 256))
    comp = paged._paged_decode_kernel.lower(
        params, pool, pool, *_lanes(sds, n, SM_WIDTH // BLOCK), None,
        _ssm_state(sds, (1, n + 1)), sds((n,), jnp.int32), block=BLOCK,
        n_head=32, eps=1e-5, moe_top_k=2, top_k=0, use_top_p=False,
        window=None, fam=fam).compile()
    ma, text = comp.memory_analysis(), comp.as_text()
    cache = 2 * 2 * (SM_BLOCKS + 1) * BLOCK * 256 \
        + 4 * (n + 1) * 5 * (128 * 64 * 128 + 4 * 10240)
    print(f"ssm_moe decode: temporaries {ma.temp_size_in_bytes}, aliased "
          f"{ma.alias_size_in_bytes}, arguments "
          f"{ma.argument_size_in_bytes}")
    assert cache <= ma.alias_size_in_bytes < 1.01 * cache
    # the smallest of: a latent projection (4.2 M elements), the conv
    # arena (26.4 M), a pool (67 M), a mixer's in-projection (76 M), the
    # state arena (676 M), an expert stack (1.8 G).  (The one attention
    # layer's W_o, 16.8 M, goes to the compiler's fast memory in every
    # program of this family: not a copy in the chip's memory.)
    assert _big_copies(text, floor=4.2e6, hbm_only=True) == []
    assert ma.temp_size_in_bytes < 0.5e9
    assert ma.argument_size_in_bytes > 12.3e9
    calls = re.findall(r"%(paged_decode_attn[\w.\-]*) = ", text)
    assert calls, "the decode program holds no kernel"
    paged._keep_scopes("ssm_decode", fam.scopes, text)
    found = paged.program_scopes()["ssm_decode"]
    assert {v for k, v in found.items()
            if k.split(" ")[0] in calls} == {"attn_full"}
    assert {"ssm_step", "ssm_proj", "attn_full", "moe_route", "moe_latent",
            "moe_experts", "head"} <= set(found.values())
    # the five Mamba layers' steps: a kernel call in the scanned period
    # (ME x 4) and one in the tail (M*E), the 2.7 GB arena its aliased
    # operand
    assert len(_step_kernel_calls(text, found)) == 2


@pytest.mark.parametrize("blocks", [1, 2, 3, 4, "pair"])
def test_the_ssm_family_chunk_row_programs_compile(ssm, blocks):
    """A launch of one block, of two, three and the budget's four, and of
    two requests in slots of two blocks each: nothing the size of an
    expert stack, of a mixer's in-projection (76 M elements) or of a
    request's carried state (5.2 M) is copied, and the temporaries stay
    under 0.5 GB."""
    from singa_tpu.serve import engine, paged

    cfg, fam, params, sds = ssm
    row = sds((1, 1, 2, SM_WIDTH, 128))
    args = (sds((1, SM_WIDTH), jnp.int32), row, row,
            sds((2 if blocks == "pair" else blocks,)
                if blocks != 1 else (), jnp.int32),
            _ssm_state(sds, (1,)), sds((), jnp.int32))
    if blocks == "pair":
        args = tuple((a, a) for a in args)
    comp = engine._chunk_row.lower(
        params, *args, n_head=32, eps=1e-5, moe_top_k=2, chunk=BLOCK,
        window=None, fam=fam).compile()
    ma = comp.memory_analysis()
    print(f"ssm_moe chunk {blocks}: temporaries {ma.temp_size_in_bytes}")
    assert ma.temp_size_in_bytes < 0.5e9
    assert _big_copies(comp.as_text(), floor=5.3e6, hbm_only=True) == []
    paged._keep_scopes(f"ssm_chunk{blocks}", fam.scopes, comp.as_text())
    assert {"ssm_scan", "attn_full", "moe_experts", "moe_latent"} <= set(
        paged.program_scopes()[f"ssm_chunk{blocks}"].values())
