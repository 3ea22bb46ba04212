"""The serve programs of the hybrid family compiled at their real widths
for a described TPU v5e -- no chip attached, nothing runs: what the
chip's compiler refuses, and what it would copy or keep beside the
arguments, shows here at no chip time (on-chip-measurement guide,
section 2).  Two layers stand for six: every program scans one layer
body.  The topology is described inside a fixture, never at import.
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
    pool = sds((L, BLOCKS + 1, 4, BLOCK, 128))
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
