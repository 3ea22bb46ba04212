"""The state-step kernel (ops/pallas/mamba2_step.py) against the loop it
stands in for, ``ops/mamba2.step``'s own a lane at a time, through
``step`` itself at small sizes of the two served arena layouts; and the
rule that chooses between them.  On the CPU the kernel body runs under
``interpret=True`` (the kernel function's own private argument); the
same file run on a TPU compiles it with Mosaic: that run is the on-chip
parity."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.ops import mamba2
from singa_tpu.ops.pallas import mamba2_step as kernel

ON_TPU = jax.default_backend() == "tpu"

# heads, head size, state size, groups (four heads read a group's B and
# C), conv taps, lanes, slots (row SLOTS of an arena is the trash row)
H, P, N, G, K, LANES, SLOTS = 8, 8, 128, 2, 4, 6, 9
LAYERS = 3
WIDTH = H * P + 2 * G * N
# an arena's leading axes -> (arena shape before (h, p, n), the leading
# indices of a layer's rows): the hybrid family's (L, S+1), the layer
# traced inside a scan; the one-mixer-a-layer family's (A, S+1, rows),
# static
LAYOUTS = ("layer traced inside a scan", "static rows under a leading axis")
DEAD = {"no lane dead": (), "one lane dead": (2,),
        "three lanes dead": (0, 2, 5), "every lane dead": range(LANES)}
# heads a block: all eight (two groups a block), a group, half a group
BLOCKS = {"one block a lane": 8, "two blocks a lane": 4,
          "four blocks a lane": 2}


def _operands(layout, dead, seed=0):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape, scale=1.0: jnp.asarray(
        scale * rng.normal(size=shape), jnp.float32)
    lead = ((LAYERS, SLOTS + 1) if layout == LAYOUTS[0]
            else (2, SLOTS + 1, LAYERS))
    slots = rng.permutation(SLOTS)[:LANES].astype(np.int32)  # shuffled
    slots[list(dead)] = SLOTS
    p = dict(conv_w=f32(K, WIDTH, scale=0.5), conv_b=f32(WIDTH, scale=0.1),
             dt_bias=f32(H), a_log=jnp.log(jnp.asarray(
                 rng.uniform(1, 16, H), jnp.float32)), d=f32(H))
    return dict(xbc=f32(LAYERS, LANES, WIDTH), dt=f32(LAYERS, LANES, H),
                p=p, ssm=f32(*lead, H, P, N), conv=f32(*lead, K, WIDTH),
                slots=jnp.asarray(slots))


def _run(o, layout):
    """``step`` for every layer of the arenas, as the family of that
    layout calls it.  Returns (y (LAYERS, W, h p), ssm, conv)."""
    if layout == LAYOUTS[0]:
        def layer(carry, xs):
            li, xbc, dt = xs
            y, ssm, conv = mamba2.step(xbc, dt, o["p"], *carry,
                                       lambda slot: (li, slot), o["slots"])
            return (ssm, conv), y

        (ssm, conv), y = jax.lax.scan(
            layer, (o["ssm"], o["conv"]),
            (jnp.arange(LAYERS), o["xbc"], o["dt"]))
        return y, ssm, conv
    ssm, conv, ys = o["ssm"], o["conv"], []
    for i in range(LAYERS):
        y, ssm, conv = mamba2.step(o["xbc"][i], o["dt"][i], o["p"], ssm,
                                   conv, lambda slot: (1, slot, i),
                                   o["slots"])
        ys.append(y)
    return jnp.stack(ys), ssm, conv


def _run_as(impl, o, layout, monkeypatch):
    """:func:`_run` with ``step`` made to take ``impl`` whatever the
    backend -- the kernel interpreted anywhere but on a TPU."""
    monkeypatch.setattr(mamba2, "step_impl", lambda *a, **kw: impl)
    monkeypatch.setattr(
        mamba2._pallas, "mamba2_step",
        functools.partial(kernel.mamba2_step, _interpret=not ON_TPU))
    return jax.jit(lambda: _run(o, layout))()


@pytest.mark.parametrize("blocks", list(BLOCKS))
@pytest.mark.parametrize("dead", list(DEAD))
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_kernel_leaves_what_the_loop_leaves(layout, dead, blocks,
                                                monkeypatch):
    """``y`` of the live lanes and every touched slot's state are the
    loop's to float32 reordering, every slot no lane points at is the
    arena's own bytes, and nothing anywhere -- the trash row, which
    several dead lanes advance in one step, and the dead lanes' ``y``
    included -- is a NaN."""
    o = _operands(layout, DEAD[dead])
    want = _run_as("loop", o, layout, monkeypatch)
    monkeypatch.setattr(kernel, "_BLOCK_BYTES", BLOCKS[blocks] * P * N * 4)
    assert kernel.heads_a_block(H, P, N, H // G) == BLOCKS[blocks]
    got = _run_as("kernel", o, layout, monkeypatch)
    slots = np.asarray(o["slots"])
    live = slots != SLOTS
    np.testing.assert_allclose(np.asarray(got[0])[:, live],
                               np.asarray(want[0])[:, live],
                               rtol=1e-5, atol=1e-5)
    touched = np.zeros(SLOTS + 1, bool)
    touched[slots[live]] = True
    untouched = ~touched
    untouched[SLOTS] = False               # the trash row is anybody's
    assert untouched.any()

    def by_slot(a):
        """An arena with its slots leading, and (the static layout) the
        leading row no lane points at."""
        a = np.asarray(a)
        if layout == LAYOUTS[0]:
            return np.moveaxis(a, 1, 0), None
        return a[1], a[0]

    for g, w_, was in zip(got[1:], want[1:], (o["ssm"], o["conv"])):
        (g, g_other), (w_, _), (was, was_other) = map(by_slot,
                                                      (g, w_, was))
        np.testing.assert_allclose(g[touched], w_[touched],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(g[untouched], was[untouched])
        if g_other is not None:
            np.testing.assert_array_equal(g_other, was_other)
    for a in got:
        assert np.isfinite(np.asarray(a)).all()


# the two served arenas (benchmark/configs: falcon-h1-34b at six layers
# and 32 slots, nemotron-3-super at five Mamba layers and 128) and what
# the rule turns away
F32, BF16 = jnp.float32, jnp.bfloat16
RULE = {
    "the hybrid family's arena on a TPU":
        ((6, 33, 32, 128, 256), F32, "tpu", "kernel"),
    "the one-mixer-a-layer family's arena on a TPU":
        ((1, 129, 5, 128, 64, 128), F32, "tpu", "kernel"),
    "the same arena on the CPU":
        ((1, 129, 5, 128, 64, 128), F32, "cpu", "loop"),
    "... on a GPU": ((6, 33, 32, 128, 256), F32, "gpu", "loop"),
    "a bfloat16 state": ((6, 33, 32, 128, 256), BF16, "tpu", "loop"),
    "a state of 64: half a tile of lanes":
        ((2, 5, 8, 64, 64), F32, "tpu", "loop"),
    "a state of 16 (every CPU test's)":
        ((2, 5, 4, 16, 16), F32, "tpu", "loop"),
    "a state of 192: a tile and a half":
        ((2, 5, 8, 64, 192), F32, "tpu", "loop"),
    "heads of 4: half a tile of sublanes":
        ((2, 5, 8, 4, 128), F32, "tpu", "loop"),
    "an arena that varies over a mesh axis":
        ((6, 33, 32, 128, 256), F32, "tpu", "loop", {"tp"}),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_rule_chooses_by_the_arena_alone(case):
    """``step_impl`` on shapes alone (no device): the kernel for an
    unsharded float32 arena of whole tiles on a TPU, the loop for
    anything else."""
    shape, dtype, backend, says, *vma = RULE[case]
    arena = jax.ShapeDtypeStruct(shape, dtype,
                                 **({"vma": frozenset(vma[0])} if vma
                                    else {}))
    assert mamba2.step_impl(arena, backend=backend) == says
    if not vma:     # ... and with no backend said, this process's
        assert mamba2.step_impl(arena) == (
            mamba2.step_impl(arena, backend="tpu") if ON_TPU else "loop")


def test_step_runs_what_the_rule_says(monkeypatch):
    """Through ``step`` with nothing patched: the loop anywhere but on a
    TPU, the kernel there, to the bit -- the rule decides, not the
    caller."""
    o = _operands(LAYOUTS[0], DEAD["one lane dead"], seed=3)
    impl = mamba2.step_impl(o["ssm"])
    assert impl == ("kernel" if ON_TPU else "loop")
    got = jax.jit(lambda: _run(o, LAYOUTS[0]))()
    want = _run_as(impl, o, LAYOUTS[0], monkeypatch)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(g, w_)
