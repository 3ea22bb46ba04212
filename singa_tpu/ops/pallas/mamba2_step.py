"""The Mamba-2 recurrence one token a lane over the engine's state arena
-- a Pallas TPU kernel.

``ops/mamba2.step`` decides when it runs (:func:`ops.mamba2.step_impl`);
its per-lane loop is the reference the kernel is held to
(tests/test_mamba2_step_kernel.py).

* The arena -- ``(*lead, h, p, n)`` float32, whatever its leading axes --
  goes in whole and comes out ALIASED: a row the step does not touch is
  neither read, written nor copied, and the program goes on updating the
  arena where it lies.
* The lanes' leading indices ``(n_lead, W)`` are scalar prefetch (a
  layer index may be traced inside a layer scan), and so are the
  per-head decays ``da`` (W, h): a scalar a head, read where it is used.
* Grid ``(W, h // hb)``.  A grid step's block is ``hb`` heads of ONE
  lane's state, ``(hb, p, n)`` (``_BLOCK_BYTES``), chosen by the index
  map from the prefetched indices, in and out under the same map: the
  pipeline fetches the block after this one while this one is advanced
  and the one before is written back.
* Inside a block, a head at a time: ``s <- da s + (dt x) (x) B`` and
  ``y = s C`` summed over ``n``, float32 throughout.  ``B`` and ``C``
  arrive a row a group and broadcast down the state's sublanes.  ``dt
  x`` has to lie along its lanes, and a lane-broadcast a state tile
  beside the read-out's cross-lane add is more than the copies hide
  (PERF.md section 5, PR 45): the MXU lays it out instead, exactly.  It
  arrives with ``p`` down the sublanes as three bf16 terms side by side
  along the lanes (hi | mid | lo of every head of the block, a few KB a
  lane, split by the caller), and ONE matmul a block against a matrix of
  ones and zeros -- head i's columns pick the terms' rows i, hb + i, 2 hb
  + i -- gives every head's ``dt x`` across its ``n`` columns: a term
  times one, the three summed in float32.  ``y`` leaves a head a column.
* Dead lanes point at the trash row, several of them in one step maybe:
  their blocks are the only ones that repeat, what lands there is some
  mix of the dead lanes' updates of finite values -- never read by a
  live lane, and finite.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK_BYTES = 1 << 20      # of one lane's state a grid step advances
_LANES = 128                # a tile's lanes: the width the terms lie in


def heads_a_block(h, p, n, k):
    """``hb``: the most heads whose ``(p, n)`` float32 fill a block of
    ``_BLOCK_BYTES`` and whose three bf16 terms fill a tile's lanes, a
    divisor of ``h`` that is a whole number of groups of ``k`` heads or
    a whole fraction of one."""
    fits = [d for d in range(1, min(h, _LANES // 3) + 1)
            if h % d == 0 and (d % k == 0 or k % d == 0)
            and d * p * n * 4 <= _BLOCK_BYTES]
    return max(fits, default=1)


def _bf16_terms(x):
    """``x`` float32 as three bf16 terms along a new leading axis, hi +
    mid + lo == x to float32's last bit.  (Rounded by
    ``reduce_precision``: a float32 -> bf16 -> float32 round trip in
    plain XLA is dropped as excess precision, and the terms after the
    first are then zero.)"""
    rnd = functools.partial(jax.lax.reduce_precision, exponent_bits=8,
                            mantissa_bits=7)
    hi = rnd(x)
    mid = rnd(x - hi)
    lo = rnd(x - hi - mid)
    return jnp.stack([hi, mid, lo]).astype(jnp.bfloat16)


def _kernel(idx_ref, da_ref, s_ref, dx_ref, b_ref, c_ref, o_ref, y_ref,
            e_ref, *, k):
    hb, p, n = s_ref.shape
    w, j = pl.program_id(0), pl.program_id(1)

    @pl.when((w == 0) & (j == 0))
    def _first():
        # head i's columns of the broadcast pick rows i, hb + i and
        # 2 hb + i of the terms: ones there, zeros elsewhere
        row = jax.lax.broadcasted_iota(jnp.int32, (_LANES, n), 0)
        for i in range(hb):
            e_ref[:, i * n:(i + 1) * n] = (
                (row == i) | (row == hb + i) | (row == 2 * hb + i)
            ).astype(e_ref.dtype)

    # dt x of every head of the block along the state's lanes, on the
    # MXU: exact -- a term times one, the three summed in float32
    dxb = jnp.dot(dx_ref[...], e_ref[...],
                  preferred_element_type=jnp.float32)       # (p, hb n)
    at = (w * pl.num_programs(1) + j) * hb
    col = jax.lax.broadcasted_iota(jnp.int32, (p, hb), 1)
    y = jnp.zeros((p, hb), jnp.float32)
    for i in range(hb):
        g = i // k                          # the head's group in the block
        s = da_ref[at + i] * s_ref[i] \
            + dxb[:, i * n:(i + 1) * n] * b_ref[g]
        o_ref[i] = s
        # the head's column of y: one select a tile, one store a block
        y = jnp.where(col == i,
                      jnp.sum(s * c_ref[g], axis=-1, keepdims=True), y)
    y_ref[...] = y


def mamba2_step(ssm_all, idx, da, dx, b, c, _interpret=False):
    """Every lane's state one step on: ``ssm_all`` (*lead, h, p, n)
    float32; ``idx`` (n_lead, W) int32, lane w's state is
    ``ssm_all[idx[0, w], ..., idx[-1, w]]``; ``da`` (W, h) the decay,
    ``dx`` (W, h, p) the input ``dt x``, ``b`` and ``c`` (W, g, n) a
    group of ``h / g`` heads.  Returns (ssm_all with the lanes' rows
    advanced, y (W, h, p) = the new state read out through ``c``)."""
    h, p, n = ssm_all.shape[-3:]
    n_lead = ssm_all.ndim - 3
    n_w, g = b.shape[:2]
    k = h // g
    hb = heads_a_block(h, p, n, k)
    n_j = h // hb
    gb = max(1, hb // k)                    # groups a block reads
    # a block's dt x, p down the sublanes: its three bf16 terms side by
    # side along the lanes, hi | mid | lo | zeros
    terms = _bf16_terms(dx.reshape(n_w, n_j, hb, p))    # (3, W, n_j, hb, p)
    terms = terms.transpose(1, 2, 4, 0, 3).reshape(n_w, n_j, p, 3 * hb)
    terms = jnp.pad(terms, ((0, 0),) * 3 + ((0, _LANES - 3 * hb),))

    def lane_rows(w, j, idx_ref, _):
        return tuple(idx_ref[a * n_w + w] for a in range(n_lead)) \
            + (j, 0, 0)

    def block(*tail):
        return pl.BlockSpec((None, None) + tail,
                            lambda w, j, *_: (w, j, 0, 0))

    state = pl.BlockSpec((None,) * n_lead + (hb, p, n), lane_rows)
    rows = pl.BlockSpec((None, gb, 1, n),
                        lambda w, j, *_: (w, (j * hb) // (gb * k), 0, 0))
    ssm_all, y = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_w, n_j),
            in_specs=[state, block(p, _LANES), rows, rows],
            out_specs=[state, block(p, hb)],
            scratch_shapes=[pltpu.VMEM((_LANES, hb * n), jnp.bfloat16)]),
        out_shape=[jax.ShapeDtypeStruct(ssm_all.shape, ssm_all.dtype),
                   jax.ShapeDtypeStruct((n_w, n_j, p, hb), jnp.float32)],
        # the arena (operand 2, after the two prefetched) is output 0
        input_output_aliases={2: 0},
        # lanes in order: dead lanes share the trash row, and the first
        # grid step fills the scratch the others read
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, 8 * hb * p * n * 4)),
        name="mamba2_step",
        interpret=_interpret,
    )(idx.reshape(-1).astype(jnp.int32), da.reshape(-1), ssm_all, terms,
      b.reshape(n_w, g, 1, n), c.reshape(n_w, g, 1, n))
    return ssm_all, y.transpose(0, 1, 3, 2).reshape(n_w, h, p)
