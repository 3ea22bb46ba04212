"""On-chip Mosaic sweep for the flash-attention kernel paths.

The pytest suite runs on the CPU backend (tests/conftest.py), where
Pallas executes in interpret mode — so a kernel that passes the tests
can still be refused by Mosaic on hardware (oversized tiles, an
unsupported relayout).  ``chip_smoke.py`` proves the GPT-2 shapes
compile; this script sweeps the other kernel entries the wrapper can
select, ON THE CHIP, by hand through the chip tool:

  1. pad-to-block wrapper: unaligned S=1537, causal, fwd + grad
  2. general (B,1,S,S) mask streamed as kernel tiles, fwd + grad
  3. padded head dim D=192 (shrunken block budget)
  4. the flash kernel INSIDE shard_map on a 1-device ('seq') mesh
     (manual-mode Mosaic, the ring-attention composition), fwd + grad
  5. per-head (1,H,S,S) ALiBi-layout mask (modulo index map)

    python tpu_smoke.py    # prints one JSON line; exit 1 if any failed

It writes nothing into the checkout.
"""

import json
import math
import time

import numpy as np

NEG_INF = -1e30
# f32 matmuls ride the MXU as bf16 passes at DEFAULT precision, so the
# oracle comparison tolerance is bf16-scale, not f32-scale
ATOL = 1e-2


def _ref(q, k, v, mask=None, causal=False):
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    sc = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                    k.astype(jnp.float32)) / math.sqrt(d)
    if mask is not None:
        sc = sc + mask
    if causal:
        s = q.shape[2]
        cm = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(cm[None, None], sc, NEG_INF)
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(sc, -1),
                      v.astype(jnp.float32)).astype(q.dtype)


def main():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from singa_tpu.ops.pallas.flash_attention import flash_attention
    from singa_tpu.parallel.ring_attention import ring_self_attention

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"tpu_smoke needs the TPU backend, found {backend!r} (the "
            f"CPU runs interpret mode, which is what this script "
            f"exists to go beyond)")

    rng = np.random.RandomState(0)

    def qkv(b, h, s, d):
        return tuple(jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
                     for _ in range(3))

    checks = []

    def check(name, fn):
        t0 = time.time()
        try:
            fn()
            checks.append({"name": name, "ok": True,
                           "seconds": round(time.time() - t0, 1)})
        except Exception as e:  # record, keep sweeping
            checks.append({"name": name, "ok": False,
                           "error": f"{type(e).__name__}: {e}"[:300]})

    def c1():
        q, k, v = qkv(1, 2, 1537, 64)
        o = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(_ref(q, k, v, causal=True)),
            atol=ATOL)
        g = jax.jit(jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, causal=True) ** 2)))(q)
        gr = jax.grad(lambda q: jnp.sum(_ref(q, k, v, causal=True) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   atol=5e-2, rtol=5e-2)

    def c2():
        q, k, v = qkv(2, 2, 1024, 64)
        mask = jnp.asarray(np.where(
            rng.rand(2, 1, 1024, 1024) > 0.2, 0.0, -1e9)
            .astype(np.float32))
        o = jax.jit(flash_attention)(q, k, v, mask)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(_ref(q, k, v, mask)), atol=ATOL)
        g = jax.jit(jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, mask) ** 2)))(q)
        gr = jax.grad(lambda q: jnp.sum(_ref(q, k, v, mask) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   atol=5e-2, rtol=5e-2)

    def c3():
        q, k, v = qkv(1, 2, 512, 192)
        o = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(_ref(q, k, v, causal=True)),
            atol=ATOL)

    def c4():
        q, k, v = qkv(1, 2, 2048, 64)
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("seq",))
        spec = P(None, None, "seq", None)
        f = jax.jit(jax.shard_map(
            lambda q_, k_, v_: ring_self_attention(
                q_, k_, v_, "seq", causal=True, use_flash=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False))
        o = f(q, k, v)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(_ref(q, k, v, causal=True)),
            atol=ATOL)
        g = jax.jit(jax.grad(lambda q: jnp.sum(f(q, k, v) ** 2)))(q)
        gr = jax.grad(lambda q: jnp.sum(_ref(q, k, v, causal=True) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   atol=5e-2, rtol=5e-2)

    def c5():
        q, k, v = qkv(2, 4, 512, 64)
        alibi = jnp.asarray(
            rng.randn(1, 4, 512, 512).astype(np.float32) * 0.1)
        o = jax.jit(flash_attention)(q, k, v, alibi)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(_ref(q, k, v, alibi)), atol=ATOL)

    check("pad_to_block_unaligned_S1537_causal_fwd_grad", c1)
    check("general_mask_B1SS_kernel_tiles_fwd_grad", c2)
    check("wide_head_D192_padded", c3)
    check("shard_map_1dev_mesh_ring_flash_fwd_grad", c4)
    check("per_head_alibi_mask_1HSS", c5)

    out = {
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "ok": all(c["ok"] for c in checks),
        "checks": checks,
    }
    print(json.dumps(out))
    raise SystemExit(0 if out["ok"] else 1)


if __name__ == "__main__":
    main()
