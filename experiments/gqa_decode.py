"""Measure the GQA decode win: steady-state tokens/sec vs n_kv_head.

GQA shrinks the K/V cache (and its per-token read traffic) by
n_head / n_kv_head while leaving per-token GEMM work almost unchanged,
so on a cache-read-bound decode loop fewer KV heads should mean more
tokens/sec.  Same two-length differencing methodology as
bench.bench_gpt2_decode (cancels prefill + dispatch + sampling warmup);
GPT-2 small geometry, bf16 weights, greedy, the bench decode config
(batch 8, prompt 128, 512 new tokens).

Run on the real chip:  python experiments/gqa_decode.py
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")


def measure(n_kv_head, batch=8, prompt_len=128, n_new=512, repeats=3,
            quant_cache=False, ctx=1024, attn_window=None):
    import jax
    import jax.numpy as jnp

    from singa_tpu import device, tensor
    from singa_tpu.models import gpt2_decode
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    dev = device.create_tpu_device(0)
    dev.SetRandSeed(0)
    cfg = GPT2Config.small(n_positions=ctx, dropout=0.0,
                           attn_impl="fused", n_kv_head=n_kv_head,
                           attn_window=attn_window)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 8), np.int32), dev)],
              is_train=False, use_graph=False)
    params = gpt2_decode.extract_params(m, dtype=jnp.bfloat16)

    rng = np.random.RandomState(0)
    window = np.zeros((batch, ctx), np.int32)
    window[:, :prompt_len] = rng.randint(0, cfg.vocab_size,
                                         (batch, prompt_len))
    ids = jnp.asarray(window)
    keys = jax.random.split(jax.random.PRNGKey(0), batch)

    def run(nn):
        out = gpt2_decode.generate_cached_uniform(
            params, ids, prompt_len, cfg.n_head,
            float(cfg.layer_norm_eps), nn, ctx, True,
            jnp.float32(1.0), keys, quant_cache=quant_cache,
            window=gpt2_decode._norm_window(cfg))
        np.asarray(out)

    def timed(nn):
        ts = []
        for _ in range(repeats):
            t0 = time.time()
            run(nn)
            ts.append(time.time() - t0)
        return sorted(ts)[len(ts) // 2]

    run(n_new)        # compile + warm
    run(n_new // 2)
    ests = sorted(
        batch * (n_new - n_new // 2) / (timed(n_new) - timed(n_new // 2))
        for _ in range(3))
    d = cfg.n_embd // cfg.n_head
    # bf16 values are 2 bytes; int8 is 1 byte plus a 4-byte f32 scale
    # per (token, head) row of D values
    bytes_per = 1 + 4.0 / d if quant_cache else 2
    span = ctx if attn_window is None else min(attn_window, ctx)
    cache_mib = (2 * cfg.n_layer * batch * cfg.n_kv_head * span
                 * d * bytes_per) / 2**20
    return ests[1], ests[0], ests[-1], cache_mib


if __name__ == "__main__":
    for n_kv in (12, 4, 2, 1):
        for quant in (False, True):
            med, lo, hi, cache = measure(n_kv, quant_cache=quant)
            tag = "int8" if quant else "bf16"
            print(f"n_kv_head={n_kv:2d} cache={tag}: {med:7.1f} tok/s "
                  f"[{lo:.1f}, {hi:.1f}]  kv_cache={cache:.0f} MiB",
                  flush=True)
    # long-context rows: at ctx=4096 the cache dominates the weight
    # reads (1152 vs ~250 MiB at full heads) — the regime the int8
    # cache targets
    for n_kv in (12, 4):
        for quant in (False, True):
            med, lo, hi, cache = measure(n_kv, quant_cache=quant,
                                         ctx=4096)
            tag = "int8" if quant else "bf16"
            print(f"ctx=4096 n_kv_head={n_kv:2d} cache={tag}: "
                  f"{med:7.1f} tok/s [{lo:.1f}, {hi:.1f}]  "
                  f"kv_cache={cache:.0f} MiB", flush=True)
    # sliding window at long context: the O(W) rolling cache should
    # put ctx=4096 decode back at ~ctx=W cost
    med, lo, hi, cache = measure(12, ctx=4096, attn_window=1024)
    print(f"ctx=4096 window=1024 cache=bf16: {med:7.1f} tok/s "
          f"[{lo:.1f}, {hi:.1f}]  kv_cache={cache:.0f} MiB",
          flush=True)
