"""Benchmark harness — prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "device": {...}, ...}

Workloads (BASELINE.json configs):
  * ResNet-50 train throughput (primary metric), samples/sec/chip,
    bf16 amp, batch 128, graph mode (one donated jit executable).
  * BERT-base masked-LM train, S=512, batch 16 (config #4-ish).
  * MLP (config #1) and char-RNN LSTM (config #3) functional-parity
    workloads (lax.scan LSTM cell — the Pallas fused cell was deleted
    in round 4 after losing/tying at every measurable shape).

Timing protocol: each workload warms (compile + one replay + sync),
then runs ``repeats`` timed windows of ``iters`` steps; the reported
value is the MEDIAN window (min/max recorded for variance).  Device
sync (`float(loss)`) happens before the timer starts and at each window
boundary.

A measuring path: it runs on the accelerator JAX finds or not at all.
No chip, an unknown ``device_kind`` (no peak on record) or a workload
that raises ends the run with a non-zero exit and no result line.
"""

import argparse
import json
import os
import time

import numpy as np


def _step_flops(m):
    """FLOPs of one compiled training step, from XLA cost analysis."""
    for _, cost in m._graph_runner.cost_tables():
        f = cost.get("flops")
        if f:
            return float(f)
    return None


def _timed_windows(m, x, y, iters, repeats):
    """Median-of-windows timing: warm fully, then time `repeats`
    windows of `iters` steps each (sync at every boundary)."""
    m(x, y)  # trace + compile
    _, loss = m(x, y)
    float(loss.data)  # sync before the first timer starts
    dts = []
    for _ in range(repeats):
        t0 = time.time()
        for _ in range(iters):
            _, loss = m(x, y)
        lv = float(loss.data)  # force completion
        dts.append(time.time() - t0)
    assert np.isfinite(lv), f"loss diverged: {lv}"
    return dts


def _throughput(dts, batch, iters):
    """(median, min, max) samples/sec over the timed windows."""
    tps = sorted(batch * iters / dt for dt in dts)
    return tps[len(tps) // 2], tps[0], tps[-1]


def _timed_windows_multi(m, x, y, n_steps, repeats):
    """Multi-step dispatch timing (repeat mode): each window is ONE
    ``train_n_batches(..., n_steps=K)`` call — K optimizer steps per
    host dispatch, so the dispatch cost amortizes K× and the
    latency-bound workloads (MLP, char-RNN) report on-device
    throughput (round-5; the reference dispatches per iteration)."""
    def last_loss(ret):
        losses = ret[1] if isinstance(ret, (tuple, list)) else ret
        return float(np.asarray(losses.data)[-1])

    ret = m.train_n_batches(x, y, n_steps=n_steps)  # trace + compile
    ret = m.train_n_batches(x, y, n_steps=n_steps)  # warm replay
    lv = last_loss(ret)  # sync
    dts = []
    for _ in range(repeats):
        t0 = time.time()
        ret = m.train_n_batches(x, y, n_steps=n_steps)
        lv = last_loss(ret)  # force completion
        dts.append(time.time() - t0)
    assert np.isfinite(lv), f"loss diverged: {lv}"
    return dts


def bench_resnet50(batch=128, hw=224, iters=20, repeats=3, bf16=True):
    from singa_tpu import amp, device, opt, tensor
    from singa_tpu.models.resnet import resnet50

    amp.enable(bf16)
    try:
        dev = device.create_tpu_device(0)
        dev.SetRandSeed(0)
        m = resnet50(num_classes=1000)
        m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))

        rng = np.random.RandomState(0)
        x = tensor.from_numpy(
            rng.randn(batch, 3, hw, hw).astype(np.float32), dev)
        y = tensor.from_numpy(
            rng.randint(0, 1000, (batch,)).astype(np.int32), dev)
        m.compile([x], is_train=True, use_graph=True, sequential=False)
        dts = _timed_windows(m, x, y, iters, repeats)
        med, lo, hi = _throughput(dts, batch, iters)
        return {"tp": med, "tp_min": lo, "tp_max": hi,
                "flops": _step_flops(m),
                "steps_per_sec": med / batch}
    finally:
        amp.enable(False)


def bench_bert(batch=16, seqlen=512, iters=10, repeats=3, bf16=True):
    """BERT-base masked-LM training step (the second BASELINE workload)."""
    from singa_tpu import amp, device, opt, tensor
    from singa_tpu.models.bert import BertConfig, BertForMaskedLM

    amp.enable(bf16)
    try:
        dev = device.create_tpu_device(0)
        dev.SetRandSeed(0)
        cfg = BertConfig.base()
        cfg.max_position_embeddings = seqlen
        m = BertForMaskedLM(cfg)
        m.set_optimizer(opt.SGD(lr=1e-4, momentum=0.9))

        rng = np.random.RandomState(0)
        ids = tensor.from_numpy(
            rng.randint(0, cfg.vocab_size,
                        (batch, seqlen)).astype(np.int32), dev)
        labels = tensor.from_numpy(
            rng.randint(0, cfg.vocab_size,
                        (batch, seqlen)).astype(np.int32), dev)
        m.compile([ids], is_train=True, use_graph=True, sequential=False)
        dts = _timed_windows(m, ids, labels, iters, repeats)
        med, lo, hi = _throughput(dts, batch, iters)
        return {"tp": med, "tp_min": lo, "tp_max": hi,
                "flops": _step_flops(m),
                "steps_per_sec": med / batch}
    finally:
        amp.enable(False)


def bench_gpt2(batch=8, seqlen=1024, iters=10, repeats=3, bf16=True):
    """GPT-2 small causal-LM training step (beyond-parity transformer
    workload).  attn_impl='auto' resolves to FLASH at S=1024 since the
    round-4 crossover re-sweep (flash +31% over fused here; the full
    long-context regime is swept separately by bench_longctx.py)."""
    from singa_tpu import amp, device, opt, tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    amp.enable(bf16)
    try:
        dev = device.create_tpu_device(0)
        dev.SetRandSeed(0)
        cfg = GPT2Config.small(n_positions=seqlen, dropout=0.0)
        m = GPT2LMHead(cfg)
        m.set_optimizer(opt.SGD(lr=1e-4, momentum=0.9))

        rng = np.random.RandomState(0)
        ids = tensor.from_numpy(
            rng.randint(0, cfg.vocab_size,
                        (batch, seqlen)).astype(np.int32), dev)
        labels = tensor.from_numpy(
            rng.randint(0, cfg.vocab_size,
                        (batch, seqlen)).astype(np.int32), dev)
        m.compile([ids], is_train=True, use_graph=True, sequential=False)
        dts = _timed_windows(m, ids, labels, iters, repeats)
        med, lo, hi = _throughput(dts, batch, iters)
        return {"tp": med, "tp_min": lo, "tp_max": hi,
                "flops": _step_flops(m),
                "steps_per_sec": med / batch,
                "tokens_per_sec": med * seqlen}
    finally:
        amp.enable(False)


def _chip_tflops(size=4096, k0=200, k1=1200, repeats=5):
    """Fixed-work chip-health probe (round-5 verdict, weak #2): achieved
    bf16 matmul TFLOP/s from a jitted fori_loop of ``k`` dependent
    (size, size) matmuls, timed at k1 and k0 and DIFFERENCED — the
    dispatch RTT and loop overhead cancel exactly, leaving pure MXU
    time.  A per-iteration tanh keeps activations bounded (and defeats
    loop-invariant hoisting) at O(size²) cost vs the matmul's O(size³).

    Emitted per bench run as ``chip_tflops``: the same chip's achieved
    matmul rate beside the workload rows, so a slow chip is not read as
    a slow program."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(size, size) / np.sqrt(size), jnp.bfloat16)

    @partial(jax.jit, static_argnames=("k",))
    def loop(x, k):
        return jax.lax.fori_loop(
            0, k, lambda i, y: jnp.tanh(y @ a), x)

    def timed(k):
        float(loop(a, k=k)[0, 0].astype(jnp.float32))  # compile + warm
        ts = []
        for _ in range(repeats):
            t0 = time.time()
            float(loop(a, k=k)[0, 0].astype(jnp.float32))
            ts.append(time.time() - t0)
        return sorted(ts)[len(ts) // 2]

    dt = timed(k1) - timed(k0)
    if dt <= 0:
        return None
    return round(2 * size ** 3 * (k1 - k0) / dt / 1e12, 1)


def _dispatch_rtt_ms(n=20):
    """Host→device dispatch round-trip (tiny no-op jit + scalar
    readback, median of n) — what a latency-bound workload
    (charrnn/mlp) pays per dispatch, recorded beside its row."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros(())
    float(f(x))  # compile + first transfer
    ts = []
    for _ in range(n):
        t0 = time.time()
        float(f(x))
        ts.append(time.time() - t0)
    return round(sorted(ts)[n // 2] * 1000, 3)


def bench_gpt2_decode(batch=8, prompt_len=128, n_new=512, repeats=3,
                      bf16=True):
    """KV-cached batched inference (models/gpt2_decode.py): GPT-2 small,
    batch of right-padded prompts, greedy, bf16 weights (decode is
    weight-read-bound; bf16 measured ≈2× over fp32).  The whole
    generation is ONE compiled executable, so dispatch is paid once
    per call.

    ``decode_tokens_per_sec`` is STEADY-STATE: timed at n_new and
    n_new/2 and differenced, which cancels prefill + dispatch + sampling
    warmup exactly.  The whole differencing procedure repeats ``outer``
    times and the MEDIAN estimate is reported with its [min, max]
    spread.  ``ragged`` adds a second row for
    a mixed-length batch (lengths 0.5×–1.0× prompt_len) decoded through
    the round-5 left-padding fast path — the number users get without
    length-sorting their batches; steady-state differencing keeps it
    comparable to the uniform row (per-token decode work is
    length-independent once the cache is live).  ``first_token_ms`` is the raw
    latency of a prefill+1-token call (RTT included — subtract
    dispatch_rtt_ms for the on-device time)."""
    import jax
    import jax.numpy as jnp

    from singa_tpu import device, tensor
    from singa_tpu.models import gpt2_decode
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    dev = device.create_tpu_device(0)
    dev.SetRandSeed(0)
    # attn_impl pinned to fused: the layer-stack forward here only
    # exists to deferred-init the params (decode itself is the pure-jnp
    # KV path); S=1024 auto would resolve to the flash kernel, which an
    # 8-token init forward has no use for
    cfg = GPT2Config.small(n_positions=1024, dropout=0.0,
                           attn_impl="fused")
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 8), np.int32), dev)],
              is_train=False, use_graph=False)
    params = gpt2_decode.extract_params(
        m, dtype=jnp.bfloat16 if bf16 else None)

    rng = np.random.RandomState(0)
    ctx = cfg.n_positions
    window = np.zeros((batch, ctx), np.int32)
    window[:, :prompt_len] = rng.randint(0, cfg.vocab_size,
                                         (batch, prompt_len))
    ids = jnp.asarray(window)
    # ragged batch: lengths 0.5×–1.0× prompt_len (mean ~0.78×; less
    # prefill work than the uniform row, same steady-state decode
    # work), LEFT-padded
    r_lens = np.asarray(
        [prompt_len, prompt_len * 3 // 4, prompt_len // 2,
         prompt_len * 7 // 8, prompt_len * 5 // 8,
         prompt_len * 13 // 16, prompt_len * 9 // 16,
         prompt_len * 15 // 16][:batch], np.int32)
    r_lens = np.resize(r_lens, batch)
    max_len = int(r_lens.max())
    r_window = np.zeros((batch, ctx), np.int32)
    for i, ln in enumerate(r_lens):
        r_window[i, max_len - ln:max_len] = rng.randint(
            0, cfg.vocab_size, ln)
    r_ids = jnp.asarray(r_window)
    r_start = jnp.asarray(max_len - r_lens)
    keys = jax.random.split(jax.random.PRNGKey(0), batch)
    args = (cfg.n_head, float(cfg.layer_norm_eps))

    def run(nn):
        # equal-length prompts: the uniform fast path (shared position,
        # batched cache writes) — what generate() auto-selects here
        out = gpt2_decode.generate_cached_uniform(
            params, ids, prompt_len, *args, nn, ctx, True,
            jnp.float32(1.0), keys)
        np.asarray(out)  # sync

    def run_ragged(nn):
        out = gpt2_decode.generate_cached_uniform(
            params, r_ids, max_len, *args, nn, ctx, True,
            jnp.float32(1.0), keys, start=r_start)
        np.asarray(out)

    def timed(fn, nn):
        ts = []
        for _ in range(repeats):
            t0 = time.time()
            fn(nn)
            ts.append(time.time() - t0)
        return sorted(ts)[len(ts) // 2]

    def steady(fn, outer=3):
        fn(n_new)                # compile + warm (full)
        fn(n_new // 2)           # compile + warm (half)
        ests = sorted(
            batch * (n_new - n_new // 2)
            / (timed(fn, n_new) - timed(fn, n_new // 2))
            for _ in range(outer))
        return ests[len(ests) // 2], ests[0], ests[-1]

    med, lo, hi = steady(run)
    r_med, r_lo, r_hi = steady(run_ragged)
    run(1)
    t_first = timed(run, 1)
    return {"tokens_per_sec": med,
            "spread": [round(lo, 1), round(hi, 1)],
            "ragged_tokens_per_sec": r_med,
            "ragged_spread": [round(r_lo, 1), round(r_hi, 1)],
            "ragged_lens": r_lens.tolist(),
            "first_token_ms": round(t_first * 1000, 1),
            "batch": batch, "prompt_len": prompt_len, "n_new": n_new,
            "sampling": "greedy",
            "dtype": "bf16" if bf16 else "fp32",
            "model": "gpt2-small (randomly initialized)"}


def bench_mlp(batch=512, data_size=784, iters=20000, repeats=3):
    """Config #1: MLP (MNIST-shaped), fp32 — functional-parity workload.
    Runs through multi-step dispatch (train_n_batches repeat mode): all
    ``iters`` steps per window compile into ONE lax.scan executable, so
    the reported number is on-device throughput — the single dispatch
    RTT amortizes iters×, instead of one RTT per step."""
    from singa_tpu import device, opt, tensor
    from singa_tpu.models.mlp import MLP

    class LossOnlyMLP(MLP):
        # return only the (K,) loss history from the scan — stacking
        # the (K, B, 10) per-step logits at K=20000 would burn ~400 MB
        # of HBM writes per window for outputs nobody reads
        def train_one_batch(self, x, y):
            _, loss = super().train_one_batch(x, y)
            return loss

    dev = device.create_tpu_device(0)
    dev.SetRandSeed(0)
    m = LossOnlyMLP(data_size=data_size, perceptron_size=100,
                    num_classes=10)
    m.set_optimizer(opt.SGD(lr=0.05, momentum=0.9))
    rng = np.random.RandomState(0)
    x = tensor.from_numpy(
        rng.randn(batch, data_size).astype(np.float32), dev)
    y = tensor.from_numpy(
        rng.randint(0, 10, (batch,)).astype(np.int32), dev)
    m.compile([x], is_train=True, use_graph=True, sequential=False)
    dts = _timed_windows_multi(m, x, y, iters, repeats)
    med, lo, hi = _throughput(dts, batch, iters)
    return {"tp": med, "tp_min": lo, "tp_max": hi,
            "steps_per_dispatch": iters}


def bench_charrnn(batch=64, seqlen=100, vocab=100, hidden=256, layers=2,
                  iters=1000, repeats=3):
    """Config #3: char-RNN LSTM (lax.scan cell — the Pallas fused cell
    was deleted in round 4 after losing/tying at every measurable
    shape; see ops/rnn.py RNNHandle docstring).  Multi-step dispatch
    (repeat mode): one executable runs all ``iters`` steps, deleting
    the per-step RTT tax.  The bench model returns only the (K,) loss
    history, not (K, B·T, V) stacked logits, to keep HBM flat."""
    from singa_tpu import device, opt, tensor
    from singa_tpu import layer, model, autograd
    from singa_tpu.models.char_rnn import one_hot

    class BenchCharRNN(model.Model):
        def __init__(self):
            super().__init__()
            self.lstm = layer.LSTM(hidden, num_layers=layers,
                                   batch_first=True)
            self.dense = layer.Linear(vocab)
            self.loss_fn = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            yv, _ = self.lstm(x)
            return self.dense(autograd.reshape(yv, (-1, hidden)))

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = self.loss_fn(out, autograd.reshape(y, (-1,)))
            self.optimizer(loss)
            return loss

    dev = device.create_tpu_device(0)
    dev.SetRandSeed(0)
    m = BenchCharRNN()
    m.set_optimizer(opt.SGD(lr=0.1))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seqlen))
    x = tensor.from_numpy(one_hot(ids, vocab), dev)
    y = tensor.from_numpy(
        np.roll(ids, -1, axis=1).astype(np.int32), dev)
    m.compile([x], is_train=True, use_graph=True, sequential=False)
    dts = _timed_windows_multi(m, x, y, iters, repeats)
    med, lo, hi = _throughput(dts, batch, iters)
    return {"tp": med, "tp_min": lo, "tp_max": hi,
            "steps_per_dispatch": iters}


def main():
    from singa_tpu import observe

    ap = argparse.ArgumentParser(
        description="singa_tpu training benchmark harness")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="trace the whole bench run (compile spans with "
                         "XLA cost tables, train.step / train.dispatch, "
                         "opt/update traces) and write a Chrome "
                         "trace-event JSON there")
    ap.add_argument("--health-out", default=None, metavar="PATH",
                    help="also write observe.health_report() (MFU from "
                         "the XLA cost tables, step-time summaries, "
                         "watchdog state) as JSON")
    cli = ap.parse_args()
    if cli.trace_out:
        observe.enable()
    # active monitoring rides the whole bench (flight recorder + hang
    # watchdog + MFU meter); its overhead is two clock calls and an
    # EWMA update per dispatch — the acceptance bar is < 2% tokens/s
    # and the instrumented dispatches are ≥ milliseconds each.  The
    # timeout is generous: a cold resnet/bert compile legitimately
    # runs a long while with no dispatch heartbeat in between.
    # crash_handler: a bench killed mid-run (uncaught exception,
    # SIGTERM from a CI timeout) leaves a monitor-crash-*.json bundle.
    observe.monitor.start(watchdog_timeout_s=900.0, crash_handler=True)

    batch = int(os.environ.get("BENCH_BATCH", "128"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    repeats = int(os.environ.get("BENCH_REPEATS", "3"))
    bert_batch = int(os.environ.get("BENCH_BERT_BATCH", "16"))
    bf16 = os.environ.get("BENCH_BF16", "1") != "0"
    skip = set(os.environ.get("BENCH_SKIP", "").split(","))

    import jax

    from singa_tpu.observe.monitor import peak_flops

    d0 = jax.devices()[0]
    if d0.platform == "cpu":
        raise SystemExit(
            "bench.py measures the accelerator and JAX found only "
            f"{len(jax.devices())} cpu device(s); a CPU timing is not "
            "a result (tests/ covers correctness on the CPU)")
    # the chip's bf16 peak — an unknown device_kind raises here, before
    # any workload runs, instead of printing null MFUs after them.
    # MFU is only reported for bf16 runs: TPUs execute fp32 matmuls as
    # multi-pass bf16, so an fp32 "peak" denominator would be fiction.
    peak = peak_flops(d0.device_kind) if bf16 else None
    rtt_ms = _dispatch_rtt_ms()
    chip_tflops = _chip_tflops()

    # a workload that raises ends the run: no partial result line
    results = {"resnet50": bench_resnet50(
        batch=batch, iters=iters, repeats=repeats, bf16=bf16)}
    for name, fn in (
        ("bert", lambda: bench_bert(batch=bert_batch, repeats=repeats,
                                    bf16=bf16)),
        ("gpt2", lambda: bench_gpt2(repeats=repeats, bf16=bf16)),
        ("mlp", lambda: bench_mlp(repeats=repeats)),
        ("charrnn", lambda: bench_charrnn(repeats=repeats)),
    ):
        if name not in skip:
            results[name] = fn()
    resnet = results["resnet50"]

    def mfu(r):
        if r and r.get("flops") and r.get("steps_per_sec") and peak:
            return round(r["flops"] * r["steps_per_sec"] / peak, 4)
        return None

    out = {
        "metric": "resnet50_train_throughput",
        "value": round(resnet["tp"], 2),
        "unit": "samples/sec/chip",
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(jax.devices())},
        "repeats": repeats,
        "dispatch_rtt_ms": rtt_ms,
        "chip_tflops": chip_tflops,
        "resnet50_mfu": mfu(resnet),
        "bert_mfu": mfu(results.get("bert")),
        "gpt2_mfu": mfu(results.get("gpt2")),
        "mfu_denominator": "bf16_peak" if peak else None,
        "bf16": bf16,
        "batch": batch,
        "bert_batch": bert_batch,
        "seqlen": 512,
    }
    for name, r in results.items():
        out[f"{name}_train_throughput"] = round(r["tp"], 2)
        out[f"{name}_tp_spread"] = [round(r["tp_min"], 2),
                                    round(r["tp_max"], 2)]
        if "steps_per_dispatch" in r:
            out[f"{name}_steps_per_dispatch"] = r["steps_per_dispatch"]
    # KV-cached inference path (one executable per generation)
    if "decode" not in skip:
        dec = bench_gpt2_decode(repeats=repeats)
        out["decode_tokens_per_sec"] = round(dec["tokens_per_sec"], 1)
        out["decode_tp_spread"] = dec["spread"]
        out["decode_ragged_tokens_per_sec"] = round(
            dec["ragged_tokens_per_sec"], 1)
        out["decode_ragged_tp_spread"] = dec["ragged_spread"]
        out["decode_first_token_ms"] = dec["first_token_ms"]
        out["decode_config"] = {
            k: dec[k] for k in ("batch", "prompt_len", "n_new",
                                "sampling", "dtype", "model",
                                "ragged_lens")}
    # observe registry: graph cache hit/miss, train.steps, opt.updates —
    # the attribution surface for "where did this bench's time go"
    out["registry"] = observe.registry().snapshot()
    # active-layer summary: MFU/model-flops gauges (XLA step flops ×
    # train.steps rate ÷ chip peak — the per-workload resnet50_mfu
    # above stays the per-workload number; this one is the whole-run
    # rate), per-process step-time summaries, watchdog hang/anomaly
    # state, flight-recorder status.  include_registry=False: the
    # snapshot already rides the top-level `registry` key
    out["health"] = observe.health_report(include_registry=False)
    observe.monitor.stop()
    if cli.trace_out:
        observe.disable()
        out["trace"] = {
            "path": cli.trace_out,
            "trace_events": observe.export.write_chrome_trace(
                cli.trace_out, metadata={"bench": "train"}),
        }
    # strict JSON on stdout/disk: nan (MFU on unknown backends, empty
    # histogram summaries) becomes null — jq-safe BENCH trajectory
    out = observe.export.json_sanitize(out)
    if cli.health_out:
        with open(cli.health_out, "w") as f:
            json.dump(out["health"], f, default=str, allow_nan=False)
    print(json.dumps(out, default=str, allow_nan=False))


if __name__ == "__main__":
    main()
