"""Long-context crossover harness (SURVEY.md §5.7; round-2 verdict
item #5): GPT-2 small on the real chip at S in {512 .. 32768},
fused vs flash attention x remat off/on.

Each config runs in its own SUBPROCESS so peak-HBM readings are clean
and an OOM kills one cell, not the sweep — an OOM *is* a data point
(the fused S x S path is EXPECTED to die first; flash's O(S·D) HBM
footprint surviving it is the kernel's reason to exist).

Tokens/step is held constant (B·S = 16·512 = 8192) up to S=8192; at
S=16384/32768 the batch floors at 1, so tokens/step grows to S (2x/4x
nominal).  Every cell therefore records ``tokens_per_step`` and
``step_ms_per_8k_tokens`` (= step_ms · 8192 / tokens_per_step) — the
normalized column is the one that is like-for-like across all S;
``tokens_per_sec`` is already per-token and needs no normalization.
Peak-HBM cells at floored-batch S reflect the LARGER step (more
tokens resident), which only understates the flash kernel's advantage.
Output: LONGCTX.json + one summary line.

    python bench_longctx.py --out LONGCTX.json
"""

import argparse
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.abspath(__file__))

_CHILD = r"""
import json, sys, time
import numpy as np

seqlen, impl, remat, iters = (int(sys.argv[1]), sys.argv[2],
                              sys.argv[3] == "1", int(sys.argv[4]))
tokens = 16 * 512
batch = max(1, tokens // seqlen)

from singa_tpu import amp, device, opt, tensor
from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead

amp.enable(True)
dev = device.create_tpu_device(0)
dev.SetRandSeed(0)
cfg = GPT2Config.small(n_positions=seqlen, dropout=0.0,
                       attn_impl=impl, remat=remat)
m = GPT2LMHead(cfg)
m.set_optimizer(opt.SGD(lr=1e-4, momentum=0.9))
rng = np.random.RandomState(0)
ids = tensor.from_numpy(
    rng.randint(0, cfg.vocab_size, (batch, seqlen)).astype(np.int32), dev)
labels = tensor.from_numpy(
    rng.randint(0, cfg.vocab_size, (batch, seqlen)).astype(np.int32), dev)
m.compile([ids], is_train=True, use_graph=True)
m(ids, labels)
m(ids, labels)
_, loss = m(ids, labels)
float(loss.data)
t0 = time.time()
for _ in range(iters):
    _, loss = m(ids, labels)
lv = float(loss.data)
dt = (time.time() - t0) / iters
# one process per cell, so the allocator's high-water mark is this
# cell's own (activations/residuals between fwd and bwd included — the
# quantity the flash kernel exists to shrink)
peak = dev.jax_device.memory_stats()["peak_bytes_in_use"]
print("CELL " + json.dumps({
    "seqlen": seqlen, "impl": impl, "remat": remat, "batch": batch,
    "tokens_per_step": batch * seqlen,
    "tokens_per_sec": round(batch * seqlen / dt, 1),
    "step_ms": round(dt * 1e3, 2),
    "step_ms_per_8k_tokens": round(dt * 1e3 * 8192 / (batch * seqlen), 2),
    "peak_hbm_gib": round(peak / 2**30, 3),
    "device_kind": dev.jax_device.device_kind,
    "loss": round(lv, 3)}), flush=True)
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default="LONGCTX.json")
    ap.add_argument("--seqlens",
                    default="512,1024,2048,4096,8192,16384,32768")
    args = ap.parse_args()

    cells = []
    for s in (int(x) for x in args.seqlens.split(",")):
        for impl in ("fused", "flash"):
            # remat only matters for fused (the flash kernels already
            # recompute probabilities blockwise in backward; GPT2's
            # remat flag is a no-op on the flash path)
            for remat in ((False, True) if impl == "fused" else (False,)):
                p = subprocess.run(
                    [sys.executable, "-c", _CHILD, str(s), impl,
                     "1" if remat else "0", str(args.iters)],
                    capture_output=True, text=True, timeout=1200,
                    cwd=_REPO)
                cell = None
                for line in p.stdout.splitlines():
                    if line.startswith("CELL "):
                        cell = json.loads(line[5:])
                if cell is None:
                    err = (p.stderr or "")[-400:]
                    oom = ("RESOURCE_EXHAUSTED" in p.stderr
                           or "Out of memory" in p.stderr
                           or "out of memory" in p.stderr)
                    cell = {"seqlen": s, "impl": impl, "remat": remat,
                            "failed": True, "oom": oom,
                            "error_tail": err if not oom else
                            "RESOURCE_EXHAUSTED (OOM)"}
                cells.append(cell)
                print(json.dumps(cell), flush=True)

    # crossover: at each S, which impl wins on throughput (remat=False
    # preferred; a failed cell loses by definition)
    winners = {}
    for s in sorted({c["seqlen"] for c in cells}):
        best = None
        for c in cells:
            if c["seqlen"] != s or c.get("failed"):
                continue
            if best is None or c["tokens_per_sec"] > best["tokens_per_sec"]:
                best = c
        winners[str(s)] = (None if best is None else
                           {"impl": best["impl"], "remat": best["remat"],
                            "tokens_per_sec": best["tokens_per_sec"]})
    # the parent never imports jax (a parent that touched it would
    # hold the chip against its own children): the device is whatever
    # the cells report
    kinds = sorted({c["device_kind"] for c in cells
                    if "device_kind" in c})
    result = {"workload": "gpt2-small causal LM train, 8192 tokens/step "
                          "(batch floors at 1 past S=8192 — see "
                          "tokens_per_step / step_ms_per_8k_tokens per "
                          "cell), bf16 amp",
              "backend": kinds[0] if len(kinds) == 1 else kinds,
              "cells": cells, "winner_by_seqlen": winners}
    with open(os.path.join(_REPO, args.out), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"winner_by_seqlen": winners}))


if __name__ == "__main__":
    main()
