"""Shared by the benchmark's tests: tiny configurations and cells that a
CPU test run can hold.  Everything goes through the same loader, drivers,
readers and ``run.measure`` as a chip run; only the sizes differ."""

import copy
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import loader  # noqa: E402

TINY_SIZES = dict(vocab_size=256, n_positions=128, n_ctx=128, n_embd=64,
                  n_layer=2, n_head=4, n_inner=128)
TINY_ENGINE = dict(block_size=8, num_blocks=96, max_slots=8,
                   prefill_token_budget=16, dtype="float32")
# closed loop: a toy engine on a fast host ends 16 short requests a caller
# inside a 2 s run, and the driver refuses a window whose callers ran dry;
# 4 x 64 requests a caller outlast any test-size window
TINY_ROUNDS = 64
TINY_LENGTHS = dict(prompt_len={"dist": "uniform", "min": 8, "max": 60},
                    reply_len={"dist": "uniform", "min": 4, "max": 20})


def tiny_cell(name, root=loader.ROOT):
    """The named cell with its configuration and traffic cut to test
    size.  Widths are cut only here, never in a file the driver runs."""
    cell = copy.deepcopy(loader.load_cell(name, root=root))
    cfg = dict(cell["config"], **TINY_SIZES)
    mix = dict(cell["traffic"])
    if "engine" in cfg:
        cfg["engine"] = dict(cfg["engine"], **TINY_ENGINE)
        mix.update(TINY_LENGTHS, preroll_s=0.5)
        if "clients" in mix["arrivals"]:
            mix["arrivals"] = {"clients": 4, "stagger_s": 0.3}
            mix["rounds"] = TINY_ROUNDS
        else:
            mix["arrivals"] = dict(mix["arrivals"], rate_per_s=20.0)
        if "sessions" in mix:
            mix["sessions"] = dict(mix["sessions"], system_prompt_len=24)
            mix["prompt_len"] = {"dist": "uniform", "min": 4, "max": 12}
            mix["reply_len"] = {"dist": "uniform", "min": 2, "max": 6}
        cell["cell"]["trace_window"] = {"length_s": 0.5}
    else:
        cfg["job"] = dict(cfg["job"], attn_impl="fused")
        mix.update(rows_per_chip=2, seq_len=64)
    cell["config"], cell["traffic"] = cfg, mix
    return cell


def measure(cell, limits, seconds=1.5, seed=2147483900, trace=False,
            **kw):
    """One whole run on the CPU devices, minus the look for a chip.
    Returns the parsed result line."""
    import jax

    from benchmark import run

    cell["cell"]["limits"] = limits
    devices = jax.devices()[:cell["chips"]]
    line = run.measure(cell, devices, seed, seconds, trace,
                       time.perf_counter(), **kw)
    return json.loads(line)


def copy_data_tree(dst):
    """A checkout's worth of the benchmark's DATA (manifest, cells,
    traffic, metrics, configs) under ``dst``, for rehearsing additions."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    for d in ("cells", "traffic", "metrics", "configs"):
        shutil.copytree(os.path.join(ROOT, "benchmark", d),
                        os.path.join(dst, "benchmark", d))
    return dst
