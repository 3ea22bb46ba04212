"""Shares of a roofline or of a peak.  The work comes from the shape
functions under ``benchmark/work/``; the time from the device trace (or,
for ``mfu``, the window's own rate); the peaks from
``benchmark/harness/peaks.py``.

* ``mfu``            train FLOPs of one step / the time from one step's
                     start on the device to the next one's / (chips x peak)
* ``decode_step``    bytes a decode step must read (weights + the live
                     keys and values, once) / HBM bandwidth / the median
                     device time of one decode dispatch
* ``flash_attention``  the three kernels' shape-derived bound / their
                     device time per step
"""

import re
from statistics import median

from benchmark.harness import loader
from benchmark.harness import trace_reduce as tr


def read(ctx, what, module=None, kernels=None):
    run, peaks = ctx["run"], ctx["peaks"]
    ref = loader.load_module("references", ctx["cell"]["config"]["family"])
    sizes = ref.sizes_of(ctx["cell"]["config"])
    c = run["counters"]
    trace = ctx.get("trace")
    if trace is None:
        return None
    t0, t1 = ctx["trace_window"]
    if what == "mfu":
        steps = tr.module_runs(trace, module, t0, t1)
        if not steps:
            return None
        work = loader.load_module("work", "gpt2_train")
        flops = work.train_flops_per_token(sizes, c["seq_len"]) \
            * c["batch"] * c["seq_len"]
        # from one step to the next on the device, so that what the
        # profiler costs the host does not count against the program
        starts = sorted(s for s, _ in steps)
        period = median(b - a for a, b in zip(starts, starts[1:])) \
            if len(starts) > 2 else median(d for _, d in steps)
        return 100.0 * flops / period \
            / (c["chips"] * peaks["bf16_flops_per_s"])
    if what == "decode_step":
        runs = tr.module_runs(trace, module, t0, t1)
        if not runs or not c.get("live_slots_mean"):
            return None
        work = loader.load_module("work", "gpt2_decode")
        live_positions = c["live_slots_mean"] * c["live_positions_mean"]
        least = work.decode_step_bytes(sizes, live_positions) \
            / peaks["hbm_bytes_per_s"]
        return 100.0 * least / median(d for _, d in runs)
    if what == "flash_attention":
        steps = tr.module_runs(trace, module, t0, t1)
        if not steps:
            return None
        work = loader.load_module("work", "flash_attention")
        rows = c["batch"] // c["chips"]
        bound = work.bound_seconds(rows, sizes["H"], c["seq_len"],
                                   sizes["E"] // sizes["H"], peaks)
        least = sizes["L"] * sum(s for s, _ in bound.values())
        rx = re.compile(kernels)
        spent = tr.ops_inside(trace, steps,
                              lambda n: bool(rx.search(n))) / len(steps)
        if not spent:
            return None
        return 100.0 * least / spent
    raise ValueError(f"unknown roofline reading {what!r}")
