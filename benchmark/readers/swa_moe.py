"""Numbers of the ``swa_moe`` family that need what its decode program
counted about itself: arguments of the program's ``singa/serve.step``
spans -- ``full_rows`` and ``window_rows`` (the cached positions the
step's lanes hold, all of them and those inside the window),
``experts_hit``, ``kv_bytes_held`` and ``kv_bytes_uniform`` -- laid
against the device trace and the scope map (``readers/scopes.py``).
``None`` where there is no trace, no such argument or no such scope (any
commit before them).

``what`` selects one:

* ``decode_roofline``  bytes a decode step must move -- weights outside
                       the routed experts once, the experts that were
                       HIT once, the full layers' live rows and the
                       window layers' rows inside the window once -- /
                       HBM bandwidth / the median device time of one run
                       of ``module``, %
* ``attn_roofline``    the bound of the attention of ``kind`` (window,
                       full) for one step (the larger of its operations
                       / peak and its rows' bytes / bandwidth) / the
                       device time under ``scope`` a run of ``module``, %
* ``kv_saved_share``   1 - mean ``kv_bytes_held`` / mean
                       ``kv_bytes_uniform``: what the two-kind cache
                       saves of a cache that kept every position of
                       every layer for the same sequences, %
"""

from statistics import mean, median

from benchmark.harness import loader
from benchmark.harness import trace_reduce as tr


def read(ctx, what, module=None, program=None, scope=None, kind=None):
    trace = ctx.get("trace")
    if trace is None or not trace.devices:
        return None
    # an argument of the window's ``serve.step`` spans, wherever it is set
    _step_args = loader.load_module("readers", "mla_moe")._step_args
    if what == "kv_saved_share":
        held = _step_args(ctx, "kv_bytes_held")
        whole = _step_args(ctx, "kv_bytes_uniform")
        if not held or not mean(whole):
            return None
        return 100.0 * (1.0 - mean(held) / mean(whole))
    full, window = (_step_args(ctx, "full_rows"),
                    _step_args(ctx, "window_rows"))
    if not full or not window:
        return None
    family = ctx["cell"]["config"]["family"]
    sizes = loader.load_module("references", family).sizes_of(
        ctx["cell"]["config"])
    work = loader.load_module("work", family)
    t0, t1 = ctx["trace_window"]
    runs = tr.module_runs(trace, module, t0, t1)
    if not runs:
        return None
    peaks = ctx["peaks"]
    if what == "decode_roofline":
        hit = _step_args(ctx, "experts_hit")
        lanes = ctx["run"]["counters"].get("live_slots_mean")
        if not hit or not lanes:
            return None
        least = work.decode_step_bytes(
            sizes, lanes, mean(full), mean(window), mean(hit)) \
            / peaks["hbm_bytes_per_s"]
        return 100.0 * least / median(d for _, d in runs)
    if what != "attn_roofline":
        raise ValueError(f"unknown swa_moe reading {what!r}")
    scopes = loader.load_module("readers", "scopes")
    spent = scopes._scope_seconds(trace, runs, program, scope)
    if not spent:
        return None
    if kind == "window":
        least, _ = work.window_attn_bound_seconds(sizes, mean(window),
                                                  peaks)
    elif kind == "full":
        least, _ = work.full_attn_bound_seconds(sizes, mean(full), peaks)
    else:
        raise ValueError(f"unknown kind of attention {kind!r}")
    return 100.0 * least / (spent / len(runs))
