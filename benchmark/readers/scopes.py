"""Numbers that need the program's own account of its programs: which
device operation lies under which ``jax.named_scope`` (the trace names
operations, not scopes; ``singa_tpu.serve.paged.program_scopes()`` keeps
the map as each program compiles), and the ``state_slots`` argument of
its ``singa/serve.step`` spans.  ``None`` where there is no trace, or the
program has no such map, span or argument (any commit before them).

``what`` selects one:

* ``scope_share``     device time of the operations under ``scope`` in
                      the runs of ``module`` / those runs' time, %
* ``scan_roofline``   the chunked scan's shape-derived bound for one
                      chunk row (``work/<family>.py``) / the device time
                      under ``scope`` a run of ``module``, %
* ``decode_roofline`` bytes a decode step must move (weights once, live
                      K/V once, live state read and written once) / HBM
                      bandwidth / the median device time of one run of
                      ``module``, %
* ``arg_peak``        the largest ``arg`` over the window's ``span``
                      spans / the configuration's number at ``over``, %
"""

import re
from statistics import median

from benchmark.harness import loader
from benchmark.harness import program_trace as pt
from benchmark.harness import trace_reduce as tr

_EVENT = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])")
_NESTING = ("while", "conditional", "call")   # events that span others


def _scope_seconds(trace, runs, program, scope):
    """Seconds of the first chip's operations inside ``runs`` that the
    program put under ``scope``; None if it keeps no map."""
    try:
        from singa_tpu.serve.paged import program_scopes
    except ImportError:
        return None
    scopes = program_scopes().get(program)
    if not scopes:
        return None

    def keep(hlo):
        m = _EVENT.match(hlo)
        if not m or m.group(1).split(".")[0] in _NESTING:
            return False
        return scopes.get(f"{m.group(1)} {m.group(2)}") == scope

    return tr.ops_inside(trace, runs, keep)


def _work(ctx):
    family = ctx["cell"]["config"]["family"]
    ref = loader.load_module("references", family)
    return (loader.load_module("work", family),
            ref.sizes_of(ctx["cell"]["config"]))


def read(ctx, what, module=None, program=None, scope=None, span=None,
         arg=None, over=None):
    trace = ctx.get("trace")
    if trace is None or not trace.devices:
        return None
    t0, t1 = ctx["trace_window"]
    if what == "arg_peak":
        tracer = ctx["run"].get("tracer")
        path = pt.newest_xplane(tracer.out_dir) if tracer else None
        spans = pt.within(pt.load_spans(path), t0, t1) if path else []
        seen = [s[3][arg] for s in spans if s[0] == span and arg in s[3]]
        if not seen:
            return None
        full = ctx["cell"]["config"]
        for key in over.split("."):
            full = full[key]
        return 100.0 * max(float(v) for v in seen) / full
    runs = tr.module_runs(trace, module, t0, t1)
    if not runs:
        return None
    c = ctx["run"]["counters"]
    if what == "decode_roofline":
        if not c.get("live_slots_mean"):
            return None
        work, sizes = _work(ctx)
        lanes = c["live_slots_mean"]
        least = work.decode_step_bytes(
            sizes, lanes, lanes * c["live_positions_mean"]) \
            / ctx["peaks"]["hbm_bytes_per_s"]
        return 100.0 * least / median(d for _, d in runs)
    spent = _scope_seconds(trace, runs, program, scope)
    if not spent:
        return None
    if what == "scope_share":
        return 100.0 * spent / sum(d for _, d in runs)
    if what == "scan_roofline":
        work, sizes = _work(ctx)
        chunk = ctx["cell"]["config"]["engine"]["block_size"]
        least, _ = work.ssm_scan_bound_seconds(sizes, chunk, ctx["peaks"])
        return 100.0 * least / (spent / len(runs))
    raise ValueError(f"unknown scopes reading {what!r}")
