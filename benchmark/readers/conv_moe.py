"""Numbers of the ``conv_moe`` family that the readers before it do not
give (``readers/mla_moe.py`` reads this family's decode roofline, its
experts' roofline and its hit share as it does the others';
``readers/scopes.py`` a scope's share): the conv operator's roofline, and
two readings of the expert loop's load from the arguments of the
program's ``singa/serve.step`` spans.  ``None`` where there is no trace,
no such argument or no such scope (any commit before them).

``what`` selects one:

* ``conv_roofline``  the conv operators' bound for one decode step (their
                     matrices once and the live lanes' tails read and
                     written / bandwidth, or their operations / peak if
                     larger) / the device time under ``scope`` a run of
                     ``module``, %
* ``reread_share``   1 - sum ``experts_hit`` / sum ``expert_tiles`` over
                     the window's decode steps: the share of the expert
                     loop's tile iterations that read an expert's
                     matrices a further time (or for nobody), %
* ``load_peak``      mean over the window's decode steps of
                     ``expert_tokens_max`` / ``expert_tokens_mean``: the
                     busiest held expert against the even load, % (100 =
                     even)
"""

from statistics import mean

from benchmark.harness import loader
from benchmark.harness import trace_reduce as tr


def read(ctx, what, module=None, program=None, scope=None):
    trace = ctx.get("trace")
    if trace is None or not trace.devices:
        return None
    _step_args = loader.load_module("readers", "mla_moe")._step_args
    if what == "reread_share":
        hit, tiles = (_step_args(ctx, "experts_hit"),
                      _step_args(ctx, "expert_tiles"))
        if not hit or not tiles or not sum(tiles):
            return None
        return 100.0 * (1.0 - sum(hit) / sum(tiles))
    if what == "load_peak":
        most, even = (_step_args(ctx, "expert_tokens_max"),
                      _step_args(ctx, "expert_tokens_mean"))
        ratios = [m / e for m, e in zip(most, even) if e]
        return 100.0 * mean(ratios) if ratios else None
    if what != "conv_roofline":
        raise ValueError(f"unknown conv_moe reading {what!r}")
    family = ctx["cell"]["config"]["family"]
    work = loader.load_module("work", family)
    if not hasattr(work, "short_conv_bound_seconds"):
        return None
    lanes = ctx["run"]["counters"].get("live_slots_mean")
    t0, t1 = ctx["trace_window"]
    runs = tr.module_runs(trace, module, t0, t1)
    if not runs or not lanes:
        return None
    spent = loader.load_module("readers", "scopes")._scope_seconds(
        trace, runs, program, scope)
    if not spent:
        return None
    sizes = loader.load_module("references", family).sizes_of(
        ctx["cell"]["config"])
    least, _ = work.short_conv_bound_seconds(sizes, lanes, ctx["peaks"])
    return 100.0 * least / (spent / len(runs))
