"""The one number of the ``ssm_moe`` family that the readers before it do
not give (``readers/mla_moe.py`` reads this family's decode roofline, its
experts' roofline and its hit share as it does the others';
``readers/scopes.py`` a scope's share; ``readers/conv_moe.py`` the expert
loop's load): the roofline of the one-step recurrence.  ``None`` where
there is no trace, no such scope or no such bound (any commit before
them).

``what`` selects it:

* ``step_roofline``  the recurrences' bound for one decode step (every
                     Mamba layer's state of every live lane read and
                     written / bandwidth, or their operations / peak if
                     larger: ``work/<family>.ssm_step_bound_seconds``) /
                     the device time under ``scope`` a run of ``module``,
                     %
"""

from benchmark.harness import loader
from benchmark.harness import trace_reduce as tr


def read(ctx, what, module=None, program=None, scope=None):
    if what != "step_roofline":
        raise ValueError(f"unknown ssm_moe reading {what!r}")
    trace = ctx.get("trace")
    if trace is None or not trace.devices:
        return None
    family = ctx["cell"]["config"]["family"]
    work = loader.load_module("work", family)
    if not hasattr(work, "ssm_step_bound_seconds"):
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
    least, _ = work.ssm_step_bound_seconds(sizes, lanes, ctx["peaks"])
    return 100.0 * least / (spent / len(runs))
