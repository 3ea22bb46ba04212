"""Numbers taken from the device trace of the traced slice of the window.

``what`` selects one:

* ``idle_share``       1 - union of device operations / traced window, %
* ``module_ms``        median device time of one run of the programs whose
                       name matches ``module``
* ``op_share``         device time of the operations picked by ``ops``
                       (``pool_copy``: copies shaped like the K/V pool)
                       inside runs of ``module``, over those runs' time, %
* ``exposed_collective_share``  time of collective operations on the
                       cores / time of the runs of ``module``, %
"""

from statistics import median

from benchmark.harness import trace_reduce as tr


def read(ctx, what, module=None, ops=None):
    trace = ctx.get("trace")
    if trace is None:
        return None
    t0, t1 = ctx["trace_window"]
    if what == "idle_share":
        return 100.0 * (1.0 - tr.busy_seconds(trace, t0, t1) / (t1 - t0))
    runs = tr.module_runs(trace, module, t0, t1)
    if not runs:
        return None
    if what == "module_ms":
        return 1e3 * median(d for _, d in runs)
    total = sum(d for _, d in runs)
    if what == "op_share":
        if ops != "pool_copy":
            raise ValueError(f"unknown ops selector {ops!r}")
        shape = ctx["run"]["pool_shape"]
        return 100.0 * tr.ops_inside(
            trace, runs, lambda n: tr.is_pool_copy(n, shape)) / total
    if what == "exposed_collective_share":
        return 100.0 * tr.exposed_collective_seconds(trace, t0, t1) / total
    raise ValueError(f"unknown device_trace reading {what!r}")
