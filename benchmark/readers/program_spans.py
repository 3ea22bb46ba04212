"""Numbers taken from the program's own phases (its ``singa/`` spans) in
the trace of the traced slice of the window, alone or laid against the
device's operations.  ``None`` where there is no trace, no device plane,
or no such span -- a CPU run, or a program from before its phases.

``what`` selects one:

* ``span_ms``   the ``q``-th percentile over the window's spans called
                ``span`` of their time, less the spans called ``minus``
                inside them
* ``lead_ms``   the same of the time from the start of ``span`` to the
                start of the first ``until`` inside it
* ``idle_ms_per_step``  idle time of the first chip put down to
                ``phase`` (``program_trace.IDLE_PHASES``; ``other`` is
                what no host work covers: under ``serve.sync``, between
                steps), over the window's ``serve.step`` spans
* ``arg_use``   what the cumulative ``arg`` of ``span`` grew by from the
                window's first such span to its last, over the spans
                after the first times the configuration's number at
                ``over`` (a dotted path), %
"""

from benchmark.harness import program_trace as pt
from benchmark.harness.stats import percentile


def _spans(ctx):
    """The window's ``singa/`` spans; loaded once for the run."""
    if "program_spans" not in ctx:
        tracer = ctx["run"].get("tracer")
        path = pt.newest_xplane(tracer.out_dir) if tracer else None
        spans = pt.load_spans(path) if path else []
        ctx["program_spans"] = pt.within(spans, *ctx["trace_window"])
    return ctx["program_spans"]


def read(ctx, what, span=None, minus=None, until=None, q=50, phase=None,
         arg=None, over=None):
    trace = ctx.get("trace")
    if trace is None or not trace.devices:
        return None
    spans = _spans(ctx)
    if not spans:
        return None
    if what == "span_ms":
        seconds = pt.durations_less(spans, span, minus)
        return 1e3 * percentile(seconds, q) if seconds else None
    if what == "lead_ms":
        seconds = pt.leads(spans, span, until)
        return 1e3 * percentile(seconds, q) if seconds else None
    if what == "idle_ms_per_step":
        steps = sum(1 for s in spans if s[0] == "serve.step")
        if not steps:
            return None
        ops = next(iter(trace.devices.values()))["ops"]
        t0, t1 = ctx["trace_window"]
        return 1e3 * pt.idle_by_phase(ops, spans, t0, t1)[phase] / steps
    if what == "arg_use":
        grew = pt.arg_delta(spans, span, arg)
        if grew is None:
            return None
        per_span = ctx["cell"]["config"]
        for key in over.split("."):
            per_span = per_span[key]
        return 100.0 * grew[0] / (grew[1] * per_span)
    raise ValueError(f"unknown program_spans reading {what!r}")
