"""The program's own phases, read from a ``jax.profiler`` trace.

The program writes one ``jax.profiler.TraceAnnotation`` per step-level
phase (``singa_tpu/observe/trace.py:phase``): ``singa/serve.step`` with
``serve.grow``, ``serve.decode`` (holding ``serve.sync``), ``serve.emit``
and ``serve.schedule`` inside it, ``singa/train.step`` around
``train.dispatch``.  They land on the ``/host:CPU`` plane, on the clock
the device's operations are on, and their keyword arguments come back as
the events' stats.  A program that writes none (any commit before those
phases) gives an empty list, and every reading below is then ``None``.

Everything except ``newest_xplane`` and ``load_spans`` works on plain
tuples ``(name, start_s, duration_s, args)`` -- the name without its
``singa/`` -- so that it can be checked without a trace.  Device
operations are ``trace_reduce``'s ``(name, start_s, duration_s)``.
"""

import glob
import os

from .trace_reduce import clip, union

PREFIX = "singa/"
# whose idle time a gap of the device is: the innermost of these that
# covers most of it.  serve.decode counts up to its serve.sync only --
# building the pool step's inputs and launching it; inside serve.sync the
# host does no work (the device has finished and the host has not seen it
# yet), so what serve.sync wins is ``other``'s, with the idle time under
# no span at all.
IDLE_PHASES = {
    "decode_launch": ("serve.grow", "serve.decode"),
    "emit": ("serve.emit",),
    "schedule": ("serve.schedule",),
    "other": ("serve.sync",),
}
OTHER = "other"
EPS = 1e-12     # seconds; the trace's clock ticks in nanoseconds


def newest_xplane(out_dir):
    """The newest ``*.xplane.pb`` a profiler session left under
    ``out_dir``, or None."""
    paths = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load_spans(path):
    """[(name, start_s, duration_s, args)] of the ``singa/`` events on
    the ``/host:CPU`` plane, by start (outer before inner)."""
    from jax.profiler import ProfileData

    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        # the same event can sit on the thread's own line and, with the
        # Python tracer on, on the line ``python``
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    key = (e.name[len(PREFIX):], e.start_ns * 1e-9,
                           e.duration_ns * 1e-9)
                    seen.setdefault(key, dict(e.stats))
    return sorted((k + (a,) for k, a in seen.items()),
                  key=lambda s: (s[1], -s[2]))


def within(spans, t0, t1):
    """The spans that lie wholly inside [t0, t1], by start."""
    return [s for s in spans if s[1] >= t0 and s[1] + s[2] <= t1]


def inside(spans, outer, name):
    """The spans called ``name`` that lie inside the span ``outer``."""
    a, b = outer[1], outer[1] + outer[2]
    return [s for s in spans
            if s[0] == name and s[1] >= a and s[1] + s[2] <= b + 1e-9]


def durations_less(spans, name, minus=None):
    """Seconds of each span called ``name``, less the spans called
    ``minus`` inside it."""
    out = []
    for s in spans:
        if s[0] == name:
            d = s[2]
            if minus is not None:
                d -= sum(c[2] for c in inside(spans, s, minus))
            out.append(d)
    return out


def leads(spans, name, until):
    """Seconds from the start of each span called ``name`` to the start
    of the first span called ``until`` inside it; a span that holds none
    is left out."""
    out = []
    for s in spans:
        if s[0] == name:
            kids = inside(spans, s, until)
            if kids:
                out.append(min(c[1] for c in kids) - s[1])
    return out


def covered_share(spans, name):
    """The share of the spans called ``name`` that the spans directly
    inside them cover (their union, so nesting counts once), over all of
    them; None where there is no such span."""
    total = cover = 0.0
    for s in spans:
        if s[0] != name:
            continue
        a, b = s[1], s[1] + s[2]
        kids = [(c[1], c[1] + c[2]) for c in spans
                if c is not s and c[1] >= a and c[1] + c[2] <= b + 1e-9]
        total += s[2]
        cover += sum(e - st for st, e in union(kids))
    return cover / total if total else None


def arg_delta(spans, name, arg):
    """(difference of the cumulative ``arg`` between the first and the
    last span called ``name``, spans between them) -- the last one's
    value less the first one's is what the spans after the first added.
    None where fewer than two carry it."""
    vals = [s[3][arg] for s in spans if s[0] == name and arg in s[3]]
    if len(vals) < 2:
        return None
    return vals[-1] - vals[0], len(vals) - 1


def idle_gaps(ops, t0, t1):
    """[(start, end)] inside [t0, t1] in which no operation of ``ops``
    ran."""
    busy = union((s, s + d) for _, s, d in clip(ops, t0, t1))
    gaps, at = [], t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def attribute(gaps, spans):
    """{span name or ``other``: seconds}: each gap whole to the span
    that covers most of it, the innermost (shortest) of those that cover
    it equally -- ``trace_reduce.idle_gaps``' rule; ``other`` where none
    covers any of it."""
    by = {}
    spans = sorted(spans, key=lambda s: s[1])
    for a, b in gaps:
        best, cover, length = OTHER, 0.0, 0.0
        for name, s, d, _ in spans:
            if s >= b:
                break
            c = min(b, s + d) - max(a, s)
            if c > cover + EPS or (c > cover - EPS and c > 0
                                   and d < length):
                best, cover, length = name, c, d
        by[best] = by.get(best, 0.0) + (b - a)
    return by


def cut_at(spans, name, until):
    """``spans`` with each span called ``name`` ended where the first span
    called ``until`` inside it starts."""
    out = []
    for s in spans:
        if s[0] == name:
            kids = inside(spans, s, until)
            if kids:
                s = (s[0], s[1], min(c[1] for c in kids) - s[1], s[3])
        out.append(s)
    return out


def idle_by_phase(ops, spans, t0, t1):
    """{phase of IDLE_PHASES: idle seconds of the device in [t0, t1]};
    the values sum to the window's whole idle time."""
    names = {n: phase for phase, ns in IDLE_PHASES.items() for n in ns}
    spans = cut_at(spans, "serve.decode", "serve.sync")
    by_span = attribute(idle_gaps(ops, t0, t1),
                        [s for s in spans if s[0] in names])
    out = dict.fromkeys(IDLE_PHASES, 0.0)
    for name, seconds in by_span.items():
        out[names.get(name, OTHER)] += seconds
    return out
