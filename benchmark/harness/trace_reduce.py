"""From a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

What a v5e trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per run of a
compiled program, named ``jit_<fn>(<fingerprint>)``) and a line ``XLA
Ops`` (one event per operation of it, named by its HLO text, one after
the other on the core); and the plane ``/host:CPU``, whose thread
lines hold the ``TraceAnnotation`` spans, on the same clock.

Everything below works on plain tuples ``(name, start_s, duration_s)``
so that it can be checked without a trace; ``load`` is the one function
that touches the file.
"""

import re
from dataclasses import dataclass, field

SPAN_PREFIX = "bench/"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")

_HLO = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)* = \(?([a-z]+\d*)"
                  r"\[([\d,]*)\]")


@dataclass
class Trace:
    # device plane name -> {"modules": [...], "ops": [...]}
    devices: dict = field(default_factory=dict)
    host_spans: list = field(default_factory=list)   # bench/ spans only


def load(path):
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    lines[key] = [(e.name, e.start_ns * 1e-9,
                                   e.duration_ns * 1e-9)
                                  for e in line.events]
            trace.devices[plane.name] = lines
        elif plane.name == "/host:CPU":
            # with the Python tracer on the spans sit on the line
            # ``python``, without it on the calling thread's own line
            for line in plane.lines:
                trace.host_spans += [
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX)]
    trace.host_spans = sorted(set(trace.host_spans), key=lambda e: e[1])
    return trace


def short_name(hlo):
    """``%copy.3 = bf16[36,561,20,32,64]{...} copy(...)`` ->
    ``copy_bf16_36_561_20_32_64``: the operation and what it produces,
    which is what stays the same from run to run."""
    m = _HLO.match(hlo)
    if not m:
        return re.sub(r"\W+", "_", hlo)[:64]
    op, dtype, dims = m.groups()
    return "_".join([op, dtype] + [d for d in dims.split(",") if d])[:64]


def op_dims(hlo):
    """(operation, [dims of its first result]) or (None, [])."""
    m = _HLO.match(hlo)
    if not m:
        return None, []
    return m.group(1), [int(d) for d in m.group(3).split(",") if d]


def union(intervals):
    """Merge (start, end) pairs; returns the merged, sorted list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events, t0, t1):
    """Events cut to the window [t0, t1]."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def window_of(trace):
    """The traced window: from the first benchmark span's start to the
    last one's end."""
    if not trace.host_spans:
        raise ValueError("the trace holds no bench/ span")
    t0 = min(s for _, s, _ in trace.host_spans)
    t1 = max(s + d for _, s, d in trace.host_spans)
    return t0, t1


def busy_seconds(trace, t0, t1):
    """Seconds in [t0, t1] in which an operation ran, averaged over the
    chips in the trace."""
    per_chip = []
    for lines in trace.devices.values():
        iv = union((s, s + d) for _, s, d in clip(lines["ops"], t0, t1))
        per_chip.append(sum(e - s for s, e in iv))
    if not per_chip:
        raise ValueError("the trace holds no device plane")
    return sum(per_chip) / len(per_chip)


def op_totals(trace, t0, t1):
    """{short name: seconds}, averaged over the chips."""
    tot = {}
    for lines in trace.devices.values():
        for name, _, d in clip(lines["ops"], t0, t1):
            k = short_name(name)
            tot[k] = tot.get(k, 0.0) + d
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in tot.items()}


def module_runs(trace, pattern, t0, t1):
    """[(start, duration)] of each whole run inside [t0, t1] of the
    programs whose name matches ``pattern``, on the first chip."""
    rx = re.compile(pattern)
    lines = next(iter(trace.devices.values()))
    return [(s, d) for name, s, d in lines["modules"]
            if rx.search(name) and s >= t0 and s + d <= t1]


def module_counts(trace, t0, t1):
    """{program name without its fingerprint: runs} on the first chip."""
    lines = next(iter(trace.devices.values()))
    out = {}
    for name, s, d in lines["modules"]:
        if s >= t0 and s + d <= t1:
            k = name.split("(")[0]
            out[k] = out.get(k, 0) + 1
    return out


def ops_inside(trace, runs, keep):
    """Seconds of the first chip's operations that lie inside ``runs``
    and for which ``keep(hlo name)`` is true."""
    lines = next(iter(trace.devices.values()))
    ops = sorted(lines["ops"], key=lambda e: e[1])
    runs = sorted(runs)
    total, i = 0.0, 0
    for name, s, d in ops:
        while i < len(runs) and runs[i][0] + runs[i][1] < s:
            i += 1
        if i == len(runs):
            break
        if s >= runs[i][0] and s + d <= runs[i][0] + runs[i][1] + 1e-9 \
                and keep(name):
            total += d
    return total


def is_pool_copy(hlo, pool_shape):
    """A ``copy`` whose result is the whole K or V pool, or one layer of
    it: the trailing (blocks, heads, block, head size) dims match."""
    op, dims = op_dims(hlo)
    tail = list(pool_shape[-4:])
    return op == "copy" and len(dims) >= 4 and dims[-4:] == tail


def is_collective(hlo):
    op, _ = op_dims(hlo)
    return bool(op) and any(op.startswith(c) for c in COLLECTIVES)


def exposed_collective_seconds(trace, t0, t1):
    """Seconds the cores spent in collective operations, averaged over
    the chips.  The ``XLA Ops`` line is one core's sequence: while a
    collective (or the ``-done`` that waits for an asynchronous one) is
    on it, nothing else computes there, so its time is exposed."""
    per_chip = []
    for lines in trace.devices.values():
        per_chip.append(sum(d for name, _, d in clip(lines["ops"], t0, t1)
                            if is_collective(name)))
    return sum(per_chip) / max(len(per_chip), 1)


def idle_gaps(trace, t0, t1, top=10):
    """The idle time of the first chip by what the host was doing:
    [[span name, seconds]], largest first.  A gap belongs to the
    benchmark span that covers most of it; ``unattributed`` where none
    does."""
    lines = next(iter(trace.devices.values()))
    busy = union((s, s + d) for _, s, d in clip(lines["ops"], t0, t1))
    gaps, at = [], t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    by = {}
    spans = trace.host_spans
    for a, b in gaps:
        best, cover, length = "unattributed", 0.0, 0.0
        for name, s, d in spans:
            if s >= b:
                break
            c = min(b, s + d) - max(a, s)
            # of spans that cover the gap equally, the innermost
            # (shortest) says most about what the host was doing
            if c > cover + 1e-9 or (c > cover - 1e-9 and c > 0
                                    and d < length):
                best, cover, length = name[len(SPAN_PREFIX):], c, d
        by[best] = by.get(best, 0.0) + (b - a)
    return [[k, v] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:top]]


def breakdown(trace, t0, t1, top=10):
    ops = sorted(op_totals(trace, t0, t1).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": idle_gaps(trace, t0, t1, top)}
