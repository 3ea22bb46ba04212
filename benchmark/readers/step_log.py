"""The rare long iterations of a serve window, from the program's
always-on step log (``singa_tpu.observe.stepprof.iterations()``: per
``engine.step()`` the tuple ``(t0, wall_s, gap_s, sync_s)`` on
``time.perf_counter``, the driver's clock; ``gap_s`` is the caller's time
since the engine's last step while work was waiting).  ``None`` where the
program keeps no such log.

The span read is the UNTRACED part of the window: ``setup_end`` to
``setup_end + window_s - trace_window.length_s`` (to the tracer's own
``start_s`` where the run has one).  The profiler is started from the
driver's loop inside the window (``harness/profile.py``), and that call
would read as a caller stall in every traced run.

An iteration is a step with the gap before it.  With M the median of
``gap_s + wall_s`` over the span, an iteration is long if it is over
``4 M`` and over ``M + 0.1 s`` (the program's own rule, taken with the
span's median).  A long iteration's excess over M is put down to

* ``caller``  its ``gap_s`` less the span's median gap: the driver's loop
              between two steps (``submit``, the due-heap, ``tracer.poll``)
* ``sync``    its ``sync_s`` less the span's median: the host blocked on
              the device's result (``singa/serve.sync``)
* ``host``    the rest: the engine's own code inside ``step()``

none below 0.  ``what`` selects one:

* ``stall_s``        seconds of excess put down to ``where``, summed over
                     the span's long iterations (0.0 where none was long)
* ``excess_max_ms``  the largest excess of one long iteration (0.0 the
                     same)

Once a run it says the span's ten longest iterations with their anatomy;
for a long one the program's full record (which host segment, the step's
own args) is printed beside it.
"""

from benchmark.harness.output import say
from benchmark.harness.stats import percentile

FACTOR, OVER_S = 4.0, 0.1          # singa_tpu/observe/stepprof.py's rule
WHERE = ("caller", "sync", "host")


def reduce(iterations):
    """``iterations``: [(t0, wall_s, gap_s, sync_s)].  Returns the span's
    medians and its long iterations, each with its excess split three
    ways; ``None`` for a span with no iteration."""
    if not iterations:
        return None
    took = [w + g for _, w, g, _ in iterations]
    m = percentile(took, 50)
    m_gap = percentile([g for _, _, g, _ in iterations], 50)
    m_sync = percentile([s for _, _, _, s in iterations], 50)
    long = []
    for (t0, wall, gap, sync), t in zip(iterations, took):
        if t > FACTOR * m and t > m + OVER_S:
            caller = max(gap - m_gap, 0.0)
            in_sync = max(sync - m_sync, 0.0)
            long.append(dict(
                t0=t0, wall_s=wall, gap_s=gap, sync_s=sync, excess_s=t - m,
                caller=caller, sync=in_sync,
                host=max(t - m - caller - in_sync, 0.0)))
    return dict(n=len(iterations), median_s=m, median_gap_s=m_gap,
                median_sync_s=m_sync, long=long)


def _span_of(ctx):
    run = ctx["run"]
    since = run["setup_end"]
    # where the driver's tracer says it starts (the window's end is a
    # step late, and the profiler is started at the first look after
    # ``start_s``), else the window less the cell's traced slice
    start_s = getattr(run.get("tracer"), "start_s", None)
    if start_s is None:
        traced = ctx["cell"]["cell"]["trace_window"]["length_s"]
        start_s = run["window_s"] - min(traced, run["window_s"])
    return since, since + start_s


def _say_longest(iterations, red, records, since):
    by_t0 = {r["t0"]: r for r in records}
    long = {it["t0"]: it for it in red["long"]}
    say(f"step log: {red['n']} iterations in the untraced "
        f"{iterations[-1][0] - since:.1f} s, median "
        f"{1e3 * red['median_s']:.2f} ms (gap "
        f"{1e3 * red['median_gap_s']:.3f}, sync "
        f"{1e3 * red['median_sync_s']:.2f}); {len(long)} long")
    for t0, wall, gap, sync in sorted(
            iterations, key=lambda it: -(it[1] + it[2]))[:10]:
        line = (f"step log: at {t0 - since:7.3f} s  step {1e3 * wall:9.2f} "
                f"ms (sync {1e3 * sync:9.2f})  gap {1e3 * gap:9.2f} ms")
        if t0 in long:
            it = long[t0]
            line += (f"  LONG: caller {it['caller']:.3f} sync "
                     f"{it['sync']:.3f} host {it['host']:.3f} s")
        rec = by_t0.get(t0)
        if rec is not None:
            segs = " ".join(f"{k} {v:.3f}" for k, v in sorted(
                rec["segments"].items(), key=lambda kv: -kv[1]))
            args = " ".join(
                f"{k} {rec[k]}" for k in ("live", "width", "queue_depth",
                                          "admitted", "chunks", "launches")
                if k in rec)
            line += f"  [step {rec['step']}: {segs}; {args}]"
        say(line)


def _reduced(ctx):
    """The span's reduction; made, and said, once for the run."""
    if "step_log" not in ctx:
        ctx["step_log"] = None
        try:
            from singa_tpu.observe import stepprof
        except ImportError:
            return None
        if not hasattr(stepprof, "iterations"):
            return None
        since, until = _span_of(ctx)
        iterations = stepprof.iterations(since=since, until=until)
        red = ctx["step_log"] = reduce(iterations)
        if red is not None:
            _say_longest(iterations, red,
                         stepprof.long_iterations(since=since, until=until),
                         since)
    return ctx["step_log"]


def read(ctx, what, where=None):
    red = _reduced(ctx)
    if red is None:
        return None
    if what == "stall_s":
        if where not in WHERE:
            raise ValueError(f"unknown step_log where {where!r}")
        return float(sum(it[where] for it in red["long"]))
    if what == "excess_max_ms":
        return 1e3 * max((it["excess_s"] for it in red["long"]),
                         default=0.0)
    raise ValueError(f"unknown step_log reading {what!r}")
