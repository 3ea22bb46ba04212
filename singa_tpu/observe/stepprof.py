"""Step anatomy: a host-clock decomposition of every ``engine.step()``
— always on — and, behind :func:`enable`, a host-fence ESTIMATE of the
device's share with its histograms, ring and trace records.

The RequestLedger attributes per-REQUEST phases (queue/prefill/decode/
stall); this module says where one STEP's wall time goes on the host's
clock.  It has no fence sites of its own: ``observe.trace.phase()`` —
the one call at each step-level site of ``serve/engine.py`` — feeds it
through a hook, so the segments below are the program's
``singa/serve.*`` phases under their short names:

* **host segments** — ``schedule`` (the scheduling pass), ``admit``
  (one admission's host work), ``prefix_lookup`` (radix-cache probes),
  ``dispatch`` (building inputs + launching an executable, at the
  executor seam), ``sync`` (the host blocked on the device's result),
  ``emit`` (token emission, callbacks, retire, ledger hooks).  Phases
  nest; accounting is EXCLUSIVE (a prefix lookup inside an admission
  is prefix_lookup time, never double-counted as admit), and time
  under no segment lands in ``other`` — so the segments always sum to
  the wall exactly, the RequestLedger's seal-time idiom.

**Always on**, from the first ``InferenceEngine`` a process builds
(:func:`install`; a training process never installs it): the hook and
the segment stack above — two clock reads a segment phase and a
dictionary update, no fence, no histogram, no trace record, nothing
kept of a step but what follows.  It answers, in every window, traced
or not, where a rare long iteration went:

* :func:`iterations` — per step ``(t0, wall_s, gap_s, sync_s)`` in a
  ring of 16,384 (a 60 s window of 6.6 ms steps is ~9,000).  ``gap_s``
  is the time from the previous ``step()``'s return on the same engine
  to this one's start, and only if that step returned ``pending``: the
  CALLER's time while work was waiting.  After a step that left no
  work the gap is idleness by design and reads 0.
* :func:`long_iterations` — the full record (engine, step, ``t0``,
  ``wall_s``, ``gap_s``, ``median_s``, the segment seconds, ``where``
  with the seconds put down to each, and the step's own args ``live``,
  ``width``, ``queue_depth``, ``admitted``, ``chunks``, ``launches``)
  of an iteration whose ``gap_s + wall_s`` was over four times the
  median of the engine's last 256 AND over it by 100 ms, once the
  engine has 64 iterations behind it (warm-up's first steps are not
  reported).  Newest 256 kept.
* counters ``serve.step.long_iterations{engine=,where=}`` and
  ``serve.step.long_seconds{engine=,where=}``: ``where`` is ``caller``
  (the gap over its median), ``sync`` (the ``sync`` segment over its
  median) or ``host`` (the rest of the excess: every other segment of
  ``step()``).  Created on an engine's first long iteration and removed
  with its other series at close (:func:`forget_engine`); the rings
  are the module's and outlive the engine.
* one ``WARNING`` on the engine's logger (channel ``serve``) a long
  iteration, at most one an engine every 5 s, with the count of those
  it swallowed — what leaves the finding in an untraced run's output.

**Behind** :func:`enable`, exactly as before — sinks added to the one
record the always-on path makes, never a second accounting:

* **the ``device`` column is a host-fence estimate, not device time** —
  :func:`fence_device` at the executor seam (``engine._ProfExec``:
  ``_LocalExec``, ``TPExecutor`` and the ep/pp executors all route
  through it) blocks the host on each dispatch's output and books
  dispatch-return → ``block_until_ready`` as ``device``.  That
  serialises the dispatches it times, runs on the host's clock, and
  includes queueing behind earlier work; ``bubble_frac = (wall -
  device) / wall`` inherits all three.  The device's real busy and idle
  time comes from a ``jax.profiler`` trace, where the same phases sit
  as ``singa/`` spans under the device's operations
  (docs/OBSERVABILITY.md "Span API").  The seam's fence is ONE
  module-flag read (``if stepprof._active:``) while off, and the
  always-on half never blocks on an output; nothing enters jitted code
  either way (the fence only adds a ``block_until_ready`` on
  already-dispatched outputs, so the recompile pin holds).
* registry: ``serve.step.{wall_s,host_s,device_s}{engine=}`` and
  ``serve.step.segment_s{engine=,segment=}`` histograms on a dedicated
  100µs–5s ladder (:data:`STEP_BUCKETS` — the default request ladder
  is far too coarse for 5–50ms steps), plus
  ``serve.step.bubble_frac{engine=}`` on a 0–1 fraction ladder.
  Registered lazily per engine label; an engine's close
  (:func:`forget_engine`) removes its series — the retire-unregisters
  contract.
* trace: one ``cat="step.host"`` COMPLETE record per step (segment
  fractions in args) and one ``cat="step.device"`` record per fenced
  window, emitted through ``trace._emit`` whenever tracing or the
  flight-recorder ring is live — so worker step anatomy rides the
  existing cross-host trace federation (observe/federate.py) and
  shows up as two lanes per host pid in the merged Chrome trace.
* ring: the last N full step records (per-piece host intervals +
  fenced windows) for the dual-lane local Chrome trace
  (``export.chrome_trace(steps=...)``).
* health: :func:`section` → ``health_report()["serve"]
  ["step_anatomy"]``; :func:`why_slow_summary` rides the why_slow
  section; :func:`culprit` feeds the Watchdog so a step-time anomaly
  names host-vs-device.

The fence's ``device`` column, ``bubble_frac`` and the
``serve.step.{host_s,device_s,bubble_frac}`` families are read by no
benchmark metric and remain a ``simplicity`` issue's to remove (PERF.md
§7; 13 tests go with them).  The always-on half neither extends them
nor depends on them.

State is MODULE-level (like trace/monitor): an ``EngineSupervisor``
restart builds a fresh engine under the same module, whose fresh
``engine=`` label starts fresh series while the dead engine's are
removed.  The always-on clock is ``time.perf_counter``; while
``enable(clock=...)`` is on, that clock stamps the one record, the
always-on log included.
"""

from __future__ import annotations

import collections
import statistics
import threading
import time

from ..utils.logging import get_channel as _get_channel
from .registry import registry as _registry
from . import trace as _trace

__all__ = ["StepProfiler", "install", "enable", "disable", "active",
           "profiler", "iterations", "long_iterations", "section",
           "why_slow_summary", "culprit", "records", "forget_engine",
           "SEGMENTS", "WHERE", "STEP_BUCKETS", "FRACTION_BUCKETS"]

#: segment taxonomy (docs/OBSERVABILITY.md "Step anatomy"): the named
#: host segments, the host-fenced device windows, and the remainder
#: under no segment.  Fractions over these sum to 1 per step by
#: construction.
SEGMENTS = ("schedule", "admit", "prefix_lookup", "dispatch", "device",
            "sync", "emit", "other")

# phase name -> segment.  ``serve.dispatch.<method>`` is ``dispatch``;
# a phase with no entry (serve.grow, serve.decode, serve.prefill) opens
# no segment: its time stays with the segment around it, or ``other``.
_SEGMENT_OF = {"serve.schedule": "schedule", "serve.admit": "admit",
               "serve.prefix_lookup": "prefix_lookup",
               "serve.sync": "sync", "serve.emit": "emit"}
_STEP = object()   # the exit token of a phase that opened a step

#: dedicated step-latency ladder: 100µs–5s.  registry.DEFAULT_BUCKETS
#: starts at 1ms and tops at 2min — the request ladder, far too coarse
#: for 5–50ms steps.
STEP_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0)

#: bubble_frac is a ratio in [0, 1]; a time ladder would be nonsense
FRACTION_BUCKETS = (0.01, 0.02, 0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6,
                    0.7, 0.8, 0.9, 0.95, 0.99, 1.0)

#: where a long iteration's excess over the median is put down to
WHERE = ("caller", "sync", "host")

#: the long-iteration rule and the always-on rings' sizes
LONG_FACTOR = 4.0          # over this many times the running median
LONG_OVER_S = 0.1          # AND over it by this many seconds
LONG_WARMUP = 64           # iterations an engine must have behind it
MEDIAN_WINDOW = 256        # iterations the running median looks back
ITERATION_RING = 16384     # a 60 s window of 6.6 ms steps is ~9,000
LONG_RING = 256
WARN_EVERY_S = 5.0         # at most one warning an engine in this long
_STEP_ARGS = ("live", "width", "queue_depth", "admitted", "chunks",
              "launches")
_LONG_HELP = {
    "long_iterations": "iterations (the caller's gap + step()) far over "
                       "the engine's running median, by where most of "
                       "the excess went",
    "long_seconds": "seconds over the running median in those "
                    "iterations, by where they went"}

# Module-global fast path, mirroring trace._active: `if not
# stepprof._active: <skip>` is the ENTIRE disabled cost of a fence
# site.  _prof is non-None exactly while _active is True.
_active = False
_prof = None
_tls = threading.local()

# the always-on half: installed by the first engine built, never taken
# out again by disable()
_installed = False
_clock = time.perf_counter
_iterations = collections.deque(maxlen=ITERATION_RING)
_long = collections.deque(maxlen=LONG_RING)
_engines = {}              # engine label -> _EngineLog

_block_until_ready = None  # lazy jax import (observe stays jax-free
#                            until a profiled dispatch actually runs)


def _block(out):
    global _block_until_ready
    if _block_until_ready is None:
        import jax
        _block_until_ready = jax.block_until_ready
    return _block_until_ready(out)


def install():
    """Turn the always-on half on (idempotent): every
    ``InferenceEngine`` calls this as it is built.  Host clock stamps
    only — :func:`active` stays False and no dispatch is fenced."""
    global _installed
    if not _installed:
        _installed = True
        _trace._set_phase_hook(_HOOK)


def enable(clock=None, ring=512, reg=None) -> "StepProfiler":
    """Attach a fresh process-wide profiler and turn the fences on.
    ``clock``: ``() -> float`` seconds — pass the trace clock when
    both are on so step-anatomy trace records share its time base
    (the dist worker does; offsets then correct both together).
    ``ring`` bounds the per-step record buffer."""
    global _active, _prof
    _prof = StepProfiler(clock=clock, ring=ring, reg=reg)
    _active = True
    _trace._set_phase_hook(_HOOK)
    _forget_gaps()
    return _prof


def disable(unregister=True):
    """Turn the fences off and detach the profiler; the always-on half
    stays if an engine installed it.  ``unregister=True`` (default)
    also removes every ``serve.step.*`` series the profiler created —
    the retire-unregisters contract; pass False to keep them readable
    (export after disable)."""
    global _active, _prof
    p, _prof = _prof, None
    _active = False
    if not _installed:
        _trace._set_phase_hook(None)
    _tls.cur = None
    _forget_gaps()
    if p is not None and unregister:
        p.unregister()


def _forget_gaps():
    # the clock may differ on the two sides of enable()/disable(): the
    # next step of every engine measures no gap across the change
    for e in _engines.values():
        e.pending = False


def _reset():
    """The always-on half as a process that has built no engine has it
    (the tests' way back to that state)."""
    global _installed
    _installed = False
    if not _active:
        _trace._set_phase_hook(None)
    for label in list(_engines):
        forget_engine(label)
    _iterations.clear()
    _long.clear()


def active() -> bool:
    return _active


def profiler():
    """The live profiler, or None when off."""
    return _prof


def forget_engine(label):
    """Remove a closed engine's ``serve.step.*{engine=label}`` series
    (``engine._release_everything`` calls this): a supervisor-rebuilt
    engine's fresh label must not leave the dead one's series frozen
    in the exposition.  The iteration rings keep what the engine
    logged."""
    e = _engines.pop(label, None)
    if e is not None and e.counters:
        _registry().remove(*e.counters.values())
    if _prof is not None:
        _prof.forget_engine(label)


# -- the always-on log --------------------------------------------------

def _within(t0, since, until):
    return ((since is None or t0 >= since)
            and (until is None or t0 < until))


def iterations(since=None, until=None) -> list:
    """``(t0, wall_s, gap_s, sync_s)`` of the newest steps of every
    engine of this process, oldest first; ``since <= t0 < until`` on
    ``time.perf_counter``."""
    return [r for r in tuple(_iterations) if _within(r[0], since, until)]


def long_iterations(since=None, until=None) -> list:
    """The full records of the newest long iterations (module
    docstring), oldest first, filtered like :func:`iterations`."""
    return [r for r in tuple(_long) if _within(r["t0"], since, until)]


class _EngineLog:
    """One engine's side of the always-on log: the running median's
    window, when its last step returned and whether work was left, its
    counters once it has had a long iteration, the warning's limiter."""

    __slots__ = ("label", "n", "recent", "last_end", "pending",
                 "counters", "warned_at", "swallowed")

    def __init__(self, label):
        self.label = label
        self.n = 0
        self.recent = collections.deque(maxlen=MEDIAN_WINDOW)
        self.last_end = 0.0
        self.pending = False
        self.counters = {}
        self.warned_at = None
        self.swallowed = 0

    def note(self, st, now, wall, args):
        gap = max(st.t0 - self.last_end, 0.0) if self.pending else 0.0
        self.last_end = now
        self.pending = bool(args.get("pending"))
        sync = st.seg.get("sync", 0.0)
        _iterations.append((st.t0, wall, gap, sync))
        took = gap + wall
        if took > LONG_OVER_S and self.n >= LONG_WARMUP:
            median = statistics.median(r[0] for r in self.recent)
            if took > LONG_FACTOR * median and took > median + LONG_OVER_S:
                self._long(st, now, wall, gap, sync, median, args)
        self.recent.append((took, gap, sync))
        self.n += 1

    def _long(self, st, now, wall, gap, sync, median, args):
        caller = max(gap - statistics.median(
            r[1] for r in self.recent), 0.0)
        in_sync = max(sync - statistics.median(
            r[2] for r in self.recent), 0.0)
        parts = {"caller": caller, "sync": in_sync,
                 "host": max(gap + wall - median - caller - in_sync, 0.0)}
        where = max(parts, key=parts.get)
        seen = dict(st.args, **args)
        rec = {"engine": self.label, "step": st.step, "t0": st.t0,
               "wall_s": wall, "gap_s": gap, "median_s": median,
               "segments": dict(st.seg), "where": where,
               "excess_s": parts,
               **{k: seen[k] for k in _STEP_ARGS if k in seen}}
        _long.append(rec)
        reg = _registry()
        for name, key, n in [("long_iterations", where, 1)] + [
                ("long_seconds", w, v) for w, v in parts.items() if v]:
            c = self.counters.get((name, key))
            if c is None:
                c = self.counters[name, key] = reg.counter(
                    "serve.step." + name, help=_LONG_HELP[name],
                    engine=self.label, where=key)
            c.inc(n)
        if (self.warned_at is not None
                and now - self.warned_at < WARN_EVERY_S):
            self.swallowed += 1
            return
        more = (f"; {self.swallowed} more long iterations since the "
                f"last such line" if self.swallowed else "")
        self.warned_at, self.swallowed = now, 0
        _get_channel("serve").warning("%s%s", _describe(rec), more)


def _describe(rec) -> str:
    """One long iteration as the line its warning carries: segments by
    size, then the step's own args."""
    segs = " ".join(f"{k} {v:.3f}" for k, v in sorted(
        rec["segments"].items(), key=lambda kv: -kv[1]))
    args = " ".join(f"{k} {rec[k]}" for k in _STEP_ARGS if k in rec)
    return (f"serve step {rec['step']} (engine {rec['engine']}) took "
            f"{rec['wall_s']:.3f} s after a {rec['gap_s']:.3f} s gap "
            f"(median iteration {rec['median_s']:.3f} s): {segs}; {args}")


# -- the hook ``trace.phase()`` calls -----------------------------------

def _phase_enter(name, args):
    """``serve.step`` opens a step record, and so does
    ``serve.prefix_build`` — an out-of-``step()`` work quantum on a
    disaggregated prefill specialist, whose engine never runs the
    decode step loop but whose dispatches are exactly this anatomy —
    unless a step is already open on this thread (a build driven from
    inside ``step()`` stays attributed to that step).  Any other phase
    pushes its segment onto the open step, if there is one.  Returns
    the token :func:`_phase_exit` wants, or None."""
    st = getattr(_tls, "cur", None)
    if name == "serve.step" or (name == "serve.prefix_build"
                                and st is None):
        p = _prof
        _tls.cur = _StepState(p, args.get("engine"), args.get("step"),
                              _clock if p is None else p._clock,
                              name == "serve.step")
        return _STEP
    if st is None:
        return None
    seg = _SEGMENT_OF.get(name)
    if seg is None:
        if not name.startswith("serve.dispatch."):
            return None
        seg = "dispatch"
    st.push(seg)
    return st


def _phase_exit(token, failed, args):
    if token is not _STEP:
        token.pop()
        if args:          # serve.schedule's admitted, chunks, launches
            token.args.update(args)
        return
    st = getattr(_tls, "cur", None)
    _tls.cur = None
    # a step that raised has no meaningful anatomy: drop its record
    if failed or st is None:
        return
    now = st.clock()
    while st.stack:          # a dangling fence closes at step end
        st.pop()
    wall = max(now - st.t0, 0.0)
    other = wall - sum(st.seg.values())
    if other > 0.0:
        st.seg["other"] = st.seg.get("other", 0.0) + other
    if st.is_step:
        e = _engines.get(st.engine)
        if e is None:
            e = _engines[st.engine] = _EngineLog(st.engine)
        e.note(st, now, wall, args)
    if st.owner is not None and st.owner is _prof:
        st.owner._publish_step(st, wall)


def _step_elapsed():
    """Seconds from the open step's start to its newest stamp (the end
    of the last segment closed): what ``Phase.step_elapsed`` reads."""
    st = getattr(_tls, "cur", None)
    return None if st is None else st.last - st.t0


_HOOK = (_phase_enter, _phase_exit, _step_elapsed)


def fence_device(out):
    """The executor-seam fence (``engine._ProfExec``, behind one
    ``_active`` read): block the host on a dispatch's output and book
    dispatch-return → ``block_until_ready`` as the step's ``device``
    segment.  A HOST-FENCE ESTIMATE: it serialises the dispatches it
    times and includes queueing behind earlier work — the device trace
    is the source for device time.  The block is the ONLY added work —
    it runs on already-dispatched outputs, so nothing new enters
    jitted code and the recompile pin holds.  Outside an open step, or
    in one that opened before :func:`enable`, it does nothing."""
    st = getattr(_tls, "cur", None)
    if st is None or st.owner is None:
        return
    st.push("device")
    _block(out)
    t0, dur = st.pop()
    st.dev += dur
    st.dev_windows.append((t0, dur))


# -- health/monitor read surface --------------------------------------

def section() -> dict:
    """``health_report()["serve"]["step_anatomy"]``: always a dict
    with an ``enabled`` key, so dashboards and the CI gate can assert
    on it unconditionally."""
    if _prof is None:
        return {"enabled": False}
    return _prof.section()


def why_slow_summary():
    """The compact step-anatomy rider on ``why_slow``: overall
    host/device split, the dominant host segment, and the culprit
    verdict.  None when the profiler is off or has no steps."""
    if _prof is None:
        return None
    return _prof.why_slow_summary()


def culprit(source=None):
    """The Watchdog feed: host-vs-device attribution for the LAST
    completed step of the engine behind heartbeat ``source``
    (``serve.e<label>``), or of the most recent step when the source
    doesn't parse.  None when the profiler is off or has no record."""
    if _prof is None:
        return None
    return _prof.culprit(source)


def records() -> list:
    """Snapshot of the per-step ring (for the dual-lane Chrome
    trace exporter)."""
    if _prof is None:
        return []
    return list(_prof._ring)


# -- the profiler ------------------------------------------------------

class _StepState:
    """One step's open record: an exclusive-time segment stack, and —
    for the profiler's sinks only (``owner``) — the host pieces and the
    device windows."""

    __slots__ = ("owner", "engine", "step", "t0", "last", "stack",
                 "seg", "args", "pieces", "dev", "dev_windows", "clock",
                 "is_step")

    def __init__(self, owner, engine, step, clock, is_step):
        self.owner = owner
        self.engine = engine
        self.step = step
        self.clock = clock
        self.is_step = is_step     # engine.step(), not a build quantum
        self.t0 = self.last = clock()
        self.stack = []
        self.seg = {}
        self.args = {}         # what the phases inside found (set())
        # for the profiler's ring alone: (segment, t_start, dur) host
        # intervals and (t_start, dur) device-busy intervals
        self.pieces = [] if owner is not None else None
        self.dev_windows = [] if owner is not None else None
        self.dev = 0.0

    def push(self, name):
        now = self.clock()
        if self.stack:
            # the parent's elapsed-so-far is the parent's, exclusively
            cur = self.stack[-1]
            dt = now - self.last
            self.seg[cur] = self.seg.get(cur, 0.0) + dt
            if self.pieces is not None:
                self.pieces.append((cur, self.last, dt))
        self.stack.append(name)
        self.last = now

    def pop(self):
        if not self.stack:
            return (self.last, 0.0)
        now = self.clock()
        name = self.stack.pop()
        t0, dt = self.last, now - self.last
        self.seg[name] = self.seg.get(name, 0.0) + dt
        if self.pieces is not None:
            self.pieces.append((name, t0, dt))
        self.last = now
        return (t0, dt)


class StepProfiler:
    """The sinks :func:`enable` adds to each step's record (module
    docstring): the fenced ``device`` column, histograms, ring, trace
    records.

    Single-writer per thread (each engine's step loop is
    single-threaded; concurrent engines on different threads each
    carry their own open step via a thread-local)."""

    def __init__(self, clock=None, ring=512, reg=None):
        self._clock = clock if clock is not None else time.perf_counter
        self._reg = reg if reg is not None else _registry()
        self._ring = collections.deque(maxlen=int(ring))
        self._metrics = {}      # engine label -> {"wall": h, ...}
        self._seg_metrics = {}  # (label, segment) -> Histogram
        self._registered = []
        self._agg = {}          # label -> {"steps", "wall_s",
        #                                   "device_s", "seg": {...}}
        self.steps = 0

    # -- recording -------------------------------------------------------
    def _publish_step(self, st, wall):
        """A sealed step (segments sum to ``wall``) into every sink."""
        seg = st.seg
        device = st.dev
        host = max(wall - device, 0.0)
        bubble = (host / wall) if wall > 0.0 else 0.0
        label = st.engine
        agg = self._agg.get(label)
        if agg is None:
            agg = self._agg[label] = {"steps": 0, "wall_s": 0.0,
                                      "device_s": 0.0, "seg": {}}
        agg["steps"] += 1
        agg["wall_s"] += wall
        agg["device_s"] += device
        aseg = agg["seg"]
        for k, v in seg.items():
            aseg[k] = aseg.get(k, 0.0) + v
        self._publish(label, wall, host, device, bubble, seg)
        rec = {"engine": label, "step": st.step, "t0": st.t0,
               "wall_s": wall, "host_s": host, "device_s": device,
               "bubble_frac": bubble, "segments": dict(seg),
               "pieces": st.pieces, "device_windows": st.dev_windows}
        self._ring.append(rec)
        self.steps += 1
        if _trace._active:
            # ride the trace buffer/ring (and, on a dist worker, the
            # trace federation): one host-lane record per step, one
            # device-lane record per window — per-host dual lanes in
            # the merged Chrome trace come from exactly these
            tid = threading.current_thread().name
            _trace._emit({
                "name": f"step/e{label}", "cat": "step.host",
                "ph": "X", "ts": st.t0, "dur": wall, "tid": tid,
                "depth": 0, "parent": None,
                "args": {"engine": label, "step": st.step,
                         "bubble_frac": round(bubble, 4),
                         "device_s": device,
                         "segments": {k: round(v, 6)
                                      for k, v in seg.items()}}})
            for t0w, dw in st.dev_windows:
                _trace._emit({
                    "name": f"device/e{label}", "cat": "step.device",
                    "ph": "X", "ts": t0w, "dur": dw, "tid": tid,
                    "depth": 0, "parent": None,
                    "args": {"engine": label, "step": st.step}})

    def _publish(self, label, wall, host, device, bubble, seg):
        m = self._metrics.get(label)
        if m is None:
            reg = self._reg
            m = {
                "wall": reg.histogram(
                    "serve.step.wall_s",
                    help="engine.step() wall seconds",
                    buckets=STEP_BUCKETS, engine=label),
                "host": reg.histogram(
                    "serve.step.host_s",
                    help="host-side step seconds (wall - device)",
                    buckets=STEP_BUCKETS, engine=label),
                "device": reg.histogram(
                    "serve.step.device_s",
                    help="host-fence estimate of device seconds "
                         "(dispatch -> block_until_ready, summed "
                         "per window)",
                    buckets=STEP_BUCKETS, engine=label),
                "bubble": reg.histogram(
                    "serve.step.bubble_frac",
                    help="(wall - fenced device) / wall: a host-"
                         "fence estimate of the idle fraction",
                    buckets=FRACTION_BUCKETS, engine=label),
            }
            self._metrics[label] = m
            self._registered += list(m.values())
        m["wall"].observe(wall)
        m["host"].observe(host)
        m["device"].observe(device)
        m["bubble"].observe(bubble)
        for name, v in seg.items():
            h = self._seg_metrics.get((label, name))
            if h is None:
                h = self._reg.histogram(
                    "serve.step.segment_s",
                    help="per-segment host/device step seconds",
                    buckets=STEP_BUCKETS, engine=label, segment=name)
                self._seg_metrics[(label, name)] = h
                self._registered.append(h)
            h.observe(v)

    # -- lifecycle -------------------------------------------------------
    def forget_engine(self, label):
        dead = list(self._metrics.get(label, {}).values())
        dead += [h for (lbl, _), h in self._seg_metrics.items()
                 if lbl == label]
        if dead:
            self._reg.remove(*dead)
            self._registered = [m for m in self._registered
                                if m not in dead]
        self._metrics.pop(label, None)
        for key in [k for k in self._seg_metrics if k[0] == label]:
            del self._seg_metrics[key]

    def unregister(self):
        if self._registered:
            self._reg.remove(*self._registered)
            self._registered = []
        self._metrics = {}
        self._seg_metrics = {}

    # -- reads -----------------------------------------------------------
    def section(self) -> dict:
        engines = {}
        for label, agg in self._agg.items():
            denom = sum(agg["seg"].values())
            wall = agg["wall_s"]
            n = agg["steps"]
            engines[label] = {
                "steps": n,
                "wall_s_total": wall,
                "wall_s_mean": wall / n if n else 0.0,
                "device_s_total": agg["device_s"],
                "host_s_total": max(wall - agg["device_s"], 0.0),
                "bubble_frac": (max(wall - agg["device_s"], 0.0)
                                / wall if wall > 0 else 0.0),
                # fractions over ONE denominator (the summed segment
                # chain) — they sum to 1 up to float rounding, the
                # ledger's exact-arithmetic idiom
                "fractions": ({k: v / denom
                               for k, v in sorted(agg["seg"].items())}
                              if denom > 0 else {}),
            }
        return {"enabled": True, "steps": self.steps,
                "engines": engines,
                "why_slow": self.why_slow_summary()}

    def why_slow_summary(self):
        wall = sum(a["wall_s"] for a in self._agg.values())
        if wall <= 0.0:
            return None
        device = sum(a["device_s"] for a in self._agg.values())
        host_seg = {}
        for a in self._agg.values():
            for k, v in a["seg"].items():
                if k != "device":
                    host_seg[k] = host_seg.get(k, 0.0) + v
        top = max(host_seg, key=host_seg.get) if host_seg else None
        bubble = max(wall - device, 0.0) / wall
        return {
            "bubble_frac": bubble,
            "device_frac": min(device / wall, 1.0),
            "host_frac": bubble,
            "top_host_segment": top,
            "top_host_segment_frac": (host_seg[top] / wall
                                      if top is not None else 0.0),
            "culprit": "host" if bubble >= 0.5 else "device",
        }

    def culprit(self, source=None):
        label = None
        if isinstance(source, str) and source.startswith("serve.e"):
            label = source[len("serve.e"):]
        for rec in reversed(self._ring):
            if label is not None and rec["engine"] != label:
                continue
            host_seg = {k: v for k, v in rec["segments"].items()
                        if k != "device"}
            top = (max(host_seg, key=host_seg.get)
                   if host_seg else None)
            return {
                "culprit": ("host" if rec["bubble_frac"] >= 0.5
                            else "device"),
                "bubble_frac": round(rec["bubble_frac"], 4),
                "host_s": rec["host_s"],
                "device_s": rec["device_s"],
                "top_host_segment": top,
            }
        return None
