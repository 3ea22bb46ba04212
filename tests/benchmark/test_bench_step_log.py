"""``benchmark/readers/step_log.py``: the rare long iterations of a serve
window, read from the program's always-on step log.

On synthetic logs: the span's edges (the traced slice is left out), the
long rule at the three cells' medians, the three-way split of a long
iteration's excess, 0.0 for a quiet window and ``None`` for a program
that keeps no log.  Then whole test-size runs through ``run.measure`` with
an engine that is made to stall once: inside ``step()`` reads as ``host``,
between two steps as ``caller``.  (What those runs time means nothing; a
0.3 s sleep is the one thing in them that is far over a CPU step.)
"""

import time
import types

import pytest
from bench_util import measure, tiny_cell

from benchmark.harness import loader, peaks, profile
from singa_tpu.observe import stepprof

step_log = loader.load_module("readers", "step_log")

SERVE_LIMITS = {"served_logit_gap_mean": 1e-5, "served_logit_gap_max": 1e-4,
                "malformed_results": 0, "unchecked": 0}


# ---------------------------------------------------------------------------
# synthetic logs
# ---------------------------------------------------------------------------

def _log(n, step_s, t0=1000.0, gap_s=0.0005, sync_share=0.6, stalls=()):
    """``n`` iterations of ``step_s`` back to back from ``t0``;
    ``stalls``: {index: (more wall not in sync, more gap, more sync)}."""
    stalls, out, t = dict(stalls), [], t0
    for i in range(n):
        host, gap, sync = stalls.get(i, (0.0, 0.0, 0.0))
        gap += gap_s
        wall = step_s - gap_s + host + sync
        t += gap
        out.append((t, wall, gap, sync_share * step_s + sync))
        t += wall
    return out


def _ctx(monkeypatch, iterations, setup_end=1000.0, window_s=40.0,
         traced_s=3.0, records=()):
    def between(rows, t0_of):
        def read(since=None, until=None):
            return [r for r in rows if since <= t0_of(r) < until]
        return read

    monkeypatch.setattr(stepprof, "iterations",
                        between(iterations, lambda r: r[0]))
    monkeypatch.setattr(stepprof, "long_iterations",
                        between(list(records), lambda r: r["t0"]))
    return dict(run=dict(setup_end=setup_end, window_s=window_s),
                cell=dict(cell=dict(trace_window=dict(length_s=traced_s))))


def _all(ctx):
    return ({w: step_log.read(ctx, "stall_s", where=w)
             for w in step_log.WHERE},
            step_log.read(ctx, "excess_max_ms"))


def test_a_quiet_window_reads_zero_and_not_none(monkeypatch, capsys):
    ctx = _ctx(monkeypatch, _log(2500, 0.014))
    assert _all(ctx) == ({"caller": 0.0, "sync": 0.0, "host": 0.0}, 0.0)
    said = capsys.readouterr().out.splitlines()
    # once a run: a header and the ten longest, whatever was read
    assert len(said) == 11 and "0 long" in said[0]
    assert "2500 iterations" in said[0] and "median 14.00 ms" in said[0]


def test_a_program_without_the_log_reads_none(monkeypatch):
    nothing = ({"caller": None, "sync": None, "host": None}, None)
    # so does a window in which the engine never stepped
    assert _all(_ctx(monkeypatch, [])) == nothing
    ctx = _ctx(monkeypatch, _log(100, 0.014))
    monkeypatch.delattr(stepprof, "iterations")
    assert _all(ctx) == nothing


def test_the_span_is_the_untraced_part_of_the_window(monkeypatch):
    """Window 1000..1040 with its last 3 s traced: a stall before the
    window, one in the traced slice (where starting the profiler stalls
    the driver's loop in every traced run) and one after it are not
    read; the two inside the span are."""
    its = _log(4000, 0.0125, t0=990.0, stalls={
        100: (0.0, 2.0, 0.0),          # ~993: before the window
        700: (0.0, 0.5, 0.0),          # ~1001: inside
        2950: (0.0, 0.25, 0.0),        # ~1030: inside
        3600: (0.0, 1.5, 0.0),         # ~1039: the traced slice
        3900: (0.0, 4.0, 0.0)})        # ~1047: after the window
    t0s = [it[0] for it in its]
    assert t0s[100] < 1000.0 < t0s[700] < t0s[2950] < 1037.0
    assert 1037.0 < t0s[3600] < 1040.0 < t0s[3900]
    stalls, worst = _all(_ctx(monkeypatch, its))
    assert stalls == pytest.approx(
        {"caller": 0.75, "sync": 0.0, "host": 0.0})
    assert worst == pytest.approx(500.0)
    # half open: an iteration that starts at the span's end is outside
    ctx = _ctx(monkeypatch, its, setup_end=t0s[700], window_s=3.0 + (
        t0s[2950] - t0s[700]))
    assert _all(ctx)[0]["caller"] == pytest.approx(0.5)
    # where the run has the driver's tracer, the span ends where that
    # says the profiler starts, whatever the window overran by
    ctx = _ctx(monkeypatch, its, window_s=43.0)
    ctx["run"]["tracer"] = types.SimpleNamespace(start_s=37.0)
    assert _all(ctx)[0]["caller"] == pytest.approx(0.75)


@pytest.mark.parametrize("median_ms, just_under_ms, just_over_ms", [
    (6.6, 106.0, 107.0),        # gpt2l-serve-chat: M + 100 ms binds
    (14.0, 113.5, 114.5),       # gpt2l-serve-longdoc: the same
    (73.0, 291.0, 293.0),       # trinitymini-serve-mixedlen: 4 M binds
])
def test_the_long_rule_at_the_cells_medians(monkeypatch, median_ms,
                                            just_under_ms, just_over_ms):
    m = median_ms / 1e3
    under = _log(500, m, stalls={250: (just_under_ms / 1e3 - m, 0, 0)})
    assert _all(_ctx(monkeypatch, under))[1] == 0.0
    over = _log(500, m, stalls={250: (just_over_ms / 1e3 - m, 0, 0)})
    stalls, worst = _all(_ctx(monkeypatch, over))
    assert worst == pytest.approx(just_over_ms - median_ms)
    assert stalls == pytest.approx(
        {"caller": 0.0, "sync": 0.0, "host": (just_over_ms - median_ms) / 1e3})


def test_the_excess_is_split_three_ways_and_the_longest_are_said(
        monkeypatch, capsys):
    its = _log(2000, 0.014, stalls={
        300: (0.0, 0.0, 2.4),          # the device wait
        900: (0.5, 0.0, 0.0),          # the engine's host code
        1500: (0.0, 1.0, 0.0),         # the caller
        1800: (0.2, 0.3, 0.1)})        # some of each
    rec = dict(engine="0", step=4242, t0=its[900][0], wall_s=0.514,
               gap_s=0.0005, median_s=0.014, where="host",
               segments={"emit": 0.501, "sync": 0.0084, "dispatch": 0.003},
               live=7, width=12, queue_depth=0, admitted=0, chunks=4,
               launches=1)
    ctx = _ctx(monkeypatch, its, records=[rec])
    stalls, worst = _all(ctx)
    assert stalls == pytest.approx(
        {"caller": 1.3, "sync": 2.5, "host": 0.7})
    assert worst == pytest.approx(2400.0)
    said = capsys.readouterr().out.splitlines()
    assert len(said) == 11 and "4 long" in said[0]      # said once
    assert "LONG: caller 0.000 sync 2.400 host 0.000 s" in said[1]
    assert "LONG: caller 1.000 sync 0.000 host 0.000 s" in said[2]
    assert "LONG: caller 0.300 sync 0.100 host 0.200 s" in said[3]
    # the program's own record names the host segment and the step's args
    assert said[4].endswith(
        "LONG: caller 0.000 sync 0.000 host 0.500 s  [step 4242: emit "
        "0.501 sync 0.008 dispatch 0.003; live 7 width 12 queue_depth 0 "
        "admitted 0 chunks 4 launches 1]")
    assert "LONG" not in said[5]
    with pytest.raises(ValueError, match="unknown step_log"):
        step_log.read(ctx, "stall_s", where="device")
    with pytest.raises(ValueError, match="unknown step_log"):
        step_log.read(ctx, "p99")


# ---------------------------------------------------------------------------
# whole runs, with an engine made to stall once inside the untraced span
# ---------------------------------------------------------------------------

class _NoTracer:
    """The window's tracer with the profiler left out: on the CPU the
    trace has no device plane and the run would end there; the step log
    needs none."""

    done = False

    def __init__(self, root, length_s, seconds):
        self.out_dir = None

    def poll(self, now_in_window):
        pass

    def stop(self):
        pass


def _traced_run(monkeypatch, name, tamper):
    monkeypatch.setattr(profile, "Tracer", _NoTracer)
    monkeypatch.setattr(peaks, "peaks",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    cell = tiny_cell(name)
    # or a 1.5 s window has no untraced part to speak of
    cell["cell"]["trace_window"] = {"length_s": 0.3}
    line = measure(cell, SERVE_LIMITS, seconds=1.5, trace=True,
                   tamper=tamper)
    assert line["correct"] is True and line["failed"] == 0
    return {k: v["value"] for k, v in line["metrics"].items()}


class _Once:
    """Fires once, 0.3 s into the window: the pre-roll is 0.5 s and the
    untraced span the window's first 1.2 s.  Traffic starts with the
    first request that carries the driver's ``on_token``."""

    def __init__(self, eng):
        self.eng, self.start, self.fired = eng, None, False
        submit = eng.submit

        def watched(req):
            if self.start is None and req.on_token is not None:
                self.start = time.perf_counter()
            return submit(req)
        eng.submit = watched

    def due(self):
        if (self.fired or self.start is None
                or time.perf_counter() < self.start + 0.8):
            return False
        self.fired = True
        return True


def stall_in_decode(eng):
    once, decode = _Once(eng), eng._decode_once

    def slow():
        if once.due():
            time.sleep(0.3)
        return decode()
    eng._decode_once = slow


def stall_between_steps(eng):
    once, step, left = _Once(eng), eng.step, [False]

    def late():
        if left[0] and once.due():     # work was waiting: the caller's
            time.sleep(0.3)
        left[0] = step()
        return left[0]
    eng.step = late


@pytest.mark.parametrize("tamper, where", [
    (stall_in_decode, "host"), (stall_between_steps, "caller")])
def test_a_stall_of_a_closed_loop_reads_where_it_happened(
        monkeypatch, capsys, tamper, where):
    got = _traced_run(monkeypatch, "gpt2l-serve-longdoc", tamper)
    # (the other two read 0 but for noise of this host's own: a loaded
    # test machine may add one short hiccup, never a second 0.3 s)
    for w in step_log.WHERE:
        v = got["stall_s." + w]
        assert (0.25 <= v < 1.0) if w == where else (v < 0.2), (w, got)
    assert "iteration_excess_max_ms" not in got     # chat's alone
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("step log:")]
    assert len(said) == 11 and "LONG: " in said[1]


def test_a_stall_in_chat_reads_as_its_largest_excess(monkeypatch):
    got = _traced_run(monkeypatch, "gpt2l-serve-chat", stall_in_decode)
    assert 250.0 <= got["iteration_excess_max_ms"] < 1000.0
    assert not [k for k in got if k.startswith("stall_s.")]
