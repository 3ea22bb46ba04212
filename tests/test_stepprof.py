"""observe.stepprof: step anatomy — the always-on step log and, behind
``enable()``, the host/device attribution.

The always-on half's contracts, each tested directly:

* **the log** — every step leaves ``(t0, wall_s, gap_s, sync_s)``; an
  iteration far over the engine's running median leaves its full
  record, counters by where the excess went (``caller`` / ``sync`` /
  ``host``) and one rate-limited warning; the gap counts only while
  work was left waiting.
* **cost** — with everything off a step reads the clock at most twice a
  phase, allocates no histogram, never fences a dispatch, and a quiet
  engine leaves the registry and the profiler's ring untouched.

The profiler's three contracts (``enable()``), each tested directly:

* **exactness** — exclusive-time segments sum to the step wall (one
  denominator, the ledger's seal-time idiom), host_s + device_s ==
  wall_s, and device windows sit inside the step span.
* **one accounting** — ``enable()`` adds sinks to the record the
  always-on path makes: the log goes on through it.
* **invisibility when on** — byte parity with the unprofiled engine
  and zero runtime recompiles (``block_until_ready`` on materialized
  outputs never enters jitted code).

Plus the publication surfaces: dedicated-ladder registry series that
die with their engine (the retire-unregisters contract, supervisor
restarts included), the dual-lane Chrome trace, health/why_slow
sections, the Watchdog culprit feed, prefix-build quanta on a
shipless engine, and FleetTelemetry's per-host lanes."""

import logging
import time

import numpy as np
import pytest

from singa_tpu import observe, tensor
from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from singa_tpu.observe import export, monitor, stepprof
from singa_tpu.observe.federate import FleetTelemetry
from singa_tpu.observe.health import health_report
from singa_tpu.observe.registry import MetricsRegistry, registry
from singa_tpu.serve import GenerationRequest, PagedConfig, \
    PrefixCacheConfig
from singa_tpu.serve.jitpin import jit_cache_size
from singa_tpu.utils.logging import get_channel


@pytest.fixture(scope="module")
def model():
    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.fixture(autouse=True)
def _clean():
    """Profiler off, the step log as a process that has built no engine
    has it, monitor off, tracing off around each test — all of it is
    process-global module state."""
    def clean():
        stepprof.disable()
        stepprof._reset()
        monitor.stop()
        observe.disable()
        observe.clear()

    clean()
    yield
    clean()


_PROMPTS = [np.arange(9) % 256, (np.arange(4) + 3) % 256,
            np.asarray([5, 1, 200])]
_NEWS = [6, 4, 5]


def _drain(eng, prompts=_PROMPTS, news=_NEWS):
    hs = [eng.submit(GenerationRequest(p, max_new_tokens=n,
                                       temperature=0.0))
          for p, n in zip(prompts, news)]
    for _ in range(200):
        if not eng.pending:
            break
        eng.step()
    return [[int(t) for t in h.result().tokens] for h in hs]


# ---------------------------------------------------------------------------
# invisibility when off
# ---------------------------------------------------------------------------

def test_disabled_mode_leaves_no_trace_in_registry_or_ring(model):
    eng = model.serve(max_slots=2)
    try:
        _drain(eng)
    finally:
        eng.close()
    assert stepprof.active() is False
    assert stepprof.profiler() is None
    assert stepprof.records() == []
    assert not [k for k in registry().snapshot()["histograms"]
                if k.startswith("serve.step.")]
    assert stepprof.section() == {"enabled": False}
    assert stepprof.why_slow_summary() is None
    assert stepprof.culprit("serve.e0") is None


def test_disabled_mode_reads_the_clock_twice_a_phase_at_most(
        model, monkeypatch):
    """The cost contract of the always-on half: with the profiler, the
    monitor and tracing off a step reads the clock at most twice a
    phase (the step log's stamps: none of the engine's own), allocates
    no histogram and fences nothing.  With monitoring on the engine
    still reads no clock of its own: the Watchdog's step time comes
    from the step log's stamps.  Counted by swapping the clocks."""
    from singa_tpu.observe import trace

    eng = model.serve(max_slots=2)
    h = eng.submit(GenerationRequest(_PROMPTS[0], max_new_tokens=20,
                                     temperature=0.0))
    eng.step()  # admission + first decode: compiles out of the way
    eng.step()
    real, real_phase = time.perf_counter, trace.phase
    own, log, phases = [0], [0], [0]

    def counting(calls):
        def clock():
            calls[0] += 1
            return real()
        return clock

    def counted_phase(*a, **kw):
        phases[0] += 1
        return real_phase(*a, **kw)

    def no_fence(out):
        raise AssertionError("the always-on half fenced a dispatch")

    try:
        monkeypatch.setattr(time, "perf_counter", counting(own))
        monkeypatch.setattr(stepprof, "_clock", counting(log))
        monkeypatch.setattr(trace, "phase", counted_phase)
        monkeypatch.setattr(stepprof, "fence_device", no_fence)
        eng.step()
        assert own[0] == 0
        assert 0 < log[0] <= 2 * phases[0]
        wd = monitor.start(thread=False, dump_on_hang=False)
        own[0] = log[0] = phases[0] = 0
        eng.step()
        assert own[0] == 0 and log[0] <= 2 * phases[0]
        fed = wd._sources["serve.e" + eng.stats.engine_label]
        last = stepprof.iterations()[-1]
        assert fed.n_samples == 1
        assert 0.0 < fed.ewma_mean <= last[1]
        monkeypatch.setattr(time, "perf_counter", real)
    finally:
        monitor.stop()
        while eng.pending:
            eng.step()
        h.result()
        eng.close()
    assert not [k for k in registry().snapshot()["histograms"]
                if k.startswith("serve.step.")]


# ---------------------------------------------------------------------------
# the always-on step log (injected clock, no engine: the phases alone)
# ---------------------------------------------------------------------------

class _Clk:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.fixture
def log(monkeypatch):
    """The always-on half on a clock the test moves, with what the
    ``serve`` channel was told."""
    clk = _Clk()
    monkeypatch.setattr(stepprof, "_clock", clk)
    stepprof.install()
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    keep = Keep(level=logging.WARNING)
    get_channel("serve").addHandler(keep)
    yield clk, lines
    get_channel("serve").removeHandler(keep)


def _step(clk, n, engine="7", gap=0.001, sync=0.005, emit=0.001,
          pending=True):
    """One engine iteration as ``engine.step()`` writes it: 10 ms as the
    defaults stand (1 gap, 2 dispatch, 5 sync, 1 emit, 1 schedule - the
    gap counts only after a step that left work)."""
    clk.t += gap
    with observe.phase("serve.step", cat="serve", engine=engine,
                       step=n) as ph:
        with observe.phase("serve.decode", cat="serve"):
            with observe.phase("serve.dispatch.paged_decode_step",
                               cat="serve"):
                clk.t += 0.002
            with observe.phase("serve.sync", cat="serve"):
                clk.t += sync
        with observe.phase("serve.emit", cat="serve") as em:
            clk.t += emit
            em.set(tokens=7)
        with observe.phase("serve.schedule", cat="serve") as sp:
            clk.t += 0.001
            sp.set(admitted=0, chunks=4, launches=1)
        ph.set(live=7, width=12, queue_depth=0, pending=pending)


def _long_counters(engine="7"):
    return {k: v for k, v in registry().snapshot()["counters"].items()
            if k.startswith("serve.step.long_")
            and f"engine={engine}" in k}


def test_a_long_sync_among_short_steps_is_logged_as_sync(log):
    clk, lines = log
    for n in range(300):
        _step(clk, n, sync=2.0 if n == 200 else 0.005)
    its = stepprof.iterations()
    assert len(its) == 300
    t0, wall, gap, sync = its[200]
    assert (wall, gap, sync) == pytest.approx((2.004, 0.001, 2.0))
    assert its[199][0] < t0 < its[201][0]
    rec, = stepprof.long_iterations()
    assert (rec["engine"], rec["step"], rec["where"]) == ("7", 200, "sync")
    assert rec["t0"] == t0 and rec["median_s"] == pytest.approx(0.010)
    assert rec["segments"] == pytest.approx(
        {"dispatch": 0.002, "sync": 2.0, "emit": 0.001,
         "schedule": 0.001})
    assert rec["excess_s"] == pytest.approx(
        {"caller": 0.0, "sync": 1.995, "host": 0.0}, abs=1e-9)
    assert {k: rec[k] for k in ("live", "width", "queue_depth",
                                "admitted", "chunks", "launches")} == {
        "live": 7, "width": 12, "queue_depth": 0, "admitted": 0,
        "chunks": 4, "launches": 1}
    got = _long_counters()
    assert got["serve.step.long_iterations{engine=7,where=sync}"] == 1
    assert got["serve.step.long_seconds{engine=7,where=sync}"] == \
        pytest.approx(1.995)
    assert set(got) == {
        "serve.step.long_iterations{engine=7,where=sync}",
        "serve.step.long_seconds{engine=7,where=sync}"}
    assert lines == [
        "serve step 200 (engine 7) took 2.004 s after a 0.001 s gap "
        "(median iteration 0.010 s): sync 2.000 dispatch 0.002 emit "
        "0.001 schedule 0.001; live 7 width 12 queue_depth 0 admitted "
        "0 chunks 4 launches 1"]
    # the window filters are on t0, half open
    assert stepprof.iterations(since=t0, until=its[201][0]) == [its[200]]
    assert stepprof.long_iterations(until=t0) == []
    assert stepprof.long_iterations(since=t0) == [rec]
    # the engine's close takes its counters, not what it logged
    stepprof.forget_engine("7")
    assert _long_counters() == {}
    assert len(stepprof.iterations()) == 300
    assert stepprof.long_iterations() == [rec]


def test_the_early_launch_pass_opens_no_segment(log):
    """``serve.launch`` (the launches that go out behind the decode
    program, before ``serve.sync``) is a phase with no segment of its
    own: the dispatches inside it are ``dispatch``, the rest of it
    ``other``, the record still seals to the wall, and the step's
    ``chunks`` / ``launches`` stay ``serve.schedule``'s totals."""
    clk, lines = log

    def step(n, launch_s):
        clk.t += 0.001
        with observe.phase("serve.step", cat="serve", engine="7",
                           step=n) as ph:
            with observe.phase("serve.decode", cat="serve"):
                with observe.phase("serve.dispatch.paged_decode_step",
                                   cat="serve"):
                    clk.t += 0.002
                with observe.phase("serve.launch", cat="serve") as lp:
                    clk.t += launch_s
                    with observe.phase("serve.dispatch.chunk_row",
                                       cat="serve"):
                        clk.t += 0.001
                    lp.set(launches=1, chunks=3)
                with observe.phase("serve.sync", cat="serve"):
                    clk.t += 0.005
            with observe.phase("serve.schedule", cat="serve") as sp:
                clk.t += 0.001
                sp.set(admitted=1, chunks=4, launches=2)
            ph.set(live=7, width=12, queue_depth=0, pending=True)

    for n in range(300):
        step(n, 0.5 if n == 200 else 0.0)
    rec, = stepprof.long_iterations()
    assert (rec["step"], rec["where"]) == (200, "host")
    assert rec["segments"] == pytest.approx(
        {"dispatch": 0.003, "sync": 0.005, "schedule": 0.001,
         "other": 0.5})
    assert sum(rec["segments"].values()) == pytest.approx(rec["wall_s"])
    assert (rec["admitted"], rec["chunks"], rec["launches"]) == (1, 4, 2)
    assert lines[0].endswith("admitted 1 chunks 4 launches 2")


def test_a_budgeted_engines_records_seal_with_early_launches(model):
    """A lane decoding beside a prompt of several steps' budget, the
    fences on: every step's segments seal to its wall, the fractions to
    1, and some of the launches went out ahead of the sync."""
    stepprof.enable()
    eng = model.serve(max_slots=2, paged=PagedConfig(
        block_size=8, num_blocks=32, prefill_token_budget=8))
    try:
        eng.submit(GenerationRequest(np.arange(6) % 256,
                                     max_new_tokens=12, temperature=0.0))
        eng.step()
        eng.submit(GenerationRequest(np.arange(40) % 256,
                                     max_new_tokens=2, temperature=0.0))
        while eng.pending:
            eng.step()
        assert 0 < eng._c_early_launches.value < eng._c_launches.value
        recs = stepprof.records()
        assert len(recs) == eng.step_count
        for r in recs:
            assert sum(r["segments"].values()) == \
                pytest.approx(r["wall_s"], abs=1e-9)
        fr, = [e["fractions"]
               for e in stepprof.section()["engines"].values()]
        assert abs(sum(fr.values()) - 1.0) < 1e-9
    finally:
        eng.close()


def test_a_gap_is_the_callers_only_while_work_was_waiting(log):
    clk, lines = log
    for n in range(100):
        _step(clk, n)
    _step(clk, 100, gap=1.0)                 # step 99 left work
    _step(clk, 101, pending=False)           # drains the engine
    _step(clk, 102, gap=1.0)                 # idle by design: no gap
    _step(clk, 103, emit=0.5)                # slow host code in step()
    caller, host = stepprof.long_iterations()
    assert (caller["step"], caller["where"]) == (100, "caller")
    assert caller["gap_s"] == pytest.approx(1.0)
    assert caller["excess_s"] == pytest.approx(
        {"caller": 0.999, "sync": 0.0, "host": 0.0}, abs=1e-9)
    assert (host["step"], host["where"]) == (103, "host")
    assert host["excess_s"] == pytest.approx(
        {"caller": 0.0, "sync": 0.0, "host": 0.499}, abs=1e-9)
    assert host["segments"]["emit"] == pytest.approx(0.5)
    assert [r[2] for r in stepprof.iterations()[100:104]] == \
        pytest.approx([1.0, 0.001, 0.0, 0.001])
    got = _long_counters()
    assert got["serve.step.long_iterations{engine=7,where=caller}"] == 1
    assert got["serve.step.long_iterations{engine=7,where=host}"] == 1
    assert "serve.step.long_iterations{engine=7,where=sync}" not in got


def test_warmup_and_the_rule_keep_ordinary_steps_out(log):
    clk, _ = log
    # a compile in an engine's first steps is not reported...
    for n in range(63):
        _step(clk, n, sync=3.0 if n == 5 else 0.005)
    # ...nor are 5 x the median under 100 ms over it, or 100 ms over a
    # median of 73 ms (the rule wants four times it as well)
    for n in range(63, 200):
        _step(clk, n, sync=0.046 if n == 150 else 0.005)
    for n in range(300):
        _step(clk, n, engine="8", sync=0.199 if n == 280 else 0.068)
    assert stepprof.long_iterations() == []
    _step(clk, 300, engine="8", sync=0.300)
    rec, = stepprof.long_iterations()
    assert (rec["engine"], rec["step"]) == ("8", 300)
    assert rec["median_s"] == pytest.approx(0.073)


def test_the_warning_is_limited_and_says_what_it_swallowed(log):
    clk, lines = log
    for n in range(100):
        _step(clk, n)
    _step(clk, 100, sync=1.0)                # logged
    _step(clk, 101, sync=1.0)                # 1 s later: counted
    _step(clk, 102, sync=1.0)                # 2 s later: counted
    for n in range(103, 400):                # ~3 s of ordinary steps
        _step(clk, n)
    _step(clk, 400, sync=1.0)                # 6 s after the first line
    assert len(stepprof.long_iterations()) == 4
    assert len(lines) == 2
    assert lines[0].startswith("serve step 100 (engine 7) took 1.004 s")
    assert lines[1].startswith("serve step 400 (engine 7) took 1.004 s")
    assert lines[1].endswith(
        "; 2 more long iterations since the last such line")
    assert _long_counters()[
        "serve.step.long_iterations{engine=7,where=sync}"] == 4
    # one engine's limiter is not another's
    for n in range(100):
        _step(clk, n, engine="8")
    _step(clk, 100, engine="8", sync=1.0)
    assert len(lines) == 3 and "(engine 8)" in lines[2]


def test_a_quiet_engine_logs_its_steps_and_nothing_else(model):
    """With nothing enabled a process that has served leaves
    ``iterations()`` covering its steps and no long one; the profiler
    is still off, no dispatch was fenced, the registry and the
    profiler's ring are as they were."""
    t0 = time.perf_counter()
    eng = model.serve(max_slots=2)
    try:
        _drain(eng)
        steps = eng.step_count
    finally:
        eng.close()
    its = stepprof.iterations(since=t0, until=time.perf_counter())
    assert len(its) == steps > 0
    assert all(w > 0 and g >= 0 and 0 < s < w for _, w, g, s in its[1:])
    assert its == sorted(its)
    assert stepprof.long_iterations() == []
    assert stepprof.active() is False and stepprof.records() == []
    snap = registry().snapshot()
    assert not [k for kind in snap.values() for k in kind
                if k.startswith("serve.step.")]


def test_the_always_on_path_keeps_parity_and_never_fences(
        model, monkeypatch):
    def no_fence(out):
        raise AssertionError("the always-on half fenced a dispatch")

    stepprof.enable()
    eng = model.serve(max_slots=2)
    try:
        want = _drain(eng)
    finally:
        eng.close()
    stepprof.disable()
    monkeypatch.setattr(stepprof, "fence_device", no_fence)
    jit0 = jit_cache_size()
    eng = model.serve(max_slots=2)
    try:
        got = _drain(eng)
    finally:
        eng.close()
    assert got == want, "the step log changed tokens"
    assert jit_cache_size() == jit0, "the step log entered jitted code"


def test_enable_adds_sinks_to_the_one_record(model):
    """``enable()`` on top of the always-on half: the same steps land
    in the log and in the profiler's ring, with one wall, and the
    fractions still seal to 1."""
    eng = model.serve(max_slots=2)
    try:
        _drain(eng)
        quiet = len(stepprof.iterations())
        assert quiet > 0 and stepprof.records() == []
        stepprof.enable()
        _drain(eng)
        recs, its = stepprof.records(), stepprof.iterations()[quiet:]
        assert len(recs) == len(its) > 0
        for r, (t0, wall, _, sync) in zip(recs, its):
            assert (r["t0"], r["wall_s"]) == (t0, wall)
            assert r["segments"].get("sync", 0.0) == sync
            assert sum(r["segments"].values()) == \
                pytest.approx(wall, abs=1e-9)
            assert r["device_s"] > 0
        fr, = [e["fractions"]
               for e in stepprof.section()["engines"].values()]
        assert abs(sum(fr.values()) - 1.0) < 1e-9
        stepprof.disable()
        _drain(eng)                   # the log goes on without it
        assert len(stepprof.iterations()) > quiet + len(its)
        assert len(stepprof.records()) == 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# exactness
# ---------------------------------------------------------------------------

def test_fractions_sum_to_one_and_ring_invariants(model):
    stepprof.enable()
    eng = model.serve(max_slots=2)
    try:
        _drain(eng)
        recs = stepprof.records()
        assert recs
        for r in recs:
            # host/device split is exact by construction
            assert r["host_s"] + r["device_s"] == \
                pytest.approx(r["wall_s"], abs=1e-12)
            # exclusive segments seal to the wall ("other" absorbs
            # unfenced time; "device" is a segment key too)
            assert sum(r["segments"].values()) == \
                pytest.approx(r["wall_s"], abs=1e-9)
            assert r["device_s"] > 0 and 0.0 < r["bubble_frac"] < 1.0
            for t0, dur in r["device_windows"]:
                assert r["t0"] <= t0
                assert t0 + dur <= r["t0"] + r["wall_s"] + 1e-9
        sec = stepprof.section()
        assert sec["enabled"] is True and sec["steps"] == len(recs)
        for e in sec["engines"].values():
            fr = e["fractions"]
            assert abs(sum(fr.values()) - 1.0) < 1e-9, fr
            assert "device" in fr and "schedule" in fr
        ws = sec["why_slow"]
        assert ws["culprit"] in ("host", "device")
        assert ws["bubble_frac"] + ws["device_frac"] == \
            pytest.approx(1.0, abs=1e-9)
        assert ws["top_host_segment"] not in (None, "device")
    finally:
        eng.close()


def test_segments_are_fed_by_phase_alone(model):
    """The engine and the graph runner hold no profiler fence of their
    own: every segment arrives through ``trace.phase()``'s hook, and a
    drained engine still shows the whole taxonomy it exercised."""
    import inspect
    import re

    from singa_tpu import model as model_mod
    from singa_tpu.serve import engine

    fence = re.compile(
        r"_stepprof\.(push|pop|begin|end|abort|begin_quantum)\b")
    assert not fence.search(inspect.getsource(engine))
    for fn in (engine.InferenceEngine.step,
               engine.InferenceEngine._decode_once,
               model_mod._GraphRunner.run, model_mod._GraphRunner._run):
        src = inspect.getsource(fn)
        assert "_trace.span(" not in src and "_stepprof" not in src
    stepprof.enable()
    eng = model.serve(max_slots=2)
    try:
        _drain(eng)
        fr, = [e["fractions"]
               for e in stepprof.section()["engines"].values()]
        assert {"schedule", "admit", "dispatch", "device", "sync",
                "emit"} <= set(fr) <= set(stepprof.SEGMENTS)
        for r in stepprof.records():
            assert sum(r["segments"].values()) == \
                pytest.approx(r["wall_s"], abs=1e-9)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# invisibility when on: parity + the recompile pin
# ---------------------------------------------------------------------------

def test_profiler_on_keeps_parity_and_compiles_nothing(model):
    eng = model.serve(max_slots=2)
    try:
        want = _drain(eng)
    finally:
        eng.close()
    jit0 = jit_cache_size()
    stepprof.enable()
    eng = model.serve(max_slots=2)
    try:
        got = _drain(eng)
    finally:
        eng.close()
    assert got == want, "profiler changed tokens"
    assert jit_cache_size() == jit0, "profiler entered jitted code"


# ---------------------------------------------------------------------------
# registry series: dedicated ladder, retire-unregisters
# ---------------------------------------------------------------------------

def test_series_use_dedicated_ladder_and_die_with_engine(model):
    stepprof.enable()
    eng = model.serve(max_slots=2)
    lbl = eng.stats.engine_label
    try:
        _drain(eng)
        snap = registry().snapshot()["histograms"]
        for fam in ("wall_s", "host_s", "device_s", "bubble_frac"):
            assert f"serve.step.{fam}{{engine={lbl}}}" in snap
        assert any(k.startswith("serve.step.segment_s{")
                   and f"engine={lbl}" in k for k in snap)
        # dedicated ladder: the 100us bucket exists and the running
        # dump satisfies the +Inf == _count cumulative invariant
        for m in registry().dump()["metrics"]:
            if not m["name"].startswith("serve.step."):
                continue
            assert m["kind"] == "histogram"
            if m["name"] != "serve.step.bubble_frac":
                assert m["buckets"][0][0] == pytest.approx(1e-4)
            assert m["buckets"][-1][0] == float("inf")
            assert m["buckets"][-1][1] == m["count"]
    finally:
        eng.close()
    # the engine's close forgot its series...
    assert not [k for k in registry().snapshot()["histograms"]
                if k.startswith("serve.step.")
                and f"engine={lbl}" in k]
    # ...and a fresh engine gets fresh ones under its own label
    eng2 = model.serve(max_slots=2)
    try:
        _drain(eng2)
        lbl2 = eng2.stats.engine_label
        assert lbl2 != lbl
        assert f"serve.step.wall_s{{engine={lbl2}}}" \
            in registry().snapshot()["histograms"]
    finally:
        eng2.close()


def test_disable_without_unregister_keeps_series_readable(model):
    stepprof.enable()
    eng = model.serve(max_slots=2)
    try:
        _drain(eng)
        stepprof.disable(unregister=False)
        # profiler off, series still in the exposition (the bench's
        # --prom-out ordering: disable BEFORE close, so the close's
        # forget_engine is a no-op on a dead profiler)
        assert stepprof.active() is False
        assert [k for k in registry().snapshot()["histograms"]
                if k.startswith("serve.step.")]
    finally:
        eng.close()
    assert [k for k in registry().snapshot()["histograms"]
            if k.startswith("serve.step.")]


def test_supervisor_restart_forgets_dead_label_and_holds_jit_pin(
        model):
    """A supervisor rebuild retires the dead engine's series, the
    fresh engine's steps register under its new label, and the
    rebuild recompiles nothing (executables are cached)."""
    from singa_tpu.resilience import FailAfterN, faults
    from singa_tpu.serve import EngineSupervisor

    stepprof.enable()
    sup = EngineSupervisor(model, max_slots=2, restart_budget=2)
    lbl0 = sup.engine.stats.engine_label
    try:
        hs = [sup.submit(GenerationRequest(p, max_new_tokens=n,
                                           temperature=0.0))
              for p, n in zip(_PROMPTS, _NEWS)]
        faults.inject("serve.decode_step", FailAfterN(2, times=1))
        jit0 = jit_cache_size()
        sup.run_until_complete(max_steps=500)
        faults.clear()
        assert sup.restarts == 1
        assert jit_cache_size() == jit0
        for h in hs:
            assert h.done()
        lbl1 = sup.engine.stats.engine_label
        assert lbl1 != lbl0
        snap = registry().snapshot()["histograms"]
        assert not [k for k in snap if k.startswith("serve.step.")
                    and f"engine={lbl0}" in k]
        assert f"serve.step.wall_s{{engine={lbl1}}}" in snap
    finally:
        faults.clear()
        sup.close()


# ---------------------------------------------------------------------------
# dual-lane Chrome trace
# ---------------------------------------------------------------------------

def test_dual_lane_export_shows_bubble_gaps(model):
    stepprof.enable()
    eng = model.serve(max_slots=2)
    lbl = eng.stats.engine_label
    try:
        _drain(eng)
        recs = stepprof.records()
    finally:
        eng.close()
    doc = export.chrome_trace([], steps=recs)
    ev = doc["traceEvents"]
    names = {e["args"]["name"] for e in ev if e.get("ph") == "M"
             and e["name"] == "thread_name" and e["pid"] == 2}
    assert f"e{lbl} host" in names and f"e{lbl} device" in names
    host = [e for e in ev if e.get("ph") == "X" and e["pid"] == 2
            and e["name"].startswith("step ")]
    segs = [e for e in ev if e.get("ph") == "X" and e["pid"] == 2
            and not e["name"].startswith(("step ", "device"))]
    dev = [e for e in ev if e.get("ph") == "X" and e["pid"] == 2
          and e["name"] == "device"]
    assert len(host) == len(recs) and segs and dev
    # the bubble is VISIBLE: device slices cover strictly less of the
    # lane than the step spans (gaps = the device sitting idle)
    assert sum(e["dur"] for e in dev) < sum(e["dur"] for e in host)
    # segment sub-slices never include the device pseudo-segment
    assert all(e["name"] != "device" for e in segs)
    assert doc["otherData"]["step_records"] == len(recs)


# ---------------------------------------------------------------------------
# health + Watchdog integration
# ---------------------------------------------------------------------------

def test_health_report_carries_step_anatomy(model):
    stepprof.enable()
    eng = model.serve(max_slots=2)
    try:
        _drain(eng)
        sa = health_report()["serve"]["step_anatomy"]
        assert sa["enabled"] is True and sa["steps"] > 0
        assert sa["why_slow"]["culprit"] in ("host", "device")
    finally:
        eng.close()
    assert health_report()["serve"]["step_anatomy"]["enabled"] is True


def test_watchdog_anomaly_names_host_vs_device_culprit(model):
    """A step-time anomaly's trace event carries the profiler's
    verdict for THAT engine: host-vs-device plus the dominant host
    segment — the 'why did this step spike' answer inline."""
    stepprof.enable()
    eng = model.serve(max_slots=2)
    src = "serve.e" + eng.stats.engine_label
    try:
        _drain(eng)
    finally:
        eng.close()

    class _Clk:
        t = 0.0

        def __call__(self):
            return self.t

    clk = _Clk()
    reg = MetricsRegistry()
    wd = monitor.Watchdog(timeout_s=100.0, clock=clk, reg=reg,
                          dump_on_hang=False, warmup=8)
    observe.enable(clock=clk)
    for i in range(20):
        wd.beat(src, step_time=0.10 + 0.01 * (i % 2))
        clk.t += 0.1
    wd.beat(src, step_time=5.0)
    ev = next(e for e in observe.events()
              if e["name"] == "monitor/step_time_anomaly")
    assert ev["args"]["culprit"] in ("host", "device")
    assert 0.0 < ev["args"]["bubble_frac"] < 1.0
    assert ev["args"]["top_host_segment"] is not None


# ---------------------------------------------------------------------------
# prefix-build quanta (the disaggregated prefill specialist)
# ---------------------------------------------------------------------------

def test_prefix_build_quanta_profile_without_a_step_loop(model):
    """A prefill specialist never runs ``step()`` — its anatomy comes
    from ``advance_prefix_build`` opening a quantum per budgeted
    advance, with the chunk dispatches timed through the same
    executor seam."""
    stepprof.enable()
    eng = model.serve(
        max_slots=2, paged=PagedConfig(block_size=8, num_blocks=64),
        prefix_cache=PrefixCacheConfig(block_size=8))
    try:
        doc = (np.arange(40) * 3 % 256).astype(np.int32)
        job = eng.start_prefix_build(doc)
        assert job is not None and not job.hit
        while not eng.advance_prefix_build(job, max_tokens=8):
            pass
        eng.export_prefix_image(job)
        recs = stepprof.records()
        assert recs, "build quanta produced no step records"
        lbl = eng.stats.engine_label
        assert all(r["engine"] == lbl for r in recs)
        assert sum(len(r["device_windows"]) for r in recs) >= 4
        assert all(r["device_s"] > 0 for r in recs)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# federation: per-host lanes + per-host anatomy
# ---------------------------------------------------------------------------

def _step_host_rec(ts, wall, dev):
    return {"name": "step/e0", "cat": "step.host", "ph": "X",
            "ts": ts, "dur": wall, "tid": "MainThread", "depth": 0,
            "parent": None,
            "args": {"engine": "0", "step": 1,
                     "bubble_frac": round(1 - dev / wall, 4),
                     "device_s": dev, "segments": {}}}


def _step_dev_rec(ts, dur):
    return {"name": "device/e0", "cat": "step.device", "ph": "X",
            "ts": ts, "dur": dur, "tid": "MainThread", "depth": 0,
            "parent": None, "args": {"engine": "0", "step": 1}}


def _host_dump(bub_sum, n):
    return {"metrics": [
        {"name": "serve.step.bubble_frac", "kind": "histogram",
         "labels": {"engine": "0"}, "sum": bub_sum, "count": n},
        {"name": "serve.step.wall_s", "kind": "histogram",
         "labels": {"engine": "0"}, "sum": 0.5, "count": n},
    ]}


def test_fleet_telemetry_builds_per_host_step_lanes():
    class _Clk:
        def __call__(self):
            return 1000.0

    ft = FleetTelemetry(clock=_Clk())
    ft.host_online("w0")
    ft.host_online("w1")
    for i, host in enumerate(("w0", "w1")):
        ft.ingest(host, {
            "trace": [_step_host_rec(10.0 + i, 0.02, 0.008),
                      _step_dev_rec(10.001 + i, 0.008)],
            "registry": _host_dump(0.6 * (i + 1), 2 + i),
        })
    doc = ft.chrome_trace(events=[], requests=[])
    by_cat = {}
    for e in doc["traceEvents"]:
        if e.get("cat") in ("step.host", "step.device") \
                and e["pid"] >= 10:
            by_cat.setdefault(e["cat"], set()).add(e["pid"])
    assert by_cat["step.host"] == by_cat["step.device"] == {10, 11}
    sec = ft.section()
    for i, host in enumerate(("w0", "w1")):
        a = sec["hosts"][host]["step_anatomy"]
        assert a["steps"] == 2 + i
        assert a["bubble_frac"] == pytest.approx(0.6 * (i + 1)
                                                 / (2 + i))
    # a host that never shipped the families answers None, not zero
    ft.host_online("w2")
    ft.ingest("w2", {"registry": {"metrics": []}})
    assert ft.section()["hosts"]["w2"]["step_anatomy"] is None
