"""observe.stepprof: step-anatomy host/device attribution.

The profiler's three contracts, each tested directly:

* **exactness** — exclusive-time segments sum to the step wall (one
  denominator, the ledger's seal-time idiom), host_s + device_s ==
  wall_s, and device windows sit inside the step span.
* **invisibility when off** — no registry series, no ring, and ZERO
  extra clock calls at the engine seams (the Watchdog's two
  ``perf_counter`` calls per step are the whole budget, counted by
  monkeypatching the clock).
* **invisibility when on** — byte parity with the unprofiled engine
  and zero runtime recompiles (``block_until_ready`` on materialized
  outputs never enters jitted code).

Plus the publication surfaces: dedicated-ladder registry series that
die with their engine (the retire-unregisters contract, supervisor
restarts included), the dual-lane Chrome trace, health/why_slow
sections, the Watchdog culprit feed, prefix-build quanta on a
shipless engine, and FleetTelemetry's per-host lanes."""

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


@pytest.fixture(scope="module")
def model():
    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.fixture(autouse=True)
def _clean():
    """Profiler off, monitor off, tracing off around each test — all
    three are process-global module state."""
    stepprof.disable()
    monitor.stop()
    observe.disable()
    observe.clear()
    yield
    stepprof.disable()
    monitor.stop()
    observe.disable()
    observe.clear()


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


def test_disabled_mode_adds_zero_clock_calls(model, monkeypatch):
    """The whole per-step clock budget with the profiler OFF is the
    Watchdog's two ``perf_counter`` calls — and zero with monitoring
    off too.  Counted by swapping the clock itself."""
    eng = model.serve(max_slots=2)
    h = eng.submit(GenerationRequest(_PROMPTS[0], max_new_tokens=20,
                                     temperature=0.0))
    eng.step()  # admission + first decode: compiles out of the way
    eng.step()
    real = time.perf_counter
    calls = [0]

    def counting():
        calls[0] += 1
        return real()

    try:
        monkeypatch.setattr(time, "perf_counter", counting)
        calls[0] = 0
        eng.step()
        assert calls[0] == 0
        monitor.start(thread=False, dump_on_hang=False)
        calls[0] = 0
        eng.step()
        assert calls[0] == 2
        monkeypatch.setattr(time, "perf_counter", real)
    finally:
        monitor.stop()
        while eng.pending:
            eng.step()
        h.result()
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
