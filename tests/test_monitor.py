"""observe.monitor + observe.health: flight recorder bounds, crash
bundles, watchdog hang/anomaly firing rules (injectable clock), MFU
accounting honesty (nan, never 0, never a crash), and serve SLO
violation counters.

Everything host-side and deterministic: the watchdog is driven by
``check()`` on a fake clock (no thread), metrics live in private
registries, and crash bundles land in tmp_path."""

import glob
import json
import math
import os
import sys

import pytest

from singa_tpu import observe
from singa_tpu.observe import monitor
from singa_tpu.observe.health import SLO, health_report
from singa_tpu.observe.registry import MetricsRegistry


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


@pytest.fixture(autouse=True)
def _clean_monitor():
    """Monitoring off, recorder detached, tracing off around each
    test — the module-level monitor is process-global state."""
    monitor.stop()
    monitor.uninstall_crash_handler()
    observe.disable()
    observe.clear()
    yield
    monitor.stop()
    monitor.uninstall_crash_handler()
    observe.disable()
    observe.clear()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_records_with_tracing_off_and_stays_bounded():
    rec = monitor.flight_recorder()
    rec.clear()
    rec.start(capacity=100)
    try:
        assert not observe.is_enabled()
        for i in range(1000):  # 10x capacity
            observe.event(f"e{i}", cat="x", i=i)
        assert len(rec) == 100
        # the ring keeps the TAIL (newest 100), oldest first
        evs = rec.events()
        assert evs[0]["name"] == "e900" and evs[-1]["name"] == "e999"
        # independence: the main trace buffer saw NOTHING
        assert observe.events() == []
    finally:
        rec.stop()
    # detached: emissions stop reaching the ring
    observe.event("after-stop")
    assert len(rec) == 100


def test_flight_recorder_and_tracing_compose():
    rec = monitor.flight_recorder()
    rec.clear()
    rec.start(capacity=10)
    observe.enable(clock=FakeClock())
    try:
        with observe.span("s", cat="x"):
            pass
        assert [e["name"] for e in observe.events()] == ["s"]
        assert [e["name"] for e in rec.events()] == ["s"]
    finally:
        rec.stop()


# ---------------------------------------------------------------------------
# crash bundles
# ---------------------------------------------------------------------------

def test_dump_report_roundtrips_through_json(tmp_path):
    rec = monitor.flight_recorder()
    rec.clear()
    rec.start(capacity=128)
    try:
        observe.registry().counter("monitor_test.count").inc(3)
        for i in range(60):
            observe.event(f"e{i}", cat="t")
        path = monitor.dump_report(path=str(tmp_path / "bundle.json"),
                                   reason="unit-test")
        d = json.loads(open(path).read())
    finally:
        rec.stop()
    assert d["schema"] == "singa_tpu.crash/1"
    assert d["reason"] == "unit-test"
    assert len(d["recent_events"]) >= 50
    assert d["registry"]["counters"]["monitor_test.count"] >= 3
    assert d["host"]["pid"] == os.getpid()
    assert "process_index" in d["host"]
    assert isinstance(d["cost_tables"], list)


def test_crash_handler_dumps_on_uncaught_exception(tmp_path, monkeypatch):
    """The acceptance path: a synthetic run dies mid-step on an
    injected exception; a parseable bundle with the last >= 50 events
    and a registry snapshot must be on disk afterwards."""
    monkeypatch.setenv("SINGA_TPU_CRASH_DIR", str(tmp_path))
    # chain onto a silent hook so the test log stays clean
    monkeypatch.setattr(sys, "excepthook", lambda *a: None)
    monitor.flight_recorder().clear()
    monitor.install_crash_handler(signals=())
    try:
        for i in range(75):
            observe.event(f"step{i}", cat="train", step=i)
        try:
            raise RuntimeError("injected mid-step failure")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
    finally:
        monitor.uninstall_crash_handler()
        monitor.flight_recorder().stop()
    bundles = glob.glob(str(tmp_path / "monitor-crash-*.json"))
    assert len(bundles) == 1
    d = json.loads(open(bundles[0]).read())
    assert "injected mid-step failure" in d["reason"]
    assert "RuntimeError" in d["traceback"]
    assert len(d["recent_events"]) >= 50
    assert set(d["registry"]) == {"counters", "gauges", "histograms"}
    # uninstall restored the (silenced) previous hook
    assert sys.excepthook.__name__ == "<lambda>"


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_fires_exactly_once_per_missed_heartbeat():
    clk = FakeClock()
    reg = MetricsRegistry()
    wd = monitor.Watchdog(timeout_s=10.0, clock=clk, reg=reg,
                          dump_on_hang=False)
    wd.beat("train", step_time=0.1)
    clk.advance(5.0)
    assert wd.check() == []          # within timeout
    clk.advance(6.0)
    assert wd.check() == ["train"]   # missed -> fires
    clk.advance(100.0)
    assert wd.check() == []          # latched: ONE incident, not one/poll
    assert wd.hangs == 1
    wd.beat("train", step_time=0.1)  # recovery resets the latch
    clk.advance(11.0)
    assert wd.check() == ["train"]
    assert wd.hangs == 2
    s = wd.summary()
    assert s["sources"]["train"]["hang_latched"] is True
    assert s["hangs"] == 2


def test_watchdog_hang_emits_stacks_and_dumps_bundle(tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv("SINGA_TPU_CRASH_DIR", str(tmp_path))
    clk = FakeClock()
    rec = monitor.flight_recorder()
    rec.clear()
    rec.start(capacity=64)
    observe.enable(clock=clk)
    try:
        wd = monitor.Watchdog(timeout_s=1.0, clock=clk,
                              reg=MetricsRegistry())
        wd.beat("serve", step_time=0.01)
        clk.advance(2.0)
        assert wd.check() == ["serve"]
        hang = next(e for e in observe.events()
                    if e["name"] == "monitor/hang")
        assert hang["args"]["source"] == "serve"
        assert any("MainThread" in t for t in hang["args"]["threads"])
        assert wd.last_dump is not None
        d = json.loads(open(wd.last_dump).read())
        assert d["reason"] == "hang:serve"
        assert "MainThread" in d["thread_stacks"]
    finally:
        rec.stop()


def test_watchdog_step_time_anomaly_zscore():
    clk = FakeClock()
    reg = MetricsRegistry()
    wd = monitor.Watchdog(timeout_s=100.0, clock=clk, reg=reg,
                          dump_on_hang=False, warmup=8)
    observe.enable(clock=clk)
    # steady-but-not-constant feed (constant would keep the EWMA
    # variance at exactly 0, which disables the z-test by design)
    for i in range(20):
        wd.beat("train", step_time=0.10 + 0.01 * (i % 2))
        clk.advance(0.1)
    anom = reg.counter("train.step_time_anomalies",
                       process=wd._process)
    assert anom.value == 0
    wd.beat("train", step_time=5.0)     # ~1000 sigma
    assert anom.value == 1
    ev = next(e for e in observe.events()
              if e["name"] == "monitor/step_time_anomaly")
    assert ev["args"]["source"] == "train" and ev["args"]["z"] > 6
    # fresh-compile dispatches are liveness-only: no sample, no anomaly
    wd.beat("train", step_time=50.0, fresh_compile=True)
    assert anom.value == 1
    # per-process straggler histogram got every replay sample
    h = reg.histogram("train.step_time", process=wd._process)
    assert h.count == 21


def test_watchdog_multi_step_beat_normalizes_per_step():
    reg = MetricsRegistry()
    wd = monitor.Watchdog(clock=FakeClock(), reg=reg,
                          dump_on_hang=False)
    wd.beat("train", step_time=20.0, steps=100)  # one K-step dispatch
    h = reg.histogram("train.step_time", process=wd._process)
    assert h.summary()["max"] == pytest.approx(0.2)
    assert wd.summary()["sources"]["train"]["beats"] == 100


# ---------------------------------------------------------------------------
# MFU accounting
# ---------------------------------------------------------------------------

def test_mfu_gauge_is_nan_without_cost_table_or_known_backend(
        monkeypatch):
    # no compiled graph step anywhere: step_flops has no table to read
    # (extra cost sources too — serve-side AOT compiles from earlier
    # test modules register paged cost tables process-wide, and this
    # test's contract is "no table ANYWHERE")
    monkeypatch.setattr("singa_tpu.model._graph_runners", [])
    monkeypatch.setattr(
        "singa_tpu.observe.monitor._extra_cost_sources", [])
    clk = FakeClock()
    reg = MetricsRegistry()
    meter = monitor.MfuMeter(reg=reg, clock=clk)
    # nan BEFORE any sample too (gauges initialize to nan, not 0)
    assert math.isnan(reg.gauge("train.mfu").value)
    reg.counter("train.steps").inc(50)
    clk.advance(10.0)
    s = meter.sample()                    # must not raise
    assert s["steps_per_s"] == pytest.approx(5.0)
    assert math.isnan(s["step_flops"])
    assert math.isnan(s["model_flops_per_s"])
    assert math.isnan(s["mfu"]) and not s["mfu"] == 0
    assert math.isnan(reg.gauge("train.mfu").value)
    assert math.isnan(reg.gauge("train.model_flops_per_s").value)


def test_mfu_math_against_known_peak(monkeypatch):
    monkeypatch.setattr(monitor, "step_flops", lambda: 1e12)
    monkeypatch.setattr(monitor, "peak_flops",
                        lambda device_kind=None: 275e12)
    clk = FakeClock()
    reg = MetricsRegistry()
    meter = monitor.MfuMeter(reg=reg, clock=clk)
    reg.counter("train.steps").inc(100)
    clk.advance(2.0)
    s = meter.sample()
    assert s["model_flops_per_s"] == pytest.approx(50 * 1e12)
    assert s["mfu"] == pytest.approx(50 / 275)
    assert reg.gauge("train.mfu").value == pytest.approx(50 / 275)


def test_mfu_zero_step_interval_is_nan_pair(monkeypatch):
    """An interval with ZERO train steps (a process serving, not
    training) publishes model_flops_per_s AND mfu as nan TOGETHER —
    never a hard 0.0 flops/s next to a null mfu (the committed
    BENCH_SERVE health.train inconsistency on unknown-peak backends):
    a busy process must never read as 0 flops/s, whatever the
    backend's peak table knows."""
    monkeypatch.setattr(monitor, "step_flops", lambda: 1e12)
    clk = FakeClock()
    reg = MetricsRegistry()
    meter = monitor.MfuMeter(reg=reg, clock=clk)
    for peak in (275e12, float("nan")):   # known AND unknown peak
        monkeypatch.setattr(monitor, "peak_flops",
                            lambda device_kind=None, p=peak: p)
        clk.advance(5.0)                  # a real interval, 0 steps
        s = meter.sample()
        assert math.isnan(s["model_flops_per_s"]), s
        assert math.isnan(s["mfu"]), s
        assert math.isnan(
            reg.gauge("train.model_flops_per_s").value)
        assert math.isnan(reg.gauge("train.mfu").value)
    # and a real training interval afterwards still rates normally
    monkeypatch.setattr(monitor, "peak_flops",
                        lambda device_kind=None: 100e12)
    reg.counter("train.steps").inc(10)
    clk.advance(10.0)
    assert meter.sample()["model_flops_per_s"] == pytest.approx(1e12)


def test_mfu_read_does_not_reset_the_sampling_window(monkeypatch):
    """health_report() must not shrink the watchdog thread's rate
    interval to ~0 (which would publish a misleading 0 for a process
    that just trained hard) — read() returns the last published
    sample; back-to-back sample()s inside MIN_INTERVAL_S are no-ops."""
    monkeypatch.setattr(monitor, "step_flops", lambda: 1e12)
    monkeypatch.setattr(monitor, "peak_flops",
                        lambda device_kind=None: 100e12)
    clk = FakeClock()
    reg = MetricsRegistry()
    meter = monitor.MfuMeter(reg=reg, clock=clk)
    reg.counter("train.steps").inc(100)
    clk.advance(10.0)
    s1 = meter.sample()                 # 10 steps/s
    assert s1["mfu"] == pytest.approx(0.1)
    clk.advance(0.01)                   # a report lands right after
    assert meter.sample() is s1         # short interval: unchanged
    assert meter.read() is s1           # read never mutates
    assert reg.gauge("train.mfu").value == pytest.approx(0.1)


def test_mfu_first_sample_in_tiny_interval_is_nan_not_zero(
        monkeypatch):
    """health_report() milliseconds after monitor.start() on a busy
    TPU: 0 steps over a ~0s window must report nan, never publish 0."""
    monkeypatch.setattr(monitor, "step_flops", lambda: 1e12)
    monkeypatch.setattr(monitor, "peak_flops",
                        lambda device_kind=None: 100e12)
    clk = FakeClock()
    reg = MetricsRegistry()
    meter = monitor.MfuMeter(reg=reg, clock=clk)
    reg.counter("train.steps").inc(100)
    clk.advance(0.01)
    s = meter.read()
    assert math.isnan(s["mfu"]) and math.isnan(s["model_flops_per_s"])
    assert math.isnan(reg.gauge("train.mfu").value)  # not published
    clk.advance(10.0)                   # a real interval later: real mfu
    # window runs from construction (the tiny probe did not reset it)
    assert meter.sample()["mfu"] == pytest.approx(100 / 10.01 / 100)


def test_span_clock_swap_mid_span_never_reaches_the_ring():
    """disable() mid-span restores perf_counter; the half-open span's
    mixed-clock duration must not land in the flight recorder either."""
    rec = monitor.flight_recorder()
    rec.clear()
    rec.start(capacity=16)
    try:
        observe.enable(clock=FakeClock(1_000_000.0))
        with observe.span("crossing", cat="x"):
            observe.disable()  # clock swapped back mid-span
        assert rec.events() == []
        assert observe.events() == []
    finally:
        rec.stop()


def test_crash_bundle_is_strict_json(tmp_path):
    """nan gauges (train.mfu on CPU) must serialize as null — the
    bundle is readable by jq, not just Python."""
    monitor.MfuMeter(reg=observe.registry())  # plants nan gauges
    path = monitor.dump_report(path=str(tmp_path / "b.json"),
                               reason="strictness")

    def raiser(c):
        raise ValueError(f"non-strict JSON constant {c}")

    d = json.loads(open(path).read(), parse_constant=raiser)
    assert d["registry"]["gauges"]["train.mfu"] is None


def test_idle_beat_disarms_hang_detection():
    """Idle is not hung: a drained source (busy=False) never fires,
    however long it stays silent; the next busy beat re-arms."""
    clk = FakeClock()
    wd = monitor.Watchdog(timeout_s=1.0, clock=clk,
                          reg=MetricsRegistry(), dump_on_hang=False)
    wd.beat("serve.e0", step_time=0.01)
    wd.beat("serve.e0", busy=False)      # drained
    clk.advance(1_000.0)
    assert wd.check() == []              # idle != hung
    assert wd.summary()["sources"]["serve.e0"]["armed"] is False
    wd.beat("serve.e0", step_time=0.01)  # traffic again: re-armed
    clk.advance(2.0)
    assert wd.check() == ["serve.e0"]


def test_forget_source_releases_state_and_metrics():
    clk = FakeClock()
    reg = MetricsRegistry()
    wd = monitor.Watchdog(timeout_s=1.0, clock=clk, reg=reg,
                          dump_on_hang=False)
    wd.beat("serve.e7", step_time=0.01)
    assert len(reg.metrics()) == 2  # step_time hist + anomalies
    wd.forget("serve.e7")
    assert reg.metrics() == []
    assert "serve.e7" not in wd.summary()["sources"]
    clk.advance(100.0)
    assert wd.check() == []  # forgotten sources cannot fire


def test_engine_heartbeats_per_engine_disarm_on_drain_and_forget():
    """End to end: each engine beats its own serve.e<n> source (a
    wedged engine is never masked by a healthy sibling), disarms when
    drained, and close() drops the source + its metrics."""
    import numpy as np

    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.serve import GenerationRequest

    clk_wd = FakeClock()
    wd = monitor.start(watchdog_timeout_s=30.0, clock=clk_wd,
                       thread=False, dump_on_hang=False)
    try:
        cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=16,
                         n_layer=1, n_head=2, n_inner=32, dropout=0.0,
                         attn_impl="fused")
        m = GPT2LMHead(cfg)
        m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
                  is_train=False, use_graph=False)
        eng = m.serve(max_slots=2)
        src = eng._hb_source
        assert src == "serve.e" + eng.stats.engine_label
        eng.submit(GenerationRequest(np.asarray([1, 2, 3]),
                                     max_new_tokens=2))
        eng.run_until_complete(max_steps=20)
        s = wd.summary()["sources"][src]
        assert s["beats"] >= 1 and s["armed"] is False  # drained
        clk_wd.advance(1_000.0)
        assert wd.check() == []  # idle engine never a false hang
        # new traffic re-arms BEFORE the dispatch (a wedged first
        # prefill/decode after idle must still be detectable)
        eng.submit(GenerationRequest(np.asarray([2, 3]),
                                     max_new_tokens=3))
        eng.step()
        assert wd.summary()["sources"][src]["armed"] is True
        eng.run_until_complete(max_steps=20)
        assert wd.summary()["sources"][src]["armed"] is False
        eng.close()
        assert src not in wd.summary()["sources"]
    finally:
        monitor.stop()


def test_sigint_handler_and_excepthook_write_one_bundle(tmp_path,
                                                        monkeypatch):
    """Ctrl-C path: the SIGINT handler dumps signal:2, then chains to
    default_int_handler whose KeyboardInterrupt reaches the chained
    excepthook — which must NOT write a second bundle."""
    import signal as _signal

    monkeypatch.setenv("SINGA_TPU_CRASH_DIR", str(tmp_path))
    monkeypatch.setattr(sys, "excepthook", lambda *a: None)
    monitor.flight_recorder().clear()
    monitor.install_crash_handler(signals=(_signal.SIGINT,))
    try:
        handler = _signal.getsignal(_signal.SIGINT)
        with pytest.raises(KeyboardInterrupt):
            handler(int(_signal.SIGINT), None)  # dumps + chains
        try:
            raise KeyboardInterrupt
        except KeyboardInterrupt:
            sys.excepthook(*sys.exc_info())  # must dedupe
    finally:
        monitor.uninstall_crash_handler()
        monitor.flight_recorder().stop()
    bundles = glob.glob(str(tmp_path / "monitor-crash-*.json"))
    assert len(bundles) == 1
    assert json.loads(open(bundles[0]).read())["reason"] == "signal:2"


def test_hangs_counter_is_labeled_per_source():
    clk = FakeClock()
    reg = MetricsRegistry()
    wd = monitor.Watchdog(timeout_s=1.0, clock=clk, reg=reg,
                          dump_on_hang=False)
    wd.beat("train")
    wd.beat("serve")
    clk.advance(2.0)
    assert sorted(wd.check()) == ["serve", "train"]
    assert reg.counter("monitor.hangs", source="train").value == 1
    assert reg.counter("monitor.hangs", source="serve").value == 1
    assert wd.hangs == 2  # cross-source total


def test_peak_flops_table_lookup():
    assert monitor.peak_flops("TPU v5 lite") == 197e12
    # never a guess: kinds nobody has run on are errors, and a bare
    # "v5"/"v6" substring no longer assigns a peak to them
    for kind in ("cpu", "A100", "TPU v5p", "TPU v6 lite"):
        with pytest.raises(KeyError, match="no bf16 peak on record"):
            monitor.peak_flops(kind)
    # the always-on monitor alone reads an unknown kind as nan
    assert math.isnan(monitor._peak_or_nan())  # CPU test backend


# ---------------------------------------------------------------------------
# serve SLO monitor
# ---------------------------------------------------------------------------

def _result(ttft, tpot, rid="r-0"):
    class R:
        pass

    r = R()
    r.ttft, r.tpot, r.request_id = ttft, tpot, rid
    return r


def test_slo_violation_counters_on_slow_retire():
    from singa_tpu.serve.stats import EngineStats

    reg = MetricsRegistry()
    slo = SLO(ttft_p99_s=0.1, tpot_p50_s=0.05, queue_depth_max=4)
    st = EngineStats(max_slots=2, clock=FakeClock(), reg=reg, slo=slo)
    lbl = dict(engine=st.engine_label)
    st.on_complete(_result(ttft=0.02, tpot=0.01))   # within targets
    assert reg.counter("serve.slo_violations", kind="ttft",
                       **lbl).value == 0
    st.on_complete(_result(ttft=0.5, tpot=0.2))     # synthetic slow one
    assert reg.counter("serve.slo_violations", kind="ttft",
                       **lbl).value == 1
    assert reg.counter("serve.slo_violations", kind="tpot",
                       **lbl).value == 1
    st.on_complete(_result(ttft=0.5, tpot=None))    # 1-token: no tpot
    assert reg.counter("serve.slo_violations", kind="ttft",
                       **lbl).value == 2
    assert reg.counter("serve.slo_violations", kind="tpot",
                       **lbl).value == 1
    # queue pressure fires past queue_depth_max
    st.on_schedule(queue_depth=3)
    st.on_schedule(queue_depth=9)
    assert reg.counter("serve.slo_violations", kind="queue",
                       **lbl).value == 1
    snap = st.snapshot()
    assert snap["slo"]["violations"] == {"ttft": 2, "tpot": 1,
                                         "queue": 1}
    assert snap["slo"]["targets"]["ttft_p99_s"] == 0.1
    json.dumps(snap)


def test_slo_counters_unregister_with_the_engine():
    from singa_tpu.serve.stats import EngineStats

    reg = MetricsRegistry()
    st = EngineStats(2, FakeClock(), reg=reg,
                     slo=SLO(ttft_p99_s=1.0))
    assert len(reg.metrics()) == 20  # 17 base + 3 slo kinds
    st.unregister()
    assert len(reg.metrics()) == 0


def test_snapshot_gains_uptime_and_goodput():
    from singa_tpu.serve.stats import EngineStats

    clk = FakeClock()
    st = EngineStats(2, clk, reg=MetricsRegistry())
    for _ in range(30):
        st.on_token()
    clk.advance(3.0)
    snap = st.snapshot()
    assert snap["throughput"]["uptime_s"] == pytest.approx(3.0)
    assert snap["throughput"]["goodput_tokens_per_s"] == pytest.approx(
        10.0)
    assert snap["slo"] is None  # no targets configured


# ---------------------------------------------------------------------------
# health report + module lifecycle
# ---------------------------------------------------------------------------

def test_health_report_schema_and_sections():
    clk = FakeClock()
    monitor.start(watchdog_timeout_s=60.0, clock=clk, thread=False)
    try:
        monitor.heartbeat("train", step_time=0.1)
        report = health_report()
        assert set(report) == {
            "schema", "host", "train", "step_time", "serve",
            "windowed", "resilience", "watchdog", "flight_recorder",
            "registry"}
        # always-present feature sections: {"enabled": False} until
        # their layers install (windowed rings, burn-rate policy,
        # autoscaler)
        assert report["windowed"] == {"enabled": False}
        assert report["serve"]["slo_alerts"] == {"enabled": False}
        assert report["serve"]["autoscale"] == {"enabled": False}
        # the resilience section is always present, zeroed when the
        # layer never armed
        assert report["resilience"]["engine_restarts"] >= 0
        assert isinstance(report["resilience"]["retries"], dict)
        assert report["watchdog"]["active"] is True
        assert report["watchdog"]["hangs"] == 0
        assert "train" in report["watchdog"]["sources"]
        assert report["flight_recorder"]["active"] is True
        assert math.isnan(report["train"]["mfu"])  # CPU: honest nan
        assert report["serve"]["slo_violations"] == {
            "ttft": 0, "tpot": 0, "queue": 0}
        # per-process step-time summary names this process
        sec = report["step_time"]["train"]
        assert sec["straggler"]["process"] in sec["per_process"]
        json.dumps(report, default=str)
        # benches embed next to their own top-level registry key and
        # opt out of the duplicate snapshot
        slim = health_report(include_registry=False)
        assert set(report) - set(slim) == {"registry"}
    finally:
        monitor.stop()
    assert not monitor.active()
    monitor.heartbeat("train", step_time=0.1)  # no-op after stop


def test_health_report_aggregates_engine_goodput():
    from singa_tpu.serve.stats import EngineStats

    clk = FakeClock()
    reg = MetricsRegistry()
    a = EngineStats(2, clk, reg=reg)
    b = EngineStats(2, clk, reg=reg)
    for _ in range(8):
        a.on_token()
    for _ in range(4):
        b.on_token()
    clk.advance(2.0)
    report = health_report(
        engine_snapshots=[a.snapshot(), b.snapshot()])
    # summed across concurrent engines (4 + 2), same scope as the
    # cross-engine slo_violations totals beside it
    assert report["serve"]["goodput_tokens_per_s"] == pytest.approx(6.0)
    assert len(report["serve"]["engines"]) == 2


def test_graph_runner_feeds_watchdog_and_health_report():
    """End to end over the real instrumentation site: graph-mode
    training beats the watchdog (replays feed step times, the compile
    dispatch is liveness-only) and the health report carries the XLA
    step flops with an honest nan MFU on CPU."""
    import numpy as np

    from singa_tpu import device, opt, tensor
    from singa_tpu.models.mlp import MLP

    wd = monitor.start(watchdog_timeout_s=600.0, clock=FakeClock(),
                       thread=False, dump_on_hang=False)
    try:
        dev = device.create_tpu_device(0)
        dev.SetRandSeed(0)
        m = MLP(data_size=8, perceptron_size=4, num_classes=3)
        m.set_optimizer(opt.SGD(lr=0.05))
        rng = np.random.RandomState(0)
        x = tensor.from_numpy(rng.randn(4, 8).astype(np.float32), dev)
        y = tensor.from_numpy(
            rng.randint(0, 3, (4,)).astype(np.int32), dev)
        m.compile([x], is_train=True, use_graph=True)
        before = observe.registry().histogram(
            "train.step_time", process=wd._process).count
        m(x, y)  # compile: heartbeat, but no step-time sample
        m(x, y)  # replay
        m(x, y)  # replay
        assert wd.summary()["sources"]["train"]["beats"] >= 3
        after = observe.registry().histogram(
            "train.step_time", process=wd._process).count
        assert after - before == 2
        report = health_report()
        assert report["train"]["step_flops"] > 0  # XLA cost table
        assert math.isnan(report["train"]["mfu"])  # CPU backend
        assert report["watchdog"]["hangs"] == 0
    finally:
        monitor.stop()


def test_module_heartbeat_routes_to_started_watchdog():
    clk = FakeClock()
    wd = monitor.start(watchdog_timeout_s=5.0, clock=clk, thread=False,
                       dump_on_hang=False)
    try:
        assert monitor.start() is wd  # idempotent while running
        monitor.heartbeat("serve", step_time=0.02)
        clk.advance(6.0)
        assert wd.check() == ["serve"] or wd.hangs >= 1
    finally:
        monitor.stop()
