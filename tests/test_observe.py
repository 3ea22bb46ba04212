"""singa_tpu.observe: span tracing (deterministic clock), metrics
registry, exporters (Chrome trace / JSONL / Prometheus), EngineStats
registry adoption, and the disabled-mode overhead contract."""

import json
import threading

import pytest

from singa_tpu import observe
from singa_tpu.observe import export
from singa_tpu.observe.registry import MetricsRegistry


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Each test starts with tracing off and an empty buffer; the
    process registry is shared (get-or-create), so tests below use
    private MetricsRegistry instances for exact-value asserts."""
    observe.disable()
    observe.clear()
    yield
    observe.disable()
    observe.clear()


# ---------------------------------------------------------------------------
# trace core
# ---------------------------------------------------------------------------

def test_span_nesting_with_deterministic_clock():
    clk = FakeClock()
    observe.enable(clock=clk)
    with observe.span("outer", cat="train", step=7) as sp:
        clk.advance(1.0)
        with observe.span("inner", cat="train"):
            clk.advance(0.5)
        sp.set(loss=0.25)
        clk.advance(2.0)
    evs = observe.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    assert inner["parent"] == "outer" and inner["depth"] == 1
    assert outer["parent"] is None and outer["depth"] == 0
    assert inner["ts"] == 1.0 and inner["dur"] == 0.5
    assert outer["ts"] == 0.0 and outer["dur"] == 3.5
    assert outer["args"] == {"step": 7, "loss": 0.25}


def test_event_instant_and_stack_attribution():
    clk = FakeClock(5.0)
    observe.enable(clock=clk)
    with observe.span("scope", cat="serve"):
        observe.event("tick", cat="serve", slot=3)
    ev = [e for e in observe.events() if e["ph"] == "i"][0]
    assert ev["name"] == "tick" and ev["parent"] == "scope"
    assert ev["ts"] == 5.0 and ev["args"] == {"slot": 3}


def test_traced_decorator_names_and_args():
    observe.enable(clock=FakeClock())

    @observe.traced
    def plain():
        return 41

    @observe.traced(name="custom/name", cat="serve")
    def named():
        return 1

    assert plain() + named() == 42
    names = {(e["name"], e["cat"]) for e in observe.events()}
    assert ("custom/name", "serve") in names
    assert any(n.endswith("plain") for n, _ in names)


def test_disabled_mode_is_noop_singleton():
    """The overhead contract: disabled span() returns ONE shared
    object (no allocation) and records nothing."""
    assert not observe.is_enabled()
    s1 = observe.span("a", cat="x", big_arg=list(range(100)))
    s2 = observe.span("b")
    assert s1 is s2  # the shared null span
    with s1 as s:
        s.set(anything=1)
    observe.event("nope")

    @observe.traced
    def f():
        return 3

    for _ in range(10_000):
        with observe.span("hot"):
            pass
        f()
    assert observe.events() == []


def test_disable_mid_span_records_nothing():
    clk = FakeClock(1000.0)
    observe.enable(clock=clk)
    with observe.span("crossing"):
        observe.disable()  # swaps the clock back to perf_counter
    # the half-open span must NOT be emitted with a garbage duration
    assert observe.events() == []


def test_buffer_cap_drops_not_grows():
    observe.enable(clock=FakeClock())
    observe.set_max_events(10)
    try:
        for i in range(25):
            observe.event(f"e{i}")
        assert len(observe.events()) == 10
        assert observe.trace.dropped() == 15
    finally:
        observe.set_max_events(1_000_000)


def test_threaded_spans_keep_separate_stacks():
    observe.enable(clock=FakeClock())
    done = threading.Event()

    def worker():
        with observe.span("w", cat="bg"):
            pass
        done.set()

    with observe.span("main", cat="fg"):
        t = threading.Thread(target=worker, name="bg-thread")
        t.start()
        t.join()
    assert done.is_set()
    w = [e for e in observe.events() if e["name"] == "w"][0]
    # the worker's span must not see the main thread's open span
    assert w["parent"] is None and w["depth"] == 0
    assert w["tid"] == "bg-thread"


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_kinds():
    reg = MetricsRegistry()
    c = reg.counter("x.count", op="sum")
    assert reg.counter("x.count", op="sum") is c
    c.inc().inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("x.level")
    g.set(3.5)
    g.dec(0.5)
    assert g.value == 3.0
    h = reg.histogram("x.lat")
    h.observe(0.1)
    h.observe(0.3)
    assert h.count == 2 and h.summary()["p50"] == 0.1
    with pytest.raises(TypeError):
        reg.gauge("x.count", op="sum")  # kind morph forbidden
    with pytest.raises(TypeError):
        # even under DIFFERENT labels: a Prometheus family shares one
        # TYPE declaration, so kind is enforced per name
        reg.gauge("x.count", op="other")


def test_registry_snapshot_schema():
    reg = MetricsRegistry()
    reg.counter("a").inc(2)
    reg.gauge("b", k="v").set(1)
    reg.histogram("c").observe(2.0)
    snap = reg.snapshot()
    assert snap["counters"] == {"a": 2}
    assert snap["gauges"] == {"b{k=v}": 1}
    assert snap["histograms"]["c"]["count"] == 1
    json.dumps(snap)  # JSON-able end to end


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _sample_events():
    clk = FakeClock()
    observe.enable(clock=clk)
    with observe.span("train/step", cat="train", step=1):
        clk.advance(0.25)
    with observe.span("serve/decode_step", cat="serve", live=4):
        clk.advance(0.001)
    observe.event("graph/cache_miss", cat="train", key="k0")
    observe.disable()
    return observe.events()


def test_chrome_trace_schema_roundtrip(tmp_path):
    evs = _sample_events()
    path = tmp_path / "trace.json"
    n = export.write_chrome_trace(str(path), evs)
    doc = json.loads(path.read_text())
    tes = doc["traceEvents"]
    assert isinstance(tes, list) and len(tes) == n
    # one thread_name metadata row per subsystem (cat)
    meta = {e["args"]["name"]: e["tid"] for e in tes if e["ph"] == "M"}
    assert set(meta) == {"train", "serve"}
    xs = [e for e in tes if e["ph"] == "X"]
    for e in xs:
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["tid"] == meta[e["cat"]]  # track per subsystem
    step = next(e for e in xs if e["name"] == "train/step")
    assert step["ts"] == 0.0 and step["dur"] == 0.25 * 1e6  # µs
    assert step["args"]["step"] == 1
    inst = next(e for e in tes if e["ph"] == "i")
    assert inst["name"] == "graph/cache_miss" and inst["s"] == "t"


def test_jsonl_roundtrip(tmp_path):
    evs = _sample_events()
    path = tmp_path / "events.jsonl"
    n = export.write_jsonl(str(path), evs)
    lines = path.read_text().splitlines()
    assert len(lines) == n == len(evs)
    back = [json.loads(ln) for ln in lines]
    assert back == json.loads(json.dumps(evs))


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("graph.cache_miss", help="compiles").inc(3)
    reg.gauge("serve.queue_depth", engine="0").set(2)
    h = reg.histogram("serve.ttft", engine="0")
    h.observe(0.2)
    h.observe(0.4)
    text = export.prometheus_text(reg)
    lines = text.splitlines()
    # counter TYPE/HELP declared under the _total SAMPLE name
    # (prometheus_client classic-format convention)
    assert "# HELP singa_tpu_graph_cache_miss_total compiles" in lines
    assert "# TYPE singa_tpu_graph_cache_miss_total counter" in lines
    assert "singa_tpu_graph_cache_miss_total 3" in lines
    assert "# TYPE singa_tpu_serve_queue_depth gauge" in lines
    assert 'singa_tpu_serve_queue_depth{engine="0"} 2' in lines
    # histograms export as REAL histogram families (cumulative
    # _bucket series aggregable across a fleet of scraped replicas),
    # with the in-process nearest-rank quantiles as a sibling gauge
    # family — not as quantile samples inside the histogram family,
    # which conformant scrapers reject
    assert "# TYPE singa_tpu_serve_ttft histogram" in lines
    assert 'singa_tpu_serve_ttft_bucket{engine="0",le="0.25"} 1' \
        in lines
    assert 'singa_tpu_serve_ttft_bucket{engine="0",le="0.5"} 2' \
        in lines
    assert 'singa_tpu_serve_ttft_bucket{engine="0",le="+Inf"} 2' \
        in lines
    assert 'singa_tpu_serve_ttft_count{engine="0"} 2' in lines
    assert "# TYPE singa_tpu_serve_ttft_quantile gauge" in lines
    assert ('singa_tpu_serve_ttft_quantile{engine="0",quantile="0.5"}'
            ' 0.2' in lines)
    # exposition charset: no dots/slashes survive in metric names
    for ln in lines:
        if not ln.startswith("#"):
            assert "." not in ln.split("{")[0].split(" ")[0]


def test_prometheus_bucket_override_and_inf_invariant():
    """Per-metric bucket ladders override the default, cumulative
    counts are monotone, and le="+Inf" always equals _count."""
    reg = MetricsRegistry()
    h = reg.histogram("serve.request.queue_wait_s", engine="0",
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.bucket_counts() == [(0.1, 1), (1.0, 2),
                                 (float("inf"), 3)]
    lines = export.prometheus_text(reg).splitlines()
    pfx = "singa_tpu_serve_request_queue_wait_s"
    assert f'{pfx}_bucket{{engine="0",le="0.1"}} 1' in lines
    assert f'{pfx}_bucket{{engine="0",le="1"}} 2' in lines
    assert f'{pfx}_bucket{{engine="0",le="+Inf"}} 3' in lines
    assert f'{pfx}_count{{engine="0"}} 3' in lines
    # a default-ladder histogram ends in the same invariant
    d = reg.histogram("serve.ttft", engine="0")
    d.observe(0.2)
    lines = export.prometheus_text(reg).splitlines()
    assert 'singa_tpu_serve_ttft_bucket{engine="0",le="+Inf"} 1' \
        in lines
    with pytest.raises(ValueError):
        reg.histogram("bad.buckets", buckets=(1.0, 0.5))


def test_prometheus_sum_count_stay_consistent_under_windowing():
    """_sum comes from the series' RUNNING total, not the retained
    values window — evicting values must not desync the pair."""
    from singa_tpu.utils.metrics import LatencySeries

    reg = MetricsRegistry()
    h = reg.histogram("serve.ttft", engine="0",
                      series=LatencySeries(max_samples=2))
    for v in (0.1, 0.2, 0.3):
        h.observe(v)  # the bounded ring evicts 0.1
    assert list(h.series.values) == [0.2, 0.3]
    lines = export.prometheus_text(reg).splitlines()
    assert 'singa_tpu_serve_ttft_sum{engine="0"} 0.6000000000000001' \
        in lines
    assert 'singa_tpu_serve_ttft_count{engine="0"} 3' in lines


def test_dropped_is_public_and_rides_chrome_metadata():
    """Satellite: observe.dropped() is part of the public API and a
    truncated trace is self-describing in its Chrome metadata."""
    observe.enable(clock=FakeClock())
    observe.set_max_events(5)
    try:
        for i in range(8):
            observe.event(f"e{i}")
        assert observe.dropped() == 3  # re-exported at package level
        doc = export.chrome_trace(observe.events())
        assert doc["otherData"]["dropped_events"] == 3
    finally:
        observe.set_max_events(1_000_000)


# ---------------------------------------------------------------------------
# EngineStats adoption
# ---------------------------------------------------------------------------

def test_engine_stats_registers_into_registry():
    from singa_tpu.serve.stats import EngineStats

    clk = FakeClock()
    reg = MetricsRegistry()
    st = EngineStats(max_slots=4, clock=clk, reg=reg)
    st.on_submit()
    st.on_submit()
    st.on_prefill()
    st.on_decode_step(live_slots=3)
    st.on_token()
    st.on_schedule(queue_depth=5)
    st.on_queue_full("r-1")

    lbl = dict(engine=st.engine_label)
    assert reg.counter("serve.submitted", **lbl).value == 2
    assert reg.counter("serve.prefills", **lbl).value == 1
    assert reg.counter("serve.tokens_out", **lbl).value == 1
    assert reg.counter("serve.rejected_queue_full", **lbl).value == 1
    assert reg.gauge("serve.queue_depth", **lbl).value == 5
    assert reg.gauge("serve.occupancy", **lbl).value == 0.75
    # the registry ADOPTED the TTFT series: same object, two views
    assert reg.histogram("serve.ttft", **lbl).series is st.ttft

    class R:
        ttft = 0.5
        tpot = 0.01

    st.on_complete(R())
    assert reg.histogram("serve.ttft", **lbl).count == 1
    # snapshot schema unchanged by the registry rebase
    snap = st.snapshot()
    assert snap["requests"]["submitted"] == 2
    assert snap["queue"]["max_depth"] == 5
    assert snap["slots"]["occupancy_mean"] == 0.75
    json.dumps(snap)


def test_two_engines_do_not_collide():
    from singa_tpu.serve.stats import EngineStats

    reg = MetricsRegistry()
    a = EngineStats(2, FakeClock(), reg=reg)
    b = EngineStats(2, FakeClock(), reg=reg)
    a.on_submit()
    a.on_submit()
    b.on_submit()
    assert a.submitted == 2 and b.submitted == 1


def test_engine_stats_unregister_releases_metrics():
    from singa_tpu.serve.stats import EngineStats

    reg = MetricsRegistry()
    a = EngineStats(2, FakeClock(), reg=reg)
    b = EngineStats(2, FakeClock(), reg=reg)
    a.on_submit()
    assert len(reg.metrics()) == 34  # 17 per engine (incl. the
    #   queue-wait + cold/warm admission request-phase histograms,
    #   the prefill-token counter and the two kernel-step counters)
    a.unregister()
    remaining = reg.metrics()
    assert len(remaining) == 17
    assert all(("engine", b.engine_label) in m.labels
               for m in remaining)
    # a fully-removed NAME frees its kind reservation
    c = reg.counter("ephemeral")
    reg.remove(c)
    reg.gauge("ephemeral")  # no TypeError: the name was freed
    # the retired stats object still reads its own counters
    assert a.submitted == 1 and a.snapshot()["requests"]["submitted"] == 1


def test_engine_close_unregisters_and_requires_drain():
    import numpy as np

    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.observe.registry import registry
    from singa_tpu.serve import GenerationRequest

    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=16,
                     n_layer=1, n_head=2, n_inner=32, dropout=0.0,
                     attn_impl="fused")
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    with m.serve(max_slots=2) as eng:
        lbl = dict(engine=eng.stats.engine_label)
        eng.submit(GenerationRequest(np.asarray([1, 2, 3]),
                                     max_new_tokens=2))
        with pytest.raises(RuntimeError):
            eng.close()  # work in flight
        eng.run_until_complete(max_steps=20)
    # context exit closed it: serve.* metrics for THIS engine are gone
    assert not any(dict(mm.labels).get("engine") == lbl["engine"]
                   for mm in registry().metrics())
    eng.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(GenerationRequest(np.asarray([1]), max_new_tokens=1))
    with pytest.raises(RuntimeError, match="closed"):
        eng.step()


def test_drain_swaps_buffer():
    observe.enable(clock=FakeClock())
    observe.event("a")
    observe.event("b")
    out = observe.drain()
    assert [e["name"] for e in out] == ["a", "b"]
    assert observe.events() == []
    observe.event("c")
    assert [e["name"] for e in observe.events()] == ["c"]


def test_chrome_trace_survives_numpy_args(tmp_path):
    import numpy as np

    observe.enable(clock=FakeClock())
    with observe.span("s", cat="x", loss=np.float32(0.5)):
        pass
    observe.disable()
    path = tmp_path / "np_trace.json"
    export.write_chrome_trace(str(path), observe.events())
    doc = json.loads(path.read_text())
    ev = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert ev["args"]["loss"] == "0.5"  # stringified, not crashed


# ---------------------------------------------------------------------------
# instrumented sites
# ---------------------------------------------------------------------------

def test_communicator_records_collective_metrics():
    import numpy as np

    from singa_tpu.observe.registry import registry
    from singa_tpu.parallel.communicator import _record_collective

    reg = registry()
    before = reg.counter("comms.collectives", op="all_reduce").value
    before_b = reg.counter("comms.bytes", op="all_reduce").value
    observe.enable(clock=FakeClock())
    _record_collective("all_reduce", [np.zeros((4, 8), np.float32)])
    assert reg.counter("comms.collectives",
                       op="all_reduce").value == before + 1
    assert reg.counter("comms.bytes",
                       op="all_reduce").value == before_b + 4 * 8 * 4
    ev = [e for e in observe.events() if e["cat"] == "comms"][-1]
    assert ev["name"] == "comms/all_reduce"
    assert ev["args"]["bytes"] == 128


def test_graph_runner_counts_compiles_and_replays():
    import numpy as np

    from singa_tpu import device, opt, tensor
    from singa_tpu.models.mlp import MLP
    from singa_tpu.observe.registry import registry

    dev = device.create_tpu_device(0)
    dev.SetRandSeed(0)
    m = MLP(data_size=8, perceptron_size=4, num_classes=3)
    m.set_optimizer(opt.SGD(lr=0.05))
    rng = np.random.RandomState(0)
    x = tensor.from_numpy(rng.randn(4, 8).astype(np.float32), dev)
    y = tensor.from_numpy(rng.randint(0, 3, (4,)).astype(np.int32), dev)
    m.compile([x], is_train=True, use_graph=True)

    reg = registry()
    h0 = reg.counter("graph.cache_hit").value
    m0 = reg.counter("graph.cache_miss").value
    s0 = reg.counter("train.steps").value
    observe.enable(clock=FakeClock())
    m(x, y)          # compile
    m(x, y)          # replay
    m(x, y)          # replay
    observe.disable()
    assert reg.counter("graph.cache_miss").value == m0 + 1
    assert reg.counter("graph.cache_hit").value == h0 + 2
    assert reg.counter("train.steps").value == s0 + 3
    names = [e["name"] for e in observe.events()]
    assert names.count("graph.compile") == 1
    # one whole-call phase and one dispatch phase per call
    assert names.count("train.step") == 3
    assert names.count("train.dispatch") == 3
    assert "graph/cache_miss" in names
    compile_span = next(e for e in observe.events()
                        if e["name"] == "graph.compile")
    # XLA cost-table estimates ride the span args (flops present on
    # the CPU backend too)
    assert "flops" in compile_span["args"]


# ---------------------------------------------------------------------------
# phase(): the step-level sites, on the profiler's clock
# ---------------------------------------------------------------------------

def _tiny_model():
    import numpy as np

    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    m = GPT2LMHead(GPT2Config.tiny(dropout=0.0))
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.fixture(scope="module")
def tiny_model():
    return _tiny_model()


def test_observe_imports_without_jax():
    """``phase()`` reaches for ``jax.profiler`` on its first call, not
    when ``observe`` is imported (checked in a fresh interpreter, with
    the package's own ``__init__`` -- which does import JAX -- stubbed
    out)."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys, types\n"
        "pkg = types.ModuleType('singa_tpu')\n"
        f"pkg.__path__ = [{os.path.join(root, 'singa_tpu')!r}]\n"
        "sys.modules['singa_tpu'] = pkg\n"
        "import singa_tpu.observe as o\n"
        "assert 'jax' not in sys.modules, 'observe imported jax'\n"
        "assert o.phase is o.trace.phase\n"
        "with o.phase('serve.step', cat='serve', step=1) as ph:\n"
        "    ph.set(live=2)\n"
        "assert 'jax' in sys.modules\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr


def test_phase_off_is_the_bare_annotation():
    """With tracing and the step profiler off, in a process that has
    built no serve engine (a training process: the always-on step log
    is an engine's to install), a phase is the profiler's own
    annotation and nothing else: no host record, no ``_Span``, no
    clock call -- ``span()`` keeps its shared no-op."""
    from jax.profiler import TraceAnnotation

    from singa_tpu.observe import stepprof, trace

    stepprof._reset()          # engines of earlier tests installed it
    ph = observe.phase("serve.step", cat="serve", step=3)
    assert isinstance(ph, TraceAnnotation)
    assert type(ph) is trace._Annotation
    with ph as inside:
        assert inside is ph
        assert ph.set(live=2) is ph
        assert ph.step_elapsed() is None
    assert observe.events() == []
    assert observe.span("x") is trace._NULL_SPAN


def test_phase_feeds_the_host_record_when_tracing_is_on():
    clk = FakeClock()
    observe.enable(clock=clk)
    with observe.phase("serve.step", cat="serve", step=7) as ph:
        clk.advance(1.0)
        with observe.phase("serve.decode", cat="serve"):
            clk.advance(0.5)
        ph.set(live=2)
        clk.advance(0.25)
    inner, outer = observe.events()
    assert (inner["name"], inner["parent"], inner["dur"]) == \
        ("serve.decode", "serve.step", 0.5)
    assert (outer["name"], outer["cat"], outer["dur"]) == \
        ("serve.step", "serve", 1.75)
    assert outer["args"] == {"step": 7, "live": 2}


def test_phase_feeds_the_step_profiler_through_its_hook_alone():
    """``stepprof.enable()`` registers the hook and ``disable()`` takes
    it away, in a process whose engines have not installed the always-on
    half (then it stays: tests/test_stepprof.py); ``trace.py`` never
    imports ``stepprof``.  On a fake clock the segments are exact:
    exclusive, ``other`` for time under no segment, summing to the
    wall."""
    import ast
    import inspect

    from singa_tpu.observe import stepprof, trace

    imported = [a.name for n in ast.walk(ast.parse(inspect.getsource(trace)))
                if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names]
    assert "stepprof" not in " ".join(imported)
    stepprof._reset()          # engines of earlier tests installed it
    assert trace._phase_hook is None
    clk = FakeClock()
    stepprof.enable(clock=clk, reg=MetricsRegistry())
    try:
        assert trace._phase_hook is not None
        with observe.phase("serve.step", cat="serve", engine="9",
                           step=4):
            clk.advance(0.001)                      # other
            with observe.phase("serve.decode", cat="serve"):
                clk.advance(0.002)                  # no segment: other
                with observe.phase("serve.dispatch.paged_decode_step",
                                   cat="serve"):
                    clk.advance(0.003)              # dispatch
                with observe.phase("serve.sync", cat="serve") as sy:
                    clk.advance(0.070)              # sync
                # the step's start to its newest stamp, off the record
                assert sy.step_elapsed() == pytest.approx(0.076)
            with observe.phase("serve.emit", cat="serve"):
                clk.advance(0.004)                  # emit
            with observe.phase("serve.schedule", cat="serve"):
                clk.advance(0.005)                  # schedule
                with observe.phase("serve.admit", cat="serve"):
                    clk.advance(0.006)              # admit
                    with observe.phase("serve.prefix_lookup",
                                       cat="serve"):
                        clk.advance(0.007)          # prefix_lookup
        rec, = stepprof.records()
        assert (rec["engine"], rec["step"]) == ("9", 4)
        assert rec["segments"] == pytest.approx(
            {"other": 0.003, "dispatch": 0.003, "sync": 0.070,
             "emit": 0.004, "schedule": 0.005, "admit": 0.006,
             "prefix_lookup": 0.007})
        assert sum(rec["segments"].values()) == \
            pytest.approx(rec["wall_s"]) == pytest.approx(0.098)
        # a step that raises leaves no record behind
        with pytest.raises(RuntimeError):
            with observe.phase("serve.step", cat="serve", engine="9",
                               step=5):
                raise RuntimeError("boom")
        assert len(stepprof.records()) == 1
    finally:
        stepprof.disable()
    assert trace._phase_hook is None


def test_a_quiet_step_makes_no_span_and_reads_only_the_step_logs_clock(
        tiny_model, monkeypatch):
    """No profiler session, ``observe`` off, step profiler off, monitor
    off: a whole engine step allocates no ``_Span`` and reads no clock
    through the instrumentation but the always-on step log's own, at
    most twice a phase (observe/stepprof.py)."""
    import time

    import numpy as np

    from singa_tpu.observe import stepprof, trace
    from singa_tpu.serve import GenerationRequest

    eng = tiny_model.serve(max_slots=2)
    h = eng.submit(GenerationRequest(np.arange(9) % 256,
                                     max_new_tokens=12, temperature=0.0))
    eng.step()
    eng.step()
    made, calls, stamps, phases = [], [0], [0], [0]
    real_span, real_clock = trace._Span, time.perf_counter
    real_phase = trace.phase

    class Counted(real_span):
        def __init__(self, *a):
            made.append(a[0])
            super().__init__(*a)

    def counting(n):
        def clock():
            n[0] += 1
            return real_clock()
        return clock

    def counted_phase(*a, **kw):
        phases[0] += 1
        return real_phase(*a, **kw)

    try:
        monkeypatch.setattr(trace, "_Span", Counted)
        monkeypatch.setattr(time, "perf_counter", counting(calls))
        monkeypatch.setattr(trace, "_clock", counting(calls))
        monkeypatch.setattr(stepprof, "_clock", counting(stamps))
        monkeypatch.setattr(trace, "phase", counted_phase)
        eng.step()
        assert made == [] and calls[0] == 0
        assert 0 < stamps[0] <= 2 * phases[0]
        monkeypatch.setattr(time, "perf_counter", real_clock)
    finally:
        while eng.pending:
            eng.step()
        h.result()
        eng.close()


def test_a_profiler_session_records_the_step_with_its_children(
        tiny_model, tmp_path):
    """Under ``jax.profiler`` (a CPU session here) every engine step is
    a ``singa/serve.step`` span carrying its args, with grow, decode
    (holding the launch and the sync), emit and schedule inside it."""
    import glob

    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from singa_tpu.serve import GenerationRequest, PagedConfig

    eng = tiny_model.serve(
        max_slots=2, paged=PagedConfig(block_size=8, num_blocks=32,
                                       prefill_token_budget=16))
    try:
        def submit(n):
            return eng.submit(GenerationRequest(
                (np.arange(n) * 7) % 256, max_new_tokens=8,
                temperature=0.0))

        hs = [submit(9)]
        while not eng.live_slots:       # (compiles outside the session)
            eng.step()
        hs.append(submit(21))           # two budgeted chunks to come
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            first = eng.step_count
            for _ in range(4):
                eng.step()
        finally:
            jax.profiler.stop_trace()
        while eng.pending:
            eng.step()
        for h in hs:
            h.result()
        total = eng.stats.prefill_tokens
    finally:
        eng.close()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("singa/"):
                        seen[(e.name, e.start_ns, e.duration_ns)] = \
                            dict(e.stats)
    spans = sorted((k + (a,) for k, a in seen.items()),
                   key=lambda s: s[1])
    steps = [s for s in spans if s[0] == "singa/serve.step"]
    assert [s[3]["step"] for s in steps] == list(range(first, first + 4))
    for name, t0, dur, args in steps:
        assert {"engine", "step", "live", "width", "queue_depth",
                "blocks_used", "prefill_tokens"} <= set(args)
        assert 0 <= args["prefill_tokens"] <= total
        kids = [s for s in spans
                if t0 <= s[1] and s[1] + s[2] <= t0 + dur
                and s[0] != name]
        names = [k[0] for k in kids]
        for child in ("singa/serve.grow", "singa/serve.decode",
                      "singa/serve.emit", "singa/serve.schedule"):
            assert names.count(child) == 1, (child, names)
        decode = next(k for k in kids if k[0] == "singa/serve.decode")
        for inner in ("singa/serve.dispatch.paged_decode_step",
                      "singa/serve.sync"):
            k = next(k for k in kids if k[0] == inner)
            assert decode[1] <= k[1] and \
                k[1] + k[2] <= decode[1] + decode[2]
        assert decode[3]["paged"] == 1 and decode[3]["live"] >= 1
        # which attention the decode dispatch ran: the block loop
        # anywhere but on a TPU (ops/paged_attention.decode_attn_impl)
        assert decode[3]["attn"] == "loop" and args["attn"] == "loop"
    # the budgeted prefill of the 21-token prompt ran inside the session:
    # chunk rows under serve.schedule, counted in its args
    sched = [s for s in spans if s[0] == "singa/serve.schedule"]
    assert sum(s[3]["chunks"] for s in sched) >= 1
    assert any(s[0] == "singa/serve.dispatch.chunk_row" for s in spans)
    # its second launch found a lane decoding: it went out in the early
    # pass (serve.launch), inside serve.decode, behind the decode
    # dispatch and before the wait for its tokens; serve.schedule's
    # counts stay the whole step's
    launch, = [s for s in spans if s[0] == "singa/serve.launch"]
    assert (launch[3]["launches"], launch[3]["chunks"]) == (1, 1)

    def holding(name):
        return [s for s in spans if s[0] == name and s[1] <= launch[1]
                and launch[1] + launch[2] <= s[1] + s[2]]
    step, = holding("singa/serve.step")
    decode, = holding("singa/serve.decode")

    def of_step(name):
        return [s for s in spans if s[0] == name and step[1] <= s[1]
                and s[1] + s[2] <= step[1] + step[2]]
    dispatch, = of_step("singa/serve.dispatch.paged_decode_step")
    row, = of_step("singa/serve.dispatch.chunk_row")
    sync, = of_step("singa/serve.sync")
    assert dispatch[1] + dispatch[2] <= launch[1] <= row[1]
    assert row[1] + row[2] <= launch[1] + launch[2] <= sync[1]
    assert sync[1] + sync[2] <= decode[1] + decode[2]
    total, = of_step("singa/serve.schedule")
    assert (total[3]["launches"], total[3]["chunks"]) == (1, 1)
    assert sum(s[3]["launches"] for s in sched) == 2


@pytest.mark.parametrize("engine", ["paged", "paged, a verify chunk",
                                    "slot arena"])
def test_decode_steps_say_which_attention_they_ran(tiny_model, engine):
    """``serve.decode.attn_kernel_steps`` counts the decode dispatches
    whose attention over the pool ran the Pallas kernel, beside
    ``serve.decode_steps``: none of them here (no TPU), by the rule the
    programs themselves dispatch on; an engine without a pool makes no
    claim."""
    import numpy as np

    from singa_tpu.serve import GenerationRequest, PagedConfig

    kw = {}
    if engine != "slot arena":
        kw["paged"] = PagedConfig(block_size=8, num_blocks=32)
    if engine == "paged, a verify chunk":
        kw.update(draft_model=tiny_model, spec_k=2)
    eng = tiny_model.serve(max_slots=2, **kw)
    try:
        h = eng.submit(GenerationRequest(np.arange(9) % 256,
                                         max_new_tokens=6,
                                         temperature=0.0))
        while eng.pending:
            eng.step()
        h.result()
        assert eng._decode_attn == (None if engine == "slot arena"
                                    else "loop")
        assert eng.stats.decode_steps >= 3
        assert eng.stats.attn_kernel_steps == 0
        name = "serve.decode.attn_kernel_steps"
        assert any(m.name == name for m in eng.stats._registered)
    finally:
        eng.close()


def _tiny_hybrid():
    """A Mamba-2 mixer beside attention in each of two layers
    (models/falcon_h1.py), widths of a few tens."""
    import numpy as np

    from singa_tpu import tensor
    from singa_tpu.models.falcon_h1 import FalconH1Config, FalconH1LMHead

    m = FalconH1LMHead(FalconH1Config(
        vocab_size=256, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        intermediate_size=64, mamba_d_ssm=32, mamba_d_state=16,
        mamba_n_groups=1, mamba_n_heads=2, mamba_d_head=16,
        mamba_chunk_size=8, max_len=64))
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.mark.parametrize("model", ["a recurrent state", "K/V only",
                                   "the counter's hook"])
def test_decode_steps_say_what_advanced_the_state(tiny_model, model):
    """``serve.decode.state_kernel_steps`` counts the decode dispatches
    whose recurrent state the Pallas kernel advanced (every lane in one
    call a layer), beside ``serve.decode_steps``, and the ``serve.decode``
    and ``serve.step`` spans say ``state="kernel"`` or ``"loop"``: the
    loop here (no TPU), by the rule the program itself dispatches on
    (``ops/mamba2.step_impl`` on the engine's own arena); an engine
    whose family steps no such state makes no claim."""
    import numpy as np

    from singa_tpu.serve import GenerationRequest, PagedConfig

    if model == "the counter's hook":
        from singa_tpu.serve.stats import EngineStats

        stats = EngineStats(2, FakeClock(), reg=MetricsRegistry())
        stats.on_decode_step(1)
        stats.on_decode_step(2, state_kernel=True)
        assert (stats.decode_steps, stats.state_kernel_steps,
                stats.attn_kernel_steps) == (2, 1, 0)
        return
    says = "loop" if model == "a recurrent state" else None
    m = _tiny_hybrid() if says else tiny_model
    eng = m.serve(max_slots=2, paged=PagedConfig(
        block_size=8, num_blocks=32, prefill_token_budget=16))
    observe.enable()
    try:
        h = eng.submit(GenerationRequest(np.arange(9) % 256,
                                         max_new_tokens=6,
                                         temperature=0.0))
        while eng.pending:
            eng.step()
        h.result()
        assert eng._decode_state == says
        assert eng.stats.decode_steps >= 3
        assert eng.stats.state_kernel_steps == 0
        name = "serve.decode.state_kernel_steps"
        assert any(m.name == name for m in eng.stats._registered)
        decodes = [e["args"] for e in observe.events()
                   if e["name"] == "serve.decode"]
        steps = [e["args"] for e in observe.events()
                 if e["name"] == "serve.step" and e["args"].get("width")]
        assert len(decodes) == eng.stats.decode_steps == len(steps)
        assert {a.get("state") for a in decodes + steps} == {says}
        assert {a["attn"] for a in decodes + steps} == {"loop"}
    finally:
        eng.close()


@pytest.mark.parametrize("path", ["cold", "budgeted", "warm"])
def test_prefill_token_counter_counts_real_prompt_positions(tiny_model,
                                                            path):
    """``serve.prefill.tokens``: the prompt positions a prefill really
    computed -- a whole admission's on the cold path, chunk by chunk
    (the last one cut at the prompt's end, not padded to the block)
    under a token budget, and less what the prefix cache supplied on
    the warm path."""
    import numpy as np

    from singa_tpu.observe.registry import registry
    from singa_tpu.serve import (GenerationRequest, PagedConfig,
                                 PrefixCacheConfig)

    kw = {"cold": dict(),
          "budgeted": dict(paged=PagedConfig(
              block_size=8, num_blocks=64, prefill_token_budget=16)),
          "warm": dict(prefix_cache=PrefixCacheConfig(block_size=8))}[path]
    eng = tiny_model.serve(max_slots=2, **kw)
    shared = (np.arange(24) * 5 + 1) % 256
    prompts = [np.concatenate([shared, [7, 8, 9]]),
               np.concatenate([shared, [3, 1]]), np.asarray([5, 1, 200])]
    try:
        cached = 0
        for p in prompts:                 # one after the other, so the
            h = eng.submit(GenerationRequest(   # second finds the first
                p, max_new_tokens=3, temperature=0.0))
            while eng.pending:
                eng.step()
            h.result()
        if path == "warm":
            cached = eng.stats.snapshot()["prefix"]["hit_tokens"]
            assert cached == 24           # the second prompt's three blocks
        want = sum(len(p) for p in prompts) - cached
        assert eng.stats.prefill_tokens == want
        assert eng.stats.snapshot()["throughput"]["prefill_tokens"] == want
        assert registry().counter(
            "serve.prefill.tokens",
            engine=eng.stats.engine_label).value == want
    finally:
        eng.close()


def test_train_step_phase_holds_its_dispatch():
    import numpy as np

    from singa_tpu import device, layer, model, opt, tensor

    class Net(model.Model):
        def __init__(self):
            super().__init__()
            self.fc = layer.Linear(3)
            self.loss = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.fc(x)

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = self.loss(out, y)
            self.optimizer(loss)
            return out, loss

    dev = device.get_default_device()
    m = Net()
    m.set_optimizer(opt.SGD(lr=0.05))
    rng = np.random.RandomState(0)
    x = tensor.from_numpy(rng.randn(4, 8).astype(np.float32), dev)
    y = tensor.from_numpy(rng.randint(0, 3, (4,)).astype(np.int32), dev)
    m.compile([x], is_train=True, use_graph=True)
    observe.enable(clock=FakeClock())
    m(x, y)
    m(x, y)
    observe.disable()
    by = {}
    for e in observe.events():
        by.setdefault(e["name"], []).append(e)
    assert len(by["train.step"]) == len(by["train.dispatch"]) == 2
    assert all(e["parent"] == "train.step" for e in by["train.dispatch"])
    assert by["graph.compile"][0]["parent"] == "train.step"
    assert by["train.step"][0]["args"] == {"steps": 1}
