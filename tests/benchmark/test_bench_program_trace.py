"""The readings taken from the program's own phases (``singa/`` spans):
on hand-made tuples, and on a small trace recorded on the chip."""

import os
import types

import pytest

from bench_util import ROOT
from benchmark.harness import loader
from benchmark.harness import program_trace as pt
from benchmark.harness import trace_reduce as tr

RECORDED = os.path.join(ROOT, "benchmark", "harness", "testdata",
                        "program.xplane.pb")


def step(t, n, tokens, sync=0.060):
    """One 100 ms engine step at ``t``: grow 1 ms, decode 70 ms of which
    the last ``sync`` is serve.sync, emit 5 ms, schedule 20 ms holding one
    4 ms chunk-row launch; 4 ms of it under no child."""
    return [
        ("serve.step", t, 0.100, {"step": n, "prefill_tokens": tokens}),
        ("serve.grow", t + 0.001, 0.001, {}),
        ("serve.decode", t + 0.002, 0.070, {"live": 3}),
        ("serve.dispatch.paged_decode_step", t + 0.008, 0.002, {}),
        ("serve.sync", t + 0.072 - sync, sync, {}),
        ("serve.emit", t + 0.073, 0.005, {"tokens": 3}),
        ("serve.schedule", t + 0.078, 0.020, {"chunks": 1}),
        ("serve.dispatch.chunk_row", t + 0.080, 0.004, {}),
    ]


SPANS = step(1.0, 7, 1000) + step(1.1, 8, 1128) + step(1.2, 9, 1224)


def test_the_innermost_span_wins_a_gap():
    # the device idles from 1.004 to 1.010, inside serve.step and
    # serve.decode alike: serve.decode, the shorter, gets it (the launch
    # inside it covers a third of the gap) -- and a gap that serve.sync
    # covers goes to serve.sync, not to the serve.decode around it
    got = pt.attribute([(1.004, 1.010), (1.020, 1.030)], SPANS)
    assert got == pytest.approx({"serve.decode": 0.006,
                                 "serve.sync": 0.010})
    ops = [("fusion", 1.010, 0.010), ("fusion", 1.030, 0.270)]
    by = pt.idle_by_phase(ops, SPANS, 1.004, 1.300)
    assert by["decode_launch"] == pytest.approx(0.006)
    assert by["other"] == pytest.approx(0.010)      # under serve.sync
    assert sum(by.values()) == pytest.approx(0.016)


def test_decode_launch_ends_where_its_sync_starts():
    """The device has finished and the host, inside serve.sync, has not
    seen it yet: that idle time is not decode launch's, although
    serve.decode runs a little past its sync -- it is ``other``'s, unless
    the phase after it covers more of the gap."""
    spans = pt.cut_at(SPANS, "serve.decode", "serve.sync")
    assert [s[2] for s in spans if s[0] == "serve.decode"] == \
        pytest.approx([0.010] * 3)
    assert len(spans) == len(SPANS)
    # idle 1.0700..1.0745: 2 ms of sync's tail, 1 ms under no child,
    # 1.5 ms of emit
    ops = [("fusion", 1.0, 0.070), ("fusion", 1.0745, 0.2255)]
    by = pt.idle_by_phase(ops, SPANS, 1.0, 1.3)
    assert by["other"] == pytest.approx(0.0045)
    assert by["decode_launch"] == by["emit"] == 0.0


def test_a_gap_goes_whole_to_the_span_that_covers_most_of_it():
    # 1.070..1.076: 2 ms under serve.sync and serve.decode, then 1 ms
    # under no child, then 3 ms under serve.emit
    ops = [("fusion", 1.0, 0.070), ("fusion", 1.076, 0.224)]
    by = pt.idle_by_phase(ops, SPANS, 1.0, 1.3)
    assert by["emit"] == pytest.approx(0.006)
    assert by["decode_launch"] == by["other"] == 0.0


def test_other_where_no_program_span_covers_a_gap():
    # idle before the first step and after the last: the load generator
    ops = [("fusion", 1.0, 0.3)]
    by = pt.idle_by_phase(ops, SPANS, 0.9, 1.35)
    assert by[pt.OTHER] == pytest.approx(0.15)
    assert sum(by.values()) == pytest.approx(0.15)
    # and a program that wrote no span at all
    assert pt.idle_by_phase(ops, [], 0.9, 1.35)[pt.OTHER] == \
        pytest.approx(0.15)


def test_step_less_its_sync_and_the_lead_to_the_sync():
    spans = step(1.0, 7, 0) + step(1.1, 8, 0, sync=0.050) \
        + step(1.2, 9, 0, sync=0.040)
    assert sorted(pt.durations_less(spans, "serve.step", "serve.sync")) \
        == pytest.approx([0.040, 0.050, 0.060])
    assert pt.durations_less(spans, "serve.emit") == \
        pytest.approx([0.005] * 3)
    # start of serve.decode -> start of the serve.sync inside it
    assert sorted(pt.leads(spans, "serve.decode", "serve.sync")) == \
        pytest.approx([0.010, 0.020, 0.030])
    assert pt.leads(spans, "serve.emit", "serve.sync") == []


def test_children_cover_the_step_but_for_what_lies_between_them():
    assert pt.covered_share(SPANS, "serve.step") == pytest.approx(0.96)
    assert pt.covered_share(SPANS, "train.step") is None


def test_the_growth_of_a_cumulative_arg_over_the_steps():
    assert pt.arg_delta(SPANS, "serve.step", "prefill_tokens") == (224, 2)
    assert pt.arg_delta(SPANS[:8], "serve.step", "prefill_tokens") is None
    assert pt.arg_delta(SPANS, "serve.step", "no_such_arg") is None


def test_only_whole_spans_inside_the_window_count():
    got = pt.within(SPANS, 1.05, 1.25)
    assert {s[3]["step"] for s in got if s[0] == "serve.step"} == {8}
    assert {s[0] for s in got} == {s[0] for s in SPANS}


# ------------------------------------------------------------ the reader

def _ctx(spans, ops, window, budget=128):
    devices = {"/device:TPU:0": {"modules": [], "ops": ops}} if ops else {}
    return dict(
        run=dict(tracer=None), trace=tr.Trace(devices=devices),
        trace_window=window, program_spans=pt.within(spans, *window),
        cell=dict(config=dict(engine=dict(prefill_token_budget=budget))))


def _read(ctx, metric):
    """Through the metric's own file, as ``run.measure`` does."""
    f = loader._read_json(os.path.join(
        loader.BENCH, "metrics", metric + ".json"), metric)
    return loader.load_module("readers", f["reader"]).read(
        ctx, **f["params"])


OPS = [("fusion", 1.004, 0.068), ("fusion", 1.079, 0.199)]


@pytest.mark.parametrize("metric, want", [
    ("step_host_work_ms_p50", 40.0),
    ("decode_launch_host_ms_p50", 10.0),
    ("emit_ms_p50", 5.0),
    ("schedule_pass_ms_p50", 20.0),
    # idle 1.0..1.004 (decode has 2 ms of it, grow 1), 1.072..1.079
    # (emit, 5 of the 7 ms), 1.278..1.3 (schedule 20 ms, then 2 under
    # no child)
    ("idle_ms_per_step.decode_launch", 4.0 / 3),
    ("idle_ms_per_step.emit", 7.0 / 3),
    ("idle_ms_per_step.schedule", 22.0 / 3),
    ("idle_ms_per_step.other", 0.0),
    ("prefill_budget_use", 100.0 * 224 / (2 * 128)),
])
def test_each_serve_metric_reads_its_number(metric, want):
    assert _read(_ctx(SPANS, OPS, (1.0, 1.3)), metric) == \
        pytest.approx(want)


def test_the_train_metric_reads_train_step():
    spans = [("train.step", 1.0 + 0.14 * i, 0.004 + 0.001 * i, {})
             for i in range(5)]
    ctx = _ctx(spans, OPS, (0.9, 2.0))
    assert _read(ctx, "train_step_host_ms_p50") == pytest.approx(6.0)
    assert _read(ctx, "emit_ms_p50") is None


def test_none_without_a_device_plane_a_trace_or_a_span():
    no_plane = _ctx(SPANS, None, (1.0, 1.3))
    no_trace = dict(no_plane, trace=None)
    no_span = _ctx([], OPS, (1.0, 1.3))          # the parent's program
    for ctx in (no_plane, no_trace, no_span):
        for metric in ("step_host_work_ms_p50", "prefill_budget_use",
                       "idle_ms_per_step.other", "train_step_host_ms_p50"):
            assert _read(ctx, metric) is None


def test_the_reader_finds_the_tracers_newest_trace(tmp_path):
    older = tmp_path / "plugins" / "profile" / "2026_01_01"
    newer = tmp_path / "plugins" / "profile" / "2026_01_02"
    for d in (older, newer):
        d.mkdir(parents=True)
    assert pt.newest_xplane(str(tmp_path)) is None
    (older / "vm.xplane.pb").write_bytes(b"")
    os.utime(older / "vm.xplane.pb", (1, 1))
    with open(RECORDED, "rb") as f:
        (newer / "vm.xplane.pb").write_bytes(f.read())
    assert pt.newest_xplane(str(tmp_path)) == str(newer / "vm.xplane.pb")
    trace = tr.load(RECORDED)
    ctx = dict(run=dict(tracer=types.SimpleNamespace(out_dir=str(tmp_path))),
               trace=trace, trace_window=tr.window_of(trace))
    assert _read(ctx, "emit_ms_p50") > 0
    assert ctx["program_spans"]          # loaded once, kept for the run


def test_every_new_metric_is_in_the_manifest_as_a_program_span():
    man = {m["name"]: m for m in loader.manifest()["per_layer"]}
    for name, m in man.items():
        f = loader._read_json(os.path.join(
            loader.BENCH, "metrics", name + ".json"), name)
        if f["reader"] == "program_spans":
            idle = name.startswith("idle_ms_per_step.")
            assert m["source"] == ("device_trace" if idle
                                   else "program_span"), name


# ------------------------------------------------ the recorded chip trace

@pytest.fixture(scope="module")
def recorded():
    """A tiny paged engine with a prefill budget of 16 tokens, eight steps
    on one v5e chip under ``jax.profiler`` (PR 24; cut down to the planes,
    lines and events the readers read)."""
    trace = tr.load(RECORDED)
    t0, t1 = tr.window_of(trace)
    return trace, pt.within(pt.load_spans(RECORDED), t0, t1), (t0, t1)


def test_recorded_steps_carry_their_args_and_hold_their_children(recorded):
    _, spans, _ = recorded
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == 8
    for s in steps:
        assert {"engine", "step", "live", "width", "queue_depth",
                "blocks_used", "prefill_tokens"} <= set(s[3])
        for child in ("serve.grow", "serve.decode", "serve.emit",
                      "serve.schedule"):
            assert len(pt.inside(spans, s, child)) == 1, (child, s)
        decode, = pt.inside(spans, s, "serve.decode")
        assert len(pt.inside(spans, decode, "serve.sync")) == 1
        assert pt.inside(spans, decode, "serve.dispatch.paged_decode_step")
    assert [s[3]["step"] for s in steps] == \
        list(range(steps[0][3]["step"], steps[0][3]["step"] + 8))
    tokens = [s[3]["prefill_tokens"] for s in steps]
    assert tokens == sorted(tokens) and tokens[-1] > tokens[0]
    assert pt.covered_share(spans, "serve.step") > 0.9


def test_recorded_idle_time_is_all_put_down_to_some_phase(recorded):
    trace, spans, (t0, t1) = recorded
    ops = trace.devices["/device:TPU:0"]["ops"]
    by = pt.idle_by_phase(ops, spans, t0, t1)
    idle = (t1 - t0) - tr.busy_seconds(trace, t0, t1)
    assert sum(by.values()) == pytest.approx(idle, rel=1e-6)
    assert by["schedule"] > 0 and by["decode_launch"] > 0


def test_recorded_budget_use_is_a_share(recorded):
    trace, spans, window = recorded
    ctx = _ctx(spans, trace.devices["/device:TPU:0"]["ops"], window,
               budget=16)
    use = _read(ctx, "prefill_budget_use")
    assert 0 < use <= 100.0
