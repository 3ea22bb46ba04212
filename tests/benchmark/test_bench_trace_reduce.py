"""The reduction from a trace to numbers, on the small trace recorded on
the chip that is kept beside it (three runs of a matmul program and three
of an elementwise one, under ``bench/step`` and ``bench/copy`` spans), and
on hand-made events."""

import os

import pytest
from bench_util import ROOT

from benchmark.harness import trace_reduce as tr

RECORDED = os.path.join(ROOT, "benchmark", "harness", "testdata",
                        "probe.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(RECORDED)


def test_recorded_trace_has_the_planes_the_reduction_reads(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    lines = trace.devices["/device:TPU:0"]
    assert len(lines["modules"]) == 6 and len(lines["ops"]) == 36
    names = {n for n, _, _ in trace.host_spans}
    assert names == {"bench/step", "bench/copy"}


# the recorded window is 13 ms long and the device's clock sits about a
# millisecond before the host's in it, so the device-side checks take the
# whole recording; a run's traced window is seconds long
WHOLE = (0.0, 1.0)


def test_busy_is_the_union_and_idle_is_the_rest(trace):
    t0, t1 = WHOLE
    busy = tr.busy_seconds(trace, t0, t1)
    ops = trace.devices["/device:TPU:0"]["ops"]
    assert 0 < busy <= sum(d for _, _, d in tr.clip(ops, t0, t1)) + 1e-12
    assert busy < t1 - t0
    # three matmul programs of ~21 us and three copies of ~0.8 us ran
    assert busy == pytest.approx(66e-6, rel=0.15)


def test_program_runs_are_found_by_name(trace):
    t0, t1 = WHOLE
    assert tr.module_counts(trace, t0, t1) == {"jit_probe_step": 3,
                                               "jit_probe_copy": 3}
    runs = tr.module_runs(trace, "probe_step", t0, t1)
    assert [round(d * 1e6) for _, d in runs] == [22, 22, 21]


def test_operations_sum_under_the_names_printed(trace):
    t0, t1 = WHOLE
    tot = tr.op_totals(trace, t0, t1)
    assert "fusion_bf16_1024_1024" in tot
    assert tot["fusion_bf16_1024_1024"] == pytest.approx(3 * 14.6e-6,
                                                         rel=0.05)
    b = tr.breakdown(trace, t0, t1)
    assert b["device_ops"][0][0] == "fusion_bf16_1024_1024"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    inside = tr.ops_inside(trace, tr.module_runs(trace, "probe_step", t0, t1),
                           lambda n: "fusion" in n)
    assert inside >= tot["fusion_bf16_1024_1024"]


def test_idle_gaps_go_to_the_span_that_covers_them(trace):
    t0, t1 = tr.window_of(trace)
    gaps = dict(tr.idle_gaps(trace, t0, t1))
    assert set(gaps) <= {"step", "copy", "unattributed"}
    assert sum(gaps.values()) == pytest.approx(
        (t1 - t0) - tr.busy_seconds(trace, t0, t1), rel=1e-6)


HLO_POOL = ("%copy.7 = bf16[36,561,20,32,64]{4,3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[36,561,20,32,64]{...} %p)")
HLO_LAYER = "%copy.9 = bf16[1,561,20,32,64]{4,3,2,1,0} copy(%x)"
HLO_SMALL = "%copy.1 = bf16[48,20,32,64]{3,2,1,0} copy(%y)"
HLO_AR = ("%all-reduce-done.3 = f32[768,3072]{1,0} all-reduce-done("
          "%all-reduce-start.3)")


def test_names_are_shortened_to_operation_and_result():
    assert tr.short_name(HLO_POOL) == "copy_bf16_36_561_20_32_64"
    assert tr.short_name(HLO_AR) == "all-reduce-done_f32_768_3072"
    assert tr.op_dims(HLO_LAYER) == ("copy", [1, 561, 20, 32, 64])


def test_whole_pool_copies_are_found_by_shape():
    pool = (36, 561, 20, 32, 64)
    assert tr.is_pool_copy(HLO_POOL, pool)
    assert tr.is_pool_copy(HLO_LAYER, pool)       # one layer of the pool
    assert not tr.is_pool_copy(HLO_SMALL, pool)
    assert not tr.is_pool_copy(HLO_AR, pool)


def _synthetic():
    ops = [("%fusion.1 = f32[8]{0} fusion(%a)", 0.0, 4.0),
           (HLO_AR, 4.0, 1.0),
           ("%fusion.2 = f32[8]{0} fusion(%a)", 6.0, 2.0),
           (HLO_POOL, 8.0, 1.0)]
    t = tr.Trace()
    t.devices["/device:TPU:0"] = {
        "modules": [("jit_step(1)", 0.0, 5.0), ("jit_step(1)", 6.0, 3.0)],
        "ops": ops}
    t.devices["/device:TPU:1"] = {"modules": [], "ops": [
        ("%fusion.1 = f32[8]{0} fusion(%a)", 0.0, 4.0), (HLO_AR, 4.0, 3.0)]}
    t.host_spans = [("bench/window", 0.0, 10.0), ("bench/train.step", 4.9, 1.2),
                    ("bench/train.wait", 9.0, 1.0)]
    return t


def test_collective_time_on_the_cores_is_exposed_time():
    t = _synthetic()
    assert tr.is_collective(HLO_AR) and not tr.is_collective(HLO_POOL)
    # chip 0 waits 1 s, chip 1 waits 3 s: 2 s on average
    assert tr.exposed_collective_seconds(t, 0.0, 10.0) == pytest.approx(2.0)
    runs = tr.module_runs(t, "jit_step", 0.0, 10.0)
    assert tr.ops_inside(t, runs, tr.is_collective) == pytest.approx(1.0)
    assert tr.ops_inside(t, runs, lambda n: tr.is_pool_copy(
        n, (36, 561, 20, 32, 64))) == pytest.approx(1.0)


def test_busy_averages_over_chips_and_gaps_prefer_the_inner_span():
    t = _synthetic()
    assert tr.busy_seconds(t, 0.0, 10.0) == pytest.approx((8.0 + 7.0) / 2)
    gaps = dict(tr.idle_gaps(t, 0.0, 10.0))
    # the gap 5..6 lies inside train.step (and inside window); 9..10 in wait
    assert gaps == {"train.step": pytest.approx(1.0),
                    "train.wait": pytest.approx(1.0)}
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
