"""Window accounting on a fake clock, percentiles, and the output line."""

import json

import pytest
from bench_util import ROOT  # noqa: F401

from benchmark.harness import output, stats
from benchmark.harness.stats import RequestLog


def _log(due, submitted, tokens, max_new=4):
    r = RequestLog(due, 10, max_new)
    r.submitted = submitted
    r.token_times = list(tokens)
    return r


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 50) == 50
    assert stats.percentile([5.0], 99) == 5.0
    assert stats.percentile([1, 2, 3, float("inf")], 90) == float("inf")
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_mean_is_over_all_samples_and_a_missing_answer_makes_it_infinite():
    assert stats.mean([1.0, 2.0, 6.0]) == 3.0
    assert stats.mean([0.1] * 10) == pytest.approx(0.1, abs=1e-15)
    assert stats.mean([1.0, float("inf")]) == float("inf")
    with pytest.raises(ValueError):
        stats.mean([])


def test_ttft_counts_from_the_due_time_not_the_submit_time():
    # due at 10.0, the generator got to it at 10.2, first token at 10.5
    s = stats.window_samples([_log(10.0, 10.2, [10.5, 10.6])], 10, 20)
    assert s["ttft"] == [pytest.approx(0.5)]
    assert s["late"] == [pytest.approx(0.2)]
    assert s["attempted"] == 1


def test_requests_carried_in_give_gaps_but_no_ttft():
    # due in the pre-roll (t=8), still decoding when the window opens
    carried = _log(8.0, 8.0, [8.5, 9.5, 10.5, 11.5])
    s = stats.window_samples([carried], 10, 20)
    assert s["ttft"] == [] and s["attempted"] == 0
    assert s["gaps"] == [pytest.approx(1.0), pytest.approx(1.0)]


def test_requests_unfinished_at_the_end_are_cut_at_the_edge():
    r = _log(18.0, 18.0, [18.5, 19.5, 20.5, 21.5])
    s = stats.window_samples([r], 10, 20)
    assert s["ttft"] == [pytest.approx(0.5)]
    assert s["gaps"] == [pytest.approx(1.0)]      # only the gap ending <20
    assert not r.finished or r.max_new == 4


def test_unanswered_and_failed_requests_are_counted_not_dropped():
    waiting = _log(19.9, 19.95, [])
    refused = _log(15.0, 15.0, [])
    refused.failed = True
    outside = _log(25.0, 25.0, [25.5])
    s = stats.window_samples([waiting, refused, outside], 10, 20)
    assert (s["unanswered"], s["failed"], s["attempted"]) == (1, 1, 2)
    assert s["ttft"] == []


def test_result_line_has_the_contract_keys_and_whole_digits():
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 123}
    line = json.loads(output.result_line(
        True, 400, 0, {"setup_s": (95.312712345, "s")}, dev))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device"]
    assert line["metrics"]["setup_s"] == {"value": 95.312712345,
                                          "unit": "s"}
    traced = json.loads(output.result_line(
        False, 1, 1, {}, dev, {"device_ops": [], "idle_gaps": []}))
    assert traced["correct"] is False and "breakdown" in traced
