"""The stratified generator: every seed offers the same lengths at the
same instants, in the order the mix fixes, and the stated quantiles."""

import glob
import json
import os

import numpy as np
import pytest
from bench_util import ROOT

from benchmark.harness import loader, traffic

CHAT = loader.load_cell("gpt2l-serve-chat")["traffic"]
LONGDOC = loader.load_cell("gpt2l-serve-longdoc")["traffic"]
PHASES = (15.0, 40.0, 20.0)


def _window(reqs):
    return [r for r in reqs if PHASES[0] <= r.due_s < PHASES[0] + PHASES[1]]


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2147483900)])
def test_open_loop_offers_the_same_work_for_any_two_seeds(seeds):
    mix = CHAT
    a, b = (traffic.make_requests(mix, s, PHASES, 50257, 1024)
            for s in seeds)
    assert len(a) == len(b)
    wa, wb = _window(a), _window(b)
    assert len(wa) == len(wb) == int(
        mix["arrivals"]["rate_per_s"] * PHASES[1])
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, wa)) == sorted(map(key, wb))
    # ... with other token ids
    assert not np.array_equal(wa[0].prompt[:8], wb[0].prompt[:8])
    # the same gaps: arrival times are a permutation's cumulative sums, so
    # the last arrival of each phase is the same instant
    assert a[-1].due_s == pytest.approx(b[-1].due_s, abs=1e-9)
    assert wa[-1].due_s == pytest.approx(wb[-1].due_s, abs=1e-9)
    # the mix's schedule_seed fixes the order, whatever the run's seed
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.due_s for r in a] == [r.due_s for r in b]


def test_another_schedule_seed_is_another_order_of_the_same_work():
    a, b = (traffic.make_requests(dict(CHAT, schedule_seed=k), 1, PHASES,
                                  50257, 1024) for k in (1, 2))
    wa, wb = _window(a), _window(b)
    assert sorted(len(r.prompt) for r in wa) == \
        sorted(len(r.prompt) for r in wb)
    assert [len(r.prompt) for r in wa] != [len(r.prompt) for r in wb]


def test_a_mix_without_a_schedule_seed_is_refused():
    free = {k: v for k, v in CHAT.items() if k != "schedule_seed"}
    with pytest.raises(KeyError, match="schedule_seed"):
        traffic.make_requests(free, 1, PHASES, 50257, 1024)


def test_same_seed_same_requests():
    a, b = (traffic.make_requests(CHAT, 5, PHASES, 50257, 1024)
            for _ in range(2))
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               and x.max_new == y.max_new for x, y in zip(a, b))


def test_lengths_have_the_stated_quantiles():
    rng = np.random.default_rng(0)
    p = traffic.stratified_lengths(CHAT["prompt_len"], 400, rng)
    assert p.min() >= 32 and p.max() <= 640
    assert np.median(p) == pytest.approx(128, abs=2)
    r = traffic.stratified_lengths(CHAT["reply_len"], 400, rng)
    assert r.min() >= 16 and r.max() <= 192
    assert np.median(r) == pytest.approx(48, abs=1)
    assert 55 <= r.mean() <= 70            # the issue's "mean about 64"
    u = traffic.stratified_lengths(LONGDOC["prompt_len"], 64, rng)
    assert u.min() >= 512 and u.max() <= 960
    assert u.mean() == pytest.approx(736, abs=2)


def test_gaps_are_exponential_with_the_stated_rate():
    g = traffic.stratified_gaps(3.0, 300, np.random.default_rng(1))
    assert g.mean() == pytest.approx(1 / 3.0, rel=0.01)
    assert np.median(g) == pytest.approx(np.log(2) / 3.0, rel=0.02)
    g2 = traffic.stratified_gaps(3.0, 300, np.random.default_rng(2))
    assert sorted(g) == sorted(g2) and list(g) != list(g2)


def test_closed_loop_rounds_and_chains():
    a = traffic.make_requests(LONGDOC, 3, PHASES, 50257, 1024)
    b = traffic.make_requests(LONGDOC, 4, PHASES, 50257, 1024)
    c = LONGDOC["arrivals"]["clients"]
    per_round = c * LONGDOC["requests_per_client"]
    assert len(a) == per_round * LONGDOC["rounds"]
    for k in range(LONGDOC["rounds"]):
        ra, rb = (x[k * per_round:(k + 1) * per_round] for x in (a, b))
        assert sorted(len(r.prompt) for r in ra) == \
            sorted(len(r.prompt) for r in rb)
        assert sorted(r.max_new for r in ra) == sorted(r.max_new for r in rb)
    # the first request of each client is staggered, the rest wait for
    # the same client's previous request
    first = a[:c]
    assert [r.after for r in first] == [-1] * c
    assert first[0].due_s == 0 and first[-1].due_s < \
        LONGDOC["arrivals"]["stagger_s"]
    assert sorted({round(y.due_s - x.due_s, 9)
                   for x, y in zip(first, first[1:])}).__len__() == 1
    for i, r in enumerate(a[c:], start=c):
        assert r.after == i - c and r.client == a[r.after].client
    traffic.check_fits(a, 1024)


@pytest.mark.parametrize("seed", [1, 2147483900])
def test_longer_chains_start_with_the_requests_four_rounds_sent(seed):
    """``rounds`` went 4 -> 20 (PR 35) so that the chains outlast the
    window; each round draws its order and its token ids after the rounds
    before it, so the first 256 requests -- all that a window at the
    parent's speed sees -- are the ones the cell sent before."""
    assert LONGDOC["rounds"] == 20
    old = traffic.make_requests(dict(LONGDOC, rounds=4), seed, PHASES,
                                50257, 1024)
    new = traffic.make_requests(LONGDOC, seed, PHASES, 50257, 1024)
    assert len(old) == 256 and len(new) == 1280
    for a, b in zip(old, new):
        assert (a.due_s, a.max_new, a.client, a.after, a.think_s) == \
            (b.due_s, b.max_new, b.client, b.after, b.think_s)
        assert np.array_equal(a.prompt, b.prompt)
    # ... and every caller goes on from where its chain used to end
    assert [r.after for r in new[256:]] == list(range(240, 1264))


def _closed_loop_mixes():
    out = {}
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "traffic",
                                              "*.json"))):
        with open(path) as f:
            mix = json.load(f)
        if "clients" in mix.get("arrivals", {}):
            out[os.path.basename(path)[:-len(".json")]] = mix
    return out


CLOSED = _closed_loop_mixes()
# tokens/s up to which a mix's chains have to outlast pre-roll + window,
# taken over all callers together (the first caller to end its chain does so
# at some four fifths of that: PERF.md section 4): ISSUE 35's floors, 3 to 9
# times the rates of PR 34 (3.4 k, 6.8 k, 4.3 k)
CHAIN_FLOOR = {"longdoc": 10_000, "docqa": 25_000, "reason": 40_000}


@pytest.mark.parametrize("name", sorted(CLOSED))
def test_closed_loop_chains_outlast_the_window_at_a_faster_engine(name):
    """Nothing follows a caller's last request.  From the data files
    alone: the tokens of all chains over the seconds they have to last,
    traffic's start to the window's end (the stagger's ramp lies inside
    the pre-roll).  An engine that processes more than this a second ends
    a chain inside the window, and the driver then refuses the run; a
    later edit that shortens a chain fails here first."""
    mix = CLOSED[name]
    assert set(CHAIN_FLOOR) <= set(CLOSED)
    rng = np.random.default_rng(0)
    per_round = mix["arrivals"]["clients"] * mix["requests_per_client"]
    tokens = mix["rounds"] * sum(
        int(traffic.stratified_lengths(mix[k], per_round, rng).sum())
        for k in ("prompt_len", "reply_len"))
    assert mix["arrivals"]["stagger_s"] <= mix["preroll_s"]
    lasts = mix["preroll_s"] + loader.manifest()["run_seconds"]
    assert tokens / lasts >= CHAIN_FLOOR.get(name, 0), (tokens, lasts)


def test_an_unknown_arrival_process_is_refused():
    mix = dict(CHAT, arrivals={"process": "onoff", "rate_per_s": 3.0})
    with pytest.raises(ValueError, match="unknown arrival process"):
        traffic.make_requests(mix, 1, (50.0,), 50257, 1024)


def test_sessions_share_a_system_prompt_and_grow():
    mix = dict(CHAT, prompt_len={"dist": "uniform", "min": 32, "max": 96},
               reply_len={"dist": "uniform", "min": 16, "max": 32},
               sessions={"system_prompt_len": 384, "turns_min": 3,
                         "turns_max": 5, "think_s": 1.0})
    reqs = traffic.make_requests(mix, 9, (30.0,), 50257, 1024)
    system = reqs[0].prompt[:384]
    assert all(np.array_equal(r.prompt[:384], system) for r in reqs)
    turns = [r for r in reqs if r.after >= 0]
    assert turns, "sessions have follow-up turns"
    for i, r in enumerate(reqs):
        if r.after >= 0:
            prev = reqs[r.after]
            assert r.after == i - 1 and r.think_s == 1.0
            assert np.array_equal(r.prompt[:len(prev.prompt)], prev.prompt)
    traffic.check_fits(reqs, 1024)


def test_traffic_that_does_not_fit_is_refused():
    mix = dict(CHAT, prompt_len={"dist": "uniform", "min": 900, "max": 1000},
               reply_len={"dist": "uniform", "min": 100, "max": 200})
    reqs = traffic.make_requests(mix, 1, (5.0,), 50257, 1024)
    with pytest.raises(ValueError, match="does not fit"):
        traffic.check_fits(reqs, 1024)
