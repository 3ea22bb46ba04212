"""One general traffic generator, driven by a mix's data file.

A serve mix (``benchmark/traffic/<mix>.json``) gives:

    prompt_len, reply_len   {"dist": "lognormal", "median", "sigma",
                             "min", "max"} or {"dist": "uniform", "min",
                             "max"}
    arrivals                open loop: {"process": "poisson",
                            "rate_per_s"}
                            closed loop: {"clients", "stagger_s"}
    sessions (optional)     {"system_prompt_len", "turns_min",
                            "turns_max", "think_s"}: requests come in
                            sessions that share one system prompt; each
                            turn's prompt is the session so far plus the
                            turn's own tokens
    preroll_s               traffic before the window, part of set-up
    schedule_seed           orders the grid below

Stratified, not sampled: lengths and inter-arrival gaps are the fixed
quantile grid of the stated distribution, as many points as each phase
holds requests, permuted by the mix's ``schedule_seed``.  Every run seed
therefore offers the same prompts' and replies' lengths at the same
instants -- the same work in the same order -- with other token ids (and
other weights).  Queueing, and with it every tail, follows the order: two
orders of the same work read tens of percent apart (PERF.md, Findings,
PR 23), so the order belongs to the mix and not to the seed.
"""

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    due_s: float            # open loop: offset from the traffic's start
    prompt: np.ndarray      # int32 token ids
    max_new: int
    client: int = -1        # closed loop: which client sends it
    after: int = -1         # index of the request that must finish first
    think_s: float = 0.0    # ... and the pause after it


def quantile(spec, u):
    """The u-quantile (0 < u < 1) of a length distribution, as a whole
    number inside [min, max]."""
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif spec["dist"] == "lognormal":
        x = spec["median"] * math.exp(
            spec["sigma"] * NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return int(min(hi, max(lo, round(x))))


def grid(n):
    return [(i + 0.5) / n for i in range(n)]


def stratified_lengths(spec, n, rng):
    vals = np.array([quantile(spec, u) for u in grid(n)], np.int64)
    return vals[rng.permutation(n)]


def stratified_gaps(rate_per_s, n, rng):
    """n exponential inter-arrival gaps of mean 1/rate: the quantile grid,
    permuted.  Their sum is the same for every seed."""
    g = np.array([-math.log(1.0 - u) / rate_per_s for u in grid(n)])
    return g[rng.permutation(n)]


def _token_ids(rng, n, vocab):
    return rng.integers(0, vocab, n, dtype=np.int64).astype(np.int32)


def make_requests(mix, seed, phases_s, vocab, max_positions):
    """The requests of one run, in the order they are due.

    ``phases_s`` are the lengths of the run's phases (pre-roll, window,
    and what the driver keeps sending while it waits for the window's
    last first tokens).  Open loop: each phase gets a quantile grid of
    its own, so the requests DUE IN THE WINDOW are the same multiset for
    every seed.  Closed loop: the mix's ``rounds`` rounds of
    ``requests_per_client`` requests a client, each round the same grid
    in another order."""
    # token ids come from the run's seed; the ORDER of the grid (which
    # length meets which gap) from the mix's ``schedule_seed``
    ids_rng = np.random.default_rng(int(seed))
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    arr = mix["arrivals"]
    if "clients" in arr:
        c = int(arr["clients"])
        per_round = int(mix["requests_per_client"]) * c
        stagger = float(arr["stagger_s"])
        reqs = []
        for _ in range(int(mix["rounds"])):
            prompts = stratified_lengths(mix["prompt_len"], per_round, rng)
            replies = stratified_lengths(mix["reply_len"], per_round, rng)
            for p, r in zip(prompts, replies):
                i = len(reqs)
                reqs.append(Request(
                    due_s=(stagger * i / c) if i < c else 0.0,
                    prompt=_token_ids(ids_rng, int(p), vocab),
                    max_new=int(r), client=i % c,
                    after=(i - c) if i >= c else -1))
        return reqs
    times, prompts, replies, at = [], [], [], 0.0
    for length in phases_s:
        # rounded down: the grid's gaps sum to a little under n / rate,
        # so a phase's last arrival stays inside the phase
        n = max(1, int(arr["rate_per_s"] * length))
        t = at + np.cumsum(stratified_gaps(arr["rate_per_s"], n, rng))
        times += t.tolist()
        prompts += stratified_lengths(mix["prompt_len"], n, rng).tolist()
        replies += stratified_lengths(mix["reply_len"], n, rng).tolist()
        at += length
    n = len(times)
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    ses = mix.get("sessions")
    if not ses:
        return [Request(due_s=float(t),
                        prompt=_token_ids(ids_rng, int(p), vocab),
                        max_new=int(r))
                for t, p, r in zip(times, prompts, replies)]
    # sessions: arrival i opens a session or continues one.  A session's
    # turns are spaced by its think time after the previous turn FINISHED
    # (``after``), its first turn comes on the arrival schedule.
    system = _token_ids(ids_rng, int(ses["system_prompt_len"]), vocab)
    reqs, i = [], 0
    span = ses["turns_max"] - ses["turns_min"] + 1
    while i < n:
        turns = ses["turns_min"] + (len(reqs) % span)
        history = system
        for turn in range(min(turns, n - i)):
            own = _token_ids(ids_rng, int(prompts[i]), vocab)
            prompt = np.concatenate([history, own])
            room = max_positions - int(replies[i])
            if len(prompt) > room:      # a session that outgrew the model
                break
            reqs.append(Request(
                due_s=float(times[i]) if turn == 0 else 0.0,
                prompt=prompt, max_new=int(replies[i]),
                after=(len(reqs) - 1) if turn else -1,
                think_s=float(ses["think_s"]) if turn else 0.0))
            # the next turn re-sends the conversation: this prompt plus
            # stand-in reply tokens (seeded; the served reply of a model
            # with random weights is as arbitrary)
            history = np.concatenate(
                [prompt, _token_ids(ids_rng, int(replies[i]), vocab)])
            i += 1
        else:
            continue
        i += 1
    return reqs


def check_fits(reqs, max_positions):
    """Traffic on which no operation fails: every request fits the model."""
    for r in reqs:
        if len(r.prompt) + r.max_new > max_positions:
            raise ValueError(
                f"a request of {len(r.prompt)} + {r.max_new} tokens does "
                f"not fit {max_positions} positions: fix the mix")
