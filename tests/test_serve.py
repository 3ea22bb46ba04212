"""Continuous-batching serving engine (singa_tpu/serve): token parity
against the offline generate paths, iteration-level scheduling
semantics (retire + same-step backfill, prefill/decode interleave),
admission control (queue depth, deadlines), and the stats schema.

All deterministic on CPU: token streams come from fixed seeds and the
scheduling tests run on a fake clock."""

import numpy as np
import pytest

from singa_tpu import tensor
from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from singa_tpu.serve import (DeadlineExceededError, FIFOScheduler,
                             GenerationRequest, QueueFullError)


def _model(**kw):
    kw.setdefault("dropout", 0.0)
    cfg = GPT2Config.tiny(**kw)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)
    return m


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


_PROMPTS = [np.arange(9) % 256,
            (np.arange(4) + 3) % 256,
            (np.arange(13) * 2 + 1) % 256,
            np.asarray([5, 1, 200]),
            (np.arange(7) + 40) % 256]


def test_engine_matches_single_prompt_generate():
    """Ragged arrivals through the slot pool produce per-request token
    streams identical to the same prompts run one-at-a-time through
    generate — the core exactness contract (acceptance criterion)."""
    m = _model()
    news = [6, 3, 9, 1, 5]
    eng = m.serve(max_slots=2)
    handles = []
    arrivals = {0: [0, 1], 2: [2, 3], 4: [4]}  # ragged arrival steps
    submitted = 0
    for step in range(200):
        for i in arrivals.get(step, []):
            handles.append(eng.submit(GenerationRequest(
                _PROMPTS[i], max_new_tokens=news[i])))
            submitted += 1
        if submitted == len(_PROMPTS) and not eng.pending:
            break
        eng.step()
    assert not eng.pending
    for h, p, n in zip(handles, _PROMPTS, news):
        res = h.result()
        assert res.finish_reason == "length"
        want = m.generate(np.asarray(p), max_new_tokens=n,
                          temperature=0)
        np.testing.assert_array_equal(res.tokens, want)


def test_sampled_request_matches_seeded_generate():
    """A temperature request with an explicit seed reproduces the
    offline sampled stream: the engine splits the request's key chain
    exactly as generate does."""
    m = _model()
    seed_rs = 11
    s = int(np.random.RandomState(seed_rs).randint(0, 2 ** 31 - 1))
    eng = m.serve(max_slots=2)
    h = eng.submit(GenerationRequest(_PROMPTS[0], max_new_tokens=8,
                                     temperature=0.8, seed=s))
    eng.run_until_complete(max_steps=100)
    want = m.generate(np.asarray(_PROMPTS[0]), max_new_tokens=8,
                      temperature=0.8,
                      rng=np.random.RandomState(seed_rs))
    np.testing.assert_array_equal(h.result().tokens, want)


def test_top_p_engine_matches_generate():
    """Engine-level nucleus filtering matches the offline top-p path
    for a seeded request (mixed with a greedy request in the same
    pool — one executable serves both)."""
    m = _model()
    s = int(np.random.RandomState(3).randint(0, 2 ** 31 - 1))
    eng = m.serve(max_slots=2, top_p=0.9)
    h1 = eng.submit(GenerationRequest(_PROMPTS[1], max_new_tokens=7,
                                      temperature=1.0, seed=s))
    h2 = eng.submit(GenerationRequest(_PROMPTS[2], max_new_tokens=4))
    eng.run_until_complete(max_steps=100)
    from singa_tpu.models import gpt2_decode
    want1 = gpt2_decode.generate(
        m, np.asarray(_PROMPTS[1]), max_new_tokens=7, temperature=1.0,
        top_p=0.9, rng=np.random.RandomState(3))
    np.testing.assert_array_equal(h1.result().tokens, want1)
    want2 = m.generate(np.asarray(_PROMPTS[2]), max_new_tokens=4,
                       temperature=0)
    np.testing.assert_array_equal(h2.result().tokens, want2)


def test_backfill_lands_on_the_retirement_step():
    """When a row hits its token budget, the queued request enters the
    freed slot in the SAME engine step (retire -> backfill), not a
    step later — the iteration-level scheduling contract."""
    m = _model()
    eng = m.serve(max_slots=2)
    ha = eng.submit(GenerationRequest(_PROMPTS[0], max_new_tokens=2))
    hb = eng.submit(GenerationRequest(_PROMPTS[1], max_new_tokens=6))
    hc = eng.submit(GenerationRequest(_PROMPTS[2], max_new_tokens=3))
    eng.run_until_complete(max_steps=100)
    ra, rc = ha.result(), hc.result()
    # A emits token 1 at admission (step 0) and token 2 on the next
    # decode; C must be admitted within that same step
    assert rc.admitted_step == ra.finished_step
    # and every stream still matches the offline oracle
    for h, p, n in ((ha, _PROMPTS[0], 2), (hb, _PROMPTS[1], 6),
                    (hc, _PROMPTS[2], 3)):
        want = m.generate(np.asarray(p), max_new_tokens=n,
                          temperature=0)
        np.testing.assert_array_equal(h.result().tokens, want)


def test_prefill_interleave_caps_admissions_per_step():
    """max_prefills_per_step bounds admissions per scheduling pass so
    an arrival burst cannot starve the decode loop."""
    m = _model()
    eng = m.serve(max_slots=4,
                  scheduler=FIFOScheduler(max_prefills_per_step=1))
    hs = [eng.submit(GenerationRequest(_PROMPTS[i], max_new_tokens=4))
          for i in range(3)]
    eng.run_until_complete(max_steps=100)
    steps = [h.result().admitted_step for h in hs]
    assert steps == sorted(steps) and len(set(steps)) == 3, steps


def test_deadline_expired_requests_rejected_distinctly():
    """A request whose deadline passes while queued is rejected with
    DeadlineExceededError (distinct from QueueFullError); rows already
    in a slot are unaffected."""
    m = _model()
    clock = _FakeClock()
    eng = m.serve(max_slots=1, clock=clock)
    h1 = eng.submit(GenerationRequest(_PROMPTS[0], max_new_tokens=6))
    h2 = eng.submit(GenerationRequest(_PROMPTS[1], max_new_tokens=2,
                                      deadline=5.0))
    eng.step()          # admits h1 (single slot); h2 queued
    clock.advance(10.0)  # h2's deadline passes while queued
    eng.run_until_complete(max_steps=100)
    assert h1.result().finish_reason == "length"
    assert h2.done()
    with pytest.raises(DeadlineExceededError):
        h2.result()
    snap = eng.stats.snapshot()
    assert snap["requests"]["rejected_deadline"] == 1
    assert snap["requests"]["completed"] == 1


def test_queue_depth_rejection_is_synchronous():
    m = _model()
    eng = m.serve(max_slots=1,
                  scheduler=FIFOScheduler(max_queue_depth=2))
    eng.submit(GenerationRequest(_PROMPTS[0], max_new_tokens=2))
    eng.submit(GenerationRequest(_PROMPTS[1], max_new_tokens=2))
    with pytest.raises(QueueFullError):
        eng.submit(GenerationRequest(_PROMPTS[2], max_new_tokens=2))
    assert eng.stats.snapshot()["requests"]["rejected_queue_full"] == 1


def test_streaming_callback_sees_every_token_in_order():
    m = _model()
    streamed = []
    eng = m.serve(max_slots=1)
    h = eng.submit(GenerationRequest(
        _PROMPTS[0], max_new_tokens=5,
        on_token=lambda req, tok: streamed.append(tok)))
    eng.run_until_complete(max_steps=50)
    res = h.result()
    np.testing.assert_array_equal(
        np.asarray(streamed, np.int32),
        res.tokens[len(_PROMPTS[0]):])


def test_stats_schema_stable():
    """BENCH_SERVE.json and dashboards key on this schema; extend by
    adding keys, never renaming."""
    m = _model()
    eng = m.serve(max_slots=2)
    eng.submit(GenerationRequest(_PROMPTS[0], max_new_tokens=3))
    eng.submit(GenerationRequest(_PROMPTS[1], max_new_tokens=1))
    eng.run_until_complete(max_steps=50)
    snap = eng.stats.snapshot()
    assert set(snap) == {"requests", "throughput", "latency", "queue",
                         "slots", "slo", "prefix", "spec", "paged",
                         "tp", "ep", "pp"}
    # no prefix cache / draft model / paged arena / tp-ep-pp mesh
    # configured: present but None
    assert snap["prefix"] is None
    assert snap["spec"] is None
    assert snap["paged"] is None
    assert snap["tp"] is None
    assert snap["ep"] is None
    assert snap["pp"] is None
    assert set(snap["requests"]) == {
        "submitted", "completed", "rejected_deadline",
        "rejected_queue_full"}
    assert set(snap["throughput"]) == {
        "tokens_out", "wall_s", "uptime_s", "tokens_per_s",
        "goodput_tokens_per_s", "prefills", "prefill_tokens",
        "decode_steps"}
    assert set(snap["latency"]) == {"ttft", "tpot", "tpot_ewma_s"}
    for series in (snap["latency"]["ttft"], snap["latency"]["tpot"]):
        assert set(series) == {"count", "mean", "p50", "p99", "max"}
    # the router's headroom signal: set once a multi-token retire exists
    assert snap["latency"]["tpot_ewma_s"] == pytest.approx(
        snap["latency"]["tpot"]["mean"])
    assert set(snap["queue"]) == {"mean_depth", "max_depth"}
    assert set(snap["slots"]) == {"max_slots", "occupancy_mean"}
    assert snap["requests"]["completed"] == 2
    assert snap["throughput"]["tokens_out"] == 4
    assert snap["latency"]["ttft"]["count"] == 2
    # the 1-token request contributes no TPOT sample
    assert snap["latency"]["tpot"]["count"] == 1
    assert 0.0 < snap["slots"]["occupancy_mean"] <= 1.0


def test_engine_validates_requests_and_models():
    m = _model()
    eng = m.serve(max_slots=1)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(GenerationRequest(
            np.zeros(120, np.int32), max_new_tokens=20))
    with pytest.raises(ValueError, match="max_new_tokens"):
        GenerationRequest(_PROMPTS[0], max_new_tokens=0)
    with pytest.raises(ValueError, match="prompt_ids"):
        GenerationRequest(np.zeros(0, np.int32))
    mw = _model(attn_window=8)
    with pytest.raises(NotImplementedError, match="sliding-window"):
        mw.serve()
    with pytest.raises(ValueError, match="max_queue_depth"):
        FIFOScheduler(max_queue_depth=0)


def test_duplicate_request_id_rejected_and_handles_evicted():
    """An in-flight duplicate request_id would orphan the earlier
    handle (the id routes completion) — rejected at submit.  Resolved
    requests are evicted from the engine's routing table, so the id
    becomes reusable and a long-lived engine stays memory-flat."""
    m = _model()
    eng = m.serve(max_slots=1)
    eng.submit(GenerationRequest(_PROMPTS[0], max_new_tokens=2,
                                 request_id="trace-1"))
    with pytest.raises(ValueError, match="in flight"):
        eng.submit(GenerationRequest(_PROMPTS[1], max_new_tokens=2,
                                     request_id="trace-1"))
    eng.run_until_complete(max_steps=50)
    assert len(eng._handles) == 0
    # id reusable once its predecessor resolved
    h = eng.submit(GenerationRequest(_PROMPTS[1], max_new_tokens=2,
                                     request_id="trace-1"))
    eng.run_until_complete(max_steps=50)
    assert h.result().finish_reason == "length"
    assert len(eng._handles) == 0


def test_gqa_model_serves_exactly():
    """GQA keeps its narrow H_kv arena in the pool and still matches
    the offline oracle token for token."""
    m = _model(n_kv_head=2)
    eng = m.serve(max_slots=2)
    hs = [eng.submit(GenerationRequest(p, max_new_tokens=4))
          for p in _PROMPTS[:3]]
    eng.run_until_complete(max_steps=100)
    for h, p in zip(hs, _PROMPTS):
        want = m.generate(np.asarray(p), max_new_tokens=4,
                          temperature=0)
        np.testing.assert_array_equal(h.result().tokens, want)
