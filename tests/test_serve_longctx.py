"""Long-context serving (the long-context round): the Sarathi-style
chunked-prefill token budget (``PagedConfig(prefill_token_budget=)``),
windowed paged decode (sliding-window models in O(window) blocks), and
ring-attention prefill over the TP mesh
(``TPConfig(ring_prefill=True)``).

Parity discipline matches the rest of the serve suite: token streams
are np.array_equal-pinned against the unbudgeted engine / the offline
windowed ``generate`` oracle / the single-device engine — budgeted
chunk prefill rides the same ``_chunk_row`` executable the prefix
cache pinned bitwise against full prefill, so budgeted streams are
BYTE-identical; the windowed block kernel and the ring logsumexp merge
reorder float reductions, so those pins are token-identity (the same
caveat the kernel and TP rounds document)."""

import math

import numpy as np
import pytest

from singa_tpu import tensor
from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from singa_tpu.observe import requests as reqtrace
from singa_tpu.resilience import FailAfterN, FailOnce, faults
from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                             GenerationRequest, PagedConfig,
                             PrefixCacheConfig)
from singa_tpu.serve.tp import TPConfig

B = 8  # pool block size every engine below uses


def _build(cfg):
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.fixture(scope="module")
def model():
    return _build(GPT2Config.tiny(dropout=0.0))


@pytest.fixture(scope="module")
def windowed(model):
    """Sliding-window twin of ``model`` — SAME weights, so in-window
    streams must agree byte-for-byte with the full-cache engine."""
    cfg = GPT2Config.tiny(dropout=0.0, attn_window=2 * B)
    wm = _build(cfg)
    wm.set_states(model.get_states())
    return wm


@pytest.fixture(scope="module")
def draft():
    return _build(GPT2Config.tiny(dropout=0.0, n_layer=1))


def _reqs(specs):
    return [GenerationRequest(
        np.asarray(p, np.int32), max_new_tokens=n,
        temperature=t, seed=s)
        for p, n, t, s in specs]


def _drive(m, reqs, max_slots=4, max_steps=6000, **kw):
    eng = m.serve(max_slots=max_slots, **kw)
    hs = [eng.submit(r) for r in reqs]
    eng.run_until_complete(max_steps=max_steps)
    outs = [h.result().tokens for h in hs]
    snap = eng.stats.snapshot()
    eng.close()
    return outs, snap


def _mix(seed=0):
    """One long admission (64-token prompt) among short chat traffic,
    greedy and sampled mixed."""
    rng = np.random.RandomState(seed)
    specs = [(rng.randint(0, 256, 64), 4, 0.0, 11)]
    for i in range(3):
        specs.append((rng.randint(0, 256, rng.randint(4, 12)),
                      6, float(rng.choice([0.0, 0.9])), 20 + i))
    return _reqs(specs)


# -- chunked-prefill token budget -------------------------------------------

def test_budget_streams_byte_identical(model):
    """Budgeted chunk prefill == unbudgeted whole-prompt prefill,
    byte for byte (greedy + seeded sampling): the chunks ride the
    bitwise-pinned ``_chunk_row`` path and the admission token
    samples through ``_first_from_hidden`` exactly like the warm
    path."""
    base, _ = _drive(model, _mix(),
                     paged=PagedConfig(block_size=B, num_blocks=32))
    outs, _ = _drive(model, _mix(),
                     paged=PagedConfig(block_size=B, num_blocks=32,
                                       prefill_token_budget=B))
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))


def test_budget_decode_dispatches_every_step(model):
    """While the long admission's prefill spreads across steps, the
    already-live chat slots advance EVERY step (decode is dispatched
    before the budget pass) — the stall the budget exists to kill —
    and a queued follower admits only after the expensive head
    finishes (FIFO blocks, it never skips)."""
    eng = model.serve(max_slots=4, paged=PagedConfig(
        block_size=B, num_blocks=32, prefill_token_budget=B))
    chat = eng.submit(GenerationRequest(
        np.arange(6, dtype=np.int32), max_new_tokens=40,
        temperature=0.0, seed=1))
    eng.step()                      # chat admitted + decoding
    long_prompt = np.arange(64, dtype=np.int32) % 256
    h_long = eng.submit(GenerationRequest(
        long_prompt, max_new_tokens=2, temperature=0.0, seed=2))
    h_follow = eng.submit(GenerationRequest(
        np.arange(5, dtype=np.int32), max_new_tokens=2,
        temperature=0.0, seed=3))
    long_steps = 0
    while True:
        pos_before = int(eng._pos[0])
        eng.step()
        if not eng._prefilling:
            break
        long_steps += 1
        assert int(eng._pos[0]) == pos_before + 1, \
            "chat decode stalled behind the budgeted prefill"
        # the head consumes the whole budget each step, so the
        # follower must not overtake it (FIFO blocks, never skips)
        assert not h_follow.done()
        live = sum(s is not None for s in eng._slots)
        assert live == 1 and len(eng._prefilling) == 1, \
            "follower overtook the budgeted head"
        if eng.step_count > 200:
            pytest.fail("budgeted prefill never completed")
    # 64-token prompt at an 8-token budget: 8 chunks, one per step
    assert long_steps >= len(long_prompt) // B - 1
    eng.run_until_complete(max_steps=2000)
    for h in (chat, h_long, h_follow):
        assert h.result().tokens is not None
    assert eng.paged_arena.blocks_used == 0
    eng.close()


def test_budget_ledger_chunks_and_stall_attribution(model):
    """The request ledger sees every budgeted chunk (prefill phase of
    the long request spans steps) and chat requests' stall phase
    stays bounded."""
    led = reqtrace.enable(capacity=256)
    try:
        outs, _ = _drive(model, _mix(),
                         paged=PagedConfig(block_size=B,
                                           num_blocks=32,
                                           prefill_token_budget=B))
        entries = {e["request_id"]: e for e in led.entries()}
        long_e = [e for e in entries.values()
                  if e["prompt_len"] == 64][0]
        assert long_e["phases"]["prefill"] > 0
        # phase attribution stays exact arithmetic with chunked
        # prefill in the timeline
        ph = long_e["phases"]
        assert abs(ph["hops"] + ph["queue"] + ph["prefill"]
                   - long_e["ttft_s"]) <= 1e-9 + 1e-6 * long_e["ttft_s"]
    finally:
        reqtrace.disable()


def test_budget_with_prefix_cache_warm_hits(model):
    """Budget + radix prefix cache: a warm second request (admitted
    after the first retired and donated) re-admits through the
    budgeted path and stays byte-identical to the cold stream (same
    canonical chunk form)."""
    shared = (np.arange(24, dtype=np.int32) * 3) % 256
    specs = [(np.concatenate([shared, np.arange(6, dtype=np.int32)]),
              5, 0.0, 1),
             (np.concatenate([shared,
                              np.arange(9, dtype=np.int32) + 1]),
              5, 0.0, 2)]
    cold, _ = _drive(model, _reqs(specs),
                     paged=PagedConfig(block_size=B, num_blocks=32))
    eng = model.serve(max_slots=4,
                      paged=PagedConfig(block_size=B, num_blocks=32,
                                        prefill_token_budget=B),
                      prefix_cache=PrefixCacheConfig(block_size=B))
    warm = []
    for r in _reqs(specs):      # sequential: donation before reuse
        h = eng.submit(r)
        eng.run_until_complete(max_steps=500)
        warm.append(h.result().tokens)
    snap = eng.stats.snapshot()
    eng.close()
    assert all(np.array_equal(a, b) for a, b in zip(warm, cold))
    assert snap["prefix"]["hit_tokens"] > 0


def test_budget_fault_mid_prefill_frees_blocks(model):
    """A fault BETWEEN chunks (the ``serve.prefill_chunk`` site)
    fails the engine typed — the mid-prefill request rejects
    requeue-safe (started=False) and its partial blocks return to
    the free list (no leak); under a supervisor the requeued request
    completes with byte parity."""
    want = np.asarray(model.generate(
        np.arange(64, dtype=np.int32) % 256, max_new_tokens=3,
        temperature=0))
    # direct engine: typed failure, started=False, zero leak
    eng = model.serve(max_slots=2, paged=PagedConfig(
        block_size=B, num_blocks=32, prefill_token_budget=B))
    h = eng.submit(GenerationRequest(
        np.arange(64, dtype=np.int32) % 256, max_new_tokens=3,
        temperature=0.0))
    faults.inject("serve.prefill_chunk", FailAfterN(2, times=1))
    try:
        with pytest.raises(EngineFailedError):
            for _ in range(50):
                eng.step()
    finally:
        faults.clear()
    with pytest.raises(EngineFailedError) as ei:
        h.result()
    assert ei.value.started is False
    assert eng.paged_arena.blocks_used == 0, "mid-prefill leak"
    eng.close(force=True)
    # supervised: restart + requeue, parity kept
    sup = EngineSupervisor(model, max_slots=2, restart_budget=2,
                           paged=PagedConfig(
                               block_size=B, num_blocks=32,
                               prefill_token_budget=B))
    h = sup.submit(GenerationRequest(
        np.arange(64, dtype=np.int32) % 256, max_new_tokens=3,
        temperature=0.0))
    pol = faults.inject("serve.prefill_chunk", FailAfterN(2, times=1))
    try:
        sup.run_until_complete(max_steps=2000)
    finally:
        faults.clear()
    assert pol.fired == 1
    assert np.array_equal(h.result().tokens, want)
    assert sup.engine.paged_arena.blocks_used == 0
    sup.close()


def test_budget_with_spec_draft(model, draft):
    """Budget composes with speculative decoding: the target prefill
    chunks, the draft prefills whole at completion, streams equal the
    unbudgeted spec engine's."""
    kw = dict(draft_model=draft, spec_k=4)
    base, _ = _drive(model, _mix(3),
                     paged=PagedConfig(block_size=B, num_blocks=32),
                     **kw)
    outs, _ = _drive(model, _mix(3),
                     paged=PagedConfig(block_size=B, num_blocks=32,
                                       prefill_token_budget=B), **kw)
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))


def test_resume_never_lands_on_prefilling_slot(model):
    """Regression (review finding): a slot reserved by an in-flight
    chunked prefill is NOT free — a swapped request resuming into it
    would be clobbered when the prefill completes and promotes the
    reservation.  The collision needs the prefilling slot BELOW the
    freed one (resume picks the lowest 'free' index): slot 0's first
    tenant retires and the queued long admission backfills it while
    slot 1's tenant is then preempted."""
    eng = model.serve(max_slots=2, paged=PagedConfig(
        block_size=B, num_blocks=32, prefill_token_budget=B))
    h_a = eng.submit(GenerationRequest(        # slot 0: retires at
        np.arange(4, dtype=np.int32), max_new_tokens=4,
        temperature=0.0, seed=3))              # step 4 (after b admits)
    eng.step()
    h_b = eng.submit(GenerationRequest(        # slot 1, long-running
        np.arange(6, dtype=np.int32), max_new_tokens=30,
        temperature=0.0, seed=2))
    eng.step()
    h_long = eng.submit(GenerationRequest(     # queued behind both
        np.arange(64, dtype=np.int32) % 256, max_new_tokens=2,
        temperature=0.0, seed=1))
    for _ in range(20):                        # until long reserves 0
        eng.step()
        if 0 in eng._prefilling:
            break
    assert 0 in eng._prefilling and eng._slots[0] is None
    assert eng._slots[1] is not None
    eng._preempt_slot(1, reason="test")        # swapped entry, slot 1
    assert eng._swapped
    eng.step()   # resume pass: must pick slot 1, NOT the reserved 0
    assert 0 in eng._prefilling or eng._slots[0] is not None
    # drain: every request must resolve (with the bug the resumed
    # request's slot was overwritten and its handle never finished)
    eng.run_until_complete(max_steps=2000)
    for h, (p, n) in ((h_a, (4, 4)), (h_b, (6, 30)), (h_long, (64, 2))):
        want = model.generate(
            (np.arange(p, dtype=np.int32) % 256) if p == 64
            else np.arange(p, dtype=np.int32),
            max_new_tokens=n, temperature=0)
        assert np.array_equal(h.result().tokens, want)
    assert eng.paged_arena.blocks_used == 0
    eng.close()


def test_start_prefilling_copy_fault_frees_blocks(model):
    """Regression (review finding): a fault in the row copy BETWEEN
    block allocation and the prefilling registration must not leak
    the freshly allocated blocks."""
    eng = model.serve(max_slots=2, paged=PagedConfig(
        block_size=B, num_blocks=32, prefill_token_budget=B))
    h = eng.submit(GenerationRequest(
        np.arange(40, dtype=np.int32) % 256, max_new_tokens=2,
        temperature=0.0))
    faults.inject("serve.paged_copy", FailOnce())
    try:
        with pytest.raises(EngineFailedError):
            for _ in range(20):
                eng.step()
    finally:
        faults.clear()
    with pytest.raises(EngineFailedError) as ei:
        h.result()
    assert ei.value.started is False
    assert eng.paged_arena.blocks_used == 0, "copy-fault block leak"
    eng.close(force=True)


# -- windowed paged decode ---------------------------------------------------

def test_windowed_in_window_byte_parity(model, windowed):
    """Sequences that never leave the window: the windowed paged
    engine streams byte-identically to the full-cache paged engine on
    the same weights (the band never binds, the masks add no float
    difference)."""
    specs = [(np.arange(5, dtype=np.int32), 6, 0.0, 1),
             (np.arange(7, dtype=np.int32) + 3, 6, 0.9, 2)]
    base, _ = _drive(model, _reqs(specs),
                     paged=PagedConfig(block_size=B, num_blocks=32))
    outs, _ = _drive(windowed, _reqs(specs),
                     paged=PagedConfig(block_size=B, num_blocks=32))
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))


def test_windowed_long_generation_block_accounting(windowed):
    """A generation far beyond the window: the slot never holds more
    than ceil(window/B)+1 blocks, dropped blocks are REUSED (the
    total blocks touched exceeds the pool), the stream equals the
    offline windowed ``generate`` oracle, and the drained pool is
    leak-free."""
    prompt = np.arange(10, dtype=np.int32)
    n_new = 90   # total 100 positions = 13 blocks > 6-block pool
    eng = windowed.serve(max_slots=1, paged=PagedConfig(
        block_size=B, num_blocks=6))
    h = eng.submit(GenerationRequest(prompt, max_new_tokens=n_new,
                                     temperature=0.0))
    peak = 0
    while eng.pending:
        eng.step()
        s = eng._slots[0]
        if s is not None:
            peak = max(peak, sum(1 for b in s.blocks
                                 if b != eng.paged_arena.trash))
    window = 2 * B
    assert peak <= math.ceil(window / B) + 1, peak
    assert eng.paged_arena.window_drops > 6, "pool blocks not reused"
    assert eng.paged_arena.blocks_used == 0
    want = windowed.generate(prompt, max_new_tokens=n_new,
                             temperature=0)
    assert np.array_equal(h.result().tokens, want)
    eng.close()


def test_windowed_long_prompt_admits_in_window_blocks(windowed):
    """A prompt longer than the window admits holding only the
    in-window lanes' blocks — the below-window prefix is computed but
    never allocated."""
    prompt = (np.arange(64, dtype=np.int32) * 5) % 256
    eng = windowed.serve(max_slots=1, paged=PagedConfig(
        block_size=B, num_blocks=6))
    h = eng.submit(GenerationRequest(prompt, max_new_tokens=4,
                                     temperature=0.0))
    eng.step()
    s = eng._slots[0]
    held = sum(1 for b in s.blocks if b != eng.paged_arena.trash)
    assert held <= math.ceil(2 * B / B) + 1, held
    eng.run_until_complete(max_steps=500)
    want = windowed.generate(prompt, max_new_tokens=4, temperature=0)
    assert np.array_equal(h.result().tokens, want)
    eng.close()


def test_windowed_int8_parity(windowed):
    """Windowed x int8: token streams equal the offline windowed int8
    oracle's (per-block dequant in the kernel vs the rolling cache's
    folded scales — same quantized values, same key set)."""
    specs = [(np.arange(10, dtype=np.int32), 30, 0.0, 1)]
    from singa_tpu.models import gpt2_decode

    outs, _ = _drive(windowed, _reqs(specs), max_slots=1,
                     cache_dtype="int8",
                     paged=PagedConfig(block_size=B, num_blocks=8))
    want = gpt2_decode.generate(windowed, specs[0][0],
                                max_new_tokens=30, temperature=0,
                                cache_dtype="int8")
    assert np.array_equal(outs[0], want)


def test_windowed_spec_parity(windowed, draft):
    """Windowed x speculative: greedy spec streams equal the plain
    windowed engine's (argmax-match acceptance over the same windowed
    target logits)."""
    specs = [(np.arange(9, dtype=np.int32), 24, 0.0, 1),
             (np.arange(6, dtype=np.int32) + 2, 20, 0.0, 2)]
    base, _ = _drive(windowed, _reqs(specs),
                     paged=PagedConfig(block_size=B, num_blocks=16))
    outs, snap = _drive(windowed, _reqs(specs),
                        paged=PagedConfig(block_size=B,
                                          num_blocks=16),
                        draft_model=draft, spec_k=4)
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))
    # untrained random draft/target rarely argmax-agree — acceptance
    # may legitimately be 0; the pin is that verify CHUNKS ran the
    # windowed chunk kernel and streams stayed equal
    assert snap["spec"]["chunks"] > 0


def test_windowed_preempt_resume_parity(windowed):
    """Windowed x preemption: an over-committed pool swaps a windowed
    slot out (O(window) host image) and the resumed stream equals the
    uninterrupted run's."""
    specs = [(np.arange(8, dtype=np.int32), 40, 0.0, 1),
             ((np.arange(10, dtype=np.int32) * 7) % 256, 40, 0.7, 2),
             (np.arange(5, dtype=np.int32) + 9, 40, 0.0, 3)]
    base, _ = _drive(windowed, _reqs(specs), max_slots=3,
                     paged=PagedConfig(block_size=B, num_blocks=32))
    outs, snap = _drive(windowed, _reqs(specs), max_slots=3,
                        paged=PagedConfig(block_size=B, num_blocks=8),
                        scheduler="priority")
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))
    assert snap["paged"]["blocks_used"] == 0


def test_windowed_tp_parity(windowed):
    """Windowed x tensor parallelism: the sharded twins carry the
    window static; tp=2 streams are token-identical to the
    single-device windowed engine."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs a 2-device mesh")
    specs = [(np.arange(9, dtype=np.int32), 30, 0.0, 1),
             (np.arange(7, dtype=np.int32) + 1, 24, 0.9, 2)]
    base, _ = _drive(windowed, _reqs(specs),
                     paged=PagedConfig(block_size=B, num_blocks=16))
    outs, _ = _drive(windowed, _reqs(specs), tp=2,
                     paged=PagedConfig(block_size=B, num_blocks=16))
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))


# -- ring-attention prefill --------------------------------------------------

def test_ring_prefill_token_identical(model):
    """Ring-sharded prefill == single-device chunk/serial prefill,
    token-identical on the virtual mesh (greedy + seeded sampling;
    the logsumexp merge reorders floats, identity away from ties),
    and the short prompt stays below the threshold on the serial
    path."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs a 2-device mesh")
    specs = [((np.arange(72, dtype=np.int32) * 3) % 256, 5, 0.0, 1),
             ((np.arange(70, dtype=np.int32) * 5) % 256, 5, 0.8, 2),
             (np.arange(8, dtype=np.int32), 5, 0.0, 3)]
    base, _ = _drive(model, _reqs(specs),
                     paged=PagedConfig(block_size=B, num_blocks=48))
    eng = model.serve(max_slots=4,
                      paged=PagedConfig(block_size=B, num_blocks=48),
                      tp=TPConfig(tp=2, ring_prefill=True,
                                  ring_min_tokens=32))
    hs = [eng.submit(r) for r in _reqs(specs)]
    eng.run_until_complete(max_steps=2000)
    outs = [h.result().tokens for h in hs]
    assert eng.tp_exec.ring_prefills == 2   # the two long prompts
    eng.close()
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))


def test_ring_budget_composition(model):
    """Ring + prefill_token_budget: long admissions take the one-shot
    ring dispatch (charged against the budget), short ones chunk —
    streams stay identical to the plain engine's."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs a 2-device mesh")
    base, _ = _drive(model, _mix(5),
                     paged=PagedConfig(block_size=B, num_blocks=32))
    eng = model.serve(max_slots=4,
                      paged=PagedConfig(block_size=B, num_blocks=32,
                                        prefill_token_budget=2 * B),
                      tp=TPConfig(tp=2, ring_prefill=True,
                                  ring_min_tokens=32))
    hs = [eng.submit(r) for r in _mix(5)]
    eng.run_until_complete(max_steps=2000)
    outs = [h.result().tokens for h in hs]
    assert eng.tp_exec.ring_prefills == 1
    eng.close()
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))


# -- configuration contracts -------------------------------------------------

def test_longctx_config_validation(model, windowed, draft):
    """Every refused composition is typed at construction with a
    message naming the long-context path it relates to."""
    # windowed without paged: still NotImplementedError, now naming
    # the paged path instead of only the offline fallback
    with pytest.raises(NotImplementedError, match="paged"):
        windowed.serve()
    # windowed + prefix cache: dropped blocks break the radix
    # contiguity contract
    with pytest.raises(NotImplementedError, match="prefix"):
        windowed.serve(paged=PagedConfig(block_size=B, num_blocks=8),
                       prefix_cache=PrefixCacheConfig(block_size=B))
    # budget must be a block multiple
    with pytest.raises(ValueError, match="prefill_token_budget"):
        PagedConfig(block_size=B, num_blocks=8,
                    prefill_token_budget=B + 1)
    with pytest.raises(ValueError, match="ring_min_tokens"):
        TPConfig(tp=2, ring_min_tokens=-1)
    # ring requires paged
    with pytest.raises(ValueError, match="ring_prefill"):
        model.serve(tp=TPConfig(tp=2, ring_prefill=True))
    # ring + prefix cache refused (non-canonical K/V)
    with pytest.raises(ValueError, match="ring_prefill"):
        model.serve(paged=PagedConfig(block_size=B, num_blocks=8),
                    prefix_cache=PrefixCacheConfig(block_size=B),
                    tp=TPConfig(tp=2, ring_prefill=True))
    # ring + int8 refused (byte-parity pin would not survive)
    with pytest.raises(ValueError, match="int8"):
        model.serve(paged=PagedConfig(block_size=B, num_blocks=8),
                    cache_dtype="int8",
                    tp=TPConfig(tp=2, ring_prefill=True))
    # over-length submit names the long-context path
    eng = model.serve(max_slots=1)
    with pytest.raises(ValueError, match="Long-context serving"):
        eng.submit(GenerationRequest(
            np.zeros(120, np.int32), max_new_tokens=30))
    eng.close()
