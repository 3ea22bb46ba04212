"""The order of one engine step under ``prefill_token_budget``: the
launches of the requests already in chunked prefill go out behind the
decode program and BEFORE the host waits for its tokens
(``InferenceEngine._decode_once``: launch, early pass, collect, emit;
then the schedule pass with the budget that is left).

What is held here, on the CPU: the order itself (from the engine's own
phases under a counting clock), the one budget, where a prefill is
promoted, token streams against the same engine driven in the parent's
order (four families; plain, compacted-width and speculative dispatch),
no new program, and a fault that fires in the early pass.
"""

import contextlib
import importlib
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import device, tensor
from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from singa_tpu.observe import registry
from singa_tpu.observe import trace
from singa_tpu.resilience import FailAfterN, faults
from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                             GenerationRequest, PagedConfig)
from singa_tpu.serve.jitpin import jit_cache_size

B = 8       # the pool block of every engine below
FAMILIES = ("gpt2", "falcon_h1", "mla_moe", "swa_moe", "conv_moe")


def _gpt2(n_layer=2):
    m = GPT2LMHead(GPT2Config.tiny(dropout=0.0, n_layer=n_layer))
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.fixture(scope="module")
def model():
    return _gpt2()


@pytest.fixture(scope="module")
def family_models(model):
    """``name -> (model, vocabulary, serve() keywords)``, each family at
    the tiny preset of its own test file, built on first use."""
    built = {"gpt2": (model, 256, {})}

    def get(name):
        if name not in built:
            from benchmark.harness import loader
            tiny = importlib.import_module("test_" + name).TINY
            ad = loader.load_module("adapters", name)
            ref = loader.load_module("references", name)
            m = ad.build_model(tiny, device.get_default_device(),
                               train=False, batch_shape=(1, 16))
            ad.put_weights(m, ref.init_weights(ref.sizes_of(tiny), 7))
            built[name] = (m, tiny["vocab_size"],
                           {"dtype": jnp.float32})
        return built[name]
    return get


def _engine(m, budget=B, max_slots=4, num_blocks=64, **kw):
    return m.serve(max_slots=max_slots, paged=PagedConfig(
        block_size=B, num_blocks=num_blocks,
        prefill_token_budget=budget), **kw)


def _req(n, n_new, seed=0, vocab=256, temperature=0.0):
    return GenerationRequest(
        np.random.default_rng(seed).integers(0, vocab, n)
        .astype(np.int32), max_new_tokens=n_new,
        temperature=temperature, seed=seed)


@contextlib.contextmanager
def _recorded():
    """The engine's phases under a clock that counts its own reads, so
    every start and end has a place in one order.  Yields ``spans()``:
    ``[(name, start, end, args)]`` by start."""
    tick = itertools.count()
    trace.clear()
    trace.enable(clock=lambda: float(next(tick)))

    def spans():
        return sorted(((e["name"], e["ts"], e["ts"] + e["dur"],
                        e["args"] or {})
                       for e in trace.events() if e["ph"] == "X"),
                      key=lambda s: s[1])
    try:
        yield spans
    finally:
        trace.disable()
        trace.clear()


def _one(spans, name):
    got = [s for s in spans if s[0] == name]
    assert len(got) == 1, (name, [s[0] for s in spans])
    return got[0]


def _inside(inner, outer):
    return outer[1] < inner[1] and inner[2] < outer[2]


def _step(eng):
    """One ``eng.step()`` and its spans."""
    with _recorded() as spans:
        eng.step()
        return spans()


def _chat_then_long(eng, long_len=40):
    """A lane decoding and, submitted behind it, a prompt of several
    steps' budget: returns the two handles with the long one still
    queued."""
    chat = eng.submit(_req(6, 40, seed=1))
    eng.step()
    assert eng.live_slots == 1
    return chat, eng.submit(_req(long_len, 3, seed=2))


# -- the order of a step ----------------------------------------------------

def test_an_inflight_launch_lies_between_the_decode_dispatch_and_the_sync(
        model):
    eng = _engine(model)
    try:
        _chat_then_long(eng)
        eng.step()                  # admitted: its first launch is late
        assert list(eng._prefilling) and eng.live_slots == 1
        early0 = eng._c_early_launches.value
        spans = _step(eng)
        decode = _one(spans, "serve.decode")
        dispatch = _one(spans, "serve.dispatch.paged_decode_step")
        launch = _one(spans, "serve.launch")
        row = _one(spans, "serve.dispatch.chunk_row")
        sync = _one(spans, "serve.sync")
        assert _inside(launch, decode) and _inside(row, launch)
        assert dispatch[2] < launch[1] and launch[2] < sync[1]
        assert _inside(sync, decode)
        assert launch[3] == {"launches": 1, "chunks": 1}
        # the step's totals are still serve.schedule's
        sched = _one(spans, "serve.schedule")
        assert (sched[3]["launches"], sched[3]["chunks"],
                sched[3]["admitted"]) == (1, 1, 0)
        assert not any(_inside(s, sched) for s in spans
                       if s[0] == "serve.dispatch.chunk_row")
        assert eng._c_early_launches.value == early0 + 1
    finally:
        eng.close(force=True)


def test_a_request_admitted_in_a_step_launches_after_its_emit(model):
    eng = _engine(model)
    try:
        _chat_then_long(eng)
        spans = _step(eng)          # the admission's step
        assert not [s for s in spans if s[0] == "serve.launch"]
        emit = _one(spans, "serve.emit")
        sched = _one(spans, "serve.schedule")
        row = _one(spans, "serve.dispatch.chunk_row")
        assert emit[2] < sched[1] and _inside(row, sched)
        assert _inside(_one(spans, "serve.admit"), sched)
        assert eng._c_early_launches.value == 0
        assert eng._c_launches.value == 2      # the chat's and this one
    finally:
        eng.close(force=True)


def test_with_no_live_lane_the_launch_runs_in_the_schedule_pass(model):
    eng = _engine(model)
    try:
        h = eng.submit(_req(40, 2, seed=3))
        eng.step()
        assert list(eng._prefilling) and eng.live_slots == 0
        spans = _step(eng)
        assert not [s for s in spans
                    if s[0] in ("serve.decode", "serve.launch")]
        assert _inside(_one(spans, "serve.dispatch.chunk_row"),
                       _one(spans, "serve.schedule"))
        eng.run_until_complete(max_steps=200)
        # a lone request never has a lane to hide behind
        assert eng._c_early_launches.value == 0
        assert eng._c_launches.value == 5
        assert h.result().tokens is not None
    finally:
        eng.close()


@pytest.mark.parametrize("spec", [False, True],
                         ids=["plain", "speculative"])
def test_the_budget_is_spent_once_and_the_head_still_blocks(model, spec):
    """Budget of two blocks, a lane decoding, a 3-block prompt, then a
    2-block one: the step that launches the first prompt's last block
    early leaves one block, and the second prompt gets exactly that."""
    kw = dict(draft_model=_gpt2(1), spec_k=3) if spec else {}
    eng = _engine(model, budget=2 * B, **kw)
    try:
        chat = eng.submit(_req(6, 60, seed=1))
        eng.step()
        first = eng.submit(_req(3 * B - 2, 2, seed=2))
        second = eng.submit(_req(2 * B - 3, 2, seed=3))
        per_step = []
        while not (first.done() and second.done()):
            spans = _step(eng)
            early = [s[3] for s in spans if s[0] == "serve.launch"]
            total = _one(spans, "serve.schedule")[3]
            assert len(early) <= 1
            e = early[0]["chunks"] if early else 0
            assert e <= total["chunks"] <= 2
            per_step.append((e, total["chunks"] - e))
            assert eng.step_count < 50
        # (early, late) blocks: the head takes the whole budget and the
        # follower waits; then the head's last block goes out early and
        # the follower is admitted with the one block that is left
        assert per_step[:3] == [(0, 2), (1, 1), (1, 0)]
        assert eng._c_early_launches.value == 2 <= eng._c_launches.value
        assert not chat.done()
    finally:
        eng.close(force=True)


def test_a_prefill_that_lands_early_is_promoted_after_the_steps_emit(
        model):
    eng = _engine(model)
    try:
        chat, long = _chat_then_long(eng, long_len=2 * B - 3)
        eng.step()                  # first block, late
        (idx, pf), = eng._prefilling.items()
        assert pf.off == pf.last_off
        seen, emit = [], eng._emit_step

        def spy(toks, a_draft, lps):
            # the last block is launched, the request is not live yet
            seen.append((pf.off > pf.last_off, idx in eng._prefilling,
                         eng._slots[idx]))
            return emit(toks, a_draft, lps)
        eng._emit_step = spy
        n_chat = len(eng._slots[0].emitted)
        eng.step()
        assert seen == [(True, True, None)]
        slot = eng._slots[idx]
        # promoted in the schedule pass: the admission token alone, no
        # token of the decode step it was not in
        assert slot is not None and not eng._prefilling
        assert len(slot.emitted) == 1
        assert eng._pos[idx] == 2 * B - 3
        assert len(eng._slots[0].emitted) == n_chat + 1
        assert eng._c_early_launches.value == 1
    finally:
        eng.close(force=True)


@pytest.mark.parametrize("live", [True, False],
                         ids=["behind-a-lane", "no-lane"])
def test_a_pass_dispatches_its_admission_before_it_fetches_a_first_token(
        model, live):
    """Budget of two blocks, a 3-block prompt and a 2-block one behind
    it: in the pass where the first prompt's last block has landed the
    second is admitted with the block that is left, and its launch goes
    out BEFORE the host waits for the first prompt's token — the chip is
    on that last block meanwhile, so the admission costs it no idle time
    (what took docqa's p95 off its cliff: PERF.md section 6, PR 41)."""
    eng = _engine(model, budget=2 * B)
    try:
        if live:
            eng.submit(_req(6, 60, seed=1))
            eng.step()
        first = eng.submit(_req(3 * B - 2, 2, seed=2))
        second = eng.submit(_req(2 * B - 3, 2, seed=3))
        eng.step()                  # the head takes the whole budget
        (idx, pf), = eng._prefilling.items()
        order, chunks, promote = [], eng._launch_chunks, eng._promote
        emit = eng._emit_step

        def spy_chunks(pieces):
            n = eng._launches_run
            try:
                return chunks(pieces)
            finally:
                order.extend(["launch"] * (eng._launches_run - n))

        def spy_promote(i, p):
            order.append(("promote", p.request.request_id,
                          eng._slots[i] is None and p.first is not None))
            return promote(i, p)

        def spy_emit(*a):
            order.append("emit")
            return emit(*a)
        eng._launch_chunks = spy_chunks
        eng._promote, eng._emit_step = spy_promote, spy_emit
        try:
            eng.step()
        finally:
            eng._launch_chunks = chunks
            eng._promote, eng._emit_step = promote, emit
        rid = first.request.request_id
        # the head's last block (early behind a lane), the decode's
        # emit, the follower's first block, and only then the fetch
        assert order == (["launch", "emit"] if live else ["launch"]) \
            + ["launch", ("promote", rid, True)]
        slot = eng._slots[idx]
        assert slot is not None and len(slot.emitted) == 1
        assert eng._pos[idx] == 3 * B - 2
        (_, pf2), = eng._prefilling.items()
        assert pf2.request is second.request and pf2.first is None
        assert pf2.off == B
        assert eng._c_early_launches.value == (1 if live else 0)
    finally:
        eng.close(force=True)


# -- two requests in one launch ---------------------------------------------

def _three_in_a_pass(eng):
    """A lane decoding and, queued behind it, prompts of 2, 1 and 3
    blocks on a budget of four: one pass admits all three -- (2, 1) fit
    the pair program's slots and are ONE launch, the third gets the
    block that is left."""
    chat = eng.submit(_req(6, 60, seed=1))
    eng.step()
    return [chat] + [eng.submit(_req(n, 2, seed=s))
                     for n, s in ((2 * B - 3, 2), (B - 1, 3), (3 * B - 2, 4))]


def test_a_pair_in_the_pass_spends_the_budget_once_and_the_head_blocks(
        model):
    eng = _engine(model, budget=4 * B)
    try:
        assert eng._pair_blocks == 2
        _, a, b, c = _three_in_a_pass(eng)
        spans = _step(eng)
        total = _one(spans, "serve.schedule")[3]
        # four blocks, the budget: 2 + 1 in one launch, then 1 of the
        # third's three -- which blocks whatever is queued behind it
        # (a and b landed whole and went live in the same pass)
        assert (total["admitted"], total["chunks"], total["launches"],
                total["segments"]) == (2, 4, 2, 3)
        assert eng._c_merged_launches.value == 1
        assert not [s for s in spans if s[0] == "serve.launch"]
        rows = [s for s in spans if s[0] == "serve.dispatch.chunk_row"]
        assert len(rows) == 2
        (pf,) = eng._prefilling.values()
        assert pf.request is c.request and pf.off == B
        d = eng.submit(_req(B - 2, 2, seed=5))
        spans = _step(eng)
        # c's remainder goes out early, alone (the early pass waits for
        # no admission); d takes what is left
        early = _one(spans, "serve.launch")[3]
        total = _one(spans, "serve.schedule")[3]
        assert (early["launches"], early["chunks"]) == (1, 2)
        assert (total["admitted"], total["chunks"], total["launches"],
                total["segments"]) == (2, 3, 2, 2)
        assert eng._c_merged_launches.value == 1
        eng.run_until_complete(max_steps=300)
        assert all(h.done() for h in (a, b, c, d))
    finally:
        eng.close(force=True)


def test_a_pass_that_shares_a_launch_compiles_no_program(model):
    """Construction compiled every shape the planner can choose: after a
    warm-up that reaches the one-block program alone, a mix that takes
    every width and the pair program compiles nothing."""
    eng = _engine(model, budget=4 * B, max_len=112)
    try:
        # one admission a pass: every decode bucket, no shared launch
        for k in range(4):
            eng.submit(_req(B - 1, 8, seed=k))
            eng.step()
        eng.run_until_complete(max_steps=50)
        assert eng._c_merged_launches.value == 0
        assert eng._c_launches.value == eng._c_budget_chunks.value == 4
        warmed = jit_cache_size()
        hs = _three_in_a_pass(eng)
        hs += [eng.submit(_req(n, 3, seed=10 + n))
               for n in (4 * B - 1, 5, 6, 3 * B, 2 * B + 1, 7)]
        eng.run_until_complete(max_steps=400)
        assert all(h.done() for h in hs)
        assert eng._c_merged_launches.value >= 2
        assert warmed is not None and jit_cache_size() == warmed
    finally:
        eng.close()


def test_a_slot_that_would_pass_the_rows_end_is_launched_alone(model):
    """A warm admission starts at its cached prefix: 15 of a row's 16
    blocks in, a slot of two blocks would reach past the row's end,
    where the program's slices clamp -- that piece goes out alone, and
    the stream is the engine's whose budget is one block."""
    from singa_tpu.serve import PrefixCacheConfig

    base = np.random.default_rng(5).integers(0, 256, 15 * B) \
        .astype(np.int32)
    tail = np.random.default_rng(6).integers(0, 256, 5).astype(np.int32)

    def serve(budget):
        eng = _engine(model, budget=budget,
                      prefix_cache=PrefixCacheConfig(block_size=B))
        try:
            assert eng.max_len == 16 * B
            first = eng.submit(GenerationRequest(
                base, max_new_tokens=2, temperature=0.0))
            eng.run_until_complete(max_steps=200)
            merged = eng._c_merged_launches.value
            # admitted in one pass: a warm one (off = 15 blocks, one
            # block to go) and a one-block prompt -- two that a pair's
            # slots would hold, were the row longer
            hs = [eng.submit(GenerationRequest(
                np.concatenate([base, tail]), max_new_tokens=2,
                temperature=0.0)), eng.submit(_req(B - 1, 2, seed=7))]
            spans = _step(eng)
            total = _one(spans, "serve.schedule")[3]
            eng.run_until_complete(max_steps=200)
            return ([list(map(int, h.result().tokens))
                     for h in [first] + hs], total,
                    eng._c_merged_launches.value - merged)
        finally:
            eng.close()

    want = serve(B)
    got = serve(4 * B)
    assert got[0] == want[0]
    assert (got[1]["chunks"], got[1]["launches"],
            got[1]["segments"]) == (2, 2, 2)
    assert got[2] == 0


# -- the same streams as the parent's order ---------------------------------

def _parent_order(eng):
    """Drive ``eng`` in the order of a step before the early pass: inside
    ``_decode_once`` nothing is launched, so the whole budget reaches the
    schedule pass, which launches there."""
    launch, once, inside = eng._launch_inflight, eng._decode_once, []

    def decode():
        inside.append(1)
        try:
            return once()
        finally:
            inside.pop()
    eng._decode_once = decode
    eng._launch_inflight = lambda left: left if inside else launch(left)


def _corner(eng, vocab, temperature=0.0):
    """Two slots; a lane that retires in the step in which a prefill's
    last block lands and a queued request takes the freed slot.
    Returns each request's (tokens, admitted step, finished step) and
    the widths the decode steps ran at."""
    hs = [eng.submit(_req(5, 4, seed=11, vocab=vocab,
                          temperature=temperature))]
    eng.step()
    hs += [eng.submit(_req(n, n_new, seed=s, vocab=vocab,
                           temperature=temperature))
           for n, n_new, s in ((5 * B - 1, 5, 12), (2 * B - 4, 3, 13),
                               (B + 3, 6, 14))]
    with _recorded() as spans:
        eng.run_until_complete(max_steps=400)
        widths = {s[3]["width"] for s in spans()
                  if s[0] == "serve.decode"}
    res = [h.result() for h in hs]
    return ([(list(map(int, r.tokens)), r.admitted_step,
              r.finished_step) for r in res], widths)


@pytest.mark.parametrize("name, dispatch", [
    (name, d) for name in FAMILIES for d in ("compacted", "sampled")]
    # (draft_model= is the GPT-2 family's)
    + [("gpt2", "speculative")])
def test_streams_are_those_of_the_parents_order(family_models, name,
                                                dispatch):
    m, vocab, kw = family_models(name)
    if dispatch == "speculative":
        kw = dict(kw, draft_model=_gpt2(1), spec_k=3)
    temp = 0.8 if dispatch == "sampled" else 0.0
    want = got = None
    for parent in (True, False):
        eng = _engine(m, budget=2 * B, max_slots=2, **kw)
        try:
            if parent:
                _parent_order(eng)
            out, widths = _corner(eng, vocab, temp)
            early = eng._c_early_launches.value
            assert eng.paged_arena.blocks_used == 0
        finally:
            eng.close()
        if parent:
            want, early_parent = out, early
        else:
            got = out
    assert early_parent == 0 and early >= 3
    # the corner: request 0 retires in the step in which request 1's
    # admission completes and request 2 is admitted
    assert want[0][2] == want[2][1]
    assert got == want
    if dispatch != "speculative":
        assert widths == {1, 2}     # compacted and full width


def test_the_early_pass_compiles_no_program(model):
    """Every program of a served run is one the parent's order ran."""
    sizes = []
    for parent in (True, False):
        eng = _engine(model, budget=2 * B, max_slots=2, max_len=104)
        try:
            if parent:
                _parent_order(eng)
            _corner(eng, 256)
            sizes.append(jit_cache_size())
        finally:
            eng.close()
    assert sizes[0] is not None and sizes[1] == sizes[0]


# -- faults and close -------------------------------------------------------

def test_a_fault_in_the_early_pass_fails_typed_and_frees_the_blocks(
        model):
    eng = _engine(model)
    chat, long = _chat_then_long(eng, long_len=64)
    eng.step()                      # one launch, late
    # the next serve.prefill_chunk check is the early pass's
    faults.inject("serve.prefill_chunk", FailAfterN(0, times=1))
    try:
        with _recorded() as spans:
            with pytest.raises(EngineFailedError):
                eng.step()
            names = [s[0] for s in spans()]
    finally:
        faults.clear()
    # it fired behind the decode dispatch, before its tokens were read
    assert "serve.dispatch.paged_decode_step" in names
    assert "serve.launch" in names and "serve.sync" not in names
    for h, started in ((chat, True), (long, False)):
        with pytest.raises(EngineFailedError) as ei:
            h.result()
        assert ei.value.started is started
    assert eng.paged_arena.blocks_used == 0, "mid-prefill leak"
    assert not eng._prefilling and eng.live_slots == 0
    eng.close(force=True)


def test_a_fault_in_the_admission_behind_a_landed_prefill_fails_typed(
        model):
    """The pass has dispatched the landed prompt's completion and not yet
    fetched its token when the follower's first launch raises: the landed
    request has streamed nothing, so it is rejected ``started=False``
    with the follower, and both prompts' blocks come back."""
    eng = _engine(model, budget=2 * B)
    chat = eng.submit(_req(6, 60, seed=1))
    eng.step()
    first = eng.submit(_req(3 * B - 2, 2, seed=2))
    second = eng.submit(_req(2 * B - 3, 2, seed=3))
    eng.step()
    # the step's checks: the head's last block (early), then the
    # follower's first
    faults.inject("serve.prefill_chunk", FailAfterN(1, times=1))
    try:
        with pytest.raises(EngineFailedError):
            eng.step()
    finally:
        faults.clear()
    for h, started in ((chat, True), (first, False), (second, False)):
        with pytest.raises(EngineFailedError) as ei:
            h.result()
        assert ei.value.started is started
    assert eng.paged_arena.blocks_used == 0, "mid-prefill leak"
    assert not eng._prefilling and eng.live_slots == 0
    eng.close(force=True)


def test_a_fault_behind_a_pairs_dispatch_frees_both_requests_blocks(model):
    """The pair has been dispatched -- both rows donated and rebound --
    when its bookkeeping raises: the engine fails typed, both requests
    have streamed nothing and are rejected ``started=False``, and both
    prompts' blocks come back."""
    eng = _engine(model, budget=4 * B)
    chat, a, b, c = _three_in_a_pass(eng)
    counter = eng._c_merged_launches

    class Boom:
        value = property(lambda self: counter.value)

        def inc(self):
            counter.inc()
            raise RuntimeError("behind the dispatch")
    eng._c_merged_launches = Boom()
    try:
        with pytest.raises(EngineFailedError):
            eng.step()
    finally:
        eng._c_merged_launches = counter
    assert eng._c_merged_launches.value == 1 and eng._c_launches.value == 2
    for h, started in ((chat, True), (a, False), (b, False), (c, False)):
        with pytest.raises(EngineFailedError) as ei:
            h.result()
        assert ei.value.started is started
    assert eng.paged_arena.blocks_used == 0, "mid-prefill leak"
    assert not eng._prefilling and eng.live_slots == 0
    eng.close(force=True)


def test_a_supervisor_rebuilds_behind_a_fault_in_the_early_pass(model):
    want = np.asarray(model.generate(
        np.arange(64, dtype=np.int32) % 256, max_new_tokens=3,
        temperature=0))
    sup = EngineSupervisor(model, max_slots=2, restart_budget=2,
                           paged=PagedConfig(block_size=B, num_blocks=32,
                                             prefill_token_budget=B))
    try:
        chat = sup.submit(GenerationRequest(
            np.arange(6, dtype=np.int32), max_new_tokens=30,
            temperature=0.0))
        sup.step()
        h = sup.submit(GenerationRequest(
            np.arange(64, dtype=np.int32) % 256, max_new_tokens=3,
            temperature=0.0))
        sup.step()
        first = sup.engine
        assert first._prefilling and first.live_slots == 1
        pol = faults.inject("serve.prefill_chunk", FailAfterN(1, times=1))
        try:
            sup.run_until_complete(max_steps=2000)
        finally:
            faults.clear()
        assert pol.fired == 1 and sup.engine is not first
        assert first._c_early_launches.value == 1    # then the fault
        assert np.array_equal(h.result().tokens, want)
        with pytest.raises(EngineFailedError) as ei:
            chat.result()           # it had streamed: never re-run
        assert ei.value.started is True
        assert sup.engine.paged_arena.blocks_used == 0
    finally:
        sup.close()


def test_close_mid_prefill_leaves_no_series_of_the_budgets_counters(
        model):
    eng = _engine(model)
    _chat_then_long(eng)
    eng.step()
    eng.step()
    assert eng._prefilling and eng._c_early_launches.value == 1
    label = str(eng.stats.engine_label)

    def series():
        return sorted(
            m.name for m in registry().metrics()
            if m.name.startswith("serve.prefill.")
            and dict(m.labels).get("engine") == label)
    assert "serve.prefill.early_launches" in series()
    assert "serve.prefill.launches" in series()
    assert "serve.prefill.merged_launches" in series()
    eng.close(force=True)
    assert series() == []
