"""Paged KV arena (serve/paged.py + the engine's ``paged=`` mode):
byte parity against the slot-arena oracle (cold / warm / int8 / GQA /
speculative / preempt-resume), block accounting and leak checks,
priority preemption ordering, config validation, and the
observability surface (``serve.paged.*`` metrics, health section,
request-ledger ``preempted`` phase).

Everything deterministic on CPU: parity is np.array_equal on token
streams, and the slot-arena engine (itself parity-pinned against
single-prompt ``generate`` in tests/test_serve.py) is the oracle, so
preemption/swap noise cannot hide behind tolerance."""

import launch_widths as lw
import numpy as np
import pytest

from singa_tpu import tensor
from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from singa_tpu.observe import health_report
from singa_tpu.observe import requests as reqtrace
from singa_tpu.observe.registry import registry
from singa_tpu.resilience import FailAfterN, faults
from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                             GenerationRequest, PagedConfig,
                             PrefixCacheConfig, PriorityScheduler)


def _build(cfg):
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.fixture(scope="module")
def model():
    return _build(GPT2Config.tiny(dropout=0.0))


@pytest.fixture(scope="module")
def draft():
    return _build(GPT2Config.tiny(dropout=0.0, n_layer=1))


def _workload(seed, n, p_lo=3, p_hi=14, n_lo=2, n_hi=9, sampled=False):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        out.append(dict(
            prompt=rng.randint(0, 256, rng.randint(p_lo, p_hi))
            .astype(np.int32),
            n_new=int(rng.randint(n_lo, n_hi)),
            temperature=(float(rng.choice([0.0, 0.9]))
                         if sampled else 0.0),
            seed=int(rng.randint(0, 1000))))
    return out


def _run(m, work, max_slots=2, max_steps=4000, **kw):
    eng = m.serve(max_slots=max_slots, **kw)
    hs = [eng.submit(GenerationRequest(
        w["prompt"], max_new_tokens=w["n_new"],
        temperature=w["temperature"], seed=w["seed"]))
        for w in work]
    eng.run_until_complete(max_steps=max_steps)
    outs = [h.result().tokens for h in hs]
    snap = eng.stats.snapshot()
    eng.close()
    return outs, snap


@pytest.mark.parametrize("seed", [0, 20])
def test_cold_parity_and_clean_accounting(model, seed):
    """Cold paged streams (greedy AND seeded sampling mixed in one
    pool) are byte-identical to the slot engine's, and a drained
    engine returns every block.  Online softmax reorders the float
    reduction, so this (plus the logits oracle below) is the parity
    pin; bitwise logit equality is impossible by construction
    (docs/SERVING.md "Paged KV and preemption")."""
    work = _workload(seed, 8, sampled=True)
    base, _ = _run(model, work)
    outs, snap = _run(model, work,
                      paged=PagedConfig(block_size=8, num_blocks=32))
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))
    assert snap["paged"]["blocks_used"] == 0
    assert snap["paged"]["preemptions"] == 0


def test_preempt_resume_byte_parity(model):
    """An over-committed pool forces mid-decode swaps; the resumed
    streams (byte-copied KV + restored key chain) equal the
    uninterrupted slot-engine run exactly — greedy and sampled."""
    work = _workload(1, 6, n_lo=12, n_hi=30, p_lo=4, p_hi=20,
                     sampled=True)
    base, _ = _run(model, work, max_slots=4)
    outs, snap = _run(model, work, max_slots=4,
                      paged=PagedConfig(block_size=8, num_blocks=10))
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))
    pg = snap["paged"]
    assert pg["preemptions"] > 0 and pg["swap_in"] > 0
    assert pg["blocks_used"] == 0, "leaked blocks after drain"


def test_gqa_paged_parity():
    """GQA models (narrow H_kv cache leaves) page identically."""
    m = _build(GPT2Config.tiny(dropout=0.0, n_kv_head=2))
    work = _workload(2, 5, n_lo=8, n_hi=20, p_lo=4, p_hi=16)
    base, _ = _run(m, work, max_slots=3)
    outs, snap = _run(m, work, max_slots=3,
                      paged=PagedConfig(block_size=8, num_blocks=8))
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))
    assert snap["paged"]["preemptions"] > 0  # pool was over-committed


@pytest.mark.parametrize("seed,n,n_hi,oracle", [
    (3, 5, 12, "plain"), (21, 4, 10, "speculative")])
def test_spec_paged_greedy_parity(model, draft, seed, n, n_hi, oracle):
    """Speculative decoding over the paged target arena: greedy
    streams equal the slot engine's, plain and speculative (the
    chunk-query accumulator against the same draft proposal chain;
    verify chunks scatter one or two blocks back per slot per step)."""
    work = _workload(seed, n, n_lo=4, n_hi=n_hi, p_lo=4, p_hi=12)
    spec = (dict(draft_model=draft, spec_k=3)
            if oracle == "speculative" else {})
    base, _ = _run(model, work, max_slots=3, **spec)
    outs, snap = _run(model, work, max_slots=3, draft_model=draft,
                      spec_k=3,
                      paged=PagedConfig(block_size=8, num_blocks=32))
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))
    assert snap["spec"]["chunks"] > 0


def test_warm_prefix_zero_copy(model):
    """The radix cache rides the SAME pool: warm admissions share
    matched blocks by reference, donation adopts private blocks, and
    after the drain every used block is a cached block (nothing
    leaked, nothing copied)."""
    rng = np.random.RandomState(4)
    system = rng.randint(0, 256, 24).astype(np.int32)
    work = [dict(prompt=np.concatenate(
        [system, rng.randint(0, 256, rng.randint(3, 10))
         .astype(np.int32)]),
        n_new=int(rng.randint(3, 8)), temperature=0.0, seed=0)
        for _ in range(8)]
    base, _ = _run(model, work)
    outs, snap = _run(model, work,
                      paged=PagedConfig(block_size=8, num_blocks=48),
                      prefix_cache=PrefixCacheConfig(block_size=8))
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))
    assert snap["prefix"]["hit_tokens"] > 0
    assert snap["paged"]["blocks_used"] == snap["prefix"]["cached_blocks"]
    assert snap["prefix"]["donate_skipped"] == 0  # adoption never skips


def test_warm_admission_never_evicts_its_own_match(model):
    """Regression (review-confirmed): under pool pressure a warm
    admission's block allocation runs the eviction path, which spares
    only REFERENCED nodes — the matched-but-not-yet-pinned path could
    be evicted mid-allocation and its block handed back to the SAME
    request, aliasing one pool block in two table lanes (silent KV
    corruption).  The fix acquires the match before allocating; this
    pins byte parity on the exact repro: serve A, then B (pressure),
    then A again warm against a pool with nothing else to evict."""
    rng = np.random.RandomState(13)
    A = rng.randint(0, 256, 12).astype(np.int32)
    Bp = rng.randint(0, 256, 12).astype(np.int32)
    oracle = {p.tobytes(): np.asarray(model.generate(
        p, max_new_tokens=6, temperature=0.0)) for p in (A, Bp)}
    eng = model.serve(max_slots=2,
                      paged=PagedConfig(block_size=4, num_blocks=6),
                      prefix_cache=PrefixCacheConfig(block_size=4))
    for p in (A, Bp, A):
        h = eng.submit(GenerationRequest(p, max_new_tokens=6,
                                         temperature=0.0))
        eng.run_until_complete(max_steps=1000)
        np.testing.assert_array_equal(h.result().tokens,
                                      oracle[p.tobytes()])
    eng.close()


@pytest.mark.slow  # int8 variant: serve-gate cache_int8 parity is the fast rep
def test_int8_paged_parity_vs_offline_oracle(model):
    """int8 pools ((values, scales) pytree leaves) page byte-exactly:
    engine streams equal the offline int8 generate oracle."""
    work = _workload(5, 5, n_lo=3, n_hi=8)
    from singa_tpu.models import gpt2_decode
    base = [np.asarray(gpt2_decode.generate(
        model, w["prompt"], max_new_tokens=w["n_new"], temperature=0,
        cache_dtype="int8")) for w in work]
    outs, snap = _run(model, work, cache_dtype="int8",
                      paged=PagedConfig(block_size=8, num_blocks=32))
    assert all(np.array_equal(a, b) for a, b in zip(outs, base))


def test_int8_prefix_cache_lifted(model):
    """The old int8 + prefix-cache refusal is LIFTED: quantized
    engines get warm admissions through the chunked canonical form —
    warm and cold streams are byte-identical to each other (two fresh
    engines agree exactly), and the paged and slot-arena versions
    agree too."""
    rng = np.random.RandomState(6)
    system = rng.randint(0, 256, 24).astype(np.int32)
    work = [dict(prompt=np.concatenate(
        [system, rng.randint(0, 256, rng.randint(3, 10))
         .astype(np.int32)]),
        n_new=int(rng.randint(3, 8)), temperature=0.0, seed=0)
        for _ in range(6)]
    kw = dict(cache_dtype="int8",
              paged=PagedConfig(block_size=8, num_blocks=64),
              prefix_cache=PrefixCacheConfig(block_size=8))
    outs_a, snap_a = _run(model, work, **kw)   # cold tree
    outs_b, _ = _run(model, work, **kw)        # fresh engine, again
    assert all(np.array_equal(a, b) for a, b in zip(outs_a, outs_b))
    assert snap_a["prefix"]["hit_tokens"] > 0
    outs_c, snap_c = _run(
        model, work, cache_dtype="int8",
        prefix_cache=PrefixCacheConfig(block_size=8, num_blocks=64))
    assert all(np.array_equal(a, b) for a, b in zip(outs_a, outs_c))
    assert snap_c["prefix"]["hit_tokens"] > 0


def test_session_multi_turn_on_paged(model):
    """pin_session on a paged engine: the generated region is
    re-canonicalized in place, the full sequence pinned, and turn 2
    is a warm hit with oracle parity."""
    rng = np.random.RandomState(7)
    eng = model.serve(max_slots=2,
                      paged=PagedConfig(block_size=8, num_blocks=64),
                      prefix_cache=PrefixCacheConfig(block_size=8))
    p = rng.randint(0, 256, 20).astype(np.int32)
    h = eng.submit(GenerationRequest(p, max_new_tokens=6,
                                     pin_session=True, temperature=0.0))
    eng.run_until_complete(max_steps=1000)
    sess = h.result().session
    assert sess is not None and sess.pinned_blocks > 0
    extra = rng.randint(0, 256, 5).astype(np.int32)
    req2 = sess.request(extra, max_new_tokens=6, temperature=0.0)
    hits0 = eng.prefix_cache.snapshot()["hit_tokens"]
    h2 = eng.submit(req2)
    eng.run_until_complete(max_steps=1000)
    want = np.asarray(model.generate(req2.prompt_ids, max_new_tokens=6,
                                     temperature=0.0))
    np.testing.assert_array_equal(h2.result().tokens, want)
    assert eng.prefix_cache.snapshot()["hit_tokens"] > hits0
    sess.release()
    eng.close()


def test_priority_preemption_ordering(model):
    """A high-priority arrival that does not fit in blocks PREEMPTS
    the strictly-lower-priority live request (swap to host) instead of
    waiting behind it: the urgent request finishes first, the victim
    resumes byte-identically, and the ledger attributes the victim's
    pause to the ``preempted`` phase with exact sums."""
    rng = np.random.RandomState(8)
    p_lo = rng.randint(0, 256, 10).astype(np.int32)
    p_hi = rng.randint(0, 256, 12).astype(np.int32)
    base_lo = np.asarray(model.generate(p_lo, max_new_tokens=16,
                                        temperature=0.0))
    base_hi = np.asarray(model.generate(p_hi, max_new_tokens=8,
                                        temperature=0.0))
    led = reqtrace.enable(capacity=64)
    try:
        eng = model.serve(max_slots=2, scheduler="priority",
                          paged=PagedConfig(block_size=8, num_blocks=4))
        h_lo = eng.submit(GenerationRequest(
            p_lo, max_new_tokens=16, temperature=0.0, priority=0))
        for _ in range(8):
            eng.step()
        h_hi = eng.submit(GenerationRequest(
            p_hi, max_new_tokens=8, temperature=0.0, priority=5))
        eng.run_until_complete(max_steps=2000)
        np.testing.assert_array_equal(h_lo.result().tokens, base_lo)
        np.testing.assert_array_equal(h_hi.result().tokens, base_hi)
        assert eng.stats.snapshot()["paged"]["preemptions"] >= 1
        # urgency won: the high-priority request retired first
        assert (h_hi.result().finished_step
                <= h_lo.result().finished_step)
        e = led.entry(h_lo.request.request_id)
        ph = e["phases"]
        assert ph["preempted"] > 0
        total = e["t_retire"] - e["t_submit"]
        assert sum(ph.values()) == pytest.approx(total, abs=1e-9)
        assert "preempted" in led.why_slow()["tpot_p99_attribution"]
        eng.close()
    finally:
        reqtrace.disable()


def test_priority_scheduler_queue_order():
    """Host-only: PriorityScheduler pops higher priority first, FIFO
    within a class, and requeue_front lands at the head of the
    request's own class."""
    sched = PriorityScheduler()
    reqs = [GenerationRequest(np.ones(4, np.int32), priority=p)
            for p in (0, 5, 0, 5, 2)]
    for r in reqs:
        sched.enqueue(r)
    admit, _ = sched.schedule(5, now=0.0)
    assert [r.priority for r in admit] == [5, 5, 2, 0, 0]
    # FIFO within the class
    assert admit[0] is reqs[1] and admit[1] is reqs[3]
    # requeue_front: ahead of equals, behind strictly higher
    for r in admit:
        sched.enqueue(r)
    sched.requeue_front(reqs[4])            # priority 2
    admit2, _ = sched.schedule(5, now=0.0)
    assert admit2[2] is reqs[4]


def test_block_accounting_under_churn(model):
    """Fragmentation-free allocation: across admit/preempt/retire
    churn the accounting invariant ``free + used == num_blocks`` holds
    at every step, and the drained engine holds exactly the cached
    blocks."""
    work = _workload(9, 12, n_lo=6, n_hi=24, p_lo=3, p_hi=20)
    eng = model.serve(max_slots=4,
                      paged=PagedConfig(block_size=8, num_blocks=12))
    arena = eng.paged_arena
    pending = list(work)
    hs = []
    while pending or eng.pending:
        if pending:
            w = pending.pop(0)
            hs.append(eng.submit(GenerationRequest(
                w["prompt"], max_new_tokens=w["n_new"],
                temperature=0.0)))
        eng.step()
        assert arena.blocks_free + arena.blocks_used \
            == arena.num_blocks
        held = sum(len(s.blocks) - s.n_shared
                   for s in eng._slots if s is not None)
        assert arena.blocks_used == held  # no cache: used == slot-held
    assert all(h.done() for h in hs)
    assert arena.blocks_used == 0
    assert eng.stats.snapshot()["paged"]["preemptions"] > 0
    eng.close()


def test_fail_rejects_swapped_started_true(model):
    """Engine failure with swapped-out work: swapped requests are
    STARTED (tokens streamed) — rejected typed started=True, never
    requeue-safe, and live_request_ids includes them (the fleet's
    failover verdict)."""
    eng = model.serve(max_slots=2,
                      paged=PagedConfig(block_size=8, num_blocks=6))
    rng = np.random.RandomState(10)
    hs = [eng.submit(GenerationRequest(
        rng.randint(0, 256, 10).astype(np.int32), max_new_tokens=20,
        temperature=0.0)) for _ in range(4)]
    steps = 0
    while not eng._swapped and steps < 60:
        eng.step()
        steps += 1
    assert eng._swapped, "pool never over-committed"
    swapped_ids = {sw.request.request_id for sw in eng._swapped}
    assert swapped_ids <= eng.live_request_ids
    faults.inject("serve.decode_step", FailAfterN(0, times=1))
    try:
        with pytest.raises(EngineFailedError):
            while eng.pending:
                eng.step()
    finally:
        faults.clear()
    for h in hs:
        assert h.done()
        if h.request.request_id in swapped_ids:
            with pytest.raises(EngineFailedError) as ei:
                h.result()
            assert ei.value.started is True
    eng.close(force=True)


def test_supervisor_restart_paged_parity(model):
    """A decode fault against a paged engine: supervisor rebuild gets
    a FRESH arena, never-started requests requeue with byte parity."""
    work = _workload(11, 8, n_lo=3, n_hi=8)
    base, _ = _run(model, work)
    sup = EngineSupervisor(model, max_slots=2, restart_budget=2,
                           paged=PagedConfig(block_size=8,
                                             num_blocks=32))
    arena0 = sup.engine.paged_arena
    hs = [sup.submit(GenerationRequest(
        w["prompt"], max_new_tokens=w["n_new"], temperature=0.0,
        seed=w["seed"])) for w in work]
    pol = faults.inject("serve.decode_step", FailAfterN(3, times=1))
    try:
        sup.run_until_complete(max_steps=4000)
    finally:
        faults.clear()
    assert pol.fired == 1
    assert sup.engine.paged_arena is not arena0
    assert sup.engine.paged_arena.blocks_used == 0
    done = typed = 0
    for w, h, want in zip(work, hs, base):
        try:
            got = h.result().tokens
            assert np.array_equal(
                got, np.asarray(model.generate(
                    w["prompt"], max_new_tokens=w["n_new"],
                    temperature=0)))
            done += 1
        except EngineFailedError:
            typed += 1
    assert done + typed == len(work) and done > 0
    sup.close()


def test_config_validation_typed_errors(model, draft):
    """Every impossible paged configuration fails typed at
    construction or submit, never inside a jitted dispatch."""
    with pytest.raises(ValueError, match="block_size"):
        PagedConfig(block_size=0)
    with pytest.raises(ValueError, match="num_blocks"):
        PagedConfig(num_blocks=0)
    with pytest.raises(ValueError, match="multiple"):
        model.serve(paged=PagedConfig(block_size=7))  # 128 % 7 != 0
    with pytest.raises(ValueError, match="paged must be"):
        model.serve(paged="yes")
    with pytest.raises(ValueError, match="spec_k"):
        model.serve(draft_model=draft, spec_k=16,
                    paged=PagedConfig(block_size=8))
    with pytest.raises(ValueError, match="granularity|block_size"):
        model.serve(paged=PagedConfig(block_size=8),
                    prefix_cache=PrefixCacheConfig(block_size=16))
    with pytest.raises(ValueError, match="unknown scheduler"):
        model.serve(scheduler="lifo")
    eng = model.serve(max_slots=1,
                      paged=PagedConfig(block_size=8, num_blocks=4))
    with pytest.raises(ValueError, match="KV blocks"):
        # needs (20 + 40 - 1)//8 + 1 = 8 blocks > 4: could never fit
        eng.submit(GenerationRequest(np.zeros(20, np.int32),
                                     max_new_tokens=40))
    eng.close()


@pytest.mark.parametrize("gone", [dict(kernel="gather"),
                                  dict(admit_per_step=2)])
def test_removed_paged_options_are_unknown_fields(model, gone):
    """``PagedConfig`` is the pool's geometry and one budget; the
    options that went are refused as any unknown field is, in both
    forms ``paged=`` takes."""
    import dataclasses

    assert [f.name for f in dataclasses.fields(PagedConfig)] == [
        "block_size", "num_blocks", "prefill_token_budget"]
    with pytest.raises(TypeError):
        PagedConfig(block_size=8, **gone)
    with pytest.raises(TypeError):
        model.serve(paged=dict(block_size=8, **gone))


def test_kv_memory_and_below_import_nothing_from_the_engine():
    """The arrows point one way, engine -> paged -> models/ops: no
    module below the engine imports it, at any depth of nesting (a
    lazy import inside a function body is still an import)."""
    import ast
    import glob
    import os

    pkg = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "singa_tpu")
    files = [os.path.join(pkg, "serve", "paged.py"),
             os.path.join(pkg, "models", "gpt2_decode.py"),
             os.path.join(pkg, "models", "served.py"),
             *sorted(glob.glob(os.path.join(pkg, "ops", "*.py")))]
    assert len(files) > 5
    for path in files:
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [
                    f"{node.module or ''}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            else:
                continue
            hit = [n for n in names if n.split(".")[-1] == "engine"]
            assert not hit, (os.path.relpath(path, pkg), node.lineno, hit)


def test_kernel_logits_allclose_row_math_oracle(model):
    """Unit-level oracle for the online-softmax accumulator: one
    decode step through ``decode_step_paged`` against a random pool
    (a live lane and a dead one) vs the row-math ``decode_step`` on the
    SAME KV materialized into a row — logits allclose (reduction order
    is the only difference), every pool byte but the written row and
    the trash block BYTE-equal to the pool before (the whole-block
    read-modify-write round-trips bytes; the dead lane writes only the
    trash block), and the written K rows equal to the row path's."""
    from singa_tpu.models import gpt2_decode as gd
    from singa_tpu.ops.paged_attention import row_to_blocks
    import jax.numpy as jnp

    params = gd.extract_params(model)
    cfg = model.cfg
    L, H = cfg.n_layer, cfg.n_kv_head
    D = cfg.n_embd // cfg.n_head
    B, N = 8, 6
    rng = np.random.RandomState(0)
    pool_k = rng.randn(L, N + 1, B, H * D).astype(np.float32)
    pool_v = rng.randn(L, N + 1, B, H * D).astype(np.float32)
    pos, tok = 13, 7              # mid-block: block 1, offset 5
    tbl = np.full((2, 4), N, np.int32)
    tbl[:, :2] = [3, 1]           # non-contiguous blocks, trash-padded
    x = params["wte"][tok] + params["wpe"][pos]
    n_blk = (pos + B - 1) // B
    eps = float(cfg.layer_norm_eps)
    logits_k, pk2, pv2 = gd.decode_step_paged(
        params, jnp.stack([x, x]), jnp.asarray(pool_k),
        jnp.asarray(pool_v), jnp.asarray(tbl),
        jnp.asarray([pos, 0], jnp.int32), jnp.asarray([True, False]),
        jnp.int32(n_blk), cfg.n_head, eps, block=B, trash=N)
    # oracle: the same KV materialized into a (max_len) row
    W = tbl.shape[1] * B
    row_k = np.zeros((L, 1, H, W, D), np.float32)
    row_v = np.zeros((L, 1, H, W, D), np.float32)
    for j, b in enumerate(tbl[0, :2]):
        blk = lambda p: p[:, b].reshape(L, B, H, D).transpose(0, 2, 1, 3)
        row_k[:, 0, :, j * B:(j + 1) * B] = blk(pool_k)
        row_v[:, 0, :, j * B:(j + 1) * B] = blk(pool_v)
    # the layout helper agrees with the hand-built row
    np.testing.assert_array_equal(
        np.asarray(row_to_blocks(jnp.asarray(row_k), B))[:, :2],
        pool_k[:, tbl[0, :2]])
    logits_r, kc2, vc2 = gd.decode_step(
        params, x[None, None, :], jnp.asarray(row_k),
        jnp.asarray(row_v), jnp.int32(pos), cfg.n_head, eps)
    np.testing.assert_allclose(np.asarray(logits_k)[0],
                               np.asarray(logits_r)[0],
                               rtol=2e-5, atol=2e-5)
    # only row pos % B of block tbl[1] (and the trash block) changed
    pk2 = np.asarray(pk2)
    off = pos % B
    same = np.ones(pool_k.shape[1:3], bool)
    same[1, off] = same[N] = False
    np.testing.assert_array_equal(pk2[:, same], pool_k[:, same])
    assert not np.array_equal(pk2[:, 1, off], pool_k[:, 1, off])
    # the written K rows: layer 0's from the same x through the same
    # projection (each path compiles its own layer body, so to rounding)
    kb = pk2[:, 1, off].reshape(L, H, D)
    np.testing.assert_allclose(
        kb[0], np.asarray(kc2)[0, 0][:, pos], rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(
        kb, np.asarray(kc2)[:, 0][:, :, pos],
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_kv,d,quant", [(20, 64, False), (4, 128, False),
                                          (1, 64, False), (4, 128, True)])
def test_pool_layout_helpers(n_kv, d, quant):
    """ops/paged_attention.py alone: a cache row scattered into a pool
    through a block table with trash entries and gathered back is the
    row again (``row_to_blocks`` / ``blocks_to_row``, values and scales
    leaves), ``write_rows`` changes one row of one block and nothing
    else but the trash block, and ``paged_attn`` over the token-a-row
    pool equals plain softmax attention over the gathered row."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.models.gpt2_decode import _quantize_kv
    from singa_tpu.ops import paged_attention as pa

    L, B, N, nb = 2, 8, 9, 4
    rng = np.random.RandomState(n_kv + d)

    def cache_row():
        r = jnp.asarray(rng.randn(L, 1, n_kv, nb * B, d), jnp.float32)
        return _quantize_kv(r) if quant else r

    row_k, row_v = cache_row(), cache_row()
    pools = [jax.tree.map(lambda z: z + 7, pa.pool_zeros(
        L, N + 1, B, n_kv, d, jnp.float32, quant)) for _ in "kv"]
    assert jax.tree.leaves(pools[0])[0].shape == (L, N + 1, B, n_kv * d)
    # blocks 5, 2, 7 hold the row's first three; the fourth lands in
    # the trash block (N)
    idx = jnp.asarray([5, 2, 7, N], jnp.int32)
    pool_k, pool_v = (jax.tree.map(
        lambda p, r: p.at[:, idx].set(pa.row_to_blocks(r, B)), pool, row)
        for pool, row in zip(pools, (row_k, row_v)))
    back = jax.tree.map(
        lambda p, hd: pa.blocks_to_row(jnp.take(p, idx[:3], axis=1), hd),
        pool_k, pa.leaf_dims(pool_k, d))
    jax.tree.map(lambda b, r: np.testing.assert_array_equal(
        np.asarray(b), np.asarray(r)[:, 0, :, :3 * B]), back, row_k)
    untouched = [b for b in range(N) if b not in (5, 2, 7)]
    for leaf in jax.tree.leaves(pool_k):
        assert (np.asarray(leaf)[:, untouched] == 7).all()

    # one new row a lane: lane 0 live at position 13 (block 2, row 5),
    # lane 1 dead
    tables = jnp.asarray([[5, 2, 7, N]] * 2, jnp.int32)
    pos, live = jnp.asarray([13, 0]), jnp.asarray([True, False])
    new = jax.tree.map(
        lambda p: jnp.asarray(rng.randn(2, 1, p.shape[-1]), p.dtype),
        pool_k)
    wrote = jax.tree.map(
        lambda p, r: pa.write_rows(p, 1, r, tables, pos, live, B, N),
        pool_k, new)
    for w, p, r in zip(*map(jax.tree.leaves, (wrote, pool_k, new))):
        w, p = np.asarray(w), np.asarray(p)
        np.testing.assert_array_equal(w[1, 2, 5], np.asarray(r)[0, 0])
        same = np.ones(p.shape[:3], bool)
        same[1, 2, 5] = same[:, N] = False
        np.testing.assert_array_equal(w[same], p[same])

    # attention of 2 queries a K/V head at position 21 (3 keys of the
    # third block live) plus one current key
    g, p_limit = 2, 21
    q = jnp.asarray(rng.randn(n_kv, g, 1, d), jnp.float32)
    cur = jax.tree.map(lambda p: p[0, 0, :1], pool_k)     # any one row
    out = pa.paged_attn(q, pool_k, pool_v, 1, tables[0], p_limit, 3, B,
                        N, cur, cur, jnp.ones((1, 1), bool), d ** -0.5)

    def dense(row):                     # (H, W, D) float32 of layer 1
        if quant:
            return np.asarray(row[0])[1, 0] * np.asarray(row[1])[1, 0][
                ..., None]
        return np.asarray(row)[1, 0]

    def cur_dense():
        if quant:
            return (np.asarray(cur[0], np.float32).reshape(1, n_kv, d)
                    * np.asarray(cur[1])[..., None]).transpose(1, 0, 2)
        return np.asarray(cur).reshape(1, n_kv, d).transpose(1, 0, 2)

    k = np.concatenate([dense(row_k)[:, :p_limit], cur_dense()], 1)
    v = np.concatenate([dense(row_v)[:, :p_limit], cur_dense()], 1)
    sc = np.einsum("kgqd,ktd->kgqt", np.asarray(q), k) * d ** -0.5
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.einsum("kgqt,ktd->kgqd", pr, v),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
def test_arena_copies_are_byte_exact(quant):
    """The arena's device copies over the token-a-row pool, with no
    engine around them: swap-out -> swap-in into other blocks, image
    export -> import into a second arena, the batched admission scatter
    and the copy-on-write block copy all move exactly the rows' bytes
    (values and scales leaves), leave every other block alone, and an
    image of another head geometry with the same row width is refused
    typed."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.serve.kvimage import KVImageError
    from singa_tpu.serve.paged import PagedKVArena

    L, H, D, B, N, W = 2, 4, 16, 8, 12, 32
    rng = np.random.RandomState(int(quant))

    def arena(label, n_kv=H, d=D):
        a = PagedKVArena(PagedConfig(block_size=B, num_blocks=N), L, n_kv,
                         d, jnp.float32, W, quant=quant,
                         engine_label=label)
        fill = lambda z: jnp.asarray(
            rng.randint(-100, 100, z.shape).astype(z.dtype))
        a.pool_k = jax.tree.map(fill, a.pool_k)
        a.pool_v = jax.tree.map(fill, a.pool_v)
        return a

    def blocks(a, ids):
        return [np.asarray(leaf)[:, ids] for leaf in
                jax.tree.leaves((a.pool_k, a.pool_v))]

    def same(xs, ys):
        for x, y in zip(xs, ys):
            np.testing.assert_array_equal(x, y)

    a, b = arena("copies-a"), arena("copies-b")
    try:
        src, dst = [7, 2, 9], [4, 11, 0]
        others = [i for i in range(N) if i not in dst]
        want, before = blocks(a, src), blocks(a, others)
        a.swap_in(a.swap_out(src, 3), dst)
        same(blocks(a, dst), want)
        same(blocks(a, others), before)
        # ship: a narrow image of two blocks into another arena
        img = a.export_image(src[:2], 2)
        assert img.width == 2 * B
        b.import_image(img, {0: 5, 1: 3})
        same(blocks(b, [5, 3]), blocks(a, src[:2]))
        # the admission scatter: rows 2 and 0 of a batch of three
        rows = [jax.tree.map(lambda *r: jnp.concatenate(r, axis=1),
                             *(a.gather_row([i, i + 1])[kv]
                               for i in (1, 4, 7))) for kv in (0, 1)]
        b.scatter_rows(rows[0], rows[1], [2, 0],
                       [{0: 10, 1: 6}, {0: 8}])
        same(blocks(b, [10, 6, 8]), blocks(a, [7, 8, 1]))
        # copy-on-write
        b.copy_block(10, 1)
        same(blocks(b, [1]), blocks(b, [10]))
        # half the heads of twice the size: the same row width
        c = arena("copies-c", n_kv=H // 2, d=2 * D)
        try:
            with pytest.raises(KVImageError, match="incompatible"):
                c.import_image(img, {0: 1})
        finally:
            c.unregister()
    finally:
        a.unregister()
        b.unregister()


def test_prefill_width_invariance(model):
    """The paged cold-admission fast path prefills at the smallest
    block-multiple width covering the prompt instead of max_len
    (engine._admit).  What it leans on: right-pad lanes cannot reach
    live rows (every op in the prefill stack is row-independent over
    the position axis), so the sampled first token is the SAME at any
    padded width and the K/V at positions < plen agree to float32
    reduction order.

    Not bitwise: XLA is free to tile the wider GEMM differently, which
    reorders the f32 accumulation — jax 0.9.0's CPU backend differs by
    1.2e-6 here (max |d|, values O(1)) where an earlier build happened
    to agree exactly.  That is a fact about a backend, not the program,
    and nothing the engine does needs more: narrow and full-width rows
    are interchangeable to the same 2e-5 the chunk-vs-decode pin above
    allows, and the warm==cold TOKEN-stream identity the prefix cache
    promises is held by its own stream-parity tests (test_prefix.py,
    the warm/cold parity tests in this file), which is where a
    width-dependent flip would show."""
    import jax
    import jax.numpy as jnp
    from singa_tpu.serve.engine import _prefill_one
    from singa_tpu.models.gpt2_decode import extract_params

    cfg = model.cfg
    params = extract_params(model)
    statics = dict(n_head=cfg.n_head,
                   eps=float(cfg.layer_norm_eps),
                   moe_top_k=2, top_k=0, use_top_p=False)
    rng = np.random.RandomState(23)
    plen = 20
    prompt = rng.randint(0, 256, plen).astype(np.int32)
    key0 = jax.random.PRNGKey(0)
    outs = {}
    for W in (32, cfg.n_positions):
        ids = np.zeros((1, W), np.int32)
        ids[0, :plen] = prompt
        tok0, _, kc, vc = _prefill_one(
            params, jnp.asarray(ids), jnp.int32(plen), key0,
            np.float32(0.0), jnp.float32(1.0), **statics)
        outs[W] = (int(tok0), np.asarray(kc)[:, :, :, :plen],
                   np.asarray(vc)[:, :, :, :plen])
    assert outs[32][0] == outs[cfg.n_positions][0]
    for lane in (1, 2):
        np.testing.assert_allclose(outs[32][lane],
                                   outs[cfg.n_positions][lane],
                                   rtol=2e-5, atol=2e-5)


def test_prefill_batch_bitwise_equals_single(model):
    """The batched pass prefill (engine._prefill_batch — one dispatch
    for a scheduling pass's cold paged admissions) produces each
    row's (first token, carried key, cache rows) BITWISE equal to
    the per-request ``_prefill_one`` call, key chain included."""
    import jax
    import jax.numpy as jnp
    from singa_tpu.serve.engine import _prefill_batch, _prefill_one
    from singa_tpu.models.gpt2_decode import extract_params

    cfg = model.cfg
    params = extract_params(model)
    statics = dict(n_head=cfg.n_head,
                   eps=float(cfg.layer_norm_eps),
                   moe_top_k=2, top_k=0, use_top_p=False)
    rng = np.random.RandomState(24)
    R, W = 3, 32
    ids = np.zeros((R, W), np.int32)
    plens = np.array([20, 7, 13], np.int32)
    for r, p in enumerate(plens):
        ids[r, :p] = rng.randint(0, 256, p)
    seeds = np.array([5, 99, 0], np.int32)
    temps = np.array([0.0, 0.9, 0.9], np.float32)
    top_p = jnp.float32(1.0)
    t_b, k_b, kc_b, vc_b = _prefill_batch(
        params, jnp.asarray(ids), jnp.asarray(plens),
        jnp.asarray(seeds), jnp.asarray(temps), top_p, **statics)
    for r in range(R):
        key0 = jax.random.split(
            jax.random.PRNGKey(int(seeds[r])), 1)[0]
        t1, k1, kc1, vc1 = _prefill_one(
            params, jnp.asarray(ids[r:r + 1]), jnp.int32(int(plens[r])),
            key0, np.float32(temps[r]), top_p, **statics)
        assert int(t1) == int(t_b[r])
        np.testing.assert_array_equal(np.asarray(k1),
                                      np.asarray(k_b[r]))
        np.testing.assert_array_equal(
            np.asarray(kc1)[:, 0, :, :plens[r]],
            np.asarray(kc_b)[:, r, :, :plens[r]])
        np.testing.assert_array_equal(
            np.asarray(vc1)[:, 0, :, :plens[r]],
            np.asarray(vc_b)[:, r, :, :plens[r]])


def test_kernel_edge_geometry(model):
    """The kernel's edge cases, each pinned token-identical to the
    slot engine: block_size ∈ {1, 8, 16} (block_size=1 was a prior
    bug site — session donation clamp, round 14), a partially-filled
    final block, prompts landing ``pos`` EXACTLY on a block boundary
    at admission, and a slot whose block list is length 1."""
    rng = np.random.RandomState(22)
    for B, N in ((1, 64), (8, 16), (16, 16)):
        work = []
        # plen % B == 0: admission's first decode write lands on a
        # block boundary (a fresh block's lane 0)
        for plen, n_new in ((max(B, 4), 5), (2 * max(B, 2), 3),
                            (3, 4), (5, 2)):
            work.append(dict(
                prompt=rng.randint(0, 256, plen).astype(np.int32),
                n_new=n_new,
                temperature=float(rng.choice([0.0, 0.9])),
                seed=int(rng.randint(0, 1000))))
        base, _ = _run(model, work)
        outs, snap = _run(model, work,
                          paged=PagedConfig(block_size=B,
                                            num_blocks=N))
        assert all(np.array_equal(a, b)
                   for a, b in zip(outs, base)), f"B={B}"
        assert snap["paged"]["blocks_used"] == 0
    # single-block list + trash-lane masking: ONE live request in a
    # 4-slot pool (three dead slots carry all-trash tables through
    # the same executable) whose whole lifetime fits block 0
    p = rng.randint(0, 256, 4).astype(np.int32)
    want = np.asarray(model.generate(p, max_new_tokens=4,
                                     temperature=0.0))
    eng = model.serve(max_slots=4,
                      paged=PagedConfig(block_size=16, num_blocks=8))
    h = eng.submit(GenerationRequest(p, max_new_tokens=4,
                                     temperature=0.0))
    peak_blocks = 0
    steps = 0
    while eng.pending and steps < 200:
        eng.step()
        steps += 1
        peak_blocks = max([peak_blocks] + [len(s.blocks)
                                           for s in eng._slots
                                           if s is not None])
    np.testing.assert_array_equal(h.result().tokens, want)
    assert peak_blocks == 1   # the whole lifetime fit ONE block
    eng.close()


def test_metrics_and_health_surface(model):
    """serve.paged.* metrics ride the process registry while the
    engine lives (and unregister at close); health_report carries the
    always-present serve.paged section."""
    eng = model.serve(max_slots=2,
                      paged=PagedConfig(block_size=8, num_blocks=6))
    rng = np.random.RandomState(12)
    hs = [eng.submit(GenerationRequest(
        rng.randint(0, 256, 10).astype(np.int32), max_new_tokens=18,
        temperature=0.0)) for _ in range(3)]
    eng.run_until_complete(max_steps=2000)
    assert all(h.done() for h in hs)
    lbl = eng.stats.engine_label
    snap = registry().snapshot()
    assert snap["gauges"][
        f"serve.paged.blocks_free{{engine={lbl}}}"] == 6
    assert f"serve.paged.preemptions{{engine={lbl}}}" \
        in snap["counters"]
    hp = health_report(include_registry=False)["serve"]["paged"]
    assert set(hp) == {"blocks_free", "blocks_used", "preemptions",
                       "swap_out", "swap_in"}
    assert hp["preemptions"] == eng.stats.snapshot()["paged"][
        "preemptions"]
    # cost-table capture (VERDICT weak #6): the paged steps' AOT
    # compiles are visible to crash bundles
    from singa_tpu.observe.monitor import _cost_tables
    keys = [t["key"] for t in _cost_tables()]
    assert any(k.startswith("serve.paged/") for k in keys), keys
    eng.close()
    snap2 = registry().snapshot()
    assert f"serve.paged.blocks_free{{engine={lbl}}}" \
        not in snap2["gauges"]


def test_compiled_programs_report_their_temporaries(model):
    """``_aot_call`` puts what a paged program keeps beside its
    arguments (``temp_bytes``) and how much of them it updates where
    they lie (``alias_bytes``: at least the two pools) on its
    ``serve/compile`` span, and the temporaries into the gauge
    ``serve.paged.program_temp_bytes{program=}`` -- a step that stopped
    being in place says so at set-up, on any backend."""
    import jax

    from singa_tpu.observe import trace

    trace.enable()
    trace.clear()
    try:
        # a pool geometry no other test uses: the program compiles here
        eng = model.serve(max_slots=2,
                          paged=PagedConfig(block_size=8, num_blocks=11))
        try:
            pools = sum(a.nbytes for a in jax.tree.leaves(
                (eng.paged_arena.pool_k, eng.paged_arena.pool_v)))
            h = eng.submit(GenerationRequest(
                np.arange(1, 9, dtype=np.int32), max_new_tokens=4,
                temperature=0.0))
            eng.run_until_complete(max_steps=200)
            assert h.done()
        finally:
            eng.close(force=True)
        spans = [e for e in trace.events()
                 if e.get("name") == "serve/compile"
                 and e.get("args", {}).get("fn") == "paged_decode_kernel"]
    finally:
        trace.disable()
        trace.clear()
    assert spans, "the decode kernel compiled under no serve/compile span"
    args = spans[-1]["args"]
    assert args["alias_bytes"] >= pools
    assert 0 <= args["temp_bytes"] < pools + 64 * 2 ** 20
    gauges = registry().snapshot()["gauges"]
    assert gauges["serve.paged.program_temp_bytes"
                  "{program=paged_decode_kernel}"] == args["temp_bytes"]


# -- the budgeted prefill's launch widths (tests/launch_widths.py) ---------

@pytest.fixture(scope="module")
def launch_runs(model):
    runs = lw.Runs(lambda budget: model.serve(
        max_slots=4, paged=PagedConfig(block_size=8, num_blocks=64,
                                       prefill_token_budget=budget)), 256)
    yield runs
    runs.close()


@pytest.mark.parametrize("case", list(lw.CASES))
@pytest.mark.parametrize("ratio", lw.RATIOS)
def test_a_launch_of_several_blocks_leaves_what_one_block_at_a_time_did(
        launch_runs, ratio, case):
    """One launch a request a step, ``ratio`` blocks wide at most: the
    tokens and the private K/V rows of every admission against the
    engine that launches a block at a time; ``serve.prefill.
    budget_chunks`` counts the same blocks whatever the ratio (the
    benchmark multiplies it by the block for its tokens), and
    ``serve.prefill.launches`` fewer exactly when a launch can be wider
    than a block."""
    lw.assert_same_as_one_block(launch_runs.run(ratio, case),
                                launch_runs.run(1, case), case, ratio,
                                atol=1e-5)


def test_one_block_lowers_to_the_program_it_was(launch_runs):
    assert lw.chunk_row_lowering(launch_runs.engine(1)) == \
        lw.PARENT_LOWERING["gpt2"]


@pytest.mark.parametrize("ratio", lw.RATIOS)
def test_every_launch_width_is_compiled_when_the_engine_is_built(model,
                                                                 ratio):
    """A warm-up of short prompts (the benchmark's: one block less a
    token) reaches the one-block program only; prompts of every length
    after it -- every width, at offsets even and odd, and at four blocks
    two requests in a launch -- compile nothing, because the engine
    compiled every shape its planner can choose from abstract shapes
    when it was built."""
    from singa_tpu.serve import paged
    from singa_tpu.serve.jitpin import jit_cache_size

    # a row width no other test uses: the programs compile here
    eng = model.serve(max_slots=4, max_len=120, paged=PagedConfig(
        block_size=8, num_blocks=64, prefill_token_budget=8 * ratio))
    try:
        widths = eng._launch_widths
        assert widths == tuple(8 * n for n in range(ratio, 0, -1))
        # the pair program: two slots of half the budget, from four
        # blocks on
        assert eng._pair_blocks == (2 if ratio == 4 else 0)
        shapes = {() if w == 8 else (w // 8,) for w in widths}
        if eng._pair_blocks:
            shapes.add(((2,), (2,)))
        # before any request: a program a shape, under the key its
        # first launch will look up
        memo = eng._x._aot_memo
        assert {t[1] for t in memo} == shapes
        assert all(key in paged._aot_cache for key in memo.values())
        rng = np.random.RandomState(ratio)

        def serve(lengths, n_new=3):
            hs = [eng.submit(GenerationRequest(
                rng.randint(0, 256, n).astype(np.int32),
                max_new_tokens=n_new, temperature=0.0))
                for n in lengths]
            eng.run_until_complete(max_steps=2000)
            assert all(h.done() for h in hs)

        # every decode bucket, the row copies, the first-token sampler
        serve([7] * 4)
        warmed = jit_cache_size()
        serve([7, 60, 27, 117, 12, 13, 33, 5, 90])
        assert jit_cache_size() == warmed
        chunks, launches = (eng._c_budget_chunks.value,
                            eng._c_launches.value)
        assert (launches < chunks) == (ratio > 1)
        # (the four short warm-up prompts were two pairs already)
        assert (eng._c_merged_launches.value > 2) == (ratio == 4)
    finally:
        eng.close(force=True)
