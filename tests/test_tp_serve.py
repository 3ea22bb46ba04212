"""Tensor-parallel serving (serve/tp.py + the engine's ``tp=`` mode):
token-stream parity against the single-device engine on the virtual
CPU mesh (cold / warm / int8 / GQA / speculative / preempt-resume,
greedy AND seeded sampling mixed in one pool), supervisor restart of a
sharded engine under an injected ``serve.tp_collective`` fault, typed
config validation, sharded-placement checks, and the observability
surface (``serve.tp.*`` metrics, stats/health sections).

The single-device engine is the oracle (itself parity-pinned against
single-prompt ``generate`` in tests/test_serve.py), so TP parity here
is transitively offline-oracle parity.  The TP twins' one arithmetic
difference is the per-shard psum (the row-parallel contraction is
summed per shard then reduced), so logits agree to float addition
order — on TOKEN streams that is identity away from exact ties, and
every workload below is seed-pinned deterministic."""

import numpy as np
import pytest

from singa_tpu import tensor
from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from singa_tpu.observe import health_report
from singa_tpu.observe.registry import registry
from singa_tpu.resilience import FailAfterN, faults
from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                             GenerationRequest, PagedConfig,
                             PrefixCacheConfig, ServeFleet, TPConfig)


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


def _workload(seed, n, p_lo=3, p_hi=14, n_lo=2, n_hi=9, sampled=True):
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


def _parity(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


def test_cold_parity_tp2(model):
    """TP=2 slot-arena streams (greedy and seeded sampling mixed in
    one pool) are token-identical to the single-device engine's, and
    the stats snapshot carries the tp section."""
    work = _workload(0, 7, sampled=True)
    base, _ = _run(model, work)
    outs, snap = _run(model, work, tp=2)
    assert _parity(outs, base)
    tp = snap["tp"]
    assert tp["shards"] == 2
    assert tp["sharded_dispatches"] > 0
    assert tp["kv_bytes_per_shard"] > 0
    assert tp["collectives_per_step"] == 2 * model.cfg.n_layer


def test_cold_parity_tp4(model):
    """The same engine at tp=4 on the 8-device virtual mesh."""
    work = _workload(1, 4, sampled=True)
    base, _ = _run(model, work)
    outs, snap = _run(model, work, tp=4)
    assert _parity(outs, base)
    assert snap["tp"]["shards"] == 4


def test_gqa_parity_tp2():
    """GQA models shard the NARROW H_kv cache: each shard owns
    H_kv/tp = 1 kv head serving its full query group."""
    m = _build(GPT2Config.tiny(dropout=0.0, n_kv_head=2))
    work = _workload(2, 5, n_lo=6, n_hi=14, p_lo=4, p_hi=16)
    base, _ = _run(m, work, max_slots=3)
    outs, _ = _run(m, work, max_slots=3, tp=2)
    assert _parity(outs, base)


def test_int8_parity_and_scales_sharding(model):
    """int8 arenas under TP: token parity vs the single-device int8
    engine, and the (values, scales) leaves are BOTH actually sharded
    on the H_kv axis (the scales leaf lacks the trailing D axis — the
    rank-generic cache spec must still land on axis 2)."""
    work = _workload(3, 5, sampled=True)
    base, _ = _run(model, work, cache_dtype="int8")

    eng = model.serve(max_slots=2, tp=2, cache_dtype="int8")
    try:
        vals, scales = eng._kc
        H = model.cfg.n_kv_head
        # global shapes keep the full head axis; each shard's
        # addressable piece holds H/2 heads of values AND scales
        assert vals.shape[2] == H and scales.shape[2] == H
        assert vals.addressable_shards[0].data.shape[2] == H // 2
        assert scales.addressable_shards[0].data.shape[2] == H // 2
        hs = [eng.submit(GenerationRequest(
            w["prompt"], max_new_tokens=w["n_new"],
            temperature=w["temperature"], seed=w["seed"]))
            for w in work]
        eng.run_until_complete(max_steps=4000)
        outs = [h.result().tokens for h in hs]
    finally:
        eng.close(force=True)
    assert _parity(outs, base)


def test_spec_parity_tp2(model, draft):
    """Speculative decoding on a sharded TARGET with a fully
    REPLICATED draft: streams equal the single-device engine's (the
    draft proposes identically on every shard; the verify chunk is
    the sharded dispatch)."""
    work = _workload(4, 5, n_lo=4, n_hi=12, sampled=False)
    base, _ = _run(model, work, max_slots=3)
    outs, snap = _run(model, work, max_slots=3, tp=2,
                      draft_model=draft, spec_k=3)
    assert _parity(outs, base)
    assert snap["spec"]["chunks"] > 0


def test_paged_preempt_resume_parity_tp2(model):
    """The paged pool sharded per shard on H_kv: an over-committed
    pool forces preemption/swap mid-decode, the host copy carries the
    FULL head axis (np.asarray assembles the global row), and resumed
    TP streams equal the uninterrupted single-device run's."""
    work = _workload(5, 6, n_lo=12, n_hi=30, p_lo=4, p_hi=20,
                     sampled=True)
    base, _ = _run(model, work, max_slots=4)
    outs, snap = _run(model, work, max_slots=4, tp=2,
                      paged=PagedConfig(block_size=8, num_blocks=10))
    assert _parity(outs, base)
    pg = snap["paged"]
    assert pg["preemptions"] > 0 and pg["swap_in"] > 0
    assert pg["blocks_used"] == 0, "leaked blocks after drain"


@pytest.mark.parametrize("ratio", [1, 4])
def test_budgeted_launch_widths_parity_tp2(model, ratio):
    """The budgeted prefill's launches, one block wide and as wide as
    a budget of four blocks allows (the sharded twin retraces a width:
    ``off`` carries it as a shape): streams equal the single-device
    whole-prompt run's, and the blocks counted do not depend on the
    width."""
    work = _workload(8, 4, p_lo=20, p_hi=60, n_lo=3, n_hi=7)
    base, _ = _run(model, work, max_slots=4)
    eng = model.serve(max_slots=4, tp=2, paged=PagedConfig(
        block_size=8, num_blocks=48, prefill_token_budget=8 * ratio))
    hs = [eng.submit(GenerationRequest(
        w["prompt"], max_new_tokens=w["n_new"],
        temperature=w["temperature"], seed=w["seed"])) for w in work]
    eng.run_until_complete(max_steps=4000)
    outs = [h.result().tokens for h in hs]
    chunks, launches = (eng._c_budget_chunks.value,
                        eng._c_launches.value)
    eng.close()
    assert _parity(outs, base)
    assert chunks == sum((len(w["prompt"]) - 1) // 8 + 1 for w in work)
    assert (launches < chunks) == (ratio > 1)


@pytest.mark.parametrize("cache_dtype", [None, "int8"],
                         ids=["dense", "int8"])
def test_paged_pool_shards_its_rows(model, cache_dtype):
    """The token-a-row pool under TP: every leaf is sharded on its LAST
    axis (a row is the K/V heads side by side: contiguous heads a
    shard; the int8 scales leaf the same way), and a host image of a
    request's blocks is byte-compatible with the unsharded engine's --
    rows laid in by the sharded engine gather back equal through the
    single-device pool's copies."""
    import jax

    kw = dict(max_slots=2, paged=PagedConfig(block_size=8, num_blocks=6))
    if cache_dtype:
        kw["cache_dtype"] = cache_dtype
    cfg = model.cfg
    H, D = cfg.n_kv_head, cfg.n_embd // cfg.n_head
    eng, ref = model.serve(tp=2, **kw), model.serve(**kw)
    try:
        leaves = jax.tree.leaves(eng.paged_arena.pool_k)
        widths = [H * D, H] if cache_dtype else [H * D]
        for leaf, w in zip(leaves, widths):
            assert leaf.shape == (cfg.n_layer, 7, 8, w)
            assert leaf.addressable_shards[0].data.shape[-1] == w // 2
        hs = [e.submit(GenerationRequest(
            list(range(1, 12)), max_new_tokens=6, temperature=0.0))
            for e in (eng, ref)]
        def slot(e):
            return next((s for s in e._slots if s is not None), None)

        while not (slot(eng) and slot(ref)):
            eng.step()
            ref.step()
        # the prompt's first block, from each engine's own pool
        imgs = [e.paged_arena.swap_out(slot(e).blocks[:1], 1)
                for e in (eng, ref)]
        for a, b in zip(jax.tree.leaves((imgs[0].kc, imgs[0].vc)),
                        jax.tree.leaves((imgs[1].kc, imgs[1].vc))):
            # the psum reorders a float sum: an int8 value may land one
            # step of its scale away
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-4, atol=1.0 if a.dtype == np.int8 else 2e-5)
        # and either image lands in the other engine's pool
        imgs[0].validate(8, bool(cache_dtype),
                         pool_k=ref.paged_arena.pool_k, head_dim=D)
        imgs[1].validate(8, bool(cache_dtype),
                         pool_k=eng.paged_arena.pool_k, head_dim=D)
        for e in (eng, ref):
            e.run_until_complete(max_steps=400)
        assert _parity([hs[0].result().tokens], [hs[1].result().tokens])
    finally:
        eng.close(force=True)
        ref.close(force=True)


def test_warm_prefix_parity_tp2(model):
    """Prefix-cache rows as sharded pytrees: a shared system prompt
    makes later admissions warm (sharded gather + sharded chunk
    prefill), streams byte-identical to the single-device engine."""
    rng = np.random.RandomState(6)
    system = rng.randint(0, 256, 40).astype(np.int32)
    work = [dict(prompt=np.concatenate(
        [system, rng.randint(0, 256, rng.randint(3, 8))
         .astype(np.int32)]),
        n_new=6, temperature=0.0, seed=int(rng.randint(0, 1000)))
        for _ in range(5)]
    base, _ = _run(model, work)
    outs, snap = _run(model, work, tp=2,
                      prefix_cache=PrefixCacheConfig(block_size=8,
                                                     num_blocks=64))
    assert _parity(outs, base)
    assert snap["prefix"]["hits"] > 0, "workload never went warm"


def test_supervisor_restart_tp2(model):
    """An injected ``serve.tp_collective`` fault fails the sharded
    engine TYPED mid-decode; the supervisor rebuilds it (same device
    group, twin-cache hit) and requeued never-started streams keep
    parity.  Zero wedged handles."""
    work = _workload(7, 6, n_lo=4, n_hi=10, sampled=True)
    base, _ = _run(model, work)
    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    sup = EngineSupervisor(model, max_slots=2, restart_budget=2, tp=2)
    hs = [sup.submit(GenerationRequest(
        w["prompt"], max_new_tokens=w["n_new"],
        temperature=w["temperature"], seed=w["seed"]))
        for w in work]
    pol = faults.inject("serve.tp_collective", FailAfterN(3, times=1))
    try:
        sup.run_until_complete(max_steps=4000)
    finally:
        faults.clear()
    assert pol.fired == 1
    restarts = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0) - restarts0
    assert restarts == 1
    completed = typed = 0
    for i, h in enumerate(hs):
        assert h.done(), "wedged handle after TP restart"
        try:
            got = h.result().tokens
            assert np.array_equal(got, base[i])
            completed += 1
        except EngineFailedError as e:
            assert e.started is True
            typed += 1
    assert completed + typed == len(work)
    assert completed > 0
    sup.close()


def test_fleet_of_tp_replicas(model):
    """serve_fleet(tp=2, replicas=2) partitions the 8-device mesh into
    disjoint 2-wide groups; streams keep parity with the single-device
    engine and both replicas carry traffic."""
    work = _workload(8, 8, sampled=True)
    base, _ = _run(model, work, max_slots=4)
    fleet = ServeFleet(model, replicas=2, max_slots=2, tp=2)
    try:
        d0 = fleet.supervisor(0).engine.tp_exec.mesh.devices.flat
        d1 = fleet.supervisor(1).engine.tp_exec.mesh.devices.flat
        assert {d.id for d in d0}.isdisjoint({d.id for d in d1})
        hs = [fleet.submit(GenerationRequest(
            w["prompt"], max_new_tokens=w["n_new"],
            temperature=w["temperature"], seed=w["seed"]))
            for w in work]
        fleet.run_until_complete(max_steps=4000)
        outs = [h.result().tokens for h in hs]
        snap = fleet.snapshot()
    finally:
        fleet.close()
    assert _parity(outs, base)
    assert all(v > 0 for v in snap["routed"].values())


def test_config_validation(model):
    """Every incompatible tp configuration is a typed construction
    error, never a shape blow-up inside a shard_map trace."""
    # tp not dividing n_head (tiny: n_head=4)
    with pytest.raises(ValueError, match="does not divide n_head"):
        model.serve(max_slots=2, tp=3)
    # tp not dividing H_kv (GQA narrow cache)
    mg = _build(GPT2Config.tiny(dropout=0.0, n_kv_head=2))
    with pytest.raises(ValueError, match="H_kv"):
        mg.serve(max_slots=2, tp=4)
    # tp wider than the mesh
    with pytest.raises(ValueError, match="devices"):
        model.serve(max_slots=2, tp=16)
    # tp x replicas exceeding the mesh (8-device conftest topology)
    with pytest.raises(ValueError, match="exceeds"):
        ServeFleet(model, replicas=5, max_slots=2, tp=2)
    # bad knob type
    with pytest.raises(ValueError, match="TPConfig"):
        model.serve(max_slots=2, tp="wide")
    # tp=1 is simply off
    eng = model.serve(max_slots=2, tp=1)
    assert eng.tp_exec is None
    eng.close()
    # explicit TPConfig passes through
    eng = model.serve(max_slots=2, tp=TPConfig(tp=2))
    assert eng.tp_exec is not None and eng.tp_exec.tp == 2
    eng.close()


def test_twin_cache_keyed_on_model_structure(model, draft):
    """Two TP engines for DIFFERENT-depth models with identical
    statics on the same device group must not share a sharded twin:
    the twin's in_specs closure bakes the params spec tree in, and the
    first model's 2-layer blocks list is not a valid prefix for the
    1-layer draft's pytree (review finding — the module-wide cache key
    now includes the param treedef)."""
    work = _workload(9, 3)
    base2, _ = _run(model, work)
    outs2, _ = _run(model, work, tp=2)       # 2-layer twins cached
    base1, _ = _run(draft, work)
    outs1, _ = _run(draft, work, tp=2)       # 1-layer: same statics
    assert _parity(outs2, base2)
    assert _parity(outs1, base1)


def test_moe_model_refused():
    """MoE blocks shard over the expert axis, not tp: typed refusal
    at construction, and the message is the CONTRACT — it must name
    the ``serve(ep=)`` path that does serve this model (the EP/PP
    round's rewritten refusal; serve/ep.py)."""
    m = _build(GPT2Config.tiny(dropout=0.0, moe_every=2,
                               moe_experts=2))
    from singa_tpu.observe.registry import registry

    def tp_gauges():
        return {k for k in registry().snapshot()["gauges"]
                if k.startswith("serve.tp.")}

    before = tp_gauges()
    with pytest.raises(NotImplementedError,
                       match=r"serve\(ep=EPConfig"):
        m.serve(max_slots=2, tp=2)
    # the refusal fired BEFORE the executor registered anything: a
    # failed construction must leak no serve.tp gauges (the PR-12
    # leaked-gauge hazard, audited for the rewritten refusal)
    assert tp_gauges() == before


def test_metrics_and_health_surface(model):
    """serve.tp.* metrics register per engine, surface in
    health_report()["serve"]["tp"], and unregister at close."""
    eng = model.serve(max_slots=2, tp=2)
    try:
        h = eng.submit(GenerationRequest(
            np.arange(5, dtype=np.int32), max_new_tokens=3))
        eng.run_until_complete(max_steps=200)
        h.result()
        rep = health_report(include_registry=False)
        tp = rep["serve"]["tp"]
        assert tp["shards"] == 2
        assert tp["kv_bytes_per_shard"] > 0
        assert tp["sharded_dispatches"] > 0
        assert tp["collectives_per_step"] == 2 * model.cfg.n_layer
    finally:
        eng.close()
    snap = registry().snapshot()["gauges"]
    lbl = f"serve.tp.shards{{engine={eng.stats.engine_label}}}"
    assert lbl not in snap, "tp metrics leaked past close()"
    # the section stays present (zeroed) with no live TP engine
    rep = health_report(include_registry=False)
    assert "tp" in rep["serve"]
