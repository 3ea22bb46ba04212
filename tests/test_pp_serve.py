"""Pipeline-parallel serving (serve/pp.py + the engine's ``pp=``
mode): token-stream parity against the single-device paged engine on
the virtual CPU mesh (cold / warm / int8 / preempt-resume /
chunked-prefill budget, greedy AND seeded sampling mixed in one pool,
microbatch widths against the compacted dispatch buckets), supervisor
restart under an injected ``serve.pp_boundary`` fault, typed config
validation (fired BEFORE any registration — the leaked-gauge audit),
and the metrics/health/unregister surface.

The single-device paged engine is the oracle (itself parity-pinned
against the slot engine and offline ``generate`` in
tests/test_paged.py), so PP parity here is transitively
offline-oracle parity.  The pipeline reorders NO arithmetic — layers
run in the same order with the same per-layer block-native kernels,
and the stage-boundary ``ppermute`` moves bytes, not partial sums —
so the parity pin is strictly tighter than TP's psum caveat; every
workload below is seed-pinned deterministic."""

import numpy as np
import pytest

from singa_tpu import tensor
from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from singa_tpu.observe import health_report
from singa_tpu.observe.registry import registry
from singa_tpu.resilience import FailAfterN, faults
from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                             GenerationRequest, PagedConfig, PPConfig,
                             PrefixCacheConfig, ServeFleet)


def _build(cfg):
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.fixture(scope="module")
def model():
    return _build(GPT2Config.tiny(dropout=0.0))


_PCFG = PagedConfig(block_size=8, num_blocks=32)


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


def _run(m, work, max_slots=4, max_steps=4000, **kw):
    kw.setdefault("paged", _PCFG)
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


def test_cold_parity_pp2(model):
    """2 stages x 2 microbatches: streams token-identical to the
    single-device paged engine, the stats snapshot carries the pp
    section, no blocks leak."""
    work = _workload(0, 7, sampled=True)
    base, _ = _run(model, work)
    outs, snap = _run(model, work, pp=PPConfig(stages=2,
                                               microbatches=2))
    assert _parity(outs, base)
    pp = snap["pp"]
    assert pp["stages"] == 2
    assert pp["layers_per_stage"] == model.cfg.n_layer // 2
    assert pp["microbatches"] == 2
    assert pp["sharded_dispatches"] > 0
    assert pp["kv_bytes_per_stage"] > 0
    assert pp["boundary_hops"] > 0
    assert snap["paged"]["blocks_used"] == 0


@pytest.mark.slow  # variant: test_cold_parity_pp2 is the fast rep
def test_deep_model_stage_per_layer():
    """The scenario the subsystem exists for — a model DEEPER than one
    device: 4 layers across 4 stages, one layer per device, parity
    preserved."""
    m = _build(GPT2Config.tiny(dropout=0.0, n_layer=4))
    work = _workload(1, 5, sampled=True)
    base, _ = _run(m, work)
    outs, snap = _run(m, work, pp=4)
    assert _parity(outs, base)
    assert snap["pp"]["stages"] == 4
    assert snap["pp"]["layers_per_stage"] == 1


@pytest.mark.slow
def test_microbatch_widths_and_compaction(model):
    """The GPipe microbatch count clamps (gcd) to the compacted
    dispatch width: a pool whose live width collapses below the
    microbatch count still decodes correctly (slots drain raggedly,
    buckets halve), and an odd microbatch request works."""
    work = _workload(2, 6, n_lo=2, n_hi=14, sampled=True)
    base, _ = _run(model, work, max_slots=8)
    outs, _ = _run(model, work, max_slots=8,
                   pp=PPConfig(stages=2, microbatches=4))
    assert _parity(outs, base)
    outs3, _ = _run(model, work, max_slots=8,
                    pp=PPConfig(stages=2, microbatches=3))
    assert _parity(outs3, base)


def test_gqa_parity_pp2():
    """GQA models: the narrow H_kv cache slices per stage on the
    LAYER axis (the head axis stays whole per stage)."""
    m = _build(GPT2Config.tiny(dropout=0.0, n_kv_head=2))
    work = _workload(3, 5, n_lo=6, n_hi=14, p_lo=4, p_hi=16)
    base, _ = _run(m, work, max_slots=3)
    outs, _ = _run(m, work, max_slots=3, pp=2)
    assert _parity(outs, base)


@pytest.mark.slow
def test_int8_parity_pp2(model):
    """int8 pools under PP: the (values, scales) leaves both slice on
    the layer axis; token parity vs the single-device int8 paged
    engine."""
    work = _workload(4, 5, sampled=True)
    base, _ = _run(model, work, cache_dtype="int8")
    eng = model.serve(max_slots=4, paged=_PCFG, cache_dtype="int8",
                      pp=2)
    try:
        vals, scales = eng.paged_arena.pool_k
        L = model.cfg.n_layer
        assert vals.shape[0] == L and scales.shape[0] == L
        assert vals.addressable_shards[0].data.shape[0] == L // 2
        assert scales.addressable_shards[0].data.shape[0] == L // 2
        hs = [eng.submit(GenerationRequest(
            w["prompt"], max_new_tokens=w["n_new"],
            temperature=w["temperature"], seed=w["seed"]))
            for w in work]
        eng.run_until_complete(max_steps=4000)
        outs = [h.result().tokens for h in hs]
    finally:
        eng.close(force=True)
    assert _parity(outs, base)


@pytest.mark.slow
def test_warm_prefix_parity_pp2(model):
    """Prefix cache on a PP engine: warm chunks flow stage-to-stage
    through the chunk twin against layer-sharded cache rows; streams
    stay byte-identical to the single-device engine."""
    rng = np.random.RandomState(6)
    system = rng.randint(0, 256, 40).astype(np.int32)
    work = [dict(prompt=np.concatenate(
        [system, rng.randint(0, 256, rng.randint(3, 8))
         .astype(np.int32)]),
        n_new=6, temperature=0.0, seed=int(rng.randint(0, 1000)))
        for _ in range(5)]
    cache = PrefixCacheConfig(block_size=8)
    base, _ = _run(model, work, max_slots=2, prefix_cache=cache,
                   paged=PagedConfig(block_size=8, num_blocks=64))
    outs, snap = _run(model, work, max_slots=2, prefix_cache=cache,
                      paged=PagedConfig(block_size=8, num_blocks=64),
                      pp=2)
    assert _parity(outs, base)
    assert snap["prefix"]["hits"] > 0, "workload never went warm"


@pytest.mark.slow
def test_preempt_resume_parity_pp2(model):
    """Preemption/swap against stage-sliced pools: the pool<->row
    copy twins run with layer-axis specs and the host image
    reassembles the full layer axis, so resumed PP streams equal the
    uninterrupted single-device run's and no block leaks."""
    work = _workload(5, 6, n_lo=12, n_hi=30, p_lo=4, p_hi=20,
                     sampled=True)
    small = PagedConfig(block_size=8, num_blocks=10)
    base, _ = _run(model, work, paged=small)
    outs, snap = _run(model, work, paged=small, pp=2)
    assert _parity(outs, base)
    pg = snap["paged"]
    assert pg["preemptions"] > 0 and pg["swap_in"] > 0
    assert pg["blocks_used"] == 0, "leaked blocks after drain"


def test_budget_parity_pp2(model):
    """The chunked-prefill token budget composes: a long admission
    splits across steps in chunk twins that flow the pipeline, and
    budgeted streams stay byte-identical to unbudgeted PP streams."""
    work = _workload(6, 4, p_lo=20, p_hi=40, n_lo=3, n_hi=7,
                     sampled=True)
    base, _ = _run(model, work,
                   paged=PagedConfig(block_size=8, num_blocks=48),
                   pp=2)
    outs, snap = _run(model, work,
                      paged=PagedConfig(block_size=8, num_blocks=48,
                                        prefill_token_budget=16),
                      pp=2)
    assert _parity(outs, base)


def test_stage_boundary_fault_supervisor_restart(model):
    """An injected ``serve.pp_boundary`` fault fails the pipelined
    engine TYPED; the supervisor rebuilds (same stage group,
    twin-cache hit) and requeued never-started streams keep parity.
    Zero wedged handles."""
    work = _workload(7, 6, n_lo=4, n_hi=10, sampled=True)
    base, _ = _run(model, work, max_slots=2)
    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    sup = EngineSupervisor(model, max_slots=2, restart_budget=2,
                           pp=2, paged=_PCFG)
    hs = [sup.submit(GenerationRequest(
        w["prompt"], max_new_tokens=w["n_new"],
        temperature=w["temperature"], seed=w["seed"]))
        for w in work]
    pol = faults.inject("serve.pp_boundary", FailAfterN(3, times=1))
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
        assert h.done(), "wedged handle after PP restart"
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


def test_fleet_of_pp_replicas(model):
    """serve_fleet(pp=2, replicas=2) partitions the mesh into
    disjoint stage-wide groups; streams keep parity and both
    replicas carry traffic."""
    work = _workload(8, 8, sampled=True)
    base, _ = _run(model, work)
    fleet = ServeFleet(model, replicas=2, max_slots=2, pp=2,
                       paged=_PCFG)
    try:
        d0 = fleet.supervisor(0).engine.pp_exec.mesh.devices.flat
        d1 = fleet.supervisor(1).engine.pp_exec.mesh.devices.flat
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
    """Every incompatible pp configuration is a typed construction
    error fired BEFORE any registration (no serve.pp gauge may leak
    from a refused construction)."""

    def pp_gauges():
        return {k for k in registry().snapshot()["gauges"]
                if k.startswith("serve.pp.")}

    before = pp_gauges()
    # pp without paged: the memory model IS the stage-sliced pool
    with pytest.raises(ValueError, match="requires paged="):
        model.serve(max_slots=2, pp=2)
    # stages not dividing n_layer
    m3 = _build(GPT2Config.tiny(dropout=0.0, n_layer=3))
    with pytest.raises(ValueError, match="does not divide n_layer"):
        m3.serve(max_slots=2, pp=2, paged=_PCFG)
    # speculative draft: the proposal scan would serialize the
    # pipeline, and a mismatched-depth draft cannot take the split
    d = _build(GPT2Config.tiny(dropout=0.0, n_layer=1))
    with pytest.raises(ValueError, match="mismatched depth"):
        model.serve(max_slots=2, pp=2, paged=_PCFG, draft_model=d,
                    spec_k=3)
    # sliding-window models
    mw = _build(GPT2Config.tiny(dropout=0.0, attn_window=16))
    with pytest.raises(NotImplementedError, match="sliding-window"):
        mw.serve(max_slots=2, pp=2,
                 paged=PagedConfig(block_size=8, num_blocks=32))
    # MoE models take ep=, not pp=
    mm = _build(GPT2Config.tiny(dropout=0.0, moe_every=2,
                                moe_experts=4))
    with pytest.raises(ValueError, match=r"ep=EPConfig"):
        mm.serve(max_slots=2, pp=2, paged=_PCFG)
    # pp together with tp
    with pytest.raises(ValueError, match="one sharded executor"):
        model.serve(max_slots=2, pp=2, tp=2, paged=_PCFG)
    # stages wider than the mesh (8-device conftest topology)
    with pytest.raises(ValueError, match="devices"):
        model.serve(max_slots=2, pp=16, paged=_PCFG)
    # stages x replicas exceeding the mesh
    with pytest.raises(ValueError, match="exceeds"):
        ServeFleet(model, replicas=5, max_slots=2, pp=2, paged=_PCFG)
    # bad knob type
    with pytest.raises(ValueError, match="PPConfig"):
        model.serve(max_slots=2, pp="deep", paged=_PCFG)
    assert pp_gauges() == before, \
        "a refused construction leaked serve.pp gauges"
    # pp=1 is simply off (and then needs no paged=)
    eng = model.serve(max_slots=2, pp=1)
    assert eng.pp_exec is None
    eng.close()
    # explicit PPConfig passes through
    eng = model.serve(max_slots=2, pp=PPConfig(stages=2), paged=_PCFG)
    assert eng.pp_exec is not None and eng.pp_exec.stages == 2
    eng.close()


def test_metrics_and_health_unregister(model):
    """serve.pp.* metrics register per engine, surface in
    health_report()["serve"]["pp"], and unregister at close; the
    health section stays present (zeroed) with no live PP engine."""
    eng = model.serve(max_slots=2, pp=2, paged=_PCFG)
    lbl = eng.stats.engine_label
    try:
        h = eng.submit(GenerationRequest(
            np.arange(5, dtype=np.int32), max_new_tokens=3))
        eng.run_until_complete(max_steps=200)
        h.result()
        rep = health_report(include_registry=False)
        pp = rep["serve"]["pp"]
        assert pp["stages"] == 2
        assert pp["kv_bytes_per_stage"] > 0
        assert pp["sharded_dispatches"] > 0
        assert pp["boundary_hops"] > 0
    finally:
        eng.close()
    snap = registry().snapshot()["gauges"]
    assert f"serve.pp.stages{{engine={lbl}}}" not in snap, \
        "pp gauges leaked past close()"
    rep = health_report(include_registry=False)
    assert "pp" in rep["serve"]
