"""Expert-parallel MoE serving (serve/ep.py + the engine's ``ep=``
mode): token-stream parity against the single-device MoE engine on the
virtual CPU mesh (cold / warm / int8 / GQA / speculative /
preempt-resume, greedy AND seeded sampling mixed in one pool),
capacity-overflow determinism under a finite ``capacity_factor``,
supervisor restart under an injected ``serve.ep_dispatch`` fault,
typed config validation (fired BEFORE any registration — the
leaked-gauge audit), expert-load observability, and the
metrics/health/unregister surface.

The single-device engine is the oracle (itself parity-pinned against
single-prompt ``generate`` in tests/test_serve.py), so EP parity here
is transitively offline-oracle parity.  At the default
``capacity_factor=None`` nothing ever drops and routing is per-token
independent, so the ONE arithmetic difference is the per-MoE-layer
psum over the ``ep`` axis (plus the dense layers' tp psums when
``EPConfig(tp>1)``) — float addition order, identity on token streams
away from exact ties; every workload below is seed-pinned
deterministic."""

import numpy as np
import pytest

from singa_tpu import tensor
from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from singa_tpu.observe import health_report
from singa_tpu.observe.registry import registry
from singa_tpu.resilience import FailAfterN, faults
from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                             EPConfig, GenerationRequest, PagedConfig,
                             PrefixCacheConfig, ServeFleet)


def _build(cfg):
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)
    return m


@pytest.fixture(scope="module")
def model():
    """2-layer GPT-MoE: every 2nd block's MLP is a 4-expert top-2
    MoEFFN (the architecture serve/tp.py refuses and this round
    serves)."""
    return _build(GPT2Config.tiny(dropout=0.0, moe_every=2,
                                  moe_experts=4))


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


def test_cold_parity_ep2_tp2(model):
    """ep=2 x tp=2 on the 8-device mesh: experts sharded over ep,
    dense layers Megatron over tp — streams token-identical to the
    single-device MoE engine, and the stats snapshot carries the ep
    section with per-expert routed-token load."""
    work = _workload(0, 7, sampled=True)
    base, _ = _run(model, work)
    outs, snap = _run(model, work, ep=EPConfig(ep=2, tp=2))
    assert _parity(outs, base)
    ep = snap["ep"]
    assert ep["shards"] == 2 and ep["dense_tp"] == 2
    assert ep["experts"] == 4 and ep["experts_per_shard"] == 2
    assert ep["capacity_factor"] is None
    assert ep["sharded_dispatches"] > 0
    assert ep["kv_bytes_per_shard"] > 0
    assert sum(ep["expert_tokens"]) > 0
    assert ep["dropped_tokens"] == 0, \
        "capacity_factor=None must never drop"
    assert ep["load_imbalance"] is not None


def test_cold_parity_ep4(model):
    """The full expert axis sharded one expert per device (ep=4)."""
    work = _workload(1, 4, sampled=True)
    base, _ = _run(model, work)
    outs, snap = _run(model, work, ep=4)
    assert _parity(outs, base)
    assert snap["ep"]["shards"] == 4
    assert snap["ep"]["experts_per_shard"] == 1


def test_gqa_parity_ep2_tp2():
    """GQA MoE: the narrow H_kv cache shards over the orthogonal tp
    axis (replicated over ep), experts over ep — both at once."""
    m = _build(GPT2Config.tiny(dropout=0.0, n_kv_head=2, moe_every=2,
                               moe_experts=4))
    work = _workload(2, 5, n_lo=6, n_hi=14, p_lo=4, p_hi=16)
    base, _ = _run(m, work, max_slots=3)
    outs, _ = _run(m, work, max_slots=3, ep=EPConfig(ep=2, tp=2))
    assert _parity(outs, base)


def test_int8_parity_and_scales_sharding(model):
    """int8 arenas under EP: token parity vs the single-device int8
    MoE engine, and the (values, scales) leaves shard on the H_kv
    axis over the tp sub-axis of the (ep, tp) mesh — each of the 4
    mesh devices holds an addressable H_kv/tp slice (replicated
    across ep)."""
    work = _workload(3, 5, sampled=True)
    base, _ = _run(model, work, cache_dtype="int8")

    eng = model.serve(max_slots=2, ep=EPConfig(ep=2, tp=2),
                      cache_dtype="int8")
    try:
        vals, scales = eng._kc
        H = model.cfg.n_kv_head
        assert vals.shape[2] == H and scales.shape[2] == H
        assert vals.addressable_shards[0].data.shape[2] == H // 2
        assert scales.addressable_shards[0].data.shape[2] == H // 2
        assert len(vals.addressable_shards) == 4  # ep x tp devices
        hs = [eng.submit(GenerationRequest(
            w["prompt"], max_new_tokens=w["n_new"],
            temperature=w["temperature"], seed=w["seed"]))
            for w in work]
        eng.run_until_complete(max_steps=4000)
        outs = [h.result().tokens for h in hs]
    finally:
        eng.close(force=True)
    assert _parity(outs, base)


def test_spec_parity_ep2(model, draft):
    """Speculative decoding on an expert-sharded TARGET with a fully
    REPLICATED dense draft (greedy — the byte-parity regime): the
    draft proposes identically on every rank, the verify chunk routes
    through the capacity-bounded EP dispatch."""
    work = _workload(4, 5, n_lo=4, n_hi=12, sampled=False)
    base, _ = _run(model, work, max_slots=3)
    outs, snap = _run(model, work, max_slots=3, ep=2,
                      draft_model=draft, spec_k=3)
    assert _parity(outs, base)
    assert snap["spec"]["chunks"] > 0


def test_paged_preempt_resume_parity_ep2(model):
    """Paged pool under EP (tp sub-axis slices, replicated over ep):
    an over-committed pool forces preemption/swap mid-decode and the
    resumed streams equal the uninterrupted single-device run's —
    swap images carry the full head axis, blocks never leak."""
    work = _workload(5, 6, n_lo=12, n_hi=30, p_lo=4, p_hi=20,
                     sampled=True)
    base, _ = _run(model, work, max_slots=4)
    outs, snap = _run(model, work, max_slots=4, ep=2,
                      paged=PagedConfig(block_size=8, num_blocks=10))
    assert _parity(outs, base)
    pg = snap["paged"]
    assert pg["preemptions"] > 0 and pg["swap_in"] > 0
    assert pg["blocks_used"] == 0, "leaked blocks after drain"


@pytest.mark.parametrize("ratio", [1, 4])
def test_budgeted_launch_widths_parity_ep2(model, ratio):
    """The budgeted prefill's launches, one block wide and as wide as
    a budget of four blocks allows (the sharded twin retraces a width:
    ``off`` carries it as a shape): streams equal the single-device
    whole-prompt run's, and the blocks counted do not depend on the
    width."""
    work = _workload(8, 4, p_lo=20, p_hi=60, n_lo=3, n_hi=7)
    base, _ = _run(model, work, max_slots=4)
    eng = model.serve(max_slots=4, ep=2, paged=PagedConfig(
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


def test_warm_prefix_parity_ep2(model):
    """Prefix cache on an EP engine (legal at capacity_factor=None —
    drop-free routing is per-token independent, so chunked prefill
    stays canonical): a shared system prompt goes warm and streams
    stay byte-identical to the single-device engine."""
    rng = np.random.RandomState(6)
    system = rng.randint(0, 256, 40).astype(np.int32)
    work = [dict(prompt=np.concatenate(
        [system, rng.randint(0, 256, rng.randint(3, 8))
         .astype(np.int32)]),
        n_new=6, temperature=0.0, seed=int(rng.randint(0, 1000)))
        for _ in range(5)]
    base, _ = _run(model, work)
    outs, snap = _run(model, work, ep=2,
                      prefix_cache=PrefixCacheConfig(block_size=8,
                                                     num_blocks=64))
    assert _parity(outs, base)
    assert snap["prefix"]["hits"] > 0, "workload never went warm"


def test_capacity_overflow_determinism(model):
    """A FINITE capacity_factor is the GShard capacity mode: prefill
    dispatch groups drop over-capacity assignments through the
    residual path.  The drop pattern must be DETERMINISTIC — two
    fresh engines over the same workload produce identical streams —
    and counted (``dropped_tokens`` > 0 under a factor tight enough
    to overflow)."""
    work = _workload(9, 5, p_lo=16, p_hi=30, sampled=True)
    cfg = EPConfig(ep=2, capacity_factor=0.25)
    a, snap_a = _run(model, work, ep=cfg,
                     paged=PagedConfig(block_size=8, num_blocks=48))
    b, snap_b = _run(model, work, ep=cfg,
                     paged=PagedConfig(block_size=8, num_blocks=48))
    assert _parity(a, b), "capacity drops must be deterministic"
    assert snap_a["ep"]["dropped_tokens"] > 0, \
        "factor 0.25 over 16+-token prefills must overflow"
    assert snap_a["ep"]["dropped_tokens"] == \
        snap_b["ep"]["dropped_tokens"]


def test_expert_load_observability(model):
    """The dispatch twins feed the expert-load surface everywhere it
    is promised: per-expert registry counters (labeled expert=),
    snapshot()["ep"]["expert_tokens"], and a LIVE
    health_report()["serve"]["ep"] with the imbalance ratio."""
    eng = model.serve(max_slots=2, ep=2)
    try:
        h = eng.submit(GenerationRequest(
            np.arange(9, dtype=np.int32), max_new_tokens=4))
        eng.run_until_complete(max_steps=200)
        h.result()
        lbl = eng.stats.engine_label
        counters = registry().snapshot()["counters"]
        per_expert = [
            counters.get(
                f"serve.ep.expert_tokens{{engine={lbl},expert={e}}}",
                0)
            for e in range(4)]
        assert sum(per_expert) > 0
        snap = eng.stats.snapshot()["ep"]
        assert snap["expert_tokens"] == per_expert
        rep = health_report(include_registry=False)
        ep = rep["serve"]["ep"]
        assert ep["shards"] == 2
        assert sum(ep["expert_tokens"]) >= sum(per_expert)
        assert ep["load_imbalance"] is not None
        assert ep["dropped_tokens"] == 0
    finally:
        eng.close()


def test_supervisor_restart_ep2(model):
    """An injected ``serve.ep_dispatch`` fault fails the sharded
    engine TYPED mid-decode; the supervisor rebuilds it (same device
    group, twin-cache hit) and requeued never-started streams keep
    parity.  Zero wedged handles."""
    work = _workload(7, 6, n_lo=4, n_hi=10, sampled=True)
    base, _ = _run(model, work)
    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    sup = EngineSupervisor(model, max_slots=2, restart_budget=2, ep=2)
    hs = [sup.submit(GenerationRequest(
        w["prompt"], max_new_tokens=w["n_new"],
        temperature=w["temperature"], seed=w["seed"]))
        for w in work]
    pol = faults.inject("serve.ep_dispatch", FailAfterN(3, times=1))
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
        assert h.done(), "wedged handle after EP restart"
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


def test_fleet_of_ep_replicas(model):
    """serve_fleet(ep=EPConfig(ep=2, tp=2), replicas=2) partitions
    the 8-device mesh into disjoint 4-wide (ep x tp) groups; streams
    keep parity and both replicas carry traffic."""
    work = _workload(8, 8, sampled=True)
    base, _ = _run(model, work, max_slots=4)
    fleet = ServeFleet(model, replicas=2, max_slots=2,
                       ep=EPConfig(ep=2, tp=2))
    try:
        d0 = fleet.supervisor(0).engine.ep_exec.mesh.devices.flat
        d1 = fleet.supervisor(1).engine.ep_exec.mesh.devices.flat
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
    """Every incompatible ep configuration is a typed construction
    error fired BEFORE any registration (no serve.ep gauge may leak
    from a refused construction — the PR-12 hazard, audited)."""

    def ep_gauges():
        return {k for k in registry().snapshot()["gauges"]
                if k.startswith("serve.ep.")}

    before = ep_gauges()
    # ep on a dense model: no expert axis
    dense = _build(GPT2Config.tiny(dropout=0.0))
    with pytest.raises(ValueError, match="dense model"):
        dense.serve(max_slots=2, ep=2)
    # ep not dividing moe_experts (4 experts)
    with pytest.raises(ValueError, match="does not divide "
                                         "moe_experts"):
        model.serve(max_slots=2, ep=3)
    # orthogonal tp not dividing n_head (tiny: n_head=4)
    with pytest.raises(ValueError, match="does not divide n_head"):
        model.serve(max_slots=2, ep=EPConfig(ep=2, tp=3))
    # ep together with the bare tp= knob
    with pytest.raises(ValueError, match="drop the bare"):
        model.serve(max_slots=2, ep=2, tp=2)
    # ep together with pp
    with pytest.raises(ValueError, match="not both"):
        model.serve(max_slots=2, ep=2, pp=2)
    # finite capacity factor next to a prefix cache: chunk
    # canonicality cannot hold
    with pytest.raises(ValueError, match="capacity_factor"):
        model.serve(max_slots=2,
                    ep=EPConfig(ep=2, capacity_factor=1.25),
                    prefix_cache=PrefixCacheConfig(block_size=8))
    # mesh too small (8-device conftest topology)
    with pytest.raises(ValueError, match="devices"):
        model.serve(max_slots=2, ep=EPConfig(ep=4, tp=4))
    # (ep x tp) x replicas exceeding the mesh
    with pytest.raises(ValueError, match="exceeds"):
        ServeFleet(model, replicas=3, max_slots=2,
                   ep=EPConfig(ep=2, tp=2))
    # bad knob type
    with pytest.raises(ValueError, match="EPConfig"):
        model.serve(max_slots=2, ep="wide")
    # a bad capacity factor is a config-time error
    with pytest.raises(ValueError, match="capacity_factor"):
        EPConfig(ep=2, capacity_factor=0.0)
    assert ep_gauges() == before, \
        "a refused construction leaked serve.ep gauges"
    # ep=1 (x tp=1) is simply off
    eng = model.serve(max_slots=2, ep=1)
    assert eng.ep_exec is None
    eng.close()
    # explicit EPConfig passes through
    eng = model.serve(max_slots=2, ep=EPConfig(ep=2))
    assert eng.ep_exec is not None and eng.ep_exec.ep == 2
    eng.close()


def test_metrics_and_health_unregister(model):
    """serve.ep.* metrics register per engine, surface in
    health_report()["serve"]["ep"], and unregister at close; the
    health section stays present (zeroed) with no live EP engine."""
    eng = model.serve(max_slots=2, ep=2)
    lbl = eng.stats.engine_label
    try:
        h = eng.submit(GenerationRequest(
            np.arange(5, dtype=np.int32), max_new_tokens=3))
        eng.run_until_complete(max_steps=200)
        h.result()
        rep = health_report(include_registry=False)
        ep = rep["serve"]["ep"]
        assert ep["shards"] == 2
        assert ep["kv_bytes_per_shard"] > 0
        assert ep["sharded_dispatches"] > 0
    finally:
        eng.close()
    snap = registry().snapshot()
    assert f"serve.ep.shards{{engine={lbl}}}" not in snap["gauges"], \
        "ep gauges leaked past close()"
    assert not any(
        k.startswith("serve.ep.expert_tokens{")
        and f"engine={lbl}" in k
        for k in snap["counters"]), \
        "per-expert counters leaked past close()"
    rep = health_report(include_registry=False)
    assert "ep" in rep["serve"]
