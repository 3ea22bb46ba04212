"""Chaos bench — drive the resilience layer end to end and PROVE the
recovery invariants the unit tests assert piecewise:

* **checkpoint corruption** — write two manager checkpoints, truncate
  AND bit-flip the newest, and require ``restore_latest`` to fall back
  to the previous good step (``resilience.checkpoint_fallbacks``);
  a transient injected write fault must be absorbed by the retry
  layer (``resilience.retries{site=checkpoint.write}``).
* **collective retry** — a transient fault at the host-side
  ``comm.collective`` dispatch site retries under backoff and the run
  proceeds.
* **decode fault + supervised restart** — a seeded fault injected into
  ``serve.decode_step`` mid-run fails the engine TYPED; the supervisor
  rebuilds it and requeues never-started requests.  The bench asserts
  ZERO wedged/lost requests (every submitted request retires or fails
  typed), token-stream parity against an uninterrupted run for every
  completed request, and ``resilience.engine_restarts`` equal to the
  number of injected decode faults.
* **fault mid-verify (speculative engine)** — the same decode-site
  fault against a trained-pair SPECULATIVE engine: the spec step
  (draft scan + chunk verify + rejection sample) fails typed, not
  wedged; the rebuilt engine gets fresh target AND draft arenas at
  zero recompiles and requeued streams keep byte parity.
* **fault mid-swap (paged engine)** — a ``serve.paged_copy`` fault
  against a block-paged engine whose pool deliberately over-commits
  (growth swaps fire every round): the copy raises mid-preemption, the
  engine fails TYPED (swapped requests ``started=True`` — tokens
  streamed, never requeued), the supervisor rebuild gets a FRESH pool,
  and requeued never-started streams keep byte parity, preemption and
  swap/resume included post-restart.
* **fault at a TP collective (tensor-parallel engine)** — a
  ``serve.tp_collective`` fault fires at a sharded-twin dispatch
  mid-decode: the sharded engine fails typed, the supervisor rebuilds
  it on the same device group (twin-cache hit, fresh sharded arenas),
  requeued streams keep byte parity, zero wedged/lost, restarts ==
  injected.
* **replica kill + fleet failover** — the same decode fault against a
  ``ServeFleet`` replica with a ZERO restart budget kills that replica
  outright mid-decode; the fleet requeues its never-started work onto
  the survivor (stream parity), fails started work typed, keeps
  serving new requests, and the jit cache stays pinned at zero
  recompiles across the failover.
* **fault mid-branch (CoW fork family)** — a ``serve.fork_copy``
  fault fires on the copy-on-write block copy inside a best-of-n
  family: the WRITING branch rejects typed (``FaultInjected``) and
  frees its private blocks, its siblings complete with byte parity
  against the clean run, the ENGINE never fails (blast radius is one
  branch — zero restarts), zero blocks leak, and a fresh-pool rerun
  reproduces the clean streams exactly.
* **disaggregated fleet under fire** — a ``serve.kv_ship`` fault
  mid-transfer requeues the shipped request COLD with byte parity
  (nothing streams during a ship) and leaks zero blocks on either
  replica; a chunk fault with a zero restart budget KILLS a prefill
  specialist mid-build and the fleet serves everything cold on the
  decode side — zero wedged, zero lost, zero leaked.

The whole run happens under active monitoring; the report embeds
``observe.health_report()`` and the bench FAILS unless
``watchdog.hangs == 0`` — recovery that trips the hang detector is
not recovery.  Writes CHAOS.json (strict JSON) and prints it; CI runs
this on CPU and re-parses the file as its gate (tier1.yml chaos job).
"""

import argparse
import json
import os
import shutil
import tempfile

import numpy as np


def chaos_checkpoint(report):
    """Corrupt-newest fallback + retried transient write fault."""
    from singa_tpu import device, opt, tensor
    from singa_tpu.models.mlp import MLP
    from singa_tpu.observe.registry import registry
    from singa_tpu.resilience import (CheckpointManager, FailOnce,
                                      RetryPolicy, faults)
    from singa_tpu.resilience.checkpoint import STATES_NAME

    dev = device.get_default_device()
    m = MLP(data_size=10, perceptron_size=16, num_classes=4)
    m.set_optimizer(opt.SGD(lr=0.05))
    x = tensor.from_numpy(np.zeros((8, 10), np.float32), dev)
    m.compile([x], is_train=True, use_graph=False, sequential=False)
    rng = np.random.RandomState(0)

    def train(n):
        for _ in range(n):
            xb = tensor.from_numpy(
                rng.randn(8, 10).astype(np.float32), dev)
            yb = tensor.from_numpy(
                rng.randint(0, 4, (8,)).astype(np.int32), dev)
            m(xb, yb)

    root = tempfile.mkdtemp(prefix="chaos-ckpt-")
    try:
        mgr = CheckpointManager(
            root, keep=3,
            retry_policy=RetryPolicy(max_attempts=3, base_delay_s=0.01,
                                     max_delay_s=0.05))
        train(2)
        # transient write fault: FailOnce fires on the first attempt,
        # the retry layer's second attempt commits the checkpoint
        faults.inject("checkpoint.write", FailOnce())
        mgr.save(m, 100, aux_states={"tag": np.int64(100)})
        faults.clear()
        good = {k: tensor.to_numpy(v) for k, v in m.get_params().items()}
        train(2)
        mgr.save(m, 200, aux_states={"tag": np.int64(200)})

        # crash-mid-write: truncate the newest states file mid-record
        sp = os.path.join(mgr.step_dir(200), STATES_NAME)
        data = open(sp, "rb").read()
        open(sp, "wb").write(data[:len(data) // 2])

        m2 = MLP(data_size=10, perceptron_size=16, num_classes=4)
        m2.compile([x], is_train=True, use_graph=False, sequential=False)
        step, aux = mgr.restore_latest(m2)
        assert step == 100 and int(aux["tag"]) == 100, \
            f"fallback restored step {step}, wanted 100"
        for k, v in m2.get_params().items():
            np.testing.assert_array_equal(tensor.to_numpy(v), good[k])

        snap = registry().snapshot()["counters"]
        report["checkpoint"] = {
            "fallbacks": snap.get("resilience.checkpoint_fallbacks", 0),
            "write_retries": snap.get(
                "resilience.retries{site=checkpoint.write}", 0),
            "restored_step_after_corruption": step,
        }
        assert report["checkpoint"]["fallbacks"] >= 1
        assert report["checkpoint"]["write_retries"] >= 1
    finally:
        shutil.rmtree(root, ignore_errors=True)


def chaos_collective(report):
    """Transient fault at the host-side collective dispatch hook —
    retried under the communicator's fast backoff policy."""
    from singa_tpu.observe.registry import registry
    from singa_tpu.parallel.communicator import _record_collective
    from singa_tpu.resilience import FailOnce, faults

    faults.inject("comm.collective", FailOnce())
    # the trace-time dispatch hook every collective method calls
    _record_collective("all_reduce", [np.zeros((1024,), np.float32)])
    faults.clear()
    snap = registry().snapshot()["counters"]
    report["collective"] = {
        "retries": snap.get(
            "resilience.retries{site=comm.collective}", 0),
    }
    assert report["collective"]["retries"] >= 1


def chaos_serve(report):
    """Injected decode faults mid-run: zero wedged/lost requests,
    parity for everything that completed, restarts == injected."""
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.observe.registry import registry
    from singa_tpu.resilience import FailAfterN, faults
    from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                                 GenerationRequest)

    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)

    rng = np.random.RandomState(0)
    workload = [(rng.randint(0, 256, rng.randint(3, 14)).astype(np.int32),
                 int(rng.randint(2, 9))) for _ in range(10)]
    # uninterrupted oracle, one prompt at a time
    base = [np.asarray(m.generate(p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]

    injected = 0
    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    completed = wedged = typed_failed = 0
    # two chaos rounds, each killing the engine once at a different
    # depth into the run
    for round_i, fail_after in enumerate((2, 4)):
        sup = EngineSupervisor(m, max_slots=2, restart_budget=2)
        handles = [sup.submit(GenerationRequest(
            p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]
        pol = faults.inject("serve.decode_step",
                            FailAfterN(fail_after, times=1))
        sup.run_until_complete(max_steps=2000)
        faults.clear()
        injected += pol.fired
        for (p, n), h, want in zip(workload, handles, base):
            if not h.done():
                wedged += 1
                continue
            try:
                got = h.result().tokens
                assert np.array_equal(got, want), \
                    "token stream diverged after restart"
                completed += 1
            except EngineFailedError:
                typed_failed += 1  # in-flight at fault: typed, not lost
        sup.close()

    restarts = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0) - restarts0
    report["serve"] = {
        "requests": 2 * len(workload),
        "completed_with_parity": completed,
        "typed_failures": typed_failed,
        "wedged_or_lost": wedged,
        "decode_faults_injected": injected,
        "engine_restarts": restarts,
    }
    assert wedged == 0, f"{wedged} requests wedged/lost"
    assert completed + typed_failed == 2 * len(workload)
    assert completed > 0 and typed_failed > 0
    assert restarts == injected, \
        f"restarts ({restarts}) != injected decode faults ({injected})"


def chaos_prefix(report):
    """Injected prefix-cache copy faults (serve.prefix_copy fires in
    the warm-admission block copy AND the retire-time donation): the
    engine fails TYPED, the supervisor rebuilds it with an EMPTY radix
    tree, and every request either completes with parity or fails
    typed — zero wedged/lost, restarts == injected."""
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.observe.registry import registry
    from singa_tpu.resilience import FailAfterN, faults
    from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                                 GenerationRequest, PrefixCacheConfig)

    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)

    rng = np.random.RandomState(2)
    system = rng.randint(0, 256, 24).astype(np.int32)
    workload = [(np.concatenate(
        [system,
         rng.randint(0, 256, rng.randint(3, 10)).astype(np.int32)]),
        int(rng.randint(2, 7))) for _ in range(10)]
    base = [np.asarray(m.generate(p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]

    injected = 0
    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    completed = wedged = typed_failed = 0
    for fail_after in (3, 8):
        sup = EngineSupervisor(
            m, max_slots=2, restart_budget=2,
            prefix_cache=PrefixCacheConfig(block_size=8,
                                           num_blocks=32))
        cache0 = sup.engine.prefix_cache
        handles = [sup.submit(GenerationRequest(
            p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]
        pol = faults.inject("serve.prefix_copy",
                            FailAfterN(fail_after, times=1))
        sup.run_until_complete(max_steps=2000)
        faults.clear()
        injected += pol.fired
        if pol.fired:
            # the advertised restart contract: a FRESH cache object,
            # rebuilt from empty (its contents now reflect only
            # post-restart donations, never pre-fault state)
            assert sup.engine.prefix_cache is not cache0, \
                "rebuilt engine carried the old prefix cache"
        for (p, n), h, want in zip(workload, handles, base):
            if not h.done():
                wedged += 1
                continue
            try:
                got = h.result().tokens
                assert np.array_equal(got, want), \
                    "warm/restarted token stream diverged"
                completed += 1
            except EngineFailedError:
                typed_failed += 1
        sup.close()

    restarts = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0) - restarts0
    report["serve_prefix"] = {
        "requests": 2 * len(workload),
        "completed_with_parity": completed,
        "typed_failures": typed_failed,
        "wedged_or_lost": wedged,
        "copy_faults_injected": injected,
        "engine_restarts": restarts,
    }
    assert wedged == 0, f"{wedged} requests wedged/lost"
    assert completed + typed_failed == 2 * len(workload)
    assert completed > 0 and injected > 0
    assert restarts == injected, \
        f"restarts ({restarts}) != injected copy faults ({injected})"


def chaos_spec(report):
    """A fault mid-verify against a SPECULATIVE engine
    (``serve.decode_step`` gates the whole spec step: draft scan +
    chunk verify + rejection sample): the engine fails TYPED, never
    wedges, the supervisor rebuilds it — fresh target AND draft
    arenas, every executable a jit cache hit — and requeued
    never-started requests stream byte-identically to an
    uninterrupted speculative run (which itself equals the
    non-speculative oracle)."""
    from singa_tpu import device, opt, tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.observe.registry import registry
    from singa_tpu.resilience import FailAfterN, faults
    from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                                 GenerationRequest)

    def train(cfg, seed, steps=12):
        device.get_default_device().SetRandSeed(seed)
        m = GPT2LMHead(cfg)
        rng = np.random.RandomState(0)
        motif = rng.randint(0, cfg.vocab_size, 8)
        ids = np.tile(motif, (4, 4)).astype(np.int32)[:, :32]
        noise = rng.randint(0, cfg.vocab_size, ids.shape)
        mask = rng.rand(*ids.shape) < 0.05
        ids[mask] = noise[mask]
        labels = np.roll(ids, -1, axis=1).astype(np.int32)
        m.set_optimizer(opt.Adam(lr=1e-3))
        m.compile([tensor.from_numpy(ids)], is_train=True,
                  use_graph=True)
        for _ in range(steps):
            m(tensor.from_numpy(ids), tensor.from_numpy(labels))
        m.eval()
        return m, ids

    target, ids = train(GPT2Config.tiny(dropout=0.0), seed=0)
    draft, _ = train(GPT2Config.tiny(dropout=0.0, n_layer=1), seed=1,
                     steps=8)

    rng = np.random.RandomState(5)
    workload = []
    for _ in range(10):
        plen = int(rng.randint(4, 13))
        row, off = int(rng.randint(0, 4)), int(rng.randint(0, 32 - 13))
        workload.append((np.asarray(ids[row, off:off + plen], np.int32),
                         int(rng.randint(3, 9))))
    base = [np.asarray(target.generate(p, max_new_tokens=n,
                                       temperature=0.0))
            for p, n in workload]

    injected = 0
    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    completed = wedged = typed_failed = 0
    accepted = drafted = 0
    for fail_after in (2, 4):
        sup = EngineSupervisor(target, max_slots=2, restart_budget=2,
                               draft_model=draft, spec_k=3)
        handles = [sup.submit(GenerationRequest(
            p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]
        pol = faults.inject("serve.decode_step",
                            FailAfterN(fail_after, times=1))
        sup.run_until_complete(max_steps=2000)
        faults.clear()
        injected += pol.fired
        spec = sup.engine.stats.snapshot()["spec"]
        accepted += spec["accepted"]
        drafted += spec["drafted"]
        for (p, n), h, want in zip(workload, handles, base):
            if not h.done():
                wedged += 1
                continue
            try:
                got = h.result().tokens
                assert np.array_equal(got, want), \
                    "speculative stream diverged after restart"
                completed += 1
            except EngineFailedError:
                typed_failed += 1
        sup.close()

    restarts = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0) - restarts0
    report["serve_spec"] = {
        "requests": 2 * len(workload),
        "completed_with_parity": completed,
        "typed_failures": typed_failed,
        "wedged_or_lost": wedged,
        "decode_faults_injected": injected,
        "engine_restarts": restarts,
        "acceptance_rate": accepted / drafted if drafted else None,
    }
    assert wedged == 0, f"{wedged} speculative requests wedged/lost"
    assert completed + typed_failed == 2 * len(workload)
    assert completed > 0 and typed_failed > 0
    assert restarts == injected > 0, \
        f"restarts ({restarts}) != injected spec-step faults ({injected})"
    assert report["serve_spec"]["acceptance_rate"] > 0


def chaos_paged(report):
    """A fault in the paged arena's copy path (``serve.paged_copy``
    fires in the admission scatter, the swap-out gather, and the
    swap-in restore): the engine fails TYPED mid-operation — the
    first injection lands on the first SWAP-OUT gather by
    construction (two admissions check the site once each, the next
    check is the preemption gather on this workload) — never wedges;
    the supervisor rebuild gets a FRESH pool (zero blocks used), and
    every request either completes with byte parity (requeued
    never-started work, swap/resume included post-restart) or fails
    typed started=True (live + swapped).  Zero wedged/lost,
    restarts == injected."""
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.observe.registry import registry
    from singa_tpu.resilience import FailAfterN, faults
    from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                                 GenerationRequest, PagedConfig)

    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)

    rng = np.random.RandomState(7)
    # fixed 10-token prompts + 20-token budgets against a 6-block pool
    # of 8-token blocks: two live slots grow past the pool and the
    # growth self-swap fires every round
    workload = [(rng.randint(0, 256, 10).astype(np.int32), 20)
                for _ in range(8)]
    base = [np.asarray(m.generate(p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]

    injected = 0
    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    completed = wedged = typed_failed = 0
    preempted_total = 0
    # the recovery invariants below cover the block-native decode
    # path, and the serve.paged_copy fault site still fires on the
    # admission scatter and the swap gather/scatter (those copies kept
    # their fixed-shape form; swap is off the hot path —
    # docs/SERVING.md)
    pcfg = PagedConfig(block_size=8, num_blocks=6)
    for fail_after in (2, 7):
        sup = EngineSupervisor(
            m, max_slots=2, restart_budget=2, paged=pcfg)
        arena0 = sup.engine.paged_arena
        handles = [sup.submit(GenerationRequest(
            p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]
        pol = faults.inject("serve.paged_copy",
                            FailAfterN(fail_after, times=1))
        sup.run_until_complete(max_steps=4000)
        faults.clear()
        injected += pol.fired
        if pol.fired:
            assert sup.engine.paged_arena is not arena0, \
                "rebuilt engine carried the old paged arena"
        pg = sup.engine.stats.snapshot()["paged"]
        preempted_total += pg["preemptions"]
        assert pg["blocks_used"] == 0, \
            f"drained paged engine leaked {pg['blocks_used']} blocks"
        for (p, n), h, want in zip(workload, handles, base):
            if not h.done():
                wedged += 1
                continue
            try:
                got = h.result().tokens
                assert np.array_equal(got, want), \
                    "paged token stream diverged after restart"
                completed += 1
            except EngineFailedError:
                typed_failed += 1
        sup.close()

    restarts = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0) - restarts0
    report["serve_paged"] = {
        "requests": 2 * len(workload),
        "completed_with_parity": completed,
        "typed_failures": typed_failed,
        "wedged_or_lost": wedged,
        "copy_faults_injected": injected,
        "engine_restarts": restarts,
        "preemptions": preempted_total,
        "blocks_leaked": 0,
        "kernel": "block",
    }
    assert wedged == 0, f"{wedged} paged requests wedged/lost"
    assert completed + typed_failed == 2 * len(workload)
    assert completed > 0 and typed_failed > 0
    assert preempted_total > 0, "no preemption — the swap path was " \
        "not exercised"
    assert restarts == injected > 0, \
        f"restarts ({restarts}) != injected copy faults ({injected})"


def chaos_fork(report):
    """A fault on the copy-on-write block copy (``serve.fork_copy``
    fires inside ``PagedKVArena.copy_block`` when a forked branch
    first writes a sibling-shared block): the WRITING branch rejects
    typed and its private blocks return to the pool; siblings keep
    decoding to byte parity with the clean run; the ENGINE survives —
    the blast radius of a CoW fault is ONE branch, so unlike every
    other serve scenario here there is no supervisor restart to
    count (the bench asserts restarts stayed ZERO).  A fresh-pool
    rerun of the same family reproduces the clean streams, proving
    the fault never corrupted the shared prompt blocks."""
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.observe.registry import registry
    from singa_tpu.resilience import FailAfterN, FaultInjected, faults
    from singa_tpu.serve import GenerationRequest, PagedConfig

    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)

    rng = np.random.RandomState(15)
    prompt = rng.randint(0, 256, 12).astype(np.int32)
    pcfg = PagedConfig(block_size=8, num_blocks=32)
    n_branches = 3

    def run(inject):
        eng = m.serve(max_slots=4, paged=pcfg)
        fh = eng.submit(GenerationRequest(
            prompt, max_new_tokens=16, temperature=0.9, seed=3,
            n=n_branches))
        pol = None
        if inject:
            # the FIRST CoW copy of the family fires the fault
            pol = faults.inject("serve.fork_copy",
                                FailAfterN(0, times=1))
        try:
            eng.run_until_complete(max_steps=4000)
        finally:
            faults.clear()
        outs = {}
        typed = 0
        for b in fh.branches:
            try:
                r = b.result()
                outs[r.branch] = r.tokens
            except FaultInjected as e:
                assert e.site == "serve.fork_copy", e.site
                typed += 1
        # the leak invariant: every pool block accounted after drain
        leaked = eng.check_block_accounting()
        eng.close()
        return outs, typed, (pol.fired if pol else 0), leaked

    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    clean, typed0, _, leak0 = run(False)
    assert typed0 == 0 and len(clean) == n_branches
    faulted, typed, fired, leak1 = run(True)
    parity = sum(1 for b, toks in faulted.items()
                 if np.array_equal(toks, clean[b]))
    fresh, typed2, _, leak2 = run(False)
    fresh_parity = (typed2 == 0 and len(fresh) == n_branches
                    and all(np.array_equal(fresh[b], clean[b])
                            for b in fresh))
    restarts = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0) - restarts0

    report["serve_fork"] = {
        "requests": n_branches,
        "completed_with_parity": parity,
        "typed_failures": typed,
        "wedged_or_lost": n_branches - len(faulted) - typed,
        "cow_faults_injected": fired,
        "engine_restarts": restarts,
        "blocks_leaked": leak0 + leak1 + leak2,
        "fresh_pool_parity": bool(fresh_parity),
        "kernel": "block",
    }
    sf = report["serve_fork"]
    assert sf["wedged_or_lost"] == 0, "fork branches wedged/lost"
    assert sf["cow_faults_injected"] == 1 == sf["typed_failures"]
    assert sf["completed_with_parity"] == len(faulted) \
        == n_branches - 1, "a surviving sibling diverged"
    assert sf["engine_restarts"] == 0, \
        "a CoW fault must reject one branch, not restart the engine"
    assert sf["blocks_leaked"] == 0, sf["blocks_leaked"]
    assert sf["fresh_pool_parity"] is True


def chaos_tp(report):
    """A fault at the ``serve.tp_collective`` site (every sharded-twin
    dispatch of a tensor-parallel engine checks it) fires mid-decode:
    the sharded engine fails TYPED — never wedges — and the supervisor
    rebuilds it on the SAME device group (sharded-twin cache hit,
    fresh sharded arenas).  Requeued never-started streams keep byte
    parity with the uninterrupted single-device run; started requests
    fail typed.  Zero wedged/lost, restarts == injected."""
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.observe.registry import registry
    from singa_tpu.resilience import FailAfterN, faults
    from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                                 GenerationRequest)

    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)

    rng = np.random.RandomState(11)
    workload = [(rng.randint(0, 256, rng.randint(4, 12))
                 .astype(np.int32), int(rng.randint(4, 10)))
                for _ in range(10)]
    base = [np.asarray(m.generate(p, max_new_tokens=n,
                                  temperature=0.0))
            for p, n in workload]

    injected = 0
    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    completed = wedged = typed_failed = 0
    for fail_after in (4, 9):
        sup = EngineSupervisor(m, max_slots=2, restart_budget=2, tp=2)
        exec0 = sup.engine.tp_exec
        handles = [sup.submit(GenerationRequest(
            p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]
        pol = faults.inject("serve.tp_collective",
                            FailAfterN(fail_after, times=1))
        sup.run_until_complete(max_steps=4000)
        faults.clear()
        injected += pol.fired
        if pol.fired:
            assert sup.engine.tp_exec is not exec0, \
                "rebuilt engine carried the failed TP executor"
            assert sup.engine.tp_exec.tp == 2
        for (p, n), h, want in zip(workload, handles, base):
            if not h.done():
                wedged += 1
                continue
            try:
                got = h.result().tokens
                assert np.array_equal(got, want), \
                    "TP token stream diverged after restart"
                completed += 1
            except EngineFailedError:
                typed_failed += 1
        sup.close()

    restarts = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0) - restarts0
    report["serve_tp"] = {
        "requests": 2 * len(workload),
        "shards": 2,
        "completed_with_parity": completed,
        "typed_failures": typed_failed,
        "wedged_or_lost": wedged,
        "collective_faults_injected": injected,
        "engine_restarts": restarts,
    }
    assert wedged == 0, f"{wedged} TP requests wedged/lost"
    assert completed + typed_failed == 2 * len(workload)
    assert completed > 0 and typed_failed > 0
    assert restarts == injected > 0, \
        f"restarts ({restarts}) != injected TP faults ({injected})"


def chaos_ep(report):
    """A fault at the ``serve.ep_dispatch`` site (every sharded-twin
    dispatch of an expert-parallel MoE engine checks it) fires
    mid-decode: the sharded engine fails TYPED — never wedges — and
    the supervisor rebuilds it on the SAME (ep, tp) device group
    (twin-cache hit, fresh sharded pool).  Requeued never-started
    streams keep byte parity with the uninterrupted single-device MoE
    run; started requests fail typed; the rebuilt engine's paged pool
    drains to ZERO used blocks.  Zero wedged/lost/leaked, restarts ==
    injected."""
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.observe.registry import registry
    from singa_tpu.resilience import FailAfterN, faults
    from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                                 GenerationRequest, PagedConfig)

    cfg = GPT2Config.tiny(dropout=0.0, moe_every=2, moe_experts=4)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)

    rng = np.random.RandomState(13)
    workload = [(rng.randint(0, 256, rng.randint(4, 12))
                 .astype(np.int32), int(rng.randint(4, 10)))
                for _ in range(10)]
    base = [np.asarray(m.generate(p, max_new_tokens=n,
                                  temperature=0.0))
            for p, n in workload]

    injected = 0
    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    completed = wedged = typed_failed = leaked = 0
    expert_tokens_after = 0
    for fail_after in (4, 9):
        sup = EngineSupervisor(
            m, max_slots=2, restart_budget=2, ep=2,
            paged=PagedConfig(block_size=8, num_blocks=32))
        exec0 = sup.engine.ep_exec
        handles = [sup.submit(GenerationRequest(
            p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]
        pol = faults.inject("serve.ep_dispatch",
                            FailAfterN(fail_after, times=1))
        sup.run_until_complete(max_steps=4000)
        faults.clear()
        injected += pol.fired
        if pol.fired:
            assert sup.engine.ep_exec is not exec0, \
                "rebuilt engine carried the failed EP executor"
            assert sup.engine.ep_exec.ep == 2
        if pol.fired:
            # the rebuilt engine kept routing: expert load flowed
            # after the restart (an imbalanced-router signal that
            # survives chaos is a working signal) — counted only for
            # iterations whose fault actually fired, so a
            # never-restarted run cannot mask a dead-router rebuild
            expert_tokens_after += sum(
                sup.engine.ep_exec.expert_tokens)
        leaked += sup.engine.paged_arena.blocks_used
        for (p, n), h, want in zip(workload, handles, base):
            if not h.done():
                wedged += 1
                continue
            try:
                got = h.result().tokens
                assert np.array_equal(got, want), \
                    "EP token stream diverged after restart"
                completed += 1
            except EngineFailedError:
                typed_failed += 1
        sup.close()

    restarts = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0) - restarts0
    report["serve_ep"] = {
        "requests": 2 * len(workload),
        "expert_shards": 2,
        "completed_with_parity": completed,
        "typed_failures": typed_failed,
        "wedged_or_lost": wedged,
        "blocks_leaked": int(leaked),
        "dispatch_faults_injected": injected,
        "engine_restarts": restarts,
        "expert_tokens_after_restart": int(expert_tokens_after),
    }
    assert wedged == 0, f"{wedged} EP requests wedged/lost"
    assert leaked == 0, f"{leaked} EP pool blocks leaked"
    assert completed + typed_failed == 2 * len(workload)
    assert completed > 0 and typed_failed > 0
    assert expert_tokens_after > 0
    assert restarts == injected > 0, \
        f"restarts ({restarts}) != injected EP faults ({injected})"


def chaos_pp(report):
    """A fault at the ``serve.pp_boundary`` site (every sharded
    dispatch of a pipeline-parallel engine checks it — a raising
    stage-boundary hop) fires mid-decode: the pipelined engine fails
    TYPED — never wedges — and the supervisor rebuilds it on the SAME
    stage group (twin-cache hit, fresh stage-sliced pool).  Requeued
    never-started streams keep byte parity with the uninterrupted
    single-device paged run; started requests fail typed; the rebuilt
    pool drains to ZERO used blocks.  Zero wedged/lost/leaked,
    restarts == injected."""
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.observe.registry import registry
    from singa_tpu.resilience import FailAfterN, faults
    from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                                 GenerationRequest, PagedConfig)

    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)

    rng = np.random.RandomState(17)
    workload = [(rng.randint(0, 256, rng.randint(4, 12))
                 .astype(np.int32), int(rng.randint(4, 10)))
                for _ in range(10)]
    base = [np.asarray(m.generate(p, max_new_tokens=n,
                                  temperature=0.0))
            for p, n in workload]

    injected = 0
    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    completed = wedged = typed_failed = leaked = 0
    for fail_after in (4, 9):
        sup = EngineSupervisor(
            m, max_slots=2, restart_budget=2, pp=2,
            paged=PagedConfig(block_size=8, num_blocks=32))
        exec0 = sup.engine.pp_exec
        handles = [sup.submit(GenerationRequest(
            p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]
        pol = faults.inject("serve.pp_boundary",
                            FailAfterN(fail_after, times=1))
        sup.run_until_complete(max_steps=4000)
        faults.clear()
        injected += pol.fired
        if pol.fired:
            assert sup.engine.pp_exec is not exec0, \
                "rebuilt engine carried the failed PP executor"
            assert sup.engine.pp_exec.stages == 2
        leaked += sup.engine.paged_arena.blocks_used
        for (p, n), h, want in zip(workload, handles, base):
            if not h.done():
                wedged += 1
                continue
            try:
                got = h.result().tokens
                assert np.array_equal(got, want), \
                    "PP token stream diverged after restart"
                completed += 1
            except EngineFailedError:
                typed_failed += 1
        sup.close()

    restarts = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0) - restarts0
    report["serve_pp"] = {
        "requests": 2 * len(workload),
        "stages": 2,
        "completed_with_parity": completed,
        "typed_failures": typed_failed,
        "wedged_or_lost": wedged,
        "blocks_leaked": int(leaked),
        "boundary_faults_injected": injected,
        "engine_restarts": restarts,
    }
    assert wedged == 0, f"{wedged} PP requests wedged/lost"
    assert leaked == 0, f"{leaked} PP pool blocks leaked"
    assert completed + typed_failed == 2 * len(workload)
    assert completed > 0 and typed_failed > 0
    assert restarts == injected > 0, \
        f"restarts ({restarts}) != injected PP faults ({injected})"


def chaos_longctx(report):
    """A fault BETWEEN budgeted prefill chunks (the
    ``serve.prefill_chunk`` site, armed while a 72-token admission is
    mid-split under ``prefill_token_budget``): the engine fails TYPED
    mid-prefill — the chunked request has streamed NOTHING, so it
    rejects requeue-safe and the supervisor replays it to byte parity
    on the rebuilt engine; the partial chunks' blocks return to the
    free list (zero leaked on the failed engine AND zero on the
    drained rebuild).  Zero wedged/lost, restarts == injected."""
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.observe.registry import registry
    from singa_tpu.resilience import FailAfterN, faults
    from singa_tpu.serve import (EngineFailedError, EngineSupervisor,
                                 GenerationRequest, PagedConfig)

    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)

    rng = np.random.RandomState(9)
    # one long document + chat tails: the long admission's 9 chunks
    # (72 tokens at an 8-token budget) are where the fault lands
    workload = [(rng.randint(0, 256, 72).astype(np.int32), 3)] + \
        [(rng.randint(0, 256, rng.randint(4, 10)).astype(np.int32),
          int(rng.randint(3, 7))) for _ in range(5)]
    base = [np.asarray(m.generate(p, max_new_tokens=n,
                                  temperature=0.0))
            for p, n in workload]

    pcfg = PagedConfig(block_size=8, num_blocks=32,
                       prefill_token_budget=8)
    injected = 0
    restarts0 = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0)
    completed = wedged = typed_failed = 0
    for fail_after in (3, 6):
        sup = EngineSupervisor(m, max_slots=3, restart_budget=2,
                               paged=pcfg)
        arena0 = sup.engine.paged_arena
        handles = [sup.submit(GenerationRequest(
            p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]
        pol = faults.inject("serve.prefill_chunk",
                            FailAfterN(fail_after, times=1))
        sup.run_until_complete(max_steps=4000)
        faults.clear()
        injected += pol.fired
        if pol.fired:
            assert sup.engine.paged_arena is not arena0, \
                "rebuilt engine carried the old paged arena"
            assert arena0.blocks_used == 0, \
                f"failed engine leaked {arena0.blocks_used} blocks " \
                f"behind partial prefill chunks"
        pg = sup.engine.stats.snapshot()["paged"]
        assert pg["blocks_used"] == 0, \
            f"drained longctx engine leaked {pg['blocks_used']} blocks"
        for (p, n), h, want in zip(workload, handles, base):
            if not h.done():
                wedged += 1
                continue
            try:
                got = h.result().tokens
                assert np.array_equal(got, want), \
                    "budgeted-prefill stream diverged after restart"
                completed += 1
            except EngineFailedError:
                typed_failed += 1
        sup.close()

    restarts = registry().snapshot()["counters"].get(
        "resilience.engine_restarts", 0) - restarts0
    report["serve_longctx"] = {
        "requests": 2 * len(workload),
        "completed_with_parity": completed,
        "typed_failures": typed_failed,
        "wedged_or_lost": wedged,
        "chunk_faults_injected": injected,
        "engine_restarts": restarts,
        "blocks_leaked": 0,
        "prefill_token_budget": pcfg.prefill_token_budget,
    }
    assert wedged == 0, f"{wedged} longctx requests wedged/lost"
    assert completed + typed_failed == 2 * len(workload)
    assert completed > 0
    assert restarts == injected > 0, \
        f"restarts ({restarts}) != injected chunk faults ({injected})"


def chaos_fleet(report):
    """Kill one replica mid-decode (``serve.decode_step`` fault against
    a zero restart budget): the fleet marks it unhealthy, requeues its
    never-started requests onto the survivor in arrival order (token-
    stream parity vs an uninterrupted single-engine run), started
    requests fail typed, the fleet KEEPS SERVING on the survivor — and
    the jit cache stays pinned at zero runtime recompiles across the
    failover (replicas share every executable)."""
    from bench_serve import _serve_jit_cache_size
    from singa_tpu import observe, tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.resilience import FailAfterN, faults
    from singa_tpu.serve import (EngineFailedError, GenerationRequest,
                                 ServeFleet)

    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)

    rng = np.random.RandomState(3)
    workload = [(rng.randint(0, 256, rng.randint(3, 12)).astype(np.int32),
                 int(rng.randint(3, 8))) for _ in range(12)]
    extra = [(rng.randint(0, 256, rng.randint(3, 10)).astype(np.int32),
              int(rng.randint(2, 6))) for _ in range(4)]
    base = [np.asarray(m.generate(p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]
    base_extra = [np.asarray(m.generate(p, max_new_tokens=n,
                                        temperature=0.0))
                  for p, n in extra]

    def build():
        return ServeFleet(m, replicas=2, max_slots=2, restart_budget=0)

    # warmup: compile every executable the fleet dispatches, then pin
    # the jit cache across the whole chaos run
    fleet = build()
    for p, n in workload:
        fleet.submit(GenerationRequest(p, max_new_tokens=n))
    fleet.run_until_complete(max_steps=4000)
    fleet.close()
    jit0 = _serve_jit_cache_size()

    fleet = build()
    handles = [fleet.submit(GenerationRequest(
        p, max_new_tokens=n, temperature=0.0)) for p, n in workload]
    pol = faults.inject("serve.decode_step", FailAfterN(4, times=1))
    fleet.run_until_complete(max_steps=4000)
    faults.clear()

    completed = wedged = typed_failed = 0
    for (p, n), h, want in zip(workload, handles, base):
        if not h.done():
            wedged += 1
            continue
        try:
            got = h.result().tokens
            assert np.array_equal(got, want), \
                "token stream diverged across the failover"
            completed += 1
        except EngineFailedError:
            typed_failed += 1
    snap = fleet.snapshot()

    # service-level availability: the survivor keeps admitting and
    # completing new work after the failover
    hs2 = [fleet.submit(GenerationRequest(
        p, max_new_tokens=n, temperature=0.0)) for p, n in extra]
    fleet.run_until_complete(max_steps=2000)
    post_completed = sum(
        bool(np.array_equal(h.result().tokens, want))
        for h, want in zip(hs2, base_extra))
    jit1 = _serve_jit_cache_size()

    # the fleet health section reflects the failover BEFORE close
    # unregisters this fleet's metrics
    h_fleet = observe.health_report(
        include_registry=False)["serve"]["fleet"]
    assert h_fleet["failovers"] >= 1 and h_fleet["requeues"] >= 1
    assert h_fleet["replicas_healthy"] == 1
    fleet.close()

    report["serve_fleet"] = {
        "replicas": 2,
        "requests": len(workload),
        "completed_with_parity": completed,
        "typed_failures": typed_failed,
        "wedged_or_lost": wedged,
        "decode_faults_injected": pol.fired,
        "failovers": snap["failovers"],
        "requeues": snap["requeues"],
        "replicas_healthy_after": snap["replicas_healthy"],
        "post_failover_requests": len(extra),
        "post_failover_completed": post_completed,
        "recompiles": (None if jit0 is None else jit1 - jit0),
    }
    sf = report["serve_fleet"]
    assert wedged == 0, f"{wedged} requests wedged/lost"
    assert completed + typed_failed == len(workload)
    assert completed > 0 and typed_failed > 0
    assert sf["decode_faults_injected"] == 1 and sf["failovers"] == 1
    assert sf["requeues"] >= 1, "no never-started work moved — the " \
        "failover path was not exercised"
    assert sf["replicas_healthy_after"] == 1
    assert post_completed == len(extra), \
        "survivor stopped serving after the failover"
    assert sf["recompiles"] in (0, None), sf["recompiles"]


def chaos_disagg(report):
    """Disaggregated fleet under fire, two scenarios on a
    2-replica prefill/decode fleet:

    (a) an injected ``serve.kv_ship`` fault mid-transfer — the ship
        aborts, the request is requeued COLD onto the decode replica
        (byte parity: nothing streamed during a ship), zero leaked
        blocks on either replica, both replicas stay healthy (a ship
        fault is a transfer failure, not an engine death);
    (b) a ``serve.prefill_chunk`` fault with a ZERO restart budget
        KILLS the prefill specialist mid-build — the fleet fails it
        over, the mid-ship request (and everything queued) completes
        cold on the decode replica with parity, the dead arena holds
        zero blocks behind the partial build.

    Zero wedged/lost across both."""
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.resilience import FailOnce, faults
    from singa_tpu.serve import (GenerationRequest, PagedConfig,
                                 PrefixCacheConfig, ServeFleet)

    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)

    rng = np.random.RandomState(15)
    workload = [(rng.randint(0, 256, 48).astype(np.int32), 3)] + \
        [(rng.randint(0, 256, rng.randint(3, 7)).astype(np.int32),
          int(rng.randint(2, 5))) for _ in range(4)]
    base = [np.asarray(m.generate(p, max_new_tokens=n,
                                  temperature=0.0))
            for p, n in workload]
    kw = dict(roles=("prefill", "decode"), max_slots=2,
              paged=PagedConfig(block_size=8, num_blocks=48),
              prefix_cache=PrefixCacheConfig(block_size=8))

    def run(site, restart_budget):
        fleet = ServeFleet(m, replicas=2, restart_budget=restart_budget,
                           **kw)
        pol = faults.inject(site, FailOnce())
        handles = [fleet.submit(GenerationRequest(
            p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]
        fleet.run_until_complete(max_steps=800)
        faults.clear()
        completed = wedged = 0
        for h, want in zip(handles, base):
            if not h.done():
                wedged += 1
                continue
            got = h.result().tokens
            assert np.array_equal(got, want), \
                "disagg stream diverged across the fault"
            completed += 1
        leaked = sum(
            fleet.supervisor(i).engine.paged_arena.blocks_used
            - fleet.supervisor(i).engine.prefix_cache.cached_blocks
            for i in range(2)
            if not fleet.supervisor(i).engine._closed)
        snap = fleet.snapshot()
        arena0 = fleet.supervisor(0).engine.paged_arena
        fleet.close()
        return pol.fired, completed, wedged, leaked, snap, arena0

    # (a) mid-transfer ship fault: cold requeue, nobody dies
    ship_fired, comp_a, wedged_a, leak_a, snap_a, _ = run(
        "serve.kv_ship", restart_budget=2)
    assert snap_a["replicas_healthy"] == 2
    assert snap_a["ship_fallbacks"] >= 1
    # (b) specialist killed mid-build: failover, cold completion
    chunk_fired, comp_b, wedged_b, leak_b, snap_b, arena0 = run(
        "serve.prefill_chunk", restart_budget=0)
    assert snap_b["replicas_healthy"] == 1
    assert snap_b["failovers"] == 1
    assert arena0.blocks_used == 0, \
        f"dead specialist leaked {arena0.blocks_used} blocks"

    report["serve_disagg"] = {
        "requests": 2 * len(workload),
        "completed_with_parity": comp_a + comp_b,
        "wedged_or_lost": wedged_a + wedged_b,
        "ship_faults_injected": ship_fired,
        "chunk_faults_injected": chunk_fired,
        "failovers": snap_b["failovers"],
        "ship_fallbacks": (snap_a["ship_fallbacks"]
                           + snap_b["ship_fallbacks"]),
        "blocks_leaked": leak_a + leak_b,
    }
    sd = report["serve_disagg"]
    assert sd["wedged_or_lost"] == 0, \
        f"{sd['wedged_or_lost']} disagg requests wedged/lost"
    assert sd["completed_with_parity"] == sd["requests"]
    assert sd["ship_faults_injected"] == 1
    assert sd["chunk_faults_injected"] == 1
    assert sd["blocks_leaked"] == 0, sd["blocks_leaked"]


def _dist_model_spec():
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.serve import gpt2_spec

    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)
    return m, gpt2_spec(m)


def _dist_leaks(fleet):
    """Wire-level leak count: the step reply mirrors blocks_used AND
    cached_blocks parent-side, so the invariant is checkable without
    reaching into worker engines."""
    total = 0
    for i in range(fleet.replicas):
        eng = fleet.supervisor(i).engine
        if eng._closed or eng.paged_arena is None:
            continue
        total += (eng.paged_arena.blocks_used
                  - eng.prefix_cache.cached_blocks)
    return total


def chaos_dist_partition(report):
    """A PARTITIONED peer mid-decode (the dist round): the injected
    ``serve.dist.rpc`` fault fires on a step RPC exactly where a real
    network split would — the peer is marked down through the same
    PeerGone -> failover path, never-started work requeues onto the
    survivor with byte parity (nothing had streamed), and the
    role-aware autoscaler's ``replace_dead`` heals the fleet back to
    width with a FRESH worker that then serves traffic.  Workers run
    as threads here (same wire protocol and fault sites as processes;
    the chaos matrix stays bounded-time)."""
    from singa_tpu.resilience import FailOnce, faults
    from singa_tpu.serve import DistFleet, GenerationRequest
    from singa_tpu.serve.autoscale import AutoscaleConfig, Autoscaler

    m, spec = _dist_model_spec()
    rng = np.random.RandomState(21)
    workload = [(rng.randint(0, 256, rng.randint(3, 7)).astype(np.int32),
                 int(rng.randint(2, 5))) for _ in range(5)]
    base = [np.asarray(m.generate(p, max_new_tokens=n,
                                  temperature=0.0))
            for p, n in workload]

    fleet = DistFleet(spec, replicas=2, spawn="thread", max_slots=2)
    pol = faults.inject("serve.dist.rpc", FailOnce())
    handles = [fleet.submit(GenerationRequest(
        p, max_new_tokens=n, temperature=0.0))
        for p, n in workload]
    fleet.run_until_complete(max_steps=800)
    faults.clear()
    completed = wedged = 0
    for h, want in zip(handles, base):
        if not h.done():
            wedged += 1
            continue
        assert np.array_equal(h.result().tokens, want), \
            "dist stream diverged across the partition"
        completed += 1
    snap = fleet.snapshot()
    assert snap["replicas_healthy"] == 1, snap["replicas_healthy"]
    assert snap["failovers"] >= 1

    # the autoscaler replaces the dead peer on its next check, and
    # the fresh worker serves
    sc = Autoscaler(fleet, AutoscaleConfig(
        min_replicas=2, max_replicas=2,
        scale_up_cooldown_s=0.0, scale_down_cooldown_s=0.0))
    ev = sc.check()
    assert ev is not None and ev["action"] == "replace_dead", ev
    assert fleet.healthy_replicas == 2
    post = [fleet.submit(GenerationRequest(
        p, max_new_tokens=n, temperature=0.0))
        for p, n in workload[:3]]
    fleet.run_until_complete(max_steps=400)
    post_done = sum(
        1 for h, want in zip(post, base)
        if h.done() and np.array_equal(h.result().tokens, want))
    fleet.close()

    report["serve_dist_partition"] = {
        "replicas": 2,
        "requests": len(workload),
        "completed_with_parity": completed,
        "wedged_or_lost": wedged,
        "rpc_faults_injected": pol.fired,
        "failovers": snap["failovers"],
        "requeues": snap["requeues"],
        "replaced_dead": 1,
        "replicas_healthy_after": 2,
        "post_heal_requests": len(post),
        "post_heal_completed": post_done,
    }
    d = report["serve_dist_partition"]
    assert d["wedged_or_lost"] == 0, d
    assert d["completed_with_parity"] == d["requests"], d
    assert d["rpc_faults_injected"] == 1, d
    assert d["post_heal_completed"] == d["post_heal_requests"], d


def chaos_dist_halfship(report):
    """A HALF-SHIPPED image (the dist round): the transport dies
    between layers of a streamed cross-host ship — the injected
    ``serve.dist.frame`` fault fires mid-relay, the destination's
    staging buffer is aborted (typed, never admitted), the request
    falls back to a cold serve with byte parity, neither peer is
    condemned, and a LATER ship on the same fleet still streams
    clean.  Zero leaked blocks on both sides."""
    from singa_tpu.resilience import FailOnce, faults
    from singa_tpu.serve import (DistFleet, GenerationRequest,
                                 PagedConfig, PrefixCacheConfig)

    m, spec = _dist_model_spec()
    rng = np.random.RandomState(22)
    workload = [(rng.randint(0, 256, 48).astype(np.int32), 3),
                (rng.randint(0, 256, 48).astype(np.int32), 3)] + \
        [(rng.randint(0, 256, rng.randint(3, 7)).astype(np.int32),
          int(rng.randint(2, 5))) for _ in range(2)]
    base = [np.asarray(m.generate(p, max_new_tokens=n,
                                  temperature=0.0))
            for p, n in workload]

    fleet = DistFleet(
        spec, replicas=2, spawn="thread",
        roles=("prefill", "decode"), max_slots=2,
        paged=PagedConfig(block_size=8, num_blocks=48),
        prefix_cache=PrefixCacheConfig(block_size=8))
    pol = faults.inject("serve.dist.frame", FailOnce())
    handles = [fleet.submit(GenerationRequest(
        p, max_new_tokens=n, temperature=0.0))
        for p, n in workload]
    fleet.run_until_complete(max_steps=800)
    faults.clear()
    completed = wedged = 0
    for h, want in zip(handles, base):
        if not h.done():
            wedged += 1
            continue
        assert np.array_equal(h.result().tokens, want), \
            "dist stream diverged across the half-ship"
        completed += 1
    snap = fleet.snapshot()
    leaked = _dist_leaks(fleet)
    fleet.close()

    report["serve_dist_halfship"] = {
        "replicas": 2,
        "requests": len(workload),
        "completed_with_parity": completed,
        "wedged_or_lost": wedged,
        "frame_faults_injected": pol.fired,
        "ship_fallbacks": snap["ship_fallbacks"],
        "frames_relayed": snap["dist"]["frames"],
        "replicas_healthy_after": snap["replicas_healthy"],
        "blocks_leaked": leaked,
    }
    d = report["serve_dist_halfship"]
    assert d["wedged_or_lost"] == 0, d
    assert d["completed_with_parity"] == d["requests"], d
    assert d["frame_faults_injected"] == 1, d
    assert d["ship_fallbacks"] >= 1, d
    assert d["replicas_healthy_after"] == 2, d
    assert d["frames_relayed"] > 0, \
        "the post-fault ship never streamed — the fleet stayed cold"
    assert d["blocks_leaked"] == 0, d


def chaos_dist_blip(report):
    """A transient NETWORK BLIP mid-decode (the recover round): the
    controller-side socket is severed without the worker knowing — the
    worker redials with full-jitter backoff, the session RESUMES
    inside the reconnect window (same seq space, same epoch), and the
    one in-flight step CALL replays exactly-once against the worker's
    reply cache.  The hard numbers: ZERO failovers, ZERO requeues,
    ZERO respawns — the fleet never even noticed at the routing layer
    — and every stream is byte-identical to the single-model oracle."""
    from singa_tpu.serve import DistFleet, GenerationRequest

    m, spec = _dist_model_spec()
    rng = np.random.RandomState(23)
    workload = [(rng.randint(0, 256, rng.randint(3, 7)).astype(np.int32),
                 int(rng.randint(3, 6))) for _ in range(5)]
    base = [np.asarray(m.generate(p, max_new_tokens=n,
                                  temperature=0.0))
            for p, n in workload]

    fleet = DistFleet(spec, replicas=2, spawn="thread", max_slots=2)
    handles = [fleet.submit(GenerationRequest(
        p, max_new_tokens=n, temperature=0.0))
        for p, n in workload]
    for _ in range(3):
        fleet.step()           # decode is genuinely mid-flight
    fleet.blip_worker(0)
    fleet.run_until_complete(max_steps=800)
    completed = wedged = 0
    for h, want in zip(handles, base):
        if not h.done():
            wedged += 1
            continue
        assert np.array_equal(h.result().tokens, want), \
            "dist stream diverged across the blip"
        completed += 1
    snap = fleet.snapshot()
    respawns = sum(fleet.supervisor(i).restarts
                   for i in range(fleet.replicas))
    fleet.close()

    report["serve_dist_blip"] = {
        "replicas": 2,
        "requests": len(workload),
        "completed_with_parity": completed,
        "wedged_or_lost": wedged,
        "reconnects": snap["dist"]["reconnects"],
        "resumed_calls": snap["dist"]["resumed_calls"],
        "epoch": snap["dist"]["epoch"],
        "failovers": snap["failovers"],
        "requeues": snap["requeues"],
        "respawns": respawns,
        "replicas_healthy_after": snap["replicas_healthy"],
    }
    d = report["serve_dist_blip"]
    assert d["wedged_or_lost"] == 0, d
    assert d["completed_with_parity"] == d["requests"], d
    assert d["reconnects"] >= 1, d
    assert d["resumed_calls"] >= 1, d
    assert d["epoch"] == 1, d              # a resume, not an adoption
    assert d["failovers"] == 0, d
    assert d["requeues"] == 0, d
    assert d["respawns"] == 0, d
    assert d["replicas_healthy_after"] == 2, d


def chaos_dist_controller(report):
    """CONTROLLER CRASH + fenced adoption (the recover round's
    tentpole): the controller dies mid-flight with every request still
    decoding — no shutdown RPCs, no drains.  The orphaned workers keep
    stepping, journal progress, and redial; a successor controller
    ADOPTS them at their old address — fencing epoch bumped to 2 (the
    dead controller is refused typed on every op from that moment),
    journals reconciled (live work re-attached, parked results
    claimed exactly-once, never-started work requeued in arrival
    order, nothing rejected), and routing resumes against engines that
    were NEVER rebuilt.  The hard numbers: zero lost tokens, zero
    duplicated tokens (byte parity per request), zero wedged, zero
    recompiles (the jit cache is the same size after adoption — warm
    engines survived the controller)."""
    from singa_tpu.serve import DistFleet, GenerationRequest
    from singa_tpu.serve.jitpin import jit_cache_size

    m, spec = _dist_model_spec()
    rng = np.random.RandomState(24)
    workload = [(rng.randint(0, 256, rng.randint(3, 7)).astype(np.int32),
                 int(rng.randint(4, 7))) for _ in range(5)]
    base = [np.asarray(m.generate(p, max_new_tokens=n,
                                  temperature=0.0))
            for p, n in workload]

    A = DistFleet(spec, replicas=2, spawn="thread", max_slots=2)
    port, token = A._listener.port, A._token
    handles = [A.submit(GenerationRequest(
        p, max_new_tokens=n, temperature=0.0))
        for p, n in workload]
    for _ in range(2):
        A.step()
    assert not any(h.done() for h in handles), \
        "crash must land mid-flight for the scenario to mean anything"
    jit_before = jit_cache_size()
    A.crash()

    B = DistFleet.adopt(spec, port=port, token=token, replicas=2,
                        spawn="thread", max_slots=2)
    rep = B.adoption
    assert rep["rejected"] == {}, rep["rejected"]
    adopted = dict(rep["resumed"])
    adopted.update(rep["delivered"])
    adopted.update(rep["requeued"])
    B.run_until_complete(max_steps=800)
    completed = wedged = 0
    for h, want in zip(handles, base):
        rid = h.request.request_id
        bh = adopted.get(rid)
        if bh is None or not bh.done():
            wedged += 1
            continue
        # byte parity == zero lost AND zero duplicated tokens: any
        # replayed decode step would append a duplicate, any dropped
        # parked result would truncate the stream
        assert np.array_equal(bh.result().tokens, want), \
            "dist stream diverged across the controller adoption"
        completed += 1
    snap = B.snapshot()
    recompiles = jit_cache_size() - jit_before
    B.close()

    report["serve_dist_controller"] = {
        "replicas": 2,
        "requests": len(workload),
        "completed_with_parity": completed,
        "wedged_or_lost": wedged,
        "adopted_resumed": len(rep["resumed"]),
        "adopted_delivered": len(rep["delivered"]),
        "adopted_requeued": len(rep["requeued"]),
        "adopted_rejected": len(rep["rejected"]),
        "parked_results": snap["dist"]["parked_results"],
        "epoch_after": snap["dist"]["epoch"],
        "recompiles": recompiles,
        "replicas_healthy_after": snap["replicas_healthy"],
    }
    d = report["serve_dist_controller"]
    assert d["wedged_or_lost"] == 0, d
    assert d["completed_with_parity"] == d["requests"], d
    assert (d["adopted_resumed"] + d["adopted_delivered"]
            + d["adopted_requeued"]) == d["requests"], d
    assert d["adopted_rejected"] == 0, d
    assert d["epoch_after"] == 2, d
    assert d["recompiles"] == 0, \
        f"adoption recompiled {d['recompiles']} entries — the warm " \
        f"engines were not actually adopted"
    assert d["replicas_healthy_after"] == 2, d


def chaos_autoscale(report):
    """Fault the ``serve.autoscale`` site mid-scale-up (the autoscale
    round): the scaling DECISION aborts typed — ledger records
    ``scale_up_failed``, no half-registered replica exists (replica
    count and fleet counter families unchanged), the fleet keeps
    serving on its existing replica — and the next check simply
    retries and succeeds.  After the burst drains, the autoscaler
    drains the spare replica back down and the retired engine's
    ``serve.*{engine=n}`` series leave the registry (the scale-down
    leaked-gauge audit, same hazard class as the EP/PP refusal
    audits)."""
    from singa_tpu import observe, tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.resilience import FailOnce, faults
    from singa_tpu.serve import (AutoscaleConfig, Autoscaler,
                                 GenerationRequest, ServeFleet)

    cfg = GPT2Config.tiny(dropout=0.0)
    m = GPT2LMHead(cfg)
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32))],
              is_train=False, use_graph=False)

    rng = np.random.RandomState(21)
    workload = [(rng.randint(0, 256, rng.randint(3, 12)).astype(np.int32),
                 int(rng.randint(3, 7))) for _ in range(12)]
    base = [np.asarray(m.generate(p, max_new_tokens=n, temperature=0.0))
            for p, n in workload]

    T = [0.0]
    fleet = ServeFleet(m, replicas=1, max_slots=2,
                       clock=lambda: T[0])
    sc = Autoscaler(fleet, AutoscaleConfig(
        min_replicas=1, max_replicas=2, scale_up_cooldown_s=1.0,
        scale_down_cooldown_s=2.0, queue_high=2.0, queue_low=0.5,
        occupancy_high=0.95, occupancy_low=0.45),
        clock=lambda: T[0])
    handles = [fleet.submit(GenerationRequest(
        p, max_new_tokens=n, temperature=0.0)) for p, n in workload]

    def fleet_counter_sets():
        snap = observe.registry().snapshot()
        return sorted(
            k for k in snap["counters"]
            if k.startswith("serve.fleet.routed{")
            and f"fleet={fleet.fleet_label}" in k)

    counters_before = fleet_counter_sets()
    pol = faults.inject("serve.autoscale", FailOnce())
    ev1 = sc.check()
    assert ev1 is not None and ev1["action"] == "scale_up_failed", ev1
    assert pol.fired == 1
    # no half-registered replica: same replica count, same fleet
    # counter families, the lone replica still serving
    assert fleet.replicas == 1
    assert fleet_counter_sets() == counters_before
    for _ in range(3):
        fleet.step()
    T[0] += 0.5
    ev2 = sc.check()  # the retry: no cooldown was spent on the abort
    assert ev2 is not None and ev2["action"] == "scale_up", ev2
    faults.clear()
    assert fleet.replicas == 2

    while fleet.pending:
        fleet.step()
        T[0] += 0.5
        sc.check()
    completed = sum(
        bool(np.array_equal(h.result().tokens, want))
        for h, want in zip(handles, base))
    wedged = sum(1 for h in handles if not h.done())

    # all-quiet: the spare replica drains and retires (the decision
    # ledger is the evidence — the drain may already have completed
    # during the serving loop's checks)
    for _ in range(16):
        if any(e["action"] == "drain_done"
               for e in sc.scaling_events):
            break
        T[0] += 1.0
        sc.check()
    assert any(e["action"] == "drain_done"
               for e in sc.scaling_events), \
        [e["action"] for e in sc.scaling_events]
    retired = [r for r in fleet._replicas if r.retired]
    assert len(retired) == 1
    # leaked-gauge audit: the retired engine's label series must be
    # GONE from the registry, not frozen at their last values
    lbl = f"engine={retired[0].sup.engine.stats.engine_label}"
    snap = observe.registry().snapshot()
    leaked = [k for sec in snap.values() for k in sec if lbl in k]
    assert not leaked, leaked
    actions = [e["action"] for e in sc.scaling_events]
    sc.close()
    fleet.close()

    report["serve_autoscale"] = {
        "requests": len(workload),
        "completed_with_parity": completed,
        "wedged_or_lost": wedged,
        "autoscale_faults_injected": pol.fired,
        "decisions_failed": 1,
        "scale_ups": actions.count("scale_up"),
        "scale_downs": actions.count("drain_done"),
        "actions": actions,
        "leaked_series": len(leaked),
    }
    sa = report["serve_autoscale"]
    assert sa["wedged_or_lost"] == 0, sa
    assert sa["completed_with_parity"] == len(workload), sa
    assert sa["autoscale_faults_injected"] == 1
    assert sa["scale_ups"] == 1 and sa["scale_downs"] == 1


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="CHAOS.json", metavar="PATH",
                    help="where to write the strict-JSON chaos report")
    args = ap.parse_args()

    # chaos_tp needs a >=2-device mesh before jax initializes; the
    # flag only affects the CPU platform (tests/conftest.py topology)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    from singa_tpu import observe

    # the whole chaos run is monitored: recovery that hangs is failure
    observe.monitor.start(watchdog_timeout_s=900.0, crash_handler=True)
    report = {"bench": "chaos_resilience", "schema": "singa_tpu.chaos/1"}
    chaos_checkpoint(report)
    chaos_collective(report)
    chaos_serve(report)
    chaos_prefix(report)
    chaos_spec(report)
    chaos_paged(report)
    chaos_fork(report)
    chaos_longctx(report)
    chaos_tp(report)
    chaos_ep(report)
    chaos_pp(report)
    chaos_fleet(report)
    chaos_disagg(report)
    chaos_dist_partition(report)
    chaos_dist_halfship(report)
    chaos_dist_blip(report)
    chaos_dist_controller(report)
    chaos_autoscale(report)

    health = observe.health_report(include_registry=False)
    report["health"] = health
    assert health["watchdog"]["hangs"] == 0, "chaos run tripped the " \
        "hang watchdog — recovery wedged somewhere"
    assert health["resilience"]["engine_restarts"] >= \
        report["serve"]["engine_restarts"]
    observe.monitor.stop()

    line = json.dumps(observe.export.json_sanitize(report),
                      default=str, allow_nan=False)
    print(line)
    with open(args.out, "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
