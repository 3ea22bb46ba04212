"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full published width of GPT-2 small (12 layers, hidden 768, 12 heads,
vocab 50257, 1024 positions; seeded random weights), in ONE process:

1. **train** — ``GPT2LMHead`` on ``device.create_tpu_device(0)``, bf16
   amp, ``Model.compile(is_train=True, use_graph=True)``, batch 8 x 1024:
   three optimizer steps plus one ``train_n_batches(n_steps=2)``, with
   the Pallas flash kernels (forward, dq, dk/dv) compiled by Mosaic.
2. **serve** — ``model.serve(paged=PagedConfig(...), dtype=bfloat16,
   max_slots=8)``: six ragged requests (whole-prompt prefill, paged
   decode, two arrivals after stepping began), then three more under a
   prefill token budget (chunked prefill); each engine serves its
   traffic twice and the second wave must compile nothing.
3. **four chips** — only where JAX shows at least four: DistOpt
   data-parallel training and ``model.serve(tp=4)`` across them.  On a
   smaller machine the phase is printed as *not run*, never as ok.

It asserts rather than assumes: every array it checks must sit on a TPU
device, the compiled train step must contain the Mosaic custom call, and
what the server emits must agree with a plain float32 ``jax.numpy``
forward of the same weights (``reference_logits`` below).  Any failed
check or raised exception ends the process with a non-zero code and no
result line; there is no path that continues on a CPU and no handler
around a phase.  It starts no other process, times nothing for the
record and writes into no file of the repository.

    python chip_smoke.py      # last stdout line: {"ok": true, "device": ...}
"""

import gc
import importlib.metadata
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SEED = 0
BATCH, SEQ = 8, 1024          # the bench.py GPT-2 training shape
TRAIN_STEPS, SCAN_STEPS = 3, 2

# Flash vs fused at step 1, same seeded weights and batch.  Both run
# bf16 matmuls with f32 accumulation and hand back bf16 logits; they
# differ in the order of the softmax reduction and in where the
# probabilities are rounded to bf16.  Tolerance: four bf16 ulps (2^-8
# relative each) of the logits' own scale, and 2e-4 on the mean loss
# over 8192 tokens.  Measured on the v5e (PR 21): logits max|d| 0.0156
# at scale 2.72 — one ulp — and loss |d| 1.0e-5.  A kernel that dropped
# the causal mask or a key block moves the logits by O(scale).
LOGIT_RTOL_FLASH_VS_FUSED = 2.0 ** -6
LOSS_ATOL_FLASH_VS_FUSED = 2e-4

# Serve path (bf16 weights and activations through 12 layers, f32
# accumulation and f32 LayerNorm statistics) vs the float32 reference
# under matmul precision "highest".  Rounding weights and activations
# to bf16 costs 2^-9 relative per operand; through 12 residual blocks
# the logits of this randomly initialised model (scale 2.6) landed
# within 0.021 of the reference on the v5e (PR 21; emitted tokens
# within 0.017 of the reference's best logit).  The bound is four times
# that: an fp8-class path (2^-4 relative), a wrong position or a
# dropped KV block exceeds it several times over.
LOGIT_ATOL_SERVE_VS_F32 = 0.08

# six requests: (prompt tokens, token budget, submitted after N steps)
REQUESTS = ((16, 8, 0), (90, 64, 0), (333, 32, 0), (700, 48, 0),
            (200, 16, 2), (520, 24, 5))
# the chunked-prefill engine: one prompt under the per-step token
# budget, two over it
PREFILL_TOKEN_BUDGET = 256
CHUNKED_REQUESTS = ((90, 8, 0), (700, 16, 0), (333, 12, 1))
BLOCK, NUM_BLOCKS, MAX_SLOTS = 32, 512, 8


def say(msg):
    print(msg, flush=True)


def check(cond, msg):
    """Raise (never ``assert``: -O must not turn the smoke into a no-op)."""
    if not cond:
        raise AssertionError(msg)


def check_on(tree, devices, what):
    """Every array leaf of ``tree`` lives on exactly ``devices``."""
    leaves = jax.tree.leaves(tree)
    check(leaves, f"{what}: nothing to check")
    for a in leaves:
        check(a.devices() == devices,
              f"{what}: a {a.shape} {a.dtype} leaf is on {a.devices()}, "
              f"expected {devices}")
    check(all(d.platform == "tpu" for d in devices),
          f"{what}: {devices} is not a set of TPU devices")
    return len(leaves)


# ---------------------------------------------------------------- reference


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def reference_weights(m):
    """The model's float32 weights, read off the layer objects (not via
    the decode path's ``extract_params``, so a mis-mapped weight there
    cannot hide)."""
    t = m.transformer
    f32 = lambda x: jnp.asarray(x.data, jnp.float32)  # noqa: E731
    blocks = [dict(
        ln1=(f32(b.ln1.scale), f32(b.ln1.bias)),
        q=(f32(b.attn.q_proj.W), f32(b.attn.q_proj.b)),
        k=(f32(b.attn.k_proj.W), f32(b.attn.k_proj.b)),
        v=(f32(b.attn.v_proj.W), f32(b.attn.v_proj.b)),
        o=(f32(b.attn.out_proj.W), f32(b.attn.out_proj.b)),
        ln2=(f32(b.ln2.scale), f32(b.ln2.bias)),
        fc1=(f32(b.mlp.fc1.W), f32(b.mlp.fc1.b)),
        fc2=(f32(b.mlp.fc2.W), f32(b.mlp.fc2.b))) for b in t.blocks]
    return dict(wte=f32(t.wte.W), wpe=f32(t.wpe.W), blocks=blocks,
                lnf=(f32(t.ln_f.scale), f32(t.ln_f.bias)))


def reference_logits(w, ids, n_head, eps):
    """GPT-2 forward as published (Radford et al. 2019: learned
    positions, pre-LayerNorm blocks, causal softmax attention scaled by
    1/sqrt(head_dim), tanh-approximated GELU MLP, final LayerNorm, tied
    output head) in plain ``jax.numpy`` float32 — no kernel, no cache, no
    batching.  ids (S,) int32 -> logits (S, V).  Call under
    ``jax.default_matmul_precision("highest")``: on a TPU a float32
    matmul otherwise runs in bf16 passes."""
    s = ids.shape[0]
    x = w["wte"][ids] + w["wpe"][:s]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for p in w["blocks"]:
        h = _ln(x, *p["ln1"], eps)
        q, k, v = (h @ p[n][0] + p[n][1] for n in "qkv")
        d = q.shape[-1] // n_head
        q, k, v = (a.reshape(s, n_head, d) for a in (q, k, v))
        sc = jnp.einsum("shd,thd->hst", q, k) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        a = jnp.einsum("hst,thd->shd", pr, v).reshape(s, -1)
        x = x + a @ p["o"][0] + p["o"][1]
        h = _ln(x, *p["ln2"], eps)
        h = jax.nn.gelu(h @ p["fc1"][0] + p["fc1"][1], approximate=True)
        x = x + h @ p["fc2"][0] + p["fc2"][1]
    return _ln(x, *w["lnf"], eps) @ w["wte"].T


# -------------------------------------------------------------------- train


def _train_batch(cfg, dev):
    from singa_tpu import tensor

    rng = np.random.RandomState(SEED)
    ids, labels = (tensor.from_numpy(
        rng.randint(0, cfg.vocab_size, (BATCH, SEQ)).astype(np.int32), dev)
        for _ in range(2))
    return ids, labels


def _train_model(dev, attn_impl, optimizer=None):
    """A seeded GPT-2 small trainer, compiled through the normal entry
    point.  The same seed gives the flash, fused and DistOpt models
    identical initial weights."""
    from singa_tpu import opt
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    dev.SetRandSeed(SEED)
    cfg = GPT2Config.small(n_positions=SEQ, dropout=0.0,
                           attn_impl=attn_impl)
    m = GPT2LMHead(cfg)
    m.set_optimizer(optimizer or opt.SGD(lr=1e-4, momentum=0.9))
    ids, labels = _train_batch(cfg, dev)
    m.compile([ids], is_train=True, use_graph=True, sequential=False)
    return m, ids, labels


def _check_loss(loss, vocab, what):
    check(np.all(np.isfinite(loss)), f"{what}: loss {loss} is not finite")
    band = 3.0 * math.log(vocab)
    check(np.all((loss > 0.0) & (loss < band)),
          f"{what}: loss {loss} outside the cross-entropy band "
          f"(0, {band:.2f})")


def _probe_rows(logits):
    """Rows of a (B, S, V) logits tensor to compare across attention
    implementations: the last position attends over every key block."""
    return np.asarray(logits.data[:, (0, SEQ // 2 - 1, SEQ - 1), :],
                      np.float32)


def train_phase(dev):
    """Returns (set-up seconds, run seconds, step-1 loss)."""
    from singa_tpu import amp

    d = {dev.jax_device}
    amp.enable(True)
    try:
        t0 = time.perf_counter()
        m, ids, labels = _train_model(dev, "auto")
        check(m.cfg.attn_impl == "flash",
              f"attn_impl='auto' at n_positions={SEQ} resolved to "
              f"{m.cfg.attn_impl!r}, not the flash kernel")
        logits, loss = m(ids, labels)          # compiles the step
        losses = [float(loss.data)]
        rows_flash = _probe_rows(logits)
        setup = time.perf_counter() - t0

        t0 = time.perf_counter()
        for _ in range(TRAIN_STEPS - 1):
            _, loss = m(ids, labels)
            losses.append(float(loss.data))
        run = time.perf_counter() - t0

        t0 = time.perf_counter()
        _, scan_loss = m.train_n_batches(ids, labels, n_steps=SCAN_STEPS)
        scan_loss = np.asarray(scan_loss.data)  # compiles the K-step scan
        setup += time.perf_counter() - t0

        _check_loss(np.asarray(losses), m.cfg.vocab_size, "train")
        check(scan_loss.shape == (SCAN_STEPS,),
              f"train_n_batches returned losses of shape {scan_loss.shape}")
        _check_loss(scan_loss, m.cfg.vocab_size, "train_n_batches")
        n = check_on([t.data for t in m.persistent_tensors().values()],
                     d, "train state after the steps")
        check_on(logits.data, d, "train step output")
        # compiled, not interpreted and not a reference route: one Mosaic
        # custom call per kernel (fwd, dq, dk/dv) per layer
        hlo = [ex.as_text() for ex in m._graph_runner.executables()]
        calls = [h.count('custom_call_target="tpu_custom_call"')
                 for h in hlo]
        check(len(hlo) == 2 and all(c >= 3 for c in calls),
              f"expected two compiled train executables each holding the "
              f"Mosaic flash kernels, found tpu_custom_call counts {calls}")
        say(f"  train: {TRAIN_STEPS} steps + scan of {SCAN_STEPS}, losses "
            f"{[round(v, 4) for v in losses]} + "
            f"{np.round(scan_loss, 4).tolist()}; {n} state leaves on "
            f"{dev.jax_device}; Mosaic custom calls per executable {calls}")

        # the same seeded model through the fused (XLA) attention path
        t0 = time.perf_counter()
        mf, ids_f, labels_f = _train_model(dev, "fused")
        logits_f, loss_f = mf(ids_f, labels_f)
        loss_f = float(loss_f.data)
        rows_fused = _probe_rows(logits_f)
        setup += time.perf_counter() - t0
        scale = float(np.abs(rows_fused).max())
        dl = float(np.abs(rows_flash - rows_fused).max())
        say(f"  flash vs fused, step 1: loss {losses[0]:.6f} vs "
            f"{loss_f:.6f}; logits max|d| {dl:.4f} at scale {scale:.2f}")
        check(abs(losses[0] - loss_f) <= LOSS_ATOL_FLASH_VS_FUSED,
              f"step-1 loss: flash {losses[0]} vs fused {loss_f} differ "
              f"by more than {LOSS_ATOL_FLASH_VS_FUSED}")
        check(dl <= LOGIT_RTOL_FLASH_VS_FUSED * scale,
              f"step-1 logits: flash vs fused max|d| {dl} exceeds "
              f"{LOGIT_RTOL_FLASH_VS_FUSED} x scale {scale}")
        return setup, run, losses[0]
    finally:
        amp.enable(False)


# -------------------------------------------------------------------- serve


def _serve_model(dev):
    from singa_tpu import tensor
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    dev.SetRandSeed(SEED + 1)
    m = GPT2LMHead(GPT2Config.small(dropout=0.0))
    m.compile([tensor.from_numpy(np.zeros((1, 16), np.int32), dev)],
              is_train=False, use_graph=False)
    return m


def _wave(eng, vocab, requests, seed):
    """Submit ``requests`` (late ones after their step count), drive the
    engine dry, and check every result.  Returns [(prompt, tokens)]."""
    from singa_tpu.serve import GenerationRequest

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, vocab, plen).astype(np.int32)
               for plen, _, _ in requests]
    handles = [None] * len(requests)
    steps = 0
    while any(h is None for h in handles) or eng.pending:
        for i, (_, budget, after) in enumerate(requests):
            if handles[i] is None and steps >= after:
                handles[i] = eng.submit(GenerationRequest(
                    prompts[i], max_new_tokens=budget, temperature=0.0,
                    seed=seed + i))
        eng.step()
        steps += 1
        check(steps < 2000, "serve: engine did not drain in 2000 steps")
    out = []
    for (plen, budget, _), prompt, h in zip(requests, prompts, handles):
        toks = np.asarray(h.result().tokens)
        check(len(toks) == plen + budget,
              f"serve: request with prompt {plen} and budget {budget} "
              f"returned {len(toks)} tokens")
        check(np.array_equal(toks[:plen], prompt),
              "serve: result does not start with its prompt")
        check(((toks >= 0) & (toks < vocab)).all(),
              "serve: token id out of range")
        out.append((prompt, toks))
    return out, steps


def _reference(m):
    """``ref(tokens) -> (len(tokens), V)`` float32 reference logits for
    one sequence of this model.  Right-padding is invisible to earlier
    positions (causal), so every sequence rides one compiled
    ``n_positions``-wide shape."""
    cfg = m.cfg
    w = reference_weights(m)
    # the weights are an argument, not a closure: closed over, half a
    # gigabyte of float32 would be baked into the executable as constants
    fwd = jax.jit(lambda w, ids: reference_logits(
        w, ids, cfg.n_head, cfg.layer_norm_eps))

    def ref(tokens):
        ids = np.zeros(cfg.n_positions, np.int32)
        ids[:len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(fwd(w, jnp.asarray(ids)))[:len(tokens)]
        check(np.isfinite(lg).all(), "reference logits are not finite")
        return lg

    return ref


def _check_against_reference(ref, results, what):
    """Prefill and then decoding through the cache must agree with the
    reference's full forward: every token the server emitted has to be a
    near-argmax of the float32 logits at its position — a comparison of
    logits, not tokens (with random weights the argmax flips on
    rounding)."""
    worst = 0.0
    for prompt, toks in results:
        lg = ref(toks)
        pos = np.arange(len(prompt) - 1, len(toks) - 1)
        gap = lg[pos].max(-1) - lg[pos, toks[pos + 1]]
        worst = max(worst, float(gap.max()))
    check(worst <= LOGIT_ATOL_SERVE_VS_F32,
          f"{what}: an emitted token sits {worst:.4f} below the float32 "
          f"reference's best logit (tolerance {LOGIT_ATOL_SERVE_VS_F32})")
    return worst


def _first_token_logits(m, ref, prompt):
    """The serve path's first-token logits for ``prompt`` — the bf16
    weights the engines hold (``extract_params`` hands every caller the
    same cached arrays) through the decode path's prefill, the body of
    the engine's ``_prefill_one`` — against the float32 reference.
    Returns (max|d|, reference scale)."""
    from singa_tpu.models import gpt2_decode as G

    cfg = m.cfg

    @jax.jit
    def sys_logits(params, ids):
        hidden, _, _ = G.prefill(params, ids[None], cfg.n_head,
                                 float(cfg.layer_norm_eps))
        return G._logits(hidden[:, -1:, :], params)[0, 0]

    got = np.asarray(sys_logits(G.extract_params(m, dtype=jnp.bfloat16),
                                jnp.asarray(prompt)), np.float32)
    want = ref(prompt)[-1]
    return float(np.abs(got - want).max()), float(np.abs(want).max())


def _paged(budget=None):
    from singa_tpu.serve import PagedConfig

    return PagedConfig(block_size=BLOCK, num_blocks=NUM_BLOCKS,
                       prefill_token_budget=budget)


def _serve_twice(m, dev, paged, requests, seed, what):
    """One engine, two waves of the same shapes.  Wave 1 compiles every
    shape the traffic touches; wave 2 (same lengths, budgets and
    arrivals, other tokens) must compile nothing — the engine's "never
    recompiles at run time" contract.  Returns ([(prompt, tokens)],
    wave-1 seconds, wave-2 seconds, facts about the engine)."""
    from singa_tpu.serve.jitpin import jit_cache_size

    d = {dev.jax_device}
    t0 = time.perf_counter()
    eng = m.serve(paged=paged, dtype=jnp.bfloat16, max_slots=MAX_SLOTS)
    try:
        held = [eng.paged_arena.pool_k, eng.paged_arena.pool_v, eng._keys]
        n = check_on(eng._params, d, f"{what}: engine params")
        check_on(held, d, f"{what}: KV pool and key table")
        first, _ = _wave(eng, m.cfg.vocab_size, requests, seed)
        size = jit_cache_size()
        check(size is not None, "serve: jit_cache_size() is unavailable")
        t1 = time.perf_counter()
        second, steps = _wave(eng, m.cfg.vocab_size, requests, seed + 50)
        t2 = time.perf_counter()
        check(jit_cache_size() == size,
              f"{what}: jit cache grew {size} -> {jit_cache_size()} on "
              f"traffic whose every shape was already seen")
        held = [eng.paged_arena.pool_k, eng.paged_arena.pool_v, eng._keys]
        check_on(held, d, f"{what}: KV pool and key table after serving")
        eng.check_block_accounting()
        facts = dict(params=n, pool=eng.paged_arena.pool_k.shape,
                     steps=steps, jit=size, prefills=eng.stats.prefills,
                     chunks=(eng._c_budget_chunks.value
                             if paged.prefill_token_budget else 0))
    finally:
        eng.close()
    return first + second, t1 - t0, t2 - t1, facts


def serve_phase(dev):
    """Returns (set-up seconds, run seconds)."""
    t0 = time.perf_counter()
    m = _serve_model(dev)
    setup = time.perf_counter() - t0

    # whole-prompt (cold) prefill at the engine's narrow widths + the
    # block-native paged decode kernel
    cold, s1, r1, f1 = _serve_twice(
        m, dev, _paged(), REQUESTS, 100, "serve")
    check(f1["prefills"] == 2 * len(REQUESTS) and f1["chunks"] == 0,
          f"serve: expected {2 * len(REQUESTS)} whole-prompt prefills, "
          f"got {f1}")
    say(f"  serve: 2 x {len(REQUESTS)} requests finished with their "
        f"budgets ({f1['steps']} steps in wave 2); {f1['params']} param "
        f"leaves, KV pool {f1['pool']} and keys on {dev.jax_device}; "
        f"jit cache flat at {f1['jit']}")
    # the same traffic's long prompts under a prefill token budget:
    # admission splits into block-width chunks across steps
    chunked, s2, r2, f2 = _serve_twice(
        m, dev, _paged(PREFILL_TOKEN_BUDGET), CHUNKED_REQUESTS, 300,
        "serve (chunked prefill)")
    want = 2 * sum(-(-plen // BLOCK) for plen, _, _ in CHUNKED_REQUESTS)
    check(f2["chunks"] == want,
          f"serve (chunked prefill): {f2['chunks']} chunk dispatches, "
          f"expected {want}")
    say(f"  serve (chunked prefill, budget {PREFILL_TOKEN_BUDGET}): 2 x "
        f"{len(CHUNKED_REQUESTS)} requests finished with their budgets "
        f"in {f2['chunks']} chunk dispatches; jit cache flat at "
        f"{f2['jit']}")

    t0 = time.perf_counter()
    ref = _reference(m)
    dl, scale = _first_token_logits(m, ref, cold[2][0])
    check(dl <= LOGIT_ATOL_SERVE_VS_F32,
          f"serve: first-token logits differ from the float32 reference "
          f"by {dl} (tolerance {LOGIT_ATOL_SERVE_VS_F32})")
    worst = _check_against_reference(ref, cold + chunked, "serve")
    say(f"  serve vs float32 reference: first-token logits max|d| "
        f"{dl:.4f} at scale {scale:.2f}; every emitted token within "
        f"{worst:.4f} of the reference argmax")
    return (setup + s1 + s2 + time.perf_counter() - t0), r1 + r2


# --------------------------------------------------------------- four chips


def multichip_phase(dev, devices, loss_one_chip):
    """DistOpt data-parallel training and tensor-parallel serving over
    ``devices`` (four chips), in this same process."""
    from singa_tpu import amp, opt
    from singa_tpu.parallel.communicator import Communicator, get_mesh
    from singa_tpu.parallel.dist_opt import DistOpt

    world = len(devices)
    dset = set(devices)
    t0 = time.perf_counter()
    amp.enable(True)
    try:
        dist = DistOpt(opt.SGD(lr=1e-4, momentum=0.9),
                       communicator=Communicator(
                           mesh=get_mesh(devices=devices)))
        m, ids, labels = _train_model(dev, "auto", optimizer=dist)
        _, loss = m(ids, labels)
        l1 = float(loss.data)
        _, loss = m(ids, labels)
        _check_loss(np.asarray([l1, float(loss.data)]),
                    m.cfg.vocab_size, "DistOpt train")
        # same seed, same global batch: the mean of the per-chip losses
        # is the one-chip loss up to bf16 reduction order
        check(abs(l1 - loss_one_chip) <= LOSS_ATOL_FLASH_VS_FUSED,
              f"DistOpt step-1 loss {l1} vs one-chip {loss_one_chip}")
        check_on([t.data for t in m.persistent_tensors().values()], dset,
                 "DistOpt train state")
        ex, = m._graph_runner.executables()
        state_sh, batch_sh = ex.input_shardings[0]
        for sh in batch_sh:
            check(set(sh.device_set) == dset and not sh.is_fully_replicated,
                  f"DistOpt: a batch input is laid out as {sh}, not split "
                  f"over the {world} chips")
        hlo = ex.as_text()
        n_ar = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
        check(n_ar > 0, "DistOpt: no all-reduce in the compiled step")
        check('custom_call_target="tpu_custom_call"' in hlo,
              "DistOpt: no Mosaic custom call in the compiled step")
        say(f"  DistOpt over {world} chips: step-1 loss {l1:.6f} (one chip "
            f"{loss_one_chip:.6f}); state replicated and batch split over "
            f"{world} distinct devices; {n_ar} all-reduce ops in the step")
    finally:
        amp.enable(False)
    del m, ids, labels, loss, ex, dist
    gc.collect()

    m = _serve_model(dev)
    eng = m.serve(tp=world, paged=_paged(), dtype=jnp.bfloat16,
                  max_slots=MAX_SLOTS)
    try:
        for name, pool in (("K", eng.paged_arena.pool_k),
                           ("V", eng.paged_arena.pool_v)):
            sh = pool.sharding
            check(set(sh.device_set) == dset
                  and not sh.is_fully_replicated
                  and sh.shard_shape(pool.shape)[2] * world
                  == pool.shape[2],
                  f"tp serve: {name} pool {pool.shape} is laid out as "
                  f"{sh}, not split by KV head over the {world} chips")
        check_on(eng._params, dset, "tp engine params")
        results, steps = _wave(eng, m.cfg.vocab_size, REQUESTS[:4],
                               seed=300)
        worst = _check_against_reference(_reference(m), results,
                                         "tp serve")
        eng.check_block_accounting()
        say(f"  serve tp={world}: {len(results)} requests finished with "
            f"their budgets in {steps} steps; KV pool split by head over "
            f"{world} distinct devices; emitted tokens within "
            f"{worst:.4f} of the float32 argmax")
    finally:
        eng.close()
    return time.perf_counter() - t0


# --------------------------------------------------------------------- main


def _version(dist):
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main():
    devices = jax.devices()
    d0 = devices[0]
    say(f"platform={d0.platform} device_kind={d0.device_kind!r} "
        f"count={len(devices)} jax={jax.__version__} "
        f"jaxlib={_version('jaxlib')} libtpu={_version('libtpu')}")
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {d0.platform!r} "
                 f"({len(devices)} device(s)), not a TPU — nothing was "
                 f"run; this script has no CPU path")

    from singa_tpu import device

    say(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    dev = device.create_tpu_device(0)
    check(dev.jax_device == d0, f"create_tpu_device(0) gave "
                                f"{dev.jax_device}, not {d0}")

    say("phase 1/3 train (GPT-2 small 12L/768/12H/V50257, "
        f"batch {BATCH} x {SEQ}, bf16 amp, graph mode)")
    setup, run, loss1 = train_phase(dev)
    say(f"  train passed: set-up {setup:.1f} s, run {run:.1f} s")
    gc.collect()

    say(f"phase 2/3 serve (paged {NUM_BLOCKS} x {BLOCK}, bf16, "
        f"{MAX_SLOTS} slots)")
    setup, run = serve_phase(dev)
    say(f"  serve passed: set-up {setup:.1f} s, run {run:.1f} s")
    gc.collect()

    if len(devices) >= 4:
        say("phase 3/3 four chips (DistOpt data-parallel train, serve tp=4)")
        total = multichip_phase(dev, devices[:4], loss1)
        say(f"  four chips passed: {total:.1f} s, compiles included")
    else:
        say(f"phase 3/3 four chips: NOT RUN — this machine shows "
            f"{len(devices)} chip(s)")

    say(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
