"""The ``mla_moe`` family (dots.vlm1's language model: latent attention,
sigmoid-routed experts) at a tiny size on the CPU, float32: the program
(model, served family, the engine's paged path over a ONE-LEAF latent
pool) against the plain reference ``benchmark/references/mla_moe.py`` on
the reference's own seeded weights.  Logits are compared, not sampled
tokens.

The tiny preset lives here only, every ratio of the published model
kept: a leading dense layer then two expert layers, heads of a ``nope``
part and a ``rope`` part, a query latent and a K/V latent, 64 router
outputs in 8 groups of which 4 stay, top-8, one shared expert; this
"chip" holds experts 0-3, one of sixteen shares; blocks of 8 positions.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import launch_widths as lw  # noqa: E402
from benchmark.harness import loader  # noqa: E402
from singa_tpu import device, tensor  # noqa: E402
from singa_tpu.serve import GenerationRequest, PagedConfig  # noqa: E402

TINY = dict(
    family="mla_moe", vocab_size=512, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=8,
    n_group=8, topk_group=4, routed_scaling_factor=2.5, rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 64, "type": "yarn"},
    max_position_embeddings=163840,
    share=dict(n_routed_experts_published=64, experts_held=[0, 4]),
    engine=dict(max_len=128, dtype="float32", block_size=8))
TOL = 2e-4      # float32 against float32, other orders of summation


@pytest.fixture(scope="module")
def ref():
    return loader.load_module("references", "mla_moe")


@pytest.fixture(scope="module")
def built(ref):
    """(model, reference weights, sizes) on the reference's seed-7
    weights."""
    ad = loader.load_module("adapters", "mla_moe")
    sizes = ref.sizes_of(TINY)
    m = ad.build_model(TINY, device.get_default_device(), train=False,
                       batch_shape=(1, 16))
    w = ref.init_weights(sizes, 7)
    ad.put_weights(m, w)
    return m, w, sizes


def _engine(m, num_blocks=64, max_slots=4, budget=16):
    return m.serve(paged=PagedConfig(block_size=8, num_blocks=num_blocks,
                                     prefill_token_budget=budget),
                   dtype=jnp.float32, max_slots=max_slots)


def _ref_logits(ref, w, toks):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(w, ref.hidden_states(
            w, jnp.asarray(np.asarray(toks, np.int32)))))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


def _serve(eng, prompts, n_new):
    hs = [eng.submit(GenerationRequest(p, max_new_tokens=n_new,
                                       temperature=0.0)) for p in prompts]
    while eng.pending:
        eng.step()
    return [np.asarray(h.result().tokens) for h in hs]


def _chunk_rows(fam, cfg, params, toks):
    """The family's chunk rows as the engine drives them, from a fresh
    zero row: (every prompt position's logits, the private row)."""
    plen = len(toks)
    ids = np.zeros((1, cfg.max_len), np.int32)
    ids[0, :plen] = toks
    n_l, n_kv, d = fam.kv_geometry(cfg)
    kc = jnp.zeros((n_l, 1, n_kv, cfg.max_len, d), jnp.float32)
    got = []
    for off in range(0, plen, 8):
        hidden, kc, vc, state = fam.chunk_row(
            params, jnp.asarray(ids), kc, None, None, jnp.int32(off),
            jnp.int32(min(8, plen - off)), chunk=8)
        assert vc is None and state is None
        got.append(np.asarray(fam.logits(params, hidden))[0])
    return np.concatenate(got)[:plen], kc


def test_full_forward_in_the_expanded_form_matches_the_reference(ref,
                                                                 built):
    m, w, _ = built
    toks = _prompt(37)
    got = np.asarray(m.forward(tensor.from_numpy(
        toks[None], device.get_default_device())).data)[0]
    want = _ref_logits(ref, w, toks)
    assert np.abs(want).max() > 1.0          # logits of a real scale
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("plen", [24, 21, 5])
def test_absorbed_chunk_rows_match_the_reference(ref, built, plen):
    """Block-width windows over a private latent row, queries absorbed;
    every prompt position's logits against the reference's full forward
    (expanded keys and values) -- across chunk-row boundaries, and for a
    prompt that is not a multiple of the block."""
    m, w, _ = built
    fam = m.served_family()
    params = fam.extract_params(m, dtype=jnp.float32)
    toks = _prompt(plen, seed=plen)
    got, _ = _chunk_rows(fam, m.cfg, params, toks)
    np.testing.assert_allclose(got, _ref_logits(ref, w, toks), atol=TOL)


def test_prefill_then_decode_through_the_engines_pool(ref, built):
    """Through the engine: budgeted chunked prefill of two prompts, then
    decode steps.  Before every step the family's decode math is run on
    the engine's own pool and block tables (undonated, so nothing moves)
    and each live lane's logits are held to the reference's full forward
    over that lane's sequence so far."""
    m, w, _ = built
    fam = m.served_family()
    eng = _engine(m)
    prompts = [_prompt(21, 1), _prompt(9, 2)]
    hs = [eng.submit(GenerationRequest(p, max_new_tokens=7,
                                       temperature=0.0)) for p in prompts]
    seqs = {}
    checked = 0
    while eng.pending:
        live = np.asarray([s is not None for s in eng._slots])
        if live.any():
            arena = eng.paged_arena
            pos = jnp.asarray(eng._pos)
            n_blk = jnp.max((jnp.where(live, pos, 0) + 7) // 8)
            logits, pool, none_v, none_s, counts = fam.decode_step(
                eng._params, arena.pool_k, None, None, None,
                eng._block_tables(), jnp.asarray(eng._toks), pos,
                jnp.asarray(live), n_blk, block=8, trash=arena.trash)
            assert none_v is None and none_s is None
            assert counts.shape == (2, 5)
            # 8 choices a live token an expert layer, here or elsewhere
            assert (np.asarray(counts).sum(1) == 8 * live.sum()).all()
            for i in np.flatnonzero(live):
                rid = eng._slots[i].handle.request.request_id
                seq = np.concatenate([seqs[rid][0], eng._slots[i].emitted])
                assert len(seq) == eng._pos[i] + 1
                want = _ref_logits(ref, w, seq)[-1]
                np.testing.assert_allclose(np.asarray(logits[i]), want,
                                           atol=TOL)
                checked += 1
        eng.step()
        for h, p in zip(hs, prompts):
            seqs.setdefault(h.request.request_id, (p,))
    assert checked >= 12
    for h, p in zip(hs, prompts):
        out = np.asarray(h.result().tokens)
        lg = _ref_logits(ref, w, out)
        # greedy: each served token is the reference's first choice
        assert (lg[len(p) - 1:-1].argmax(-1) == out[len(p):]).all()
    eng.close()


def test_absorbed_attention_equals_the_expanded_form():
    """``paged_attn`` over latent rows with the value taken from the
    row's first ``v_dim`` values, queries carried into the latent,
    against per-head keys and values made from the same rows."""
    from singa_tpu.ops.paged_attention import paged_attn

    rng = np.random.default_rng(0)
    h, nope, rope, r, v, n = 4, 16, 8, 16, 16, 19
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    w_kb = f32(rng.normal(size=(h, nope, r)))
    w_vb = f32(rng.normal(size=(h, r, v)))
    c_kv, k_r = f32(rng.normal(size=(n, r))), f32(rng.normal(size=(n, rope)))
    q_nope = f32(rng.normal(size=(h, nope)))
    q_r = f32(rng.normal(size=(h, rope)))
    scale = 0.3
    # expanded: a key and a value a head
    k_nope = jnp.einsum("tc,hdc->htd", c_kv, w_kb)
    val = jnp.einsum("tc,hcd->htd", c_kv, w_vb)
    sc = (jnp.einsum("hd,htd->ht", q_nope, k_nope)
          + jnp.einsum("hd,td->ht", q_r, k_r)) * scale
    want = jnp.einsum("ht,htd->hd", jax.nn.softmax(sc, -1), val)
    # absorbed: rows padded to 32 wide, 16 cached in two blocks of 8 and
    # the last 3 as the current chunk's... one query at position n - 1
    rows = jnp.concatenate([c_kv, k_r, jnp.zeros((n, 8))], -1)
    pool = jnp.zeros((1, 4, 8, 32)).at[0, 1].set(rows[:8]) \
        .at[0, 2].set(rows[8:16]).at[0, 0, :2].set(rows[16:18])
    q = jnp.concatenate([jnp.einsum("hd,hdc->hc", q_nope, w_kb), q_r,
                         jnp.zeros((h, 8))], -1)
    o_lat = paged_attn(q[None, :, None], pool, None, 0,
                       jnp.asarray([1, 2, 0]), n - 1, 3, 8, 3,
                       rows[n - 1:], None, jnp.ones((1, 1), bool), scale,
                       v_dim=r)[0, :, 0]
    got = jnp.einsum("hc,hcd->hd", o_lat, w_vb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_decode_equals_the_models_own_expanded_forward(built):
    """Program against program: prefill by chunk rows, scatter into a
    pool, one absorbed decode step -- against ``forward_full``."""
    from singa_tpu.models.mla_moe import forward_full
    from singa_tpu.ops.paged_attention import row_to_blocks

    m, _, _ = built
    fam, cfg = m.served_family(), m.cfg
    params = fam.extract_params(m, dtype=jnp.float32)
    toks = _prompt(22, 11)
    _, kc = _chunk_rows(fam, cfg, params, toks[:-1])
    pool = jnp.concatenate([row_to_blocks(kc, 8),
                            jnp.zeros((3, 1, 8, kc.shape[-1]))], axis=1)
    tables = jnp.arange(16)[None]
    logits, *_ = fam.decode_step(
        params, pool, None, None, None, tables, jnp.asarray(toks[-1:]),
        jnp.asarray([21]), jnp.asarray([True]), jnp.int32(3), block=8,
        trash=16)
    want = forward_full(params, jnp.asarray(toks), cfg)[-1]
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=TOL)


# ------------------------------------------------------- the expert layer


def test_the_sixteen_shares_add_up_to_the_whole_layer(ref, built):
    """The routed parts that all sixteen shares give, plus the shared
    expert once, are the uncut reference's layer -- for the reference's
    share and for the program's ``held_terms`` alike."""
    from singa_tpu.ops.expert_layer import held_terms, route, swiglu

    _, w, sizes = built
    layer = 1
    x = jnp.asarray(np.random.default_rng(5).normal(size=(23, 64)),
                    jnp.float32)
    whole = ref._ffn(w, x, layer, "f32", held=(0, 64)) - x
    g = ref._norm(x, w.tensor("ln2", layer), sizes["eps"])
    shared = ref._swiglu(g, w.tensor("s_gate", layer),
                         w.tensor("s_up", layer),
                         w.tensor("s_down", layer), precision="f32")
    # the reference's own shares
    parts = sum(ref._ffn(w, x, layer, "f32", held=(4 * i, 4 * i + 4)) - x
                - shared for i in range(16))
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole), atol=TOL)
    # the program's: one route, sixteen ownership ranges
    idx, wt = route(g, w.tensor("router", layer), w.tensor("bias", layer),
                    n_group=8, topk_group=4, top_k=8, scale=2.5)
    total, seen = 0.0, 0
    for i in range(16):
        es = range(4 * i, 4 * i + 4)
        w_gu = jnp.stack([jnp.concatenate(
            [w.tensor("e_gate", layer, e), w.tensor("e_up", layer, e)], 1)
            for e in es])
        w_down = jnp.stack([w.tensor("e_down", layer, e) for e in es])
        y, counts = held_terms(g, idx, wt, w_gu, w_down, 4 * i)
        total = total + y
        seen += int(counts[:-1].sum())
        assert int(counts.sum()) == 23 * 8
    assert seen == 23 * 8               # every choice computed once
    with jax.default_matmul_precision("highest"):
        total = total + swiglu(g, jnp.concatenate(
            [w.tensor("s_gate", layer), w.tensor("s_up", layer)], 1),
            w.tensor("s_down", layer))
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=TOL)
    # one share alone is NOT the layer, and is normalised over all 8
    # chosen: its weights do not sum to the scaling factor
    assert float(jnp.abs(parts - (ref._ffn(w, x, layer, "f32") - x
                                  - shared)).max()) > 0.01


def test_the_router_chooses_by_score_plus_bias_inside_the_kept_groups(ref,
                                                                      built):
    from singa_tpu.ops.expert_layer import route

    _, w, sizes = built
    g = jnp.asarray(np.random.default_rng(6).normal(size=(40, 64)),
                    jnp.float32)
    router, bias = w.tensor("router", 1), w.tensor("bias", 1)
    assert float(jnp.abs(bias).max()) > 0.01          # seeded non-zero
    idx, wt = route(g, router, bias, n_group=8, topk_group=4, top_k=8,
                    scale=2.5)
    dense = np.asarray(ref.route(g, router, bias, sz=w._sz))
    got = np.zeros_like(dense)
    np.put_along_axis(got, np.asarray(idx), np.asarray(wt), axis=1)
    np.testing.assert_allclose(got, dense, atol=1e-6)
    np.testing.assert_allclose(np.asarray(wt).sum(1), 2.5, rtol=1e-5)
    # at most 4 of the 8 groups of 8 are chosen from
    assert all(len(set(r // 8)) <= 4 for r in np.asarray(idx))
    # the weights are the scores', not the biased scores'
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(g @ router))
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(
        np.asarray(wt), chosen / chosen.sum(1, keepdims=True) * 2.5,
        rtol=1e-5)
    # and the bias does move the choice
    idx0, _ = route(g, router, 0 * bias, n_group=8, topk_group=4, top_k=8,
                    scale=2.5)
    assert (np.sort(np.asarray(idx0), 1)
            != np.sort(np.asarray(idx), 1)).any()


@pytest.mark.parametrize("case", ["all-on-one-expert", "nobody-here",
                                  "padding-chooses-nothing"])
def test_no_token_is_dropped_whatever_the_routing(case):
    """More tokens on one expert than a tile holds; no token at all;
    padding left out -- against every expert applied to every token."""
    from singa_tpu.ops.expert_layer import held_terms, swiglu

    rng = np.random.default_rng(8)
    t, k, e, i, n = 40, 2, 16, 8, 3
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(rng.normal(size=(t, e)))
    w_gu, w_down = f32(rng.normal(size=(n, e, 2 * i))), \
        f32(rng.normal(size=(n, i, e)))
    wt = f32(rng.uniform(size=(t, k)))
    first, valid = 10, None
    if case == "all-on-one-expert":      # 40 tokens, tiles of 16
        idx = np.stack([np.full(t, 11), rng.integers(0, 64, t)], 1)
    elif case == "nobody-here":
        idx = rng.integers(20, 64, (t, k))
    else:
        idx = rng.integers(8, 16, (t, k))
        valid = jnp.arange(t) < 29
    idx = jnp.asarray(idx, jnp.int32)
    y, counts = held_terms(x, idx, wt, w_gu, w_down, first, valid)
    want = np.zeros((t, e), np.float32)
    with jax.default_matmul_precision("highest"):
        for j in range(n):
            hit = np.asarray(idx) == first + j
            if valid is not None:
                hit &= np.asarray(valid)[:, None]
            wj = (np.asarray(wt) * hit).sum(1, keepdims=True)
            want += wj * np.asarray(swiglu(x, w_gu[j], w_down[j]))
            assert int(counts[j]) == hit.sum()
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)
    n_tok = t if valid is None else 29
    assert int(counts.sum()) == n_tok * k
    if case == "nobody-here":
        assert float(jnp.abs(y).max()) == 0.0


@pytest.fixture(scope="module")
def launch_runs(built):
    runs = lw.Runs(lambda budget: _engine(built[0], budget=budget), 512)
    yield runs
    runs.close()


@pytest.mark.parametrize("case", list(lw.CASES))
@pytest.mark.parametrize("ratio", lw.RATIOS)
def test_a_launch_of_several_blocks_leaves_what_one_block_at_a_time_did(
        launch_runs, ratio, case):
    """One launch a request a step, ``ratio`` blocks wide at most: the
    tokens and the private latent row of every admission against the
    engine that launches a block at a time (the padding after a
    prompt's end chooses no expert at any width)."""
    lw.assert_same_as_one_block(launch_runs.run(ratio, case),
                                launch_runs.run(1, case), case, ratio,
                                atol=TOL)


def test_one_block_lowers_to_the_program_it_was(launch_runs):
    assert lw.chunk_row_lowering(launch_runs.engine(1)) == \
        lw.PARENT_LOWERING["mla_moe"]


def test_the_engine_tells_chunk_rows_where_the_prompt_ends(built,
                                                          monkeypatch):
    """A prompt's last chunk row is padded to the block; the padding's
    rows are never read, but like tokens that all chose one held expert
    would cost it further tiles (at size: a seed's router then sets the
    chunk rows' time).  The family is ``pad_aware``: the engine hands it
    ``n_valid`` and the expert layer is told which tokens are real, in
    chunk rows as in decode steps (dead lanes)."""
    from singa_tpu.models import mla_moe

    m = built[0]
    seen, sound = [], mla_moe.held_terms

    def spy(x, idx, w, w_gu, w_down, first, valid=None, **kw):
        seen.append(None if valid is None else valid.shape)
        return sound(x, idx, w, w_gu, w_down, first, valid, **kw)

    monkeypatch.setattr(mla_moe, "held_terms", spy)
    eng = _engine(m)
    # another hash: no program traced with the sound function is found
    # (nor the keys of those the engine compiled when it was built)
    eng._fam = dataclasses.replace(eng._fam, cfg=dataclasses.replace(
        m.cfg, max_position_embeddings=163841))
    eng._x._aot_memo.clear()
    assert eng._fam.pad_aware
    _serve(eng, [_prompt(13)], 2)
    eng.close()
    # one launch of two blocks (the budget's width) for the 13 tokens,
    # and decode steps over the lanes' bucket
    assert seen and None not in seen and (16,) in seen


def test_yarn_frequencies_and_the_softmax_scale(ref):
    from singa_tpu.models.mla_moe import MlaMoeConfig

    c = MlaMoeConfig()
    cell = loader.load_cell("dotsvlm1-serve-reason")
    s = ref.sizes_of(cell["config"])
    np.testing.assert_allclose(c.rope_frequencies(),
                               ref.rope_frequencies(s), rtol=1e-6)
    f = c.rope_frequencies()
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # fast dims keep their frequency, slow ones are divided by 40, and
    # some lie between
    np.testing.assert_allclose(f[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(f[-4:], plain[-4:] / 40, rtol=1e-6)
    between = (f < plain * 0.999) & (f > plain / 40 * 1.001)
    assert between.sum() >= 5
    assert abs(c.softmax_scale - 0.13523) < 1e-5
    assert abs(ref.softmax_scale(s) - c.softmax_scale) < 1e-9
    assert (c.row_width, c.pool_row_width) == (576, 640)


# ------------------------------------------------------------- the engine


def test_the_arena_is_one_latent_leaf(built):
    """One pool of rows as wide as the family states -- the 24 values of
    a row (latent 16 + rotated key 8) padded to a whole 128-lane tile --
    and no second leaf: not a K and a V."""
    from singa_tpu.observe.registry import registry

    m, _, _ = built
    eng = _engine(m)
    arena = eng.paged_arena
    assert arena.pool_v is None
    assert arena.pool_k.shape == (3, 65, 8, 128)
    assert m.cfg.row_width == 24 and m.cfg.pool_row_width == 128
    assert m.served_family().kv_geometry(m.cfg) == (3, 1, 128)
    g = registry().gauge("serve.kv.row_bytes",
                         engine=eng.stats.engine_label)
    assert g.value == 128 * 4
    row, none = arena.gather_row([], n_used=0)
    assert none is None and row.shape == (3, 1, 1, 128, 128)
    _serve(eng, [_prompt(10)], 3)
    assert arena.pool_v is None
    eng.close()
    # a two-leaf family's gauge counts both leaves
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    g2 = GPT2LMHead(GPT2Config.tiny())
    ids = tensor.from_numpy(np.zeros((1, 8), np.int32),
                            device.get_default_device())
    g2.compile([ids], is_train=False, use_graph=False, sequential=False)
    e2 = g2.serve(paged=PagedConfig(block_size=8, num_blocks=8),
                  max_slots=2)
    assert e2.paged_arena.pool_v is not None
    assert registry().gauge(
        "serve.kv.row_bytes", engine=e2.stats.engine_label).value \
        == 2 * e2.paged_arena.pool_k.shape[-1] * 4
    e2.close()


def test_preempt_then_resume_restores_a_lanes_latent_rows(built):
    m, _, _ = built
    prompts = [_prompt(17, 6), _prompt(11, 8)]
    eng = _engine(m)
    want = _serve(eng, prompts, 12)
    eng.close()
    eng = _engine(m)
    hs = [eng.submit(GenerationRequest(p, max_new_tokens=12,
                                       temperature=0.0)) for p in prompts]
    done = False
    while eng.pending:
        eng.step()
        idx = [i for i, s in enumerate(eng._slots)
               if s is not None and len(s.emitted) == 5]
        if idx and not done:
            blocks = list(eng._slots[idx[0]].blocks)
            before = np.asarray(eng.paged_arena.pool_k[:, blocks[0]])
            eng._preempt_slot(idx[0], reason="test")
            # the freed blocks' bytes must not be what the resume needs
            eng.paged_arena.pool_k = eng.paged_arena.pool_k.at[
                :, jnp.asarray(blocks)].set(7.0)
            done = True
    assert done and float(np.abs(before).max()) > 0
    snap = eng.paged_arena.snapshot()
    assert snap["swap_out"] == 1 and snap["swap_in"] == 1
    got = [np.asarray(h.result().tokens) for h in hs]
    for g, w_ in zip(got, want):
        assert g.tolist() == w_.tolist()
    eng.close()


def test_the_steps_counts_reach_the_span_and_the_counters(built):
    from singa_tpu.observe.registry import registry

    m, _, _ = built
    eng = _engine(m)
    seen = []
    on = eng._on_step_counts

    def keep(counts):
        on(counts)
        seen.append((np.array(counts), dict(eng._step_counts)))

    eng._on_step_counts = keep
    _serve(eng, [_prompt(10), _prompt(13, 1)], 6)
    lbl = eng.stats.engine_label
    assert len(seen) >= 5
    held = sum(c[:, :-1].sum(0) for c, _ in seen)
    for e in range(4):
        assert registry().counter("serve.moe.expert_tokens", engine=lbl,
                                  expert=str(e)).value == held[e]
    assert registry().counter("serve.moe.assignments_elsewhere",
                              engine=lbl).value \
        == sum(c[:, -1].sum() for c, _ in seen)
    for c, args in seen:
        assert args["experts_hit"] == np.count_nonzero(c[:, :-1])
        assert args["expert_tokens_max"] == c[:, :-1].max()
        assert abs(args["expert_tokens_mean"] - c[:, :-1].mean()) < 1e-9
        assert 0 <= args["experts_hit"] <= 8
    eng.close()


def test_the_family_names_its_scopes_and_programs_keep_them(built):
    from singa_tpu.serve import paged

    m, _, _ = built
    fam = m.served_family()
    assert set(fam.scopes) == {"mla_attn", "mla_proj", "moe_route",
                               "moe_experts", "moe_shared", "dense_mlp",
                               "head"}
    eng = _engine(m)
    _serve(eng, [_prompt(12)], 3)
    eng.close()
    kept = paged.program_scopes()
    assert {"mla_attn", "moe_experts", "moe_shared", "dense_mlp",
            "head"} <= set(kept["paged_decode_kernel"].values())
    assert {"mla_attn", "moe_experts"} <= set(kept["chunk_row"].values())


def test_the_family_serves_with_gpt2s_math_out_of_reach(built,
                                                        monkeypatch):
    from singa_tpu.models import gpt2_decode

    def out_of_reach(*a, **k):
        raise AssertionError("GPT-2's math was called for another family")

    for name, fn in vars(gpt2_decode).items():
        if callable(fn) and getattr(fn, "__module__", "") \
                == gpt2_decode.__name__ and not isinstance(fn, type):
            monkeypatch.setattr(gpt2_decode, name, out_of_reach)
    m, _, _ = built
    eng = _engine(m, budget=8)
    out = _serve(eng, [_prompt(12, 9)], 4)[0]
    eng.close()
    assert len(out) == 16


@pytest.mark.parametrize("feature, kw", [
    ("tp=", dict(tp=2)),
    ("ep=", dict(ep=dict(ep=2))),
    ("pp=", dict(pp=dict(stages=2))),
    ("draft_model=", dict(draft_model="a draft")),
    ("cache_dtype='int8'", dict(cache_dtype="int8")),
    ("prefix_cache=", dict(prefix_cache=True)),
    ("the slot arena (serving without paged=)", dict(paged=None)),
    ("whole-prompt admission", dict(paged=PagedConfig(block_size=8))),
])
def test_what_the_family_lacks_is_refused_by_name(built, feature, kw):
    m, _, _ = built
    base = dict(paged=PagedConfig(block_size=8, prefill_token_budget=8),
                max_slots=2)
    with pytest.raises(NotImplementedError) as e:
        m.serve(**dict(base, **kw))
    assert feature in str(e.value) and "mla_moe" in str(e.value)


def test_fork_and_kv_ship_are_refused_by_name(built):
    m, _, _ = built
    eng = _engine(m)
    with pytest.raises(NotImplementedError, match="fork"):
        eng.submit(GenerationRequest(_prompt(9), max_new_tokens=2, n=2))
    with pytest.raises(NotImplementedError, match="KV image ship"):
        eng.start_prefix_build(_prompt(9))
    eng.close()


def test_the_configuration_refuses_what_it_cannot_be():
    from singa_tpu.models.mla_moe import MlaMoeConfig

    with pytest.raises(ValueError, match="experts_held"):
        MlaMoeConfig(experts_held=(250, 260))
    with pytest.raises(ValueError, match="yarn"):
        MlaMoeConfig(rope_scaling={"type": "linear", "factor": 2})
    with pytest.raises(ValueError, match="dense"):
        MlaMoeConfig(first_k_dense_replace=0)
    a = MlaMoeConfig(rope_scaling=dict(MlaMoeConfig().rope_scaling))
    assert a == MlaMoeConfig() and hash(a) == hash(MlaMoeConfig())
    assert dataclasses.replace(a, experts_held=(16, 32)).n_held == 16


def test_the_default_queue_holds_a_request_a_slot(built):
    """An engine of 128 lanes is not refused its 65th request while all
    its slots are free (the benchmark's warm-up fills every lane at
    once); a scheduler that is handed in keeps its own bound."""
    from singa_tpu.serve.scheduler import FIFOScheduler

    m, _, _ = built
    eng = _engine(m, max_slots=96)
    assert eng.scheduler.max_queue_depth == 96
    hs = [eng.submit(GenerationRequest(_prompt(3, i), max_new_tokens=1,
                                       temperature=0.0))
          for i in range(96)]
    eng.close(force=True)
    assert len(hs) == 96
    small = _engine(m, max_slots=4)
    assert small.scheduler.max_queue_depth == 64
    small.close()
    own = m.serve(paged=PagedConfig(block_size=8, num_blocks=8,
                                    prefill_token_budget=8),
                  max_slots=96, scheduler=FIFOScheduler(max_queue_depth=5))
    assert own.scheduler.max_queue_depth == 5
    own.close()
