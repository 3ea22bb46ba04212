"""The ``conv_moe`` family (LFM2-24B-A2B: gated short-convolution layers
beside rotary grouped-query attention, sigmoid-routed experts with none
shared) at a tiny size on the CPU, float32: the program (model, served
family, the engine's paged path over K/V for the attention layers alone
and a two-row convolution tail a slot for the others) against the plain
reference ``benchmark/references/conv_moe.py`` on the reference's own
seeded weights.  Logits are compared, not sampled tokens.

The tiny preset lives here only, every ratio of the benchmark's cut
kept: a dense conv layer, then two periods of one attention layer and
three conv layers; 4 query heads on 2 K/V heads; 16 router outputs,
top-4, all held; three taps; blocks of 8; matrices of a standard
deviation that keeps std x sqrt(hidden) at the published model's 0.9, so
that every layer adds to the stream what it adds at the published width.
"""

import dataclasses
import functools
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

BLOCK = 8
KINDS = ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
TINY = dict(
    family="conv_moe", vocab_size=512, hidden_size=64, num_hidden_layers=9,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    moe_intermediate_size=32, num_dense_layers=1, num_experts=16,
    num_experts_per_tok=4, norm_topk_prob=True, use_expert_bias=True,
    routed_scaling_factor=1, conv_L_cache=3, conv_bias=False,
    layer_types=KINDS, norm_eps=1e-5,
    rope_parameters=dict(rope_theta=1000000, rope_type="default"),
    max_position_embeddings=128000, initializer_range=0.11,
    engine=dict(max_len=128, dtype="float32", block_size=BLOCK))
TOL = 2e-4      # float32 against float32, other orders of summation


@pytest.fixture(scope="module")
def ref():
    return loader.load_module("references", "conv_moe")


@pytest.fixture(scope="module")
def built(ref):
    """(model, reference weights, sizes) on the reference's seed-7
    weights."""
    ad = loader.load_module("adapters", "conv_moe")
    sizes = ref.sizes_of(TINY)
    m = ad.build_model(TINY, device.get_default_device(), train=False,
                       batch_shape=(1, 16))
    w = ref.init_weights(sizes, 7)
    ad.put_weights(m, w)
    return m, w, sizes


def _engine(m, num_blocks=64, max_slots=4, budget=16):
    return m.serve(paged=PagedConfig(block_size=BLOCK,
                                     num_blocks=num_blocks,
                                     prefill_token_budget=budget),
                   dtype=jnp.float32, max_slots=max_slots)


def _ref_logits(ref, w, toks):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(w, ref.hidden_states(
            w, np.asarray(toks, np.int32))))[:len(toks)]


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


def _serve(eng, prompts, n_new):
    hs = [eng.submit(GenerationRequest(p, max_new_tokens=n_new,
                                       temperature=0.0)) for p in prompts]
    while eng.pending:
        eng.step()
    return [np.asarray(h.result().tokens) for h in hs]


def _zero_state(fam, cfg):
    return {k: jnp.zeros((cfg.n_full,) + shape, dt)
            for k, (shape, dt) in fam.state_spec(cfg).items()}


def _chunk_rows(fam, cfg, params, toks, widths=(16, 8)):
    """The family's chunk rows as the engine drives them, from a fresh
    zero row and zeroed tails, in launches of ``widths`` in turn: (every
    prompt position's logits, the private rows, the tails)."""
    plen = len(toks)
    ids = np.zeros((1, cfg.max_len), np.int32)
    ids[0, :plen] = toks
    n_l, n_kv, d = fam.kv_geometry(cfg)
    kc = jnp.zeros((n_l, 1, n_kv, cfg.max_len, d), jnp.float32)
    vc, state = kc, _zero_state(fam, cfg)
    got, off, i = [], 0, 0
    row = jax.jit(fam.chunk_row, static_argnames=("chunk", "block"))
    while off < plen:
        w = widths[i % len(widths)]
        i += 1
        hidden, kc, vc, state = row(
            params, jnp.asarray(ids), kc, vc, state, jnp.int32(off),
            jnp.int32(min(w, plen - off)), chunk=w, block=BLOCK)
        got.append(np.asarray(fam.logits(params, hidden))[0])
        off += w
    return np.concatenate(got)[:plen], (kc, vc), state


def _tails_of(ref, w, toks):
    """What every conv layer's tail must hold after ``toks``: the last
    two inputs ``B * x`` of its convolution, from the reference's own
    pass, in the order of the conv layers."""
    s = w.sizes
    ids = np.asarray(toks, np.int32)
    with jax.default_matmul_precision("highest"):
        xs, out = [ref.embed(w, jnp.asarray(ids))], []
        for layer in range(s["L"]):
            if ref.is_conv(s, layer):
                a = ref._norm(xs[0], w.tensor("ln_op", layer), s["eps"])
                u, _ = ref._conv_input(a @ w.tensor("w_in", layer))
                out.append(np.asarray(u[-2:]))
            xs = ref._layer(w, xs, layer, "f32")
    return np.stack(out)


def test_full_forward_matches_the_reference(ref, built):
    m, w, _ = built
    toks = _prompt(53)
    got = np.asarray(m.forward(tensor.from_numpy(
        toks[None], device.get_default_device())).data)[0]
    want = _ref_logits(ref, w, toks)
    assert np.abs(want).max() > 0.5          # logits of a real scale
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_reference_in_small_blocks_is_the_reference(ref, built,
                                                        monkeypatch):
    """Rows 16 at a time (the convolution across the blocks' seams,
    blocks of queries against blocks of keys, the experts four at a
    time) against the whole sequence as one block."""
    _, w, _ = built
    toks = _prompt(90, 3)
    whole = _ref_logits(ref, w, toks)
    monkeypatch.setattr(ref, "ROWS", 16)
    monkeypatch.setattr(ref, "EG", 4)
    np.testing.assert_allclose(_ref_logits(ref, w, toks), whole, atol=1e-5)


@pytest.mark.parametrize("plen, widths", [(70, (16, 8)), (45, (8, 16)),
                                          (21, (8,)), (17, (32,))])
def test_chunk_rows_of_mixed_widths_match_the_reference(ref, built, plen,
                                                        widths):
    """Launches of one, two and four blocks in turn over a private row
    (the attention layers) and carried tails (the conv layers): every
    prompt position's logits against the reference's full forward, for
    prompts whose end falls inside a launch -- and the tails left behind
    are the unpadded prompt's: the last two inputs of each convolution
    BEFORE the padding (``n_valid``)."""
    m, w, _ = built
    fam = m.served_family()
    params = fam.extract_params(m, dtype=jnp.float32)
    toks = _prompt(plen, seed=plen)
    got, _, state = _chunk_rows(fam, m.cfg, params, toks, widths)
    np.testing.assert_allclose(got, _ref_logits(ref, w, toks), atol=TOL)
    # 7 conv layers' tails in the 2 x 4 places of the arena's rows; the
    # eighth place stays zero
    assert state["conv"].shape == (2, 4, 2, 64)
    tails = np.asarray(state["conv"]).reshape(8, 2, 64)
    np.testing.assert_allclose(tails[:7], _tails_of(ref, w, toks),
                               atol=TOL)
    assert np.abs(tails[:7]).max() > 0.1 and not tails[7].any()


def test_prefill_then_decode_through_the_pool_and_the_tails(ref, built):
    """Through the engine: budgeted chunked prefill of a long prompt and
    a short one (launches of two blocks and of one), then decode steps
    with both in ONE program.  Before every step the family's decode
    math is run on the engine's own pool, block tables and tail arena
    (undonated, so nothing moves) and each live lane's logits are held
    to the reference's full forward over that lane's sequence so far."""
    m, w, _ = built
    fam = m.served_family()
    eng = _engine(m)
    prompts = [_prompt(70, 1), _prompt(9, 2)]
    hs = [eng.submit(GenerationRequest(p, max_new_tokens=30,
                                       temperature=0.0)) for p in prompts]
    seqs, checked, both = {}, 0, 0
    step = jax.jit(functools.partial(fam.decode_step, block=8,
                                     trash=eng.paged_arena.trash))
    while eng.pending:
        live = np.asarray([s is not None for s in eng._slots])
        if live.any():
            arena = eng.paged_arena
            pos = jnp.asarray(eng._pos)
            n_blk = jnp.max((jnp.where(live, pos, 0) + 7) // 8)
            slots = jnp.asarray(np.where(live, np.arange(4), 4), jnp.int32)
            logits, _, _, _, counts = step(
                eng._params, arena.pool_k, arena.pool_v, eng._state, slots,
                eng._block_tables(), jnp.asarray(eng._toks), pos,
                jnp.asarray(live), n_blk)
            # 8 expert layers' rows: every live lane's 4 choices, all held
            assert counts.shape == (8, 17)
            assert (np.asarray(counts)[:, :-1].sum(1)
                    == 4 * live.sum()).all()
            assert not np.asarray(counts)[:, -1].any()
            both += live.sum() == 2
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
    assert checked >= 50 and both >= 20
    for h, p in zip(hs, prompts):
        out = np.asarray(h.result().tokens)
        lg = _ref_logits(ref, w, out)
        # greedy: each served token is the reference's first choice
        assert (lg[len(p) - 1:-1].argmax(-1) == out[len(p):]).all()
    eng.close()


def test_decode_equals_the_models_own_full_forward(built):
    """Program against program: prefill by chunk rows, the rows into a
    pool and the tails into an arena, one decode step -- against
    ``forward_full``."""
    from singa_tpu.models.conv_moe import forward_full
    from singa_tpu.ops.paged_attention import row_to_blocks

    m, _, _ = built
    fam, cfg = m.served_family(), m.cfg
    params = fam.extract_params(m, dtype=jnp.float32)
    toks = _prompt(46, 11)
    _, (kc, vc), state = _chunk_rows(fam, cfg, params, toks[:-1])
    trash = jnp.zeros((2, 1, 8, kc.shape[2] * kc.shape[4]))
    pool_k = jnp.concatenate([row_to_blocks(kc, 8), trash], axis=1)
    pool_v = jnp.concatenate([row_to_blocks(vc, 8), trash], axis=1)
    # slot 0 of an arena of one slot and the trash row
    arena = {k: jnp.stack([v, jnp.zeros_like(v)], axis=1)
             for k, v in state.items()}
    logits, _, _, after, _ = jax.jit(functools.partial(
        fam.decode_step, block=8, trash=16))(
        params, pool_k, pool_v, arena, jnp.asarray([0]),
        jnp.arange(16)[None], jnp.asarray(toks[-1:]), jnp.asarray([45]),
        jnp.asarray([True]), jnp.int32(6))
    want = forward_full(params, jnp.asarray(toks), cfg)[-1]
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=TOL)
    # the tail moved on a row: its older row is the newer one of before
    before, now = np.asarray(arena["conv"]), np.asarray(after["conv"])
    np.testing.assert_array_equal(now[:, 0, :, 0], before[:, 0, :, 1])
    assert not now[:, 1].any()              # the trash row: no dead lane


# --------------------------------------------- K/V for a quarter, tails for
# --------------------------------------------- the rest


def test_only_the_attention_layers_have_kv_and_a_tail_is_two_rows(built):
    from singa_tpu.observe.registry import registry

    m, _, _ = built
    fam, cfg = m.served_family(), m.cfg
    assert fam.kv_geometry(cfg) == (2, 2, 16)   # the ATTENTION layers only
    assert fam.window(cfg) is None and fam.value_leaf
    assert fam.state_spec(cfg) == {
        "conv": ((4, 2, 64), jnp.dtype("float32"))}
    assert (cfg.n_full, cfg.n_conv, cfg.tail, cfg.tail_rows) == (2, 7, 2, 4)
    eng = _engine(m)
    lbl = eng.stats.engine_label
    assert eng.paged_arena.pool_k.shape == (2, 65, 8, 32)
    assert eng._state["conv"].shape == (2, 5, 4, 2, 64)
    tail_bytes = 2 * 4 * 2 * 64 * 4
    assert cfg.tail_bytes() == tail_bytes
    assert registry().gauge("serve.state.bytes", engine=lbl).value \
        == 5 * tail_bytes
    _serve(eng, [_prompt(80, 1), _prompt(9, 2)], 12)
    assert registry().gauge("serve.state.conv_tail_bytes",
                            engine=lbl).value == tail_bytes
    eng.close()


def test_the_layers_are_walked_as_runs_and_a_repeating_stretch(built):
    from singa_tpu.models.conv_moe import ConvMoeConfig

    cfg = built[0].cfg
    assert cfg.stack_sizes() == {"dc": 1, "ef": 2, "ec": 6}
    assert cfg.plan() == ((("dc", 0, 0, 1),),
                          ((("ef", 0, 0, 1), ("ec", 0, 1, 3)), 2), ())
    assert [cfg.place(i) for i in (0, 1, 2, 4, 5, 8)] == [
        ("dc", 0), ("ef", 0), ("ec", 0), ("ec", 2), ("ef", 1), ("ec", 5)]
    # as published: 40 layers, 10 of them attention, two dense; the last
    # period is cut short, so it runs after the nine that repeat
    c = ConvMoeConfig()
    assert (c.n_full, c.n_conv, c.n_moe, c.head_dim, c.kv_width) == (
        10, 30, 38, 64, 512)
    assert c.layer_types[:7] == ("conv", "conv", "full_attention", "conv",
                                 "conv", "conv", "full_attention")
    assert c.stack_sizes() == {"dc": 2, "ef": 10, "ec": 28}
    head, (unit, n), tail = c.plan()
    assert head == (("dc", 0, 0, 2),) and n == 9
    assert unit == (("ef", 0, 0, 1), ("ec", 0, 2, 3))
    assert tail == (("ef", 9, 9, 1), ("ec", 27, 29, 1))
    assert c.tail_rows == 3 and c.experts_held == (0, 64)
    with pytest.raises(ValueError, match="experts_held"):
        ConvMoeConfig(experts_held=(60, 70))
    with pytest.raises(ValueError, match="layer_types"):
        ConvMoeConfig(num_hidden_layers=9, layer_types=("conv",) * 8)
    with pytest.raises(ValueError, match="full_attention layer"):
        ConvMoeConfig(num_hidden_layers=2, layer_types=("conv",) * 2)
    with pytest.raises(ValueError, match="conv_bias"):
        ConvMoeConfig(conv_bias=True)
    a = ConvMoeConfig(layer_types=list(c.layer_types))
    assert a == c and hash(a) == hash(c)
    assert dataclasses.replace(a, experts_held=(16, 32)).n_held == 16


def test_a_dense_attention_layer_and_layers_that_do_not_repeat(ref):
    """Another order of layers through the same code: the dense layer an
    attention layer (the ``df`` stack), runs of like layers of which none
    repeats, 3 conv layers' tails in 2 x 2 places -- served through the
    engine and held to the reference token for token."""
    ad = loader.load_module("adapters", "conv_moe")
    cfg = dict(TINY, num_hidden_layers=5, layer_types=[
        "full_attention", "conv", "conv", "full_attention", "conv"])
    sizes = ref.sizes_of(cfg)
    m = ad.build_model(cfg, device.get_default_device(), train=False,
                       batch_shape=(1, 16))
    w = ref.init_weights(sizes, 11)
    ad.put_weights(m, w)
    assert m.cfg.stack_sizes() == {"df": 1, "ec": 3, "ef": 1}
    assert m.cfg.plan() == ((), ((), 0), (
        ("df", 0, 0, 1), ("ec", 0, 0, 2), ("ef", 0, 1, 1),
        ("ec", 2, 2, 1)))
    assert m.cfg.tail_rows == 2
    toks = _prompt(37, 2)
    got = np.asarray(m.forward(tensor.from_numpy(
        toks[None], device.get_default_device())).data)[0]
    np.testing.assert_allclose(got, _ref_logits(ref, w, toks), atol=TOL)
    eng = _engine(m)
    prompts = [_prompt(30, 3), _prompt(9, 4)]
    for p, out in zip(prompts, _serve(eng, prompts, 10)):
        assert ref.served_token_gap(w, sizes, out, len(p))[0] == 0.0
    eng.close()


def test_the_whole_layer_and_eight_shares_of_it(ref, built):
    """With all 16 held the program's layer IS the reference's uncut
    layer: every choice is computed here (the counts sum to 4 a token,
    none elsewhere).  And the guide's share test: the parts that eight
    ownership ranges of two give add up to it, for the reference's
    share and for the program's ``held_terms`` alike."""
    from singa_tpu.models.conv_moe import TILE
    from singa_tpu.ops.expert_layer import held_terms, route

    _, w, sizes = built
    layer = 5
    x = jnp.asarray(np.random.default_rng(5).normal(size=(23, 64)),
                    jnp.float32)
    m_in = ref._norm(x, w.tensor("ln_ffn", layer), sizes["eps"])
    terms = lambda held: ref.ffn_terms(w, [m_in], layer, "f32",
                                       held=held)[0]
    whole = terms((0, 16))
    parts = sum(terms((2 * i, 2 * i + 2)) for i in range(8))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=TOL)
    idx, wt = route(m_in, w.tensor("router", layer),
                    w.tensor("bias", layer), n_group=1, topk_group=1,
                    top_k=4, scale=1.0)
    dense = np.asarray(ref.route(m_in, w.tensor("router", layer),
                                 w.tensor("bias", layer), sz=w._sz))
    got = np.zeros_like(dense)
    np.put_along_axis(got, np.asarray(idx), np.asarray(wt), axis=1)
    # the program's 1e-20 against the family's 1e-6 under the sum
    np.testing.assert_allclose(got, dense, atol=1e-6)
    np.testing.assert_allclose(np.asarray(wt).sum(1), 1.0, rtol=1e-5)

    def stack(es):
        return (jnp.stack([jnp.concatenate(
            [w.tensor("e_gate", layer, e), w.tensor("e_up", layer, e)], 1)
            for e in es]),
            jnp.stack([w.tensor("e_down", layer, e) for e in es]))

    y, counts = held_terms(m_in, idx, wt, *stack(range(16)), 0, tile=TILE)
    assert int(counts[:-1].sum()) == 23 * 4 and int(counts[-1]) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(whole), atol=TOL)
    total, seen = 0.0, 0
    for i in range(8):
        y, counts = held_terms(m_in, idx, wt, *stack(range(2 * i, 2 * i + 2)),
                               2 * i, tile=TILE)
        total = total + y
        seen += int(counts[:-1].sum())
        assert int(counts.sum()) == 23 * 4
    assert seen == 23 * 4               # every choice computed once
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=TOL)
    # one share alone is NOT the layer
    assert float(jnp.abs(terms((0, 2)) - whole).max()) \
        > 0.2 * float(jnp.abs(whole).max())


def test_a_slot_reused_after_retirement_starts_from_zeroed_tails(ref,
                                                                 built):
    m, w, sizes = built
    a, b = _prompt(40, 4), _prompt(13, 5)
    eng = _engine(m, max_slots=1)
    _serve(eng, [a], 6)
    assert float(jnp.abs(eng._state["conv"][:, 0]).max()) > 0   # a's tails
    second = _serve(eng, [b], 6)[0]
    resets = eng._c_state_resets.value
    eng.close()
    assert resets == 2
    assert ref.served_token_gap(w, sizes, second, len(b))[0] == 0.0


def test_preempt_then_resume_continues_token_for_token(built):
    """A lane preempted in mid-reply: its blocks of the pool and its
    tails go to the host and come back, and it goes on as if nothing had
    happened."""
    m, _, _ = built
    prompts = [_prompt(40, 6), _prompt(11, 8)]
    eng = _engine(m)
    want = _serve(eng, prompts, 14)
    eng.close()
    eng = _engine(m)
    hs = [eng.submit(GenerationRequest(p, max_new_tokens=14,
                                       temperature=0.0)) for p in prompts]
    done = False
    while eng.pending:
        eng.step()
        idx = [i for i, s in enumerate(eng._slots)
               if s is not None and len(s.emitted) == 5
               and len(s.handle.request.prompt_ids) == 40]
        if idx and not done:
            blocks = list(eng._slots[idx[0]].blocks)
            eng._preempt_slot(idx[0], reason="test")
            # neither the freed blocks' bytes nor the slot's old tails
            # may be what the resume needs
            arena = eng.paged_arena
            arena.pool_k = arena.pool_k.at[:, jnp.asarray(blocks)].set(7.0)
            eng._state = jax.tree.map(
                lambda a_: a_.at[:, idx[0]].set(7.0), eng._state)
            done = True
    assert done
    assert eng._c_state_snapshots.value == 1
    assert eng._c_state_restores.value == 1
    snap = eng.paged_arena.snapshot()
    assert snap["swap_out"] == 1 and snap["swap_in"] == 1
    got = [np.asarray(h.result().tokens) for h in hs]
    for g, w_ in zip(got, want):
        assert g.tolist() == w_.tolist()
    eng.close()


# ----------------------------------------------------------------- the seam


@pytest.fixture(scope="module")
def launch_runs(built):
    runs = lw.Runs(lambda budget: _engine(built[0], budget=budget), 512)
    yield runs
    runs.close()


@pytest.mark.parametrize("ratio", [2, 3, 4])
@pytest.mark.parametrize("case", list(lw.CASES))
def test_a_wide_launch_leaves_what_one_block_at_a_time_did(
        launch_runs, case, ratio):
    """A pass's pieces in the fewest launches, two to four blocks wide
    and at four two requests in one: the tokens, the private rows and
    the TAILS of every admission against the engine that launches a
    block at a time."""
    lw.assert_same_as_one_block(launch_runs.run(ratio, case),
                                launch_runs.run(1, case), case, ratio,
                                atol=TOL)


def test_one_block_lowers_to_the_program_it_was(launch_runs):
    assert lw.chunk_row_lowering(launch_runs.engine(1)) == \
        lw.PARENT_LOWERING["conv_moe"]


def test_a_slots_unused_blocks_touch_nothing_and_choose_no_expert(
        built, monkeypatch):
    """The pair program at the family's seam: two requests' segments in
    slots of two blocks, the first 5 tokens into its second block (13
    real positions behind it, 11 of its slot unused), the second a whole
    slot.  Each request's hidden rows, private rows and tails are those
    of its own launch alone; the rows below ``off`` are the bytes they
    were; and the expert layers are told which 21 of the 32 tokens are
    real."""
    from singa_tpu.models import conv_moe
    from singa_tpu.models.served import Segment

    m, _, _ = built
    fam, cfg = m.served_family(), m.cfg
    params = fam.extract_params(m, dtype=jnp.float32)
    n_l, n_kv, d = fam.kv_geometry(cfg)
    row = lambda: jnp.zeros((n_l, 1, n_kv, cfg.max_len, d), jnp.float32)

    def ids_of(toks):
        ids = np.zeros((1, cfg.max_len), np.int32)
        ids[0, :len(toks)] = toks
        return jnp.asarray(ids)

    one = jax.jit(fam.chunk_row, static_argnames=("chunk", "block"))
    a_ids, b_ids = ids_of(_prompt(13, 1)), ids_of(_prompt(16, 2))
    # request a's first block, alone: what the pair finds below its off
    _, a_kc, a_vc, a_st = one(params, a_ids, row(), row(),
                              _zero_state(fam, cfg), jnp.int32(0),
                              jnp.int32(8), chunk=8, block=BLOCK)
    want_a = one(params, a_ids, a_kc, a_vc, a_st, jnp.int32(8),
                 jnp.int32(5), chunk=8, block=BLOCK)
    want_b = one(params, b_ids, row(), row(), _zero_state(fam, cfg),
                 jnp.int32(0), jnp.int32(16), chunk=16, block=BLOCK)
    segs = [Segment(a_ids, a_kc, a_vc, a_st, jnp.int32(8), 16,
                    jnp.int32(5)),
            Segment(b_ids, row(), row(), _zero_state(fam, cfg),
                    jnp.int32(0), 16, jnp.int32(16))]
    got_a, got_b = jax.jit(
        lambda p: fam.chunk_rows(p, segs, block=BLOCK))(params)
    for got, want, n in ((got_a, want_a, 5), (got_b, want_b, 16)):
        np.testing.assert_allclose(got[0][0, :n], want[0][0, :n],
                                   atol=TOL)
        np.testing.assert_allclose(got[3]["conv"], want[3]["conv"],
                                   atol=TOL)
    for k in (1, 2):
        # a's first block as it was, its second as its own launch wrote
        np.testing.assert_array_equal(got_a[k][..., :8, :],
                                      (a_kc, a_vc)[k - 1][..., :8, :])
        np.testing.assert_allclose(got_a[k][..., :13, :],
                                   want_a[k][..., :13, :], atol=TOL)
        np.testing.assert_allclose(got_b[k], want_b[k], atol=TOL)

    seen, sound = [], conv_moe.held_terms

    def spy(x, idx, w, w_gu, w_down, first, valid=None, **kw):
        jax.debug.callback(lambda v: seen.append(np.asarray(v)), valid)
        return sound(x, idx, w, w_gu, w_down, first, valid, **kw)

    monkeypatch.setattr(conv_moe, "held_terms", spy)
    jax.block_until_ready(jax.jit(
        lambda p: fam.chunk_rows(p, segs, block=BLOCK))(params))
    jax.effects_barrier()
    real = np.r_[np.arange(16) < 5, np.ones(16, bool)]
    assert len(seen) == cfg.n_layer - cfg.num_dense_layers
    assert all((v == real).all() for v in seen)


def test_the_steps_counts_reach_the_span_and_the_counters(built):
    from singa_tpu.models.conv_moe import TILE
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
    for e in range(16):
        assert registry().counter("serve.moe.expert_tokens", engine=lbl,
                                  expert=str(e)).value == held[e]
    assert registry().counter("serve.moe.assignments_elsewhere",
                              engine=lbl).value == 0
    tiles = 0
    for c, args in seen:
        assert args["experts_hit"] == np.count_nonzero(c[:, :-1])
        assert args["expert_tokens_max"] == c[:, :-1].max()
        # at one or two lanes nobody needs a second tile: a tile an
        # expert a layer, hit or not
        assert args["expert_tiles"] == 8 * 16
        assert c[:, :-1].max() <= TILE
        assert 0 < args["experts_hit"] <= 8 * 4 * 2
        assert set(args) == {"experts_hit", "expert_tiles",
                             "expert_tokens_max", "expert_tokens_mean"}
        tiles += args["expert_tiles"]
    assert registry().counter("serve.moe.tiles", engine=lbl).value == tiles
    eng.close()
    # the engine's metrics go with it
    assert all(m_.name != "serve.state.conv_tail_bytes"
               or dict(m_.labels).get("engine") != lbl
               for m_ in registry().metrics())


def test_a_crowded_expert_takes_further_tiles():
    """The tiles follow from the counts: an expert takes one for every
    ``TILE`` assignments, and one if it has none."""
    from singa_tpu.models.conv_moe import (TILE, ConvMoeConfig,
                                           ConvMoeFamily)

    cfg = ConvMoeConfig(num_hidden_layers=2, num_dense_layers=0,
                        layer_types=("conv", "full_attention"),
                        num_experts=4)
    counts = np.array([[0, 1, TILE, TILE + 1, 0],
                       [3 * TILE, 0, 0, 2, 0]], np.int32)
    args, incs, gauges = ConvMoeFamily(cfg).on_step_counts(counts, cfg)
    assert args["expert_tiles"] == (1 + 1 + 1 + 2) + (3 + 1 + 1 + 1)
    assert args["experts_hit"] == 5
    assert incs["serve.moe.tiles", ()] == 11
    assert gauges == {("serve.state.conv_tail_bytes", ()):
                      cfg.tail_bytes()}


def test_the_family_names_its_scopes_and_programs_keep_them(built):
    from singa_tpu.serve import paged

    m, _, _ = built
    fam = m.served_family()
    assert set(fam.scopes) == {"short_conv", "attn_full", "attn_proj",
                               "moe_route", "moe_experts", "dense_mlp",
                               "head"}
    assert fam.pad_aware and fam.step_counts and fam.value_leaf
    assert fam.features == frozenset()
    eng = _engine(m)
    _serve(eng, [_prompt(12)], 3)
    eng.close()
    kept = paged.program_scopes()
    assert {"short_conv", "attn_full", "moe_experts", "dense_mlp",
            "head"} <= set(kept["paged_decode_kernel"].values())
    assert {"short_conv", "attn_full", "moe_experts"} <= set(
        kept["chunk_row"].values())


def test_the_family_serves_with_gpt2s_math_out_of_reach(built,
                                                        monkeypatch):
    from singa_tpu.models import gpt2_decode

    def out_of_reach(*a, **k):
        raise AssertionError("GPT-2's math was called for another family")

    for name, fn in vars(gpt2_decode).items():
        if callable(fn) and getattr(fn, "__module__", "") \
                == gpt2_decode.__name__ and not isinstance(fn, type):
            monkeypatch.setattr(gpt2_decode, name, out_of_reach)
    eng = _engine(built[0], budget=8)
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
    assert feature in str(e.value) and "conv_moe" in str(e.value)


def test_fork_and_kv_ship_are_refused_by_name(built):
    m, _, _ = built
    eng = _engine(m)
    with pytest.raises(NotImplementedError, match="fork"):
        eng.submit(GenerationRequest(_prompt(9), max_new_tokens=2, n=2))
    with pytest.raises(NotImplementedError, match="KV image ship"):
        eng.start_prefix_build(_prompt(9))
    eng.close()
