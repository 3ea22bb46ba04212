"""The ``ssm_moe`` family (Nemotron-3-Super-120B-A12B: layers that are ONE
mixer each -- a Mamba-2 mixer, attention without positions, or a LatentMoE
of sigmoid-routed ``relu^2`` experts in a latent beside one shared expert)
at a tiny size on the CPU, float32: the program (model, served family, the
engine's paged path over K/V for the one attention layer and recurrent
state a slot for the Mamba layers) against the plain reference
``benchmark/references/ssm_moe.py`` on the reference's own seeded weights.
Logits are compared, not sampled tokens.

The tiny preset lives here only, every kind of layer in it: ``MEMEM*E``
(a stretch that repeats, then one of each), 4 query heads on 2 K/V heads
of 16; 8 Mamba heads of 16 in 2 groups, state 16, 4 taps; a router of 8
outputs, top-3, experts [0, 4) held, a latent of 32; blocks of 8 = the
scan's chunk.  The reference scales its weights by their fan-in, so every
layer adds to the stream what it adds at the published width.
"""

import dataclasses
import functools
import hashlib
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
TINY = dict(
    family="ssm_moe", vocab_size=512, hidden_size=64, num_hidden_layers=7,
    hybrid_override_pattern="MEMEM*E", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
    mamba_head_dim=16, expand=2, ssm_state_size=16, n_groups=2,
    conv_kernel=4, chunk_size=BLOCK, use_conv_bias=True,
    n_routed_experts=4, num_experts_per_tok=3, n_group=1, topk_group=1,
    norm_topk_prob=True, routed_scaling_factor=5, n_shared_experts=1,
    moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, mlp_hidden_act="relu2",
    tie_word_embeddings=False, layer_norm_epsilon=1e-5,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=0.0001,
    max_position_embeddings=262144,
    share=dict(num_experts_published=8, experts_held=[0, 4]),
    engine=dict(max_len=128, dtype="float32", block_size=BLOCK))
# float32 against float32, other orders of summation: the chunked scan
# against the recurrence a token at a time (exponentials of sums against
# products of exponentials), tiles of sorted rows against every expert on
# every row.  Logits are of scale 8; the same engine in bfloat16 reads
# 0.05-0.3 (test_a_bfloat16_engine_fails_the_float32_tolerance)
TOL = 3e-4


@pytest.fixture(scope="module")
def ref():
    return loader.load_module("references", "ssm_moe")


def _build(ref, cfg, seed):
    ad = loader.load_module("adapters", "ssm_moe")
    sizes = ref.sizes_of(cfg)
    m = ad.build_model(cfg, device.get_default_device(), train=False,
                       batch_shape=(1, 16))
    w = ref.init_weights(sizes, seed)
    ad.put_weights(m, w)
    return m, w, sizes


@pytest.fixture(scope="module")
def built(ref):
    """(model, reference weights, sizes) on the reference's seed-7
    weights."""
    return _build(ref, TINY, 7)


def _engine(m, num_blocks=64, max_slots=4, budget=16, dtype=jnp.float32):
    return m.serve(paged=PagedConfig(block_size=BLOCK,
                                     num_blocks=num_blocks,
                                     prefill_token_budget=budget),
                   dtype=dtype, max_slots=max_slots)


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
    return {k: jnp.zeros((cfg.n_a,) + shape, dt)
            for k, (shape, dt) in fam.state_spec(cfg).items()}


def _chunk_rows(fam, cfg, params, toks, widths=(16, 8)):
    """The family's chunk rows as the engine drives them, from a fresh
    zero row and zeroed state, in launches of ``widths`` in turn: (every
    prompt position's logits, the private rows, the state)."""
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


def _state_of(ref, w, toks):
    """What every Mamba layer must hold after ``toks``, a third way: the
    reference's own stream up to the layer, then the recurrence as a
    plain numpy loop over the tokens.  Returns (SSM states (n_m, h, p,
    n), the last four inputs of each convolution (n_m, 4, conv_dim))."""
    s = w.sizes
    ids = np.asarray(toks, np.int32)
    h, p, n, g = s["MH"], s["MP"], s["N"], s["G"]
    ds, cd, k = ref.d_ssm(s), ref.conv_dim(s), s["KC"]
    ssm, tails = [], []
    with jax.default_matmul_precision("highest"):
        xs = [ref.embed(w, jnp.asarray(ids))]
        for layer in range(s["L"]):
            if ref.kind(s, layer) == "M":
                t = {name: np.asarray(w.tensor(name, layer), np.float64)
                     for name in ("conv_w", "conv_b", "dt_bias", "a_log")}
                a = ref._norm(xs[0], w.tensor("ln", layer), s["eps"])
                proj = np.asarray(a @ w.tensor("w_in", layer), np.float64)
                xbc, dt = proj[:, ds:ds + cd], proj[:, ds + cd:]
                ext = np.concatenate([np.zeros((k, cd)), xbc])
                tails.append(ext[-k:])
                conv = sum(t["conv_w"][j] * ext[1 + j:1 + j + len(ids)]
                           for j in range(k)) + t["conv_b"]
                conv = conv / (1.0 + np.exp(-conv))               # silu
                x = conv[:, :ds].reshape(-1, h, p)
                b = np.repeat(conv[:, ds:ds + g * n].reshape(-1, g, n),
                              h // g, axis=1)
                dt = np.log1p(np.exp(dt + t["dt_bias"]))      # softplus
                st = np.zeros((h, p, n))
                for i in range(len(ids)):
                    st = (np.exp(-dt[i] * np.exp(t["a_log"]))[:, None, None]
                          * st + (dt[i][:, None] * x[i])[:, :, None]
                          * b[i][:, None, :])
                ssm.append(st)
            xs = ref._layer(w, xs, layer, "f32")
    return np.stack(ssm), np.stack(tails)


def test_full_forward_matches_the_reference(ref, built):
    m, w, _ = built
    toks = _prompt(53)
    got = np.asarray(m.forward(tensor.from_numpy(
        toks[None], device.get_default_device())).data)[0]
    want = _ref_logits(ref, w, toks)
    assert np.abs(want).max() > 2.0          # logits of a real scale
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_reference_in_small_blocks_is_the_reference(ref, built,
                                                        monkeypatch):
    """Rows 16 at a time (the recurrence and the convolution across the
    blocks' seams, blocks of queries against blocks of keys, the experts
    two at a time) against the whole sequence as one block."""
    _, w, _ = built
    toks = _prompt(90, 3)
    whole = _ref_logits(ref, w, toks)
    monkeypatch.setattr(ref, "ROWS", 16)
    monkeypatch.setattr(ref, "EG", 2)
    np.testing.assert_allclose(_ref_logits(ref, w, toks), whole, atol=1e-5)


@pytest.mark.parametrize("plen, widths", [(70, (16, 8)), (45, (8, 16)),
                                          (21, (8,)), (17, (32,)),
                                          (30, (24, 8))])
def test_chunk_rows_of_mixed_widths_match_the_reference(ref, built, plen,
                                                        widths):
    """Launches of one, two, three and four blocks in turn over a private
    row (the attention layer) and carried state (the Mamba layers): every
    prompt position's logits against the reference's full forward, for
    prompts whose end falls inside a launch -- and the state left behind
    is the unpadded prompt's: what the recurrence a token at a time
    leaves after the prompt's last token, and the convolution's last four
    inputs BEFORE the padding (``n_valid``)."""
    m, w, _ = built
    fam = m.served_family()
    params = fam.extract_params(m, dtype=jnp.float32)
    toks = _prompt(plen, seed=plen)
    got, _, state = _chunk_rows(fam, m.cfg, params, toks, widths)
    np.testing.assert_allclose(got, _ref_logits(ref, w, toks), atol=TOL)
    # 3 Mamba layers' state in the 1 x 3 places of the arenas' row
    assert state["ssm"].shape == (1, 3, 8, 16, 16)
    assert state["conv"].shape == (1, 3, 4, 192)
    ssm, tails = _state_of(ref, w, toks)
    np.testing.assert_allclose(np.asarray(state["ssm"])[0], ssm, atol=TOL)
    np.testing.assert_allclose(np.asarray(state["conv"])[0], tails,
                               atol=TOL)
    assert np.abs(ssm).max() > 0.05 and np.abs(tails).max() > 0.5


def test_prefill_then_decode_through_the_pool_and_the_state(ref, built):
    """Through the engine: budgeted chunked prefill of a long prompt and
    a short one (launches of two blocks and of one), then decode steps
    with both in ONE program.  Before every step the family's decode
    math is run on the engine's own pool, block tables and state arenas
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
            # 3 expert layers' rows: every live lane's 3 choices, over
            # the 4 held experts and the 4 held elsewhere
            assert counts.shape == (3, 5)
            assert (np.asarray(counts).sum(1) == 3 * live.sum()).all()
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
    pool and the state into arenas, one decode step -- against
    ``forward_full``."""
    from singa_tpu.models.ssm_moe import forward_full
    from singa_tpu.ops.paged_attention import row_to_blocks

    m, _, _ = built
    fam, cfg = m.served_family(), m.cfg
    params = fam.extract_params(m, dtype=jnp.float32)
    toks = _prompt(46, 11)
    _, (kc, vc), state = _chunk_rows(fam, cfg, params, toks[:-1])
    trash = jnp.zeros((1, 1, 8, kc.shape[2] * kc.shape[4]))
    pool_k = jnp.concatenate([row_to_blocks(kc, 8), trash], axis=1)
    pool_v = jnp.concatenate([row_to_blocks(vc, 8), trash], axis=1)
    # slot 0 of arenas of one slot and the trash row
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
    # the tail moved on a row: its older rows are the newer ones of before
    before, now = np.asarray(arena["conv"]), np.asarray(after["conv"])
    np.testing.assert_array_equal(now[:, 0, :, :3], before[:, 0, :, 1:])
    assert np.abs(np.asarray(after["ssm"])[:, 0]
                  - np.asarray(arena["ssm"])[:, 0]).max() > 1e-3
    assert not now[:, 1].any()              # the trash row: no dead lane
    assert not np.asarray(after["ssm"])[:, 1].any()


def test_a_bfloat16_engine_fails_the_float32_tolerance(ref, built):
    """The tolerance is tight enough to tell the precision: the same
    weights served by a bfloat16 engine (bf16 matrices and pool rows,
    float32 state) miss the float32 reference by hundreds of times
    ``TOL``."""
    m, w, _ = built
    fam = m.served_family()
    params = fam.extract_params(m, dtype=jnp.bfloat16)
    toks = _prompt(40, 13)
    ids = np.zeros((1, m.cfg.max_len), np.int32)
    ids[0, :40] = toks
    n_l, n_kv, d = fam.kv_geometry(m.cfg)
    kc = jnp.zeros((n_l, 1, n_kv, m.cfg.max_len, d), jnp.bfloat16)
    hidden, *_ = jax.jit(fam.chunk_row, static_argnames=("chunk", "block"))(
        params, jnp.asarray(ids), kc, kc, _zero_state(fam, m.cfg),
        jnp.int32(0), jnp.int32(40), chunk=40, block=BLOCK)
    got = np.asarray(fam.logits(params, hidden))[0]
    err = np.abs(got - _ref_logits(ref, w, toks)).max()
    assert err > 30 * TOL, err


# ---------------------------------------- one mixer a layer: K/V for one
# ---------------------------------------- layer in seven, state for three


def test_only_the_attention_layer_has_kv_and_the_mamba_layers_state(built):
    from singa_tpu.observe.registry import registry

    m, _, _ = built
    fam, cfg = m.served_family(), m.cfg
    assert fam.kv_geometry(cfg) == (1, 2, 16)   # the ATTENTION layer only
    assert fam.window(cfg) is None and fam.value_leaf
    f32 = jnp.dtype("float32")
    assert fam.state_spec(cfg) == {"ssm": ((3, 8, 16, 16), f32),
                                   "conv": ((3, 4, 192), f32)}
    assert (cfg.n_m, cfg.n_a, cfg.n_e, cfg.state_rows, cfg.d_ssm,
            cfg.conv_dim) == (3, 1, 3, 3, 128, 192)
    eng = _engine(m)
    lbl = eng.stats.engine_label
    assert eng.paged_arena.pool_k.shape == (1, 65, 8, 32)
    assert eng._state["ssm"].shape == (1, 5, 3, 8, 16, 16)
    assert eng._state["conv"].shape == (1, 5, 3, 4, 192)
    slot_bytes = 3 * 4 * (8 * 16 * 16 + 4 * 192)
    assert cfg.state_bytes() == slot_bytes
    assert registry().gauge("serve.state.bytes", engine=lbl).value \
        == 5 * slot_bytes
    _serve(eng, [_prompt(80, 1), _prompt(9, 2)], 12)
    # the last decode step of the two had one lane live
    assert registry().gauge("serve.state.ssm_bytes",
                            engine=lbl).value == slot_bytes
    eng.close()


def test_the_layers_are_walked_as_the_pattern_says(built):
    from singa_tpu.models.ssm_moe import (PUBLISHED_PATTERN, SsmMoeConfig,
                                          _plan)

    cfg = built[0].cfg
    assert cfg.stack_sizes() == {"m": 3, "a": 1, "e": 3}
    assert cfg.plan() == ("", ("ME", 2), "M*E")
    assert [cfg.place(i) for i in range(7)] == [
        ("m", 0), ("e", 0), ("m", 1), ("e", 1), ("m", 2), ("a", 0),
        ("e", 2)]
    # the benchmark's cut: one whole period
    assert _plan("MEMEMEMEM*E") == ("", ("ME", 4), "M*E")
    # as published: 88 layers, 40 : 8 : 40, five Mamba layers' state under
    # each attention layer's row of the arenas
    c = SsmMoeConfig()
    assert (c.n_m, c.n_a, c.n_e, c.state_rows) == (40, 8, 40, 5)
    assert (c.d_ssm, c.conv_dim, c.kv_width, c.n_held) == (
        8192, 10240, 256, 512)
    assert PUBLISHED_PATTERN[27:38] == "MEMEMEMEM*E"
    head, (unit, n), tail = c.plan()
    assert head + unit * n + tail == PUBLISHED_PATTERN and n * len(unit) > 44
    assert c.shapes("m")["w_in"] == (4096, 8192 + 10240 + 128)
    assert c.shapes("e")["e_up"] == (512, 1024, 2688)
    assert c.state_bytes() == 40 * 4 * (128 * 64 * 128 + 4 * 10240)
    with pytest.raises(ValueError, match="experts_held"):
        SsmMoeConfig(experts_held=(500, 600))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        SsmMoeConfig(num_hidden_layers=9)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        SsmMoeConfig(num_hidden_layers=3, hybrid_override_pattern="M-E")
    with pytest.raises(ValueError, match="a layer of each kind"):
        SsmMoeConfig(num_hidden_layers=2, hybrid_override_pattern="ME")
    with pytest.raises(ValueError, match="use_conv_bias"):
        SsmMoeConfig(use_conv_bias=False)
    a = SsmMoeConfig(hybrid_override_pattern=str(PUBLISHED_PATTERN))
    assert a == c and hash(a) == hash(c)
    assert dataclasses.replace(a, experts_held=(128, 256)).n_held == 128


def test_two_attention_layers_and_another_order_of_layers(ref):
    """Another pattern through the same code: two attention layers (a
    pool of two layers, the three Mamba layers' state in 2 x 2 places of
    which the last stays zero), a stretch of one letter that repeats --
    served through the engine and held to the reference token for
    token."""
    cfg = dict(TINY, hybrid_override_pattern="M*EMM*E")
    m, w, sizes = _build(ref, cfg, 11)
    assert m.cfg.stack_sizes() == {"m": 3, "a": 2, "e": 2}
    assert m.cfg.plan() == ("M*E", ("M", 2), "*E")
    assert m.cfg.state_rows == 2
    toks = _prompt(37, 2)
    got = np.asarray(m.forward(tensor.from_numpy(
        toks[None], device.get_default_device())).data)[0]
    np.testing.assert_allclose(got, _ref_logits(ref, w, toks), atol=TOL)
    eng = _engine(m)
    assert eng.paged_arena.pool_k.shape == (2, 65, 8, 32)
    assert eng._state["ssm"].shape == (2, 5, 2, 8, 16, 16)
    prompts = [_prompt(30, 3), _prompt(9, 4)]
    for p, out in zip(prompts, _serve(eng, prompts, 10)):
        assert ref.served_token_gap(w, sizes, out, len(p))[0] == 0.0
    ssm = np.asarray(eng._state["ssm"])
    assert np.abs(ssm[0, :2]).max() > 0 and np.abs(ssm[1, :2, 0]).max() > 0
    assert not ssm[1, :, 1].any()           # the fourth place: nobody's
    eng.close()


# -------------------------------------------------------- the expert layer


def test_the_four_shares_add_up_to_the_whole_layer(ref, built):
    """The guide's share test: the routed parts that the four ownership
    ranges of two give, with the shared expert counted ONCE and the
    latent up-projection applied to their sum (it is linear), equal the
    uncut reference's whole ``E`` layer -- for the reference's share and
    for the program's ``held_terms`` alike.  With all 8 held every choice
    is computed here; one share alone is not the layer."""
    from singa_tpu.models.ssm_moe import TILE
    from singa_tpu.ops.expert_layer import held_terms, relu2, route

    _, w, sizes = built
    layer = 3
    t = lambda name, *a: w.tensor(name, layer, *a)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(23, 64)),
                    jnp.float32)
    a = ref._norm(x, t("ln"), sizes["eps"])
    whole = ref.expert_layer(w, [a], layer, "f32", held=(0, 8))[0]
    shared = whole - ref.expert_layer(w, [a], layer, "f32", held=(0, 8),
                                      shared=False)[0]
    assert float(jnp.abs(shared).max()) > 0.2
    parts = sum(ref.expert_layer(w, [a], layer, "f32",
                                 held=(2 * i, 2 * i + 2), shared=False)[0]
                for i in range(4))
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole), atol=TOL)
    # one share alone is NOT the layer
    one = ref.expert_layer(w, [a], layer, "f32", held=(0, 2))[0]
    assert float(jnp.abs(one - whole).max()) \
        > 0.2 * float(jnp.abs(whole).max())

    idx, wt = route(a, t("router"), t("bias"), n_group=1, topk_group=1,
                    top_k=3, scale=5.0)
    dense = np.asarray(ref.route(a, t("router"), t("bias"), sz=w._sz))
    got = np.zeros_like(dense)
    np.put_along_axis(got, np.asarray(idx), np.asarray(wt), axis=1)
    np.testing.assert_allclose(got, dense, atol=1e-6)
    np.testing.assert_allclose(np.asarray(wt).sum(1), 5.0, rtol=1e-5)

    def stack(es):
        return (jnp.stack([t("e_up", e) for e in es]),
                jnp.stack([t("e_down", e) for e in es]))

    with jax.default_matmul_precision("highest"):
        u = a @ t("w_fc1")
        r, counts = held_terms(u, idx, wt, *stack(range(8)), 0, tile=TILE,
                               body=relu2)
        assert int(counts[:-1].sum()) == 23 * 3 and int(counts[-1]) == 0
        total, seen = 0.0, 0
        for i in range(4):
            r_i, counts = held_terms(u, idx, wt,
                                     *stack(range(2 * i, 2 * i + 2)),
                                     2 * i, tile=TILE, body=relu2)
            total = total + r_i
            seen += int(counts[:-1].sum())
            assert int(counts.sum()) == 23 * 3
        assert seen == 23 * 3               # every choice computed once
        np.testing.assert_allclose(np.asarray(total), np.asarray(r),
                                   atol=TOL)
        np.testing.assert_allclose(
            np.asarray(total @ t("w_fc2") + shared), np.asarray(whole),
            atol=TOL)


def test_the_latent_body_matches_a_plain_loop_at_top_22():
    """``held_terms`` with the two-matrix ``relu^2`` body, 22 choices a
    token over 64 experts of which [16, 40) are held, against every held
    expert applied to every row and weighted."""
    from singa_tpu.ops.expert_layer import held_terms, relu2, route

    rng = np.random.default_rng(3)
    t, e, im, r, lo, hi = 37, 24, 40, 64, 16, 40
    x = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(e, r)) / np.sqrt(e), jnp.float32)
    bias = jnp.asarray(0.01 * rng.normal(size=r), jnp.float32)
    up = jnp.asarray(rng.normal(size=(hi - lo, e, im)) / np.sqrt(e),
                     jnp.float32)
    down = jnp.asarray(rng.normal(size=(hi - lo, im, e)) / np.sqrt(im),
                       jnp.float32)
    idx, wt = route(x, router, bias, n_group=1, topk_group=1, top_k=22,
                    scale=5.0)
    assert idx.shape == (t, 22)
    valid = jnp.arange(t) < 30
    with jax.default_matmul_precision("highest"):
        y, counts = held_terms(x, idx, wt, up, down, lo, valid, tile=16,
                               body=relu2)
        dense = np.zeros((t, r), np.float32)
        np.put_along_axis(dense, np.asarray(idx), np.asarray(wt), axis=1)
        dense[30:] = 0.0
        want = sum(dense[:, lo + j, None]
                   * (np.square(np.maximum(np.asarray(x @ up[j]), 0.0))
                      @ np.asarray(down[j])) for j in range(hi - lo))
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4)
    held = (np.asarray(idx)[:30] >= lo) & (np.asarray(idx)[:30] < hi)
    assert int(counts[:-1].sum()) == held.sum()
    assert int(counts[-1]) == 30 * 22 - held.sum()
    assert not np.asarray(y)[30:].any()     # padding chooses nothing


#: sha256 of the text ``held_terms`` lowered to at ea77d7e (the parent of
#: the PR that made the expert body the caller's) on the three callers'
#: arguments at their tiny presets: ``mla_moe`` (tile 16, a layer's own
#: stacks), ``swa_moe`` and ``conv_moe`` (a traced layer of whole stacks;
#: tiles of 16 and of 32).  The default body lowers to the same text.
PARENT_HELD_TERMS = {
    "mla_moe":
        "6167c5f28fefcb3ab12d0402c1d5ef1115e86406d224a0fd13b5c738a07b602a",
    "swa_moe":
        "6b649b0bbac77bb29a9b320749a68ac47bec54ec9e585d893f37ef0f572fde6b",
    "conv_moe":
        "e9092e9c281b6671e97ef365fac329655af146c1b9c4e4020d96af08a15b01dd",
}


def held_terms_lowering(caller):
    from singa_tpu.ops.expert_layer import held_terms

    f32 = jnp.float32
    sds = lambda *s, dt=f32: jax.ShapeDtypeStruct(s, dt)
    t, e, im, k, n = 16, 64, 32, 4, 8
    stacks = (sds(n, e, 2 * im), sds(n, im, e))
    kw = dict(tile=16)
    if caller != "mla_moe":
        stacks = tuple(sds(3, *s.shape) for s in stacks)
        kw = dict(tile=32 if caller == "conv_moe" else 16)

    def run(x, idx, w, w_gu, w_down, valid, layer):
        return held_terms(x, idx, w, w_gu, w_down, 8, valid,
                          layer=None if caller == "mla_moe" else layer,
                          **kw)

    text = jax.jit(run).lower(
        sds(t, e), sds(t, k, dt=jnp.int32), sds(t, k), *stacks,
        sds(t, dt=jnp.bool_), sds(dt=jnp.int32)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("caller", list(PARENT_HELD_TERMS))
def test_the_swiglu_default_lowers_to_the_parents_text(caller):
    assert held_terms_lowering(caller) == PARENT_HELD_TERMS[caller]


# ------------------------------------------------------- slots and state


def test_a_slot_reused_after_retirement_starts_from_zeroed_state(ref,
                                                                 built):
    m, w, sizes = built
    a, b = _prompt(40, 4), _prompt(13, 5)
    eng = _engine(m, max_slots=1)
    _serve(eng, [a], 6)
    assert float(jnp.abs(eng._state["ssm"][:, 0]).max()) > 0   # a's state
    second = _serve(eng, [b], 6)[0]
    resets = eng._c_state_resets.value
    eng.close()
    assert resets == 2
    assert ref.served_token_gap(w, sizes, second, len(b))[0] == 0.0


def test_preempt_then_resume_continues_token_for_token(built):
    """A lane preempted in mid-reply: its blocks of the pool and its
    recurrent state go to the host and come back, and it goes on as if
    nothing had happened."""
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
            # neither the freed blocks' bytes nor the slot's old state
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
    the STATE of every admission against the engine that launches a
    block at a time."""
    lw.assert_same_as_one_block(launch_runs.run(ratio, case),
                                launch_runs.run(1, case), case, ratio,
                                atol=TOL)


def test_a_slots_unused_blocks_touch_nothing_and_choose_no_expert(
        built, monkeypatch):
    """The pair program at the family's seam: two requests' segments in
    slots of two blocks, the first 5 tokens into its second block (13
    real positions behind it, 11 of its slot unused), the second a whole
    slot.  Each request's hidden rows, private rows and state are those
    of its own launch alone; the rows below ``off`` are the bytes they
    were; and the expert layers are told which 21 of the 32 tokens are
    real."""
    from singa_tpu.models import ssm_moe
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
        for k in ("ssm", "conv"):
            np.testing.assert_allclose(got[3][k], want[3][k], atol=TOL)
    for k in (1, 2):
        # a's first block as it was, its second as its own launch wrote
        np.testing.assert_array_equal(got_a[k][..., :8, :],
                                      (a_kc, a_vc)[k - 1][..., :8, :])
        np.testing.assert_allclose(got_a[k][..., :13, :],
                                   want_a[k][..., :13, :], atol=TOL)
        np.testing.assert_allclose(got_b[k], want_b[k], atol=TOL)

    seen, sound = [], ssm_moe.held_terms

    def spy(x, idx, w, w_up, w_down, first, valid=None, **kw):
        jax.debug.callback(lambda v: seen.append(np.asarray(v)), valid)
        return sound(x, idx, w, w_up, w_down, first, valid, **kw)

    monkeypatch.setattr(ssm_moe, "held_terms", spy)
    jax.block_until_ready(jax.jit(
        lambda p: fam.chunk_rows(p, segs, block=BLOCK))(params))
    jax.effects_barrier()
    real = np.r_[np.arange(16) < 5, np.ones(16, bool)]
    assert len(seen) == cfg.n_e
    assert all((v == real).all() for v in seen)


def test_one_block_lowers_to_one_program_whatever_the_budget(launch_runs):
    """The one-block call is one program: the engine whose budget is four
    blocks lowers it to the text the one-block engine does."""
    assert lw.chunk_row_lowering(launch_runs.engine(1)) == \
        lw.chunk_row_lowering(launch_runs.engine(4))


# ------------------------------------------------------------ the tracing


def test_the_steps_counts_reach_the_span_and_the_counters(built):
    from singa_tpu.models.ssm_moe import TILE
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
    elsewhere = sum(int(c[:, -1].sum()) for c, _ in seen)
    assert elsewhere > 0
    assert registry().counter("serve.moe.assignments_elsewhere",
                              engine=lbl).value == elsewhere
    tiles = 0
    for c, args in seen:
        lanes = c.sum() // (3 * 3)
        assert lanes in (1, 2) and c.sum() == lanes * 9
        assert args["experts_hit"] == np.count_nonzero(c[:, :-1])
        assert args["expert_tokens_max"] == c[:, :-1].max()
        assert args["expert_tokens_mean"] == pytest.approx(
            c[:, :-1].mean())
        assert args["choices_elsewhere"] == pytest.approx(
            100.0 * c[:, -1].sum() / c.sum())
        # at one or two lanes nobody needs a second tile: a tile an
        # expert a layer, hit or not
        assert args["expert_tiles"] == 3 * 4
        assert c[:, :-1].max() <= TILE
        assert set(args) == {"experts_hit", "expert_tiles",
                             "expert_tokens_max", "expert_tokens_mean",
                             "choices_elsewhere"}
        tiles += args["expert_tiles"]
    assert registry().counter("serve.moe.tiles", engine=lbl).value == tiles
    eng.close()
    # the engine's metrics go with it
    assert all(m_.name != "serve.state.ssm_bytes"
               or dict(m_.labels).get("engine") != lbl
               for m_ in registry().metrics())


def test_a_crowded_expert_takes_further_tiles():
    """The tiles follow from the counts: an expert takes one for every
    ``TILE`` assignments, and one if it has none; the live lanes follow
    from the choices."""
    from singa_tpu.models.ssm_moe import TILE, SsmMoeConfig, SsmMoeFamily

    cfg = SsmMoeConfig(num_hidden_layers=4, hybrid_override_pattern="ME*E",
                       n_routed_experts=8, experts_held=(0, 4),
                       num_experts_per_tok=2)
    counts = np.array([[0, 1, TILE, TILE + 1, 14],
                       [3 * TILE, 0, 0, 0, 0]], np.int32)
    assert counts.sum(1).tolist() == [2 * (TILE + 8)] * 2
    args, incs, gauges = SsmMoeFamily(cfg).on_step_counts(counts, cfg)
    assert args["expert_tiles"] == (1 + 1 + 1 + 2) + (3 + 1 + 1 + 1)
    assert args["experts_hit"] == 4
    assert args["choices_elsewhere"] == pytest.approx(
        100.0 * 14 / (4 * (TILE + 8)))
    assert incs == {("serve.moe.tiles", ()): 11,
                    ("serve.moe.assignments_elsewhere", ()): 14}
    assert gauges == {("serve.state.ssm_bytes", ()):
                      (TILE + 8) * cfg.state_bytes()}


def test_the_family_names_its_scopes_and_programs_keep_them(built):
    from singa_tpu.serve import paged

    m, _, _ = built
    fam = m.served_family()
    assert set(fam.scopes) == {"ssm_proj", "ssm_scan", "ssm_step",
                               "attn_full", "attn_proj", "moe_route",
                               "moe_latent", "moe_experts", "head"}
    assert fam.pad_aware and fam.step_counts and fam.value_leaf
    assert fam.features == frozenset()
    eng = _engine(m)
    _serve(eng, [_prompt(12)], 3)
    eng.close()
    kept = paged.program_scopes()
    assert {"ssm_step", "ssm_proj", "attn_full", "moe_latent",
            "moe_experts", "head"} <= set(
        kept["paged_decode_kernel"].values())
    assert {"ssm_scan", "attn_full", "moe_latent", "moe_experts"} <= set(
        kept["chunk_row"].values())


def test_one_copy_of_the_scan_and_of_the_step_serves_both_families():
    """``falcon_h1`` and ``ssm_moe`` run the same functions of
    ``ops/mamba2.py``: neither keeps a scan chunk or a lane loop of its
    own."""
    import inspect

    from singa_tpu.models import falcon_h1, ssm_moe
    from singa_tpu.ops import mamba2

    assert falcon_h1.ssd_chunk is mamba2.ssd_chunk
    for mod in (falcon_h1, ssm_moe):
        src = inspect.getsource(mod)
        assert "mamba2.mix(" in src and "mamba2.step(" in src
        assert "fori_loop" not in src and "def ssd_chunk" not in src


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
    assert feature in str(e.value) and "ssm_moe" in str(e.value)


def test_fork_and_kv_ship_are_refused_by_name(built):
    m, _, _ = built
    eng = _engine(m)
    with pytest.raises(NotImplementedError, match="fork"):
        eng.submit(GenerationRequest(_prompt(9), max_new_tokens=2, n=2))
    with pytest.raises(NotImplementedError, match="KV image ship"):
        eng.start_prefix_build(_prompt(9))
    eng.close()
