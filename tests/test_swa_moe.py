"""The ``swa_moe`` family (Trinity-Mini: window and full attention mixed
by layer, gated attention, sigmoid-routed experts) at a tiny size on the
CPU, float32: the program (model, served family, the engine's paged path
over a cache of TWO kinds -- the pool for the full layers, per-slot rings
for the window layers) against the plain reference
``benchmark/references/swa_moe.py`` on the reference's own seeded
weights.  Logits are compared, not sampled tokens.

The tiny preset lives here only, every ratio of the published model
kept: two periods of three window layers and a full one, the two leading
layers dense, 4 query heads on 2 K/V heads, 32 router outputs, top-8, one
shared expert; this "chip" holds experts 0-3, one of eight shares; a
window of 16 positions in blocks of 8, so a 100-position sequence turns
its rings six times.
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

PERIOD, WINDOW, BLOCK = 4, 16, 8
TINY = dict(
    family="swa_moe", vocab_size=512, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_dense_layers=2,
    num_experts=4, num_shared_experts=1, num_experts_per_tok=8, n_group=1,
    topk_group=1, route_norm=True, route_scale=2.826,
    sliding_window=WINDOW, global_attn_every_n_layers=PERIOD,
    layer_types=["full_attention" if (i + 1) % PERIOD == 0
                 else "sliding_attention" for i in range(8)],
    mup_enabled=True, rms_norm_eps=1e-5, rope_theta=10000,
    max_position_embeddings=131072,
    share=dict(num_experts_published=32, experts_held=[0, 4]),
    engine=dict(max_len=128, dtype="float32", block_size=BLOCK))
TOL = 2e-4      # float32 against float32, other orders of summation


@pytest.fixture(scope="module")
def ref():
    return loader.load_module("references", "swa_moe")


@pytest.fixture(scope="module")
def built(ref):
    """(model, reference weights, sizes) on the reference's seed-7
    weights."""
    ad = loader.load_module("adapters", "swa_moe")
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
    return {k: jnp.zeros((cfg.n_periods,) + shape, dt)
            for k, (shape, dt) in fam.state_spec(cfg).items()}


def _chunk_rows(fam, cfg, params, toks, widths=(16, 8)):
    """The family's chunk rows as the engine drives them, from a fresh
    zero row and zeroed rings, in launches of ``widths`` in turn: (every
    prompt position's logits, the private rows, the rings)."""
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


def test_full_forward_under_the_band_mask_matches_the_reference(ref, built):
    m, w, _ = built
    toks = _prompt(53)
    got = np.asarray(m.forward(tensor.from_numpy(
        toks[None], device.get_default_device())).data)[0]
    want = _ref_logits(ref, w, toks)
    assert np.abs(want).max() > 0.5          # logits of a real scale
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_reference_in_small_blocks_is_the_reference(ref, built,
                                                        monkeypatch):
    """Rows 16 at a time (blocks of queries against blocks of keys, the
    band's blocks skipped) against the whole sequence as one block."""
    _, w, _ = built
    toks = _prompt(90, 3)
    whole = _ref_logits(ref, w, toks)
    monkeypatch.setattr(ref, "ROWS", 16)
    np.testing.assert_allclose(_ref_logits(ref, w, toks), whole, atol=1e-5)


@pytest.mark.parametrize("plen, widths", [(70, (16, 8)), (45, (8, 16)),
                                          (21, (8,))])
def test_chunk_rows_of_mixed_widths_match_the_reference(ref, built, plen,
                                                        widths):
    """Launches of one and of two blocks in turn over a private row (the
    full layers) and a ring (the window layers): every prompt position's
    logits against the reference's full forward -- across the window,
    round the ring several times, for a prompt that is not a multiple of
    the block."""
    m, w, _ = built
    fam = m.served_family()
    params = fam.extract_params(m, dtype=jnp.float32)
    toks = _prompt(plen, seed=plen)
    got, _, state = _chunk_rows(fam, m.cfg, params, toks, widths)
    np.testing.assert_allclose(got, _ref_logits(ref, w, toks), atol=TOL)
    # a ring holds the newest ``window`` positions and no more
    assert state["win_k"].shape == (2, 3, WINDOW, 32)


def test_prefill_then_decode_through_the_pool_and_the_rings(ref, built):
    """Through the engine: budgeted chunked prefill of a long prompt and
    a short one (launches of two blocks and of one), then decode steps
    with both in ONE program.  Before every step the family's decode
    math is run on the engine's own pool, block tables and ring arenas
    (undonated, so nothing moves) and each live lane's logits are held
    to the reference's full forward over that lane's sequence so far:
    the long lane crosses the window and turns its rings six times
    beside the short lane, which has not filled its window yet."""
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
            # 6 expert layers' rows, then the five counts of the lanes
            assert counts.shape == (6 + 5, 5)
            assert (np.asarray(counts)[:6].sum(1) == 8 * live.sum()).all()
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
    pool and the rings into an arena, one decode step -- against
    ``forward_full``."""
    from singa_tpu.models.swa_moe import forward_full
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
    logits, *_ = jax.jit(functools.partial(fam.decode_step, block=8,
                                           trash=16))(
        params, pool_k, pool_v, arena, jnp.asarray([0]),
        jnp.arange(16)[None], jnp.asarray(toks[-1:]), jnp.asarray([45]),
        jnp.asarray([True]), jnp.int32(6))
    want = forward_full(params, jnp.asarray(toks), cfg)[-1]
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=TOL)


# ------------------------------------------------------ the cache's two kinds


def test_a_slots_window_bytes_are_its_rings_whatever_its_length(built):
    """The window layers hold ``sliding_window`` rows a slot a layer, for
    9 positions as for 110; the full layers hold blocks by the length."""
    from singa_tpu.observe.registry import registry

    m, _, _ = built
    fam, cfg = m.served_family(), m.cfg
    assert fam.kv_geometry(cfg) == (2, 2, 16)       # the FULL layers only
    assert fam.window(cfg) is None
    spec = fam.state_spec(cfg)
    assert spec["win_k"] == ((3, WINDOW, 32), jnp.dtype("float32"))
    assert set(spec) == {"win_k", "win_v"}
    ring_bytes = 2 * 6 * WINDOW * 32 * 4            # K and V, 6 layers
    full_row = 2 * 2 * 32 * 4       # a position's K and V in 2 layers
    eng = _engine(m)
    lbl = eng.stats.engine_label
    assert eng.paged_arena.pool_k.shape == (2, 65, 8, 32)
    assert eng._state["win_k"].shape == (2, 5, 3, WINDOW, 32)
    assert registry().gauge("serve.state.bytes", engine=lbl).value \
        == 5 * ring_bytes
    seen = []
    on = eng._on_step_counts

    def keep(counts):
        on(counts)
        seen.append(dict(eng._step_counts, blocks=[
            len(s.blocks) for s in eng._slots if s is not None]))

    eng._on_step_counts = keep
    _serve(eng, [_prompt(80, 1), _prompt(9, 2)], 30)
    g = registry().gauge
    assert g("serve.kv.window_ring_bytes", engine=lbl).value == ring_bytes
    assert g("serve.kv.row_bytes", engine=lbl, kind="window").value \
        == 6 * 2 * 32 * 4
    assert g("serve.kv.row_bytes", engine=lbl, kind="full").value \
        == 2 * 2 * 32 * 4
    # decode steps that began a turn of a ring: the long lane's at
    # positions 80 and 96, the short one's at 16 and 32
    assert registry().counter("serve.kv.ring_wraps",
                              engine=lbl).value == 4
    eng.close()
    assert {a["window_ring_bytes"] for a in seen} == {ring_bytes}
    two = [a for a in seen if len(a["blocks"]) == 2]
    assert len(two) >= 25
    for a in two:
        cap = 8 * sum(a["blocks"])
        assert a["kv_bytes_held"] == cap * full_row + 2 * ring_bytes
        assert a["kv_bytes_uniform"] == cap * 4 * full_row
    # what the long lane holds grows with its blocks only
    first, last = two[0], two[-1]
    assert last["kv_bytes_held"] - first["kv_bytes_held"] == \
        8 * (sum(last["blocks"]) - sum(first["blocks"])) * full_row
    # rows a step must read: the short lane's all, the long lane's window
    assert first["window_rows"] < 2 * WINDOW <= first["full_rows"]
    assert last["window_rows"] == 2 * WINDOW


def test_a_window_layers_decode_work_is_its_own_lanes(built):
    """The window layers' loop in the decode program runs once a lane,
    over that lane's ring: its bound is the number of lanes, a constant
    of the program, whatever the positions -- a lane 12,000 positions in
    does not lengthen what a lane 300 in walks.  (The full layers' block
    loop is the one whose bound follows the longest lane.)"""
    from singa_tpu.ops.paged_attention import ring_decode_attn

    def loops(jaxpr, out):
        for e in jaxpr.eqns:
            if e.primitive.name in ("while", "scan"):
                out.append((e.primitive.name, e.params.get("length")))
            for v in e.params.values():
                for j in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(j, "jaxpr", j)
                    if hasattr(inner, "eqns"):
                        loops(inner, out)
        return out

    w, ring, x = 2, 2048, 512
    arena = jax.ShapeDtypeStruct((8, 3, 3, ring, x), jnp.bfloat16)
    f = lambda q, k, v, a_k, a_v, slots, pos: ring_decode_attn(
        q, k, v, a_k, a_v, (jnp.int32(1), jnp.int32(2)), slots, pos,
        0.088, 2048)
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(f)(
        sds((w, 4, 8, 128), jnp.bfloat16), sds((w, x), jnp.bfloat16),
        sds((w, x), jnp.bfloat16), arena, arena, sds((w,), jnp.int32),
        sds((w,), jnp.int32))
    # one loop, of as many steps as lanes, and no loop whose trip count
    # is traced (a ``while``)
    assert loops(jaxpr.jaxpr, []) == [("scan", w)]
    # and the rows the step counts as read: the window's, not the length's
    m = built[0]
    fam, cfg = m.served_family(), m.cfg
    counts = np.zeros((6 + 5, 5), np.int32)
    counts[-5:, 0] = [2, 12000 + 300, min(12000, 16) + min(300, 16),
                      12416, 0]
    args, _, _ = fam.on_step_counts(counts, cfg)
    assert args["window_rows"] == 2 * WINDOW
    assert args["full_rows"] == 12300


def test_the_eight_shares_add_up_to_the_whole_layer(ref, built):
    """The routed parts that all eight ownership ranges give, plus the
    shared expert once, are the uncut 32-expert reference layer -- for
    the reference's share and for the program's ``held_terms`` alike.
    (The sum is taken before the layer's post-norm, which every chip
    applies to what it has: the exchange is what would make that sum
    whole on every chip.)"""
    from singa_tpu.ops.expert_layer import held_terms, route, swiglu

    _, w, sizes = built
    layer = 5
    x = jnp.asarray(np.random.default_rng(5).normal(size=(23, 64)),
                    jnp.float32)
    m_in = ref._norm(x, w.tensor("ln_pre_mlp", layer), sizes["eps"])
    terms = lambda held: ref.ffn_terms(w, [m_in], layer, "f32",
                                       held=held)[0]
    whole = terms((0, 32))
    shared = ref._swiglu(m_in, w.tensor("s_gate", layer),
                         w.tensor("s_up", layer),
                         w.tensor("s_down", layer), precision="f32")
    parts = sum(terms((4 * i, 4 * i + 4)) - shared for i in range(8))
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole), atol=TOL)
    # the program's: one route, eight ownership ranges
    idx, wt = route(m_in, w.tensor("router", layer),
                    w.tensor("bias", layer), n_group=1, topk_group=1,
                    top_k=8, scale=2.826)
    dense = np.asarray(ref.route(m_in, w.tensor("router", layer),
                                 w.tensor("bias", layer), sz=w._sz))
    got = np.zeros_like(dense)
    np.put_along_axis(got, np.asarray(idx), np.asarray(wt), axis=1)
    np.testing.assert_allclose(got, dense, atol=1e-6)
    np.testing.assert_allclose(np.asarray(wt).sum(1), 2.826, rtol=1e-5)
    total, seen = 0.0, 0
    for i in range(8):
        es = range(4 * i, 4 * i + 4)
        w_gu = jnp.stack([jnp.concatenate(
            [w.tensor("e_gate", layer, e), w.tensor("e_up", layer, e)], 1)
            for e in es])
        w_down = jnp.stack([w.tensor("e_down", layer, e) for e in es])
        y, counts = held_terms(m_in, idx, wt, w_gu, w_down, 4 * i)
        total = total + y
        seen += int(counts[:-1].sum())
        assert int(counts.sum()) == 23 * 8
    assert seen == 23 * 8               # every choice computed once
    with jax.default_matmul_precision("highest"):
        total = total + swiglu(m_in, jnp.concatenate(
            [w.tensor("s_gate", layer), w.tensor("s_up", layer)], 1),
            w.tensor("s_down", layer))
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=TOL)
    # one share alone is NOT the layer
    assert float(jnp.abs(terms((0, 4)) - whole).max()) \
        > 0.2 * float(jnp.abs(whole).max())


def test_a_slot_reused_after_retirement_starts_from_zeroed_rings(ref,
                                                                 built):
    m, w, sizes = built
    a, b = _prompt(40, 4), _prompt(13, 5)
    eng = _engine(m, max_slots=1)
    _serve(eng, [a], 6)
    assert float(jnp.abs(eng._state["win_k"][:, 0]).max()) > 0   # a's rows
    second = _serve(eng, [b], 6)[0]
    resets = eng._c_state_resets.value
    eng.close()
    assert resets == 2
    assert ref.served_token_gap(w, sizes, second, len(b))[0] == 0.0


def test_preempt_then_resume_mid_ring_continues_token_for_token(built):
    """A lane preempted with its rings part-way round (position 45 of a
    ring of 16): its blocks of the pool and its rings go to the host and
    come back, and it goes on as if nothing had happened."""
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
            assert eng._pos[idx[0]] % WINDOW not in (0, WINDOW - 1)
            blocks = list(eng._slots[idx[0]].blocks)
            eng._preempt_slot(idx[0], reason="test")
            # neither the freed blocks' bytes nor the slot's old rings
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


def test_ring_writes_run_on_at_the_rings_start_and_skip_the_padding():
    from singa_tpu.ops.paged_attention import ring_write_chunk

    ring = jnp.zeros((2, 32, 4)) - 1.0
    rows = jnp.arange(16 * 4, dtype=jnp.float32).reshape(16, 4)
    # 16 rows at position 56 of a ring of 32: rows 24-31, then 0-7; the
    # last 3 are padding
    out = np.asarray(ring_write_chunk(ring, 1, rows, jnp.int32(56),
                                      jnp.int32(13), 8))
    assert (out[0] == -1).all()
    np.testing.assert_array_equal(out[1, 24:32], np.asarray(rows[:8]))
    np.testing.assert_array_equal(out[1, 0:5], np.asarray(rows[8:13]))
    assert (out[1, 5:24] == -1).all()


# ----------------------------------------------------------------- the seam


@pytest.fixture(scope="module")
def launch_runs(built):
    runs = lw.Runs(lambda budget: _engine(built[0], budget=budget), 512)
    yield runs
    runs.close()


@pytest.mark.parametrize("case", list(lw.CASES))
def test_a_launch_of_two_blocks_leaves_what_one_block_at_a_time_did(
        launch_runs, case):
    """One launch a request a step, two blocks wide (the whole ring, at
    this size): the tokens, the private rows and the RINGS of every
    admission against the engine that launches a block at a time."""
    lw.assert_same_as_one_block(launch_runs.run(2, case),
                                launch_runs.run(1, case), case, 2,
                                atol=TOL)


def test_one_block_lowers_to_the_program_it_was(launch_runs):
    assert lw.chunk_row_lowering(launch_runs.engine(1)) == \
        lw.PARENT_LOWERING["swa_moe"]


@pytest.fixture(scope="module")
def wide_runs(ref):
    """The preset with a window of four blocks, so that a budget of
    three and of four blocks is allowed (and with four, the pair
    program)."""
    ad = loader.load_module("adapters", "swa_moe")
    tiny = dict(TINY, sliding_window=4 * BLOCK)
    m = ad.build_model(tiny, device.get_default_device(), train=False,
                       batch_shape=(1, 16))
    ad.put_weights(m, ref.init_weights(ref.sizes_of(tiny), 7))
    runs = lw.Runs(lambda budget: _engine(m, budget=budget), 512)
    yield runs
    runs.close()


@pytest.mark.parametrize("ratio", [3, 4])
@pytest.mark.parametrize("case", list(lw.CASES))
def test_three_blocks_and_two_requests_a_launch_leave_the_same(
        wide_runs, case, ratio):
    """Three blocks in one launch, and at a budget of four blocks two
    requests' pieces in one: tokens, private rows and RINGS (an unused
    block of a slot lays nothing into its ring) against a block at a
    time."""
    lw.assert_same_as_one_block(wide_runs.run(ratio, case),
                                wide_runs.run(1, case), case, ratio,
                                atol=TOL)


def test_a_budget_wider_than_the_window_is_refused(built):
    with pytest.raises(ValueError, match="the launch"):
        _engine(built[0], budget=32)


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
    held = sum(c[:-5, :-1].sum(0) for c, _ in seen)
    for e in range(4):
        assert registry().counter("serve.moe.expert_tokens", engine=lbl,
                                  expert=str(e)).value == held[e]
    assert registry().counter("serve.moe.assignments_elsewhere",
                              engine=lbl).value \
        == sum(c[:-5, -1].sum() for c, _ in seen)
    for c, args in seen:
        assert args["experts_hit"] == np.count_nonzero(c[:-5, :-1])
        assert args["expert_tokens_max"] == c[:-5, :-1].max()
        assert 0 <= args["experts_hit"] <= 6 * 4
        lanes, full_rows = c[-5, 0], c[-4, 0]
        assert 1 <= lanes <= 2 and args["full_rows"] == full_rows
        assert set(args) == {
            "experts_hit", "expert_tokens_max", "expert_tokens_mean",
            "kv_bytes_held", "kv_bytes_uniform", "window_ring_bytes",
            "full_rows", "window_rows"}
    eng.close()
    # the engine's metrics go with it
    assert all(m_.name != "serve.kv.window_ring_bytes"
               or dict(m_.labels).get("engine") != lbl
               for m_ in registry().metrics())


def test_the_family_names_its_scopes_and_programs_keep_them(built):
    from singa_tpu.serve import paged

    m, _, _ = built
    fam = m.served_family()
    assert set(fam.scopes) == {"attn_window", "attn_full", "attn_proj",
                               "moe_route", "moe_experts", "moe_shared",
                               "dense_mlp", "head"}
    assert fam.pad_aware and fam.step_counts and fam.value_leaf
    eng = _engine(m)
    _serve(eng, [_prompt(12)], 3)
    eng.close()
    kept = paged.program_scopes()
    assert {"attn_window", "attn_full", "moe_experts", "moe_shared",
            "dense_mlp", "head"} <= set(
                kept["paged_decode_kernel"].values())
    assert {"attn_window", "attn_full", "moe_experts"} <= set(
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
    assert feature in str(e.value) and "swa_moe" in str(e.value)


def test_fork_and_kv_ship_are_refused_by_name(built):
    m, _, _ = built
    eng = _engine(m)
    with pytest.raises(NotImplementedError, match="fork"):
        eng.submit(GenerationRequest(_prompt(9), max_new_tokens=2, n=2))
    with pytest.raises(NotImplementedError, match="KV image ship"):
        eng.start_prefix_build(_prompt(9))
    eng.close()


def test_the_configuration_refuses_what_it_cannot_be():
    from singa_tpu.models.swa_moe import SwaMoeConfig

    c = SwaMoeConfig()
    assert (c.n_periods, c.n_win, c.ring, c.kv_width) == (8, 3, 2048, 512)
    assert c.layer_types.count("full_attention") == 8
    assert c.stack_sizes() == {"dw": 2, "ew0": 1, "ew": 21, "ef": 8}
    assert [c.place(i) for i in (0, 1, 2, 3, 4, 6, 7, 31)] == [
        ("dw", 0), ("dw", 1), ("ew0", 0), ("ef", 0), ("ew", 0), ("ew", 2),
        ("ef", 1), ("ef", 7)]
    assert c.row_bytes(2) == {"full": 16384, "window": 49152}
    assert c.embedding_multiplier == 2048 ** 0.5
    with pytest.raises(ValueError, match="experts_held"):
        SwaMoeConfig(experts_held=(120, 130))
    with pytest.raises(ValueError, match="whole periods"):
        SwaMoeConfig(num_hidden_layers=30)
    with pytest.raises(ValueError, match="whole periods"):
        SwaMoeConfig(layer_types=("full_attention",) * 32)
    with pytest.raises(ValueError, match="dense"):
        SwaMoeConfig(num_dense_layers=3)
    a = SwaMoeConfig(layer_types=list(c.layer_types))
    assert a == c and hash(a) == hash(c)
    assert dataclasses.replace(a, experts_held=(16, 32)).n_held == 16
