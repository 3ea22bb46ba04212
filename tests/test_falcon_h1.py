"""Falcon-H1 at a tiny size on the CPU, float32: the program (model,
served family, the engine's paged path with its per-slot state arenas)
against the plain reference ``benchmark/references/falcon_h1.py`` on the
reference's own seeded weights.  Logits are compared, not sampled tokens.

The tiny preset lives here only: 2 layers, hidden 64, d_ssm 64, d_state
16, 2 groups, 4 query / 2 K/V heads, vocabulary 512; blocks (= scan
chunks) of 8 positions.
"""

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
    family="falcon_h1", vocab_size=512, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, mamba_d_ssm=64, mamba_d_state=16,
    mamba_n_groups=2, mamba_n_heads=4, mamba_d_head=16, mamba_d_conv=4,
    mamba_chunk_size=8, rms_norm_eps=1e-5, rope_theta=1e4,
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    max_position_embeddings=262144,
    engine=dict(max_len=128, dtype="float32", block_size=8))
TOL = 2e-4      # float32 against float32, other orders of summation


@pytest.fixture(scope="module")
def ref():
    return loader.load_module("references", "falcon_h1")


@pytest.fixture(scope="module")
def built(ref):
    """(model, reference weights, sizes) on the reference's seed-7
    weights."""
    ad = loader.load_module("adapters", "falcon_h1")
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


def test_full_forward_matches_the_reference(ref, built):
    m, w, _ = built
    toks = _prompt(37)
    got = np.asarray(m.forward(tensor.from_numpy(
        toks[None], device.get_default_device())).data)[0]
    want = _ref_logits(ref, w, toks)
    assert np.abs(want).max() > 1.0          # logits of a real scale
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("plen", [24, 21, 5])
def test_chunk_rows_carry_the_state_and_match_the_reference(ref, built,
                                                            plen):
    """The family's chunk row as the engine drives it: a fresh zero row
    and zero state, then block-width windows; every prompt position's
    logits against the reference's full forward -- across chunk-row
    boundaries, and for a prompt that is not a multiple of the block."""
    m, w, _ = built
    fam, cfg = m.served_family(), m.cfg
    params = fam.extract_params(m, dtype=jnp.float32)
    toks = _prompt(plen, seed=plen)
    ids = np.zeros((1, cfg.max_len), np.int32)
    ids[0, :plen] = toks
    n_l, n_kv, d = fam.kv_geometry(cfg)
    row = jnp.zeros((n_l, 1, n_kv, cfg.max_len, d), jnp.float32)
    kc, vc = row, row
    state = {k: jnp.zeros((n_l,) + tuple(s), dt)
             for k, (s, dt) in fam.state_spec(cfg).items()}
    got = []
    for off in range(0, plen, 8):
        hidden, kc, vc, state = fam.chunk_row(
            params, jnp.asarray(ids), kc, vc, state, jnp.int32(off),
            jnp.int32(min(8, plen - off)), chunk=8)
        got.append(np.asarray(fam.logits(params, hidden))[0])
    got = np.concatenate(got)[:plen]
    np.testing.assert_allclose(got, _ref_logits(ref, w, toks), atol=TOL)


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
    tokens, the private K/V rows and the carried scan and conv state of
    every admission against the engine that launches a block at a time
    -- the scan walks a wide row a block at a time, so its arithmetic
    is that engine's."""
    lw.assert_same_as_one_block(launch_runs.run(ratio, case),
                                launch_runs.run(1, case), case, ratio,
                                atol=TOL)


def test_one_block_lowers_to_the_program_it_was(launch_runs):
    assert lw.chunk_row_lowering(launch_runs.engine(1)) == \
        lw.PARENT_LOWERING["falcon_h1"]


def test_prefill_then_decode_through_the_engines_cache(ref, built):
    """Through the engine: budgeted chunked prefill of two prompts, then
    decode steps.  Before every step the family's decode math is run on
    the engine's own pool, block tables and state arenas (undonated, so
    nothing moves) and each live lane's logits are held to the
    reference's full forward over that lane's sequence so far."""
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
            slots = np.where(live, np.arange(eng.max_slots), eng.max_slots)
            pos = jnp.asarray(eng._pos)
            n_blk = jnp.max((jnp.where(live, pos, 0) + 7) // 8)
            logits, *_ = fam.decode_step(
                eng._params, arena.pool_k, arena.pool_v, eng._state,
                jnp.asarray(slots.astype(np.int32)), eng._block_tables(),
                jnp.asarray(eng._toks), pos, jnp.asarray(live), n_blk,
                block=8, trash=arena.trash)
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


def test_chunked_scan_equals_the_plain_recurrence():
    """``ssd_chunk`` over chunks of 8 against one-token-at-a-time
    recurrence in float64, across chunk boundaries and with a last chunk
    padded by tokens that must leave the state alone."""
    from singa_tpu.models.falcon_h1 import ssd_chunk

    rng = np.random.default_rng(3)
    n, h, p, ns, g = 21, 4, 16, 16, 2
    x = rng.normal(size=(n, h, p))
    b = rng.normal(size=(n, g, ns))
    c = rng.normal(size=(n, g, ns))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(n, h)))
    a = -rng.uniform(1, 16, size=h)
    state = rng.normal(size=(h, p, ns))      # a state carried in
    s64, want = state.copy(), []
    for t in range(n):
        bh, ch = np.repeat(b[t], h // g, 0), np.repeat(c[t], h // g, 0)
        s64 = np.exp(dt[t] * a)[:, None, None] * s64 \
            + (dt[t][:, None] * x[t])[:, :, None] * bh[:, None, :]
        want.append(np.einsum("hpn,hn->hp", s64, ch))
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    s, got = f32(state), []
    for off in range(0, n, 8):
        k = min(8, n - off)
        pad = lambda v: np.concatenate(
            [v[off:off + k], np.ones((8 - k,) + v.shape[1:])])
        d = pad(dt)
        d[k:] = 0.0                     # padding: the state stays
        y, s = ssd_chunk(f32(pad(x)), f32(pad(b)), f32(pad(c)), f32(d),
                         f32(a), s)
        got.append(np.asarray(y)[:k])
    np.testing.assert_allclose(np.concatenate(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), s64, rtol=2e-4, atol=2e-4)


def test_a_slot_reused_after_retirement_starts_from_zero_state(ref, built):
    m, w, sizes = built
    a, b = _prompt(19, 4), _prompt(13, 5)
    eng = _engine(m, max_slots=1)
    _serve(eng, [a], 6)
    assert float(jnp.abs(eng._state["ssm"][:, 0]).max()) > 0   # a's state
    second = _serve(eng, [b], 6)[0]
    resets = eng._c_state_resets.value
    eng.close()
    fresh = _engine(m, max_slots=1)
    alone = _serve(fresh, [b], 6)[0]
    fresh.close()
    assert resets == 2
    assert second.tolist() == alone.tolist()
    assert ref.served_token_gap(w, sizes, second, len(b))[0] == 0.0


def test_preempt_then_resume_continues_byte_exactly(built):
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
            eng._preempt_slot(idx[0], reason="test")
            # the next occupant must not inherit it, nor the resume need it
            eng._state = jax.tree.map(
                lambda a_: a_.at[:, idx[0]].set(7.0), eng._state)
            done = True
    assert done
    assert eng._c_state_snapshots.value == 1
    assert eng._c_state_restores.value == 1
    got = [np.asarray(h.result().tokens) for h in hs]
    for g, w_ in zip(got, want):
        assert g.tolist() == w_.tolist()
    eng.close()


def test_state_metrics_and_the_step_spans_argument(built):
    from singa_tpu.observe.registry import registry

    m, _, _ = built
    eng = _engine(m)
    g = registry().gauge("serve.state.bytes",
                         engine=eng.stats.engine_label)
    n_l = m.cfg.n_layer
    per_slot = 4 * (4 * 16 * 16 + 3 * m.cfg.conv_dim)
    assert g.value == n_l * (eng.max_slots + 1) * per_slot
    _serve(eng, [_prompt(10)], 3)
    eng.close()


# ----------------------------------------------------------------- the seam


def test_engine_imports_no_gpt2_name_on_the_paged_path():
    """``engine.py`` imports no name from ``models/gpt2_decode.py``, and
    the programs of the paged path reach the model through the family
    alone: no function that a paged engine's executor dispatches names
    the ``_gpt2`` module (the slot arena, whole-prompt prefill,
    speculation and the sharded executors' per-row twins still do)."""
    import ast

    src = {name: open(os.path.join(ROOT, "singa_tpu", *name.split("/")))
           .read()
           for name in ("serve/engine.py", "serve/paged.py",
                        "ops/sampling.py")}
    tree = ast.parse(src["serve/engine.py"])
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.endswith("gpt2_decode"):
            pytest.fail(f"engine.py imports {[a.name for a in node.names]} "
                        f"from {node.module}")
    paged_path = {"serve/engine.py": {"_chunk_row", "_first_from_hidden",
                                      "_write_state", "_read_state"},
                  "serve/paged.py": {"_paged_decode_kernel", "_aot_call"},
                  "ops/sampling.py": {"select_sample"}}
    for name, fns in paged_path.items():
        found = set()
        for node in ast.walk(ast.parse(src[name])):
            if isinstance(node, ast.FunctionDef) and node.name in fns:
                found.add(node.name)
                used = {n.id for n in ast.walk(node)
                        if isinstance(n, ast.Name)}
                used |= {n.module or "" for n in ast.walk(node)
                         if isinstance(n, ast.ImportFrom)}
                assert not any("gpt2" in u for u in used), (node.name, used)
        assert found == fns


def test_the_new_family_serves_with_gpt2s_math_out_of_reach(built,
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
def test_what_the_new_family_lacks_is_refused_by_name(built, feature, kw):
    m, _, _ = built
    base = dict(paged=PagedConfig(block_size=8, prefill_token_budget=8),
                max_slots=2)
    with pytest.raises(NotImplementedError) as e:
        m.serve(**dict(base, **kw))
    assert feature in str(e.value) and "falcon_h1" in str(e.value)


def test_fork_and_kv_ship_are_refused_by_name(built):
    m, _, _ = built
    eng = _engine(m)
    with pytest.raises(NotImplementedError, match="fork"):
        eng.submit(GenerationRequest(_prompt(9), max_new_tokens=2, n=2))
    with pytest.raises(NotImplementedError, match="fork"):
        eng.fork("no such request")
    with pytest.raises(NotImplementedError, match="KV image ship"):
        eng.start_prefix_build(_prompt(9))
    eng.close()


def test_gpt2_is_behind_the_same_contract():
    """GPT-2's family has no state of its own and every feature."""
    from singa_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from singa_tpu.models.served import FEATURES, ServedFamily

    fam = GPT2LMHead(GPT2Config.tiny()).served_family()
    assert isinstance(fam, ServedFamily)
    assert fam.features == FEATURES and fam.state_spec(None) == {}
    assert fam is GPT2LMHead(GPT2Config.tiny()).served_family()
