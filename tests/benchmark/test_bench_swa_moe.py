"""The ``swa_moe`` family through the benchmark: its cell loads by name,
the serve driver runs it at a tiny size on the CPU (the loader, driver,
adapter, reference and ``run.measure`` a chip run uses; only the sizes
differ), the check catches the broken paths a cache of two kinds and a
gated attention can have, the mix's chains outlast the window, and the
shape functions are pinned against hand counts.

Test-size readings (float32 program, seed 2147483900): both gaps 0 for
the sound program.
"""

import copy
import dataclasses

import numpy as np
import pytest
from bench_util import TINY_ROUNDS, measure

from benchmark.harness import loader, traffic

CELL = "trinitymini-serve-mixedlen"
LIMITS = {"served_logit_gap_mean": 1e-5, "served_logit_gap_max": 1e-4,
          "malformed_results": 0, "unchecked": 0}
#: all chains' tokens over the 54 s from traffic's start to the window's
#: end, as ``test_bench_traffic.CHAIN_FLOOR`` holds the other three mixes
#: (it gives a mix it does not list a floor of 0, and may not be edited)
CHAIN_FLOOR = 25_000
# the tiny preset: in the tests only.  Every ratio kept: two periods of
# three window layers and a full one, the two leading layers dense, 32
# router outputs, top-8; this share holds 8 of the 32 (a quarter, so that
# a test-size window shows what a broken layer does); a window of 16 in
# blocks of 8, so most test-size requests turn their rings
PERIOD = 4
TINY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=8,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            intermediate_size=96, moe_intermediate_size=32, num_experts=8,
            sliding_window=16,
            layer_types=["full_attention" if (i + 1) % PERIOD == 0
                         else "sliding_attention" for i in range(8)],
            share=dict(num_experts_published=32, experts_held=[0, 8]))
TINY_ENGINE = dict(block_size=8, num_blocks=96, max_slots=8,
                   prefill_token_budget=16, dtype="float32", max_len=128)


def tiny_cell():
    cell = copy.deepcopy(loader.load_cell(CELL))
    cfg = dict(cell["config"], **TINY)
    cfg["engine"] = dict(cfg["engine"], **TINY_ENGINE)
    mix = dict(cell["traffic"], preroll_s=0.5, rounds=TINY_ROUNDS,
               prompt_len={"dist": "uniform", "min": 8, "max": 60},
               reply_len={"dist": "uniform", "min": 4, "max": 20},
               arrivals={"clients": 4, "stagger_s": 0.3})
    cell["cell"]["trace_window"] = {"length_s": 0.5}
    cell["config"], cell["traffic"] = cfg, mix
    return cell


def test_the_cell_loads_with_its_files_and_its_family():
    cell = loader.load_cell(CELL)
    cfg, e = cell["config"], cell["config"]["engine"]
    assert cell["chips"] == 1 and cfg["family"] == "swa_moe"
    # published widths, untouched
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["sliding_window"],
            cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_shared_experts"], cfg["route_scale"]) == (
                2048, 32, 4, 128, 6144, 1024, 8, 2048, 32, 2, 1, 2.826)
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 8
    assert (cfg["n_group"], cfg["topk_group"], cfg["route_norm"],
            cfg["mup_enabled"], cfg["rope_theta"], cfg["rope_scaling"],
            cfg["max_position_embeddings"]) == (1, 1, True, True, 10000,
                                                None, 131072)
    # the cut, and the deployment stated beside it
    assert (cfg["num_experts"], cfg["vocab_size"], e["max_len"]) == (
        16, 25024, 16384)
    assert set(cfg["reduced"]) == {"num_experts", "vocab_size",
                                   "engine.max_len"}
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    sh = cfg["share"]
    assert (sh["chips_per_layer"], sh["num_experts_published"],
            sh["experts_held"], sh["vocab_size_published"]) == (
                8, 128, [0, 16], 200192)
    assert "one of 8 chips that share each layer, all 32 layers here" \
        in cfg["deployment"]
    assert len(cfg["assumed"]) >= 8
    assert set(e["why"]) == set(e) - {"why"} and all(e["why"].values())
    man = next(c for c in loader.manifest()["configs"]
               if c["name"] == "trinity-mini")
    assert man["reduced"] == cfg["reduced"] and man["source"] == \
        cfg["source"] and len(man["source"]) <= 200
    # the traffic, exactly as the issue names it
    mix = cell["traffic"]
    assert mix["arrivals"] == {"clients": 32, "stagger_s": 12.0}
    assert (mix["prompt_len"], mix["reply_len"]) == (
        {"dist": "lognormal", "median": 2048, "sigma": 0.9, "min": 256,
         "max": 12288}, {"dist": "uniform", "min": 128, "max": 512})
    assert (mix["requests_per_client"], mix["preroll_s"],
            mix["schedule_seed"]) == (4, 14, 1)
    assert cell["cell"]["check"]["requests"] == 12
    assert cell["cell"]["trace_window"]["length_s"] == 1.0
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "token_gap_p95_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"swa_moe_decode_step_roofline", "window_attn_roofline",
            "full_attn_roofline", "window_attn_share",
            "window_kv_saved_share", "moe_experts_roofline",
            "moe_expert_share", "expert_hit_share",
            "state_slots_used_peak", "kv_blocks_used_peak",
            "decode_step_device_ms", "prefill_chunk_device_ms",
            "batch_occupancy", "prefill_budget_use",
            "hbm_peak_share.serve", "compiles_in_window.serve",
            "idle_ms_per_step.other"} <= names
    assert not {"decode_pool_copy_share", "decode_step_roofline",
                "mla_attn_roofline", "ssm_step_share"} & names
    # every reader a metric of the cell names is a file, and the family
    # names a reference, an adapter and its shape functions
    for m in cell["per_layer"]:
        loader.load_module("readers", m["file"]["reader"])
    for kind in ("references", "adapters", "work"):
        loader.load_module(kind, cfg["family"])
    c = loader.load_module("adapters", "swa_moe").program_config(cfg)
    assert (c.n_layer, c.n_periods, c.n_win, c.max_len, c.dtype) == (
        32, 8, 3, 16384, "bfloat16")
    assert (c.num_experts, c.experts_held, c.n_held, c.ring) == (
        128, (0, 16), 16, 2048)
    assert cell["traffic"]["kind"] == "serve"
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert all(m["moves"] in e2e for m in cell["per_layer"])
    assert set(cell["cell"]) == {"trace_window", "check", "limits",
                                 "limits_from"}
    assert set(cell["cell"]["limits"]) == set(cell["cell"]["limits_from"])
    assert all(v is not None for v in cell["cell"]["limits"].values())
    lim = cell["cell"]["limits"]
    assert lim["malformed_results"] == 0 and lim["unchecked"] == 0
    assert 0 < lim["served_logit_gap_mean"] < lim["served_logit_gap_max"]
    # seven cells, still one of them on four chips
    cells = loader.manifest()["workloads"]
    assert len(cells) == 7 and sum(w["chips"] == 4 for w in cells) == 1


def test_the_mix_is_the_stated_one_and_its_chains_outlast_the_window():
    """Half the prompts are longer than the window and most prompt
    tokens lie in them; all chains' tokens over the 54 s they have to
    last are over the floor; every request fits the served context."""
    mix = loader.load_cell(CELL)["traffic"]
    reqs = traffic.make_requests(mix, 1, (14.0, 40.0, 20.0), 25024, 16384)
    assert len(reqs) == 32 * mix["requests_per_client"] * mix["rounds"]
    traffic.check_fits(reqs, 16384)
    plens = np.array([len(r.prompt) for r in reqs])
    assert plens.min() >= 256 and plens.max() <= 12288
    assert abs(np.median(plens) - 2048) < 40
    assert 2800 < plens.mean() < 3100
    assert 0.45 < (plens > 2048).mean() < 0.55
    assert 0.18 < (plens > 4096).mean() < 0.26
    assert plens[plens > 2048].sum() / plens.sum() > 0.75
    tokens = plens.sum() + sum(r.max_new for r in reqs)
    lasts = mix["preroll_s"] + loader.manifest()["run_seconds"]
    assert mix["arrivals"]["stagger_s"] <= mix["preroll_s"]
    assert tokens / lasts >= CHAIN_FLOOR, (tokens, lasts)
    # every caller's chain, not just their sum: the shortest chain's
    # tokens at a 32nd of the floor
    per_caller = [sum(len(r.prompt) + r.max_new for r in reqs
                      if r.client == c) for c in range(32)]
    assert min(per_caller) / lasts >= 0.5 * CHAIN_FLOOR / 32, per_caller


def test_the_serve_driver_runs_the_family_and_comes_out_correct():
    line = measure(tiny_cell(), LIMITS)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s",
                                    "token_gap_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())


# ---- broken timed paths: each hands the engine a family, weights or a
# ---- state path that leaves one piece of the mathematics out


def _refamily(eng):
    """The engine's family under another hash, so that no program traced
    with the sound functions is found again."""
    fam = eng._fam
    eng._fam = dataclasses.replace(fam, cfg=dataclasses.replace(
        fam.cfg, max_position_embeddings=131073))
    eng._x._aot_memo.clear()


def _tamper_no_band(eng, monkeypatch):
    """The band mask left off a decode step's window layers: a lane also
    sees the row its ring is about to lose."""
    from singa_tpu.models import swa_moe

    sound = swa_moe.ring_decode_attn

    def no_band(q, k, v, a_k, a_v, at, slots, pos, scale, window):
        return sound(q, k, v, a_k, a_v, at, slots, pos, scale, 10 ** 6)

    monkeypatch.setattr(swa_moe, "ring_decode_attn", no_band)
    _refamily(eng)


def _tamper_no_carry(eng, monkeypatch):
    """The rings not carried across chunk rows: every launch starts from
    empty rings, so a prompt's later launches lose the window below
    them."""
    import jax

    x = eng._x

    class NoCarry:
        def __getattr__(self, name):
            return getattr(x, name)

        def chunk_row(self, params, ids, kc, vc, off, state=None,
                      n_valid=None):
            return x.chunk_row(params, ids, kc, vc, off,
                               state=jax.tree.map(lambda a: a * 0, state),
                               n_valid=n_valid)

    eng._x = NoCarry()


def _stale_rings(eng):
    """The rings NOT reset at admission: a request starts from what the
    slot's rings last held.  No broken path here: a row of a ring is
    seen only by the position arithmetic of the sequence that wrote it
    (``ring_decode_attn``'s ``held``, the chunk rows' walk below
    ``off``), so what an earlier occupant left is never read."""
    import jax.numpy as jnp

    from singa_tpu.serve.engine import _read_state

    start = eng._start_prefilling

    def start_prefilling(idx, req, now):
        out = start(idx, req, now)
        if out is not None:
            eng._prefilling[out].state = _read_state(eng._state,
                                                     jnp.int32(out))
        return out

    eng._start_prefilling = start_prefilling


def _tamper_rotary_everywhere(eng, monkeypatch):
    """Rotary positions on the full layers too."""
    from singa_tpu.models import swa_moe

    sound = swa_moe._qkvg
    monkeypatch.setattr(
        swa_moe, "_qkvg",
        lambda a, p, c, pos, kind: sound(a, p, c, pos, "window"))
    _refamily(eng)


def _tamper_no_gate(eng, monkeypatch):
    """The attention's gate left out: with W_g at zero every value is
    halved alike, which the norm after the attention takes out again."""
    params = dict(eng._params)
    for stack in ("dw", "ew0", "ew", "ef"):
        params[stack] = dict(params[stack], wg=params[stack]["wg"] * 0)
    eng._params = params


def _tamper_no_post_norm(eng, monkeypatch):
    """The norm after the feed-forward read as the identity's weights."""
    params = dict(eng._params)
    for stack in ("dw", "ew0", "ew", "ef"):
        ln = params[stack]["ln_post_mlp"]
        params[stack] = dict(params[stack], ln_post_mlp=ln * 0 + 1)
    eng._params = params


TAMPERS = {"band-mask-left-off": _tamper_no_band,
           "rings-not-carried": _tamper_no_carry,
           "rotary-on-full-layers": _tamper_rotary_everywhere,
           "gate-left-out": _tamper_no_gate,
           "post-norm-weights-left-out": _tamper_no_post_norm}


@pytest.mark.parametrize("name", list(TAMPERS))
def test_a_broken_path_is_not_correct(name, monkeypatch):
    line = measure(tiny_cell(), LIMITS,
                   tamper=lambda eng: TAMPERS[name](eng, monkeypatch))
    assert line["correct"] is False
    assert line["failed"] == 0


def test_rings_left_as_the_last_occupant_left_them_change_nothing():
    """The engine zeroes a request's rings at admission; the check does
    not need it to."""
    line = measure(tiny_cell(), LIMITS, tamper=_stale_rings)
    assert line["correct"] is True and line["failed"] == 0


MID = dict(TINY, vocab_size=4096, hidden_size=256, num_attention_heads=8,
           num_key_value_heads=2, head_dim=32, intermediate_size=768,
           moe_intermediate_size=128)


def test_the_control_one_precision_down_fails_the_limit():
    """The control: the reference with fp8 operands in the program's
    place (at each served position the token fp8 puts first takes the
    served token's place), at a middle size.  The float32 reference's
    own greedy tokens read 0."""
    ref = loader.load_module("references", "swa_moe")
    cfg = dict(loader.load_cell(CELL)["config"], **MID)
    cfg["engine"] = dict(cfg["engine"], max_len=128)
    sizes = ref.sizes_of(cfg)
    means = []
    for seed in (2147483900, 5):
        w = ref.init_weights(sizes, seed)
        toks = np.random.default_rng(seed).integers(0, 4096, 120)
        worst, total, scale = ref.served_token_gap(w, sizes, toks, 20,
                                                   "fp8")
        assert scale > 1.0
        assert worst > LIMITS["served_logit_gap_max"]
        means.append(total / 100)
    assert min(means) > 3 * LIMITS["served_logit_gap_mean"], means
    lg = np.asarray(ref.logits(w, ref.hidden_states(w, toks[:20])))
    own = np.concatenate([toks[:20], [lg[19].argmax()]])
    assert ref.served_token_gap(w, sizes, own, 20)[0] == 0.0


def test_shape_functions_against_hand_counts():
    ref = loader.load_module("references", "swa_moe")
    work = loader.load_module("work", "swa_moe")
    s = ref.sizes_of(loader.load_cell(CELL)["config"])
    assert (work.n_full(s), work.n_window(s)) == (8, 24)
    # attention: q, gate and o 3 x 2048x4096 = 25.2 M, k and v 2 x
    # 2048x512 = 2.1 M
    assert work.attn_params(s) == 3 * 8_388_608 + 2 * 1_048_576
    assert round(work.attn_params(s) / 1e6, 1) == 27.3
    assert work.dense_ffn_params(s) == 3 * 2048 * 6144 == 37_748_736
    assert work.expert_params(s) == 3 * 2048 * 1024 == 6_291_456
    assert work.expert_bytes(s) == 12_582_912
    assert work.router_params(s) == 2048 * 128 + 128
    # K and V of 4 heads of 128 in bf16: 2 KB a position a layer; 16 KB a
    # position over the full layers, 48 KB over the window layers
    assert work.row_bytes(s) == 2048
    assert work.full_rows_bytes(s, 1) == 16_384
    assert work.window_rows_bytes(s, 1) == 49_152
    # everything the chip holds: 4.27 B parameters, 8.55 GB
    assert abs(work.held_weight_bytes(s) - 8.55e9) < 0.02e9
    # what a step reads whoever is chosen: attention 32 x 54.5 MB, two
    # dense feed-forwards 151 MB, 30 shared experts 377 MB, the head 102
    # MB, routers and vectors 33 MB: 2.41 GB
    assert abs(work.fixed_weight_bytes(s, 24) - 2.41e9) < 0.01e9
    # a decode step of 24 lanes at 3,300 positions each with every held
    # expert hit: + 480 x 12.6 MB = 6.04 GB, full rows 79,200 x 16 KB =
    # 1.30 GB, window rows 24 x 2,048 x 48 KB = 2.42 GB
    b = work.decode_step_bytes(s, 24, 79_200, 24 * 2048, 480)
    assert abs(b - (2.41e9 + 6.04e9 + 1.30e9 + 2.42e9)) < 0.03e9
    # a lane counts the rows inside its window, not its ring's capacity:
    # fewer rows, fewer bytes, and no share over 100% for a program that
    # reads only those
    assert work.decode_step_bytes(s, 24, 0, 300, 0) == \
        work.fixed_weight_bytes(s, 24) + 300 * 49_152
    # attention: 32 heads x 128 x 4 = 16,384 FLOP a row a layer a lane,
    # against 2 KB to read: bound by bytes (2.5 us against 0.08 us)
    assert work.attn_flops_per_row(s) == 16_384
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, by = work.window_attn_bound_seconds(s, 24 * 2048, peaks)
    assert by == "bytes" and abs(least - 24 * 2048 * 49_152 / 819e9) < 1e-9
    assert abs(least - 2.95e-3) < 0.01e-3
    least, by = work.full_attn_bound_seconds(s, 79_200, peaks)
    assert by == "bytes" and abs(least - 1.584e-3) < 0.001e-3
    # the held experts: read-bound at 1.5 tokens an expert
    least, by = work.experts_bound_seconds(s, 480, 720, peaks)
    assert by == "bytes" and abs(least - 7.37e-3) < 0.01e-3


def test_the_readers_return_nothing_without_a_trace_or_the_counts():
    reader = loader.load_module("readers", "swa_moe")
    assert reader.read({"trace": None}, "kv_saved_share") is None

    class NoDevices:
        devices = {}

    assert reader.read({"trace": NoDevices()}, "decode_roofline") is None
    assert reader.read({"trace": NoDevices()}, "attn_roofline",
                       kind="window") is None
