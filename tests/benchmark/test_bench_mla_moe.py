"""The ``mla_moe`` family through the benchmark: its cell loads by name,
the serve driver runs it at a tiny size on the CPU (the loader, driver,
adapter, reference and ``run.measure`` a chip run uses; only the sizes
differ), the check catches the broken paths a latent-attention,
routed-expert block can have, and the shape functions are pinned against
hand counts.

Test-size readings (float32 program, seed 2147483900): both gaps 0 for
the sound program.
"""

import copy
import dataclasses

import numpy as np
import pytest
from bench_util import TINY_ROUNDS, measure

from benchmark.harness import loader

CELL = "dotsvlm1-serve-reason"
LIMITS = {"served_logit_gap_mean": 1e-5, "served_logit_gap_max": 1e-4,
          "malformed_results": 0, "unchecked": 0}
# the tiny preset: in the tests only.  Every ratio kept: a dense layer
# then expert layers, a rope part beside the nope part, 64 router outputs
# in 8 groups of which 4 stay, top-8; this share holds 16 of the 64 (a
# quarter, so that a test-size window shows what a broken router does)
TINY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=16,
            share=dict(n_routed_experts_published=64, experts_held=[0, 16]))
TINY_ENGINE = dict(block_size=8, num_blocks=96, max_slots=8,
                   prefill_token_budget=16, dtype="float32", max_len=128)


def tiny_cell():
    cell = copy.deepcopy(loader.load_cell(CELL))
    cfg = dict(cell["config"], **TINY)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=64)
    cfg["engine"] = dict(cfg["engine"], **TINY_ENGINE)
    mix = dict(cell["traffic"], preroll_s=0.5, rounds=TINY_ROUNDS,
               prompt_len={"dist": "uniform", "min": 8, "max": 60},
               reply_len={"dist": "uniform", "min": 4, "max": 20},
               arrivals={"clients": 4, "stagger_s": 0.3})
    cell["cell"]["trace_window"] = {"length_s": 0.5}
    cell["config"], cell["traffic"] = cfg, mix
    return cell


def test_the_cell_loads_by_name_with_its_metrics():
    cell = loader.load_cell(CELL)
    cfg, e = cell["config"], cell["config"]["engine"]
    assert cell["chips"] == 1 and cfg["family"] == "mla_moe"
    # published widths, untouched
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"]) == (7168, 128, 1536, 512, 128, 64, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"],
            cfg["routed_scaling_factor"], cfg["n_shared_experts"]) == (
                18432, 2048, 8, 8, 4, 2.5, 1)
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert cfg["max_position_embeddings"] == 163840
    # the cut, and the deployment stated beside it
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"], e["max_len"]) == (
                6, 1, 16, 16160, 0, 2048)
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers", "engine.max_len"}
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    sh = cfg["share"]
    assert (sh["chips_per_layer"], sh["n_routed_experts_published"],
            sh["experts_held"], sh["vocab_size_published"]) == (
                16, 256, [0, 16], 129280)
    assert "sixteen chips" in cfg["deployment"] and cfg["assumed"]
    assert e["max_slots"] == 128 and set(e["why"]) == set(e) - {"why"}
    assert all(e["why"].values())
    man = next(c for c in loader.manifest()["configs"]
               if c["name"] == "dots-vlm1-inst")
    assert man["reduced"] == cfg["reduced"] and man["source"] == \
        cfg["source"] and len(man["source"]) <= 200
    # the traffic, exactly as the issue names it
    mix = cell["traffic"]
    assert mix["arrivals"] == {"clients": 128, "stagger_s": 12.0}
    assert (mix["prompt_len"], mix["reply_len"]) == (
        {"dist": "uniform", "min": 256, "max": 1024},
        {"dist": "uniform", "min": 256, "max": 768})
    assert (mix["requests_per_client"], mix["rounds"], mix["preroll_s"],
            mix["schedule_seed"]) == (4, 4, 14, 1)
    assert cell["cell"]["check"]["requests"] == 12
    assert cell["cell"]["trace_window"]["length_s"] == 3.0
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "token_gap_p95_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"mla_moe_decode_step_roofline", "mla_attn_roofline",
            "moe_experts_roofline", "moe_expert_share",
            "expert_hit_share", "decode_step_device_ms",
            "prefill_chunk_device_ms", "batch_occupancy",
            "kv_blocks_used_peak", "prefill_budget_use",
            "hbm_peak_share.serve", "compiles_in_window.serve",
            "idle_ms_per_step.other"} <= names
    assert not {"decode_pool_copy_share", "decode_step_roofline",
                "state_slots_used_peak"} & names
    # every reader a metric of the cell names is a file
    for m in cell["per_layer"]:
        loader.load_module("readers", m["file"]["reader"])
    c = loader.load_module("adapters", "mla_moe").program_config(cfg)
    assert (c.n_layer, c.n_dense, c.n_moe, c.max_len, c.dtype) == (
        6, 1, 5, 2048, "bfloat16")
    assert (c.n_routed_experts, c.experts_held, c.n_held) == (256, (0, 16),
                                                              16)
    assert c.pool_row_width == 640
    # what test_bench_loader.py holds every cell to, but for the family
    assert cell["traffic"]["kind"] == "serve"
    assert hasattr(loader.load_module("drivers", "serve"), "Session")
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert all(m["moves"] in e2e for m in cell["per_layer"])
    assert set(cell["cell"]) == {"trace_window", "check", "limits",
                                 "limits_from"}
    assert set(cell["cell"]["limits"]) == set(cell["cell"]["limits_from"])
    assert all(v is not None for v in cell["cell"]["limits"].values())
    lim = cell["cell"]["limits"]
    assert lim["malformed_results"] == 0 and lim["unchecked"] == 0
    assert 0 < lim["served_logit_gap_mean"] < lim["served_logit_gap_max"]


def test_the_chains_outlast_the_run():
    """128 callers x 16 requests: even at the floor of a decode step (11
    GB of weights at 819 GB/s = 13.4 ms a token, no prefill, no wait)
    every caller's chain lasts beyond pre-roll + window + drain."""
    from benchmark.harness import traffic

    mix = loader.load_cell(CELL)["traffic"]
    reqs = traffic.make_requests(mix, 1, (14.0, 40.0, 20.0), 16160, 2048)
    assert len(reqs) == 128 * 16
    traffic.check_fits(reqs, 2048)
    shortest = min(sum(r.max_new for r in reqs if r.client == c) * 0.0134
                   for c in range(128))
    assert shortest > mix["preroll_s"] + 40 + 20, shortest


def test_the_serve_driver_runs_the_family_and_comes_out_correct():
    line = measure(tiny_cell(), LIMITS)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s",
                                    "token_gap_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())


# ---- broken timed paths: each hands the engine a family or weights that
# ---- leave one piece of the mathematics out


def _zero(eng, kind, name):
    stack = dict(eng._params[kind])
    stack[name] = stack[name] * 0
    eng._params = dict(eng._params, **{kind: stack})


def _refamily(eng, **changes):
    """The engine's family over a changed configuration (another hash:
    its programs compile anew)."""
    fam = eng._fam
    eng._fam = dataclasses.replace(
        fam, cfg=dataclasses.replace(fam.cfg, **changes))


def _tamper_no_rope_score(eng):
    """The rope half of the score left out: rot(q_r) reads zero."""
    for kind in ("dense", "moe"):
        _zero(eng, kind, "w_qr")


def _tamper_no_shared_expert(eng):
    _zero(eng, "moe", "s_down")


def _tamper_no_group_limit(eng):
    """Every group stays eligible: top-8 of all 64."""
    _refamily(eng, topk_group=eng._fam.cfg.n_group)


def _tamper_no_mscale(eng):
    """The softmax scale without m^2 (mscale_all_dim read as 0)."""
    rs = dict(eng._fam.cfg.rope_scaling, mscale_all_dim=0)
    _refamily(eng, rope_scaling=rs)
    assert abs(eng._fam.cfg.softmax_scale - 24 ** -0.5) < 1e-12


def _patched_route(change):
    """``models.mla_moe.route`` with its result changed; the family is
    re-made so that no program compiled with the sound router is found
    again."""
    def tamper(eng, monkeypatch):
        from singa_tpu.models import mla_moe
        from singa_tpu.ops import expert_layer

        def route(x, w_router, bias, **kw):
            return change(expert_layer.route, x, w_router, bias, kw,
                          eng._fam.cfg)

        monkeypatch.setattr(mla_moe, "route", route)
        _refamily(eng, max_position_embeddings=163841)
    return tamper


def _bias_into_weights(sound, x, w_router, bias, kw, cfg):
    """The weights from the biased scores: (s + b) / sum(s + b)."""
    import jax
    import jax.numpy as jnp

    idx, _ = sound(x, w_router, bias, **kw)
    sc = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), w_router,
                                precision=jax.lax.Precision.HIGHEST))
    chosen = jnp.take_along_axis(sc + bias, idx, axis=1)
    return idx, chosen / jnp.sum(chosen, 1, keepdims=True) * kw["scale"]


def _normalised_over_held(sound, x, w_router, bias, kw, cfg):
    import jax.numpy as jnp

    idx, w = sound(x, w_router, bias, **kw)
    lo, hi = cfg.experts_held
    held = (idx >= lo) & (idx < hi)
    mine = jnp.sum(jnp.where(held, w, 0.0), 1, keepdims=True)
    return idx, jnp.where(mine > 0, w / jnp.maximum(mine, 1e-20)
                          * kw["scale"], w)


PARAM_TAMPERS = {"rope-score-left-out": _tamper_no_rope_score,
                 "shared-expert-left-out": _tamper_no_shared_expert,
                 "group-limit-left-out": _tamper_no_group_limit,
                 "mscale-squared-left-out": _tamper_no_mscale}
ROUTE_TAMPERS = {"bias-in-the-weights": _bias_into_weights,
                 "normalised-over-held-only": _normalised_over_held}


@pytest.mark.parametrize("name", list(PARAM_TAMPERS) + list(ROUTE_TAMPERS))
def test_a_broken_path_is_not_correct(name, monkeypatch):
    if name in PARAM_TAMPERS:
        tamper = PARAM_TAMPERS[name]
    else:
        patched = _patched_route(ROUTE_TAMPERS[name])
        tamper = lambda eng: patched(eng, monkeypatch)
    line = measure(tiny_cell(), LIMITS, tamper=tamper)
    assert line["correct"] is False
    assert line["failed"] == 0


MID = dict(TINY, vocab_size=4096, hidden_size=256, num_hidden_layers=4,
           num_attention_heads=8, q_lora_rank=96, kv_lora_rank=64,
           qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
           intermediate_size=512, moe_intermediate_size=128)


def test_the_control_one_precision_down_fails_the_limit():
    """The control: the reference with fp8 operands in the program's
    place (at each served position the token fp8 puts first takes the
    served token's place), at a middle size.  The float32 reference's
    own greedy tokens read 0."""
    ref = loader.load_module("references", "mla_moe")
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
    lg = np.asarray(ref.logits(w, ref.hidden_states(
        w, ref._padded(toks[:20], sizes))))
    own = np.concatenate([toks[:20], [lg[19].argmax()]])
    assert ref.served_token_gap(w, sizes, own, 20)[0] == 0.0


def test_shape_functions_against_hand_counts():
    ref = loader.load_module("references", "mla_moe")
    work = loader.load_module("work", "mla_moe")
    s = ref.sizes_of(loader.load_cell(CELL)["config"])
    # attention: q_a 7168x1536 = 11.0 M, q_b 1536 x 128x192 = 37.7 M,
    # kv_a 7168x576 = 4.1 M, kv_b 512 x 128x256 = 16.8 M, o 16384x7168 =
    # 117.4 M
    assert work.attn_params(s) == (11_010_048 + 37_748_736 + 4_128_768
                                   + 16_777_216 + 117_440_512)
    assert round(work.attn_params(s) / 1e6, 1) == 187.1
    assert work.dense_ffn_params(s) == 3 * 7168 * 18432 == 396_361_728
    assert work.expert_params(s) == 3 * 7168 * 2048 == 44_040_192
    assert work.router_params(s) == 7168 * 256 + 256
    # an expert layer outside its routed experts: the driver's "about 233M"
    assert round(work.moe_layer_fixed_params(s) / 1e6, 1) == 233.0
    # the latent cache: (512 + 64) x 2 B = 1,152 B a position a layer
    assert work.row_bytes(s) == 1152
    assert work.kv_bytes_per_position(s) == 6 * 1152 == 6912
    # everything the chip holds: 5.50 B parameters, 11.0 GB
    assert abs(work.held_weight_bytes(s) - 11.0e9) < 0.05e9
    assert work.expert_bytes(s) == 88_080_384
    # a decode step at 128 lanes of 900 positions with every held expert
    # hit: fixed weights 3.75 GB (attention 6 x 374 MB, dense ffn 793 MB,
    # five shared experts 440 MB, head 232 MB), experts 80 x 88 MB = 7.05
    # GB, rows 115,200 x 6,912 B = 0.80 GB
    assert abs(work.fixed_weight_bytes(s, 128) - 3.75e9) < 0.01e9
    b = work.decode_step_bytes(s, 128, 128 * 900, 80)
    assert abs(b - (3.75e9 + 7.046e9 + 0.796e9)) < 0.02e9
    # fewer experts hit, fewer bytes: the roofline cannot pass 100% for a
    # program that skips them
    assert work.decode_step_bytes(s, 128, 0, 40) == \
        work.fixed_weight_bytes(s, 128) + 40 * 88_080_384
    # absorbed attention: 128 heads x (576 + 512) x 2 = 278,528 FLOP a
    # position a layer a lane
    assert work.absorbed_attn_flops_per_position(s) == 278_528
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, by = work.attn_bound_seconds(s, 115_200, peaks)
    # ... which at the chip's peaks is as long as reading the rows: 0.977
    # ms of operations against 0.972 ms of bytes
    assert by == "flops" and abs(least - 6 * 278_528 * 115_200 / 197e12) \
        < 1e-9
    assert abs(least - 0.977e-3) < 0.001e-3
    assert abs(115_200 * 6912 / 819e9 - 0.972e-3) < 0.001e-3
    # the held experts: read-bound at 4 tokens an expert (80 x 88 MB =
    # 8.6 ms against 320 x 88 MFLOP = 0.14 ms)
    least, by = work.experts_bound_seconds(s, 80, 320, peaks)
    assert by == "bytes" and abs(least - 8.60e-3) < 0.01e-3
    least, by = work.experts_bound_seconds(s, 80, 80 * 512, peaks)
    assert by == "flops"


def test_the_readers_return_nothing_without_a_trace_or_the_counts():
    reader = loader.load_module("readers", "mla_moe")
    assert reader.read({"trace": None}, "hit_share") is None

    class NoDevices:
        devices = {}

    assert reader.read({"trace": NoDevices()}, "decode_roofline") is None
