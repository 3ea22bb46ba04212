"""The ``conv_moe`` family through the benchmark: its cell loads by name,
the serve driver runs it at a tiny size on the CPU (the loader, driver,
adapter, reference and ``run.measure`` a chip run uses; only the sizes
differ), the check catches the broken paths that layers with a carried
convolution tail, gated streams, per-head norms and a biased router can
have, the mix's chains outlast the window, and the shape functions are
pinned against hand counts.

Test-size readings (float32 program, seed 2147483900): both gaps 0 for
the sound program.
"""

import copy
import dataclasses

import numpy as np
import pytest
from bench_util import TINY_ROUNDS, measure

from benchmark.harness import loader, traffic

CELL = "lfm2moe-serve-manychat"
LIMITS = {"served_logit_gap_mean": 1e-5, "served_logit_gap_max": 1e-4,
          "malformed_results": 0, "unchecked": 0}
#: all chains' tokens over the 54 s from traffic's start to the window's
#: end (``test_bench_traffic.CHAIN_FLOOR`` gives a mix it does not list a
#: floor of 0, and may not be edited): 4.5 x the rate the cell reads
CHAIN_FLOOR = 60_000
# the tiny preset: in the tests only.  The configuration's own nine
# layers (a dense conv layer, two periods of attention and three conv
# layers), 16 router outputs, top-4, all held; three taps; heads of 16;
# matrices of a standard deviation that keeps std x sqrt(hidden) at the
# published model's 0.9 (at 0.02 a 64-wide layer adds a thousandth of the
# embedding, the tied head repeats the last token and no broken layer
# shows)
HEAD = 16
TINY = dict(vocab_size=512, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=96,
            moe_intermediate_size=32, num_experts=16,
            initializer_range=0.11)
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
    assert cell["chips"] == 1 and cfg["family"] == "conv_moe"
    # published widths, untouched
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["conv_L_cache"],
            cfg["vocab_size"], cfg["routed_scaling_factor"]) == (
                2048, 32, 8, 11776, 1536, 64, 4, 3, 65536, 1)
    assert (cfg["conv_bias"], cfg["norm_topk_prob"], cfg["use_expert_bias"],
            cfg["norm_eps"], cfg["max_position_embeddings"],
            cfg["model_type"]) == (False, True, True, 1e-5, 128000,
                                   "lfm2_moe")
    assert cfg["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    # the cut: layer 0 and the published layers 2-9, the dense layers once
    published = ["full_attention" if i >= 2 and (i - 2) % 4 == 0
                 else "conv" for i in range(40)]
    kept = cfg["published"]["layers_kept"]
    assert kept == [0, 2, 3, 4, 5, 6, 7, 8, 9]
    assert cfg["layer_types"] == [published[i] for i in kept]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            e["max_len"]) == (9, 1, 8192)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_dense_layers",
                                   "layer_types", "engine.max_len"}
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    assert "num_experts" not in cfg["reduced"] and "share" not in cfg
    assert "one pipeline stage of five" in cfg["deployment"]
    assert "every expert and the whole vocabulary" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 8
    assert set(e["why"]) == set(e) - {"why"} and all(e["why"].values())
    assert (e["max_slots"], e["block_size"], e["dtype"]) == (
        256, 128, "bfloat16")
    man = next(c for c in loader.manifest()["configs"]
               if c["name"] == "lfm2-24b-a2b")
    assert man["reduced"] == cfg["reduced"] and man["source"] == \
        cfg["source"] and len(man["source"]) <= 200
    # the traffic, exactly as the issue names it
    mix = cell["traffic"]
    assert mix["arrivals"] == {"clients": 256, "stagger_s": 12.0}
    assert (mix["prompt_len"], mix["reply_len"]) == (
        {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32,
         "max": 4096}, {"dist": "uniform", "min": 128, "max": 512})
    assert (mix["requests_per_client"], mix["preroll_s"],
            mix["schedule_seed"]) == (4, 14, 1)
    assert cell["cell"]["check"]["requests"] == 12
    assert cell["cell"]["trace_window"]["length_s"] == 2.0
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "token_gap_p95_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"conv_moe_decode_step_roofline", "short_conv_roofline",
            "short_conv_share", "expert_reread_share", "expert_load_peak",
            "moe_experts_roofline", "moe_expert_share", "expert_hit_share",
            "state_slots_used_peak", "kv_blocks_used_peak",
            "decode_step_device_ms", "prefill_chunk_device_ms",
            "batch_occupancy", "prefill_budget_use", "stall_s.host",
            "hbm_peak_share.serve", "compiles_in_window.serve",
            "step_host_work_ms_p50", "idle_ms_per_step.other"} <= names
    # prefill_launch_host_ms_p50 reads the EARLY pass (`serve.launch`), which
    # a step has only while a prompt longer than the budget (a quarter of
    # this mix's) is between its launches: a traced slice can hold none,
    # and a metric a cell lists has to be in every traced line
    assert not {"decode_pool_copy_share", "decode_step_roofline",
                "mla_attn_roofline", "ssm_step_share",
                "window_attn_share", "prefill_launch_host_ms_p50"} & names
    new = [m for m in loader.manifest()["per_layer"]
           if m["workloads"] == [CELL]]
    assert len(new) == 5 and all(
        m["moves"] == "token_gap_p95_ms" for m in new)
    # every reader a metric of the cell names is a file, and the family
    # names a reference, an adapter and its shape functions
    for m in cell["per_layer"]:
        loader.load_module("readers", m["file"]["reader"])
    for kind in ("references", "adapters", "work"):
        loader.load_module(kind, cfg["family"])
    c = loader.load_module("adapters", "conv_moe").program_config(cfg)
    assert (c.n_layer, c.n_full, c.n_conv, c.max_len, c.dtype) == (
        9, 2, 7, 8192, "bfloat16")
    assert (c.num_experts, c.experts_held, c.n_held, c.rope_theta) == (
        64, (0, 64), 64, 1e6)
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
    # eight cells, still one of them on four chips
    cells = loader.manifest()["workloads"]
    assert len(cells) == 8 and sum(w["chips"] == 4 for w in cells) == 1


def test_the_mix_is_the_stated_one_and_its_chains_outlast_the_window():
    """Short turns, short to medium answers, 256 callers: all chains'
    tokens over the 54 s they have to last are over the floor; every
    request fits the served context."""
    mix = loader.load_cell(CELL)["traffic"]
    reqs = traffic.make_requests(mix, 1, (14.0, 40.0, 20.0), 65536, 8192)
    assert len(reqs) == 256 * mix["requests_per_client"] * mix["rounds"]
    traffic.check_fits(reqs, 8192)
    plens = np.array([len(r.prompt) for r in reqs])
    news = np.array([r.max_new for r in reqs])
    assert plens.min() >= 32 and plens.max() <= 4096
    assert abs(np.median(plens) - 256) < 8
    assert 380 < plens.mean() < 440
    assert 0.05 < (plens > 1024).mean() < 0.11
    assert news.min() >= 128 and news.max() <= 512
    assert max(len(r.prompt) + r.max_new for r in reqs) <= 4608
    tokens = plens.sum() + news.sum()
    lasts = mix["preroll_s"] + loader.manifest()["run_seconds"]
    assert mix["arrivals"]["stagger_s"] <= mix["preroll_s"]
    assert tokens / lasts >= CHAIN_FLOOR, (tokens, lasts)
    # every caller's chain, not just their sum: the shortest chain's
    # tokens at a 256th of the floor
    per_caller = [0] * 256
    for r in reqs:
        per_caller[r.client] += len(r.prompt) + r.max_new
    assert min(per_caller) / lasts >= 0.5 * CHAIN_FLOOR / 256, \
        min(per_caller)
    # ids from the whole vocabulary
    assert max(int(r.prompt.max()) for r in reqs[:256]) > 65000


def test_the_serve_driver_runs_the_family_and_comes_out_correct():
    line = measure(tiny_cell(), LIMITS)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s",
                                    "token_gap_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())


# ---- broken timed paths: each hands the engine a family or a state path
# ---- that leaves one piece of the mathematics out


def _refamily(eng):
    """The engine's family under another hash, so that no program traced
    with the sound functions is found again -- and the launch widths'
    programs compiled anew, as the engine compiles them when it is
    built: the driver's warm-up reaches the one-block launch only, and
    on a busy machine a wider launch compiled inside a 1.5 s window
    left no token gap to read."""
    fam = eng._fam
    eng._fam = dataclasses.replace(fam, cfg=dataclasses.replace(
        fam.cfg, max_position_embeddings=128001))
    eng._x._aot_memo.clear()
    eng._compile_launch_widths()


def _tamper_stale_tails(eng, monkeypatch):
    """The tails NOT reset at admission: a request's first convolution
    outputs see what the slot's last occupant left."""
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


def _tamper_no_carry(eng, monkeypatch):
    """The tails not carried across chunk rows: every launch starts its
    convolutions from zeros, as if the prompt began there."""
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


def _tamper_no_c_gate(eng, monkeypatch):
    """The C gate left out: the convolution's result goes to the
    out-projection as it is."""
    from singa_tpu.models import conv_moe

    monkeypatch.setattr(
        conv_moe, "_conv_out",
        lambda conv, c_gate, p, dtype: conv.astype(dtype) @ p["w_out"])
    _refamily(eng)


def _tamper_no_head_norms(eng, monkeypatch):
    """Queries and keys not normalised per head."""
    from singa_tpu.models import conv_moe

    sound = conv_moe._rms
    monkeypatch.setattr(
        conv_moe, "_rms",
        lambda x, w, eps: x if w.shape[-1] == HEAD else sound(x, w, eps))
    _refamily(eng)


def _tamper_bias_in_weights(eng, monkeypatch):
    """The router's bias counted into the weights, not into the choice
    alone."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.models import conv_moe

    def biased(x, w_router, bias, *, top_k, scale, **_):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)) + bias
        w, idx = jax.lax.top_k(s, top_k)
        return (idx.astype(jnp.int32),
                w / jnp.sum(w, axis=1, keepdims=True) * scale)

    monkeypatch.setattr(conv_moe, "route", biased)
    _refamily(eng)


TAMPERS = {"tails-not-reset-at-admission": _tamper_stale_tails,
           "tails-not-carried": _tamper_no_carry,
           "c-gate-left-out": _tamper_no_c_gate,
           "head-norms-left-out": _tamper_no_head_norms,
           "bias-counted-into-the-weights": _tamper_bias_in_weights}


@pytest.mark.parametrize("name", list(TAMPERS))
def test_a_broken_path_is_not_correct(name, monkeypatch):
    line = measure(tiny_cell(), LIMITS,
                   tamper=lambda eng: TAMPERS[name](eng, monkeypatch))
    assert line["correct"] is False
    assert line["failed"] == 0


MID = dict(TINY, vocab_size=4096, hidden_size=256, num_attention_heads=8,
           num_key_value_heads=2, intermediate_size=768,
           moe_intermediate_size=128, initializer_range=0.056)


def test_the_control_one_precision_down_fails_the_limit():
    """The control: the reference with fp8 operands in the program's
    place (at each served position the token fp8 puts first takes the
    served token's place), at a middle size.  The float32 reference's
    own greedy tokens read 0."""
    ref = loader.load_module("references", "conv_moe")
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
    ref = loader.load_module("references", "conv_moe")
    work = loader.load_module("work", "conv_moe")
    s = ref.sizes_of(loader.load_cell(CELL)["config"])
    assert (work.n_full(s), work.n_conv(s)) == (2, 7)
    # the conv operator: 2048 x 6144 in, 2048 x 2048 out = 16.78 M
    assert work.conv_params(s) == 2048 * 6144 + 2048 * 2048 == 16_777_216
    # attention: q and o 2 x 4.19 M, k and v 2 x 1.05 M = 10.49 M
    assert work.attn_params(s) == 2 * 4_194_304 + 2 * 1_048_576
    assert work.dense_ffn_params(s) == 3 * 2048 * 11776 == 72_351_744
    assert work.expert_params(s) == 3 * 2048 * 1536 == 9_437_184
    assert work.expert_bytes(s) == 18_874_368
    assert work.router_params(s) == 2048 * 64 + 64
    # the issue's count: 89.1 + 6 x 620.9 + 2 x 614.6 + 134.2 M = 5.18 B
    n = work.param_count(s)
    dense = work.conv_params(s) + work.dense_ffn_params(s)
    moe = 64 * work.expert_params(s) + work.router_params(s)
    assert abs(n - (dense + 6 * (work.conv_params(s) + moe)
                    + 2 * (work.attn_params(s) + moe)
                    + 65536 * 2048)) < 0.1e6
    assert round(n / 1e9, 2) == 5.18
    assert abs(work.held_weight_bytes(s) - 10.36e9) < 0.01e9
    assert 64 * 8 * work.expert_bytes(s) / work.held_weight_bytes(s) > 0.93
    # K and V of 8 heads of 64 in bf16: 2 KB a position a layer, 4 KB a
    # position over the two attention layers
    assert work.row_bytes(s) == 2048
    assert work.kv_bytes_per_position(s) == 4096
    # a lane's tails: 7 layers x 2 rows x 2048 x 4 B, read and written
    assert work.tail_bytes(s, 1) == 2 * 114_688
    # what a step reads whoever is chosen: 7 conv operators 235 MB, 2
    # attentions 42 MB, a dense feed-forward 145 MB, the head 268 MB,
    # routers and vectors 4.5 MB: 0.69 GB
    assert abs(work.fixed_weight_bytes(s, 230) - 0.696e9) < 0.005e9
    # a decode step of 230 lanes at 600 positions each with every expert
    # hit: + 512 x 18.9 MB = 9.66 GB, rows 138,000 x 4 KB = 0.57 GB,
    # tails 230 x 229 KB = 0.05 GB
    b = work.decode_step_bytes(s, 230, 138_000, 512)
    assert abs(b - (0.696e9 + 9.664e9 + 0.565e9 + 0.053e9)) < 0.01e9
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert abs(b / peaks["hbm_bytes_per_s"] - 13.4e-3) < 0.1e-3
    # the conv operators at 230 lanes: read-bound (0.35 ms against 0.08)
    least, by = work.short_conv_bound_seconds(s, 230, peaks)
    assert by == "bytes" and abs(least - 0.352e-3) < 0.005e-3
    assert abs(230 * 7 * 2 * work.conv_params(s) / 197e12 - 0.274e-3) \
        < 0.01e-3
    # attention: 32 heads x 64 x 4 = 8,192 FLOP a row a layer a lane,
    # against 2 KB to read: bound by bytes
    assert work.attn_flops_per_row(s) == 8_192
    least, by = work.attn_bound_seconds(s, 138_000, peaks)
    assert by == "bytes" and abs(least - 138_000 * 4096 / 819e9) < 1e-9
    # the experts at 14 tokens each: read-bound by 13 x
    least, by = work.experts_bound_seconds(s, 512, 920 * 8, peaks)
    assert by == "bytes" and abs(least - 11.8e-3) < 0.05e-3
    assert 920 * 8 * 2 * work.expert_params(s) / 197e12 < least / 13


def test_the_readers_return_nothing_without_a_trace_or_the_counts():
    reader = loader.load_module("readers", "conv_moe")
    assert reader.read({"trace": None}, "reread_share") is None

    class NoDevices:
        devices = {}

    for what in ("conv_roofline", "reread_share", "load_peak"):
        assert reader.read({"trace": NoDevices()}, what) is None
