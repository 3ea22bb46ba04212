"""The ``ssm_moe`` family through the benchmark: its cell loads by name,
the serve driver runs it at a tiny size on the CPU (the loader, driver,
adapter, reference and ``run.measure`` a chip run uses; only the sizes
differ), the check catches the broken paths that layers of one mixer each
-- recurrent state a slot, attention without positions, experts in a
latent beside a shared expert -- can have, the mix's chains outlast the
window, and the shape functions are pinned against hand counts.

Test-size readings (float32 program, seed 2147483900): both gaps 0 for
the sound program.
"""

import copy
import dataclasses

import numpy as np
import pytest
from bench_util import TINY_ROUNDS, measure

from benchmark.harness import loader, traffic

CELL = "nemotron3s-serve-agents"
CONFIG = "nemotron-3-super-120b-a12b"
LIMITS = {"served_logit_gap_mean": 1e-5, "served_logit_gap_max": 1e-4,
          "malformed_results": 0, "unchecked": 0}
#: all chains' tokens over the 54 s from traffic's start to the window's
#: end (``test_bench_traffic.CHAIN_FLOOR`` gives a mix it does not list a
#: floor of 0, and may not be edited): 6 x the rate the cell reads
CHAIN_FLOOR = 20_000
# the tiny preset: in the tests only.  Seven layers with every kind among
# them (a stretch that repeats, then one of each), a router of 8 outputs,
# top-3, experts [0, 4) held, a latent of 32; 8 Mamba heads of 16 in 2
# groups, state 16; the reference scales its weights by their fan-in, so
# every layer adds to the stream what it adds at the published width
TINY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=7,
            hybrid_override_pattern="MEMEM*E", num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
            mamba_head_dim=16, ssm_state_size=16, n_groups=2, chunk_size=8,
            n_routed_experts=4, num_experts_per_tok=3,
            moe_intermediate_size=48, moe_latent_size=32,
            moe_shared_expert_intermediate_size=96,
            share=dict(num_experts_published=8, experts_held=[0, 4]))
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


def _catalog_row():
    import json
    import os

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16":
                return row
    return None


def test_the_cell_loads_with_its_files_and_its_family():
    cell = loader.load_cell(CELL)
    cfg, e = cell["config"], cell["config"]["engine"]
    assert cell["chips"] == 1 and cfg["family"] == "ssm_moe"
    # published widths, untouched; the router 512 wide and top-22
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"],
            cfg["chunk_size"], cfg["expand"]) == (
                4096, 32, 2, 128, 128, 64, 128, 8, 4, 128, 2)
    assert (cfg["moe_intermediate_size"], cfg["moe_latent_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["routed_scaling_factor"], cfg["n_group"],
            cfg["topk_group"], cfg["intermediate_size"]) == (
                2688, 1024, 5376, 22, 1, 5, 1, 1, 2688)
    assert (cfg["mlp_hidden_act"], cfg["use_conv_bias"],
            cfg["norm_topk_prob"], cfg["tie_word_embeddings"],
            cfg["layer_norm_epsilon"], cfg["max_position_embeddings"],
            cfg["model_type"], cfg["rope_theta"]) == (
                "relu2", True, True, False, 1e-5, 262144, "nemotron_h",
                10000)
    share = cfg["share"]
    assert share == {"chips_per_layer": 4, "num_experts_published": 512,
                     "experts_held": [0, 128],
                     "vocab_size_published": 131072}
    # the cut: one whole period, the published layers 27-37
    pub = cfg["published"]
    assert pub["layers_kept"] == list(range(27, 38))
    assert cfg["hybrid_override_pattern"] == "MEMEMEMEM*E" == "".join(
        pub["hybrid_override_pattern"][i] for i in pub["layers_kept"])
    assert len(pub["hybrid_override_pattern"]) == 88 == \
        pub["num_hidden_layers"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["num_nextn_predict_layers"],
            e["max_len"]) == (11, 128, 32768, 0, 9216)
    reduced = {"num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size",
               "num_nextn_predict_layers", "engine.max_len"}
    assert set(cfg["reduced"]) == reduced == set(cfg["reduced_why"])
    assert "8 pipeline stages" in cfg["deployment"] \
        and "32 chips" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 10
    assert any("NO positions" in a for a in cfg["assumed"])
    assert set(e["why"]) == set(e) - {"why"} and all(e["why"].values())
    assert (e["block_size"], e["dtype"], e["state_dtype"],
            e["prefill_token_budget"]) == (128, "bfloat16", "float32", 512)
    man = next(c for c in loader.manifest()["configs"]
               if c["name"] == CONFIG)
    assert man["reduced"] == cfg["reduced"] and man["source"] == \
        cfg["source"] and len(man["source"]) <= 200
    # every number of the catalog's row under the same key, but the
    # reduced ones
    row = _catalog_row()
    if row is not None:
        assert cfg["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in reduced:
                assert cfg[k] == v, k
    # the traffic, exactly as the issue names it
    mix = cell["traffic"]
    assert mix["arrivals"] == {"clients": 128, "stagger_s": 12.0}
    assert (mix["prompt_len"], mix["reply_len"]) == (
        {"dist": "lognormal", "median": 512, "sigma": 1.0, "min": 64,
         "max": 8192}, {"dist": "uniform", "min": 256, "max": 768})
    assert (mix["requests_per_client"], mix["rounds"], mix["preroll_s"],
            mix["schedule_seed"]) == (4, 4, 14, 1)
    assert cell["cell"]["check"]["requests"] == 12
    assert cell["cell"]["trace_window"]["length_s"] == 2.0
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "token_gap_p95_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"ssm_moe_decode_step_roofline", "ssm_step_roofline",
            "ssm_step_share", "expert_reread_share", "expert_load_peak",
            "moe_experts_roofline", "moe_expert_share", "expert_hit_share",
            "state_slots_used_peak", "kv_blocks_used_peak",
            "decode_step_device_ms", "prefill_chunk_device_ms",
            "batch_occupancy", "prefill_budget_use", "stall_s.host",
            "hbm_peak_share.serve", "compiles_in_window.serve",
            "step_host_work_ms_p50", "idle_ms_per_step.other"} <= names
    # ssm_scan_roofline's bound is one block's against a launch of up to
    # four (PERF.md section 7)
    assert not {"decode_pool_copy_share", "decode_step_roofline",
                "mla_attn_roofline", "short_conv_share",
                "window_attn_share", "ssm_scan_roofline"} & names
    new = [m for m in loader.manifest()["per_layer"]
           if m["workloads"] == [CELL]]
    assert {m["name"] for m in new} == {"ssm_moe_decode_step_roofline",
                                        "ssm_step_roofline"}
    assert all(m["moves"] == "token_gap_p95_ms" and m["layer"]
               == "decode math (models/ssm_moe.py)" for m in new)
    # every reader a metric of the cell names is a file, and the family
    # names a reference, an adapter and its shape functions
    for m in cell["per_layer"]:
        loader.load_module("readers", m["file"]["reader"])
    for kind in ("references", "adapters", "work"):
        loader.load_module(kind, cfg["family"])
    c = loader.load_module("adapters", "ssm_moe").program_config(cfg)
    assert (c.n_layer, c.n_m, c.n_a, c.n_e, c.max_len, c.dtype) == (
        11, 5, 1, 5, 9216, "bfloat16")
    assert (c.n_routed_experts, c.experts_held, c.n_held,
            c.num_experts_per_tok, c.vocab_size) == (
                512, (0, 128), 128, 22, 32768)
    s = loader.load_module("references", "ssm_moe").sizes_of(cfg)
    assert s["held"] == (0, 128) and s["L"] - s["KD"] == 5 and s["R"] == 512
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
    # this cell is there, on one chip; of all cells at most a quarter ask
    # for four
    cells = loader.manifest()["workloads"]
    assert sum(w["name"] == CELL for w in cells) == 1
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    assert len(next(w for w in cells if w["name"] == CELL)["why"]) <= 200


def test_the_mix_is_the_stated_one_and_its_chains_outlast_the_window():
    """A brief and tool output in, a reasoning trace out, 128 callers:
    all chains' tokens over the 54 s they have to last are over the
    floor; every request fits the served context."""
    mix = loader.load_cell(CELL)["traffic"]
    reqs = traffic.make_requests(mix, 1, (14.0, 40.0, 20.0), 32768, 9216)
    assert len(reqs) == 128 * mix["requests_per_client"] * mix["rounds"]
    traffic.check_fits(reqs, 9216)
    plens = np.array([len(r.prompt) for r in reqs])
    news = np.array([r.max_new for r in reqs])
    assert plens.min() >= 64 and plens.max() <= 8192
    assert abs(np.median(plens) - 512) < 16
    assert 760 < plens.mean() < 880
    assert 0.2 < (plens > 1024).mean() < 0.3
    assert news.min() >= 256 and news.max() <= 768
    assert max(len(r.prompt) + r.max_new for r in reqs) <= 8960
    tokens = plens.sum() + news.sum()
    lasts = mix["preroll_s"] + loader.manifest()["run_seconds"]
    assert mix["arrivals"]["stagger_s"] <= mix["preroll_s"]
    assert tokens / lasts >= CHAIN_FLOOR, (tokens, lasts)
    # every caller's chain, not just their sum: the shortest chain's
    # tokens at a 128th of the floor
    per_caller = [0] * 128
    for r in reqs:
        per_caller[r.client] += len(r.prompt) + r.max_new
    assert min(per_caller) / lasts >= 0.5 * CHAIN_FLOOR / 128, \
        min(per_caller)
    # ids from the held slice of the vocabulary, all of it
    top = max(int(r.prompt.max()) for r in reqs[:128])
    assert 32000 < top < 32768


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
    programs compiled anew, as the engine compiles them when it is built
    (tests/benchmark/test_bench_conv_moe.py says why)."""
    fam = eng._fam
    eng._fam = dataclasses.replace(fam, cfg=dataclasses.replace(
        fam.cfg, max_position_embeddings=262145))
    eng._x._aot_memo.clear()
    eng._compile_launch_widths()


def _tamper_stale_state(eng, monkeypatch):
    """The state NOT reset at admission: a request's recurrence starts
    from what the slot's last occupant left."""
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
    """The state not carried across launches: every launch starts its
    recurrences and convolutions from zeros, as if the prompt began
    there."""
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


def _tamper_no_latent_projection(eng, monkeypatch):
    """The latent down-projection skipped: the routed experts are handed
    the first ``moe_latent_size`` channels of the stream as they are."""
    from singa_tpu.models import ssm_moe

    monkeypatch.setattr(ssm_moe, "_to_latent",
                        lambda a, p: a[:, :p["w_fc1"].shape[-1]])
    _refamily(eng)


def _tamper_relu_for_relu2(eng, monkeypatch):
    """``relu`` where the experts square it."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.models import ssm_moe

    def relu1(x, w_up, w_down):
        h = jax.nn.relu(jnp.dot(x, w_up,
                                preferred_element_type=jnp.float32))
        return jnp.dot(h.astype(x.dtype), w_down,
                       preferred_element_type=jnp.float32)

    monkeypatch.setattr(ssm_moe, "relu2", relu1)
    _refamily(eng)


def _tamper_no_shared_expert(eng, monkeypatch):
    """The shared expert left out: the layer is its routed experts."""
    import jax.numpy as jnp

    from singa_tpu.models import ssm_moe

    monkeypatch.setattr(
        ssm_moe, "_shared_expert",
        lambda a, p: jnp.zeros(a.shape[:-1] + (p["w_sd"].shape[-1],),
                               jnp.float32))
    _refamily(eng)


def _tamper_rotary(eng, monkeypatch):
    """Positions where the model has none: queries and keys turned by
    rotary angles (``rope_theta`` is published, and the block does not
    use it)."""
    import jax.numpy as jnp

    from singa_tpu.models import ssm_moe
    from singa_tpu.ops.paged_attention import rotary

    sound = ssm_moe._qkv

    def turned(a, p, c):
        q, k, v = sound(a, p, c)
        pos = jnp.arange(a.shape[0])
        turn = lambda x: rotary(x.transpose(1, 0, 2), pos,
                                10000.0).transpose(1, 0, 2)
        return turn(q), turn(k), v

    monkeypatch.setattr(ssm_moe, "_qkv", turned)
    _refamily(eng)


def _tamper_norm_before_gate(eng, monkeypatch):
    """The mixer's norm before its gate: ``norm(y) * silu(z)`` for
    ``norm(y * silu(z))``."""
    import jax

    from singa_tpu.models import ssm_moe

    def norm_first(y, z, p, c):
        t = y.shape[0]
        y = y.reshape(t, c.n_groups, -1)
        y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True)
                              + c.layer_norm_epsilon)
        y = y.reshape(t, -1) * p["norm"] * jax.nn.silu(z)
        return y.astype(p["w_out"].dtype) @ p["w_out"]

    monkeypatch.setattr(ssm_moe, "_mixer_out", norm_first)
    _refamily(eng)


TAMPERS = {"state-not-reset-at-admission": _tamper_stale_state,
           "state-not-carried-across-a-launch": _tamper_no_carry,
           "latent-down-projection-skipped": _tamper_no_latent_projection,
           "relu-for-relu2": _tamper_relu_for_relu2,
           "shared-expert-left-out": _tamper_no_shared_expert,
           "rotary-applied": _tamper_rotary,
           "norm-before-the-gate": _tamper_norm_before_gate}


@pytest.mark.parametrize("name", list(TAMPERS))
def test_a_broken_path_is_not_correct(name, monkeypatch):
    line = measure(tiny_cell(), LIMITS,
                   tamper=lambda eng: TAMPERS[name](eng, monkeypatch))
    assert line["correct"] is False
    assert line["failed"] == 0


MID = dict(TINY, vocab_size=4096, hidden_size=256, num_attention_heads=8,
           num_key_value_heads=2, head_dim=32, mamba_num_heads=16,
           mamba_head_dim=32, ssm_state_size=32, moe_intermediate_size=128,
           moe_latent_size=64, moe_shared_expert_intermediate_size=256)


def test_the_control_one_precision_down_fails_the_limit():
    """The control: the reference with fp8 operands in the program's
    place (at each served position the token fp8 puts first takes the
    served token's place), at a middle size.  The float32 reference's
    own greedy tokens read 0."""
    ref = loader.load_module("references", "ssm_moe")
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
    ref = loader.load_module("references", "ssm_moe")
    work = loader.load_module("work", "ssm_moe")
    s = ref.sizes_of(loader.load_cell(CELL)["config"])
    assert [work.count(s, k) for k in "M*E"] == [5, 1, 5]
    assert (work.d_ssm(s), work.conv_dim(s)) == (8192, 10240)
    # the issue's arithmetic: a mixer's in-projection 4,096 x (8,192 +
    # 10,240 + 128) and out-projection 8,192 x 4,096 = 109.6 M
    assert work.mixer_params(s) == 4096 * 18560 + 8192 * 4096 \
        == 109_576_192
    # attention: q and o 2 x 16.8 M, k and v 2 x 1.05 M = 35.7 M
    assert work.attn_params(s) == 2 * 4096 ** 2 + 2 * 4096 * 256 \
        == 35_651_584
    # an expert layer outside its routed experts: latent projections 2 x
    # 4.2 M + the shared expert 2 x 4,096 x 5,376 = 44.0 M -> 52.4 M, and
    # the router 2.1 M: 54.5 M
    assert work.latent_params(s) == 2 * 4_194_304 + 2 * 4096 * 5376 \
        == 52_428_800
    assert work.router_params(s) == 4096 * 512 + 512
    assert work.expert_params(s) == 2 * 1024 * 2688 == 5_505_024
    assert work.expert_bytes(s) == 11_010_048
    # 5 x 109.6 + 35.7 + 5 x (54.5 + 128 x 5.5) + 268 = 4.65 B
    n = work.param_count(s)
    moe = work.latent_params(s) + work.router_params(s) \
        + 128 * work.expert_params(s)
    assert abs(n - (5 * work.mixer_params(s) + work.attn_params(s)
                    + 5 * moe + 2 * 32768 * 4096)) < 0.5e6
    assert round(n / 1e9, 2) == 4.65
    assert abs(work.held_weight_bytes(s) - 9.32e9) < 0.01e9
    assert 0.75 < 5 * 128 * work.expert_bytes(s) \
        / work.held_weight_bytes(s) < 0.76
    # K and V of 2 heads of 128 in bf16: 1 KB a position, one layer
    assert work.row_bytes(s) == 1024 == work.kv_bytes_per_position(s)
    # a lane's state: 5 layers x (128 x 64 x 128 + 3 x 10,240) x 4 B =
    # 21.6 MB, 4.2 MB of it a layer's SSM state
    assert work.ssm_state_bytes(s) == 4_194_304
    assert work.state_bytes_per_slot(s) == 5 * (4_194_304 + 122_880) \
        == 21_585_920
    # what a step reads whoever is chosen: 5 mixers 1.10 GB, attention
    # 0.07, 5 x latent and shared 0.52, the head 0.27, routers and
    # vectors 0.05: 2.0 GB
    assert abs(work.fixed_weight_bytes(s, 128) - 2.004e9) < 0.005e9
    # a decode step of 128 lanes at 1,000 positions each with every held
    # expert hit: + 640 x 11.0 MB = 7.05 GB, rows 128,000 x 1 KB = 0.13
    # GB, state 128 x 21.6 MB read and written = 5.53 GB: 14.7 GB, 18 ms
    b = work.decode_step_bytes(s, 128, 128_000, 640)
    assert abs(b - (2.004e9 + 7.046e9 + 0.131e9 + 5.526e9)) < 0.01e9
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert abs(b / peaks["hbm_bytes_per_s"] - 17.96e-3) < 0.05e-3
    # the one-step recurrences at 128 lanes: 5 x 128 x 4.2 MB read and
    # written = 5.37 GB: 6.6 ms, a hundred times their operations
    least, by = work.ssm_step_bound_seconds(s, 128, peaks)
    assert by == "bytes" and abs(least - 6.555e-3) < 0.005e-3
    assert 4 * 128 * 5 * 128 * 64 * 128 / 197e12 < least / 100
    # the experts at 5.5 tokens each: read-bound by 40 x
    least, by = work.experts_bound_seconds(s, 640, 128 * 22 * 5 / 4, peaks)
    assert by == "bytes" and abs(least - 8.60e-3) < 0.01e-3
    assert 3520 * 2 * work.expert_params(s) / 197e12 < least / 40
    # attention: 32 heads x 128 x 4 = 16,384 FLOP a row a lane against 1
    # KB to read: bound by bytes
    assert work.attn_flops_per_row(s) == 16_384
    least, by = work.attn_bound_seconds(s, 128_000, peaks)
    assert by == "bytes" and abs(least - 128_000 * 1024 / 819e9) < 1e-9


def test_the_readers_return_nothing_without_a_trace_or_the_scope():
    reader = loader.load_module("readers", "ssm_moe")
    kw = dict(module="paged_decode_kernel", program="paged_decode_kernel",
              scope="ssm_step")
    assert reader.read({"trace": None}, "step_roofline", **kw) is None

    class NoDevices:
        devices = {}

    assert reader.read({"trace": NoDevices()}, "step_roofline",
                       **kw) is None
    with pytest.raises(ValueError, match="unknown ssm_moe reading"):
        reader.read({"trace": None}, "another")


def test_the_new_metrics_files_name_readers_that_exist():
    man = loader.manifest()
    for name, reader in (("ssm_moe_decode_step_roofline", "mla_moe"),
                         ("ssm_step_roofline", "ssm_moe")):
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        assert entry["unit"] == "%" and entry["source"] == "device_trace"
        cell = loader.load_cell(CELL)
        f = next(m for m in cell["per_layer"] if m["name"] == name)["file"]
        assert f["reader"] == reader
        assert f["params"]["module"] == "paged_decode_kernel"
