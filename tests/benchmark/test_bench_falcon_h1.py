"""The ``falcon_h1`` family through the benchmark: its cell loads by
name, the serve driver runs it at a tiny size on the CPU (the loader,
driver, adapter, reference and ``run.measure`` a chip run uses; only the
sizes differ), the check catches the broken paths a hybrid block can
have, and the shape functions are pinned against hand counts.

Test-size readings (float32 program, seeds 2147483900 and 5): both gaps
0 for the sound program.
"""

import copy

import numpy as np
import pytest
from bench_util import TINY_ROUNDS, measure

from benchmark.harness import loader

CELL = "falconh1-serve-docqa"
LIMITS = {"served_logit_gap_mean": 1e-5, "served_logit_gap_max": 1e-4,
          "malformed_results": 0, "unchecked": 0}
# the tiny preset: in the tests only
TINY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            intermediate_size=128, mamba_d_ssm=64, mamba_d_state=16,
            mamba_n_groups=2, mamba_n_heads=4, mamba_d_head=16,
            mamba_chunk_size=8, rope_theta=1e4)
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


def test_the_cell_loads_by_name_with_its_metrics():
    cell = loader.load_cell(CELL)
    cfg, e = cell["config"], cell["config"]["engine"]
    assert cell["chips"] == 1 and cfg["family"] == "falcon_h1"
    # published widths, untouched
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (5120, 20, 4, 128)
    assert (cfg["intermediate_size"], cfg["mamba_d_ssm"],
            cfg["mamba_d_state"], cfg["mamba_n_groups"],
            cfg["mamba_n_heads"], cfg["mamba_d_conv"]) == (
                21504, 4096, 256, 2, 32, 4)
    assert cfg["vocab_size"] == 261120 and cfg["num_hidden_layers"] == 6
    assert set(cfg["reduced"]) == {"num_hidden_layers", "engine.max_len"}
    assert e["block_size"] == cfg["mamba_chunk_size"] == 128
    mix = cell["traffic"]
    assert mix["arrivals"] == {"clients": 32, "stagger_s": 12.0}
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (1024,
                                                                   4096)
    assert (mix["reply_len"]["min"], mix["reply_len"]["max"]) == (128, 384)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "token_gap_p95_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"hybrid_decode_step_roofline", "ssm_step_share",
            "ssm_scan_roofline", "state_slots_used_peak",
            "decode_step_device_ms", "prefill_chunk_device_ms",
            "hbm_peak_share.serve", "compiles_in_window.serve"} <= names
    assert "decode_pool_copy_share" not in names
    # every reader a metric of the cell names is a file
    for m in cell["per_layer"]:
        loader.load_module("readers", m["file"]["reader"])
    # the published file writes rope_theta as a whole number too wide
    # for an int32: the program takes it as a float
    c = loader.load_module("adapters", "falcon_h1").program_config(cfg)
    assert isinstance(c.rope_theta, float) and c.rope_theta == 1e11
    assert (c.n_layer, c.max_len, c.dtype) == (6, 8192, "bfloat16")
    # what test_bench_loader.py holds every cell to, but for the family
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert all(m["moves"] in e2e for m in cell["per_layer"])
    assert set(cell["cell"]) == {"trace_window", "check", "limits",
                                 "limits_from"}
    assert set(cell["cell"]["limits"]) == set(cell["cell"]["limits_from"])
    assert all(v is not None for v in cell["cell"]["limits"].values())
    lim = cell["cell"]["limits"]
    assert lim["malformed_results"] == 0 and lim["unchecked"] == 0
    assert 0 < lim["served_logit_gap_mean"] < lim["served_logit_gap_max"]


def test_the_serve_driver_runs_the_family_and_comes_out_correct():
    line = measure(tiny_cell(), LIMITS)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s",
                                    "token_gap_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())


def _tamper_no_carry(eng):
    """State not carried across a chunk row: every row starts from
    zero."""
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


def _tamper_no_reset(eng):
    """State not reset at admission: a request starts from what the
    slot's row last held."""
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


def _tamper_no_mamba(eng):
    """The Mamba branch left out: its out-projection reads zero."""
    lay = dict(eng._params["layers"])
    lay["w_out"] = lay["w_out"] * 0
    eng._params = dict(eng._params, layers=lay)


@pytest.mark.parametrize("tamper", [_tamper_no_carry, _tamper_no_reset,
                                    _tamper_no_mamba],
                         ids=["state-not-carried", "state-not-reset",
                              "mamba-left-out"])
def test_a_broken_hybrid_path_is_not_correct(tamper):
    line = measure(tiny_cell(), LIMITS, tamper=tamper)
    assert line["correct"] is False


MID = dict(TINY, vocab_size=8192, hidden_size=256, num_hidden_layers=6,
           intermediate_size=1024, mamba_d_ssm=256, mamba_d_state=64,
           mamba_n_heads=8, mamba_d_head=32, head_dim=64)


def test_the_control_one_precision_down_fails_the_limit():
    """The control: the reference with fp8 operands in the program's
    place (at each served position the token fp8 puts first takes the
    served token's place), at a middle size the reference alone holds in
    a test run.  The float32 reference's own greedy tokens read 0."""
    ref = loader.load_module("references", "falcon_h1")
    cfg = dict(loader.load_cell(CELL)["config"], **MID)
    cfg["engine"] = dict(cfg["engine"], max_len=128)
    sizes = ref.sizes_of(cfg)
    means = []
    for seed in (2147483900, 5):
        w = ref.init_weights(sizes, seed)
        toks = np.random.default_rng(seed).integers(0, 8192, 120)
        worst, total, scale = ref.served_token_gap(w, sizes, toks, 20,
                                                   "fp8")
        assert scale > 1.0
        assert worst > LIMITS["served_logit_gap_max"]
        means.append(total / 100)
    assert min(means) > 3 * LIMITS["served_logit_gap_mean"], means


def test_shape_functions_against_hand_counts():
    ref = loader.load_module("references", "falcon_h1")
    work = loader.load_module("work", "falcon_h1")
    s = ref.sizes_of(loader.load_cell(CELL)["config"])
    # a layer: attention 5120x2560 + 2 x 5120x512 + 2560x5120 = 31.5 M;
    # mixer in 5120 x (4096+4096+1024+32) = 47.3 M, out 4096x5120 = 21.0 M;
    # feed-forward 3 x 5120x21504 = 330.3 M
    assert work.layer_matmul_params(s) == (
        31_457_280 + 47_349_760 + 20_971_520 + 330_301_440)
    assert round(work.layer_matmul_params(s) / 1e6) == 430
    # recurrent state a slot: 6 x 32 x 128 x 256 float32 = 25.2 MB, plus
    # the conv's tail 6 x 3 x 5120 float32
    assert work.state_bytes_per_slot(s) == 6 * 4 * (1_048_576 + 15_360)
    assert round(6 * 4 * 1_048_576 / 1e6, 1) == 25.2
    # K and V: 2 x 4 heads x 128 x 2 bytes = 2 KB a position a layer
    assert work.kv_bytes_per_position(s) == 6 * 2048 == 12_288
    # a decode step at 30 lanes of 2,700 positions: weights 7.8 GB
    # (layers 5.2 + head 2.7), state 1.5 GB, K/V 1.0 GB
    b = work.decode_step_bytes(s, 30, 30 * 2700)
    assert 10.2e9 < b < 10.5e9
    assert abs(work.weight_bytes(s, 30) - 7.84e9) < 0.02e9
    # the scan of one chunk row, a layer: 0.69 GFLOP; the state read and
    # written (2 x 4.19 MB) and 128 x (4096 + 4096 + 2 x 512 + 32)
    # float32 of x, y, B, C, dt: 13.1 MB; bound by bytes
    assert abs(work.ssm_scan_flops(s, 128) - 0.69e9) < 0.01e9
    assert work.ssm_scan_bytes(s, 128) == 4 * (2 * 1_048_576
                                               + 128 * 9248) == 13_123_584
    least, by = work.ssm_scan_bound_seconds(
        s, 128, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert by == "bytes" and abs(least - 6 * 13_123_584 / 819e9) < 1e-9
    assert abs(work.chunk_row_flops(s, 128, 0) - 0.67e12) < 0.01e12


def test_the_scope_reader_returns_nothing_without_a_trace_or_a_map():
    reader = loader.load_module("readers", "scopes")
    assert reader.read({"trace": None}, "scope_share") is None
