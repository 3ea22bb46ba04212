"""The shape functions against hand-worked sizes, and the peaks table."""

import pytest
from bench_util import ROOT  # noqa: F401

from benchmark.harness import loader, peaks

ref = loader.load_module("references", "gpt2")
train = loader.load_module("work", "gpt2_train")
decode = loader.load_module("work", "gpt2_decode")
flash = loader.load_module("work", "flash_attention")


def sizes(cell):
    return ref.sizes_of(loader.load_cell(cell)["config"])


def test_published_sizes():
    s = sizes("gpt2s-train-1chip")
    assert (s["L"], s["E"], s["H"], s["I"], s["V"], s["P"]) == \
        (12, 768, 12, 3072, 50257, 1024)
    l = sizes("gpt2l-serve-chat")
    assert (l["L"], l["E"], l["H"], l["I"], l["V"], l["P"]) == \
        (36, 1280, 20, 5120, 50257, 1024)
    assert s["E"] // s["H"] == l["E"] // l["H"] == 64


def test_train_flops_per_token_gpt2_small():
    s = sizes("gpt2s-train-1chip")
    # 12 x (4 x 768^2 + 2 x 768 x 3072) + 50257 x 768
    assert train.matmul_params(s) == 12 * 7077888 + 38597376 == 123532032
    # 12 layers x 2 products x 2 FLOPs x 768 x (1024 + 1) / 2
    assert train.attention_flops_per_token(s, 1024) == 12 * 4 * 768 * 512.5
    assert train.train_flops_per_token(s, 1024) == pytest.approx(
        3 * (2 * 123532032 + 18892800))
    # ~0.80 GFLOP a token: 117.6k tokens/s would be ~47.6% of 197 TFLOP/s
    assert train.train_flops_per_token(s, 1024) * 117.6e3 / 197e12 == \
        pytest.approx(0.476, abs=0.002)


def test_decode_bytes_gpt2_large():
    l = sizes("gpt2l-serve-chat")
    per_layer = 4 * 1280 ** 2 + 2 * 1280 * 5120 + 9 * 1280 + 5120
    assert decode.weight_bytes(l) == 2 * (
        36 * per_layer + 50257 * 1280 + 2 * 1280)
    # 774M parameters less the position table, in bf16: ~1.55 GB
    assert decode.weight_bytes(l) == pytest.approx(1.545e9, rel=0.01)
    # one cached position: K and V, 36 layers x 1280 wide x 2 bytes
    assert decode.kv_bytes(l, 1) == 2 * 36 * 1280 * 2 == 184320
    assert decode.decode_step_bytes(l, 30 * 300) == \
        decode.weight_bytes(l) + 9000 * 184320
    # the whole 561 x 32 pool: 3.3 GB
    assert decode.kv_bytes(l, 561 * 32) == pytest.approx(3.31e9, rel=0.01)


def test_decode_and_chunk_flops():
    l = sizes("gpt2l-serve-chat")
    mm = 36 * (4 * 1280 ** 2 + 2 * 1280 * 5120)
    assert decode.decode_step_flops(l, 48, 0) == 2 * (mm + 50257 * 1280) * 48
    assert decode.chunk_row_flops(l, 32, 0) == 32 * (
        2 * mm + 4 * 36 * 1280 * 16.5)
    assert decode.chunk_row_flops(l, 32, 512) > decode.chunk_row_flops(
        l, 32, 0)


def test_flash_kernel_bounds():
    f = flash.flops(8, 12, 1024, 64)
    one = 2 * 1024 * 1024 * 64 * 8 * 12 / 2
    assert (f["fwd"], f["dq"], f["dkv"]) == (2 * one, 3 * one, 4 * one)
    b = flash.bytes_moved(8, 12, 1024, 64)
    assert b["fwd"] == 4 * 8 * 12 * 1024 * 64 * 2
    bound = flash.bound_seconds(8, 12, 1024, 64, peaks.peaks("TPU v5 lite"))
    # head size 64 at S = 1024: 256 FLOPs a byte forward, over the
    # chip's 197e12 / 819e9 = 240 -- compute bound, if only just
    assert f["fwd"] / b["fwd"] == 256
    assert bound["fwd"] == (pytest.approx(f["fwd"] / 197e12), "compute")
    short = flash.bound_seconds(8, 12, 256, 64, peaks.peaks("TPU v5 lite"))
    assert short["fwd"][1] == "memory"
    assert bound["dkv"][0] >= f["dkv"] / 197e12


def test_peaks_table_and_unknown_kinds():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")
