"""The work functions against counts made by hand at qwen3-1.7b widths
(d 2048, d_ff 6144, 16 query and 8 KV heads of 128, 28 layers, vocab
151936, bf16)."""
import json

import pytest

from serve_bench_tiny import SERVE
import work

QWEN = json.loads((SERVE / "configs" / "qwen3-1.7b.json").read_text())
V5E = work.peaks("TPU v5 lite")


def test_matmul_weights_per_token_and_layer():
    q = o = 2048 * 16 * 128                  # 4,194,304 each
    k = v = 2048 * 8 * 128                   # 2,097,152 each
    mlp = 3 * 2048 * 6144                    # 37,748,736
    assert work.layer_matmul_params(QWEN) == q + k + v + o + mlp == 50_331_648


def test_head_and_decode_flops():
    assert work.head_flops(QWEN) == 2 * 2048 * 151_936 == 622_329_856
    # context 100: per layer 2 * 50,331,648 + 4 * 16 * 128 * 100
    per_layer = 100_663_296 + 819_200
    assert work.decode_flops(QWEN, 100) == 28 * per_layer + 622_329_856 \
        == 3_463_839_744


def test_prefill_flops_count_the_causal_triangle_once():
    plen = 128
    attn = 4 * 16 * 128 * (128 * 129 // 2)   # 67,633,152 per layer
    mm = 2 * 128 * 50_331_648                # 12,884,901,888 per layer
    assert work.prefill_flops(QWEN, plen) == 28 * (mm + attn) + 622_329_856


def test_paged_attention_call_counts_only_real_context():
    flops, nbytes = work.paged_attention_call(QWEN, [100, 300])
    assert flops == 4 * 16 * 128 * 400 == 3_276_800
    # K and V at 8 heads x 128 x 2 bytes per position, plus q and out
    assert nbytes == 2 * 8 * 400 * 128 * 2 + 2 * (2 * 16 * 128 * 2) \
        == 1_654_784
    # memory bound: 1.65 MB at 819 GB/s
    assert work.least_seconds(flops, nbytes, V5E) == pytest.approx(
        1_654_784 / 819e9)


def test_mlp_gemms_and_their_roofline():
    assert work.mlp_gemms(QWEN, 128) == [(128, 2048, 6144)] * 2 + [
        (128, 6144, 2048)]
    flops, nbytes = work.gemm(QWEN, 128, 2048, 6144)
    assert flops == 3_221_225_472
    assert nbytes == (262_144 + 12_582_912 + 786_432) * 2 == 27_262_976
    # at M = 128 the weight stream bounds it: 33.3 us against 16.4 us
    assert work.least_seconds(flops, nbytes, V5E) == pytest.approx(
        27_262_976 / 819e9)
    big = work.gemm(QWEN, 4096, 2048, 6144)
    assert work.least_seconds(*big, V5E) == pytest.approx(big[0] / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
