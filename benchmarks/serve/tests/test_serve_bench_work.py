"""The work functions against counts made by hand at qwen3-1.7b widths
(d 2048, d_ff 6144, 16 query and 8 KV heads of 128, 28 layers, vocab
151936, bf16): the dense decoder's own counts, and ``work.py``'s
dispatch to them by the configuration's architecture."""
import json

import pytest

from serve_bench_tiny import SERVE
import catalog
import work

QWEN = json.loads((SERVE / "configs" / "qwen3-1.7b.json").read_text())
MISTRAL = json.loads((SERVE / "configs" / "mistral-nemo-12b.json").read_text())
CONFIGS = {"qwen3-1.7b": QWEN, "mistral-nemo-12b": MISTRAL}
V5E = work.peaks("TPU v5 lite")
DENSE = catalog.architecture("dense_decoder")

# The counts of both benchmark configurations at fixed lengths, as
# recorded before the dense counts moved from work.py into
# architectures/dense_decoder.py: the move keeps every number equal as a
# float.  The paged attention call and the MLP GEMMs were then one
# layer's; the call is now summed over the layers, and each GEMM carries
# the number of layers that run it.
PREFILL_LENGTHS = (256, 1024, 1536, 1792)
DECODE_CONTEXTS = (1, 1000, 2047)
RAGGED = [1, 17, 400, 1023, 2047]
PINNED = {
    "qwen3-1.7b": {
        "prefill": [729_722_388_480, 3_007_216_877_568, 4_600_708_464_640,
                    5_420_002_836_480],
        "decode": [3_441_131_520, 3_670_278_144, 3_910_434_816],
        "paged_per_layer": (28_573_696, 14_327_808),
        "mlp": [(1536, 2048, 6144), (1536, 2048, 6144), (1536, 6144, 2048)],
    },
    "mistral-nemo-12b": {
        "prefill": [1_402_596_229_120, 5_670_782_894_080, 8_569_927_761_920,
                    10_035_606_323_200],
        "decode": [6_794_936_320, 6_958_612_480, 7_130_152_960],
        "paged_per_layer": (57_147_392, 14_368_768),
        "mlp": [(1536, 5120, 14336), (1536, 5120, 14336),
                (1536, 14336, 5120)],
    },
}


def test_matmul_weights_per_token_and_layer():
    q = o = 2048 * 16 * 128                  # 4,194,304 each
    k = v = 2048 * 8 * 128                   # 2,097,152 each
    mlp = 3 * 2048 * 6144                    # 37,748,736
    assert DENSE.layer_matmul_params(QWEN) == q + k + v + o + mlp == 50_331_648


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
    # per layer, over the 28 layers
    assert flops == 28 * 4 * 16 * 128 * 400 == 28 * 3_276_800
    # K and V at 8 heads x 128 x 2 bytes per position, plus q and out
    assert nbytes == 28 * (2 * 8 * 400 * 128 * 2 + 2 * (2 * 16 * 128 * 2)) \
        == 28 * 1_654_784
    # memory bound: 1.65 MB a layer at 819 GB/s
    assert work.least_seconds(flops, nbytes, V5E) == pytest.approx(
        28 * 1_654_784 / 819e9)


def test_mlp_gemms_and_their_roofline():
    assert DENSE.mlp_gemms(QWEN, 128) == [(28, 128, 2048, 6144)] * 2 + [
        (28, 128, 6144, 2048)]
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


@pytest.mark.parametrize("name", CONFIGS)
def test_prefill_flops_are_pinned(name):
    c = CONFIGS[name]
    for count in (work.prefill_flops, DENSE.prefill_flops):
        assert [count(c, n) for n in PREFILL_LENGTHS] \
            == PINNED[name]["prefill"]


@pytest.mark.parametrize("name", CONFIGS)
def test_decode_flops_are_pinned(name):
    c = CONFIGS[name]
    for count in (work.decode_flops, DENSE.decode_flops):
        assert [count(c, n) for n in DECODE_CONTEXTS] \
            == PINNED[name]["decode"]


@pytest.mark.parametrize("name", CONFIGS)
def test_paged_attention_call_is_pinned(name):
    c = CONFIGS[name]
    flops, nbytes = PINNED[name]["paged_per_layer"]
    layers = c["num_hidden_layers"]
    for count in (work.paged_attention_call, DENSE.paged_attention_call):
        assert count(c, RAGGED) == (layers * flops, layers * nbytes)


@pytest.mark.parametrize("name", CONFIGS)
def test_mlp_gemms_are_pinned(name):
    c = CONFIGS[name]
    assert DENSE.mlp_gemms(c, 1536) == [
        (c["num_hidden_layers"], *g) for g in PINNED[name]["mlp"]]
