"""The work counts of a decode call against hand arithmetic, for both
configurations as they are run and for DeepSeek-V2-Lite's published
shape (experts, a leading dense layer, latent attention)."""
import dataclasses
import json

import pytest

from chipbench import roofline, spec
from chipbench.spec import Bench
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def shape(name):
    return spec.shape(Bench(ROOT).config(name))


def test_starcoder2_weights_are_the_compiled_parameter_bytes():
    s = shape("starcoder2-7b")
    # attention 4608*128*(2*36 + 2*4), ungated FFN 2*4608*18432
    assert s.layer_matmul_params == 47_185_920 + 169_869_312
    # 16 layers with two norms each, the final norm, the tied table:
    # the bytes of the compiled parameters (compile, described v5e)
    assert s.weight_params * 2 == 7_399_056_384
    assert s.kv_bytes_per_token == 16 * 2 * 4 * 128 * 2 == 32_768


def test_phi3_weights_are_the_compiled_parameter_bytes():
    s = shape("phi3-medium-14b")
    # attention 5120*128*(2*40 + 2*10), SwiGLU 3*5120*17920
    assert s.layer_matmul_params == 65_536_000 + 275_251_200
    assert s.weight_params * 2 == 7_472_629_760
    assert s.kv_bytes_per_token == 10 * 2 * 10 * 128 * 2 == 51_200


def test_starcoder2_decode_call_by_hand():
    s = shape("starcoder2-7b")
    flops, nbytes = roofline.decode_work(s, [100, 200])
    per_token = 2 * (16 * 217_055_232 + 4608 * 49152)
    attn = 4 * 16 * 36 * 128 * (300 + 2)
    assert flops == 2 * per_token + attn
    # every weight once (the tied table is the head), 300 cached tokens
    # read, one slot per sequence written
    assert nbytes == 7_399_056_384 + 302 * 32_768


def test_phi3_decode_call_by_hand():
    s = shape("phi3-medium-14b")
    flops, nbytes = roofline.decode_work(s, [8000] * 8)
    per_token = 2 * (10 * 340_787_200 + 5120 * 32064)
    assert flops == 8 * per_token + 4 * 10 * 40 * 128 * (64_000 + 8)
    # untied: the head is read whole, the embedding only in its 8 rows
    weights = 7_472_629_760 - 32064 * 5120 * 2
    assert nbytes == weights + 8 * 5120 * 2 + 64_008 * 51_200


def test_least_time_takes_the_larger_bound():
    s = shape("phi3-medium-14b")
    flops, nbytes = roofline.decode_work(s, [8000] * 8)
    assert roofline.least_time(flops, nbytes, "TPU v5 lite") == \
        pytest.approx(nbytes / 819e9)
    big = roofline.prefill_flops(s, 4096)
    assert roofline.least_time(big, 1.0, "TPU v5 lite") == \
        pytest.approx(big / 197e12)


def test_prefill_flops_by_hand():
    s = shape("starcoder2-7b")
    per_token = 2 * (16 * 217_055_232 + 4608 * 49152)
    assert roofline.prefill_flops(s, 64) == \
        64 * per_token + 4 * 16 * 36 * 128 * 64 * 65 / 2


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    assert roofline.decode_work(shape("starcoder2-7b"), []) == (0.0, 0.0)


#: the counts of the dense shapes on a fixed list of contexts, recorded
#: from the counts before they took experts and latent attention
CONTEXTS = [[1], [100, 200], [2047] * 32, [8000] * 8,
            list(range(1, 2048, 97)), [5, 8191, 1024]]
RECORDED = {
    "starcoder2-7b": {
        "decode": [(7399342080.0, 7399121920.0),
                   (14886567936.0, 7408952320.0),
                   (256087425024.0, 9546540032.0),
                   (78066745344.0, 9496470528.0),
                   (169393618944.0, 8134730752.0),
                   (24916230144.0, 7701275648.0)],
        "prefill": [7399047168.0, 14798389248.0, 125823909888.0,
                    474133561344.0]},
    "phi3-medium-14b": {
        "decode": [(7144488960.0, 7144407040.0),
                   (14350008320.0, 7159777280.0),
                   (242032312320.0, 10500065280.0),
                   (70261473280.0, 10421585920.0),
                   (161767710720.0, 8294010880.0),
                   (23321108480.0, 7616542720.0)],
        "prefill": [7144284160.0, 14288773120.0, 121480683520.0,
                    457647063040.0]},
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_dense_counts_are_the_recorded_ones(name):
    s = shape(name)
    got = [roofline.decode_work(s, c) for c in CONTEXTS]
    assert got == RECORDED[name]["decode"]
    assert [roofline.prefill_flops(s, t) for t in (1, 2, 17, 64)] == \
        RECORDED[name]["prefill"]


def deepseek_v2_lite(layers=27):
    """DeepSeek-V2-Lite's published shape, with ``layers`` layers."""
    conf = json.loads(
        (ROOT / "chipbench/testdata/deepseek-v2-lite.json").read_text())
    return dataclasses.replace(spec.shape(conf), layers=layers)


#: one layer's attention (no q_lora): wq 2048*16*(128+64), w_dkv
#: 2048*(512+64), w_uk 512*16*128, w_uv 512*16*128, wo 16*128*2048
MLA = 6_291_456 + 1_179_648 + 1_048_576 + 1_048_576 + 4_194_304
#: its norms: two RMSNorms of 2048, the latent's of 512
NORMS = 2 * 2048 + 512
#: one SwiGLU expert of 1408
EXPERT = 3 * 2048 * 1408
#: a MoE layer's router and two shared experts
FIXED = 2048 * 64 + 2 * EXPERT
#: the leading dense layer's SwiGLU FFN of 10944
DENSE_FFN = 3 * 2048 * 10944
#: embedding and untied head
VOCAB = 2 * 102400 * 2048


def test_deepseek_v2_lite_parameters_by_hand():
    s = deepseek_v2_lite()
    assert (s.dense_prefix, s.experts, s.experts_per_token,
            s.shared_experts, s.expert_ff) == (1, 64, 6, 2, 1408)
    assert s.attn_params == MLA
    dense_layer = MLA + NORMS + DENSE_FFN
    moe_layer = MLA + NORMS + FIXED + 64 * EXPERT
    assert dense_layer + 26 * moe_layer + 2048 + VOCAB == 15_706_484_224
    assert s.weight_params == 15_706_484_224
    # the cut: layers 0-8, one dense layer and eight with experts
    assert deepseek_v2_lite(9).weight_params == 5_179_222_528
    # the latent (512) and the shared rotary key (64) in bf16
    assert s.kv_bytes_per_token == 27 * 1_152


def test_deepseek_v2_lite_counts_the_fewest_routed_experts():
    """Six routed experts a layer with experts, whatever the tokens: the
    fewest that any routing reaches, so no dispatch that reads only the
    experts reached reads fewer bytes than are counted."""
    s = deepseek_v2_lite(9)
    least = (9 * (MLA + NORMS) + DENSE_FFN + 8 * (FIXED + 6 * EXPERT)) * 2
    assert s.layer_bytes == least
    every = (9 * (MLA + NORMS) + DENSE_FFN + 8 * (FIXED + 64 * EXPERT)) * 2
    assert 2 * (s.weight_params - 2048 - VOCAB) == every > least
    one = roofline.decode_work(s, [1000])[1]
    many = roofline.decode_work(s, [1000] * 32)[1]
    # 31 more tokens add their embedding rows and cache, and no expert
    assert many - one == 31 * (2048 * 2 + 1001 * 9 * 1_152)


def test_deepseek_v2_lite_decode_call_by_hand():
    s = deepseek_v2_lite(9)
    flops, nbytes = roofline.decode_work(s, [1000] * 32)
    # per token: each layer's attention, the dense FFN in layer 0, the
    # router, two shared and six routed experts in the others, the head
    per_token = 2 * (9 * MLA + DENSE_FFN + 8 * (FIXED + 6 * EXPERT)
                     + 2048 * 102400)
    # absorbed latent attention: QK over latent and rotary key, PV over
    # the latent, 16 heads, 9 layers, every valid key
    attn = 9 * 2 * 16 * (2 * 512 + 64) * (32_000 + 32)
    assert flops == 32 * per_token + attn
    # weights once, six routed experts a layer, 32 embedding rows
    layers = (9 * (MLA + NORMS) + DENSE_FFN + 8 * (FIXED + 6 * EXPERT)) * 2
    cache = (32_000 + 32) * 9 * 1_152
    assert nbytes == layers + 2048 * 2 + VOCAB + 32 * 2048 * 2 + cache


def test_deepseek_v2_lite_prefill_by_hand():
    s = deepseek_v2_lite(9)
    per_token = 2 * (9 * MLA + DENSE_FFN + 8 * (FIXED + 6 * EXPERT)
                     + 2048 * 102400)
    # per head: QK over nope + rope (192), PV over v (128)
    attn = 9 * 2 * 16 * (128 + 64 + 128) * 64 * 65 / 2
    assert roofline.prefill_flops(s, 64) == 64 * per_token + attn
