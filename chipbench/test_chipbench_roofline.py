"""The work counts of a decode call against hand arithmetic, for both
configurations as they are run."""
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
