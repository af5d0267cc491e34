"""FLOP and byte counts (bench/flops.py) against hand counts for
glm4-9b and the f32 reduction."""

import json
import os

import pytest

from bench import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def dec():
    with open(os.path.join(ROOT, "bench", "configs", "glm4-9b.json")) as f:
        return flops.Decoder(json.load(f))


def test_glm4_parameter_counts(dec):
    assert dec.layer_params() == 203_960_832
    assert dec.embed_params() == 620_756_992
    assert dec.head_params() == 620_756_992
    assert dec.layers == 20
    # 20 layers + embedding + head + final norm, two bytes each.
    assert dec.weight_bytes() == 2 * (20 * 203_960_832
                                      + 2 * 620_756_992 + 4096)
    assert dec.weight_bytes() / 1e9 == pytest.approx(10.64, abs=0.01)


def test_glm4_decode_flops_per_token(dec):
    no_attention = dec.decode_flops(0) - dec.attention_flops([0])
    assert no_attention == pytest.approx(
        2 * (20 * 203.96e6 + 620.76e6), rel=1e-4)
    assert no_attention / 1e9 == pytest.approx(9.40, abs=0.01)
    # Attention grows with the context: 4 * 32 * 128 per key per layer.
    assert dec.decode_flops(1000) - dec.decode_flops(999) == \
        4 * 32 * 128 * 20


def test_glm4_prefill_flops_take_logits_at_the_last_position(dec):
    s = 2048
    body = dec.prefill_flops(s) - dec.attention_flops(range(s)) \
        - 2 * dec.head_params()
    assert body / s / 1e9 == pytest.approx(8.16, abs=0.01)
    # Causal: position p sees p + 1 keys.
    assert dec.attention_flops(range(s)) == \
        4 * 32 * 128 * 20 * s * (s + 1) // 2


def test_reduction_reads_each_element_once():
    assert flops.reduce_bytes(1 << 28) == 1 << 30
    assert flops.reduce_bytes(10, "bfloat16") == 20
    assert flops.reduce_flops(1 << 20) == 1 << 20
